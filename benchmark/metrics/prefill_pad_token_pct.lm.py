"""Share of the tokens the window's prefills launched that were padding or dead slots: 1 - valid_tokens / tokens over the program's lm.prefill spans (under serve.dispatch of the window's batches).  About 25 with prompts uniform in 8k-16k and one rung of 16,384."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.serve_window(ctx)
    if found is None:
        return None
    ring, batches = found
    launched = valid = 0
    for b in batches:
        for s in ring.children[ring.phase(b, "serve.dispatch")["span_id"]]:
            if s["name"] == "lm.prefill" and "valid_tokens" in s:
                launched += s["tokens"]
                valid += s["valid_tokens"]
    return 100.0 * (1.0 - valid / launched) if launched else None
