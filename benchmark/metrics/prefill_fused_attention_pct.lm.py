"""Share of the window's prefills whose causal attention ran as the fused kernel (ops/pallas_attention.py) and not as the scanned prefill_causal: the program's lm.prefill spans (under serve.dispatch of the window's batches) whose `attention` is "fused", over those that carry the attribute.  100 where the kernel's supports() takes the cell's shapes; nothing where no span carries the attribute (a program from before PR 31)."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.serve_window(ctx)
    if found is None:
        return None
    ring, batches = found
    noted = fused = 0
    for b in batches:
        for s in ring.children[ring.phase(b, "serve.dispatch")["span_id"]]:
            if s["name"] == "lm.prefill" and "attention" in s:
                noted += 1
                fused += s["attention"] == "fused"
    return 100.0 * fused / noted if noted else None
