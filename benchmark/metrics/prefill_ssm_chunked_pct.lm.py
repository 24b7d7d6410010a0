"""Share of the window's prefills whose state-space recurrence ran in the chunked form (ops/ssm.py::ssd_chunked: matrix products inside a chunk, a scan over the carried states between chunks) and not token by token or in some other form: the program's lm.prefill spans (under serve.dispatch of the window's batches) whose `ssm` is "chunked", over those that carry the attribute.  100; nothing where no span carries the attribute (a model without a recurrence, or a program from before PR 32)."""

from benchmark.harness import program_spans

program_spans.arm()


def read(ctx):
    found = program_spans.serve_window(ctx)
    if found is None:
        return None
    ring, batches = found
    noted = chunked = 0
    for b in batches:
        for s in ring.children[ring.phase(b, "serve.dispatch")["span_id"]]:
            if s["name"] == "lm.prefill" and "ssm" in s:
                noted += 1
                chunked += s["ssm"] == "chunked"
    return 100.0 * chunked / noted if noted else None
