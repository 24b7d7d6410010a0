"""The retention layers' bytes of one decode step (the configuration's work module, `retention_bytes_per_step`: `S` and `z` of every slot and layer read once and written once over the DISTINCT monomials in float32, and `wq`, `wk`, `wv`, `wg`, `wo` of every layer read once; counted from shapes, so the same work whatever implements it) over peak bandwidth, over the device time a step spends in ALL the `ret.` parts (`decode_ret_ms_per_step.lm`: a fusion carries its root's part, so time may move between the four but not out of them).  Memory-bound.  Nothing where the program records no `program.scopes` span or the configuration's work module counts no such bytes."""

import importlib

from benchmark.harness import device, program_scopes

program_scopes.arm()


def read(ctx):
    ms = program_scopes.ms_per(ctx, "decode", "ret.")
    cfg = ctx["cell"].config
    work = importlib.import_module(cfg["work"]) if "work" in cfg else None
    if not ms or not hasattr(work, "retention_bytes_per_step"):
        return None
    import jax

    peaks = device.peaks_for_kind(jax.devices()[0].device_kind)
    least_ms = 1e3 * work.retention_bytes_per_step(cfg) / peaks.hbm_bytes_s
    return 100.0 * least_ms / ms
