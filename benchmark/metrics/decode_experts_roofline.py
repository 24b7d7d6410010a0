"""The held experts' weight bytes of one decode step (the configuration's work module, `experts_bytes_per_step`: every held expert's three matrices in every layer, times the share of them the window's steps read by the engine's own counters, `decode_experts_read` / `decode_experts_held`: 1 where the form reads them all) over peak bandwidth, over the device time a step spends in the part `moe.experts` (the routed experts' products in whichever form, the identity experts' multiply-add among them).  Memory-bound: 256 tokens give a held expert 4 rows.  Nothing where the program records no `program.scopes` span or the configuration's work module counts no such bytes."""

import importlib

from benchmark.harness import device, program_scopes

program_scopes.arm()


def read(ctx):
    ms = program_scopes.ms_per(ctx, "decode", "moe.experts")
    cfg = ctx["cell"].config
    work = importlib.import_module(cfg["work"]) if "work" in cfg else None
    if not ms or not hasattr(work, "experts_bytes_per_step"):
        return None
    import jax

    lm = ctx["counters"].get("lm") or {}
    held = lm.get("decode_experts_held")
    share = lm["decode_experts_read"] / held if held else 1.0
    peaks = device.peaks_for_kind(jax.devices()[0].device_kind)
    least_ms = 1e3 * work.experts_bytes_per_step(cfg, share) / peaks.hbm_bytes_s
    return 100.0 * least_ms / ms
