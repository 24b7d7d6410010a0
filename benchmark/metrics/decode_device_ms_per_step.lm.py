"""Chip-milliseconds of one decode program (one token for every slot), from the XLA Modules line of the traced launches."""


def read(ctx):
    return ctx["trace"].get("decode_device_ms_per_step")
