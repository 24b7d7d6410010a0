"""Chip-milliseconds of the prefill programs per 1,000 valid prompt tokens, from the XLA Modules line of the traced launches."""


def read(ctx):
    return ctx["trace"].get("prefill_device_ms_per_ktok")
