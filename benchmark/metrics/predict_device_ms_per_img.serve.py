"""Chip-milliseconds of the predict programs per image, from the XLA Modules line of the traced launches."""


def read(ctx):
    return ctx["trace"].get("device_ms_per_img")
