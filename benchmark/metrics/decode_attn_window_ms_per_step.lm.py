"""Chip-milliseconds of one decode program (a step) spent in `attn.window` alone (a window layer's scores, softmax with its sink and values against its ring of `sliding_window` slots; `attn.core` is then the full layers'): the traced launches' op events summed by the part of the model their instruction belongs to, which the program's `program.scopes` spans say (benchmark/harness/program_scopes.py); nothing on a program that records no such span or opens no such part."""

from benchmark.harness import program_scopes

program_scopes.arm()


def read(ctx):
    found = program_scopes.read(ctx)
    if found is None or "attn.window" not in found["decode"]["parts"]:
        return None
    return program_scopes.ms_per(ctx, "decode", "attn.window")
