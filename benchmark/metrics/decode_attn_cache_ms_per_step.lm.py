"""Chip-milliseconds of one decode program (a step) spent in `attn.cache` alone (the step's one row written into every layer's cache, and whatever copies of a whole cache array the compiler puts around that write: microseconds where the write is in place): the traced launches' op events summed by the part of the model their instruction belongs to, which the program's `program.scopes` spans say (benchmark/harness/program_scopes.py); nothing on a program that records no such span."""

from benchmark.harness import program_scopes

program_scopes.arm()


def read(ctx):
    return program_scopes.ms_per(ctx, "decode", "attn.cache")
