"""Chip-milliseconds of one decode program (a step) spent in the gated short-convolution mixers (`conv.proj`, `conv.mix`, `conv.out`): the traced launches' op events summed by the part of the model their instruction belongs to, which the program's `program.scopes` spans say (benchmark/harness/program_scopes.py); nothing on a program that records no such span."""

from benchmark.harness import program_scopes

program_scopes.arm()


def read(ctx):
    return program_scopes.ms_per(ctx, "decode", "conv.")
