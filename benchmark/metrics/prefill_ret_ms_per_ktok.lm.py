"""Chip-milliseconds of the prefill programs per 1,000 valid prompt tokens spent in the power-retention layers (`ret.proj`, `ret.core`, `ret.state`, `ret.out`: the projections with the gate, a prompt's weights inside a chunk, the state built and carried, the division and `wo`): the traced launches' op events summed by the part of the model their instruction belongs to, which the program's `program.scopes` spans say (benchmark/harness/program_scopes.py); nothing on a program that records no such span."""

from benchmark.harness import program_scopes

program_scopes.arm()


def read(ctx):
    return program_scopes.ms_per(ctx, "prefill", "ret.")
