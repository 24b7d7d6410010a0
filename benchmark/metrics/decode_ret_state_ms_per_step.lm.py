"""Chip-milliseconds of one decode program (a step) spent in `ret.state` alone (the symmetric second power of the step's keys and queries, and every layer's matrix state read, decayed, updated, queried and written: over half of a step's bytes): the traced launches' op events summed by the part of the model their instruction belongs to, which the program's `program.scopes` spans say (benchmark/harness/program_scopes.py).  A fusion carries its root's part, so time may move between this and the other `ret.` parts: `decode_ret_ms_per_step.lm` is the family.  Nothing on a program that records no such span."""

from benchmark.harness import program_scopes

program_scopes.arm()


def read(ctx):
    return program_scopes.ms_per(ctx, "decode", "ret.state")
