"""Share of the prefill programs' device time whose op events belong to no part of the model: instructions whose `op_name` names none and that no op with a part uses or feeds, plus events whose instruction the program's `program.scopes` span does not know (near 100: the map is of another compile, a stale compile cache); benchmark/harness/program_scopes.py.  Nothing on a program that records no such span."""

from benchmark.harness import program_scopes

program_scopes.arm()


def read(ctx):
    return program_scopes.unscoped_pct(ctx, "prefill")
