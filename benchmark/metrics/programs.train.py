"""Distinct (bucket shape, batch size) programs in the epoch's plan: ShardedBatcher.program_count (exact, host)."""


def read(ctx):
    return ctx["counters"].get("programs")
