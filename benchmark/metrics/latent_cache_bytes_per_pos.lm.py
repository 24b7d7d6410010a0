"""Bytes a launch's cache keeps of one position of one slot, all layers together: the engine's stats()["lm"]["cache_bytes"] (every kind it reports) over max_batch x (largest bucket + max_new_tokens).  6,912 with the latent cache (6 layers x 576 numbers x 2 B); per-head keys and values would read 122,880."""


def read(ctx):
    held = (ctx["counters"].get("lm") or {}).get("cache_bytes")
    if not held:
        return None
    c = ctx["cell"].config
    positions = int(c["length_ladder"][-1]) + int(c["max_new_tokens"])
    return sum(held.values()) / (int(c["max_batch"]) * positions)
