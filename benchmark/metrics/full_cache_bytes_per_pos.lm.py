"""Bytes a launch's cache keeps of one position of one slot in its FULL layers, all of them together: the engine's stats()["lm"]["cache_bytes"]["full"] over max_batch x (largest bucket + max_new_tokens).  5,120 with MiMo-V2-Flash's 2 full layers of 4 key/value heads, keys 192 and values 128 wide (2 x 4 x 320 x 2 B); 6,144 would mean the keys are padded to 256 lanes.  Nothing where the cache has no such kind."""


def read(ctx):
    held = (ctx["counters"].get("lm") or {}).get("cache_bytes") or {}
    if "full" not in held:
        return None
    c = ctx["cell"].config
    positions = int(c["length_ladder"][-1]) + int(c["max_new_tokens"])
    return held["full"] / (int(c["max_batch"]) * positions)
