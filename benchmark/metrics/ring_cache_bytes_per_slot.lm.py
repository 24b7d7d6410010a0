"""Bytes a launch's cache keeps of one slot in its WINDOW layers' rings, all of them together, whatever the context: the engine's stats()["lm"]["cache_bytes"]["ring"] over max_batch.  3,276,800 with MiMo-V2-Flash's 5 window layers of 8 key/value heads over 128 positions (5 x 8 x 320 x 2 B x 128).  Nothing where the cache has no such kind."""


def read(ctx):
    held = (ctx["counters"].get("lm") or {}).get("cache_bytes") or {}
    if "ring" not in held:
        return None
    return held["ring"] / int(ctx["cell"].config["max_batch"])
