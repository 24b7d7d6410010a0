"""Bytes a launch's cache keeps of one slot WHATEVER its context, all layers together: the engine's stats()["lm"]["cache_bytes"]["state"] (serve/cache.py's kind without a position axis: the state-space mixer's recurrent state and its convolution's tail) over max_batch.  25,350,144 with Falcon-H1's 6 layers of 32 x 128 x 256 float32 and 5,120 x 3 bfloat16; half that would mean the state is kept in bfloat16, which the configuration does not state.  Nothing where the cache has no such kind."""


def read(ctx):
    held = (ctx["counters"].get("lm") or {}).get("cache_bytes") or {}
    if "state" not in held:
        return None
    return held["state"] / int(ctx["cell"].config["max_batch"])
