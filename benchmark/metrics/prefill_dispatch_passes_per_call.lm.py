"""Passes over the sorted buffer that an expert layer of a prefill slice took, on average: the engine's counters stats()["lm"]["dispatch_passes"] over ["dispatch_calls"] (a call = one expert layer of one prefill slice; ops/moe.py's sorted form counts its passes on the device, the launch's sum is fetched with its answers).  1.0 exactly where the buffer holds every assignment that can land here (every expert held) or no call's routing went past the bound; each call past it adds its further passes.  Nothing where the program counts none (a program from before PR 43, a model without experts, a prefill in another form)."""


def read(ctx):
    lm = ctx["counters"].get("lm") or {}
    calls = lm.get("dispatch_calls")
    return lm["dispatch_passes"] / calls if calls else None
