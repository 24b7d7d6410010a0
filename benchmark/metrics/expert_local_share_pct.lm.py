"""Share of the window's routing assignments (prefill and decode, valid tokens) that landed on an expert held on this chip: the engine's counters, reduced on the device.  16 of 128 held: 12.5 expected under even routing."""


def read(ctx):
    return ctx["counters"].get("expert_local_share_pct")
