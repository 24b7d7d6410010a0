"""Share of the window's routing choices (prefill and decode, valid tokens, all twelve a token) that were identity experts, which return their input, read no weight and are held on no chip: the engine's counters, reduced on the device, 100 x stats()["lm"]["assignments_zero"] / ["assignments_all"].  A third where the seeded router's 768 outputs are alike (256 of them identity experts); nothing on a program whose engine has no such counter (one from before PR 50) or a model whose router has no identity experts."""


def read(ctx):
    lm = ctx["counters"].get("lm") or {}
    if not lm.get("assignments_zero") or not lm.get("assignments_all"):
        return None
    return 100.0 * lm["assignments_zero"] / lm["assignments_all"]
