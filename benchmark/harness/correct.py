"""The comparison that decides ``correct``.  Every number compared is printed
beside its limit in every run; a run is correct when none passes its limit.
The limits themselves live in the configuration files (key ``limits``), with
the readings they were set from in PERF.md section 2."""

from __future__ import annotations

import statistics

import numpy as np


def _leaves(tree, prefix=""):
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _leaves(tree[k], f"{prefix}/{k}")
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _leaves(v, f"{prefix}/{i}")
    else:
        yield prefix, np.asarray(tree, np.float64)


def worst_leaf_norm_gap(got, ref) -> float:
    """max over leaves of | ||got|| - ||ref|| | / max(||ref|| of that leaf,
    ||ref|| of the median leaf): the gap between the norms, not the norm of
    the difference, held against the larger of the leaf's own and the median
    leaf's norm because some gradients are all but zero."""
    g = dict(_leaves(got))
    r = dict(_leaves(ref))
    if g.keys() != r.keys():
        raise ValueError("trees differ in structure")
    ref_norms = {k: float(np.linalg.norm(v)) for k, v in r.items()}
    median = statistics.median(ref_norms.values())
    worst = 0.0
    for k, v in g.items():
        gap = abs(float(np.linalg.norm(v)) - ref_norms[k])
        worst = max(worst, gap / max(ref_norms[k], median, 1e-30))
    return worst


def leaf_gaps(got, ref) -> dict:
    """Per leaf: ||got - ref|| / max(||ref|| of that leaf, ||ref|| of the
    median leaf).  The norm of the DIFFERENCE, leaf by leaf: it sees rounding
    noise, which the gap between two norms (first-order blind to zero-mean
    noise) does not, and no large leaf can hide a small one.  (All leaves as
    one vector are the output bias and the last layers: their gradient is
    sum(prediction - truth) and activations x that, which the arithmetic of
    the layers before hardly moves while predictions start near zero; float8
    in the reference's place read 2.9e-7 there beside a sound 0.7e-7 to 7e-7,
    my chip runs, PR 23.)"""
    g = dict(_leaves(got))
    r = dict(_leaves(ref))
    if g.keys() != r.keys():
        raise ValueError("trees differ in structure")
    ref_norms = {k: float(np.linalg.norm(v)) for k, v in r.items()}
    median = statistics.median(ref_norms.values())
    return {k: float(np.linalg.norm(g[k] - r[k])) / max(ref_norms[k], median, 1e-30)
            for k in g}


def leaf_gap_ratio(got, ref, yard, label="") -> float:
    """The root mean square over the leaves of ``leaf_gaps(got, ref)``, in
    units of the same number for ``yard`` (the reference computed in
    bfloat16 on the same weights and batch).  A ratio because a random
    network's conditioning swings from seed to seed and the plain gap with
    it; measured against bfloat16's own gap, sound bfloat16 arithmetic reads
    about 1 whatever the seed, and 8-bit arithmetic several times that."""
    a, b = leaf_gaps(got, ref), leaf_gaps(yard, ref)
    rms = lambda d: float(np.sqrt(np.mean(np.square(list(d.values())))))
    worst = max(a, key=a.get)
    print(f"[leaf{label}] gap to float32 by leaf: rms {rms(a):.6g} (bfloat16 itself "
          f"{rms(b):.6g}), median {statistics.median(a.values()):.6g} "
          f"({statistics.median(b.values()):.6g}), worst {a[worst]:.6g} at {worst} "
          f"({max(b.values()):.6g})", flush=True)
    return rms(a) / max(rms(b), 1e-300)


def tree_sub(a, b):
    if isinstance(a, dict):
        return {k: tree_sub(a[k], b[k]) for k in a}
    if isinstance(a, (list, tuple)):
        return [tree_sub(x, y) for x, y in zip(a, b)]
    return np.asarray(a, np.float64) - np.asarray(b, np.float64)


def train_numbers(got: dict, ref: dict, yard_grad1, label="") -> dict:
    """``got`` / ``ref``: {"losses", "grad1", "params0", "params_end"};
    ``yard_grad1``: the first gradient of the reference in bfloat16."""
    out = {}
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"])):
        out[f"loss_rel_step{i + 1}"] = abs(a - b) / max(abs(b), 1e-30)
    out["grad1_norm_gap"] = worst_leaf_norm_gap(got["grad1"], ref["grad1"])
    out["grad1_leaf_gap_ratio"] = leaf_gap_ratio(got["grad1"], ref["grad1"], yard_grad1,
                                                 label)
    out["dparam_norm_gap"] = worst_leaf_norm_gap(
        tree_sub(got["params_end"], got["params0"]),
        tree_sub(ref["params_end"], ref["params0"]))
    return out


def serve_numbers(counts, densities, ref, yard) -> dict:
    """Every answer of the window against the float32 reference's answer for
    the same image.  ``ref`` / ``yard`` (the reference computed in bfloat16):
    {"counts", "mass": per answer, "densities": per sampled answer}.

    ``density_gap_ratio``: the sampled density maps, taken together, by L2,
    in units of the gap that bfloat16 arithmetic itself makes on the same
    weights and images.  A ratio because with random weights the network's
    conditioning swings from seed to seed, and the plain relative error of a
    sound bfloat16 run with it (0.4% to 3.5% over 24 seeds, my chip runs,
    PR 23) - wider than the step to 8 bits.  Against bfloat16's own gap a
    sound run reads about 1 and an 8-bit one several times that.

    ``count_gap_mass``: the worst answer's count error as a share of the
    mass (sum of absolute values) of the reference's density map.  A count is
    the sum of its map's errors, which is their MEAN: a hundredth of their
    root mean square, of either sign, so that its ratio to bfloat16's own
    count error is a ratio of two small leftovers (0.56 to 6.5 over 15 sound
    seeds, my chip runs, PR 23) and separates nothing.  Held against the
    mass, a sound count stays under a tenth, and an answer that is altered,
    zero or not a number stands out, one among all."""
    c, r = np.asarray(counts, np.float64), np.asarray(ref["counts"], np.float64)
    out = {"count_gap_mass": float(np.max(
        np.nan_to_num(np.abs(c - r), nan=np.inf) / np.asarray(ref["mass"], np.float64)))}
    if len(densities):
        stack = lambda xs: np.stack([np.asarray(x, np.float64) for x in xs])
        rd = stack(ref["densities"])
        out["density_gap_ratio"] = float(
            np.linalg.norm(stack(densities) - rd)
            / max(np.linalg.norm(stack(yard["densities"]) - rd), 1e-300))
    return out


def judge(numbers: dict, limits: dict):
    """Print each number beside its limit; True when every one holds.  A
    number without a limit is a fault of the configuration, not a pass."""
    ok = True
    for name, value in numbers.items():
        # loss_rel_step1..3 share the limit "loss_rel"
        key = name if name in limits else name.rsplit("_step", 1)[0]
        if key not in limits:
            raise KeyError(f"configuration gives no limit for {name!r}")
        limit = float(limits[key])
        good = bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        print(f"[correct] {name} = {value:.6g}  limit {limit:.6g}  "
              f"{'ok' if good else 'FAIL'}", flush=True)
    return ok
