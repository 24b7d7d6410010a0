"""The comparison that decides ``correct`` in a language-model cell, from
what the timed path produced: for each compared request the reference
(``benchmark/reference/exaone_moe_ref.py``) runs the prompt and the
program's own generated tokens in one full forward, and its logits at the
probe positions (the last prompt position: prefill; decode steps 1, the
middle one and the last: through the ring and the full cache) are held
against the program's.  Logits and not sampled tokens: with random weights
the largest logit changes on rounding.

* ``logit_gap_ratio``: the L2 gap between the program's logits and the
  reference's, judged PER PROBE KIND (prefill; each probed decode step) and
  the worst kind reported, in units of the median gap that the reference
  computed in bfloat16 makes over every position of the same sequences (as
  ``density_gap_ratio``: about 1 for sound bfloat16 arithmetic, whatever
  the seed's conditioning).  Per kind, so that a prefill that is wrong in
  every compared request, or a last step that is, cannot hide behind the
  other kinds' probes.  A kind's reading is the lower median of its probes
  (of 4 the second smallest), because the gap is heavy-tailed: where
  rounding flips a routing choice onto or off a held expert the logits
  move ten to thirty times as far as elsewhere, in the program and in the
  yardstick alike, at about one sound probe in twenty-five (my chip runs,
  PR 26: 12 of 304); the plain median of 4 reads two flips in one kind as a
  fault, about one sound run in forty.  A broken piece of the mathematics moves
  every probe of its kind; a flip moves one.
* ``token_miss_share``: the share of generated tokens that are neither the
  reference's argmax at their position nor within ``margin`` of it in the
  reference's logits; ``margin`` is ``token_margin_rms`` (the configuration
  states it) times the root mean square of bfloat16's own logit gap.
* ``routing_diff_share``: the share of the program's routing choices at the
  probes that the reference did not make.
"""

from __future__ import annotations

import numpy as np


def probe_positions(prompt_len: int, probes) -> dict:
    """Probe name -> row of the reference's logits: ``prefill`` is the last
    prompt position, ``step<s>`` the position of the s-th generated token's
    input (the reference's row ``prompt_len - 1 + s``)."""
    out = {}
    for name in probes:
        out[name] = prompt_len - 1 + (0 if name == "prefill" else int(name[4:]))
    return out


def lm_numbers(compared, ref, yard, *, token_margin_rms: float) -> dict:
    """``compared``: per request {"prompt", "tokens"} and, for those that
    asked for their logits (they come first), "logits": {probe: (V,)} and
    "routing": {probe: (layers, k)}, from the program (or a control in its
    place); ``ref``: per request {"logits" (L, V), "chosen": [(L, k) per
    expert layer]} of the float32 reference over prompt + tokens; ``yard``:
    the same of the reference in bfloat16, for the requests with logits."""
    got, want, kinds = [], [], []
    miss = total = differ = choices = 0
    gaps = []
    for c, r, y in zip(compared, ref, yard):
        if "logits" not in c:
            raise ValueError("the requests with logits come first, one "
                             "bfloat16 reference each")
        n = len(c["prompt"])
        rl, yl = np.asarray(r["logits"], np.float64), np.asarray(y["logits"], np.float64)
        for name, row in probe_positions(n, c["logits"]).items():
            got.append(np.asarray(c["logits"][name], np.float64))
            want.append(rl[row])
            kinds.append(name)
            for layer, chosen in enumerate(r["chosen"]):
                mine = set(np.asarray(c["routing"][name][layer]).tolist())
                differ += len(mine - set(np.asarray(chosen[row]).tolist()))
                choices += len(mine)
        gaps.append(yl - rl)
    rms = float(np.sqrt(np.mean(np.square(np.concatenate(gaps)))))
    margin = token_margin_rms * rms
    for c, r in zip(compared, ref):
        n, toks = len(c["prompt"]), np.asarray(c["tokens"])
        rows = np.asarray(r["logits"], np.float64)[n - 1:n - 1 + len(toks)]
        short = rows.max(axis=-1) - rows[np.arange(len(toks)), toks]
        miss += int(np.sum(short > margin))
        total += len(toks)
    got, want = np.stack(got), np.stack(want)
    mine = np.linalg.norm(got - want, axis=-1)               # per probe
    theirs = np.linalg.norm(np.concatenate(gaps), axis=-1)   # per position
    by_kind = {k: np.sort(mine[[j for j, n in enumerate(kinds) if n == k]])
               for k in dict.fromkeys(kinds)}
    # the lower median: of 4 probes the second smallest
    reading = {k: float(g[(len(g) - 1) // 2]) for k, g in by_kind.items()}
    print(f"[logits] probes {len(got)}: |reference| rms {np.sqrt(np.mean(want ** 2)):.4g}; "
          f"L2 gap to the reference by kind "
          + "; ".join(f"{k} " + " ".join(f"{g:.3g}" for g in by_kind[k])
                      + f" (reads {reading[k]:.4g})" for k in by_kind)
          + f"; bfloat16's own over {len(theirs)} "
          f"positions: median {np.median(theirs):.4g}, 90% {np.quantile(theirs, 0.9):.4g}, "
          f"99% {np.quantile(theirs, 0.99):.4g}, rms per logit {rms:.4g}; token margin "
          f"{margin:.4g}; tokens missed {miss} of {total}; routing choices that "
          f"differ {differ} of {choices}", flush=True)
    return {
        "logit_gap_ratio": max(reading.values()) / max(float(np.median(theirs)), 1e-300),
        "token_miss_share": miss / max(total, 1),
        "routing_diff_share": differ / max(choices, 1),
    }
