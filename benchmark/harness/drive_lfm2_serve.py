"""Driver of the LFM2-MoE serving cell: ``drive_glm_serve.run`` as it stands
(the closed loop, the probe, the segments, the model's reference, weights and
work functions found BY NAME from the configuration file, ``correct`` from
what the timed path produced), and then the ONE counter that driver leaves
out: the share of the window's routing assignments that landed on an expert
held on this chip (``expert_local_share_pct.lm``; ``drive_lm_serve`` reports
it for K-EXAONE's rank, ``drive_glm_serve`` was written for a chip that
holds every expert and could not be edited by this PR).  It is read from the
engine's own counters over the window (``stats()["lm"]``, which
``drive_glm_serve`` hands on whole): ``assignments_held`` of
``assignments_all``.

The next ``benchmark`` issue's fold of the language-model drivers (PERF.md
section 7) takes this one with them."""

from __future__ import annotations

from benchmark.harness import drive_glm_serve


def run(cell, seed, seconds, trace, env):
    res = drive_glm_serve.run(cell, seed, seconds, trace, env)
    lm = res.counters.get("lm") or {}
    if lm.get("assignments_all"):
        res.counters["expert_local_share_pct"] = (
            100.0 * lm["assignments_held"] / lm["assignments_all"])
    return res
