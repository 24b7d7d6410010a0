"""Operations and bytes a MiMo-V2-Flash launch needs, from the configuration's
shapes, by part: attention of its two kinds (full layers over the whole
context, window layers over ``min(context, sliding_window)``; each kind with
its own key/value heads; keys ``head_dim`` and values ``v_head_dim`` wide),
the held experts with their routers, the dense layers, and the head.  What
the ALGORITHM needs, as ``flops_lm.py`` counts K-EXAONE's: valid tokens only
(padding and dead slots are the system's waste), the causal part of the
scores, each weight read once per program, keys and values up to each
sequence's own context.  bfloat16: 2 bytes a parameter, a key or a value.

The experts.  A prefill's operations count ``held_assignments``, the
program's own counter of the routing choices that landed on a held expert:
what the router sent here.  A decode step's bytes take THE UNIFORM
EXPECTATION of the share of the held experts' weights that a step reads, ``1
- (1 - k / total) ** tokens`` (0.398 at 16 tokens' top-8 of 256), NOT the
program's counter: ``trace_lm_any.reduce`` hands a work function the
contexts alone, and the launch's ``experts_read / experts_held`` (its
``serve.fetch`` span) does not reach it.  Seeded routers are skewed: the
skipping kernel read 28.8 to 32.2% of the held experts (my chip runs, PR
40), so ``decode_step_roofline`` reads about a tenth high (64.4-66.1 where
the bytes read give 58-60; PERF.md sections 5 and 7 give both).  The
batched form reads every held expert, which is the system's waste.  The rows
computed are the expected ``tokens x k x held / total``.

``hybrid_layer_pattern`` and ``moe_layer_freq`` are read layer by layer: any
pattern, not the published one alone."""

from __future__ import annotations

BYTES = 2


def dims(cfg: dict) -> dict:
    n = int(cfg["num_hidden_layers"])
    window = [int(x) for x in cfg["hybrid_layer_pattern"][:n]]
    sparse = [int(x) for x in cfg["moe_layer_freq"][:n]]
    held = int(cfg["n_routed_experts"])
    return {
        "d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
        "hd": int(cfg["head_dim"]), "dv": int(cfg["v_head_dim"]),
        "kv_full": int(cfg["num_key_value_heads"]),
        "kv_window": int(cfg["swa_num_key_value_heads"]),
        "window": int(cfg["sliding_window"]),
        "windows": sum(window), "fulls": n - sum(window),
        "sinks": int(bool(cfg["add_swa_attention_sink_bias"])),
        "sparse": sum(sparse), "dense": n - sum(sparse),
        "f_dense": int(cfg["intermediate_size"]),
        "f": int(cfg["moe_intermediate_size"]),
        "held": held,
        "total": int(cfg.get("published", {}).get("n_routed_experts", held)),
        "k": int(cfg["num_experts_per_tok"]),
        "vocab": int(cfg["vocab_size"]),
    }


def _attention_params(m: dict, kv: int) -> int:
    d = m["d"]
    return (d * m["h"] * m["hd"] + d * kv * (m["hd"] + m["dv"])
            + m["h"] * m["dv"] * d)


def params_by_part(cfg: dict) -> dict:
    """Parameters held on the chip, by part (norms and the router's bias left
    out)."""
    m = dims(cfg)
    d = m["d"]
    return {
        "attention_full": m["fulls"] * _attention_params(m, m["kv_full"]),
        "attention_window": m["windows"] * (_attention_params(m, m["kv_window"])
                                            + m["sinks"] * m["h"]),
        "experts": m["sparse"] * m["held"] * 3 * d * m["f"],
        "router": m["sparse"] * d * m["total"],
        "dense_mlp": m["dense"] * 3 * d * m["f_dense"],
        "embedding": d * m["vocab"],
        "head": d * m["vocab"],
    }


def full_bytes_per_position(cfg: dict) -> int:
    """What the full layers together keep of one position."""
    m = dims(cfg)
    return BYTES * m["fulls"] * m["kv_full"] * (m["hd"] + m["dv"])


def ring_bytes_per_slot(cfg: dict) -> int:
    """What the window layers together keep of one sequence, whatever its
    context."""
    m = dims(cfg)
    return (BYTES * m["windows"] * m["kv_window"] * (m["hd"] + m["dv"])
            * m["window"])


def _scores_ops(m: dict, full_keys: float, window_keys: float) -> float:
    """Scores and values: 2 x (head_dim + v_head_dim) a query head and key."""
    return 2.0 * m["h"] * (m["hd"] + m["dv"]) * (
        m["fulls"] * full_keys + m["windows"] * window_keys)


def decode_step(cfg: dict, contexts) -> dict:
    """One decode step of the sequences whose contexts (positions seen, the
    new token's included) are ``contexts``: -> {"ops", "bytes", each by
    part, and their totals}.  The held experts' bytes by the UNIFORM
    expectation (the module's docstring says what that costs)."""
    m, p = dims(cfg), params_by_part(cfg)
    t = len(contexts)
    hit = 1.0 - (1.0 - m["k"] / m["total"]) ** t
    routed_rows = t * m["k"] * m["held"] / m["total"]       # expected
    full_keys = float(sum(contexts))
    window_keys = float(sum(min(c, m["window"]) for c in contexts))
    attention = p["attention_full"] + p["attention_window"]
    ops = {
        "attention": 2.0 * attention * t + _scores_ops(m, full_keys, window_keys),
        "experts": (2.0 * 3 * m["d"] * m["f"] * m["sparse"] * routed_rows
                    + 2.0 * p["router"] * t),
        "dense_mlp": 2.0 * p["dense_mlp"] * t,
        "head": 2.0 * p["head"] * t,
    }
    by = {
        "attention": BYTES * (
            attention
            + m["fulls"] * m["kv_full"] * (m["hd"] + m["dv"]) * full_keys
            + m["windows"] * m["kv_window"] * (m["hd"] + m["dv"]) * window_keys),
        "experts": BYTES * (p["experts"] * hit + p["router"]),
        "dense_mlp": BYTES * p["dense_mlp"],
        "head": BYTES * (p["head"] + t * m["d"]),
    }
    return {"ops": ops, "bytes": by, "ops_total": sum(ops.values()),
            "bytes_total": sum(by.values())}


def prefill(cfg: dict, lengths, held_assignments: float) -> dict:
    """The prefill of prompts of ``lengths`` tokens, ``held_assignments`` of
    whose routing choices landed on held experts (the program's counter):
    -> {"ops" by part, "ops_total", "bytes_total"}.  Logits at the last
    position only; the causal part of the scores (position i sees i + 1 keys
    in a full layer, min(i + 1, window) in a window layer)."""
    m, p = dims(cfg), params_by_part(cfg)
    w = m["window"]
    tokens = float(sum(lengths))
    full_keys = float(sum(n * (n + 1) // 2 for n in lengths))
    window_keys = float(sum(
        min(n, w) * (min(n, w) + 1) // 2 + max(n - w, 0) * w for n in lengths))
    attention = p["attention_full"] + p["attention_window"]
    ops = {
        "attention": 2.0 * attention * tokens
                     + _scores_ops(m, full_keys, window_keys),
        "experts": (2.0 * 3 * m["d"] * m["f"] * held_assignments
                    + 2.0 * p["router"] * tokens),
        "dense_mlp": 2.0 * p["dense_mlp"] * tokens,
        "head": 2.0 * p["head"] * len(lengths),
    }
    return {"ops": ops, "ops_total": sum(ops.values()),
            "bytes_total": BYTES * (sum(p.values()) + 2.0 * tokens * m["d"])
                           + tokens * full_bytes_per_position(cfg)
                           + len(lengths) * ring_bytes_per_slot(cfg)}


def least_seconds(work: dict, peaks) -> float:
    """The roofline's floor of one program: the larger of operations over
    peak and bytes over bandwidth."""
    return max(work["ops_total"] / peaks.flops,
               work["bytes_total"] / peaks.hbm_bytes_s)
