"""Operations and bytes an LFM2-MoE launch needs, from the configuration's
shapes, by part: the gated short-convolution mixers with their tails,
attention with its keys and values, the held experts with their routers,
the leading dense layers, and the tied head.  What the ALGORITHM needs, as
``flops_lm.py`` counts K-EXAONE's: valid tokens only (padding and dead slots
are the system's waste), the causal half of the scores, each weight read
once per program, keys and values up to each sequence's own context, the
convolution's tail read once and written once a decode step.  bfloat16: 2
bytes a parameter, a key or value and a number of the tail.

The experts, by what the router sent here and not a uniform guess: a
prefill's operations count ``held_assignments``, the program's own counter
of the routing choices that landed on a held expert; a decode step reads the
expected share of the held experts' weights that gets a token, ``1 - (1 - k
/ total) ** tokens`` (0.984 at 64 tokens' top-4 of 64), and computes the
expected ``tokens x k x held / total`` rows.

``layer_types`` and ``num_dense_layers`` are read layer by layer: any
pattern, not the published one alone."""

from __future__ import annotations

BYTES = 2
CONV = "conv"


def dims(cfg: dict) -> dict:
    kinds = list(cfg["layer_types"])
    d, h = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    held = int(cfg["num_experts"])
    dense = min(int(cfg["num_dense_layers"]), len(kinds))
    return {
        "d": d, "h": h, "kv": int(cfg["num_key_value_heads"]),
        "hd": int(cfg.get("assumed", {}).get("head_dim", d // h)),
        "conv": kinds.count(CONV), "attn": len(kinds) - kinds.count(CONV),
        "taps": int(cfg["conv_L_cache"]),
        "dense": dense, "sparse": len(kinds) - dense,
        "f_dense": int(cfg["intermediate_size"]),
        "f": int(cfg["moe_intermediate_size"]),
        "held": held,
        "total": int(cfg.get("published", {}).get("num_experts", held)),
        "k": int(cfg["num_experts_per_tok"]),
        "vocab": int(cfg["vocab_size"]),
    }


def params_by_part(cfg: dict) -> dict:
    """Parameters held on the chip, by part (norms left out; ``embedding`` is
    the head too: a decode step reads it whole once, as the head)."""
    m = dims(cfg)
    d = m["d"]
    return {
        "conv": m["conv"] * (3 * d * d + d * m["taps"] + d * d),
        "attention": m["attn"] * (2 * d * m["h"] * m["hd"]
                                  + 2 * d * m["kv"] * m["hd"]),
        "experts": m["sparse"] * m["held"] * 3 * d * m["f"],
        "router": m["sparse"] * d * m["total"],
        "dense_mlp": m["dense"] * 3 * d * m["f_dense"],
        "embedding": d * m["vocab"],
    }


def state_bytes_per_slot(cfg: dict) -> int:
    """What the convolution layers together keep of one sequence whatever its
    length: ``conv_L_cache - 1`` inputs of ``hidden_size`` channels each."""
    m = dims(cfg)
    return BYTES * m["conv"] * m["d"] * (m["taps"] - 1)


def kv_bytes_per_position(cfg: dict) -> int:
    """What the attention layers together keep of one position."""
    m = dims(cfg)
    return BYTES * m["attn"] * 2 * m["kv"] * m["hd"]


def decode_step(cfg: dict, contexts) -> dict:
    """One decode step of the sequences whose contexts (positions seen, the
    new token's included) are ``contexts``: -> {"ops", "bytes", each by
    part, and their totals}."""
    m, p = dims(cfg), params_by_part(cfg)
    t = len(contexts)
    hit = 1.0 - (1.0 - m["k"] / m["total"]) ** t
    routed_rows = t * m["k"] * m["held"] / m["total"]       # expected
    keys = float(sum(contexts)) * m["attn"]
    ops = {
        "conv": 2.0 * p["conv"] * t,
        "attention": 2.0 * p["attention"] * t + 4.0 * m["h"] * m["hd"] * keys,
        "experts": (2.0 * 3 * m["d"] * m["f"] * m["sparse"] * routed_rows
                    + 2.0 * p["router"] * t),
        "dense_mlp": 2.0 * p["dense_mlp"] * t,
        "head": 2.0 * p["embedding"] * t,
    }
    by = {
        # the tail read once and written once, each slot's own
        "conv": BYTES * p["conv"] + 2.0 * t * state_bytes_per_slot(cfg),
        "attention": BYTES * (p["attention"] + 2 * m["kv"] * m["hd"] * keys),
        "experts": BYTES * (p["experts"] * hit + p["router"]),
        "dense_mlp": BYTES * p["dense_mlp"],
        "head": BYTES * (p["embedding"] + t * m["d"]),
    }
    return {"ops": ops, "bytes": by, "ops_total": sum(ops.values()),
            "bytes_total": sum(by.values())}


def prefill(cfg: dict, lengths, held_assignments: float) -> dict:
    """The prefill of prompts of ``lengths`` tokens, ``held_assignments`` of
    whose routing choices landed on held experts (the program's counter):
    -> {"ops" by part, "ops_total", "bytes_total"}.  Logits at the last
    position only; the causal half of the scores (position i sees i + 1
    keys)."""
    m, p = dims(cfg), params_by_part(cfg)
    tokens = float(sum(lengths))
    keys = float(sum(n * (n + 1) // 2 for n in lengths)) * m["attn"]
    ops = {
        "conv": 2.0 * p["conv"] * tokens,
        "attention": 2.0 * p["attention"] * tokens + 4.0 * m["h"] * m["hd"] * keys,
        "experts": (2.0 * 3 * m["d"] * m["f"] * held_assignments
                    + 2.0 * p["router"] * tokens),
        "dense_mlp": 2.0 * p["dense_mlp"] * tokens,
        "head": 2.0 * p["embedding"] * len(lengths),
    }
    return {"ops": ops, "ops_total": sum(ops.values()),
            "bytes_total": BYTES * (sum(p.values()) + 2.0 * tokens * m["d"])
                           + tokens * kv_bytes_per_position(cfg)
                           + len(lengths) * state_bytes_per_slot(cfg)}


def least_seconds(work: dict, peaks) -> float:
    """The roofline's floor of one program: the larger of operations over
    peak and bytes over bandwidth."""
    return max(work["ops_total"] / peaks.flops,
               work["bytes_total"] / peaks.hbm_bytes_s)
