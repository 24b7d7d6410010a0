"""Operations and bytes a GLM-4.7-Flash launch needs, from the
configuration's shapes, by part: latent attention with its cache, the
experts (routed and shared), and the rest (dense layer, router, head).  What
the ALGORITHM needs, as ``flops_lm.py`` counts K-EXAONE's: valid tokens only
(padding and dead slots are the system's waste), the causal half of the
scores, each weight read once per program, the cache at ``kv_lora_rank +
qk_rope_head_dim`` numbers a position a layer up to each sequence's own
context.  bfloat16: 2 bytes a parameter and a cache entry.

Latent attention, per token and layer.  Projections: ``wq_a``, ``wq_b``,
``wkv_a``, ``wo``, and ``wkv_b`` ONCE (prefill: every position's keys and
values rebuilt per head; decode: the query carried through ``W_uk``, the
attended latent through ``W_uv``: together the same 2 x rank x heads x (nope
+ v) operations).  Scores and values, per attended key: prefill, the
expanded form, ``2 heads (qk_nope + qk_rope) + 2 heads v``; decode, the
absorbed form, ``2 heads (rank + qk_rope) + 2 heads rank`` (the price of
reading 576 numbers a position in place of 20 x 448)."""

from __future__ import annotations

BYTES = 2


def dims(cfg: dict) -> dict:
    n, lead = int(cfg["num_hidden_layers"]), int(cfg["first_k_dense_replace"])
    held = int(cfg["n_routed_experts"])
    return {
        "d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
        "rq": int(cfg["q_lora_rank"]), "r": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]), "rope": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]),
        "layers": n, "dense": min(lead, n), "sparse": max(n - lead, 0),
        "f_dense": int(cfg["intermediate_size"]),
        "f": int(cfg["moe_intermediate_size"]),
        "held": held,
        "total": int(cfg.get("published", {}).get("n_routed_experts", held)),
        "k": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["n_shared_experts"]),
        "vocab": int(cfg["vocab_size"]),
    }


def params_by_part(cfg: dict) -> dict:
    """Parameters held on the chip, by part (embedding apart: a decode step
    reads one row a sequence; norms left out)."""
    m = dims(cfg)
    attn = (m["d"] * m["rq"] + m["rq"] * m["h"] * (m["nope"] + m["rope"])
            + m["d"] * (m["r"] + m["rope"])
            + m["r"] * m["h"] * (m["nope"] + m["dv"]) + m["h"] * m["dv"] * m["d"])
    expert = 3 * m["d"] * m["f"]
    return {
        "attention": attn * m["layers"],
        "experts_routed": m["sparse"] * m["held"] * expert,
        "experts_shared": m["sparse"] * m["shared"] * expert,
        "dense_mlp": m["dense"] * 3 * m["d"] * m["f_dense"],
        "router": m["sparse"] * m["d"] * m["total"],
        "head": m["d"] * m["vocab"],
        "embedding": m["d"] * m["vocab"],
    }


def cache_bytes_per_position(cfg: dict) -> int:
    """What every layer together keeps of one position."""
    m = dims(cfg)
    return BYTES * m["layers"] * (m["r"] + m["rope"])


def decode_step(cfg: dict, contexts) -> dict:
    """One decode step of the sequences whose contexts (positions seen, the
    new token's included) are ``contexts``: -> {"ops", "bytes", each by
    part, and their totals}.  Of the held experts' weights the expected
    share that gets a token is read: 1 - (1 - k / total) ** tokens."""
    m, p = dims(cfg), params_by_part(cfg)
    t = len(contexts)
    hit = 1.0 - (1.0 - m["k"] / m["total"]) ** t
    routed_rows = t * m["k"] * m["held"] / m["total"]      # expected
    expert = 3 * m["d"] * m["f"]
    keys = float(sum(contexts)) * m["layers"]
    per_key = 2.0 * m["h"] * (m["r"] + m["rope"]) + 2.0 * m["h"] * m["r"]
    ops = {
        "experts": 2.0 * expert * m["sparse"] * (routed_rows + t * m["shared"]),
        "attention": 2.0 * p["attention"] * t + per_key * keys,
        "rest": 2.0 * t * (p["dense_mlp"] + p["router"] + p["head"]),
    }
    by = {
        "experts": BYTES * (p["experts_routed"] * hit + p["experts_shared"]),
        "attention": BYTES * (p["attention"] + (m["r"] + m["rope"]) * keys),
        "rest": BYTES * (p["dense_mlp"] + p["router"] + p["head"] + t * m["d"]),
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
    expert = 3 * m["d"] * m["f"]
    keys = float(sum(n * (n + 1) // 2 for n in lengths)) * m["layers"]
    per_key = 2.0 * m["h"] * (m["nope"] + m["rope"]) + 2.0 * m["h"] * m["dv"]
    ops = {
        "experts": 2.0 * expert * (held_assignments
                                   + tokens * m["sparse"] * m["shared"]),
        "attention": 2.0 * p["attention"] * tokens + per_key * keys,
        "rest": 2.0 * (tokens * (p["dense_mlp"] + p["router"])
                       + len(lengths) * p["head"]),
    }
    weights = sum(v for k, v in p.items() if k != "embedding")
    return {"ops": ops, "ops_total": sum(ops.values()),
            "bytes_total": BYTES * (weights + 2.0 * tokens * m["d"])
                           + tokens * cache_bytes_per_position(cfg)}


def least_seconds(work: dict, peaks) -> float:
    """The roofline's floor of one program: the larger of operations over
    peak and bytes over bandwidth."""
    return max(work["ops_total"] / peaks.flops,
               work["bytes_total"] / peaks.hbm_bytes_s)
