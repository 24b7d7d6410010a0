"""Operations and bytes a K-EXAONE launch needs, from the configuration's
shapes, by part: the experts (routed, held here, and shared), attention with
its cache, and the rest (dense layer, router, head).  What the ALGORITHM
needs: valid tokens only (padding and dead slots are the system's waste),
each weight read once per program, the cache read up to each sequence's own
context.  bfloat16: 2 bytes a parameter and a cache entry."""

from __future__ import annotations

BYTES = 2


def dims(cfg: dict) -> dict:
    n = int(cfg["num_hidden_layers"])
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    h, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    return {
        "d": d, "hd": hd, "h": h, "kv": kv,
        "window": int(cfg["sliding_window"]),
        "layer_types": list(cfg["layer_types"][:n]),
        "mlp_types": list(cfg["mlp_layer_types"][:n]),
        "f_dense": int(cfg["intermediate_size"]),
        "f": int(cfg["moe_intermediate_size"]),
        "held": int(cfg["num_experts"]),
        "total": int(cfg.get("published", {}).get("num_experts", cfg["num_experts"])),
        "k": int(cfg["num_experts_per_tok"]),
        "shared": int(cfg["num_shared_experts"]),
        "vocab": int(cfg["vocab_size"]),
    }


def params_by_part(cfg: dict) -> dict:
    """Parameters held on the chip, by part (embedding apart: a decode step
    reads one row a sequence)."""
    m = dims(cfg)
    attn = m["d"] * (2 * m["h"] * m["hd"] + 2 * m["kv"] * m["hd"])
    expert = 3 * m["d"] * m["f"]
    sparse = sum(t != "dense" for t in m["mlp_types"])
    dense = len(m["mlp_types"]) - sparse
    return {
        "attention": attn * len(m["layer_types"]),
        "experts_routed": sparse * m["held"] * expert,
        "experts_shared": sparse * m["shared"] * expert,
        "dense_mlp": dense * 3 * m["d"] * m["f_dense"],
        "router": sparse * m["d"] * m["total"],
        "head": m["d"] * m["vocab"],
        "embedding": m["d"] * m["vocab"],
    }


def _context_keys(m: dict, contexts) -> float:
    """Keys attended, summed over layers, by queries that each see
    ``contexts[i]`` positions (their own included)."""
    full = sum(t != "sliding_attention" for t in m["layer_types"])
    ring = len(m["layer_types"]) - full
    return float(sum(full * c + ring * min(c, m["window"]) for c in contexts))


def decode_step(cfg: dict, contexts) -> dict:
    """One decode step of the sequences whose contexts (positions seen, the
    new token's included) are ``contexts``: -> {"ops", "bytes", each by
    part, and their totals}.  Every held expert's weights count as read
    when the step has more tokens than experts would leave idle: the
    expected share that gets a token, 1 - (1 - k / total) ** tokens."""
    m, p = dims(cfg), params_by_part(cfg)
    t = len(contexts)
    sparse = sum(x != "dense" for x in m["mlp_types"])
    hit = 1.0 - (1.0 - m["k"] / m["total"]) ** t
    routed_rows = t * m["k"] * m["held"] / m["total"]      # expected
    expert = 3 * m["d"] * m["f"]
    keys = _context_keys(m, contexts)
    ops = {
        "experts": 2.0 * expert * sparse * (routed_rows + t * m["shared"]),
        "attention": 2.0 * p["attention"] * t + 4.0 * m["h"] * m["hd"] * keys,
        "rest": 2.0 * t * (p["dense_mlp"] + p["router"] + p["head"]),
    }
    by = {
        "experts": BYTES * (p["experts_routed"] * hit + p["experts_shared"]),
        "attention": BYTES * (p["attention"] + 2.0 * m["kv"] * m["hd"] * keys),
        "rest": BYTES * (p["dense_mlp"] + p["router"] + p["head"] + t * m["d"]),
    }
    return {"ops": ops, "bytes": by, "ops_total": sum(ops.values()),
            "bytes_total": sum(by.values())}


def prefill(cfg: dict, lengths, held_assignments: float) -> dict:
    """The prefill of prompts of ``lengths`` tokens, ``held_assignments`` of
    whose routing choices landed on held experts (the program's counter):
    -> {"ops" by part, "ops_total", "bytes_total"}.  Logits at the last
    position only."""
    m, p = dims(cfg), params_by_part(cfg)
    tokens = float(sum(lengths))
    sparse = sum(x != "dense" for x in m["mlp_types"])
    expert = 3 * m["d"] * m["f"]
    keys = sum(_context_keys(m, range(1, n + 1)) for n in lengths)
    ops = {
        "experts": 2.0 * expert * (held_assignments
                                   + tokens * sparse * m["shared"]),
        "attention": 2.0 * p["attention"] * tokens + 4.0 * m["h"] * m["hd"] * keys,
        "rest": 2.0 * (tokens * (p["dense_mlp"] + p["router"])
                       + len(lengths) * p["head"]),
    }
    weights = sum(v for k, v in p.items() if k != "embedding")
    return {"ops": ops, "ops_total": sum(ops.values()),
            "bytes_total": BYTES * (weights + 2.0 * tokens * m["d"])}


def least_seconds(work: dict, peaks) -> float:
    """The roofline's floor of one program: the larger of operations over
    peak and bytes over bandwidth."""
    return max(work["ops_total"] / peaks.flops,
               work["bytes_total"] / peaks.hbm_bytes_s)
