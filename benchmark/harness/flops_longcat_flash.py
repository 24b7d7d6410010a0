"""Operations and bytes a LongCat-Flash launch needs, from the configuration's
shapes, by part: the two latent-attention sublayers a layer with their cache,
the held experts with their router, the two dense SwiGLUs a layer, and the
head.  What the ALGORITHM needs, as ``flops_glm.py`` counts GLM's: valid
tokens only (padding and dead slots are the system's waste), the causal half
of the scores, each weight read once per program.  bfloat16: 2 bytes a
parameter and a cache entry.

The cache.  ``kv_lora_rank + qk_rope_head_dim`` numbers a position a SUBLAYER,
two sublayers a layer.  A decode step reads it in the blocks of
``CACHE_BLOCK`` positions that latent attention's decode kernel reads
(``ops/pallas_latent.py``): every block up to the one that holds a slot's
position, whole, the last block of the allocation as long as it is (1,280 =
1,024 + 256).  A context of 300 positions therefore counts 1,024, which is
what the algorithm's one pass over the cache costs on this device; the
scores' operations count the context alone.

The experts.  A prefill's operations count ``held_assignments``, the
program's own counter of the routing choices that landed on a held expert.
A decode step reads every held expert's weights once (at 256 tokens a step
the chance that a held expert gets none is (1 - 12 / 768) ** 256 = 1.8%) and
computes the expected ``tokens x k x held / outputs`` rows (``outputs``: the
router's 768, the identity experts among them); a chosen identity expert is
one multiply-add a number and no byte, counted under the experts' operations.
The batched form computes ``held x tokens`` rows: the system's waste.

``experts_bytes_per_step`` is ``decode_experts_roofline``'s work: the held
experts' weights alone, all layers, times the share the step read."""

from __future__ import annotations

BYTES = 2
SUBLAYERS = 2
CACHE_BLOCK = 1024     # ops/pallas_latent.py::BLOCK, as the kernel reads


def dims(cfg: dict) -> dict:
    held = int(cfg["n_routed_experts"])
    routed = int(cfg.get("published", {}).get("n_routed_experts", held))
    return {
        "d": int(cfg["hidden_size"]), "h": int(cfg["num_attention_heads"]),
        "rq": int(cfg["q_lora_rank"]), "r": int(cfg["kv_lora_rank"]),
        "nope": int(cfg["qk_nope_head_dim"]), "rope": int(cfg["qk_rope_head_dim"]),
        "dv": int(cfg["v_head_dim"]),
        "layers": int(cfg["num_layers"]),
        "f_dense": int(cfg["ffn_hidden_size"]),
        "f": int(cfg["expert_ffn_hidden_size"]),
        "held": held, "routed": routed,
        "outputs": routed + int(cfg["zero_expert_num"]),
        "k": int(cfg["moe_topk"]),
        "vocab": int(cfg["vocab_size"]),
        "positions": int(cfg["length_ladder"][-1]) + int(cfg["max_new_tokens"]),
    }


def params_by_part(cfg: dict) -> dict:
    """Parameters held on the chip, by part (norms and the router's bias left
    out; the embedding apart: a decode step reads one row a sequence)."""
    m = dims(cfg)
    attn = (m["d"] * m["rq"] + m["rq"] * m["h"] * (m["nope"] + m["rope"])
            + m["d"] * (m["r"] + m["rope"])
            + m["r"] * m["h"] * (m["nope"] + m["dv"]) + m["h"] * m["dv"] * m["d"])
    return {
        "attention": m["layers"] * SUBLAYERS * attn,
        "experts": m["layers"] * m["held"] * 3 * m["d"] * m["f"],
        "router": m["layers"] * m["d"] * m["outputs"],
        "dense_mlp": m["layers"] * SUBLAYERS * 3 * m["d"] * m["f_dense"],
        "head": m["d"] * m["vocab"],
        "embedding": m["d"] * m["vocab"],
    }


def cache_bytes_per_position(cfg: dict) -> int:
    """What every layer (both sublayers) together keeps of one position."""
    m = dims(cfg)
    return BYTES * m["layers"] * SUBLAYERS * (m["r"] + m["rope"])


def cache_positions_read(m: dict, context: int) -> int:
    """Positions of one slot's cache that a step at ``context`` positions
    seen reads: whole blocks up to the one that holds the newest."""
    return min(-(-context // CACHE_BLOCK) * CACHE_BLOCK, m["positions"])


def experts_bytes_per_step(cfg: dict, read_share: float = 1.0) -> float:
    """The held experts' weight bytes a decode step reads: all layers, times
    the share of them the step's form read (1: a form that reads all)."""
    return BYTES * params_by_part(cfg)["experts"] * read_share


def decode_step(cfg: dict, contexts) -> dict:
    """One decode step of the sequences whose contexts (positions seen, the
    new token's included) are ``contexts``: -> {"ops", "bytes", each by
    part, and their totals}."""
    m, p = dims(cfg), params_by_part(cfg)
    t = len(contexts)
    routed_rows = t * m["k"] * m["held"] / m["outputs"]        # expected
    zero_choices = t * m["k"] * (m["outputs"] - m["routed"]) / m["outputs"]
    keys = float(sum(contexts)) * m["layers"] * SUBLAYERS
    read = float(sum(cache_positions_read(m, c) for c in contexts))
    per_key = 2.0 * m["h"] * (m["r"] + m["rope"]) + 2.0 * m["h"] * m["r"]
    ops = {
        "attention": 2.0 * p["attention"] * t + per_key * keys,
        "experts": (2.0 * 3 * m["d"] * m["f"] * m["layers"] * routed_rows
                    + 2.0 * m["d"] * m["layers"] * zero_choices
                    + 2.0 * p["router"] * t),
        "dense_mlp": 2.0 * p["dense_mlp"] * t,
        "head": 2.0 * p["head"] * t,
    }
    by = {
        "attention": BYTES * p["attention"] + read * cache_bytes_per_position(cfg),
        "experts": experts_bytes_per_step(cfg) + BYTES * p["router"],
        "dense_mlp": BYTES * p["dense_mlp"],
        "head": BYTES * (p["head"] + t * m["d"]),
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
    keys = float(sum(n * (n + 1) // 2 for n in lengths)) * m["layers"] * SUBLAYERS
    per_key = 2.0 * m["h"] * (m["nope"] + m["rope"]) + 2.0 * m["h"] * m["dv"]
    ops = {
        "attention": 2.0 * p["attention"] * tokens + per_key * keys,
        "experts": (2.0 * 3 * m["d"] * m["f"] * held_assignments
                    + 2.0 * p["router"] * tokens),
        "dense_mlp": 2.0 * p["dense_mlp"] * tokens,
        "head": 2.0 * p["head"] * len(lengths),
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
