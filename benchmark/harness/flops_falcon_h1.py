"""Operations and bytes a Falcon-H1 launch needs, from the configuration's
shapes, by part: the state-space mixer with its recurrent state, attention
with its keys and values, the MLP, and the head.  What the ALGORITHM needs,
as ``flops_lm.py`` counts K-EXAONE's: valid tokens only (padding and dead
slots are the system's waste), the causal half of the scores, each weight
read once per program, keys and values up to each sequence's own context,
and the recurrent state read once and written once a decode step at 4 bytes
a number (float32: the configuration's stated precision of the state; the
convolution's tail beside it at 2).  bfloat16 elsewhere: 2 bytes a parameter
and a key or value.

The mixer, per token and layer.  Projections: ``in_proj`` and ``out_proj``;
the convolution ``2 x channels x d_conv``.  The recurrence in a decode step:
for each of heads x head_dim x d_state state numbers a decay, an update and
a read, 5 operations.  In a prefill the CHUNKED form's (chunk Q, the causal
half inside a chunk): scores ``groups x d_state x (Q + 1)``, their product
with the inputs ``heads x head_dim x (Q + 1)``, each chunk's state built and
the state before it read, ``2 x 2 x heads x head_dim x d_state``: at the
published sizes 4.8 MFLOP beside the block's 860 of matrix products."""

from __future__ import annotations

BYTES = 2
STATE_BYTES = 4


def dims(cfg: dict) -> dict:
    return {
        "d": int(cfg["hidden_size"]), "layers": int(cfg["num_hidden_layers"]),
        "h": int(cfg["num_attention_heads"]), "kv": int(cfg["num_key_value_heads"]),
        "hd": int(cfg["head_dim"]), "f": int(cfg["intermediate_size"]),
        "ds": int(cfg["mamba_d_ssm"]), "hm": int(cfg["mamba_n_heads"]),
        "p": int(cfg["mamba_d_head"]), "n": int(cfg["mamba_d_state"]),
        "g": int(cfg["mamba_n_groups"]), "kc": int(cfg["mamba_d_conv"]),
        "q": int(cfg["mamba_chunk_size"]), "vocab": int(cfg["vocab_size"]),
    }


def params_by_part(cfg: dict) -> dict:
    """Parameters held on the chip, by part (embedding apart: a decode step
    reads one row a sequence; norms and the mixer's vectors left out)."""
    m = dims(cfg)
    conv = m["ds"] + 2 * m["g"] * m["n"]
    return {
        "attention": m["layers"] * (m["d"] * m["h"] * m["hd"]
                                    + 2 * m["d"] * m["kv"] * m["hd"]
                                    + m["h"] * m["hd"] * m["d"]),
        "mixer": m["layers"] * (m["d"] * (m["ds"] + conv + m["hm"])
                                + conv * m["kc"] + m["ds"] * m["d"]),
        "mlp": m["layers"] * 3 * m["d"] * m["f"],
        "head": m["d"] * m["vocab"],
        "embedding": m["d"] * m["vocab"],
    }


def state_bytes_per_slot(cfg: dict) -> int:
    """What every layer together keeps of one sequence whatever its length:
    the recurrent state in float32 and the convolution's tail."""
    m = dims(cfg)
    conv = m["ds"] + 2 * m["g"] * m["n"]
    return m["layers"] * (STATE_BYTES * m["hm"] * m["p"] * m["n"]
                          + BYTES * conv * (m["kc"] - 1))


def kv_bytes_per_position(cfg: dict) -> int:
    m = dims(cfg)
    return BYTES * m["layers"] * 2 * m["kv"] * m["hd"]


def _conv_ops(m) -> float:
    return 2.0 * (m["ds"] + 2 * m["g"] * m["n"]) * m["kc"] * m["layers"]


def decode_step(cfg: dict, contexts) -> dict:
    """One decode step of the sequences whose contexts (positions seen, the
    new token's included) are ``contexts``: -> {"ops", "bytes", each by
    part, and their totals}."""
    m, p = dims(cfg), params_by_part(cfg)
    t = len(contexts)
    keys = float(sum(contexts)) * m["layers"]
    state = m["hm"] * m["p"] * m["n"] * m["layers"]
    ops = {
        "mixer": t * (2.0 * p["mixer"] + 5.0 * state),
        "attention": 2.0 * p["attention"] * t + 4.0 * m["h"] * m["hd"] * keys,
        "mlp": 2.0 * p["mlp"] * t,
        "head": 2.0 * p["head"] * t,
    }
    by = {
        # the state read once and written once, each slot's own
        "mixer": BYTES * p["mixer"] + 2.0 * t * state_bytes_per_slot(cfg),
        "attention": BYTES * (p["attention"] + 2 * m["kv"] * m["hd"] * keys),
        "mlp": BYTES * p["mlp"],
        "head": BYTES * (p["head"] + t * m["d"]),
    }
    return {"ops": ops, "bytes": by, "ops_total": sum(ops.values()),
            "bytes_total": sum(by.values())}


def prefill(cfg: dict, lengths, held_assignments: float = 0.0) -> dict:
    """The prefill of prompts of ``lengths`` tokens: -> {"ops" by part,
    "ops_total", "bytes_total"}.  Logits at the last position only; the
    causal half of the scores (position i sees i + 1 keys); the recurrence
    in its chunked form.  ``held_assignments`` is the expert layers' (the
    trace reduction hands every model's work function one): a dense model
    has none."""
    m, p = dims(cfg), params_by_part(cfg)
    tokens = float(sum(lengths))
    keys = float(sum(n * (n + 1) // 2 for n in lengths)) * m["layers"]
    state = m["hm"] * m["p"] * m["n"]
    chunked = ((m["g"] * m["n"] + m["hm"] * m["p"]) * (m["q"] + 1)
               + 4.0 * state) * m["layers"]
    ops = {
        "mixer": tokens * (2.0 * p["mixer"] + chunked),
        "attention": 2.0 * p["attention"] * tokens + 4.0 * m["h"] * m["hd"] * keys,
        "mlp": 2.0 * p["mlp"] * tokens,
        "head": 2.0 * p["head"] * len(lengths),
    }
    weights = sum(v for k, v in p.items() if k != "embedding")
    return {"ops": ops, "ops_total": sum(ops.values()),
            "bytes_total": BYTES * (weights + 2.0 * tokens * m["d"])
                           + tokens * kv_bytes_per_position(cfg)
                           + len(lengths) * state_bytes_per_slot(cfg)}


def least_seconds(work: dict, peaks) -> float:
    """The roofline's floor of one program: the larger of operations over
    peak and bytes over bandwidth."""
    return max(work["ops_total"] / peaks.flops,
               work["bytes_total"] / peaks.hbm_bytes_s)
