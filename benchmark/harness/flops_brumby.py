"""Operations and bytes a Brumby launch needs, from the configuration's
shapes, by part: power retention with its matrix state, the MLP, and the
head.  What the ALGORITHM needs, as ``flops_falcon_h1.py`` counts Falcon-H1's:
valid tokens only (padding and dead slots are the system's waste), the causal
half of the weights ``a_ts``, each weight read once per program, and the state
read once and written once a decode step at 4 bytes a number (float32: the
configuration's stated precision of the state).  bfloat16 elsewhere: 2 bytes
a parameter.

The state, per key head: the ``head_dim (head_dim + 1) / 2`` DISTINCT
monomials of the symmetric second power (8,256 at 128) times ``head_dim``
values, and as many numbers again for the normaliser's ``z``: what any
implementation has to keep, whatever rows it pads them to (the program's
8,320 rows are 0.8% more, and its own: ``state_cache_bytes_per_slot.lm`` reads
them).

Retention per token and layer.  The projections ``wq``, ``wk``, ``wv``,
``wg``, ``wo``.  A decode step: for each state number a decay, an update and a
read for each of the group's query heads: ``(3 + 2 G)`` operations.  A
prefill, the LESSER of two forms' counts (so that the form the program runs
cannot push ``prefill_roofline`` over 100): the QUADRATIC form, for each pair
``s <= t`` and query head a dot product and a weighted value, ``4 head_dim``,
and the state built once at the end, ``2 rows head_dim`` a key head and
token; the CHUNKED form at chunk ``c`` in ``CHUNKS``, the pairs inside a chunk
only, and for each token the state queried (``2 rows head_dim`` a query head)
and built (the same a key head)."""

from __future__ import annotations

BYTES = 2
STATE_BYTES = 4
CHUNKS = (128, 256, 512)


def dims(cfg: dict) -> dict:
    hd = int(cfg["head_dim"])
    return {
        "d": int(cfg["hidden_size"]), "layers": int(cfg["num_hidden_layers"]),
        "h": int(cfg["num_attention_heads"]), "kv": int(cfg["num_key_value_heads"]),
        "hd": hd, "f": int(cfg["intermediate_size"]),
        "rows": hd * (hd + 1) // 2, "vocab": int(cfg["vocab_size"]),
    }


def params_by_part(cfg: dict) -> dict:
    """Parameters held on the chip, by part (embedding apart: a decode step
    reads one row a sequence; norms left out)."""
    m = dims(cfg)
    return {
        "retention": m["layers"] * (2 * m["d"] * m["h"] * m["hd"]
                                    + 2 * m["d"] * m["kv"] * m["hd"]
                                    + m["d"] * m["kv"]),
        "mlp": m["layers"] * 3 * m["d"] * m["f"],
        "head": m["d"] * m["vocab"],
        "embedding": m["d"] * m["vocab"],
    }


def state_bytes_per_slot(cfg: dict) -> int:
    """What every layer together has to keep of one sequence whatever its
    length: ``S`` and ``z`` over the distinct monomials, float32."""
    m = dims(cfg)
    return m["layers"] * STATE_BYTES * m["kv"] * m["rows"] * (m["hd"] + 1)


def retention_bytes_per_step(cfg: dict) -> float:
    """The bytes of one decode step that are the retention layers': ``S`` and
    ``z`` of every slot (``max_batch``) and layer read once and written once,
    and ``wq``, ``wk``, ``wv``, ``wg``, ``wo`` of every layer read once."""
    return (2.0 * int(cfg["max_batch"]) * state_bytes_per_slot(cfg)
            + BYTES * params_by_part(cfg)["retention"])


def decode_step(cfg: dict, contexts) -> dict:
    """One decode step of the sequences whose contexts (positions seen, the
    new token's included) are ``contexts``: -> {"ops", "bytes", each by
    part, and their totals}.  The contexts' lengths change nothing: that is
    the model."""
    m, p = dims(cfg), params_by_part(cfg)
    t = len(contexts)
    state = m["kv"] * m["rows"] * (m["hd"] + 1) * m["layers"]
    ops = {
        "retention": t * (2.0 * p["retention"]
                          + (3.0 + 2.0 * m["h"] / m["kv"]) * state),
        "mlp": 2.0 * p["mlp"] * t,
        "head": 2.0 * p["head"] * t,
    }
    by = {
        # the state read once and written once, each slot's own
        "retention": BYTES * p["retention"] + 2.0 * t * state_bytes_per_slot(cfg),
        "mlp": BYTES * p["mlp"],
        "head": BYTES * (p["head"] + t * m["d"]),
    }
    return {"ops": ops, "bytes": by, "ops_total": sum(ops.values()),
            "bytes_total": sum(by.values())}


def retention_prefill_ops(cfg: dict, lengths) -> float:
    """The recurrence's operations over prompts of ``lengths`` tokens (the
    projections apart), all layers: the least of the quadratic form's count
    and the chunked form's at each chunk of ``CHUNKS``."""
    m = dims(cfg)
    tokens = float(sum(lengths))
    build = 2.0 * m["rows"] * m["hd"] * m["kv"] * tokens
    query = 2.0 * m["rows"] * m["hd"] * m["h"] * tokens
    pair = 4.0 * m["hd"] * m["h"]

    def pairs(chunk):
        """Pairs ``s <= t`` inside chunks of ``chunk`` positions."""
        whole = [(n // chunk, n % chunk) for n in lengths]
        return float(sum(k * chunk * (chunk + 1) // 2 + r * (r + 1) // 2
                         for k, r in whole))

    quadratic = pair * pairs(max(max(lengths), 1)) + build
    chunked = min(pair * pairs(c) + query + build for c in CHUNKS)
    return m["layers"] * min(quadratic, chunked)


def prefill(cfg: dict, lengths, held_assignments: float = 0.0) -> dict:
    """The prefill of prompts of ``lengths`` tokens: -> {"ops" by part,
    "ops_total", "bytes_total"}.  Logits at the last position only.
    ``held_assignments`` is the expert layers' (the trace reduction hands
    every model's work function one): a dense model has none."""
    m, p = dims(cfg), params_by_part(cfg)
    tokens = float(sum(lengths))
    ops = {
        "retention": 2.0 * p["retention"] * tokens
                     + retention_prefill_ops(cfg, lengths),
        "mlp": 2.0 * p["mlp"] * tokens,
        "head": 2.0 * p["head"] * len(lengths),
    }
    weights = sum(v for k, v in p.items() if k != "embedding")
    return {"ops": ops, "ops_total": sum(ops.values()),
            "bytes_total": BYTES * (weights + 2.0 * tokens * m["d"])
                           + len(lengths) * state_bytes_per_slot(cfg)}


def least_seconds(work: dict, peaks) -> float:
    """The roofline's floor of one program: the larger of operations over
    peak and bytes over bandwidth."""
    return max(work["ops_total"] / peaks.flops,
               work["bytes_total"] / peaks.hbm_bytes_s)
