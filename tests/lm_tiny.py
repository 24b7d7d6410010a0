"""The tiny K-EXAONE preset of the CPU tests: every mechanism of the
published model (window and full layers ``L L L G L``, a dense first layer,
sparse experts with a shared one, grouped-query heads, an MTP layer) at
sizes a CPU runs in milliseconds."""

import jax
import jax.numpy as jnp

from can_tpu.models import exaone_moe as em


def tiny_config(*, held=8, rank=0, vocab_held=256, mtp=1, **assumed) -> dict:
    """A configuration-file dict: ``held`` of 8 experts and ``vocab_held``
    of 256 rows live on rank ``rank``."""
    return {
        "first_k_dense_replace": 1, "head_dim": 16, "hidden_size": 64,
        "intermediate_size": 160,
        "layer_types": ["sliding_attention"] * 3 + ["full_attention",
                                                    "sliding_attention"],
        "mlp_layer_types": ["dense"] + ["sparse"] * 4,
        "moe_intermediate_size": 32, "n_group": 1, "norm_topk_prob": True,
        "num_attention_heads": 4, "num_experts": held,
        "num_experts_per_tok": 2, "num_hidden_layers": 5,
        "num_key_value_heads": 2, "num_nextn_predict_layers": mtp,
        "num_shared_experts": 1, "rms_norm_eps": 1e-5,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "sliding_window": 8, "topk_group": 1, "vocab_size": vocab_held,
        "published": {"num_experts": 8, "vocab_size": 256},
        "deployment": {"rank": rank},
        "assumed": assumed,
    }


def tiny_model(seed=0, dtype=jnp.float32, **kw):
    """-> (config dict, ExaoneMoeConfig, params)."""
    d = tiny_config(**kw)
    cfg = em.ExaoneMoeConfig.from_dict(d)
    return d, cfg, em.init_params(jax.random.key(seed), cfg, dtype)
