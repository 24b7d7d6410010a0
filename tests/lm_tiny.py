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


def tiny_glm_config(*, held=16, rank=0, mtp=1, **assumed) -> dict:
    """The tiny GLM-4.7-Flash preset, as a configuration-file dict: latent
    attention (query rank 24, latent 16 + 8 rotary, 4 heads of 8 + 8 / 16),
    a dense first layer and two expert layers of 16 experts (top-4, one
    shared), an MTP layer; ``held`` of the 16 experts live on rank ``rank``."""
    return {
        "model_type": "glm4_moe_lite", "attention_bias": False,
        "first_k_dense_replace": 1, "hidden_size": 64,
        "intermediate_size": 160, "moe_intermediate_size": 32,
        "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
        "norm_topk_prob": True, "num_attention_heads": 4,
        "n_routed_experts": held, "n_shared_experts": 1,
        "num_experts_per_tok": 4, "num_hidden_layers": 3,
        "num_nextn_predict_layers": mtp, "partial_rotary_factor": 1,
        "rms_norm_eps": 1e-5, "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 1.8, "tie_word_embeddings": False,
        "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "vocab_size": 256,
        "published": {"n_routed_experts": 16, "vocab_size": 256},
        "deployment": {"rank": rank},
        "assumed": assumed,
    }


def tiny_glm_model(seed=0, dtype=jnp.float32, **kw):
    """-> (config dict, Glm4MoeLiteConfig, params)."""
    from can_tpu.models import glm_moe_lite as gm

    d = tiny_glm_config(**kw)
    cfg = gm.Glm4MoeLiteConfig.from_dict(d)
    return d, cfg, gm.init_params(jax.random.key(seed), cfg, dtype)


def tiny_falcon_config(**assumed) -> dict:
    """The tiny Falcon-H1 preset, as a configuration-file dict: every
    mechanism of the published model at sizes a CPU runs in milliseconds,
    with every multiplier different from 1: 2 blocks of a Mamba-2 mixer (6
    heads of 8, state 16, G = 2 groups, convolution of width 4, chunks of 8)
    beside grouped-query attention (5 query heads to each of 2 key/value
    heads) and a SwiGLU."""
    return {
        "model_type": "falcon_h1", "attention_bias": False,
        "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.0375,
        "attn_layer_indices": None, "embedding_multiplier": 5.6,
        "head_dim": 8, "hidden_size": 64, "intermediate_size": 96,
        "key_multiplier": 0.011, "lm_head_multiplier": 0.0078,
        "mamba_chunk_size": 8, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 8, "mamba_d_ssm": 48, "mamba_d_state": 16,
        "mamba_n_groups": 2, "mamba_n_heads": 6,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True, "mlp_bias": False,
        "mlp_multipliers": [0.18, 0.011], "num_attention_heads": 10,
        "num_hidden_layers": 2, "num_key_value_heads": 2,
        "projectors_bias": False, "rms_norm_eps": 1e-5, "rope_scaling": None,
        "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.35],
        "ssm_out_multiplier": 0.088, "tie_word_embeddings": False,
        "vocab_size": 256, "published": {"vocab_size": 256},
        "assumed": assumed,
    }


def tiny_falcon_model(seed=0, dtype=jnp.float32, **kw):
    """-> (config dict, FalconH1Config, params)."""
    from can_tpu.models import falcon_h1 as fh

    d = tiny_falcon_config(**kw)
    cfg = fh.FalconH1Config.from_dict(d)
    return d, cfg, fh.init_params(jax.random.key(seed), cfg, dtype)


def tiny_lfm2_config(*, held=16, rank=0, layer_types=None, **assumed) -> dict:
    """The tiny LFM2-MoE preset, as a configuration-file dict: both kinds of
    mixer (a gated short convolution of width 3, grouped-query attention of
    4 heads of 16 with q/k norm) in the published order ``c c A c c c A c``,
    both leading dense layers, 6 expert layers of 16 experts (top-4, a bias
    on the choice, NO shared expert), a tied head over 512 ids; ``held`` of
    the 16 experts live on rank ``rank``."""
    kinds = list(layer_types or ["conv", "conv", "full_attention", "conv",
                                 "conv", "conv", "full_attention", "conv"])
    return {
        "model_type": "lfm2_moe", "conv_L_cache": 3, "conv_bias": False,
        "hidden_size": 64, "intermediate_size": 160, "layer_types": kinds,
        "max_position_embeddings": 128000, "moe_intermediate_size": 32,
        "norm_eps": 1e-5, "norm_topk_prob": True, "num_attention_heads": 4,
        "num_dense_layers": 2, "num_experts": held, "num_experts_per_tok": 4,
        "num_hidden_layers": len(kinds), "num_key_value_heads": 2,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "vocab_size": 512, "published": {"num_experts": 16},
        "deployment": {"rank": rank},
        "assumed": assumed,
    }


def tiny_lfm2_model(seed=0, dtype=jnp.float32, **kw):
    """-> (config dict, Lfm2MoeConfig, params)."""
    from can_tpu.models import lfm2_moe as lm

    d = tiny_lfm2_config(**kw)
    cfg = lm.Lfm2MoeConfig.from_dict(d)
    return d, cfg, lm.init_params(jax.random.key(seed), cfg, dtype)


def tiny_mimo_config(*, held=32, rank=0, layers=7, **edits) -> dict:
    """The tiny MiMo-V2-Flash preset, as a configuration-file dict: every
    mechanism of the published model at sizes a CPU runs in milliseconds: 8
    query heads; full layers of 2 key/value heads (theta 5e6), window layers
    of 4 (theta 1e4, a window of 8, a learned sink); keys 24 wide of which
    the first 8 rotary, values 16; a value scale; the published pattern's
    first seven layers ``F S S S S F S`` with layer 0 dense; 32 experts
    (top-4, a bias on the choice, NO shared expert); an untied head over 512
    ids.  ``held`` of the 32 experts live on rank ``rank``; ``edits``
    replace keys (``assumed=...`` among them)."""
    d = {
        "model_type": "mimo_v2_flash", "attention_value_scale": 0.707,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 160,
        "max_position_embeddings": 262144, "num_attention_heads": 8,
        "head_dim": 24, "num_hidden_layers": layers, "num_key_value_heads": 2,
        "layernorm_epsilon": 1e-5, "rope_theta": 5000000,
        "tie_word_embeddings": False, "vocab_size": 512,
        "partial_rotary_factor": 0.334, "sliding_window": 8,
        "swa_rope_theta": 10000, "attention_bias": False, "v_head_dim": 16,
        "hybrid_layer_pattern": [0, 1, 1, 1, 1, 0, 1, 1, 1, 1, 1, 0],
        "add_swa_attention_sink_bias": True,
        "add_full_attention_sink_bias": False, "sliding_window_size": 8,
        "attention_chunk_size": 8,
        "moe_layer_freq": [0] + [1] * 11, "moe_intermediate_size": 32,
        "n_routed_experts": held, "n_shared_experts": None,
        "num_experts_per_tok": 4, "norm_topk_prob": True,
        "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
        "topk_method": "noaux_tc", "routed_scaling_factor": None,
        "swa_num_attention_heads": 8, "swa_num_key_value_heads": 4,
        "swa_head_dim": 24, "swa_v_head_dim": 16,
        "published": {"n_routed_experts": 32, "vocab_size": 512},
        "deployment": {"rank": rank},
        "assumed": {},
    }
    d.update(edits)
    return d


def tiny_mimo_model(seed=0, dtype=jnp.float32, **kw):
    """-> (config dict, MimoV2FlashConfig, params)."""
    from can_tpu.models import mimo_v2_flash as mv

    d = tiny_mimo_config(**kw)
    cfg = mv.MimoV2FlashConfig.from_dict(d)
    return d, cfg, mv.init_params(jax.random.key(seed), cfg, dtype)


def tiny_brumby_config(**edits) -> dict:
    """The tiny Brumby preset, as a configuration-file dict: every mechanism
    of the published model at sizes a CPU runs in milliseconds: 3 layers of
    power retention (6 query heads over 2 key heads of 8, so groups of 3 and
    a state of 40 rows a key head, per-head norms, rotary, a gate a key
    head) and a SwiGLU, an untied head over 256 ids.  ``edits`` replace keys
    (``assumed=...`` among them)."""
    d = {
        "model_type": "brumby", "attention_bias": False, "head_dim": 8,
        "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
        "max_position_embeddings": 32768, "max_window_layers": 3,
        "num_attention_heads": 6, "num_hidden_layers": 3,
        "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_scaling": None,
        "rope_theta": 1000000, "sliding_window": None,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 256, "published": {"num_hidden_layers": 3,
                                         "vocab_size": 256},
        "assumed": {},
    }
    d.update(edits)
    return d


def tiny_brumby_model(seed=0, dtype=jnp.float32, **kw):
    """-> (config dict, BrumbyConfig, params)."""
    from can_tpu.models import brumby as bm

    d = tiny_brumby_config(**kw)
    cfg = bm.BrumbyConfig.from_dict(d)
    return d, cfg, bm.init_params(jax.random.key(seed), cfg, dtype)


def tiny_longcat_config(*, held=2, rank=0, **edits) -> dict:
    """The tiny LongCat-Flash preset, as a configuration-file dict: every
    mechanism of the published model at sizes a CPU runs in milliseconds: 2
    layers of two latent-attention sublayers (query rank 24, latent 16 + 8
    rotary, 4 heads of 8 + 8 / 16, both latents scaled), two dense SwiGLUs
    and one expert layer on the shortcut: a softmax router of 8 experts + 4
    identity experts, top-3, not normalised, times 6, a bias on the choice;
    an untied head over 256 ids.  ``held`` of the 8 experts live on rank
    ``rank``; ``edits`` replace keys (``assumed=...`` among them)."""
    d = {
        "model_type": "longcat_flash", "attention_bias": False,
        "vocab_size": 256, "hidden_size": 64, "ffn_hidden_size": 160,
        "expert_ffn_hidden_size": 32, "num_layers": 2,
        "num_attention_heads": 4, "kv_lora_rank": 16, "q_lora_rank": 24,
        "qk_rope_head_dim": 8, "v_head_dim": 16, "qk_nope_head_dim": 8,
        "mla_scale_q_lora": True, "mla_scale_kv_lora": True,
        "routed_scaling_factor": 6, "n_routed_experts": held,
        "max_position_embeddings": 131072, "rms_norm_eps": 1e-5,
        "rope_theta": 10000000, "attention_method": "MLA",
        "zero_expert_num": 4, "zero_expert_type": "identity", "moe_topk": 3,
        "published": {"n_routed_experts": 8, "vocab_size": 256},
        "deployment": {"rank": rank},
        "assumed": {},
    }
    d.update(edits)
    return d


def tiny_longcat_model(seed=0, dtype=jnp.float32, **kw):
    """-> (config dict, LongcatFlashConfig, params)."""
    from can_tpu.models import longcat_flash as lf

    d = tiny_longcat_config(**kw)
    cfg = lf.LongcatFlashConfig.from_dict(d)
    return d, cfg, lf.init_params(jax.random.key(seed), cfg, dtype)


def interpret_skipping_experts(monkeypatch) -> None:
    """The skipping experts kernel (``ops/pallas_experts.py``) interpreted
    wherever its shapes fit: what a TPU backend turns on, steered here as
    the compile tests steer it."""
    import functools

    from can_tpu.ops import pallas_experts

    monkeypatch.setattr(pallas_experts, "supports", functools.partial(
        pallas_experts.supports, interpret=True))
    monkeypatch.setattr(pallas_experts, "skipping_experts", functools.partial(
        pallas_experts.skipping_experts, interpret=True))


def interpret_fused_retention(monkeypatch) -> None:
    """The fused retention step (``ops/pallas_retention.py``) interpreted
    wherever its shapes fit: what a TPU backend turns on, steered here as
    the compile tests steer it."""
    import functools

    from can_tpu.ops import pallas_retention

    monkeypatch.setattr(pallas_retention, "supports", functools.partial(
        pallas_retention.supports, interpret=True))
    monkeypatch.setattr(pallas_retention, "fused_step", functools.partial(
        pallas_retention.fused_step, interpret=True))


def interpret_fused_latent(monkeypatch, block: int = 128) -> None:
    """The fused latent decode (``ops/pallas_latent.py``) interpreted
    wherever its shapes fit, in blocks of ``block`` positions: what a TPU
    backend turns on, steered here as the compile tests steer it."""
    import functools

    from can_tpu.ops import pallas_latent

    monkeypatch.setattr(pallas_latent, "supports", functools.partial(
        pallas_latent.supports, block=block, interpret=True))
    monkeypatch.setattr(pallas_latent, "fused_latent_decode", functools.partial(
        pallas_latent.fused_latent_decode, block=block, interpret=True))
