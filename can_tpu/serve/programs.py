"""The model seam of the serving path: the programs an engine runs.

An engine (``serve/engine.py``) owns device-resident parameters, compile
counting, warm-up and the fetch; WHAT it runs it is given.  This module
builds those programs from the networks:

* ``cannet_predict``: CANNet's one program, a padded image batch ->
  (counts, masked density): the offline eval step's math;
* ``LMPrograms``: the language model's two, over one launch's cache:
  ``prefill_slice`` (some of the launch's prompts -> their first generated
  token, their cache rows written at their slots) and ``decode`` (one greedy
  token for every slot, the cache and the step's state donated).

``MODEL_TYPES`` is the table ``serve.build_model_service`` reads: for each
``model_type`` a configuration file may name, what is the model's own on
the serving path.  This module is the only one under ``serve/`` that
imports a network.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from can_tpu.serve import cache as kv_cache


def cannet_predict(serve_dtype: str, compute_dtype):
    """``predict(params, batch, batch_stats) -> (counts, masked density)``."""
    from can_tpu.models import cannet_apply, stage1_traced
    from can_tpu.serve.quant import dequantize_tree
    from can_tpu.train.loss import density_counts
    from can_tpu.train.steps import _batch_image

    def predict(params, batch, batch_stats):
        # int8 mode: in-program dequant (fused multiply; HBM holds
        # int8) -> f32 weights -> f32 arithmetic ("f32 accumulation")
        params = dequantize_tree(params, serve_dtype)
        image = _batch_image(batch)  # u8 -> normalised f32, f32 passthru
        if batch_stats is not None:
            pred = cannet_apply(params, image,
                                compute_dtype=compute_dtype,
                                batch_stats=batch_stats, train=False)
        else:
            pred = cannet_apply(params, image,
                                compute_dtype=compute_dtype)
        counts, _ = density_counts(pred, batch)
        # the counts are reduced BEFORE the density is masked, as a program
        # of their own would reduce them: fused into one loop with the
        # masked density XLA:CPU sums in another order, and a served count
        # then differs from evaluate()'s in the last bit
        pred, counts = jax.lax.optimization_barrier((pred, counts))
        mask = (batch["pixel_mask"]
                * batch["sample_mask"][:, None, None, None])
        return counts, pred.astype(jnp.float32) * mask

    # (image shape) -> how the newest trace of this network on such a batch
    # carried its first stage; the engine asks after a program's first launch
    predict.stage1_traced = stage1_traced
    return predict


class LMPrograms:
    """A language model's pure functions as serving programs.  The model is
    GIVEN.  What is asked of it is what every language model has:

    * ``model.prefill(params, tokens, lengths, cfg, cache_len, active=)`` ->
      (float32 logits (B, V) at each prompt's last position, the cache rows
      of those B sequences, routing); ``tokens`` are right-padded, and
      whatever the model keeps of a prompt (keys, a latent, a recurrence's
      state) is what it is AT EACH PROMPT'S OWN LENGTH;
    * ``model.decode_step(params, cache, tokens, positions, cfg, active=)``
      -> (logits, cache, routing);
    * ``model.cache_layout(cfg)``: for each layer held an
      ``ops.cache_layout.LayerSpec``, or a tuple of them where a block keeps
      two kinds;
    * ``routing``: {"counts" (expert layers, held experts) int32, "choices"
      (expert layers, B, k) int32}; a model WITHOUT an expert layer returns
      them with no rows (``lm_blocks.no_routing``) and is counted as any
      other: its counters read zero, its answers' routing is empty;
    * ``cfg.vocab`` (``lm_blocks.VocabSlice``): ids and logits are over the
      slice held;
    * optionally ``model.attention_traced`` / ``model.ssm_traced`` /
      ``model.conv_traced`` / ``model.retention_traced`` /
      ``model.latent_traced``: (B, L) of a program's tokens -> the form its
      newest trace ran that layer in;
    * optionally ``model.CACHE_PART``: the part of the model (a name of
      ``lm_blocks.PARTS``) that placing a prefill slice's rows into the
      launch's cache belongs to; ``attn.cache`` where the model names none;
    * a model with an expert layer: ``model.experts_form(cfg, tokens, dtype)``
      -> the form its expert layers take for a program of that many tokens
      (``ops.moe.share_form``).  In the ``"skipping"`` form its ``routing``
      also has ``experts_read`` () int32, the held experts whose weights the
      step read, summed over the expert layers; in the ``"sorted"`` form
      ``dispatch_passes`` () int32, the passes over the sorted buffer that
      the expert layers took, summed over them.  A model whose router has
      identity experts (``ops.moe.ExpertShare.zero``) also returns ``zero``
      (expert layers,) int32: the counted tokens' choices that are identity
      experts, held nowhere and reading nothing.

    State between the programs of a launch, all on the device: the cache
    (``serve/cache.py``) and ``state``: ``tokens`` (slots,) the token each
    slot feeds next, ``positions`` (slots,) where it stands, ``active``
    (slots,) which slots hold a request, ``ids`` (slots, max_new_tokens + 1)
    what each slot has generated (column 0 from prefill, column s from
    decode step s), ``step`` the next decode step (from 1), ``counts``
    (expert layers, held) decode assignments that landed on each held
    expert; where decode's expert layers skip (``experts_form``), also
    ``experts_read`` () the experts they read so far, summed over layers and
    steps; where the router has identity experts, ``zero`` (expert layers,)
    decode choices that were identity experts.  Greedy: the next token is the argmax over the vocabulary slice.
    """

    def __init__(self, model, cfg, *, max_new_tokens: int,
                 dtype=jnp.bfloat16):
        self._m = model
        self.cfg = cfg
        self.max_new_tokens = int(max_new_tokens)
        self.dtype = dtype   # of the cache: the parameters' own
        self.cache_layout = tuple(model.cache_layout(cfg))
        # ((B, L) of a prefill's prompts) -> "fused" / "scanned", as the
        # newest trace of the model's prefill on such prompts ran its
        # attention; None for a model with one form
        self.attention_traced = getattr(model, "attention_traced", None)
        # ((B, L) of a program's tokens; L = 1: decode) -> "chunked" /
        # "step" / a kernel's name, as its newest trace ran the state-space
        # recurrence; None for a model without one
        self.ssm_traced = getattr(model, "ssm_traced", None)
        # the same of a gated short convolution: "causal" / "step"
        self.conv_traced = getattr(model, "conv_traced", None)
        # the same of a retention layer: "chunked" / "step" / "fused"
        self.retention_traced = getattr(model, "retention_traced", None)
        # ((B, 1) of a decode step) -> "fused" / "plain", as its newest
        # trace read a latent cache; None for a model without one
        self.latent_traced = getattr(model, "latent_traced", None)
        self._cache_part = getattr(model, "CACHE_PART", "attn.cache")
        self._experts_form = getattr(model, "experts_form", None)
        self.vocab_size = cfg.vocab.held   # ids and logits are over the slice
        # {a name on a compiled instruction's path: the part of the model it
        # stands for}: the scopes the programs open, and what the compiler
        # renames (imported here: a process that serves CANNet never loads it)
        from can_tpu.models.lm_blocks import PARTS, RENAMED_BY_COMPILER

        self.parts = {**{p: p for p in PARTS}, **RENAMED_BY_COMPILER}

    def decode_experts(self, slots: int):
        """``"skipping"`` / ``"batched"`` / ``"sorted"``: the form the expert
        layers take in a decode step of ``slots`` tokens (shapes and the
        backend say, as they do when the step is traced); None for a model
        without an expert layer."""
        if self._experts_form is None:
            return None
        return self._experts_form(self.cfg, slots, self.dtype)

    def positions(self, bucket: int) -> int:
        """Positions of context a launch of ``bucket``-token prompts needs."""
        return bucket + self.max_new_tokens

    def new_cache(self, slots: int, bucket: int):
        return kv_cache.allocate(self.cache_layout, slots=slots,
                                 positions=self.positions(bucket),
                                 dtype=self.dtype)

    def new_state(self, outs, lengths, active):
        """The slices' outputs put together and the decode state after
        prefill: -> (state, {"logits" (slots, V), "choices", "counts"} of
        the whole launch's prefill; where the slices' expert layers count
        their passes, also "dispatch_passes" (), their sum; where the
        router has identity experts, "zero" (expert layers,), theirs)."""
        cat = lambda name, axis=0: jnp.concatenate([o[name] for o in outs], axis)
        first = cat("first")
        pre = {"logits": cat("logits"), "choices": cat("choices", 1),
               "counts": sum(o["counts"] for o in outs)}
        for name in ("dispatch_passes", "zero"):
            if name in outs[0]:
                pre[name] = sum(o[name] for o in outs)
        ids = jnp.zeros((first.shape[0], self.max_new_tokens + 1), jnp.int32)
        state = {"tokens": first, "positions": lengths.astype(jnp.int32),
                 "active": active, "ids": ids.at[:, 0].set(first),
                 "step": jnp.ones((), jnp.int32),
                 "counts": jnp.zeros_like(pre["counts"])}
        if self.decode_experts(first.shape[0]) == "skipping":
            state["experts_read"] = jnp.zeros((), jnp.int32)
        if "zero" in pre:
            state["zero"] = jnp.zeros_like(pre["zero"])
        return state, pre

    def prefill_slice(self, params, batch, cache, start):
        """``batch``: {"tokens" (s, L), "lengths" (s,), "active" (s,)} for
        slots ``start .. start + s - 1`` -> ({"first", "logits", "choices",
        "counts"; "dispatch_passes" where the expert layers count them,
        "zero" where the router has identity experts}, the cache with those
        slots' rows written)."""
        logits, part, routing = self._m.prefill(
            params, batch["tokens"], batch["lengths"], self.cfg,
            self.positions(batch["tokens"].shape[1]), active=batch["active"])
        # the scopes are the models' vocabulary (``lm_blocks.PARTS``)
        with jax.named_scope(self._cache_part):
            cache = jax.tree.map(
                lambda c, p: jax.lax.dynamic_update_slice_in_dim(
                    c, p.astype(c.dtype), start, axis=0), cache, part)
        with jax.named_scope("sample"):
            first = jnp.argmax(logits, -1).astype(jnp.int32)
        out = {"first": first, "logits": logits,
               "choices": routing["choices"], "counts": routing["counts"]}
        for name in ("dispatch_passes", "zero"):
            if name in routing:
                out[name] = routing[name]
        return out, cache

    def decode(self, params, state, cache):
        """One greedy step for every slot -> (state, cache, {"logits",
        "choices"} of this step)."""
        logits, cache, routing = self._m.decode_step(
            params, cache, state["tokens"], state["positions"], self.cfg,
            active=state["active"])
        with jax.named_scope("sample"):
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
            ids = jax.lax.dynamic_update_slice(
                state["ids"], nxt[:, None], (jnp.zeros((), jnp.int32),
                                             state["step"]))
            moved = {"tokens": nxt, "positions": state["positions"] + 1,
                     "active": state["active"], "ids": ids,
                     "step": state["step"] + 1,
                     "counts": state["counts"] + routing["counts"]}
            for name in ("experts_read", "zero"):
                if name in routing:
                    moved[name] = state[name] + routing[name]
        return moved, cache, {"logits": logits, "choices": routing["choices"]}


# -- the table of served models -------------------------------------------
class ServingModel(NamedTuple):
    """What the serving path has to be told about one ``model_type``."""

    # (config dict, params or None, seed) -> (programs, params): the
    # model's config read from the file, its programs, and its parameters
    # (made from the seed where none were given)
    programs: Callable
    # (params, programs, config dict, telemetry) -> the engine that runs them
    engine: Callable
    # (engine, config dict, **queue and batcher arguments) -> the service
    service: Callable


def _lm_programs(model_of):
    """``ServingModel.programs`` for a language model: ``model_of()`` ->
    (the model's module, its config class), imported when first asked for."""
    def programs(config: dict, params, seed: int):
        model, config_class = model_of()
        cfg = config_class.from_dict(config)
        if params is None:
            params = model.init_params(jax.random.key(seed), cfg)
        return LMPrograms(model, cfg,
                          max_new_tokens=int(config["max_new_tokens"]),
                          dtype=params["embed"].dtype), params
    return programs


def _exaone_moe():
    from can_tpu.models import exaone_moe

    return exaone_moe, exaone_moe.ExaoneMoeConfig


def _glm_moe_lite():
    from can_tpu.models import glm_moe_lite

    return glm_moe_lite, glm_moe_lite.Glm4MoeLiteConfig


def _falcon_h1():
    from can_tpu.models import falcon_h1

    return falcon_h1, falcon_h1.FalconH1Config


def _lfm2_moe():
    from can_tpu.models import lfm2_moe

    return lfm2_moe, lfm2_moe.Lfm2MoeConfig


def _mimo_v2_flash():
    from can_tpu.models import mimo_v2_flash

    return mimo_v2_flash, mimo_v2_flash.MimoV2FlashConfig


def _brumby():
    from can_tpu.models import brumby

    return brumby, brumby.BrumbyConfig


def _longcat_flash():
    from can_tpu.models import longcat_flash

    return longcat_flash, longcat_flash.LongcatFlashConfig


def _lm_engine(params, programs, config: dict, telemetry):
    from can_tpu.serve.engine import LMEngine

    return LMEngine(params, programs, telemetry=telemetry,
                    prefill_slice=int(config["prefill_slice"]))


def _generate_service(engine, config: dict, **kw):
    from can_tpu.serve.service import GenerateService

    return GenerateService(engine, length_ladder=config["length_ladder"], **kw)


MODEL_TYPES = {
    "exaone_moe": ServingModel(_lm_programs(_exaone_moe), _lm_engine,
                               _generate_service),
    "glm4_moe_lite": ServingModel(_lm_programs(_glm_moe_lite), _lm_engine,
                                  _generate_service),
    "falcon_h1": ServingModel(_lm_programs(_falcon_h1), _lm_engine,
                              _generate_service),
    "lfm2_moe": ServingModel(_lm_programs(_lfm2_moe), _lm_engine,
                             _generate_service),
    "mimo_v2_flash": ServingModel(_lm_programs(_mimo_v2_flash), _lm_engine,
                                  _generate_service),
    "brumby": ServingModel(_lm_programs(_brumby), _lm_engine,
                           _generate_service),
    "longcat_flash": ServingModel(_lm_programs(_longcat_flash), _lm_engine,
                                  _generate_service),
}


def serving_model(model_type) -> ServingModel:
    if model_type not in MODEL_TYPES:
        raise ValueError(f"no serving programs for model_type {model_type!r} "
                         f"(served from a configuration file: "
                         f"{sorted(MODEL_TYPES)})")
    return MODEL_TYPES[model_type]
