"""BatchNorm variant of CANNet: torch parity + SyncBN-by-construction.

The reference's --syncBN flag is vestigial (its model has no BN layers,
SURVEY §2); here cannet_init(batch_norm=True) is the real BN variant of
make_layers (reference model/CANNet.py:104-119) and sharded-batch statistics
ARE cross-replica statistics under GSPMD.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from can_tpu.models import (
    cannet_apply,
    cannet_init,
    has_batch_norm,
    init_batch_stats,
)
from can_tpu.models.cannet import _batch_norm
from can_tpu.parallel import make_dp_train_step, make_global_batch, make_mesh
from can_tpu.train import create_train_state, make_lr_schedule, make_optimizer
from can_tpu.data.batching import Batch


class TestBatchNormOp:
    def test_train_mode_matches_torch(self):
        import torch

        rng = np.random.default_rng(0)
        y = rng.normal(size=(4, 6, 5, 8)).astype(np.float32)  # NHWC
        scale = rng.normal(size=(8,)).astype(np.float32)
        bias = rng.normal(size=(8,)).astype(np.float32)
        run_mean = rng.normal(size=(8,)).astype(np.float32)
        run_var = rng.uniform(0.5, 2.0, size=(8,)).astype(np.float32)

        out, updated = _batch_norm(
            jnp.asarray(y), {"scale": jnp.asarray(scale), "bias": jnp.asarray(bias)},
            {"mean": jnp.asarray(run_mean), "var": jnp.asarray(run_var)},
            train=True, momentum=0.1)

        tbn = torch.nn.BatchNorm2d(8, momentum=0.1)
        with torch.no_grad():
            tbn.weight.copy_(torch.tensor(scale))
            tbn.bias.copy_(torch.tensor(bias))
            tbn.running_mean.copy_(torch.tensor(run_mean))
            tbn.running_var.copy_(torch.tensor(run_var))
        tbn.train()
        t_out = tbn(torch.tensor(y).permute(0, 3, 1, 2)).permute(0, 2, 3, 1)

        np.testing.assert_allclose(np.asarray(out), t_out.detach().numpy(),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(updated["mean"]),
                                   tbn.running_mean.numpy(), rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(updated["var"]),
                                   tbn.running_var.numpy(), rtol=1e-4, atol=1e-6)

    def test_eval_mode_matches_torch(self):
        import torch

        rng = np.random.default_rng(1)
        y = rng.normal(size=(2, 4, 4, 5)).astype(np.float32)
        scale = rng.normal(size=(5,)).astype(np.float32)
        bias = rng.normal(size=(5,)).astype(np.float32)
        mean = rng.normal(size=(5,)).astype(np.float32)
        var = rng.uniform(0.5, 2.0, size=(5,)).astype(np.float32)

        out, updated = _batch_norm(
            jnp.asarray(y), {"scale": jnp.asarray(scale), "bias": jnp.asarray(bias)},
            {"mean": jnp.asarray(mean), "var": jnp.asarray(var)},
            train=False, momentum=0.1)
        assert updated is None

        tbn = torch.nn.BatchNorm2d(5)
        with torch.no_grad():
            tbn.weight.copy_(torch.tensor(scale))
            tbn.bias.copy_(torch.tensor(bias))
            tbn.running_mean.copy_(torch.tensor(mean))
            tbn.running_var.copy_(torch.tensor(var))
        tbn.eval()
        t_out = tbn(torch.tensor(y).permute(0, 3, 1, 2)).permute(0, 2, 3, 1)
        np.testing.assert_allclose(np.asarray(out), t_out.detach().numpy(),
                                   rtol=1e-4, atol=1e-5)


class TestMaskedMomentsAllFill:
    def test_all_fill_batch_yields_zeros_not_nan(self):
        """ADVICE r5: with a zero mask (every slot a dead remnant slot)
        the weighted moments were 0/0 -> NaN, poisoning params through
        the running stats.  The s0 floor must yield finite zeros."""
        rng = np.random.default_rng(2)
        y = jnp.asarray(rng.normal(size=(2, 4, 4, 3)).astype(np.float32))
        bn = {"scale": jnp.ones((3,)), "bias": jnp.zeros((3,))}
        stats = {"mean": jnp.full((3,), 1.5), "var": jnp.full((3,), 2.0)}
        mask = jnp.zeros((2, 4, 4, 1))
        out, updated = _batch_norm(y, bn, stats, train=True, momentum=0.1,
                                   mask=mask)
        assert np.isfinite(np.asarray(out)).all()
        # and the RUNNING stats must be untouched: blending the batch's
        # degenerate mean=var=0 would drag them toward zero by one
        # momentum step per all-fill batch (review r6)
        np.testing.assert_array_equal(np.asarray(updated["mean"]),
                                      np.full(3, 1.5, np.float32))
        np.testing.assert_array_equal(np.asarray(updated["var"]),
                                      np.full(3, 2.0, np.float32))

    def test_partial_mask_unchanged_by_guard(self):
        """The floor must not perturb the normal masked path."""
        rng = np.random.default_rng(3)
        y = jnp.asarray(rng.normal(size=(2, 4, 4, 3)).astype(np.float32))
        bn = {"scale": jnp.ones((3,)), "bias": jnp.zeros((3,))}
        mask = np.ones((2, 4, 4, 1), np.float32)
        mask[1] = 0.0  # second item is a fill slot
        out, updated = _batch_norm(y, bn, None, train=True, momentum=0.1,
                                   mask=jnp.asarray(mask))
        # moments must equal the unmasked moments of the valid half
        ref_mean = np.asarray(y[:1]).mean(axis=(0, 1, 2))
        np.testing.assert_allclose(np.asarray(updated["mean"]), ref_mean,
                                   rtol=1e-5, atol=1e-6)


class TestBNModel:
    def test_plain_model_has_no_bn(self):
        params = cannet_init(jax.random.key(0))
        assert not has_batch_norm(params)
        assert init_batch_stats(params) is None

    def test_bn_forward_and_stats_update(self):
        params = cannet_init(jax.random.key(0), batch_norm=True)
        assert has_batch_norm(params)
        stats = init_batch_stats(params)
        x = jnp.asarray(np.random.default_rng(0).normal(
            size=(2, 64, 64, 3)).astype(np.float32))
        out, new_stats = cannet_apply(params, x, batch_stats=stats, train=True)
        assert out.shape == (2, 8, 8, 1)
        # stats moved away from the init values
        assert not np.allclose(np.asarray(new_stats["frontend"][0]["mean"]),
                               np.asarray(stats["frontend"][0]["mean"]))
        # eval mode consumes stats, single return
        out2 = cannet_apply(params, x, batch_stats=new_stats, train=False)
        assert out2.shape == (2, 8, 8, 1)
        assert np.isfinite(np.asarray(out2)).all()

    def test_eval_without_stats_raises(self):
        params = cannet_init(jax.random.key(0), batch_norm=True)
        with pytest.raises(ValueError, match="batch_stats"):
            cannet_apply(params, jnp.ones((1, 64, 64, 3)), train=False)


class TestSyncBNSpatial:
    """SyncBN composed with spatial (context) parallelism: the dp x sp
    shard_map step pmean's batch moments over BOTH mesh axes, so BN stats
    and gradients equal the unsharded global-batch ones (VERDICT.md item 2;
    reference train.py:116-118 composes unconditionally)."""

    def test_sp_train_step_bn_stats_and_params_match_unsharded(self):
        from can_tpu.parallel.spatial import make_sp_train_step
        from can_tpu.train import make_train_step
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh(jax.devices()[:8], dp=2, sp=4)
        h, w = 128, 96
        params = cannet_init(jax.random.key(0), batch_norm=True)
        opt = make_optimizer(make_lr_schedule(1e-3, world_size=2))
        rng = np.random.default_rng(3)
        batch_np = {
            "image": rng.normal(size=(2, h, w, 3)).astype(np.float32),
            "dmap": rng.uniform(size=(2, h // 8, w // 8, 1)).astype(np.float32),
            "pixel_mask": np.ones((2, h // 8, w // 8, 1), np.float32),
            "sample_mask": np.ones((2,), np.float32),
        }
        shardings = {
            "image": NamedSharding(mesh, P("data", "spatial", None, None)),
            "dmap": NamedSharding(mesh, P("data", "spatial", None, None)),
            "pixel_mask": NamedSharding(mesh, P("data", "spatial", None, None)),
            "sample_mask": NamedSharding(mesh, P("data")),
        }
        gbatch = {k: jax.device_put(v, shardings[k]) for k, v in batch_np.items()}

        step_sp = make_sp_train_step(opt, mesh, (h, w), donate=False)
        s_sp = create_train_state(jax.tree.map(jnp.array, params), opt,
                                  init_batch_stats(params))
        s_sp, m_sp = step_sp(s_sp, gbatch)

        step_1 = jax.jit(make_train_step(cannet_apply, opt, grad_divisor=2))
        s_1 = create_train_state(jax.tree.map(jnp.array, params), opt,
                                 init_batch_stats(params))
        s_1, m_1 = step_1(s_1, {k: jnp.asarray(v) for k, v in batch_np.items()})

        np.testing.assert_allclose(float(m_sp["loss"]), float(m_1["loss"]),
                                   rtol=1e-4)
        # running stats: sharded == global-batch (SyncBN across dp AND sp)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5),
            s_sp.batch_stats, s_1.batch_stats)

        # Gradient flow THROUGH the BN collectives: parameter deltas match
        # (traversal + pre-BN-bias exclusion shared with the x64 worker via
        # parity_utils).
        #
        # Tolerance is noise-calibrated, not sloppy: the x64 subprocess
        # test below runs this exact comparison under jax_enable_x64 and
        # every real-gradient tensor agrees to <1e-4 relative, i.e. the
        # sharded gradient is structurally identical.  In f32 the backprop
        # chain through ten stacked BNs (1/sqrt(var+eps) factors) amplifies
        # reduction-order noise to ~1e-1 of each tensor's max delta, for
        # ANY two evaluation orders — so 1.5e-1 is the f32 noise floor
        # here, while a missing psum (local-shard stats) or a wrong grad
        # divisor still fails by a factor of 2+.
        from parity_utils import param_delta_rel

        for path, rel in param_delta_rel(params, s_sp.params, s_1.params):
            assert rel <= 1.5e-1, (path, rel)

    @pytest.mark.slow
    def test_sp_gradient_parity_tight_in_x64(self):
        """The strong form of the delta check above: same comparison under
        jax_enable_x64 (subprocess — x64 is process-global), where f32 BN
        noise vanishes and real-gradient deltas must agree to 1e-4
        relative.  Catches the ~10% skews the f32 noise floor would hide."""
        import os
        import subprocess
        import sys

        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        proc = subprocess.run(
            [sys.executable,
             os.path.join(os.path.dirname(__file__), "bn_sp_x64_worker.py")],
            env=env, capture_output=True, text=True, timeout=900,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert proc.returncode == 0, (
            f"x64 parity worker failed:\n{proc.stdout}\n{proc.stderr}")

    def test_sp_eval_with_running_stats_matches_dp(self):
        from can_tpu.parallel import make_dp_eval_step
        from can_tpu.parallel.spatial import make_sp_eval_step

        mesh_sp = make_mesh(jax.devices()[:8], dp=2, sp=4)
        mesh_dp = make_mesh(jax.devices()[:8])
        h, w = 128, 96
        params = cannet_init(jax.random.key(1), batch_norm=True)
        stats = init_batch_stats(params)
        rng = np.random.default_rng(4)
        batch = Batch(
            image=rng.normal(size=(8, h, w, 3)).astype(np.float32),
            dmap=rng.uniform(size=(8, h // 8, w // 8, 1)).astype(np.float32),
            pixel_mask=np.ones((8, h // 8, w // 8, 1), np.float32),
            sample_mask=np.ones((8,), np.float32),
        )
        ev_sp = make_sp_eval_step(mesh_sp, (h, w))
        m_sp = jax.device_get(ev_sp(
            params, make_global_batch(batch, mesh_sp, spatial=True), stats))
        ev_dp = make_dp_eval_step(cannet_apply, mesh_dp)
        m_dp = jax.device_get(ev_dp(params, make_global_batch(batch, mesh_dp),
                                    stats))
        np.testing.assert_allclose(m_sp["abs_err_sum"], m_dp["abs_err_sum"],
                                   rtol=2e-4)
        np.testing.assert_allclose(m_sp["sq_err_sum"], m_dp["sq_err_sum"],
                                   rtol=4e-4)


class TestMaskedBNMoments:
    """Train-mode BN moments must exclude bucket padding and dead fill
    slots (code-review r5): the reference's BN never sees padding, so the
    unmasked moments were biased by exactly the schedule's padding
    fraction."""

    def _stats(self, params, img, pm, sm):
        return cannet_apply(params, jnp.asarray(img),
                            batch_stats=init_batch_stats(params), train=True,
                            pixel_mask=jnp.asarray(pm),
                            sample_mask=jnp.asarray(sm))[1]

    def test_fill_slots_excluded_exactly(self):
        # a dead fill slot (sample_mask 0) must not move ANY layer's
        # stats: slot 0's activations are batch-independent, so masked
        # stats of [img, garbage] == stats of [img] everywhere
        params = cannet_init(jax.random.key(1), batch_norm=True)
        rng = np.random.default_rng(3)
        h = w = 16
        img = rng.normal(size=(1, h, w, 3)).astype(np.float32)
        want = self._stats(params, img, np.ones((1, 2, 2, 1), np.float32),
                           np.ones((1,), np.float32))
        two = np.concatenate([img, rng.normal(size=(1, h, w, 3))
                              .astype(np.float32)])
        got = self._stats(params, two, np.ones((2, 2, 2, 1), np.float32),
                          np.array([1.0, 0.0], np.float32))
        for g in ("frontend", "backend"):
            for a, b in zip(got[g], want[g]):
                np.testing.assert_allclose(np.asarray(a["mean"]),
                                           np.asarray(b["mean"]),
                                           rtol=1e-5, atol=1e-6)
                np.testing.assert_allclose(np.asarray(a["var"]),
                                           np.asarray(b["var"]),
                                           rtol=1e-5, atol=1e-6)

    def test_bucket_padding_excluded_from_moments(self):
        # Pad H 16->24 (zeros == normalized-space padding) and compare
        # against the unpadded run.  conv0's valid-region activations are
        # identical (its input pad really is zero), so masked conv0 stats
        # must match the unpadded truth EXACTLY — the direct
        # pad-pixel-inclusion bias is gone.  Deeper layers additionally
        # carry seam bleed (conv0's relu(bias) is nonzero in the pad
        # region and the VGG receptive field spans the toy image), which
        # masking cannot remove — that part is a bucketing approximation
        # independent of BN, shared by the loss's boundary cells; masked
        # and unmasked stats are comparable there (measured) and only
        # conv0 admits an exact claim.
        params = cannet_init(jax.random.key(1), batch_norm=True)
        rng = np.random.default_rng(4)
        h, w, ph = 16, 16, 24
        img = rng.normal(size=(1, h, w, 3)).astype(np.float32)
        want = self._stats(params, img, np.ones((1, 2, 2, 1), np.float32),
                           np.ones((1,), np.float32))
        pimg = np.zeros((1, ph, w, 3), np.float32)
        pimg[0, :h] = img[0]
        pm = np.zeros((1, 3, 2, 1), np.float32)
        pm[0, :2] = 1.0
        got = self._stats(params, pimg, pm, np.ones((1,), np.float32))
        unmasked = cannet_apply(params, jnp.asarray(pimg),
                                batch_stats=init_batch_stats(params),
                                train=True)[1]
        np.testing.assert_allclose(
            np.asarray(got["frontend"][0]["mean"]),
            np.asarray(want["frontend"][0]["mean"]), rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(got["frontend"][0]["var"]),
            np.asarray(want["frontend"][0]["var"]), rtol=1e-5, atol=1e-6)
        # and the unmasked run demonstrably HAS the direct bias at conv0
        assert not np.allclose(
            np.asarray(unmasked["frontend"][0]["mean"]),
            np.asarray(want["frontend"][0]["mean"]), rtol=1e-5, atol=1e-6)

    def test_all_ones_mask_matches_unmasked(self):
        params = cannet_init(jax.random.key(1), batch_norm=True)
        rng = np.random.default_rng(5)
        img = rng.normal(size=(2, 16, 16, 3)).astype(np.float32)
        masked = self._stats(params, img, np.ones((2, 2, 2, 1), np.float32),
                             np.ones((2,), np.float32))
        plain = cannet_apply(params, jnp.asarray(img),
                             batch_stats=init_batch_stats(params),
                             train=True)[1]
        for g in ("frontend", "backend"):
            for a, b in zip(masked[g], plain[g]):
                np.testing.assert_allclose(np.asarray(a["mean"]),
                                           np.asarray(b["mean"]),
                                           rtol=1e-5, atol=1e-6)
                np.testing.assert_allclose(np.asarray(a["var"]),
                                           np.asarray(b["var"]),
                                           rtol=1e-5, atol=1e-6)


class TestBNMomentsImpls:
    """r10 moments-path rebuild (ISSUE 7): onepass (one activation read,
    one packed collective) and the Pallas kernel must reproduce the
    two-pass reference moments; twopass stays the bit-compatible A/B
    anchor (``--bn-impl twopass`` / ``bn_ops=None``)."""

    def _data(self, seed=0, shape=(2, 16, 24, 8), dtype=np.float32):
        rng = np.random.default_rng(seed)
        y = rng.normal(size=shape).astype(dtype)
        m = np.ones(shape[:3] + (1,), np.float32)
        m[1, shape[1] // 2:] = 0.0  # real partial mask: padding fraction
        return jnp.asarray(y), jnp.asarray(m)

    def _impls(self):
        from can_tpu.ops import bn_moments as bm

        return {
            "twopass": bm.masked_moments_twopass,
            "onepass": bm.masked_moments_onepass,
            "pallas": lambda y, m, axes: bm.masked_moments_pallas(
                y, m, axes, interpret=True),
        }

    def test_masked_moments_parity_f32(self):
        y, m = self._data()
        impls = self._impls()
        want = [np.asarray(x) for x in impls["twopass"](y, m, None)]
        for name in ("onepass", "pallas"):
            got = [np.asarray(x) for x in impls[name](y, m, None)]
            for a, b in zip(got, want):
                np.testing.assert_allclose(a, b, rtol=2e-5, atol=1e-5,
                                           err_msg=name)

    def test_f32_accumulators_pinned_for_bf16_inputs(self):
        """The contract every impl shares: bf16 activations enter the
        reduction as f32 (cannet casts before the moments), so the sums
        must match a float64 numpy reference to f32 precision — a bf16
        accumulator would miss by orders of magnitude more."""
        y, m = self._data(dtype=np.float32)
        ybf = y.astype(jnp.bfloat16)
        yf = ybf.astype(jnp.float32)  # what _batch_norm hands the impls
        y64 = np.asarray(yf, np.float64)
        m64 = np.asarray(m, np.float64)
        ref_mean = (y64 * m64).sum((0, 1, 2)) / m64.sum()
        ref_var = ((y64 ** 2) * m64).sum((0, 1, 2)) / m64.sum() - ref_mean ** 2
        for name, fn in self._impls().items():
            mean, var, s0 = fn(yf, m, None)
            assert mean.dtype == jnp.float32 and var.dtype == jnp.float32
            np.testing.assert_allclose(np.asarray(mean), ref_mean,
                                       rtol=1e-5, atol=1e-6, err_msg=name)
            np.testing.assert_allclose(np.asarray(var), ref_var,
                                       rtol=1e-4, atol=1e-5, err_msg=name)

    @pytest.mark.parametrize("impl", ["onepass", "pallas"])
    def test_all_fill_guard_every_impl(self, impl):
        """The maximum(s0, 1) floor and the running-stats freeze are
        implementation-independent (the ADVICE-r5 guard must survive the
        moments rebuild)."""
        from can_tpu.ops.bn_moments import make_bn_ops

        rng = np.random.default_rng(2)
        y = jnp.asarray(rng.normal(size=(2, 4, 4, 3)).astype(np.float32))
        bn = {"scale": jnp.ones((3,)), "bias": jnp.zeros((3,))}
        stats = {"mean": jnp.full((3,), 1.5), "var": jnp.full((3,), 2.0)}
        out, updated = _batch_norm(
            y, bn, stats, train=True, momentum=0.1,
            mask=jnp.zeros((2, 4, 4, 1)),
            bn_ops=make_bn_ops(impl, interpret=True))
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_array_equal(np.asarray(updated["mean"]),
                                      np.full(3, 1.5, np.float32))
        np.testing.assert_array_equal(np.asarray(updated["var"]),
                                      np.full(3, 2.0, np.float32))

    @pytest.mark.parametrize("impl", ["onepass", "pallas"])
    def test_gradients_match_twopass(self, impl):
        from can_tpu.ops.bn_moments import make_bn_ops

        y, m = self._data(seed=3)
        bn = {"scale": jnp.full((8,), 1.3), "bias": jnp.full((8,), 0.2)}

        def loss(y, bn_ops):
            out, _ = _batch_norm(y, bn, None, train=True, momentum=0.1,
                                 mask=m, bn_ops=bn_ops)
            return jnp.sum(out ** 2)

        g_ref = jax.grad(lambda y: loss(y, None))(y)
        g = jax.grad(lambda y: loss(y, make_bn_ops(impl, interpret=True)))(y)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-5)

    @pytest.mark.parametrize("impl", ["onepass", "pallas"])
    def test_full_model_stats_parity(self, impl):
        """Model level: every BN layer's new running stats under the
        rebuilt moments path match the twopass reference (bucket padding
        + a dead fill slot in the batch, the exact train-step masking)."""
        from can_tpu.models.cannet import LocalOps
        from can_tpu.ops.bn_moments import make_bn_ops

        params = cannet_init(jax.random.key(1), batch_norm=True)
        rng = np.random.default_rng(6)
        img = rng.normal(size=(2, 24, 16, 3)).astype(np.float32)
        pm = np.ones((2, 3, 2, 1), np.float32)
        pm[0, 2:] = 0.0  # bucket padding rows on slot 0
        sm = np.array([1.0, 0.0], np.float32)  # slot 1 is a fill slot

        def stats(bn_ops):
            return cannet_apply(params, jnp.asarray(img),
                                ops=LocalOps(bn_ops=bn_ops),
                                batch_stats=init_batch_stats(params),
                                train=True, pixel_mask=jnp.asarray(pm),
                                sample_mask=jnp.asarray(sm))[1]

        want = stats(None)
        got = stats(make_bn_ops(impl, interpret=True))
        # scale-relative per leaf: 13 stacked BN layers amplify the
        # E[x^2]-mean^2 vs centered-sum f32 rounding difference, and the
        # deepest stats have tiny magnitudes where elementwise relative
        # error reads rounding as divergence.  ~1e-3 of each leaf's own
        # scale is the measured parity band; a masking bug (padding
        # counted into the moments) misses by orders of magnitude
        for g in ("frontend", "backend"):
            for a, b in zip(got[g], want[g]):
                for k in ("mean", "var"):
                    da = float(np.abs(np.asarray(a[k])
                                      - np.asarray(b[k])).max())
                    scale = max(float(np.abs(np.asarray(b[k])).max()), 1e-6)
                    assert da / scale < 5e-3, (g, k, da, scale)

    def test_bf16_compute_model_parity(self):
        """bf16 compute: the f32-accumulator pin at model level — onepass
        stats track twopass to bf16-noise tolerance, not bf16-accumulator
        tolerance."""
        from can_tpu.models.cannet import LocalOps
        from can_tpu.ops.bn_moments import make_bn_ops

        params = cannet_init(jax.random.key(1), batch_norm=True)
        rng = np.random.default_rng(7)
        img = rng.normal(size=(2, 16, 16, 3)).astype(np.float32)
        pm = np.ones((2, 2, 2, 1), np.float32)
        sm = np.ones((2,), np.float32)

        def stats(bn_ops):
            return cannet_apply(params, jnp.asarray(img),
                                ops=LocalOps(bn_ops=bn_ops),
                                compute_dtype=jnp.bfloat16,
                                batch_stats=init_batch_stats(params),
                                train=True, pixel_mask=jnp.asarray(pm),
                                sample_mask=jnp.asarray(sm))[1]

        want, got = stats(None), stats(make_bn_ops("onepass"))
        for g in ("frontend", "backend"):
            for a, b in zip(got[g], want[g]):
                for k in ("mean", "var"):
                    da = np.abs(np.asarray(a[k]) - np.asarray(b[k]))
                    scale = max(float(np.abs(np.asarray(b[k])).max()), 1e-6)
                    # scale-relative: stacked bf16 layers amplify the
                    # E[x^2]-mean^2 vs centered-sum rounding difference
                    # to ~3% of the (tiny-scale) deepest backend means
                    # (measured); a bf16 ACCUMULATOR would miss by ~10x
                    assert float(da.max()) / scale < 5e-2, (g, k)

    def test_make_bn_ops_contract(self):
        from can_tpu.ops.bn_moments import make_bn_ops

        assert make_bn_ops(None) is None
        assert make_bn_ops("twopass") is None  # the built-in default path
        assert make_bn_ops("onepass").impl == "onepass"
        assert make_bn_ops("pallas", interpret=True).interpret
        with pytest.raises(ValueError, match="unknown bn impl"):
            make_bn_ops("threepass")

    def test_pallas_unsupported_shape_falls_back(self):
        """Compiled-mode supports(): C % 128 / W % 8 gates; interpret
        accepts anything; the bn_moments wrapper takes the jnp twin for a
        gated shape and TALLIES the decision (``routed``) — the gate is
        legitimate, its silence was not."""
        from can_tpu.ops import pallas_bn
        from can_tpu.ops.bn_moments import masked_moments_pallas

        assert pallas_bn.supports((2, 16, 24, 128))
        assert not pallas_bn.supports((2, 16, 24, 64))   # C not 128-mult
        assert not pallas_bn.supports((2, 16, 20, 128))  # W not 8-mult
        assert pallas_bn.supports((2, 16, 20, 64), interpret=True)
        routed = []
        y = jax.ShapeDtypeStruct((2, 16, 24, 64), jnp.float32)
        m = jax.ShapeDtypeStruct((2, 16, 24, 1), jnp.float32)
        jax.eval_shape(lambda y, m: masked_moments_pallas(
            y, m, None, interpret=False, routed=routed), y, m)
        assert routed == [(False, (2, 16, 24, 64))]

    def test_cli_routing_line_counts_kernel_and_twin_layers(self):
        """cli.common.bn_kernel_routing asks the model itself: compiled
        mode at a real bucket routes the C >= 128 layers to the kernel
        and the C=64 stem to the twin; interpret mode takes everything."""
        from can_tpu.cli.common import bn_kernel_routing

        params = cannet_init(jax.random.key(0), batch_norm=True)
        kernel, twin = bn_kernel_routing(params, (576, 768),
                                         interpret=False)
        assert kernel > 0 and twin > 0
        assert bn_kernel_routing(params, (576, 768), interpret=True) \
            == (kernel + twin, 0)
        # W/8 = 100 -> 100 % 8 != 0 at the deepest stage: more twins
        k2, t2 = bn_kernel_routing(params, (576, 800), interpret=False)
        assert k2 + t2 == kernel + twin and t2 > twin


class TestSyncBNOnePassSpatial:
    """The shard_map 2-axis sync case (satellite): the dp x sp step with
    the rebuilt moments must still equal the unsharded global-batch step
    — AND issue strictly fewer collectives (the batched-psum half of the
    one-pass contract)."""

    @pytest.mark.parametrize("impl", ["onepass", "pallas"])
    def test_sp_onepass_stats_match_unsharded_twopass(self, impl):
        from can_tpu.ops.bn_moments import make_bn_ops
        from can_tpu.parallel.spatial import make_sp_train_step
        from can_tpu.train import make_train_step
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = make_mesh(jax.devices()[:8], dp=2, sp=4)
        h, w = 128, 96
        params = cannet_init(jax.random.key(0), batch_norm=True)
        opt = make_optimizer(make_lr_schedule(1e-3, world_size=2))
        rng = np.random.default_rng(11)
        pm = np.ones((2, h // 8, w // 8, 1), np.float32)
        pm[0, -4:] = 0.0  # unequal valid pixels across H-shards: the
        # weighted-psum path must stay exact where pmean couldn't
        batch_np = {
            "image": rng.normal(size=(2, h, w, 3)).astype(np.float32),
            "dmap": rng.uniform(size=(2, h // 8, w // 8, 1)).astype(np.float32),
            "pixel_mask": pm,
            "sample_mask": np.ones((2,), np.float32),
        }
        shardings = {
            "image": NamedSharding(mesh, P("data", "spatial", None, None)),
            "dmap": NamedSharding(mesh, P("data", "spatial", None, None)),
            "pixel_mask": NamedSharding(mesh, P("data", "spatial", None, None)),
            "sample_mask": NamedSharding(mesh, P("data")),
        }
        gbatch = {k: jax.device_put(v, shardings[k])
                  for k, v in batch_np.items()}
        step_sp = make_sp_train_step(opt, mesh, (h, w), donate=False,
                                     bn_ops=make_bn_ops(impl,
                                                        interpret=True))
        s_sp = create_train_state(jax.tree.map(jnp.array, params), opt,
                                  init_batch_stats(params))
        s_sp, m_sp = step_sp(s_sp, gbatch)

        # unsharded reference on the DEFAULT (twopass) path: cross-impl
        # and cross-sharding at once
        step_1 = jax.jit(make_train_step(cannet_apply, opt, grad_divisor=2))
        s_1 = create_train_state(jax.tree.map(jnp.array, params), opt,
                                 init_batch_stats(params))
        s_1, m_1 = step_1(s_1, {k: jnp.asarray(v)
                                for k, v in batch_np.items()})
        np.testing.assert_allclose(float(m_sp["loss"]), float(m_1["loss"]),
                                   rtol=1e-4)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5),
            s_sp.batch_stats, s_1.batch_stats)

    def test_onepass_issues_fewer_collectives(self):
        """The lowered dp x sp BN train step must carry strictly fewer
        all_reduce ops under onepass, and its moment rounds must be the
        packed ``(2C+1,)`` vectors — one per BN layer per pass.  Counting
        now rides the program-contract analyzer (the one implementation
        the committed PROGRAM_CONTRACTS.json audit also uses —
        can_tpu/analysis/hlo_audit.py; the hand-rolled regex this test
        carried is deleted)."""
        from can_tpu.analysis import hlo_audit

        facts = {
            impl: hlo_audit.program_facts(f"train_step_syncbn_{impl}")
            for impl in ("twopass", "onepass")
        }
        counts = {impl: f.collectives["all_reduce"]
                  for impl, f in facts.items()}
        assert counts["onepass"] < counts["twopass"], counts
        chans = hlo_audit.bn_channels()
        # onepass: every BN layer contributes one packed forward psum
        # plus its transpose in backward; twopass has none
        assert hlo_audit.packed_bn_reduce_count(
            facts["onepass"].all_reduce_shapes, chans) == 2 * len(chans)
        assert hlo_audit.packed_bn_reduce_count(
            facts["twopass"].all_reduce_shapes, chans) == 0


class TestBNImplDefaultByteIdentity:
    def test_plain_model_lowering_unchanged_by_bn_ops_hook(self):
        """Satellite pin (same mechanism as tests/test_perf.py): a
        default run — no --syncBN, no BN layers — lowers a byte-identical
        train step whether or not a BNOps rides in LocalOps.  The hook
        must be free when unused."""
        import functools

        from can_tpu.models.cannet import LocalOps
        from can_tpu.ops.bn_moments import make_bn_ops
        from can_tpu.train import (
            create_train_state,
            make_lr_schedule,
            make_optimizer,
            make_train_step,
        )

        params = cannet_init(jax.random.key(0))  # plain model, no BN
        opt = make_optimizer(make_lr_schedule(1e-3))
        state = create_train_state(params, opt)
        batch = {
            "image": jnp.zeros((1, 64, 64, 3), jnp.float32),
            "dmap": jnp.zeros((1, 8, 8, 1), jnp.float32),
            "pixel_mask": jnp.ones((1, 8, 8, 1), jnp.float32),
            "sample_mask": jnp.ones((1,), jnp.float32),
        }

        def lowered(apply_fn):
            return jax.jit(make_train_step(apply_fn, opt)).lower(
                state, batch).as_text()

        base = lowered(cannet_apply)
        hooked = lowered(functools.partial(
            cannet_apply, ops=LocalOps(bn_ops=make_bn_ops("onepass"))))
        assert base == hooked


class TestSyncBN:
    def test_sharded_train_step_is_syncbn(self):
        """BN stats from the dp=8-sharded batch equal full-batch stats: the
        sharded model IS SyncBatchNorm."""
        mesh = make_mesh(jax.devices()[:8])
        params = cannet_init(jax.random.key(0), batch_norm=True)
        opt = make_optimizer(make_lr_schedule(1e-8, world_size=8))
        rng = np.random.default_rng(0)
        b = 8
        batch = Batch(
            image=rng.normal(size=(b, 64, 64, 3)).astype(np.float32),
            dmap=rng.uniform(size=(b, 8, 8, 1)).astype(np.float32),
            pixel_mask=np.ones((b, 8, 8, 1), np.float32),
            sample_mask=np.ones((b,), np.float32),
        )
        step = make_dp_train_step(cannet_apply, opt, mesh, donate=False)
        state = create_train_state(params, opt, init_batch_stats(params))
        state2, _ = step(state, make_global_batch(batch, mesh))

        # reference: unsharded forward over the SAME full batch
        _, want = cannet_apply(params, jnp.asarray(batch.image),
                               batch_stats=init_batch_stats(params), train=True)
        got = state2.batch_stats
        np.testing.assert_allclose(
            np.asarray(got["frontend"][0]["mean"]),
            np.asarray(want["frontend"][0]["mean"]), rtol=1e-4, atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(got["backend"][-1]["var"]),
            np.asarray(want["backend"][-1]["var"]), rtol=1e-3, atol=1e-6)
