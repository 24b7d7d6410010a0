"""Spatial (context) parallelism parity on the virtual 8-device CPU mesh.

The H-sharded forward (halo-exchange convs, psum'd adaptive pooling,
row-sliced upsampling) must be numerically identical to the unsharded
single-device forward — same math, different layout.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from can_tpu.models import cannet_apply, cannet_init
from can_tpu.parallel import make_mesh
from can_tpu.parallel.spatial import (
    halo_exchange_rows,
    make_sp_train_step,
    make_spatial_apply,
)
from can_tpu.train import create_train_state, make_lr_schedule, make_optimizer, make_train_step
from can_tpu.parallel.mesh import SPATIAL_AXIS
from jax.sharding import NamedSharding, PartitionSpec as P


@pytest.fixture(scope="module")
def params():
    return cannet_init(jax.random.key(0))


def _image(b=2, h=128, w=96, seed=0):
    return np.random.default_rng(seed).normal(size=(b, h, w, 3)).astype(np.float32)


class TestHaloExchange:
    def test_halo_equals_zero_padding_on_edges(self):
        """Sharded halo exchange reproduces contiguous rows; global-edge
        shards get zeros (SAME padding)."""
        mesh = make_mesh(jax.devices()[:4], dp=1, sp=4)
        x = np.arange(4 * 8 * 2 * 1, dtype=np.float32).reshape(1, 32, 2, 1)

        from functools import partial

        from jax import shard_map

        @partial(shard_map, mesh=mesh,
                 in_specs=P(None, SPATIAL_AXIS, None, None),
                 out_specs=P(None, SPATIAL_AXIS, None, None), check_vma=False)
        def ex(x):
            return halo_exchange_rows(x, 2, SPATIAL_AXIS, 4)

        out = np.asarray(ex(jnp.asarray(x)))  # (1, 4*(8+4), 2, 1)
        blocks = out.reshape(1, 4, 12, 2, 1)
        full = np.pad(x, ((0, 0), (2, 2), (0, 0), (0, 0)))
        for s in range(4):
            np.testing.assert_array_equal(blocks[0, s], full[0, s * 8: s * 8 + 12])


class TestSpatialForwardParity:
    @pytest.mark.parametrize("dp,sp", [(1, 8), (2, 4), (4, 2)])
    def test_matches_unsharded(self, params, dp, sp):
        mesh = make_mesh(jax.devices()[:8], dp=dp, sp=sp)
        b = max(dp, 2)
        x = _image(b=b, h=128, w=96)
        want = np.asarray(jax.jit(lambda p, x: cannet_apply(p, x))(params, x))
        fwd = make_spatial_apply(mesh, (128, 96))
        got = np.asarray(fwd(params, jnp.asarray(x)))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)

    def test_height_divisibility_enforced(self, params):
        mesh = make_mesh(jax.devices()[:8], dp=1, sp=8)
        with pytest.raises(ValueError, match="divisible"):
            make_spatial_apply(mesh, (120, 96))  # 120 % 64 != 0


class TestSpatialTrainStep:
    def test_matches_data_parallel_only_step(self, params):
        """(dp=2, sp=4) training == plain single-device step with the same
        global batch and grad_divisor."""
        mesh = make_mesh(jax.devices()[:8], dp=2, sp=4)
        h, w = 128, 96
        rng = np.random.default_rng(1)
        batch_np = {
            "image": rng.normal(size=(2, h, w, 3)).astype(np.float32),
            "dmap": rng.uniform(size=(2, h // 8, w // 8, 1)).astype(np.float32),
            "pixel_mask": np.ones((2, h // 8, w // 8, 1), np.float32),
            "sample_mask": np.ones((2,), np.float32),
        }
        opt = make_optimizer(make_lr_schedule(1e-3, world_size=2))

        step_sp = make_sp_train_step(opt, mesh, (h, w), donate=False)
        shardings = {
            "image": NamedSharding(mesh, P("data", "spatial", None, None)),
            "dmap": NamedSharding(mesh, P("data", "spatial", None, None)),
            "pixel_mask": NamedSharding(mesh, P("data", "spatial", None, None)),
            "sample_mask": NamedSharding(mesh, P("data")),
        }
        gbatch = {k: jax.device_put(v, shardings[k]) for k, v in batch_np.items()}
        s_sp = create_train_state(jax.tree.map(jnp.array, params), opt)
        s_sp, m_sp = step_sp(s_sp, gbatch)

        step_1 = jax.jit(make_train_step(cannet_apply, opt, grad_divisor=2))
        s_1 = create_train_state(jax.tree.map(jnp.array, params), opt)
        s_1, m_1 = step_1(s_1, {k: jnp.asarray(v) for k, v in batch_np.items()})

        np.testing.assert_allclose(float(m_sp["loss"]), float(m_1["loss"]),
                                   rtol=1e-4)
        assert float(m_sp["num_valid"]) == float(m_1["num_valid"]) == 2.0

        # compare the parameter *updates* (deltas), each leaf against its own
        # scale — raw params barely move (lr 1e-7), so elementwise rtol just
        # measures reduction-order noise on near-zero entries
        def close(p0, a, b):
            da = np.asarray(a) - np.asarray(p0)
            db = np.asarray(b) - np.asarray(p0)
            scale = max(np.abs(db).max(), 1e-12)
            # floor: deltas below ~a float32 ulp of the params (~1e-9 at the
            # 0.01 init scale) are storage quantization, not math
            assert np.abs(da - db).max() <= max(2e-3 * scale, 3e-8)

        jax.tree.map(close, params, s_sp.params, s_1.params)


class TestSpatialRemat:
    def test_sp_remat_matches_sp_plain(self, params):
        """remat only changes WHEN activations are computed, not the math —
        sp+remat step == sp step (VERDICT.md item 3; serves the UCF-QNRF
        very-large-image config)."""
        mesh = make_mesh(jax.devices()[:8], dp=2, sp=4)
        h, w = 128, 96
        rng = np.random.default_rng(7)
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
        opt = make_optimizer(make_lr_schedule(1e-3, world_size=2))

        outs = {}
        for remat in (False, True):
            step = make_sp_train_step(opt, mesh, (h, w), donate=False,
                                      remat=remat)
            s = create_train_state(jax.tree.map(jnp.array, params), opt)
            s, m = step(s, gbatch)
            outs[remat] = (s, m)

        np.testing.assert_allclose(float(outs[True][1]["loss"]),
                                   float(outs[False][1]["loss"]), rtol=1e-5)
        jax.tree.map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-8),
            outs[True][0].params, outs[False][0].params)


class TestSpatialEval:
    def test_sp_eval_matches_dp_eval(self, params):
        """dp x sp eval metrics == plain dp eval on the same batch."""
        from can_tpu.parallel import make_dp_eval_step, make_global_batch
        from can_tpu.parallel.spatial import make_sp_eval_step
        from can_tpu.data.batching import Batch

        mesh_sp = make_mesh(jax.devices()[:8], dp=2, sp=4)
        mesh_dp = make_mesh(jax.devices()[:8])
        h, w = 128, 96
        rng = np.random.default_rng(9)
        batch = Batch(
            image=rng.normal(size=(8, h, w, 3)).astype(np.float32),
            dmap=rng.uniform(size=(8, h // 8, w // 8, 1)).astype(np.float32),
            pixel_mask=np.ones((8, h // 8, w // 8, 1), np.float32),
            sample_mask=np.asarray([1, 1, 1, 1, 1, 1, 0, 0], np.float32),
        )
        ev_sp = make_sp_eval_step(mesh_sp, (h, w))
        m_sp = jax.device_get(ev_sp(params,
                                    make_global_batch(batch, mesh_sp, spatial=True),
                                    None))

        ev_dp = make_dp_eval_step(cannet_apply, mesh_dp)
        m_dp = jax.device_get(ev_dp(params, make_global_batch(batch, mesh_dp),
                                    None))
        assert m_sp["num_valid"] == m_dp["num_valid"] == 6.0
        np.testing.assert_allclose(m_sp["abs_err_sum"], m_dp["abs_err_sum"],
                                   rtol=2e-4)
        np.testing.assert_allclose(m_sp["sq_err_sum"], m_dp["sq_err_sum"],
                                   rtol=4e-4)


class TestSpatialBNForward:
    def test_bn_eval_forward_matches_unsharded(self):
        """BN checkpoints through the H-sharded viz/eval forward: eval-mode
        BN consumes replicated running stats, so the sharded forward must
        equal the single-device one (cli/test.py --sp --show-index on a
        --syncBN checkpoint rides this path)."""
        from can_tpu.models import init_batch_stats

        bn_params = cannet_init(jax.random.key(1), batch_norm=True)
        stats = init_batch_stats(bn_params)
        # perturb the running stats away from init so the test can't pass
        # by ignoring them
        stats = jax.tree.map(
            lambda a: a + 0.1 * np.arange(a.size, dtype=np.float32
                                          ).reshape(a.shape) / a.size, stats)
        x = _image(b=2, h=128, w=96, seed=3)
        want = np.asarray(jax.jit(
            lambda p, x, s: cannet_apply(p, x, batch_stats=s, train=False)
        )(bn_params, x, stats))
        mesh = make_mesh(jax.devices()[:8], dp=2, sp=4)
        fwd = make_spatial_apply(mesh, (128, 96))
        got = np.asarray(fwd(bn_params, jnp.asarray(x), stats))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-5)
