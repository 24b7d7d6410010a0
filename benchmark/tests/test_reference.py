"""The plain reference describes the network the program runs: in float32
the two agree to rounding, forward and gradient, on the benchmark's own
weights; its lower-precision modes (the controls) move away from it in the
order one expects."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.harness import weights
from benchmark.reference import cannet_ref


@pytest.fixture(scope="module")
def setup():
    params = weights.make_params(3)
    rng = np.random.default_rng(0)
    batch = {"image": rng.standard_normal((2, 48, 64, 3)).astype(np.float32),
             "dmap": rng.random((2, 6, 8, 1)).astype(np.float32),
             "pixel_mask": np.ones((2, 6, 8, 1), np.float32),
             "sample_mask": np.ones((2,), np.float32)}
    batch["pixel_mask"][1, :, 6:] = 0.0  # a padded image
    return params, batch


def test_weights_have_the_published_size(setup):
    assert cannet_ref.param_count(setup[0]) == 20_719_937


def test_seeds_over_31_bits_make_different_weights():
    a = weights.make_params(5)["output"]["w"]
    b = weights.make_params(5 + 2 ** 31)["output"]["w"]
    assert not np.allclose(a, b)


def test_forward_and_gradient_agree_with_the_program_in_float32(setup):
    from can_tpu.models import cannet_apply
    from can_tpu.train.loss import masked_mse_sum

    params, batch = setup
    with jax.default_matmul_precision("highest"):
        want = cannet_apply(params, jnp.asarray(batch["image"]))
        got = cannet_ref.forward(params, jnp.asarray(batch["image"]))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=1e-7)
        g_want = jax.grad(lambda p: masked_mse_sum(
            cannet_apply(p, jnp.asarray(batch["image"])), batch))(params)
    loss, g_got = cannet_ref.loss_and_grad(params, batch, "f32", block=1)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        scale = float(jnp.max(jnp.abs(b))) + 1e-12
        assert float(jnp.max(jnp.abs(a - b))) <= 2e-3 * scale


def test_lower_precisions_move_away_in_order(setup):
    params, batch = setup
    _, ref = cannet_ref.predict(params, batch, "f32")
    err = {}
    for mode in ("bf16", "int8w", "int8"):
        _, d = cannet_ref.predict(params, batch, mode)
        assert np.isfinite(d).all()
        err[mode] = np.linalg.norm(d - ref) / np.linalg.norm(ref)
    assert 0 < err["bf16"] < err["int8w"] < err["int8"] < 1


def test_sgd_steps_follow_momentum_and_learning_rate(setup):
    params, batch = setup
    losses, g1, end1 = cannet_ref.train_steps(params, [batch], "f32")
    w0 = np.asarray(params["backend"][5]["w"])
    # one step: p - lr * g
    np.testing.assert_allclose(end1["backend"][5]["w"],
                               w0 - cannet_ref.LR * g1["backend"][5]["w"], rtol=1e-6)
    losses, g1b, end3 = cannet_ref.train_steps(params, [batch, batch, batch], "f32")
    assert len(losses) == 3 and all(np.isfinite(losses))
    np.testing.assert_allclose(g1b["output"]["w"], g1["output"]["w"])
    step1 = np.linalg.norm(end1["backend"][5]["w"] - w0)
    step3 = np.linalg.norm(end3["backend"][5]["w"] - w0)
    # the same batch thrice with momentum 0.95: 1 + 1.95 + 2.8525 steps' worth
    assert step3 == pytest.approx(step1 * (1 + 1.95 + 2.8525), rel=0.05)
