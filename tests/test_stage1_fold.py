"""Stage 1 of CANNet on W-pairs of 128 channels (models/cannet.py::
stage1_layout, ops/conv.py::fold_w_pairs_kernel, ops/pooling.py::
max_pool2d_w_pairs): the folded stage computes what the plain one computes,
forward and backward, trains the ORIGINAL kernels, leaves no 64-channel
full-resolution tensor in the program, and every caller it cannot serve
takes the plain path by what the code observes."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from can_tpu.models import (
    LocalOps,
    cannet_apply,
    cannet_init,
    stage1_layout,
    stage1_traced,
)
from can_tpu.ops.conv import conv2d, fold_w_pairs, fold_w_pairs_kernel
from can_tpu.ops.pooling import max_pool2d, max_pool2d_w_pairs

SHAPES = [(1, 8, 8), (2, 16, 24), (8, 32, 40)]
# float32: the same sums of the same products, in another order; bfloat16:
# against the plain path in float32 on bf16-quantised weights and inputs
# (XLA:CPU may accumulate bf16 convolutions in bf16, the chip does not)
DTYPES = [pytest.param("float32", id="f32"), pytest.param("bfloat16", id="bf16")]
TOL = {"float32": 1e-6, "bfloat16": 4e-2}
PLAIN = LocalOps(max_pool_pairs=None)


def rel_gap(got, want):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.abs(got - want).max() / (np.abs(want).max() + 1e-30))


def make_params(seed=0):
    """He-scaled front end, as the benchmark's weights: activations of order
    one at every depth, so a gap is not hidden under 1e-12 outputs."""
    params = cannet_init(jax.random.key(seed))
    for p in params["frontend"]:
        fan_in = 9 * p["w"].shape[2]
        p["w"] = p["w"] * (np.sqrt(2.0 / fan_in) / 0.01)
        p["b"] = p["b"] + 0.05
    for p in params["backend"]:
        p["w"] = p["w"] * (np.sqrt(2.0 / (9 * p["w"].shape[2])) / 0.01)
    return params


def make_batch(shape, seed=1):
    n, h, w = shape
    rng = np.random.default_rng(seed)
    image = jnp.asarray(rng.standard_normal((n, h, w, 3)), jnp.float32)
    dmap = jnp.asarray(rng.random((n, h // 8, w // 8, 1)), jnp.float32)
    mask = jnp.asarray(rng.random((n, h // 8, w // 8, 1)) > 0.2, jnp.float32)
    return image, dmap, mask


def quantised(tree):
    return jax.tree.map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), tree)


class Recorder:
    """An ``ops`` that keeps what the convolutions and pools of one eager
    forward saw and returned."""

    def __init__(self, fold: bool):
        self.kernels, self.convs, self.pools = [], [], []
        self.ops = LocalOps(conv2d=self.conv2d, max_pool=self.max_pool,
                            max_pool_pairs=self.pool_pairs if fold else None)

    def conv2d(self, x, w, b=None, **kw):
        y = conv2d(x, w, b, **kw)
        self.kernels.append(tuple(w.shape))
        self.convs.append(y)
        return y

    def max_pool(self, x):
        self.pools.append(max_pool2d(x))
        return self.pools[-1]

    def pool_pairs(self, x):
        self.pools.append(max_pool2d_w_pairs(x))
        return self.pools[-1]


# -- the two primitives ----------------------------------------------------
@pytest.mark.parametrize("c,o", [(3, 64), (64, 64), (2, 5)])
def test_folded_kernel_places_six_blocks_and_exact_zeros(c, o):
    rng = np.random.default_rng(3)
    w = rng.standard_normal((3, 3, c, o)).astype(np.float32) + 3.0  # no zero
    b = rng.standard_normal((o,)).astype(np.float32)
    wp, bp = fold_w_pairs_kernel(jnp.asarray(w), jnp.asarray(b))
    wp = np.asarray(wp)
    assert wp.shape == (3, 3, 2 * c, 2 * o)
    want = np.zeros_like(wp)
    # output column dp of the pair, tap v: input column dp + v of the pair
    for dp in (0, 1):
        for v in (-1, 0, 1):
            fb, rb = divmod(dp + v, 2)
            want[:, fb + 1, rb * c:(rb + 1) * c, dp * o:(dp + 1) * o] = w[:, v + 1]
    np.testing.assert_array_equal(wp, want)   # placed, not computed
    assert np.count_nonzero(wp) == 6 * 3 * c * o      # of 12 x 3 blocks
    # the taps that would reach columns 2j-2 and 2j+3 do not exist
    assert not wp[:, 0, :c, :].any() and not wp[:, 0, c:, o:].any()
    assert not wp[:, 2, c:, :].any() and not wp[:, 2, :c, :o].any()
    np.testing.assert_array_equal(np.asarray(bp), np.tile(b, 2))
    assert fold_w_pairs_kernel(jnp.asarray(w))[1] is None


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_folded_conv_is_the_plain_conv_of_the_same_array(shape, dtype):
    n, h, w = shape
    rng = np.random.default_rng(4)
    x = quantised(jnp.asarray(rng.standard_normal((n, h, w, 64)), jnp.float32))
    k = quantised(jnp.asarray(rng.standard_normal((3, 3, 64, 64)) / 24, jnp.float32))
    b = quantised(jnp.asarray(rng.standard_normal((64,)) * 0.1, jnp.float32))
    want = conv2d(x, k, b)
    cast = lambda a: a.astype(dtype)
    kp, bp = fold_w_pairs_kernel(cast(k), cast(b))
    got = conv2d(fold_w_pairs(cast(x)), kp, bp)
    assert got.shape == (n, h, w // 2, 128) and got.dtype == jnp.dtype(dtype)
    assert rel_gap(got.reshape(n, h, w, 64), want) <= TOL[dtype]


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES + [(2, 9, 6)])   # odd H: floor
def test_folded_pool_is_the_plain_pool_forward_and_backward(shape, dtype):
    n, h, w = shape
    rng = np.random.default_rng(5)
    # few distinct values, so windows hold ties and the rule for them shows
    x = jnp.asarray(rng.integers(0, 4, (n, h, w, 64)), dtype)
    g = jnp.asarray(rng.standard_normal((n, h // 2, w // 2, 64)), dtype)
    want, vjp = jax.vjp(max_pool2d, x)
    got, vjp_f = jax.vjp(lambda a: max_pool2d_w_pairs(fold_w_pairs(a)), x)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    # every window hands its whole cotangent to one of its maxima
    (dx,), (dx_f,) = vjp(g), vjp_f(g)
    win = lambda a: np.asarray(a, np.float32)[:, :h // 2 * 2].reshape(
        n, h // 2, 2, w // 2, 2, 64).sum((2, 4))
    np.testing.assert_array_equal(win(dx_f), win(dx))
    hit = np.asarray(dx_f, np.float32) != 0
    top = np.repeat(np.repeat(np.asarray(want, np.float32), 2, 1), 2, 2)
    assert (np.asarray(x, np.float32)[:, :h // 2 * 2][hit[:, :h // 2 * 2]]
            == top[hit[:, :h // 2 * 2]]).all()


# -- the stage inside cannet_apply -----------------------------------------
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_stage1_and_the_whole_forward_equal_the_plain_path(shape, dtype):
    n, h, w = shape
    params = quantised(make_params())
    image = quantised(make_batch(shape)[0])
    kw = {} if dtype == "float32" else {"compute_dtype": jnp.bfloat16}
    plain, fold = Recorder(fold=False), Recorder(fold=True)
    want = cannet_apply(params, image, ops=plain.ops)
    got = cannet_apply(params, image, ops=fold.ops, **kw)
    assert plain.kernels[:2] == [(3, 3, 3, 64), (3, 3, 64, 64)]
    assert fold.kernels[:2] == [(3, 3, 6, 128), (3, 3, 128, 128)]
    assert fold.kernels[2:] == plain.kernels[2:]
    tol = TOL[dtype]
    for name, f, p in (("conv1_1", fold.convs[0], plain.convs[0]),
                       ("conv1_2", fold.convs[1], plain.convs[1])):
        assert f.shape == (n, h, w // 2, 128), name
        assert rel_gap(f.reshape(n, h, w, 64), p) <= tol, name
    # pool1 leaves the folded domain: the rest of the network is untouched
    assert fold.pools[0].shape == plain.pools[0].shape == (n, h // 2, w // 2, 64)
    assert rel_gap(fold.pools[0], plain.pools[0]) <= tol
    assert got.shape == want.shape == (n, h // 8, w // 8, 1)
    assert rel_gap(got, want) <= 5 * tol   # through 17 more layers


GRAD_DTYPES = [pytest.param("float64", id="f64"), pytest.param("float32", id="f32"),
               pytest.param("bfloat16", id="bf16")]


@pytest.mark.parametrize("dtype", GRAD_DTYPES)
@pytest.mark.parametrize("shape", SHAPES)
def test_gradients_reach_the_original_parameters(shape, dtype):
    """``jax.grad`` of the masked loss, every leaf.  float64 shows that the
    mathematics is the same (1e-12); float32 and bfloat16 are held to what
    the same rounding does to the PLAIN path: a pre-activation or a pooling
    window within an ulp of a tie may flip under another order of
    summation, and the flip moves a gradient by far more than an ulp."""
    with jax.enable_x64(dtype == "float64"):
        ref_dtype = jnp.float64 if dtype == "float64" else jnp.float32
        cast = lambda tree: jax.tree.map(lambda a: a.astype(ref_dtype), tree)
        params = cast(quantised(make_params()))
        image, dmap, mask = map(cast, make_batch(shape))
        image = cast(quantised(image))
        kw = {"compute_dtype": jnp.bfloat16} if dtype == "bfloat16" else {}

        def loss(p, ops, **kw):
            pred = cannet_apply(p, image, ops=ops, **kw).astype(ref_dtype)
            return jnp.sum(((pred - dmap) * mask) ** 2)

        def gaps(tree, want):
            return {jax.tree_util.keystr(path): float(
                jnp.linalg.norm(g - w) / (jnp.linalg.norm(w) + 1e-30))
                for (path, g), w in zip(
                    jax.tree_util.tree_leaves_with_path(tree),
                    jax.tree.leaves(want))}

        grad = lambda ops, **kw: jax.jit(
            lambda p: jax.grad(loss)(p, ops, **kw))(params)
        want = grad(PLAIN)
        got = grad(LocalOps(), **kw)
        assert jax.tree.structure(got) == jax.tree.structure(params)
        for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert g.shape == w.shape and g.dtype == w.dtype
        if dtype == "float64":
            limit = {k: 1e-12 for k in gaps(got, want)}
        elif dtype == "float32":
            limit = {k: 2e-2 for k in gaps(got, want)}   # a flip or two
        else:
            yard = gaps(grad(PLAIN, **kw), want)
            limit = {k: 1.5 * v + 2e-3 for k, v in yard.items()}
        for leaf, gap in gaps(got, want).items():
            assert gap <= limit[leaf], (leaf, gap, limit[leaf])
        for i in (0, 1):   # the fold's own kernels moved, not only the rest
            assert float(jnp.abs(got["frontend"][i]["w"]).max()) > 0


# -- who takes the plain path ----------------------------------------------
def test_batch_norm_parameters_take_the_plain_path():
    from can_tpu.models import init_batch_stats

    params = cannet_init(jax.random.key(0), batch_norm=True)
    image = make_batch((2, 16, 24))[0]
    assert stage1_layout(params, image.shape) == "plain"
    rec = Recorder(fold=True)
    cannet_apply(params, image, ops=rec.ops, train=False,
                 batch_stats=init_batch_stats(params))
    assert rec.kernels[:2] == [(3, 3, 3, 64), (3, 3, 64, 64)]
    assert stage1_traced(image.shape) == "plain"


def test_an_odd_width_takes_the_plain_path():
    params = make_params()
    image = jnp.ones((1, 16, 9, 3), jnp.float32)
    assert stage1_layout(params, image.shape) == "plain"
    rec = Recorder(fold=True)
    out = cannet_apply(params, image, ops=rec.ops)
    assert rec.kernels[0] == (3, 3, 3, 64) and out.shape == (1, 2, 1, 1)
    assert stage1_traced(image.shape) == "plain"


def test_an_ops_without_the_folded_pool_takes_the_plain_path():
    params = make_params()
    image = make_batch((1, 8, 8))[0]
    assert stage1_layout(params, image.shape) == "folded"
    assert stage1_layout(params, image.shape, PLAIN) == "plain"
    rec = Recorder(fold=False)
    cannet_apply(params, image, ops=rec.ops)
    assert rec.kernels[:2] == [(3, 3, 3, 64), (3, 3, 64, 64)]
    assert stage1_traced(image.shape) == "plain"
    cannet_apply(params, image)
    assert stage1_traced(image.shape) == "folded"


def test_a_stage_of_other_widths_takes_the_plain_path():
    params = make_params()
    params["frontend"][0]["w"] = params["frontend"][0]["w"][..., :32]
    assert stage1_layout(params, (1, 8, 8, 3)) == "plain"


def test_the_h_sharded_forward_folds_and_equals_the_unsharded_one():
    from jax.sharding import Mesh

    from can_tpu.parallel.mesh import DATA_AXIS, SPATIAL_AXIS
    from can_tpu.parallel.spatial import make_spatial_apply, make_spatial_ops

    assert make_spatial_ops(SPATIAL_AXIS, 2, (4, 3)).max_pool_pairs is max_pool2d_w_pairs
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), (DATA_AXIS, SPATIAL_AXIS))
    params = make_params()
    image = make_batch((2, 32, 24))[0]
    got = make_spatial_apply(mesh, (32, 24))(params, image)
    assert stage1_traced((2, 16, 24)) == "folded"   # the shard's own shape
    assert rel_gap(got, cannet_apply(params, image, ops=PLAIN)) <= 1e-5


# -- what the programs hold ------------------------------------------------
FULL_RES_64 = r"tensor<{n}x{h}x{w}x64x"


def _train_text(params, shape, ops):
    from can_tpu.train import create_train_state, make_optimizer
    from can_tpu.train.steps import make_train_step
    import functools

    n, h, w = shape
    opt = make_optimizer(1e-7)
    state = create_train_state(params, opt, None)
    step = make_train_step(functools.partial(cannet_apply, ops=ops), opt,
                           compute_dtype=jnp.bfloat16)
    batch = {"image": jnp.zeros((n, h, w, 3), jnp.float32),
             "dmap": jnp.zeros((n, h // 8, w // 8, 1), jnp.float32),
             "pixel_mask": jnp.ones((n, h // 8, w // 8, 1), jnp.float32),
             "sample_mask": jnp.ones((n,), jnp.float32)}
    return jax.jit(step).lower(state, batch).as_text()


@pytest.mark.parametrize("program", ["predict", "train_step"])
def test_no_full_resolution_64_channel_tensor_when_folded(program):
    """Shapes only, from the lowered text: forward and backward, a later
    change cannot quietly unfold the stage."""
    n, h, w = shape = (2, 32, 48)
    params = make_params()
    if program == "predict":
        from can_tpu.serve.programs import cannet_predict

        predict = cannet_predict("bf16", jnp.bfloat16)
        batch = {"image": jnp.zeros((n, h, w, 3), jnp.float32),
                 "dmap": jnp.zeros((n, h // 8, w // 8, 1), jnp.float32),
                 "pixel_mask": jnp.ones((n, h // 8, w // 8, 1), jnp.float32),
                 "sample_mask": jnp.ones((n,), jnp.float32)}
        text = jax.jit(predict).lower(params, batch, None).as_text()
        plain_text = jax.jit(
            lambda p, x: cannet_apply(p, x, ops=PLAIN,
                                      compute_dtype=jnp.bfloat16)
        ).lower(params, batch["image"]).as_text()
    else:
        text = _train_text(params, shape, LocalOps())
        plain_text = _train_text(params, shape, PLAIN)
    full = re.compile(FULL_RES_64.format(n=n, h=h, w=w))
    pooled_w = re.compile(FULL_RES_64.format(n=n, h=h, w=w // 2))
    assert full.search(plain_text)          # the pattern has teeth
    assert not full.search(text)
    assert not pooled_w.search(text)        # nor a half-unfolded one
    assert f"tensor<{n}x{h}x{w // 2}x128x" in text
    assert "tensor<3x3x128x128x" in text and "tensor<3x3x6x128x" in text
    assert f"tensor<{n}x{h // 2}x{w // 2}x64x" in text   # pool1's output


# -- the counter that says the mechanism engaged ---------------------------
def test_serving_stats_and_dispatch_spans_carry_stage1():
    from can_tpu.obs.spans import SpanTracer, install, uninstall
    from can_tpu.serve import CountService, ServeEngine

    tracer = install(SpanTracer(prefix="t"))
    try:
        engine = ServeEngine(make_params(), serve_dtype="bf16")
        svc = CountService(engine, bucket_ladder=((16,), (24,)), max_batch=2,
                           max_wait_ms=1.0)
        with svc:
            svc.predict(np.zeros((16, 24, 3), np.float32), timeout=120.0)
            svc.predict(np.zeros((16, 24, 3), np.float32), timeout=120.0)
            stats = svc.stats()
        spans = [s for s in tracer.snapshot() if s["name"] == "serve.dispatch"]
    finally:
        uninstall()
    assert stats["stage1"] and set(stats["stage1"].values()) == {"folded"}
    assert all(re.fullmatch(r"\d+x16x24:float32", k) for k in stats["stage1"])
    assert len(spans) >= 2 and {s["stage1"] for s in spans} == {"folded"}
    from can_tpu.obs.exporter import render_stats

    assert 'can_tpu_serve_stage1_folded{program="' in render_stats(stats)


def test_train_dispatch_spans_carry_stage1():
    from can_tpu.data.batching import Batch
    from can_tpu.obs.spans import SpanTracer, install, uninstall
    from can_tpu.train import (
        create_train_state,
        make_optimizer,
        train_one_epoch,
    )
    from can_tpu.train.steps import make_train_step

    opt = make_optimizer(1e-7)
    state = create_train_state(make_params(), opt, None)
    step = jax.jit(make_train_step(cannet_apply, opt))
    image, dmap, mask = make_batch((2, 16, 24))
    batches = [Batch(np.asarray(image), np.asarray(dmap), np.asarray(mask),
                     np.ones((2,), np.float32))] * 2
    put = lambda b: {"image": b.image, "dmap": b.dmap,
                     "pixel_mask": b.pixel_mask, "sample_mask": b.sample_mask}
    tracer = install(SpanTracer(prefix="t"))
    try:
        train_one_epoch(step, state, batches, put_fn=put, show_progress=False)
        spans = [s for s in tracer.snapshot() if s["name"] == "train.dispatch"]
    finally:
        uninstall()
    assert [s["stage1"] for s in spans] == ["folded", "folded"]
    assert {s["program"] for s in spans} == {"2x16x24"}
