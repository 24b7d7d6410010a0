"""Ask the chip's compiler before the chip: AOT-compile the main path's
kernels and programs, at their real widths, for a DESCRIBED v5e:2x2.

The TPU compiler is installed in the CPU-only sandbox and compiles for a
topology that is described, not attached (on-chip-measurement guide §2,
rehearsal 3).  It refuses what interpret mode and the CPU backend cannot
see — a slice not aligned to the tiling, a kernel over its VMEM budget, a
program over HBM — so these guard every later PR at no chip time.  Nothing
runs: a compile that passes is not a chip run and says nothing about
results or speed (``chip_smoke.py`` is the chip run).

``jax.default_backend()`` is still ``cpu`` during such a compile, so code
that branches on it would take its CPU branch: the kernels / jitted steps
are compiled directly, with ``interpret=False`` spelled out.
"""

import functools
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from can_tpu.models import cannet_apply, cannet_init, init_batch_stats
from can_tpu.parallel.mesh import DATA_AXIS, SPATIAL_AXIS
from can_tpu.train import create_train_state, make_lr_schedule, make_optimizer

V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e:2x2 — skipped where the
    topology cannot be described.  The persistent compile cache is off
    around these compiles: an entry written for a described chip cannot be
    read back without one, and the next run would warn and recompile."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _mesh(devices, dp, sp):
    return Mesh(np.asarray(devices).reshape(dp, sp), (DATA_AXIS, SPATIAL_AXIS))


def _batch(mesh, gb, h, w, *, spatial=False):
    big = NamedSharding(mesh, P(DATA_AXIS, SPATIAL_AXIS, None, None)
                        if spatial else P(DATA_AXIS))
    row = NamedSharding(mesh, P(DATA_AXIS))
    f32 = jnp.float32
    return {
        "image": jax.ShapeDtypeStruct((gb, h, w, 3), f32, sharding=big),
        "dmap": jax.ShapeDtypeStruct((gb, h // 8, w // 8, 1), f32,
                                     sharding=big),
        "pixel_mask": jax.ShapeDtypeStruct((gb, h // 8, w // 8, 1), f32,
                                           sharding=big),
        "sample_mask": jax.ShapeDtypeStruct((gb,), f32, sharding=row),
    }


def _state(mesh, opt, *, batch_norm=False):
    """Shapes only: ``device_put`` to a described device fails."""
    def make():
        params = cannet_init(jax.random.key(0), batch_norm=batch_norm)
        return create_train_state(
            params, opt, init_batch_stats(params) if batch_norm else None)

    repl = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=repl),
        jax.eval_shape(make))


def _fits_hbm(compiled):
    ma = compiled.memory_analysis()
    used = (ma.temp_size_in_bytes + ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert used < V5E_HBM_BYTES, f"{used / 2**30:.2f} GiB per device"
    return used


# the three real BN shapes the kernel's shape gate admits at b16 x 576x768
@pytest.mark.parametrize("shape", [(16, 288, 384, 128), (16, 144, 192, 256),
                                   (16, 72, 96, 512)])
def test_pallas_bn_moment_sums_compiles_to_a_tpu_kernel(v5e, shape):
    from can_tpu.ops import pallas_bn

    assert pallas_bn.supports(shape)
    one = SingleDeviceSharding(v5e[0])
    y = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one)
    m = jax.ShapeDtypeStruct(shape[:3] + (1,), jnp.float32, sharding=one)
    compiled = jax.jit(functools.partial(
        pallas_bn.moment_sums, interpret=False)).lower(y, m).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _fits_hbm(compiled)


def test_bf16_serve_predict_compiles_for_one_device(v5e):
    """The program ``cli.serve --serve-dtype bf16`` warms for a 576x768
    bucket at --max-batch 4 — the engine's own jitted predict, lowered for
    one described device."""
    from can_tpu.data.batching import pad_batch
    from can_tpu.obs.costs import resolve_jit
    from can_tpu.serve import ServeEngine
    from can_tpu.serve.engine import _batch_dict

    engine = ServeEngine(cannet_init(jax.random.key(0)), serve_dtype="bf16")
    batch = _batch_dict(pad_batch(
        [(np.zeros((576, 768, 3), np.float32),
          np.zeros((72, 96, 1), np.float32))], (576, 768), 4, [False], 8))
    one = SingleDeviceSharding(v5e[0])

    def described(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one), tree)

    args = (engine.params, batch, None)
    compiled = resolve_jit(engine._predict, args).lower(
        described(engine.params), described(batch), None).compile()
    assert "bf16" in compiled.as_text()
    _fits_hbm(compiled)


@pytest.mark.slow
def test_bf16_train_step_b16_fits_one_chip(v5e):
    """The driver's cell: ``make_dp_train_step`` b16 x 576x768 bf16 on one
    device (~45 s).  ~11 GB of temporaries on a 16 GB chip: it fits, and
    whatever else is resident matters."""
    from can_tpu.parallel import make_dp_train_step

    mesh = _mesh(v5e[:1], 1, 1)
    opt = make_optimizer(make_lr_schedule(1e-7))
    step = make_dp_train_step(cannet_apply, opt, mesh,
                              compute_dtype=jnp.bfloat16)
    compiled = step.lower(_state(mesh, opt),
                          _batch(mesh, 16, 576, 768)).compile()
    assert _fits_hbm(compiled) > 8 * 2**30  # not a toy program


@pytest.mark.slow
def test_dp2_sp2_train_step_compiles_on_the_2x2_mesh(v5e):
    """``--sp 2`` on four chips: the shard_map step with halo ``ppermute``
    and psum'd pooling, dp=2 x sp=2, global b16 x 576x768 bf16."""
    from can_tpu.parallel.spatial import make_sp_train_step

    mesh = _mesh(v5e, 2, 2)
    opt = make_optimizer(make_lr_schedule(1e-7, world_size=2))
    step = make_sp_train_step(opt, mesh, (576, 768),
                              compute_dtype=jnp.bfloat16)
    compiled = step.lower(_state(mesh, opt),
                          _batch(mesh, 16, 576, 768, spatial=True)).compile()
    text = compiled.as_text()
    assert "collective-permute" in text  # the halo exchange
    assert "all-reduce" in text          # gradient / pooling psums
    _fits_hbm(compiled)


def _cache_copies(compiled, programs, cache, positioned=True) -> dict:
    """{type of a cache array that has positions: how many times the
    compiled program copies such an array whole} (``obs.trace.cache_copies``
    over ``positioned_leaves``: summed, what ``LMEngine`` writes on its
    ``program.scopes`` span as ``cache_copies``; ``positioned`` False: over
    ``state_leaves``, the arrays WITHOUT positions, its ``state_copies``).  A
    donated cache written in place reads 0 throughout; each copy is a leaf
    moved into another layout or back."""
    from can_tpu.obs.trace import cache_copies, hlo_type
    from can_tpu.ops.cache_layout import positioned_leaves, state_leaves

    text = compiled.as_text()
    leaves = positioned_leaves if positioned else state_leaves
    return {hlo_type(a.shape, a.dtype): cache_copies(text, [a])
            for a in leaves(programs.cache_layout, cache)}


def _plain_write_slot(cache, new, slot):
    """``write_slot`` as it stood before PR 37."""
    return cache.at[jnp.arange(cache.shape[0]), :, slot].set(
        new.astype(cache.dtype))


@pytest.mark.parametrize("shape,heads,write,copies", [
    ((64, 8, 1280, 128), 8, None, 0), ((64, 8, 128, 128), 8, None, 0),
    ((64, 4, 1280, 128), 4, None, 0),
    ((64, 4, 1280, 128), 4, _plain_write_slot, 2),
    ((64, 4, 1280, 128), 8, None, 0), ((64, 8, 1280, 64), 8, None, 2),
], ids=["k-exaone-full", "k-exaone-ring", "falcon-h1-full",
        "falcon-h1-full-plain-indexed", "lfm2-full-packed",
        "lfm2-full-a-head-a-row"])
def test_write_slot_and_decode_leave_a_donated_cache_where_it_is(
        v5e, shape, heads, write, copies):
    """One row written into a donated leaf of the cells' shapes and the leaf
    read by ``decode``: the merged scatter compiles to no copy of the leaf;
    the plain indexed write it replaced, kept here as the record of the
    cause, to one copy into ``{3,1,2,0}`` and one back.  LFM2's ``heads``
    of 64 two to a row of 128 lanes (PR 39) likewise to none; a head a row,
    as they were stored before and kept here as the record of THAT cause,
    to one copy into ``{2,3,1,0}`` and one back whatever the write."""
    from can_tpu.obs.trace import cache_copies
    from can_tpu.ops import attention

    write = write or attention.write_slot
    b, kv, s, d = shape
    head = kv * d // heads
    one = SingleDeviceSharding(v5e[0])
    arr = lambda sh, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        sh, dt, sharding=one)

    def step(cache, q, new, slot):
        cache = write(cache, new, slot)
        return cache, attention.decode(q, cache, cache, jnp.ones((b, s), bool))

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        arr(shape), arr((b, heads, 2, head)), arr((b, heads, head)),
        arr((b,), jnp.int32)).compile()
    assert cache_copies(compiled.as_text(), [arr(shape)]) == copies
    assert compiled.memory_analysis().alias_size_in_bytes == b * kv * s * d * 2


# -- the language model's serving programs at the published widths --------
def _lm_programs_and_shapes(v5e, slots, part,
                            name="k-exaone-ep8-serve-bf16", edit=None):
    """``serve/programs.py::LMPrograms`` of the benchmark's configuration
    ``name`` (``edit``: a change to the file's dict first), its parameters,
    one launch's cache of ``slots`` and a prefill slice of ``part`` prompts,
    as shapes on one described device."""
    import json

    from can_tpu.serve.programs import LMPrograms

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs", name + ".json")) as f:
        config = json.load(f)
    if edit is not None:
        edit(config)
    if config["model_type"] == "exaone_moe":
        from can_tpu.models import exaone_moe as em

        cfg = em.ExaoneMoeConfig.from_dict(config)
    elif config["model_type"] == "falcon_h1":
        from can_tpu.models import falcon_h1 as em

        cfg = em.FalconH1Config.from_dict(config)
    elif config["model_type"] == "lfm2_moe":
        from can_tpu.models import lfm2_moe as em

        cfg = em.Lfm2MoeConfig.from_dict(config)
    elif config["model_type"] == "mimo_v2_flash":
        from can_tpu.models import mimo_v2_flash as em

        cfg = em.MimoV2FlashConfig.from_dict(config)
    elif config["model_type"] == "brumby":
        from can_tpu.models import brumby as em

        cfg = em.BrumbyConfig.from_dict(config)
    elif config["model_type"] == "longcat_flash":
        from can_tpu.models import longcat_flash as em

        cfg = em.LongcatFlashConfig.from_dict(config)
    else:
        from can_tpu.models import glm_moe_lite as em

        cfg = em.Glm4MoeLiteConfig.from_dict(config)
    programs = LMPrograms(em, cfg, max_new_tokens=int(config["max_new_tokens"]))
    one = SingleDeviceSharding(v5e[0])
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        em.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    params = jax.tree_util.tree_unflatten(treedef, [
        shape(s, jnp.float32 if path[-1].key == "bias" else jnp.bfloat16)
        for path, s in flat])
    bucket = int(config["length_ladder"][-1])
    cache = jax.tree.map(lambda a: shape(a.shape, a.dtype),
                         jax.eval_shape(lambda: programs.new_cache(slots, bucket)))
    batch = {"tokens": shape((part, bucket), jnp.int32),
             "lengths": shape((part,), jnp.int32),
             "active": shape((part,), jnp.bool_)}
    return programs, params, cache, batch, shape


def test_lm_decode_step_compiles_for_one_device(v5e):
    """One greedy step of 64 slots at K-EXAONE's published widths (16 of 128
    experts held): the few-token form of the grouped product (every held
    expert on every token, no grouped kernel), the cache updated in place
    (donated), and weights + cache + temporaries fit."""
    programs, params, cache, _, shape = _lm_programs_and_shapes(v5e, 64, 8)
    state = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: programs.new_state(
            [{"first": jnp.zeros((64,), jnp.int32),
              "logits": jnp.zeros((64, 8), jnp.float32),
              "choices": jnp.zeros((4, 64, 8), jnp.int32),
              "counts": jnp.zeros((4, 16), jnp.int32)}],
            jnp.ones((64,), jnp.int32), jnp.ones((64,), bool))[0]))
    compiled = jax.jit(programs.decode, donate_argnums=(1, 2)).lower(
        params, state, cache).compile()
    assert "ragged-dot" not in compiled.as_text()
    # ``write_slot`` writes in place: neither the full layer's cache nor a
    # window layer's ring is copied (a copy of either is 0.13-0.5 ms a step)
    assert _cache_copies(compiled, programs, cache) == {
        "bf16[64,8,1280,128]": 0, "bf16[64,8,128,128]": 0}
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 64 * 8 * (1280 + 4 * 128) * 128 * 2 * 2
    assert _fits_hbm(compiled) > 7 * 2**30   # the weights alone are 7.4 GB


def test_lm_prefill_slice_compiles_for_one_device(v5e):
    """8 prompts of 1,024 tokens into a 64-slot cache: the sorted buffer
    holds twice the even share, 16,384 rows (``moe.sorted_rows``; every
    assignment that can land here would be 65,536), one loop over its passes
    an expert layer, beside the weights."""
    programs, params, cache, batch, shape = _lm_programs_and_shapes(v5e, 64, 8)
    compiled = jax.jit(programs.prefill_slice, donate_argnums=(2,)).lower(
        params, batch, cache, shape((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text
    assert "bf16[16384,6144]" in text and "bf16[65536,6144]" not in text
    assert _dispatch_loops(programs, text) == 4
    assert 8.5 * 2**30 < _fits_hbm(compiled) < 9.5 * 2**30


# -- the latent-attention model at the published widths -------------------
GLM = "glm-4.7-flash-pp8-serve-bf16"


def _glm_step_state(programs, shape):
    """The decode state of a launch of 16 slots after its prefill, as shapes
    on the described device."""
    return jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: programs.new_state(
            [{"first": jnp.zeros((16,), jnp.int32),
              "logits": jnp.zeros((16, 8), jnp.float32),
              "choices": jnp.zeros((5, 16, 4), jnp.int32),
              "counts": jnp.zeros((5, 64), jnp.int32)}],
            jnp.ones((16,), jnp.int32), jnp.ones((16,), bool))[0]))


def test_glm_decode_step_compiles_for_one_device(v5e, monkeypatch):
    """One greedy step of 16 slots over a latent cache of 16,512 positions
    as the chip traces it (``supports`` and ``write_row`` ask the backend,
    which is the CPU's during a compile for a described chip: steered here;
    the absorbed form: no per-head keys anywhere in the program), the cache
    updated in place (donated): 7.79 GB of weights + 1.83 GB of cache.  The
    attention is the fused kernel, six launches that read both leaves WHERE
    THEY LIE: the latent from the scatter that wrote its row in place, the
    rotary keys as a bitcast of the leaf in the layout it arrives in
    (positions minor), written a ``dynamic_update_slice`` a slot; no score
    array is left in HBM."""
    import re

    from can_tpu.models import glm_moe_lite as gm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    programs, params, cache, _, shape = _lm_programs_and_shapes(v5e, 16, 2, GLM)
    state = _glm_step_state(programs, shape)
    compiled = jax.jit(programs.decode, donate_argnums=(1, 2)).lower(
        params, state, cache).compile()
    assert gm.latent_traced((16, 1)) == "fused"
    text = compiled.as_text()
    assert "ragged-dot" not in text
    assert "bf16[16,16512,512]" in text          # the latent, as stored
    assert "bf16[16,20,16512,192]" not in text   # no key rebuilt per head
    assert "f32[16,20,16512]" not in text        # no score array in HBM
    assert "f32[16,32,16512]" not in text
    # ``write_row`` writes both leaves in place (until PR 49 the rotary
    # keys' leaf, 64 wide, was re-laid on the way in and out of a scatter,
    # twice a layer: 12 copies, 1.78 ms of a 13.05 ms step)
    assert _cache_copies(compiled, programs, cache) == {
        "bf16[16,16512,512]": 0, "bf16[16,16512,64]": 0}
    # the kernel's operands: the latent from the instruction that wrote its
    # row (a scatter fusion on the parameter's own buffer), the rotary keys
    # a bitcast of the leaf after its sixteen updates; never a copy
    launches = _kernel_operands(text, "fused_latent_decode")
    assert [made[-2:] for made in launches] == [["fusion", "bitcast"]] * 6
    assert re.search(r"bf16\[16,64,16512\]\{2,1,0[^}]*\} bitcast\(", text)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 16 * 16512 * 6912
    # (8.98 GiB: without the scores and the re-laid leaves the step's
    # temporaries are 0.1 GB less than the plain form's)
    assert 8.9 * 2**30 < _fits_hbm(compiled) < 9 * 2**30
    _parts_of_the_compiled(programs, text, "fused_latent_decode", "attn.core",
                           6)


def test_glm_decode_step_in_the_plain_form_keeps_its_known_copies(v5e):
    """The same step where ``supports`` says no (the backend is the CPU's
    here and nothing steers it): ``decode_latent``'s two products around
    float32 scores in HBM and ``write_row`` as a scatter, as until PR 49: the
    rotary keys' leaf arrives with the positions minor and the scatter re-lays
    it on the way in and out, twice a layer.  What the kernel's path has to
    stay clear of, pinned so that a change to either form shows."""
    from can_tpu.models import glm_moe_lite as gm

    programs, params, cache, _, shape = _lm_programs_and_shapes(v5e, 16, 2, GLM)
    state = _glm_step_state(programs, shape)
    compiled = jax.jit(programs.decode, donate_argnums=(1, 2)).lower(
        params, state, cache).compile()
    assert gm.latent_traced((16, 1)) == "plain"
    text = compiled.as_text()
    assert "fused_latent_decode" not in text and "f32[16,20,16512]" in text
    assert _cache_copies(compiled, programs, cache) == {
        "bf16[16,16512,512]": 0, "bf16[16,16512,64]": 12}
    assert _fits_hbm(compiled) > 9 * 2**30


def test_glm_prefill_slice_compiles_for_one_device(v5e):
    """2 prompts of 16,384 tokens into a 16-slot latent cache: the expanded
    form in blocks with a running softmax (ONE loop body a layer, not 64
    shapes), the sorted buffer of 131,072 rows, all beside the weights."""
    programs, params, cache, batch, shape = _lm_programs_and_shapes(
        v5e, 16, 2, GLM)
    compiled = jax.jit(programs.prefill_slice, donate_argnums=(2,)).lower(
        params, batch, cache, shape((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "ragged-dot" in text
    assert "f32[20,1024,1024]" in text           # a score block
    assert "f32[20,1024,16384]" not in text and "f32[2,20,16384,16384]" not in text
    # every expert is held: the bound is the whole buffer, one pass, no loop
    # of the sorted form's (the scanned attention's loops are ``attn.core``'s)
    assert "bf16[131072,2048]" in text
    assert _dispatch_loops(programs, text) == 0
    assert 10 * 2**30 < _fits_hbm(compiled) < 14 * 2**30


# -- the skipping experts kernel (ops/pallas_experts.py) ---------------------
@pytest.mark.parametrize("tokens,held,d,f", [(16, 64, 2048, 1536),
                                             (1, 64, 2048, 1536),
                                             (64, 16, 6144, 2048)],
                         ids=["glm-16", "glm-1", "k-exaone-64"])
def test_skipping_experts_kernel_compiles_at_published_widths(v5e, tokens,
                                                              held, d, f):
    """A decode step's rows through one layer's held experts, the weights
    in tiles of 512 of their ``f`` (three blocks of 2 to 6 MB, twice)."""
    from can_tpu.ops import pallas_experts

    one = SingleDeviceSharding(v5e[0])
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one)  # noqa: E731
    experts = {"gate": shape((held, d, f), jnp.bfloat16),
               "up": shape((held, d, f), jnp.bfloat16),
               "down": shape((held, f, d), jnp.bfloat16)}
    compiled = jax.jit(pallas_experts.skipping_experts).lower(
        shape((tokens, d), jnp.bfloat16), shape((tokens, held), jnp.float32),
        shape((held,), jnp.int32), shape((), jnp.int32), experts).compile()
    assert "skipping_experts" in compiled.as_text()


def test_glm_decode_step_compiles_with_the_skipping_experts(v5e, monkeypatch):
    """The GLM cell's decode step as the chip traces it (``supports`` asks
    the backend, which is the CPU's during a compile for a described chip:
    steered here): five kernel launches, none of the batched form's
    products, the counter in the state, and the experts' weights handed to
    the kernel AS STORED: the program's own parameters, never a copy or a
    transpose of a (64, 2048, 1536) or (64, 1536, 2048) operand."""
    import re

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    programs, params, cache, _, shape = _lm_programs_and_shapes(v5e, 16, 2, GLM)
    assert programs.decode_experts(16) == "skipping"
    state = _glm_step_state(programs, shape)
    assert state["experts_read"].shape == ()
    compiled = jax.jit(programs.decode, donate_argnums=(1, 2)).lower(
        params, state, cache).compile()
    text = compiled.as_text()
    calls = re.findall(r"%skipping_experts[.\d]* = \S+ custom-call\(([^)]*)\)",
                       text)
    assert len(calls) == 5
    assert "bf16[64,16,1536]" not in text         # no product over all 64
    entry = text[text.index("ENTRY"):]
    for operands in calls:
        weights = re.sub(r"/\*.*?\*/", "", operands).split(", ")[-3:]  # gate, up, down
        for name in weights:
            made = re.search(rf"^\s*{re.escape(name)} = (\S+) (\S+?)\(", entry,
                             re.M)
            assert made and made.group(2) == "parameter", (name, made)
            assert re.match(r"bf16\[64,(2048,1536|1536,2048)\]", made.group(1))
    for line in entry.splitlines():
        if re.search(r"= bf16\[64,(2048,1536|1536,2048)\]", line):
            assert " parameter(" in line, line    # nothing else has the shape
    assert _fits_hbm(compiled) > 8.9 * 2**30
    _parts_of_the_compiled(programs, text, "skipping_experts", "moe.experts", 5)


def _parts_of_the_compiled(programs, text, kernel, part, launches):
    """The chip's own compiled text, read as ``LMEngine`` reads it for its
    ``program.scopes`` span: the kernel's launches belong to ``part``, every
    part is of the vocabulary, and next to nothing is left without one (over
    half of the instructions are the compiler's own prefetches and copies,
    which carry no metadata and take their user's part: read at PR 35, 2 of
    954 and 2 of 1,341 stay without)."""
    from can_tpu.models.lm_blocks import PARTS
    from can_tpu.obs.trace import program_scopes

    got = program_scopes(text, programs.parts)
    found = got["parts"]
    mine = {i: p for i, p in found.items() if i.startswith(kernel)}
    assert len(mine) == launches and set(mine.values()) == {part}, mine
    assert set(found.values()) <= set(PARTS) | {None}
    assert got["unscoped"] <= 0.01 * got["instructions"], got["unscoped"]
    assert 0.4 < len(got["inherited"]) / got["instructions"] < 0.75
    return found


def _dispatch_loops(programs, text) -> int:
    """The sorted expert form's loops over its buffer in a compiled prefill
    slice (``ops/moe.py::_sorted_in_passes``): -> how many ``while``
    instructions the part ``moe.dispatch`` owns.  Every op of their bodies
    and conditions belongs to ``moe.dispatch`` or ``moe.experts``; the inner
    ``jit`` that traces the form once a program left no ``call`` behind; and
    next to nothing of the program is left without a part
    (``prefill_unscoped_pct.lm`` stays under 0.1)."""
    import re

    from can_tpu.obs.trace import _instructions, part_of, program_scopes

    got = program_scopes(text, programs.parts)
    assert " call(" not in text
    assert got["unscoped"] <= 0.005 * got["instructions"], got["unscoped"]
    loops = [m for m in re.finditer(
        r" while\(.*?condition=%([\w.\-]+), body=%([\w.\-]+).*?op_name=\"([^\"]*)\"",
        text) if part_of(m.group(3), programs.parts) == "moe.dispatch"]
    inside = {name for m in loops for name in m.group(1, 2)}
    ops = [got["parts"][inst] for comp, inst, *_ in _instructions(text)[0]
           if comp in inside and inst in got["parts"]]
    assert set(ops) <= {"moe.dispatch", "moe.experts"}, set(ops)
    assert not loops or ops.count("moe.experts") >= 3 * len(loops)
    return len(loops)


# -- the state-space hybrid at the published widths -------------------------
FALCON = "falcon-h1-34b-pp12-serve-bf16"


def test_falcon_h1_decode_step_compiles_for_one_device(v5e):
    """One greedy step of 64 slots: keys and values AND the float32
    recurrent state (64 x 6 x 32 x 128 x 256) updated in place (both
    donated), beside 10.5 GB of weights."""
    programs, params, cache, _, shape = _lm_programs_and_shapes(
        v5e, 64, 8, FALCON)
    state = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: programs.new_state(
            [{"first": jnp.zeros((64,), jnp.int32),
              "logits": jnp.zeros((64, 8), jnp.float32),
              "choices": jnp.zeros((0, 64, 0), jnp.int32),
              "counts": jnp.zeros((0, 0), jnp.int32)}],
            jnp.ones((64,), jnp.int32), jnp.ones((64,), bool))[0]))
    compiled = jax.jit(programs.decode, donate_argnums=(1, 2)).lower(
        params, state, cache).compile()
    assert "f32[64,32,128,256]" in compiled.as_text()    # the state, as stored
    # keys and values written in place (a copy of one is 0.12-0.26 ms a
    # step); the state's leaves, rewritten whole, are not counted
    assert _cache_copies(compiled, programs, cache) == {
        "bf16[64,4,1280,128]": 0}
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 64 * (25_350_144 + 1280 * 12_288)
    assert 12 * 2**30 < _fits_hbm(compiled) < 15 * 2**30


def test_falcon_h1_prefill_slice_compiles_for_one_device(v5e):
    """8 prompts of 1,024 tokens into a 64-slot cache: the recurrence in
    its chunked form (decay matrices of 128 x 128 a chunk and head, eight
    carried states a sequence; no loop over 1,024 positions), the MLP's
    21,504-wide intermediates, all beside the weights and the cache."""
    programs, params, cache, batch, shape = _lm_programs_and_shapes(
        v5e, 64, 8, FALCON)
    compiled = jax.jit(programs.prefill_slice, donate_argnums=(2,)).lower(
        params, batch, cache, shape((), jnp.int32)).compile()
    text = compiled.as_text()
    assert "128,128]" in text                     # a chunk's decay matrix
    assert "trip_count=1024" not in text and '"n":"1024"' not in text
    assert 12 * 2**30 < _fits_hbm(compiled) < 15.5 * 2**30


# -- the short-convolution / attention model at the published widths --------
LFM2 = "lfm2-24b-a2b-ep8-serve-bf16"


def _lfm2_depth(layers):
    """The cell's file cut to its first ``layers`` layers (6: both dense
    layers and one period ``A c c c``): a compile's size, not a cell."""
    def edit(config):
        config["num_hidden_layers"] = layers
        config["layer_types"] = config["layer_types"][:layers]
    return edit


def _lfm2_programs(v5e, layers):
    """-> (programs, decode's compiled program, prefill slice's, the cache's
    shapes) at the published widths, 64 slots, slices of 8."""
    programs, params, cache, batch, shape = _lm_programs_and_shapes(
        v5e, 64, 8, LFM2, _lfm2_depth(layers))
    sparse = layers - 2
    state = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: programs.new_state(
            [{"first": jnp.zeros((64,), jnp.int32),
              "logits": jnp.zeros((64, 8), jnp.float32),
              "choices": jnp.zeros((sparse, 64, 4), jnp.int32),
              "counts": jnp.zeros((sparse, 8), jnp.int32)}],
            jnp.ones((64,), jnp.int32), jnp.ones((64,), bool))[0]))
    decode = jax.jit(programs.decode, donate_argnums=(1, 2)).lower(
        params, state, cache).compile()
    prefill = jax.jit(programs.prefill_slice, donate_argnums=(2,)).lower(
        params, batch, cache, shape((), jnp.int32)).compile()
    return programs, decode, prefill, cache


def _lfm2_checks(programs, decode, prefill, cache, layers, through_vmem=0):
    """What holds at any depth: the forms, the tails as stored, and the
    keys and values of the 64-wide heads two to a row of 128 lanes
    (``cache_layout.kv_pack``, PR 39), written in place by a decode step and
    by a prefill slice: a head a row, the step kept 2 whole-leaf copies a
    leaf (into ``{2,3,1,0}`` and back), 4 an attention layer (the toy case
    ``lfm2-full-a-head-a-row`` above is the record).  ``through_vmem``: the
    leaves the compiler's own memory assignment still moves whole, a NAMED
    DEBT of another kind that ``cache_copies`` does not count (PERF.md
    section 7): sliced into the chip's fast memory (``S(1)``), written and
    read there, and sent back to HBM by one ``copy-start`` of the merged
    leaf ``bf16[256,1280,128]``."""
    attention = len([s for s in programs.cache_layout if s.kind == "full"])
    text = decode.as_text()
    assert "ragged-dot" not in text and "ragged-dot" in prefill.as_text()
    assert "bf16[64,2048,2]" in text                 # a tail, as stored
    assert _cache_copies(decode, programs, cache) == {"bf16[64,4,1280,128]": 0}
    assert _cache_copies(prefill, programs, cache) == {
        "bf16[64,4,1280,128]": 0}
    assert len([line for line in text.splitlines() if " copy-start(" in line
                and "= (bf16[256,1280,128]" in line]) == through_vmem
    held = 64 * (1280 * 2048 * attention + 8192 * (layers - attention))
    assert decode.memory_analysis().alias_size_in_bytes >= held
    # the sorted form's buffer: twice the even share of a slice's 8,192
    # tokens' top-4 at an eighth, 8,192 rows (every assignment that can land
    # here would be 32,768), and one loop over its passes an expert layer
    text = prefill.as_text()
    assert "bf16[8192,1536]" in text and "bf16[32768,1536]" not in text
    assert _dispatch_loops(programs, text) == layers - 2


def test_lfm2_decode_and_prefill_compile_on_a_six_layer_pattern(v5e,
                                                                monkeypatch):
    """Both dense layers and one period (``c c A c c c``) at the published
    widths, 8 of 64 experts held: decode's experts batched, the prefill's
    sorted, the attention layer's keys and values written where they lie.
    **The inner ``jit`` of the sorted form changes what the host does and
    next to nothing the chip does:** the same slice compiled with the
    function traced in line, layer by layer, runs opcode for opcode the same
    instructions but for ONE small fusion an expert layer (the scatters'
    index clamp over ``s32[32768,1]``, one multi-output fusion in line, two
    fusions and a reshape behind the call)."""
    import collections

    from can_tpu.obs.trace import _NO_OP, _instructions
    from can_tpu.ops import moe as moe_ops

    programs, decode, prefill, cache = _lfm2_programs(v5e, 6)
    _lfm2_checks(programs, decode, prefill, cache, 6)
    assert _fits_hbm(decode) < 2 * 2**30 and _fits_hbm(prefill) < 2.5 * 2**30
    monkeypatch.setattr(moe_ops, "_sorted_in_passes",
                        moe_ops._sorted_in_passes.__wrapped__)
    programs, params, cache, batch, shape = _lm_programs_and_shapes(
        v5e, 64, 8, LFM2, _lfm2_depth(6))
    lowered = jax.jit(programs.prefill_slice, donate_argnums=(2,)).lower(
        params, batch, cache, shape((), jnp.int32))
    assert "_sorted_in_passes" not in lowered.as_text()

    def ran(text):
        rows, inlined = _instructions(text)
        return collections.Counter(opcode for comp, _, opcode, *_ in rows
                                   if comp not in inlined and opcode not in _NO_OP)

    called, in_line = ran(prefill.as_text()), ran(lowered.compile().as_text())
    assert {op for op in called | in_line if called[op] != in_line[op]} <= {
        "fusion", "reshape"}
    assert 0 <= called["fusion"] - in_line["fusion"] <= 4   # 4 expert layers


@pytest.mark.slow
def test_lfm2_decode_and_prefill_compile_at_full_depth(v5e):
    """All 40 layers, as the cell runs them (45 s here): 9.2 GB of weights
    and cache as arguments, 0.3 / 1.8 GB of temporaries (0.8 in the prefill
    before PR 43: around the 38 loops the scheduler leaves the 30 mixers'
    tail gathers to the program's end, and each holds its layer's ``u``,
    33.5 MB, until then), no copy of a cache leaf in either program (40 a
    decode step before PR 39); the keys of nine attention layers pass
    through the fast memory and back; 38 loops of the sorted form, one an
    expert layer."""
    programs, decode, prefill, cache = _lfm2_programs(v5e, 40)
    _lfm2_checks(programs, decode, prefill, cache, 40, through_vmem=9)
    assert 9e9 < _fits_hbm(decode) < _fits_hbm(prefill) < 11.5e9


# -- MiMo-V2-Flash: two kinds of attention layer, keys wider than values -----
MIMO = "mimo-v2-flash-ep16-serve-bf16"


def _mimo_programs(v5e, monkeypatch):
    """-> (programs, decode's compiled program, prefill slice's, the cache's
    shapes) at the cell's widths: all 7 held layers, 16 slots of 8,448
    positions, slices of 4, as a TPU traces them: decode's experts in the
    skipping form and the full layers' prefill in the fused kernel (both
    ``supports`` ask the backend, which is the CPU's during a compile for a
    described chip: steered here)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    programs, params, cache, batch, shape = _lm_programs_and_shapes(
        v5e, 16, 4, MIMO)
    assert programs.decode_experts(16) == "skipping"
    state = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: programs.new_state(
            [{"first": jnp.zeros((16,), jnp.int32),
              "logits": jnp.zeros((16, 8), jnp.float32),
              "choices": jnp.zeros((6, 16, 8), jnp.int32),
              "counts": jnp.zeros((6, 16), jnp.int32)}],
            jnp.ones((16,), jnp.int32), jnp.ones((16,), bool))[0]))
    decode = jax.jit(programs.decode, donate_argnums=(1, 2)).lower(
        params, state, cache).compile()
    prefill = jax.jit(programs.prefill_slice, donate_argnums=(2,)).lower(
        params, batch, cache, shape((), jnp.int32)).compile()
    assert programs.attention_traced((4, 8192)) == "fused"
    return programs, decode, prefill, cache


# every positioned leaf of the cell's cache: a full layer's keys (4 heads,
# two to a row of 384 lanes) and values (a head a row of 128), a window
# layer's ring of 128 (8 heads)
MIMO_LEAVES = ("bf16[16,2,8448,384]", "bf16[16,4,8448,128]",
               "bf16[16,4,128,384]", "bf16[16,8,128,128]")


def _kernel_operands(text, kernel="fused_causal_attention"):
    """Per launch of ``kernel`` in a compiled program's text: the op that
    made each of its array operands (the scalar prefetch left out)."""
    import re

    calls = re.findall(rf"%{kernel}[.\d]* = \S+ custom-call\(([^)]*)\)", text)
    return [[re.search(rf"^\s*{re.escape(name)} = \S+ (\S+?)\(", text,
                       re.M).group(1) for name in operands.split(", ")[1:]]
            for operands in calls]


def test_mimo_decode_and_prefill_compile_for_one_device(v5e, monkeypatch):
    """The cell's two programs at the published widths and all 7 held layers
    (2 full, 5 window; 16 of 256 experts held): both fit, decode's experts
    skip, the prefill's are sorted, **the full layers' prefill is two
    launches of the fused kernel** (64 query heads of 192 over 4 key heads,
    values 128: no scanned loop is left, the only ``while``s are the sorted
    expert form's six, and no float32 score block of the full layers goes to
    HBM), **q, k and v reach the kernel by bitcast** (the rotary part is the
    first 64 of 192 and XLA keeps the positions minor all the same, as in
    GLM's program: no transposing copy of 805 MB a slice and layer), and **no
    leaf of the cache is copied whole in either program**: keys 192 wide two
    heads to a row of 384 lanes are written where they lie, as values of 128
    are.  **The prefill slice's memory:** 11.92 GB, 4.32 of them temporaries,
    as with the scanned form (14.47 and 6.87 before PR 43, when the sorted
    expert form's buffer held ``T x 8`` = 262,144 rows of 4,096 a layer for
    16,384 in use).  The buffer now holds twice the even share, 32,768 rows
    (``moe.sorted_rows``), with one loop over its passes an expert layer, and
    what sets the peak is a window layer's attention
    (``f32[4,64,8,8,128,256]``, 2.1 GB of scores with their mask and their
    bfloat16 copy), neither an expert layer nor a full layer."""
    programs, decode, prefill, cache = _mimo_programs(v5e, monkeypatch)
    text = decode.as_text()
    assert "ragged-dot" not in text and "ragged-dot" in prefill.as_text()
    assert "skipping_experts" in text
    assert _cache_copies(decode, programs, cache) == dict.fromkeys(MIMO_LEAVES, 0)
    assert _cache_copies(prefill, programs, cache) == dict.fromkeys(
        MIMO_LEAVES, 0)
    # the cache is donated and handed back: 16 x (8,448 x 5,120 + 3,276,800) B
    assert decode.memory_analysis().alias_size_in_bytes >= 744_488_960
    assert 7.5e9 < _fits_hbm(decode) < 8.5e9
    assert 11.5e9 < _fits_hbm(prefill) < 12.4e9
    assert prefill.memory_analysis().temp_size_in_bytes < 4.5e9
    text = prefill.as_text()
    assert "bf16[32768,2048]" in text and "bf16[262144," not in text
    assert _dispatch_loops(programs, text) == 6 == text.count(" while(")
    assert "f32[64,1024,1024]" not in text       # the scanned form's score block
    assert _kernel_operands(text) == [["bitcast"] * 3] * 2
    _parts_of_the_compiled(programs, text, "fused_causal_attention",
                           "attn.core", 2)


# -- the fused prefill attention (ops/pallas_attention.py) ------------------
@pytest.mark.parametrize("q,kv,blocks", [
    ((2, 16384, 20, 256), (20, 256), (1024, 1024)),
    ((2, 16384, 20, 256), (20, 256), (512, 1024)),
    ((4, 8192, 64, 192), (4, 128), (1024, 1024)),
    ((4, 8192, 64, 192), (4, 128), (512, 1024)),
    ((4, 8192, 64, 192), (4, 128), (1024, 512)),
    # the narrowest and a wide key ``supports`` admits in bfloat16: one
    # sublane tile, and two lane rows and a half
    ((2, 2048, 8, 16), (2, 128), (1024, 1024)),
    ((2, 2048, 8, 320), (2, 128), (1024, 1024)),
], ids=["1024x1024", "512x1024", "mimo-1024x1024", "mimo-512x1024",
        "mimo-1024x512", "keys-16", "keys-320"])
def test_fused_attention_kernel_compiles_at_the_cell_s_shape(v5e, q, kv, blocks):
    """A slice of the GLM cell: 2 x 16,384 positions x 20 heads of 256,
    a head's keys and values whole in VMEM (32 MB, twice); and of MiMo's:
    4 x 8,192 x 64 query heads of 192 over 4 key heads, values of 128 (a
    head's rows are no whole number of lanes once turned: Mosaic takes the
    contraction over 192 as it stands).  What ``supports`` admits compiles."""
    from can_tpu.ops import pallas_attention as fused_attn

    one = SingleDeviceSharding(v5e[0])
    b, l, _, d = q
    assert fused_attn.supports(q, (b, l) + kv, jnp.bfloat16, block_q=blocks[0],
                               block_k=blocks[1], interpret=True)

    def arr(*s):
        return jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one)

    n = jax.ShapeDtypeStruct((b,), jnp.int32, sharding=one)
    compiled = jax.jit(lambda q, k, v, n: fused_attn.fused_causal(
        q, k, v, n, block_q=blocks[0], block_k=blocks[1])).lower(
            arr(*q), arr(b, l, kv[0], d), arr(b, l, *kv), n).compile()
    assert "fused_causal_attention" in compiled.as_text()


def test_glm_prefill_slice_compiles_with_the_fused_attention(v5e, monkeypatch):
    """The same slice as the chip traces it (``supports`` asks the backend,
    which is the CPU's during a compile for a described chip: steered
    here): six kernel launches and no loop, no score block in HBM, and
    q, k, v handed to the kernel as XLA leaves them (positions in the
    lanes): a bitcast, never a transposing copy."""
    from can_tpu.models import glm_moe_lite as gm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    programs, params, cache, batch, shape = _lm_programs_and_shapes(
        v5e, 16, 2, GLM)
    compiled = jax.jit(programs.prefill_slice, donate_argnums=(2,)).lower(
        params, batch, cache, shape((), jnp.int32)).compile()
    assert gm.attention_traced((2, 16384)) == "fused"
    text = compiled.as_text()
    assert " while(" not in text and "f32[20,1024,1024]" not in text
    assert _kernel_operands(text) == [["bitcast"] * 3] * 6
    assert 9 * 2**30 < _fits_hbm(compiled) < 13 * 2**30
    found = _parts_of_the_compiled(programs, text, "fused_causal_attention",
                                   "attn.core", 6)
    # XLA:TPU stamps its grouped-matmul kernels' op_name anew: the scope is
    # lost and ``lm_blocks.RENAMED_BY_COMPILER`` gives it back
    assert {p for i, p in found.items() if i.startswith("ragged-dot")} == {
        "moe.experts"}
    assert "moe.dispatch" in found.values()


# -- power retention at the published widths ------------------------------------
BRUMBY = "brumby-14b-pp5-serve-bf16"


def _brumby_parts(programs, text) -> set:
    """The parts the compiled program's instructions belong to, as
    ``LMEngine`` reads them for its ``program.scopes`` span; all of the
    vocabulary, next to nothing without one."""
    from can_tpu.models.lm_blocks import PARTS
    from can_tpu.obs.trace import program_scopes

    got = program_scopes(text, programs.parts)
    assert set(got["parts"].values()) <= set(PARTS) | {None}
    assert got["unscoped"] <= 0.01 * got["instructions"], got["unscoped"]
    return set(got["parts"].values())


def test_brumby_decode_step_writes_its_state_where_it_lies(v5e, monkeypatch):
    """One greedy step of 16 slots: every layer's float32 matrix state (16 x
    8 x 128 x 8,320: 545 MB a layer, 4.4 GB in all) read, moved on and
    written IN PLACE (donated) by ONE launch a layer of the fused kernel
    (``ops/pallas_retention.py``, PR 48): no whole copy of a state leaf, no
    second state alive, no XLA fusion the size of a layer's state (the update
    and the query were two until PR 48), beside 8.4 GB of weights."""
    import re

    from can_tpu.models import brumby as bm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    programs, params, cache, _, shape = _lm_programs_and_shapes(
        v5e, 16, 4, BRUMBY)
    state = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: programs.new_state(
            [{"first": jnp.zeros((16,), jnp.int32),
              "logits": jnp.zeros((16, 8), jnp.float32),
              "choices": jnp.zeros((0, 16, 0), jnp.int32),
              "counts": jnp.zeros((0, 0), jnp.int32)}],
            jnp.ones((16,), jnp.int32), jnp.ones((16,), bool))[0]))
    compiled = jax.jit(programs.decode, donate_argnums=(1, 2)).lower(
        params, state, cache).compile()
    assert bm.retention_traced((16, 1)) == "fused"
    assert _cache_copies(compiled, programs, cache) == {}   # nothing has positions
    assert _cache_copies(compiled, programs, cache, positioned=False) == {
        "f32[16,8,128,8320]": 0, "f32[16,8,8320]": 0}
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 16 * 274_759_680
    # weights + state, and temporaries under ONE layer's state
    assert m.temp_size_in_bytes < 16 * 274_759_680 // 8
    assert 11.5 * 2**30 < _fits_hbm(compiled) < 12.5 * 2**30
    text = compiled.as_text()
    # one launch a layer, its state operand the cache's own leaf (a parameter
    # or the tuple element of one: no copy, no transpose before it)
    calls = re.findall(r"^\s*%fused_retention_step[.\d]* = .* custom-call\("
                       r"[^)]*(%cache__layers___\d___S__[.\d]*)", text, re.M)
    assert len(calls) == len(set(calls)) == 8, calls
    # nothing else in the program is the size of a layer's state: the
    # ``add_select_fusion`` that updated it and the product that read it
    # again are gone (the kernel's own result apart)
    whole = [l for l in text.splitlines()
             if re.search(r"= f32\[16,8,(128,8320|8320,128)\]", l)
             and "parameter(" not in l and "get-tuple-element(" not in l]
    assert whole == [], whole[:3]
    found = _parts_of_the_compiled(programs, text, "fused_retention_step",
                                   "ret.state", 8)
    assert {"ret.proj", "ret.state", "ret.out"} <= set(found.values())


def test_brumby_prefill_slice_compiles_for_one_device(v5e):
    """4 prompts of 1,024 tokens into a 16-slot cache: the bucket is one
    chunk, so the quadratic weights (4 x 40 x 1,024 x 1,024 float32) and one
    state at the end, no loop over chunks; the slice's states placed into
    the donated cache without a whole copy of a leaf."""
    from can_tpu.models import brumby as bm

    programs, params, cache, batch, shape = _lm_programs_and_shapes(
        v5e, 16, 4, BRUMBY)
    compiled = jax.jit(programs.prefill_slice, donate_argnums=(2,)).lower(
        params, batch, cache, shape((), jnp.int32)).compile()
    assert bm.retention_traced((4, 1024)) == "chunked"
    text = compiled.as_text()
    assert " while(" not in text
    assert _cache_copies(compiled, programs, cache, positioned=False) == {
        "f32[16,8,128,8320]": 0, "f32[16,8,8320]": 0}
    assert 12 * 2**30 < _fits_hbm(compiled) < 15.5 * 2**30
    assert {"ret.proj", "ret.core", "ret.state", "ret.out"} <= _brumby_parts(
        programs, text)


# -- LongCat-Flash: two latent sublayers a layer, 256 slots ---------------------
LONGCAT = "longcat-flash-omni-ep32-serve-bf16"
# every positioned leaf of the cell's cache: a sublayer's latent and its
# rotary keys (four of each a layer: two sublayers)
LONGCAT_LEAVES = ("bf16[256,1280,512]", "bf16[256,1280,64]")


def test_longcat_decode_step_compiles_for_one_device(v5e, monkeypatch):
    """One greedy step of 256 slots over 1,280 positions as the chip traces
    it (``supports`` asks the backend: steered here), at the published
    widths: 10.35 GB of weights + 3.02 GB of latent cache (8 leaves a
    layer's two sublayers, 16 of each kind).  **Latent attention's decode
    kernel takes the second shape it has met** (64 heads, rank 512, rope 64,
    blocks of 1,024 over 1,280 positions: eight launches, two a layer), each
    sublayer reading ITS leaves where they lie; the experts are the batched
    form (256 tokens' top-12 of 768 leave a held expert idle 1.8% of the
    time); no leaf of the cache is copied whole; the peak is named and under
    16 GiB; the identity experts' counter rides in the state."""
    import re

    from can_tpu.models import longcat_flash as lf

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    programs, params, cache, _, shape = _lm_programs_and_shapes(
        v5e, 256, 32, LONGCAT)
    assert programs.decode_experts(256) == "batched"
    state = jax.tree.map(
        lambda a: shape(a.shape, a.dtype),
        jax.eval_shape(lambda: programs.new_state(
            [{"first": jnp.zeros((256,), jnp.int32),
              "logits": jnp.zeros((256, 8), jnp.float32),
              "choices": jnp.zeros((4, 256, 12), jnp.int32),
              "counts": jnp.zeros((4, 16), jnp.int32),
              "zero": jnp.zeros((4,), jnp.int32)}],
            jnp.ones((256,), jnp.int32), jnp.ones((256,), bool))[0]))
    assert state["zero"].shape == (4,) and "experts_read" not in state
    compiled = jax.jit(programs.decode, donate_argnums=(1, 2)).lower(
        params, state, cache).compile()
    assert lf.latent_traced((256, 1)) == "fused"
    text = compiled.as_text()
    assert "ragged-dot" not in text and "skipping_experts" not in text
    assert "f32[256,64,1280]" not in text        # no score array in HBM
    assert _cache_copies(compiled, programs, cache) == dict.fromkeys(
        LONGCAT_LEAVES, 0)
    # the kernel's operands: the latent from the scatter that wrote its row
    # in place; the rotary keys from the ONE fusion that writes their row by
    # a select over the leaf where it lies, positions minor
    # (``attention.write_row`` at or under ``SELECT_MAX_POSITIONS``: an
    # update a slot would be 256 x 8 = 2,048 small programs a step), the
    # kernel's own turn of the leaf folded into it: never a copy
    launches = _kernel_operands(text, "fused_latent_decode")
    assert [made[-2:] for made in launches] == [["fusion", "fusion"]] * 8
    assert text.count("attn.cache/dynamic_update_slice") == 0
    assert re.search(r"bf16\[256,64,1280\]\{2,1,0[^}]*\} fusion\(", text)
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= 256 * 1280 * 9216
    # weights 10.35 GB + cache 3.02 GB + a step's temporaries
    assert 13.3e9 < _fits_hbm(compiled) < 14.2e9
    # (``_parts_of_the_compiled``'s checks but its last: of this step's
    # instructions far fewer are the compiler's own copies)
    from can_tpu.models.lm_blocks import PARTS
    from can_tpu.obs.trace import program_scopes

    got = program_scopes(text, programs.parts)
    mine = {i: p for i, p in got["parts"].items()
            if i.startswith("fused_latent_decode")}
    assert len(mine) == 8 and set(mine.values()) == {"attn.core"}, mine
    assert set(got["parts"].values()) <= set(PARTS) | {None}
    assert got["unscoped"] <= 0.01 * got["instructions"], got["unscoped"]


def test_longcat_prefill_slice_compiles_for_one_device(v5e, monkeypatch):
    """32 prompts of 256 tokens into the 256-slot cache as the chip traces
    it: the bucket is under the fused attention's block of 1,024, so both
    sublayers take the scanned ``prefill_causal`` (one block: 64 heads x 256 x
    256 scores a prompt); the experts are sorted, the buffer twice the even
    share over the router's WIDTH (8,192 x 12 x 16 / 768 = 2,048: 4,096
    rows, where 16 / 512 would have made it 6,144), one loop an expert
    layer; no leaf of the cache is copied whole; the peak is under 16 GiB."""
    from can_tpu.models import glm_moe_lite as gm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    programs, params, cache, batch, shape = _lm_programs_and_shapes(
        v5e, 256, 32, LONGCAT)
    compiled = jax.jit(programs.prefill_slice, donate_argnums=(2,)).lower(
        params, batch, cache, shape((), jnp.int32)).compile()
    assert gm.attention_traced((32, 256)) == "scanned"
    text = compiled.as_text()
    assert "ragged-dot" in text and "fused_causal_attention" not in text
    assert "bf16[4096,6144]" in text and "bf16[6144,6144]" not in text
    assert _dispatch_loops(programs, text) == 4
    assert _cache_copies(compiled, programs, cache) == dict.fromkeys(
        LONGCAT_LEAVES, 0)
    assert 13.3e9 < _fits_hbm(compiled) < 16.5e9
