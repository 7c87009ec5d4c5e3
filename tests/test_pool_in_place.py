"""The paged K/V pools cross a serving program untouched.

``decode``@32 and ``prefill_chunk``@256 are compiled at the stand-in's
widths (``benchmark/configs/nope-lm-2048x24.json``: 24 layers x 2048,
32 heads x 64, FFN 8192, vocabulary 50272, 768 blocks of 16, bf16) for a
DESCRIBED TPU v5e: shapes only, no chip, nothing runs, no time is
implied.  What the compiled program says is the counter of ISSUE 29's
mechanism, which is always on: the donated pools are updated in place,
the program keeps next to no temporaries, no instruction copies, slices
or re-lays-out a pool or a layer of one, and the kernel reads the pool
itself once a layer.  Until that PR the pools were stored
``[..., heads, head_dim]`` and read through ``pool[layer]``: the decode
program re-laid both pools out on entry, copied them back, and
materialised 48 slices (7.38 GB of temporaries).

The same for a LATENT pool (ISSUE 35): ``decode``@64 and
``prefill_chunk``@1024 of ``benchmark/configs/deepseek-v2-ep4-5of60.json``
(5 layers x 5120, 128 heads, a row of 576 values on 640 lanes, 2,560
blocks of 128, 40 of 160 experts held), and both of its kernels
(``mxtpu_mla_decode``, ``mxtpu_moe_experts``) compiled by Mosaic.

And for WINDOW and global K/V tables (ISSUE 37): both programs of
``benchmark/configs/trinity-large-ep8-5of60.json`` (four pools: window
and global, K and V) and the grouped decode kernel ``mxtpu_gqa_decode``.
"""
import importlib.util
import json
import os
import re

import pytest

import jax
import jax.numpy as jnp

D_MODEL, HEADS, FFN, VOCAB = 2048, 32, 8192, 50272
#: plain bf16 pools at the configuration's real depth, so the program's
#: sizes are the cells' own; fp8 pools at 4 layers (no cell runs them:
#: the check is of the program's structure, and a compile is a minute)
DEPTH = {None: 24, "fp8": 4}
CHIP_BYTES = 16e9
ENGINE = dict(block_size=16, num_blocks=768, max_batch=32, max_queue=4096,
              max_prompt_len=1024, max_seq_len=2048, prefill_chunk=256,
              prefix_cache=False, speculate=False)
TEMPORARIES_LIMIT = 0.5e9


def _param_shapes(layers):
    d, f, v = D_MODEL, FFN, VOCAB
    shapes = {"embed_weight": (v, d), "final_ln_gamma": (d,),
              "final_ln_beta": (d,), "lm_head_weight": (v, d),
              "lm_head_bias": (v,)}
    for i in range(layers):
        p = f"layer{i}_"
        for nm in ("q", "k", "v", "proj"):
            shapes[p + nm + "_weight"] = (d, d)
            shapes[p + nm + "_bias"] = (d,)
        shapes.update({p + "ffn1_weight": (f, d), p + "ffn1_bias": (f,),
                       p + "ffn2_weight": (d, f), p + "ffn2_bias": (d,)})
        for ln in ("ln1", "ln2"):
            shapes[p + ln + "_gamma"] = (d,)
            shapes[p + ln + "_beta"] = (d,)
    return shapes


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


def _shape_engine(kv_quant):
    """The engine built from SHAPES (no 2.8 GB of weights, no 2.4 GB of
    pools are made): only its program builders and avals are used.  The
    pools' shapes are ``kvcache.make_pools``'s own."""
    import mxnet_tpu.serve.engine as eng_mod
    from mxnet_tpu.serve import Engine, EngineConfig

    sds = jax.ShapeDtypeStruct
    real_asarray, real_pools = jnp.asarray, eng_mod.kvcache.make_pools
    try:
        eng_mod.jnp.asarray = lambda v, *a, **k: (
            v if isinstance(v, sds) else real_asarray(v, *a, **k))
        eng_mod.kvcache.make_pools = lambda *a, **k: jax.eval_shape(
            lambda: real_pools(*a, **k))
        return Engine(
            {k: sds(s, jnp.bfloat16)
             for k, s in _param_shapes(DEPTH[kv_quant]).items()},
            EngineConfig(heads=HEADS, dtype=jnp.bfloat16, attn_impl="flash",
                         kv_quant=kv_quant, **ENGINE))
    finally:
        eng_mod.jnp.asarray = real_asarray
        eng_mod.kvcache.make_pools = real_pools


def _counts(shape):
    n = 1
    for s in shape:
        n *= s
    return n


@pytest.mark.parametrize("kv_quant", [None, "fp8"])
@pytest.mark.parametrize("kind,bucket", [("decode", 32),
                                         ("prefill_chunk", 256)])
def test_program_neither_copies_nor_slices_a_pool(topo, kind, bucket,
                                                  kv_quant):
    from jax.sharding import SingleDeviceSharding
    from mxnet_tpu.serve import kvcache

    eng = _shape_engine(kv_quant)
    one_chip = SingleDeviceSharding(topo.devices[0])
    make = {"decode": eng._make_decode_fn,
            "prefill_chunk": eng._make_chunk_prefill_fn}[kind]
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._avals(kind, bucket))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        comp = jax.jit(make(bucket), donate_argnums=(0, 1)).trace(
            *avals).lower(lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)

    pool = eng._pool_aval()
    m = comp.memory_analysis()
    print(kind, bucket, kv_quant, "GB: arguments %.2f aliased %.2f "
          "temporaries %.3f" % (m.argument_size_in_bytes / 1e9,
                                m.alias_size_in_bytes / 1e9,
                                m.temp_size_in_bytes / 1e9))
    # fits one chip beside nothing else (how ``num_blocks`` is chosen) ...
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) < CHIP_BYTES
    # ... both pools updated in place ...
    assert m.alias_size_in_bytes >= kvcache.pool_nbytes(pool, pool)
    # ... beside next to nothing (a copy of one plain pool is 1.2 GB)
    assert m.temp_size_in_bytes < TEMPORARIES_LIMIT, m.temp_size_in_bytes

    # no instruction whose result is as large as a pool or a layer of one
    # moves data without computing anything (the payload: an fp8 pool's
    # scales are 1.2 MB, which XLA may stage in faster memory)
    payload = pool.payload if kvcache.is_quantized(pool) else pool
    sizes = {_counts(payload.shape), _counts(payload.shape[1:])}
    moved = []
    for line in comp.as_text().splitlines():
        hit = re.search(r"= \(?(\w+)\[([\d,]+)\]\S* (copy|slice|bitcast|"
                        r"dynamic-slice|transpose)\(", line)
        if hit and _counts(int(d) for d in hit.group(2).split(",")) in sizes:
            moved.append(line.strip()[:200])
    assert not moved, moved[:3]

    if kind == "decode":            # the kernel, on the pool, once a layer
        calls = [ln for ln in comp.as_text().splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln
                 and "mxtpu_flash_decode" in ln]
        assert len(calls) == DEPTH[kv_quant], len(calls)


@pytest.mark.parametrize("h,hd", [(32, 64), (8, 128), (2, 32)])
@pytest.mark.parametrize("pool", ["f32", "bf16", "fp8"])
def test_kernel_compiles_for_a_v5e(topo, pool, h, hd):
    """Mosaic COMPILES ``mxtpu_flash_decode`` (lowering alone,
    ``tests/test_flash_decode.py::test_kernel_lowers_for_tpu``, does not
    show what it refuses: a copy out of an array in HBM whose rows are
    not whole 128-lane rows passes there and fails here), for every kind
    of pool, at the stand-in's width, at one head a lane row and at a
    width under one row, and the call keeps no pool-sized temporary at a
    deployed width."""
    from jax.sharding import SingleDeviceSharding
    from mxnet_tpu.serve import kvcache
    from mxnet_tpu.serve.flash_decode import flash_decode_attention

    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    b, bs, nl, nb, nblk = 32, 16, 3, 768, 128
    dtype = jnp.dtype({"f32": "float32"}.get(pool, "bfloat16"))
    if pool == "fp8":
        kv = kvcache.QuantPool(sds((nl, nb, bs, h * hd), jnp.float8_e4m3fn),
                               sds((nl, nb, bs), jnp.float32))
    else:
        kv = sds((nl, nb, bs, h * hd), dtype)
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        comp = jax.jit(lambda q, k, v, t, n: flash_decode_attention(
            q, k, v, 1, t, n)).trace(
            sds((b, h, hd), dtype), kv, kv, sds((b, nblk), jnp.int32),
            sds((b,), jnp.int32)).lower(lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "mxtpu_flash_decode" in comp.as_text()
    if h * hd % 128 == 0:
        assert comp.memory_analysis().temp_size_in_bytes < 4e6


# ---------------------------------------------------------------------------
# the latent pool and the expert layer (ISSUE 35)
# ---------------------------------------------------------------------------

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LATENT_CONFIG = os.path.join(REPO, "benchmark", "configs",
                             "deepseek-v2-ep4-5of60.json")


def _latent_shape_engine():
    """The engine of the latent configuration, from SHAPES: the
    parameters' shapes are the benchmark reference's own (loaded by path),
    the pool's ``kvcache.make_pools``'s."""
    import mxnet_tpu.serve.engine as eng_mod
    from mxnet_tpu.serve import Engine, EngineConfig

    with open(LATENT_CONFIG) as f:
        cfg = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "deepseek_v2_reference",
        os.path.join(REPO, "benchmark", "reference", "deepseek_v2.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    sds = jax.ShapeDtypeStruct
    real_asarray, real_pools = jnp.asarray, eng_mod.kvcache.make_pools
    try:
        eng_mod.jnp.asarray = lambda v, *a, **k: (
            v if isinstance(v, sds) else real_asarray(v, *a, **k))
        eng_mod.kvcache.make_pools = lambda *a, **k: jax.eval_shape(
            lambda: real_pools(*a, **k))
        engine = dict(cfg["serve"]["engine"], attn_impl="flash")
        return Engine(
            {k: sds(s, jnp.bfloat16) for k, s in ref.param_shapes(cfg).items()},
            EngineConfig(heads=int(cfg["num_attention_heads"]),
                         dtype=jnp.bfloat16, **engine))
    finally:
        eng_mod.jnp.asarray = real_asarray
        eng_mod.kvcache.make_pools = real_pools


@pytest.mark.parametrize("kind,bucket", [("decode", 64),
                                         ("prefill_chunk", 1024)])
def test_latent_program_keeps_its_pool_in_place(topo, kind, bucket):
    from jax.sharding import SingleDeviceSharding
    from mxnet_tpu.serve import kvcache

    eng = _latent_shape_engine()
    assert eng.latent and len(eng._caches) == 1
    one_chip = SingleDeviceSharding(topo.devices[0])
    make = {"decode": eng._make_decode_fn,
            "prefill_chunk": eng._make_chunk_prefill_fn}[kind]
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._avals(kind, bucket))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        comp = jax.jit(make(bucket), donate_argnums=(0,)).trace(
            *avals).lower(lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    pool = eng._pool_aval()
    assert pool.shape == (5, 2561, 128, 640)
    m = comp.memory_analysis()
    print(kind, bucket, "latent GB: arguments %.2f aliased %.2f temporaries "
          "%.3f" % (m.argument_size_in_bytes / 1e9, m.alias_size_in_bytes / 1e9,
                    m.temp_size_in_bytes / 1e9))
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) < CHIP_BYTES
    assert m.alias_size_in_bytes >= kvcache.pool_nbytes(pool)
    # a copy of the pool would be 2.1 GB
    assert m.temp_size_in_bytes < 1.5e9, m.temp_size_in_bytes
    sizes = {_counts(pool.shape), _counts(pool.shape[1:])}
    moved = []
    for line in comp.as_text().splitlines():
        # (a ``bitcast`` moves nothing: the chunk's scatter sees the pool
        # as [positions, lanes] through one)
        hit = re.search(r"= \(?(\w+)\[([\d,]+)\]\S* (copy|slice|"
                        r"dynamic-slice|transpose)\(", line)
        if hit and _counts(int(d) for d in hit.group(2).split(",")) in sizes:
            moved.append(line.strip()[:200])
    assert not moved, moved[:3]
    calls = [ln for ln in comp.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    # the grouped product twice a routed layer (gate and up in one pass,
    # then down), the decode kernel on the pool once a layer
    assert sum("mxtpu_moe_experts" in ln for ln in calls) == 2 * 4
    assert sum("mxtpu_mla_decode" in ln for ln in calls) == (
        5 if kind == "decode" else 0)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_latent_kernels_compile_for_a_v5e(topo, dtype):
    """Mosaic COMPILES ``mxtpu_mla_decode`` and ``mxtpu_moe_experts`` at
    the published widths (128 heads over rows of 640 lanes in blocks of
    128; 40 experts of 5120 x 1536, decode's and a chunk's row tiles),
    and neither call keeps a pool- or weight-sized temporary."""
    from jax.sharding import SingleDeviceSharding
    from mxnet_tpu.serve import moe_experts
    from mxnet_tpu.serve.mla_decode import mla_decode_attention

    one_chip = SingleDeviceSharding(topo.devices[0])
    dt = jnp.dtype(dtype)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def compiled(fn, *avals):
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            return jax.jit(fn).trace(*avals).lower(
                lowering_platforms=("tpu",)).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)

    comp = compiled(
        lambda q, pool, t, n: mla_decode_attention(
            q, pool, 1, t, n, rank=512, scale=0.1147),
        sds((64, 128, 576), dt), sds((3, 256, 128, 640), dt),
        sds((64, 48), jnp.int32), sds((64,), jnp.int32))
    assert "mxtpu_mla_decode" in comp.as_text()
    assert comp.memory_analysis().temp_size_in_bytes < 40e6

    held, d, f = 8, 5120, 1536
    for tokens in (64, 1024):
        def experts(x, local, mask, wg, wu, wd):
            tm = moe_experts.row_tile(local.size, wg.dtype)
            p = moe_experts.plan(local, mask, held, tm)
            xs = jnp.take(x, p.src, axis=0)
            h = moe_experts.grouped_matmul(xs, (wg, wu), p, tm,
                                           column_tile=512)
            return moe_experts.grouped_matmul(h, (wd,), p, tm,
                                              column_tile=1280)
        comp = compiled(
            experts, sds((tokens, d), dt), sds((tokens, 6), jnp.int32),
            sds((tokens, 6), jnp.bool_), sds((held, d, f), dt),
            sds((held, d, f), dt), sds((held, f, d), dt))
        assert comp.as_text().count("mxtpu_moe_experts") >= 2


# ---------------------------------------------------------------------------
# window and global K/V tables under one allocator (ISSUE 37)
# ---------------------------------------------------------------------------

WINDOW_CONFIG = os.path.join(REPO, "benchmark", "configs",
                             "trinity-large-ep8-5of60.json")


def _window_shape_engine():
    """The engine of the window configuration, from SHAPES: the
    parameters' shapes are the benchmark reference's own (loaded by path;
    the selection bias float32), the pools' ``kvcache.make_pools``'s."""
    import mxnet_tpu.serve.engine as eng_mod
    from mxnet_tpu.serve import Engine, EngineConfig

    with open(WINDOW_CONFIG) as f:
        cfg = json.load(f)
    spec = importlib.util.spec_from_file_location(
        "trinity_reference",
        os.path.join(REPO, "benchmark", "reference", "trinity.py"))
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    sds = jax.ShapeDtypeStruct
    real_asarray, real_pools = jnp.asarray, eng_mod.kvcache.make_pools
    try:
        eng_mod.jnp.asarray = lambda v, *a, **k: (
            v if isinstance(v, sds) else real_asarray(v, *a, **k))
        eng_mod.kvcache.make_pools = lambda *a, **k: jax.eval_shape(
            lambda: real_pools(*a, **k))
        engine = dict(cfg["serve"]["engine"], attn_impl="flash")
        return Engine(
            {k: sds(s, jnp.float32 if k.endswith("router_bias")
                    else jnp.bfloat16)
             for k, s in ref.param_shapes(cfg).items()},
            EngineConfig(heads=int(cfg["num_attention_heads"]),
                         dtype=jnp.bfloat16, **engine))
    finally:
        eng_mod.jnp.asarray = real_asarray
        eng_mod.kvcache.make_pools = real_pools


@pytest.mark.parametrize("kind,bucket", [("decode", 32),
                                         ("prefill_chunk", 1024)])
def test_window_program_keeps_its_pools_in_place(topo, kind, bucket):
    """Both programs of ``trinity-large-ep8-5of60`` (4 window layers in a
    ring of 41 blocks a row, 1 global layer in 3,000 blocks; 48 query
    heads over 8 of 128; 32 of 256 experts) compile for a v5e, fit it,
    alias all four pools, move none of them, and call the grouped decode
    kernel once a layer in the decode program."""
    from jax.sharding import SingleDeviceSharding
    from mxnet_tpu.serve import kvcache

    eng = _window_shape_engine()
    assert eng.described_kv and eng.alloc.ring == 41
    one_chip = SingleDeviceSharding(topo.devices[0])
    make = {"decode": eng._make_decode_fn,
            "prefill_chunk": eng._make_chunk_prefill_fn}[kind]
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._avals(kind, bucket))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        comp = jax.jit(make(bucket), donate_argnums=(0, 1, 2, 3)).trace(
            *avals).lower(lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    pools = [eng._pool_aval(i) for i in range(4)]
    assert [p.shape for p in pools] == [(4, 1313, 128, 1024)] * 2 + [
        (1, 3001, 128, 1024)] * 2
    m = comp.memory_analysis()
    print(kind, bucket, "window GB: arguments %.2f aliased %.2f temporaries "
          "%.3f" % (m.argument_size_in_bytes / 1e9, m.alias_size_in_bytes / 1e9,
                    m.temp_size_in_bytes / 1e9))
    assert (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes) < CHIP_BYTES
    assert m.alias_size_in_bytes >= kvcache.pool_nbytes(*pools)
    assert m.temp_size_in_bytes < 0.5e9, m.temp_size_in_bytes
    sizes = {_counts(p.shape) for p in pools} | {
        _counts(p.shape[1:]) for p in pools}
    moved = []
    for line in comp.as_text().splitlines():
        hit = re.search(r"= \(?(\w+)\[([\d,]+)\]\S* (copy|slice|"
                        r"dynamic-slice|transpose)\(", line)
        if hit and _counts(int(d) for d in hit.group(2).split(",")) in sizes:
            moved.append(line.strip()[:200])
    assert not moved, moved[:3]
    calls = [ln for ln in comp.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert sum("mxtpu_moe_experts" in ln for ln in calls) == 2 * 4
    assert sum("mxtpu_gqa_decode" in ln for ln in calls) == (
        5 if kind == "decode" else 0)


@pytest.mark.parametrize("window,ring,columns", [(4096, 41, 41),
                                                 (0, 0, 120)])
def test_grouped_decode_kernel_compiles_for_a_v5e(topo, window, ring,
                                                  columns):
    """Mosaic COMPILES ``mxtpu_gqa_decode`` at the published widths (48
    query heads over 8 of 128, blocks of [128, 1024] bf16) for a window
    layer's ring and a global layer's table, with no pool-sized
    temporary."""
    from jax.sharding import SingleDeviceSharding
    from mxnet_tpu.serve.gqa_decode import gqa_decode

    one_chip = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    jax.config.update("jax_enable_compilation_cache", False)
    try:
        comp = jax.jit(lambda q, k, v, t, n: gqa_decode(
            q, k, v, 1, t, n, scale=128 ** -0.5, window=window, ring=ring)
        ).trace(sds((32, 48, 128), jnp.bfloat16),
                sds((4, 1313, 128, 1024), jnp.bfloat16),
                sds((4, 1313, 128, 1024), jnp.bfloat16),
                sds((32, columns), jnp.int32),
                sds((32,), jnp.int32)).lower(
                    lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert "mxtpu_gqa_decode" in comp.as_text()
    assert comp.memory_analysis().temp_size_in_bytes < 10e6


# -- the training step's names ----------------------------------------------

def _shape_trainer(topo, which, optimizer=("sgd", {"learning_rate": 0.1,
                                                     "momentum": 0.9,
                                                     "wd": 1e-4})):
    """A trainer bound to described devices: parameters and optimizer
    state are shapes (``_global_put`` and the optimizer's zeros hand back
    ``ShapeDtypeStruct``s), so nothing is placed."""
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import models
    from mxnet_tpu import symbol as S
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh, megatron_rules

    class ShapeTrainer(ShardedTrainer):
        def _global_put(self, val, sh):
            if not hasattr(val, "dtype"):
                val = np.asarray(val)
            return jax.ShapeDtypeStruct(tuple(val.shape), val.dtype,
                                        sharding=sh)

    def shape_state(tr):
        # optimizer state as shapes too: zeros of a described array
        # would be placed
        zeros = tr.optimizer.state_zeros_like
        tr.optimizer.state_zeros_like = lambda w: jax.eval_shape(
            zeros, jax.ShapeDtypeStruct(w.shape, w.dtype))
        return tr

    mx.random.seed(7)
    if which == "resnet_like":
        # the conv weight kinds ResNet-50 holds, each in its own tiled
        # layout on a v5e ([O, 3, 7, 7], [O, I, 3, 3], [O, I, 1, 1]),
        # BatchNorm gamma/beta, biases and an FC weight
        d = S.Variable("data")
        net = S.Convolution(d, num_filter=64, kernel=(7, 7), stride=(2, 2),
                            pad=(3, 3), name="conv0")
        for i, (nf, k) in enumerate([(64, 3), (256, 1), (128, 3)]):
            net = S.BatchNorm(net, name=f"bn{i}")
            net = S.Activation(net, act_type="relu")
            net = S.Convolution(net, num_filter=nf, kernel=(k, k),
                                pad=(k // 2, k // 2), name=f"conv{i + 1}",
                                no_bias=k == 1)
        net = S.Pooling(net, global_pool=True, kernel=(1, 1),
                        pool_type="avg", name="pool")
        net = S.FullyConnected(net, num_hidden=1000, name="fc")
        tr = shape_state(ShapeTrainer(
            S.SoftmaxOutput(net, name="softmax"),
            mesh=make_mesh({"data": 1}, topo.devices[:1]),
            optimizer=optimizer[0], optimizer_params=optimizer[1],
            compute_dtype="bfloat16"))
        tr.bind(data_shapes={"data": (8, 3, 64, 64)},
                label_shapes={"softmax_label": (8,)})
        return tr
    if which == "conv":
        d = S.Variable("data")
        net = S.Convolution(d, num_filter=128, kernel=(3, 3), pad=(1, 1),
                            name="conv1")
        net = S.BatchNorm(net, name="bn1")
        net = S.Activation(net, act_type="relu", name="relu1")
        net = S.Pooling(net, global_pool=True, kernel=(1, 1),
                        pool_type="avg", name="pool")
        net = S.FullyConnected(net, num_hidden=128, name="fc")
        tr = shape_state(ShapeTrainer(
            S.SoftmaxOutput(net, name="softmax"),
            mesh=make_mesh({"data": 1}, topo.devices[:1]), optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9},
            compute_dtype="bfloat16"))
        tr.bind(data_shapes={"data": (16, 8, 32, 32)},
                label_shapes={"softmax_label": (16,)})
        return tr
    sym = models.get_symbol("transformer-lm", vocab_size=512, num_layers=2,
                            d_model=256, heads=4, batch_size=4, seq_len=256,
                            loss_head=True, remat=True)
    tr = ShapeTrainer(sym, mesh=make_mesh({"data": 2, "model": 2},
                                          topo.devices[:4]),
                      rules=megatron_rules(), optimizer="adam",
                      optimizer_params={"learning_rate": 1e-4},
                      compute_dtype="bfloat16", matmul_precision="bfloat16")
    shape_state(tr)
    tr.bind(data_shapes={"data": (4, 256)},
            label_shapes={"softmax_label": (4, 256)})
    return tr


@pytest.mark.parametrize("which", ["conv", "lm"])
def test_train_step_names_are_metadata_only_on_a_v5e(topo, which,
                                                     monkeypatch):
    """Compiled for a described v5e, the training step with its phase and
    node scopes is, metadata and the location table stripped, the same
    program as without them (the fused sgd-momentum conv step on one
    chip; the tensor-parallel LM with remat and adam on data 2 x model
    2, where each collective's mesh axis is read from the program)."""
    import contextlib

    from mxnet_tpu.parallel.collectives import collective_inventory

    def compiled_text():
        tr = _shape_trainer(topo, which)
        jax.config.update("jax_enable_compilation_cache", False)
        try:
            comp = tr.trace_program("train")[0].lower(
                lowering_platforms=("tpu",)).compile()
        finally:
            jax.config.update("jax_enable_compilation_cache", True)
        return tr, comp.as_text()

    def stripped(text):
        return re.sub(r",? metadata=\{[^}]*\}", "",
                      text.split("\nFileNames")[0])

    tr, text = compiled_text()
    assert "/transpose(jvp(forward))/" in text and "/update/" in text
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        _, bare = compiled_text()
    assert "/update/" not in bare
    assert stripped(text) == stripped(bare)
    if which == "lm":
        inv = collective_inventory(text, tr.mesh)
        axes = {r["axes"] for r in inv.values()}
        assert ("model",) in axes and ("data",) in axes, axes


#: instructions that move a parameter's bytes into another shape or layout
RELAYOUT_OPS = {"reshape", "copy", "copy-start", "transpose", "concatenate",
                "slice"}


@pytest.mark.parametrize("optimizer", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("adamw", {"learning_rate": 1e-3, "wd": 1e-4}),
], ids=["sgd_momentum", "adamw"])
def test_step_reshapes_no_parameter_and_its_update_relays_none_on_a_v5e(
        topo, optimizer):
    """A conv net's train step compiled for a described v5e.  Nowhere in
    the program is a parameter-sized array reshaped, transposed,
    concatenated or sliced, and under ``update`` (HLO ``op_name``) not
    copied either: each parameter is updated in the layout it lives in.
    XLA's bytes accessed stay near the step's own work: 137 MB for
    SGD momentum here, where an update over flat buckets (a row-major
    ``reshape(-1)`` of a tiled conv weight is a transpose; every weight
    and gradient gathered, the weights scattered back) read 617 MB.
    The copies left are the weights' and states' entry-layout copies
    into the convolutions' layouts, outside ``update``."""
    import numpy as np
    tr = _shape_trainer(topo, "resnet_like", optimizer)
    assert len(tr._param_names) == 15
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        comp = tr.trace_program("train")[0].lower(
            lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    text = comp.as_text()
    sizes = {int(np.prod(tr._params[n].shape)) for n in tr._param_names}
    inst = re.compile(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* "
                      r"([\w-]+)\(")
    under_update = []
    relayouts = []
    for line in text.splitlines():
        m = inst.match(line)
        if not m:
            continue
        op_name = re.search(r'op_name="([^"]*)"', line)
        in_update = bool(op_name) and "/update/" in op_name.group(1)
        if in_update:
            under_update.append(m.group(2))
        n = int(np.prod([int(x) for x in m.group(1).split(",") if x]))
        if m.group(2) in RELAYOUT_OPS and (
                n in sizes or m.group(2) == "concatenate") and (
                in_update or m.group(2) not in ("copy", "copy-start")):
            relayouts.append(line.strip()[:160])
    assert under_update, "no instruction carries the update scope"
    assert not relayouts, "\n".join(relayouts)
    cost = comp.cost_analysis()
    cost = cost[0] if isinstance(cost, list) else cost
    # SGD momentum reads 137 MB, AdamW (one more state) 158 MB
    assert cost["bytes accessed"] < 200e6, cost["bytes accessed"]
