"""Flash-decode Pallas kernel (mxnet_tpu/serve/flash_decode.py).

The kernel is the TPU decode-attention path behind
``kvcache.paged_attention(impl="flash")``; on CPU the SAME kernel body
runs under the Pallas interpreter (``impl="flash_interpret"``), so these
tests pin the kernel's numerics — not a Python re-implementation:

* parity with the dense one-shot reference across block counts (single
  block through long ragged contexts) and every split-K partitioning,
  including splits that do not divide the block count; the pools come
  from ``make_pools`` (the stored form, ``[L, blocks, BS, H*hd]``) and
  the kernel is handed the whole pool and a layer other than 0, with
  other values in the same slots of the other layers;
* the stand-in's widths (32 heads x 64, blocks of 16, bf16), where the
  kernel walks a block in 128-lane chunks and multiplies in bf16;
* fp8 QuantPool in-kernel dequantization matches the dense fp8 read
  (both read the same payload/scale pairs);
* the walk over a row's blocks is a loop inside the kernel, bounded by
  the row's length: the grid is ``(rows, splits)`` whatever the tables'
  width, ragged rows (empty, one token, a block's edge, the table's last
  column, one live row of 32) match dense, and dead columns or unused
  table entries full of NaN change nothing;
* the ``default_split_k`` heuristic: serial up to 8 blocks, then
  partitions of <= 8 blocks each, capped at 8 streams;
* end-to-end: an engine configured with ``attn_impl="flash_interpret"``
  replays the dense engine token-for-token.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import kvcache
from mxnet_tpu.serve.flash_decode import (_flash_decode, _lane_chunks,
                                          _split_bf16, default_split_k,
                                          flash_decode_attention)

LAYER = 2       # of 3: the layer read; layers 0 and 1 hold decoys


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def _setup(seed, B, H, HD, BS, nblk_per_req, npool=64, dtype=jnp.float32,
           quant=None, lengths=None, max_blocks=None):
    """Paged pools (``make_pools``, filled through ``write_prefill``)
    with per-request ragged lengths (drawn, unless ``lengths`` gives
    them); returns the dense reader's output at ``LAYER`` alongside the
    paged operands."""
    rng = np.random.RandomState(seed)
    given = lengths
    max_blocks = max_blocks or max(nblk_per_req)
    q = jnp.asarray(rng.randn(B, H, HD), dtype)
    tables = np.zeros((B, max_blocks), np.int32)
    lengths = np.zeros(B, np.int32)
    free = iter(rng.permutation(np.arange(1, npool)))
    for b, nb in enumerate(nblk_per_req):
        tables[b, :nb] = [next(free) for _ in range(nb)]
        # ragged: last block partially filled (at least one slot)
        lengths[b] = (nb - 1) * BS + int(rng.randint(1, BS + 1))
    if given is not None:
        lengths[:] = given
    kp, vp = kvcache.make_pools(3, npool, BS, H, HD, dtype=dtype, quant=quant)
    for layer in range(3):
        for b, nb in enumerate(nblk_per_req):
            if not nb:
                continue
            ks, vs = (jnp.asarray(rng.randn(nb * BS, H, HD), dtype)
                      for _ in range(2))
            row, full = jnp.asarray(tables[b]), jnp.int32(nb * BS)
            kp = kvcache.write_prefill(kp, layer, ks, row, full)
            vp = kvcache.write_prefill(vp, layer, vs, row, full)
    args = (q, kp, vp, LAYER, jnp.asarray(tables), jnp.asarray(lengths))
    ref = np.asarray(kvcache.paged_attention(*args, impl="dense")
                     .astype(jnp.float32))
    return args, ref


@pytest.mark.parametrize("nblk_per_req", [
    [1],                     # single block, single request
    [2, 1],                  # tiny ragged batch
    [3, 1, 2],
    [5, 2, 5],
    [8, 3, 6, 1],            # at the serial/split boundary
])
@pytest.mark.parametrize("split_k", [None, 1, 2, 4])
def test_flash_matches_dense(nblk_per_req, split_k):
    args, ref = _setup(
        seed=11 + len(nblk_per_req), B=len(nblk_per_req), H=2, HD=16,
        BS=4, nblk_per_req=nblk_per_req)
    out = np.asarray(flash_decode_attention(
        *args, split_k=split_k, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("nblk,split_k,splits", [
    (4, None, 1), (16, None, 2), (64, None, 8), (128, None, 8),
    (16, 4, 4), (64, 4, 4), (128, 1, 1), (5, 8, 5),
])
def test_the_grid_does_not_grow_with_the_tables(nblk, split_k, splits):
    """The walk over a row's table columns is a loop in the kernel, not
    a grid dimension: the ``pallas_call`` has ``rows x splits`` grid
    steps however wide the tables are."""
    b, h, hd, bs = 6, 2, 16, 4
    sds = jax.ShapeDtypeStruct
    pool = sds((3, 2 * nblk, bs, h * hd), jnp.float32)
    jaxpr = jax.make_jaxpr(lambda q, k, v, t, n: flash_decode_attention(
        q, k, v, 1, t, n, split_k=split_k, interpret=True))(
        sds((b, h, hd), jnp.float32), pool, pool,
        sds((b, nblk), jnp.int32), sds((b,), jnp.int32))

    def calls(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from calls(sub)

    (call,) = calls(jaxpr.jaxpr)
    assert call.params["name"] == "mxtpu_flash_decode"
    assert tuple(call.params["grid_mapping"].grid) == (b, splits)


# rows of a 6-column table of 4-token blocks: empty, one token, a block's
# edge (two ways), the table's last column (partly and wholly filled)
_RAGGED = [0, 1, 4, 8, 21, 24, 13]


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("split_k", [None, 1, 2, 4])
def test_flash_ragged_rows_match_dense(split_k, quant):
    args, ref = _setup(
        seed=21, B=len(_RAGGED), H=2, HD=16, BS=4,
        nblk_per_req=[-(-n // 4) for n in _RAGGED], lengths=_RAGGED,
        max_blocks=6, quant=quant)
    out = np.asarray(flash_decode_attention(
        *args, split_k=split_k, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    assert not out[0].any()         # nothing cached: zeros, not NaN


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("split_k", [None, 1, 2, 4])
def test_flash_one_live_row_of_32(split_k, quant):
    """A decode bucket nearly empty: only its last row holds anything."""
    lengths = [0] * 31 + [37]
    args, ref = _setup(
        seed=22, B=32, H=2, HD=16, BS=4, nblk_per_req=[0] * 31 + [10],
        lengths=lengths, max_blocks=12, quant=quant)
    out = np.asarray(flash_decode_attention(
        *args, split_k=split_k, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)
    assert not out[:31].any() and out[31].any()


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("split_k", [None, 1, 3])
def test_flash_never_reads_a_dead_block(split_k, quant):
    """Table columns past a row's length, and every pool block no row's
    live prefix lists (the trash block among them), hold NaN: the result
    is bit for bit what clean ones give."""
    nblk = [9, 0, 3, 1]
    (q, kp, vp, layer, tables, lengths), _ = _setup(
        seed=23, B=4, H=2, HD=16, BS=4, nblk_per_req=nblk, max_blocks=12,
        quant=quant)
    clean = np.asarray(flash_decode_attention(
        q, kp, vp, layer, tables, lengths, split_k=split_k, interpret=True))
    tables = np.array(tables)
    live = np.unique(np.concatenate(
        [tables[b, :n] for b, n in enumerate(nblk)]))
    dead = np.setdiff1d(np.arange(64), live)
    for b, n in enumerate(nblk):
        tables[b, n:] = dead[b::4][:12 - n]

    def poisoned(pool):
        if quant:
            return kvcache.QuantPool(*(x.at[:, dead].set(np.nan)
                                       for x in pool))
        return pool.at[:, dead].set(np.nan)

    kp, vp = poisoned(kp), poisoned(vp)
    assert np.isnan(np.asarray(
        (kp.scale if quant else kp)[LAYER, 0], np.float32)).all()
    out = np.asarray(flash_decode_attention(
        q, kp, vp, layer, jnp.asarray(tables), lengths, split_k=split_k,
        interpret=True))
    np.testing.assert_array_equal(out, clean)


@pytest.mark.parametrize("layer", [0, 1, 2])
def test_flash_reads_the_layer_it_is_given(layer):
    """The kernel's block map carries the layer: each layer's read
    matches the dense reader's of that layer, and no two agree."""
    (q, kp, vp, _, tables, lengths), _ = _setup(
        seed=2, B=3, H=2, HD=16, BS=4, nblk_per_req=[5, 2, 3])
    outs = [np.asarray(flash_decode_attention(
        q, kp, vp, i, tables, lengths, interpret=True)) for i in range(3)]
    dense = np.asarray(kvcache.paged_attention(
        q, kp, vp, layer, tables, lengths, impl="dense"))
    np.testing.assert_allclose(outs[layer], dense, rtol=1e-5, atol=1e-6)
    for other in set(range(3)) - {layer}:
        assert np.abs(outs[other] - outs[layer]).max() > 0.1


@pytest.mark.parametrize("heads,head_dim,want", [
    (32, 64, (128, 8)),      # the stand-in: two heads a 128-lane chunk
    (8, 64, (128, 8)),
    (8, 128, (128, 8)),      # one head a chunk
    (4, 128, (128, 4)),      # fewer heads than a sublane tile
    (16, 32, (128, 8)),
    (2, 16, (32, 2)),        # the CPU tests' widths: one chunk, one group
    (2, 256, (512, 2)),      # a head wider than a lane row: one chunk
    (12, 64, (128, 12)),     # 12 heads do not tile by 8: one group
])
def test_lane_chunks(heads, head_dim, want):
    assert _lane_chunks(heads, head_dim) == want
    w, r = want
    assert heads * head_dim % w == 0 and heads % r == 0
    assert w % head_dim == 0 and r % (w // head_dim) == 0


def test_split_bf16_sums_to_the_float32():
    """Three bf16 pieces carry all 24 mantissa bits of a float32."""
    x = jnp.asarray(np.random.RandomState(0).rand(8, 16), jnp.float32)
    pieces = _split_bf16(x)
    assert pieces.dtype == jnp.bfloat16 and pieces.shape == (24, 16)
    back = sum(np.asarray(pieces[i * 8:(i + 1) * 8].astype(jnp.float32))
               for i in range(3))
    np.testing.assert_array_equal(back, np.asarray(x))


@pytest.mark.parametrize("dtype,quant", [("bfloat16", None),
                                         ("float32", None),
                                         ("bfloat16", "fp8")])
def test_flash_at_the_stand_ins_widths(dtype, quant):
    """32 heads x 64 in blocks of 16: the kernel walks each block in
    sixteen 128-lane chunks of two heads, in four groups of eight head
    rows, and (bf16, fp8) multiplies in bf16 with the probabilities in
    three pieces: the code the chip runs.  Against a float32 softmax of
    the values the pool holds."""
    H, HD, BS = 32, 64, 16
    args, ref = _setup(seed=4, B=3, H=H, HD=HD, BS=BS,
                       nblk_per_req=[9, 2, 4], npool=24,
                       dtype=jnp.dtype(dtype), quant=quant)
    q, kp, vp, layer, tables, lengths = args
    out = np.asarray(flash_decode_attention(*args, interpret=True)
                     .astype(jnp.float32))
    f32 = jnp.float32
    if quant:
        wide = [(p.payload.astype(f32) * p.scale[..., None]) for p in (kp, vp)]
    else:
        wide = [p.astype(f32) for p in (kp, vp)]
    exact = np.asarray(kvcache.paged_attention(
        q.astype(f32), *wide, layer, tables, lengths, impl="scan"))
    # the output is rounded to the queries' dtype, and to nothing else
    ulp = 2.0 ** -8 if dtype == "bfloat16" else 2.0 ** -20
    np.testing.assert_allclose(out, exact, rtol=ulp, atol=ulp)
    np.testing.assert_allclose(out, ref, rtol=0.02, atol=0.02)


def test_flash_long_context_split_k():
    """Long ragged contexts where split-K actually engages, including a
    split that does not divide the block count (trash-padded tail)."""
    nblk = [17, 9, 23]
    args, ref = _setup(
        seed=3, B=3, H=4, HD=8, BS=4, nblk_per_req=nblk, npool=128)
    for sk in (None, 1, 3, 8):
        out = np.asarray(flash_decode_attention(
            *args, split_k=sk, interpret=True))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"split_k={sk}")


@pytest.mark.parametrize("split_k", [None, 2])
def test_flash_fp8_matches_dense_fp8(split_k):
    """In-kernel dequant reads the same payload/scale pairs the dense
    path reads — fp8 flash vs fp8 dense is a tight comparison, and both
    stay near the f32 reference."""
    shape = dict(seed=5, B=3, H=2, HD=16, BS=4, nblk_per_req=[4, 1, 3])
    _, f32_ref = _setup(**shape)
    args, dense = _setup(**shape, quant="fp8")      # the same values
    assert kvcache.is_quantized(args[1])
    flash = np.asarray(flash_decode_attention(
        *args, split_k=split_k, interpret=True))
    np.testing.assert_allclose(flash, dense, rtol=1e-5, atol=1e-6)
    assert np.max(np.abs(flash - f32_ref)) < 0.1


def test_flash_rejects_mixed_pools():
    shape = dict(seed=9, B=2, H=2, HD=8, BS=4, nblk_per_req=[2, 1])
    (q, kp, vp, layer, tables, lengths), _ = _setup(**shape)
    (_, qkp, _, _, _, _), _ = _setup(**shape, quant="fp8")
    with pytest.raises(MXNetError):
        flash_decode_attention(q, qkp, vp, layer, tables, lengths,
                               interpret=True)


def test_flash_rejects_a_pool_of_another_width():
    """The head geometry comes with the queries; a pool that stores
    another number of lanes a position is refused by name."""
    (q, kp, vp, layer, tables, lengths), _ = _setup(
        seed=9, B=2, H=2, HD=8, BS=4, nblk_per_req=[2, 1])
    with pytest.raises(MXNetError, match="lanes a position"):
        flash_decode_attention(q[:, :1], kp, vp, layer, tables, lengths,
                               interpret=True)


@pytest.mark.parametrize("h,hd", [(8, 64), (32, 64), (2, 32)])
@pytest.mark.parametrize("pool", ["f32", "bf16", "fp8"])
def test_kernel_lowers_for_tpu(pool, h, hd):
    """Mosaic must accept the kernel at serving shapes — cross-lowered
    here, no chip needed.  The head-batched dots this kernel once used
    passed every interpret-mode test and could not lower at all, which
    ``attn_impl="auto"`` (-> flash on TPU) turns into an engine that
    cannot warm up.  (2, 32) is a width under one 128-lane row: the
    one-chunk walk lowers too, so no width is refused."""
    sds = jax.ShapeDtypeStruct
    b, bs, nl, nb, nblk = 16, 16, 3, 256, 128
    dtype = jnp.dtype({"f32": "float32"}.get(pool, "bfloat16"))
    if pool == "fp8":
        kv = kvcache.QuantPool(sds((nl, nb, bs, h * hd), jnp.float8_e4m3fn),
                               sds((nl, nb, bs), jnp.float32))
    else:
        kv = sds((nl, nb, bs, h * hd), dtype)
    text = jax.jit(lambda q, k, v, t, n: flash_decode_attention(
        q, k, v, 1, t, n)).trace(
        sds((b, h, hd), dtype), kv, kv, sds((b, nblk), jnp.int32),
        sds((b,), jnp.int32)).lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text


def test_default_split_k():
    assert [default_split_k(n) for n in (1, 4, 8)] == [1, 1, 1]
    assert default_split_k(9) == 2      # no partition scans > 8 blocks
    assert default_split_k(16) == 2
    assert default_split_k(17) == 3
    assert default_split_k(64) == 8
    assert default_split_k(1024) == 8   # capped stream count


def test_engine_flash_interpret_parity():
    """An engine on the interpreted flash kernel emits token-for-token
    what the dense engine emits (greedy + seeded sampling)."""
    from tests.test_serve import _KW, _PROMPTS, _engine
    dense = _engine()
    refs = [dense.result(dense.submit(p, **k))
            for p, k in zip(_PROMPTS, _KW)]
    eng = _engine(attn_impl="flash_interpret")
    assert eng.attn_impl == "flash_interpret"
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    assert [eng.result(i) for i in ids] == refs
