"""Flash-decode Pallas kernel (mxnet_tpu/serve/flash_decode.py).

The kernel is the TPU decode-attention path behind
``kvcache.paged_attention(impl="flash")``; on CPU the SAME kernel body
runs under the Pallas interpreter (``impl="flash_interpret"``), so these
tests pin the kernel's numerics — not a Python re-implementation:

* parity with the dense one-shot reference across block counts (single
  block through long ragged contexts) and every split-K partitioning,
  including splits that do not divide the block count;
* fp8 QuantPool in-kernel dequantization matches the dense fp8 read
  exactly (both dequantize the same payload/scale pairs);
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
from mxnet_tpu.quant import rowwise_quantize
from mxnet_tpu.serve import kvcache
from mxnet_tpu.serve.flash_decode import (default_split_k,
                                          flash_decode_attention)


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def _setup(seed, B, H, HD, BS, nblk_per_req, npool=64):
    """Paged pools with per-request ragged lengths; returns the dense
    reference output alongside the paged operands."""
    rng = np.random.RandomState(seed)
    max_blocks = max(nblk_per_req)
    q = rng.randn(B, H, HD).astype(np.float32)
    kp = rng.randn(npool, BS, H, HD).astype(np.float32)
    vp = rng.randn(npool, BS, H, HD).astype(np.float32)
    tables = np.zeros((B, max_blocks), np.int32)
    lengths = np.zeros(B, np.int32)
    free = iter(rng.permutation(np.arange(1, npool)))
    for b, nb in enumerate(nblk_per_req):
        tables[b, :nb] = [next(free) for _ in range(nb)]
        # ragged: last block partially filled (at least one slot)
        lengths[b] = (nb - 1) * BS + int(rng.randint(1, BS + 1))
    args = (jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
            jnp.asarray(tables), jnp.asarray(lengths))
    ref = np.asarray(kvcache.paged_attention(*args, impl="dense"))
    return args, ref


def _quantize(pool):
    npool, bs = pool.shape[:2]
    pay, sc = rowwise_quantize(
        jnp.asarray(np.asarray(pool).reshape(npool * bs, -1)), "e4m3")
    return kvcache.QuantPool(pay.reshape(pool.shape),
                             sc.reshape(npool, bs))


@pytest.mark.parametrize("nblk_per_req", [
    [1],                     # single block, single request
    [2, 1],                  # tiny ragged batch
    [3, 1, 2],
    [5, 2, 5],
    [8, 3, 6, 1],            # at the serial/split boundary
])
@pytest.mark.parametrize("split_k", [None, 1, 2, 4])
def test_flash_matches_dense(nblk_per_req, split_k):
    (q, kp, vp, tables, lengths), ref = _setup(
        seed=11 + len(nblk_per_req), B=len(nblk_per_req), H=2, HD=16,
        BS=4, nblk_per_req=nblk_per_req)
    out = np.asarray(flash_decode_attention(
        q, kp, vp, tables, lengths, split_k=split_k, interpret=True))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_flash_long_context_split_k():
    """Long ragged contexts where split-K actually engages, including a
    split that does not divide the block count (trash-padded tail)."""
    nblk = [17, 9, 23]
    (q, kp, vp, tables, lengths), ref = _setup(
        seed=3, B=3, H=4, HD=8, BS=4, nblk_per_req=nblk, npool=128)
    for sk in (None, 1, 3, 8):
        out = np.asarray(flash_decode_attention(
            q, kp, vp, tables, lengths, split_k=sk, interpret=True))
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6,
                                   err_msg=f"split_k={sk}")


@pytest.mark.parametrize("split_k", [None, 2])
def test_flash_fp8_matches_dense_fp8(split_k):
    """In-kernel dequant reads the same payload/scale pairs the dense
    path reads — fp8 flash vs fp8 dense is a tight comparison, and both
    stay near the f32 reference."""
    (q, kp, vp, tables, lengths), f32_ref = _setup(
        seed=5, B=3, H=2, HD=16, BS=4, nblk_per_req=[4, 1, 3])
    qkp, qvp = _quantize(kp), _quantize(vp)
    dense = np.asarray(kvcache.paged_attention(
        q, qkp, qvp, tables, lengths, impl="dense"))
    flash = np.asarray(flash_decode_attention(
        q, qkp, qvp, tables, lengths, split_k=split_k, interpret=True))
    np.testing.assert_allclose(flash, dense, rtol=1e-5, atol=1e-6)
    assert np.max(np.abs(flash - f32_ref)) < 0.1


def test_flash_rejects_mixed_pools():
    (q, kp, vp, tables, lengths), _ = _setup(
        seed=9, B=2, H=2, HD=8, BS=4, nblk_per_req=[2, 1])
    with pytest.raises(MXNetError):
        flash_decode_attention(q, _quantize(kp), vp, tables, lengths,
                               interpret=True)


@pytest.mark.parametrize("pool", ["f32", "bf16", "fp8"])
def test_kernel_lowers_for_tpu(pool):
    """Mosaic must accept the kernel at serving shapes — cross-lowered
    here, no chip needed.  The head-batched dots this kernel once used
    passed every interpret-mode test and could not lower at all, which
    ``attn_impl="auto"`` (-> flash on TPU) turns into an engine that
    cannot warm up."""
    sds = jax.ShapeDtypeStruct
    b, h, hd, bs, nb, nblk = 16, 8, 64, 16, 256, 128
    if pool == "fp8":
        kv = kvcache.QuantPool(sds((nb, bs, h, hd), jnp.float8_e4m3fn),
                       sds((nb, bs), jnp.float32))
    else:
        kv = sds((nb, bs, h, hd), jnp.dtype(
            {"f32": "float32", "bf16": "bfloat16"}[pool]))
    text = jax.jit(flash_decode_attention).trace(
        sds((b, h, hd), jnp.float32), kv, kv, sds((b, nblk), jnp.int32),
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
