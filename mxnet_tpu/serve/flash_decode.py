"""Flash-decode: a Pallas kernel for paged KV-cache reads (docs/serving.md).

Decode attention is HBM-bound — each step streams every cached K/V
position of every running request once, does ~4 flops per byte, and
throws the bytes away.  The generic ``kvcache._attend_blocks`` scan
expresses that stream as one gather + softmax-update op chain per block
column, which XLA schedules as independent HLOs; this kernel is the
serving twin of the r8 fused-update kernel: the whole per-request scan
becomes **one fused Pallas program** that

* prefetches the block *tables* as scalars, so the grid's index map
  streams each table-addressed KV block from HBM into VMEM exactly once
  (the gather indirection compiles into the block pipeline itself);
* runs **split-K across block partitions** for long contexts: the grid
  is ``(batch, splits, blocks_per_split)`` and each split accumulates an
  independent online-softmax partial ``(acc, m, l)``, so a 32k-token
  context becomes ``splits`` concurrent streams instead of one long
  serial scan.  Partials combine outside the kernel in one cheap f32
  pass (``exp(m_s - m*)`` reweighting — the standard flash-decoding
  reduction);
* dequantizes **fp8 pools in-kernel**: a :class:`~.kvcache.QuantPool`
  layer ships its e4m3 payload and per-position f32 scales as separate
  block streams, so the HBM traffic is the 1-byte payload, not a
  pre-widened f32 copy.

Numerics match the reference scan: f32 scores/statistics, ``NEG_INF``
masking, the same ``exp(m - m_new)`` rescale — pinned against
``kvcache.dense_attention`` by ``tests/test_flash_decode.py``.  Like
``ops/fused_update.py``, the kernel runs under ``interpret=True`` on CPU
(same program, emulated grid) so every test exercises the true kernel
body; ``paged_attention(impl="flash_interpret")`` selects that twin.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..parallel.flash_attention import NEG_INF
from .kvcache import QuantPool, is_quantized, softmax_scale

__all__ = ["flash_decode_attention", "default_split_k"]


def default_split_k(nblk: int) -> int:
    """Split-K heuristic: short contexts stay single-stream (no combine
    overhead); long contexts split so no partition scans more than 8
    blocks serially."""
    if nblk <= 8:
        return 1
    return min(8, -(-nblk // 8))


def _decode_kernel(*refs, bps: int, block_size: int, quantized: bool,
                   scale: np.float32):
    """One grid step: fold logical block ``j = s*bps + p`` of request
    ``b`` into split ``s``'s online-softmax partial.

    Ref layout (scalar-prefetch args first, then inputs, then outputs):
    ``tables, lengths, q, k, v[, kscale, vscale], acc, m, l``.
    """
    if quantized:
        (tables_ref, lengths_ref, q_ref, k_ref, v_ref,
         kscale_ref, vscale_ref, acc_ref, m_ref, l_ref) = refs
    else:
        (tables_ref, lengths_ref, q_ref, k_ref, v_ref,
         acc_ref, m_ref, l_ref) = refs
        kscale_ref = vscale_ref = None

    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():  # fresh partial per (request, split)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Single-query attention has no matmul worth the MXU (M = 1 per
    # head), and Mosaic only lowers dots whose batch dims lead; the
    # [BS, H, hd] block keeps (H, hd) on (sublanes, lanes) exactly as
    # the pool stores it, so both contractions run on the VPU: a lane
    # reduction for q.k and a leading-dim reduction for p.v.  Every
    # statistic stays in the keepdims "column" form ([.., H, 1]) so no
    # value ever has to move between lanes and sublanes.
    q = q_ref[...].astype(jnp.float32)                      # [H, hd]
    k = k_ref[...].astype(jnp.float32)                      # [BS, H, hd]
    v = v_ref[...].astype(jnp.float32)
    if quantized:
        k = k * kscale_ref[...]                             # [BS, 1, 1]
        v = v * vscale_ref[...]

    s = jnp.sum(q[None] * k, axis=-1, keepdims=True) * scale  # [BS, H, 1]

    # logical block index of this grid step -> absolute positions
    j = pl.program_id(1) * bps + p
    pos = j * block_size + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 0)                               # [BS, H, 1]
    valid = pos < lengths_ref[b]
    # f32-typed constants: weak python-float literals re-materialize at
    # lowering time and can widen to f64 under an ambient x64 context.
    s = jnp.where(valid, s, np.float32(NEG_INF))

    m_prev = m_ref[...]                                      # [H, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
    alpha = jnp.exp(m_prev - m_new)                          # [H, 1]
    pmat = jnp.where(valid, jnp.exp(s - m_new[None]), np.float32(0.0))
    l_ref[...] = l_ref[...] * alpha + jnp.sum(pmat, axis=0)
    acc_ref[...] = acc_ref[...] * alpha + jnp.sum(pmat * v, axis=0)
    m_ref[...] = m_new


def flash_decode_attention(q, k_pool, v_pool, tables, lengths, *,
                           scale: Optional[float] = None,
                           split_k: Optional[int] = None,
                           interpret: bool = False):
    """Drop-in twin of ``kvcache.paged_attention``: ``q`` [B, H, hd],
    one layer's pool (plain array or :class:`~.kvcache.QuantPool`),
    ``tables`` [B, max_blocks], ``lengths`` [B].  Returns [B, H, hd].

    ``split_k`` partitions the logical blocks into that many concurrent
    online-softmax streams (default :func:`default_split_k`); partials
    are combined outside the kernel.  ``interpret=True`` runs the same
    kernel body on the Pallas interpreter — the CPU test twin.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    quantized = is_quantized(k_pool)
    if quantized != is_quantized(v_pool):
        raise MXNetError("flash_decode_attention: mixed quantized / plain "
                         "K and V pools")
    kp = k_pool.payload if quantized else k_pool
    vp = v_pool.payload if quantized else v_pool
    b, h, hd = q.shape
    _, bs, _, _ = kp.shape
    nblk = tables.shape[1]

    splits = default_split_k(nblk) if split_k is None else int(split_k)
    if splits < 1:
        raise MXNetError(f"split_k must be >= 1, got {splits}")
    splits = min(splits, nblk)
    bps = -(-nblk // splits)                # blocks per split partition
    padded = splits * bps
    if padded != nblk:
        # pad with trash-slot entries: their logical positions are
        # >= nblk*bs >= every length, so the mask kills them.
        tables = jnp.pad(tables, ((0, 0), (0, padded - nblk)))

    kernel = partial(_decode_kernel, bps=bps, block_size=bs,
                     quantized=quantized, scale=softmax_scale(hd, scale))

    def kv_spec():
        return pl.BlockSpec(
            (None, bs, h, hd),
            lambda bi, si, pi, tref, lref: (tref[bi, si * bps + pi], 0, 0, 0))

    def scale_spec():
        return pl.BlockSpec(
            (None, bs, 1, 1),
            lambda bi, si, pi, tref, lref: (tref[bi, si * bps + pi], 0, 0, 0))

    in_specs = [
        pl.BlockSpec((None, h, hd), lambda bi, si, pi, tref, lref: (bi, 0, 0)),
        kv_spec(), kv_spec(),
    ]
    operands = [q, kp, vp]
    if quantized:
        in_specs += [scale_spec(), scale_spec()]
        # one scale per cached position, shaped to broadcast against the
        # [BS, H, hd] payload block with BS on the untiled leading dim
        operands += [k_pool.scale[:, :, None, None],
                     v_pool.scale[:, :, None, None]]

    out_specs = [
        pl.BlockSpec((None, None, h, hd),
                     lambda bi, si, pi, tref, lref: (bi, si, 0, 0)),
        pl.BlockSpec((None, None, h, 1),
                     lambda bi, si, pi, tref, lref: (bi, si, 0, 0)),
        pl.BlockSpec((None, None, h, 1),
                     lambda bi, si, pi, tref, lref: (bi, si, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((b, splits, h, hd), jnp.float32),
        jax.ShapeDtypeStruct((b, splits, h, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, splits, h, 1), jnp.float32),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, splits, bps),
        in_specs=in_specs,
        out_specs=out_specs,
    )
    with jax.enable_x64(False):
        acc, m, l = pl.pallas_call(
            kernel,
            name="mxtpu_flash_decode",
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands)

    # split-K combine: reweight each partition's partial by its distance
    # to the global running max, then one normalized sum.  Empty
    # partitions carry (m=NEG_INF, l=0, acc=0) and contribute nothing.
    m = m[..., 0]                                    # [B, S, H]
    l = l[..., 0]
    m_star = jnp.max(m, axis=1)                      # [B, H]
    w = jnp.exp(m - m_star[:, None, :])              # [B, S, H]
    l_star = jnp.maximum(jnp.sum(l * w, axis=1), 1e-30)
    out = jnp.sum(acc * w[..., None], axis=1) / l_star[..., None]
    return out.astype(q.dtype)
