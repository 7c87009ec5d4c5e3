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
  (the gather indirection compiles into the block pipeline itself).  The
  operand is the WHOLE pool in its stored form, ``[num_layers,
  num_blocks, block_size, heads * head_dim]`` (``kvcache.make_pools``),
  and the block map is ``(layer, tables[b, j], 0, 0)``: no layer's slice
  is taken outside the kernel, and a ``[block_size, heads * head_dim]``
  block fills every one of the 128 lanes (at 32 heads x 64 it is one
  bf16 sublane tile by 16 lane rows), which is the layout XLA gives the
  buffer anyway, so the pool is never re-laid-out on the kernel's
  behalf;
* runs **split-K across block partitions** for long contexts: the grid
  is ``(batch, splits, blocks_per_split)`` and each split accumulates an
  independent online-softmax partial ``(acc, m, l)``, so a 32k-token
  context becomes ``splits`` concurrent streams instead of one long
  serial scan.  Partials combine outside the kernel in one cheap f32
  pass (``exp(m_s - m*)`` reweighting — the standard flash-decoding
  reduction).  A table column wholly past its row's length folds
  nothing in and is skipped;
* dequantizes **fp8 pools in-kernel**: a :class:`~.kvcache.QuantPool`
  ships its e4m3 payload and per-position f32 scales as separate block
  streams, so the HBM traffic is the 1-byte payload, not a pre-widened
  f32 copy.  One scale a position: the scores take K's, the
  probabilities V's.

Heads lie side by side along the lanes, so a contraction per head is
block-diagonal: head ``h`` owns lanes ``h*hd .. (h+1)*hd``.  Both run on
the MXU, a 128-lane chunk (``128 // hd`` whole heads) at a time.  q.k
contracts a chunk of K against the same chunk of the query laid out
block-diagonally (``[heads, heads * head_dim]``, row ``h`` zero outside
head ``h``'s lanes; built once a split): bf16 times bf16 is exact in
float32, so the scores are float32 sums of exact products.  p.v
contracts the probabilities of the chunk's group of heads against the
chunk of V, the float32 probabilities as three bf16 pieces whose sum is
the float32 (float32 pools multiply in float32 at highest precision
instead); each head keeps its own lanes of its accumulator row when the
split finishes.

Numerics match the reference scan: f32 scores, max, sum and accumulator,
``NEG_INF`` masking, the same ``exp(m - m_new)`` rescale — pinned against
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


def _lane_chunks(heads: int, head_dim: int):
    """How the kernel walks a ``[BS, H * hd]`` block: ``(W, R)`` = lanes
    a chunk, head rows a group.  Head ``h`` owns lanes ``h*hd ..
    (h+1)*hd``, so a contraction per head is block-diagonal; a chunk of
    one 128-lane row holds ``128 // hd`` whole heads, and those heads sit
    in one group of 8 rows (one f32 sublane tile), so each chunk costs
    one ``[8, 128]`` operand and not ``[H, H * hd]``.  Widths that do not
    tile that way run as one chunk and one group — the same code, which
    is what the tiny models of the CPU tests go through."""
    width = heads * head_dim
    if width % 128 == 0 and 128 % head_dim == 0:
        rows = 8 if heads % 8 == 0 else heads
        if rows % (128 // head_dim) == 0:
            return 128, rows
    return width, heads


def _split_bf16(x):
    """An f32 ``[R, n]`` as three stacked bf16 pieces ``[3R, n]`` whose
    sum is ``x`` to the last of its 24 mantissa bits: the MXU multiplies
    bf16 exactly into f32, so a contraction of the pieces against a
    bf16 operand, summed, is the float32 contraction."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    r1 = x - x.astype(bf16).astype(f32)
    r2 = r1 - r1.astype(bf16).astype(f32)
    return jnp.concatenate([x, r1, r2], axis=0).astype(bf16)


def _decode_kernel(*refs, bps: int, block_size: int, heads: int,
                   head_dim: int, quantized: bool, scale: np.float32):
    """One grid step: fold logical block ``j = s*bps + p`` of request
    ``b`` into split ``s``'s online-softmax partial.

    Ref layout (scalar-prefetch args first, then inputs, outputs,
    scratch): ``tables, lengths, layer, q, k, v[, kscale, vscale], out,
    m, l, qm, acc`` (the layer is the block maps' alone).
    """
    (tables_ref, lengths_ref, _, q_ref, k_ref, v_ref, *scale_refs,
     out_ref, m_ref, l_ref, qm_ref, acc_ref) = refs
    kscale_ref, vscale_ref = scale_refs if quantized else (None, None)

    from jax.experimental import pallas as pl

    f32 = jnp.float32
    width = heads * head_dim
    w, r = _lane_chunks(heads, head_dim)
    nchunk, ngroup, hpc = width // w, heads // r, w // head_dim
    cd = qm_ref.dtype                   # what both contractions multiply in
    exact = cd == jnp.bfloat16          # bf16 x bf16 is exact in one pass
    contract = partial(
        jax.lax.dot_general, preferred_element_type=f32,
        precision=None if exact else jax.lax.Precision.HIGHEST)

    def lanes(c):
        return slice(c * w, (c + 1) * w)

    def operand(ref, c):
        """Lane chunk ``c`` of a K or V block in the multiplying dtype
        (fp8 widens through f32: Mosaic has no fp8 -> bf16 cast)."""
        x = ref[:, lanes(c)]
        return (x.astype(f32) if quantized else x).astype(cd)

    def group(c):
        """The group of head rows that chunk ``c``'s heads sit in."""
        return c * hpc // r

    def rows(g):
        return slice(g * r, (g + 1) * r)

    def owned(c):
        """Which (row of the chunk's group, lane of the chunk) pairs are
        a head's own lanes: ``[R, W]``."""
        first = c * hpc % r             # the chunk's first head, in its group
        head = jax.lax.broadcasted_iota(jnp.int32, (r, w), 0) - first
        lane = jax.lax.broadcasted_iota(jnp.int32, (r, w), 1)
        return (lane >= head * head_dim) & (lane < (head + 1) * head_dim)

    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():  # fresh partial per (request, split)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        # the query as a block-diagonal [H, H*hd]: row h keeps head h's
        # lanes, so q.k for every head is ONE contraction over the lanes
        # of a chunk, on the MXU, with exact products
        for c in range(nchunk):
            qc = jnp.broadcast_to(q_ref[:, lanes(c)].astype(f32), (r, w))
            qm_ref[rows(group(c)), lanes(c)] = jnp.where(
                owned(c), qc, np.float32(0.0)).astype(cd)

    # logical block index of this grid step -> absolute positions
    j = pl.program_id(1) * bps + p

    # A column past the row's length folds nothing in (every score
    # NEG_INF: m and l unmoved, alpha 1, p 0), so it is not computed: a
    # table is as wide as max_seq_len and most of a row's columns are
    # dead.  The grid still walks them (ROADMAP S2 bounds the walk).
    @pl.when(j * block_size < lengths_ref[b])
    def _fold():
        if quantized:
            # one scale a position: the 8-slot tile of scales this step's
            # block sits in, BS along the lanes as the scores want it
            srow = pl.ds(tables_ref[b, j] % kscale_ref.shape[0], 1)

        # scores [H, BS], heads on sublanes and positions on lanes: each
        # chunk contracts its 128 lanes for the heads of its group (the
        # group's other heads meet zeros of the block-diagonal query)
        parts = [None] * ngroup
        for c in range(nchunk):
            g = group(c)
            part = contract(qm_ref[rows(g), lanes(c)], operand(k_ref, c),
                            (((1,), (1,)), ((), ())))             # [R, BS]
            parts[g] = part if parts[g] is None else parts[g] + part
        s = parts[0] if ngroup == 1 else jnp.concatenate(parts, axis=0)
        if quantized:
            s = s * kscale_ref[srow, :]                           # [1, BS]
        s = s * scale

        pos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < lengths_ref[b]
        # f32-typed constants: weak python-float literals re-materialize at
        # lowering time and can widen to f64 under an ambient x64 context.
        s = jnp.where(valid, s, np.float32(NEG_INF))

        m_prev = m_ref[...]                                      # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                          # [H, 1]
        pmat = jnp.where(valid, jnp.exp(s - m_new), np.float32(0.0))
        l_ref[...] = l_ref[...] * alpha + jnp.sum(pmat, axis=1, keepdims=True)
        m_ref[...] = m_new
        if quantized:
            pmat = pmat * vscale_ref[srow, :]

        # p.v: a chunk of V under its group's probabilities.  The rows of
        # other heads come out as garbage sums nobody reads (_finish keeps
        # each head's own lanes).  A bf16 (or fp8) payload is multiplied
        # exactly: the probabilities go in as three bf16 pieces.
        lhs = [_split_bf16(pmat[rows(g)]) if exact else pmat[rows(g)]
               for g in range(ngroup)]
        for c in range(nchunk):
            o = contract(lhs[group(c)], operand(v_ref, c),
                         (((1,), (0,)), ((), ())))                # [R or 3R, W]
            if exact:
                o = o[:r] + o[r:2 * r] + o[2 * r:]
            acc_ref[c] = acc_ref[c] * alpha[rows(group(c))] + o

    @pl.when(p == bps - 1)
    def _finish():  # each head's own lanes of its accumulator row
        for c in range(nchunk):
            out_ref[:, lanes(c)] = jnp.sum(
                jnp.where(owned(c), acc_ref[c], np.float32(0.0)),
                axis=0, keepdims=True)


def flash_decode_attention(q, k_pool, v_pool, layer, tables, lengths, *,
                           scale: Optional[float] = None,
                           split_k: Optional[int] = None,
                           interpret: bool = False):
    """Drop-in twin of ``kvcache.paged_attention``: ``q`` [B, H, hd],
    the WHOLE pools (plain arrays or :class:`~.kvcache.QuantPool`) and
    the ``layer`` to read, ``tables`` [B, max_blocks], ``lengths`` [B].
    Returns [B, H, hd].

    ``split_k`` partitions the logical blocks into that many concurrent
    online-softmax streams (default :func:`default_split_k`); partials
    are combined outside the kernel.  ``interpret=True`` runs the same
    kernel body on the Pallas interpreter — the CPU test twin.
    """
    if is_quantized(k_pool) != is_quantized(v_pool):
        raise MXNetError("flash_decode_attention: mixed quantized / plain "
                         "K and V pools")
    b, h, hd = q.shape
    width = (k_pool.payload if is_quantized(k_pool) else k_pool).shape[-1]
    if width != h * hd:
        raise MXNetError(f"flash_decode_attention: the pool stores {width} "
                         f"lanes a position, the queries have {h} x {hd}")
    nblk = tables.shape[1]
    splits = default_split_k(nblk) if split_k is None else int(split_k)
    if splits < 1:
        raise MXNetError(f"split_k must be >= 1, got {splits}")
    return _flash_decode(q, k_pool, v_pool, jnp.asarray(layer, jnp.int32),
                         tables, lengths, scale=softmax_scale(hd, scale),
                         splits=min(splits, nblk), interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "splits", "interpret"))
def _flash_decode(q, k_pool, v_pool, layer, tables, lengths, *, scale,
                  splits: int, interpret: bool):
    """The kernel's call.  Its own ``jit`` with the layer an operand (it
    reaches the block maps as a prefetched scalar): a program that reads
    every layer traces and lowers this ONCE and calls it ``num_layers``
    times, where the kernel's body, traced a layer, was 0.3 s each of a
    24-layer decode program's set-up."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    quantized = is_quantized(k_pool)
    kp = k_pool.payload if quantized else k_pool
    vp = v_pool.payload if quantized else v_pool
    b, h, hd = q.shape
    _, nb, bs, width = kp.shape
    nblk = tables.shape[1]
    bps = -(-nblk // splits)                # blocks per split partition
    padded = splits * bps
    if padded != nblk:
        # pad with trash-slot entries: their logical positions are
        # >= nblk*bs >= every length, so the mask kills them.
        tables = jnp.pad(tables, ((0, 0), (0, padded - nblk)))

    # both contractions multiply in bf16 when queries and payload hold no
    # more than bf16 does (fp8 widens to it exactly), else in float32
    narrow = quantized or kp.dtype == jnp.bfloat16
    cd = jnp.bfloat16 if narrow and q.dtype == jnp.bfloat16 else jnp.float32
    kernel = partial(_decode_kernel, bps=bps, block_size=bs, heads=h,
                     head_dim=hd, quantized=quantized, scale=scale)

    def kv_spec():      # one block of one layer, straight out of the pool
        return pl.BlockSpec(
            (None, None, bs, width),
            lambda bi, si, pi, tref, lref, yref: (
                yref[0], tref[bi, si * bps + pi], 0, 0))

    # the scales of 8 slots (one f32 sublane tile; [L, blocks, BS] has
    # no smaller legal block): the kernel takes its slot's row
    srows = min(8, nb)

    def scale_spec():
        return pl.BlockSpec(
            (None, srows, bs),
            lambda bi, si, pi, tref, lref, yref: (
                yref[0], tref[bi, si * bps + pi] // srows, 0))

    def row_spec(*block):       # a [b, splits, ...] output's (b, s) block
        return pl.BlockSpec(
            (None, None) + block,
            lambda bi, si, pi, tref, lref, yref: (bi, si, 0, 0))

    in_specs = [
        pl.BlockSpec((None, 1, width),
                     lambda bi, si, pi, tref, lref, yref: (bi, 0, 0)),
        kv_spec(), kv_spec(),
    ]
    operands = [q.reshape(b, 1, width), kp, vp]
    if quantized:
        in_specs += [scale_spec(), scale_spec()]
        operands += [k_pool.scale, v_pool.scale]

    out_shape = [
        jax.ShapeDtypeStruct((b, splits, 1, width), jnp.float32),
        jax.ShapeDtypeStruct((b, splits, h, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, splits, h, 1), jnp.float32),
    ]

    w, r = _lane_chunks(h, hd)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, splits, bps),
        in_specs=in_specs,
        out_specs=[row_spec(1, width), row_spec(h, 1), row_spec(h, 1)],
        scratch_shapes=[
            pltpu.VMEM((h, width), cd),                  # block-diagonal q
            pltpu.VMEM((width // w, r, w), jnp.float32),  # accumulator
        ],
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
        )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
          layer.reshape(1), *operands)

    # split-K combine: reweight each partition's partial by its distance
    # to the global running max, then one normalized sum.  Empty
    # partitions carry (m=NEG_INF, l=0, acc=0) and contribute nothing.
    acc = acc.reshape(b, splits, h, hd)
    m = m[..., 0]                                    # [B, S, H]
    l = l[..., 0]
    m_star = jnp.max(m, axis=1)                      # [B, H]
    wgt = jnp.exp(m - m_star[:, None, :])            # [B, S, H]
    l_star = jnp.maximum(jnp.sum(l * wgt, axis=1), 1e-30)
    out = jnp.sum(acc * wgt[..., None], axis=1) / l_star[..., None]
    return out.astype(q.dtype)
