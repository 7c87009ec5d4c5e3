"""Flash-decode: a Pallas kernel for paged KV-cache reads (docs/serving.md).

Decode attention is HBM-bound — each step streams every cached K/V
position of every running request once, does ~4 flops per byte, and
throws the bytes away.  The generic ``kvcache._attend_blocks`` scan
expresses that stream as one gather + softmax-update op chain per block
column, which XLA schedules as independent HLOs; in this kernel the
whole per-request scan becomes **one fused Pallas program** that

* prefetches the block *tables*, the rows' *lengths* and the layer as
  scalars and takes the WHOLE pools as they are stored, ``[num_layers,
  num_blocks, block_size, heads * head_dim]`` (``kvcache.make_pools``),
  left in HBM (``memory_space=pl.ANY``): no layer's slice is taken
  outside the kernel and the pool is never re-laid-out on its behalf.
  A grid step owns one split of one row and **walks that row's live
  blocks in a loop of its own**: ``cdiv(length, block_size)`` of them,
  not the table's width (a table is as wide as ``max_seq_len``; at the
  benchmark's serving shapes 84-99 % of its columns hold nothing).  An
  iteration takes ``_FOLD`` blocks: blocks ``tables[b, j
  ..]`` of the layer are copied into one of two VMEM buffers
  (``pltpu.make_async_copy``) while the ones before them are folded, so
  a live block crosses HBM -> VMEM once (the row's last up to ``_FOLD``
  times, to fill the last iteration with something finite) and a dead
  column, or a pool block no live prefix lists, is never read (the
  walk is :func:`walk_live_blocks`, which ``mla_decode.py`` and
  ``gqa_decode.py`` take too: only what a kernel folds differs).  A
  ``[block_size, heads * head_dim]`` block fills every one of the 128
  lanes (at 32 heads x 64 it is one bf16 sublane tile by 16 lane rows);
* runs **split-K across block partitions**: the grid is ``(batch,
  splits)`` and split ``s`` folds table columns ``s * bps .. (s + 1) *
  bps`` (those of them that are live) into an independent
  online-softmax partial ``(acc, m, l)``; a split with no live column
  writes ``(0, NEG_INF, 0)`` and costs a grid step's overhead.  Partials
  combine outside the kernel in one cheap f32 pass (``exp(m_s - m*)``
  reweighting — the standard flash-decoding reduction).  What the
  splits buy is in :func:`default_split_k`;
* dequantizes **fp8 pools in-kernel**: a :class:`~.kvcache.QuantPool`'s
  e4m3 payload is copied like any block, so the HBM traffic is the
  1-byte payload, not a pre-widened f32 copy.  One scale a position:
  the scores take K's, the probabilities V's.  The scales of a row's
  table columns arrive with its query, gathered by XLA (``[batch,
  columns, block_size]`` floats a call): Mosaic copies whole 128-lane
  rows out of HBM, and a block's scales are ``block_size`` lanes of
  ``[num_layers, num_blocks, block_size]``.

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
``kvcache.dense_attention`` by ``tests/test_flash_decode.py``.  The
kernel runs under ``interpret=True`` on CPU
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
    """Split-K heuristic: tables of up to 8 columns stay one partition
    (no combine); wider ones split into ``cdiv(columns, 8)`` partitions,
    at most 8.

    What a split buys depends on the chip.  Every grid dimension is
    ``arbitrary`` and a v5e has one TensorCore, so there the splits of a
    row run one after another: nothing runs side by side, each live
    split pays a grid step's set-up, its block-diagonal query and the
    un-hidden copy of its first block, and a split with no live column
    costs a grid step.  They are kept because the partials are what a
    second core needs to take half of a long row (the split dimension
    is the one to declare ``parallel`` on a two-core chip: one row of
    32k tokens is otherwise one core's serial chain), and they bound a
    partial's chain of ``exp(m - m_new)`` rescales to ``bps`` blocks."""
    if nblk <= 8:
        return 1
    return min(8, -(-nblk // 8))


# Blocks a loop iteration fetches and folds: one ``[_FOLD * BS, 128]``
# operand a contraction and one max / sum / rescale for them all, which
# is what a block costs (two MXU round trips, whatever its size).  On a
# v5e, bf16 ``[16, 2048]`` blocks, ms a call at 1 / 2 / 4 / 8: 32 short
# rows 0.435 / 0.31 / 0.26 / 0.27, two live rows of 32 0.079 / 0.08 /
# 0.073 / 0.075, six rows that fill their tables 0.417 / 0.282 / 0.221 /
# 0.220 (PERF.md section 6, PR 32).
_FOLD = 4


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


def walk_live_blocks(block, first, live, trips, fold, srcs, bufs, sems,
                     start, body):
    """PR 32's walk over a row's LIVE blocks, the one every paged decode
    kernel takes (``mxtpu_flash_decode``, ``mxtpu_mla_decode``,
    ``mxtpu_gqa_decode``).  ``trips`` iterations; iteration ``i`` takes
    logical blocks ``j = first + i * fold`` on, ``fold`` of them, and past
    ``live - 1`` that block again (the body masks by position: what
    multiplies a zero probability has to be finite).  Pool block
    ``block(logical)`` of each source in ``srcs`` (a layer of a pool, left
    in HBM) is copied into the same place of its buffer in ``bufs``
    (VMEM, ``[2, fold, ...]``), all on the slot's semaphore in ``sems``:
    double-buffered, iteration ``i + 1``'s copies start before ``i``'s
    are waited for.  ``start()`` runs once the first copies are on their
    way and returns the loop's first carry; ``body(j, slot, carry)``
    folds an iteration's blocks, waited for in slot ``slot``.  Returns
    the last carry."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def copies(i, slot):
        """The copies that bring iteration ``i``'s blocks into ``slot``
        (built anew to be waited for)."""
        # (a traced index costs a ref's every use some tracing: once each)
        dsts, sem = [buf.at[slot] for buf in bufs], sems.at[slot]
        out = []
        for g in range(fold):
            blk = block(jnp.minimum(first + i * fold + g, live - 1))
            out += [pltpu.make_async_copy(src.at[blk], dst.at[g], sem)
                    for src, dst in zip(srcs, dsts)]
        return out

    def fetch(i, slot):
        for copy in copies(i, slot):
            copy.start()

    def fold_in(i, carry):
        j, slot = first + i * fold, i % 2

        @pl.when(i + 1 < trips)
        def _next():
            fetch(i + 1, 1 - slot)

        for copy in copies(i, slot):
            copy.wait()
        return body(j, slot, carry)

    fetch(0, 0)
    return jax.lax.fori_loop(0, trips, fold_in, start())


def _decode_kernel(*refs, bps: int, nblk: int, fold: int, block_size: int,
                   heads: int, head_dim: int, quantized: bool,
                   scale: np.float32):
    """One grid step: split ``s`` of request ``b``.  Walks the row's LIVE
    blocks ``s*bps .. min((s+1)*bps, cdiv(length, BS))``, ``fold`` of
    them an iteration, bringing blocks ``tables[b, j]`` of the layer from
    the pool in HBM into one of two VMEM buffers while the ones before
    them are folded into the split's online-softmax partial.

    Ref layout (scalar-prefetch args first, then inputs, outputs,
    scratch): ``tables, lengths, layer, q, k, v[, kscale, vscale], out,
    m, l, qm, acc, kbuf, vbuf, sems`` (``k`` and ``v`` are the whole
    pools, in HBM; the scales are those of the row's table columns,
    ``[columns / fold, fold * BS]``).
    """
    (tables_ref, lengths_ref, layer_ref, q_ref, k_hbm, v_hbm, *scale_refs,
     out_ref, m_ref, l_ref, qm_ref, acc_ref, kbuf, vbuf, sems) = refs
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

    def operand(blocks, c):
        """Lane chunk ``c`` of an iteration's K or V blocks, ``[fold * BS,
        W]`` in the multiplying dtype (fp8 widens through f32: Mosaic
        has no fp8 -> bf16 cast)."""
        x = blocks[:, :, lanes(c)]
        x = (x.astype(f32) if quantized else x).astype(cd)
        return x.reshape(fold * block_size, w)

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
    first = pl.program_id(1) * bps
    # the split's live blocks: none of a row shorter than the split's
    # first position, so a table as wide as max_seq_len costs a row only
    # what it holds
    length = lengths_ref[b]
    live = jnp.minimum(pl.cdiv(length, block_size), nblk)
    limit = jnp.minimum(length, live * block_size)
    count = jnp.minimum(first + bps, live) - first
    trips = pl.cdiv(count, fold)

    k_layer, v_layer = k_hbm.at[layer_ref[0]], v_hbm.at[layer_ref[0]]

    def fold_in(j, slot, carry):
        """Blocks ``j ..``, waited for in their buffer, into (m, l) and
        the accumulator; the ones after them are already on their way."""
        m_prev, l_prev = carry                                   # [H, 1]
        kblocks, vblocks = kbuf.at[slot], vbuf.at[slot]

        # scores [H, fold * BS], heads on sublanes and positions on
        # lanes: each chunk contracts its 128 lanes for the heads of its
        # group (the group's other heads meet zeros of the block-diagonal
        # query)
        parts = [None] * ngroup
        for c in range(nchunk):
            g = group(c)
            part = contract(qm_ref[rows(g), lanes(c)], operand(kblocks, c),
                            (((1,), (1,)), ((), ())))       # [R, fold * BS]
            parts[g] = part if parts[g] is None else parts[g] + part
        s = parts[0] if ngroup == 1 else jnp.concatenate(parts, axis=0)
        if quantized:
            # one scale a position: the iteration's row of the table's,
            # positions along the lanes as the scores want them
            srow = pl.ds(j // fold, 1)
            s = s * kscale_ref[srow, :]                    # [1, fold * BS]
        s = s * scale

        pos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < limit
        # f32-typed constants: weak python-float literals re-materialize at
        # lowering time and can widen to f64 under an ambient x64 context.
        s = jnp.where(valid, s, np.float32(NEG_INF))

        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)                          # [H, 1]
        pmat = jnp.where(valid, jnp.exp(s - m_new), np.float32(0.0))
        l_new = l_prev * alpha + jnp.sum(pmat, axis=1, keepdims=True)
        if quantized:
            # (the scale of a position past the length is anything)
            pmat = pmat * jnp.where(valid[:1], vscale_ref[srow, :],
                                    np.float32(0.0))

        # p.v: a chunk of V under its group's probabilities.  The rows of
        # other heads come out as garbage sums nobody reads (the finish
        # keeps each head's own lanes).  A bf16 (or fp8) payload is
        # multiplied exactly: the probabilities go in as three bf16 pieces.
        lhs = [_split_bf16(pmat[rows(g)]) if exact else pmat[rows(g)]
               for g in range(ngroup)]
        for c in range(nchunk):
            o = contract(lhs[group(c)], operand(vblocks, c),
                         (((1,), (0,)), ((), ())))                # [R or 3R, W]
            if exact:
                o = o[:r] + o[r:2 * r] + o[2 * r:]
            acc_ref[c] = acc_ref[c] * alpha[rows(group(c))] + o
        return m_new, l_new

    @pl.when(count > 0)
    def _live():
        def start():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            # the query as a block-diagonal [H, H*hd]: row h keeps head
            # h's lanes, so q.k for every head is ONE contraction over the
            # lanes of a chunk, on the MXU, with exact products
            for c in range(nchunk):
                qc = jnp.broadcast_to(q_ref[:, lanes(c)].astype(f32), (r, w))
                qm_ref[rows(group(c)), lanes(c)] = jnp.where(
                    owned(c), qc, np.float32(0.0)).astype(cd)
            return (jnp.full(m_ref.shape, NEG_INF, f32),
                    jnp.zeros(l_ref.shape, f32))

        m, l = walk_live_blocks(
            lambda n: tables_ref[b, n], first, live, trips, fold,
            (k_layer, v_layer), (kbuf, vbuf), sems, start, fold_in)
        m_ref[...] = m
        l_ref[...] = l
        for c in range(nchunk):     # each head's own lanes of its row
            out_ref[:, lanes(c)] = jnp.sum(
                jnp.where(owned(c), acc_ref[c], np.float32(0.0)),
                axis=0, keepdims=True)

    @pl.when(count <= 0)
    def _empty():   # the partial that the combine weighs with nothing
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        out_ref[...] = jnp.zeros_like(out_ref)


def flash_decode_attention(q, k_pool, v_pool, layer, tables, lengths, *,
                           scale: Optional[float] = None,
                           split_k: Optional[int] = None,
                           interpret: bool = False):
    """Drop-in twin of ``kvcache.paged_attention``: ``q`` [B, H, hd],
    the WHOLE pools (plain arrays or :class:`~.kvcache.QuantPool`) and
    the ``layer`` to read, ``tables`` [B, max_blocks], ``lengths`` [B].
    Returns [B, H, hd].

    ``split_k`` partitions the table's columns into that many
    online-softmax partials (default :func:`default_split_k`), combined
    outside the kernel.  ``interpret=True`` runs the same
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
    reaches the kernel as a prefetched scalar): a program that reads
    every layer traces and lowers this ONCE and calls it ``num_layers``
    times, where the kernel's body, traced a layer, was 0.3 s each of a
    24-layer decode program's set-up."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    quantized = is_quantized(k_pool)
    kp = k_pool.payload if quantized else k_pool
    vp = v_pool.payload if quantized else v_pool
    b, h, hd = q.shape
    _, _, bs, width = kp.shape
    # Mosaic copies whole 128-lane rows out of HBM.  Every deployed width
    # is some; a narrower pool (the tiny models of the tests) is padded
    # to one, which copies it
    rowed = -(-width // 128) * 128
    if rowed != width:
        kp, vp = (jnp.pad(p, ((0, 0),) * 3 + ((0, rowed - width),))
                  for p in (kp, vp))
    nblk = tables.shape[1]
    # table columns per split, whole iterations of the kernel's loop
    bps = -(-nblk // (splits * _FOLD)) * _FOLD

    # both contractions multiply in bf16 when queries and payload hold no
    # more than bf16 does (fp8 widens to it exactly), else in float32
    narrow = quantized or kp.dtype == jnp.bfloat16
    cd = jnp.bfloat16 if narrow and q.dtype == jnp.bfloat16 else jnp.float32
    kernel = partial(_decode_kernel, bps=bps, nblk=nblk, fold=_FOLD,
                     block_size=bs, heads=h, head_dim=hd, quantized=quantized,
                     scale=scale)

    def row_spec(*block):       # a [b, splits, ...] output's (b, s) block
        return pl.BlockSpec(
            (None, None) + block,
            lambda bi, si, tref, lref, yref: (bi, si, 0, 0))

    whole = pl.BlockSpec(memory_space=pl.ANY)   # stays in HBM; the kernel
    in_specs = [                                # copies the blocks it reads
        pl.BlockSpec((None, 1, width),
                     lambda bi, si, tref, lref, yref: (bi, 0, 0)),
        whole, whole,
    ]
    operands = [q.reshape(b, 1, width), kp, vp]
    w, r = _lane_chunks(h, hd)
    scratch_shapes = [
        pltpu.VMEM((h, width), cd),                  # block-diagonal q
        pltpu.VMEM((width // w, r, w), jnp.float32),  # accumulator
        pltpu.VMEM((2, _FOLD, bs, rowed), kp.dtype),  # K, double-buffered
        pltpu.VMEM((2, _FOLD, bs, rowed), vp.dtype),  # V
        pltpu.SemaphoreType.DMA((2,)),               # one a buffer
    ]
    if quantized:
        # Mosaic copies whole 128-lane rows out of HBM and a block's
        # scales are BS lanes of [L, blocks, BS], so XLA gathers the
        # tables' scale rows (B x nblk x BS floats) and a row's arrive
        # with its query, an iteration's side by side along the lanes
        cols = splits * bps
        padded = jnp.pad(tables, ((0, 0), (0, cols - nblk)))
        in_specs += [pl.BlockSpec(
            (None, cols // _FOLD, _FOLD * bs),
            lambda bi, si, tref, lref, yref: (bi, 0, 0))] * 2
        operands += [p.scale[layer, padded].reshape(b, cols // _FOLD, -1)
                     for p in (k_pool, v_pool)]

    out_shape = [
        jax.ShapeDtypeStruct((b, splits, 1, width), jnp.float32),
        jax.ShapeDtypeStruct((b, splits, h, 1), jnp.float32),
        jax.ShapeDtypeStruct((b, splits, h, 1), jnp.float32),
    ]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, splits),
        in_specs=in_specs,
        out_specs=[row_spec(1, width), row_spec(h, 1), row_spec(h, 1)],
        scratch_shapes=scratch_shapes,
    )
    with jax.enable_x64(False):
        acc, m, l = pl.pallas_call(
            kernel,
            name="mxtpu_flash_decode",
            grid_spec=grid_spec,
            out_shape=out_shape,
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
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
