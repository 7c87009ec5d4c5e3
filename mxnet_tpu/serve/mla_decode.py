"""MLA-decode: a Pallas kernel for one query position a row over a paged
LATENT cache (``kvcache`` kind ``paged_latent``; docs/serving.md).

Multi-head latent attention caches ONE row a position, ``[c | k_r]``
(the normed latent and the shared rotary key), and in its **absorbed**
form decode never up-projects it: with ``q~ = q_n W_kvb[keys]`` every
head's score is ``[q~ | q_r] . [c | k_r]`` and its output ``softmax(s)
c`` (then ``W_kvb[values]`` outside).  So a row's cached bytes are read
once for ALL heads: 128 heads x (576 + 512) x 2 FLOP for 1,152 bytes at
the published sizes, 242 FLOP a byte against a v5e's 240: at the ridge,
where the memory-bound ``mxtpu_flash_decode`` (a key and a value row a
head, block-diagonal q.k) is the wrong kernel and XLA's gather of a
table's blocks the wrong reader.

The kernel, ``mxtpu_mla_decode``:

* takes the WHOLE pool as stored, ``[num_layers, num_blocks,
  block_size, lanes]``, left in HBM (``memory_space=pl.ANY``), with the
  block tables, the rows' lengths and the layer as prefetched scalars;
* grid ``(rows, splits)``; a grid step walks its split's LIVE blocks in
  a loop of its own (``cdiv(length, block_size)`` of them, never the
  table's width), ``fold`` blocks an iteration copied into one of two
  VMEM buffers (``pltpu.make_async_copy``) while the ones before them
  are folded: PR 32's walk, ``flash_decode.walk_live_blocks``, which
  all three decode kernels take; what is folded differs in every line
  (one operand for keys and values, all heads one MXU operand, no
  block-diagonal query, no scales);
* both contractions on the MXU with M = heads: ``[H, lanes] x [N,
  lanes]^T`` for the scores and ``[H, N] x [N, rank]`` for the output,
  the values being the first ``rank`` lanes of the very rows the scores
  read.  bfloat16 pools multiply in bfloat16 (the probabilities are
  rounded to it, as a flash kernel does; three exact pieces would put
  the kernel over the ridge), float32 pools in float32 at highest
  precision;
* split-K partials ``(acc, m, l)`` combined outside, as flash-decode's.

``interpret=True`` runs the same body on the Pallas interpreter (the
CPU tests' twin, ``attn_impl="flash_interpret"``).
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..parallel.flash_attention import NEG_INF
from .flash_decode import walk_live_blocks

__all__ = ["mla_decode_attention", "default_split_k"]

#: cached positions an iteration folds (one ``[N, lanes]`` operand a
#: contraction, one max / sum / rescale for them all)
_FOLD_POSITIONS = 256


def default_split_k(nblk: int, block_size: int) -> int:
    """One partition up to 4,096 table positions, then one more for each
    further 4,096 (at most 8).  On a one-core chip the splits of a row
    run one after another and each writes a ``[heads, rank]`` float32
    partial, so they are kept few; they exist for what
    ``flash_decode.default_split_k`` says: a second core, and a bound on
    a partial's chain of rescales."""
    return max(1, min(8, -(-nblk * block_size // 4096)))


def _mla_kernel(tables_ref, lengths_ref, layer_ref, q_ref, pool_hbm,
                out_ref, m_ref, l_ref, acc_ref, buf, sems, *, bps: int,
                nblk: int, fold: int, block_size: int, rank_lanes: int,
                scale: np.float32):
    """One grid step: split ``s`` of row ``b``: the row's LIVE blocks
    ``s*bps .. min((s+1)*bps, cdiv(length, BS))``, ``fold`` an
    iteration, into the split's online-softmax partial."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    cd = q_ref.dtype
    exact = cd == jnp.bfloat16
    contract = partial(
        jax.lax.dot_general, preferred_element_type=f32,
        precision=None if exact else jax.lax.Precision.HIGHEST)

    b = pl.program_id(0)
    first = pl.program_id(1) * bps
    length = lengths_ref[b]
    live = jnp.minimum(pl.cdiv(length, block_size), nblk)
    limit = jnp.minimum(length, live * block_size)
    count = jnp.minimum(first + bps, live) - first
    trips = pl.cdiv(count, fold)
    layer_pool = pool_hbm.at[layer_ref[0]]

    def fold_in(j, slot, carry):
        m_prev, l_prev = carry                                   # [H, 1]
        rows = buf[slot].reshape(fold * block_size, buf.shape[-1])  # [N, lanes]
        # every head's score over the iteration's positions: ONE
        # contraction over a row's lanes (zero lanes meet zero lanes)
        s = contract(q_ref[...], rows, (((1,), (1,)), ((), ()))) * scale
        pos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = pos < limit
        s = jnp.where(valid, s, np.float32(NEG_INF))
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), np.float32(0.0))
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        # the values are the rows' first lanes: no second read
        pv = contract(p.astype(cd), rows[:, :rank_lanes],
                      (((1,), (0,)), ((), ())))                  # [H, rank]
        acc_ref[...] = acc_ref[...] * alpha + pv
        return m_new, l_new

    @pl.when(count > 0)
    def _live():
        def start():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            return (jnp.full(m_ref.shape, NEG_INF, f32),
                    jnp.zeros(l_ref.shape, f32))

        m, l = walk_live_blocks(
            lambda n: tables_ref[b, n], first, live, trips, fold,
            (layer_pool,), (buf,), sems, start, fold_in)
        m_ref[...] = m
        l_ref[...] = l
        out_ref[...] = acc_ref[...]

    @pl.when(count <= 0)
    def _empty():   # the partial that the combine weighs with nothing
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        out_ref[...] = jnp.zeros_like(out_ref)


def mla_decode_attention(q, pool, layer, tables, lengths, *, rank: int,
                         scale, split_k: Optional[int] = None,
                         interpret: bool = False):
    """``q`` [B, H, width]: the absorbed queries ``[q~ | q_r]`` (width =
    ``rank`` + rope); ``pool``: the whole latent pool ``[L, blocks, BS,
    lanes]`` and ``layer`` the layer to read; ``tables`` [B, max_blocks],
    ``lengths`` [B] (a row of length 0 reads nothing).  Returns each
    head's attention over the cached latents, ``softmax(s) c``: [B, H,
    rank] in ``q``'s type."""
    b, h, width = q.shape
    lanes = pool.shape[-1]
    if lanes % 128 or width > lanes or rank > width:
        raise MXNetError(
            f"mla_decode_attention: the pool stores {lanes} lanes a "
            f"position (whole 128-lane rows), the queries have {width} "
            f"of which {rank} are the latent")
    nblk = tables.shape[1]
    splits = (default_split_k(nblk, pool.shape[2]) if split_k is None
              else int(split_k))
    if splits < 1:
        raise MXNetError(f"split_k must be >= 1, got {splits}")
    return _mla_decode(q, pool, jnp.asarray(layer, jnp.int32), tables,
                       lengths, rank=int(rank), scale=np.float32(scale),
                       splits=min(splits, nblk), interpret=interpret)


@partial(jax.jit, static_argnames=("rank", "scale", "splits", "interpret"))
def _mla_decode(q, pool, layer, tables, lengths, *, rank: int, scale,
                splits: int, interpret: bool):
    """The kernel's call: its own ``jit`` with the layer an operand, so a
    program that reads every layer traces and lowers it once."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, width = q.shape
    _, _, bs, lanes = pool.shape
    nblk = tables.shape[1]
    fold = max(1, _FOLD_POSITIONS // bs)
    bps = -(-nblk // (splits * fold)) * fold      # whole iterations a split
    rank_lanes = min(-(-rank // 128) * 128, lanes)
    # multiply in the pool's type: bf16 x bf16 is exact in float32
    cd = jnp.bfloat16 if pool.dtype == jnp.bfloat16 else jnp.float32
    qp = jnp.pad(q.astype(cd), ((0, 0), (0, 0), (0, lanes - width)))

    kernel = partial(_mla_kernel, bps=bps, nblk=nblk, fold=fold,
                     block_size=bs, rank_lanes=rank_lanes, scale=scale)

    def row_spec(*block):       # a [b, splits, ...] output's (b, s) block
        return pl.BlockSpec(
            (None, None) + block,
            lambda bi, si, tref, lref, yref: (bi, si, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, splits),
        in_specs=[
            pl.BlockSpec((None, h, lanes),
                         lambda bi, si, tref, lref, yref: (bi, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM
        ],
        out_specs=[row_spec(h, rank_lanes), row_spec(h, 1), row_spec(h, 1)],
        scratch_shapes=[
            pltpu.VMEM((h, rank_lanes), jnp.float32),     # accumulator
            pltpu.VMEM((2, fold, bs, lanes), pool.dtype),  # double buffer
            pltpu.SemaphoreType.DMA((2,)),                # one a buffer
        ],
    )
    with jax.enable_x64(False):
        acc, m, l = pl.pallas_call(
            kernel,
            name="mxtpu_mla_decode",
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((b, splits, h, rank_lanes), jnp.float32),
                jax.ShapeDtypeStruct((b, splits, h, 1), jnp.float32),
                jax.ShapeDtypeStruct((b, splits, h, 1), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
          layer.reshape(1), qp, pool)

    # split-K combine: each partial reweighted by its distance to the
    # row's maximum; an empty one carries (0, NEG_INF, 0) and adds nothing
    acc = acc[..., :rank]                             # [B, S, H, rank]
    m_star = jnp.max(m, axis=1)                       # [B, H, 1]
    wgt = jnp.exp(m - m_star[:, None])                # [B, S, H, 1]
    l_star = jnp.maximum(jnp.sum(l * wgt, axis=1), np.float32(1e-30))
    return (jnp.sum(acc * wgt, axis=1) / l_star).astype(q.dtype)
