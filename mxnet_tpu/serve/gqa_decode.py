"""Grouped-query decode over paged K/V, bounded by a window: a Pallas
kernel, ``mxtpu_gqa_decode`` (docs/serving.md).

The softmax layers of a described model (``models/decoder.py``) have
fewer key/value heads than query heads, and a sliding layer sees only the
last ``window`` positions.  ``flash_decode.py``'s kernel serves the
in-tree LM (one query head a key/value head, every position) with a
block-diagonal query over all heads' lanes; this one is its grouped twin:

* the pools are ``kvcache.make_pools``' stored form, ``[num_layers,
  num_blocks, block_size, kv_heads * head_dim]``, left WHOLE in HBM
  (``memory_space=pl.ANY``) with the layer, the tables and the lengths
  as prefetched scalars;
* a grid step owns one row and walks that row's LIVE blocks in a loop of
  its own, ``_FOLD`` blocks an iteration, double-buffered (the next
  iteration's copies start before this one's are folded): the walk of
  ``flash_decode.walk_live_blocks``, which all three decode kernels
  take, handed where a row's window starts and its ring.  The walk
  runs from the block of the first position the row's query sees,
  ``max(0, length - window)``, to the block of ``length - 1``: a
  window layer reads ``ceil(window / block_size) + 1`` blocks a row
  whatever its length.  A window table is a ring (``ring`` columns,
  ``kvcache.ring_width``): logical block ``l`` is column ``l % ring``;
* key/value head ``c`` is lanes ``c * hd .. (c + 1) * hd`` of a block
  (one 128-lane row at ``head_dim`` 128), and its ``group`` query heads
  contract against it alone: scores ``[group, fold * block_size]`` a
  head on the MXU, the group padded to a whole sublane tile of 8 rows
  (48 heads over 8: 6 of 8 rows carry a head).  One online softmax
  (float32 max, sum and accumulator, ``NEG_INF`` masking) over all
  heads' rows at once; p.v a key/value head at a time, bf16 payloads
  multiplied exactly (the probabilities as three bf16 pieces, as
  ``flash_decode`` does).

No split-K: a v5e has one TensorCore, so the splits of a row would run
one after another (``flash_decode.default_split_k``), and a window row
is 33 blocks.  The output is normalised in the kernel.  Pinned against
``kvcache.gqa_decode_attention``'s XLA form by ``tests/test_trinity_serve.py``;
``interpret=True`` runs the same body on the CPU.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError
from ..parallel.flash_attention import NEG_INF
from .flash_decode import _FOLD, _split_bf16, walk_live_blocks

__all__ = ["gqa_decode"]


def _kernel(tables_ref, lengths_ref, layer_ref, q_ref, k_hbm, v_hbm, out_ref,
            acc_ref, kbuf, vbuf, sems, *, fold: int, block_size: int, kv: int,
            rows: int, head_dim: int, window: int, ring: int, scale):
    """One grid step: row ``b``.  ``q_ref`` [kv, rows, hd] (a key/value
    head's query heads, zero rows past the group), ``out_ref`` the
    same; ``acc_ref`` [kv, rows, hd] float32."""
    from jax.experimental import pallas as pl

    f32 = jnp.float32
    cd = q_ref.dtype if q_ref.dtype == jnp.bfloat16 else f32
    exact = cd == jnp.bfloat16
    contract = partial(
        jax.lax.dot_general, preferred_element_type=f32,
        precision=None if exact else jax.lax.Precision.HIGHEST)

    b = pl.program_id(0)
    length = lengths_ref[b]
    lo = jnp.maximum(length - window, 0) if window else 0
    first = lo // block_size
    live = pl.cdiv(length, block_size)
    trips = pl.cdiv(live - first, fold)
    k_layer, v_layer = k_hbm.at[layer_ref[0]], v_hbm.at[layer_ref[0]]

    def head(blocks, c):
        """Key/value head ``c``'s lanes of an iteration's blocks."""
        x = blocks[:, :, c * head_dim:(c + 1) * head_dim]
        return x.reshape(fold * block_size, head_dim).astype(cd)

    def fold_in(j, slot, carry):
        m_prev, l_prev = carry                              # [kv * rows, 1]
        kblocks, vblocks = kbuf.at[slot], vbuf.at[slot]
        s = jnp.concatenate(
            [contract(q_ref[c].astype(cd), head(kblocks, c),
                      (((1,), (1,)), ((), ()))) for c in range(kv)],
            axis=0) * scale                                 # [kv * rows, N]
        pos = j * block_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        valid = (pos >= lo) & (pos < length)
        s = jnp.where(valid, s, np.float32(NEG_INF))
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(valid, jnp.exp(s - m_new), np.float32(0.0))
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        for c in range(kv):
            pc = p[c * rows:(c + 1) * rows]
            o = contract(_split_bf16(pc) if exact else pc, head(vblocks, c),
                         (((1,), (0,)), ((), ())))          # [(3) rows, hd]
            if exact:
                o = o[:rows] + o[rows:2 * rows] + o[2 * rows:]
            acc_ref[c] = acc_ref[c] * alpha[c * rows:(c + 1) * rows] + o
        return m_new, l_new

    @pl.when(trips > 0)
    def _live():
        def start():
            acc_ref[...] = jnp.zeros_like(acc_ref)
            return (jnp.full((kv * rows, 1), NEG_INF, f32),
                    jnp.zeros((kv * rows, 1), f32))

        _, l = walk_live_blocks(
            lambda n: tables_ref[b, n % ring if ring else n], first, live,
            trips, fold, (k_layer, v_layer), (kbuf, vbuf), sems, start,
            fold_in)
        l = jnp.maximum(l, np.float32(1e-30))
        for c in range(kv):
            out_ref[c] = (acc_ref[c] / l[c * rows:(c + 1) * rows]).astype(
                out_ref.dtype)

    @pl.when(trips <= 0)
    def _empty():           # a row that attends nothing
        out_ref[...] = jnp.zeros_like(out_ref)


def gqa_decode(q, k_pool, v_pool, layer, tables, lengths, *, scale,
               window: int = 0, ring: int = 0, interpret: bool = False):
    """``q`` [B, H, hd]; the WHOLE pools and the ``layer`` to read;
    ``tables`` [B, columns] (a window table with ``ring``); ``lengths``
    [B] (0: the row attends nothing).  Returns [B, H, hd]."""
    b, h, hd = q.shape
    width = k_pool.shape[-1]
    if width % hd or h % (width // hd):
        raise MXNetError(f"gqa_decode: the pool stores {width} lanes a "
                         f"position, not whole heads of {hd} under {h} "
                         "query heads")
    return _gqa_decode(q, k_pool, v_pool, jnp.asarray(layer, jnp.int32),
                       tables, lengths, scale=np.float32(scale),
                       window=int(window), ring=int(ring),
                       interpret=interpret)


@partial(jax.jit, static_argnames=("scale", "window", "ring", "interpret"))
def _gqa_decode(q, k_pool, v_pool, layer, tables, lengths, *, scale, window,
                ring, interpret):
    """The kernel's call: its own ``jit``, the layer an operand, so that
    a program traces and lowers it once a kind of layer."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, hd = q.shape
    _, _, bs, width = k_pool.shape
    kv = width // hd
    group = h // kv
    rows = -(-group // 8) * 8                   # whole sublane tiles a head
    # Mosaic copies whole 128-lane rows out of HBM: every deployed width
    # is some; a narrower pool (the CPU tests' tiny models) is padded
    rowed = -(-width // 128) * 128
    if rowed != width:
        k_pool, v_pool = (jnp.pad(p, ((0, 0),) * 3 + ((0, rowed - width),))
                          for p in (k_pool, v_pool))
    qg = jnp.pad(q.reshape(b, kv, group, hd),
                 ((0, 0), (0, 0), (0, rows - group), (0, 0)))
    kernel = partial(_kernel, fold=_FOLD, block_size=bs, kv=kv, rows=rows,
                     head_dim=hd, window=window, ring=ring, scale=scale)
    row = pl.BlockSpec((None, kv, rows, hd),
                       lambda bi, tref, lref, yref: (bi, 0, 0, 0))
    whole = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[row, whole, whole],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((kv, rows, hd), jnp.float32),          # accumulator
            pltpu.VMEM((2, _FOLD, bs, rowed), k_pool.dtype),  # K, 2 buffers
            pltpu.VMEM((2, _FOLD, bs, rowed), v_pool.dtype),  # V
            pltpu.SemaphoreType.DMA((2,)),
        ],
    )
    with jax.enable_x64(False):
        out = pl.pallas_call(
            kernel,
            name="mxtpu_gqa_decode",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(tables.astype(jnp.int32), lengths.astype(jnp.int32),
          layer.reshape(1), qg, k_pool, v_pool)
    return out[:, :, :group].reshape(b, h, hd)
