"""Retention decode: a Pallas kernel for the recurrent-state cache.

A decode step of a power-retention layer (``models/retention.py``)
reads every live request's state once and writes it once:
``S <- exp(g) S + phi(k) v'^T`` and ``y = phi(q)^T S`` for the query
heads that share the state.  There is nothing to reuse — 36 MB a
request and layer at head size 128 against a few hundred FLOPs a byte
short of the MXU's break-even — so the step is bound by HBM, and what a
kernel can add is (a) the pool updated **in place**
(``input_output_aliases``: the pool a step returns is the buffer it was
given, and no program copies it whole), (b) each state tile read from
HBM once for the update and all ``group`` query heads, and (c) rows that
are not active left alone (their slot is the trash slot 0).

Grid ``(rows, kv_heads, chunk tiles)``: one step moves ``tc`` chunks of
``[rows_of_S, hd]`` float32 in and out; the query read accumulates in a
VMEM scratch over the chunk tiles and leaves as ``[group, rows_of_S]``
(numerators, with the normaliser in channel ``hd``).  ``phi`` of the
keys and queries is made by XLA outside (2 % of the state's bytes); the
values come lane-broadcast for the same reason a matmul is not used:
with one key a row there is no contraction, only a broadcast
multiply-add on the VPU.  ``interpret=True`` runs the same body on the
CPU (tier-1); :func:`retention_decode_xla` is its ``jax.numpy`` twin.
"""
from __future__ import annotations

from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

from ..models import retention

__all__ = ["retention_decode", "retention_decode_xla"]

_MAX_TILE_CHUNKS = 13     # 13 x 136 x 128 x 4 B = 0.9 MB a tile at hd 128


def _tile_chunks(nch: int) -> int:
    return max(t for t in range(1, min(nch, _MAX_TILE_CHUNKS) + 1)
               if nch % t == 0)


def _kernel(slots_ref, s_ref, gam_ref, phik_ref, vb_ref, phiq_ref,
            o_ref, y_ref, acc_ref, *, tc: int, group: int, nt: int):
    """One grid step: chunks ``t*tc .. (t+1)*tc`` of row ``b``, head
    ``h``.  Refs: ``s``/``o`` [tc, R, hd]; ``gam`` [1, hd] (the decay,
    lane-broadcast); ``phik`` [nch, hd] and ``phiq`` [group, nch, hd]
    (whole: a tile of ``tc`` chunks is no multiple of 8 sublanes);
    ``vb`` [R, hd] (``v'`` lane-broadcast); ``y`` [group, R]; ``acc``
    [group, R, hd] scratch."""
    from jax.experimental import pallas as pl

    del slots_ref                       # read by the index maps alone
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    gam = gam_ref[...]
    vb = vb_ref[...]
    for j in range(tc):
        o_ref[j] = gam * s_ref[j] + vb * phik_ref[pl.ds(t * tc + j, 1), :]
    for h in range(group):
        acc = acc_ref[h]
        for j in range(tc):
            acc = acc + o_ref[j] * phiq_ref[h, pl.ds(t * tc + j, 1), :]
        acc_ref[h] = acc

    @pl.when(t == nt - 1)
    def _emit():
        # the lane sum as a matmul with ones: it turns the [R, 1] column
        # of sums into the [1, R] row the output block stores
        ones = jnp.ones((8, acc_ref.shape[-1]), jnp.float32)
        for h in range(group):
            row = jax.lax.dot_general(
                ones, acc_ref[h], (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)
            y_ref[h:h + 1, :] = row[0:1, :]


def retention_decode_xla(pool, layer: int, slots, q, k, v, g, eps: float):
    """The ``jax.numpy`` twin: gather the rows' states, one
    :func:`~mxnet_tpu.models.retention.recurrent_step`, scatter back."""
    y, new = retention.recurrent_step(pool[layer][slots], q, k, v, g, eps)
    return y, pool.at[layer, slots].set(new)


def retention_decode(pool, layer: int, slots, q, k, v, g, eps: float, *,
                     interpret: bool = False):
    """One decode position for each of B rows over the state pool.

    ``pool`` [L, slots, KV, nch, R, hd] float32 (donate it: it is
    updated in place); ``slots`` [B] int32, ``kvcache.TRASH_BLOCK`` for
    rows that are not active; ``q`` [B, H, hd]; ``k``/``v`` [B, KV, hd];
    ``g`` [B, KV] log-gates.  Returns ``(y [B, H, hd] float32, pool)``.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, h, hd = q.shape
    kv = k.shape[1]
    group = h // kv
    nch, rows, _ = pool.shape[3:]
    tc = _tile_chunks(nch)
    nt = nch // tc
    gam_b = jnp.broadcast_to(
        jnp.exp(g.astype(jnp.float32))[:, :, None, None], (b, kv, 1, hd))
    phik = retention.phi(k)                               # [B, KV, nch, hd]
    vb = jnp.broadcast_to(retention.augment_values(v)[..., None],
                          (b, kv, rows, hd))
    phiq = retention.phi(q).reshape(b, kv, group, nch, hd)

    state_spec = pl.BlockSpec(
        (None, None, None, tc, rows, hd),
        lambda bi, hi, ti, s: (layer, s[bi], hi, ti, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, kv, nt),
        in_specs=[
            state_spec,
            pl.BlockSpec((None, None, 1, hd),
                         lambda bi, hi, ti, s: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, nch, hd),
                         lambda bi, hi, ti, s: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, rows, hd),
                         lambda bi, hi, ti, s: (bi, hi, 0, 0)),
            pl.BlockSpec((None, None, group, nch, hd),
                         lambda bi, hi, ti, s: (bi, hi, 0, 0, 0)),
        ],
        out_specs=[
            state_spec,
            pl.BlockSpec((None, None, group, rows),
                         lambda bi, hi, ti, s: (bi, hi, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((group, rows, hd), jnp.float32)],
    )
    with jax.enable_x64(False):
        pool, out = pl.pallas_call(
            partial(_kernel, tc=tc, group=group, nt=nt),
            name="mxtpu_retention_decode",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                       jax.ShapeDtypeStruct((b, kv, group, rows),
                                            jnp.float32)],
            # operand 0 is the scalar-prefetched slots; the pool is 1
            input_output_aliases={1: 0},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
            interpret=interpret,
        )(slots.astype(jnp.int32), pool, gam_b, phik, vb, phiq)
    y = out[..., :hd] / (out[..., hd:hd + 1] + np.float32(eps))
    return y.reshape(b, h, hd), pool
