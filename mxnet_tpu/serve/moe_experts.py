"""The held experts of a routed layer, as work that follows the
assignments: a grouped matrix product in Pallas (``mxtpu_moe_experts``).

``models/experts.py`` has the layer's plain form, every held expert
over every token under a mask: free at decode, where the experts'
weights are read whatever is computed, and forty times the routed work
in a prefill chunk.  Here the assignments to held experts are **sorted
by expert** and each expert multiplies only its own rows:

* :func:`plan` (XLA, integers only): every (token, choice) assigned to a
  held expert gets a row of one buffer, the rows of an expert
  contiguous and each expert's first row on a multiple of ``tm`` (the
  product's row tile), so that **a tile belongs to one expert**.  The
  buffer is sized for the worst skew (every choice of every token held
  here, ``tokens x experts_per_token`` rows, plus a tile of padding an
  expert): there is no capacity and no assignment is ever dropped.  The
  group sizes are data: which tile is whose, and how many tiles are
  live, reach the kernel as prefetched scalars.
* :func:`grouped_matmul`: grid ``(column tiles, row tiles)``.  A row
  tile's block of the weights is its expert's, picked by the prefetched
  map, so consecutive tiles of one expert re-use the block already in
  VMEM, an expert with no assignment is never read, and a column pass
  reads each hit expert's columns once: **a held expert's weights cross
  HBM -> VMEM at most once a call**.  Tiles past the last live one map
  to the last live one's blocks (nothing moves) and compute nothing.
  The gated form does ``silu(x W_gate) * (x W_up)`` in one pass over
  ``x``.
* the results are gathered back by assignment and summed with the
  router's weights in float32; an assignment to an expert held
  elsewhere contributes nothing here (its chip's part; on one chip no
  exchange runs).

The tiling is this module's own (tile-aligned groups in place of a
tile visited once a group it straddles); the idea of driving a matrix
product's block maps from group metadata held as prefetched scalars is
that of ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (jax 0.9.0).
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from ..models.decoder import ModelSpec, _param
from ..models.experts import held_assignments, route, router_bias, shared_ffn

__all__ = ["Plan", "plan", "grouped_matmul", "routed_ffn", "row_tile"]

#: VMEM the product may use: two weight blocks of the gated form, double
#: buffered, are 21 MB at the published widths (a v5e has 128 MiB)
_VMEM_LIMIT = 64 * 1024 * 1024


def row_tile(assignments: int, dtype) -> int:
    """Rows a tile: one sublane tile of the type at decode (a few
    assignments an expert: padding is what the MXU would idle on), 64
    in a chunk (an expert's ~38 rows of a 1024-token chunk in one
    tile)."""
    least = 16 if jnp.dtype(dtype).itemsize < 4 else 8
    return least if assignments <= 1024 else 64


class Plan(NamedTuple):
    """Where each assignment's row is, and whose each tile is."""
    src: jax.Array          # [rows] int32: the token each buffer row reads
    pos: jax.Array          # [T, k] int32: an assignment's row (held ones)
    tile_group: jax.Array   # [tiles] int32: the held expert of each tile
    tile_index: jax.Array   # [tiles] int32: the tile itself, or the last live
    live_tiles: jax.Array   # [1] int32
    counts: jax.Array       # [held] int32: assignments to each held expert


def plan(local, held, count: int, tm: int) -> Plan:
    """``local`` [T, k]: the chosen experts as indices among the
    ``count`` held ones; ``held`` [T, k]: which choices are held here."""
    t, k = local.shape
    a = t * k
    tiles = -(-a // tm) + count
    i32 = jnp.int32
    e = jnp.where(held, local, count).reshape(a).astype(i32)
    onehot = e[:, None] == jnp.arange(count, dtype=i32)[None, :]   # [A, E]
    upto = jnp.cumsum(onehot.astype(i32), axis=0)
    counts = upto[-1]
    rank = jnp.sum(jnp.where(onehot, upto, 0), axis=1) - 1         # -1: elsewhere
    padded = -(-counts // tm) * tm
    ends = jnp.cumsum(padded)
    starts = ends - padded
    flat_held = held.reshape(a)
    pos = jnp.where(flat_held,
                    jnp.take(starts, jnp.minimum(e, count - 1)) + rank,
                    tiles * tm).astype(i32)
    token = (jnp.arange(a, dtype=i32) // k)
    src = jnp.zeros((tiles * tm,), i32).at[pos].set(token, mode="drop")
    live = (ends[-1] // tm).astype(i32)
    index = jnp.minimum(jnp.arange(tiles, dtype=i32), jnp.maximum(live - 1, 0))
    group = jnp.minimum(
        jnp.searchsorted(ends, index * tm, side="right").astype(i32),
        count - 1)
    return Plan(src, pos.reshape(t, k), group, index, live.reshape(1), counts)


def _column_tile(n: int, want: int) -> int:
    for tn in (want, 512, 256, 128):
        if tn <= n and n % tn == 0:
            return tn
    return n


def _product_kernel(group_ref, index_ref, live_ref, x_ref, *refs,
                    gated: bool):
    from jax.experimental import pallas as pl

    w_refs, o_ref = refs[:-1], refs[-1]
    exact = x_ref.dtype == jnp.bfloat16
    dot = partial(jnp.dot, preferred_element_type=jnp.float32,
                  precision=None if exact else jax.lax.Precision.HIGHEST)

    @pl.when(pl.program_id(1) < live_ref[0])
    def _live():
        x = x_ref[...]
        out = dot(x, w_refs[0][...])
        if gated:
            out = out * jax.nn.sigmoid(out) * dot(x, w_refs[1][...])
        o_ref[...] = out.astype(o_ref.dtype)


def grouped_matmul(x, weights: Tuple[jax.Array, ...], p: Plan, tm: int, *,
                   column_tile: int, interpret: bool = False):
    """``x`` [rows, K] (the plan's buffer) against each row's expert:
    ``weights`` one ``[held, K, N]`` array (``x W``) or two (``silu(x
    W_0) * (x W_1)``).  Returns [rows, N] in ``x``'s type; rows of tiles
    past the live ones are not written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows, kdim = x.shape
    n = weights[0].shape[-1]
    tn = _column_tile(n, column_tile)
    tiles = rows // tm
    w_spec = pl.BlockSpec((None, kdim, tn),
                          lambda j, i, grp, idx, live: (grp[i], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n // tn, tiles),
        in_specs=[pl.BlockSpec((tm, kdim),
                               lambda j, i, grp, idx, live: (idx[i], 0))]
        + [w_spec] * len(weights),
        out_specs=pl.BlockSpec((tm, tn),
                               lambda j, i, grp, idx, live: (idx[i], j)),
    )
    with jax.enable_x64(False):
        return pl.pallas_call(
            partial(_product_kernel, gated=len(weights) == 2),
            name="mxtpu_moe_experts",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(p.tile_group, p.tile_index, p.live_tiles, x, *weights)


def routed_ffn(spec: ModelSpec, params, i: int, x, live=None, *,
               interpret: bool = False):
    """The routed layer ``i`` on normed states ``x`` [..., d] ->
    ``(out [..., d], experts_hit, assigned_here)``: the shared experts'
    and the held experts' part of the layer, the number of held experts
    with an assignment and the number of assignments to held experts
    (int32 scalars).  ``live`` [T] bool: see
    ``models.experts.held_assignments``."""
    lead, d = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, d)
    first, count = spec.held
    wg = _param(params, f"layer{i}_experts_gate_weight")
    wu = _param(params, f"layer{i}_experts_up_weight")
    wd = _param(params, f"layer{i}_experts_down_weight")
    with jax.named_scope("router"):
        idx, w = route(spec, xt, _param(params, f"layer{i}_router_weight"),
                       router_bias(spec, params, i))
        local, held = held_assignments(spec, idx, live)
        tm = row_tile(local.size, wg.dtype)
        p = plan(local, held, count, tm)
    with jax.named_scope("experts"):
        xs = jnp.take(xt.astype(wg.dtype), p.src, axis=0)       # [rows, d]
        f = grouped_matmul(xs, (wg, wu), p, tm, column_tile=512,
                           interpret=interpret)
        y = grouped_matmul(f, (wd,), p, tm, column_tile=1280,
                           interpret=interpret)                 # [rows, d]
        # back by assignment; a choice held elsewhere adds nothing (and
        # its row index points past the buffer: never read as a number)
        got = jnp.take(y, jnp.minimum(p.pos, y.shape[0] - 1), axis=0)
        out = jnp.sum(jnp.where(held[..., None],
                                got.astype(jnp.float32) * w[..., None],
                                np.float32(0.0)), axis=1)
    out = out + shared_ffn(params, i, xt).astype(jnp.float32)
    hit = jnp.sum((p.counts > 0).astype(jnp.int32))
    return (out.reshape(lead + (d,)).astype(x.dtype), hit,
            jnp.sum(p.counts).astype(jnp.int32))
