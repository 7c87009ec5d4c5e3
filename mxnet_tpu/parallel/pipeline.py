"""Pipeline parallelism over the ``pipe`` mesh axis (GPipe schedule).

The reference's closest analog is the model-parallel LSTM whose
wavefront emerges from the dependency engine
(``example/model-parallel-lstm``, SURVEY §2.4 marks true pipeline
parallelism absent).  Here the schedule is explicit: each device owns
one stage's parameters, microbatches stream through the ring via
``ppermute``, and a ``scan`` over ticks overlaps stage compute with
neighbor transfers — reverse-differentiable end to end.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from .mesh import PIPE_AXIS

__all__ = ["pipeline_apply"]


def _pipeline_sharded(params, x_mb, *, stage_fn, axis_name):
    """Per-device body: run my stage on whatever microbatch is resident,
    pass activations to the next stage each tick.

    ``params`` arrives with a leading stage dim of 1 (the local shard of
    the stacked [S, ...] stage parameters); ``x_mb`` is the full
    [M, mb, ...] microbatch stream (replicated).
    """
    s = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    m = x_mb.shape[0]
    local = jax.tree.map(lambda p: p[0], params)
    ticks = m + s - 1

    # seed carries as pipe-varying (buf/outs depend on the stage id) so
    # scan/cond type-checking under shard_map accepts the updates
    zero = x_mb[0] * 0.0 + idx.astype(x_mb.dtype) * 0.0

    def tick(carry, t):
        buf, outs = carry
        # stage 0 injects microbatch t (garbage after the stream ends —
        # masked out at collection); later stages consume the neighbor's
        # activation from the previous tick
        inject = x_mb[jnp.clip(t, 0, m - 1)]
        cur = jnp.where(idx == 0, inject, buf)
        y = stage_fn(local, cur)
        # collect on the last stage for valid ticks
        out_slot = t - (s - 1)
        valid = (idx == s - 1) & (out_slot >= 0) & (out_slot < m)
        outs = jax.lax.cond(
            valid,
            lambda o: o.at[jnp.clip(out_slot, 0, m - 1)].set(y),
            lambda o: o,
            outs)
        # forward the activation ring: stage i -> i+1
        nxt = jax.lax.ppermute(
            y, axis_name, [(i, (i + 1) % s) for i in range(s)])
        return (nxt, outs), None

    outs0 = jnp.zeros((m,) + zero.shape, zero.dtype) + zero[None] * 0.0
    (_, outs), _ = jax.lax.scan(tick, (zero, outs0), jnp.arange(ticks))
    # every device returns its (mostly-zero) collection; summing over the
    # pipe axis leaves exactly the last stage's outputs
    return jax.lax.psum(outs, axis_name)


def pipeline_apply(stage_fn: Callable, stage_params, x, mesh: Mesh, *,
                   num_microbatches: int, pipe_axis: str = PIPE_AXIS):
    """Run ``stage_fn`` S times over pipeline stages.

    Parameters
    ----------
    stage_fn : callable(params_one_stage, x) -> y
        One stage's computation; input and output must share shape (as in
        classic GPipe layer-stacking).
    stage_params : pytree with leading stage dim S on every leaf
        Stage s uses ``tree_map(lambda p: p[s], stage_params)``.
    x : [batch, ...] global input.
    mesh : Mesh with ``pipe_axis`` of size S.
    num_microbatches : int
        The batch splits into this many microbatches (must divide batch).

    Returns the [batch, ...] output of the final stage.
    """
    s = mesh.shape[pipe_axis]
    b = x.shape[0]
    if b % num_microbatches:
        raise ValueError(f"batch {b} not divisible by "
                         f"{num_microbatches} microbatches")
    mb = b // num_microbatches
    x_mb = x.reshape((num_microbatches, mb) + x.shape[1:])

    nstage = jax.tree.leaves(stage_params)[0].shape[0]
    if nstage != s:
        raise ValueError(f"stage_params has {nstage} stages, mesh axis "
                         f"{pipe_axis} has {s}")

    pspec = jax.tree.map(lambda _: P(pipe_axis), stage_params)
    body = functools.partial(_pipeline_sharded, stage_fn=stage_fn,
                             axis_name=pipe_axis)
    out_mb = jax.shard_map(body, mesh=mesh, in_specs=(pspec, P()),
                           out_specs=P())(stage_params, x_mb)
    return out_mb.reshape((b,) + out_mb.shape[2:])
