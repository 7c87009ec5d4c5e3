"""Mixture-of-experts with expert parallelism over the ``expert`` axis.

Another capability upgrade SURVEY §2.4 marks absent in the 2016
reference.  Top-1 (Switch) routing realized as dense dispatch/combine
einsums — the GSPMD recipe: expert weight tensors lead with the expert
dim, shard that dim over the ``expert`` mesh axis
(``ShardingRules([("expert", P("expert", ...))])``) and XLA inserts the
all-to-alls that move tokens to their expert's chip.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["switch_ffn", "moe_ffn", "moe_ffn_ep", "load_balance_loss"]


def _topk_dispatch(topi, gates, e: int, cap: int, dtype):
    """Rank-major (GShard) capacity accounting shared by the dense and
    expert-parallel paths: every token's rank-0 assignment claims a slot
    before ANY rank-1 assignment does.

    topi : [N, k] expert ids; gates : [N, k] renormalized gate weights.
    Returns ``(dispatch, combine)``, both ``[N, E, C]``.
    """
    n, k = topi.shape
    onehot_i = jax.nn.one_hot(topi, e, dtype=jnp.int32)       # [N, k, E]
    flat = onehot_i.transpose(1, 0, 2).reshape(k * n, e)      # [k*N, E]
    pos = (jnp.cumsum(flat, axis=0) * flat - flat)
    pos = pos.reshape(k, n, e).transpose(1, 0, 2)             # [N, k, E]
    keep = ((pos < cap) & (onehot_i > 0)).astype(dtype)
    slot = jax.nn.one_hot(pos, cap, dtype=dtype)              # [N, k, E, C]
    disp_k = slot * keep[..., None]
    dispatch = jnp.sum(disp_k, axis=1)                        # [N, E, C]
    combine = jnp.sum(disp_k * gates.astype(dtype)[..., None, None], axis=1)
    return dispatch, combine


def moe_ffn(x, gate_w, w1, b1, w2, b2, k: int = 2,
            capacity_factor: float = 1.5):
    """Top-k routed expert feed-forward (k=2 is the GShard default).

    Each token goes to its top-k experts with gates renormalized over
    the chosen k (GShard/Mixtral convention); per-expert capacity
    ``C = ceil(cf * k * N / E)`` drops overflow assignments (the token
    still passes through via its surviving assignments, or contributes
    zero if all overflow).

    Shapes as :func:`switch_ffn`; returns ``(y, router_probs)``.
    """
    n, d = x.shape
    e = gate_w.shape[1]
    k = min(k, e)
    cap = max(1, math.ceil(capacity_factor * k * n / e))

    logits = jnp.dot(x, gate_w)                       # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)              # [N, k]
    gates = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True), 1e-9)

    dispatch, combine = _topk_dispatch(topi, gates, e, cap, x.dtype)

    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x)        # [E, C, D]
    h = jnp.einsum("ecd,edh->ech", expert_in, w1) + b1[:, None]
    h = jax.nn.relu(h)
    expert_out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None]
    y = jnp.einsum("nec,ecd->nd", combine, expert_out)
    return y, probs


def switch_ffn(x, gate_w, w1, b1, w2, b2, capacity_factor: float = 1.5):
    """Top-1 routed expert feed-forward.

    Parameters
    ----------
    x : [N, D] tokens.
    gate_w : [D, E] router weights.
    w1, b1 : [E, D, H], [E, H] expert up-projections.
    w2, b2 : [E, H, D], [E, D] expert down-projections.
    capacity_factor : float
        Per-expert capacity C = ceil(cf * N / E); overflow tokens pass
        through with zero expert output (standard Switch behavior).

    Returns ``(y, router_probs)`` with ``y`` [N, D].
    """
    n, d = x.shape
    e = gate_w.shape[1]
    cap = max(1, math.ceil(capacity_factor * n / e))

    logits = jnp.dot(x, gate_w)                      # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)          # [N]
    gate = jnp.max(probs, axis=-1)                   # [N]

    # routing bookkeeping in int32 — token dtypes like bf16 cannot count
    # past 256 and would collide capacity slots
    onehot_i = jax.nn.one_hot(expert_idx, e, dtype=jnp.int32)  # [N, E]
    # arrival order within each expert decides who fits under capacity
    pos = jnp.cumsum(onehot_i, axis=0) * onehot_i - onehot_i   # [N, E]
    keep = ((pos < cap) & (onehot_i > 0)).astype(x.dtype)
    slot = jax.nn.one_hot(pos, cap, dtype=x.dtype)             # [N, E, C]
    dispatch = slot * keep[..., None]                          # [N, E, C]
    combine = dispatch * gate.astype(x.dtype)[:, None, None]

    expert_in = jnp.einsum("nec,nd->ecd", dispatch, x)          # [E, C, D]
    h = jnp.einsum("ecd,edh->ech", expert_in, w1) + b1[:, None]
    h = jax.nn.relu(h)
    expert_out = jnp.einsum("ech,ehd->ecd", h, w2) + b2[:, None]
    y = jnp.einsum("nec,ecd->nd", combine, expert_out)
    return y, probs


def moe_ffn_ep(x, gate_w, w1, b1, w2, b2, mesh, k: int = 2,
               capacity_factor: float = 1.5, expert_axis: str = "expert",
               data_axis: str = "data"):
    """Expert-parallel top-k MoE with an EXPLICIT token all-to-all.

    The dense-dispatch formulation leaves collective choice to GSPMD
    (which tends to all-gather activations).  This is the canonical
    expert-parallel program instead: each chip routes its local tokens,
    an ``all_to_all`` over the ``expert`` mesh axis moves token slots to
    their experts' chips, the expert FFN runs on local experts only, and
    the reverse ``all_to_all`` brings results home — comm proportional to
    routed tokens, not to the full activation tensor.

    ``x`` must be sharded ``P((data_axis, expert_axis), None)`` — tokens
    split over ALL chips, the canonical EP layout (``P(expert_axis,
    None)`` when the mesh has no data axis); expert weights
    ``P(expert_axis, ...)`` (replicated over ``data``, so their grads
    psum over it in the transpose).  Returns ``(y, router_probs)``, both
    sharded like ``x`` on the token dim.

    ``k=1`` uses the Switch gate convention (scale by the router
    probability itself) so this is an exact expert-parallel lowering of
    :func:`switch_ffn`; ``k>1`` renormalizes over the chosen k like
    :func:`moe_ffn`.
    """
    from functools import partial

    from jax.sharding import PartitionSpec as P

    ep = mesh.shape[expert_axis]
    e = gate_w.shape[1]
    if e % ep:
        raise ValueError(f"num_experts {e} not divisible by expert-axis "
                         f"size {ep}")
    tok_axes = tuple(a for a in (data_axis, expert_axis)
                     if a in mesh.axis_names)
    tok_spec = P(tok_axes, None)

    @partial(
        jax.shard_map, mesh=mesh,
        in_specs=(tok_spec, P(),
                  P(expert_axis, None, None), P(expert_axis, None),
                  P(expert_axis, None, None), P(expert_axis, None)),
        out_specs=(tok_spec, tok_spec))
    def fn(x_l, gw, w1_l, b1_l, w2_l, b2_l):
        n_l, d = x_l.shape
        kk = min(k, e)
        cap = max(1, math.ceil(capacity_factor * kk * n_l / e))
        logits = jnp.dot(x_l, gw)
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = jax.lax.top_k(probs, kk)
        if kk == 1:
            gates = topv  # Switch convention: scale by the router prob
        else:
            gates = topv / jnp.maximum(jnp.sum(topv, -1, keepdims=True),
                                       1e-9)
        dispatch, combine = _topk_dispatch(topi, gates, e, cap,
                                           x_l.dtype)      # [n_l, E, C]

        expert_in = jnp.einsum("nec,nd->ecd", dispatch, x_l)  # [E, C, D]
        # all-to-all: split the expert dim over the expert axis, gather
        # every peer's slots for MY experts along the capacity dim
        recv = jax.lax.all_to_all(expert_in, expert_axis, split_axis=0,
                                  concat_axis=1, tiled=True)  # [E/ep, ep*C, D]
        h = jnp.einsum("ecd,edh->ech", recv, w1_l) + b1_l[:, None]
        h = jax.nn.relu(h)
        out = jnp.einsum("ech,ehd->ecd", h, w2_l) + b2_l[:, None]
        # reverse all-to-all: send each peer its tokens' results back
        back = jax.lax.all_to_all(out, expert_axis, split_axis=1,
                                  concat_axis=0, tiled=True)  # [E, C, D]
        return jnp.einsum("nec,ecd->nd", combine, back), probs

    return fn(x, gate_w, w1, b1, w2, b2)


def load_balance_loss(router_probs, num_experts: Optional[int] = None):
    """Switch-style auxiliary loss: E * sum_e fraction_e * mean_prob_e."""
    e = num_experts or router_probs.shape[-1]
    expert_idx = jnp.argmax(router_probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(expert_idx, e,
                                   dtype=router_probs.dtype), axis=0)
    mean_prob = jnp.mean(router_probs, axis=0)
    return e * jnp.sum(frac * mean_prob)
