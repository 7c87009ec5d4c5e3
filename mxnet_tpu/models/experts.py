"""A routed expert layer of a described decoder (``ModelSpec.ffn_layers``
``"routed"``), told **which experts this chip holds**.

    p   = softmax(W_g x) over all ``n_routed_experts`` (or sigmoid(W_g x)
          with ``score_func`` "sigmoid"), float32;  c = p (+ b, the
          selection bias, with ``router_bias``)
    keep the ``topk_group`` groups (of ``n_group``) whose largest c is
    largest, zero the rest; choose the ``experts_per_token`` largest c
    w_e = routed_scaling_factor * p_e   (/ the chosen p's sum if
          norm_topk_prob: over all chosen, held here or not)
    out = shared(x) + sum over the chosen e HELD HERE of w_e expert_e(x)

The router keeps its published width and its experts per token whatever
is held: under expert parallelism a chip routes over all experts and
computes its own experts' part of the result; what the absent experts
would add is the other chips', and on one chip it is left out (no
exchange runs, and nothing stands in for one).  No capacity: every
assignment to a held expert is computed, at any skew.

This module is the plain form: the router, the shared experts, and the
held experts as a pass of every token through every held expert under a
mask.  That is what a CPU engine and the tests run; the serving
programs on a chip hand :func:`decoder_forward` the form whose work
follows the assignments (``serve/moe_experts.py``).  Imported when a
description has a routed layer, not with the package.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .decoder import ModelSpec, _fcm, _param

__all__ = ["router_logits", "route", "router_bias", "held_assignments",
           "shared_ffn", "routed_ffn"]


def router_logits(x, w_router):
    """The router's scores ``[T, experts]`` in float32 (sums of exact
    products where states and weights are bfloat16)."""
    return jax.lax.dot_general(
        x, w_router, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST)


def route(spec: ModelSpec, x, w_router, bias=None):
    """``x`` [T, d] -> the chosen experts ``[T, k]`` int32 (ids among
    all ``n_routed_experts``) and their weights ``[T, k]`` float32.
    ``bias`` [experts] (``spec.router_bias``): added to the scores for
    the choice alone; a chosen expert's weight is its score."""
    n, g, k = spec.n_routed_experts, spec.n_group, spec.experts_per_token
    logits = router_logits(x, w_router)
    p = (jax.nn.sigmoid(logits) if spec.score_func == "sigmoid"
         else jax.nn.softmax(logits, axis=-1))
    p_in = p if bias is None else p + bias.astype(jnp.float32)
    if g > 1:
        best = jnp.max(p_in.reshape(-1, g, n // g), axis=-1)       # [T, g]
        _, kept = jax.lax.top_k(best, spec.topk_group)
        mask = jnp.zeros_like(best).at[
            jnp.arange(best.shape[0])[:, None], kept].set(1.0)
        p_in = (p_in.reshape(-1, g, n // g) * mask[..., None]).reshape(-1, n)
    w, idx = jax.lax.top_k(p_in, k)
    if bias is not None:
        w = jnp.take_along_axis(p, idx, axis=1)
    if spec.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + np.float32(1e-20))
    return idx.astype(jnp.int32), w * np.float32(spec.routed_scaling_factor)


def router_bias(spec: ModelSpec, params, i: int):
    """Layer ``i``'s selection bias, or None where the router has none."""
    return (_param(params, f"layer{i}_router_bias") if spec.router_bias
            else None)


def held_assignments(spec: ModelSpec, idx, live=None):
    """The chosen ids as indices among the held experts, and which of
    them are held here: ``(local [T, k] int32, held [T, k] bool)``.
    ``live`` [T] bool: positions that are nobody's (a padded row of a
    batch or a chunk) assign nothing."""
    first, count = spec.held
    local = idx - first
    held = (local >= 0) & (local < count)
    if live is not None:
        held = held & live.reshape(-1, 1)
    return local, held


def shared_ffn(params, i: int, x):
    """The shared experts: one gated SiLU FFN every token goes through
    (their widths side by side)."""
    with jax.named_scope("shared_expert"):
        p = f"layer{i}_shared_"
        f = (jax.nn.silu(_fcm(x, _param(params, p + "gate_weight")))
             * _fcm(x, _param(params, p + "up_weight")))
        return _fcm(f, _param(params, p + "down_weight"))


def routed_ffn(spec: ModelSpec, params, i: int, x, live=None):
    """The layer on normed states ``x`` [..., d], every held expert over
    every token under the assignments' mask -> ``(out [..., d],
    experts_hit, assigned_here)``: the held experts with an assignment
    and the assignments to held experts (int32 scalars)."""
    lead, d = x.shape[:-1], x.shape[-1]
    xt = x.reshape(-1, d)
    with jax.named_scope("router"):
        idx, w = route(spec, xt, _param(params, f"layer{i}_router_weight"),
                       router_bias(spec, params, i))
        local, held = held_assignments(spec, idx, live)
    with jax.named_scope("experts"):
        count = spec.held[1]
        # [T, k, held experts]: which held expert each choice is
        chosen = held[..., None] & (local[..., None] == jnp.arange(count))
        # [T, held experts]: the weight of each held expert for each token
        gatew = jnp.sum(jnp.where(chosen, w[..., None], np.float32(0.0)),
                        axis=1)
        wg = _param(params, f"layer{i}_experts_gate_weight")
        wu = _param(params, f"layer{i}_experts_up_weight")
        wd = _param(params, f"layer{i}_experts_down_weight")
        xe = xt.astype(wg.dtype)
        f = (jax.nn.silu(jnp.einsum("td,edf->etf", xe, wg))
             * jnp.einsum("td,edf->etf", xe, wu))
        y = jnp.einsum("etf,efd->etd", f, wd).astype(jnp.float32)
        out = jnp.einsum("etd,te->td", y, gatew)
    out = out + shared_ffn(params, i, xt).astype(jnp.float32)
    hit = jnp.sum(jnp.any(chosen, axis=(0, 1)).astype(jnp.int32))
    return (out.reshape(lead + (d,)).astype(x.dtype), hit,
            jnp.sum(held.astype(jnp.int32)))
