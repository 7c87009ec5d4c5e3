"""``runners/serve_engine_closed_latent.py``'s loop (that module's ``run``
is called, not copied) for a latent model whose residual path is
``hc_mult`` streams joined by hyper-connections, with one more number in
``correct``.

The probe compares greedy tokens over a few hundred positions, and its
tolerance is set by the routing's own spread: a connection's maps
computed in a lower precision than float32 stray further than that
costs.  This number holds them: the program's own kernel,
``mxtpu_hc_mix`` (``serve/hc_mix.py``; interpreted off the chip), at the
configuration's widths (``max_batch`` rows of ``hc_mult`` streams of
``hidden_size``, ``hc_sinkhorn_iters`` iterations) and with layer 0's
attention connection of the seeded weights (Phi, bias and scales as the
engine holds them), on seeded streams in the activations' type, against
float64 numpy of the same maps from the same rounded streams: for each
of H_pre, H_post and H_res the largest ``|got - want|`` over the largest
``|want|``, and the largest of the three.  No model activation is
involved, so what is left is the maps' own arithmetic: float32's against
float64's.  ``serve.hc_tolerance`` is the limit.
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from benchmark.harness import spec
from benchmark.harness.runtime import Result, Run, say

latent = spec.load_module("runners", "serve_engine_closed_latent")


def maps64(x, w, b, a, *, eps, iters, hc_eps, clamp):
    """A connection's (H_pre, H_post, H_res) in float64 numpy from the
    streams ``x`` [T, n, d]."""
    x = np.asarray(x, np.float64)
    t, n, d = x.shape
    flat = x.reshape(t, n * d)
    h = flat @ np.asarray(w, np.float64).T / np.sqrt(
        np.mean(flat ** 2, axis=1, keepdims=True) + eps)
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    pre = 1.0 / (1.0 + np.exp(-(a[0] * h[:, :n] + b[:n])))
    post = 2.0 / (1.0 + np.exp(-(a[1] * h[:, n:2 * n] + b[n:2 * n])))
    m = np.exp(np.clip(a[2] * h[:, 2 * n:] + b[2 * n:], -clamp,
                       clamp)).reshape(t, n, n)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + hc_eps)
        m = m / (m.sum(-2, keepdims=True) + hc_eps)
    return pre, post, m


def _maps_bf16(x, w, b, a, *, eps, iters, hc_eps, clamp):
    """The control: the same maps computed in bfloat16 throughout (the
    nearest precision under the float32 the configuration states)."""
    import jax
    import jax.numpy as jnp

    bf = jnp.bfloat16
    t, n, d = x.shape
    flat = x.reshape(t, n * d).astype(bf)
    h = jax.lax.dot_general(flat, w.astype(bf), (((1,), (1,)), ((), ())),
                            preferred_element_type=bf)
    h = h * jax.lax.rsqrt(jnp.mean(jnp.square(flat), axis=1, keepdims=True)
                          + jnp.asarray(eps, bf))
    a, b = a.astype(bf), b.astype(bf)
    pre = jax.nn.sigmoid(a[0] * h[:, :n] + b[:n])
    post = jnp.asarray(2.0, bf) * jax.nn.sigmoid(a[1] * h[:, n:2 * n]
                                                 + b[n:2 * n])
    m = jnp.exp(jnp.clip(a[2] * h[:, 2 * n:] + b[2 * n:], -clamp,
                         clamp)).reshape(t, n, n)
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + jnp.asarray(hc_eps, bf))
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + jnp.asarray(hc_eps, bf))
    return pre, post, m


def hc_error(params, cfg: Dict[str, Any], seed: int,
             control: bool = False) -> float:
    """The kernel's maps against float64's, the largest of the three
    relative errors.  ``control``: the bfloat16 maps in the kernel's
    place, the reading the limit has to refuse."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.models.decoder import ModelSpec
    from mxnet_tpu.serve.hc_mix import hc_mix

    serve = cfg["serve"]
    model = ModelSpec.resolve(serve["engine"]["model"],
                              int(cfg["num_attention_heads"]))
    rows = int(serve["engine"]["max_batch"])
    n, d = int(model.hc_mult), int(cfg["hidden_size"])
    rng = np.random.default_rng([int(seed), 0x4C5D])
    # four streams of their own scales, as a layer's are
    x = jnp.asarray((rng.standard_normal((rows, n, d))
                     * rng.uniform(0.5, 2.0, (1, n, 1))).astype(np.float32),
                    jnp.dtype(serve["weights_dtype"]))
    w, b, a = (params[f"layer0_hc_attn_{k}"] for k in ("weight", "bias",
                                                      "scale"))
    settings = dict(eps=float(model.norm_eps),
                    iters=int(model.hc_sinkhorn_iters),
                    hc_eps=float(model.hc_eps), clamp=float(model.hc_clamp))
    if control:
        got = _maps_bf16(x, w, b, a, **settings)
    else:
        got = hc_mix(model, x, w, b, a,
                     interpret=jax.default_backend() != "tpu")[1:]
    want = maps64(np.asarray(x.astype(jnp.float32)), np.asarray(w),
                  np.asarray(b), np.asarray(a), **settings)
    return max(float(np.max(np.abs(np.asarray(g.astype(jnp.float32)) - q))
                     / np.max(np.abs(q))) for g, q in zip(got, want))


def run(run: Run) -> Result:
    seen = {}
    build = latent.closed.serving.build_engine

    def build_and_read(r: Run):
        eng, params, ref = build(r)
        seen["error"] = hc_error(params, r.config, r.seed)
        return eng, params, ref

    latent.closed.serving.build_engine = build_and_read
    try:
        result = latent.run(run)
    finally:
        latent.closed.serving.build_engine = build
    tol = float(run.config["serve"]["hc_tolerance"])
    say(f"[correct] the connection kernel's maps at the configuration's "
        f"widths: max |got - float64| / max |float64| over H_pre, H_post, "
        f"H_res = {seen['error']:.3g} (tolerance {tol})")
    run.compared["hc_error"] = (seen["error"], tol)
    if not seen["error"] <= tol:
        result.notes.append(
            f"the connection kernel's maps stray {seen['error']:.3g} from "
            f"float64's, tolerance {tol}: they are not computed in the "
            "float32 the configuration states")
        result.correct = False
    return result
