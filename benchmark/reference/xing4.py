"""Plain reference of the ``xing4`` family (XingChen-AGI/Xing4.0-29B-A4B,
``model_type`` ``xing4_0``): DeepSeek-style multi-head latent attention,
one leading dense layer, then expert layers routed by sigmoid scores with
a selection bias beside one shared expert, on a residual path of
``hc_mult`` streams joined by manifold-constrained hyper-connections
(mHC: Manifold-Constrained Hyper-Connections, DeepSeek-AI 2025;
Hyper-Connections, Zhu et al., ICLR 2025).  Float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``; no cache, no chunks, no
kernel, nothing imported from the program under test.  ``RMS`` is RMSNorm
with a learned gain.

    streams X in R^{n x d} a position, n = hc_mult;  X_0[i] = E[token] for every i
    a sublayer F (a layer's attention, then its FFN) through its OWN connection
    (Phi [2n + n^2, n d], b [2n + n^2], a = (a_pre, a_post, a_res); all float32):
            x = vec(X);  h = (Phi x) (mean(x^2) + rms_norm_eps)^-1/2            (no gain)
            H_pre  = sigmoid(a_pre h[:n] + b[:n]);  H_post = 2 sigmoid(a_post h[n:2n] + b[n:2n])
            M = exp(clamp(a_res mat(h[2n:]) + b[2n:], -30, 30))                 (row-major n x n)
            20 times:  M <- M / (rowsum(M) + hc_eps);  M <- M / (colsum(M) + hc_eps)
            u = sum_j H_pre[j] X[j];  y = F(u);  X[i] <- sum_j M[i, j] X[j] + H_post[i] y
    Attn    deepseek_v2.py's MLA (low-rank queries, one cached [c | k_r] row, YaRN), its
            pre-norm inside:  F(u) = W_o MLA(RMS(u))
    dense   F(u) = W_down(silu(W_gate x) * W_up x),  x = RMS(u)                  (layer 0)
    experts s = sigmoid(W_r x) over all experts, float32;  chosen = top_k of (s + b_sel);
            w_e = routed_scaling_factor * s_e / sum of s over the chosen;
            F(u) = shared(x) + sum over the chosen e IN ``experts_held`` of w_e expert_e(x)
    head    logits = W_head RMS(sum_i X_N[i])                                   (untied, no bias)

The attention and the dense FFN are ``deepseek_v2.py``'s and the expert
layer ``trinity.py``'s (``moe_layer(..., parts=True)``: sigmoid scores,
a selection bias, weights renormalised over the chosen), loaded by path.
``deepseek_v2.py``'s attention and dense FFN return ``u + F(u)``: ``F(u)``
is that minus ``u`` (float32, an error of a unit in the last place of
``|u|``).  Departures from the published description: none in the
mathematics; the rotary layout is ``deepseek_v2.py``'s half-split one,
which with seeded weights is the same model up to a permutation of the
rotary rows; the choices the published description leaves open are the
configuration file's ``assumed``.  Parameters carry the program's names:
a FullyConnected weight is ``[out, in]``, a held expert's matrices are
``[in, out]``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import spec

ds = spec.load_module("reference", "deepseek_v2")
tr = spec.load_module("reference", "trinity")

_HI = "highest"
#: the published settings (config.json); ``forward`` takes them as
#: keywords so that a tiny test can state its own
PUBLISHED = dict(
    top_k=4, routed_scaling_factor=2.0, rope_theta=10000.0, eps=1e-6,
    hc_sinkhorn_iters=20, hc_eps=1e-6, hc_clamp=30.0,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
                  "mscale_all_dim": 1,
                  "original_max_position_embeddings": 4096, "type": "yarn"})
#: the selection bias's spread: routing logits of std 0.02 x sqrt(3584) =
#: 1.2 over 64 experts put a token's fourth and fifth sigmoid scores a
#: median 0.0128 apart; a bias of that spread changes one choice in
#: fourteen (7 %) and every expert is still hit by 128 rows (a simulation
#: of the router, PERF.md section 6).  A trained bias balances load
BIAS_STD = 0.013
#: a connection's seeded maps: Phi N(0, 0.02) makes h of std 0.02 x
#: sqrt(4 x 3584) = 2.4 (the RMS scale makes it independent of the
#: streams); these scales and a bias of std 0.3 put the logits of H_pre,
#: H_post and H_res at std ~1: neither gate saturates, and H_res is
#: neither uniform, nor one-hot, nor the identity (PERF.md section 6)
HC_SCALE = (0.4, 0.4, 0.4)
HC_BIAS_STD = 0.3


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    z = ds.dims(cfg)
    z["streams"] = int(cfg["hc_mult"])
    return z


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    z = dims(cfg)
    shapes = ds.param_shapes(cfg)
    n, d = z["streams"], z["d"]
    for i in range(z["n"]):
        for which in ("attn", "ffn"):
            p = f"layer{i}_hc_{which}_"
            shapes[p + "weight"] = (2 * n + n * n, n * d)
            shapes[p + "bias"] = (2 * n + n * n,)
            shapes[p + "scale"] = (3,)
        if i >= z["dense"]:
            shapes[f"layer{i}_router_bias"] = (z["experts"],)
    return shapes


def param_count(cfg: Dict[str, Any]) -> int:
    return sum(int(np.prod(s)) for s in param_shapes(cfg).values())


def score_scale(nope: int, rope: int, scaling: Dict[str, Any]) -> float:
    return ds.score_scale(nope, rope, scaling)


def connection_flops(n: int, d: int, iters: int) -> float:
    """FLOPs of one connection a position: the projection (2 a weight),
    the sum of squares, ``u`` (2 n d), the ``iters`` normalisations (a
    sum and a division an entry, twice: 4 n^2) and the depth update
    (2 n^2 d + 2 n d)."""
    m = 2 * n + n * n
    return (2.0 * m * n * d + 2 * n * d + 2 * n * d + iters * 4 * n * n
            + 2 * n * n * d + 2 * n * d)


def forward_flops(cfg: Dict[str, Any], positions: int, attended: int) -> float:
    """FLOPs the forward pass NEEDS here for ``positions`` new positions
    that attend over ``attended`` cached ones between them: 2 per matmul
    parameter active on this chip (the attention's five projections a
    layer, the dense FFN, the shared expert, the router, the ``top_k``
    experts a position, all held here, and the head), the two
    connections of every layer (:func:`connection_flops`), plus the
    attention in the up-projected form, ``2 x heads x (nope + rope +
    v)`` an attended position and layer (``deepseek_v2.py``'s count)."""
    z = dims(cfg)
    d, h = z["d"], z["h"]
    mla = (d * z["q_rank"] + z["q_rank"] * h * (z["nope"] + z["rope"])
           + d * (z["rank"] + z["rope"])
           + z["rank"] * h * (z["nope"] + z["vd"]) + h * z["vd"] * d)
    expected = PUBLISHED["top_k"] * z["held"] / z["experts"]
    active = (z["n"] * mla + z["dense"] * 3 * d * z["f"]
              + (z["n"] - z["dense"]) * (3 * d * z["shared"] * z["fe"]
                                         + z["experts"] * d
                                         + expected * 3 * d * z["fe"])
              + z["v"] * d)
    hc = 2 * z["n"] * connection_flops(z["streams"], d,
                                       PUBLISHED["hc_sinkhorn_iters"])
    attention = z["n"] * 2.0 * h * (z["nope"] + z["rope"] + z["vd"])
    return (2.0 * active + hc) * positions + attention * attended


def init_params(seed: int, cfg: Dict[str, Any], dtype=jnp.float32,
                std: float = 0.02, bias_std: float = BIAS_STD,
                hc_scale=HC_SCALE, hc_bias_std: float = HC_BIAS_STD
                ) -> Dict[str, jax.Array]:
    """Seeded random weights made ON the device: matrices N(0, std) and
    gains 1 + N(0, std) in ``dtype``; the selection bias N(0, bias_std),
    the connections' Phi N(0, std) and bias N(0, hc_bias_std) and their
    scales ``hc_scale``, in float32; one jitted draw a leaf.
    ``experts_held`` (int32): the ids of the held experts."""

    @functools.partial(jax.jit, static_argnames=("shape", "scale", "shift",
                                                 "kind"))
    def draw(key, shape, scale, shift, kind):
        x = jax.random.normal(key, shape, jnp.float32)
        return (shift + scale * x).astype(kind)

    # seeds run a little past 2**31: fold the two halves in
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        k = jax.random.fold_in(key, i)
        if name.endswith("_hc_attn_scale") or name.endswith("_hc_ffn_scale"):
            out[name] = jnp.asarray(hc_scale, jnp.float32)
        elif name.endswith("_router_bias"):
            out[name] = draw(k, shape, bias_std, 0.0, jnp.float32)
        elif "_hc_" in name:
            out[name] = draw(k, shape, hc_bias_std if name.endswith("_bias")
                             else std, 0.0, jnp.float32)
        else:
            shift = 1.0 if name.endswith("_gamma") else 0.0
            out[name] = draw(k, shape, std, shift, jnp.dtype(dtype))
    out["experts_held"] = jnp.asarray(ds.held_ids(cfg))
    return out


# ---------------------------------------------------------------------------
# the connections
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("eps", "iters", "hc_eps",
                                             "clamp"))
def connection(x, phi, bias, scale, eps, iters, hc_eps, clamp):
    """The maps of one connection from the streams ``x`` [..., n, d]:
    ``(u [..., d], H_pre [..., n], H_post [..., n], H_res [..., n, n])``."""
    n = x.shape[-2]
    lead = x.shape[:-2]
    flat = x.reshape(lead + (-1,))
    h = jnp.matmul(flat, phi.T, precision=_HI) / jnp.sqrt(
        jnp.mean(jnp.square(flat), axis=-1, keepdims=True) + eps)
    pre = jax.nn.sigmoid(scale[0] * h[..., :n] + bias[:n])
    post = 2.0 * jax.nn.sigmoid(scale[1] * h[..., n:2 * n] + bias[n:2 * n])
    m = jnp.exp(jnp.clip(scale[2] * h[..., 2 * n:] + bias[2 * n:], -clamp,
                         clamp)).reshape(lead + (n, n))
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + hc_eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + hc_eps)
    u = jnp.einsum("...j,...jd->...d", pre, x, precision=_HI)
    return u, pre, post, m


@jax.jit
def _depth(x, post, res, y):
    return (jnp.einsum("...ij,...jd->...id", res, x, precision=_HI)
            + post[..., None] * y[..., None, :])


def _layers(params) -> int:
    n = 0
    while f"layer{n}_q_a_weight" in params:
        n += 1
    return n


def forward(params: Dict[str, Any], tokens, heads: int, *,
            head_block: int = 8, **settings) -> jax.Array:
    """Logits [B, L, V] in float32 for token ids [B, L].  A layer at a
    time, the attention a sequence and ``head_block`` heads at a time,
    the experts one at a time: beside the logits it holds the streams
    ([B, L, n, d] float32, twice), one ``[head_block, L, L]`` block of
    scores (twice) and one float32 copy of a slice of a weight: about
    1 GB at three prompts of 1,152 tokens, beside 1.8 GB of logits.
    ``settings`` override :data:`PUBLISHED` (a tiny test states its own)."""
    st = dict(PUBLISHED, **settings)
    scaling = tuple(sorted((st["rope_scaling"] or {}).items()))
    eps, theta = float(st["eps"]), float(st["rope_theta"])
    hc = dict(eps=eps, iters=int(st["hc_sinkhorn_iters"]),
              hc_eps=float(st["hc_eps"]), clamp=float(st["hc_clamp"]))
    tokens = jnp.asarray(tokens, jnp.int32)
    b, l = tokens.shape
    held = np.asarray(params["experts_held"])
    x = ds._embed(tokens, params["embed_weight"])                  # [B, L, d]
    n = params["layer0_hc_attn_bias"].shape[0]
    n = int(round(math.sqrt(n + 1) - 1))               # 2n + n^2 maps
    x = jnp.broadcast_to(x[:, :, None, :], (b, l, n, x.shape[-1]))
    for i in range(_layers(params)):
        pre = f"layer{i}_"
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        att = {k: p[k] for k in ds._ATTENTION_KEYS}

        def through(which, sublayer, x):
            u, _, post, res = connection(
                x, p[f"hc_{which}_weight"], p[f"hc_{which}_bias"],
                p[f"hc_{which}_scale"], **hc)
            return _depth(x, post, res, sublayer(u))

        x = through("attn", lambda u: jnp.stack([
            ds._attention(u[r], att, heads, theta, eps, scaling,
                          min(head_block, heads)) - u[r]
            for r in range(b)]), x)

        def ffn(u):
            flat = u.reshape(b * l, -1)
            if "router_weight" in p:
                shared, routed = tr.moe_layer(
                    flat, p, held, eps=eps, top_k=int(st["top_k"]),
                    route_scale=float(st["routed_scaling_factor"]),
                    parts=True)
                return (shared + routed).reshape(u.shape)
            return (ds._dense_ffn(flat, p, eps, 4) - flat).reshape(u.shape)

        x = through("ffn", ffn, x)
    return ds._head(jnp.sum(x, axis=2), params["final_ln_gamma"],
                    params["lm_head_weight"], eps)
