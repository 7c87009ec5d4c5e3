"""Plain reference of the ``brumby`` family (manifestai/Brumby-14B-Base):
a Qwen3-shaped dense decoder whose attention is POWER RETENTION, written
fresh from the layer equations in float32 ``jax.numpy`` under
``jax.default_matmul_precision("highest")``.  The attention form only:
no state, no chunks, no cache, no kernel, and nothing imported from the
program under test.

    x_0 = E[tokens]
    h   = RMSNorm(x);  q = W_q h (H heads of hd);  k, v = W_k h, W_v h (KV heads)
    q,k <- RoPE(RMSNorm_head(q)), RoPE(RMSNorm_head(k))     (gain of hd each, theta)
    g_t = log sigmoid(W_g h_t + b_g)   (one scalar per key/value head);  G_t = sum_{s<=t} g_s
    a_ts = exp(G_t - G_s) (q_t . k_s)^2 / hd   for s <= t, else 0
           (query head i reads key/value head i // (H / KV))
    y_t = sum_s a_ts v_s / (sum_s a_ts + eps)
    x   = x + W_o y;   x = x + W_down(silu(W_gate h') * W_up h'),  h' = RMSNorm(x)
    logits = W_head RMSNorm(x_N)                              (untied, no bias)

RMSNorm is ``x / sqrt(mean(x^2) + eps) * gain``; RoPE is the half-split
("rotate half") convention at absolute positions 0, 1, 2, ...  What
``config.json`` does not give (the degree 2, the gate and its
granularity, the score scale ``1 / hd``, ``eps``) is listed under
``assumed`` in ``benchmark/configs/brumby-14b-6of40.json`` with its
source.  Parameters carry the program's names (``layer{i}_q_weight`` is
``[out, in]``) so that one dict serves both sides.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

_HI = "highest"
RETENTION_EPS = 1e-6
GATE_BIAS = 4.0          # sigmoid(4.0) = 0.982: see ``init_params``
GATE_STD = 0.01


def dims(cfg: Dict[str, Any]) -> Tuple[int, int, int, int, int, int, int]:
    """(vocab, layers, d_model, heads, kv_heads, head_dim, ffn) from a
    config file's keys (the source's own names)."""
    return (int(cfg["vocab_size"]), int(cfg["num_hidden_layers"]),
            int(cfg["hidden_size"]), int(cfg["num_attention_heads"]),
            int(cfg["num_key_value_heads"]), int(cfg["head_dim"]),
            int(cfg["intermediate_size"]))


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    v, n, d, h, kv, hd, f = dims(cfg)
    shapes: Dict[str, Tuple[int, ...]] = {"embed_weight": (v, d)}
    for i in range(n):
        p = f"layer{i}_"
        shapes[p + "q_weight"] = (h * hd, d)
        shapes[p + "k_weight"] = (kv * hd, d)
        shapes[p + "v_weight"] = (kv * hd, d)
        shapes[p + "proj_weight"] = (d, h * hd)
        shapes[p + "gate_weight"] = (kv, d)
        shapes[p + "gate_bias"] = (kv,)
        shapes[p + "q_norm_gamma"] = (hd,)
        shapes[p + "k_norm_gamma"] = (hd,)
        shapes[p + "ffn_gate_weight"] = (f, d)
        shapes[p + "ffn_up_weight"] = (f, d)
        shapes[p + "ffn_down_weight"] = (d, f)
        shapes[p + "ln1_gamma"] = (d,)
        shapes[p + "ln2_gamma"] = (d,)
    shapes["final_ln_gamma"] = (d,)
    shapes["lm_head_weight"] = (v, d)
    return shapes


def param_count(cfg: Dict[str, Any]) -> int:
    total = 0
    for shape in param_shapes(cfg).values():
        n = 1
        for s in shape:
            n *= s
        total += n
    return total


def state_bytes_per_request(cfg: Dict[str, Any]) -> int:
    """What the recurrent form must keep for one request: per layer and
    key/value head the symmetric degree-2 embedding's ``hd (hd + 1) / 2``
    rows of ``hd`` value channels (S) and one normaliser (z), float32.
    The program pads this (its layout is its own business): compare."""
    _, n, _, _, kv, hd, _ = dims(cfg)
    return n * kv * (hd * (hd + 1) // 2) * (hd + 1) * 4


def forward_flops(cfg: Dict[str, Any], positions: int, attended: int) -> float:
    """FLOPs the forward pass needs for ``positions`` new positions: 2
    per parameter of the matmuls and position (q, k, v, gate, proj, the
    three FFN matrices, the head; the embedding is a lookup) and, per
    layer and key/value head, the recurrent form's work on a state of
    ``hd (hd + 1) / 2`` features by ``hd + 1``: decay and rank-one
    update, 3 an element, and the read, 2 an element and query head of
    the group (``benchmark/kernels/mxtpu_retention_decode.py`` counts
    the same).  The state is as large at any length, so ``attended``
    (the cached positions a softmax layer would read) changes nothing.
    Served tokens' share of the chip's peak (``serve_mfu``) reads it."""
    v, n, d, h, kv, hd, f = dims(cfg)
    matmul_params = n * (2 * h * hd * d + 2 * kv * hd * d + kv * d
                         + 3 * d * f) + v * d
    state = (hd * (hd + 1) // 2) * (hd + 1)
    retention = n * kv * state * (3 + 2 * (h // kv))
    return (2.0 * matmul_params + retention) * positions


def init_params(seed: int, cfg: Dict[str, Any], dtype=jnp.float32,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """Seeded random weights made ON the device, in the type they are
    used in: matrices N(0, std), gains 1 + N(0, std), and the forget
    gate's projection N(0, GATE_STD) with bias GATE_BIAS + N(0, std).
    The normed hidden state has unit mean square, so the gate's logit is
    4.0 +- 0.72 (hidden 5120) and ``sigmoid`` sits in 0.93-0.995 at two
    deviations: a state remembers some 15 to 200 tokens, so over 3 k
    tokens it neither dies (as at 0.5) nor saturates (as at 0.9999,
    where 3 k terms of equal weight pile up).  One jitted draw a LEAF
    (one compilation a distinct shape and kind): the embedding alone is
    3.1 GB in float32 on its way to bfloat16, and a draw of several
    leaves at once would not fit beside the 7 GB it leaves behind."""

    @functools.partial(jax.jit, static_argnames=("shape", "scale", "shift"))
    def draw(key, shape, scale, shift):
        x = jax.random.normal(key, shape, jnp.float32)
        return (shift + scale * x).astype(dtype)

    # seeds run a little past 2**31: fold the two halves in
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        if name.endswith("_gamma"):
            scale, shift = std, 1.0
        elif name.endswith("_gate_bias"):
            scale, shift = std, GATE_BIAS
        elif name.endswith("_gate_weight") and "ffn" not in name:
            scale, shift = GATE_STD, 0.0
        else:
            scale, shift = std, 0.0
        out[name] = draw(jax.random.fold_in(key, i), shape, scale, shift)
    return out


def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def _lin(x, w):
    return jnp.matmul(x, w.T, precision=_HI)


def _rope(x, theta):
    """``x`` [B, L, heads, hd] at positions 0..L-1."""
    hd = x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def retention(q, k, v, g):
    """The attention form: ``q`` [B, L, H, hd], ``k``/``v`` [B, L, KV,
    hd], log-gates ``g`` [B, L, KV] -> ``y`` [B, L, H, hd], float32."""
    b, l, heads, hd = q.shape
    kv = k.shape[2]
    big_g = jnp.cumsum(g, axis=1)                            # [B, L, KV]
    q = q.reshape(b, l, kv, heads // kv, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=_HI)
    causal = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
    diff = (big_g.transpose(0, 2, 1)[:, :, :, None]
            - big_g.transpose(0, 2, 1)[:, :, None, :])       # [B, KV, q, s]
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    a = jnp.square(s) / hd * decay[:, :, None]
    num = jnp.einsum("bkgqs,bskd->bqkgd", a, v, precision=_HI)
    den = jnp.sum(a, axis=-1).transpose(0, 3, 1, 2)[..., None]
    return (num / (den + RETENTION_EPS)).reshape(b, l, heads, hd)


@functools.partial(jax.jit, static_argnames=("heads", "theta", "eps"))
def _block(x, p, heads, theta, eps):
    """One decoder block on ``x`` [B, L, d]; ``p`` holds this layer's
    parameters under their suffixes, cast to float32 here.  Key/value
    heads and the head size are read from the shapes."""
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    b, l, d = x.shape
    hd = p["q_norm_gamma"].shape[0]
    kv = p["gate_weight"].shape[0]
    h = _rms(x, p["ln1_gamma"], eps)
    q = _lin(h, p["q_weight"]).reshape(b, l, heads, hd)
    k = _lin(h, p["k_weight"]).reshape(b, l, kv, hd)
    v = _lin(h, p["v_weight"]).reshape(b, l, kv, hd)
    q = _rope(_rms(q, p["q_norm_gamma"], eps), theta)
    k = _rope(_rms(k, p["k_norm_gamma"], eps), theta)
    g = jax.nn.log_sigmoid(_lin(h, p["gate_weight"]) + p["gate_bias"])
    y = retention(q, k, v, g).reshape(b, l, heads * hd)
    x = x + _lin(y, p["proj_weight"])
    h = _rms(x, p["ln2_gamma"], eps)
    f = jax.nn.silu(_lin(h, p["ffn_gate_weight"])) * _lin(
        h, p["ffn_up_weight"])
    return x + _lin(f, p["ffn_down_weight"])


@jax.jit
def _embed(tokens, e):
    return jnp.take(e, tokens.astype(jnp.int32), axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gain, w, eps):
    # the head's weight stays in its stored type's array (151,936 x 5120
    # is 3.1 GB in float32): it is cast in slices of the vocabulary
    h = _rms(x, gain.astype(jnp.float32), eps)
    out = jnp.zeros(x.shape[:-1] + (w.shape[0],), jnp.float32)
    for i in range(0, w.shape[0], 16384):
        out = out.at[..., i:i + 16384].set(
            _lin(h, w[i:i + 16384].astype(jnp.float32)))
    return out


def _layers(params) -> int:
    n = 0
    while f"layer{n}_q_weight" in params:
        n += 1
    return n


def forward(params: Dict[str, Any], tokens, heads: int,
            theta: float = 1e6, eps: float = 1e-6) -> jax.Array:
    """Logits [B, L, V] in float32 for token ids [B, L].  Runs layer by
    layer, so only one layer's float32 copy of the weights lives at a
    time (the parameters may be stored in bfloat16).  ``theta`` and
    ``eps`` default to the published ``rope_theta`` and
    ``rms_norm_eps``."""
    tokens = jnp.asarray(tokens, jnp.int32)
    x = _embed(tokens, params["embed_weight"])
    for i in range(_layers(params)):
        pre = f"layer{i}_"
        layer = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
        x = _block(x, layer, heads, float(theta), float(eps))
    return _head(x, params["final_ln_gamma"], params["lm_head_weight"],
                 float(eps))
