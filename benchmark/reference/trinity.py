"""Plain reference of the ``afmoe`` family (arcee-ai/Trinity-Large-Preview):
sliding-window and global softmax layers with grouped heads, a gated
attention output, "sandwich" norms, one leading dense layer, then expert
layers routed by sigmoid scores with a selection bias beside one shared
expert, written fresh from the layer equations in float32 ``jax.numpy``
under ``jax.default_matmul_precision("highest")``.  Whole-prefix masked
attention a block of queries at a time, a Python loop over the experts
with a mask: no cache, no ring, no chunks, no kernel, and nothing
imported from the program under test.  ``RMS`` is RMSNorm with a gain.

    embed   h_0 = sqrt(d) * E[token]                              (mup_enabled)
    block   u = RMS_in(x);  h = x + RMS_post_attn(Attn(u));  y = h + RMS_post_ffn(FFN(RMS_pre_ffn(h)))
    Attn    q = W_q u, k = W_k u, v = W_v u  (H query heads, KV key/value heads of hd)
            q, k <- RMS(q), RMS(k) per head;  sliding layers: q, k <- RoPE(q, k) (half-split, theta)
            query head i reads key/value head i // (H / KV);  s = q.k / sqrt(hd)
            sliding: query p sees key j iff 0 <= p - j < window;  full: iff j <= p
            o = W_o (sigmoid(W_g u) * concat_heads(softmax(s) v))
    dense   W_down(silu(W_gate x) * W_up x)                                   (layer 0)
    experts s = sigmoid(W_r x) [experts], float32;  chosen = top_k of (s + b)  (b: the selection bias)
            w_e = route_scale * s_e / sum of s over ALL chosen;  FFN = shared(x) + sum over the
            chosen e IN ``experts_held`` of w_e expert_e(x)
    head    logits = W_head RMS(h_N)                                           (untied, no bias)

**The share.**  ``params["experts_held"]`` (int32 ids, from the
configuration's ``deployment_share``) says which routed experts this
chip holds; the routed sum runs over those alone and the partial result
goes on to the next layer, as in the program.  The expert weights hold
the held experts only, in that order.  Parameters carry the program's
names: a FullyConnected weight is ``[out, in]``, a held expert's
matrices are ``[in, out]``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"
#: the published settings (config.json); ``forward`` takes them as
#: keywords so that a tiny test can state its own.  ``layer_types`` is
#: the benchmark configuration's cut: published layers 0, 8, 9, 10, 11.
PUBLISHED = dict(
    sliding_window=4096, rope_theta=10000.0, eps=1e-5, top_k=4,
    route_scale=2.448,
    layer_types=("sliding_attention",) * 4 + ("full_attention",))
#: the selection bias's spread, sized from the score gap: at the published
#: widths (routing logits of std 0.02 x sqrt(3072) = 1.1 over 256 experts)
#: the fourth and fifth sigmoid scores of a token lie a median 0.0062
#: apart, and a bias of about that spread changes one of the four choices
#: in twelve (8.5 %) and leaves the load near uniform: 12.7 of the 32 held
#: experts hit by 32 rows, as with no bias (a simulation of the router,
#: PERF.md section 6; ``tests/test_trinity_serve.py`` pins it).  A trained
#: bias balances load; one several gaps wide would decide the routing and
#: send most tokens to a hot set of experts that moves with the seed
BIAS_STD = 0.006


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The sizes, from a config file's keys (the source's own names)."""
    return dict(
        v=int(cfg["vocab_size"]), n=int(cfg["num_hidden_layers"]),
        d=int(cfg["hidden_size"]), h=int(cfg["num_attention_heads"]),
        kv=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        f=int(cfg["intermediate_size"]), fe=int(cfg["moe_intermediate_size"]),
        held=int(cfg["num_experts"]),
        experts=int(cfg.get("published", {}).get("num_experts",
                                                 cfg["num_experts"])),
        shared=int(cfg["num_shared_experts"]),
        dense=int(cfg["num_dense_layers"]))


def held_ids(cfg: Dict[str, Any]) -> np.ndarray:
    """The routed experts this chip holds: ``num_experts`` of them from
    ``deployment_share.first_expert`` on."""
    first = int(cfg.get("deployment_share", {}).get("first_expert", 0))
    return np.arange(first, first + int(cfg["num_experts"]), dtype=np.int32)


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    z = dims(cfg)
    d, qw, kw = z["d"], z["h"] * z["hd"], z["kv"] * z["hd"]
    shapes: Dict[str, Tuple[int, ...]] = {"embed_weight": (z["v"], d)}
    for i in range(z["n"]):
        p = f"layer{i}_"
        shapes.update({
            p + "q_weight": (qw, d), p + "k_weight": (kw, d),
            p + "v_weight": (kw, d), p + "attn_gate_weight": (qw, d),
            p + "proj_weight": (d, qw),
            p + "q_norm_gamma": (z["hd"],), p + "k_norm_gamma": (z["hd"],),
            p + "ln1_gamma": (d,), p + "post_attn_norm_gamma": (d,),
            p + "ln2_gamma": (d,), p + "post_ffn_norm_gamma": (d,)})
        if i < z["dense"]:
            shapes[p + "ffn_gate_weight"] = (z["f"], d)
            shapes[p + "ffn_up_weight"] = (z["f"], d)
            shapes[p + "ffn_down_weight"] = (d, z["f"])
        else:
            fs = z["shared"] * z["fe"]
            shapes[p + "router_weight"] = (z["experts"], d)
            shapes[p + "router_bias"] = (z["experts"],)
            shapes[p + "shared_gate_weight"] = (fs, d)
            shapes[p + "shared_up_weight"] = (fs, d)
            shapes[p + "shared_down_weight"] = (d, fs)
            shapes[p + "experts_gate_weight"] = (z["held"], d, z["fe"])
            shapes[p + "experts_up_weight"] = (z["held"], d, z["fe"])
            shapes[p + "experts_down_weight"] = (z["held"], z["fe"], d)
    shapes["final_ln_gamma"] = (d,)
    shapes["lm_head_weight"] = (z["v"], d)
    return shapes


def param_count(cfg: Dict[str, Any]) -> int:
    return sum(int(np.prod(s)) for s in param_shapes(cfg).values())


def kv_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """What one cached position of one layer needs: its K and V rows."""
    z = dims(cfg)
    return 2 * z["kv"] * z["hd"] * itemsize


def forward_flops(cfg: Dict[str, Any], positions: int, attended: int) -> float:
    """FLOPs the forward pass NEEDS here for ``positions`` new positions
    that attend over ``attended`` cached ones between them: 2 per matmul
    parameter ACTIVE on this chip (a layer's five attention projections,
    the dense FFN, the router, the shared expert, the EXPECTED held
    experts a position, ``top_k x held / experts``, and the head; the
    embedding is a lookup), plus ``4 x heads x head_dim`` an attended
    position and layer (q.k and p.v).  A sliding layer attends over at
    most ``window`` positions a query: it is counted ``min(attended,
    positions x window)``, exact for the decode rows of a cell whose
    prompts are longer than the window, and over by up to ``window^2 /
    2`` a prompt's prefill (its first ``window`` queries see fewer)."""
    z = dims(cfg)
    d, qw, kw = z["d"], z["h"] * z["hd"], z["kv"] * z["hd"]
    attention = 3 * d * qw + 2 * d * kw
    routed = z["n"] - z["dense"]
    expected = PUBLISHED["top_k"] * z["held"] / z["experts"]
    active = (z["n"] * attention + z["dense"] * 3 * d * z["f"]
              + routed * (z["experts"] * d + 3 * d * z["shared"] * z["fe"]
                          + expected * 3 * d * z["fe"])
              + z["v"] * d)
    kinds = cfg.get("layer_types", PUBLISHED["layer_types"])
    window = int(cfg.get("sliding_window", PUBLISHED["sliding_window"]))
    sliding = sum(1 for k in kinds if k == "sliding_attention")
    per = 4.0 * z["h"] * z["hd"]
    return (2.0 * active * positions
            + per * (sliding * min(attended, positions * window)
                     + (z["n"] - sliding) * attended))


def init_params(seed: int, cfg: Dict[str, Any], dtype=jnp.float32,
                std: float = 0.02, bias_std: float = BIAS_STD
                ) -> Dict[str, jax.Array]:
    """Seeded random weights made ON the device, in the type they are
    used in: matrices N(0, std), gains 1 + N(0, std), the selection bias
    N(0, bias_std) in float32; one jitted draw a leaf.  ``experts_held``
    (int32) is the share: the ids of the held experts."""

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
        if name.endswith("_router_bias"):
            out[name] = draw(jax.random.fold_in(key, i), shape, bias_std, 0.0,
                             jnp.float32)
            continue
        shift = 1.0 if name.endswith("_gamma") else 0.0
        out[name] = draw(jax.random.fold_in(key, i), shape, std, shift,
                         jnp.dtype(dtype))
    out["experts_held"] = jnp.asarray(held_ids(cfg))
    return out


# ---------------------------------------------------------------------------
# the parts
# ---------------------------------------------------------------------------

def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain.astype(jnp.float32)


def _lin(x, w):
    return jnp.matmul(x, w.astype(jnp.float32).T, precision=_HI)


def _rope(x, theta):
    """``x`` [L, heads, hd] at positions 0..L-1, half-split pairs."""
    half = x.shape[-1] // 2
    inv = (theta ** (-2.0 * np.arange(half, dtype=np.float64)
                     / x.shape[-1])).astype(np.float32)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "window", "rotate",
                                             "theta", "eps", "q_block"))
def _attention(x, p, heads, window, rotate, theta, eps, q_block):
    """The attention half of one layer on ONE sequence ``x`` [L, d]
    (``L`` a multiple of ``q_block``); ``p`` this layer's parameters.
    Queries ``q_block`` at a time, so that the scores alive at once are
    ``[heads, q_block, L]``."""
    l, _ = x.shape
    hd = p["q_norm_gamma"].shape[0]
    kv = p["k_weight"].shape[0] // hd
    g = heads // kv
    u = _rms(x, p["ln1_gamma"], eps)
    q = _rms(_lin(u, p["q_weight"]).reshape(l, heads, hd), p["q_norm_gamma"],
             eps)
    k = _rms(_lin(u, p["k_weight"]).reshape(l, kv, hd), p["k_norm_gamma"], eps)
    v = _lin(u, p["v_weight"]).reshape(l, kv, hd)
    if rotate:
        q, k = _rope(q, theta), _rope(k, theta)
    key_pos = jnp.arange(l)

    def some_queries(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, q_block).reshape(
            q_block, kv, g, hd)
        s = jnp.einsum("qkgd,lkd->kgql", qb, k, precision=_HI) / math.sqrt(hd)
        qpos = start + jnp.arange(q_block)
        seen = key_pos[None, :] <= qpos[:, None]
        if window:
            seen &= key_pos[None, :] > qpos[:, None] - window
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("kgql,lkd->qkgd", a, v,
                          precision=_HI).reshape(q_block, heads * hd)

    # ``lax.map``: one block of queries after another BY CONSTRUCTION
    y = jax.lax.map(some_queries, jnp.arange(0, l, q_block)).reshape(l, -1)
    y = y * jax.nn.sigmoid(_lin(u, p["attn_gate_weight"]))
    return x + _rms(_lin(y, p["proj_weight"]), p["post_attn_norm_gamma"], eps)


@functools.partial(jax.jit, static_argnames=("eps", "pieces"))
def _dense_ffn(x, p, eps, pieces):
    """``x`` [T, d]; the width in ``pieces`` slices (one float32 copy of
    a slice of the weights at a time)."""
    h = _rms(x, p["ln2_gamma"], eps)
    f = p["ffn_gate_weight"].shape[0]
    step = -(-f // pieces)
    out = jnp.zeros_like(x)
    for a in range(0, f, step):
        gt = jax.nn.silu(_lin(h, p["ffn_gate_weight"][a:a + step])) * _lin(
            h, p["ffn_up_weight"][a:a + step])
        out = out + _lin(gt, p["ffn_down_weight"][:, a:a + step])
    return x + _rms(out, p["post_ffn_norm_gamma"], eps)


def route(x, w_router, bias, top_k, route_scale):
    """``x`` [T, d] normed -> (chosen expert ids [T, top_k], their
    weights [T, top_k]): the top_k of ``sigmoid + bias``, weighted by
    the sigmoid alone, renormalised over the chosen."""
    s = jax.nn.sigmoid(_lin(x, w_router))                        # [T, E]
    ids = jnp.argsort(-(s + bias.astype(jnp.float32)), axis=-1)[:, :top_k]
    w = jnp.take_along_axis(s, ids, axis=1)
    return ids, w / jnp.sum(w, axis=1, keepdims=True) * route_scale


@functools.partial(jax.jit, static_argnames=("eps", "top_k", "route_scale"))
def _router_and_shared(x, p, eps, top_k, route_scale):
    h = _rms(x, p["ln2_gamma"], eps)
    ids, w = route(h, p["router_weight"], p["router_bias"], top_k,
                   route_scale)
    shared = _lin(jax.nn.silu(_lin(h, p["shared_gate_weight"]))
                  * _lin(h, p["shared_up_weight"]), p["shared_down_weight"])
    return h, ids, w, shared


@jax.jit
def _expert(h, wg, wu, wd, weight):
    """One expert over every token, weighted (0 where it was not chosen)."""
    f32 = jnp.float32
    g = jax.nn.silu(jnp.matmul(h, wg.astype(f32), precision=_HI)) * jnp.matmul(
        h, wu.astype(f32), precision=_HI)
    return jnp.matmul(g, wd.astype(f32), precision=_HI) * weight[:, None]


def moe_layer(x, p, held, *, eps, top_k, route_scale, parts: bool = False):
    """``x`` [T, d] -> ``x + RMS_post_ffn(shared + routed)`` where
    ``routed`` sums the chosen experts that are in ``held`` (ids; ``p``'s
    expert weights hold those, in that order).  ``parts``: return
    ``(shared, routed)``, before the norm."""
    h, ids, w, shared = _router_and_shared(x, p, eps, top_k,
                                           float(route_scale))
    routed = jnp.zeros_like(x)
    for j, e in enumerate(np.asarray(held).tolist()):
        weight = jnp.sum(jnp.where(ids == e, w, 0.0), axis=1)
        routed = routed + _expert(h, p["experts_gate_weight"][j],
                                  p["experts_up_weight"][j],
                                  p["experts_down_weight"][j], weight)
    if parts:
        return shared, routed
    return x + _rms(shared + routed, p["post_ffn_norm_gamma"], eps)


@functools.partial(jax.jit, static_argnames=("scale",))
def _embed(tokens, e, scale):
    return jnp.take(e, tokens.astype(jnp.int32), axis=0).astype(
        jnp.float32) * scale


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gain, w, eps):
    # the head's weight is cast in slices of the vocabulary
    h = _rms(x, gain, eps)
    out = jnp.zeros(x.shape[:-1] + (w.shape[0],), jnp.float32)
    step = 6256
    for i in range(0, w.shape[0], step):
        out = out.at[..., i:i + step].set(_lin(h, w[i:i + step]))
    return out


_ATTENTION_KEYS = ("ln1_gamma", "q_weight", "k_weight", "v_weight",
                   "q_norm_gamma", "k_norm_gamma", "attn_gate_weight",
                   "proj_weight", "post_attn_norm_gamma")


def _layers(params) -> int:
    n = 0
    while f"layer{n}_q_weight" in params:
        n += 1
    return n


def forward(params: Dict[str, Any], tokens, heads: int, *,
            q_block: int = 512, **settings) -> jax.Array:
    """Logits [B, L, V] in float32 for token ids [B, L].  A layer at a
    time, the attention a sequence and ``q_block`` queries at a time, the
    experts one at a time: beside the logits it holds the hidden states
    ([B, L, d] float32, twice), one ``[heads, q_block, L]`` block of
    scores (twice) and one float32 copy of a weight: about 1 GB at three
    prompts of 4,480 tokens, beside 1.35 GB of logits.  ``settings``
    override :data:`PUBLISHED` (a tiny test states its own)."""
    st = dict(PUBLISHED, **settings)
    eps, theta = float(st["eps"]), float(st["rope_theta"])
    tokens = jnp.asarray(tokens, jnp.int32)
    b, l = tokens.shape
    qb = min(q_block, l)
    pad = -(-l // qb) * qb - l
    held = np.asarray(params["experts_held"])
    d = params["embed_weight"].shape[1]
    x = _embed(tokens, params["embed_weight"], math.sqrt(d))
    kinds = tuple(st["layer_types"])
    for i in range(_layers(params)):
        pre = f"layer{i}_"
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        att = {k: p[k] for k in _ATTENTION_KEYS}
        sliding = kinds[i] == "sliding_attention"
        x = jnp.stack([
            _attention(jnp.pad(x[r], ((0, pad), (0, 0))), att, heads,
                       int(st["sliding_window"]) if sliding else 0, sliding,
                       theta, eps, qb)[:l]
            for r in range(b)])
        flat = x.reshape(b * l, -1)
        if "router_weight" in p:
            flat = moe_layer(flat, p, held, eps=eps, top_k=int(st["top_k"]),
                             route_scale=float(st["route_scale"]))
        else:
            flat = _dense_ffn(flat, p, eps, 4)
        x = flat.reshape(b, l, -1)
    return _head(x, params["final_ln_gamma"], params["lm_head_weight"], eps)
