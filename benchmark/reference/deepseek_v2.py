"""Plain reference of the ``deepseek_v2`` family (deepseek-ai/DeepSeek-V2):
multi-head latent attention, one leading dense layer, then routed expert
layers beside shared experts, written fresh from the layer equations in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
The NON-absorbed attention form only (every head's keys and values are
up-projected from the latents), a Python loop over the experts with a
mask: no sort, no kernel, no cache, no chunks, and nothing imported from
the program under test.  ``RMS`` is RMSNorm with a learned gain.

    MLA     c_q = RMS(W_qa h);  q = W_qb c_q -> H heads of [q_n (nope) | q_r (rope)]
            [c | k_r] = W_kva h;  c <- RMS(c);  k_r <- RoPE_yarn(k_r)   (ONE rotary key for all heads)
            [k_n | v] = W_kvb c -> H heads of (nope | v);  q_r <- RoPE_yarn(q_r)
            s_ts = ([q_n | q_r]_t . [k_n | k_r]_s) * scale,  s <= t;  y = softmax(s) v;  out = W_o [y_1 .. y_H]
            scale = (nope + rope)^-0.5 * m^2,  m = 0.1 * mscale_all_dim * ln(factor) + 1
    YaRN    inv_freq_i = (1 - g_i) / (factor * base^(2i/rope)) + g_i / base^(2i/rope)
            g_i = 1 - clip((i - low) / (high - low), 0, 1);  low, high = floor / ceil of
            rope ln(original_max / (beta 2 pi)) / (2 ln base) at beta = beta_fast, beta_slow
            cos and sin scaled by mscale-ratio = (0.1 mscale ln f + 1) / (0.1 mscale_all_dim ln f + 1)
    dense   h + W_down(silu(W_gate x) * W_up x),  x = RMS(h)                    (layer 0)
    routed  p = softmax(W_g x) over ALL experts;  group score = max p within each of n_group groups;
            keep the topk_group best groups, zero the rest;  the top_k largest p among them;
            w_e = routed_scaling_factor * p_e   (not renormalised)
            out = h + shared(x) + sum over the chosen e IN ``experts_held`` of w_e expert_e(x)
    head    logits = W_head RMS(h_N)                                             (untied, no bias)

**The share.**  ``params["experts_held"]`` (int32 ids, made by
``init_params`` from the configuration's ``deployment_share``) says which
routed experts this chip holds; the routed sum runs over those alone,
and that partial result goes on to the next layer, as in the program.
The expert weights hold the held experts only, in that order.

The rotary layout is the half-split ("rotate half") one at absolute
positions 0, 1, 2, ...; the published weights interleave the pairs,
which with seeded weights is the same model up to a fixed permutation of
the rotary rows (``assumed`` in the configuration file).  Parameters
carry the program's names so that one dict serves both sides: a
FullyConnected weight is ``[out, in]``, a held expert's matrices are
``[in, out]``.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HI = "highest"
#: the published routing and rotary settings (config.json); ``forward``
#: takes them as keywords so that a tiny test can state its own
PUBLISHED = dict(
    n_group=8, topk_group=3, top_k=6, routed_scaling_factor=16.0,
    rope_theta=10000.0, eps=1e-6,
    rope_scaling={"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096, "type": "yarn"})


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    """The sizes, from a config file's keys (the source's own names)."""
    return dict(
        v=int(cfg["vocab_size"]), n=int(cfg["num_hidden_layers"]),
        d=int(cfg["hidden_size"]), h=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        vd=int(cfg["v_head_dim"]), f=int(cfg["intermediate_size"]),
        fe=int(cfg["moe_intermediate_size"]),
        held=int(cfg["n_routed_experts"]),
        experts=int(cfg.get("published", {}).get("n_routed_experts",
                                                 cfg["n_routed_experts"])),
        shared=int(cfg["n_shared_experts"]),
        dense=int(cfg["first_k_dense_replace"]))


def held_ids(cfg: Dict[str, Any]) -> np.ndarray:
    """The routed experts this chip holds: ``n_routed_experts`` of them
    from ``deployment_share.first_expert`` on."""
    first = int(cfg.get("deployment_share", {}).get("first_expert", 0))
    return np.arange(first, first + int(cfg["n_routed_experts"]),
                     dtype=np.int32)


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    z = dims(cfg)
    d, h = z["d"], z["h"]
    shapes: Dict[str, Tuple[int, ...]] = {"embed_weight": (z["v"], d)}
    for i in range(z["n"]):
        p = f"layer{i}_"
        shapes[p + "q_a_weight"] = (z["q_rank"], d)
        shapes[p + "q_a_norm_gamma"] = (z["q_rank"],)
        shapes[p + "q_b_weight"] = (h * (z["nope"] + z["rope"]), z["q_rank"])
        shapes[p + "kv_a_weight"] = (z["rank"] + z["rope"], d)
        shapes[p + "kv_a_norm_gamma"] = (z["rank"],)
        shapes[p + "kv_b_weight"] = (h * (z["nope"] + z["vd"]), z["rank"])
        shapes[p + "proj_weight"] = (d, h * z["vd"])
        shapes[p + "ln1_gamma"] = (d,)
        shapes[p + "ln2_gamma"] = (d,)
        if i < z["dense"]:
            shapes[p + "ffn_gate_weight"] = (z["f"], d)
            shapes[p + "ffn_up_weight"] = (z["f"], d)
            shapes[p + "ffn_down_weight"] = (d, z["f"])
        else:
            fs = z["shared"] * z["fe"]
            shapes[p + "router_weight"] = (z["experts"], d)
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


def latent_bytes_per_token(cfg: Dict[str, Any], itemsize: int = 2) -> int:
    """What one cached position of one layer NEEDS: the latent and the
    one rotary key.  The program stores it on whole 128-lane rows
    (its layout is its own business): compare."""
    z = dims(cfg)
    return (z["rank"] + z["rope"]) * itemsize


def forward_flops(cfg: Dict[str, Any], positions: int, attended: int) -> float:
    """FLOPs the forward pass NEEDS here for ``positions`` new positions
    that attend over ``attended`` cached ones between them: 2 per matmul
    parameter ACTIVE on this chip (the attention's five projections a
    layer, the dense FFN, the shared experts, the router, the EXPECTED
    held experts a position, ``top_k x held / experts`` of them, and the
    head; the embedding is a lookup), plus the attention in the
    up-projected form: ``2 x heads x (nope + rope + v)`` an attended
    position and layer.  The absorbed form the decode kernel runs does
    more (``2 x heads x (2 rank + rope)``); the smaller count is stated
    so that ``serve_mfu`` counts no work a path does not need."""
    z = dims(cfg)
    d, h = z["d"], z["h"]
    mla = (d * z["q_rank"] + z["q_rank"] * h * (z["nope"] + z["rope"])
           + d * (z["rank"] + z["rope"])
           + z["rank"] * h * (z["nope"] + z["vd"]) + h * z["vd"] * d)
    routed_layers = z["n"] - z["dense"]
    expected = PUBLISHED["top_k"] * z["held"] / z["experts"]
    active = (z["n"] * mla + z["dense"] * 3 * d * z["f"]
              + routed_layers * (3 * d * z["shared"] * z["fe"]
                                 + z["experts"] * d
                                 + expected * 3 * d * z["fe"])
              + z["v"] * d)
    attention = z["n"] * 2.0 * h * (z["nope"] + z["rope"] + z["vd"])
    return 2.0 * active * positions + attention * attended


def init_params(seed: int, cfg: Dict[str, Any], dtype=jnp.float32,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """Seeded random weights made ON the device, in the type they are
    used in: matrices N(0, std), gains 1 + N(0, std); one jitted draw a
    leaf.  ``experts_held`` (int32) is the share: the ids of the held
    experts."""

    @functools.partial(jax.jit, static_argnames=("shape", "scale", "shift"))
    def draw(key, shape, scale, shift):
        x = jax.random.normal(key, shape, jnp.float32)
        return (shift + scale * x).astype(dtype)

    # seeds run a little past 2**31: fold the two halves in
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    out = {}
    for i, (name, shape) in enumerate(sorted(param_shapes(cfg).items())):
        shift = 1.0 if name.endswith("_gamma") else 0.0
        out[name] = draw(jax.random.fold_in(key, i), shape, std, shift)
    out["experts_held"] = jnp.asarray(held_ids(cfg))
    return out


# ---------------------------------------------------------------------------
# the parts
# ---------------------------------------------------------------------------

def _rms(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def _lin(x, w):
    return jnp.matmul(x, w.astype(jnp.float32).T, precision=_HI)


def yarn(dim: int, theta: float, scaling: Dict[str, Any]):
    """(inv_freq [dim / 2], the factor on cos and sin)."""
    if not scaling:
        i = np.arange(dim // 2, dtype=np.float64)
        return (theta ** (-2.0 * i / dim)).astype(np.float32), 1.0
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def turns(beta):
        return dim * math.log(orig / (beta * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns(float(scaling["beta_fast"]))), 0)
    high = min(math.ceil(turns(float(scaling["beta_slow"]))), dim - 1)
    i = np.arange(dim // 2, dtype=np.float64)
    g = 1.0 - np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    plain = theta ** (-2.0 * i / dim)
    inv = (1.0 - g) * plain / factor + g * plain

    def m(scale):
        return 0.1 * scale * math.log(factor) + 1.0 if factor > 1 else 1.0

    return inv.astype(np.float32), (m(float(scaling.get("mscale", 1)))
                                    / m(float(scaling.get("mscale_all_dim",
                                                          0))))


def score_scale(nope: int, rope: int, scaling: Dict[str, Any]) -> float:
    scale = (nope + rope) ** -0.5
    if scaling and scaling.get("mscale_all_dim"):
        m = 0.1 * float(scaling["mscale_all_dim"]) * math.log(
            float(scaling["factor"])) + 1.0
        scale *= m * m
    return scale


def _rope(x, theta, scaling):
    """``x`` [L, heads, rope] at positions 0..L-1, half-split pairs."""
    half = x.shape[-1] // 2
    inv, mscale = yarn(x.shape[-1], theta, scaling)
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    cos, sin = jnp.cos(ang) * mscale, jnp.sin(ang) * mscale
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("heads", "theta", "eps",
                                             "scaling", "head_block"))
def _attention(x, p, heads, theta, eps, scaling, head_block):
    """The attention of one layer on ONE sequence ``x`` [L, d]; ``p``
    this layer's parameters.  Heads ``head_block`` at a time, so that
    the scores alive at once are ``[head_block, L, L]``."""
    scaling = dict(scaling)
    l, _ = x.shape
    rank = p["kv_a_norm_gamma"].shape[0]
    rope = p["kv_a_weight"].shape[0] - rank
    nope = p["q_b_weight"].shape[0] // heads - rope
    vd = p["kv_b_weight"].shape[0] // heads - nope
    h = _rms(x, p["ln1_gamma"].astype(jnp.float32), eps)
    cq = _rms(_lin(h, p["q_a_weight"]),
              p["q_a_norm_gamma"].astype(jnp.float32), eps)
    ckr = _lin(h, p["kv_a_weight"])
    c = _rms(ckr[:, :rank], p["kv_a_norm_gamma"].astype(jnp.float32), eps)
    k_r = _rope(ckr[:, None, rank:], theta, scaling)[:, 0]       # [L, rope]
    scale = score_scale(nope, rope, scaling)
    causal = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
    blocks = heads // head_block
    w_qb = p["q_b_weight"].reshape(blocks, head_block, nope + rope, -1)
    w_kvb = p["kv_b_weight"].reshape(blocks, head_block, nope + vd, rank)

    def some_heads(w):
        wq, wkv = (x.astype(jnp.float32) for x in w)
        q = jnp.einsum("lr,hdr->lhd", cq, wq, precision=_HI)
        q_r = _rope(q[..., nope:], theta, scaling)
        kv = jnp.einsum("lr,hdr->lhd", c, wkv, precision=_HI)
        s = (jnp.einsum("qhd,khd->hqk", q[..., :nope], kv[..., :nope],
                        precision=_HI)
             + jnp.einsum("qhd,kd->hqk", q_r, k_r, precision=_HI)) * scale
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", a, kv[..., nope:], precision=_HI)

    # ``lax.map``: one block of heads after another BY CONSTRUCTION (a
    # Python loop inside one jit leaves the order, and so how many
    # blocks' scores are alive at once, to the scheduler)
    outs = jax.lax.map(some_heads, (w_qb, w_kvb))        # [blocks, L, hb, v]
    y = outs.transpose(1, 0, 2, 3).reshape(l, heads * vd)
    return x + _lin(y, p["proj_weight"])


@functools.partial(jax.jit, static_argnames=("eps", "pieces"))
def _dense_ffn(x, p, eps, pieces):
    """``x`` [T, d]; the width in ``pieces`` slices (one float32 copy of
    a slice of the weights at a time)."""
    h = _rms(x, p["ln2_gamma"].astype(jnp.float32), eps)
    f = p["ffn_gate_weight"].shape[0]
    step = -(-f // pieces)
    out = x
    for a in range(0, f, step):
        g = jax.nn.silu(_lin(h, p["ffn_gate_weight"][a:a + step])) * _lin(
            h, p["ffn_up_weight"][a:a + step])
        out = out + _lin(g, p["ffn_down_weight"][:, a:a + step])
    return out


def route(x, w_router, n_group, topk_group, top_k, routed_scaling_factor):
    """``x`` [T, d] normed -> (chosen expert ids [T, top_k], their
    weights [T, top_k]); group-limited greedy over ALL experts."""
    p = jax.nn.softmax(_lin(x, w_router), axis=-1)               # [T, E]
    t, e = p.shape
    if n_group > 1:
        best = jnp.max(p.reshape(t, n_group, e // n_group), axis=-1)
        order = jnp.argsort(-best, axis=-1)[:, :topk_group]
        keep = jnp.zeros((t, n_group), bool).at[
            jnp.arange(t)[:, None], order].set(True)
        p = jnp.where(jnp.repeat(keep, e // n_group, axis=1), p, 0.0)
    ids = jnp.argsort(-p, axis=-1)[:, :top_k]
    return ids, jnp.take_along_axis(p, ids, axis=1) * routed_scaling_factor


@functools.partial(jax.jit, static_argnames=("eps", "n_group", "topk_group",
                                             "top_k",
                                             "routed_scaling_factor"))
def _router_and_shared(x, p, eps, n_group, topk_group, top_k,
                       routed_scaling_factor):
    h = _rms(x, p["ln2_gamma"].astype(jnp.float32), eps)
    ids, w = route(h, p["router_weight"], n_group, topk_group, top_k,
                   routed_scaling_factor)
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


def moe_layer(x, p, held, *, eps, n_group, topk_group, top_k,
              routed_scaling_factor, parts: bool = False):
    """``x`` [T, d] -> ``x + shared + routed`` where ``routed`` sums the
    chosen experts that are in ``held`` (ids; ``p``'s expert weights hold
    those, in that order).  ``parts``: return ``(shared, routed)``."""
    h, ids, w, shared = _router_and_shared(
        x, p, eps, n_group, topk_group, top_k, float(routed_scaling_factor))
    routed = jnp.zeros_like(x)
    for j, e in enumerate(np.asarray(held).tolist()):
        weight = jnp.sum(jnp.where(ids == e, w, 0.0), axis=1)
        routed = routed + _expert(h, p["experts_gate_weight"][j],
                                  p["experts_up_weight"][j],
                                  p["experts_down_weight"][j], weight)
    return (shared, routed) if parts else x + shared + routed


@jax.jit
def _embed(tokens, e):
    return jnp.take(e, tokens.astype(jnp.int32), axis=0).astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, gain, w, eps):
    # the head's weight is cast in slices of the vocabulary
    h = _rms(x, gain.astype(jnp.float32), eps)
    out = jnp.zeros(x.shape[:-1] + (w.shape[0],), jnp.float32)
    for i in range(0, w.shape[0], 6400):
        out = out.at[..., i:i + 6400].set(_lin(h, w[i:i + 6400]))
    return out


_ATTENTION_KEYS = ("ln1_gamma", "q_a_weight", "q_a_norm_gamma", "q_b_weight",
                   "kv_a_weight", "kv_a_norm_gamma", "kv_b_weight",
                   "proj_weight")


def _layers(params) -> int:
    n = 0
    while f"layer{n}_q_a_weight" in params:
        n += 1
    return n


def forward(params: Dict[str, Any], tokens, heads: int, *,
            head_block: int = 8, **settings) -> jax.Array:
    """Logits [B, L, V] in float32 for token ids [B, L].  A layer at a
    time, the attention a sequence and ``head_block`` heads at a time,
    the experts one at a time: beside the logits themselves it peaks at
    the hidden states ([B, L, d] float32, twice), one ``[head_block, L,
    L]`` block of scores (twice: the scores and their softmax) and one
    float32 copy of a slice of a weight: about 1 GB at three prompts of
    2,176 tokens and 8 heads a block, beside 0.67 GB of logits.  ``settings``
    override :data:`PUBLISHED` (a tiny test states its own groups)."""
    st = dict(PUBLISHED, **settings)
    scaling = tuple(sorted((st["rope_scaling"] or {}).items()))
    eps, theta = float(st["eps"]), float(st["rope_theta"])
    tokens = jnp.asarray(tokens, jnp.int32)
    b, l = tokens.shape
    held = np.asarray(params["experts_held"])
    x = _embed(tokens, params["embed_weight"])
    for i in range(_layers(params)):
        pre = f"layer{i}_"
        p = {k[len(pre):]: v for k, v in params.items() if k.startswith(pre)}
        att = {k: p[k] for k in _ATTENTION_KEYS}
        x = jnp.stack([_attention(x[r], att, heads, theta, eps, scaling,
                                  min(head_block, heads))
                       for r in range(b)])
        flat = x.reshape(b * l, -1)
        if "router_weight" in p:
            flat = moe_layer(flat, p, held, eps=eps, n_group=st["n_group"],
                             topk_group=st["topk_group"], top_k=st["top_k"],
                             routed_scaling_factor=st["routed_scaling_factor"])
        else:
            flat = _dense_ffn(flat, p, eps, 4)
        x = flat.reshape(b, l, -1)
    return _head(x, params["final_ln_gamma"], params["lm_head_weight"], eps)
