"""Plain reference of the ``nope_lm`` family: a pre-LN decoder with NO
positional encoding, written fresh from the layer equations in
float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``.
No cache, no kernels, no batching tricks, and nothing imported from the
program under test.

    h_0 = E[tokens]
    a   = LN1(h);  q,k,v = a W_q^T + b_q, ...   (H heads of d/H)
    h   = h + softmax(causal(q k^T / sqrt(d/H))) v W_o^T + b_o
    f   = LN2(h);  h = h + relu(f W_1^T + b_1) W_2^T + b_2
    logits = LN_f(h_N) W_head^T + b_head

LayerNorm has gain and bias and eps 1e-5.  Position enters through the
causal mask alone: the program has no position table, which is the
family's first departure from the OPT block it takes its widths from;
the second is the untied output head with a bias.  Parameters carry the
program's names (``layer{i}_q_weight`` is ``[d_out, d_in]``) so that one
dict serves both sides.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

LN_EPS = 1e-5
_HI = "highest"


def dims(cfg: Dict[str, Any]) -> Tuple[int, int, int, int, int]:
    """(vocab, layers, d_model, heads, ffn) from a config file's keys
    (the source's own names)."""
    return (int(cfg["vocab_size"]), int(cfg["num_hidden_layers"]),
            int(cfg["hidden_size"]), int(cfg["num_attention_heads"]),
            int(cfg["ffn_dim"]))


def param_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple[int, ...]]:
    v, n, d, _, f = dims(cfg)
    shapes: Dict[str, Tuple[int, ...]] = {"embed_weight": (v, d)}
    for i in range(n):
        p = f"layer{i}_"
        for nm in ("q", "k", "v", "proj"):
            shapes[p + nm + "_weight"] = (d, d)
            shapes[p + nm + "_bias"] = (d,)
        shapes[p + "ffn1_weight"] = (f, d)
        shapes[p + "ffn1_bias"] = (f,)
        shapes[p + "ffn2_weight"] = (d, f)
        shapes[p + "ffn2_bias"] = (d,)
        for ln in ("ln1", "ln2"):
            shapes[p + ln + "_gamma"] = (d,)
            shapes[p + ln + "_beta"] = (d,)
    shapes["final_ln_gamma"] = (d,)
    shapes["final_ln_beta"] = (d,)
    shapes["lm_head_weight"] = (v, d)
    shapes["lm_head_bias"] = (v,)
    return shapes


def param_count(cfg: Dict[str, Any]) -> int:
    total = 0
    for shape in param_shapes(cfg).values():
        n = 1
        for s in shape:
            n *= s
        total += n
    return total


def init_params(seed: int, cfg: Dict[str, Any], dtype=jnp.float32,
                std: float = 0.02) -> Dict[str, jax.Array]:
    """Seeded random weights made ON the device in one jitted call, in
    the type they are used in: matrices and biases N(0, std), LayerNorm
    gains 1 + N(0, std)."""
    shapes = param_shapes(cfg)
    groups: Dict[Tuple[int, ...], list] = {}
    for name in sorted(shapes):
        groups.setdefault(shapes[name], []).append(name)

    @jax.jit
    def make(key):
        # one draw per distinct shape (seven of them), sliced into its
        # leaves: a few hundred separate draws compile far longer
        out = {}
        for i, (shape, names) in enumerate(sorted(groups.items())):
            x = std * jax.random.normal(jax.random.fold_in(key, i),
                                        (len(names),) + shape, jnp.float32)
            for j, name in enumerate(names):
                leaf = x[j] + 1.0 if name.endswith("_gamma") else x[j]
                out[name] = leaf.astype(dtype)
        return out

    # seeds run a little past 2**31: fold the two halves in
    key = jax.random.fold_in(jax.random.PRNGKey(int(seed) & 0x7FFFFFFF),
                             int(seed) >> 31)
    return make(key)


def _ln(x, gamma, beta):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * gamma + beta


def _lin(x, w, b):
    return jnp.matmul(x, w.T, precision=_HI) + b


@functools.partial(jax.jit, static_argnames=("heads",))
def _block(h, p, heads):
    """One decoder block on ``h`` [B, L, d]; ``p`` holds this layer's
    parameters under their suffixes, cast to float32 here."""
    p = {k: v.astype(jnp.float32) for k, v in p.items()}
    b, l, d = h.shape
    hd = d // heads
    a = _ln(h, p["ln1_gamma"], p["ln1_beta"])
    q, k, v = (_lin(a, p[n + "_weight"], p[n + "_bias"])
               .reshape(b, l, heads, hd) for n in ("q", "k", "v"))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=_HI) / jnp.sqrt(
        jnp.float32(hd))
    causal = jnp.arange(l)[:, None] >= jnp.arange(l)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    att = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v,
                     precision=_HI).reshape(b, l, d)
    h = h + _lin(att, p["proj_weight"], p["proj_bias"])
    f = _ln(h, p["ln2_gamma"], p["ln2_beta"])
    f = jnp.maximum(_lin(f, p["ffn1_weight"], p["ffn1_bias"]), 0.0)
    return h + _lin(f, p["ffn2_weight"], p["ffn2_bias"])


@jax.jit
def _embed(tokens, e):
    return jnp.take(e.astype(jnp.float32), tokens.astype(jnp.int32), axis=0)


@jax.jit
def _head(h, gamma, beta, w, b):
    f32 = jnp.float32
    return _lin(_ln(h, gamma.astype(f32), beta.astype(f32)),
                w.astype(f32), b.astype(f32))


def _layers(params) -> int:
    n = 0
    while f"layer{n}_q_weight" in params:
        n += 1
    return n


def forward(params: Dict[str, Any], tokens, heads: int) -> jax.Array:
    """Logits [B, L, V] in float32 for token ids [B, L].  Runs layer by
    layer, so only one layer's float32 copy of the weights lives at a
    time (the parameters may be stored in bfloat16)."""
    tokens = jnp.asarray(tokens, jnp.int32)
    h = _embed(tokens, params["embed_weight"])
    for i in range(_layers(params)):
        pre = f"layer{i}_"
        layer = {k[len(pre):]: v for k, v in params.items()
                 if k.startswith(pre)}
        h = _block(h, layer, heads)
    return _head(h, params["final_ln_gamma"], params["final_ln_beta"],
                 params["lm_head_weight"], params["lm_head_bias"])


# ---------------------------------------------------------------------------
# analytic sizes the metrics use
# ---------------------------------------------------------------------------

def kv_bytes_per_token(cfg: Dict[str, Any], itemsize: int) -> int:
    """K and V of one position across every layer."""
    _, n, d, _, _ = dims(cfg)
    return 2 * n * d * itemsize


def forward_flops(cfg: Dict[str, Any], positions: int, attended: int) -> float:
    """FLOPs the forward pass needs for ``positions`` new positions that
    attend over ``attended`` cached positions between them (each one's
    own included): 2 per parameter of the matmuls and position (q, k, v,
    proj, the two FFN matrices, the head; the embedding is a lookup),
    and q.k and p.v, 2 each per attended position, layer and channel
    (``benchmark/kernels/mxtpu_flash_decode.py`` counts the same 4).
    Served tokens' share of the chip's peak (``serve_mfu``) reads it."""
    v, n, d, _, f = dims(cfg)
    matmul_params = n * (4 * d * d + 2 * d * f) + v * d
    return 2.0 * matmul_params * positions + 4.0 * n * d * attended
