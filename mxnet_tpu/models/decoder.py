"""A decoder-only LM described by data, and the one block that runs it.

The serving tier used to recover its model by counting
``layer{n}_q_weight`` keys and hard-coding biased LayerNorm, a ReLU FFN
and one head count (the twins of ``transformer.py``).  A
:class:`ModelSpec` says those things instead — the norm, the FFN, the
head counts, how position enters, and the **kind of attention of every
layer** — and :func:`decoder_forward` runs any such model over a
caller-owned cache.  The in-tree ``transformer-lm`` is
``ModelSpec(heads=h)``: every default below is that model, and its
programs are the ones the twins traced before (same ops, same order).

Attention kinds (``ModelSpec.attention``, one name for all layers or a
tuple with one name per layer):

* ``"softmax"`` — causal softmax attention; the cache is paged K and V
  (``serve.kvcache`` kind ``paged_kv``).
* ``"power_retention"`` — linear attention with a degree-2 kernel and a
  learned per-kv-head forget gate (``models/retention.py``); the cache
  is one fixed-size state per request and layer (kind
  ``recurrent_state``).

A window/global or hybrid model adds a kind here and a cache kind in
``serve.kvcache``; it does not add a file of twins.

Parameter names (``layer{i}_`` prefix; FullyConnected weights are
``[out, in]``): ``q/k/v/proj_weight`` (+ ``_bias`` when ``bias``),
``ln1/ln2_gamma`` (+ ``_beta`` for LayerNorm), ``ffn1/ffn2`` (ReLU) or
``ffn_gate/ffn_up/ffn_down`` (gated SiLU), ``q_norm/k_norm_gamma``
(``qk_norm``), ``gate_weight`` ``[kv_heads, d]`` + ``gate_bias``
(retention layers); ``embed_weight``, ``final_ln_*``, ``lm_head_*``.
"""
from __future__ import annotations

from dataclasses import dataclass, fields
from typing import Any, Callable, Dict, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError

__all__ = ["ModelSpec", "decoder_forward", "lm_config_from_params",
           "SOFTMAX", "POWER_RETENTION"]

SOFTMAX = "softmax"
POWER_RETENTION = "power_retention"
_ATTENTION_KINDS = (SOFTMAX, POWER_RETENTION)

_LN_EPS = 1e-5   # LayerNorm op default (ops/nn_ops.py)


@dataclass(frozen=True)
class ModelSpec:
    """The architecture of a decoder-only LM, as the engine is told it.
    The defaults are the in-tree ``transformer-lm``."""
    heads: int = 4
    kv_heads: Optional[int] = None      # None: as many as ``heads``
    head_dim: Optional[int] = None      # None: d_model // heads
    norm: str = "layernorm"             # "layernorm" (gain + bias) | "rmsnorm"
    norm_eps: float = _LN_EPS
    qk_norm: bool = False               # per-head RMSNorm of q and k
    bias: bool = True                   # projections, FFN and head biased
    ffn: str = "relu"                   # "relu" | "silu_gated"
    position: str = "none"              # "none" | "rope"
    rope_theta: float = 10000.0
    attention: Union[str, Tuple[str, ...]] = SOFTMAX
    retention_eps: float = 1e-6         # the retention normaliser's eps

    def __post_init__(self):
        if self.norm not in ("layernorm", "rmsnorm"):
            raise MXNetError(f"ModelSpec.norm {self.norm!r}: expected "
                             "'layernorm' or 'rmsnorm'")
        if self.ffn not in ("relu", "silu_gated"):
            raise MXNetError(f"ModelSpec.ffn {self.ffn!r}: expected 'relu' "
                             "or 'silu_gated'")
        if self.position not in ("none", "rope"):
            raise MXNetError(f"ModelSpec.position {self.position!r}: "
                             "expected 'none' or 'rope'")
        kinds = ((self.attention,) if isinstance(self.attention, str)
                 else tuple(self.attention))
        for k in kinds:
            if k not in _ATTENTION_KINDS:
                raise MXNetError(f"ModelSpec.attention kind {k!r}: expected "
                                 f"one of {_ATTENTION_KINDS}")
        if not isinstance(self.attention, str):
            object.__setattr__(self, "attention", kinds)
        kv = self.heads if self.kv_heads is None else int(self.kv_heads)
        if kv < 1 or self.heads % kv:
            raise MXNetError(f"heads {self.heads} not a multiple of "
                             f"kv_heads {kv}")

    @classmethod
    def resolve(cls, model: Any, heads: int) -> "ModelSpec":
        """``None`` (the in-tree LM with the caller's ``heads``), a
        :class:`ModelSpec`, or a dict of its fields (a configuration
        file's ``serve.engine.model``)."""
        if model is None:
            return cls(heads=int(heads))
        if isinstance(model, cls):
            return model
        if isinstance(model, dict):
            known = {f.name for f in fields(cls)}
            extra = sorted(set(model) - known)
            if extra:
                raise MXNetError(f"ModelSpec has no field(s) {extra}; it "
                                 f"has {sorted(known)}")
            model = dict(model)
            if isinstance(model.get("attention"), list):
                model["attention"] = tuple(model["attention"])
            model.setdefault("heads", int(heads))
            return cls(**model)
        raise MXNetError(f"cannot read a ModelSpec from {type(model)}")

    @property
    def num_kv_heads(self) -> int:
        return self.heads if self.kv_heads is None else int(self.kv_heads)

    def dims(self, d_model: int) -> Tuple[int, int, int]:
        """(heads, kv_heads, head_dim) for a model of width ``d_model``."""
        if self.head_dim is not None:
            return self.heads, self.num_kv_heads, int(self.head_dim)
        if d_model % self.heads:
            raise MXNetError(f"d_model {d_model} not divisible by heads "
                             f"{self.heads}")
        return self.heads, self.num_kv_heads, d_model // self.heads

    def layer_kinds(self, num_layers: int) -> Tuple[str, ...]:
        if isinstance(self.attention, str):
            return (self.attention,) * num_layers
        if len(self.attention) != num_layers:
            raise MXNetError(
                f"ModelSpec.attention names {len(self.attention)} layers, "
                f"the parameters hold {num_layers}")
        return self.attention

    def signature(self) -> str:
        """A short stable string for program-cache fingerprints; empty
        for the in-tree LM, whose keys predate the description."""
        if self == ModelSpec(heads=self.heads):
            return ""
        return ":" + ",".join(f"{f.name}={getattr(self, f.name)}"
                              for f in fields(self) if f.name != "heads")


# ---------------------------------------------------------------------------
# the parts; each mirrors the registered symbol op where one exists
# ---------------------------------------------------------------------------

def _param(params, name):
    try:
        return params[name]
    except KeyError:
        raise MXNetError(f"transformer_lm params missing {name!r} — not a "
                         "transformer_lm parameter dict?")


def lm_config_from_params(params):
    """Infer ``(vocab_size, num_layers, d_model)`` from a decoder LM's
    parameter dict (head counts are not recoverable from shapes — they
    come from the caller's :class:`ModelSpec`)."""
    embed = _param(params, "embed_weight")
    n = 0
    while f"layer{n}_q_weight" in params:
        n += 1
    if n == 0:
        raise MXNetError("no layer0_q_weight: not transformer_lm params")
    return int(embed.shape[0]), n, int(embed.shape[1])


def _fcm(x, weight, bias=None):
    """Mirror of the FullyConnected op on [..., d_in] activations."""
    lead = x.shape[:-1]
    h = x.reshape((-1, x.shape[-1]))
    if h.dtype != weight.dtype:
        h = h.astype(weight.dtype)
    h = jnp.dot(h, weight.T)
    if bias is not None:
        h = h + bias.astype(weight.dtype)
    return h.reshape(lead + (weight.shape[0],))


def _lnm(x, gamma, beta):
    """Mirror of the LayerNorm op (f32 stats under AMP)."""
    x32 = x.astype(jnp.float32) if x.dtype in (jnp.bfloat16,
                                               jnp.float16) else x
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    xhat = (x32 - mean) * jax.lax.rsqrt(var + _LN_EPS)
    out = xhat * gamma.astype(x32.dtype) + beta.astype(x32.dtype)
    return out.astype(x.dtype)


def _rmsm(x, gamma, eps):
    """RMSNorm over the last axis with a learned gain, float32 inside."""
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(ms + np.float32(eps)) * gamma.astype(
        jnp.float32)
    return out.astype(x.dtype)


def _norm(spec, params, prefix, x):
    if spec.norm == "layernorm":
        return _lnm(x, _param(params, prefix + "_gamma"),
                    _param(params, prefix + "_beta"))
    return _rmsm(x, _param(params, prefix + "_gamma"), spec.norm_eps)


def _linear(spec, params, name, x):
    return _fcm(x, _param(params, name + "_weight"),
                _param(params, name + "_bias") if spec.bias else None)


def rope(x, positions, theta: float):
    """Rotary positions on ``x`` [..., heads, hd] at integer
    ``positions`` [...] (the leading shape of ``x``): the half-split
    ("rotate half") convention of the Qwen/Llama family, float32
    inside."""
    hd = x.shape[-1]
    half = hd // 2
    inv = np.float32(theta) ** (np.arange(half, dtype=np.float32)
                                * np.float32(-2.0 / hd))
    ang = positions.astype(jnp.float32)[..., None, None] * inv   # [..,1,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def embed(params, tokens):
    with jax.named_scope("embed"):
        return jnp.take(_param(params, "embed_weight"),
                        tokens.astype(jnp.int32), axis=0)


def block(spec: ModelSpec, params, i: int, kind: str, h, positions,
          attend: Callable):
    """One decoder block on hidden states ``h`` ([..., d]).

    ``attend(q, k, v, gate)`` receives the per-head states (q
    [..., H, hd]; k, v [..., KV, hd]; ``gate`` the log forget gate
    [..., KV] in float32 for a retention layer, else None), owns the
    cache, and returns the attention output [..., H, hd].  The parts are
    ``jax.named_scope``s, so a device trace names them."""
    heads, kv, hd = spec.dims(h.shape[-1])
    lead = h.shape[:-1]

    def name(suffix):
        return f"layer{i}_{suffix}"

    with jax.named_scope("qkv"):
        hn = _norm(spec, params, name("ln1"), h)
        q, k, v = (_linear(spec, params, name(nm), hn)
                   for nm in ("q", "k", "v"))
        gate = None
        if kind == POWER_RETENTION:
            logit = (_fcm(hn, _param(params, name("gate_weight")))
                     .astype(jnp.float32)
                     + _param(params, name("gate_bias")).astype(jnp.float32))
            gate = jax.nn.log_sigmoid(logit)
    q = q.reshape(lead + (heads, hd))
    k = k.reshape(lead + (kv, hd))
    v = v.reshape(lead + (kv, hd))
    if spec.qk_norm:
        with jax.named_scope("qkv"):
            q = _rmsm(q, _param(params, name("q_norm_gamma")), spec.norm_eps)
            k = _rmsm(k, _param(params, name("k_norm_gamma")), spec.norm_eps)
    if spec.position == "rope":
        with jax.named_scope("rope"):
            q = rope(q, positions, spec.rope_theta)
            k = rope(k, positions, spec.rope_theta)
    att = attend(q, k, v, gate).reshape(lead + (heads * hd,))
    with jax.named_scope("proj"):
        h = h + _linear(spec, params, name("proj"), att)
    with jax.named_scope("ffn"):
        hn = _norm(spec, params, name("ln2"), h)
        if spec.ffn == "relu":
            f = _linear(spec, params, name("ffn1"), hn)
            f = jnp.maximum(f, 0)
            return h + _linear(spec, params, name("ffn2"), f)
        f = (jax.nn.silu(_linear(spec, params, name("ffn_gate"), hn))
             * _linear(spec, params, name("ffn_up"), hn))
        return h + _linear(spec, params, name("ffn_down"), f)


def lm_head(spec: ModelSpec, params, h):
    with jax.named_scope("lm_head"):
        h = _norm(spec, params, "final_ln", h)
        return _linear(spec, params, "lm_head", h)


def decoder_forward(spec: ModelSpec, params: Dict[str, Any], tokens,
                    positions, attend: Callable):
    """Logits [..., V] for ``tokens`` [...] (one position per decode row
    ``[B]``, or a chunk / verify window ``[B, C]``) over a caller-owned
    cache.  ``positions`` has the tokens' shape (absolute positions; read
    only where ``spec.position`` is ``"rope"`` — may be None otherwise).
    ``attend(layer, kind, q, k, v, gate)`` extends the caller's cache
    with the new states and returns each position's attention over the
    cached prefix, itself included (see :func:`block`)."""
    _, num_layers, _ = lm_config_from_params(params)
    kinds = spec.layer_kinds(num_layers)
    h = embed(params, tokens)
    for i, kind in enumerate(kinds):
        h = block(spec, params, i, kind, h, positions,
                  lambda q, k, v, g, i=i, kind=kind: attend(i, kind, q, k,
                                                           v, g))
    return lm_head(spec, params, h)
