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

* ``"softmax"`` — causal softmax attention over the whole prefix; the
  cache is paged K and V (``serve.kvcache`` kind ``paged_kv``).
* ``"sliding"`` — causal softmax attention over the last
  ``sliding_window`` positions (itself included); its K and V rows live
  in a ring of blocks a request (kind ``paged_window``).  A model may mix
  it with ``"softmax"`` layers (window and global layers, one allocator).
* ``"power_retention"`` — linear attention with a degree-2 kernel and a
  learned per-kv-head forget gate (``models/retention.py``); the cache
  is one fixed-size state per request and layer (kind
  ``recurrent_state``).
* ``"latent"`` — multi-head latent attention: low-rank queries
  (``q_lora_rank``; None: one full projection) and ONE compressed
  key/value row a position, ``[c | k_r]`` of ``kv_lora_rank +
  qk_rope_head_dim`` values shared by all heads (the normed latent and
  one rotary key), from which every head's no-position key and its
  value are up-projected; rotary positions (optionally YaRN-scaled) on
  ``qk_rope_head_dim`` channels only.  The cache is that row, paged
  (kind ``paged_latent``).

Softmax layers may have grouped heads (``kv_heads`` < ``heads``: query
head ``i`` reads key/value head ``i // (heads / kv_heads)``), rotary
positions on every layer (``position="rope"``) or on the sliding ones
only (``"rope_sliding"``), an output gate (``attn_gate``: ``o = W_o
(sigmoid(W_g u) * attention)``) and a "sandwich" of norms
(``sandwich_norm``: an RMSNorm of the attention's and of the FFN's
output before each residual add).  ``embed_scale`` multiplies the
embedding.  A hybrid model adds a kind here and a cache kind in
``serve.kvcache``; it does not add a file of twins.

The FFN of a layer is dense (``ffn``: ReLU or gated SiLU) or, where
``ffn_layers`` says ``"routed"``, an expert layer
(``models/experts.py``): a router over ``n_routed_experts`` with
``experts_per_token`` chosen (``group_limited_greedy`` over ``n_group``
groups of which ``topk_group`` are kept; scores ``score_func`` softmax
or sigmoid, and with ``router_bias`` a selection bias that decides the
choice and not the weights), shared experts beside them,
and **the experts this chip holds** (``experts_held`` = first, count):
the layer routes over all of them and computes the held ones' part.

Parameter names (``layer{i}_`` prefix; FullyConnected weights are
``[out, in]``): ``q/k/v/proj_weight`` (+ ``_bias`` when ``bias``),
``ln1/ln2_gamma`` (+ ``_beta`` for LayerNorm), ``ffn1/ffn2`` (ReLU) or
``ffn_gate/ffn_up/ffn_down`` (gated SiLU), ``q_norm/k_norm_gamma``
(``qk_norm``), ``attn_gate_weight`` ``[heads * hd, d]`` (``attn_gate``),
``post_attn_norm/post_ffn_norm_gamma`` (``sandwich_norm``),
``gate_weight`` ``[kv_heads, d]`` + ``gate_bias``
(retention layers); ``q_a/q_b_weight`` + ``q_a_norm_gamma`` (or
``q_weight``), ``kv_a/kv_b_weight`` + ``kv_a_norm_gamma`` (latent
layers; ``kv_b`` is ``[heads * (nope + v), kv_lora_rank]``, a head's
key rows before its value rows); ``router_weight`` ``[experts, d]``
(+ ``router_bias`` ``[experts]`` float32),
``shared_gate/up/down_weight``, ``experts_gate/up_weight`` ``[held, d,
width]`` and ``experts_down_weight`` ``[held, width, d]`` (routed
layers: a held expert's matrices are ``[in, out]``, as the grouped
product reads them); ``embed_weight``, ``final_ln_*``, ``lm_head_*``.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, fields
from typing import Any, Callable, Dict, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..base import MXNetError

__all__ = ["ModelSpec", "decoder_forward", "lm_config_from_params",
           "SOFTMAX", "SLIDING", "POWER_RETENTION", "LATENT", "DENSE",
           "ROUTED"]

SOFTMAX = "softmax"
SLIDING = "sliding"
POWER_RETENTION = "power_retention"
LATENT = "latent"
_ATTENTION_KINDS = (SOFTMAX, SLIDING, POWER_RETENTION, LATENT)
DENSE = "dense"
ROUTED = "routed"

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
    position: str = "none"              # "none" | "rope" | "rope_sliding"
    rope_theta: float = 10000.0
    attention: Union[str, Tuple[str, ...]] = SOFTMAX
    retention_eps: float = 1e-6         # the retention normaliser's eps
    # -- latent attention (the fields above it that it reads: heads,
    # norm_eps, rope_theta; head_dim is not read) --
    q_lora_rank: Optional[int] = None   # None: one full q projection
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # a config.json's ``rope_scaling`` (type "yarn"), frozen to sorted pairs
    rope_scaling: Optional[Tuple[Tuple[str, Any], ...]] = None
    # -- the FFN of each layer, and the expert layer --
    ffn_layers: Optional[Tuple[str, ...]] = None   # None: every layer dense
    n_routed_experts: int = 0
    experts_per_token: int = 0
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = False
    experts_held: Optional[Tuple[int, int]] = None  # (first, count); None: all
    score_func: str = "softmax"         # router scores: softmax | sigmoid
    router_bias: bool = False           # a selection bias: chooses only
    # -- window layers, and what a softmax block adds around attention --
    sliding_window: int = 0             # positions a "sliding" query sees
    attn_gate: bool = False             # o = W_o(sigmoid(W_g u) * attention)
    sandwich_norm: bool = False         # norm attention's, FFN's outputs
    embed_scale: float = 1.0            # h_0 = embed_scale * E[token]

    def __post_init__(self):
        if self.norm not in ("layernorm", "rmsnorm"):
            raise MXNetError(f"ModelSpec.norm {self.norm!r}: expected "
                             "'layernorm' or 'rmsnorm'")
        if self.ffn not in ("relu", "silu_gated"):
            raise MXNetError(f"ModelSpec.ffn {self.ffn!r}: expected 'relu' "
                             "or 'silu_gated'")
        if self.position not in ("none", "rope", "rope_sliding"):
            raise MXNetError(f"ModelSpec.position {self.position!r}: "
                             "expected 'none', 'rope' or 'rope_sliding'")
        if self.score_func not in ("softmax", "sigmoid"):
            raise MXNetError(f"ModelSpec.score_func {self.score_func!r}: "
                             "expected 'softmax' or 'sigmoid'")
        kinds = ((self.attention,) if isinstance(self.attention, str)
                 else tuple(self.attention))
        for k in kinds:
            if k not in _ATTENTION_KINDS:
                raise MXNetError(f"ModelSpec.attention kind {k!r}: expected "
                                 f"one of {_ATTENTION_KINDS}")
        if not isinstance(self.attention, str):
            object.__setattr__(self, "attention", kinds)
        if SLIDING in kinds and self.sliding_window < 1:
            raise MXNetError("a sliding layer needs sliding_window >= 1, "
                             f"got {self.sliding_window}")
        if LATENT in kinds:
            sizes = (self.kv_lora_rank, self.qk_nope_head_dim,
                     self.qk_rope_head_dim, self.v_head_dim)
            if min(sizes) < 1 or self.qk_rope_head_dim % 2:
                raise MXNetError(
                    "latent attention needs kv_lora_rank, qk_nope_head_dim, "
                    f"an even qk_rope_head_dim and v_head_dim, got {sizes}")
            if self.norm != "rmsnorm" or self.bias:
                raise MXNetError("latent attention is described with "
                                 "norm='rmsnorm' and bias=False")
        if isinstance(self.rope_scaling, dict):
            object.__setattr__(self, "rope_scaling",
                               tuple(sorted(self.rope_scaling.items())))
        if self.rope_scaling is not None and self.yarn.get("type") != "yarn":
            raise MXNetError("ModelSpec.rope_scaling: the one type known is "
                             f"'yarn', got {self.yarn.get('type')!r}")
        if self.ffn_layers is not None:
            object.__setattr__(self, "ffn_layers", tuple(self.ffn_layers))
            for k in self.ffn_layers:
                if k not in (DENSE, ROUTED):
                    raise MXNetError(f"ModelSpec.ffn_layers kind {k!r}: "
                                     f"expected {DENSE!r} or {ROUTED!r}")
        if self.experts_held is not None:
            object.__setattr__(self, "experts_held",
                               tuple(int(x) for x in self.experts_held))
        if self.ffn_layers and ROUTED in self.ffn_layers:
            n, g = self.n_routed_experts, self.n_group
            if (n < 1 or not 1 <= self.experts_per_token <= n or g < 1
                    or n % g or not 1 <= self.topk_group <= g):
                raise MXNetError(
                    f"a routed layer needs n_routed_experts ({n}) a "
                    f"multiple of n_group ({g}), 1 <= topk_group "
                    f"({self.topk_group}) <= n_group and 1 <= "
                    f"experts_per_token ({self.experts_per_token}) <= "
                    "n_routed_experts")
            if self.ffn != "silu_gated" or self.bias:
                raise MXNetError("experts are gated SiLU FFNs without "
                                 "biases: ffn='silu_gated', bias=False")
            first, count = self.held
            if first < 0 or count < 1 or first + count > n:
                raise MXNetError(f"experts_held {self.experts_held}: not "
                                 f"a range of the {n} routed experts")
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
            for name in ("attention", "ffn_layers", "experts_held"):
                if isinstance(model.get(name), list):
                    model[name] = tuple(model[name])
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

    @property
    def yarn(self) -> Dict[str, Any]:
        return dict(self.rope_scaling or ())

    @property
    def held(self) -> Tuple[int, int]:
        """(first, count) of the routed experts this chip holds."""
        if self.experts_held is None:
            return 0, self.n_routed_experts
        return self.experts_held

    @property
    def latent_width(self) -> int:
        """Values a latent layer caches a position: ``[c | k_r]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def latent_scale(self) -> np.float32:
        """The latent scores' scale: ``(nope + rope)^-0.5``, times
        ``mscale^2`` under YaRN with ``mscale_all_dim`` (the published
        attention corrects its temperature for the stretched rotary
        range there)."""
        scale = (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        y = self.yarn
        if y.get("mscale_all_dim"):
            m = yarn_mscale(float(y["factor"]), float(y["mscale_all_dim"]))
            scale *= m * m
        return np.float32(scale)

    def ffn_kinds(self, num_layers: int) -> Tuple[str, ...]:
        if self.ffn_layers is None:
            return (DENSE,) * num_layers
        if len(self.ffn_layers) != num_layers:
            raise MXNetError(
                f"ModelSpec.ffn_layers names {len(self.ffn_layers)} layers, "
                f"the parameters hold {num_layers}")
        return self.ffn_layers

    def layer_kinds(self, num_layers: int) -> Tuple[str, ...]:
        if isinstance(self.attention, str):
            return (self.attention,) * num_layers
        if len(self.attention) != num_layers:
            raise MXNetError(
                f"ModelSpec.attention names {len(self.attention)} layers, "
                f"the parameters hold {num_layers}")
        return self.attention

    def rotates(self, kind: str) -> bool:
        """Whether a softmax layer of ``kind`` takes rotary positions."""
        return self.position == "rope" or (
            self.position == "rope_sliding" and kind == SLIDING)

    def signature(self) -> str:
        """A short stable string for program-cache fingerprints; empty
        for the in-tree LM, whose keys predate the description."""
        default = ModelSpec(heads=self.heads)
        if self == default:
            return ""
        # the fields a description had when the first keys were made are
        # always spelt out; a later field only where it is not its
        # default, so those keys stay what they were
        return ":" + ",".join(
            f"{f.name}={getattr(self, f.name)}" for f in fields(self)
            if f.name != "heads" and (
                f.name in _FIRST_FIELDS
                or getattr(self, f.name) != getattr(default, f.name)))


_FIRST_FIELDS = ("kv_heads", "head_dim", "norm", "norm_eps", "qk_norm", "bias",
                 "ffn", "position", "rope_theta", "attention",
                 "retention_eps")


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
    # a latent layer with low-rank queries has q_a/q_b in q's place
    while (f"layer{n}_q_weight" in params
           or f"layer{n}_q_a_weight" in params):
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


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, scaling: Dict[str, Any]):
    """YaRN's rotary frequencies for ``dim`` channels (float32
    ``[dim / 2]``): channel pairs that turn more than ``beta_fast``
    times over the original context keep their frequency, those that
    turn fewer than ``beta_slow`` times have it divided by ``factor``,
    a linear ramp between; and the factor on cos and sin."""
    half = dim // 2
    base, orig = float(theta), float(scaling["original_max_position_embeddings"])

    def turn(beta):     # the pair index that turns ``beta`` times
        return dim * math.log(orig / (beta * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(turn(float(scaling.get("beta_fast", 32)))), 0)
    high = min(math.ceil(turn(float(scaling.get("beta_slow", 1)))), dim - 1)
    i = np.arange(half, dtype=np.float64)
    keep = 1.0 - np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
    extra = base ** (-2.0 * i / dim)
    factor = float(scaling["factor"])
    inv = (1.0 - keep) * extra / factor + keep * extra
    mscale = (yarn_mscale(factor, float(scaling.get("mscale", 1)))
              / yarn_mscale(factor, float(scaling.get("mscale_all_dim", 0))))
    return inv.astype(np.float32), np.float32(mscale)


def rope(x, positions, theta: float, scaling: Optional[Dict[str, Any]] = None):
    """Rotary positions on ``x`` [..., heads, hd] at integer
    ``positions`` [...] (the leading shape of ``x``): the half-split
    ("rotate half") convention of the Qwen/Llama family, float32
    inside.  ``scaling`` (a ``rope_scaling`` of type yarn) changes the
    frequencies and may scale cos and sin."""
    hd = x.shape[-1]
    half = hd // 2
    if scaling:
        inv, mscale = yarn_inv_freq(hd, theta, scaling)
    else:
        inv = np.float32(theta) ** (np.arange(half, dtype=np.float32)
                                    * np.float32(-2.0 / hd))
        mscale = None
    ang = positions.astype(jnp.float32)[..., None, None] * inv   # [..,1,half]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if mscale is not None and mscale != 1.0:
        cos, sin = cos * mscale, sin * mscale
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., :half], x32[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


def embed(params, tokens):
    with jax.named_scope("embed"):
        return jnp.take(_param(params, "embed_weight"),
                        tokens.astype(jnp.int32), axis=0)


def _latent_states(spec: ModelSpec, params, name, hn, positions):
    """What a latent layer makes of the normed hidden states ``hn``
    [..., d]: the queries [..., H, nope + rope] with their rotary part
    rotated, and the row it caches, ``[c | k_r]`` [..., kv_lora_rank +
    rope]: the normed latent and the ONE rotary key all heads share."""
    lead = hn.shape[:-1]
    dn, dr, rank = (spec.qk_nope_head_dim, spec.qk_rope_head_dim,
                    spec.kv_lora_rank)
    with jax.named_scope("mla_q"):
        if spec.q_lora_rank is None:
            q = _fcm(hn, _param(params, name("q_weight")))
        else:
            cq = _rmsm(_fcm(hn, _param(params, name("q_a_weight"))),
                       _param(params, name("q_a_norm_gamma")), spec.norm_eps)
            q = _fcm(cq, _param(params, name("q_b_weight")))
        q = q.reshape(lead + (spec.heads, dn + dr))
        q = jnp.concatenate(
            [q[..., :dn], rope(q[..., dn:], positions, spec.rope_theta,
                               spec.yarn)], axis=-1)
    with jax.named_scope("mla_kv"):
        ckr = _fcm(hn, _param(params, name("kv_a_weight")))
        c = _rmsm(ckr[..., :rank], _param(params, name("kv_a_norm_gamma")),
                  spec.norm_eps)
        k_r = rope(ckr[..., None, rank:], positions, spec.rope_theta,
                   spec.yarn)[..., 0, :]
        return q, jnp.concatenate([c, k_r], axis=-1)


def block(spec: ModelSpec, params, i: int, kind: str, h, positions,
          attend: Callable, ffn_kind: str = DENSE,
          routed: Optional[Callable] = None):
    """One decoder block on hidden states ``h`` ([..., d]).

    ``attend(q, k, v, gate)`` receives the per-head states (q
    [..., H, hd]; k, v [..., KV, hd]; ``gate`` the log forget gate
    [..., KV] in float32 for a retention layer, else None), owns the
    cache, and returns the attention output [..., H, hd].  A latent
    layer hands it ``(q, row, None, None)``: the queries [..., H, nope +
    rope] and the row it caches ([..., kv_lora_rank + rope]), and gets
    [..., H, v_head_dim] back.  ``routed(x)`` is the expert layer of a
    ``"routed"`` block on the normed states (shared experts included).
    The parts are ``jax.named_scope``s, so a device trace names them."""
    heads, kv, hd = spec.dims(h.shape[-1])
    lead = h.shape[:-1]

    def name(suffix):
        return f"layer{i}_{suffix}"

    if kind == LATENT:
        with jax.named_scope("mla_q"):
            hn = _norm(spec, params, name("ln1"), h)
        q, row = _latent_states(spec, params, name, hn, positions)
        att = attend(q, row, None, None).reshape(
            lead + (heads * spec.v_head_dim,))
    else:
        with jax.named_scope("qkv"):
            hn = _norm(spec, params, name("ln1"), h)
            q, k, v = (_linear(spec, params, name(nm), hn)
                       for nm in ("q", "k", "v"))
            gate = None
            if kind == POWER_RETENTION:
                logit = (_fcm(hn, _param(params, name("gate_weight")))
                         .astype(jnp.float32)
                         + _param(params, name("gate_bias")).astype(
                             jnp.float32))
                gate = jax.nn.log_sigmoid(logit)
        q = q.reshape(lead + (heads, hd))
        k = k.reshape(lead + (kv, hd))
        v = v.reshape(lead + (kv, hd))
        if spec.qk_norm:
            with jax.named_scope("qkv"):
                q = _rmsm(q, _param(params, name("q_norm_gamma")),
                          spec.norm_eps)
                k = _rmsm(k, _param(params, name("k_norm_gamma")),
                          spec.norm_eps)
        if spec.rotates(kind):
            with jax.named_scope("rope"):
                q = rope(q, positions, spec.rope_theta)
                k = rope(k, positions, spec.rope_theta)
        att = attend(q, k, v, gate).reshape(lead + (heads * hd,))
        if spec.attn_gate:
            with jax.named_scope("attn_gate"):
                g = jax.nn.sigmoid(_fcm(hn, _param(params, name(
                    "attn_gate_weight"))).astype(jnp.float32))
                att = (att.astype(jnp.float32) * g).astype(att.dtype)
    with jax.named_scope("proj"):
        h = _residual(spec, params, name("post_attn_norm"), h,
                      _linear(spec, params, name("proj"), att))
    if ffn_kind == ROUTED:
        with jax.named_scope("router"):
            hn = _norm(spec, params, name("ln2"), h)
        return _residual(spec, params, name("post_ffn_norm"), h,
                         routed(hn).astype(h.dtype))
    with jax.named_scope("ffn"):
        hn = _norm(spec, params, name("ln2"), h)
        if spec.ffn == "relu":
            f = _linear(spec, params, name("ffn1"), hn)
            f = jnp.maximum(f, 0)
            return h + _linear(spec, params, name("ffn2"), f)
        f = (jax.nn.silu(_linear(spec, params, name("ffn_gate"), hn))
             * _linear(spec, params, name("ffn_up"), hn))
        return _residual(spec, params, name("post_ffn_norm"), h,
                         _linear(spec, params, name("ffn_down"), f))


def _residual(spec: ModelSpec, params, norm: str, h, out):
    """``h + out``, the output normed first under ``sandwich_norm``."""
    if spec.sandwich_norm:
        out = _rmsm(out, _param(params, norm + "_gamma"), spec.norm_eps)
    return h + out


def lm_head(spec: ModelSpec, params, h):
    with jax.named_scope("lm_head"):
        h = _norm(spec, params, "final_ln", h)
        return _linear(spec, params, "lm_head", h)


def decoder_forward(spec: ModelSpec, params: Dict[str, Any], tokens,
                    positions, attend: Callable, *,
                    routed: Optional[Callable] = None,
                    select: Optional[Callable] = None):
    """Logits [..., V] for ``tokens`` [...] (one position per decode row
    ``[B]``, or a chunk / verify window ``[B, C]``) over a caller-owned
    cache.  ``positions`` has the tokens' shape (absolute positions; read
    only where ``spec.position`` is ``"rope"`` or a layer is latent --
    may be None otherwise).
    ``attend(layer, kind, q, k, v, gate)`` extends the caller's cache
    with the new states and returns each position's attention over the
    cached prefix, itself included (see :func:`block`).
    ``routed(layer, x)`` runs a routed layer's experts on the normed
    states (None: the plain form of ``models/experts.py``, every held
    expert over every token).  ``select(h)`` picks the hidden states the
    head is computed for (None: all)."""
    _, num_layers, _ = lm_config_from_params(params)
    kinds = spec.layer_kinds(num_layers)
    ffns = spec.ffn_kinds(num_layers)
    if routed is None and ROUTED in ffns:
        from .experts import routed_ffn

        def routed(i, x):
            return routed_ffn(spec, params, i, x)[0]
    h = embed(params, tokens)
    if spec.embed_scale != 1.0:
        h = (h.astype(jnp.float32) * np.float32(spec.embed_scale)).astype(
            h.dtype)
    for i, (kind, ffn_kind) in enumerate(zip(kinds, ffns)):
        h = block(spec, params, i, kind, h, positions,
                  lambda q, k, v, g, i=i, kind=kind: attend(i, kind, q, k,
                                                           v, g),
                  ffn_kind, lambda x, i=i: routed(i, x))
    if select is not None:
        h = select(h)
    return lm_head(spec, params, h)
