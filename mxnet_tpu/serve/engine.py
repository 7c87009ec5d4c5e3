"""Serving engine: continuous batching over a paged KV-cache.

The front door of the serving tier (docs/serving.md): ``submit`` /
``stream`` / ``cancel`` plus a ``step()`` loop that, every iteration,

1. evicts finished/cancelled requests (their KV blocks return to the
   pool immediately),
2. admits queued requests into free decode slots
   (:class:`~mxnet_tpu.serve.scheduler.Scheduler` policy: FIFO with an
   SLO-aware jump),
3. **prefills** each admitted prompt through a bucket-laddered AOT
   program (one program per padded prompt length), and
4. runs ONE **decode** step for the whole running batch through a
   slot-bucketed AOT program.

Both program families compile through
:mod:`~mxnet_tpu.compile_cache` (:func:`Engine.warmup` resolves every
bucket up front — memory/disk hits on a warm restart, zero traces in
steady state, pinned by ``tests/test_serve.py``).  Model math is the
functional twin of the training graph
(:func:`~mxnet_tpu.models.transformer.transformer_lm_prefill` /
``transformer_lm_decode``) reading/writing the paged pools of
:mod:`~mxnet_tpu.serve.kvcache`, so a checkpoint trained on the symbol
serves unmodified — load it with :func:`Engine.from_checkpoint`
(CheckpointManager directory or legacy ``prefix``/``.params``, the one
weight-loading story shared with :mod:`mxnet_tpu.predictor`).

Determinism: decode slots are bucketed to ``decode_buckets`` (default:
a single bucket at ``max_batch``, so every step runs the same program
shape — XLA:CPU gemm schedules differ per row count, docs/perf.md r7)
and rows are independent, so a request decodes token-for-token
identically whether it runs alone or inside a full continuously-batched
engine.  Sampling keys are derived per (request, position), so even
temperature>0 streams replay identically across admission orders and
preemptions.
"""
from __future__ import annotations

import collections
import os
import time
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import chaos as chaos_mod
from .. import compile_cache as cc
from .. import telemetry
from ..base import MXNetError
from ..models.decoder import (ROUTED, SLIDING, ModelSpec,
                              decoder_forward)
from ..models.retention import chunk_form
from ..models.transformer import (lm_config_from_params,
                                  transformer_lm_decode,
                                  transformer_lm_prefill,
                                  transformer_lm_verify)
from . import kvcache
from . import retention_decode as retention_mod
from . import speculate as speculate_mod
from .scheduler import (CANCELLED, FAILED, FINISHED, Request, Scheduler,
                        ServeError)

__all__ = ["EngineConfig", "Engine", "ServeError"]

_NEG = -1e30


def _env_int(name: str, default: int) -> int:
    raw = os.environ.get(name, "").strip()
    return int(raw) if raw else default


def _env_float(name: str, default: Optional[float]) -> Optional[float]:
    raw = os.environ.get(name, "").strip()
    return float(raw) if raw else default


@dataclass(frozen=True)
class EngineConfig:
    """Static engine geometry.  Every field is baked into program
    shapes or pool sizes — changing one means new programs (the
    compile-cache key includes them all via the avals/fingerprint).

    ``heads`` must come from the caller (or checkpoint meta): it is the
    one transformer_lm hyperparameter not recoverable from parameter
    shapes.  ``model`` describes any other architecture
    (:class:`~mxnet_tpu.models.decoder.ModelSpec`, or a dict of its
    fields as a configuration file holds it); None is the in-tree
    ``transformer-lm`` with ``heads`` heads.

    For a model whose layers all keep a recurrent state (no paged K/V:
    ``kvcache.CacheSpec``), ``num_blocks`` counts the state slots (slot
    0 the trash slot, so ``num_blocks - 1`` requests can be live: give
    it ``max_batch + 1``), ``block_size`` is not read (a slot holds any
    number of tokens; ``Engine.max_blocks`` is 1) and ``dtype`` is the
    state's type: float32, the one type the state is kept in (any other
    is refused).  ``Engine.num_layers`` /
    ``heads`` / ``head_dim`` are the model's; ``Request.cached`` counts
    the tokens a request's state has absorbed.

    For a model whose layers are latent (``kvcache`` kind
    ``paged_latent``) every field reads as for paged K/V: blocks of
    ``block_size`` rows of ONE pool, ``dtype`` the rows' type; a larger
    ``block_size`` (128) suits it, since a row is shared by all heads
    and the decode kernel copies a block at a time.  It ingests prompts
    through the chunk program (``prefill_chunk > 0``) and refuses
    ``prefix_cache``, ``speculate`` and ``kv_quant`` by name.

    A described model of softmax layers (any ``model`` but the in-tree
    LM's; ``kvcache`` kinds ``paged_kv`` and ``paged_window``) keeps its
    global layers' K/V in ``num_blocks`` blocks of ``block_size`` rows
    under a table a request, and its window layers' in a ring of
    ``kvcache.ring_width(sliding_window, prefill_chunk, block_size)``
    blocks a request, from a window pool of ``max_batch`` rings + the
    trash block (so a window block never runs short), both from ONE
    ``kvcache.WindowAllocator``.  It too ingests prompts through the
    chunk program and refuses the three options by name.
    """
    heads: int = 4
    model: Any = None             # ModelSpec | dict of its fields | None
    block_size: int = 16          # kv entries per pool block
    num_blocks: int = 128         # physical pool blocks (slot 0 = trash)
    max_batch: int = 8            # decode slots
    max_queue: int = 64           # bounded wait queue
    max_prompt_len: int = 128     # top rung of the prefill ladder
    max_seq_len: int = 256        # prompt + generated, per request
    decode_buckets: Optional[Tuple[int, ...]] = None  # None -> (max_batch,)
    prompt_bucket_min: int = 16
    prompt_bucket_factor: float = 2.0
    slo_ms: Optional[float] = None       # default per-request SLO
    slo_admit_frac: float = 0.5
    deadline_ms: Optional[float] = None  # default per-request hard wall
    seed: int = 0
    dtype: Any = jnp.float32
    # -- round-12 tail-latency knobs (docs/serving.md) --
    prefill_chunk: int = 0        # >0: chunked prefill, chunk budget;
                                  # 0: whole-prompt bucket ladder
    kv_quant: Optional[str] = None   # None (f32) | "fp8" (e4m3+scales)
    attn_impl: str = "auto"       # auto | scan | dense | flash
                                  # | flash_interpret
    # -- round-15 speculative decoding (docs/serving.md) --
    speculate: bool = False       # draft-then-verify multi-token steps
    spec_k: int = 4               # drafted tokens per verify window
    spec_draft: str = "ngram"     # "ngram" (prompt lookup) | "model"
    spec_window: int = 16         # model drafter's context window
    # -- round-18 cross-request prefix cache (docs/serving.md) --
    prefix_cache: bool = False    # content-hashed KV block reuse
    prefix_cap_frac: float = 0.5  # max fraction of the pool parked as
                                  # refcount-0 cached prefix blocks
    prefix_min_blocks: int = 1    # shortest prefix hit worth mapping

    @classmethod
    def from_env(cls, **overrides) -> "EngineConfig":
        """Environment defaults (docs/env_vars.md rounds 11-12, 17-18);
        explicit kwargs win."""
        env = dict(
            block_size=_env_int("MXNET_TPU_SERVE_BLOCK_SIZE", 16),
            num_blocks=_env_int("MXNET_TPU_SERVE_BLOCKS", 128),
            max_batch=_env_int("MXNET_TPU_SERVE_MAX_BATCH", 8),
            max_queue=_env_int("MXNET_TPU_SERVE_MAX_QUEUE", 64),
            max_seq_len=_env_int("MXNET_TPU_SERVE_MAX_SEQ", 256),
            slo_ms=_env_float("MXNET_TPU_SERVE_SLO_MS", None),
            deadline_ms=_env_float("MXNET_TPU_SERVE_DEADLINE_MS", None),
            prefill_chunk=_env_int("MXNET_TPU_SERVE_PREFILL_CHUNK", 0),
            kv_quant=(os.environ.get("MXNET_TPU_SERVE_KV_QUANT", "")
                      .strip().lower() or None),
            attn_impl=(os.environ.get("MXNET_TPU_SERVE_ATTN", "")
                       .strip().lower() or "auto"),
            speculate=bool(_env_int("MXNET_TPU_SERVE_SPECULATE", 0)),
            spec_k=_env_int("MXNET_TPU_SERVE_SPEC_K", 4),
            spec_draft=(os.environ.get("MXNET_TPU_SERVE_SPEC_DRAFT", "")
                        .strip().lower() or "ngram"),
            prefix_cache=bool(_env_int("MXNET_TPU_SERVE_PREFIX_CACHE", 0)),
            prefix_cap_frac=_env_float(
                "MXNET_TPU_SERVE_PREFIX_CAP_FRAC", 0.5),
            prefix_min_blocks=_env_int(
                "MXNET_TPU_SERVE_PREFIX_MIN_BLOCKS", 1),
        )
        env.update(overrides)
        return cls(**env)

    def resolved_decode_buckets(self) -> Tuple[int, ...]:
        if self.decode_buckets:
            bs = tuple(sorted(set(int(b) for b in self.decode_buckets)))
            if bs[-1] < self.max_batch:
                raise MXNetError(
                    f"decode_buckets {bs} cannot cover max_batch "
                    f"{self.max_batch}")
            return bs
        return (self.max_batch,)

    def resolved_attn_impl(self) -> str:
        """Decode attention strategy.  ``"auto"`` picks the Pallas
        flash-decode kernel on TPU and the one-shot gather ("dense")
        elsewhere — on thunk-dispatch-bound backends (XLA:CPU) the
        reference block scan's ~10 ops per block column, not HBM
        bandwidth, dominates the decode step."""
        impl = self.attn_impl
        if impl == "auto":
            # "flash" is the Pallas kernel of the model's cache kind:
            # flash-decode over paged K/V, retention-decode over states
            return "flash" if jax.default_backend() == "tpu" else "dense"
        if impl not in ("scan", "dense", "flash", "flash_interpret"):
            raise MXNetError(
                f"attn_impl {impl!r}: expected 'auto', 'scan', 'dense', "
                "'flash', or 'flash_interpret'")
        return impl


def _kth_largest(x, k):
    """The ``k``-th largest value along the last axis of float32 ``x``
    (``k`` clipped to 1..V; one for each leading index, or one for all
    of them) by selection: the float, bit for bit, that
    ``flip(sort(x))[k - 1]`` holds, without ordering the rest.

    A float's bits, with the sign bit set on a non-negative value and
    all of them inverted on a negative one, order as unsigned integers
    the way the values do.  The answer's image is the largest ``t`` with
    ``count(image >= t) >= k``; the count only falls as ``t`` grows, so
    ``t`` is built a bit at a time from the top: 32 passes of a compare
    and a row sum, whatever ``k`` is.  (A sort calls -0.0 and 0.0 equal
    and this orders them; ``x < kth`` reads the same either way.)
    """
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    top = jnp.uint32(1 << 31)
    image = jnp.where(bits >= top, ~bits, bits | top)
    k = jnp.broadcast_to(jnp.clip(k, 1, x.shape[-1]), x.shape[:-1])

    def keep_bit(i, t):
        cand = t | (top >> i.astype(jnp.uint32))
        count = jnp.sum(image >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(count >= k, cand, t)

    t = jax.lax.fori_loop(0, 32, keep_bit, jnp.zeros(k.shape, jnp.uint32))
    return jax.lax.bitcast_convert_type(
        jnp.where(t >= top, t ^ top, ~t), jnp.float32)


def _greedy(logits):
    """The token of a ``temp == 0`` row: both branches of
    :func:`_sample` take it by this ``argmax`` of the float32 logits."""
    return jnp.argmax(logits.astype(jnp.float32), axis=-1).astype(jnp.int32)


def _topk_masked(scaled, topk):
    """``scaled`` with everything under its ``topk``-th largest value
    masked out (ties at that value stay); ``topk <= 0`` keeps all."""
    kth = _kth_largest(scaled, topk)[..., None]
    return jnp.where((topk > 0)[..., None] & (scaled < kth), _NEG, scaled)


def _sample_row(logits, key, temp, topk, pos):
    """Greedy / temperature / top-k sampling for one row.

    ``pos`` keys the PRNG: the sample for (request, position) is a pure
    function of the request key and the logits — independent of batch
    composition, admission order, or preemption restarts.
    """
    scaled = logits.astype(jnp.float32) / jnp.maximum(temp, 1e-6)
    sampled = jax.random.categorical(
        jax.random.fold_in(key, pos),
        _topk_masked(scaled, topk)).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, _greedy(logits))


_sample_batch = jax.vmap(_sample_row, in_axes=(0, 0, 0, 0, 0))


def _sample(logits, keys, temps, topks, pos):
    """Sample one row (``logits`` [V], scalars beside it) or a batch
    ([B, V]).  When no row has ``temp > 0`` — the common serving case,
    padding rows included — the program takes the ``argmax`` and nothing
    else: ``lax.cond`` executes only the taken branch, so no division,
    no selection, no threefry.  A greedy row's token is ``_greedy`` of
    the same logits in both branches, so the branch taken can never
    change a stream; :func:`_sampler_branch` names it on the host."""
    rows = _sample_row if logits.ndim == 1 else _sample_batch
    return jax.lax.cond(
        jnp.any(temps > 0.0),
        lambda: rows(logits, keys, temps, topks, pos),
        lambda: _greedy(logits))


def _sampler_branch(temps) -> str:
    """Which branch of :func:`_sample` (or of :func:`_spec_accept`) a
    step's program takes, by the program's own predicate on the host's
    copy of ``temps``: the ``serve.decode`` span's ``sampler`` arg."""
    return "select" if np.any(temps > 0.0) else "greedy"


# PRNG salts: acceptance-u and residual draws fold one extra constant
# into the per-position key chain (``fold_in(key, pos)``), so they are
# independent streams from the plain token draw at the same position —
# and the plain draw itself stays untouched, which is what makes a
# live=0 speculative row byte-identical to non-speculative decode.
_SALT_ACCEPT = 0x5ACC
_SALT_RESID = 0x5E51


def _spec_accept_row(logits, toks, live, key, temp, topk, length):
    """Replay-exact acceptance for one request's verify window.

    ``logits``: [C, V] target scores (row c scores the token after
    window position c); ``toks``: [C] — ``toks[0]`` the current last
    token, ``toks[1:]`` the K drafted tokens; ``live``: how many drafts
    are in play for this row (0..K — budget/shape clamps); ``length``:
    cache entries before this step, so the token sampled from
    ``logits[c]`` sits at absolute position ``length + 1 + c`` (the
    same position-keying as plain decode).

    Greedy (temp == 0): draft c is accepted iff it equals
    ``argmax(logits[c-1])`` — the emitted stream is the non-speculative
    argmax stream token for token.  Temperature: draft x at position p
    is accepted iff ``u < p(x)`` with ``p`` the temp/top-k sampling
    distribution and ``u`` uniform from the salted position key; a
    rejected draft resamples the residual — ``p`` with x's point mass
    removed and renormalized (its logit masked to -inf) — which makes
    the emitted marginal exactly ``p`` for ANY deterministic drafter:
    ``p(x)·δx + (1-p(x))·(p-p(x)δx)/(1-p(x)) = p``.  When every live
    draft is accepted the bonus token is drawn by the plain sampler
    (:func:`_sample_row`) at its position, so a live=0 row degrades to
    plain decode bit-for-bit, temperature included.

    Returns ``(out [C] int32, n_emit int32)``: ``out[:n_emit]`` are the
    emitted tokens (accepted drafts + the correction/bonus token).
    """
    logits = logits.astype(jnp.float32)
    c = logits.shape[0]
    k = c - 1
    draft = toks[1:]
    greedy = _greedy(logits)
    scaled = logits / jnp.maximum(temp, 1e-6)
    masked = _topk_masked(scaled, topk)
    probs = jax.nn.softmax(masked, axis=-1)
    pos = length + 1 + jnp.arange(c)

    def accept_u(p):
        # float32 by name: the default is float64 under the package's
        # x64, and `us < p_draft` would then compare in float64
        return jax.random.uniform(jax.random.fold_in(
            jax.random.fold_in(key, p), _SALT_ACCEPT), dtype=jnp.float32)

    us = jax.vmap(accept_u)(pos[:k])
    p_draft = jnp.take_along_axis(probs[:k], draft[:, None], axis=1)[:, 0]
    acc = jnp.where(temp > 0, us < p_draft, greedy[:k] == draft)
    acc = acc & (jnp.arange(k) < live)
    a = jnp.sum(jnp.cumprod(acc.astype(jnp.int32)))   # leading accepts
    la = jnp.take(logits, a, axis=0)
    # all live drafts accepted -> bonus token, the PLAIN sampler at its
    # position (exactly the non-speculative draw)
    bonus = _sample_row(la, key, temp, topk, length + 1 + a)
    # rejection -> greedy corrects with argmax; temperature draws the
    # residual (draft's point mass masked out) from a salted stream
    d_rej = jnp.take(draft, jnp.minimum(a, k - 1))
    resid_logits = jnp.take(masked, a, axis=0).at[d_rej].set(_NEG)
    rkey = jax.random.fold_in(jax.random.fold_in(key, length + 1 + a),
                              _SALT_RESID)
    resid = jax.random.categorical(rkey, resid_logits).astype(jnp.int32)
    corr = jnp.where(temp > 0, resid, jnp.take(greedy, a))
    final = jnp.where(a >= live, bonus, corr)
    idx = jnp.arange(c)
    draft_pad = jnp.concatenate([draft, jnp.zeros((1,), draft.dtype)])
    out = jnp.where(idx == a, final, jnp.where(idx < a, draft_pad, 0))
    return out.astype(jnp.int32), (a + 1).astype(jnp.int32)


_spec_accept_batch = jax.vmap(_spec_accept_row,
                              in_axes=(0, 0, 0, 0, 0, 0, 0))


def _spec_accept_row_greedy(logits, toks, live):
    """Greedy-only acceptance: for temp == 0 the full rule collapses
    to pure argmax (accept iff draft == argmax; both the correction
    and the bonus token ARE ``argmax(logits[a])``), so an all-greedy
    batch needs no selection, no softmax, no PRNG.  Produces exactly the
    integers :func:`_spec_accept_row` produces at temp == 0 — the
    verify program picks this branch under ``lax.cond``, so greedy
    byte-identity is preserved by construction."""
    logits = logits.astype(jnp.float32)
    c = logits.shape[0]
    k = c - 1
    draft = toks[1:]
    greedy = _greedy(logits)
    acc = (greedy[:k] == draft) & (jnp.arange(k) < live)
    a = jnp.sum(jnp.cumprod(acc.astype(jnp.int32)))
    idx = jnp.arange(c)
    draft_pad = jnp.concatenate([draft, jnp.zeros((1,), draft.dtype)])
    out = jnp.where(idx == a, jnp.take(greedy, a),
                    jnp.where(idx < a, draft_pad, 0))
    return out.astype(jnp.int32), (a + 1).astype(jnp.int32)


_spec_accept_batch_greedy = jax.vmap(_spec_accept_row_greedy,
                                     in_axes=(0, 0, 0))


def _spec_accept(logits, tokens, live, keys, temps, topks, lengths):
    """Batch acceptance with an all-greedy fast path.  ``lax.cond``
    executes only the taken branch, so a greedy batch (the common
    serving case, and the accept-friendly bench row) skips the top-k
    selection, softmax, and threefry chains entirely; any temperature row
    in the batch routes the whole batch through the full rule.  Both
    branches emit identical integers for temp == 0 rows, so the
    branch choice can never change a stream."""
    return jax.lax.cond(
        jnp.any(temps > 0.0),
        lambda: _spec_accept_batch(logits, tokens, live, keys, temps,
                                   topks, lengths),
        lambda: _spec_accept_batch_greedy(logits, tokens, live))


def _moe_counts(stats):
    """``[experts_hit, assigned_here]`` summed over a program's routed
    layers (zeros where the model has none)."""
    if not stats:
        return jnp.zeros((2,), jnp.int32)
    return jnp.stack([sum(h for h, _ in stats),
                      sum(n for _, n in stats)]).astype(jnp.int32)


class Engine:
    """Continuous-batching autoregressive server for ``transformer_lm``
    parameter dicts.  See the module docstring for the step anatomy."""

    def __init__(self, params: Dict[str, Any], config: EngineConfig,
                 chaos: Optional[chaos_mod.ChaosSpec] = None,
                 draft_params: Optional[Dict[str, Any]] = None,
                 draft_heads: Optional[int] = None):
        self.config = config
        # chaos=None reads MXNET_TPU_CHAOS (serve_* kinds); pass an
        # empty ChaosSpec to force chaos off (the router does, for
        # replicas the spec does not target)
        if chaos is None:
            chaos = chaos_mod.serve_from_env()
        self.chaos = chaos if chaos else None
        self.beat = 0            # liveness: +1 per COMPLETED step
        self._hung = False       # chaos serve_hang: steps become no-ops
        self._poison_step = False
        self._poison_params = None
        self._params = {k: jnp.asarray(
            v.asnumpy() if hasattr(v, "asnumpy") else v)
            for k, v in params.items()}
        self.vocab, self.num_layers, self.d_model = (
            lm_config_from_params(self._params))
        # the architecture is TOLD (``config.model``): shapes give the
        # vocabulary, the depth and the width alone
        self.model = ModelSpec.resolve(config.model, config.heads)
        self.heads, self.kv_heads, self.head_dim = self.model.dims(
            self.d_model)
        self.cache = kvcache.CacheSpec.for_attention(
            self.model.layer_kinds(self.num_layers))
        self.recurrent = self.cache.recurrent
        self.latent = self.cache.kind == kvcache.PAGED_LATENT
        # a described softmax model: grouped heads, window and global
        # layers through the paged pools (the in-tree LM keeps its twins)
        self.described_kv = (not self.recurrent and not self.latent
                             and self.model != ModelSpec(heads=self.heads))
        self._routed_layers = self.model.ffn_kinds(self.num_layers).count(
            ROUTED)
        if self._routed_layers and "experts_held" in self._params:
            # weights that say which experts they are: the description's
            # share has to be the same one
            first, count = self.model.held
            told = np.asarray(self._params["experts_held"]).tolist()
            if told != list(range(first, first + count)):
                raise MXNetError(
                    f"the parameters hold experts {told[:3]}..{told[-1:]} "
                    f"({len(told)}), the description says experts_held="
                    f"{self.model.held}")
        # which options each cache kind refuses, and why (paged_kv, the
        # in-tree LM's, takes them all)
        refused = {
            kvcache.RECURRENT_STATE: (
                "needs paged K/V; this model's layers keep a recurrent "
                f"state ({self.model.attention}): a state has no "
                "per-token rows to share, roll back or quantize"),
            kvcache.PAGED_LATENT: (
                "is not served on a latent cache (kind paged_latent) yet: "
                "its rows are paged like K/V, but the prefix index has "
                "not been tried on them, no verify program reads them "
                "and an 8-bit latent row is another result (ROADMAP R4)"),
        }.get(self.cache.kind)
        if self.described_kv:
            refused = (
                "is not served on a described softmax model's tables "
                "(kinds paged_kv and paged_window) yet: a window block "
                "holds other positions as a request grows, so the prefix "
                "index cannot publish it, no verify program reads the "
                "ring, and an 8-bit row is another result")
        if refused is not None:
            for name, on in (("prefix_cache", config.prefix_cache),
                             ("speculate", config.speculate),
                             ("kv_quant", config.kv_quant)):
                if on:
                    raise ServeError("unsupported", -1,
                                     f"EngineConfig.{name} {refused}")
        if self.latent or self.described_kv:
            if not config.prefill_chunk:
                raise MXNetError(
                    "a described model on a paged cache ingests prompts "
                    "through the chunk program: set prefill_chunk > 0")
        if self.latent:
            self.head_dim = (self.model.qk_nope_head_dim
                             + self.model.qk_rope_head_dim)
        elif self.recurrent:
            if not config.prefill_chunk:
                raise MXNetError(
                    "a recurrent-state model ingests prompts through the "
                    "chunk program: set prefill_chunk > 0")
            if jnp.dtype(config.dtype) != jnp.float32:
                raise ServeError(
                    "unsupported", -1,
                    f"EngineConfig.dtype {jnp.dtype(config.dtype).name}: "
                    "the recurrent state is kept in float32 (every decode "
                    "step rounds it again, so a narrower state is another "
                    "result, not a faster one)")
        # a state slot holds any number of tokens: one "block" a request
        bs = config.max_seq_len if self.recurrent else config.block_size
        self.max_blocks = -(-config.max_seq_len // bs)
        self.attn_impl = config.resolved_attn_impl()
        self.kv_quant = config.kv_quant
        self.prefill_chunk = int(config.prefill_chunk or 0)
        if self.prefill_chunk < 0:
            raise MXNetError(f"prefill_chunk must be >= 0, "
                             f"got {self.prefill_chunk}")
        if self.described_kv:
            kinds = self.model.layer_kinds(self.num_layers)
            window = self.model.sliding_window if SLIDING in kinds else 0
            ring = (kvcache.ring_width(window, self.prefill_chunk, bs)
                    if window else 1)
            self.alloc = kvcache.WindowAllocator(
                config.num_blocks, 1 + config.max_batch * ring, bs, ring)
            # layer -> (index of its K pool among the caches, its layer
            # there): window layers in the first pair, global ones in
            # the second
            seen = {0: 0, 2: 0}
            self._kv_slot = {}
            for i, kind in enumerate(kinds):
                at = 0 if kind == SLIDING else 2
                self._kv_slot[i] = (at, seen[at])
                seen[at] += 1
        else:
            self.alloc = kvcache.BlockAllocator(config.num_blocks, bs)
            # a latent model's layer i is layer i of its one pool
            self._kv_slot = {i: (0, i) for i in range(self.num_layers)}
        # -- round-18 cross-request prefix cache --
        self.prefix: Optional[kvcache.PrefixIndex] = None
        self._prefix_hits = 0
        self._prefix_misses = 0
        self._prefix_hit_tokens = 0
        self._prefix_evictions = 0
        if config.prefix_cache:
            if not self.prefill_chunk:
                raise MXNetError(
                    "prefix_cache requires chunked prefill "
                    "(prefill_chunk > 0): cache hits skip whole chunks")
            if not (0.0 < config.prefix_cap_frac <= 1.0):
                raise MXNetError(
                    f"prefix_cap_frac must be in (0, 1], "
                    f"got {config.prefix_cap_frac}")
            if config.prefix_min_blocks < 1:
                raise MXNetError(
                    f"prefix_min_blocks must be >= 1, "
                    f"got {config.prefix_min_blocks}")
            self.prefix = kvcache.PrefixIndex(bs)
            # hits are floored to a multiple of lcm(block, chunk): the
            # warm run's remaining chunks then land on the SAME chunk
            # grid a cold prefill uses, so every suffix chunk is the
            # identical program invocation and the stream stays
            # byte-identical to a cache-cold run by construction
            self._hit_quantum = (bs * self.prefill_chunk
                                 // np.gcd(bs, self.prefill_chunk))
            self.alloc.cache_cap = max(
                1, int(config.prefix_cap_frac * (config.num_blocks - 1)))
            self.alloc.cache_filter = self.prefix.contains_block

            def _on_evict(block: int) -> None:
                self.prefix.drop_block(block)
                self._prefix_evictions += 1
                telemetry.counter("serve.prefix.evictions").inc()

            self.alloc.on_evict = _on_evict
        # the donated cache arrays, in the order the programs take them
        if self.recurrent:
            self._caches = (kvcache.make_state_pool(
                self.num_layers, config.num_blocks, self.kv_heads,
                self.head_dim),)
        elif self.latent:
            self._caches = kvcache.make_pools(
                self.num_layers, config.num_blocks, bs, self.heads,
                self.head_dim, dtype=config.dtype,
                latent_width=self.model.latent_width)
        elif self.described_kv:
            # (K, V) of the window layers, then (K, V) of the global ones
            self._caches = (
                kvcache.make_pools(seen[0], self.alloc.window.num_blocks, bs,
                                   self.kv_heads, self.head_dim,
                                   dtype=config.dtype)
                + kvcache.make_pools(seen[2], config.num_blocks, bs,
                                     self.kv_heads, self.head_dim,
                                     dtype=config.dtype))
        else:
            self._caches = kvcache.make_pools(
                self.num_layers, config.num_blocks, bs, self.heads,
                self.head_dim, dtype=config.dtype, quant=config.kv_quant)
        self.sched = Scheduler(config.max_batch, config.max_queue,
                               config.slo_ms, config.slo_admit_frac)
        if config.max_prompt_len > config.max_seq_len:
            raise MXNetError(
                f"max_prompt_len {config.max_prompt_len} exceeds "
                f"max_seq_len {config.max_seq_len}")
        if self.prefill_chunk:
            # chunked prefill: ONE chunk shape replaces the whole
            # geometric ladder — any prompt (or preemption re-prefill up
            # to max_seq_len) is ingested as ceil(len / chunk) runs of
            # the same program
            self.prompt_buckets = tuple(
                cc.BucketPolicy.fixed(self.prefill_chunk).buckets)
        else:
            policy = cc.BucketPolicy(min_bucket=config.prompt_bucket_min,
                                     factor=config.prompt_bucket_factor,
                                     round_to=config.prompt_bucket_min)
            # the ladder covers max_seq_len, not max_prompt_len: a
            # preempted request re-prefills with prompt +
            # already-generated tokens, which may exceed any fresh
            # prompt's length
            self.prompt_buckets = tuple(policy._ladder(config.max_seq_len))
        self.decode_buckets = config.resolved_decode_buckets()
        self._base_key = jax.random.PRNGKey(config.seed)
        self._programs: Dict[Tuple[str, int], cc.AotProgram] = {}
        self.trace_counts = collections.Counter()
        self.aot_stats = collections.Counter()
        self.requests: Dict[int, Request] = {}
        self.step_idx = 0
        self.swap_count = 0      # successful swap_weights installs
        self._chunk_ms = 0.0   # EWMA chunk-prefill latency (SLO backlog)
        # -- round-15 speculative decoding --
        self.spec: Optional[speculate_mod.Drafter] = None
        self.spec_k = int(config.spec_k)
        self._spec_drafted = 0   # lifetime drafted positions
        self._spec_accepted = 0  # lifetime accepted drafts
        self._decode_ms = 0.0    # EWMA decode/verify step latency
        self._tps = 1.0          # EWMA tokens emitted per row per step
        if config.speculate:
            if self.spec_k < 1:
                raise MXNetError(f"spec_k must be >= 1, got {self.spec_k}")
            if self.spec_k + 1 >= config.max_seq_len:
                raise MXNetError(
                    f"spec_k {self.spec_k} cannot exceed max_seq_len "
                    f"{config.max_seq_len} - 2")
            self.spec = speculate_mod.make_drafter(
                config.spec_draft, draft_params=draft_params,
                draft_heads=(draft_heads if draft_heads is not None
                             else self.heads),
                window=config.spec_window)
            if self.spec.kind == "model":
                self.spec.bind_runner(self._run_draft_program)
        # "serve2": program outputs grew a finite-logits guard flag —
        # old cached executables have the wrong output arity.  The spec
        # suffix appears ONLY when speculation is on, so every
        # non-speculative program key (and warm disk cache) is
        # untouched by this round.
        spec_tag = (f":spec{self.spec_k}:{self.spec.signature()}"
                    if self.spec is not None else "")
        self._fingerprint = (
            f"serve2:{self.vocab}:{self.num_layers}:{self.d_model}:"
            f"{self.heads}:bs{bs}:nb{config.num_blocks}:"
            f"mb{self.max_blocks}:{np.dtype(config.dtype).name}:"
            f"pc{self.prefill_chunk}:kv{config.kv_quant or 'f32'}:"
            f"{self.attn_impl}{spec_tag}{self.model.signature()}")
        if self.recurrent:
            telemetry.gauge("serve.state.bytes_per_request").set(
                kvcache.pool_nbytes(self.state) // config.num_blocks)
        else:
            telemetry.gauge("kv_bytes_per_token").set(
                kvcache.kv_bytes_per_token(
                    self.num_layers, self.kv_heads, self.head_dim,
                    config.kv_quant, dtype=config.dtype,
                    latent_width=(self.model.latent_width if self.latent
                                  else None)))

    # the cache arrays by name
    @property
    def kpool(self):
        return self._caches[0]

    @property
    def vpool(self):
        return self._caches[1]

    @property
    def state(self):
        """The recurrent-state pool (``kvcache.make_state_pool``)."""
        return self._caches[0]

    @property
    def latents(self):
        """The latent pool (``kvcache.make_pools`` with a
        ``latent_width``): the one cache array of a latent model."""
        return self._caches[0]

    def _run(self, kind: str, bucket: int, *args):
        """Run a warmed program over the donated caches and this step's
        weights; keeps the caches it returns and hands back the rest."""
        n = len(self._caches)
        out = self._programs[(kind, bucket)](
            *self._caches, self._step_params(), *args)
        self._caches = tuple(out[:n])
        return out[n:]

    # -- weight loading ---------------------------------------------------

    @classmethod
    def from_checkpoint(cls, source: str, config: EngineConfig,
                        epoch: Optional[int] = None) -> "Engine":
        """Build from a CheckpointManager directory, a legacy
        ``prefix`` (``prefix-symbol.json`` + ``prefix-%04d.params``), or
        a ``.params`` file — :func:`mxnet_tpu.predictor.load_weights`,
        the story shared with the deployment predictor."""
        from ..predictor import load_weights
        _, arg_params, _, _meta = load_weights(source, epoch)
        return cls(arg_params, config)

    def swap_weights(self, params_or_source: Any,
                     epoch: Optional[int] = None) -> Dict[str, Any]:
        """Zero-downtime weight hot-swap: install a new checkpoint into
        this running engine between steps (docs/train_serve.md).

        ``params_or_source`` is a parameter dict or anything
        :func:`~mxnet_tpu.predictor.load_weights` accepts.  Weights are
        program *operands* (``_step_params``), so a signature-identical
        swap reuses every warm AOT program — zero retraces, pinned by
        ``trace_counts`` in tests/test_online.py.  KV entries survive:
        same architecture, same pool layout (positions cached under the
        old weights simply feed the new ones — in-flight streams see
        the update at their next decode step; callers who need
        request-boundary semantics drain first, which is exactly what
        ``Router.rolling_swap`` does).

        An incompatible signature (key set / shape / dtype delta)
        raises :class:`MXNetError` without touching engine state — new
        avals would mean new programs and a stale KV layout, so the
        deployment path must rebuild the replica instead.  Returns the
        :class:`~mxnet_tpu.online.compat.CompatReport` dict.
        """
        from ..online.compat import check_compat, signature_of_params
        if isinstance(params_or_source, str):
            from ..predictor import load_weights
            _, params_or_source, _, _ = load_weights(params_or_source,
                                                     epoch)
        new = {k: jnp.asarray(
            v.asnumpy() if hasattr(v, "asnumpy") else v)
            for k, v in params_or_source.items()}
        report = check_compat(signature_of_params(self._params),
                              signature_of_params(new))
        if not report.compatible:
            raise MXNetError(
                "swap_weights: incompatible weights — "
                f"{report.summary()} (added={report.added[:4]} "
                f"removed={report.removed[:4]} "
                f"changed={[c['name'] for c in report.changed[:4]]}); "
                "rebuild the engine (Router.rolling_swap does)")
        self._params = new
        # the NaN-poison cache was derived from the OLD weights; a
        # later serve_poison_logits must poison the CURRENT ones
        self._poison_params = None
        # prefix-cache invalidation: resident KV was computed under the
        # OLD weights, so every index entry is stale.  The version bump
        # makes stale hashes unreachable; ref-0 cached blocks go
        # straight back to the free list (still-referenced shares just
        # stop being cacheable — they free when their holders finish).
        # Draft swaps (swap_draft_weights) deliberately do NOT pass
        # through here: the draft model never writes target KV.
        if self.prefix is not None:
            self.alloc.uncache(self.prefix.invalidate())
        self.swap_count += 1
        telemetry.counter("online.swaps").inc()
        return report.to_dict()

    def swap_draft_weights(self, params_or_source: Any,
                           epoch: Optional[int] = None) -> Dict[str, Any]:
        """Hot-swap the DRAFT model's weights, independently of the
        target (docs/serving.md §Speculative decoding).  Draft weights
        are operands of the draft program — a signature-compatible swap
        runs zero retraces, and the output contract is untouched: only
        acceptance rates move, never the emitted stream (greedy) or its
        distribution (temperature).  Requires a 'model' drafter."""
        if self.spec is None or self.spec.kind != "model":
            raise MXNetError(
                "swap_draft_weights: engine has no model drafter "
                "(speculate off, or spec_draft='ngram')")
        if isinstance(params_or_source, str):
            from ..predictor import load_weights
            _, params_or_source, _, _ = load_weights(params_or_source,
                                                     epoch)
        report = self.spec.swap(params_or_source)
        telemetry.counter("serve.spec.draft_swaps").inc()
        return report

    # -- program construction ---------------------------------------------

    def _make_prefill_fn(self, lb: int):
        heads, nl = self.heads, self.num_layers

        def fn_prefill(kpool, vpool, params, tokens, length, table_row,
                       key, temp, topk):
            self.trace_counts[f"prefill@{lb}"] += 1
            logits, ks, vs = transformer_lm_prefill(params, tokens,
                                                    heads=heads)
            for i in range(nl):
                kpool = kvcache.write_prefill(kpool, i, ks[i][0],
                                              table_row, length)
                vpool = kvcache.write_prefill(vpool, i, vs[i][0],
                                              table_row, length)
            with jax.named_scope("sample"):
                last = jnp.take(logits[0], length - 1, axis=0)
                tok = _sample(last, key, temp, topk, length)
                ok = jnp.all(jnp.isfinite(last.astype(jnp.float32)))
            return kpool, vpool, tok, ok

        return fn_prefill

    def _make_chunk_prefill_fn(self, cb: int):
        """Chunked prefill: ingest one [1, cb] slice of a prompt at
        absolute offset ``start``, extending the paged cache, and sample
        the first token (read only when this is the final chunk — the
        sampled value is position-keyed at ``length``, identical to the
        whole-prompt program's)."""
        if self.recurrent:
            return self._make_state_chunk_fn(cb)
        if self.latent or self.described_kv:
            return self._make_paged_chunk_fn(cb)
        heads, nl = self.heads, self.num_layers
        from ..models.transformer import transformer_lm_prefill_chunk

        def fn_prefill_chunk(kpool, vpool, params, tokens, start, length,
                             table_row, key, temp, topk):
            self.trace_counts[f"prefill_chunk@{cb}"] += 1
            pools = [kpool, vpool]

            def attend(i, q, k, v):
                # write this chunk's K/V first: chunk positions attend
                # causally over the whole cached prefix, themselves
                # included (same order as the decode path)
                pools[0] = kvcache.write_prefill(pools[0], i, k[0],
                                                 table_row, length,
                                                 start=start)
                pools[1] = kvcache.write_prefill(pools[1], i, v[0],
                                                 table_row, length,
                                                 start=start)
                out = kvcache.paged_prefill_attention(
                    q[0], pools[0], pools[1], i, table_row, start, length)
                return out[None]

            logits = transformer_lm_prefill_chunk(params, tokens,
                                                  heads=heads,
                                                  attend=attend)
            with jax.named_scope("sample"):
                last = jnp.take(logits[0],
                                jnp.clip(length - 1 - start, 0, cb - 1),
                                axis=0)
                tok = _sample(last, key, temp, topk, length)
                ok = jnp.all(jnp.isfinite(last.astype(jnp.float32)))
            return pools[0], pools[1], tok, ok

        return fn_prefill_chunk

    def _make_state_chunk_fn(self, cb: int):
        """The chunk program of a recurrent-state model: the request's
        state slot and its position offset go from chunk to chunk in
        place of a table row.  The state is read with a dynamic slice
        and written back with a dynamic update of the donated pool (in
        place); a request's FIRST chunk (``start == 0``) reads zeros
        whatever the slot held, which is how a slot is scrubbed before
        reuse."""
        spec, eps = self.model, self.model.retention_eps

        def fn_prefill_chunk(state, params, tokens, start, length, slot,
                             key, temp, topk):
            self.trace_counts[f"prefill_chunk@{cb}"] += 1
            pool = [state]
            positions = start + jnp.arange(cb, dtype=jnp.int32)[None, :]
            n_valid = jnp.clip(length - start, 0, cb)

            def attend(i, _kind, q, k, v, gate):
                with jax.named_scope("retention"):
                    at = (np.int32(i), slot) + (np.int32(0),) * (
                        state.ndim - 2)
                    prev = jax.lax.dynamic_slice(
                        pool[0], at, (1, 1) + state.shape[2:])[0, 0]
                    prev = jnp.where(start == 0, np.float32(0.0), prev)
                    y, new = chunk_form(prev, q[0], k[0], v[0], gate[0],
                                        n_valid, eps)
                    pool[0] = jax.lax.dynamic_update_slice(
                        pool[0], new[None, None], at)
                return y[None].astype(q.dtype)

            logits = decoder_forward(spec, params, tokens, positions,
                                     attend)
            with jax.named_scope("sample"):
                last = jnp.take(logits[0],
                                jnp.clip(length - 1 - start, 0, cb - 1),
                                axis=0)
                tok = _sample(last, key, temp, topk, length)
                ok = jnp.all(jnp.isfinite(last.astype(jnp.float32)))
            return pool[0], tok, ok

        return fn_prefill_chunk

    def _make_state_decode_fn(self, bb: int):
        """The decode program of a recurrent-state model: every row's
        state is read and rewritten once a layer, in place (the Pallas
        kernel aliases the pool; rows that are not active carry the
        trash slot)."""
        spec, eps, impl = self.model, self.model.retention_eps, self.attn_impl

        def update(pool, i, slots, q, k, v, gate):
            if impl in ("flash", "flash_interpret"):
                return retention_mod.retention_decode(
                    pool, i, slots, q, k, v, gate, eps,
                    interpret=impl == "flash_interpret")
            return retention_mod.retention_decode_xla(
                pool, i, slots, q, k, v, gate, eps)

        def fn_decode(state, params, tokens, lengths, slots, keys, temps,
                      topks):
            self.trace_counts[f"decode@{bb}"] += 1
            pool = [state]

            def attend(i, _kind, q, k, v, gate):
                with jax.named_scope("retention"):
                    y, pool[0] = update(pool[0], i, slots, q, k, v, gate)
                return y.astype(q.dtype)

            logits = decoder_forward(spec, params, tokens, lengths, attend)
            with jax.named_scope("sample"):
                toks = _sample(logits, keys, temps, topks, lengths + 1)
                oks = jnp.all(jnp.isfinite(logits.astype(jnp.float32)),
                              axis=-1)
            return pool[0], toks, oks

        return fn_decode

    # -- a DESCRIBED model on a paged cache --------------------------------
    # The makers below run a ``ModelSpec`` through ``decoder_forward``
    # over block tables: latent layers over the one latent pool, softmax
    # layers (grouped heads, window and global) over two pairs of K/V
    # pools, the window layers' under a ring a request.

    def _routed(self, params, live, stats):
        """``decoder_forward``'s ``routed`` for this engine: the form
        whose work follows the assignments where the kernels run (on the
        chip, or interpreted), the plain form elsewhere.  ``live`` [T]
        marks the positions that are some request's; each routed layer
        appends ``(experts_hit, assigned_here)`` to ``stats``."""
        if not self._routed_layers:
            return None
        spec, impl = self.model, self.attn_impl
        if impl in ("flash", "flash_interpret"):
            from .moe_experts import routed_ffn
            kw = dict(interpret=impl == "flash_interpret")
        else:
            from ..models.experts import routed_ffn
            kw = {}

        def routed(i, x):
            out, hit, here = routed_ffn(spec, params, i, x, live=live, **kw)
            stats.append((hit, here))
            return out

        return routed

    def _paged_attend(self, pools, write, read):
        """``decoder_forward``'s ``attend`` over the paged cache.  Layer
        ``i`` lives at ``self._kv_slot[i] = (at, j)``: layer ``j`` of the
        pools from ``pools[at]`` on.  ``write(pool, j, rows, sliding)``
        scatters the new positions' rows (a latent layer's one row, a
        softmax layer's K, then its V), then ``read(q, at, j, sliding)``
        attends over the cache."""
        def attend(i, kind, q, k, v, _gate):
            at, j = self._kv_slot[i]
            sliding = kind == SLIDING
            if self.latent:
                # every layer is latent: ``kvcache.CacheSpec`` refuses a mix
                with jax.named_scope("latent_write"):
                    pools[0] = write(pools[0], j,
                                     kvcache.latent_rows(pools[0], k), False)
            else:
                pools[at] = write(pools[at], j, k, sliding)
                pools[at + 1] = write(pools[at + 1], j, v, sliding)
            return read(q, at, j, sliding)

        return attend

    def _paged_sizes(self):
        """What the paged makers' readers take from the description: the
        score scale, the latent's sizes, the window and the ring."""
        spec = self.model
        if self.latent:
            return dict(scale=spec.latent_scale(), rank=spec.kv_lora_rank,
                        nope=spec.qk_nope_head_dim, window=0, ring=0)
        return dict(scale=kvcache.softmax_scale(self.head_dim), rank=0,
                    nope=0, window=spec.sliding_window, ring=self.alloc.ring)

    def _make_paged_chunk_fn(self, cb: int):
        """The chunk program of a described model on a paged cache: one
        ``[1, cb]`` slice of a prompt at absolute offset ``start`` over
        the cache's pools (one latent pool; or the window layers' K and
        V, then the global layers'), the request's table row and, for
        the window kind, its ring row.  Each layer writes the chunk's
        rows (a window layer into its ring), then its positions attend
        causally over the request's cached prefix: a latent layer in the
        up-projected form (``kvcache.latent_prefill_attention``), a
        softmax layer a key/value head's query group at a time, a window
        layer's over the last ``sliding_window`` positions
        (``kvcache.gqa_prefill_attention``); both walk the context in
        XLA.  The head is computed for the one position that is
        sampled."""
        spec, n, z = self.model, len(self._caches), self._paged_sizes()

        def fn_prefill_chunk(*args):
            self.trace_counts[f"prefill_chunk@{cb}"] += 1
            pools = list(args[:n])
            (params, tokens, start, length, table_row, *ring_row, key, temp,
             topk) = args[n:]
            positions = start + jnp.arange(cb, dtype=jnp.int32)[None, :]
            last = jnp.clip(length - 1 - start, 0, cb - 1)

            def write(p, j, rows, sliding):
                return kvcache.write_prefill(
                    p, j, rows[0], ring_row[0] if sliding else table_row,
                    length, start=start, ring=z["ring"] if sliding else 0)

            def read(q, at, j, sliding):
                if self.latent:
                    return kvcache.latent_prefill_attention(
                        q[0], pools[0], j, table_row, start, length,
                        params[f"layer{j}_kv_b_weight"], rank=z["rank"],
                        nope=z["nope"], scale=z["scale"])[None]
                return kvcache.gqa_prefill_attention(
                    q[0], pools[at], pools[at + 1], j,
                    ring_row[0] if sliding else table_row, start, length,
                    scale=z["scale"], window=z["window"] if sliding else 0,
                    ring=z["ring"] if sliding else 0)[None]

            logits = decoder_forward(
                spec, params, tokens, positions,
                self._paged_attend(pools, write, read),
                routed=self._routed(params, (positions < length)[0], []),
                select=lambda h: jax.lax.dynamic_slice_in_dim(h, last, 1, 1))
            with jax.named_scope("sample"):
                last_logits = logits[0, 0]
                tok = _sample(last_logits, key, temp, topk, length)
                ok = jnp.all(jnp.isfinite(last_logits.astype(jnp.float32)))
            return (*pools, tok, ok)

        return fn_prefill_chunk

    def _make_paged_decode_fn(self, bb: int):
        """The decode program of a described model on a paged cache, over
        ``_make_paged_chunk_fn``'s pools and, for the window kind, the
        rows' ring tables and the ring slots written.  Each row's new
        rows are written (a window layer's into its ring), then its one
        query attends over the row's cached positions: a latent layer's
        in the absorbed form (the Pallas kernel ``mxtpu_mla_decode``
        with ``attn_impl="flash"``), a softmax layer's a key/value
        head's query group at a time over the row's live blocks, a
        window layer's from the window's first block on
        (``mxtpu_gqa_decode``).  Beside the tokens it returns
        ``[experts_hit, assigned_here]`` summed over the routed layers
        (zeros where the model has none), so that the one fetch brings
        them."""
        spec, impl = self.model, self.attn_impl
        n, z = len(self._caches), self._paged_sizes()

        def fn_decode(*args):
            self.trace_counts[f"decode@{bb}"] += 1
            pools, stats = list(args[:n]), []
            (params, tokens, tables, lengths, slots, offsets, active,
             *ring_args, keys, temps, topks) = args[n:]
            rings, ring_slots = ring_args or (None, None)
            # a row past ``active`` attends nothing: the kernel reads no
            # block for it
            attended = jnp.where(active, lengths + 1, 0)

            def write(p, j, rows, sliding):
                return kvcache.write_decode(
                    p, j, rows, ring_slots if sliding else slots, offsets,
                    active)

            def read(q, at, j, sliding):
                if self.latent:
                    w_kvb = params[f"layer{j}_kv_b_weight"]
                    with jax.named_scope("attn"):
                        qa = kvcache.latent_absorb(q, w_kvb, z["nope"])
                    y = kvcache.latent_decode_attention(
                        qa, pools[0], j, tables, attended, rank=z["rank"],
                        scale=z["scale"], impl=impl)
                    with jax.named_scope("attn"):
                        return kvcache.latent_expand(y, w_kvb, z["nope"])
                return kvcache.gqa_decode_attention(
                    q, pools[at], pools[at + 1], j,
                    rings if sliding else tables, attended, scale=z["scale"],
                    window=z["window"] if sliding else 0,
                    ring=z["ring"] if sliding else 0, impl=impl)

            logits = decoder_forward(
                spec, params, tokens, lengths,
                self._paged_attend(pools, write, read),
                routed=self._routed(params, active, stats))
            with jax.named_scope("sample"):
                toks = _sample(logits, keys, temps, topks, lengths + 1)
                oks = jnp.all(jnp.isfinite(logits.astype(jnp.float32)),
                              axis=-1)
            return (*pools, toks, oks, _moe_counts(stats))

        return fn_decode

    def _make_decode_fn(self, bb: int):
        if self.recurrent:
            return self._make_state_decode_fn(bb)
        if self.latent or self.described_kv:
            return self._make_paged_decode_fn(bb)
        heads, impl = self.heads, self.attn_impl

        def fn_decode(kpool, vpool, params, tokens, tables, lengths, slots,
                      offsets, active, keys, temps, topks):
            self.trace_counts[f"decode@{bb}"] += 1
            pools = [kpool, vpool]

            def attend(i, q, k, v):
                pools[0] = kvcache.write_decode(pools[0], i, k, slots,
                                                offsets, active)
                pools[1] = kvcache.write_decode(pools[1], i, v, slots,
                                                offsets, active)
                return kvcache.paged_attention(
                    q, pools[0], pools[1], i, tables, attended, impl=impl)

            # a row past ``active`` attends nothing (its output is thrown
            # away): told so, the flash kernel reads no block for it
            attended = jnp.where(active, lengths + 1, 0)
            logits = transformer_lm_decode(params, tokens, heads=heads,
                                           attend=attend)
            with jax.named_scope("sample"):
                toks = _sample(logits, keys, temps, topks, lengths + 1)
                oks = jnp.all(jnp.isfinite(logits.astype(jnp.float32)),
                              axis=-1)
            return pools[0], pools[1], toks, oks

        return fn_decode

    def _make_verify_fn(self, bb: int):
        """The speculative step program: write the window's K/V, score
        all K+1 positions causally against the paged cache
        (:func:`transformer_lm_verify`), run replay-exact acceptance,
        and scrub the rejected tail — one fixed-shape program per
        decode bucket, replacing the decode program entirely when
        speculation is on (a row with ``live=0`` IS a decode step)."""
        heads, nl = self.heads, self.num_layers
        c = self.spec_k + 1
        bsz = self.config.block_size
        mb = self.max_blocks

        def fn_verify(kpool, vpool, params, tokens, tables, lengths, live,
                      active, keys, temps, topks):
            self.trace_counts[f"verify@{bb}"] += 1
            pools = [kpool, vpool]
            win = jnp.arange(c)[None, :]
            posm = lengths[:, None] + win                  # [bb, C] writes
            logical = jnp.minimum(posm // bsz, mb - 1)
            slot_raw = jnp.take_along_axis(tables, logical, axis=1)
            writemask = active[:, None] & (win <= live[:, None])
            slots = jnp.where(writemask, slot_raw, kvcache.TRASH_BLOCK)
            offs = posm % bsz

            def attend(i, q, k, v):
                pools[0] = kvcache.write_spec(pools[0], i, k, slots, offs)
                pools[1] = kvcache.write_spec(pools[1], i, v, slots, offs)
                return kvcache.paged_verify_attention(
                    q, pools[0], pools[1], i, tables, lengths)

            logits = transformer_lm_verify(params, tokens, heads=heads,
                                           attend=attend)
            with jax.named_scope("sample"):
                out, nem = _spec_accept(logits, tokens, live, keys,
                                        temps, topks, lengths)
            # cursor rollback: the block cursor truncates to the last
            # accepted draft, and the rejected tail's K/V is scrubbed
            # in-graph (kept positions redirect to the trash block)
            scrub = writemask & (win > (nem - 1)[:, None])
            sslots = jnp.where(scrub, slot_raw, kvcache.TRASH_BLOCK)
            pools[0] = kvcache.scrub_positions(pools[0], sslots, offs)
            pools[1] = kvcache.scrub_positions(pools[1], sslots, offs)
            # finite guard over the window positions acceptance read
            # (dead positions attend over unwritten garbage by design)
            livemask = win <= live[:, None]
            with jax.named_scope("sample"):
                oks = jnp.all(jnp.isfinite(logits.astype(jnp.float32))
                              | ~livemask[:, :, None], axis=(1, 2))
            return pools[0], pools[1], out, nem, oks

        return fn_verify

    def _make_draft_fn(self, bb: int):
        """The model drafter's program: K-step greedy unroll of the
        small LM over a right-aligned context window.  Draft weights
        are operands (hot-swappable); drafting is deterministic in the
        window, which the temperature path's replay-exactness needs."""
        k = self.spec_k
        heads, w = self.spec.heads, self.spec.window

        def fn_draft(dparams, window, ctx_len):
            self.trace_counts[f"draft@{bb}"] += 1
            toks, ln = window, ctx_len
            outs = []
            for _ in range(k):
                logits = speculate_mod.draft_window_logits(
                    dparams, toks, ln, heads=heads)
                nxt = jnp.argmax(logits.astype(jnp.float32),
                                 axis=-1).astype(jnp.int32)
                outs.append(nxt)
                toks = jnp.concatenate([toks[:, 1:], nxt[:, None]], axis=1)
                ln = jnp.minimum(ln + 1, w)
            return jnp.stack(outs, axis=1)

        return fn_draft

    def _run_draft_program(self, win: np.ndarray, lens: np.ndarray):
        """Runner bound into the ModelDrafter: pad to the decode
        bucket, run the AOT draft program, strip the padding."""
        n = win.shape[0]
        bb = cc.bucket_for(n, self.decode_buckets)
        self._ensure_program("draft", bb)
        padw = np.zeros((bb, self.spec.window), np.int32)
        padw[:n] = win
        padl = np.ones((bb,), np.int32)
        padl[:n] = np.maximum(lens, 1)
        out = self._programs[("draft", bb)](self.spec.params, padw, padl)
        return np.asarray(out)[:n]

    def _pool_aval(self, i: int = 0):
        sds = jax.ShapeDtypeStruct
        return jax.tree_util.tree_map(lambda a: sds(a.shape, a.dtype),
                                      self._caches[i])

    def _avals(self, kind: str, bucket: int):
        sds = jax.ShapeDtypeStruct
        pool = self._pool_aval()
        params = {k: sds(v.shape, v.dtype) for k, v in self._params.items()}
        key = sds((2,), jnp.uint32)
        if self.recurrent:
            i32 = lambda *s: sds(s, jnp.int32)
            if kind == "prefill_chunk":
                return (pool, params, i32(1, bucket), i32(), i32(), i32(),
                        key, sds((), jnp.float32), i32())
            if kind == "decode":
                b = bucket
                return (pool, params, i32(b), i32(b), i32(b),
                        sds((b, 2), jnp.uint32), sds((b,), jnp.float32),
                        i32(b))
            raise MXNetError(f"a recurrent-state model has no {kind!r} "
                             "program")
        if self.latent or self.described_kv:
            # the caches, then the paged makers' operands; the window kind
            # adds its ring tables (and decode the ring slots written)
            i32 = lambda *s: sds(s, jnp.int32)
            pools = tuple(self._pool_aval(i) for i in range(len(self._caches)))
            ring = self.alloc.ring if self.described_kv else 0
            if kind == "prefill_chunk":
                rings = (i32(ring),) if ring else ()
                return pools + (params, i32(1, bucket), i32(), i32(),
                                i32(self.max_blocks), *rings, key,
                                sds((), jnp.float32), i32())
            if kind == "decode":
                b = bucket
                rings = (i32(b, ring), i32(b)) if ring else ()
                return pools + (params, i32(b), i32(b, self.max_blocks),
                                i32(b), i32(b), i32(b), sds((b,), jnp.bool_),
                                *rings, sds((b, 2), jnp.uint32),
                                sds((b,), jnp.float32), i32(b))
            raise MXNetError(f"a described model on a paged cache has no "
                             f"{kind!r} program")
        if kind == "prefill":
            return (pool, pool, params, sds((1, bucket), jnp.int32),
                    sds((), jnp.int32), sds((self.max_blocks,), jnp.int32),
                    key, sds((), jnp.float32), sds((), jnp.int32))
        if kind == "prefill_chunk":
            return (pool, pool, params, sds((1, bucket), jnp.int32),
                    sds((), jnp.int32), sds((), jnp.int32),
                    sds((self.max_blocks,), jnp.int32),
                    key, sds((), jnp.float32), sds((), jnp.int32))
        b = bucket
        i32 = lambda *s: sds(s, jnp.int32)
        if kind == "draft":
            dparams = {k: sds(v.shape, v.dtype)
                       for k, v in self.spec.params.items()}
            return (dparams, i32(b, self.spec.window), i32(b))
        if kind == "verify":
            return (pool, pool, params, i32(b, self.spec_k + 1),
                    i32(b, self.max_blocks), i32(b), i32(b),
                    sds((b,), jnp.bool_), sds((b, 2), jnp.uint32),
                    sds((b,), jnp.float32), i32(b))
        return (pool, pool, params, i32(b), i32(b, self.max_blocks),
                i32(b), i32(b), i32(b), sds((b,), jnp.bool_),
                sds((b, 2), jnp.uint32), sds((b,), jnp.float32), i32(b))

    def _ensure_program(self, kind: str, bucket: int) -> Dict[str, Any]:
        pkey = (kind, bucket)
        if pkey in self._programs:
            return {"source": "ready", "kind": kind, "bucket": bucket}
        make = {"prefill": self._make_prefill_fn,
                "prefill_chunk": self._make_chunk_prefill_fn,
                "decode": self._make_decode_fn,
                "verify": self._make_verify_fn,
                "draft": self._make_draft_fn}[kind]
        # the draft program owns no pools — nothing to donate
        donate = () if kind == "draft" else tuple(range(len(self._caches)))
        jit_fn = jax.jit(make(bucket), donate_argnums=donate)
        avals = self._avals(kind, bucket)
        ckey = cc.program_key(self._fingerprint, avals, donate=donate,
                              extra={"serve": kind, "bucket": bucket})
        compiled, info = cc.get_cache().get_or_compile(
            ckey, lambda: jit_fn.lower(*avals).compile(),
            label=f"serve.{kind}.{bucket}")
        self.aot_stats[info["source"]] += 1
        # a fallback here means the engine built arguments its own
        # program rejects — counted so warm-path checks can pin it at 0
        self._programs[pkey] = cc.AotProgram(
            compiled, jit_fn, f"serve.{kind}.{bucket}",
            stats=self.aot_stats)
        return dict(info, kind=kind, bucket=bucket)

    def warmup(self) -> List[Dict[str, Any]]:
        """Resolve every prefill/decode bucket program through the
        compile cache.  After this, steady-state serving runs zero
        traces (``trace_counts`` stays flat — pinned by tests).  With
        speculation on, the verify program replaces the decode program
        (one more AOT bucket family, not one more per step) and a
        'model' drafter warms its draft program too."""
        with telemetry.span("serve.warmup"):
            pkind = "prefill_chunk" if self.prefill_chunk else "prefill"
            infos = [self._ensure_program(pkind, lb)
                     for lb in self.prompt_buckets]
            dkind = "verify" if self.spec is not None else "decode"
            infos += [self._ensure_program(dkind, bb)
                      for bb in self.decode_buckets]
            if self.spec is not None and self.spec.kind == "model":
                infos += [self._ensure_program("draft", bb)
                          for bb in self.decode_buckets]
        return infos

    # -- submit / stream / cancel -----------------------------------------

    def submit(self, prompt: Sequence[int], *, max_new_tokens: int = 32,
               temperature: float = 0.0, top_k: int = 0,
               slo_ms: Optional[float] = None,
               eos_id: Optional[int] = None,
               seed: Optional[int] = None,
               deadline_ms: Optional[float] = None) -> int:
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise MXNetError("empty prompt")
        if len(prompt) > self.config.max_prompt_len:
            raise MXNetError(
                f"prompt length {len(prompt)} exceeds max_prompt_len "
                f"{self.config.max_prompt_len}")
        if len(prompt) + max_new_tokens > self.config.max_seq_len:
            raise MXNetError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} "
                f"exceeds max_seq_len {self.config.max_seq_len}")
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), top_k=int(top_k),
                      slo_ms=slo_ms, eos_id=eos_id)
        req.deadline_ms = (deadline_ms if deadline_ms is not None
                           else self.config.deadline_ms)
        # the sampling key is (engine seed, request seed, position)-pure:
        # an explicit `seed` replays the same stream in any engine,
        # regardless of admission order or batch composition
        req.key = np.asarray(jax.random.fold_in(
            self._base_key, req.id if seed is None else int(seed)),
            np.uint32)
        self.sched.submit(req)
        self.requests[req.id] = req
        telemetry.counter("serve.submitted").inc()
        return req.id

    def adopt(self, prompt: Sequence[int], tokens: Sequence[int], *,
              max_new_tokens: int = 32, temperature: float = 0.0,
              top_k: int = 0, slo_ms: Optional[float] = None,
              eos_id: Optional[int] = None, seed: Optional[int] = None,
              deadline_ms: Optional[float] = None,
              submit_t: Optional[float] = None) -> int:
        """Admit a request that already produced ``tokens`` on another
        engine — the router's mid-stream failover path.  The request
        re-prefills ``prompt + tokens`` (the standard preemption
        mechanics) and, because sampling keys are (seed, position)-pure,
        continues the exact token stream the dead replica would have
        produced.  ``seed`` is mandatory: the implicit seed (this
        engine's request id) could never match the original's.
        ``submit_t`` carries the original submit time so SLO and
        deadline clocks keep running across the failure."""
        prompt = [int(t) for t in prompt]
        tokens = [int(t) for t in tokens]
        if seed is None:
            raise MXNetError("adopt() needs the original request seed")
        if not prompt:
            raise MXNetError("empty prompt")
        if len(prompt) + max_new_tokens > self.config.max_seq_len:
            raise MXNetError(
                f"prompt {len(prompt)} + max_new_tokens {max_new_tokens} "
                f"exceeds max_seq_len {self.config.max_seq_len}")
        if len(tokens) >= max_new_tokens:
            raise MXNetError(
                f"nothing to adopt: {len(tokens)} tokens already meet "
                f"max_new_tokens {max_new_tokens}")
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), top_k=int(top_k),
                      slo_ms=slo_ms, eos_id=eos_id)
        req.deadline_ms = (deadline_ms if deadline_ms is not None
                           else self.config.deadline_ms)
        req.tokens = list(tokens)
        req.key = np.asarray(jax.random.fold_in(
            self._base_key, int(seed)), np.uint32)
        self.sched.submit(req, now=submit_t)
        if tokens:
            # first token already streamed elsewhere — don't re-record
            # TTFT for the continuation
            req.first_token_t = req.submit_t
        self.requests[req.id] = req
        telemetry.counter("serve.adopted").inc()
        return req.id

    def cancel(self, req_id: int) -> None:
        req = self._req(req_id)
        if not req.done():
            self.sched.cancel(req)
            telemetry.counter("serve.cancelled").inc()

    def request(self, req_id: int) -> Request:
        return self._req(req_id)

    def _req(self, req_id: int) -> Request:
        try:
            return self.requests[req_id]
        except KeyError:
            raise MXNetError(f"unknown request id {req_id}")

    def stream(self, req_id: int):
        """Generator of token ids as they are produced; drives the
        engine loop while the request is live.  A request that fails
        (timeout, NaN logits, shed) raises :class:`ServeError` after
        any tokens produced so far — mid-stream failure surfaces as a
        typed exception, never silently truncated output."""
        req = self._req(req_id)
        cursor = 0
        while True:
            while cursor < len(req.tokens):
                yield req.tokens[cursor]
                cursor += 1
            if req.done():
                if req.state == FAILED:
                    raise ServeError(req.finish_reason or "error", req_id)
                return
            self.step()

    def result(self, req_id: int) -> List[int]:
        """Run the engine until the request completes; returns its
        generated tokens.  Raises :class:`ServeError` (with the finish
        reason) if the request failed."""
        req = self._req(req_id)
        guard = 0
        while not req.done():
            self.step()
            guard += 1
            if guard > 10 * self.config.max_seq_len + 100:
                raise MXNetError(f"request {req_id} failed to converge")
        if req.state == FAILED:
            raise ServeError(req.finish_reason or "error", req_id)
        return list(req.tokens)

    def run(self, max_steps: int = 100000) -> None:
        """Drive the loop until every submitted request completes."""
        for _ in range(max_steps):
            if self.sched.idle():
                return
            self.step()
        raise MXNetError(f"engine still busy after {max_steps} steps")

    # -- the step loop -----------------------------------------------------

    def step(self) -> None:
        """One continuous-batching iteration: evict, admit+prefill, one
        batched decode step.  Any exception dumps the flight recorder
        (``serve-error``) before propagating."""
        try:
            self._step_inner()
        except Exception as exc:   # noqa: BLE001 — observe, then re-raise
            telemetry.dump_flight("serve-error", extra={
                "error": repr(exc), "step": self.step_idx,
                "active": [r.id for r in self.sched.running],
                "queued": [r.id for r in self.sched.queue]})
            raise

    def _step_inner(self) -> None:
        if self._hung:
            # a wedged device step: returns nothing, makes no progress,
            # never advances `beat` — the router's heartbeat timeout is
            # the only way its requests get out
            return
        self.step_idx += 1
        self._poison_step = False
        if self.chaos is not None:
            self._chaos_fire()
            if self._hung:
                return
        with telemetry.span("serve.step", step=self.step_idx,
                            queued=self.sched.queue_depth) as step_span:
            now = time.monotonic()
            for req in list(self.sched.running):
                if req.cancel_requested:
                    self._finish(req, "cancelled", CANCELLED)
            for req in list(self.sched.running) + list(self.sched.queue):
                if (req.deadline_ms is not None
                        and (now - req.submit_t) * 1e3 > req.deadline_ms):
                    telemetry.counter("serve.timeouts").inc()
                    self._finish(req, "timeout", FAILED)
            with telemetry.span("serve.admit", step=self.step_idx,
                                queued=self.sched.queue_depth):
                admitted = self.sched.admit(
                    self._admission_gate(), now,
                    prefill_backlog_ms=self._prefill_backlog_ms(),
                    decode_backlog_ms=self._decode_backlog_ms())
            if self.prefill_chunk:
                for req in admitted:
                    self._prefill_begin(req)
                chunk = self._prefill_pump()
            else:
                for req in admitted:
                    self._prefill(req)
                chunk = bool(admitted)
            rows = self._decode_step() if self.sched.running else 0
            self.publish_load_gauges()
            telemetry.flight_recorder().record({
                "kind": "serve", "step": self.step_idx,
                "active": self.sched.active,
                "queued": self.sched.queue_depth,
                "blocks_used": self.alloc.num_used})
            step_span.annotate(rows=rows, chunk=int(chunk))
        self.beat += 1

    def publish_load_gauges(self) -> None:
        """Refresh this engine's load gauges.  ``_step_inner`` calls it
        per step; the router overwrites the shared names with fleet
        aggregates every *router* step (``Router._publish_gauges``) so
        multi-replica readings never depend on which engine stepped
        last — or whether any engine stepped at all."""
        telemetry.gauge("serve.queue_depth").set(self.sched.queue_depth)
        telemetry.gauge("serve.active_slots").set(self.sched.active)
        telemetry.gauge("serve.kv_blocks_used").set(self.alloc.num_used)
        if self.recurrent:
            telemetry.gauge("serve.state.slots_used").set(
                self.alloc.num_used)
        if self.described_kv:
            telemetry.gauge("serve.kv.window_blocks_used").set(
                self.alloc.window.num_used)
            telemetry.gauge("serve.kv.global_blocks_used").set(
                self.alloc.global_used)
        if self.prefix is not None:
            telemetry.gauge("serve.prefix.cached_frac").set(
                self.alloc.num_cached / (self.config.num_blocks - 1))

    def _chaos_fire(self) -> None:
        """Serve-side chaos points, fired by exact step index (global
        over the engine's lifetime, so failures reproduce bit-for-bit)."""
        i = self.step_idx
        if self.chaos.at("serve_crash", i):
            telemetry.counter("serve.chaos_injected").inc(kind="crash")
            raise chaos_mod.ChaosError(
                "chaos: injected replica crash at serve step %d" % i)
        if self.chaos.at("serve_hang", i):
            telemetry.counter("serve.chaos_injected").inc(kind="hang")
            self._hung = True
            return
        if self.chaos.at("serve_poison_logits", i):
            telemetry.counter("serve.chaos_injected").inc(kind="poison")
            self._poison_step = True

    def _step_params(self):
        """Model weights for this step — NaN-poisoned under the
        ``serve_poison_logits`` chaos point (same shapes/dtypes, so the
        same compiled program runs; the in-graph finite guard must be
        what catches it, not a shape error)."""
        if not self._poison_step:
            return self._params
        if self._poison_params is None:
            self._poison_params = {
                k: (jnp.full_like(v, jnp.nan)
                    if jnp.issubdtype(v.dtype, jnp.floating) else v)
                for k, v in self._params.items()}
        return self._poison_params

    def _fail_nan(self, req: Request) -> None:
        telemetry.counter("serve.nan_logits").inc()
        telemetry.flight_recorder().record({
            "kind": "serve.nan_logits", "req": req.id,
            "step": self.step_idx})
        # the request's cached K/V (and the trash block, which padding
        # rows wrote this step) may hold NaN — scrub before the blocks
        # go back to the pool, or the residue leaks into the next
        # request that reuses them (masked attention lanes multiply by
        # zero, and 0 * NaN = NaN).  Blocks another owner still
        # references, and blocks published to the prefix index, are
        # NOT scrubbed: a shared/indexed block is provably clean (it
        # was published only after a finite-ok chunk and is never
        # written again — this request's poisoned writes all landed in
        # its private unpublished blocks), and zeroing it would corrupt
        # the co-owner's stream.  This request merely drops its
        # references via _finish.
        # A state slot needs no scrub here: its next request's first
        # chunk reads zeros in its place, and the trash slot is read by
        # no live row.
        if not self.recurrent:
            scrub = [b for b in req.blocks
                     if self.alloc.refcount(b) <= 1
                     and (self.prefix is None
                          or not self.prefix.contains_block(b))]
            scrub += [kvcache.TRASH_BLOCK]
            # a described softmax model's window pools (the first pair)
            # are under the request's ring
            ring = req.ring + [kvcache.TRASH_BLOCK]
            self._caches = tuple(
                kvcache.scrub_blocks(
                    pool, ring if self.described_kv and i < 2 else scrub)
                for i, pool in enumerate(self._caches))
        self._finish(req, "error", FAILED)

    # -- prefix cache (round 18) ------------------------------------------

    def _probe(self, tokens: Sequence[int]) -> List[int]:
        """Longest usable cached prefix of ``tokens``: physical blocks
        from the index, floored to the hit quantum (chunk-grid
        alignment — see ``__init__``) and capped strictly below
        ``len(tokens)`` so at least one suffix chunk always runs (the
        final chunk is what samples the first token)."""
        if self.prefix is None:
            return []
        blocks = self.prefix.match(tokens)
        bs = self.alloc.block_size
        hit = min(len(blocks) * bs, len(tokens) - 1)
        hit -= hit % self._hit_quantum
        nblk = hit // bs
        if nblk < self.config.prefix_min_blocks:
            return []
        return blocks[:nblk]

    def prefix_probe(self, tokens: Sequence[int]) -> int:
        """Tokens of ``tokens`` this engine could serve from its prefix
        cache right now (0 when the cache is off).  Read-only — no
        pinning — the router's affinity dispatch calls this on every
        healthy replica."""
        if self.prefix is None:
            return 0
        return len(self._probe([int(t) for t in tokens])) \
            * self.alloc.block_size

    def _count_prefix_hit(self, req: Request, nblocks: int) -> None:
        bs = self.alloc.block_size
        self._prefix_hits += 1
        self._prefix_hit_tokens += nblocks * bs
        telemetry.counter("serve.prefix.hits").inc()
        telemetry.counter("serve.prefix.shared_blocks").inc(nblocks)
        telemetry.counter("serve.prefix.hit_tokens").inc(nblocks * bs)

    def _publish_prefix(self, req: Request) -> None:
        """Publish every newly-completed *full* prefill block of
        ``req`` to the index.  Called only after a finite-ok chunk and
        never on a poison step, so indexed blocks are provably clean:
        a full block is never written again (decode/spec writes land at
        positions past the prefill target)."""
        if self.prefix is None or self._poison_step:
            return
        n_full = min(req.prefilled, req.prefill_target) \
            // self.alloc.block_size
        if n_full <= req.published:
            return
        toks = req.seed_tokens[:n_full * self.alloc.block_size]
        hashes = self.prefix.chain_hashes(toks)
        for j in range(req.published, n_full):
            self.prefix.publish(hashes[j], req.blocks[j])
        req.published = n_full

    def _map_prefix_second_chance(self, req: Request) -> None:
        """Re-probe just before the FIRST prefill chunk runs.  A cohort
        admitted in one step probes an index that none of them has
        populated yet; by the time the pump reaches request N, request
        0 may have prefilled and published the shared prefix — this is
        what makes "8 streams, one prefill of the prefix" hold even for
        same-step arrivals (and gives re-prefill-after-preemption and
        adopted failover continuations their cached TTFT)."""
        hits = self._probe(req.seed_tokens)
        if not hits:
            self._prefix_misses += 1
            telemetry.counter("serve.prefix.misses").inc()
            return
        n = len(hits)
        for b in hits:
            self.alloc.addref(b, req.id)
        drop = req.blocks[:n]
        req.blocks = hits + req.blocks[n:]
        # the dropped fresh blocks are unwritten and unindexed, so
        # release sends them straight back to the free list
        self.alloc.release(drop, req.id)
        req.prefilled = req.cached = n * self.alloc.block_size
        req.prefix_hit = n * self.alloc.block_size
        req.published = n
        self._count_prefix_hit(req, n)

    # -- admission ---------------------------------------------------------

    def _admission_gate(self):
        """``can_place`` for one admit pass.  Blocks promised to earlier
        accepted candidates are reserved against the available count, so
        two requests admitted in the same pass can never jointly claim
        more blocks than the pool has (their ``_prefill`` allocs all
        succeed).  With the prefix cache on, the candidate's longest
        cached prefix is pinned (addref) and *discounted from the
        reserve* — cache-satisfiable blocks cost nothing — and the
        budget is ``num_available`` (free + evictable cached): parked
        prefix blocks are extra capacity, never admission pressure."""
        reserved = 0
        reserved_ring = 0     # a described softmax model's window blocks

        def can_place(req: Request) -> bool:
            nonlocal reserved, reserved_ring
            toks = req.seed_tokens
            total = self.alloc.blocks_for_tokens(len(toks))
            ring = (self.alloc.ring_blocks(len(toks)) if self.described_kv
                    else 0)
            hits = self._probe(toks)
            for b in hits:
                self.alloc.addref(b, req.id)
            need = total - len(hits)
            if (reserved + need > self.alloc.num_available
                    or (ring and reserved_ring + ring
                        > self.alloc.window.num_available)):
                if hits:       # roll the pins back — admission stops
                    self.alloc.release(hits, req.id)
                return False
            reserved += need
            reserved_ring += ring
            req.prefix_blocks = hits
            return True

        return can_place

    def _prefill(self, req: Request) -> None:
        toks = req.seed_tokens
        plen = len(toks)
        nblocks = self.alloc.blocks_for_tokens(plen)
        req.blocks = self.alloc.alloc(nblocks, req.id)
        lb = cc.bucket_for(plen, self.prompt_buckets)
        self._ensure_program("prefill", lb)
        t0 = time.monotonic()
        with telemetry.span("serve.prefill", req=req.id, bucket=lb,
                            prompt=plen):
            with telemetry.span("serve.build"):
                padded = np.zeros((1, lb), np.int32)
                padded[0, :plen] = toks
                table_row = np.zeros((self.max_blocks,), np.int32)
                table_row[:len(req.blocks)] = req.blocks
            with telemetry.span("serve.dispatch", kind="prefill",
                                bucket=lb):
                tok, ok = self._run(
                    "prefill", lb, padded, np.int32(plen), table_row,
                    req.key, np.float32(req.temperature),
                    np.int32(req.top_k))
            with telemetry.span("serve.fetch"):
                tok, ok = jax.device_get((tok, ok))
        req.cached = plen
        req.prefilled = req.prefill_target = plen
        telemetry.counter("serve.prefills").inc()
        telemetry.histogram("serve.prefill_ms").observe(
            (time.monotonic() - t0) * 1e3)
        if not ok:
            self._fail_nan(req)
            return
        self._append_token(req, int(tok))

    # -- chunked prefill (round 12) ---------------------------------------

    def _prefill_begin(self, req: Request) -> None:
        """Admit-time half of chunked prefill: reserve the blocks the
        whole prompt needs (the admission gate already accounted for
        them) and arm the chunk pump; no device work yet.  Blocks the
        admission gate pinned from the prefix index slot in as the
        table's leading entries — their tokens count as already
        prefilled, so the pump starts at the first uncached chunk."""
        toks = req.seed_tokens
        req.prefill_target = len(toks)
        if self.recurrent and req.tokens:
            # a preempted (or adopted) stream: its state is rebuilt by
            # re-chunking prompt + tokens, as paged K/V is
            telemetry.counter("serve.state.rebuilds").inc()
        hits = req.prefix_blocks
        req.prefix_blocks = []
        fresh = self.alloc.alloc(
            self.alloc.blocks_for_tokens(len(toks)) - len(hits), req.id)
        req.blocks = hits + fresh
        if self.described_kv:
            req.ring = self.alloc.window.alloc(
                self.alloc.ring_blocks(len(toks)), req.id)
        req.prefilled = req.cached = len(hits) * self.alloc.block_size
        req.prefix_hit = req.prefilled
        req.published = len(hits)
        if hits:
            self._count_prefix_hit(req, len(hits))

    def _prefill_pump(self) -> None:
        """Run prefill chunks for mid-prefill requests, oldest first.

        While any request is decode-ready, at most ONE chunk runs per
        engine step — that is the whole point of chunked prefill: the
        stall a prefill injects into in-flight decodes is bounded by the
        chunk budget, not the longest admitted prompt.  (Running more
        chunks per step when few requests decode amortizes fine in
        aggregate but lands multi-chunk stalls on exactly the intervals
        the p99 ITL contract protects — measured in docs/perf.md r12.)
        When nothing can decode yet (engine start, or every slot
        mid-prefill) the pump keeps going until one request completes,
        since there is no decode to stall.  Returns whether a chunk ran.
        """
        ran = False
        while True:
            pending = [r for r in self.sched.running
                       if r.prefilled < r.prefill_target]
            if not pending:
                return ran
            self._prefill_chunk_step(pending[0])
            ran = True
            if any(r.prefilled >= r.prefill_target
                   for r in self.sched.running):
                return ran

    def _prefill_chunk_step(self, req: Request) -> None:
        cb = self.prefill_chunk
        if self.prefix is not None and req.prefilled == 0:
            self._map_prefix_second_chance(req)
        start = req.prefilled
        plen = req.prefill_target
        toks = req.seed_tokens[start:start + cb]
        self._ensure_program("prefill_chunk", cb)
        t0 = time.monotonic()
        with telemetry.span("serve.prefill", req=req.id, bucket=cb,
                            prompt=plen, chunk_start=start,
                            chunk_budget=cb):
            with telemetry.span("serve.build"):
                padded = np.zeros((1, cb), np.int32)
                padded[0, :len(toks)] = toks
                if self.recurrent:
                    # the request's state slot; the program zeroes it
                    # when start == 0
                    where = np.int32(req.blocks[0])
                    if start == 0:
                        telemetry.counter("serve.state.resets").inc()
                else:
                    where = np.zeros((self.max_blocks,), np.int32)
                    where[:len(req.blocks)] = req.blocks
                where = (where,)
                if self.described_kv:
                    ring = np.zeros((self.alloc.ring,), np.int32)
                    ring[:len(req.ring)] = req.ring
                    where += (ring,)
            with telemetry.span("serve.dispatch", kind="prefill_chunk",
                                bucket=cb):
                tok, ok = self._run(
                    "prefill_chunk", cb, padded, np.int32(start),
                    np.int32(plen), *where, req.key,
                    np.float32(req.temperature), np.int32(req.top_k))
            with telemetry.span("serve.fetch"):
                # one read of both, and the clock below stops after it:
                # the chunk's real time, not its dispatch
                tok, ok = jax.device_get((tok, ok))
        ms = (time.monotonic() - t0) * 1e3
        self._chunk_ms = (ms if self._chunk_ms == 0.0
                          else 0.8 * self._chunk_ms + 0.2 * ms)
        req.prefilled = min(start + cb, plen)
        req.cached = req.prefilled
        if self.described_kv:
            self._count_ring_reuse(start, req.cached)
        telemetry.counter("serve.prefill_chunks").inc()
        telemetry.histogram("serve.prefill_ms").observe(ms)
        if not ok:
            # mid-chunk NaN already contaminated this request's cached
            # K/V — fail now rather than stream garbage at the end
            self._fail_nan(req)
            return
        self._publish_prefix(req)
        if req.prefilled >= plen:
            telemetry.counter("serve.prefills").inc()
            self._append_token(req, int(tok))

    def _prefill_backlog_ms(self) -> float:
        """Expected serialization delay from remaining prefill chunks of
        already-admitted requests — wait a queued request will certainly
        absorb before its own prefill, credited to its SLO clock so the
        chunk pump cannot silently starve at-risk requests of their
        admission jump."""
        if not self.prefill_chunk or not self._chunk_ms:
            return 0.0
        remaining = sum(
            -(-(r.prefill_target - r.prefilled) // self.prefill_chunk)
            for r in self.sched.running
            if r.prefilled < r.prefill_target)
        return remaining * self._chunk_ms

    def _grow_blocks(self, req: Request, extra: int = 1) -> bool:
        """Ensure the request owns blocks through cache index
        ``cached + extra - 1`` (plain decode writes one entry; a
        speculative step writes up to ``live + 1``), and a described
        softmax model's window ring as many as that needs (at most the
        ring's width).  On pool exhaustion, preempts the
        youngest-admitted request (recompute-style: blocks freed,
        request requeued; its sampling replays identically).  Returns
        False if ``req`` itself was preempted."""
        need = req.cached + extra
        while True:
            if len(req.blocks) * self.alloc.block_size < need:
                pool, table = self.alloc, req.blocks
            elif (self.described_kv
                  and len(req.ring) < self.alloc.ring_blocks(need)):
                pool, table = self.alloc.window, req.ring
            else:
                return True
            if pool.can_alloc(1):
                table += pool.alloc(1, req.id)
                continue
            victim = max(self.sched.running,
                         key=lambda r: (r.admit_t or 0.0, r.id))
            self._preempt(victim)
            if victim is req:
                return False

    def _release_ring(self, req: Request) -> None:
        if req.ring:
            self.alloc.window.release(req.ring, req.id)
            req.ring = []

    def _count_ring_reuse(self, before: int, after: int) -> None:
        """Window blocks reused in place while a request's cache grew
        from ``before`` to ``after`` positions: blocks entered past the
        ring's width (``serve.kv.window_blocks_reused``)."""
        bs, ring = self.alloc.block_size, self.alloc.ring
        n = -(-after // bs) - max(ring, -(-before // bs))
        if n > 0:
            telemetry.counter("serve.kv.window_blocks_reused").inc(n)

    def _preempt(self, victim: Request) -> None:
        telemetry.counter("serve.preemptions").inc()
        # drop references, don't force-free: a shared prefix block must
        # survive for its co-owners, and this victim's own published
        # blocks park in the cache — its re-prefill re-probes the index
        # and gets most of its context back at cached-TTFT cost
        self.alloc.release(victim.blocks, victim.id)
        victim.blocks = []
        self._release_ring(victim)
        victim.cached = 0
        victim.prefilled = 0
        victim.prefill_target = 0
        victim.prefix_blocks = []
        victim.prefix_hit = 0
        victim.published = 0
        self.sched.requeue(victim)

    def _decode_step(self) -> int:
        """One batched decode step; returns the decode-ready rows it
        carried (0: every running request is still mid-prefill)."""
        if self.spec is not None:
            return self._verify_step()
        # growth pass first: a preemption inside _grow_blocks mutates
        # sched.running, so the batch roster is only read afterwards
        # (a preempted victim must not decode on freed blocks).
        # Mid-prefill requests (chunked prefill still ingesting) hold
        # blocks for their whole prompt already and have no last token
        # to feed — they stay off the decode roster until the pump
        # finishes them.
        for req in list(self.sched.running):
            if req in self.sched.running and req.prefilled >= req.prefill_target:
                self._grow_blocks(req)
        active = [r for r in self.sched.running
                  if r.prefilled >= r.prefill_target]
        if not active:
            return 0
        bb = cc.bucket_for(len(active), self.decode_buckets)
        self._ensure_program("decode", bb)
        with telemetry.span("serve.decode", step=self.step_idx, bucket=bb,
                            active=len(active)) as decode_span:
            with telemetry.span("serve.build"):
                bsz = self.alloc.block_size
                tokens = np.zeros((bb,), np.int32)
                tables = np.zeros((bb, self.max_blocks), np.int32)
                lengths = np.zeros((bb,), np.int32)
                slots = np.zeros((bb,), np.int32)
                offsets = np.zeros((bb,), np.int32)
                active_m = np.zeros((bb,), np.bool_)
                keys = np.zeros((bb, 2), np.uint32)
                temps = np.zeros((bb,), np.float32)
                topks = np.zeros((bb,), np.int32)
                for i, req in enumerate(active):
                    tokens[i] = req.tokens[-1]
                    tables[i, :len(req.blocks)] = req.blocks
                    lengths[i] = req.cached
                    slots[i] = req.blocks[req.cached // bsz]
                    offsets[i] = req.cached % bsz
                    active_m[i] = True
                    keys[i] = req.key
                    temps[i] = req.temperature
                    topks[i] = req.top_k
                decode_span.annotate(sampler=_sampler_branch(temps))
                if self.recurrent:
                    # ``slots`` are the rows' state slots (a request's
                    # one "block"); rows past ``active`` keep the trash
                    # slot 0 and are not otherwise told apart
                    where = (lengths, slots)
                else:
                    where = (tables, lengths, slots, offsets, active_m)
                    # what the attention reads (``lengths + 1`` positions
                    # of each active row) against what its tables could
                    # hold
                    decode_span.annotate(
                        live_blocks=int(np.sum(
                            (lengths // bsz + 1)[:len(active)])),
                        table_blocks=bb * self.max_blocks)
                if self.described_kv:
                    ring = self.alloc.ring
                    rings = np.zeros((bb, ring), np.int32)
                    ring_slots = np.zeros((bb,), np.int32)
                    for i, req in enumerate(active):
                        rings[i, :len(req.ring)] = req.ring
                        ring_slots[i] = req.ring[(req.cached // bsz) % ring]
                    where += (rings, ring_slots)
                    # cached positions a layer of each kind walks
                    seen = lengths[:len(active)] + 1
                    decode_span.annotate(
                        window_rows=int(np.sum(np.minimum(
                            seen, self.model.sliding_window or seen))),
                        global_rows=int(np.sum(seen)))
            t0 = time.monotonic()
            with telemetry.span("serve.dispatch", kind="decode", bucket=bb):
                toks, oks, *more = self._run("decode", bb, tokens, *where,
                                             keys, temps, topks)
            with telemetry.span("serve.fetch"):
                toks = np.asarray(toks)
                oks = np.asarray(oks)
                if self._routed_layers:
                    # the routed layers' counts came with the tokens
                    hit, here = (int(x) for x in np.asarray(more[0]))
                    offered = (len(active) * self.model.experts_per_token
                               * self._routed_layers)
                    decode_span.annotate(experts_hit=hit, assigned_here=here)
                    telemetry.counter("serve.moe.assignments").inc(offered)
                    telemetry.counter("serve.moe.assignments_here").inc(here)
            step_ms = (time.monotonic() - t0) * 1e3
            hist = telemetry.histogram("serve.token_ms")
            with telemetry.span("serve.emit"):
                if self.described_kv:
                    for req in active:
                        self._count_ring_reuse(req.cached, req.cached + 1)
                for i, req in enumerate(active):
                    req.cached += 1
                    if not bool(oks[i]):
                        self._fail_nan(req)
                        continue
                    hist.observe(step_ms)
                    self._append_token(req, int(toks[i]))
        return len(active)

    def _verify_step(self) -> int:
        """The speculative replacement for :meth:`_decode_step`: draft
        K tokens per row, verify all of them (plus the bonus position)
        in ONE fixed-shape program, emit ``1..K+1`` tokens per row.

        Per-row ``live`` (how many drafts are actually in play) is
        clamped by the remaining token budget and — under pool
        pressure — degraded to 0 rather than preempting a neighbor for
        speculative headroom: a live=0 row runs the exact decode math
        inside the verify shape, so speculation never changes WHAT is
        emitted, only how many tokens arrive per step."""
        k = self.spec_k
        c = k + 1
        for req in list(self.sched.running):
            if (req not in self.sched.running
                    or req.prefilled < req.prefill_target):
                continue
            live = max(min(k, req.max_new_tokens - len(req.tokens) - 1), 0)
            need = (self.alloc.blocks_for_tokens(req.cached + live + 1)
                    - len(req.blocks))
            if live > 0 and need > 0 and not self.alloc.can_alloc(need):
                live = 0      # no preemption for speculative headroom
            if not self._grow_blocks(req, extra=live + 1):
                continue
            req.spec_live = live
        active = [r for r in self.sched.running
                  if r.prefilled >= r.prefill_target]
        if not active:
            return 0
        bb = cc.bucket_for(len(active), self.decode_buckets)
        self._ensure_program("verify", bb)
        # drafting (a device program of its own with the model drafter)
        # is the step's, not the verify program's: outside serve.decode
        drafts = np.asarray(
            self.spec.propose([r.seed_tokens for r in active], k),
            np.int32)
        with telemetry.span("serve.decode", step=self.step_idx, bucket=bb,
                            active=len(active), spec_k=k) as decode_span:
            with telemetry.span("serve.build"):
                # drafter hygiene: a wrong draft is wasted width, an
                # out-of-range id would be an invalid embedding lookup
                drafts = np.clip(drafts, 0, self.vocab - 1)
                tokens = np.zeros((bb, c), np.int32)
                tables = np.zeros((bb, self.max_blocks), np.int32)
                lengths = np.zeros((bb,), np.int32)
                live_v = np.zeros((bb,), np.int32)
                active_m = np.zeros((bb,), np.bool_)
                keys = np.zeros((bb, 2), np.uint32)
                temps = np.zeros((bb,), np.float32)
                topks = np.zeros((bb,), np.int32)
                for i, req in enumerate(active):
                    tokens[i, 0] = req.tokens[-1]
                    tokens[i, 1:] = drafts[i]
                    tables[i, :len(req.blocks)] = req.blocks
                    lengths[i] = req.cached
                    live_v[i] = req.spec_live
                    active_m[i] = True
                    keys[i] = req.key
                    temps[i] = req.temperature
                    topks[i] = req.top_k
                decode_span.annotate(sampler=_sampler_branch(temps))
            t0 = time.monotonic()
            with telemetry.span("serve.dispatch", kind="verify", bucket=bb):
                out, nem, oks = self._run(
                    "verify", bb, tokens, tables, lengths, live_v,
                    active_m, keys, temps, topks)
            with telemetry.span("serve.fetch"):
                out = np.asarray(out)
                nem = np.asarray(nem)
                oks = np.asarray(oks)
            step_ms = (time.monotonic() - t0) * 1e3
            self._decode_ms = (step_ms if self._decode_ms == 0.0
                               else 0.8 * self._decode_ms + 0.2 * step_ms)
            hist = telemetry.histogram("serve.token_ms")
            drafted = int(np.sum(live_v[:len(active)]))
            accepted = 0
            emitted = 0
            with telemetry.span("serve.emit"):
                for i, req in enumerate(active):
                    n = int(nem[i])
                    req.cached += n          # cursor: +accepted drafts +1
                    if not bool(oks[i]):
                        self._fail_nan(req)
                        continue
                    accepted += n - 1
                    for j in range(n):
                        # multi-token burst: the step's latency lands on
                        # the first token; later burst tokens arrive
                        # back-to-back (that IS their inter-token latency
                        # — satellite of BENCH_r15, keeps p99 ITL honest)
                        hist.observe(step_ms if j == 0 else 0.0)
                        emitted += 1
                        self._append_token(req, int(out[i, j]))
                        if req.done():
                            break
        self._tps = 0.8 * self._tps + 0.2 * (emitted / max(len(active), 1))
        self._spec_drafted += drafted
        self._spec_accepted += accepted
        telemetry.counter("serve.spec.steps").inc()
        if drafted:
            telemetry.counter("serve.spec.drafted").inc(drafted)
        if accepted:
            telemetry.counter("serve.spec.accepted").inc(accepted)
        if self._spec_drafted:
            telemetry.gauge("serve.spec.accept_rate").set(
                self._spec_accepted / self._spec_drafted)
        return len(active)

    def _decode_backlog_ms(self) -> float:
        """Expected wait until a decode slot frees, credited to queued
        requests' SLO clocks when every slot is busy (the decode-side
        sibling of :meth:`_prefill_backlog_ms`).  Speculation makes
        this K-aware: a step emits ``_tps`` tokens per row on average,
        so the soonest slot frees after ``remaining / _tps`` steps —
        without the tokens-per-step term the scheduler would overstate
        backlog by the acceptance rate and jump requests early."""
        if self.spec is None or not self._decode_ms:
            return 0.0
        running = [r for r in self.sched.running]
        if not running or len(running) < self.sched.max_batch:
            return 0.0
        rem = min(r.max_new_tokens - len(r.tokens) for r in running)
        return (rem / max(self._tps, 1.0)) * self._decode_ms

    def _append_token(self, req: Request, tok: int) -> None:
        now = time.monotonic()
        req.tokens.append(tok)
        req.token_times.append(now)
        if req.first_token_t is None:
            req.first_token_t = now
            telemetry.histogram("serve.ttft_ms").observe(
                (now - req.submit_t) * 1e3)
        telemetry.counter("serve.tokens_total").inc()
        if req.eos_id is not None and tok == req.eos_id:
            self._finish(req, "eos")
        elif len(req.tokens) >= req.max_new_tokens:
            self._finish(req, "length")

    def _finish(self, req: Request, reason: str,
                state: str = FINISHED) -> None:
        self.sched.finish(req, reason, state)
        if req.blocks:
            # reference drop, not force-free: shared prefix blocks stay
            # for their co-owners, published blocks park in the LRU
            # cache for the next request with this prefix
            self.alloc.release(req.blocks, req.id)
            req.blocks = []
        self._release_ring(req)
        if req.prefix_blocks:
            # admission pinned a prefix but the request died before
            # _prefill_begin consumed it (deadline/cancel sweep)
            self.alloc.release(req.prefix_blocks, req.id)
            req.prefix_blocks = []
        telemetry.counter("serve.evictions").inc(reason=reason)

    # -- maintenance / introspection ---------------------------------------

    def defrag(self) -> int:
        """Compact live KV blocks to the low end of the pool (both
        pools move in lockstep, tables are rewritten).  Returns the
        number of relocated blocks; outputs are bitwise unaffected."""
        mapping = self.alloc.defrag()
        if mapping:
            # blocks and state slots alike sit on the pools' axis 1; a
            # described softmax model's window pools (the first pair,
            # under rings) are not compacted
            self._caches = tuple(
                pool if self.described_kv and i < 2
                else kvcache.compact_pool(pool, mapping)
                for i, pool in enumerate(self._caches))
            for req in self.sched.running:
                req.blocks = [mapping.get(b, b) for b in req.blocks]
            if self.prefix is not None:
                self.prefix.remap(mapping)
        return len(mapping)

    def check_tables(self) -> None:
        """Allocator/table integrity audit (raises on any violation)."""
        tables = {r.id: r.blocks for r in self.sched.running if r.blocks}
        if self.described_kv:
            self.alloc.check(tables, {r.id: r.ring for r in self.sched.running
                                      if r.ring})
        else:
            self.alloc.check(tables)

    def stats(self) -> Dict[str, Any]:
        return {
            "aot": dict(self.aot_stats),
            "traces": dict(self.trace_counts),
            "blocks_used": self.alloc.num_used,
            "blocks_free": self.alloc.num_free,
            "active": self.sched.active,
            "queued": self.sched.queue_depth,
            "steps": self.step_idx,
            "beat": self.beat,
            "weight_swaps": self.swap_count,
            "hung": self._hung,
            "chaos": bool(self.chaos),
            "prompt_buckets": list(self.prompt_buckets),
            "decode_buckets": list(self.decode_buckets),
            "prefill_chunk": self.prefill_chunk,
            "kv_quant": self.kv_quant,
            "attn_impl": self.attn_impl,
            "prefix": (None if self.prefix is None else {
                "entries": len(self.prefix),
                "version": self.prefix.version,
                "cached_blocks": self.alloc.num_cached,
                "hits": self._prefix_hits,
                "misses": self._prefix_misses,
                "hit_tokens": self._prefix_hit_tokens,
                "evictions": self._prefix_evictions,
                "hit_rate": (self._prefix_hits
                             / (self._prefix_hits + self._prefix_misses)
                             if self._prefix_hits + self._prefix_misses
                             else 0.0),
            }),
            "speculate": (None if self.spec is None else {
                "draft": self.spec.kind,
                "k": self.spec_k,
                "drafted": self._spec_drafted,
                "accepted": self._spec_accepted,
                "accept_rate": (self._spec_accepted / self._spec_drafted
                                if self._spec_drafted else 0.0),
                "tokens_per_step": self._tps,
                "draft_swaps": getattr(self.spec, "swap_count", 0),
            }),
        }
