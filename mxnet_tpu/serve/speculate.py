"""Draft sources for speculative decoding (docs/serving.md).

Speculative decoding breaks the one-token-per-step wall: a cheap
**drafter** proposes K continuation tokens per request, the target
model scores all K in ONE batched verify program
(:func:`~mxnet_tpu.models.transformer.transformer_lm_verify` over the
paged cache), and a replay-exact acceptance rule keeps the emitted
stream byte-identical (greedy) or distribution-identical (temperature)
to the non-speculative engine.  The drafter is pure *proposal*
machinery — a wrong draft costs wasted verify width, never wrong
output — so drafters are free to be fast and dumb.

Two sources behind one interface:

* :class:`NGramDrafter` — **prompt-lookup / n-gram** drafting: propose
  the continuation that followed the longest matching suffix of the
  request's own context (prompt + generated tokens).  Zero device
  cost, zero weights, and devastatingly effective on templated or
  repetitive traffic (copy-heavy prompts, cycling generations).
* :class:`ModelDrafter` — a **small transformer_lm** draft model.  Its
  weights are per-replica *operands* (never baked into programs), so a
  new draft model deploys independently of the target via
  ``Router.rolling_swap(..., target="draft")`` with zero retraces.
  The engine runs the drafter's K-step greedy unroll as one AOT
  program over a fixed right-aligned context window
  (:func:`draft_window_logits` is the single-step forward it unrolls).

Drafts feed the engine's verify step; nothing in this module touches
the KV pools or the sampling PRNG chain.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from ..models.transformer import (_block_step, _lm_head, _param,
                                  lm_config_from_params)
from ..parallel.flash_attention import NEG_INF
from .kvcache import softmax_scale

__all__ = ["Drafter", "NGramDrafter", "ModelDrafter", "make_drafter",
           "DRAFT_KINDS", "draft_window_logits"]

#: recognized MXNET_TPU_SERVE_SPEC_DRAFT values
DRAFT_KINDS = ("ngram", "model")


class Drafter:
    """Interface every draft source implements.

    ``kind`` names the source ("ngram" / "model").  ``propose`` maps N
    request contexts (prompt + generated tokens, as python int lists)
    to an ``[N, k]`` int array of drafted continuations — deterministic
    in the contexts, because replay-exactness of the *temperature* path
    relies on preemption/failover re-runs reproposing identical drafts.
    Host drafters implement it directly; device drafters run through a
    runner the engine binds (one AOT program per decode bucket).
    """

    kind: str = "?"

    def propose(self, contexts: Sequence[Sequence[int]],
                k: int) -> np.ndarray:
        raise NotImplementedError

    def swap(self, params: Dict[str, Any]) -> Dict[str, Any]:
        raise MXNetError(
            f"{self.kind!r} drafter has no weights to swap — only the "
            "'model' drafter deploys through rolling_swap(target='draft')")

    def signature(self) -> str:
        """Geometry string folded into the engine fingerprint (program
        shapes depend on it for device drafters)."""
        return self.kind


class NGramDrafter(Drafter):
    """Prompt-lookup drafting: longest-suffix n-gram match over the
    request's own context.

    For n from ``max_n`` down to 1, find the most recent earlier
    occurrence of the context's length-n suffix and propose the tokens
    that followed it.  A match at distance ``p`` back implies the
    stream is locally period-p, so a continuation that runs off the
    end of the context extends CYCLICALLY (``ctx[-p + (i % p)]``) —
    the continuation-following-the-match and the periodic extension
    agree wherever both are defined, and a length-2 cycle drafts all k
    tokens right instead of stuttering on its last element.  No match
    at any n falls back to repeating the last token (the period-1
    guess — free, and exactly right for degenerate constant streams).
    Pure host-side: no device program, no weights, nothing to warm.
    """

    kind = "ngram"

    def __init__(self, max_n: int = 3):
        if max_n < 1:
            raise MXNetError(f"NGramDrafter max_n must be >= 1, got {max_n}")
        self.max_n = int(max_n)

    def _draft_one(self, ctx: Sequence[int], k: int) -> List[int]:
        ctx = list(ctx)
        m = len(ctx)
        for n in range(min(self.max_n, m - 1), 0, -1):
            suffix = ctx[-n:]
            # most recent earlier occurrence of the suffix
            for j in range(m - n - 1, -1, -1):
                if ctx[j:j + n] == suffix:
                    # ctx[j+n+i] == ctx[m-p+i] for i < p; extend with
                    # period p past the context's end
                    p = m - n - j
                    return [ctx[m - p + (i % p)] for i in range(k)]
        return [ctx[-1]] * k

    def propose(self, contexts: Sequence[Sequence[int]],
                k: int) -> np.ndarray:
        return np.asarray([self._draft_one(c, k) for c in contexts],
                          np.int32)


def draft_window_logits(params, tokens, ctx_len, *, heads):
    """Last-position logits of a small transformer_lm over a
    right-aligned context window — the single forward the engine's
    draft program unrolls K times.

    ``tokens``: [B, W] ids, right-aligned (left entries are padding
    when the context is shorter than W); ``ctx_len``: [B] valid tokens
    per row (>= 1).  Padding is masked out of attention (a left pad is
    never a valid key), so the result equals the forward over the
    unpadded context.  Returns [B, V] logits for the token following
    position W-1 — always the row's latest real token, because the
    window is right-aligned.
    """
    vocab, num_layers, d = lm_config_from_params(params)
    if d % heads:
        raise MXNetError(f"draft d_model {d} not divisible by heads {heads}")
    hd = d // heads
    b, w = tokens.shape
    f32 = jnp.float32
    scale = softmax_scale(hd)
    idx = jnp.arange(w)
    # key j of row b is valid iff it is inside the context window and
    # causally visible: j >= W - ctx_len[b] and j <= query position
    valid_k = idx[None, :] >= (w - ctx_len)[:, None]           # [B, W]
    causal = idx[:, None] >= idx[None, :]                      # [Wq, Wk]
    mask = valid_k[:, None, None, :] & causal[None, None, :, :]
    h = jnp.take(_param(params, "embed_weight"),
                 tokens.astype(jnp.int32), axis=0)

    def attend(q, k, v):
        q, k, v = (t.reshape(b, w, heads, hd) for t in (q, k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(f32) * scale
        s = jnp.where(mask, s, NEG_INF)
        m = jnp.max(s, axis=-1)
        p = jnp.where(mask, jnp.exp(s - m[..., None]), 0.0)
        l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
        out = jnp.einsum("bhqk,bkhd->bqhd", p / l[..., None],
                         v.astype(f32)).astype(q.dtype)
        return out.reshape(b, w, d)

    for i in range(num_layers):
        h = _block_step(params, i, h, attend)
    return _lm_head(params, h)[:, -1]


class ModelDrafter(Drafter):
    """A small ``transformer_lm`` as the draft source.

    Holds its own parameter dict + heads; the engine compiles the
    K-step greedy unroll of :func:`draft_window_logits` as one AOT
    program per decode bucket and binds it here (``bind_runner``).
    Draft weights are program *operands*: :meth:`swap` installs a
    signature-compatible replacement with zero retraces — the draft
    half of the round-13 deploy story, reachable through
    ``Engine.swap_draft_weights`` / ``Router.rolling_swap(...,
    target="draft")``.  Drafting is always greedy: drafts are
    proposals, and the verify step's acceptance rule owns the output
    distribution.
    """

    kind = "model"

    def __init__(self, params: Dict[str, Any], *, heads: int,
                 window: int = 16):
        if window < 1:
            raise MXNetError(f"ModelDrafter window must be >= 1, "
                             f"got {window}")
        self.params = {k: jnp.asarray(
            v.asnumpy() if hasattr(v, "asnumpy") else v)
            for k, v in params.items()}
        self.heads = int(heads)
        self.window = int(window)
        self.vocab, self.num_layers, self.d_model = (
            lm_config_from_params(self.params))
        if self.d_model % self.heads:
            raise MXNetError(f"draft d_model {self.d_model} not divisible "
                             f"by heads {self.heads}")
        self.swap_count = 0
        self._runner = None     # engine-bound: (window, ctx_len) -> [N, k]

    def signature(self) -> str:
        return (f"model:{self.vocab}:{self.num_layers}:{self.d_model}:"
                f"{self.heads}:w{self.window}")

    def bind_runner(self, runner) -> None:
        self._runner = runner

    def windows(self, contexts: Sequence[Sequence[int]]):
        """Right-align each context into a [N, W] window + [N] valid
        lengths (the draft program's operands)."""
        w = self.window
        out = np.zeros((len(contexts), w), np.int32)
        lens = np.zeros((len(contexts),), np.int32)
        for i, ctx in enumerate(contexts):
            tail = list(ctx)[-w:]
            out[i, w - len(tail):] = tail
            lens[i] = len(tail)
        return out, lens

    def propose(self, contexts: Sequence[Sequence[int]],
                k: int) -> np.ndarray:
        if self._runner is None:
            raise MXNetError("ModelDrafter has no bound draft program — "
                             "construct the engine with draft_params and "
                             "run warmup()")
        win, lens = self.windows(contexts)
        return np.asarray(self._runner(win, lens), np.int32)

    def swap(self, params: Dict[str, Any]) -> Dict[str, Any]:
        """Install new draft weights (compat-checked: the draft program
        was compiled against the current signature, so shape/dtype/key
        deltas must rebuild instead)."""
        from ..online.compat import check_compat, signature_of_params
        new = {k: jnp.asarray(
            v.asnumpy() if hasattr(v, "asnumpy") else v)
            for k, v in params.items()}
        report = check_compat(signature_of_params(self.params),
                              signature_of_params(new))
        if not report.compatible:
            raise MXNetError(
                "swap (draft): incompatible draft weights — "
                f"{report.summary()}; rebuild the replica instead")
        self.params = new
        self.swap_count += 1
        return report.to_dict()


def make_drafter(kind: str, *, draft_params: Optional[Dict[str, Any]] = None,
                 draft_heads: Optional[int] = None,
                 window: int = 16, max_n: int = 3) -> Drafter:
    """Build a drafter from config ("ngram" | "model")."""
    kind = (kind or "ngram").strip().lower()
    if kind == "ngram":
        return NGramDrafter(max_n=max_n)
    if kind == "model":
        if draft_params is None:
            raise MXNetError(
                "spec_draft='model' needs draft_params (a transformer_lm "
                "parameter dict for the draft model)")
        if draft_heads is None:
            raise MXNetError("spec_draft='model' needs draft_heads")
        return ModelDrafter(draft_params, heads=int(draft_heads),
                            window=window)
    raise MXNetError(f"unknown spec_draft {kind!r}, expected one of "
                     f"{DRAFT_KINDS}")
