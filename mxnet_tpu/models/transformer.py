"""Decoder-only transformer LM on the Symbol API.

The long-context flagship: attention runs through the ``RingAttention``
op, which turns into sequence-parallel ring attention whenever a mesh
with a ``seq`` axis is active (``mxnet_tpu.parallel.default_mesh``) —
the capability upgrade over the reference's bucketed-RNN story
(SURVEY §2.4/§7 item 10).

Seq len is baked per config because the 2016-era ``FullyConnected``
flattens trailing dims, so per-position projections go through explicit
``Reshape``s — the same static-unroll style as the reference's
``example/rnn/lstm.py``.  The batch dim is a ``-1`` wildcard
everywhere, so one symbol serves both the global-shape implicit-comm
path and the per-device shards of the explicit shard_map path.
"""
import contextlib

import jax
import jax.numpy as jnp

from .. import symbol as sym
from ..attribute import AttrScope
from ..base import MXNetError


def _linear(x, b, l, d_in, d_out, name, quant=""):
    """Per-position linear: [B, L, d_in] -> [B, L, d_out].  The batch
    dim stays a -1 wildcard so the same symbol evaluates on per-device
    shards inside the explicit-communication shard_map path (local
    batch = B/ndev)."""
    h = sym.Reshape(data=x, shape=(-1, d_in))
    h = sym.FullyConnected(data=h, num_hidden=d_out, name=name, quant=quant)
    return sym.Reshape(data=h, shape=(-1, l, d_out))


def _layernorm(x, name):
    return sym.LayerNorm(data=x, name=name)


def transformer_block(x, b, l, d, heads, name, causal=True,
                      attn_block_size=0, quant=""):
    hd = d // heads

    # heads stay at dim 2 ([B, L, H, hd] — the natural post-projection
    # layout): RingAttention(layout='blhd') consumes it directly.  The
    # graph carries no SwapAxis; the remaining head transposes live
    # inside the attention wrapper (the current Mosaic lowering cannot
    # slice per-head blocks out of an (H, d)-tiled ref, so real-TPU
    # runs still transpose to the [BH, L, D] kernel — the H-looped
    # native-layout kernels are written, interpret-verified, and switch
    # on when Mosaic supports them; see flash_attention.py)
    def split_heads(t):
        return sym.Reshape(data=t, shape=(-1, l, heads, hd))

    h = _layernorm(x, f"{name}_ln1")
    q = split_heads(_linear(h, b, l, d, d, f"{name}_q", quant=quant))
    k = split_heads(_linear(h, b, l, d, d, f"{name}_k", quant=quant))
    v = split_heads(_linear(h, b, l, d, d, f"{name}_v", quant=quant))
    att = sym.RingAttention(query=q, key=k, value=v, causal=causal,
                            block_size=attn_block_size, layout="blhd",
                            name=f"{name}_attn")
    att = sym.Reshape(data=att, shape=(-1, l, d))
    att = _linear(att, b, l, d, d, f"{name}_proj", quant=quant)
    x = x + att
    h = _layernorm(x, f"{name}_ln2")
    h = _linear(h, b, l, d, 4 * d, f"{name}_ffn1", quant=quant)
    h = sym.Activation(data=h, act_type="relu")
    h = _linear(h, b, l, 4 * d, d, f"{name}_ffn2", quant=quant)
    return x + h


def transformer_lm(vocab_size=256, num_layers=2, d_model=64, heads=4,
                   batch_size=8, seq_len=64, causal=True, remat=False,
                   head_same_dtype=False, loss_head=False,
                   attn_block_size=0, ignore_label=None, quant=None):
    """Build the LM symbol; inputs ``data``/``softmax_label`` are
    ``[batch, seq]`` token ids.  ``remat=True`` wraps each block in a
    ``remat_scope`` so backward recomputes the block from its boundary
    activations (jax.checkpoint over the subgraph) — the memory lever
    that fits 32k-token training on one chip.  ``head_same_dtype=True``
    emits the softmax head's probabilities in the activation dtype
    (bf16 under AMP — halves the [B*L, vocab] head-output HBM, the
    other 32k lever; loss math stays f32).  ``loss_head=True`` is the
    TRAINING head: the symbol's output is the per-token cross-entropy
    ([B*L], f32) and no [B*L, vocab] probability tensor is emitted at
    all — gradients are identical to the parity head (use the default
    probs head for eval/predict).  ``ignore_label`` masks positions
    whose label equals it out of the loss AND its gradient (×1.0 at
    every valid position, so masked and unmasked runs agree bitwise at
    valid positions) — the correctness mask for bucket-padded batches
    (compile_cache.BucketPolicy / io.pad_batch_to_bucket).
    ``quant`` routes the block projections (q/k/v/proj/ffn1/ffn2)
    through the block-scaled fp8 matmul path (mxnet_tpu.quant: e4m3
    fwd / e5m2 grad, f32 masters + accumulation); embed/lm_head stay
    full precision — the standard fp8 recipe.  None consults
    ``MXNET_TPU_QUANT``."""
    from .. import quant as _quant
    qcfg = _quant.resolve_quant(quant)
    qstr = "fp8" if qcfg is not None else ""
    b, l, d = batch_size, seq_len, d_model
    net = sym.Embedding(data=sym.Variable("data"), input_dim=vocab_size,
                        output_dim=d, name="embed")
    for i in range(num_layers):
        scope = (AttrScope(remat_scope=f"layer{i}") if remat
                 else contextlib.nullcontext())
        with scope:
            net = transformer_block(net, b, l, d, heads, f"layer{i}",
                                    causal=causal,
                                    attn_block_size=attn_block_size,
                                    quant=qstr)
    net = _layernorm(net, "final_ln")
    net = sym.Reshape(data=net, shape=(-1, d))
    net = sym.FullyConnected(data=net, num_hidden=vocab_size, name="lm_head")
    label = sym.Reshape(data=sym.Variable("softmax_label"), shape=(-1,))
    head_kwargs = {}
    if ignore_label is not None:
        head_kwargs = dict(use_ignore=True, ignore_label=ignore_label)
    return sym.SoftmaxOutput(data=net, label=label, name="softmax",
                             out_dtype="same" if head_same_dtype else "",
                             out_mode="loss" if loss_head else "",
                             **head_kwargs)


# ---------------------------------------------------------------------------
# Incremental decode: the stepwise-generation head the symbol above cannot
# express (its seq len is baked into every Reshape).  These are pure-JAX
# functional twins of the SAME graph — each op mirrors the registered
# symbol op exactly (FullyConnected flatten/cast/dot/bias, LayerNorm f32
# stats + rsqrt, Embedding take, dense RingAttention short-seq path), and
# they consume the symbol's OWN parameter dict (``layer{i}_q_weight``,
# ``final_ln_gamma``, ...) so trained checkpoints load unchanged.  The
# serving tier (mxnet_tpu/serve/) jits these behind compile_cache; they
# also work standalone with the dense cache helpers below.
# ---------------------------------------------------------------------------

from .decoder import (SOFTMAX, ModelSpec, _param, block,  # noqa: F401
                      decoder_forward, lm_config_from_params)
from .decoder import embed as _embed
from .decoder import lm_head as _head

# the in-tree description with ``heads=1``: the two helpers below hand
# ``attend`` the flat [..., d] states, and their callers split the heads
_FLAT = ModelSpec(heads=1)


def _block_step(params, i, h, attend):
    """One in-tree transformer block on hidden states ``h`` ([..., d])
    where ``attend(q, k, v)`` maps the flat projected states [..., d]
    to the attention output of the same shape (the caller owns the KV
    story).  :func:`~mxnet_tpu.models.decoder.block` under the in-tree
    description."""
    lead, d = h.shape[:-1], h.shape[-1]

    def flat(q, k, v, _gate):
        out = attend(*(t.reshape(lead + (d,)) for t in (q, k, v)))
        return out.reshape(lead + (1, d))

    return block(_FLAT, params, i, SOFTMAX, h, None, flat)


def _lm_head(params, h):
    return _head(_FLAT, params, h)


def _windowed(params, tokens, heads, attend):
    """The in-tree LM over a caller-owned cache: ``attend(layer, q, k,
    v)`` on per-head states, as the three entry points below document."""
    return decoder_forward(
        ModelSpec(heads=heads), params, tokens, None,
        lambda i, _kind, q, k, v, _gate: attend(i, q, k, v))


def transformer_lm_prefill(params, tokens, *, heads):
    """Causal forward over full prompts, emitting the KV states.

    ``tokens``: [B, L] ids.  Returns ``(logits [B, L, V], ks, vs)``
    where ``ks``/``vs`` are per-layer [B, L, H, hd] states — exactly
    what a cache (dense or paged) stores.  Attention runs the dense
    short-sequence path the RingAttention op uses below
    ``AUTO_SWITCH_LEN``, so logits match the symbol's teacher-forced
    forward at the same [B, L] shape.
    """
    from ..parallel.ring_attention import local_attention
    vocab, num_layers, d = lm_config_from_params(params)
    if d % heads:
        raise MXNetError(f"d_model {d} not divisible by heads {heads}")
    hd = d // heads
    b, l = tokens.shape
    h = _embed(params, tokens)
    ks, vs = [], []

    def attend(q, k, v):
        q, k, v = (t.reshape(b, l, heads, hd) for t in (q, k, v))
        ks.append(k)
        vs.append(v)
        with jax.named_scope("attn"):
            qt, kt, vt = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
            out = local_attention(qt, kt, vt, causal=True, block_size=None)
            return out.transpose(0, 2, 1, 3).reshape(b, l, d)

    for i in range(num_layers):
        h = _block_step(params, i, h, attend)
    return _lm_head(params, h), ks, vs


def transformer_lm_prefill_chunk(params, tokens, *, heads, attend):
    """One **chunk** of a prompt's prefill over a caller-owned KV cache.

    The chunked twin of :func:`transformer_lm_prefill`: ``tokens`` is a
    [B, C] slice of the prompt (C = the serve tier's chunk budget) and
    ``attend(layer, q, k, v)`` receives the chunk's per-head states
    ([B, C, H, hd] each), must extend the caller's cache with
    ``k``/``v`` and return each chunk position's causal attention over
    the full cached prefix (earlier chunks included) as [B, C, H, hd].
    Returns logits [B, C, V].

    There is no positional embedding in this architecture — position
    enters only through the attention mask — so the chunk's absolute
    offset is entirely the attend closure's business (the serve tier
    passes it to ``serve.kvcache.paged_prefill_attention``).
    """
    return _windowed(params, tokens, heads, attend)


def transformer_lm_verify(params, tokens, *, heads, attend):
    """Speculative-decode **verify** window: score C candidate
    positions per request in one forward over a caller-owned KV cache.

    The K-position extension of :func:`transformer_lm_decode`:
    ``tokens`` is [B, C] — per request, position 0 is the current last
    token and 1..C-1 a drafted continuation — and
    ``attend(layer, q, k, v)`` receives the window's per-head states
    ([B, C, H, hd] each), must extend the caller's cache with
    ``k``/``v`` and return each window position's causal attention over
    the full cached prefix (the window's earlier positions included) as
    [B, C, H, hd].  Returns logits [B, C, V]: row ``c`` scores the
    token *following* drafted position ``c`` — exactly what acceptance
    needs.  A window of C=1 is the decode twin; no positional
    embedding exists in this architecture, so absolute offsets are the
    attend closure's business (the serve tier passes them to
    ``serve.kvcache.paged_verify_attention``).
    """
    return _windowed(params, tokens, heads, attend)


def transformer_lm_decode(params, tokens, *, heads, attend):
    """One incremental decode step over a caller-owned KV cache.

    ``tokens``: [B] ids of the tokens being processed this step.
    ``attend(layer, q, k, v)`` receives the new per-head states
    ([B, H, hd] each), must extend the caller's cache with ``k``/``v``
    and return ``q``'s attention over the full cached prefix (including
    the new position) as [B, H, hd].  Returns next-token logits [B, V].

    The serve tier passes a paged-cache closure
    (``serve.kvcache.paged_attention``); :func:`transformer_lm_decode_dense`
    below is the self-contained dense-cache form.
    """
    return _windowed(params, tokens, heads, attend)


def transformer_lm_decode_dense(params, tokens, lengths, k_cache, v_cache,
                                *, heads):
    """Dense-cache decode step: consumes and extends preallocated
    [num_layers, B, L_max, H, hd] K/V caches.

    ``tokens``: [B] ids; ``lengths``: [B] entries already cached (the
    new token is written at position ``lengths``).  Returns
    ``(logits [B, V], k_cache', v_cache')``.  Attention is the same f32
    masked softmax as the dense attention path, masked to
    ``lengths + 1`` valid positions per row.
    """
    b = tokens.shape[0]
    rows = jnp.arange(b)
    cache = [k_cache, v_cache]
    d = _param(params, "embed_weight").shape[1]
    scale = 1.0 / jnp.sqrt(jnp.float32(d // heads))

    def attend(i, q, k, v):
        cache[0] = cache[0].at[i, rows, lengths].set(k)
        cache[1] = cache[1].at[i, rows, lengths].set(v)
        kc, vc = cache[0][i], cache[1][i]
        s = (jnp.einsum("bhd,blhd->bhl", q, kc) * scale).astype(jnp.float32)
        valid = jnp.arange(kc.shape[1])[None, :] < (lengths + 1)[:, None]
        s = jnp.where(valid[:, None, :], s, -1e30)
        probs = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhl,blhd->bhd", probs, vc)

    logits = transformer_lm_decode(params, tokens, heads=heads,
                                   attend=attend)
    return logits, cache[0], cache[1]
