"""Power retention: linear attention with a degree-2 kernel and a
learned forget gate (arXiv:2507.04239), in the three forms that must
agree.

For query head ``i`` reading key/value head ``i // group``, log-gate
``g_t <= 0`` per key/value head and ``G_t = sum_{s<=t} g_s``:

* **attention form** — ``a_ts = exp(G_t - G_s) (q_t . k_s)^2 / hd`` for
  ``s <= t``, ``y_t = sum_s a_ts v_s / (sum_s a_ts + eps)``
  (:func:`attention_form`; the plain reference of the benchmark writes
  the same thing on its own).
* **recurrent form** — ``S_t = exp(g_t) S_{t-1} + phi(k_t) v_t^T``,
  ``z_t = exp(g_t) z_{t-1} + phi(k_t)``,
  ``y_t = phi(q_t)^T S_t / (phi(q_t)^T z_t + eps)`` with
  ``phi(a) . phi(b) = (a . b)^2 / hd`` (:func:`recurrent_step`; decode).
* **chunked form** — the attention form inside a chunk, ``phi(q)^T
  S_prev`` scaled by ``exp(G_t)`` across chunks, then ``S <- exp(G_C) S
  + sum_s exp(G_C - G_s) phi(k_s) v_s^T`` (:func:`chunk_form`; prefill).

**The state's layout.**  ``phi`` is the symmetric embedding of ``a a^T``:
``hd (hd + 1) / 2`` products, the off-diagonal ones weighted ``sqrt 2``.
They are stored as ``hd / 2 + 1`` chunks of ``hd`` lanes, folding the
triangle into a rectangle: chunk ``i`` holds row ``i`` of the triangle
(``a_i a_l``, ``l >= i``) in lanes ``i..hd-1`` and row ``hd - i``
(``a_{hd-i} a_{hd-i+l}``) in lanes ``0..i-1``; the last chunk is half
empty (8256 products in 65 x 128 = 8320 lanes for ``hd`` 128).  Every
chunk is a lane-rotation of ``a`` times a scalar of ``a``, so no gather
is needed.  ``S`` is kept transposed with ``z`` as one more value
channel: a request's state for one key/value head is
``[hd/2 + 1, rows, hd]`` float32 where row ``c < hd`` is value channel
``c`` of ``S``, row ``hd`` is ``z`` (the value ``1`` appended to ``v``),
and the rows up to the next multiple of 8 are zero.  The feature index
lies on the lanes, the value channel on the sublanes: the update is a
broadcast multiply-add and a query reads it with one more.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["state_shape", "phi", "augment_values", "attention_form",
           "recurrent_step", "chunk_form"]

_HI = jax.lax.Precision.HIGHEST
_NEG = np.float32(-1e30)


def state_shape(head_dim: int):
    """``(chunks, rows, lanes)`` of one key/value head's state."""
    return (head_dim // 2 + 1, -(-(head_dim + 1) // 8) * 8, head_dim)


def _phi_tables(hd: int):
    nch = hd // 2 + 1
    i = np.arange(nch)[:, None]
    l = np.arange(hd)[None, :]
    first = l >= i                                  # row i, columns l >= i
    diag = np.where(first, l == i, l == 0)
    coef = np.where(diag, 1.0, np.sqrt(2.0)) / np.sqrt(hd)
    coef = np.where((i == hd // 2) & ~first, 0.0, coef)   # the empty half
    return first, coef.astype(np.float32)


def phi(x):
    """The folded symmetric embedding: ``x`` [..., hd] -> [..., hd/2+1,
    hd] float32 with ``sum(phi(a) * phi(b)) == (a . b)^2 / hd``."""
    hd = x.shape[-1]
    nch = hd // 2 + 1
    first, coef = _phi_tables(hd)
    x = x.astype(jnp.float32)
    # rolled[i, l] = x[(l - i) % hd]
    rolled = jnp.stack([jnp.roll(x, i, axis=-1) for i in range(nch)], -2)
    a = x[..., :nch, None]                                    # x_i
    b = jnp.concatenate([x[..., :1], jnp.flip(x[..., hd - nch + 1:], -1)],
                        -1)[..., None]                        # x_{hd-i}
    return jnp.where(first, a * x[..., None, :], b * rolled) * coef


def augment_values(v):
    """``v`` [..., hd] -> [..., rows] float32: the values, a ``1`` (the
    channel that accumulates ``z``), zeros up to a multiple of 8."""
    hd = v.shape[-1]
    rows = state_shape(hd)[1]
    pad = jnp.zeros(v.shape[:-1] + (rows - hd - 1,), jnp.float32)
    return jnp.concatenate(
        [v.astype(jnp.float32), jnp.ones(v.shape[:-1] + (1,), jnp.float32),
         pad], -1)


def _grouped(q, kv_heads: int):
    """[T, H, hd] -> [T, KV, group, hd]."""
    t, h, hd = q.shape
    return q.reshape(t, kv_heads, h // kv_heads, hd)


def _intra(q, k, g_cum, valid):
    """The attention form's weights inside one stretch of positions:
    ``a`` [KV, group, T, T] float32, zero above the diagonal and at
    keys that are not ``valid``."""
    t, kv, hd = k.shape
    s = jnp.einsum("tkgd,skd->kgts", _grouped(q, kv), k, precision=_HI,
                   preferred_element_type=jnp.float32)
    s = jnp.square(s) * np.float32(1.0 / hd)
    gk = g_cum.T                                              # [KV, T]
    pos = jnp.arange(t)
    mask = (pos[None, :] <= pos[:, None]) & valid[None, :]    # [t, s]
    decay = jnp.exp(jnp.where(mask[None], gk[:, :, None] - gk[:, None, :],
                              _NEG))
    return s * decay[:, None]


def attention_form(q, k, v, g, eps: float):
    """``q`` [T, H, hd], ``k``/``v`` [T, KV, hd], log-gates ``g``
    [T, KV] -> ``y`` [T, H, hd] float32.  O(T^2), no state."""
    t, h, hd = q.shape
    a = _intra(q, k, jnp.cumsum(g.astype(jnp.float32), 0),
               jnp.ones((t,), bool))
    num = jnp.einsum("kgts,skd->tkgd", a, v.astype(jnp.float32),
                     precision=_HI)
    den = jnp.sum(a, axis=-1).transpose(2, 0, 1)[..., None]   # [T, KV, g, 1]
    return (num / (den + np.float32(eps))).reshape(t, h, hd)


def _read(out, hd: int, eps: float):
    """``[..., rows]`` numerators with the normaliser in channel ``hd``
    -> ``[..., hd]``."""
    return out[..., :hd] / (out[..., hd:hd + 1] + np.float32(eps))


def recurrent_step(state, q, k, v, g, eps: float):
    """One position for each of B rows: ``state`` [B, KV, chunks, rows,
    hd], ``q`` [B, H, hd], ``k``/``v`` [B, KV, hd], ``g`` [B, KV] ->
    ``(y [B, H, hd] float32, new state)``."""
    b, h, hd = q.shape
    kv = k.shape[1]
    gam = jnp.exp(g.astype(jnp.float32))
    new = (gam[:, :, None, None, None] * state
           + augment_values(v)[:, :, None, :, None]
           * phi(k)[:, :, :, None, :])
    pq = phi(q).reshape((b, kv, h // kv) + new.shape[2:3] + (hd,))
    out = jnp.einsum("bkgil,bkicl->bkgc", pq, new, precision=_HI)
    return _read(out, hd, eps).reshape(b, h, hd), new


def chunk_form(state, q, k, v, g, n_valid, eps: float):
    """A stretch of T positions of ONE request, of which the first
    ``n_valid`` are real: ``state`` [KV, chunks, rows, hd], ``q``
    [T, H, hd], ``k``/``v`` [T, KV, hd], ``g`` [T, KV] -> ``(y [T, H,
    hd] float32, new state)``.  Positions past ``n_valid`` neither decay
    the state nor enter it (their ``y`` is finite and unused)."""
    t, h, hd = q.shape
    kv = k.shape[1]
    valid = jnp.arange(t) < n_valid
    g_cum = jnp.cumsum(jnp.where(valid[:, None], g.astype(jnp.float32),
                                 np.float32(0.0)), 0)             # [T, KV]
    vaug = augment_values(v)                                      # [T,KV,rows]
    a = _intra(q, k, g_cum, valid)
    out = jnp.einsum("kgts,skc->tkgc", a, vaug, precision=_HI)
    pq = phi(q).reshape((t, kv, h // kv) + state.shape[1:2] + (hd,))
    cross = jnp.einsum("tkgil,kicl->tkgc", pq, state, precision=_HI)
    out = out + cross * jnp.exp(g_cum)[:, :, None, None]
    w = jnp.exp(g_cum[-1][None] - g_cum) * valid[:, None]         # [T, KV]
    new = (jnp.exp(g_cum[-1])[:, None, None, None] * state
           + jnp.einsum("tkc,tkil->kicl", vaug * w[..., None], phi(k),
                        precision=_HI))
    return _read(out, hd, eps).reshape(t, h, hd), new
