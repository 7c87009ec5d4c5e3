"""HC-mix: a Pallas kernel for the maps of one hyper-connection
(``ModelSpec.hc_mult``; the equations are ``models/decoder.py``'s
:func:`~mxnet_tpu.models.decoder.hc_maps`, the plain form).

A connection reads the ``n`` residual streams of a position once and
makes, in float32: the RMS scale of ``vec(X)``, the ``[2n + n^2, n d]``
projection, the sigmoids of ``H_pre`` and ``H_post``, the clamp and
``exp`` of ``H_res``'s logits and all ``hc_sinkhorn_iters`` row and
column normalisations, and the sublayer's input ``u = sum_j H_pre[j]
X[j]``.  Composed by XLA, each Sinkhorn iteration is a few fusions of
its own (78 for one connection's 20 at the published widths): a chain of
tiny programs whose launch latency is the same at any batch.  The
kernel, ``mxtpu_hc_mix``, makes it one call:

* grid over row tiles of ``_ROWS`` (the decode program's rows and the
  chunk program's positions take one path); a tile's ``[rows, n d]``
  streams in, ``u`` ``[rows, d]`` out, and the maps out TRANSPOSED,
  ``[2n + n^2, rows]``: H_pre, H_post and H_res's rows, one row of
  positions each;
* the projection on the MXU against Phi ``[2n + n^2, n d]`` as stored,
  a stream's ``d`` columns at a time: bfloat16 streams are exact in
  bfloat16, and Phi is split into three bfloat16 pieces whose sum is
  its float32 value, so three single-pass products accumulated in
  float32 give the float32 projection; float32 streams multiply at
  highest precision;
* the maps' logits are turned once (``[rows, 2n + n^2]`` ->
  ``[2n + n^2, rows]``), so that the Sinkhorn iterations run on
  ``[n, rows]`` slabs with the positions along the lanes: a row sum is a
  sum over sublanes, a column sum a sum of the ``n`` slabs, a few vector
  operations an iteration whatever the rows;
* the depth update (``H_res X + H_post y``) stays XLA's and fuses into
  its neighbours.

``interpret=True`` runs the same body on the Pallas interpreter (the CPU
tests' twin, ``attn_impl="flash_interpret"``).
"""
from __future__ import annotations

import math
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["hc_mix"]

#: positions a grid step maps (the maps leave transposed: a lane tile)
_ROWS = 128
#: VMEM the kernel may use: a tile's streams double buffered (3.7 MB at
#: 4 x 3584 in bf16), Phi (1.4 MB, double buffered) and float32 temporaries
_VMEM_LIMIT = 48 * 1024 * 1024


def _pieces(w):
    """Three bfloat16 arrays whose float32 sum is ``w`` (float32)."""
    hi = w.astype(jnp.bfloat16)
    rest = w - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    lo = (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)
    return hi, mid, lo


def _hc_kernel(x_ref, phi_ref, vec_ref, u_ref, maps_ref, *, n: int, d: int,
               iters: int, eps: np.float32, clamp: np.float32,
               norm_eps: np.float32):
    """One tile of positions: ``x_ref`` [rows, n d] streams, ``phi_ref``
    [m, n d], ``vec_ref`` [2, m] (the bias, then each map's scale a
    lane) -> ``u_ref`` [rows, d], ``maps_ref`` [m, rows]."""
    f32 = jnp.float32
    rows = x_ref.shape[0]
    m = 2 * n + n * n
    exact = x_ref.dtype == jnp.bfloat16
    nt = (((1,), (1,)), ((), ()))
    h = jnp.zeros((rows, m), f32)
    ss = jnp.zeros((rows, 1), f32)
    for j in range(n):
        xj = x_ref[:, j * d:(j + 1) * d]
        phi = phi_ref[:, j * d:(j + 1) * d]
        if exact:
            for piece in _pieces(phi):
                h = h + jax.lax.dot_general(xj, piece, nt,
                                            preferred_element_type=f32)
        else:
            h = h + jax.lax.dot_general(
                xj.astype(f32), phi, nt, preferred_element_type=f32,
                precision=jax.lax.Precision.HIGHEST)
        x32 = xj.astype(f32)
        ss = ss + jnp.sum(x32 * x32, axis=1, keepdims=True)
    z = (h * jax.lax.rsqrt(ss * np.float32(1.0 / (n * d)) + norm_eps)
         * vec_ref[1:2, :] + vec_ref[0:1, :])
    pre = jax.nn.sigmoid(z)                  # its first n lanes are H_pre
    u = pre[:, 0:1] * x_ref[:, 0:d].astype(f32)
    for j in range(1, n):
        u = u + pre[:, j:j + 1] * x_ref[:, j * d:(j + 1) * d].astype(f32)
    u_ref[...] = u.astype(u_ref.dtype)
    # the positions along the lanes from here on
    zt = z.T                                                 # [m, rows]
    gates = jax.nn.sigmoid(zt[:2 * n])
    # H_res's row i: an [n, rows] slab (its columns along the sublanes)
    slabs = [jnp.exp(jnp.clip(zt[2 * n + n * i:2 * n + n * (i + 1)],
                              -clamp, clamp)) for i in range(n)]
    for _ in range(iters):
        slabs = [a / (jnp.sum(a, axis=0, keepdims=True) + eps)
                 for a in slabs]
        col = slabs[0]
        for a in slabs[1:]:
            col = col + a
        slabs = [a / (col + eps) for a in slabs]
    maps_ref[...] = jnp.concatenate(
        [gates[:n], np.float32(2.0) * gates[n:]] + slabs, axis=0)


def hc_mix(spec, x, weight, bias, scale, *, interpret: bool = False):
    """:func:`~mxnet_tpu.models.decoder.hc_maps` as one kernel call:
    ``x`` [..., n, d] streams, ``weight`` Phi [2n + n^2, n d], ``bias``
    [2n + n^2], ``scale`` [3] -> ``(u [..., d] in x's type, H_pre [...,
    n], H_post [..., n], H_res [..., n, n])``, the maps float32."""
    n = int(spec.hc_mult)
    lead, d = x.shape[:-2], x.shape[-1]
    t = math.prod(lead)
    f32 = jnp.float32
    a = scale.astype(f32)
    alpha = jnp.concatenate([jnp.broadcast_to(a[0], (n,)),
                             jnp.broadcast_to(a[1], (n,)),
                             jnp.broadcast_to(a[2], (n * n,))])
    vec = jnp.stack([bias.astype(f32), alpha])
    u, maps = _hc_mix(x.reshape(t, n * d), weight.astype(f32), vec, n=n,
                      iters=int(spec.hc_sinkhorn_iters),
                      eps=np.float32(spec.hc_eps),
                      clamp=np.float32(spec.hc_clamp),
                      norm_eps=np.float32(spec.norm_eps), interpret=interpret)
    maps = maps.T                                            # [t, m]
    return (u.reshape(lead + (d,)), maps[:, :n].reshape(lead + (n,)),
            maps[:, n:2 * n].reshape(lead + (n,)),
            maps[:, 2 * n:].reshape(lead + (n, n)))


@partial(jax.jit, static_argnames=("n", "iters", "eps", "clamp", "norm_eps",
                                   "interpret"))
def _hc_mix(x, weight, vec, *, n: int, iters: int, eps, clamp, norm_eps,
            interpret: bool):
    """The kernel's call on ``x`` [t, n d]: its own ``jit``, so that a
    program of many connections traces and lowers it once a shape."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t, nd = x.shape
    d, m = nd // n, weight.shape[0]
    rows = _ROWS if t >= _ROWS else -(-t // 8) * 8
    padded = -(-t // rows) * rows
    if padded != t:
        x = jnp.pad(x, ((0, padded - t), (0, 0)))
    kernel = partial(_hc_kernel, n=n, d=d, iters=iters, eps=eps, clamp=clamp,
                     norm_eps=norm_eps)
    with jax.enable_x64(False):
        u, maps = pl.pallas_call(
            kernel,
            name="mxtpu_hc_mix",
            grid=(padded // rows,),
            in_specs=[pl.BlockSpec((rows, nd), lambda i: (i, 0)),
                      pl.BlockSpec((m, nd), lambda i: (0, 0)),
                      pl.BlockSpec((2, m), lambda i: (0, 0))],
            out_specs=[pl.BlockSpec((rows, d), lambda i: (i, 0)),
                       pl.BlockSpec((m, rows), lambda i: (0, i))],
            out_shape=[jax.ShapeDtypeStruct((padded, d), x.dtype),
                       jax.ShapeDtypeStruct((m, padded), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
        )(x, weight, vec)
    return u[:t], maps[:, :t]
