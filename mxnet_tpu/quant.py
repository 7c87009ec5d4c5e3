"""Low-precision compute policy: block-scaled fp8 matmul paths.

The r9 quantization stack has three layers (docs/perf.md "r9"); this
module is the *compute* layer.  ``QuantConfig`` is the policy object —
which fp8 wire formats to use for forward activations/weights (e4m3:
4-bit exponent, more mantissa) and for gradients (e5m2: wider dynamic
range, the backward signal spans more octaves) — and ``fp8_linear`` is
the op: a ``custom_vjp`` matmul whose operands are quantized per
*block* of the contraction dimension, so one outlier poisons 128
elements rather than a whole tensor row.

Scaling layout (DeepSeek-V3-style fine-grained blocks, not per-tensor
delayed scaling): for ``a @ b.T`` with ``a:[M,K]``, ``b:[N,K]``, both
operands are split into ``K/B`` blocks along the contraction axis; each
(row, block) gets its own f32 scale.  The dot then runs per block on
the fp8 payloads with ``preferred_element_type=f32`` (fp8 inputs, f32
accumulation — the MXU-native contract) and the partial products are
rescaled and summed in f32:

    out[m,n] = sum_kb  sa[kb,m] * sb[kb,n] * dot(qa[kb,m,:], qb[kb,n,:])

Because scales ride the *non-contracted* coordinates of each partial
dot they factor out exactly; no scale ever multiplies inside the fp8
contraction.  Master weights stay f32 in the (already sharded)
optimizer state — quantization happens in-graph on the forward/backward
edges, and the optimizer update consumes f32 masters unchanged.

Backends without an fp8 dot lowering (older CPU jaxlibs) fall back to
running the contraction on the fp8 values upcast to f32 — numerically
identical (every fp8 value is exact in f32; accumulation is f32 either
way), only the operand width in the dot differs.  The quantization
itself (the lossy part) always happens.

Env knobs (docs/env_vars.md "Low-precision quantization"):

- ``MXNET_TPU_QUANT``        — default for ``transformer_lm(quant=)``
- ``MXNET_TPU_QUANT_BLOCK``  — contraction block size (default 128)
- ``MXNET_TPU_QUANT_EF``     — error-feedback default for lossy
                               gradient compression (collectives layer)
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .base import MXNetError

__all__ = [
    "QuantConfig", "resolve_quant", "block_quantize", "rowwise_quantize",
    "fp8_dot", "fp8_linear", "FP8_MAX", "WIRE_ITEMSIZE", "wire_itemsize",
    "error_feedback_default", "symbol_uses_fp8",
]

# Largest finite magnitude representable in each fp8 wire format.
FP8_MAX = {"e4m3": 448.0, "e5m2": 57344.0}

_FP8_DTYPES = {"e4m3": jnp.float8_e4m3fn, "e5m2": jnp.float8_e5m2}

#: bytes per element actually crossing the wire for each gradient
#: compression format (None = native f32).  int8's reduction runs on
#: int32 lanes and fp8's on f32 lanes — exact accumulation — but the
#: payload entering/leaving the collective is 1 byte, which is what an
#: EQuARX-style in-XLA implementation puts on the ICI links.
WIRE_ITEMSIZE = {None: 4, "bf16": 2, "int8": 1, "fp8": 1}


def wire_itemsize(compression: Optional[str], itemsize: int = 4) -> int:
    """Bytes per element on the wire for a gradient bucket."""
    if compression is None:
        return itemsize
    try:
        return WIRE_ITEMSIZE[compression]
    except KeyError:
        raise MXNetError(f"unknown compression {compression!r}")


def _env_flag(name: str, default: Optional[bool]) -> Optional[bool]:
    raw = os.environ.get(name, "").strip().lower()
    if raw in ("", None):
        return default
    return raw not in ("0", "off", "false", "no")


def default_block_size() -> int:
    """Contraction-axis block size for fp8/int8 block scales."""
    raw = os.environ.get("MXNET_TPU_QUANT_BLOCK", "").strip()
    if not raw:
        return 128
    try:
        block = int(raw)
        if block <= 0:
            raise ValueError
    except ValueError:
        raise MXNetError(
            f"MXNET_TPU_QUANT_BLOCK={raw!r}: expected a positive integer")
    return block


def error_feedback_default(compression: Optional[str]) -> bool:
    """Whether error-feedback residual accumulation defaults ON for a
    gradient compression format.  Lossy formats (int8/fp8/bf16) carry
    per-step quantization error that EF cancels across steps; exact
    f32 has nothing to feed back."""
    if compression is None:
        return False
    env = _env_flag("MXNET_TPU_QUANT_EF", None)
    if env is not None:
        return env
    return compression in ("int8", "fp8")


@dataclass(frozen=True)
class QuantConfig:
    """Low-precision compute policy for matmul-heavy layers.

    ``fwd``/``bwd`` name fp8 wire formats ("e4m3"/"e5m2") or None to
    leave that direction in the ambient compute dtype.  ``block`` is
    the contraction-axis block size for the per-block scales.
    """
    fwd: Optional[str] = "e4m3"
    bwd: Optional[str] = "e5m2"
    block: int = 128

    def __post_init__(self):
        for field, v in (("fwd", self.fwd), ("bwd", self.bwd)):
            if v is not None and v not in FP8_MAX:
                raise MXNetError(
                    f"QuantConfig.{field}={v!r}: expected one of "
                    f"{sorted(FP8_MAX)} or None")
        if self.block <= 0:
            raise MXNetError("QuantConfig.block must be positive")

    @property
    def enabled(self) -> bool:
        return self.fwd is not None or self.bwd is not None

    def describe(self) -> str:
        """Stable identity string (feeds the program cache key)."""
        return f"fp8:{self.fwd}:{self.bwd}:b{self.block}"


def resolve_quant(quant) -> Optional[QuantConfig]:
    """Normalize a user-facing quant spec into a ``QuantConfig``.

    Accepts None (check ``MXNET_TPU_QUANT``), bool, "fp8", or an
    explicit ``QuantConfig``.
    """
    if quant is None:
        env = _env_flag("MXNET_TPU_QUANT", None)
        if not env:
            return None
        quant = True
    if isinstance(quant, QuantConfig):
        return quant if quant.enabled else None
    if quant is False:
        return None
    if quant is True or quant == "fp8":
        return QuantConfig(block=default_block_size())
    raise MXNetError(f"unknown quant spec {quant!r}: expected None, bool, "
                     "'fp8', or a QuantConfig")


# ---------------------------------------------------------------------------
# Block-scaled quantization
# ---------------------------------------------------------------------------

def _pad_to_blocks(x2d, block):
    """Pad the last (contraction) axis up to a block multiple and
    reshape to ``[nblocks, rows, block]``."""
    rows, k = x2d.shape
    nb = -(-k // block)
    pad = nb * block - k
    if pad:
        x2d = jnp.pad(x2d, ((0, 0), (0, pad)))
    return x2d.reshape(rows, nb, block).transpose(1, 0, 2), nb


def block_quantize(x2d, fmt: str, block: int):
    """Quantize ``[rows, K]`` to fp8 with one f32 scale per (row,
    K-block): returns ``(q [nb, rows, block], scale [nb, rows, 1])``
    with ``q * scale ~= x`` blockwise.  Scales are chosen so the block
    absmax lands exactly on the format's largest finite value — fp8
    casts then never overflow (e4m3fn has no inf to saturate into)."""
    xb, _ = _pad_to_blocks(x2d.astype(jnp.float32), block)
    absmax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    scale = jnp.maximum(absmax, jnp.float32(1e-30)) / jnp.float32(FP8_MAX[fmt])
    q = (xb / scale).astype(_FP8_DTYPES[fmt])
    return q, scale


def rowwise_quantize(x, fmt: str):
    """Quantize ``[rows, ...]`` to fp8 with one f32 scale per leading-axis
    row: returns ``(q, scale [rows])`` with ``q * scale ~= x`` rowwise.
    Same scaling rule as :func:`block_quantize` (the row absmax lands on
    the format's largest finite value, so the cast never overflows), but
    the "block" is everything behind the leading axis — the layout the
    paged KV-cache wants, where a row is one cached token position and
    its H x head_dim states share a scale."""
    if fmt not in _FP8_DTYPES:
        raise MXNetError(f"rowwise_quantize: unknown fp8 format {fmt!r}, "
                         f"expected one of {sorted(_FP8_DTYPES)}")
    x32 = x.astype(jnp.float32)
    reduce_axes = tuple(range(1, x32.ndim))
    absmax = jnp.max(jnp.abs(x32), axis=reduce_axes)
    scale = jnp.maximum(absmax, jnp.float32(1e-30)) / jnp.float32(FP8_MAX[fmt])
    q = (x32 / scale.reshape(scale.shape + (1,) * (x32.ndim - 1)))
    return q.astype(_FP8_DTYPES[fmt]), scale


_FP8_DOT_OK: Optional[bool] = None


def _fp8_dot_supported() -> bool:
    """Whether the active backend lowers dot_general on fp8 operands.
    Probed once with a tiny real dot; backends without the lowering
    use the (bitwise-identical) f32-upcast contraction instead."""
    global _FP8_DOT_OK
    if _FP8_DOT_OK is None:
        try:
            a = jnp.ones((1, 8, 8), jnp.float8_e4m3fn)
            out = jax.lax.dot_general(
                a, a, (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32)
            jax.block_until_ready(out)
            _FP8_DOT_OK = True
        except Exception:  # pragma: no cover - backend-dependent
            _FP8_DOT_OK = False
    return _FP8_DOT_OK


def _block_dot(qa, qb):
    """Per-block contraction on fp8 payloads with f32 accumulation:
    ``[nb, M, B] x [nb, N, B] -> [nb, M, N]``."""
    if not _fp8_dot_supported():  # pragma: no cover - backend-dependent
        qa, qb = qa.astype(jnp.float32), qb.astype(jnp.float32)
    return jax.lax.dot_general(
        qa, qb, (((2,), (2,)), ((0,), (0,))),
        preferred_element_type=jnp.float32)


def fp8_dot(a, b, fmt_a: str, fmt_b: str, block: int):
    """Block-scaled quantized ``a @ b.T``: ``[M,K] x [N,K] -> [M,N]``
    f32.  Both operands are quantized here (the lossy step); the
    contraction runs on fp8 payloads, partials rescaled in f32."""
    qa, sa = block_quantize(a, fmt_a, block)       # [nb, M, B], [nb, M, 1]
    qb, sb = block_quantize(b, fmt_b, block)       # [nb, N, B], [nb, N, 1]
    partial = _block_dot(qa, qb)                   # [nb, M, N] f32
    return jnp.sum(partial * sa * sb.transpose(0, 2, 1), axis=0)


# ---------------------------------------------------------------------------
# fp8 linear: e4m3 forward / e5m2 backward, f32 master weights
# ---------------------------------------------------------------------------

@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fp8_linear(x, w, fwd, bwd, block):
    if fwd is None:      # bwd-only policy: exact forward, quantized grads
        return x.astype(jnp.float32) @ w.astype(jnp.float32).T
    return fp8_dot(x, w, fwd, fwd, block)


def _fp8_linear_fwd(x, w, fwd, bwd, block):
    return _fp8_linear(x, w, fwd, bwd, block), (x, w)


def _fp8_linear_bwd(fwd, bwd, block, res, g):
    x, w = res
    if bwd is None:                      # fp8 forward, high-precision bwd
        g32 = g.astype(jnp.float32)
        dx = g32 @ w.astype(jnp.float32)
        dw = g32.T @ x.astype(jnp.float32)
    else:
        wfmt = fwd or bwd
        # dx[n,k] = sum_h g[n,h] w[h,k]   (contract H: re-block both)
        dx = fp8_dot(g, w.T, bwd, wfmt, block)
        # dw[h,k] = sum_n g[n,h] x[n,k]   (contract N)
        dw = fp8_dot(g.T, x.T, bwd, wfmt, block)
    return (dx.astype(x.dtype), dw.astype(w.dtype))


_fp8_linear.defvjp(_fp8_linear_fwd, _fp8_linear_bwd)


def fp8_linear(x, w, cfg: QuantConfig):
    """``x @ w.T`` through the fp8 policy: activations/weights cast to
    ``cfg.fwd`` (e4m3) on the forward edge, the incoming cotangent to
    ``cfg.bwd`` (e5m2) on the backward edge, block scales on the
    contraction axis, f32 accumulation throughout.  ``w`` is the f32
    master weight — it is never stored in fp8."""
    return _fp8_linear(x, w, cfg.fwd, cfg.bwd, cfg.block)


def symbol_uses_fp8(sym) -> bool:
    """True when any op in the symbol graph requests the fp8 matmul
    path (drives the trainer's fp8-aware loss-scale default)."""
    try:
        nodes = sym._topo()
    except Exception:  # pragma: no cover - non-symbol input
        return False
    for node in nodes:
        if node.is_variable:
            continue
        if str(node.attrs.get("quant", "")) == "fp8":
            return True
    return False
