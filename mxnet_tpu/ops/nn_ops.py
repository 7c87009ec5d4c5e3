"""Neural-network operators (the reference's ``OperatorProperty`` op set).

TPU-native rebuild of the 35 ops registered via ``MXNET_REGISTER_OP_PROPERTY``
in ``src/operator/*.cc`` (SURVEY.md §2.1): Activation, BatchNorm, BlockGrad,
Cast, Concat, Convolution, Crop, Deconvolution, Dropout, ElementWiseSum,
Embedding, Flatten, FullyConnected, IdentityAttachKLSparseReg,
L2Normalization, LRN, LeakyReLU, Linear/Logistic/MAERegressionOutput,
MakeLoss, Pooling, ROIPooling, Reshape, SliceChannel, Softmax,
SoftmaxActivation, SoftmaxOutput, SwapAxis, UpSampling.

Design mapping:

* Each reference op's templated mshadow kernel (``*-inl.h`` ``Forward``/
  ``Backward``) becomes a pure JAX function; gradients are structural
  autodiff except where the reference defines non-structural backward
  semantics (the ``*Output`` loss heads, ``MakeLoss``, ``BlockGrad``,
  ``IdentityAttachKLSparseReg``) which use ``jax.custom_vjp``.
* ``dmlc::Parameter`` structs (e.g. ``ConvolutionParam``,
  ``src/operator/convolution-inl.h``) become ``OpParam`` tables.
* Auxiliary states (BatchNorm ``moving_mean/moving_var``,
  ``batch_norm-inl.h``) flow through ``OpContext.aux`` /
  ``OpContext.aux_updates`` instead of mutable aux TBlobs.
* Convolutions/matmuls stay NCHW at the API (reference layout) and lower to
  ``lax.conv_general_dilated`` / ``lax.dot_general`` so XLA tiles them onto
  the MXU; there is nothing like the cuDNN fast-path split
  (``src/operator/cudnn_*``) to replicate — XLA owns kernel selection.
"""
from __future__ import annotations

from functools import partial
from typing import List

import jax
import jax.numpy as jnp
import numpy as np

from ..base import MXNetError
from .registry import OpDef, OpParam, elemwise_shape, register_op

__all__ = []  # ops land in the registry



def _amp_f32(x):
    """Promote low-precision activations to f32 for stats/loss math; f32
    and f64 pass through (x64 mode must keep full precision)."""
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return x.astype(jnp.float32)
    return x

def _pair(v, n=2):
    if isinstance(v, (tuple, list)):
        if len(v) == 1:
            return tuple(v) * n
        return tuple(int(x) for x in v)
    return (int(v),) * n


def _num_args_list(prefix="arg"):
    return lambda params: [f"{prefix}{i}" for i in range(params["num_args"])]


# ---------------------------------------------------------------------------
# Activation (src/operator/activation-inl.h)
# ---------------------------------------------------------------------------

_ACTIVATIONS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
}

register_op(OpDef(
    name="Activation",
    forward=lambda ctx, params, x: _ACTIVATIONS[params["act_type"]](x),
    arguments=("data",),
    params={"act_type": OpParam("act_type", "str", required=True,
                                enum=tuple(_ACTIVATIONS))},
    infer_shape=elemwise_shape,
    doc="Elementwise activation (relu/sigmoid/tanh/softrelu).",
))


# ---------------------------------------------------------------------------
# LeakyReLU family (src/operator/leaky_relu-inl.h)
# ---------------------------------------------------------------------------

def _leaky_relu_fwd(ctx, params, *inputs):
    act = params["act_type"]
    x = inputs[0]
    if act == "leaky":
        return jnp.where(x > 0, x, params["slope"] * x)
    if act == "elu":
        return jnp.where(x > 0, x, params["slope"] * (jnp.exp(x) - 1.0))
    if act == "prelu":
        gamma = inputs[1]
        g = gamma.reshape((1, -1) + (1,) * (x.ndim - 2)) if x.ndim > 1 else gamma
        return jnp.where(x > 0, x, g * x)
    if act == "rrelu":
        if ctx.is_train and ctx.rng is not None:
            lo, hi = params["lower_bound"], params["upper_bound"]
            slope = jax.random.uniform(ctx.rng, x.shape, minval=lo, maxval=hi)
        else:
            slope = (params["lower_bound"] + params["upper_bound"]) / 2.0
        return jnp.where(x > 0, x, slope * x)
    raise MXNetError(f"unknown LeakyReLU act_type {act}")


def _leaky_relu_shape(params, in_shapes):
    if params["act_type"] != "prelu":
        return elemwise_shape(params, in_shapes)
    d, g = in_shapes
    if d is not None and g is None:
        g = (d[1],)
    return [d, g], [d], []


register_op(OpDef(
    name="LeakyReLU",
    forward=_leaky_relu_fwd,
    arguments=lambda p: ["data", "gamma"] if p["act_type"] == "prelu" else ["data"],
    params={
        "act_type": OpParam("act_type", "str", default="leaky",
                            enum=("leaky", "prelu", "rrelu", "elu")),
        "slope": OpParam("slope", "float", default=0.25),
        "lower_bound": OpParam("lower_bound", "float", default=0.125),
        "upper_bound": OpParam("upper_bound", "float", default=0.334),
    },
    infer_shape=_leaky_relu_shape,
    needs_rng=True,
    doc="Leaky/parametric/randomized/exponential rectified unit.",
))


# ---------------------------------------------------------------------------
# FullyConnected (src/operator/fully_connected-inl.h:29-110)
# ---------------------------------------------------------------------------

def _fc_fwd(ctx, params, data, weight, bias=None):
    from .. import quant as _quant
    # reference flattens trailing dims: (N, ...) -> (N, K)  (fully_connected-inl.h:70)
    x = data.reshape((data.shape[0], -1))
    if params.get("quant") == "fp8":
        # block-scaled fp8 matmul (e4m3 fwd / e5m2 grad, f32 accumulate);
        # `weight` stays the f32/bf16 master — quantization is in-graph
        # on the forward/backward edges only (quant.fp8_linear)
        cfg = _quant.resolve_quant("fp8")
        out = _quant.fp8_linear(x, weight, cfg).astype(weight.dtype)
    else:
        # mixed precision: the weight dtype is the compute dtype (bf16
        # under the AMP policy) — cast the activation at the MXU edge
        if x.dtype != weight.dtype:
            x = x.astype(weight.dtype)
        out = jnp.dot(x, weight.T)      # out = dot(data, wmat.T()) :76-80
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


def _fc_shape(params, in_shapes):
    n_in = 2 if params["no_bias"] else 3
    shapes = list(in_shapes) + [None] * (n_in - len(in_shapes))
    d = shapes[0]
    h = params["num_hidden"]
    if d is not None:
        k = int(np.prod(d[1:]))
        shapes[1] = (h, k)
        out = (d[0], h)
    else:
        out = None
    if not params["no_bias"]:
        shapes[2] = (h,)
    return shapes, [out], []


register_op(OpDef(
    name="FullyConnected",
    forward=_fc_fwd,
    arguments=lambda p: ["data", "weight"] if p["no_bias"] else ["data", "weight", "bias"],
    params={
        "num_hidden": OpParam("num_hidden", "int", required=True),
        "no_bias": OpParam("no_bias", "bool", default=False),
        "quant": OpParam("quant", "str", default="", enum=("", "fp8")),
    },
    infer_shape=_fc_shape,
    doc="Linear layer: out = data @ weight.T + bias (MXU matmul); "
        "quant='fp8' routes through the block-scaled fp8 path.",
))


# ---------------------------------------------------------------------------
# Convolution (src/operator/convolution-inl.h)
# ---------------------------------------------------------------------------

def _conv_fwd(ctx, params, data, weight, bias=None):
    from .conv_backward import conv2d
    stride = _pair(params["stride"])
    dilate = _pair(params["dilate"])
    pad = _pair(params["pad"])
    # weight dtype is the compute dtype (bf16 under AMP); the MXU
    # accumulates in f32 internally either way
    if data.dtype != weight.dtype:
        data = data.astype(weight.dtype)
    # conv2d carries per-shape tuned backward paths (conv_backward.py)
    # — the analog of the reference's cuDNN dgrad/wgrad algorithm picks
    out = conv2d(data, weight, stride=stride, pad=pad, dilate=dilate,
                 groups=params["num_group"])
    if bias is not None:
        out = out + bias.astype(out.dtype).reshape(1, -1, 1, 1)
    return out


def _conv_out_dim(x, k, s, p, d=1):
    eff = (k - 1) * d + 1
    return (x + 2 * p - eff) // s + 1


def _conv_shape(params, in_shapes):
    n_in = 2 if params["no_bias"] else 3
    shapes = list(in_shapes) + [None] * (n_in - len(in_shapes))
    d = shapes[0]
    kh, kw = _pair(params["kernel"])
    sh, sw = _pair(params["stride"])
    dh, dw = _pair(params["dilate"])
    ph, pw = _pair(params["pad"])
    f = params["num_filter"]
    g = params["num_group"]
    if d is not None:
        n, c, h, w = d
        shapes[1] = (f, c // g, kh, kw)
        out = (n, f, _conv_out_dim(h, kh, sh, ph, dh), _conv_out_dim(w, kw, sw, pw, dw))
    else:
        out = None
    if not params["no_bias"]:
        shapes[2] = (f,)
    return shapes, [out], []


_CONV_PARAMS = {
    "kernel": OpParam("kernel", "shape", required=True),
    "stride": OpParam("stride", "shape", default=(1, 1)),
    "dilate": OpParam("dilate", "shape", default=(1, 1)),
    "pad": OpParam("pad", "shape", default=(0, 0)),
    "num_filter": OpParam("num_filter", "int", required=True),
    "num_group": OpParam("num_group", "int", default=1),
    "no_bias": OpParam("no_bias", "bool", default=False),
    # accepted for API parity; XLA owns scratch memory (reference: cuDNN workspace)
    "workspace": OpParam("workspace", "int", default=512),
    "cudnn_tune": OpParam("cudnn_tune", "str", default=""),
}

register_op(OpDef(
    name="Convolution",
    forward=_conv_fwd,
    arguments=lambda p: ["data", "weight"] if p["no_bias"] else ["data", "weight", "bias"],
    params=dict(_CONV_PARAMS),
    infer_shape=_conv_shape,
    doc="2D convolution, NCHW/OIHW, grouped + dilated (lax.conv on MXU).",
))


# ---------------------------------------------------------------------------
# Deconvolution (src/operator/deconvolution-inl.h)
# ---------------------------------------------------------------------------

def _deconv_adj(params, in_hw):
    """Output-size adjustment: explicit ``adj`` or derived from target_shape
    (deconvolution-inl.h InferShape)."""
    ah, aw = _pair(params["adj"])
    tgt = params["target_shape"]
    if tgt:
        th, tw = _pair(tgt)
        kh, kw = _pair(params["kernel"])
        sh, sw = _pair(params["stride"])
        ph, pw = _pair(params["pad"])
        if in_hw is not None:
            h, w = in_hw
            ah = th - (sh * (h - 1) + kh - 2 * ph)
            aw = tw - (sw * (w - 1) + kw - 2 * pw)
    return ah, aw


def _deconv_fwd(ctx, params, data, weight, bias=None):
    # weight layout (C_in, F/g, kh, kw) as in the reference; realize the
    # transposed conv as input-dilated conv with spatially flipped kernel.
    sh, sw = _pair(params["stride"])
    ph, pw = _pair(params["pad"])
    kh, kw = _pair(params["kernel"])
    ah, aw = _deconv_adj(params, data.shape[2:])
    g = params["num_group"]
    c_in = data.shape[1]
    f = params["num_filter"]
    if data.dtype != weight.dtype:
        data = data.astype(weight.dtype)
    w = weight.reshape(g, c_in // g, f // g, kh, kw)
    w = jnp.transpose(w, (0, 2, 1, 3, 4)).reshape(f, c_in // g, kh, kw)
    w = jnp.flip(w, axis=(-2, -1))
    out = jax.lax.conv_general_dilated(
        data, w,
        window_strides=(1, 1),
        padding=[(kh - 1 - ph, kh - 1 - ph + ah), (kw - 1 - pw, kw - 1 - pw + aw)],
        lhs_dilation=(sh, sw),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=g,
    )
    if bias is not None:
        out = out + bias.astype(out.dtype).reshape(1, -1, 1, 1)
    return out


def _deconv_shape(params, in_shapes):
    n_in = 2 if params["no_bias"] else 3
    shapes = list(in_shapes) + [None] * (n_in - len(in_shapes))
    d = shapes[0]
    kh, kw = _pair(params["kernel"])
    sh, sw = _pair(params["stride"])
    ph, pw = _pair(params["pad"])
    f = params["num_filter"]
    g = params["num_group"]
    if d is not None:
        n, c, h, w = d
        ah, aw = _deconv_adj(params, (h, w))
        shapes[1] = (c, f // g, kh, kw)
        out = (n, f, sh * (h - 1) + kh - 2 * ph + ah,
               sw * (w - 1) + kw - 2 * pw + aw)
    else:
        out = None
    if not params["no_bias"]:
        shapes[2] = (f,)
    return shapes, [out], []


_DECONV_PARAMS = {
    "kernel": OpParam("kernel", "shape", required=True),
    "stride": OpParam("stride", "shape", default=(1, 1)),
    "pad": OpParam("pad", "shape", default=(0, 0)),
    "adj": OpParam("adj", "shape", default=(0, 0)),
    "target_shape": OpParam("target_shape", "shape", default=()),
    "num_filter": OpParam("num_filter", "int", required=True),
    "num_group": OpParam("num_group", "int", default=1),
    # reference DeconvolutionParam defaults no_bias=true (deconvolution-inl.h:61)
    "no_bias": OpParam("no_bias", "bool", default=True),
    "workspace": OpParam("workspace", "int", default=512),
}

register_op(OpDef(
    name="Deconvolution",
    forward=_deconv_fwd,
    arguments=lambda p: ["data", "weight"] if p["no_bias"] else ["data", "weight", "bias"],
    params=dict(_DECONV_PARAMS),
    infer_shape=_deconv_shape,
    doc="2D transposed convolution (input-dilated conv).",
))


# ---------------------------------------------------------------------------
# Pooling (src/operator/pooling-inl.h)
# ---------------------------------------------------------------------------

def _pool_out_dim(x, k, s, p):
    # reference ceil convention (pooling-inl.h:190-193):
    # oshape = min(x + 2p - k + s - 1, x + 2p - 1) / s + 1
    return min(x + 2 * p - k + s - 1, x + 2 * p - 1) // s + 1


def _pool_fwd(ctx, params, x):
    kh, kw = _pair(params["kernel"])
    sh, sw = _pair(params["stride"])
    ph, pw = _pair(params["pad"])
    ptype = params["pool_type"]
    if params["global_pool"]:
        kh, kw = x.shape[2], x.shape[3]
        sh, sw, ph, pw = 1, 1, 0, 0
    h, w = x.shape[2], x.shape[3]
    oh = _pool_out_dim(h, kh, sh, ph)
    ow = _pool_out_dim(w, kw, sw, pw)
    # extend right/bottom padding so reduce_window emits the ceil-count
    # of windows the reference produces
    extra_h = max(0, (oh - 1) * sh + kh - (h + 2 * ph))
    extra_w = max(0, (ow - 1) * sw + kw - (w + 2 * pw))
    # init must be a CONCRETE scalar: a traced/array init defeats XLA's
    # monoid-reducer recognition and reverse-mode AD of the reduce_window
    # fails during jit partial-eval linearization
    in_dtype = x.dtype
    if ptype == "max":
        init, op = np.asarray(-np.inf, x.dtype), jax.lax.max
    else:
        # sum/avg accumulate in >=f32 (a bf16 window sum loses mantissa;
        # global avg pool reduces thousands of elements)
        x = _amp_f32(x)
        init, op = np.asarray(0.0, x.dtype), jax.lax.add
    out = jax.lax.reduce_window(
        x, init, op,
        window_dimensions=(1, 1, kh, kw),
        window_strides=(1, 1, sh, sw),
        padding=((0, 0), (0, 0), (ph, ph + extra_h), (pw, pw + extra_w)),
    )
    if ptype == "avg":
        # reference divides by the full kernel area incl. padding
        # (pooling-inl.h mshadow pool_avg semantics)
        out = out / (kh * kw)
    return out.astype(in_dtype)


def _pool_shape(params, in_shapes):
    (d,) = in_shapes
    if d is None:
        return in_shapes, [None], []
    n, c, h, w = d
    if params["global_pool"]:
        return [tuple(d)], [(n, c, 1, 1)], []
    kh, kw = _pair(params["kernel"])
    sh, sw = _pair(params["stride"])
    ph, pw = _pair(params["pad"])
    oh = _pool_out_dim(h, kh, sh, ph)
    ow = _pool_out_dim(w, kw, sw, pw)
    return [tuple(d)], [(n, c, oh, ow)], []


register_op(OpDef(
    name="Pooling",
    forward=_pool_fwd,
    arguments=("data",),
    params={
        "kernel": OpParam("kernel", "shape", required=True),
        "pool_type": OpParam("pool_type", "str", default="max",
                             enum=("max", "avg", "sum")),
        "stride": OpParam("stride", "shape", default=(1, 1)),
        "pad": OpParam("pad", "shape", default=(0, 0)),
        "global_pool": OpParam("global_pool", "bool", default=False),
    },
    infer_shape=_pool_shape,
    doc="2D max/avg/sum pooling (lax.reduce_window).",
))


# ---------------------------------------------------------------------------
# BatchNorm (src/operator/batch_norm-inl.h) — aux: moving_mean, moving_var
# ---------------------------------------------------------------------------

def _bn_fwd(ctx, params, data, gamma, beta):
    eps = params["eps"]
    momentum = params["momentum"]
    axes = tuple(i for i in range(data.ndim) if i != 1)
    cshape = (1, -1) + (1,) * (data.ndim - 2)
    if params["fix_gamma"]:
        gamma = jax.lax.stop_gradient(jnp.ones_like(gamma))
    # statistics always accumulate in >=f32: a bf16 mean over N*H*W
    # elements loses most of its mantissa; moving aux states stay f32
    x32 = _amp_f32(data)
    if ctx.is_train and not params["use_global_stats"]:
        # single-pass moments (E[x^2]-E[x]^2): jnp.var materializes the
        # centered tensor (x-mean) at full activation size — real HBM
        # traffic at 224x224 ResNet scale
        mean = jnp.mean(x32, axis=axes)
        # clamp: E[x^2]-E[x]^2 can go slightly negative under f32
        # cancellation when |mean| >> std (rsqrt would then NaN)
        var = jnp.maximum(
            jnp.mean(jnp.square(x32), axis=axes) - jnp.square(mean), 0.0)
        ctx.aux_updates["moving_mean"] = (
            momentum * ctx.aux["moving_mean"] + (1.0 - momentum) * jax.lax.stop_gradient(mean))
        ctx.aux_updates["moving_var"] = (
            momentum * ctx.aux["moving_var"] + (1.0 - momentum) * jax.lax.stop_gradient(var))
    else:
        mean = ctx.aux["moving_mean"]
        var = ctx.aux["moving_var"]
    # fold into per-channel scale/shift (f32, C elements — free) and do
    # the full-tensor elementwise math in the ACTIVATION dtype: under AMP
    # this keeps the big tensors bf16 instead of paying f32 HBM traffic
    inv = jax.lax.rsqrt(var + eps)
    scale = (gamma.astype(x32.dtype) * inv).reshape(cshape)
    shift = (beta.astype(x32.dtype) - mean * gamma.astype(x32.dtype)
             * inv).reshape(cshape)
    return data * scale.astype(data.dtype) + shift.astype(data.dtype)


def _bn_shape(params, in_shapes):
    shapes = list(in_shapes) + [None] * (3 - len(in_shapes))
    d = shapes[0]
    if d is None:
        return shapes, [None], [None, None]
    c = (d[1],)
    shapes[1] = c
    shapes[2] = c
    return shapes, [tuple(d)], [c, c]


register_op(OpDef(
    name="BatchNorm",
    forward=_bn_fwd,
    arguments=("data", "gamma", "beta"),
    aux_states=("moving_mean", "moving_var"),
    params={
        "eps": OpParam("eps", "float", default=1e-3),
        "momentum": OpParam("momentum", "float", default=0.9),
        "fix_gamma": OpParam("fix_gamma", "bool", default=True),
        "use_global_stats": OpParam("use_global_stats", "bool", default=False),
    },
    infer_shape=_bn_shape,
    doc="Batch normalization over the channel axis with moving-stat aux states.",
))


# ---------------------------------------------------------------------------
# Dropout (src/operator/dropout-inl.h)
# ---------------------------------------------------------------------------

def _dropout_fwd(ctx, params, x):
    p = params["p"]
    if not ctx.is_train or p <= 0.0 or ctx.rng is None:
        return x
    keep = 1.0 - p
    mask = jax.random.bernoulli(ctx.rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0).astype(x.dtype)


register_op(OpDef(
    name="Dropout",
    forward=_dropout_fwd,
    arguments=("data",),
    params={"p": OpParam("p", "float", default=0.5)},
    infer_shape=elemwise_shape,
    needs_rng=True,
    doc="Inverted dropout; identity at inference.",
))


# ---------------------------------------------------------------------------
# Structure ops: Flatten, Reshape, Concat, SliceChannel, SwapAxis, Cast,
# ElementWiseSum, BlockGrad, Crop, Embedding (src/operator/{reshape,concat,
# slice_channel,swapaxis,cast,elementwise_sum,block_grad,crop,embedding}-inl.h)
# ---------------------------------------------------------------------------

register_op(OpDef(
    name="Flatten",
    forward=lambda ctx, params, x: x.reshape(x.shape[0], -1),
    arguments=("data",),
    infer_shape=lambda params, in_shapes: (
        in_shapes,
        [None if in_shapes[0] is None
         else (in_shapes[0][0], int(np.prod(in_shapes[0][1:])))],
        []),
    doc="Collapse all trailing axes into one.",
))


def _reshape_target(params, in_shape):
    tgt = params["target_shape"] if params["target_shape"] else params["shape"]
    if not tgt:
        raise MXNetError("Reshape needs `shape` (or legacy `target_shape`)")
    tgt = list(tgt)
    if 0 in tgt and -1 not in tgt:
        # legacy target_shape: 0 means inferred batch dim
        tgt = [-1 if t == 0 else t for t in tgt]
    if in_shape is None:
        return None
    total = int(np.prod(in_shape))
    if -1 in tgt:
        rest = int(np.prod([t for t in tgt if t != -1]))
        tgt = [total // rest if t == -1 else t for t in tgt]
    return tuple(tgt)


register_op(OpDef(
    name="Reshape",
    forward=lambda ctx, params, x: x.reshape(_reshape_target(params, x.shape)),
    arguments=("data",),
    params={
        "shape": OpParam("shape", "shape", default=()),
        "target_shape": OpParam("target_shape", "shape", default=()),
    },
    infer_shape=lambda params, in_shapes: (
        in_shapes, [_reshape_target(params, in_shapes[0])], []),
    doc="Reshape with -1/0 wildcard support.",
))


def _concat_shape(params, in_shapes):
    dim = params["dim"]
    known = [s for s in in_shapes if s is not None]
    if not known:
        return in_shapes, [None], []
    base = list(known[0])
    total = 0
    for s in in_shapes:
        if s is None:
            return in_shapes, [None], []
        total += s[dim]
    base[dim] = total
    return [tuple(s) for s in in_shapes], [tuple(base)], []


register_op(OpDef(
    name="Concat",
    forward=lambda ctx, params, *xs: jnp.concatenate(xs, axis=params["dim"]),
    arguments=_num_args_list(),
    params={
        "num_args": OpParam("num_args", "int", required=True),
        "dim": OpParam("dim", "int", default=1),
    },
    infer_shape=_concat_shape,
    doc="Concatenate along an axis.",
))


def _slice_channel_fwd(ctx, params, x):
    n = params["num_outputs"]
    ax = params["axis"]
    parts = jnp.split(x, n, axis=ax)
    if params["squeeze_axis"]:
        parts = [jnp.squeeze(p, axis=ax) for p in parts]
    return tuple(parts)


def _slice_channel_shape(params, in_shapes):
    (d,) = in_shapes
    n = params["num_outputs"]
    if d is None:
        return in_shapes, [None] * n, []
    ax = params["axis"] % len(d)
    if d[ax] % n:
        raise MXNetError(f"SliceChannel: axis {ax} size {d[ax]} not divisible by {n}")
    out = list(d)
    out[ax] = d[ax] // n
    if params["squeeze_axis"]:
        if out[ax] != 1:
            raise MXNetError("SliceChannel: squeeze_axis requires size-1 result axis")
        out = out[:ax] + out[ax + 1:]
    return [tuple(d)], [tuple(out)] * n, []


register_op(OpDef(
    name="SliceChannel",
    forward=_slice_channel_fwd,
    arguments=("data",),
    outputs=lambda p: [f"output{i}" for i in range(p["num_outputs"])],
    params={
        "num_outputs": OpParam("num_outputs", "int", required=True),
        "axis": OpParam("axis", "int", default=1),
        "squeeze_axis": OpParam("squeeze_axis", "bool", default=False),
    },
    infer_shape=_slice_channel_shape,
    doc="Split along an axis into equal parts (inverse of Concat).",
))


def _swapaxis_shape(params, in_shapes):
    (d,) = in_shapes
    if d is None:
        return in_shapes, [None], []
    a, b = params["dim1"], params["dim2"]
    out = list(d)
    out[a], out[b] = out[b], out[a]
    return [tuple(d)], [tuple(out)], []


register_op(OpDef(
    name="SwapAxis",
    forward=lambda ctx, params, x: jnp.swapaxes(x, params["dim1"], params["dim2"]),
    arguments=("data",),
    params={
        "dim1": OpParam("dim1", "int", default=0),
        "dim2": OpParam("dim2", "int", default=0),
    },
    infer_shape=_swapaxis_shape,
    doc="Swap two axes.",
))

register_op(OpDef(
    name="Cast",
    forward=lambda ctx, params, x: x.astype(np.dtype(params["dtype"])),
    arguments=("data",),
    params={"dtype": OpParam("dtype", "str", required=True)},
    infer_shape=elemwise_shape,
    infer_type=lambda params, in_types: (
        in_types, [np.dtype(params["dtype"])], []),
    doc="Elementwise dtype cast.",
))

register_op(OpDef(
    name="ElementWiseSum",
    forward=lambda ctx, params, *xs: sum(xs[1:], xs[0]),
    arguments=_num_args_list(),
    params={"num_args": OpParam("num_args", "int", required=True)},
    infer_shape=elemwise_shape,
    func_name="_element_wise_sum",
    doc="Sum of N arrays.",
))

register_op(OpDef(
    name="BlockGrad",
    forward=lambda ctx, params, x: jax.lax.stop_gradient(x),
    arguments=("data",),
    infer_shape=elemwise_shape,
    doc="Identity forward, zero backward (block_grad-inl.h).",
))


def _crop_fwd(ctx, params, *inputs):
    x = inputs[0]
    if params["num_args"] == 2:
        th, tw = inputs[1].shape[2], inputs[1].shape[3]
    else:
        th, tw = _pair(params["h_w"])
    if params["center_crop"]:
        oy = (x.shape[2] - th) // 2
        ox = (x.shape[3] - tw) // 2
    else:
        oy, ox = _pair(params["offset"])
    return jax.lax.slice(x, (0, 0, oy, ox), (x.shape[0], x.shape[1], oy + th, ox + tw))


def _crop_shape(params, in_shapes):
    d = in_shapes[0]
    if d is None:
        return in_shapes, [None], []
    if params["num_args"] == 2:
        like = in_shapes[1]
        if like is None:
            return in_shapes, [None], []
        th, tw = like[2], like[3]
    else:
        th, tw = _pair(params["h_w"])
    return [tuple(s) if s else s for s in in_shapes], [(d[0], d[1], th, tw)], []


register_op(OpDef(
    name="Crop",
    forward=_crop_fwd,
    arguments=lambda p: ["data", "crop_like"] if p["num_args"] == 2 else ["data"],
    params={
        "num_args": OpParam("num_args", "int", default=1),
        "offset": OpParam("offset", "shape", default=(0, 0)),
        "h_w": OpParam("h_w", "shape", default=(0, 0)),
        "center_crop": OpParam("center_crop", "bool", default=False),
    },
    infer_shape=_crop_shape,
    doc="Spatial crop to a target size / like-array (crop-inl.h).",
))


def _embedding_shape(params, in_shapes):
    shapes = list(in_shapes) + [None] * (2 - len(in_shapes))
    d = shapes[0]
    shapes[1] = (params["input_dim"], params["output_dim"])
    out = None if d is None else tuple(d) + (params["output_dim"],)
    return shapes, [out], []


register_op(OpDef(
    name="Embedding",
    forward=lambda ctx, params, data, weight: jnp.take(
        weight, data.astype(jnp.int32), axis=0),
    arguments=("data", "weight"),
    params={
        "input_dim": OpParam("input_dim", "int", required=True),
        "output_dim": OpParam("output_dim", "int", required=True),
    },
    infer_shape=_embedding_shape,
    doc="Index into an embedding table; grad is a scatter-add.",
))


# ---------------------------------------------------------------------------
# Normalization ops: L2Normalization, LRN
# ---------------------------------------------------------------------------

def _l2norm_fwd(ctx, params, x):
    eps = params["eps"]
    mode = params["mode"]
    if mode == "instance":
        axes = tuple(range(1, x.ndim))
    elif mode == "channel":
        axes = (1,)
    elif mode == "spatial":
        axes = tuple(range(2, x.ndim))
    else:
        raise MXNetError(f"L2Normalization: unknown mode {mode}")
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    return x / norm


register_op(OpDef(
    name="L2Normalization",
    forward=_l2norm_fwd,
    arguments=("data",),
    params={
        "eps": OpParam("eps", "float", default=1e-10),
        "mode": OpParam("mode", "str", default="instance",
                        enum=("instance", "channel", "spatial")),
    },
    infer_shape=elemwise_shape,
    doc="x / ||x||_2 over instance/channel/spatial axes.",
))


def _lrn_fwd(ctx, params, x):
    n = params["nsize"]
    alpha, beta, k = params["alpha"], params["beta"], params["knorm"]
    sq = jnp.square(x)
    half = n // 2
    padded = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    window = sum(padded[:, i:i + x.shape[1]] for i in range(n))
    return x * jnp.power(k + (alpha / n) * window, -beta)


register_op(OpDef(
    name="LRN",
    forward=_lrn_fwd,
    arguments=("data",),
    params={
        "alpha": OpParam("alpha", "float", default=1e-4),
        "beta": OpParam("beta", "float", default=0.75),
        "knorm": OpParam("knorm", "float", default=2.0),
        "nsize": OpParam("nsize", "int", required=True),
    },
    infer_shape=elemwise_shape,
    doc="Cross-channel local response normalization (lrn-inl.h).",
))


# ---------------------------------------------------------------------------
# Softmax family (src/operator/{softmax_output,softmax_activation}-inl.h)
# ---------------------------------------------------------------------------

def _softmax_row_block(n, c, itemsize):
    """Pick a VMEM-bounded row-block size for the fused softmax kernel.

    Mosaic needs the sublane (row) block divisible by 8 or equal to n,
    and the in+out blocks should stay well inside the ~16MB/core VMEM
    budget (~2MB each).  Returns None when no legal block exists — the
    caller then uses the XLA softmax.
    """
    rows_cap = (2 * 1024 * 1024) // max(1, c * itemsize)
    if rows_cap < 1:
        return None
    if n <= rows_cap:
        return n  # whole array in one block (equal-to-dim is always legal)
    for block in range(rows_cap // 8 * 8, 0, -8):
        if n % block == 0:
            return block
    return None


def _pallas_softmax_rows(x, block=None):
    """Fused row-softmax Pallas kernel (one VMEM pass: max, exp, sum,
    divide) — the MXRtc-analog bespoke kernel for the hottest head op.
    Grid over row blocks so large batches stream through VMEM."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, c = x.shape
    if block is None:
        block = _softmax_row_block(n, c, x.dtype.itemsize)
        if block is None:
            return jax.nn.softmax(x, axis=-1)

    def body(x_ref, o_ref):
        v = x_ref[:]
        m = jnp.max(v, axis=-1, keepdims=True)
        e = jnp.exp(v - m)
        o_ref[:] = e / jnp.sum(e, axis=-1, keepdims=True)

    # Mosaic rejects i64 index types, so trace the kernel with x64 off
    # (the package enables jax_enable_x64 globally)
    with jax.enable_x64(False):
        return pl.pallas_call(
            body,
            name="mxtpu_softmax_rows",
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
            grid=(n // block,),
            in_specs=[pl.BlockSpec((block, c), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM)],
            out_specs=pl.BlockSpec((block, c), lambda i: (i, 0),
                                   memory_space=pltpu.VMEM),
        )(x)


_DISABLE_PALLAS = []  # non-empty -> plain jnp softmax (export tracing)


def _softmax_rows(x):
    """Row softmax: Pallas kernel on accelerator backends, jnp on cpu.

    ``platform_dependent`` resolves the branch at lowering time, so one
    traced graph works for both the cpu test mesh and the real chip."""
    if (_DISABLE_PALLAS or x.ndim != 2 or x.shape[-1] > 16384
            or x.dtype not in (jnp.float32, jnp.bfloat16)):
        return jax.nn.softmax(x, axis=-1)
    if _softmax_row_block(x.shape[0], x.shape[1], x.dtype.itemsize) is None:
        return jax.nn.softmax(x, axis=-1)
    kernel = _pallas_softmax_rows
    from ..parallel.mesh import DATA_AXIS, current_mesh, in_manual_region
    mesh = current_mesh()
    if mesh is not None and mesh.size > 1 and not in_manual_region():
        # GSPMD cannot partition a Mosaic kernel (jax refuses to lower
        # one outside a fully manual region).  Rows are independent, so
        # every device runs the kernel on its own rows of the batch.
        from jax.sharding import PartitionSpec as P
        ndata = dict(mesh.shape).get(DATA_AXIS, 1)
        spec = P(DATA_AXIS if ndata > 1 and x.shape[0] % ndata == 0
                 else None, None)
        kernel = jax.shard_map(kernel, mesh=mesh, in_specs=spec,
                               out_specs=spec, check_vma=False)
    return jax.lax.platform_dependent(
        x, cpu=lambda v: jax.nn.softmax(v, axis=-1), default=kernel)


def _softmax_output_core(data, label, grad_scale, ignore_label, multi_output,
                         use_ignore, normalization, out_dtype="",
                         out_mode=""):
    # loss heads compute in >=f32 regardless of the activation dtype (AMP
    # policy: softmax/log in bf16 destroys small probabilities).  The
    # cast happens INSIDE fwd/bwd so the residual keeps the ORIGINAL
    # dtype — for a [B*L, vocab] LM head under bf16 AMP that halves the
    # saved-logits HBM (gigabytes at long context).

    @jax.custom_vjp
    def _fn(data, label):
        in_dtype = data.dtype
        if out_mode == "loss":
            # training head: per-position cross-entropy, label-shaped.
            # No [N, num_class] probability tensor is ever EMITTED — the
            # logsumexp fuses into the logits producer, and backward
            # recomputes softmax from the saved (activation-dtype)
            # logits.  Reference analog: make_loss-inl.h's loss-value
            # path over softmax (MakeLoss grad_scale semantics stay on
            # the GRADIENT, as in SoftmaxOutput).
            #
            # Gather BEFORE the f32 cast: convert is elementwise, so
            # gather-then-convert == convert-then-gather bit-for-bit —
            # but converting first forces XLA to MATERIALIZE the f32
            # [N, num_class] logits just to pick one scalar per row
            # (2.1 GB / 4.5 ms at the seq-2048 LM head, traced r5).
            # The logsumexp's own f32 convert fuses into its reduction.
            axis = 1 if (multi_output and data.ndim > 2) else -1
            lse = jax.scipy.special.logsumexp(
                _amp_f32(data), axis=axis)
            picked = _amp_f32(jnp.take_along_axis(
                data, jnp.expand_dims(label.astype(jnp.int32), axis),
                axis=axis))
            nll = lse - jnp.squeeze(picked, axis)
            if use_ignore:
                nll = nll * (label != ignore_label).astype(nll.dtype)
            return nll
        data = _amp_f32(data)
        if multi_output and data.ndim > 2:
            prob = jax.nn.softmax(data, axis=1)
        else:
            prob = _softmax_rows(data)
        # out_dtype='same': emit probs in the INPUT dtype.  Softmax/log
        # still compute in f32; only the OUTPUT buffer shrinks — at a
        # [B*L, 32000] LM head under bf16 AMP that's the difference
        # between a 4.2 GB and a 2.1 GB head output per step (the 32k-
        # token single-chip limiter, docs/perf.md)
        if out_dtype == "same":
            prob = prob.astype(in_dtype)
        return prob

    def _fwd(data, label):
        return _fn(data, label), (data, label)

    def _bwd(res, g):
        # grad = (prob - onehot(label)) * grad_scale * head-cotangent,
        # optionally normalized by batch/valid count
        # (softmax_output-inl.h Backward, SoftmaxOutputParam
        # normalization).  A ones cotangent multiplies by exactly 1.0 —
        # bitwise the reference ignore-out_grad behavior — while a
        # scale-filled one implements loss scaling (resilience.py)
        cot = g
        data, label = res
        in_dtype = data.dtype
        data = _amp_f32(data)

        def apply_cot(grad):
            c = cot.astype(grad.dtype)
            if c.ndim == grad.ndim:
                return grad * c
            # label-shaped cotangent (out_mode='loss'): broadcast over
            # the class axis
            if multi_output and grad.ndim > 2:
                return grad * jnp.expand_dims(c, 1)
            return grad * c[..., None]

        def norm_denom(mask):
            # count in f32: a bf16 accumulator cannot count past 256
            if normalization == "batch":
                return jnp.asarray(float(label.shape[0]), jnp.float32)
            if normalization == "valid":
                return jnp.maximum(
                    jnp.sum(mask.astype(jnp.float32)) if use_ignore
                    else jnp.asarray(float(label.size), jnp.float32), 1.0)
            return None

        if multi_output and data.ndim > 2:
            prob = jax.nn.softmax(data, axis=1)
            oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[1],
                                axis=1, dtype=data.dtype)
            grad = (prob - oh) * grad_scale
            mask = (label != ignore_label).astype(data.dtype)
            if use_ignore:
                grad = grad * jnp.expand_dims(mask, 1)
            denom = norm_denom(mask)
            if denom is not None:
                grad = grad / denom.astype(grad.dtype)
            grad = apply_cot(grad)
        else:
            prob = jax.nn.softmax(data, axis=-1)
            oh = jax.nn.one_hot(label.astype(jnp.int32), data.shape[-1],
                                dtype=data.dtype)
            g = prob - oh                      # compute (>= f32) dtype
            mask = (label != ignore_label).astype(data.dtype)
            if use_ignore:
                g = g * mask[..., None]
            # fold grad_scale AND the normalization denominator into ONE
            # scalar in the compute dtype, applied BEFORE the narrowing
            # cast: dividing after the cast quantizes 1/denom to bf16
            # and biases every gradient by up to ~2^-8 relative.  The
            # cast still happens right here at the fusion boundary —
            # under bf16 AMP at an LM head this is the difference
            # between writing a 2.1 GB f32 and a 1.05 GB bf16 dlogits
            # tensor per step (traced: 4.7 ms -> memory-bound).  The
            # optimization barrier pins the boundary: without it XLA
            # fuses the convert into the consumers and materializes the
            # PRE-convert f32 tensor (observed in the compiled module)
            denom = norm_denom(mask)
            scale = jnp.asarray(grad_scale, data.dtype)
            if denom is not None:
                scale = scale / denom.astype(data.dtype)
            if denom is not None or grad_scale != 1.0:
                g = g * scale
            g = apply_cot(g)
            grad = g.astype(in_dtype)
            if grad.dtype != jnp.float32:  # only when the cast narrows
                grad = jax.lax.optimization_barrier(grad)
        return grad.astype(in_dtype), jnp.zeros_like(label)

    _fn.defvjp(_fwd, _bwd)
    return _fn(data, label)


def _softmax_output_shape(params, in_shapes):
    shapes = list(in_shapes) + [None] * (2 - len(in_shapes))
    d = shapes[0]
    if d is not None:
        if params["multi_output"] and len(d) > 2:
            shapes[1] = (d[0],) + tuple(d[2:])
        else:
            shapes[1] = (d[0],)
        # loss mode emits per-position NLL (label-shaped), not probs
        out = shapes[1] if params.get("out_mode") == "loss" else tuple(d)
    else:
        out = None
    return shapes, [out], []


_SOFTMAX_OUT_PARAMS = {
    "grad_scale": OpParam("grad_scale", "float", default=1.0),
    "ignore_label": OpParam("ignore_label", "float", default=-1.0),
    "multi_output": OpParam("multi_output", "bool", default=False),
    "use_ignore": OpParam("use_ignore", "bool", default=False),
    "normalization": OpParam("normalization", "str", default="null",
                             enum=("null", "batch", "valid")),
    "out_dtype": OpParam("out_dtype", "str", default="",
                         enum=("", "same"),
                         doc="'same' emits probabilities in the input "
                             "dtype (halves the head-output HBM under "
                             "bf16 AMP; compute stays f32)"),
    "out_mode": OpParam("out_mode", "str", default="",
                        enum=("", "loss"),
                        doc="'loss' emits per-position cross-entropy "
                            "(label-shaped) instead of the [N, C] "
                            "probabilities; gradients are identical. "
                            "Training-side lever: nothing [N, C]-sized "
                            "leaves the head (make_loss-inl.h analog)"),
}

for _name in ("SoftmaxOutput", "Softmax"):  # "Softmax" is the deprecated alias
    register_op(OpDef(
        name=_name,
        forward=lambda ctx, params, data, label: _softmax_output_core(
            data, label, params["grad_scale"], params["ignore_label"],
            params["multi_output"], params["use_ignore"],
            params["normalization"], params["out_dtype"],
            params["out_mode"]),
        arguments=("data", "label"),
        params=dict(_SOFTMAX_OUT_PARAMS),
        infer_shape=_softmax_output_shape,
        is_loss=True,
        doc="Softmax forward; backward = (prob - onehot(label)) times "
            "the head cotangent (ones = reference behavior).",
    ))

register_op(OpDef(
    name="SoftmaxActivation",
    forward=lambda ctx, params, x: jax.nn.softmax(
        x, axis=1 if (params["mode"] == "channel" and x.ndim > 2) else -1),
    arguments=("data",),
    params={"mode": OpParam("mode", "str", default="instance",
                            enum=("instance", "channel"))},
    infer_shape=elemwise_shape,
    doc="Softmax with true autodiff backward (softmax_activation-inl.h).",
))


# ---------------------------------------------------------------------------
# Regression output heads (src/operator/regression_output-inl.h)
# ---------------------------------------------------------------------------

def _regression_head(transform, grad_fn):
    def fwd(ctx, params, data, label):
        grad_scale = params["grad_scale"]
        data = _amp_f32(data)  # loss heads compute in >=f32 (AMP)

        @jax.custom_vjp
        def _fn(data, label):
            return transform(data)

        def _f(data, label):
            return _fn(data, label), (data, label)

        def _b(res, g):
            data, label = res
            out = transform(data)
            n = max(1, int(np.prod(label.shape[1:])) if label.ndim > 1 else 1)
            grad = grad_fn(out, label.reshape(out.shape)) * (grad_scale / n)
            # honor the head cotangent multiplicatively: a ones cotangent
            # multiplies by exactly 1.0 (bitwise-neutral, the reference
            # ignore-out_grad semantics), while a uniform scale-filled
            # cotangent implements loss scaling and a per-element one a
            # weighted loss
            grad = grad * g.astype(grad.dtype)
            return grad, jnp.zeros_like(label)

        _fn.defvjp(_f, _b)
        return _fn(data, label)
    return fwd


def _regression_shape(params, in_shapes):
    shapes = list(in_shapes) + [None] * (2 - len(in_shapes))
    d = shapes[0]
    if d is not None:
        shapes[1] = tuple(d)
        out = tuple(d)
    else:
        out = None
    return shapes, [out], []


_REG_PARAMS = {"grad_scale": OpParam("grad_scale", "float", default=1.0)}

register_op(OpDef(
    name="LinearRegressionOutput",
    forward=_regression_head(lambda x: x, lambda o, l: o - l),
    arguments=("data", "label"),
    params=dict(_REG_PARAMS),
    infer_shape=_regression_shape,
    is_loss=True,
    doc="Identity forward; grad = out - label.",
))

register_op(OpDef(
    name="LogisticRegressionOutput",
    forward=_regression_head(jax.nn.sigmoid, lambda o, l: o - l),
    arguments=("data", "label"),
    params=dict(_REG_PARAMS),
    infer_shape=_regression_shape,
    is_loss=True,
    doc="Sigmoid forward; grad = sigmoid(out) - label.",
))

register_op(OpDef(
    name="MAERegressionOutput",
    forward=_regression_head(lambda x: x, lambda o, l: jnp.sign(o - l)),
    arguments=("data", "label"),
    params=dict(_REG_PARAMS),
    infer_shape=_regression_shape,
    is_loss=True,
    doc="Identity forward; grad = sign(out - label).",
))


# ---------------------------------------------------------------------------
# MakeLoss (src/operator/make_loss-inl.h)
# ---------------------------------------------------------------------------

def _make_loss_fwd(ctx, params, x):
    grad_scale = params["grad_scale"]

    @jax.custom_vjp
    def _fn(x):
        return x

    def _f(x):
        return x, None

    def _b(res, g):
        # grad_scale times the head cotangent: ones in (the reference
        # semantics) gives grad_scale everywhere; a scale-filled
        # cotangent rides loss scaling through (resilience.py)
        return (g * jnp.asarray(grad_scale, g.dtype),)

    _fn.defvjp(_f, _b)
    return _fn(x)


register_op(OpDef(
    name="MakeLoss",
    forward=_make_loss_fwd,
    arguments=("data",),
    params={"grad_scale": OpParam("grad_scale", "float", default=1.0)},
    infer_shape=elemwise_shape,
    is_loss=True,
    doc="Treat any symbol as a loss: backward is grad_scale everywhere.",
))


# ---------------------------------------------------------------------------
# IdentityAttachKLSparseReg (src/operator/identity_attach_KL_sparse_reg-inl.h)
# ---------------------------------------------------------------------------

def _kl_sparse_fwd(ctx, params, x):
    # x is expected to already be a sigmoid activation's output, as in the
    # reference (identity_attach_KL_sparse_reg-inl.h:88-95): the moving
    # average of the raw input feeds the KL penalty in backward.
    penalty = params["penalty"]
    target = params["sparseness_target"]
    momentum = params["momentum"]
    batch_mean = jnp.mean(x, axis=0)
    if ctx.aux and "avg" in ctx.aux:
        avg = (momentum * ctx.aux["avg"]
               + (1 - momentum) * jax.lax.stop_gradient(batch_mean))
        ctx.aux_updates["avg"] = avg
    else:
        avg = jax.lax.stop_gradient(batch_mean)

    @jax.custom_vjp
    def _fn(x):
        return x

    def _f(x):
        return x, None

    def _b(res, g):
        rho_hat = jnp.clip(avg, 1e-6, 1.0 - 1e-6)
        kl_grad = penalty * (-target / rho_hat + (1.0 - target) / (1.0 - rho_hat))
        return (g + jnp.broadcast_to(kl_grad, g.shape),)

    _fn.defvjp(_f, _b)
    return _fn(x)


register_op(OpDef(
    name="IdentityAttachKLSparseReg",
    forward=_kl_sparse_fwd,
    arguments=("data",),
    aux_states=("avg",),
    params={
        "sparseness_target": OpParam("sparseness_target", "float", default=0.1),
        "penalty": OpParam("penalty", "float", default=0.001),
        "momentum": OpParam("momentum", "float", default=0.9),
    },
    infer_shape=lambda params, in_shapes: (
        in_shapes, [in_shapes[0]],
        [None if in_shapes[0] is None else (in_shapes[0][1],)]),
    doc="Identity with KL sparseness penalty added to the gradient.",
))


# ---------------------------------------------------------------------------
# ROIPooling (src/operator/roi_pooling-inl.h)
# ---------------------------------------------------------------------------

def _roi_pool_fwd(ctx, params, data, rois):
    ph, pw = _pair(params["pooled_size"])
    scale = params["spatial_scale"]
    n, c, h, w = data.shape

    def one_roi(roi):
        batch_idx = roi[0].astype(jnp.int32)
        x1 = jnp.round(roi[1] * scale).astype(jnp.int32)
        y1 = jnp.round(roi[2] * scale).astype(jnp.int32)
        x2 = jnp.round(roi[3] * scale).astype(jnp.int32)
        y2 = jnp.round(roi[4] * scale).astype(jnp.int32)
        rh = jnp.maximum(y2 - y1 + 1, 1)
        rw = jnp.maximum(x2 - x1 + 1, 1)
        img = data[batch_idx]                       # (C, H, W)
        ys = jnp.arange(h)
        xs = jnp.arange(w)

        def one_bin(iy, ix):
            hstart = y1 + (iy * rh) // ph
            hend = y1 + ((iy + 1) * rh + ph - 1) // ph
            wstart = x1 + (ix * rw) // pw
            wend = x1 + ((ix + 1) * rw + pw - 1) // pw
            mask = ((ys[:, None] >= hstart) & (ys[:, None] < hend) &
                    (xs[None, :] >= wstart) & (xs[None, :] < wend))
            masked = jnp.where(mask[None], img, -jnp.inf)
            val = jnp.max(masked, axis=(1, 2))
            return jnp.where(jnp.isfinite(val), val, 0.0)

        iy, ix = jnp.meshgrid(jnp.arange(ph), jnp.arange(pw), indexing="ij")
        bins = jax.vmap(jax.vmap(one_bin))(iy, ix)  # (ph, pw, C)
        return jnp.transpose(bins, (2, 0, 1))

    return jax.vmap(one_roi)(rois)


def _roi_pool_shape(params, in_shapes):
    d, r = in_shapes
    ph, pw = _pair(params["pooled_size"])
    if d is None or r is None:
        return in_shapes, [None], []
    return [tuple(d), tuple(r)], [(r[0], d[1], ph, pw)], []


register_op(OpDef(
    name="ROIPooling",
    forward=_roi_pool_fwd,
    arguments=("data", "rois"),
    params={
        "pooled_size": OpParam("pooled_size", "shape", required=True),
        "spatial_scale": OpParam("spatial_scale", "float", required=True),
    },
    infer_shape=_roi_pool_shape,
    doc="Max-pool regions of interest to a fixed spatial size.",
))


# ---------------------------------------------------------------------------
# UpSampling (src/operator/upsampling-inl.h)
# ---------------------------------------------------------------------------

def _upsample_fwd(ctx, params, *inputs):
    scale = params["scale"]
    stype = params["sample_type"]
    if stype == "nearest":
        outs = []
        target_h = inputs[0].shape[2] * scale
        target_w = inputs[0].shape[3] * scale
        for x in inputs:
            rep_h = target_h // x.shape[2]
            rep_w = target_w // x.shape[3]
            y = jnp.repeat(jnp.repeat(x, rep_h, axis=2), rep_w, axis=3)
            outs.append(y)
        return jnp.concatenate(outs, axis=1) if len(outs) > 1 else outs[0]
    # bilinear: depthwise transposed conv with the bound (learnable,
    # bilinear-initialized) weight, as the reference's Deconvolution
    # (upsampling-inl.h: kernel = 2*scale - scale%2, stride = scale,
    # pad = ceil((scale-1)/2), num_group = C, weight (C, 1, k, k))
    x = inputs[0]
    n, c, h, w = x.shape
    if len(inputs) < 2:
        # weightless fallback (no weight bound): plain bilinear resize
        return jax.image.resize(x, (n, c, h * scale, w * scale),
                                method="bilinear")
    weight = inputs[1]
    k = 2 * scale - scale % 2
    p = -(-(scale - 1) // 2)  # ceil((scale-1)/2)
    wk = jnp.flip(weight, axis=(-2, -1))
    return jax.lax.conv_general_dilated(
        x, wk,
        window_strides=(1, 1),
        padding=[(k - 1 - p, k - 1 - p), (k - 1 - p, k - 1 - p)],
        lhs_dilation=(scale, scale),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        feature_group_count=c,
    )


def _upsample_args(p):
    if p["sample_type"] == "bilinear":
        return ["data", "weight"] if p["num_args"] > 1 else ["data"]
    return [f"arg{i}" for i in range(p["num_args"])]


def _upsample_shape(params, in_shapes):
    d = in_shapes[0]
    scale = params["scale"]
    if d is None:
        return in_shapes, [None], []
    if params["sample_type"] == "nearest":
        if any(s is None for s in in_shapes):
            return in_shapes, [None], []
        c = sum(s[1] for s in in_shapes)
        out = (d[0], c, d[2] * scale, d[3] * scale)
        return [tuple(s) if s else s for s in in_shapes], [out], []
    out = (d[0], d[1], d[2] * scale, d[3] * scale)
    shapes = [tuple(d)]
    if len(in_shapes) > 1:
        # depthwise deconv weight (upsampling-inl.h: Shape4(C, 1, k, k))
        k = 2 * scale - scale % 2
        shapes.append((d[1], 1, k, k))
    return shapes, [out], []


register_op(OpDef(
    name="UpSampling",
    forward=_upsample_fwd,
    arguments=_upsample_args,
    params={
        "scale": OpParam("scale", "int", required=True),
        "num_filter": OpParam("num_filter", "int", default=0),
        "sample_type": OpParam("sample_type", "str", default="nearest",
                               enum=("nearest", "bilinear")),
        "num_args": OpParam("num_args", "int", default=1),
        "workspace": OpParam("workspace", "int", default=512),
    },
    infer_shape=_upsample_shape,
    doc="Nearest/bilinear spatial upsampling; multi-input concat on channels.",
))


# ---------------------------------------------------------------------------
# _CrossDeviceCopy (src/operator/cross_device_copy.cc) — placement is handled
# by the executor/sharding layer; inside a compiled graph this is identity.
# ---------------------------------------------------------------------------

register_op(OpDef(
    name="_CrossDeviceCopy",
    forward=lambda ctx, params, x: x,
    arguments=("data",),
    infer_shape=elemwise_shape,
    doc="Device-boundary copy marker; XLA/sharding layer realizes the transfer.",
))


# ---------------------------------------------------------------------------
# LayerNorm — capability upgrade beyond the 2016 reference op set (needed by
# the transformer zoo models; the reference's only norms are BatchNorm/LRN).
# ---------------------------------------------------------------------------

def _layernorm_fwd(ctx, params, x, gamma, beta):
    eps = params["eps"]
    x32 = _amp_f32(x)  # stats in >=f32 under the AMP policy
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    xhat = (x32 - mean) * jax.lax.rsqrt(var + eps)
    out = xhat * gamma.astype(x32.dtype) + beta.astype(x32.dtype)
    return out.astype(x.dtype)


def _layernorm_shape(params, in_shapes):
    d, g, b = (list(in_shapes) + [None] * 3)[:3]
    if d is None:
        return in_shapes, [None], []
    feat = (d[-1],)
    return [tuple(d), feat, feat], [tuple(d)], []


register_op(OpDef(
    name="LayerNorm",
    forward=_layernorm_fwd,
    arguments=("data", "gamma", "beta"),
    params={"eps": OpParam("eps", "float", default=1e-5)},
    infer_shape=_layernorm_shape,
    doc="Last-axis layer normalization with learnable scale/shift.",
))
