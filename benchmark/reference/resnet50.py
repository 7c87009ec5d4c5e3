"""Plain float32 reference of ResNet-50 (He et al. 2015,
arXiv:1512.03385, Table 1, 50-layer column) in ``jax.numpy``, adapted
from ``tools/resnet_probe.py``: float32 throughout under
``jax.default_matmul_precision("highest")``, and the trainer's parameter
names, so that the trainer's own initial parameters feed it.

Departures from the paper, all the program's and kept so that the two
sides compute the same function: the stride of a down-sampling
bottleneck sits on its 3x3 convolution (the paper puts it on the first
1x1); BatchNorm eps is 1e-3 with biased batch variance; convolutions
have no bias; and the 3x3 max pool after the stem rounds its output size
UP (the 2016 MXNet pooling convention, ``ops/nn_ops.py:_pool_out_dim``),
so a 224 x 224 image gives feature maps of 57, 29, 15 and 8 where the
paper has 56, 28, 14 and 7.  Nothing is imported from the program under
test.  :func:`forward_macs_per_image` counts the PAPER's model, which is
what the work requires; the program's extra rows and columns are work
no one asked for and count against its utilization.

The program names its layers by creation order (``convolution0``,
``batchnorm0``, ...; within a bottleneck: 1x1, 3x3, 1x1, then the
projection shortcut if there is one).  The counter is per process, so
the reference takes the names that are there, sorted by their number,
and walks the architecture in the same order.
"""
from __future__ import annotations

import re
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

UNITS = (3, 4, 6, 3)
FILTERS = (256, 512, 1024, 2048)
BN_EPS = 1e-3
_HI = "highest"


def _numbered(names, stem: str, suffix: str) -> List[str]:
    """Layer prefixes ``<stem><n>`` present in ``names``, by ``n``."""
    pat = re.compile(r"^(%s(\d+))_%s$" % (stem, suffix))
    found = sorted((int(m.group(2)), m.group(1))
                   for m in map(pat.match, names) if m)
    return [p for _, p in found]


def architecture() -> List[Tuple[str, int, int, int, int, bool]]:
    """The convolutions in creation order:
    ``(role, c_in, c_out, kernel, stride, relu)`` with role one of
    ``stem``, ``a`` (1x1 reduce), ``b`` (3x3), ``c`` (1x1 expand),
    ``sc`` (projection shortcut)."""
    convs = [("stem", 3, 64, 7, 2, True)]
    cin = 64
    for stage, (units, cout) in enumerate(zip(UNITS, FILTERS)):
        inner = cout // 4
        for unit in range(units):
            stride = 2 if unit == 0 and stage > 0 else 1
            convs.append(("a", cin, inner, 1, 1, True))
            convs.append(("b", inner, inner, 3, stride, True))
            convs.append(("c", inner, cout, 1, 1, False))
            if unit == 0:
                convs.append(("sc", cin, cout, 1, stride, False))
            cin = cout
    return convs


def _conv(x, w, stride: int, pad: int):
    return jax.lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=_HI)


def _bn_train(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    return ((x - mean) / jnp.sqrt(var + BN_EPS) * gamma.reshape(1, -1, 1, 1)
            + beta.reshape(1, -1, 1, 1))


def pooled_size(h: int, ceil_mode: bool, k: int = 3, s: int = 2,
                p: int = 1) -> int:
    """Output size of the stem's max pool: the paper's (floor) or the
    program's (ceil, but never a window that starts in the padding)."""
    if not ceil_mode:
        return (h + 2 * p - k) // s + 1
    return min(h + 2 * p - k + s - 1, h + 2 * p - 1) // s + 1


def _ceil_pool_pad(h: int, k: int = 3, s: int = 2, p: int = 1):
    extra = max(0, (pooled_size(h, True, k, s, p) - 1) * s + k - (h + 2 * p))
    return (p, p + extra)


def logits(params: Dict[str, Any], x) -> jax.Array:
    """Training-mode forward (batch statistics) to the 1000 logits."""
    p = {k: jnp.asarray(v, jnp.float32) for k, v in params.items()}
    conv_names = _numbered(p, "convolution", "weight")
    bn_names = _numbered(p, "batchnorm", "gamma")
    arch = architecture()
    if len(conv_names) != len(arch) or len(bn_names) != len(arch):
        raise ValueError(f"{len(conv_names)} convolutions and "
                         f"{len(bn_names)} batch norms for an architecture "
                         f"of {len(arch)}")
    x = jnp.asarray(x, jnp.float32)
    layers = iter(zip(conv_names, bn_names))

    def conv_bn(src, k, stride, relu):
        cn, bn = next(layers)
        y = _bn_train(_conv(src, p[cn + "_weight"], stride, k // 2),
                      p[bn + "_gamma"], p[bn + "_beta"])
        return jnp.maximum(y, 0.0) if relu else y

    x = conv_bn(x, 7, 2, True)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
        [(0, 0), (0, 0), _ceil_pool_pad(x.shape[2]),
         _ceil_pool_pad(x.shape[3])])
    for stage, units in enumerate(UNITS):
        for unit in range(units):
            stride = 2 if unit == 0 and stage > 0 else 1
            body = conv_bn(x, 1, 1, True)
            body = conv_bn(body, 3, stride, True)
            body = conv_bn(body, 1, 1, False)
            shortcut = conv_bn(x, 1, stride, False) if unit == 0 else x
            x = jnp.maximum(body + shortcut, 0.0)
    x = jnp.mean(x, axis=(2, 3))
    return jnp.matmul(x, p["fc1_weight"].T, precision=_HI) + p["fc1_bias"]


def example_losses(params: Dict[str, Any], x, y) -> jax.Array:
    """Per-image softmax cross-entropy of the training-mode forward."""
    z = logits(params, x)
    lse = jax.scipy.special.logsumexp(z, axis=-1)
    picked = jnp.take_along_axis(
        z, jnp.asarray(y, jnp.int32)[:, None], axis=-1)[:, 0]
    return lse - picked


def mean_loss(params: Dict[str, Any], x, y) -> jax.Array:
    return jnp.mean(example_losses(params, x, y))


# ---------------------------------------------------------------------------
# analytic sizes the metrics use
# ---------------------------------------------------------------------------

def forward_macs_per_image(size: int = 224, classes: int = 1000,
                           as_executed: bool = False) -> int:
    """Multiply-accumulates of one forward pass: every convolution's
    H_out x W_out x C_out x C_in x k^2, plus the classifier.  By default
    the paper's feature maps; ``as_executed`` counts the program's larger
    ones (see the module docstring)."""
    macs = 0
    h_stem = size // 2                # 7x7 stride 2, pad 3
    h = pooled_size(h_stem, as_executed)   # 3x3 max pool stride 2, pad 1
    unit_h = h
    for role, cin, cout, k, stride, _ in architecture():
        if role == "stem":
            out = h_stem
        elif role == "a":
            unit_h = out = h
        elif role == "b":
            h = out = (h - 1) // stride + 1      # 3x3, pad 1
        elif role == "c":
            out = h
        else:
            out = (unit_h - 1) // stride + 1     # 1x1, pad 0
        macs += out * out * cout * cin * k * k
    return macs + FILTERS[-1] * classes


def train_flops_per_image(size: int = 224, classes: int = 1000) -> float:
    """Forward + backward: 3 passes of 2 FLOPs per multiply-accumulate.
    No recomputation, no optimizer, no BatchNorm or activation work."""
    return 3.0 * 2.0 * forward_macs_per_image(size, classes)


def param_count(classes: int = 1000) -> int:
    n = 0
    for _role, cin, cout, k, _s, _r in architecture():
        n += cout * cin * k * k + 2 * cout
    return n + FILTERS[-1] * classes + classes


# ---------------------------------------------------------------------------
# what the training runner calls
# ---------------------------------------------------------------------------

def reference_loss(params: Dict[str, Any], batch: Dict[str, Any],
                   cfg: Dict[str, Any]) -> float:
    """Mean loss of the WHOLE first batch (BatchNorm couples its images,
    so no sample of it would do)."""
    with jax.default_matmul_precision(_HI):
        return float(jax.jit(mean_loss)(
            params, jnp.asarray(batch["data"]),
            jnp.asarray(batch["softmax_label"])))


def program_loss(head, batch: Dict[str, Any], cfg: Dict[str, Any]) -> float:
    """The program's head is the softmax's probabilities [B, classes]:
    its loss is the mean of -log p[label]."""
    import numpy as np
    p = np.asarray(head, np.float64)
    y = np.asarray(batch["softmax_label"]).astype(np.int64)
    return float(np.mean(-np.log(np.maximum(p[np.arange(len(y)), y], 1e-300))))


def train_flops_per_step(cfg: Dict[str, Any], shapes: Dict[str, Any]) -> float:
    batch, _c, size, _w = shapes["data"]
    classes = int(cfg["train"]["symbol"]["kwargs"]["num_classes"])
    return batch * train_flops_per_image(int(size), classes)
