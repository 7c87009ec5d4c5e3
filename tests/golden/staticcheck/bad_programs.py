"""Seeded-bad lowered programs for the staticcheck gate corpus.

Imported (via file path) by ``tools/staticcheck.py gate`` and
``tests/test_staticcheck.py``.  Each builder traces a tiny program with
one deliberate hazard and returns ``(traced, audit_kwargs)`` for
:func:`mxnet_tpu.analysis.audit_traced`; ``PROGRAMS`` maps builder name
to the rules that MUST fire on it (empty list = negative control).
"""
import numpy as np

import jax
import jax.numpy as jnp

_SDS = jax.ShapeDtypeStruct


def carry_widen():
    """The PR 2 bug class: an int32 metric carry accumulated with an
    unpinned bool-sum widens to int64 under the package's enable_x64 —
    the next step call sees a new input dtype and re-traces forever."""
    def step(carry, pred, label):
        hits = jnp.sum(pred.astype(jnp.int32) == label.astype(jnp.int32))
        return carry + hits
    tr = jax.jit(step).trace(_SDS((), jnp.int32), _SDS((16,), jnp.float32),
                             _SDS((16,), jnp.float32))
    return tr, {"carry_pairs": [(0, 0, "metric carry")]}


def host_transfer():
    def step(x):
        y = jax.pure_callback(lambda a: np.tanh(a),
                              _SDS((8,), jnp.float32), x)
        return y * 2.0
    return jax.jit(step).trace(_SDS((8,), jnp.float32)), {}


def captured_const():
    table = np.arange(65536, dtype=np.float32)    # 256 KiB baked in
    def step(idx):
        return jnp.take(jnp.asarray(table), idx)
    return jax.jit(step).trace(_SDS((4,), jnp.int32)), {}


def donation_miss():
    def step(x):
        # no output shares x's shape/dtype -> XLA cannot alias the
        # donated buffer; it is freed + reallocated every call
        return (x[:4] * 2.0).astype(jnp.bfloat16)
    jf = jax.jit(step, donate_argnums=(0,))
    return jf.trace(_SDS((8,), jnp.float32)), {"donate_flat": [0]}


def clean():
    """Negative control: the gate fails if anything fires here."""
    def step(x, y):
        return x @ y
    return jax.jit(step).trace(_SDS((4, 4), jnp.float32),
                               _SDS((4, 4), jnp.float32)), {}


def fused_regress():
    """The PR 7 regression class: a trainer that claims the single-pass
    fused update (tags its flat bucket) but still runs the legacy
    multi-pass chain — the bucket is traversed once for the rescale and
    again for the momentum update, so the 1R/1W contract is broken."""
    from mxnet_tpu.analysis.program import tag

    def step(g, w, m):
        g = tag(g, label="gradbucket:0")
        g = g * 0.0625                  # pass 1: rescale sweep
        m2 = 0.9 * m - 0.1 * g          # pass 2: momentum sweep
        return w + m2, m2
    tr = jax.jit(step).trace(_SDS((64,), jnp.float32),
                             _SDS((64,), jnp.float32),
                             _SDS((64,), jnp.float32))
    return tr, {"expect_fused": True}


def fused_clean():
    """Negative control for ``expect_fused``: the tagged bucket feeds
    ONE opaque fused-update eqn, so the audit must report exactly
    1R/1W and stay silent."""
    from mxnet_tpu.analysis.program import tag
    from mxnet_tpu.ops.fused_update import fused_update

    def step(g, w, m):
        g = tag(g, label="gradbucket:0")
        new_w, new_m = fused_update(g, w, (m,), (0.1,),
                                    kind="sgd_momentum", momentum=0.9,
                                    rescale_grad=0.0625)
        return new_w, new_m
    tr = jax.jit(step).trace(_SDS((64,), jnp.float32),
                             _SDS((64,), jnp.float32),
                             _SDS((64,), jnp.float32))
    return tr, {"expect_fused": True}


def _data_mesh():
    from jax.sharding import Mesh
    devs = np.array(jax.devices())
    return Mesh(devs, ("data",))


def hbm_bytes_widened():
    """The r9 regression class: a trainer configured for quantized grad
    reduction whose bucket silently re-widened — the psum payload is
    full-width f32, so every step moves 4x the contracted wire bytes."""
    from jax.sharding import PartitionSpec as P
    mesh = _data_mesh()

    def step(g):
        def body(gl):
            return jax.lax.psum(gl, "data")     # f32 on the wire
        return jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P())(g)
    n = 512 * len(jax.devices())
    tr = jax.jit(step).trace(_SDS((n,), jnp.float32))
    return tr, {"expect_wire_itemsize": 1}


def hbm_bytes_quantized():
    """Negative control for ``expect_wire_itemsize``: the bucket rides
    the block-quantized fp8 reduction, so the narrowest same-shape value
    in the psum's cone is the 1-byte payload and the audit stays
    silent."""
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel.collectives import psum_compressed
    mesh = _data_mesh()

    def step(g):
        def body(gl):
            return psum_compressed(gl, "data", "fp8")
        return jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                             out_specs=P())(g)
    n = 512 * len(jax.devices())
    tr = jax.jit(step).trace(_SDS((n,), jnp.float32))
    return tr, {"expect_wire_itemsize": 1}


def _decode_read(quant):
    """Trace one layer's paged decode-attention read (the dense impl's
    table gather) over pools that are f32 or fp8-quantized."""
    from mxnet_tpu.serve import kvcache
    nl, nb, bs, h, hd, b, mb = 2, 16, 8, 2, 16, 2, 4

    if quant:
        pool = kvcache.QuantPool(
            _SDS((nl, nb, bs, h * hd), jnp.float8_e4m3fn),
            _SDS((nl, nb, bs), jnp.float32))
    else:
        pool = _SDS((nl, nb, bs, h * hd), jnp.float32)

    def step(q, kp, vp, tables, lengths):
        return kvcache.paged_attention(q, kp, vp, 1, tables, lengths,
                                       impl="dense")

    return jax.jit(step).trace(
        _SDS((b, h, hd), jnp.float32), pool, pool,
        _SDS((b, mb), jnp.int32), _SDS((b,), jnp.int32))


def decode_kv_widened():
    """The r12 regression class: an engine configured for fp8 KV pools
    whose decode program gathers a full-width f32 pool — the quantize
    was silently dropped and the step streams 4x the contracted KV
    bytes/token."""
    return _decode_read(quant=False), {"expect_kv_itemsize": 1}


def decode_kv_quantized():
    """Negative control for ``expect_kv_itemsize``: the pool-shaped
    gathers read the 1-byte e4m3 payload (the f32 scales are rank-2
    gathers, outside the KV-read shape filter), so the audit stays
    silent."""
    return _decode_read(quant=True), {"expect_kv_itemsize": 1}


PROGRAMS = {
    "carry_widen": (carry_widen, ["program.carry-widen", "program.widen"]),
    "host_transfer": (host_transfer, ["program.host-transfer"]),
    "captured_const": (captured_const, ["program.captured-const"]),
    "donation_miss": (donation_miss, ["program.donation-miss"]),
    "clean": (clean, []),
    "fused_regress": (fused_regress, ["program.fused-update"]),
    "fused_clean": (fused_clean, []),
    "hbm_bytes_widened": (hbm_bytes_widened, ["program.hbm-bytes"]),
    "hbm_bytes_quantized": (hbm_bytes_quantized, []),
    "decode_kv_widened": (decode_kv_widened, ["program.hbm-bytes"]),
    "decode_kv_quantized": (decode_kv_quantized, []),
}
