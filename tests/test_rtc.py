"""MXRtc-analog tests: user Pallas kernels + the fused softmax op path.

Parity model: reference ``tests/python/gpu/test_rtc.py`` (compile a tiny
kernel from Python, launch on device data, check the result).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx


def test_pallas_kernel_push():
    def body(x_ref, y_ref, o_ref):
        o_ref[:] = x_ref[:] * y_ref[:] + 1.0

    krn = mx.rtc.PallasKernel("axpb", body)
    x = mx.nd.array(np.full((8, 128), 2.0, np.float32))
    y = mx.nd.array(np.full((8, 128), 3.0, np.float32))
    out = mx.nd.array(np.zeros((8, 128), np.float32))
    krn.push([x, y], [out])
    np.testing.assert_allclose(out.asnumpy(), np.full((8, 128), 7.0))


def test_pallas_kernel_functional_and_cache():
    def body(x_ref, o_ref):
        o_ref[:] = x_ref[:] * 2.0

    krn = mx.rtc.PallasKernel("dbl", body)
    x = jnp.asarray(np.arange(256, dtype=np.float32).reshape(2, 128))
    (y,) = krn(x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x) * 2)
    (y2,) = krn(x)  # compiled-program cache hit
    np.testing.assert_allclose(np.asarray(y2), np.asarray(x) * 2)
    assert len(krn._compiled) == 1


def test_softmax_rows_platform_branch():
    """_softmax_rows must equal jnp softmax regardless of platform."""
    from mxnet_tpu.ops.nn_ops import _softmax_rows
    x = jnp.asarray(np.random.RandomState(0).randn(64, 10).astype(np.float32))
    y = jax.jit(_softmax_rows)(x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jax.nn.softmax(x, -1)), atol=1e-6)


def test_softmax_rows_lowers_for_tpu_under_a_mesh():
    """Under a multi-device mesh the kernel branch runs per shard inside
    a ``shard_map`` — GSPMD cannot partition a Mosaic kernel, and jax
    refuses to lower one that is not wrapped (cross-lowered, no chip)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from mxnet_tpu.ops.nn_ops import _softmax_rows
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.mesh import default_mesh
    mesh = make_mesh({"data": 4, "model": 2})
    x = jax.ShapeDtypeStruct((256, 1000), jnp.float32,
                             sharding=NamedSharding(mesh, P("data", None)))
    with default_mesh(mesh):
        traced = jax.jit(_softmax_rows).trace(x)
        assert "tpu_custom_call" in traced.lower(
            lowering_platforms=("tpu",)).as_text()

        # already inside a manual region: no second shard_map
        def body(v):
            return _softmax_rows(v)
        inner = jax.jit(jax.shard_map(
            body, mesh=mesh, in_specs=P("data", None),
            out_specs=P("data", None), check_vma=False)).trace(x)
    assert str(traced.jaxpr).count("shard_map") == 1
    assert str(inner.jaxpr).count("shard_map") == 1


@pytest.mark.tpu
def test_pallas_softmax_on_accelerator():
    """The bespoke kernel runs natively on the chip."""
    from mxnet_tpu.ops.nn_ops import _pallas_softmax_rows
    dev = jax.devices("tpu")[0]
    x = jax.device_put(
        np.random.RandomState(1).randn(640, 100).astype(np.float32), dev)
    y = jax.jit(_pallas_softmax_rows)(x)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(jax.nn.softmax(jnp.asarray(x), -1)),
                               atol=1e-6)
