"""Pallas flash-attention kernel: interpret-mode correctness on CPU.

The fused kernel (``parallel/flash_attention.py``) replaces the jnp-scan
blockwise path on accelerators (VERDICT r3 item 2); here the SAME kernel
code runs under ``pallas_call(interpret=True)`` against the dense
reference, including the custom-VJP backward kernels.  The real-chip
lane (``test_tpu_real.py``) exercises the compiled Mosaic path.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.parallel.flash_attention import flash_attention
from mxnet_tpu.parallel.ring_attention import local_attention


def _qkv(b=1, h=2, l=256, d=64, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, h, l, d).astype(dtype) * 0.3)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_interpret_matches_dense(causal):
    q, k, v = _qkv()
    y = flash_attention(q, k, v, causal=causal, block_q=128, block_k=128,
                        interpret=True)
    ref = local_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bwd_interpret_matches_dense(causal):
    q, k, v = _qkv(seed=3)

    def loss_flash(q, k, v):
        y = flash_attention(q, k, v, causal=causal, block_q=128,
                            block_k=128, interpret=True)
        return jnp.sum(y * jnp.cos(y))

    def loss_dense(q, k, v):
        y = local_attention(q, k, v, causal=causal)
        return jnp.sum(y * jnp.cos(y))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for gf, gd, name in zip(g_flash, g_dense, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gd),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name}")


def test_flash_uneven_blocks_interpret():
    """block_q != block_k and multiple batch/head rows."""
    q, k, v = _qkv(b=2, h=3, l=256, seed=5)
    y = flash_attention(q, k, v, causal=True, block_q=64, block_k=128,
                        interpret=True)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_cpu_dispatch_runs_reference():
    """Without interpret, the cpu branch of platform_dependent serves the
    jnp-scan path — same numbers, no Mosaic involved."""
    q, k, v = _qkv(seed=7)
    y = flash_attention(q, k, v, causal=True)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_fallback_unsupported_shape():
    """Shapes with no valid block divisor fall back to the jnp path."""
    q, k, v = _qkv(l=192, seed=9)  # 192 = 64*3: block 64 works
    y = flash_attention(q, k, v, causal=False, interpret=False)
    assert y.shape == q.shape
    # l=100 has no >=64 divisor -> reference path (still correct)
    q2, k2, v2 = _qkv(l=100, seed=11)
    y2 = flash_attention(q2, k2, v2, causal=True)
    ref2 = local_attention(q2, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(ref2),
                               rtol=2e-5, atol=2e-5)


def test_flash_fallback_indivisible_length_is_dense():
    """L with no >=64 power-of-two divisor must serve the DENSE reference
    instead of crashing in blockwise (review finding r4)."""
    q, k, v = _qkv(l=1000, seed=13)
    y = flash_attention(q, k, v, causal=True)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_flash_nondividing_explicit_blocks_fall_back():
    """Explicit blocks that do not divide L must take the safe reference
    path (review finding r4: the kernel grid would silently truncate)."""
    q, k, v = _qkv(l=320, seed=15)
    y = flash_attention(q, k, v, causal=True, block_q=128, block_k=128,
                        interpret=True)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("lq,lk", [(128, 256), (256, 128)])
def test_flash_cross_attention_interpret(lq, lk):
    """Non-causal cross-attention (lq != lk) runs through the kernel."""
    rng = np.random.RandomState(17)
    mk = lambda l: jnp.asarray(rng.randn(1, 2, l, 64).astype(np.float32)
                               * 0.3)
    q, k, v = mk(lq), mk(lk), mk(lk)
    y = flash_attention(q, k, v, causal=False, block_q=64, block_k=64,
                        interpret=True)
    ref = local_attention(q, k, v, causal=False)
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_flash(q, k, v):
        return jnp.sum(jnp.square(flash_attention(
            q, k, v, causal=False, block_q=64, block_k=64,
            interpret=True)))

    def loss_dense(q, k, v):
        return jnp.sum(jnp.square(local_attention(q, k, v, causal=False)))

    gf = jax.grad(loss_flash, (0, 1, 2))(q, k, v)
    gd = jax.grad(loss_dense, (0, 1, 2))(q, k, v)
    for a, b, n in zip(gf, gd, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4, err_msg=f"d{n}")


def test_flash_under_dp_tp_mesh_uses_shard_map():
    """Advisor r4 medium: inside a GSPMD dp/tp-sharded step the pallas
    kernel must run per-shard under shard_map (XLA cannot partition an
    opaque custom call), and the result must stay exact."""
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.mesh import default_mesh

    rng = np.random.RandomState(0)
    b, h, l, d = 4, 4, 128, 32
    q, k, v = (jnp.asarray(rng.randn(b, h, l, d).astype(np.float32)) * 0.3
               for _ in range(3))
    mesh = make_mesh({"data": 2, "model": 2}, jax.devices()[:4])
    with default_mesh(mesh):
        # the wrap decision happens at trace time with the mesh active
        out = flash_attention(q, k, v, causal=True, block_q=64, block_k=64,
                              interpret=True)
        jaxpr = jax.make_jaxpr(
            lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            block_q=64, block_k=64,
                                            interpret=True))(q, k, v)
    assert "shard_map" in str(jaxpr), \
        "pallas path not wrapped in shard_map under a dp/tp mesh"
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


def test_flash_under_manual_region_not_double_wrapped():
    """Inside an existing shard_map region the operands carry varying
    manual axes — the GSPMD wrap must not re-enter shard_map."""
    import functools
    from jax.sharding import PartitionSpec as P
    from mxnet_tpu.parallel import make_mesh
    from mxnet_tpu.parallel.mesh import default_mesh

    rng = np.random.RandomState(1)
    b, h, l, d = 2, 2, 128, 32
    q, k, v = (jnp.asarray(rng.randn(b, h, l, d).astype(np.float32)) * 0.3
               for _ in range(3))
    mesh = make_mesh({"data": 2}, jax.devices()[:2])
    spec = P("data", None, None, None)

    def body(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64,
                               block_k=64, interpret=True)

    with default_mesh(mesh):
        fn = jax.shard_map(body, mesh=mesh, in_specs=(spec,) * 3,
                           out_specs=spec)
        out = jax.jit(fn)(q, k, v)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_blhd_layout_interpret_matches_dense(causal):
    """The native [B, L, H, D] kernels (H-looped grid cells): exact in
    interpret mode vs the dense reference, fwd and grads.  These switch
    onto real TPU when Mosaic supports per-head slices of an
    (H, d)-tiled block — this test keeps them correct until then."""
    rng = np.random.RandomState(0)
    b, h, l, d = 2, 4, 256, 32
    q4, k4, v4 = (jnp.asarray(rng.randn(b, l, h, d).astype(np.float32)) * 0.3
                  for _ in range(3))

    def t(x):
        return x.transpose(0, 2, 1, 3)

    out = flash_attention(q4, k4, v4, causal=causal, block_q=64,
                          block_k=64, interpret=True, layout="blhd")
    ref = t(local_attention(t(q4), t(k4), t(v4), causal=causal))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss(q, k, v):
        return jnp.sum(jnp.tanh(flash_attention(
            q, k, v, causal=causal, block_q=64, block_k=64,
            interpret=True, layout="blhd")))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.tanh(t(local_attention(
            t(q), t(k), t(v), causal=causal))))

    g = jax.grad(loss, argnums=(0, 1, 2))(q4, k4, v4)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q4, k4, v4)
    for n, a, b_ in zip("qkv", g, gr):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=3e-4, atol=3e-5,
            err_msg=f"blhd d{n} mismatch (causal={causal})")


def test_flash_blhd_real_path_transposes_to_bhld():
    """Non-interpret blhd must route through the PROVEN bhld kernel
    (Mosaic limitation): same trace on both layouts, values equal."""
    rng = np.random.RandomState(1)
    b, h, l, d = 2, 2, 128, 32
    q4, k4, v4 = (jnp.asarray(rng.randn(b, l, h, d).astype(np.float32)) * 0.3
                  for _ in range(3))

    def t(x):
        return x.transpose(0, 2, 1, 3)

    out = flash_attention(q4, k4, v4, causal=True, block_q=64, block_k=64,
                          layout="blhd")
    ref = t(flash_attention(t(q4), t(k4), t(v4), causal=True, block_q=64,
                            block_k=64))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
