"""Flash kernel INSIDE ring attention (VERDICT r4 item 3).

The per-ring-step compute must be the blockwise/flash path — no
``[lq, lkv]`` f32 score tensor may materialize on any shard — while
results and gradients stay exact vs dense single-device attention.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


from mxnet_tpu.parallel.ring_attention import (_ring_flash,
                                               local_attention,
                                               ring_attention,
                                               ring_self_attention)
from mxnet_tpu.parallel import make_mesh


def _mk(b=2, h=2, l=256, d=32, seed=0):
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randn(b, h, l, d).astype(np.float32)) * 0.3,
            jnp.asarray(rng.randn(b, h, l, d).astype(np.float32)) * 0.3,
            jnp.asarray(rng.randn(b, h, l, d).astype(np.float32)) * 0.3)


def _ring_fn(mesh, sp, causal):
    spec = P(None, None, "seq", None)
    return jax.shard_map(
        functools.partial(ring_attention, axis_name="seq", causal=causal),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("sp", [2, 4])
def test_ring_flash_matches_dense(causal, sp):
    """L=256 over sp shards: shard length >= 64 admits the flash path;
    compare against dense single-device attention."""
    q, k, v = _mk()
    mesh = make_mesh({"seq": sp}, jax.devices()[:sp])
    out = jax.jit(_ring_fn(mesh, sp, causal))(q, k, v)
    ref = local_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_gradients_match_dense(causal):
    q, k, v = _mk(l=256)
    sp = 4
    mesh = make_mesh({"seq": sp}, jax.devices()[:sp])
    fn = _ring_fn(mesh, sp, causal)

    def loss_ring(q, k, v):
        return jnp.sum(jnp.tanh(fn(q, k, v)))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.tanh(local_attention(q, k, v, causal=causal)))

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g_ring, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-4, atol=3e-5,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_ring_flash_no_dense_scores_in_hlo():
    """The VERDICT 'done' criterion: lower the seq-sharded train-side
    ring attention at a shape where block < shard and assert the
    compiled HLO holds no per-shard [lq, lkv] f32 score tensor."""
    sp = 2
    l, d = 4096, 32                      # shard 2048 > flash block 1024
    lq = l // sp
    q, k, v = _mk(b=1, h=1, l=l, d=d)
    mesh = make_mesh({"seq": sp}, jax.devices()[:sp])
    fn = _ring_fn(mesh, sp, True)

    def loss(q, k, v):
        return jnp.sum(fn(q, k, v) ** 2)

    txt = (jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
           .lower(q, k, v).compile().as_text())
    assert f"f32[1,1,{lq},{lq}]" not in txt, \
        "per-shard dense score tensor materialized in ring attention"
    # block-sized score tensors are expected and fine
    assert f"{lq},{lq}" not in txt.replace(f"f32[1,1,{lq},{lq}]", ""), \
        "a [shard, shard] tensor survived somewhere in the ring program"


def test_ring_flash_user_wrapper_and_tiny_fallback():
    """ring_self_attention still works end to end, and tiny shards
    (below the kernel's block floor) keep the dense fallback exact."""
    q, k, v = _mk(l=64)                  # shard 16 at sp=4: dense path
    mesh = make_mesh({"seq": 4}, jax.devices()[:4])
    out = ring_self_attention(q, k, v, mesh, batch_axis=None, causal=True)
    ref = local_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
