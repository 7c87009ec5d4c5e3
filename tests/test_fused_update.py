"""mxnet_tpu.ops.fused_update: the single-pass fused optimizer kernel.

The contract under test is BITWISE identity with the unfused per-param
path — not allclose.  The fused trainer must be a drop-in numerical
twin: same params, same optimizer state (reconstructed from the flat
buckets through ``FusedPlan.scatter``), same heads, over multiple steps,
for every supported optimizer kind, with the bad-step guard on and off,
including a chaos step whose update must be a bitwise no-op on both
paths.  On top of the numerics the fused path must keep the framework
contracts: one trace, donated buffers aliased, and a 1R/1W grad-bucket
audit (the unfused baseline stays at its multi-pass count).
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import analysis
from mxnet_tpu import symbol as S
from mxnet_tpu.base import MXNetError
from mxnet_tpu.ops import fused_update as fu
from mxnet_tpu.parallel import ShardedTrainer, make_mesh

N_STEPS = 3


def _mlp(no_bias=False):
    d = S.Variable("data")
    net = S.FullyConnected(d, num_hidden=32, name="fc1", no_bias=no_bias)
    net = S.Activation(net, act_type="relu")
    net = S.FullyConnected(net, num_hidden=10, name="fc2", no_bias=no_bias)
    return S.SoftmaxOutput(net, name="softmax")


def _trainer(fused, optimizer="sgd", opt_params=None, no_bias=False, **kw):
    mx.random.seed(7)
    tr = ShardedTrainer(_mlp(no_bias), mesh=make_mesh({"data": len(jax.devices())}),
                        optimizer=optimizer,
                        optimizer_params=opt_params or
                        {"learning_rate": 0.1, "momentum": 0.9},
                        fused_update=fused, **kw)
    tr.bind(data_shapes={"data": (16, 8)},
            label_shapes={"softmax_label": (16,)})
    return tr


def _feeds(n=N_STEPS, seed=0):
    rng = np.random.RandomState(seed)
    return [{"data": rng.rand(16, 8).astype(np.float32),
             "softmax_label": rng.randint(0, 10, (16,)).astype(np.float32)}
            for _ in range(n)]


def _params_bytes(tr):
    return {n: np.asarray(tr._params[n]).tobytes() for n in tr._param_names}


def _fused_state_bytes(tr):
    """Per-param optimizer state of a FUSED trainer, reconstructed from
    the flat buckets through the plan (the layout contract)."""
    plan = tr._fused_plan
    leaves = [jax.tree_util.tree_leaves(tr._opt_state[f"fused:{i}"])
              for i in range(len(plan.buckets))]
    out = {n: [] for n in tr._param_names}
    for li in range(len(leaves[0])):
        per = plan.scatter([leaves[i][li] for i in range(len(plan.buckets))])
        for n, v in per.items():
            out.setdefault(n, []).append(np.asarray(v).tobytes())
    return out


def _unfused_state_bytes(tr):
    out = {}
    for n in tr._param_names:
        out[n] = [np.asarray(x).tobytes()
                  for x in jax.tree_util.tree_leaves(tr._opt_state[n])]
    return out


def _assert_twins(a, b, steps, what=""):
    for si, f in enumerate(steps):
        ha, hb = a.step(f), b.step(f)
        assert np.asarray(ha[0]).tobytes() == np.asarray(hb[0]).tobytes(), \
            f"{what}: heads diverged at step {si}"
        assert _params_bytes(a) == _params_bytes(b), \
            f"{what}: params diverged at step {si}"
        assert _fused_state_bytes(a) == _unfused_state_bytes(b), \
            f"{what}: optimizer state diverged at step {si}"
    assert a.trace_counts["train"] == 1 and b.trace_counts["train"] == 1


KINDS = [
    ("sgd", {"learning_rate": 0.1}, False),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, False),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9,
             "wd": 0.01, "clip_gradient": 0.5}, True),
    ("adam", {"learning_rate": 1e-3}, False),
    # bias-free net: wd_mult uniform -> scalar wd into the kernel
    ("adamw", {"learning_rate": 1e-3, "wd": 0.01}, True),
    # WITH biases wd_mult is 0 on *_bias params -> non-uniform wd rides
    # the per-bucket wd segment vector ("fusedwd:<i>") into the kernel
    # (adam is absent: folded wd has no bitwise fused twin — see the
    # eligibility-gate test)
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9,
             "wd": 0.01, "clip_gradient": 0.5}, False),
    ("adamw", {"learning_rate": 1e-3, "wd": 0.01}, False),
]


@pytest.mark.parametrize("opt,op,no_bias", KINDS,
                         ids=["sgd", "sgd_momentum", "sgd_wd_clip",
                              "adam", "adamw", "sgd_wdvec",
                              "adamw_wdvec"])
def test_fused_is_bitwise_twin_of_unfused(opt, op, no_bias):
    a = _trainer(True, opt, op, no_bias=no_bias)
    b = _trainer(False, opt, op, no_bias=no_bias)
    assert a._fused and not b._fused
    if op.get("wd") and not no_bias:
        # per-param wd -> the segment vectors must exist, one per bucket
        assert any(k.startswith("fusedwd:") for k in a._opt_state)
    _assert_twins(a, b, _feeds(), what=f"{opt}:{op}")


@pytest.mark.parametrize("opt,op", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-3}),
], ids=["sgd_momentum", "adam"])
def test_fused_guard_twin_and_chaos_step_is_bitwise_noop(opt, op):
    a = _trainer(True, opt, op, guard=True)
    b = _trainer(False, opt, op, guard=True)
    feeds = _feeds(4)
    feeds[2] = {k: v.copy() for k, v in feeds[2].items()}
    feeds[2]["data"][0, 0] = np.nan          # chaos: one poisoned sample
    for si, f in enumerate(feeds):
        pre_w, pre_s = _params_bytes(a), _fused_state_bytes(a)
        a.step(f), b.step(f)
        if si == 2:
            # the guard must turn the whole update into a bitwise no-op
            assert _params_bytes(a) == pre_w
            assert _fused_state_bytes(a) == pre_s
        assert _params_bytes(a) == _params_bytes(b), f"step {si}"
        assert _fused_state_bytes(a) == _unfused_state_bytes(b), f"step {si}"


def test_fused_multi_bucket_and_split_params_stay_bitwise():
    """A small byte budget forces several buckets and makes params
    straddle bucket boundaries — gather/scatter must stay exact."""
    kw = dict(grad_bucket_bytes=1024)
    a = _trainer(True, **kw)
    b = _trainer(False, **kw)
    assert len(a._fused_plan.buckets) > 1
    # at least one param is split across buckets
    per_bucket = [{n for n, _, _ in b_} for b_ in a._fused_plan.buckets]
    assert any(per_bucket[i] & per_bucket[i + 1]
               for i in range(len(per_bucket) - 1))
    _assert_twins(a, b, _feeds(), what="multi-bucket")


def test_fused_explicit_comm_hands_buckets_to_kernel_bitwise():
    a = _trainer(True, grad_compression="bf16")
    b = _trainer(False, grad_compression="bf16")
    _assert_twins(a, b, _feeds(), what="explicit-comm")
    rep = analysis.audit_trainer(a, programs=("train",))
    hbm = rep.metrics["trainer.train"]["hbm_passes"]
    assert hbm["max_reads"] == 1 and hbm["max_writes"] == 1


def test_fused_audit_one_read_one_write_and_unfused_baseline():
    rep = analysis.audit_trainer(_trainer(True), programs=("train",))
    assert rep.clean, rep.format_text()
    hbm = rep.metrics["trainer.train"]["hbm_passes"]
    assert len(hbm["buckets"]) == 1
    assert hbm["max_reads"] == 1 and hbm["max_writes"] == 1
    don = rep.metrics["trainer.train"]["donation"]
    assert don["donated_leaves"] == don["aliased_outputs"] > 0

    rep = analysis.audit_trainer(_trainer(False), programs=("train",))
    hbm = rep.metrics["trainer.train"]["hbm_passes"]
    assert hbm["max_reads"] == 5 and hbm["max_writes"] == 5


def test_fused_eligibility_gate():
    # per-param effective wd (bias wd_mult=0) is fused-ELIGIBLE since the
    # wd segment-vector operand landed: the old silent fallback is gone
    op = {"learning_rate": 1e-3, "wd": 0.01}
    tr = _trainer(None, "adamw", op)
    assert tr._fused and not tr._fused_wd_uniform
    assert any(k.startswith("fusedwd:") for k in tr._opt_state)
    # ...and the segment vectors hold exactly wd * wd_mult per element
    vec = np.asarray(tr._opt_state["fusedwd:0"])
    assert set(np.unique(vec)) <= {np.float32(0.0), np.float32(0.01)}

    # per-param lr_mult still cannot fuse
    mx.random.seed(7)
    tr = ShardedTrainer(_mlp(), mesh=make_mesh({"data": len(jax.devices())}),
                        optimizer="adamw", optimizer_params=op,
                        fused_update=True)
    tr.optimizer.lr_mult = {"fc1_weight": 2.0}
    with pytest.raises(MXNetError, match="cannot fuse"):
        tr.bind(data_shapes={"data": (16, 8)},
                label_shapes={"softmax_label": (16,)})

    # adam's FOLDED wd (g + wd*w feeds both moments) has no bitwise
    # fused twin — LLVM's FMA contraction of the fold is context-
    # dependent.  Silent fallback on default, error when forced.  This
    # also closes a latent hole: the old gate let uniform-wd adam fuse.
    assert not _trainer(None, "adam", op)._fused
    with pytest.raises(MXNetError, match="use adamw"):
        _trainer(True, "adam", op)

    # env opt-out wins over the default
    os.environ["MXNET_TPU_FUSED_UPDATE"] = "0"
    try:
        assert not _trainer(None)._fused
    finally:
        del os.environ["MXNET_TPU_FUSED_UPDATE"]
    assert _trainer(None)._fused


def test_fused_kind_detection():
    from mxnet_tpu.optimizer import SGD, Adam, AdamW
    assert fu.fused_kind(SGD(learning_rate=0.1)) == "sgd"
    assert fu.fused_kind(SGD(learning_rate=0.1, momentum=0.9)) == "sgd_momentum"
    assert fu.fused_kind(Adam()) == "adam"
    assert fu.fused_kind(AdamW()) == "adamw"

    class NotSGD(SGD):
        def _functional_step(self, *a, **k):  # pragma: no cover
            raise NotImplementedError
    # overridden update rule → no fused twin, silent fallback
    assert fu.fused_kind(NotSGD(learning_rate=0.1)) is None


def test_pallas_kernel_matches_reference():
    """interpret-mode Pallas vs the jnp reference, every kind, with the
    guard/mult operands exercised in both accept and reject states.

    This guards the kernel's FORMULA (rtol 1e-5, atol 5e-6 on O(1)
    operands — a wrong term or constant is off by 1e-3 or more) — not
    its rounding: interpret mode wraps the kernel ops in block slicing, so
    its CPU fusion shape differs from the plain jitted reference and
    LLVM's backend FMA contraction picks different multiplies to fuse
    (the hazard the trainer's while-loop lowering removes — see
    ``_materialized_reference``; the trainer-level fused-vs-unfused pins
    above ARE bitwise).  How far that drifts is a property of the
    installed XLA:CPU (1 ulp once, 4 ulp for adam on jaxlib 0.9.0), so a
    tighter pin here arbitrates the wrong compiler.  The arbiter of the
    compiled kernel's rounding is the chip: ``chip_smoke.py`` compares
    the Mosaic kernel to ``reference_update`` there (0 ulp on a v5e for
    adam and sgd_momentum, PR 21).  The ``ok=False`` reject path must
    still be a bitwise no-op."""
    rng = np.random.RandomState(3)
    n = 618                      # deliberately not a multiple of 8*128
    g = jnp.asarray(rng.randn(n).astype(np.float32))
    w = jnp.asarray(rng.randn(n).astype(np.float32))
    s1 = jnp.asarray(rng.randn(n).astype(np.float32) * 1e-2)
    s2 = jnp.asarray(np.abs(rng.randn(n).astype(np.float32)) * 1e-3)
    cases = [
        ("sgd", (), dict(wd=0.01, rescale_grad=0.25)),
        ("sgd_momentum", (s1,), dict(momentum=0.9, wd=0.01,
                                     clip_gradient=0.5, rescale_grad=0.25)),
        ("adam", (s1, s2), dict(beta1=0.9, beta2=0.999, epsilon=1e-8,
                                wd=0.01, rescale_grad=0.25)),
        ("adamw", (s1, s2), dict(beta1=0.9, beta2=0.999, epsilon=1e-8,
                                 rescale_grad=0.25)),
    ]
    # the wd segment-vector operand (per-element effective wd)
    wdv = jnp.asarray((rng.rand(n) < 0.5).astype(np.float32) * 0.01)
    cases += [
        ("sgd", (), dict(rescale_grad=0.25, wd_vec=wdv)),
        ("sgd_momentum", (s1,), dict(momentum=0.9, clip_gradient=0.5,
                                     rescale_grad=0.25, wd_vec=wdv)),
        ("adam", (s1, s2), dict(beta1=0.9, beta2=0.999, epsilon=1e-8,
                                rescale_grad=0.25, wd_vec=wdv)),
        ("adamw", (s1, s2), dict(beta1=0.9, beta2=0.999, epsilon=1e-8,
                                 rescale_grad=0.25, wd_vec=wdv)),
    ]
    for kind, state, hyper in cases:
        scalars = (np.float32(0.05),) if kind != "adamw" \
            else (np.float32(0.05), np.float32(1e-4))
        for mult in (None, np.float32(0.5)):
            for oks in ((None,), (True, False)):
                # jit, not eager: eager runs every op as its own XLA
                # program where the backend never FMA-contracts — the
                # spec is the jitted form.  ``ok`` is an operand, so
                # accept and reject share one program.
                def both(g, w, s, ok):
                    kw = dict(kind=kind, mult=mult, ok=ok, **hyper)
                    return (fu.reference_update(g, w, s, scalars, **kw),
                            fu.pallas_update(g, w, s, scalars, **kw))
                run = jax.jit(both)
                for ok in oks:
                    ref, pal = run(g, w, state,
                                   None if ok is None else jnp.asarray(ok))
                    for r, p in zip(ref, pal):
                        np.testing.assert_allclose(
                            np.asarray(p), np.asarray(r), rtol=1e-5,
                            atol=5e-6, err_msg=str((kind, mult, ok)))
                    if ok is False:  # reject: bitwise no-op on BOTH paths
                        assert np.asarray(ref[0]).tobytes() == \
                            np.asarray(w).tobytes()
                        assert np.asarray(pal[0]).tobytes() == \
                            np.asarray(w).tobytes()


def test_tpu_lowering_under_a_mesh():
    """GSPMD cannot partition a Mosaic kernel, so under a multi-device
    mesh the TPU lowering must open its own ``shard_map`` (cross-lowered
    here, no chip needed).  Found when the data=4 LM step was first
    compiled for a v5e host: every CPU-mesh test passed because the
    default lowering is plain jnp."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()), ("data",))
    aval = jax.ShapeDtypeStruct((5000,), jnp.float32,
                                sharding=NamedSharding(mesh, P()))

    def step(mesh_):
        return jax.jit(lambda g, w, m, v: fu.fused_update(
            g, w, (m, v), (np.float32(0.01),), kind="adam", beta1=0.9,
            beta2=0.999, epsilon=1e-8, mesh=mesh_)).trace(*[aval] * 4)

    assert "tpu_custom_call" in step(mesh).lower(
        lowering_platforms=("tpu",)).as_text()
    # the refusal this guards against, so the test cannot rot silently
    with pytest.raises(NotImplementedError, match="shard_map"):
        step(None).lower(lowering_platforms=("tpu",))
    # one opaque eqn either way: the auditor's fusion boundary holds
    assert [e.primitive.name for e in step(mesh).jaxpr.eqns] == \
        ["mxtpu_fused_update"]


def test_plan_round_trip_and_reduce_grads_mirror():
    shapes = {"a": (10, 32), "b": (32,), "c": (32, 8), "d": (10,)}
    plan = fu.build_plan(["a", "b", "c", "d"], shapes, bucket_bytes=1024)
    rng = np.random.RandomState(0)
    tree = {n: jnp.asarray(rng.randn(*s).astype(np.float32))
            for n, s in shapes.items()}
    buckets = [plan.gather(tree, i) for i in range(len(plan.buckets))]
    assert sum(plan.bucket_sizes) == sum(int(np.prod(s))
                                         for s in shapes.values())
    back = plan.scatter(buckets)
    for n in shapes:
        assert np.asarray(back[n]).tobytes() == np.asarray(tree[n]).tobytes()
