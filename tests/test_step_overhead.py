"""Step-overhead guarantees: zero steady-state retraces, donation-safe
reads and sync-free metrics.

The PR-2 contract (docs/perf.md "step overhead attribution"):

* a static-shape train loop traces each compiled program EXACTLY once —
  ``trainer.trace_counts`` stays at 1 while ``dispatch_count`` climbs,
  and ``assert_steady_state()`` passes (the ``dispatch_count == 1``
  per-program contract pipeline_spmd asserts);
* a signature change warns (default) or raises (``strict_retrace``)
  naming the offending input instead of silently recompiling;
* reading an NDArray whose buffer was donated to a compiled step raises
  a descriptive RuntimeError naming the donating step, not an opaque
  jax "deleted buffer" error;
* AsyncMetric snapshots device values at update() time, so a later
  donation/deletion of the source buffer cannot corrupt the metric.
"""
import logging

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.base import MXNetError
from mxnet_tpu.metric import AsyncMetric
from mxnet_tpu.parallel import ShardedTrainer, make_mesh


def _fc_trainer(batch=16, feat=8, hidden=4):
    net = mx.symbol.FullyConnected(data=mx.symbol.Variable("data"),
                                   num_hidden=hidden, name="fc")
    net = mx.symbol.SoftmaxOutput(data=net, name="softmax")
    tr = ShardedTrainer(net, mesh=make_mesh({"data": 1}, jax.devices()[:1]),
                        optimizer="sgd",
                        optimizer_params={"learning_rate": 0.01})
    tr.bind(data_shapes={"data": (batch, feat)},
            label_shapes={"softmax_label": (batch,)})
    return tr


def _fc_batch(rng, batch=16, feat=8, hidden=4):
    return {"data": rng.randn(batch, feat).astype(np.float32),
            "softmax_label": rng.randint(0, hidden, (batch,))
            .astype(np.float32)}


# ---------------------------------------------------------------------------
# retrace guards
# ---------------------------------------------------------------------------

def test_no_retrace_fc_steady_state():
    """5 static-shape steps: the train program traces once, dispatches 5
    times, and assert_steady_state holds."""
    tr = _fc_trainer()
    rng = np.random.RandomState(0)
    for _ in range(5):
        tr.step(_fc_batch(rng))
    assert tr.trace_counts["train"] == 1, tr.trace_counts
    assert tr.dispatch_count == 5
    tr.assert_steady_state()


def test_no_retrace_resnet_steady_state():
    """Zero-recompilation contract on a real ResNet step loop (n=1 ->
    8-layer CIFAR ResNet: conv/BN/residual stack with aux state)."""
    sym = models.get_symbol("resnet-28-small", num_classes=4, n=1)
    tr = ShardedTrainer(sym, mesh=make_mesh({"data": 1}, jax.devices()[:1]),
                        optimizer="sgd",
                        optimizer_params={"learning_rate": 0.01})
    tr.bind(data_shapes={"data": (4, 3, 28, 28)},
            label_shapes={"softmax_label": (4,)})
    rng = np.random.RandomState(9)
    for _ in range(5):
        tr.step({"data": rng.rand(4, 3, 28, 28).astype(np.float32),
                 "softmax_label": rng.randint(0, 4, (4,))
                 .astype(np.float32)})
    assert tr.trace_counts["train"] == 1, tr.trace_counts
    assert tr.dispatch_count == 5
    tr.assert_steady_state()


def test_no_retrace_transformer_lm_steady_state():
    """Same zero-recompilation contract on the transformer-LM step loop
    (reshape-baking symbol — the shape-sensitive worst case)."""
    B, L, V = 8, 16, 50
    sym = models.get_symbol("transformer-lm", vocab_size=V, num_layers=2,
                            d_model=32, heads=2, batch_size=B, seq_len=L)
    tr = ShardedTrainer(sym, mesh=make_mesh({"data": 1}, jax.devices()[:1]),
                        optimizer="sgd",
                        optimizer_params={"learning_rate": 0.01})
    tr.bind(data_shapes={"data": (B, L)},
            label_shapes={"softmax_label": (B, L)})
    rng = np.random.RandomState(1)
    for _ in range(5):
        tr.step({"data": rng.randint(0, V, (B, L)).astype(np.float32),
                 "softmax_label": rng.randint(0, V, (B, L))
                 .astype(np.float32)})
    assert tr.trace_counts["train"] == 1, tr.trace_counts
    assert tr.dispatch_count == 5
    tr.assert_steady_state()


def test_retrace_warns_by_default_and_steady_state_catches(caplog):
    tr = _fc_trainer()
    rng = np.random.RandomState(2)
    tr.step(_fc_batch(rng))
    with caplog.at_level(logging.WARNING):
        tr.step(_fc_batch(rng, batch=8))   # shape change: warn, not raise
    assert any("signature changed" in r.message for r in caplog.records)
    assert tr.trace_counts["train"] == 2   # it really did retrace
    with pytest.raises(MXNetError, match="retraced"):
        tr.assert_steady_state()


def test_strict_retrace_raises_naming_input():
    tr = _fc_trainer()
    tr.strict_retrace = True
    rng = np.random.RandomState(3)
    tr.step(_fc_batch(rng))
    with pytest.raises(MXNetError, match="data"):
        tr.step(_fc_batch(rng, batch=8))
    # the guard fired BEFORE dispatch: no second trace happened
    assert tr.trace_counts["train"] == 1


def test_same_signature_reseen_is_free():
    """Alternating between two already-seen signatures neither warns nor
    grows the recorded signature set."""
    tr = _fc_trainer()
    rng = np.random.RandomState(4)
    tr.step(_fc_batch(rng))
    tr.step(_fc_batch(rng, batch=8))       # second signature (warns once)
    for _ in range(3):
        tr.step(_fc_batch(rng))
        tr.step(_fc_batch(rng, batch=8))
    assert len(tr._train_sigs) == 2
    assert tr.trace_counts["train"] == 2   # one trace per distinct shape


def test_no_retrace_fused_metric_fit_loop():
    """Regression: the fused-accuracy carry must be a dtype+sharding fixed
    point of the step program.  An uncommitted host int32 seed (widened to
    int64 by the bool-sum fold under x64) made batch 2 recompile the whole
    train program — caught by these counters, pinned here."""
    from mxnet_tpu.io import NDArrayIter
    rng = np.random.RandomState(8)
    X = rng.randn(64, 8).astype(np.float32)
    y = rng.randint(0, 4, 64).astype(np.float32)
    tr = _fc_trainer()
    tr.fit(NDArrayIter(X, y, batch_size=16), num_epoch=3)
    assert tr.trace_counts["train_acc"] == 1, tr.trace_counts
    assert tr.trace_counts["train"] == 0
    tr.assert_steady_state()


# ---------------------------------------------------------------------------
# donation-safe reads
# ---------------------------------------------------------------------------

def test_donated_buffer_read_raises_descriptive():
    """asnumpy()/asscalar() on a donated-then-consumed buffer must name
    the donating step.  CPU backends may silently skip real donation, so
    the deletion is forced explicitly — the guard path is identical."""
    a = mx.nd.array(np.ones((2, 2), np.float32))
    a.mark_donated("ShardedTrainer.step #7 (donate_argnums: params, aux, "
                   "opt_state)")
    a._chunk.data.delete()
    with pytest.raises(RuntimeError, match=r"ShardedTrainer\.step #7"):
        a.asnumpy()
    with pytest.raises(RuntimeError, match="donated"):
        a.wait_to_read()
    s = mx.nd.array(np.ones((1,), np.float32))
    s.mark_donated("ShardedTrainer.step #3 (donate_argnums: params, aux, "
                   "opt_state)")
    s._chunk.data.delete()
    with pytest.raises(RuntimeError, match=r"ShardedTrainer\.step #3"):
        s.asscalar()


def test_deleted_buffer_without_owner_still_descriptive():
    """Deletion with no recorded owner falls back to the most recent
    donation note — still a descriptive error, never a bare jax one."""
    a = mx.nd.array(np.ones((3,), np.float32))
    a._chunk.data.delete()
    with pytest.raises(RuntimeError, match="donate"):
        a.asnumpy()


def test_live_params_stay_readable_through_donating_steps():
    """The donating step consumes its OWN previous outputs; the trainer's
    current params must stay readable after many steps."""
    tr = _fc_trainer()
    rng = np.random.RandomState(5)
    for _ in range(4):
        tr.step(_fc_batch(rng))
    args, _ = tr.get_params()
    for name, arr in args.items():
        v = arr.asnumpy()
        assert np.all(np.isfinite(v)), name


# ---------------------------------------------------------------------------
# sync-free metric path
# ---------------------------------------------------------------------------

def test_async_metric_snapshots_survive_buffer_reuse():
    """AsyncMetric defers the host fetch but snapshots the device value
    at update() time: the prefetch path ref-swaps the NEXT batch into the
    same NDArray handles before the deferred drain runs, and that reuse
    must not corrupt the deferred result."""
    labels_np = np.array([0., 1., 1., 0.], np.float32)
    preds_np = np.array([[.9, .1], [.2, .8], [.6, .4], [.3, .7]], np.float32)
    lbl, pred = mx.nd.array(labels_np), mx.nd.array(preds_np)
    m = AsyncMetric("acc", period=16)
    m.update([lbl], [pred])
    # the staged next batch overwrites the handles (all predictions now
    # wrong) before the deferred drain — exactly what load_data_batch's
    # ref-swap does between update() and get()
    lbl._write(1.0 - labels_np)
    pred._write(preds_np[:, ::-1].copy())
    name, value = m.get()
    expect = float(np.mean(np.argmax(preds_np, 1) == labels_np))
    assert name == "accuracy" and abs(value - expect) < 1e-6


def test_async_metric_matches_eager_inner():
    rng = np.random.RandomState(6)
    eager = mx.metric.create("acc")
    deferred = AsyncMetric("acc", period=5)
    for _ in range(12):
        lbl = rng.randint(0, 3, (8,)).astype(np.float32)
        pred = rng.rand(8, 3).astype(np.float32)
        eager.update([mx.nd.array(lbl)], [mx.nd.array(pred)])
        deferred.update([mx.nd.array(lbl)], [mx.nd.array(pred)])
    assert deferred.get() == eager.get()
    deferred.reset()
    assert deferred.num_inst == 0
