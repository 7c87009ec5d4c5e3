"""The trainer's optimizer update, and checkpoints written while its
state lived in flat buckets.

The update runs each parameter's optimizer step in the parameter's own
shape, so XLA fuses it in the layout the parameter lives in.  Under test:
it matches the imperative optimizer that ``Module`` drives, for every
optimizer kind, with per-parameter weight-decay and learning-rate
multipliers; a guarded poisoned step leaves the whole update a bitwise
no-op; on a ``data`` mesh the state stays replicated; and a checkpoint
whose optimizer state was kept in flat f32 buckets
(``opt:fused:<i>:<leaf>``, what the earlier fused update wrote) restores
and continues.  ``tests/golden/bucketed_ckpt/`` holds such checkpoints,
written by that update's own code (``make.py`` there says how).
"""
import os

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu import symbol as S
from mxnet_tpu.io import DataBatch
from mxnet_tpu.parallel import ShardedTrainer, make_mesh

N_STEPS = 3
GOLDEN = os.path.join(os.path.dirname(__file__), "golden", "bucketed_ckpt")


def _mlp(no_bias=False):
    d = S.Variable("data")
    net = S.FullyConnected(d, num_hidden=32, name="fc1", no_bias=no_bias)
    net = S.Activation(net, act_type="relu")
    net = S.FullyConnected(net, num_hidden=10, name="fc2", no_bias=no_bias)
    return S.SoftmaxOutput(net, name="softmax")


def _trainer(optimizer="sgd", opt_params=None, no_bias=False, ndev=None,
             **kw):
    mx.random.seed(7)
    devs = jax.devices()[:ndev] if ndev else jax.devices()
    tr = ShardedTrainer(_mlp(no_bias), mesh=make_mesh({"data": len(devs)},
                                                      devs),
                        optimizer=optimizer,
                        optimizer_params=opt_params or
                        {"learning_rate": 0.1, "momentum": 0.9}, **kw)
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


def _state_bytes(tr):
    out = {}
    for n in tr._param_names:
        out[n] = [np.asarray(x).tobytes()
                  for x in jax.tree_util.tree_leaves(tr._opt_state[n])]
    return out


KINDS = [
    ("sgd", {"learning_rate": 0.1}, False),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}, False),
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9,
             "wd": 0.01, "clip_gradient": 0.5}, True),
    ("adam", {"learning_rate": 1e-3}, False),
    # bias-free net: every param decays
    ("adamw", {"learning_rate": 1e-3, "wd": 0.01}, True),
    # WITH biases: wd_mult is 0 on *_bias params, so each parameter's
    # step takes its own wd (adam folds it into the gradient)
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9,
             "wd": 0.01, "clip_gradient": 0.5}, False),
    ("adamw", {"learning_rate": 1e-3, "wd": 0.01}, False),
    ("adam", {"learning_rate": 1e-3, "wd": 0.01}, False),
]
KIND_IDS = ["sgd", "sgd_momentum", "sgd_wd_clip", "adam", "adamw",
            "sgd_wd_bias", "adamw_wd_bias", "adam_wd_bias"]


def _assert_tracks_module(tr, sym, feeds, opt, op, lr_mult=None):
    """Step ``tr`` and a ``Module`` (executor + the imperative optimizer,
    one gradient and one ``update`` a parameter) from the same
    parameters on the same batches; the parameters agree after every
    step."""
    arg, aux = tr.get_params()
    shape = feeds[0]["data"].shape
    mod = mx.mod.Module(sym, context=mx.cpu())
    mod.bind(data_shapes=[("data", shape)],
             label_shapes=[("softmax_label", (shape[0],))])
    mod.init_params(arg_params=arg, aux_params=aux)
    mod.init_optimizer(optimizer=opt, optimizer_params=dict(op))
    if lr_mult:
        mod._optimizer.set_lr_mult(lr_mult)
    for si, f in enumerate(feeds):
        tr.step(f)
        mod.forward_backward(DataBatch(data=[nd.array(f["data"])],
                                       label=[nd.array(f["softmax_label"])]))
        mod.update()
        want = mod.get_params()[0]
        for n in tr._param_names:
            np.testing.assert_allclose(
                np.asarray(tr._params[n]), want[n].asnumpy(), rtol=2e-5,
                atol=2e-6, err_msg=f"{opt} {n} step {si}")
    assert tr.trace_counts["train"] == 1


@pytest.mark.parametrize("opt,op,no_bias", KINDS, ids=KIND_IDS)
def test_update_tracks_the_imperative_optimizer(opt, op, no_bias):
    tr = _trainer(opt, op, no_bias=no_bias, ndev=1)
    # one state entry a parameter, in its shape
    assert list(tr._opt_state) == tr._param_names
    for n in tr._param_names:
        for leaf in jax.tree_util.tree_leaves(tr._opt_state[n]):
            assert leaf.shape == tr._params[n].shape
    _assert_tracks_module(tr, _mlp(no_bias), _feeds(), opt, op)


def _conv_net():
    """Conv weights of the tiled kinds ([O, I, 3, 3], [O, I, 1, 1],
    [O, 3, 7, 7]), BatchNorm gamma/beta, biases and an FC weight."""
    d = S.Variable("data")
    net = S.Convolution(d, num_filter=8, kernel=(7, 7), stride=(2, 2),
                        pad=(3, 3), name="conv0")
    net = S.BatchNorm(net, name="bn0")
    net = S.Activation(net, act_type="relu")
    net = S.Convolution(net, num_filter=8, kernel=(3, 3), pad=(1, 1),
                        name="conv1")
    net = S.BatchNorm(net, name="bn1")
    net = S.Activation(net, act_type="relu")
    net = S.Convolution(net, num_filter=16, kernel=(1, 1), name="conv2",
                        no_bias=True)
    net = S.Pooling(net, global_pool=True, kernel=(1, 1), pool_type="avg")
    net = S.FullyConnected(net, num_hidden=10, name="fc")
    return S.SoftmaxOutput(net, name="softmax")


def test_conv_net_with_per_parameter_multipliers_tracks_the_optimizer():
    """BatchNorm's gamma/beta and the biases take wd_mult 0, one weight
    its own lr_mult: each parameter's step takes its own scalars."""
    op = {"learning_rate": 0.1, "momentum": 0.9, "wd": 0.01}
    mx.random.seed(7)
    tr = ShardedTrainer(_conv_net(), mesh=make_mesh({"data": 1},
                                                    jax.devices()[:1]),
                        optimizer="sgd", optimizer_params=op)
    tr.optimizer.lr_mult = {"conv1_weight": 2.0}
    tr.bind(data_shapes={"data": (4, 3, 16, 16)},
            label_shapes={"softmax_label": (4,)})
    assert {tr._wd_mult[n] for n in tr._param_names} == {0.0, 1.0}
    assert tr._lr_mult["conv1_weight"] == 2.0
    rng = np.random.RandomState(1)
    feeds = [{"data": rng.rand(4, 3, 16, 16).astype(np.float32),
              "softmax_label": rng.randint(0, 10, (4,)).astype(np.float32)}
             for _ in range(N_STEPS)]
    _assert_tracks_module(tr, _conv_net(), feeds, "sgd", op,
                          lr_mult={"conv1_weight": 2.0})


@pytest.mark.parametrize("opt,op", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adam", {"learning_rate": 1e-3}),
], ids=["sgd_momentum", "adam"])
def test_guarded_poisoned_step_is_a_bitwise_noop(opt, op):
    """With the guard on, clean steps match an unguarded run bit for
    bit, a step whose gradients are not finite leaves every parameter
    and every state leaf as it was, and the steps after it go on."""
    a = _trainer(opt, op, guard=True)
    b = _trainer(opt, op)
    feeds = _feeds(4)
    for f in feeds[:2]:
        a.step(f), b.step(f)
        assert _params_bytes(a) == _params_bytes(b)
        assert _state_bytes(a) == _state_bytes(b)
    bad = {k: v.copy() for k, v in feeds[2].items()}
    bad["data"][0, 0] = np.nan          # chaos: one poisoned sample
    pre_w, pre_s = _params_bytes(a), _state_bytes(a)
    a.step(bad)
    assert _params_bytes(a) == pre_w
    assert _state_bytes(a) == pre_s
    a.step(feeds[3])
    assert _params_bytes(a) != pre_w
    for n in a._param_names:
        assert np.isfinite(np.asarray(a._params[n])).all(), n


def test_update_stays_replicated_on_a_data_mesh():
    """On a ``data`` 2 mesh each parameter's update runs on the
    replicated parameter in its own shape, and the new weights and
    state come back replicated, matching a one-device run."""
    def run(ndev):
        tr = _trainer(ndev=ndev)
        for f in _feeds():
            tr.step(f)
        return tr
    two = run(2)
    for n in two._param_names:
        for arr in (two._params[n], two._opt_state[n]):
            assert arr.sharding.is_fully_replicated, n
            assert arr.shape == two._params[n].shape
    one = run(1)
    for n in two._param_names:
        np.testing.assert_allclose(np.asarray(two._params[n]),
                                   np.asarray(one._params[n]),
                                   rtol=1e-5, atol=1e-6)


def _bucketed_checkpoint(tr, mgr, step):
    """Save ``tr`` the way the fused update did before its state was
    kept per parameter: every optimizer leaf as flat f32 buckets
    ``opt:fused:<i>:<leaf>``, the parameters in reversed order cut
    greedily at ``grad_bucket_bytes``."""
    from mxnet_tpu.parallel.collectives import plan_buckets
    arrays = dict(tr._state_arrays())
    order = list(reversed(tr._param_names))
    buckets = plan_buckets([tr._params[n].size for n in order], 4,
                           tr.grad_bucket_bytes)
    n_leaves = len(jax.tree_util.tree_leaves(tr._opt_state[order[0]]))
    for li in range(n_leaves):
        flat = {n: np.asarray(arrays.pop(f"opt:{n}:{li}")).reshape(-1)
                for n in order}
        for i, bucket in enumerate(buckets):
            arrays[f"opt:fused:{i}:{li}"] = np.concatenate(
                [flat[order[idx]][s0:s1] for idx, s0, s1 in bucket])
    return mgr.save(step, arrays, meta=tr._state_meta(), blocking=True)


@pytest.mark.parametrize("opt,op", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9}),
    ("adamw", {"learning_rate": 1e-3, "wd": 0.01}),
], ids=["sgd_momentum", "adamw"])
def test_bucketed_checkpoint_restores_and_continues_bitwise(tmp_path, opt,
                                                            op):
    from mxnet_tpu.checkpoint import CheckpointManager
    feeds = _feeds(5)
    # several buckets, parameters straddling their boundaries
    kw = dict(grad_bucket_bytes=1024)
    ref = _trainer(opt, op, **kw)
    for f in feeds:
        ref.step(f)

    tr = _trainer(opt, op, **kw)
    for f in feeds[:2]:
        tr.step(f)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    _bucketed_checkpoint(tr, mgr, 2)
    mgr.close()

    back = _trainer(opt, op, **kw)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    _, step = back.restore_state(mgr)
    mgr.close()
    assert step == 2
    assert _state_bytes(back) == _state_bytes(tr)
    for f in feeds[2:]:
        back.step(f)
    assert _params_bytes(back) == _params_bytes(ref)
    assert _state_bytes(back) == _state_bytes(ref)


#: the runs of ``tests/golden/bucketed_ckpt/make.py``: optimizer,
#: its parameters, whether the net has biases
GOLDEN_RUNS = {
    "sgd": ("sgd", {"learning_rate": 0.1, "wd": 0.01}),
    "sgd_momentum": ("sgd", {"learning_rate": 0.1, "momentum": 0.9,
                             "wd": 0.01}),
    "adam": ("adam", {"learning_rate": 1e-3}),
    "adamw": ("adamw", {"learning_rate": 1e-3, "wd": 0.01}),
}


@pytest.mark.parametrize("run", sorted(GOLDEN_RUNS))
def test_checkpoint_written_by_the_bucketed_update_restores(tmp_path, run):
    """A checkpoint the fused update's own code wrote (two steps on a
    ``data`` 1 mesh, ``grad_bucket_bytes`` 1024: several buckets,
    parameters straddling them, and per-bucket wd vectors where biases
    take no decay) restores into per-parameter state equal to two
    steps of this trainer, and the next three steps follow a run that
    never saved."""
    import shutil
    from mxnet_tpu.checkpoint import CheckpointManager
    opt, op = GOLDEN_RUNS[run]
    kw = dict(grad_bucket_bytes=1024, ndev=1)
    feeds = _feeds(5)
    ref = _trainer(opt, op, **kw)
    for f in feeds[:2]:
        ref.step(f)
    at2 = {n: [np.asarray(x).copy()
               for x in jax.tree_util.tree_leaves(ref._opt_state[n])]
           for n in ref._param_names}
    for f in feeds[2:]:
        ref.step(f)

    src = os.path.join(GOLDEN, run)
    assert os.path.isdir(src), src
    shutil.copytree(src, tmp_path / "ckpt")
    back = _trainer(opt, op, **kw)
    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    _, step = back.restore_state(mgr)
    mgr.close()
    assert step == 2 and back._num_update == 2
    for n in back._param_names:
        leaves = jax.tree_util.tree_leaves(back._opt_state[n])
        assert len(leaves) == len(at2[n])
        for got, want in zip(leaves, at2[n]):
            assert got.shape == back._params[n].shape
            np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6,
                                       atol=1e-7, err_msg=n)
    for f in feeds[2:]:
        back.step(f)
    for n in back._param_names:
        np.testing.assert_allclose(np.asarray(back._params[n]),
                                   np.asarray(ref._params[n]), rtol=1e-6,
                                   atol=1e-7, err_msg=n)
