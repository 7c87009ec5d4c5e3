"""The training step names its phases and its collectives' mesh axes.

The compiled step's HLO ``op_name``s hold ``forward`` (the function
``jax.vjp`` differentiates: ``jvp(forward)/<node>/...``), its transpose
``transpose(jvp(forward))`` (the backward, a remat scope's recomputed
forward inside it), ``update`` (all optimizer work), ``guard`` and
``grad_reduce`` where those run, and each symbol node's name.  The names
are metadata only: with metadata stripped the optimized HLO is the one
the same trainer compiles without them.  ``collective_inventory`` reads
each collective's mesh axes from the compiled program's replica groups,
and a recording ``step.dispatch`` span carries them as ``collectives``.
"""
import contextlib
import re

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import models, telemetry
from mxnet_tpu import symbol as S
from mxnet_tpu.parallel import ShardedTrainer, make_mesh, megatron_rules
from mxnet_tpu.parallel.collectives import (collective_inventory,
                                            collectives_by_axes,
                                            replica_groups)
from mxnet_tpu.telemetry import tracing


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def _conv_net():
    d = S.Variable("data")
    net = S.Convolution(d, num_filter=8, kernel=(3, 3), pad=(1, 1),
                        name="conv1")
    net = S.BatchNorm(net, name="bn1")
    net = S.Activation(net, act_type="relu", name="relu1")
    net = S.Convolution(net, num_filter=8, kernel=(3, 3), pad=(1, 1),
                        name="conv2")
    net = S.Pooling(net, global_pool=True, kernel=(1, 1), pool_type="avg",
                    name="pool")
    net = S.FullyConnected(net, num_hidden=10, name="fc")
    return S.SoftmaxOutput(net, name="softmax")


def _conv_trainer(**kw):
    mx.random.seed(7)
    tr = ShardedTrainer(_conv_net(), mesh=make_mesh({"data": 1},
                                                    jax.devices()[:1]),
                        optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1,
                                          "momentum": 0.9}, **kw)
    tr.bind(data_shapes={"data": (4, 3, 8, 8)},
            label_shapes={"softmax_label": (4,)})
    return tr


def _lm_trainer(mesh_shape=None, **kw):
    mx.random.seed(7)
    sym = models.get_symbol("transformer-lm", vocab_size=32, num_layers=2,
                            d_model=16, heads=2, batch_size=4, seq_len=8,
                            loss_head=True, remat=True)
    mesh_shape = mesh_shape or {"data": 1}
    n = int(np.prod(list(mesh_shape.values())))
    tr = ShardedTrainer(sym, mesh=make_mesh(mesh_shape, jax.devices()[:n]),
                        optimizer="adam",
                        optimizer_params={"learning_rate": 1e-3}, **kw)
    tr.bind(data_shapes={"data": (4, 8)},
            label_shapes={"softmax_label": (4, 8)})
    return tr


def _compiled_text(tr):
    return tr.trace_program("train")[0].lower().compile().as_text()


def _without_metadata(text):
    """The program without its ops' metadata and the table of source
    locations the metadata points into."""
    return re.sub(r",? metadata=\{[^}]*\}", "",
                  text.split("\nFileNames")[0])


def _op_names(text):
    return re.findall(r'op_name="([^"]*)"', text)


def _unscoped_text(build, monkeypatch):
    """The same trainer's compiled step with every named scope off."""
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        return _compiled_text(build())


def test_fused_conv_step_names_forward_backward_update_and_nodes(
        monkeypatch):
    tr = _conv_trainer()
    text = _compiled_text(tr)
    names = _op_names(text)
    assert any("/jvp(forward)/conv1/" in n for n in names)
    assert any("/transpose(jvp(forward))/conv2/" in n for n in names)
    assert any("/jvp(forward)/bn1/" in n for n in names)
    # each parameter's optimizer step is update's
    assert any("/update/" in n for n in names)
    assert not any("/guard/" in n for n in names)
    assert _without_metadata(text) == _without_metadata(
        _unscoped_text(_conv_trainer, monkeypatch))


def test_remat_adam_step_names_the_recomputed_forward_inside_backward(
        monkeypatch):
    tr = _lm_trainer()
    text = _compiled_text(tr)
    names = _op_names(text)
    assert any(re.search(r"/jvp\(forward\)/layer0_ffn1/", n)
               and "transpose(" not in n for n in names)
    # remat: the block's forward is recomputed inside the transpose
    assert any(re.search(r"/transpose\(jvp\(forward\)\)/jvp\(forward\)/"
                         r"checkpoint/layer1_q/", n) for n in names)
    assert any("/update/" in n for n in names)
    assert _without_metadata(text) == _without_metadata(
        _unscoped_text(_lm_trainer, monkeypatch))


def test_guard_and_explicit_reduction_have_their_own_scopes(monkeypatch):
    def build():
        return _conv_trainer(guard=True)
    names = _op_names(_compiled_text(build()))
    assert any("/guard/" in n for n in names)

    def mlp_explicit():
        mx.random.seed(7)
        d = S.Variable("data")
        net = S.FullyConnected(d, num_hidden=16, name="fc1")
        net = S.SoftmaxOutput(S.FullyConnected(net, num_hidden=4, name="fc2"),
                              name="softmax")
        tr = ShardedTrainer(net, mesh=make_mesh({"data": 2},
                                                jax.devices()[:2]),
                            optimizer="sgd",
                            optimizer_params={"learning_rate": 0.1},
                            grad_compression="bf16")
        tr.bind(data_shapes={"data": (8, 8)},
                label_shapes={"softmax_label": (8,)})
        return tr
    text = _compiled_text(mlp_explicit())
    assert any("/grad_reduce/" in n for n in _op_names(text))
    assert _without_metadata(text) == _without_metadata(
        _unscoped_text(mlp_explicit, monkeypatch))


def test_inventory_names_model_collectives_in_forward_and_data_gradients():
    tr = _lm_trainer({"data": 2, "model": 2}, rules=megatron_rules())
    tr.compile()
    inv = tr.collective_inventory["train"]
    assert inv
    model_fwd = [n for n, r in inv.items() if r["axes"] == ("model",)
                 and "jvp(forward)" in r["op_name"]
                 and "transpose(" not in r["op_name"]]
    data_grad = [n for n, r in inv.items() if r["axes"] == ("data",)
                 and "transpose(jvp(forward))" in r["op_name"]]
    assert model_fwd and data_grad
    assert all(r["kind"].startswith("all-reduce") and r["bytes"] > 0
               for r in inv.values())
    # every collective of the program text is listed, under its own name
    text = tr._aot["train"].as_text()
    assert set(inv) == set(re.findall(
        r"%([\w.\-]+) = [^\n]*? all-reduce\(", text))
    by_axis = collectives_by_axes(inv)
    assert set(by_axis) == {"model", "data"}
    assert sum(len(v) for v in by_axis.values()) == len(inv)
    assert by_axis == tr._collective_axes["train"]


def test_replica_groups_both_forms_and_the_mesh_device_order():
    assert replica_groups("{{0,1},{2,3}}", 4) == [[0, 1], [2, 3]]
    assert replica_groups("[2,2]<=[4]", 4) == [[0, 1], [2, 3]]
    assert replica_groups("[2,2]<=[2,2]T(1,0)", 4) == [[0, 2], [1, 3]]
    assert replica_groups("[1,4]<=[4]", 4) == [[0, 1, 2, 3]]
    assert replica_groups("[4,2]<=[2,2,2]T(2,1,0)", 8) == [
        [0, 4], [2, 6], [1, 5], [3, 7]]
    assert replica_groups("{}", 4) == [[0, 1, 2, 3]]

    mesh = make_mesh({"data": 2, "model": 2}, jax.devices()[:4])
    hlo = "\n".join([
        '  %all-reduce.1 = f32[16,16]{1,0} all-reduce(f32[16,16]{1,0} %dot), '
        'channel_id=1, replica_groups={{0,1},{2,3}}, use_global_device_ids='
        'true, to_apply=%add, metadata={op_name="jit(f)/jvp(forward)/l/dot"}',
        '  %all-reduce.2 = (bf16[8]{0}, f32[4,2]{1,0}) all-reduce(%a, %b), '
        'channel_id=2, replica_groups=[2,2]<=[2,2]T(1,0), to_apply=%add',
        '  %all-gather-start.3 = (f32[4]{0}, f32[8]{0}) all-gather-start('
        'f32[4]{0} %c), channel_id=3, replica_groups=[1,4]<=[4], '
        'dimensions={0}',
        '  %all-gather-done.3 = f32[8]{0} all-gather-done((f32[4]{0}, '
        'f32[8]{0}) %all-gather-start.3)',
        '  %collective-permute.4 = f32[2]{0} collective-permute(f32[2]{0} %d)'
        ', channel_id=4, source_target_pairs={{0,2},{2,0},{1,3},{3,1}}',
        '  %fusion.5 = f32[8]{0} fusion(f32[8]{0} %e), kind=kLoop',
    ])
    inv = collective_inventory(hlo, mesh)
    assert set(inv) == {"all-reduce.1", "all-reduce.2", "all-gather-start.3",
                        "all-gather-done.3", "collective-permute.4"}
    assert inv["all-reduce.1"]["axes"] == ("model",)
    assert inv["all-reduce.1"]["bytes"] == 16 * 16 * 4
    assert inv["all-reduce.1"]["op_name"] == "jit(f)/jvp(forward)/l/dot"
    assert inv["all-reduce.2"]["axes"] == ("data",)
    assert inv["all-reduce.2"]["bytes"] == 8 * 2 + 8 * 4
    assert inv["all-gather-start.3"]["axes"] == ("data", "model")
    assert inv["all-gather-done.3"]["axes"] == ("data", "model")
    assert inv["all-gather-start.3"]["bytes"] == 8 * 4
    assert inv["all-gather-done.3"]["kind"] == "all-gather-done"
    assert inv["collective-permute.4"]["axes"] == ("data",)
    assert collectives_by_axes(inv)["data+model"] == {
        "all-gather-start.3": 32, "all-gather-done.3": 32}
    # the ids are the executable's: a mesh laid over devices in another
    # order maps them through that order
    flipped = make_mesh({"data": 2, "model": 2},
                        [jax.devices()[i] for i in (0, 2, 1, 3)])
    inv = collective_inventory(hlo, flipped, device_order=[0, 1, 2, 3])
    assert inv["all-reduce.1"]["axes"] == ("data",)
    assert inv["all-reduce.2"]["axes"] == ("model",)


def test_step_dispatch_carries_collectives_only_while_recording():
    tr = _lm_trainer({"data": 2, "model": 2}, rules=megatron_rules())
    tr.compile()
    batch = {"data": np.zeros((4, 8), np.float32),
             "softmax_label": np.zeros((4, 8), np.float32)}
    tr.step(batch)
    assert not tracing.tail(10)             # off: no span at all
    tracing.configure(None, enable=True)
    try:
        tr.step(batch)
    finally:
        tracing.configure(None, enable=False)
    (ev,) = [e for e in tracing.tail(10) if e["name"] == "step.dispatch"]
    # the mapping read once at compile, not a copy made per step
    assert ev["args"]["collectives"] is tr._collective_axes["train"]
    assert set(ev["args"]["collectives"]) == {"model", "data"}
    # one device: no inventory and nothing to carry
    one = _conv_trainer()
    one.compile()
    assert one.collective_inventory == {}


def test_no_per_step_aot_counter():
    tr = _conv_trainer()
    tr.compile()
    batch = {"data": np.zeros((4, 3, 8, 8), np.float32),
             "softmax_label": np.zeros((4,), np.float32)}
    for _ in range(3):
        tr.step(batch)
    assert tr.aot_stats["hits"] == 3
    assert "trainer.aot_hits" not in telemetry.snapshot_flat()
