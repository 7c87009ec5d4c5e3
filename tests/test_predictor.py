"""Deployment predictor tests (reference c_predict_api.h parity)."""
import os

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import predictor, symbol as sym


def _train_and_checkpoint(tmp_path, prefix="m"):
    rng = np.random.RandomState(0)
    X = rng.rand(120, 6).astype(np.float32)
    y = (X.sum(axis=1) > 3).astype(np.float32) + (X[:, 0] > 0.5)
    net = sym.FullyConnected(data=sym.Variable("data"), num_hidden=16,
                             name="fc1")
    net = sym.Activation(data=net, act_type="relu", name="relu1")
    net = sym.FullyConnected(data=net, num_hidden=3, name="fc2")
    net = sym.SoftmaxOutput(data=net, name="softmax")
    model = mx.FeedForward(net, ctx=mx.cpu(), num_epoch=4,
                           optimizer="sgd", learning_rate=0.2,
                           numpy_batch_size=30)
    model.fit(X=X, y=y, kvstore=None)
    p = str(tmp_path / prefix)
    model.save(p)
    return p, X, model


def test_predictor_matches_model(tmp_path):
    prefix, X, model = _train_and_checkpoint(tmp_path)
    pred = predictor.create(prefix, 4, {"data": (20, 6)}, ctx=mx.cpu())
    outs = pred.predict(data=X[:20])
    expect = np.asarray(model.predict(
        mx.io.NDArrayIter(X[:20], batch_size=20)))
    np.testing.assert_allclose(outs[0], expect, rtol=1e-5)


def test_predictor_from_blob(tmp_path):
    prefix, X, model = _train_and_checkpoint(tmp_path)
    with open(f"{prefix}-symbol.json") as f:
        sjson = f.read()
    with open(f"{prefix}-0004.params", "rb") as f:
        blob = f.read()
    pred = predictor.Predictor(sjson, blob, {"data": (5, 6)}, ctx=mx.cpu())
    pred.set_input("data", X[:5])
    pred.forward()
    out = pred.get_output(0)
    assert out.shape == (5, 3)
    np.testing.assert_allclose(out.sum(axis=1), np.ones(5), rtol=1e-5)


def test_predictor_partial_out(tmp_path):
    """MXPredCreatePartialOut analog: read an internal layer."""
    prefix, X, model = _train_and_checkpoint(tmp_path)
    pred = predictor.create(prefix, 4, {"data": (5, 6)}, ctx=mx.cpu(),
                            output_names=["relu1"])
    (out,) = pred.predict(data=X[:5])
    assert out.shape == (5, 16)
    assert (out >= 0).all()  # relu output


def test_export_model_single_artifact(tmp_path):
    """Amalgamation analog: one StableHLO artifact, served by a process
    that imports ONLY jax (no mxnet_tpu)."""
    import subprocess
    import sys

    import mxnet_tpu as mx
    import numpy as np

    net = mx.symbol.FullyConnected(data=mx.symbol.Variable("data"),
                                   num_hidden=5, name="fc")
    net = mx.symbol.SoftmaxOutput(data=net, name="softmax")
    rng = np.random.RandomState(0)
    arg = {"fc_weight": mx.nd.array(rng.randn(5, 7).astype(np.float32)),
           "fc_bias": mx.nd.array(rng.randn(5).astype(np.float32))}
    out = str(tmp_path / "model.mxtpu")
    from mxnet_tpu.predictor import export_model, load_exported
    export_model(net, arg, {}, {"data": (4, 7)}, out)

    x = rng.rand(4, 7).astype(np.float32)
    # in-process serving
    pred = load_exported(out)
    y = pred.predict(data=x)[0]
    # reference result through the regular executor
    ref = mx.predictor.Predictor(net.tojson(),
                                 {f"arg:{k}": v for k, v in arg.items()},
                                 {"data": (4, 7)}).predict(data=x)[0]
    np.testing.assert_allclose(y, ref, rtol=1e-5, atol=1e-6)

    # framework-free serving: subprocess imports jax ONLY
    code = f"""
import sys
sys.modules['mxnet_tpu'] = None  # poison: any import attempt crashes
import json, struct
import numpy as np
import jax
from jax import export as jexport
with open({out!r}, 'rb') as f:
    assert f.read(9) == b'MXTPUEXP2'  # V2: header entries carry dtype
    (hlen,) = struct.unpack('<i', f.read(4))
    meta = json.loads(f.read(hlen).decode())
    exp = jexport.deserialize(f.read())
x = np.load({str(tmp_path / 'x.npy')!r})
(y,) = exp.call(x)
np.save({str(tmp_path / 'y.npy')!r}, np.asarray(y))
print('served ok')
"""
    np.save(str(tmp_path / "x.npy"), x)
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXNET_TPU_TESTS="0")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    y_sub = np.load(str(tmp_path / "y.npy"))
    np.testing.assert_allclose(y_sub, ref, rtol=1e-5, atol=1e-6)


def test_export_model_int_dtype(tmp_path):
    """V2 artifacts preserve integer input dtypes (advisor r3 finding):
    an Embedding model exports with int32 token ids end to end."""
    import mxnet_tpu as mx
    import numpy as np

    emb = mx.symbol.Embedding(data=mx.symbol.Variable("data"),
                              input_dim=20, output_dim=6, name="emb")
    net = mx.symbol.SoftmaxOutput(
        data=mx.symbol.FullyConnected(data=mx.symbol.Flatten(emb),
                                      num_hidden=3, name="fc"),
        name="softmax")
    rng = np.random.RandomState(3)
    arg = {"emb_weight": mx.nd.array(rng.randn(20, 6).astype(np.float32)),
           "fc_weight": mx.nd.array(rng.randn(3, 4 * 6).astype(np.float32)),
           "fc_bias": mx.nd.array(np.zeros(3, np.float32))}
    out = str(tmp_path / "emb.mxtpu")
    from mxnet_tpu.predictor import export_model, load_exported
    export_model(net, arg, {}, {"data": (2, 4)}, out,
                 input_dtypes={"data": "int32"})
    pred = load_exported(out)
    assert pred.input_dtypes["data"] == np.dtype("int32")
    ids = np.array([[1, 2, 3, 4], [19, 0, 7, 5]], np.int64)  # cast to i32
    y = pred.predict(data=ids)[0]
    assert y.shape == (2, 3)
    np.testing.assert_allclose(y.sum(axis=1), 1.0, rtol=1e-5)


def test_export_v1_artifact_still_loads(tmp_path):
    """Reader back-compat: a V1 artifact (2-tuple header entries, implied
    f32, MXTPUEXP1 magic) still deserializes and serves."""
    import struct

    import mxnet_tpu as mx
    import numpy as np

    net = mx.symbol.SoftmaxOutput(
        data=mx.symbol.FullyConnected(data=mx.symbol.Variable("data"),
                                      num_hidden=3, name="fc"),
        name="softmax")
    rng = np.random.RandomState(4)
    arg = {"fc_weight": mx.nd.array(rng.randn(3, 5).astype(np.float32)),
           "fc_bias": mx.nd.array(np.zeros(3, np.float32))}
    v2 = str(tmp_path / "m2.mxtpu")
    from mxnet_tpu.predictor import export_model, load_exported
    export_model(net, arg, {}, {"data": (2, 5)}, v2)
    # rewrite as a V1 artifact: old magic + 2-tuple entries
    import json
    with open(v2, "rb") as f:
        assert f.read(9) == b"MXTPUEXP2"
        (hlen,) = struct.unpack("<i", f.read(4))
        meta = json.loads(f.read(hlen).decode())
        blob = f.read()
    meta["inputs"] = [[n, s] for n, s, _ in meta["inputs"]]
    hdr = json.dumps(meta).encode()
    v1 = str(tmp_path / "m1.mxtpu")
    with open(v1, "wb") as f:
        f.write(b"MXTPUEXP1")
        f.write(struct.pack("<i", len(hdr)))
        f.write(hdr)
        f.write(blob)
    pred = load_exported(v1)
    assert pred.input_dtypes["data"] == np.dtype("float32")
    y = pred.predict(data=rng.rand(2, 5).astype(np.float64))[0]
    assert y.shape == (2, 3)
