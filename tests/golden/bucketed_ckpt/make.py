"""Write the checkpoints ``tests/test_fused_update.py`` restores: the
optimizer state as the bucketed fused update kept it, in flat f32
buckets saved as ``opt:fused:<i>:<leaf>``.

They must come from that update's own code, not from a re-implementation
of its layout, so run this with a checkout of a commit that still has
``mxnet_tpu/ops/fused_update.py``'s ``build_plan`` (up to 7a77072) first
on the path::

    mkdir "$DIR" && git archive 7a77072 | tar -x -C "$DIR"
    JAX_PLATFORMS=cpu PYTHONPATH="$DIR" \\
        python tests/golden/bucketed_ckpt/make.py

Each run: the test file's two-layer MLP with biases on a ``data`` 1
mesh, ``grad_bucket_bytes`` 1024 (several buckets, parameters
straddling them), the test's first two batches, saved at step 2.
"""
import os
import shutil

import numpy as np

import jax

import mxnet_tpu as mx
from mxnet_tpu import symbol as S
from mxnet_tpu.checkpoint import CheckpointManager
from mxnet_tpu.parallel import ShardedTrainer, make_mesh

HERE = os.path.dirname(os.path.abspath(__file__))
RUNS = {
    "sgd": ("sgd", {"learning_rate": 0.1, "wd": 0.01}),
    "sgd_momentum": ("sgd", {"learning_rate": 0.1, "momentum": 0.9,
                             "wd": 0.01}),
    "adam": ("adam", {"learning_rate": 1e-3}),
    "adamw": ("adamw", {"learning_rate": 1e-3, "wd": 0.01}),
}


def _mlp():
    d = S.Variable("data")
    net = S.FullyConnected(d, num_hidden=32, name="fc1")
    net = S.Activation(net, act_type="relu")
    net = S.FullyConnected(net, num_hidden=10, name="fc2")
    return S.SoftmaxOutput(net, name="softmax")


def main():
    from mxnet_tpu.ops import fused_update
    assert hasattr(fused_update, "build_plan"), \
        "run with the bucketed fused update's code first on the path"
    rng = np.random.RandomState(0)
    feeds = [{"data": rng.rand(16, 8).astype(np.float32),
              "softmax_label": rng.randint(0, 10, (16,)).astype(np.float32)}
             for _ in range(2)]
    for run, (opt, op) in RUNS.items():
        mx.random.seed(7)
        tr = ShardedTrainer(_mlp(), mesh=make_mesh({"data": 1},
                                                   jax.devices()[:1]),
                            optimizer=opt, optimizer_params=op,
                            grad_bucket_bytes=1024, fused_update=True)
        tr.bind(data_shapes={"data": (16, 8)},
                label_shapes={"softmax_label": (16,)})
        assert tr._fused and len(tr._fused_plan.buckets) > 1
        for f in feeds:
            tr.step(f)
        out = os.path.join(HERE, run)
        shutil.rmtree(out, ignore_errors=True)
        mgr = CheckpointManager(out)
        tr.save_state(mgr, blocking=True)
        mgr.close()
        print(run, sorted(os.listdir(out)))


if __name__ == "__main__":
    main()
