"""Worker for the multi-host collective-tier test.

Each of 2 processes owns 2 virtual CPU devices; ``init_distributed``
builds the global runtime (4 global devices), a global ``data`` mesh
spans both processes, and one ShardedTrainer step must aggregate
integer-valued gradients EXACTLY across processes (the reference
nightly's exact-arithmetic pattern, tests/nightly/dist_sync_kvstore.py).
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# 2 virtual CPU devices, set before first backend use
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 2)

import numpy as np


def main():
    from mxnet_tpu.parallel import dist
    dist.init_distributed()
    assert dist.process_count() == 2, dist.process_count()
    rank = dist.process_index()
    devs = jax.devices()
    assert len(devs) == 4, devs  # 2 local x 2 processes

    # ---- exactness of a raw global collective -------------------------
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    mesh = Mesh(np.array(devs), ("data",))
    sh = NamedSharding(mesh, P("data"))
    # global vector 0..15, rows 4*rank..4*rank+7 fed locally
    local = np.arange(8, dtype=np.float64) + 8 * rank
    gx = jax.make_array_from_process_local_data(sh, local)
    total = jax.jit(jnp.sum, out_shardings=NamedSharding(mesh, P()))(gx)
    assert float(np.asarray(total)) == 120.0, float(np.asarray(total))

    # ---- ShardedTrainer step: exact integer gradient aggregation ------
    import mxnet_tpu as mx
    from mxnet_tpu.parallel import ShardedTrainer

    # linear head: loss grad wrt output = (pred - label); with W=0,b=0
    # pred=0, so dW = -sum_i label_i * x_i / batch  (rescale 1/batch)
    net = mx.symbol.FullyConnected(data=mx.symbol.Variable("data"),
                                   num_hidden=2, name="fc")
    net = mx.symbol.LinearRegressionOutput(
        data=net, label=mx.symbol.Variable("lro_label"), name="lro")
    tr = ShardedTrainer(net, mesh=mesh, optimizer="sgd",
                        optimizer_params={"learning_rate": 1.0})
    global_batch, feat = 8, 3
    tr.bind(data_shapes={"data": (global_batch, feat)},
            label_shapes={"lro_label": (global_batch, 2)})
    # zero params for closed-form expectations
    zero = {n: np.zeros(v.shape, np.float32)
            for n, v in tr._params.items()}
    tr.set_params(zero)

    # integer data, different per process (this process feeds rows
    # [4*rank, 4*rank+4) of the global batch)
    gx_np = np.arange(global_batch * feat, dtype=np.float32).reshape(
        global_batch, feat)
    gy_np = (np.arange(global_batch * 2, dtype=np.float32).reshape(
        global_batch, 2) % 5) - 2
    local_rows = slice(4 * rank, 4 * rank + 4)
    tr.step({"data": gx_np[local_rows], "lro_label": gy_np[local_rows]})

    # expected: W' = W - lr * dW.  LinearRegressionOutput's per-sample
    # grad is (pred - label) * grad_scale / label_width (label_width=2),
    # summed into dW across the GLOBAL batch, then the trainer rescales
    # by 1/global_batch
    dW = (0.0 - gy_np).T @ gx_np / (global_batch * 2)
    db = (0.0 - gy_np).sum(axis=0) / (global_batch * 2)
    W = np.asarray(tr._params["fc_weight"])
    b = np.asarray(tr._params["fc_bias"])
    np.testing.assert_array_equal(W, -dW.astype(np.float32))
    np.testing.assert_array_equal(b, -db.astype(np.float32))
    print(f"rank {rank}: exact aggregation ok")


if __name__ == "__main__":
    main()
