"""Multi-host collective tier: 2-process ``jax.distributed`` on localhost
CPU (VERDICT round-2 item 5).  Each process owns 2 virtual devices; the
global mesh spans both, and a ShardedTrainer step must aggregate
integer-valued gradients exactly across process boundaries — the
reference nightly pattern (tests/nightly/dist_sync_kvstore.py:20-46)
applied to the XLA-collective tier instead of the parameter server.
"""
import os
import socket
import subprocess
import sys


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_two_process_collective_trainer():
    here = os.path.dirname(os.path.abspath(__file__))
    worker = os.path.join(here, "dist_collective_worker.py")
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # worker sets its own device count
        env.pop("JAX_PLATFORMS", None)
        env.update({
            "MXTPU_COORDINATOR": f"127.0.0.1:{port}",
            "MXTPU_NUM_PROC": "2",
            "MXTPU_PROC_ID": str(rank),
            "MXNET_TPU_TESTS": "0",
        })
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = []
    for p in procs:
        out, _ = p.communicate(timeout=280)
        outs.append(out.decode("utf-8", "replace"))
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank} failed:\n{out}"
        assert "exact aggregation ok" in out, out
