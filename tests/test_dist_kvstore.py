"""Distributed kvstore: real local processes, exact aggregation.

The reference validates ``dist_sync`` by launching scheduler + servers +
workers all on localhost and asserting integer aggregation
(``tests/nightly/dist_sync_kvstore.py``, ``tools/launch.py --launcher
local``); same strategy here.
"""
import os
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu.parallel.launch import launch_local


def test_only_workers_may_reach_the_chip(monkeypatch):
    """One process per chip: scheduler and server roles are pinned to the
    host platform; a worker keeps whatever platform its launcher has."""
    from mxnet_tpu.parallel.launch import _env_for
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    for role in ("scheduler", "server"):
        assert _env_for(role, 2, 1, "127.0.0.1", 9091)["JAX_PLATFORMS"] == "cpu"
    assert _env_for("worker", 2, 1, "127.0.0.1", 9091)["JAX_PLATFORMS"] == "tpu"


def test_dist_kvstore_requires_cluster_env(monkeypatch):
    for v in ("MXTPU_ROLE", "DMLC_ROLE"):
        monkeypatch.delenv(v, raising=False)
    with pytest.raises(mx.base.MXNetError, match="launch"):
        mx.kvstore.create("dist_sync")


@pytest.mark.parametrize("num_workers,num_servers", [(2, 1), (3, 2)])
def test_dist_sync_exact_aggregation(num_workers, num_servers):
    script = os.path.join(os.path.dirname(__file__), "dist_sync_worker.py")
    code = launch_local([sys.executable, script], num_workers=num_workers,
                        num_servers=num_servers,
                        root_port=19300 + num_workers * 10 + num_servers,
                        timeout=300)
    assert code == 0


def test_dist_training_convergence():
    """Sharded data + dist_sync gradient sync trains to the accuracy gate
    on every worker (reference tests/nightly/dist_lenet.py)."""
    script = os.path.join(os.path.dirname(__file__), "dist_train_worker.py")
    code = launch_local([sys.executable, script], num_workers=2,
                        num_servers=1, root_port=19477, timeout=300)
    assert code == 0


def test_priority_sender_ordering_and_async():
    """Sender drains by priority (higher first, reference -param_index
    convention) and submit() returns before the work runs."""
    import threading
    import time as _time
    from mxnet_tpu.parallel.dist_kvstore import _PrioritySender

    s = _PrioritySender("t")
    order = []
    gate = threading.Event()
    # block the queue so later submissions can reorder behind the gate
    s.submit(100, gate.wait)
    t0 = _time.perf_counter()
    for prio in (0, -3, -1, -2):
        s.submit(prio, lambda p=prio: order.append(p))
    submit_cost = _time.perf_counter() - t0
    assert submit_cost < 0.1, "submit must not block on the queued work"
    gate.set()
    s.flush()
    assert order == [0, -1, -2, -3], order
    s.close()


def test_priority_sender_error_surfaces_at_flush():
    from mxnet_tpu.parallel.dist_kvstore import _PrioritySender

    s = _PrioritySender("err")
    s.submit(0, lambda: (_ for _ in ()).throw(RuntimeError("boom")))
    with pytest.raises(RuntimeError, match="boom"):
        s.flush()
    s.close()


def test_scheduler_detects_dead_worker():
    """A worker dying mid-job must fail the others' barriers promptly
    instead of wedging the cluster (the upgrade over the reference's
    hang + tools/kill-mxnet.py story)."""
    import socket
    import threading
    from mxnet_tpu.parallel import dist_kvstore as dk

    ls = socket.socket()
    ls.bind(("127.0.0.1", 0))
    port = ls.getsockname()[1]
    ls.close()
    cfg = {"role": "scheduler", "root_host": "127.0.0.1",
           "root_port": port, "num_workers": 2, "num_servers": 0}
    t = threading.Thread(target=dk.run_scheduler, args=(cfg,), daemon=True)
    t.start()

    a = dk._connect("127.0.0.1", port)
    b = dk._connect("127.0.0.1", port)
    dk._send(a, ("register_worker",))
    assert dk._recv(a)[0] == "ok"
    dk._send(b, ("register_worker",))
    assert dk._recv(b)[0] == "ok"

    # A parks in a barrier; B dies without sending 'stop'
    dk._send(a, ("barrier",))
    b.close()
    a.settimeout(10)
    reply = dk._recv(a)
    assert reply[0] == "barrier_failed", reply
    assert "died" in reply[1]
    # subsequent barriers fail immediately too
    dk._send(a, ("barrier",))
    reply = dk._recv(a)
    assert reply[0] == "barrier_failed", reply
    a.close()
    # grace period is 10s; leave real margin for loaded CI machines
    t.join(timeout=25)
    assert not t.is_alive(), "scheduler did not shut down after failure"


def test_dead_worker_aborts_server_sync_wait():
    """A survivor blocked in a sync-mode server push (no barrier in
    sight) must get an error once the scheduler detects the death —
    the wedge the reference could only resolve with kill-mxnet.py."""
    import socket
    import threading
    import time as _time
    from mxnet_tpu.parallel import dist_kvstore as dk

    ls = socket.socket()
    ls.bind(("127.0.0.1", 0))
    port = ls.getsockname()[1]
    ls.close()
    cfg = {"role": "scheduler", "root_host": "127.0.0.1",
           "root_port": port, "num_workers": 2, "num_servers": 1}
    threading.Thread(target=dk.run_scheduler, args=(cfg,),
                     daemon=True).start()
    threading.Thread(target=dk.run_server,
                     args=(dict(cfg, role="server"),), daemon=True).start()

    a = dk._connect("127.0.0.1", port)
    b = dk._connect("127.0.0.1", port)
    dk._send(a, ("register_worker",))
    ra = dk._recv(a)
    dk._send(b, ("register_worker",))
    rb = dk._recv(b)
    (host, sport) = ra[2][0]

    sa = socket.create_connection((host, sport), timeout=10)
    import numpy as np
    dk._send(sa, ("cmd", dk._SYNC_MODE, b""))
    assert dk._recv(sa)[0] == "ok"
    dk._send(sa, ("init", 0, dk._pack_arr(np.zeros(4, np.float32))))
    assert dk._recv(sa)[0] == "ok"

    # worker A pushes (sync mode waits for worker B's contribution)...
    result = {}

    def push_blocking():
        dk._send(sa, ("push", 0, dk._pack_arr(np.ones(4, np.float32))))
        result["reply"] = dk._recv(sa)

    t = threading.Thread(target=push_blocking, daemon=True)
    t.start()
    _time.sleep(0.5)
    assert "reply" not in result, "push should be waiting for worker B"
    # ...then worker B dies
    b.close()
    t.join(timeout=15)
    assert result.get("reply", ("none",))[0] == "err", result
    assert "aborted" in result["reply"][1]
    a.close()
