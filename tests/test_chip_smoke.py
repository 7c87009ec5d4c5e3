"""``chip_smoke.py`` off the chip: what tier-1 can hold it to.

The script's job is the TPU (README "Testing"); here we pin the two
things a CPU can check — it refuses to run without a chip, and its
explicit ``--rehearsal`` dry run walks every phase (kernels in interpret
mode, train, serve) to the result line, keeping its compile cache where
``JAX_COMPILATION_CACHE_DIR`` says.
"""
import json
import os
import subprocess
import sys

import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*flags, **env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    return subprocess.run([sys.executable, SMOKE, *flags], env=env,
                          capture_output=True, text=True, timeout=300)


def test_no_chip_is_a_failure_not_a_fallback():
    res = _run()
    assert res.returncode != 0
    assert "no TPU" in res.stderr
    assert '"ok"' not in res.stdout and "REHEARSAL" not in res.stdout


def test_rehearsal_walks_every_phase(tmp_path):
    res = _run("--rehearsal", JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert res.returncode == 0, res.stderr[-2000:]
    lines = res.stdout.strip().splitlines()
    assert "REHEARSAL" in lines[0]
    for phase in ("[kernels]", "[train]", "[serve]"):
        assert any(l.startswith(phase) for l in lines), phase
    assert json.loads(lines[-1]) == {
        "ok": True, "rehearsal": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    # the cache went where the environment put it, and nowhere else
    assert f"compile cache: {tmp_path} " in res.stdout
    assert os.listdir(tmp_path)


def test_tpu_context_without_a_chip_raises():
    """``FeedForward(ctx=mx.tpu())`` must not train on the host unnoticed."""
    with pytest.raises(MXNetError, match="no TPU"):
        mx.context.tpu(0).jax_device
    assert mx.context.cpu(0).jax_device.platform == "cpu"
    assert mx.context.num_devices("tpu") == 0
