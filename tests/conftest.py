"""Test configuration: virtual 8-device CPU mesh; the chip only on request.

Mirrors the reference's test strategy (SURVEY.md §4): real stack, local
devices, exact-arithmetic assertions — multi-chip behavior is validated on
host-platform virtual devices the way the reference validates distributed
kvstore with all workers on localhost.

Tier-1 pins the CPU platform (fast, deterministic, 8 devices) and never
looks for another.  The real-chip lane (``-m tpu``: ``test_tpu_real.py``,
``test_rtc.py::test_pallas_softmax_on_accelerator``) is one explicit
opt-in, run where a chip exists (README "Testing"):

    chiprun -- env MXNET_TPU_TESTS=1 python -m pytest tests -m tpu -q

which keeps cpu the default backend and registers the TPU as a second
platform, so those tests reach it through ``mx.context.tpu()`` — the
analog of the reference's gpu lane (``tests/python/gpu/
test_operator_gpu.py``) — and fail, not skip, if it is missing.
"""
import os

import pytest

_CHIP_LANE = os.environ.get("MXNET_TPU_TESTS") == "1"
os.environ["JAX_PLATFORMS"] = "cpu,tpu" if _CHIP_LANE else "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()


def pytest_collection_modifyitems(config, items):
    """``@pytest.mark.tpu`` tests run only when the lane is opted into."""
    if _CHIP_LANE:
        return
    skip = pytest.mark.skip(
        reason="real-chip lane is opt-in: MXNET_TPU_TESTS=1")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip)
