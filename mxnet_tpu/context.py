"""Device context, analog of reference ``python/mxnet/context.py:1-126``.

The reference models devices as ``Context(device_type, device_id)`` with a
thread-local default stack usable as a ``with`` block.  Here a context
resolves to a concrete :class:`jax.Device`.  ``tpu`` replaces the
reference's ``gpu``; ``gpu`` is kept as an alias for source compatibility
with reference-era scripts (it resolves to the accelerator backend).
"""
from __future__ import annotations

import functools
import threading
from typing import Optional

import jax

from .base import MXNetError

__all__ = ["Context", "cpu", "tpu", "gpu", "current_context", "num_devices", "default_ctx"]


@functools.lru_cache(maxsize=1)
def _accel_platform() -> Optional[str]:
    """``"tpu"`` when a TPU backend is live, else None (cached: the
    platform set is immutable once the backend is initialized).

    Finds the chip whether it is the default backend or a secondary one
    (``JAX_PLATFORMS=cpu,tpu`` keeps cpu as default while the chip stays
    reachable — the dual-lane setup of ``tests/test_tpu_real.py``).
    """
    try:
        return "tpu" if jax.devices("tpu") else None
    except RuntimeError:
        return None


class Context:
    """Device context.

    Parameters
    ----------
    device_type : str
        'cpu', 'tpu' (or 'gpu', alias for the accelerator backend).
    device_id : int
        Ordinal of the device within its backend.
    """

    _default_ctx = threading.local()

    devtype2mask = {"cpu": 1, "tpu": 2, "gpu": 2, "cpu_pinned": 3}
    devmask2type = {1: "cpu", 2: "tpu", 3: "cpu_pinned"}

    def __init__(self, device_type: "str | Context" = "tpu", device_id: int = 0):
        if isinstance(device_type, Context):
            self.device_type: str = device_type.device_type
            self.device_id: int = device_type.device_id
        else:
            self.device_type = device_type
            self.device_id = device_id
        self._old_ctx: Optional[Context] = None

    @property
    def device_typeid(self) -> int:
        return self.devtype2mask[self.device_type]

    @property
    def jax_device(self) -> jax.Device:
        """Resolve to a concrete jax.Device (raises MXNetError if absent)."""
        dt = self.device_type
        if dt in ("tpu", "gpu"):
            platform = _accel_platform()
            if platform is None:
                # never stand a cpu device in for the chip: a run that
                # asked for the accelerator must not train on the host
                # unnoticed
                raise MXNetError(
                    f"{self} requested but jax found no TPU (default "
                    f"backend {jax.default_backend()!r}); use cpu() "
                    "contexts to run on the host")
            # process-LOCAL devices: on a multi-host pod jax.devices() is
            # the global list and ctx ids must address this host's chips
            devices = jax.local_devices(backend=platform)
        elif dt in ("cpu", "cpu_pinned"):
            devices = jax.local_devices(backend="cpu")
        else:
            raise MXNetError(f"unknown device type {dt}")
        if self.device_id >= len(devices):
            raise MXNetError(
                f"{self} requested but only {len(devices)} {dt} device(s) present")
        return devices[self.device_id]

    def __eq__(self, other) -> bool:
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __str__(self):
        return f"{self.device_type}({self.device_id})"

    __repr__ = __str__

    def __enter__(self):
        if not hasattr(Context._default_ctx, "value"):
            Context._default_ctx.value = Context("cpu", 0)
        self._old_ctx = Context._default_ctx.value
        Context._default_ctx.value = self
        return self

    def __exit__(self, exc_type, exc_value, traceback):
        Context._default_ctx.value = self._old_ctx


def cpu(device_id: int = 0) -> Context:
    """Return a CPU context (reference ``context.py:cpu``)."""
    return Context("cpu", device_id)


def tpu(device_id: int = 0) -> Context:
    """Return a TPU context — the accelerator analog of reference ``gpu()``."""
    return Context("tpu", device_id)


def gpu(device_id: int = 0) -> Context:
    """Alias of :func:`tpu` kept for reference-script compatibility."""
    return Context("tpu", device_id)


def current_context() -> Context:
    """Return the current context (reference ``context.py:current_context``)."""
    if not hasattr(Context._default_ctx, "value"):
        Context._default_ctx.value = Context("cpu", 0)
    return Context._default_ctx.value


def default_ctx() -> Context:
    """Best single-device context for this process: tpu if present else cpu.

    Only consults the DEFAULT backend: when the accelerator is registered
    as a secondary platform (dual-lane test setup, cpu first), untyped
    NDArrays stay on cpu and only explicit ``tpu()`` contexts reach the
    chip.
    """
    return Context("tpu" if jax.default_backend() == "tpu" else "cpu", 0)


def num_devices(device_type: str = "tpu") -> int:
    """Number of visible devices of the given type."""
    if device_type in ("tpu", "gpu"):
        platform = _accel_platform()
        return len(jax.devices(platform)) if platform else 0
    return len(jax.devices("cpu"))
