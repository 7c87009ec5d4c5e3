"""What jax found, and the refusal to measure on anything but the chip."""
from __future__ import annotations

from typing import Any, Dict


class NoChip(Exception):
    """No accelerator, or fewer chips than the cell asks for."""


def require(chips: int, rehearsal: bool) -> Dict[str, Any]:
    """The device dict of the last line.  Raises :class:`NoChip` unless
    jax's default backend is the TPU with at least ``chips`` local
    devices; ``rehearsal`` (the explicit CPU dry run) skips the platform
    test and nothing else."""
    import jax

    try:
        backend = jax.default_backend()
        devices = jax.local_devices()
    except RuntimeError as e:
        raise NoChip(f"jax found no usable backend: {e}") from e
    if not rehearsal and backend != "tpu":
        raise NoChip(
            f"no TPU: jax.default_backend() is {backend!r} "
            f"({len(devices)} x {devices[0].device_kind}); the benchmark "
            "measures on the chip and never falls back (--rehearsal is "
            "the explicit CPU dry run and reports no device metric)")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chip(s); jax sees "
                     f"{len(devices)}")
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def allocator_bytes(chips: int, key: str = "bytes_in_use") -> int:
    """The allocator's ``key`` on the fullest of the chips used
    (``bytes_in_use``: the arrays held now; ``peak_bytes_in_use``: the
    most it ever held); 0 where the backend reports nothing, as the CPU
    does not."""
    import jax

    return max((int((d.memory_stats() or {}).get(key, 0))
                for d in jax.local_devices()[:chips]), default=0)


def memory_peak_bytes(held_bytes: int, temp_bytes: int) -> int:
    """An ESTIMATE of the window's peak on the fullest chip, from two
    measured parts: ``held_bytes``, the most the allocator held at the
    window's opening or close (weights, pools, state, batches), plus
    ``temp_bytes``, the largest temporary allocation of the programs the
    window ran (their ``memory_analysis()`` on the chip).  The parts are
    added because on the TPU the allocator's figures do not include a
    running program's temporaries (measured, PR 23: a peak of 6.88 GB
    while a program with 8.71 GB of temporaries ran over 5.25 GB of
    arguments; ResNet-50: 1.57 GB beside 9.91 GB).  Set-up's own arrays
    (the float32 reference's logits) are left out: they are gone when
    the window opens.  Both parts go into the last line's ``device``
    beside the sum."""
    return int(held_bytes) + int(temp_bytes) if held_bytes else 0


class CompileCounter:
    """Counts backend compilations and persistent-cache hits/misses from
    jax's own monitoring events; ``mark()`` starts a new count, which is
    how compilations inside the measured window are counted (must be
    0)."""

    def __init__(self):
        import jax
        self.compiles = self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event: str, _secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> int:
        n, self.compiles = self.compiles, 0
        return n
