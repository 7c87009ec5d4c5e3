"""Execution profiling — capability upgrade over the reference.

The reference era had no profiler (SURVEY §5: Monitor + engine debug
logging only; MXNet's profiler came later).  On TPU the native story is
XLA's trace viewer: this module wraps ``jax.profiler`` in the start/stop
shape later MXNet exposed, producing TensorBoard-loadable traces of
device compute, HLO ops, and host activity.

    mx.profiler.start("/tmp/profile")
    ... training steps ...
    mx.profiler.stop()
The second half of this module is a lightweight **step-phase profiler**
(:func:`profile_step`) that attributes one training step's wall time to
the phases the framework controls:

* ``place_ms``  — host time to build + dispatch the sharded ``device_put``
  for a batch (hidden by :class:`~mxnet_tpu.io.DevicePrefetchIter`),
* ``dispatch_ms`` — host time for ``trainer.step`` to *return* on a
  pre-placed batch (trace/lower excluded; this is the Python+jax dispatch
  overhead per step),
* ``device_ms`` — pure device compute per step, measured with the
  two-point slope method from ``docs/perf.md`` (run N then 3N steps, each
  closed by one forced fetch; the slope cancels the constant cost of the
  closing fetch and the pipelined dispatch ramp),
* ``fetch_ms`` — one device→host scalar fetch on an idle device (the
  per-readback round trip a per-batch metric would pay).

``host_gap_ms = max(0, place_ms + dispatch_ms - device_ms)`` is the part
of host work that CANNOT hide under device compute — the framework
overhead a step actually pays.  Exposed via ``bench.py --profile-step``.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

import jax

from .base import MXNetError

__all__ = ["start", "stop", "trace", "annotate", "profile_step",
           "format_step_profile", "record_compile", "compile_events",
           "reset_compile_events", "format_compile_report",
           "bump", "counter", "counters", "reset_counters"]

_active_dir: Optional[str] = None


def start(log_dir: str) -> None:
    """Begin capturing a device/host trace into ``log_dir``."""
    global _active_dir
    if _active_dir is not None:
        raise MXNetError(f"profiler already running (dir={_active_dir!r})")
    jax.profiler.start_trace(log_dir)
    _active_dir = log_dir


def stop() -> str:
    """Stop the capture; returns the trace directory."""
    global _active_dir
    if _active_dir is None:
        raise MXNetError("profiler is not running")
    out = _active_dir
    try:
        jax.profiler.stop_trace()
    finally:
        # a failed export must not wedge the module in 'running' state
        _active_dir = None
    return out


@contextlib.contextmanager
def trace(log_dir: str):
    """``with mx.profiler.trace(dir): ...`` capture scope."""
    start(log_dir)
    try:
        yield
    finally:
        stop()


def annotate(name: str):
    """Label a region so it shows up in the trace timeline
    (``jax.profiler.TraceAnnotation``)."""
    return jax.profiler.TraceAnnotation(name)


# ---------------------------------------------------------------------------
# Compile telemetry
# ---------------------------------------------------------------------------
#
# Every program resolution in the compile-cache subsystem (memory hit,
# disk attach, fresh XLA compile) lands here as one event, so a run can
# answer "where did my cold-start seconds go" without a trace viewer.

_compile_events: List[Dict[str, object]] = []
_compile_lock = threading.Lock()


def record_compile(label: str, seconds: float, source: str = "compile",
                   digest: str = "") -> None:
    """Record one program resolution.  ``source`` is where the program
    came from: ``compile`` (fresh XLA build), ``disk`` (persistent-cache
    attach) or ``memory`` (in-process LRU hit)."""
    with _compile_lock:
        _compile_events.append({"label": str(label),
                                "seconds": float(seconds),
                                "source": str(source),
                                "digest": str(digest)})
    from . import telemetry
    telemetry.counter("compile.events").inc(source=str(source))
    telemetry.histogram("compile.seconds").observe(float(seconds))


def compile_events() -> List[Dict[str, object]]:
    """Snapshot of recorded compile events (oldest first)."""
    with _compile_lock:
        return [dict(e) for e in _compile_events]


def reset_compile_events() -> None:
    with _compile_lock:
        _compile_events.clear()


def format_compile_report(title: str = "compile") -> str:
    """Render the compile-event log: per-program line plus hit/miss and
    total-seconds-by-source footer."""
    events = compile_events()
    lines = [f"compile report [{title}]  ({len(events)} programs)"]
    if not events:
        return lines[0]
    width = max(len(str(e["label"])) for e in events)
    totals: Dict[str, float] = {}
    counts: Dict[str, int] = {}
    for e in events:
        src = str(e["source"])
        totals[src] = totals.get(src, 0.0) + float(e["seconds"])
        counts[src] = counts.get(src, 0) + 1
        lines.append(f"  {str(e['label']).ljust(width)}  {src:<7}  "
                     f"{float(e['seconds']):8.3f}s")
    foot = "  ".join(f"{s}={counts[s]} ({totals[s]:.3f}s)"
                     for s in sorted(counts))
    lines.append(f"  -- {foot}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# Static-audit events (mxnet_tpu.analysis)
# ---------------------------------------------------------------------------
#
# Each program the static auditor walks lands here (label, finding
# count, wall seconds), so "why is the staticcheck gate slow" and "which
# program produced findings" are answerable from the same process-wide
# event log as compiles.

_audit_events: List[Dict[str, object]] = []


def record_audit(program: str, findings: int, seconds: float) -> None:
    """Record one audited program (called by ``analysis.audit_traced``)."""
    with _compile_lock:
        _audit_events.append({"program": str(program),
                              "findings": int(findings),
                              "seconds": float(seconds)})
    from . import telemetry
    telemetry.counter("audit.programs").inc()
    if findings:
        telemetry.counter("audit.findings").inc(int(findings))


def audit_events() -> List[Dict[str, object]]:
    """Snapshot of recorded audit events (oldest first)."""
    with _compile_lock:
        return [dict(e) for e in _audit_events]


def reset_audit_events() -> None:
    with _compile_lock:
        _audit_events.clear()


# ---------------------------------------------------------------------------
# Event counters
# ---------------------------------------------------------------------------
#
# Process-wide named counters for rare-but-interesting events the
# resilience tier produces (skipped steps, prefetch retries, corrupt
# records, rollbacks).  Dotted names namespace the producer, e.g.
# ``io.prefetch_retries``.  Cheap enough to bump from worker threads.
#
# These are now a thin shim over the unified telemetry registry
# (``mxnet_tpu.telemetry`` — docs/observability.md): every ``bump``
# lands in a registry counter of the same name, so the metrics JSONL
# stream, ``telemetry.scrape()``, and flight-recorder dumps all see
# them with zero changes at the call sites.


def bump(name: str, n: int = 1) -> None:
    """Increment counter ``name`` by ``n`` (created at 0)."""
    from . import telemetry
    telemetry.counter(name).inc(int(n))


def counter(name: str) -> int:
    from . import telemetry
    v = telemetry.registry().get_value(name)
    return int(v) if v is not None else 0


def counters(prefix: str = "") -> Dict[str, int]:
    """Snapshot of counters, optionally filtered by dotted prefix."""
    from . import telemetry
    return telemetry.registry().counters_with_prefix(prefix)


def reset_counters(prefix: str = "") -> None:
    from . import telemetry
    telemetry.registry().reset(prefix, kinds=("counter",))


# ---------------------------------------------------------------------------
# Step-phase profiler
# ---------------------------------------------------------------------------

def _fetch(heads) -> None:
    """Force one tiny device→host transfer (closes the async pipeline)."""
    h = heads[0] if isinstance(heads, (list, tuple)) else heads
    np.asarray(h[(0,) * h.ndim])


def _device_slope_ms(run_steps: Callable[[int], None], base_steps: int,
                     repeats: int = 3) -> float:
    """Two-point-slope device time per step (docs/perf.md): time N and 3N
    steps, each closed by one forced fetch; ``(t2-t1)/2N`` cancels the
    constant cost of the closing fetch and the pipelined dispatch ramp.
    Lower median of ``repeats`` slopes."""
    slopes = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        run_steps(base_steps)
        t1 = time.perf_counter()
        run_steps(3 * base_steps)
        t2 = time.perf_counter()
        slopes.append(((t2 - t1) - (t1 - t0)) / (2 * base_steps))
    slopes.sort()
    return slopes[(len(slopes) - 1) // 2] * 1e3


def profile_step(trainer, host_feeds: List[dict], steps: int = 10,
                 repeats: int = 3) -> Dict[str, float]:
    """Attribute one training step's wall time to framework phases.

    ``host_feeds``: a few *host* batch dicts ({input name: numpy array},
    static shapes) — kept on host so the place phase measures the real
    ``device_put`` dispatch cost.  Returns a dict with per-phase
    milliseconds plus the derived ``host_gap_ms`` (host work that cannot
    hide under device compute) and ``step_ms`` (slope-measured total).
    """
    feeds = [dict(f) for f in host_feeds]
    placed = [dict(trainer.place_batch(f)) for f in feeds]

    # warm up: compile + one full step closed by a fetch
    _fetch(trainer.step(placed[0]))

    # host pre-step: build + dispatch the sharded device_put for a batch
    t0 = time.perf_counter()
    for i in range(steps):
        trainer.place_batch(dict(feeds[i % len(feeds)]))
    place_ms = (time.perf_counter() - t0) / steps * 1e3

    # dispatch: step() return time on pre-placed feeds (async — this is
    # the host-side per-step framework cost, not device compute)
    t0 = time.perf_counter()
    for i in range(steps):
        heads = trainer.step(placed[i % len(placed)])
    dispatch_ms = (time.perf_counter() - t0) / steps * 1e3
    _fetch(heads)  # drain before the slope phase

    def run_steps(n: int) -> None:
        h = None
        for i in range(n):
            h = trainer.step(placed[i % len(placed)])
        _fetch(h)

    device_ms = _device_slope_ms(run_steps, steps, repeats)

    # fetch: device idle (run_steps ended with a fetch) — time the pure
    # device→host scalar round trip
    heads = trainer.step(placed[0])
    _fetch(heads)
    t0 = time.perf_counter()
    for _ in range(max(3, repeats)):
        _fetch(heads)
    fetch_ms = (time.perf_counter() - t0) / max(3, repeats) * 1e3

    return {
        "place_ms": place_ms,
        "dispatch_ms": dispatch_ms,
        "device_ms": device_ms,
        "fetch_ms": fetch_ms,
        "host_gap_ms": max(0.0, place_ms + dispatch_ms - device_ms),
        "step_ms": device_ms + max(0.0, place_ms + dispatch_ms - device_ms),
    }


def format_step_profile(prof: Dict[str, float], title: str = "step") -> str:
    """Render a profile dict as the per-phase attribution table."""
    rows = [
        ("host pre-step (place_batch)", prof["place_ms"]),
        ("dispatch (step() return)", prof["dispatch_ms"]),
        ("device compute (slope)", prof["device_ms"]),
        ("fetch (device->host RTT)", prof["fetch_ms"]),
        ("host gap (unhidden host work)", prof["host_gap_ms"]),
        ("effective step", prof["step_ms"]),
    ]
    width = max(len(r[0]) for r in rows)
    lines = [f"step-phase profile [{title}]",
             f"{'phase'.ljust(width)}   ms/step"]
    for name, ms in rows:
        lines.append(f"{name.ljust(width)}   {ms:8.3f}")
    return "\n".join(lines)
