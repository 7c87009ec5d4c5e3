"""Execution profiling — capability upgrade over the reference.

The reference era had no profiler (SURVEY §5: Monitor + engine debug
logging only; MXNet's profiler came later).  On TPU the native story is
XLA's trace viewer: this module wraps ``jax.profiler`` in the start/stop
shape later MXNet exposed, producing TensorBoard-loadable traces of
device compute, HLO ops, and host activity.

    mx.profiler.start("/tmp/profile")
    ... training steps ...
    mx.profiler.stop()
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, List, Optional

import jax

from .base import MXNetError

__all__ = ["start", "stop", "trace", "annotate",
           "record_compile", "compile_events",
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
