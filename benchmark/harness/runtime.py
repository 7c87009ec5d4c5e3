"""What a runner is handed and what it hands back, and the profiler
window a traced run opens."""
from __future__ import annotations

import contextlib
import os
import shutil
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from . import trace


def say(msg: str) -> None:
    """An earlier line of the run's output (never the last)."""
    print(msg, flush=True)


@dataclass
class Run:
    """One invocation: the cell and its files, the arguments and the
    clocks."""
    cell: Dict[str, Any]
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    traced: bool
    process_t0: float                   # time.monotonic() at process start
    compiles: Any                       # device.CompileCounter
    scratch: str                        # this run's own temporary directory
    held_bytes: int = 0                 # most the allocator held, see below
    # every number ``correct`` compared, beside its limit: name ->
    # (value, limit); the result line's last key and stderr's last lines
    compared: Dict[str, Any] = field(default_factory=dict)

    @property
    def chips(self) -> int:
        return int(self.cell["chips"])

    def region(self, name: str):
        """A host region on the profiler's clock (``bench.<name>``) in a
        traced run; nothing at all otherwise."""
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    def setup_seconds(self, window_t0: float) -> float:
        return window_t0 - self.process_t0

    def sample_memory(self) -> None:
        """Called by the runner just outside the window, at its opening
        and its close: keeps the most the allocator held then."""
        from . import device
        self.held_bytes = max(self.held_bytes,
                              device.allocator_bytes(self.chips))


@dataclass
class Result:
    """What a runner returns.  ``end_to_end`` holds the cell's end-to-end
    values other than ``setup_s``; ``facts`` is what the per-layer
    readers read (program spans, per-step records, the trace summary)."""
    correct: bool
    attempted: int
    failed: int
    setup_s: float
    end_to_end: Dict[str, float]
    facts: Dict[str, Any] = field(default_factory=dict)
    notes: List[str] = field(default_factory=list)   # why not correct
    # the largest temporary allocation among the compiled programs the
    # window ran (``memory_analysis().temp_size_in_bytes``): the TPU
    # backend's ``peak_bytes_in_use`` does not see it
    temp_bytes: int = 0


def temp_bytes(compiled_programs) -> int:
    return max((int(c.memory_analysis().temp_size_in_bytes)
                for c in compiled_programs), default=0)


class TraceWindow:
    """The profiler over the LAST ``length_s`` seconds of the measured
    window of a traced run.  ``tick`` is called by the runner between
    steps, so the traced window starts and ends on step boundaries and
    the steps inside it are known exactly."""

    def __init__(self, run: Run, length_s: float):
        self.enabled = run.traced
        self.length_s = float(length_s)
        self.logdir = os.path.join(run.scratch, "trace")
        self.state = 0                   # 0 before, 1 tracing, 2 done
        self.start_cost_s = self.stop_cost_s = 0.0
        self._ann = None
        if self.enabled:
            shutil.rmtree(self.logdir, ignore_errors=True)
            os.makedirs(self.logdir, exist_ok=True)

    def tick(self, now: float, window_end: float) -> bool:
        """True while the profiler is on."""
        if not self.enabled:
            return False
        if self.state == 0 and now >= window_end - self.length_s:
            import jax
            t = time.monotonic()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0      # no per-call Python events
            jax.profiler.start_trace(self.logdir, profiler_options=options)
            self._ann = jax.profiler.TraceAnnotation("bench.window")
            self._ann.__enter__()
            self.start_cost_s = time.monotonic() - t
            self.state = 1
        return self.state == 1

    def close(self) -> None:
        if self.state != 1:
            return
        import jax
        t = time.monotonic()
        self._ann.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.stop_cost_s = time.monotonic() - t
        self.state = 2

    def summary(self) -> Optional[trace.Summary]:
        """The reduced trace, or None where no device plane was written
        (a rehearsal on the CPU)."""
        if self.state != 2:
            return None
        path = trace.find_xplane(self.logdir)
        if path is None:
            return None
        tr = trace.read_xplane(path)
        if not tr.device_ops:
            return None
        return trace.summarize(tr)


def read_program_spans(t_lo_ns: int, t_hi_ns: int) -> List[Dict[str, Any]]:
    """The program's own telemetry spans that closed inside the window
    (``mxnet_tpu.telemetry.tracing`` keeps them in a ring; times are
    microseconds from its own epoch)."""
    from mxnet_tpu.telemetry import tracing
    lo = (t_lo_ns - tracing._epoch_ns) // 1000
    hi = (t_hi_ns - tracing._epoch_ns) // 1000
    return [ev for ev in tracing.tail(tracing._MAX_EVENTS)
            if lo <= ev["ts"] and ev["ts"] + ev["dur"] <= hi]


def enable_program_spans(on: bool) -> None:
    from mxnet_tpu.telemetry import tracing
    tracing.clear()
    tracing.configure(None, enable=on)
