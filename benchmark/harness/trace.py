"""From the profiler's trace to numbers: device busy and idle, time per
operation and per named kernel, and the longest idle gaps attributed to what the benchmark's loop was doing.

The reduction works on plain tuples so that it can be tested on a small
recorded fixture (``benchmark/fixtures``) and, through
:func:`read_xplane`, on what ``jax.profiler`` wrote.  Reading needs
nothing but jax (``jax.profiler.ProfileData``).

Layout of a TPU trace (looked at by hand, PR 23): one plane per chip
named ``/device:TPU:<n>`` whose line ``XLA Ops`` holds one event per
executed HLO instruction (a Pallas kernel appears under the name its
``pallas_call`` was given, e.g. ``mxtpu_flash_decode.7``); the line
``XLA Modules`` holds one event per executed program.  Host threads are
lines of the plane ``/host:CPU``; ``jax.profiler.TraceAnnotation``
regions appear there under their own names, on the same clock.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
KERNEL_PREFIX = "mxtpu_"          # every pallas_call(name=...) of the program
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"

# instructions that only wrap others (their time is their children's)
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")

Event = Tuple[str, int, int]      # name, start_ns, duration_ns


@dataclass
class Trace:
    """What was read: per chip the op events and module events, and the
    benchmark's own host regions."""
    device_ops: Dict[int, List[Event]] = field(default_factory=dict)
    device_modules: Dict[int, List[Event]] = field(default_factory=dict)
    host_regions: List[Event] = field(default_factory=list)


def find_xplane(logdir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def clean_name(name: str) -> str:
    """``%fusion.12 = ...`` -> ``fusion.12``."""
    name = name.strip()
    if name.startswith("%"):
        name = name[1:]
    return name.split(" ", 1)[0]


def base_name(name: str) -> str:
    """``mxtpu_flash_decode.7`` -> ``mxtpu_flash_decode``."""
    return re.sub(r"(\.\d+)+$", "", clean_name(name))


def read_xplane(path: str, region_prefix: str = "bench.") -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dest = tr.device_ops.setdefault(chip, [])
                elif line.name == MODULES_LINE:
                    dest = tr.device_modules.setdefault(chip, [])
                else:
                    continue
                for ev in line.events:
                    dest.append((clean_name(ev.name), int(ev.start_ns),
                                 int(ev.duration_ns)))
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(region_prefix):
                        tr.host_regions.append(
                            (ev.name, int(ev.start_ns), int(ev.duration_ns)))
    return tr


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[Tuple[int, int]] = []
    for a, b in sorted(i for i in intervals if i[1] > i[0]):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Sequence[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Tuple[int, int]],
             b: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """The part of disjoint sorted ``a`` not covered by disjoint sorted
    ``b``."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[str, int, int]]:
    """Events cut to the window ``[lo, hi)`` as (name, start, end)."""
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b))
    return out


# ---------------------------------------------------------------------------
# the summary the metrics read
# ---------------------------------------------------------------------------

@dataclass
class Summary:
    chips: int
    window_s: float
    busy_s: float                    # mean over chips of the op union
    op_seconds: Dict[str, float]     # by instruction base name, mean/chip
    op_calls: Dict[str, int]         # events by base name, all chips
    # idle seconds of the first chip, summed by the benchmark host region
    # that covers each gap's middle
    gap_seconds_by_region: Dict[str, float]
    # executed programs of the first chip that lie wholly inside the
    # window: device ms of each run, by program name, and the device ms
    # each named kernel (``mxtpu_*``) took inside the runs of that name
    module_ms: Dict[str, List[float]] = field(default_factory=dict)
    module_kernels: Dict[str, Dict[str, float]] = field(default_factory=dict)

    def programs_with(self, kernel: str, present: bool = True,
                      prefix: str = "") -> List[float]:
        """Device ms of every run of the programs named ``prefix...`` that
        do (or do not) hold the named kernel."""
        out: List[float] = []
        for name, runs in self.module_ms.items():
            has = kernel in self.module_kernels.get(name, {})
            if name.startswith(prefix) and has == present:
                out += runs
        return out

    def kernel_seconds(self, name: str) -> float:
        """Device time of every instruction whose base name starts with
        ``name`` (a kernel's ``pallas_call`` name), mean over chips."""
        return sum(s for n, s in self.op_seconds.items() if n.startswith(name))

    def kernel_calls(self, name: str) -> int:
        return sum(c for n, c in self.op_calls.items() if n.startswith(name))

    def kernel_ms_per_run(self, kernel: str) -> Optional[float]:
        """Device ms of the named kernel per run of the programs that hold
        it, over the runs that lie wholly inside the window."""
        runs = len(self.programs_with(kernel))
        total_ms = sum(held.get(kernel, 0.0)
                       for held in self.module_kernels.values())
        return total_ms / runs if runs else None

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        """Top device operations by time, and the idle time of the first
        chip summed by what the benchmark's loop was doing meanwhile."""
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:top]
        idle = sorted(self.gap_seconds_by_region.items(),
                      key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in idle]}


def window_of(tr: Trace, region: str = "bench.window") -> Tuple[int, int]:
    """The traced window: the benchmark's ``bench.window`` host region if
    it was recorded, else first device event to last."""
    for name, s, d in tr.host_regions:
        if name == region:
            return s, s + d
    starts = [s for evs in tr.device_ops.values() for _, s, _ in evs]
    ends = [s + d for evs in tr.device_ops.values() for _, s, d in evs]
    if not starts:
        raise ValueError("the trace holds no device operation")
    return min(starts), max(ends)


def region_at(regions: Sequence[Event], t: int,
              exclude: Sequence[str] = ("bench.window",)) -> str:
    """Innermost benchmark host region covering instant ``t``."""
    best, best_d = "outside the benchmark's loop", None
    for name, s, d in regions:
        if name in exclude:
            continue
        if s <= t < s + d and (best_d is None or d < best_d):
            best, best_d = name, d
    return best


def summarize(tr: Trace) -> Summary:
    lo, hi = window_of(tr)
    chips = sorted(tr.device_ops)
    if not chips:
        raise ValueError("the trace holds no device plane with an "
                         f"{OPS_LINE!r} line")
    n = len(chips)
    busy = 0
    op_ns: Dict[str, int] = {}
    op_calls: Dict[str, int] = {}
    gaps: List[Tuple[int, int]] = []
    for chip in chips:
        evs = [e for e in clip(tr.device_ops[chip], lo, hi)
               if not CONTAINER.match(e[0])]
        for name, a, b in evs:
            key = base_name(name)
            op_ns[key] = op_ns.get(key, 0) + (b - a)
            op_calls[key] = op_calls.get(key, 0) + 1
        all_u = union((a, b) for _, a, b in evs)
        busy += total(all_u)
        if chip == chips[0]:
            gaps = subtract([(lo, hi)], all_u)
    module_ms: Dict[str, List[float]] = {}
    module_kernels: Dict[str, Dict[str, float]] = {}
    mods = sorted((s, s + d, name) for name, s, d
                  in tr.device_modules.get(chips[0], ())
                  if s >= lo and s + d <= hi)
    starts = [m[0] for m in mods]
    for a, b, name in mods:
        module_ms.setdefault(name, []).append((b - a) / 1e6)
    for name, s, d in tr.device_ops[chips[0]]:
        key = base_name(name)
        if key.startswith(KERNEL_PREFIX):
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][1]:
                held = module_kernels.setdefault(mods[i][2], {})
                held[key] = held.get(key, 0.0) + d / 1e6
    by_region: Dict[str, float] = {}
    for a, b in gaps:
        name = region_at(tr.host_regions, (a + b) // 2)
        by_region[name] = by_region.get(name, 0.0) + (b - a) / 1e9
    return Summary(
        chips=n, window_s=(hi - lo) / 1e9, busy_s=busy / n / 1e9,
        op_seconds={k: v / n / 1e9 for k, v in op_ns.items()},
        op_calls=op_calls, gap_seconds_by_region=by_region,
        module_ms=module_ms, module_kernels=module_kernels)
