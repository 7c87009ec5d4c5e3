"""The device trace's op events with the names the program gave them:
each executed instruction's full name (``all-reduce.12``) and its HLO
``op_name`` (``jit(wrapped)/transpose(jvp(forward))/layer3_ffn1/dot``),
and the training step read by them.

``trace.read_xplane`` reads through ``jax.profiler.ProfileData``, which
shows an event's own stats only; the ``op_name`` (stat ``tf_op``) sits
on the event's METADATA.  So this module reads the proto itself, with
the ``xplane_pb2`` that ships inside the installed tensorflow, loaded by
path (importing tensorflow takes seconds).  One reading gives both the
:class:`trace.Trace` that ``trace.summarize`` reduces and the op events
with their names.

The training step's names (``parallel/trainer.py``): ``forward`` around
the function ``jax.vjp`` differentiates (its ops read
``jvp(forward)/...``), the backward ``transpose(jvp(forward))/...`` (a
``remat`` scope's recomputed forward inside it), ``update`` around the
optimizer's work, ``guard`` around the resilience guard's statistics and
gate, ``grad_reduce`` around the explicit-comm path's reductions; each
symbol node's ops under its node's name (``graph_eval.py``).  A step's
collectives by mesh axis come from the recording ``step.dispatch``
span's ``collectives`` argument (axis -> {instruction: bytes}), read
once from the compiled program (``ShardedTrainer.collective_inventory``).
"""
from __future__ import annotations

import bisect
import collections
import importlib.util
import os
import re
import statistics
import sys
from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Tuple

from . import trace

#: an op event: full instruction name, start ns, duration ns, op_name
OpEvent = Tuple[str, int, int, str]

#: the step's phases, in the order a step runs them; "" is no scope of
#: the trainer's (the ops outside forward, backward and update)
PHASES = ("forward", "backward", "grad_reduce", "update", "guard", "")
#: the instructions ``collective_exposed_share`` counts (its pattern)
COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|"
                        r"collective-broadcast)")
#: name-stack components that wrap a node's scope without naming one
WRAPPERS = {"checkpoint", "rematted_computation", "while", "body", "cond"}


@dataclass
class OpTrace:
    """A trace read once: ``trace`` for ``trace.summarize``, and per
    chip the op events with their names."""
    trace: trace.Trace
    ops: Dict[int, List[OpEvent]] = field(default_factory=dict)


def load_xspace(raw: bytes):
    """The trace as an ``XSpace`` message, or None where no
    ``xplane_pb2`` is installed."""
    for root in sys.path:
        path = os.path.join(root, "tensorflow", "tsl", "profiler", "protobuf",
                            "xplane_pb2.py")
        if os.path.isfile(path):
            spec = importlib.util.spec_from_file_location("xplane_pb2", path)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            space = mod.XSpace()
            space.ParseFromString(raw)
            return space
    return None


def read(raw: bytes, region_prefix="bench.") -> Optional[OpTrace]:
    """Device planes' ``XLA Ops`` and ``XLA Modules`` lines and the host
    regions named ``region_prefix...`` (a string or a tuple), as
    ``trace.read_xplane`` reads them, with each op's ``op_name``."""
    space = load_xspace(raw)
    if space is None:
        return None
    out = OpTrace(trace=trace.Trace())
    for plane in space.planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        names = {k: v.name for k, v in plane.stat_metadata.items()}
        tf_op = {}
        for mid, meta in plane.event_metadata.items():
            for st in meta.stats:
                if names.get(st.metadata_id) == "tf_op":
                    tf_op[mid] = st.str_value or names.get(st.ref_value, "")
        for line in plane.lines:
            if m and line.name in (trace.OPS_LINE, trace.MODULES_LINE):
                chip = int(m.group(1))
                for ev in line.events:
                    name = trace.clean_name(
                        plane.event_metadata[ev.metadata_id].name)
                    start = line.timestamp_ns + ev.offset_ps // 1000
                    dur = ev.duration_ps // 1000
                    if line.name == trace.MODULES_LINE:
                        out.trace.device_modules.setdefault(chip, []).append(
                            (name, start, dur))
                        continue
                    out.trace.device_ops.setdefault(chip, []).append(
                        (name, start, dur))
                    out.ops.setdefault(chip, []).append(
                        (name, start, dur, tf_op.get(ev.metadata_id, "")))
            elif not m and plane.name.startswith(trace.HOST_PLANE_PREFIX):
                for ev in line.events:
                    name = plane.event_metadata[ev.metadata_id].name
                    if name.startswith(region_prefix):
                        out.trace.host_regions.append(
                            (name, line.timestamp_ns + ev.offset_ps // 1000,
                             ev.duration_ps // 1000))
    return out


# ---------------------------------------------------------------------------
# names
# ---------------------------------------------------------------------------

def _inner(part: str) -> str:
    """``transpose(jvp(forward))`` -> ``forward``."""
    while part.endswith(")") and "(" in part:
        part = part[part.index("(") + 1:-1]
    return part


def phase_of(op_name: str) -> str:
    """The step phase an op belongs to (one of :data:`PHASES`): the
    first component of its name stack, past ``jit(...)``, that is one
    of the trainer's scopes; ``forward`` under a transpose is the
    backward."""
    for part in op_name.split(";")[0].split("/"):
        if part.startswith("jit("):
            continue
        inner = _inner(part)
        if inner == "forward":
            return "backward" if part.startswith("transpose(") else "forward"
        if inner in ("update", "guard", "grad_reduce"):
            return inner
    return ""


def node_of(op_name: str) -> str:
    """What a phase's op is for: the first component after the phase's
    own that is not a transform or a wrapper (a symbol node's name in
    forward and backward: ``layer3_ffn1``; the optimizer's op in
    update)."""
    parts = op_name.split(";")[0].split("/")
    for i, part in enumerate(parts):
        if not part.startswith("jit(") and _inner(part) in (
                "forward", "update", "guard", "grad_reduce"):
            for rest in parts[i + 1:-1] or parts[-1:]:
                if "(" not in rest and rest not in WRAPPERS:
                    return rest.rstrip(":")
            return parts[-1].rstrip(":")
    return ""


# ---------------------------------------------------------------------------
# the step read by its names
# ---------------------------------------------------------------------------

def program_runs(tr: trace.Trace, chip: int, lo: int, hi: int
                 ) -> Dict[str, List[Tuple[int, int]]]:
    """The runs (start, end) of each program on ``chip`` that lie wholly
    inside ``[lo, hi)``, by program (``jit_wrapped(<fingerprint>)``)."""
    runs: Dict[str, List[Tuple[int, int]]] = collections.defaultdict(list)
    for name, s, d in tr.device_modules.get(chip, ()):
        if s >= lo and s + d <= hi:
            runs[name].append((s, s + d))
    return {k: sorted(v) for k, v in runs.items()}


def ops_in_runs(ops: Iterable[OpEvent], runs: List[Tuple[int, int]]
                ) -> List[OpEvent]:
    """The op events (containers left out: their time is their
    children's) that start inside one of ``runs``."""
    starts = [a for a, _ in runs]
    out = []
    for ev in ops:
        if trace.CONTAINER.match(ev[0]):
            continue
        i = bisect.bisect_right(starts, ev[1]) - 1
        if i >= 0 and ev[1] < runs[i][1]:
            out.append(ev)
    return out


def axis_of(collectives: Optional[Dict[str, Dict[str, int]]]
            ) -> Dict[str, str]:
    """instruction -> axis, from a ``step.dispatch`` span's
    ``collectives`` argument."""
    return {name: axis for axis, held in (collectives or {}).items()
            for name in held}


def step_split(ot: OpTrace, program: Optional[str] = None,
               collectives: Optional[Dict[str, Dict[str, int]]] = None,
               window: Optional[Tuple[int, int]] = None) -> Optional[Dict[str, Any]]:
    """Device ms a step of the training program, by phase, by symbol
    node within a phase, by instruction where no scope claims the op,
    and of the collectives by mesh axis and phase.

    A step is one run of ``program`` (default: the program with the
    most device time in the window) that lies wholly inside the window;
    per chip the ops inside those runs are summed and divided by the
    runs, and the chips are averaged (the first chip alone holds the
    breakdowns by node and instruction, as ``Summary.module_kernels``
    does).  None where no run lies inside the window."""
    tr = ot.trace
    lo, hi = window or trace.window_of(tr)
    chips = sorted(ot.ops)
    if not chips:
        return None
    first = program_runs(tr, chips[0], lo, hi)
    if program is None:
        if not first:
            return None
        program = max(first, key=lambda k: sum(b - a for a, b in first[k]))
    if not first.get(program):
        return None
    axis = axis_of(collectives)
    phase_ms: Dict[str, float] = collections.Counter()
    coll_ms: Dict[Tuple[str, str], float] = collections.Counter()
    coll_all_ms = 0.0
    nodes: Dict[str, Dict[str, float]] = collections.defaultdict(
        collections.Counter)
    unowned: Dict[str, float] = collections.Counter()
    counted = 0
    for chip in chips:
        runs = program_runs(tr, chip, lo, hi).get(program, [])
        if not runs:
            continue
        counted += 1
        per = 1e6 * len(runs)                 # ns -> ms a run
        for name, _, d, op in ops_in_runs(ot.ops[chip], runs):
            ph = phase_of(op)
            phase_ms[ph] += d / per
            if COLLECTIVE.match(trace.base_name(name)):
                coll_all_ms += d / per
                coll_ms[(axis.get(name, "?"), ph)] += d / per
            if chip == chips[0]:
                if ph:
                    nodes[ph][node_of(op) or "(none)"] += d / per
                else:
                    unowned[trace.base_name(name)] += d / per
    n = max(counted, 1)
    runs_ms = [(b - a) / 1e6 for a, b in first[program]]
    return {
        "program": program, "runs": len(runs_ms), "chips": counted,
        "run_ms_median": statistics.median(runs_ms),
        "phase_ms": {p: phase_ms.get(p, 0.0) / n for p in PHASES},
        "collective_ms": {k: v / n for k, v in coll_ms.items()},
        "collective_all_ms": coll_all_ms / n,
        "nodes": {p: dict(c) for p, c in nodes.items()},
        "unowned": dict(unowned),
    }


def axis_totals(split: Dict[str, Any]) -> Dict[str, float]:
    """Collective device ms a step by axis, phases summed."""
    out: Dict[str, float] = collections.Counter()
    for (ax, _), v in split["collective_ms"].items():
        out[ax] += v
    return dict(out)


def metric_values(split: Dict[str, Any]) -> Dict[str, float]:
    """The per-step numbers a benchmark reader of the step would report:
    ``train_fwd_ms`` (``forward``, not under a transpose),
    ``train_bwd_ms`` (under ``transpose(``, remat's recomputed forward
    included), ``train_update_ms`` (``update`` and ``guard``),
    ``train_unowned_ms`` (no scope of the trainer's: ``grad_reduce``
    too, which no cell runs), and ``collective_ms.<axis>``."""
    ph = split["phase_ms"]
    out = {"train_fwd_ms": ph["forward"], "train_bwd_ms": ph["backward"],
           "train_update_ms": ph["update"] + ph["guard"],
           "train_unowned_ms": ph[""] + ph["grad_reduce"]}
    for ax, v in axis_totals(split).items():
        out[f"collective_ms.{ax}"] = v
    return out
