#!/usr/bin/env python3
"""Where a traced benchmark cell's time went, by the program's own names.

    chiprun -- python3 tools/trace_report.py --workload serve-batch --seed 7
    python3 tools/trace_report.py --xplane chiprun_out/trace/serve-batch-7.xplane.pb.gz

The first form runs the cell exactly as ``benchmark/run.py --trace 1``
does (same runner, same window, needs the chip), keeps what the run
throws away — the profiler's ``.xplane.pb`` and the window's span ring,
under ``chiprun_out/trace/`` — and reads them; the second only reads,
anywhere.  It prints what ``PERF.md`` section 5 is written from:

* the device's idle gaps summed by the innermost host region covering
  each gap's middle, ``bench.*`` AND the program's ``serve.*`` spans
  (a recording span is a ``TraceAnnotation``, so both are on the device
  trace's clock);
* for every engine program (``jit_fn_<kind>``) its runs, and for its
  median run the device ms by ``jax.named_scope`` (``kv_write``,
  ``pool_read``: the XLA readers' gather of a table's blocks, ``attn``,
  ``ffn``, ...), from the ``XLA Ops`` events'
  ``tf_op`` stat (the HLO ``op_name``), with what no scope claims broken
  down by instruction;
* the span ring's medians beside the device's, so a span that stopped
  covering its program shows, and the window's ratio of the
  ``serve.decode`` spans' ``live_blocks`` to ``table_blocks`` (what the
  decode attention read of what its tables could hold), ``[sampler]``:
  the steps by the branch their program's sampler took (``greedy``: an
  ``argmax`` alone; ``select``: a row samples) and, for a model with
  routed layers, ``[experts]``: ``experts_hit`` and ``assigned_here`` a
  step.

It measures nothing the benchmark reports and changes no number of it.
"""
from __future__ import annotations

import argparse
import collections
import gzip
import json
import os
import shutil
import statistics
import sys
import tempfile
import time

PROCESS_T0 = time.monotonic()
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

REGIONS = ("bench.", "serve.", "prefetch.")


def run_cell(args) -> str:
    """The traced run of ``benchmark/run.py``, keeping the trace."""
    from benchmark.harness import device as dev
    from benchmark.harness import spec, trace
    from benchmark.harness.runtime import Run
    from mxnet_tpu import compile_cache as cc

    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    mix = spec.load_traffic(cell["traffic"])
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    device = dev.require(int(cell["chips"]), False)
    cc.enable_persistent_cache(os.path.join(REPO, ".jax_cache"))
    scratch = tempfile.mkdtemp(prefix="mxtpu-trace-")
    run = Run(cell=cell, config=spec.load_config(bench, cell["config"]),
              traffic=mix, seed=args.seed,
              seconds=float(args.seconds or bench["run_seconds"]),
              traced=True, process_t0=PROCESS_T0,
              compiles=dev.CompileCounter(), scratch=scratch)
    try:
        result = spec.load_module("runners", mix["kind"]).run(run)
        os.makedirs(args.keep, exist_ok=True)
        stem = os.path.join(args.keep, f"{args.workload}-{args.seed}")
        with open(trace.find_xplane(os.path.join(scratch, "trace")), "rb") as f, \
                gzip.open(stem + ".xplane.pb.gz", "wb") as out:
            shutil.copyfileobj(f, out)
        with open(stem + ".spans.json", "w") as f:
            json.dump(result.facts.get("spans", []), f)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    print(f"[cell] {args.workload} seed {args.seed} on {device['kind']!r}: "
          f"correct {result.correct}, {result.notes or 'no notes'}")
    return stem


def scope_of(op_name: str) -> str:
    """``jit(fn_decode)/kv_write/scatter:`` -> ``kv_write``: the first
    component under the jit wrappers, if a primitive follows it."""
    parts = [p for p in op_name.split("/") if p and not p.startswith("jit(")]
    return parts[0] if len(parts) > 1 else ""


def load_xspace(raw: bytes):
    """The trace as an ``XSpace`` message.  ``jax.profiler.ProfileData``
    shows an event's own stats only; the HLO ``op_name`` (stat
    ``tf_op``) sits on the event's METADATA, so the proto is read with
    the ``xplane_pb2`` that ships inside the installed tensorflow,
    loaded by path (importing tensorflow itself takes seconds)."""
    import importlib.util
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


def split_gaps(tr):
    """Idle seconds of the first chip by innermost host region, each gap
    SPLIT over the regions it crosses (``summarize`` gives a whole gap to
    the region at its middle)."""
    from benchmark.harness import trace
    lo, hi = trace.window_of(tr)
    chip = sorted(tr.device_ops)[0]
    busy = trace.union((a, b) for name, a, b
                       in trace.clip(tr.device_ops[chip], lo, hi)
                       if not trace.CONTAINER.match(name))
    edges = sorted({t for _, a, d in tr.host_regions for t in (a, a + d)})
    out = collections.Counter()
    for a, b in trace.subtract([(lo, hi)], busy):
        cuts = [a] + [t for t in edges if a < t < b] + [b]
        for x, y in zip(cuts, cuts[1:]):
            out[trace.region_at(tr.host_regions, (x + y) // 2)] += (y - x) / 1e9
    return out


def by_scope(space, lo: int, hi: int) -> None:
    """Device ms by named scope for the median run of each program."""
    from benchmark.harness import trace
    plane = next((p for p in space.planes
                  if trace.DEVICE_PLANE.match(p.name)), None)
    if plane is None:
        return
    names = {k: v.name for k, v in plane.stat_metadata.items()}
    lines = {line.name: line for line in plane.lines}

    def events(line):
        for ev in line.events:
            yield (line.timestamp_ns + ev.offset_ps // 1000,
                   ev.duration_ps / 1000.0, plane.event_metadata[ev.metadata_id])

    def op_name(meta) -> str:
        for st in meta.stats:
            if names.get(st.metadata_id) == "tf_op":
                return st.str_value or names.get(st.ref_value, "")
        return ""

    runs = collections.defaultdict(list)
    for start, dur, meta in events(lines[trace.MODULES_LINE]):
        if start >= lo and start + dur <= hi:
            runs[trace.clean_name(meta.name).split("(")[0]].append((dur, start))
    ops = list(events(lines[trace.OPS_LINE]))
    for prog, rs in sorted(runs.items(), key=lambda kv: -sum(d for d, _ in kv[1])):
        dur, start = sorted(rs)[len(rs) // 2]
        print(f"[program] {prog}: {len(rs)} runs in the window, median "
              f"{dur / 1e6:.3f} ms")
        if dur < 1e6:
            continue
        scopes = collections.defaultdict(collections.Counter)
        for t0, d, meta in ops:
            name = trace.base_name(meta.name)
            if t0 < start or t0 + d > start + dur or trace.CONTAINER.match(name):
                continue
            op = op_name(meta).split(";")[0]    # a merged op: its first
            # inside a scope: by the rest of the op_name; outside: by what
            # the instruction is and what it is for.  A ``copy`` named
            # ``kpool:`` / ``vpool:`` here is a whole K/V pool re-laid-out
            # on entry or copied back on exit (7-12 ms each at the
            # stand-in's 1.2 GB until ISSUE 29): the pools' stored form
            # (``kvcache.make_pools``) and the layout some instruction
            # wants no longer agree, or a reader took ``pool[layer]``
            # again.  tests/test_pool_in_place.py reads the same from the
            # compiled program without a chip.
            scopes[scope_of(op) or "(no scope)"][
                op.split("/", 2)[-1].rstrip(":") if scope_of(op)
                else f"{name} {op}".strip()] += d
        covered = sum(sum(c.values()) for c in scopes.values())
        print(f"  ops cover {covered / 1e6:.3f} ms = "
              f"{100 * covered / dur:.2f} % of the run; by scope:")
        for k, c in sorted(scopes.items(), key=lambda kv: -sum(kv[1].values())):
            v = sum(c.values())
            print(f"    {k:12s} {v / 1e6:9.3f} ms {100 * v / dur:5.1f} %  "
                  + ", ".join(f"{n} {x / 1e6:.2f}" for n, x in c.most_common(4)))


def read(stem: str) -> None:
    from benchmark.harness import trace

    with gzip.open(stem + ".xplane.pb.gz", "rb") as f:
        raw = f.read()
    path = os.path.join(tempfile.mkdtemp(prefix="mxtpu-xplane-"), "t.xplane.pb")
    with open(path, "wb") as f:
        f.write(raw)
    try:
        tr = trace.read_xplane(path, region_prefix=REGIONS)
    finally:
        shutil.rmtree(os.path.dirname(path), ignore_errors=True)
    s = trace.summarize(tr)
    idle = s.window_s - s.busy_s
    print(f"[device] window {s.window_s:.4f} s, busy {s.busy_s:.4f} s, idle "
          f"{idle:.4f} s = {100 * idle / s.window_s:.3f} %")
    split = split_gaps(tr)
    print("[idle by innermost host region] seconds and share of idle: each gap "
          "given to the region at its middle (the harness's rule) | split "
          "over the regions it crosses")
    for name in sorted(set(s.gap_seconds_by_region) | set(split),
                       key=lambda n: -split[n]):
        mid = s.gap_seconds_by_region.get(name, 0.0)
        print(f"  {name:30s} {mid:9.5f} {100 * mid / idle:6.2f} % | "
              f"{split[name]:9.5f} {100 * split[name] / idle:6.2f} %")
    print(f"  {'sum':30s} {sum(s.gap_seconds_by_region.values()):9.5f}"
          f"          | {sum(split.values()):9.5f}")

    # nesting on the host plane: every serve.step inside a bench.engine_step
    by = collections.defaultdict(list)
    for name, start, dur in tr.host_regions:
        by[name].append((start, start + dur))
    outer = by.get("bench.engine_step", [])
    steps = by.get("serve.step", [])
    nested = sum(1 for a, b in steps
                 if any(lo <= a and b <= hi for lo, hi in outer))
    print(f"[host plane] regions {({k: len(v) for k, v in sorted(by.items())})}; "
          f"{nested}/{len(steps)} serve.step inside a bench.engine_step")

    space = load_xspace(raw)
    if space is None:
        print("[program] no xplane_pb2 found: device ms by scope not read")
    else:
        by_scope(space, *trace.window_of(tr))

    # the ring's medians, to lay beside the device's
    if os.path.exists(stem + ".spans.json"):
        with open(stem + ".spans.json") as f:
            spans = json.load(f)
        durs = collections.defaultdict(list)
        for ev in spans:
            key = ev["name"]
            if key == "serve.dispatch":
                key += "[" + ev["args"]["kind"] + "]"
            durs[key].append(ev["dur"] / 1e3)
        print("[spans] median ms (count) over the window")
        for k in sorted(durs):
            print(f"  {k:34s} {statistics.median(durs[k]):10.3f}  "
                  f"({len(durs[k])})")
        # how much of its tables the decode attention had to read
        walks = [ev["args"] for ev in spans if ev["name"] == "serve.decode"
                 and "live_blocks" in ev["args"]]
        if walks:
            live = sum(a["live_blocks"] for a in walks)
            table = sum(a["table_blocks"] for a in walks)
            print(f"[blocks] {len(walks)} serve.decode spans: live_blocks "
                  f"{live} of table_blocks {table} = {100 * live / table:.2f} %"
                  f" ({live / len(walks):.1f} of {table / len(walks):.0f} a "
                  "step)")
        # which branch of the sampler each step's program took
        branches = collections.Counter(
            ev["args"]["sampler"] for ev in spans
            if ev["name"] == "serve.decode" and "sampler" in ev["args"])
        if branches:
            n = sum(branches.values())
            print(f"[sampler] {n} serve.decode spans by the sampler's "
                  "branch: " + ", ".join(
                      f"{k} {v} ({100 * v / n:.2f} %)"
                      for k, v in branches.most_common()))
        # what the routed layers' held experts were given
        routed = [ev["args"] for ev in spans if ev["name"] == "serve.decode"
                  and "experts_hit" in ev["args"]]
        if routed:
            hit = sum(a["experts_hit"] for a in routed) / len(routed)
            here = sum(a["assigned_here"] for a in routed) / len(routed)
            rows = sum(a["active"] for a in routed) / len(routed)
            print(f"[experts] {len(routed)} serve.decode spans: experts_hit "
                  f"{hit:.1f} a step (held experts with an assignment, "
                  f"summed over the routed layers), assigned_here {here:.1f} "
                  f"a step = {here / max(hit, 1e-9):.2f} rows a hit expert, "
                  f"{here / max(rows, 1e-9):.2f} a decoded row")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--keep", default=os.path.join(REPO, "chiprun_out", "trace"))
    ap.add_argument("--xplane", help="read a kept trace, run nothing")
    args = ap.parse_args()
    if bool(args.workload) == bool(args.xplane):
        ap.error("give one of --workload (run, then read) and --xplane (read)")
    stem = (args.xplane[:-len(".xplane.pb.gz")] if args.xplane
            else run_cell(args))
    read(stem)
    return 0


if __name__ == "__main__":
    sys.exit(main())
