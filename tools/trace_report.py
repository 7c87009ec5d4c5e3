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
  step;
* ``[pipeline]``: the ``serve.decode`` spans dispatched while another
  decode step was in flight (``overlapped``), out of all; the drains by
  ``reason``; the rows computed and thrown away (``discarded``); and the
  device's idle ms a ``serve.step``, for the steps that drained (a
  ``serve.drain`` region inside) and for those that dispatched a decode
  step without draining;
* ``[train]``, for a training cell: the step program's device ms a step
  by the trainer's phases (``forward``, ``backward`` = ops under
  ``transpose(``, a remat's recomputed forward included, ``update``,
  ``guard``, ``grad_reduce``, and ``unowned``: no scope of the
  trainer's), the top symbol nodes of each phase and the top
  instructions of ``unowned``; ``[collectives]``: count, bytes and
  device ms a step of the step's collectives by mesh axis and phase,
  the axes from the ``step.dispatch`` spans' ``collectives`` argument
  (``ShardedTrainer.collective_inventory``).

Every ``op_name`` comes from the harness's one reader of them,
``benchmark/harness/trace_ops.py``.

It measures nothing the benchmark reports and changes no number of it.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import gzip
import itertools
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
    # a cached executable keyed without its metadata would bring another
    # build's op_names (the scopes this tool reads) into the trace
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
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
    # regions by start, and the longest one that counts: the regions that
    # can cover an instant of a gap start within that much before it, so
    # each gap looks at those alone (a scan of every edge and region for
    # every gap takes many minutes on an open loop's trace)
    regions = sorted((r for r in tr.host_regions if r[0] != "bench.window"),
                     key=lambda r: r[1])
    starts = [s for _, s, _ in regions]
    longest = max((d for _, _, d in regions), default=0)
    out = collections.Counter()
    for a, b in trace.subtract([(lo, hi)], busy):
        near = regions[bisect.bisect_left(starts, a - longest):
                       bisect.bisect_right(starts, b)]
        inner = edges[bisect.bisect_right(edges, a):bisect.bisect_left(edges, b)]
        cuts = [a] + inner + [b]
        for x, y in zip(cuts, cuts[1:]):
            out[trace.region_at(near, (x + y) // 2)] += (y - x) / 1e9
    return out


def idle_by_step(tr):
    """``{"drained": [steps, idle s], "overlapped": [steps, idle s]}``:
    the first chip's idle time inside each ``serve.step`` host region of
    the window, by whether the step drained the pipeline (a
    ``serve.drain`` region inside it) or dispatched a decode step
    without draining (a ``serve.decode`` region and no drain)."""
    from benchmark.harness import trace
    lo, hi = trace.window_of(tr)
    chip = sorted(tr.device_ops)[0]
    busy = trace.union((a, b) for name, a, b
                       in trace.clip(tr.device_ops[chip], lo, hi)
                       if not trace.CONTAINER.match(name))
    idle = trace.subtract([(lo, hi)], busy)
    starts = [a for a, _ in idle]
    spans = collections.defaultdict(list)
    for name, start, dur in sorted(tr.host_regions, key=lambda r: r[1]):
        if lo <= start and start + dur <= hi:
            spans[name].append((start, start + dur))
    out = {"drained": [0, 0.0], "overlapped": [0, 0.0]}
    for a, b in spans["serve.step"]:
        def holds(name):
            inside = spans[name][bisect.bisect_left(spans[name], (a,)):]
            return any(y <= b for x, y in
                       itertools.takewhile(lambda r: r[0] <= b, inside))
        kind = ("drained" if holds("serve.drain")
                else "overlapped" if holds("serve.decode") else None)
        if kind is None:
            continue
        gap = 0
        for x, y in idle[max(bisect.bisect_right(starts, a) - 1, 0):]:
            if x >= b:
                break
            gap += max(0, min(y, b) - max(x, a))
        out[kind][0] += 1
        out[kind][1] += gap / 1e9
    return out


def by_scope(ot, lo: int, hi: int) -> None:
    """Device ms by named scope for the median run of each program."""
    from benchmark.harness import trace
    chip = min(ot.ops, default=None)
    if chip is None:
        return
    runs = collections.defaultdict(list)
    for name, start, dur in ot.trace.device_modules.get(chip, ()):
        if start >= lo and start + dur <= hi:
            runs[name.split("(")[0]].append((dur, start))
    ops = ot.ops[chip]
    for prog, rs in sorted(runs.items(), key=lambda kv: -sum(d for d, _ in kv[1])):
        dur, start = sorted(rs)[len(rs) // 2]
        print(f"[program] {prog}: {len(rs)} runs in the window, median "
              f"{dur / 1e6:.3f} ms")
        if dur < 1e6:
            continue
        scopes = collections.defaultdict(collections.Counter)
        for full, t0, d, op in ops:
            name = trace.base_name(full)
            if t0 < start or t0 + d > start + dur or trace.CONTAINER.match(name):
                continue
            op = op.split(";")[0]    # a merged op: its first
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


def train_step(ot, spans) -> None:
    """``[train]`` and ``[collectives]``: the training step program read
    by the trainer's phases, and its collectives by mesh axis."""
    from benchmark.harness import trace_ops
    dispatch = [ev["args"] for ev in spans if ev["name"] == "step.dispatch"]
    axes = next((a["collectives"] for a in reversed(dispatch)
                 if a.get("collectives")), None)
    split = trace_ops.step_split(ot, collectives=axes)
    if split is None or not any(split["phase_ms"][p]
                                for p in trace_ops.PHASES if p):
        return
    phases = split["phase_ms"]
    total = sum(phases.values())
    med = split["run_ms_median"]
    print(f"[train] {split['program']}: {split['runs']} runs in the window, "
          f"median {med:.3f} ms; device ms a step by phase (mean over "
          f"{split['chips']} chip(s); sum {total:.3f} = "
          f"{100 * total / med:.2f} % of the median run):")
    for p in trace_ops.PHASES:
        v = phases[p]
        if not v and p in ("grad_reduce", "guard"):
            continue
        top = split["nodes"].get(p, {}) if p else split["unowned"]
        print(f"  {p or 'unowned':12s} {v:9.3f} ms "
              f"{100 * v / max(total, 1e-9):5.1f} %  "
              + ", ".join(f"{n} {x:.2f}" for n, x in
                          collections.Counter(top).most_common(6)))
    print("[train] the step's per-layer numbers: " + ", ".join(
        f"{k} {v:.3f}" for k, v in trace_ops.metric_values(split).items()))
    if axes is None and not split["collective_all_ms"]:
        return
    held = collections.defaultdict(lambda: [0, 0])
    for ax, names in (axes or {}).items():
        for n, b in names.items():
            if not trace_ops.trace.base_name(n).endswith("-done"):
                held[ax][0] += 1
                held[ax][1] += b
    by_ax = trace_ops.axis_totals(split)
    print(f"[collectives] {sum(c for c, _ in held.values())} in the step "
          f"program; {split['collective_all_ms']:.3f} device ms a step, by "
          "axis ('?': no axis known) and phase:")
    for ax in sorted(set(held) | set(by_ax)):
        print(f"  {ax or '(none)':12s} {held[ax][0]:4d} x "
              f"{held[ax][1] / 1e6:10.2f} MB {by_ax.get(ax, 0.0):9.3f} ms  "
              + ", ".join(f"{ph or 'unowned'} {v:.3f}" for (a, ph), v
                          in sorted(split["collective_ms"].items())
                          if a == ax))


def read(stem: str) -> None:
    from benchmark.harness import trace, trace_ops

    with gzip.open(stem + ".xplane.pb.gz", "rb") as f:
        ot = trace_ops.read(f.read(), region_prefix=REGIONS)
    if ot is None:
        sys.exit("no xplane_pb2 is installed: the trace cannot be read")
    tr = ot.trace
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

    per_step = idle_by_step(tr)
    by_scope(ot, *trace.window_of(tr))
    spans = []
    if os.path.exists(stem + ".spans.json"):
        with open(stem + ".spans.json") as f:
            spans = json.load(f)
    train_step(ot, spans)

    # the ring's medians, to lay beside the device's
    if spans:
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
        # the decode pipeline: how often a step was queued behind another
        decodes = [ev["args"] for ev in spans if ev["name"] == "serve.decode"
                   and "overlapped" in ev["args"]]
        if decodes:
            over = sum(a["overlapped"] for a in decodes)
            drains = collections.Counter(
                ev["args"]["reason"] for ev in spans
                if ev["name"] == "serve.drain")
            print(f"[pipeline] {over} of {len(decodes)} serve.decode spans "
                  f"overlapped ({100 * over / len(decodes):.2f} %); drains "
                  + (", ".join(f"{k} {v}" for k, v in drains.most_common())
                     or "none")
                  + f"; rows discarded {sum(a.get('discarded', 0) for a in decodes)}"
                  "; device idle ms a step (traced window): " + ", ".join(
                      f"{k} {1e3 * s / max(n, 1):.3f} over {n}"
                      for k, (n, s) in per_step.items()))


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
