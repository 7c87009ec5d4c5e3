#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is one entry of ``workloads`` in ``BENCHMARK.json``: a
configuration under a traffic mix on 1 or 4 chips.  Everything the cell
needs is found from its names: ``benchmark/configs/<config>.json``,
``benchmark/traffic/<traffic>.json`` (whose ``kind`` names the runner in
``benchmark/runners/``), the per-layer readers in
``benchmark/metrics/<metric>.py``.  The last line of the output is one
JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, traced, ``breakdown``); with ``--trace 0`` the metrics
are the cell's end-to-end metrics, measured with the profiler and span
recording off, and with ``--trace 1`` its per-layer metrics.

The run fails (no result, exit code 2) unless jax's default backend is
the TPU with the chips the cell asks for.  ``--rehearsal`` is the one
way onto the CPU: tiny sizes from the files' ``rehearsal`` sections, to
debug the harness; it says what it is and reports no metric.
"""
from __future__ import annotations

import argparse
import copy
import json
import math
import os
import shutil
import sys
import tempfile
import time

PROCESS_T0 = time.monotonic()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(BENCH_DIR)


def merged(base, over):
    """``base`` with ``over`` laid on top, dicts merged key by key."""
    out = copy.deepcopy(base)
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = merged(out[k], v)
        else:
            out[k] = copy.deepcopy(v)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: "
                    "run_seconds of BENCHMARK.json)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU dry run at tiny sizes; reports no metric")
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    from benchmark.harness import spec

    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    config = spec.load_config(bench, cell["config"])
    mix = spec.load_traffic(cell["traffic"])
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if args.rehearsal:
        # asked for by name, before jax loads; never reached by finding
        # no chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={cell['chips']}")
        config = merged(config, config.get("rehearsal", {}))
        mix = merged(mix, mix.get("rehearsal", {}))
        print("*** REHEARSAL: tiny sizes on the CPU.  This checks the "
              "harness, NOT the chip; no metric is reported. ***",
              flush=True)

    from benchmark.harness import device as dev
    from benchmark.harness.runtime import Run, say

    try:
        device = dev.require(int(cell["chips"]), args.rehearsal)
    except dev.NoChip as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2

    cache_dir = "off (rehearsal)"
    if not args.rehearsal:
        from mxnet_tpu import compile_cache as cc
        cache_dir = cc.enable_persistent_cache(
            os.path.join(REPO, ".jax_cache"))
    scratch = tempfile.mkdtemp(prefix="mxtpu-bench-")   # under $TMPDIR
    say(f"[device] {device['count']} x {device['kind']!r} "
        f"({device['platform']}); cell {cell['name']} = {cell['config']} x "
        f"{cell['traffic']} on {cell['chips']} chip(s); seed {args.seed}, "
        f"{seconds} s, trace {args.trace}; compile cache {cache_dir}")

    run = Run(cell=cell, config=config, traffic=mix, seed=int(args.seed),
              seconds=seconds, traced=bool(args.trace), process_t0=PROCESS_T0,
              compiles=dev.CompileCounter(), scratch=scratch)
    runner = spec.load_module("runners", mix["kind"])
    say(f"[setup] {time.monotonic() - PROCESS_T0:.1f} s from process start "
        "to the runner (imports, the device's runtime)")
    try:
        result = runner.run(run)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    for note in result.notes:
        say(f"[incorrect] {note}")
    say(f"[cache] jax persistent cache: {run.compiles.hits} hits, "
        f"{run.compiles.misses} misses")

    device = dict(
        device,
        memory_peak_bytes=dev.memory_peak_bytes(run.held_bytes,
                                                result.temp_bytes),
        held_bytes=run.held_bytes, program_temp_bytes=result.temp_bytes)
    say(f"[memory] {run.held_bytes / 1e9:.2f} GB of arrays held around the "
        f"window + {result.temp_bytes / 1e9:.2f} GB of temporaries of its "
        f"largest program = {device['memory_peak_bytes'] / 1e9:.2f} GB "
        "estimated peak on the fullest chip; the allocator's own peak since "
        f"the process started, set-up included and temporaries not, is "
        f"{dev.allocator_bytes(int(cell['chips']), 'peak_bytes_in_use') / 1e9:.2f} GB")
    line = {"correct": bool(result.correct), "attempted": result.attempted,
            "failed": result.failed, "metrics": {}, "device": device}
    if args.rehearsal:
        line["rehearsal"] = True
        say(f"[rehearsal] set-up {result.setup_s:.1f} s; counts: "
            + json.dumps({k: v for k, v in result.facts.items()
                          if isinstance(v, (int, float, str))}))
        if args.trace:
            # the readers' control flow, not their values: none is printed
            facts = dict(result.facts, config=config, traffic=mix,
                         chips=int(cell["chips"]),
                         peaks=spec.load_peaks("TPU v5 lite"))
            answered = [
                m["name"]
                for m in spec.metrics_for(bench, cell["name"], "per_layer")
                if spec.load_reader(m["name"]).read(facts)
                is not None]
            say(f"[rehearsal] per-layer readers that found something to "
                f"read: {answered}")
    elif not args.trace:
        values = dict(result.end_to_end, setup_s=result.setup_s)
        for m in spec.metrics_for(bench, cell["name"], "end_to_end"):
            if m["name"] not in values:
                raise RuntimeError(
                    f"the runner reported no {m['name']!r} for "
                    f"{cell['name']}")
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
    else:
        summary = result.facts.get("trace")
        if summary is None or summary.busy_s <= 0:
            raise RuntimeError("the traced run recorded no operation on "
                               "the device")
        facts = dict(result.facts, config=config, traffic=mix,
                     chips=int(cell["chips"]),
                     peaks=spec.load_peaks(device["kind"]))
        for m in spec.metrics_for(bench, cell["name"], "per_layer"):
            value = spec.load_reader(m["name"]).read(facts)
            if value is not None:
                line["metrics"][m["name"]] = {"value": value,
                                              "unit": m["unit"]}
        line["device"]["busy_s"] = summary.busy_s
        line["device"]["window_s"] = summary.window_s
        line["breakdown"] = summary.breakdown()
        cost = result.facts.get("trace_cost_s", (0.0, 0.0))
        say(f"[trace] window {summary.window_s:.3f} s, busy "
            f"{summary.busy_s:.3f} s on {summary.chips} chip(s); starting "
            f"the profiler took {cost[0]:.2f} s, stopping it {cost[1]:.2f} s")
    # each number compared beside its limit: the line's last key, and
    # the last lines on standard error
    line["compared"] = {}
    for k, (v, lim) in run.compared.items():
        v = float(v)                # a non-finite number is no JSON
        line["compared"][k] = {"value": v if math.isfinite(v) else repr(v),
                               "limit": float(lim)}
        print(f"[compared] {k} {v} limit {lim}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
