#!/usr/bin/env python3
"""Find an open-loop cell's knee, once, when the cell is defined.

    python3 benchmark/sweep.py --workload serve-chat --rates 0.7,0.8,0.9,1.0 --seeds 1,2

One process, one set-up, one warm engine; each rate and seed gets the
cell's own lead-in and a window of ``--seconds``, lowest rate first,
and the sweep stops after the first rate that falls behind.  A window
KEEPS UP
(``keeps_up``) when every request due in it reached its first token
before the drain limit, the scheduler's queue as the window closes is
no deeper than ``max_batch``, and the tokens completed in it are at
least ``KEEP_UP_SHARE`` of the tokens offered in it.  The KNEE
(``knee``) is the highest swept rate at which every seed's window keeps
up, with no lower swept rate failing.  The traffic file then gets 0.8 x
the knee as its fixed rate; the benchmark itself never searches.  A row
also counts the window's ``preemptions`` (the program's counter
``serve.preemptions``): the cell's rate has to be one at which the pool
evicts nobody.  This is a tool
for a ``benchmark`` PR, not part of a measured run: it prints a table
and the knee, and no result line.
"""
from __future__ import annotations

import argparse
import copy
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# Completed over offered tokens that still counts as keeping up.  Not 1:
# a window's edges are ragged (far under any knee, windows of some thirty
# requests completed 86-110 % of what they offered, PR 23; windows of
# hundreds 99-102 %, PR 34).  It is the coarsest of the three tests: at
# 22 req/s, 5 % over capacity, windows completed 94-95 % with requests
# waiting seconds, and it is the queue at the close that fails them.
KEEP_UP_SHARE = 0.9


def keeps_up(row, max_batch: int) -> bool:
    return (row["no_first_token"] == 0 and row["queue_end"] <= max_batch
            and row["tokens_per_s"]
            >= KEEP_UP_SHARE * row["offered_tokens_per_s"])


def knee(rows, max_batch: int):
    """The highest rate all of whose windows keep up, below the lowest
    rate that has one that does not; None if the lowest rate fails."""
    best = None
    for rate in sorted({r["rate_per_s"] for r in rows}):
        if not all(keeps_up(r, max_batch) for r in rows
                   if r["rate_per_s"] == rate):
            break
        best = rate
    return best


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seeds", default="0",
                    help="comma-separated; every rate runs once per seed "
                    "(weights come from the first)")
    ap.add_argument("--out", default=None, help="also write the table here")
    args = ap.parse_args(argv)
    t0 = time.monotonic()
    sys.path.insert(0, REPO)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from benchmark.harness import device as dev
    from benchmark.harness import serving, spec, stats
    from benchmark.harness.runtime import Run, TraceWindow, say

    bench = spec.load_benchmark()
    cell = spec.find_cell(bench, args.workload)
    config = spec.load_config(bench, cell["config"])
    mix = spec.load_traffic(cell["traffic"])
    try:
        device = dev.require(int(cell["chips"]), rehearsal=False)
    except dev.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    from mxnet_tpu import compile_cache as cc
    cc.enable_persistent_cache(os.path.join(REPO, ".jax_cache"))
    scratch = tempfile.mkdtemp(prefix="mxtpu-sweep-")
    seeds = [int(x) for x in args.seeds.split(",")]
    run = Run(cell=cell, config=config, traffic=mix, seed=seeds[0],
              seconds=args.seconds, traced=False, process_t0=t0,
              compiles=dev.CompileCounter(), scratch=scratch)
    runner = spec.load_module("runners", mix["kind"])
    eng, params, ref = serving.build_engine(run)
    ok, notes = serving.probe(run, eng, params, ref)
    max_batch = int(eng.config.max_batch)
    rows = []
    rates = sorted(float(r) for r in args.rates.split(","))
    for rate, seed in ((r, x) for r in rates for x in seeds):
        if any(r["rate_per_s"] < rate and not r["keeps_up"] for r in rows):
            break                       # a lower rate already fell behind
        m = copy.deepcopy(mix)
        m["arrivals"]["rate_per_s"] = rate
        w = runner.window(dataclasses.replace(run, seed=seed), eng, m,
                          TraceWindow(run, 0.0))
        tt, gp = w["ttft_ms"], w["gaps_ms"]
        rows.append({
            "rate_per_s": rate, "seed": seed, "requests": w["attempted"],
            "offered_tokens_per_s": w["offered_tokens"] / args.seconds,
            "tokens_per_s": w["tokens"] / args.seconds,
            "queue_end": w["queue_end"],
            "preemptions": w["preemptions"],
            "ttft_p50_ms": stats.percentile(tt, 50),
            "ttft_p90_ms": stats.percentile(tt, 90),
            "itl_p50_ms": stats.percentile(gp, 50),
            "itl_p95_ms": stats.percentile(gp, 95),
            "no_first_token": w["attempted"] - len(tt),
            "steps": len(w["steps"]),
            "notes": w["notes"]})
        rows[-1]["keeps_up"] = keeps_up(rows[-1], max_batch)
        say("[sweep] " + json.dumps(rows[-1]))
    found = knee(rows, max_batch)
    say(f"[sweep] knee {found} req/s (highest rate whose windows all keep "
        f"up: first tokens for all, queue <= {max_batch} at the close, "
        f"completed >= {KEEP_UP_SHARE} x offered tokens); the cell's rate "
        f"is 0.8 x that" + (f" = {0.8 * found:.3f}" if found else ""))
    shutil.rmtree(scratch, ignore_errors=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump({"workload": args.workload, "seconds": args.seconds,
                       "seeds": seeds, "device": device, "probe_ok": ok,
                       "knee_req_per_s": found, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
