#!/usr/bin/env python
"""Scrape training logs into a table (reference tools/parse_log.py).

Parses the logging output of ``FeedForward.fit`` / ``Module.fit`` /
``ShardedTrainer.fit`` — epoch times, train/validation metrics,
Speedometer throughput — and prints a per-epoch markdown table.

``--diff-resilience A B`` instead diffs the training-guardrail epoch counters
(``Epoch[N] Resilience: skipped=... overflows=... rollbacks=...
loss-scale=... lr-scale=...``) of two runs — the triage view for
stability changes (docs/resilience.md).

``--diff-audit A B`` diffs two ``bench.py --audit`` reports
(BENCH_r08.json-style: a JSON array, or one JSON object per line): for
every audited config present in both, the per-bucket HBM pass counts
(reads/writes), bucket count, findings, and pass verdict — the
regression-triage view for grad-bucket memory-traffic changes
(docs/static_analysis.md).

``--diff-serve A B`` diffs two ``bench.py --serve`` reports
(BENCH_r10.json-style): tokens/s and p99 per-token latency per serving
config — exits 1 when tokens/s regresses beyond the noise floor or p99
grows more than 10% (docs/serving.md).

``--diff-metrics A.jsonl B.jsonl`` diffs two telemetry metric streams
(``MXNET_TPU_METRICS_FILE``): the final registry snapshots' headline
series (mean step time from the ``step.host_ms`` histogram, guard /
sentinel counters, collective wire bytes, compile-cache hits, derived
MFU/bandwidth gauges), plus any tee'd audit rows and per-epoch
resilience rows — the one-command answer to "what changed between
these two runs" (docs/observability.md).

``--diff-elastic A B`` diffs two ``bench.py --elastic`` reports
(BENCH_r14.json): per-resize training-pause deltas, with absolute
gates on B's correctness fields — steps lost, retraces, and the
bitwise post-resize degradation check must all hold
(docs/elastic.md).

``--diff-staticcheck A B`` diffs two ``staticcheck <cmd> --json``
reports keyed by ``(rule, location)``: any unsuppressed non-info
finding new in B is a regression (stderr + exit 1); findings present
only in A are listed as resolved (docs/static_analysis.md).
"""
import argparse
import json
import re
import sys
from collections import defaultdict

EPOCH_RE = re.compile(r"Epoch\[(\d+)\]")
# "Time cost=1.23" (FeedForward/Module) or "Elapsed=1.23s" (ShardedTrainer)
TIME_RE = re.compile(r"Epoch\[(\d+)\].*?(?:Time cost|Elapsed)=([\d.]+)")
VAL_RE = re.compile(
    r"Epoch\[(\d+)\] (?:Mesh-)?Validation-([\w-]+)=([\d.eE+-]+)")
TRAIN_RE = re.compile(
    r"Epoch\[(\d+)\].*?(?:Mesh-)?Train-([\w-]+)=([\d.eE+-]+)")
SPEED_RE = re.compile(r"Epoch\[(\d+)\].*?Speed: ([\d.]+) samples/sec")
# "Epoch[2] Resilience: skipped=1 overflows=0 rollbacks=0
#  loss-scale=512 lr-scale=0.5" (ShardedTrainer.fit, guard on)
RESIL_RE = re.compile(
    r"Epoch\[(\d+)\] Resilience: skipped=(\d+) overflows=(\d+) "
    r"rollbacks=(\d+) loss-scale=([\d.eE+-]+) lr-scale=([\d.eE+-]+)")
RESIL_KEYS = ("skipped", "overflows", "rollbacks", "loss-scale",
              "lr-scale")


def parse(lines):
    rows = defaultdict(dict)
    speeds = defaultdict(list)
    for line in lines:
        m = TIME_RE.search(line)
        if m:
            rows[int(m.group(1))]["time"] = float(m.group(2))
        m = VAL_RE.search(line)
        if m:
            rows[int(m.group(1))][f"val-{m.group(2)}"] = float(m.group(3))
        m = TRAIN_RE.search(line)
        if m:
            rows[int(m.group(1))][f"train-{m.group(2)}"] = float(m.group(3))
        m = SPEED_RE.search(line)
        if m:
            speeds[int(m.group(1))].append(float(m.group(2)))
        m = RESIL_RE.search(line)
        if m:
            for i, key in enumerate(RESIL_KEYS):
                rows[int(m.group(1))][key] = float(m.group(2 + i))
    for epoch, sp in speeds.items():
        rows[epoch]["speed"] = sum(sp) / len(sp)
    return rows


def read_resilience(path):
    """{epoch: {counter: value}} from a run's Resilience epoch lines."""
    out = {}
    with open(path) as f:
        for line in f:
            m = RESIL_RE.search(line)
            if m:
                out[int(m.group(1))] = {
                    key: float(m.group(2 + i))
                    for i, key in enumerate(RESIL_KEYS)}
    return out


def diff_resilience(path_a, path_b):
    """Per-epoch guardrail-counter comparison of two runs (B - A):
    the triage view for 'did this change make training less stable'."""
    a, b = read_resilience(path_a), read_resilience(path_b)
    if not a and not b:
        print("no Resilience epoch lines in either log (guard off?)",
              file=sys.stderr)
        return 1
    epochs = sorted(set(a) | set(b))
    print("| epoch | " + " | ".join(
        f"{k} A | {k} B | Δ" for k in RESIL_KEYS) + " |")
    print("|" + "---|" * (1 + 3 * len(RESIL_KEYS)))
    for ep in epochs:
        cells = []
        for k in RESIL_KEYS:
            va = a.get(ep, {}).get(k)
            vb = b.get(ep, {}).get(k)
            cells.append("" if va is None else f"{va:g}")
            cells.append("" if vb is None else f"{vb:g}")
            cells.append(f"{vb - va:+g}"
                         if va is not None and vb is not None else "")
        print(f"| {ep} | " + " | ".join(cells) + " |")
    for name, run in (("A", a), ("B", b)):
        if run:
            last = run[max(run)]
            print(f"{name} final: " + " ".join(
                f"{k}={last[k]:g}" for k in RESIL_KEYS), file=sys.stderr)
    return 0


def read_audits(path):
    """{metric: row} for the audit rows of a ``bench.py --audit``
    report.  Accepts either a whole-file JSON array (the BENCH_r09.json
    format) or one JSON object per line (tee'd stdout); audit rows are
    the grad-bucket HBM-pass ones (``writes_per_bucket``) and the r9
    collective wire-bytes ones (``wire_bytes``)."""
    with open(path) as f:
        text = f.read()
    try:
        recs = json.loads(text)
        if isinstance(recs, dict):
            recs = [recs]
    except ValueError:
        recs = []
        for line in text.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                recs.append(json.loads(line))
            except ValueError:
                continue
    # pre-r8 reports name the (only) legacy chain without an
    # ", unfused" label; normalize it away so r7->r8 diffs line up the
    # like-for-like rows (the fused rows stay distinct)
    return {rec["metric"].replace(", unfused,", ","): rec for rec in recs
            if isinstance(rec, dict)
            and ("writes_per_bucket" in rec or "wire_bytes" in rec)}


# a wire-bytes row reuses "value" for the f32/wire compression ratio, so
# the reads column doubles as it there; the wire column stays empty on
# HBM-pass rows and vice versa
AUDIT_KEYS = (("reads", "value"), ("writes", "writes_per_bucket"),
              ("buckets", "buckets"), ("wire_B", "wire_bytes"),
              ("findings", "findings"), ("pass", "pass"))


def diff_audits(path_a, path_b):
    """Per-config HBM-pass comparison of two audit reports (B - A): the
    triage view for 'did this change add a sweep over the grad bucket'."""
    a, b = read_audits(path_a), read_audits(path_b)
    common = [m for m in a if m in b]
    if not common:
        print("no common grad-bucket audit rows between the two reports",
              file=sys.stderr)
        return 1
    worse = 0
    print("| config | " + " | ".join(
        f"{k} A | {k} B | Δ" for k, _ in AUDIT_KEYS) + " |")
    print("|" + "---|" * (1 + 3 * len(AUDIT_KEYS)))
    for metric in common:
        ra, rb = a[metric], b[metric]
        cells = []
        for _, key in AUDIT_KEYS:
            va, vb = ra.get(key), rb.get(key)
            for v in (va, vb):
                cells.append("" if v is None else f"{v:g}"
                             if isinstance(v, (int, float))
                             and not isinstance(v, bool) else str(v))
            if (isinstance(va, (int, float)) and isinstance(vb, (int, float))
                    and not isinstance(va, bool) and not isinstance(vb, bool)):
                cells.append(f"{vb - va:+g}")
                if key in ("writes_per_bucket", "findings", "wire_bytes"):
                    worse += vb > va
                elif key == "value":
                    # reads/bucket must not grow; a compression ratio
                    # (wire-bytes row) must not SHRINK
                    worse += ((vb < va) if "wire_bytes" in ra
                              else (vb > va))
            else:
                cells.append("")
        print(f"| {metric} | " + " | ".join(cells) + " |")
    only = [m for m in (set(a) | set(b)) if m not in common]
    if only:
        print(f"\n(unmatched configs: {sorted(only)})", file=sys.stderr)
    if worse:
        print(f"{worse} count(s) regressed (B > A)", file=sys.stderr)
        return 1
    return 0


def _read_bench_rows(path, prefix):
    """{metric: row} for the rows of a bench.py report (JSON array, or
    one JSON object per line) whose metric starts with ``prefix``."""
    with open(path) as f:
        text = f.read()
    try:
        recs = json.loads(text)
        if isinstance(recs, dict):
            recs = [recs]
    except ValueError:
        recs = []
        for line in text.splitlines():
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                recs.append(json.loads(line))
            except ValueError:
                continue
    return {rec["metric"]: rec for rec in recs
            if isinstance(rec, dict)
            and str(rec.get("metric", "")).startswith(prefix)}


def read_serve(path):
    """{metric: row} for the serving rows of a ``bench.py --serve``
    report (BENCH_r10.json-style JSON array, or one JSON object per
    line).  Serve rows carry tokens/s plus per-token latency
    percentiles (``p99_token_ms``) or the headline speedup ratio."""
    return _read_bench_rows(path, "serve ")


# tokens/s gets a small noise floor (a shared CPU host wobbles a few
# percent run to run); the p99 latency bars are the ISSUE 10/11 contract
SERVE_TOKENS_TOL = 0.05   # B may be up to 5% below A before failing
SERVE_P99_GROWTH = 0.10   # p99 per-token latency may grow up to 10%
SERVE_TTFT_GROWTH = 0.10  # p99 TTFT may grow up to 10%
# a p99 over ~500 millisecond-scale intervals moves 1-2 ms run to run
# from scheduler jitter alone; latency growth below this absolute delta
# is noise, not regression, however large the percentage looks
SERVE_LAT_SLACK_MS = 2.0
# swap latency is drain-dominated (in-flight decode finishing), so it
# wobbles with scheduler noise far more than a p99 over hundreds of
# intervals does — gate only a blow-up, not jitter
SWAP_MS_GROWTH = 0.50
SWAP_MS_SLACK = 25.0
# speculative acceptance is a property of drafter + workload, not of
# host load: a real drop means the drafter (or the acceptance rule)
# changed behavior.  Gate absolute drops beyond this, not noise.
SPEC_ACCEPT_DROP = 0.10
# prefix-cache hit rate is likewise workload-determined (the bench
# replays a fixed shared-prefix trace): a drop means probe/publish
# behavior changed, not that the host was busy
PREFIX_HIT_DROP = 0.10
# trace-gameday shed rate is a deterministic function of the virtual-
# time schedule, so even small absolute growth means admission or
# autoscale policy changed; latency on trace rows is wall-clock under a
# virtual-time driver (jitters >10% run to run) and is gated by each
# row's own SLO bars instead of a relative diff
TRACE_SHED_GROWTH = 0.05


def diff_serve(path_a, path_b):
    """Per-config serving comparison of two ``bench.py --serve``
    reports (B relative to A): tokens/s must not regress (beyond the
    5% noise floor) and neither p99 per-token latency nor p99 TTFT may
    grow more than 10% — the triage gate for serving-path changes.
    The TTFT gate skips rows where either side predates the field
    (r10 reports carry only p50 TTFT).

    Chaos rows (``bench.py --serve --chaos`` failover scenario) are
    gated on correctness, not latency: the scenario in report B must
    have completed every request with zero tokens lost and
    byte-identical streams — a failover that drops or mutates tokens
    is a correctness regression no throughput can buy back.

    Hotswap rows (``bench.py --serve --hotswap`` rolling-deploy
    scenario) get the same correctness gate plus two of their own: the
    swap must have run zero post-warmup retraces (a retracing "hot"
    swap is the bug the whole design exists to prevent), and the
    per-replica swap latency may not blow up between reports (growth
    over ``SWAP_MS_GROWTH`` beyond the absolute slack).

    Speculative rows (``bench.py --serve --speculate``, BENCH_r15)
    gate the round-15 contract: the accept-friendly row must keep its
    own >= 2x pass, greedy streams must stay byte-identical to the
    non-speculative engine, zero post-warmup retraces, acceptance rate
    may not drop more than ``SPEC_ACCEPT_DROP`` absolute, and the
    speedup ratio gets the ``SERVE_TOKENS_TOL`` noise floor.

    Prefix rows (``bench.py --serve --prefix``, BENCH_r16) gate the
    round-18 contract: the gated shared-prefix row must keep its own
    pass (cached TTFT and tokens/s bars), warm streams must stay
    byte-identical to the cache-cold engine with zero post-warmup
    retraces, cached TTFT may not grow past ``SERVE_TTFT_GROWTH``
    (beyond the absolute slack), and the hit rate — a
    workload-determined property — may not fall more than
    ``PREFIX_HIT_DROP`` absolute between reports.

    Trace rows (``bench.py --serve --trace``, BENCH_r17) gate the
    round-19 contract on report B: both rows keep their own SLO-bar
    pass, the autoscaler moved in both directions (>= 1 up and >= 1
    down), failovers stayed replay-exact (gameday streams
    byte-identical to clean; same-seed replay byte-identical including
    the scale schedule and shed set), zero post-warmup retraces, a
    clean block ledger, and the deterministic shed rate may not grow
    more than ``TRACE_SHED_GROWTH`` absolute vs report A.  Trace rows
    are excluded from the relative latency gates above: their TTFT/ITL
    are wall-clock measurements under a virtual-time driver and jitter
    beyond the 10% bars run to run."""
    a, b = read_serve(path_a), read_serve(path_b)
    common = [m for m in a if m in b]
    if not common:
        print("no common serve rows between the two reports",
              file=sys.stderr)
        return 1
    worse = []
    print("| config | tok/s A | tok/s B | Δ% | p99 A | p99 B | Δ% "
          "| ttft99 A | ttft99 B | Δ% |")
    print("|---|---|---|---|---|---|---|---|---|---|")
    for metric in common:
        if " trace " in metric:
            continue          # gated below on the round-19 contract
        ra, rb = a[metric], b[metric]
        cells = []
        ta = ra.get("value") if ra.get("unit") == "tokens/s" else None
        tb = rb.get("value") if rb.get("unit") == "tokens/s" else None
        for va, vb, shrink_ok, bar, what in (
                (ta, tb, False, SERVE_TOKENS_TOL, "tokens/s"),
                (ra.get("p99_token_ms"), rb.get("p99_token_ms"),
                 True, SERVE_P99_GROWTH, "p99_token_ms"),
                (ra.get("p99_ttft_ms"), rb.get("p99_ttft_ms"),
                 True, SERVE_TTFT_GROWTH, "p99_ttft_ms")):
            cells.append("" if va is None else f"{va:g}")
            cells.append("" if vb is None else f"{vb:g}")
            if va and vb is not None:
                pct = (vb - va) / va
                cells.append(f"{100 * pct:+.1f}%")
                if shrink_ok and pct > bar \
                        and vb - va > SERVE_LAT_SLACK_MS:
                    worse.append(f"{metric}: {what} grew {100 * pct:.1f}%"
                                 f" (> {100 * bar:.0f}%)")
                elif not shrink_ok and pct < -bar:
                    worse.append(f"{metric}: {what} fell {-100 * pct:.1f}%"
                                 f" (> {100 * bar:.0f}% floor)")
            else:
                cells.append("")
        print(f"| {metric} | " + " | ".join(cells) + " |")
    only = [m for m in (set(a) | set(b)) if m not in common]
    if only:
        print(f"\n(unmatched configs: {sorted(only)})", file=sys.stderr)
    for metric, rec in b.items():
        if "chaos" not in metric and "hotswap" not in metric:
            continue
        what = "failover" if "chaos" in metric else "rolling swap"
        if rec.get("completed") != rec.get("total"):
            worse.append(
                f"{metric}: scenario incomplete "
                f"({rec.get('completed')}/{rec.get('total')} requests)")
        if rec.get("tokens_lost", 0) != 0:
            worse.append(f"{metric}: {what} lost "
                         f"{rec.get('tokens_lost')} tokens (must be 0)")
        if rec.get("streams_identical") is False:
            worse.append(f"{metric}: {what} streams diverged from the "
                         "clean run")
        if "hotswap" not in metric:
            continue
        if rec.get("retraces_after_warmup", 0) != 0:
            worse.append(f"{metric}: hot swap retraced "
                         f"{rec.get('retraces_after_warmup')} programs "
                         "(must reuse every warm program)")
        sa = a.get(metric, {}).get("swap_ms_max")
        sb = rec.get("swap_ms_max")
        if sa and sb is not None:
            pct = (sb - sa) / sa
            if pct > SWAP_MS_GROWTH and sb - sa > SWAP_MS_SLACK:
                worse.append(f"{metric}: swap latency grew "
                             f"{100 * pct:.0f}% ({sa:g} -> {sb:g} ms)")
    for metric, rec in b.items():
        if "speculative" not in metric:
            continue
        # the BENCH_r15 contract: the gated accept-friendly row keeps
        # its >= 2x bar (the row's own "pass"), greedy streams stay
        # byte-identical to the non-speculative engine, nothing
        # retraces post-warmup, and acceptance — a drafter-behavior
        # property, not a load-wobble one — may not fall more than
        # SPEC_ACCEPT_DROP absolute between reports.  The speedup
        # ratio itself gets the same noise floor as raw tokens/s.
        if rec.get("pass") is False:
            worse.append(f"{metric}: speculative row failed its own "
                         "gate in report B")
        if rec.get("temperature") == 0 \
                and rec.get("streams_identical") is False:
            worse.append(f"{metric}: greedy speculative streams "
                         "diverged from the non-speculative engine "
                         "(replay-exactness broken)")
        if rec.get("new_traces", 0) != 0:
            worse.append(f"{metric}: speculative scenario retraced "
                         f"{rec.get('new_traces')} programs post-warmup")
        ra = a.get(metric, {})
        aa, ab = ra.get("accept_rate"), rec.get("accept_rate")
        if aa is not None and ab is not None \
                and aa - ab > SPEC_ACCEPT_DROP:
            worse.append(f"{metric}: acceptance rate fell {aa:g} -> "
                         f"{ab:g} (> {SPEC_ACCEPT_DROP:g} absolute)")
        sa, sb = ra.get("value"), rec.get("value")
        if sa and sb is not None \
                and (sb - sa) / sa < -SERVE_TOKENS_TOL:
            worse.append(f"{metric}: speculative speedup fell "
                         f"{sa:g}x -> {sb:g}x")
    for metric, rec in b.items():
        if "prefix" not in metric:
            continue
        # the BENCH_r16 contract (docs/serving.md §Cross-request
        # prefix cache): warm streams byte-identical to cache-cold,
        # zero retraces, cached TTFT bounded, hit rate stable
        if rec.get("pass") is False:
            worse.append(f"{metric}: prefix-cache row failed its own "
                         "gate in report B")
        if rec.get("streams_identical") is False:
            worse.append(f"{metric}: warm streams diverged from the "
                         "cache-cold engine (byte-identity broken)")
        if rec.get("new_traces", 0) != 0:
            worse.append(f"{metric}: prefix-cache scenario retraced "
                         f"{rec.get('new_traces')} programs post-warmup")
        ra = a.get(metric, {})
        ca, cb = ra.get("cached_ttft_ms"), rec.get("cached_ttft_ms")
        if ca and cb is not None:
            pct = (cb - ca) / ca
            if pct > SERVE_TTFT_GROWTH and cb - ca > SERVE_LAT_SLACK_MS:
                worse.append(f"{metric}: cached TTFT grew "
                             f"{100 * pct:.0f}% ({ca:g} -> {cb:g} ms)")
        ha, hb = ra.get("hit_rate"), rec.get("hit_rate")
        if ha is not None and hb is not None \
                and ha - hb > PREFIX_HIT_DROP:
            worse.append(f"{metric}: prefix hit rate fell {ha:g} -> "
                         f"{hb:g} (> {PREFIX_HIT_DROP:g} absolute)")
    for metric, rec in b.items():
        if " trace " not in metric:
            continue
        # the BENCH_r17 contract (docs/serving.md §Traffic simulation
        # & autoscaling): SLO bars hold, the closed loop moved both
        # ways, failovers stayed replay-exact, nothing retraced or
        # leaked, and the deterministic shed rate stayed put
        if rec.get("pass") is False:
            worse.append(f"{metric}: trace row failed its own SLO/"
                         "replay gate in report B")
        if rec.get("scale_ups", 0) < 1 or rec.get("scale_downs", 0) < 1:
            worse.append(f"{metric}: autoscaler did not move both ways "
                         f"({rec.get('scale_ups', 0)} ups / "
                         f"{rec.get('scale_downs', 0)} downs; need >= 1 "
                         "each)")
        if rec.get("streams_identical") is False:
            worse.append(f"{metric}: gameday streams diverged from the "
                         "clean run (failover byte-identity broken)")
        if rec.get("replay_identical") is False:
            worse.append(f"{metric}: same-seed replay diverged (streams"
                         "/scale schedule/shed set must be "
                         "byte-identical)")
        if rec.get("retraces_after_warmup", 0) != 0:
            worse.append(f"{metric}: trace scenario retraced "
                         f"{rec.get('retraces_after_warmup')} programs "
                         "post-warmup (autoscaled replicas must reuse "
                         "warm programs)")
        if rec.get("kv_leak", 0) != 0:
            worse.append(f"{metric}: {rec.get('kv_leak')} KV blocks "
                         "leaked (ledger must be clean)")
        sa = a.get(metric, {}).get("shed_rate")
        sb = rec.get("shed_rate")
        if sa is not None and sb is not None \
                and sb - sa > TRACE_SHED_GROWTH:
            worse.append(f"{metric}: shed rate grew {sa:g} -> {sb:g} "
                         f"(> {TRACE_SHED_GROWTH:g} absolute — the "
                         "trace is deterministic, so admission or "
                         "autoscale policy changed)")
    for msg in worse:
        print(f"REGRESSED: {msg}", file=sys.stderr)
    return 1 if worse else 0


# a resize pause is tiny (tens of ms) and jittery on shared CI; gate a
# blow-up, not noise — both the relative AND absolute bars must trip
ELASTIC_PAUSE_GROWTH = 0.50
ELASTIC_PAUSE_SLACK_MS = 50.0


def diff_elastic(path_a, path_b):
    """Diff two ``bench.py --elastic`` reports (BENCH_r14.json), B
    relative to A (docs/elastic.md).

    Correctness rows are absolute gates on B alone: every resize must
    lose 0 steps and run 0 retraces, and the round-trip summary row's
    ``pass`` verdict (which folds in the bitwise degradation check)
    must hold — an elastic resize that drops an update or compiles
    cold has regressed no matter what A looked like.  The resize
    *pause* is the one relative gate: growth beyond
    ``ELASTIC_PAUSE_GROWTH`` AND ``ELASTIC_PAUSE_SLACK_MS`` fails."""
    a = _read_bench_rows(path_a, "elastic ")
    b = _read_bench_rows(path_b, "elastic ")
    if not b:
        print(f"no elastic rows in {path_b}", file=sys.stderr)
        return 1
    worse = []
    print("| config | pause A | pause B | Δ% | lost B | retraces B |")
    print("|---|---|---|---|---|---|")
    for metric, rb in b.items():
        ra = a.get(metric, {})
        if rb.get("steps_lost", 0) != 0:
            worse.append(f"{metric}: lost {rb['steps_lost']} steps "
                         "(drain-then-snapshot must be exact)")
        if rb.get("retraces", 0) != 0:
            worse.append(f"{metric}: {rb['retraces']} retraces (warm "
                         "restart must hit the compile cache)")
        if rb.get("pass") is False:
            worse.append(f"{metric}: pass=false "
                         f"(target: {rb.get('target', '?')})")
        if rb.get("bitwise_vs_fresh_mesh") is False:
            worse.append(f"{metric}: post-resize segment diverged from "
                         "a fresh run on the new mesh (must be bitwise)")
        pa, pb = ra.get("pause_ms"), rb.get("pause_ms")
        delta = ""
        if pa and pb is not None:
            pct = (pb - pa) / pa
            delta = f"{100 * pct:+.1f}%"
            if pct > ELASTIC_PAUSE_GROWTH \
                    and pb - pa > ELASTIC_PAUSE_SLACK_MS:
                worse.append(f"{metric}: resize pause grew "
                             f"{100 * pct:.0f}% ({pa:g} -> {pb:g} ms)")
        print(f"| {metric} | {pa if pa is not None else ''} "
              f"| {pb if pb is not None else ''} | {delta} "
              f"| {rb.get('steps_lost', '')} | {rb.get('retraces', '')} |")
    for msg in worse:
        print(f"REGRESSED: {msg}", file=sys.stderr)
    return 1 if worse else 0


def read_metrics_stream(path):
    """Parse a telemetry JSONL stream (``MXNET_TPU_METRICS_FILE``):
    returns ``(final_snapshot, step_rows, resil_rows)``.  The LAST
    ``kind=metrics`` row wins (counters are cumulative); step and
    resilience rows are kept in order."""
    snap = {}
    steps, resil = [], []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line.startswith("{"):
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            if not isinstance(rec, dict):
                continue
            kind = rec.get("kind")
            if kind == "metrics" and isinstance(rec.get("metrics"), dict):
                snap = rec["metrics"]
            elif kind == "step":
                steps.append(rec)
            elif kind == "resilience":
                resil.append(rec)
    return snap, steps, resil


def _derive_metrics(snap):
    """Headline series from a flat metrics snapshot: derived mean step
    time plus the guard / wire / cache / derived-gauge families."""
    out = {}
    n = snap.get("step.host_ms.count")
    if n:
        out["step_ms_mean"] = snap["step.host_ms.sum"] / n
    for key, val in snap.items():
        fam = key.split(".", 1)[0].split("{", 1)[0]
        if fam in ("step", "resilience", "sentinel", "collectives",
                   "compile_cache", "compile", "derived", "trainer",
                   "ckpt", "watchdog", "io", "recordio", "flight"):
            out[key] = val
    return out


def diff_metrics(path_a, path_b):
    """Diff two telemetry JSONL streams: final-snapshot headline series
    (step time, guard counters, wire bytes, cache hits, derived
    gauges), then any audit rows and per-epoch resilience rows the
    streams carry."""
    sa, steps_a, resil_a = read_metrics_stream(path_a)
    sb, steps_b, resil_b = read_metrics_stream(path_b)
    if not sa and not sb:
        print("no kind=metrics snapshot rows in either stream "
              "(MXNET_TPU_METRICS_FILE unset during the runs?)",
              file=sys.stderr)
        return 1
    da, db = _derive_metrics(sa), _derive_metrics(sb)
    keys = sorted(set(da) | set(db))
    print(f"final metrics snapshot ({len(steps_a)} vs {len(steps_b)} "
          "step rows)")
    print("| series | A | B | Δ |")
    print("|---|---|---|---|")
    for k in keys:
        va, vb = da.get(k), db.get(k)
        cells = ["" if v is None else f"{v:g}" for v in (va, vb)]
        cells.append(f"{vb - va:+g}"
                     if va is not None and vb is not None else "")
        print(f"| {k} | " + " | ".join(cells) + " |")
    other = sorted((set(sa) ^ set(sb)) - set(keys))
    if other:
        print(f"(series present in only one stream: {other})",
              file=sys.stderr)

    # audit rows (bench.py tees them with kind=audit) share the
    # BENCH_rNN row schema, so the audit differ applies as-is
    if read_audits(path_a) and read_audits(path_b):
        print("\naudit rows")
        diff_audits(path_a, path_b)

    ra = {r.get("epoch"): r for r in resil_a}
    rb = {r.get("epoch"): r for r in resil_b}
    epochs = sorted(set(ra) & set(rb), key=lambda e: (e is None, e))
    if epochs:
        keys = sorted(k for e in epochs
                      for k in set(ra[e]) & set(rb[e])
                      if isinstance(ra[e][k], (int, float))
                      and not isinstance(ra[e][k], bool)
                      and k not in ("ts", "pid", "epoch"))
        keys = sorted(set(keys))
        print("\nresilience rows")
        print("| epoch | " + " | ".join(
            f"{k} A | {k} B | Δ" for k in keys) + " |")
        print("|" + "---|" * (1 + 3 * len(keys)))
        for e in epochs:
            cells = []
            for k in keys:
                va, vb = ra[e].get(k), rb[e].get(k)
                cells.append("" if va is None else f"{va:g}")
                cells.append("" if vb is None else f"{vb:g}")
                cells.append(f"{vb - va:+g}" if None not in (va, vb)
                             else "")
            print(f"| {e} | " + " | ".join(cells) + " |")
    return 0


def diff_staticcheck(path_a, path_b):
    """Diff two ``staticcheck <cmd> --json`` reports keyed by
    ``(rule, location)``.  Findings that are new in B (and not
    suppressed) are regressions — printed to stderr, exit 1; findings
    present only in A are listed as resolved.  ``info``-severity
    findings are observational and never regress the diff."""
    def load(path):
        with open(path) as f:
            doc = json.load(f)
        out = {}
        for fd in doc.get("findings", []):
            if fd.get("suppressed") or fd.get("severity") == "info":
                continue
            loc = fd.get("program") or (
                f"{fd.get('path', '')}:{fd.get('line', 0)}")
            out[(fd["rule"], loc)] = fd
        return out
    a, b = load(path_a), load(path_b)
    resolved = sorted(set(a) - set(b))
    new = sorted(set(b) - set(a))
    print(f"staticcheck diff: {len(a)} -> {len(b)} findings "
          f"({len(new)} new, {len(resolved)} resolved)")
    for rule, loc in resolved:
        print(f"resolved: {loc}: [{rule}]")
    for rule, loc in new:
        print(f"REGRESSED: {loc}: [{rule}] "
              f"{b[(rule, loc)].get('message', '')}", file=sys.stderr)
    return 1 if new else 0


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("logfile", nargs="?", help="default: stdin")
    ap.add_argument("--diff-resilience", nargs=2, metavar=("A", "B"),
                    help="diff the guardrail counters (skipped/overflows/"
                    "rollbacks/loss-scale/lr-scale) of two runs' epoch "
                    "logs, B relative to A")
    ap.add_argument("--diff-audit", nargs=2, metavar=("A", "B"),
                    help="diff the grad-bucket HBM pass counts of two "
                    "bench.py --audit reports (reads/writes/buckets/"
                    "findings per config, B relative to A; exits 1 if "
                    "any count regressed)")
    ap.add_argument("--diff-metrics", nargs=2, metavar=("A", "B"),
                    help="diff two telemetry JSONL streams "
                    "(MXNET_TPU_METRICS_FILE): headline metric series "
                    "(step time, guard, wire bytes, cache hits), plus "
                    "audit and resilience rows, B relative to A")
    ap.add_argument("--diff-serve", nargs=2, metavar=("A", "B"),
                    help="diff two bench.py --serve reports "
                    "(BENCH_r10.json): exits 1 if tokens/s regressed "
                    "beyond the 5%% noise floor or p99 per-token "
                    "latency grew more than 10%%, B relative to A")
    ap.add_argument("--diff-elastic", nargs=2, metavar=("A", "B"),
                    help="diff two bench.py --elastic reports "
                    "(BENCH_r14.json): exits 1 if any resize in B lost "
                    "steps, retraced, failed the bitwise degradation "
                    "check, or if the resize pause blew up vs A")
    ap.add_argument("--diff-staticcheck", nargs=2, metavar=("A", "B"),
                    help="diff two `staticcheck <cmd> --json` reports "
                    "keyed by (rule, location): exits 1 on any new "
                    "unsuppressed non-info finding in B, lists findings "
                    "resolved since A")
    args = ap.parse_args()
    if args.diff_staticcheck:
        return diff_staticcheck(*args.diff_staticcheck)
    if args.diff_serve:
        return diff_serve(*args.diff_serve)
    if args.diff_elastic:
        return diff_elastic(*args.diff_elastic)
    if args.diff_resilience:
        return diff_resilience(*args.diff_resilience)
    if args.diff_audit:
        return diff_audits(*args.diff_audit)
    if args.diff_metrics:
        return diff_metrics(*args.diff_metrics)
    lines = (open(args.logfile).readlines() if args.logfile
             else sys.stdin.readlines())
    rows = parse(lines)
    if not rows:
        print("no epochs found", file=sys.stderr)
        return 1
    cols = sorted({k for r in rows.values() for k in r})
    print("| epoch | " + " | ".join(cols) + " |")
    print("|" + "---|" * (len(cols) + 1))
    for epoch in sorted(rows):
        cells = [f"{rows[epoch].get(c, ''):.6g}" if c in rows[epoch]
                 else "" for c in cols]
        print(f"| {epoch} | " + " | ".join(cells) + " |")
    return 0


if __name__ == "__main__":
    sys.exit(main())
