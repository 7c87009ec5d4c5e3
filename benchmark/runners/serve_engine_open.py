"""Open loop in real time: requests are due on a schedule fixed by the
traffic file and the seed, whether or not earlier ones have finished.
One ``serve.Engine`` is stepped by this loop: submit what is due, then
``eng.step()``.  Times to first token count from when a request was DUE.

End to end it reports the gaps between a request's tokens
(``itl_p50_ms``, ``itl_p95_ms``: some tens of thousands of gaps a
window).  The times to first token go on an earlier line with their
count and the highest percentile that count supports; they are not
bounded metrics (PERF.md section 2: their p90 does not repeat from one
run to the next).  That line and the next also say what a reader of a
far-off run needs: preemptions, how late the generator ran, the queue
as the window closed, the longest engine step and the longest pause
between steps, the garbage collector's pauses; a traced run adds what
its steps were (``describe_steps``).

Traffic keys: ``arrivals`` (process, rate_per_s), ``prompt_tokens``,
``output_tokens``, ``sampling``, ``lead_in_s`` (arrivals that run before
the window opens, so that it opens on a system in its steady state, not
an empty one), ``drain_limit_s`` (how long after the window a request
due inside it may take to its first token before it counts as failed),
``trace_s`` (length of the profiler's window in a traced run).
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.harness import readers, serving, stats, traffic
from benchmark.harness.runtime import (Result, Run, TraceWindow,
                                       enable_program_spans,
                                       read_program_spans, say)


def run(run: Run) -> Result:
    eng, params, ref = serving.build_engine(run)
    ok_probe, notes = serving.probe(run, eng, params, ref)
    enable_program_spans(run.traced)
    w = window(run, eng, run.traffic, TraceWindow(run, float(
        run.traffic["trace_s"])))
    notes += w["notes"]
    spans = read_program_spans(*w["window_ns"]) if run.traced else []
    if spans:
        say(describe_steps(spans))
    facts = {
        "window_s": run.seconds, "kind": "serve_open",
        "steps": w["steps"],
        "spans": spans,
        "engine": serving.engine_facts(eng, run.config),
        "trace": w["trace"].summary(),
        "trace_cost_s": (w["trace"].start_cost_s, w["trace"].stop_cost_s),
    }
    return Result(correct=ok_probe and not notes, attempted=w["attempted"],
                  failed=w["failed"], setup_s=w["setup_s"],
                  end_to_end=w["end_to_end"], facts=facts, notes=notes,
                  temp_bytes=serving.program_temp_bytes(eng))


class GcPauses:
    """Times every collection of the interpreter's cyclic garbage
    collector while it is installed (``gc.callbacks``): the loop is one
    thread, so a collection is a pause of the engine."""

    def __init__(self):
        self.pauses = []                 # (start, seconds, generation)
        self._t = None
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.pauses.append((self._t, time.monotonic() - self._t,
                                info["generation"]))
            self._t = None

    def stop(self) -> None:
        gc.callbacks.remove(self)

    def describe(self, lo: float, hi: float) -> str:
        inside = [p for p in self.pauses if lo <= p[0] < hi]
        full = [p for p in inside if p[2] == 2]
        return (f"garbage collections in the window {len(inside)} taking "
                f"{sum(p[1] for p in inside) * 1e3:.1f} ms in all, "
                f"{len(full)} of them full, the longest "
                f"{max((p[1] for p in inside), default=0.0) * 1e3:.1f} ms")


def describe_steps(spans) -> str:
    """What the window's steps were, by the program's own spans: a step
    runs at most one prefill chunk and then one decode step over the
    ``rows`` that are ready, and each such row's token closes a gap, so
    the rows of the steps with a chunk are the gaps that hold one."""
    steps = [ev["args"] for ev in spans
             if ev["name"] == "serve.step" and "rows" in ev["args"]]
    rows = sum(a["rows"] for a in steps)
    chunked = [a for a in steps if a.get("chunk")]
    held = sum(a["rows"] for a in chunked)
    active = readers.span_arg_mean({"spans": spans}, "serve.decode", "active")
    worst = max((ev for ev in spans if ev["name"] == "serve.step"),
                key=lambda ev: ev["dur"], default=None)
    inside = {}
    if worst is not None:
        for ev in spans:
            if (ev is not worst and ev["tid"] == worst["tid"]
                    and worst["ts"] <= ev["ts"]
                    and ev["ts"] + ev["dur"] <= worst["ts"] + worst["dur"]):
                inside[ev["name"]] = inside.get(ev["name"], 0) + ev["dur"]
    longest = ("" if worst is None else
               f"; the longest step {worst['dur'] / 1e3:.1f} ms, spans inside "
               "it ms " + ", ".join(f"{k} {v / 1e3:.1f}" for k, v in
                                    sorted(inside.items(), key=lambda kv: -kv[1])))
    return (f"[steps] {len(steps)} serve.step spans in the window, "
            f"{len(chunked)} with a prefill chunk; {rows} decoded rows, "
            f"{held} of them ({held / max(rows, 1):.1%}) in a "
            f"step with a chunk; mean active rows a decode step "
            f"{0.0 if active is None else active:.2f}" + longest)


def window(run: Run, eng, mix, tw: TraceWindow) -> dict:
    """One lead-in and one measured window of ``run.seconds`` on a warm
    engine; leaves the engine drained.  ``benchmark/sweep.py`` calls it
    once per rate."""
    vocab = int(run.config["vocab_size"])
    notes = []
    lead_s = float(mix["lead_in_s"])
    rate = float(mix["arrivals"]["rate_per_s"])
    lead_plan, body_plan = traffic.open_loop(mix, vocab, run.seed,
                                             run.seconds, lead_s)
    lead = [r for _, r in lead_plan]
    body = [r for _, r in body_plan]
    offsets = np.array([t for t, _ in lead_plan + body_plan])
    reqs = lead + body
    say(f"[traffic] open loop, {rate} req/s: {len(lead)} in a {lead_s} s "
        f"lead-in, then {traffic.describe_lengths(body)}")

    steps = serving.StepLog(eng)
    traces_before = dict(eng.trace_counts)
    preempt_start = preempt_w0 = serving.preemptions()
    drain_limit = float(mix["drain_limit_s"])

    pauses = GcPauses()
    start = time.monotonic() + 0.05
    w0 = start + lead_s                     # the window opens
    w1 = w0 + run.seconds
    due = start + lead_s + offsets
    sent = [None] * len(reqs)
    n_sent = 0
    w0_ns = None
    queue_close = None
    run.compiles.mark()
    lead_compiles = 0
    while True:
        now = time.monotonic()
        if w0_ns is None and now >= w0:
            w0_ns = time.perf_counter_ns()
            lead_compiles = run.compiles.mark()
            preempt_w0 = serving.preemptions()
            run.sample_memory()
        if now >= w1:
            if queue_close is None:
                queue_close = eng.sched.queue_depth
            tw.close()
        traced = tw.tick(now, w1)
        if n_sent < len(reqs) and due[n_sent] <= now:
            with run.region("submit"):
                while n_sent < len(reqs) and due[n_sent] <= now:
                    try:
                        rid = serving.submit(eng, reqs[n_sent])
                        sent[n_sent] = eng.request(rid)
                    except Exception as e:          # refused: counted
                        say(f"[window] request {n_sent} refused: {e}")
                    n_sent += 1
        if eng.sched.idle():
            if n_sent >= len(reqs):
                break
            with run.region("wait_arrivals"):
                time.sleep(max(0.0, min(due[n_sent] - time.monotonic(),
                                        0.05)))
            continue
        t0 = time.monotonic()
        with run.region("engine_step"):
            eng.step()
        steps.record(t0, time.monotonic(), traced)
        if now >= w1:
            waiting = [r for r in sent[len(lead):]
                       if r is not None and r.first_token_t is None
                       and not r.done()]
            # a traced run's loop stands still while the profiler stops
            # (tens of seconds, just past ``w1``): the drain limit is of
            # the engine's time, so that stop does not count against it
            if not waiting or now >= w1 + drain_limit + tw.stop_cost_s:
                break
    tw.close()
    pauses.stop()
    run.sample_memory()
    w1_ns = w0_ns + int(run.seconds * 1e9)
    in_window = run.compiles.mark()
    # the queue as the window closed (the first pass of the loop past
    # it), which is what the sweep's rule holds to ``max_batch``: by the
    # loop's end the drain has emptied it, whatever the rate
    queue_end = eng.sched.queue_depth if queue_close is None else queue_close
    lead_preempted = preempt_w0 - preempt_start
    preempted = serving.preemptions() - preempt_w0
    setup_s = run.setup_seconds(w0)

    # -- reduce -----------------------------------------------------------
    window = list(zip(sent[len(lead):], body, due[len(lead):]))
    ttft = [stats.ttft_ms(d, r.first_token_t) for r, _, d in window
            if r is not None and r.first_token_t is not None]
    missing = sum(1 for r, _, _ in window
                  if r is None or r.first_token_t is None)
    gaps = []
    for r in sent:
        if r is not None:
            gaps += stats.gaps_ms(r.token_times, w0, w1)
    late = [(r.submit_t - d) * 1e3 for r, _, d in window if r is not None]
    failed, more = serving.settle(
        eng, [(r, s) for r, s, _ in window], vocab, traces_before, in_window)
    notes += more
    if missing:
        notes.append(f"{missing} request(s) due in the window had no first "
                     f"token {drain_limit} s after it closed")
        failed = max(failed, missing)
    if not ttft or not gaps:
        notes.append("nothing was measured in the window")

    n = len(ttft)
    waits = [(r.admit_t - d) * 1e3 for r, _, d in window
             if r is not None and r.admit_t is not None]
    offered = sum(s.max_new_tokens for s in body)
    in_steps = steps.in_window(w0, w1)
    tokens = sum(1 for r in sent if r is not None
                 for t in r.token_times if w0 <= t < w1)
    say(f"[window] {len(window)} requests due in {run.seconds} s offering "
        f"{offered} tokens ({n} reached a first token); {tokens} tokens "
        f"completed in it over {len(gaps)} inter-token gaps and "
        f"{len(in_steps)} engine steps; lead-in compiles "
        f"{lead_compiles}, in-window compiles {in_window}; preemptions "
        f"{lead_preempted} in the lead-in, {preempted} from the window's "
        f"opening to the loop's end")
    # where a stall of the loop sat: inside ``eng.step()`` (the program,
    # the runtime), between two steps (this loop: submitting, sleeping),
    # or in the interpreter's garbage collector, wherever it struck
    longest = max(in_steps, key=lambda r: r["t1"] - r["t0"], default=None)
    between = max(((b["t0"] - a["t1"], b["t0"]) for a, b in
                   zip(in_steps, in_steps[1:])), default=(0.0, w0))
    if longest is not None:
        say(f"[window] longest engine step "
            f"{(longest['t1'] - longest['t0']) * 1e3:.1f} ms (at +"
            f"{longest['t0'] - w0:.1f} s), longest pause between two steps "
            f"{between[0] * 1e3:.1f} ms (at +{between[1] - w0:.1f} s); "
            + pauses.describe(w0, w1))
    if n:
        top = stats.highest_supported_percentile(n)
        say(f"[window] ttft ms over {n} requests (enough for p{top:.0f} and "
            f"nothing higher: ten samples beyond it): p50 "
            f"{stats.percentile(ttft, 50):.1f} mean {sum(ttft) / n:.1f} p90 "
            f"{stats.percentile(ttft, 90):.1f} max {max(ttft):.1f}; queue "
            f"wait ms p50 {stats.percentile(waits, 50):.1f} p95 "
            f"{stats.percentile(waits, 95):.1f}; generator late ms p50 "
            f"{stats.percentile(late, 50):.2f} p95 "
            f"{stats.percentile(late, 95):.2f}; queue at the window's "
            f"close {queue_end}")
    end_to_end = {}
    if gaps:
        say("[window] itl ms " + " ".join(
            f"p{q} {stats.percentile(gaps, q):.2f}"
            for q in (40, 50, 60, 90, 95, 98, 99)) + f" max {max(gaps):.1f}")
        end_to_end = {"itl_p50_ms": stats.percentile(gaps, 50),
                      "itl_p95_ms": stats.percentile(gaps, 95)}
    return {"notes": notes, "attempted": len(window), "failed": failed,
            "setup_s": setup_s, "end_to_end": end_to_end,
            "steps": in_steps,
            "window_ns": (w0_ns, w1_ns), "trace": tw,
            "ttft_ms": ttft, "gaps_ms": gaps, "queue_end": queue_end,
            "tokens": tokens, "offered_tokens": offered,
            "preemptions": preempted}
