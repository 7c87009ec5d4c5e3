"""Closed loop: a fixed number of clients, each of which sends its next
request when its last one completes.  One ``serve.Engine`` is stepped by
this loop; with as many clients as decode slots every slot stays busy,
so the rate is whatever the engine completes.

Traffic keys: ``clients``, ``requests_per_client`` (drawn up front; the
run fails loudly if a client runs out), ``prompt_tokens``,
``output_tokens``, ``sampling``, ``stagger_first`` (client c's first
request gets (c + 1)/clients of its drawn output budget, so that the
clients are out of step from the start and not only after a whole
generation), ``lead_in_s`` (the loop runs this long before the window
opens), ``trace_s``.  Every seed sends the same multiset of sizes;
``--seed`` draws which client gets which, in what order.
"""
from __future__ import annotations

import dataclasses
import time

from benchmark.harness import serving, stats, traffic
from benchmark.harness.runtime import (Result, Run, TraceWindow,
                                       enable_program_spans,
                                       read_program_spans, say)


def run(run: Run) -> Result:
    mix = run.traffic
    eng, params, ref = serving.build_engine(run)
    ok_probe, notes = serving.probe(run, eng, params, ref)
    vocab = int(run.config["vocab_size"])

    clients = int(mix["clients"])
    per = int(mix["requests_per_client"])
    pool = traffic.make_requests(mix, vocab, run.seed, clients * per)
    plans = [pool[c::clients] for c in range(clients)]
    if mix.get("stagger_first"):
        for c, plan in enumerate(plans):
            first = plan[0]
            plan[0] = dataclasses.replace(
                first, max_new_tokens=max(
                    2, first.max_new_tokens * (c + 1) // clients))
    say(f"[traffic] closed loop, {clients} clients x {per} requests drawn: "
        f"{traffic.describe_lengths(pool)}")

    enable_program_spans(run.traced)
    steps = serving.StepLog(eng)
    tw = TraceWindow(run, float(mix["trace_s"]))
    traces_before = dict(eng.trace_counts)

    start = time.monotonic()
    w0 = start + float(mix["lead_in_s"])
    w1 = w0 + run.seconds
    current = [None] * clients        # the live request of each client
    turn = [0] * clients
    sent = []                         # (request, spec) in submit order
    w0_ns = None
    run.compiles.mark()
    lead_compiles = 0
    while True:
        now = time.monotonic()
        if w0_ns is None and now >= w0:
            w0_ns = time.perf_counter_ns()
            lead_compiles = run.compiles.mark()
            run.sample_memory()
        if now >= w1:
            break
        traced = tw.tick(now, w1)
        idle = [c for c in range(clients)
                if current[c] is None or current[c].done()]
        if idle:
            with run.region("submit"):
                for c in idle:
                    if turn[c] >= per:
                        raise RuntimeError(
                            f"client {c} ran out of its {per} requests: "
                            "raise requests_per_client in the traffic file")
                    r = plans[c][turn[c]]
                    turn[c] += 1
                    current[c] = eng.request(serving.submit(eng, r))
                    sent.append((current[c], r))
        t0 = time.monotonic()
        with run.region("engine_step"):
            eng.step()
        steps.record(t0, time.monotonic(), traced)
    tw.close()
    run.sample_memory()
    w1_ns = w0_ns + int(run.seconds * 1e9)
    in_window = run.compiles.mark()
    setup_s = run.setup_seconds(w0)

    # -- reduce -----------------------------------------------------------
    tokens = sum(1 for req, _ in sent for t in req.token_times
                 if w0 <= t < w1)
    touched = [(req, r) for req, r in sent
               if (req.token_times and req.token_times[-1] >= w0)
               or not req.done()]
    finished = sum(1 for req, _ in touched
                   if req.done() and req.finish_t < w1)
    failed, more = serving.settle(eng, touched, vocab, traces_before,
                                  in_window)
    notes += more
    if not tokens:
        notes.append("no token was completed in the window")
    in_win = steps.in_window(w0, w1)
    say(f"[window] {tokens} tokens in {run.seconds} s over {len(in_win)} "
        f"engine steps; {len(touched)} requests touched the window, "
        f"{finished} finished inside it; lead-in compiles {lead_compiles}, "
        f"in-window compiles {in_window}")
    gaps = []
    for req, _ in sent:
        gaps += stats.gaps_ms(req.token_times, w0, w1)
    if gaps:
        say(f"[window] itl ms p50 {stats.percentile(gaps, 50):.1f} p95 "
            f"{stats.percentile(gaps, 95):.1f}; rows per step mean "
            f"{sum(s['rows'] for s in in_win) / max(len(in_win), 1):.1f}")
    end_to_end = {"serve_tok_s": tokens / run.seconds} if tokens else {}
    facts = {
        "window_s": run.seconds, "kind": "serve_closed",
        "steps": in_win,
        "spans": (read_program_spans(w0_ns, w1_ns) if run.traced else []),
        "engine": serving.engine_facts(eng, run.config),
        "trace": tw.summary(),
        "trace_cost_s": (tw.start_cost_s, tw.stop_cost_s),
    }
    return Result(correct=ok_probe and not notes, attempted=len(touched),
                  failed=failed, setup_s=setup_s, end_to_end=end_to_end,
                  facts=facts, notes=notes,
                  temp_bytes=serving.program_temp_bytes(eng))
