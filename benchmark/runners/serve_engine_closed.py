"""Closed loop: a fixed number of clients, each of which sends its next
request when its last one completes.  One ``serve.Engine`` is stepped by
this loop; with as many clients as decode slots every slot stays busy,
so the rate is whatever the engine completes.

Traffic keys: ``clients``, ``requests_per_client`` (the size of a ROUND:
``clients x requests_per_client`` requests drawn together; round 0 is
drawn in set-up and a further one whenever the first client needs it,
as many as the window takes, so a client never runs out however fast
the engine is), ``prompt_tokens``, ``output_tokens``, ``sampling``,
``stagger_first`` (client c's first request gets (c + 1)/clients of its
drawn output budget, so that the clients are out of step from the start
and not only after a whole generation), ``lead_in_s`` (the loop runs
this long before the window opens), ``trace_s``.  Every round of every
seed is the same multiset of sizes; ``--seed`` draws which client gets
which, in what order.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List

from benchmark.harness import serving, stats, traffic
from benchmark.harness.runtime import (Result, Run, TraceWindow,
                                       enable_program_spans,
                                       read_program_spans, say)


class Rounds:
    """What each client sends.  Round ``r`` is ``make_requests(mix, vocab,
    seed, clients * per, stream=r)``; client ``c``'s ``t``-th request is
    entry ``c + clients * (t % per)`` of round ``t // per``.  Every round
    is the same multiset of lengths in an order of its own, so a window
    offers the same work per request however many rounds it takes.
    ``stagger_first`` cuts each client's first request of round 0 and no
    other.  A round is drawn when its first request is asked for;
    ``drawn`` keeps what each draw cost."""

    def __init__(self, mix, vocab: int, seed: int):
        self.mix, self.vocab, self.seed = mix, int(vocab), int(seed)
        self.clients = int(mix["clients"])
        self.per = int(mix["requests_per_client"])
        self.rounds: List[List[traffic.RequestSpec]] = []
        self.drawn: List[float] = []          # seconds each draw took

    def request(self, c: int, t: int) -> traffic.RequestSpec:
        r, k = divmod(t, self.per)
        while r >= len(self.rounds):
            self._draw()
        return self.rounds[r][c + self.clients * k]

    def _draw(self) -> None:
        t0 = time.monotonic()
        r = len(self.rounds)
        pool = traffic.make_requests(self.mix, self.vocab, self.seed,
                                     self.clients * self.per, stream=r)
        if r == 0 and self.mix.get("stagger_first"):
            for c in range(self.clients):
                pool[c] = dataclasses.replace(
                    pool[c], max_new_tokens=max(
                        2, pool[c].max_new_tokens * (c + 1) // self.clients))
        self.rounds.append(pool)
        self.drawn.append(time.monotonic() - t0)


@dataclasses.dataclass
class Driven:
    """What the loop leaves: the window's opening on the loop's clock and
    on the spans', every request sent with its spec, in submit order, and
    how many each client sent."""
    w0: float
    w0_ns: int
    sent: list
    turn: List[int]
    lead_compiles: int


def drive(run: Run, eng, plan: Rounds, steps, tw, lead_in_s: float) -> Driven:
    """Step ``eng`` for ``lead_in_s`` and then ``run.seconds``; a client
    whose request is done sends its next before the following step."""
    clients = plan.clients
    start = time.monotonic()
    w0 = start + lead_in_s
    w1 = w0 + run.seconds
    current = [None] * clients        # the live request of each client
    turn = [0] * clients              # requests each client has sent
    drawn = len(plan.drawn)
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
                    r = plan.request(c, turn[c])
                    turn[c] += 1
                    if len(plan.drawn) > drawn:   # here, never in engine_step
                        drawn = len(plan.drawn)
                        say(f"[traffic] round {drawn - 1} drawn at "
                            f"+{time.monotonic() - start:.1f} s of the loop, "
                            f"{plan.drawn[-1] * 1e3:.1f} ms")
                    current[c] = eng.request(serving.submit(eng, r))
                    sent.append((current[c], r))
        t0 = time.monotonic()
        with run.region("engine_step"):
            eng.step()
        steps.record(t0, time.monotonic(), traced)
    tw.close()
    run.sample_memory()
    return Driven(w0, w0_ns, sent, turn, lead_compiles)


def served(sent, w0: float, w1: float) -> Dict[str, int]:
    """The forward passes whose token fell in the window: ``decoded``
    positions (every token of a request but its first), ``prefilled``
    positions (the whole prompt, where the first token fell inside) and
    the cached positions they ``attended`` over between them, each one's
    own included.  ``serve_mfu`` reads it."""
    out = {"decoded": 0, "prefilled": 0, "attended": 0}
    for req, r in sent:
        p = len(r.prompt)
        for j, t in enumerate(req.token_times):
            if not w0 <= t < w1:
                continue
            if j:
                out["decoded"] += 1
                out["attended"] += p + j
            else:
                out["prefilled"] += p
                out["attended"] += p * (p + 1) // 2
    return out


def run(run: Run) -> Result:
    mix = run.traffic
    eng, params, ref = serving.build_engine(run)
    ok_probe, notes = serving.probe(run, eng, params, ref)
    vocab = int(run.config["vocab_size"])

    plan = Rounds(mix, vocab, run.seed)
    plan.request(0, 0)
    say(f"[traffic] closed loop, {plan.clients} clients, rounds of "
        f"{plan.per} requests a client, as many as the window needs; round "
        f"0 drawn in {plan.drawn[0] * 1e3:.1f} ms: "
        f"{traffic.describe_lengths(plan.rounds[0])}")

    enable_program_spans(run.traced)
    steps = serving.StepLog(eng)
    tw = TraceWindow(run, float(mix["trace_s"]))
    traces_before = dict(eng.trace_counts)
    d = drive(run, eng, plan, steps, tw, float(mix["lead_in_s"]))
    w0, w1, sent = d.w0, d.w0 + run.seconds, d.sent
    w1_ns = d.w0_ns + int(run.seconds * 1e9)
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
        f"{finished} finished inside it; rounds drawn {len(plan.rounds)}, "
        f"requests sent by a client {min(d.turn)}-{max(d.turn)}; lead-in "
        f"compiles {d.lead_compiles}, in-window compiles {in_window}")
    gaps = []
    for req, _ in sent:
        gaps += stats.gaps_ms(req.token_times, w0, w1)
    if gaps:
        say(f"[window] itl ms p50 {stats.percentile(gaps, 50):.1f} p95 "
            f"{stats.percentile(gaps, 95):.1f}; rows per step mean "
            f"{sum(s['rows'] for s in in_win) / max(len(in_win), 1):.1f}")
    done = served(sent, w0, w1)
    say(f"[window] forward passes whose token fell inside: {done}")
    end_to_end = {"serve_tok_s": tokens / run.seconds} if tokens else {}
    facts = {
        "window_s": run.seconds, "kind": "serve_closed",
        "rounds_drawn": len(plan.rounds),
        "served": done,
        "steps": in_win,
        "spans": (read_program_spans(d.w0_ns, w1_ns) if run.traced else []),
        "engine": serving.engine_facts(eng, run.config),
        "trace": tw.summary(),
        "trace_cost_s": (tw.start_cost_s, tw.stop_cost_s),
    }
    return Result(correct=ok_probe and not notes, attempted=len(touched),
                  failed=failed, setup_s=setup_s, end_to_end=end_to_end,
                  facts=facts, notes=notes,
                  temp_bytes=serving.program_temp_bytes(eng))
