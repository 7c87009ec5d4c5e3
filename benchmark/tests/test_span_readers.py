"""The readers of the program's span tree, on hand-made spans and in a
rehearsal of each cell: ``step_host_ms`` (a step's ``dur`` minus the
``serve.fetch`` spans beneath it) and ``prefetch_wait_ms``."""
import os
import subprocess
import sys

import pytest

from benchmark.harness import spec

step_host = spec.load_reader("step_host_ms.chat")
prefetch_wait = spec.load_reader("prefetch_wait_ms")


def span(name, sid, ts, dur, parent=None, tid=1, **args):
    args["id"] = sid
    if parent is not None:
        args["parent"] = parent
    return {"name": name, "cat": "mxtpu", "ph": "X", "ts": ts, "dur": dur,
            "tid": tid, "args": args}


def two_steps():
    """Step 1 decodes only: 90,000 us of which 86,000 in the fetch.
    Step 2 also ingests a chunk: 600,000 us, fetches 500,000 + 87,000."""
    return [
        span("serve.admit", 2, 10, 40, parent=1),
        span("serve.build", 4, 100, 900, parent=3),
        span("serve.dispatch", 5, 1000, 2000, parent=3, kind="decode"),
        span("serve.fetch", 6, 3000, 86000, parent=3),
        span("serve.emit", 7, 89000, 500, parent=3),
        span("serve.decode", 3, 100, 89500, parent=1, active=32),
        span("serve.step", 1, 0, 90000, step=1, rows=32, chunk=0),
        span("serve.build", 12, 100100, 400, parent=11),
        span("serve.dispatch", 13, 100500, 2000, parent=11),
        span("serve.fetch", 14, 102500, 500000, parent=11),
        span("serve.prefill", 11, 100100, 502400, parent=10),
        span("serve.dispatch", 16, 603000, 2000, parent=15),
        span("serve.fetch", 17, 605000, 87000, parent=15),
        span("serve.decode", 15, 602600, 96000, parent=10, active=32),
        span("serve.step", 10, 100000, 600000, step=2, rows=32, chunk=1),
    ]


def test_step_host_is_the_step_minus_the_fetches_beneath_it():
    # (90 - 86) = 4 ms and (600 - 587) = 13 ms: the median of two
    assert step_host.read({"spans": two_steps()}) == pytest.approx(8.5)
    assert step_host.read({"spans": two_steps()[:7]}) == pytest.approx(4.0)
    # both splits are served by the one file
    assert spec.load_reader("step_host_ms.batch") is step_host


def test_a_step_that_ran_no_program_is_not_a_sample():
    idle = span("serve.step", 30, 800000, 50, step=3, rows=0, chunk=0)
    assert step_host.read({"spans": two_steps() + [idle]}) == pytest.approx(8.5)


@pytest.mark.parametrize("how", ["unknown parent id", "no parent at all",
                                 "the decode span is missing"])
def test_a_broken_tree_reads_none_not_a_number(how):
    spans = two_steps()
    if how == "unknown parent id":
        spans[3]["args"]["parent"] = 999
    elif how == "no parent at all":
        del spans[3]["args"]["parent"]
    else:
        del spans[5]
    assert step_host.read({"spans": spans}) is None


def test_a_fetch_whose_step_closed_outside_the_window_is_skipped():
    # the window's last step: its fetch closed in time, the step did not,
    # so the runner's ring holds the children without their step
    edge = [span("serve.fetch", 42, 705000, 80000, parent=41),
            span("serve.decode", 41, 702000, 84000, parent=40)]
    assert step_host.read({"spans": two_steps() + edge}) == pytest.approx(8.5)


@pytest.mark.parametrize("facts", [{}, {"spans": []}, {"spans": [
    span("serve.decode", 1, 0, 2000, active=3),      # the parent commit's
    span("serve.admit", 2, 3000, 10)]}])
def test_nothing_to_read_is_none(facts):
    assert step_host.read(facts) is None
    assert prefetch_wait.read(facts) is None


def test_prefetch_wait_is_the_median_wait_of_the_consumer():
    spans = [span("prefetch.wait", i, 1000 * i, d, tid=1)
             for i, d in enumerate([100, 300, 4000], 1)]
    spans.append(span("prefetch.batch", 9, 0, 90000, tid=2, n=0))
    assert prefetch_wait.read({"spans": spans}) == pytest.approx(0.3)


def test_the_steps_line_counts_the_gaps_that_hold_a_chunk():
    """A step's decoded rows each close a gap; the rows of the steps that
    also ran a prefill chunk are the gaps ``itl_p95_ms`` is meant to
    read (ISSUE 34: under 5 % of them the percentile sits on an edge)."""
    open_loop = spec.load_module("runners", "serve_engine_open")
    spans = [span("serve.step", 1, 0, 9000, rows=3, chunk=0),
             span("serve.step", 2, 9000, 25000, rows=5, chunk=1),
             span("serve.step", 3, 34000, 9000, rows=2, chunk=0),
             span("serve.step", 4, 43000, 100),          # cut at the edge
             span("serve.decode", 5, 100, 8000, active=3),
             span("serve.decode", 6, 20000, 8000, active=5)]
    said = open_loop.describe_steps(spans)
    assert "3 serve.step spans in the window, 1 with a prefill chunk" in said
    assert "10 decoded rows, 5 of them (50.0%)" in said
    assert "mean active rows a decode step 4.00" in said
    assert said.endswith("the longest step 25.0 ms, spans inside it ms "
                         "serve.decode 8.0")


BENCH = spec.load_benchmark()
NEW = {"serve-chat": "step_host_ms.chat", "serve-batch": "step_host_ms.batch",
       "resnet50-train": "prefetch_wait_ms"}


def test_benchmark_json_declares_the_readers_for_their_cells():
    for cell, name in NEW.items():
        assert name in [m["name"] for m in
                        spec.metrics_for(BENCH, cell, "per_layer")]


@pytest.mark.parametrize("cell", sorted(NEW))
def test_a_traced_rehearsal_finds_something_to_read(cell):
    out = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", cell, "--seed", str(2**31 + 9), "--seconds", "1.5",
         "--trace", "1", "--rehearsal"],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    found = [l for l in out.stdout.splitlines()
             if "per-layer readers that found something to read" in l]
    assert found and repr(NEW[cell]) in found[0], out.stdout[-2000:]
