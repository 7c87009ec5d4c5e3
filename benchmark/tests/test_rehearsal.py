"""``run.py --rehearsal`` end to end for each runner, at tiny sizes on
the CPU: the control flow of every cell, traced and not, and that a
rehearsal reports no metric."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import spec

BENCH = spec.load_benchmark()


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_rehearsal_runs_and_reports_no_metric(cell, trace):
    out = subprocess.run(
        [sys.executable, os.path.join(spec.BENCH_DIR, "run.py"),
         "--workload", cell, "--seed", str(2**31 + 5), "--seconds", "1.5",
         "--trace", str(trace), "--rehearsal"],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    assert "REHEARSAL" in out.stdout
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True, out.stdout[-2000:]
    assert line["metrics"] == {} and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"
    assert line["attempted"] > 0 and line["failed"] == 0
    assert "in-window compiles 0" in out.stdout
    # every number ``correct`` compared, beside its limit: the line's last
    # key and the last lines on standard error
    assert list(line)[-1] == "compared" and line["compared"]
    for name, c in line["compared"].items():
        assert c["value"] <= c["limit"], (name, c)
    said = [l for l in out.stderr.strip().splitlines()][-len(line["compared"]):]
    assert [l.split()[:2] for l in said] == [
        ["[compared]", name] for name in line["compared"]], out.stderr[-500:]
    mix = spec.load_traffic(spec.find_cell(BENCH, cell)["traffic"])
    if mix["kind"] == "serve_engine_open":
        assert "preemptions 0 in the lead-in, 0 from" in out.stdout
        assert "queue at the window's close 0" in out.stdout
    if mix["kind"].startswith("serve_engine_closed"):
        # the tiny engine is the fast one: its clients go through dozens
        # of rounds where the chip's stay in round 0
        assert "requests_per_client" not in mix["rehearsal"]
        assert "[traffic] round 2 drawn at +" in out.stdout
