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
    mix = spec.load_traffic(spec.find_cell(BENCH, cell)["traffic"])
    if mix["kind"].startswith("serve_engine_closed"):
        # the tiny engine is the fast one: its clients go through dozens
        # of rounds where the chip's stay in round 0
        assert "requests_per_client" not in mix["rehearsal"]
        assert "[traffic] round 2 drawn at +" in out.stdout
