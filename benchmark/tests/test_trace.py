"""The reduction from a profiler trace to numbers: interval arithmetic
on hand-made events, then the whole path on a small ``.xplane.pb`` cut
from this benchmark's first traced run on the chip (PR 23)."""
import os

import pytest

from benchmark.harness import spec, trace

FIXTURE = os.path.join(spec.BENCH_DIR, "fixtures", "serve-batch.xplane.pb")
MS = 1_000_000


def test_union_subtract_total():
    u = trace.union([(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)])
    assert u == [(0, 20), (30, 45)]
    assert trace.total(u) == 35
    assert trace.subtract([(0, 100)], u) == [(20, 30), (45, 100)]
    assert trace.subtract([(0, 10), (20, 30)], [(5, 25)]) == [(0, 5), (25, 30)]
    assert trace.subtract([(0, 10)], []) == [(0, 10)]


def test_names():
    assert trace.clean_name("%fusion.12 = f32[8] fusion(...)") == "fusion.12"
    assert trace.base_name("mxtpu_flash_decode.7") == "mxtpu_flash_decode"
    assert trace.base_name("all-reduce.1.2") == "all-reduce"
    assert trace.CONTAINER.match("while.2") and not trace.CONTAINER.match("whiled")


def hand_made():
    """100 ms window on two chips.  Chip 0: busy 0-30 (fusion), 30-40
    (kernel), idle 40-60 while the host is in engine_step, a copy 60-80
    of which 70-80 overlaps a fusion, idle 90-100 waiting for arrivals.
    Chip 1: busy 0-50."""
    tr = trace.Trace()
    tr.device_ops[0] = [("fusion.1", 0, 30 * MS),
                        ("mxtpu_flash_decode.3", 30 * MS, 10 * MS),
                        ("copy.1", 60 * MS, 20 * MS),
                        ("fusion.2", 70 * MS, 20 * MS),
                        ("while.1", 0, 90 * MS)]          # a container
    tr.device_ops[1] = [("fusion.1", 0, 50 * MS)]
    tr.host_regions = [("bench.window", 0, 100 * MS),
                       ("bench.engine_step", 35 * MS, 30 * MS),
                       ("bench.wait_arrivals", 88 * MS, 12 * MS)]
    return tr


def test_busy_idle_kernel_time_and_gap_attribution():
    s = trace.summarize(hand_made())
    assert s.chips == 2 and s.window_s == pytest.approx(0.100)
    # chip 0 busy 0-40 and 60-90 = 70 ms, chip 1 busy 50 ms: mean 60 ms
    assert s.busy_s == pytest.approx(0.060)
    assert s.kernel_seconds("mxtpu_flash_decode") == pytest.approx(0.010 / 2)
    assert s.kernel_calls("mxtpu_flash_decode") == 1
    assert "while" not in s.op_seconds
    # gaps of chip 0, summed under the host region at their middle
    assert s.gap_seconds_by_region == {
        "bench.engine_step": pytest.approx(0.020),
        "bench.wait_arrivals": pytest.approx(0.010)}
    b = s.breakdown()
    assert b["device_ops"][0][0] == "fusion"
    assert b["idle_gaps"][0] == ["bench.engine_step", pytest.approx(0.020)]


def test_events_outside_the_window_are_clipped():
    tr = hand_made()
    tr.host_regions[0] = ("bench.window", 20 * MS, 30 * MS)       # 20-50
    s = trace.summarize(tr)
    assert s.window_s == pytest.approx(0.030)
    assert s.busy_s == pytest.approx((0.020 + 0.030) / 2)


def test_a_trace_without_device_ops_is_refused():
    with pytest.raises(ValueError):
        trace.summarize(trace.Trace(host_regions=[("bench.window", 0, 10)]))


# -- the recorded fixture: 1.3 s of serve-batch's first traced chip run (PR 23,
# seed 1): two engine steps and the start of a third, each a 500 ms
# chunk-prefill program and an 87 ms decode program; op names cut to 48
# characters, host lines reduced to the benchmark's own regions ------------

@pytest.fixture(scope="module")
def recorded():
    return trace.summarize(trace.read_xplane(FIXTURE))


def test_fixture_is_small():
    assert os.path.getsize(FIXTURE) < 1_000_000


def test_fixture_busy_and_idle(recorded):
    s = recorded
    assert s.chips == 1
    assert s.window_s == pytest.approx(1.3)
    assert s.busy_s == pytest.approx(1.2765, abs=1e-3)
    assert 100 * (1 - s.busy_s / s.window_s) == pytest.approx(1.81, abs=0.02)


def test_fixture_programs_and_kernel_time_by_name(recorded):
    s = recorded
    decode = s.programs_with("mxtpu_flash_decode")
    prefill = s.programs_with("mxtpu_flash_decode", False, prefix="jit_fn")
    assert [round(x, 1) for x in decode] == [87.4, 87.4]
    assert [round(x, 1) for x in prefill] == [499.9, 500.1]
    assert s.kernel_calls("mxtpu_flash_decode") == 2 * 24   # once a layer
    assert s.kernel_seconds("mxtpu_flash_decode") == pytest.approx(0.0899, abs=1e-4)
    assert s.kernel_ms_per_run("mxtpu_flash_decode") == pytest.approx(44.97, abs=0.02)
    assert s.kernel_ms_per_run("mxtpu_fused_update") is None


def test_fixture_breakdown_and_gap_attribution(recorded):
    b = recorded.breakdown()
    assert len(b["device_ops"]) == 10
    assert b["device_ops"][0][0] == "select_dynamic-update-slice_fusion"
    assert b["device_ops"][0][1] == pytest.approx(0.294, abs=1e-3)
    assert [n for n, _ in b["device_ops"]].index("mxtpu_flash_decode") == 3
    # nearly all of the idle 23.5 ms fall while the host is inside eng.step()
    assert b["idle_gaps"][0][0] == "bench.engine_step"
    assert b["idle_gaps"][0][1] == pytest.approx(0.0235, abs=1e-3)
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        recorded.window_s - recorded.busy_s, abs=1e-6)
