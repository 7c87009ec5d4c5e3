"""Percentile and due-time arithmetic; the generators replay exactly
from a seed and give every seed the same multiset of sizes and gaps."""
import numpy as np
import pytest

from benchmark.harness import spec, stats, traffic

CHAT = spec.load_traffic("chat-open")
BATCH = spec.load_traffic("batch-closed")


def test_percentile_interpolates_between_order_statistics():
    xs = [10, 20, 30, 40, 50]
    assert stats.percentile(xs, 0) == 10
    assert stats.percentile(xs, 50) == 30
    assert stats.percentile(xs, 90) == pytest.approx(46.0)
    assert stats.percentile(xs, 100) == 50
    assert stats.percentile([], 90) is None
    assert stats.percentile([7], 99) == 7


def test_highest_supported_percentile_wants_ten_beyond():
    assert stats.highest_supported_percentile(99) == 50.0
    assert stats.highest_supported_percentile(100) == 90.0
    assert stats.highest_supported_percentile(200) == 95.0
    assert stats.highest_supported_percentile(1000) == 99.0


def test_iqr_share_is_statistics_quantiles():
    xs = [100, 101, 102, 103, 104, 110]
    # statistics.quantiles(n=4) on 6 points: q1 = 100.75, q3 = 105.5
    assert stats.iqr_share(xs) == pytest.approx((105.5 - 100.75) / 102.5)


def test_ttft_counts_from_due_time_not_submit_time():
    assert stats.ttft_ms(10.0, 10.25) == pytest.approx(250.0)


def test_gaps_only_where_the_later_token_is_in_the_window():
    times = [0.9, 1.0, 1.1, 1.3, 2.05]
    assert stats.gaps_ms(times, 1.0, 2.0) == pytest.approx([100, 100, 200])


def test_requests_replay_exactly_from_a_seed():
    a = traffic.make_requests(CHAT, 50272, 2**31 + 77, 120)
    b = traffic.make_requests(CHAT, 50272, 2**31 + 77, 120)
    assert a == b
    c = traffic.make_requests(CHAT, 50272, 5, 120)
    assert a != c


def test_every_seed_gets_the_same_multiset_in_an_order_of_its_own():
    a = traffic.make_requests(CHAT, 50272, 1, 120)
    b = traffic.make_requests(CHAT, 50272, 2**31 + 4, 120)
    shape = lambda r: (len(r.prompt), r.max_new_tokens)
    for part in (lambda r: len(r.prompt), lambda r: r.max_new_tokens,
                 lambda r: (r.temperature, r.top_k)):
        assert sorted(map(part, a)) == sorted(map(part, b))
    assert [shape(r) for r in a] != [shape(r) for r in b]      # the order
    assert sum(r.temperature == 0 for r in a) == 60
    lead = traffic.make_requests(CHAT, 50272, 1, 120, stream=1)
    assert [shape(r) for r in lead] != [shape(r) for r in a]


def test_power_law_quantiles_stay_in_range_with_a_heavy_tail():
    xs = traffic.length_quantiles(CHAT["prompt_tokens"], 1000)
    assert xs.min() == 64 and xs.max() == 1024
    assert np.median(xs) < 110 < xs.mean() < 180       # bounded Pareto 1.5
    u = traffic.length_quantiles(BATCH["prompt_tokens"], 129)
    assert u.min() == 128 and u.max() == 256
    assert sorted(set(u.tolist())) == list(range(128, 257))


def test_open_loop_has_the_same_gaps_for_every_seed_in_another_order():
    mix = dict(CHAT, arrivals={"process": "exponential_quantiles",
                               "rate_per_s": 2.0})
    lead1, body1 = traffic.open_loop(mix, 50272, 1, 30.0, 6.0)
    lead2, body2 = traffic.open_loop(mix, 50272, 2, 30.0, 6.0)
    assert (lead1, body1) == traffic.open_loop(mix, 50272, 1, 30.0, 6.0)
    assert len(body1) == len(body2) == 60 and len(lead1) == len(lead2) == 12
    t1 = np.array([t for t, _ in body1])
    t2 = np.array([t for t, _ in body2])
    assert t1[0] == 0.0 and np.all(np.diff(t1) > 0) and t1[-1] < 30.0
    g1 = np.diff(np.append(t1, 30.0))
    g2 = np.diff(np.append(t2, 30.0))
    assert np.allclose(np.sort(g1), np.sort(g2)) and not np.allclose(g1, g2)
    assert g1.sum() == pytest.approx(30.0)
    # exponential gaps: the coefficient of variation is near 1
    assert 0.8 < g1.std() / g1.mean() < 1.1
    # the lead-in is its own stretch at the same rate, back to -6 s
    lt = np.array([t for t, _ in lead1])
    assert lt[0] == pytest.approx(-6.0) and lt[-1] < 0
    assert np.all(np.diff(lt) > 0)
    with pytest.raises(ValueError):
        traffic.arrival_gaps({"process": "poisson", "rate_per_s": 1.0}, 10)


def test_chat_open_runs_at_four_fifths_of_the_knee_its_file_states():
    """The rate and the knee it was derived from may not drift apart
    again (until PR 34 the cell ran at 0.8 x a knee swept when a decode
    step took ten times as long): ``rate_per_s`` is 0.8 x
    ``knee_req_per_s`` to two significant digits, or under that where
    the file says why (``rate_below_four_fifths_because``), and the
    cell's ``why`` states both numbers."""
    rate, knee = CHAT["arrivals"]["rate_per_s"], CHAT["knee_req_per_s"]
    four_fifths = float(f"{0.8 * knee:.2g}")
    if "rate_below_four_fifths_because" in CHAT:
        assert 0 < rate < four_fifths
    else:
        assert rate == four_fifths
    cell = spec.find_cell(spec.load_benchmark(), "serve-chat")
    assert cell["traffic"] == "chat-open"
    assert f"{rate} req/s" in cell["why"] and f"knee {knee}" in cell["why"]


def test_training_batches_replay_from_a_seed():
    inputs = {"data": {"shape": [2, 3, 4], "kind": "uniform"},
              "softmax_label": {"shape": [2], "kind": "int", "high": 50}}
    a = traffic.batch_arrays(inputs, 3, 2**31 + 11)
    b = traffic.batch_arrays(inputs, 3, 2**31 + 11)
    assert a["data"].shape == (6, 3, 4) and a["softmax_label"].shape == (6,)
    assert np.array_equal(a["data"], b["data"])
    assert not np.array_equal(a["data"],
                              traffic.batch_arrays(inputs, 3, 12)["data"])
    assert a["softmax_label"].max() < 50


def test_the_knee_is_the_highest_rate_whose_windows_all_keep_up():
    from benchmark import sweep

    def row(rate, done, offered=100.0, queue=0, missing=0):
        return {"rate_per_s": rate, "tokens_per_s": done,
                "offered_tokens_per_s": offered, "queue_end": queue,
                "no_first_token": missing}
    assert sweep.keeps_up(row(1, 90.0), 32)
    assert not sweep.keeps_up(row(1, 89.9), 32)
    assert not sweep.keeps_up(row(1, 100.0, queue=33), 32)
    assert not sweep.keeps_up(row(1, 100.0, missing=1), 32)
    rows = [row(0.6, 95), row(0.6, 93), row(0.8, 96), row(0.8, 91),
            row(1.0, 95), row(1.0, 85), row(1.2, 99), row(1.2, 99)]
    assert sweep.knee(rows, 32) == 0.8      # 1.2 lies above a failed rate
    assert sweep.knee([row(0.6, 50)], 32) is None
