"""The closed loop's rounds (ISSUE 28): round 0 is the draw the cells
always had, further rounds are the same multiset sampled anew and come
on demand, so no speed of the engine ends a run; and ``serve_mfu``, the
served positions' share of the chip's peak."""
import dataclasses

import pytest

from benchmark.harness import readers, spec, traffic
from benchmark.harness.runtime import Run, TraceWindow
from benchmark.run import merged

closed = spec.load_module("runners", "serve_engine_closed")
BENCH = spec.load_benchmark()
MIXES = {name: spec.load_traffic(name)
         for name in ("batch-closed", "gen-closed-16")}
VOCAB = 50272
SEEDS = [3, 2**31 + 17, 2**31 + 936]


def lengths(reqs):
    return [(len(r.prompt), r.max_new_tokens) for r in reqs]


# -- the plan ----------------------------------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_round_0_is_the_draw_the_cells_always_had(mix, seed):
    m = MIXES[mix]
    clients, per = m["clients"], m["requests_per_client"]
    pool = traffic.make_requests(m, VOCAB, seed, clients * per, stream=0)
    plan = closed.Rounds(m, VOCAB, seed)
    for c in range(clients):
        old = pool[c::clients]
        old[0] = dataclasses.replace(
            old[0], max_new_tokens=max(
                2, old[0].max_new_tokens * (c + 1) // clients))
        assert [plan.request(c, t) for t in range(per)] == old
    assert len(plan.rounds) == 1 and len(plan.drawn) == 1


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("mix", sorted(MIXES))
def test_later_rounds_are_the_same_multiset_in_another_order(mix, seed):
    m = MIXES[mix]
    clients, per = m["clients"], m["requests_per_client"]
    plan = closed.Rounds(m, VOCAB, seed)
    plan.request(clients - 1, 3 * per - 1)         # the last of round 2
    assert len(plan.rounds) == 3
    whole = traffic.make_requests(m, VOCAB, seed, clients * per)
    for part in (0, 1):                            # prompts, budgets
        want = sorted(x[part] for x in lengths(whole))
        for r in (1, 2):
            assert sorted(x[part] for x in lengths(plan.rounds[r])) == want
    assert lengths(plan.rounds[1]) != lengths(whole)
    assert lengths(plan.rounds[1]) != lengths(plan.rounds[2])
    # client c's t-th request is entry c + clients * (t % per) of round
    # t // per, and the whole plan replays from the seed
    again = closed.Rounds(m, VOCAB, seed)
    for c, t in ((0, per), (clients - 1, per + 1), (3, 2 * per + per - 1)):
        r, k = divmod(t, per)
        assert plan.request(c, t) is plan.rounds[r][c + clients * k]
        assert again.request(c, t) == plan.request(c, t)
    assert closed.Rounds(m, VOCAB, seed + 1).request(0, per) \
        != plan.request(0, per)


@pytest.mark.parametrize("mix", sorted(MIXES))
def test_the_stagger_touches_each_clients_first_request_and_no_other(mix):
    m = MIXES[mix]
    clients, per = m["clients"], m["requests_per_client"]
    plan = closed.Rounds(m, VOCAB, 11)
    flat = closed.Rounds(dict(m, stagger_first=False), VOCAB, 11)
    for c in range(clients):
        for t in range(2 * per + 1):
            a, b = plan.request(c, t), flat.request(c, t)
            if t:
                assert a == b
            else:
                assert a.prompt == b.prompt
                assert a.max_new_tokens == max(
                    2, b.max_new_tokens * (c + 1) // clients)
    assert plan.request(0, 0).max_new_tokens \
        < flat.request(0, 0).max_new_tokens


@pytest.mark.parametrize("mix,vocab,n,stream,crc", [
    ("batch-closed", 50272, 192, 0, 248314607),
    ("batch-closed", 50272, 192, 1, 1991020859),
    ("gen-closed-16", 151936, 64, 0, 4196607214),
    ("gen-closed-16", 151936, 64, 1, 3530617830),
    ("chat-open", 50272, 672, 0, 813173129),
    ("chat-open", 50272, 210, 1, 1998124053)])
def test_the_generator_draws_what_it_drew_at_pr_26(mix, vocab, n, stream, crc):
    """Checksums of the accepted benchmark's own draws (commit 0b90c33):
    a cheaper draw may not change a token, a length or a sampling seed,
    or the ledger's levels would no longer carry on.  ``chat-open`` was
    re-rated at PR 34 (0.56 -> 14 req/s: 672 requests a window and 210
    in the lead-in, where it drew 27 and 8) and its levels restart
    there; the generator's code is the same, and at the old count it
    still draws the old checksums (255550456, 3956870344 at 27)."""
    import zlib
    reqs = traffic.make_requests(spec.load_traffic(mix), vocab, 2**31 + 936,
                                 n, stream=stream)
    assert all(type(t) is int for t in reqs[0].prompt)
    assert zlib.crc32(repr(reqs).encode()) == crc


# -- the loop, over an engine that is as fast as one likes --------------------

class Clock:
    """``time`` for the runner's module: it moves when the engine steps."""

    def __init__(self):
        self.now = 100.0

    def monotonic(self):
        return self.now

    def perf_counter_ns(self):
        return int(self.now * 1e9)


class StubRequest:
    def __init__(self, budget):
        self.left = budget
        self.token_times = []

    def done(self):
        return self.left <= 0


class StubEngine:
    """Every live request gets a token a step and finishes after its
    budget of steps; a step takes 10 ms of the clock."""

    def __init__(self, clock):
        self.clock, self.requests, self.steps = clock, [], 0

    def submit(self, prompt, max_new_tokens, **_):
        self.requests.append(StubRequest(max_new_tokens))
        return len(self.requests) - 1

    def request(self, rid):
        return self.requests[rid]

    def step(self):
        self.steps += 1
        self.clock.now += 0.01
        for r in self.requests:
            if r.left > 0:
                r.left -= 1
                r.token_times.append(self.clock.now)


class Quiet:
    def mark(self):
        return 0

    def record(self, *_):
        pass


@pytest.mark.parametrize("mix,tiny", [("batch-closed", False),
                                      ("batch-closed", True),
                                      ("gen-closed-16", True)])
def test_no_client_is_ever_without_a_request(mix, tiny, monkeypatch, capsys):
    m = MIXES[mix]
    if tiny:
        m = merged(m, m["rehearsal"])
    clock = Clock()
    monkeypatch.setattr(closed, "time", clock)
    run = Run(cell={"chips": 1}, config={}, traffic=m, seed=2**31 + 5,
              seconds=49.5, traced=False, process_t0=0.0, compiles=Quiet(),
              scratch="")
    monkeypatch.setattr(Run, "sample_memory", lambda self: None)
    eng = StubEngine(clock)
    plan = closed.Rounds(m, 96 if tiny else VOCAB, run.seed)
    d = closed.drive(run, eng, plan, Quiet(), TraceWindow(run, 0.5), 0.5)
    per = plan.per
    assert 4999 <= eng.steps <= 5001
    assert min(d.turn) > 2 * per, d.turn
    assert len(plan.rounds) == (max(d.turn) - 1) // per + 1 >= 3
    assert len(d.sent) == sum(d.turn)
    # every step of the loop had a live request for every client
    assert sum(len(r.token_times) for r, _ in d.sent) \
        == eng.steps * plan.clients
    assert [r for _, r in d.sent[:plan.clients]] \
        == [plan.request(c, 0) for c in range(plan.clients)]
    said = capsys.readouterr().out
    for r in range(1, len(plan.rounds)):
        assert f"[traffic] round {r} drawn at +" in said
    assert d.w0 == pytest.approx(100.5)


# -- serve_mfu ---------------------------------------------------------------

def config(name):
    return spec.load_config(BENCH, name)


def matmul_params(ref, cfg):
    return sum(s[0] * s[1] for k, s in ref.param_shapes(cfg).items()
               if len(s) == 2 and k != "embed_weight")


def test_the_stand_in_needs_2_62_gflop_a_position_before_attention():
    ref = spec.load_module("reference", "nope_lm")
    cfg = config("nope-lm-2048x24")
    assert ref.forward_flops(cfg, 1, 0) == 2 * matmul_params(ref, cfg) \
        == 2 * (24 * (4 * 2048**2 + 2 * 2048 * 8192) + 50272 * 2048)
    assert ref.forward_flops(cfg, 1, 0) / 1e9 == pytest.approx(2.6218, abs=1e-4)
    # attention: what the decode kernel's cost function counts, all layers
    cost = readers.kernel_cost("mxtpu_flash_decode")
    assert ref.forward_flops(cfg, 0, 1000) \
        == cost(1000, 4, 32, 64, 2, layers=24)["flops"] == 4 * 24 * 2048 * 1000
    assert ref.forward_flops(cfg, 7, 1000) == 7 * ref.forward_flops(cfg, 1, 0) \
        + ref.forward_flops(cfg, 0, 1000)
    # a third of what training needs for the same token (no backward)
    loss = spec.load_module("reference", "nope_lm_loss")
    job = {"train": spec.load_traffic("lm-tokens-8x2048")["train"]}
    assert 3 * ref.forward_flops(cfg, 1, 2048) \
        == pytest.approx(loss.train_flops_per_token(job, 2048))


def test_brumby_needs_6_18_gflop_a_position_at_any_length():
    ref = spec.load_module("reference", "brumby")
    cfg = config("brumby-14b-6of40")
    cost = readers.kernel_cost("mxtpu_retention_decode")
    state = cost(1, 40, 8, 128, 2, layers=6)["flops"]
    assert state == 6 * 8 * (8256 * 129) * 13
    assert ref.forward_flops(cfg, 1, 0) == 2 * matmul_params(ref, cfg) + state
    assert ref.forward_flops(cfg, 1, 0) / 1e9 == pytest.approx(6.1845, abs=1e-4)
    assert ref.forward_flops(cfg, 5, 10**6) == 5 * ref.forward_flops(cfg, 1, 0)


def test_served_counts_the_positions_whose_token_fell_in_the_window():
    class Req:
        def __init__(self, times):
            self.token_times = times

    spec3 = traffic.RequestSpec((1, 2, 3), 4, 0.0, 0, 0)
    spec5 = traffic.RequestSpec((1,) * 5, 4, 0.0, 0, 0)
    sent = [(Req([9.0, 10.0, 11.0, 20.0]), spec3),   # 2 decoded inside
            (Req([10.5, 11.5]), spec5),              # prefilled + 1 decoded
            (Req([]), spec5)]
    got = closed.served(sent, 10.0, 20.0)
    assert got == {"decoded": 3, "prefilled": 5,
                   "attended": (3 + 1) + (3 + 2) + (5 + 1) + 5 * 6 // 2}


@pytest.mark.parametrize("metric,cell", [("serve_mfu.batch", "serve-batch"),
                                         ("serve_mfu.gen", "brumby-gen-closed")])
def test_serve_mfu_is_the_served_flops_over_the_window_and_the_peak(metric, cell):
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["workloads"] == [cell] and entry["moves"] == "serve_tok_s"
    assert entry["source"] == "host_clock" and entry["unit"] == "%"
    cfg = config(spec.find_cell(BENCH, cell)["config"])
    ref = spec.load_module("reference", cfg["family"])
    read = spec.load_reader(metric).read
    facts = {"config": cfg, "chips": 1, "window_s": 48.0,
             "peaks": spec.load_peaks("TPU v5 lite"),
             "served": {"decoded": 15000, "prefilled": 12000,
                        "attended": 15000 * 320 + 12000 * 100}}
    want = ref.forward_flops(cfg, 27000, facts["served"]["attended"])
    assert read(facts) == pytest.approx(100 * want / 48.0 / 197e12)
    assert 0.1 < read(facts) < 5
    assert read(dict(facts, chips=4)) == pytest.approx(read(facts) / 4)
    # nothing served, or a runner that does not count: nothing to read
    assert read(dict(facts, served={"decoded": 0, "prefilled": 0,
                                    "attended": 0})) is None
    assert read({k: v for k, v in facts.items() if k != "served"}) is None
