"""Speculative decoding (mxnet_tpu/serve/speculate.py + the engine's
verify step, docs/serving.md §Speculative decoding).

The contracts under test, per issue 16's acceptance criteria:

* **replay-exact greedy**: a speculative engine emits byte-identical
  streams to the non-speculative engine — across batch composition,
  admission order, pool-pressure preemption, and mid-stream Router
  failover;
* **distribution-correct temperature**: the acceptance rule's emitted
  marginal is exactly the temp/top-k sampling distribution (residual
  resampling lemma, checked statistically over many keys), and a
  live=0 row is byte-identical to plain decode even under temperature;
* **KV rollback**: a rejected draft tail is scrubbed from the pools
  in-graph — the block cursor truncates, table integrity holds every
  step, and freed blocks carry no stale K/V into their next tenant;
* **zero retraces**: warmup compiles the verify (and draft) bucket
  family once; a full speculative workload then runs zero new traces;
* **draft hot-swap**: a 'model' drafter's weights are per-replica
  operands — ``Engine.swap_draft_weights`` / ``Router.rolling_swap(...,
  target="draft")`` install compatible weights with zero retraces and
  no drain; incompatible weights raise before anything changes;
* scheduler admission discounts SLO slack by the K-aware decode
  backlog (``decode_backlog_ms``).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.chaos import ChaosSpec
from mxnet_tpu.models.transformer import transformer_lm
from mxnet_tpu.serve import (Engine, EngineConfig, NGramDrafter, Router,
                             RouterConfig, make_drafter)
from mxnet_tpu.serve.engine import _spec_accept_row
from mxnet_tpu.serve.router import DEAD, HEALTHY
from mxnet_tpu.serve.scheduler import Request, Scheduler

V, NL, D, H = 61, 2, 32, 4


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def _make_params(seed=0, d_model=D, heads=H):
    rng = np.random.RandomState(seed)
    sym = transformer_lm(vocab_size=V, num_layers=NL, d_model=d_model,
                         heads=heads, batch_size=1, seq_len=8)
    shapes, _, _ = sym.infer_shape(data=(1, 8), softmax_label=(1, 8))
    return {n: (rng.randn(*s) * 0.05).astype(np.float32)
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}


_PARAMS = _make_params()
_DRAFT = _make_params(seed=7)

_ECFG = dict(heads=H, block_size=4, num_blocks=64, max_batch=4,
             max_prompt_len=16, max_seq_len=48, prompt_bucket_min=8)


def _engine(speculate=True, draft_params=None, **over):
    cfg = dict(_ECFG)
    cfg.update(over)
    kw = {}
    if draft_params is not None:
        kw = dict(draft_params=draft_params, draft_heads=H)
    return Engine(_PARAMS, EngineConfig(speculate=speculate, **cfg), **kw)


# mixed greedy / seeded-sampling workload (same shape as the serve
# parity suite): greedy rows must match the non-speculative engine
# byte-for-byte; sampled rows must be invariant to batch composition,
# preemption, and failover (position-keyed draws + deterministic
# drafts).
_PROMPTS = [[1, 2, 3], [10, 11, 12, 13, 14, 15], [20, 21], [30, 31, 32, 33]]
_KW = [dict(max_new_tokens=10, seed=101),
       dict(max_new_tokens=8, temperature=0.9, top_k=7, seed=202),
       dict(max_new_tokens=12, seed=303),
       dict(max_new_tokens=6, temperature=1.3, seed=404)]


def _alone(speculate, **over):
    outs = []
    for p, k in zip(_PROMPTS, _KW):
        e = _engine(speculate=speculate, **over)
        outs.append(e.result(e.submit(p, **k)))
    return outs


# ---------------------------------------------------------------------------
# NGram drafter
# ---------------------------------------------------------------------------

def test_ngram_drafter_suffix_match():
    d = NGramDrafter(max_n=3)
    # trigram [5,6,7] seen earlier, followed by 8, 9
    assert d._draft_one([1, 5, 6, 7, 8, 9, 2, 5, 6, 7], 2) == [8, 9]
    # continuation shorter than k extends cyclically (period 2 here)
    assert d._draft_one([3, 4, 3, 4], 3) == [3, 4, 3]
    # most RECENT match wins over an older one
    assert d._draft_one([3, 4, 9, 3, 4, 7, 3, 4], 1) == [7]
    # no match at any n -> repeat last token
    assert d._draft_one([1, 2, 3], 2) == [3, 3]
    assert d._draft_one([4], 3) == [4, 4, 4]
    # degenerate constant stream: period-1 match nails it
    assert d._draft_one([9, 9, 9], 2) == [9, 9]
    out = d.propose([[1, 2, 1, 2], [7]], 3)
    assert out.shape == (2, 3) and out.dtype == np.int32
    assert list(out[0]) == [1, 2, 1]


def test_make_drafter_validation():
    assert make_drafter("ngram").kind == "ngram"
    assert make_drafter("").kind == "ngram"            # default
    with pytest.raises(MXNetError):
        make_drafter("beam")
    with pytest.raises(MXNetError):
        make_drafter("model")                          # needs params
    with pytest.raises(MXNetError):
        make_drafter("model", draft_params=_DRAFT)     # needs heads
    m = make_drafter("model", draft_params=_DRAFT, draft_heads=H)
    assert m.kind == "model" and "model:" in m.signature()
    with pytest.raises(MXNetError):                    # no bound program
        m.propose([[1, 2]], 2)
    with pytest.raises(MXNetError):                    # ngram has no weights
        make_drafter("ngram").swap(_DRAFT)


# ---------------------------------------------------------------------------
# Acceptance rule: greedy exactness + temperature distribution lemma
# ---------------------------------------------------------------------------

def test_accept_rule_greedy_rolling_argmax():
    """Greedy acceptance emits exactly the rolling-argmax stream: every
    accepted draft equals argmax at its position, and the first
    mismatch is corrected to the argmax."""
    rng = np.random.RandomState(0)
    logits = jnp.asarray(rng.randn(5, V).astype(np.float32))
    am = np.argmax(np.asarray(logits), axis=-1)
    key = jax.random.PRNGKey(3)
    z = jnp.float32(0.0)
    # drafts match argmax for 2 positions, then diverge
    toks = jnp.asarray([17, am[0], am[1], (am[2] + 1) % V, am[3]],
                       jnp.int32)
    out, nem = _spec_accept_row(logits, toks, jnp.int32(4), key, z,
                                jnp.int32(0), jnp.int32(9))
    assert int(nem) == 3
    assert list(np.asarray(out[:3])) == [am[0], am[1], am[2]]
    # all live accepted -> bonus token is the next argmax
    toks = jnp.asarray([17, am[0], am[1], am[2], am[3]], jnp.int32)
    out, nem = _spec_accept_row(logits, toks, jnp.int32(4), key, z,
                                jnp.int32(0), jnp.int32(9))
    assert int(nem) == 5
    assert list(np.asarray(out)) == list(am)
    # live clamps acceptance regardless of draft quality
    out, nem = _spec_accept_row(logits, toks, jnp.int32(0), key, z,
                                jnp.int32(0), jnp.int32(9))
    assert int(nem) == 1 and int(out[0]) == am[0]


def test_accept_rule_temperature_marginal_is_sampling_dist():
    """The residual-resampling lemma: for ANY deterministic draft, the
    emitted token's marginal at a position is exactly the temp/top-k
    sampling distribution p — p(x)·δx + (1-p(x))·residual = p.
    Checked empirically over many keys at the first window position."""
    rng = np.random.RandomState(1)
    logits = jnp.asarray((rng.randn(3, V) * 2).astype(np.float32))
    temp, topk = jnp.float32(1.1), jnp.int32(0)
    draft = int(np.argmax(np.asarray(logits)[0]))   # a high-mass draft
    toks = jnp.asarray([5, draft, draft], jnp.int32)
    n = 6000

    def first_tok(key):
        out, _ = _spec_accept_row(logits, toks, jnp.int32(2), key,
                                  temp, topk, jnp.int32(4))
        return out[0]

    keys = jax.random.split(jax.random.PRNGKey(0), n)
    toks_out = np.asarray(jax.jit(jax.vmap(first_tok))(keys))
    emp = np.bincount(toks_out, minlength=V) / n
    ref = np.asarray(jax.nn.softmax(logits[0] / temp))
    tv = 0.5 * np.abs(emp - ref).sum()
    assert tv < 0.08, f"total variation {tv:.3f} vs sampling dist"


def test_live_zero_row_is_plain_decode_even_with_temperature():
    """A live=0 speculative row must run the plain sampler at its
    position (bonus path) — byte-identical to non-speculative decode,
    temperature included.  max_new_tokens=1 forces live=0 for the
    whole (single-step) stream."""
    for kw in (dict(max_new_tokens=1, seed=11),
               dict(max_new_tokens=1, temperature=1.2, seed=12),
               dict(max_new_tokens=1, temperature=0.7, top_k=5, seed=13)):
        ref = _engine(speculate=False)
        spec = _engine(speculate=True, spec_k=4)
        assert (spec.result(spec.submit([4, 8, 15, 16], **kw))
                == ref.result(ref.submit([4, 8, 15, 16], **kw)))


# ---------------------------------------------------------------------------
# Engine byte-identity: the headline acceptance
# ---------------------------------------------------------------------------

def test_speculative_batch_matches_non_speculative():
    """Speculative continuous batching emits the exact streams of the
    non-speculative engine (greedy rows) and of speculative-alone runs
    (all rows — batch composition never perturbs a stream)."""
    plain = _alone(False)
    alone = _alone(True, spec_k=4)
    for i in (0, 2):                       # greedy rows: spec == plain
        assert alone[i] == plain[i]
    eng = _engine(spec_k=4)
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    assert [eng.result(i) for i in ids] == alone
    st = eng.stats()["speculate"]
    assert st["draft"] == "ngram" and st["drafted"] > 0
    assert eng.alloc.num_used == 0


def test_speculative_admission_order_invariance():
    """Staggered submissions change batch composition mid-stream; no
    speculative row may notice."""
    alone = _alone(True, spec_k=4)
    eng = _engine(spec_k=4)
    i0 = eng.submit(_PROMPTS[0], **_KW[0])
    for _ in range(3):
        eng.step()
    i1 = eng.submit(_PROMPTS[1], **_KW[1])
    for _ in range(2):
        eng.step()
    i2 = eng.submit(_PROMPTS[2], **_KW[2])
    i3 = eng.submit(_PROMPTS[3], **_KW[3])
    eng.run()
    assert [eng.requests[i].tokens for i in (i0, i1, i2, i3)] == alone
    assert eng.alloc.num_used == 0


def test_speculative_preemption_replay_exact():
    """Pool pressure under speculation: headroom degrades to live=0
    before anyone is preempted for it, mandatory growth may still
    preempt — greedy rows replay their exact non-speculative stream
    (acceptance is draw-free, so the live schedule cannot move it),
    and the whole run is deterministic: an identical engine replays
    every stream bit-for-bit, temperature rows included."""
    plain = _alone(False)

    def _run():
        e = _engine(spec_k=4, num_blocks=10, max_batch=4)
        ids = [e.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
        return e, [e.result(i) for i in ids]

    eng, outs = _run()
    for i in (0, 2):                     # greedy rows: byte-identical
        assert outs[i] == plain[i]
    _, outs2 = _run()                    # deterministic replay
    assert outs2 == outs
    assert telemetry.snapshot_flat().get("serve.preemptions", 0) > 0
    assert eng.alloc.num_used == 0


def test_speculative_zero_trace_warm_cycle():
    """After warmup, a full speculative workload runs ZERO new traces:
    verify is one more AOT bucket family, not one more trace per
    step."""
    eng = _engine(spec_k=4)
    eng.warmup()
    snap = dict(eng.trace_counts)
    kinds = {k for k, _ in eng._programs}
    assert "verify" in kinds and "decode" not in kinds
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    for i in ids:
        eng.result(i)
    assert dict(eng.trace_counts) == snap


def test_speculative_multi_token_itl_accounting():
    """Satellite: a K-token burst lands the step latency on its first
    token and 0 ms on the rest — the token_ms histogram must count
    every emitted token, not every step."""
    eng = _engine(spec_k=4)
    rid = eng.submit([9, 9, 9], max_new_tokens=12, seed=1)
    eng.result(rid)
    flat = telemetry.snapshot_flat()
    assert flat.get("serve.tokens_total") == 12
    # one observation per DECODED token (the first token is prefill's,
    # measured by ttft_ms) — not one per step
    assert flat.get("serve.token_ms.count") == 11
    st = eng.stats()["speculate"]
    assert st["accept_rate"] > 0.5            # degenerate cycle drafts well
    assert eng.step_idx < 12 + 3              # multi-token steps happened


# ---------------------------------------------------------------------------
# KV rollback: rejected tails truncate clean and leak nothing
# ---------------------------------------------------------------------------

def test_spec_rejected_tail_scrubbed_and_tables_clean():
    """Drive a workload whose drafts mostly reject (temperature):
    after every step the cursor invariant holds, the allocator audit
    passes, and every pool entry past a request's cursor is zero —
    the rejected tail was written, then scrubbed in-graph."""
    eng = _engine(spec_k=4)
    rid = eng.submit([3, 1, 4, 1, 5], max_new_tokens=14, temperature=1.4,
                     seed=77)
    bsz = eng.alloc.block_size
    saw_reject = False
    while not eng.sched.idle():
        eng.step()
        eng.check_tables()
        req = eng.requests[rid]
        if req.done():
            break
        assert req.cached == len(req.seed_tokens) - 1
        kp = np.asarray(eng.kpool)            # [L, blocks, bsz, H*hd]
        for pos_i, blk in enumerate(req.blocks):
            for off in range(bsz):
                if pos_i * bsz + off >= req.cached:
                    if np.any(kp[:, blk, off]):
                        pytest.fail(f"stale K/V past cursor at block "
                                    f"{blk} offset {off}")
                    saw_reject = saw_reject or True
    st = eng.stats()["speculate"]
    assert st["drafted"] > st["accepted"]      # rejections happened
    assert eng.alloc.num_used == 0


def test_spec_freed_blocks_carry_no_stale_kv():
    """A request admitted after a speculative (reject-heavy) tenant
    freed its blocks must decode exactly as on a fresh engine — the
    scrub leaves nothing for the allocator to hand out."""
    fresh = _engine(spec_k=4)
    ref = fresh.result(fresh.submit([2, 4, 6, 8], max_new_tokens=10,
                                    seed=5))
    eng = _engine(spec_k=4)
    first = eng.submit([7, 3, 7, 1], max_new_tokens=12, temperature=1.5,
                       seed=9)
    eng.result(first)                          # reject-heavy, then freed
    got = eng.result(eng.submit([2, 4, 6, 8], max_new_tokens=10, seed=5))
    assert got == ref


def test_spec_config_validation():
    with pytest.raises(MXNetError):
        _engine(spec_k=0)
    with pytest.raises(MXNetError):
        _engine(spec_k=64)                     # k + 1 >= max_seq_len
    with pytest.raises(MXNetError):
        _engine(spec_draft="model")            # needs draft_params
    with pytest.raises(MXNetError):
        _engine(speculate=False).swap_draft_weights(_DRAFT)
    with pytest.raises(MXNetError):            # ngram drafter: no weights
        _engine(spec_k=2).swap_draft_weights(_DRAFT)


# ---------------------------------------------------------------------------
# Model drafter: draft program + hot-swap (the round-13 deploy story)
# ---------------------------------------------------------------------------

def test_model_drafter_greedy_identity_and_swap_zero_retrace():
    """A (deliberately mismatched) draft model must not change WHAT is
    emitted — only acceptance rates.  Swapping its weights is a pure
    operand install: zero retraces, counted in draft_swaps."""
    plain = _alone(False)
    eng = _engine(spec_k=3, spec_draft="model", draft_params=_DRAFT)
    eng.warmup()
    snap = dict(eng.trace_counts)
    assert any(k == "draft" for k, _ in eng._programs)
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    outs = [eng.result(i) for i in ids]
    for i in (0, 2):
        assert outs[i] == plain[i]
    # swap in the TARGET weights as the draft -> drafts become the
    # target's own argmax -> greedy acceptance goes perfect
    eng.swap_draft_weights(_PARAMS)
    assert eng.spec.swap_count == 1
    rid = eng.submit(_PROMPTS[0], **_KW[0])
    assert eng.result(rid) == plain[0]
    st = eng.stats()["speculate"]
    assert st["draft_swaps"] == 1
    assert dict(eng.trace_counts) == snap      # ZERO new traces
    flat = telemetry.snapshot_flat()
    assert flat.get("serve.spec.draft_swaps") == 1


def test_model_drafter_incompatible_swap_raises():
    eng = _engine(spec_k=2, spec_draft="model", draft_params=_DRAFT)
    bad = _make_params(seed=3, d_model=16, heads=4)
    with pytest.raises(MXNetError, match="incompatible"):
        eng.swap_draft_weights(bad)
    assert eng.spec.swap_count == 0            # untouched


def test_router_rolling_swap_draft_target():
    """rolling_swap(target='draft') deploys new draft weights fleetwide
    with zero retraces and no drain; 'model'-target swaps and bogus
    targets are rejected cleanly."""
    router = Router(_PARAMS,
                    EngineConfig(speculate=True, spec_k=3,
                                 spec_draft="model", **_ECFG),
                    RouterConfig(replicas=2),
                    draft_params=_DRAFT, draft_heads=H)
    router.warmup()
    snap = {rep.idx: dict(rep.engine.trace_counts)
            for rep in router.replicas}
    ids = [router.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    router.run()
    res = router.rolling_swap(_PARAMS, target="draft")
    assert res["mode"] == "draft" and res["replicas"] == [0, 1]
    assert all(rep.engine.spec.swap_count == 1 for rep in router.replicas)
    assert all(rep.state == HEALTHY for rep in router.replicas)
    # fleet still serves, streams unchanged, zero retraces anywhere
    ref = _alone(True, spec_k=3, spec_draft="model", draft_params=_DRAFT)
    i2 = [router.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    router.run()
    # greedy rows match (sampled rows too: acceptance path changed by
    # the new drafts, but greedy replay-exactness is draft-independent)
    plain = _alone(False)
    for j in (0, 2):
        assert router.request(i2[j]).tokens == plain[j]
        assert router.request(ids[j]).tokens == ref[j]
    for rep in router.replicas:
        assert dict(rep.engine.trace_counts) == snap[rep.idx]
    with pytest.raises(MXNetError, match="target"):
        router.rolling_swap(_PARAMS, target="bogus")


def test_router_swap_draft_requires_model_drafter():
    router = Router(_PARAMS, EngineConfig(speculate=True, spec_k=2,
                                          **_ECFG),
                    RouterConfig(replicas=1))
    router.warmup()
    with pytest.raises(MXNetError, match="model drafter"):
        router.rolling_swap(_PARAMS, target="draft")


# ---------------------------------------------------------------------------
# Router failover with speculation on
# ---------------------------------------------------------------------------

def test_spec_failover_crash_mid_stream_byte_identical():
    """Kill a speculating replica mid-stream: the merged client-visible
    streams are byte-identical to the no-failure speculative run (and
    greedy rows to the non-speculative engine) — adopt re-prefill,
    deterministic drafts, position-keyed acceptance draws."""
    def _mk(chaos):
        return Router(_PARAMS, EngineConfig(speculate=True, spec_k=4,
                                            **_ECFG),
                      RouterConfig(replicas=2), chaos=chaos)

    clean = _mk({})
    clean.warmup()
    ids = [clean.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    clean.run()
    ref = [clean.request(i).tokens for i in ids]
    plain = _alone(False)
    for j in (0, 2):
        assert ref[j] == plain[j]

    # speculation compresses the step count — crash EARLY so the
    # replica still holds live streams when it dies
    router = _mk({0: ChaosSpec({"serve_crash": {2}})})
    router.warmup()
    snap = {rep.idx: dict(rep.engine.trace_counts)
            for rep in router.replicas}
    ids = [router.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    router.run()
    assert [router.request(i).state for i in ids] == ["finished"] * 4
    assert [router.request(i).tokens for i in ids] == ref
    dead, surv = router.replicas
    assert dead.state == DEAD and surv.state == HEALTHY
    assert dict(surv.engine.trace_counts) == snap[1]   # zero retraces
    assert surv.engine.alloc.num_used == 0


# ---------------------------------------------------------------------------
# Scheduler: K-aware decode backlog
# ---------------------------------------------------------------------------

def test_scheduler_decode_backlog_discounts_slack():
    s = Scheduler(max_batch=2, slo_admit_frac=0.5)
    early = s.submit(Request(prompt=[1]), now=0.0)
    slo = s.submit(Request(prompt=[2], slo_ms=100.0), now=0.0)
    assert s.admission_order(now=0.030)[0] is early
    # a 25 ms decode backlog pushes the SLO row over the jump line
    assert s.admission_order(now=0.030,
                             decode_backlog_ms=25.0)[0] is slo
    got = s.admit(lambda r: True, now=0.030, decode_backlog_ms=25.0)
    assert got[0] is slo


def test_engine_decode_backlog_estimate():
    """K-aware: the soonest slot frees after remaining/_tps steps; zero
    when speculation is off, a slot is free, or no history yet."""
    off = _engine(speculate=False)
    assert off._decode_backlog_ms() == 0.0
    eng = _engine(spec_k=4, max_batch=2)
    assert eng._decode_backlog_ms() == 0.0          # no EWMA history
    eng._decode_ms, eng._tps = 2.0, 2.5
    r1 = Request(prompt=[1], max_new_tokens=10)
    r2 = Request(prompt=[2], max_new_tokens=20)
    r1.tokens, r2.tokens = [0] * 5, [0] * 5
    eng.sched.running.append(r1)
    assert eng._decode_backlog_ms() == 0.0          # a slot is free
    eng.sched.running.append(r2)
    # min remaining = 5 tokens / 2.5 tok/step * 2 ms = 4 ms
    assert eng._decode_backlog_ms() == pytest.approx(4.0)


def test_spec_fp8_kv_greedy_parity():
    """Speculation composes with the fp8 KV pool: per-position rowwise
    quantization keeps a live=K verify write byte-equal to the plain
    decode write, so greedy identity survives quantized caches."""
    ref = _engine(speculate=False, kv_quant="fp8")
    spec = _engine(spec_k=4, kv_quant="fp8")
    kw = dict(max_new_tokens=10, seed=21)
    assert (spec.result(spec.submit([9, 9, 9], **kw))
            == ref.result(ref.submit([9, 9, 9], **kw)))
    assert spec.stats()["speculate"]["accepted"] > 0
