"""Serving tier (mxnet_tpu/serve, docs/serving.md): paged KV-cache +
continuous batching + AOT prefill/decode.

The contracts under test, per issue 10's acceptance criteria:

* block allocator: alloc/free/reuse determinism, table integrity,
  defrag relocation — and defrag never changes outputs (pure gather);
* paged attention is BITWISE identical to the dense (contiguous-cache)
  read of the same values, and matches a plain-softmax reference;
* continuous batching is token-for-token identical to running each
  request alone — greedy AND seeded sampling, including mid-flight
  admission, staggered eviction, and pool-pressure preemption;
* after warmup a full admit→decode→evict cycle runs ZERO new traces,
  and a warm-restarted engine re-attaches to cached programs without
  a single compile;
* scheduler policy: FIFO, bounded queue, SLO-aware jump, no
  head-of-line skipping;
* cancel mid-generation frees blocks and terminates streams;
* engine exceptions dump the flight recorder.
"""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.transformer import (transformer_lm,
                                          transformer_lm_prefill,
                                          transformer_lm_decode_dense)
from mxnet_tpu.serve import Engine, EngineConfig, kvcache
from mxnet_tpu.serve.kvcache import BlockAllocator, TRASH_BLOCK
from mxnet_tpu.serve.scheduler import (ACTIVE, CANCELLED, FINISHED,
                                       QUEUED, Request, Scheduler)

V, NL, D, H = 61, 2, 32, 4


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def _make_params(seed=0):
    rng = np.random.RandomState(seed)
    sym = transformer_lm(vocab_size=V, num_layers=NL, d_model=D, heads=H,
                         batch_size=1, seq_len=8)
    shapes, _, _ = sym.infer_shape(data=(1, 8), softmax_label=(1, 8))
    return sym, {n: (rng.randn(*s) * 0.05).astype(np.float32)
                 for n, s in zip(sym.list_arguments(), shapes)
                 if n not in ("data", "softmax_label")}


_SYM, _PARAMS = _make_params()


def _engine(**over):
    cfg = dict(heads=H, block_size=4, num_blocks=64, max_batch=4,
               max_prompt_len=16, max_seq_len=48, prompt_bucket_min=8)
    cfg.update(over)
    return Engine(_PARAMS, EngineConfig(**cfg))


# ---------------------------------------------------------------------------
# Block allocator + table integrity + defrag
# ---------------------------------------------------------------------------

def test_allocator_alloc_free_reuse():
    al = BlockAllocator(num_blocks=8, block_size=4)
    assert al.num_free == 7                     # slot 0 reserved
    a = al.alloc(3, "a")
    assert a == [1, 2, 3]                       # lowest-first, deterministic
    b = al.alloc(2, "b")
    assert b == [4, 5]
    al.free(a)
    c = al.alloc(3, "c")
    assert c == [1, 2, 3]                       # freed slots recycle
    assert al.blocks_for_tokens(1) == 1
    assert al.blocks_for_tokens(4) == 1
    assert al.blocks_for_tokens(5) == 2
    with pytest.raises(MXNetError):
        al.alloc(5, "d")                        # only 2 free
    with pytest.raises(MXNetError):
        al.free([4, 4])                         # double free
    with pytest.raises(MXNetError):
        BlockAllocator(num_blocks=1, block_size=4)


def test_allocator_table_integrity():
    al = BlockAllocator(num_blocks=8, block_size=4)
    a = al.alloc(2, "a")
    b = al.alloc(2, "b")
    al.check({"a": a, "b": b})                  # clean state passes
    with pytest.raises(MXNetError, match="trash"):
        al.check({"a": [TRASH_BLOCK] + a[1:], "b": b})
    with pytest.raises(MXNetError, match="not owned"):
        al.check({"a": a, "b": [a[0], b[1]]})
    with pytest.raises(MXNetError, match="leaked"):
        al.check({"a": a})                      # b's blocks unaccounted


def test_allocator_defrag_compacts():
    al = BlockAllocator(num_blocks=10, block_size=4)
    a = al.alloc(2, "a")
    b = al.alloc(2, "b")
    c = al.alloc(2, "c")
    al.free(b)
    mapping = al.defrag()
    # live slots a=[1,2], c=[5,6] compact to [1,2,3,4]
    assert mapping == {5: 3, 6: 4}
    assert al.owned_by("c") == [3, 4]
    assert al.num_free == 9 - 4
    al.check({"a": a, "c": [mapping.get(x, x) for x in c]})
    assert al.defrag() == {}                    # idempotent


def test_engine_defrag_bitwise_stable():
    """Mid-generation defrag (tables rewritten + pools compacted) must
    not change a single output token: relocation is a pure copy."""
    base = _engine()
    base.warmup()
    ids = [base.submit([3, 1, 4, 1, 5], max_new_tokens=10),
           base.submit([9, 2, 6], max_new_tokens=10)]
    want = [base.result(i) for i in ids]

    eng = _engine()
    i0 = eng.submit([3, 1, 4, 1, 5], max_new_tokens=10)
    i1 = eng.submit([9, 2, 6], max_new_tokens=10)
    for _ in range(20):
        if eng.sched.idle():
            break
        eng.step()
        eng.defrag()                            # defrag EVERY step
        eng.check_tables()
    assert [eng.requests[i0].tokens, eng.requests[i1].tokens] == want


# ---------------------------------------------------------------------------
# Paged attention: bitwise vs dense, allclose vs reference
# ---------------------------------------------------------------------------

_LAYER = 1      # the layer the readers are asked for; 0 and 2 hold decoys


def _paged_setup(seed=7, B=3, HD=8, BS=4, NBLK=5, NPOOL=32, quant=None):
    """Three-layer pools from ``make_pools``, filled through
    ``write_prefill``: layer ``_LAYER`` holds ``kd``/``vd`` behind the
    tables, the other layers hold other values in the same slots, so a
    reader that ignores the layer it is given fails every comparison."""
    rng = np.random.RandomState(seed)
    q = rng.randn(B, H, HD).astype(np.float32)
    kd = rng.randn(B, NBLK * BS, H, HD).astype(np.float32)
    vd = rng.randn(B, NBLK * BS, H, HD).astype(np.float32)
    lengths = np.array([18, 5, 11], np.int32)
    perm = rng.permutation(np.arange(1, NPOOL))[:B * NBLK].reshape(B, NBLK)
    perm = perm.astype(np.int32)
    kp, vp = kvcache.make_pools(3, NPOOL, BS, H, HD, quant=quant)
    full = jnp.int32(NBLK * BS)
    for layer in range(3):
        for b in range(B):
            ks, vs = ((kd[b], vd[b]) if layer == _LAYER else
                      (rng.randn(*kd[b].shape).astype(np.float32),
                       rng.randn(*vd[b].shape).astype(np.float32)))
            kp = kvcache.write_prefill(kp, layer, jnp.asarray(ks),
                                       jnp.asarray(perm[b]), full)
            vp = kvcache.write_prefill(vp, layer, jnp.asarray(vs),
                                       jnp.asarray(perm[b]), full)
    return q, kd, vd, kp, vp, perm, lengths, BS


def _softmax_reference(qrow, k, v):
    s = np.einsum("hd,lhd->hl", qrow, k) / np.sqrt(qrow.shape[-1])
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("hl,lhd->hd", p, v)


def test_paged_vs_dense_bitwise():
    q, kd, vd, kp, vp, tables, lengths, BS = _paged_setup()
    paged = np.asarray(kvcache.paged_attention(
        jnp.asarray(q), kp, vp, _LAYER, jnp.asarray(tables),
        jnp.asarray(lengths)))
    dense = np.asarray(kvcache.dense_attention(
        jnp.asarray(q), jnp.asarray(kd), jnp.asarray(vd),
        jnp.asarray(lengths), block_size=BS))
    assert (paged == dense).all()               # bitwise: paging is a gather


def test_paged_attention_matches_softmax_reference():
    q, kd, vd, kp, vp, tables, lengths, BS = _paged_setup()
    paged = np.asarray(kvcache.paged_attention(
        jnp.asarray(q), kp, vp, _LAYER, jnp.asarray(tables),
        jnp.asarray(lengths)))
    for b in range(q.shape[0]):
        L = int(lengths[b])
        ref = _softmax_reference(q[b], kd[b, :L], vd[b, :L])
        np.testing.assert_allclose(paged[b], ref, rtol=1e-5, atol=1e-6)


def _read(reader, q, kp, vp, layer, tables, lengths):
    """One decode position a row through any reader: ``[B, H, hd]``."""
    q, tables, lengths = (jnp.asarray(x) for x in (q, tables, lengths))
    if reader == "verify":          # a window of one, lengths before it
        return np.asarray(kvcache.paged_verify_attention(
            q[:, None], kp, vp, layer, tables, lengths - 1))[:, 0]
    if reader == "prefill":         # a chunk of one, row by row
        return np.stack([np.asarray(kvcache.paged_prefill_attention(
            q[b][None], kp, vp, layer, tables[b], lengths[b] - 1,
            lengths[b]))[0] for b in range(q.shape[0])])
    return np.asarray(kvcache.paged_attention(
        q, kp, vp, layer, tables, lengths, impl=reader))


_READERS = ("scan", "dense", "flash_interpret", "prefill", "verify")


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("reader", _READERS)
def test_every_reader_reads_the_layer_it_is_given(reader, quant):
    """The pools are handed over whole with the layer beside them: a
    reader that ignored the layer would return layer 0's decoys."""
    q, kd, vd, kp, vp, tables, lengths, BS = _paged_setup(quant=quant)
    got = _read(reader, q, kp, vp, _LAYER, tables, lengths)
    tol = dict(rtol=1e-5, atol=1e-6) if quant is None else dict(atol=0.05)
    for b in range(q.shape[0]):
        L = int(lengths[b])
        np.testing.assert_allclose(
            got[b], _softmax_reference(q[b], kd[b, :L], vd[b, :L]), **tol)
    for other in (0, 2):
        decoy = _read(reader, q, kp, vp, other, tables, lengths)
        assert np.abs(decoy - got).max() > 0.1


@pytest.mark.parametrize("quant", [None, "fp8"])
@pytest.mark.parametrize("reader", ("scan", "dense", "flash_interpret"))
def test_compact_and_scrub_between_two_decode_steps(reader, quant):
    """Defragmentation and the NaN scrub act on the stored form without
    knowing it (axis 1 is the slot, whatever lies behind): a decode
    step, then ``compact_pool`` + ``scrub_blocks`` of the vacated slots,
    then another decode step reads bit for bit what an undisturbed pool
    gives."""
    q, kd, vd, kp, vp, tables, lengths, BS = _paged_setup(quant=quant)
    rng = np.random.RandomState(23)
    B = q.shape[0]

    def decode_step(kp, vp, tables, lengths):
        """Append one position a row to every layer, read ``_LAYER``."""
        slots = jnp.asarray(tables[np.arange(B), lengths // BS])
        offs = jnp.asarray(lengths % BS)
        active = jnp.ones((B,), bool)
        for layer in range(3):
            k, v = (jnp.asarray(rng.randn(B, H, q.shape[-1])
                                .astype(np.float32)) for _ in range(2))
            kp = kvcache.write_decode(kp, layer, k, slots, offs, active)
            vp = kvcache.write_decode(vp, layer, v, slots, offs, active)
        out = _read(reader, q, kp, vp, _LAYER, tables, lengths + 1)
        return kp, vp, out

    kp, vp, first = decode_step(kp, vp, tables, lengths)
    state = rng.get_state()
    _, _, want = decode_step(kp, vp, tables, lengths + 1)

    # move every live slot to the low end, as BlockAllocator.defrag would
    live = sorted(int(x) for x in np.unique(tables))
    mapping = {old: new for new, old in enumerate(live, start=1)
               if old != new}
    vacated = sorted(set(mapping) - set(mapping.values()))
    assert mapping and vacated
    kp, vp = (kvcache.scrub_blocks(kvcache.compact_pool(p, mapping), vacated)
              for p in (kp, vp))
    moved = np.vectorize(lambda x: mapping.get(int(x), int(x)))(tables)
    for pool in (kp, vp):
        for leaf in jax.tree_util.tree_leaves(pool):
            assert not np.asarray(leaf[:, np.asarray(vacated)]
                                  .astype(jnp.float32)).any()
    np.testing.assert_array_equal(
        _read(reader, q, kp, vp, _LAYER, moved, lengths + 1), first)
    rng.set_state(state)
    _, _, got = decode_step(kp, vp, moved.astype(np.int32), lengths + 1)
    np.testing.assert_array_equal(got, want)


def _rows(states):
    """States as a pool stores them: the heads side by side."""
    return np.asarray(states).reshape(states.shape[:-2] + (-1,))


@pytest.mark.parametrize("layer", [0, 1])
def test_write_prefill_pads_to_trash(layer):
    pool, _ = kvcache.make_pools(2, 6, 4, H, 2)  # BS=4
    assert pool.shape == (2, 6, 4, H * 2)        # the stored form
    states = jnp.arange(8 * H * 2, dtype=jnp.float32).reshape(8, H, 2) + 1
    table = jnp.asarray([2, 5, 0, 0], jnp.int32)
    out = np.asarray(kvcache.write_prefill(pool, layer, states, table,
                                           jnp.int32(6)))
    np.testing.assert_array_equal(out[layer, 2], _rows(states[:4]))
    np.testing.assert_array_equal(out[layer, 5, :2], _rows(states[4:6]))
    assert not out[layer, 5, 2:].any()           # padded tail never lands
    assert not out[layer, [1, 3, 4]].any()       # untouched slots stay zero
    assert not out[1 - layer, 1:].any()          # nor the other layer


# ---------------------------------------------------------------------------
# Incremental decode vs teacher-forced forward
# ---------------------------------------------------------------------------

def test_decode_dense_matches_teacher_forced():
    """Stepwise decode over a dense cache reproduces the full causal
    forward position by position (the correctness anchor tying the
    serving math to the training graph)."""
    jp = {k: jnp.asarray(v) for k, v in _PARAMS.items()}
    toks = np.array([[7, 3, 11, 2, 9, 1, 30, 12]], np.int32)
    full_logits, _, _ = transformer_lm_prefill(jp, jnp.asarray(toks),
                                               heads=H)
    hd = D // H
    kc = jnp.zeros((NL, 1, 8, H, hd))
    vc = jnp.zeros((NL, 1, 8, H, hd))
    for t in range(8):
        logits, kc, vc = transformer_lm_decode_dense(
            jp, jnp.asarray(toks[:, t]), jnp.asarray([t], jnp.int32),
            kc, vc, heads=H)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(full_logits[0, t]),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# Scheduler policy units
# ---------------------------------------------------------------------------

def test_scheduler_fifo_and_queue_cap():
    s = Scheduler(max_batch=2, max_queue=3)
    reqs = [Request(prompt=[1]) for _ in range(3)]
    for i, r in enumerate(reqs):
        s.submit(r, now=float(i))
    with pytest.raises(MXNetError, match="queue full"):
        s.submit(Request(prompt=[1]), now=9.0)
    admitted = s.admit(lambda r: True, now=10.0)
    assert admitted == reqs[:2]                 # FIFO, capped at max_batch
    assert [r.state for r in admitted] == [ACTIVE, ACTIVE]
    assert s.queue == [reqs[2]]
    s.finish(admitted[0], "length")
    assert s.admit(lambda r: True, now=11.0) == [reqs[2]]


def test_scheduler_slo_jump():
    s = Scheduler(max_batch=1, max_queue=8, slo_admit_frac=0.5)
    plain = Request(prompt=[1])                  # no SLO: never jumps
    slo = Request(prompt=[2], slo_ms=100.0)
    s.submit(plain, now=0.0)
    s.submit(slo, now=0.01)
    # early: SLO budget barely consumed -> FIFO order holds
    assert s.admission_order(now=0.02) == [plain, slo]
    # 60ms waited out of a 100ms budget -> at risk, jumps the queue
    assert s.admission_order(now=0.07) == [slo, plain]
    assert s.admit(lambda r: True, now=0.07) == [slo]
    # tighter slack sorts first among at-risk peers
    s2 = Scheduler(max_batch=4, max_queue=8)
    a = Request(prompt=[1], slo_ms=200.0)
    b = Request(prompt=[2], slo_ms=100.0)
    s2.submit(a, now=0.0)
    s2.submit(b, now=0.0)
    assert s2.admission_order(now=0.09) == [b, a]


def test_scheduler_no_head_of_line_skip():
    s = Scheduler(max_batch=4, max_queue=8)
    big = Request(prompt=[1] * 10)
    small = Request(prompt=[2])
    s.submit(big, now=0.0)
    s.submit(small, now=0.1)
    # big can't be placed -> admission stops; small must NOT jump it
    assert s.admit(lambda r: len(r.prompt) < 5, now=1.0) == []
    assert [r.state for r in (big, small)] == [QUEUED, QUEUED]


# ---------------------------------------------------------------------------
# Continuous batching: token-for-token parity
# ---------------------------------------------------------------------------

_PROMPTS = [[1, 2, 3], [10, 11, 12, 13, 14, 15], [20, 21], [30, 31, 32, 33]]
_KW = [dict(max_new_tokens=10, seed=101),
       dict(max_new_tokens=8, temperature=0.9, top_k=7, seed=202),
       dict(max_new_tokens=12, seed=303),
       dict(max_new_tokens=6, temperature=1.3, seed=404)]


def _alone_outputs():
    outs = []
    for p, k in zip(_PROMPTS, _KW):
        e = _engine()
        outs.append(e.result(e.submit(p, **k)))
    return outs


def test_continuous_batching_token_parity():
    """The headline acceptance: requests decoded inside a full
    continuously-batched engine emit exactly the tokens they emit when
    served alone — greedy and seeded-sampled rows alike."""
    alone = _alone_outputs()
    eng = _engine()
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    assert [eng.result(i) for i in ids] == alone


def test_mid_flight_admit_evict_token_parity():
    """Admission/eviction mid-decode (the continuous part of continuous
    batching) must not perturb in-flight rows: stagger submissions so
    the batch composition changes while request 0 decodes; the shorter
    requests also finish (evict) at different steps."""
    alone = _alone_outputs()
    eng = _engine()
    i0 = eng.submit(_PROMPTS[0], **_KW[0])
    for _ in range(3):
        eng.step()                               # r0 mid-generation
    i1 = eng.submit(_PROMPTS[1], **_KW[1])
    for _ in range(2):
        eng.step()
    i2 = eng.submit(_PROMPTS[2], **_KW[2])
    i3 = eng.submit(_PROMPTS[3], **_KW[3])
    eng.run()
    assert [eng.requests[i].tokens for i in (i0, i1, i2, i3)] == alone
    assert all(eng.requests[i].state == FINISHED
               for i in (i0, i1, i2, i3))
    assert eng.alloc.num_used == 0               # every block came home


def test_preemption_token_parity():
    """A pool too small for the full batch forces recompute-preemption;
    preempted requests restart and still produce their exact stream
    (position-keyed sampling + deterministic allocator)."""
    alone = _alone_outputs()
    # 9 usable blocks of 4 = 36 entries; the four requests need up to
    # 13+14+16+10 entries -> preemption must kick in
    eng = _engine(num_blocks=10, max_batch=4)
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    outs = [eng.result(i) for i in ids]
    assert outs == alone
    assert telemetry.snapshot_flat().get("serve.preemptions", 0) > 0
    assert eng.alloc.num_used == 0


def test_admit_pass_never_overcommits_pool():
    """Two requests accepted in the same admit pass must not jointly
    claim more KV blocks than are free: the admission gate reserves
    tentatively, so the second stays QUEUED instead of crashing
    ``step()`` with 'kv pool exhausted' mid-prefill."""
    # 3 usable blocks of 4; each prompt needs 2 blocks at prefill
    eng = _engine(num_blocks=4, max_batch=4)
    a = eng.submit([1, 2, 3, 4, 5], max_new_tokens=3)
    b = eng.submit([6, 7, 8, 9, 10], max_new_tokens=3)
    eng.step()                                   # must not raise
    assert eng.requests[a].state == ACTIVE
    assert eng.requests[b].state == QUEUED       # deferred, not crashed
    eng.run()
    assert eng.requests[a].state == FINISHED
    assert eng.requests[b].state == FINISHED
    assert eng.alloc.num_used == 0


def test_reprefill_after_preemption_has_bucket():
    """A preempted request re-prefills with prompt + generated tokens,
    which can exceed ``max_prompt_len``; the prefill ladder is built to
    ``max_seq_len`` so the re-admission still finds a bucket — and the
    replayed stream is exact."""
    ref_eng = _engine()
    ref = ref_eng.result(
        ref_eng.submit(list(range(1, 17)), max_new_tokens=12))
    eng = _engine()
    rid = eng.submit(list(range(1, 17)), max_new_tokens=12)
    for _ in range(6):
        eng.step()
    req = eng.requests[rid]
    assert len(req.seed_tokens) > eng.config.max_prompt_len
    eng._preempt(req)                            # force recompute-restart
    assert eng.result(rid) == ref


# ---------------------------------------------------------------------------
# Zero traces after warmup; warm restart
# ---------------------------------------------------------------------------

def test_zero_trace_warm_cycle():
    eng = _engine()
    eng.warmup()
    snap = dict(eng.trace_counts)
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    eng.run()                                    # admit -> decode -> evict
    assert all(eng.requests[i].done() for i in ids)
    assert dict(eng.trace_counts) == snap        # ZERO new traces
    eng2 = _engine()                             # warm restart, same config
    rid = eng2.submit(_PROMPTS[0], **_KW[0])
    eng2.result(rid)
    assert dict(eng2.trace_counts) == {}         # never traced at all
    assert eng2.aot_stats.get("compile", 0) == 0
    infos = eng2.warmup()
    assert all(i["source"] in ("memory", "disk", "ready") for i in infos)


def test_decode_bucket_ladder_selects_smallest():
    eng = _engine(decode_buckets=(1, 2, 4))
    eng.warmup()
    snap = dict(eng.trace_counts)
    used = []
    for pk, prog in list(eng._programs.items()):
        eng._programs[pk] = (
            lambda k, p: lambda *a: (used.append(k), p(*a))[1])(pk, prog)
    rid = eng.submit([5, 6, 7], max_new_tokens=3)
    eng.result(rid)
    # a single active request must run the 1-slot program
    assert {k for k in used if k[0] == "decode"} == {("decode", 1)}
    assert dict(eng.trace_counts) == snap        # AOT, no retrace
    assert telemetry.snapshot_flat().get("serve.tokens_total") == 3
    used.clear()
    for p in _PROMPTS[:3]:
        eng.submit(p, max_new_tokens=3)
    eng.run()
    # three concurrent rows round up to the 4-slot bucket
    assert ("decode", 4) in used
    with pytest.raises(MXNetError):
        EngineConfig(heads=H, max_batch=8,
                     decode_buckets=(1, 2)).resolved_decode_buckets()


# ---------------------------------------------------------------------------
# Cancel / streaming / validation / telemetry
# ---------------------------------------------------------------------------

def test_cancel_mid_generation():
    eng = _engine()
    rid = eng.submit([1, 2, 3, 4], max_new_tokens=30)
    for _ in range(4):
        eng.step()
    produced = len(eng.requests[rid].tokens)
    assert 0 < produced < 30
    eng.cancel(rid)
    eng.step()
    req = eng.requests[rid]
    assert req.state == CANCELLED and req.finish_reason == "cancelled"
    assert len(req.tokens) == produced           # nothing after cancel
    assert req.blocks == [] and eng.alloc.num_used == 0
    # cancelling a queued request removes it before it ever runs
    eng2 = _engine(max_batch=1)
    a = eng2.submit([1], max_new_tokens=4)
    b = eng2.submit([2], max_new_tokens=4)
    eng2.cancel(b)
    eng2.run()
    assert eng2.requests[b].state == CANCELLED
    assert eng2.requests[b].tokens == []
    assert eng2.requests[a].state == FINISHED


def test_stream_yields_incrementally():
    eng = _engine()
    rid = eng.submit([4, 5], max_new_tokens=5)
    got = list(eng.stream(rid))
    assert got == eng.requests[rid].tokens and len(got) == 5


def test_submit_validation():
    eng = _engine(max_queue=2)
    with pytest.raises(MXNetError, match="empty"):
        eng.submit([])
    with pytest.raises(MXNetError, match="exceeds max_prompt_len"):
        eng.submit(list(range(17)))
    with pytest.raises(MXNetError, match="exceeds max_seq_len"):
        eng.submit([1], max_new_tokens=1000)
    eng.submit([1])
    eng.submit([2])
    with pytest.raises(MXNetError, match="queue full"):
        eng.submit([3])


def test_eos_finishes_early():
    eng = _engine()
    rid = eng.submit([1, 2, 3], max_new_tokens=30)
    toks = eng.result(rid)
    eos = toks[2]
    eng2 = _engine()
    rid2 = eng2.submit([1, 2, 3], max_new_tokens=30, eos_id=eos)
    toks2 = eng2.result(rid2)
    assert toks2 == toks[:toks.index(eos) + 1]   # stop at FIRST eos
    assert eng2.requests[rid2].finish_reason == "eos"


def test_engine_error_dumps_flight(tmp_path, monkeypatch):
    telemetry.configure(flightrec_dir=str(tmp_path))
    eng = _engine()
    eng.submit([1, 2], max_new_tokens=4)

    def boom():
        raise RuntimeError("injected decode failure")

    monkeypatch.setattr(eng, "_decode_step", boom)
    with pytest.raises(RuntimeError, match="injected"):
        eng.step()
    dumps = glob.glob(str(tmp_path / "*.json"))
    assert dumps, "flight recorder dump expected on engine exception"


def test_serve_telemetry_counters():
    eng = _engine()
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS[:2], _KW[:2])]
    eng.run()
    flat = telemetry.snapshot_flat()
    want = _KW[0]["max_new_tokens"] + _KW[1]["max_new_tokens"]
    assert flat["serve.tokens_total"] == want
    assert flat["serve.prefills"] == 2
    assert flat.get("serve.queue_depth") == 0
    assert flat.get("serve.active_slots") == 0
    assert any(k.startswith("serve.evictions") for k in flat)
    assert any(k.startswith("serve.token_ms") for k in flat)
    assert any(k.startswith("serve.ttft_ms") for k in flat)


# ---------------------------------------------------------------------------
# Weight loading: manifest dir + legacy prefix (shared with predictor)
# ---------------------------------------------------------------------------

def test_engine_from_checkpoint_manifest_and_legacy(tmp_path):
    from mxnet_tpu.predictor import load_weights
    nd_params = {k: mx.nd.array(v) for k, v in _PARAMS.items()}

    mgr = mx.CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save_model(3, _SYM, nd_params, {})
    mgr.close()
    sym, args, aux, meta = load_weights(str(tmp_path / "ckpt"))
    assert meta == {"source_kind": "manifest", "step": 3}
    assert sym is not None and not aux
    cfg = EngineConfig(heads=H, block_size=4, num_blocks=64, max_batch=2,
                       max_prompt_len=16, max_seq_len=48,
                       prompt_bucket_min=8)
    eng = Engine.from_checkpoint(str(tmp_path / "ckpt"), cfg)
    want = eng.result(eng.submit([5, 6, 7], max_new_tokens=4, seed=11))

    prefix = str(tmp_path / "legacy")
    mx.model.save_checkpoint(prefix, 0, _SYM, nd_params, {})
    sym2, args2, _, meta2 = load_weights(prefix, 0)
    assert meta2 == {"source_kind": "legacy", "epoch": 0}
    eng2 = Engine.from_checkpoint(prefix, cfg, epoch=0)
    got = eng2.result(eng2.submit([5, 6, 7], max_new_tokens=4, seed=11))
    assert got == want                           # one loading story
    # .params file path spelling resolves too
    _, args3, _, _ = load_weights(prefix + "-0000.params")
    assert set(args3) == set(_PARAMS)
    with pytest.raises(MXNetError, match="neither"):
        load_weights(str(tmp_path / "nope"))


def test_predictor_create_from_manifest_with_aot(tmp_path):
    """Satellite: predictor accepts a CheckpointManager directory and
    routes its forward through the compile cache (AOT warm path)."""
    from mxnet_tpu import predictor as pred
    nd_params = {k: mx.nd.array(v) for k, v in _PARAMS.items()}
    mgr = mx.CheckpointManager(str(tmp_path / "ckpt"))
    mgr.save_model(1, _SYM, nd_params, {})
    mgr.close()
    shapes = {"data": (1, 8), "softmax_label": (1, 8)}
    p = pred.create(str(tmp_path / "ckpt"), input_shapes=shapes)
    assert p.aot_info and p.aot_info[0]["kind"] == "fwd_False"
    assert p.aot_info[0]["source"] in ("compile", "memory", "disk")
    stats = p.cache_stats()
    assert stats["puts"] + stats["memory_hits"] + stats["disk_hits"] >= 1
    toks = np.array([[7, 3, 11, 2, 9, 1, 30, 12]], np.int32)
    (probs,) = p.predict(data=toks)
    # the predictor's AOT forward is the same math the decode head
    # mirrors: argmax chains agree with the functional prefill
    jp = {k: jnp.asarray(v) for k, v in _PARAMS.items()}
    logits, _, _ = transformer_lm_prefill(jp, jnp.asarray(toks), heads=H)
    np.testing.assert_allclose(
        probs.reshape(8, V),
        np.asarray(jax.nn.softmax(logits[0], axis=-1)), rtol=1e-5,
        atol=1e-6)
    # a second predictor re-attaches warm (memory hit, no new compile)
    p2 = pred.create(str(tmp_path / "ckpt"), input_shapes=shapes)
    assert p2.aot_info[0]["source"] in ("memory", "disk")


# ---------------------------------------------------------------------------
# Round 12: chunked prefill, fp8 KV pools, decode-attention impls
# ---------------------------------------------------------------------------

def test_chunked_prefill_matches_unchunked():
    """Chunked prompt ingestion is a pure scheduling change: every
    request emits token-for-token what the whole-prompt engine emits —
    greedy and seeded-sampled rows alike, prompts spanning 1..2 chunks
    and a mid-chunk tail."""
    alone = _alone_outputs()
    eng = _engine(prefill_chunk=4)
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    assert [eng.result(i) for i in ids] == alone


def test_chunked_prefill_batched_vs_alone():
    chunked_alone = []
    for p, k in zip(_PROMPTS, _KW):
        e = _engine(prefill_chunk=4)
        chunked_alone.append(e.result(e.submit(p, **k)))
    assert chunked_alone == _alone_outputs()
    eng = _engine(prefill_chunk=4)
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    assert [eng.result(i) for i in ids] == chunked_alone


def test_chunked_ladder_collapses_to_two_programs():
    """The whole geometric prompt ladder becomes ONE chunk shape: a
    warmed chunked engine holds exactly two programs — the chunk and
    the decode bucket."""
    eng = _engine(prefill_chunk=8)
    assert eng.prompt_buckets == (8,)
    eng.warmup()
    assert sorted(eng._programs) == [("decode", 4), ("prefill_chunk", 8)]
    ladder = _engine()
    assert len(ladder.prompt_buckets) > 1         # the r10 ladder


def test_chunked_zero_trace_warm_cycle():
    eng = _engine(prefill_chunk=4)
    eng.warmup()
    snap = dict(eng.trace_counts)
    ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
    eng.run()
    assert all(eng.requests[i].done() for i in ids)
    assert dict(eng.trace_counts) == snap         # ZERO new traces
    assert eng.alloc.num_used == 0


def test_chunked_mid_prefill_preemption_replay():
    """Preempting a request while only part of its prompt is ingested
    must reset the chunk cursor: on re-admission it re-chunks from
    position 0 and still replays its exact stream."""
    prompts = [list(range(1, 15)), list(range(20, 30))]
    kws = [dict(max_new_tokens=8, temperature=0.8, seed=55),
           dict(max_new_tokens=6, seed=66)]
    refs = []
    for p, k in zip(prompts, kws):
        e = _engine(prefill_chunk=4)
        refs.append(e.result(e.submit(p, **k)))
    eng = _engine(prefill_chunk=4)
    a = eng.submit(prompts[0], **kws[0])
    b = eng.submit(prompts[1], **kws[1])
    eng.step()     # nothing decodable: pump drains A's prompt fully
    eng.step()     # A decodes; strict pump lands ONE chunk of B
    req_b = eng.requests[b]
    assert 0 < req_b.prefilled < req_b.prefill_target   # mid-prefill
    eng._preempt(req_b)
    assert req_b.prefilled == 0 and req_b.prefill_target == 0
    eng.run()
    assert [eng.requests[a].tokens, eng.requests[b].tokens] == refs


def test_fp8_kv_engine_replay_and_greedy_parity():
    """fp8-quantized pools serve deterministically (same tokens on
    every run) and, at this scale, greedily match the f32 engine."""
    runs = []
    for _ in range(2):
        eng = _engine(prefill_chunk=4, kv_quant="fp8")
        ids = [eng.submit(p, **k) for p, k in zip(_PROMPTS, _KW)]
        runs.append([eng.result(i) for i in ids])
    assert runs[0] == runs[1]
    f32 = _engine()
    greedy = [i for i, k in enumerate(_KW) if "temperature" not in k]
    refs = [f32.result(f32.submit(_PROMPTS[i], **_KW[i])) for i in greedy]
    assert [runs[0][i] for i in greedy] == refs


def test_fp8_kv_logit_error_bound():
    """Accuracy contract: attention read from an fp8 pool stays within
    a small bound of the f32-pool read (per-block e4m3 scales)."""
    from mxnet_tpu.quant import rowwise_quantize
    q, kd, vd, kp, vp, tables, lengths, BS = _paged_setup()
    f32 = np.asarray(kvcache.paged_attention(
        jnp.asarray(q), kp, vp, _LAYER, jnp.asarray(tables),
        jnp.asarray(lengths), impl="dense"))
    # the same values through the quantizing writer: one e4m3 payload
    # row and one scale a position
    _, _, _, kq, vq, _, _, _ = _paged_setup(quant="fp8")
    assert kq.payload.dtype == jnp.float8_e4m3fn
    np.testing.assert_array_equal(
        np.asarray(kq.payload[_LAYER, tables[0, 0]]),
        np.asarray(rowwise_quantize(
            jnp.asarray(kd[0, :BS].reshape(BS, -1)), "e4m3")[0]))
    fp8 = np.asarray(kvcache.paged_attention(
        jnp.asarray(q), kq, vq, _LAYER, jnp.asarray(tables),
        jnp.asarray(lengths), impl="dense"))
    assert 0 < np.max(np.abs(fp8 - f32)) < 0.05


def test_fp8_kv_capacity_doubles():
    """The capacity contract: fp8 pools hold the same tokens in less
    than half the bytes, so a fixed byte budget fits 2x the resident
    requests (kv_bytes_per_token is the gauge the engine exports)."""
    hd = D // H
    f32_pools = kvcache.make_pools(NL, 16, 4, H, hd)
    fp8_pools = kvcache.make_pools(NL, 16, 4, H, hd, quant="fp8")
    assert 2 * kvcache.pool_nbytes(*fp8_pools) <= \
        kvcache.pool_nbytes(*f32_pools)
    assert 2 * kvcache.kv_bytes_per_token(NL, H, hd, "fp8") <= \
        kvcache.kv_bytes_per_token(NL, H, hd)


def test_attn_impl_parity():
    """The decode-attention impl knob is numerics-neutral: the one-shot
    dense gather and the interpret-mode flash kernel match the
    reference block scan on the same paged pools."""
    q, kd, vd, kp, vp, tables, lengths, BS = _paged_setup()
    args = (jnp.asarray(q), kp, vp, _LAYER, jnp.asarray(tables),
            jnp.asarray(lengths))
    scan = np.asarray(kvcache.paged_attention(*args, impl="scan"))
    dense = np.asarray(kvcache.paged_attention(*args, impl="dense"))
    flash = np.asarray(kvcache.paged_attention(*args,
                                               impl="flash_interpret"))
    np.testing.assert_allclose(dense, scan, rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(flash, scan, rtol=1e-5, atol=1e-6)
    with pytest.raises(MXNetError):
        kvcache.paged_attention(*args, impl="nope")


def test_scheduler_prefill_backlog_discounts_slack():
    """The r12 scheduler fix: SLO at-risk slack must account for the
    prefill-chunk backlog of already-active requests — wait the queued
    request will certainly absorb before its first token."""
    s = Scheduler(max_batch=2, slo_admit_frac=0.5)
    early = s.submit(Request(prompt=[1]), now=0.0)       # FIFO head
    slo = s.submit(Request(prompt=[2], slo_ms=100.0), now=0.0)
    # 30 ms waited: under the 50 ms jump threshold on its own...
    assert s.admission_order(now=0.030)[0] is early
    # ...but a 25 ms chunk backlog pushes it over -> SLO jump
    assert s.admission_order(now=0.030,
                             prefill_backlog_ms=25.0)[0] is slo
    # admit() honors the same discounted order
    got = s.admit(lambda r: True, now=0.030, prefill_backlog_ms=25.0)
    assert got[0] is slo


def test_engine_prefill_backlog_estimate():
    """The engine's backlog estimate counts remaining chunks of
    mid-prefill requests only, scaled by the EWMA chunk latency."""
    eng = _engine(prefill_chunk=4)
    assert eng._prefill_backlog_ms() == 0.0        # no history, no work
    eng._chunk_ms = 2.0                            # pretend EWMA history
    r = Request(prompt=list(range(9)))
    r.prefilled, r.prefill_target = 1, 9           # ceil(8/4) = 2 chunks
    eng.sched.running.append(r)
    assert eng._prefill_backlog_ms() == pytest.approx(4.0)
    r.prefilled = 9                                # drained -> no backlog
    assert eng._prefill_backlog_ms() == 0.0


def test_chunked_prefill_telemetry():
    """Round-12 telemetry: the chunk counter ticks once per chunk and
    the kv_bytes_per_token gauge is fp8-aware."""
    eng = _engine(prefill_chunk=4, kv_quant="fp8")
    rid = eng.submit(list(range(1, 11)), max_new_tokens=4)
    eng.result(rid)
    flat = telemetry.snapshot_flat()
    assert flat.get("serve.prefill_chunks", 0) >= 3   # ceil(10 / 4)
    assert flat.get("kv_bytes_per_token") == \
        kvcache.kv_bytes_per_token(NL, H, D // H, "fp8")
    assert flat.get("serve.prefills", 0) >= 1         # completion ticks


def test_engine_config_validation_round12():
    with pytest.raises(MXNetError):
        _engine(attn_impl="nope")
    with pytest.raises(MXNetError):
        _engine(kv_quant="int4")
    with pytest.raises(MXNetError):
        _engine(prefill_chunk=-1)
    assert _engine(attn_impl="auto").attn_impl == "dense"  # CPU resolve


# ---------------------------------------------------------------------------
# Round-15 speculative-decode kvcache primitives: windowed write, verify
# attention, rejected-tail scrub (the engine-level contracts live in
# tests/test_speculate.py)
# ---------------------------------------------------------------------------

def test_write_spec_and_scrub_positions_roundtrip():
    """write_spec lands a [B, C] window of positions; scrub_positions
    zeroes exactly the rejected tail and leaves accepted neighbours —
    including entries in the SAME block — untouched."""
    BS, HD = 4, 2
    pool, _ = kvcache.make_pools(1, 6, BS, H, HD)
    rng = np.random.RandomState(3)
    states = jnp.asarray(rng.randn(2, 3, H, HD).astype(np.float32))
    # row 0 writes block 2 offsets 1..3; row 1 straddles blocks 4 -> 5
    slots = jnp.asarray([[2, 2, 2], [4, 4, 5]], jnp.int32)
    offs = jnp.asarray([[1, 2, 3], [2, 3, 0]], jnp.int32)
    out = kvcache.write_spec(pool, 0, states, slots, offs)
    np.testing.assert_array_equal(np.asarray(out[0, 2, 1:4]),
                                  _rows(states[0]))
    np.testing.assert_array_equal(np.asarray(out[0, 4, 2:4]),
                                  _rows(states[1, :2]))
    np.testing.assert_array_equal(np.asarray(out[0, 5, 0]),
                                  _rows(states[1, 2]))
    # scrub row 0's last two positions and row 1's last one (kept
    # positions redirect to the trash block, the engine's convention)
    sslots = jnp.asarray([[TRASH_BLOCK, 2, 2],
                          [TRASH_BLOCK, TRASH_BLOCK, 5]], jnp.int32)
    scrubbed = kvcache.scrub_positions(out, sslots, offs)
    assert not np.asarray(scrubbed[0, 2, 2:4]).any()   # rejected tail gone
    assert not np.asarray(scrubbed[0, 5, 0]).any()
    np.testing.assert_array_equal(                      # survivors intact
        np.asarray(scrubbed[0, 2, 1]), _rows(states[0, 0]))
    np.testing.assert_array_equal(
        np.asarray(scrubbed[0, 4, 2:4]), _rows(states[1, :2]))


def test_write_spec_fp8_matches_decode_write():
    """fp8 pools quantize per position (the window is flattened before
    rowwise_quantize), so a C-wide speculative write of one position is
    byte-equal to the 1-wide decode write of the same state — the
    quantization invariant greedy byte-identity rides on."""
    BS, HD = 4, 2
    pool, _ = kvcache.make_pools(1, 6, BS, H, HD, quant="fp8")
    rng = np.random.RandomState(5)
    st = jnp.asarray(rng.randn(1, 3, H, HD).astype(np.float32))
    slots = jnp.asarray([[2, 2, 2]], jnp.int32)
    offs = jnp.asarray([[0, 1, 2]], jnp.int32)
    wide = kvcache.write_spec(pool, 0, st, slots, offs)
    via_decode = pool
    for c in range(3):
        via_decode = kvcache.write_decode(
            via_decode, 0, st[:, c], jnp.asarray([2], jnp.int32),
            jnp.asarray([c], jnp.int32), jnp.asarray([True]))
    np.testing.assert_array_equal(np.asarray(wide.payload[0, 2, :3]),
                                  np.asarray(via_decode.payload[0, 2, :3]))
    np.testing.assert_array_equal(np.asarray(wide.scale[0, 2, :3]),
                                  np.asarray(via_decode.scale[0, 2, :3]))
    # scrub clears payload AND scale
    sslots = jnp.asarray([[TRASH_BLOCK, 2, 2]], jnp.int32)
    scrubbed = kvcache.scrub_positions(wide, sslots, offs)
    assert not np.asarray(scrubbed.payload[0, 2, 1:3]).any()
    assert not np.asarray(scrubbed.scale[0, 2, 1:3]).any()
    assert np.asarray(scrubbed.scale[0, 2, 0]) == \
        np.asarray(wide.scale[0, 2, 0])


def test_paged_verify_attention_c1_matches_decode():
    """A C=1 verify window reads the cache like the dense decode path
    (same mask, same f32 softmax math; XLA schedules the extra window
    axis' gemm differently, so equality is to ulps, not bits — the
    engine's stream-level greedy byte-identity is pinned in
    tests/test_speculate.py)."""
    q, kd, vd, kp, vp, tables, lengths, BS = _paged_setup()
    ref = np.asarray(kvcache.paged_attention(
        jnp.asarray(q), kp, vp, _LAYER, jnp.asarray(tables),
        jnp.asarray(lengths), impl="dense"))
    ver = np.asarray(kvcache.paged_verify_attention(
        jnp.asarray(q)[:, None], kp, vp, _LAYER,
        jnp.asarray(tables), jnp.asarray(lengths) - 1))
    np.testing.assert_allclose(ver[:, 0], ref, rtol=1e-6, atol=1e-6)


def test_paged_verify_attention_matches_reference():
    """Each window position c attends over cache positions
    0..lengths+c (causal within the window) — checked against a plain
    softmax reference."""
    q, kd, vd, kp, vp, tables, lengths, BS = _paged_setup()
    C = 3
    rng = np.random.RandomState(11)
    qw = rng.randn(q.shape[0], C, H, q.shape[-1]).astype(np.float32)
    base = lengths - C                 # cache holds the window's K/V too
    ver = np.asarray(kvcache.paged_verify_attention(
        jnp.asarray(qw), kp, vp, _LAYER,
        jnp.asarray(tables), jnp.asarray(base)))
    for b in range(q.shape[0]):
        for c in range(C):
            L = int(base[b]) + c + 1
            ref = _softmax_reference(qw[b, c], kd[b, :L], vd[b, :L])
            np.testing.assert_allclose(ver[b, c], ref, rtol=1e-5,
                                       atol=1e-6)
