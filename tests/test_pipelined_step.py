"""The one-deep decode pipeline (docs/serving.md "The step contract"):
``Engine.step()`` queues the next decode program before it reads the
tokens of the one in flight, and a row's input token goes from one step
to the next on the device.  On tiny models of all four cache kinds
(``paged_kv``, ``recurrent_state``, ``paged_latent``, ``paged_window``)
on the CPU:

* streams are token-for-token those of an unpipelined execution of the
  same requests (every step read before the next is built), greedy and
  sampled, with an EOS mid-stream, a cancel between calls, a preemption
  under a tiny pool and a NaN-poisoned step;
* in the span tree the next step's ``serve.dispatch[decode]`` opens
  before the last step's ``serve.fetch`` closes, but where a prefill
  chunk ran (it is read alone, after the step in flight);
* ``serve.pipeline.overlapped``, ``serve.pipeline.drains{reason=...}``
  and ``serve.pipeline.discarded_rows`` count what happened.
"""
import importlib.util
import math
import os

import numpy as np
import pytest

import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.chaos import ChaosSpec
from mxnet_tpu.models.transformer import transformer_lm
from mxnet_tpu.serve import Engine, EngineConfig
from mxnet_tpu.telemetry import tracing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference(name):
    spec = importlib.util.spec_from_file_location(
        f"{name}_reference",
        os.path.join(REPO, "benchmark", "reference", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _lm_params():
    rng = np.random.RandomState(0)
    sym = transformer_lm(vocab_size=61, num_layers=2, d_model=32, heads=4,
                         batch_size=1, seq_len=8)
    shapes, _, _ = sym.infer_shape(data=(1, 8), softmax_label=(1, 8))
    return {n: (rng.randn(*s) * 0.05).astype(np.float32)
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}


def _brumby():
    cfg = dict(vocab_size=96, num_hidden_layers=2, hidden_size=64,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               intermediate_size=128)
    model = dict(kv_heads=2, head_dim=16, norm="rmsnorm", norm_eps=1e-6,
                 qk_norm=True, bias=False, ffn="silu_gated", position="rope",
                 rope_theta=1e6, attention="power_retention")
    params = _reference("brumby").init_params(3, cfg, jnp.float32, std=0.2)
    return params, dict(model=model, num_blocks=5, prefill_chunk=8)


def _deepseek():
    ref = _reference("deepseek_v2")
    cfg = dict(vocab_size=96, num_hidden_layers=3, hidden_size=64,
               num_attention_heads=4, q_lora_rank=24, kv_lora_rank=32,
               qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
               intermediate_size=128, moe_intermediate_size=32,
               n_routed_experts=4, n_shared_experts=2,
               first_k_dense_replace=1,
               published=dict(n_routed_experts=16),
               deployment_share=dict(first_expert=0))
    model = dict(norm="rmsnorm", norm_eps=1e-6, bias=False, ffn="silu_gated",
                 position="rope", rope_theta=10000.0, attention="latent",
                 q_lora_rank=24, kv_lora_rank=32, qk_nope_head_dim=16,
                 qk_rope_head_dim=8, v_head_dim=16,
                 rope_scaling=dict(ref.PUBLISHED["rope_scaling"]),
                 ffn_layers=["dense", "routed", "routed"],
                 n_routed_experts=16, experts_per_token=3, n_group=4,
                 topk_group=2, routed_scaling_factor=4.0,
                 experts_held=[0, 4])
    params = ref.init_params(3, cfg, jnp.float32, std=0.2)
    return params, dict(model=model, block_size=4, num_blocks=48,
                        prefill_chunk=8)


def _trinity():
    kinds = ["sliding_attention"] * 3 + ["full_attention"]
    cfg = dict(vocab_size=96, num_hidden_layers=4, hidden_size=32,
               num_attention_heads=4, num_key_value_heads=2, head_dim=16,
               intermediate_size=64, moe_intermediate_size=16, num_experts=4,
               published=dict(num_experts=16), num_shared_experts=1,
               num_dense_layers=1, layer_types=kinds, sliding_window=16,
               deployment_share=dict(first_expert=0))
    model = dict(kv_heads=2, head_dim=16, norm="rmsnorm", norm_eps=1e-5,
                 qk_norm=True, bias=False, ffn="silu_gated",
                 position="rope_sliding", rope_theta=10000.0,
                 attention=["sliding"] * 3 + ["softmax"], sliding_window=16,
                 attn_gate=True, sandwich_norm=True, embed_scale=math.sqrt(32),
                 ffn_layers=["dense", "routed", "routed", "routed"],
                 n_routed_experts=16, experts_per_token=4,
                 routed_scaling_factor=2.448, norm_topk_prob=True,
                 experts_held=[0, 4], score_func="sigmoid", router_bias=True)
    params = _reference("trinity").init_params(3, cfg, jnp.float32, std=0.2)
    return params, dict(model=model, block_size=4, num_blocks=80,
                        prefill_chunk=8)


_MAKERS = {"paged_kv": lambda: (_lm_params(), dict(block_size=4,
                                                     num_blocks=64,
                                                     prefill_chunk=4)),
           "recurrent_state": _brumby, "paged_latent": _deepseek,
           "paged_window": _trinity}
KINDS = sorted(_MAKERS)
_MADE = {}


def _engine(kind, chaos=None, **over):
    if kind not in _MADE:
        _MADE[kind] = _MAKERS[kind]()
    params, cfg = _MADE[kind]
    cfg = dict(dict(heads=4, max_batch=4, max_prompt_len=20, max_seq_len=48,
                    attn_impl="dense"), **cfg)
    cfg.update(over)
    return Engine(params, EngineConfig(**cfg),
                  chaos=chaos if chaos is not None else ChaosSpec({}))


def _vocab(kind):
    return 61 if kind == "paged_kv" else 96


def _prompts(kind, n=4, seed=5):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, _vocab(kind), int(k)).tolist()
            for k in rng.randint(3, 19, n)]


_KW = [dict(max_new_tokens=9, seed=11),
       dict(max_new_tokens=7, temperature=0.8, top_k=7, seed=12),
       dict(max_new_tokens=12, seed=13),
       dict(max_new_tokens=5, temperature=1.1, seed=14)]


@pytest.fixture(autouse=True)
def _fresh_telemetry():
    telemetry.reset_for_tests()
    yield
    telemetry.reset_for_tests()


def _submit(eng, prompts, kws):
    return [eng.submit(p, **k) for p, k in zip(prompts, kws)]


def _run_unpipelined(eng, limit=500):
    """Every step's tokens read before the next step is built: the
    engine drained after each call, as an external call would."""
    for _ in range(limit):
        if eng.sched.idle():
            return
        eng.step()
        eng._drain("test")
    raise AssertionError("engine did not finish")


def _streams(eng, ids):
    return [(eng.request(i).state, list(eng.request(i).tokens)) for i in ids]


def _count(name, **labels):
    return telemetry.registry().counter(name).value(**labels)


# -- the same streams --------------------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_streams_equal_an_unpipelined_run(kind):
    """Greedy and sampled rows, one of them ending at an EOS it samples
    mid-stream: the pipelined engine emits what the unpipelined one
    does, nothing after the EOS, and throws away the row-step computed
    past it."""
    prompts = _prompts(kind)
    plain = _engine(kind)
    ids = _submit(plain, prompts, _KW)
    _run_unpipelined(plain)
    want = _streams(plain, ids)
    assert _count("serve.pipeline.overlapped") == 0
    # an EOS mid-stream: the first decoded token (not a prompt's first,
    # not a request's last) that its request had not sampled before
    i, at = next((i, k) for i, (_, toks) in enumerate(want)
                 for k in range(1, len(toks) - 1) if toks[k] not in toks[:k])
    toks = want[i][1]
    kws = list(_KW)
    kws[i] = dict(kws[i], eos_id=toks[at])
    want[i] = ("finished", toks[:at + 1])

    telemetry.reset_for_tests()
    eng = _engine(kind)
    ids = _submit(eng, prompts, kws)
    eng.run()
    assert _streams(eng, ids) == want
    assert eng.request(ids[i]).finish_reason == "eos"
    assert eng.alloc.num_used == 0 and eng._flight is None
    eng.check_tables()
    assert _count("serve.pipeline.overlapped") > 0
    # alone, the EOS is read by the step that queued the next one behind
    # it: that row-step is computed and thrown away
    telemetry.reset_for_tests()
    alone = _engine(kind)
    rid = alone.submit(prompts[i], **kws[i])
    alone.run()
    assert alone.request(rid).tokens == want[i][1]
    assert _count("serve.pipeline.discarded_rows") == 1
    assert _count("serve.pipeline.drains", reason="idle") == 1


@pytest.mark.parametrize("kind", KINDS)
def test_cancel_between_calls_freezes_the_stream(kind):
    eng = _engine(kind)
    rid, other = _submit(eng, _prompts(kind, 2), [dict(max_new_tokens=30),
                                                 dict(max_new_tokens=12)])
    for _ in range(6):
        eng.step()
    produced = list(eng.request(rid).tokens)
    assert 0 < len(produced) < 30 and eng._flight is not None
    eng.cancel(rid)
    eng.step()
    req = eng.request(rid)
    assert req.state == "cancelled" and req.tokens == produced
    assert req.blocks == []
    eng.run()
    assert eng.request(rid).tokens == produced
    assert eng.request(other).state == "finished"
    assert eng.alloc.num_used == 0
    assert _count("serve.pipeline.discarded_rows") >= 1


@pytest.mark.parametrize("kind", ["paged_kv", "paged_latent", "paged_window"])
def test_forced_preemption_drains_first(kind):
    """A pool too small for the batch: the growth pass needs a
    preemption, so the step drains before it picks a victim, and the
    preempted request still replays its exact stream."""
    prompts = _prompts(kind, seed=9)
    kws = [dict(k, max_new_tokens=20) for k in _KW]
    plain = _engine(kind)
    ids = _submit(plain, prompts, kws)
    _run_unpipelined(plain)
    want = _streams(plain, ids)

    telemetry.reset_for_tests()
    eng = _engine(kind, num_blocks=13)
    ids = _submit(eng, prompts, kws)
    eng.run()
    assert _streams(eng, ids) == want
    assert _count("serve.preemptions") > 0
    assert _count("serve.pipeline.drains", reason="preempt") > 0
    assert _count("serve.pipeline.overlapped") > 0
    assert eng.alloc.num_used == 0


@pytest.mark.parametrize("kind", KINDS)
def test_poisoned_step_fails_its_rows_and_leaks_nothing(kind):
    """A ``serve_poison_logits`` step runs alone, after the step in
    flight is read: its rows fail, and a request served afterwards on
    the same pools decodes as on a clean engine."""
    prompts = _prompts(kind, 3, seed=3)
    kws = [dict(max_new_tokens=12, seed=21), dict(max_new_tokens=12,
                                                  seed=22)]
    clean = _engine(kind)
    ref = clean.result(clean.submit(prompts[2], max_new_tokens=8, seed=23))

    eng = _engine(kind, chaos=ChaosSpec({"serve_poison_logits": {6}}))
    ids = _submit(eng, prompts[:2], kws)
    eng.run()
    for rid in ids:
        req = eng.request(rid)
        assert req.state == "failed" and req.finish_reason == "error"
        assert 0 < len(req.tokens) < 12
    assert _count("serve.pipeline.drains", reason="chaos") == 1
    assert eng.alloc.num_used == 0
    assert eng.result(eng.submit(prompts[2], max_new_tokens=8,
                                 seed=23)) == ref


# -- the span tree and the counters ------------------------------------------

@pytest.mark.parametrize("kind", KINDS)
def test_next_dispatch_opens_before_the_last_fetch_closes(kind):
    """Every decode step but the first and those of a step that ran a
    prefill chunk is dispatched while the one before it is on the
    device: its ``serve.dispatch`` opens before the ``serve.fetch`` that
    reads the step before closes."""
    eng = _engine(kind)
    eng.warmup()
    tracing.configure(None, enable=True)
    try:
        ids = _submit(eng, _prompts(kind, seed=7),
                      [dict(max_new_tokens=24, seed=s) for s in range(4)])
        eng.run()
    finally:
        tracing.configure(None, enable=False)
    assert all(eng.request(i).state == "finished" for i in ids)
    spans = {ev["args"]["id"]: ev for ev in tracing.tail(10**6)}
    decodes = {ev["args"]["step"]: ev for ev in spans.values()
               if ev["name"] == "serve.decode"}
    dispatch = {spans[ev["args"]["parent"]]["args"]["step"]: ev
                for ev in spans.values() if ev["name"] == "serve.dispatch"
                and ev["args"]["kind"] == "decode"}
    reads = {ev["args"]["step"]: ev for ev in spans.values()
             if ev["name"] == "serve.fetch" and "step" in ev["args"]}
    assert sorted(reads) == sorted(decodes) == sorted(dispatch)
    steps = sorted(decodes)
    overlapped = [s for s in steps if decodes[s]["args"]["overlapped"]]
    # a decode step is queued behind the one before it, but in a step
    # that ran a prefill chunk (read alone, after the step in flight)
    chunked = {ev["args"]["step"] for ev in spans.values()
               if ev["name"] == "serve.step" and ev["args"]["chunk"]}
    assert overlapped == [s for s in steps[1:] if s not in chunked]
    for prev, s in zip(steps, steps[1:]):
        if not decodes[s]["args"]["overlapped"]:
            continue
        d, f = dispatch[s], reads[prev]
        assert d["ts"] < f["ts"] + f["dur"], (prev, s)
        assert reads[s]["ts"] >= d["ts"] + d["dur"]
    assert _count("serve.pipeline.overlapped") == len(overlapped)


def test_counters_count_what_happened():
    """Drains by reason, overlapped steps and discarded rows, in a run
    with an EOS, a cancel, a defrag and a weight swap between calls."""
    eng = _engine("paged_kv")
    prompts = _prompts("paged_kv")
    first = eng.result(eng.submit(prompts[0], max_new_tokens=10, seed=1))
    telemetry.reset_for_tests()
    tracing.configure(None, enable=True)
    try:
        a = eng.submit(prompts[0], max_new_tokens=10, seed=1,
                       eos_id=first[3])
        b = eng.submit(prompts[1], max_new_tokens=20, seed=2)
        c = eng.submit(prompts[2], max_new_tokens=20, seed=3)
        for _ in range(4):
            eng.step()
        eng.defrag()
        eng.step()
        eng.step()
        eng.cancel(c)
        eng.step()
        eng.swap_weights(eng._params)
        eng.run()
    finally:
        tracing.configure(None, enable=False)
    assert eng.request(a).tokens == first[:first.index(first[3]) + 1]
    assert eng.request(b).state == "finished"
    assert eng.request(c).state == "cancelled"
    spans = tracing.tail(10**6)
    decodes = [ev["args"] for ev in spans if ev["name"] == "serve.decode"]
    drains = [ev["args"]["reason"] for ev in spans
              if ev["name"] == "serve.drain"]
    assert _count("serve.pipeline.overlapped") == sum(
        a["overlapped"] for a in decodes)
    # c's step in flight when it was cancelled (and a's step past its EOS,
    # unless a drain read the EOS before the next step was built)
    assert _count("serve.pipeline.discarded_rows") == sum(
        a["discarded"] for a in decodes) >= 1
    assert _count("serve.pipeline.drains", reason="defrag") == 1
    assert _count("serve.pipeline.drains", reason="swap_weights") == 1
    assert sorted(drains) == sorted(
        r for r in ("prefill", "defrag", "swap_weights", "idle", "preempt",
                    "chaos")
        for _ in range(int(_count("serve.pipeline.drains", reason=r))))
