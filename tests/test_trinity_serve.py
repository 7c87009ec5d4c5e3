"""Window and global softmax layers with grouped heads and sigmoid-routed
experts on ``serve.Engine``'s normal path (ISSUE 37): the afmoe block
(48 query heads over 8 in the published model; q/k norms; rotary
positions on the sliding layers only; a sigmoid output gate; sandwich
norms; a scaled embedding; a leading dense layer, then experts chosen by
sigmoid scores plus a selection bias, of which THIS chip holds a share,
beside a shared expert) at a tiny size on the CPU, against the
benchmark's plain float32 reference: window 16, blocks of 4, chunks of
8, sequences of three windows (past the ring's wrap), [s, s, s, f]
layers, 16 experts in 4 shares.  Also the grouped decode kernel against
its XLA reader, the two kinds of table under one allocator, and the
routing's default path against DeepSeek's, bit for bit."""
import importlib.util
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import decoder, experts
from mxnet_tpu.models.decoder import ModelSpec
from mxnet_tpu.serve import Engine, EngineConfig, ServeError, kvcache
from mxnet_tpu.serve import moe_experts
from mxnet_tpu.serve.gqa_decode import gqa_decode

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    """``benchmark/reference/trinity.py`` by path: it imports nothing
    from the program, and the program nothing from it."""
    spec = importlib.util.spec_from_file_location(
        "trinity_reference",
        os.path.join(REPO, "benchmark", "reference", "trinity.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()
V, NL, D, H, KV, HD, F, FE = 96, 4, 32, 4, 2, 16, 64, 16
EXPERTS, HELD, TOPK, SCALE = 16, 4, 4, 2.448
WINDOW, BS, CHUNK = 16, 4, 8
KINDS = ("sliding_attention",) * 3 + ("full_attention",)
SETTINGS = dict(sliding_window=WINDOW, layer_types=KINDS, top_k=TOPK,
                route_scale=SCALE, eps=1e-5, rope_theta=10000.0)


def _cfg(held=HELD, first=0):
    return dict(vocab_size=V, num_hidden_layers=NL, hidden_size=D,
                num_attention_heads=H, num_key_value_heads=KV, head_dim=HD,
                intermediate_size=F, moe_intermediate_size=FE,
                num_experts=held, published=dict(num_experts=EXPERTS),
                num_shared_experts=1, num_dense_layers=1,
                layer_types=list(KINDS), sliding_window=WINDOW,
                deployment_share=dict(first_expert=first))


def _model(first=0, held=HELD, **over):
    m = dict(kv_heads=KV, head_dim=HD, norm="rmsnorm", norm_eps=1e-5,
             qk_norm=True, bias=False, ffn="silu_gated",
             position="rope_sliding", rope_theta=10000.0,
             attention=["sliding"] * 3 + ["softmax"], sliding_window=WINDOW,
             attn_gate=True, sandwich_norm=True, embed_scale=math.sqrt(D),
             ffn_layers=["dense", "routed", "routed", "routed"],
             n_routed_experts=EXPERTS, experts_per_token=TOPK,
             routed_scaling_factor=SCALE, norm_topk_prob=True,
             experts_held=[first, held], score_func="sigmoid",
             router_bias=True)
    m.update(over)
    return m


SPEC = ModelSpec.resolve(_model(), H)
RING = kvcache.ring_width(WINDOW, CHUNK, BS)


@pytest.fixture(scope="module")
def params():
    # std 0.2, not 0.02: logits of std ~1.2 at this width, so a wrong
    # cache row or a dropped assignment moves them far past the tolerance
    return ref.init_params(3, _cfg(), jnp.float32, std=0.2)


def _engine(params, impl="dense", **over):
    cfg = dict(heads=H, model=_model(), block_size=BS, num_blocks=80,
               max_batch=4, max_prompt_len=52, max_seq_len=72,
               prefill_chunk=CHUNK, attn_impl=impl)
    cfg.update(over)
    return Engine(params, EngineConfig(**cfg))


def _serve(eng, prompts, new):
    ids = [eng.submit(p, max_new_tokens=new, seed=100 + i)
           for i, p in enumerate(prompts)]
    eng.run()
    return [list(eng.request(i).tokens) for i in ids]


def _prompts(seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, V, n).tolist() for n in lengths]


# ---------------------------------------------------------------------------
# the description
# ---------------------------------------------------------------------------

def test_signatures_of_the_three_benchmark_descriptions_are_unchanged():
    """The new fields are spelt out only where they differ from their
    defaults: the in-tree LM, the recurrent-state and the latent
    descriptions keep the AOT cache keys they had (their strings at the
    parent commit)."""
    assert ModelSpec(heads=32).signature() == ""
    brumby = ModelSpec.resolve(dict(
        kv_heads=8, head_dim=128, norm="rmsnorm", norm_eps=1e-6,
        qk_norm=True, bias=False, ffn="silu_gated", position="rope",
        rope_theta=1000000.0, attention="power_retention",
        retention_eps=1e-6), 40)
    assert brumby.signature() == (
        ":kv_heads=8,head_dim=128,norm=rmsnorm,norm_eps=1e-06,qk_norm=True,"
        "bias=False,ffn=silu_gated,position=rope,rope_theta=1000000.0,"
        "attention=power_retention,retention_eps=1e-06")
    import json
    with open(os.path.join(REPO, "benchmark", "configs",
                           "deepseek-v2-ep4-5of60.json")) as f:
        dsv2 = ModelSpec.resolve(json.load(f)["serve"]["engine"]["model"], 128)
    assert dsv2.signature() == (
        ":kv_heads=None,head_dim=None,norm=rmsnorm,norm_eps=1e-06,"
        "qk_norm=False,bias=False,ffn=silu_gated,position=rope,"
        "rope_theta=10000.0,attention=latent,retention_eps=1e-06,"
        "q_lora_rank=1536,kv_lora_rank=512,qk_nope_head_dim=128,"
        "qk_rope_head_dim=64,v_head_dim=128,rope_scaling=(('beta_fast', 32), "
        "('beta_slow', 1), ('factor', 40), ('mscale', 0.707), "
        "('mscale_all_dim', 0.707), ('original_max_position_embeddings', "
        "4096), ('type', 'yarn')),ffn_layers=('dense', 'routed', 'routed', "
        "'routed', 'routed'),n_routed_experts=160,experts_per_token=6,"
        "n_group=8,topk_group=3,routed_scaling_factor=16.0,"
        "experts_held=(0, 40)")
    sig = SPEC.signature()
    assert "sliding_window=16" in sig and "score_func=sigmoid" in sig


def test_the_description_says_which_layers_keep_a_window():
    assert SPEC.layer_kinds(NL) == ("sliding",) * 3 + ("softmax",)
    assert [SPEC.rotates(k) for k in SPEC.layer_kinds(NL)] == [True] * 3 + [
        False]
    cache = kvcache.CacheSpec.for_attention(SPEC.layer_kinds(NL))
    assert cache.kind == kvcache.PAGED_WINDOW and not cache.recurrent
    assert kvcache.CacheSpec.for_attention(("sliding",)).kind == \
        kvcache.PAGED_WINDOW
    with pytest.raises(MXNetError, match="mixing cache kinds"):
        kvcache.CacheSpec.for_attention(("sliding", "latent")).kind
    with pytest.raises(MXNetError, match="sliding_window >= 1"):
        ModelSpec.resolve(_model(sliding_window=0), H)
    with pytest.raises(MXNetError, match="score_func"):
        ModelSpec.resolve(_model(score_func="tanh"), H)
    # the published ring: every position a 1024-chunk's queries see
    assert kvcache.ring_width(4096, 1024, 128) == 41 and RING == 7


# ---------------------------------------------------------------------------
# the block and the routing against the reference
# ---------------------------------------------------------------------------

def test_block_math_matches_the_reference_logits(params):
    """``decoder_forward`` over a plain whole-sequence cache (grouped
    heads, the window mask on sliding layers) gives the reference's
    logits to float32 rounding, over three windows of positions."""
    rng = np.random.default_rng(1)
    toks = rng.integers(1, V, (2, 3 * WINDOW))
    l = toks.shape[1]
    pos = jnp.arange(l)
    causal = pos[None, :] <= pos[:, None]
    window = causal & (pos[None, :] > pos[:, None] - WINDOW)

    def attend(_i, kind, q, k, v, _g):
        g = H // KV
        s = jnp.einsum("blkgd,bmkd->bkglm", q.reshape(2, l, KV, g, HD), k,
                       precision="highest") / math.sqrt(HD)
        seen = window if kind == "sliding" else causal
        a = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkglm,bmkd->blkgd", a, v,
                          precision="highest").reshape(2, l, H, HD)

    with jax.default_matmul_precision("highest"):
        got = decoder.decoder_forward(SPEC, params, jnp.asarray(toks),
                                      jnp.broadcast_to(pos, (2, l)), attend)
    want = ref.forward(params, toks, H, q_block=16, **SETTINGS)
    assert float(jnp.std(want)) > 1.0
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_the_bias_chooses_and_weighs_nothing(params):
    """The selection bias changes some tokens' choices, and a chosen
    expert's weight is its sigmoid score alone, renormalised over the
    four chosen and scaled: the bias never enters a weight."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((200, D)), jnp.float32)
    w_r, b = params["layer1_router_weight"], params["layer1_router_bias"]
    idx, w = experts.route(SPEC, x, w_r, b)
    idx0, _ = experts.route(SPEC, x, w_r, None)
    changed = np.any(np.sort(np.asarray(idx), 1)
                     != np.sort(np.asarray(idx0), 1), axis=1)
    assert 0 < changed.mean() < 1
    s = jax.nn.sigmoid(experts.router_logits(x, w_r))
    picked = jnp.take_along_axis(s, idx, axis=1)
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(picked / picked.sum(1, keepdims=True)
                                  * SCALE), rtol=1e-6)
    ids_ref, w_ref = ref.route(x, w_r, b, TOPK, SCALE)
    np.testing.assert_array_equal(np.sort(np.asarray(idx), 1),
                                  np.sort(np.asarray(ids_ref), 1))
    np.testing.assert_allclose(np.sort(np.asarray(w), 1),
                               np.sort(np.asarray(w_ref), 1), rtol=1e-5)


def test_the_benchmarks_bias_changes_a_minority_and_keeps_the_load_even():
    """At the published widths (3,072 wide, 256 experts, top 4, router
    weights N(0, 0.02)) a bias of the reference's spread changes a
    minority of the choices, and 32 rows a step still hit about 12.7 of
    the 32 held experts: what even routing gives, 32 x (1 - (31/32)^16).
    A bias several score gaps wide (0.05) decides the routing: it changes
    half the choices and concentrates them on fewer experts."""
    spec = ModelSpec.resolve(_model(n_routed_experts=256,
                                    experts_held=[0, 32]), H)
    rng = np.random.default_rng(8)
    w_r = jnp.asarray(rng.normal(0, 0.02, (256, 3072)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((64 * 32, 3072)), jnp.float32)
    noise = rng.standard_normal(256)
    idx0 = np.sort(np.asarray(experts.route(spec, x, w_r, None)[0]), 1)

    def reading(std):
        b = jnp.asarray(noise * std, jnp.float32)
        idx = np.sort(np.asarray(experts.route(spec, x, w_r, b)[0]), 1)
        changed = np.mean([4 - len(set(p) & set(q))
                           for p, q in zip(idx, idx0)]) / 4
        steps = idx.reshape(64, 32 * 4)
        return changed, np.mean([len(set(s[s < 32].tolist()))
                                 for s in steps])

    changed, hit = reading(ref.BIAS_STD)
    assert 0.03 < changed < 0.2 and abs(hit - 12.7) < 0.8, (changed, hit)
    changed, hit = reading(0.05)
    assert changed > 0.3 and hit < 12.0, (changed, hit)


def _route_before_the_bias(spec, x, w_router):
    """``models.experts.route`` as it was before sigmoid scores and the
    bias (PR 35), kept here to pin the default path bit for bit."""
    n, g, k = spec.n_routed_experts, spec.n_group, spec.experts_per_token
    p = jax.nn.softmax(experts.router_logits(x, w_router), axis=-1)
    if g > 1:
        best = jnp.max(p.reshape(-1, g, n // g), axis=-1)
        _, kept = jax.lax.top_k(best, spec.topk_group)
        mask = jnp.zeros_like(best).at[
            jnp.arange(best.shape[0])[:, None], kept].set(1.0)
        p_in = (p.reshape(-1, g, n // g) * mask[..., None]).reshape(-1, n)
    else:
        p_in = p
    w, idx = jax.lax.top_k(p_in, k)
    if spec.norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + np.float32(1e-20))
    return idx.astype(jnp.int32), w * np.float32(spec.routed_scaling_factor)


@pytest.mark.parametrize("groups,norm", [(8, False), (1, True)])
def test_the_default_route_is_deepseeks_bit_for_bit(groups, norm):
    spec = ModelSpec(heads=4, norm="rmsnorm", bias=False, ffn="silu_gated",
                     ffn_layers=("routed",), n_routed_experts=160,
                     experts_per_token=6, n_group=groups,
                     topk_group=3 if groups > 1 else 1,
                     routed_scaling_factor=16.0, norm_topk_prob=norm)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((64, 48)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((160, 48)) * 0.1, jnp.bfloat16)
    for got, want in zip(jax.jit(lambda a, b: experts.route(spec, a, b))(x, w),
                         jax.jit(lambda a, b: _route_before_the_bias(
                             spec, a, b))(x, w)):
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's test of the share: the routed parts of
    the four chips (experts 0-3, 4-7, 8-11, 12-15) and the shared
    expert, which every chip computes alike, counted ONCE, add up to the
    uncut reference's expert layer (before its output norm); no share
    alone does."""
    whole = ref.init_params(11, _cfg(held=EXPERTS), jnp.float32, std=0.2)
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((30, D)), jnp.float32)
    pre = "layer1_"
    layer = {k[len(pre):]: v for k, v in whole.items() if k.startswith(pre)}
    s_all, r_all = ref.moe_layer(h, layer, np.arange(EXPERTS), eps=1e-5,
                                 top_k=TOPK, route_scale=SCALE, parts=True)
    want = np.asarray(s_all + r_all)
    x = decoder._rmsm(h, whole["layer1_ln2_gamma"], 1e-5)
    shared = np.asarray(experts.shared_ffn(whole, 1, x))
    total, heres = shared.copy(), []
    for k in range(EXPERTS // HELD):
        spec = ModelSpec.resolve(_model(first=k * HELD), H)
        part = dict(whole)
        for nm in ("gate", "up", "down"):
            key = f"layer1_experts_{nm}_weight"
            part[key] = whole[key][k * HELD:(k + 1) * HELD]
        for fn in (experts.routed_ffn,
                   lambda *a: moe_experts.routed_ffn(*a, interpret=True)):
            out, _, here = fn(spec, part, 1, x)
            s_ref, r_ref = ref.moe_layer(
                h, {k2[len(pre):]: v for k2, v in part.items()
                    if k2.startswith(pre)},
                np.arange(k * HELD, (k + 1) * HELD), eps=1e-5, top_k=TOPK,
                route_scale=SCALE, parts=True)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(s_ref + r_ref), rtol=2e-4,
                                       atol=2e-5)
        total += np.asarray(out) - shared
        heres.append(int(here))
        assert np.abs(np.asarray(out) - want).max() > 0.05
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    assert sum(heres) == 30 * TOPK          # every choice is somebody's


# ---------------------------------------------------------------------------
# the decode kernel against its XLA reader
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window,ring", [(0, 0), (WINDOW, RING)])
def test_decode_kernel_matches_the_xla_reader(dtype, window, ring):
    """Ragged rows: one that attends nothing, one inside its first
    block, one across the window, one past the ring's wrap."""
    rng = np.random.default_rng(7)
    nb = 80
    kp, vp = (jnp.asarray(rng.standard_normal((2, nb, BS, KV * HD)), dtype)
              for _ in range(2))
    q = jnp.asarray(rng.standard_normal((4, H, HD)), dtype)
    cols = ring or 18
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))[:4 * cols]
                         .reshape(4, cols), jnp.int32)
    lengths = jnp.asarray([0, 3, WINDOW + 5, 3 * WINDOW + 2 if ring else 70],
                          jnp.int32)
    want = kvcache.gqa_decode_attention(q, kp, vp, 1, tables, lengths,
                                        scale=0.25, window=window, ring=ring)
    got = gqa_decode(q, kp, vp, 1, tables, lengths, scale=0.25,
                     window=window, ring=ring, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol,
                               rtol=tol)
    assert not np.any(np.asarray(got[0], np.float32))


# ---------------------------------------------------------------------------
# the engine against the plain reference (logits, not tokens)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["dense", "flash_interpret"])
def test_engine_matches_the_reference_through_prefill_and_decode(params, impl):
    """Chunked prefill (boundaries at 1, C-1, C+1, one window, and three
    windows past the ring's wrap) then decode through the window ring and
    the global table: the reference's logit of every token the engine
    emitted is its maximum to within 1e-4 (float32 on both sides; the
    block's logits agree to 2e-4 above)."""
    prompts = _prompts(0, (1, CHUNK - 1, CHUNK + 1, WINDOW, 3 * WINDOW + 3))
    new = 12
    eng = _engine(params, impl, max_batch=5)
    assert eng.described_kv and eng.alloc.ring == RING
    assert [c.shape for c in eng._caches] == (
        [(3, 1 + 5 * RING, BS, KV * HD)] * 2 + [(1, 80, BS, KV * HD)] * 2)
    outs = _serve(eng, prompts, new)
    assert eng.alloc.num_used == 0
    eng.check_tables()
    toks = np.zeros((len(prompts), 64), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        toks[i, :len(p)] = p
        toks[i, len(p):len(p) + new - 1] = o[:-1]
    logits = np.asarray(ref.forward(params, toks, H, q_block=16, **SETTINGS))
    assert logits.std() > 1.0
    for i, (p, o) in enumerate(zip(prompts, outs)):
        rows = logits[i, len(p) - 1:len(p) - 1 + new]
        deficit = rows.max(-1) - rows[np.arange(new), o]
        assert deficit.max() < 1e-4, (i, deficit)


def test_tables_stay_capped_and_preemption_and_the_drain_free_both(params):
    """Step by step: no window table is wider than the ring, both kinds
    of table pass ``check_tables``; a request preempted past the wrap
    frees both kinds and resumes byte-identical; the drain frees
    everything; the counters and the decode spans say what was walked."""
    from mxnet_tpu.telemetry import tracing
    telemetry.reset_for_tests()
    prompts = _prompts(2, (9, 2 * WINDOW + 5, 3))
    want = _serve(_engine(params), prompts, 20)
    eng = _engine(params)
    ids = [eng.submit(p, max_new_tokens=20, seed=100 + i)
           for i, p in enumerate(prompts)]
    tracing.clear()
    tracing.configure(None, enable=True)
    try:
        for _ in range(9):
            eng.step()
            eng.check_tables()
            assert all(len(r.ring) <= RING for r in eng.sched.running)
        victim = eng.request(ids[1])
        assert victim.cached > RING * BS and len(victim.ring) == RING
        eng._preempt(victim)
        assert victim.blocks == [] and victim.ring == []
        eng.check_tables()
        assert eng.alloc.window.num_used == sum(
            len(r.ring) for r in eng.sched.running)
        eng.run()
        spans = [ev["args"] for ev in tracing.tail(tracing._MAX_EVENTS)
                 if ev["name"] == "serve.decode"]
    finally:
        tracing.configure(None, enable=False)
    assert [list(eng.request(i).tokens) for i in ids] == want
    assert eng.alloc.num_used == 0 and eng.alloc.window.num_used == 0
    eng.check_tables()
    assert int(telemetry.counter("serve.preemptions").value()) == 1
    assert telemetry.counter("serve.kv.window_blocks_reused").value() > 0
    assert telemetry.gauge("serve.kv.window_blocks_used").value() == 0
    for a in spans:
        assert a["active"] <= a["window_rows"] <= a["active"] * WINDOW
        assert a["window_rows"] <= a["global_rows"]
    assert max(a["global_rows"] for a in spans) > WINDOW * 2
    with pytest.raises(MXNetError, match="a window table of"):
        eng.alloc.check({}, {1: list(range(RING + 1))})


def test_steady_state_runs_zero_traces(params):
    eng = _engine(params, "flash_interpret")
    eng.warmup()
    before = dict(eng.trace_counts)
    together = _serve(eng, _prompts(3, (3, 20, 30, 13)), 9)
    assert dict(eng.trace_counts) == before
    assert not eng.aot_stats["fallbacks"]
    alone = _serve(_engine(params, "flash_interpret"),
                   _prompts(3, (3, 20))[1:], 9)
    assert together[1] == alone[0]


@pytest.mark.parametrize("option", [dict(prefix_cache=True),
                                    dict(speculate=True),
                                    dict(kv_quant="fp8")])
def test_options_the_window_kind_refuses_say_so_by_name(params, option):
    name = next(iter(option))
    with pytest.raises(ServeError, match=f"EngineConfig.{name} is not served "
                       "on a described softmax model's tables"):
        _engine(params, **option)


def test_whole_prompt_prefill_is_refused(params):
    with pytest.raises(MXNetError, match="prefill_chunk > 0"):
        _engine(params, prefill_chunk=0)
