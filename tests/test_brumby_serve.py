"""A recurrent-state model on ``serve.Engine``'s normal path (ISSUE 26):
the Brumby block (RMSNorm, per-head q/k norms, RoPE, grouped heads, a
gated SiLU FFN, power-retention attention) at a tiny size on the CPU,
against the benchmark's plain float32 reference, plus the retention
layer's three forms, its two implementations of the decode update, and
the cache manager's state slots."""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import retention
from mxnet_tpu.models.decoder import ModelSpec
from mxnet_tpu.serve import Engine, EngineConfig, ServeError, kvcache
from mxnet_tpu.serve import retention_decode as rd

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    """``benchmark/reference/brumby.py`` by path: it imports nothing from
    the program, and the program nothing from it."""
    spec = importlib.util.spec_from_file_location(
        "brumby_reference",
        os.path.join(REPO, "benchmark", "reference", "brumby.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()
V, NL, D, H, KV, HD, F = 96, 2, 64, 4, 2, 16, 128
CFG = dict(vocab_size=V, num_hidden_layers=NL, hidden_size=D,
           num_attention_heads=H, num_key_value_heads=KV, head_dim=HD,
           intermediate_size=F)
MODEL = dict(kv_heads=KV, head_dim=HD, norm="rmsnorm", norm_eps=1e-6,
             qk_norm=True, bias=False, ffn="silu_gated", position="rope",
             rope_theta=1e6, attention="power_retention")
CHUNK = 8
EPS = 1e-6


@pytest.fixture(scope="module")
def params():
    # std 0.2, not 0.02: logits of std ~1.6 at this width, so a wrong
    # state moves them by far more than the tolerance
    return ref.init_params(3, CFG, jnp.float32, std=0.2)


def _engine(params, impl="dense", **over):
    cfg = dict(heads=H, model=MODEL, num_blocks=5, max_batch=4,
               max_prompt_len=40, max_seq_len=64, prefill_chunk=CHUNK,
               attn_impl=impl)
    cfg.update(over)
    return Engine(params, EngineConfig(**cfg))


def _qkvg(rng, t, hd=HD, h=H, kv=KV):
    q = rng.randn(t, h, hd).astype(np.float32)
    k = rng.randn(t, kv, hd).astype(np.float32)
    v = rng.randn(t, kv, hd).astype(np.float32)
    g = np.log(1 / (1 + np.exp(-(3 + rng.randn(t, kv))))).astype(np.float32)
    return q, k, v, g


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hd", [8, 16])
def test_phi_is_the_degree_2_kernel(hd):
    rng = np.random.RandomState(0)
    a, b = rng.randn(2, 5, hd).astype(np.float32)
    got = np.asarray(jnp.sum(retention.phi(a) * retention.phi(b),
                             axis=(-1, -2)))
    np.testing.assert_allclose(got, (a * b).sum(-1) ** 2 / hd, rtol=2e-5,
                               atol=1e-6)
    chunks, rows, lanes = retention.state_shape(hd)
    assert retention.phi(a).shape == (5, chunks, lanes)
    # hd (hd + 1) / 2 products in the folded rectangle, the rest empty
    assert int((np.asarray(retention.phi(np.ones(hd))) != 0).sum()) == \
        hd * (hd + 1) // 2
    assert rows % 8 == 0 and rows > hd


@pytest.mark.parametrize("t", [1, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK + 3])
def test_three_forms_of_retention_agree(t):
    """Attention form == recurrent form == chunked form, outputs and
    final state, with a chunk boundary at 1, C-1, C, C+1 tokens and
    two query heads on each state (grouped heads)."""
    rng = np.random.RandomState(t)
    q, k, v, g = _qkvg(rng, t)
    want = np.asarray(retention.attention_form(q, k, v, g, EPS))
    state = jnp.zeros((1, KV) + retention.state_shape(HD), jnp.float32)
    ys = []
    for i in range(t):
        y, state = retention.recurrent_step(state, q[i:i + 1], k[i:i + 1],
                                            v[i:i + 1], g[i:i + 1], EPS)
        ys.append(np.asarray(y[0]))
    np.testing.assert_allclose(np.stack(ys), want, rtol=2e-4, atol=2e-5)
    chunked = jnp.zeros((KV,) + retention.state_shape(HD), jnp.float32)
    outs = []
    for s0 in range(0, t, CHUNK):
        n = min(CHUNK, t - s0)
        pad = [np.concatenate([x[s0:s0 + n],
                               np.zeros((CHUNK - n,) + x.shape[1:], x.dtype)])
               for x in (q, k, v, g)]
        y, chunked = retention.chunk_form(chunked, *pad, n, EPS)
        outs.append(np.asarray(y[:n]))
    np.testing.assert_allclose(np.concatenate(outs), want, rtol=2e-4,
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(chunked), np.asarray(state[0]),
                               rtol=2e-4, atol=2e-5)


def test_five_query_heads_read_one_state():
    """The published grouping: 5 query heads a key/value head."""
    rng = np.random.RandomState(5)
    q, k, v, g = _qkvg(rng, 6, h=10, kv=2)
    want = np.asarray(retention.attention_form(q, k, v, g, EPS))
    y, state = retention.chunk_form(
        jnp.zeros((2,) + retention.state_shape(HD), jnp.float32), q, k, v, g,
        6, EPS)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-5)
    assert state.shape[0] == 2           # one state a key/value head
    # heads 0-4 read key/value head 0: swapping its values moves them alone
    v2 = v.copy()
    v2[:, 1] += 1.0
    moved = np.asarray(retention.attention_form(q, k, v2, g, EPS)) - want
    assert np.abs(moved[:, :5]).max() == 0 and np.abs(moved[:, 5:]).min() > 0


def test_a_bfloat16_state_or_a_dropped_z_is_far_outside_the_tolerance():
    """What the engine test's 1e-3 must catch: the recurrent form with
    its state rounded to bfloat16 after every step, and with the
    normaliser left out."""
    rng = np.random.RandomState(9)
    t = 24
    q, k, v, g = _qkvg(rng, t)
    want = np.asarray(retention.attention_form(q, k, v, g, EPS))
    state = jnp.zeros((1, KV) + retention.state_shape(HD), jnp.float32)
    worst = 0.0
    for i in range(t):
        y, state = retention.recurrent_step(state, q[i:i + 1], k[i:i + 1],
                                            v[i:i + 1], g[i:i + 1], EPS)
        state = state.astype(jnp.bfloat16).astype(jnp.float32)
        worst = max(worst, float(np.abs(np.asarray(y[0]) - want[i]).max()))
    assert worst > 3e-3
    # z dropped: the numerators alone, with no division
    exact = jnp.zeros((1, KV) + retention.state_shape(HD), jnp.float32)
    for i in range(t):
        _, exact = retention.recurrent_step(exact, q[i:i + 1], k[i:i + 1],
                                            v[i:i + 1], g[i:i + 1], EPS)
    pq = retention.phi(q[-1:]).reshape((1, KV, H // KV)
                                       + retention.state_shape(HD)[::2])
    num = jnp.einsum("bkgil,bkicl->bkgc", pq, exact)[..., :HD]
    assert float(np.abs(np.asarray(num).reshape(H, HD) - want[-1]).max()) > 0.1


# ---------------------------------------------------------------------------
# the decode update: the Pallas kernel (interpreted) against its twin
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hd,h,kv", [(16, 4, 2), (64, 2, 1)])
def test_retention_decode_kernel_matches_its_twin(hd, h, kv):
    rng = np.random.RandomState(1)
    layers, slots, rows = 2, 4, 3
    shape = (layers, slots, kv) + retention.state_shape(hd)
    # a state that could have been reached: a few recurrent steps
    new = jnp.zeros(shape[1:], jnp.float32)
    for _ in range(4):
        q, k, v, g = _qkvg(rng, slots, hd, h, kv)
        _, new = retention.recurrent_step(new, q, k, v, g, EPS)
    pool = jnp.zeros(shape, jnp.float32).at[1].set(new)
    q, k, v, g = _qkvg(rng, rows, hd, h, kv)
    where = jnp.asarray([2, kvcache.TRASH_BLOCK, 3], jnp.int32)
    y_t, p_t = rd.retention_decode_xla(pool, 1, where, q, k, v, g, EPS)
    y_k, p_k = jax.jit(lambda p: rd.retention_decode(
        p, 1, where, q, k, v, g, EPS, interpret=True))(pool)
    live = [0, 2]
    np.testing.assert_allclose(np.asarray(y_k)[live], np.asarray(y_t)[live],
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(p_k), np.asarray(p_t), rtol=1e-5,
                               atol=1e-6)
    # layers and slots no active row names are untouched, bit for bit
    assert np.array_equal(np.asarray(p_k[0]), np.asarray(pool[0]))
    assert np.array_equal(np.asarray(p_k[1, 1]), np.asarray(pool[1, 1]))


def test_the_chunk_program_needs_no_kernel_of_its_own():
    """The chunk is XLA einsums (``models.retention.chunk_form``): one
    decode kernel is all this model adds."""
    names = [n for n in dir(rd) if n.startswith("retention_")]
    assert sorted(names) == ["retention_decode", "retention_decode_xla"]


# ---------------------------------------------------------------------------
# the engine against the plain reference (logits, not tokens)
# ---------------------------------------------------------------------------

def _serve(eng, prompts, new):
    ids = [eng.submit(p, max_new_tokens=new, seed=100 + i)
           for i, p in enumerate(prompts)]
    eng.run()
    return [list(eng.request(i).tokens) for i in ids]


@pytest.mark.parametrize("impl", ["dense", "flash_interpret"])
def test_engine_matches_the_reference_through_prefill_and_decode(params, impl):
    """Chunked prefill (boundaries at 1, C-1, C, C+1 and two chunks
    crossed) then decode through the state: the reference's logit of
    every token the engine emitted is its maximum to within 1e-3 (float32
    on both sides: what is left is summation order, 1e-5 of logits of
    std 1.6; a bfloat16 state or a dropped z is 3e-3 .. 1e3 off, above)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, V, n).tolist()
               for n in (1, CHUNK - 1, CHUNK, CHUNK + 1, 2 * CHUNK + 3)]
    new = 6
    eng = _engine(params, impl, max_batch=5, num_blocks=6)
    outs = _serve(eng, prompts, new)
    assert eng.alloc.num_used == 0
    toks = np.zeros((len(prompts), 32), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        toks[i, :len(p)] = p
        toks[i, len(p):len(p) + new - 1] = o[:-1]
    logits = np.asarray(ref.forward(params, toks, H))
    assert logits.std() > 1.0
    for i, (p, o) in enumerate(zip(prompts, outs)):
        rows = logits[i, len(p) - 1:len(p) - 1 + new]
        deficit = rows.max(-1) - rows[np.arange(new), o]
        assert deficit.max() < 1e-3, (i, deficit)


def test_in_tree_lm_is_the_default_description():
    """``model=None`` is the in-tree LM: the description reproduces it."""
    assert ModelSpec.resolve(None, 4) == ModelSpec(heads=4)
    assert ModelSpec(heads=4).signature() == ""
    spec = ModelSpec.resolve(MODEL, H)
    assert spec.dims(D) == (H, KV, HD) and spec.signature()
    assert kvcache.CacheSpec.for_attention(spec.layer_kinds(2)).recurrent
    assert not kvcache.CacheSpec.for_attention(("softmax",) * 2).recurrent
    with pytest.raises(MXNetError, match="mixing cache kinds"):
        kvcache.CacheSpec.for_attention(("softmax", "power_retention")
                                        ).recurrent
    with pytest.raises(MXNetError, match="no field"):
        ModelSpec.resolve({"window": 5}, 4)


@pytest.mark.parametrize("option", [dict(prefix_cache=True),
                                    dict(speculate=True),
                                    dict(kv_quant="fp8")])
def test_options_that_need_paged_kv_refuse_this_model(params, option):
    with pytest.raises(ServeError, match="needs paged K/V"):
        _engine(params, **option)


def test_a_described_softmax_model_is_served_not_misrun(params):
    """The same block with softmax attention in place of retention
    (grouped heads, RoPE, q/k norms) runs through the paged programs of a
    described softmax model (ISSUE 37 lifted the refusal), never the
    in-tree block: its greedy tokens are the maxima of
    ``decoder_forward`` over the whole sequence with plain causal
    attention, teacher-forced on them."""
    from mxnet_tpu.models import decoder
    model = dict(MODEL, attention="softmax")
    eng = _engine(params, model=model, num_blocks=40, block_size=4)
    assert eng.described_kv and not eng.recurrent
    prompt = np.random.RandomState(5).randint(1, V, 13).tolist()
    out = eng.result(eng.submit(prompt, max_new_tokens=6))
    toks = jnp.asarray([prompt + out[:-1]])
    n = toks.shape[1]
    causal = jnp.arange(n)[None, :] <= jnp.arange(n)[:, None]

    def attend(_i, _kind, q, k, v, _g):
        q = q.reshape(1, n, KV, H // KV, HD)
        s = jnp.einsum("blkgd,bmkd->bkglm", q, k) / np.sqrt(HD)
        a = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bkglm,bmkd->blkgd", a, v).reshape(1, n, H, HD)

    with jax.default_matmul_precision("highest"):
        logits = decoder.decoder_forward(
            ModelSpec.resolve(model, H), params, toks,
            jnp.arange(n)[None, :], attend)
    rows = np.asarray(logits[0, len(prompt) - 1:])
    assert rows.std() > 1.0
    assert (rows.max(-1) - rows[np.arange(6), out]).max() < 1e-4


def test_a_narrower_state_is_refused(params):
    """``EngineConfig.dtype`` is the state's type and float32 is the one
    type it is kept in: a bfloat16 state is another result (3e-3 off at
    this size, above), so asking for one is an error, never a quiet
    float32 or a quiet bfloat16."""
    with pytest.raises(ServeError, match="kept in float32"):
        _engine(params, dtype=jnp.bfloat16)
    assert _engine(params, dtype=jnp.float32).state.dtype == jnp.float32


def test_whole_prompt_prefill_refuses_this_model(params):
    with pytest.raises(MXNetError, match="prefill_chunk > 0"):
        _engine(params, prefill_chunk=0)


# ---------------------------------------------------------------------------
# the cache manager's state slots
# ---------------------------------------------------------------------------

def test_slots_are_counted_checked_and_scrubbed_before_reuse(params):
    telemetry.reset_for_tests()
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, V, n).tolist() for n in (5, 11)]
    clean = _serve(_engine(params), prompts, 7)
    eng = _engine(params)
    assert eng.state.shape == (NL, 5, KV) + retention.state_shape(HD)
    assert eng.max_blocks == 1 and eng.num_layers == NL
    assert (eng.heads, eng.kv_heads, eng.head_dim) == (H, KV, HD)
    # every slot dirty, NaN included: a request's first chunk must read
    # zeros whatever its slot held
    eng._caches = (jnp.full_like(eng.state, jnp.nan),)
    ids = [eng.submit(p, max_new_tokens=7, seed=100 + i)
           for i, p in enumerate(prompts)]
    eng.step()
    assert eng.alloc.num_used == 2
    assert sorted(r.blocks[0] for r in eng.sched.running) == [1, 2]
    eng.check_tables()
    eng.run()
    assert [list(eng.request(i).tokens) for i in ids] == clean
    assert eng.alloc.num_used == 0
    eng.check_tables()
    # cancel and failure return the slot too
    rid = eng.submit(prompts[0], max_new_tokens=30)
    eng.step()
    assert eng.alloc.num_used == 1
    eng.cancel(rid)
    eng.step()
    assert eng.alloc.num_used == 0
    snap = telemetry.registry().snapshot()
    text = str(snap)
    assert "serve.state.resets" in text and "serve.state.slots_used" in text


def test_a_preempted_stream_resumes_byte_identical(params):
    """Preemption frees the slot and re-chunks prompt + tokens: the
    rebuilt state continues the very stream (the replay contract)."""
    telemetry.reset_for_tests()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, V, n).tolist() for n in (9, 14)]
    want = _serve(_engine(params), prompts, 12)
    eng = _engine(params)
    ids = [eng.submit(p, max_new_tokens=12, seed=100 + i)
           for i, p in enumerate(prompts)]
    for _ in range(6):
        eng.step()
    victim = eng.request(ids[1])
    assert 0 < len(victim.tokens) < 12
    eng._preempt(victim)
    assert victim.blocks == [] and eng.alloc.num_used == 1
    eng.run()
    assert [list(eng.request(i).tokens) for i in ids] == want
    assert eng.alloc.num_used == 0
    assert "serve.state.rebuilds" in str(telemetry.registry().snapshot())


def test_steady_state_runs_zero_traces_and_batches_like_alone(params):
    eng = _engine(params)
    eng.warmup()
    before = dict(eng.trace_counts)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, V, n).tolist() for n in (3, 20, 8, 13, 30)]
    together = _serve(eng, prompts, 9)
    assert dict(eng.trace_counts) == before
    assert not eng.aot_stats["fallbacks"]
    alone = [_serve(_engine(params), [p], 9)[0] for p in prompts[:2]]
    # request seeds differ (100 + index), greedy streams do not care
    assert together[:2] == alone
