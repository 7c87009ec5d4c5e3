"""A latent-attention model with routed experts on ``serve.Engine``'s
normal path (ISSUE 35): the DeepSeek-V2 block (low-rank queries, ONE
cached row ``[c | k_r]`` a position shared by all heads, YaRN rotary
channels beside no-position ones, a leading dense layer, then experts
chosen group-limited greedy of which THIS chip holds a share, beside
shared experts) at a tiny size on the CPU, against the benchmark's plain
float32 reference; the attention's two forms; the decode kernel against
the XLA reader; the expert layer's plan, its grouped product and its
share of the uncut layer; the cache manager's third kind."""
import importlib.util
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
from mxnet_tpu.serve import mla_decode, moe_experts

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _reference():
    """``benchmark/reference/deepseek_v2.py`` by path: it imports nothing
    from the program, and the program nothing from it."""
    spec = importlib.util.spec_from_file_location(
        "deepseek_v2_reference",
        os.path.join(REPO, "benchmark", "reference", "deepseek_v2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()
V, NL, D, H = 96, 3, 64, 4
QR, RANK, NOPE, ROPE, VD, F, FE = 24, 32, 16, 8, 16, 128, 32
EXPERTS, GROUPS, KEPT, TOPK, HELD, SCALING = 16, 4, 2, 3, 4, 4.0
YARN = dict(ref.PUBLISHED["rope_scaling"])
ROUTING = dict(n_group=GROUPS, topk_group=KEPT, top_k=TOPK,
               routed_scaling_factor=SCALING)


def _cfg(held=HELD, first=0):
    return dict(vocab_size=V, num_hidden_layers=NL, hidden_size=D,
                num_attention_heads=H, q_lora_rank=QR, kv_lora_rank=RANK,
                qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=VD,
                intermediate_size=F, moe_intermediate_size=FE,
                n_routed_experts=held, n_shared_experts=2,
                first_k_dense_replace=1,
                published=dict(n_routed_experts=EXPERTS),
                deployment_share=dict(first_expert=first))


def _model(first=0, held=HELD):
    return dict(norm="rmsnorm", norm_eps=1e-6, bias=False, ffn="silu_gated",
                position="rope", rope_theta=10000.0, attention="latent",
                q_lora_rank=QR, kv_lora_rank=RANK, qk_nope_head_dim=NOPE,
                qk_rope_head_dim=ROPE, v_head_dim=VD, rope_scaling=YARN,
                ffn_layers=["dense", "routed", "routed"],
                n_routed_experts=EXPERTS, experts_per_token=TOPK,
                n_group=GROUPS, topk_group=KEPT,
                routed_scaling_factor=SCALING, experts_held=[first, held])


SPEC = ModelSpec.resolve(_model(), H)
CHUNK, BS = 8, 4


@pytest.fixture(scope="module")
def params():
    # std 0.2, not 0.02: logits of std ~1.6 at this width, so a wrong
    # cache row or a dropped assignment moves them far past the tolerance
    return ref.init_params(3, _cfg(), jnp.float32, std=0.2)


def _engine(params, impl="dense", **over):
    cfg = dict(heads=H, model=_model(), block_size=BS, num_blocks=48,
               max_batch=4, max_prompt_len=40, max_seq_len=64,
               prefill_chunk=CHUNK, attn_impl=impl)
    cfg.update(over)
    return Engine(params, EngineConfig(**cfg))


def _serve(eng, prompts, new):
    ids = [eng.submit(p, max_new_tokens=new, seed=100 + i)
           for i, p in enumerate(prompts)]
    eng.run()
    return [list(eng.request(i).tokens) for i in ids]


# ---------------------------------------------------------------------------
# the description
# ---------------------------------------------------------------------------

def test_signatures_of_the_benchmarks_two_descriptions_are_unchanged():
    """The new fields are spelt out only where they differ from their
    defaults, so the AOT cache keys of the in-tree LM and of the
    recurrent-state description stay what they were."""
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
    sig = SPEC.signature()
    assert "attention=latent" in sig and "experts_held=(0, 4)" in sig
    assert SPEC.signature() != ModelSpec.resolve(_model(first=4), H).signature()


def test_the_description_says_what_is_cached_and_what_is_held():
    assert SPEC.layer_kinds(NL) == ("latent",) * NL
    assert SPEC.ffn_kinds(NL) == ("dense", "routed", "routed")
    assert SPEC.latent_width == RANK + ROPE and SPEC.held == (0, HELD)
    cache = kvcache.CacheSpec.for_attention(SPEC.layer_kinds(NL))
    assert cache.kind == kvcache.PAGED_LATENT and not cache.recurrent
    for mix in (("softmax", "latent"), ("latent", "power_retention")):
        with pytest.raises(MXNetError, match="mixing cache kinds"):
            kvcache.CacheSpec.for_attention(mix).kind
    with pytest.raises(MXNetError, match="not a range"):
        ModelSpec.resolve(_model(first=14), H)
    with pytest.raises(MXNetError, match="multiple of n_group"):
        ModelSpec.resolve(dict(_model(), n_group=5), H)
    with pytest.raises(MXNetError, match="latent attention needs"):
        ModelSpec.resolve(dict(_model(), kv_lora_rank=0), H)


def test_yarn_frequencies_and_scale_are_the_references():
    for dim in (ROPE, 64):
        inv, m = decoder.yarn_inv_freq(dim, 10000.0, YARN)
        want, wm = ref.yarn(dim, 10000.0, YARN)
        np.testing.assert_allclose(inv, want, rtol=1e-6)
        assert m == pytest.approx(wm) and m == pytest.approx(1.0)
    # at the published sizes: pairs 10..23 of 32 ramp, the score scale
    inv, _ = decoder.yarn_inv_freq(64, 10000.0, YARN)
    plain = 10000.0 ** (-np.arange(32) / 32.0)
    assert np.allclose(inv[:11], plain[:11]) and np.allclose(
        inv[23:], plain[23:] / 40, rtol=1e-6)
    assert np.all(inv[11:23] < plain[11:23]) and np.all(
        inv[11:23] > plain[11:23] / 40)
    published = ModelSpec.resolve(dict(
        _model(), qk_nope_head_dim=128, qk_rope_head_dim=64), H)
    assert float(published.latent_scale()) == pytest.approx(
        192 ** -0.5 * (0.1 * 0.707 * np.log(40) + 1) ** 2, rel=1e-6)
    assert float(published.latent_scale()) == pytest.approx(
        ref.score_scale(128, 64, YARN), rel=1e-6)


# ---------------------------------------------------------------------------
# the block's mathematics: logits against the reference, tightly
# ---------------------------------------------------------------------------

def test_block_math_matches_the_reference_logits(params):
    """``decoder_forward`` over a whole sequence as one chunk (its rows
    written to a latent pool, then read in the up-projected form, the
    context walked three blocks at a time): every logit, not a choice."""
    length = 27
    toks = np.random.default_rng(1).integers(1, V, (1, length))
    pools = list(kvcache.make_pools(NL, 12, BS, H, NOPE + ROPE,
                                    latent_width=RANK + ROPE))
    table = jnp.arange(1, 9, dtype=jnp.int32)
    positions = jnp.arange(length, dtype=jnp.int32)[None]

    def attend(i, kind, q, row, _v, _g):
        pools[0] = kvcache.write_prefill(
            pools[0], i, kvcache.latent_rows(pools[0], row[0]), table, length)
        return kvcache.latent_prefill_attention(
            q[0], pools[0], i, table, 0, length,
            params[f"layer{i}_kv_b_weight"], rank=RANK, nope=NOPE,
            scale=SPEC.latent_scale(), ctx_block=3 * BS, head_group=2)[None]

    got = np.asarray(decoder.decoder_forward(SPEC, params, jnp.asarray(toks),
                                             positions, attend))
    want = np.asarray(ref.forward(params, toks, H, **ROUTING))
    assert want.std() > 1.0
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    assert pools[0].shape == (NL, 12, BS, 128)      # 40 values on 128 lanes
    assert float(jnp.abs(pools[0][..., RANK + ROPE:]).max()) == 0.0


def test_absorbed_and_up_projected_attention_agree():
    """Decode's form (``q~ = q_n W_kvb[keys]``, scores against the cached
    rows, ``W_kvb[values]`` after) and the chunk's (keys and values made
    of the rows) give the same output for the last position."""
    rng = np.random.default_rng(2)
    length, nblk = 22, 6
    pool, = kvcache.make_pools(2, 10, BS, H, NOPE + ROPE,
                               latent_width=RANK + ROPE)
    rows = rng.standard_normal((length, RANK + ROPE)).astype(np.float32)
    table = jnp.asarray([3, 1, 4, 7, 9, 2], jnp.int32)
    pool = kvcache.write_prefill(pool, 1, kvcache.latent_rows(pool, rows),
                                 table, length)
    w = jnp.asarray(rng.standard_normal((H * (NOPE + VD), RANK)) * 0.3,
                    jnp.float32)
    q = jnp.asarray(rng.standard_normal((length, H, NOPE + ROPE)), jnp.float32)
    scale = SPEC.latent_scale()
    chunk = kvcache.latent_prefill_attention(
        q, pool, 1, table, 0, length, w, rank=RANK, nope=NOPE, scale=scale,
        ctx_block=2 * BS, head_group=2)
    for t in (0, 7, length - 1):
        qa = kvcache.latent_absorb(q[t][None], w, NOPE)
        y = kvcache.latent_decode_attention(
            qa, pool, 1, table[None], jnp.asarray([t + 1]), rank=RANK,
            scale=scale)
        np.testing.assert_allclose(
            np.asarray(kvcache.latent_expand(y, w, NOPE)[0]),
            np.asarray(chunk[t]), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("bs,lengths", [(4, (1, 4, 9, 0, 23)),
                                        (8, (17, 64, 33, 8, 1))])
def test_decode_kernel_matches_the_xla_reader_at_ragged_lengths(bs, lengths):
    """``mxtpu_mla_decode`` (interpreted) against the gather-and-softmax
    reader: rows that end inside a block, fill their table, hold one
    position, or are not active (length 0: reads nothing, returns 0)."""
    rng = np.random.default_rng(bs)
    nblk = -(-max(lengths) // bs)
    rows_n = len(lengths)
    pool = jnp.asarray(rng.standard_normal((2, 1 + rows_n * nblk, bs, 128)),
                       jnp.float32).at[..., RANK + ROPE:].set(0.0)
    tables = jnp.asarray(1 + rng.permutation(rows_n * nblk).reshape(
        rows_n, nblk), jnp.int32)
    q = jnp.asarray(rng.standard_normal((rows_n, H, RANK + ROPE)), jnp.float32)
    n = jnp.asarray(lengths, jnp.int32)
    want = kvcache.latent_decode_attention(q, pool, 1, tables, n, rank=RANK,
                                           scale=0.3, impl="dense")
    for splits in (1, 2):
        got = jax.jit(lambda p: mla_decode.mla_decode_attention(
            q, p, 1, tables, n, rank=RANK, scale=0.3, split_k=splits,
            interpret=True))(pool)
        live = [i for i, x in enumerate(lengths) if x]
        np.testing.assert_allclose(np.asarray(got)[live],
                                   np.asarray(want)[live], rtol=2e-5,
                                   atol=2e-6)
        dead = [i for i, x in enumerate(lengths) if not x]
        assert not dead or float(jnp.abs(got[jnp.asarray(dead)]).max()) == 0.0
    assert mla_decode.default_split_k(48, 128) == 2
    assert mla_decode.default_split_k(32, 128) == 1


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------

def test_group_limited_top_k_is_the_references_choice(params):
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((50, D)), jnp.float32)
    w = params["layer1_router_weight"]
    idx, wgt = experts.route(SPEC, x, w)
    ids, want = ref.route(x, w, GROUPS, KEPT, TOPK, SCALING)
    assert np.array_equal(np.asarray(idx), np.asarray(ids))
    np.testing.assert_allclose(np.asarray(wgt), np.asarray(want), rtol=1e-5)
    # never more than KEPT groups a token, weights 4 p and not renormalised
    groups = np.asarray(idx) // (EXPERTS // GROUPS)
    assert max(len(set(g)) for g in groups) <= KEPT
    assert float(jnp.sum(wgt, axis=-1).max()) < SCALING


@pytest.mark.parametrize("tokens,tm", [(9, 8), (64, 16), (200, 64)])
def test_the_plan_gives_every_held_assignment_a_row_of_its_experts_tiles(
        tokens, tm):
    rng = np.random.default_rng(tokens)
    local = jnp.asarray(rng.integers(-3, HELD + 2, (tokens, TOPK)), jnp.int32)
    held = (local >= 0) & (local < HELD)
    p = moe_experts.plan(local, held, HELD, tm)
    pos, src = np.asarray(p.pos), np.asarray(p.src)
    heldn, localn = np.asarray(held), np.asarray(local)
    rows = pos[heldn]
    assert len(set(rows.tolist())) == rows.size          # one row each
    assert rows.max(initial=0) < src.size and (pos[~heldn] >= src.size).all()
    # a row reads its own token, and lies in a tile of its own expert
    t_of = np.repeat(np.arange(tokens), TOPK).reshape(tokens, TOPK)
    assert np.array_equal(src[rows], t_of[heldn])
    assert np.array_equal(np.asarray(p.tile_group)[rows // tm], localn[heldn])
    live = int(p.live_tiles[0])
    counts = np.asarray(p.counts)
    assert counts.sum() == heldn.sum()
    assert live == sum(-(-c // tm) for c in counts)
    # an expert's tiles are consecutive: its weights are read once a pass
    grp = np.asarray(p.tile_group)[:live]
    assert (np.diff(grp) >= 0).all()
    # tiles past the live ones point at the last live one: nothing moves
    assert (np.asarray(p.tile_index)[live:] == max(live - 1, 0)).all()
    assert src.size == (-(-tokens * TOPK // tm) + HELD) * tm


@pytest.mark.parametrize("skew", ["routed", "all_to_one", "none_here"])
def test_no_assignment_is_dropped_at_any_skew(params, skew):
    """The grouped product (interpreted) against the plain form, every
    held expert over every token under a mask: as routed, with ALL
    tokens' choices sent to ONE held expert (a capacity factor would drop
    most of them), and with nothing routed here."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((40, D)), jnp.float32)
    p = dict(params)
    if skew == "all_to_one":
        # expert 2's router row far above all: every token chooses it first
        p["layer1_router_weight"] = params["layer1_router_weight"].at[2].set(
            20.0 * x.mean(0) / jnp.linalg.norm(x.mean(0)) + 3.0)
        x = x + 4.0 * x.mean(0)
    spec = SPEC if skew != "none_here" else ModelSpec.resolve(
        dict(_model(), experts_held=[12, 4]), H)
    if skew == "none_here":
        # a component all tokens share, and the held experts' rows far
        # against it: nobody chooses experts 12-15
        u = jnp.ones((D,), jnp.float32) / np.float32(np.sqrt(D))
        x = x + 6.0 * u
        p["layer1_router_weight"] = params["layer1_router_weight"].at[12:].set(
            -50.0 * u)
    want, hit_w, here_w = experts.routed_ffn(spec, p, 1, x)
    got, hit, here = jax.jit(lambda x: moe_experts.routed_ffn(
        spec, p, 1, x, interpret=True))(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4,
                               atol=2e-5)
    assert (int(hit), int(here)) == (int(hit_w), int(here_w))
    if skew == "all_to_one":
        idx, _ = experts.route(spec, x, p["layer1_router_weight"])
        assert (np.asarray(idx)[:, 0] == 2).all() and int(here) >= 40
    if skew == "none_here":
        assert int(here) == 0 and int(hit) == 0
        # the shared experts alone: every token still has its result
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(experts.shared_ffn(p, 1, x)),
            rtol=1e-5, atol=1e-6)
    # positions that are nobody's assign nothing
    live = jnp.arange(40) < 25
    _, _, part = moe_experts.routed_ffn(spec, p, 1, x, live, interpret=True)
    assert int(part) <= int(here)


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The model-configs guide's test of the share: the routed parts of
    the four chips (experts 0-3, 4-7, 8-11, 12-15) and the shared
    experts, which every chip computes alike, counted ONCE, add up to the
    uncut reference's layer; no share alone does."""
    whole = ref.init_params(11, _cfg(held=EXPERTS), jnp.float32, std=0.2)
    rng = np.random.default_rng(6)
    h = jnp.asarray(rng.standard_normal((30, D)), jnp.float32)
    pre = "layer1_"
    layer = {k[len(pre):]: v for k, v in whole.items() if k.startswith(pre)}
    want = np.asarray(ref.moe_layer(h, layer, np.arange(EXPERTS), eps=1e-6,
                                    **ROUTING)) - np.asarray(h)
    x = decoder._rmsm(h, whole["layer1_ln2_gamma"], 1e-6)
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
            # the reference, given the same share, gives the same part
            s_ref, r_ref = ref.moe_layer(
                h, {k2[len(pre):]: v for k2, v in part.items()
                    if k2.startswith(pre)},
                np.arange(k * HELD, (k + 1) * HELD), eps=1e-6, parts=True,
                **ROUTING)
            np.testing.assert_allclose(np.asarray(out),
                                       np.asarray(s_ref + r_ref), rtol=2e-4,
                                       atol=2e-5)
        total += np.asarray(out) - shared
        heres.append(int(here))
        assert np.abs(np.asarray(out) - want).max() > 0.05
    np.testing.assert_allclose(total, want, rtol=2e-4, atol=2e-5)
    assert sum(heres) == 30 * TOPK          # every choice is somebody's


# ---------------------------------------------------------------------------
# the engine against the plain reference (logits, not tokens)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["dense", "flash_interpret"])
def test_engine_matches_the_reference_through_prefill_and_decode(params, impl):
    """Chunked prefill (boundaries at 1, C-1, C, C+1 and a prompt of four
    chunks) then decode through the latent pool: the reference's logit of
    every token the engine emitted is its maximum to within 1e-3 (float32
    on both sides; the block's logits agree to 2e-4 above)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, V, n).tolist()
               for n in (1, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 3)]
    new = 6
    eng = _engine(params, impl, max_batch=5)
    assert eng.latent and not eng.recurrent
    assert eng.latents.shape == (NL, 48, BS, 128) and len(eng._caches) == 1
    outs = _serve(eng, prompts, new)
    assert eng.alloc.num_used == 0
    eng.check_tables()
    toks = np.zeros((len(prompts), 40), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        toks[i, :len(p)] = p
        toks[i, len(p):len(p) + new - 1] = o[:-1]
    logits = np.asarray(ref.forward(params, toks, H, **ROUTING))
    assert logits.std() > 1.0
    for i, (p, o) in enumerate(zip(prompts, outs)):
        rows = logits[i, len(p) - 1:len(p) - 1 + new]
        deficit = rows.max(-1) - rows[np.arange(new), o]
        assert deficit.max() < 1e-3, (i, deficit)


def test_a_preempted_request_is_re_chunked_and_resumes_byte_identical(params):
    telemetry.reset_for_tests()
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, V, n).tolist() for n in (9, 19)]
    want = _serve(_engine(params), prompts, 12)
    eng = _engine(params)
    ids = [eng.submit(p, max_new_tokens=12, seed=100 + i)
           for i, p in enumerate(prompts)]
    for _ in range(7):
        eng.step()
    victim = eng.request(ids[1])
    assert 0 < len(victim.tokens) < 12
    eng._preempt(victim)
    assert victim.blocks == [] and eng.alloc.num_used == len(
        eng.request(ids[0]).blocks) > 0
    eng.check_tables()
    eng.run()
    assert [list(eng.request(i).tokens) for i in ids] == want
    assert eng.alloc.num_used == 0
    assert int(telemetry.counter("serve.preemptions").value()) == 1


def test_steady_state_runs_zero_traces_and_counts_what_the_experts_got(params):
    from mxnet_tpu.telemetry import tracing
    telemetry.reset_for_tests()
    eng = _engine(params, "flash_interpret")
    eng.warmup()
    before = dict(eng.trace_counts)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, V, n).tolist() for n in (3, 20, 8, 13, 30)]
    tracing.clear()
    tracing.configure(None, enable=True)
    try:
        together = _serve(eng, prompts, 9)
        spans = [ev for ev in tracing.tail(tracing._MAX_EVENTS)
                 if ev["name"] == "serve.decode"]
    finally:
        tracing.configure(None, enable=False)
    assert dict(eng.trace_counts) == before
    assert not eng.aot_stats["fallbacks"]
    # the decode spans carry the walk's and the experts' counts
    assert spans and all(
        {"live_blocks", "table_blocks", "experts_hit", "assigned_here"}
        <= set(ev["args"]) for ev in spans)
    for ev in spans:
        a = ev["args"]
        assert 0 <= a["experts_hit"] <= 2 * HELD
        assert a["experts_hit"] <= a["assigned_here"] <= a["active"] * TOPK * 2
    offered = int(telemetry.counter("serve.moe.assignments").value())
    here = int(telemetry.counter("serve.moe.assignments_here").value())
    assert offered == sum(ev["args"]["active"] for ev in spans) * TOPK * 2
    assert here == sum(ev["args"]["assigned_here"] for ev in spans)
    assert 0 < here < offered
    alone = [_serve(_engine(params, "flash_interpret"), [p], 9)[0]
             for p in prompts[:2]]
    assert together[:2] == alone


@pytest.mark.parametrize("option", [dict(prefix_cache=True),
                                    dict(speculate=True),
                                    dict(kv_quant="fp8")])
def test_options_the_latent_kind_refuses_say_so_by_name(params, option):
    name = next(iter(option))
    with pytest.raises(ServeError, match=f"EngineConfig.{name} is not served "
                       "on a latent cache"):
        _engine(params, **option)


def test_whole_prompt_prefill_and_a_wrong_share_are_refused(params):
    with pytest.raises(MXNetError, match="prefill_chunk > 0"):
        _engine(params, prefill_chunk=0)
    with pytest.raises(MXNetError, match="the description says experts_held"):
        _engine(params, model=_model(first=4))


def test_the_latent_row_is_counted_as_stored():
    assert kvcache.latent_lanes(576) == 640 and kvcache.latent_lanes(40) == 128
    assert kvcache.kv_bytes_per_token(5, 128, 192, dtype=jnp.bfloat16,
                                      latent_width=576) == 5 * 1280
    pool, = kvcache.make_pools(5, 3, 128, 128, 192, jnp.bfloat16,
                               latent_width=576)
    assert pool.shape == (5, 3, 128, 640) and pool.dtype == jnp.bfloat16
    with pytest.raises(MXNetError, match="not quantized"):
        kvcache.make_pools(5, 3, 128, 128, 192, quant="fp8", latent_width=576)
