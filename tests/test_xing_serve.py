"""A model whose residual path is FOUR streams joined by
manifold-constrained hyper-connections, on ``serve.Engine``'s normal
path: Xing4.0-29B-A4B's block (latent attention, one leading dense layer,
then sigmoid-routed experts with a selection bias beside a shared expert,
two connections a layer whose H_res is 20 Sinkhorn iterations of a
clamped ``exp``) at a tiny size on the CPU, against the benchmark's plain
float32 reference; the connection kernel ``mxtpu_hc_mix`` (interpreted)
against the plain form and float64 numpy; and that a description
without ``hc_mult`` is the program it was."""
import hashlib
import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models import decoder
from mxnet_tpu.models.decoder import ModelSpec
from mxnet_tpu.serve import Engine, EngineConfig, kvcache
from mxnet_tpu.serve.hc_mix import hc_mix

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:          # the reference loads its siblings by name
    sys.path.insert(0, REPO)


def _reference():
    """``benchmark/reference/xing4.py`` by path: it imports nothing from
    the program, and the program nothing from it."""
    spec = importlib.util.spec_from_file_location(
        "xing4_reference",
        os.path.join(REPO, "benchmark", "reference", "xing4.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()
V, NL, D, H, N, ITERS = 96, 3, 64, 4, 4, 20
QR, RANK, NOPE, ROPE, VD, F, FE = 24, 32, 16, 8, 16, 128, 32
EXPERTS, TOPK = 8, 2
CHUNK, BS = 8, 8
YARN = dict(ref.PUBLISHED["rope_scaling"])
CFG = dict(vocab_size=V, num_hidden_layers=NL, hidden_size=D,
           num_attention_heads=H, q_lora_rank=QR, kv_lora_rank=RANK,
           qk_nope_head_dim=NOPE, qk_rope_head_dim=ROPE, v_head_dim=VD,
           intermediate_size=F, moe_intermediate_size=FE,
           n_routed_experts=EXPERTS, n_shared_experts=1,
           first_k_dense_replace=1, hc_mult=N)
MODEL = dict(norm="rmsnorm", norm_eps=1e-6, bias=False, ffn="silu_gated",
             position="rope", rope_theta=10000.0, attention="latent",
             q_lora_rank=QR, kv_lora_rank=RANK, qk_nope_head_dim=NOPE,
             qk_rope_head_dim=ROPE, v_head_dim=VD, rope_scaling=YARN,
             ffn_layers=["dense", "routed", "routed"],
             n_routed_experts=EXPERTS, experts_per_token=TOPK,
             score_func="sigmoid", router_bias=True, norm_topk_prob=True,
             routed_scaling_factor=2.0, hc_mult=N, hc_sinkhorn_iters=ITERS,
             hc_eps=1e-6, hc_clamp=30.0)
SPEC = ModelSpec.resolve(MODEL, H)
SETTINGS = dict(top_k=TOPK)


@pytest.fixture(scope="module")
def params():
    # std 0.2, not 0.02: logits of std ~1.7 at this width, so a wrong
    # cache row, stream or map moves them far past the tolerance; the
    # maps' scales keep their logits near std 1 at this width
    return ref.init_params(3, CFG, jnp.float32, std=0.2,
                           hc_scale=(0.15, 0.15, 0.3))


def _engine(params, impl="dense", **over):
    cfg = dict(heads=H, model=MODEL, block_size=BS, num_blocks=48,
               max_batch=5, max_prompt_len=40, max_seq_len=64,
               prefill_chunk=CHUNK, attn_impl=impl)
    cfg.update(over)
    return Engine(params, EngineConfig(**cfg))


def _serve(eng, prompts, new):
    ids = [eng.submit(p, max_new_tokens=new, seed=100 + i)
           for i, p in enumerate(prompts)]
    eng.run()
    return [list(eng.request(i).tokens) for i in ids]


def _maps64(x, w, b, a, eps=1e-6, iters=ITERS, hc_eps=1e-6, clamp=30.0):
    """The connection's maps in float64 numpy: x [T, n, d]."""
    x = np.asarray(x, np.float64)
    t, n, d = x.shape
    flat = x.reshape(t, n * d)
    h = flat @ np.asarray(w, np.float64).T / np.sqrt(
        np.mean(flat ** 2, axis=1, keepdims=True) + eps)
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    sig = lambda z: 1.0 / (1.0 + np.exp(-z))    # noqa: E731
    pre = sig(a[0] * h[:, :n] + b[:n])
    post = 2.0 * sig(a[1] * h[:, n:2 * n] + b[n:2 * n])
    m = np.exp(np.clip(a[2] * h[:, 2 * n:] + b[2 * n:], -clamp,
                       clamp)).reshape(t, n, n)
    for _ in range(iters):
        m = m / (m.sum(-1, keepdims=True) + hc_eps)
        m = m / (m.sum(-2, keepdims=True) + hc_eps)
    return np.einsum("tj,tjd->td", pre, x), pre, post, m


# ---------------------------------------------------------------------------
# the description: nothing changes where there is no hc_mult
# ---------------------------------------------------------------------------

#: sha256 of ``str(jax.make_jaxpr(decoder_forward))`` for each serving
#: configuration's description at its rehearsal sizes, and of its
#: ``signature()``, as the code before hyper-connections made them
BEFORE = {
    "nope-lm-2048x24": (
        "7844823fbbf5581908b08a899955f2544eb9e222f7f7c67f2165da1ee5c5def3",
        "e3b0c44298fc1c14"),
    "brumby-14b-6of40": (
        "7bc4ad84a0fb1df98a14d90a2c8e4eeb73803ecf03ffe9453abed976efd10729",
        "552351d4343691db"),
    "deepseek-v2-ep4-5of60": (
        "9b37ade103dc8727a699d9967dc96dc90928269555a2f56c4593adf44d1cf89b",
        "95d5b7c2837f96e7"),
    "trinity-large-ep8-5of60": (
        "e99cb8572d9d0dd4d41e46ccd6e95d9eb8b6578450cc962ea888174c60cac850",
        "79f83c914e9464ea"),
}


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_a_description_without_hc_mult_traces_what_it_did(name):
    """``hc_mult=None`` (every existing description) leaves the
    program-cache signature and ``decoder_forward``'s jaxpr byte for byte
    what they were before the streams existed."""
    from benchmark.harness import spec
    from benchmark.run import merged

    cfg = spec.load_config(spec.load_benchmark(), name)
    cfg = merged(cfg, cfg.get("rehearsal", {}))
    model = ModelSpec.resolve(cfg["serve"]["engine"].get("model"),
                              cfg["num_attention_heads"])
    assert model.hc_mult is None
    shapes = spec.load_module("reference", cfg["family"]).param_shapes(cfg)
    sds = {k: jax.ShapeDtypeStruct(s, jnp.float32) for k, s in shapes.items()}
    sds["experts_held"] = jax.ShapeDtypeStruct((1,), jnp.int32)
    vd = model.v_head_dim

    def attend(i, kind, q, k, v, g):
        return q[..., :vd] if kind == "latent" else q

    i32 = jax.ShapeDtypeStruct((2, 8), jnp.int32)
    text = str(jax.make_jaxpr(lambda p, t, s: decoder.decoder_forward(
        model, p, t, s, attend))(sds, i32, i32))
    digest = hashlib.sha256(text.encode()).hexdigest()
    signed = hashlib.sha256(model.signature().encode()).hexdigest()[:16]
    assert (digest, signed) == BEFORE[name]


def test_the_description_states_its_streams_and_refuses_bad_ones():
    sig = SPEC.signature()
    assert "hc_mult=4" in sig and "hc_sinkhorn_iters" not in sig
    assert ModelSpec.resolve(dict(MODEL, hc_sinkhorn_iters=10),
                             H).signature() != sig
    for bad in (dict(hc_mult=0), dict(hc_sinkhorn_iters=0),
                dict(hc_eps=0.0), dict(hc_clamp=-1.0)):
        with pytest.raises(MXNetError, match="hyper-connections need"):
            ModelSpec.resolve(dict(MODEL, **bad), H)
    assert ref.param_count(CFG) == sum(
        int(np.prod(s)) for s in ref.param_shapes(CFG).values())
    assert ref.param_shapes(CFG)["layer1_hc_ffn_weight"] == (
        2 * N + N * N, N * D)


# ---------------------------------------------------------------------------
# the connection kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("rows,dtype", [(5, jnp.float32), (130, jnp.float32),
                                        (37, jnp.bfloat16)])
def test_hc_mix_matches_the_plain_form_and_float64(rows, dtype):
    """``mxtpu_hc_mix`` (interpreted; rows that are not a tile, and more
    than one tile) against ``decoder.hc_maps`` and float64 numpy; H_res
    is doubly stochastic: each column sums to 1 within ``n x hc_eps``
    plus float32 rounding (2e-5), each row as float64's does, within
    1e-3 of 1 after 20 iterations."""
    rng = np.random.default_rng(rows)
    m = 2 * N + N * N
    x = jnp.asarray(rng.standard_normal((rows, N, D)) * 0.7, dtype)
    w = jnp.asarray(rng.standard_normal((m, N * D)) * 0.2, jnp.float32)
    b = jnp.asarray(rng.standard_normal(m) * 0.3, jnp.float32)
    a = jnp.asarray([0.15, 0.15, 0.3], jnp.float32)
    got = jax.jit(lambda *z: hc_mix(SPEC, *z, interpret=True))(x, w, b, a)
    plain = decoder.hc_maps(SPEC, x, w, b, a)
    want = _maps64(np.asarray(x.astype(jnp.float32)), w, b, a)
    for g, p, q in zip(got, plain, want):
        assert g.shape == p.shape and g.dtype == p.dtype
        g32 = np.asarray(g.astype(jnp.float32))
        np.testing.assert_allclose(g32, np.asarray(p.astype(jnp.float32)),
                                   rtol=1e-5, atol=1e-5 if g.ndim == 2 and
                                   dtype == jnp.bfloat16 else 1e-6)
    for g, q in zip(got[1:], want[1:]):
        assert np.max(np.abs(np.asarray(g) - q)) / np.max(np.abs(q)) < 2e-6
    u = np.asarray(got[0].astype(jnp.float32))
    tol = 1e-2 if dtype == jnp.bfloat16 else 1e-5      # u in x's type
    np.testing.assert_allclose(u, want[0], rtol=tol, atol=tol)
    res = np.asarray(got[3])
    assert np.abs(res.sum(-2) - 1).max() < 2e-5
    # rows: the last normalisation is the columns', and 20 iterations
    # leave the rows as near 1 as the mathematics does (float64 too)
    assert np.abs(res.sum(-1) - want[3].sum(-1)).max() < 2e-5
    assert np.abs(res.sum(-1) - 1).max() < 1e-3
    # neither uniform nor one-hot nor the identity
    assert 0.02 < res.std() and res.max() < 0.99
    pre, post = np.asarray(got[1]), np.asarray(got[2])
    assert 0.0 < pre.min() and pre.max() < 1.0
    assert 0.0 < post.min() and post.max() < 2.0


# ---------------------------------------------------------------------------
# the model against the plain reference (logits)
# ---------------------------------------------------------------------------

def test_block_math_matches_the_reference_logits(params):
    """``decoder_forward`` over a plain whole-prefix latent attention (no
    pool) against the reference's full forward: the streams, both
    connections of every layer, the collapse and the head."""
    rng = np.random.default_rng(1)
    toks = rng.integers(1, V, (2, 11)).astype(np.int32)
    positions = jnp.broadcast_to(jnp.arange(11, dtype=jnp.int32), (2, 11))
    rows = {}

    def attend(i, kind, q, row, v, g):
        rows[i] = row
        c = row[..., :RANK]
        k_r = row[..., RANK:]
        w = params[f"layer{i}_kv_b_weight"].reshape(H, NOPE + VD, RANK)
        kn = jnp.einsum("blr,hdr->blhd", c, w[:, :NOPE],
                        precision="highest")
        val = jnp.einsum("blr,hdr->blhd", c, w[:, NOPE:],
                         precision="highest")
        s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :NOPE], kn,
                        precision="highest")
             + jnp.einsum("bqhd,bkd->bhqk", q[..., NOPE:], k_r,
                          precision="highest")) * SPEC.latent_scale()
        causal = jnp.arange(11)[:, None] >= jnp.arange(11)[None, :]
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, val, precision="highest")

    got = np.asarray(decoder.decoder_forward(SPEC, params, jnp.asarray(toks),
                                             positions, attend))
    want = np.asarray(ref.forward(params, toks, H, **SETTINGS))
    assert want.std() > 1.0
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    # the streams exist only inside the program: the cache is the row
    assert rows[0].shape == (2, 11, RANK + ROPE)


@pytest.mark.parametrize("impl", ["dense", "flash_interpret"])
def test_engine_matches_the_reference_through_prefill_and_decode(params, impl):
    """Chunked prefill (boundaries at 1, C-1, C, C+1 and a prompt past
    three chunks) then decode through the latent pool, the connections
    by ``hc_maps`` (dense) or ``mxtpu_hc_mix`` (interpreted): the
    reference's logit of every token the engine emitted is its maximum
    to within 1e-3 (float32 on both sides; the block's logits agree to
    2e-4 above)."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, V, n).tolist()
               for n in (1, CHUNK - 1, CHUNK, CHUNK + 1, 3 * CHUNK + 3)]
    new = 6
    eng = _engine(params, impl)
    assert eng.latent and eng.latents.shape == (NL, 48, BS, 128)
    assert (eng._mix() is None) == (impl == "dense")
    outs = _serve(eng, prompts, new)
    assert eng.alloc.num_used == 0
    eng.check_tables()
    toks = np.zeros((len(prompts), 40), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outs)):
        toks[i, :len(p)] = p
        toks[i, len(p):len(p) + new - 1] = o[:-1]
    logits = np.asarray(ref.forward(params, toks, H, **SETTINGS))
    assert logits.std() > 1.0
    for i, (p, o) in enumerate(zip(prompts, outs)):
        rows = logits[i, len(p) - 1:len(p) - 1 + new]
        deficit = rows.max(-1) - rows[np.arange(new), o]
        assert deficit.max() < 1e-3, (i, deficit)


def test_spans_say_how_many_connections_a_program_runs(params):
    """The prefill and decode spans of a model with connections carry
    ``hc_calls`` (two a layer) and ``hc_streams``; steady state traces
    nothing new; a row served among others is the row served alone."""
    from mxnet_tpu.telemetry import tracing
    telemetry.reset_for_tests()
    eng = _engine(params)
    eng.warmup()
    before = dict(eng.trace_counts)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, V, n).tolist() for n in (3, 20, 9)]
    tracing.clear()
    tracing.configure(None, enable=True)
    try:
        together = _serve(eng, prompts, 7)
        spans = [ev for ev in tracing.tail(tracing._MAX_EVENTS)
                 if ev["name"] in ("serve.decode", "serve.prefill")]
    finally:
        tracing.configure(None, enable=False)
    assert dict(eng.trace_counts) == before
    assert {ev["name"] for ev in spans} == {"serve.decode", "serve.prefill"}
    for ev in spans:
        assert ev["args"]["hc_calls"] == 2 * NL
        assert ev["args"]["hc_streams"] == N
    assert together[:2] == [_serve(_engine(params), [p], 7)[0]
                            for p in prompts[:2]]
    # a model without connections says nothing of them
    assert _engine(params)._hc_args and not Engine(
        {k: v for k, v in params.items() if "_hc_" not in k},
        EngineConfig(heads=H, model=dict(MODEL, hc_mult=None),
                     block_size=BS, num_blocks=48, max_batch=5,
                     max_prompt_len=40, max_seq_len=64,
                     prefill_chunk=CHUNK))._hc_args
    assert kvcache.CacheSpec.for_attention(
        SPEC.layer_kinds(NL)).kind == kvcache.PAGED_LATENT
