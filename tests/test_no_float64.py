"""No serving program makes a float64 (ISSUE 25).

The package turns ``jax_enable_x64`` on (NDArray float64 is a reference
capability), so one ``np.float64`` scalar meeting an array — ``1.0 /
np.sqrt(d)`` is one — silently promotes everything after it.  A TPU has
no float64: XLA emulates it in ``while`` loops, and the prefill chunk's
attention ran 8x slower than its float32 for it.  Every program the
engine can make is traced here at a tiny size and its jaxpr walked,
sub-jaxprs included; the training step gets the same walk.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.analysis.program import iter_eqns
from mxnet_tpu.models.transformer import transformer_lm
from mxnet_tpu.serve import Engine, EngineConfig

V, NL, D, H = 61, 2, 32, 4


def _lm_params(seed=0):
    rng = np.random.RandomState(seed)
    sym = transformer_lm(vocab_size=V, num_layers=NL, d_model=D, heads=H,
                         batch_size=1, seq_len=8)
    shapes, _, _ = sym.infer_shape(data=(1, 8), softmax_label=(1, 8))
    return {n: (rng.randn(*s) * 0.05).astype(np.float32)
            for n, s in zip(sym.list_arguments(), shapes)
            if n not in ("data", "softmax_label")}


def float64_eqns(closed):
    """``(name_stack, primitive, shapes)`` of every equation of a closed
    jaxpr, at any depth, with a float64 (or complex128) output."""
    found = []
    for eqn, _ in iter_eqns(closed):
        wide = [v.aval for v in eqn.outvars
                if getattr(v.aval, "dtype", None) in (jnp.float64,
                                                      jnp.complex128)]
        if wide:
            found.append((str(eqn.source_info.name_stack),
                          eqn.primitive.name,
                          [("~" if a.weak_type else "") + a.str_short()
                           for a in wide]))
    return found


def _assert_no_float64(closed, what):
    found = float64_eqns(closed)
    assert not found, (
        f"{what}: {len(found)} equations make a float64; the first is "
        f"`{found[0][1]}` -> {found[0][2]} under name_stack "
        f"'{found[0][0]}'")


_ENGINE = dict(heads=H, block_size=4, num_blocks=64, max_batch=4,
               max_prompt_len=16, max_seq_len=48, prompt_bucket_min=8)
_POOLS = {"plain": None, "fp8": "fp8"}
_MAKERS = {"prefill": "_make_prefill_fn",
           "prefill_chunk": "_make_chunk_prefill_fn",
           "decode": "_make_decode_fn",
           "verify": "_make_verify_fn",
           "draft": "_make_draft_fn"}
# the attention strategy only reaches the decode program; the others
# have one formulation each
_CASES = ([("decode", pool, impl) for pool in _POOLS
           for impl in ("scan", "dense", "flash_interpret")]
          + [(kind, pool, "dense") for pool in _POOLS
             for kind in ("prefill", "prefill_chunk", "verify")]
          + [("draft", "plain", "dense")])


# a recurrent-state model (ISSUE 26): the block with RMSNorm, q/k norms,
# RoPE, a gated SiLU FFN and power retention; its two programs
_RETENTION = dict(kv_heads=2, head_dim=8, norm="rmsnorm", norm_eps=1e-6,
                  qk_norm=True, bias=False, ffn="silu_gated",
                  position="rope", rope_theta=1e6,
                  attention="power_retention")


def _retention_params(seed=0):
    rng = np.random.RandomState(seed)
    kv, hd, f = 2, 8, 48
    shapes = {"embed_weight": (V, D), "final_ln_gamma": (D,),
              "lm_head_weight": (V, D)}
    for i in range(NL):
        p = f"layer{i}_"
        shapes.update({
            p + "q_weight": (H * hd, D), p + "k_weight": (kv * hd, D),
            p + "v_weight": (kv * hd, D), p + "proj_weight": (D, H * hd),
            p + "gate_weight": (kv, D), p + "gate_bias": (kv,),
            p + "q_norm_gamma": (hd,), p + "k_norm_gamma": (hd,),
            p + "ffn_gate_weight": (f, D), p + "ffn_up_weight": (f, D),
            p + "ffn_down_weight": (D, f), p + "ln1_gamma": (D,),
            p + "ln2_gamma": (D,)})
    return {n: (rng.randn(*s) * 0.05).astype(np.float32)
            for n, s in shapes.items()}


@pytest.mark.parametrize("kind,impl", [("prefill_chunk", "dense"),
                                       ("decode", "dense"),
                                       ("decode", "flash_interpret")])
def test_recurrent_state_program_makes_no_float64(kind, impl):
    eng = Engine(_retention_params(), EngineConfig(
        heads=H, model=_RETENTION, num_blocks=5, max_batch=4,
        max_prompt_len=16, max_seq_len=48, prefill_chunk=8, attn_impl=impl))
    bucket = 8 if kind == "prefill_chunk" else 4
    fn = getattr(eng, _MAKERS[kind])(bucket)
    closed = jax.make_jaxpr(fn)(*eng._avals(kind, bucket))
    assert len(closed.jaxpr.eqns) > 20, "nothing was traced"
    _assert_no_float64(closed, f"{kind}@{bucket} (recurrent state, {impl})")


# a latent model with routed experts (ISSUE 35): low-rank queries, one
# cached row a position, YaRN rotary channels, a dense layer then a
# routed one; its two programs, with the XLA readers and with both
# kernels interpreted
_LATENT = dict(norm="rmsnorm", norm_eps=1e-6, bias=False, ffn="silu_gated",
               position="rope", rope_theta=10000.0, attention="latent",
               q_lora_rank=12, kv_lora_rank=16, qk_nope_head_dim=8,
               qk_rope_head_dim=4, v_head_dim=8,
               rope_scaling={"type": "yarn", "factor": 40, "beta_fast": 32,
                             "beta_slow": 1, "mscale": 0.707,
                             "mscale_all_dim": 0.707,
                             "original_max_position_embeddings": 4096},
               ffn_layers=["dense", "routed"], n_routed_experts=8,
               experts_per_token=2, n_group=4, topk_group=2,
               routed_scaling_factor=16.0, experts_held=[2, 4])


def _latent_params(seed=0):
    rng = np.random.RandomState(seed)
    f, fe = 48, 16
    shapes = {"embed_weight": (V, D), "final_ln_gamma": (D,),
              "lm_head_weight": (V, D)}
    for i in range(NL):
        p = f"layer{i}_"
        shapes.update({
            p + "q_a_weight": (12, D), p + "q_a_norm_gamma": (12,),
            p + "q_b_weight": (H * 12, 12), p + "kv_a_weight": (20, D),
            p + "kv_a_norm_gamma": (16,), p + "kv_b_weight": (H * 16, 16),
            p + "proj_weight": (D, H * 8), p + "ln1_gamma": (D,),
            p + "ln2_gamma": (D,)})
    shapes.update({
        "layer0_ffn_gate_weight": (f, D), "layer0_ffn_up_weight": (f, D),
        "layer0_ffn_down_weight": (D, f), "layer1_router_weight": (8, D),
        "layer1_shared_gate_weight": (2 * fe, D),
        "layer1_shared_up_weight": (2 * fe, D),
        "layer1_shared_down_weight": (D, 2 * fe),
        "layer1_experts_gate_weight": (4, D, fe),
        "layer1_experts_up_weight": (4, D, fe),
        "layer1_experts_down_weight": (4, fe, D)})
    return {n: (rng.randn(*s) * 0.05).astype(np.float32)
            for n, s in shapes.items()}


@pytest.mark.parametrize("kind,impl", [("prefill_chunk", "dense"),
                                       ("prefill_chunk", "flash_interpret"),
                                       ("decode", "dense"),
                                       ("decode", "flash_interpret")])
def test_latent_program_makes_no_float64(kind, impl):
    eng = Engine(_latent_params(), EngineConfig(
        heads=H, model=_LATENT, block_size=4, num_blocks=24, max_batch=4,
        max_prompt_len=16, max_seq_len=48, prefill_chunk=8, attn_impl=impl))
    bucket = 8 if kind == "prefill_chunk" else 4
    fn = getattr(eng, _MAKERS[kind])(bucket)
    closed = jax.make_jaxpr(fn)(*eng._avals(kind, bucket))
    assert len(closed.jaxpr.eqns) > 20, "nothing was traced"
    _assert_no_float64(closed, f"{kind}@{bucket} (latent pool, {impl})")


# the same latent model on four residual streams joined by
# hyper-connections: two connections a layer, their maps by
# ``hc_maps`` (dense) or the kernel ``mxtpu_hc_mix`` (interpreted)
@pytest.mark.parametrize("kind,impl", [("prefill_chunk", "dense"),
                                       ("prefill_chunk", "flash_interpret"),
                                       ("decode", "dense"),
                                       ("decode", "flash_interpret")])
def test_hyper_connection_program_makes_no_float64(kind, impl):
    params = _latent_params()
    rng = np.random.RandomState(1)
    for i in range(NL):
        for which in ("attn", "ffn"):
            p = f"layer{i}_hc_{which}_"
            params[p + "weight"] = (rng.randn(24, 4 * D) * 0.05).astype(
                np.float32)
            params[p + "bias"] = np.zeros((24,), np.float32)
            params[p + "scale"] = np.full((3,), 0.4, np.float32)
    eng = Engine(params, EngineConfig(
        heads=H, model=dict(_LATENT, hc_mult=4), block_size=4,
        num_blocks=24, max_batch=4, max_prompt_len=16, max_seq_len=48,
        prefill_chunk=8, attn_impl=impl))
    bucket = 8 if kind == "prefill_chunk" else 4
    fn = getattr(eng, _MAKERS[kind])(bucket)
    closed = jax.make_jaxpr(fn)(*eng._avals(kind, bucket))
    assert len(closed.jaxpr.eqns) > 20, "nothing was traced"
    _assert_no_float64(closed, f"{kind}@{bucket} (streams, {impl})")


# window and global softmax layers with grouped heads and sigmoid-routed
# experts (ISSUE 37): rotary positions on the window layer only, q/k
# norms, the output gate, sandwich norms, a scaled embedding, a dense
# layer then a routed one with a selection bias; its two programs, with
# the XLA readers and with both kernels interpreted
_WINDOW = dict(kv_heads=2, head_dim=8, norm="rmsnorm", norm_eps=1e-5,
               qk_norm=True, bias=False, ffn="silu_gated",
               position="rope_sliding", rope_theta=10000.0,
               attention=["sliding", "softmax"], sliding_window=12,
               attn_gate=True, sandwich_norm=True, embed_scale=5.657,
               ffn_layers=["dense", "routed"], n_routed_experts=8,
               experts_per_token=2, routed_scaling_factor=2.448,
               norm_topk_prob=True, experts_held=[2, 4],
               score_func="sigmoid", router_bias=True)


def _window_params(seed=0):
    shapes = _retention_params(seed)
    for i in range(NL):
        p = f"layer{i}_"
        for gone in ("gate_weight", "gate_bias"):
            del shapes[p + gone]
        shapes[p + "attn_gate_weight"] = shapes[p + "q_weight"]
        shapes[p + "post_attn_norm_gamma"] = shapes[p + "ln1_gamma"]
        shapes[p + "post_ffn_norm_gamma"] = shapes[p + "ln2_gamma"]
    latent = _latent_params(seed)
    for k in list(shapes):
        if k.startswith("layer1_ffn_"):
            del shapes[k]
    shapes.update({k: v for k, v in latent.items()
                   if k.startswith(("layer1_shared", "layer1_experts",
                                    "layer1_router"))})
    shapes["layer1_router_bias"] = np.zeros((8,), np.float32)
    return shapes


@pytest.mark.parametrize("kind,impl", [("prefill_chunk", "dense"),
                                       ("prefill_chunk", "flash_interpret"),
                                       ("decode", "dense"),
                                       ("decode", "flash_interpret")])
def test_window_program_makes_no_float64(kind, impl):
    eng = Engine(_window_params(), EngineConfig(
        heads=H, model=_WINDOW, block_size=4, num_blocks=24, max_batch=4,
        max_prompt_len=16, max_seq_len=48, prefill_chunk=8, attn_impl=impl))
    assert eng.described_kv
    bucket = 8 if kind == "prefill_chunk" else 4
    fn = getattr(eng, _MAKERS[kind])(bucket)
    closed = jax.make_jaxpr(fn)(*eng._avals(kind, bucket))
    assert len(closed.jaxpr.eqns) > 20, "nothing was traced"
    _assert_no_float64(closed, f"{kind}@{bucket} (window + global, {impl})")


@pytest.mark.parametrize("kind,pool,impl", _CASES,
                         ids=["-".join(c) for c in _CASES])
def test_serving_program_makes_no_float64(kind, pool, impl):
    over = dict(_ENGINE, attn_impl=impl, kv_quant=_POOLS[pool])
    kw = {}
    if kind == "prefill_chunk":
        over["prefill_chunk"] = 8
    if kind in ("verify", "draft"):
        over.update(speculate=True, spec_k=3)
    if kind == "draft":
        over["spec_draft"] = "model"
        kw = dict(draft_params=_lm_params(seed=7), draft_heads=H)
    eng = Engine(_lm_params(), EngineConfig(**over), **kw)
    bucket = 8 if kind.startswith("prefill") else 4
    fn = getattr(eng, _MAKERS[kind])(bucket)
    closed = jax.make_jaxpr(fn)(*eng._avals(kind, bucket))
    assert len(closed.jaxpr.eqns) > 20, "nothing was traced"
    _assert_no_float64(closed, f"{kind}@{bucket} ({pool} pools, {impl})")


def test_the_walk_sees_a_float64_inside_a_sub_jaxpr():
    """The walker's own check: a float64 made two levels down, under a
    name scope, is found and named."""
    def f(x):
        def body(c, _):
            with jax.named_scope("attn"):
                return (c * (1.0 / np.sqrt(64))).astype(c.dtype), None
        return jax.lax.scan(body, x, None, length=2)[0]

    found = float64_eqns(jax.make_jaxpr(jax.jit(f))(
        jax.ShapeDtypeStruct((4,), jnp.float32)))
    assert found and any(p == "mul" and "attn" in ns for ns, p, _ in found)
    with pytest.raises(AssertionError, match="name_stack '.*attn"):
        _assert_no_float64(jax.make_jaxpr(f)(
            jax.ShapeDtypeStruct((4,), jnp.float32)), "f")


def _train_step_jaxpr(symbol, shapes):
    """The closed jaxpr of ``ShardedTrainer``'s step as the benchmark's
    training cell configures it: sgd momentum, bf16 AMP."""
    from mxnet_tpu.parallel import ShardedTrainer, make_mesh
    tr = ShardedTrainer(symbol, mesh=make_mesh({"data": 1}, jax.devices()[:1]), optimizer="sgd",
                        optimizer_params={"learning_rate": 0.1,
                                          "momentum": 0.9, "wd": 1e-4},
                        compute_dtype="bfloat16",
                        matmul_precision="bfloat16")
    tr.bind(data_shapes={k: v for k, v in shapes.items()
                         if k != "softmax_label"},
            label_shapes={"softmax_label": shapes["softmax_label"]})
    return tr.trace_program("train")[0].jaxpr


def _convnet():
    data = mx.sym.Variable("data")
    net = mx.sym.Convolution(data, num_filter=4, kernel=(3, 3), pad=(1, 1),
                             no_bias=True, name="c1")
    net = mx.sym.BatchNorm(net, name="bn1")
    net = mx.sym.Activation(net, act_type="relu")
    net = mx.sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = mx.sym.Pooling(net, kernel=(4, 4), global_pool=True,
                         pool_type="avg")
    net = mx.sym.FullyConnected(mx.sym.Flatten(net), num_hidden=5, name="fc")
    return (mx.sym.SoftmaxOutput(net, name="softmax"),
            {"data": (4, 3, 8, 8), "softmax_label": (4,)})


def _lm():
    return (transformer_lm(vocab_size=V, num_layers=1, d_model=D, heads=H,
                           batch_size=2, seq_len=8),
            {"data": (2, 8), "softmax_label": (2, 8)})


@pytest.mark.parametrize("make", [_convnet, _lm], ids=["convnet", "lm"])
def test_training_step_makes_no_float64_array(make):
    """The same walk over ``ShardedTrainer``'s step, report first: what
    it finds is printed (``-s``), and PERF.md section 7 has it.

    Left out of the assertion: weakly typed float64 SCALARS.  The
    learning rate enters the step as a Python float, which under x64 is
    a weak ``float64[]`` argument, and ``lr * lr_mult`` (one ``mul``) is
    one too; it takes the dtype of whatever array it meets, so nothing
    array-sized widens.  Making it float32 changes the step's call
    signature (``_program_call_args``, every AOT cache key): not this
    PR's (ISSUE 25), and it owns none of ResNet-50's ``reshape``/``copy``.
    """
    closed = _train_step_jaxpr(*make())
    found = float64_eqns(closed)
    for name_stack, prim, shapes in found:
        print(f"float64 in the train step: `{prim}` -> {shapes} "
              f"under '{name_stack}'")
    arrays = [f for f in found if f[2] != ["~float64[]"]]
    assert not arrays, arrays[0]
