"""What ISSUE 26's two cells add to the benchmark: the loss reference and
the job of ``lm-train-4chip``, the plain reference of ``brumby``, and the
new per-layer readers on hand-made traces (each returns None, and does
not raise, where there is nothing for it to read: a parent commit, or a
cell of another kind)."""
import numpy as np
import pytest

import jax.numpy as jnp

from benchmark.harness import spec, trace

brumby = spec.load_module("reference", "brumby")
lm_loss = spec.load_module("reference", "nope_lm_loss")
JOB = spec.load_traffic("lm-tokens-8x2048")
GEN = spec.load_traffic("gen-closed-16")
BRUMBY = spec.load_json(spec.BENCH_DIR + "/configs/brumby-14b-6of40.json")
PEAKS = spec.load_peaks("TPU v5 lite")


def summary(op_seconds, op_calls=None, chips=1, busy_s=1.0, modules=None,
            kernels=None):
    return trace.Summary(
        chips=chips, window_s=2.0, busy_s=busy_s, op_seconds=op_seconds,
        op_calls=op_calls or {}, gap_seconds_by_region={},
        module_ms=modules or {}, module_kernels=kernels or {})


# -- lm-train-4chip ----------------------------------------------------------

def test_the_lm_needs_9_07_gflop_a_token():
    cfg = {"train": JOB["train"]}
    assert lm_loss.train_flops_per_token(cfg, 2048) / 1e9 == \
        pytest.approx(9.07, abs=0.005)
    flops = lm_loss.train_flops_per_step(cfg, {"data": (8, 2048)})
    assert flops / 1e12 == pytest.approx(148.7, abs=0.1)
    k = JOB["train"]["symbol"]["kwargs"]
    lm = spec.load_json(spec.BENCH_DIR + "/configs/nope-lm-2048x24.json")
    assert (k["d_model"], k["num_layers"], k["heads"], k["vocab_size"]) == (
        lm["hidden_size"], lm["num_hidden_layers"],
        lm["num_attention_heads"], lm["vocab_size"])
    assert JOB["train"]["mesh"] == {"data": 2, "model": 2}


def test_next_token_batches_are_the_sequence_shifted_by_one():
    runner = spec.load_module("runners", "train_sharded_lm")
    inputs = {"data": {"shape": [2, 5], "kind": "next_token", "high": 11},
              "softmax_label": {"shape": [2, 5], "kind": "next_token_label"}}
    a = runner.NextTokenTraffic.batch_arrays(inputs, 3, 2**31 + 9)
    assert a["data"].shape == a["softmax_label"].shape == (6, 5)
    assert a["data"].dtype == np.float32 and a["data"].max() < 11
    assert np.array_equal(a["data"][:, 1:], a["softmax_label"][:, :-1])
    b = runner.NextTokenTraffic.batch_arrays(inputs, 3, 2**31 + 9)
    assert np.array_equal(a["data"], b["data"])
    # the runner leaves the module it borrows the loop from as it found it
    ts = runner.train_sharded
    assert ts.build_trainer is not runner.build_trainer
    assert ts.traffic.__name__.endswith("traffic")


def test_the_loss_reference_is_the_mean_next_token_cross_entropy():
    nope = spec.load_module("reference", "nope_lm")
    cfg = {"vocab_size": 31, "num_hidden_layers": 1, "hidden_size": 16,
           "num_attention_heads": 2, "ffn_dim": 64,
           "train": {"label_name": "softmax_label",
                     "symbol": {"kwargs": {"heads": 2}}}}
    params = nope.init_params(5, cfg)
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 31, (3, 9))
    batch = {"data": ids[:, :-1].astype(np.float32),
             "softmax_label": ids[:, 1:].astype(np.float32)}
    logits = np.asarray(nope.forward(params, ids[:, :-1], 2), np.float64)
    logp = logits - np.log(np.exp(logits).sum(-1, keepdims=True))
    want = -np.take_along_axis(logp, ids[:, 1:, None], -1).mean()
    assert lm_loss.reference_loss(params, batch, cfg) == pytest.approx(
        want, rel=1e-5)
    assert lm_loss.program_loss(np.full((24,), 2.5, np.float32), batch,
                                cfg) == pytest.approx(2.5)


def test_the_first_update_is_adams_on_the_float32_gradient():
    """Backwards block by block gives ``jax.grad`` of the whole forward
    pass; the objective is the cross-entropy summed over positions and
    averaged over sequences; the batch's first half alone is kept too."""
    import jax
    nope = spec.load_module("reference", "nope_lm")
    cfg = {"vocab_size": 31, "num_hidden_layers": 4, "hidden_size": 16,
           "num_attention_heads": 2, "ffn_dim": 64, "first_update": {},
           "train": {"label_name": "softmax_label",
                     "optimizer_params": {"learning_rate": 1e-2},
                     "symbol": {"kwargs": {"heads": 2}}}}
    params = {k: np.asarray(v) for k, v in nope.init_params(5, cfg).items()}
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 31, (6, 9))
    batch = {"data": ids[:, :-1].astype(np.float32),
             "softmax_label": ids[:, 1:].astype(np.float32)}

    def objective(p, rows):
        logp = jax.nn.log_softmax(nope.forward(p, ids[rows, :-1], 2))
        return -jnp.sum(jnp.take_along_axis(
            logp, jnp.asarray(ids[rows, 1:, None]), -1)) / len(ids[rows])

    loss = lm_loss.reference_loss(params, batch, cfg)
    out = cfg["first_update"]
    assert loss == pytest.approx(
        float(objective(params, slice(0, 6))) / 8, rel=1e-5)
    assert sorted({k.split("_")[0] for k in out["want"]}) == [
        "final", "layer0", "layer2", "layer3", "lm"]
    for rows, key in ((slice(0, 6), "want"), (slice(0, 3), "want_half")):
        g = jax.grad(objective)(params, rows)
        for k, got in out[key].items():
            want = np.asarray(lm_loss.adam_first_update(g[k], 1e-2))
            # every element moves by lr, but for those whose gradient is
            # a rounding error of zero
            sure = np.abs(np.asarray(g[k])) > 1e-6
            assert np.allclose(got[sure], want[sure], rtol=1e-3, atol=1e-6), k
            assert np.allclose(np.abs(got[sure]), 1e-2, rtol=0.3), k
            assert np.array_equal(out["before"][k], params[k])


def test_update_error_reads_0_for_the_reference_and_1_for_no_update():
    runner = spec.load_module("runners", "train_sharded_lm")
    want = {"a": np.array([1e-4, -1e-4, 1e-4, 1e-4], np.float32),
            "b": np.array([[-1e-4]], np.float32)}
    assert runner.update_error(want, want) == 0.0
    zero = {k: np.zeros_like(v) for k, v in want.items()}
    assert runner.update_error(zero, want) == pytest.approx(1.0)
    # one element of five moved the other way: 2 sqrt(1 / 5)
    flipped = dict(want, b=-want["b"])
    assert runner.update_error(flipped, want) == pytest.approx(
        2 * np.sqrt(0.2))
    assert 0 < JOB["train"]["update_tolerance"] < 1


def test_flash_attn_roofline_counts_the_calls_the_trace_holds():
    reader = spec.load_reader("flash_attn_roofline")
    cost = spec.load_module("kernels", "mxtpu_flash_attn").cost
    per = 4 * 16 * 2048 * 64                      # the chip's share
    assert cost("mxtpu_flash_fwd", 4, 16, 2048, 64, 2) == {
        "flops": 2 * per * 2048, "bytes": 4 * per * 2}
    calls = {"mxtpu_flash_fwd": 4 * 48, "mxtpu_flash_dq": 4 * 24,
             "mxtpu_flash_dkdv": 4 * 24}
    flops = (48 * 2 + 24 * 3 + 24 * 4) * per * 2048
    seconds = 2 * flops / PEAKS["bf16_flops_per_s"]        # 50 %
    tr = summary({"mxtpu_flash_fwd": seconds / 2, "mxtpu_flash_dq": seconds / 4,
                  "mxtpu_flash_dkdv": seconds / 4}, calls, chips=4)
    facts = {"trace": tr, "traffic": JOB, "peaks": PEAKS}
    assert reader.read(facts) == pytest.approx(50.0)
    assert reader.read(dict(facts, traffic=GEN)) is None
    assert reader.read(dict(facts, trace=summary({}))) is None
    assert reader.read(dict(facts, trace=None)) is None


def test_collective_exposed_share_is_the_collectives_on_the_ops_line():
    reader = spec.load_reader("collective_exposed_share")
    tr = summary({"all-reduce": 0.05, "all-reduce-done": 0.03,
                  "all-gather-start": 0.02, "fusion": 0.7, "reduce": 0.2},
                 busy_s=1.0)
    assert reader.read({"trace": tr}) == pytest.approx(10.0)
    assert reader.read({"trace": summary({"fusion": 1.0})}) is None
    assert reader.read({"trace": None}) is None


# -- brumby-gen-closed -------------------------------------------------------

def _serve_facts(tr, rows=16):
    return {"trace": tr, "config": BRUMBY, "traffic": GEN, "peaks": PEAKS,
            "engine": {"num_blocks": 17, "heads": 40, "head_dim": 128,
                       "layers": 6, "max_batch": 16},
            "steps": [{"traced": True, "rows": rows, "kv_used": 16},
                      {"traced": False, "rows": rows, "kv_used": 12}]}


def test_retention_readers_read_the_kernel_and_its_programs():
    roof = spec.load_reader("retention_decode_roofline")
    step = spec.load_reader("retention_decode_step_ms")
    chunk = spec.load_reader("retention_chunk_ms")
    slots = spec.load_reader("state_slots_peak_share")
    cost = spec.load_module("kernels", "mxtpu_retention_decode").cost
    need = cost(16, 40, 8, 128, 2, 6)["bytes"] / PEAKS["hbm_bytes_per_s"]
    tr = summary({"mxtpu_retention_decode": need / 0.8},
                 modules={"jit_fn_decode": [24.0, 26.0, 25.0],
                          "jit_fn_prefill_chunk": [40.0]},
                 kernels={"jit_fn_decode": {"mxtpu_retention_decode": 30.0}})
    facts = _serve_facts(tr)
    assert roof.read(facts) == pytest.approx(80.0)
    assert step.read(facts) == pytest.approx(25.0)
    assert slots.read(facts) == pytest.approx(100.0)
    # the chunk is read from the window's spans, not from the trace: a
    # traced 4 s that hold no chunk (one seed in five) still report it
    assert chunk.read(facts) is None
    spans = [{"name": "serve.prefill", "dur": d, "args": {"chunk_start": s}}
             for d, s in ((41000, 0), (27000, 256), (28000, 512))]
    spans += [{"name": "serve.prefill", "dur": 900000, "args": {}},
              {"name": "serve.decode", "dur": 23000, "args": {"active": 16}}]
    no_chunk = summary({"mxtpu_retention_decode": 1.0},
                       modules={"jit_fn_decode": [25.0]})
    for seen in (tr, no_chunk, None):
        assert chunk.read(dict(facts, trace=seen, spans=spans)) == \
            pytest.approx(28.0)


def test_retention_readers_find_nothing_in_the_stand_ins_cells():
    lm = spec.load_json(spec.BENCH_DIR + "/configs/nope-lm-2048x24.json")
    tr = summary({"mxtpu_flash_decode": 0.5},
                 modules={"jit_fn_decode": [87.0], "jit_fn_prefill_chunk": [50.0]},
                 kernels={"jit_fn_decode": {"mxtpu_flash_decode": 45.0}})
    facts = dict(_serve_facts(tr), config=lm)
    for name in ("retention_decode_roofline", "retention_decode_step_ms",
                 "retention_chunk_ms", "state_slots_peak_share"):
        assert spec.load_reader(name).read(facts) is None, name
        assert spec.load_reader(name).read(dict(facts, trace=None)) is None
    chunk_span = [{"name": "serve.prefill", "dur": 50000,
                   "args": {"chunk_start": 0}}]
    assert spec.load_reader("retention_chunk_ms").read(
        dict(facts, spans=chunk_span)) is None


# -- the plain reference -----------------------------------------------------

TINY = dict(vocab_size=50, num_hidden_layers=2, hidden_size=32,
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            intermediate_size=48)


def test_brumby_reference_is_causal_and_groups_its_heads():
    params = brumby.init_params(2**31 + 3, TINY, jnp.float32, std=0.2)
    assert set(params) == set(brumby.param_shapes(TINY))
    assert params["layer0_gate_weight"].shape == (2, 32)     # one a kv head
    assert params["layer1_k_weight"].shape == (2 * 8, 32)
    assert float(params["layer0_gate_bias"].mean()) == pytest.approx(4.0,
                                                                     abs=0.5)
    rng = np.random.default_rng(1)
    toks = rng.integers(1, 50, (2, 12))
    logits = np.asarray(brumby.forward(params, toks, 4))
    assert logits.shape == (2, 12, 50) and np.isfinite(logits).all()
    later = toks.copy()
    later[:, 7:] = (later[:, 7:] + 1) % 50
    moved = np.asarray(brumby.forward(params, later, 4)) - logits
    assert np.abs(moved[:, :7]).max() == 0 and np.abs(moved[:, 7:]).max() > 0
    # position enters through RoPE: another theta moves every position
    # but the first, whose angle is 0
    other = np.asarray(brumby.forward(params, toks, 4, theta=100.0)) - logits
    assert np.abs(other[:, 0]).max() == 0 and np.abs(other[:, 1:]).max() > 1e-4


def test_brumby_reference_imports_nothing_from_the_program():
    src = open(spec.BENCH_DIR + "/reference/brumby.py").read()
    assert "import mxnet_tpu" not in src and "from mxnet_tpu" not in src
    assert '_HI = "highest"' in src


# -- the cell's second number and the readings in a lower precision ---------

def _rehearsal_config():
    from benchmark.run import merged
    return merged(BRUMBY, BRUMBY["rehearsal"])


@pytest.mark.parametrize("round_to,passes", [(None, True),
                                             (jnp.bfloat16, False)])
def test_state_error_holds_a_float32_state_and_fails_a_bfloat16_one(
        round_to, passes):
    """The decode update (the Pallas kernel, interpreted) against the
    reference's attention form: rounding to float32 alone is 1e-7 of the
    output, a state rounded to bfloat16 after every step 6e-3, and the
    configuration's limit lies between with a factor of ten each way at
    the least."""
    import jax
    from mxnet_tpu.models.retention import state_shape
    runner = spec.load_module("runners", "serve_engine_closed_state")
    cfg = _rehearsal_config()
    like = jax.ShapeDtypeStruct(
        (1, 1, cfg["num_key_value_heads"]) + state_shape(cfg["head_dim"]),
        jnp.float32)
    err = runner.state_error(like, cfg, 2**31 + 7, round_to)
    tol = BRUMBY["serve"]["state_tolerance"]
    assert (err < tol / 10) if passes else (err > tol * 10), err
    assert GEN["kind"] == "serve_engine_closed_state"


def test_precision_reading_passes_the_stated_precision_and_fails_the_lower(
        capsys):
    """The recurrent stand-in handed to ``serving.probe`` in the engine's
    place: as stated it picks the reference's own tokens; a bfloat16
    state fails the state's limit, float8 weights the probe's."""
    from benchmark import precision_reading
    assert precision_reading.main(["--config", "brumby-14b-6of40", "--seed",
                                   str(2**31 + 11), "--rehearsal"]) == 0
    out = capsys.readouterr().out
    assert out.count("[correct] 3 greedy probes") == 3
    assert "as stated: float32 state: True" in out
    assert "bfloat16 every position: False" in out
    assert "float8 e4m3: False" in out
    assert precision_reading.main(["--config", "nope-lm-2048x24"]) == 2
