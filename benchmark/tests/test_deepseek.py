"""``deepseek-v2-ep4-5of60`` and its cell ``deepseek-gen-closed`` (ISSUE
35): the configuration's sizes worked by hand, every published width
kept, the latent row as needed and as stored, both kernels' cost
functions from shapes, the four new per-layer readers on hand-made
traces (each returns None, and does not raise, on another cell's
facts), the cell's second number, and the reference's independence.
The serving programs and both kernels are compiled for a described v5e
in ``tests/test_pool_in_place.py``; ``test_rehearsal.py`` picks the cell
up by itself."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import readers, spec, trace

CFG = spec.load_json(spec.BENCH_DIR + "/configs/deepseek-v2-ep4-5of60.json")
MIX = spec.load_traffic("long-gen-closed-64")
BENCH = spec.load_benchmark()
PEAKS = spec.load_peaks("TPU v5 lite")
dsv2 = spec.load_module("reference", "deepseek_v2")
NEW = ("mla_decode_roofline", "moe_experts_roofline", "mla_decode_step_ms",
       "mla_chunk_ms", "serve_mfu.dsv2", "decode_rows_mean.dsv2",
       "step_host_ms.dsv2", "device_idle_share.dsv2",
       "kv_peak_used_share.dsv2")


def test_one_dense_and_four_expert_layers_are_5_16_billion_parameters():
    d = 5120
    mla = (d * 1536 + 1536 * 128 * 192 + d * 576 + 512 * 128 * 256
           + 128 * 128 * d)
    assert mla == 149_225_472
    norms = 1536 + 512 + 2 * d                    # two latent gains, ln1, ln2
    dense = mla + norms + 3 * d * 12288
    expert = 3 * d * 1536
    assert expert == 23_592_960
    routed = mla + norms + 160 * d + 3 * d * 3072 + 40 * expert
    want = dense + 4 * routed + 2 * 25600 * d + d
    assert dsv2.param_count(CFG) == want == CFG["parameters"] == 5_163_975_680
    assert want * 2 / 1e9 == pytest.approx(10.33, abs=0.005)          # bf16
    # what a position's forward pass needs HERE: 1.3995 G matmul parameters
    assert dsv2.forward_flops(CFG, 1, 0) / 2 == pytest.approx(1.39952e9,
                                                             rel=1e-5)
    # and the stated attention form: 2 x 128 x 320 an attended position, x 5
    assert dsv2.forward_flops(CFG, 0, 1) == 5 * 2 * 128 * 320
    assert "experts_held" not in dsv2.param_shapes(CFG)
    assert dsv2.held_ids(CFG).tolist() == list(range(40))


def test_every_published_width_is_kept_and_the_cut_is_the_stated_one():
    published = {
        "hidden_size": 5120, "intermediate_size": 12288,
        "moe_intermediate_size": 1536, "num_attention_heads": 128,
        "num_key_value_heads": 128, "q_lora_rank": 1536, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_experts_per_tok": 6, "n_shared_experts": 2, "n_group": 8,
        "topk_group": 3, "routed_scaling_factor": 16, "norm_topk_prob": False,
        "first_k_dense_replace": 1, "moe_layer_freq": 1, "rope_theta": 10000,
        "rms_norm_eps": 1e-06, "max_position_embeddings": 163840,
        "topk_method": "group_limited_greedy", "scoring_func": "softmax",
        "tie_word_embeddings": False, "attention_bias": False,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"}}
    for key, value in published.items():
        assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert CFG["published"] == {"num_hidden_layers": 60,
                                "n_routed_experts": 160, "vocab_size": 102400}
    # inside the guide's floors: 4 layers after the dense one, >= 8
    # experts, >= an eighth of the vocabulary
    assert CFG["num_hidden_layers"] - CFG["first_k_dense_replace"] >= 4
    assert CFG["n_routed_experts"] >= 8
    assert CFG["vocab_size"] * 8 >= CFG["published"]["vocab_size"]
    assert CFG["deployment_share"]["chips_sharing_a_layer"] == 4
    entry = [c for c in BENCH["configs"]
             if c["name"] == "deepseek-v2-ep4-5of60"][0]
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    # the description the engine is told says the same
    m = CFG["serve"]["engine"]["model"]
    for key in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                "qk_rope_head_dim", "v_head_dim", "n_group", "topk_group",
                "rope_scaling", "norm_topk_prob"):
        assert m[key] == CFG[key], key
    assert m["n_routed_experts"] == 160 and m["experts_held"] == [0, 40]
    assert m["experts_per_token"] == CFG["num_experts_per_tok"]
    assert m["routed_scaling_factor"] == CFG["routed_scaling_factor"]
    assert m["ffn_layers"] == ["dense"] + ["routed"] * 4
    assert dsv2.PUBLISHED["rope_scaling"] == CFG["rope_scaling"]
    assert (dsv2.PUBLISHED["n_group"], dsv2.PUBLISHED["topk_group"],
            dsv2.PUBLISHED["top_k"]) == (8, 3, 6)


def test_a_position_needs_1152_bytes_a_layer_and_the_program_stores_1280():
    from mxnet_tpu.serve import kvcache
    assert dsv2.latent_bytes_per_token(CFG) == 1152
    e = CFG["serve"]["engine"]
    stored = kvcache.kv_bytes_per_token(5, 128, 192, dtype=jnp.bfloat16,
                                        latent_width=576)
    assert stored == 5 * 1280 == 6400
    tokens = (e["num_blocks"] - 1) * e["block_size"]
    assert tokens == 327_680 <= e["max_batch"] * e["max_seq_len"]
    assert tokens * stored / 1e9 == pytest.approx(2.10, abs=0.005)
    held = CFG["parameters"] * 2 + e["num_blocks"] * e["block_size"] * stored
    assert 0.25 * 16e9 < held < 15e9 and held / 1e9 == pytest.approx(12.43,
                                                                    abs=0.01)
    assert e["max_seq_len"] % e["block_size"] == 0
    # the traffic fits the engine, and its ids the sliced vocabulary
    assert MIX["clients"] == e["max_batch"] == 64
    assert MIX["prompt_tokens"]["max"] == e["max_prompt_len"]
    assert (MIX["prompt_tokens"]["max"] + MIX["output_tokens"]["max"]
            == e["max_seq_len"])
    probe = CFG["serve"]["probe"]["prompt_tokens"]
    assert max(probe) > 2048 and any(
        e["prefill_chunk"] < p <= 2 * e["prefill_chunk"] for p in probe)


def test_both_kernels_costs_come_from_shapes():
    mla = readers.kernel_cost("mxtpu_mla_decode")
    c = mla(cached_tokens=64 * 3300, rows=64, heads=128, rank=512, rope=64,
            itemsize=2, layers=5)
    assert c["flops"] == 5 * 278_528 * 64 * 3300
    assert c["bytes"] == 5 * (64 * 3300 * 1152 + 64 * 128 * (576 + 512) * 2)
    # at the ridge: 242 FLOP a byte of rows against the chip's 240
    ridge = PEAKS["bf16_flops_per_s"] / PEAKS["hbm_bytes_per_s"]
    assert 278_528 / 1152 == pytest.approx(241.8, abs=0.1)
    assert ridge == pytest.approx(240.5, abs=0.5)
    moe = readers.kernel_cost("mxtpu_moe_experts")
    c = moe(experts_hit=4 * 36.5, assigned=4 * 96.0, d_model=5120, width=1536,
            itemsize=2, layers=4)
    assert c["flops"] == 2 * 23_592_960 * 384
    assert c["bytes"] == (146 * 47_185_920
                          + 384 * (2 * 5120 + 2 * 1536) * 2)
    # memory-bound by far: a hit expert's 47 MB for 2.6 rows
    assert (c["bytes"] / PEAKS["hbm_bytes_per_s"]
            > 50 * c["flops"] / PEAKS["bf16_flops_per_s"])


def summary(op_seconds, modules=None, kernels=None, busy_s=1.0):
    return trace.Summary(
        chips=1, window_s=2.0, busy_s=busy_s, op_seconds=op_seconds,
        op_calls={}, gap_seconds_by_region={}, module_ms=modules or {},
        module_kernels=kernels or {})


def _facts(tr, spans=()):
    return {"trace": tr, "config": CFG, "traffic": MIX, "peaks": PEAKS,
            "chips": 1, "window_s": 48.0, "spans": list(spans),
            "engine": {"num_blocks": 2561, "heads": 128, "head_dim": 192,
                       "layers": 5, "max_batch": 64, "kv_itemsize": 2,
                       "block_size": 128},
            "served": {"decoded": 90000, "prefilled": 250000,
                       "attended": 600_000_000},
            "steps": [{"traced": True, "rows": 64, "cached_tokens": 200_000,
                       "kv_used": 1800},
                      {"traced": True, "rows": 0, "cached_tokens": 0,
                       "kv_used": 1700},
                      {"traced": False, "rows": 64, "cached_tokens": 210_000,
                       "kv_used": 1900}]}


def test_the_four_new_readers_on_a_hand_made_trace():
    mla_cost = readers.kernel_cost("mxtpu_mla_decode")(
        200_000, 64, 128, 512, 64, 2, 5)
    mla_least = max(mla_cost["flops"] / PEAKS["bf16_flops_per_s"],
                    mla_cost["bytes"] / PEAKS["hbm_bytes_per_s"])
    runs = 3
    moe_cost = readers.kernel_cost("mxtpu_moe_experts")(
        runs * 146.0, runs * 380.0, 5120, 1536, 2, 4)
    moe_least = moe_cost["bytes"] / PEAKS["hbm_bytes_per_s"]
    tr = summary(
        {"mxtpu_mla_decode": mla_least / 0.5,
         "mxtpu_moe_experts": 9.9},      # all programs': NOT what is read
        modules={"jit_fn_decode": [20.0, 22.0, 21.0],
                 "jit_fn_prefill_chunk": [30.0]},
        kernels={"jit_fn_decode": {"mxtpu_mla_decode": 9.0,
                                   "mxtpu_moe_experts": moe_least * 1e3 / 0.75},
                 "jit_fn_prefill_chunk": {"mxtpu_moe_experts": 40.0}})
    spans = [{"name": "serve.decode", "dur": 25000,
              "args": {"active": 64, "experts_hit": h, "assigned_here": a}}
             for h, a in ((146, 380), (144, 376), (148, 384))]
    spans += [{"name": "serve.prefill", "dur": d, "args": {"chunk_start": s}}
              for d, s in ((21000, 0), (24000, 1024), (29000, 2048))]
    spans += [{"name": "serve.prefill", "dur": 900000, "args": {}}]
    facts = _facts(tr, spans)
    read = {n: spec.load_reader(n).read(facts) for n in NEW}
    assert read["mla_decode_roofline"] == pytest.approx(50.0)
    assert read["moe_experts_roofline"] == pytest.approx(75.0)
    assert read["mla_decode_step_ms"] == pytest.approx(21.0)
    assert read["mla_chunk_ms"] == pytest.approx(24.0)
    assert read["decode_rows_mean.dsv2"] == pytest.approx(64.0)
    assert read["kv_peak_used_share.dsv2"] == pytest.approx(100 * 1900 / 2560)
    assert read["device_idle_share.dsv2"] == pytest.approx(50.0)
    flops = dsv2.forward_flops(CFG, 340000, 600_000_000)
    assert read["serve_mfu.dsv2"] == pytest.approx(
        100 * flops / 48.0 / PEAKS["bf16_flops_per_s"])
    assert 0 < read["serve_mfu.dsv2"] < 100
    # the chunk is read from the WINDOW's spans: a traced 4 s without one
    # still report it; and the kernels' readers fall silent, not wrong,
    # where the trace holds no decode run
    quiet = summary({"fusion": 1.0}, modules={"jit_fn_prefill_chunk": [30.0]},
                    kernels={"jit_fn_prefill_chunk":
                             {"mxtpu_moe_experts": 40.0}})
    facts = _facts(quiet, spans)
    assert spec.load_reader("mla_chunk_ms").read(facts) == pytest.approx(24.0)
    for name in ("mla_decode_roofline", "moe_experts_roofline",
                 "mla_decode_step_ms"):
        assert spec.load_reader(name).read(facts) is None, name
    # a parent's program records no counts on its spans: silent too
    bare = [dict(ev, args={"active": 64}) for ev in spans
            if ev["name"] == "serve.decode"]
    assert spec.load_reader("moe_experts_roofline").read(
        _facts(tr, bare)) is None


@pytest.mark.parametrize("config", ["nope-lm-2048x24", "brumby-14b-6of40"])
def test_the_new_readers_find_nothing_on_another_cells_facts(config):
    cfg = spec.load_json(f"{spec.BENCH_DIR}/configs/{config}.json")
    tr = summary({"mxtpu_flash_decode": 0.5, "mxtpu_retention_decode": 0.5},
                 modules={"jit_fn_decode": [25.0],
                          "jit_fn_prefill_chunk": [30.0]},
                 kernels={"jit_fn_decode": {"mxtpu_flash_decode": 5.0,
                                            "mxtpu_retention_decode": 5.0}})
    spans = [{"name": "serve.decode", "dur": 23000,
              "args": {"active": 16, "live_blocks": 600, "table_blocks": 4096}},
             {"name": "serve.prefill", "dur": 50000,
              "args": {"chunk_start": 0}}]
    facts = dict(_facts(tr, spans), config=cfg)
    for name in ("mla_decode_roofline", "moe_experts_roofline",
                 "mla_decode_step_ms", "mla_chunk_ms"):
        assert spec.load_reader(name).read(facts) is None, name
        assert spec.load_reader(name).read(dict(facts, trace=None)) is None


def test_benchmark_json_declares_the_cell_and_its_nine_metrics():
    cell = spec.find_cell(BENCH, "deepseek-gen-closed")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "deepseek-v2-ep4-5of60", "long-gen-closed-64", 1)
    assert MIX["kind"] == "serve_engine_closed_latent"
    per_layer = {m["name"]: m for m in spec.metrics_for(
        BENCH, "deepseek-gen-closed", "per_layer")}
    assert sorted(per_layer) == sorted(NEW)
    for m in per_layer.values():
        assert m["workloads"] == ["deepseek-gen-closed"]
        assert m["moves"] == "serve_tok_s"
    e2e = [m["name"] for m in spec.metrics_for(BENCH, "deepseek-gen-closed",
                                               "end_to_end")]
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    assert len(BENCH["workloads"]) == 6
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1


def test_the_reference_imports_nothing_from_the_program():
    with open(spec.BENCH_DIR + "/reference/deepseek_v2.py") as f:
        src = f.read()
    assert "import mxnet_tpu" not in src and "from mxnet_tpu" not in src
    assert "from .." not in src and "from benchmark" not in src


def _rehearsal_config():
    from benchmark.run import merged
    return merged(CFG, CFG["rehearsal"])


def test_the_reference_is_causal_and_shares_one_rotary_key():
    cfg = _rehearsal_config()
    params = dsv2.init_params(5, cfg, jnp.float32, std=0.2)
    toks = np.random.default_rng(0).integers(1, cfg["vocab_size"], (2, 12))
    base = np.asarray(dsv2.forward(params, toks, 4))
    later = toks.copy()
    later[:, 8:] = (later[:, 8:] + 1) % cfg["vocab_size"]
    moved = np.asarray(dsv2.forward(params, later, 4))
    assert np.abs(moved[:, :8] - base[:, :8]).max() == 0      # causal
    assert np.abs(moved[:, 8:] - base[:, 8:]).max() > 1e-3
    # the share travels in the parameters: other experts, another result
    other = dict(params, experts_held=params["experts_held"] + 40)
    assert np.abs(np.asarray(dsv2.forward(other, toks, 4)) - base).max() > 1e-3
    shapes = dsv2.param_shapes(cfg)
    assert shapes["layer0_kv_a_weight"] == (32 + 8, 64)       # ONE k_r
    assert shapes["layer1_router_weight"] == (160, 64)        # all experts
    assert shapes["layer1_experts_gate_weight"] == (40, 64, 32)


@pytest.mark.parametrize("round_to,passes", [(None, True),
                                             (jnp.float8_e4m3fn, False)])
def test_latent_error_holds_the_stated_rows_and_fails_8_bit_ones(round_to,
                                                                 passes):
    """The program's writer and decode kernel (interpreted) over a
    float32 pool against float64 attention of the unrounded rows: 2e-7;
    the rows rounded to float8 e4m3 first: 1e-2 and more.  The
    rehearsal's limit lies between with a factor of ten each way."""
    runner = spec.load_module("runners", "serve_engine_closed_latent")
    cfg = _rehearsal_config()
    e = cfg["serve"]["engine"]
    like = jax.ShapeDtypeStruct((1, 1, e["block_size"], 128), jnp.float32)
    err = runner.latent_error(like, cfg, 2**31 + 7, round_to)
    tol = cfg["serve"]["latent_tolerance"]
    assert (err < tol / 10) if passes else (err > tol * 10), err
