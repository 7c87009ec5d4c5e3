"""``trinity-large-ep8-5of60`` and its cell ``trinity-window-gen-closed``
(ISSUE 37): the configuration's sizes worked by hand, every published
width kept and every changed key named, the two kinds of K/V table as
sized, the grouped decode kernel's cost from shapes, the new per-layer
readers on hand-made traces (each returns None, and does not raise, on
another cell's facts), the cell's second number, and the reference's
independence.  The serving programs and the kernel are compiled for a
described v5e in ``tests/test_pool_in_place.py``; ``test_rehearsal.py``
picks the cell up by itself."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.harness import readers, spec, trace

CFG = spec.load_json(spec.BENCH_DIR + "/configs/trinity-large-ep8-5of60.json")
MIX = spec.load_traffic("window-gen-closed-32")
BENCH = spec.load_benchmark()
PEAKS = spec.load_peaks("TPU v5 lite")
CELL = "trinity-window-gen-closed"
trinity = spec.load_module("reference", "trinity")
NEW = ("gqa_decode_roofline", "trinity_experts_roofline",
       "trinity_decode_step_ms", "trinity_chunk_ms",
       "window_blocks_peak_share", "global_blocks_peak_share",
       "serve_mfu.trinity", "decode_rows_mean.trinity",
       "step_host_ms.trinity", "device_idle_share.trinity")
S, F = "sliding_attention", "full_attention"


def test_one_dense_and_four_expert_layers_are_4_32_billion_parameters():
    d = 3072
    attention = 6144 * d + 1024 * d + 1024 * d + 6144 * d + d * 6144
    assert attention == 62_914_560
    norms = 4 * d + 2 * 128             # four sandwich norms, q and k norms
    dense = attention + norms + 3 * d * 12288
    assert dense == 176_173_312
    expert = 3 * d * 3072
    routed = attention + norms + 256 * d + 256 + expert + 32 * expert
    assert routed == 997_995_008
    want = dense + 4 * routed + 2 * 25024 * d + d
    assert trinity.param_count(CFG) == want == CFG["parameters"] \
        == 4_321_903_872
    assert want * 2 / 1e9 == pytest.approx(8.64, abs=0.005)           # bf16
    # what a position's forward pass needs HERE: 5 attentions, the dense
    # FFN, 4 x (router + shared + 0.5 held experts), the head
    active = 5 * attention + 3 * d * 12288 + 4 * (256 * d + 1.5 * expert) \
        + 25024 * d
    assert trinity.forward_flops(CFG, 1, 0) == 2 * active
    # 4 x 48 x 128 an attended position a layer; a window layer sees 4,096
    per = 4 * 48 * 128
    assert trinity.forward_flops(CFG, 10, 10) - trinity.forward_flops(
        CFG, 10, 0) == 5 * per * 10
    assert trinity.forward_flops(CFG, 1, 9216) - trinity.forward_flops(
        CFG, 1, 0) == per * (4 * 4096 + 9216)
    assert trinity.held_ids(CFG).tolist() == list(range(32))


def test_every_published_width_is_kept_and_the_cut_is_the_stated_one():
    published = {
        "hidden_size": 3072, "intermediate_size": 12288,
        "moe_intermediate_size": 3072, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128, "num_experts_per_tok": 4,
        "num_shared_experts": 1, "n_group": 1, "topk_group": 1,
        "num_expert_groups": 1, "num_limited_groups": 1,
        "route_scale": 2.448, "route_norm": True, "score_func": "sigmoid",
        "sliding_window": 4096, "global_attn_every_n_layers": 4,
        "rope_theta": 10000, "rope_scaling": None, "rms_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mup_enabled": True,
        "tie_word_embeddings": False, "model_type": "afmoe",
        "hidden_act": "silu", "load_balance_coeff": 5e-05,
        "use_grouped_mm": True}
    for key, value in published.items():
        assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types", "num_experts", "vocab_size"]
    assert (CFG["num_hidden_layers"], CFG["num_dense_layers"],
            CFG["num_experts"], CFG["vocab_size"]) == (5, 1, 32, 25024)
    # published layers 0, 8, 9, 10, 11: one whole period in its 3:1 ratio
    assert CFG["layer_types"] == [S, S, S, S, F]
    assert CFG["published"]["num_experts"] == 256
    assert CFG["vocab_size"] * 8 == CFG["published"]["vocab_size"]
    assert CFG["num_experts"] >= 8
    assert CFG["deployment_share"]["chips_sharing_a_layer"] == 8
    entry = [c for c in BENCH["configs"]
             if c["name"] == "trinity-large-ep8-5of60"][0]
    assert entry["reduced"] == CFG["reduced"]
    assert entry["source"] == CFG["source"]
    # the description the engine is told says the same
    m = CFG["serve"]["engine"]["model"]
    assert (m["kv_heads"], m["head_dim"], m["sliding_window"]) == (8, 128, 4096)
    assert m["attention"] == ["sliding"] * 4 + ["softmax"]
    assert m["ffn_layers"] == ["dense"] + ["routed"] * 4
    assert m["n_routed_experts"] == 256 and m["experts_held"] == [0, 32]
    assert m["experts_per_token"] == CFG["num_experts_per_tok"]
    assert m["routed_scaling_factor"] == CFG["route_scale"]
    assert m["norm_topk_prob"] is CFG["route_norm"] is True
    assert m["score_func"] == "sigmoid" and m["router_bias"]
    assert m["embed_scale"] == pytest.approx(3072 ** 0.5)
    assert m["norm_eps"] == CFG["rms_norm_eps"]
    assert trinity.PUBLISHED["layer_types"] == tuple(CFG["layer_types"])
    assert (trinity.PUBLISHED["sliding_window"], trinity.PUBLISHED["top_k"],
            trinity.PUBLISHED["route_scale"]) == (4096, 4, 2.448)


def test_the_two_kinds_of_table_fit_the_chip_and_the_traffic():
    from mxnet_tpu.serve import kvcache
    e = CFG["serve"]["engine"]
    block = 128 * 8 * 128 * 2 * 2                   # K and V, one layer
    assert trinity.kv_bytes_per_token(CFG) * 128 == block == 512 * 1024
    ring = kvcache.ring_width(4096, e["prefill_chunk"], e["block_size"])
    assert ring == 41
    window = (1 + e["max_batch"] * ring) * 4 * block
    glob = e["num_blocks"] * block
    assert window / 1e9 == pytest.approx(2.75, abs=0.01)
    assert glob / 1e9 == pytest.approx(1.57, abs=0.01)
    held = CFG["parameters"] * 2 + window + glob
    assert 0.25 * 16e9 < held < 15e9 and held / 1e9 == pytest.approx(12.97,
                                                                    abs=0.01)
    # without a window kind every layer keeps the whole prefix: no fit
    assert CFG["parameters"] * 2 + 5 * e["max_batch"] * 120 * block > 16e9
    assert e["max_seq_len"] % e["block_size"] == 0
    assert MIX["clients"] == e["max_batch"] == 32
    assert MIX["prompt_tokens"]["max"] == e["max_prompt_len"]
    assert (MIX["prompt_tokens"]["max"] + MIX["output_tokens"]["max"]
            == e["max_seq_len"])
    # every decode row is past the window
    assert MIX["prompt_tokens"]["min"] >= CFG["sliding_window"]
    probe = CFG["serve"]["probe"]["prompt_tokens"]
    assert max(probe) > CFG["sliding_window"] + e["prefill_chunk"] // 4


def test_the_kernels_cost_comes_from_shapes():
    cost = readers.kernel_cost("mxtpu_gqa_decode")
    c = cost(positions=32 * 4096, rows=32, heads=48, kv_heads=8, head_dim=128,
             itemsize=2, layers=4)
    assert c["bytes"] == 4 * (32 * 4096 * 2 * 1024 * 2 + 2 * 32 * 6144 * 2)
    assert c["flops"] == 4 * 4 * 32 * 4096 * 6144
    # memory-bound: 6 FLOP a byte of K/V against the chip's 240
    assert 4 * 6144 / (2 * 1024 * 2) == 6


def summary(op_seconds, modules=None, kernels=None, busy_s=1.0):
    return trace.Summary(
        chips=1, window_s=2.0, busy_s=busy_s, op_seconds=op_seconds,
        op_calls={}, gap_seconds_by_region={}, module_ms=modules or {},
        module_kernels=kernels or {})


def _facts(tr, spans=(), config=CFG):
    return {"trace": tr, "config": config, "traffic": MIX, "peaks": PEAKS,
            "chips": 1, "window_s": 48.0, "spans": list(spans),
            "served": {"decoded": 80000, "prefilled": 300000,
                       "attended": 2_000_000_000},
            "steps": [{"traced": True, "rows": 32, "cached_tokens": 300_000,
                       "kv_used": 3000}],
            "blocks": {"window_peak": 1312, "window_usable": 1312,
                       "global_peak": 2400, "global_usable": 3000}}


def _decode_spans():
    # the window's decode steps, oldest first: the last two are traced
    return [{"name": "serve.decode", "dur": 15000, "ts": ts,
             "args": {"active": 32, "window_rows": 32 * 4096,
                      "global_rows": g, "experts_hit": 50,
                      "assigned_here": 64}}
            for ts, g in ((10, 250_000), (20, 290_000), (30, 300_000))]


def test_the_new_readers_on_a_hand_made_trace():
    gqa = readers.kernel_cost("mxtpu_gqa_decode")
    shape = dict(heads=48, kv_heads=8, head_dim=128, itemsize=2)
    need = {"bytes": 0.0, "flops": 0.0}
    for g in (290_000, 300_000):                     # the traced two
        for seen, layers in ((32 * 4096, 4), (g, 1)):
            c = gqa(seen, 32, layers=layers, **shape)
            need = {k: need[k] + c[k] for k in need}
    gqa_least = need["bytes"] / PEAKS["hbm_bytes_per_s"]
    moe = readers.kernel_cost("mxtpu_moe_experts")(100, 128, 3072, 3072, 2, 4)
    moe_least = moe["bytes"] / PEAKS["hbm_bytes_per_s"]
    tr = summary(
        {"mxtpu_gqa_decode": gqa_least / 0.8,
         "mxtpu_moe_experts": 9.9},      # all programs': NOT what is read
        modules={"jit_fn_decode": [9.0, 11.0],
                 "jit_fn_prefill_chunk": [27.0]},
        kernels={"jit_fn_decode": {"mxtpu_gqa_decode": 9.0,
                                   "mxtpu_moe_experts": moe_least * 1e3 / 0.6},
                 "jit_fn_prefill_chunk": {"mxtpu_moe_experts": 40.0}})
    spans = _decode_spans()
    spans += [{"name": "serve.prefill", "dur": d, "ts": 5,
               "args": {"chunk_start": s}}
              for d, s in ((21000, 0), (27000, 1024), (29000, 2048))]
    facts = _facts(tr, spans)
    read = {n: spec.load_reader(n).read(facts) for n in NEW}
    assert read["gqa_decode_roofline"] == pytest.approx(80.0)
    assert read["trinity_experts_roofline"] == pytest.approx(60.0)
    assert read["trinity_decode_step_ms"] == pytest.approx(10.0)
    assert read["trinity_chunk_ms"] == pytest.approx(27.0)
    assert read["window_blocks_peak_share"] == pytest.approx(100.0)
    assert read["global_blocks_peak_share"] == pytest.approx(80.0)
    assert read["decode_rows_mean.trinity"] == pytest.approx(32.0)
    assert read["device_idle_share.trinity"] == pytest.approx(50.0)
    flops = trinity.forward_flops(CFG, 380000, 2_000_000_000)
    assert read["serve_mfu.trinity"] == pytest.approx(
        100 * flops / 48.0 / PEAKS["bf16_flops_per_s"])
    assert 0 < read["serve_mfu.trinity"] < 100
    # a parent's program records no counts on its spans: silent
    bare = [dict(ev, args={"active": 32}) for ev in spans
            if ev["name"] == "serve.decode"]
    for name in ("gqa_decode_roofline", "trinity_experts_roofline"):
        assert spec.load_reader(name).read(_facts(tr, bare)) is None, name


@pytest.mark.parametrize("config", ["nope-lm-2048x24", "brumby-14b-6of40",
                                    "deepseek-v2-ep4-5of60"])
def test_the_new_readers_find_nothing_on_another_cells_facts(config):
    cfg = spec.load_json(f"{spec.BENCH_DIR}/configs/{config}.json")
    tr = summary({"mxtpu_flash_decode": 0.5, "mxtpu_mla_decode": 0.5,
                  "mxtpu_moe_experts": 0.5},
                 modules={"jit_fn_decode": [25.0],
                          "jit_fn_prefill_chunk": [30.0]},
                 kernels={"jit_fn_decode": {"mxtpu_mla_decode": 5.0,
                                            "mxtpu_moe_experts": 5.0}})
    spans = [{"name": "serve.decode", "dur": 23000, "ts": 1,
              "args": {"active": 16, "experts_hit": 3, "assigned_here": 9}},
             {"name": "serve.prefill", "dur": 50000, "ts": 2,
              "args": {"chunk_start": 0}}]
    facts = dict(_facts(tr, spans, cfg))
    del facts["blocks"]
    for name in NEW[:6]:
        assert spec.load_reader(name).read(facts) is None, name
        assert spec.load_reader(name).read(dict(facts, trace=None)) is None


def test_benchmark_json_declares_the_cell_and_its_ten_metrics():
    cell = spec.find_cell(BENCH, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "trinity-large-ep8-5of60", "window-gen-closed-32", 1)
    assert MIX["kind"] == "serve_engine_closed_window"
    per_layer = {m["name"]: m for m in spec.metrics_for(BENCH, CELL,
                                                        "per_layer")}
    assert sorted(per_layer) == sorted(NEW)
    for m in per_layer.values():
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tok_s"
    e2e = [m["name"] for m in spec.metrics_for(BENCH, CELL, "end_to_end")]
    assert sorted(e2e) == ["serve_tok_s", "setup_s"]
    assert BENCH["workloads"][-1]["name"] == CELL


def _rehearsal_config():
    from benchmark.run import merged
    return merged(CFG, CFG["rehearsal"])


def test_the_reference_is_causal_windowed_and_groups_its_heads():
    """A token changed at position 8 moves no logit before it; with a
    window of 4 (a setting of the reference) a token changed at 0 moves
    nothing a sliding-only model computes from position 4 on, while the
    whole-prefix layer still sees it."""
    cfg = _rehearsal_config()
    params = trinity.init_params(5, cfg, jnp.float32, std=0.2)
    v = cfg["vocab_size"]
    toks = np.random.default_rng(0).integers(1, v, (2, 12))
    base = np.asarray(trinity.forward(params, toks, 4))
    later = toks.copy()
    later[:, 8:] = (later[:, 8:] % (v - 1)) + 1
    moved = np.asarray(trinity.forward(params, later, 4))
    assert np.abs(moved[:, :8] - base[:, :8]).max() == 0      # causal
    assert np.abs(moved[:, 8:] - base[:, 8:]).max() > 1e-3
    long = np.random.default_rng(1).integers(1, v, (1, 20))
    first = long.copy()
    first[:, 0] = (first[:, 0] % (v - 1)) + 1
    for kinds, untouched in (((S,) * 5, True), ((S,) * 4 + (F,), False)):
        a, b = (np.asarray(trinity.forward(params, t, 4, layer_types=kinds,
                                           sliding_window=4))
                for t in (long, first))
        assert np.abs(a[:, :4] - b[:, :4]).max() > 1e-3
        # five window layers of 4: position p sees back to p - 15 at
        # most; a whole-prefix layer sees position 0 from everywhere
        assert bool(np.abs(a[:, 16:] - b[:, 16:]).max() == 0) == untouched
    shapes = trinity.param_shapes(cfg)
    assert shapes["layer0_k_weight"] == (2 * 16, 64)          # KV heads
    assert shapes["layer1_router_weight"] == (256, 64)        # all experts
    assert shapes["layer1_experts_gate_weight"] == (8, 64, 32)
    # the share travels in the parameters: other experts, another result
    other = dict(params, experts_held=params["experts_held"] + 8)
    assert np.abs(np.asarray(trinity.forward(other, toks, 4))
                  - base).max() > 1e-3


@pytest.mark.parametrize("reading", ["stated", "rows8"])
def test_window_error_holds_the_stated_rows_and_fails_8_bit_ones(reading):
    """The program's ring writer and decode kernel (interpreted) over a
    float32 pool against float64 attention over exactly the window's
    keys: 2e-7; the rows rounded to float8 e4m3 first: 1e-2 and more.
    The rehearsal's limit lies between with a factor of ten each way.
    At a window of 16 (a tiny engine's) the kernel told one block more
    reads keys outside it: far over."""
    from mxnet_tpu.serve.kvcache import ring_width
    runner = spec.load_module("runners", "serve_engine_closed_window")
    cfg = _rehearsal_config()
    e = cfg["serve"]["engine"]
    like = jax.ShapeDtypeStruct((1, 1, e["block_size"], 32), jnp.float32)
    tol = cfg["serve"]["window_tolerance"]

    def ring(c):
        return ring_width(c["sliding_window"], e["prefill_chunk"],
                          e["block_size"])

    if reading == "stated":
        err = runner.window_error(like, ring(cfg), cfg, 2**31 + 7)
        assert err < tol / 10, err
        small = dict(cfg, sliding_window=16)       # rows of 64 wrap its ring
        assert ring(small) * e["block_size"] < e["max_seq_len"]
        assert runner.window_error(like, ring(small), small, 3) < tol / 10
        assert runner.window_error(like, ring(small), small, 3,
                                   widen=8) > tol * 10
    else:
        err = runner.window_error(like, ring(cfg), cfg, 2**31 + 7,
                                  jnp.float8_e4m3fn)
        assert err > tol * 10, err
