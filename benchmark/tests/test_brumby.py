"""``brumby-14b-6of40``: its sizes worked by hand, its cost function, and
its two serving programs compiled at the cell's REAL shapes for a
described TPU v5e (no chip, nothing runs, no time is implied): they fit
one chip, the decode program holds the retention kernel once a layer,
and neither copies the state pool whole.  Run with ``-s`` to see the
sizes.  Everything that touches the TPU's compiler is inside fixtures of
this file (``python -m pytest benchmark/tests`` runs in one process, so
this file and ``test_compile_for_chip.py`` share the compiler)."""
import os
import re

import pytest

from benchmark.harness import readers, spec

CFG = spec.load_json(spec.BENCH_DIR + "/configs/brumby-14b-6of40.json")
brumby = spec.load_module("reference", "brumby")
CHIP_BYTES = 16e9


def test_six_layers_are_3_54_billion_parameters():
    d, f, v = 5120, 17408, 151936
    layer = (d * 40 * 128 + 2 * d * 8 * 128 + 40 * 128 * d     # q, k, v, o
             + 3 * d * f + 8 * d + 8 + 2 * 128 + 2 * d)        # FFN, gate, norms
    assert layer == 330_352_904
    want = 6 * layer + 2 * v * d + d
    assert brumby.param_count(CFG) == want == CFG["parameters"]
    assert want * 2 / 1e9 == pytest.approx(7.08, abs=0.01)     # bf16


def test_every_published_width_is_kept():
    published = {"head_dim": 128, "hidden_size": 5120,
                 "intermediate_size": 17408, "num_attention_heads": 40,
                 "num_key_value_heads": 8, "vocab_size": 151936,
                 "rms_norm_eps": 1e-06, "rope_theta": 1000000,
                 "max_position_embeddings": 32768, "max_window_layers": 40}
    for key, value in published.items():
        assert CFG[key] == value, key
    assert CFG["reduced"] == ["num_hidden_layers"]
    assert CFG["published"]["num_hidden_layers"] == 40
    model = CFG["serve"]["engine"]["model"]
    assert (model["kv_heads"], model["head_dim"]) == (8, 128)
    assert model["rope_theta"] == CFG["rope_theta"]
    assert model["norm_eps"] == CFG["rms_norm_eps"]


def test_a_request_needs_34_mb_of_state_a_layer_and_the_program_stores_36():
    need = brumby.state_bytes_per_request(CFG)
    assert need == 6 * 8 * 8256 * 129 * 4
    assert need / 6 / 1e6 == pytest.approx(34.08, abs=0.01)
    from mxnet_tpu.models.retention import state_shape
    chunks, rows, lanes = state_shape(128)
    assert (chunks, rows, lanes) == (65, 136, 128)
    stored = 6 * 8 * chunks * rows * lanes * 4
    assert stored / need == pytest.approx(1.063, abs=0.001)
    slots = CFG["serve"]["engine"]["num_blocks"]
    assert slots == CFG["serve"]["engine"]["max_batch"] + 1
    assert slots * stored / 1e9 == pytest.approx(3.69, abs=0.01)


def test_retention_decode_cost_is_the_state_in_and_out():
    cost = readers.kernel_cost("mxtpu_retention_decode")
    c = cost(rows=16, heads=40, kv_heads=8, head_dim=128, act_itemsize=2,
             layers=6)
    state = 8256 * 129
    acts = (2 * 40 + 2 * 8) * 128 * 2 + 4 * 8
    assert c["bytes"] == 6 * 16 * (8 * 2 * state * 4 + acts)
    assert c["bytes"] / 1e9 == pytest.approx(6.55, abs=0.01)
    assert c["flops"] == 6 * 16 * 8 * state * 13
    # memory-bound by two orders of magnitude
    peaks = spec.load_peaks("TPU v5 lite")
    assert (c["bytes"] / peaks["hbm_bytes_per_s"]
            > 50 * c["flops"] / peaks["bf16_flops_per_s"])


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler here, or it is taken
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def engine():
    """The engine of the configuration file, built on the CPU from SHAPES
    (no 7 GB of weights, no 3.7 GB of state)."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serve import Engine, EngineConfig

    serve = CFG["serve"]
    dtype = jnp.dtype(serve["weights_dtype"])
    ecfg = EngineConfig(heads=CFG["num_attention_heads"],
                        dtype=jnp.dtype(serve["kv_dtype"]),
                        **dict(serve["engine"], attn_impl="flash"))
    shapes = brumby.param_shapes(CFG)

    class ShapeEngine(Engine):
        def __init__(self):
            import mxnet_tpu.serve.engine as eng_mod
            from mxnet_tpu.models.retention import state_shape
            real_asarray = jnp.asarray
            real_pool = eng_mod.kvcache.make_state_pool
            sds = jax.ShapeDtypeStruct
            try:
                eng_mod.jnp.asarray = lambda v, *a, **k: (
                    v if isinstance(v, sds) else real_asarray(v, *a, **k))
                eng_mod.kvcache.make_state_pool = (
                    lambda nl, ns, kv, hd: sds(
                        (nl, ns, kv) + state_shape(hd), jnp.float32))
                super().__init__({k: sds(s, dtype) for k, s in shapes.items()},
                                 ecfg)
            finally:
                eng_mod.jnp.asarray = real_asarray
                eng_mod.kvcache.make_state_pool = real_pool

    return ShapeEngine()


@pytest.mark.parametrize("kind,bucket_key", [("decode", "max_batch"),
                                             ("prefill_chunk", "prefill_chunk")])
def test_brumby_program_compiles_for_v5e_fits_and_copies_no_pool(
        topo, engine, kind, bucket_key):
    import jax
    from jax.sharding import SingleDeviceSharding

    bucket = CFG["serve"]["engine"][bucket_key]
    one_chip = SingleDeviceSharding(topo.devices[0])
    make = {"decode": engine._make_decode_fn,
            "prefill_chunk": engine._make_chunk_prefill_fn}[kind]
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        engine._avals(kind, bucket))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        comp = jax.jit(make(bucket), donate_argnums=(0,)).trace(
            *avals).lower(lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    m = comp.memory_analysis()
    mem = {"arguments": m.argument_size_in_bytes,
           "outputs": m.output_size_in_bytes,
           "aliased": m.alias_size_in_bytes,
           "temporaries": m.temp_size_in_bytes}
    mem["sum"] = (mem["arguments"] + mem["temporaries"]
                  + mem["outputs"] - mem["aliased"])
    print(kind, bucket, {k: round(v / 1e9, 2) for k, v in mem.items()})
    assert mem["sum"] < CHIP_BYTES, mem
    pool = engine._avals(kind, bucket)[0]
    pool_bytes = 4
    for s in pool.shape:
        pool_bytes *= s
    assert mem["aliased"] >= pool_bytes          # updated in place
    text = comp.as_text()
    dims = ",".join(str(s) for s in pool.shape)
    copies = [ln for ln in text.splitlines()
              if re.search(r"= f32\[%s\]\S* copy\(" % re.escape(dims), ln)]
    assert not copies, copies[:2]
    if kind == "decode":
        assert text.count('custom_call_target="tpu_custom_call"') >= \
            CFG["num_hidden_layers"]
        assert "mxtpu_retention_decode" in text
