"""Each serving program of the cells, compiled at the cells' REAL shapes
for a described TPU v5e (``on-chip-measurement`` section 2.3): no chip,
nothing runs, no time is implied.  It is how ``num_blocks`` is chosen
before any chip time is spent: ``memory_analysis()`` of both programs
has to fit one chip beside nothing else.  Slow (half a minute to a
minute and a half per program); everything that touches the TPU's
compiler is inside fixtures of this one file.

The train steps are not compiled here: ``ShardedTrainer.bind`` places its
parameters with ``jax.device_put``, which a described device refuses, and
handing it shapes instead needs a subclass overriding the private
``_global_put`` (the verify skill's recipe) -- a scratch script, not a
kept test.  ``resnet50-train`` fits with room to spare (PERF.md).  Run
with ``-s`` to see the sizes.
"""
import os

import pytest

from benchmark.harness import spec

CHIP_BYTES = 16e9                 # benchmark/peaks.json, TPU v5 lite


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
    (no 2.8 GB of weights, no 2.4 GB of pools are made): only its program
    builders and avals are used."""
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.serve import Engine, EngineConfig

    cfg = spec.load_config(spec.load_benchmark(), "nope-lm-2048x24")
    ref = spec.load_module("reference", cfg["family"])
    serve = cfg["serve"]
    dtype = jnp.dtype(serve["weights_dtype"])
    settings = dict(serve["engine"], attn_impl="flash")   # what auto picks
    ecfg = EngineConfig(heads=cfg["num_attention_heads"],
                        dtype=jnp.dtype(serve["kv_dtype"]), **settings)
    shapes = ref.param_shapes(cfg)

    class ShapeEngine(Engine):
        """Engine whose weights and pools are shapes only."""

        def __init__(self):
            import mxnet_tpu.serve.engine as eng_mod
            real_asarray, real_pools = jnp.asarray, eng_mod.kvcache.make_pools
            sds = jax.ShapeDtypeStruct
            try:
                eng_mod.jnp.asarray = lambda v, *a, **k: (
                    v if isinstance(v, sds) else real_asarray(v, *a, **k))
                # the pools' shapes are ``kvcache.make_pools``'s own
                eng_mod.kvcache.make_pools = lambda *a, **k: jax.eval_shape(
                    lambda: real_pools(*a, **k))
                super().__init__({k: sds(s, dtype) for k, s in shapes.items()},
                                 ecfg)
            finally:
                eng_mod.jnp.asarray = real_asarray
                eng_mod.kvcache.make_pools = real_pools

    return ShapeEngine(), cfg


def _compile(eng, topo, kind, bucket):
    import jax
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])
    make = {"decode": eng._make_decode_fn,
            "prefill_chunk": eng._make_chunk_prefill_fn}[kind]
    avals = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        eng._avals(kind, bucket))
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        comp = jax.jit(make(bucket), donate_argnums=(0, 1)).trace(
            *avals).lower(lowering_platforms=("tpu",)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    return comp


@pytest.mark.parametrize("kind,bucket_key", [("decode", "max_batch"),
                                             ("prefill_chunk", "prefill_chunk")])
def test_serving_program_compiles_for_v5e_and_fits_one_chip(
        topo, engine, kind, bucket_key):
    eng, cfg = engine
    bucket = cfg["serve"]["engine"][bucket_key]
    comp = _compile(eng, topo, kind, bucket)
    m = comp.memory_analysis()
    mem = {"arguments": m.argument_size_in_bytes,
           "outputs": m.output_size_in_bytes,
           "aliased": m.alias_size_in_bytes,
           "temporaries": m.temp_size_in_bytes,
           "num_blocks": cfg["serve"]["engine"]["num_blocks"]}
    # donated pools alias their outputs: what the chip holds at once is
    # arguments + temporaries + whatever output is not an alias
    mem["sum"] = (mem["arguments"] + mem["temporaries"]
                  + mem["outputs"] - mem["aliased"])
    print(kind, bucket, {k: round(v / 1e9, 2) for k, v in mem.items()
                         if k != "num_blocks"})
    assert mem["sum"] < CHIP_BYTES, mem
    text = comp.as_text()
    if kind == "decode":
        # the kernel auto picks on the chip is in the program, once a layer
        assert text.count('custom_call_target="tpu_custom_call"') >= \
            cfg["num_hidden_layers"]
        assert "mxtpu_flash_decode" in text
