"""The hyper-connection configuration's numbers against numbers worked by
hand: its parameter count, the connection kernel's cost, the readers
that find nothing on a parent's spans, and ``hc_error``'s two sides at a
tiny size (the kernel passes its limit, bfloat16 maps do not)."""
import numpy as np
import pytest

from benchmark.harness import readers, spec
from benchmark.run import merged

BENCH = spec.load_benchmark()
CFG = spec.load_config(BENCH, "xing4-29b-a4b-6of40")
xing4 = spec.load_module("reference", "xing4")


def test_the_stage_holds_4_79_billion_parameters():
    # q_a, its norm, q_b, kv_a, its norm, kv_b, o, the two norms
    mla = (3584 * 768 + 768 + 768 * 32 * 192 + 3584 * 576 + 512
           + 512 * 32 * 256 + 4096 * 3584 + 2 * 3584)
    hc = 2 * (24 * 14_336 + 24 + 3)         # two connections a layer
    expert = 3 * 3584 * 1024
    layer = {"dense": mla + 3 * 3584 * 9216 + hc,
             "routed": mla + 3584 * 64 + 64 + 65 * expert + hc}
    want = (layer["dense"] + 5 * layer["routed"] + 2 * 131_072 * 3584
            + 3584)
    assert xing4.param_count(CFG) == want == CFG["parameters"]
    assert want == 4_792_669_828


def test_the_connection_kernel_needs_its_streams_phi_and_maps():
    cost = readers.kernel_cost("mxtpu_hc_mix")
    c = cost(rows=128, n=4, d=3584, iters=20, itemsize=2)
    assert c["bytes"] == 128 * (4 * 3584 + 3584) * 2 + 24 * 14336 * 4 \
        + 128 * 24 * 4
    assert c["flops"] == 128 * (2 * 24 * 14336 + 4 * 4 * 3584 + 20 * 64)
    assert cost(128, 4, 3584, 20, 2, calls=12)["bytes"] == 12 * c["bytes"]


@pytest.mark.parametrize("metric", ["hc_mix_roofline", "hc_mix_share"])
def test_a_run_without_the_kernel_reads_nothing(metric):
    class Trace:
        module_kernels = {"jit_fn_decode": {"mxtpu_mla_decode": 3.0}}

        def programs_with(self, kernel):
            return [17.0] if kernel == "mxtpu_mla_decode" else []

    facts = {"trace": Trace(), "config": CFG, "spans": [
        {"name": "serve.decode", "ts": 0, "dur": 9,
         "args": {"active": 128}}]}
    assert spec.load_reader(metric).read(facts) is None
    assert spec.load_reader(metric).read({"config": CFG}) is None


def test_hc_error_passes_the_kernel_and_refuses_bfloat16_maps():
    import jax
    import jax.numpy as jnp

    cfg = merged(CFG, CFG["rehearsal"])
    cfg["serve"]["weights_dtype"] = "bfloat16"
    hc = spec.load_module("runners", "serve_engine_closed_hc")
    key = jax.random.PRNGKey(3)
    d = int(cfg["hidden_size"])
    params = {"layer0_hc_attn_weight": jax.random.normal(key, (24, 4 * d))
              * 0.2,
              "layer0_hc_attn_bias": jax.random.normal(
                  jax.random.fold_in(key, 1), (24,)) * 0.3,
              "layer0_hc_attn_scale": jnp.asarray([0.4, 0.4, 0.4])}
    tol = float(CFG["serve"]["hc_tolerance"])
    assert hc.hc_error(params, cfg, 5) < tol / 10
    assert hc.hc_error(params, cfg, 5, control=True) > tol * 10
    assert np.isfinite(hc.hc_error(params, cfg, 6))
