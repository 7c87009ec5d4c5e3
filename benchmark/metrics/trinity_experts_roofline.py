"""``mxtpu_moe_experts``'s share of its roofline INSIDE THE DECODE
PROGRAM of a model with grouped-query layers: the kernel's device time
within the runs of the programs that hold ``mxtpu_gqa_decode``
(``Summary.module_kernels``: the chunk program calls the grouped product
too, on other shapes), against what the traced decode steps needed:
their ``serve.decode`` spans' ``experts_hit`` x an expert's three
matrices, plus the assignments' activations, and 2 FLOP a parameter and
``assigned_here`` (``kernels/mxtpu_moe_experts.py``).  At 32 rows x 4 of
256 a held expert has 0.5 assignments a step: ~12.7 of 32 hit a layer,
bound by memory.  ``moe_experts_roofline`` reads the same kernel inside
the latent model's decode program.  None where the spans carry no counts
(a parent commit) and where the trace holds no decode run."""
import jax.numpy as jnp

from benchmark.harness import readers, spec

KERNEL = "mxtpu_moe_experts"
DECODE_HOLDS = "mxtpu_gqa_decode"


def read(facts):
    tr = facts.get("trace")
    cfg = facts.get("config", {})
    if tr is None or "num_dense_layers" not in cfg:
        return None
    spans = spec.load_module("metrics", "gqa_decode_roofline") \
        .traced_decode_spans(facts, DECODE_HOLDS)
    spans = [ev["args"] for ev in spans if "experts_hit" in ev["args"]]
    ms = sum(held.get(KERNEL, 0.0) for held in tr.module_kernels.values()
             if DECODE_HOLDS in held)
    if not spans or not ms:
        return None
    itemsize = jnp.dtype(cfg["serve"]["weights_dtype"]).itemsize
    routed = int(cfg["num_hidden_layers"]) - int(cfg["num_dense_layers"])
    cost = readers.kernel_cost(KERNEL)(
        sum(a["experts_hit"] for a in spans),
        sum(a["assigned_here"] for a in spans), int(cfg["hidden_size"]),
        int(cfg["moe_intermediate_size"]), itemsize, routed)
    return readers.roofline_pct(cost, ms / 1e3, facts["peaks"])
