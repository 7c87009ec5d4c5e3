"""``mxtpu_moe_experts``'s share of its roofline INSIDE THE DECODE
PROGRAM: the kernel's device time within the runs of the programs that
hold ``mxtpu_mla_decode`` (``Summary.module_kernels``: the chunk program
calls the grouped product too, on other shapes), against what those
runs needed: the traced decode steps x the window's mean
``experts_hit`` a ``serve.decode`` span x an expert's three matrices,
plus the assignments' activations, and 2 FLOP a parameter and
``assigned_here``.  At 64 rows 36-37 of 40 held experts a layer are hit
in every step, so the window's mean loses little against the traced
steps' own.  Bound by memory: a hit expert's 47 MB for a few rows.  None
for a model without routed layers, where the spans carry no counts (a
parent commit), and where the trace holds no decode run."""
import jax.numpy as jnp

from benchmark.harness import readers

KERNEL = "mxtpu_moe_experts"
DECODE_HOLDS = "mxtpu_mla_decode"


def read(facts):
    tr = facts.get("trace")
    cfg = facts.get("config", {})
    if tr is None or "moe_intermediate_size" not in cfg:
        return None
    hit = readers.span_arg_mean(facts, "serve.decode", "experts_hit")
    here = readers.span_arg_mean(facts, "serve.decode", "assigned_here")
    runs = len(tr.programs_with(DECODE_HOLDS))
    ms = sum(held.get(KERNEL, 0.0) for held in tr.module_kernels.values()
             if DECODE_HOLDS in held)
    if hit is None or here is None or not runs or not ms:
        return None
    itemsize = jnp.dtype(cfg["serve"]["weights_dtype"]).itemsize
    routed = int(cfg["num_hidden_layers"]) - int(cfg["first_k_dense_replace"])
    cost = readers.kernel_cost(KERNEL)(
        runs * hit, runs * here, int(cfg["hidden_size"]),
        int(cfg["moe_intermediate_size"]), itemsize, routed)
    return readers.roofline_pct(cost, ms / 1e3, facts["peaks"])
