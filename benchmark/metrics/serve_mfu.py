"""The served tokens' share of the chip's peak: the forward FLOPs the
model needs for the positions the window completed (decoded one at a
time, and prefilled; analytic, from the configuration's reference
module: its matmuls and its attention over the positions each one had
cached) over the window and the chips' peak.  The whole engine step's
number beside the kernels' rooflines: a kernel taken off the path leaves
its roofline silent, and this still bounds the claim.  It reads no trace
event, so every traced run reports it.  Decode at a few tens of rows is
bound by memory, and the number says so."""
from benchmark.harness import spec


def read(facts):
    s = facts.get("served")
    if not s or not s["decoded"] + s["prefilled"]:
        return None
    ref = spec.load_module("reference", facts["config"]["family"])
    flops = ref.forward_flops(facts["config"], s["decoded"] + s["prefilled"],
                              s["attended"])
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * flops / facts["window_s"] / peak
