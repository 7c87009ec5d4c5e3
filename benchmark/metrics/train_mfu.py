"""Model FLOP/s utilization: the forward and backward FLOPs a step
requires (analytic, from the configuration's reference module; no
recomputation, no optimizer) over the step time and the chips' peak."""


def read(facts):
    t = facts.get("train")
    if not t or not t["steps"]:
        return None
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * t["flops_per_step"] / (t["step_ms"] / 1e3) / peak
