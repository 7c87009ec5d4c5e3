"""``mxtpu_mla_decode``'s share of its roofline: what the decode steps
inside the traced window needed (their rows' live latent rows once, the
absorbed queries in and the outputs out; 278,528 FLOP a cached position
and layer at the published sizes; from the runner's per-step record and
the configuration's shapes) against the kernel's device time in the
trace.  The kernel sits at the ridge (242 FLOP a byte against the
chip's 240), so ``roofline_pct`` takes whichever bound is larger.  None
for a model without latent layers, and where the trace holds no call."""
from benchmark.harness import readers

KERNEL = "mxtpu_mla_decode"


def read(facts):
    tr = facts.get("trace")
    cfg = facts.get("config", {})
    if tr is None or "engine" not in facts or "kv_lora_rank" not in cfg:
        return None
    seconds = tr.kernel_seconds(KERNEL)
    if not seconds:
        return None
    e = facts["engine"]
    cost = readers.kernel_cost(KERNEL)
    total = {"bytes": 0.0, "flops": 0.0}
    for s in facts.get("steps", ()):
        if s["traced"] and s["rows"]:
            c = cost(s["cached_tokens"], s["rows"], e["heads"],
                     int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"]),
                     e["kv_itemsize"], e["layers"])
            total["bytes"] += c["bytes"]
            total["flops"] += c["flops"]
    if not total["bytes"]:
        return None
    return readers.roofline_pct(total, seconds, facts["peaks"])
