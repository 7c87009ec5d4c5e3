"""``mxtpu_retention_decode``'s share of its roofline: the bytes the
decode steps inside the traced window had to move (their active rows'
states in and out, the rows' q, k, v, gate and y; from the runner's
per-step record and the configuration's shapes) over HBM bandwidth,
against the kernel's device time in the trace.  Bound by memory: 13
FLOPs for every 8 bytes."""
from benchmark.harness import readers

KERNEL = "mxtpu_retention_decode"


def read(facts):
    tr = facts.get("trace")
    if tr is None or "engine" not in facts:
        return None
    seconds = tr.kernel_seconds(KERNEL)
    cfg, e = facts["config"], facts["engine"]
    if not seconds or "num_key_value_heads" not in cfg:
        return None
    cost = readers.kernel_cost(KERNEL)
    import jax.numpy as jnp
    act = jnp.dtype(cfg["serve"]["weights_dtype"]).itemsize
    total = {"bytes": 0.0, "flops": 0.0}
    for s in facts.get("steps", ()):
        if s["traced"] and s["rows"]:
            c = cost(s["rows"], e["heads"], int(cfg["num_key_value_heads"]),
                     e["head_dim"], act, e["layers"])
            total["bytes"] += c["bytes"]
            total["flops"] += c["flops"]
    if not total["bytes"]:
        return None
    return readers.roofline_pct(total, seconds, facts["peaks"])
