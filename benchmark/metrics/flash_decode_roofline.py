"""``mxtpu_flash_decode``'s share of its roofline: the bytes the decode
steps inside the traced window had to read (their rows' live keys and
values, from the runner's per-step record) over HBM bandwidth, against
the kernel's device time in the trace.  The kernel is bound by memory:
4 FLOPs for every 2 x itemsize bytes."""
from benchmark.harness import readers

KERNEL = "mxtpu_flash_decode"


def read(facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    seconds = tr.kernel_seconds(KERNEL)
    e = facts["engine"]
    cost = readers.kernel_cost(KERNEL)
    total = {"bytes": 0.0, "flops": 0.0}
    for s in facts.get("steps", ()):
        if s["traced"] and s["rows"]:
            c = cost(s["cached_tokens"], s["rows"], e["heads"],
                     e["head_dim"], e["kv_itemsize"], e["layers"])
            total["bytes"] += c["bytes"]
            total["flops"] += c["flops"]
    if not seconds or not total["bytes"]:
        return None
    return readers.roofline_pct(total, seconds, facts["peaks"])
