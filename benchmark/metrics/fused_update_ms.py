"""Device time of ``mxtpu_fused_update`` per optimizer step (all its
calls of a step together), from the trace.  Not a roofline share: XLA
stages the kernel's operands into fast memory with asynchronous copies
that run OUTSIDE the kernel's own interval, so bytes over the kernel's
time reads above 100 % (PERF.md, Open questions)."""

KERNEL = "mxtpu_fused_update"


def read(facts):
    tr, t = facts.get("trace"), facts.get("train")
    if tr is None or not t or not t["fused_update"]:
        return None
    return tr.kernel_ms_per_run(KERNEL)
