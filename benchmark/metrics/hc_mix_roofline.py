"""``mxtpu_hc_mix``'s share of its roofline INSIDE THE DECODE PROGRAM of
a latent model with hyper-connections: the kernel's device time within
the runs of the programs that hold ``mxtpu_mla_decode``
(``Summary.module_kernels``: the chunk program calls it too, on 1,024
positions), against what the traced decode steps needed: each
``serve.decode`` span's ``hc_calls`` connections over its ``active``
rows of ``hc_streams`` streams (``kernels/mxtpu_hc_mix.py``).  At 128
rows of 4 x 3,584 a call reads 3.7 MB of streams and 1.4 MB of Phi for
~0.1 GFLOP: bound by memory.  None where the spans carry no
``hc_calls`` (a model without connections, a parent commit) and where
the trace holds no decode run."""
import jax.numpy as jnp

from benchmark.harness import readers, spec

KERNEL = "mxtpu_hc_mix"
DECODE_HOLDS = "mxtpu_mla_decode"


def decode_kernel_ms(facts):
    """(the kernel's device ms inside the decode program's runs, those
    runs' device ms), or None where either is nothing."""
    tr = facts.get("trace")
    if tr is None:
        return None
    ms = sum(held.get(KERNEL, 0.0) for held in tr.module_kernels.values()
             if DECODE_HOLDS in held)
    program = sum(tr.programs_with(DECODE_HOLDS))
    return (ms, program) if ms and program else None


def read(facts):
    cfg = facts.get("config", {})
    times = decode_kernel_ms(facts)
    if times is None or "hc_mult" not in cfg:
        return None
    spans = spec.load_module("metrics", "gqa_decode_roofline") \
        .traced_decode_spans(facts, DECODE_HOLDS)
    spans = [ev["args"] for ev in spans if "hc_calls" in ev["args"]]
    if not spans:
        return None
    cost = readers.kernel_cost(KERNEL)
    itemsize = jnp.dtype(cfg["serve"]["weights_dtype"]).itemsize
    model = cfg["serve"]["engine"]["model"]
    total = {"bytes": 0.0, "flops": 0.0}
    for a in spans:
        c = cost(a["active"], a["hc_streams"], int(cfg["hidden_size"]),
                 int(model["hc_sinkhorn_iters"]), itemsize,
                 calls=a["hc_calls"])
        total["bytes"] += c["bytes"]
        total["flops"] += c["flops"]
    return readers.roofline_pct(total, times[0] / 1e3, facts["peaks"])
