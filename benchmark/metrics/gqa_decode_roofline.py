"""``mxtpu_gqa_decode``'s share of its roofline: what the traced decode
steps needed (each step's ``window_rows`` seen by every window layer and
``global_rows`` by every global one, and its rows' queries and outputs;
from the engine's ``serve.decode`` spans and the configuration's shapes)
against the kernel's device time in the trace.  The traced decode steps
are the window's last ``serve.decode`` spans, as many as the trace holds
runs of the decode program (the profiler runs over the window's last
seconds).  Bound by memory: 6 FLOP a byte at 48 query heads over 8 of
bf16.  None for a model without such layers, where the spans carry no
counts (a parent commit), and where the trace holds no call."""
import jax.numpy as jnp

from benchmark.harness import readers

KERNEL = "mxtpu_gqa_decode"


def traced_decode_spans(facts, kernel=KERNEL):
    """The ``serve.decode`` spans of the traced decode steps: the last of
    the window's, as many as the trace holds runs of programs that call
    ``kernel``; [] where there are none."""
    tr = facts.get("trace")
    runs = len(tr.programs_with(kernel)) if tr is not None else 0
    spans = sorted((ev for ev in facts.get("spans", ())
                    if ev["name"] == "serve.decode"), key=lambda ev: ev["ts"])
    return spans[-runs:] if runs else []


def read(facts):
    tr = facts.get("trace")
    cfg = facts.get("config", {})
    if tr is None or "sliding_window" not in cfg:
        return None
    seconds = tr.kernel_seconds(KERNEL)
    spans = [ev["args"] for ev in traced_decode_spans(facts)
             if "window_rows" in ev["args"]]
    if not seconds or not spans:
        return None
    kinds = cfg["layer_types"]
    window = sum(1 for k in kinds if k == "sliding_attention")
    shape = dict(heads=int(cfg["num_attention_heads"]),
                 kv_heads=int(cfg["num_key_value_heads"]),
                 head_dim=int(cfg["head_dim"]),
                 itemsize=jnp.dtype(cfg["serve"]["kv_dtype"]).itemsize)
    cost = readers.kernel_cost(KERNEL)
    total = {"bytes": 0.0, "flops": 0.0}
    for a in spans:
        for seen, layers in ((a["window_rows"], window),
                             (a["global_rows"], len(kinds) - window)):
            c = cost(seen, a["active"], layers=layers, **shape)
            total["bytes"] += c["bytes"]
            total["flops"] += c["flops"]
    return readers.roofline_pct(total, seconds, facts["peaks"])
