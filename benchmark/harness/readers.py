"""Small reductions that several per-layer readers share.  A reader
takes the run's ``facts`` and returns a number, or None where there is
nothing to read (the harness then leaves the metric out of the line)."""
from __future__ import annotations

from typing import Any, Dict, Optional

from . import spec


def span_arg_mean(facts: Dict[str, Any], name: str, arg: str) -> Optional[float]:
    """Mean of one argument over the program's telemetry spans called
    ``name`` that closed inside the window."""
    vals = [ev["args"][arg] for ev in facts.get("spans", ())
            if ev["name"] == name and arg in ev.get("args", {})]
    return sum(vals) / len(vals) if vals else None


def idle_share_pct(facts: Dict[str, Any]) -> Optional[float]:
    """100 x (1 - union of device-op intervals / traced window), mean over
    the chips used."""
    tr = facts.get("trace")
    if tr is None or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s / tr.window_s)


def roofline_pct(cost: Dict[str, float], seconds: float,
                 peaks: Dict[str, Any]) -> Optional[float]:
    """The least time one chip could take for ``cost`` (the larger of
    FLOPs over peak FLOP/s and bytes over peak bytes/s) over the time
    taken."""
    if seconds <= 0:
        return None
    least = max(cost["flops"] / peaks["bf16_flops_per_s"],
                cost["bytes"] / peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds


def kernel_cost(name: str):
    return spec.load_module("kernels", name).cost
