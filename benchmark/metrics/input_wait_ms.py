"""Median time the loop spent in ``next(iter)`` before a step: what the
input pipeline did not hide."""
from benchmark.harness import stats


def read(facts):
    t = facts.get("train")
    return stats.median(t["input_wait_ms"]) if t else None
