"""Median device time of one run of the decode program of a model with
grouped-query layers, from the trace's ``XLA Modules`` line: the
programs that hold the ``mxtpu_gqa_decode`` kernel (``jit_fn_decode``).
None where no program holds it."""
from benchmark.harness import stats


def read(facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    return stats.median(tr.programs_with("mxtpu_gqa_decode"))
