"""Median device time of one run of the decode program of a
recurrent-state model, from the trace's ``XLA Modules`` line: the
programs that hold the ``mxtpu_retention_decode`` kernel
(``jit_fn_decode``)."""
from benchmark.harness import stats


def read(facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    return stats.median(tr.programs_with("mxtpu_retention_decode"))
