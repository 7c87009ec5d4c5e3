"""Median device time of one run of the chunk-prefill program, from the
trace's ``XLA Modules`` line: the engine's programs (all named
``jit_fn``) that do NOT hold the flash-decode kernel.  (The engine's
``serve.prefill`` span closes before the step's blocking fetch and so
times the dispatch alone, 2 ms: not read.)"""
from benchmark.harness import stats


def read(facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    return stats.median(tr.programs_with("mxtpu_flash_decode", False,
                                         prefix="jit_fn"))
