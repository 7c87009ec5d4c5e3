"""Median device time of one run of the decode program, from the
trace's ``XLA Modules`` line: the programs that hold the
``mxtpu_flash_decode`` kernel.  (The engine's ``serve.decode`` span closes
before the step's blocking fetch and so times the dispatch alone: not
read.)"""
from benchmark.harness import stats


def read(facts):
    tr = facts.get("trace")
    if tr is None:
        return None
    return stats.median(tr.programs_with("mxtpu_flash_decode"))
