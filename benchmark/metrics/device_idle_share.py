"""Share of the traced window in which no operation ran on the device
(1 - union of device-op intervals / window, mean over the chips used)."""
from benchmark.harness import readers


def read(facts):
    return readers.idle_share_pct(facts)
