"""Median time the training loop waited on the prefetch queue: the
``dur`` of the program's ``prefetch.wait`` spans (the consumer's side of
``io.DevicePrefetchIter``, on the loop's own thread).  Unlike
``input_wait_ms``, timed from outside, it holds neither the label's copy
to the host nor the iterator's restart at the end of the host batches."""
from benchmark.harness import stats


def read(facts):
    return stats.median(ev["dur"] / 1e3 for ev in facts.get("spans", ())
                        if ev["name"] == "prefetch.wait")
