"""Median time of one prefill chunk of a recurrent-state model: the
``dur`` of the engine's ``serve.prefill`` spans that closed inside the
window and carry ``chunk_start`` (build, dispatch, the program on the
device and the fetch; the step waits on the fetch, so the device is
idle when the chunk is dispatched and the span is the program's device
time plus the host's part of one dispatch).

Not the trace's ``XLA Modules`` line, which ``prefill_chunk_ms`` reads:
a request of this cell decodes for 28-56 s, so the last ``trace_s``
(4 s) of the window hold no chunk on about one seed in five, while the
48 s window always holds some (client 0's second request ends inside it
on every seed, and its third is prefilled then).

None for a model with paged K/V (that is ``prefill_chunk_ms``'s), and
where the program recorded no such span."""
from benchmark.harness import stats


def read(facts):
    model = facts["config"].get("serve", {}).get("engine", {}).get("model")
    if not model or model.get("attention") != "power_retention":
        return None
    return stats.median(ev["dur"] / 1e3 for ev in facts.get("spans", ())
                        if ev["name"] == "serve.prefill"
                        and "chunk_start" in ev.get("args", {}))
