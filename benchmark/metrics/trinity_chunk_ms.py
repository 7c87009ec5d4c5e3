"""Median time of one prefill chunk of a model with window layers: the
``dur`` of the engine's ``serve.prefill`` spans that closed inside the
WINDOW and carry ``chunk_start`` (build, dispatch, the chunk program on
the device and the fetch), as ``mla_chunk_ms`` reads the latent model's.
A chunk's time grows with the prefix its global layer attends over (the
walk's trip count is data), so this is the median over chunks at every
offset.  None for a model without window layers, and where the program
recorded no such span."""
from benchmark.harness import stats


def read(facts):
    model = facts["config"].get("serve", {}).get("engine", {}).get("model")
    if not model or "sliding" not in model.get("attention", ()):
        return None
    return stats.median(ev["dur"] / 1e3 for ev in facts.get("spans", ())
                        if ev["name"] == "serve.prefill"
                        and "chunk_start" in ev.get("args", {}))
