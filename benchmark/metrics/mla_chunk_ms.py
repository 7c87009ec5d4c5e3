"""Median time of one prefill chunk of a latent model: the ``dur`` of
the engine's ``serve.prefill`` spans that closed inside the WINDOW and
carry ``chunk_start`` (build, dispatch, the chunk program on the device
and the fetch), as ``retention_chunk_ms`` reads its model's: the traced
4 s need not hold a chunk, the 48 s window always holds some.  A chunk's
time grows with the prefix it attends over (the context walk's trip
count is data), so this is the median over chunks at every offset.

None for a model whose layers are not latent, and where the program
recorded no such span."""
from benchmark.harness import stats


def read(facts):
    model = facts["config"].get("serve", {}).get("engine", {}).get("model")
    if not model or model.get("attention") != "latent":
        return None
    return stats.median(ev["dur"] / 1e3 for ev in facts.get("spans", ())
                        if ev["name"] == "serve.prefill"
                        and "chunk_start" in ev.get("args", {}))
