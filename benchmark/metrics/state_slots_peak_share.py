"""Largest share of the recurrent-state slots in use after any step of
the window (``alloc.num_used`` over the slots that can be handed out;
slot 0 is the trash slot).  None for a model with paged K/V, whose
``num_used`` counts blocks (``kv_peak_used_share``)."""


def read(facts):
    steps = facts.get("steps", ())
    model = facts["config"].get("serve", {}).get("engine", {}).get("model")
    if not steps or not model or model.get("attention") != "power_retention":
        return None
    usable = facts["engine"]["num_blocks"] - 1
    return 100.0 * max(s["kv_used"] for s in steps) / usable
