"""Largest share of the KV pool in use after any step of the window
(``alloc.num_used`` over the blocks that can be handed out; block 0 is
the trash block)."""


def read(facts):
    steps = facts.get("steps", ())
    if not steps:
        return None
    usable = facts["engine"]["num_blocks"] - 1
    return 100.0 * max(s["kv_used"] for s in steps) / usable
