"""Largest share of the global layers' blocks in use after any step of
the window: the engine's ``serve.kv.global_blocks_used`` gauge over the
global pool's blocks that can be handed out (block 0 is the trash
block), as the runner recorded it after each step.  Near 100 % the pool
would preempt; far under it the pool is larger than the traffic needs.
None where the runner recorded no such count."""


def read(facts):
    b = facts.get("blocks")
    if not b:
        return None
    return 100.0 * b["global_peak"] / b["global_usable"]
