"""Largest share of the window layers' blocks in use after any step of
the window: the engine's ``serve.kv.window_blocks_used`` gauge over the
window pool's blocks that can be handed out (block 0 is the trash
block), as the runner recorded it after each step.  The pool holds a
full ring for every decode slot, so at 100 % every slot holds a full
ring and no request waits for a window block.  None where the runner
recorded no such count."""


def read(facts):
    b = facts.get("blocks")
    if not b:
        return None
    return 100.0 * b["window_peak"] / b["window_usable"]
