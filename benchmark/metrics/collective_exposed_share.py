"""Share of the device's busy time spent in collective operations that
nothing overlaps: the instructions of the trace's ``XLA Ops`` line whose
names are collectives (``all-reduce``, ``all-gather``, ``reduce-scatter``,
``collective-permute``, ``all-to-all``, and the ``-start`` / ``-done``
halves of their asynchronous forms), over the union of all device ops,
mean over the chips.  That line is one instruction stream a core: while
a collective (or the wait for one, ``-done``) is on it, no compute is,
so every second counted here is exposed.  None where the trace holds no
collective (one chip)."""
import re

COLLECTIVE = re.compile(r"^(all-reduce|all-gather|reduce-scatter|"
                        r"collective-permute|all-to-all|"
                        r"collective-broadcast)")


def read(facts):
    tr = facts.get("trace")
    if tr is None or tr.busy_s <= 0:
        return None
    seconds = sum(s for name, s in tr.op_seconds.items()
                  if COLLECTIVE.match(name))
    if not seconds:
        return None
    return 100.0 * seconds / tr.busy_s
