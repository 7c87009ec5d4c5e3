"""Host time of one engine step: the median, over the window's
``serve.step`` spans that ran a program, of the step's ``dur`` minus the
``dur`` of the ``serve.fetch`` spans beneath it (found by walking
``args.parent``) -- what a step does not spend waiting on the device:
admission, table build, dispatch, token append, gauges.

None where the program records no such spans (a parent commit without
the tree), and None, not a number, where the tree is broken: a fetch
whose chain of parents is cut although it lies inside a recorded step
would silently count the device's time as the host's.  A cut chain at
the window's edge, where the step itself closed outside the window, is
the one expected case and is skipped."""
from benchmark.harness import stats


def read(facts):
    spans = facts.get("spans", ())
    by_id = {ev["args"]["id"]: ev for ev in spans
             if "id" in ev.get("args", {})}
    steps = {i: ev for i, ev in by_id.items() if ev["name"] == "serve.step"}
    waited = dict.fromkeys(steps, 0)
    for ev in spans:
        if ev["name"] != "serve.fetch":
            continue
        up = ev
        while up is not None and up["name"] != "serve.step":
            up = by_id.get(up.get("args", {}).get("parent"))
        if up is not None:
            waited[up["args"]["id"]] += ev["dur"]
        elif any(s["tid"] == ev["tid"] and s["ts"] <= ev["ts"]
                 and ev["ts"] + ev["dur"] <= s["ts"] + s["dur"]
                 for s in steps.values()):
            return None
    return stats.median((steps[i]["dur"] - w) / 1e3
                        for i, w in waited.items() if w)
