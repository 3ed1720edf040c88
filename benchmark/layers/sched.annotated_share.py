"""The share of the engine thread's busy loop that has a name on the
profiler's clock: the union of its ``sched.*`` annotations other than
``sched.idle`` inside the traced slice, over the slice less its ``sched.idle``
time. 100 where every stretch between two ``sched.admit``s lies under an
annotation (``sched.count`` and ``sched.record`` close what the six phases
left open); an ``unattributed`` idle gap is then one in which the thread was
not in its loop. None where the program annotates nothing."""

from harness import trace_reduce as tr


def read(ctx):
    trace = ctx.get("trace") or {}
    if not trace.get("phases"):
        return None
    lo, hi = trace["window_at_s"]
    named, idle = [], []
    for s, e, name in trace["phases"]:
        if e > lo and s < hi:
            (idle if name == "sched.idle" else named).append(
                (max(s, lo), min(e, hi)))
    busy = (hi - lo) - tr.union_seconds(idle)
    return 100.0 * tr.union_seconds(named) / busy if busy > 0 else None
