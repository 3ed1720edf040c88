"""The host's share of the dispatch wall over the window: the flight ring's
(gap + sched + launch) over dispatch time, compile-bearing rows left out. A
split of HOST time (obs/anatomy.py); the device's idle share comes from the
profiler, not from here."""

from harness import layerlib as ll


def read(ctx):
    w = ctx["window"]
    rows = ll.flight(ctx, w.t_open, w.t_close)
    wall = sum(r["dispatch_ms"] for r in rows)
    host = sum(r["gap_ms"] + r["sched_ms"] + r["launch_ms"] for r in rows)
    return 100.0 * host / wall if wall else None
