"""Mean share of the decode slots in use, over the window's decode dispatches
(flight ring), weighted by each dispatch's time."""

from harness import layerlib as ll


def read(ctx):
    w = ctx["window"]
    rows = ll.flight(ctx, w.t_open, w.t_close, ("decode", "decode_n"))
    wall = sum(r["dispatch_ms"] for r in rows)
    if not wall:
        return None
    return 100.0 * sum(r["occupancy"] * r["dispatch_ms"] for r in rows) / wall
