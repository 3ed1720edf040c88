"""Largest share of the KV block pool reserved at any dispatch of the window
(the allocator's own accounting, reservations included)."""

from harness import layerlib as ll


def read(ctx):
    w = ctx["window"]
    rows = ll.flight(ctx, w.t_open, w.t_close)
    return 100.0 * max(r["kv_utilization"] for r in rows) if rows else None
