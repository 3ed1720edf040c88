"""Of the window's decode rows ((live slot, step) pairs of the flight ring's
decode launches, compile rows left out), the share at or past the model's
``dense_len``: rows whose sparse layers SELECT the blocks they attend (the
ring's ``sparse_rows``, counted by the scheduler from each stream's length
when the launch was enqueued). Below 100 part of the window's steps ran plain
grouped-query attention over a short context and the cell's other readings
mix two regimes. None where the ring has no such column or no decode row
counted one (every other configuration, whose models select nothing, and the
parent)."""

from harness import layerlib as ll


def read(ctx):
    w = ctx["window"]
    rows = ll.flight(ctx, w.t_open, w.t_close, ("decode", "decode_n"))
    sparse = sum(r.get("sparse_rows") or 0 for r in rows)
    pairs = sum(r["steps"] * (r.get("live_slots") or 0) for r in rows)
    if not sparse or not pairs:
        return None
    return 100.0 * sparse / pairs
