"""Rows an expert's bytes are paid for: over the window's decode dispatches
(flight ring), the token-expert pairs that landed on the held experts
(``local_assignments``, counted on the device) over the experts that had a
token (``experts_touched``), both summed over the expert blocks and steps of
a launch. 12 where the cell's 96 rows choose 4 of 32 held experts and every
expert is touched; ``ops/moe.py``'s kernel runs every row against every touched expert
whatever this reads, so rows / this is how many times the useful products it
does. None where the ring has no such columns (a model without routed
experts, the parent's)."""

from harness import layerlib as ll


def read(ctx):
    w = ctx["window"]
    rows = [r for r in ll.flight(ctx, w.t_open, w.t_close,
                                 ("decode", "decode_n"))
            if r.get("experts_touched")]
    touched = sum(r["experts_touched"] for r in rows)
    if not touched:
        return None
    return sum(r.get("local_assignments") or 0 for r in rows) / touched
