"""Passes over the layer stack a decode step, over the window's decode
dispatches (flight ring): the sum of their ``passes`` over the sum of their
``steps``. 4.0 where every token takes the published four passes; the number a
later change that skips work (an exit gate that is served) would move. None
where the ring has no such column (a program from before PR 37)."""

from harness import layerlib as ll


def read(ctx):
    w = ctx["window"]
    rows = [r for r in ll.flight(ctx, w.t_open, w.t_close,
                                 ("decode", "decode_n")) if "passes" in r]
    steps = sum(r["steps"] for r in rows)
    if not steps:
        return None
    return sum(r["passes"] for r in rows) / steps
