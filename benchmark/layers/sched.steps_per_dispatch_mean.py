"""Decode steps a dispatch, over the window's decode dispatches (flight ring):
the mean over the window's decode STEPS of the step count k of the dispatch
each rode in (sum of k squared over sum of k), so a window that mixes k reads
where its tokens were made. A stream's token waits for the rest of its
dispatch and an arrival for the dispatches ahead of it: what
``Scheduler._effective_steps`` chose, compile-bearing rows left out."""

from harness import layerlib as ll


def read(ctx):
    w = ctx["window"]
    rows = ll.flight(ctx, w.t_open, w.t_close, ("decode", "decode_n"))
    steps = sum(r["steps"] for r in rows)
    if not steps:
        return None
    return sum(r["steps"] ** 2 for r in rows) / steps
