"""``stall_ms_p98`` (the 98th percentile of the gaps between consecutive
content chunks the clients saw while the window was open) in a cell where it
is no end-to-end metric: there it sits on a cliff of the gap distribution, so
it is read beside the bounded metrics and judges nothing. What the scheduler's
interleaving of prefill chunks and decode steps lets through to a reader."""


def read(ctx):
    return ctx["e2e"].get("stall_ms_p98")
