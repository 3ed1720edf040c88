"""Bookkeeping's part of ``sched.host_share``: the flight ring's ``book_ms``, the
measured wall of the engine loop's own accounting (the step count and what a
launch holds under ``sched.count``; the routed counts, the EMAs and the ring
row under ``sched.record``), a part of ``gap_ms``, over the window's dispatch
wall (harness/hostclock.py). Also what the instrumentation costs a dispatch.
None where the program writes no such column."""

from harness import hostclock


def read(ctx):
    return hostclock.host_part_share(ctx, "book")
