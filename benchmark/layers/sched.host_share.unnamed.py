"""The part of ``sched.host_share`` that still has no name: the flight ring's
``gap_ms - process_ms - book_ms - free_ms`` over the window's dispatch wall
(harness/hostclock.py). None where the program writes no such columns."""

from harness import hostclock


def read(ctx):
    return hostclock.host_part_share(ctx, "unnamed")
