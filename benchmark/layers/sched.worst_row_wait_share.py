"""The share of the window's worst row (``sched.worst_row_ms``) the engine thread
spent inside ``sched.wait_device`` (``wait_ms``): the device or the runtime
answered late (harness/hostclock.py)."""

from harness import hostclock


def read(ctx):
    return hostclock.worst_row_share(ctx, "wait_ms")
