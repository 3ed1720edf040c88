"""The longest wall the engine thread spent on one row of the window, idle left
out: the largest ``span_ms - idle_ms`` of a non-compile flight row
(harness/hostclock.py). A run that stalled reads its stall here, and
``sched.worst_row_*_share`` say who owned it. None where the program writes no
such columns."""

from harness import hostclock


def read(ctx):
    row = hostclock.worst_row(ctx)
    return None if row is None else hostclock.busy_ms(row)
