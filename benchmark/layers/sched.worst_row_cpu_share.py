"""The share of the window's worst row (``sched.worst_row_ms``) the engine thread
spent on a CPU (``cpu_ms``): the program's own Python (harness/hostclock.py).
What wait and cpu leave to 100 was not on a CPU: asleep (``blocked_ms``) or,
where the kernel says, runnable with no core (``runq_ms``).

ONE row's ``cpu_ms`` is good to one tick of the thread's CPU clock, and on the
chip's machine that clock ticks in 10 ms (a tick the row had no room for is
credited to the rows behind it: ``obs/flight.py ThreadClock``): of a worst row
of 30-50 ms the share is good to 20-30 points, of a stall of 100 ms and more
to under 10."""

from harness import hostclock


def read(ctx):
    return hostclock.worst_row_share(ctx, "cpu_ms")
