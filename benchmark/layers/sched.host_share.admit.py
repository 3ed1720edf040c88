"""The admission's part of ``sched.host_share``: the flight ring's ``sched_ms``
(``Scheduler._admit_pending`` under ``sched.admit``, and a chunk row's staging)
over the window's dispatch wall (harness/hostclock.py)."""

from harness import hostclock


def read(ctx):
    return hostclock.host_part_share(ctx, "admit")
