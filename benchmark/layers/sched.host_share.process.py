"""Token processing's part of ``sched.host_share``: the flight ring's
``process_ms``, the measured wall of ``Scheduler._process_rows`` (stop checks,
detokenising, handing tokens to the streams; what ``sched.process`` brackets),
a part of ``gap_ms``, over the window's dispatch wall (harness/hostclock.py).
None where the program writes no such column."""

from harness import hostclock


def read(ctx):
    return hostclock.host_part_share(ctx, "process")
