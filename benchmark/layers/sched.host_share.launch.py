"""The enqueue's part of ``sched.host_share``: the flight ring's ``launch_ms`` (the
jit call's return and the start of the copy back) over the window's dispatch
wall, compile-bearing rows left out. With ``.admit``, ``.process``, ``.book``,
``.free`` and ``.unnamed`` it sums to ``sched.host_share`` (harness/hostclock.py)."""

from harness import hostclock


def read(ctx):
    return hostclock.host_part_share(ctx, "launch")
