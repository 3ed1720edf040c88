"""The part of ``sched.host_share`` spent dropping a drained dispatch's result:
the flight ring's ``free_ms``, the measured wall of the ``del`` at the end of
a drain (``sched.free``), a part of ``gap_ms``, over the window's dispatch
wall (harness/hostclock.py). The device arrays die there, the runtime frees
their buffers, and the call lets go of the GIL, which the stream threads the
tokens have just woken take in turn: the engine thread is asleep for most of
it. None where the program writes no such column."""

from harness import hostclock


def read(ctx):
    return hostclock.host_part_share(ctx, "free")
