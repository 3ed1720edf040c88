"""Of the time the engine thread had work in the window (its rows' ``span_ms``
less ``wait_ms`` and ``idle_ms``), the share it was NOT on a CPU: runnable with
no core (``runq_ms``) or asleep on the GIL, a lock or a file (``blocked_ms``):
the flight ring's thread clocks (harness/hostclock.py). A CPU clock that ticks
coarser than a row is long is carried from row to row, not cut
(``obs/flight.py ThreadClock``), so the window's sums are sound to one tick.
None where the program writes no such columns."""

from harness import hostclock


def read(ctx):
    return hostclock.offcpu_share(ctx)
