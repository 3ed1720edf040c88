"""What the flight ring says of the engine thread's own time (PR 53): the
host's share of a dispatch by measured parts, and who owned the thread's
time, over the window's rows and in its worst one.

A ring row (``/debug/flight``; localai_tpu/obs/flight.py) carries, beside
the four phases that tile its ``dispatch_ms`` (gap, sched, launch, sync),

  * three measured PARTS of ``gap_ms``: ``process_ms`` (the wall of
    ``Scheduler._process_rows``), ``book_ms`` (the engine loop's own
    bookkeeping) and ``free_ms`` (dropping the drained dispatch's device
    arrays: the runtime call lets go of the GIL and the stream threads run);
    what is left of ``gap_ms`` is host time no one has named;
  * the engine thread's own clocks for the wall since the previous row's
    span ended (``span_ms``; a row's span ends where the interval its
    ``dispatch_ms`` accounts for ends): inside ``sched.wait_device`` (``wait_ms``), inside
    ``sched.idle`` (``idle_ms``), on a CPU (``cpu_ms``), runnable with no
    core (``runq_ms``; None where the kernel's file cannot be read, as on
    the machine the benchmark runs on: no reader here reads it alone), and
    asleep on the GIL, a lock or a file (``blocked_ms``): the five sum to the
    span.

Every function reads the WINDOW's rows (``layerlib.flight``: compile-bearing
rows left out), the rows ``sched.host_share`` reads, and answers None where
the program writes no such column (a parent of PR 53).
"""

from __future__ import annotations

from typing import Optional

from harness import layerlib as ll

# the parts of the host's share: the ring's column, or gap's remainder
HOST_PARTS = {"launch": "launch_ms", "admit": "sched_ms",
              "process": "process_ms", "book": "book_ms", "free": "free_ms",
              "unnamed": None}


def window_rows(ctx: dict, programs=()) -> list[dict]:
    w = ctx["window"]
    return ll.flight(ctx, w.t_open, w.t_close, programs)


def host_part_share(ctx: dict, part: str) -> Optional[float]:
    """One part of ``sched.host_share``, in % of the same dispatch wall: the
    six parts sum to it."""
    rows = window_rows(ctx)
    wall = sum(r["dispatch_ms"] for r in rows)
    if not wall or any("free_ms" not in r for r in rows):
        return None
    column = HOST_PARTS[part]
    if column is None:
        ms = sum(r["gap_ms"] - r["process_ms"] - r["book_ms"] - r["free_ms"]
                 for r in rows)
    else:
        ms = sum(r[column] for r in rows)
    return 100.0 * ms / wall


def busy_ms(row: dict) -> float:
    """The wall of a row's span in which the engine thread had work."""
    return row["span_ms"] - row["idle_ms"]


def clocked_rows(ctx: dict) -> list[dict]:
    """The window's rows that carry the thread's clocks and a span."""
    return [r for r in window_rows(ctx) if r.get("span_ms")]


def offcpu_share(ctx: dict) -> Optional[float]:
    """Of the time the engine thread had work (its spans less what it spent
    in ``sched.wait_device`` and ``sched.idle``), the % it was not on a CPU:
    runnable with no core (where the kernel says) plus blocked."""
    rows = clocked_rows(ctx)
    had_work = sum(busy_ms(r) - r["wait_ms"] for r in rows)
    if had_work <= 0:
        return None
    off = sum((r["runq_ms"] or 0.0) + r["blocked_ms"] for r in rows)
    return 100.0 * off / had_work


def worst_row(ctx: dict) -> Optional[dict]:
    """The window's row whose span, idle left out, is the longest."""
    rows = clocked_rows(ctx)
    return max(rows, key=busy_ms) if rows else None


def worst_row_share(ctx: dict, column: str) -> Optional[float]:
    """A state's % of the worst row's busy wall (None where that row could
    not read the state)."""
    row = worst_row(ctx)
    if row is None or row[column] is None or busy_ms(row) <= 0:
        return None
    return 100.0 * row[column] / busy_ms(row)
