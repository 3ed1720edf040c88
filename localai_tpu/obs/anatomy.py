"""Dispatch anatomy: where each engine dispatch's wall time went.

The flight ring (:mod:`obs.flight`) records each dispatch as one
wall-clock blob. This module owns the VOCABULARY that splits that blob —
with zero added device syncs — into four phases, so metrics, the debug
API, fleet telemetry, and the bench harness all speak the same names:

======  ===========================================================
phase   meaning
======  ===========================================================
gap     idle since the previous dispatch retired: host scheduling /
        staging between drains (row processing, queue bookkeeping,
        waits) that no other phase claims
sched   admit / select / host-mirror work before entering the runner
launch  time for the jit call to return — JAX dispatch is async, so
        this is enqueue overhead only, not device compute
sync    time blocked at the EXISTING result fetch (``np.asarray`` /
        ``int(tok)``): device-bound time when the host arrived early
======  ===========================================================

Three PARTS of ``gap`` are measured where the work happens (the four phases
above tile a dispatch; these lie inside ``gap`` and tile part of it):

=======  ==========================================================
part     meaning
=======  ==========================================================
process  the wall of ``Scheduler._process_rows`` (and of a first
         token's consume): stop checks, detokenising, handing the
         tokens to the streams
book     the engine loop's own bookkeeping: choosing the step count
         and counting what a launch holds in front of the enqueue;
         the routed counts, the EMAs and the ring row behind the
         drain. What the instrumentation itself costs a dispatch
free     dropping the drained dispatch's result: the device arrays
         die there, the runtime frees their buffers, and the call
         lets go of the GIL, which the stream threads that the
         tokens have just woken take in turn (on a 32-stream batch
         a millisecond, asleep: found by PR 53 behind the last
         clock read of a drain, at its ``return``)
=======  ==========================================================

``process + book + free <= gap`` for every record; what is left of gap is
the host time that still has no name.

Beside the wall clock a row carries the engine thread's OWN clocks for the
span since the previous row (obs.flight ``CLOCK_COLUMNS``): inside
``sched.wait_device``, inside ``sched.idle``, on a CPU, runnable with no core
(the machine's other processes had it), and blocked (asleep on the GIL, a
lock, a file). The five tile the span, so a long row names who owned it.

Attribution model (interval tiling). Each record's phases decompose the
wall interval its ``dispatch_ms`` accounts for — for pipelined records
the inter-drain interval, for synchronous records the issue→drain span —
NOT the dispatch's own per-issue timeline. ``sched``/``launch`` are
accumulated host measurements since the previous record; ``sync`` is the
measured block at the drain; ``gap`` is everything the interval holds
that no measured phase claims (computed by exclusion). Consequences:

* ``gap + sched + launch + sync <= dispatch_ms`` holds structurally for
  every record (gap is clamped at 0, measured phases are clamped to the
  interval), and windowed phase totals tile the timeline without double
  counting.
* ``host_overhead_fraction`` = (gap+sched+launch) / dispatch wall — the
  share of accounted time the host spent NOT blocked on the device. This
  is the number ROADMAP's fused k-step dispatch must drive down.
* How idle the DEVICE was is not in these columns. The host cannot
  tell from its own clock whether the device queue covered its time.
  ``POST /backend/trace`` measures it (the trace holds the device's
  operations beside the engine thread's ``sched.*`` phases, on one clock).

Caveats worth restating wherever these numbers render: compile-bearing
rows are excluded (a single trace would drown every phase); ``launch``
can absorb device back-pressure (a full dispatch queue makes the async
call itself block); records written by sources that predate or skip
attribution carry all-zero phases and show up as ``unattributed``.
"""

from __future__ import annotations

from typing import Any, Optional

#: Phase column order — stable; UI stacked bars and bench lines rely on it.
PHASES = ("gap", "sched", "launch", "sync")

#: Measured parts of ``gap``: inside it, so in no sum over PHASES.
PARTS = ("process", "book", "free")

QUANTILES = ("p50", "p90", "p99")

#: One-line phase definitions, served with /debug/anatomy payloads.
PHASE_HELP = {
    "gap": ("idle since the previous dispatch retired — host scheduling/"
            "staging no measured phase claims (by exclusion)"),
    "sched": "admit/select/host-mirror work before entering the runner",
    "launch": "time for the async jit call to return (enqueue overhead)",
    "sync": "time blocked at the existing result fetch (device-bound)",
    "process": ("part of gap: handling a dispatch's tokens (stop checks, "
                "detokenising, handing them to the streams)"),
    "book": ("part of gap: the engine loop's own bookkeeping (step count, "
             "what a launch holds, EMAs, the flight row)"),
    "free": ("part of gap: dropping the drained dispatch's device arrays "
             "(a runtime call that lets go of the GIL: the stream threads "
             "run here)"),
}

#: Window the scheduler/metrics plane summarizes over, matching the
#: step-time percentile window in Scheduler.metrics().
DEFAULT_WINDOW_S = 60.0


def summarize(flight: Any, window_s: Optional[float] = DEFAULT_WINDOW_S,
              now: Optional[float] = None) -> dict:
    """Windowed per-phase percentiles/totals + fractions for one ring."""
    return flight.phases(window_s=window_s, now=now)


def phase_quantiles(summary: dict) -> dict:
    """``{phase: {quantile: ms}}`` from a :func:`summarize` dict.

    Skips absent/None entries, so gauge feeding degrades cleanly on empty
    windows and on payloads from replicas that predate the phase columns.
    """
    out: dict = {}
    for ph in PHASES + PARTS:
        qs = {}
        for q in QUANTILES:
            v = summary.get(f"{ph}_ms_{q}")
            if v is not None:
                qs[q] = float(v)
        if qs:
            out[ph] = qs
    return out


def breakdown(flight: Any, window_s: Optional[float] = DEFAULT_WINDOW_S,
              now: Optional[float] = None) -> dict:
    """``GET /debug/anatomy`` payload: summary + per-phase wall shares.

    Adds ``phase_share`` (each phase's fraction of the windowed dispatch
    wall), the ``unattributed`` remainder (records whose writers did not
    attribute phases — all-zero columns — land here, never silently in a
    phase), ``part_share`` (process/book/free over the same wall: parts of
    gap, counted in no sum), and the phase definitions for self-description.
    The summary's ``thread`` block rides along.
    """
    s = summarize(flight, window_s=window_s, now=now)
    total = s.get("dispatch_ms_total") or 0.0
    attributed = 0.0
    shares: dict = {}
    for ph in PHASES:
        ms = s.get(f"{ph}_ms_total") or 0.0
        attributed += ms
        shares[ph] = round(ms / total, 4) if total > 0 else None
    unattr = max(0.0, total - attributed)
    s["phase_share"] = shares
    s["part_share"] = {
        pt: (round((s.get(f"{pt}_ms_total") or 0.0) / total, 4)
             if total > 0 else None) for pt in PARTS}
    s["unattributed_ms_total"] = round(unattr, 3)
    s["unattributed_share"] = (round(unattr / total, 4)
                               if total > 0 else None)
    s["window_s"] = window_s
    s["definitions"] = dict(PHASE_HELP)
    return s
