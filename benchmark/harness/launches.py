"""Which flight-ring row a device execution belongs to, with no clock
arithmetic between processes (PR 39).

The scheduler numbers every serving program it launches. The number is the
``launch`` column of the launch's ring row, which also says what the launch
HELD when it was enqueued (a decode row: ``live_slots``, ``attended_tokens``;
a prefill row: ``chunk_tokens``, ``chunk_bucket``, ``chunk_offset``,
``chunk_ctx``), and it is in the NAME of a host annotation around the enqueue,
``sched.launch/<n>``, nested in the engine thread's open phase. The trace
holds those annotations (``trace["phases"]``) and chip 0's program executions
(``trace["modules"]``) on the profiler's one clock, and the device runs
launches in the order they were made. So the i-th annotated launch of a kind
this file knows is the i-th such execution that starts at or after the first
annotation's start:

  * an execution that starts before it was launched before the capture
    began: left out;
  * a launch whose execution the slice does not hold (enqueued as the
    capture stopped) is left out too.

What is left out is left out of BOTH sides of every share: work and seconds
are summed over the matched pairs alone. A row is found by ``launch``, never
by ``ts_unix``.

A wrong join must not read as a number, so every pair is held to what the
engine loop guarantees of a right one, and ONE pair that breaks it voids the
slice for the readers here (``join`` answers None, and the ``trace`` line
says ``launches: {"mismatch": ...}``; the run goes on):

  * the execution's kind is its row's;
  * it starts no earlier than its enqueue began;
  * it has ended when the ``depth``-th decode launch after it begins
    (``engine.pipeline_depth``): the loop reads a dispatch's result, which
    waits for its execution and for every chunk queued in front of it,
    before it lets that launch go. A join that has slipped by one (an
    execution the device plane did not record, a chunk queued from before
    the capture) breaks this on a busy device, where a step ends only while
    the host waits for it with the next one already enqueued;
  * a launch the ring has no row for lies behind every launch it has one
    for (its kind is not known, so neither is what it ran).
"""

from __future__ import annotations

import bisect
import re
from typing import Optional

from harness import layerlib

LAUNCH = "sched.launch/"
# the programs of a kind, by the names the readers match today
# (jit__decode_paged_fn, jit__decode_paged_n_fn; jit__prefill_paged_fn)
KINDS = (("decode", re.compile(r"decode")), ("prefill", re.compile(r"prefill")))


def module_kind(name: str) -> Optional[str]:
    return next((k for k, rx in KINDS if rx.search(name)), None)


def row_kind(program: str) -> Optional[str]:
    """The kind of program a ring row's launch enqueued: ``decode``,
    ``decode_n`` and ``decode_frozen_n`` run a decode program,
    ``prefill_chunk`` a prefill one; a speculative window (``spec``) runs
    neither, and its execution is not among those joined."""
    if program.startswith("decode"):
        return "decode"
    return "prefill" if program.startswith("prefill") else None


def real_pairs(row: dict) -> int:
    """(query, attended) pairs a chunk's REAL tokens need: each attends the
    cached tokens in front of the chunk, and causally its own."""
    t = row["chunk_tokens"]
    return t * row["chunk_offset"] + t * (t + 1) // 2


def _void(trace: dict, why: str, **where) -> None:
    trace.setdefault("notes", {})["launches"] = {"mismatch": why, **where}


def join(ctx: dict) -> Optional[dict]:
    """The slice's matched pairs: ``{"pairs": [(row, kind, start, end)],
    "early": executions left out in front, "late": executions left out
    behind, "unrun": the numbers of the launches left out behind, "unrowed":
    annotated launches the ring has no row for}``. None where there is
    nothing to join (no trace, a program that annotates no launch, a ring
    with no ``launch`` column: the parent's) and where the join does not
    hold (the module's docstring). The counts go onto the ``trace`` line as
    ``launches``, with the least room a pair left to each of its two bounds
    (``slack_ms``: start after the enqueue, end before the bounding launch;
    None where no pair had that bound)."""
    trace = ctx.get("trace")
    if not trace:
        return None
    rows = {r["launch"]: r for r in ctx["traced"].get("flight", [])
            if r.get("launch")}
    noted = sorted((s, int(name[len(LAUNCH):]))
                   for s, _, name in trace.get("phases") or ()
                   if name.startswith(LAUNCH))
    if not noted or not rows:
        return None
    rowed = [n in rows for _, n in noted]
    if False in rowed and any(rowed[rowed.index(False):]):
        return _void(trace, "a launch with no ring row in front of one "
                            "with a row", launch=noted[rowed.index(False)][1])
    launched = [(at, rows[n], row_kind(rows[n]["program"]))
                for at, n in noted if n in rows]
    launched = [x for x in launched if x[2]]
    runs = sorted((s, e, module_kind(name))
                  for s, e, name in trace["modules"] if module_kind(name))
    early = sum(s < noted[0][0] for s, _, _ in runs)
    pairs = list(zip(launched, runs[early:]))
    depth = int(ctx["cell"].config["engine"].get("pipeline_depth", 2))
    decodes = [at for at, _, kind in launched if kind == "decode"]
    after, before = [], []      # each pair's room to its two bounds
    for (at, row, kind), (s, e, ran) in pairs:
        # the first decode launch at or behind this one runs behind it, and
        # is read before the depth-th decode launch after THAT one begins
        bound = bisect.bisect_left(decodes, at) + depth
        after.append(s - at)
        if bound < len(decodes):
            before.append(decodes[bound] - e)
        why = (f"a {kind} launch against a {ran} execution" if kind != ran
               else "an execution that starts before its enqueue"
               if s < at else
               "an execution still running when the loop had read it"
               if bound < len(decodes) and e > decodes[bound] else None)
        if why:
            return _void(trace, why, launch=row["launch"])
    out = {"pairs": [(row, kind, s, e)
                     for (_, row, kind), (s, e, _) in pairs],
           "early": early, "late": len(runs) - early - len(pairs),
           "unrun": [row["launch"] for _, row, _ in launched[len(pairs):]],
           "unrowed": rowed.count(False)}
    trace.setdefault("notes", {})["launches"] = {
        "matched": {k: sum(kind == k for _, kind, _, _ in out["pairs"])
                    for k, _ in KINDS},
        "executions": len(runs),
        **{k: out[k] for k in ("early", "late", "unrun", "unrowed")},
        "slack_ms": [round(min(x) * 1e3, 3) if x else None
                     for x in (after, before)]}
    return out


def matched(ctx: dict, programs: tuple) -> list[tuple[dict, float]]:
    """(row, device seconds) of the slice's matched executions whose row's
    ``program`` is among ``programs``."""
    joined = join(ctx)
    return [] if joined is None else [
        (row, e - s) for row, _, s, e in joined["pairs"]
        if row["program"] in programs]


def window_chunks(ctx: dict) -> list[dict]:
    """The window's prefill rows that count what they held, compile-bearing
    ones left out (by the drain's time, as every window-wide reader)."""
    w = ctx["window"]
    return [r for r in layerlib.flight(ctx, w.t_open, w.t_close,
                                       ("prefill_chunk",))
            if r.get("chunk_bucket")]
