"""What several per-layer readers share: the traced slice on the client's
clock, the flight ring's records and the client's tokens inside an interval,
and the request spans by trace id. A reader (benchmark/layers/<metric>.py)
takes the run's context and returns its number, or None when there is nothing
to read.

The context (run.py builds it): ``cell``, ``records`` (client timelines),
``window``, ``loop``, ``setup`` (set-up by parts), ``traced`` (flight records,
request traces, compile counters), ``trace`` (harness/trace_reduce.py's
reduction: with ``--trace 2`` of a slice traced after the window, so ask
``trace_window`` and not ``window`` where the slice is), ``devices``
(/debug/devices), ``device``, ``peak``, ``anchor`` ((unix, monotonic) read
together in the parent).
"""

from __future__ import annotations

from typing import Iterable, Optional

from harness import metrics as mtr
from harness import work


def to_mono(ctx: dict, unix: float) -> float:
    wall, mono = ctx["anchor"]
    return unix - wall + mono


def trace_window(ctx: dict) -> Optional[tuple[float, float]]:
    """The profiler's slice on the client's monotonic clock."""
    tr = ctx.get("trace")
    if not tr or tr.get("start_unix") is None:
        return None
    lo, hi = tr["window_at_s"]
    return (to_mono(ctx, tr["start_unix"] + lo),
            to_mono(ctx, tr["start_unix"] + hi))


def flight(ctx: dict, lo: float, hi: float,
           programs: Iterable[str] = ()) -> list[dict]:
    """Flight-ring records drained in [lo, hi) (client clock), compile-bearing
    first dispatches left out."""
    programs = set(programs)
    out = []
    for r in ctx["traced"].get("flight", []):
        t = to_mono(ctx, r["ts_unix"])
        if lo <= t < hi and not r["compile"] and (
                not programs or r["program"] in programs):
            out.append(r)
    return out


def spans(ctx: dict) -> dict[str, dict]:
    """trace id -> {span name: (start on the client clock, seconds)} for the
    engine's request traces."""
    out = {}
    for t in ctx["traced"].get("traces", []):
        if t.get("kind") != "request":
            continue
        out[t["trace_id"]] = {
            s["name"]: (to_mono(ctx, s["start_unix"]),
                        (s["duration_ms"] or 0.0) * 1e-3)
            for s in t.get("children", [])}
    return out


def scored(ctx: dict) -> list[mtr.Record]:
    return mtr.scored(ctx["records"], ctx["window"], ctx["loop"])


def attended_in(ctx: dict, lo: float, hi: float) -> tuple[int, int]:
    """(output tokens, attended tokens) of the tokens clients received in
    [lo, hi): output token k of a request with prompt p attends p + k."""
    tokens = attended = 0
    for r in ctx["records"]:
        if r.prompt_tokens is None:
            continue
        k = 0
        for t, c in zip(r.times, r.counts):
            if lo <= t < hi:
                tokens += c
                # tokens k+1 .. k+c of this reply
                attended += c * r.prompt_tokens + c * k + c * (c + 1) // 2
            k += c
    return tokens, attended


def prefilled_in(ctx: dict, lo: float, hi: float) -> tuple[float, float]:
    """(prompt tokens, attended pairs) prefilled in [lo, hi): each request's
    ``prefill`` span, by the share of it inside the interval."""
    by_id = spans(ctx)
    tokens = pairs = 0.0
    for r in ctx["records"]:
        sp = by_id.get(r.trace_id, {}).get("prefill")
        if not sp or not r.prompt_tokens or sp[1] <= 0:
            continue
        share = max(0.0, min(hi, sp[0] + sp[1]) - max(lo, sp[0])) / sp[1]
        tokens += share * r.prompt_tokens
        pairs += share * work.causal_pairs(r.prompt_tokens)
    return tokens, pairs


def share_of_roofline(work_needed: dict, seconds: float, ctx: dict) -> float:
    """Least time over measured time, in %; work is the whole system's, time
    is one chip's (every chip runs the same program on its share)."""
    least, _ = work.roofline_seconds(work_needed, ctx["peak"],
                                     ctx["cell"].chips)
    return 100.0 * least / seconds
