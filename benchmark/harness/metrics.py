"""Metric arithmetic on the client's timelines. No I/O, no clock: the
end-to-end numbers are pure functions of the records the load generator
wrote, so hand-made timelines test them (benchmark/tests/test_metrics.py).

All times are seconds on the client's monotonic clock.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


@dataclasses.dataclass
class Record:
    """One streamed request as its client saw it."""

    idx: int
    stream: str                     # warm | probe | ramp | window | prefix
    due: float                      # when the schedule (or the closed loop,
                                    # or a session's think time) said to send
    max_tokens: int
    trace_id: str = ""
    sent: Optional[float] = None    # just before the POST left
    status: Optional[int] = None
    times: list = dataclasses.field(default_factory=list)   # content chunks
    counts: list = dataclasses.field(default_factory=list)  # tokens in each
    done: Optional[float] = None    # the finish_reason chunk
    ended: Optional[float] = None   # the stream returned, well or not (None:
                                    # cut off while still running)
    finish_reason: Optional[str] = None
    prompt_tokens: Optional[int] = None     # usage, as the server counted
    completion_tokens: Optional[int] = None
    text: str = ""
    error: str = ""

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None

    @property
    def tokens(self) -> int:
        return int(sum(self.counts))

    def problem(self) -> str:
        """'' for a request that completed as asked, else why it failed:
        exactly max_tokens letters, finish_reason length, usage agreeing."""
        if self.error:
            return self.error
        if self.status != 200:
            return f"http {self.status}"
        if self.done is None:
            return "not finished"
        if self.finish_reason != "length":
            return f"finish_reason {self.finish_reason}"
        if (len(self.text) != self.max_tokens or not self.text.isalpha()
                or not self.text.islower() or not self.text.isascii()):
            return (f"reply is {len(self.text)} characters, not "
                    f"{self.max_tokens} letters")
        if self.completion_tokens != self.max_tokens:
            return f"usage says {self.completion_tokens} tokens"
        return ""

    def ttft(self) -> Optional[float]:
        return None if self.first is None else self.first - self.due

    def tpot(self) -> Optional[float]:
        """(last token - first token) / (output tokens - 1)."""
        n = self.tokens
        if n < 2:
            return None
        return (self.times[-1] - self.times[0]) / (n - 1)


def percentile(values, q: float) -> Optional[float]:
    """Linear interpolation between order statistics (numpy's default)."""
    if len(values) == 0:
        return None
    return float(np.percentile(np.asarray(values, float), q))


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    t_end: float        # when the run stopped waiting (drain limit)

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def holds(self, t: Optional[float]) -> bool:
        return t is not None and self.t_open <= t < self.t_close


def scored(records: list[Record], w: Window, loop: str) -> list[Record]:
    """Open loop: the requests DUE in the window, sent or not (a stall
    charges the requests queued behind it); the run waits for them. Closed
    loop: those that ENDED in it, well or not; what is still running when the
    window closes is cut off and not scored, so a closed-loop run needs no
    drain (its callers started as many requests as they finished)."""
    if loop == "open":
        return [r for r in records if r.stream == "window" and w.holds(r.due)]
    return [r for r in records if r.stream == "window" and w.holds(r.ended)]


def gaps_in(records: list[Record], w: Window) -> np.ndarray:
    """Gaps between consecutive content chunks of one stream that ended
    inside the window, pooled over every stream (the ramp's too: what a
    reader saw while the window was open)."""
    out = []
    for r in records:
        t = np.asarray(r.times, float)
        if len(t) < 2:
            continue
        g, end = np.diff(t), t[1:]
        out.append(g[(end >= w.t_open) & (end < w.t_close)])
    return np.concatenate(out) if out else np.zeros(0)


def tokens_in(records: list[Record], lo: float, hi: float) -> int:
    """Output tokens clients received in [lo, hi), whatever stream sent."""
    n = 0
    for r in records:
        for t, c in zip(r.times, r.counts):
            if lo <= t < hi:
                n += c
    return n


def end_to_end(records: list[Record], w: Window, loop: str,
               setup_s: float) -> dict:
    """Every end-to-end metric the benchmark knows, by name, in its unit; a
    cell reports the ones BENCHMARK.json lists for it. A failed request
    counts with the time the run waited for it: it cannot improve a tail."""
    sc = scored(records, w, loop)

    ttft, tpot = [], []
    for r in sc:
        if r.problem():
            ttft.append(w.t_end - r.due)
            tpot.append(w.t_end - r.due)
            continue
        ttft.append(r.ttft())
        if r.tpot() is not None:
            tpot.append(r.tpot())
    out = {"setup_s": setup_s,
           "out_tok_s": tokens_in(records, w.t_open, w.t_close) / w.seconds}
    for name, values in (("ttft", ttft), ("tpot", tpot)):
        if values:
            # the mean is carried by every scored request: the statistic for
            # a window that holds only some tens of them
            out[f"{name}_ms_mean"] = 1e3 * float(np.mean(values))
            for q in (50, 90, 99):
                out[f"{name}_ms_p{q}"] = 1e3 * percentile(values, q)
    gaps = gaps_in(records, w)
    for q in (50, 98, 99):
        if len(gaps):
            out[f"stall_ms_p{q}"] = 1e3 * percentile(gaps, q)
    return out


def counts(records: list[Record], w: Window, loop: str) -> tuple[int, int]:
    """(attempted, failed) over the scored requests."""
    sc = scored(records, w, loop)
    return len(sc), sum(1 for r in sc if r.problem())


def attained(records: list[Record], w: Window, loop: str,
             limits: dict) -> Optional[float]:
    """Share of the scored requests that met the cell's TTFT and TPOT limits;
    a failed request misses."""
    sc = scored(records, w, loop)
    if not sc:
        return None
    ok = 0
    for r in sc:
        tpot = r.tpot()
        ok += (not r.problem()
               and r.ttft() * 1e3 <= limits["ttft_ms"]
               and (tpot is None or tpot * 1e3 <= limits["tpot_ms"]))
    return ok / len(sc)


def waiting_at(records: list[Record], t: float) -> int:
    """Requests due by t whose first token had not arrived by t: the queue,
    as the clients see it."""
    return sum(1 for r in records
               if r.due <= t and (r.first is None or r.first > t))
