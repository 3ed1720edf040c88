"""The load generator: one asyncio loop, one aiohttp session, one SSE read per
stream. Every end-to-end time is taken here, on this process's monotonic
clock, with the profiler off.

An open loop sleeps until each request's DUE time and times the request from
it, not from when the send happened: a late generator or a stalled server
charges the requests behind the stall. How late each send left is recorded
(``sent - due``), so a starved generator is not read as a fast server.
"""

from __future__ import annotations

import asyncio
import json
import time
from typing import Iterator, Optional

import aiohttp

from harness import traffic as trf
from harness.metrics import Record

CHAT = "/v1/chat/completions"


class Client:
    def __init__(self, base: str, model: str, sampling: dict,
                 run_tag: str = "bench"):
        self.base, self.model, self.sampling = base, model, sampling
        self.run_tag = run_tag
        self.records: list[Record] = []
        self.session: Optional[aiohttp.ClientSession] = None

    async def __aenter__(self) -> "Client":
        self.session = aiohttp.ClientSession(
            connector=aiohttp.TCPConnector(limit=0),
            timeout=aiohttp.ClientTimeout(total=None, sock_read=120))
        return self

    async def __aexit__(self, *exc) -> None:
        await self.session.close()

    async def stream(self, rec: Record, body: dict) -> Record:
        """POST one streaming chat request and record what arrives when."""
        self.records.append(rec)
        rec.sent = time.monotonic()
        try:
            async with self.session.post(
                    self.base + CHAT, json=body,
                    headers={"X-Trace-ID": rec.trace_id}) as resp:
                rec.status = resp.status
                if resp.status != 200:
                    rec.error = (f"http {resp.status}: "
                                 f"{(await resp.text())[:200]}")
                    rec.ended = time.monotonic()
                    return rec
                parts = []
                async for raw in resp.content:
                    if not raw.startswith(b"data: "):
                        continue
                    now = time.monotonic()
                    data = raw[6:].strip()
                    if data == b"[DONE]":
                        break
                    chunk = json.loads(data)
                    if chunk.get("usage"):
                        rec.prompt_tokens = chunk["usage"]["prompt_tokens"]
                        rec.completion_tokens = chunk["usage"][
                            "completion_tokens"]
                    for choice in chunk.get("choices") or ():
                        text = choice.get("delta", {}).get("content")
                        if text:
                            rec.times.append(now)
                            rec.counts.append(len(text))
                            parts.append(text)
                        if choice.get("finish_reason"):
                            rec.finish_reason = choice["finish_reason"]
                            rec.done = now
                rec.text = "".join(parts)
        except asyncio.CancelledError:
            rec.error = rec.error or "not finished within the drain limit"
            raise
        except (aiohttp.ClientError, asyncio.TimeoutError, OSError,
                ValueError) as e:
            rec.error = f"{type(e).__name__}: {e}"
        rec.ended = time.monotonic()
        return rec

    async def converse(self, req: trf.Request, due: float,
                       sampling: Optional[dict] = None) -> None:
        """One arrival: its turns in order, each due ``think_s`` after the
        previous reply ended; a turn's prompt is the conversation so far."""
        messages: list[dict] = []
        for k, turn in enumerate(req.turns):
            if k:
                due = time.monotonic() + turn.think_s
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            messages = messages + [{"role": "user", "content": turn.text}]
            rec = Record(
                idx=req.idx, stream=req.stream, due=due,
                max_tokens=turn.max_tokens,
                trace_id=f"{self.run_tag}-{req.stream}-{req.idx}-{k}")
            await self.stream(rec, trf.request_body(
                self.model, messages, turn,
                self.sampling if sampling is None else sampling))
            if rec.problem():
                return      # a broken conversation has no next turn
            messages = messages + [{"role": "assistant",
                                    "content": rec.text}]

    async def closed_rounds(self, requests: list[trf.Request],
                            concurrency: int,
                            sampling: Optional[dict] = None) -> None:
        """Send ``requests`` with at most ``concurrency`` in flight, until
        all have completed (warm-up, probes, prefix fill)."""
        it = iter(requests)

        async def worker() -> None:
            for req in it:
                await self.converse(req, time.monotonic(), sampling)

        await asyncio.gather(*(worker() for _ in range(
            max(1, min(concurrency, len(requests))))))

    async def open_loop(self, schedule: list[trf.Request], t0: float,
                        tasks: set) -> None:
        """Start each arrival at t0 + its due time; return when the last one
        has been started (not finished)."""
        for req in schedule:
            due = t0 + req.due
            delay = due - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            task = asyncio.ensure_future(self.converse(req, due))
            tasks.add(task)

    async def closed_loop(self, supply: Iterator[trf.Request], clients: int,
                          t0: float, stagger_s: float, t_stop: float,
                          tasks: set) -> None:
        """``clients`` callers, started ``stagger_s``/clients apart from t0;
        each takes the next request when its last one completed, and starts
        none after ``t_stop``."""

        async def caller(i: int) -> None:
            delay = t0 + stagger_s * i / clients - time.monotonic()
            if delay > 0:
                await asyncio.sleep(delay)
            while time.monotonic() < t_stop:
                await self.converse(next(supply), time.monotonic())

        for i in range(clients):
            tasks.add(asyncio.ensure_future(caller(i)))
        await asyncio.sleep(max(0.0, t_stop - time.monotonic()))


async def drain(tasks: set, deadline: float) -> None:
    """Wait for the started requests until ``deadline`` (monotonic), then
    cancel what is left; their records say so. Every task's result is read."""
    pending = {t for t in tasks if not t.done()}
    if pending:
        _, pending = await asyncio.wait(
            pending, timeout=max(0.0, deadline - time.monotonic()))
    for t in pending:
        t.cancel()
    for t in tasks:
        try:
            await t
        except asyncio.CancelledError:
            pass
