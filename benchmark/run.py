#!/usr/bin/env python3
"""Run one cell of the benchmark once, in a new process.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

Starts the server as a child (benchmark/serve.py), waits for the load, warms
up the cell's own shapes, checks served tokens against the plain reference,
ramps, measures from the client for ``--seconds``, stops the child, and prints
as its last stdout line one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` with ``--trace 1|2``).
``--trace 0`` gives the cell's end-to-end metrics, ``--trace 1`` its per-layer
ones from a slice traced INSIDE the window. ``--trace 2`` is a ``--trace 0``
run to the end of its last scored request, which then reads the server's
rings and traces a slice of the same mix under a stream of its own
(``traced_slice``): both kinds of metric on one line, and nothing of the
tracing in an end-to-end number. No chip, fewer chips than the cell asks for,
or a device kind that is not in the benchmark's peak table is an error and
prints no result line: never a CPU number.

This parent never initialises a JAX backend: the child holds the chip(s).
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path
from typing import Optional

import aiohttp

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from harness import metrics as mtr  # noqa: E402
from harness import spec, traffic as trf  # noqa: E402
from harness.client import Client, drain  # noqa: E402
from harness.peaks import peaks  # noqa: E402
from harness.server import (HarnessFailure, Server, check_devices,  # noqa: E402
                            compiles)

TRACE_AT_S, TRACE_FOR_S = 10.0, 3.0     # the traced slice of the window
# --trace 2's slice comes after the window, under a stream of its own: its
# seed is no run's (--seed stays under 2**32), its open-loop schedule runs
# this long past its ramp (on one v5e chip the capture answers 11-19 s after
# its 3 s, PERF.md section 6; what is due after the answer is never sent)
TRACE_SEED, TRACE_SPAN_S = 1 << 32, 30.0
# the most rows /debug/flight answers with, and a ``since`` before every
# record: paging starts at the ring's oldest (0 would ask for the newest)
FLIGHT_PAGE, RING_START = 4096, 1e-9


def trace_for_s(chips: int) -> float:
    """Seconds of the traced slice. The profiler writes one plane a chip, so
    what a capture costs (the wait for ``POST /backend/trace``'s answer, the
    file, its reduction) goes with seconds x chips. 3.0 s on one chip, as
    ever; a cell on more chips traces at most twice one chip's chip-seconds:
    1.5 s on four, the longest slice whose capture still answers in half of
    ``capture_trace``'s 240 s by the costliest reading there is (3 s took
    122 s at half today's rate of events; PERF.md section 6 has the table)."""
    return min(TRACE_FOR_S, 2 * TRACE_FOR_S / chips)


PROBE_TOKENS = (16, 200, 600, 1100)     # prompt lengths of the reference
PROBE_EACH, PROBE_NEW = 4, 4            # probes: 4 each, 4 new tokens
ANCHOR = (time.time(), time.monotonic())    # Unix <-> this process's clock


def say(msg: str) -> None:
    print(f"[benchmark {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


# ---------------------------------------------------------------------------
# set-up steps


async def warm_up(client: Client, server: Server, cell: spec.Cell,
                  seed: int) -> dict:
    """Replay seeded samples of the cell's own mix, closed loop, until a
    whole round starts no new program: first one request alone (the fastest
    decode steps, so the largest steps-per-dispatch the scheduler picks),
    then rounds at the cell's concurrency (the slowest). Only this cell's
    shapes; outputs are cut short, since a decode program's shape does not
    depend on how long it runs."""
    mix = cell.traffic
    rounds, quiet = 0, 0
    before = compiles(server.metrics())
    start = before
    await client.closed_rounds(
        trf.warm_sample(mix, 2, seed, 0, cap_output=48), 1)
    while quiet < 1 and rounds < 6:
        rounds += 1
        await client.closed_rounds(
            trf.warm_sample(mix, 12, seed, rounds, cap_output=48),
            cell.max_slots)
        now = compiles(server.metrics())
        quiet = quiet + 1 if now == before and rounds > 1 else 0
        before = now
    bad = [r for r in client.records if r.stream == "warm" and r.problem()]
    if bad:
        raise HarnessFailure(f"warm-up request failed: {bad[0].problem()}")
    return {"rounds": rounds, "programs": int(before - start)}


async def fill_prefixes(client: Client, cell: spec.Cell) -> None:
    """The mix's shared prefixes, each sent once, so the window finds them in
    the prefix cache (a cache filled in set-up is part of the deployment)."""
    mix = cell.traffic
    if not (mix["prefix"]["fill_in_setup"] and mix["prefix"]["pool"]):
        return
    reqs = [trf.Request(i, "prefix", None, [trf.Turn(len(p), 1, 0.0, 1, p)])
            for i, p in enumerate(trf.prefixes(mix))]
    await client.closed_rounds(reqs, cell.max_slots)


async def reference_check(client: Client, server: Server, cell: spec.Cell,
                          seed: int) -> dict:
    """16 seeded greedy probes over HTTP, then the plain reference on the
    served weights in the child (harness/refcheck.py). Prompt lengths span a
    one-bucket prefill, a chunked (> 512 token) prefill and multi-block
    contexts; tokens 2-4 decode through the cache. Beside it, the model
    served is the model the file describes: the served parameter count
    equals the family's ``param_count`` of the published keys exactly, or
    the run is not correct (a published key the program reads under another
    name, or not at all, shows here)."""
    rng = trf.stream_rng(seed, "probe")
    reqs = []
    room = int(cell.config["context_size"]) - PROBE_NEW - 1
    for n in (min(n, room) for n in PROBE_TOKENS):
        for _ in range(PROBE_EACH):
            # n tokens with the BOS the byte tokenizer adds
            reqs.append(trf.Request(len(reqs), "probe", None, [trf.Turn(
                n - 1, PROBE_NEW, 0.0, 0, trf.random_text(rng, n - 1))]))
    await client.closed_rounds(reqs, 4, sampling={"temperature": 0.0})
    served = sorted((r for r in client.records if r.stream == "probe"),
                    key=lambda r: r.idx)
    probes = []
    for req, rec in zip(reqs, served):
        if rec.problem():
            raise HarnessFailure(f"probe failed: {rec.problem()}")
        prompt = [256] + list(req.turns[0].text.encode())   # BOS + bytes
        if rec.prompt_tokens != len(prompt):
            raise HarnessFailure(
                f"the server counted {rec.prompt_tokens} prompt tokens for a "
                f"probe of {len(prompt)}: not the byte tokenizer?")
        probes.append({"prompt": prompt, "served": list(rec.text.encode())})
    reply = await asyncio.get_running_loop().run_in_executor(
        None, server.reference, probes)
    short = [p["shortfall"] for rows in reply["shortfalls"] for p in rows]
    margin = mtr.percentile(
        [p["margin"] for rows in reply["shortfalls"] for p in rows], 10)
    check = {**judge(short, cell.config["reference"]), "margin_p10": margin,
             "params_served": reply["params"]["served"],
             "params_described": reply["params"]["described"]}
    if check["params_served"] != check["params_described"]:
        say(f"NOT the model described: the server holds "
            f"{check['params_served']} parameters, the configuration's "
            f"family counts {check['params_described']} from its file")
        check["ok"] = False
    return check


def judge(short: list, reference: dict) -> dict:
    """The check's verdict on a run's shortfalls. The configuration's
    ``reference.statistic`` names the number held to ``reference.epsilon``:
    ``max``, the largest shortfall (the default, what every configuration
    had before PR 27), or ``mean`` over the check's positions. Where one
    near-tie sets the largest (the 24B's sound runs read 0.01 to 0.14) the
    mean, which goes with how many ties flip and how far, is what a lower
    precision moves (PERF.md section 2). Both are printed in every run."""
    statistic = reference.get("statistic", "max")
    epsilon = float(reference["epsilon"])
    mean = sum(short) / len(short)
    return {"ok": {"max": max(short), "mean": mean}[statistic] <= epsilon,
            "statistic": statistic, "epsilon": epsilon,
            "mean_shortfall": mean, "max_shortfall": max(short),
            "positions": len(short), "nonzero": sum(s > 0 for s in short),
            "shortfalls": sorted(short)[-8:]}


# ---------------------------------------------------------------------------
# the window


async def read_flight(server: Server, client: Client, since: float,
                      out: list) -> tuple[float, bool]:
    """One page of the flight ring into ``out``: the OLDEST ``FLIGHT_PAGE``
    records after ``since`` (a record's ``ts``), or with ``since`` 0 the
    newest. Returns the last ``ts`` read and whether the page came full."""
    async with client.session.get(
            f"{server.base}/debug/flight?since={since!r}"
            f"&limit={FLIGHT_PAGE}") as resp:
        ring = (await resp.json())["models"].get(server.name, {})
    recs = ring.get("records", [])
    full = len(recs) == FLIGHT_PAGE
    if full and recs[0]["ts"] < recs[-1]["ts"]:
        # the next page starts strictly after this one's last ``ts``: where
        # the page ends inside a run of rows that share it, the rest of the
        # run would be lost. Hold the run back; it comes whole next time
        recs = [r for r in recs if r["ts"] < recs[-1]["ts"]]
    out += recs
    return (recs[-1]["ts"] if recs else since), full


async def page_flight(server: Server, client: Client, since: float,
                      out: list) -> float:
    """Every record the ring holds after ``since`` into ``out``, page by page
    forward until a page comes back short: nothing between two reads is
    missed, however many dispatches fell there. Returns the last ``ts``."""
    full = True
    while full:
        since, full = await read_flight(server, client, since, out)
    return since


async def poll_flight(server: Server, client: Client, until: float,
                      out: list) -> None:
    """Page the flight ring out while the window runs."""
    since = 0.0
    while True:
        since, _ = await read_flight(server, client, since, out)
        if time.monotonic() > until:
            return
        await asyncio.sleep(2.0)


async def read_traces(server: Server, client: Client) -> list:
    async with client.session.get(
            server.base + "/v1/traces?limit=500&kind=request") as r:
        return (await r.json())["traces"]


async def capture_trace(server: Server, client: Client, at: float,
                        seconds: float, out: dict) -> None:
    """POST /backend/trace at ``at``: the profiler runs in the process that
    holds the chip, while it serves. The call returns when the trace is
    written (11-19 s after its ``seconds`` on one v5e chip, PR 25)."""
    await asyncio.sleep(max(0.0, at - time.monotonic()))
    out["asked_unix"] = time.time()
    try:
        async with client.session.post(
                server.base + "/backend/trace", json={"seconds": seconds},
                timeout=aiohttp.ClientTimeout(total=240)) as resp:
            if resp.status != 200:
                raise HarnessFailure(f"/backend/trace answered {resp.status}: "
                                     f"{await resp.text()}")
            out.update(await resp.json())
    except (aiohttp.ClientError, asyncio.TimeoutError) as e:
        raise HarnessFailure(
            f"/backend/trace did not answer: {type(e).__name__}: {e}") from e
    out["answered_unix"] = time.time()


async def run_window(client: Client, server: Server, cell: spec.Cell,
                     seed: int, seconds: float, trace: bool,
                     traced: dict) -> mtr.Window:
    """The ramp (the cell's own traffic, loading the system, not scored),
    then the window, then the drain. Returns the window's edges; what was
    read from the server on the way (compile counters at the edges and, with
    ``trace``, the profiler's answer and the flight ring) goes into
    ``traced``."""
    mix, drive = cell.traffic, cell.drive
    ramp_s = float(drive.get("ramp_s", 5.0))
    tasks: set = set()
    side: list = []
    gc.collect()
    gc.freeze()
    t0 = time.monotonic() + 0.05
    # an open loop waits for what it sent; a closed loop cuts off at the close
    drain_s = float(drive.get("drain_s", 30.0)) if mix["loop"] == "open" else 0
    w = mtr.Window(t0 + ramp_s, t0 + ramp_s + seconds,
                   t0 + ramp_s + seconds + drain_s)
    if trace:
        at = w.t_open + min(TRACE_AT_S, seconds / 3)
        side.append(asyncio.ensure_future(capture_trace(
            server, client, at, min(trace_for_s(cell.chips), seconds / 4),
            traced)))
        traced["flight"] = []
        side.append(asyncio.ensure_future(poll_flight(
            server, client, w.t_close + 1.0, traced["flight"])))

    async def snapshot_at_open() -> None:
        await asyncio.sleep(max(0.0, w.t_open - time.monotonic()))
        traced["compiles_open"] = compiles(
            await asyncio.get_running_loop().run_in_executor(
                None, server.metrics))

    side.append(asyncio.ensure_future(snapshot_at_open()))
    if mix["loop"] == "open":
        schedule = trf.open_schedule(mix, float(drive["rate_rps"]), ramp_s,
                                     seconds, seed)
        await client.open_loop(schedule, t0, tasks)
        await asyncio.sleep(max(0.0, w.t_close - time.monotonic()))
    else:
        clients = int(drive["clients"])
        await client.closed_loop(trf.closed_stream(mix, clients, seed),
                                 clients, t0, ramp_s, w.t_close, tasks)
    traced["compiles_close"] = compiles(
        await asyncio.get_running_loop().run_in_executor(
            None, server.metrics))
    await drain(tasks, w.t_end)
    for t in side:
        await t
    gc.unfreeze()
    return w


async def traced_slice(client: Client, server: Server, cell: spec.Cell,
                       seed: int, w: mtr.Window, traced: dict) -> None:
    """``--trace 2`` after the window: first what the window left in the
    server's rings (the flight ring, paged forward from its oldest record;
    the request spans); then the
    cell's mix again under the stream ``trace`` (seeded, never scored: its
    requests are due after the close), ramped as the window's was, and
    ``POST /backend/trace`` for ``trace_for_s`` with that traffic running until
    it answers; then a bounded drain (a reply's ``usage`` comes at its end)
    and the rings again, for the slice. A profiler's first start costs more
    than its second, so one capture is made and thrown away while the slice
    ramps."""
    mix, drive = cell.traffic, cell.drive
    ramp_s = float(drive.get("ramp_s", 5.0))
    flight: list = []
    since = await page_flight(server, client, RING_START, flight)
    # the ring holds set-up's rows in front of the window's: it has wrapped
    # past the opening unless its oldest record is older than that
    traced["flight_cut"] = not flight or (
        flight[0]["ts_unix"] - ANCHOR[0] + ANCHOR[1] > w.t_open)
    traces = await read_traces(server, client)
    loop = asyncio.get_running_loop()
    before = compiles(await loop.run_in_executor(None, server.metrics))
    answered = asyncio.Event()
    tasks: set = set()
    t0 = time.monotonic() + 0.05
    if mix["loop"] == "open":
        schedule = [dataclasses.replace(r, stream="trace")
                    for r in trf.open_schedule(
                        mix, float(drive["rate_rps"]), ramp_s, TRACE_SPAN_S,
                        seed + TRACE_SEED)]
        sender = asyncio.ensure_future(client.open_loop(schedule, t0, tasks))
    else:
        clients = int(drive["clients"])
        supply = trf.closed_stream(mix, clients, seed + TRACE_SEED)

        async def caller(i: int) -> None:   # client.closed_loop's, but it
            # stops when the capture has answered, not at a set time
            await asyncio.sleep(max(
                0.0, t0 + ramp_s * i / clients - time.monotonic()))
            while not answered.is_set():
                await client.converse(dataclasses.replace(
                    next(supply), stream="trace"), time.monotonic())

        tasks |= {asyncio.ensure_future(caller(i)) for i in range(clients)}
        sender = None
    try:
        first: dict = {}
        await capture_trace(server, client, t0, 0.1, first)
        shutil.rmtree(server.run_dir / first["trace_dir"],
                      ignore_errors=True)
        await capture_trace(server, client, t0 + ramp_s,
                            trace_for_s(cell.chips), traced)
    finally:
        answered.set()          # a closed loop's callers start no more,
        if sender is not None:  # nor does an open loop's schedule
            sender.cancel()
            await asyncio.gather(sender, return_exceptions=True)
        await drain(tasks, time.monotonic()
                    + float(drive.get("drain_s", 30.0)))
    traced["compiles_in_slice"] = compiles(
        await loop.run_in_executor(None, server.metrics)) - before
    await page_flight(server, client, since, flight)
    traced["flight"] = flight
    traced["traces"] = traces + await read_traces(server, client)


# ---------------------------------------------------------------------------
# one run


def wait_ready(server: Server, cell: spec.Cell,
               platform: str) -> tuple[float, dict, dict]:
    """Until the model is loaded on the devices the cell asks for: (seconds
    since the child started, the device as JAX reports it, its peaks)."""
    load_s = server.wait_loaded(1000.0)
    device = check_devices(server.get("/system"), cell.chips, platform)
    peak = peaks(device["kind"])    # raises for a kind with no entry
    say(f"loaded in {load_s:.1f}s on {device}")
    return load_s, device, peak


async def one_run(server: Server, cell: spec.Cell, args, t_start: float,
                  platform: str) -> dict:
    load_s, device, peak = wait_ready(server, cell, platform)
    parts = {"load_s": load_s}
    traced: dict = {}
    async with Client(server.base, server.name, cell.traffic["sampling"],
                      run_tag=f"s{args.seed}") as client:
        t = time.monotonic()
        parts["warmup"] = await warm_up(client, server, cell, args.seed)
        parts["warmup_s"] = time.monotonic() - t
        say(f"warm-up {parts['warmup_s']:.1f}s {parts['warmup']}")
        t = time.monotonic()
        await fill_prefixes(client, cell)
        check = await reference_check(client, server, cell, args.seed)
        parts["reference_s"] = time.monotonic() - t
        say(f"reference check {parts['reference_s']:.1f}s {check}")
        w = await run_window(client, server, cell, args.seed, args.seconds,
                             args.trace == 1, traced)
        records = client.records
        if args.trace == 1:
            traced["traces"] = await read_traces(server, client)
        # --trace 2: a closed loop was cut off at the close, an open loop's
        # drain has returned: every scored record has ended, and what is
        # scored is final before the server is asked anything more
        final = list(records)
        if args.trace == 2:
            await traced_slice(client, server, cell, args.seed, w, traced)
    devices = server.get("/debug/devices?probe=0")
    parts["ramp_s"] = float(cell.drive.get("ramp_s", 5.0))
    setup_s = w.t_open - t_start
    loop = cell.traffic["loop"]
    attempted, failed = mtr.counts(final, w, loop)
    e2e = mtr.end_to_end(final, w, loop, setup_s)
    mem = [d["memory"]["peak_bytes_in_use"] for d in devices["devices"]
           if d.get("memory")]
    device["memory_peak_bytes"] = max(mem) if mem else 0
    scored = mtr.scored(final, w, loop)
    problems = sorted({r.problem() for r in scored} - {""})
    late = [r.sent - r.due for r in scored if r.sent is not None]
    return {
        "cell": cell, "records": records, "window": w, "loop": loop,
        "anchor": ANCHOR,
        "setup": parts, "setup_s": setup_s, "check": check,
        "attempted": attempted, "failed": failed, "problems": problems,
        "e2e": e2e, "device": device, "devices": devices, "peak": peak,
        "traced": traced, "send_late_ms_p99": (
            1e3 * mtr.percentile(late, 99) if late else None),
        "compiles_in_window": traced["compiles_close"]
        - traced["compiles_open"],
    }


def slice_tok_s(ctx: dict) -> Optional[float]:
    """Tokens per second the clients received inside the traced slice: set
    against ``out_tok_s`` it says what the tracing costs while it is on."""
    from harness import layerlib

    win = layerlib.trace_window(ctx)
    return None if win is None else mtr.tokens_in(
        ctx["records"], *win) / (win[1] - win[0])


def save_raw(run_dir: Path, ctx: dict, args) -> None:
    """The client's timelines, for reading a run again without the chip."""
    w = ctx["window"]
    raw = {"workload": ctx["cell"].name, "seed": args.seed,
           "seconds": args.seconds, "trace": args.trace,
           "window": [w.t_open, w.t_close, w.t_end], "loop": ctx["loop"],
           "setup": ctx["setup"], "setup_s": ctx["setup_s"],
           "check": ctx["check"], "e2e": ctx["e2e"],
           "problems": ctx["problems"],
           "compiles_in_window": ctx["compiles_in_window"],
           "records": [{
               "idx": r.idx, "stream": r.stream, "due": r.due, "sent": r.sent,
               "status": r.status, "times": r.times, "counts": r.counts,
               "done": r.done, "ended": r.ended, "max_tokens": r.max_tokens,
               "prompt_tokens": r.prompt_tokens, "problem": r.problem(),
           } for r in ctx["records"]
               if r.stream in ("ramp", "window", "trace")]}
    (run_dir / f"raw-seed{args.seed}-trace{args.trace}.json").write_text(
        json.dumps(raw))


def run(args, *, platform: str = "tpu", root: Path = spec.ROOT) -> dict:
    """Everything but the printing; returns the result line's object."""
    t_start = time.monotonic()
    cell = spec.load_cell(args.workload, root)
    if args.seconds is None:
        args.seconds = float(cell.run_seconds)
    run_dir = spec.bench_dir(root) / ".run" / cell.name
    shutil.rmtree(run_dir, ignore_errors=True)
    server = Server(cell, root, run_dir, platform=platform)
    try:
        ctx = asyncio.run(one_run(server, cell, args, t_start, platform))
    except BaseException:
        server.kill()
        raise
    server.stop()
    save_raw(run_dir, ctx, args)
    say(f"set-up {ctx['setup_s']:.1f}s parts {ctx['setup']}")
    summary = {k: ctx[k] for k in ("setup", "check", "problems",
                                   "send_late_ms_p99", "compiles_in_window")}
    summary["all_end_to_end"] = ctx["e2e"]
    print("summary " + json.dumps(summary), flush=True)
    result = {
        "correct": bool(ctx["check"]["ok"] and ctx["failed"] == 0),
        "attempted": ctx["attempted"], "failed": ctx["failed"],
    }
    result["metrics"] = {} if args.trace == 1 else {
        m["name"]: {"value": ctx["e2e"][m["name"]], "unit": m["unit"]}
        for m in cell.end_to_end}
    if args.trace:
        from harness import trace_reduce

        trace = ctx["trace"] = trace_reduce.reduce_run(run_dir, ctx["traced"])
        notes = trace["notes"]
        notes["slice_tok_s"] = slice_tok_s(ctx)
        if args.trace == 2:
            shutil.rmtree(run_dir / ctx["traced"]["trace_dir"],
                          ignore_errors=True)     # reduced: delete it
            notes.update({k: ctx["traced"][k] for k in (
                "compiles_in_slice", "flight_cut")})
            if notes["compiles_in_slice"]:
                # a program compiled inside the slice voids the slice, not
                # the run: the device-trace readers find no trace
                ctx["trace"] = None
        for m in cell.per_layer:
            value = spec.load_reader(m["name"], root)(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": float(value),
                                                "unit": m["unit"]}
        ctx["device"].update(busy_s=trace["busy_s"],
                             window_s=trace["window_s"])
        result["breakdown"] = trace["breakdown"]
        print("trace " + json.dumps(notes), flush=True)
    result["device"] = ctx["device"]
    return result


def main(argv=None, *, platform: str = "tpu",
         root: Path = spec.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    args = ap.parse_args(argv)
    asked = os.environ.get("JAX_PLATFORMS", "")
    if asked and platform not in asked.split(","):
        print(f"JAX_PLATFORMS={asked!r}: the benchmark runs on a {platform} "
              f"or not at all", file=sys.stderr)
        return 2
    try:
        result = run(args, platform=platform, root=root)
    except (HarnessFailure, spec.SpecError, LookupError) as e:
        print(f"benchmark failed: {e}", file=sys.stderr)
        return 1
    # the result line: these keys and no others (the driver's contract)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
