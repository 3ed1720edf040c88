"""Introspection endpoints: device health, program costs, thread stacks.

``GET /debug/devices`` — per-device liveness + memory: a timeout-guarded
jit probe (``?probe=0`` skips the device dispatch, ``?probe_timeout=S``
bounds it), ``memory_stats()`` where the backend has an allocator, a
live-array HBM census attributed to KV cache vs weights vs other, and the
stall watchdog's channel table. The "is my TPU actually alive and what is
eating its HBM" view.

``GET /debug/programs`` — the compiled-program cost catalog: per watched
jit entry, XLA ``cost_analysis``/``memory_analysis`` (FLOPs, bytes
accessed, temp/output sizes), compile seconds and dispatch counts: what a
program costs by the compiler's account. The first call lazily re-lowers
each program from its recorded abstract signature (``?harvest=0`` lists
without compiling).

``GET /debug/stacks`` — every live thread's stack, on demand (the same
payload the watchdog dumps on a stall, for when an operator wants it
BEFORE the deadline).

``GET /debug/flight`` — the engine flight recorder: per-model rings of
per-dispatch records (step times, occupancy, queue depth, KV utilization,
tokens, preemptions, speculative acceptance, the anatomy's phases and
parts, the engine thread's clocks for the row's span) with windowed
step-time percentiles. ``?since=<monotonic ts>`` returns only records newer than
the given timestamp (pollers pass the ``ts`` of the last record they
saw); ``?limit=N`` (at most 4096) bounds the records returned: the newest
N, or with ``since`` the first N after it (a page). The "what was the
engine doing for the last N seconds" view — reading it never touches a
device.

``GET /debug/anatomy`` — the dispatch-anatomy breakdown (obs.anatomy):
per-model windowed gap/sched/launch/sync phase percentiles and totals
from the flight ring's phase columns, the derived
``host_overhead_fraction``, per-phase wall shares (stacked-bar ready), and
the unattributed remainder; beside them the measured PARTS of gap
(``process``, ``book``, ``free``: quantiles, totals, ``part_share``) and the engine
thread's own account of the window (``thread``: the shares of its span on a
CPU, runnable with no core, blocked, waiting for the device, idle). How
idle the DEVICE was is measured, not estimated: ``POST /backend/trace``.
``?window=S`` sets the window (default 60 s; ``window=0`` reads the
whole ring). The "where did the dispatch time go" view — host-side
reads only, zero device syncs.

``GET /debug/fleet/flight`` — the fleet-wide flight view: every replica's
ring harvested over GetTelemetry (off the event loop, fleet RPC deadline)
and merged into one table with a ``replica`` column plus per-replica
step-time percentiles (obs.fleetview). A wedged replica degrades to an
``unreachable`` pane — the endpoint itself always answers.

``GET /debug/profiles`` — the anomaly-capture manifest (obs.profiler):
every auto-captured jax.profiler trace with its trigger (stall /
slo_shed / step_p99_regression), triggering trace id, reason, and
artifact path, plus the manager's rate-limit state (cooldown, per-hour
budget, skip counts).

``GET /debug/history`` / ``/debug/history/{series}`` — the persistent
multi-resolution metrics history (obs.history): 1 s / 10 s / 5 m rings of
every engine and usage series, queryable per resolution with ``?res=``
and ``?since=``. The "what did occupancy look like an hour ago" view —
survives restarts via the snapshot dir (``LOCALAI_HISTORY_DIR``).

``GET /debug/kv`` — per-model paged block-pool audit: allocator stats,
live tables, and the result of ``BlockAllocator.check_invariants()``
(block conservation + refcount sanity). Any violation is a leak.

``/debug/faults`` — the fault-injection registry (localai_tpu.faults):
``GET`` lists armed specs with hit/fire counts plus the self-healing
supervisor state per model; ``POST {"site", "mode", "after", "times",
"match", "delay_s"}`` arms one; ``DELETE`` (``?site=`` to scope) clears.
Chaos tooling only — nothing is armed (and the hot path pays one boolean
read) unless an operator or ``LOCALAI_FAULT_*`` arms it.
"""

from __future__ import annotations

import asyncio
import time

from aiohttp import web

from localai_tpu import faults
from localai_tpu.obs import compile as obs_compile
from localai_tpu.obs import device as obs_device
from localai_tpu.obs import watchdog as obs_watchdog


def _state(request: web.Request):
    from localai_tpu.api.server import STATE_KEY

    return request.app[STATE_KEY]


def _runners(state) -> list:
    out = []
    for sm in state.manager.loaded_snapshot().values():
        runner = getattr(sm, "runner", None)
        if runner is not None:
            out.append(runner)
    return out


async def devices(request: web.Request) -> web.Response:
    state = _state(request)
    want_probe = request.query.get("probe", "1") != "0"
    try:
        probe_timeout = float(request.query.get("probe_timeout", 5.0))
    except ValueError:
        raise web.HTTPBadRequest(text="probe_timeout must be a number")
    if not probe_timeout > 0:  # rejects 0, negatives, and NaN
        raise web.HTTPBadRequest(text="probe_timeout must be positive")
    # hard cap: the probe join blocks one shared api-wait executor thread;
    # an unbounded (or inf) timeout against a wedged device would let a
    # key holder pin the pool one request at a time
    probe_timeout = min(probe_timeout, 120.0)
    loop = asyncio.get_running_loop()

    def build() -> dict:
        runners = _runners(state)
        report: dict = {
            "devices": obs_device.device_memory(),
            "census": obs_device.hbm_census(
                obs_device.known_arrays(runners)),
            "watchdog": obs_watchdog.WATCHDOG.status(),
        }
        if want_probe:
            # the probe itself is timeout-guarded; a wedged device costs
            # this handler probe_timeout seconds, not forever
            report["probe"] = obs_device.probe_device(
                timeout=probe_timeout).to_dict()
        return report

    return web.json_response(
        await loop.run_in_executor(state.executor, build))


async def programs(request: web.Request) -> web.Response:
    state = _state(request)
    harvest = request.query.get("harvest", "1") != "0"
    loop = asyncio.get_running_loop()

    def build() -> dict:
        return {"programs": obs_compile.CATALOG.report(harvest=harvest)}

    return web.json_response(
        await loop.run_in_executor(state.executor, build))


async def stacks(request: web.Request) -> web.Response:
    return web.json_response({"threads": obs_watchdog.dump_stacks()})


async def flight(request: web.Request) -> web.Response:
    state = _state(request)
    try:
        since = float(request.query.get("since", 0.0))
    except ValueError:
        raise web.HTTPBadRequest(
            text="since must be a number (a record's monotonic ts)")
    try:
        limit = int(request.query.get("limit", 256))
    except ValueError:
        raise web.HTTPBadRequest(text="limit must be an integer")
    limit = max(1, min(limit, 4096))
    models = {}
    for name, sm in state.manager.loaded_snapshot().items():
        rec = getattr(getattr(sm, "scheduler", None), "flight", None)
        if rec is None:
            continue  # worker-backed / non-LLM serving models have no ring
        models[name] = {
            "records": rec.snapshot(since=since, limit=limit),
            "percentiles": rec.percentiles(),
            "dispatches": rec.count,
            "tokens_total": rec.total_tokens,
            "capacity": rec.capacity,
        }
    return web.json_response({
        # the clock records are stamped with, so pollers can window
        "now_monotonic": round(time.monotonic(), 6),
        "models": models,
    })


async def anatomy(request: web.Request) -> web.Response:
    from localai_tpu.obs import anatomy as obs_anatomy

    state = _state(request)
    try:
        window = float(request.query.get(
            "window", obs_anatomy.DEFAULT_WINDOW_S))
    except ValueError:
        raise web.HTTPBadRequest(text="window must be a number (seconds)")
    window_s = window if window > 0 else None  # 0 = whole ring
    models = {}
    for name, sm in state.manager.loaded_snapshot().items():
        rec = getattr(getattr(sm, "scheduler", None), "flight", None)
        if rec is None:
            continue  # worker-backed / non-LLM serving models have no ring
        models[name] = obs_anatomy.breakdown(rec, window_s=window_s)
    return web.json_response({
        "now_monotonic": round(time.monotonic(), 6),
        "phases": list(obs_anatomy.PHASES),
        "parts": list(obs_anatomy.PARTS),       # inside gap
        "models": models,
    })


async def fleet_flight(request: web.Request) -> web.Response:
    from localai_tpu.obs import fleetview

    state = _state(request)
    try:
        since = float(request.query.get("since", 0.0))
    except ValueError:
        raise web.HTTPBadRequest(
            text="since must be a number (a record's monotonic ts)")
    try:
        limit = int(request.query.get("limit", 256))
    except ValueError:
        raise web.HTTPBadRequest(text="limit must be an integer")
    limit = max(1, min(limit, 4096))
    loop = asyncio.get_running_loop()

    def build() -> dict:
        # one bounded GetTelemetry per replica, NEVER on the event loop:
        # a wedged replica costs its pane one fleet RPC deadline, not the
        # endpoint
        models = {}
        for name, sm in state.manager.loaded_snapshot().items():
            if getattr(sm, "pool", None) is None:
                continue
            models[name] = fleetview.fleet_flight(
                sm, since=since, limit=limit)
        return models

    return web.json_response({
        "now_monotonic": round(time.monotonic(), 6),
        "models": await loop.run_in_executor(state.executor, build),
    })


async def profiles(request: web.Request) -> web.Response:
    from localai_tpu.obs.profiler import PROFILER

    return web.json_response(PROFILER.report())


async def history_index(request: web.Request) -> web.Response:
    """GET /debug/history — the multi-resolution metrics history
    (obs.history): every recorded series name plus the ring geometry, so
    a dashboard can enumerate before querying."""
    from localai_tpu.obs import history as obs_history

    return web.json_response({
        "series": obs_history.HISTORY.series_names(),
        "resolutions_s": list(obs_history.RESOLUTIONS),
        "capacity": {str(r): c
                     for r, c in obs_history.CAPACITY.items()},
    })


async def history_series(request: web.Request) -> web.Response:
    """GET /debug/history/{series}?res=<1|10|300>&since=<unix ts> — one
    series' ring at one resolution. Counters return the bucket max
    (monotone totals), gauges the bucket mean. Pure in-memory ring reads
    — no device work, no locks held across the render."""
    from localai_tpu.obs import history as obs_history

    name = request.match_info["series"]
    try:
        res = int(request.query.get("res", 10))
    except ValueError:
        raise web.HTTPBadRequest(text="res must be an integer (seconds)")
    try:
        since = float(request.query.get("since", 0.0))
    except ValueError:
        raise web.HTTPBadRequest(text="since must be a unix timestamp")
    out = obs_history.HISTORY.query(name, res=res, since=since)
    if out is None:
        raise web.HTTPNotFound(text=f"unknown series {name!r}")
    return web.json_response(out)


async def kv(request: web.Request) -> web.Response:
    state = _state(request)
    loop = asyncio.get_running_loop()

    def build() -> dict:
        # allocator walks + invariant checks scale with table count:
        # executor-side, like every other debug-pane builder here
        models = {}
        for name, sm in state.manager.loaded_snapshot().items():
            sched = getattr(sm, "scheduler", None)
            alloc = getattr(getattr(sm, "runner", None), "allocator", None)
            if alloc is None:
                # fleet facades have no local allocator, but their KV
                # economy plane (prefix directory + sibling/migration
                # counters) is this endpoint's business too
                directory = getattr(sched, "directory", None)
                if directory is not None:
                    models[name] = {
                        "directory": directory.stats(),
                        "sibling_transfers": sched.sibling_transfers,
                        "sibling_fallbacks": sched.sibling_fallbacks,
                        "migrations": sched.migrations,
                        "migration_fallbacks": sched.migration_fallbacks,
                    }
                    # host-tier roll-up across replicas rides the same
                    # metrics pane the /metrics scrape reads
                    m = sched.metrics()
                    if "kv_tier_spills" in m:
                        models[name]["tier"] = {
                            "blocks": m.get("kv_tier_blocks", 0),
                            "bytes": m.get("kv_tier_bytes", 0),
                            "spills_total": m.get("kv_tier_spills", 0),
                            "reloads_total": m.get("kv_tier_reloads", 0),
                        }
                continue  # contiguous / worker-backed / non-LLM engines
            models[name] = {
                "block_tokens": alloc.block_tokens,
                "blocks": {},
                "tables": {str(s): n
                           for s, n in alloc.tables_snapshot().items()},
                "shared_tokens_total": alloc.shared_tokens_total,
                "evictions_total": alloc.evictions_total,
                "invariant_violations": alloc.check_invariants(),
                "violations_seen": getattr(
                    sched, "kv_invariant_violations", 0),
            }
            st = alloc.stats()
            models[name]["blocks"] = {
                "total": st.total, "free": st.free, "used": st.used,
                "cached": st.cached, "watermark": st.high_watermark,
            }
            ts = alloc.tier_stats()
            if ts is not None:
                models[name]["tier"] = ts
            # per-slot state that is not keys, held beside the pool
            state_bytes = getattr(sm.runner, "state_bytes", 0)
            if state_bytes:
                models[name]["state_bytes"] = state_bytes
        return models

    return web.json_response(
        {"models": await loop.run_in_executor(state.executor, build)})


async def faults_get(request: web.Request) -> web.Response:
    state = _state(request)
    supervisors = {}
    for name, sm in state.manager.loaded_snapshot().items():
        sup = getattr(getattr(sm, "scheduler", None), "supervisor", None)
        if sup is not None:
            supervisors[name] = sup.status()
    return web.json_response({
        "active": faults.active(),
        "sites": faults.SITES,
        "armed": faults.snapshot(),
        "supervisors": supervisors,
    })


async def faults_post(request: web.Request) -> web.Response:
    try:
        body = await request.json()
    except Exception:  # noqa: BLE001 — malformed body is a client error
        raise web.HTTPBadRequest(text="body must be a JSON object")
    if not isinstance(body, dict) or not body.get("site"):
        raise web.HTTPBadRequest(text='need {"site": ..., ...}')
    allowed = {"site", "mode", "after", "times", "match", "delay_s"}
    unknown = set(body) - allowed
    if unknown:
        raise web.HTTPBadRequest(text=f"unknown fields {sorted(unknown)}")
    try:
        spec = faults.arm(faults.FaultSpec(**body))
    except (TypeError, ValueError) as e:
        raise web.HTTPBadRequest(text=str(e))
    return web.json_response({"armed": spec.to_dict()})


async def faults_delete(request: web.Request) -> web.Response:
    site = request.query.get("site") or None
    return web.json_response({"cleared": faults.clear(site)})


def routes() -> list[web.RouteDef]:
    return [
        web.get("/debug/devices", devices),
        web.get("/debug/programs", programs),
        web.get("/debug/stacks", stacks),
        web.get("/debug/flight", flight),
        web.get("/debug/anatomy", anatomy),
        web.get("/debug/fleet/flight", fleet_flight),
        web.get("/debug/profiles", profiles),
        web.get("/debug/history", history_index),
        web.get("/debug/history/{series}", history_series),
        web.get("/debug/kv", kv),
        web.get("/debug/faults", faults_get),
        web.post("/debug/faults", faults_post),
        web.delete("/debug/faults", faults_delete),
    ]
