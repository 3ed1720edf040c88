"""LocalAI-specific endpoints: tokenize, metrics, system info, backend
monitor/shutdown, readiness.

Parity: /root/reference/core/http/routes/localai.go:20-67 and
core/http/endpoints/localai/ (tokenize, system, backend_monitor,
welcome/health).
"""

from __future__ import annotations

import asyncio
import logging
import time

from aiohttp import web

from localai_tpu.api.metrics import REGISTRY
from localai_tpu.version import __version__

log = logging.getLogger(__name__)


def _state(request: web.Request):
    from localai_tpu.api.server import STATE_KEY

    return request.app[STATE_KEY]


async def healthz(_request: web.Request) -> web.Response:
    return web.json_response({"status": "ok"})


async def readyz(request: web.Request) -> web.Response:
    """Ready = config loader up; per-model engines load lazily."""
    state = _state(request)
    return web.json_response({
        "status": "ok",
        "models_configured": len(state.loader.names()),
        "models_loaded": state.manager.loaded_names(),
    })


async def version(_request: web.Request) -> web.Response:
    return web.json_response({"version": __version__})


async def tokenize(request: web.Request) -> web.Response:
    """POST {model, content} → {tokens} (parity: TokenizeEndpoint,
    core/http/endpoints/localai/tokenize.go + TokenizeString RPC)."""
    from localai_tpu.api.openai import _serving
    from localai_tpu.api.schema import OpenAIRequest

    try:
        body = await request.json()
    except Exception:
        raise web.HTTPBadRequest(text="invalid JSON body")
    state = _state(request)
    model = body.get("model") or (state.loader.names() or [""])[0]
    if not model:
        raise web.HTTPNotFound(text="no models configured")
    content = body.get("content") or body.get("prompt") or ""
    sm, _cfg = await _serving(request, OpenAIRequest(model=model))
    ids = sm.tokenizer.encode(str(content), add_bos=False)
    return web.json_response({"tokens": ids})


async def metrics(request: web.Request) -> web.Response:
    # refresh token/slot/engine series from live engine state at scrape
    # time (counters are monotone: scheduler totals only grow; gauges are
    # point-in-time) — the decode loop itself never touches the registry
    from localai_tpu.obs.device import update_device_gauges
    from localai_tpu.obs.metrics import update_engine_gauges

    from localai_tpu.obs.history import HISTORY
    from localai_tpu.obs.ledger import LEDGER

    state = _state(request)
    # a fleet-served model's metrics() pulls one stats RPC per replica —
    # off the event loop, or a wedged replica freezes every endpoint for
    # the duration of its RPC timeout (single-engine models are host-side
    # reads and ride along unharmed)
    loop = asyncio.get_running_loop()
    engine_metrics = await loop.run_in_executor(None, state.manager.metrics)
    for name, m in engine_metrics.items():
        if isinstance(m, dict):
            update_engine_gauges(name, m)
            # multi-resolution history: every scrape doubles as a
            # sampling tick (host-side dict reads — no device work)
            HISTORY.observe_engine(name, m)
    # usage ledger → tenant/goodput/waste families + history series
    LEDGER.export(REGISTRY)
    HISTORY.observe_ledger(LEDGER)
    # fleet replica-state gauges refresh at scrape time too (host-side
    # state reads only; the routed/transfer counters are event-driven)
    for sm in state.manager.loaded_snapshot().values():
        export = getattr(getattr(sm, "scheduler", None),
                         "export_gauges", None)
        if export is not None:
            export()
    # device health at scrape time is host metadata only (memory_stats +
    # live-array census) — never a device dispatch: a scrape must not
    # queue work behind a wedged device (the probe lives in /debug/devices)
    runners = [
        r for r in (
            getattr(sm, "runner", None)
            for sm in state.manager.loaded_snapshot().values()
        ) if r is not None
    ]
    update_device_gauges(runners)
    # SLO observatory: burn-rate + shedding gauges refresh at scrape time
    # too (host-side window scans only — never a device dispatch)
    from localai_tpu.obs import slo as obs_slo
    from localai_tpu.obs import trace as obs_trace

    obs_slo.SLO.export_gauges()
    # trace-store sizing receipt (LOCALAI_TRACE_CAPACITY): dashboards can
    # tell "trace evicted from the ring" from "trace never recorded"
    REGISTRY.trace_ring_size.set(obs_trace.STORE.capacity)
    # offline batch subsystem: job-state gauge + lane-paused flag refresh
    # at scrape time (host-side JSON reads only)
    state.batches.export_gauges()
    svc = state._batch_service
    REGISTRY.batch_lane_paused.set(
        1 if (svc is not None and svc.paused) else 0
    )
    return web.Response(
        text=REGISTRY.render(),
        content_type="text/plain",
        charset="utf-8",
    )


async def usage(request: web.Request) -> web.Response:
    """GET /v1/usage — the usage accounting plane (obs.ledger): per-tenant
    delivered tokens / dispatch-ms / queue-wait / KV-block-seconds by
    (model, lane), the goodput-vs-waste decomposition, and — for
    fleet-served models — per-replica drill-down panes harvested over
    GetTelemetry.

    Query params: ``?since=<unix ts>`` or ``?window=<seconds>`` narrow
    the per-tenant rows to the ledger's event ring (bounded — the
    response says how far back its coverage actually reaches); without
    them the lifetime totals answer. Tenants are hashed buckets
    (``t-<sha256/12>``) or ``anonymous`` — a raw API key never appears
    here. ``?replicas=1`` adds the fleet drill-down (one bounded RPC per
    replica, off the event loop)."""
    from localai_tpu.obs.fleetview import fleet_usage
    from localai_tpu.obs.ledger import LEDGER

    def num(name):
        raw = request.query.get(name)
        if raw is None or raw == "":
            return None
        try:
            return float(raw)
        except ValueError:
            raise web.HTTPBadRequest(text=f"{name} must be a number")

    since = num("since")
    window = num("window")
    state = _state(request)
    want_replicas = request.query.get("replicas") not in (None, "", "0")

    def build() -> dict:
        payload = LEDGER.usage_payload(since=since, window=window)
        if want_replicas:
            panes = {}
            for name, sm in state.manager.loaded_snapshot().items():
                if getattr(sm, "pool", None) is not None:
                    panes[name] = fleet_usage(sm)
            payload["replicas"] = panes
        return payload

    # the fleet drill-down pulls one bounded RPC per replica — executor,
    # never the event loop (same rule as every other harvest endpoint)
    loop = asyncio.get_running_loop()
    return web.json_response(await loop.run_in_executor(
        _state(request).executor, build))


async def slo_report(_request: web.Request) -> web.Response:
    """GET /v1/slo — the SLO observatory: per-model sliding-window
    (1m/5m/30m) TTFT/TPOT/e2e/queue-wait percentiles, burn rates against
    the configured p95 targets, and load-shedding state (obs.slo)."""
    from localai_tpu.obs import slo as obs_slo

    return web.json_response(obs_slo.SLO.report())


async def fleet_status(request: web.Request) -> web.Response:
    """GET /v1/fleet — the fleet observatory: per-model replica states,
    dial health, routing counters (affinity/least_loaded/failover +
    route-around), prefix-transfer stats, and per-replica shedding
    (localai_tpu.fleet). Models served by a single engine are listed with
    ``fleet: false`` so the panel shows the whole serving surface."""
    state = _state(request)
    loop = asyncio.get_running_loop()
    out: dict[str, dict] = {}
    for name, sm in state.manager.loaded_snapshot().items():
        status_fn = getattr(sm, "fleet_status", None)
        if status_fn is None:
            out[name] = {"fleet": False}
            continue
        # the status pulls one metrics RPC per replica — off the loop
        out[name] = {"fleet": True,
                     **await loop.run_in_executor(None, status_fn)}
    return web.json_response({
        "configured_replicas": state.config.fleet_replicas,
        "configured_prefill_replicas": state.config.fleet_prefill_replicas,
        "backend": state.config.fleet_backend,
        "models": out,
    })


async def fleet_register(request: web.Request) -> web.Response:
    """POST /federated/register on the SERVING instance: a remote worker
    announces itself (``{"address": "host:port", "model": optional,
    "role": "decode"|"prefill"}``) and is adopted into the matching fleet
    pools as a RemoteReplica — the fleet-tier twin of the federation
    router's registry, with the same ``peer_token`` guard, the same
    unroutable-address rejection, and offline-eviction parity (a peer
    that stops answering dials is evicted from routing and redialed on
    backoff, exactly like the router flips nodes offline)."""
    import hmac

    from localai_tpu.federation.server import validate_advertised_address

    state = _state(request)
    if state.config.peer_token:
        header = request.headers.get("Authorization", "")
        token = header.removeprefix("Bearer ").strip()
        if not hmac.compare_digest(token, state.config.peer_token):
            return web.json_response({"error": "invalid peer token"},
                                     status=401)
    try:
        body = await request.json()
        address = str(body["address"])
    except Exception:
        return web.json_response({"error": "address is required"},
                                 status=400)
    try:
        validate_advertised_address(address)
    except ValueError as e:
        return web.json_response({"error": str(e)}, status=400)
    role = str(body.get("role", "decode"))
    if role not in ("decode", "prefill"):
        return web.json_response(
            {"error": f"unknown role {role!r} (decode|prefill)"},
            status=400)
    model = body.get("model")
    targets = {}
    for name, sm in state.manager.loaded_snapshot().items():
        if model and name != model:
            continue
        if hasattr(sm, "adopt_remote"):
            targets[name] = sm
    if not targets:
        return web.json_response(
            {"error": (f"model {model!r} is not fleet-served" if model
                       else "no fleet-served model loaded")},
            status=409)
    if len(targets) > 1:
        # a worker process holds ONE model: adopting it into several
        # pools would leave every pool after the first seeing Status
        # READY and silently serving the FIRST pool's model under its
        # own name — the registration must say which model the peer is
        # for
        return web.json_response(
            {"error": "multiple fleet-served models are loaded "
                      f"({sorted(targets)}); pass \"model\" to say which "
                      "one the peer serves"},
            status=409)
    loop = asyncio.get_running_loop()
    adopted = {}
    for name, sm in targets.items():
        # the adoption dials + LoadModels the peer — off the event loop
        adopted[name] = await loop.run_in_executor(
            None, sm.adopt_remote, address, role)
    return web.json_response({"address": address, "adopted": adopted})


async def fleet_swap(request: web.Request) -> web.Response:
    """POST /v1/fleet/{model}/swap: hot weight swap as the deploy
    primitive — boot fresh replicas (``{"checkpoint": "ref"}`` switches
    weights; an empty body recycles the current ones), shift router
    traffic, drain and retire the old generation. Same ``peer_token``
    guard as fleet registration: this mutates serving capacity."""
    import hmac

    state = _state(request)
    if state.config.peer_token:
        header = request.headers.get("Authorization", "")
        token = header.removeprefix("Bearer ").strip()
        if not hmac.compare_digest(token, state.config.peer_token):
            return web.json_response({"error": "invalid peer token"},
                                     status=401)
    checkpoint = None
    if request.can_read_body:
        try:
            body = await request.json()
        except Exception:
            return web.json_response({"error": "invalid JSON body"},
                                     status=400)
        if not isinstance(body, dict):
            return web.json_response({"error": "body must be a JSON "
                                               "object"}, status=400)
        checkpoint = body.get("checkpoint")
        if checkpoint is not None and not isinstance(checkpoint, str):
            return web.json_response({"error": "checkpoint must be a "
                                               "string"}, status=400)
    name = request.match_info["model"]
    sm = state.manager.loaded_snapshot().get(name)
    if sm is None:
        return web.json_response({"error": f"model {name!r} is not "
                                           "loaded"}, status=404)
    swap_fn = getattr(sm, "swap", None)
    if swap_fn is None:
        return web.json_response({"error": f"model {name!r} is not "
                                           "fleet-served"}, status=409)
    loop = asyncio.get_running_loop()
    # the swap boots replicas and drains the old generation — off the loop
    result = await loop.run_in_executor(None, swap_fn, checkpoint)
    return web.json_response({"model": name, **result},
                             status=200 if result.get("ok") else 409)


async def system(request: web.Request) -> web.Response:
    """GET /system (parity: SystemInformations, routes/localai.go:64 —
    CPU/GPU info becomes the JAX device inventory)."""
    import jax

    state = _state(request)
    devices = [
        {
            "id": d.id,
            "platform": d.platform,
            "kind": getattr(d, "device_kind", ""),
            "process_index": d.process_index,
        }
        for d in jax.devices()
    ]
    return web.json_response({
        "version": __version__,
        "devices": devices,
        "backends": ["jax"],
        "loaded_models": state.manager.loaded_names(),
        "configured_models": state.loader.names(),
    })


async def backend_monitor(request: web.Request) -> web.Response:
    """POST {model} → engine status (parity: BackendMonitorEndpoint,
    core/http/endpoints/localai/backend_monitor.go)."""
    body = await request.json()
    name = body.get("model", "")
    if not name:
        raise web.HTTPBadRequest(text="missing 'model'")
    return web.json_response(_state(request).manager.monitor(name))


async def backend_shutdown(request: web.Request) -> web.Response:
    body = await request.json()
    name = body.get("model", "")
    if not name:
        raise web.HTTPBadRequest(text="missing 'model'")
    ok = _state(request).manager.shutdown_model(name)
    return web.json_response({"shutdown": ok, "model": name})


async def engine_metrics(request: web.Request) -> web.Response:
    """Per-model live slot metrics (parity: the GetMetrics RPC surface,
    grpc-server.cpp:2434-2457, exposed over /backend/monitor)."""
    loop = asyncio.get_running_loop()
    metrics = await loop.run_in_executor(
        None, _state(request).manager.metrics)
    return web.json_response(metrics)


async def backend_trace(request: web.Request) -> web.Response:
    """POST {seconds?, dir?, python_tracer?} → capture a device/XLA
    profiler trace (jax.profiler, TensorBoard/XProf format) while serving
    continues. It holds the device's operations under their scope names
    and the scheduler's ``sched.*`` phases on one clock; Python frames
    only with ``"python_tracer": true`` (they slow the host it measures).
    The TPU-era upgrade of the reference's pprof-style debug surface:
    traces show per-program device time, fusion layout, and HBM traffic —
    the ground truth for kernel/serving optimization. API-key-protected;
    one capture at a time; ``dir`` must stay under generated assets."""
    if request.can_read_body:
        try:
            body = await request.json()
        except Exception:  # malformed body is a client error, not a 500
            raise web.HTTPBadRequest(text="invalid JSON body")
        if not isinstance(body, dict):
            raise web.HTTPBadRequest(text="body must be a JSON object")
    else:
        body = {}
    try:
        seconds = float(body.get("seconds", 3.0))
    except (TypeError, ValueError):
        raise web.HTTPBadRequest(text="seconds must be a number")
    if not 0.1 <= seconds <= 60.0:
        raise web.HTTPBadRequest(text="seconds must be in [0.1, 60]")
    python_tracer = body.get("python_tracer", False)
    if not isinstance(python_tracer, bool):
        raise web.HTTPBadRequest(text="python_tracer must be true or false")
    from localai_tpu.utils.paths import verify_path

    state = _state(request)
    base = state.config.backend_assets_path or "."
    try:
        out = verify_path(str(body.get("dir", "traces")), base)
    except ValueError as e:
        raise web.HTTPBadRequest(text=str(e))

    def capture() -> str:
        # single-flight is SHARED with the anomaly profiler
        # (obs.profiler), and so is the capture itself: the device runs
        # at most one at a time no matter which surface asked for it
        from localai_tpu.obs import profiler

        if not profiler.PROFILER.acquire_capture():
            raise RuntimeError("a trace capture is already running")
        try:
            path = str(out / time.strftime("trace-%Y%m%d-%H%M%S"))
            profiler.capture(path, seconds, python_tracer)
            return path
        finally:
            profiler.PROFILER.release_capture()

    loop = asyncio.get_running_loop()
    try:
        path = await loop.run_in_executor(None, capture)
    except RuntimeError as e:
        raise web.HTTPConflict(text=str(e))
    return web.json_response({"trace_dir": path, "seconds": seconds})


def routes() -> list[web.RouteDef]:
    return [
        web.get("/healthz", healthz),
        web.get("/readyz", readyz),
        web.get("/version", version),
        web.get("/metrics", metrics),
        web.get("/v1/usage", usage),
        web.get("/v1/slo", slo_report),
        web.get("/v1/fleet", fleet_status),
        web.post("/v1/fleet/{model}/swap", fleet_swap),
        web.post("/federated/register", fleet_register),
        web.get("/system", system),
        web.post("/v1/tokenize", tokenize),
        web.post("/tokenize", tokenize),
        web.post("/backend/monitor", backend_monitor),
        web.post("/backend/shutdown", backend_shutdown),
        web.get("/backend/metrics", engine_metrics),
        web.post("/backend/trace", backend_trace),
    ]
