"""HTTP application wiring: middlewares (auth, metrics, errors, CORS) +
route registration + lifecycle.

Parity: /root/reference/core/http/app.go:52-186 — fiber app with error
handling (optional opaque errors), request logging, recover, metrics
middleware, key-auth with exemptions, CORS, route registration — rebuilt
on aiohttp (FastAPI/uvicorn are not in this image; aiohttp is, and SSE
streaming maps directly onto StreamResponse).
"""

from __future__ import annotations

import asyncio
import contextvars
import logging
import secrets
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional

from aiohttp import web

from localai_tpu.api import localai as localai_routes
from localai_tpu.api import openai as openai_routes
from localai_tpu.api.metrics import REGISTRY
from localai_tpu.api.schema import error_body
from localai_tpu.config.app_config import AppConfig
from localai_tpu.config.loader import ConfigLoader
from localai_tpu.models.manager import ModelManager
from localai_tpu.obs import logging as obs_logging
from localai_tpu.obs import trace as obs_trace

log = logging.getLogger(__name__)

STATE_KEY = web.AppKey("state", object)
# per-request trace id, set by trace_middleware (a plain str key: aiohttp
# Requests are MutableMappings; handlers read it via request.get())
TRACE_KEY = "trace_id"
# per-request tenant bucket (obs.ledger.derive_tenant output — hashed
# key / anonymous; NEVER the raw key), set by auth_middleware
TENANT_KEY = "tenant"
# observability/probe endpoints whose HTTP spans are pure scrape noise:
# they still get a trace id, but are not recorded into the trace store
# (a 15s Prometheus scrape would otherwise dominate the http ring)
TRACE_SKIP = {"/metrics", "/healthz", "/readyz", "/v1/traces", "/v1/slo",
              "/debug/devices", "/debug/programs", "/debug/stacks",
              "/debug/flight", "/debug/fleet/flight", "/debug/profiles",
              "/debug/kv", "/debug/faults"}
TRACE_SKIP_PREFIXES = ("/debug/timeline/", "/v1/traces/")

# paths reachable without an API key (parity: auth exemption filter,
# core/http/middleware/auth.go:17+)
# /swagger docs expose only the route list, which the exempt "/" JSON
# welcome already lists; the explorer page fetches doc.json without auth
AUTH_EXEMPT = {"/", "/healthz", "/readyz", "/version", "/swagger",
               "/swagger/doc.json"}
# UI documents are key-free to GET (they hold no data; their JS calls the
# protected JSON APIs with the key the operator enters in the page header)
from localai_tpu.api.ui import UI_EXACT, UI_PREFIXES  # noqa: E402


class ContextExecutor(ThreadPoolExecutor):
    """ThreadPoolExecutor that copies the caller's contextvars into the
    worker thread. ``loop.run_in_executor`` does NOT do this, so without
    it every log line from a blocking engine wait (lazy model load, the
    generation join) would lose the request's bound trace id
    (obs.logging) and break the JSON-log ↔ trace join."""

    def submit(self, fn, /, *args, **kwargs):
        ctx = contextvars.copy_context()
        return super().submit(lambda: ctx.run(fn, *args, **kwargs))


class AppState:
    """Shared handler state (the reference passes (cl, ml, appConfig)
    closures into every endpoint — app.go:159-165)."""

    def __init__(self, app_config: Optional[AppConfig] = None,
                 loader: Optional[ConfigLoader] = None,
                 manager: Optional[ModelManager] = None):
        from localai_tpu.gallery import Gallery

        self.config = app_config or AppConfig()
        self.loader = loader or ConfigLoader(self.config.model_path)
        self.manager = manager or ModelManager(self.config, self.loader)
        # deterministic fault injection (localai_tpu.faults): arm any
        # LOCALAI_FAULT_* specs once at boot — the registry is never
        # consulted from a request path while nothing is armed
        from localai_tpu import faults

        faults.install_from_env()
        # SLO observatory targets from app config (env-overridable via
        # LOCALAI_SLO_* through AppConfig.from_env; all-zero = shedding
        # disabled). Wired here so every server entry path — serve(),
        # tests, embedded — configures the process-wide tracker once.
        from localai_tpu.obs import slo as obs_slo

        obs_slo.SLO.configure(
            targets=obs_slo.targets_from_config(self.config),
            burn_threshold=self.config.slo_burn_threshold,
        )
        # anomaly-triggered profiler capture (obs.profiler): armed only
        # when LOCALAI_PROFILE_ON_ANOMALY=1 — hooks watchdog stalls, SLO
        # shed onsets, and the per-engine flight rings; profiles land
        # under <backend-assets>/profiles with a manifest
        # (GET /debug/profiles)
        from localai_tpu.obs import profiler as obs_profiler

        obs_profiler.install_from_env(
            str(self.config.backend_assets_path or "."))
        # multi-resolution metrics history (obs.history): re-onboard the
        # last snapshot and start the periodic writer thread when
        # LOCALAI_HISTORY_DIR is set — the series survive restarts
        from localai_tpu.obs import history as obs_history

        obs_history.install_from_env()
        self.galleries: list[Gallery] = [
            Gallery(name=g.get("name", ""), url=g.get("url", ""))
            for g in self.config.galleries
        ]
        self._gallery_service = None
        from localai_tpu.stores import StoreRegistry

        self.stores = StoreRegistry()
        # blocking engine waits run here, off the event loop (contextvar-
        # propagating: executor-side log lines keep the request trace id)
        self.executor = ContextExecutor(
            max_workers=32, thread_name_prefix="api-wait"
        )
        # dynamic config: api_keys.json / external_backends.json hot-reload
        # (parity: core/startup/config_file_watcher.go)
        from localai_tpu.config.watcher import (
            ConfigWatcher,
            attach_standard_handlers,
        )

        self.watcher = ConfigWatcher(self.config.config_path)
        attach_standard_handlers(self.watcher, self)
        self.watcher.start()
        # unified /v1/files registry + assistants persistence, reloaded at
        # boot (parity: app.go:152-154 LoadConfig of assistants.json/
        # uploadedFiles.json) — one FileRegistry serves assistants
        # attachments, batch inputs, and batch result downloads
        from localai_tpu.api.assistants import AssistantStore
        from localai_tpu.batch import BatchStore, FileRegistry

        self.files = FileRegistry(self.config.upload_path)
        self.assistants = AssistantStore(
            self.config.config_path, self.config.upload_path,
            registry=self.files,
        )
        # offline batch subsystem: durable job store now, executor thread
        # lazily (batch_service) — but jobs that survived a restart resume
        # without waiting for an API call
        self.batches = BatchStore(
            self.config.upload_path, self.files,
            expiry_h=self.config.batch_expiry_h,
        )
        self._batch_service = None
        if self.batches.runnable() is not None:
            self.batch_service.wake()

    @property
    def batch_service(self):
        """Lazily started batch executor (the background-lane drain
        thread); first access starts it."""
        if self._batch_service is None:
            from localai_tpu.batch import BatchExecutor

            def serving_for(name: str):
                mcfg = self.loader.get(name)
                if mcfg is None:
                    raise ValueError(f"model {name!r} not found")
                return self.manager.get(name), mcfg

            self._batch_service = BatchExecutor(
                self.batches, serving_for,
                concurrency=self.config.batch_concurrency,
                deadline_s=self.config.request_deadline_s,
            )
            self._batch_service.start()
        return self._batch_service

    @property
    def gallery_service(self):
        """Lazily started job runner (parity: gallery service start,
        core/http/app.go:141-150)."""
        if self._gallery_service is None:
            from localai_tpu.gallery import GalleryService

            self._gallery_service = GalleryService(
                self.config.model_path, self.galleries,
                on_installed=lambda p: self.loader.load_single(
                    p, context_size=self.config.context_size
                ),
                on_deleted=self.loader.remove,
            )
        return self._gallery_service

    def add_gallery(self, gallery) -> None:
        self.galleries.append(gallery)
        if self._gallery_service is not None:
            self._gallery_service.galleries = list(self.galleries)

    def remove_gallery(self, name: str) -> bool:
        before = len(self.galleries)
        self.galleries = [g for g in self.galleries if g.name != name]
        if self._gallery_service is not None:
            self._gallery_service.galleries = list(self.galleries)
        return len(self.galleries) < before

    def shutdown(self) -> None:
        self.watcher.stop()
        if self._batch_service is not None:
            # stop BEFORE the engines go down: an in_progress job stays
            # durable and resumes from its output file on next boot
            self._batch_service.stop()
        self.manager.shutdown_all()
        if self._gallery_service is not None:
            self._gallery_service.shutdown()
        self.executor.shutdown(wait=False, cancel_futures=True)


@web.middleware
async def error_middleware(request: web.Request, handler):
    state = request.app[STATE_KEY]
    try:
        return await handler(request)
    except web.HTTPException as e:
        if e.status >= 400:
            msg = e.text or e.reason or "error"
            resp = web.json_response(
                error_body(msg, code=e.status), status=e.status
            )
            # the JSON re-wrap must not strip semantic headers the
            # handler set on the exception (Retry-After on a shed 429,
            # Allow on a 405, ...) — only the body-describing ones are
            # superseded by the JSON wrapper
            for k, v in e.headers.items():
                if k.lower() not in ("content-type", "content-length"):
                    resp.headers[k] = v
            return resp
        raise
    except Exception as e:  # noqa: BLE001 — recover middleware parity
        log.exception("unhandled error on %s %s", request.method,
                      request.path)
        msg = ("internal error" if state.config.opaque_errors
               else f"{type(e).__name__}: {e}")
        return web.json_response(
            error_body(msg, kind="internal_error", code=500), status=500
        )


def _canonical_path(request: web.Request) -> str:
    # the matched route pattern, not the raw URL — raw paths are
    # attacker-controlled and would grow the registry without bound
    resource = getattr(request.match_info.route, "resource", None)
    return getattr(resource, "canonical", None) or "(unmatched)"


@web.middleware
async def metrics_middleware(request: web.Request, handler):
    t0 = time.monotonic()
    try:
        return await handler(request)
    finally:
        REGISTRY.api_call.observe(
            time.monotonic() - t0,
            method=request.method, path=_canonical_path(request),
        )


@web.middleware
async def trace_middleware(request: web.Request, handler):
    """Tag every request with a trace id (client-supplied X-Trace-ID /
    X-Correlation-ID, else generated) and record its HTTP span into the
    trace store — the root the engine's request spans group under."""
    tid = (request.headers.get("X-Trace-ID")
           or request.headers.get("X-Correlation-ID")
           or obs_trace.new_trace_id())
    request[TRACE_KEY] = tid
    # bind for structured logging: every log line emitted from this
    # request's context (handlers run as one asyncio task; contextvars
    # isolate concurrent requests) carries the trace id in JSON mode
    log_token = obs_logging.bind_trace_id(tid)
    t0 = time.monotonic()
    status = 500
    try:
        resp = await handler(request)
        status = resp.status
        if not resp.prepared:  # streaming handlers already sent headers
            resp.headers["X-Trace-ID"] = tid
        return resp
    except web.HTTPException as e:
        status = e.status
        raise
    finally:
        obs_logging.unbind_trace_id(log_token)
        if (request.path not in TRACE_SKIP
                and not request.path.startswith(TRACE_SKIP_PREFIXES)):
            tr = obs_trace.RequestTrace(
                tid, f"http-{id(request):x}", kind="http",
                method=request.method, path=_canonical_path(request),
                status=status,
            )
            tr.t0 = t0
            span = tr.begin("http", method=request.method,
                            path=_canonical_path(request), status=status)
            span.t0 = t0  # the span covers the whole handler, not just now
            tr.end("http")
            obs_trace.STORE.record(tr)


@web.middleware
async def auth_middleware(request: web.Request, handler):
    """Key auth + tenant derivation (obs.ledger): the ledger's tenant
    bucket is stamped HERE — a contextvar the ContextExecutor propagates
    into engine waits (build_gen_request resolves it), plus a request
    key for handlers. Always derive_tenant()'s output, never the raw
    key: auth-off/exempt traffic lands in the ``anonymous`` bucket."""
    from localai_tpu.obs import ledger as obs_ledger

    state = request.app[STATE_KEY]
    keys = state.config.api_keys

    def _admit(tenant: str):
        request[TENANT_KEY] = tenant
        obs_ledger.set_current_tenant(tenant)
        return handler(request)

    if not keys or request.path in AUTH_EXEMPT:
        return await _admit(obs_ledger.ANONYMOUS)
    if (request.method == "GET" and not state.config.disable_webui
            and (request.path.startswith(UI_PREFIXES)
                 or request.path in UI_EXACT)):
        return await _admit(obs_ledger.ANONYMOUS)
    header = request.headers.get("Authorization", "")
    token = header.removeprefix("Bearer ").strip()
    if token and any(secrets.compare_digest(token, k) for k in keys):
        return await _admit(obs_ledger.derive_tenant(token))
    return web.json_response(
        error_body("invalid or missing API key",
                   kind="authentication_error", code=401),
        status=401,
    )


@web.middleware
async def cors_middleware(request: web.Request, handler):
    state = request.app[STATE_KEY]
    if not state.config.cors:
        return await handler(request)
    if request.method == "OPTIONS":
        resp: web.StreamResponse = web.Response(status=204)
    else:
        resp = await handler(request)
    resp.headers["Access-Control-Allow-Origin"] = (
        state.config.cors_allow_origins or "*"
    )
    resp.headers["Access-Control-Allow-Methods"] = "GET, POST, DELETE, OPTIONS"
    resp.headers["Access-Control-Allow-Headers"] = "Authorization, Content-Type"
    return resp


async def welcome(request: web.Request) -> web.Response:
    state = request.app[STATE_KEY]
    if not state.config.disable_webui:
        from localai_tpu.api import ui

        # browsers get the UI home; API clients keep the JSON welcome
        if ui.wants_html(request):
            return await ui.home(request)
    return web.json_response({
        "message": "LocalAI-TPU",
        "models": state.loader.names(),
        "endpoints": sorted({
            r.resource.canonical
            for r in request.app.router.routes()
            if r.resource is not None
        }),
    })


def create_app(state: Optional[AppState] = None) -> web.Application:
    state = state or AppState()
    app = web.Application(middlewares=[
        trace_middleware, cors_middleware, error_middleware, auth_middleware,
        metrics_middleware,
    ], client_max_size=64 * 1024 * 1024)
    app[STATE_KEY] = state
    from localai_tpu.api import assistants as assistant_routes
    from localai_tpu.api import audio as audio_routes
    from localai_tpu.api import batches as batch_routes
    from localai_tpu.api import gallery as gallery_routes
    from localai_tpu.api import images as image_routes
    from localai_tpu.api import jina as jina_routes
    from localai_tpu.api import stores as stores_routes

    app.add_routes([web.get("/", welcome)])
    app.add_routes(openai_routes.routes())
    app.add_routes(localai_routes.routes())
    app.add_routes(gallery_routes.routes())
    app.add_routes(stores_routes.routes())
    app.add_routes(jina_routes.routes())
    app.add_routes(audio_routes.routes())
    app.add_routes(image_routes.routes())
    app.add_routes(assistant_routes.routes())
    app.add_routes(batch_routes.routes())
    if not state.config.disable_webui:
        from localai_tpu.api import ui as ui_routes

        app.add_routes(ui_routes.routes())
    from localai_tpu.api import debug as debug_routes
    from localai_tpu.api import openapi as openapi_routes
    from localai_tpu.api import traces as traces_routes

    app.add_routes(openapi_routes.routes())
    app.add_routes(traces_routes.routes())
    app.add_routes(debug_routes.routes())

    async def on_cleanup(_app):
        # shutdown joins engine threads and workers — seconds of wall
        # time; run it off-loop so in-flight connection teardown (and a
        # loopsan watching the dispatch) never sees the stall. Not on
        # state.executor: shutdown() tears that executor down.
        await asyncio.get_running_loop().run_in_executor(
            None, state.shutdown)

    app.on_cleanup.append(on_cleanup)
    return app


def serve(app_config: Optional[AppConfig] = None) -> None:
    """Blocking server entry (parity: appHTTP.Listen, run.go:199)."""
    cfg = app_config or AppConfig()
    if cfg.coordinator_address and cfg.num_processes > 1:
        # multi-host leader: join the jax.distributed group BEFORE any
        # jax use so jax.devices() spans every host (parallel/multihost)
        from localai_tpu.parallel.multihost import initialize

        initialize(cfg.coordinator_address, cfg.num_processes,
                   cfg.process_id)
    if cfg.mirror_port:
        # open the follower command channel NOW: followers connect at
        # boot, long before the first request lazily loads a model
        from localai_tpu.parallel.multihost import get_leader

        get_leader(cfg.mirror_port, cfg.mirror_followers,
                   token=cfg.peer_token)
    cfg.ensure_dirs()
    loader = ConfigLoader(cfg.model_path)
    loader.load_from_path(context_size=cfg.context_size)
    state = AppState(cfg, loader)
    # preload = make the model configured (embedded short names, gallery
    # refs — parity: pkgStartup.InstallModels, pkg/startup/model_preload.go)
    for name in cfg.preload_models:
        if loader.exists(name):
            continue
        try:
            from localai_tpu.gallery import install_model, resolve_ref

            m = resolve_ref(state.galleries, name)
            if m is None:
                log.warning("preload: unknown model ref %r", name)
                continue
            path = install_model(m, cfg.model_path,
                                 install_name="" if m.url else name)
            loader.load_single(path, context_size=cfg.context_size)
        except Exception as e:  # noqa: BLE001
            log.warning("preload of %s failed: %s", name, e)
    # load_to_memory = eager engine load (parity: LoadToMemory,
    # startup.go:148-176). A model the operator asked to have loaded at
    # boot and that cannot load is fatal: a server that logs a warning and
    # then answers /readyz with nothing loaded hides the failure until the
    # first request.
    for name in cfg.load_to_memory or cfg.preload_models:
        try:
            state.manager.get(name)
        except Exception:
            log.exception("eager load of %s failed; not serving", name)
            state.shutdown()
            raise
    if cfg.federated and cfg.federated_router:
        # join a federation: announce our address to the router (parity:
        # the p2p node advertising its service tunnel, federated_server.go)
        import socket

        from localai_tpu.federation import announce

        own = cfg.federated_advertise or (
            f"http://{socket.gethostname()}:{cfg.port}"
        )
        announce(cfg.federated_router, own, cfg.peer_token)
    log.info("serving on %s:%d (%d models configured)",
             cfg.address, cfg.port, len(loader.names()))
    web.run_app(create_app(state), host=cfg.address, port=cfg.port,
                print=None, access_log=None)
