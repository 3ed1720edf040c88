"""Worker-backed serving: the ServingModel facade over a spawned gRPC
worker process.

This delivers the reference's central lifecycle property — a model crash
never takes down the API server (/root/reference/pkg/model/
initializers.go:271-407: spawn, health-gate, LoadModel over gRPC;
loader.go:170-206: health-check-and-respawn) — for models configured with
``backend: worker`` or registered in ``external_backends``.

The facade presents the same surface the HTTP endpoints use on the
in-process ServingModel (tokenizer/templates locally, ``scheduler.submit``
returning a GenHandle), but the engine runs in its own process; prompts go
over the wire as token ids and constraints as their source regex
(PredictOptions.constraint_regex — the worker rebuilds the FSM against the
same tokenizer).
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from typing import Any, Optional

from localai_tpu.config.app_config import AppConfig
from localai_tpu.config.model_config import ModelConfig
from localai_tpu.engine.scheduler import GenHandle, GenRequest
from localai_tpu.obs import EngineTelemetry
from localai_tpu.obs import watchdog as obs_watchdog
from localai_tpu.worker import backend_pb2 as pb
from localai_tpu.worker.client import WorkerClient

log = logging.getLogger(__name__)

_SAMPLING_FIELDS = (
    "temperature", "top_k", "top_p", "min_p",
    "repeat_penalty", "presence_penalty", "frequency_penalty", "seed",
)


class WorkerGenHandle(GenHandle):
    """GenHandle fed from a PredictStream RPC instead of the engine thread.
    Token ids don't cross the wire, so completion counts come from the
    final Reply's usage fields — and when the stream dies BEFORE that
    final reply (worker killed mid-generation), from the count of
    streamed deltas (each Reply carries >= 1 sampled token), so a failed
    request never reports 0 tokens for work the engine actually did."""

    def __init__(self, req: GenRequest, rid: int):
        super().__init__(req, rid)
        self._completion_override: Optional[int] = None
        self._streamed_deltas = 0

    @property
    def completion_tokens(self) -> int:
        if self._completion_override is not None:
            return self._completion_override
        return max(len(self.token_ids), self._streamed_deltas)


def predict_options(gr: GenRequest) -> pb.PredictOptions:
    """GenRequest → wire options (inverse of worker.server._gen_request)."""
    opts = pb.PredictOptions(
        tokens=list(gr.prompt),
        max_tokens=gr.max_new_tokens,
        stop=list(gr.stop),
        ignore_eos=gr.ignore_eos,
        correlation_id=gr.correlation_id,
        stream=gr.stream,
    )
    for f in _SAMPLING_FIELDS:
        v = getattr(gr, f)
        if v is not None:
            setattr(opts, f, v)
    if gr.logit_bias:
        for k, v in gr.logit_bias.items():
            opts.logit_bias[int(k)] = float(v)
    if gr.constraint is not None:
        regex = getattr(gr.constraint, "source_regex", None)
        if regex:
            opts.constraint_regex = regex
        else:
            log.warning(
                "constraint without a serializable source regex; the "
                "worker will decode unconstrained"
            )
    return opts


def consume_stream(handle: WorkerGenHandle, replies, *,
                   watchdog=None, channel: str = "",
                   tr=None) -> tuple[str, bool]:
    """Drain one PredictStream-shaped reply iterator into ``handle``.

    The one place the wire protocol is interpreted on the API side —
    WorkerScheduler (single worker) and fleet.FleetScheduler (replica
    fleets) both feed their handles through here, so a protocol change
    cannot diverge their accounting. Returns ``(finish, got_final)``:
    ``got_final=False`` means the stream ended WITHOUT the final usage
    Reply — the worker/replica died mid-generation; the caller decides
    whether that is a failover signal (fleet) or a terminal error."""
    finish = "stop"
    got_final = False
    for reply in replies:
        if watchdog is not None:
            watchdog.pulse(channel)
        if handle.cancelled:
            finish = "cancelled"
            got_final = True
            break
        if reply.finish_reason:
            finish = reply.finish_reason
            got_final = True
            handle._completion_override = reply.tokens or None
            if reply.prompt_tokens:
                handle.prompt_tokens = reply.prompt_tokens
            break
        if reply.message:
            if tr is not None and handle.t_first_token is None:
                tr.event("first_delta")
            handle._streamed_deltas += 1
            handle._emit(reply.message.decode("utf-8", "replace"), None)
    return finish, got_final


class WorkerScheduler:
    """The scheduler-shaped surface of a worker-backed model: submit() runs
    a PredictStream RPC on a daemon thread feeding a GenHandle."""

    def __init__(self, owner: "WorkerServingModel"):
        self._owner = owner
        self._ids = itertools.count()
        self._inflight = 0
        self._lock = threading.Lock()
        # API-side view of the worker's requests: queued → rpc spans here,
        # engine-phase spans in the worker process under the same trace id
        self.telemetry = EngineTelemetry(model=owner.name)
        # the RPC stream is a device round-trip once removed: a wedged
        # worker stops the reply stream, and the watchdog
        # must see that silence like any other stall
        self.watchdog = obs_watchdog.WATCHDOG
        self._wd_channel = f"rpc:{owner.name}"
        self.watchdog.start()
        # SLO admission-control rejections happen at the API tier, so the
        # counter lives here (the worker process never sees shed requests)
        self.shed_total = 0

    @property
    def busy(self) -> bool:
        with self._lock:
            return self._inflight > 0

    def submit(self, gr: GenRequest) -> GenHandle:
        handle = WorkerGenHandle(gr, next(self._ids))
        handle.trace = self.telemetry.queued(handle)
        if gr.mm_embeds is not None:
            # image embeddings don't cross the proto yet; fail loudly
            # rather than silently serving text-only
            self.telemetry.finished(handle.trace, handle, "error")
            handle._finish("error")
            log.error("worker-backed models do not support multimodal input")
            return handle
        # mark busy before the thread starts: an eviction sweep between
        # submit() and the thread's first instruction must not kill the
        # worker under an accepted request
        with self._lock:
            self._inflight += 1
        threading.Thread(
            target=self._run, args=(handle,), daemon=True,
            name=f"worker-req-{handle.id}",
        ).start()
        return handle

    def _run(self, handle: WorkerGenHandle) -> None:
        tr = handle.trace
        # armed across the whole RPC, pulsed per reply: a worker that stops
        # streaming (dead process, wedged device) trips the stall watchdog
        # even though grpc's own 600 s deadline is nowhere near
        self.watchdog.arm(self._wd_channel)
        try:
            client = self._owner.client()
            opts = predict_options(handle.request)
            req = handle.request
            if tr is not None:
                tr.end("queued")
                tr.begin("rpc", worker=client.address)
            finish, got_final = consume_stream(
                handle,
                client.predict_stream(
                    opts, timeout=600.0,
                    trace_id=req.trace_id or req.correlation_id,
                    tenant=req.tenant),
                watchdog=self.watchdog, channel=self._wd_channel, tr=tr)
            if not got_final:
                # the stream ended without the final usage Reply: the
                # worker died (or its connection dropped) mid-generation.
                # Mark the handle failed — completion_tokens falls back
                # to the streamed-delta count instead of reporting 0.
                finish = "error"
                log.warning(
                    "worker request %d: stream ended without a final "
                    "reply after %d deltas", handle.id,
                    handle._streamed_deltas)
            # trace retires before _finish unblocks the awaiting handler
            self.telemetry.finished(tr, handle, finish)
            handle._finish(finish)
        except Exception as e:  # noqa: BLE001 — worker crash ≠ API crash
            log.warning("worker request %d failed: %s", handle.id, e)
            self.telemetry.finished(tr, handle, "error")
            handle._finish("error")
        finally:
            self.watchdog.disarm(self._wd_channel)
            with self._lock:
                self._inflight -= 1

    def note_shed(self) -> None:
        """Record one API-level SLO admission rejection for this model."""
        with self._lock:
            self.shed_total += 1

    def metrics(self) -> dict:
        try:
            m = self._owner.client().metrics()
        except Exception as e:  # noqa: BLE001
            return {"error": str(e)}
        # monotone int scrape read; a one-increment-stale value is fine
        m["shed_total"] = self.shed_total  # jaxlint: disable=lock-guarded-attr
        return m

    def shutdown(self, timeout: float = 10.0) -> None:
        self._owner.close()


class WorkerServingModel:
    """ServingModel counterpart whose engine lives in a worker process.

    Tokenization/templating stay local (the reference templates in Go while
    llama.cpp owns the weights); generation RPCs go to the worker. The
    pool health-checks and respawns on access, and ensure_loaded() re-issues
    LoadModel after any respawn."""

    def __init__(self, mcfg: ModelConfig, app: AppConfig, pool,
                 *, external_address: Optional[str] = None):
        from localai_tpu.models.registry import resolve_tokenizer
        from localai_tpu.templates.cache import TemplateCache

        self.name = mcfg.name
        self.config = mcfg
        self.app = app
        self.pool = pool
        self.external_address = external_address
        self.tokenizer = resolve_tokenizer(
            mcfg.model or mcfg.name, app.model_path
        )
        self.templates = TemplateCache(app.model_path)
        self.vision = None
        self.image_token_id = 0
        if mcfg.mmproj:
            log.warning(
                "model %s: mmproj is not supported on worker-backed models "
                "yet; images will be ignored", mcfg.name,
            )
        self.scheduler = WorkerScheduler(self)
        self.loaded_at = time.monotonic()
        self.last_used = time.monotonic()
        self._client_lock = threading.Lock()
        self._loaded_client: Optional[WorkerClient] = None
        self.client()  # spawn + load eagerly so config errors surface now

    # -- lifecycle ---------------------------------------------------------

    def client(self) -> WorkerClient:
        """Healthy client for this model's worker: spawns/respawns via the
        pool and guarantees the model is loaded (a respawned process comes
        up empty)."""
        with self._client_lock:
            if self.external_address is not None:
                c = self.pool.register_external(self.name,
                                                self.external_address)
            else:
                c = self.pool.get(self.name, env=self.app.worker_env or None)
            # the pool hands back the same client object while the worker
            # stays healthy; a new object means a respawn (empty process) —
            # only then pay the Status round trip + LoadModel
            if c is not self._loaded_client:
                # load-once barrier, deliberately under the lock:
                # concurrent callers MUST wait for the respawned
                # worker's LoadModel — racing it would double-load
                self._ensure_loaded(c)  # jaxlint: disable=blocking-under-lock
                self._loaded_client = c
            return c

    def _ensure_loaded(self, c: WorkerClient) -> None:
        st = c.status()
        if st.state in (pb.StatusResponse.READY, pb.StatusResponse.BUSY):
            return
        import yaml

        doc = self.config.model_dump(exclude_none=True, exclude_defaults=True)
        doc["name"] = self.config.name
        doc["model"] = self.config.model or self.config.name
        doc.pop("backend", None)  # the worker itself runs in-process
        res = c.load_model(
            config_yaml=yaml.safe_dump(doc),
            model_path=str(self.app.model_path),
        )
        if not res.success:
            raise RuntimeError(
                f"worker LoadModel failed for {self.name}: {res.message}"
            )
        from localai_tpu.worker.process import check_worker_device

        self.device = check_worker_device(
            res.message,
            None if self.external_address is not None
            else self.app.worker_env, self.name)

    def touch(self) -> None:
        self.last_used = time.monotonic()

    @property
    def busy(self) -> bool:
        return self.scheduler.busy

    def alive(self) -> bool:
        """Cheap liveness only — this runs under the ModelManager lock, so
        no RPCs here (a blocking health check would serialize every model
        lookup behind one dead worker). Spawned workers: process poll.
        External workers: assumed alive; failures surface per-request."""
        if self.external_address is not None:
            return True
        wp = self.pool._workers.get(self.name)
        return wp is not None and wp.alive

    def engine_metrics(self) -> dict:
        return self.scheduler.metrics()

    def close(self) -> None:
        self.pool.shutdown(self.name)
