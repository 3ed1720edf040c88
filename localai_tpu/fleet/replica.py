"""One engine replica: lifecycle state, dial bookkeeping, dispatch surface.

Two implementations behind one duck-typed surface:

  * :class:`WorkerReplica` — a spawned gRPC worker process
    (worker/process.py WorkerProcess + worker/client.py WorkerClient), the
    production shape: crash isolation per replica, device pinning via the
    spawn env, KV prefixes crossing the wire as PrefixChunk streams.
  * :class:`RemoteReplica` — an externally managed worker dialed at
    ``host:port`` across the network (static ``LOCALAI_FLEET_HOSTS``
    adoption or a federation-registry join): same WorkerClient transport
    as WorkerReplica, but NOT respawnable — this process does not own the
    remote's lifecycle, so a failed remote is *evicted* from routing and
    *redialed* on jittered exponential backoff instead of respawned.
  * :class:`InProcessReplica` — a full engine (build_serving_model) inside
    this process: the CPU-testable shape the router/pool/disaggregation
    tests and the CI telemetry smoke drive, with the same reply/chunk
    schema (worker.server.gen_request_from_options decodes requests for
    both, so the two kinds cannot drift).

States: ``starting`` → ``healthy`` ⇄ ``dead`` → ``respawning`` →
``healthy`` for locally owned replicas; remotes flip ``healthy`` ⇄
``evicted`` (redial instead of respawn). "Shedding" is not a stored state
— it is derived per route from the fleet's per-replica SLO tracker
(router.py)."""

from __future__ import annotations

import logging
import queue
import threading
import time
from typing import Any, Iterator, Optional

from localai_tpu.faults import registry as _faults

log = logging.getLogger(__name__)

STARTING = "starting"
HEALTHY = "healthy"
DEAD = "dead"
RESPAWNING = "respawning"
# a remote replica out of routing after failed dials: the pool redials it
# on backoff but never tries to (re)spawn a process it does not own
EVICTED = "evicted"


class _Reply:
    """pb.Reply-shaped streaming element from an in-process replica."""

    __slots__ = ("message", "tokens", "prompt_tokens", "finish_reason")

    def __init__(self, message: bytes = b"", tokens: int = 0,
                 prompt_tokens: int = 0, finish_reason: str = ""):
        self.message = message
        self.tokens = tokens
        self.prompt_tokens = prompt_tokens
        self.finish_reason = finish_reason


class BaseReplica:
    """Shared lifecycle/accounting; subclasses provide transport."""

    # False on replicas whose process this server does not own (remotes):
    # the pool evicts-and-redials them instead of stop()+respawn
    respawnable = True

    def __init__(self, rid: str, role: str):
        self.id = rid
        self.role = role                  # "decode" | "prefill"
        self.state = STARTING
        self._lock = threading.Lock()
        self.inflight = 0
        self.dispatched = 0               # lifetime requests routed here
        self.errors = 0                   # request-level failures
        self.failures = 0                 # consecutive dial failures
        self.dial_seconds: Optional[float] = None
        self.checked_mono: Optional[float] = None
        self.started_at = time.monotonic()
        # deliberately removed from the pool (autoscale scale-in, hot
        # swap): an in-flight respawn thread must park the corpse instead
        # of resurrecting a replica the operator just drained away
        self.retired = False
        # last-dispatch clock pair: monotonic drives the idle_s policy
        # signal (never jumps), wall time is the human-readable export in
        # GET /v1/fleet. A replica that never served reads idle since
        # boot — an unused fleet is exactly as scale-in-eligible as a
        # quiesced one.
        self.last_dispatch_mono = self.started_at
        self.last_dispatch_wall: Optional[float] = None
        # last reported decode queue depth (monitor-refreshed when the
        # pool tracks it — router.py's queue-override admission hint
        # reads this as a plain field, never an RPC)
        self.queue_depth = 0

    # -- accounting (router reads these for least-loaded) ------------------

    def begin(self) -> None:
        with self._lock:
            self.inflight += 1
            self.dispatched += 1
            self.last_dispatch_mono = time.monotonic()
            self.last_dispatch_wall = time.time()

    def done(self, *, error: bool = False) -> None:
        with self._lock:
            self.inflight -= 1
            if error:
                self.errors += 1

    @property
    def load(self) -> tuple[int, int]:
        """Least-loaded sort key: (inflight, lifetime dispatched)."""
        with self._lock:
            return (self.inflight, self.dispatched)

    # -- health dial (explorer-style: consecutive failures, dial timing) --

    def dial(self, timeout: float = 2.0) -> bool:
        t0 = time.monotonic()
        try:
            if _faults.ACTIVE:
                # chaos: an unreachable/refusing peer as the monitor sees
                # it — the injected raise is a failed dial, exactly like a
                # real partition (keyed by replica id so a schedule can
                # partition one peer)
                _faults.apply("fleet.dial", key=self.id)
            ok = self._dial(timeout)
        except Exception:  # noqa: BLE001 — a dial failing IS the signal
            ok = False
        self.dial_seconds = round(time.monotonic() - t0, 4)
        self.checked_mono = time.monotonic()
        if ok:
            self.failures = 0
            if self.state in (STARTING, RESPAWNING):
                self.state = HEALTHY
        else:
            self.failures += 1
        return ok

    def idle_s(self) -> float:
        """Seconds since the last request was dispatched here (or since
        boot, for a replica that never served) — the autoscale policy's
        scale-in/scale-to-zero signal. 0 while anything is in flight: a
        slow generation is work, not idleness."""
        with self._lock:
            if self.inflight > 0:
                return 0.0
            return max(0.0, time.monotonic() - self.last_dispatch_mono)

    def snapshot(self) -> dict:
        with self._lock:
            inflight, dispatched = self.inflight, self.dispatched
            errors = self.errors
            last_wall = self.last_dispatch_wall
            idle = (0.0 if inflight > 0
                    else max(0.0, time.monotonic() - self.last_dispatch_mono))
        return {
            "id": self.id,
            "role": self.role,
            "state": self.state,
            "inflight": inflight,
            "dispatched": dispatched,
            "errors": errors,
            "idle_s": round(idle, 1),
            "last_dispatch": last_wall,
            "dial_failures": self.failures,
            "dial_seconds": self.dial_seconds,
            "checked_age_s": (
                round(time.monotonic() - self.checked_mono, 1)
                if self.checked_mono is not None else None),
            "age_s": round(time.monotonic() - self.started_at, 1),
            # worker replicas: the device their LoadModel reported
            "device": getattr(self, "device", None) or None,
        }

    # -- transport (subclass responsibility) -------------------------------

    def start(self) -> None:
        raise NotImplementedError

    def _dial(self, timeout: float) -> bool:
        raise NotImplementedError

    def predict_stream(self, opts: Any, trace_id: str = "",
                       tenant: str = "") -> Iterator:
        raise NotImplementedError

    def prefill_prefix(self, opts: Any, trace_id: str = "") -> Iterator:
        raise NotImplementedError

    def transfer_prefix(self, chunks: Iterator, trace_id: str = "",
                        timeout: Optional[float] = None) -> Any:
        raise NotImplementedError

    def export_cached(self, prompt: list,
                      trace_id: str = "") -> Optional[list]:
        """Sibling-fetch donor half: this replica's ALREADY-CACHED prefix
        rows for ``prompt`` as TransferPrefix chunks, without running any
        prefill — or None when nothing matching is cached. Default None:
        client-backed replicas have no remote cache-peek RPC, so the
        fleet scheduler falls back to ``prefill_prefix`` for them (cheap
        on the donor — its paged prefix pool makes the re-prefill mostly
        block reuse)."""
        return None

    def migrate_out(self, corr_id: str,
                    timeout: float = 30.0) -> Optional[dict]:
        """Live-migration donor half: cancel the in-flight request with
        the KV-export flag set and return ``{"tokens": full token
        record, "generated": n, "chunks": TransferPrefix payload or
        None}`` — or None when the request is unknown here / the kind
        doesn't support migration (client-backed replicas would need a
        dedicated RPC)."""
        return None

    def metrics(self) -> dict:
        raise NotImplementedError

    def telemetry(self, trace_id: str = "", since: float = 0.0,
                  limit: int = 256, recent: int = 20) -> dict:
        """This replica's observability pane (obs.fleetview payload:
        trace spans + flight snapshot + metrics). Never raises — a
        wedged/partitioned replica returns ``{"error", "unreachable"}``
        so the caller degrades that replica's pane, not the endpoint."""
        raise NotImplementedError

    def process_alive(self) -> bool:
        """Cheap no-RPC liveness (worker: process poll)."""
        raise NotImplementedError

    def stop(self) -> None:
        raise NotImplementedError


class _ClientReplica(BaseReplica):
    """Transport shared by every WorkerClient-backed replica (spawned
    worker processes AND adopted remote workers): the streaming dispatch,
    both halves of the disaggregated prefix handoff, bounded stats pulls,
    and the LoadModel handshake. Subclasses own lifecycle (spawn vs dial)
    and set ``self._client``."""

    mcfg = None
    app = None
    _client = None
    device: dict = {}     # what the worker's LoadModel reported it runs on

    def _load_model(self) -> None:
        import yaml

        doc = self.mcfg.model_dump(exclude_none=True, exclude_defaults=True)
        doc["name"] = self.mcfg.name
        doc["model"] = self.mcfg.model or self.mcfg.name
        doc.pop("backend", None)  # the replica itself runs in-process
        res = self._client.load_model(
            config_yaml=yaml.safe_dump(doc),
            model_path=str(self.app.model_path),
        )
        if not res.success:
            raise RuntimeError(
                f"replica {self.id} LoadModel failed: {res.message}")
        from localai_tpu.worker.process import check_worker_device

        self.device = check_worker_device(
            res.message, getattr(self, "_env", None), self.id)

    def _dial(self, timeout: float) -> bool:
        return self._client is not None and self._client.health(timeout)

    def predict_stream(self, opts, trace_id: str = "",
                       tenant: str = "") -> Iterator:
        return self._client.predict_stream(opts, trace_id=trace_id,
                                           tenant=tenant)

    def prefill_prefix(self, opts, trace_id: str = "") -> Iterator:
        return self._client.prefill_prefix(opts, trace_id=trace_id)

    def transfer_prefix(self, chunks, trace_id: str = "",
                        timeout: Optional[float] = None):
        from localai_tpu.fleet import net
        from localai_tpu.worker import backend_pb2 as pb

        def as_protos():
            for c in chunks:
                yield c if not isinstance(c, dict) else pb.PrefixChunk(**c)

        # explicit deadline: the transfer moves bulk KV rows, so it gets
        # headroom (4×) over the per-reply bound — but never hangs a
        # partitioned peer's dispatch thread for the 600 s stream
        # default. The caller (FleetScheduler) passes its CONFIGURED
        # timeout so --fleet-rpc-timeout-s governs this path too; the
        # env read is only the no-caller fallback.
        t = net.rpc_timeout_s() if timeout is None else timeout
        return self._client.transfer_prefix(
            as_protos(), timeout=(t * 4 if t > 0 else 600.0),
            trace_id=trace_id)

    def metrics(self) -> dict:
        try:
            # short deadline: this is the scrape/status path, and a wedged
            # replica must cost seconds, not the full RPC default
            return self._client.metrics(timeout=3.0)
        except Exception as e:  # noqa: BLE001 — stats pull ≠ serving
            return {"error": str(e)}

    def telemetry(self, trace_id: str = "", since: float = 0.0,
                  limit: int = 256, recent: int = 20) -> dict:
        from localai_tpu.fleet import net

        try:
            # the harvest carries the fleet RPC deadline — one bounded
            # pull, no retries: a wedged peer must degrade its pane in one
            # deadline, not three (the read is idempotent; the NEXT pane
            # refresh is the retry)
            t = net.rpc_timeout_s()
            return self._client.get_telemetry(
                trace_id=trace_id, since=since, limit=limit, recent=recent,
                timeout=t if t > 0 else 60.0)
        except Exception as e:  # noqa: BLE001 — telemetry pull ≠ serving
            return {"error": str(e), "unreachable": True}


class WorkerReplica(_ClientReplica):
    """A replica backed by its own spawned gRPC worker process."""

    def __init__(self, rid: str, role: str, mcfg, app,
                 *, env: Optional[dict] = None):
        super().__init__(rid, role)
        self.mcfg = mcfg
        self.app = app
        self._env = dict(env or {})
        self._wp = None
        self._client = None

    def start(self) -> None:
        from localai_tpu.worker.process import WorkerProcess

        self._wp = WorkerProcess(self.id, env=self._env or None)
        self._client = self._wp.start()
        self._load_model()

    def process_alive(self) -> bool:
        return self._wp is not None and self._wp.alive

    def kill(self) -> None:
        """SIGKILL the worker (tests / operator surface)."""
        if self._wp is not None and self._wp.proc is not None:
            self._wp.proc.kill()

    def stop(self) -> None:
        if self._wp is not None:
            self._wp.stop()
            self._wp = None
            self._client = None


class RemoteReplica(_ClientReplica):
    """A replica served by an externally managed worker at ``host:port``
    — another box entirely. Adopted from the static ``LOCALAI_FLEET_HOSTS``
    list or a ``POST /federated/register`` join; this process does NOT own
    the remote's lifecycle, so ``respawnable = False``: on failed dials
    the pool evicts it from routing and redials on backed-off holds
    instead of respawning. ``stop()`` only closes the channel."""

    respawnable = False

    def __init__(self, rid: str, role: str, address: str,
                 mcfg=None, app=None, *, dial_timeout: float = 5.0):
        super().__init__(rid, role)
        self.address = address
        self.mcfg = mcfg
        self.app = app
        self.dial_timeout = dial_timeout
        self._client = None

    def start(self) -> None:
        """Dial (or redial) the remote: a fresh channel, a health gate,
        and — because a redial may find a *rebooted, empty* worker — a
        Status check that re-issues LoadModel when the peer lost the
        model. Raises when the peer is unreachable; the pool turns that
        into eviction + backed-off redial, never a respawn."""
        from localai_tpu.worker.client import WorkerClient

        if self._client is not None:
            self._client.close()
        self._client = WorkerClient(self.address)
        if not self._client.health(self.dial_timeout):
            raise RuntimeError(
                f"remote replica {self.id} at {self.address} is "
                "unreachable")
        if self.mcfg is not None:
            self._ensure_loaded()

    def _ensure_loaded(self) -> None:
        from localai_tpu.fleet import net
        from localai_tpu.worker import backend_pb2 as pb

        # idempotent status probe: bounded retry absorbs a peer that just
        # came up and is still binding its servicer. NOTE: Status carries
        # no model identity (a worker process holds exactly ONE model),
        # so READY is trusted as "holds THIS pool's model" — the
        # registration layer enforces that a peer is only ever adopted
        # into one model's pool (api.localai.fleet_register refuses an
        # ambiguous join).
        st = net.call_with_retries(
            lambda: self._client.status(timeout=self.dial_timeout),
            rid=self.id, what="status")
        if st.state in (pb.StatusResponse.READY, pb.StatusResponse.BUSY):
            return
        self._load_model()

    def process_alive(self) -> bool:
        """No local process to poll — the health dial is the only truth
        about a peer across a network."""
        return self._client is not None

    def stop(self) -> None:
        if self._client is not None:
            self._client.close()
            self._client = None


class InProcessReplica(BaseReplica):
    """A replica owning a full in-process engine (factory →
    models.manager.ServingModel). The CPU-testable twin of WorkerReplica:
    same opts/reply/chunk schema, no processes, no sockets."""

    def __init__(self, rid: str, role: str, factory):
        super().__init__(rid, role)
        self._factory = factory
        self.sm = None
        self._killed = False
        # correlation id → inner GenHandle while its stream is being
        # pumped: the live-migration surface (migrate_out) finds the
        # in-flight request here. Plain dict: writes are
        # insert/pop-by-key from the dispatch thread, reads are a
        # single get() from the migration caller — GIL-atomic.
        self._streaming: dict = {}

    def start(self) -> None:
        from localai_tpu.fleet.prefix import PrefixCache

        self._killed = False
        self.sm = self._factory()
        # both halves of the disaggregated handoff run through this cache
        # (export at release on prefill replicas, import at admission on
        # decode replicas) — attach it up front; a configured disk cache
        # is layered under it rather than replaced (layer=True)
        self.sm.scheduler.attach_prompt_cache(PrefixCache(
            min_prefix=getattr(self.sm.runner, "prefix_reuse_min", 16)),
            layer=True)

    def _cache(self):
        return self.sm.scheduler.prompt_cache

    def _dial(self, timeout: float) -> bool:
        return (not self._killed and self.sm is not None
                and self.sm.scheduler._thread.is_alive())

    def predict_stream(self, opts, trace_id: str = "",
                       tenant: str = "") -> Iterator:
        from localai_tpu.worker.server import gen_request_from_options

        if self._killed:
            raise RuntimeError(f"replica {self.id} is dead")
        sm = self.sm
        # ``tenant`` is accepted for surface parity and deliberately
        # DROPPED: this engine shares the front door's process, and the
        # fleet dispatch thread already feeds the usage ledger for the
        # front-door request — stamping the inner resubmit too would
        # double-count every fleet token ("whoever stamped the tenant
        # owns the feed", obs.ledger)
        gr = gen_request_from_options(opts, sm, trace_id=trace_id)
        handle = sm.scheduler.submit(gr)
        if gr.correlation_id:
            self._streaming[gr.correlation_id] = handle
        try:
            while True:
                try:
                    # bounded wait so a kill() mid-stream surfaces as a
                    # transport error (exactly like a SIGKILLed worker)
                    # instead of parking on a queue the dead engine thread
                    # will never feed again
                    item = handle._q.get(timeout=0.25)
                except queue.Empty:
                    if self._killed:
                        raise RuntimeError(
                            f"replica {self.id} died mid-stream")
                    continue
                if self._killed:
                    raise RuntimeError(f"replica {self.id} died mid-stream")
                if _faults.ACTIVE:
                    # same chaos surface as the gRPC worker stream: an
                    # injected error/slowdown mid-stream, keyed by the
                    # replica id so a schedule can target one replica
                    _faults.apply("worker.stream", key=self.id)
                if item.finish_reason is not None:
                    yield _Reply(b"", handle.completion_tokens,
                                 handle.prompt_tokens, item.finish_reason)
                    break
                if item.delta:
                    yield _Reply(item.delta.encode("utf-8"))
        finally:
            if gr.correlation_id:
                self._streaming.pop(gr.correlation_id, None)
            if handle.finish_reason is None:
                handle.cancel()

    def prefill_prefix(self, opts, trace_id: str = "") -> Iterator:
        from localai_tpu.fleet.prefix import export_prefix, pack_chunks
        from localai_tpu.worker.server import gen_request_from_options

        if self._killed:
            raise RuntimeError(f"replica {self.id} is dead")
        sm = self.sm
        gr = gen_request_from_options(opts, sm, trace_id=trace_id)
        prompt, arrays = export_prefix(sm, gr, self._cache())
        yield from pack_chunks(prompt, arrays)

    def transfer_prefix(self, chunks, trace_id: str = "",
                        timeout: Optional[float] = None):
        # timeout accepted for surface parity with the client-backed
        # kinds; an in-process import has no wire to bound
        from types import SimpleNamespace

        from localai_tpu.fleet.prefix import import_prefix

        if self._killed:
            raise RuntimeError(f"replica {self.id} is dead")
        n = import_prefix(self._cache(), chunks)
        return SimpleNamespace(success=True, message=f"{n} rows")

    def export_cached(self, prompt: list,
                      trace_id: str = "") -> Optional[list]:
        from localai_tpu.fleet.prefix import pack_chunks

        if self._killed or self.sm is None:
            return None
        cache = self._cache()
        if cache is None:
            return None
        hit = cache.lookup(list(prompt))
        # the LCP winner must be a TRUE prefix of the prompt: lookup can
        # return an entry that diverges past the common prefix, and its
        # arrays cover the entry's rows, not the LCP
        if hit is None or list(hit.tokens) != list(prompt)[:len(hit.tokens)]:
            return None
        return list(pack_chunks(hit.tokens, hit.arrays,
                                transfer_id=trace_id))

    def migrate_out(self, corr_id: str,
                    timeout: float = 30.0) -> Optional[dict]:
        from localai_tpu.fleet.prefix import pack_chunks

        ih = self._streaming.get(corr_id)
        if ih is None or self._killed or self.sm is None:
            return None
        # flag first, then cancel: the engine's release reads the flag,
        # keeps the generated tail, and snapshots prompt+generation KV
        # into this replica's prefix cache (scheduler._release)
        ih.migrate_export = True
        ih.cancel()
        try:
            ih.result(timeout)
        except TimeoutError:
            return None
        full = list(ih.request.prompt) + list(ih.token_ids)
        out = {"tokens": full, "generated": len(ih.token_ids),
               "chunks": None}
        cache = self._cache()
        if cache is None or len(full) < cache.min_prefix:
            return out  # nothing exportable: destination re-prefills
        # the export lands off-thread (prompt-cache writer); the stored
        # key is the full token record (migration keeps the generation)
        arrays = cache.wait_for(full, timeout=min(timeout, 10.0))
        tokens = full
        if arrays is None:
            # context-cap edge (or a racing store): take the longest
            # cached true prefix instead — the destination re-prefills
            # only the uncovered tail
            hit = cache.lookup(full)
            if hit is not None and list(hit.tokens) == full[:len(hit.tokens)]:
                tokens, arrays = list(hit.tokens), hit.arrays
        if arrays is not None:
            out["chunks"] = list(pack_chunks(tokens, arrays))
        return out

    def metrics(self) -> dict:
        if self.sm is None:
            return {"error": "not started"}
        return self.sm.scheduler.metrics()

    def telemetry(self, trace_id: str = "", since: float = 0.0,
                  limit: int = 256, recent: int = 20) -> dict:
        # same payload builder the gRPC servicer uses (obs.fleetview), so
        # the wire and in-process panes cannot drift. NOTE: in-process
        # engines share the front door's trace STORE — the stitcher
        # dedupes harvested traces it already holds locally.
        from localai_tpu.obs.fleetview import telemetry_payload

        if self._killed or self.sm is None:
            return {"error": f"replica {self.id} is dead",
                    "unreachable": True}
        try:
            payload = telemetry_payload(
                self.sm.scheduler, trace_id=trace_id, since=since,
                limit=limit, recent=recent)
            # the stitcher must dedupe ONLY panes that share the caller's
            # store: request ids are per-process counters, so a worker's
            # "model-0" legitimately coexists with the front door's —
            # only an in-process replica's traces are literally the same
            # records
            payload["shared_store"] = True
            return payload
        except Exception as e:  # noqa: BLE001 — telemetry pull ≠ serving
            return {"error": str(e), "unreachable": True}

    def process_alive(self) -> bool:
        return self._dial(0.0)

    def kill(self) -> None:
        """Simulate a replica crash: in-flight streams raise, dials fail,
        the engine thread stops (tests / failover drills)."""
        self._killed = True
        if self.sm is not None:
            self.sm.scheduler.shutdown(timeout=2.0)

    def stop(self) -> None:
        if self.sm is not None:
            self.sm.scheduler.shutdown()
            self.sm = None
