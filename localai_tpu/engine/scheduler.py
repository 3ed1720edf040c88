"""Continuous-batching scheduler: the serving loop over ModelRunner.

TPU-era redesign of llama.cpp's slot engine (`update_slots`, task queue and
deferred-task handling — /root/reference/backend/cpp/llama/
grpc-server.cpp:1546-1990, utils.hpp:192-357):

  * requests queue on the host; a single engine thread admits them into free
    slots (prefill) and then advances ALL active slots with one compiled
    decode step per iteration — continuous batching is slot masking inside a
    static-shape program, not ragged batch rebuilds.
  * per-request streams: each request owns a thread-safe queue of text
    deltas; SSE writers drain it without touching the engine thread.
  * stop handling: EOS ids, stop strings (with split-across-tokens holdback),
    max_tokens, context exhaustion (slot released at n_ctx — parity with the
    reference's no-context-shift policy, grpc-server.cpp:1573-1592).
  * grammar constraints: an optional per-request TokenConstraint advances an
    FSM on the host and writes a -1e30 mask row into the device bias before
    the next step (see localai_tpu.functions for the FSM compiler).
  * metrics: per-slot prompt/generated token counts and tokens/sec — the
    GetMetrics surface (grpc-server.cpp:2434-2457).
"""

from __future__ import annotations

import dataclasses
import itertools
import logging
import os
import queue
import threading
import time
from collections import deque
from typing import Any, Optional, Protocol, Sequence

import numpy as np
from jax.profiler import TraceAnnotation

from localai_tpu.engine.runner import NAN_TOKEN, ModelRunner
from localai_tpu.engine.stream import IncrementalDetokenizer, StopChecker
from localai_tpu.faults import registry as _faults
from localai_tpu.obs import anatomy as obs_anatomy
from localai_tpu.obs import flight as obs_flight
from localai_tpu.obs import ledger as obs_ledger
from localai_tpu.obs import profiler as obs_profiler
from localai_tpu.obs import watchdog as obs_watchdog
from localai_tpu.obs.engine import EngineTelemetry

log = logging.getLogger(__name__)


def _ema(prev: Optional[float], sample: float) -> float:
    """The smoothing of the scheduler's per-dispatch timings (step seconds,
    host seconds): a fifth of each new sample."""
    return sample if prev is None else 0.8 * prev + 0.2 * sample


# a decode row is SLOW (localai_slow_dispatch_total) when the engine thread's
# wall for it, idle left out, is over SLOW_FACTOR times what its steps take
# by the step EMA and SLOW_EXCESS_MS more than that: a pause a stream's
# reader sees, well clear of a long step
SLOW_FACTOR = 4.0
SLOW_EXCESS_MS = 50.0
SLOW_OWNERS = ("wait", "cpu", "runq", "blocked")


class _EngineAbandoned(Exception):
    """Raised inside a fenced-off engine thread (its epoch was bumped by
    a rebuild while it sat in a blocked round-trip): exit without
    touching the rebuilt engine's state."""


# admission lanes: interactive requests (API traffic with a client
# waiting) are admitted strictly before background batch work — a batch
# line only fills a slot when no interactive request is queued, so
# offline jobs soak idle capacity without touching interactive TTFT.
PRIORITY_INTERACTIVE = 0
PRIORITY_BATCH = 1


class TokenConstraint(Protocol):
    """Grammar/JSON-schema constraint driven by the scheduler.

    ``allowed_mask`` returns a [V] f32 additive bias row (0 allowed, -1e30
    disallowed) or None for "anything"; ``advance`` consumes the sampled
    token; ``done`` means the constrained region is complete.
    """

    def allowed_mask(self) -> Optional[np.ndarray]: ...
    def advance(self, token_id: int) -> None: ...
    @property
    def done(self) -> bool: ...


@dataclasses.dataclass
class GenRequest:
    """One generation request (the scheduler-facing request schema)."""

    prompt: list[int]
    max_new_tokens: int = 256
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    min_p: Optional[float] = None
    repeat_penalty: Optional[float] = None
    presence_penalty: Optional[float] = None
    frequency_penalty: Optional[float] = None
    seed: Optional[int] = None
    logit_bias: Optional[dict[int, float]] = None
    stop: Sequence[str] = ()
    ignore_eos: bool = False
    constraint: Optional[TokenConstraint] = None
    correlation_id: str = ""
    # tracing: groups this request's lifecycle spans with the HTTP span
    # that spawned it (obs subsystem); crosses the worker RPC boundary as
    # gRPC metadata (worker.rpc.trace_metadata)
    trace_id: str = ""
    # usage accounting (obs.ledger): the derive_tenant() bucket of the
    # request's API key — NEVER the raw key. Non-empty means "feed the
    # cost ledger at the terminal event"; crosses the worker RPC boundary
    # as gRPC metadata (worker.rpc.tenant_metadata)
    tenant: str = ""
    # an SSE client is attached: the scheduler bounds delivery lag by
    # shrinking the per-dispatch step count while this request is active
    stream: bool = False
    # multimodal injection: image-embedding rows [n_mm, D] scattered over
    # placeholder token positions [n_mm] during prefill (see ModelRunner)
    mm_embeds: Optional[Any] = None
    mm_positions: Optional[Any] = None
    # admission lane: PRIORITY_BATCH requests queue on the background lane
    # and are admitted only when the interactive lane is empty
    priority: int = PRIORITY_INTERACTIVE


class StreamItem:
    """Sentinel-free stream element: text delta or end-of-stream marker."""

    __slots__ = ("delta", "token_id", "finish_reason")

    def __init__(self, delta: str, token_id: Optional[int],
                 finish_reason: Optional[str]):
        self.delta = delta
        self.token_id = token_id
        self.finish_reason = finish_reason


class GenHandle:
    """Per-request handle: iterate deltas (streaming) or join for the full
    result. Filled by the engine thread."""

    def __init__(self, req: GenRequest, rid: int):
        self.request = req
        self.id = rid
        self._q: "queue.Queue[StreamItem]" = queue.Queue()
        self.text = ""
        self.token_ids: list[int] = []
        self.finish_reason: Optional[str] = None
        self.prompt_tokens = len(req.prompt)
        self._done = threading.Event()
        self.cancelled = False
        # perf (parity: per-slot timings grpc-server.cpp:1650,1661)
        self.t_submit = time.monotonic()
        self.t_first_token: Optional[float] = None
        self.t_done: Optional[float] = None
        # lifecycle trace (obs.RequestTrace), attached by the scheduler
        self.trace = None
        # live-migration export flag (fleet.kveconomy): a migration
        # cancels the request but still needs its prompt+generation KV
        # snapshotted into the prompt cache at release — set by the
        # replica's migrate_out before cancel()
        self.migrate_export = False
        # NaN-guard receipt: set by Scheduler._poisoned just before the
        # error release, so the ledger classifies the waste as
        # nan_quarantine instead of a generic error
        self.nan_poisoned = False
        # global admission order (engine thread stamps it in _start):
        # lane-ordering tests and forensics read it; None until admitted
        self.admit_index: Optional[int] = None

    # engine-thread side -------------------------------------------------
    def _emit(self, delta: str, token_id: Optional[int]) -> None:
        if self.t_first_token is None:
            self.t_first_token = time.monotonic()
        if token_id is not None:
            self.token_ids.append(token_id)
        if delta:
            self.text += delta
        if delta or token_id is not None:
            self._q.put(StreamItem(delta, token_id, None))

    def _finish(self, reason: str) -> None:
        self.finish_reason = reason
        self.t_done = time.monotonic()
        self._q.put(StreamItem("", None, reason))
        self._done.set()

    # consumer side ------------------------------------------------------
    def cancel(self) -> None:
        """Request cancellation; the engine releases the slot on next step."""
        self.cancelled = True

    def __iter__(self):
        while True:
            item = self._q.get()
            yield item
            if item.finish_reason is not None:
                return

    def result(self, timeout: Optional[float] = None) -> "GenHandle":
        if not self._done.wait(timeout):
            raise TimeoutError(f"request {self.id} not finished")
        return self

    @property
    def completion_tokens(self) -> int:
        return len(self.token_ids)

    @property
    def tokens_per_second(self) -> float:
        if self.t_first_token is None:
            return 0.0
        end = self.t_done or time.monotonic()
        dt = end - self.t_first_token
        return (len(self.token_ids) - 1) / dt if dt > 0 else 0.0


@dataclasses.dataclass
class _PendingPrefill:
    """A chunked paged admission in flight: the engine loop dispatches one
    chunk per iteration (interleaved with decode dispatches) until the
    final chunk samples the first token and arms the slot on the device;
    the token itself comes back through the pipeline (``_Dispatch.first``)."""

    slot: int
    handle: GenHandle
    adm: Any                 # engine.runner.PagedAdmission
    base: Optional[np.ndarray]
    mask_set: bool


@dataclasses.dataclass
class _Dispatch:
    """One dispatch whose result the host has not read: its copy to the
    host started at launch, and the engine loop reads them in the order
    they were launched."""

    toks: Any                # the device array the host will read
    seq: int                 # dispatch counter at launch
    k: int                   # decode steps; 0 = a speculative window
    pipelined: bool          # launched behind another dispatch
    t_issue: float
    fresh: bool              # first dispatch of its shape: pays a compile
    # a final prefill chunk (``toks`` its one sampled token, k = 0): the
    # admission whose first token this is. With k = 1 the chunk rode the
    # step (``Scheduler._launch_ride``): ``toks`` is the step's [S] tokens
    # and the first token behind them (a routed model's count of the launch
    # behind both, as behind any step's or final chunk's)
    first: Optional[_PendingPrefill] = None
    # what the launch held, counted when it was enqueued and written with
    # its row at the drain (``Scheduler._launch``)
    held: Optional[dict] = None


@dataclasses.dataclass
class _SlotCtx:
    """Host-side state for one occupied slot."""

    handle: GenHandle
    detok: IncrementalDetokenizer
    stopper: StopChecker
    generated: int = 0
    base_bias: Optional[np.ndarray] = None  # [V] row from logit_bias
    mask_set: bool = False                  # constraint mask currently on device
    admit_seq: int = 0                      # dispatch counter at admit time:
                                            # tokens from dispatches issued
                                            # before admission are not ours


class Scheduler:
    """Owns one ModelRunner + tokenizer; runs the engine thread."""

    def __init__(self, runner: ModelRunner, tokenizer: Any,
                 *, default_max_tokens: int = 2048, pipeline_depth: int = 2,
                 multi_step: int = 16, stream_latency_target: float = 0.1,
                 spec: Optional[Any] = None,
                 prompt_cache: Optional[Any] = None,
                 prompt_cache_all: bool = False,
                 telemetry: Optional[EngineTelemetry] = None,
                 watchdog: Optional[obs_watchdog.Watchdog] = None,
                 flight: Optional[obs_flight.FlightRecorder] = None):
        self.runner = runner
        self.tokenizer = tokenizer
        # request-lifecycle spans + engine histograms (obs subsystem); the
        # manager names it after the model, tests may inject their own
        self.telemetry = telemetry or EngineTelemetry()
        # the ledger's KV-block-seconds unit follows this runner's actual
        # paged block size (contiguous runners keep the 16-token default)
        self.telemetry.kv_block_tokens = getattr(runner, "block_tokens", 16)
        # stall watchdog: every blocking device round-trip this engine
        # makes (drain here, syncs inside the runner) is heartbeat-guarded;
        # no progress past the deadline → engine_stalled gauge + a
        # thread-stack forensic span (obs.watchdog). The runner shares the
        # instance so "device" and "engine" channels trip together.
        self.watchdog = watchdog or obs_watchdog.WATCHDOG
        runner.watchdog = self.watchdog
        self._wd_channel = (f"engine:{self.telemetry.model}"
                            if self.telemetry.model else "engine")
        self.watchdog.start()
        # flight recorder: one per-dispatch record from every drain, all
        # host mirrors this thread already holds (zero device syncs, no
        # per-record allocation — the ring is preallocated numpy columns).
        # Windowed step-time percentiles come from here; snapshots ride
        # every stall dump via the watchdog context provider below.
        self.flight = (flight if flight is not None
                       else obs_flight.FlightRecorder())
        self._tokens_emitted = 0      # host-side token counter (_consume)
        self._flight_mark = 0         # emitted count at the last record
        self.watchdog.add_context(
            f"flight:{self._wd_channel}", self._flight_forensics
        )
        # anomaly profiler: the ring is watched (weakly) for step-time
        # p99 regressions against its own trailing window — a no-op dict
        # insert unless LOCALAI_PROFILE_ON_ANOMALY armed the manager
        obs_profiler.PROFILER.watch_flight(
            self.telemetry.model or "engine", self.flight)
        # speculative decoding (localai_tpu.spec.SpecEngine): when set and
        # no grammar constraint is active, dispatches run draft+verify
        # windows instead of plain multi-step decode — on BOTH KV layouts
        # (the paged verify writes through the block-table mirror into
        # speculation blocks reserved at admission). Slot lifecycle ops
        # route through the spec engine so the drafter's state mirrors the
        # target's. After any non-speculative dispatch (or a chunked
        # admission, which bypasses spec.admit) the drafts are stale —
        # _spec_dirty forces a per-slot resync before the next window. A
        # drafter may decline a window (self-drafting with no lookup hit
        # anywhere): that dispatch falls back to plain multi-step decode.
        self.spec = spec
        self._spec_dirty = False
        # slots admitted through the chunked path whose drafter seeding
        # is pending — resynced individually (a full-batch resync per
        # admission would cost O(slots) draft prefills for model
        # drafters)
        self._spec_stale: set[int] = set()
        self._engine = spec if spec is not None else runner
        # disk prompt-KV persistence (engine.promptcache): looked up when the
        # in-memory resident record can't cover the prompt; finished slots
        # store their prefix back (prompt only, or prompt+generation with
        # prompt_cache_all). Parity: backend_config.go:120-122.
        self.prompt_cache = prompt_cache
        self.prompt_cache_all = prompt_cache_all
        # stores run off-thread: the engine thread only enqueues a device
        # snapshot (cheap slice dispatches); the writer does the blocking
        # D2H copy + npz write so completions never stall the decode loop
        self._pc_queue: "queue.Queue[Optional[tuple]]" = queue.Queue()
        self._pc_thread: Optional[threading.Thread] = None
        if prompt_cache is not None and not prompt_cache.read_only:
            self._pc_thread = threading.Thread(
                target=self._pc_writer, name="prompt-cache", daemon=True
            )
            self._pc_thread.start()
        self.default_max_tokens = default_max_tokens
        self.pipeline_depth = max(1, pipeline_depth)
        # tokens decoded per dispatch (lax.scan inside one program): one
        # dispatch and one result fetch per multi_step tokens.
        # Delivery lag ≈ multi_step×pipeline_depth×step-time;
        # when any active request has an SSE stream attached, the dispatch
        # size adapts down (power-of-two steps, so at most log2(multi_step)
        # program variants ever compile): the smallest that keeps the device
        # busy while the host handles a dispatch, and never more than keeps
        # that product under stream_latency_target seconds
        # (_effective_steps). Batch requests keep the full size.
        self.multi_step = max(1, multi_step)
        self.stream_latency_target = stream_latency_target
        self._step_ema: Optional[float] = None   # seconds per decoded token
        # the EMA the newest sample was folded INTO: what a row is slow
        # against (its own sample has already moved ``_step_ema``)
        self._step_ema_prior: Optional[float] = None
        # host seconds per pipelined decode dispatch: the flight record's
        # gap + sched + launch (what the device does not wait for)
        self._host_ema: Optional[float] = None
        self._last_drain_t: Optional[float] = None
        # dispatch-anatomy accumulators (obs.anatomy): measured host-phase
        # seconds since the LAST flight record, taken-and-reset by
        # _take_anat() at each record. Engine-thread-only scratch.
        self._anat_sched_s = 0.0    # admit/select/host-mirror spans
        self._anat_launch_s = 0.0   # async jit call-return spans
        self._anat_overlap_s = 0.0  # wall other records already account
        # measured PARTS of what gap holds (obs.anatomy.PARTS), accumulated
        # the same way: token processing, the loop's own bookkeeping, and
        # the drop of a drained dispatch's result
        self._anat_process_s = 0.0
        self._anat_book_s = 0.0
        self._anat_free_s = 0.0
        # the engine thread's own clocks (obs.flight.ThreadClock): built on
        # that thread at the start of each ``_run``
        self._clock: Optional[obs_flight.ThreadClock] = None
        # slow decode rows by the state that owned most of the row
        self.slow_dispatches = dict.fromkeys(SLOW_OWNERS, 0)
        self.last_dispatch_steps = 0             # observability + tests
        # program shapes already dispatched once: the FIRST dispatch of a
        # new step count includes XLA trace+compile time, which must not be
        # folded into the per-token EMA (one multi-second compile sample
        # would pin the adaptive size at 1 for a long recovery)
        self._seen_shapes: set = set()
        # chunked prefill (paged runners): admissions queue their prompt
        # chunks here and the engine loop interleaves ONE chunk per
        # iteration with decode dispatches, so a long prompt never stalls
        # other slots' TPOT. Paged spec engines chunk too — the drafter
        # is seeded from the resident record once the final chunk lands.
        self._chunked = bool(getattr(runner, "paged", False))
        self._prefills: "deque[_PendingPrefill]" = deque()
        self.total_prefill_chunks = 0
        # ... of them, a prompt's small last chunks that were ONE launch with
        # the decode step behind them (``_launch_ride``)
        self.total_chunk_rides = 0
        # chunk launches by the row parts they ran behind the attend
        # (``chunk_parts`` of the flight ring: 1 a whole bucket, 2 to 4 the
        # live quarters of a prompt's last)
        self.total_chunk_parts: dict[int, int] = {}
        # what an admission costs the device besides its prefill: the
        # admissions made, and the times the admission path read the device
        # and WAITED (a frontier read to pick a slot, a first token the
        # host had to see before the next dispatch); the programs it
        # launched are the runner's count (``admit_programs``)
        self.total_admissions = 0
        self.total_admit_blocking_reads = 0
        # passes over the layer stack a forward makes (a looped decoder's
        # ``num_passes``; 1 for every other model) and those dispatched so
        # far: the flight ring's ``passes`` column, ``loop_passes`` below
        self._passes_per_forward = int(getattr(
            getattr(runner, "cfg", None), "num_passes", 1))
        self.total_loop_passes = 0
        # a model with routed experts (models.qwen3_next, models.afmoe)
        # counts each launch's routed work on the device and sends it behind
        # the sampled tokens (``_routed``); these are the lifetime sums, and
        # for a model with recurrent state the slots armed with it zeroed
        cfg = getattr(runner, "cfg", None)
        self._recurrent = bool(getattr(cfg, "recurrent", False))
        self._routed_model = bool(getattr(cfg, "routed", False))
        # keys a window layer's query sees (``LlamaConfig.sliding_window``;
        # 0: no layer has a window): what ``window_tokens`` cuts a stream's
        # context to
        self._window = int(getattr(cfg, "sliding_window", 0) or 0)
        # a model with latent attention (models.deepseek): launches by the
        # FORM of the attention their program computes, counted at the
        # enqueue (a decode program the absorbed one, a chunk the
        # decompressed one); None for every other model
        self.total_mla_attends = ({"absorbed": 0, "decompressed": 0}
                                  if getattr(cfg, "latent", False) else None)
        # a model whose full layers select the rows they attend by an
        # indexer (models.dots3): rows a decode step SCORED (every cached
        # token's index key) and rows it ATTENDED (the ``index_topk`` best),
        # a full layer each, counted at the enqueue; None for every other
        self._index_topk = int(getattr(cfg, "index_topk", 0) or 0)
        self._index_layers = int(getattr(cfg, "full_layers", 0) or 0)
        self.total_dsa_rows = ({"scored": 0, "attended": 0}
                               if self._index_topk else None)
        # a model whose streams attend a selection of their blocks once
        # their context has a dense length (``LlamaConfig.select_blocks``):
        # the first position whose row selects; 0 for every other
        self._select_from = int((getattr(cfg, "select_blocks", None)
                                 or (0, 0, 0))[2])
        self.total_experts_touched = 0
        self.total_local_assignments = 0
        self.total_state_slots_armed = 0
        # a request the paged block pool couldn't cover yet: admission is
        # FIFO, so it parks here (not back in the queue) until blocks free
        self._held: Optional[GenHandle] = None
        # two-lane admission: interactive requests drain strictly before
        # the background batch lane (see _next_pending)
        self._pending: "queue.Queue[GenHandle]" = queue.Queue()
        self._pending_batch: "queue.Queue[GenHandle]" = queue.Queue()
        self._admit_seq = 0
        self._slots: dict[int, _SlotCtx] = {}
        self._ids = itertools.count()
        self._wake = threading.Event()
        self._stopping = False
        self._lock = threading.Lock()
        self._dispatch_seq = 0
        # serving programs launched, one count a program (decode, a prefill
        # chunk, a speculative window): the flight row's ``launch`` and, for
        # a decode or prefill program, the host trace's ``sched.launch/<n>``.
        # Not ``_dispatch_seq``, which chunks do not move (``admit_seq``,
        # the quarantine release)
        self._launch_seq = 0
        # self-healing (faults.supervisor): rebuild() bumps _epoch so a
        # wedged engine thread — parked inside a device round-trip that
        # may never return — is fenced off and exits harmlessly when (if)
        # it unblocks, while a fresh thread takes over the re-initialized
        # runner state. rebuild()/mark_failed() run ONLY on the
        # supervisor's single recovery thread (its _recovering flag is
        # the serialization point), which owns the engine structures
        # exactly while the fenced thread is parked — the same single-
        # owner-thread design the engine loop itself uses. failed latches
        # after the supervisor exhausts its bounded rebuild attempts:
        # submit() then fails fast and the manager's dead-engine reload
        # path owns further recovery.
        self._epoch = 0
        self.failed = False
        self.rebuilds = 0
        self.supervisor = None          # set by EngineSupervisor
        # NaN/inf decode guard: a slot whose logits row went non-finite
        # fails only its own request and is quarantined (kept out of
        # admission) for a fixed number of dispatches — a transient blip
        # returns the slot to service, a poisoned cache region keeps
        # erroring visibly instead of silently corrupting co-batched
        # streams. Counters feed localai_nan_rows_total.
        self._quarantined: dict[int, int] = {}  # slot -> release dispatch
        self.nan_rows = 0
        try:
            self._nan_quarantine = int(os.environ.get(
                "LOCALAI_NAN_QUARANTINE_DISPATCHES", "16") or 16)
        except ValueError:
            self._nan_quarantine = 16
        # block-leak invariant sweep (engine.paged.check_invariants) on
        # every drain — debug builds and the chaos harness only; the
        # O(blocks) walk is too hot for production dispatch cadence
        self._kv_check = os.environ.get("LOCALAI_KV_CHECK", "") == "1"
        self.kv_invariant_violations = 0
        # per-slot resident tokens (prompt + generated) for KV prefix reuse
        self._resident: dict[int, list[int]] = {}
        # lifetime metrics (GetMetrics parity)
        self.total_prompt_tokens = 0
        self.total_generated_tokens = 0
        self.total_preemptions = 0  # cancelled / engine-error slot exits
        # requests refused by SLO admission control (API-level 429s); a
        # mirror for the JSON metrics surface — the registry counter is
        # owned by obs.slo (single-writer rule, see update_engine_gauges)
        self.shed_total = 0
        self._thread = threading.Thread(
            target=self._run, args=(0,), name="engine", daemon=True
        )
        self._thread.start()

    # -- public API ------------------------------------------------------

    def submit(self, req: GenRequest) -> GenHandle:
        handle = GenHandle(req, next(self._ids))
        handle.trace = self.telemetry.queued(handle)
        # failed-check and enqueue are one atomic step vs mark_failed()'s
        # terminal queue drain (which flips the flag under the same lock
        # BEFORE draining): a submit can land in the queue only while the
        # drain is still obligated to pop it — no handle is ever parked
        # on a dead engine unresolved
        with self._lock:
            rejected = self.failed
            if not rejected:
                lane = (self._pending_batch
                        if req.priority >= PRIORITY_BATCH
                        else self._pending)
                lane.put(handle)
        if rejected:
            # the supervisor exhausted its rebuild budget: fail fast with
            # a clean error instead of queueing onto a dead engine
            self.telemetry.finished(handle.trace, handle, "error",
                                    preempted=False)
            handle._finish("error")
            return handle
        self._wake.set()
        return handle

    def generate(self, req: GenRequest, timeout: float = 600.0) -> GenHandle:
        return self.submit(req).result(timeout)

    def attach_prompt_cache(self, prompt_cache: Any,
                            *, layer: bool = False) -> None:
        """Attach a prompt-KV cache after construction (fleet replicas get
        an in-memory PrefixCache lazily, on first PrefillPrefix/
        TransferPrefix use — see localai_tpu.fleet.prefix). No-op when a
        cache is already wired — unless ``layer=True`` and the existing
        cache lacks the store-signalling surface the disaggregation
        export blocks on (``wait_for``): then the new cache FRONTS it
        (``fallthrough``), so a configured disk prompt cache keeps
        working while the fleet handoff gets its RAM tier. Starts the
        off-thread writer for writable caches, exactly as __init__ would
        have. Safe while the engine thread runs: its reads are a single
        attribute load, and the new cache only affects admissions/
        releases that start after the set."""
        if prompt_cache is None:
            return
        if self.prompt_cache is not None:
            if not layer or hasattr(self.prompt_cache, "wait_for"):
                return
            prompt_cache.fallthrough = self.prompt_cache
            self.prompt_cache = prompt_cache
        else:
            self.prompt_cache = prompt_cache
        if not self.prompt_cache.read_only and self._pc_thread is None:
            self._pc_thread = threading.Thread(
                target=self._pc_writer, name="prompt-cache", daemon=True
            )
            self._pc_thread.start()

    @property
    # lock-free liveness poll: every term is an atomic read of an engine-
    # thread-owned structure; worker Status tolerates a one-iteration lag
    def busy(self) -> bool:  # jaxlint: disable=lock-guarded-attr
        return (bool(self._slots) or bool(self._prefills)
                or self._held is not None
                or not self._pending.empty()
                or not self._pending_batch.empty())

    def note_shed(self) -> None:
        """Record one SLO admission-control rejection against this engine
        (called by the API tier when it 429s a request for this model)."""
        with self._lock:
            self.shed_total += 1

    def metrics(self) -> dict:
        """Live engine metrics (parity: GetMetrics RPC,
        grpc-server.cpp:2434-2457).

        ``step_time_ema`` is SECONDS PER DECODED TOKEN (per-token, not
        per-dispatch — a k-step dispatch contributes dt/k), the lifetime
        smoothed estimate that drives the adaptive streaming dispatch
        size. ``step_ms_p50``/``step_ms_p99`` are its windowed
        counterparts in milliseconds, computed from the flight ring's
        resident dispatches (compile-bearing first dispatches excluded);
        None until a post-compile dispatch lands."""
        num_slots = self.runner.num_slots
        pct = self.flight.percentiles()
        anat = obs_anatomy.summarize(self.flight)
        with self._lock:
            active = [
                {
                    "slot": s,
                    "prompt_tokens_processed": c.handle.prompt_tokens,
                    "tokens_generated": c.handle.completion_tokens,
                    "tokens_per_second": c.handle.tokens_per_second,
                    "correlation_id": c.handle.request.correlation_id,
                }
                for s, c in self._slots.items()
            ]
            # alloc.stats() inside is host-side allocator accounting,
            # not a worker RPC — the name-based heuristic misreads it
            kv_utilization = self._kv_utilization()  # jaxlint: disable=blocking-under-lock
            batch_slots = sum(
                1 for c in self._slots.values()
                if c.handle.request.priority >= PRIORITY_BATCH
            )
            # tokens the pool holds that no window layer can read any more:
            # each live stream's context past the window, in whole blocks
            bt = getattr(self.runner, "block_tokens", 1)
            window_dead = sum(
                max(c.handle.prompt_tokens + c.generated - self._window, 0)
                // bt * bt for c in self._slots.values())
            # capture the lifetime counters under the same lock: a scrape
            # must not interleave half-updated totals from a mid-dispatch
            # engine iteration
            totals = {
                "prompt": self.total_prompt_tokens,
                "generated": self.total_generated_tokens,
                "preemptions": self.total_preemptions,
                "shed": self.shed_total,
                "failed": self.failed,
            }
        paged_stats = {}
        alloc = getattr(self.runner, "allocator", None)
        if alloc is not None:
            st = alloc.stats()
            paged_stats = {
                "kv_block_tokens": self.runner.block_tokens,
                # kernel-impl receipt ("pallas" | "pallas_interpret" |
                # "lax"): feeds the localai_paged_kernel_impl series, so
                # which implementation serves is dashboard-visible (and
                # the interpreter can never pass for the compiled kernel)
                "paged_attn_impl": (
                    "lax"
                    if getattr(self.runner, "paged_attn_impl", "") !=
                    "pallas"
                    else "pallas_interpret"
                    if getattr(self.runner, "_paged_attn_interpret", False)
                    else "pallas"),
                # who writes a decode step's rows ("kernel" | "scatter"):
                # the localai_paged_kv_write_impl series
                "paged_kv_write_impl": getattr(
                    self.runner, "paged_kv_write_impl", "scatter"),
                "kv_dtype": str(self.runner.kv_dtype),
                "kv_blocks_total": st.total,
                # free = immediately free + reclaimable prefix-pool cache
                "kv_blocks_free": st.free + st.cached,
                "kv_blocks_used": st.used,
                "kv_blocks_cached": st.cached,
                "kv_block_watermark": st.high_watermark,
                "kv_blocks_spec_reserved": st.spec_reserved,
                "kv_overcommit_ratio": getattr(
                    self.runner, "kv_overcommit", 1.0),
                "kv_shared_tokens": alloc.shared_tokens_total,
                "prefill_chunks": self.total_prefill_chunks,
                "chunk_rides": self.total_chunk_rides,
                "prefill_chunk_parts": dict(self.total_chunk_parts),
                "prefill_chunk_queue_depth": sum(
                    p.adm.chunks_remaining for p in list(self._prefills)
                ),
            }
            ts = alloc.tier_stats()
            if ts is not None:
                paged_stats.update({
                    "kv_tier_blocks": ts["entries"],
                    "kv_tier_bytes": ts["bytes"],
                    "kv_tier_budget_bytes": ts["budget_bytes"],
                    "kv_tier_spills": ts["spills_total"],
                    "kv_tier_reloads": ts["reloads_total"],
                })
        return {
            "active_slots": active,
            "num_slots": num_slots,
            "occupancy": len(active) / num_slots if num_slots else 0.0,
            "kv_utilization": kv_utilization,
            **paged_stats,
            "queue_depth": self._pending.qsize(),
            "batch_queue_depth": self._pending_batch.qsize(),
            "batch_slots": batch_slots,
            "total_prompt_tokens": totals["prompt"],
            "total_generated_tokens": totals["generated"],
            "prefix_tokens_reused": self.runner.total_prefix_reused,
            # the admission path (engine-thread counters, read unlocked
            # like the flight ring's): blocking reads and programs are
            # set against admissions by whoever reads them
            "admissions": self.total_admissions,
            "admit_blocking_reads": self.total_admit_blocking_reads,
            "admit_programs": getattr(self.runner, "admit_programs", 0),
            "loop_passes": self.total_loop_passes,
            **({"moe_experts_touched": self.total_experts_touched,
                "moe_assignments": self.total_local_assignments}
               if self._routed_model else {}),
            **({"state_slots_armed": self.total_state_slots_armed,
                "state_bytes": self.runner.state_bytes,
                **self._snapshot_counts()}
               if self._recurrent else {}),
            **({"kv_window_dead_tokens": window_dead}
               if self._window else {}),
            **({"mla_attends": dict(self.total_mla_attends)}
               if self.total_mla_attends is not None else {}),
            **({"dsa_rows": dict(self.total_dsa_rows)}
               if self.total_dsa_rows is not None else {}),
            "last_dispatch_steps": self.last_dispatch_steps,
            "dispatches": self._dispatch_seq,
            "preemptions": totals["preemptions"],
            "shed_total": totals["shed"],
            # self-healing + NaN-guard surface (faults subsystem)
            "engine_state": "failed" if totals["failed"] else "serving",
            "rebuilds": self.rebuilds,
            "nan_rows": self.nan_rows,
            "quarantined_slots": len(self._quarantined),
            "kv_invariant_violations": self.kv_invariant_violations,
            "step_time_ema": self._step_ema,  # seconds per decoded token
            "step_ms_p50": pct["step_ms_p50"],
            "step_ms_p99": pct["step_ms_p99"],
            # dispatch anatomy (obs.anatomy): windowed host/device
            # attribution over the same ring the step percentiles read
            "host_overhead_fraction": anat["host_overhead_fraction"],
            "dispatch_phase_ms": obs_anatomy.phase_quantiles(anat),
            # the engine thread's seconds by state over every row written
            # (the ring's totals), and its slow decode rows by owner
            "engine_thread_seconds": {
                st: ms * 1e-3
                for st, ms in self.flight.thread_ms_total.items()},
            "slow_dispatches": dict(self.slow_dispatches),
            **(
                {"prompt_cache": self.prompt_cache.stats()}
                if self.prompt_cache is not None else {}
            ),
            **(
                {"spec_acceptance_rate": self.spec.acceptance_rate,
                 "spec_windows": self.spec.total_windows,
                 "spec_accept_rate": self.spec.accept_rate,
                 "spec_draft_tokens": self.spec.total_proposed,
                 "spec_accepted_tokens": self.spec.total_accepted,
                 "spec_tokens_per_dispatch": self.spec.tokens_per_dispatch,
                 "spec_suppressed": self.spec.total_suppressed,
                 "spec_drafter": self.spec.drafter.name,
                 "spec_gamma": self.spec.gamma}
                if self.spec is not None else {}
            ),
        }

    def _kv_utilization(self) -> float:  # jaxlint: disable=lock-guarded-attr
        """Fraction of KV capacity holding live context. Paged runners
        report block-pool utilization (used / allocatable blocks — the
        allocator's own accounting, reservation included); contiguous
        runners keep the row-level estimate from the host token record.
        Caller must own ``_slots`` — hold ``_lock`` or be the engine
        thread (the only mutator)."""
        alloc = getattr(self.runner, "allocator", None)
        if alloc is not None:
            return alloc.stats().utilization
        num_slots = self.runner.num_slots
        max_ctx = self.runner.max_ctx
        if not num_slots:
            return 0.0
        kv_rows = sum(
            min(c.handle.prompt_tokens + c.generated, max_ctx)
            for c in self._slots.values()
        )
        return kv_rows / (num_slots * max_ctx)

    def _pc_writer(self) -> None:
        """Writer loop: materialize KV snapshots and persist them."""
        while True:
            item = self._pc_queue.get()
            if item is None:
                return
            tokens, snapshot = item
            try:
                self.prompt_cache.store(
                    tokens, self.runner.pack_prefix(snapshot)
                )
            except Exception as e:  # noqa: BLE001 — cache ≠ serving
                log.warning("prompt-cache store failed: %s", e)

    def _take_anat(self, dt: float, sync_s: float,
                   ) -> dict:  # jaxlint: disable=lock-guarded-attr
        """Take-and-reset the anatomy accumulators into phase ms for a
        record accounting the wall interval ``dt`` (seconds).

        Clamp order is by trust: the measured ``sync`` block first, then
        the measured ``launch`` spans, then accumulated ``sched`` (which
        may predate a non-pipelined record's issue→drain interval and is
        crowded out rather than stealing from measured phases), then the
        wall other records already account (``overlap`` — prefill-chunk
        records inside this interval must not double count as gap).
        ``gap`` is the remainder, so gap+sched+launch+sync <= dispatch_ms
        holds structurally for every record. Engine thread only."""
        wall = max(0.0, dt)
        sync = min(max(0.0, sync_s), wall)
        launch = min(self._anat_launch_s, wall - sync)
        sched = min(self._anat_sched_s, wall - sync - launch)
        overlap = min(self._anat_overlap_s, wall - sync - launch - sched)
        gap = max(0.0, wall - sync - launch - sched - overlap)
        self._anat_sched_s = 0.0
        self._anat_launch_s = 0.0
        self._anat_overlap_s = 0.0
        return {"gap_ms": gap * 1e3, "sched_ms": sched * 1e3,
                "launch_ms": launch * 1e3, "sync_ms": sync * 1e3}

    def _take_row(self, end: float, parts: bool = True,
                  ) -> dict:  # jaxlint: disable=lock-guarded-attr
        """Close the interval a record accounts for, AT its end (``end`` is
        ``time.monotonic()`` just read: a drain's end of wait, a chunk's end
        of launch): the engine thread's own clocks for the span since the
        previous record's interval ended (obs.flight ``CLOCK_COLUMNS``), and
        with ``parts`` the three measured parts of gap accumulated in it,
        taken and reset (a chunk's record leaves them to the next decode
        record, like the anatomy accumulators). What the thread does behind
        ``end`` (the tokens' processing, the record itself) is the next
        interval's, on every clock alike."""
        taken = self._clock.take(end)
        taken["process_ms"] = taken["book_ms"] = taken["free_ms"] = 0.0
        if parts:
            taken["process_ms"] = self._anat_process_s * 1e3
            taken["book_ms"] = self._anat_book_s * 1e3
            taken["free_ms"] = self._anat_free_s * 1e3
            self._anat_process_s = 0.0
            self._anat_book_s = 0.0
            self._anat_free_s = 0.0
        return taken

    def _routed(self, rows: np.ndarray,
                held: Optional[dict] = None) -> np.ndarray:
        """Split what a launch's copy brought: the sampled tokens, and behind
        them (a model with routed experts; nothing otherwise) the launch's
        [experts touched, token-expert pairs here] a step, which go to the
        totals and, summed, to the launch's flight row."""
        if not self._routed_model:
            return rows
        rows = rows.reshape(-1, rows.shape[-1])
        touched, pairs = rows[:, -2:].sum(axis=0).tolist()
        self.total_experts_touched += touched
        self.total_local_assignments += pairs
        if held is not None:
            held["experts_touched"] = touched
            held["local_assignments"] = pairs
        return rows[:, :-2]

    def _launch(self, k: int = 0, inflight: Sequence[_Dispatch] = (),
                chunk: bool = False,
                ) -> dict:  # jaxlint: disable=lock-guarded-attr
        """Number the serving program about to be enqueued, and count what
        a decode launch of ``k`` steps holds, NOW: the slots that hold a
        stream, and the cached tokens its steps attend (step j of a stream
        attends its prompt, what it has generated and j more; a window
        layer's call the last ``sliding_window`` of them). A stream
        that ends inside the dispatch stays counted: the device attended
        for it. Host mirrors of the engine thread, as ``_flight_record``'s,
        brought up to the device by what ``inflight`` (launched, not yet
        read) will add to them: a decode dispatch its k tokens for every
        stream armed before it, a final chunk its stream's first token (a
        speculative window's yield is not known before it is read: not
        added). The counts ride to the row under obs.flight's
        WORK_COLUMNS."""
        self._launch_seq += 1
        held = {"launch": self._launch_seq}
        if self.total_mla_attends is not None:
            self.total_mla_attends[
                "decompressed" if chunk else "absorbed"] += 1
        if k:
            live = len(self._slots)
            cached = windowed = selected = sparse = 0
            for c in self._slots.values():
                n = c.handle.prompt_tokens + c.generated
                for d in inflight:
                    if d.first is not None:
                        n += d.first.handle is c.handle
                    # (a chunk that rode a step: the step's token for the
                    # streams armed before it, as any step's)
                    if d.seq > c.admit_seq:
                        n += d.k
                cached += n
                if self._window:
                    windowed += sum(min(n + j, self._window)
                                    for j in range(k))
                if self._index_topk:
                    selected += sum(min(n + j, self._index_topk)
                                    for j in range(k))
                if self._select_from:
                    # step j writes position n + j
                    sparse += sum(n + j >= self._select_from
                                  for j in range(k))
            held["live_slots"] = live
            held["attended_tokens"] = k * cached + live * (k * (k - 1) // 2)
            held["window_tokens"] = windowed
            if self._select_from:
                held["sparse_rows"] = sparse
            if self.total_dsa_rows is not None:
                held["selected_tokens"] = selected
                self.total_dsa_rows["scored"] += (
                    self._index_layers * held["attended_tokens"])
                self.total_dsa_rows["attended"] += (
                    self._index_layers * selected)
        return held

    def _snapshot_counts(self) -> dict:
        """A recurrent model's state snapshots (engine.paged): taken at a
        registered prompt's boundary, restored in front of an admission's
        tail, evicted with their chain's last block."""
        alloc = getattr(self.runner, "allocator", None)
        if alloc is None:
            return {}
        return {"state_snapshots_taken": alloc.snapshots_taken,
                "state_snapshots_restored": alloc.snapshots_restored,
                "state_snapshot_evictions": alloc.snapshot_evictions}

    def _flight_record(self, program: str, steps: int, dt: float,
                       fresh: bool, spec_proposed: int = 0,
                       spec_accepted: int = 0, sync_s: float = 0.0,
                       phases: Optional[dict] = None,
                       held: Optional[dict] = None, *, taken: dict,
                       ) -> None:  # jaxlint: disable=lock-guarded-attr
        """One flight-ring record at a drain point. Everything here is a
        host mirror this (engine) thread already owns — ``_slots`` is only
        mutated on this thread, token counts come from ``_consume`` — so
        the cost is a handful of scalar reads plus one in-place ring row
        write. Called AFTER ``_process_rows`` so occupancy/tokens reflect
        end-of-dispatch state. ``spec_proposed``/``spec_accepted`` are
        THIS dispatch's draft counts (speculative windows only).
        ``sync_s`` is the measured result-fetch block for this drain;
        phase attribution comes from _take_anat unless the caller passes
        a pre-built ``phases`` dict (prefill chunks, whose span must not
        consume the accumulators owed to the next decode record). ``held``
        is the one part that is NOT end-of-dispatch state: what the launch
        held when it was enqueued (``_launch``), carried here. ``taken`` is
        ``_take_row``'s reading at the END of the interval ``dt`` accounts
        for: the thread's clocks, and the measured parts of gap, which are
        clamped here to what this record's gap holds (process first, then
        book, then free)."""
        emitted = self._tokens_emitted
        num_slots = self.runner.num_slots
        batch_slots = sum(
            1 for c in self._slots.values()
            if c.handle.request.priority >= PRIORITY_BATCH
        )
        if phases is None:
            phases = self._take_anat(dt, sync_s)
        # a decode dispatch runs a forward a step; a chunk or a speculative
        # window (whose ``steps`` is its yield) runs one
        forwards = int(steps) if program.startswith("decode") else 1
        passes = forwards * self._passes_per_forward
        self.total_loop_passes += passes
        gap_ms = phases["gap_ms"]
        process_ms = min(taken["process_ms"], gap_ms)
        book_ms = min(taken["book_ms"], gap_ms - process_ms)
        free_ms = min(taken["free_ms"], gap_ms - process_ms - book_ms)
        taken.update(process_ms=process_ms, book_ms=book_ms, free_ms=free_ms)
        if not fresh and program in ("decode", "decode_n", "decode_chunk"):
            self._note_slow(steps, taken)
        self.flight.record(
            program=program,
            steps=steps,
            passes=passes,
            dispatch_ms=dt * 1e3,
            occupancy=len(self._slots) / num_slots if num_slots else 0.0,
            batch_slots=batch_slots,
            queue_depth=self._pending.qsize(),
            kv_utilization=self._kv_utilization(),
            tokens=emitted - self._flight_mark,
            preemptions=self.total_preemptions,
            spec_accept=(self.spec.acceptance_rate
                         if self.spec is not None else None),
            spec_proposed=spec_proposed,
            spec_accepted=spec_accepted,
            gap_ms=phases["gap_ms"],
            sched_ms=phases["sched_ms"],
            launch_ms=phases["launch_ms"],
            sync_ms=phases["sync_ms"],
            compile=fresh,
            **taken,
            **(held or {}),
        )
        self._flight_mark = emitted
        if spec_proposed > spec_accepted:
            # rejected draft tokens are device work the ring never counts
            # as emitted — the waste decomposition's spec_rejected class
            # (a short-lock dict update; safe at drain cadence)
            obs_ledger.LEDGER.note_waste(
                "spec_rejected", tokens=spec_proposed - spec_accepted,
                model=self.telemetry.model or "engine")
        if self._kv_check:
            self._check_kv_invariants()

    def _note_slow(self, steps: int,
                   clock: dict) -> None:  # jaxlint: disable=lock-guarded-attr
        """Count a decode row that took the engine thread far longer than
        its steps take (``SLOW_FACTOR``, ``SLOW_EXCESS_MS``), under the
        state that owned most of it."""
        if not self._step_ema_prior:
            return
        busy = clock["span_ms"] - clock["idle_ms"]
        expected = max(1, steps) * self._step_ema_prior * 1e3
        if busy > SLOW_FACTOR * expected and busy - expected >= SLOW_EXCESS_MS:
            owner = max(SLOW_OWNERS,
                        key=lambda st: clock[f"{st}_ms"] or 0.0)
            self.slow_dispatches[owner] += 1

    def _check_kv_invariants(self) -> None:
        """Debug-flag drain sweep: the block allocator must conserve its
        pool (free + used + cached == total, refcount sanity) after every
        dispatch. Violations log, count, and feed
        localai_kv_invariant_violations_total — they mean a leak."""
        alloc = getattr(self.runner, "allocator", None)
        if alloc is None:
            return
        problems = alloc.check_invariants()
        if problems:
            self.kv_invariant_violations += len(problems)
            self.telemetry.registry.kv_invariant_violations.inc(
                len(problems), model=self.telemetry.model or "engine")
            log.error("KV block invariants violated: %s", problems)

    def _flight_forensics(self) -> dict:
        """Watchdog context provider: the last-N engine timeline attached
        to every ``kind="stall"`` forensic trace (host-only, cheap)."""
        return {
            "channel": self._wd_channel,
            "records": self.flight.snapshot(limit=32),
            **self.flight.percentiles(),
        }

    def shutdown(self, timeout: float = 10.0) -> None:
        self._stopping = True
        self._wake.set()
        if self.supervisor is not None:
            self.supervisor.detach()
        self.watchdog.remove_context(f"flight:{self._wd_channel}")
        obs_profiler.PROFILER.unwatch_flight(
            self.telemetry.model or "engine")
        self._thread.join(timeout)
        if self._pc_thread is not None:
            self._pc_queue.put(None)  # flush: writer drains FIFO first
            self._pc_thread.join(timeout)
            self._pc_thread = None

    # -- self-healing (faults.supervisor drives these) -------------------

    def _fail_handle(self, handle: GenHandle, reason: str = "error",
                     *, preempted: bool = True) -> None:
        self.telemetry.finished(handle.trace, handle, reason,
                                preempted=preempted)
        handle._finish(reason)

    def rebuild(self, probe_timeout: float = 30.0) -> None:
        """Tear down and re-initialize the engine after a suspected-wedged
        dispatch (called by the EngineSupervisor, off-thread, while the
        engine thread is presumed parked inside a device round-trip that
        may never return).

        Sequence: fence the old engine thread off (epoch bump — it exits
        whenever its blocked call returns, without touching the rebuilt
        state), fail every request holding engine state with a clean
        ``error`` (the API tier maps that to a 5xx), re-initialize the
        runner's device state (fresh KV pool / decode state / tables —
        compiled programs survive), verify the device answers with a
        probe dispatch in an abandonable thread, then start a fresh
        engine thread that resumes the still-queued requests. Raises if
        the probe fails or times out — the supervisor escalates.

        Runs ONLY on the supervisor's single recovery thread (its
        ``_recovering`` flag is the serialization point): while the
        fenced engine thread is parked, that thread is the sole owner of
        the engine structures — the same single-owner-thread design the
        engine loop itself uses (``_lock`` still guards the cross-thread
        ``_slots`` views)."""
        if self.spec is not None and not getattr(
                self.spec, "supports_rebuild", False):
            raise RuntimeError(
                "engine rebuild is not supported with this speculative "
                "engine")
        if self._stopping:
            raise RuntimeError("scheduler is shutting down")
        self._epoch += 1
        epoch = self._epoch
        with self._lock:
            failed = list(self._slots.items())
            self._slots.clear()
            self.total_preemptions += len(failed) + len(self._prefills)
        log.warning("engine rebuild: fencing old engine thread "
                    "(epoch %d), draining %d active slots",
                    epoch - 1, len(failed))
        for _slot, ctx in failed:
            self._fail_handle(ctx.handle)
        for pf in list(self._prefills):
            self._fail_handle(pf.handle)
        self._prefills.clear()
        # the held request has no engine state (its reservation is
        # only attempted at admit) — it survives the rebuild and is
        # retried against the fresh pool, like the queued requests
        self._resident.clear()
        self._quarantined.clear()
        self._spec_dirty = False
        self._spec_stale.clear()
        self._last_drain_t = None
        # the fenced thread never exits its wedged guard, so its arm()
        # has no disarm(): drop the channel or the leaked armed count
        # fires a spurious stall (and rebuild) every idle gap forever
        self.watchdog.reset(self._wd_channel)
        self.runner.reinit()
        if self.spec is not None:
            # the drafter's device/host state referenced the old pool —
            # reset it alongside (SpecEngine.reinit)
            self.spec.reinit()
        self._probe(probe_timeout)
        self.rebuilds += 1
        self._thread = threading.Thread(
            target=self._run, args=(epoch,), name="engine", daemon=True
        )
        self._thread.start()
        self._wake.set()

    def _probe(self, timeout: float) -> None:
        """One real admit+release against the rebuilt runner, in a side
        thread so a still-dead device costs ``timeout`` seconds (and an
        abandoned daemon) instead of wedging the supervisor forever."""
        done = threading.Event()
        err: list = []

        def probe() -> None:
            slot = None
            try:
                slot = self.runner.acquire_slot()
                if slot is None:
                    raise RuntimeError("no free slot after reinit")
                self.runner.admit(slot, [1, 2, 3], temperature=0.0)
                self.runner.release(slot)
            except Exception as e:  # noqa: BLE001 — reported to the waiter
                err.append(e)
                if slot is not None:
                    try:
                        self.runner.release(slot)
                    except Exception:  # noqa: BLE001
                        pass
            finally:
                done.set()

        t = threading.Thread(target=probe, name="engine-probe", daemon=True)
        t.start()
        if not done.wait(timeout):
            raise RuntimeError(
                f"probe dispatch made no progress in {timeout}s")
        if err:
            raise RuntimeError(f"probe dispatch failed: {err[0]}")

    def mark_failed(self) -> None:
        """Terminal state: the supervisor exhausted its rebuild budget.
        Every queued/held request resolves with a clean error, future
        submits fail fast, and the engine thread is fenced off; the
        manager's dead-engine reload path owns any further recovery."""
        self._epoch += 1  # fence whatever engine thread still exists
        with self._lock:
            # flag flip and slot collection share the lock submit()'s
            # check-and-enqueue holds: every handle that beat the flip is
            # already in a queue the drain below will pop
            self.failed = True
            failed = list(self._slots.items())
            self._slots.clear()
            self.total_preemptions += len(failed)
        for _slot, ctx in failed:
            self._fail_handle(ctx.handle)
        for pf in list(self._prefills):
            self._fail_handle(pf.handle)
        self._prefills.clear()
        if self._held is not None:
            self._fail_handle(self._held, preempted=False)
            self._held = None
        while True:
            handle = self._next_pending()
            if handle is None:
                break
            self._fail_handle(handle, preempted=False)

    # -- fault injection (chaos harness; no-ops unless armed) ------------

    def _inject_slot_faults(self) -> None:
        """decode.nan site: poison the bias row of the first active slot
        whose correlation/trace id matches an armed spec — its next
        logits row goes NaN on device and the per-row guard must catch
        it. Runs only when faults.ACTIVE (never in production)."""
        with self._lock:
            slots = {s: c.handle.request for s, c in self._slots.items()}
        for slot, req in slots.items():
            key = req.correlation_id or req.trace_id or str(slot)
            spec = _faults.fire("decode.nan", key=key)
            if spec is None:
                continue
            row = np.full(self.runner.cfg.vocab_size, np.nan, np.float32)
            self._engine.set_bias(slot, row)

    def _poisoned(self, slot: int, ctx: _SlotCtx) -> None:
        """The device-side per-row finite guard flagged this slot's
        logits (NAN_TOKEN sentinel in the sampled row): fail ONLY the
        affected request with ``error`` and quarantine the slot for
        ``LOCALAI_NAN_QUARANTINE_DISPATCHES`` dispatches — co-batched
        slots keep streaming untouched."""
        self.nan_rows += 1
        self.telemetry.registry.nan_rows.inc(
            model=self.telemetry.model or "engine")
        log.error(
            "non-finite logits for slot %d (request %s): failing the "
            "request, quarantining the slot for %d dispatches",
            slot, ctx.handle.request.correlation_id or ctx.handle.id,
            self._nan_quarantine)
        # the ledger's waste class for this failure is nan_quarantine,
        # not a generic error — stamp before the release feeds telemetry
        ctx.handle.nan_poisoned = True
        self._release(slot, ctx, "error")
        # _release returned the slot to the free list; pull it back out
        # until the quarantine window passes
        if self._engine.acquire_slot(slot) is not None:
            self._quarantined[slot] = (
                self._dispatch_seq + self._nan_quarantine)

    def _unquarantine(self) -> None:
        for slot, release_at in list(self._quarantined.items()):
            if self._dispatch_seq >= release_at:
                del self._quarantined[slot]
                self._engine.release(slot)
                log.info("slot %d leaves NaN quarantine", slot)

    # -- engine thread ---------------------------------------------------

    def _run(self, epoch: int) -> None:
        """Engine-thread entry: run the loop until shutdown — or until a
        rebuild fences this thread off (``_epoch`` moved past ours while
        we sat in a blocked round-trip), in which case exit silently:
        the replacement thread owns the state now."""
        # this thread's clocks, opened here: ``thread-self`` is whoever opens
        clock = self._clock = obs_flight.ThreadClock()
        try:
            self._run_loop(epoch)
        except _EngineAbandoned:
            log.warning("engine thread (epoch %d) abandoned after rebuild",
                        epoch)
        finally:
            clock.close()

    # the engine thread is the SOLE mutator of _slots/_prefills/etc.;
    # its own lock-free reads here are the single-owner-thread design the
    # class docstring documents (the lock exists for cross-thread viewers)
    def _run_loop(self, epoch: int) -> None:  # jaxlint: disable=lock-guarded-attr
        # Pipelined multi-step decode: each dispatch advances all slots
        # multi_step tokens inside ONE compiled program (lax.scan), up to
        # pipeline_depth dispatches stay in flight, and each result's D2H
        # copy starts immediately (copy_to_host_async). The device never
        # waits for the host round-trip and the dispatch overhead is
        # amortized over multi_step tokens. Grammar constraints need the
        # sampled token on the host before the next dispatch (the FSM mask
        # feeds the next step), so constrained slots
        # run synchronously one token per dispatch — but via the frozen-slot
        # program the UNconstrained slots still ride the same dispatch for
        # multi_step tokens (one tool-call request no longer de-pipelines
        # the whole batch).
        #
        # An admission's first token rides the same pipeline: the final
        # prefill chunk arms its slot ON THE DEVICE, so the decode step
        # that follows is launched behind it at once and the chunk's one
        # sampled token is read here in its turn — after the dispatches
        # that were in flight when the request arrived (their tokens go out
        # on time), before the decode steps launched behind it.
        inflight: deque[_Dispatch] = deque()

        def drain_one() -> None:
            d = inflight.popleft()
            toks, seq, k, pipelined, t_issue, fresh = (
                d.toks, d.seq, d.k, d.pipelined, d.t_issue, d.fresh)
            # the designed drain point: copy_to_host_async started this
            # D2H at dispatch time, so materializing here overlaps with
            # the next dispatch already running on device. Watchdog-guarded:
            # a device that never answers parks this exact line forever, and
            # the stall forensics must say so.
            t_sync = time.monotonic()  # anatomy: the result-fetch block
            waiting = self._clock.enter()
            with self.watchdog.guard(self._wd_channel), \
                    TraceAnnotation("sched.wait_device"):  # waiting, not work
                if _faults.ACTIVE:  # chaos: wedge/raise inside the guard
                    _faults.apply("engine.drain", key=self._wd_channel)
                rows = np.asarray(toks)  # jaxlint: disable=host-sync-in-hot-path
            if self._epoch != epoch:
                # a rebuild replaced this engine while we were parked in
                # the round-trip above — the state is no longer ours
                raise _EngineAbandoned
            now = time.monotonic()
            sync_s = now - t_sync
            # the interval ends here: the thread's clocks are read, and the
            # routed counts split off, under the name the row's write has
            with TraceAnnotation("sched.record"):
                self._clock.leave(waiting, sync_s)
                if d.first is None or k:
                    taken = self._take_row(now)
                rows = self._routed(rows, d.held)
            if d.first is not None and not k:
                # a final prefill chunk: the device has just finished it,
                # so the decode step behind it is timed from here
                self._last_drain_t = now
                t_proc = time.monotonic()
                self._anat_book_s += t_proc - now
                with TraceAnnotation("sched.process"):
                    self._first_token(d.first, int(rows.reshape(-1)[0]))
                t_free = time.monotonic()
                self._anat_process_s += t_free - t_proc
                with TraceAnnotation("sched.free"):
                    del d, toks, rows
                self._anat_free_s += time.monotonic() - t_free
                return
            window = None
            if k == 0 and self.spec is not None:  # speculative window
                window = self.spec.observe_window(rows)
            rode = d.first
            if rode is not None:
                # a chunk rode this step: its first token lies behind the
                # step's [S] (one row: ``_routed`` hands back [1, S + 1])
                rows = rows.reshape(-1)
                rows, first_tok = rows[:-1], int(rows[-1])
            # per-token timing for the adaptive streaming dispatch size:
            # when this dispatch was issued while another was still on the
            # device, the interval between drains is pure device time for
            # its k tokens; otherwise (pipeline_depth=1, or a draining
            # pipeline) issue→drain wall time is the estimate. The first
            # dispatch of a new program shape is skipped — it pays compile.
            if pipelined and self._last_drain_t is not None:
                dt = now - self._last_drain_t
            else:
                dt = now - t_issue
            # a spec window's effective step count is its measured yield:
            # mean emitted tokens per active slot-window this dispatch.
            # With speculation the default lane, these dispatches feed
            # the step-time percentiles and the EMA like any other —
            # excluding them would blind the timeline to the hot path.
            k_eff = k
            if window is not None:
                k_eff = (max(1, round(window["emitted"]
                                      / window["windows"]))
                         if window["windows"] else 0)
            if not fresh and k_eff > 0 and rode is None:
                # (a step that carried a chunk is no sample of a step's time)
                self._observe_step_time(dt / k_eff)
            self._last_drain_t = now
            if rows.ndim == 1:
                rows = rows[None]
            t_proc = time.monotonic()
            self._anat_book_s += t_proc - now
            self._process_rows(rows, seq)
            if rode is not None:
                with TraceAnnotation("sched.process"):
                    self._first_token(rode, first_tok)
            t_book = time.monotonic()
            self._anat_process_s += t_book - t_proc
            with TraceAnnotation("sched.record"):
                phases = self._take_anat(dt, sync_s)
                # (nor is a ride a sample of a step's HOST work: its launch
                # holds the admission's, arming and chunk included. Where the
                # host stands just under the step, as with a family's 96
                # streams, a burst of rides would carry the EMA over it and
                # the next dispatch would be the first of two steps: a program
                # nothing has compiled)
                if not fresh and k > 0 and rode is None:
                    self._observe_host_time(
                        (phases["gap_ms"] + phases["sched_ms"]
                         + phases["launch_ms"]) * 1e-3)
                # flight ring: spec windows carry their yield as steps plus
                # per-dispatch proposed/accepted counts (ROADMAP item 3:
                # accept-rate in the flight ring); compile-bearing
                # dispatches are flagged
                self._flight_record(
                    "spec" if k == 0
                    else "decode_chunk" if rode is not None
                    else ("decode_n" if k > 1 else "decode"),
                    k_eff, dt, fresh,
                    spec_proposed=window["proposed"] if window else 0,
                    spec_accepted=window["accepted"] if window else 0,
                    phases=phases, held=d.held, taken=taken,
                )
            t_free = time.monotonic()
            self._anat_book_s += t_free - t_book
            # the dispatch's result dies HERE and not at the return, behind
            # the last clock read: dropping a device array calls into the
            # runtime, and that call lets go of the GIL, which the stream
            # threads the tokens have just woken then take in turn: a
            # millisecond of a 32-stream batch that had no name (PR 53)
            with TraceAnnotation("sched.free"):
                del d, toks, rows, window, rode
            self._anat_free_s += time.monotonic() - t_free

        while not self._stopping and self._epoch == epoch:
            if _faults.ACTIVE:
                # decode.nan chaos: poison a matching active slot's bias
                # row so its next logits go non-finite — exercising the
                # real device-side guard end to end
                self._inject_slot_faults()
            t_adm = time.monotonic()
            with TraceAnnotation("sched.admit"):
                admitted = self._admit_pending()
            adm_s = time.monotonic() - t_adm
            if admitted and not self._chunked:
                # one-shot admissions dispatch AND sync a full prefill
                # inside _admit_pending — device compute, not host
                # scheduling; overlap keeps it out of the next record's gap
                self._anat_overlap_s += adm_s
            else:
                self._anat_sched_s += adm_s
            # chunked prefill: ONE chunk per loop iteration, so pending
            # chunks and decode dispatches alternate — a long prompt
            # spreads its prefill across the batch's decode cadence
            # instead of stalling it. A prompt's small LAST chunk waits for
            # the plain step this iteration launches and rides it
            # (``_ride_head``): the two are one program
            chunked = False
            ride = self._ride_head()
            if self._prefills and ride is None:
                with TraceAnnotation("sched.prefill_chunk"):
                    chunked, entry = self._step_prefill_chunk()
                if entry is not None:
                    entry.pipelined = bool(inflight)
                    # the host waits for the token only where the next
                    # dispatch needs it, by what the request carries: an
                    # FSM whose mask feeds the next step, a drafter that
                    # proposes from drained history. Everything launched
                    # before the chunk is read first, in order.
                    wait = (entry.first.handle.request.constraint is not None
                            or (self.spec is not None
                                and not self.spec.pipeline_safe))
                    if wait:
                        self.total_admit_blocking_reads += 1
                        while inflight:
                            drain_one()
                    inflight.append(entry)
                    if wait:
                        drain_one()
            if not self._slots:
                self._last_drain_t = None  # idle gap would pollute the EMA
                if inflight:
                    drain_one()
                    continue
                if self._prefills:
                    continue  # no decode work yet — keep chunking
                if not admitted and not chunked:
                    # true idle: the poll spans accumulated above belong
                    # to no future record — drop them
                    self._anat_sched_s = 0.0
                    self._anat_launch_s = 0.0
                    self._anat_overlap_s = 0.0
                    self._anat_process_s = 0.0
                    self._anat_book_s = 0.0
                    self._anat_free_s = 0.0
                    with TraceAnnotation("sched.idle"):
                        t_idle = time.monotonic()
                        waiting = self._clock.enter()
                        self._wake.wait(timeout=0.05)
                        self._clock.leave(
                            waiting, time.monotonic() - t_idle, idle=True)
                    self._wake.clear()
                continue
            try:
                if _faults.ACTIVE:  # chaos: a device dispatch that raises
                    _faults.apply("engine.dispatch", key="decode")

                def constrained_slots() -> set[int]:
                    return {
                        s for s, c in self._slots.items()
                        if c.handle.request.constraint is not None
                    }

                if constrained_slots():
                    # sync mode: drain the pipeline so set_bias updates from
                    # processed tokens apply to the very next dispatch
                    if self.spec is not None:
                        # plain dispatches leave the drafts without KV for
                        # the tokens they decode — resync before next window
                        self._spec_dirty = True
                    while inflight:
                        drain_one()
                    constrained = constrained_slots()
                    if not self._slots or not constrained:
                        continue
                    t_count = time.monotonic()
                    with TraceAnnotation("sched.count"):
                        steps = self._effective_steps(pipelined=False)
                        self._dispatch_seq += 1
                        # every slot waits for its own token, or one step
                        # is all the budget holds: the plain program;
                        # else the others ride ``steps`` tokens frozen-slot
                        plain = (len(constrained) == len(self._slots)
                                 or steps == 1)
                        if plain:
                            program, steps = "decode", 1
                        else:
                            program = "decode_frozen_n"
                            freeze = np.zeros(self.runner.num_slots, bool)
                            freeze[list(constrained)] = True
                        fresh = self._fresh_shape(
                            1 if plain else ("frozen", steps))
                        # a frozen-slot launch writes no decode row: no counts
                        held = self._launch(1 if plain else 0)
                    t0 = time.monotonic()
                    self._anat_book_s += t0 - t_count
                    with TraceAnnotation(f"sched.launch/{held['launch']}"):
                        rows = self._routed(
                            self.runner.step()[None] if plain
                            else self.runner.step_frozen_n(freeze, steps),
                            held)
                    t_proc = time.monotonic()
                    dt = t_proc - t0
                    # the runner waited for the device inside that call: its
                    # wall is the row's wait, as a pipelined row's drain is
                    self._clock.leave(None, self.runner.last_sync_ms * 1e-3)
                    taken = self._take_row(t_proc)
                    # anatomy: the runner split its own wall into
                    # enqueue vs result-fetch — harvest the scratch
                    self._anat_launch_s += self.runner.last_launch_ms * 1e-3
                    if not fresh:
                        self._observe_step_time(dt / steps)
                    self.last_dispatch_steps = steps
                    self._process_rows(
                        rows, self._dispatch_seq,
                        frozen=None if plain else constrained)
                    t_book = time.monotonic()
                    self._anat_process_s += t_book - t_proc
                    with TraceAnnotation("sched.record"):
                        self._flight_record(
                            program, steps, dt, fresh,
                            sync_s=self.runner.last_sync_ms * 1e-3,
                            held=held, taken=taken)
                    self._anat_book_s += time.monotonic() - t_book
                    self._last_drain_t = None  # sync path: drain clock stale
                else:
                    # cheap speculation pre-gate, BEFORE any drain or
                    # resync: suppressed (acceptance backoff) or
                    # no-candidate (n-gram lookup misses everywhere)
                    # dispatches must cost exactly plain pipelined
                    # decode — the whole drain/resync/propose sequence
                    # is only worth paying when a window could land
                    spec_ready = self._spec_ready()
                    if (spec_ready and (self._spec_dirty or self._spec_stale)
                            and inflight):
                        # a resync must see the COMPLETE resident record
                        # — drain the in-flight plain dispatches (and a
                        # freshly armed slot's first token) before
                        # rebuilding drafts
                        drain_one()
                        continue
                    spec_rows = None
                    if spec_ready and self._spec_usable():
                        if not self.spec.pipeline_safe:
                            # host drafters (n-gram lookup) propose from
                            # drained history — the previous window must
                            # be observed before the next proposal, so
                            # spec dispatches serialize for them
                            while inflight:
                                drain_one()
                            if not self._slots:
                                continue
                        t_issue = time.monotonic()
                        # None = the drafter declined (no lookup hit
                        # anywhere) — fall through to plain decode
                        with TraceAnnotation("sched.decode_launch"):
                            spec_rows = self.spec.step_spec_async()
                        # anatomy: proposal + verify enqueue span (host
                        # drafter work rides in launch — documented
                        # caveat); a declined proposal dispatched nothing,
                        # so its host work is scheduling, not launch
                        if spec_rows is not None:
                            self._anat_launch_s += (
                                time.monotonic() - t_issue)
                        else:
                            self._anat_sched_s += (
                                time.monotonic() - t_issue)
                    if spec_rows is not None:
                        self._dispatch_seq += 1
                        # a window takes its number once it is enqueued (a
                        # declined draft leaves none unused); no reader
                        # joins a window to its execution, so no
                        # ``sched.launch/<n>`` names it
                        held = self._launch()
                        fresh = self._fresh_shape("spec")
                        self.last_dispatch_steps = self.spec.gamma + 1
                        try:
                            spec_rows.copy_to_host_async()
                        except AttributeError:
                            pass
                        # k=0 marks a spec window: rows carry SKIP
                        # sentinels; the drain folds the real token yield
                        # into the flight ring + step-time EMA
                        inflight.append(_Dispatch(
                            spec_rows, self._dispatch_seq, 0,
                            bool(inflight), t_issue, fresh, held=held))
                        while len(inflight) >= self.pipeline_depth:
                            drain_one()
                        continue
                    if self.spec is not None:
                        self._spec_dirty = True
                    t_count = time.monotonic()
                    with TraceAnnotation("sched.count"):
                        # (``_ride_head`` has asked: a ride is one step)
                        steps = 1 if ride else self._effective_steps()
                        self._dispatch_seq += 1
                        fresh = self._fresh_shape(
                            ("ride", ride.adm.ride_bucket) if ride else steps)
                        held = self._launch(steps, inflight)
                    t_issue = time.monotonic()
                    self._anat_book_s += t_issue - t_count
                    with TraceAnnotation("sched.decode_launch"):
                        with TraceAnnotation(
                                f"sched.launch/{held['launch']}"):
                            if ride:
                                tokens = self._launch_ride(ride, held)
                            elif steps > 1:
                                tokens = self.runner.step_n_async(steps)
                            else:
                                tokens = self.runner.step_async()
                        self.last_dispatch_steps = steps
                        try:
                            tokens.copy_to_host_async()
                        except AttributeError:
                            pass
                    # anatomy: async enqueue span (jit call + D2H start)
                    self._anat_launch_s += time.monotonic() - t_issue
                    inflight.append(_Dispatch(
                        tokens, self._dispatch_seq, steps,
                        bool(inflight), t_issue, fresh, first=ride,
                        held=held))
                    # with a final chunk's entry in the queue this reads
                    # more than one: the step that was in flight at the
                    # arrival (on time), then the chunk's token as soon as
                    # the chunk is done, the step just launched queued
                    # behind it on the device
                    while len(inflight) >= self.pipeline_depth:
                        drain_one()
            except _EngineAbandoned:
                raise
            except Exception:  # noqa: BLE001 — engine must not die silently
                if self._epoch != epoch:
                    # a rebuild raced this dispatch; the new engine owns
                    # the slots — do not fail them from the fenced thread
                    raise _EngineAbandoned
                log.exception("decode step failed; failing active requests")
                inflight.clear()
                with self._lock:
                    failed = list(self._slots.items())
                    self._slots.clear()
                    self.total_preemptions += len(failed)
                for slot, ctx in failed:
                    self._engine.release(slot)
                    self.telemetry.finished(ctx.handle.trace, ctx.handle,
                                            "error")
                    ctx.handle._finish("error")

    def _spec_ready(self) -> bool:
        """The cheap speculation pre-gate, run BEFORE any pipeline drain
        or drafter resync: not backoff-suppressed, and the drafter has a
        proposal candidate for at least one active slot (checked against
        the live resident records — the same data a resync would seed).
        Keeping this ahead of _spec_usable means no-structure traffic
        keeps full plain-decode pipelining and suppressed cooldowns cost
        nothing."""
        if self.spec is None:
            return False
        if self.spec.suppressed_tick():
            return False
        with self._lock:
            residents = {s: self._resident.get(s) for s in self._slots}
        return self.spec.has_candidate(residents)

    def _spec_usable(self) -> bool:
        """Speculative windows require: a spec decoder, every active slot
        far enough from the context edge (a window writes gamma+1 KV rows),
        and fresh drafts (resynced if plain dispatches intervened)."""
        if self.spec is None:
            return False
        gamma = self.spec.gamma
        with self._lock:
            slots = {s: c.handle for s, c in self._slots.items()}
            gen = {s: c.generated for s, c in self._slots.items()}
        for s, h in slots.items():
            if (h.prompt_tokens + gen[s] + gamma + 2
                    >= self.runner.max_ctx):
                return False
        if self._spec_dirty:
            # draft KV is stale for every active slot; rebuild from the
            # resident token record (absent for multimodal slots — wait
            # until those finish)
            if any(self._resident.get(s) is None for s in slots):
                return False
            for s in slots:
                self.spec.resync_draft(s, self._resident[s])
            self._spec_dirty = False
            self._spec_stale.clear()
        elif self._spec_stale:
            # freshly admitted slots only — seed each one individually
            if any(self._resident.get(s) is None
                   for s in self._spec_stale if s in slots):
                return False  # multimodal slot: no token record to seed
            for s in list(self._spec_stale):
                if s in slots:
                    self.spec.resync_draft(s, self._resident[s])
                self._spec_stale.discard(s)
        return True

    def _fresh_shape(self, key) -> bool:
        """True exactly once per program shape — its first dispatch pays
        XLA compile and must not feed the timing EMA."""
        if key in self._seen_shapes:
            return False
        self._seen_shapes.add(key)
        return True

    def _observe_step_time(self, dt: float) -> None:
        """Fold one per-token timing sample into the EMA that drives the
        adaptive streaming dispatch size."""
        if dt <= 0:
            return
        self._step_ema_prior = self._step_ema
        self._step_ema = _ema(self._step_ema, dt)

    def _observe_host_time(self, host_s: float) -> None:
        """Fold one pipelined decode dispatch's host seconds (the flight
        record's gap + sched + launch) into the EMA that says how long a
        dispatch must last on the device to hide the host."""
        self._host_ema = _ema(self._host_ema, host_s)

    def _effective_steps(self, pipelined: bool = True) -> int:
        """Tokens per dispatch for the next dispatch.

        Batch-only traffic takes the full multi_step (throughput). With any
        SSE stream attached, delivery lag ≈ steps×pipeline_depth×step_time
        must stay under stream_latency_target: that budget is a CEILING,
        the step count that fits it quantized DOWN to a power of two
        (bounding the number of distinct compiled decode programs at
        log2(multi_step)+1). On the pipelined path the step count is the
        SMALLEST power of two whose dispatch lasts as long on the device as
        the host spends on one (steps×step_time ≥ host_time, both EMAs of
        post-compile dispatches), never above the ceiling: a longer
        dispatch amortises host work the device never waits for, and every
        arrival waits behind it. So a faster step alone never lengthens the
        dispatch; a host slower than the device does, as far as the budget
        lets it. The synchronous path (``pipelined`` False: constrained
        slots) hides no host work, so the budget alone sizes it. With no
        timing sample yet, streams get single-step dispatches (latency-safe;
        the EMAs fill in from the first post-compile dispatch).
        """
        k = self.multi_step
        if k <= 1:
            return 1
        with self._lock:
            streaming = any(
                c.handle.request.stream for c in self._slots.values()
            )
        if not streaming:
            return k
        if self._step_ema is None:
            return 1
        budget = self.stream_latency_target / max(1, self.pipeline_depth)
        n = int(budget / self._step_ema) if self._step_ema > 0 else k
        ceiling = 1
        while ceiling * 2 <= min(n, k):
            ceiling *= 2
        if not pipelined:
            return ceiling
        p = 1
        while p < ceiling and p * self._step_ema < (self._host_ema or 0.0):
            p *= 2
        return p

    def _next_pending(self) -> Optional[GenHandle]:
        """Two-lane admission pop: the interactive lane drains strictly
        first; a batch request is handed out only when the interactive
        queue depth is zero at this instant — so background work is
        invisible to interactive queue wait by construction."""
        try:
            return self._pending.get_nowait()
        except queue.Empty:
            pass
        try:
            return self._pending_batch.get_nowait()
        except queue.Empty:
            return None

    def _admit_pending(self) -> bool:
        if self._quarantined:
            self._unquarantine()
        admitted = False
        while self._engine.free_slots():
            if self._held is not None:
                if (not self._held.cancelled
                        and not self._reservation_fits(self._held.request)):
                    # still no room — skip the (vocab-row + cache-scan)
                    # admission preamble entirely; this runs
                    # every engine iteration while parked, exactly under
                    # saturation. A cancelled parked request falls through
                    # to the cancelled check below and is dropped now —
                    # it must not keep head-of-line blocking admissions.
                    return admitted
                handle, self._held = self._held, None
            else:
                handle = self._next_pending()
            if handle is None:
                return admitted
            if handle.cancelled:
                # abandoned while still queued: not a slot exit, so it is
                # not a preemption — only requests_total records it
                self.telemetry.finished(handle.trace, handle, "cancelled",
                                        preempted=False)
                handle._finish("cancelled")
                continue
            if not self._reservation_fits(handle.request):
                # block pool can't cover the reservation yet: park the
                # request BEFORE the admission preamble (bias row, prompt
                # cache scan) so saturation
                # costs host arithmetic only. Interactive requests hold
                # their place (FIFO); a batch request goes back to its own
                # lane so it can never block interactive admissions.
                if handle.request.priority >= PRIORITY_BATCH:
                    self._pending_batch.put(handle)
                else:
                    self._held = handle
                return admitted
            # prefer the free slot whose resident tokens share the longest
            # prefix with this prompt (KV prefix-cache reuse); the loop
            # guard guarantees a free slot exists (slot lists are mutated
            # only on this thread). One [S] snapshot of the free slots'
            # frontiers serves the whole ranking + admit — they are frozen
            # until we prefill them, so the snapshot stays valid.
            positions = self._free_frontiers()
            slot = self._engine.acquire_slot(
                self._best_slot(handle.request.prompt, positions)
            )
            assert slot is not None
            try:
                if not self._start(slot, handle, positions):
                    # block pool can't cover the reservation yet: park the
                    # request and stop admitting — finishing slots free
                    # blocks and the loop retries. Interactive requests
                    # hold their place (FIFO); a batch request goes back
                    # to its own lane so it can never block interactive
                    # admissions behind a full pool.
                    self._engine.release(slot)
                    if handle.request.priority >= PRIORITY_BATCH:
                        self._pending_batch.put(handle)
                    else:
                        self._held = handle
                    return admitted
                admitted = True
            except Exception as e:  # noqa: BLE001 — bad request ≠ dead engine
                log.warning("admit failed: %s", e)
                self._engine.release(slot)
                with self._lock:
                    self.total_preemptions += 1
                self.telemetry.finished(handle.trace, handle, "error")
                handle._finish("error")

    def _start(self, slot: int, handle: GenHandle,
               positions: Optional[np.ndarray] = None) -> bool:
        """Admit ``handle`` into ``slot``. Returns False when a paged
        runner's block pool can't cover the reservation right now — the
        caller holds the request (nothing was dispatched or stamped)."""
        req = handle.request
        base = self._padded_vocab_ban()
        if req.logit_bias:
            if base is None:
                base = np.zeros(self.runner.cfg.vocab_size, np.float32)
            # bound by the tokenizer vocab, not the (possibly padded) model
            # vocab — a user bias must not resurrect banned padded ids
            limit = min(
                base.shape[0],
                getattr(self.tokenizer, "vocab_size", None) or base.shape[0],
            )
            for tid, b in req.logit_bias.items():
                if 0 <= int(tid) < limit:
                    base[int(tid)] = b
        mask = (
            req.constraint.allowed_mask() if req.constraint is not None else None
        )
        resident = self._resident.get(slot)
        if positions is None:
            positions = self._free_frontiers()
        valid_n = int(positions[slot])
        rows = getattr(self._engine, "resident_rows", None)
        if rows is not None:
            # paged runners free a slot's blocks at release — only rows
            # just loaded from the disk prompt cache stay reusable
            valid_n = rows(slot, valid_n)
        if self.prompt_cache is not None and req.mm_embeds is None:
            mem_lcp = (
                self._engine.reusable_prefix(slot, resident, req.prompt,
                                             valid_n=valid_n)
                if resident else 0
            )
            hit = self.prompt_cache.lookup(req.prompt)
            # score the disk hit through the same feasibility gates as the
            # in-memory resident (validity = its own row count): a hit whose
            # tail bucket can't fit would admit() as a full prefill, losing
            # in-memory reuse that was available (ADVICE r4)
            disk_lcp = (
                self._engine.reusable_prefix(
                    slot, hit.tokens, req.prompt, valid_n=hit.n)
                if hit is not None else 0
            )
            if (disk_lcp > mem_lcp
                    and self.runner.load_prefix(slot, hit.arrays, hit.n)):
                resident = hit.tokens
                valid_n = hit.n  # load_prefix moved the slot's frontier
        sampling = dict(
            resident=resident,
            valid_n=valid_n,
            temperature=req.temperature,
            top_k=req.top_k,
            top_p=req.top_p,
            min_p=req.min_p,
            repeat_penalty=req.repeat_penalty,
            presence_penalty=req.presence_penalty,
            frequency_penalty=req.frequency_penalty,
            seed=req.seed,
            bias_row=self._compose_bias(base, mask),
            mm_embeds=req.mm_embeds,
            mm_positions=req.mm_positions,
        )
        if self._chunked:
            # reserve the worst case so decode can never run out of blocks
            # mid-flight (preemption-free by construction)
            reserve = (len(req.prompt) + req.max_new_tokens + 1
                       if req.max_new_tokens
                       else len(req.prompt) + self.default_max_tokens + 1)
            adm = self._engine.begin_admit(
                slot, req.prompt, reserve_tokens=reserve, **sampling)
            if adm is None:
                return False
            handle.admit_index = self._admit_seq
            self._admit_seq += 1
            self.total_admissions += 1
            self.total_state_slots_armed += self._recurrent
            self.telemetry.admitted(
                handle.trace, slot=slot,
                queue_wait=time.monotonic() - handle.t_submit,
                background=req.priority >= PRIORITY_BATCH,
            )
            self._prefills.append(_PendingPrefill(
                slot=slot, handle=handle, adm=adm, base=base,
                mask_set=mask is not None,
            ))
            return True
        handle.admit_index = self._admit_seq  # engine thread is sole writer
        self._admit_seq += 1
        self.total_admissions += 1
        # a one-shot admission returns its first token: the host waits
        self.total_admit_blocking_reads += 1
        self.telemetry.admitted(
            handle.trace, slot=slot,
            queue_wait=time.monotonic() - handle.t_submit,
            background=req.priority >= PRIORITY_BATCH,
        )
        first = self._engine.admit(slot, req.prompt, **sampling)
        self.telemetry.prefill_done(
            handle.trace,
            path=self.runner.last_prefill_path,
            prefix_reused=self._engine.last_prefix_reused,
        )
        self._consume(
            slot, self._install_slot(slot, handle, base, mask is not None),
            int(first))
        return True

    def _install_slot(self, slot: int, handle: GenHandle,
                      base: Optional[np.ndarray],
                      mask_set: bool) -> _SlotCtx:
        """The slot's prefill is dispatched to its end (one-shot, or the
        final chunk, which arms the slot on the device): record the
        resident tokens and install the live slot context. Rows of
        dispatches issued up to now are not this request's
        (``admit_seq``); its first token is consumed when it is read."""
        req = handle.request
        # multimodal KV mixes injected embeddings with token ids, so the
        # token record alone can't prove prefix equality — never reuse it.
        # Mirror the runner's empty-prompt normalization ([0]) so the
        # record stays aligned with the cache rows.
        self._resident[slot] = (
            None if req.mm_embeds is not None
            else list(req.prompt) or [0]
        )
        ctx = _SlotCtx(
            handle=handle,
            detok=IncrementalDetokenizer(self.tokenizer.decode),
            stopper=StopChecker(req.stop),
            base_bias=base,
            mask_set=mask_set,
            admit_seq=self._dispatch_seq,
        )
        with self._lock:
            self._slots[slot] = ctx
            self.total_prompt_tokens += handle.prompt_tokens
        if self.spec is not None and self._chunked:
            # chunked paged admissions bypass spec.admit — mark THIS
            # slot's draft stale so the drafter is seeded from the
            # resident record before the next speculative window
            self._spec_stale.add(slot)
        return ctx

    # engine-thread only (called from _run_loop's drain) — see _run_loop
    def _first_token(  # jaxlint: disable=lock-guarded-attr
            self, pf: _PendingPrefill, token_id: int) -> None:
        """A chunked admission's first token is on the host: the end of its
        ``prefill`` span, and the first token its stream consumes."""
        self.telemetry.prefill_done(
            pf.handle.trace,
            path=getattr(pf.adm, "path", "paged"),
            prefix_reused=pf.adm.prefix_reused,
        )
        ctx = self._slots.get(pf.slot)
        if ctx is not None and ctx.handle is pf.handle:
            self._consume(pf.slot, ctx, token_id)

    def _reservation_fits(self, req: GenRequest) -> bool:
        """Host-arithmetic estimate of whether ``req``'s block reservation
        could be allocated right now (pool availability + pool-shareable
        prefix). Slightly optimistic — allocate() stays authoritative —
        so a True merely permits an admission attempt."""
        alloc = getattr(self.runner, "allocator", None)
        if alloc is None or not self._chunked:
            return True
        # spec engines reserve a gamma+1 speculation lookahead on top of
        # the decode worst case (begin_admit spec_tokens) — mirror it here
        # or a full pool would loop begin_admit→None on every iteration
        look = self.spec.gamma + 1 if self.spec is not None else 0
        reserve = min(
            self.runner.max_ctx,
            len(req.prompt) + (req.max_new_tokens
                               or self.default_max_tokens) + 1 + look,
        )
        need = alloc.blocks_for(reserve) - len(alloc.match_prefix(req.prompt))
        return alloc.stats().available >= need

    def _step_prefill_chunk(self) -> tuple[bool, Optional[_Dispatch]]:
        """Dispatch ONE pending prefill chunk (FIFO across admissions).
        Returns (whether anything was done, the final chunk's entry for the
        pipeline). Nothing here waits for the device: a final chunk arms
        its slot there, so the slot's context is installed at once and the
        chunk's sampled token is read in its turn (``_first_token``). The
        flight record tags these dispatches as ``prefill_chunk`` with
        steps=0, keeping them out of the decode step-time percentiles
        while /debug/flight still shows them; it accounts the launch
        alone (``sync_ms`` 0: the wait for a first token, where there is
        one, lies in the engine loop's drain)."""
        if not self._prefills:
            return False, None
        pf = self._prefills[0]
        if pf.handle.cancelled:
            self._prefills.popleft()
            pf.adm.abort()   # frees the blocks, slot returns to free list
            with self._lock:
                self.total_preemptions += 1
            self.telemetry.finished(pf.handle.trace, pf.handle, "cancelled")
            pf.handle._finish("cancelled")
            return True, None
        held = self._launch(chunk=True)
        t0 = time.monotonic()
        with TraceAnnotation(f"sched.launch/{held['launch']}"):
            last = pf.adm.launch_chunk()
        held.update(getattr(pf.adm, "last_chunk", {}))
        entry = None
        if last:
            self._prefills.popleft()
            entry = _Dispatch(pf.adm.first, self._dispatch_seq, 0, False,
                              t0, False, first=pf)
            self._install_slot(pf.slot, pf.handle, pf.base, pf.mask_set)
        t_end = time.monotonic()
        dt = t_end - t0
        taken = self._take_row(t_end, parts=False)
        self._count_chunk(held)
        # anatomy: the admission object measured its own enqueue span; the
        # remainder of THIS span is chunk staging and slot bookkeeping
        # (sched). Pre-built phases so the chunk does not consume
        # accumulators owed to the next decode record — and its whole span
        # becomes overlap there (no double count).
        wall_ms = max(0.0, dt) * 1e3
        launch_ms = min(getattr(pf.adm, "last_launch_ms", 0.0), wall_ms)
        self._flight_record(
            "prefill_chunk", 0, dt, False,
            phases={"gap_ms": 0.0, "sched_ms": wall_ms - launch_ms,
                    "launch_ms": launch_ms, "sync_ms": 0.0},
            held=held, taken=taken)
        self._anat_overlap_s += dt
        # writing the chunk's row is the loop's bookkeeping: it lies in the
        # next decode row's gap, and gets its name there
        self._anat_book_s += time.monotonic() - t_end
        return True, entry

    # engine-thread only (the loop's chunk stage) — see _run_loop
    def _ride_head(  # jaxlint: disable=lock-guarded-attr
            self) -> Optional[_PendingPrefill]:
        """The head admission, where its next chunk will RIDE the decode
        step this iteration launches instead of going out in front of it:
        the chunk is the prompt's last and of at most ``RIDE_ROWS`` rows on
        a runner whose programs can (``PagedAdmission.ride_bucket``), streams
        are decoding, and the launch will be the plain pipelined single
        step: no stream and not this request under a constraint (theirs is
        the synchronous branch, and the host waits for a constrained first
        token), no drafter whose window the launch might be, one step a
        dispatch. Asked ONCE an iteration, of what the loop holds; every
        other case is ``_step_prefill_chunk``'s, as before: a cancelled
        head too, which is dropped there."""
        if not self._prefills or not self._slots or self.spec is not None:
            return None
        pf = self._prefills[0]
        if (pf.handle.cancelled or pf.handle.request.constraint is not None
                or not getattr(pf.adm, "ride_bucket", None)
                or any(c.handle.request.constraint is not None
                       for c in self._slots.values())):
            return None
        return pf if self._effective_steps() == 1 else None

    def _launch_ride(self, pf: _PendingPrefill,
                     held: dict):  # jaxlint: disable=lock-guarded-attr
        """Enqueue the head admission's last chunk and the decode step as
        ONE program (``ModelRunner._decode_prefill_paged_fn``); the device
        array of the step's [S] tokens with the first token behind them.
        What ``_step_prefill_chunk`` does for a final chunk is done here for
        this one: the slot's context is installed at once, behind the
        step's number (the step's row for the new slot is not its stream's:
        ``admit_seq``), and ``held`` takes what the chunk held beside what
        the step did. Its one ring row is written at the drain, as
        ``decode_chunk``."""
        pf.adm.launch_chunk(ride=True)
        # (behind the launch: one that raises leaves the admission queued)
        self._prefills.popleft()
        held.update(pf.adm.last_chunk)
        self._install_slot(pf.slot, pf.handle, pf.base, pf.mask_set)
        self._count_chunk(held)
        self.total_chunk_rides += 1
        return pf.adm.first

    def _count_chunk(self, held: dict) -> None:
        """One more chunk launched, by the row parts it ran behind the
        attend (``held``: what its launch held)."""
        self.total_prefill_chunks += 1
        parts = held.get("chunk_parts", 1)
        self.total_chunk_parts[parts] = self.total_chunk_parts.get(parts, 0) + 1

    def _free_frontiers(self) -> np.ndarray:
        """[S] KV frontier of every free slot. A paged runner answers from
        the host (its free slots hold what ``load_prefix`` put there, else
        nothing); a contiguous one must read the device, which waits for
        every dispatch in flight and is counted."""
        if not self._chunked:
            self.total_admit_blocking_reads += 1
        return self._engine.free_frontiers()

    def _best_slot(self, prompt: list[int],
                   positions: Optional[np.ndarray] = None) -> Optional[int]:
        """Free slot with the longest reusable token prefix (None → FIFO).
        Uses the runner's own feasibility gates so the ranking can't pick a
        slot whose reuse collapses to zero at admit time. ``positions`` is
        the ``_free_frontiers()`` snapshot — passing valid_n explicitly
        keeps this loop free of per-candidate device syncs."""
        if positions is None:
            positions = self._free_frontiers()
        best, best_lcp = None, 0
        for s in self._engine.free_slots():
            r = self._resident.get(s)
            if not r:
                continue
            lcp = self._engine.reusable_prefix(
                s, r, prompt, valid_n=int(positions[s])
            )
            if lcp > best_lcp:
                best, best_lcp = s, lcp
        return best

    def _padded_vocab_ban(self) -> Optional[np.ndarray]:
        """Standing bias banning ids the tokenizer cannot produce or decode.

        Model vocabs are often padded wider than the tokenizer (mesh/MXU
        alignment — e.g. the debug presets pad the 258-id byte tokenizer to
        512); without the ban, sampling can land on a padded id and the
        stream silently emits empty deltas. Returns a fresh [V] row
        (callers mutate it) or None when vocabs already agree."""
        tok_v = getattr(self.tokenizer, "vocab_size", None)
        V = self.runner.cfg.vocab_size
        if not tok_v or tok_v >= V:
            return None
        row = np.zeros(V, np.float32)
        row[tok_v:] = -1e30
        return row

    def _compose_bias(
        self, base: Optional[np.ndarray], mask: Optional[np.ndarray]
    ) -> Optional[np.ndarray]:
        base = self._fit_vocab(base, 0.0)
        # a constraint mask covers the tokenizer's vocab; model vocab may be
        # padded wider (MXU/mesh-aligned) — padded ids are disallowed
        mask = self._fit_vocab(mask, -1e30)
        if base is None:
            return mask
        if mask is None:
            return base
        return base + mask

    def _fit_vocab(
        self, row: Optional[np.ndarray], fill: float
    ) -> Optional[np.ndarray]:
        if row is None:
            return None
        V = self.runner.cfg.vocab_size
        if len(row) == V:
            return row
        out = np.full(V, fill, np.float32)
        out[: min(len(row), V)] = row[:V]
        return out

    # engine-thread only (called from _run's drain path) — see _run
    def _process_rows(  # jaxlint: disable=lock-guarded-attr
        self, rows: np.ndarray, seq: int,
        frozen: Optional[set[int]] = None,
    ) -> None:
        # _slots is authoritative: the runner only deactivates slots when this
        # thread releases them, so no device round-trip for liveness. The seq
        # guard drops tokens from dispatches issued before a slot's admission
        # (pipelined mode re-admits slots while a read is still in flight);
        # it works at dispatch granularity because admissions only happen
        # between dispatches. Rows are consumed in temporal order, so a slot
        # that finishes at row i (removed from _slots) ignores rows i+1..;
        # ``frozen`` slots only advanced on the first step of the dispatch,
        # so only row 0 is theirs.
        with TraceAnnotation("sched.process"):
            for i in range(rows.shape[0]):
                for slot, ctx in list(self._slots.items()):
                    if seq <= ctx.admit_seq:
                        continue
                    if i > 0 and frozen is not None and slot in frozen:
                        continue
                    tok = int(rows[i, slot])
                    if tok == NAN_TOKEN:
                        # per-row NaN/inf guard sentinel: fail THIS request,
                        # quarantine the slot, keep the rest of the batch
                        self._poisoned(slot, ctx)
                        continue
                    if tok < 0:  # SKIP sentinel: spec window ended early
                        continue
                    self._consume(slot, ctx, tok)

    def _consume(self, slot: int, ctx: _SlotCtx, token_id: int) -> None:
        """Handle one sampled token for one slot: stream, stop, constrain."""
        r = self._resident.get(slot)
        if r is not None:
            r.append(token_id)
        handle = ctx.handle
        req = handle.request
        if handle.cancelled:
            self._release(slot, ctx, "cancelled")
            return

        is_eos = (not req.ignore_eos) and token_id in getattr(
            self.tokenizer, "eos_ids", set()
        )
        if is_eos:
            handle._emit(ctx.stopper.flush(), None)
            self._release(slot, ctx, "stop")
            return

        ctx.generated += 1
        self._tokens_emitted += 1  # flight-ring per-dispatch token delta
        delta = ctx.detok.push(token_id)
        safe = ctx.stopper.push(delta)
        handle._emit(safe, token_id)

        if ctx.stopper.stopped is not None:
            self._release(slot, ctx, "stop")
            return

        if req.constraint is not None:
            req.constraint.advance(token_id)
            if req.constraint.done:
                handle._emit(ctx.stopper.flush(), None)
                self._release(slot, ctx, "stop")
                return
            mask = req.constraint.allowed_mask()
            if mask is not None or ctx.mask_set:
                # always refresh when a mask was ever set, so an FSM entering
                # a free-text region (mask=None) clears the stale device mask
                self._engine.set_bias(slot, self._compose_bias(ctx.base_bias, mask))
                ctx.mask_set = mask is not None

        limit = req.max_new_tokens or self.default_max_tokens
        if ctx.generated >= limit:
            handle._emit(ctx.stopper.flush(), None)
            self._release(slot, ctx, "length")
            return
        if handle.prompt_tokens + ctx.generated >= self.runner.max_ctx - 1:
            # context exhausted: finish (no silent context shifting — parity
            # with grpc-server.cpp:1573-1592)
            handle._emit(ctx.stopper.flush(), None)
            self._release(slot, ctx, "length")

    def _release(self, slot: int, ctx: _SlotCtx, reason: str) -> None:
        with self._lock:
            self._slots.pop(slot, None)
            self.total_generated_tokens += ctx.handle.completion_tokens
            if reason in ("cancelled", "error"):
                self.total_preemptions += 1
        migrating = (reason == "cancelled"
                     and getattr(ctx.handle, "migrate_export", False))
        if (self.prompt_cache is not None
                and not self.prompt_cache.read_only
                and (reason in ("stop", "length") or migrating)):
            r = self._resident.get(slot)
            if r:
                # prompt_cache_all keeps generation too; otherwise prompt
                # only. Generated length comes from the host record — no
                # device sync on the engine thread. A migration export
                # always keeps the generation: the destination replica
                # resumes from the full token record's frontier.
                pos = min(len(r) - 1, self.runner.max_ctx - 1)
                keep = (pos if (self.prompt_cache_all or migrating)
                        else min(ctx.handle.prompt_tokens, pos))
                if keep >= self.prompt_cache.min_prefix:
                    try:
                        self._pc_queue.put((
                            list(r[:keep]),
                            self.runner.snapshot_prefix(slot, keep),
                        ))
                    except Exception as e:  # noqa: BLE001 — cache ≠ serving
                        log.warning("prompt-cache snapshot failed: %s", e)
        self._engine.release(slot)
        # retire the trace BEFORE _finish unblocks the client: a traces
        # query racing the response must not see a half-annotated trace
        self.telemetry.finished(ctx.handle.trace, ctx.handle, reason)
        ctx.handle._finish(reason)
        if reason in ("stop", "length") and self.supervisor is not None:
            # a natural completion closes any open incident: the
            # supervisor's bounded rebuild budget refills
            self.supervisor.note_healthy()
