"""Observability subsystem: tracing, telemetry, and introspection.

Cooperating pieces:

  * ``obs.metrics`` — the process-wide OpenMetrics registry (moved here
    from ``api.metrics``, which remains as a compatibility shim) extended
    with engine series: TTFT/TPOT/queue-wait histograms, batch occupancy,
    KV-slot utilization, prompt/prefix-cache hit rates, speculative accept
    rate, XLA compile count/seconds, stall + device-health gauges.
  * ``obs.trace`` — a lock-protected span recorder with a bounded
    ring-buffer trace store. All timestamps are ``time.monotonic()`` taken
    on the host; nothing here ever touches a device array, so
    instrumentation adds zero device syncs to the step loop.
  * ``obs.engine`` — ``EngineTelemetry``, the scheduler-facing facade that
    turns request lifecycle events (queued → admitted → prefill → decode →
    drained) into spans + histogram observations.
  * ``obs.watchdog`` — dispatch-heartbeat stall detection around every
    blocking device round-trip, with thread-stack forensic spans dumped
    into the trace store on a trip (``kind="stall"`` at ``/v1/traces``).
  * ``obs.device`` — timeout-guarded device liveness probe, per-device
    ``memory_stats()`` gauges, and a live-array HBM census (KV cache vs
    weights vs other) behind ``GET /debug/devices``.
  * ``obs.compile`` — XLA compile telemetry plus the compiled-program cost
    catalog (``cost_analysis``/``memory_analysis`` of every watched
    program) behind ``GET /debug/programs``.
  * ``obs.logging`` — structured JSON log formatter with the request
    trace id bound via contextvar by the API middleware.
  * ``obs.flight`` — the engine flight recorder: a lock-light fixed-size
    ring of per-dispatch records (step times, occupancy, queue depth, KV
    utilization, tokens, preemptions, spec acceptance) fed from the
    scheduler drain loop using host mirrors only, with windowed step-time
    percentiles (``GET /debug/flight``; snapshots ride every stall dump).
  * ``obs.slo`` — the SLO observatory: sliding-window TTFT/TPOT/e2e/
    queue-wait percentiles per model (1m/5m/30m), p95 targets from env/
    config, multi-window burn rates, and burn-rate admission control
    (429 + ``Retry-After`` with automatic recovery) behind
    ``GET /v1/slo`` and ``localai_overload_shedding``.
  * ``obs.fleetview`` — the fleet telemetry plane: per-replica
    GetTelemetry harvests (trace spans + flight ring + metrics) stitched
    into one skew-anchored waterfall per trace id
    (``GET /v1/traces/{id}``) and one merged fleet flight table
    (``GET /debug/fleet/flight``).
  * ``obs.profiler`` — anomaly-triggered ``jax.profiler`` capture:
    watchdog stalls, SLO shed onsets, and step-time p99 regressions fire
    a bounded, rate-limited, single-flight capture recorded in a manifest
    (``GET /debug/profiles``, ``localai_profiles_captured_total``).
  * ``obs.ledger`` — the per-tenant cost ledger + goodput/waste
    decomposition: every finished request attributes delivered tokens,
    dispatch milliseconds, queue wait and KV-block-seconds to a
    (tenant, model, lane) pane (tenant = hashed API key, LRU-bounded
    cardinality), and every dispatch's work splits into goodput vs named
    waste classes reconciled against the flight ring
    (``GET /v1/usage``, ``localai_tenant_*``/``localai_goodput_*``/
    ``localai_waste_*``).
  * ``obs.history`` — the multi-resolution metrics history: 1s/10s/5m
    downsampled rings for the key engine + usage series, snapshotted
    atomically under ``LOCALAI_HISTORY_DIR`` and re-onboarded at boot
    (``GET /debug/history/{series}``, the ``/usage`` UI pane).

HTTP surface: ``GET /v1/traces``, ``GET /debug/timeline/{request_id}``
(``api.traces``), ``GET /debug/devices``, ``GET /debug/programs``,
``GET /debug/stacks`` (``api.debug``), fed by the trace-id middleware in
``api.server``.
"""

from localai_tpu.obs.engine import EngineTelemetry
from localai_tpu.obs.flight import FlightRecorder
from localai_tpu.obs.history import HISTORY, History
from localai_tpu.obs.ledger import (
    LEDGER,
    TenantLedger,
    current_tenant,
    derive_tenant,
)
from localai_tpu.obs.metrics import (
    REGISTRY,
    Counter,
    Gauge,
    Histogram,
    Registry,
    escape_label_value,
    update_engine_gauges,
)
from localai_tpu.obs.profiler import PROFILER, ProfileManager
from localai_tpu.obs.slo import SLO, SLOTracker
from localai_tpu.obs.trace import (
    STORE,
    RequestTrace,
    Span,
    TraceStore,
    new_trace_id,
)
from localai_tpu.obs.watchdog import WATCHDOG, StallEvent, Watchdog

__all__ = [
    "HISTORY",
    "LEDGER",
    "PROFILER",
    "REGISTRY",
    "SLO",
    "STORE",
    "WATCHDOG",
    "Counter",
    "EngineTelemetry",
    "FlightRecorder",
    "Gauge",
    "Histogram",
    "History",
    "ProfileManager",
    "Registry",
    "RequestTrace",
    "SLOTracker",
    "Span",
    "StallEvent",
    "TenantLedger",
    "TraceStore",
    "Watchdog",
    "current_tenant",
    "derive_tenant",
    "escape_label_value",
    "new_trace_id",
    "update_engine_gauges",
]
