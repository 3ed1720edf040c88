"""CI telemetry smoke: prove the obs subsystem observes a real generation.

Boots the tiny debug model in-process (no downloads, no HTTP), runs a few
generations through the continuous-batching scheduler, then:

  1. asserts the engine series appear in the /metrics exposition
     (batch occupancy, KV utilization, TTFT/TPOT/queue-wait histograms,
     compile time) — a regression here means the subsystem went blind;
  2. asserts the round-6 introspection surfaces: the device liveness probe
     + HBM census render their gauges, and a SIMULATED stall (a blocking
     callable under a short-deadline watchdog) trips ``engine_stalled``,
     records a thread-stack forensic span, and clears on recovery;
  3. asserts the round-7 SLO observatory + flight recorder: the synthetic
     load leaves a non-empty flight ring with computable step-time
     percentiles, the SLO burn-rate/shedding gauges render, and a
     simulated overload (tight targets against a scratch tracker) trips
     shedding, counts a shed request, then recovers as the fast window
     slides past the burst;
  4. asserts the round-8 offline batch subsystem end-to-end: a 5-line
     JSONL job submitted through the FileRegistry + BatchStore runs to
     terminal ``completed`` through the scheduler's BACKGROUND lane
     (every line at ``PRIORITY_BATCH``), the ``localai_batch_jobs`` /
     ``localai_batch_lines_total`` / ``localai_batch_lane_paused``
     series render, and the per-line result file is written
     (``--batch-out`` — CI uploads it as a build artifact);
  5. asserts the round-10 fleet router end-to-end: a 2-replica (+1
     prefill) in-process fleet of the tiny model serves mixed traffic
     through the affinity router, one long prompt takes the
     disaggregated prefill→TransferPrefix→decode path, and the
     ``localai_fleet_*`` replica/routing/transfer series render;
  6. writes a TTFT/TPOT summary JSON (``--out``) that CI uploads as a
     build artifact — the seed of the serving-latency bench trajectory
     (BENCH_*.json tracks throughput; this tracks latency per PR) — and
     the flight-ring snapshot (``--flight-out``) so every CI run carries
     the engine timeline it measured.

  7. asserts the round-15 fleet telemetry plane end-to-end: a 2-replica
     WORKER-PROCESS fleet serves a mixed tenant workload from
     ``tools.loadgen``, one request's trace renders as ONE stitched
     waterfall (front-door spans untagged, worker-side engine spans
     harvested over the GetTelemetry RPC, skew-anchored and
     ``replica=``-tagged), the merged fleet flight view
     (``--fleet-flight-out``, a CI artifact) carries ≥2 replicas' rings
     with a ``replica`` column, and an injected ``engine.drain`` stall
     auto-captures a jax.profiler trace into the profile manifest
     (``--profile-dir``) with its triggering trace id — while a second
     stall inside the cooldown does NOT capture;

  9. under ``--loopsan``, boots the REAL aiohttp API tier over a
     2-replica in-process fleet of the tiny model and runs it under
     ``tools.loopsan``'s event-loop stall sanitizer: first a deliberate
     ``time.sleep(0.2)`` injected onto the loop must be caught (the
     sanitizer's own self-check — a detector that can't see a 200 ms
     stall proves nothing), then mixed ``tools.loadgen`` HTTP traffic
     plus one live SSE stream must complete with ZERO callbacks holding
     the loop ≥ 50 ms — the runtime proof that the API layer's executor
     offloads (the static loopcheck contract) actually hold under load.
     The stall report lands in ``--loopsan-out`` (a CI artifact);

  8. asserts the round-18 usage accounting plane end-to-end: a 2-replica
     worker-process fleet serves a 3:1 weighted tenant mix, the ledger
     attributes every request to the right HASHED tenant bucket (raw
     names never reach a label), each worker's delivered + flight-class
     waste tokens reconcile against its own flight ring, the history
     store survives a disk snapshot round trip, and the
     ``/v1/usage``-shaped payload lands in ``--usage-out`` (a CI
     artifact);

 11. asserts the round-19 dispatch anatomy: extra ``tools.loadgen``
     traffic through the smoke engine leaves every flight-ring record
     with gap/sched/launch/sync phases summing within its
     ``dispatch_ms`` (the interval-tiling invariant), its measured
     ``process`` + ``book`` + ``free`` parts within its ``gap_ms`` and its thread
     states summing to its ``span_ms``, the derived
     host-overhead fraction in (0, 1), the
     ``localai_dispatch_phase_ms`` / ``localai_host_overhead_fraction``
     series rendering, and the client-observed TTFT p95 agreeing with the server-side histogram;
     the breakdown lands in ``--anatomy-out`` (a CI artifact);

 12. asserts the round-20 elastic capacity loop: a 1-replica autoscaled
     fleet scales OUT under a seeded loadgen spike (queue-depth signal),
     hot-swaps its replicas mid-life with zero failed requests, scales
     to ZERO after the traffic quiesces, and cold-re-onboards a replica
     for the next request — which waits for the boot and completes; the
     capacity trajectory lands in ``--autoscale-out`` (a CI artifact);

 10. under ``--racecheck``, runs the WHOLE lifecycle above with
     ``tools.racecheck``'s instrumented locks installed (every
     ``threading.Lock``/``RLock`` the serving stack creates records its
     acquisition ordering) and fails if the observed lock-order graph
     contains a cycle — an ABBA inversion across the fleet pool/router,
     batch executor, scheduler, and obs planes is a deadlock waiting
     for load, exactly what this smoke's mixed traffic provokes.

Usage:  python -m tools.telemetry_smoke [--out telemetry_summary.json]
                                        [--flight-out flight_snapshot.json]
                                        [--batch-out batch_result.jsonl]
                                        [--usage-out usage_snapshot.json]
                                        [--racecheck]
                                        [--loopsan]
                                        [--loopsan-out loopsan_report.json]
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time


REQUIRED_SERIES = (
    'localai_batch_occupancy{model="smoke"}',
    'localai_kv_slot_utilization{model="smoke"}',
    'localai_ttft_seconds_count{model="smoke"}',
    'localai_tpot_seconds_count{model="smoke"}',
    'localai_queue_wait_seconds_count{model="smoke"}',
    'localai_requests_total{',
    'localai_decode_dispatches_total{model="smoke"}',
    # the smoke engine runs the paged KV cache (the serving default), so
    # prefill compiles under the chunked-prefill program label
    'localai_xla_compile_total{program="prefill_chunk"}',
    'localai_xla_compile_seconds_total{program="decode',
    # paged block-pool gauges (round 9)
    'localai_kv_blocks_free{model="smoke"}',
    'localai_kv_blocks_used{model="smoke"}',
    'localai_prefill_chunk_queue_depth{model="smoke"}',
    'localai_prefill_chunks_total{model="smoke"}',
)
REQUIRED_FAMILIES = (
    "# TYPE localai_prompt_cache_hit_rate gauge",
    "# TYPE localai_speculative_accept_rate gauge",
    "# TYPE localai_prefix_tokens_reused_total counter",
)
# device-health + stall series the smoke provokes explicitly (probe +
# census + a simulated stall) before checking the exposition
REQUIRED_INTROSPECTION = (
    "localai_device_ok 1",
    "localai_device_probe_seconds",
    'localai_hbm_live_bytes{category="kv_cache"}',
    'localai_hbm_live_bytes{category="weights"}',
    'localai_engine_stalled{channel="smoke-stall"} 0',
    'localai_stalls_total{channel="smoke-stall"} 1',
)
# SLO observatory + flight recorder series (round 7): windowed step-time
# percentiles from the ring, burn-rate gauges from the real run, and the
# simulated-overload lifecycle (shed → counted → recovered)
REQUIRED_SLO = (
    'localai_step_time_ms{model="smoke",quantile="p50"}',
    'localai_step_time_ms{model="smoke",quantile="p99"}',
    'localai_slo_burn_rate{model="smoke",window="1m"}',
    'localai_slo_burn_rate{model="smoke",window="5m"}',
    'localai_overload_shedding{model="smoke"} 0',
    'localai_overload_shedding{model="smoke-overload"} 0',
    'localai_requests_shed_total{model="smoke-overload"} 1',
)
# offline batch subsystem series (round 8): the 5-line job the smoke
# submits through the background lane must land every line and leave the
# lane un-paused
REQUIRED_BATCH = (
    'localai_batch_jobs{state="completed"} 1',
    'localai_batch_jobs{state="failed"} 0',
    'localai_batch_lines_total{result="completed"} 5',
    "localai_batch_lane_paused 0",
)
# fleet router series (round 10): the 2-replica in-process fleet the smoke
# boots must leave every replica healthy, a routed mix, and exactly one
# disaggregated prefix transfer (one long prompt crosses the threshold)
REQUIRED_FLEET = (
    'localai_fleet_replicas{model="fleet-smoke",state="healthy"} 3',
    'localai_fleet_replicas{model="fleet-smoke",state="dead"} 0',
    'localai_fleet_routed_total{model="fleet-smoke",reason="affinity"}',
    'localai_fleet_prefix_transfers_total{model="fleet-smoke"} 1',
    'localai_fleet_prefix_transfer_bytes_total{model="fleet-smoke"}',
)
# fleet KV-economy series (round 17): the 2-replica tiered fleet must
# render directory traffic, at least one sibling prefix transfer, and a
# real HBM→host spill→reload round trip (values asserted in-code by
# check_kveconomy; the exposition check pins the series names)
REQUIRED_KVECONOMY = (
    'localai_fleet_directory_entries{model="fleet-kv"}',
    'localai_fleet_directory_hits_total{model="fleet-kv"}',
    'localai_fleet_sibling_transfers_total{model="fleet-kv"}',
    'localai_fleet_sibling_transfer_bytes_total{model="fleet-kv"}',
    'localai_kv_tier_blocks{model="fleet-kv"}',
    'localai_kv_tier_spills_total{model="fleet-kv"}',
    'localai_kv_tier_reloads_total{model="fleet-kv"}',
)
# fleet telemetry plane series (round 15): the worker-process fleet must
# come up healthy, the anomaly profiler must capture EXACTLY one stall-
# triggered profile (the cooldown eats the second), and the trace-ring
# sizing receipt must render
REQUIRED_FLEETVIEW = (
    'localai_fleet_replicas{model="fleet-grpc",state="healthy"} 2',
    'localai_profiles_captured_total{trigger="stall"} 1',
    "localai_trace_ring_size",
)
# usage accounting plane series (round 18): after check_usage exports the
# ledger, the tenant/goodput/waste families must render with HASHED
# tenant buckets only (the in-code check pins the exact t-… series and
# the absence of raw tenant names)
REQUIRED_USAGE = (
    "# TYPE localai_tenant_requests_total counter",
    "# TYPE localai_tenant_tokens_total counter",
    "# TYPE localai_tenant_kv_block_seconds_total counter",
    "# TYPE localai_tenant_lru_evictions_total counter",
    'localai_goodput_tokens_total{model="fleet-usage"}',
    'localai_goodput_ratio{model="fleet-usage"}',
)
# dispatch-anatomy series (round 19): after real traffic through the
# smoke engine, every phase column must render a windowed percentile and
# the derived host fraction must be present (values asserted in-code by
# check_anatomy; the exposition check pins the series names)
REQUIRED_ANATOMY = (
    'localai_dispatch_phase_ms{model="smoke",phase="gap",quantile="p50"}',
    'localai_dispatch_phase_ms{model="smoke",phase="sched",quantile="p50"}',
    'localai_dispatch_phase_ms{model="smoke",phase="launch",quantile="p50"}',
    'localai_dispatch_phase_ms{model="smoke",phase="sync",quantile="p99"}',
    'localai_dispatch_phase_ms{model="smoke",phase="process",quantile="p50"}',
    'localai_dispatch_phase_ms{model="smoke",phase="book",quantile="p50"}',
    'localai_dispatch_phase_ms{model="smoke",phase="free",quantile="p50"}',
    'localai_host_overhead_fraction{model="smoke"}',
    'localai_engine_thread_seconds_total{model="smoke",state="cpu"}',
    'localai_slow_dispatch_total{model="smoke",owner="blocked"}',
)
# elastic-capacity series (round 20): the autoscaled fleet must record a
# spike-driven scale-out, the quiesce-driven scale-to-zero, the cold
# re-onboard that served the held request, and one hot weight swap
# (values asserted in-code by check_autoscale; the exposition check pins
# the series names — labels render alphabetically)
REQUIRED_AUTOSCALE = (
    'localai_autoscale_decisions_total{action="scale_out",'
    'model="fleet-auto"}',
    'localai_autoscale_decisions_total{action="scale_to_zero",'
    'model="fleet-auto"}',
    'localai_autoscale_decisions_total{action="cold_start",'
    'model="fleet-auto"}',
    'localai_autoscale_decisions_total{action="swap",model="fleet-auto"}',
    'localai_fleet_target_replicas{model="fleet-auto"}',
    'localai_model_swaps_total{model="fleet-auto"} 1',
)


def check_introspection(runner, registry, store) -> list[str]:
    """Probe the device, census its HBM, and simulate one stall →
    returns the list of failures (empty = healthy)."""
    import threading

    from localai_tpu.obs import Watchdog
    from localai_tpu.obs import device as obs_device

    problems: list[str] = []
    probe = obs_device.probe_device(timeout=60.0, registry=registry)
    if not probe.ok:
        problems.append(f"device probe failed: {probe.error}")
    obs_device.update_device_gauges([runner], registry=registry)

    wd = Watchdog(deadline=0.1, registry=registry, store=store,
                  poll_interval=0.02)
    wd.start()
    release = threading.Event()
    tripped = threading.Event()
    wd.on_stall(lambda e: e.kind == "stall" and tripped.set())

    def hung():
        with wd.guard("smoke-stall"):
            release.wait(10.0)

    t = threading.Thread(target=hung, daemon=True)
    t.start()
    if not tripped.wait(5.0):
        problems.append("simulated stall did not trip the watchdog")
    release.set()
    t.join(5.0)
    deadline = time.monotonic() + 3.0
    while wd.stalled("smoke-stall") and time.monotonic() < deadline:
        time.sleep(0.02)
    if wd.stalled("smoke-stall"):
        problems.append("stall did not clear on recovery")
    wd.stop()
    forensic = [tr for tr in store.recent(limit=20, kind="stall")
                if tr.attrs.get("channel") == "smoke-stall"]
    if not forensic:
        problems.append("no forensic stall span recorded")
    elif not any("stack" in s.attrs for s in forensic[0].spans()):
        problems.append("forensic span carries no thread stacks")
    return problems


def check_slo_overload(registry) -> list[str]:
    """Simulated overload: a scratch tracker with tight targets sheds,
    counts the refusal, then recovers once the fast window drains —
    the full load-shedding lifecycle without waiting a real minute
    (injected clock)."""
    from localai_tpu.obs.slo import SLOTracker

    problems: list[str] = []
    t = {"now": 1000.0}
    slo = SLOTracker(registry=registry, clock=lambda: t["now"],
                     targets={"ttft_ms": 0.001}, burn_threshold=1.0,
                     recover_burn=1.0, min_events=3)
    for _ in range(4):
        slo.observe("smoke-overload", ttft_ms=50.0, e2e_ms=80.0)
    if not slo.should_shed("smoke-overload"):
        problems.append("simulated overload did not trip shedding")
    if 'localai_overload_shedding{model="smoke-overload"} 1' \
            not in registry.render():
        problems.append("shedding gauge not set during overload")
    slo.shed("smoke-overload")  # what the API's 429 path records
    t["now"] += 120.0           # the fast window slides past the burst
    if slo.should_shed("smoke-overload"):
        problems.append("shedding did not recover after the window slid")
    return problems


def check_batch(sched, registry, batch_out: str) -> list[str]:
    """Submit a 5-line batch job end-to-end through the background lane:
    file upload → job create → executor drain → terminal ``completed`` →
    per-line result file copied to ``batch_out`` (the CI artifact)."""
    import json as jsonlib
    import shutil
    import tempfile
    from pathlib import Path
    from types import SimpleNamespace

    from localai_tpu.batch import BatchExecutor, BatchStore, FileRegistry
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.obs.slo import SLOTracker
    from localai_tpu.templates.cache import TemplateCache
    from localai_tpu.utils.tokenizer import ByteTokenizer

    problems: list[str] = []
    with tempfile.TemporaryDirectory() as tmp:
        reg = FileRegistry(Path(tmp) / "uploads")
        store = BatchStore(reg.upload_dir, reg)
        lines = "\n".join(jsonlib.dumps({
            "custom_id": f"smoke-{i}", "method": "POST",
            "url": "/v1/chat/completions",
            "body": {"model": "smoke", "max_tokens": 8, "temperature": 0.0,
                     "messages": [{"role": "user",
                                   "content": f"batch smoke line {i}"}]},
        }) for i in range(5))
        f = reg.register_bytes("smoke_input.jsonl",
                               (lines + "\n").encode(), "batch")
        job = store.create(endpoint="/v1/chat/completions",
                           input_file_id=f["id"])
        sm = SimpleNamespace(tokenizer=ByteTokenizer(), scheduler=sched,
                             templates=TemplateCache(tmp))
        mcfg = ModelConfig(name="smoke")
        ex = BatchExecutor(
            store, lambda name: (sm, mcfg), poll_s=0.02,
            registry=registry,
            slo=SLOTracker(registry=registry, targets={}),
        )
        ex.start()
        deadline = time.monotonic() + 300
        while (store.get(job["id"])["status"]
               not in ("completed", "failed", "cancelled", "expired")
               and time.monotonic() < deadline):
            time.sleep(0.05)
        ex.stop()
        job = store.get(job["id"])
        if job["status"] != "completed":
            problems.append(
                f"batch job ended {job['status']!r}, not completed "
                f"({job['request_counts']})")
            return problems
        if job["request_counts"]["completed"] != 5:
            problems.append(
                f"batch counts wrong: {job['request_counts']}")
        out_path = reg.content_path(job["output_file_id"])
        records = [jsonlib.loads(l)
                   for l in out_path.read_text().splitlines()]
        if {r["custom_id"] for r in records} != {f"smoke-{i}"
                                                for i in range(5)}:
            problems.append("batch output file misses custom_ids")
        store.export_gauges(registry)
        shutil.copy(out_path, batch_out)
    return problems


def check_fleet(registry) -> list[str]:
    """Boot a 2-replica (+1 prefill) in-process fleet of the tiny debug
    model, run mixed traffic through the router (short prompts +
    one long prompt over the disaggregation threshold), and assert the
    routing/transfer accounting — the localai_fleet_* exposition strings
    are checked by REQUIRED_FLEET after this returns."""
    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.engine.scheduler import GenRequest
    from localai_tpu.fleet import FleetServingModel
    from localai_tpu.fleet.replica import InProcessReplica
    from localai_tpu.models.manager import build_serving_model

    problems: list[str] = []
    app = AppConfig()
    mcfg = ModelConfig.model_validate({
        "name": "fleet-smoke", "model": "debug:tiny", "context_size": 256,
        "parameters": {"temperature": 0.0, "max_tokens": 8},
        "engine": {"max_slots": 2, "prefill_buckets": [16, 32, 64, 128],
                   "dtype": "float32", "kv_dtype": "float32",
                   "kv_block_tokens": 16},
    })

    def factory(rid, role):
        return InProcessReplica(
            rid, role, lambda: build_serving_model(mcfg, app))

    fm = FleetServingModel(mcfg, app, factory, replicas=2,
                           prefill_replicas=1, disagg_threshold=48)
    try:
        tok = fm.tokenizer
        handles = [
            fm.scheduler.submit(GenRequest(
                prompt=tok.encode(f"fleet smoke request {i} " * (1 + i % 2)),
                max_new_tokens=6, temperature=0.0,
            ))
            for i in range(5)
        ]
        # ONE prompt over the disaggregation threshold: prefill replica →
        # TransferPrefix → decode replica
        handles.append(fm.scheduler.submit(GenRequest(
            prompt=tok.encode("fleet disaggregated long prompt " * 6),
            max_new_tokens=6, temperature=0.0,
        )))
        for h in handles:
            h.result(timeout=300)
        bad = [h.finish_reason for h in handles
               if h.finish_reason not in ("stop", "length")]
        if bad:
            problems.append(f"fleet requests finished {bad}")
        if sum(fm.router.routed.values()) != len(handles):
            problems.append(
                f"router placed {sum(fm.router.routed.values())} of "
                f"{len(handles)} requests: {fm.router.routed}")
        if fm.router.routed["affinity"] < 1:
            problems.append(
                f"no affinity placements in {fm.router.routed}")
        if fm.scheduler.prefix_transfers != 1:
            problems.append(
                f"{fm.scheduler.prefix_transfers} prefix transfers "
                f"(expected 1; {fm.scheduler.disagg_fallbacks} fallbacks)")
        if fm.scheduler.prefix_transfer_bytes <= 0:
            problems.append("prefix transfer moved 0 bytes")
        fm.scheduler.export_gauges()
    finally:
        fm.close()
    return problems


def check_kveconomy(registry) -> list[str]:
    """Round-17 fleet KV economy: a 2-replica fleet with a deliberately
    small block pool and the host-RAM tier armed (LOCALAI_KV_TIER_MB)
    serves a tools.loadgen prefix-heavy workload. Asserts the three
    planes end-to-end: the prefix directory takes routing hits, a
    replica loss forces at least one sibling TransferPrefix warm-up on
    the failover path, and prefix-pool pressure drives at least one
    HBM→host spill that a later family re-request reloads. The
    localai_fleet_directory_* / localai_fleet_sibling_* /
    localai_kv_tier_* exposition strings are checked by
    REQUIRED_KVECONOMY after this returns."""
    import os

    from localai_tpu import faults
    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.engine.scheduler import GenRequest
    from localai_tpu.fleet import FleetServingModel
    from localai_tpu.fleet.replica import InProcessReplica
    from localai_tpu.fleet.router import affinity_key
    from localai_tpu.models.manager import build_serving_model
    from localai_tpu.obs.metrics import update_engine_gauges
    from tools.loadgen import PREFIX_PROMPTS, EngineSink, LoadGen, Tenant

    problems: list[str] = []
    prev_tier = os.environ.get("LOCALAI_KV_TIER_MB")
    os.environ["LOCALAI_KV_TIER_MB"] = "8"
    app = AppConfig()
    mcfg = ModelConfig.model_validate({
        "name": "fleet-kv", "model": "debug:tiny", "context_size": 256,
        "parameters": {"temperature": 0.0, "max_tokens": 6},
        # 40-block prefix pool per replica: the four prefix-heavy
        # families (~12 blocks each) plus their unique tails overflow it,
        # so cold chains MUST spill to the tier instead of vanishing
        "engine": {"max_slots": 2, "prefill_buckets": [16, 32, 64, 128],
                   "dtype": "float32", "kv_dtype": "float32",
                   "kv_block_tokens": 16, "kv_num_blocks": 40},
    })

    def factory(rid, role):
        return InProcessReplica(
            rid, role, lambda: build_serving_model(mcfg, app))

    fm = FleetServingModel(mcfg, app, factory, replicas=2,
                           prefill_replicas=0, disagg_threshold=10_000)
    tok = fm.tokenizer

    def submit(text):
        return fm.scheduler.submit(GenRequest(
            prompt=tok.encode(text), max_new_tokens=6, temperature=0.0))

    try:
        # -- directory traffic: prefix-heavy families repeat, so every
        # repeat after the first routes on a directory hit
        gen = LoadGen(mix={"chat": 1.0}, rate=50.0, max_tokens=6,
                      profile="prefix_heavy",
                      tenants=[Tenant("kv-a"), Tenant("kv-b")])
        summary = gen.run(EngineSink(fm, max_tokens=6), total=16,
                          timeout_s=300.0)
        if summary.get("errors"):
            problems.append(f"prefix-heavy load errors: {summary['errors']}")
        # -- sibling transfer: kill the directory-known holder of one
        # family pre-stream; the failover replica must pull the family's
        # warm prefix from the holder over TransferPrefix before
        # dispatching (placement away from warm KV ≠ a cold re-prefill)
        warm = submit(PREFIX_PROMPTS[0] + " [sibling/warm]")
        warm.result(300)
        key = affinity_key(tok.encode(PREFIX_PROMPTS[0] + " [sibling/hit]"),
                           block_tokens=fm.router.block_tokens,
                           blocks=fm.router.affinity_blocks)
        holder = fm.scheduler.directory.holder(
            key, [r.id for r in fm.pool.replicas])
        if holder is None:
            problems.append("prefix family never registered in directory")
        else:
            faults.arm(faults.FaultSpec(site="worker.stream", mode="raise",
                                        match=holder, times=1))
            try:
                h = submit(PREFIX_PROMPTS[0] + " [sibling/hit]")
                h.result(300)
                if h.finish_reason not in ("stop", "length"):
                    problems.append(
                        f"sibling-path request finished {h.finish_reason!r}")
            finally:
                faults.clear()
        # -- spill→reload round trip: a dozen cold filler families crush
        # both replicas' 40-block pools (the prefix families become LRU
        # victims → spill to host RAM), then every family re-request
        # re-onboards its spilled chain
        fillers = [
            submit(f"cold filler family {k:02d} keeps the prefix pool "
                   f"under sustained eviction pressure " * 3)
            for k in range(12)
        ]
        for h in fillers:
            h.result(300)
        for i, head in enumerate(PREFIX_PROMPTS):
            submit(head + f" [reload/{i}]").result(300)
        # -- assertions across both replicas' allocators
        spills = reloads = 0
        for r in fm.pool.replicas:
            ts = r.sm.runner.allocator.tier_stats()
            if ts is None:
                problems.append(f"{r.id}: tier never attached "
                                f"(LOCALAI_KV_TIER_MB ignored)")
                continue
            spills += ts["spills_total"]
            reloads += ts["reloads_total"]
        if spills < 1:
            problems.append("no HBM→host spills under pool pressure")
        if reloads < 1:
            problems.append(
                f"no spill→reload round trip ({spills} spills)")
        st = fm.scheduler.directory.stats()
        if st["hits"] < 1:
            problems.append(f"directory took no routing hits: {st}")
        if fm.scheduler.sibling_transfers < 1:
            problems.append(
                f"no sibling prefix transfer "
                f"({fm.scheduler.sibling_fallbacks} fallbacks)")
        if fm.scheduler.sibling_transfer_bytes <= 0 \
                and fm.scheduler.sibling_transfers > 0:
            problems.append("sibling transfer moved 0 bytes")
        # scrape-time refresh, exactly what GET /metrics does: the tier
        # roll-up rides the engine gauges, the directory its own pane
        update_engine_gauges("fleet-kv", fm.scheduler.metrics())
        fm.scheduler.export_gauges()
    finally:
        faults.clear()
        fm.close()
        if prev_tier is None:
            os.environ.pop("LOCALAI_KV_TIER_MB", None)
        else:
            os.environ["LOCALAI_KV_TIER_MB"] = prev_tier
    return problems


def check_fleetview(registry, fleet_flight_out: str) -> list[str]:
    """Round-15 fleet telemetry plane: a 2-replica WORKER-PROCESS fleet
    under a tools.loadgen mixed tenant workload → one request stitched
    into ONE waterfall (front-door + worker spans, worker side harvested
    over the real GetTelemetry gRPC and skew-anchored) + the merged
    fleet flight view written as a CI artifact."""
    import json as jsonlib

    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.fleet import FleetServingModel
    from localai_tpu.fleet.replica import WorkerReplica
    from localai_tpu.obs import fleetview
    from localai_tpu.obs.trace import STORE
    from tools.loadgen import EngineSink, LoadGen, Tenant

    problems: list[str] = []
    app = AppConfig()
    mcfg = ModelConfig.model_validate({
        "name": "fleet-grpc", "model": "debug:tiny", "context_size": 256,
        "parameters": {"temperature": 0.0, "max_tokens": 6},
        "engine": {"max_slots": 2, "prefill_buckets": [16, 32, 64, 128],
                   "dtype": "float32", "kv_dtype": "float32",
                   "kv_block_tokens": 16},
    })

    def factory(rid, role):
        return WorkerReplica(rid, role, mcfg, app,
                             env={"JAX_PLATFORMS": "cpu"})

    fm = FleetServingModel(mcfg, app, factory, replicas=2,
                           prefill_replicas=0, disagg_threshold=1 << 30)
    try:
        gen = LoadGen(mix={"chat": 0.7, "batch": 0.3},
                      tenants=[Tenant("free", 3), Tenant("pro", 1)],
                      rate=10.0, seed=3, max_tokens=6)
        summary = gen.run(EngineSink(fm, max_tokens=6), total=8)
        bad = {r: n for r, n in summary["outcomes"].items()
               if r not in ("stop", "length")}
        if bad or summary["errors"]:
            problems.append(
                f"loadgen traffic failed: {bad} {summary['errors']}")
        stitched = None
        for tid in summary["trace_ids"]:
            local = [t.to_dict() for t in STORE.find(tid)]
            if not local:
                continue
            s = fleetview.stitched_trace(fm, tid, local)
            if any(e["replica"] for e in s["waterfall"]):
                stitched = s
                break
        if stitched is None:
            problems.append(
                "no loadgen trace stitched a worker-side half "
                "(GetTelemetry harvest returned nothing)")
        else:
            worker_spans = {e["name"] for e in stitched["waterfall"]
                            if e["replica"]}
            front_spans = {e["name"] for e in stitched["waterfall"]
                           if not e["replica"]}
            if not {"prefill", "decode"} & worker_spans:
                problems.append(
                    f"worker-side engine spans missing: {worker_spans}")
            if "rpc" not in front_spans:
                problems.append(
                    f"front-door rpc span missing: {front_spans}")
            panes = [p for p in stitched["replicas"].values()
                     if p.get("traces")]
            if not panes or not panes[0]["traces"][0]["attrs"].get(
                    "skew_anchored"):
                problems.append("harvested worker trace is not "
                                "skew-anchored")
        flight = fleetview.fleet_flight(fm)
        with_records = [rid for rid, p in flight["replicas"].items()
                        if p.get("records")]
        if len(with_records) < 2:
            problems.append(
                f"merged fleet flight covers {with_records} "
                f"(need >=2 replicas): {flight['replicas']}")
        if flight["count"] == 0 or any(
                "replica" not in r for r in flight["records"]):
            problems.append("merged fleet flight rows miss the replica "
                            "column")
        with open(fleet_flight_out, "w") as f:
            jsonlib.dump(flight, f, indent=2, sort_keys=True)
        fm.scheduler.export_gauges()
    finally:
        fm.close()
    return problems


def check_usage(registry, usage_out: str) -> list[str]:
    """Round-18 usage accounting plane: a 2-replica WORKER-PROCESS fleet
    serves a weighted tenant mix from tools.loadgen, then the ledger must
    (a) attribute every request to the right HASHED tenant bucket (exact
    against what loadgen actually sent, and within tolerance of the
    configured mix), (b) reconcile per worker process: delivered +
    flight-class waste tokens == that worker's flight-ring total, with
    the front door's own ledger summing to the workers' (no double feed,
    no dropped feed), (c) round-trip the history store through a disk
    snapshot, and (d) export to /metrics WITHOUT any raw tenant name.
    The ``/v1/usage``-shaped payload lands in ``usage_out`` (a CI
    artifact)."""
    import json as jsonlib
    import tempfile

    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.fleet import FleetServingModel
    from localai_tpu.fleet.replica import WorkerReplica
    from localai_tpu.obs import fleetview
    from localai_tpu.obs.history import History
    from localai_tpu.obs.ledger import FLIGHT_WASTE, LEDGER, derive_tenant
    from tools.loadgen import EngineSink, LoadGen, Tenant

    problems: list[str] = []
    # the ledger is process-global and earlier rounds' loadgen traffic
    # fed it; this round asserts exact attribution, so start clean
    LEDGER.reset()
    app = AppConfig()
    mcfg = ModelConfig.model_validate({
        "name": "fleet-usage", "model": "debug:tiny", "context_size": 256,
        "parameters": {"temperature": 0.0, "max_tokens": 6},
        "engine": {"max_slots": 2, "prefill_buckets": [16, 32, 64, 128],
                   "dtype": "float32", "kv_dtype": "float32",
                   "kv_block_tokens": 16},
    })

    def factory(rid, role):
        return WorkerReplica(rid, role, mcfg, app,
                             env={"JAX_PLATFORMS": "cpu"})

    fm = FleetServingModel(mcfg, app, factory, replicas=2,
                           prefill_replicas=0, disagg_threshold=1 << 30)
    mix = {"usage-free": 3, "usage-pro": 1}
    try:
        gen = LoadGen(mix={"chat": 1.0},
                      tenants=[Tenant(n, w) for n, w in mix.items()],
                      rate=20.0, seed=7, max_tokens=6)
        summary = gen.run(EngineSink(fm, max_tokens=6), total=24,
                          timeout_s=300.0)
        bad = {r: n for r, n in summary["outcomes"].items()
               if r not in ("stop", "length")}
        if bad or summary["errors"]:
            problems.append(
                f"usage traffic failed: {bad} {summary['errors']}")
        payload = LEDGER.usage_payload()
        by_tenant: dict[str, int] = {}
        for row in payload["data"]:
            by_tenant[row["tenant"]] = (by_tenant.get(row["tenant"], 0)
                                        + row["requests"])
        # exact attribution: the ledger's per-tenant request counts must
        # equal what loadgen actually sent under each name's hash
        for name, sent in summary["tenants"].items():
            got = by_tenant.get(derive_tenant(name), 0)
            if got != sent:
                problems.append(
                    f"tenant {name}: ledger counted {got} of {sent} "
                    f"requests")
        # …and the realized shares must sit near the configured 3:1 mix
        total = sum(summary["tenants"].values())
        weight = sum(mix.values())
        for name, w in mix.items():
            share = by_tenant.get(derive_tenant(name), 0) / max(1, total)
            want = w / weight
            if abs(share - want) > 0.25:
                problems.append(
                    f"tenant {name} share {share:.2f} vs configured "
                    f"{want:.2f} (tolerance 0.25)")
        leaked = [t for t in by_tenant if t.startswith("usage-")]
        if leaked:
            problems.append(
                f"raw tenant names leaked into the ledger: {leaked}")
        # windowed view: every finished request is inside the last hour,
        # so the ring-backed aggregation must see all of them
        windowed = LEDGER.usage_payload(window=3600.0)
        if windowed["events"] != total:
            problems.append(
                f"windowed usage saw {windowed['events']} of {total} "
                f"events")
        # per-engine-process reconciliation: each worker's ledger
        # (harvested over GetTelemetry) must balance its own flight ring
        usage_panes = fleetview.fleet_usage(fm)
        flight = fleetview.fleet_flight(fm)
        reconciled = 0
        for rid, pane in usage_panes.items():
            if "goodput_tokens" not in pane:
                problems.append(
                    f"{rid}: no worker usage pane harvested: {pane}")
                continue
            delivered = sum(pane["goodput_tokens"].values())
            waste = sum(
                cell["tokens"] for key, cell in pane["waste"].items()
                if key.partition("/")[0] in FLIGHT_WASTE)
            ftotal = (flight["replicas"].get(rid) or {}).get("tokens_total")
            if ftotal is None:
                problems.append(f"{rid}: no flight pane to reconcile "
                                f"against")
            elif delivered + waste != ftotal:
                problems.append(
                    f"{rid}: ledger {delivered} delivered + {waste} "
                    f"flight-waste != flight ring {ftotal} tokens")
            else:
                reconciled += 1
        if reconciled < 2:
            problems.append(
                f"reconciled {reconciled} worker ledger(s), need 2")
        # the front door counted every delivered token exactly once —
        # its total equals the workers' (one feed per tier, no overlap)
        front = LEDGER.goodput_totals("fleet-usage")
        worker_delivered = sum(
            sum(p.get("goodput_tokens", {}).values())
            for p in usage_panes.values())
        if front["delivered_tokens"] != worker_delivered:
            problems.append(
                f"front-door delivered {front['delivered_tokens']} != "
                f"workers' {worker_delivered}")
        # history round-trip: ledger series → disk snapshot → fresh store
        h = History()
        h.observe_ledger(LEDGER)
        with tempfile.TemporaryDirectory() as td:
            h.save(td)
            h2 = History()
            if not h2.load(td):
                problems.append("history snapshot did not restore")
            elif h2.series_names() != h.series_names():
                problems.append(
                    f"restored history lost series: "
                    f"{set(h.series_names()) - set(h2.series_names())}")
            else:
                name = f"tenant_tokens.{derive_tenant('usage-free')}"
                q = h2.query(name, res=1)
                if not q or not q["points"]:
                    problems.append(
                        f"restored history has no points for {name}")
        # export + exposition safety: hashed buckets render, raw names
        # never do (REQUIRED_USAGE pins the family lines)
        LEDGER.export(registry)
        expo = registry.render()
        tser = (f'localai_tenant_tokens_total{{lane="interactive",'
                f'model="fleet-usage",'
                f'tenant="{derive_tenant("usage-free")}"}}')
        if tser not in expo:
            problems.append(f"tenant series missing from /metrics: {tser}")
        for raw in mix:
            if raw in expo:
                problems.append(
                    f"raw tenant name {raw!r} leaked into /metrics")
        with open(usage_out, "w") as f:
            jsonlib.dump({
                "payload": payload,
                "windowed": windowed,
                "replicas": usage_panes,
                "loadgen": {k: v for k, v in summary.items()
                            if k != "trace_ids"},
            }, f, indent=2, sort_keys=True)
        fm.scheduler.export_gauges()
    finally:
        fm.close()
    return problems


def check_anatomy(sched, tok, registry, anatomy_out: str) -> list[str]:
    """Round-19 dispatch anatomy: drive extra client traffic through the
    REAL smoke engine, then assert the phase decomposition holds record
    by record (gap+sched+launch+sync ≤ dispatch_ms — the interval-tiling
    invariant ``Scheduler._take_anat`` guarantees by clamp order), the
    derived ``host_overhead_fraction`` is a genuine fraction in (0, 1),
    and the client-observed TTFT p95 from ``tools.loadgen`` agrees with
    the server-side ``localai_ttft_seconds`` histogram (same submit /
    first-token stamps, so gross disagreement means one side is lying —
    the tolerance only absorbs bucket granularity and the earlier smoke
    requests sharing the histogram). Writes the breakdown + cross-check
    receipt to ``anatomy_out`` (a CI artifact)."""
    import json as jsonlib
    import re
    import types

    from localai_tpu.obs import anatomy as obs_anatomy
    from localai_tpu.obs.metrics import update_engine_gauges
    from tools.loadgen import EngineSink, LoadGen

    problems = []

    def ttft_buckets():
        # cumulative (upper_bound_s, count) pairs for model="smoke" out
        # of the rendered exposition — the same text a scrape would see
        pat = re.compile(r'localai_ttft_seconds_bucket\{model="smoke",'
                         r'le="([^"]+)"\} (\d+)')
        return [(float("inf") if le == "+Inf" else float(le), int(c))
                for le, c in pat.findall(registry.ttft.render())]

    # chat-only mix: the batch lane is excluded from the TTFT histogram
    # by design, so every client latency sample must have a server twin.
    # Snapshot the histogram FIRST: the earlier smoke requests paid the
    # compile, and diffing bucket counts is what isolates the server-side
    # view of exactly this traffic.
    before = dict(ttft_buckets())
    sm = types.SimpleNamespace(scheduler=sched, tokenizer=tok, runner=None)
    gen = LoadGen(mix={"chat": 1.0}, rate=64.0, seed=19, max_tokens=8)
    summary = gen.run(EngineSink(sm, max_tokens=8), total=8)
    if summary["errors"]:
        problems.append(f"anatomy loadgen traffic errored: "
                        f"{summary['errors'][:3]}")

    # (a) per-record tiling invariant over the live ring
    rows = sched.flight.snapshot()
    decode_rows = [r for r in rows if not r["compile"]]
    if not decode_rows:
        problems.append("anatomy: flight ring has no post-compile rows")
    for r in decode_rows:
        phase_sum = (r["gap_ms"] + r["sched_ms"] + r["launch_ms"]
                     + r["sync_ms"])
        # 5e-3 slack: snapshot rounds each column to 3 decimals, so four
        # rounded-up phases can nominally exceed a rounded-down dispatch
        if phase_sum > r["dispatch_ms"] + 5e-3:
            problems.append(
                f"anatomy: phase sum {phase_sum:.3f}ms exceeds "
                f"dispatch_ms {r['dispatch_ms']:.3f} "
                f"(program={r['program']})")
            break
    # the two measured parts lie inside gap, and the engine thread's five
    # states tile the row's span (2e-3 slack: the snapshot's rounding)
    for r in decode_rows:
        if (r["process_ms"] + r["book_ms"] + r["free_ms"]
                > r["gap_ms"] + 2e-3):
            problems.append(
                f"anatomy: process {r['process_ms']} + book "
                f"{r['book_ms']} + free {r['free_ms']} ms exceed gap_ms "
                f"{r['gap_ms']} "
                f"(program={r['program']})")
            break
        states = (r["wait_ms"] + r["idle_ms"] + r["cpu_ms"]
                  + (r["runq_ms"] or 0.0) + r["blocked_ms"])
        if abs(states - r["span_ms"]) > 2e-3:
            problems.append(
                f"anatomy: the thread's states sum to {states:.4f} ms of "
                f"a span of {r['span_ms']} (program={r['program']})")
            break
    if decode_rows and not any(r["process_ms"] > 0 and r["book_ms"] > 0
                               for r in decode_rows):
        problems.append("anatomy: no row measured process and book")

    # (b) the derived fraction: a genuine open-interval fraction
    anat = obs_anatomy.summarize(sched.flight, window_s=None)
    hof = anat["host_overhead_fraction"]
    if not anat["samples"]:
        problems.append("anatomy: summarize() saw zero samples")
    elif hof is None or not (0.0 < hof < 1.0):
        problems.append(
            f"anatomy: host_overhead_fraction {hof} outside (0, 1)")
    for part in obs_anatomy.PARTS:
        if anat.get(f"{part}_ms_p50") is None:
            problems.append(f"anatomy: summarize() has no {part} quantiles")

    # (c) client-vs-server latency cross-check: diff the histogram around
    # the loadgen run (isolating exactly this traffic's server view),
    # then the client p95 must land inside the delta-histogram's p95
    # bucket — both sides derive from the same handle stamps, so the
    # slack only absorbs bucket granularity
    client = summary.get("client_ttft_ms")
    cross = {"client_ttft_ms": client}
    if not client:
        problems.append("anatomy: loadgen produced no client TTFT samples")
    else:
        delta = [(ub, cum - before.get(ub, 0))
                 for ub, cum in ttft_buckets()]
        total = delta[-1][1] if delta else 0
        if total < client["count"]:
            problems.append(
                f"anatomy: server ttft histogram gained {total} samples "
                f"but the client observed {client['count']}")
        else:
            lo, hi = 0.0, float("inf")
            for ub, cum in delta:
                if cum >= 0.95 * total:
                    hi = ub
                    break
                lo = ub
            client_p95_s = client["p95"] / 1e3
            if (client_p95_s < lo / 2 - 0.05
                    or client_p95_s > hi * 2 + 0.05):
                problems.append(
                    f"anatomy: client ttft p95 {client_p95_s:.3f}s "
                    f"disagrees with server histogram p95 bucket "
                    f"({lo}, {hi}]s")
            cross.update(server_p95_bucket_lo_s=lo,
                         server_p95_bucket_hi_s=(
                             None if hi == float("inf") else hi),
                         server_samples=total)

    # re-export so the phase gauges reflect the anatomy traffic, exactly
    # what a scrape after this load would show
    update_engine_gauges("smoke", sched.metrics())
    with open(anatomy_out, "w") as f:
        jsonlib.dump({
            "breakdown": obs_anatomy.breakdown(sched.flight,
                                               window_s=None),
            "client_cross_check": cross,
            "loadgen": {k: v for k, v in summary.items()
                        if k != "trace_ids"},
        }, f, indent=2, sort_keys=True)
    return problems


def check_autoscale(registry, autoscale_out: str) -> list[str]:
    """Round 20 — elastic capacity end-to-end: a 1-replica autoscaled
    in-process fleet rides a seeded spike (tools.loadgen profile=spike)
    into a telemetry-driven scale-out, hot-swaps its replicas mid-life,
    quiesces into scale-to-zero, and cold-re-onboards a replica for the
    next request (which waits and completes — never errors). The
    capacity trajectory lands in ``autoscale_out`` (a CI artifact,
    ingestible by ``tools/usage_report.py --ingest-autoscale``)."""
    import json as jsonlib
    import threading

    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.engine.scheduler import GenRequest
    from localai_tpu.fleet import FleetServingModel
    from localai_tpu.fleet.autoscale import (AutoscaleConfig,
                                             AutoscaleController)
    from localai_tpu.fleet.replica import InProcessReplica
    from localai_tpu.models.manager import build_serving_model
    from localai_tpu.obs.history import HISTORY
    from tools.loadgen import EngineSink, LoadGen

    problems: list[str] = []
    app = AppConfig()
    mcfg = ModelConfig.model_validate({
        "name": "fleet-auto", "model": "debug:tiny", "context_size": 256,
        "parameters": {"temperature": 0.0, "max_tokens": 6},
        "engine": {"max_slots": 2, "prefill_buckets": [16, 32, 64, 128],
                   "dtype": "float32", "kv_dtype": "float32",
                   "kv_block_tokens": 16},
    })

    def factory(rid, role):
        return InProcessReplica(
            rid, role, lambda: build_serving_model(mcfg, app))

    fm = FleetServingModel(mcfg, app, factory, replicas=1)
    auto = AutoscaleController(fm, config=AutoscaleConfig(
        min_replicas=0, max_replicas=3, interval_s=0.1,
        in_idle_s=1.0, zero_idle_s=1.5, out_queue_depth=1.5,
        out_cooldown_s=0.5, in_cooldown_s=0.3, cold_timeout_s=120.0))
    fm.autoscaler = auto
    peak = {"healthy": 0}
    sampling = threading.Event()

    def sample():
        while not sampling.wait(0.05):
            peak["healthy"] = max(peak["healthy"],
                                  len(fm.pool.healthy("decode")))

    sampler = threading.Thread(target=sample, daemon=True)
    report: dict = {}
    try:
        auto.start()
        sampler.start()
        # phase 1 — spike: seeded Poisson baseline, 6× burst window; the
        # burst queues behind the single replica and the controller adds
        # capacity (queue-depth signal)
        gen = LoadGen(mix={"chat": 1.0}, rate=6.0, seed=11, max_tokens=6,
                      profile="spike", spike_start_s=0.5, spike_len_s=4.0,
                      spike_mult=8.0)
        summary = gen.run(EngineSink(fm, max_tokens=6), total=36,
                          timeout_s=300.0)
        bad = {r: n for r, n in summary["outcomes"].items()
               if r not in ("stop", "length")}
        if bad or summary["errors"]:
            problems.append(
                f"autoscale: spike traffic failed: {bad} "
                f"{summary['errors'][:3]}")
        deadline = time.monotonic() + 30.0
        while (auto.decisions["scale_out"] == 0
               and time.monotonic() < deadline):
            time.sleep(0.05)
        if auto.decisions["scale_out"] < 1:
            problems.append(
                f"autoscale: no scale-out under the spike "
                f"(decisions {auto.decisions})")
        if peak["healthy"] < 2:
            problems.append(
                f"autoscale: fleet never exceeded 1 healthy replica "
                f"(peak {peak['healthy']})")
        # phase 2 — hot weight swap while capacity is up: every local
        # replica is replaced by a freshly booted one, traffic shifts,
        # the old generation drains clean
        swap = fm.swap()
        report["swap"] = swap
        if not swap.get("ok"):
            problems.append(f"autoscale: hot swap failed: {swap}")
        # phase 3 — quiesce: all replicas idle past zero_idle_s → the
        # model scales to ZERO
        deadline = time.monotonic() + 60.0
        while (fm.pool.healthy("decode")
               and time.monotonic() < deadline):
            time.sleep(0.1)
        if fm.pool.healthy("decode"):
            problems.append(
                f"autoscale: fleet did not scale to zero after quiesce "
                f"(decisions {auto.decisions})")
        if auto.decisions["scale_to_zero"] < 1:
            problems.append(
                f"autoscale: no scale_to_zero decision recorded "
                f"({auto.decisions})")
        # phase 4 — cold re-onboard: the next request finds ZERO
        # replicas, waits out the cold boot, and completes
        t0 = time.monotonic()
        h = fm.scheduler.submit(GenRequest(
            prompt=fm.tokenizer.encode("wake the scaled-to-zero fleet"),
            max_new_tokens=6, temperature=0.0))
        h.result(timeout=300)
        cold_ms = (time.monotonic() - t0) * 1e3
        if h.finish_reason not in ("stop", "length"):
            problems.append(
                f"autoscale: held request finished "
                f"{h.finish_reason!r} instead of being served by the "
                f"cold re-onboard")
        if auto.decisions["cold_start"] < 1:
            problems.append(
                f"autoscale: no cold_start recorded ({auto.decisions})")
        fm.scheduler.export_gauges()
        report.update({
            "loadgen": summary,
            "decisions": dict(auto.decisions),
            "peak_healthy": peak["healthy"],
            "cold_start_ms": round(cold_ms, 1),
            "last_decision": auto.last_decision,
            "target_series": HISTORY.query(
                "fleet_target_replicas.fleet-auto", res=1),
        })
    finally:
        sampling.set()
        sampler.join(2)
        auto.stop()
        fm.close()
    with open(autoscale_out, "w") as f:
        jsonlib.dump(report, f, indent=2, sort_keys=True)
    return problems


def check_anomaly_capture(registry, profile_dir: str) -> list[str]:
    """Round-15 anomaly profiler: an injected ``engine.drain`` stall
    trips the watchdog and auto-captures a (real) jax.profiler trace
    with the stall's forensic trace id; a second stall inside the
    cooldown is refused. Scratch watchdog + scratch manager — hermetic,
    no env fiddling."""
    from pathlib import Path

    from localai_tpu import faults
    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.models.registry import resolve_model
    from localai_tpu.obs import EngineTelemetry, TraceStore, Watchdog
    from localai_tpu.obs.profiler import ProfileManager
    from localai_tpu.obs.slo import SLOTracker
    from localai_tpu.utils.tokenizer import ByteTokenizer

    problems: list[str] = []
    store = TraceStore()
    wd = Watchdog(deadline=0.8, registry=registry, store=store,
                  poll_interval=0.1)
    wd.start()
    pm = ProfileManager(enabled=True, seconds=0.2, out_dir=profile_dir,
                        max_per_hour=10, cooldown_s=3600.0,
                        registry=registry)
    pm.install(watchdog=wd, slo=SLOTracker(registry=registry, targets={}))
    tiny = resolve_model("debug:tiny", dtype="float32")
    runner = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                         prefill_buckets=[16], kv_dtype="float32",
                         paged=True, kv_block_tokens=16)
    sched = Scheduler(
        runner, ByteTokenizer(), watchdog=wd,
        telemetry=EngineTelemetry(model="stall-anomaly", store=store,
                                  slo=SLOTracker(registry=registry,
                                                 targets={})))
    tok = ByteTokenizer()
    try:
        for _ in range(2):  # second stall lands inside the cooldown
            faults.arm(faults.FaultSpec(
                site="engine.drain", mode="hang", delay_s=3.0, times=1,
                match="stall-anomaly"))
            h = sched.submit(GenRequest(prompt=tok.encode("stall me"),
                                        max_new_tokens=4, temperature=0.0))
            h.result(timeout=120)
        pm.wait_idle(30.0)
        stalls = [e for e in pm.entries() if e["trigger"] == "stall"]
        if len(stalls) != 1:
            problems.append(
                f"expected exactly 1 stall capture (cooldown eats the "
                f"second), got {len(stalls)}")
        else:
            if not stalls[0]["trace_id"].startswith("stall-"):
                problems.append(
                    f"capture carries no triggering trace id: {stalls[0]}")
            if not stalls[0].get("ok"):
                problems.append(
                    f"profiler capture failed: {stalls[0].get('error')}")
        if pm.report()["skipped"].get("cooldown", 0) < 1:
            problems.append("second stall inside the cooldown was not "
                            "refused")
        if not (Path(profile_dir) / "manifest.json").exists():
            problems.append("no profile manifest written")
    finally:
        faults.clear("engine.drain")
        sched.shutdown()
        pm.stop()
        wd.stop()
    return problems


# the fleet-served model for the --loopsan phase: NO embeddings usecase
# (embeddings-capable models keep the single-engine path — manager._load),
# so with fleet_replicas=2 this serves from a 2-replica in-process fleet
LOOPSAN_YAML = """\
name: fleet-http
model: "debug:tiny"
context_size: 96
parameters:
  temperature: 0.0
  max_tokens: 8
engine:
  max_slots: 2
  prefill_buckets: [16, 32]
  dtype: float32
  kv_dtype: float32
"""


def check_loopsan(loopsan_out: str) -> list[str]:
    """Round-16 event-loop sanitizer: boot the real aiohttp API over a
    2-replica in-process fleet, install ``tools.loopsan``, prove the
    detector catches a deliberately injected ``time.sleep(0.2)`` on the
    loop, reset, then drive mixed loadgen HTTP traffic plus one SSE
    stream and require ZERO ≥ 50 ms stalls. The earlier phases run the
    engine/fleet stack on plain threads — the event loop only exists in
    the API tier, so this phase is where the sanitizer has something to
    watch."""
    import asyncio
    import json as jsonlib
    import tempfile
    import threading
    from pathlib import Path

    import httpx

    from localai_tpu.api.server import AppState, create_app
    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.loader import ConfigLoader
    from tools.loadgen import HttpSink, LoadGen, Tenant
    from tools.loopsan import LoopSanitizer

    problems: list[str] = []
    selfcheck: dict = {}
    with tempfile.TemporaryDirectory() as tmp:
        models = Path(tmp) / "models"
        models.mkdir()
        (models / "fleet-http.yaml").write_text(LOOPSAN_YAML)
        cfg = AppConfig(
            model_path=str(models),
            upload_path=str(Path(tmp) / "uploads"),
            config_path=str(Path(tmp) / "conf"),
            fleet_replicas=2, fleet_backend="inprocess",
        )
        loader = ConfigLoader(models)
        loader.load_from_path(context_size=cfg.context_size)
        state = AppState(cfg, loader)

        boot: dict = {}
        started = threading.Event()

        def serve():
            from aiohttp import web

            loop = asyncio.new_event_loop()
            asyncio.set_event_loop(loop)
            boot["loop"] = loop

            async def up():
                app = create_app(state)
                runner = web.AppRunner(app)
                await runner.setup()
                site = web.TCPSite(runner, "127.0.0.1", 0)
                await site.start()
                boot["port"] = runner.addresses[0][1]
                boot["runner"] = runner
                started.set()

            loop.run_until_complete(up())
            loop.run_forever()

        th = threading.Thread(target=serve, daemon=True, name="loopsan-api")
        th.start()
        if not started.wait(60):
            return ["loopsan: API server failed to start"]
        base = f"http://127.0.0.1:{boot['port']}"
        loop = boot["loop"]

        def chat_body(text, **extra):
            return {"model": "fleet-http", "max_tokens": 6,
                    "temperature": 0.0,
                    "messages": [{"role": "user", "content": text}],
                    **extra}

        try:
            # warm up BEFORE the sanitizer installs: the first request
            # builds both fleet replicas (jit compile in executor
            # threads); measuring loop health while compiles monopolize
            # CPU would report scheduler noise, not handler stalls
            with httpx.Client(base_url=base, timeout=300.0) as c:
                r = c.post("/v1/chat/completions",
                           json=chat_body("loopsan warmup"))
                if r.status_code != 200:
                    return [f"loopsan: warmup request failed "
                            f"{r.status_code}: {r.text[:200]}"]

            san = LoopSanitizer(threshold_ms=50.0)
            san.install()
            try:
                # self-check: a sync sleep dispatched onto the live loop
                # is EXACTLY the bug class the sanitizer exists for — it
                # must be caught before a clean run means anything
                loop.call_soon_threadsafe(time.sleep, 0.2)
                deadline = time.monotonic() + 10.0
                while not san.stalls() and time.monotonic() < deadline:
                    time.sleep(0.02)
                injected = san.stalls()
                if len(injected) != 1:
                    problems.append(
                        f"loopsan self-check: injected 200 ms sleep "
                        f"produced {len(injected)} stall(s), expected 1")
                else:
                    s = injected[0]
                    if "sleep" not in s.label or s.duration_ms < 150.0:
                        problems.append(
                            f"loopsan self-check: stall misattributed: "
                            f"{s.label} ({s.duration_ms:.1f} ms)")
                    selfcheck = s.to_dict()
                san.reset()

                sink = HttpSink(base, "fleet-http", max_tokens=6)
                try:
                    gen = LoadGen(mix={"chat": 0.7, "batch": 0.3},
                                  tenants=[Tenant("free", 3),
                                           Tenant("pro", 1)],
                                  rate=12.0, seed=5, max_tokens=6)
                    summary = gen.run(sink, total=10)
                finally:
                    sink.close()
                bad = {r: n for r, n in summary["outcomes"].items()
                       if r not in ("stop", "length")}
                if bad or summary["errors"]:
                    problems.append(f"loopsan: HTTP traffic failed: "
                                    f"{bad} {summary['errors']}")
                # one live SSE stream: the chunked writer must yield
                # between deltas, never hold the loop for a whole reply
                events = []
                with httpx.Client(base_url=base, timeout=120.0) as c:
                    with c.stream(
                            "POST", "/v1/chat/completions",
                            json=chat_body("stream smoke", stream=True),
                    ) as resp:
                        status = resp.status_code
                        for line in resp.iter_lines():
                            if line.startswith("data: "):
                                events.append(line)
                if status != 200 or len(events) < 2:
                    problems.append(f"loopsan: SSE stream broke: status "
                                    f"{status}, {len(events)} events")
                stalls = san.stalls()
                snap = san.snapshot()
            finally:
                san.uninstall()
        finally:
            fut = asyncio.run_coroutine_threadsafe(
                boot["runner"].cleanup(), loop)
            fut.result(30)
            loop.call_soon_threadsafe(loop.stop)
            th.join(15)

    if snap["callbacks_seen"] == 0:
        problems.append("loopsan: sanitizer observed no loop callbacks — "
                        "the Handle._run patch is not active")
    snap["injected_selfcheck"] = selfcheck
    with open(loopsan_out, "w") as f:
        jsonlib.dump(snap, f, indent=2, sort_keys=True)
    if stalls:
        print(san.report())
        problems.append(
            f"loopsan: {len(stalls)} event-loop stall(s) >= "
            f"{san.threshold_ms:g} ms during the fleet HTTP lifecycle "
            f"(report → {loopsan_out})")
    return problems


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--out", default="telemetry_summary.json")
    parser.add_argument("--flight-out", default="flight_snapshot.json")
    parser.add_argument("--batch-out", default="batch_result.jsonl")
    parser.add_argument("--fleet-flight-out", default="fleet_flight.json")
    parser.add_argument("--usage-out", default="usage_snapshot.json")
    parser.add_argument("--anatomy-out", default="anatomy_report.json")
    parser.add_argument("--autoscale-out", default="autoscale_report.json")
    parser.add_argument("--profile-dir", default="profile_manifest")
    parser.add_argument("--requests", type=int, default=4)
    # two dispatch-rounds past the compile-bearing first one, so the
    # flight ring has post-compile samples and step_ms percentiles exist
    parser.add_argument("--max-tokens", type=int, default=40)
    parser.add_argument(
        "--racecheck", action="store_true",
        help="run the lifecycle under tools.racecheck instrumented locks "
             "and fail on any observed lock-order inversion")
    parser.add_argument(
        "--loopsan", action="store_true",
        help="boot the real HTTP API over a 2-replica fleet under "
             "tools.loopsan and fail on any event-loop stall >= 50 ms")
    parser.add_argument("--loopsan-out", default="loopsan_report.json")
    args = parser.parse_args(argv)

    monitor = None
    if args.racecheck:
        # install BEFORE the localai imports below: module import is when
        # the process-wide locks (trace store, registry, watchdog) are
        # constructed, and only post-install locks are traced
        from tools.racecheck import LockMonitor

        monitor = LockMonitor().install()

    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.models.registry import resolve_model
    from localai_tpu.obs import REGISTRY, EngineTelemetry, TraceStore
    from localai_tpu.obs.metrics import update_engine_gauges
    from localai_tpu.obs.slo import SLOTracker
    from localai_tpu.utils.tokenizer import ByteTokenizer

    t_boot = time.monotonic()
    tiny = resolve_model("debug:tiny", dtype="float32")
    runner = ModelRunner(
        tiny.cfg, tiny.params, num_slots=4, max_ctx=96,
        prefill_buckets=[16, 32], kv_dtype="float32",
        # the serving default: paged block pool + chunked prefill — the
        # smoke must exercise (and assert) the block gauges end-to-end
        paged=True, kv_block_tokens=16, prefill_chunk=16,
    )
    store = TraceStore()
    # a dedicated observatory (no env targets) so the smoke is hermetic;
    # it still writes the shared REGISTRY the exposition check reads
    slo = SLOTracker(registry=REGISTRY, targets={})
    sched = Scheduler(
        runner, ByteTokenizer(),
        telemetry=EngineTelemetry(model="smoke", store=store, slo=slo),
    )
    tok = ByteTokenizer()
    try:
        handles = [
            sched.submit(GenRequest(
                prompt=tok.encode(f"telemetry smoke request {i}"),
                max_new_tokens=args.max_tokens, temperature=0.0,
                trace_id=f"smoke-{i}",
            ))
            for i in range(args.requests)
        ]
        for h in handles:
            h.result(timeout=300)
        # scrape-time refresh, exactly what GET /metrics does
        engine_metrics = sched.metrics()
        update_engine_gauges("smoke", engine_metrics)
        slo.export_gauges()
        problems = check_introspection(runner, REGISTRY, store)
        problems += check_slo_overload(REGISTRY)
        problems += check_batch(sched, REGISTRY, args.batch_out)
        problems += check_fleet(REGISTRY)
        problems += check_kveconomy(REGISTRY)
        problems += check_fleetview(REGISTRY, args.fleet_flight_out)
        problems += check_usage(REGISTRY, args.usage_out)
        problems += check_anatomy(sched, tok, REGISTRY, args.anatomy_out)
        problems += check_autoscale(REGISTRY, args.autoscale_out)
        problems += check_anomaly_capture(REGISTRY, args.profile_dir)
        if args.loopsan:
            problems += check_loopsan(args.loopsan_out)
        # scrape-time trace-ring sizing receipt, exactly what GET /metrics
        # exports (LOCALAI_TRACE_CAPACITY satellite)
        from localai_tpu.obs.trace import STORE as TRACE_STORE

        REGISTRY.trace_ring_size.set(TRACE_STORE.capacity)
        flight_pct = sched.flight.percentiles()
        flight_snapshot = {
            "model": "smoke",
            "dispatches": sched.flight.count,
            "tokens_total": sched.flight.total_tokens,
            "percentiles": flight_pct,
            "records": sched.flight.snapshot(),
        }
        if sched.flight.count == 0:
            problems.append("flight ring is empty after synthetic load")
        if flight_pct["step_ms_p50"] is None:
            problems.append(
                "flight ring has no post-compile step-time samples")
    finally:
        sched.shutdown()

    racecheck_summary = None
    if monitor is not None:
        monitor.uninstall()
        inversions = monitor.inversions()
        print(monitor.report())
        if inversions:
            print("FAIL: lock-order inversions observed across the "
                  "fleet+batch+shed lifecycle (see report above)")
            return 1
        racecheck_summary = {
            "locks_created": monitor.locks_created,
            "ordered_edges": len(monitor.edges()),
            "inversions": 0,
        }

    exposition = REGISTRY.render()
    missing = [s for s in (REQUIRED_SERIES + REQUIRED_FAMILIES
                           + REQUIRED_INTROSPECTION + REQUIRED_SLO
                           + REQUIRED_BATCH + REQUIRED_FLEET
                           + REQUIRED_KVECONOMY + REQUIRED_FLEETVIEW
                           + REQUIRED_USAGE + REQUIRED_ANATOMY
                           + REQUIRED_AUTOSCALE)
               if s not in exposition]
    if missing or problems:
        print("FAIL: missing engine telemetry in /metrics exposition:")
        for s in missing:
            print(f"  - {s}")
        for p in problems:
            print(f"  - {p}")
        return 1

    traces = [t.to_dict() for t in store.recent(limit=args.requests * 2)
              if t.kind == "request"]
    ttfts = [t["attrs"]["ttft_ms"] for t in traces
             if t["attrs"].get("ttft_ms") is not None]
    tpots = [t["attrs"]["tpot_ms"] for t in traces
             if t["attrs"].get("tpot_ms") is not None]
    if not ttfts or not tpots:
        print("FAIL: completed traces carry no TTFT/TPOT")
        return 1

    def stats(vals):
        return {
            "n": len(vals),
            "mean_ms": round(statistics.mean(vals), 3),
            "min_ms": round(min(vals), 3),
            "max_ms": round(max(vals), 3),
            "median_ms": round(statistics.median(vals), 3),
        }

    summary = {
        "model": "debug:tiny",
        "requests": args.requests,
        "max_tokens": args.max_tokens,
        "wall_seconds": round(time.monotonic() - t_boot, 2),
        "ttft": stats(ttfts),
        "tpot": stats(tpots),
        "tokens_per_second": [
            t["attrs"].get("tokens_per_second") for t in traces
        ],
        "engine": {
            k: v for k, v in engine_metrics.items() if k != "active_slots"
        },
    }
    if racecheck_summary is not None:
        summary["racecheck"] = racecheck_summary
    with open(args.out, "w") as f:
        json.dump(summary, f, indent=2, sort_keys=True)
    with open(args.flight_out, "w") as f:
        json.dump(flight_snapshot, f, indent=2, sort_keys=True)
    print(f"OK: engine telemetry present; summary → {args.out}, "
          f"flight ring → {args.flight_out}, "
          f"batch result → {args.batch_out}, "
          f"fleet flight → {args.fleet_flight_out}, "
          f"usage → {args.usage_out}, "
          f"anatomy → {args.anatomy_out}, "
          f"autoscale → {args.autoscale_out}, "
          f"profiles → {args.profile_dir}/manifest.json"
          + (f", loopsan → {args.loopsan_out}" if args.loopsan else ""))
    print(f"    ttft mean {summary['ttft']['mean_ms']}ms  "
          f"tpot mean {summary['tpot']['mean_ms']}ms  "
          f"over {len(ttfts)} requests; "
          f"step p50 {flight_pct['step_ms_p50']}ms "
          f"p99 {flight_pct['step_ms_p99']}ms "
          f"over {flight_pct['samples']} dispatches")
    return 0


if __name__ == "__main__":
    sys.exit(main())
