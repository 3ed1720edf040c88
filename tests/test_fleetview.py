"""Fleet telemetry plane (ISSUE 15): GetTelemetry harvest, skew-anchored
trace stitching, merged fleet flight view.

Unit tier: anchoring math, anchor/replica-id extraction, stitch dedup +
unreachable panes against fake payloads. Wire tier: the GetTelemetry RPC
against an in-process gRPC worker. Serving tier: a real in-process fleet
stitched end-to-end (fast), and a worker-PROCESS fleet with a
disaggregated request showing prefill+decode replicas in one waterfall
(slow)."""

import time
from types import SimpleNamespace

import pytest

from localai_tpu.obs import fleetview
from localai_tpu.obs.flight import FlightRecorder
from localai_tpu.obs.trace import RequestTrace, TraceStore

TINY = {
    "name": "fvt", "model": "debug:tiny", "context_size": 256,
    "parameters": {"temperature": 0.0, "max_tokens": 8},
    "engine": {"max_slots": 2, "prefill_buckets": [16, 32, 64, 128],
               "dtype": "float32", "kv_dtype": "float32",
               "kv_block_tokens": 16},
}

TINY_YAML = """\
name: tiny
model: "debug:tiny"
context_size: 96
engine:
  max_slots: 2
  prefill_buckets: [16]
  dtype: float32
  kv_dtype: float32
"""


def _trace_dict(trace_id="t1", request_id="req-0", model="m", start=100.0,
                spans=(), attrs=None):
    return {
        "trace_id": trace_id, "request_id": request_id, "kind": "request",
        "model": model, "name": "request", "start_unix": start,
        "duration_ms": 10.0, "finished": True, "attrs": dict(attrs or {}),
        "children": [
            {"name": n, "start_unix": s, "duration_ms": d,
             "attrs": dict(a)} for n, s, d, a in spans
        ],
    }


# ---------------------------------------------------------------------------
# skew anchoring


def test_anchor_trace_shifts_rigidly():
    # remote clock is ~49 minutes ahead; anchoring pins the root to the
    # local rpc start and shifts every child by the SAME offset
    remote = _trace_dict(start=5000.0, spans=(
        ("queued", 5000.0, 0.5, {}),
        ("decode", 5000.25, 3.0, {}),
    ))
    out = fleetview.anchor_trace(remote, 100.5, replica="m/r0")
    assert out["start_unix"] == pytest.approx(100.5)
    assert out["children"][0]["start_unix"] == pytest.approx(100.5)
    assert out["children"][1]["start_unix"] == pytest.approx(100.75)
    # durations and relative ordering untouched
    assert out["children"][1]["duration_ms"] == 3.0
    assert out["attrs"]["skew_anchored"] is True
    assert out["attrs"]["skew_offset_ms"] == pytest.approx(-4899500.0)
    assert out["attrs"]["replica"] == "m/r0"
    assert all(c["attrs"]["replica"] == "m/r0" for c in out["children"])
    # the input dict is never mutated
    assert remote["start_unix"] == 5000.0
    assert "skew_anchored" not in remote["attrs"]


def test_replica_anchors_and_ids():
    local = [_trace_dict(
        model="m", attrs={"replica": "m/r1", "prefill_replica": "m/p0"},
        spans=(
            ("route", 100.0, 0.1, {"replica": "m/r1"}),
            ("prefix_transfer", 100.2, 2.0,
             {"prefill": "m/p0", "decode": "m/r1"}),
            ("rpc", 102.5, 5.0, {"replica": "m/r1"}),
        ))]
    anchors = fleetview.replica_anchors(local)
    # first span naming the replica wins: r1 anchors at the route span,
    # p0 at the prefix_transfer span
    assert anchors == {"m/r1": 100.0, "m/p0": 100.2}
    assert fleetview.replica_ids_for_trace(local) == {"m/r1", "m/p0"}


def test_stitch_dedup_unreachable_and_tagging():
    local = [_trace_dict(
        trace_id="tx", request_id="front-0", model="m",
        attrs={"replica": "m/r0"},
        spans=(("rpc", 100.0, 5.0, {"replica": "m/r0"}),
               ("route", 99.9, 0.05, {"replica": "m/r1"})))]
    dup = _trace_dict(trace_id="tx", request_id="front-0", model="m")
    remote = _trace_dict(trace_id="tx", request_id="m/r0-0", model="m/r0",
                         start=7777.0,
                         spans=(("decode", 7777.5, 2.0, {}),))
    out = fleetview.stitch("tx", local, {
        "m/r0": {"traces": [dup, remote], "shared_store": True},
        "m/r1": {"error": "deadline", "unreachable": True},
    })
    # the duplicate (same trace id + request id as a local trace —
    # in-process replicas share the store and say so) is dropped
    assert len(out["replicas"]["m/r0"]["traces"]) == 1
    assert out["replicas"]["m/r1"]["unreachable"] is True
    # remote decode span anchored into the local rpc window + tagged;
    # front-door spans stay untagged
    events = {(e["replica"], e["name"]): e for e in out["waterfall"]}
    assert ("m/r0", "decode") in events
    assert ("", "rpc") in events and ("", "route") in events
    decode = events[("m/r0", "decode")]
    rpc = events[("", "rpc")]
    assert decode["offset_ms"] == pytest.approx(rpc["offset_ms"] + 500.0)
    # waterfall is time-ordered
    offsets = [e["offset_ms"] for e in out["waterfall"]]
    assert offsets == sorted(offsets)


def test_stitch_never_dedupes_cross_process_panes():
    # request ids are per-process counters: a WORKER's "m-0" must not be
    # mistaken for the front door's "m-0" (only shared_store panes dedup)
    local = [_trace_dict(trace_id="tz", request_id="m-0", model="m",
                         spans=(("rpc", 10.0, 5.0, {"replica": "m/r0"}),))]
    worker_half = _trace_dict(trace_id="tz", request_id="m-0", model="m",
                              start=9000.0,
                              spans=(("decode", 9000.2, 2.0, {}),))
    out = fleetview.stitch("tz", local, {
        "m/r0": {"traces": [worker_half]},  # no shared_store marker
    })
    assert len(out["replicas"]["m/r0"]["traces"]) == 1
    assert ("m/r0", "decode") in {(e["replica"], e["name"])
                                  for e in out["waterfall"]}


def test_stitch_fallback_anchor_for_unnamed_replica():
    # a harvested pane for a replica the local spans never named anchors
    # at the earliest local root instead of crashing
    local = [_trace_dict(trace_id="ty", request_id="front-1", start=50.0)]
    remote = _trace_dict(trace_id="ty", request_id="m/r9-3", model="m/r9",
                         start=9999.0, spans=(("decode", 9999.1, 1.0, {}),))
    out = fleetview.stitch("ty", local, {"m/r9": {"traces": [remote]}})
    anchored = out["replicas"]["m/r9"]["traces"][0]
    assert anchored["start_unix"] == pytest.approx(50.0)


# ---------------------------------------------------------------------------
# payload builder (what GetTelemetry serves; shared by both replica kinds)


def _fake_scheduler(metrics=None):
    flight = FlightRecorder(8)
    flight.record(program="decode_n", steps=4, dispatch_ms=8.0,
                  occupancy=0.5, queue_depth=0, kv_utilization=0.1,
                  tokens=4)
    return SimpleNamespace(flight=flight,
                           metrics=lambda: metrics or {"num_slots": 2})


def test_telemetry_payload_trace_filter_and_flight():
    store = TraceStore(8)
    tr = RequestTrace("trace-abc", "eng-0", model="m")
    tr.begin("decode")
    store.start(tr)
    store.finish(tr)
    other = RequestTrace("trace-zzz", "eng-1", model="m")
    store.start(other)
    store.finish(other)
    payload = fleetview.telemetry_payload(
        _fake_scheduler(), trace_id="trace-abc", store=store)
    assert [t["trace_id"] for t in payload["traces"]] == ["trace-abc"]
    assert len(payload["flight"]["records"]) == 1
    assert payload["flight"]["capacity"] == 8
    assert payload["metrics"]["num_slots"] == 2
    # trace-id-less harvest: recent request traces, bounded
    payload = fleetview.telemetry_payload(
        _fake_scheduler(), recent=1, store=store)
    assert len(payload["traces"]) == 1


def test_telemetry_payload_no_scheduler_and_metrics_error():
    store = TraceStore(4)
    payload = fleetview.telemetry_payload(None, store=store)
    assert payload["flight"] is None and payload["metrics"] == {}

    def boom():
        raise RuntimeError("stats broke")

    sched = SimpleNamespace(flight=None, metrics=boom)
    payload = fleetview.telemetry_payload(sched, store=store)
    assert payload["metrics"] == {"error": "stats broke"}


# ---------------------------------------------------------------------------
# wire tier: GetTelemetry against an in-process gRPC worker


@pytest.fixture(scope="module")
def worker():
    from localai_tpu.worker import WorkerClient
    from localai_tpu.worker.server import BackendServicer, serve_worker

    servicer = BackendServicer()
    server, port = serve_worker("127.0.0.1:0", servicer=servicer, block=False)
    client = WorkerClient(f"127.0.0.1:{port}")
    res = client.load_model(config_yaml=TINY_YAML)
    assert res.success, res.message
    yield client
    client.close()
    # the loaded model's engine thread goes with its worker: left running,
    # it writes ``sched.*`` into whatever this process captures next
    # (tests/test_profiler.py's two capture cases, when they share a worker)
    servicer.shutdown()
    server.stop(grace=None)


def test_get_telemetry_rpc(worker):
    from localai_tpu.worker import backend_pb2 as pb

    list(worker.predict_stream(pb.PredictOptions(
        prompt="harvest me", max_tokens=6, temperature=0.0),
        trace_id="trace-rpc-harvest"))
    t = worker.get_telemetry(trace_id="trace-rpc-harvest")
    assert [tr["trace_id"] for tr in t["traces"]] == ["trace-rpc-harvest"]
    names = [s["name"] for s in t["traces"][0]["children"]]
    assert "prefill" in names and "decode" in names
    assert t["flight"]["records"], "flight ring empty after a generation"
    assert t["metrics"]["num_slots"] == 2
    # trace-id-less harvest returns the recent window
    t = worker.get_telemetry(recent=5)
    assert t["traces"]


def test_get_telemetry_flight_since_windowing(worker):
    # the engine thread may still be writing a trailing drain record
    # when the previous test's stream ends — wait for the ring to quiesce
    last_ts = worker.get_telemetry()["flight"]["records"][-1]["ts"]
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        time.sleep(0.05)
        ts = worker.get_telemetry()["flight"]["records"][-1]["ts"]
        if ts == last_ts:
            break
        last_ts = ts
    # feeding back the last seen ts returns only newer records (none yet)
    t2 = worker.get_telemetry(since=last_ts)
    assert t2["flight"]["records"] == []


# ---------------------------------------------------------------------------
# serving tier: in-process fleet stitched end-to-end


@pytest.fixture(scope="module")
def fleet():
    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.fleet import FleetServingModel
    from localai_tpu.fleet.replica import InProcessReplica
    from localai_tpu.models.manager import build_serving_model

    app = AppConfig()
    mcfg = ModelConfig.model_validate(TINY)

    def factory(rid, role):
        # per-replica identity, like the manager's real factory: the
        # stitcher keys in-process engine traces by model == rid
        rcfg = mcfg.model_copy(update={"name": rid})
        return InProcessReplica(
            rid, role, lambda: build_serving_model(rcfg, app))

    fm = FleetServingModel(mcfg, app, factory, replicas=2,
                           prefill_replicas=1, disagg_threshold=48)
    yield fm
    fm.close()


def _run(fm, text, trace_id, timeout=180):
    from localai_tpu.engine.scheduler import GenRequest

    h = fm.scheduler.submit(GenRequest(
        prompt=fm.tokenizer.encode(text), max_new_tokens=6,
        temperature=0.0, trace_id=trace_id))
    h.result(timeout=timeout)
    assert h.finish_reason in ("stop", "length")
    return h


def test_fleet_stitched_waterfall(fleet):
    from localai_tpu.obs.trace import STORE

    _run(fleet, "stitch this request", "trace-fv-short")
    local = [t.to_dict() for t in STORE.find("trace-fv-short")]
    out = fleetview.stitched_trace(fleet, "trace-fv-short", local)
    pairs = {(e["replica"], e["name"]) for e in out["waterfall"]}
    # ONE waterfall: untagged front-door spans + replica-tagged engine
    # spans (in-process replicas: deduped from the shared store)
    assert ("", "route") in pairs and ("", "rpc") in pairs
    assert any(r.startswith("fvt/r") and n == "decode" for r, n in pairs)


def test_fleet_stitched_disagg_two_replicas(fleet):
    from localai_tpu.obs.trace import STORE

    before = fleet.scheduler.prefix_transfers
    _run(fleet, "fleet disaggregated long prompt " * 6, "trace-fv-disagg")
    assert fleet.scheduler.prefix_transfers == before + 1
    local = [t.to_dict() for t in STORE.find("trace-fv-disagg")]
    rids = fleetview.replica_ids_for_trace(local)
    assert any(r.startswith("fvt/p") for r in rids), rids
    out = fleetview.stitched_trace(fleet, "trace-fv-disagg", local)
    tagged = {e["replica"] for e in out["waterfall"] if e["replica"]}
    # prefill AND decode replicas appear in the ONE waterfall
    assert any(r.startswith("fvt/p") for r in tagged), tagged
    assert any(r.startswith("fvt/r") for r in tagged), tagged


def test_fleet_flight_merges_replicas(fleet):
    out = fleetview.fleet_flight(fleet)
    with_records = [rid for rid, p in out["replicas"].items()
                    if p.get("records")]
    assert len(with_records) >= 2, out["replicas"]
    assert out["count"] == len(out["records"]) > 0
    assert all(r["replica"] for r in out["records"])
    # wall-ordered merge
    ts = [r["ts_unix"] for r in out["records"]]
    assert ts == sorted(ts)
    # percentile panes ride along
    assert all("percentiles" in p for p in
               (out["replicas"][rid] for rid in with_records))
    # dispatch-anatomy columns on every merged row, fraction gauges per
    # replica pane (the per-replica phase columns on /debug/fleet/flight)
    for rec in out["records"]:
        for ph in ("gap_ms", "sched_ms", "launch_ms", "sync_ms"):
            assert ph in rec
    assert all("host_overhead_fraction" in out["replicas"][rid]
               for rid in with_records)


def test_fleet_flight_tolerates_replicas_without_phase_columns():
    """A mixed-version fleet: a replica whose payload predates the
    anatomy columns merges with BLANK phase cells and None fractions —
    never a KeyError (round-19 satellite)."""

    class LegacyReplica:
        id = "legacy/r0"
        state = "healthy"

        def telemetry(self, trace_id="", since=0.0, limit=64, recent=0):
            return {"flight": {
                "records": [{"ts": 1.0, "ts_unix": 100.0,
                             "program": "decode_n", "dispatch_ms": 5.0}],
                "percentiles": None, "dispatches": 1, "tokens_total": 8,
            }}

    class Pool:
        def members(self):
            return [LegacyReplica()]

    class SM:
        pool = Pool()

    out = fleetview.fleet_flight(SM())
    assert out["count"] == 1
    row = out["records"][0]
    assert row["replica"] == "legacy/r0"
    for ph in ("gap_ms", "sched_ms", "launch_ms", "sync_ms"):
        assert row[ph] is None
    pane = out["replicas"]["legacy/r0"]
    assert pane["host_overhead_fraction"] is None
    assert pane["anatomy"] is None


def test_replica_telemetry_never_raises(fleet):
    r = fleet.pool.members()[0]
    pane = r.telemetry(trace_id="trace-fv-short")
    assert pane.get("traces") is not None
    # a dead in-process replica degrades to an unreachable pane
    from localai_tpu.fleet.replica import InProcessReplica

    dead = InProcessReplica("fvt/dead", "decode", lambda: None)
    dead._killed = True
    pane = dead.telemetry()
    assert pane["unreachable"] is True and "error" in pane


def test_fleet_status_has_per_replica_percentiles(fleet):
    status = fleet.fleet_status()
    engines = [r.get("engine", {}) for r in status["replicas"]
               if r["state"] == "healthy"]
    assert engines and all("step_ms_p50" in e and "spec_accept_rate" in e
                           for e in engines if e)


# ---------------------------------------------------------------------------
# worker-process fleet: the REAL cross-process stitch (slow tier)


@pytest.mark.slow
def test_worker_fleet_stitch_cross_process(tmp_path):
    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.fleet import FleetServingModel
    from localai_tpu.fleet.replica import WorkerReplica
    from localai_tpu.obs.trace import STORE

    app = AppConfig()
    mcfg = ModelConfig.model_validate({**TINY, "name": "fvw"})

    def factory(rid, role):
        return WorkerReplica(rid, role, mcfg, app,
                             env={"JAX_PLATFORMS": "cpu"})

    fm = FleetServingModel(mcfg, app, factory, replicas=2,
                           prefill_replicas=1, disagg_threshold=48)
    try:
        _run(fm, "cross process stitch", "trace-fvw-short", timeout=300)
        local = [t.to_dict() for t in STORE.find("trace-fvw-short")]
        out = fleetview.stitched_trace(fm, "trace-fvw-short", local)
        pairs = {(e["replica"], e["name"]) for e in out["waterfall"]}
        assert ("", "rpc") in pairs
        assert any(r.startswith("fvw/r") and n == "decode"
                   for r, n in pairs), pairs
        # the worker half came over the wire and is skew-anchored
        panes = [p for p in out["replicas"].values() if p.get("traces")]
        assert panes, out["replicas"]
        assert panes[0]["traces"][0]["attrs"]["skew_anchored"] is True

        # disagg: prefill + decode replicas in ONE cross-process trace
        _run(fm, "fleet disaggregated long prompt " * 6,
             "trace-fvw-disagg", timeout=300)
        assert fm.scheduler.prefix_transfers >= 1
        local = [t.to_dict() for t in STORE.find("trace-fvw-disagg")]
        out = fleetview.stitched_trace(fm, "trace-fvw-disagg", local)
        tagged = {e["replica"] for e in out["waterfall"] if e["replica"]}
        assert any(r.startswith("fvw/p") for r in tagged), tagged
        assert any(r.startswith("fvw/r") for r in tagged), tagged

        # merged flight across worker processes
        flight = fleetview.fleet_flight(fm)
        with_records = [rid for rid, p in flight["replicas"].items()
                        if p.get("records")]
        assert len(with_records) >= 2

        # a SIGKILLed worker degrades its pane, never raises
        victim = next(r for r in fm.pool.members()
                      if r.role == "decode")
        victim.kill()
        time.sleep(0.5)
        pane = victim.telemetry()
        assert pane.get("unreachable") is True
    finally:
        fm.close()
