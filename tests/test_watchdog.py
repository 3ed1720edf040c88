"""Stall watchdog + device health + JSON logging (obs introspection).

The unit half of the round-6 obs surfaces: watchdog trip/recover semantics
with the thread-stack forensic span, the timeout-guarded device probe, the
live-array HBM census, the compiled-program cost catalog, and the JSON log
formatter's contextvar trace-id binding. The HTTP halves (/debug/devices,
/debug/programs, stall spans at /v1/traces) live in test_api.py.
"""

import json
import logging
import threading
import time

import pytest

from localai_tpu.obs import Registry, TraceStore, Watchdog
from localai_tpu.obs import compile as obs_compile
from localai_tpu.obs import device as obs_device
from localai_tpu.obs import logging as obs_logging

# -- watchdog ---------------------------------------------------------------


@pytest.fixture()
def wd_parts():
    reg, store = Registry(), TraceStore()
    wd = Watchdog(deadline=0.08, registry=reg, store=store,
                  poll_interval=0.02)
    yield wd, reg, store
    wd.stop()


def test_idle_channel_never_stalls(wd_parts):
    wd, reg, _store = wd_parts
    wd.pulse("idle")                      # known but nothing armed
    time.sleep(0.12)
    assert wd.check() == []
    assert not wd.stalled()


def test_armed_silence_trips_and_recovery_clears(wd_parts):
    wd, reg, store = wd_parts
    events = []
    wd.on_stall(events.append)
    wd.arm("engine")
    time.sleep(0.12)                      # silence past the deadline
    trips = wd.check()
    assert [e.kind for e in trips] == ["stall"]
    assert wd.stalled("engine")
    text = reg.render()
    assert 'localai_engine_stalled{channel="engine"} 1' in text
    assert 'localai_stalls_total{channel="engine"} 1' in text
    # forensic span: kind="stall", one thread event per live thread, each
    # carrying a formatted stack
    stall = [t for t in store.recent() if t.kind == "stall"]
    assert stall, "no forensic trace recorded"
    spans = stall[0].spans()
    assert spans and all("stack" in s.attrs for s in spans)
    assert any("test_armed_silence" in s.attrs["stack"] for s in spans), (
        "the dump must contain this very test frame")
    assert stall[0].trace_id == trips[0].trace_id
    # progress clears the stall (gauge → 0) and fires the recovery event
    wd.pulse("engine")
    assert not wd.stalled("engine")
    assert 'localai_engine_stalled{channel="engine"} 0' in reg.render()
    assert [e.kind for e in events] == ["stall", "recovered"]
    # steady state afterwards: no re-trip without new silence
    assert wd.check() == []
    wd.disarm("engine")


def test_guard_context_manager_and_background_thread(wd_parts):
    wd, reg, store = wd_parts
    tripped = threading.Event()
    wd.on_stall(lambda e: e.kind == "stall" and tripped.set())
    wd.start()
    release = threading.Event()

    def hung_dispatch():
        with wd.guard("device"):
            release.wait(5.0)             # the simulated wedged device

    t = threading.Thread(target=hung_dispatch, daemon=True)
    t.start()
    assert tripped.wait(2.0), "background checker never tripped"
    assert wd.stalled("device")
    status = wd.status()["device"]
    assert status["armed"] == 1 and status["stalled"]
    release.set()                         # the device answers again
    t.join(2.0)
    deadline = time.monotonic() + 2.0
    while wd.stalled("device") and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not wd.stalled("device")


def test_stall_dump_includes_flight_snapshot(wd_parts):
    """The round-7 forensic upgrade: a registered flight-ring context
    provider attaches the preceding engine timeline to every stall dump,
    and a broken provider degrades to an error marker instead of killing
    the dump."""
    from localai_tpu.obs import FlightRecorder

    wd, _reg, store = wd_parts
    fl = FlightRecorder(8)
    fl.record(program="decode_n", steps=4, dispatch_ms=8.0, occupancy=0.5,
              queue_depth=2, kv_utilization=0.25, tokens=16)
    fl.record(program="decode_n", steps=4, dispatch_ms=12.0, occupancy=0.5,
              queue_depth=3, kv_utilization=0.3, tokens=16)
    wd.add_context("flight:engine", lambda: {
        "records": fl.snapshot(limit=32), **fl.percentiles()})
    wd.add_context("broken", lambda: 1 / 0)
    try:
        wd.arm("engine")
        time.sleep(0.12)
        trips = wd.check()
        assert [e.kind for e in trips] == ["stall"]
        stall = [t for t in store.recent() if t.kind == "stall"][0]
        ctx = {s.attrs.get("source"): s for s in stall.spans()
               if s.name == "context"}
        assert set(ctx) == {"flight:engine", "broken"}
        flight = ctx["flight:engine"].attrs
        assert [r["queue_depth"] for r in flight["records"]] == [2, 3]
        assert flight["step_ms_p50"] == pytest.approx(2.5)
        assert flight["samples"] == 2
        assert ctx["broken"].attrs["error"] == "provider failed"
        # the stack half of the dump still stands next to the contexts
        assert any(s.name == "thread" for s in stall.spans())
    finally:
        wd.disarm("engine")
        wd.remove_context("flight:engine")
        wd.remove_context("broken")


def test_remove_context_stops_attaching(wd_parts):
    wd, _reg, store = wd_parts
    wd.add_context("gone", lambda: {"x": 1})
    wd.remove_context("gone")
    wd.arm("engine")
    time.sleep(0.12)
    wd.check()
    wd.disarm("engine")
    stall = [t for t in store.recent() if t.kind == "stall"][0]
    assert not [s for s in stall.spans() if s.name == "context"]


def test_check_refreshes_progress_age_gauge(wd_parts):
    wd, reg, _store = wd_parts
    wd.arm("rpc")
    time.sleep(0.03)
    wd.check()
    assert 'localai_last_progress_age_seconds{channel="rpc"}' in reg.render()
    wd.disarm("rpc")


# -- device probe + census --------------------------------------------------


def test_probe_device_ok_sets_gauges():
    reg = Registry()
    res = obs_device.probe_device(timeout=30.0, registry=reg)
    assert res.ok and res.seconds > 0
    text = reg.render()
    assert "localai_device_ok 1" in text
    assert "localai_device_probe_seconds" in text


def test_probe_device_timeout_path():
    reg = Registry()
    res = obs_device.probe_device(
        timeout=0.1, registry=reg, fn=lambda: time.sleep(10))
    assert not res.ok
    assert "timeout" in res.error
    assert "localai_device_ok 0" in reg.render()


def test_probe_device_error_path():
    def boom():
        raise RuntimeError("device reset")

    res = obs_device.probe_device(timeout=5.0, registry=Registry(), fn=boom)
    assert not res.ok and "device reset" in res.error


def test_hbm_census_attributes_categories():
    import jax.numpy as jnp

    reg = Registry()
    kv = jnp.zeros((8, 16), jnp.float32)
    w = jnp.ones((32,), jnp.float32)
    out = obs_device.hbm_census(
        {"kv_cache": [kv], "weights": [w]}, registry=reg)
    assert out["by_category"]["kv_cache"] >= kv.nbytes
    assert out["by_category"]["weights"] >= w.nbytes
    assert out["arrays"] >= 2
    assert 'localai_hbm_live_bytes{category="kv_cache"}' in reg.render()


def test_known_arrays_from_runner_shape():
    class FakeCache:
        def stacked(self):
            import jax.numpy as jnp

            return (jnp.zeros((2, 2)), jnp.zeros((2, 2)))

    class FakeRunner:
        kv = FakeCache()
        params = {"w": __import__("jax.numpy", fromlist=["zeros"]).zeros(4)}

    known = obs_device.known_arrays([FakeRunner()])
    assert len(known["kv_cache"]) == 2 and len(known["weights"]) == 1


# -- program cost catalog ---------------------------------------------------


def test_catalog_reports_cost():
    import jax
    import jax.numpy as jnp

    reg = Registry()
    watched = obs_compile.watch(
        jax.jit(lambda x, *, n: (x @ x) * n, static_argnames=("n",)),
        "toyprog", registry=reg)
    x = jnp.ones((16, 16), jnp.float32)
    watched(x, n=2)
    watched(x, n=2)
    rep = obs_compile.CATALOG.report()
    rows = [r for r in rep if r["program"] == "toyprog"]
    assert rows, "watched program missing from the catalog"
    row = rows[0]
    assert row["dispatches"] == 2
    assert row["flops"] > 0 and row["bytes_accessed"] > 0
    # XLA's account alone: no dispatch's wall is set against it
    assert not {"dispatch_seconds_ema", "achieved_gbps", "achieved_gflops",
                "bandwidth_fraction", "flops_fraction"} & set(row)


def test_catalog_survives_dead_program():
    import jax
    import jax.numpy as jnp

    watched = obs_compile.watch(jax.jit(lambda x: x + 1), "ephemeral",
                                registry=Registry())
    watched(jnp.ones(4))
    del watched
    import gc

    gc.collect()
    rep = obs_compile.CATALOG.report(harvest=True)
    rows = [r for r in rep if r["program"] == "ephemeral"]
    # either collected (error noted) or still cached — never a crash
    assert rows and (rows[0].get("cost_error") or "flops" in rows[0])


# -- JSON logging -----------------------------------------------------------


def _one_record(logger_name="t", msg="hello", exc=False, **extra):
    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(self.format(record))

    logger = logging.getLogger(logger_name)
    logger.propagate = False
    h = Capture()
    h.setFormatter(obs_logging.JsonFormatter())
    logger.addHandler(h)
    logger.setLevel(logging.INFO)
    try:
        if exc:
            try:
                raise ValueError("kaboom")
            except ValueError:
                logger.exception(msg, extra=extra)
        else:
            logger.info(msg, extra=extra)
    finally:
        logger.removeHandler(h)
    return json.loads(records[0])


def test_json_formatter_basic_shape():
    out = _one_record(msg="engine up", component="scheduler")
    assert out["message"] == "engine up"
    assert out["level"] == "info"
    assert out["logger"] == "t"
    assert out["component"] == "scheduler"   # extra= passthrough
    assert out["ts"].endswith("Z")
    assert "trace_id" not in out             # nothing bound


def test_json_formatter_binds_and_unbinds_trace_id():
    token = obs_logging.bind_trace_id("trace-json-1")
    try:
        assert obs_logging.current_trace_id() == "trace-json-1"
        assert _one_record()["trace_id"] == "trace-json-1"
    finally:
        obs_logging.unbind_trace_id(token)
    assert obs_logging.current_trace_id() == ""
    assert "trace_id" not in _one_record()


def test_json_formatter_exceptions_and_threads():
    out = _one_record(msg="boom", exc=True)
    assert "kaboom" in out["exc"]
    # contextvars do NOT leak across threads: a fresh thread logs without
    # the caller's trace id
    token = obs_logging.bind_trace_id("outer")
    try:
        seen = {}

        def run():
            seen["tid"] = obs_logging.current_trace_id()

        t = threading.Thread(target=run)
        t.start()
        t.join()
        assert seen["tid"] == ""
    finally:
        obs_logging.unbind_trace_id(token)


def test_setup_configures_root(capsys):
    import io

    buf = io.StringIO()
    obs_logging.setup("json", logging.INFO, stream=buf)
    try:
        logging.getLogger("setup-test").info("structured")
        line = buf.getvalue().strip().splitlines()[-1]
        assert json.loads(line)["message"] == "structured"
    finally:
        obs_logging.setup("text", logging.WARNING)


def test_context_executor_propagates_trace_id():
    """run_in_executor does not copy contextvars; the API's ContextExecutor
    must, so executor-side log lines keep the request trace id."""
    from concurrent.futures import ThreadPoolExecutor

    from localai_tpu.api.server import ContextExecutor

    token = obs_logging.bind_trace_id("ctx-exec-1")
    try:
        with ContextExecutor(max_workers=1) as pool:
            assert pool.submit(
                obs_logging.current_trace_id).result(5) == "ctx-exec-1"
        with ThreadPoolExecutor(max_workers=1) as plain:
            assert plain.submit(
                obs_logging.current_trace_id).result(5) == ""
    finally:
        obs_logging.unbind_trace_id(token)


def test_trip_recovery_race_never_latches_gauge(wd_parts):
    """A recovery racing the trip emission (progress lands between check()
    marking the channel stalled and the gauge write) must still leave
    engine_stalled at 0 — the emission re-reads current state."""
    wd, reg, _store = wd_parts
    wd.arm("race")
    time.sleep(0.12)
    # replicate the racy interleaving deterministically: mark stalled (what
    # check() does under the lock) ...
    with wd._lock:
        wd._channels["race"].stalled = True
    wd.pulse("race")            # ... recovery emits FIRST (gauge -> 0)
    wd._emit_stall("race", 1.0)  # ... then the trip's late emission
    assert 'localai_engine_stalled{channel="race"} 0' in reg.render()
    wd.disarm("race")


def test_catalog_same_program_name_two_watchers_do_not_collide():
    """Two runners watch same-named programs whose top-level args are
    pytrees (identical shape keys); entries must not overwrite."""
    import jax
    import jax.numpy as jnp

    reg = Registry()
    f1 = obs_compile.watch(jax.jit(lambda d: d["x"] + 1), "dupprog",
                           registry=reg)
    f2 = obs_compile.watch(jax.jit(lambda d: d["x"] * 2), "dupprog",
                           registry=reg)
    arg = {"x": jnp.ones(4)}
    f1(arg)
    f1(arg)
    f2(arg)
    rows = [r for r in obs_compile.CATALOG.report(harvest=False)
            if r["program"] == "dupprog"]
    assert len(rows) == 2, rows
    assert sorted(r["dispatches"] for r in rows) == [1, 2]
    assert rows[0]["instance"] != rows[1]["instance"]


def test_probe_single_flight_does_not_leak_threads_per_call():
    """Against a wedged device, repeated default probes must join the ONE
    in-flight probe thread instead of parking a new thread per call."""
    import localai_tpu.obs.device as dev

    block = threading.Event()
    counts = {"n": 0}

    def wedged():
        counts["n"] += 1
        block.wait(30.0)

    # install the wedged probe as the DEFAULT (fn=None path uses the
    # latch); restore afterwards
    real = dev._default_probe
    dev._default_probe = wedged
    try:
        with dev._probe_lock:
            prior = dict(dev._probe_inflight)
            dev._probe_inflight.update(thread=None, box=None)
        r1 = dev.probe_device(timeout=0.1, registry=Registry())
        r2 = dev.probe_device(timeout=0.1, registry=Registry())
        assert not r1.ok and not r2.ok
        assert counts["n"] == 1, "second probe spawned a new thread"
    finally:
        block.set()
        time.sleep(0.05)
        dev._default_probe = real
        with dev._probe_lock:
            dev._probe_inflight.update(**prior)
