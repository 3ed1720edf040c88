"""Anomaly-triggered profiler capture (obs.profiler, ISSUE 15).

Everything runs against an injected clock + fake capture_fn — the
trigger / rate-limit / cooldown / single-flight state machine is the
unit under test, not jax.profiler (the CI telemetry smoke exercises the
real capture)."""

import json
import threading
import time

import pytest

from localai_tpu.obs import profiler as obs_profiler
from localai_tpu.obs.flight import FlightRecorder
from localai_tpu.obs.metrics import Registry
from localai_tpu.obs.profiler import ProfileManager
from localai_tpu.obs.slo import SLOTracker
from localai_tpu.obs.trace import TraceStore
from localai_tpu.obs.watchdog import Watchdog


def make_pm(tmp_path, clock, caps, **kw):
    kw.setdefault("enabled", True)
    kw.setdefault("seconds", 0.01)
    kw.setdefault("max_per_hour", 4)
    kw.setdefault("cooldown_s", 10.0)
    return ProfileManager(
        out_dir=str(tmp_path), registry=kw.pop("registry", Registry()),
        clock=lambda: clock["now"],
        capture_fn=lambda path, s: caps.append(path), **kw)


def test_disabled_never_captures(tmp_path):
    caps = []
    pm = make_pm(tmp_path, {"now": 0.0}, caps, enabled=False)
    assert not pm.maybe_capture("stall", sync=True)
    assert caps == [] and pm.entries() == []


def test_capture_manifest_and_receipts(tmp_path):
    clock = {"now": 1000.0}
    caps = []
    reg = Registry()
    pm = make_pm(tmp_path, clock, caps, registry=reg)
    assert pm.maybe_capture("stall", trace_id="stall-abc",
                            reason="channel went dark", sync=True)
    assert len(caps) == 1
    entry = pm.entries()[0]
    assert entry["trigger"] == "stall"
    assert entry["trace_id"] == "stall-abc"
    assert entry["ok"] is True
    man = json.loads((tmp_path / "manifest.json").read_text())
    assert [p["id"] for p in man["profiles"]] == [entry["id"]]
    assert ('localai_profiles_captured_total{trigger="stall"} 1'
            in reg.render())


def test_cooldown_blocks_second_capture(tmp_path):
    clock = {"now": 1000.0}
    caps = []
    pm = make_pm(tmp_path, clock, caps, cooldown_s=30.0)
    assert pm.maybe_capture("stall", sync=True)
    clock["now"] += 5.0
    assert not pm.maybe_capture("stall", sync=True)
    assert pm.report()["skipped"]["cooldown"] == 1
    clock["now"] += 30.0  # cooldown over
    assert pm.maybe_capture("stall", sync=True)
    assert len(caps) == 2


def test_hourly_cap_and_refill(tmp_path):
    clock = {"now": 0.0}
    caps = []
    pm = make_pm(tmp_path, clock, caps, max_per_hour=2, cooldown_s=0.0)
    assert pm.maybe_capture("stall", sync=True)
    assert pm.maybe_capture("slo_shed", sync=True)
    assert not pm.maybe_capture("stall", sync=True)  # budget spent
    assert pm.report()["skipped"]["hourly_cap"] == 1
    clock["now"] += 3601.0  # the hour window slides
    assert pm.maybe_capture("stall", sync=True)
    assert len(caps) == 3


def test_single_flight_shared_lock(tmp_path):
    clock = {"now": 0.0}
    caps = []
    pm = make_pm(tmp_path, clock, caps, cooldown_s=0.0)
    # the manual-trace path (POST /backend/trace) holds the same lock
    assert pm.acquire_capture()
    try:
        assert not pm.maybe_capture("stall", sync=True)
        assert pm.report()["skipped"]["in_flight"] == 1
    finally:
        pm.release_capture()
    assert pm.maybe_capture("stall", sync=True)


def test_single_flight_concurrent_trigger(tmp_path):
    clock = {"now": 0.0}
    started = threading.Event()
    release = threading.Event()
    done = []

    def slow_capture(path, seconds):
        started.set()
        release.wait(5.0)
        done.append(path)

    reg = Registry()
    pm = ProfileManager(enabled=True, seconds=0.01, out_dir=str(tmp_path),
                        max_per_hour=10, cooldown_s=0.0, registry=reg,
                        clock=lambda: clock["now"],
                        capture_fn=slow_capture)
    assert pm.maybe_capture("stall")          # async capture holds the lock
    assert started.wait(5.0)
    assert not pm.maybe_capture("stall")      # second trigger mid-capture
    release.set()
    assert pm.wait_idle(5.0)
    assert len(done) == 1 and len(pm.entries()) == 1


def test_failed_capture_is_a_receipt_and_releases(tmp_path):
    clock = {"now": 0.0}

    def broken(path, seconds):
        raise RuntimeError("no backend")

    pm = ProfileManager(enabled=True, seconds=0.01, out_dir=str(tmp_path),
                        cooldown_s=0.0, registry=Registry(),
                        clock=lambda: clock["now"], capture_fn=broken)
    assert pm.maybe_capture("stall", sync=True)
    entry = pm.entries()[0]
    assert entry["ok"] is False and "no backend" in entry["error"]
    # the lock was released — the next trigger can run
    assert pm.acquire_capture()
    pm.release_capture()


def test_watchdog_stall_trigger(tmp_path):
    caps = []
    reg = Registry()
    store = TraceStore(8)
    wd = Watchdog(deadline=0.05, registry=reg, store=store,
                  poll_interval=0.01)
    pm = ProfileManager(enabled=True, seconds=0.01, out_dir=str(tmp_path),
                        cooldown_s=0.0, registry=reg,
                        capture_fn=lambda p, s: caps.append(p))
    pm.install(watchdog=wd, slo=SLOTracker(registry=reg, targets={}))
    wd.start()
    release = threading.Event()

    def hung():
        with wd.guard("pm-stall"):
            release.wait(5.0)

    t = threading.Thread(target=hung, daemon=True)
    t.start()
    deadline = time.monotonic() + 5.0
    while not pm.entries() and time.monotonic() < deadline:
        time.sleep(0.02)
    release.set()
    t.join(5.0)
    pm.wait_idle(5.0)
    wd.stop()
    pm.stop()
    entries = pm.entries()
    assert entries and entries[0]["trigger"] == "stall"
    # the capture is joined to the watchdog's forensic stall trace
    assert entries[0]["trace_id"].startswith("stall-")
    # recovery events never trigger
    assert all(e["trigger"] == "stall" for e in entries)


def test_shed_onset_trigger_fires_once(tmp_path):
    caps = []
    reg = Registry()
    clock = {"now": 1000.0}
    slo = SLOTracker(registry=reg, clock=lambda: clock["now"],
                     targets={"ttft_ms": 0.001}, burn_threshold=1.0,
                     recover_burn=1.0, min_events=3)
    pm = ProfileManager(enabled=True, seconds=0.01, out_dir=str(tmp_path),
                        cooldown_s=0.0, registry=reg,
                        clock=lambda: clock["now"],
                        capture_fn=lambda p, s: caps.append(p))
    pm.install(slo=slo, watchdog=Watchdog(deadline=60, registry=reg,
                                          store=TraceStore(4)))
    for _ in range(4):
        slo.observe("hot", ttft_ms=50.0)
    assert slo.should_shed("hot")
    assert slo.should_shed("hot")  # standing shed: onset already fired
    pm.wait_idle(5.0)
    pm.stop()
    sheds = [e for e in pm.entries() if e["trigger"] == "slo_shed"]
    assert len(sheds) == 1 and sheds[0]["model"] == "hot"


def test_regression_detector(tmp_path):
    caps = []
    pm = ProfileManager(enabled=True, seconds=0.01, out_dir=str(tmp_path),
                        cooldown_s=0.0, max_per_hour=100,
                        regression_ratio=2.0, registry=Registry(),
                        capture_fn=lambda p, s: caps.append(p))
    rec = FlightRecorder(256)

    def feed(n, ms):
        for _ in range(n):
            rec.record(program="decode_n", steps=8, dispatch_ms=ms,
                       occupancy=0.5, queue_depth=0, kv_utilization=0.1,
                       tokens=8)

    pm.watch_flight("m", rec)
    feed(64, 16.0)                      # 2 ms/step baseline
    assert pm.check_regressions() == []  # healthy: no trigger
    feed(32, 20.0)                      # 2.5 ms/step: below the 2x ratio
    assert pm.check_regressions() == []
    feed(32, 80.0)                      # 10 ms/step: 4-5x regression
    assert pm.check_regressions() == ["m"]
    pm.wait_idle(5.0)
    assert pm.entries()[0]["trigger"] == "step_p99_regression"
    assert pm.entries()[0]["model"] == "m"
    # the same records never re-trigger (wait for a fresh window)
    assert pm.check_regressions() == []
    # compile-bearing rows are excluded from both windows
    rec2 = FlightRecorder(256)
    pm.watch_flight("m2", rec2)
    for _ in range(80):
        rec2.record(program="decode_n", steps=8, dispatch_ms=16.0,
                    occupancy=0.5, queue_depth=0, kv_utilization=0.1,
                    tokens=8)
    for _ in range(32):
        rec2.record(program="decode_n", steps=8, dispatch_ms=400.0,
                    occupancy=0.5, queue_depth=0, kv_utilization=0.1,
                    tokens=8, compile=True)
    assert "m2" not in pm.check_regressions()


def test_watch_flight_weakref_drops_dead_ring(tmp_path):
    pm = make_pm(tmp_path, {"now": 0.0}, [])
    rec = FlightRecorder(8)
    pm.watch_flight("gone", rec)
    del rec
    import gc

    gc.collect()
    assert pm.check_regressions() == []
    with pm._lock:
        assert "gone" not in pm._flights


def test_install_idempotent_and_stop_deregisters(tmp_path):
    reg = Registry()
    wd = Watchdog(deadline=60, registry=reg, store=TraceStore(4))
    slo = SLOTracker(registry=reg, targets={})
    pm = make_pm(tmp_path, {"now": 0.0}, [], registry=reg)
    pm.install(watchdog=wd, slo=slo)
    pm.install(watchdog=wd, slo=slo)  # second install is a no-op
    assert len(wd._callbacks) == 1
    assert len(slo._shed_callbacks) == 1
    # stop() DEREGISTERS: an install after stop registers exactly once
    # (a leaked hook would fire two captures per stall)
    pm.stop()
    assert wd._callbacks == [] and slo._shed_callbacks == []
    pm.install(watchdog=wd, slo=slo)
    assert len(wd._callbacks) == 1 and len(slo._shed_callbacks) == 1
    pm.stop()


# -- one capture, one clock: names in the profiler's own trace (ISSUE 25) ----


def test_one_place_starts_the_profiler():
    """``jax.profiler.start_trace`` is called from obs.profiler.capture and
    nowhere else in the package: both surfaces share it."""
    import pathlib
    import re

    import localai_tpu

    root = pathlib.Path(localai_tpu.__file__).parent
    calls = {str(p.relative_to(root))
             for p in root.rglob("*.py")
             if re.search(r"\bstart_trace\(", p.read_text())}
    assert calls == {"obs/profiler.py"}
    assert ProfileManager()._capture_fn is obs_profiler.capture


@pytest.mark.parametrize("asked, level", [(False, 0), (True, 1)])
def test_capture_turns_the_python_tracer_off_unless_asked(
        monkeypatch, tmp_path, asked, level):
    import jax

    seen = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda path, profiler_options=None: seen.append(profiler_options))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    obs_profiler.capture(str(tmp_path), 0.0, python_tracer=asked)
    assert seen[0].python_tracer_level == level
    assert seen[0].host_tracer_level >= 1     # TraceAnnotations are recorded


def test_backend_trace_python_tracer_is_off_unless_the_body_asks(
        tmp_path, monkeypatch):
    """The capture behind POST /backend/trace is obs.profiler's one capture
    function: Python frames are recorded only for {"python_tracer": true}."""
    import jax

    levels = []
    monkeypatch.setattr(
        jax.profiler, "start_trace",
        lambda path, profiler_options=None: levels.append(
            profiler_options.python_tracer_level))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    from test_api import _ServerThread, make_state

    state = make_state(tmp_path, write_tiny=True)
    srv = _ServerThread(state)
    try:
        import httpx

        with httpx.Client(base_url=srv.base, timeout=120.0) as c:
            for body in ({"seconds": 0.1},
                         {"seconds": 0.1, "python_tracer": False},
                         {"seconds": 0.1, "python_tracer": True}):
                assert c.post("/backend/trace", json=body).status_code == 200
            assert c.post("/backend/trace", json={
                "seconds": 0.1, "python_tracer": "yes"}).status_code == 400
    finally:
        srv.stop()
    assert levels == [0, 0, 1]


@pytest.fixture(scope="module")
def paged_runner():
    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models.registry import resolve_model

    tiny = resolve_model("debug:tiny", dtype="float32")
    return ModelRunner(tiny.cfg, tiny.params, num_slots=4, max_ctx=128,
                       paged=True, kv_block_tokens=16, prefill_chunk=32,
                       kv_dtype="float32", attn_impl="pallas_interpret")


def test_a_capture_holds_the_schedulers_phases(paged_runner, tmp_path):
    """A real 0.3 s capture on the CPU while a request is served: the
    engine thread's line of the host plane carries the ``sched.*``
    annotations, no Python frame (the tracer is off by default)."""
    from jax.profiler import ProfileData

    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.utils.tokenizer import ByteTokenizer

    s = Scheduler(paged_runner, ByteTokenizer())
    try:
        done, served = threading.Event(), []

        def serve():    # requests back to back until the capture is over
            while not done.is_set():
                served.append(s.generate(GenRequest(
                    prompt=list(b"x" * 40), max_new_tokens=32,
                    ignore_eos=True)).finish_reason)

        s.generate(GenRequest(prompt=list(b"x" * 40), max_new_tokens=2))
        t = threading.Thread(target=serve)
        t.start()
        try:
            obs_profiler.capture(str(tmp_path), 0.3)
        finally:
            done.set()
            t.join(120)
        assert not t.is_alive() and set(served) == {"length"}
    finally:
        s.shutdown()
    path = next(tmp_path.rglob("*.xplane.pb"))
    host = [p for p in ProfileData.from_file(str(path)).planes
            if p.name == "/host:CPU"]
    by_line = {ln.name: {ev.name for ev in ln.events}
               for p in host for ln in p.lines}
    phases = {n for names in by_line.values() for n in names
              if n.startswith("sched.")}
    assert {"sched.admit", "sched.count", "sched.decode_launch",
            "sched.wait_device", "sched.process", "sched.record",
            "sched.free"} <= phases
    # one thread wrote them all: the engine thread
    assert sum(1 for names in by_line.values()
               if any(n.startswith("sched.") for n in names)) == 1
    assert not any(n.startswith("$") for names in by_line.values()
                   for n in names)        # "$file:line fn" = a Python frame


def test_a_capture_holds_every_launch_under_its_phase(paged_runner, tmp_path):
    """A real 0.3 s capture on the CPU: the engine thread's line holds a
    ``sched.launch/<n>`` for each serving program enqueued meanwhile, each
    INSIDE the phase that was open (a decode launch in
    ``sched.decode_launch``, a chunk in ``sched.prefill_chunk``), one a
    phase, and every n is the ``launch`` of a ring row of that kind; the
    line comes sorted, an enclosing event before what it encloses (what the
    benchmark's reduction of a gap's owner rests on)."""
    from jax.profiler import ProfileData

    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.utils.tokenizer import ByteTokenizer

    # a ring that keeps the capture's rows however long a loaded machine
    # takes to write the capture out: serving goes on meanwhile, and the
    # default 512 rows had rolled over a 0.3 s window's by then
    s = Scheduler(paged_runner, ByteTokenizer(), flight=FlightRecorder(1 << 16))
    try:
        done = threading.Event()

        def serve():
            while not done.is_set():
                s.generate(GenRequest(prompt=list(b"y" * 70),
                                      max_new_tokens=16, ignore_eos=True))

        s.generate(GenRequest(prompt=list(b"y" * 70), max_new_tokens=2))
        t = threading.Thread(target=serve)
        t.start()
        try:
            obs_profiler.capture(str(tmp_path), 0.3)
        finally:
            done.set()
            t.join(120)
        rows = {r["launch"]: r["program"] for r in s.flight.snapshot()}
    finally:
        s.shutdown()
    path = next(tmp_path.rglob("*.xplane.pb"))
    lines = [[(ev.start_ns, ev.start_ns + ev.duration_ns, ev.name)
              for ev in ln.events if ev.name.startswith("sched.")]
             for p in ProfileData.from_file(str(path)).planes
             if p.name == "/host:CPU" for ln in p.lines]
    line = max(lines, key=len)
    assert sum(bool(ln) for ln in lines) == 1       # the engine thread's
    assert line == sorted(line, key=lambda ev: (ev[0], -ev[1]))
    launches = [ev for ev in line if ev[2].startswith("sched.launch/")]
    phases = [ev for ev in line if not ev[2].startswith("sched.launch/")]
    assert launches
    seen = set()
    for lo, hi, name in launches:
        n = int(name.rsplit("/", 1)[1])
        around = [p for p in phases if p[0] <= lo and hi <= p[1]]
        assert len(around) == 1, (name, around)
        kind = {"sched.decode_launch": "decode",
                "sched.prefill_chunk": "prefill_chunk"}[around[0][2]]
        assert rows[n].startswith(kind), (name, rows[n], around)
        # one launch a phase: the phase's own time is its launch's
        assert sum(around[0][0] <= l[0] < around[0][1]
                   for l in launches) == 1
        seen.add(kind)
    # a loaded machine fits a handful of launches into 0.3 s: decode steps
    # always, a chunk nearly always
    assert "decode" in seen and seen <= {"decode", "prefill_chunk"}
    ns = [int(ev[2].rsplit("/", 1)[1]) for ev in launches]
    assert ns == sorted(set(ns))
    # PR 53: the loop's two bookkeeping stretches and the drop of a drained
    # dispatch's result are phases like the six, top-level (no phase lies
    # inside another: only a launch is nested), a ``sched.count`` directly
    # in front of each decode launch's phase, a ``sched.record`` behind each
    # wait (the clocks' reading) and behind each ``sched.process`` of a
    # decode dispatch (the row's write), a ``sched.free`` behind that (and
    # behind a first token's ``sched.process``)
    for i, (lo, hi, name) in enumerate(phases):
        assert not any(p[0] <= lo and hi <= p[1]
                       for j, p in enumerate(phases) if j != i), name
    order = [name for _, _, name in phases]
    assert {"sched.count", "sched.record", "sched.free"} <= set(order)
    for i, name in enumerate(order[1:], 1):
        if name == "sched.decode_launch":
            assert order[i - 1] == "sched.count"
        if name == "sched.record":
            assert order[i - 1] in ("sched.wait_device", "sched.process")
        if name == "sched.process":
            assert order[i - 1] == "sched.record"
        if name == "sched.free":
            assert order[i - 1] in ("sched.record", "sched.process")
    assert order.count("sched.record") <= 2 * order.count("sched.process")


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_lowered_programs_carry_the_scope_names(paged_runner, program):
    """Scopes are metadata of the lowered program (no profiler needed): the
    model's parts, the pool's writes and the kernel are named in both
    serving programs."""
    import jax
    import jax.numpy as jnp

    r = paged_runner
    if program == "decode":
        lowered = jax.jit(r._decode_paged_fn).lower(
            r.params, r.kv, r.state, r.block_tables)
        want = ("decode/", "attn.paged_decode", "paged_decode_attn")
    else:
        i32 = jnp.int32
        lowered = jax.jit(
            r._prefill_paged_fn, static_argnames=("bucket", "sample")).lower(
            r.params, r.kv, r.state, jnp.zeros((1, 32), i32), i32(5),
            i32(0), jnp.zeros((r.max_blocks,), i32), i32(0),
            jnp.zeros((r.cfg.vocab_size,), i32), bucket=32, sample=True)
        want = ("prefill/", "attn.prefill", "kv_pool.gather")
    text = lowered.as_text(debug_info=True)
    for name in want + ("embed", "layers", "attn.qkv", "attn.rope",
                        "kv_pool.write", "attn.out", "mlp", "final_norm",
                        "lm_head", "sample"):
        assert name in text, name
