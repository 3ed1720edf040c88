"""Fault injection + self-healing: registry predicates, the NaN decode
guard + slot quarantine, block-pool invariants, and the supervisor's
stall → rebuild → failed escalation — on the tiny debug model."""

import time

import pytest

from localai_tpu import faults
from localai_tpu.engine.paged import BlockAllocator
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.engine.scheduler import GenRequest, Scheduler
from localai_tpu.faults import EngineSupervisor, FaultInjected, FaultSpec
from localai_tpu.models.registry import resolve_model
from localai_tpu.obs.engine import EngineTelemetry
from localai_tpu.obs.metrics import Registry
from localai_tpu.obs.slo import SLOTracker
from localai_tpu.obs.trace import TraceStore
from localai_tpu.obs.watchdog import Watchdog
from localai_tpu.utils.tokenizer import ByteTokenizer


@pytest.fixture(autouse=True)
def clean_registry():
    faults.clear()
    yield
    faults.clear()
    assert faults.active() is False


@pytest.fixture(scope="module")
def tiny():
    return resolve_model("debug:tiny", dtype="float32")


def _engine(tiny, name="faults", *, watchdog=None, registry=None,
            store=None, **kw):
    registry = registry or Registry()
    runner = ModelRunner(tiny.cfg, tiny.params, num_slots=4, max_ctx=256,
                         prefill_buckets=[16, 32], kv_dtype="float32",
                         paged=True, kv_block_tokens=16, prefill_chunk=16,
                         **kw)
    sched = Scheduler(
        runner, ByteTokenizer(),
        telemetry=EngineTelemetry(
            model=name, registry=registry, store=store or TraceStore(),
            slo=SLOTracker(registry=registry, targets={})),
        watchdog=watchdog,
    )
    return runner, sched


def _req(text, **kw):
    kw.setdefault("temperature", 0.0)
    return GenRequest(prompt=ByteTokenizer().encode(text), **kw)


# -- registry ------------------------------------------------------------


def test_registry_arm_sets_and_clear_resets_active():
    assert faults.active() is False
    faults.arm(FaultSpec(site="engine.dispatch"))
    assert faults.active() is True
    assert faults.clear() == 1
    assert faults.active() is False


def test_registry_rejects_unknown_site_and_bad_fields():
    with pytest.raises(ValueError):
        faults.arm(FaultSpec(site="engine.dipsatch"))
    with pytest.raises(ValueError):
        faults.arm(FaultSpec(site="engine.dispatch", after=-1))
    assert faults.active() is False


def test_fire_predicate_after_times_match():
    faults.arm(FaultSpec(site="engine.dispatch", after=2, times=2,
                         match="decode"))
    assert faults.fire("engine.dispatch", key="prefill") is None  # no match
    assert faults.fire("engine.drain", key="decode") is None      # site
    assert faults.fire("engine.dispatch", key="decode") is None   # skip 1
    assert faults.fire("engine.dispatch", key="decode") is None   # skip 2
    assert faults.fire("engine.dispatch", key="decode") is not None
    assert faults.fire("engine.dispatch", key="decode") is not None
    assert faults.fire("engine.dispatch", key="decode") is None   # exhausted
    snap = faults.snapshot()[0]
    assert snap["fired"] == 2 and snap["hits"] == 5


def test_apply_raise_and_sleep_modes():
    faults.arm(FaultSpec(site="engine.dispatch", mode="raise", times=1))
    with pytest.raises(FaultInjected):
        faults.apply("engine.dispatch", key="decode")
    faults.clear()
    faults.arm(FaultSpec(site="engine.drain", mode="hang", delay_s=0.05,
                         times=1))
    t0 = time.monotonic()
    assert faults.apply("engine.drain").mode == "hang"
    assert time.monotonic() - t0 >= 0.05


def test_parse_spec_and_env_install():
    spec = faults.parse_spec(
        "engine.drain", "mode=hang,delay_s=1.5,after=2,times=3,match=x")
    assert (spec.mode, spec.delay_s, spec.after, spec.times, spec.match) \
        == ("hang", 1.5, 2, 3, "x")
    with pytest.raises(ValueError):
        faults.parse_spec("engine.drain", "bogus_field=1")
    armed = faults.install_from_env({
        "LOCALAI_FAULT_ENGINE_DISPATCH": "mode=raise,times=1",
        "LOCALAI_FAULT_NO_SUCH_SITE": "mode=raise",   # ignored, logged
        "OTHER_VAR": "x",
    })
    assert armed == 1
    assert faults.snapshot()[0]["site"] == "engine.dispatch"


# -- block-pool invariants ----------------------------------------------


def test_check_invariants_clean_allocator():
    a = BlockAllocator(num_blocks=10, block_tokens=16, max_blocks_per_seq=8)
    assert a.check_invariants() == []
    a.allocate(0, 48, prompt=list(range(40)))
    a.allocate(1, 32)
    assert a.check_invariants() == []
    a.register_prefix(0, list(range(40)))
    assert a.check_invariants() == []
    a.release(0)
    a.release(1)
    assert a.check_invariants() == []
    st = a.stats()
    assert st.free + st.cached == st.total


def test_check_invariants_detects_corruption():
    a = BlockAllocator(num_blocks=10, block_tokens=16, max_blocks_per_seq=8)
    a.allocate(0, 48)
    a._ref[a.tables[0][0]] = 0            # leaked refcount
    assert any("refcount" in p for p in a.check_invariants())
    a = BlockAllocator(num_blocks=10, block_tokens=16, max_blocks_per_seq=8)
    a._free.append(a._free[-1])           # duplicate free entry
    assert any("duplicate" in p for p in a.check_invariants())
    a = BlockAllocator(num_blocks=10, block_tokens=16, max_blocks_per_seq=8)
    bid = a._free.pop()                   # vanished block (leak)
    assert any(f"block {bid} leaked" in p for p in a.check_invariants())
    a = BlockAllocator(num_blocks=10, block_tokens=16, max_blocks_per_seq=8)
    bid = a._free.pop()
    a._ref[bid] = 1                       # refcounted but unreachable
    assert any("no table or pool entry" in p
               for p in a.check_invariants())


def test_injected_pool_exhaustion():
    a = BlockAllocator(num_blocks=10, block_tokens=16, max_blocks_per_seq=8)
    faults.arm(FaultSpec(site="paged.allocate", mode="exhaust", times=1))
    assert a.allocate(0, 32) is None      # injected: pool reports full
    assert a.allocate(0, 32) is not None  # schedule exhausted: real answer
    assert a.check_invariants() == []


# -- NaN/inf decode guard ------------------------------------------------


def test_nan_guard_fails_only_poisoned_slot_and_quarantines(tiny):
    reg = Registry()
    runner, sched = _engine(tiny, "nan", registry=reg)
    try:
        ref = sched.generate(_req("co-batched survivor", max_new_tokens=16),
                             timeout=120)
        faults.arm(FaultSpec(site="decode.nan", mode="nan",
                             match="poison-me", times=1))
        poisoned = sched.submit(_req("poison target", max_new_tokens=300,
                                     correlation_id="poison-me"))
        survivor = sched.submit(_req("co-batched survivor",
                                     max_new_tokens=16))
        poisoned.result(120)
        survivor.result(120)
        # only the poisoned request fails; the co-batched one is
        # byte-identical to the unpoisoned greedy reference
        assert poisoned.finish_reason == "error"
        assert survivor.finish_reason in ("stop", "length")
        assert survivor.token_ids == ref.token_ids
        assert sched.nan_rows == 1
        m = sched.metrics()
        assert m["nan_rows"] == 1
        assert m["quarantined_slots"] == 1
        assert 'localai_nan_rows_total{model="nan"} 1' in reg.render()
        # the quarantined slot is out of admission now, and returns to
        # service after the quarantine window of dispatches passes
        deadline = time.monotonic() + 60
        while sched._quarantined and time.monotonic() < deadline:
            sched.generate(_req("quarantine drain", max_new_tokens=40),
                           timeout=120)
        assert not sched._quarantined
        assert runner.allocator.check_invariants() == []
    finally:
        sched.shutdown()


def test_quarantine_gauge_exported():
    from localai_tpu.obs.metrics import update_engine_gauges

    reg = Registry()
    update_engine_gauges("m", {"quarantined_slots": 2}, registry=reg)
    assert 'localai_quarantined_slots{model="m"} 2' in reg.render()


# -- self-healing supervisor --------------------------------------------


def _supervised(tiny, name, **sup_kw):
    reg = Registry()
    store = TraceStore()
    wd = Watchdog(deadline=0.4, registry=reg, store=store,
                  poll_interval=0.1)
    runner, sched = _engine(tiny, name, watchdog=wd, registry=reg,
                            store=store)
    sup_kw.setdefault("max_rebuilds", 3)
    sup_kw.setdefault("backoff_s", 0.05)
    sup_kw.setdefault("probe_timeout_s", 60.0)
    sup = EngineSupervisor(sched, registry=reg, **sup_kw)
    return reg, wd, runner, sched, sup


def test_stall_escalates_to_rebuild_and_recovers(tiny):
    reg, wd, runner, sched, sup = _supervised(tiny, "rebuild")
    try:
        wedged = sched.submit(_req("about to wedge", max_new_tokens=400))
        deadline = time.monotonic() + 60
        while wedged.t_first_token is None and time.monotonic() < deadline:
            time.sleep(0.02)
        faults.arm(FaultSpec(site="engine.drain", mode="hang",
                             delay_s=2.0, times=1))
        wedged.result(90)
        assert wedged.finish_reason == "error"   # drained with clean error
        deadline = time.monotonic() + 60
        while sched.rebuilds == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sched.rebuilds == 1
        assert not sched.failed
        faults.clear()
        # probe passed and the fresh engine thread serves again
        after = sched.generate(_req("after rebuild", max_new_tokens=8),
                               timeout=120)
        assert after.finish_reason in ("stop", "length")
        assert runner.allocator.check_invariants() == []
        assert 'localai_engine_rebuilds_total{model="rebuild"} 1' \
            in reg.render()
        # a healthy completion reset the incident budget
        assert sup.attempts == 0
    finally:
        sched.shutdown()
        wd.stop()


def test_cold_compile_is_not_taken_for_a_stall(tiny):
    """A cold XLA compile of an 8B program runs for tens of seconds on the
    chip, the stall deadline is 60 s, and the supervisor's cure for a stall
    is to fence the engine and fail the requests in flight. Compilation
    happens synchronously inside the jit CALL; the watchdog only counts
    while a guard is armed, and guards wrap result FETCHES — so no compile,
    however long, is ever inside one. Pinned here with every program's
    first dispatch slowed to 3× the deadline: the request completes, no
    stall is recorded, nothing is rebuilt."""
    reg, wd, runner, sched, sup = _supervised(tiny, "coldcompile")
    faults.arm(FaultSpec(site="engine.compile", mode="sleep",
                         delay_s=3 * wd.deadline, times=0))
    try:
        # a multi-chunk prompt: several prefill programs, then decode, then
        # speculation-free multi-step decode — each "compiles" for 1.2 s
        done = sched.generate(
            _req("cold compile " * 4, max_new_tokens=24), timeout=120)
        assert done.finish_reason in ("stop", "length")
        assert done.completion_tokens == 24
        assert faults.snapshot()[0]["fired"] >= 3    # the schedule ran
        assert sched.rebuilds == 0 and sup.attempts == 0
        assert not wd.stalled()
        assert not [line for line in reg.render().splitlines()
                    if line.startswith("localai_stalls_total")]
    finally:
        sched.shutdown()
        wd.stop()


def test_rebuild_exhaustion_marks_model_failed(tiny):
    # every rebuild's probe dispatch is forced to fail (the allocator
    # reports exhaustion forever), so the supervisor must walk its whole
    # bounded ladder and then latch the failed state
    reg, wd, runner, sched, sup = _supervised(
        tiny, "doomed", max_rebuilds=2, probe_timeout_s=10.0)
    try:
        wedged = sched.submit(_req("wedge me", max_new_tokens=400))
        deadline = time.monotonic() + 60
        while wedged.t_first_token is None and time.monotonic() < deadline:
            time.sleep(0.02)
        faults.arm(FaultSpec(site="engine.drain", mode="hang",
                             delay_s=2.0, times=1))
        faults.arm(FaultSpec(site="paged.allocate", mode="exhaust",
                             times=0))  # unlimited: every probe fails
        wedged.result(90)
        assert wedged.finish_reason == "error"
        deadline = time.monotonic() + 90
        while not sched.failed and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sched.failed
        assert sched.rebuilds == 0               # no attempt succeeded
        assert 'localai_engine_failed{model="doomed"} 1' in reg.render()
        faults.clear()
        # failed engines refuse new work with a clean, instant error
        h = sched.submit(_req("too late", max_new_tokens=4))
        h.result(10)
        assert h.finish_reason == "error"
        assert sched.metrics()["engine_state"] == "failed"
    finally:
        sched.shutdown()
        wd.stop()


def test_supervisor_rejects_spec_engines(tiny):
    class FakeSched:
        spec = object()

    with pytest.raises(ValueError):
        EngineSupervisor(FakeSched())


def test_abandoned_engine_thread_exits_without_touching_new_state(tiny):
    """The fenced-off thread must exit once its blocked round-trip
    returns — and the rebuilt engine keeps serving afterwards."""
    reg, wd, runner, sched, sup = _supervised(tiny, "fence")
    try:
        old_thread = sched._thread
        wedged = sched.submit(_req("wedge for fence", max_new_tokens=400))
        deadline = time.monotonic() + 60
        while wedged.t_first_token is None and time.monotonic() < deadline:
            time.sleep(0.02)
        faults.arm(FaultSpec(site="engine.drain", mode="hang",
                             delay_s=1.5, times=1))
        wedged.result(90)
        deadline = time.monotonic() + 60
        while sched.rebuilds == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert sched._thread is not old_thread
        old_thread.join(timeout=30)      # wakes from the hang, sees the
        assert not old_thread.is_alive()  # fence, exits without damage
        faults.clear()
        after = sched.generate(_req("post fence", max_new_tokens=8),
                               timeout=120)
        assert after.finish_reason in ("stop", "length")
    finally:
        sched.shutdown()
        wd.stop()


# -- zero overhead while disarmed ----------------------------------------


def test_disarmed_hot_path_is_one_boolean():
    """With nothing armed, injection sites reduce to a module-attribute
    truthiness check."""
    assert faults.active() is False
    # the scheduler/runner sites all gate on this exact attribute; a
    # regression to per-dispatch env reads would show up here
    import localai_tpu.engine.paged as paged_mod
    import localai_tpu.engine.scheduler as sched_mod
    import localai_tpu.obs.compile as compile_mod

    for mod in (sched_mod, paged_mod, compile_mod):
        assert mod._faults is faults.registry or \
            mod._faults.__name__ == "localai_tpu.faults.registry"


def test_watchdog_remove_callback():
    wd = Watchdog(deadline=60.0, registry=Registry(), store=TraceStore())
    seen = []
    cb = seen.append
    wd.on_stall(cb)
    wd.remove_callback(cb)
    wd.remove_callback(cb)  # idempotent
    wd._fire(object())
    assert seen == []


def test_watchdog_reset_clears_leaked_armed_count():
    """rebuild() abandons a thread parked inside a guard it will never
    exit; reset() must drop the channel so the leaked armed count can't
    fire spurious stalls forever — and the abandoned thread's eventual
    disarm() on the recreated channel must be a harmless no-op."""
    wd = Watchdog(deadline=0.01, registry=Registry(), store=TraceStore())
    wd.arm("leaky")
    assert wd.check(now=time.monotonic() + 1.0)  # trips while armed
    wd.reset("leaky")
    assert not wd.stalled("leaky")
    assert wd.check(now=time.monotonic() + 100.0) == []  # nothing armed
    wd.disarm("leaky")  # the abandoned thread finally returns: no-op
    assert wd.status()["leaky"]["armed"] == 0


def test_supervisor_detach_stops_reacting(tiny):
    reg, wd, runner, sched, sup = _supervised(tiny, "detached")
    try:
        sup.detach()
        from localai_tpu.obs.watchdog import StallEvent

        sup._on_event(StallEvent(sched._wd_channel, "stall", 1.0))
        time.sleep(0.2)
        assert sched.rebuilds == 0
    finally:
        sched.shutdown()
        wd.stop()


def test_anatomy_phases_attribute_injected_delays(tiny):
    """Dispatch-anatomy attribution pin: a host-side sleep injected at
    the engine.dispatch site (loop body, BEFORE the device issue) must
    land in the record's gap/sched phases, while a delay injected at the
    engine.drain site (inside the result-fetch watchdog guard, AFTER the
    sync mark) must land in sync_ms — the decomposition blames the right
    side of the dispatch, and every record keeps the tiling invariant
    gap+sched+launch+sync <= dispatch_ms."""
    runner, sched = _engine(tiny, "anatomy")
    tokzr = ByteTokenizer()

    def run_one(text):
        h = sched.generate(GenRequest(
            prompt=tokzr.encode(text), max_new_tokens=16,
            temperature=0.0, ignore_eos=True))
        assert h.finish_reason == "length"

    def rows_after(base_ts):
        return [r for r in sched.flight.snapshot()
                if not r["compile"] and r["ts"] > base_ts]

    keeper = None
    try:
        # warm-up: compile-bearing dispatches are flagged (and excluded
        # from phases()); the injected runs below measure steady state
        run_one("warm me up")

        # keep a long request in flight across both injections: if the
        # engine loop goes idle between requests it drops its last-drain
        # anchor, and a pre-issue delay on the NEXT dispatch lands
        # nowhere (dt falls back to issue→drain) — steady decode keeps
        # every drain pipelined, so attribution is deterministic
        keeper = sched.submit(GenRequest(
            prompt=tokzr.encode("keeper"), max_new_tokens=224,
            temperature=0.0, ignore_eos=True))
        deadline = time.monotonic() + 30.0
        while keeper.t_first_token is None and time.monotonic() < deadline:
            time.sleep(0.005)
        assert keeper.t_first_token is not None

        # host-side: 120 ms sleep before a decode dispatch
        base = sched.flight.snapshot()[-1]["ts"]
        faults.arm(FaultSpec(site="engine.dispatch", mode="sleep",
                             delay_s=0.12, times=1, match="decode"))
        run_one("host-side delay")
        hit = max(rows_after(base),
                  key=lambda r: r["gap_ms"] + r["sched_ms"])
        assert hit["gap_ms"] + hit["sched_ms"] >= 100.0
        assert hit["sync_ms"] < 100.0
        faults.clear()

        # device-side: 120 ms delay at the result fetch
        base = sched.flight.snapshot()[-1]["ts"]
        faults.arm(FaultSpec(site="engine.drain", mode="sleep",
                             delay_s=0.12, times=1))
        run_one("device-side delay")
        hit = max(rows_after(base), key=lambda r: r["sync_ms"])
        assert hit["sync_ms"] >= 100.0
        keeper.cancel()
        keeper.result(timeout=30.0)
        keeper = None

        # the tiling invariant holds ring-wide (5e-3 slack: snapshot
        # rounds each phase column to 3 decimals)
        for r in sched.flight.snapshot():
            total = (r["gap_ms"] + r["sched_ms"] + r["launch_ms"]
                     + r["sync_ms"])
            assert total <= r["dispatch_ms"] + 5e-3, r
    finally:
        if keeper is not None:
            keeper.cancel()
        sched.shutdown()
