"""Flight recorder + SLO observatory (obs.flight / obs.slo).

The unit half of the round-7 obs surfaces: ring wraparound + windowed
percentile math, sliding-window expiry, burn-rate computation, and the
shed→recover hysteresis state machine. The HTTP halves (/debug/flight,
/v1/slo, the 429 admission path) live in test_api.py; the scheduler feed
is covered in test_obs.py.
"""

import numpy as np
import pytest

from localai_tpu.obs import FlightRecorder, Registry, SLOTracker
from localai_tpu.obs import slo as obs_slo

# -- flight ring -------------------------------------------------------------


def _rec(fl, i, *, steps=8, ms=8.0, compile=False, tokens=32, ts=None,
         program="decode_n", gap=0.0, sched=0.0, launch=0.0, sync=0.0):
    fl.record(program=program, steps=steps, dispatch_ms=ms,
              occupancy=0.5, queue_depth=i, kv_utilization=0.25,
              tokens=tokens, preemptions=0, compile=compile, ts=ts,
              gap_ms=gap, sched_ms=sched, launch_ms=launch, sync_ms=sync)


def test_ring_wraparound_keeps_newest():
    fl = FlightRecorder(4)
    for i in range(10):
        _rec(fl, i, ms=float(i))
    assert fl.count == 10
    snap = fl.snapshot()
    assert len(snap) == 4                       # capacity bound
    assert [r["dispatch_ms"] for r in snap] == [6.0, 7.0, 8.0, 9.0]
    assert [r["queue_depth"] for r in snap] == [6, 7, 8, 9]
    # oldest → newest ordering across the wrap point
    ts = [r["ts"] for r in snap]
    assert ts == sorted(ts)


def test_total_tokens_survives_wraparound():
    fl = FlightRecorder(2)
    for i in range(7):
        _rec(fl, i, tokens=10)
    assert fl.total_tokens == 70                # not just the resident 2


WORK = {"launch": 7, "live_slots": 3, "attended_tokens": 1234,
        "window_tokens": 999, "selected_tokens": 777, "chunk_tokens": 88, "chunk_bucket": 128, "chunk_offset": 512,
        "chunk_ctx": 4096, "chunk_parts": 3, "experts_touched": 29,
        "local_assignments": 41, "sparse_rows": 17, "chunk_state": 3}


@pytest.mark.parametrize("column", sorted(WORK))
def test_what_a_launch_held_round_trips(column):
    """Each of the columns goes in by record()'s keyword and comes
    out of snapshot() under the same name, beside rows that gave none."""
    from localai_tpu.obs.flight import WORK_COLUMNS

    assert set(WORK_COLUMNS) == set(WORK)
    fl = FlightRecorder(8)
    _rec(fl, 0)
    fl.record(program="prefill_chunk", steps=0, dispatch_ms=3.0,
              occupancy=0.5, queue_depth=1, kv_utilization=0.25, tokens=0,
              **{column: WORK[column]})
    bare, row = fl.snapshot()
    assert row[column] == WORK[column] and type(row[column]) is int
    # every other column, and every column of a row that gave none: 0
    assert {c: row[c] for c in WORK if c != column} == {
        c: 0 for c in WORK if c != column}
    assert {c: bare[c] for c in WORK} == {c: 0 for c in WORK}


def test_what_a_launch_held_survives_the_wrap():
    fl = FlightRecorder(4)
    for i in range(1, 11):
        fl.record(program="decode", steps=1, dispatch_ms=1.0, occupancy=1.0,
                  queue_depth=0, kv_utilization=0.5, tokens=4, launch=i,
                  live_slots=4, attended_tokens=100 * i)
    snap = fl.snapshot()
    assert [r["launch"] for r in snap] == [7, 8, 9, 10]
    assert [r["attended_tokens"] for r in snap] == [700, 800, 900, 1000]
    # a page forward from a row keeps its columns too
    page = fl.snapshot(since=snap[1]["ts"], limit=1)
    assert [r["launch"] for r in page] == [9] and page[0]["live_slots"] == 4


def test_percentile_math_matches_numpy():
    fl = FlightRecorder(64)
    ms = [4.0, 8.0, 12.0, 16.0, 40.0]
    for i, m in enumerate(ms):
        _rec(fl, i, steps=4, ms=m)
    pct = fl.percentiles()
    per_step = np.array(ms) / 4.0
    assert pct["samples"] == 5
    assert pct["step_ms_p50"] == pytest.approx(
        np.percentile(per_step, 50), abs=1e-3)
    assert pct["step_ms_p90"] == pytest.approx(
        np.percentile(per_step, 90), abs=1e-3)
    assert pct["step_ms_p99"] == pytest.approx(
        np.percentile(per_step, 99), abs=1e-3)


def test_percentiles_exclude_compile_and_spec_rows():
    fl = FlightRecorder(16)
    _rec(fl, 0, steps=1, ms=5000.0, compile=True)   # compile-bearing
    _rec(fl, 1, steps=0, ms=30.0, program="spec")   # spec window
    _rec(fl, 2, steps=10, ms=10.0)
    _rec(fl, 3, steps=10, ms=10.0)
    pct = fl.percentiles()
    assert pct["samples"] == 2
    assert pct["step_ms_p50"] == pytest.approx(1.0)
    assert pct["step_ms_p99"] == pytest.approx(1.0)
    # spec rows surface step_ms=None in snapshots (variable token yield)
    snap = fl.snapshot()
    assert snap[1]["step_ms"] is None
    assert snap[0]["compile"] is True


def test_percentiles_empty_and_windowed():
    fl = FlightRecorder(8)
    assert fl.percentiles() == {
        "step_ms_p50": None, "step_ms_p90": None, "step_ms_p99": None,
        "samples": 0,
    }
    _rec(fl, 0, steps=2, ms=2.0, ts=100.0)     # old
    _rec(fl, 1, steps=2, ms=20.0, ts=200.0)    # recent
    pct = fl.percentiles(window_s=50.0, now=210.0)
    assert pct["samples"] == 1
    assert pct["step_ms_p50"] == pytest.approx(10.0)


def test_snapshot_since_and_limit():
    fl = FlightRecorder(16)
    for i in range(6):
        _rec(fl, i, ts=100.0 + i)
    snap = fl.snapshot()
    mid = snap[2]["ts"]
    newer = fl.snapshot(since=mid)
    assert [r["queue_depth"] for r in newer] == [3, 4, 5]
    assert len(fl.snapshot(limit=2)) == 2
    assert fl.snapshot(limit=2)[-1]["queue_depth"] == 5
    assert fl.snapshot(since=106.0) == []


def test_snapshot_since_pages_forward():
    """A poller that feeds back the last ``ts`` it saw reads every record
    once, however many fell between two reads: with ``since`` a ``limit``
    keeps the OLDEST records after it (benchmark/run.py reads the ring once
    after its traced slice, 4096 rows at most, and the slice comes first)."""
    fl = FlightRecorder(16)
    for i in range(10):
        _rec(fl, i, ts=100.0 + i)

    def depths(**kw):
        return [r["queue_depth"] for r in fl.snapshot(**kw)]

    assert depths(limit=4) == [6, 7, 8, 9]               # the tail view
    seen, since = [], 100.5
    while page := fl.snapshot(since=since, limit=4):
        seen += [r["queue_depth"] for r in page]
        since = page[-1]["ts"]
    assert seen == list(range(1, 10))                    # none missed
    assert depths(since=104.5, limit=4) == [5, 6, 7, 8]


# -- dispatch anatomy (phase columns + obs.anatomy) --------------------------


def test_phase_columns_default_zero_and_survive_since_filter():
    fl = FlightRecorder(8)
    _rec(fl, 0, ts=100.0)                       # no phase kwargs
    _rec(fl, 1, ts=101.0, gap=1.0, sched=2.0, launch=3.0, sync=4.0)
    snap = fl.snapshot()
    for key in ("gap_ms", "sched_ms", "launch_ms", "sync_ms"):
        assert snap[0][key] == 0.0              # pre-anatomy degrade shape
    assert snap[1]["gap_ms"] == 1.0
    assert snap[1]["sync_ms"] == 4.0
    # the since-filtered view carries the same phase keys (satellite:
    # merged fleet rows must never KeyError on them)
    newer = fl.snapshot(since=100.5)
    assert len(newer) == 1
    assert newer[0]["sched_ms"] == 2.0 and newer[0]["launch_ms"] == 3.0


def test_phase_columns_survive_wraparound():
    fl = FlightRecorder(4)
    for i in range(10):
        _rec(fl, i, sync=float(i))
    snap = fl.snapshot()
    assert [r["sync_ms"] for r in snap] == [6.0, 7.0, 8.0, 9.0]
    ph = fl.phases()
    assert ph["samples"] == 4                   # resident rows only
    assert ph["sync_ms_total"] == pytest.approx(30.0)


def test_phases_percentile_math_matches_numpy():
    fl = FlightRecorder(64)
    gaps = [1.0, 2.0, 3.0, 4.0, 5.0]
    syncs = [0.5, 1.0, 1.5, 2.0, 2.5]
    for i, (g, s) in enumerate(zip(gaps, syncs)):
        _rec(fl, i, ms=20.0, gap=g, sched=0.5, launch=2.0, sync=s)
    ph = fl.phases()
    assert ph["samples"] == 5
    assert ph["gap_ms_p50"] == pytest.approx(
        np.percentile(gaps, 50), abs=1e-3)
    assert ph["gap_ms_p90"] == pytest.approx(
        np.percentile(gaps, 90), abs=1e-3)
    assert ph["sync_ms_p99"] == pytest.approx(
        np.percentile(syncs, 99), abs=1e-3)
    # host percentiles are over the per-record SUM (percentiles of
    # independent phases do not compose)
    host = np.array(gaps) + 0.5 + 2.0
    assert ph["host_ms_p50"] == pytest.approx(
        np.percentile(host, 50), abs=1e-3)
    # windowed totals + fractions
    assert ph["dispatch_ms_total"] == pytest.approx(100.0)
    assert ph["host_ms_total"] == pytest.approx(host.sum(), abs=1e-3)
    assert ph["host_overhead_fraction"] == pytest.approx(
        host.sum() / 100.0, abs=1e-3)
    # the host's clock splits HOST time; no estimate of the device's idle
    # share rides along (the profiler measures it)
    assert not [k for k in ph if "bubble" in k]


def test_phases_exclude_compile_rows_and_window():
    fl = FlightRecorder(16)
    # a compile row's minutes of tracing must not drown the phases
    _rec(fl, 0, ms=5000.0, compile=True, gap=4000.0, sync=900.0, ts=100.0)
    _rec(fl, 1, ms=10.0, gap=6.0, sync=4.0, ts=100.0)
    _rec(fl, 2, ms=10.0, gap=2.0, sync=8.0, ts=200.0)
    ph = fl.phases()
    assert ph["samples"] == 2
    assert ph["dispatch_ms_total"] == pytest.approx(20.0)
    assert ph["gap_ms_total"] == pytest.approx(8.0)
    # window keeps only the recent row
    ph = fl.phases(window_s=50.0, now=210.0)
    assert ph["samples"] == 1
    assert ph["sync_ms_total"] == pytest.approx(8.0)
    assert ph["host_overhead_fraction"] == pytest.approx(0.2)


def test_phases_empty_returns_none_percentiles():
    fl = FlightRecorder(8)
    ph = fl.phases()
    assert ph["samples"] == 0
    for name in ("gap", "sched", "launch", "sync", "host"):
        assert ph[f"{name}_ms_p50"] is None
    assert ph["host_overhead_fraction"] is None
    assert ph["dispatch_ms_total"] == 0.0


def test_anatomy_breakdown_shares_and_quantiles():
    from localai_tpu.obs import anatomy

    fl = FlightRecorder(16)
    _rec(fl, 0, ms=10.0, gap=1.0, sched=2.0, launch=3.0, sync=4.0)
    _rec(fl, 1, ms=10.0)                        # fully unattributed
    b = anatomy.breakdown(fl, window_s=None)
    assert b["samples"] == 2
    assert b["phase_share"]["gap"] == pytest.approx(0.05)
    assert b["phase_share"]["sync"] == pytest.approx(0.2)
    # the all-zero record's wall lands in unattributed, not in a phase
    assert b["unattributed_ms_total"] == pytest.approx(10.0)
    assert b["unattributed_share"] == pytest.approx(0.5)
    q = anatomy.phase_quantiles(anatomy.summarize(fl, window_s=None))
    assert set(q) == set(anatomy.PHASES + anatomy.PARTS)
    assert set(q["gap"]) == {"p50", "p90", "p99"}
    assert q["launch"]["p99"] == pytest.approx(
        np.percentile([3.0, 0.0], 99), abs=1e-3)


# the measured parts of gap and the engine thread's clocks (PR 53): what a
# row that gives them reads back, in ms
TIMED = {"process_ms": 1.25, "book_ms": 0.5, "free_ms": 0.375, "span_ms": 10.0, "wait_ms": 6.0,
         "idle_ms": 0.75, "cpu_ms": 2.0, "runq_ms": 0.125, "blocked_ms": 1.125,
         "proc_cpu_ms": 7.5}


@pytest.mark.parametrize("column", sorted(TIMED))
def test_the_parts_and_the_thread_clocks_round_trip(column):
    """Each column goes in by record()'s keyword and comes out of snapshot()
    under the same name; a row that gave none reads 0, and ``runq_ms`` null
    (the kernel's file was not read: not the same as no delay); the columns
    survive the wrap and a page forward."""
    from localai_tpu.obs.flight import CLOCK_COLUMNS, PART_COLUMNS

    assert set(PART_COLUMNS + CLOCK_COLUMNS) == set(TIMED)
    fl = FlightRecorder(4)
    _rec(fl, 0)
    fl.record(program="decode", steps=1, dispatch_ms=10.0, occupancy=0.5,
              queue_depth=1, kv_utilization=0.25, tokens=1, gap_ms=3.0,
              **{column: TIMED[column]})
    bare, row = fl.snapshot()
    assert row[column] == TIMED[column]
    default = {c: (None if c == "runq_ms" else 0.0) for c in TIMED}
    assert {c: bare[c] for c in TIMED} == default
    assert {c: row[c] for c in TIMED if c != column} == {
        c: v for c, v in default.items() if c != column}
    for i in range(1, 10):          # past the wrap, every row with its own
        fl.record(program="decode", steps=1, dispatch_ms=10.0, occupancy=0.5,
                  queue_depth=1, kv_utilization=0.25, tokens=1,
                  **{column: TIMED[column] * i})
    snap = fl.snapshot()
    assert [r[column] for r in snap] == [TIMED[column] * i
                                         for i in (6, 7, 8, 9)]
    page = fl.snapshot(since=snap[1]["ts"], limit=1)
    assert [r[column] for r in page] == [TIMED[column] * 8]


def _timed(fl, **cols):
    fl.record(program="decode", steps=1, dispatch_ms=10.0, occupancy=1.0,
              queue_depth=0, kv_utilization=0.5, tokens=1, **cols)


def test_phases_report_the_parts_of_gap_and_the_thread_block():
    """``process``, ``book`` and ``free`` get quantiles and totals like the
    four phases, and are in no sum (they lie INSIDE gap: ``attributed`` counts
    gap once); ``thread`` gives each state's share of the window's span,
    ``runq`` None where no row could read it; compile rows are left out."""
    from localai_tpu.obs import anatomy

    fl = FlightRecorder(16)
    _timed(fl, gap_ms=3.0, sync_ms=7.0, process_ms=1.0, book_ms=0.5,
           free_ms=0.25, span_ms=10.0, wait_ms=7.0, cpu_ms=2.0, blocked_ms=1.0)
    _timed(fl, gap_ms=5.0, sync_ms=5.0, process_ms=3.0, book_ms=1.5,
           span_ms=30.0, wait_ms=5.0, idle_ms=20.0, cpu_ms=4.0,
           blocked_ms=1.0)
    _timed(fl, compile=True, gap_ms=9.0, process_ms=9.0, span_ms=1e3,
           cpu_ms=1e3)
    ph = fl.phases()
    assert ph["samples"] == 2
    assert ph["process_ms_total"] == 4.0 and ph["book_ms_total"] == 2.0
    assert ph["free_ms_total"] == 0.25 and ph["free_ms_p50"] == 0.125
    assert ph["process_ms_p50"] == pytest.approx(2.0)
    assert ph["book_ms_p99"] == pytest.approx(np.percentile([0.5, 1.5], 99))
    assert ph["host_ms_total"] == 8.0           # gap alone: parts not added
    assert ph["thread"] == {"span_ms_total": 40.0, "share": {
        "cpu": 0.15, "runq": None, "blocked": 0.05, "wait": 0.3,
        "idle": 0.5}}
    b = anatomy.breakdown(fl, window_s=None)
    assert b["part_share"] == {"process": 0.2, "book": 0.1, "free": 0.0125}
    assert sum(b["phase_share"].values()) == pytest.approx(1.0)
    assert b["unattributed_ms_total"] == 0.0
    assert b["thread"] == ph["thread"]
    assert set(anatomy.PARTS) <= set(b["definitions"])
    # a row that read the kernel's file gives runq a share; the ring's
    # totals (localai_engine_thread_seconds_total) hold every row, compile
    # rows too, and survive the wrap
    _timed(fl, span_ms=10.0, cpu_ms=6.0, runq_ms=4.0)
    assert fl.phases()["thread"]["share"]["runq"] == pytest.approx(0.08)
    assert fl.thread_ms_total == {"cpu": 1012.0, "runq": 4.0, "blocked": 2.0,
                                  "wait": 12.0, "idle": 20.0}
    empty = FlightRecorder(4).phases()
    assert empty["process_ms_p50"] is None and empty["book_ms_total"] == 0.0
    assert set(empty["thread"]["share"].values()) == {None}


def test_a_thread_clock_tiles_its_span(tmp_path, monkeypatch):
    """wait + idle + cpu + runq + blocked = span for every take: a wait's
    wall is the wait's, a sleep outside one is ``blocked``, a busy loop is
    ``cpu``; the CPU burnt inside a wait is not ``cpu``'s; a file that is
    not there gives ``runq_ms`` None and the identity still holds."""
    import time

    from localai_tpu.obs import flight
    from localai_tpu.obs.flight import CLOCK_COLUMNS, ThreadClock

    def states(row):
        return (row["wait_ms"] + row["idle_ms"] + row["cpu_ms"]
                + (row["runq_ms"] or 0.0) + row["blocked_ms"])

    def burn(cpu_s):        # the thread's own CPU: a loaded machine's wall
        t0 = time.thread_time()                 # gives a loop less
        while time.thread_time() - t0 < cpu_s:
            pass

    for path, readable in ((flight.SCHEDSTAT, True),
                           (str(tmp_path / "absent"), False)):
        monkeypatch.setattr(flight, "SCHEDSTAT", path)
        clock = ThreadClock()
        cpu_start = time.thread_time()
        mark, t0 = clock.enter(), time.monotonic()
        burn(0.01)                              # burnt INSIDE a wait
        clock.leave(mark, time.monotonic() - t0)
        mark, t0 = clock.enter(), time.monotonic()
        time.sleep(0.005)
        clock.leave(mark, time.monotonic() - t0, idle=True)
        time.sleep(0.015)                       # asleep outside a wait
        burn(0.01)                              # computing outside one
        row = clock.take(time.monotonic())
        cpu_all = (time.thread_time() - cpu_start) * 1e3
        assert tuple(row) == CLOCK_COLUMNS
        assert states(row) == pytest.approx(row["span_ms"], abs=1e-6)
        assert (row["runq_ms"] is not None) == readable
        assert row["wait_ms"] >= 10.0 and row["idle_ms"] >= 5.0
        # the burn outside the wait is ``cpu``; the wait's own 10 ms is not
        assert row["cpu_ms"] >= 10.0 and cpu_all - row["cpu_ms"] >= 9.9
        assert row["blocked_ms"] + (row["runq_ms"] or 0.0) >= 12.0
        assert row["proc_cpu_ms"] >= row["cpu_ms"]
        # the next take starts where this one ended: no wall is carried
        again = clock.take(time.monotonic())
        assert again["wait_ms"] == again["idle_ms"] == 0.0
        assert again["span_ms"] < 5.0
        assert states(again) == pytest.approx(again["span_ms"], abs=1e-6)
        clock.close()
        clock.close()                           # twice is fine
        assert clock.take(time.monotonic())["runq_ms"] is None


@pytest.mark.parametrize("tick_ms", [10.0, 4.0, 0.001])
def test_a_coarse_cpu_clock_is_carried_not_cut(monkeypatch, tick_ms):
    """A CPU clock that ticks coarser than a row is long (the chip's machine:
    10 ms under rows of 8) loses nothing: a tick the span has no room for is
    owed to the next rows, so the window's ``cpu_ms`` sums to the thread's
    true CPU to a tick, ``blocked`` takes none of it, and every row tiles."""
    from localai_tpu.obs import flight

    # a scripted thread: rows of 8 ms = 3.6 ms in the wait (asleep), 2.4 ms
    # on a CPU, 2.0 ms asleep on a lock; its CPU clock reads whole ticks
    t = {"wall": 100.0, "cpu": 0.0}
    tick = tick_ms * 1e-3
    monkeypatch.setattr(flight.time, "monotonic", lambda: t["wall"])
    monkeypatch.setattr(
        flight.time, "thread_time", lambda: t["cpu"] // tick * tick)
    monkeypatch.setattr(
        flight.time, "process_time", lambda: t["cpu"] // tick * tick)
    monkeypatch.setattr(flight, "SCHEDSTAT", "/nonexistent/schedstat")
    clock = flight.ThreadClock()
    rows = []
    for _ in range(500):
        mark = clock.enter()
        t["wall"] += 3.6e-3
        clock.leave(mark, 3.6e-3)
        t["wall"] += 4.4e-3
        t["cpu"] += 2.4e-3
        rows.append(clock.take(t["wall"]))
    for r in rows:
        assert (r["wait_ms"] + r["idle_ms"] + r["cpu_ms"] + r["blocked_ms"]
                == pytest.approx(r["span_ms"], abs=1e-6))
        assert r["cpu_ms"] <= 4.4 + 1e-6        # never into the wait
    cpu = sum(r["cpu_ms"] for r in rows)
    assert cpu == pytest.approx(500 * 2.4, abs=tick_ms + 1e-6)
    assert sum(r["blocked_ms"] for r in rows) == pytest.approx(
        500 * 2.0, abs=tick_ms + 1e-6)
    assert sum(r["wait_ms"] for r in rows) == pytest.approx(500 * 3.6)
    # a clock that reads HIGH (a second of CPU in a row of 8 ms) is owed only
    # as far as ``OWED_MAX_S``: the rows long after it do not pay for it
    t["cpu"] += 1.0
    later = [clock.take(t.__setitem__("wall", t["wall"] + 8e-3) or t["wall"])
             for _ in range(100)]
    assert sum(r["cpu_ms"] for r in later) == pytest.approx(
        8.0 + flight.OWED_MAX_S * 1e3, abs=tick_ms + 1e-6)
    assert later[-1]["cpu_ms"] == 0.0 and later[-1]["blocked_ms"] > 7.9


# -- SLO observatory ---------------------------------------------------------


def _tracker(clock, **kw):
    kw.setdefault("targets", {"ttft_ms": 100.0})
    kw.setdefault("burn_threshold", 2.0)
    kw.setdefault("recover_burn", 1.0)
    kw.setdefault("min_events", 2)
    kw.setdefault("objective", 0.95)
    return SLOTracker(registry=Registry(), clock=clock, **kw)


def test_window_expiry_drops_old_events():
    t = {"now": 1000.0}
    slo = _tracker(lambda: t["now"])
    slo.observe("m", ttft_ms=500.0)            # bad
    assert slo.burn_rate("m", "1m") == pytest.approx(20.0)
    t["now"] += 90                              # out of the 1m window
    assert slo.burn_rate("m", "1m") == 0.0
    assert slo.burn_rate("m", "5m") == pytest.approx(20.0)
    t["now"] += 3600                            # past the 30m horizon too
    slo.observe("m", ttft_ms=10.0)             # prunes on the way in
    w = slo.windows("m")
    assert w["30m"]["count"] == 1 and w["30m"]["bad"] == 0


def test_burn_rate_is_bad_fraction_over_budget():
    t = {"now": 0.0}
    slo = _tracker(lambda: t["now"])
    for ttft in (50.0, 50.0, 50.0, 200.0):     # 1 bad of 4, budget 5%
        slo.observe("m", ttft_ms=ttft)
    assert slo.burn_rate("m", "1m") == pytest.approx(0.25 / 0.05)
    w = slo.windows("m")["1m"]
    assert w["count"] == 4 and w["bad"] == 1
    assert w["ttft_ms"]["p50"] == pytest.approx(50.0)


def test_error_counts_as_violation_and_percentiles_skip_none():
    t = {"now": 0.0}
    slo = _tracker(lambda: t["now"])
    slo.observe("m", ttft_ms=None, error=True)  # failed before first token
    w = slo.windows("m")["1m"]
    assert w["bad"] == 1 and w["ttft_ms"] is None


def test_shed_hysteresis_trip_and_recover():
    t = {"now": 1000.0}
    slo = _tracker(lambda: t["now"])
    # one bad event: burn is high but min_events (2) not met → no shed
    slo.observe("m", ttft_ms=500.0)
    assert not slo.should_shed("m")
    slo.observe("m", ttft_ms=500.0)
    assert slo.should_shed("m")                 # fast AND slow over 2.0
    assert slo.shedding("m")
    assert slo.shed("m") == slo.retry_after_s   # the 429 path records
    assert slo.shed_total("m") == 1
    # hysteresis: still shedding while the fast window stays hot
    t["now"] += 10
    assert slo.should_shed("m")
    # the fast window slides past the burst → automatic recovery ...
    t["now"] += 80
    assert not slo.should_shed("m")
    assert not slo.shedding("m")
    # ... even though the slow (5m) window still holds the bad events
    assert slo.burn_rate("m", "5m") > slo.burn_threshold


def test_shed_needs_both_windows_hot():
    t = {"now": 1000.0}
    slo = _tracker(lambda: t["now"])
    # two bad events, but 4m ago: slow window hot, fast window empty
    slo.observe("m", ttft_ms=500.0, now=760.0)
    slo.observe("m", ttft_ms=500.0, now=760.0)
    assert slo.burn_rate("m", "5m") > slo.burn_threshold
    assert slo.burn_rate("m", "1m") == 0.0
    assert not slo.should_shed("m")


def test_no_targets_never_sheds_and_unlatches():
    t = {"now": 0.0}
    slo = _tracker(lambda: t["now"])
    slo.observe("m", ttft_ms=500.0)
    slo.observe("m", ttft_ms=500.0)
    assert slo.should_shed("m")
    slo.configure(targets={})                   # operator clears the SLO
    assert not slo.should_shed("m")
    assert not slo.shedding("m")


def test_scrape_observes_recovery_without_traffic():
    """A shedding model whose clients all back off must still recover:
    the scrape/report paths re-run the state machine instead of echoing
    the latched flag (no request required to un-stick the gauge)."""
    t = {"now": 1000.0}
    reg = Registry()
    slo = SLOTracker(registry=reg, clock=lambda: t["now"],
                     targets={"ttft_ms": 1.0}, burn_threshold=1.0,
                     recover_burn=1.0, min_events=1)
    slo.observe("m", ttft_ms=50.0)
    assert slo.should_shed("m")
    t["now"] += 120                    # fast window drains, zero traffic
    slo.export_gauges()                # a scrape, not an admission
    assert 'localai_overload_shedding{model="m"} 0' in reg.render()
    assert slo.report()["models"]["m"]["shedding"] is False


def test_export_gauges_renders_series():
    t = {"now": 0.0}
    reg = Registry()
    slo = SLOTracker(registry=reg, clock=lambda: t["now"],
                     targets={"ttft_ms": 100.0}, burn_threshold=2.0,
                     min_events=1)
    slo.observe("m", ttft_ms=500.0)
    assert slo.should_shed("m")
    slo.shed("m")
    slo.export_gauges()
    text = reg.render()
    assert 'localai_slo_burn_rate{model="m",window="1m"} 20.0' in text
    assert 'localai_slo_burn_rate{model="m",window="30m"} 20.0' in text
    assert 'localai_overload_shedding{model="m"} 1' in text
    assert 'localai_requests_shed_total{model="m"} 1' in text


def test_reset_clears_state_and_gauges():
    reg = Registry()
    slo = SLOTracker(registry=reg, clock=lambda: 0.0,
                     targets={"ttft_ms": 1.0}, min_events=1,
                     burn_threshold=1.0)
    slo.observe("m", ttft_ms=50.0)
    assert slo.should_shed("m")
    slo.reset()
    assert not slo.shedding("m")
    assert slo.shed_total("m") == 0
    assert 'localai_overload_shedding{model="m"} 0' in reg.render()
    assert slo.report()["models"] == {}


def test_env_targets_parse(monkeypatch):
    monkeypatch.setenv("LOCALAI_SLO_TTFT_P95_MS", "250")
    monkeypatch.setenv("LOCALAI_SLO_TPOT_P95_MS", "0")      # disabled
    monkeypatch.setenv("LOCALAI_SLO_E2E_P95_MS", "garbage")  # ignored
    monkeypatch.delenv("LOCALAI_SLO_QUEUE_P95_MS", raising=False)
    assert obs_slo.env_targets() == {"ttft_ms": 250.0}


def test_targets_from_app_config():
    from localai_tpu.config.app_config import AppConfig

    cfg = AppConfig(slo_ttft_p95_ms=300.0, slo_e2e_p95_ms=2000.0)
    assert obs_slo.targets_from_config(cfg) == {
        "ttft_ms": 300.0, "e2e_ms": 2000.0,
    }


def test_report_shape():
    t = {"now": 0.0}
    slo = _tracker(lambda: t["now"])
    slo.observe("m", ttft_ms=50.0, tpot_ms=5.0, e2e_ms=80.0, queue_ms=1.0)
    rep = slo.report()
    assert rep["windows"] == ["1m", "5m", "30m"]
    assert rep["targets"] == {"ttft_ms": 100.0}
    m = rep["models"]["m"]
    assert m["shedding"] is False and m["shed_total"] == 0
    agg = m["windows"]["1m"]
    assert agg["count"] == 1 and agg["burn_rate"] == 0.0
    for metric in ("ttft_ms", "tpot_ms", "e2e_ms", "queue_ms"):
        assert set(agg[metric]) == {"p50", "p95", "p99"}
