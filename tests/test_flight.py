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
        "local_assignments": 41}


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
    assert set(q) == set(anatomy.PHASES)
    assert set(q["gap"]) == {"p50", "p90", "p99"}
    assert q["launch"]["p99"] == pytest.approx(
        np.percentile([3.0, 0.0], 99), abs=1e-3)


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
