"""The benchmark's tracing in the run that measures (``--trace 2``, ISSUE 25),
held to its order of events on the CPU at the benchmark's tiny test
configuration, and the reduction of a trace that names what ran: scope paths
for the device's operations, ``sched.*`` phases for its idle gaps, the three
readers that rest on them. Traces are hand-made XSpace protobufs
(benchmark/tests/xspace.py): the CPU backend has no device plane."""

import importlib.util
import json
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]

import run as bench  # noqa: E402
import xspace  # noqa: E402
from harness import launches as lch  # noqa: E402
from harness import metrics as mtr  # noqa: E402
from harness import peaks, spec, work  # noqa: E402
from harness import trace_reduce as tr  # noqa: E402

_spec = importlib.util.spec_from_file_location(
    "benchmark_tests_conftest", BENCH / "tests" / "conftest.py")
_conftest = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_conftest)
bench_copy = _conftest.bench_copy   # the fixture: a copy with the tiny cells

T0 = 1_790_000_000_000_000_000      # Unix ns
FP = 77                             # the decode program's fingerprint
POOL = "bf16[2,9,2,16,16]"          # tiny: 2 layers, 9 blocks, 2 kv heads
HLO = {
    "mlp": "%fusion.1 = bf16[4,128]{1,0} fusion(bf16[4,64]{1,0} %p0)",
    "kernel": "%paged_decode_attn.2 = bf16[4,2,2,16]{3,2,1,0} custom-call("
              "bf16[4,2,2,16]{3,2,1,0} %q), custom_call_target="
              '"tpu_custom_call"',
    "write": "%scatter.3 = bf16[9,2,16,16]{3,2,1,0} scatter(bf16[9,2,16,16]"
             "{3,2,1,0} %pool)",
    "restack": f"%copy.4 = {POOL}{{4,3,2,1,0}} copy({POOL}{{4,3,2,1,0}} %kv)",
    "slice": "%dynamic-slice.5 = s8[1,64,64]{2,1,0} dynamic-slice(s8[2,64,64]"
             "{2,1,0} %w)",
    "prefill": "%fusion.9 = bf16[1,32,64]{2,1,0} fusion(bf16[1,32,64]{2,1,0}"
               " %x)",
}
PATH = "jit(_decode_paged_fn)/jit(main)/decode/layers/while/body/"


LAUNCHES = (12, 13, 14, 15)         # the launch numbers of ``named_trace``
CHAT_COUNTED = {"model.prefill_mfu.counted", "runner.prefill_pad_share",
                "runner.prefill_attend_pad_share"}


def named_trace(tmp_path, host=True, scopes=True, launches=None) -> Path:
    """One chip, two decode steps and a prefill chunk (ns from the trace's
    start), idle in [400,600) and [800,900) and [1000,1100); the engine
    thread admits in [380,590), processes twice and waits in [790,1000).

    With ``launches`` (four numbers, or "bare") the engine thread also
    launches: the decode step that runs at 600 inside the phase it had
    (``sched.decode_launch`` [590,600)), a chunk in a ``sched.prefill_chunk``
    [600,640) that runs at 900, a decode step in a ``sched.decode_launch``
    [1040,1090) that runs at 1100, and one in [1150,1160) that the trace
    holds no execution of; the decode step at 100 was launched before the
    capture began. The numbers name the ``sched.launch/<n>`` nested in each
    (the third as long as its phase, inside the idle gap [1000,1100));
    "bare" writes the phases and no launch: the program before PR 39."""
    ops = [(HLO["mlp"], 100, 100), (HLO["kernel"], 200, 100),
           (HLO["write"], 300, 50), (HLO["restack"], 350, 50),
           (HLO["slice"], 600, 50), (HLO["mlp"], 650, 150),
           (HLO["prefill"], 900, 100), (HLO["mlp"], 1100, 100)]
    meta = {HLO["mlp"]: {"tf_op": PATH + "mlp/dot_general:"},
            HLO["kernel"]: {"tf_op": PATH + "closed_call/attn.paged_decode/"
                                            "paged_decode_attn/pallas_call:"},
            HLO["write"]: {"tf_op": PATH + "kv_pool.write/scatter:"},
            HLO["slice"]: {"tf_op": PATH + "dynamic_slice:"},
            HLO["prefill"]: {"tf_op": "jit(_prefill_paged_fn)/jit(main)/"
                                      "prefill/layers/while/body/mlp/dot:"}}
    for name in meta:
        meta[name]["program_id"] = 88 if name == HLO["prefill"] else FP
    planes = [xspace.plane("/device:TPU:0", {
        "XLA Ops": ops,
        "XLA Modules": [(f"jit__decode_paged_fn({FP})", 100, 300),
                        (f"jit__decode_paged_fn({FP})", 600, 200),
                        ("jit__prefill_paged_fn(88)", 900, 100),
                        (f"jit__decode_paged_fn({FP})", 1100, 100)]},
        meta=meta if scopes else None)]
    if host:
        engine = [("sched.process", 340, 20), ("sched.admit", 380, 210),
                  ("sched.decode_launch", 590, 10),
                  ("sched.process", 760, 30),
                  ("sched.wait_device", 790, 210)]
        if launches:
            engine += [("sched.prefill_chunk", 600, 40),
                       ("sched.decode_launch", 1040, 50),
                       ("sched.decode_launch", 1150, 10)]
        if launches and launches != "bare":
            engine += [(f"sched.launch/{n}", at, ns) for n, (at, ns) in zip(
                launches, ((592, 6), (605, 30), (1040, 50), (1152, 6)))]
        # as the profiler writes a line: by start, the enclosing event first
        engine.sort(key=lambda ev: (ev[1], -ev[2]))
        planes.append(xspace.plane("/host:CPU", {
            "engine-tiny/71": engine,
            "MainThread/1": [("sched.admit", 0, 2000),     # not the engine:
                             ("aiohttp", 0, 50)]}))        # fewer phases
    planes.append(xspace.plane("Task Environment", {}, {
        "profile_start_time": T0, "profile_stop_time": T0 + 2000}))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(xspace.space(planes))
    return path


def test_scope_paths_drop_transforms_control_flow_and_the_primitive():
    assert tr.scope_path(PATH + "mlp/dot_general:") == "decode/layers/mlp"
    assert tr.scope_path("jit(f)/jit(main)/decode/layers/while/body/"
                         "closed_call/attn.paged_decode/paged_decode_attn/"
                         "pallas_call") == (
        "decode/layers/attn.paged_decode/paged_decode_attn")
    assert tr.scope_path("jit(step)/while/body/closed_call/dot_general:") == ""
    assert tr.scope_path("jit(f)/cond/branch_1_fun/sample/top_k") == "sample"


def test_operations_go_by_scope_and_gaps_by_the_schedulers_phase(tmp_path):
    out = tr.reduce(named_trace(tmp_path))
    ops = {k: pytest.approx(v) for k, v in out["breakdown"]["device_ops"]}
    assert ops == {
        # a leaf scope sums what was staged under it, in either program
        "decode/layers/mlp": 350e-9, "prefill/layers/mlp": 100e-9,
        "decode/layers/attn.paged_decode/paged_decode_attn": 100e-9,
        "decode/layers/kv_pool.write": 50e-9,
        # directly under a scope that has scopes inside: by shape
        "decode/layers s8[1,64,64]": 50e-9,
        # the program named nothing (an XLA layout copy): as before
        f"copy.4 copy {POOL}": 50e-9}
    # by HLO name too, for the readers that match on it
    assert tr.op_seconds(out, r"custom-call:tpu_custom_call")[0] == (
        pytest.approx(100e-9))
    assert tr.module_seconds(out, "decode") == (pytest.approx(600e-9), 3)
    # gaps, longest first: [400,600) is the admission's, [800,900) falls
    # under the wait for the device, [1000,1100) under nothing the engine
    # thread wrote: the other thread's two-microsecond "sched.admit" is not
    # the engine's
    assert out["breakdown"]["idle_gaps"] == [
        ["sched.admit", pytest.approx(200e-9)],
        ["sched.wait_device", pytest.approx(100e-9)],
        ["unattributed", pytest.approx(100e-9)]]
    assert out["notes"]["engine_line"] == "engine-tiny/71"


def test_a_gap_no_phase_covers_is_unattributed(tmp_path):
    phases = [(0.0, 1.0, "sched.admit"), (1.0, 1.5, "sched.process")]
    assert tr.gap_owner((0.8, 0.5), phases) == "sched.process"
    assert tr.gap_owner((2.0, 0.5), phases) == "unattributed"
    out = tr.reduce(named_trace(tmp_path, host=False))
    assert {g[0] for g in out["breakdown"]["idle_gaps"]} == {"unattributed"}
    assert out["phases"] == [] and out["idle_owned_s"] is None


def tiny_ctx(trace) -> dict:
    class Cell:
        published = {"num_hidden_layers": 2, "num_key_value_heads": 2,
                     "hidden_size": 64, "num_attention_heads": 4}
        config = {"engine": {"kv_num_blocks": 9, "kv_block_tokens": 16}}

    return {"cell": Cell, "trace": trace}


@pytest.mark.parametrize("metric, value", [
    # of 600 ns in the decode programs: the scoped write (50) and the
    # unscoped pool-shaped copy (50); the weight slice is not the pool's
    ("runner.kv_move_share", 100.0 * 100 / 600),
    # 20 and 30 ns: numpy's p90 between two samples
    ("sched.process_ms_p90", 29e-6),
    # idle under the admission and the launch ([400,600)) over 1100 ns;
    # what falls under sched.wait_device is not the scheduler's
    ("sched.device_idle_share", 100.0 * 200 / 1100),
])
def test_the_new_readers_read_scopes_and_phases(tmp_path, metric, value):
    read = spec.load_reader(metric, ROOT)
    assert read(tiny_ctx(tr.reduce(named_trace(tmp_path)))) == (
        pytest.approx(value))
    # a program that names nothing (the parent's), or no trace at all:
    # nothing to read, and no error
    bare = tr.reduce(named_trace(tmp_path, host=False, scopes=False))
    assert read(tiny_ctx(bare)) is None
    assert read(tiny_ctx(None)) is None


# ---------------------------------------------------------------------------
# what a launch holds (PR 39): the ring row of an execution, by launch number


def ring_row(launch, program, at_s, **held) -> dict:
    """A flight-ring row as /debug/flight serves it, drained ``at_s`` into
    the window of ``launch_ctx``."""
    return {"launch": launch, "program": program, "compile": False,
            "steps": 0, "ts_unix": 1000.0 + at_s,
            **{c: 0 for c in ("live_slots", "attended_tokens", "chunk_tokens",
                              "chunk_bucket", "chunk_offset", "chunk_ctx")},
            **held}


RING = [
    # a chunk of the window that the slice does not hold, a compile-bearing
    # one, and one from before the window
    ring_row(3, "prefill_chunk", -5.0, chunk_tokens=7, chunk_bucket=128,
             chunk_offset=0, chunk_ctx=512),
    {**ring_row(9, "prefill_chunk", 2.0, chunk_tokens=1, chunk_bucket=128,
                chunk_offset=0, chunk_ctx=512), "compile": True},
    ring_row(11, "prefill_chunk", 3.0, chunk_tokens=100, chunk_bucket=128,
             chunk_offset=0, chunk_ctx=512),
    # the slice's four launches (LAUNCHES)
    ring_row(12, "decode", 4.0, steps=1, live_slots=3, attended_tokens=300),
    ring_row(13, "prefill_chunk", 4.1, chunk_tokens=20, chunk_bucket=32,
             chunk_offset=64, chunk_ctx=512),
    ring_row(14, "decode_n", 4.2, steps=2, live_slots=2,
             attended_tokens=500),
    ring_row(15, "decode", 4.3, steps=1, live_slots=2, attended_tokens=260),
]


def launch_ctx(trace) -> dict:
    """A run of the 7B's chat cell whose window is [0, 10) s of the ring's
    clock, with ``trace`` as its slice."""
    return {"cell": spec.load_cell("m7b-chat", ROOT), "trace": trace,
            "traced": {"flight": RING}, "anchor": (1000.0, 0.0),
            "window": mtr.Window(0.0, 10.0, 12.0),
            "peak": peaks.PEAKS["TPU v5 lite"]}


def test_the_join_keeps_the_launches_the_slice_holds_whole(tmp_path):
    """Launched before the capture (the step at 100), inside it (three),
    and as it stopped (one with no execution): the inner three are matched,
    each to its own row and its own device time, and the counts of what was
    left out go onto the ``trace`` line."""
    ctx = launch_ctx(tr.reduce(named_trace(tmp_path, launches=LAUNCHES)))
    joined = lch.join(ctx)
    assert [(row["launch"], kind, round((e - s) * 1e9))
            for row, kind, s, e in joined["pairs"]] == [
        (12, "decode", 200), (13, "prefill", 100), (14, "decode", 100)]
    assert {k: joined[k] for k in ("early", "late", "unrun", "unrowed")} == {
        "early": 1, "late": 0, "unrun": [15], "unrowed": 0}
    # the least room to a pair's bounds: launch 12 began 8 ns before its
    # step did, and the step had ended 352 ns before launch 15 began
    assert ctx["trace"]["notes"]["launches"] == {
        "matched": {"decode": 2, "prefill": 1}, "executions": 4,
        "early": 1, "late": 0, "unrun": [15], "unrowed": 0,
        "slack_ms": [pytest.approx(8e-6, abs=1e-3),
                     pytest.approx(352e-6, abs=1e-3)]}
    # no launch in the trace (the parent's), no ``launch`` in the ring (the
    # parent's), no trace (a voided slice): nothing to join, and no error
    bare = launch_ctx(tr.reduce(named_trace(tmp_path, launches="bare")))
    assert lch.join(bare) is None
    old = {**ctx, "traced": {"flight": [
        {k: v for k, v in r.items() if k != "launch"} for r in RING]}}
    assert lch.join(old) is None
    assert lch.join(launch_ctx(None)) is None


def _counted(metric, ctx):
    """The four readers' numbers by hand, from RING and the trace's times."""
    cell = ctx["cell"]
    fam, hf = cell.family, cell.published
    if metric == "model.prefill_mfu.counted":
        # launch 13 alone: 20 real tokens behind 64 cached, 100 ns
        pairs = 20 * 64 + 20 * 21 // 2
        flops = 2.0 * fam.token_params(hf) * 20 + fam.attn_flops(hf, pairs)
        return 100.0 * flops / 100e-9 / 197e12
    if metric == "model.decode_bw_share.counted":
        # launches 12 (one step over 3 streams, 200 ns) and 14 (two steps
        # over 2, 100 ns); 15 never ran, the step at 100 has no launch
        weights = fam.step_params(hf, 3) + 2 * fam.step_params(hf, 2)
        kv = (300 + 500) * fam.kv_bytes_per_token(hf, 2.0)
        return 100.0 * (weights * 1.0 + kv) / 819e9 / 300e-9
    # the window's chunks: launches 11 and 13 (3 is before it, 9 compiled)
    if metric == "runner.prefill_pad_share":
        return 100.0 * (1 - (100 + 20) / (128 + 32))
    assert metric == "runner.prefill_attend_pad_share"
    real = 100 * 101 // 2 + (20 * 64 + 20 * 21 // 2)
    return 100.0 * (1 - real / (128 * 512 + 32 * 512))


@pytest.mark.parametrize("metric", [
    "model.prefill_mfu.counted", "model.decode_bw_share.counted",
    "runner.prefill_pad_share", "runner.prefill_attend_pad_share"])
def test_the_counted_readers_sum_matched_work_over_matched_time(
        tmp_path, metric):
    read = spec.load_reader(metric, ROOT)
    ctx = launch_ctx(tr.reduce(named_trace(tmp_path, launches=LAUNCHES)))
    assert read(ctx) == pytest.approx(_counted(metric, ctx), rel=1e-9)
    # the parent's program (no launch named, no count in the ring): None,
    # and no error; the two window-wide readers need no trace at all
    parent = launch_ctx(tr.reduce(named_trace(tmp_path, launches="bare")))
    parent["traced"] = {"flight": [
        {k: v for k, v in r.items() if k in (
            "program", "compile", "steps", "ts_unix")} for r in RING]}
    assert read(parent) is None
    voided = launch_ctx(None)
    if metric.startswith("runner."):
        assert read(voided) == pytest.approx(_counted(metric, ctx))
    else:
        assert read(voided) is None


def test_a_nested_launch_changes_no_phase_reading(tmp_path):
    """The readers of the engine thread's phases read THE SAME with the
    launches nested in them as without: a gap's owner (the third launch
    covers the gap [1000,1100) exactly as far as its phase does, and the
    phase keeps it), the idle the scheduler owns, ``sched.process``."""
    bare = tr.reduce(named_trace(tmp_path, launches="bare"))
    nested = tr.reduce(named_trace(tmp_path, launches=LAUNCHES))
    assert nested["notes"]["phases"] == bare["notes"]["phases"] + 4
    assert nested["breakdown"]["idle_gaps"] == bare["breakdown"]["idle_gaps"]
    assert [g[0] for g in bare["breakdown"]["idle_gaps"]] == [
        "sched.admit", "sched.wait_device", "sched.decode_launch"]
    assert nested["idle_owned_s"] == pytest.approx(bare["idle_owned_s"])
    assert bare["idle_owned_s"] == pytest.approx(250e-9)
    for metric in ("sched.process_ms_p90", "sched.device_idle_share"):
        read = spec.load_reader(metric, ROOT)
        assert read(tiny_ctx(nested)) == read(tiny_ctx(bare)) is not None


# ---------------------------------------------------------------------------
# a ring row accounts for its own wall (PR 53): the host's share by measured
# parts, and who owned the engine thread's time (harness/hostclock.py)

HOST_PARTS = ("launch", "admit", "process", "book", "free", "unnamed")
PR53 = ([f"sched.host_share.{p}" for p in HOST_PARTS]
        + ["sched.chunk_stage_ms_p50", "sched.thread_offcpu_share",
           "sched.worst_row_ms", "sched.worst_row_wait_share",
           "sched.worst_row_cpu_share", "sched.annotated_share"])


def timed_row(launch, program, at_s, ms, **cols) -> dict:
    """A ring row of a program that accounts for its wall: ``ms`` is
    (dispatch, gap, sched, launch, sync), ``cols`` the parts of gap and the
    thread's clocks; what is not given is 0 (``runq_ms`` too)."""
    row = ring_row(launch, program, at_s)
    row.update(zip(("dispatch_ms", "gap_ms", "sched_ms", "launch_ms",
                    "sync_ms"), ms))
    row.update({c: 0.0 for c in (
        "process_ms", "book_ms", "free_ms", "span_ms", "wait_ms", "idle_ms", "cpu_ms",
        "runq_ms", "blocked_ms", "proc_cpu_ms")})
    row.update(cols)
    return row


CLOCKED = [
    # before the window, and a compile-bearing row inside it: left out
    timed_row(1, "decode", -1.0, (900.0, 800.0, 0, 0, 100.0),
              process_ms=700.0, span_ms=900.0, cpu_ms=900.0),
    {**timed_row(2, "decode", 1.0, (5000.0, 4000.0, 0, 0, 1000.0),
                 span_ms=5000.0, cpu_ms=5000.0), "compile": True},
    timed_row(3, "decode", 2.0, (10.0, 4.0, 0.5, 1.5, 4.0), process_ms=2.0,
              book_ms=1.0, free_ms=0.75, span_ms=10.0, wait_ms=4.0, cpu_ms=5.0,
              runq_ms=0.25, blocked_ms=0.75),
    # a chunk: no gap, staging 0.75 ms of its 3 ms
    timed_row(4, "prefill_chunk", 2.1, (3.0, 0.0, 0.75, 2.25, 0.0),
              span_ms=3.0, cpu_ms=3.0),
    # the stall: 2.4 s asleep in the tokens' processing, after 50 ms idle
    timed_row(5, "decode", 3.0, (2460.0, 2450.0, 1.0, 2.0, 5.0),
              process_ms=2401.0, book_ms=1.0, free_ms=8.0, span_ms=2510.0, wait_ms=5.0,
              idle_ms=50.0, cpu_ms=30.0, runq_ms=25.0, blocked_ms=2400.0),
    timed_row(6, "prefill_chunk", 3.1, (5.0, 0.0, 1.25, 3.75, 0.0),
              span_ms=5.0, cpu_ms=4.0, blocked_ms=1.0),
]


def clocked_ctx(rows=CLOCKED) -> dict:
    return {"traced": {"flight": rows}, "anchor": (1000.0, 0.0),
            "window": mtr.Window(0.0, 10.0, 12.0), "trace": None}


def test_the_six_parts_sum_to_the_hosts_share():
    """Over the SAME rows and the same denominator as ``sched.host_share``:
    launch + admit + process + book + free + unnamed is that reader's
    number."""
    ctx = clocked_ctx()
    parts = {p: spec.load_reader(f"sched.host_share.{p}", ROOT)(ctx)
             for p in HOST_PARTS}
    wall = 10.0 + 3.0 + 2460.0 + 5.0
    assert parts == pytest.approx({
        "launch": 100 * (1.5 + 2.25 + 2.0 + 3.75) / wall,
        "admit": 100 * (0.5 + 0.75 + 1.0 + 1.25) / wall,
        "process": 100 * (2.0 + 2401.0) / wall,
        "book": 100 * (1.0 + 1.0) / wall,
        "free": 100 * (0.75 + 8.0) / wall,
        "unnamed": 100 * (0.25 + 40.0) / wall})
    assert sum(parts.values()) == pytest.approx(
        spec.load_reader("sched.host_share", ROOT)(ctx), abs=1e-9)


@pytest.mark.parametrize("metric, value", [
    ("sched.worst_row_ms", 2460.0),         # row 5: its span less its idle
    ("sched.worst_row_wait_share", 100 * 5.0 / 2460.0),
    ("sched.worst_row_cpu_share", 100 * 30.0 / 2460.0),
    # (runq + blocked) over (span - wait - idle), all four rows
    ("sched.thread_offcpu_share",
     100 * (0.25 + 0.75 + 25.0 + 2400.0 + 1.0) / (6.0 + 3.0 + 2455.0 + 5.0)),
    # the chunk rows' sched_ms alone: 0.75 and 1.25, not a decode row's
    ("sched.chunk_stage_ms_p50", 1.0),
])
def test_the_thread_readers_read_the_windows_rows(metric, value):
    read = spec.load_reader(metric, ROOT)
    assert read(clocked_ctx()) == pytest.approx(value, rel=1e-12)


def test_the_pr53_readers_answer_none_where_there_is_nothing_to_read():
    """The parent's ring (no part, no clock), an empty window: None and no
    error; the chunk's staging is in the parent's ring already. A kernel
    whose file cannot be read (``runq_ms`` null in every row: the machine
    the benchmark runs on, so no reader reads that column alone) takes
    nothing from the others."""
    old = clocked_ctx([{k: v for k, v in r.items() if k in (
        "launch", "program", "compile", "steps", "ts_unix", "dispatch_ms",
        "gap_ms", "sched_ms", "launch_ms", "sync_ms")} for r in CLOCKED])
    for metric in PR53:
        read = spec.load_reader(metric, ROOT)
        assert read(clocked_ctx([])) is None, metric
        if metric == "sched.chunk_stage_ms_p50":
            assert read(old) == pytest.approx(1.0)
        else:
            assert read(old) is None, metric
    blind = clocked_ctx([{**r, "runq_ms": None} for r in CLOCKED])
    assert spec.load_reader("sched.thread_offcpu_share", ROOT)(
        blind) == pytest.approx(100 * (0.75 + 2400.0 + 1.0) / 2469.0)
    assert spec.load_reader("sched.worst_row_ms", ROOT)(blind) == 2460.0


def test_every_pr53_entry_has_its_reader_and_the_files_kinds():
    entries = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
    by_name = {m["name"]: m for m in entries}
    # appended as one block at what was then the end; behind it only what
    # later PRs appended for cells of their own (PR 55's two)
    first = [m["name"] for m in entries].index(PR53[0])
    before, behind = entries[:first], entries[first + len(PR53):]
    assert [m["name"] for m in entries[first:first + len(PR53)]] == PR53
    assert all("workloads" in m for m in behind)
    for name in PR53:
        m = by_name[name]
        assert (BENCH / "layers" / f"{name}.py").exists()
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves"}          # no list: every cell reports it
        assert m["layer"] == "scheduler" and m["moves"] == "tpot_ms_p90"
        assert m["unit"] in {b["unit"] for b in before}
        assert m["source"] in {b["source"] for b in before}
        assert m["better"] == ("higher" if name == "sched.annotated_share"
                               else "lower")


def phase_trace(tmp_path, engine) -> dict:
    """A one-chip trace with device operations over [100,1100) ns and the
    given engine-thread annotations [(name, start, ns)]."""
    path = tmp_path / "p.xplane.pb"
    path.write_bytes(xspace.space([
        xspace.plane("/device:TPU:0", {
            "XLA Ops": [(HLO["mlp"], 100, 100), (HLO["mlp"], 1000, 100)],
            "XLA Modules": [(f"jit__decode_paged_fn({FP})", 100, 100),
                            (f"jit__decode_paged_fn({FP})", 1000, 100)]}),
        xspace.plane("/host:CPU", {"engine-tiny/71": sorted(
            engine, key=lambda ev: (ev[1], -ev[2]))}),
        xspace.plane("Task Environment", {}, {
            "profile_start_time": T0, "profile_stop_time": T0 + 2000})]))
    return tr.reduce(path)


def test_the_annotated_share_is_100_on_a_tiled_slice_and_less_with_a_hole(
        tmp_path):
    """The slice is [100,1100) ns. Tiled by phases (a launch nested in one,
    an idle wait of 200 ns that is no part of the busy loop, annotations
    that begin before the slice and end after it): 100. The same with
    ``sched.record`` [600,660) taken out: 740 of 800 busy ns. A program that
    annotates nothing, or no trace: None."""
    read = spec.load_reader("sched.annotated_share", ROOT)
    tiled = [("sched.admit", 50, 150), ("sched.count", 200, 50),
             ("sched.decode_launch", 250, 100), ("sched.launch/7", 260, 80),
             ("sched.wait_device", 350, 150), ("sched.process", 500, 100),
             ("sched.record", 600, 60), ("sched.free", 660, 40),
             ("sched.idle", 700, 200),
             ("sched.admit", 900, 50), ("sched.prefill_chunk", 950, 400)]
    assert read(tiny_ctx(phase_trace(tmp_path, tiled))) == pytest.approx(100.0)
    holed = [ev for ev in tiled if ev[0] != "sched.record"]
    assert read(tiny_ctx(phase_trace(tmp_path, holed))) == pytest.approx(
        100 * 740 / 800)
    bare = tr.reduce(named_trace(tmp_path, host=False, scopes=False))
    assert read(tiny_ctx(bare)) is None and read(tiny_ctx(None)) is None
    # the parent's annotations (PR 25's six and the launches): a number
    parent = read(tiny_ctx(tr.reduce(named_trace(tmp_path, launches=LAUNCHES))))
    assert 0.0 < parent < 100.0


# ---------------------------------------------------------------------------
# --trace 2, end to end on the CPU


@pytest.mark.parametrize("workload", ["tiny-open", "tiny-closed"])
def test_trace_2_scores_first_and_asks_the_server_afterwards(
        bench_copy, workload, capsys, monkeypatch, tmp_path):
    """One line with both kinds of metric; the end-to-end values are what
    ``--trace 0`` computes from the same records; the rings are read, the
    profiler started and the ``trace`` stream sent only after the last scored
    record has ended."""
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])
    # the open-loop tiny cell reports TTFT: it stands for the chat cells in
    # the three metrics of PR 39 that name their cells (in the copy)
    entries = json.loads((bench_copy / "BENCHMARK.json").read_text())
    for m in entries["per_layer"]:
        if m["name"] in CHAT_COUNTED:
            m["workloads"].append("tiny-open")
    (bench_copy / "BENCHMARK.json").write_text(json.dumps(entries))
    # ONE decode program (in the copy). The tiny configuration allows two
    # steps a dispatch, and the scheduler takes them only while its host
    # time exceeds its step time (``Scheduler._effective_steps``): on a CPU
    # that is a matter of the machine's load, some 50 dispatches of 3000, so
    # ``decode_n``'s FIRST dispatch, which compiles, fell into warm-up in one
    # run and into the traced slice in another, and a compile inside the
    # slice voids it (no ``device.idle_share``: PR 59's run of tier-1). What
    # is held here is the order of events and the arithmetic, not that choice
    tiny = bench_copy / "benchmark" / "configs" / "tiny.json"
    config = json.loads(tiny.read_text())
    config["engine"]["decode_steps_per_dispatch"] = 1
    tiny.write_text(json.dumps(config))
    asked: dict[str, list] = {}
    for name in ("read_flight", "read_traces", "capture_trace"):
        def spy(*args, _real=getattr(bench, name), _name=name, **kw):
            asked.setdefault(_name, []).append(time.monotonic())
            return _real(*args, **kw)
        monkeypatch.setattr(bench, name, spy)

    def reduce_run(run_dir, traced, _real=tr.reduce_run):
        # the real capture's file is there (and has no device plane)
        assert tr.find_xplane(run_dir, traced) is not None
        # the hand-made trace's launches carry numbers of the ring's own
        # rows of the kinds it runs: decode, a chunk, decode, decode
        ring = {"decode": [], "prefill": []}
        for r in traced["flight"]:
            if lch.row_kind(r["program"]) and not r["compile"]:
                ring[lch.row_kind(r["program"])].append(r["launch"])
        dec, pre = ring["decode"][-3:], ring["prefill"][-1]
        fake = named_trace(tmp_path, launches=(dec[0], pre, dec[1], dec[2]))
        monkeypatch.setattr(tr, "find_xplane", lambda *a: fake)
        return _real(run_dir, traced)

    monkeypatch.setattr(tr, "reduce_run", reduce_run)
    # (6 s, not 3: beside five busy workers a 3 s window of the closed loop
    # can end before any request does, and then holds no admission's chunk)
    rc = bench.main(["--workload", workload, "--seed", str(2**31 + 5),
                     "--seconds", "6", "--trace", "2"], platform="cpu",
                    root=bench_copy)
    said = capsys.readouterr()
    assert rc == 0, said.err[-2000:]     # (why the harness failed)
    lines = said.out.strip().splitlines()
    out = json.loads(lines[-1])
    assert set(out) == {"correct", "attempted", "failed", "metrics",
                        "device", "breakdown"}
    assert out["correct"] is True and out["failed"] == 0
    cell = spec.load_cell(workload, bench_copy)
    e2e = {m["name"] for m in cell.end_to_end}
    assert e2e < set(out["metrics"])
    for name in ("sched.host_share", "runner.occupancy_mean",
                 "runner.compiles_in_window", "device.idle_share",
                 "runner.kv_move_share", "sched.process_ms_p90",
                 "sched.device_idle_share", *PR53):
        assert name in out["metrics"], name
    parts = [out["metrics"][f"sched.host_share.{p}"]["value"]
             for p in HOST_PARTS]
    assert sum(parts) == pytest.approx(
        out["metrics"]["sched.host_share"]["value"], abs=1e-6)
    # PR 39's four: the decode share in every cell, the prefill three where
    # the cell reports TTFT and in no other
    assert "model.decode_bw_share.counted" in out["metrics"]
    assert (CHAT_COUNTED <= set(out["metrics"])) == (workload == "tiny-open")
    assert (CHAT_COUNTED & set(out["metrics"]) == set()) == (
        workload == "tiny-closed")
    notes = json.loads(next(ln for ln in lines if ln.startswith("trace "))[6:])
    assert notes["compiles_in_slice"] == 0
    assert notes["launches"]["matched"] == {"decode": 2, "prefill": 1}
    assert {"busy_s", "window_s", "memory_peak_bytes"} <= set(out["device"])
    assert out["breakdown"]["idle_gaps"][0][0] == "sched.admit"

    run_dir = bench_copy / "benchmark" / ".run" / workload
    raw = json.loads(next(run_dir.glob("raw-*.json")).read_text())
    records = [mtr.Record(
        idx=r["idx"], stream=r["stream"], due=r["due"], sent=r["sent"],
        status=r["status"], times=r["times"], counts=r["counts"],
        done=r["done"], ended=r["ended"], max_tokens=r["max_tokens"],
        # the raw file keeps a reply's verdict, not its text
        error=r["problem"], finish_reason="length",
        text="a" * r["max_tokens"], completion_tokens=r["max_tokens"],
    ) for r in raw["records"]]
    w = mtr.Window(*raw["window"])
    # what --trace 0 prints: the same function on the records as they stood
    # when the last scored one ended; the slice's, all later, change nothing
    again = mtr.end_to_end(records, w, raw["loop"], raw["setup_s"])
    for name in e2e:
        assert out["metrics"][name]["value"] == pytest.approx(again[name])
    scored = mtr.scored(records, w, raw["loop"])
    slice_ = [r for r in records if r.stream == "trace"]
    assert out["attempted"] == len(scored) > 0 and slice_
    assert not {id(r) for r in slice_} & {id(r) for r in scored}
    last = max(r.ended for r in scored)
    if raw["loop"] == "closed":     # cut off at the close
        last = max(last, w.t_close)
    assert len(asked["capture_trace"]) == 2     # one thrown away, one read
    for name, times in asked.items():
        assert min(times) > last, name
    assert min(r.sent for r in slice_) > last
    assert not list(run_dir.rglob("*.xplane.pb"))   # reduced, then deleted


# -- PR 59: rides over small last chunks, from the ring ----------------------


def _ride_rows(*rows):
    """Ring rows (program, seconds into the window, chunk_bucket[, compile])."""
    return [{"program": p, "ts_unix": 1000.0 + at, "chunk_bucket": bucket,
             "compile": bool(rest), "launch": i + 1}
            for i, (p, at, bucket, *rest) in enumerate(rows)]


@pytest.mark.parametrize("rows, value", [
    # three rides, one small last chunk that met an idle engine; a 512-row
    # chunk is no candidate, nor is a row outside the window or one that
    # compiled
    ([("decode_chunk", 1.0, 128), ("decode_chunk", 2.0, 128),
      ("decode_chunk", 3.0, 64), ("prefill_chunk", 4.0, 128),
      ("prefill_chunk", 5.0, 512), ("decode", 5.5, 0),
      ("prefill_chunk", -1.0, 128), ("prefill_chunk", 11.0, 128),
      ("prefill_chunk", 6.0, 128, True), ("decode_chunk", 7.0, 128, True)],
     75.0),
    ([("decode_chunk", 1.0, 128)], 100.0),
    # no ride in the window (the parent's ring; a cell that steps aside;
    # an empty ring): nothing to read, and no error
    ([("prefill_chunk", 4.0, 128), ("decode", 5.0, 0)], None),
    ([], None)])
def test_chunk_ride_share_counts_rides_over_small_last_chunks(rows, value):
    from localai_tpu.engine import runner

    read = spec.load_reader("runner.chunk_ride_share", ROOT)
    assert read.__globals__["RIDE_ROWS"] == runner.RIDE_ROWS
    assert read(clocked_ctx(_ride_rows(*rows))) == (
        value if value is None else pytest.approx(value))
    # a ring with no ``chunk_bucket`` column (before PR 39): still no error
    bare = [{k: v for k, v in r.items() if k != "chunk_bucket"}
            for r in _ride_rows(*rows)]
    assert read(clocked_ctx(bare)) == (None if value is None else 100.0)


@pytest.mark.parametrize("name, moves, cell", [
    ("runner.chunk_ride_share", "stall_ms_p98", "m7b-decode"),
    # PR 61: a family's forward takes the ride; the accepted entry lists its
    # cells, so this cell's reads the same reader under a name of its own
    ("lfm2.chunk_ride_share", "tpot_ms_p90", "lfm2-pp2-decode"),
    # PR 64: the DeltaNet hybrid's forward takes it too, under its cell's name
    ("gdn.chunk_ride_share", "tpot_ms_p90", "qn80-ep8-decode")])
def test_the_ride_entry_is_appended_for_the_one_cell_that_claims_it(
        name, moves, cell):
    # (by NAME: later PRs append behind it, PR 62 the first)
    entries = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
    assert next(e for e in entries if e["name"] == name) == {
        "name": name, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "runner",
        "moves": moves, "workloads": [cell]}
    rows = _ride_rows(("decode_chunk", 1.0, 128), ("prefill_chunk", 2.0, 128),
                      ("prefill_chunk", 3.0, 512))
    read = spec.load_reader(name, ROOT)
    assert read(clocked_ctx(rows)) == 50.0
    assert read(clocked_ctx(rows[1:])) is None


# ---------------------------------------------------------------------------
# the sampler's share of the device (PR 63)

SAMPLER = {     # HLO line -> (program, the path it was staged under)
    "%fusion.20 = s32[8,2040,8]{2,1,0} fusion(bf16[64,261120]{1,0} %l)":
        ("_decode_paged_fn", "decode/sample/tile_max/reduce_max:"),
    "%sort.21 = s32[64,2048]{1,0} sort(s32[64,2048]{1,0} %p)":
        ("_decode_paged_fn", "decode/sample/topk/sort:"),
    "%fusion.22 = f32[64,256]{1,0} fusion(f32[64,256]{1,0} %c)":
        ("_decode_paged_fn", "decode/sample/div:"),
    "%fusion.23 = s32[96,4096]{1,0} fusion(f32[96,65536]{1,0} %l)":
        ("_decode_prefill_paged_fn", "ride/sample/chunk_max/reduce_max:"),
    "%fusion.24 = s32[1,4096]{1,0} fusion(f32[1,65536]{1,0} %l)":
        ("_prefill_paged_fn", "prefill/sample/chunk_max/reduce_max:"),
    "%fusion.25 = bf16[64,128]{1,0} fusion(bf16[64,64]{1,0} %h)":
        ("_decode_paged_fn", "decode/layers/while/body/mlp/dot_general:"),
    "%fusion.26 = bf16[64,128]{1,0} fusion(bf16[64,64]{1,0} %g)":
        ("_decode_paged_fn", "decode/resample_ish/dot_general:"),
}


def sampler_trace(tmp_path, lines) -> dict:
    """One chip; the given operations of ``SAMPLER`` run 100 ns each, end
    to end, each inside an execution of its own program."""
    ops, modules, meta = [], [], {}
    for i, line in enumerate(lines):
        program, path = SAMPLER[line]
        fp = 70 + sorted({p for p, _ in SAMPLER.values()}).index(program)
        ops.append((line, 100 + 100 * i, 100))
        modules.append((f"jit_{program}({fp})", 100 + 100 * i, 100))
        meta[line] = {"tf_op": f"jit({program})/jit(main)/{path}",
                      "program_id": fp}
    path = tmp_path / "s.xplane.pb"
    path.write_bytes(xspace.space([
        xspace.plane("/device:TPU:0", {"XLA Ops": ops,
                                       "XLA Modules": modules}, meta=meta),
        xspace.plane("Task Environment", {}, {
            "profile_start_time": T0, "profile_stop_time": T0 + 2000})]))
    return tr.reduce(path)


def test_sample_device_share_reads_the_decode_programs_sampler(tmp_path):
    """``sample.device_share``: the device seconds of the decode programs'
    operations under a ``sample`` scope (the front stage's, the older
    stages', the draw directly under it; the ride's, whose program is a
    decode program too) over the slice's busy time. A chunk program's
    sampler, the layers and a scope that only holds the word are not it. It
    reads the PARENT's trace too (``chunk_max`` and ``topk`` alone: the
    scope is PR 45's); a trace with no such row, or none, reads nothing."""
    entries = json.loads((ROOT / "BENCHMARK.json").read_text())["per_layer"]
    assert next(e for e in entries if e["name"] == "sample.device_share") == {
        "name": "sample.device_share", "unit": "%", "better": "lower",
        "source": "device_trace", "layer": "runner", "moves": "tpot_ms_p90",
        "workloads": ["fh1-34b-decode", "lfm2-pp2-decode",
                      "sala-longdoc-decode", "m7b-decode"]}
    read = spec.load_reader("sample.device_share", ROOT)
    assert read(tiny_ctx(sampler_trace(tmp_path, list(SAMPLER)))) == (
        pytest.approx(100.0 * 4 / 7))
    parents = [ln for ln, (_, p) in SAMPLER.items() if "tile_" not in p]
    assert read(tiny_ctx(sampler_trace(tmp_path, parents))) == (
        pytest.approx(100.0 * 3 / 6))
    rest = [ln for ln, (prog, p) in SAMPLER.items()
            if "/sample/" not in p or prog == "_prefill_paged_fn"]
    assert read(tiny_ctx(sampler_trace(tmp_path, rest))) is None
    bare = tr.reduce(named_trace(tmp_path, host=False, scopes=False))
    assert read(tiny_ctx(bare)) is None
    assert read(tiny_ctx(None)) is None
