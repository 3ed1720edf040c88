"""The benchmark's contract, driven end to end on the CPU at a tiny
configuration (the device check is stubbed HERE only: ``platform="cpu"`` and a
peak-table row for it), and the proof that run plainly it accepts nothing but
a TPU. The tiny configuration, its two mixes, its two cells and one per-layer
metric are NEW files added to a temporary copy of the benchmark: the harness
finds them by name, and no file of the benchmark is edited for them."""

import json
import os
import subprocess
import sys

import pytest

import run as bench
import xspace
from conftest import BENCH, ROOT, add_architecture, result_line
from harness import spec, trace_reduce

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_an_added_cell_runs_by_name_and_prints_the_result_line(
        bench_copy, cpu_peaks, capsys):
    rc = bench.main(["--workload", "tiny-open", "--seed", "3", "--seconds",
                     "3", "--trace", "0"], platform="cpu", root=bench_copy)
    assert rc == 0
    out = result_line(capsys)
    assert set(out) == RESULT_KEYS
    assert out["correct"] is True and out["failed"] == 0
    assert out["attempted"] == 12                   # 4 requests/s for 3 s
    assert set(out["metrics"]) == {"ttft_ms_mean", "tpot_ms_p90",
                                   "stall_ms_p98", "setup_s"}
    for m in out["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert out["device"] == {"platform": "cpu", "kind": "cpu", "count": 1,
                             "memory_peak_bytes": 0}
    raw = json.loads(next((bench_copy / "benchmark" / ".run" / "tiny-open")
                          .glob("raw-*.json")).read_text())
    assert raw["compiles_in_window"] == 0 and raw["check"]["positions"] == 64
    # the cache is at a fixed path inside the checkout
    assert any((bench_copy / "benchmark" / ".cache" / "jax").iterdir())


def test_a_closed_loop_cell_reports_tokens_per_second(bench_copy, cpu_peaks,
                                                      capsys):
    rc = bench.main(["--workload", "tiny-closed", "--seed", "1", "--seconds",
                     "3", "--trace", "0"], platform="cpu", root=bench_copy)
    assert rc == 0
    out = result_line(capsys)
    assert set(out["metrics"]) == {"tpot_ms_p90", "stall_ms_p98",
                                   "out_tok_s", "setup_s"}
    assert out["attempted"] >= 4 and out["failed"] == 0 and out["correct"]


# Mixtral's keys: a sparse-expert block no other configuration has
MOE = {"model_type": "mixtral", "num_local_experts": 4,
       "num_experts_per_tok": 2}


def test_an_added_architecture_runs_by_files_alone(bench_copy, cpu_peaks,
                                                   capsys):
    """A sparse-expert configuration (4 experts, top-2: a block no other
    configuration of the benchmark has) runs by name, is checked against ITS
    family's reference and is counted by ITS family's arithmetic."""
    add_architecture(bench_copy, "tiny-moe", "moe_family", **MOE)
    cell = spec.load_cell("tiny-moe-closed", bench_copy)
    assert cell.family_file == (bench_copy / "benchmark" / "reference"
                                / "moe_family.py")
    rc = bench.main(["--workload", "tiny-moe-closed", "--seed", "5",
                     "--seconds", "3", "--trace", "0"], platform="cpu",
                    root=bench_copy)
    assert rc == 0
    out = result_line(capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert set(out["metrics"]) == {"tpot_ms_p90", "stall_ms_p98",
                                   "out_tok_s", "setup_s"}
    check = json.loads(next((bench_copy / "benchmark" / ".run"
                             / "tiny-moe-closed").glob("raw-*.json"))
                       .read_text())["check"]
    # the served model is the model described: four experts a layer and a
    # router, 287552 weights, where the dense tiny configuration holds 139584
    assert check["params_served"] == check["params_described"] == 287552
    assert check["params_served"] == cell.family.param_count(cell.published)
    dense = spec.load_cell("tiny-closed", bench_copy)
    assert dense.family.param_count(dense.published) == 139584
    assert check["positions"] == 64 and check["ok"] is True


def test_a_family_that_is_missing_or_incomplete_is_an_error(bench_copy,
                                                            capsys):
    config = add_architecture(bench_copy, "tiny-moe", "no_such_family", **MOE)
    with pytest.raises(spec.SpecError, match="reference.family "
                       "'no_such_family' names no file"):
        spec.load_cell("tiny-moe-closed", bench_copy)
    # a module with the mathematics and without the arithmetic
    (bench_copy / "benchmark" / "reference" / "half_family.py").write_text(
        "from reference.llama_family import (decoder_layer, logits,\n"
        "                                    rope_tables)\n")
    config.write_text(json.dumps({**json.loads(config.read_text()),
                                  "reference": {"epsilon": 0.06,
                                                "family": "half_family"}}))
    with pytest.raises(spec.SpecError) as e:
        spec.load_cell("tiny-moe-closed", bench_copy)
    assert "half_family.py" in str(e.value)
    assert "param_count" in str(e.value) and "step_params" in str(e.value)
    assert "decoder_layer" not in str(e.value).split("lacks")[1]
    # and the run says so, with no result line
    assert bench.main(["--workload", "tiny-moe-closed"], platform="cpu",
                      root=bench_copy) != 0
    assert "{" not in capsys.readouterr().out


def hand_made_trace(monkeypatch, path, ops, chips=1):
    """Have ``trace_reduce.reduce_run`` read a hand-made trace in place of
    the real capture's (the CPU backend writes no device plane): ``ops`` on
    each of ``chips`` planes, two executions of the decode program, the
    profile starting when the capture was asked for."""
    real_reduce = trace_reduce.reduce_run

    def reduce_run(run_dir, traced):
        # the real capture's file is there
        assert trace_reduce.find_xplane(run_dir, traced) is not None
        start = int(traced["asked_unix"] * 1e9)
        path.write_bytes(xspace.space([
            xspace.plane(f"/device:TPU:{i}", {
                "XLA Ops": ops,
                "XLA Modules": [("jit__decode_paged_fn(1)", 1000, 10**8),
                                ("jit__decode_paged_fn(1)", 3 * 10**8,
                                 2 * 10**8)]})
            for i in range(chips)] + [xspace.plane("Task Environment", {}, {
                "profile_start_time": start,
                "profile_stop_time": start + 10**9})]))
        monkeypatch.setattr(trace_reduce, "find_xplane", lambda *a: path)
        return real_reduce(run_dir, traced)

    monkeypatch.setattr(trace_reduce, "reduce_run", reduce_run)


def test_a_traced_run_reports_the_per_layer_metrics(bench_copy, cpu_peaks,
                                                    capsys, monkeypatch,
                                                    tmp_path):
    """The CPU backend has no device plane, so the reduction is handed a
    hand-made trace; everything around it (the capture through
    /backend/trace, the flight ring, the spans, the readers found by name,
    the line) is the real path."""
    hand_made_trace(monkeypatch, tmp_path / "fake.xplane.pb",
                    [("fusion.1", 1000, 10**8),
                     ("fusion.2", 3 * 10**8, 2 * 10**8)])
    rc = bench.main(["--workload", "tiny-open", "--seed", "2", "--seconds",
                     "6", "--trace", "1"], platform="cpu", root=bench_copy)
    assert rc == 0
    out = result_line(capsys)
    assert set(out) == RESULT_KEYS | {"breakdown"}
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert out["device"]["busy_s"] == pytest.approx(0.3)
    assert out["device"]["window_s"] == pytest.approx(0.5, abs=1e-5)
    m = out["metrics"]
    assert "ttft_ms_mean" not in m              # --trace 1: per-layer only
    assert m["test.requests_seen"]["value"] > 20        # the added reader
    assert m["runner.compiles_in_window"]["value"] == 0
    assert m["device.idle_share"]["value"] == pytest.approx(40.0, abs=0.01)
    assert m["gen.send_late_ms_p99"]["value"] < 50
    for name in ("load.load_s", "http.overhead_ms_p50", "sched.host_share",
                 "sched.queue_wait_ms_p90", "runner.occupancy_mean",
                 "runner.kv_used_peak_share", "model.decode_bw_share",
                 "http.attained_share"):
        assert name in m, name
    # nothing to read -> left out: the hand-made trace has no custom call
    assert "paged_decode_attn_roofline" not in m


@pytest.mark.parametrize("workload, chips, slice_s", [
    ("tiny-open", 1, 3.0), ("tiny-tp4-open", 4, 1.5)])
def test_the_traced_slice_is_cut_to_the_chips(
        bench_copy, cpu_peaks, capsys, monkeypatch, tmp_path, workload,
        chips, slice_s):
    """``--trace 2`` asks the profiler for ``run.trace_for_s(chips)`` seconds
    (one capture of 0.1 s is thrown away first): 3.0 on one chip, 1.5 on
    four, since what a capture costs goes with seconds x planes and the
    profiler writes a plane a chip. The four-chip cell is served
    tensor-parallel over four CPU devices of the child, and its line carries
    ``mesh.collective_share``, which no one-chip cell's does."""
    assert bench.trace_for_s(chips) == slice_s
    monkeypatch.setenv(
        "XLA_FLAGS", f"--xla_force_host_platform_device_count={chips}")
    asked = []

    def capture(server, client, at, seconds, out, _real=bench.capture_trace):
        asked.append(seconds)
        return _real(server, client, at, seconds, out)

    monkeypatch.setattr(bench, "capture_trace", capture)
    ops = [("%fusion.1 = f32[4,64]{1,0} fusion(f32[4,64]{1,0} %x)", 1000,
            10**8)]
    if chips > 1:
        ops.append(("%all-reduce.2 = f32[4,64]{1,0} all-reduce(f32[4,64]{1,0} "
                    "%fusion.1)", 2 * 10**8, 10**8))
    hand_made_trace(monkeypatch, tmp_path / "fake.xplane.pb", ops, chips)
    rc = bench.main(["--workload", workload, "--seed", str(2**31 + 9),
                     "--seconds", "3", "--trace", "2"], platform="cpu",
                    root=bench_copy)
    assert rc == 0
    assert asked == [0.1, slice_s]
    out = result_line(capsys)
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == chips
    got = {k: v["value"] for k, v in out["metrics"].items()
           if k.startswith("mesh.")}
    assert got == (pytest.approx({"mesh.collective_share": 50.0}, abs=1e-3)
                   if chips > 1 else {})
    assert "ttft_ms_mean" in out["metrics"]


def test_a_compile_inside_the_slice_voids_the_slice_and_not_the_run(
        bench_copy, cpu_peaks, capsys, monkeypatch, tmp_path):
    """A watched program that compiles while the slice is traced (the counter
    is made to step once the slice's capture has answered) takes the
    device-trace readers' trace away: their metrics are left out of the
    line. The window closed before, so the run stays ``correct`` with every
    end-to-end metric and the window-wide readers on its line."""
    late = []

    async def capture(server, client, at, seconds, out,
                      _real=bench.capture_trace):
        await _real(server, client, at, seconds, out)
        if seconds != 0.1:          # not the capture that is thrown away
            late.append(1)

    monkeypatch.setattr(bench, "capture_trace", capture)
    monkeypatch.setattr(bench, "compiles",
                        lambda text, _real=bench.compiles:
                        _real(text) + len(late))
    hand_made_trace(monkeypatch, tmp_path / "fake.xplane.pb",
                    [("fusion.1", 1000, 10**8)])
    rc = bench.main(["--workload", "tiny-open", "--seed", "11", "--seconds",
                     "3", "--trace", "2"], platform="cpu", root=bench_copy)
    assert rc == 0
    lines = capsys.readouterr().out.strip().splitlines()
    out = json.loads(lines[-1])
    notes = json.loads(next(ln for ln in lines if ln.startswith("trace "))[6:])
    assert notes["compiles_in_slice"] == 1
    assert out["correct"] is True and out["failed"] == 0
    m = out["metrics"]
    assert {"ttft_ms_mean", "tpot_ms_p90", "stall_ms_p98", "setup_s"} <= set(m)
    assert m["runner.compiles_in_window"]["value"] == 0
    assert "runner.occupancy_mean" in m
    for name in ("device.idle_share", "model.decode_bw_share",
                 "runner.kv_move_share", "sched.device_idle_share"):
        assert name not in m, name


class FakeRing:
    """``/debug/flight`` as the program answers it: with ``since`` the OLDEST
    ``limit`` records after it, without it the newest."""

    def __init__(self, n_rows: int, tied: range = range(0)):
        # the rows of ``tied`` share one ``ts``; ``row`` tells them apart
        self.rows = [{"ts": 100.0 + (tied[0] if i in tied else i),
                      "ts_unix": 5000.0 + i, "row": i}
                     for i in range(n_rows)]
        self.asked: list = []
        self.session, self.base, self.name = self, "http://ring", "tiny"

    def get(self, url: str):
        query = dict(kv.split("=") for kv in url.split("?")[1].split("&"))
        since, limit = float(query["since"]), int(query["limit"])
        self.asked.append(since)
        after = [r for r in self.rows if r["ts"] > since]
        page = after[:limit] if since else after[-limit:]
        ring = self

        class Reply:
            async def __aenter__(self):
                return self

            async def __aexit__(self, *exc):
                return False

            async def json(self):
                return {"models": {ring.name: {"records": page}}}

        return Reply()


@pytest.mark.parametrize("n_rows, pages, tied", [
    (0, 1, range(0)), (300, 1, range(0)), (4096, 2, range(0)),
    (9000, 3, range(0)), (9000, 3, range(4094, 4098)),
    (9000, 3, range(8188, 8200))])
def test_the_flight_ring_is_paged_forward_until_a_short_page(n_rows, pages,
                                                             tied):
    """A window's rows are read whole however many they are (one read of the
    newest 4096 cut a window of a faster step): forward from the ring's
    oldest record, page by page, and a later read goes on where it ended.
    Rows that share a ``ts`` across a page's end are not lost to the next
    page's strict ``ts > since``."""
    import asyncio

    ring = FakeRing(n_rows, tied)
    out: list = []
    since = asyncio.run(bench.page_flight(ring, ring, bench.RING_START, out))
    assert out == ring.rows and len(ring.asked) == pages
    assert ring.asked[0] == bench.RING_START > 0    # 0 asks for the newest
    assert since == (ring.rows[-1]["ts"] if n_rows else bench.RING_START)
    ring.rows += [{"ts": 1e6 + i, "ts_unix": 1e7 + i} for i in range(5)]
    asyncio.run(bench.page_flight(ring, ring, since, out))
    assert out == ring.rows


def test_the_four_chip_cell_reports_what_a_chat_cell_and_a_mesh_report():
    """BENCHMARK.json as committed: ``ms24b-tp4-chat`` gets TTFT, the four
    readers that move it and the ``mesh.*`` reader; the one-chip cells get no
    ``mesh.*`` reader; its rate is 0.7-0.8 x the knee its file states."""
    from harness import spec

    chat = {"http.overhead_ms_p50", "sched.queue_wait_ms_p90",
            "runner.kv_used_peak_share", "model.prefill_mfu"}
    mesh = {"mesh.collective_share"}
    cells = {w["name"]: spec.load_cell(w["name"]) for w in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["workloads"]}
    new = cells["ms24b-tp4-chat"]
    assert new.chips == 4 and new.config["sharding"] == {
        "tensor_parallel_size": 4}
    assert {m["name"] for m in new.end_to_end} == {
        "ttft_ms_mean", "tpot_ms_p90", "stall_ms_p98", "setup_s"}
    layers = {m["name"] for m in new.per_layer}
    assert chat | mesh <= layers
    # m7b-chat reads the gap tail per layer, not end to end (it sits on a
    # cliff there), and the two readers that move the tail under names that
    # move its TTFT: otherwise the two chat cells report the same
    one = cells["m7b-chat"]
    assert {m["name"] for m in one.end_to_end} == {
        "ttft_ms_mean", "tpot_ms_p90", "setup_s"}
    split = {"gen.send_late_ms_p99", "runner.compiles_in_window"}
    assert layers - mesh - split == {m["name"] for m in one.per_layer} - {
        "sched.stall_ms_p98"} - {s + ".ttft" for s in split}
    assert split <= layers and {s + ".ttft" for s in split} | {
        "sched.stall_ms_p98"} <= {m["name"] for m in one.per_layer}
    # every per-layer metric names an end-to-end metric its cells report
    for cell in cells.values():
        assert {m["moves"] for m in cell.per_layer} <= {
            m["name"] for m in cell.end_to_end}, cell.name
    for name, cell in cells.items():
        if cell.chips == 1:
            assert not mesh & {m["name"] for m in cell.per_layer}, name
    # the rate is what the sweep found: 0.7-0.8 x the knee in the cell file
    knee = new.drive["knee_rps"]
    assert 0.7 * knee <= new.drive["rate_rps"] <= 0.8 * knee + 1e-9


def test_plain_run_exits_nonzero_without_a_tpu():
    """In this sandbox: no accelerator, so no result line, never a CPU
    number. With JAX_PLATFORMS=cpu it refuses at once; without it the child
    asks for the TPU and fails."""
    cmd = [sys.executable, str(BENCH / "run.py"), "--workload", "m7b-chat",
           "--seed", "0", "--seconds", "1", "--trace", "0"]
    for env in ({**os.environ, "JAX_PLATFORMS": "cpu"},
                {k: v for k, v in os.environ.items()
                 if k != "JAX_PLATFORMS"}):
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=300, env=env, cwd=str(ROOT))
        assert proc.returncode != 0
        assert not any(line.startswith("{")
                       for line in proc.stdout.splitlines()), proc.stdout


def test_alone_in_a_directory_it_fails(bench_copy, cpu_peaks, capsys):
    """Only BENCHMARK.json and the benchmark's files: there is no program to
    measure, so a non-zero exit and no result line."""
    (bench_copy / "localai_tpu").unlink()
    rc = bench.main(["--workload", "tiny-open", "--seed", "1", "--seconds",
                     "1", "--trace", "0"], platform="cpu", root=bench_copy)
    assert rc != 0
    assert not any(line.startswith("{")
                   for line in capsys.readouterr().out.splitlines())


def test_an_unknown_workload_or_key_is_an_error(bench_copy, capsys):
    assert bench.main(["--workload", "nope"], platform="cpu",
                      root=bench_copy) != 0
    cell = bench_copy / "benchmark" / "cells" / "tiny-open.json"
    cell.write_text(json.dumps({**json.loads(cell.read_text()),
                                "rate": 3}))
    assert bench.main(["--workload", "tiny-open"], platform="cpu",
                      root=bench_copy) != 0
    # a configuration states how close to the reference it must come
    cfg = bench_copy / "benchmark" / "configs" / "tiny.json"
    cfg.write_text(json.dumps({k: v for k, v in json.loads(
        cfg.read_text()).items() if k != "reference"}))
    assert bench.main(["--workload", "tiny-closed"], platform="cpu",
                      root=bench_copy) != 0
    assert "{" not in capsys.readouterr().out


def test_benchmark_json_names_only_files_that_exist():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer",
                         "trace_in_run"}
    assert spec["trace_in_run"] is True     # run.py takes --trace 2
    for c in spec["configs"]:
        assert (ROOT / c["file"]).exists()
    for w in spec["workloads"]:
        assert (BENCH / "cells" / f"{w['name']}.json").exists()
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert len(w["why"]) <= 200
    for m in spec["per_layer"]:
        assert (BENCH / "layers" / f"{m['name']}.py").exists(), m["name"]
        assert m["moves"] in {e["name"] for e in spec["end_to_end"]}
    four = [w for w in spec["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(spec["workloads"]) // 4)
