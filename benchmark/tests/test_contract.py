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
from conftest import BENCH, ROOT
from harness import peaks, trace_reduce

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture()
def cpu_peaks(monkeypatch):
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def result_line(capsys) -> dict:
    lines = capsys.readouterr().out.strip().splitlines()
    for line in lines[:-1]:
        assert not line.startswith("{"), line
    return json.loads(lines[-1])


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


def test_a_traced_run_reports_the_per_layer_metrics(bench_copy, cpu_peaks,
                                                    capsys, monkeypatch,
                                                    tmp_path):
    """The CPU backend has no device plane, so the reduction is handed a
    hand-made trace; everything around it (the capture through
    /backend/trace, the flight ring, the spans, the readers found by name,
    the line) is the real path."""
    fake = tmp_path / "fake.xplane.pb"
    real_reduce = trace_reduce.reduce_run

    def reduce_run(run_dir, traced):
        assert trace_reduce.find_xplane(run_dir, traced) is not None
        start = int(traced["asked_unix"] * 1e9)
        fake.write_bytes(xspace.space([
            xspace.plane("/device:TPU:0", {
                "XLA Ops": [("fusion.1", 1000, 10**8),
                            ("fusion.2", 3 * 10**8, 2 * 10**8)],
                "XLA Modules": [("jit__decode_paged_fn(1)", 1000, 10**8),
                                ("jit__decode_paged_fn(1)", 3 * 10**8,
                                 2 * 10**8)]}),
            xspace.plane("Task Environment", {}, {
                "profile_start_time": start,
                "profile_stop_time": start + 10**9})]))
        monkeypatch.setattr(trace_reduce, "find_xplane", lambda *a: fake)
        return real_reduce(run_dir, traced)

    monkeypatch.setattr(trace_reduce, "reduce_run", reduce_run)
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
