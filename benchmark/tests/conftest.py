"""Tests of the benchmark's own code, on the CPU at a tiny configuration:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not part of tier-1. A CPU timing is never a device metric: these tests hold
the harness's logic (schedule, arithmetic, reduction, plumbing, the result
line), and the chip is reached only through ``chiprun``.
"""

import json
import os
import shutil
import sys
from pathlib import Path

import pytest

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

# eight CPU devices IN THIS PROCESS, for the tensor-parallel reference case
# (not XLA_FLAGS: a child server inherits the environment, and a one-chip
# cell must find one device); tests/conftest.py, which may have run first,
# asks for the same eight
jax.config.update("jax_num_cpu_devices", 8)

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture()
def bench_copy(tmp_path):
    """A temporary copy of the benchmark (and BENCHMARK.json) to which a test
    adds a configuration, a mix, a cell, a per-layer metric and families that
    are no model's as NEW files; the program is linked in, since the copy is
    not a checkout."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        ".cache", ".run", "__pycache__"))
    (root / "localai_tpu").symlink_to(ROOT / "localai_tpu")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    extra = json.loads((DATA / "tiny_entries.json").read_text())
    for kind in ("configs", "traffic", "cells", "layers", "reference"):
        for f in (DATA / kind).iterdir():
            if f.is_file():
                shutil.copy(f, root / "benchmark" / kind / f.name)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in extra.get(key, []):
            same = [e for e in bench[key] if e["name"] == entry["name"]]
            if not same:
                bench[key].append(entry)
            elif "workloads" in same[0]:    # a metric gains the tiny cells
                same[0]["workloads"] = (same[0]["workloads"]
                                        + entry["workloads"])
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root


@pytest.fixture()
def cpu_peaks(monkeypatch):
    from harness import peaks

    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def result_line(capsys) -> dict:
    lines = capsys.readouterr().out.strip().splitlines()
    for line in lines[:-1]:
        assert not line.startswith("{"), line
    return json.loads(lines[-1])


def add_architecture(root, name, family, **keys):
    """What a later PR adds for a configuration of another architecture, the
    family module apart (``benchmark/reference/<family>.py``): a
    configuration file ``<name>`` (the tiny one's keys and ``keys``) that
    names its family, a cell file ``<name>-closed``, their two BENCHMARK.json
    entries, and the cell's name appended to the ``workloads`` lists of the
    metrics it reports. No file that is there is edited. Returns the
    configuration file's path."""
    config = json.loads((DATA / "configs" / "tiny.json").read_text())
    config.update({"name": name, **keys})
    config["reference"] = {**config["reference"], "family": family}
    path = root / "benchmark" / "configs" / f"{name}.json"
    path.write_text(json.dumps(config))
    (root / "benchmark" / "cells" / f"{name}-closed.json").write_text(
        (DATA / "cells" / "tiny-closed.json").read_text())
    bench_json = root / "BENCHMARK.json"
    entries = json.loads(bench_json.read_text())
    entries["configs"].append({
        "name": name, "source": "benchmark/tests", "reduced": [],
        "file": f"benchmark/configs/{name}.json", "why": "a test"})
    entries["workloads"].append({
        "name": f"{name}-closed", "config": name,
        "traffic": "tiny-closed", "chips": 1, "why": "a test"})
    for metric in entries["end_to_end"] + entries["per_layer"]:
        if "tiny-closed" in metric.get("workloads", ()):
            metric["workloads"].append(f"{name}-closed")
    bench_json.write_text(json.dumps(entries, indent=1))
    return path
