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
    adds a configuration, a mix, a cell and a per-layer metric as NEW files;
    the program is linked in, since the copy is not a checkout."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns(
        ".cache", ".run", "__pycache__"))
    (root / "localai_tpu").symlink_to(ROOT / "localai_tpu")
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    extra = json.loads((DATA / "tiny_entries.json").read_text())
    for kind in ("configs", "traffic", "cells", "layers"):
        for f in (DATA / kind).iterdir():
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
