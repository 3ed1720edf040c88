"""bench.py's contract: the two measurement bodies run, and nothing turns a
failure into a line.

bench.py used to be engineered to ALWAYS exit 0 and ALWAYS print one JSON
line — ``value: 0.0`` with the failure in a note, a paged→contiguous
fallback marked after the fact, abandoned threads, ``os._exit(0)``. A
measurement that cannot be taken now ends the process with a traceback and
a non-zero exit code; a number is printed only when it was measured, on a
TPU, with the device named in the line.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def _run_bench(**env_overrides):
    env = dict(os.environ)
    env.update(env_overrides)
    return subprocess.run(
        [sys.executable, "bench.py", "--model", "tiny", "--steps", "2",
         "--multi", "1", "--depth", "1"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=150)


def test_bench_exits_nonzero_on_a_dead_backend():
    """A backend that cannot initialize (no GPU plugin here): the first jax
    use raises, the traceback reaches stderr, no metric line is printed."""
    out = _run_bench(JAX_PLATFORMS="cuda")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "Traceback" in out.stderr or "Error" in out.stderr


def test_bench_refuses_to_measure_the_cpu():
    """A CPU timing is not a device metric: no chip, no line."""
    out = _run_bench(JAX_PLATFORMS="cpu")
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "measures a TPU" in out.stderr


def test_bench_quantized_decode_path_runs_on_cpu():
    """The measurement body itself — synthetic int8 weight generation into
    a bf16-compute, int8-KV paged runner, then pipelined batched decode —
    runs clean (dtype boundaries included) on the CPU backend."""
    sys.path.insert(0, str(REPO))
    import bench

    tok_s, info = bench.run_decode_bench(
        "tiny", "int8", steps=2, multi=1, depth=1,
        num_slots=2, max_ctx=256,
    )
    assert tok_s > 0
    # provenance: every decode line says which kernel, layout and KV dtype
    # produced its number
    assert info == {"kernel_impl": "lax", "kv": "paged", "kv_dtype": "int8",
                    "tokens_per_dispatch": 2}


def test_bench_has_no_contiguous_fallback(monkeypatch):
    """A paged path that raises, raises: the old bench retried on the
    contiguous layout and printed that number under a `paged_fallback`
    note."""
    sys.path.insert(0, str(REPO))
    import bench
    from localai_tpu.engine import runner as rn

    def boom(self, *a, **k):
        raise RuntimeError("paged decode broke")

    monkeypatch.setattr(rn.ModelRunner, "step_n", boom)
    with pytest.raises(RuntimeError, match="paged decode broke"):
        bench.run_decode_bench("tiny", "int8", steps=2, multi=1, depth=1,
                               num_slots=2, max_ctx=256)
