"""Test harness: tests and drives run on the CPU — JAX_PLATFORMS=cpu, an
8-device virtual mesh so every sharding path is exercised, Pallas kernels in
interpret mode (asked for explicitly), Mosaic through the v5e topology
compile (tests/test_tpu_compile.py). The chip is reached only through the
chip tool, one process per chip, starting with ``python chip_smoke.py``."""

import os
import shutil
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"

# A run pays each XLA compilation ONCE. Nearly every case builds a runner of
# its own, so its jitted programs are new Python objects and the in-process
# jit cache misses on HLO the case before (or another worker) compiled: JAX's
# persistent cache, keyed by the HLO, holds it. The process that starts the
# run makes an EMPTY directory named by its own pid (what a killed run leaves
# is never read again) and removes it at the end; xdist's workers and every
# server a test spawns inherit the variables. Nothing carries from one run to
# the next, so no case can pass on a stale entry.
STARTS_THE_RUN = "PYTEST_XDIST_WORKER" not in os.environ
if STARTS_THE_RUN:
    CACHE_DIR = os.path.join(tempfile.gettempdir(),
                             f"localai-tpu-tests-jax-cache-{os.getpid()}")
    shutil.rmtree(CACHE_DIR, ignore_errors=True)
    os.makedirs(CACHE_DIR)
    os.environ.update({
        "JAX_COMPILATION_CACHE_DIR": CACHE_DIR,
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
        "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "-1",
    })

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


def pytest_unconfigure(config):
    if STARTS_THE_RUN:
        shutil.rmtree(CACHE_DIR, ignore_errors=True)


# Modules measured ≥ ~20 s on CPU CI (per-file wall clock, 2026-07) get the
# module-level `slow` marker. What is left is tier-1, and it is no inner loop:
# twelve to fifteen minutes of six workers (2026-10, PR 56: 696-891 s,
# 3906-4979 worker-seconds, 1757 cases, under ``-n 6 --dist loadfile`` on
# eight shared cores; twenty-five minutes and cut by its limit before the
# run's compilation cache above).
#   python -m pytest -m "not slow" -q     (tier-1, one process)
#   python -m pytest -q                   (everything)
# Re-measure when adding heavy suites (seconds per file and worker: the
# driver's command of ROADMAP.md with ``--durations=0 --durations-min=1 -o
# junit_family=xunit2 --junitxml=<file>``, summed by file); pyproject
# registers the marker.
SLOW_MODULES = {
    "test_aio", "test_api", "test_audio", "test_cli", "test_controlnet",
    "test_engine",
    "test_flux", "test_hf_api", "test_image", "test_llama_torch",
    "test_lora",
    "test_mamba", "test_mesh_attn", "test_moe",
    "test_multihost", "test_musicgen", "test_ops", "test_prefix",
    "test_pipeline", "test_promptcache", "test_quant", "test_reranker",
    "test_ring",
    "test_rwkv", "test_sdxl", "test_selfextend", "test_sharding",
    "test_speculative",
    "test_vision", "test_vits", "test_voice_clone", "test_worker",
    "test_worker_serving",
}


# The tier-1 files of half a minute and more, longest first (seconds of one
# of six workers, 2026-10, PR 56: 643 for the first, 31 for the last; PR 59
# moved ``test_paged_serving`` (131 with its ride cases) and ``test_scheduler``
# (60) up to where their seconds stand; PR 61 reordered the first twenty by
# its own run's table: 709 for the first, ``test_lfm2`` 432 with its ride
# cases, ``test_lfm2_compile`` 128 with the ride program's compile; PR 65
# put in, where its run's table has them, four files the alphabet sent out
# late: ``test_smallthinker`` 163, ``test_minicpm_sala`` 114,
# ``test_minicpm_sala_compile`` 99, ``test_bench_walk_router`` 59). They
# are collected FIRST: under ``-n 6 --dist loadfile`` a file goes whole to the
# next free worker in collection order, so the run's wall is a sixth of the
# files' sum and no late file is its tail (``test_tpu_compile``, one file for
# libtpu's lock, is last by the alphabet). Rewrite it from a run's table.
LONGEST_FIRST = (
    "test_tpu_compile", "test_paged", "test_qwen3_next", "test_lfm2",
    "test_afmoe", "test_deepseek", "test_dots3", "test_neighbour_texts",
    "test_chip_smoke", "test_paged_serving", "test_kv_contract",
    "test_overlap", "test_bench_walk_latent", "test_bench_walk",
    "test_smallthinker", "test_lfm2_compile", "test_dots3_compile",
    "test_minicpm_sala", "test_minicpm_sala_compile", "test_ouro",
    "test_falcon_h1", "test_prefill_span", "test_moe_kernel",
    "test_bench_walk_router", "test_int4", "test_spec",
    "test_bench_trace", "test_scheduler", "test_falcon_h1_compile",
    "test_sampling", "test_fleet",
)


def pytest_configure(config):
    # xdist hands out the files with the MOST CASES first unless told not to
    # (``--no-loadscope-reorder``: its option for a suite that orders its own
    # files), which leaves LONGEST_FIRST a tie-break
    config.option.loadscopereorder = False


def pytest_collection_modifyitems(config, items):
    import pathlib

    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    stems = [pathlib.Path(str(item.fspath)).stem for item in items]
    for item, stem in zip(items, stems):
        if stem in SLOW_MODULES:
            item.add_marker(pytest.mark.slow)
    # stable: a file's tests keep their order, the other files the alphabet's
    order = sorted(range(len(items)),
                   key=lambda i: rank.get(stems[i], len(rank)))
    items[:] = [items[i] for i in order]


@pytest.fixture()
def tmp_models_dir(tmp_path):
    d = tmp_path / "models"
    d.mkdir()
    return d


@pytest.fixture()
def fresh_kernel_traces():
    """For a case that plants a fault INSIDE a kernel whose body the program
    keeps one trace of (``ops.attention._paged_decode_call``, ``models.
    qwen3_next.recur_in_place``: inlined ``jax.jit``s, a trace a shape and
    process): the kept traces are dropped before the case, so that what it
    planted is traced whatever ran before it, and after it, so that no later
    case meets the fault's trace."""
    from localai_tpu.models import qwen3_next
    from localai_tpu.ops import attention

    def drop():
        attention._paged_decode_call.clear_cache()
        qwen3_next.recur_in_place.clear_cache()

    drop()
    yield
    drop()


@pytest.fixture()
def in_stack():
    """``in_stack(a, layer=1, layers=3)``: ``a`` as layer ``layer`` of a
    stacked KV cache whose other layers hold noise: what the attention
    kernels take, since the cache rides the layer scan as a carry (a kernel
    that ignored its layer index would read noise)."""
    import jax.numpy as jnp
    import numpy as np

    def stack(a, layer=1, layers=3):
        noise = np.random.default_rng(99).integers(
            -100, 100, (layers,) + a.shape)
        return jnp.asarray(noise, a.dtype).at[layer].set(a)

    return stack
