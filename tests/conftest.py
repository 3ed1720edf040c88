"""Test harness: tests and drives run on the CPU — JAX_PLATFORMS=cpu, an
8-device virtual mesh so every sharding path is exercised, Pallas kernels in
interpret mode (asked for explicitly), Mosaic through the v5e topology
compile (tests/test_tpu_compile.py). The chip is reached only through the
chip tool, one process per chip, starting with ``python chip_smoke.py``."""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402

# Modules measured ≥ ~20 s on CPU CI (per-file wall clock, 2026-07) get the
# module-level `slow` marker, leaving a <2-minute inner-loop tier:
#   python -m pytest -m "not slow" -q     (fast tier)
#   python -m pytest -q                   (everything)
# Re-measure when adding heavy suites; pyproject registers the marker.
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


# The tier-1 files that take longest (minutes of one worker, 2026-10), longest
# first. They are collected FIRST: under ``-n 6 --dist loadfile`` a file goes
# whole to the next free worker in collection order, and a ten-minute file
# that starts last (``test_tpu_compile`` by the alphabet) is the run's tail.
LONGEST_FIRST = (
    "test_paged", "test_tpu_compile", "test_qwen3_next", "test_afmoe",
    "test_bench_walk", "test_deepseek", "test_dots3", "test_overlap",
    "test_kv_contract", "test_chip_smoke", "test_ouro", "test_spec",
    "test_dots3_compile", "test_prefill_span",
)


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
