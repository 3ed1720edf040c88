"""The serving programs are built from their configuration alone.

Two directions. The environment cannot shape a runner: each name this repo
once read on the way to a runner (a kernel choice, a pool size, a chunk, a
layout, a KV dtype, a buffer depth, an overlap split, a tuning table from
outside the tree) is set to a value that used to change the runner, and the
runner and its lowered decode program equal those of a clean environment.
And the ``engine.*`` keys the benchmark's cells are written in reach the
runner through ``models.manager.build_runner``.
"""

import json
import re
from pathlib import Path

import jax
import pytest

from localai_tpu.config.app_config import AppConfig
from localai_tpu.config.model_config import ModelConfig
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models.manager import build_runner
from localai_tpu.models.registry import resolve_model
from localai_tpu.parallel.mesh import MeshPlan, build_mesh

PKG = Path(__file__).resolve().parent.parent / "localai_tpu"


@pytest.fixture(scope="module")
def tiny():
    return resolve_model("debug:tiny", dtype="float32")


def bare(tiny, **kw):
    return ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=128,
                       prefill_buckets=[64], **kw)


def served(engine):
    mcfg = ModelConfig(name="m", model="debug:tiny", context_size=128,
                       engine={"max_slots": 2, "prefill_buckets": [64],
                               "dtype": "float32", **engine})
    return build_runner(mcfg, AppConfig())[1]


# how each case builds its runner: the shape the deleted name acted on
BUILD = {
    "bare": lambda tiny: bare(tiny),
    "paged": lambda tiny: bare(tiny, paged=True),
    "paged_kernel": lambda tiny: bare(tiny, paged=True,
                                      attn_impl="pallas_interpret"),
    "mesh2": lambda tiny: bare(
        tiny, paged=True,
        mesh=build_mesh(MeshPlan(model=2), devices=jax.devices()[:2])),
    "served": lambda tiny: served({}),
}


def shape_of(r):
    """What the deleted inputs decided: layout, block size, pool size, chunk,
    kernels, KV dtype, overlap, and the decode program as lowered."""
    lowered = jax.jit(r._decode_paged_fn).lower(
        r.params, r.kv, r.state, r.block_tables)
    pool = None
    if r.paged:
        pool = (r.block_tokens, r.allocator.num_blocks, r.prefill_chunk,
                r.paged_attn_impl, r._paged_attn_interpret, r.overlap_mode)
    return {"paged": r.paged, "pool": pool, "kv_dtype": r.kv_dtype,
            "attn": (r.attn_impl, r._attn_interpret),
            "decode": lowered.as_text()}


def tuning_table(tmp_path):
    """A table in the deleted ops.tuning's format, with this shape's entry."""
    path = tmp_path / "tuning.json"
    path.write_text(json.dumps({"hd16_kv2_bfloat16_tp1": {
        "impl": "xla", "block_tokens": 32, "num_buffers": 3}}))
    return str(path)


DELETED = {
    "LOCALAI_KV_BLOCKS": ("paged", "7"),
    "LOCALAI_PREFILL_CHUNK_TOKENS": ("paged", "64"),
    "LOCALAI_PAGED_NUM_BUFFERS": ("paged_kernel", "3"),
    "LOCALAI_KV_PAGED": ("bare", "1"),
    "LOCALAI_ATTN_IMPL": ("bare", "pallas_interpret"),
    "LOCALAI_PAGED_ATTN_IMPL": ("paged", "pallas_interpret"),
    "LOCALAI_KV_DTYPE": ("served", "int8"),
    "LOCALAI_MESH_OVERLAP_CHUNKS": ("mesh2", "2"),
    "LOCALAI_TUNE_CACHE": ("paged", tuning_table),
}


@pytest.mark.parametrize("name", sorted(DELETED))
def test_the_environment_cannot_shape_a_runner(tiny, monkeypatch, tmp_path,
                                               name):
    for n in DELETED:
        monkeypatch.delenv(n, raising=False)
    kind, value = DELETED[name]
    clean = shape_of(BUILD[kind](tiny))
    monkeypatch.setenv(name, value if isinstance(value, str)
                       else value(tmp_path))
    assert shape_of(BUILD[kind](tiny)) == clean


def test_the_hot_path_reads_no_environment():
    """engine/runner.py and ops/ hold no ``os.environ``; the three names
    tests and tools still set are each read in one module, the one that
    owns the decision."""
    sources = {p: p.read_text() for p in PKG.rglob("*.py")}
    for p in [PKG / "engine/runner.py", *(PKG / "ops").glob("*.py")]:
        assert "os.environ" not in sources[p], p
    owner = {"LOCALAI_KV_BLOCK_TOKENS": "engine/paged.py",
             "LOCALAI_KV_OVERCOMMIT": "engine/paged.py",
             "LOCALAI_MESH_OVERLAP": "parallel/overlap.py"}
    for name, module in owner.items():
        reads = re.compile(r"environ[^\n]*\n?[^\n]*\"" + name + r"\"")
        readers = sorted(str(p.relative_to(PKG)) for p, s in sources.items()
                         if reads.search(s))
        assert readers == [module], (name, readers)


def test_the_runner_holds_one_family_of_serving_programs():
    """One decode, decode-n, frozen-n, verify and chunk program over the
    runner's layout object (engine.kvcache): the contiguous-named twins are
    gone, and what dispatches the family does not ask which layout it has."""
    import inspect

    for twin in ("_decode_fn", "_decode_n_fn", "_decode_frozen_n_fn",
                 "_verify_fn", "_prefill_resume_fn"):
        assert not hasattr(ModelRunner, twin), twin
    for name in ("step_async", "verify_async", "step_n_async",
                 "step_frozen_n"):
        assert "paged" not in inspect.getsource(
            getattr(ModelRunner, name)).replace("_paged", ""), name
    # the layout's decisions are the layout's: the runner names no decode
    # kernel and no decode write policy
    source = (PKG / "engine/runner.py").read_text()
    assert "decode_attention" not in source
    for policy in ("decode_write", "verify_write", "resume_write",
                   "paged_decode_write", "paged_verify_write",
                   "paged_prefill_write", "span_attend", "window_attend",
                   "kernel_attend"):
        assert f"kvc.{policy}" not in source, policy


@pytest.fixture(scope="module")
def served_by_default():
    return served({})


@pytest.mark.parametrize("key, value, reads", [
    ("kv_paged", False, lambda r: r.paged),
    ("kv_dtype", "int8", lambda r: r.kv_dtype),
    ("kv_block_tokens", 32, lambda r: r.block_tokens),
    ("kv_num_blocks", 9, lambda r: r.allocator.num_blocks),
    ("prefill_chunk", 128, lambda r: r.prefill_chunk),
    ("attn_impl", "pallas_interpret",
     lambda r: "pallas_interpret" if (r.paged_attn_impl == "pallas"
                                      and r._paged_attn_interpret) else "?"),
])
def test_the_keys_the_cells_are_written_in_reach_the_runner(
        served_by_default, key, value, reads):
    """PERF.md section 3, "What the benchmark touches": a cell is written in
    these ``engine.*`` keys, and each is the runner's attribute."""
    assert served_by_default.paged and reads(served_by_default) != value
    assert reads(served({key: value})) == value
