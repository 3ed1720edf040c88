"""Multi-device sharding tests on the virtual 8-device CPU mesh (conftest
forces xla_force_host_platform_device_count=8 — the simulated-multi-host
strategy SURVEY.md §4 calls for, absent in the reference)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from localai_tpu.engine.runner import ModelRunner
from localai_tpu.engine.scheduler import GenRequest, Scheduler
from localai_tpu.models.registry import resolve_model
from localai_tpu.parallel import sharding as shd
from localai_tpu.parallel.mesh import MeshPlan, build_mesh
from localai_tpu.utils.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def mesh():
    assert len(jax.devices()) == 8
    return build_mesh(MeshPlan(data=2, model=4))


@pytest.fixture(scope="module")
def sharded_runner(mesh):
    tiny = resolve_model("debug:small", dtype="float32")
    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    runner = ModelRunner(
        tiny.cfg, params, num_slots=4, max_ctx=128,
        prefill_buckets=[16, 32], kv_dtype="float32", mesh=mesh,
    )
    return tiny, runner


def test_param_specs_cover_all_params(mesh):
    tiny = resolve_model("debug:small", dtype="float32")
    specs = shd.param_specs(tiny.cfg, mesh)
    jax.tree.map(
        lambda spec, arr: None, specs, tiny.params,
        is_leaf=lambda x: isinstance(x, P),
    )  # same treedef or this throws


def test_sharded_weights_are_distributed(sharded_runner, mesh):
    tiny, runner = sharded_runner
    wq = runner.params["layers"]["wq"]
    assert len(wq.sharding.device_set) == 8
    # column-parallel: last dim split over 'model' (4-way)
    shard_shape = wq.sharding.shard_shape(wq.shape)
    assert shard_shape[-1] == wq.shape[-1] // 4
    kv = runner.kv.k
    assert kv.sharding.shard_shape(kv.shape)[1] == kv.shape[1] // 2  # slots/dp


def test_sharded_generation_matches_single_device(mesh):
    tiny = resolve_model("debug:small", dtype="float32")
    prompt = list(b"sharding parity test")

    r1 = ModelRunner(tiny.cfg, tiny.params, num_slots=4, max_ctx=128,
                     prefill_buckets=[32], kv_dtype="float32")
    t1 = [r1.admit(r1.acquire_slot(), prompt, temperature=0.0)]
    t1 += [int(r1.step()[0]) for _ in range(8)]

    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    r2 = ModelRunner(tiny.cfg, params, num_slots=4, max_ctx=128,
                     prefill_buckets=[32], kv_dtype="float32", mesh=mesh)
    t2 = [r2.admit(r2.acquire_slot(), prompt, temperature=0.0)]
    t2 += [int(r2.step()[0]) for _ in range(8)]
    assert t1 == t2


def test_scheduler_on_sharded_runner(mesh):
    tiny = resolve_model("debug:small", dtype="float32")
    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    runner = ModelRunner(tiny.cfg, params, num_slots=4, max_ctx=128,
                         prefill_buckets=[32], kv_dtype="float32", mesh=mesh)
    s = Scheduler(runner, ByteTokenizer())
    try:
        tok = ByteTokenizer()
        hs = [
            s.submit(GenRequest(prompt=tok.encode(f"concurrent {i}"),
                                max_new_tokens=6, temperature=0.0))
            for i in range(5)
        ]
        for h in hs:
            h.result(120)
            assert h.finish_reason is not None
            assert h.completion_tokens > 0
    finally:
        s.shutdown()


def test_kv_replication_fallback_when_tp_exceeds_kv_heads():
    mesh8 = build_mesh(MeshPlan(model=8))
    tiny = resolve_model("debug:tiny", dtype="float32")  # 2 kv heads < 8
    spec = shd.kv_spec(tiny.cfg, mesh8)
    assert spec == P(None, "data", None, None, None)


def test_param_placement_places_host_leaves_sharded(mesh):
    """The load path's placement: a host leaf (plain or host-quantized)
    goes straight to its shards — q with the weight's spec, the scale with
    the contracted axis dropped."""
    from localai_tpu.models.quant import quantize_tensor_host

    small = resolve_model("debug:small", dtype="float32")
    place = shd.ParamPlacement(small.cfg, mesh)
    arr = np.random.default_rng(0).standard_normal(
        (small.cfg.num_layers, small.cfg.hidden_size,
         small.cfg.num_heads * small.cfg.hd)).astype(np.float32)
    placed = place.put(("layers", "wq"), arr)
    assert placed.sharding.shard_shape(placed.shape)[-1] == arr.shape[-1] // 4
    qt = place.put(("layers", "wq"), quantize_tensor_host(arr, 1))
    assert qt.q.sharding.shard_shape(qt.q.shape)[-1] == arr.shape[-1] // 4
    assert qt.scale.sharding.shard_shape(qt.scale.shape) == (
        small.cfg.num_layers, arr.shape[-1] // 4)
    # no mesh: the default device
    solo = shd.ParamPlacement(small.cfg).put(("layers", "wq"), arr)
    assert len(solo.sharding.device_set) == 1


def test_debug_preset_leaves_are_generated_on_their_shards(mesh):
    """debug presets never exist unsharded: each leaf's generator program
    writes straight into the placement's sharding, values unchanged."""
    small = resolve_model("debug:small", dtype="float32")
    sharded = resolve_model(
        "debug:small", dtype="float32",
        placement=shd.ParamPlacement(small.cfg, mesh))
    wq = sharded.params["layers"]["wq"]
    assert wq.sharding.shard_shape(wq.shape)[-1] == wq.shape[-1] // 4
    np.testing.assert_array_equal(
        np.asarray(wq), np.asarray(small.params["layers"]["wq"]))
    q8 = resolve_model(
        "debug:small", dtype="float32", quantization="int8",
        placement=shd.ParamPlacement(small.cfg, mesh))
    up = q8.params["layers"]["w_up"]
    assert up.q.dtype == jnp.int8 and up.mode == "w8"
    assert up.q.sharding.shard_shape(up.q.shape)[-1] == up.q.shape[-1] // 4


# ---------------------------------------------------------------------------
# paged-pool partition rules + mesh spec parsing (ISSUE 8)


def test_paged_kv_spec_shards_kv_heads_on_model(mesh):
    small = resolve_model("debug:small", dtype="float32")  # 4 kv heads
    # [L, num_blocks, Hkv, bt, hd]: ONLY the kv-head axis shards — block
    # ids in the host tables are global, so the block axis must stay
    # whole on every device
    assert shd.paged_kv_spec(small.cfg, mesh) == \
        P(None, None, "model", None, None)


def test_paged_kv_spec_replicates_on_indivisible_kv_heads():
    mesh8 = build_mesh(MeshPlan(model=8))
    tiny = resolve_model("debug:tiny", dtype="float32")  # 2 kv heads < 8
    assert shd.paged_kv_spec(tiny.cfg, mesh8) == P(None, None, None,
                                                   None, None)


def test_block_table_spec_puts_slots_on_data():
    assert shd.block_table_spec() == P("data", None)


def test_meshed_paged_pool_and_tables_are_sharded():
    tiny = resolve_model("debug:tiny", dtype="float32")  # 2 kv heads
    mesh2 = build_mesh(MeshPlan(data=4, model=2))
    params = shd.shard_params(tiny.params, tiny.cfg, mesh2)
    r = ModelRunner(tiny.cfg, params, num_slots=4, max_ctx=64,
                    prefill_buckets=[16], kv_dtype="float32", mesh=mesh2,
                    paged=True, kv_block_tokens=16)
    k = r.kv.k
    # pool [L, N, Hkv, bt, hd]: kv heads split 2-way, block axis whole
    assert k.sharding.shard_shape(k.shape)[2] == k.shape[2] // 2
    assert k.sharding.shard_shape(k.shape)[1] == k.shape[1]
    bt = r.block_tables
    assert bt.sharding.shard_shape(bt.shape)[0] == bt.shape[0] // 4


def test_parse_mesh_spec_both_syntaxes_and_unknown_axis():
    from localai_tpu.parallel.mesh import parse_mesh_spec

    assert parse_mesh_spec("data=2,model=4") == {"data": 2, "model": 4}
    assert parse_mesh_spec("data:2,model:4") == {"data": 2, "model": 4}
    assert parse_mesh_spec("") is None
    with pytest.raises(ValueError, match="unknown mesh axis"):
        parse_mesh_spec("modle=4")  # a typo must not serve unsharded


def test_default_tensor_parallel_prefers_all_devices():
    from localai_tpu.parallel.mesh import default_tensor_parallel

    assert default_tensor_parallel(8, num_heads=32) == 8   # model=all
    assert default_tensor_parallel(8, num_heads=12) == 4   # widest divisor
    assert default_tensor_parallel(8, num_heads=7) == 1    # no split
    assert default_tensor_parallel(1, num_heads=32) == 1


def test_localai_mesh_env_parses_into_app_config(monkeypatch):
    from localai_tpu.config.app_config import AppConfig

    monkeypatch.setenv("LOCALAI_MESH", "data:2,model:4")
    assert AppConfig.from_env().mesh_shape == {"data": 2, "model": 4}
    monkeypatch.setenv("LOCALAI_MESH", "")
    assert AppConfig.from_env().mesh_shape is None


def test_manager_serves_meshed_paged_by_default(monkeypatch):
    """ROADMAP item 3 acceptance: with >1 visible device the manager
    builds the mesh itself — no flag — and keeps the paged layout under
    it (LOCALAI_MESH_AUTO=1 stands in for a real accelerator host: the
    CPU backend is excluded from auto-meshing so tier-1 single-device
    semantics stay byte-identical)."""
    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.models.manager import build_runner

    mcfg = ModelConfig(**{
        "name": "meshed", "model": "debug:tiny",
        "engine": {"max_slots": 4, "prefill_buckets": [16, 32],
                   "dtype": "float32", "kv_dtype": "float32"},
    })
    app = AppConfig()

    monkeypatch.setenv("LOCALAI_MESH_AUTO", "1")
    _, runner = build_runner(mcfg, app)
    assert runner.mesh is not None and runner.paged
    # model=all: tiny's 4 q heads cap tp at 4, dp fills the rest
    assert runner.mesh.shape["model"] == 4
    assert runner.mesh.shape["data"] == 2

    # CPU without the force flag: no mesh, single-device paged unchanged
    monkeypatch.delenv("LOCALAI_MESH_AUTO")
    _, r2 = build_runner(mcfg, app)
    assert r2.mesh is None and r2.paged

    # explicit topology (--mesh / LOCALAI_MESH → mesh_shape) always wins
    app_explicit = AppConfig(mesh_shape={"data": 4, "model": 2})
    _, r3 = build_runner(mcfg, app_explicit)
    assert dict(r3.mesh.shape) == {"data": 4, "seq": 1, "pipe": 1,
                                   "expert": 1, "model": 2}
    assert r3.paged
