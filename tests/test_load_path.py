"""The load path that fits: a model arrives on its devices in its SERVED
form, leaf by leaf — quantized on the host (checkpoints) or generated in
place (debug presets) — and never whole or in bf16 on one chip first.

A Llama-3-8B is 16 GB in bf16: before this path existed the server put all of
it on the default device, quantized it there through an f32 copy of each
stacked tensor, and only then sharded it — it could not load on one 16 GB
chip, nor on four."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.config.app_config import AppConfig
from localai_tpu.config.model_config import ModelConfig
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import init_params, param_shapes
from localai_tpu.models.registry import (DEBUG_PRESETS, resolve_config,
                                         resolve_model, synthetic_params)
from localai_tpu.parallel.mesh import MeshPlan, build_mesh
from localai_tpu.parallel.sharding import ParamPlacement


@pytest.mark.parametrize("mode", ["int8", "int8_w8a8", "int4"])
def test_host_quantization_equals_device_quantization(mode):
    """Same arithmetic in the same order, bit for bit — stacked (layer by
    layer on the host), expert-stacked, and 2-D leaves."""
    rng = np.random.default_rng(0)
    for shape, axis in (((3, 64, 48), 1), ((2, 4, 32, 16), 2), ((96, 64), 0),
                        ((96, 64), 1)):
        w = (rng.standard_normal(shape) * 0.02).astype(np.float32)
        w = np.asarray(jnp.asarray(w, jnp.bfloat16))     # a bf16 checkpoint
        host = qnt.quantize_tensor_host(w, axis, mode, group=16)
        dev = (qnt.quantize_tensor4(w, axis, group=16) if mode == "int4"
               else qnt.quantize_tensor(w, axis))
        assert isinstance(host.q, np.ndarray)            # stayed on the host
        np.testing.assert_array_equal(
            np.asarray(host.q, np.int8), np.asarray(dev.q, np.int8))
        np.testing.assert_array_equal(host.scale, np.asarray(dev.scale))
        assert host.axis == axis
        assert host.mode == {"int8": "w8", "int8_w8a8": "w8a8",
                             "int4": "w4"}[mode]
        assert str(host.q.dtype) == ("int4" if mode == "int4" else "int8")


def test_quantize_plan_is_what_quantize_params_does():
    cfg = dataclasses.replace(DEBUG_PRESETS["tiny-moe"], dtype="float32")
    params = init_params(jax.random.key(0), cfg)
    for mode in ("int8", "int8_w8a8", "int4"):
        q = qnt.quantize_params(params, mode)
        flat = jax.tree_util.tree_flatten_with_path(
            params, is_leaf=lambda x: isinstance(x, jax.Array))[0]
        for kpath, leaf in flat:
            path = tuple(k.key for k in kpath)
            node = q
            for k in path:
                node = node[k]
            plan = qnt.quantize_plan(path, leaf.ndim, mode)
            assert isinstance(node, qnt.QuantizedTensor) == (plan is not None)
            if plan is not None:
                assert node.axis == plan[0], path


def _save_checkpoint(tmp_path, cfg, params):
    """A tiny HF-layout llama checkpoint from a stacked param tree."""
    import json

    from safetensors.numpy import save_file

    tensors = {"model.embed_tokens.weight": np.asarray(params["embed"]),
               "model.norm.weight": np.asarray(params["final_norm"]),
               "lm_head.weight": np.asarray(params["lm_head"]).T.copy()}
    names = {"attn_norm": ("input_layernorm", False),
             "mlp_norm": ("post_attention_layernorm", False),
             "wq": ("self_attn.q_proj", True), "wk": ("self_attn.k_proj", True),
             "wv": ("self_attn.v_proj", True), "wo": ("self_attn.o_proj", True),
             "w_gate": ("mlp.gate_proj", True), "w_up": ("mlp.up_proj", True),
             "w_down": ("mlp.down_proj", True)}
    for name, (hf, transpose) in names.items():
        stacked = np.asarray(params["layers"][name])
        for i in range(cfg.num_layers):
            a = stacked[i].T if transpose else stacked[i]
            tensors[f"model.layers.{i}.{hf}.weight"] = np.ascontiguousarray(a)
    d = tmp_path / "ckpt"
    d.mkdir()
    save_file(tensors, str(d / "model.safetensors"))
    (d / "config.json").write_text(json.dumps({
        "model_type": "llama", "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.hidden_size,
        "intermediate_size": cfg.intermediate_size,
        "num_hidden_layers": cfg.num_layers,
        "num_attention_heads": cfg.num_heads,
        "num_key_value_heads": cfg.num_kv_heads,
        "max_position_embeddings": cfg.max_position_embeddings,
        "rope_theta": cfg.rope_theta}))
    (d / "tokenizer.json").write_text(json.dumps({
        "version": "1.0", "model": {"type": "WordLevel",
                                    "vocab": {"a": 0}, "unk_token": "a"}}))
    return d


def test_checkpoint_loads_quantized_and_sharded_leaf_by_leaf(tmp_path):
    """Through the loader: what lands on the mesh is the host-quantized
    form of every leaf, equal to quantizing the loaded model on a device —
    and the config is readable before any weight is."""
    cfg = dataclasses.replace(DEBUG_PRESETS["small"], dtype="float32")
    params = init_params(jax.random.key(1), cfg)
    ckpt = _save_checkpoint(tmp_path, cfg, params)
    seen = resolve_config(str(ckpt), dtype="float32")
    assert param_shapes(seen) == param_shapes(cfg)

    mesh = build_mesh(MeshPlan(data=2, model=4))
    loaded = resolve_model(str(ckpt), dtype="float32", quantization="int8",
                           placement=ParamPlacement(seen, mesh))
    want = qnt.quantize_params(params, "int8")
    for name in ("wq", "w_down"):
        got, ref = loaded.params["layers"][name], want["layers"][name]
        np.testing.assert_array_equal(np.asarray(got.q), np.asarray(ref.q))
        np.testing.assert_array_equal(np.asarray(got.scale),
                                      np.asarray(ref.scale))
        assert len(got.q.sharding.device_set) == 8
    wq = loaded.params["layers"]["wq"]
    assert wq.q.sharding.shard_shape(wq.q.shape)[-1] == wq.q.shape[-1] // 4
    assert loaded.params["layers"]["attn_norm"].dtype == jnp.float32
    # a wrong-shaped tensor is refused by name, before it reaches a device
    bad = dataclasses.replace(seen, intermediate_size=999)
    from localai_tpu.models.loader import load_llama_params

    with pytest.raises(ValueError, match="w_gate.*shape"):
        load_llama_params(ckpt, cfg=bad, dtype="float32")


def test_debug_presets_are_generated_in_their_served_form():
    """`engine.quantization` on a debug preset never builds the bf16 model:
    every weight leaf comes out of its generator already quantized."""
    cfg = dataclasses.replace(DEBUG_PRESETS["tiny"], dtype="bfloat16")
    for mode, qdt, tag in (("int8", jnp.int8, "w8"), ("int4", jnp.int4, "w4"),
                           ("int8_w8a8", jnp.int8, "w8a8")):
        p = synthetic_params(cfg, mode, seed=3)
        assert p["layers"]["wq"].q.dtype == qdt
        assert p["layers"]["wq"].mode == tag
        assert p["embed"].q.dtype == jnp.int8       # per-row int8 always
        assert p["layers"]["attn_norm"].dtype == jnp.bfloat16
        # dequantized weights keep init_params' amplitude
        w = np.asarray(qnt.dequantize_tensor(p["layers"]["w_up"]))
        assert 0.005 < float(np.std(w)) < 0.02 and float(np.max(np.abs(w))) <= 0.021
    # seeded: two loads of the same preset are the same model
    a = synthetic_params(cfg, "int8", seed=3)["layers"]["wo"].q
    b = synthetic_params(cfg, "int8", seed=3)["layers"]["wo"].q
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # unquantized: exactly init_params
    plain = resolve_model("debug:tiny", dtype="float32").params
    ref = init_params(jax.random.key(0), dataclasses.replace(
        DEBUG_PRESETS["tiny"], dtype="float32"))
    np.testing.assert_array_equal(np.asarray(plain["layers"]["wq"]),
                                  np.asarray(ref["layers"]["wq"]))


def test_build_runner_places_quantized_leaves_on_the_auto_mesh(
        tmp_path, monkeypatch):
    """The server path (models.manager.build_runner): with >1 device the
    auto mesh is built from the config alone, and the quantized leaves land
    on it sharded — nothing is loaded before the mesh exists."""
    from localai_tpu.models import manager

    monkeypatch.setenv("LOCALAI_MESH_AUTO", "1")    # CPU devices count too
    import localai_tpu.models.registry as registry

    calls = []
    real = registry.resolve_model

    def spy(ref, **kw):
        calls.append(kw)
        return real(ref, **kw)

    monkeypatch.setattr(registry, "resolve_model", spy)
    mcfg = ModelConfig(name="m", model="debug:small", context_size=256,
                       engine={"quantization": "int8", "max_slots": 2,
                               "dtype": "float32"})
    _, runner = manager.build_runner(mcfg, AppConfig(model_path=str(tmp_path)))
    assert runner.mesh is not None and runner.mesh.shape["model"] == 8
    assert calls[0]["quantization"] == "int8"
    assert calls[0]["placement"].mesh is runner.mesh
    wq = runner.params["layers"]["wq"]
    assert isinstance(wq, qnt.QuantizedTensor)
    assert len(wq.q.sharding.device_set) == 8
    assert wq.q.sharding.shard_shape(wq.q.shape)[-1] == wq.q.shape[-1] // 8


def test_one_process_per_chip_is_said_where_the_manager_spawns(monkeypatch):
    """An in-process model and a `backend: worker` model cannot share a
    host's chips: refused with the reason, instead of a child that hangs
    in libtpu."""
    from localai_tpu.models.manager import ModelManager, ServingModel

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    mgr = ModelManager(AppConfig(model_path="/nonexistent"))
    inproc = object.__new__(ServingModel)
    inproc.name = "resident"
    worker_model = ModelConfig(name="w", model="debug:tiny", backend="worker")
    plain_model = ModelConfig(name="p", model="debug:tiny")
    # nothing loaded: nothing to conflict with
    mgr._check_one_process_per_chip(worker_model, spawning_worker=True)
    mgr._models["resident"] = inproc
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="belongs to one process"):
        mgr._check_one_process_per_chip(worker_model, spawning_worker=True)
    # a worker the operator put on the CPU, or a server that holds no chip
    mgr.app.worker_env = {"JAX_PLATFORMS": "cpu"}
    mgr._check_one_process_per_chip(worker_model, spawning_worker=True)
    mgr.app.worker_env = None
    mgr.app.platform = "cpu"
    mgr._check_one_process_per_chip(worker_model, spawning_worker=True)
    mgr.app.platform = ""
    # the other direction: a spawned worker on the TPU holds the chips

    class SpawnedWorkerModel:
        name, device, external_address = "w", {"platform": "tpu"}, None

    mgr._models = {"w": SpawnedWorkerModel()}
    with pytest.raises(RuntimeError, match="belongs to one process"):
        mgr._check_one_process_per_chip(plain_model, spawning_worker=False)
