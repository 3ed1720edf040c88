"""Mixtral-class sparse MoE: torch parity, engine serving, expert-axis
sharding, and quantized serving (VERDICT r4 #5 — 'make the expert axis
real'). Parity surface: the reference serves Mixtral GGUFs through
llama.cpp (gallery mixtral entries)."""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import llama as mdl
from localai_tpu.models.registry import DEBUG_PRESETS, resolve_model
from localai_tpu.parallel import sharding as shd
from localai_tpu.parallel.mesh import MeshPlan, build_mesh

torch = pytest.importorskip("torch")
from transformers import MixtralConfig as HFMixtralConfig  # noqa: E402
from transformers import MixtralForCausalLM  # noqa: E402

from localai_tpu.models.loader import load_llama_params  # noqa: E402


def _tiny_mixtral(tmp_path, seed=0):
    torch.manual_seed(seed)
    cfg = HFMixtralConfig(
        vocab_size=96, hidden_size=64, intermediate_size=112,
        num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
        num_local_experts=4, num_experts_per_tok=2,
        max_position_embeddings=256, rope_theta=10000.0,
        sliding_window=None, tie_word_embeddings=False,
    )
    model = MixtralForCausalLM(cfg).eval()
    d = tmp_path / "mixtral"
    model.save_pretrained(d, safe_serialization=True)
    return model, d


def _load_f32(d):
    cfg, params = load_llama_params(d, dtype="float32")
    return dataclasses.replace(cfg, dtype="float32"), params


PROMPT = [5, 17, 3, 42, 9, 88, 1, 63]


def test_mixtral_logits_match_torch(tmp_path):
    model, d = _tiny_mixtral(tmp_path)
    cfg, params = _load_f32(d)
    assert cfg.num_experts == 4 and cfg.num_experts_per_tok == 2

    import jax.numpy as jnp

    from localai_tpu.engine import kvcache as kvc

    T = len(PROMPT)
    tokens = jnp.asarray(np.asarray(PROMPT, np.int32)[None])
    kv = kvc.init_cache(cfg, 1, 64, "float32")
    hidden, _ = mdl.forward(
        cfg, params, tokens, jnp.arange(T, dtype=jnp.int32)[None],
        kvc.prefill_write(jnp.int32(0), jnp.zeros((), jnp.int32)),
        kv.stacked(), kvc.prefill_mask(cfg, T, jnp.int32(T)),
        mdl.rope_table(cfg, 64),
    )
    ours = np.asarray(mdl.logits_from_hidden(cfg, params, hidden[0]))
    with torch.no_grad():
        ref = model(torch.tensor([PROMPT])).logits[0].float().numpy()
    np.testing.assert_allclose(ours, ref, atol=3e-4, rtol=3e-4)


def test_mixtral_engine_greedy_matches_torch(tmp_path):
    model, d = _tiny_mixtral(tmp_path)
    cfg, params = _load_f32(d)
    runner = ModelRunner(cfg, params, num_slots=2, max_ctx=64,
                         prefill_buckets=[16], kv_dtype="float32")
    s = runner.acquire_slot()
    ours = [runner.admit(s, PROMPT, temperature=0.0)]
    while len(ours) < 10:
        ours.append(int(runner.step()[s]))

    ids = list(PROMPT)
    with torch.no_grad():
        for _ in range(10):
            ids.append(int(model(torch.tensor([ids])).logits[0, -1].argmax()))
    assert ours == ids[len(PROMPT):]


def test_expert_axis_shards_weights_and_preserves_output():
    """data×expert×model mesh: expert weights REALLY shard over 'expert'
    (addressable shard carries E/ep experts) and greedy output matches the
    unsharded runner."""
    moe = resolve_model("debug:tiny-moe", dtype="float32")
    mesh = build_mesh(MeshPlan(data=2, expert=2, model=2))
    sp = shd.shard_params(moe.params, moe.cfg, mesh)

    wg = sp["layers"]["w_gate"]
    shard = wg.addressable_shards[0].data
    E = moe.cfg.num_experts
    assert wg.shape[1] == E
    assert shard.shape[1] == E // 2, "expert axis not actually sharded"
    assert shard.shape[3] == wg.shape[3] // 2, "ffn axis not TP-sharded"

    r = ModelRunner(moe.cfg, sp, num_slots=4, max_ctx=128,
                    prefill_buckets=[32], kv_dtype="float32", mesh=mesh)
    s = r.acquire_slot()
    out = [r.admit(s, PROMPT, temperature=0.0)] + [int(r.step()[s])
                                                   for _ in range(6)]

    rx = ModelRunner(moe.cfg, moe.params, num_slots=2, max_ctx=128,
                     prefill_buckets=[32], kv_dtype="float32")
    s2 = rx.acquire_slot()
    ref = [rx.admit(s2, PROMPT, temperature=0.0)] + [int(rx.step()[s2])
                                                     for _ in range(6)]
    assert out == ref


def test_quantized_moe_serving():
    """int8 quantization covers the expert weights (per-channel over the
    contraction axis) and the quantized engine still routes/serves."""
    from localai_tpu.models.quant import QuantizedTensor, quantize_params

    moe = resolve_model("debug:tiny-moe", dtype="float32")
    q = quantize_params(moe.params)
    wg = q["layers"]["w_gate"]
    assert isinstance(wg, QuantizedTensor) and wg.axis == 2
    L, E, D, F = moe.params["layers"]["w_gate"].shape
    assert wg.scale.shape == (L, E, F)
    assert not isinstance(q["layers"]["moe_gate"], QuantizedTensor)

    cfg = dataclasses.replace(moe.cfg, dtype="bfloat16")
    r = ModelRunner(cfg, q, num_slots=2, max_ctx=128,
                    prefill_buckets=[32], kv_dtype="int8")
    s = r.acquire_slot()
    first = r.admit(s, PROMPT, temperature=0.0)
    toks = [first] + [int(r.step()[s]) for _ in range(4)]
    assert all(0 <= t < cfg.vocab_size for t in toks)


def test_synthetic_quantized_moe_params():
    from localai_tpu.models.registry import synthetic_params

    cfg = dataclasses.replace(DEBUG_PRESETS["tiny-moe"], dtype="bfloat16")
    params = synthetic_params(cfg, "int8")
    assert params["layers"]["w_gate"].q.shape[1] == cfg.num_experts
    r = ModelRunner(cfg, params, num_slots=2, max_ctx=128,
                    prefill_buckets=[32], kv_dtype="int8")
    s = r.acquire_slot()
    r.admit(s, PROMPT, temperature=0.0)
    assert r.step().shape == (2,)


def test_moe_through_scheduler(tmp_path):
    """End-to-end: YAML → build_serving_model → scheduler generation on the
    MoE preset."""
    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.engine.scheduler import GenRequest
    from localai_tpu.models.manager import build_serving_model

    mcfg = ModelConfig(
        name="moe", model="debug:tiny-moe", context_size=256,
        engine={"max_slots": 2, "prefill_buckets": [32]},
    )
    sm = build_serving_model(mcfg, AppConfig(model_path=str(tmp_path)))
    try:
        h = sm.scheduler.submit(GenRequest(
            prompt=PROMPT, max_new_tokens=4, temperature=0.0,
        ))
        h.result(timeout=120)
        assert h.finish_reason in ("stop", "length")
    finally:
        sm.scheduler.shutdown()
