"""The plain reference against the served path at tiny widths: the program's
own runner (paged, chunked prefill, decode through the cache, int8 weights)
serves greedy tokens; the reference, teacher-forced on the served weights,
must rank every served token first. And the check can fail: a reference given
weights that differ from the served ones shows a mean shortfall far above the
epsilon a configuration file allows (the control that is a LOWER PRECISION,
int8 activations, fails on the chip at the published widths and not here: at
test widths it flips no more near-ties than weight-only int8 does, PERF.md
section 2). Both at two tiny shapes: the 7B's (one device, head_dim =
hidden_size / heads) and the 24B's (an explicit head_dim that is not
hidden_size / heads, served tensor-parallel over four devices: conftest.py
asks the CPU backend for eight)."""

import dataclasses
import json

import jax
import numpy as np
import pytest

import run as bench
from conftest import BENCH
from harness import refcheck

# what the landed configurations allow on the chip, each by the statistic its
# file names (run.py ``judge``); here compute is float32
LANDED = [json.loads((BENCH / "configs" / f"{name}.json").read_text())[
    "reference"] for name in ("mistral-7b-v0.3-int8",
                              "mistral-small-24b-int8-tp4")]

HF = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
      "num_hidden_layers": 2, "num_attention_heads": 4,
      "num_key_value_heads": 2, "max_position_embeddings": 512,
      "rope_theta": 10000.0, "rms_norm_eps": 1e-5}
# head_dim 16 where hidden_size / heads is 8; 4 kv heads, one a device
HF_TP4 = {**HF, "num_attention_heads": 8, "num_key_value_heads": 4,
          "head_dim": 16}
LETTERS = {i: 100.0 for i in range(ord("a"), ord("z") + 1)}


@pytest.fixture(scope="module", params=[(HF, 1), (HF_TP4, 4)],
                ids=["one-device", "head_dim-tp4"])
def served(request):
    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models.llama import LlamaConfig
    from localai_tpu.models.registry import synthetic_params

    hf, tp = request.param
    # float32 compute: the served path then differs from the reference by
    # summation order only, and every shortfall is ~0
    cfg = dataclasses.replace(LlamaConfig.from_hf(hf), dtype="float32")
    params = synthetic_params(cfg, "int8", seed=0)
    mesh = None
    if tp > 1:
        from localai_tpu.parallel import sharding as shd
        from localai_tpu.parallel.mesh import MeshPlan, build_mesh

        if len(jax.devices()) < tp:
            pytest.skip(f"needs {tp} CPU devices (jax_num_cpu_devices, set "
                        f"in conftest.py before the backend starts)")
        assert cfg.hd * cfg.num_heads != cfg.hidden_size
        mesh = build_mesh(MeshPlan(model=tp), devices=jax.devices()[:tp])
        params = shd.shard_params(params, cfg, mesh)
    runner = ModelRunner(cfg, params, num_slots=2, max_ctx=512, paged=True,
                         attn_impl="xla", prefill_chunk=64, mesh=mesh)
    rng = np.random.default_rng(0)
    probes = []
    for n in (16, 200):          # one bucket; chunked, multi-block
        prompt = [256] + rng.integers(32, 127, n - 1).tolist()
        slot = runner.acquire_slot()
        toks = [runner.admit(slot, prompt, temperature=0.0,
                             logit_bias=LETTERS)]
        for _ in range(3):
            toks.append(int(runner.step()[slot]))
        runner.release(slot)
        probes.append({"prompt": prompt, "served": toks})
    return runner.params, hf, probes


def test_served_tokens_rank_first_in_the_reference(served):
    params, hf, probes = served
    rows = refcheck.shortfalls(params, hf, probes)
    short = [p["shortfall"] for r in rows for p in r]
    assert len(short) == 8
    assert all(ord("a") <= t <= ord("z") for p in probes for t in p["served"])
    assert max(short) < 1e-4, short
    assert [bench.judge(short, ref)["statistic"] for ref in LANDED] == [
        "max", "mean"]
    assert all(bench.judge(short, ref)["ok"] for ref in LANDED)


def test_the_check_fails_on_other_weights(served):
    params, hf, probes = served
    head = params["lm_head"]
    other = {**params, "lm_head": dataclasses.replace(
        head, q=jax.numpy.flip(head.q, axis=0))}
    short = [p["shortfall"] for r in refcheck.shortfalls(other, hf, probes)
             for p in r]
    assert not any(bench.judge(short, ref)["ok"] for ref in LANDED)
    # far over: ten times what the 24B's file allows the mean
    assert sum(short) / len(short) > 10 * LANDED[1]["epsilon"]


def test_the_verdict_is_on_the_statistic_the_configuration_names():
    """One near-tie of 0.1 among 64 positions: over 0.07 on the largest,
    under 0.009 on the mean; sixteen of them are over both."""
    one, many = [0.1] + [0.0] * 63, [0.1] * 16 + [0.0] * 48
    assert not bench.judge(one, {"epsilon": 0.07})["ok"]        # max
    assert bench.judge(one, {"epsilon": 0.009, "statistic": "mean"})["ok"]
    got = bench.judge(many, {"epsilon": 0.009, "statistic": "mean"})
    assert not got["ok"] and got["mean_shortfall"] == pytest.approx(0.025)
    assert got["max_shortfall"] == 0.1 and got["nonzero"] == 16
