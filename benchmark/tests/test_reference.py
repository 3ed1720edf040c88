"""The plain reference against the served path at tiny widths: the program's
own runner (paged, chunked prefill, decode through the cache, int8 weights)
serves greedy tokens; the reference, teacher-forced on the served weights,
must rank every served token first. And the check can fail: a reference given
weights that differ from the served ones shows shortfalls far above the
epsilon a configuration file allows."""

import dataclasses
import json

import jax
import numpy as np
import pytest

from conftest import BENCH
from harness import refcheck

# what the landed configuration allows on the chip; here compute is float32
EPSILON = json.loads((BENCH / "configs" / "mistral-7b-v0.3-int8.json")
                     .read_text())["reference"]["epsilon"]

HF = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
      "num_hidden_layers": 2, "num_attention_heads": 4,
      "num_key_value_heads": 2, "max_position_embeddings": 512,
      "rope_theta": 10000.0, "rms_norm_eps": 1e-5}
LETTERS = {i: 100.0 for i in range(ord("a"), ord("z") + 1)}


@pytest.fixture(scope="module")
def served():
    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models.llama import LlamaConfig
    from localai_tpu.models.registry import synthetic_params

    # float32 compute: the served path then differs from the reference by
    # summation order only, and every shortfall is ~0
    cfg = dataclasses.replace(LlamaConfig.from_hf(HF), dtype="float32")
    params = synthetic_params(cfg, "int8", seed=0)
    runner = ModelRunner(cfg, params, num_slots=2, max_ctx=512, paged=True,
                         attn_impl="xla", prefill_chunk=64)
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
    return params, probes


def test_served_tokens_rank_first_in_the_reference(served):
    params, probes = served
    rows = refcheck.shortfalls(params, HF, probes)
    short = [p["shortfall"] for r in rows for p in r]
    assert len(short) == 8
    assert all(ord("a") <= t <= ord("z") for p in probes for t in p["served"])
    assert max(short) < 1e-4, short
    assert max(short) <= EPSILON


def test_the_check_fails_on_other_weights(served):
    params, probes = served
    head = params["lm_head"]
    other = {**params, "lm_head": dataclasses.replace(
        head, q=jax.numpy.flip(head.q, axis=0))}
    short = [p["shortfall"] for r in refcheck.shortfalls(other, HF, probes)
             for p in r]
    assert max(short) > EPSILON
