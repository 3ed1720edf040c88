"""The peak table and the operation/byte functions against hand arithmetic
(ISSUE 23): 7.25 B and 23.6 B parameters, 128 KiB and 160 KiB of KV a token."""

import json

import pytest

from conftest import BENCH
from harness import peaks, work
from harness.spec import CONFIG_KEYS


def published(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    return {k: v for k, v in cfg.items() if k not in CONFIG_KEYS}, cfg


def test_mistral_7b_parameters_and_kv():
    hf, cfg = published("mistral-7b-v0.3-int8")
    # per layer: q 4096x4096, k and v 4096x1024, o 4096x4096, 3 x 4096x14336
    assert work.layer_params(hf) == 16777216 * 2 + 4194304 * 2 + 3 * 58720256
    assert work.param_count(hf) == pytest.approx(7.248e9, rel=1e-3)
    assert work.kv_bytes_per_token(hf) == 128 * 1024
    assert work.head_dim(hf) == 128
    # int8: one byte a weight; a decode step cannot beat 7.1 GB / 819 GB/s
    step = work.decode_bytes(hf, cfg["engine"], steps=1, attended=0)
    assert step == pytest.approx(7.114e9, rel=1e-3)
    t, bound = work.roofline_seconds({"bytes": step},
                                     peaks.peaks("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(8.69e-3, rel=1e-2)
    # prefill: 2 flops a layer weight a token = 13.96 GFLOP
    assert work.prefill_flops(hf, 1, 0) == pytest.approx(13.96e9, rel=1e-3)


def test_mistral_small_24b_parameters_and_kv():
    """The four-chip configuration's shapes (Mistral-Small-24B-Instruct-2501;
    its file lands with its cell, PERF.md section 7): the functions hold for
    an explicit head_dim and a 131 k vocabulary too."""
    hf = {"hidden_size": 5120, "intermediate_size": 32768,
          "num_hidden_layers": 40, "num_attention_heads": 32,
          "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 131072,
          "tie_word_embeddings": False}
    assert work.head_dim(hf) == 128             # explicit, not 5120 / 32
    assert work.param_count(hf) == pytest.approx(23.57e9, rel=1e-3)
    assert work.kv_bytes_per_token(hf) == 160 * 1024
    # over four chips: a quarter of the weights each, 40 KiB of KV a token
    per_chip = work.decode_bytes(hf, {"quantization": "int8"}, 1, 0) / 4
    assert per_chip == pytest.approx(5.73e9, rel=1e-2)


def test_attention_work():
    hf, cfg = published("mistral-7b-v0.3-int8")
    assert work.causal_pairs(4) == 10
    # QK^T and PV: 4 flops x 32 heads x 128 x 32 layers a pair
    assert work.attn_flops(hf, 1) == 4 * 32 * 128 * 32
    w = work.paged_decode_attn(hf, cfg["engine"], attended=1000, tokens=1)
    assert w["bytes"] == 1000 * 131072 + 2 * 2 * 32 * 32 * 128
    assert work.roofline_seconds(w, peaks.peaks("TPU v5e"))[1] == "bytes"


def test_an_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(LookupError, match="no entry"):
        peaks.peaks("cpu")
    with pytest.raises(LookupError):
        peaks.peaks("TPU v9")
