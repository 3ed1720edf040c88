"""The peak table and the operation/byte functions against hand arithmetic:
the dense family's (ISSUE 23: 7.25 B and 23.6 B parameters, 128 KiB and 160
KiB of KV a token) through harness/work.py's compositions, as before the
arithmetic moved into the family modules, and the sparse-expert family's
(ISSUE 28: OLMoE-1B-7B 6.92 B, Mixtral-8x7B 46.70 B)."""

import json

import pytest

from conftest import BENCH
from harness import peaks, spec, work

BF16 = {"kv_dtype": "bfloat16"}
dense = spec.load_family(BENCH / "reference" / "llama_family.py")
moe = spec.load_family(BENCH / "reference" / "moe_family.py")


def published(name):
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    assert spec.family_file(cfg, name).stem == "llama_family"   # the default
    return {k: v for k, v in cfg.items() if k not in spec.CONFIG_KEYS}, cfg


def test_mistral_7b_parameters_and_kv():
    hf, cfg = published("mistral-7b-v0.3-int8")
    # per layer: q 4096x4096, k and v 4096x1024, o 4096x4096, 3 x 4096x14336
    assert dense.layer_params(hf) == (16777216 * 2 + 4194304 * 2
                                      + 3 * 58720256)
    assert dense.param_count(hf) == pytest.approx(7.248e9, rel=1e-3)
    assert work.kv_bytes_per_token(dense, hf, BF16) == 128 * 1024
    assert dense.head_dim(hf) == 128
    # int8: one byte a weight; a decode step cannot beat 7.1 GB / 819 GB/s,
    # however many tokens it makes and however the steps are dispatched
    step = work.decode_bytes(dense, hf, cfg["engine"], [(1, 16)], attended=0)
    assert work.decode_bytes(dense, hf, cfg["engine"], [(2, 3), (1, 16)],
                             attended=0) == 3 * step
    assert step == pytest.approx(7.114e9, rel=1e-3)
    t, bound = work.roofline_seconds({"bytes": step},
                                     peaks.peaks("TPU v5 lite"))
    assert bound == "bytes" and t == pytest.approx(8.69e-3, rel=1e-2)
    # prefill: 2 flops a layer weight a token = 13.96 GFLOP
    assert work.prefill_flops(dense, hf, 1, 0) == pytest.approx(13.96e9,
                                                                rel=1e-3)


def test_mistral_small_24b_parameters_and_kv():
    """The four-chip configuration's shapes (Mistral-Small-24B-Instruct-2501;
    its file lands with its cell, PERF.md section 7): the functions hold for
    an explicit head_dim and a 131 k vocabulary too."""
    hf = {"hidden_size": 5120, "intermediate_size": 32768,
          "num_hidden_layers": 40, "num_attention_heads": 32,
          "num_key_value_heads": 8, "head_dim": 128, "vocab_size": 131072,
          "tie_word_embeddings": False}
    assert dense.head_dim(hf) == 128            # explicit, not 5120 / 32
    assert dense.param_count(hf) == pytest.approx(23.57e9, rel=1e-3)
    assert work.kv_bytes_per_token(dense, hf, BF16) == 160 * 1024
    # over four chips: a quarter of the weights each, 40 KiB of KV a token
    per_chip = work.decode_bytes(dense, hf, {"quantization": "int8"},
                                 [(1, 32)], 0) / 4
    assert per_chip == pytest.approx(5.73e9, rel=1e-2)


def test_attention_work():
    hf, cfg = published("mistral-7b-v0.3-int8")
    assert work.causal_pairs(4) == 10
    # QK^T and PV: 4 flops x 32 heads x 128 x 32 layers a pair
    assert dense.attn_flops(hf, 1) == 4 * 32 * 128 * 32
    w = work.paged_decode_attn(dense, hf, cfg["engine"], attended=1000,
                               tokens=1)
    assert w["bytes"] == 1000 * 131072 + 2 * 2 * 32 * 32 * 128
    assert work.roofline_seconds(w, peaks.peaks("TPU v5e"))[1] == "bytes"


# https://huggingface.co/allenai/OLMoE-1B-7B-0125-Instruct config.json
OLMOE = {"model_type": "olmoe", "hidden_size": 2048,
         "intermediate_size": 1024, "num_hidden_layers": 16,
         "num_attention_heads": 16, "num_key_value_heads": 16,
         "num_experts": 64, "num_experts_per_tok": 8,
         "norm_topk_prob": False, "vocab_size": 50304,
         "tie_word_embeddings": False}
# https://huggingface.co/mistralai/Mixtral-8x7B-v0.1 config.json
MIXTRAL = {"model_type": "mixtral", "hidden_size": 4096,
           "intermediate_size": 14336, "num_hidden_layers": 32,
           "num_attention_heads": 32, "num_key_value_heads": 8,
           "num_local_experts": 8, "num_experts_per_tok": 2,
           "vocab_size": 32000, "tie_word_embeddings": False}


def test_olmoe_holds_all_experts_multiplies_eight_reads_the_expected():
    attn, router, expert = 4 * 2048 * 2048, 2048 * 64, 3 * 2048 * 1024
    assert (attn, router, expert) == (16777216, 131072, 6291456)
    # what HBM holds: every expert of every layer, 419.6 M a layer
    assert moe.layer_params(OLMOE) == attn + router + 64 * expert == 419561472
    gains = 16 * (2 * 2048 + 2 * 2048) + 2048   # two norms, q and k norm
    assert moe.param_count(OLMOE) == (16 * 419561472 + 2 * 2048 * 50304
                                      + gains) == 6919161856        # 6.92 B
    # what a token multiplies: 8 experts of 64, 67.2 M a layer, 1.18 B with
    # the head
    assert moe.token_params(OLMOE) == 16 * (attn + router + 8 * expert)
    assert moe.token_params(OLMOE) // 16 == 67239936
    assert moe.token_params(OLMOE) + 2048 * 50304 == 1178861568
    assert work.prefill_flops(moe, OLMOE, 1, 0) == 2 * 16 * 67239936
    # MHA: 16 kv heads of 128 in 16 layers, 128 KiB a token in bf16
    assert work.kv_bytes_per_token(moe, OLMOE, BF16) == 128 * 1024
    # what a decode step reads: the experts its tokens are EXPECTED to touch,
    # 64 (1 - (7/8)^tokens): 8 for one token, 56.4 at 16, and all 64 only
    # in the limit of many tokens (a prefill chunk's)
    assert moe.experts_touched(OLMOE, 1) == pytest.approx(8.0)
    assert moe.experts_touched(OLMOE, 16) == pytest.approx(56.44, abs=0.01)
    for tokens in (1, 16, 64):
        assert moe.experts_touched(OLMOE, tokens) < 64
        assert moe.step_params(OLMOE, tokens) < (
            16 * 419561472 + 2048 * 50304)
    assert moe.experts_touched(OLMOE, 4096) <= 64
    assert moe.step_params(OLMOE, 16) == pytest.approx(
        16 * (attn + router + 56.4437 * expert) + 2048 * 50304, rel=1e-6)
    # int8, one step of 16 tokens: 6.06 GB of 6.82 GB of layers and head
    step = work.decode_bytes(moe, OLMOE, {"quantization": "int8"},
                             [(1, 16)], 0)
    assert step == pytest.approx(6.055e9, rel=1e-3)
    # two dispatches: each step reads what ITS tokens touch
    assert work.decode_bytes(moe, OLMOE, {"quantization": "int8"},
                             [(2, 32), (1, 1)], 0) == pytest.approx(
        2 * step + moe.step_params(OLMOE, 1))


def test_mixtral_8x7b_is_the_expert_count_under_its_other_name():
    """46.70 B (the block ``_moe_mlp`` was written for): 8 experts under
    ``num_local_experts``, top-2, no q/k norm, GQA."""
    attn, expert = 2 * 4096 * 4096 + 2 * 4096 * 1024, 3 * 4096 * 14336
    assert moe.layer_params(MIXTRAL) == attn + 4096 * 8 + 8 * expert
    assert moe.param_count(MIXTRAL) == (
        32 * (moe.layer_params(MIXTRAL) + 2 * 4096) + 2 * 4096 * 32000
        + 4096) == 46702792704
    # a token multiplies 2 of 8: 12.7 B a token with the head
    assert moe.token_params(MIXTRAL) + 4096 * 32000 == pytest.approx(
        12.75e9, rel=1e-3)
    assert moe.experts_touched(MIXTRAL, 16) == pytest.approx(7.92, abs=0.01)
    assert work.kv_bytes_per_token(moe, MIXTRAL, BF16) == 128 * 1024


def test_an_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["hbm_bytes_s"] == 819e9
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(LookupError, match="no entry"):
        peaks.peaks("cpu")
    with pytest.raises(LookupError):
        peaks.peaks("TPU v9")
