"""Pallas-path guarantees: for the hardware shapes that matter, the
engine's attention-impl decision must land on the flash kernels, and a
shape the kernels cannot take must be refused at load — never traded for
XLA behind the caller's back. The decision is a pure function
(ops.select_attn_impl / ops.select_paged_attn_impl) evaluated as-if on TPU
(backend='tpu'), so these assertions hold on CPU CI. That every combination
answered "pallas" here also compiles for v5e is tests/test_tpu_compile.py's
job."""

import pytest

from localai_tpu.ops import (resolve_attn_impl, select_attn_impl,
                             select_paged_attn_impl)

# Llama-3-8B: 32 q heads / 8 kv heads / head_dim 128 — the north-star
# serving config (chip_smoke.py, debug:llama3-8b)
L8B = dict(num_heads=32, num_kv_heads=8, head_dim=128)


@pytest.mark.parametrize("tp", [1, 4, 8])
@pytest.mark.parametrize("ctx", [1024, 8192])
def test_llama8b_lands_on_pallas_on_tpu(tp, ctx):
    assert select_attn_impl(
        "auto", **L8B, max_ctx=ctx, tp=tp, backend="tpu") == ("pallas", False)


@pytest.mark.parametrize("tp", [1, 4, 8])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("bt", [32, 64, 128, 256])
def test_llama8b_paged_lands_on_pallas_on_tpu(tp, kv_dtype, bt):
    assert select_paged_attn_impl(
        "auto", **L8B, block_tokens=bt, tp=tp, kv_dtype=kv_dtype,
        backend="tpu") == ("pallas", False)


def test_llama1b_hd64_is_refused_with_the_override_named():
    """Heads of 64 and the compiled kernels. Mosaic copies 128-lane rows, so
    a K/V head of 64 ALONE has none: debug:1b through models/llama.py, whose
    layers hand the pool their heads as they are, is still refused on a TPU
    under ``auto``, at load, with the override named (it used to serve XLA
    attention with a log.info) and now with what would serve it. A family
    that PACKS two such heads into one pool row (ops.attention
    ``heads_per_row``; model_type lfm2_moe) tells the selectors the POOL's
    rows, 128 wide, and lands on the compiled kernel: the published
    LFM2-8B-A1B (32 query heads over 8 K/V heads of 64) is 4 rows of 128. What
    still cannot be tiled arrives as it is and is refused: an odd count of
    64-wide K/V heads, a width that packs to no 128."""
    from localai_tpu.models.llama import LlamaConfig
    from localai_tpu.ops.attention import heads_per_row

    hd64 = dict(num_heads=32, num_kv_heads=8, head_dim=64)
    with pytest.raises(ValueError, match="128-aligned.*2 K/V heads of 64 "
                                         "share a 128-lane row.*attn_impl: "
                                         "xla"):
        select_attn_impl("auto", **hd64, max_ctx=1024, backend="tpu")
    with pytest.raises(ValueError, match="tileable.*heads_per_row.*"
                                         "attn_impl: xla"):
        select_paged_attn_impl("auto", **hd64, block_tokens=64,
                               backend="tpu")
    # the explicit choice is honoured
    assert select_attn_impl("xla", **hd64, max_ctx=1024,
                            backend="tpu") == ("xla", False)

    def lfm2(**keys):
        types = ["conv", "conv", "full_attention", "conv"]
        cfg = LlamaConfig.from_hf({
            "model_type": "lfm2_moe", "vocab_size": 65536,
            "hidden_size": 2048, "intermediate_size": 7168,
            "moe_intermediate_size": 1792, "num_hidden_layers": 4,
            "layer_types": types, "num_dense_layers": 2,
            "num_attention_heads": 32, "num_key_value_heads": 8,
            "num_experts": 32, "num_experts_per_tok": 4, **keys})
        return dict(num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                    head_dim=cfg.hd)

    assert heads_per_row(8, 64) == 2
    assert lfm2() == dict(num_heads=32, num_kv_heads=4, head_dim=128)
    for tp in (1, 4):
        assert select_paged_attn_impl("auto", **lfm2(), block_tokens=64,
                                      tp=tp, backend="tpu") == ("pallas",
                                                                False)
    assert select_attn_impl("auto", **lfm2(), max_ctx=4096,
                            backend="tpu") == ("pallas", False)
    # 32 heads of 32 over 8 K/V heads: four to a row
    assert lfm2(hidden_size=1024) == dict(num_heads=32, num_kv_heads=2,
                                          head_dim=128)
    # an odd count of 64-wide K/V heads, and heads of 96: as they are
    for keys, alone in (({"num_key_value_heads": 1}, (1, 64)),
                        ({"head_dim": 96}, (8, 96))):
        shape = lfm2(**keys)
        assert (shape["num_kv_heads"], shape["head_dim"]) == alone
        with pytest.raises(ValueError, match="tileable.*attn_impl: xla"):
            select_paged_attn_impl("auto", **shape, block_tokens=64,
                                   backend="tpu")
        assert select_paged_attn_impl("xla", **shape, block_tokens=64,
                                      backend="tpu") == ("xla", False)


def test_unaligned_ctx_is_refused():
    with pytest.raises(ValueError, match="128-aligned"):
        select_attn_impl("auto", **L8B, max_ctx=1000, backend="tpu")


def test_unaligned_block_tokens_is_refused():
    with pytest.raises(ValueError, match="block_tokens % 32"):
        select_paged_attn_impl("auto", **L8B, block_tokens=48, backend="tpu")


def test_indivisible_heads_are_refused_under_tp():
    with pytest.raises(ValueError, match="divisible"):
        select_attn_impl("auto", **L8B, max_ctx=1024, tp=3, backend="tpu")
    with pytest.raises(ValueError, match="divisible"):
        select_paged_attn_impl("pallas_interpret", **L8B, block_tokens=64,
                               tp=3, backend="cpu")


def test_int4_pool_gate():
    """The nibble-packed pool needs hd%256==0 for the compiled kernel
    (packed lane dim = hd/2); at hd 128 Mosaic refuses the 64-lane DMA and
    HBM tiling pads the rows back to 128 lanes, so the selector refuses
    it rather than gather behind the caller's back. Interpret mode and an
    explicit xla are unaffected."""
    with pytest.raises(ValueError, match="int4.*kv_dtype: int8"):
        select_paged_attn_impl("auto", **L8B, block_tokens=64,
                               kv_dtype="int4", backend="tpu")
    assert select_paged_attn_impl(
        "auto", num_heads=32, num_kv_heads=8, head_dim=256,
        block_tokens=64, kv_dtype="int4", backend="tpu") == ("pallas", False)
    assert select_paged_attn_impl(
        "pallas_interpret", **L8B, block_tokens=64, kv_dtype="int4",
        backend="tpu") == ("pallas", True)
    assert select_paged_attn_impl(
        "xla", **L8B, block_tokens=64, kv_dtype="int4",
        backend="tpu") == ("xla", False)


def test_cpu_auto_is_xla_and_interpret_is_only_ever_explicit():
    assert select_attn_impl(
        "auto", **L8B, max_ctx=1024, backend="cpu") == ("xla", False)
    assert select_attn_impl(
        "pallas_interpret", **L8B, max_ctx=1024,
        backend="cpu") == ("pallas", True)
    # "pallas" off-TPU used to mean the interpreter without saying so
    for select in (
        lambda: resolve_attn_impl("pallas", backend="cpu"),
        lambda: select_attn_impl("pallas", **L8B, max_ctx=1024,
                                 backend="cpu"),
        lambda: select_paged_attn_impl("pallas", **L8B, block_tokens=64,
                                       backend="cpu"),
    ):
        with pytest.raises(ValueError, match="pallas_interpret"):
            select()


def test_runner_exposes_decision():
    """The runner's attn_impl reflects select_attn_impl verbatim."""
    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models.registry import resolve_model

    tiny = resolve_model("debug:tiny", dtype="float32")
    r = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=128,
                    prefill_buckets=[64], attn_impl="pallas_interpret")
    assert r.attn_impl == "pallas" and r._attn_interpret
    r2 = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=128,
                     prefill_buckets=[64], attn_impl="xla")
    assert r2.attn_impl == "xla"
    with pytest.raises(ValueError, match="pallas_interpret"):
        ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=128,
                    prefill_buckets=[64], attn_impl="pallas")
