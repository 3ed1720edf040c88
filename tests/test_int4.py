"""int4 KV pool: pack/unpack, fused-dequant parity, engine wiring.

Tolerance note (pinned by the parity tests): symmetric per-(position,
head) int4 rounds to 15 levels, so the worst-case dequant error per
element is scale/2 = amax/14 — at unit-normal K/V that is ~0.22 absolute
on raw cache rows, and post-softmax attention outputs stay within ~0.2
absolute / a few percent relative of the f32 reference. The Pallas
interpret kernel must match the lax ref to ~1e-5 (same int4 math, only
the schedule differs); int4-vs-f32 carries the quantization error.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from localai_tpu import ops
from localai_tpu.engine import kvcache as kvc
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models.quant import (
    quantize_lastdim4,
    unpack_int4_lastdim,
)
from localai_tpu.models.registry import resolve_model


def test_int4_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(3, 5, 16)), jnp.float32)
    packed, scale = quantize_lastdim4(x)
    assert packed.shape == (3, 5, 8) and packed.dtype == jnp.int8
    assert scale.shape == (3, 5)
    unpacked = unpack_int4_lastdim(packed)
    # the packed bytes decode to EXACTLY the quantized int values
    q = jnp.clip(jnp.round(x / scale[..., None]), -7, 7).astype(jnp.int8)
    np.testing.assert_array_equal(np.asarray(unpacked), np.asarray(q))
    # and the dequant error is bounded by half a quantization step
    deq = unpacked.astype(jnp.float32) * scale[..., None]
    err = np.abs(np.asarray(deq - x))
    assert err.max() <= float(np.asarray(scale).max()) / 2 + 1e-6


def test_int4_pack_odd_lastdim_rejected():
    # odd trailing dims cannot split into nibble halves
    with pytest.raises(Exception):
        quantize_lastdim4(jnp.ones((2, 15)))


def _paged_problem(rng, ctx):
    S, Hq, Hkv, hd, bt = 3, 4, 2, 16, 8
    mb = -(-ctx // bt)
    n = S * mb + 1
    q = jnp.asarray(rng.normal(size=(S, Hq, hd)), jnp.float32)
    kf = jnp.asarray(rng.normal(size=(n, Hkv, bt, hd)), jnp.float32)
    vf = jnp.asarray(rng.normal(size=(n, Hkv, bt, hd)), jnp.float32)
    tables = jnp.asarray(
        np.arange(1, n).reshape(S, mb), jnp.int32)
    positions = jnp.asarray(
        rng.integers(1, ctx - 1, size=(S,)), jnp.int32)
    return q, kf, vf, tables, positions


@pytest.mark.parametrize("ctx", [24, 112])  # two lengths (multi-block)
def test_paged_int4_vs_f32_parity_ref_and_interpret(ctx, in_stack):
    rng = np.random.default_rng(1)
    q, kf, vf, tables, positions = _paged_problem(rng, ctx)
    ref_f32 = ops.paged_decode_attention_ref(
        q, kf, vf, tables, positions)
    kq, ks = quantize_lastdim4(kf)
    vq, vs = quantize_lastdim4(vf)
    # lax ref with the int4 pool: carries only the quantization error
    ref_i4 = ops.paged_decode_attention_ref(
        q, kq, vq, tables, positions, ks, vs)
    assert float(jnp.max(jnp.abs(ref_i4 - ref_f32))) < 0.25
    np.testing.assert_allclose(
        np.asarray(ref_i4), np.asarray(ref_f32), rtol=0.2, atol=0.2)
    # Pallas interpret vs the lax ref: identical int4 math, ~fp32 exact
    pal_i4 = ops.paged_decode_attention(
        q, in_stack(kq), in_stack(vq), jnp.int32(1), tables, positions,
        in_stack(ks), in_stack(vs), interpret=True)
    np.testing.assert_allclose(
        np.asarray(pal_i4), np.asarray(ref_i4), rtol=1e-5, atol=1e-5)


def test_paged_int4_buffer_depths_identical(in_stack):
    rng = np.random.default_rng(2)
    q, kf, vf, tables, positions = _paged_problem(rng, 64)
    kq, ks = quantize_lastdim4(kf)
    vq, vs = quantize_lastdim4(vf)
    stacked = (in_stack(kq), in_stack(vq), jnp.int32(1), tables, positions,
               in_stack(ks), in_stack(vs))
    d2 = ops.paged_decode_attention(q, *stacked, interpret=True,
                                    num_buffers=2)
    d3 = ops.paged_decode_attention(q, *stacked, interpret=True,
                                    num_buffers=3)
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(d3))


def test_init_paged_cache_int4_layout():
    model = resolve_model("debug:tiny", dtype="float32")
    kv = kvc.init_paged_cache(model.cfg, 8, 16, "int4")
    hd = model.cfg.hd
    assert kv.k.dtype == jnp.int8
    assert kv.k.shape[-1] == hd // 2      # nibble-packed along head_dim
    assert kv.k_scale is not None
    assert kv.k_scale.shape == kv.k.shape[:-1]
    assert kv.quantized


def _greedy_tokens(kv_dtype, attn_impl="auto", steps=12):
    model = resolve_model("debug:tiny", dtype="float32")
    runner = ModelRunner(
        model.cfg, model.params, num_slots=2, max_ctx=128,
        prefill_buckets=[64], kv_dtype=kv_dtype, paged=True,
        kv_block_tokens=16, attn_impl=attn_impl)
    slot = runner.acquire_slot()
    toks = [runner.admit(slot, list(range(1, 40)), temperature=0.0)]
    for _ in range(steps // 4):
        toks.extend(np.asarray(runner.step_n(4))[:, slot].tolist())
    return toks


def test_int4_engine_greedy_parity():
    """End-to-end: int4 paged decode (lax ref AND Pallas interpret) emits
    the same greedy stream; on the well-conditioned debug model it also
    matches the f32 stream (KV quantization noise is far below the
    greedy argmax margins there — real models document drift instead)."""
    f32 = _greedy_tokens("float32")
    i4 = _greedy_tokens("int4")
    i4_pallas = _greedy_tokens("int4", attn_impl="pallas_interpret")
    assert i4 == i4_pallas
    assert i4 == f32


def test_int4_verify_write_spec_lane():
    """Speculative verify over an int4 pool: paged_verify_write scatters
    packed rows + scales; greedy verify parity vs f32 holds on the debug
    model."""
    from localai_tpu.spec import NGramDrafter, SpecEngine

    def run(kv_dtype):
        model = resolve_model("debug:tiny", dtype="float32")
        runner = ModelRunner(
            model.cfg, model.params, num_slots=2, max_ctx=256,
            prefill_buckets=[64], kv_dtype=kv_dtype, paged=True,
            kv_block_tokens=16)
        eng = SpecEngine(runner, NGramDrafter(2, gamma=4))
        slot = eng.acquire_slot()
        out = [eng.admit(slot, list(b"abc abc abc abc abc"),
                         temperature=0.0)]
        for _ in range(30):
            if eng.total_emitted >= 24:
                break
            rows = eng.step_spec_async()
            if rows is None:
                tok = int(runner.step()[slot])
                eng.drafter.observe(slot, [tok])
                out.append(tok)
                continue
            arr = np.asarray(rows)
            eng.observe_window(arr)
            out.extend(int(t) for t in arr[:, slot] if t >= 0)
        assert not runner.allocator.check_invariants()
        return out[:24]

    assert run("int4") == run("float32")


def test_int4_snapshot_export_roundtrip():
    """export_prefix/load_prefix round-trips the packed int4 rows: a
    fresh runner loads the snapshot and resumes with identical greedy
    output."""
    model = resolve_model("debug:tiny", dtype="float32")
    prompt = list(range(1, 50))

    def mk():
        return ModelRunner(
            model.cfg, model.params, num_slots=2, max_ctx=128,
            prefill_buckets=[64], kv_dtype="int4", paged=True,
            kv_block_tokens=16)

    a = mk()
    slot = a.acquire_slot()
    first = a.admit(slot, prompt, temperature=0.0)
    snap = a.export_prefix(slot, len(prompt))
    assert snap["k"].shape[-1] == model.cfg.hd // 2  # stays packed
    cont_a = [first] + [int(a.step()[slot]) for _ in range(6)]

    b = mk()
    slot_b = b.acquire_slot()
    assert b.load_prefix(slot_b, snap, len(prompt))
    first_b = b.admit(slot_b, prompt + [first],
                      resident=prompt, temperature=0.0)
    assert b.last_prefill_path == "paged_resume"
    cont_b = [first_b] + [int(b.step()[slot_b]) for _ in range(5)]
    # stream a decoded [first, x1, x2...]; stream b prefilled prompt+first
    # then decodes [x1, x2...]
    assert cont_a[1:] == cont_b[:6]


def test_int4_requires_paged():
    model = resolve_model("debug:tiny", dtype="float32")
    with pytest.raises(ValueError, match="int4"):
        ModelRunner(model.cfg, model.params, num_slots=2, max_ctx=128,
                    prefill_buckets=[64], kv_dtype="int4", paged=False)
