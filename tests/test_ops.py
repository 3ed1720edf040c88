"""Pallas flash-attention kernels vs the XLA reference implementation.

Run in interpreter mode on CPU (real Mosaic compilation happens on TPU);
numerical agreement with models.llama._grouped_attn is the contract.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import kvcache as kvc
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import llama as mdl
from localai_tpu.models.llama import LlamaConfig
from localai_tpu.models.registry import resolve_model
from localai_tpu.ops import attention as ops_attn


def _cfg(Hq=8, Hkv=4, hd=16, window=None):
    return LlamaConfig(num_heads=Hq, num_kv_heads=Hkv, head_dim=hd,
                       hidden_size=Hq * hd, sliding_window=window)


@pytest.mark.parametrize("window", [None, 24])
def test_decode_attention_matches_xla(window, in_stack):
    cfg = _cfg(window=window)
    S, C = 4, 64
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(S, cfg.num_heads, cfg.hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(S, cfg.num_kv_heads, C, cfg.hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(S, cfg.num_kv_heads, C, cfg.hd)), jnp.float32)
    pos = jnp.asarray([0, 5, 31, 63], jnp.int32)

    ref = mdl._grouped_attn(cfg, q[:, None], k, v,
                            kvc.decode_mask(cfg, pos, C))[:, 0]
    out = ops_attn.decode_attention(q, in_stack(k), in_stack(v),
                                    jnp.int32(1), pos, sliding_window=window,
                                    block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 10])
@pytest.mark.parametrize("length", [1, 17, 48])
def test_prefill_attention_matches_xla(window, length):
    cfg = _cfg(Hq=4, Hkv=2, window=window)
    T = 48
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(T, cfg.num_heads, cfg.hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(cfg.num_kv_heads, T, cfg.hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(cfg.num_kv_heads, T, cfg.hd)), jnp.float32)

    ref = mdl._grouped_attn(cfg, q[None], k[None], v[None],
                            kvc.prefill_mask(cfg, T, jnp.int32(length)))[0]
    out = ops_attn.prefill_attention(q, k, v, jnp.int32(length),
                                     sliding_window=window,
                                     block_q=16, block_k=16, interpret=True)
    # rows past `length` attend to nothing real; compare only the valid rows
    np.testing.assert_allclose(np.asarray(out)[:length],
                               np.asarray(ref)[:length],
                               rtol=2e-5, atol=2e-5)


def test_runner_pallas_matches_xla_end_to_end():
    """Greedy generation must be bit-identical between attention impls."""
    model = resolve_model("debug:tiny", dtype="float32")
    outs = {}
    for impl in ("xla", "pallas_interpret"):
        r = ModelRunner(model.cfg, model.params, num_slots=2, max_ctx=64,
                        prefill_buckets=[16], kv_dtype="float32",
                        attn_impl=impl)
        s = r.acquire_slot()
        toks = [r.admit(s, list(b"pallas parity"), temperature=0.0)]
        for _ in range(6):
            toks.append(int(r.step()[s]))
        outs[impl] = toks
    assert outs["xla"] == outs["pallas_interpret"]


@pytest.mark.parametrize("window", [None, 24])
def test_decode_attention_int8_kv_matches_dequant_xla(window, in_stack):
    """Fused int8-KV dequant in the flash decode kernel: scales applied to
    score/prob columns must equal attention over the dequantized cache."""
    cfg = _cfg(window=window)
    S, C = 4, 64
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.normal(size=(S, cfg.num_heads, cfg.hd)), jnp.float32)
    kq = jnp.asarray(rng.integers(-127, 128, (S, cfg.num_kv_heads, C, cfg.hd)),
                     jnp.int8)
    vq = jnp.asarray(rng.integers(-127, 128, (S, cfg.num_kv_heads, C, cfg.hd)),
                     jnp.int8)
    ks = jnp.asarray(rng.uniform(0.005, 0.02, (S, cfg.num_kv_heads, C)),
                     jnp.float32)
    vs = jnp.asarray(rng.uniform(0.005, 0.02, (S, cfg.num_kv_heads, C)),
                     jnp.float32)
    pos = jnp.asarray([0, 5, 31, 63], jnp.int32)

    k = kq.astype(jnp.float32) * ks[..., None]
    v = vq.astype(jnp.float32) * vs[..., None]
    ref = mdl._grouped_attn(cfg, q[:, None], k, v,
                            kvc.decode_mask(cfg, pos, C))[:, 0]
    out = ops_attn.decode_attention(q, in_stack(kq), in_stack(vq),
                                    jnp.int32(1), pos,
                                    in_stack(ks), in_stack(vs),
                                    sliding_window=window,
                                    block_k=16, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_runner_int8_kv_pallas_matches_xla_end_to_end():
    """int8-KV serving must run the flash decode kernel (no XLA fallback)
    and agree with the fused-XLA int8 path on greedy output."""
    model = resolve_model("debug:tiny", dtype="float32")
    outs = {}
    for impl in ("xla", "pallas_interpret"):
        r = ModelRunner(model.cfg, model.params, num_slots=2, max_ctx=64,
                        prefill_buckets=[16], kv_dtype="int8",
                        attn_impl=impl)
        if impl.startswith("pallas"):
            assert r.decode_attn_impl == "pallas"
        s = r.acquire_slot()
        toks = [r.admit(s, list(b"int8 kv parity"), temperature=0.0)]
        for _ in range(8):
            toks.append(int(r.step()[s]))
        outs[impl] = toks
    assert outs["xla"] == outs["pallas_interpret"]
