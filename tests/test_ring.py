"""Ring attention / sequence-parallel prefill vs the single-device trunk."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from localai_tpu.engine import kvcache as kvc
from localai_tpu.models import llama as mdl
from localai_tpu.models.llama import LlamaConfig
from localai_tpu.models.registry import resolve_model
from localai_tpu.parallel.mesh import MeshPlan, build_mesh
from localai_tpu.parallel.ring import ring_attention, sp_prefill_forward


@pytest.fixture(scope="module")
def seq_mesh():
    return build_mesh(MeshPlan(seq=8))


def _reference_forward(model_cfg, params, tokens, length):
    """Single-device full-attention trunk; returns (hidden, (k, v) stacks)."""
    T = tokens.shape[0]
    rope = mdl.rope_table(model_cfg, T)
    mask = kvc.prefill_mask(model_cfg, T, length)

    def write(kv_stack, layer, k, v):
        # pass the fresh chunk through (head-major for _grouped_attn) and
        # keep the token-major chunk as the layer's row of the carried stack
        ks, vs = kv_stack
        return ((ks.at[layer].set(k[0]), vs.at[layer].set(v[0])),
                k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3))

    empty = jnp.zeros((model_cfg.num_layers, T, model_cfg.num_kv_heads,
                       model_cfg.hd), jnp.dtype(model_cfg.dtype))
    hidden, kvs = mdl.forward(
        model_cfg, params, tokens[None],
        jnp.arange(T, dtype=jnp.int32)[None], write, (empty, empty), mask,
        rope,
    )
    return hidden, kvs


@pytest.mark.parametrize("length", [64, 37])
def test_sp_prefill_matches_single_device(seq_mesh, length):
    model = resolve_model("debug:tiny", dtype="float32")
    T = 64
    rng = np.random.default_rng(0)
    tokens = jnp.asarray(rng.integers(0, model.cfg.vocab_size, T), jnp.int32)

    hidden, (k, v) = sp_prefill_forward(
        model.cfg, model.params, tokens, jnp.int32(length), seq_mesh,
        mdl.rope_table(model.cfg, T),
    )
    ref, (ref_k, ref_v) = _reference_forward(
        model.cfg, model.params, tokens, jnp.int32(length)
    )

    assert hidden.shape == ref.shape
    np.testing.assert_allclose(
        np.asarray(hidden)[0, :length], np.asarray(ref)[0, :length],
        rtol=2e-4, atol=2e-4,
    )
    # K/V values (not just shapes) must match — they feed the slot cache.
    # Positions < length see identical inputs in both runs.
    np.testing.assert_allclose(np.asarray(k)[:, :length],
                               np.asarray(ref_k)[:, :length],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(v)[:, :length],
                               np.asarray(ref_v)[:, :length],
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("window", [None, 12])
def test_ring_attention_matches_full(seq_mesh, window):
    """The bare primitive against unsharded masked attention."""
    cfg = LlamaConfig(num_heads=4, num_kv_heads=2, head_dim=8,
                      hidden_size=32, sliding_window=window)
    T, n = 32, 8
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(T, 4, 8)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(T, 2, 8)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(T, 2, 8)), jnp.float32)
    length = jnp.int32(29)

    ref = mdl._grouped_attn(cfg, q[None], k.transpose(1, 0, 2)[None],
                            v.transpose(1, 0, 2)[None],
                            kvc.prefill_mask(cfg, T, length))[0]

    def local(q_c, k_c, v_c):
        return ring_attention(q_c, k_c, v_c, length, n_chunks=n,
                              sliding_window=window)

    out = shard_map(
        local, mesh=seq_mesh,
        in_specs=(P("seq"), P("seq"), P("seq")),
        out_specs=P("seq"),
        check_vma=False,
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out)[:29], np.asarray(ref)[:29],
                               rtol=2e-5, atol=2e-5)


def test_sp_prefill_sliding_window_model(seq_mesh):
    """A sliding-window config must produce window-masked hidden states."""
    base = resolve_model("debug:tiny", dtype="float32")
    cfg = dataclasses.replace(base.cfg, sliding_window=8)
    T, length = 64, 64
    rng = np.random.default_rng(2)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, T), jnp.int32)

    hidden, _ = sp_prefill_forward(
        cfg, base.params, tokens, jnp.int32(length), seq_mesh,
        mdl.rope_table(cfg, T),
    )
    ref, _ = _reference_forward(cfg, base.params, tokens, jnp.int32(length))
    np.testing.assert_allclose(np.asarray(hidden)[0], np.asarray(ref)[0],
                               rtol=2e-4, atol=2e-4)


def test_sp_prefill_tp_composition():
    """TP×SP (VERDICT r4 #4): weights 'model'-sharded (Megatron layout),
    activations 'seq'-sharded, ring attention per local head group — must
    match the single-device trunk."""
    from localai_tpu.parallel import sharding as shd

    mesh = build_mesh(MeshPlan(seq=4, model=2))
    model = resolve_model("debug:tiny", dtype="float32")
    sp = shd.shard_params(model.params, model.cfg, mesh)
    T, length = 64, 57
    rng = np.random.default_rng(5)
    tokens = jnp.asarray(rng.integers(0, model.cfg.vocab_size, T), jnp.int32)

    hidden, (k, v) = sp_prefill_forward(
        model.cfg, sp, tokens, jnp.int32(length), mesh,
        mdl.rope_table(model.cfg, T),
    )
    ref, (ref_k, ref_v) = _reference_forward(
        model.cfg, model.params, tokens, jnp.int32(length)
    )
    np.testing.assert_allclose(
        np.asarray(hidden)[0, :length], np.asarray(ref)[0, :length],
        rtol=2e-4, atol=2e-4,
    )
    np.testing.assert_allclose(np.asarray(k)[:, :length],
                               np.asarray(ref_k)[:, :length],
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(v)[:, :length],
                               np.asarray(ref_v)[:, :length],
                               rtol=2e-4, atol=2e-4)


def test_sp_prefill_tp_requires_divisible_heads():
    mesh = build_mesh(MeshPlan(seq=4, model=2))
    cfg = LlamaConfig(num_heads=3, num_kv_heads=3, head_dim=8,
                      hidden_size=24, vocab_size=64, num_layers=1,
                      intermediate_size=32, dtype="float32")
    params = mdl.init_params(jax.random.key(0), cfg)
    with pytest.raises(ValueError, match="divisible"):
        sp_prefill_forward(cfg, params, jnp.zeros(16, jnp.int32),
                           jnp.int32(16), mesh, mdl.rope_table(cfg, 16))
