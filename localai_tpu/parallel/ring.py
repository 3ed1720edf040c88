"""Ring attention: sequence/context parallelism for long-context prefill.

The reference has NO sequence parallelism — long context is a single-device
concern handled by RoPE scaling and self-extend inside llama.cpp
(SURVEY.md §5.7, /root/reference/backend/cpp/llama/grpc-server.cpp:1884-1886).
On TPU, context length scales across the 'seq' mesh axis instead: the
sequence is chunked over devices, each device computes blockwise attention
between its query chunk and a rotating KV chunk, and the KV chunks travel
the ICI ring via ``lax.ppermute`` (Ring Attention, arXiv:2310.01889-style;
the blockwise online-softmax merge is the same math as the Pallas flash
kernels in ops.attention).

Communication pattern per layer: n_seq - 1 ppermute hops of one KV chunk
(2 · Tc · Hkv · hd elements) fully overlapped with the chunk attention
matmuls by XLA's latency-hiding scheduler; no all-to-all, no gather of the
full sequence on any device.

``sp_prefill_forward`` runs the whole llama trunk under shard_map with
activations sharded on 'seq', reusing models.llama._layer so the math stays
in one place. Params are replicated across 'seq' but may be 'model'-sharded
(TP×SP composition — see sp_prefill_forward's docstring); the returned
per-layer K/V is 'seq'-sharded (and head-sharded under TP), ready for
slot-cache insertion.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from localai_tpu.models import llama as mdl
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import LlamaConfig

_NEG_INF = -1e30


def ring_attention(
    q: jax.Array,          # [Tc, Hq, hd] — this device's query chunk
    k: jax.Array,          # [Tc, Hkv, hd] — this device's KV chunk
    v: jax.Array,          # [Tc, Hkv, hd]
    length: jax.Array,     # scalar i32 — real (unpadded) global length
    *,
    n_chunks: int,         # static: size of the 'seq' axis
    axis_name: str = "seq",
    sliding_window: int | None = None,
) -> jax.Array:
    """Causal GQA ring attention inside shard_map. Returns [Tc, Hq, hd].

    The q-chunk's global offset is derived from ``lax.axis_index`` — chunk
    layout and mask can never disagree.
    """
    Tc, Hq, hd = q.shape
    Hkv = k.shape[1]
    g = Hq // Hkv
    i = lax.axis_index(axis_name)

    qg = q.reshape(Tc, Hkv, g, hd).astype(jnp.float32) * hd ** -0.5
    qpos = i * Tc + jnp.arange(Tc, dtype=jnp.int32)
    perm = [(p, (p + 1) % n_chunks) for p in range(n_chunks)]

    def update(s, k_c, v_c, m, l, acc):
        j = lax.rem(i - s + n_chunks, n_chunks)  # owner of the chunk in hand
        kpos = j * Tc + jnp.arange(Tc, dtype=jnp.int32)
        scores = jnp.einsum(
            "tkgh,lkh->kgtl", qg, k_c.astype(jnp.float32)
        )
        keep = (kpos[None, :] <= qpos[:, None]) & (kpos < length)[None, :]
        if sliding_window is not None:
            keep &= kpos[None, :] > qpos[:, None] - sliding_window
        scores = jnp.where(keep[None, None], scores, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "kgtl,lkh->kgth", p, v_c.astype(jnp.float32)
        )
        return m_new, l_new, acc_new

    m0 = jnp.full((Hkv, g, Tc, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((Hkv, g, Tc, 1), jnp.float32)
    acc0 = jnp.zeros((Hkv, g, Tc, hd), jnp.float32)
    # local chunk first, then exactly n_chunks-1 ring hops: each body
    # iteration rotates the KV chunk one device along ICI, then folds it in
    carry = (k, v) + update(0, k, v, m0, l0, acc0)

    def body(s, carry):
        k_c, v_c, m, l, acc = carry
        k_c = lax.ppermute(k_c, axis_name, perm)
        v_c = lax.ppermute(v_c, axis_name, perm)
        return (k_c, v_c) + update(s, k_c, v_c, m, l, acc)

    _, _, _, l, acc = lax.fori_loop(1, n_chunks, body, carry)
    out = acc / jnp.maximum(l, 1e-30)            # [Hkv, g, Tc, hd]
    out = out.transpose(2, 0, 1, 3).reshape(Tc, Hq, hd)
    return out.astype(q.dtype)


def _tp_param_specs(cfg: LlamaConfig, mesh: Mesh, params: Any) -> Any:
    """Per-leaf PartitionSpecs for the trunk params under TP ('model' axis)
    — the shared helper in parallel.sharding (also used by the
    parallel.overlap decode path)."""
    from localai_tpu.parallel import sharding as shd

    return shd.tp_param_specs(cfg, mesh, params)


def sp_prefill_forward(
    cfg: LlamaConfig,
    params: Any,
    tokens: jax.Array,     # [T] i32, T divisible by mesh 'seq' size
    length: jax.Array,     # scalar i32
    mesh: Mesh,
    rope: tuple[jax.Array, jax.Array],
) -> tuple[jax.Array, tuple[jax.Array, jax.Array]]:
    """Sequence/context-parallel prefill of one long sequence, composed
    with tensor parallelism when the mesh's 'model' axis is >1.

    Composition (SURVEY §5.7 "sequence-sharded prefill over ICI"):
      * activations shard over 'seq' (each device owns a token chunk);
      * weights shard over 'model' exactly as in decode (Megatron layout,
        parallel.sharding.param_specs) — each device computes its local
        head group / ffn slice and the two row-parallel products psum over
        'model' (models.llama._layer's ``reduce`` hook);
      * ring attention rotates KV chunks over the 'seq' ICI ring per local
        head group — the two axes compose orthogonally (KV hops carry
        Hkv/tp heads, so TP also shrinks ring traffic per device);
      * a vocab-sharded embedding gathers locally and psums over 'model'.

    Returns (hidden [1, T, D], (k, v) each [L, T, Hkv, hd]) with T sharded
    on 'seq' and Hkv sharded on 'model'. NOTE: the slot cache
    (engine.kvcache) is head-major [L, S, Hkv, C, hd] — transpose the
    returned stacks to [L, Hkv, T, hd] before inserting into a slot.
    """
    n = mesh.shape["seq"]
    tp = mesh.shape.get("model", 1)
    T = tokens.shape[0]
    if T % n:
        raise ValueError(f"sequence length {T} not divisible by seq={n}")
    if tp > 1 and (cfg.num_heads % tp or cfg.num_kv_heads % tp
                   or cfg.intermediate_size % tp):
        # intermediate_size matters too: _sanitize would silently REPLICATE
        # an indivisible ffn weight while the manual psum still assumes
        # partial sums — multiplying the MLP branch by tp
        raise ValueError(
            f"heads ({cfg.num_heads} q / {cfg.num_kv_heads} kv) or "
            f"intermediate_size ({cfg.intermediate_size}) not divisible "
            f"by tensor_parallel {tp}"
        )
    if cfg.num_experts and mesh.shape.get("expert", 1) > 1:
        raise ValueError(
            "expert-parallel MoE prefill runs on the GSPMD path, not the "
            "manual ring shard_map (runner gates SP off for this mesh)"
        )
    Tc = T // n
    dtype = jnp.dtype(cfg.dtype)
    reduce = (lambda t: lax.psum(t, "model")) if tp > 1 else None

    if tp > 1:
        pspec = _tp_param_specs(cfg, mesh, params)
        embed_sharded = tuple(pspec["embed"].q if hasattr(pspec["embed"], "q")
                              else pspec["embed"])[:1] == ("model",)
    else:
        pspec = jax.tree.map(lambda _: P(), params)
        embed_sharded = False

    def embed_local(table, ids):
        """Token gather under a vocab-sharded table: local rows + psum."""
        v_local = table.shape[0]
        offset = lax.axis_index("model") * v_local
        local = jnp.clip(ids - offset, 0, v_local - 1)
        rows = qnt.embed_rows(table, local, dtype)
        in_range = ((ids >= offset) & (ids < offset + v_local))[..., None]
        return lax.psum(jnp.where(in_range, rows, 0), "model")

    def local_fn(params, tokens_c, length, cos_t, sin_t):
        i = lax.axis_index("seq")
        positions = i * Tc + jnp.arange(Tc, dtype=jnp.int32)
        cos = cos_t[positions][None, :, None, :]
        sin = sin_t[positions][None, :, None, :]
        if embed_sharded:
            x = embed_local(params["embed"], tokens_c[None])
        else:
            x = qnt.embed_rows(params["embed"], tokens_c[None], dtype)

        def body(carry, lp):
            def attend(q, k_new, v_new):
                out = ring_attention(
                    q[0], k_new[0], v_new[0], length,
                    n_chunks=n, sliding_window=cfg.sliding_window,
                )
                return out[None], (k_new[0], v_new[0])

            return mdl._layer(cfg, carry, lp, cos, sin, attend, reduce=reduce)

        x, kvs = lax.scan(body, x, params["layers"])
        x = mdl.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return x, kvs

    kv_heads = "model" if tp > 1 else None
    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(pspec, P("seq"), P(), P(), P()),
        out_specs=(
            P(None, "seq", None),
            (P(None, "seq", kv_heads, None), P(None, "seq", kv_heads, None)),
        ),
        check_vma=False,
    )
    return fn(params, tokens, length, rope[0], rope[1])
