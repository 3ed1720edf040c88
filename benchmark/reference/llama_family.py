"""Plain reference of the Llama-family decoder block (Llama, Mistral, Qwen2):
RMSNorm, rotate-half RoPE, grouped-query causal attention, SwiGLU.

Straightforward ``jax.numpy`` in float32: no kernels, no cache, no batching
machinery, no quantised arithmetic. Written from the published description
(Touvron et al. 2023, arXiv:2302.13971; Jiang et al. 2023, arXiv:2310.06825;
the Hugging Face ``modeling_mistral.py`` conventions for weight layout), not
from the program. Departures: none in the mathematics. ``sliding_window`` is
not implemented because both served configurations publish it as null; a
configuration that sets it must add it here.

Callers hold ``jax.default_matmul_precision("highest")`` while tracing: on a
TPU a float32 matmul otherwise runs in bfloat16 passes.

Weight layout (one layer, float32): wq [D, Hq*hd], wk/wv [D, Hkv*hd],
wo [Hq*hd, D], w_gate/w_up [D, F], w_down [F, D] (``x @ w``); attn_norm,
mlp_norm [D]. One sequence at a time: x is [T, D].
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def rope_angles(positions, head_dim: int, theta: float):
    """cos, sin [T, hd/2] for the given absolute positions."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin):
    """x [T, H, hd]; pairs are (i, i + hd/2): the rotate-half convention of
    the published checkpoints."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v):
    """Causal grouped-query attention. q [T, Hq, hd], k/v [T, Hkv, hd]."""
    t, hq, hd = q.shape
    hkv = k.shape[1]
    k = jnp.repeat(k, hq // hkv, axis=1)
    v = jnp.repeat(v, hq // hkv, axis=1)
    scores = jnp.einsum("thd,shd->hts", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("hts,shd->thd", jax.nn.softmax(scores, axis=-1), v)


def decoder_layer(x, w: dict, cos, sin, *, num_heads: int, num_kv_heads: int,
                  head_dim: int, eps: float):
    """One pre-norm decoder layer on one sequence x [T, D]."""
    t = x.shape[0]
    h = rms_norm(x, w["attn_norm"], eps)
    q = (h @ w["wq"]).reshape(t, num_heads, head_dim)
    k = (h @ w["wk"]).reshape(t, num_kv_heads, head_dim)
    v = (h @ w["wv"]).reshape(t, num_kv_heads, head_dim)
    a = attention(rope(q, cos, sin), rope(k, cos, sin), v)
    x = x + a.reshape(t, num_heads * head_dim) @ w["wo"]
    h = rms_norm(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def logits(x, final_norm, head, eps: float):
    """x [T, D] -> [T, V'] for the output-head columns given."""
    return rms_norm(x, final_norm, eps) @ head
