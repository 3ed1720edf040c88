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

A family module (the contract is in benchmark/harness/spec.py): the
mathematics first, then the operations and bytes that mathematics needs, as
functions of the published keys ``hf`` and of token counts the client saw.
Hand arithmetic the second half is checked against
(benchmark/tests/test_work.py): Mistral-7B-v0.3 7.25 B parameters, 128 KiB of
bf16 KV per token; Mistral-Small-24B 23.6 B parameters, 160 KiB per token.
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


def head_dim(hf: dict) -> int:
    return int(hf.get("head_dim")
               or hf["hidden_size"] // hf["num_attention_heads"])


def norm_eps(hf: dict) -> float:
    return float(hf.get("rms_norm_eps", 1e-5))


def shape(hf: dict) -> tuple[int, int, int, float]:
    """(query heads, kv heads, head dim, RMSNorm epsilon) as published."""
    return (hf["num_attention_heads"], hf["num_key_value_heads"],
            head_dim(hf), norm_eps(hf))


def rope_tables(hf: dict, n_tokens: int):
    """cos, sin for positions 0 .. n_tokens - 1, as ``decoder_layer`` takes
    them."""
    return rope_angles(jnp.arange(n_tokens), head_dim(hf),
                       float(hf.get("rope_theta", 10000.0)))


def decoder_layer(x, w: dict, cos, sin, hf: dict):
    """One pre-norm decoder layer on one sequence x [T, D]."""
    num_heads, num_kv_heads, head_dim, eps = shape(hf)
    t = x.shape[0]
    h = rms_norm(x, w["attn_norm"], eps)
    q = (h @ w["wq"]).reshape(t, num_heads, head_dim)
    k = (h @ w["wk"]).reshape(t, num_kv_heads, head_dim)
    v = (h @ w["wv"]).reshape(t, num_kv_heads, head_dim)
    a = attention(rope(q, cos, sin), rope(k, cos, sin), v)
    x = x + a.reshape(t, num_heads * head_dim) @ w["wo"]
    h = rms_norm(x, w["mlp_norm"], eps)
    return x + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]


def logits(x, final_norm, head, hf: dict):
    """x [T, D] -> [T, V'] for the output-head columns given."""
    return rms_norm(x, final_norm, norm_eps(hf)) @ head


# ---------------------------------------------------------------------------
# operations and bytes


def attn_params(hf: dict) -> int:
    """The four attention projections of one layer."""
    d, hd = hf["hidden_size"], head_dim(hf)
    hq, hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    return d * hq * hd + 2 * d * hkv * hd + hq * hd * d


def layer_params(hf: dict) -> int:
    """Matmul weights of one decoder layer (norm gains left out: 2 D)."""
    return attn_params(hf) + 3 * hf["hidden_size"] * hf["intermediate_size"]


def table_params(hf: dict) -> int:
    """Embedding table, output head (unless tied) and the final norm."""
    d, v = hf["hidden_size"], hf["vocab_size"]
    return d * v + (0 if hf.get("tie_word_embeddings") else d * v) + d


def param_count(hf: dict) -> int:
    """Every weight: layers with their two norm gains, embedding table,
    output head (unless tied), final norm."""
    return (hf["num_hidden_layers"]
            * (layer_params(hf) + 2 * hf["hidden_size"]) + table_params(hf))


def token_params(hf: dict) -> int:
    """Weights one token's forward pass multiplies, all layers; the output
    head runs once a request, not once a token: left out."""
    return hf["num_hidden_layers"] * layer_params(hf)


def step_params(hf: dict, tokens: float) -> int:
    """Weights a decode step over ``tokens`` query tokens must read: all
    layers and the output head, whatever ``tokens`` is. The embedding table
    is gathered (one row a token), not read."""
    return token_params(hf) + hf["hidden_size"] * hf["vocab_size"]


def kv_bytes_per_token(hf: dict, element_bytes: float) -> float:
    """K and V of one token over all layers."""
    return (2 * hf["num_hidden_layers"] * hf["num_key_value_heads"]
            * head_dim(hf) * element_bytes)


def q_elements_per_token(hf: dict) -> int:
    """Elements of one token's q (and of its attention output), all layers."""
    return (hf["num_hidden_layers"] * hf["num_attention_heads"]
            * head_dim(hf))


def attn_flops(hf: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query token, attended token) pairs, all
    layers: 2 matmuls x 2 flops x heads x head_dim each."""
    return 4.0 * q_elements_per_token(hf) * pairs
