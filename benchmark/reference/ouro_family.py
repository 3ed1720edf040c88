"""Plain reference of the looped decoder (``model_type: ouro``; Ouro-2.6B,
ByteDance, https://huggingface.co/ByteDance/Ouro-2.6B): ONE stack of like
layers run ``total_ut_steps`` times a token.

Straightforward ``jax.numpy`` in float32: no kernels, no cache, no batching
machinery, no quantised arithmetic. Written from the published description
(the model card's ``config.json`` and ``modeling_ouro.py``, from memory: this
repository has no network), not from the program:

  one layer, a "sandwich" of FOUR RMSNorm gains around two branches:
      h = x + N2(Attn(N1(x)))        y = h + N4(MLP(N3(h)))
    Attn: rotate-half RoPE on q and k, causal softmax attention, no bias,
    plain multi-head at the published keys (16 query = 16 kv heads; grouped
    heads are computed all the same); MLP: down(silu(gate(u)) * up(u))
  the model:
      x = embed(tokens)
      for t in 0 .. total_ut_steps - 1:
          for l in 0 .. num_hidden_layers - 1: x = layer_l(x)
          x = final_norm(x)
      logits = x @ lm_head
    the final norm runs after EVERY pass and its output feeds the next pass;
    the last pass's normed output goes to the head (``walk`` applies the norm
    BETWEEN passes, ``logits`` after the last)
  the cache holds K and V for every (pass, layer) pair: ``cache_layers``.

Departures from the published model: **the exit gate is left out** (a
``hidden -> 1`` projection read after each pass, 2049 numbers, which turns
the passes' outputs into an adaptive mixture). At the published
``early_exit_threshold`` of 1 the cumulative exit probability reaches the
threshold only on the last pass, so every pass runs for every token and the
logits are the last pass's; ``param_count`` leaves the gate's 2049 numbers
out. ``sliding_window`` is published as null and not implemented.

Callers hold ``jax.default_matmul_precision("highest")`` while tracing.

Weight layout (one layer, float32, the served ``layers`` pytree's names):
wq/wk/wv [D, H*hd], wo [H*hd, D], w_gate/w_up [D, F], w_down [F, D]
(``x @ w``); attn_norm (N1), attn_post_norm (N2), mlp_norm (N3),
mlp_post_norm (N4) [D]. One sequence at a time: x is [T, D].

Hand arithmetic the second half is checked against (tests/test_ouro.py,
benchmark/tests/test_ouro_family.py), at the published keys (48 layers, hidden
2048, 16 heads of 128, SwiGLU 5632, vocabulary 49152, untied, 4 passes): a
layer 4 x 2048 x 2048 + 3 x 2048 x 5632 = 51,380,224 matmul weights + 4 x 2048
gains; 2,667,972,608 parameters; a decode step reads 4 x 48 x 51,380,224 + the
head's 100,663,296 = 9,965,666,304 weights; the cache grows 2 x 192 x 16 x 128
x 2 B = 1,572,864 B = 1.5 MiB a token in bfloat16.
"""

from __future__ import annotations

import jax

from reference.llama_family import (attention, attn_params,  # noqa: F401
                                    head_dim, logits, norm_eps, rms_norm,
                                    rope, rope_tables, shape, table_params)

NORMS = 4       # gains a layer: N1 .. N4


def passes(hf: dict) -> int:
    return int(hf.get("total_ut_steps", 1))


def decoder_layer(x, w: dict, cos, sin, hf: dict):
    """One sandwich layer on one sequence x [T, D]."""
    num_heads, num_kv_heads, hd, eps = shape(hf)
    t = x.shape[0]
    u = rms_norm(x, w["attn_norm"], eps)
    q = (u @ w["wq"]).reshape(t, num_heads, hd)
    k = (u @ w["wk"]).reshape(t, num_kv_heads, hd)
    v = (u @ w["wv"]).reshape(t, num_kv_heads, hd)
    a = attention(rope(q, cos, sin), rope(k, cos, sin), v)
    a = a.reshape(t, num_heads * hd) @ w["wo"]
    h = x + rms_norm(a, w["attn_post_norm"], eps)
    u = rms_norm(h, w["mlp_norm"], eps)
    m = (jax.nn.silu(u @ w["w_gate"]) * (u @ w["w_up"])) @ w["w_down"]
    return h + rms_norm(m, w["mlp_post_norm"], eps)


def walk(x, layer, rows, leaf, hf):
    """Every pass runs the whole stack in order; the final norm between two
    passes (``logits`` applies it after the last)."""
    for turn in range(passes(hf)):
        if turn:
            x = rms_norm(x, leaf("final_norm"), norm_eps(hf))
        for index in range(rows):
            x = layer(x, index)
    return x


def cache_layers(hf: dict) -> int:
    """A K/V cache entry for every (pass, layer) pair."""
    return passes(hf) * hf["num_hidden_layers"]


# ---------------------------------------------------------------------------
# operations and bytes


def layer_params(hf: dict) -> int:
    """Matmul weights of one layer (its four norm gains left out)."""
    return attn_params(hf) + 3 * hf["hidden_size"] * hf["intermediate_size"]


def param_count(hf: dict) -> int:
    """Every weight the served model holds: the layers with their four norm
    gains ONCE (the passes share them), embedding table, output head (unless
    tied), final norm. The exit gate's hidden + 1 numbers are left out: it
    is not served."""
    return (hf["num_hidden_layers"]
            * (layer_params(hf) + NORMS * hf["hidden_size"])
            + table_params(hf))


def token_params(hf: dict) -> int:
    """Weights one token's forward pass multiplies: every layer once a pass;
    the head left out."""
    return passes(hf) * hf["num_hidden_layers"] * layer_params(hf)


def step_params(hf: dict, tokens: float) -> int:
    """Weights a decode step must read: the stack once a PASS (nothing keeps
    2.3 GiB of it on the chip between two passes) and the head once,
    whatever ``tokens`` is."""
    return token_params(hf) + hf["hidden_size"] * hf["vocab_size"]


def kv_bytes_per_token(hf: dict, element_bytes: float) -> float:
    """K and V of one token over all cache layers."""
    return (2 * cache_layers(hf) * hf["num_key_value_heads"] * head_dim(hf)
            * element_bytes)


def q_elements_per_token(hf: dict) -> int:
    """Elements of one token's q (and of its attention output), all cache
    layers."""
    return cache_layers(hf) * hf["num_attention_heads"] * head_dim(hf)


def attn_flops(hf: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query token, attended token) pairs, all
    cache layers: 2 matmuls x 2 flops x heads x head_dim each."""
    return 4.0 * q_elements_per_token(hf) * pairs
