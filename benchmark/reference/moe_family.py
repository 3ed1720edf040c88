"""Plain reference of the sparse-expert decoder block (Mixtral, OLMoE):
the Llama-family block with its SwiGLU replaced by a router and E expert
SwiGLUs, of which each token runs k.

Straightforward ``jax.numpy`` in float32, as ``llama_family.py``, whose
RMSNorm, RoPE and attention this imports. Written from the published
descriptions (Jiang et al. 2024, arXiv:2401.04088; Muennighoff et al. 2024,
arXiv:2409.02060; the Hugging Face ``modeling_mixtral.py`` /
``modeling_olmoe.py`` conventions for weight layout and router), not from the
program:

  attention: the Llama family's; OLMoE (``model_type: olmoe``) applies an
    RMSNorm with a gain to the projected q and to the projected k, over the
    WHOLE projection [Hq*hd] / [Hkv*hd], before the split into heads and
    before RoPE. Mixtral has none.
  router: softmax(h @ moe_gate) over ALL E experts, then the k largest; the
    k weights renormalised to sum to 1 only if ``norm_topk_prob`` (OLMoE
    publishes false; Mixtral publishes no such key and always renormalises,
    so absent means true). No capacity, no dropped token, no auxiliary loss.
  experts: each token's output is the sum over ITS k experts of its routing
    weight times that expert's SwiGLU. E comes from ``num_experts`` (OLMoE)
    or ``num_local_experts`` (Mixtral), k from ``num_experts_per_tok``, an
    expert's width from ``intermediate_size``.

Departures: none in the mathematics. In its evaluation one: ``experts`` loops
over the E experts, runs each on every token and multiplies by a routing
weight that is exactly 0 off the token's k: the same sum term for term, at
E/k times the routed flops (a gather of k experts' weights a token would hold
T x k x 3 x D x F floats at once; the reference is not timed). OLMoE's
``clip_qkv`` is published null and not implemented; ``sliding_window`` as in
``llama_family.py``.

Weight layout (one layer, float32, the names of the served ``layers``
pytree): wq, wk, wv, wo, attn_norm, mlp_norm as the Llama family's; moe_gate
[D, E] (the router); w_gate/w_up [E, D, F], w_down [E, F, D]; with q/k norm,
q_norm [Hq*hd] and k_norm [Hkv*hd]. One sequence at a time: x is [T, D].

The arithmetic (contract in benchmark/harness/spec.py; hand cases in
benchmark/tests/test_work.py: OLMoE-1B-7B 6.92 B parameters, 1.18 B a token;
Mixtral-8x7B 46.70 B): a layer HOLDS all E experts, a token MULTIPLIES k of
them, and a decode step READS the experts its tokens were routed to:
``step_params`` counts the EXPECTED number under uniform independent routing,
E (1 - (1 - k/E)^tokens), 56.4 of 64 at 16 tokens and top-8. It is that
assumption and never all E: real routers are less even than uniform and touch
fewer, a need that is overstated reads over 100% of a roofline, and the
driver refuses a reading over 105%. A program that counts the experts a step
touched can replace the expectation by the count.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# what this family shares with the dense one is its own by import: the
# contract's rope_tables, logits, kv_bytes_per_token, q_elements_per_token
# and attn_flops are the Llama family's
from reference.llama_family import (attention, attn_flops,  # noqa: F401
                                    attn_params, kv_bytes_per_token, logits,
                                    q_elements_per_token, rms_norm, rope,
                                    rope_tables, shape, table_params)


def num_experts(hf: dict) -> int:
    return int(hf.get("num_experts") or hf["num_local_experts"])


def qk_norm(hf: dict) -> bool:
    return hf.get("model_type") == "olmoe"


def routing(h, w_router, hf: dict):
    """Routing weights [T, E]: each token's k largest softmax probabilities
    at its experts' places, 0 elsewhere."""
    probs = jax.nn.softmax(h @ w_router, axis=-1)
    top, chosen = jax.lax.top_k(probs, hf["num_experts_per_tok"])
    if hf.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, chosen].set(top)


def experts(h, w: dict, hf: dict):
    """h [T, D] -> [T, D]: the routed sum of the experts' SwiGLUs."""
    route = routing(h, w["moe_gate"], hf)

    def add_expert(out, expert):
        w_gate, w_up, w_down, weight = expert
        y = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return out + weight[:, None] * y, None

    out, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                          (w["w_gate"], w["w_up"], w["w_down"], route.T))
    return out


def decoder_layer(x, w: dict, cos, sin, hf: dict):
    """One pre-norm sparse-expert decoder layer on one sequence x [T, D]."""
    num_heads, num_kv_heads, head_dim, eps = shape(hf)
    t = x.shape[0]
    h = rms_norm(x, w["attn_norm"], eps)
    q, k, v = h @ w["wq"], h @ w["wk"], h @ w["wv"]
    if qk_norm(hf):
        q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    q = q.reshape(t, num_heads, head_dim)
    k = k.reshape(t, num_kv_heads, head_dim)
    v = v.reshape(t, num_kv_heads, head_dim)
    a = attention(rope(q, cos, sin), rope(k, cos, sin), v)
    x = x + a.reshape(t, num_heads * head_dim) @ w["wo"]
    return x + experts(rms_norm(x, w["mlp_norm"], eps), w, hf)


# ---------------------------------------------------------------------------
# operations and bytes


def router_params(hf: dict) -> int:
    return hf["hidden_size"] * num_experts(hf)


def expert_params(hf: dict) -> int:
    """One expert's SwiGLU: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def layer_params(hf: dict) -> int:
    """Matmul weights of one layer: attention, the router, ALL E experts."""
    return (attn_params(hf) + router_params(hf)
            + num_experts(hf) * expert_params(hf))


def param_count(hf: dict) -> int:
    """Every weight: layers with their norm gains (two, and the q and k
    norms' where the family has them), table, head, final norm."""
    num_heads, num_kv_heads, head_dim, _ = shape(hf)
    gains = 2 * hf["hidden_size"] + (
        (num_heads + num_kv_heads) * head_dim if qk_norm(hf) else 0)
    return (hf["num_hidden_layers"] * (layer_params(hf) + gains)
            + table_params(hf))


def _stack_params(hf: dict, experts_a_layer: float) -> float:
    return hf["num_hidden_layers"] * (
        attn_params(hf) + router_params(hf)
        + experts_a_layer * expert_params(hf))


def token_params(hf: dict) -> int:
    """Weights one token's forward pass multiplies, all layers: attention,
    the router and ITS k experts; the output head left out."""
    return _stack_params(hf, hf["num_experts_per_tok"])


def experts_touched(hf: dict, tokens: float) -> float:
    """Experts of one layer that ``tokens`` tokens are EXPECTED to be routed
    to, each token choosing k of E uniformly and independently."""
    e, k = num_experts(hf), hf["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** tokens)


def step_params(hf: dict, tokens: float) -> float:
    """Weights a decode step over ``tokens`` query tokens is expected to
    read: attention, the router and the experts touched in every layer, and
    the output head. Never all E (the module's docstring says why)."""
    return (_stack_params(hf, experts_touched(hf, tokens))
            + hf["hidden_size"] * hf["vocab_size"])
