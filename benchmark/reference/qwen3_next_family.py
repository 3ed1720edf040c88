"""Plain reference of the Qwen3-Next decoder (``model_type: qwen3_next``;
Qwen3-Next-80B-A3B, https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct):
PERIODS of ``full_attention_interval`` layers, Gated DeltaNet layers and then
one gated full-attention layer, every layer followed by routed experts with a
shared expert.

Straightforward ``jax.numpy`` in float32 on one sequence: no kernels, no
cache, no state carried between calls, no batching machinery. Written from
the published description (the model card's ``config.json``; Yang et al.
2024, "Gated Delta Networks", arXiv:2412.06464; ``modeling_qwen3_next.py``
from memory: this repository has no network), not from the program. ``hf``
are the configuration's published keys; D = ``hidden_size``.

  norm        N(x; w) = x * rsqrt(mean(x^2) + eps) * (1 + w): a ZERO-CENTRED
              gain, for the two norms of a layer, the final norm, q_norm and
              k_norm
  layer i     ``full_attention`` where (i + 1) % full_attention_interval
              == 0, else ``linear_attention``:
              h = x + Mixer_i(N(x; w_in)),  y = h + MoE(N(h; w_post))
  full attention (Hq query heads, Hkv kv heads, head_dim hd, no bias):
              q_proj: D -> Hq x 2 hd, each head's 2 hd split into q (hd) and
              gate (hd); k_proj, v_proj: D -> Hkv x hd. q = N(q; q_norm),
              k = N(k; k_norm) over hd, per head. Rotate-half RoPE on the
              FIRST partial_rotary_factor x hd dims of q and k, the others
              pass. Causal softmax attention, scale hd^-1/2, grouped heads.
              out = o_proj((attn * sigmoid(gate)).reshape(T, Hq hd))
  Gated DeltaNet (Hk key heads of dk, Hv value heads of dv, conv width K):
              in_proj_qkvz: D -> Hk dk + Hk dk + Hv dv + Hv dv (q, k, v, z),
              in_proj_ba: D -> Hv + Hv (b, a). [q; k; v] through a causal
              depthwise conv1d of width K, no bias, then SiLU.
              beta = sigmoid(b); g = -exp(A_log) * softplus(a + dt_bias) per
              value head. q and k are L2-normalised over dk, q scaled by
              dk^-1/2, each key head serving Hv / Hk value heads (repeat).
              Per value head, S in [dk, dv], S_0 = 0; for each token t:
                  S <- exp(g_t) S;  u = S^T k_t
                  S <- S + k_t (x) (beta_t (v_t - u));  o_t = S^T q_t
              o <- rmsnorm(o; w_norm) * silu(z) over each head's dv (this
              ONE norm has a plain gain w and norms BEFORE the gate), then
              out_proj: Hv dv -> D
  experts     p = softmax(x @ W_r) over ALL experts, the k largest,
              renormalised to sum 1 if norm_topk_prob;
              routed = sum over the chosen e of p_e down_e(silu(gate_e x)
              * up_e x); shared = sigmoid(x @ w_sg) down_s(silu(gate_s x)
              * up_s x); MoE = routed + shared
  model       logits = N(x_last_layer; w_f) @ lm_head (untied)

THE SHARE. ``expert_parallel: {size, rank}`` (no published key: the
configuration file states the deployment) says that ``num_experts`` is what
ONE of ``size`` chips holds of each layer, experts ``rank x num_experts ..``;
the router keeps its full width ``num_experts x size`` and its k. ``routed``
then sums over the chosen experts HELD here: what the absent experts would
add is left out, here as in the program, and that partial result goes on.
Absent (size 1) the layer is whole.

Departures from the published model: the multi-token-prediction module is
not served and not counted (no key of the config gives it a shape). The L2
normalisation is x * rsqrt(sum(x^2) + 1e-6), as the published code's. In the
evaluation of ``experts`` one, as ``moe_family.py``'s: every held expert runs
on every token and is multiplied by a weight that is exactly 0 off the
token's choices. The checkpoint's grouped order inside in_proj_qkvz /
in_proj_ba is a loader's matter: the layout here is flat, [q; k; v; z] and
[b; a].

Callers hold ``jax.default_matmul_precision("highest")`` while tracing.

Weight layout: ``decoder_layer`` is ONE PERIOD, ``w`` one row of the served
``layers`` pytree (G = interval - 1 DeltaNet layers, M = interval expert
blocks): gdn_norm [G, D], gdn_in_qkvz [G, D, 2 Hk dk + 2 Hv dv], gdn_in_ba
[G, D, 2 Hv], gdn_conv [G, K, C] (row K - 1 multiplies the token itself),
gdn_A_log, gdn_dt_bias [G, Hv], gdn_out_norm [G, dv], gdn_wo [G, Hv dv, D];
attn_norm [D], wq [D, Hq 2 hd], wk, wv [D, Hkv hd], q_norm, k_norm [hd], wo
[Hq hd, D]; mlp_norm [M, D], moe_gate [M, D, E size], w_gate, w_up [M, E, D,
F], w_down [M, E, F, D], shared_gate, shared_up [M, D, Fs], shared_down [M,
Fs, D], shared_router [M, D].

Hand arithmetic of the second half (benchmark/tests/test_qwen3_next_family
.py), at the cut the configuration file states (12 layers = 3 periods, 64 of
512 experts held, vocabulary 18992): a DeltaNet layer 33,718,272 matmul
weights (25,165,824 + 131,072 + 32,768 + 8,388,608), a full layer 27,262,976,
an expert block 64 x 3,145,728 + 3,145,728 + 2048 + 1,048,576 outside them;
2,929,374,400 parameters; K/V 6 KiB a token in bfloat16 (3 cache layers).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.llama_family import attention, norm_eps, rope_angles

NORMS = 2           # zero-centred [D] gains a layer: input and post-mixer


def zc_norm(x, w, eps):
    """N(x; w): RMSNorm with the zero-centred gain 1 + w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * (1.0 + w)


def dims(hf: dict) -> dict:
    """The shapes the equations name, from the published keys."""
    interval = int(hf.get("full_attention_interval", 4))
    ep = hf.get("expert_parallel") or {}
    d = {
        "D": hf["hidden_size"], "interval": interval,
        "periods": hf["num_hidden_layers"] // interval, "G": interval - 1,
        "Hq": hf["num_attention_heads"], "Hkv": hf["num_key_value_heads"],
        "hd": int(hf["head_dim"]),
        "Hk": hf["linear_num_key_heads"], "Hv": hf["linear_num_value_heads"],
        "dk": hf["linear_key_head_dim"], "dv": hf["linear_value_head_dim"],
        "K": int(hf.get("linear_conv_kernel_dim", 4)),
        "E": hf["num_experts"], "topk": hf["num_experts_per_tok"],
        "F": hf["moe_intermediate_size"],
        "Fs": hf["shared_expert_intermediate_size"],
        "size": int(ep.get("size", 1)), "rank": int(ep.get("rank", 0)),
    }
    d["rot"] = int(d["hd"] * float(hf.get("partial_rotary_factor", 1.0)))
    d["C"] = 2 * d["Hk"] * d["dk"] + d["Hv"] * d["dv"]
    return d


def rope_tables(hf: dict, n_tokens: int):
    """cos, sin [T, rot / 2] over the rotated dims alone."""
    return rope_angles(jnp.arange(n_tokens), dims(hf)["rot"],
                       float(hf.get("rope_theta", 10000.0)))


def partial_rope(x, cos, sin, rot: int):
    """x [T, H, hd]: rotate-half on the first ``rot`` dims of each head."""
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    c, s = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, rest], axis=-1)


def full_attention(h, w: dict, cos, sin, hf: dict):
    n, eps, t = dims(hf), norm_eps(hf), h.shape[0]
    qg = (h @ w["wq"]).reshape(t, n["Hq"], 2 * n["hd"])
    q, gate = qg[..., :n["hd"]], qg[..., n["hd"]:]
    k = (h @ w["wk"]).reshape(t, n["Hkv"], n["hd"])
    v = (h @ w["wv"]).reshape(t, n["Hkv"], n["hd"])
    q = partial_rope(zc_norm(q, w["q_norm"], eps), cos, sin, n["rot"])
    k = partial_rope(zc_norm(k, w["k_norm"], eps), cos, sin, n["rot"])
    a = attention(q, k, v) * jax.nn.sigmoid(gate)
    return a.reshape(t, n["Hq"] * n["hd"]) @ w["wo"]


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def causal_conv(x, kernel):
    """x [T, C], kernel [K, C]: out_t = sum_i kernel[i] x_{t - (K-1) + i},
    zeros in front of the sequence."""
    k, t = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return sum(padded[i:i + t] * kernel[i] for i in range(k))


def gated_deltanet(h, w: dict, hf: dict):
    """h [T, D] -> [T, D]; ``w`` one DeltaNet layer's leaves."""
    n, t = dims(hf), h.shape[0]
    kd = n["Hk"] * n["dk"]
    qkvz, ba = h @ w["gdn_in_qkvz"], h @ w["gdn_in_ba"]
    qkv, z = qkvz[:, :n["C"]], qkvz[:, n["C"]:]
    b, a = ba[:, :n["Hv"]], ba[:, n["Hv"]:]
    qkv = jax.nn.silu(causal_conv(qkv, w["gdn_conv"]))
    q = qkv[:, :kd].reshape(t, n["Hk"], n["dk"])
    k = qkv[:, kd:2 * kd].reshape(t, n["Hk"], n["dk"])
    v = qkv[:, 2 * kd:].reshape(t, n["Hv"], n["dv"])
    rep = n["Hv"] // n["Hk"]
    q = jnp.repeat(l2norm(q) * n["dk"] ** -0.5, rep, axis=1)
    k = jnp.repeat(l2norm(k), rep, axis=1)
    beta = jax.nn.sigmoid(b)                                    # [T, Hv]
    g = -jnp.exp(w["gdn_A_log"]) * jax.nn.softplus(a + w["gdn_dt_bias"])

    def token(S, xs):                       # S [Hv, dk, dv]
        q_t, k_t, v_t, g_t, beta_t = xs
        S = jnp.exp(g_t)[:, None, None] * S
        u = jnp.einsum("hkv,hk->hv", S, k_t)
        S = S + k_t[:, :, None] * (beta_t[:, None] * (v_t - u))[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    _, o = jax.lax.scan(
        token, jnp.zeros((n["Hv"], n["dk"], n["dv"]), jnp.float32),
        (q, k, v, g, beta))                                     # [T, Hv, dv]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + norm_eps(hf)) * w["gdn_out_norm"]
    o = o * jax.nn.silu(z.reshape(t, n["Hv"], n["dv"]))
    return o.reshape(t, n["Hv"] * n["dv"]) @ w["gdn_wo"]


def routing(h, w_router, hf: dict):
    """Routing weights of the experts HELD here [T, E]: the router scores all
    E x size, a token's k largest (renormalised) stand at its experts'
    places, and the columns of this rank's experts are what is returned."""
    n = dims(hf)
    probs = jax.nn.softmax(h @ w_router, axis=-1)
    top, chosen = jax.lax.top_k(probs, n["topk"])
    if hf.get("norm_topk_prob", True):
        top = top / jnp.sum(top, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    full = jnp.zeros_like(probs).at[rows, chosen].set(top)
    return full[:, n["rank"] * n["E"]:(n["rank"] + 1) * n["E"]]


def experts(h, w: dict, hf: dict):
    """h [T, D] -> [T, D]: this share's routed sum plus the shared expert;
    ``w`` one expert block's leaves."""
    route = routing(h, w["moe_gate"], hf)

    def add_expert(out, expert):
        w_gate, w_up, w_down, weight = expert
        y = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return out + weight[:, None] * y, None

    routed, _ = jax.lax.scan(add_expert, jnp.zeros_like(h),
                             (w["w_gate"], w["w_up"], w["w_down"], route.T))
    shared = ((jax.nn.silu(h @ w["shared_gate"]) * (h @ w["shared_up"]))
              @ w["shared_down"])
    return routed + jax.nn.sigmoid(h @ w["shared_router"])[:, None] * shared


GDN_LEAVES = ("gdn_norm", "gdn_in_qkvz", "gdn_in_ba", "gdn_conv",
              "gdn_A_log", "gdn_dt_bias", "gdn_out_norm", "gdn_wo")
MOE_LEAVES = ("mlp_norm", "moe_gate", "w_gate", "w_up", "w_down",
              "shared_gate", "shared_up", "shared_down", "shared_router")


def decoder_layer(x, w: dict, cos, sin, hf: dict):
    """ONE PERIOD on one sequence x [T, D]: interval - 1 DeltaNet layers,
    then the full-attention layer, each followed by its expert block."""
    n, eps = dims(hf), norm_eps(hf)

    def moe(x, m):
        wm = {name: w[name][m] for name in MOE_LEAVES}
        return x + experts(zc_norm(x, wm["mlp_norm"], eps), wm, hf)

    for g in range(n["G"]):
        wg = {name: w[name][g] for name in GDN_LEAVES}
        x = x + gated_deltanet(zc_norm(x, wg["gdn_norm"], eps), wg, hf)
        x = moe(x, g)
    x = x + full_attention(zc_norm(x, w["attn_norm"], eps), w, cos, sin, hf)
    return moe(x, n["G"])


def logits(x, final_norm, head, hf: dict):
    """x [T, D] -> [T, V'] for the output-head columns given."""
    return zc_norm(x, final_norm, norm_eps(hf)) @ head


def cache_layers(hf: dict) -> int:
    """K/V is cached by the full-attention layers alone: one a period."""
    return dims(hf)["periods"]


# ---------------------------------------------------------------------------
# operations and bytes


def gdn_params(hf: dict) -> int:
    """Matmul weights of one DeltaNet mixer: the two input projections, the
    conv's taps, the output projection."""
    n = dims(hf)
    vd = n["Hv"] * n["dv"]
    return (n["D"] * (n["C"] + vd) + n["D"] * 2 * n["Hv"] + n["K"] * n["C"]
            + vd * n["D"])


def gdn_vectors(hf: dict) -> int:
    """A DeltaNet mixer's per-head vectors: A_log, dt_bias, the gated norm's
    gain."""
    n = dims(hf)
    return 2 * n["Hv"] + n["dv"]


def attn_params(hf: dict) -> int:
    """The four projections of the gated full attention (q carries its
    gate)."""
    n = dims(hf)
    return (n["D"] * n["Hq"] * 2 * n["hd"] + 2 * n["D"] * n["Hkv"] * n["hd"]
            + n["Hq"] * n["hd"] * n["D"])


def expert_params(hf: dict) -> int:
    """One routed expert's SwiGLU: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def block_fixed_params(hf: dict) -> int:
    """An expert block outside its routed experts: the router at its full
    width, the shared expert and its gate."""
    n = dims(hf)
    return n["D"] * n["E"] * n["size"] + 3 * n["D"] * n["Fs"] + n["D"]


def layer_params(hf: dict) -> float:
    """Matmul weights of one layer, as HBM holds them: the period's mean (a
    DeltaNet or a full mixer, the router, the shared expert and the experts
    HELD here)."""
    n = dims(hf)
    mixers = n["G"] * gdn_params(hf) + attn_params(hf)
    return (mixers / n["interval"] + block_fixed_params(hf)
            + n["E"] * expert_params(hf))


def table_params(hf: dict) -> int:
    d, v = hf["hidden_size"], hf["vocab_size"]
    return d * v + (0 if hf.get("tie_word_embeddings") else d * v) + d


def param_count(hf: dict) -> int:
    """Every weight the served model holds: the HELD share of the experts,
    every norm gain and per-head vector, table, head, final norm. The
    multi-token-prediction module is not served and not counted."""
    n = dims(hf)
    period = (n["G"] * (gdn_params(hf) + gdn_vectors(hf))
              + attn_params(hf) + 2 * n["hd"]
              + n["interval"] * (NORMS * n["D"] + block_fixed_params(hf)
                                 + n["E"] * expert_params(hf)))
    return n["periods"] * period + table_params(hf)


def _stack_params(hf: dict, experts_a_block: float) -> float:
    n = dims(hf)
    period = (n["G"] * gdn_params(hf) + attn_params(hf)
              + n["interval"] * (block_fixed_params(hf)
                                 + experts_a_block * expert_params(hf)))
    return n["periods"] * period


def token_params(hf: dict) -> float:
    """Weights one token's forward pass multiplies HERE, all layers: the
    mixers, the router, the shared expert and the k / size of its k experts
    that are expected on this share; the head left out."""
    n = dims(hf)
    return _stack_params(hf, n["topk"] / n["size"])


def experts_touched(hf: dict, tokens: float) -> float:
    """Experts of one block's HELD share that ``tokens`` tokens are EXPECTED
    to reach, each choosing k of all E x size uniformly and independently:
    E (1 - (1 - k / (E size))^tokens). 30.0 of 64 at 32 tokens, top-10 of
    512."""
    n = dims(hf)
    return n["E"] * (1.0 - (1.0 - n["topk"] / (n["E"] * n["size"]))
                     ** tokens)


def step_params(hf: dict, tokens: float) -> float:
    """WEIGHTS a decode step over ``tokens`` query tokens is expected to
    read: the mixers, routers and shared experts, the experts touched, the
    head. The DeltaNet state a step reads and writes is no weight and is not
    here (``state_bytes`` has it)."""
    return (_stack_params(hf, experts_touched(hf, tokens))
            + hf["hidden_size"] * hf["vocab_size"])


def kv_bytes_per_token(hf: dict, element_bytes: float) -> float:
    """K and V of one token over the full-attention layers."""
    n = dims(hf)
    return 2 * cache_layers(hf) * n["Hkv"] * n["hd"] * element_bytes


def q_elements_per_token(hf: dict) -> int:
    """Elements of one token's q (and of its attention output) over the
    full-attention layers."""
    n = dims(hf)
    return cache_layers(hf) * n["Hq"] * n["hd"]


def attn_flops(hf: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query token, attended token) pairs, the
    full-attention layers: 2 matmuls x 2 flops x heads x head_dim each."""
    return 4.0 * q_elements_per_token(hf) * pairs


def expert_bytes(hf: dict, touched: float, element_bytes: float = 2.0,
                 ) -> float:
    """Bytes the routed matmuls must read for ``touched`` (expert, block)
    pairs that had a token: each expert's three matrices once."""
    return touched * expert_params(hf) * element_bytes


def state_bytes(hf: dict, slot_steps: float, element_bytes: float = 2.0,
                ) -> float:
    """Bytes the DeltaNet layers must move for ``slot_steps`` (live slot,
    step) pairs: every layer's S read and written in float32, its conv rows
    read and written in the compute dtype."""
    n = dims(hf)
    per_layer = (2 * n["Hv"] * n["dk"] * n["dv"] * 4.0
                 + 2 * (n["K"] - 1) * n["C"] * element_bytes)
    return slot_steps * n["periods"] * n["G"] * per_layer
