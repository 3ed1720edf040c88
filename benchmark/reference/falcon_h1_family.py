"""Plain reference of the Falcon-H1 decoder (``model_type: falcon_h1``;
Falcon-H1-34B-Instruct, https://huggingface.co/tiiuae/Falcon-H1-34B-Instruct):
EVERY layer runs a Mamba-2 state-space mixer AND grouped-query attention on
the SAME normed input, adds the two to the residual, and follows them with a
dense gated MLP; muP multipliers (14 numbers in the config, 13 of them not 1
as published) are part of the mathematics.

Straightforward ``jax.numpy`` in float32 on one sequence: no kernels, no
cache, no state carried between calls, no chunked scan, no multiplier folded
into a weight. Written from the published keys (the model card's
``config.json``), Dao & Gu 2024 ("Transformers are SSMs", arXiv:2405.21060:
the Mamba-2 recurrence with a scalar decay a head) and transformers'
``modeling_falcon_h1.py`` FROM MEMORY (this repository has no network), not
from the program. ``hf`` are the configuration's published keys; D =
``hidden_size``; H = ``mamba_n_heads`` heads of P = ``mamba_d_head``
(H P = ``mamba_d_ssm``, NOT ``mamba_expand`` x D), G = ``mamba_n_groups``
groups of N = ``mamba_d_state``, conv width K = ``mamba_d_conv``.

  norm        N(x; w) = x * rsqrt(mean(x^2) + eps) * w: a plain gain, eps
              ``rms_norm_eps``
  model       e = embed[token] * embedding_multiplier
              logits = head(N(x_last; w_final)) * lm_head_multiplier
  layer       h = N(x; w_in)
              x = x + Mixer(h) * ssm_out_multiplier
                    + Attention(h * attention_in_multiplier)
                      * attention_out_multiplier
              f = N(x; w_ff)
              x = x + down(silu(gate(f) * mlp_multipliers[0]) * up(f))
                      * mlp_multipliers[1]
  mixer       p = in_proj(h * ssm_in_multiplier) * mup, where mup holds
              ssm_multipliers[0..4] over the segments
              [z: H P | x: H P | B: G N | C: G N | dt: H]
              z, xBC, dt = split(p, [H P, H P + 2 G N, H])
              xBC = silu(causal_depthwise_conv_K(xBC) + conv_bias)
              x_, B, C = split(xBC, [H P, G N, G N]);  x_ [H, P]; B, C [G, N];
              head h reads group h // (H / G)
              dt = softplus(dt + dt_bias)   (``time_step_limit`` is (0, inf):
              no clamp);  A = -exp(A_log)
              per head, S in [P, N], S_0 = 0; for each token t:
                  S <- exp(dt_t A) S + dt_t x_t (x) B_t
                  y_t = S C_t + D x_t
              y = group_rmsnorm(y * silu(z); w_norm): the gate FIRST
              (``mamba_norm_before_gate`` false), then an RMSNorm over each
              of the G groups of H P / G channels, gain w_norm [H P]
              out = out_proj(y)
  attention   q = wq(a), k = wk(a) * key_multiplier, v = wv(a) for the
              layer's a = h * attention_in_multiplier; Hq query heads, Hkv
              K/V heads of hd; rotate-half RoPE over the whole head (theta
              ``rope_theta``, no scaling), no bias, no q/k norm; causal
              softmax, scale hd^-1/2; out = wo(attn)

Departures from the published model: none in the mathematics. What the keys
do not settle and is ASSUMED (the configuration file lists it): the order of
the segments inside ``in_proj`` and of the conv's channels ([x | B | C]) is
the published code's as remembered; ``mamba_conv_bias`` true, ``mamba_proj_
bias``, ``attention_bias``, ``mlp_bias`` and ``projectors_bias`` false are
honoured (other values raise); ``mamba_rms_norm`` true (false raises).

Callers hold ``jax.default_matmul_precision("highest")`` while tracing.

Weight layout (one layer, float32, ``x @ w``): attn_norm, mlp_norm [D]; ssm_in
[D, 2 H P + 2 G N + H]; ssm_conv [K, H P + 2 G N] (row K - 1 multiplies the
token itself), ssm_conv_bias [H P + 2 G N]; ssm_A_log, ssm_D, ssm_dt_bias
[H]; ssm_norm [H P]; ssm_out [H P, D]; wq [D, Hq hd], wk, wv [D, Hkv hd], wo
[Hq hd, D]; w_gate, w_up [D, F], w_down [F, D]. The harness embeds the probes
(``embed[token]``); ``walk`` applies ``embedding_multiplier`` to them before
the first layer.

Hand arithmetic of the second half (benchmark/tests/test_falcon_h1_family.py),
at the published widths: a layer's MLP 3 x 5120 x 21504 = 330,301,440;
``in_proj`` 5120 x 9248 = 47,349,760; ``out_proj`` 20,971,520; q and o
13,107,200 each, k + v 5,242,880; conv 5120 x 4 + 5120; vectors 96 + 4096 +
10240: 430,120,032 a layer; each table 261120 x 5120 = 1,336,934,400. State
a slot a layer: 32 x 128 x 256 float32 = 4 MiB and 3 conv rows x 5120 x 2 B =
30 KiB; K/V 2 KiB a token a layer in bfloat16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.llama_family import (attention, attn_flops, attn_params,
                                    head_dim, kv_bytes_per_token, norm_eps,
                                    q_elements_per_token, rms_norm, rope,
                                    rope_tables, table_params)

__all__ = ["attn_flops", "kv_bytes_per_token", "q_elements_per_token",
           "rope_tables"]

NORMS = 2           # [D] gains a layer: in front of the mixers, and of the MLP


def dims(hf: dict) -> dict:
    """The shapes the equations name, from the published keys."""
    for key, want in (("mamba_conv_bias", True), ("mamba_rms_norm", True),
                      ("mamba_proj_bias", False), ("attention_bias", False),
                      ("mlp_bias", False), ("projectors_bias", False),
                      ("mamba_norm_before_gate", False)):
        if bool(hf.get(key, want)) != want:
            raise NotImplementedError(
                f"falcon_h1_family: {key} = {hf[key]!r} is not what the "
                f"published configuration states ({want}) and not written")
    d = {"D": hf["hidden_size"], "F": hf["intermediate_size"],
         "H": hf["mamba_n_heads"], "P": hf["mamba_d_head"],
         "G": hf["mamba_n_groups"], "N": hf["mamba_d_state"],
         "K": hf["mamba_d_conv"], "Hq": hf["num_attention_heads"],
         "Hkv": hf["num_key_value_heads"], "hd": head_dim(hf)}
    d["ssm"] = d["H"] * d["P"]
    if d["ssm"] != hf["mamba_d_ssm"]:
        raise ValueError(f"mamba_d_ssm {hf['mamba_d_ssm']} is not "
                         f"mamba_n_heads x mamba_d_head = {d['ssm']}")
    d["C"] = d["ssm"] + 2 * d["G"] * d["N"]         # channels the conv sees
    d["in"] = d["ssm"] + d["C"] + d["H"]            # in_proj's outputs
    return d


def mup_vector(hf: dict):
    """ssm_multipliers over in_proj's outputs [z | x | B | C | dt]."""
    n = dims(hf)
    gn = n["G"] * n["N"]
    sizes = (n["ssm"], n["ssm"], gn, gn, n["H"])
    return jnp.concatenate([
        jnp.full(size, m, jnp.float32)
        for size, m in zip(sizes, hf["ssm_multipliers"], strict=True)])


def causal_conv(x, kernel, bias):
    """x [T, C], kernel [K, C]: out_t = bias + sum_i kernel[i] x_{t-(K-1)+i},
    zeros in front of the sequence."""
    k, t = kernel.shape[0], x.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, x.shape[1]), x.dtype), x])
    return bias + sum(padded[i:i + t] * kernel[i] for i in range(k))


def mixer(h, w: dict, hf: dict):
    """The Mamba-2 mixer on normed h [T, D] -> [T, D] (before
    ``ssm_out_multiplier``)."""
    n, t = dims(hf), h.shape[0]
    p = ((h * hf["ssm_in_multiplier"]) @ w["ssm_in"]) * mup_vector(hf)
    z, xbc, dt = jnp.split(p, [n["ssm"], n["ssm"] + n["C"]], axis=-1)
    xbc = jax.nn.silu(causal_conv(xbc, w["ssm_conv"], w["ssm_conv_bias"]))
    gn = n["G"] * n["N"]
    x = xbc[:, :n["ssm"]].reshape(t, n["H"], n["P"])
    rep = n["H"] // n["G"]
    B = jnp.repeat(xbc[:, n["ssm"]:n["ssm"] + gn].reshape(t, n["G"], n["N"]),
                   rep, axis=1)                                 # [T, H, N]
    C = jnp.repeat(xbc[:, n["ssm"] + gn:].reshape(t, n["G"], n["N"]),
                   rep, axis=1)
    dt = jax.nn.softplus(dt + w["ssm_dt_bias"])                 # [T, H]
    A = -jnp.exp(w["ssm_A_log"])

    def token(S, xs):                       # S [H, P, N]
        x_t, B_t, C_t, dt_t = xs
        S = (jnp.exp(dt_t * A)[:, None, None] * S
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return S, jnp.einsum("hpn,hn->hp", S, C_t)

    _, y = jax.lax.scan(
        token, jnp.zeros((n["H"], n["P"], n["N"]), jnp.float32),
        (x, B, C, dt))                                          # [T, H, P]
    y = y + w["ssm_D"][:, None] * x
    y = y.reshape(t, n["ssm"]) * jax.nn.silu(z)                 # gate FIRST
    y = y.reshape(t, n["G"], n["ssm"] // n["G"])
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + norm_eps(hf))
    return (y.reshape(t, n["ssm"]) * w["ssm_norm"]) @ w["ssm_out"]


def self_attention(h, w: dict, cos, sin, hf: dict):
    """Grouped-query attention on h [T, D] (already times
    ``attention_in_multiplier``) -> [T, D] (before
    ``attention_out_multiplier``)."""
    n, t = dims(hf), h.shape[0]
    q = (h @ w["wq"]).reshape(t, n["Hq"], n["hd"])
    k = ((h @ w["wk"]) * hf["key_multiplier"]).reshape(t, n["Hkv"], n["hd"])
    v = (h @ w["wv"]).reshape(t, n["Hkv"], n["hd"])
    a = attention(rope(q, cos, sin), rope(k, cos, sin), v)
    return a.reshape(t, n["Hq"] * n["hd"]) @ w["wo"]


def decoder_layer(x, w: dict, cos, sin, hf: dict):
    """One layer on one sequence x [T, D]: mixer and attention side by side
    on the same normed input, then the MLP."""
    eps = norm_eps(hf)
    h = rms_norm(x, w["attn_norm"], eps)
    x = (x + mixer(h, w, hf) * hf["ssm_out_multiplier"]
         + self_attention(h * hf["attention_in_multiplier"], w, cos, sin, hf)
         * hf["attention_out_multiplier"])
    f = rms_norm(x, w["mlp_norm"], eps)
    gate_m, down_m = hf["mlp_multipliers"]
    return x + ((jax.nn.silu((f @ w["w_gate"]) * gate_m) * (f @ w["w_up"]))
                @ w["w_down"]) * down_m


def walk(x, layer, rows: int, leaf, hf: dict):
    """The harness hands the embedded probes [B, T, D]: the embedding's
    multiplier first, then every row once, in order."""
    x = x * hf["embedding_multiplier"]
    for index in range(rows):
        x = layer(x, index)
    return x


def logits(x, final_norm, head, hf: dict):
    """x [T, D] -> [T, V'] for the output-head columns given."""
    return (rms_norm(x, final_norm, norm_eps(hf)) @ head
            ) * hf["lm_head_multiplier"]


# ---------------------------------------------------------------------------
# operations and bytes


def mixer_params(hf: dict) -> int:
    """Matmul weights of one mixer: in_proj, the conv's taps, out_proj."""
    n = dims(hf)
    return n["D"] * n["in"] + n["K"] * n["C"] + n["ssm"] * n["D"]


def mixer_vectors(hf: dict) -> int:
    """A mixer's vectors: the conv's bias, A_log, D, dt_bias, the gated
    norm's gain."""
    n = dims(hf)
    return n["C"] + 3 * n["H"] + n["ssm"]


def layer_params(hf: dict) -> int:
    """Matmul weights of one layer: mixer, attention, MLP."""
    return (mixer_params(hf) + attn_params(hf)
            + 3 * hf["hidden_size"] * hf["intermediate_size"])


def param_count(hf: dict) -> int:
    """Every weight the served model holds: layers with their vectors and two
    norm gains, table, head (unless tied), final norm."""
    return (hf["num_hidden_layers"]
            * (layer_params(hf) + mixer_vectors(hf)
               + NORMS * hf["hidden_size"]) + table_params(hf))


def token_params(hf: dict) -> int:
    """Weights one token's forward pass multiplies, all layers, the head left
    out."""
    return hf["num_hidden_layers"] * layer_params(hf)


def step_params(hf: dict, tokens: float) -> int:
    """WEIGHTS a decode step must read: all layers and the head, whatever
    ``tokens`` is. The mixers' state a step reads and writes is no weight and
    is not here (``state_bytes`` has it)."""
    return token_params(hf) + hf["hidden_size"] * hf["vocab_size"]


def state_bytes(hf: dict, slot_steps: float, element_bytes: float = 2.0,
                ) -> float:
    """Bytes the mixers must move for ``slot_steps`` (live slot, step) pairs:
    every layer's S read and written in float32, its K - 1 conv rows read and
    written in the compute dtype."""
    n = dims(hf)
    per_layer = (2 * n["H"] * n["P"] * n["N"] * 4.0
                 + 2 * (n["K"] - 1) * n["C"] * element_bytes)
    return slot_steps * hf["num_hidden_layers"] * per_layer
