"""Plain reference of the AFMoE decoder (``model_type: afmoe``; Arcee Trinity,
https://huggingface.co/arcee-ai/Trinity-Large-Preview): attention layers of
two KINDS in one stack (a window with RoPE, every fourth layer full with no
positional encoding), dense layers in front of layers with sigmoid-routed
experts and a shared expert, four norms a layer.

Straightforward ``jax.numpy`` in float32 on one sequence: no kernels, no
cache, no batching machinery. Written from the published description (the
model card's ``config.json``; ``modeling_afmoe.py`` from memory: this
repository has no network), not from the program. ``hf`` are the
configuration's published keys; D = ``hidden_size``.

  norm        N(x; w) = x * rsqrt(mean(x^2) + eps) * w     (a plain gain)
  embed       x0 = E[token] * sqrt(D)                      (mup_enabled)
  layer i     kind_i = layer_types[i]: sliding_attention, or full_attention
              where (i + 1) % global_attn_every_n_layers == 0
              h = x + N(Attn_i(N(x; w_in));  w_post_attn)
              y = h + N(Mlp_i (N(h; w_pre_mlp)); w_post_mlp)
  Attn        q = x Wq [Hq x hd], k = x Wk, v = x Wv [Hkv x hd],
              g = x Wg [Hq x hd]; no bias
              q = N(q; q_norm), k = N(k; k_norm) over hd, per head
              sliding layer: rotate-half RoPE (theta, all hd dims) on q, k;
                  key j visible to query i iff i - sliding_window < j <= i
              full layer: NO positional encoding; causal
              o = softmax(q k^T * hd^-1/2) v, grouped heads (Hq / Hkv
              queries a kv head);  out = (o * sigmoid(g)) Wo
  Mlp, i < num_dense_layers:
              down(silu(gate x) * up x), width intermediate_size
  Mlp, i >= num_dense_layers:
              s = sigmoid(x Wr) over ALL experts
              chosen = the num_experts_per_tok largest of (s + b)
                  (b = expert_bias: it selects, it does not weigh)
              w_e = s_e for e in chosen; route_norm: w_e /= (their sum +
              1e-20); w_e *= route_scale
              routed = sum over the chosen e of w_e down_e(silu(gate_e x)
              * up_e x)                        (width moe_intermediate_size)
              shared = down_s(silu(gate_s x) * up_s x)   (NO gate; width
              moe_intermediate_size x num_shared_experts)
              Mlp = shared + routed
  model       logits = N(x_L; w_f) @ lm_head (untied)

``n_group`` = ``topk_group`` = ``num_expert_groups`` = ``num_limited_groups``
= 1 in the published configurations: routing has no groups (anything else is
an error here). ``load_balance_coeff``, the bias's update rule ("SMEBU") and
the "depth-scaled" initialisation of the sandwich norms are training's and
initialisation's: no forward term.

FROM MEMORY of ``modeling_afmoe.py``, not from the row's keys (the
configuration file lists them under ``assumed``): the embedding scale under
``mup_enabled``; RoPE on the window layers ONLY; the bias inside the
selection and outside the weight; the 1e-20; the gate taken from the normed
input (the same input q, k and v are projected from). ``described_as`` says
"SWA(4096) gated; global every 4th", "sigmoid routing, SMEBU bias",
"depth-scaled sandwich norm": nothing in it contradicts these.

THE SHARE. ``expert_parallel: {size, rank}`` (no published key: the
configuration file states the deployment) says that ``num_experts`` is what
ONE of ``size`` chips holds of each layer, experts ``rank x num_experts ..``;
the router keeps its full width ``num_experts x size`` and its k. ``routed``
then sums over the chosen experts HELD here: what the absent experts would
add is left out, here as in the program, and that partial result goes on.

In the evaluation of the experts one departure, as ``qwen3_next_family``'s:
every held expert runs on every token and is multiplied by a weight that is
exactly 0 off the token's choices (eight experts at a time, each group one
static slice of the stacked leaves, so that the float32 copy the harness's
dequantisation asks for is a group's and not a row's: 14 GiB at the
published widths). Attention maps over the kv heads (scores
``[Hq / Hkv, T, T]`` float32 at a time), so that a probe longer than the
window fits.

Weight layout: ``decoder_layer`` is ONE ROW of the served ``layers`` pytree,
M = ``global_attn_every_n_layers`` consecutive expert layers (leaves ``[M,
...]``): attn_norm, attn_post_norm, mlp_norm, mlp_post_norm [M, D]; wq, wg
[M, D, Hq hd]; wk, wv [M, D, Hkv hd]; wo [M, Hq hd, D]; q_norm, k_norm [M,
hd]; moe_gate [M, D, E size]; expert_bias [M, E size]; w_gate, w_up [M, E, D,
F]; w_down [M, E, F, D]; shared_gate, shared_up [M, D, Fs]; shared_down [M,
Fs, D]. The dense prefix is NOT in the stack: its leaves are the top-level
tensors ``dense_<name>`` ``[num_dense_layers, ...]`` (the attention's names,
and w_gate, w_up [n, D, intermediate_size], w_down), which ``walk`` reads
through ``leaf`` and applies in front of the rows, after scaling the
embeddings.

WHAT THE COUNTS COUNT. The harness multiplies ``kv_bytes_per_token``,
``q_elements_per_token`` and ``attn_flops`` by token counts the CLIENT saw
(every cached token of a stream): true of a FULL layer, an overstatement of a
window layer, which reads min(context, window). So those three count the
full-attention layers alone, and the window layers' needs are
``window_bytes`` / ``window_flops`` over the program's ``window_tokens``
(each stream's context cut to the window, summed), read by the ``swa.*``
readers. ``model.decode_bw_share`` and ``paged_decode_attn_roofline`` read
low in a cell of this family by the window layers' share.

Hand arithmetic (benchmark/tests/test_afmoe_family.py) at the cut the
configuration file states (1 dense + 4 expert layers, 32 of 256 experts held,
vocabulary 25024): attention 62,914,816 a layer with its q/k norm, an expert
layer 997,995,008, the dense layer 176,173,312, tables 153,750,528:
4,321,903,872 parameters;
K/V 4 KiB a token a layer in bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.llama_family import (head_dim, norm_eps, rms_norm, rope,
                                    rope_angles)

WINDOW, FULL = "sliding_attention", "full_attention"
NORMS = 4           # [D] gains a layer: each branch's input and output
ATTN_LEAVES = ("attn_norm", "attn_post_norm", "mlp_norm", "mlp_post_norm",
               "wq", "wk", "wv", "wg", "wo", "q_norm", "k_norm")
DENSE_LEAVES = ATTN_LEAVES + ("w_gate", "w_up", "w_down")


def dims(hf: dict) -> dict:
    """The shapes the equations name, from the published keys."""
    groups = {k: hf.get(k, 1) for k in ("n_group", "topk_group",
                                        "num_expert_groups",
                                        "num_limited_groups")}
    if set(groups.values()) != {1}:
        raise ValueError(f"routing over expert groups is not described "
                         f"here: {groups}")
    ep = hf.get("expert_parallel") or {}
    kinds = tuple(hf["layer_types"])
    n, nd = hf["num_hidden_layers"], int(hf.get("num_dense_layers", 0))
    if len(kinds) != n:
        raise ValueError(f"layer_types names {len(kinds)} layers, "
                         f"num_hidden_layers is {n}")
    return {
        "D": hf["hidden_size"], "L": n, "nd": nd, "kinds": kinds,
        "M": int(hf.get("global_attn_every_n_layers", 4)),
        "Hq": hf["num_attention_heads"], "Hkv": hf["num_key_value_heads"],
        "hd": head_dim(hf), "window": int(hf.get("sliding_window") or 0),
        "F": hf["intermediate_size"], "Fm": hf["moe_intermediate_size"],
        "Fs": hf["moe_intermediate_size"] * int(
            hf.get("num_shared_experts", 1)),
        "E": hf["num_experts"], "topk": hf["num_experts_per_tok"],
        "size": int(ep.get("size", 1)), "rank": int(ep.get("rank", 0)),
        "full": sum(k == FULL for k in kinds),
        "windowed": sum(k == WINDOW for k in kinds),
    }


def rope_tables(hf: dict, n_tokens: int):
    """cos, sin for positions 0 .. n_tokens - 1 (the window layers')."""
    return rope_angles(jnp.arange(n_tokens), head_dim(hf),
                       float(hf.get("rope_theta", 10000.0)))


def attention(q, k, v, window: int):
    """Causal grouped-query attention, one kv head at a time; with
    ``window`` > 0 key j is visible to query i iff i - window < j <= i.
    q [T, Hq, hd], k/v [T, Hkv, hd]."""
    t, hq, hd = q.shape
    hkv = k.shape[1]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    visible = j <= i
    if window:
        visible &= j > i - window

    def one_kv_head(heads):
        qh, kh, vh = heads                      # [g, T, hd], [T, hd], [T, hd]
        scores = jnp.einsum("gtd,sd->gts", qh, kh) / math.sqrt(hd)
        scores = jnp.where(visible[None], scores, -jnp.inf)
        return jnp.einsum("gts,sd->gtd", jax.nn.softmax(scores, axis=-1), vh)

    out = jax.lax.map(one_kv_head, (
        q.reshape(t, hkv, hq // hkv, hd).transpose(1, 2, 0, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))    # [Hkv, g, T, hd]
    return out.transpose(2, 0, 1, 3).reshape(t, hq, hd)


def mixer(x, w: dict, cos, sin, hf: dict, kind: str):
    """x + N(Attn(N(x))) of one ``kind`` layer on one sequence x [T, D]."""
    n, eps, t = dims(hf), norm_eps(hf), x.shape[0]
    h = rms_norm(x, w["attn_norm"], eps)
    q = (h @ w["wq"]).reshape(t, n["Hq"], n["hd"])
    k = (h @ w["wk"]).reshape(t, n["Hkv"], n["hd"])
    v = (h @ w["wv"]).reshape(t, n["Hkv"], n["hd"])
    gate = (h @ w["wg"]).reshape(t, n["Hq"], n["hd"])
    q, k = rms_norm(q, w["q_norm"], eps), rms_norm(k, w["k_norm"], eps)
    if kind == WINDOW:      # a full layer has NO positional encoding
        q, k = rope(q, cos, sin), rope(k, cos, sin)
    a = attention(q, k, v, n["window"] if kind == WINDOW else 0)
    a = (a * jax.nn.sigmoid(gate)).reshape(t, n["Hq"] * n["hd"])
    return x + rms_norm(a @ w["wo"], w["attn_post_norm"], eps)


def mlp(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def routing(h, w_router, bias, hf: dict):
    """Routing weights of the experts HELD here [T, E]: sigmoid scores over
    all E x size, a token's k largest of (score + bias) weigh their own
    score (renormalised, scaled), and the columns of this rank's experts are
    what is returned."""
    n = dims(hf)
    if hf.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("the family's router is sigmoid")
    s = jax.nn.sigmoid(h @ w_router)
    _, chosen = jax.lax.top_k(s + bias, n["topk"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if hf.get("route_norm", True):
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * float(hf.get("route_scale", 1.0))
    rows = jnp.arange(h.shape[0])[:, None]
    full = jnp.zeros_like(s).at[rows, chosen].set(top)
    return full[:, n["rank"] * n["E"]:(n["rank"] + 1) * n["E"]]


EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


GROUP = 8       # held experts evaluated at a time (a divisor of E is taken)


def experts(h, w: dict, hf: dict, held=None):
    """h [T, D] -> [T, D]: the shared expert plus this share's routed sum;
    ``w`` one expert layer's leaves, or without its stacked experts where
    ``held(name, lo, hi)`` hands out experts lo .. hi - 1 of a leaf. The held
    experts a GROUP at a time, each group ONE static slice of a stacked
    leaf: no float32 copy of a layer's experts (3.4 GiB at the published
    widths), nor a copy of a layer's slice of a row's, is ever asked for,
    and the program stays short (a loop written out over 4 groups, not 32
    experts)."""
    if held is None:
        def held(name, lo, hi):
            return w[name][lo:hi]

    n_e = dims(hf)["E"]
    group = max(g for g in range(1, GROUP + 1) if n_e % g == 0)
    route = routing(h, w["moe_gate"], w["expert_bias"], hf)
    out = mlp(h, w["shared_gate"], w["shared_up"], w["shared_down"])
    for lo in range(0, n_e, group):
        w_gate, w_up, w_down = (held(name, lo, lo + group)
                                for name in EXPERT_LEAVES)
        y = (jax.nn.silu(jnp.einsum("td,edf->etf", h, w_gate))
             * jnp.einsum("td,edf->etf", h, w_up))
        y = jnp.einsum("etf,efd->etd", y, w_down)
        out = out + jnp.einsum("te,etd->td", route[:, lo:lo + group], y)
    return out


def expert_layer(x, w: dict, cos, sin, hf: dict, kind: str, held=None):
    eps = norm_eps(hf)
    x = mixer(x, w, cos, sin, hf, kind)
    out = experts(rms_norm(x, w["mlp_norm"], eps), w, hf, held)
    return x + rms_norm(out, w["mlp_post_norm"], eps)


def dense_layer(x, w: dict, cos, sin, hf: dict, kind: str):
    eps = norm_eps(hf)
    x = mixer(x, w, cos, sin, hf, kind)
    out = mlp(rms_norm(x, w["mlp_norm"], eps), w["w_gate"], w["w_up"],
              w["w_down"])
    return x + rms_norm(out, w["mlp_post_norm"], eps)


def row_kinds(hf: dict) -> tuple:
    """Kinds of the M layers of a served row: the same in every row."""
    n = dims(hf)
    rows = [n["kinds"][i:i + n["M"]] for i in range(n["nd"], n["L"], n["M"])]
    if any(r != rows[0] for r in rows) or len(rows[-1]) != n["M"]:
        raise ValueError(f"the expert layers are no whole rows of "
                         f"{n['M']} alike: {rows}")
    return rows[0]


def decoder_layer(x, w: dict, cos, sin, hf: dict):
    """ONE ROW on one sequence x [T, D]: M consecutive expert layers, their
    kinds by their place in the row."""
    for m, kind in enumerate(row_kinds(hf)):
        x = expert_layer(
            x, {name: leaf[m] for name, leaf in w.items()
                if name not in EXPERT_LEAVES}, cos, sin, hf, kind,
            held=lambda name, lo, hi, m=m: w[name][m, lo:hi])
    return x


def embed_scale(hf: dict) -> float:
    return math.sqrt(hf["hidden_size"]) if hf.get("mup_enabled") else 1.0


def walk(x, one_layer, rows: int, leaf, hf: dict):
    """The embedded probes x [B, T, D], scaled; the dense prefix, its leaves
    read one tensor at a time through ``leaf``; then every row once."""
    n = dims(hf)
    x = x * embed_scale(hf)
    if n["nd"]:
        cos, sin = rope_tables(hf, x.shape[1])
        dense = {name: leaf("dense_" + name) for name in DENSE_LEAVES}
        for i in range(n["nd"]):
            # the weights an ARGUMENT of the program, not constants in it
            x = jax.jit(lambda x, w, kind=n["kinds"][i]: jax.vmap(
                lambda s: dense_layer(s, w, cos, sin, hf, kind))(x))(
                    x, {name: a[i] for name, a in dense.items()})
    for index in range(rows):
        x = one_layer(x, index)
    return x


def logits(x, final_norm, head, hf: dict):
    """x [T, D] -> [T, V'] for the output-head columns given."""
    return rms_norm(x, final_norm, norm_eps(hf)) @ head


def cache_layers(hf: dict) -> int:
    """Every layer caches K/V: the pool's layer count."""
    return dims(hf)["L"]


# ---------------------------------------------------------------------------
# operations and bytes


def attn_params(hf: dict) -> int:
    """The five projections of one attention layer: q, its gate and o at
    Hq hd, k and v at Hkv hd."""
    n = dims(hf)
    return (3 * n["D"] * n["Hq"] * n["hd"] + 2 * n["D"] * n["Hkv"] * n["hd"])


def expert_params(hf: dict) -> int:
    """One routed expert's SwiGLU: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def dense_params(hf: dict) -> int:
    """Matmul weights of one dense layer."""
    n = dims(hf)
    return attn_params(hf) + 3 * n["D"] * n["F"]


def block_fixed_params(hf: dict) -> int:
    """Matmul weights of an expert layer outside its routed experts: the
    attention, the router at its full width, the shared expert."""
    n = dims(hf)
    return (attn_params(hf) + n["D"] * n["E"] * n["size"]
            + 3 * n["D"] * n["Fs"])


def _stack_params(hf: dict, experts_a_layer: float) -> float:
    n = dims(hf)
    return (n["nd"] * dense_params(hf) + (n["L"] - n["nd"]) * (
        block_fixed_params(hf) + experts_a_layer * expert_params(hf)))


def layer_params(hf: dict) -> float:
    """Matmul weights of one layer, as HBM holds them: the stack's mean."""
    n = dims(hf)
    return _stack_params(hf, n["E"]) / n["L"]


def table_params(hf: dict) -> int:
    d, v = hf["hidden_size"], hf["vocab_size"]
    return d * v + (0 if hf.get("tie_word_embeddings") else d * v) + d


def param_count(hf: dict) -> int:
    """Every weight the served model holds: the HELD share of the experts,
    four norm gains and the q/k norm a layer, the selection bias at the
    router's width, table, head, final norm."""
    n = dims(hf)
    vectors = NORMS * n["D"] + 2 * n["hd"]
    return int(_stack_params(hf, n["E"]) + n["L"] * vectors
               + (n["L"] - n["nd"]) * n["E"] * n["size"] + table_params(hf))


def token_params(hf: dict) -> float:
    """Weights one token's forward pass multiplies HERE, all layers: the
    attention, the dense MLPs, routers and shared experts, and the k / size
    of its k experts that are expected on this share; the head left out."""
    n = dims(hf)
    return _stack_params(hf, n["topk"] / n["size"])


def experts_touched(hf: dict, tokens: float) -> float:
    """Experts of one layer's HELD share that ``tokens`` tokens are EXPECTED
    to reach, each choosing k of all E x size uniformly and independently:
    E (1 - (1 - k / (E size))^tokens). 12.7 of 32 at 32 tokens, top-4 of
    256."""
    n = dims(hf)
    return n["E"] * (1.0 - (1.0 - n["topk"] / (n["E"] * n["size"]))
                     ** tokens)


def step_params(hf: dict, tokens: float) -> float:
    """WEIGHTS a decode step over ``tokens`` query tokens is expected to
    read: every layer outside its routed experts, the experts touched, the
    head."""
    return (_stack_params(hf, experts_touched(hf, tokens))
            + hf["hidden_size"] * hf["vocab_size"])


def kv_bytes_per_token(hf: dict, element_bytes: float) -> float:
    """K and V of one token over the FULL-attention layers (see the
    docstring: the window layers' are ``window_bytes``)."""
    n = dims(hf)
    return 2 * n["full"] * n["Hkv"] * n["hd"] * element_bytes


def q_elements_per_token(hf: dict) -> int:
    """Elements of one token's q (and of its attention output) over the
    full-attention layers."""
    n = dims(hf)
    return n["full"] * n["Hq"] * n["hd"]


def attn_flops(hf: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query token, attended token) pairs, the
    full-attention layers: 2 matmuls x 2 flops x heads x head_dim each."""
    return 4.0 * q_elements_per_token(hf) * pairs


def window_bytes(hf: dict, window_tokens: float,
                 element_bytes: float = 2.0) -> float:
    """K/V bytes the WINDOW layers' decode calls must read for
    ``window_tokens`` (stream, step) contexts cut to the window and summed:
    every window layer's K and V of each once."""
    n = dims(hf)
    return (window_tokens * 2 * n["windowed"] * n["Hkv"] * n["hd"]
            * element_bytes)


def window_flops(hf: dict, window_tokens: float) -> float:
    """QK^T and PV of the window layers over the same pairs."""
    n = dims(hf)
    return 4.0 * n["windowed"] * n["Hq"] * n["hd"] * window_tokens


def expert_bytes(hf: dict, touched: float, element_bytes: float = 2.0,
                 ) -> float:
    """Bytes the routed matmuls must read for ``touched`` (expert, layer)
    pairs that had a token: each expert's three matrices once."""
    return touched * expert_params(hf) * element_bytes
