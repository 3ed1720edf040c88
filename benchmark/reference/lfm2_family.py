"""Plain reference of the LFM2-MoE decoder (``model_type: lfm2_moe``; LiquidAI
LFM2-8B-A1B, https://huggingface.co/LiquidAI/LFM2-8B-A1B): a mixer that is a
GATED SHORT CONVOLUTION in most layers and grouped-query attention in the
others, by a LIST of layer kinds with no fixed period; dense layers in front
of layers with sigmoid-routed experts and no shared expert.

Straightforward ``jax.numpy`` in float32 on one sequence: no kernels, no
cache, no batching machinery. Written from the published keys (the catalog
row beside the ``model-configs`` guide; ``modeling_lfm2_moe.py`` FROM MEMORY:
this repository has no network), not from the program. ``hf`` are the
configuration's published keys; D = ``hidden_size``, K = ``conv_L_cache``.

  norm        N(x; w) = x * rsqrt(mean(x^2) + norm_eps) * w   (a plain gain)
  layer i     x += Op_i(N(x; operator_norm));  x += Ff_i(N(x; ffn_norm))
              Op_i by layer_types[i]: ``conv`` or ``full_attention``
              Ff_i: the dense SwiGLU of width intermediate_size for
              i < num_dense_layers, else the expert block
  conv        [B | C | x] = h W_in, thirds of D in that order
              u = B * x
              v[t] = w[:, 0] u[t-2] + w[:, 1] u[t-1] + w[:, 2] u[t]  a channel
                  (K = 3; zeros in front of the sequence's start; no bias:
                  conv_bias false), NO activation
              Op = (C * v) W_out
  attention   q = h Wq [Hq x hd], k = h Wk, v = h Wv [Hkv x hd]; no bias;
              hd = D / Hq (the row's ``head_dim`` is null)
              q = N(q; q_layernorm), k = N(k; k_layernorm) over hd, per head,
              eps norm_eps, BEFORE rotate-half RoPE over the whole head
              (rope_theta)
              o = causal softmax(q k^T * hd^-1/2) v, grouped heads
              Op = o Wo
  dense Ff    W2(silu(W1 h) * W3 h)
  experts     s = sigmoid(h Wr) over ALL num_experts
              chosen = the num_experts_per_tok largest of (s + expert_bias)
                  (use_expert_bias: the bias SELECTS, it does not weigh)
              w_e = s_e for e in chosen; norm_topk_prob: w_e /= (their sum +
              1e-6); w_e *= routed_scaling_factor
              Ff = sum over the chosen e of w_e W2_e(silu(W1_e h) * W3_e h)
              NO shared expert
  model       logits = N(x_L; embedding_norm) @ E^T  (the head TIED to the
              embedding table E)

FROM MEMORY of ``modeling_lfm2_moe.py``, not from the row's keys (the
configuration file lists them under ``assumed``): the TIED tables (the row has
no ``tie_word_embeddings``; the family ties, and the published "8.3B" is met
only tied: 8.34 B, untied 8.47 B); the order [B | C | x] of ``in_proj``'s
thirds; no activation anywhere in the convolution mixer; the q/k norm in
front of RoPE; the bias inside the selection and outside the weight; the
1e-6 beside the sum. ``described_as`` says "gated short convolution (L=3);
GQA 32Q/8KV", "32 experts, top-4, 0 shared; expert bias": nothing in it
contradicts these.

THE SHARE. ``expert_parallel: {size, rank}`` (no published key) says that
``num_experts`` is what ONE of ``size`` chips holds of each layer, experts
``rank x num_experts ..``; the router keeps its full width ``num_experts x
size`` and its k, and the routed sum is over the chosen experts HELD here.
Absent (the benchmark's configuration): every expert is held and the layer's
sum is whole.

In the evaluation of the experts one departure, as ``afmoe_family``'s: every
held expert runs on every token and is multiplied by a weight that is exactly
0 off the token's choices, a GROUP of experts at a time, each group one static
slice of the stacked leaves (the float32 copy the harness's dequantisation
asks for is then a group's and not a row's: 5.6 GB at the published widths).

Weight layout. The list of layers is cut into RUNS of like ROWS (``runs``):
the dense prefix is one row; behind it a row starts at every
``full_attention`` layer; consecutive rows of the same kinds are one run.
``decoder_layer`` is ONE ROW of the served ``layers`` pytree, the FIRST run
of expert layers (M layers, nc of them convolutions, na attention; leaves,
float32, ``x @ w``): op_norm, ffn_norm [M, D]; conv_in [nc, D, 3 D]; conv_w
[nc, K, D] (row i multiplies u[t - (K - 1) + i]); conv_out [nc, D, D]; wq
[na, D, Hq hd]; wk, wv [na, D, Hkv hd]; wo [na, Hq hd, D]; q_norm, k_norm
[na, hd]; moe_gate [M, D, E size]; expert_bias [M, E size]; w_gate, w_up [M,
E, D, F]; w_down [M, E, F, D]. The other runs are NOT in the stack: their
leaves are top-level tensors under a prefix, ``dense_<name>`` (w_gate, w_up
[1, n, D, intermediate_size], w_down) and ``tail<k>_<name>`` for the k-th
later run of expert layers, each ``[rows, ...]``, which ``walk`` reads whole
(the benchmark's cut has no tail; the published 24 layers have one).

Hand arithmetic of the second half (benchmark/tests/test_lfm2_family.py), at
the published widths: a convolution mixer 2048 x 6144 + 2048 x 2048 + 3 x 2048
= 16,783,360; an attention mixer 2 x 2048 x 2048 + 2 x 2048 x 512 + 2 x 64 =
10,485,888; a dense feed-forward 3 x 2048 x 7168 = 44,040,192; an expert
block 32 x 3 x 2048 x 1792 + 2048 x 32 + 32 = 352,387,104; the tied table
65536 x 2048 = 134,217,728; two norm gains a layer and the final one. Whole:
22 blocks + 2 dense + 18 convolutions + 6 attentions + table + 100,352 gains
= 8,339,930,560; the benchmark's 14 layers 4,667,077,376. K/V 2 KiB a token
an attention layer in bfloat16; a convolution layer's state 2 rows x 2048 x
2 B = 8 KiB a slot.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference.llama_family import attention, rms_norm, rope, rope_angles

CONV, FULL = "conv", "full_attention"
ROUTE_EPS = 1e-6
NORMS = 2           # [D] gains a layer: in front of the mixer, of the Ff
GROUP = 8           # held experts evaluated at a time (a divisor of E)
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
MIXER_LEAVES = {CONV: ("conv_in", "conv_w", "conv_out"),
                FULL: ("wq", "wk", "wv", "wo", "q_norm", "k_norm")}


def dims(hf: dict) -> dict:
    """The shapes the equations name, from the published keys."""
    if hf.get("conv_bias", False):
        raise NotImplementedError("lfm2_family: conv_bias true is not what "
                                  "the published configuration states and "
                                  "not written")
    ep = hf.get("expert_parallel") or {}
    kinds = tuple(hf["layer_types"])
    n = hf["num_hidden_layers"]
    if len(kinds) != n or set(kinds) - {CONV, FULL}:
        raise ValueError(f"layer_types names {len(kinds)} layers of kinds "
                         f"{sorted(set(kinds))}; num_hidden_layers is {n}")
    heads = hf["num_attention_heads"]
    return {
        "D": hf["hidden_size"], "L": n, "kinds": kinds,
        "nd": int(hf.get("num_dense_layers", 0)),
        "K": int(hf.get("conv_L_cache", 3)),
        "Hq": heads, "Hkv": hf["num_key_value_heads"],
        "hd": int(hf.get("head_dim") or hf["hidden_size"] // heads),
        "F": hf["intermediate_size"], "Fm": hf["moe_intermediate_size"],
        "E": hf["num_experts"], "topk": hf["num_experts_per_tok"],
        "bias": bool(hf.get("use_expert_bias", True)),
        "size": int(ep.get("size", 1)), "rank": int(ep.get("rank", 0)),
        "conv": kinds.count(CONV), "full": kinds.count(FULL),
    }


def norm_eps(hf: dict) -> float:
    return float(hf.get("norm_eps", 1e-5))


def runs(hf: dict) -> list:
    """The list of layers as runs of like rows: [(prefix of the served
    leaves' names, rows, a row's kinds, dense)]; "" is the ``layers``
    stack."""
    n = dims(hf)
    rows = [(n["kinds"][:n["nd"]], True)] if n["nd"] else []
    for kind in n["kinds"][n["nd"]:]:
        if kind == FULL or not rows or rows[-1][1]:
            rows.append(((), False))
        rows[-1] = (rows[-1][0] + (kind,), False)
    out: list = []
    for kinds, dense in rows:
        if out and not dense and out[-1][2:] == (kinds, False):
            out[-1] = (out[-1][0], out[-1][1] + 1, kinds, False)
            continue
        tails = sum(not r[3] for r in out)
        out.append(("dense_" if dense else f"tail{tails}_" if tails else "",
                    1, kinds, dense))
    return out


def rope_tables(hf: dict, n_tokens: int):
    """cos, sin for positions 0 .. n_tokens - 1, over the whole head."""
    return rope_angles(jnp.arange(n_tokens), dims(hf)["hd"],
                       float(hf.get("rope_theta", 1000000.0)))


def short_conv(u, taps):
    """u [T, D], taps [K, D]: out_t = sum_i taps[i] u_{t - (K-1) + i}, zeros
    in front of the sequence: the sum of K shifted products."""
    k, t = taps.shape[0], u.shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, u.shape[1]), u.dtype), u])
    return sum(padded[i:i + t] * taps[i] for i in range(k))


def conv_mixer(h, w: dict):
    """The gated short convolution on normed h [T, D]; ``w`` one layer's
    conv_in, conv_w, conv_out."""
    b, c, x = jnp.split(h @ w["conv_in"], 3, axis=-1)
    return (c * short_conv(b * x, w["conv_w"])) @ w["conv_out"]


def attention_mixer(h, w: dict, cos, sin, hf: dict):
    """Grouped-query attention on normed h [T, D], q and k normed a head in
    front of RoPE; ``w`` one layer's wq, wk, wv, wo, q_norm, k_norm."""
    n, eps, t = dims(hf), norm_eps(hf), h.shape[0]
    q = rms_norm((h @ w["wq"]).reshape(t, n["Hq"], n["hd"]), w["q_norm"], eps)
    k = rms_norm((h @ w["wk"]).reshape(t, n["Hkv"], n["hd"]), w["k_norm"],
                 eps)
    v = (h @ w["wv"]).reshape(t, n["Hkv"], n["hd"])
    a = attention(rope(q, cos, sin), rope(k, cos, sin), v)
    return a.reshape(t, n["Hq"] * n["hd"]) @ w["wo"]


def mlp(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def routing(h, w_router, bias, hf: dict):
    """Routing weights of the experts HELD here [T, E]: sigmoid scores over
    all E x size, a token's k largest of (score + bias) weigh their own
    score (renormalised, scaled), and the columns of this rank's experts are
    what is returned."""
    n = dims(hf)
    s = jax.nn.sigmoid(h @ w_router)
    _, chosen = jax.lax.top_k(s if bias is None else s + bias, n["topk"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if hf.get("norm_topk_prob", True):
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + ROUTE_EPS)
    top = top * float(hf.get("routed_scaling_factor", 1.0))
    rows = jnp.arange(h.shape[0])[:, None]
    full = jnp.zeros_like(s).at[rows, chosen].set(top)
    return full[:, n["rank"] * n["E"]:(n["rank"] + 1) * n["E"]]


def experts(h, w: dict, hf: dict, held):
    """h [T, D] -> [T, D]: this share's routed sum, no shared expert; ``w``
    one expert block's moe_gate and expert_bias, ``held(name, lo, hi)``
    experts lo .. hi - 1 of a stacked leaf, a GROUP at a time."""
    n_e = dims(hf)["E"]
    group = max(g for g in range(1, GROUP + 1) if n_e % g == 0)
    route = routing(h, w["moe_gate"], w.get("expert_bias"), hf)
    out = jnp.zeros_like(h)
    for lo in range(0, n_e, group):
        w_gate, w_up, w_down = (held(name, lo, lo + group)
                                for name in EXPERT_LEAVES)
        y = (jax.nn.silu(jnp.einsum("td,edf->etf", h, w_gate))
             * jnp.einsum("td,edf->etf", h, w_up))
        y = jnp.einsum("etf,efd->etd", y, w_down)
        out = out + jnp.einsum("te,etd->td", route[:, lo:lo + group], y)
    return out


def row(x, w: dict, cos, sin, hf: dict, kinds: tuple, dense: bool):
    """ONE ROW on one sequence x [T, D]: ``kinds`` consecutive layers, each a
    mixer and a feed-forward; ``w`` the row's leaves, a mixer's counted over
    the row's layers of its kind, the others over all of them."""
    eps = norm_eps(hf)
    seen = {CONV: 0, FULL: 0}
    for m, kind in enumerate(kinds):
        j = seen[kind]
        seen[kind] += 1
        own = {name: w[name][j] for name in MIXER_LEAVES[kind]}
        h = rms_norm(x, w["op_norm"][m], eps)
        x = x + (conv_mixer(h, own) if kind == CONV
                 else attention_mixer(h, own, cos, sin, hf))
        h = rms_norm(x, w["ffn_norm"][m], eps)
        if dense:
            x = x + mlp(h, w["w_gate"][m], w["w_up"][m], w["w_down"][m])
        else:
            block = {name: w[name][m] for name in ("moe_gate", "expert_bias")
                     if name in w}
            x = x + experts(h, block, hf,
                            lambda name, lo, hi, m=m: w[name][m, lo:hi])
    return x


def decoder_layer(x, w: dict, cos, sin, hf: dict):
    """ONE ROW of the served ``layers`` stack (the first run of expert
    layers) on one sequence x [T, D]."""
    _, _, kinds, dense = next(r for r in runs(hf) if not r[0])
    return row(x, w, cos, sin, hf, kinds, dense)


def run_leaf_names(hf: dict, kinds: tuple, dense: bool) -> list:
    names = ["op_norm", "ffn_norm"]
    for kind in (CONV, FULL):
        if kind in kinds:
            names += MIXER_LEAVES[kind]
    if dense:
        return names + list(EXPERT_LEAVES)
    return names + ["moe_gate", *EXPERT_LEAVES] + (
        ["expert_bias"] if dims(hf)["bias"] else [])


def walk(x, one_layer, rows: int, leaf, hf: dict):
    """The embedded probes x [B, T, D] through the runs in order: the rows
    of the ``layers`` stack through ``one_layer``, every other run's leaves
    read one tensor at a time through ``leaf``."""
    cos, sin = rope_tables(hf, x.shape[1])
    for prefix, n_rows, kinds, dense in runs(hf):
        if not prefix:
            if n_rows != rows:
                raise ValueError(f"the served stack holds {rows} rows; the "
                                 f"published keys say {n_rows}")
            for index in range(rows):
                x = one_layer(x, index)
            continue
        group = {name: leaf(prefix + name)
                 for name in run_leaf_names(hf, kinds, dense)}
        for r in range(n_rows):
            # the weights an ARGUMENT of the program, not constants in it
            x = jax.jit(lambda x, w, kinds=kinds, dense=dense: jax.vmap(
                lambda s: row(s, w, cos, sin, hf, kinds, dense))(x))(
                    x, {name: a[r] for name, a in group.items()})
    return x


def logits(x, final_norm, head, hf: dict):
    """x [T, D] -> [T, V'] for the output-head columns given (the harness
    hands the embedding's rows where the tables are tied)."""
    return rms_norm(x, final_norm, norm_eps(hf)) @ head


def cache_layers(hf: dict) -> int:
    """K/V is cached by the attention layers alone."""
    return dims(hf)["full"]


# ---------------------------------------------------------------------------
# operations and bytes


def conv_params(hf: dict) -> int:
    """One convolution mixer: in_proj, the taps, out_proj."""
    n = dims(hf)
    return n["D"] * 3 * n["D"] + n["K"] * n["D"] + n["D"] * n["D"]


def attn_params(hf: dict) -> int:
    """The four projections of one attention mixer."""
    n = dims(hf)
    return 2 * n["D"] * n["Hq"] * n["hd"] + 2 * n["D"] * n["Hkv"] * n["hd"]


def dense_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def expert_params(hf: dict) -> int:
    """One routed expert's SwiGLU: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def block_fixed_params(hf: dict) -> int:
    """An expert block outside its experts: the router at its full width
    and the selection bias."""
    n = dims(hf)
    width = n["E"] * n["size"]
    return n["D"] * width + (width if n["bias"] else 0)


def _stack_params(hf: dict, experts_a_block: float) -> float:
    """Matmul weights (and taps, and bias) of the whole stack with
    ``experts_a_block`` experts counted in each expert block."""
    n = dims(hf)
    return (n["conv"] * conv_params(hf) + n["full"] * attn_params(hf)
            + n["nd"] * dense_params(hf)
            + (n["L"] - n["nd"]) * (block_fixed_params(hf)
                                    + experts_a_block * expert_params(hf)))


def layer_params(hf: dict) -> float:
    """Matmul weights of one layer, as HBM holds them: the stack's mean (a
    convolution or an attention mixer, a dense feed-forward or a router and
    the experts HELD here)."""
    n = dims(hf)
    return _stack_params(hf, n["E"]) / n["L"]


def table_params(hf: dict) -> int:
    """The embedding table, the head where it is not tied (the family ties),
    the final norm."""
    d, v = hf["hidden_size"], hf["vocab_size"]
    return d * v + (0 if hf.get("tie_word_embeddings", True) else d * v) + d


def param_count(hf: dict) -> int:
    """Every weight the served model holds: the HELD share of the experts,
    every norm gain (two a layer, two a head size an attention layer),
    table, final norm."""
    n = dims(hf)
    return int(_stack_params(hf, n["E"]) + n["L"] * NORMS * n["D"]
               + n["full"] * 2 * n["hd"] + table_params(hf))


def token_params(hf: dict) -> float:
    """Weights one token's forward pass multiplies HERE, all layers: the
    mixers, the dense layers, the routers and the k / size of its k experts
    that are expected on this share; the head left out."""
    n = dims(hf)
    return _stack_params(hf, n["topk"] / n["size"])


def experts_touched(hf: dict, tokens: float) -> float:
    """Experts of one block's HELD share that ``tokens`` tokens are EXPECTED
    to reach, each choosing k of all E x size uniformly and independently:
    E (1 - (1 - k / (E size))^tokens). 32.0 of 32 at 128 tokens, top-4 of
    32; 11.1 at 3."""
    n = dims(hf)
    return n["E"] * (1.0 - (1.0 - n["topk"] / (n["E"] * n["size"]))
                     ** tokens)


def step_params(hf: dict, tokens: float) -> float:
    """WEIGHTS a decode step over ``tokens`` query tokens is expected to
    read: the mixers, the dense layers, the routers, the experts touched (ALL
    of a block's at the batches this family is served with), the head. The
    convolution rows a step reads and writes are no weight and are not here
    (``state_bytes`` has them)."""
    return (_stack_params(hf, experts_touched(hf, tokens))
            + hf["hidden_size"] * hf["vocab_size"])


def kv_bytes_per_token(hf: dict, element_bytes: float) -> float:
    """K and V of one token over the attention layers (the model's own heads:
    that two of them share a pool row moves no byte)."""
    n = dims(hf)
    return 2 * n["full"] * n["Hkv"] * n["hd"] * element_bytes


def q_elements_per_token(hf: dict) -> int:
    """Elements of one token's q (and of its attention output) over the
    attention layers."""
    n = dims(hf)
    return n["full"] * n["Hq"] * n["hd"]


def attn_flops(hf: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query token, attended token) pairs, the
    attention layers: 2 matmuls x 2 flops x heads x head_dim each."""
    return 4.0 * q_elements_per_token(hf) * pairs


def expert_bytes(hf: dict, touched: float, element_bytes: float = 2.0,
                 ) -> float:
    """Bytes the routed matmuls must read for ``touched`` (expert, block)
    pairs that had a token: each expert's three matrices once."""
    return touched * expert_params(hf) * element_bytes


def state_bytes(hf: dict, slot_steps: float, element_bytes: float = 2.0,
                ) -> float:
    """Bytes the convolution layers must move for ``slot_steps`` (live slot,
    step) pairs: every layer's K - 1 rows read and written in the compute
    dtype."""
    n = dims(hf)
    return slot_steps * n["conv"] * 2 * (n["K"] - 1) * n["D"] * element_bytes
