"""Plain reference of the SmallThinker decoder (``model_type: smallthinker``;
PowerInfer SmallThinker-21B-A3B,
https://huggingface.co/PowerInfer/SmallThinker-21BA3B-Instruct): a router
that stands IN FRONT OF attention, ReLU-gated experts with no shared expert,
and attention layers of two KINDS in one stack (full with no positional
encoding, a window with RoPE).

Straightforward ``jax.numpy`` in float32 on one sequence: no kernels, no
cache, no batching machinery. Written from the published description (the
model card's ``config.json`` and the catalog's ``described_as``;
``modeling_smallthinker.py`` from memory: this repository has no network),
not from the program. ``hf`` are the configuration's published keys; D =
``hidden_size``.

  1 norm      h = N(x; g_in),  N(x; g) = x * rsqrt(mean(x^2) + eps) * g
  2 router    r = h W_r  (W_r [D, E], no bias): the router reads the
              ATTENTION's input. chosen = the k =
              moe_num_active_primary_experts largest r; w_e = softmax over
              THOSE k logits (moe_primary_router_apply_softmax,
              norm_topk_prob: the same number as the softmax over all E
              renormalised over the k)
  3 attention on the same h: q = h Wq [Hq x hd], k = h Wk, v = h Wv
              [Hkv x hd]; no bias, no q/k norm, no gate
              rope_layout[l] == 1: rotate-half RoPE (theta, all hd dims) on
                  q and k; 0: NO positional encoding
              sliding_window_layout[l] == 1: key j visible to query i iff
                  i - sliding_window_size < j <= i; 0: causal, every key
              o = softmax(q k^T * hd^-1/2) v, grouped heads (Hq / Hkv
              queries a kv head);  x1 = x + o Wo
  4 experts   h2 = N(x1; g_post)
              y = sum over the chosen e of w_e (relu(h2 Wgate_e) *
              (h2 Wup_e)) Wdown_e           (ReGLU, width moe_ffn_hidden_size)
              x2 = x1 + y        (no shared expert, no dense layer, no bias)
  5 model     logits = N(x_L; g_f) @ lm_head (untied)

The published lists are ``0 1 1 1`` thirteen times over: layer 4j is FULL
without positions, the three behind it WINDOW with RoPE. A layer whose two
lists differ (a window without RoPE) is not described here: an error.

ASSUMED, not settled by the row's keys (the configuration file lists them
under ``assumed``, each with the other reading): the weighing is the softmax
over the k chosen logits (the other reading, softmax over all E and then the
k largest renormalised, is the same number while ``norm_topk_prob`` is true;
false is an error here); secondary experts are switched off in the release
(no key of theirs is in the row; a truthy one is an error); the norm is
llama's plain gain; the head split is [heads, head_dim] with rotate-half
pairs (i, i + hd/2).

THE SHARE. ``expert_parallel: {size, rank}`` (no published key: the
configuration file states the deployment) says that
``moe_num_primary_experts`` is what ONE of ``size`` chips holds of each
layer, experts ``rank x E ..``; the router keeps its full width ``E x size``
and its k. ``y`` then sums over the chosen experts HELD here: what the absent
experts would add is left out, here as in the program, and that partial
result goes on.

In the evaluation of the experts one departure, as ``afmoe_family``'s: every
held expert runs on every token and is multiplied by a weight that is exactly
0 off the token's choices, ``GROUP`` experts at a time, each group one static
slice of the stacked leaves, so that the float32 copy the harness's
dequantisation asks for is a group's and not a layer's (1.5 GiB at the
published widths). Attention maps over the kv heads (scores ``[Hq / Hkv, T, T]``
float32 at a time), so that a probe longer than the window fits.

Weight layout: ``decoder_layer`` is ONE LAYER of the served ``layers``
pytree: attn_norm, mlp_norm [D]; wq [D, Hq hd]; wk, wv [D, Hkv hd]; wo [Hq hd,
D]; moe_gate [D, E size]; w_gate, w_up [E, D, F]; w_down [E, F, D]. The
harness does not tell it WHICH layer, so it returns the layer under both
kinds and ``walk`` keeps the one the layout names (``decoder_layer``).

WHAT THE COUNTS COUNT, as ``afmoe_family``'s: ``kv_bytes_per_token``,
``q_elements_per_token`` and ``attn_flops`` count the FULL layers alone (the
harness multiplies them by every cached token of a stream, an overstatement
of a window layer); the window layers' needs are ``window_bytes`` /
``window_flops`` over the program's ``window_tokens``.

Hand arithmetic (benchmark/tests/test_smallthinker_family.py) at the
published widths: a layer 9,175,040 (Wq) + 2 x 1,310,720 (Wk, Wv) +
9,175,040 (Wo) + 163,840 (router) + 5,120 (two norms) + 64 x 5,898,240 =
398,627,840; tables 2 x 151936 x 2560 = 777,912,320; the cut of 12 layers
5,561,448,960 parameters; K/V 2 KiB a token a layer in bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from reference.llama_family import head_dim, rms_norm, rope, rope_angles

NORMS = 2           # [D] gains a layer: each branch's input
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
GROUP = 8           # held experts evaluated at a time (a divisor of E is taken)


def norm_eps(hf: dict) -> float:
    return float(hf.get("rms_norm_eps", 1e-6))


def layout(hf: dict) -> tuple:
    """Per layer, whether it is a WINDOW layer with RoPE (True) or a full
    layer without positions (False)."""
    n = hf["num_hidden_layers"]
    window = [int(v) for v in hf.get("sliding_window_layout") or [0] * n]
    roped = [int(v) for v in hf.get("rope_layout") or window]
    if len(window) != n or window != roped:
        raise ValueError(
            f"sliding_window_layout {window} and rope_layout {roped} name "
            f"another kind of layer than the two described here, or not "
            f"{n} layers")
    return tuple(bool(w) for w in window)


def dims(hf: dict) -> dict:
    """The shapes the equations name, from the published keys."""
    if not hf.get("moe_primary_router_apply_softmax", True) or not hf.get(
            "norm_topk_prob", True):
        raise ValueError("routing without the softmax over the chosen "
                         "logits is not described here")
    if any(v for k, v in hf.items() if "secondary" in k):
        raise ValueError("secondary experts are not described here")
    if hf.get("rope_scaling"):
        raise ValueError("scaled RoPE is not described here")
    ep = hf.get("expert_parallel") or {}
    kinds = layout(hf)
    return {
        "D": hf["hidden_size"], "L": len(kinds), "kinds": kinds,
        "Hq": hf["num_attention_heads"], "Hkv": hf["num_key_value_heads"],
        "hd": head_dim(hf), "window": int(hf.get("sliding_window_size") or 0),
        "F": hf["moe_ffn_hidden_size"],
        "E": hf["moe_num_primary_experts"],
        "topk": hf["moe_num_active_primary_experts"],
        "size": int(ep.get("size", 1)), "rank": int(ep.get("rank", 0)),
        "full": sum(not k for k in kinds), "windowed": sum(kinds),
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


def routing(h, w_router, hf: dict):
    """Equation 2: routing weights of the experts HELD here [T, E]: a
    token's k largest logits over all E x size, softmax over those k, and
    the columns of this rank's experts are what is returned."""
    n = dims(hf)
    logits = h @ w_router
    top, chosen = jax.lax.top_k(logits, n["topk"])
    top = jax.nn.softmax(top, axis=-1)
    rows = jnp.arange(h.shape[0])[:, None]
    full = jnp.zeros_like(logits).at[rows, chosen].set(top)
    return full[:, n["rank"] * n["E"]:(n["rank"] + 1) * n["E"]]


def mixer(x, h, w: dict, cos, sin, hf: dict, windowed: bool):
    """Equation 3: x + Attn(h) Wo of one layer on one sequence x [T, D]; h
    is the normed input."""
    n, t = dims(hf), x.shape[0]
    q = (h @ w["wq"]).reshape(t, n["Hq"], n["hd"])
    k = (h @ w["wk"]).reshape(t, n["Hkv"], n["hd"])
    v = (h @ w["wv"]).reshape(t, n["Hkv"], n["hd"])
    if windowed:            # a full layer has NO positional encoding
        q, k = rope(q, cos, sin), rope(k, cos, sin)
    a = attention(q, k, v, n["window"] if windowed else 0)
    return x + a.reshape(t, n["Hq"] * n["hd"]) @ w["wo"]


def experts(h, route, hf: dict, held):
    """Equation 4's sum: h [T, D] and routing weights [T, E] -> [T, D];
    ``held(name, lo, hi)`` hands out experts lo .. hi - 1 of a leaf, a GROUP
    at a time."""
    n_e = dims(hf)["E"]
    group = max(g for g in range(1, GROUP + 1) if n_e % g == 0)
    out = jnp.zeros_like(h)
    for lo in range(0, n_e, group):
        w_gate, w_up, w_down = (held(name, lo, lo + group)
                                for name in EXPERT_LEAVES)
        y = (jax.nn.relu(jnp.einsum("td,edf->etf", h, w_gate))
             * jnp.einsum("td,edf->etf", h, w_up))
        # the routing weight on the expert's F-wide product: the down
        # projection is linear, and no [group, T, D] is formed
        y = y * route[:, lo:lo + group].T[:, :, None]
        out = out + jnp.einsum("etf,efd->td", y, w_down)
    return out


def layer(x, w: dict, cos, sin, hf: dict, windowed: bool, held=None):
    """Equations 1-4 of one layer; ``w`` its leaves, or without its stacked
    experts where ``held`` hands them out."""
    if held is None:
        def held(name, lo, hi):
            return w[name][lo:hi]

    eps = norm_eps(hf)
    h = rms_norm(x, w["attn_norm"], eps)
    route = routing(h, w["moe_gate"], hf)       # from the ATTENTION's input
    x = mixer(x, h, w, cos, sin, hf, windowed)
    return x + experts(rms_norm(x, w["mlp_norm"], eps), route, hf, held)


def decoder_layer(x, w: dict, cos, sin, hf: dict):
    """ONE LAYER of the served ``layers`` stack on one sequence x [T, D],
    under BOTH kinds: [2, T, D], the full layer's result then the window
    layer's. The harness's reader hands this function a layer's weights and
    not its index, and the stack's leading axis is the LAYER (a row of a
    period's four layers would have it copy 4 x 64 experts out of the stack
    at once, 2.8 GiB, which does not fit beside the served weights): which
    of the two a layer IS, ``walk`` knows and keeps."""
    return jnp.stack([layer(x, w, cos, sin, hf, windowed)
                      for windowed in (False, True)])


def walk(x, one_layer, rows: int, leaf, hf: dict):
    """The embedded probes x [B, T, D] through the layers in order, each
    under the kind the published lists give it."""
    kinds = dims(hf)["kinds"]
    if rows != len(kinds):
        raise ValueError(f"the served stack holds {rows} layers; the "
                         f"published keys name {len(kinds)}")
    for index, windowed in enumerate(kinds):
        x = one_layer(x, index)[:, int(windowed)]
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
    """The four projections of one attention layer: q and o at Hq hd, k and
    v at Hkv hd."""
    n = dims(hf)
    return 2 * n["D"] * n["Hq"] * n["hd"] + 2 * n["D"] * n["Hkv"] * n["hd"]


def expert_params(hf: dict) -> int:
    """One routed expert's ReGLU: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_ffn_hidden_size"]


def block_fixed_params(hf: dict) -> int:
    """Matmul weights of a layer outside its routed experts: the attention
    and the router at its full width."""
    n = dims(hf)
    return attn_params(hf) + n["D"] * n["E"] * n["size"]


def _stack_params(hf: dict, experts_a_layer: float) -> float:
    return dims(hf)["L"] * (block_fixed_params(hf)
                            + experts_a_layer * expert_params(hf))


def layer_params(hf: dict) -> int:
    """Matmul weights of one layer, as HBM holds them."""
    return block_fixed_params(hf) + dims(hf)["E"] * expert_params(hf)


def table_params(hf: dict) -> int:
    d, v = hf["hidden_size"], hf["vocab_size"]
    return d * v + (0 if hf.get("tie_word_embeddings") else d * v) + d


def param_count(hf: dict) -> int:
    """Every weight the served model holds: the HELD share of the experts,
    two norm gains a layer, table, head, final norm."""
    n = dims(hf)
    return int(n["L"] * (layer_params(hf) + NORMS * n["D"])
               + table_params(hf))


def token_params(hf: dict) -> float:
    """Weights one token's forward pass multiplies HERE, all layers: the
    attention, the router, and the k / size of its k experts that are
    expected on this share; the head left out."""
    n = dims(hf)
    return _stack_params(hf, n["topk"] / n["size"])


def experts_touched(hf: dict, tokens: float) -> float:
    """Experts of one layer's HELD share that ``tokens`` tokens are EXPECTED
    to reach, each choosing k of all E x size uniformly and independently:
    E (1 - (1 - k / (E size))^tokens). 61.3 of 64 at 32 tokens, top-6 of
    64."""
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
