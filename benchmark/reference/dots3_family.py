"""Plain reference of dots3-note-prev (``model_type: dots3_note``,
https://huggingface.co/dots-studio/dots3-note-prev, "288B-A17B"): the
DeepSeek-V3 decoder block with DeepSeek-V3.2's INDEXER (learned sparse
attention) on its full layers, WINDOW layers with latent attention of a shape
of their own between them, a headwise output gate on both, and ``noaux_tc``
sigmoid experts beside one shared expert.

Straightforward ``jax.numpy`` in float32 on one sequence: no kernels, no
cache, no batching machinery, nothing imported from the program. Every key of
the published config is a key of the public DeepSeek-V3 / DeepSeek-V3.2
configurations (whose modelling text is public and widely re-implemented),
the same key with ``swa_`` in front for the window layers, or one of three
small keys whose reading is stated below with the other reading beside it.
``hf`` are the configuration's published keys; D = ``hidden_size``.

  norm        N(x; w) = x * rsqrt(mean(x^2) + eps) * w    (plain gain, 1e-5)
  stack       ``layer_types`` = F F (S S S F) x 11 (F full_attention, S
              sliding_attention); layer i < first_k_dense_replace has the
              dense MLP, every other layer experts
  layer i     h = x + Attn_kind(N(x; w_in));  y = h + Ffn_i(N(h; w_post))
  latent attention, BOTH kinds (H heads, ranks ql / kl, head [nope | rope],
  value dv; full: 128 heads, 128 | 64, dv 128, ql 1024, kl 512, theta 8e7;
  window, the ``swa_`` keys: 64 heads, 192 | 64, dv 128, ql 1024, kl 1024,
  theta 5e4):
      cq = a_q N(x Wqa; w_q);   q = cq Wqb -> H heads of [nope | rope]
      q_rope = RoPE(q[rope])        (interleaved pairs: ASSUMED 1)
      x Wkva -> [c' | kr'];  c = a_kv N(c'; w_kv);  kr = RoPE(kr'): ONE rope
      key a token for all heads; [c | kr] is all a cache would hold
      [k_nope | v] = c Wkvb a head;  k = [k_nope | kr]
      o = softmax over the ALLOWED keys of q k^T (nope + rope)^-1/2;  o v
      a_q = sqrt(D / ql), a_kv = sqrt(D / kl)          (ASSUMED 2)
  allowed     window layer: 0 <= t - s < sliding_window_size (513 keys, the
              token itself counted: ASSUMED 3)
              full layer: s in S(t), the indexer's choice
  indexer     full layers only (index_n_heads 64, index_head_dim 128,
              index_topk 2048), DeepSeek-V3.2's:
      qI_j = cq Wiq[j]   (64 heads of 128, from the SAME cq)
      kI = LayerNorm(x Wik; weight, bias, eps 1e-6)    ONE key a token
      RoPE (the full layers' theta) on the FIRST 64 dims of qI_j and kI, in
      the HALVES order (pairs (i, i + 32); ASSUMED 4)
      w = x Ww * 64^-1/2 * 128^-1/2                    (64 numbers a token)
      I(t, s) = sum_j w_j ReLU(qI_j(t) . kI(s))   for s <= t
      S(t) = the min(2048, t + 1) positions of largest I(t, .); the token
      itself is NOT forced in
  gate        g = sigmoid(x Wg), Wg [D, H], no bias, from the layer's normed
              input; head j's output is g_j o_j before Wo  (ASSUMED 5)
  Ffn, dense  down(silu(gate x) * up x), width intermediate_size
  Ffn, else   s = sigmoid(x Wr) over ALL n_routed_experts x size; chosen =
              the num_experts_per_tok largest of s + e_score_correction_bias
              (``noaux_tc``; no n_group key: ONE group); w_e = s_e (the bias
              picks, the score weighs), / (their sum + 1e-20)
              (norm_topk_prob), * routed_scaling_factor (1)
              routed = sum over chosen e of w_e SwiGLU_e(x)  (width 1536)
              shared = SwiGLU_s(x), NO gate;  Ffn = routed + shared
  model       logits = N(x_L; w_f) @ lm_head (untied); rope_scaling null

ASSUMED, each in the configuration file too with its other reading:
(1) RoPE on the attention's rope dims mixes INTERLEAVED pairs (0, 1), (2,
3), ..., as the V3 text stores its weights (the other reading: halves).
(2) ``apply_mla_qkv_lora_rescale: true`` is read as the published
LongCat-Flash text's ``mla_scale_q_lora`` / ``mla_scale_kv_lora``: a_q =
sqrt(D / ql), a_kv = sqrt(D / kl) on the two normed low-rank vectors (the
other reading: 1, 1; one constant either way). (3) ``sliding_window_size``
513 counts the token itself: t - s < 513 (the other: 512 keys and the token,
t - s <= 513). (4) The indexer rotates the first 64 dims in the halves
order (V3.2's corrected text; the other reading: interleaved) and caches its
keys in bfloat16 (V3.2 stores them FP8 behind a Hadamard rotation:
orthogonal, no product changes; a kernel's precision, in no key); LayerNorm
eps 1e-6. (5) ``attention_gate_type`` / ``swa_attention_gate_type:
headwise`` is the public "Gated Attention for LLMs" text's headwise form: a
projection of its own, one sigmoid a head on the attention output (the other
reading: elementwise, [D, H dv]). The vision and audio towers and the
multi-token-prediction module that the model card mentions are in no key of
the config and are not described here.

THE SHARE. ``expert_parallel: {size, rank}`` (no published key: the
configuration file states the deployment) says that ``n_routed_experts`` is
what ONE of ``size`` chips holds of each layer; the router keeps its full
width and its k. ``routed`` sums over the chosen experts HELD here; that
partial result goes on, here as in the program.

Departures in the EVALUATION only: every held expert runs on every token
times a weight that is exactly 0 off the token's choices, GROUP experts at a
time, each group one static slice of a stacked leaf (GROUP is all 16 held
here: a float32 product at "highest" precision costs the TPU compiler ~3 s
each; a layer's experts in float32 are 1.5 GB beside 7.9 GiB of served
arguments); attention maps over the heads HEADS at a time; ``S(t)`` is
``lax.top_k``'s indices scattered into a [T, T] mask. ONE SHAPE for every
probe length: ``walk`` pads the probes behind their last token to a whole
``PROBE_PAD`` positions (every mask here is causal: a position behind a
token changes nothing at it) and cuts the result back, so that the check's
four lengths run the programs that the first compiled (the harness allows
the whole check 300 s, and each length compiled every program anew: 240-270
s at the published widths, PERF.md section 6). ``decoder_layer`` therefore
lays its RoPE tables out itself, for the positions it is given.

Weight layout: the served ``layers`` pytree holds ONE ROW A PERIOD (M
layers: M - 1 window layers closed by a full one): attn_norm, mlp_norm [M,
D]; moe_gate [M, D, E size]; expert_bias [M, E size]; shared_{gate,up}
[M, D, Fs], shared_down [M, Fs, D]; w_gate, w_up [M, E, D, F], w_down [M, E,
F, D]; the full layer's attention wq_a, q_norm, wq_b, wkv_a, kv_norm, wkv_b,
wo, wg, idx_wq [ql, 64 x 128], idx_wk [D, 128], idx_k_norm, idx_k_bias
[128], idx_w [D, 64]; the window layers' ``swa_<name>`` [M - 1, ...].
``decoder_layer`` is one such row. In front of the periods, read through
``walk``'s ``leaf``: ``dense_<name>`` [n_dense, ...] (a full layer's
attention and a dense MLP) and ``lone_<name>`` [n_lone, ...] (the full expert
layers in front of the first window layer; experts [n_lone, 1, E, ...]).

WHAT THE COUNTS COUNT. The generic readers multiply a step's attended
tokens by ``kv_bytes_per_token``, so that function counts ONLY what a step
must read of EVERY attended token: the full layers' index keys (128 elements
a full layer). The chosen rows and the window rows have functions of their
own (``select_bytes``, ``window_bytes``), as the indexer's flops
(``index_flops``). ``model.decode_bw_share`` therefore reads LOW where
contexts are long (PERF.md section 7). ``attn_flops`` likewise: the
indexer's 2 x 64 x 128 a pair a full layer.

Hand arithmetic (benchmark/tests/test_dots3_family.py) at the cut the
configuration file states (6 layers F(dense) F S S S F, 16 of 256 experts
held, vocabulary 19008): full attention 144,060,160 a layer with its norms
(indexer 9,371,904 of it), window attention 90,845,184, an expert
23,592,960, router + bias + shared 24,903,936, dense MLP 212,336,640, tables
194,647,040: 3,123,656,192 parameters.
"""

from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

FULL, WINDOW = "full_attention", "sliding_attention"
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
ATTN_LEAVES = ("wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo",
               "wg")
INDEX_LEAVES = ("idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_w")
GROUP = 16          # held experts evaluated at a time (a divisor of E)
HEADS = 4           # heads attended at a time
PROBE_PAD = 1152    # positions ``walk`` pads the probes to whole multiples of
                    # (benchmark/run.py's longest probe is 1100 + 3 tokens)
INDEX_NORM_EPS = 1e-6


def dims(hf: dict) -> dict:
    """The shapes the equations name, from the published keys."""
    if hf.get("topk_method") != "noaux_tc":
        raise ValueError(f"topk_method {hf.get('topk_method')!r} is not "
                         f"described here")
    if hf.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("the family's router is sigmoid")
    ep = hf.get("expert_parallel") or {}
    L, nd = hf["num_hidden_layers"], int(hf.get("first_k_dense_replace", 0))
    types = tuple(hf["layer_types"][:L])
    rest = types[nd:]
    nl = rest.index(WINDOW) if WINDOW in rest else len(rest)
    rest = rest[nl:]
    M = rest.index(FULL) + 1 if FULL in rest else len(rest)
    if M and (len(rest) % M or rest != rest[:M] * (len(rest) // M)):
        raise ValueError(f"{L} layers end inside a period of {M}")
    kinds = {
        FULL: {"H": hf["num_attention_heads"], "ql": hf["q_lora_rank"],
               "kl": hf["kv_lora_rank"], "nope": hf["qk_nope_head_dim"],
               "rope": hf["qk_rope_head_dim"], "dv": hf["v_head_dim"],
               "theta": float(hf.get("rope_theta", 10000.0))},
        WINDOW: {"H": hf["swa_num_attention_heads"],
                 "ql": hf["swa_q_lora_rank"], "kl": hf["swa_kv_lora_rank"],
                 "nope": hf["swa_qk_nope_head_dim"],
                 "rope": hf["swa_qk_rope_head_dim"],
                 "dv": hf["swa_v_head_dim"],
                 "theta": float(hf.get("swa_rope_theta", 10000.0))}}
    return {
        "D": hf["hidden_size"], "L": L, "nd": nd, "nl": nl, "M": M,
        "P": len(rest) // M if M else 0, "types": types,
        "full": types.count(FULL), "windowed": types.count(WINDOW),
        "window": int(hf.get("sliding_window_size") or 0),
        "kinds": kinds,
        "Hi": hf["index_n_heads"], "di": hf["index_head_dim"],
        "topk_rows": hf["index_topk"],
        "rescale": bool(hf.get("apply_mla_qkv_lora_rescale", False)),
        "F": hf["intermediate_size"], "Fm": hf["moe_intermediate_size"],
        "Fs": hf["moe_intermediate_size"] * int(
            hf.get("n_shared_experts") or 0),
        "E": hf["n_routed_experts"], "topk": hf["num_experts_per_tok"],
        "size": int(ep.get("size", 1)), "rank": int(ep.get("rank", 0)),
    }


def norm_eps(hf: dict) -> float:
    return float(hf.get("rms_norm_eps", 1e-6))


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def layer_norm(x, weight, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * weight + bias


def rope_tables(hf: dict, n_tokens: int):
    """(cos, sin), each {kind: [T, rope / 2]} for positions 0 .. n_tokens -
    1: a table a kind of layer, at the kind's own base; ``rope_scaling`` is
    null."""
    out = ({}, {})
    for kind, k in dims(hf)["kinds"].items():
        d = k["rope"]
        inv = 1.0 / (k["theta"] ** (jnp.arange(0, d, 2, dtype=jnp.float32)
                                    / d))
        ang = jnp.arange(n_tokens, dtype=jnp.float32)[:, None] * inv[None, :]
        out[0][kind], out[1][kind] = jnp.cos(ang), jnp.sin(ang)
    return out


def _tables(x, cos, sin):
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    return cos.reshape(shape), sin.reshape(shape)


def rope(x, cos, sin):
    """x [T, ..., d]; the rotation mixes the INTERLEAVED pairs (2j, 2j + 1).
    The result lists the pairs' first elements, then their second: q and k
    take the same order, their products are the rotation's."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    c, s = _tables(x, cos, sin)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def rope_halves(x, cos, sin):
    """x [T, ..., d]; the rotation mixes the pairs (j, j + d / 2)."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = _tables(x, cos, sin)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v, scale: float, allowed):
    """Attention under ``allowed [T, T]`` (query, key), HEADS heads at a
    time. q, k [T, H, dq], v [T, H, dv]."""
    t, h = q.shape[0], q.shape[1]
    group = max(g for g in range(1, HEADS + 1) if h % g == 0)

    def some_heads(heads):
        qh, kh, vh = heads                      # [g, T, d]
        scores = jnp.einsum("gtd,gsd->gts", qh, kh) * scale
        scores = jnp.where(allowed[None], scores, -jnp.inf)
        return jnp.einsum("gts,gsd->gtd", jax.nn.softmax(scores, axis=-1), vh)

    def grouped(a):                             # [T, H, d] -> [H/g, g, T, d]
        return a.transpose(1, 0, 2).reshape(h // group, group, t, a.shape[-1])

    out = jax.lax.map(some_heads, (grouped(q), grouped(k), grouped(v)))
    return out.reshape(h, t, v.shape[-1]).transpose(1, 0, 2)


def index_scores(h, cq, w: dict, cos, sin, hf: dict):
    """I [T, T] (query, key) of one full layer, float32; keys past a query
    read -inf."""
    n, t = dims(hf), h.shape[0]
    rope_dims = n["kinds"][FULL]["rope"]
    q = (cq @ w["idx_wq"]).reshape(t, n["Hi"], n["di"])
    q = jnp.concatenate([rope_halves(q[..., :rope_dims], cos, sin),
                         q[..., rope_dims:]], axis=-1)
    k = layer_norm(h @ w["idx_wk"], w["idx_k_norm"], w["idx_k_bias"],
                   INDEX_NORM_EPS)
    k = jnp.concatenate([rope_halves(k[:, :rope_dims], cos, sin),
                         k[:, rope_dims:]], axis=-1)
    weights = (h @ w["idx_w"]) * n["Hi"] ** -0.5 * n["di"] ** -0.5
    scores = jnp.einsum("tj,tjs->ts", weights, jax.nn.relu(
        jnp.einsum("tjd,sd->tjs", q, k)))
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)


def selection(scores, topk: int):
    """allowed [T, T]: key s is one of the min(topk, t + 1) best-scored of
    query t (``lax.top_k``'s indices; causal)."""
    t = scores.shape[0]
    causal = jnp.tril(jnp.ones((t, t), bool))
    if topk >= t:
        return causal
    _, chosen = jax.lax.top_k(scores, topk)
    picked = jnp.zeros((t, t), bool).at[
        jnp.arange(t)[:, None], chosen].set(True)
    return picked & causal


def window_mask(t: int, window: int):
    d = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    return (d >= 0) & (d < window)


def latent_attention(h, w: dict, cos, sin, hf: dict, kind: str):
    """Attn_kind(h) of one layer on one normed sequence h [T, D], in the
    published (decompressed) form. ``cos`` / ``sin``: ``rope_tables``'."""
    n, eps, t = dims(hf), norm_eps(hf), h.shape[0]
    k_ = n["kinds"][kind]
    H, nope, kl = k_["H"], k_["nope"], k_["kl"]
    c_k, s_k = cos[kind], sin[kind]
    a_q = (n["D"] / k_["ql"]) ** 0.5 if n["rescale"] else 1.0
    a_kv = (n["D"] / kl) ** 0.5 if n["rescale"] else 1.0
    cq = a_q * rms_norm(h @ w["wq_a"], w["q_norm"], eps)
    q = (cq @ w["wq_b"]).reshape(t, H, nope + k_["rope"])
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], c_k, s_k)],
                        axis=-1)
    ckr = h @ w["wkv_a"]
    c = a_kv * rms_norm(ckr[:, :kl], w["kv_norm"], eps)
    kr = rope(ckr[:, kl:], c_k, s_k)            # ONE key a token
    kv = (c @ w["wkv_b"]).reshape(t, H, nope + k_["dv"])
    k = jnp.concatenate([
        kv[..., :nope],
        jnp.broadcast_to(kr[:, None, :], (t, H, k_["rope"]))], axis=-1)
    if kind == FULL:
        allowed = selection(index_scores(h, cq, w, c_k, s_k, hf),
                            n["topk_rows"])
    else:
        allowed = window_mask(t, n["window"])
    o = attention(q, k, kv[..., nope:], (nope + k_["rope"]) ** -0.5, allowed)
    o = o * jax.nn.sigmoid(h @ w["wg"])[:, :, None]
    return o.reshape(t, H * k_["dv"]) @ w["wo"]


def mlp(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def routing(h, w_router, bias, hf: dict):
    """Routing weights of the experts HELD here [T, E]: sigmoid scores over
    all E x size; the k largest of score + bias are chosen, and weigh their
    own SCORE (renormalised, scaled)."""
    n = dims(hf)
    s = jax.nn.sigmoid(h @ w_router)
    _, chosen = jax.lax.top_k(s + bias, n["topk"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if hf.get("norm_topk_prob", True) and n["topk"] > 1:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * float(hf.get("routed_scaling_factor", 1.0))
    rows = jnp.arange(h.shape[0])[:, None]
    full = jnp.zeros_like(s).at[rows, chosen].set(top)
    return full[:, n["rank"] * n["E"]:(n["rank"] + 1) * n["E"]]


def experts(h, w: dict, hf: dict):
    """h [T, D] -> [T, D]: this share's routed sum plus the shared expert;
    ``w`` one layer's leaves (the experts' [E, ...]), the held experts a
    GROUP at a time, each group one static slice."""
    n = dims(hf)
    group = max(g for g in range(1, GROUP + 1) if n["E"] % g == 0)
    route = routing(h, w["moe_gate"], w["expert_bias"], hf)
    out = jnp.zeros_like(h)
    if n["Fs"]:
        out = mlp(h, w["shared_gate"], w["shared_up"], w["shared_down"])
    for lo in range(0, n["E"], group):
        w_gate, w_up, w_down = (w[name][lo:lo + group]
                                for name in EXPERT_LEAVES)
        y = (jax.nn.silu(jnp.einsum("td,edf->etf", h, w_gate))
             * jnp.einsum("td,edf->etf", h, w_up))
        y = jnp.einsum("etf,efd->etd", y, w_down)
        out = out + jnp.einsum("te,etd->td", route[:, lo:lo + group], y)
    return out


def expert_layer(x, attn: dict, norms, ffn: dict, cos, sin, hf: dict,
                 kind: str):
    """One expert layer of ``kind`` on x [T, D]: ``attn`` its attention's
    leaves, ``norms`` its two branch gains, ``ffn`` its router's, shared
    expert's and experts' ([E, ...])."""
    eps = norm_eps(hf)
    x = x + latent_attention(rms_norm(x, norms[0], eps), attn, cos, sin, hf,
                             kind)
    return x + experts(rms_norm(x, norms[1], eps), ffn, hf)


def decoder_layer(x, w: dict, cos, sin, hf: dict):
    """ONE PERIOD of the served stack on one sequence x [T, D]: its M - 1
    window layers, then the full layer that closes it. (Unrolled: a scan over
    the window layers compiles a third sooner and holds their float32
    weights whole, 6.9 GiB of temporaries at the published widths against
    2.4: topology compile, PR 51.) The tables handed in are for the probes'
    own length; x may be ``walk``'s padded probes, so they are laid out here
    for x's positions."""
    M = dims(hf)["M"]
    cos, sin = rope_tables(hf, x.shape[0])
    swa = {name[4:]: a for name, a in w.items() if name.startswith("swa_")}
    full = {name: w[name] for name in ATTN_LEAVES + INDEX_LEAVES}
    per_layer = {name: a for name, a in w.items()
                 if name not in full and not name.startswith("swa_")}
    for m in range(M):
        last = m == M - 1
        x = expert_layer(
            x, full if last else {k: a[m] for k, a in swa.items()},
            (w["attn_norm"][m], w["mlp_norm"][m]),
            {k: a[m] for k, a in per_layer.items()}, cos, sin, hf,
            FULL if last else WINDOW)
    return x


def prefix(x, leaf, hf: dict):
    """The layers in front of the periods on the probes x [B, T, D]: the
    dense layers and the LONE full expert layers, TWO programs a layer (its
    attention, then its MLP or expert block), each over the float32 leaves
    of its half alone: ``leaf(name)`` hands the whole ``[n, ...]`` tensor in
    float32, so what is live beside 7.9 GiB of served arguments is a dense
    MLP's three matrices (0.85 GB at the published widths) or a lone
    layer's stacked experts and shared expert (1.6 GB); a layer's part is
    taken INSIDE each program. ONE jitted callable a kind of program, kept
    for the process (``_prefix_programs``): the dense and the lone layers'
    attention are the same shapes, and ``walk`` hands every probe length in
    one shape, so each compiles once."""
    n = dims(hf)
    attend, dense, lone = _prefix_programs(json.dumps(hf, sort_keys=True))

    def run(f, x, pre: str, names, i: int):
        return f(x, {name: leaf(pre + name) for name in names}, i)

    attention_leaves = ("attn_norm",) + ATTN_LEAVES + INDEX_LEAVES
    block = ("mlp_norm", "moe_gate", "expert_bias") + EXPERT_LEAVES + ((
        "shared_gate", "shared_up", "shared_down") if n["Fs"] else ())
    for i in range(n["nd"]):
        x = run(attend, x, "dense_", attention_leaves, i)
        x = run(dense, x, "dense_", ("mlp_norm",) + EXPERT_LEAVES, i)
    for i in range(n["nl"]):
        x = run(attend, x, "lone_", attention_leaves, i)
        x = run(lone, x, "lone_", block, i)
    return x


@functools.lru_cache(maxsize=None)
def _prefix_programs(published: str):
    """``prefix``'s three programs for the published keys ``published`` (as
    JSON: a key the cache can hold), each over (x [B, T, D], the float32
    leaves of its half ``[n, ...]``, the layer's index among them)."""
    hf = json.loads(published)
    eps = norm_eps(hf)

    def attend(x, w, i):
        cos, sin = rope_tables(hf, x.shape[1])
        return jax.vmap(lambda s: s + latent_attention(
            rms_norm(s, w["attn_norm"][i], eps),
            {k: a[i] for k, a in w.items()}, cos, sin, hf, FULL))(x)

    def dense(x, w, i):
        return jax.vmap(lambda s: s + mlp(
            rms_norm(s, w["mlp_norm"][i], eps), w["w_gate"][i], w["w_up"][i],
            w["w_down"][i]))(x)

    def lone(x, w, i):
        layer = {k: (a[i, 0] if k in EXPERT_LEAVES else a[i])
                 for k, a in w.items()}
        return jax.vmap(lambda s: s + experts(
            rms_norm(s, layer["mlp_norm"], eps), layer, hf))(x)

    return tuple(jax.jit(f, static_argnums=2) for f in (attend, dense, lone))


def walk(x, one_layer, rows: int, leaf, hf: dict):
    """The embedded probes x [B, T, D], padded behind their last token to
    whole ``PROBE_PAD`` positions (zeros: no position in front of them sees
    them); the dense and the lone layers, their leaves read one tensor at a
    time through ``leaf``; then every period once; the probes' own T
    positions go back."""
    t = x.shape[1]
    x = jnp.pad(x, ((0, 0), (0, -t % PROBE_PAD), (0, 0)))
    x = prefix(x, leaf, hf)
    for index in range(rows):
        x = one_layer(x, index)
    return x[:, :t]


def logits(x, final_norm, head, hf: dict):
    """x [T, D] -> [T, V'] for the output-head columns given."""
    return rms_norm(x, final_norm, norm_eps(hf)) @ head


def cache_layers(hf: dict) -> int:
    """Every layer caches rows of its kind."""
    return dims(hf)["L"]


# ---------------------------------------------------------------------------
# operations and bytes


def latent_width(hf: dict, kind: str = FULL) -> int:
    """Elements of a token's cached row in a ``kind`` layer."""
    k = dims(hf)["kinds"][kind]
    return k["kl"] + k["rope"]


def index_params(hf: dict) -> int:
    """A full layer's indexer: its query and key projections and the heads'
    weights (the key's LayerNorm is vectors)."""
    n = dims(hf)
    return (n["kinds"][FULL]["ql"] * n["Hi"] * n["di"] + n["D"] * n["di"]
            + n["D"] * n["Hi"])


def attn_params(hf: dict, kind: str) -> int:
    """The matmul weights of one ``kind`` attention layer: q down and up,
    the latent's down (with the rope key) and up, o, the gate, and a full
    layer's indexer."""
    n = dims(hf)
    k = n["kinds"][kind]
    return (n["D"] * k["ql"] + k["ql"] * k["H"] * (k["nope"] + k["rope"])
            + n["D"] * latent_width(hf, kind)
            + k["kl"] * k["H"] * (k["nope"] + k["dv"])
            + k["H"] * k["dv"] * n["D"] + n["D"] * k["H"]
            + (index_params(hf) if kind == FULL else 0))


def attn_vectors(hf: dict, kind: str) -> int:
    """Gains and biases of one ``kind`` layer: two branch norms, the two
    low-rank norms, a full layer's index-key LayerNorm."""
    n = dims(hf)
    k = n["kinds"][kind]
    return (2 * n["D"] + k["ql"] + k["kl"]
            + (2 * n["di"] if kind == FULL else 0))


def expert_params(hf: dict) -> int:
    """One routed expert's SwiGLU: gate, up, down."""
    return 3 * hf["hidden_size"] * hf["moe_intermediate_size"]


def block_fixed_params(hf: dict) -> int:
    """Matmul weights of an expert block outside its routed experts: the
    router at its full width and the shared expert."""
    n = dims(hf)
    return n["D"] * n["E"] * n["size"] + 3 * n["D"] * n["Fs"]


def _stack_params(hf: dict, experts_a_layer: float) -> float:
    """Matmul weights of the stack with ``experts_a_layer`` routed experts
    counted in each expert layer."""
    n = dims(hf)
    total = 0.0
    for i, kind in enumerate(n["types"]):
        total += attn_params(hf, kind)
        total += (3 * n["D"] * n["F"] if i < n["nd"] else
                  block_fixed_params(hf)
                  + experts_a_layer * expert_params(hf))
    return total


def layer_params(hf: dict) -> float:
    """Matmul weights of one layer, as HBM holds them: the stack's mean."""
    n = dims(hf)
    return _stack_params(hf, n["E"]) / n["L"]


def table_params(hf: dict) -> int:
    d, v = hf["hidden_size"], hf["vocab_size"]
    return d * v + (0 if hf.get("tie_word_embeddings") else d * v) + d


def param_count(hf: dict) -> int:
    """Every weight the served model holds: the HELD share of the experts,
    every layer's vectors, the selection bias of every expert block, table,
    head, final norm."""
    n = dims(hf)
    vectors = sum(attn_vectors(hf, kind) for kind in n["types"])
    bias = (n["L"] - n["nd"]) * n["E"] * n["size"]
    return int(_stack_params(hf, n["E"]) + vectors + bias
               + table_params(hf))


def token_params(hf: dict) -> float:
    """Weights one token's forward pass multiplies HERE, all layers; the
    head left out."""
    n = dims(hf)
    return _stack_params(hf, n["topk"] / n["size"])


def experts_touched(hf: dict, tokens: float) -> float:
    """Experts of one layer's HELD share that ``tokens`` tokens are EXPECTED
    to reach, each choosing k of all E x size uniformly and independently:
    E (1 - (1 - k / (E size))^tokens). 10.2 of 16 at 32 tokens, top-8 of
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
    """What a decode step must read of EVERY attended token: its index key
    in each full layer (128 elements). Not its latent rows: a full layer
    reads the chosen rows alone (``select_bytes``), a window layer its
    window's (``window_bytes``)."""
    n = dims(hf)
    return n["full"] * n["di"] * element_bytes


def q_elements_per_token(hf: dict) -> int:
    """Elements of one token's index queries, all full layers, halved: the
    harness counts this number once for q and once for an output, and an
    index score is one number."""
    n = dims(hf)
    return n["full"] * n["Hi"] * n["di"] // 2


def attn_flops(hf: dict, pairs: float) -> float:
    """What EVERY (query token, attended token) pair costs: the indexer's
    dot products, 2 x 64 x 128 a full layer."""
    return index_flops(hf, pairs)


def index_flops(hf: dict, pairs: float) -> float:
    n = dims(hf)
    return 2.0 * n["full"] * n["Hi"] * n["di"] * pairs


def index_bytes(hf: dict, attended: float, tokens: float,
                element_bytes: float = 2.0) -> float:
    """Bytes the full layers' index scoring must move for ``tokens`` query
    tokens over ``attended`` cached tokens: every attended token's index
    key, and the queries (bfloat16) with their heads' weights (float32)."""
    n = dims(hf)
    return (attended * kv_bytes_per_token(hf, element_bytes)
            + tokens * n["full"] * n["Hi"] * (2.0 * n["di"] + 4.0))


def select_bytes(hf: dict, selected: float, tokens: float,
                 element_bytes: float = 2.0) -> float:
    """Bytes the full layers' attend over the CHOSEN rows must move:
    ``selected`` (stream, chosen row) pairs' latent rows, q read and o
    written in the published form (bfloat16)."""
    n = dims(hf)
    k = n["kinds"][FULL]
    return n["full"] * (
        selected * latent_width(hf, FULL) * element_bytes
        + tokens * 2.0 * k["H"] * (k["nope"] + k["rope"] + k["dv"]))


def select_flops(hf: dict, selected: float) -> float:
    """QK^T and PV over the chosen pairs in the PUBLISHED form: 2 x heads x
    (192 + 128) a full layer (the absorbed form's 2 x (576 + 512) is the
    larger count)."""
    n = dims(hf)
    k = n["kinds"][FULL]
    return 2.0 * n["full"] * k["H"] * (k["nope"] + k["rope"]
                                       + k["dv"]) * selected


def window_bytes(hf: dict, windowed: float, element_bytes: float = 2.0,
                 ) -> float:
    """Bytes the WINDOW layers' attends must read for ``windowed`` (stream,
    key inside the window) pairs: a window layer's latent row each."""
    n = dims(hf)
    return n["windowed"] * windowed * latent_width(hf, WINDOW) * element_bytes


def window_flops(hf: dict, windowed: float) -> float:
    """The same pairs in the published form: 2 x heads x (256 + 128)."""
    n = dims(hf)
    k = n["kinds"][WINDOW]
    return 2.0 * n["windowed"] * k["H"] * (k["nope"] + k["rope"]
                                          + k["dv"]) * windowed


def expert_bytes(hf: dict, touched: float, element_bytes: float = 2.0,
                 ) -> float:
    """Bytes the routed matmuls must read for ``touched`` (expert, layer)
    pairs that had a token: each expert's three matrices once."""
    return touched * expert_params(hf) * element_bytes
