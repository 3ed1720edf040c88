"""Plain reference of the DeepSeek-V3 decoder block as SKT's A.X-K1 publishes
it (``model_type: axk1``, https://huggingface.co/skt/A.X-K1): latent attention
(MLA) in every layer, ``first_k_dense_replace`` dense layers in front of
layers with group-limited sigmoid-routed experts beside one shared expert.

Straightforward ``jax.numpy`` in float32 on one sequence: no kernels, no
cache, no batching machinery, nothing imported from the program. Written from
the DeepSeek-V3 modelling text (``modeling_deepseek.py``, public and widely
re-implemented; every key of the A.X-K1 config but ``topk_method: "none"`` is
a key of the published DeepSeek-V3 configuration), not from the program.
``hf`` are the configuration's published keys; D = ``hidden_size``, H =
``num_attention_heads``.

  norm        N(x; w) = x * rsqrt(mean(x^2) + eps) * w          (plain gain)
  layer i     h = x + Attn_i(N(x; w_in));  y = h + Ffn_i(N(h; w_post))
  queries     cq = N(x Wqa; w_q)                    [q_lora_rank]
              q  = cq Wqb -> H heads of [nope | rope] = [128 | 64]
              q_rope = RoPE(q[rope])
  latent      x Wkva -> [c | kr] = [kv_lora_rank | qk_rope_head_dim]
              c = N(c; w_kv);  kr = RoPE(kr): ONE rope key a token, shared by
              all H heads. [c | RoPE(kr)] is all a cache would hold (576)
  attention   the PUBLISHED, decompressed form:
              [k_nope | v] = c Wkvb -> H heads of [128 | v_head_dim]
              k = [k_nope | kr];  o = softmax(q k^T * s + causal) v
              Attn = concat_heads(o) Wo                 (H v_head_dim -> D)
  scale       s = (nope + rope)^-1/2 * m^2,  m = 0.1 mscale_all_dim ln(factor)
              + 1  (YaRN's attention factor, SQUARED, on the scores: the V3
              text's ``softmax_scale * mscale * mscale``); cos and sin carry
              m(mscale) / m(mscale_all_dim), 1 in this configuration
  RoPE        YaRN NTK-by-parts over the rope dims: inv_freq_j = theta^(-2j /
              rope); low / high = floor / ceil of rope ln(orig / (beta 2 pi))
              / (2 ln theta) for beta_fast / beta_slow; ramp_j = clip((j -
              low) / (high - low), 0, 1); inv_freq = inv_freq / factor * ramp
              + inv_freq * (1 - ramp). The pairs a rotation mixes are
              INTERLEAVED, (0, 1), (2, 3), ..., as the V3 text's weights
              store them (ASSUMED: the A.X-K1 config has no
              ``rope_interleave`` key; the V3 text interleaves)
  Ffn, i < first_k_dense_replace:
              down(silu(gate x) * up x), width intermediate_size
  Ffn, else   s = sigmoid(x Wr) over ALL n_routed_experts x size
              the experts lie in n_group equal groups; a group's score is the
              sum of its two largest s; the topk_group best groups are kept,
              the others' s read 0 IN THE SELECTION
              chosen = the num_experts_per_tok largest of what is kept
              w_e = s_e for e in chosen; norm_topk_prob: w_e /= (their sum +
              1e-20); w_e *= routed_scaling_factor
              routed = sum over the chosen e of w_e down_e(silu(gate_e x) *
              up_e x)                          (width moe_intermediate_size)
              shared = down_s(silu(gate_s x) * up_s x)   (NO gate; width
              moe_intermediate_size x n_shared_experts)
              Ffn = routed + shared
  model       logits = N(x_L; w_f) @ lm_head (untied)

ASSUMED, each marked in the configuration file too: (1) ``topk_method:
"none"`` names no branch of the V3 text's gate (``noaux_tc``; V2: ``greedy``,
``group_limited_greedy``). It is read from the keys the config DOES state:
sigmoid scores, ``n_group`` 8, ``topk_group`` 4: the group-limited selection
above, WITHOUT ``noaux_tc``'s ``e_score_correction_bias`` (the one leaf that
method adds; ``param_count`` holds none). The other reading, a plain top-8
over the 192 with ``n_group`` / ``topk_group`` dead keys copied from V3, is
this file with ``n_group`` = ``topk_group`` = 1. (2) The interleaved RoPE
pairs, above. ``seq_aux`` and ``ep_size`` (1: the checkpoint's own) are
training's and the loader's: no forward term.

THE SHARE. ``expert_parallel: {size, rank}`` (no published key: the
configuration file states the deployment) says that ``n_routed_experts`` is
what ONE of ``size`` chips holds of each layer, experts ``rank x
n_routed_experts ..``; the router keeps its full width ``n_routed_experts x
size``, its groups and its k. ``routed`` then sums over the chosen experts
HELD here: what the absent experts would add is left out, here as in the
program, and that partial result goes on.

One departure in the evaluation, as the other sparse families': every held
expert runs on every token and is multiplied by a weight that is exactly 0 off
the token's choices, GROUP (4) experts at a time, each group ONE static slice
of a stacked leaf, so that the float32 copy the harness's dequantisation asks
for is a group's (0.7 GiB at the published widths) and not a layer's (2.1
GiB). Attention maps over the heads four at a time (scores ``[4, T, T]``
float32), so that a probe past 4096 tokens fits.

Weight layout: ``decoder_layer`` is ONE expert layer of the served ``layers``
pytree: attn_norm, mlp_norm [D]; wq_a [D, q_lora_rank]; q_norm [q_lora_rank];
wq_b [q_lora_rank, H (nope + rope)]; wkv_a [D, kv_lora_rank + rope]; kv_norm
[kv_lora_rank]; wkv_b [kv_lora_rank, H (nope + v)]; wo [H v, D]; moe_gate [D,
E size]; w_gate, w_up [1, E, D, F]; w_down [1, E, F, D]; shared_gate,
shared_up [D, Fs]; shared_down [Fs, D]. The dense prefix is NOT in the stack:
its leaves are the top-level tensors ``dense_<name>`` ``[first_k_dense_replace,
...]`` (the attention's names, and w_gate, w_up [n, D, intermediate_size],
w_down), which ``walk`` reads through ``leaf`` and applies in front.

WHAT THE COUNTS COUNT. ``kv_bytes_per_token`` is the latent row's 576
elements a layer, whatever lanes a pool pads it to; ``attn_flops`` is the
PUBLISHED (decompressed) form's, 2 (192 + 128) flops a head a pair: the
smaller of the two forms' counts (the absorbed form multiplies 2 (576 + 512)),
so that no implementation reads over 100% of a roofline; ``q_elements_per_
token`` likewise the published q and o (192 and 128 a head: their mean, since
the harness counts it twice).

Hand arithmetic (benchmark/tests/test_deepseek_family.py) at the cut the
configuration file states (1 dense + 6 expert layers, 12 of 192 experts held,
vocabulary 20480): attention 101,124,096 a layer with its two low-rank norms,
an expert layer 675,037,184, the dense layer 497,500,160, tables 293,608,448:
4,841,331,712 parameters; the cache 1152 B a token a layer in bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

NORMS = 2           # [D] gains a layer: each branch's input
ATTN_LEAVES = ("attn_norm", "mlp_norm", "wq_a", "q_norm", "wq_b", "wkv_a",
               "kv_norm", "wkv_b", "wo")
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
GROUP = 4           # held experts evaluated at a time (a divisor of E)
HEADS = 4           # heads attended at a time


def dims(hf: dict) -> dict:
    """The shapes the equations name, from the published keys."""
    if hf.get("topk_method", "none") != "none":
        raise ValueError(f"topk_method {hf['topk_method']!r} is not "
                         f"described here")
    if hf.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("the family's router is sigmoid")
    if int(hf.get("moe_layer_freq", 1)) != 1:
        raise ValueError("moe_layer_freq other than 1 is not described here")
    ep = hf.get("expert_parallel") or {}
    return {
        "D": hf["hidden_size"], "L": hf["num_hidden_layers"],
        "nd": int(hf.get("first_k_dense_replace", 0)),
        "H": hf["num_attention_heads"],
        "ql": hf["q_lora_rank"], "kl": hf["kv_lora_rank"],
        "nope": hf["qk_nope_head_dim"], "rope": hf["qk_rope_head_dim"],
        "dv": hf["v_head_dim"],
        "F": hf["intermediate_size"], "Fm": hf["moe_intermediate_size"],
        "Fs": hf["moe_intermediate_size"] * int(
            hf.get("n_shared_experts") or 0),
        "E": hf["n_routed_experts"], "topk": hf["num_experts_per_tok"],
        "groups": int(hf.get("n_group", 1)),
        "kept": int(hf.get("topk_group", 1)),
        "size": int(ep.get("size", 1)), "rank": int(ep.get("rank", 0)),
    }


def norm_eps(hf: dict) -> float:
    return float(hf.get("rms_norm_eps", 1e-6))


def rms_norm(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(hf: dict) -> float:
    """(nope + rope)^-1/2, times YaRN's attention factor squared."""
    n = dims(hf)
    scale = (n["nope"] + n["rope"]) ** -0.5
    sc = hf.get("rope_scaling") or {}
    if sc.get("mscale_all_dim"):
        m = yarn_mscale(float(sc["factor"]), float(sc["mscale_all_dim"]))
        scale *= m * m
    return scale


def rope_tables(hf: dict, n_tokens: int):
    """cos, sin [T, rope / 2] for positions 0 .. n_tokens - 1: YaRN's
    NTK-by-parts frequencies where ``rope_scaling`` says yarn."""
    d = dims(hf)["rope"]
    theta = float(hf.get("rope_theta", 10000.0))
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    sc = hf.get("rope_scaling") or {}
    table_scale = 1.0
    if sc.get("type", sc.get("rope_type")) == "yarn":
        factor = float(sc["factor"])
        orig = float(sc.get("original_max_position_embeddings", 4096))

        def correction(rotations: float) -> float:
            return d * math.log(orig / (rotations * 2 * math.pi)) / (
                2 * math.log(theta))

        low = max(math.floor(correction(float(sc.get("beta_fast", 32)))), 0)
        high = min(math.ceil(correction(float(sc.get("beta_slow", 1)))),
                   d - 1)
        if low == high:
            high += 0.001
        ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                        / (high - low), 0.0, 1.0)
        inv = inv / factor * ramp + inv * (1.0 - ramp)
        table_scale = (yarn_mscale(factor, float(sc.get("mscale", 1)))
                       / yarn_mscale(factor,
                                     float(sc.get("mscale_all_dim", 0))))
    ang = jnp.arange(n_tokens, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang) * table_scale, jnp.sin(ang) * table_scale


def rope(x, cos, sin):
    """x [T, ..., d]; the rotation mixes the INTERLEAVED pairs (2j, 2j + 1).
    The result lists the first elements of the pairs, then the second (the
    V3 text's order): q and k take the same order, their products are the
    rotation's."""
    x1, x2 = x[..., 0::2], x[..., 1::2]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    c, s = cos.reshape(shape), sin.reshape(shape)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v, scale: float):
    """Causal attention, HEADS heads at a time. q, k [T, H, dq], v [T, H,
    dv]."""
    t, h = q.shape[0], q.shape[1]
    causal = jnp.tril(jnp.ones((t, t), bool))
    group = max(g for g in range(1, HEADS + 1) if h % g == 0)

    def some_heads(heads):
        qh, kh, vh = heads                      # [g, T, d]
        scores = jnp.einsum("gtd,gsd->gts", qh, kh) * scale
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("gts,gsd->gtd", jax.nn.softmax(scores, axis=-1), vh)

    def grouped(a):                             # [T, H, d] -> [H/g, g, T, d]
        return a.transpose(1, 0, 2).reshape(h // group, group, t, a.shape[-1])

    out = jax.lax.map(some_heads, (grouped(q), grouped(k), grouped(v)))
    return out.reshape(h, t, v.shape[-1]).transpose(1, 0, 2)


def latent_attention(h, w: dict, cos, sin, hf: dict):
    """Attn(h) of one layer on one normed sequence h [T, D], in the
    published (decompressed) form."""
    n, eps, t = dims(hf), norm_eps(hf), h.shape[0]
    cq = rms_norm(h @ w["wq_a"], w["q_norm"], eps)
    q = (cq @ w["wq_b"]).reshape(t, n["H"], n["nope"] + n["rope"])
    q = jnp.concatenate([q[..., :n["nope"]],
                         rope(q[..., n["nope"]:], cos, sin)], axis=-1)
    ckr = h @ w["wkv_a"]
    c = rms_norm(ckr[:, :n["kl"]], w["kv_norm"], eps)
    kr = rope(ckr[:, n["kl"]:], cos, sin)       # ONE key a token
    kv = (c @ w["wkv_b"]).reshape(t, n["H"], n["nope"] + n["dv"])
    k = jnp.concatenate([
        kv[..., :n["nope"]],
        jnp.broadcast_to(kr[:, None, :], (t, n["H"], n["rope"]))], axis=-1)
    o = attention(q, k, kv[..., n["nope"]:], softmax_scale(hf))
    return o.reshape(t, n["H"] * n["dv"]) @ w["wo"]


def mlp(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def routing(h, w_router, hf: dict):
    """Routing weights of the experts HELD here [T, E]: sigmoid scores over
    all E x size, the selection limited to the best groups, a token's k
    choices weigh their own score (renormalised, scaled); the columns of
    this rank's experts are what is returned."""
    n = dims(hf)
    s = jax.nn.sigmoid(h @ w_router)
    choice = s
    if n["groups"] > 1:
        grouped = s.reshape(s.shape[0], n["groups"], -1)
        best = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(best, n["kept"])
        kept = jnp.zeros(best.shape, bool).at[
            jnp.arange(s.shape[0])[:, None], keep].set(True)
        choice = jnp.where(kept[:, :, None], grouped, 0.0).reshape(s.shape)
    _, chosen = jax.lax.top_k(choice, n["topk"])
    top = jnp.take_along_axis(s, chosen, axis=-1)
    if hf.get("norm_topk_prob", True) and n["topk"] > 1:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    top = top * float(hf.get("routed_scaling_factor", 1.0))
    rows = jnp.arange(h.shape[0])[:, None]
    full = jnp.zeros_like(s).at[rows, chosen].set(top)
    return full[:, n["rank"] * n["E"]:(n["rank"] + 1) * n["E"]]


def experts(h, w: dict, hf: dict):
    """h [T, D] -> [T, D]: this share's routed sum plus the shared expert;
    the held experts a GROUP at a time, each group ONE static slice of a
    stacked leaf ``[1, E, ...]``."""
    n = dims(hf)
    group = max(g for g in range(1, GROUP + 1) if n["E"] % g == 0)
    route = routing(h, w["moe_gate"], hf)
    out = jnp.zeros_like(h)
    if n["Fs"]:
        out = mlp(h, w["shared_gate"], w["shared_up"], w["shared_down"])
    for lo in range(0, n["E"], group):
        w_gate, w_up, w_down = (w[name][0, lo:lo + group]
                                for name in EXPERT_LEAVES)
        y = (jax.nn.silu(jnp.einsum("td,edf->etf", h, w_gate))
             * jnp.einsum("td,edf->etf", h, w_up))
        y = jnp.einsum("etf,efd->etd", y, w_down)
        out = out + jnp.einsum("te,etd->td", route[:, lo:lo + group], y)
    return out


def decoder_layer(x, w: dict, cos, sin, hf: dict):
    """ONE expert layer on one sequence x [T, D]."""
    eps = norm_eps(hf)
    x = x + latent_attention(rms_norm(x, w["attn_norm"], eps), w, cos, sin,
                             hf)
    return x + experts(rms_norm(x, w["mlp_norm"], eps), w, hf)


def dense_prefix(x, leaf, hf: dict):
    """The dense layers on the probes x [B, T, D], ONE float32 leaf on the
    device at a time: at the published widths a dense layer is 2 GB in
    float32 (its three MLP matrices 0.5 GB each) beside 10.7 GiB of served
    weights, so the attention's leaves come and go first, then gate, up and
    down one after the other. ``leaf(name)`` is the whole ``[n, ...]``
    tensor; a layer's part is taken INSIDE each program, its weights an
    argument and not constants."""
    n = dims(hf)
    eps = norm_eps(hf)
    cos, sin = rope_tables(hf, x.shape[1])
    for i in range(n["nd"]):
        def attend(x, w, i=i):
            return jax.vmap(lambda s: s + latent_attention(
                rms_norm(s, w["attn_norm"][i], eps),
                {k: a[i] for k, a in w.items()}, cos, sin, hf))(x)

        x = jax.jit(attend)(x, {name: leaf("dense_" + name)
                                for name in ATTN_LEAVES if name != "mlp_norm"})
        h = jax.jit(lambda x, g, i=i: rms_norm(x, g[i], eps))(
            x, leaf("dense_mlp_norm"))
        gate = jax.jit(lambda h, w, i=i: jax.nn.silu(h @ w[i]))(
            h, leaf("dense_w_gate"))
        up = jax.jit(lambda h, w, i=i: h @ w[i])(h, leaf("dense_w_up"))
        x = jax.jit(lambda x, gate, up, w, i=i: x + (gate * up) @ w[i])(
            x, gate, up, leaf("dense_w_down"))
    return x


def walk(x, one_layer, rows: int, leaf, hf: dict):
    """The embedded probes x [B, T, D]; the dense prefix, its leaves read one
    tensor at a time through ``leaf``; then every expert layer once."""
    x = dense_prefix(x, leaf, hf)
    for index in range(rows):
        x = one_layer(x, index)
    return x


def logits(x, final_norm, head, hf: dict):
    """x [T, D] -> [T, V'] for the output-head columns given."""
    return rms_norm(x, final_norm, norm_eps(hf)) @ head


def cache_layers(hf: dict) -> int:
    """Every layer caches its latent rows: the pool's layer count."""
    return dims(hf)["L"]


# ---------------------------------------------------------------------------
# operations and bytes


def latent_width(hf: dict) -> int:
    """Elements of a token's cached row: the latent and the rope key."""
    n = dims(hf)
    return n["kl"] + n["rope"]


def attn_params(hf: dict) -> int:
    """The five projections of one attention layer: q down and up, the
    latent's down (with the rope key) and up, o."""
    n = dims(hf)
    return (n["D"] * n["ql"] + n["ql"] * n["H"] * (n["nope"] + n["rope"])
            + n["D"] * latent_width(hf)
            + n["kl"] * n["H"] * (n["nope"] + n["dv"])
            + n["H"] * n["dv"] * n["D"])


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
    two norm gains and the two low-rank norms a layer, table, head, final
    norm. NO selection bias (``topk_method: "none"``)."""
    n = dims(hf)
    vectors = NORMS * n["D"] + n["ql"] + n["kl"]
    return int(_stack_params(hf, n["E"]) + n["L"] * vectors
               + table_params(hf))


def token_params(hf: dict) -> float:
    """Weights one token's forward pass multiplies HERE, all layers: the
    attention, the dense MLPs, routers and shared experts, and the k / size
    of its k experts that are expected on this share; the head left out."""
    n = dims(hf)
    return _stack_params(hf, n["topk"] / n["size"])


def experts_touched(hf: dict, tokens: float) -> float:
    """Experts of one layer's HELD share that ``tokens`` tokens are EXPECTED
    to reach, each choosing k of all E x size uniformly and independently
    (the group limit moves no expectation under that): E (1 - (1 - k / (E
    size))^tokens). 8.9 of 12 at 32 tokens, top-8 of 192."""
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
    """One token's latent row over all layers: 576 elements a layer."""
    return dims(hf)["L"] * latent_width(hf) * element_bytes


def q_elements_per_token(hf: dict) -> int:
    """Elements of one token's q and of its attention output in the
    published form, all layers: their MEAN a head (192 and 128: the harness
    counts this number once for q and once for the output)."""
    n = dims(hf)
    return n["L"] * n["H"] * (n["nope"] + n["rope"] + n["dv"]) // 2


def attn_flops(hf: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query token, attended token) pairs, all
    layers, in the PUBLISHED form: 2 flops x heads x (192 + 128) each (the
    absorbed form's 2 x (576 + 512) is the larger count)."""
    n = dims(hf)
    return 2.0 * n["L"] * n["H"] * (n["nope"] + n["rope"] + n["dv"]) * pairs


def expert_bytes(hf: dict, touched: float, element_bytes: float = 2.0,
                 ) -> float:
    """Bytes the routed matmuls must read for ``touched`` (expert, layer)
    pairs that had a token: each expert's three matrices once."""
    return touched * expert_params(hf) * element_bytes
