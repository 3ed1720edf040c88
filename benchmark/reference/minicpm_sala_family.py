"""Plain reference of the MiniCPM-SALA decoder (``model_type: minicpm_sala``;
openbmb MiniCPM-SALA 9B, https://huggingface.co/openbmb/MiniCPM-SALA):
``mixer_types`` is a LIST, a mixer a layer: ``lightning-attn`` (Lightning
linear attention: a decayed sum of outer products read by the query) or
``minicpm4`` (MiniCPM4's grouped-query attention WITHOUT positions whose
queries past ``dense_len`` attend InfLLM-v2's selection of blocks), each in
front of a dense gated MLP, all under MiniCPM's muP scalars.

Straightforward ``jax.numpy`` in float32 on one sequence: no kernels, no
cache, no state carried between calls, the recurrence token by token, the
selection a sort a query. Written from the published keys (the model card's
``config.json``), Qin et al. 2024 ("Lightning Attention-2", arXiv:2401.04658)
and MiniMax-01 (arXiv:2501.08313) for the decay, the MiniCPM4 report
(arXiv:2506.07900) for InfLLM-v2 and MiniCPM's muP frame (arXiv:2404.06395),
FROM MEMORY (this repository has no network), not from the program. ``hf``
are the configuration's published keys; D = ``hidden_size``, L =
``num_hidden_layers``; H = ``lightning_nh`` heads of d = ``lightning_head_
dim``; Hq = ``num_attention_heads``, G = ``num_key_value_heads`` of hd =
``head_dim``.

  norm        N(x; w) = x * rsqrt(mean(x^2) + eps) * w, eps ``rms_norm_eps``
  model       h_0 = scale_emb * embed[token]
              logits = head(N(h_L; w_final) / (D / dim_model_base))
  layer       x = x + (scale_depth / sqrt(L)) * Mixer(N(x; w_in))
              f = N(x; w_ff)
              x = x + (scale_depth / sqrt(L)) * down(silu(gate(f)) * up(f))
  lightning   q, k, v = h Wq, h Wk, h Wv as H heads of d (``lightning_nkv``
              = ``lightning_nh``); ``qk_norm``: q, k = N(q; w_q), N(k; w_k)
              over each head's d (gains [d]); ``lightning_use_rope``:
              rotate-half RoPE over the whole head, theta ``rope_theta``,
              at the token's absolute position. Per head, S in [d, d], S_0
              = 0; for each token t:
                  S <- lambda S + k_t (x) v_t
                  o_t = d^-1/2 S^T q_t        (``lightning_scale``)
              lambda(h, l) = exp(-2^(-8 (h + 1) / H) (1 - l / (L - 1) +
              1e-5)), l the layer's index in the whole stack; the served
              model holds log lambda as the layer's ``decay`` leaf (a
              buffer, as the published code holds its slopes) and
              ``log_decay`` here says what it must hold.
              ``use_output_norm``: o = N(o; w_o) over each head's d, gain
              [H d]; ``use_output_gate``: o = o * sigmoid(h Wg); y = o Wo
  minicpm4    q = h Wq (Hq heads), k, v = h Wk, h Wv (G heads) of hd; q, k
              normed as above; NO RoPE (``attn_use_rope`` false); scale
              hd^-1/2; causal. A query at position t < dense_len attends
              every key. Else, per K/V head g (InfLLM-v2): compressed keys
              c_j = mean(k[stride j : stride j + kernel]) for every j whose
              window is whole (stride j + kernel <= t + 1); p_h = softmax_j
              (q_h . c_j hd^-1/2) for each of g's Hq / G query heads; r(j)
              = sum_h p_h(j); block m (tokens block m .. block (m + 1) - 1)
              scores max r(j) over the windows that touch it (0 where none
              is whole); the first ``init_blocks`` blocks and the blocks
              that hold tokens t - window + 1 .. t score +inf; the ``topk``
              best blocks (ties to the lower index) are attended, a causal
              softmax over their tokens, ONE selection for the heads of g.
              ``attn_use_output_gate``: o = o * sigmoid(h Wg); y = o Wo

What the keys do not settle and is ASSUMED (the configuration file lists each
with the other reading): the decay's layer factor; the selection rule by the
QUERY TOKEN's position; ``sparse_config`` itself, which is MiniCPM4's
published group and not this model card's; the gate taken from the layer's
normed input; ``mup_denominator`` and ``rand_init`` enter no equation.

Callers hold ``jax.default_matmul_precision("highest")`` while tracing.

Weight layout (``x @ w``, float32). The served pytree holds the Lightning
layers a row each under ``layers`` (what ``decoder_layer`` is given one row
of): attn_norm, mlp_norm [D]; wq, wk, wv, w_ogate [D, H d]; wo [H d, D];
q_norm, k_norm [d]; out_norm [H d]; decay [H]; w_gate, w_up [D, F]; w_down
[F, D]. Sparse layer n's leaves lie at the top level under ``sa<n>_``: the
same names without out_norm and decay, wk and wv [D, G hd]. ``walk`` runs the
stack in ``mixer_types``' order.

Hand arithmetic of the second half (benchmark/tests/
test_minicpm_sala_family.py), at the published widths: a Lightning layer 5 x
4096^2 + 3 x 4096 x 16384 = 285,212,672; a sparse one 3 x 4096^2 + 2 x 4096 x
256 + 201,326,592 = 253,755,392; each table 73448 x 4096 = 300,843,008: 24 x
285,212,672 + 8 x 253,755,392 + 2 x 300,843,008 = 9,476,833,280 matmul
weights; gains 24 x 12,544 + 8 x 8,448 + 4,096 = 372,736 and the decay
buffer 24 x 32 = 768. State a slot a Lightning layer 32 x 128 x 128 float32
= 2 MiB; K/V 8 layers x 2 heads x 128 x 2 = 8 KiB a token in bfloat16 added
to the pool, 4096 / 34816 of it read a step.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from reference.llama_family import (head_dim, norm_eps, rms_norm, rope,
                                    rope_tables)

__all__ = ["rope_tables"]

LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# MiniCPM4's published ``sparse_config`` (the sibling's; ASSUMED here)
SPARSE_DEFAULTS = {"kernel_size": 32, "kernel_stride": 16, "init_blocks": 1,
                   "block_size": 64, "window_size": 2048, "topk": 64,
                   "dense_len": 8192}
# rows the sparse attention scores, and the MLP runs, at once (memory, not
# mathematics)
TILE = 256


def dims(hf: dict) -> dict:
    """The shapes the equations name, from the published keys."""
    for key, want in (("attention_bias", False), ("attn_use_rope", False),
                      ("lightning_use_rope", True), ("qk_norm", True),
                      ("use_output_gate", True), ("use_output_norm", True),
                      ("attn_use_output_gate", True)):
        if bool(hf.get(key, want)) != want:
            raise NotImplementedError(
                f"minicpm_sala_family: {key} = {hf[key]!r} is not what the "
                f"published configuration states ({want}) and not written")
    if hf.get("lightning_scale", "1/sqrt(d)") != "1/sqrt(d)":
        raise NotImplementedError("lightning_scale other than 1/sqrt(d)")
    kinds = list(hf["mixer_types"])
    if len(kinds) != hf["num_hidden_layers"] or set(kinds) - {LIGHTNING,
                                                               SPARSE}:
        raise ValueError("mixer_types does not name num_hidden_layers "
                         "layers of the two kinds")
    return {"D": hf["hidden_size"], "F": hf["intermediate_size"],
            "L": hf["num_hidden_layers"], "H": hf["lightning_nh"],
            "d": hf.get("lightning_head_dim", head_dim(hf)),
            "Hq": hf["num_attention_heads"],
            "G": hf["num_key_value_heads"], "hd": head_dim(hf),
            "kinds": kinds, "light": kinds.count(LIGHTNING),
            "sparse": kinds.count(SPARSE),
            **{**SPARSE_DEFAULTS, **(hf.get("sparse_config") or {})}}


def branch_scale(hf: dict) -> float:
    return hf["scale_depth"] / math.sqrt(hf["num_hidden_layers"])


def log_decay(hf: dict, layer: int, factor: bool = True) -> np.ndarray:
    """log lambda of every head of the Lightning layer at stack index
    ``layer`` (``factor`` False: the other reading, no layer factor)."""
    n = dims(hf)
    slope = 2.0 ** (-8.0 * (np.arange(n["H"]) + 1) / n["H"])
    f = 1.0 - layer / max(n["L"] - 1, 1) + 1e-5 if factor else 1.0
    return (-slope * f).astype(np.float32)


def head_norm(x, gain, eps):
    """RMSNorm over each head's last axis; x [T, heads, d], gain [d]."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * gain


def lightning(h, w: dict, cos, sin, hf: dict):
    """The Lightning mixer on normed h [T, D] -> [T, D]."""
    n, t, eps = dims(hf), h.shape[0], norm_eps(hf)
    H, d = n["H"], n["d"]
    q = rope(head_norm((h @ w["wq"]).reshape(t, H, d), w["q_norm"], eps),
             cos, sin)
    k = rope(head_norm((h @ w["wk"]).reshape(t, H, d), w["k_norm"], eps),
             cos, sin)
    v = (h @ w["wv"]).reshape(t, H, d)
    lam = jnp.exp(w["decay"])                                   # [H]

    def token(S, xs):                       # S [H, d(k), d(v)]
        q_t, k_t, v_t = xs
        S = lam[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t) * d ** -0.5

    _, o = jax.lax.scan(token, jnp.zeros((H, d, d), jnp.float32), (q, k, v))
    o = head_norm(o, 1.0, eps).reshape(t, H * d) * w["out_norm"]
    return (o * jax.nn.sigmoid(h @ w["w_ogate"])) @ w["wo"]


def touches(n: dict, blocks: int, windows: int) -> np.ndarray:
    """[blocks, windows] bool: window j (tokens stride j .. stride j + kernel
    - 1) holds a token of block m."""
    lo = n["kernel_stride"] * np.arange(windows)[None, :]
    m = np.arange(blocks)[:, None]
    return ((lo < n["block_size"] * (m + 1))
            & (lo + n["kernel_size"] > n["block_size"] * m))


def chosen_blocks(q, c, t, n: dict, blocks: int, group_sum: bool = True):
    """The blocks one query attends, per K/V head: [G, blocks] bool. q [G,
    g, hd] the query's heads by group, c [G, J, hd] the compressed keys, t
    its position."""
    J = c.shape[1]
    whole = n["kernel_stride"] * jnp.arange(J) + n["kernel_size"] <= t + 1
    s = jnp.einsum("kgh,kjh->kgj", q, c) * q.shape[-1] ** -0.5
    s = jnp.where(whole, s, -jnp.inf)
    p = jnp.where(whole, jax.nn.softmax(
        jnp.where(jnp.any(whole), s, 0.0), axis=-1), 0.0)
    r = jnp.sum(p, axis=1) if group_sum else p[:, 0]            # [G, J]
    touch = jnp.asarray(touches(n, blocks, J))
    score = jnp.max(jnp.where(touch[None] & whole[None, None, :],
                              r[:, None, :], 0.0), axis=-1)     # [G, blocks]
    m = jnp.arange(blocks)
    bs = n["block_size"]
    forced = (m < n["init_blocks"]) | (
        m >= jnp.maximum(t - n["window_size"] + 1, 0) // bs)
    score = jnp.where(m > t // bs, -jnp.inf,
                      jnp.where(forced, jnp.inf, score))
    order = jnp.argsort(-score, axis=-1, stable=True)[:, :n["topk"]]
    keep = jnp.zeros((q.shape[0], blocks), bool).at[
        jnp.arange(q.shape[0])[:, None], order].set(True)
    return keep & (m <= t // bs)


def sparse_attention(h, w: dict, hf: dict):
    """MiniCPM4's attention on normed h [T, D] -> [T, D]."""
    n, t, eps = dims(hf), h.shape[0], norm_eps(hf)
    Hq, G, hd = n["Hq"], n["G"], n["hd"]
    g = Hq // G
    q = head_norm((h @ w["wq"]).reshape(t, Hq, hd), w["q_norm"], eps)
    k = head_norm((h @ w["wk"]).reshape(t, G, hd), w["k_norm"], eps)
    v = (h @ w["wv"]).reshape(t, G, hd)
    s, K, bs = n["kernel_stride"], n["kernel_size"], n["block_size"]
    J = max((t - K) // s + 1, 0)
    blocks = -(-t // bs)
    if J:
        # window j is keys s j .. s j + K - 1
        at = s * jnp.arange(J)[:, None] + jnp.arange(K)[None, :]
        c = jnp.moveaxis(jnp.mean(k[at], axis=1), 0, 1)         # [G, J, hd]
    else:
        c = jnp.zeros((G, 1, hd), jnp.float32)
    pad = -t % TILE if t > TILE else 0
    qp = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, min(t, TILE), G,
                                                        g, hd)
    pos = jnp.arange(t + pad).reshape(qp.shape[:2])
    kpos = jnp.arange(t)

    def tile(args):
        qt, at = args                       # [rows, G, g, hd], [rows]
        if t > n["dense_len"]:
            keep = jax.vmap(lambda q1, t1: chosen_blocks(
                q1, c, t1, n, blocks))(qt, at)                  # [rows, G, B]
            keep = keep | (at < n["dense_len"])[:, None, None]
            seen = jnp.repeat(keep, bs, axis=-1)[..., :t]
        else:
            seen = jnp.ones((qt.shape[0], G, t), bool)
        seen = seen & (kpos[None, None, :] <= at[:, None, None])
        sc = jnp.einsum("tkgh,skh->tkgs", qt, k) * hd ** -0.5
        sc = jnp.where(seen[:, :, None, :], sc, -jnp.inf)
        return jnp.einsum("tkgs,skh->tkgh", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(tile, (qp, pos)).reshape(-1, Hq * hd)[:t]
    return (o * jax.nn.sigmoid(h @ w["w_ogate"])) @ w["wo"]


def mlp(x, w: dict, hf: dict):
    """x + the gated MLP of its norm, ``TILE`` rows at a time (rows do not
    mix: the check's longest probes set the process's peak beside a server
    that fills the chip, and a probe's [T, F] intermediates are most of
    it)."""
    t = x.shape[0]
    pad = -t % TILE if t > TILE else 0
    f = jnp.pad(rms_norm(x, w["mlp_norm"], norm_eps(hf)), ((0, pad), (0, 0)))
    out = jax.lax.map(
        lambda rows: (jax.nn.silu(rows @ w["w_gate"]) * (rows @ w["w_up"]))
        @ w["w_down"], f.reshape(-1, min(t, TILE), f.shape[-1]))
    return x + branch_scale(hf) * out.reshape(-1, x.shape[-1])[:t]


def decoder_layer(x, w: dict, cos, sin, hf: dict):
    """One LIGHTNING layer on one sequence x [T, D] (a row of the served
    ``layers`` stack)."""
    h = rms_norm(x, w["attn_norm"], norm_eps(hf))
    return mlp(x + branch_scale(hf) * lightning(h, w, cos, sin, hf), w, hf)


def sparse_layer(x, w: dict, hf: dict):
    """One SPARSE layer on one sequence x [T, D]."""
    h = rms_norm(x, w["attn_norm"], norm_eps(hf))
    return mlp(x + branch_scale(hf) * sparse_attention(h, w, hf), w, hf)


SPARSE_LEAVES = ("attn_norm", "mlp_norm", "w_gate", "w_up", "w_down", "wq",
                 "wk", "wv", "w_ogate", "wo", "q_norm", "k_norm")


def walk(x, one_layer, rows: int, leaf, hf: dict):
    """The embedded probes x [B, T, D] under ``scale_emb`` through the stack
    in ``mixer_types``' order: Lightning layer i of the stack is row
    (Lightning layers before it) of ``layers``, through ``one_layer``; sparse
    layer n's leaves are read one tensor at a time through ``leaf``."""
    n = dims(hf)
    if n["light"] != rows:
        raise ValueError(f"the served stack holds {rows} rows; the published "
                         f"keys name {n['light']} Lightning layers")
    x = x * hf["scale_emb"]
    # ONE program for the sparse layers, the weights its ARGUMENT
    run = jax.jit(lambda x, w: jax.vmap(lambda s: sparse_layer(s, w, hf))(x))
    row = sparse = 0
    for kind in n["kinds"]:
        if kind == LIGHTNING:
            x = one_layer(x, row)
            row += 1
            continue
        x = run(x, {name: leaf(f"sa{sparse}_{name}")
                    for name in SPARSE_LEAVES})
        sparse += 1
    return x


def logits(x, final_norm, head, hf: dict):
    """x [T, D] -> [T, V'] for the output-head columns given."""
    return (rms_norm(x, final_norm, norm_eps(hf))
            / (hf["hidden_size"] / hf["dim_model_base"])) @ head


def cache_layers(hf: dict) -> int:
    """K/V is cached by the sparse layers alone."""
    return dims(hf)["sparse"]


# ---------------------------------------------------------------------------
# operations and bytes


def mlp_params(hf: dict) -> int:
    return 3 * hf["hidden_size"] * hf["intermediate_size"]


def lightning_params(hf: dict) -> int:
    """Matmul weights of one Lightning layer: q, k, v, gate, out, the MLP."""
    n = dims(hf)
    return 5 * n["D"] * n["H"] * n["d"] + mlp_params(hf)


def sparse_params(hf: dict) -> int:
    """Matmul weights of one sparse layer: q, gate, out, k and v, the MLP."""
    n = dims(hf)
    return (3 * n["D"] * n["Hq"] * n["hd"] + 2 * n["D"] * n["G"] * n["hd"]
            + mlp_params(hf))


def vectors(hf: dict) -> int:
    """Every gain and buffer: two [D] norms a layer, q and k norms, a
    Lightning layer's output norm and decay, the final norm."""
    n = dims(hf)
    return (n["L"] * 2 * n["D"] + n["light"] * (2 * n["d"] + n["H"] * n["d"]
                                                + n["H"])
            + n["sparse"] * 2 * n["hd"] + n["D"])


def layer_params(hf: dict) -> int:
    """Matmul weights of the LARGEST layer (a Lightning one): what a reader
    of one layer's weights holds at most."""
    return max(lightning_params(hf), sparse_params(hf))


def token_params(hf: dict) -> int:
    """Weights one token's forward pass multiplies, all layers, the head
    left out."""
    n = dims(hf)
    return (n["light"] * lightning_params(hf)
            + n["sparse"] * sparse_params(hf))


def param_count(hf: dict) -> int:
    """Every leaf the served model holds: the layers' matmul weights, both
    tables, the gains and the decay buffer."""
    tables = hf["hidden_size"] * hf["vocab_size"] * (
        1 if hf.get("tie_word_embeddings") else 2)
    return token_params(hf) + tables + vectors(hf)


def step_params(hf: dict, tokens: float) -> int:
    """WEIGHTS a decode step must read: all layers and the head, whatever
    ``tokens`` is. State, selected K/V and compressed keys are no weights
    (``state_bytes``, ``selected_kv_bytes``, ``compressed_key_bytes``)."""
    return token_params(hf) + hf["hidden_size"] * hf["vocab_size"]


def kv_bytes_per_token(hf: dict, element_bytes: float) -> float:
    """K and V bytes a decode step must READ a cached token, the sparse
    layers (the readers price the attend's need with it and nothing else:
    harness/work.py). A token ADDS 2 x 8 x G x hd elements to the pool; a
    query reads ``topk`` blocks of its context, so a cached token of a
    context of the served length (``max_position_embeddings``) is read with
    the chance ``topk x block_size`` of that length. An expectation at the
    served context, never an upper bound: below it the attend reads more of
    each token and a share of a roofline priced here reads LOW, never over
    100."""
    n = dims(hf)
    share = min(1.0, n["topk"] * n["block_size"]
                / hf["max_position_embeddings"])
    return 2 * n["sparse"] * n["G"] * n["hd"] * element_bytes * share


def q_elements_per_token(hf: dict) -> int:
    """Elements of one token's attention q (and output), the sparse layers."""
    n = dims(hf)
    return n["sparse"] * n["Hq"] * n["hd"]


def attn_flops(hf: dict, pairs: float) -> float:
    """QK^T and PV over ``pairs`` (query token, attended token) pairs, the
    sparse layers."""
    return 4.0 * q_elements_per_token(hf) * pairs


def state_bytes(hf: dict, slot_steps: float) -> float:
    """Bytes the Lightning layers must move for ``slot_steps`` (live slot,
    step) pairs: every layer's S read and written once in float32."""
    n = dims(hf)
    return slot_steps * n["light"] * 2 * n["H"] * n["d"] * n["d"] * 4.0


def selected_kv_bytes(hf: dict, sparse_rows: float,
                      element_bytes: float = 2.0) -> float:
    """K/V bytes the sparse layers must read for ``sparse_rows`` decode rows
    at or past ``dense_len``: ``topk`` blocks a K/V head a layer, q read and
    the output written beside them."""
    n = dims(hf)
    kv = 2 * n["G"] * n["topk"] * n["block_size"] * n["hd"] * element_bytes
    return sparse_rows * n["sparse"] * (kv + 2 * 2.0 * n["Hq"] * n["hd"])


def compressed_key_bytes(hf: dict, sparse_rows: float, context: float,
                         element_bytes: float = 2.0) -> float:
    """Compressed-key bytes the scoring reads for ``sparse_rows`` decode rows
    of mean context ``context``: a key a stride a K/V head a layer."""
    n = dims(hf)
    return (sparse_rows * n["sparse"] * n["G"]
            * (context / n["kernel_stride"]) * n["hd"] * element_bytes)
