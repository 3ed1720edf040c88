"""The reference check: served greedy tokens against the plain reference.

Runs in the child (the process that holds the chip and the weights), while
the server is otherwise idle. The parent served each probe over HTTP, greedy,
4 new tokens; here the plain float32 forward pass
(benchmark/reference/llama_family.py) runs on the SERVED weights, dequantised
one layer at a time, teacher-forced on the served tokens. For each of a
probe's 4 positions it returns the shortfall: the largest reference logit
among the 26 letters minus the reference logit of the token that was served.
An exact server has shortfall 0 everywhere; rounding in the served path flips
near-ties and leaves a small one at a few positions. The parent
(run.py ``judge``) holds ONE number of the run's 64 shortfalls to the
configuration file's ``reference.epsilon``: the one its
``reference.statistic`` names, the largest (``max``, the default) or the
``mean``; both are printed in every run. The limit is set from readings on the
chip at that configuration's widths, sound runs and the control's, with both
beside it in the file. The served path computes in bfloat16 with float32
accumulation and writes bfloat16 logits: half an ulp at |logit| in [2, 4) is
2^-7 = 0.0078, and two letters whose reference logits are closer than the
accumulated rounding swap places. A lower precision than the configuration
states (int8 activations, the control) flips several times as many ties,
several times wider apart. At the 24B's widths one near-tie sets the largest
shortfall of a run and no limit on it tells the two apart, so its file judges
the mean (PERF.md sections 2 and 6, PR 27).

From the program this reads the loaded runner's ``params`` pytree and nothing
else: leaves are arrays, or quantised tensors with ``q`` (int8), ``scale``
(float32, the weight's shape without the contraction axis) and ``axis``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from reference import llama_family as ref

LETTERS = slice(ord("a"), ord("z") + 1)


def _f32(leaf, index=None, cols=None):
    """A weight (or layer ``index`` of a stacked one, or columns of it) as
    float32, dequantised if it is a quantised tensor."""
    quantised = hasattr(leaf, "q")
    if quantised and leaf.scale.ndim != leaf.q.ndim - 1:
        raise NotImplementedError("group-wise scales (int4) are not covered "
                                  "by the reference check yet")
    q = leaf.q if quantised else leaf
    axis = leaf.axis if quantised else None
    scale = leaf.scale if quantised else None
    if index is not None:
        q = jax.lax.dynamic_index_in_dim(q, index, 0, keepdims=False)
        if quantised:
            scale = jax.lax.dynamic_index_in_dim(scale, index, 0,
                                                 keepdims=False)
            axis -= 1
    if cols is not None:
        q = q[..., cols]
        if quantised:
            scale = scale[..., cols]
    q = q.astype(jnp.float32)
    return q * jnp.expand_dims(scale, axis) if quantised else q


def _embed(params, tokens):
    e = params["embed"]
    if hasattr(e, "q"):     # per-row scales
        return e.q[tokens].astype(jnp.float32) * e.scale[tokens][..., None]
    return e[tokens].astype(jnp.float32)


def _head(params):
    """The output head's 26 letter columns [D, 26]."""
    if "lm_head" in params:
        return _f32(params["lm_head"], cols=LETTERS)
    return _embed(params, jnp.arange(LETTERS.start, LETTERS.stop)).T


def programs(hf: dict, n_tokens: int):
    """The reference as three plain callables over same-length sequences
    [B, T]: tokens -> x, (x, stacked layers, layer index) -> x, and
    (params, x at the wanted positions) -> logits over the 26 letters."""
    heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
    hd = int(hf.get("head_dim") or hf["hidden_size"] // heads)
    eps = float(hf.get("rms_norm_eps", 1e-5))
    cos, sin = ref.rope_angles(jnp.arange(n_tokens), hd,
                               float(hf.get("rope_theta", 10000.0)))

    def layer(x, layers, index):
        w = {k: _f32(v, index) for k, v in layers.items()}
        return jax.vmap(lambda s: ref.decoder_layer(
            s, w, cos, sin, num_heads=heads, num_kv_heads=kv_heads,
            head_dim=hd, eps=eps))(x)

    def logits(params, x):
        return jax.vmap(lambda s: ref.logits(
            s, params["final_norm"].astype(jnp.float32), _head(params),
            eps))(x)

    return _embed, layer, logits


def reference_logits(params, hf: dict, tokens: np.ndarray, last: int):
    """Reference logits over the 26 letters at the ``last`` final positions
    of each of tokens [B, T] (same-length sequences, one vmapped batch), one
    layer's weights dequantised at a time."""
    embed, layer, logits = (jax.jit(f) for f in programs(hf, tokens.shape[1]))
    with jax.default_matmul_precision("highest"):
        x = embed(params, jnp.asarray(tokens, jnp.int32))
        for index in range(hf["num_hidden_layers"]):
            x = layer(x, params["layers"], jnp.int32(index))
        out = logits(params, x[:, -last:])
    return np.asarray(out, np.float32)


def shortfalls(params, hf: dict, probes: list[dict]) -> list[dict]:
    """probes: [{"prompt": [ids], "served": [ids]}]. Same-length probes run
    as one batch. Returns, per probe, per served position: the shortfall and
    the reference's own margin (largest minus second largest letter logit)."""
    out: list = [None] * len(probes)
    by_len: dict = {}
    for i, p in enumerate(probes):
        by_len.setdefault((len(p["prompt"]), len(p["served"])), []).append(i)
    for (_, n_new), idxs in sorted(by_len.items()):
        # logits at the last prompt position predict served[0]; teacher-force
        # served[:-1] to predict the rest
        tokens = np.array([probes[i]["prompt"] + probes[i]["served"][:-1]
                           for i in idxs], np.int32)
        lg = reference_logits(params, hf, tokens, n_new)   # [B, n_new, 26]
        for b, i in enumerate(idxs):
            rows = []
            for k, tok in enumerate(probes[i]["served"]):
                row = lg[b, k]
                top = np.sort(row)[::-1]
                rows.append({
                    "shortfall": float(top[0] - row[tok - LETTERS.start]),
                    "margin": float(top[0] - top[1])})
            out[i] = rows
    return out
