"""The reference check: served greedy tokens against the plain reference.

Runs in the child (the process that holds the chip and the weights), while
the server is otherwise idle. The parent served each probe over HTTP, greedy,
4 new tokens; here the plain float32 forward pass of the configuration's
family (``benchmark/reference/<family>.py``: the configuration file names it,
harness/spec.py has the contract) runs on the SERVED weights, dequantised one
layer at a time, teacher-forced on the served tokens. For each of a
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
else: ``embed``, ``final_norm``, ``lm_head`` (unless tied) and ``layers``, a
pytree of stacked per-layer weights that only the family's ``decoder_layer``
names; leaves are arrays, or quantised tensors with ``q`` (int8), ``scale``
(float32, the weight's shape without the contraction axis) and ``axis``.
``served_param_count`` counts them, for the set-up's check that the model
served is the model the file describes (the family's ``param_count``).

Which row of the stack runs when, how often, and what happens between is the
family's to say (its optional ``walk``, harness/spec.py); a family that says
nothing gets ``in_order``: every row once. Either way a layer runs through the
ONE jitted program, its weights dequantised inside it: never more than one
layer in float32 at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

LETTERS = slice(ord("a"), ord("z") + 1)


def _quantised(leaf) -> bool:
    return hasattr(leaf, "q")


def served_param_count(params) -> int:
    """Weights and norm gains the served model holds: every leaf's elements
    (a quantised leaf's ``q``; its scales are no parameters), global shapes
    where the leaf is sharded."""
    return sum(int((leaf.q if _quantised(leaf) else leaf).size)
               for leaf in jax.tree_util.tree_leaves(params,
                                                     is_leaf=_quantised))


def _f32(leaf, index=None, cols=None):
    """A weight (or layer ``index`` of a stacked one, or columns of it) as
    float32, dequantised if it is a quantised tensor."""
    quantised = _quantised(leaf)
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
    if _quantised(e):       # per-row scales
        return e.q[tokens].astype(jnp.float32) * e.scale[tokens][..., None]
    return e[tokens].astype(jnp.float32)


def _head(params):
    """The output head's 26 letter columns [D, 26]."""
    if "lm_head" in params:
        return _f32(params["lm_head"], cols=LETTERS)
    return _embed(params, jnp.arange(LETTERS.start, LETTERS.stop)).T


def in_order(x, layer, rows: int, leaf, hf: dict):
    """The walk of a family that defines none: every row once, in order."""
    for index in range(rows):
        x = layer(x, index)
    return x


def programs(family, hf: dict, n_tokens: int):
    """The reference as three plain callables over same-length sequences
    [B, T]: tokens -> x, (x, stacked layers, layer index) -> x, and
    (params, x at the wanted positions) -> logits over the 26 letters."""
    cos, sin = family.rope_tables(hf, n_tokens)

    def layer(x, layers, index):
        w = jax.tree_util.tree_map(lambda leaf: _f32(leaf, index), layers,
                                   is_leaf=_quantised)
        return jax.vmap(
            lambda s: family.decoder_layer(s, w, cos, sin, hf))(x)

    def logits(params, x):
        return jax.vmap(lambda s: family.logits(
            s, params["final_norm"].astype(jnp.float32), _head(params),
            hf))(x)

    return _embed, layer, logits


def reference_logits(params, family, hf: dict, tokens: np.ndarray,
                     last: int):
    """Reference logits over the 26 letters at the ``last`` final positions
    of each of tokens [B, T] (same-length sequences, one vmapped batch), one
    layer's weights dequantised at a time, the layers in the order the
    family's ``walk`` gives (``in_order`` where it has none)."""
    embed, layer, logits = (jax.jit(f) for f in programs(
        family, hf, tokens.shape[1]))
    rows = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]

    def one_layer(x, index: int):
        """Row ``index`` of the served stack applied to x [B, T, D]."""
        if not 0 <= index < rows:
            # a dynamic index out of range is clamped in silence
            raise IndexError(f"the walk asked for row {index}; the served "
                             f"model holds {rows}")
        return layer(x, params["layers"], jnp.int32(index))

    walk = getattr(family, "walk", None) or in_order
    with jax.default_matmul_precision("highest"):
        # the embedded probes go to the walk as a temporary: a name for them
        # here would keep one more [B, T, D] on the device for the whole
        # walk, and the check's longest probes set the process's peak
        x = walk(embed(params, jnp.asarray(tokens, jnp.int32)), one_layer,
                 rows, lambda name: _f32(params[name]), hf)
        out = logits(params, x[:, -last:])
    return np.asarray(out, np.float32)


def shortfalls(params, family, hf: dict,
               probes: list[dict]) -> list[dict]:
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
        lg = reference_logits(params, family, hf, tokens,
                              n_new)                       # [B, n_new, 26]
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
