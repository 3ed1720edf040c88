"""Routed experts beside a shared expert: the ONE routing and dispatch of the
sparse families that are told which experts they hold (models.qwen3_next,
models.afmoe, models.deepseek, models.dots3, models.lfm2).

An expert block scores a token over ALL ``num_experts x ep_size`` experts of
the deployment, keeps its k choices, and computes the part of the routed sum
that the ``num_experts`` experts HELD here give, in a walk over the held
experts that HAVE a token (ops.moe's grouped kernel where attention's are
kernels, ``experts_loop`` as XLA); the shared expert is added. On one chip the
block runs without its exchange: the sum is this chip's partial result.

Three things differ between the families, and are the block's arguments: the
scoring rule (``softmax_scores`` / ``sigmoid_scores``, the latter plain or
group-limited: logits -> a token's k weights and choices), the shared
expert (a closure: under a sigmoid gate, ungated, or None where the family
has no shared expert) and the function on an expert's gate (``act``: SiLU;
ReLU for models.smallthinker). Everything else is here once: the weights of
the held experts, the tokens an expert got, the order of the walk, the two
counts a launch reports ([experts touched, token-expert pairs that landed
here]; engine.scheduler ``_routed``), the scopes ``router``, ``experts``,
``shared``.

``moe_block`` is the two halves in a row on ONE tensor: ``route`` (scope
``router``) and ``walk`` (scope ``experts``). A family whose router reads
another tensor than its experts (models.smallthinker: the attention's input)
calls the halves apart and hands the first's ``Routing`` to the second.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax

from localai_tpu.models import quant as qnt

EXPERT_LEAVES = ("w_gate", "w_up", "w_down")


def init_rec(cfg=None, num_slots: int = 0) -> dict:
    """What ``DecodeState.rec`` holds for a family that routes: the routed
    work of prefill chunks whose token no copy brings to the host yet
    (engine.runner ``_prefill_paged_fn`` adds to it and empties it; a forward
    hands it on untouched). The whole ``init_rec`` of a family with no
    per-slot state of its own, an entry of a recurrent one's."""
    return {"routed": jnp.zeros(2, jnp.int32)}


def swiglu(h, w_gate, w_up, w_down):
    """down(silu(gate h) * up h): a dense layer's feed-forward, the shared
    expert."""
    y = jax.nn.silu(qnt.matmul(h, w_gate)) * qnt.matmul(h, w_up)
    return qnt.matmul(y, w_down)


def shared_expert(h, w_gate, w_up, w_down):
    """An ungated shared expert on h [N, D]; float32."""
    return swiglu(h, w_gate, w_up, w_down).astype(jnp.float32)


def softmax_scores(k: int, renormalise: bool) -> Callable:
    """softmax over ALL experts, the k largest, renormalised to sum 1 where
    the family says so."""
    def score(logits):
        probs = jax.nn.softmax(logits, axis=-1)
        topv, topi = lax.top_k(probs, k)
        if renormalise:
            topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
        return topv, topi

    return score


def sigmoid_scores(k: int, bias, renormalise: bool, scale: float, *,
                   n_group: int = 1, topk_group: int = 1,
                   eps: float = 1e-20) -> Callable:
    """s = sigmoid(logits) over ALL experts; the k largest of ``s + bias``
    are chosen (the bias SELECTS and does not weigh; None: the family has
    none); a choice weighs its own s, over the sum of the k (+ ``eps``: 1e-20,
    models.lfm2's 1e-6) where the family renormalises, times ``scale``.
    GROUP-LIMITED where
    ``n_group`` > 1 (models.deepseek): the experts lie in ``n_group`` equal
    groups, a group scores the sum of its two largest, and only the
    ``topk_group`` best groups' experts can be chosen (the others' scores
    read 0 in the selection, as the published text fills them). ``n_group``
    1 is the plain top-k."""
    def score(logits):
        s = jax.nn.sigmoid(logits)
        choice = s if bias is None else s + bias.astype(jnp.float32)
        if n_group > 1:
            groups = choice.reshape(*choice.shape[:-1], n_group, -1)
            best = jnp.sum(lax.top_k(groups, 2)[0], axis=-1)
            kept = best >= lax.top_k(best, topk_group)[0][..., -1:]
            choice = jnp.where(kept[..., None], groups, 0.0).reshape(
                choice.shape)
        _, topi = lax.top_k(choice, k)
        topv = jnp.take_along_axis(s, topi, axis=-1)
        if renormalise:
            topv = topv / (jnp.sum(topv, axis=-1, keepdims=True) + eps)
        return topv * scale, topi

    return score


class Routing(NamedTuple):
    """What ``route`` hands ``walk``: the held experts' weights [N, E]
    float32 (0 off a token's choices and for a token that is not real), the
    tokens each got [E] i32, the experts that have a token first (``order``
    [E] i32) and how many those are."""

    weights: jax.Array
    load: jax.Array
    order: jax.Array
    n_touched: jax.Array


def route(h, w_router, score: Callable, num_experts: int, ep_rank: int,
          valid) -> Routing:
    """The block's first half, under the scope ``router``: normed h [N, D]
    scored over ALL experts, and what of it lands on the experts HELD
    here."""
    E = num_experts
    with jax.named_scope("router"):
        logits = qnt.matmul(h, w_router).astype(jnp.float32)      # over ALL
        topv, topi = score(logits)
        local = topi - ep_rank * E
        here = (local >= 0) & (local < E) & valid[:, None]
        onehot = jax.nn.one_hot(jnp.where(here, local, E), E,
                                dtype=jnp.float32)
        weights = jnp.sum(onehot * topv[..., None], axis=1)
        load = jnp.sum(onehot, axis=(0, 1)).astype(jnp.int32)
        touched = load > 0
        n_touched = jnp.sum(touched).astype(jnp.int32)
        # the experts that have a token first, in their own order
        order = jnp.argsort(~touched, stable=True).astype(jnp.int32)
    return Routing(weights, load, order, n_touched)


def experts_loop(h, weights, order, n_touched, experts, p, m_idx,
                 act: Callable = jax.nn.silu):
    """ops.moe.moe_experts as XLA, and its oracle: a loop over the touched
    experts, three dots behind a scalar-indexed slice an iteration; ``act``
    the function on the gate's product."""
    w_gate, w_up, w_down = experts

    def pick(w, e):
        return lax.dynamic_slice(
            w, (p, m_idx, e, 0, 0), (1, 1, 1) + w.shape[3:])[0, 0, 0]

    def one_expert(i, acc):
        e = order[i]
        y = (act(qnt.matmul(h, pick(w_gate, e)))
             * qnt.matmul(h, pick(w_up, e)))
        y = qnt.matmul(y, pick(w_down, e))
        col = lax.dynamic_index_in_dim(weights, e, 1, keepdims=True)
        return acc + col * y.astype(jnp.float32)

    return lax.fori_loop(0, n_touched, one_expert,
                         jnp.zeros(h.shape, jnp.float32))


def walk(h, routed: Routing, experts, p, m_idx, *,
         experts_kernel: Optional[bool] = None,
         act: Callable = jax.nn.silu):
    """The block's second half, under the scope ``experts``: this chip's
    part of the routed sum of normed h [N, D] under ``routed``, [N, D]
    float32. ``experts``, ``p``, ``m_idx``, ``experts_kernel`` and ``act``
    are ``moe_block``'s."""
    with jax.named_scope("experts"):
        if experts_kernel is not None:
            from localai_tpu.ops import moe

            return moe.moe_experts(h, routed.weights, routed.order,
                                   routed.n_touched, experts, p, m_idx,
                                   interpret=experts_kernel, act=act)
        return experts_loop(h, routed.weights, routed.order,
                            routed.n_touched, experts, p, m_idx, act)


def moe_block(h, w_router, score: Callable, experts, p, m_idx, *,
              num_experts: int, ep_rank: int, valid,
              shared: Optional[Callable],
              experts_kernel: Optional[bool] = None,
              act: Callable = jax.nn.silu):
    """One expert block on normed h [N, D]: this chip's part of the routed
    sum plus the shared expert. ``experts``: the three stacked expert leaves
    WHOLE ([rows, M, E, ...]), indexed here by (p, m_idx, expert) so that a
    step reads the experts it touched and nothing else of them. ``shared``:
    h -> the shared expert's [N, D] float32; None where the family has none
    (nothing is formed or added). ``experts_kernel``: None is the
    XLA loop over the touched experts, else ops.moe's grouped kernel (the
    value: in the Pallas interpreter). ``act``: the function on an expert's
    gate. Returns (out [N, D], experts touched, tokens each held expert got
    [E]): ``counts`` makes the launch's two numbers of the last two."""
    routed = route(h, w_router, score, num_experts, ep_rank, valid)
    out = walk(h, routed, experts, p, m_idx, experts_kernel=experts_kernel,
               act=act)
    if shared is None:
        return out.astype(h.dtype), routed.n_touched, routed.load
    with jax.named_scope("shared"):
        out = (out + shared(h)).astype(h.dtype)
    return out, routed.n_touched, routed.load


def counts(n_touched, load):
    """[experts touched, token-expert pairs that landed here] i32: what a
    launch reports of one expert block."""
    return jnp.stack([n_touched, jnp.sum(load)])
