"""Qwen3-Next (``model_type: qwen3_next``): a decoder whose layers come in
PERIODS of ``full_attention_interval``: Gated DeltaNet layers (a linear
attention with a per-head recurrent state, arXiv:2412.06464) and then one
gated full-attention layer, every layer followed by a routed expert block
with a shared expert.

What the serving engine holds of it (engine.runner):

  * the stacked ``layers`` pytree's leading axis is the PERIOD, the one
    ``lax.scan`` of the forward runs over periods and unrolls the layers of
    one: DeltaNet leaves are ``[P, G, ...]`` (G = interval - 1), the full
    layer's ``[P, ...]``, the expert blocks' ``[P, G + 1, ...]``;
  * the full-attention layers use the paged K/V pool, its write policies and
    the paged decode kernel as every other model does: cache layer = period
    (``cache_layers`` = P);
  * a DeltaNet layer's state is NOT keys: per slot a float32 matrix
    ``S [Hv, dk, dv]`` and the last ``K - 1`` rows of the conv's input. It
    lives beside the pool as two dense per-SLOT arrays (``init_rec``) that the
    forward carries through the scan and updates in place; a token that is
    not real (an empty slot of a decode step, a padded row of a chunk) is the
    identity on both, and a chunk's recurrence walks its real rows alone; a
    small last chunk and a decode step go through as ONE batch (``RIDES``:
    models.llama ``family_module``'s third case), the convolution and the
    recurrence alone in two halves (``_gdn``);
  * the expert block is TOLD which experts it holds (``ep_size``,
    ``ep_rank``: a deployment's expert parallelism): the router scores all
    ``num_experts x ep_size`` experts, the ``num_experts`` held here compute
    their part for the tokens routed to them, in a walk over the experts that
    HAVE a token (a step reads the weights of the experts it touched and no
    others: ops.moe's grouped kernel where attention's are kernels, a
    ``fori_loop`` as XLA), and the shared expert is added. On one chip the layer runs
    without its exchange: the sum is this chip's partial result.

The plain reference is benchmark/reference/qwen3_next_family.py, and
tests/test_qwen3_next.py holds this file to it. The conv, the router, the
shared expert and a chunk's DeltaNet token loop are XLA under named scopes
(``gdn/*``, ``moe/*``, ``attn_gate``); the routed experts' kernel runs under
``moe/experts``, as their loop does, and the decode step's recurrence
(ops.gdn's kernel where attention's are kernels, ``gdn_step`` as XLA) under
``gdn/state``.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar, Optional

import jax
import jax.numpy as jnp
from jax import lax

from localai_tpu.models import experts as xp
from localai_tpu.models import llama as mdl
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import LlamaConfig

L2_EPS = 1e-6       # the DeltaNet's q/k normalisation: x * rsqrt(|x|^2 + eps)


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig(LlamaConfig):
    """``LlamaConfig`` with the keys the family adds. ``num_experts`` is the
    number of routed experts HELD here; the router's width is
    ``num_experts * ep_size``."""

    full_attention_interval: int = 4
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    partial_rotary_factor: float = 0.25
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    norm_topk_prob: bool = True
    ep_size: int = 1          # chips that share a layer's routed experts
    ep_rank: int = 0          # which of them this is

    recurrent: ClassVar[bool] = True
    family: ClassVar[str] = "qwen3_next"
    routed: ClassVar[bool] = True

    def __post_init__(self):
        if self.num_layers % self.full_attention_interval:
            raise ValueError(
                f"qwen3_next serves whole periods: num_hidden_layers "
                f"{self.num_layers} is no multiple of "
                f"full_attention_interval {self.full_attention_interval}")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"expert_parallel rank {self.ep_rank} outside "
                             f"size {self.ep_size}")

    @property
    def periods(self) -> int:
        return self.num_layers // self.full_attention_interval

    @property
    def gdn_per_period(self) -> int:
        return self.full_attention_interval - 1

    @property
    def cache_layers(self) -> int:
        """K/V is cached by the full-attention layers alone: one a period."""
        return self.periods

    @property
    def rotary_dim(self) -> int:
        return int(self.hd * self.partial_rotary_factor)

    @property
    def router_width(self) -> int:
        return self.num_experts * self.ep_size

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def conv_dim(self) -> int:
        """Channels the causal conv runs over: [q; k; v]."""
        return 2 * self.key_dim + self.value_dim

    @classmethod
    def from_hf(cls, hf: dict) -> "Qwen3NextConfig":
        """From published keys. ``expert_parallel: {size, rank}`` is no
        published key: it states the deployment's share (``num_experts`` is
        then what ONE of ``size`` chips holds)."""
        ep = hf.get("expert_parallel") or {}
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf.get("intermediate_size", 0),
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim"),
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            rope_scaling=hf.get("rope_scaling"),
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            full_attention_interval=hf.get("full_attention_interval", 4),
            linear_num_key_heads=hf["linear_num_key_heads"],
            linear_num_value_heads=hf["linear_num_value_heads"],
            linear_key_head_dim=hf["linear_key_head_dim"],
            linear_value_head_dim=hf["linear_value_head_dim"],
            linear_conv_kernel_dim=hf.get("linear_conv_kernel_dim", 4),
            partial_rotary_factor=hf.get("partial_rotary_factor", 1.0),
            moe_intermediate_size=hf["moe_intermediate_size"],
            shared_expert_intermediate_size=hf[
                "shared_expert_intermediate_size"],
            norm_topk_prob=hf.get("norm_topk_prob", True),
            ep_size=int(ep.get("size", 1)),
            ep_rank=int(ep.get("rank", 0)),
        )


CONFIG = Qwen3NextConfig
# what the family does not serve, the weight modes it does, and why
# (models.llama ``refusal``)
UNSERVED = mdl.KEYS_ALONE
WEIGHTS = ()
# ``forward`` takes a prompt's small last chunk and a decode step as one
# batch (the contract's ``ride``): rows meet in ``_gdn_mix`` alone
RIDES = True
WHY = (f"model_type qwen3_next: its DeltaNet layers {mdl.STATE_WHY}; its "
       f"routed experts are read one expert at a time from the stacked "
       f"bfloat16 leaves")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# leaves that are a zero-centred RMSNorm gain: N(x; w) = norm(x) * (1 + w)
ZERO_CENTRED = ("gdn_norm", "attn_norm", "mlp_norm", "q_norm", "k_norm",
                "final_norm")


def param_shapes(cfg: Qwen3NextConfig) -> dict:
    """Shapes of the stacked-parameter pytree: the leading axis of every
    ``layers`` leaf is the period."""
    D, P, G = cfg.hidden_size, cfg.periods, cfg.gdn_per_period
    M = cfg.full_attention_interval         # expert blocks a period
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    Hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
    E, F, Fs = (cfg.num_experts, cfg.moe_intermediate_size,
                cfg.shared_expert_intermediate_size)
    shapes = {
        "embed": (cfg.vocab_size, D),
        "final_norm": (D,),
        "layers": {
            # Gated DeltaNet: [q; k; v; z] and [b; a] projections, the
            # depthwise conv over [q; k; v] (row K-1 multiplies the token
            # itself), the decay's two per-head vectors, the gated norm's
            # plain gain, the output projection
            "gdn_norm": (P, G, D),
            "gdn_in_qkvz": (P, G, D, cfg.conv_dim + cfg.value_dim),
            "gdn_in_ba": (P, G, D, 2 * Hv),
            "gdn_conv": (P, G, cfg.linear_conv_kernel_dim, cfg.conv_dim),
            "gdn_A_log": (P, G, Hv),
            "gdn_dt_bias": (P, G, Hv),
            "gdn_out_norm": (P, G, dv),
            "gdn_wo": (P, G, cfg.value_dim, D),
            # gated full attention: each q head's 2 hd columns are its q
            # and then its output gate
            "attn_norm": (P, D),
            "wq": (P, D, Hq * 2 * hd),
            "wk": (P, D, Hkv * hd),
            "wv": (P, D, Hkv * hd),
            "q_norm": (P, hd),
            "k_norm": (P, hd),
            "wo": (P, Hq * hd, D),
            # the expert block of each of the period's layers
            "mlp_norm": (P, M, D),
            "moe_gate": (P, M, D, cfg.router_width),
            "w_gate": (P, M, E, D, F),
            "w_up": (P, M, E, D, F),
            "w_down": (P, M, E, F, D),
            "shared_gate": (P, M, D, Fs),
            "shared_up": (P, M, D, Fs),
            "shared_down": (P, M, Fs, D),
            "shared_router": (P, M, D),
        },
    }
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


def init_leaf(key, shape, name: str, dtype, cfg=None):
    """One synthetic leaf, for models.llama.init_params' loop. Weights are
    N(0, 0.02) as models.llama's; a zero-centred gain is N(0, 0.02) (gain
    ~ 1), the gated norm's plain gain 1 + N(0, 0.02); ``A = exp(A_log)`` is
    uniform in [1, 16) as the published initialisation draws it, and
    ``dt_bias`` the inverse softplus of a step log-uniform in [0.001, 0.1]
    (the state-space convention), so that a token decays a head's state by
    exp(-0.001) .. exp(-1.6): states that remember, as trained ones do."""
    # one draw a leaf: the three uses of ``key`` are branches of one choice
    if name == "gdn_A_log":
        w = jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    elif name == "gdn_dt_bias":
        dt = jnp.exp(jax.random.uniform(  # jaxlint: disable=rng-key-reuse
            key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
        w = dt + jnp.log(-jnp.expm1(-dt))
    else:
        w = jax.random.normal(  # jaxlint: disable=rng-key-reuse
            key, shape, jnp.float32) * 0.02
        if name == "gdn_out_norm":
            w = 1.0 + w
    return w.astype(dtype)


def checkpoint_leaves(cfg: Qwen3NextConfig, get, body: str = "model."):
    """(leaf name, host array) for every ``layers`` leaf, one at a time, from
    an HF ``qwen3_next`` checkpoint; ``get(name)`` reads one tensor. Linear
    weights are transposed to right-multiply; layer ``i`` is position
    ``i % interval`` of period ``i // interval``. Three layouts differ from
    the served one: ``in_proj_qkvz`` / ``in_proj_ba`` are GROUPED by key head
    ([q, k, its value heads' v, their z] and [b, a] a key head) and are
    regrouped here to the flat [q; k; v; z] and [b; a]; the depthwise conv's
    ``[C, 1, K]`` becomes ``[K, C]``; of the published experts those of this
    rank are read (``ep_rank * num_experts ..``), the router whole."""
    import numpy as np

    P, G, M = cfg.periods, cfg.gdn_per_period, cfg.full_attention_interval
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv, rep = (cfg.linear_key_head_dim, cfg.linear_value_head_dim,
                   Hv // Hk)
    L = body + "layers.{i}."

    def stack(fmt: str, rows, fix=lambda a: a.T):
        """[P, len(rows), ...] (or [P, ...] for one row given as an int)."""
        one = isinstance(rows, int)
        out = [[fix(get(fmt.format(i=p * M + r)))
                for r in ([rows] if one else rows)] for p in range(P)]
        return np.stack([o[0] for o in out] if one else
                        [np.stack(o) for o in out])

    def flat_qkvz(w):           # [Hk (2 dk + 2 rep dv), D] -> [D, C + Hv dv]
        w = w.reshape(Hk, 2 * dk + 2 * rep * dv, -1)
        parts = np.split(w, [dk, 2 * dk, 2 * dk + rep * dv], axis=1)
        return np.concatenate(
            [x.reshape(-1, w.shape[-1]) for x in parts]).T

    def flat_ba(w):             # [Hk 2 rep, D] -> [D, 2 Hv]
        w = w.reshape(Hk, 2 * rep, -1)
        return np.concatenate(
            [x.reshape(-1, w.shape[-1]) for x in np.split(w, 2, axis=1)]).T

    gdn, full, every = range(G), G, range(M)
    A = L + "linear_attn."
    yield "gdn_norm", stack(L + "input_layernorm.weight", gdn, np.asarray)
    yield "gdn_in_qkvz", stack(A + "in_proj_qkvz.weight", gdn, flat_qkvz)
    yield "gdn_in_ba", stack(A + "in_proj_ba.weight", gdn, flat_ba)
    yield "gdn_conv", stack(A + "conv1d.weight", gdn, lambda a: a[:, 0, :].T)
    yield "gdn_A_log", stack(A + "A_log", gdn, np.asarray)
    yield "gdn_dt_bias", stack(A + "dt_bias", gdn, np.asarray)
    yield "gdn_out_norm", stack(A + "norm.weight", gdn, np.asarray)
    yield "gdn_wo", stack(A + "out_proj.weight", gdn)
    S = L + "self_attn."
    yield "attn_norm", stack(L + "input_layernorm.weight", full, np.asarray)
    for leaf, name in (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
                       ("wo", "o_proj")):
        yield leaf, stack(S + name + ".weight", full)
    yield "q_norm", stack(S + "q_norm.weight", full, np.asarray)
    yield "k_norm", stack(S + "k_norm.weight", full, np.asarray)
    X = L + "mlp."
    yield "mlp_norm", stack(L + "post_attention_layernorm.weight", every,
                            np.asarray)
    yield "moe_gate", stack(X + "gate.weight", every)
    held = range(cfg.ep_rank * cfg.num_experts,
                 (cfg.ep_rank + 1) * cfg.num_experts)
    for leaf, name in (("w_gate", "gate_proj"), ("w_up", "up_proj"),
                       ("w_down", "down_proj")):
        yield leaf, np.stack([
            stack(X + f"experts.{e}.{name}.weight", every) for e in held],
            axis=2)
        yield "shared_" + leaf[2:], stack(
            X + f"shared_expert.{name}.weight", every)
    yield "shared_router", stack(X + "shared_expert_gate.weight", every,
                                 lambda a: a[0])


# ---------------------------------------------------------------------------
# Recurrent state: two dense per-slot arrays beside the K/V pool
# ---------------------------------------------------------------------------

def init_rec(cfg: Qwen3NextConfig, num_slots: int) -> dict:
    """The DeltaNet layers' state for ``num_slots`` slots, all zero:
    ``S [P, G, slots, Hv, dk, dv]`` float32 and the conv's last K - 1 input
    rows ``conv [P, G, slots, K - 1, C]`` in the compute dtype."""
    P, G = cfg.periods, cfg.gdn_per_period
    return {
        "S": jnp.zeros((P, G, num_slots, cfg.linear_num_value_heads,
                        cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                       jnp.float32),
        "conv": jnp.zeros((P, G, num_slots, cfg.linear_conv_kernel_dim - 1,
                           cfg.conv_dim), jnp.dtype(cfg.dtype)),
        **xp.init_rec(),
    }


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def zc_norm(x, w, eps: float):
    """Zero-centred RMSNorm: float32 inside, gain ``1 + w``."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)
            * (1.0 + w.astype(jnp.float32))).astype(x.dtype)


def _l2norm(x):
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def gdn_step(S, q, k, v, g, beta):
    """One token of the gated delta rule, per value head, float32:
    S <- exp(g) S;  u = S^T k;  S <- S + k (x) (beta (v - u));  o = S^T q.
    S [..., dk, dv]; q, k [..., dk]; v [..., dv]; g, beta [...].

    Written so that S is read twice and written once: both products with
    the OLD state come from one pass over it (S^T k and S^T q), and the
    output follows from them, o = exp(g) S^T q + (k . q) d with
    d = beta (v - exp(g) S^T k), which is S_new^T q term by term."""
    decay = jnp.exp(g)[..., None]
    Sk, Sq = (jnp.einsum("...kv,...k->...v", S, x) for x in (k, q))
    d = beta[..., None] * (v - decay * Sk)
    o = decay * Sq + jnp.sum(k * q, axis=-1, keepdims=True) * d
    return decay[..., None] * S + k[..., :, None] * d[..., None, :], o


def recur(S0, q, k, v, g, beta, *, valid):
    """The recurrence as XLA from state S0 [B, Hv, dk, dv] over the tokens
    of q, k [B, T, Hv, dk], v [B, T, Hv, dv], g, beta [B, T, Hv]:
    (S after them, o [B, T, Hv, dv]). ``valid`` [B, T] marks each row's real
    tokens, a prefix: a chunk walks rows 0 .. n - 1, n the most real tokens
    of a row, and no others (a token that is not real is the identity on S;
    its ``o`` is zero and nobody reads it)."""
    if q.shape[1] == 1:     # the decode step: no loop
        S, o = gdn_step(S0, q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        return S, o[:, None]
    # a prefill chunk: the same recurrence, token by token, the trip count
    # read from the program's input
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta))

    def token(t, carry):
        S, o = carry
        S, o_t = gdn_step(S, *(lax.dynamic_index_in_dim(
            x, t, 0, keepdims=False) for x in xs))
        return S, lax.dynamic_update_index_in_dim(o, o_t, t, 0)

    S, o = lax.fori_loop(
        0, jnp.max(jnp.sum(valid, axis=1).astype(jnp.int32)), token,
        (S0, jnp.zeros(xs[2].shape, jnp.float32)))
    return S, jnp.moveaxis(o, 0, 1)


@functools.partial(jax.jit, static_argnums=(2, 3), inline=True)
def recur_in_place(S_all, p, g_idx: int, interpret: bool, q, k, v, g, beta):
    """``recur`` for the decode step (T = 1, batch row b is slot b) as
    ops.gdn's kernel on layer (p, g_idx) of the carried state ``S_all``
    [P, G, slots, Hv, dk, dv]: (``S_all`` with the layer's rows stepped, in
    place; o [B, 1, Hv, dv]). Behind ONE trace a (layer of the period,
    shape): inlined, so a program holds what it held, but the kernel's body
    (32 heads unrolled) is traced once a process and not once a program: a
    ride's step half is the decode step's. (A test that plants a fault in
    the kernel clears the kept trace: tests/conftest.py
    ``fresh_kernel_traces``.)"""
    from localai_tpu.ops import gdn

    S_all, o = gdn.gdn_state_step(
        S_all, p, g_idx, *(t[:, 0] for t in (q, k, v, g, beta)),
        interpret=interpret)
    return S_all, o[:, None]


def gated_norm(o, z, w, eps: float):
    """The DeltaNet's output norm, float32: the ONE norm with a plain gain
    (not 1 + w), and it norms BEFORE the gate silu(z)."""
    var = jnp.mean(o * o, axis=-1, keepdims=True)
    o = o * lax.rsqrt(var + eps) * w.astype(jnp.float32)
    return o * jax.nn.silu(z.astype(jnp.float32))


def _gdn_mix(cfg: Qwen3NextConfig, qkv, z, b, a, lp, g_idx: int, state_step,
             conv0, valid):
    """Where a DeltaNet layer's rows meet: the short convolution of the
    projected qkv [B, T, C] behind the conv's rows conv0 [B, K-1, C], and
    the recurrence ``state_step`` steps (``recur`` on the layer's S0, or the
    decode step's kernel on the carried array) under the gates b, a
    [B, T, Hv]; ``valid`` [B, T] marks the real tokens, a PREFIX of each
    row. The gated norm under z [B, T, Hv dv] goes with them: it fuses with
    the recurrence's output, which then rounds as it does in the program of
    these rows alone. Returns (o [B, T, Hv, dv] float32, normed and gated,
    ``state_step``'s state, conv)."""
    B, T, _ = qkv.shape
    Hk, Hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    K = cfg.linear_conv_kernel_dim
    with jax.named_scope("conv"):
        # [the slot's last K-1 inputs; the chunk's]: token t of the chunk is
        # row t + K - 1, and its output reads rows t .. t + K - 1
        cat = jnp.concatenate([conv0.astype(qkv.dtype), qkv], axis=1)
        w = lp["gdn_conv"][g_idx].astype(jnp.float32)
        conv = sum(cat[:, i:i + T].astype(jnp.float32) * w[i]
                   for i in range(K))
        qkv = jax.nn.silu(conv)                       # float32 from here on
        # the K-1 rows in front of the first token that is NOT real: after n
        # real tokens rows n .. n + K - 2 (n = 0 leaves the state as it was)
        n_real = jnp.sum(valid, axis=1).astype(jnp.int32)
        new_conv = jax.vmap(
            lambda rows, n: lax.dynamic_slice_in_dim(rows, n, K - 1, 0))(
                cat, n_real)
    with jax.named_scope("state"):
        q = qkv[..., :Hk * dk].reshape(B, T, Hk, dk)
        k = qkv[..., Hk * dk:2 * Hk * dk].reshape(B, T, Hk, dk)
        v = qkv[..., 2 * Hk * dk:].reshape(B, T, Hv, dv)
        q = jnp.repeat(_l2norm(q) * dk ** -0.5, Hv // Hk, axis=2)
        k = jnp.repeat(_l2norm(k), Hv // Hk, axis=2)
        beta = jax.nn.sigmoid(b.astype(jnp.float32))
        g = (-jnp.exp(lp["gdn_A_log"][g_idx].astype(jnp.float32))
             * jax.nn.softplus(a.astype(jnp.float32)
                               + lp["gdn_dt_bias"][g_idx].astype(jnp.float32)))
        # a token that is not real is the identity on S: no decay, no write
        g = jnp.where(valid[..., None], g, 0.0)
        beta = jnp.where(valid[..., None], beta, 0.0)
        S, o = state_step(q, k, v, g, beta)
    with jax.named_scope("out"):
        o = gated_norm(o, z.reshape(o.shape), lp["gdn_out_norm"][g_idx],
                       cfg.rms_norm_eps)
    return o, S, new_conv


def _gdn(cfg: Qwen3NextConfig, x, lp, at: tuple, flat, S_all, conv_all, valid,
         slot, fresh, kernels: Optional[bool], ride: int = 0):
    """DeltaNet layer ``at`` = (period, g) on the residual x [B, T, D]
    against the carried per-slot arrays ``S_all`` [P, G, slots, Hv, dk, dv]
    and ``conv_all`` [P, G, slots, K-1, C], both stepped in place; ``flat``
    the layers' two large projections (``GDN_FLAT``) as [P G, ...]. Returns
    (out [B, T, D], ``S_all``, ``conv_all``).

    The norm, the projections and the output product are per row and run
    once; rows meet in ``_gdn_mix`` alone, which takes a decode
    step's rows (``slot`` None: batch row b is slot b) against every slot's
    state, as ops.gdn's kernel on the carried array where ``kernels`` says
    so, and ONE slot's chunk against that slot's, from zero where ``fresh``.
    ``ride`` (the contract's third case; x [1, ride + S, D]) is the two side
    by side, each half in the shape its own program gives it: the step's S
    rows first, for every slot, then the chunk's ``ride`` rows against what
    that left in ``slot``'s state (its own step row is not real and left it
    as it was), and the two outputs laid end to end."""
    p, g_idx = at
    C, Hv, dv = (cfg.conv_dim, cfg.linear_num_value_heads,
                 cfg.linear_value_head_dim)
    kernel = kernels is not None

    # a half is ``part`` of the rows of a [B, T, ...] against the state of
    # ``of`` (a slot, or None: every slot's, a row each), ``fused`` where the
    # step's ONE kernel a layer runs on the carried state, which is then
    # never sliced. The per-slot arrays are read and written under the scope
    # of the recurrence: ``gdn/state`` is all that moves state
    def read(S_all, conv_all, part, of, fused):
        with jax.named_scope("state"):
            S0 = None if fused else mdl.rec_read(S_all, at, of)
            conv0 = mdl.rec_read(conv_all, at, of)
            if of is not None and fresh is not None:    # a chunk
                S0 = jnp.where(fresh, 0.0, S0)
                conv0 = jnp.where(fresh, 0, conv0).astype(conv0.dtype)
            if fused:
                return functools.partial(recur_in_place, S_all, *at,
                                         kernels), conv0
            return functools.partial(recur, S0, valid=part(valid)), conv0

    def write(S_all, conv_all, S, conv, of, fused):
        with jax.named_scope("state"):
            return (S if fused else mdl.rec_write(S_all, S, at, of),
                    mdl.rec_write(conv_all, conv, at, of))

    def mix(part, state_step, conv0):
        return _gdn_mix(cfg, *(part(t) for t in (qkv, z, b, a)), lp, g_idx,
                        state_step, conv0, part(valid))

    if ride:        # the step's half first
        def chunk(t):
            return t[:, :ride]

        def step(t):
            return t[0, ride:, None]

        state = read(S_all, conv_all, step, None, kernel)
    else:
        def whole(t):
            return t

        fused = kernel and slot is None and x.shape[1] == 1
        state = read(S_all, conv_all, whole, slot, fused)
    h = zc_norm(x, lp["gdn_norm"][g_idx], cfg.rms_norm_eps)
    w_in, w_out = (lax.dynamic_index_in_dim(
        w, p * cfg.gdn_per_period + g_idx, 0, keepdims=False) for w in flat)
    with jax.named_scope("proj"):
        qkvz = qnt.matmul(h, w_in)
        ba = qnt.matmul(h, lp["gdn_in_ba"][g_idx])
        qkvz, ba = lax.optimization_barrier((qkvz, ba))
        qkv, z = qkvz[..., :C], qkvz[..., C:]
        b, a = ba[..., :Hv], ba[..., Hv:]
    if ride:        # the step's rows are written before the chunk's reads
        o_step, S, conv = mix(step, *state)
        S_all, conv_all = write(S_all, conv_all, S, conv, None, kernel)
        fused = False
        o, S, conv = mix(chunk, *read(S_all, conv_all, chunk, slot, fused))
        # the chunk's rows in front, a step's row a slot behind
        o = jnp.concatenate([o, o_step[:, 0][None]], axis=1)
    else:
        o, S, conv = mix(whole, *state)
    with jax.named_scope("out"):
        out = qnt.matmul(o.astype(h.dtype).reshape(*z.shape[:2], Hv * dv),
                         w_out)
    return (out, *write(S_all, conv_all, S, conv, slot, fused))


def _partial_rope(x, cos, sin, rot: int):
    """Rotate-half RoPE on the first ``rot`` dims of each head."""
    return jnp.concatenate(
        [mdl.apply_rope(x[..., :rot], cos, sin), x[..., rot:]], axis=-1)


def _full_attention(cfg: Qwen3NextConfig, h, lp, cos, sin, attend):
    """The gated full-attention mixer on normed h [B, T, D]."""
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    with jax.named_scope("attn.qkv"):
        qg = qnt.matmul(h, lp["wq"])
        k = qnt.matmul(h, lp["wk"])
        v = qnt.matmul(h, lp["wv"])
        # the head split stays off the dots (models.llama._layer says why)
        qg, k, v = lax.optimization_barrier((qg, k, v))
        qg = qg.reshape(*qg.shape[:-1], Hq, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
        k = k.reshape(*k.shape[:-1], Hkv, hd)
        v = v.reshape(*v.shape[:-1], Hkv, hd)
        q = zc_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = zc_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    with jax.named_scope("attn.rope"):
        q = _partial_rope(q, cos, sin, cfg.rotary_dim)
        k = _partial_rope(k, cos, sin, cfg.rotary_dim)
    attn, new_kv = attend(q, k, v)
    with jax.named_scope("attn_gate"):
        attn = mdl.output_gate(attn, gate)
    with jax.named_scope("attn.out"):
        out = qnt.matmul(attn.reshape(*attn.shape[:-2], Hq * hd), lp["wo"])
    return out, new_kv


def shared_expert(h, lp, m_idx: int):
    """The shared expert of block m on h [N, D], under its sigmoid gate
    (``shared_router``: D -> 1); float32."""
    y = (jax.nn.silu(qnt.matmul(h, lp["shared_gate"][m_idx]))
         * qnt.matmul(h, lp["shared_up"][m_idx]))
    y = qnt.matmul(y, lp["shared_down"][m_idx])
    gate = jax.nn.sigmoid(jnp.sum(
        h.astype(jnp.float32) * lp["shared_router"][m_idx].astype(jnp.float32),
        axis=-1, keepdims=True))
    return gate * y.astype(jnp.float32)


def _moe(cfg: Qwen3NextConfig, h, lp, m_idx: int, experts, p, valid,
         experts_kernel: Optional[bool] = None):
    """Expert block m of period p on normed h [B, T, D] (models.experts
    ``moe_block``, the routing and dispatch this family shares with
    models.afmoe): softmax scores, the shared expert under its gate.
    Returns (out, [experts touched, token-expert pairs here] i32)."""
    shape = h.shape
    out, n_touched, load = xp.moe_block(
        h.reshape(-1, shape[-1]), lp["moe_gate"][m_idx],
        xp.softmax_scores(cfg.num_experts_per_tok, cfg.norm_topk_prob),
        experts, p, m_idx, num_experts=cfg.num_experts, ep_rank=cfg.ep_rank,
        valid=valid.reshape(-1),
        shared=lambda h: shared_expert(h, lp, m_idx),
        experts_kernel=experts_kernel)
    return out.reshape(shape), xp.counts(n_touched, load)


EXPERT_LEAVES = xp.EXPERT_LEAVES
# a DeltaNet layer's two large projections: [P, G, ...] leaves that the
# forward reads as [P G, ...]
GDN_FLAT = ("gdn_in_qkvz", "gdn_wo")


def forward(
    cfg: Qwen3NextConfig,
    params: Any,
    tokens: jax.Array,      # [B, T] i32
    positions: jax.Array,   # [B, T] i32
    kv_write: Any,          # engine.kvcache write policy, cache layer = period
    kv_stack: Any,          # stacked K/V of the full-attention layers
    mask: jax.Array,
    rope: tuple[jax.Array, jax.Array],
    attn: Any = None,
    embeds: Optional[jax.Array] = None,
    *,
    rec: dict,              # init_rec's arrays
    valid: jax.Array,       # [B, T] bool: the real tokens, a prefix a row
    slot: Any = None,       # the decode step's rows or ONE slot's chunk, and
    fresh: Any = None,      # whether that starts from zero state: the
                            # contract (models.llama ``family_module``)
    kernels: Optional[bool] = None,     # None: the experts' walk and the
                            # DeltaNet's decode step are XLA; else ops.moe's
                            # and ops.gdn's kernels (the value: interpreted)
    ride: int = 0,          # rows of ``slot``'s chunk in front of a decode
                            # step's S, [1, ride + S] in all: the contract
) -> tuple[jax.Array, Any, dict, jax.Array]:
    """models.llama.forward for this family: (hidden [B, T, D], new K/V
    stack, new ``rec``, [experts touched, token-expert pairs] summed over
    the expert blocks). One ``lax.scan`` over the periods; (x, K/V, rec) is
    its carry, so both caches are written in place. Everything but the
    DeltaNet's convolution and recurrence is per row (the full-attention
    layer's rows meet in the attend, which a ride's composite policy
    splits), so a ride's rows go through as any batch's."""
    cos, sin = mdl.rope_rows(rope, positions)
    x = mdl.embed(cfg, params, tokens, embeds)
    if attn is None:
        attn = mdl.xla_attend(cfg, positions)
    layers = params["layers"]
    # the expert stacks stay OUT of the scanned operands: a scanned slice of
    # them would be a period's experts (1.6 GB at the published widths)
    # staged for the walk over the touched ones
    experts = tuple(layers[n] for n in EXPERT_LEAVES)
    # nor do the DeltaNet's large projections: the scan's slice of a
    # [P, G, ...] leaf is the period's G layers, copied whole (0.45 GB a step
    # at the published widths) before a layer's is taken. Flat (a bitcast)
    # and indexed by p G + g, the dot reads its layer in place
    flat = tuple(layers[n].reshape(-1, *layers[n].shape[2:])
                 for n in GDN_FLAT)
    scanned = {n: w for n, w in layers.items()
               if n not in EXPERT_LEAVES + GDN_FLAT}
    eps, G = cfg.rms_norm_eps, cfg.gdn_per_period

    def period(carry, xs):
        x, kv, S_all, conv_all, counts = carry
        lp, p = xs

        def moe(x, m_idx, counts):
            with jax.named_scope("moe"):
                h = zc_norm(x, lp["mlp_norm"][m_idx], eps)
                out, c = _moe(cfg, h, lp, m_idx, experts, p, valid, kernels)
            return x + out, counts + c

        for g_idx in range(G):
            with jax.named_scope("gdn"):
                out, S_all, conv_all = _gdn(
                    cfg, x, lp, (p, g_idx), flat, S_all, conv_all, valid,
                    slot, fresh, kernels, ride)
                x = x + out
            x, counts = moe(x, g_idx, counts)

        h = zc_norm(x, lp["attn_norm"], eps)
        out, kv = _full_attention(cfg, h, lp, cos, sin, mdl.attend_through(
            kv_write, attn, mask, kv, p))
        x = x + out
        x, counts = moe(x, G, counts)
        return (x, kv, S_all, conv_all, counts), None

    with jax.named_scope("layers"):
        (x, kv_stack, S_all, conv_all, counts), _ = lax.scan(
            period,
            (x, kv_stack, rec["S"], rec["conv"], jnp.zeros(2, jnp.int32)),
            (scanned, jnp.arange(cfg.periods, dtype=jnp.int32)))
    with jax.named_scope("final_norm"):
        x = zc_norm(x, params["final_norm"], eps)
    return x, kv_stack, {**rec, "S": S_all, "conv": conv_all}, counts
