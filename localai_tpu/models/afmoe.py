"""AFMoE (``model_type: afmoe``; Arcee Trinity): a decoder whose attention
layers come in two KINDS in one stack, ``sliding_attention`` (a window of
``sliding_window`` keys, rotate-half RoPE) and every
``global_attn_every_n_layers``-th ``full_attention`` (causal, NO positional
encoding), all of one parameter shape; ``num_dense_layers`` dense layers in
front of layers with sigmoid-routed experts and an ungated shared expert;
four norms a layer (each branch's input and OUTPUT), q/k norm, a per-element
sigmoid gate on the attention output from a projection of its own, and
embeddings scaled by sqrt(hidden_size).

What the serving engine holds of it (engine.runner):

  * the dense layers are a PREFIX of their own: top-level leaves
    ``dense_*`` ``[n_dense, ...]``, run one after the other. The expert
    layers are ROWS of ``global_attn_every_n_layers`` consecutive layers
    whose kinds are the same in every row (checked when the config is
    built): every ``layers`` leaf is ``[rows, M, ...]``, the one
    ``lax.scan`` of the forward runs over rows and unrolls the layers of
    one, their kinds static. No leaf is a scanned operand: each is read in
    place at ``row * M + m`` (a scanned slice would stage a row's four
    layers, or its experts, before a layer's is taken);
  * every layer caches K/V in the one paged pool under its own index
    (``cache_layers`` = ``num_hidden_layers``); the pool, the block
    allocator and whole-block prefix sharing are every other model's. The
    ATTEND is chosen by the layer's kind: the runner hands ``forward`` an
    attend and a mask for each of ``attn_kinds``, a window layer's decode
    call reads its window's blocks alone and its prefill chunk gathers
    ``window + bucket`` positions (engine.kvcache ``window_attend``). A
    window layer's rows outside the window stay allocated;
  * the expert block is models.experts' (the routing and dispatch
    models.qwen3_next shares): sigmoid scores, the bias inside the
    selection and outside the weight, ``route_norm``, ``route_scale``; TOLD
    which experts it holds (``expert_parallel: {size, rank}``).

The plain reference is benchmark/reference/afmoe_family.py, and
tests/test_afmoe.py holds this file to it. Scopes: ``attn.qkv``,
``attn.rope`` (window layers), ``attn_gate``, ``attn.out``, ``dense_mlp``,
``moe/{router,experts,shared}``; the attends bring their own.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Optional

import jax
import jax.numpy as jnp
from jax import lax

from localai_tpu.models import experts as xp
from localai_tpu.models import llama as mdl
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import LlamaConfig

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig(LlamaConfig):
    """``LlamaConfig`` with the keys the family adds. ``num_experts`` is the
    number of routed experts HELD here; the router's width is
    ``num_experts * ep_size``."""

    layer_types: tuple = ()
    num_dense_layers: int = 0
    global_attn_every_n_layers: int = 4
    moe_intermediate_size: int = 0
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 1.0
    mup_enabled: bool = False
    ep_size: int = 1          # chips that share a layer's routed experts
    ep_rank: int = 0          # which of them this is

    family: ClassVar[str] = "afmoe"
    routed: ClassVar[bool] = True

    def __post_init__(self):
        M = self.global_attn_every_n_layers
        if len(self.layer_types) != self.num_layers or set(
                self.layer_types) - {WINDOW, FULL}:
            raise ValueError(
                f"afmoe: layer_types names {len(self.layer_types)} layers of "
                f"kinds {sorted(set(self.layer_types))}; num_hidden_layers "
                f"is {self.num_layers} and the kinds served are "
                f"{WINDOW} and {FULL}")
        if (self.num_layers - self.num_dense_layers) % M:
            raise ValueError(
                f"afmoe serves whole rows of global_attn_every_n_layers "
                f"{M} expert layers (a pipeline stage holds whole rows): "
                f"{self.num_layers} layers less {self.num_dense_layers} "
                f"dense ones leave {self.num_layers - self.num_dense_layers}")
        rows = [self.layer_types[i:i + M]
                for i in range(self.num_dense_layers, self.num_layers, M)]
        if any(r != rows[0] for r in rows):
            raise ValueError(
                f"afmoe: the rows of {M} expert layers differ in their "
                f"layer kinds ({sorted(set(rows))}); one scanned row body "
                f"serves rows that are alike")
        if WINDOW in self.layer_types and not self.sliding_window:
            raise ValueError("afmoe: sliding_attention layers and no "
                             "sliding_window")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"expert_parallel rank {self.ep_rank} outside "
                             f"size {self.ep_size}")

    @property
    def rows(self) -> int:
        return ((self.num_layers - self.num_dense_layers)
                // self.global_attn_every_n_layers)

    @property
    def row_kinds(self) -> tuple:
        """Kinds of a row's layers, in order: the same in every row."""
        nd = self.num_dense_layers
        return tuple(self.layer_types[
            nd:nd + self.global_attn_every_n_layers])

    @property
    def attn_kinds(self) -> tuple:
        """(kind, window) of the attends a forward is handed."""
        return tuple((k, self.sliding_window if k == WINDOW else None)
                     for k in (WINDOW, FULL) if k in self.layer_types)

    @property
    def router_width(self) -> int:
        return self.num_experts * self.ep_size

    @classmethod
    def from_hf(cls, hf: dict) -> "AfmoeConfig":
        """From published keys. ``expert_parallel: {size, rank}`` is no
        published key: it states the deployment's share (``num_experts`` is
        then what ONE of ``size`` chips holds)."""
        groups = {k: hf.get(k, 1) for k in (
            "n_group", "topk_group", "num_expert_groups",
            "num_limited_groups")}
        if set(groups.values()) != {1}:
            raise ValueError(f"afmoe: routing over expert groups is not "
                             f"served ({groups}); every one of them is 1 in "
                             f"the published configurations")
        if hf.get("score_func", "sigmoid") != "sigmoid":
            raise ValueError(f"afmoe: score_func {hf['score_func']!r} is "
                             f"not served; the family's router is sigmoid")
        ep = hf.get("expert_parallel") or {}
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim"),
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            rope_scaling=hf.get("rope_scaling"),
            sliding_window=hf.get("sliding_window"),
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            layer_types=tuple(hf["layer_types"]),
            num_dense_layers=hf.get("num_dense_layers", 0),
            global_attn_every_n_layers=hf.get(
                "global_attn_every_n_layers", 4),
            moe_intermediate_size=hf["moe_intermediate_size"],
            num_shared_experts=hf.get("num_shared_experts", 1),
            route_norm=hf.get("route_norm", True),
            route_scale=float(hf.get("route_scale", 1.0)),
            mup_enabled=hf.get("mup_enabled", False),
            ep_size=int(ep.get("size", 1)),
            ep_rank=int(ep.get("rank", 0)),
        )


CONFIG = AfmoeConfig
# what the family does not serve, the weight modes it does, and why
# (models.llama ``refusal``)
UNSERVED = mdl.ONE_CHIP_POOL
WEIGHTS = ()
WHY = ("model_type afmoe: its window and full attention layers read one "
       "bfloat16 paged K/V pool through an attend chosen by the layer's "
       "kind, on one chip")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

DENSE = "dense_"        # a dense-prefix leaf: top level, ``[n_dense, ...]``
# the selection bias is float32 as published, whatever the compute dtype
FLOAT32_LEAVES = ("expert_bias",)


def _attention_shapes(cfg: AfmoeConfig, lead: tuple) -> dict:
    D, Hq, Hkv, hd = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                      cfg.hd)
    return {
        "attn_norm": (*lead, D), "attn_post_norm": (*lead, D),
        "wq": (*lead, D, Hq * hd), "wk": (*lead, D, Hkv * hd),
        "wv": (*lead, D, Hkv * hd), "wg": (*lead, D, Hq * hd),
        "wo": (*lead, Hq * hd, D),
        "q_norm": (*lead, hd), "k_norm": (*lead, hd),
        "mlp_norm": (*lead, D), "mlp_post_norm": (*lead, D),
    }


def param_shapes(cfg: AfmoeConfig) -> dict:
    """Shapes of the parameter pytree: the dense prefix's leaves at the top
    level (``dense_*``, leading axis the dense layer), the expert layers'
    under ``layers`` (leading axes row and place in the row)."""
    D, F = cfg.hidden_size, cfg.intermediate_size
    R, M, nd = cfg.rows, cfg.global_attn_every_n_layers, cfg.num_dense_layers
    E, Fm = cfg.num_experts, cfg.moe_intermediate_size
    Fs = Fm * cfg.num_shared_experts
    shapes = {"embed": (cfg.vocab_size, D), "final_norm": (D,)}
    if nd:
        shapes.update({DENSE + n: s for n, s in {
            **_attention_shapes(cfg, (nd,)),
            "w_gate": (nd, D, F), "w_up": (nd, D, F), "w_down": (nd, F, D),
        }.items()})
    shapes["layers"] = {
        **_attention_shapes(cfg, (R, M)),
        "moe_gate": (R, M, D, cfg.router_width),
        "expert_bias": (R, M, cfg.router_width),
        "w_gate": (R, M, E, D, Fm), "w_up": (R, M, E, D, Fm),
        "w_down": (R, M, E, Fm, D),
        "shared_gate": (R, M, D, Fs), "shared_up": (R, M, D, Fs),
        "shared_down": (R, M, Fs, D),
    }
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


# The synthetic norm gains and bias are drawn as a trained checkpoint's lie,
# not at 1 / wide: q/k norm gains over 1 (attention that is PEAKED: a
# position's output depends on which keys it sees, so the tokens of a batch
# differ and route apart), a few OUTLIER channels in the gains of the norms
# that feed the attention projections and the head (what makes a
# lower-precision ACTIVATION lossy: a per-token int8 scale follows the
# outlier, bfloat16's relative rounding does not care), and a selection bias
# small beside the scores' spacing at the top (ds/dlogit is ~0.05 there: a
# bias of 0.05 moved every token to the same few experts). The norm in front
# of the router and the experts keeps gain 1: outliers there make the 26
# letters a benchmark's streams decode route alike.
QK_NORM_GAIN = 1.5          # scores' spread x 2.25
OUTLIER_GAIN, OUTLIER_EVERY = mdl.OUTLIER_GAIN, mdl.OUTLIER_EVERY
BIAS_STD = 0.01
OUTLIER_NORMS = ("attn_norm", "final_norm")


def init_leaf(key, shape, name: str, dtype, cfg=None):
    """One synthetic leaf, for models.llama.init_params' loop: matrices
    N(0, 0.02) as models.llama's; norm gains 1, but ``QK_NORM_GAIN`` on q
    and k and ``OUTLIER_GAIN`` on a seeded ``1 / OUTLIER_EVERY`` of the
    channels of ``OUTLIER_NORMS`` (none under 192 channels); the selection
    bias N(0, ``BIAS_STD``) in float32: small and NOT zero, so that a
    program that weighs with the bias, or selects without it, disagrees
    with the reference."""
    base = name.removeprefix(DENSE)
    if base in ("q_norm", "k_norm"):
        return jnp.full(shape, QK_NORM_GAIN, dtype)
    if base in OUTLIER_NORMS and shape[-1] >= OUTLIER_EVERY:
        u = jax.random.uniform(key, shape)
        kth = lax.top_k(u, shape[-1] // OUTLIER_EVERY)[0][..., -1:]
        return jnp.where(u >= kth, OUTLIER_GAIN, 1.0).astype(dtype)
    if name.endswith("norm"):
        return jnp.ones(shape, dtype)
    bias = name in FLOAT32_LEAVES
    # another branch of the one draw a leaf makes, not a second use
    w = jax.random.normal(  # jaxlint: disable=rng-key-reuse
        key, shape, jnp.float32) * (BIAS_STD if bias else 0.02)
    return w if bias else w.astype(dtype)


def checkpoint_leaves(cfg: AfmoeConfig, get, body: str = "model."):
    """(leaf name, host array) for every dense-prefix and ``layers`` leaf,
    one at a time, from an HF ``afmoe`` checkpoint; ``get(name)`` reads one
    tensor. Linear weights are transposed to right-multiply; of the
    published experts those of this rank are read, router and bias whole."""
    import numpy as np

    R, M, nd = cfg.rows, cfg.global_attn_every_n_layers, cfg.num_dense_layers
    L = body + "layers.{i}."
    names = {
        "attn_norm": ("input_layernorm.weight", False),
        "attn_post_norm": ("post_attention_layernorm.weight", False),
        "mlp_norm": ("pre_mlp_layernorm.weight", False),
        "mlp_post_norm": ("post_mlp_layernorm.weight", False),
        "wq": ("self_attn.q_proj.weight", True),
        "wk": ("self_attn.k_proj.weight", True),
        "wv": ("self_attn.v_proj.weight", True),
        "wg": ("self_attn.gate_proj.weight", True),
        "wo": ("self_attn.o_proj.weight", True),
        "q_norm": ("self_attn.q_norm.weight", False),
        "k_norm": ("self_attn.k_norm.weight", False),
    }

    def one(i: int, tail: str, transpose: bool):
        a = get(L.format(i=i) + tail)
        return a.T if transpose else np.asarray(a)

    def dense(tail, transpose):
        return np.stack([one(i, tail, transpose) for i in range(nd)])

    def rows(tail, transpose):
        return np.stack([np.stack([one(nd + r * M + m, tail, transpose)
                                   for m in range(M)]) for r in range(R)])

    mlp = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
    if nd:
        for leaf, src in names.items():
            yield DENSE + leaf, dense(*src)
        for leaf, name in mlp.items():
            yield DENSE + leaf, dense(f"mlp.{name}.weight", True)
    for leaf, src in names.items():
        yield leaf, rows(*src)
    yield "moe_gate", rows("mlp.router.gate.weight", True)
    yield "expert_bias", rows("mlp.expert_bias", False)
    held = range(cfg.ep_rank * cfg.num_experts,
                 (cfg.ep_rank + 1) * cfg.num_experts)
    for leaf, name in mlp.items():
        yield leaf, np.stack([
            rows(f"mlp.experts.{e}.{name}.weight", True) for e in held],
            axis=2)
        yield "shared_" + leaf[2:], rows(
            f"mlp.shared_experts.{name}.weight", True)


# no per-slot state beside the pool: the routed count alone
init_rec = xp.init_rec


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def embed_scale(cfg: AfmoeConfig) -> float:
    """What the token embeddings are multiplied by (``mup_enabled``)."""
    return math.sqrt(cfg.hidden_size) if cfg.mup_enabled else 1.0


def post_norm(x, w, eps: float):
    """The norm on a branch's OUTPUT (the sandwich's second slice)."""
    return mdl.rms_norm(x, w, eps)


def scores(cfg: AfmoeConfig, bias):
    """The family's scoring rule over a block's selection ``bias``."""
    return xp.sigmoid_scores(cfg.num_experts_per_tok, bias, cfg.route_norm,
                             cfg.route_scale)


def rope_on(kind: str) -> bool:
    """RoPE is the window layers' alone: a full layer has no positional
    encoding."""
    return kind == WINDOW


def _attention(cfg: AfmoeConfig, h, w, cos, sin, attend, kind: str):
    """The gated attention mixer on normed h [B, T, D]; ``w(name)`` reads
    one of the layer's leaves."""
    Hq, Hkv, hd, eps = (cfg.num_heads, cfg.num_kv_heads, cfg.hd,
                        cfg.rms_norm_eps)
    with jax.named_scope("attn.qkv"):
        q = qnt.matmul(h, w("wq"))
        k = qnt.matmul(h, w("wk"))
        v = qnt.matmul(h, w("wv"))
        gate = qnt.matmul(h, w("wg"))
        # the head split stays off the dots (models.llama._layer says why)
        q, k, v, gate = lax.optimization_barrier((q, k, v, gate))
        q = q.reshape(*q.shape[:-1], Hq, hd)
        k = k.reshape(*k.shape[:-1], Hkv, hd)
        v = v.reshape(*v.shape[:-1], Hkv, hd)
        q = mdl.rms_norm(q, w("q_norm"), eps)
        k = mdl.rms_norm(k, w("k_norm"), eps)
    if rope_on(kind):
        with jax.named_scope("attn.rope"):
            q = mdl.apply_rope(q, cos, sin)
            k = mdl.apply_rope(k, cos, sin)
    attn, new_kv = attend(q, k, v)
    with jax.named_scope("attn_gate"):
        attn = mdl.output_gate(attn, gate.reshape(attn.shape))
    with jax.named_scope("attn.out"):
        out = qnt.matmul(attn.reshape(*attn.shape[:-2], Hq * hd), w("wo"))
    return out, new_kv


def forward(
    cfg: AfmoeConfig,
    params: Any,
    tokens: jax.Array,      # [B, T] i32
    positions: jax.Array,   # [B, T] i32
    kv_write: Any,          # engine.kvcache write policy, cache layer = layer
    kv_stack: Any,          # stacked K/V of every layer
    mask: dict,             # {kind: mask} for each of ``cfg.attn_kinds``
    rope: tuple[jax.Array, jax.Array],
    attn: Optional[dict] = None,    # {kind: fn(q, keys, values, mask)}: the
                                    # runner's attends by kind; None = XLA
    embeds: Optional[jax.Array] = None,
    *,
    rec: Any = None,        # handed back as it came: no per-slot state
    valid: jax.Array,       # [B, T] bool: the real tokens
    slot: Any = None,       # (a recurrent family's: models.llama
    fresh: Any = None,      # ``family_module`` has the contract)
    kernels: Optional[bool] = None,     # models.experts.moe_block's
                            # ``experts_kernel``
) -> tuple[jax.Array, Any, Any, jax.Array]:
    """models.llama.forward for this family: (hidden [B, T, D], new K/V
    stack, ``rec``, [experts touched, token-expert pairs] summed over the
    expert blocks). The dense prefix layer by layer, then one ``lax.scan``
    over the rows; (x, K/V) is its carry, so the cache is written in
    place."""
    cos, sin = mdl.rope_rows(rope, positions)
    x = mdl.embed(cfg, params, tokens, embeds, embed_scale(cfg))
    if attn is None:
        xla_attn = mdl.xla_attend(cfg, positions)
        attn = {kind: xla_attn for kind, _ in cfg.attn_kinds}
    eps, M, nd = (cfg.rms_norm_eps, cfg.global_attn_every_n_layers,
                  cfg.num_dense_layers)

    def mixer(x, kv, w, layer, kind):
        """x + N(Attn(N(x))) of cache layer ``layer``, a ``kind`` layer."""
        h = mdl.rms_norm(x, w("attn_norm"), eps)
        out, kv = _attention(cfg, h, w, cos, sin, mdl.attend_through(
            kv_write, attn[kind], mask[kind], kv, layer), kind)
        return x + post_norm(out, w("attn_post_norm"), eps), kv

    with jax.named_scope("layers"):
        for i in range(nd):
            def w(name, i=i):
                return params[DENSE + name][i]

            x, kv_stack = mixer(x, kv_stack, w, jnp.int32(i),
                                cfg.layer_types[i])
            with jax.named_scope("dense_mlp"):
                h = mdl.rms_norm(x, w("mlp_norm"), eps)
                out = xp.swiglu(h, w("w_gate"), w("w_up"), w("w_down"))
                x = x + post_norm(out, w("mlp_post_norm"), eps)

        layers = params["layers"]
        experts = tuple(layers[n] for n in xp.EXPERT_LEAVES)
        # [rows, M, ...] read as [rows M, ...] (a bitcast) at row M + m
        flat = {n: a.reshape(-1, *a.shape[2:]) for n, a in layers.items()
                if n not in xp.EXPERT_LEAVES}

        def row(carry, r):
            x, kv, counts = carry
            for m, kind in enumerate(cfg.row_kinds):
                def w(name, m=m):
                    return lax.dynamic_index_in_dim(
                        flat[name], r * M + m, 0, keepdims=False)

                x, kv = mixer(x, kv, w, nd + r * M + m, kind)
                with jax.named_scope("moe"):
                    h = mdl.rms_norm(x, w("mlp_norm"), eps)
                    out, n_touched, load = xp.moe_block(
                        h.reshape(-1, h.shape[-1]), w("moe_gate"),
                        scores(cfg, w("expert_bias")), experts, r, m,
                        num_experts=cfg.num_experts, ep_rank=cfg.ep_rank,
                        valid=valid.reshape(-1),
                        shared=lambda h, w=w: xp.shared_expert(
                            h, w("shared_gate"), w("shared_up"),
                            w("shared_down")),
                        experts_kernel=kernels)
                    out = post_norm(out.reshape(x.shape),
                                    w("mlp_post_norm"), eps)
                x = x + out
                counts = counts + xp.counts(n_touched, load)
            return (x, kv, counts), None

        (x, kv_stack, counts), _ = lax.scan(
            row, (x, kv_stack, jnp.zeros(2, jnp.int32)),
            jnp.arange(cfg.rows, dtype=jnp.int32))
    with jax.named_scope("final_norm"):
        x = mdl.rms_norm(x, params["final_norm"], eps)
    return x, kv_stack, rec, counts
