"""SmallThinker (``model_type: smallthinker``; PowerInfer
SmallThinker-21B-A3B): a decoder of two norms a layer whose ROUTER STANDS IN
FRONT OF ATTENTION: a layer's experts are chosen from the attention's normed
input and spent a branch later, on the post-attention normed tensor. The
attention layers come in two KINDS in one stack, by two published lists
(``sliding_window_layout``, ``rope_layout``): a layer with a window of
``sliding_window_size`` keys and rotate-half RoPE, and a FULL layer, causal
over every key with NO positional encoding; no q/k norm, no gate, no bias.
Every layer has ``moe_num_primary_experts`` ReLU-gated experts
(``relu(gate) * up``, ReGLU), ``moe_num_active_primary_experts`` a token, a
choice weighing softmax over the chosen logits; no shared expert, no dense
layer, no selection bias.

What the serving engine holds of it (engine.runner):

  * layers are ROWS of one period: the shortest run of layers whose kinds
    repeat through the stack (the published lists are ``0 1 1 1`` thirteen
    times: a row is FULL, WINDOW, WINDOW, WINDOW, the full layer FIRST).
    Every ``layers`` leaf is ``[L, ...]``, a LAYER its leading index (the
    benchmark's reference check copies one leading index of every leaf out
    of the stack before it reads an expert of it: a row of M layers there
    is M x 64 experts, 2.8 GiB at the published widths, which does not fit
    beside the weights; a layer's are 0.7); the one ``lax.scan`` of the
    forward runs over rows and unrolls the layers of one, their kinds
    static; each leaf is read in place at ``row * M + m`` (models.afmoe says
    why), the experts' through the bitcast ``[rows, M, E, ...]``;
  * every layer caches K/V in the one paged pool under its own index; the
    ATTEND is chosen by the layer's kind (``attn_kinds``; engine.kvcache
    ``kind_views``, ``window_attend``), as models.afmoe's. 28 query heads
    ride 4 K/V heads in groups of 7;
  * the expert block is models.experts' in its two halves: ``route`` on
    the attention's input, under ``moe/router`` in front of ``attn.qkv``,
    and ``walk`` behind attention with the ReLU gate; TOLD which experts it
    holds (``expert_parallel: {size, rank}``; absent: all of them, the
    layer's sum whole).

The plain reference is benchmark/reference/smallthinker_family.py, and
tests/test_smallthinker.py holds this file to it. Scopes: ``moe/router``,
``attn.qkv``, ``attn.rope`` (window layers), ``attn.out``, ``moe/experts``;
the attends bring their own.
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


def period(kinds: tuple) -> int:
    """Layers in a row: the shortest M that divides the depth with
    ``kinds[i] == kinds[i % M]`` for every layer."""
    n = len(kinds)
    return next(m for m in range(1, n + 1)
                if n % m == 0 and all(kinds[i] == kinds[i % m]
                                      for i in range(n)))


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig(LlamaConfig):
    """``LlamaConfig`` with the keys the family adds. ``num_experts`` is the
    number of routed experts HELD here; the router's width is
    ``num_experts * ep_size``."""

    layer_types: tuple = ()
    moe_intermediate_size: int = 0
    ep_size: int = 1          # chips that share a layer's routed experts
    ep_rank: int = 0          # which of them this is

    family: ClassVar[str] = "smallthinker"
    routed: ClassVar[bool] = True

    def __post_init__(self):
        if len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"smallthinker: sliding_window_layout names "
                f"{len(self.layer_types)} layers; num_hidden_layers is "
                f"{self.num_layers}")
        if WINDOW in self.layer_types and not self.sliding_window:
            raise ValueError("smallthinker: window layers and no "
                             "sliding_window_size")
        if self.num_heads % self.num_kv_heads:
            raise ValueError(
                f"smallthinker: {self.num_heads} query heads are no whole "
                f"groups over {self.num_kv_heads} K/V heads")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"expert_parallel rank {self.ep_rank} outside "
                             f"size {self.ep_size}")

    @property
    def row_layers(self) -> int:
        return period(self.layer_types)

    @property
    def rows(self) -> int:
        return self.num_layers // self.row_layers

    @property
    def row_kinds(self) -> tuple:
        """Kinds of a row's layers, in order: the same in every row."""
        return self.layer_types[:self.row_layers]

    @property
    def attn_kinds(self) -> tuple:
        """(kind, window) of the attends a forward is handed."""
        return tuple((k, self.sliding_window if k == WINDOW else None)
                     for k in (WINDOW, FULL) if k in self.layer_types)

    @property
    def router_width(self) -> int:
        return self.num_experts * self.ep_size

    @classmethod
    def from_hf(cls, hf: dict) -> "SmallThinkerConfig":
        """From published keys. ``expert_parallel: {size, rank}`` is no
        published key: it states the deployment's share
        (``moe_num_primary_experts`` is then what ONE of ``size`` chips
        holds). What the keys can ask for and is not written is refused:
        a router that does not weigh by softmax, weights left
        unnormalised, scaled RoPE, secondary experts, and a layer that is
        neither of the two kinds (a window without RoPE, RoPE without a
        window)."""
        for key, want in (("moe_primary_router_apply_softmax", True),
                          ("norm_topk_prob", True), ("rope_scaling", None)):
            if hf.get(key, want) != want:
                raise ValueError(
                    f"model_type smallthinker is served with {key} = "
                    f"{want!r} (what the published configuration states), "
                    f"not {hf[key]!r}")
        secondary = sorted(k for k, v in hf.items() if "secondary" in k and v)
        if secondary:
            raise ValueError(
                f"model_type smallthinker: secondary experts are not served "
                f"({secondary[0]}); the published configuration has none")
        n = hf["num_hidden_layers"]
        window = [int(v) for v in hf.get("sliding_window_layout") or [0] * n]
        rope = [int(v) for v in hf.get("rope_layout") or window]
        if window != rope:
            raise ValueError(
                "model_type smallthinker serves two kinds of layer, a window "
                "with RoPE and full attention with no positions; "
                "sliding_window_layout and rope_layout differ")
        ep = hf.get("expert_parallel") or {}
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["moe_ffn_hidden_size"],
            num_layers=n,
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim"),
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            sliding_window=hf.get("sliding_window_size"),
            num_experts=hf["moe_num_primary_experts"],
            num_experts_per_tok=hf["moe_num_active_primary_experts"],
            layer_types=tuple(WINDOW if w else FULL for w in window),
            moe_intermediate_size=hf["moe_ffn_hidden_size"],
            ep_size=int(ep.get("size", 1)),
            ep_rank=int(ep.get("rank", 0)),
        )


CONFIG = SmallThinkerConfig
# what the family does not serve, the weight modes it does, and why
# (models.llama ``refusal``)
UNSERVED = mdl.ONE_CHIP_POOL
WEIGHTS = ()
WHY = ("model_type smallthinker: its window and full attention layers read "
       "one bfloat16 paged K/V pool through an attend chosen by the layer's "
       "kind, on one chip")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: SmallThinkerConfig) -> dict:
    """Shapes of the parameter pytree: every layer's leaves under ``layers``
    (the leading axis the layer)."""
    D, Hq, Hkv, hd = (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads,
                      cfg.hd)
    L, E, F = cfg.num_layers, cfg.num_experts, cfg.moe_intermediate_size
    shapes = {
        "embed": (cfg.vocab_size, D), "final_norm": (D,),
        "layers": {
            "attn_norm": (L, D), "mlp_norm": (L, D),
            "wq": (L, D, Hq * hd), "wk": (L, D, Hkv * hd),
            "wv": (L, D, Hkv * hd), "wo": (L, Hq * hd, D),
            "moe_gate": (L, D, cfg.router_width),
            "w_gate": (L, E, D, F), "w_up": (L, E, D, F),
            "w_down": (L, E, F, D),
        }}
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


# The synthetic draw (models.lfm2's, for this block): every branch has to
# weigh on the logits, or a reference check over such weights passes whatever
# is wrong, so each matrix is drawn at the deviation that makes its OUTPUT of
# order 1 (``leaf_std``): q and k of deviation ``QK_GAIN`` an element (the
# family has no q/k norm to carry it: scores of deviation QK_GAIN^2, attention
# that is PEAKED, so a position's output depends on which keys it sees),
# attention moving the residual by ``BRANCH_RMS`` and the experts by more
# (below), the letters' spread ``LOGIT_STD``. The table's rows are N(0,
# 0.02): the residual starts far under its branches and the first layers
# multiply every rounding. Gains are 1 but for a few OUTLIER channels (what
# makes a lower-precision ACTIVATION lossy: a per-token int8 scale follows
# the outlier, bfloat16's relative rounding does not care). Here the norm in
# front of the ROUTER is the attention's (``attn_norm``), and outliers in
# front of a router make a benchmark's 26 letters route alike (models.afmoe),
# so that one keeps gain 1 and the outliers stand in the norm in front of the
# experts' projections (``mlp_norm``: the router does not read it) and in the
# final norm.
# Two constants were SWEPT (PERF.md section 6, PR 65; the check's mean
# shortfall, sound bfloat16 | int8 activations). The router's logits have
# deviation ``ROUTER_STD`` = 4: a top-6 of 64 under hard selection is a
# discontinuous function, bfloat16's noise in a logit flips the sixth choice
# against the seventh in about a tenth of the (token, layer) pairs, and at
# deviation 1 the sixth weighs 0.1, so twelve layers of flips compound (sound
# 0.22-0.42 on the chip, the control 0.39-0.76: no limit between them); at 4
# the sixth weighs under 0.01 and a flip moves nothing (and the weighted sum
# keeps ~0.85 of one expert's RMS where ``ROUTE_RMS`` reckons 0.5: the experts
# move the residual by ~0.85). The outliers stand at ``OUTLIER_GAIN`` = 64 (the
# older families' 32): an int8 scale that follows them rounds every other
# channel to nothing.
ROUTER_STD = 4.0
QK_GAIN = 1.5
BRANCH_RMS = 0.5
LOGIT_STD = 1.5
OUTLIER_GAIN, OUTLIER_EVERY = 64.0, mdl.OUTLIER_EVERY
OUTLIER_NORMS = ("mlp_norm", "final_norm")
# what the weighted sum of a token's k experts is reckoned to keep of one's
# RMS (the root of the sum of the squared weights, were they near alike)
ROUTE_RMS = 0.5


def leaf_std(cfg: SmallThinkerConfig, name: str) -> Optional[float]:
    """The deviation a synthetic MATRIX leaf is drawn at; None for a gain."""
    D, F = cfg.hidden_size, cfg.moe_intermediate_size
    fan = math.sqrt(D)
    # the RMS of a normed activation behind a gain with outlier channels
    share = (D // OUTLIER_EVERY) / D
    behind_outliers = fan * math.sqrt(1.0 + share * (OUTLIER_GAIN ** 2 - 1.0))
    return {
        "embed": 0.02,
        "lm_head": LOGIT_STD / behind_outliers,
        "wq": QK_GAIN / fan, "wk": QK_GAIN / fan, "wv": 1.0 / fan,
        # a softmax's output has ~0.6 of its values' RMS
        "wo": BRANCH_RMS / (0.6 * math.sqrt(cfg.num_heads * cfg.hd)),
        "moe_gate": ROUTER_STD / fan,
        "w_gate": 1.0 / behind_outliers, "w_up": 1.0 / behind_outliers,
        # relu(g) u of unit g, u has RMS 0.71
        "w_down": BRANCH_RMS / (0.71 * ROUTE_RMS * math.sqrt(F)),
    }.get(name)


def init_leaf(key, shape, name: str, dtype, cfg: SmallThinkerConfig):
    """One synthetic leaf, for models.llama.init_params' loop: matrices
    N(0, ``leaf_std``); gains 1, and ``OUTLIER_GAIN`` on a seeded ``1 /
    OUTLIER_EVERY`` of the channels of ``OUTLIER_NORMS`` (none under 192
    channels)."""
    # one draw a leaf: the uses of ``key`` are branches of one choice
    std = leaf_std(cfg, name)
    if std is not None:
        w = jax.random.normal(key, shape, jnp.float32) * std
    elif name in OUTLIER_NORMS and shape[-1] >= OUTLIER_EVERY:
        u = jax.random.uniform(  # jaxlint: disable=rng-key-reuse
            key, shape)
        kth = lax.top_k(u, shape[-1] // OUTLIER_EVERY)[0][..., -1:]
        w = jnp.where(u >= kth, OUTLIER_GAIN, 1.0)
    else:                       # attn_norm, a narrow norm
        w = jnp.ones(shape, jnp.float32)
    return w.astype(dtype)


def checkpoint_leaves(cfg: SmallThinkerConfig, get, body: str = "model."):
    """(leaf name, host array) for every ``layers`` leaf, one at a time,
    from an HF ``smallthinker`` checkpoint; ``get(name)`` reads one tensor.
    Linear weights are transposed to right-multiply; of the published
    experts those of this rank are read, the router whole. The names are
    the published code's FROM MEMORY (tests/test_smallthinker.py holds them
    by a checkpoint it writes)."""
    import numpy as np

    L = body + "layers.{i}."

    def stacked(tail, transpose):
        def one(i):
            a = get(L.format(i=i) + tail)
            return a.T if transpose else np.asarray(a)

        return np.stack([one(i) for i in range(cfg.num_layers)])

    yield "attn_norm", stacked("input_layernorm.weight", False)
    yield "mlp_norm", stacked("post_attention_layernorm.weight", False)
    for leaf in ("q", "k", "v", "o"):
        yield "w" + leaf, stacked(f"self_attn.{leaf}_proj.weight", True)
    moe = "block_sparse_moe."
    yield "moe_gate", stacked(moe + "primary_router.weight", True)
    held = range(cfg.ep_rank * cfg.num_experts,
                 (cfg.ep_rank + 1) * cfg.num_experts)
    for leaf, name in (("w_gate", "gate"), ("w_up", "up"),
                       ("w_down", "down")):
        yield leaf, np.stack([
            stacked(f"{moe}experts.{e}.{name}.weight", True) for e in held],
            axis=1)


# no per-slot state beside the pool: the routed count alone
init_rec = xp.init_rec


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def scores(cfg: SmallThinkerConfig):
    """The family's scoring rule: softmax over a token's k chosen logits,
    which is the softmax over all renormalised over the k."""
    return xp.softmax_scores(cfg.num_experts_per_tok, True)


def rope_on(kind: str) -> bool:
    """RoPE is the window layers' alone: a full layer has no positional
    encoding."""
    return kind == WINDOW


# the function on an expert's gate: ReGLU
act = jax.nn.relu


def _attention(cfg: SmallThinkerConfig, h, w, cos, sin, attend, kind: str):
    """Grouped-query attention on normed h [B, T, D]; ``w(name)`` reads one
    of the layer's leaves."""
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    with jax.named_scope("attn.qkv"):
        q = qnt.matmul(h, w("wq"))
        k = qnt.matmul(h, w("wk"))
        v = qnt.matmul(h, w("wv"))
        # the head split stays off the dots (models.llama._layer says why)
        q, k, v = lax.optimization_barrier((q, k, v))
        q = q.reshape(*q.shape[:-1], Hq, hd)
        k = k.reshape(*k.shape[:-1], Hkv, hd)
        v = v.reshape(*v.shape[:-1], Hkv, hd)
    if rope_on(kind):
        with jax.named_scope("attn.rope"):
            q = mdl.apply_rope(q, cos, sin)
            k = mdl.apply_rope(k, cos, sin)
    attn, new_kv = attend(q, k, v)
    with jax.named_scope("attn.out"):
        out = qnt.matmul(attn.reshape(*attn.shape[:-2], Hq * hd), w("wo"))
    return out, new_kv


def forward(
    cfg: SmallThinkerConfig,
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
    kernels: Optional[bool] = None,     # models.experts.walk's
                            # ``experts_kernel``
) -> tuple[jax.Array, Any, Any, jax.Array]:
    """models.llama.forward for this family: (hidden [B, T, D], new K/V
    stack, ``rec``, [experts touched, token-expert pairs] summed over the
    expert blocks). One ``lax.scan`` over the rows; (x, K/V, counts) is its
    carry, so the cache is written in place."""
    cos, sin = mdl.rope_rows(rope, positions)
    x = mdl.embed(cfg, params, tokens, embeds)
    if attn is None:
        xla_attn = mdl.xla_attend(cfg, positions)
        attn = {kind: xla_attn for kind, _ in cfg.attn_kinds}
    eps, M = cfg.rms_norm_eps, cfg.row_layers
    flat_valid = valid.reshape(-1)
    layers = params["layers"]
    # [L, E, ...] read as [rows, M, E, ...] (a bitcast) at (row, m, expert)
    experts = tuple(layers[n].reshape(cfg.rows, M, *layers[n].shape[1:])
                    for n in xp.EXPERT_LEAVES)

    def row(carry, r):
        x, kv, counts = carry
        for m, kind in enumerate(cfg.row_kinds):
            def w(name, m=m):
                return lax.dynamic_index_in_dim(
                    layers[name], r * M + m, 0, keepdims=False)

            h = mdl.rms_norm(x, w("attn_norm"), eps)
            # the router reads the ATTENTION's input, in front of it
            with jax.named_scope("moe"):
                routed = xp.route(
                    h.reshape(-1, h.shape[-1]), w("moe_gate"), scores(cfg),
                    cfg.num_experts, cfg.ep_rank, flat_valid)
            out, kv = _attention(cfg, h, w, cos, sin, mdl.attend_through(
                kv_write, attn[kind], mask[kind], kv, r * M + m), kind)
            x = x + out
            h = mdl.rms_norm(x, w("mlp_norm"), eps)
            with jax.named_scope("moe"):
                out = xp.walk(h.reshape(-1, h.shape[-1]), routed, experts,
                              r, m, experts_kernel=kernels, act=act)
            x = x + out.astype(x.dtype).reshape(x.shape)
            counts = counts + xp.counts(routed.n_touched, routed.load)
        return (x, kv, counts), None

    with jax.named_scope("layers"):
        (x, kv_stack, counts), _ = lax.scan(
            row, (x, kv_stack, jnp.zeros(2, jnp.int32)),
            jnp.arange(cfg.rows, dtype=jnp.int32))
    with jax.named_scope("final_norm"):
        x = mdl.rms_norm(x, params["final_norm"], eps)
    return x, kv_stack, rec, counts
