"""LFM2-MoE (``model_type: lfm2_moe``; LiquidAI LFM2-8B-A1B): a decoder whose
mixer is, in most layers, a GATED SHORT CONVOLUTION and nothing else
(``out_proj(C * conv(B * x))``: a causal depthwise convolution of width
``conv_L_cache`` between two multiplicative gates, no activation, no
recurrence, no decay) and in the others grouped-query attention with a norm
on every q and k head; ``layer_types`` is a LIST of the two kinds with no
fixed period; ``num_dense_layers`` layers with a dense gated MLP stand in
front of layers with sigmoid-routed experts and NO shared expert.

What the serving engine holds of it (engine.runner):

  * the list is cut into RUNS of like ROWS (``plan``): the dense prefix is one
    row, behind it a row starts at every attention layer, and consecutive
    rows of the same kinds are one run, one ``lax.scan`` over its rows with
    the row's layers unrolled, their kinds static. The published 24 layers
    are three scans (``c c`` once, ``a c c c`` four times, ``a c c`` twice);
    any list builds. The first run of expert layers is ``params["layers"]``,
    the dense prefix the top-level ``dense_*`` leaves, later runs
    ``tail<k>_*``: every leaf ``[rows, layers of its kind in a row, ...]``,
    read in place at ``row * n + j`` (a scanned slice would stage a row's
    layers, or its experts, before a layer's is taken);
  * the attention layers alone cache K/V, in the paged pool under their own
    count (``cache_layers``). Their heads are 64 wide, and Mosaic copies
    128-lane rows: TWO K/V heads share a pool row (ops.attention
    ``heads_per_row``), so ``num_kv_heads`` and ``head_dim`` here are the
    POOL's (4 of 128 for the published 8 of 64; ``attn_kv_heads``,
    ``attn_hd`` are the model's) and pool, tables, write policies and the
    paged decode kernel see a model of 128-wide heads. q carries the factor
    ``kv_pack^1/2`` the attends' ``(row width)^-1/2`` lacks, folded into its
    RoPE where it is rounded anyway;
  * a convolution layer's whole state is the last ``conv_L_cache - 1`` rows
    of ``B * x`` a slot (8 KiB at the published widths): ``init_rec``'s one
    dense per-SLOT array beside the pool, carried through the scans and
    updated in place; a token that is not real (an empty slot of a decode
    step, a padded row of a chunk) moves nothing. A prefill chunk is the
    PARALLEL form (``conv_L_cache`` shifted products over the chunk, the
    tail handed on through the slot's rows), never a token scan; a small
    last chunk and a decode step go through as ONE batch (``RIDES``:
    models.llama ``family_module``'s third case), the convolution alone
    in two halves;
  * the expert block is models.experts' (the routing and dispatch
    models.afmoe shares): sigmoid scores, the bias inside the selection and
    outside the weight, ``norm_topk_prob`` over the sum + 1e-6,
    ``routed_scaling_factor``; TOLD which experts it holds
    (``expert_parallel: {size, rank}``; absent: all of them, the layer's sum
    whole).

The plain reference is benchmark/reference/lfm2_family.py, and
tests/test_lfm2.py holds this file to it. Scopes: ``sconv/in_proj``,
``sconv/conv`` (the slot's rows read, the convolution, the rows written
back), ``sconv/out_proj``, ``attn.qkv``, ``attn.rope``, ``attn.out``,
``dense_mlp``, ``moe/{router,experts}``; the attends bring their own.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import re
from typing import Any, ClassVar, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from localai_tpu.models import experts as xp
from localai_tpu.models import llama as mdl
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import LlamaConfig
from localai_tpu.ops.attention import (heads_per_row, pack_kv, pack_q,
                                       unpack_out)

F32 = jnp.float32
CONV, FULL = "conv", "full_attention"
ROUTE_EPS = 1e-6        # beside the sum of a token's k scores


class Run(NamedTuple):
    """Consecutive rows of like layers: one scan."""

    prefix: str         # of its leaves' names: "" is ``params["layers"]``
    rows: int
    kinds: tuple        # a row's mixers, in order
    dense: bool         # a dense gated MLP behind each mixer; else experts
    conv0: int          # convolution layers in front of the run
    attn0: int          # attention (cache) layers in front of it


@functools.lru_cache(maxsize=None)
def plan(layer_types: tuple, num_dense: int) -> tuple:
    """``layer_types`` as runs: the dense prefix one row; behind it a row
    starts at every attention layer, and consecutive rows of the same kinds
    are one run."""
    rows = [(layer_types[:num_dense], True)] if num_dense else []
    for kind in layer_types[num_dense:]:
        if kind == FULL or not rows or rows[-1][1]:
            rows.append(((), False))
        rows[-1] = (rows[-1][0] + (kind,), False)
    runs: list = []
    conv = attn = 0
    for kinds, dense in rows:
        if runs and runs[-1].kinds == kinds and not dense \
                and not runs[-1].dense:
            runs[-1] = runs[-1]._replace(rows=runs[-1].rows + 1)
        else:
            tails = sum(not r.dense for r in runs)
            prefix = "dense_" if dense else f"tail{tails}_" if tails else ""
            runs.append(Run(prefix, 1, kinds, dense, conv, attn))
        conv += kinds.count(CONV)
        attn += kinds.count(FULL)
    return tuple(runs)


@dataclasses.dataclass(frozen=True)
class Lfm2Config(LlamaConfig):
    """``LlamaConfig`` with the keys the family adds. ``num_kv_heads`` and
    ``head_dim`` are the POOL's rows (``kv_pack`` of the model's K/V heads
    each); ``num_experts`` is the number of routed experts HELD here, the
    router's width ``num_experts * ep_size``."""

    layer_types: tuple = ()
    num_dense_layers: int = 0
    conv_L_cache: int = 3
    moe_intermediate_size: int = 0
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    kv_pack: int = 1          # K/V heads of the model in one pool row
    ep_size: int = 1          # chips that share a layer's routed experts
    ep_rank: int = 0          # which of them this is

    recurrent: ClassVar[bool] = True
    family: ClassVar[str] = "lfm2"
    routed: ClassVar[bool] = True

    def __post_init__(self):
        if len(self.layer_types) != self.num_layers or set(
                self.layer_types) - {CONV, FULL}:
            raise ValueError(
                f"lfm2_moe: layer_types names {len(self.layer_types)} layers "
                f"of kinds {sorted(set(self.layer_types))}; "
                f"num_hidden_layers is {self.num_layers} and the kinds "
                f"served are {CONV} and {FULL}")
        if not 0 <= self.num_dense_layers < self.num_layers:
            raise ValueError(
                f"lfm2_moe: num_dense_layers {self.num_dense_layers} of "
                f"{self.num_layers} layers leaves no layer with experts (a "
                f"stack of dense layers alone is model_type lfm2's, which is "
                f"not served)")
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"expert_parallel rank {self.ep_rank} outside "
                             f"size {self.ep_size}")

    @property
    def runs(self) -> tuple:
        return plan(self.layer_types, self.num_dense_layers)

    @property
    def attn_hd(self) -> int:
        """The model's head size (``head_dim`` is the pool row's)."""
        return self.hd // self.kv_pack

    @property
    def attn_kv_heads(self) -> int:
        return self.num_kv_heads * self.kv_pack

    @property
    def rotary_dim(self) -> int:
        return self.attn_hd

    @property
    def cache_layers(self) -> int:
        """K/V is cached by the attention layers alone."""
        return self.layer_types.count(FULL)

    @property
    def conv_layers(self) -> int:
        return self.layer_types.count(CONV)

    @property
    def router_width(self) -> int:
        return self.num_experts * self.ep_size

    @classmethod
    def from_hf(cls, hf: dict) -> "Lfm2Config":
        """From published keys. ``expert_parallel: {size, rank}`` is no
        published key: it states the deployment's share (``num_experts`` is
        then what ONE of ``size`` chips holds). A key that asks for what is
        not written (a bias on the convolution, scaled RoPE) is refused.
        The tables are TIED unless the file says otherwise (the family's
        convention; the published file has no such key)."""
        for key, want in (("conv_bias", False), ("rope_scaling", None)):
            if hf.get(key, want) != want:
                raise ValueError(
                    f"model_type lfm2_moe is served with {key} = {want!r} "
                    f"(what the published configuration states), not "
                    f"{hf[key]!r}")
        ep = hf.get("expert_parallel") or {}
        heads, kv_heads = hf["num_attention_heads"], hf["num_key_value_heads"]
        hd = hf.get("head_dim") or hf["hidden_size"] // heads
        pack = heads_per_row(kv_heads, hd)
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=kv_heads // pack,
            head_dim=hd * pack,
            rope_theta=float(hf.get("rope_theta", 1000000.0)),
            rms_norm_eps=hf.get("norm_eps", 1e-5),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            tie_word_embeddings=hf.get("tie_word_embeddings", True),
            num_experts=hf["num_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            layer_types=tuple(hf["layer_types"]),
            num_dense_layers=hf.get("num_dense_layers", 0),
            conv_L_cache=hf.get("conv_L_cache", 3),
            moe_intermediate_size=hf["moe_intermediate_size"],
            norm_topk_prob=hf.get("norm_topk_prob", True),
            use_expert_bias=hf.get("use_expert_bias", True),
            routed_scaling_factor=float(hf.get("routed_scaling_factor", 1.0)),
            kv_pack=pack,
            ep_size=int(ep.get("size", 1)),
            ep_rank=int(ep.get("rank", 0)),
        )


CONFIG = Lfm2Config
# what the family does not serve, the weight modes it does, and why
# (models.llama ``refusal``)
UNSERVED = mdl.KEYS_ALONE
WEIGHTS = ()
# ``forward`` takes a prompt's small last chunk and a decode step as one
# batch (the contract's ``ride``): rows meet in ``_conv_mixer`` alone
RIDES = True
WHY = (f"model_type lfm2_moe: its convolution layers {mdl.STATE_WHY}; its "
       f"routed experts are read one expert at a time from the stacked "
       f"bfloat16 leaves")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# the selection bias is float32 as published, whatever the compute dtype
FLOAT32_LEAVES = ("expert_bias",)
_PREFIX = re.compile(r"^(dense|tail\d+)_")


def base_name(name: str) -> str:
    """A leaf's name without its run's prefix."""
    return _PREFIX.sub("", name)


# a mixer's leaves count the row's layers of their KIND; the others (norms,
# feed-forward) every layer of the row
MIXER_LEAVES = ("conv_in", "conv_w", "conv_out", "wq", "wk", "wv", "wo",
                "q_norm", "k_norm")


def run_shapes(cfg: Lfm2Config, run: Run) -> dict:
    """Shapes of one run's leaves, under their names without the prefix:
    ``[rows, the row's layers of the leaf's kind, ...]``."""
    D, K, R, M = cfg.hidden_size, cfg.conv_L_cache, run.rows, len(run.kinds)
    Hq, Hkv, hd = cfg.num_heads, cfg.attn_kv_heads, cfg.attn_hd
    nc, na = run.kinds.count(CONV), run.kinds.count(FULL)
    shapes = {"op_norm": (R, M, D), "ffn_norm": (R, M, D)}
    if nc:
        # [B | C | x] from one projection, the depthwise taps (row K - 1
        # multiplies the token itself), the output projection
        shapes.update(conv_in=(R, nc, D, 3 * D), conv_w=(R, nc, K, D),
                      conv_out=(R, nc, D, D))
    if na:
        shapes.update(wq=(R, na, D, Hq * hd), wk=(R, na, D, Hkv * hd),
                      wv=(R, na, D, Hkv * hd), wo=(R, na, Hq * hd, D),
                      q_norm=(R, na, hd), k_norm=(R, na, hd))
    if run.dense:
        F = cfg.intermediate_size
        shapes.update(w_gate=(R, M, D, F), w_up=(R, M, D, F),
                      w_down=(R, M, F, D))
    else:
        E, F, W = cfg.num_experts, cfg.moe_intermediate_size, cfg.router_width
        shapes.update(moe_gate=(R, M, D, W), w_gate=(R, M, E, D, F),
                      w_up=(R, M, E, D, F), w_down=(R, M, E, F, D))
        if cfg.use_expert_bias:
            shapes["expert_bias"] = (R, M, W)
    return shapes


def param_shapes(cfg: Lfm2Config) -> dict:
    """Shapes of the parameter pytree: the first run of expert layers under
    ``layers``, every other run's leaves at the top level under its prefix
    (``dense_``, ``tail1_``, ...)."""
    shapes: dict = {"embed": (cfg.vocab_size, cfg.hidden_size),
                    "final_norm": (cfg.hidden_size,)}
    for run in cfg.runs:
        leaves = run_shapes(cfg, run)
        if run.prefix:
            shapes.update({run.prefix + n: s for n, s in leaves.items()})
        else:
            shapes["layers"] = leaves
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (cfg.hidden_size, cfg.vocab_size)
    return shapes


def run_leaves(params: dict, run: Run) -> dict:
    """One run's leaves out of the pytree, under their names without the
    prefix."""
    if not run.prefix:
        return params["layers"]
    return {n[len(run.prefix):]: a for n, a in params.items()
            if n.startswith(run.prefix)}


# The synthetic draw. Every branch has to weigh on the logits, or a reference
# check over such weights passes whatever is wrong: each matrix is drawn at
# the deviation that makes its OUTPUT of order 1 (``leaf_std``): B, C and x
# of deviation 1 in front of the convolution (the mixer is a product of
# three: at N(0, 0.02) behind a gain with outliers it would drown the other
# branches), each of mixer, attention and feed-forward moving the residual by
# ``BRANCH_RMS``, the router's logits and the letters' spread by 1 and
# ``LOGIT_STD``. The letters' spread is the TABLE's deviation (0.0136 at the
# published widths: the head is tied to it), so the residual starts far under
# its branches and the first layers multiply every rounding. That is what
# lets the benchmark's check tell precisions apart: rows drawn at 0.1, 0.25
# and 1 (the final norm's gain bringing the spread back) were tried on the
# chip, and the larger the rows, the closer int8 activations read to
# bfloat16's (PERF.md section 6, PR 57: at 1 the tied head answers every
# token with itself and the two overlap). Gains are 1 but for
# ``QK_NORM_GAIN`` on q and k (attention that is PEAKED) and a few OUTLIER channels in the norm in front of the
# mixers and in the final norm, as models.afmoe's and models.falcon_h1's
# draws have them (what makes a lower-precision ACTIVATION lossy); the norm
# in front of router and experts keeps gain 1 (outliers there make a
# benchmark's 26 letters route alike). The taps are N(0, ``CONV_TAP_STD``):
# all ``conv_L_cache`` of them weigh, so a tap left out shows. The selection
# bias is N(0, ``BIAS_STD``) in float32: it changes the choice of about a
# tenth of the tokens (tests/test_lfm2.py counts them), so that a program
# that weighs with the bias, or selects without it, disagrees with the
# reference, and small beside the scores' spacing at the top, so that the
# experts' load stays spread.
BRANCH_RMS = 0.5
LOGIT_STD = 1.5
QK_NORM_GAIN = 1.5
CONV_TAP_STD = 0.5
BIAS_STD = 0.004
OUTLIER_GAIN, OUTLIER_EVERY = mdl.OUTLIER_GAIN, mdl.OUTLIER_EVERY
OUTLIER_NORMS = ("op_norm", "final_norm")


def leaf_std(cfg: Lfm2Config, name: str) -> Optional[float]:
    """The deviation a synthetic MATRIX leaf is drawn at; None for a leaf
    that is no matrix (``init_leaf`` draws those)."""
    D = cfg.hidden_size
    fan_h = math.sqrt(D) * mdl.outlier_rms(D)     # behind op_norm / final_norm
    name = base_name(name)
    if name in ("embed", "lm_head"):
        return LOGIT_STD / fan_h
    if name in ("conv_in", "wq", "wk", "wv"):
        return 1.0 / fan_h
    if name == "conv_out":      # C v: a product of unit factors, K taps
        return BRANCH_RMS / (CONV_TAP_STD * math.sqrt(cfg.conv_L_cache * D))
    if name == "wo":        # a softmax's output has ~0.6 of its values' RMS
        return BRANCH_RMS / (0.6 * math.sqrt(cfg.num_heads * cfg.attn_hd))
    if name in ("w_gate", "w_up", "moe_gate"):
        return 1.0 / math.sqrt(D)
    return None


def init_leaf(key, shape, name: str, dtype, cfg: Lfm2Config):
    """One synthetic leaf, for models.llama.init_params' loop: matrices
    N(0, ``leaf_std``) (``w_down`` by its own fan-in: the dense layers' and
    the experts' differ); gains 1, ``QK_NORM_GAIN`` on q and k, and
    ``OUTLIER_GAIN`` on a seeded ``1 / OUTLIER_EVERY`` of the channels of
    ``OUTLIER_NORMS``; the taps N(0, ``CONV_TAP_STD``); the selection bias
    N(0, ``BIAS_STD``) in float32."""
    # one draw a leaf: the uses of ``key`` are branches of one choice
    base = base_name(name)
    std = leaf_std(cfg, name)
    if base == "w_down":        # silu(g) u of unit g, u has RMS ~0.6
        std = BRANCH_RMS / (0.6 * math.sqrt(shape[-2]))
    if base == "conv_w":
        std = CONV_TAP_STD
    if base == "expert_bias":
        return jax.random.normal(key, shape, F32) * BIAS_STD
    if std is not None:
        w = jax.random.normal(  # jaxlint: disable=rng-key-reuse
            key, shape, F32) * std
    elif base in ("q_norm", "k_norm"):
        w = jnp.full(shape, QK_NORM_GAIN, F32)
    elif base in OUTLIER_NORMS and shape[-1] >= OUTLIER_EVERY:
        u = jax.random.uniform(  # jaxlint: disable=rng-key-reuse
            key, shape)
        kth = lax.top_k(u, shape[-1] // OUTLIER_EVERY)[0][..., -1:]
        w = jnp.where(u >= kth, OUTLIER_GAIN, 1.0)
    else:                       # ffn_norm, a narrow norm
        w = jnp.ones(shape, F32)
    return w.astype(dtype)


def checkpoint_leaves(cfg: Lfm2Config, get, body: str = "model."):
    """(leaf name, host array) for every run's leaves and the final norm,
    one at a time, from an HF ``lfm2_moe`` checkpoint; ``get(name)`` reads
    one tensor. Linear weights are transposed to right-multiply; the
    depthwise conv's ``[D, 1, K]`` becomes ``[K, D]``; ``in_proj``'s rows are
    [B; C; x] as published, which is the served order; the final norm is
    ``embedding_norm``; of the published experts those of this rank are
    read, router and bias whole. The names are the published code's FROM
    MEMORY (tests/test_lfm2.py holds them by a checkpoint it writes)."""
    L = body + "layers.{i}."
    per_layer = {"op_norm": ("operator_norm.weight", np.asarray),
                 "ffn_norm": ("ffn_norm.weight", np.asarray)}
    by_kind = {
        CONV: {"conv_in": ("conv.in_proj.weight", np.transpose),
               "conv_w": ("conv.conv.weight", lambda a: a[:, 0, :].T),
               "conv_out": ("conv.out_proj.weight", np.transpose)},
        FULL: {"wq": ("self_attn.q_proj.weight", np.transpose),
               "wk": ("self_attn.k_proj.weight", np.transpose),
               "wv": ("self_attn.v_proj.weight", np.transpose),
               "wo": ("self_attn.out_proj.weight", np.transpose),
               "q_norm": ("self_attn.q_layernorm.weight", np.asarray),
               "k_norm": ("self_attn.k_layernorm.weight", np.asarray)}}
    mlp = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}
    held = range(cfg.ep_rank * cfg.num_experts,
                 (cfg.ep_rank + 1) * cfg.num_experts)
    first = 0
    for run in cfg.runs:
        M = len(run.kinds)

        def rows(tail, fix, places, first=first, M=M, run=run):
            """[rows, len(places), ...]: the tensor ``tail`` of the layers at
            ``places`` of each row."""
            return np.stack([np.stack([
                fix(get(L.format(i=first + r * M + m) + tail))
                for m in places]) for r in range(run.rows)])

        every = range(M)
        for leaf, (tail, fix) in per_layer.items():
            yield run.prefix + leaf, rows(tail, fix, every)
        for kind, names in by_kind.items():
            places = [m for m, k in enumerate(run.kinds) if k == kind]
            if places:
                for leaf, (tail, fix) in names.items():
                    yield run.prefix + leaf, rows(tail, fix, places)
        F = "feed_forward."
        for leaf, name in mlp.items():
            if run.dense:
                yield run.prefix + leaf, rows(F + name + ".weight",
                                              np.transpose, every)
            else:
                yield run.prefix + leaf, np.stack([
                    rows(f"{F}experts.{e}.{name}.weight", np.transpose,
                         every) for e in held], axis=2)
        if not run.dense:
            yield run.prefix + "moe_gate", rows(F + "gate.weight",
                                                np.transpose, every)
            if cfg.use_expert_bias:
                yield run.prefix + "expert_bias", rows(
                    F + "expert_bias", np.asarray, every)
        first += run.rows * M
    yield "final_norm", np.asarray(get(body + "embedding_norm.weight"))


# ---------------------------------------------------------------------------
# Recurrent state: one dense per-slot array beside the K/V pool
# ---------------------------------------------------------------------------

def init_rec(cfg: Lfm2Config, num_slots: int) -> dict:
    """The convolution layers' state for ``num_slots`` slots, all zero: the
    last K - 1 rows of ``B * x``, ``conv [layers, slots, K - 1, D]`` in the
    compute dtype, and NOTHING else: the family has no recurrence."""
    return {
        "conv": jnp.zeros((cfg.conv_layers, num_slots, cfg.conv_L_cache - 1,
                           cfg.hidden_size), jnp.dtype(cfg.dtype)),
        **xp.init_rec(),
    }


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def norm(x, w, eps: float):
    """Plain RMSNorm, float32 inside, rounded once."""
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps) * w.astype(F32)).astype(x.dtype)


def rotate(x, cos, sin, scale: float = 1.0):
    """models.llama.apply_rope with a factor on its float32 result, in
    front of the one rounding."""
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return (out if scale == 1.0 else out * scale).astype(x.dtype)


def gated(gate, x):
    """The elementwise product of two of in_proj's thirds, float32."""
    return gate.astype(F32) * x.astype(F32)


def short_conv(cat, taps, T: int):
    """The depthwise causal convolution over rows cat [B, K - 1 + T, D]
    (taps [K, D]) as K shifted products: token t's output reads rows t ..
    t + K - 1; float32."""
    taps = taps.astype(F32)
    return sum(cat[:, i:i + T].astype(F32) * taps[i]
               for i in range(taps.shape[0]))


def _conv_mixer(cfg: Lfm2Config, h, w, rows, valid, ride: int = 0):
    """The gated short convolution on normed h [B, T, D]; ``w(name)`` reads
    one of the layer's leaves, ``rows()`` the slots' last K - 1 rows of
    ``B * x`` [B, K - 1, D]; ``valid`` [B, T] marks the real tokens, a PREFIX
    of each row. Returns (out [B, T, D], the rows after the real tokens).

    ``ride`` (the contract's third case; h [1, ride + S, D]): the two
    products run once over every row; the convolution between them runs on
    the chunk's ``ride`` rows against ``rows()`` and on the step's S rows, a
    row a slot, against ``rows(None)`` (every slot's), each in the shape its
    own program gives it, and the two are laid end to end. The rows come
    back as the pair (the chunk's, the step's)."""
    D, K = cfg.hidden_size, cfg.conv_L_cache
    with jax.named_scope("in_proj"):
        p = lax.optimization_barrier(qnt.matmul(h, w("conv_in")))
        # rounded where the slot's rows hold it: a step and a chunk read
        # the same values
        u = gated(p[..., :D], p[..., 2 * D:]).astype(h.dtype)

    def conv(part, rows):
        # [the slot's last K-1 rows; the chunk's]: token t is row t + K - 1
        cat = jnp.concatenate([rows.astype(u.dtype), part(u)], axis=1)
        y = gated(part(p[..., D:2 * D]),
                  short_conv(cat, w("conv_w"), cat.shape[1] - K + 1))
        return y, mdl.conv_rows(
            cat, jnp.sum(part(valid), axis=1).astype(jnp.int32), K)

    with jax.named_scope("conv"):
        if ride:
            y, chunk_rows = conv(lambda a: a[:, :ride], rows())
            step, new_rows = conv(lambda a: a[0, ride:, None], rows(None))
            y = jnp.concatenate([y, step[:, 0][None]], axis=1)
            new_rows = (chunk_rows, new_rows)
        else:
            y, new_rows = conv(lambda a: a, rows())
    with jax.named_scope("out_proj"):
        return qnt.matmul(y.astype(h.dtype), w("conv_out")), new_rows


def _attention(cfg: Lfm2Config, h, w, cos, sin, attend):
    """Grouped-query attention on normed h [B, T, D], a norm on every q and
    k head in front of RoPE; the heads packed ``kv_pack`` to a pool row."""
    Hq, Hkv, hd, f = cfg.num_heads, cfg.attn_kv_heads, cfg.attn_hd, cfg.kv_pack
    with jax.named_scope("attn.qkv"):
        q = qnt.matmul(h, w("wq"))
        k = qnt.matmul(h, w("wk"))
        v = qnt.matmul(h, w("wv"))
        # the head split stays off the dots (models.llama._layer says why)
        q, k, v = lax.optimization_barrier((q, k, v))
        q = norm(q.reshape(*q.shape[:-1], Hq, hd), w("q_norm"),
                 cfg.rms_norm_eps)
        k = norm(k.reshape(*k.shape[:-1], Hkv, hd), w("k_norm"),
                 cfg.rms_norm_eps)
        v = v.reshape(*v.shape[:-1], Hkv, hd)
    with jax.named_scope("attn.rope"):
        # the attends scale by the ROW's width: q brings the rest
        q = pack_q(rotate(q, cos, sin, math.sqrt(f)), f, Hkv)
        k = pack_kv(rotate(k, cos, sin), f)
        v = pack_kv(v, f)
    attn, new_kv = attend(q, k, v)
    with jax.named_scope("attn.out"):
        attn = unpack_out(attn, f, Hkv)
        out = qnt.matmul(attn.reshape(*attn.shape[:-2], Hq * hd), w("wo"))
    return out, new_kv


def scores(cfg: Lfm2Config, bias):
    """The family's scoring rule over a block's selection ``bias``."""
    return xp.sigmoid_scores(cfg.num_experts_per_tok, bias,
                             cfg.norm_topk_prob, cfg.routed_scaling_factor,
                             eps=ROUTE_EPS)


def forward(
    cfg: Lfm2Config,
    params: Any,
    tokens: jax.Array,      # [B, T] i32
    positions: jax.Array,   # [B, T] i32
    kv_write: Any,          # engine.kvcache write policy, a cache layer an
                            # ATTENTION layer
    kv_stack: Any,          # stacked K/V of the attention layers
    mask: jax.Array,
    rope: tuple[jax.Array, jax.Array],
    attn: Any = None,
    embeds: Optional[jax.Array] = None,
    *,
    rec: dict,              # init_rec's array
    valid: jax.Array,       # [B, T] bool: the real tokens, a prefix a row
    slot: Any = None,       # the decode step's rows or ONE slot's chunk, and
    fresh: Any = None,      # whether that starts from zero state: the
                            # contract (models.llama ``family_module``)
    kernels: Optional[bool] = None,     # models.experts.moe_block's
                            # ``experts_kernel``
    ride: int = 0,          # rows of ``slot``'s chunk in front of a decode
                            # step's S, [1, ride + S] in all: the contract
) -> tuple[jax.Array, Any, dict, jax.Array]:
    """models.llama.forward for this family: (hidden [B, T, D], new K/V
    stack, new ``rec``, [experts touched, token-expert pairs] summed over
    the expert blocks). One ``lax.scan`` a run of like rows; (x, K/V, conv
    rows, counts) is the carry, so pool and state are written in place.
    Everything but the convolution is per row (the packed heads are formed
    in front of the attend, which a ride's composite policy splits), so a
    ride's rows go through as any batch's."""
    cos, sin = mdl.rope_rows(rope, positions)
    x = mdl.embed(cfg, params, tokens, embeds)
    if attn is None:
        attn = mdl.xla_attend(cfg, positions)
    eps = cfg.rms_norm_eps
    flat_valid = valid.reshape(-1)

    def scan_run(run: Run, carry):
        leaves = run_leaves(params, run)
        experts = None if run.dense else tuple(
            leaves[n] for n in xp.EXPERT_LEAVES)
        # [rows, n, ...] read as [rows n, ...] (a bitcast) at row n + j
        flat = {n: a.reshape(-1, *a.shape[2:]) for n, a in leaves.items()
                if run.dense or n not in xp.EXPERT_LEAVES}
        M = len(run.kinds)
        nc, na = run.kinds.count(CONV), run.kinds.count(FULL)

        def row(carry, r):
            x, kv, conv_all, counts = carry
            seen = {CONV: 0, FULL: 0}
            for m, kind in enumerate(run.kinds):
                j = seen[kind]
                seen[kind] += 1
                at = {CONV: r * nc + j, FULL: r * na + j}[kind]

                def w(name, at=at, every=r * M + m):
                    return lax.dynamic_index_in_dim(
                        flat[name], at if name in MIXER_LEAVES else every, 0,
                        keepdims=False)

                h = norm(x, w("op_norm"), eps)
                if kind == CONV:
                    layer = run.conv0 + at
                    with jax.named_scope("sconv"):
                        def rows(of=slot, conv_all=conv_all, layer=layer):
                            r0 = mdl.rec_read(conv_all, (layer,), of)
                            if of is None or fresh is None:
                                return r0
                            return jnp.where(fresh, 0, r0).astype(r0.dtype)

                        out, new_rows = _conv_mixer(cfg, h, w, rows, valid,
                                                    ride)
                        with jax.named_scope("conv"):
                            if ride:    # the step's first, for every slot
                                new_rows, step_rows = new_rows
                                conv_all = mdl.rec_write(
                                    conv_all, step_rows, (layer,), None)
                            conv_all = mdl.rec_write(conv_all, new_rows,
                                                     (layer,), slot)
                else:
                    out, kv = _attention(
                        cfg, h, w, cos, sin, mdl.attend_through(
                            kv_write, attn, mask, kv, run.attn0 + at))
                x = x + out
                h = norm(x, w("ffn_norm"), eps)
                if run.dense:
                    with jax.named_scope("dense_mlp"):
                        x = x + xp.swiglu(h, w("w_gate"), w("w_up"),
                                          w("w_down"))
                    continue
                with jax.named_scope("moe"):
                    out, n_touched, load = xp.moe_block(
                        h.reshape(-1, h.shape[-1]), w("moe_gate"),
                        scores(cfg, w("expert_bias")
                               if cfg.use_expert_bias else None),
                        experts, r, m, num_experts=cfg.num_experts,
                        ep_rank=cfg.ep_rank, valid=flat_valid, shared=None,
                        experts_kernel=kernels)
                x = x + out.reshape(x.shape)
                counts = counts + xp.counts(n_touched, load)
            return (x, kv, conv_all, counts), None

        carry, _ = lax.scan(row, carry,
                            jnp.arange(run.rows, dtype=jnp.int32))
        return carry

    carry = (x, kv_stack, rec["conv"], jnp.zeros(2, jnp.int32))
    with jax.named_scope("layers"):
        for run in cfg.runs:
            carry = scan_run(run, carry)
    x, kv_stack, conv_all, counts = carry
    with jax.named_scope("final_norm"):
        x = norm(x, params["final_norm"], eps)
    return x, kv_stack, {**rec, "conv": conv_all}, counts
