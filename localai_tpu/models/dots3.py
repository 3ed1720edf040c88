"""dots3-note (``model_type: dots3_note``): the DeepSeek-V3 block of
models.deepseek with two KINDS of latent-attention layer in one stack, each
with leaves of its own shape, in front of ``noaux_tc`` sigmoid experts.

  * a FULL layer (``full_attention``) is models.deepseek's latent attention
    with an INDEXER in front of it (DeepSeek-V3.2's learned sparse
    attention): every token caches, beside its latent row, ONE small index
    key; a query scores every cached index key (``index_n_heads`` heads of
    ``index_head_dim`` from the SAME low-rank query vector, a weighted sum of
    ReLUs) and attends the ``index_topk`` best rows ALONE. A context shorter
    than ``index_topk`` attends every row: dense latent attention;
  * a WINDOW layer (``sliding_attention``) is latent attention with heads,
    ranks, head split and RoPE base of its own (the ``swa_`` keys) over the
    last ``sliding_window_size`` tokens, the token itself counted; no
    indexer;
  * both gate each head's output (``attention_gate_type: headwise``:
    ``sigmoid(h Wg)``, one number a head, before ``wo``) and, where
    ``apply_mla_qkv_lora_rescale`` says so, scale the two normed low-rank
    vectors by ``sqrt(hidden / rank)``;
  * experts: models.experts' sigmoid rule with the selection bias
    (``noaux_tc``; no ``n_group`` key: one group), one ungated shared expert.

The attention's arithmetic is models.deepseek's ``_attention`` under a VIEW
of the config a kind (``Dots3Config.kind``); this file adds the gate, the
indexer, a RoPE table a kind and the walk. Which rows a layer attends, and in
which form, is its attend's (engine.kvcache ``LatentLayout``: an attend a
kind over a pool of three arrays: full rows ``c``, window rows ``w``, index
keys ``i``).

THE STACK. ``layer_types`` is F F (S S S F) x 11 as published, the first
``first_k_dense_replace`` layers with a dense MLP. The served depth is a
PREFIX of it: the dense layers (full), then the LONE full layers in front of
the first window layer, then whole PERIODS of windows closed by one full
layer. The leaves say so: ``dense_*`` ``[n_dense, ...]`` and ``lone_*``
``[n_lone, ...]`` at the top level (the full layer's names), and under
``layers`` ONE ROW A PERIOD: the period's norms, routers and experts ``[P, M,
...]`` (M layers a period), its full layer's attention ``[P, ...]`` and its
window layers' ``swa_*`` ``[P, M - 1, ...]``. ONE ``lax.scan`` over the
periods (models.afmoe's row of kinds, with a shape a kind); (x, pool) is its
carry.

The plain reference is benchmark/reference/dots3_family.py and
tests/test_dots3.py holds this file to it. Scopes: models.deepseek's and
``mla/gate``, ``dsa/q``, ``dsa/k``; the attends bring ``attn.index``,
``attn.select``, ``attn.sparse_decode``, ``attn.sparse_chunk``,
``attn.latent_window``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, ClassVar, Optional

import jax
import jax.numpy as jnp
from jax import lax

from localai_tpu.models import deepseek as ds
from localai_tpu.models import experts as xp
from localai_tpu.models import llama as mdl
from localai_tpu.models import quant as qnt
from localai_tpu.models.deepseek import DENSE, DeepseekConfig

FULL, WINDOW = "full_attention", "sliding_attention"
LONE, SWA = "lone_", "swa_"
# the array of the latent pool a kind's rows lie in (``latent_states``'
# order), and the index keys'
STATE = {FULL: 0, WINDOW: 1}
INDEX_STATE = 2
INDEX_NORM_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class Dots3Config(DeepseekConfig):
    """``DeepseekConfig`` (the FULL layers' shapes) with the window layers'
    and the stack's kinds. ``sliding_window`` counts the token itself."""

    layer_types: tuple = ()
    swa_num_heads: int = 0
    swa_q_lora_rank: int = 0
    swa_kv_lora_rank: int = 0
    swa_qk_nope_head_dim: int = 0
    swa_qk_rope_head_dim: int = 0
    swa_v_head_dim: int = 0
    swa_rope_theta: float = 10000.0
    lora_rescale: bool = False      # ``apply_mla_qkv_lora_rescale``

    family: ClassVar[str] = "dots3"

    def __post_init__(self):
        super().__post_init__()
        types, nd = self.layer_types, self.num_dense_layers
        if len(types) != self.num_layers or set(types) - {FULL, WINDOW}:
            raise ValueError(
                f"dots3_note: layer_types names {len(types)} layers of kinds "
                f"{sorted(set(types))}; the stack has {self.num_layers} of "
                f"{FULL} / {WINDOW}")
        if WINDOW in types[:nd]:
            raise ValueError(mdl.refusal(
                self, "a window layer with a dense MLP"))
        rest = types[nd + self.lone_layers:]
        M = self.period
        if M and (rest[M - 1] != FULL or len(rest) % M or any(
                rest[i:i + M] != rest[:M] for i in range(0, len(rest), M))):
            raise ValueError(mdl.refusal(
                self,
                f"a depth of {self.num_layers} layers that ends inside a "
                f"period of {M} ({M - 1} window layers closed by a full "
                f"one)"))
        if WINDOW in types and not self.sliding_window:
            raise ValueError("dots3_note: sliding_attention layers and no "
                             "sliding_window_size")

    @property
    def lone_layers(self) -> int:
        """Full expert layers in front of the first window layer."""
        rest = self.layer_types[self.num_dense_layers:]
        return rest.index(WINDOW) if WINDOW in rest else len(rest)

    @property
    def period(self) -> int:
        """Layers of a period: its window layers and the full layer that
        closes it (0: the depth holds no window layer)."""
        rest = self.layer_types[self.num_dense_layers + self.lone_layers:]
        return rest.index(FULL) + 1 if FULL in rest else len(rest)

    @property
    def periods(self) -> int:
        M = self.period
        return (self.num_layers - self.num_dense_layers
                - self.lone_layers) // M if M else 0

    @property
    def full_layers(self) -> int:
        return self.layer_types.count(FULL)

    @property
    def window_layers(self) -> int:
        return self.layer_types.count(WINDOW)

    @property
    def attn_kinds(self) -> tuple:
        """(kind, window) of the attends a forward is handed."""
        return tuple((k, self.sliding_window if k == WINDOW else None)
                     for k in (WINDOW, FULL) if k in self.layer_types)

    @property
    def latent_states(self) -> tuple:
        """The pool's arrays: a full layer's rows, a window layer's rows
        (of another width) and a full layer's index keys, each with a layer
        axis of its own under the one block table."""
        window = self.kind(WINDOW)
        return (("c", self.full_layers, self.latent_width),
                ("w", self.window_layers, window.latent_width),
                ("i", self.full_layers, self.index_head_dim))

    def kind(self, kind: str) -> DeepseekConfig:
        """The config models.deepseek's attention reads for a ``kind``
        layer: its heads, ranks, head split, RoPE base, the rescale of its
        two low-rank vectors, its window, and the indexer (full alone)."""
        base = {f.name: getattr(self, f.name)
                for f in dataclasses.fields(DeepseekConfig)}
        if kind == WINDOW:
            base.update(
                num_heads=self.swa_num_heads, num_kv_heads=self.swa_num_heads,
                q_lora_rank=self.swa_q_lora_rank,
                kv_lora_rank=self.swa_kv_lora_rank,
                qk_nope_head_dim=self.swa_qk_nope_head_dim,
                qk_rope_head_dim=self.swa_qk_rope_head_dim,
                head_dim=self.swa_qk_nope_head_dim + self.swa_qk_rope_head_dim,
                v_head_dim=self.swa_v_head_dim,
                rope_theta=self.swa_rope_theta, index_topk=0)
        else:
            base.update(sliding_window=None)
        if self.lora_rescale:
            base.update(
                q_rescale=math.sqrt(self.hidden_size / base["q_lora_rank"]),
                kv_rescale=math.sqrt(self.hidden_size / base["kv_lora_rank"]))
        return DeepseekConfig(**base)

    @classmethod
    def from_hf(cls, hf: dict) -> "Dots3Config":
        """From published keys (``expert_parallel: {size, rank}`` states the
        deployment's share, as models.deepseek's). What the equations here
        do not hold is refused by name."""
        if hf.get("topk_method") != "noaux_tc":
            raise ValueError(
                f"dots3_note: topk_method {hf.get('topk_method')!r} is not "
                f"served (the family's router is noaux_tc: sigmoid scores, "
                f"a selection bias)")
        if hf.get("scoring_func", "sigmoid") != "sigmoid":
            raise ValueError(f"dots3_note: scoring_func "
                             f"{hf['scoring_func']!r} is not served")
        if int(hf.get("n_group", 1)) != 1 or int(hf.get("topk_group", 1)) != 1:
            raise ValueError("dots3_note: a group-limited router is not "
                             "served (the published config has no n_group)")
        if int(hf.get("moe_layer_freq", 1)) != 1:
            raise ValueError("dots3_note: moe_layer_freq other than 1 is "
                             "not served")
        if hf.get("attention_bias"):
            raise ValueError("dots3_note: attention_bias is not served")
        if hf.get("rope_scaling"):
            raise ValueError("dots3_note: rope_scaling is not served (the "
                             "published value is null)")
        for key in ("attention_gate_type", "swa_attention_gate_type"):
            if hf.get(key) != "headwise":
                raise ValueError(
                    f"dots3_note: {key} {hf.get(key)!r} is not served (a "
                    f"head's output is gated by one number: headwise)")
        for key in ("q_lora_rank", "swa_q_lora_rank", "index_topk"):
            if not hf.get(key):
                raise ValueError(f"dots3_note: no {key} is not served")
        ep = hf.get("expert_parallel") or {}
        nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
        L = hf["num_hidden_layers"]
        types = tuple(hf["layer_types"])
        if len(types) < L:
            raise ValueError(f"dots3_note: layer_types names {len(types)} "
                             f"layers, num_hidden_layers {L}")
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=L,
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads",
                                hf["num_attention_heads"]),
            head_dim=nope + rope,
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            sliding_window=int(hf.get("sliding_window_size") or 0) or None,
            num_experts=hf["n_routed_experts"],
            num_experts_per_tok=hf["num_experts_per_tok"],
            q_lora_rank=hf["q_lora_rank"],
            kv_lora_rank=hf["kv_lora_rank"],
            qk_nope_head_dim=nope,
            qk_rope_head_dim=rope,
            v_head_dim=hf["v_head_dim"],
            num_dense_layers=int(hf.get("first_k_dense_replace", 0)),
            moe_intermediate_size=hf["moe_intermediate_size"],
            num_shared_experts=int(hf.get("n_shared_experts") or 0),
            route_norm=bool(hf.get("norm_topk_prob", True)),
            route_scale=float(hf.get("routed_scaling_factor", 1.0)),
            ep_size=int(ep.get("size", 1)),
            ep_rank=int(ep.get("rank", 0)),
            index_topk=int(hf["index_topk"]),
            index_n_heads=int(hf["index_n_heads"]),
            index_head_dim=int(hf["index_head_dim"]),
            # a served depth is a prefix of the published list
            layer_types=types[:L],
            swa_num_heads=hf["swa_num_attention_heads"],
            swa_q_lora_rank=hf["swa_q_lora_rank"],
            swa_kv_lora_rank=hf["swa_kv_lora_rank"],
            swa_qk_nope_head_dim=hf["swa_qk_nope_head_dim"],
            swa_qk_rope_head_dim=hf["swa_qk_rope_head_dim"],
            swa_v_head_dim=hf["swa_v_head_dim"],
            swa_rope_theta=hf.get("swa_rope_theta", 10000.0),
            lora_rescale=bool(hf.get("apply_mla_qkv_lora_rescale", False)),
        )


def rope_table(cfg: Dots3Config, max_len: int, freq_base=None,
               freq_scale=None) -> dict:
    """models.llama.rope_table a kind: {kind: (cos, sin)}, each over the
    kind's rope dims at the kind's base. The indexer rotates by the full
    layers' table."""
    return {kind: mdl.rope_table(cfg.kind(kind), max_len, freq_base,
                                 freq_scale)
            for kind, _ in cfg.attn_kinds}


CONFIG = Dots3Config
# what the family does not serve, the weight modes it does, and why
# (models.llama ``refusal``)
UNSERVED = mdl.ONE_CHIP_POOL
WEIGHTS = ()
WHY = ("model_type dots3_note: its full layers select rows by an indexer over "
       "one bfloat16 latent pool and its window layers keep latent rows of "
       "their own width there (no K/V a head), on one chip")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

FLOAT32_LEAVES = ("expert_bias",)
# the leaves of a period's row that EVERY layer of the period has ([P, M,
# ...]); the routed experts' three ([P, M, E, ...]) are models.experts'
PER_LAYER = ("attn_norm", "mlp_norm", "moe_gate", "expert_bias",
             "shared_gate", "shared_up", "shared_down")
INDEX_LEAVES = ("idx_wq", "idx_wk", "idx_k_norm", "idx_k_bias", "idx_w")


def _kind_shapes(cfg: Dots3Config, kind: str, lead: tuple) -> dict:
    """A ``kind`` layer's attention leaves (no branch norms): models.
    deepseek's, the gate, and a full layer's indexer."""
    view = cfg.kind(kind)
    shapes = ds._attention_shapes(view, lead)
    del shapes["attn_norm"], shapes["mlp_norm"]
    D = cfg.hidden_size
    shapes["wg"] = (*lead, D, view.num_heads)
    if kind == FULL:
        Hi, di = cfg.index_n_heads, cfg.index_head_dim
        shapes.update({
            "idx_wq": (*lead, cfg.q_lora_rank, Hi * di),
            "idx_wk": (*lead, D, di),
            "idx_k_norm": (*lead, di), "idx_k_bias": (*lead, di),
            "idx_w": (*lead, D, Hi)})
    return shapes


def _moe_shapes(cfg: Dots3Config, lead: tuple, experts: tuple) -> dict:
    D, E, Fm = cfg.hidden_size, cfg.num_experts, cfg.moe_intermediate_size
    Fs = Fm * cfg.num_shared_experts
    shapes = {
        "moe_gate": (*lead, D, cfg.router_width),
        "expert_bias": (*lead, cfg.router_width),
        "w_gate": (*experts, E, D, Fm), "w_up": (*experts, E, D, Fm),
        "w_down": (*experts, E, Fm, D)}
    if Fs:
        shapes.update({"shared_gate": (*lead, D, Fs),
                       "shared_up": (*lead, D, Fs),
                       "shared_down": (*lead, Fs, D)})
    return shapes


def param_shapes(cfg: Dots3Config) -> dict:
    """Shapes of the parameter pytree (the module's docstring: ``dense_*``,
    ``lone_*``, and a row a period under ``layers``)."""
    D, F = cfg.hidden_size, cfg.intermediate_size
    nd, nl, P, M = (cfg.num_dense_layers, cfg.lone_layers, cfg.periods,
                    cfg.period)
    norms = ("attn_norm", "mlp_norm")
    shapes = {"embed": (cfg.vocab_size, D), "final_norm": (D,)}
    if nd:
        shapes.update({DENSE + n: s for n, s in {
            **{n: (nd, D) for n in norms}, **_kind_shapes(cfg, FULL, (nd,)),
            "w_gate": (nd, D, F), "w_up": (nd, D, F), "w_down": (nd, F, D),
        }.items()})
    if nl:
        shapes.update({LONE + n: s for n, s in {
            **{n: (nl, D) for n in norms}, **_kind_shapes(cfg, FULL, (nl,)),
            **_moe_shapes(cfg, (nl,), (nl, 1)),
        }.items()})
    shapes["layers"] = {}
    if P:
        shapes["layers"] = {
            **{n: (P, M, D) for n in norms},
            **_kind_shapes(cfg, FULL, (P,)),
            **{SWA + n: s for n, s in _kind_shapes(
                cfg, WINDOW, (P, M - 1)).items()},
            **_moe_shapes(cfg, (P, M), (P, M))}
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


BIAS_STD = 0.01
# elements from which a stacked matrix is drawn a slice at a time
STACKED_DRAW = 1 << 24


def init_leaf(key, shape, name: str, dtype, cfg=None):
    """One synthetic leaf: models.deepseek's draw under this file's names
    (norm gains as a trained checkpoint's lie); the selection bias N(0,
    ``BIAS_STD``) in float32 and the index key's LayerNorm bias N(0, 0.02):
    small and NOT zero, so that a program that drops either disagrees with
    the reference."""
    base = name
    for prefix in (DENSE, LONE, SWA):
        base = base.removeprefix(prefix)
    if base == "expert_bias":
        return jax.random.normal(key, shape, jnp.float32) * BIAS_STD
    if base == "idx_k_norm":
        return jnp.ones(shape, dtype)
    if len(shape) > 2 and math.prod(shape) >= STACKED_DRAW:
        # ONE small program looped over the stack: the TPU compiler takes
        # 12-14 s over a draw of 500 M elements in one piece and ~1 s over
        # its [D, F] slice (topology compile, PR 51); the 75 leaves' draws
        # compiled for 137 s of a 166 s load on the chip
        # (another branch of the one draw a leaf makes, not a second use)
        keys = jax.random.split(  # jaxlint: disable=rng-key-reuse
            key, math.prod(shape[:-2]))
        return lax.map(lambda k: ds.init_leaf(k, shape[-2:], base, dtype),
                       keys).reshape(shape)
    return ds.init_leaf(key, shape, base, dtype)


def checkpoint_leaves(cfg: Dots3Config, get, body: str = "model."):
    """(leaf name, host array) for every ``dense_*``, ``lone_*`` and
    ``layers`` leaf, one at a time, in DeepSeek-V3.2's tensor names (the
    indexer under ``self_attn.indexer``; the gate ``self_attn.gate_proj``:
    ASSUMED, untested against a checkpoint). Linear weights are transposed
    to right-multiply; of the published experts those of this rank."""
    import numpy as np

    attn = {
        "wq_a": ("self_attn.q_a_proj.weight", True),
        "q_norm": ("self_attn.q_a_layernorm.weight", False),
        "wq_b": ("self_attn.q_b_proj.weight", True),
        "wkv_a": ("self_attn.kv_a_proj_with_mqa.weight", True),
        "kv_norm": ("self_attn.kv_a_layernorm.weight", False),
        "wkv_b": ("self_attn.kv_b_proj.weight", True),
        "wo": ("self_attn.o_proj.weight", True),
        "wg": ("self_attn.gate_proj.weight", True),
    }
    index = {
        "idx_wq": ("self_attn.indexer.wq_b.weight", True),
        "idx_wk": ("self_attn.indexer.wk.weight", True),
        "idx_k_norm": ("self_attn.indexer.k_norm.weight", False),
        "idx_k_bias": ("self_attn.indexer.k_norm.bias", False),
        "idx_w": ("self_attn.indexer.weights_proj.weight", True),
    }
    norms = {"attn_norm": ("input_layernorm.weight", False),
             "mlp_norm": ("post_attention_layernorm.weight", False)}
    mlp = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
    held = range(cfg.ep_rank * cfg.num_experts,
                 (cfg.ep_rank + 1) * cfg.num_experts)

    def one(i: int, tail: str, transpose: bool):
        a = get(f"{body}layers.{i}.{tail}")
        return a.T if transpose else np.asarray(a)

    def stack(layers, src, shape=None):
        a = np.stack([one(i, *src) for i in layers])
        return a if shape is None else a.reshape(*shape, *a.shape[1:])

    def moe(layers, lead, experts_lead):
        yield "moe_gate", stack(layers, ("mlp.gate.weight", True), lead)
        yield "expert_bias", stack(
            layers, ("mlp.gate.e_score_correction_bias", False), lead)
        for leaf, name in mlp.items():
            a = np.stack([
                stack(layers, (f"mlp.experts.{e}.{name}.weight", True))
                for e in held], axis=1)
            yield leaf, a.reshape(*experts_lead, *a.shape[1:])
            if cfg.num_shared_experts:
                yield "shared_" + leaf[2:], stack(
                    layers, (f"mlp.shared_experts.{name}.weight", True),
                    lead)

    nd, nl, P, M = (cfg.num_dense_layers, cfg.lone_layers, cfg.periods,
                    cfg.period)
    if nd:
        for leaf, src in {**norms, **attn, **index}.items():
            yield DENSE + leaf, stack(range(nd), src)
        for leaf, name in mlp.items():
            yield DENSE + leaf, stack(range(nd),
                                      (f"mlp.{name}.weight", True))
    if nl:
        lone = range(nd, nd + nl)
        for leaf, src in {**norms, **attn, **index}.items():
            yield LONE + leaf, stack(lone, src)
        for leaf, a in moe(lone, None, (nl, 1)):
            yield LONE + leaf, a
    if P:
        first = nd + nl
        every = range(first, first + P * M)
        fulls = [first + r * M + M - 1 for r in range(P)]
        windows = [i for i in every if i not in fulls]
        for leaf, src in norms.items():
            yield leaf, stack(every, src, (P, M))
        for leaf, src in {**attn, **index}.items():
            yield leaf, stack(fulls, src)
        for leaf, src in attn.items():
            yield SWA + leaf, stack(windows, src, (P, M - 1))
        yield from moe(every, (P, M), (P, M))


# no per-slot state beside the pool: the routed count alone
init_rec = xp.init_rec


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def scores(cfg: Dots3Config, bias):
    """``noaux_tc`` with one group: the bias picks, the score weighs."""
    return xp.sigmoid_scores(cfg.num_experts_per_tok, bias, cfg.route_norm,
                             cfg.route_scale)


def layer_norm(x, w, b, eps: float):
    """LayerNorm with weight and bias (the index key's)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    return ((xf - mean) * lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(
        x.dtype) + b.astype(x.dtype)


def index_rope(x, cos, sin, rope: int):
    """RoPE on the FIRST ``rope`` dims of an indexer vector [..., heads,
    d], in the halves order (no interleaving)."""
    return jnp.concatenate(
        [mdl.apply_rope(x[..., :rope], cos, sin), x[..., rope:]], axis=-1)


def indexer(cfg: Dots3Config, w, cos, sin):
    """``index(h, cq)`` of a full layer for models.deepseek ``_attention``:
    the tokens' index queries ``q`` [B, T, Hi, di] (from the SAME low-rank
    query vector the attention's queries come from), their ONE index key
    ``k`` [B, T, di] (LayerNorm, then RoPE), and the heads' weights ``w``
    [B, T, Hi] float32, the two constant scales folded in."""
    Hi, di, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.rotary_dim

    def index(h, cq):
        with jax.named_scope("dsa/q"):
            q = qnt.matmul(cq, w("idx_wq"))
            q = index_rope(q.reshape(*q.shape[:-1], Hi, di), cos, sin, rope)
            weights = qnt.matmul(h, w("idx_w")).astype(jnp.float32) * (
                Hi ** -0.5 * di ** -0.5)
        with jax.named_scope("dsa/k"):
            k = layer_norm(qnt.matmul(h, w("idx_wk")), w("idx_k_norm"),
                           w("idx_k_bias"), INDEX_NORM_EPS)
            k = index_rope(k[..., None, :], cos, sin, rope)[..., 0, :]
        return {"q": q, "k": k, "w": weights}

    return index


def head_gate(w):
    """``gate(h, o)``: head j's output times ``sigmoid(h Wg)_j``."""
    def gate(h, o):
        with jax.named_scope("mla/gate"):
            g = jax.nn.sigmoid(qnt.matmul(h, w("wg")).astype(jnp.float32))
            return (o.astype(jnp.float32) * g[..., None]).astype(o.dtype)

    return gate


def forward(
    cfg: Dots3Config,
    params: Any,
    tokens: jax.Array,      # [B, T] i32
    positions: jax.Array,   # [B, T] i32
    kv_write: Any,          # engine.kvcache latent write policy:
                            # (stack, layer, row, state) -> (stack, view)
    kv_stack: Any,          # the latent pool's arrays (``latent_states``)
    mask: Any,              # the layout's, handed on to its attends
    rope: dict,             # {kind: (cos, sin)}: ``rope_table``
    attn: Optional[dict] = None,    # {kind: engine.kvcache ``LatentAttend``}
    embeds: Optional[jax.Array] = None,
    *,
    rec: Any = None,        # handed back as it came: no per-slot state
    valid: jax.Array,       # [B, T] bool: the real tokens
    slot: Any = None,       # (a recurrent family's: models.llama
    fresh: Any = None,      # ``family_module`` has the contract)
    kernels: Optional[bool] = None,     # models.experts.moe_block's
                            # ``experts_kernel``
) -> tuple[jax.Array, Any, Any, jax.Array]:
    """models.llama.forward for this family: (hidden [B, T, D], new pool
    stack, ``rec``, [experts touched, token-expert pairs] summed over the
    expert blocks). The dense and the lone layers one by one, then one
    ``lax.scan`` over the periods; (x, pool) is its carry, so the pool is
    written in place."""
    if attn is None:
        raise ValueError(mdl.refusal(
            cfg, "a forward with no latent attend (the contiguous K/V "
                 "layout)"))
    views = {kind: cfg.kind(kind) for kind, _ in cfg.attn_kinds}
    tables = {kind: mdl.rope_rows(table, positions)
              for kind, table in rope.items()}
    x = mdl.embed(cfg, params, tokens, embeds)
    eps = cfg.rms_norm_eps
    nd, nl, P, M = (cfg.num_dense_layers, cfg.lone_layers, cfg.periods,
                    cfg.period)

    def mixer(x, kv, w, norm, ordinal, kind):
        """x + Attn_kind(N(x)); ``ordinal`` the layer's own among its
        kind's: its entry of the kind's arrays of the pool."""
        cos, sin = tables[kind]

        def attend(q, row, index=None, **how):
            new_kv, view = kv_write(kv, ordinal, row, STATE[kind])
            if index is not None:
                new_kv, keys = kv_write(new_kv, ordinal, index["k"],
                                        INDEX_STATE)
                how["index"] = {"q": index["q"], "w": index["w"],
                                "keys": keys.cache}
            return attn[kind].run(q, view, mask, **how), new_kv

        h = mdl.rms_norm(x, norm, eps)
        out, kv = ds._attention(
            views[kind], h, w, cos, sin, attend, attn[kind].path,
            index=indexer(cfg, w, cos, sin) if kind == FULL else None,
            gate=head_gate(w))
        return x + out, kv

    def moe(x, w, norm, experts, p, m):
        with jax.named_scope("moe"):
            h = mdl.rms_norm(x, norm, eps)

            def shared(h):
                if not cfg.num_shared_experts:
                    return jnp.zeros(h.shape, jnp.float32)
                return xp.shared_expert(h, w("shared_gate"), w("shared_up"),
                                        w("shared_down"))

            out, n_touched, load = xp.moe_block(
                h.reshape(-1, h.shape[-1]), w("moe_gate"),
                scores(cfg, w("expert_bias")), experts, p, m,
                num_experts=cfg.num_experts, ep_rank=cfg.ep_rank,
                valid=valid.reshape(-1), shared=shared,
                experts_kernel=kernels)
        return x + out.reshape(x.shape), xp.counts(n_touched, load)

    counts = jnp.zeros(2, jnp.int32)
    with jax.named_scope("layers"):
        for i in range(nd):
            def w(name, i=i):
                return params[DENSE + name][i]

            x, kv_stack = mixer(x, kv_stack, w, w("attn_norm"),
                                jnp.int32(i), FULL)
            with jax.named_scope("dense_mlp"):
                h = mdl.rms_norm(x, w("mlp_norm"), eps)
                x = x + xp.swiglu(h, w("w_gate"), w("w_up"), w("w_down"))
        for j in range(nl):
            def w(name, j=j):
                return params[LONE + name][j]

            x, kv_stack = mixer(x, kv_stack, w, w("attn_norm"),
                                jnp.int32(nd + j), FULL)
            x, work = moe(x, w, w("mlp_norm"), tuple(
                params[LONE + leaf] for leaf in xp.EXPERT_LEAVES), j, 0)
            counts = counts + work

        layers = params["layers"]
        experts = tuple(layers[n] for n in xp.EXPERT_LEAVES) if P else ()
        # [P, M, ...] read as [P M, ...] (a bitcast) at row r M + m; the
        # window layers' [P, M - 1, ...] at r (M - 1) + m
        a_layer = {n: a.reshape(-1, *a.shape[2:]) for n, a in layers.items()
                   if n in PER_LAYER or n.startswith(SWA)}

        def period(carry, r):
            x, kv, counts = carry
            for m in range(M):
                kind = FULL if m == M - 1 else WINDOW

                def of_layer(name, m=m):
                    return lax.dynamic_index_in_dim(
                        a_layer[name], r * M + m, 0, keepdims=False)

                def w(name, m=m, kind=kind):
                    if name in a_layer:
                        return of_layer(name)
                    if kind == WINDOW:
                        return lax.dynamic_index_in_dim(
                            a_layer[SWA + name], r * (M - 1) + m, 0,
                            keepdims=False)
                    return lax.dynamic_index_in_dim(layers[name], r, 0,
                                                    keepdims=False)

                ordinal = (nd + nl + r if kind == FULL
                           else r * (M - 1) + m)
                x, kv = mixer(x, kv, w, of_layer("attn_norm"), ordinal, kind)
                x, work = moe(x, w, of_layer("mlp_norm"), experts, r, m)
                counts = counts + work
            return (x, kv, counts), None

        if P:
            (x, kv_stack, counts), _ = lax.scan(
                period, (x, kv_stack, counts),
                jnp.arange(P, dtype=jnp.int32))
    with jax.named_scope("final_norm"):
        x = mdl.rms_norm(x, params["final_norm"], eps)
    return x, kv_stack, rec, counts
