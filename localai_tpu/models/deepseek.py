"""The DeepSeek-V3 block (``model_type: axk1``; SKT A.X-K1): every layer
LATENT attention (MLA), ``first_k_dense_replace`` dense layers in front of
layers with group-limited sigmoid-routed experts and an ungated shared expert.

Latent attention caches, a token a layer, ONE row: the compressed latent
``c`` (``kv_lora_rank``, normed) and ONE rope key ``kr``
(``qk_rope_head_dim``, shared by every head): ``[c | RoPE(kr)]``,
``latent_width`` elements, and nothing else. Keys and values are functions
of that row (``[k_nope | v] = c Wkvb`` a head), and there are two ways to
attend over it, identical in exact arithmetic:

  * DECOMPRESSED, the published form: rebuild k and v from the rows and
    attend as any head does. A prefill chunk's path here
    (``decompressed``): the rows of the span it has, a stretch at a time;
  * ABSORBED: fold W_uk into the query (``q~ = q_nope W_uk^T``, a head's
    query over the latent itself), attend over the rows AS THEY LIE with all
    heads at once, apply W_uv to the result. A decode step's path here
    (``absorbed``): the pool is never decompressed, a row is read once a
    slot for all 64 heads.

Which path a program takes is its layout's to say (engine.kvcache
``LatentLayout``: its ``LatentAttend.path``); this file has both.

What the serving engine holds of it (engine.runner): the latent block pool
(engine.kvcache ``LatentKVCache``) behind the block pool's allocator, tables
and whole-block prefix sharing; the dense prefix as top-level leaves
``dense_*`` ``[n_dense, ...]``, the expert layers under ``layers`` (leading
axis the layer, the stacked experts ``[layers, 1, E, ...]`` read in place by
models.experts); one ``lax.scan`` over the expert layers with (x, pool) its
carry. The expert block is models.experts': sigmoid scores over ALL
``n_routed_experts x ep_size``, the ``topk_group`` best of ``n_group``
groups kept (a group's score the sum of its two largest), the k largest
inside them, renormalised, times ``routed_scaling_factor``; TOLD which
experts it holds (``expert_parallel: {size, rank}``).

The plain reference is benchmark/reference/deepseek_family.py, and
tests/test_deepseek.py holds this file to it. Scopes: ``mla/q``,
``mla/kv_a``, ``mla/kv_b`` (under the chunk's attend), ``mla/o``,
``dense_mlp``, ``moe/{router,experts,shared}``; the attends bring their own
(``attn.latent_decode``, ``attn.latent_chunk``).
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


@dataclasses.dataclass(frozen=True)
class DeepseekConfig(LlamaConfig):
    """``LlamaConfig`` with the keys the block adds. ``num_experts`` is the
    number of routed experts HELD here; the router's width is
    ``num_experts * ep_size``. ``head_dim`` is a query's (nope + rope)."""

    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    num_dense_layers: int = 0       # ``first_k_dense_replace``
    moe_intermediate_size: int = 0
    num_shared_experts: int = 1
    n_group: int = 1
    topk_group: int = 1
    route_norm: bool = True         # ``norm_topk_prob``
    route_scale: float = 1.0        # ``routed_scaling_factor``
    softmax_mscale: float = 1.0     # YaRN's attention factor m: the scores
                                    # are scaled by hd^-1/2 m^2
    ep_size: int = 1
    ep_rank: int = 0
    # what a family built on this block adds to it (models.dots3); each off
    # here: constants on the two normed low-rank vectors, and an indexer
    # whose ``index_topk`` best rows a full layer attends alone
    q_rescale: float = 1.0
    kv_rescale: float = 1.0
    index_topk: int = 0
    index_n_heads: int = 0
    index_head_dim: int = 0

    family: ClassVar[str] = "deepseek"
    routed: ClassVar[bool] = True
    # the K/V cache holds one latent row a token (engine.kvcache
    # ``LatentLayout``), not K and V a head
    latent: ClassVar[bool] = True

    def __post_init__(self):
        if not 0 <= self.ep_rank < self.ep_size:
            raise ValueError(f"expert_parallel rank {self.ep_rank} outside "
                             f"size {self.ep_size}")
        if not 0 <= self.num_dense_layers < self.num_layers:
            raise ValueError(
                f"axk1: first_k_dense_replace {self.num_dense_layers} leaves "
                f"no expert layer of {self.num_layers}")
        if self.router_width % self.n_group or not (
                1 <= self.topk_group <= self.n_group):
            raise ValueError(
                f"axk1: {self.router_width} experts do not split into "
                f"n_group {self.n_group} groups of which topk_group "
                f"{self.topk_group} are kept")

    @property
    def rotary_dim(self) -> int:
        return self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Elements of a token's cached row: ``[c | kr]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_states(self) -> tuple:
        """(name, layers, elements a token) of each array the latent pool
        holds (engine.kvcache ``LatentKVCache``): here the one, ``c``."""
        return (("c", self.cache_layers, self.latent_width),)

    @property
    def softmax_scale(self) -> float:
        return self.hd ** -0.5 * self.softmax_mscale ** 2

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.num_dense_layers

    @property
    def router_width(self) -> int:
        return self.num_experts * self.ep_size

    @classmethod
    def from_hf(cls, hf: dict) -> "DeepseekConfig":
        """From published keys. ``expert_parallel: {size, rank}`` is no
        published key: it states the deployment's share (``n_routed_experts``
        is then what ONE of ``size`` chips holds). ``topk_method: "none"``
        is read as group-limited selection with NO correction bias (the
        keys ``n_group`` / ``topk_group`` the file states, the leaf
        ``noaux_tc`` adds absent); any other method is refused."""
        method = hf.get("topk_method", "none")
        if method != "none":
            raise ValueError(
                f"axk1: topk_method {method!r} is not served (noaux_tc adds "
                f"a selection bias leaf this family does not hold; greedy "
                f"and group_limited_greedy score with softmax)")
        if hf.get("scoring_func", "sigmoid") != "sigmoid":
            raise ValueError(f"axk1: scoring_func {hf['scoring_func']!r} is "
                             f"not served; the family's router is sigmoid")
        if int(hf.get("moe_layer_freq", 1)) != 1:
            raise ValueError("axk1: moe_layer_freq other than 1 is not "
                             "served (every layer behind the dense ones has "
                             "experts)")
        if hf.get("attention_bias"):
            raise ValueError("axk1: attention_bias is not served")
        if not hf.get("q_lora_rank"):
            raise ValueError("axk1: a query without its low-rank "
                             "projection (q_lora_rank null) is not served")
        sc = dict(hf.get("rope_scaling") or {})
        rtype = sc.get("rope_type", sc.get("type", "default"))
        factor = float(sc.get("factor", 1.0))

        def mscale(m: float) -> float:
            return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0

        m_all = float(sc.get("mscale_all_dim", 0) or 0)
        yarn = rtype == "yarn"
        if yarn:
            # the tables carry m(mscale) / m(mscale_all_dim), the SCORES
            # m(mscale_all_dim)^2 (models.llama.rope_table would put YaRN's
            # own factor on cos / sin where the dict names none)
            sc["attention_factor"] = (mscale(float(sc.get("mscale", 1)))
                                      / mscale(m_all))
        ep = hf.get("expert_parallel") or {}
        nope, rope = hf["qk_nope_head_dim"], hf["qk_rope_head_dim"]
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf.get("num_key_value_heads",
                                hf["num_attention_heads"]),
            head_dim=nope + rope,
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            rope_scaling=sc or None,
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
            n_group=int(hf.get("n_group", 1)),
            topk_group=int(hf.get("topk_group", 1)),
            route_norm=bool(hf.get("norm_topk_prob", True)),
            route_scale=float(hf.get("routed_scaling_factor", 1.0)),
            softmax_mscale=mscale(m_all) if yarn else 1.0,
            ep_size=int(ep.get("size", 1)),
            ep_rank=int(ep.get("rank", 0)),
        )


CONFIG = DeepseekConfig
# what the family does not serve, the weight modes it does, and why
# (models.llama ``refusal``)
UNSERVED = mdl.ONE_CHIP_POOL
WEIGHTS = ()
WHY = ("model_type axk1: its latent attention reads one bfloat16 pool of "
       "latent rows (no K/V a head), absorbed in a decode step and "
       "decompressed in a prefill chunk, on one chip")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

DENSE = "dense_"        # a dense-prefix leaf: top level, ``[n_dense, ...]``


def _attention_shapes(cfg: DeepseekConfig, lead: tuple) -> dict:
    D, H = cfg.hidden_size, cfg.num_heads
    ql, kl = cfg.q_lora_rank, cfg.kv_lora_rank
    return {
        "attn_norm": (*lead, D), "mlp_norm": (*lead, D),
        "wq_a": (*lead, D, ql), "q_norm": (*lead, ql),
        "wq_b": (*lead, ql, H * cfg.hd),
        "wkv_a": (*lead, D, cfg.latent_width), "kv_norm": (*lead, kl),
        "wkv_b": (*lead, kl, H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "wo": (*lead, H * cfg.v_head_dim, D),
    }


def param_shapes(cfg: DeepseekConfig) -> dict:
    """Shapes of the parameter pytree: the dense prefix's leaves at the top
    level (``dense_*``, leading axis the dense layer), the expert layers'
    under ``layers`` (leading axis the layer; the stacked experts carry the
    ``[layers, 1, E, ...]`` models.experts indexes by (layer, 0, expert))."""
    D, F = cfg.hidden_size, cfg.intermediate_size
    R, nd = cfg.expert_layers, cfg.num_dense_layers
    E, Fm = cfg.num_experts, cfg.moe_intermediate_size
    Fs = Fm * cfg.num_shared_experts
    shapes = {"embed": (cfg.vocab_size, D), "final_norm": (D,)}
    if nd:
        shapes.update({DENSE + n: s for n, s in {
            **_attention_shapes(cfg, (nd,)),
            "w_gate": (nd, D, F), "w_up": (nd, D, F), "w_down": (nd, F, D),
        }.items()})
    shapes["layers"] = {
        **_attention_shapes(cfg, (R,)),
        "moe_gate": (R, D, cfg.router_width),
        "w_gate": (R, 1, E, D, Fm), "w_up": (R, 1, E, D, Fm),
        "w_down": (R, 1, E, Fm, D),
    }
    if Fs:
        shapes["layers"].update({
            "shared_gate": (R, D, Fs), "shared_up": (R, D, Fs),
            "shared_down": (R, Fs, D)})
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


# The synthetic norm gains are drawn as a trained checkpoint's lie
# (models.afmoe ``init_leaf``'s lesson): the two low-rank norms over 1, so
# that a head's scores spread and attention is PEAKED (a position's output
# then depends on which rows it sees: the streams of a batch differ and
# route apart), and a few OUTLIER channels in the gains of the norms that
# feed the attention projections, the head, AND the cached latent (kv_norm:
# what makes a lower-precision ACTIVATION, or a cached row kept in fewer
# bits, lossy: an absmax scale follows the outlier, bfloat16's relative
# rounding does not care; with the latent at gain 1.5 throughout, rows
# rounded to 8 bits read inside the sound runs' range: PERF.md section 6,
# PR 48). The norm in front of the router and the experts keeps gain 1.
LATENT_NORM_GAIN = 1.5
OUTLIER_GAIN, OUTLIER_EVERY = mdl.OUTLIER_GAIN, mdl.OUTLIER_EVERY
OUTLIER_NORMS = ("attn_norm", "final_norm", "kv_norm")


def init_leaf(key, shape, name: str, dtype, cfg=None):
    """One synthetic leaf, for models.llama.init_params' loop: matrices
    N(0, 0.02); norm gains 1, but ``LATENT_NORM_GAIN`` on q_norm and kv_norm
    and ``OUTLIER_GAIN`` on a seeded ``1 / OUTLIER_EVERY`` of the channels of
    ``OUTLIER_NORMS`` (none under 192 channels)."""
    base = name.removeprefix(DENSE)
    rest = LATENT_NORM_GAIN if base in ("q_norm", "kv_norm") else 1.0
    if base in OUTLIER_NORMS and shape[-1] >= OUTLIER_EVERY:
        # the channels whose draw is among the vector's largest: those that
        # fewer than that many draws exceed (a stacked vector's ``lax.top_k``
        # costs the TPU compiler 12-16 s a leaf, this comparison under one:
        # topology compile, PR 51; the same channels to the tie)
        u = jax.random.uniform(key, shape)
        above = jnp.sum(u[..., None, :] > u[..., :, None], axis=-1)
        return jnp.where(above < shape[-1] // OUTLIER_EVERY, OUTLIER_GAIN,
                         rest).astype(dtype)
    if name.endswith("norm"):
        return jnp.full(shape, rest, dtype)
    # another branch of the one draw a leaf makes, not a second use
    return (jax.random.normal(  # jaxlint: disable=rng-key-reuse
        key, shape, jnp.float32) * 0.02).astype(dtype)


def checkpoint_leaves(cfg: DeepseekConfig, get, body: str = "model."):
    """(leaf name, host array) for every dense-prefix and ``layers`` leaf,
    one at a time, from an HF checkpoint of the block (DeepSeek-V3's tensor
    names); ``get(name)`` reads one tensor. Linear weights are transposed to
    right-multiply; of the published experts those of this rank are read,
    the router whole."""
    import numpy as np

    nd, R = cfg.num_dense_layers, cfg.expert_layers
    L = body + "layers.{i}."
    names = {
        "attn_norm": ("input_layernorm.weight", False),
        "mlp_norm": ("post_attention_layernorm.weight", False),
        "wq_a": ("self_attn.q_a_proj.weight", True),
        "q_norm": ("self_attn.q_a_layernorm.weight", False),
        "wq_b": ("self_attn.q_b_proj.weight", True),
        "wkv_a": ("self_attn.kv_a_proj_with_mqa.weight", True),
        "kv_norm": ("self_attn.kv_a_layernorm.weight", False),
        "wkv_b": ("self_attn.kv_b_proj.weight", True),
        "wo": ("self_attn.o_proj.weight", True),
    }

    def stack(layers, tail: str, transpose: bool):
        return np.stack([
            (lambda a: a.T if transpose else np.asarray(a))(
                get(L.format(i=i) + tail)) for i in layers])

    mlp = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
    if nd:
        for leaf, src in names.items():
            yield DENSE + leaf, stack(range(nd), *src)
        for leaf, name in mlp.items():
            yield DENSE + leaf, stack(range(nd), f"mlp.{name}.weight", True)
    rows = range(nd, nd + R)
    for leaf, src in names.items():
        yield leaf, stack(rows, *src)
    yield "moe_gate", stack(rows, "mlp.gate.weight", True)
    held = range(cfg.ep_rank * cfg.num_experts,
                 (cfg.ep_rank + 1) * cfg.num_experts)
    for leaf, name in mlp.items():
        yield leaf, np.stack([
            stack(rows, f"mlp.experts.{e}.{name}.weight", True)
            for e in held], axis=1)[:, None]
        if cfg.num_shared_experts:
            yield "shared_" + leaf[2:], stack(
                rows, f"mlp.shared_experts.{name}.weight", True)


# no per-slot state beside the pool: the routed count alone
init_rec = xp.init_rec


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def latent_norm(x, w, eps: float):
    """The norm of a low-rank projection's output (Nq, Nkv)."""
    return mdl.rms_norm(x, w, eps)


def scores(cfg: DeepseekConfig):
    """The family's scoring rule: group-limited sigmoid, no bias."""
    return xp.sigmoid_scores(
        cfg.num_experts_per_tok, None, cfg.route_norm, cfg.route_scale,
        n_group=cfg.n_group, topk_group=cfg.topk_group)


def rope_pairs(x):
    """A rope slice's lanes from the order the published weights store them
    in (pairs interleaved: (0, 1), (2, 3), ...) to the halves order
    ``models.llama.apply_rope`` rotates ((i, i + d/2)). q and the shared key
    take the same permutation, so their products are the interleaved
    rotation's; the cached key lies in the halves order."""
    d = x.shape[-1]
    return x.reshape(*x.shape[:-1], d // 2, 2).swapaxes(-1, -2).reshape(
        x.shape)


def kv_b(cfg: DeepseekConfig, w_kvb):
    """(W_uk [kl, H, nope], W_uv [kl, H, dv]) of a layer's ``wkv_b``."""
    w = w_kvb.reshape(cfg.kv_lora_rank, cfg.num_heads,
                      cfg.qk_nope_head_dim + cfg.v_head_dim)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def expand(cfg: DeepseekConfig, w_kvb, rows):
    """The published form's keys and values of cached ``rows [n, lanes]``:
    ``[k_nope | v] = c Wkvb`` a head, ``k = [k_nope | kr]`` with the ONE
    rope key of a row broadcast to the heads. -> (k [n, H, hd], v [n, H,
    dv])."""
    kl, H = cfg.kv_lora_rank, cfg.num_heads
    with jax.named_scope("mla/kv_b"):
        c = rows[:, :kl].astype(w_kvb.dtype)
        kr = rows[:, kl:cfg.latent_width].astype(w_kvb.dtype)
        kv = qnt.matmul(c, w_kvb).reshape(
            rows.shape[0], H, cfg.qk_nope_head_dim + cfg.v_head_dim)
        k = jnp.concatenate([
            kv[..., :cfg.qk_nope_head_dim],
            jnp.broadcast_to(kr[:, None, :], (rows.shape[0], H,
                                              cfg.qk_rope_head_dim))],
            axis=-1)
        return k, kv[..., cfg.qk_nope_head_dim:]


def _attention(cfg: DeepseekConfig, h, w, cos, sin, attend, path: str,
               index=None, gate=None):
    """Latent attention on normed h [B, T, D]; ``w(name)`` reads one of the
    layer's leaves, ``attend(q, row, **how)`` writes the tokens' rows and
    attends (``forward``), ``path`` the form to compute. What a family built
    on the block adds (models.dots3): ``index(h, cq)`` -> what its attend
    selects rows by, handed on as ``how["index"]``; ``gate(h, o)`` -> the
    heads' outputs [B, T, H, dv] gated, in front of ``wo``."""
    H, eps = cfg.num_heads, cfg.rms_norm_eps
    nope, kl = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    how = {}
    with jax.named_scope("mla/q"):
        cq = latent_norm(qnt.matmul(h, w("wq_a")), w("q_norm"), eps)
        if cfg.q_rescale != 1.0:
            cq = cq * cfg.q_rescale
        q = qnt.matmul(cq, w("wq_b"))
        q = lax.optimization_barrier(q).reshape(*q.shape[:-1], H, cfg.hd)
        q_rope = mdl.apply_rope(rope_pairs(q[..., nope:]), cos, sin)
        if path == "absorbed":
            # a head's query over the latent itself: W_uk folded in
            w_uk, _ = kv_b(cfg, w("wkv_b"))
            q = jnp.concatenate([
                jnp.einsum("bthn,chn->bthc", q[..., :nope], w_uk), q_rope],
                axis=-1)
        else:
            q = jnp.concatenate([q[..., :nope], q_rope], axis=-1)
    with jax.named_scope("mla/kv_a"):
        ckr = qnt.matmul(h, w("wkv_a"))
        c = latent_norm(ckr[..., :kl], w("kv_norm"), eps)
        if cfg.kv_rescale != 1.0:
            c = c * cfg.kv_rescale
        # ONE rope key a token, whatever the head
        kr = mdl.apply_rope(rope_pairs(ckr[..., None, kl:]), cos, sin)
        row = jnp.concatenate([c, kr[..., 0, :]], axis=-1)
    if index is not None:
        how["index"] = index(h, cq)
    if path == "absorbed":
        o, new_kv = attend(q, row, scale=cfg.softmax_scale, v_lanes=kl,
                           **how)
        with jax.named_scope("mla/o"):
            _, w_uv = kv_b(cfg, w("wkv_b"))
            o = jnp.einsum("bthc,chv->bthv", o, w_uv)
    else:
        o, new_kv = attend(q, row, scale=cfg.softmax_scale,
                           expand=lambda rows: expand(cfg, w("wkv_b"), rows),
                           v_dim=cfg.v_head_dim, **how)
    if gate is not None:
        o = gate(h, o)
    with jax.named_scope("mla/o"):
        out = qnt.matmul(o.reshape(*o.shape[:-2], H * cfg.v_head_dim),
                         w("wo"))
    return out, new_kv


def forward(
    cfg: DeepseekConfig,
    params: Any,
    tokens: jax.Array,      # [B, T] i32
    positions: jax.Array,   # [B, T] i32
    kv_write: Any,          # engine.kvcache latent write policy:
                            # (stack, layer, row) -> (stack, view)
    kv_stack: Any,          # the stacked latent pool
    mask: Any,              # the layout's, handed on to its attend
    rope: tuple[jax.Array, jax.Array],
    attn: Any = None,       # engine.kvcache ``LatentAttend``
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
    expert blocks). The dense prefix layer by layer, then one ``lax.scan``
    over the expert layers; (x, pool) is its carry, so the pool is written
    in place."""
    if attn is None:
        raise ValueError(mdl.refusal(
            cfg, "a forward with no latent attend (the contiguous K/V "
                 "layout)"))
    cos, sin = mdl.rope_rows(rope, positions)
    x = mdl.embed(cfg, params, tokens, embeds)
    eps, nd = cfg.rms_norm_eps, cfg.num_dense_layers

    def mixer(x, kv, w, layer):
        """x + Attn(N(x)) of cache layer ``layer``."""
        h = mdl.rms_norm(x, w("attn_norm"), eps)
        out, kv = _attention(cfg, h, w, cos, sin, mdl.attend_through(
            kv_write, attn.run, mask, kv, layer), attn.path)
        return x + out, kv

    with jax.named_scope("layers"):
        for i in range(nd):
            def w(name, i=i):
                return params[DENSE + name][i]

            x, kv_stack = mixer(x, kv_stack, w, jnp.int32(i))
            with jax.named_scope("dense_mlp"):
                h = mdl.rms_norm(x, w("mlp_norm"), eps)
                x = x + xp.swiglu(h, w("w_gate"), w("w_up"), w("w_down"))

        layers = params["layers"]
        experts = tuple(layers[n] for n in xp.EXPERT_LEAVES)
        flat = {n: a for n, a in layers.items()
                if n not in xp.EXPERT_LEAVES}

        def shared(h, w):
            if not cfg.num_shared_experts:
                return jnp.zeros(h.shape, jnp.float32)
            return xp.shared_expert(h, w("shared_gate"), w("shared_up"),
                                    w("shared_down"))

        def row(carry, r):
            x, kv, counts = carry

            def w(name):
                return lax.dynamic_index_in_dim(flat[name], r, 0,
                                                keepdims=False)

            x, kv = mixer(x, kv, w, nd + r)
            with jax.named_scope("moe"):
                h = mdl.rms_norm(x, w("mlp_norm"), eps)
                out, n_touched, load = xp.moe_block(
                    h.reshape(-1, h.shape[-1]), w("moe_gate"), scores(cfg),
                    experts, r, 0, num_experts=cfg.num_experts,
                    ep_rank=cfg.ep_rank, valid=valid.reshape(-1),
                    shared=lambda h: shared(h, w), experts_kernel=kernels)
            x = x + out.reshape(x.shape)
            return (x, kv, counts + xp.counts(n_touched, load)), None

        (x, kv_stack, counts), _ = lax.scan(
            row, (x, kv_stack, jnp.zeros(2, jnp.int32)),
            jnp.arange(cfg.expert_layers, dtype=jnp.int32))
    with jax.named_scope("final_norm"):
        x = mdl.rms_norm(x, params["final_norm"], eps)
    return x, kv_stack, rec, counts
