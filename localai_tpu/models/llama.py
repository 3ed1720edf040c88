"""Llama-family decoder (Llama 2/3, Mistral, Qwen2, Hermes, ...) — pure
functional JAX, designed for TPU serving.

This is the engine that replaces llama.cpp's C++ decode loop
(/root/reference/backend/cpp/llama/grpc-server.cpp:1546-1990) as the main LLM
compute path. Architectural choices are TPU-first, not a translation:

  * params are a pytree of stacked per-layer weights; the layer loop is a
    single ``lax.scan`` → one compiled layer body, O(1) XLA graph size.
  * all shapes are static: fixed slot count, fixed context; continuous
    batching is masking over slot tensors (see engine.scheduler), not
    ragged mutation.
  * bfloat16 weights/activations (MXU-native), float32 for RMSNorm,
    softmax and RoPE tables.
  * GQA is computed grouped ([S, n_kv, q_per_kv, ...]) so the KV repeat is
    a broadcast inside einsum, never materialized.
  * rope scaling supports linear / llama3 / yarn — parity with the
    reference's rope plumbing (/root/reference/core/config/
    backend_config.go:157-163, grpc-server.cpp:2279-2299).
"""

from __future__ import annotations

import dataclasses
import importlib
import math
from functools import partial
from typing import Any, ClassVar, Optional

import jax
import jax.numpy as jnp
from jax import lax

from localai_tpu.models import quant as qnt

PyTree = Any


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    attention_bias: bool = False          # Qwen2-style qkv bias
    rope_scaling: Optional[dict] = None   # HF rope_scaling dict
    sliding_window: Optional[int] = None  # keys a query sees, itself
                                          # included: every layer's (Mistral)
                                          # or, where a family names layer
                                          # kinds, its window layers' alone
                                          # (models.afmoe: ``attn_kinds``)
    num_experts: int = 0                  # Mixtral-class sparse MoE MLP
                                          # (0 = dense mlp)
    num_experts_per_tok: int = 2          # router top-k
    num_passes: int = 1                   # looped decoder: the whole stack
                                          # runs this often a token, the
                                          # final norm after every pass
    post_norm: bool = False               # "sandwich" layer: a second norm
                                          # on each branch's OUTPUT
    dtype: str = "bfloat16"

    # What the engine asks of a model, never its name; a family's subclass
    # (``FAMILIES``) answers otherwise.
    # layers that carry per-slot state that is not keys: the engine keeps it
    # beside the K/V pool, built by the family's ``init_rec`` and carried by
    # its ``forward``, and shares no prefix (keys alone do not restore it)
    recurrent: ClassVar[bool] = False
    # the module under localai_tpu.models that has the family's parameter
    # pytree and forward (``family_module``); None: this file's
    family: ClassVar[Optional[str]] = None
    # a family with routed experts that are told which they hold: its
    # forward counts each launch's routed work (models.experts)
    routed: ClassVar[bool] = False
    # a stack with more than one KIND of attention layer: (kind, window)
    # pairs, the window None where the kind sees every key (models.afmoe).
    # None: every layer attends alike, under ``sliding_window``
    attn_kinds: ClassVar[Optional[tuple]] = None
    # a family whose attention layers attend a SELECTION of the pool's
    # blocks, one a (stream, K/V head) (models.minicpm_sala): (tokens a
    # block, the most blocks a row attends, the first position whose row
    # selects). The engine serves it from the block pool under compacted
    # block tables (engine.kvcache ``select_decode``). None: every block
    select_blocks: ClassVar[Optional[tuple]] = None
    # a family whose attention caches ONE latent row a token and no K/V a
    # head (models.deepseek): the engine serves it from the latent block
    # pool (engine.kvcache ``LatentLayout``)
    latent: ClassVar[bool] = False

    @property
    def hd(self) -> int:
        return self.head_dim or self.hidden_size // self.num_heads

    @property
    def rotary_dim(self) -> int:
        """Leading dims of a head that RoPE rotates: all of them, but for a
        family with a partial rotary factor."""
        return self.hd

    @property
    def cache_layers(self) -> int:
        """Leading dimension of the K/V cache: an entry for every (pass,
        layer) pair, pass-major (``pass * num_layers + layer``). THE one
        place a cache's layer count comes from (engine.kvcache, the
        runner's scratch caches and prefix import)."""
        return self.num_passes * self.num_layers

    @property
    def q_per_kv(self) -> int:
        return self.num_heads // self.num_kv_heads

    @classmethod
    def from_hf(cls, hf: dict) -> "LlamaConfig":
        """Build from an HF config.json dict, by its ``model_type``: a row
        of ``FAMILIES`` is built by its module's config class; llama,
        mistral, mixtral (``num_local_experts``), qwen2 (qkv bias) and ouro,
        the looped decoder (``total_ut_steps`` passes over the stack, four
        norms a layer; its exit gate is not served: every pass runs for
        every token) are this class. A type in no row is built as a dense
        llama stack of the file's widths, unless its keys say that it has a
        state-space mixer (``mamba_*``), which such a stack would leave out:
        refused."""
        if hf.get("model_type") in FAMILIES:
            return _module(FAMILIES[hf["model_type"]]).CONFIG.from_hf(hf)
        mamba = sorted(k for k in hf if k.startswith("mamba_"))
        if mamba:
            raise ValueError(
                f"model_type {hf.get('model_type')!r} is not served: its "
                f"config carries a state-space mixer's keys ({mamba[0]}, "
                f"...) and a dense llama stack of its widths would leave "
                f"that mixer out")
        ouro = hf.get("model_type") == "ouro"
        return cls(
            vocab_size=hf.get("vocab_size", 32000),
            hidden_size=hf.get("hidden_size", 4096),
            intermediate_size=hf.get("intermediate_size", 11008),
            num_layers=hf.get("num_hidden_layers", 32),
            num_heads=hf.get("num_attention_heads", 32),
            num_kv_heads=hf.get("num_key_value_heads",
                                hf.get("num_attention_heads", 32)),
            head_dim=hf.get("head_dim"),
            rope_theta=hf.get("rope_theta", 10000.0),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            attention_bias=hf.get("attention_bias", False)
            or hf.get("model_type") == "qwen2",
            rope_scaling=hf.get("rope_scaling"),
            sliding_window=hf.get("sliding_window"),
            num_experts=hf.get("num_local_experts", 0),
            num_experts_per_tok=hf.get("num_experts_per_tok", 2),
            num_passes=int(hf.get("total_ut_steps", 1)) if ouro else 1,
            post_norm=ouro,
        )


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_table(cfg: LlamaConfig, max_len: int,
               freq_base: Optional[float] = None,
               freq_scale: Optional[float] = None) -> tuple[jax.Array, jax.Array]:
    """Precompute (cos, sin) [max_len, rotary_dim/2] in float32.

    Supports HF rope_scaling types 'linear', 'llama3', 'yarn' and the
    reference's raw rope_freq_base/rope_freq_scale overrides
    (/root/reference/core/config/backend_config.go:162-163). A family whose
    kinds of layer rotate by tables of their own brings its ``rope_table``
    (models.dots3: a table a kind).
    """
    fam = family_module(cfg)
    if hasattr(fam, "rope_table"):
        return fam.rope_table(cfg, max_len, freq_base, freq_scale)
    hd = cfg.rotary_dim
    base = freq_base or cfg.rope_theta
    inv_freq = 1.0 / (base ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    sc = cfg.rope_scaling or {}
    rtype = sc.get("rope_type", sc.get("type", "default"))
    attn_factor = 1.0

    if rtype == "linear":
        inv_freq = inv_freq / float(sc.get("factor", 1.0))
    elif rtype == "llama3":
        factor = float(sc.get("factor", 8.0))
        lo = float(sc.get("low_freq_factor", 1.0))
        hi = float(sc.get("high_freq_factor", 4.0))
        old_ctx = float(sc.get("original_max_position_embeddings", 8192))
        wavelen = 2 * math.pi / inv_freq
        # three bands: scale long wavelengths, keep short, smooth in between
        smooth = (old_ctx / wavelen - lo) / (hi - lo)
        smooth = jnp.clip(smooth, 0.0, 1.0)
        scaled = inv_freq / factor
        inv_freq = (1 - smooth) * scaled + smooth * inv_freq
    elif rtype == "yarn":
        # YaRN (arXiv:2309.00071) NTK-by-parts interpolation, as plumbed by
        # the reference's yarn_* options (backend.proto:225-229).
        factor = float(sc.get("factor", 1.0))
        old_ctx = float(sc.get("original_max_position_embeddings", 4096))
        beta_fast = float(sc.get("beta_fast", 32.0))
        beta_slow = float(sc.get("beta_slow", 1.0))
        attn_factor = float(sc.get("attention_factor") or
                            (0.1 * math.log(factor) + 1.0 if factor > 1 else 1.0))

        def corr_dim(n_rot: float) -> float:
            return (hd * math.log(old_ctx / (n_rot * 2 * math.pi))) / (
                2 * math.log(base)
            )

        low = max(math.floor(corr_dim(beta_fast)), 0)
        high = min(math.ceil(corr_dim(beta_slow)), hd // 2 - 1)
        ramp = jnp.clip(
            (jnp.arange(hd // 2, dtype=jnp.float32) - low) / max(high - low, 1),
            0.0, 1.0,
        )
        inv_freq = inv_freq / factor * ramp + inv_freq * (1 - ramp)

    if freq_scale:
        inv_freq = inv_freq * freq_scale
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [max_len, hd/2]
    return jnp.cos(freqs) * attn_factor, jnp.sin(freqs) * attn_factor


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [..., heads, hd]; cos/sin broadcastable [..., 1, hd/2].

    Uses the HF 'rotate_half' convention (pairs are (i, i+hd/2)) to match
    safetensors weights without permutation.
    """
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)).astype(x.dtype) * w.astype(x.dtype)


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# model_type -> the module under localai_tpu.models that serves it. THE one
# door: ``LlamaConfig.from_hf`` and ``family_module`` enter a family here, so
# the next one adds a row and its file (this file, models.loader and
# models.registry are not edited for it)
FAMILIES = {
    "qwen3_next": "qwen3_next",
    "afmoe": "afmoe",
    "axk1": "deepseek",
    "dots3_note": "dots3",
    "falcon_h1": "falcon_h1",
    "lfm2_moe": "lfm2",
    "minicpm_sala": "minicpm_sala",
    "smallthinker": "smallthinker",
}


def _module(name: str):
    return importlib.import_module(f"localai_tpu.models.{name}")


def family_module(cfg: LlamaConfig):
    """The module of the family ``cfg`` is of (``cfg.family``: a value of
    ``FAMILIES``); None for this file's plain stack. The contract, what
    every family's module holds:

      ``CONFIG``    its ``LlamaConfig`` subclass: ``from_hf``, ``family`` and
                    what the engine asks of a model without naming it
                    (``recurrent``, ``routed``, ``latent``, ``attn_kinds``,
                    ``cache_layers``);
      ``param_shapes(cfg)``, ``init_leaf(key, shape, name, dtype, cfg)``,
      ``checkpoint_leaves(cfg, get, body)``: its pytree, its synthetic draw
                    and its checkpoint's names;
      ``init_rec(cfg, num_slots)``: ``DecodeState.rec``, the per-slot state
                    its forward carries (and the runner's routed count);
      ``forward(cfg, params, tokens, positions, kv_write, kv_stack, mask,
      rope, attn=None, embeds=None, *, rec, valid, slot=None, fresh=None,
      kernels=None)`` -> (hidden [B, T, D], new stack, ``rec`` with its own
                    entries renewed, the launch's routed work or None).
                    ``valid`` [B, T] marks the real tokens; ``slot`` None:
                    batch row b is slot b (a decode step), else the ONE slot
                    the [1, T] chunk belongs to, which with ``fresh`` starts
                    from zero state; ``kernels`` None: the family's own
                    kernels as XLA, else whether they are interpreted. Built
                    on this file's frame (``rope_rows``, ``embed``,
                    ``xla_attend``, ``attend_through``).
                    A family that states ``RIDES`` takes one keyword more,
                    ``ride`` (a static row count; 0, the default, is the
                    two cases above, and no other family is ever passed it):
                    of the ``[1, ride + S]`` rows the first ``ride`` are
                    ``slot``'s chunk (``fresh`` as for any chunk) and the S
                    behind them a decode step's, row ``ride + b`` slot b's
                    (``engine.runner _decode_prefill_paged_fn``). Whatever
                    is per row runs ONCE over all of them, which is the
                    point: its weights are read once; where a layer mixes
                    rows each half goes in its own shape against its own
                    state, and the two are laid end to end. State goes
                    through ``rec_read`` / ``rec_write`` as ever: the step's
                    rows first, for every slot (``slot``'s own step row is
                    not live, ``valid`` False, and leaves its state as it
                    was), then the chunk's for ``slot``. What the step then
                    the chunk leave, a ride leaves; its routed work is one
                    count over both halves' real rows;
      ``UNSERVED``, ``WEIGHTS``, ``WHY``: the engine's features it does not
                    serve, the ``engine.quantization`` modes it does, and
                    the one sentence that says why (``refusal``).

    Optional, probed by ``getattr``: ``RIDES`` (True: the forward takes a
    ride, above; absent: the family's last chunk and the decode step stay two
    programs, ``engine.runner ModelRunner.rides``), ``base_name(leaf name)``
    (a leaf's name without its group's prefix) and ``FLOAT32_LEAVES``
    (models.loader), ``leaf_std(cfg, name)`` where ``WEIGHTS`` names a mode
    (models.registry), ``rope_table(cfg, max_len, freq_base, freq_scale)``
    (``rope_table`` above)."""
    return None if cfg.family is None else _module(cfg.family)


# What a family may not serve, by the engine's names for it (``UNSERVED``:
# engine.runner and models.manager ask ``unserved``). Two sets recur: what
# takes a sequence for its KEYS (state beside the pool is none), and what
# leaves one chip, the paged layout or a bfloat16 pool (attends chosen by a
# layer's kind, latent rows)
KEYS_ALONE = frozenset({
    "pipeline parallelism", "the ring prefill", "a device mesh",
    "the contiguous K/V layout", "speculative decoding",
    "the prompt cache's import"})
ONE_CHIP_POOL = frozenset({
    "self-extend", "pipeline parallelism", "the ring prefill",
    "a device mesh", "the contiguous K/V layout", "a int8 K/V pool",
    "a int4 K/V pool", "speculative decoding"})
# (a clause of a recurrent family's ``WHY``)
STATE_WHY = ("carry recurrent state, which is not keys: it lives in one "
             "dense row a slot beside the paged K/V pool and cannot be "
             "re-read, split or shipped as a prefix of keys can")


def unserved(cfg: LlamaConfig, feature: str) -> bool:
    fam = family_module(cfg)
    return fam is not None and feature in fam.UNSERVED


def refusal(cfg: LlamaConfig, what: str) -> str:
    """The one sentence that refuses ``what`` for ``cfg``'s family."""
    return f"{what} is not served for {family_module(cfg).WHY}"


def refuse(cfg: LlamaConfig, asked) -> None:
    """Raise the refusal of the first of ``asked``'s (feature, whether it is
    asked for) that is asked for and that ``cfg``'s family does not serve."""
    for feature, wanted in asked:
        if wanted and unserved(cfg, feature):
            raise ValueError(refusal(cfg, feature))


def refuse_quantization(cfg: LlamaConfig, quantization: str) -> None:
    """``engine.quantization`` for a family, synthetic weights and
    checkpoints alike: a mode its ``WEIGHTS`` does not name is refused."""
    fam = family_module(cfg)
    if fam is not None and quantization and quantization not in fam.WEIGHTS:
        raise ValueError(refusal(cfg, f"engine.quantization {quantization!r}"))


def param_shapes(cfg: LlamaConfig) -> dict:
    """Shapes of the stacked-parameter pytree."""
    fam = family_module(cfg)
    if fam is not None:
        return fam.param_shapes(cfg)
    D, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    shapes = {
        "embed": (cfg.vocab_size, D),
        "final_norm": (D,),
        "layers": {
            "attn_norm": (L, D),
            "wq": (L, D, Hq * hd),
            "wk": (L, D, Hkv * hd),
            "wv": (L, D, Hkv * hd),
            "wo": (L, Hq * hd, D),
            "mlp_norm": (L, D),
            "w_gate": (L, D, F),
            "w_up": (L, D, F),
            "w_down": (L, F, D),
        },
    }
    if cfg.num_experts:
        E = cfg.num_experts
        # Mixtral-class sparse MoE: expert-stacked ffn + a tiny router.
        # The leading E axis shards over the 'expert' mesh axis
        # (parallel.sharding), F over 'model' — expert × tensor parallelism.
        shapes["layers"].update({
            "moe_gate": (L, D, E),
            "w_gate": (L, E, D, F),
            "w_up": (L, E, D, F),
            "w_down": (L, E, F, D),
        })
    if cfg.post_norm:
        shapes["layers"]["attn_post_norm"] = (L, D)
        shapes["layers"]["mlp_post_norm"] = (L, D)
    if cfg.attention_bias:
        shapes["layers"]["bq"] = (L, Hq * hd)
        shapes["layers"]["bk"] = (L, Hkv * hd)
        shapes["layers"]["bv"] = (L, Hkv * hd)
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


def _init_leaf(key, shape, name: str, dtype):
    """One synthetic leaf: N(0, 0.02), a vector gain 1. A branch's OUTPUT
    norm gets gain 1 too: at the 0.02 the stacked pre-norm gains are drawn
    with, a layer (and so a whole pass) would change nothing."""
    if len(shape) == 1 or name.endswith("post_norm"):
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)


# what a family's synthetic draw that has OUTLIER channels in a norm's gain
# shares (one channel in ``OUTLIER_EVERY``, at ``OUTLIER_GAIN``: what makes a
# lower-precision ACTIVATION lossy)
OUTLIER_GAIN, OUTLIER_EVERY = 32.0, 192


def outlier_rms(width: int) -> float:
    """RMS of a normed activation behind a gain with outlier channels."""
    if width < OUTLIER_EVERY:
        return 1.0
    share = (width // OUTLIER_EVERY) / width
    return math.sqrt(1.0 + share * (OUTLIER_GAIN ** 2 - 1.0))


def init_params(rng: jax.Array, cfg: LlamaConfig, placement=None) -> PyTree:
    """Random init (testing / benchmarking with synthetic weights). Each
    leaf is its own jitted program so that, with a ``placement``
    (parallel.sharding.ParamPlacement), it is generated directly on the
    devices that will hold it — no leaf is ever whole on one chip first."""
    fam = family_module(cfg)
    leaf = _init_leaf if fam is None else partial(fam.init_leaf, cfg=cfg)
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(rng, len(flat))
    dtype = jnp.dtype(cfg.dtype)
    leaves = []
    for k, (kpath, shape) in zip(keys, flat):
        sh = None
        if placement is not None:
            sh = placement.shardings(
                tuple(p.key for p in kpath), jax.ShapeDtypeStruct(shape, dtype))
        leaves.append(jax.jit(  # jaxlint: disable=jit-in-loop
            leaf, static_argnums=(1, 2, 3), out_shardings=sh)(
                k, shape, kpath[-1].key, dtype))
    return jax.tree.unflatten(treedef, leaves)


# ---------------------------------------------------------------------------
# Per-slot state that is not keys (a ``recurrent`` family's ``init_rec``):
# dense arrays ``[*index, slots, ...]`` beside the K/V pool, a layer's rows
# read and written in place
# ---------------------------------------------------------------------------

def rec_read(arr, index: tuple, slot):
    """Rows of the layer at ``index`` (its leading axes: ``(layer,)``,
    ``(period, g)``): every slot's (``slot`` None) or one slot's, with a
    leading batch axis either way. ONE slice of the layer's rows: an index by
    the first axis alone would stage what lies under it."""
    n = len(index)
    zeros = (0,) * (arr.ndim - n - 1)
    if slot is None:
        return lax.dynamic_slice(arr, (*index, 0) + zeros,
                                 (1,) * n + arr.shape[n:])[(0,) * n]
    return lax.dynamic_slice(arr, (*index, slot) + zeros,
                             (1,) * (n + 1) + arr.shape[n + 1:])[(0,) * n]


def rec_write(arr, new, index: tuple, slot):
    n = len(index)
    zeros = (0,) * (arr.ndim - n - 1)
    return lax.dynamic_update_slice(
        arr, new[(None,) * n].astype(arr.dtype),
        (*index, 0 if slot is None else slot) + zeros)


def conv_rows(cat, n_real, K: int):
    """Of a causal convolution's rows ``cat`` [B, K - 1 + T, C] (a slot's last
    K - 1 inputs, then the chunk's) the K - 1 in front of the first token
    that is NOT real: after n real tokens rows n .. n + K - 2 (n = 0 leaves
    the state as it was)."""
    return jax.vmap(
        lambda rows, n: lax.dynamic_slice_in_dim(rows, n, K - 1, 0))(
            cat, n_real)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _layer(cfg: LlamaConfig, x, lp, cos, sin, attend, reduce=None,
           live=None):
    """One decoder layer. ``attend(q, k_new, v_new) -> (attn_out, new_kv)``
    is injected so prefill/decode/KV-cache policies stay out of the math.

    ``reduce`` (optional) is applied to the two row-parallel matmul outputs
    (attention-out, mlp-down) — under manual tensor parallelism inside
    shard_map it is ``lax.psum(·, 'model')``, turning the per-device
    partial sums into the Megatron two-psums-per-layer pattern. When None
    (single device, or GSPMD-managed sharding) the products are complete.

    ``live`` (a prompt's last prefill chunk, on one chip as on a mesh:
    ``forward``) cuts what stands behind the attend to the chunk's live
    rows; ``lp`` then holds the leaves in front of the attend alone."""
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    if reduce is not None:
        # local head counts under manual TP: weight shards carry Hq/tp and
        # Hkv/tp heads on each device
        Hq = lp["wq"].shape[-1] // hd
        Hkv = lp["wk"].shape[-1] // hd

    # the scopes name the model's parts in the profiler's trace (metadata
    # only: the compiled program is the same with or without them)
    with jax.named_scope("attn.qkv"):
        h = rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q = qnt.matmul(h, lp["wq"])
        k = qnt.matmul(h, lp["wk"])
        v = qnt.matmul(h, lp["wv"])
        if "bq" in lp:
            q = q + lp["bq"].astype(q.dtype)
            k = k + lp["bk"].astype(k.dtype)
            v = v + lp["bv"].astype(v.dtype)
        # the head split stays off the dots: folded into them, XLA wants each
        # weight [H, hd, D] and, the stacked leaves being [L, D, H*hd],
        # transposes every stack once a dispatch and stages a copy of each
        # layer's slice (16% of a 7B decode step). Behind the barrier the
        # dots stay [.., D] x [D, H*hd] and read the scanned slice in place,
        # as the MLP's and wo's do (tests/test_tpu_compile.py holds it)
        q, k, v = lax.optimization_barrier((q, k, v))
        q = q.reshape(*q.shape[:-1], Hq, hd)
        k = k.reshape(*k.shape[:-1], Hkv, hd)
        v = v.reshape(*v.shape[:-1], Hkv, hd)
    with jax.named_scope("attn.rope"):
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)

    attn, new_kv = attend(q, k, v)
    if live is None:
        return _behind_attend(cfg, x, attn, lp, reduce), new_kv
    rows, branch, stacks, layer = live

    def cut(r):
        # the layer's leaves are cut from their stacks inside the branch,
        # where the dots read them in place: a branch handed the layer's
        # slices has them copied out of the stacks first, 131 MB a layer
        def run(x, attn, stacks, layer):
            back = jax.tree.map(
                lambda w: lax.dynamic_index_in_dim(w, layer, 0, False), stacks)
            return jnp.concatenate(
                [_behind_attend(cfg, x[:, :r], attn[:, :r], {**lp, **back},
                                reduce), x[:, r:]], axis=1)
        return run

    return lax.switch(branch, [cut(r) for r in rows], x, attn, stacks,
                      layer), new_kv


# the stacked leaves ``_behind_attend`` reads
BEHIND_ATTEND = ("wo", "attn_post_norm", "mlp_norm", "moe_gate", "w_gate",
                 "w_up", "w_down", "mlp_post_norm")


def _behind_attend(cfg: LlamaConfig, x, attn, lp, reduce=None):
    """A layer from the attend's output on: the out product and its
    residual, the MLP (or the experts) and its residual. Rows do not mix."""
    with jax.named_scope("attn.out"):
        attn = attn.reshape(*attn.shape[:-2], -1)
        wo_out = qnt.matmul(attn, lp["wo"])
        if reduce is not None:
            wo_out = reduce(wo_out)
        if "attn_post_norm" in lp:      # sandwich layer: x + N2(Attn(N1 x))
            wo_out = rms_norm(wo_out, lp["attn_post_norm"], cfg.rms_norm_eps)
        x = x + wo_out

    if "moe_gate" in lp:
        with jax.named_scope("moe"):
            h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            x = x + _moe_mlp(cfg, h, lp, reduce)
    else:
        with jax.named_scope("mlp"):
            h = rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
            gated = (jax.nn.silu(qnt.matmul(h, lp["w_gate"]))
                     * qnt.matmul(h, lp["w_up"]))
            down = qnt.matmul(gated, lp["w_down"])
            if reduce is not None:
                down = reduce(down)
            if "mlp_post_norm" in lp:   # sandwich layer: h + N4(MLP(N3 h))
                down = rms_norm(down, lp["mlp_post_norm"], cfg.rms_norm_eps)
            x = x + down
    return x


def _moe_mlp(cfg: LlamaConfig, h, lp, reduce=None):
    """Mixtral-class sparse MoE MLP (parity: the reference's Mixtral GGUFs
    served by llama.cpp, gallery/index.yaml mixtral entries).

    Routing matches HF MixtralSparseMoeBlock: softmax over ALL experts,
    top-k, renormalize the selected weights. Compute is the dense-einsum
    formulation: every expert runs on every token and the router weights
    (zero off the top-k) select — the idiomatic TPU layout, since decode is
    weight-bandwidth-bound anyway (all expert weights stream from HBM once
    per step regardless) and it keeps static shapes/no gathers, letting the
    E axis shard over the 'expert' mesh axis and F over 'model'."""
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    logits = qnt.matmul(h, lp["moe_gate"]).astype(jnp.float32)   # [B, T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = lax.top_k(probs, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    # scatter the renormalized top-k back to a dense [B, T, E] weighting
    wfull = jnp.sum(
        jax.nn.one_hot(topi, E, dtype=topv.dtype) * topv[..., None], axis=-2
    )
    g = qnt.moe_up(h, lp["w_gate"])                              # [B, T, E, F]
    u = qnt.moe_up(h, lp["w_up"])
    a = jax.nn.silu(g) * u
    d = qnt.moe_down(a, lp["w_down"])                            # [B, T, E, D]
    out = jnp.einsum("...te,...ted->...td", wfull.astype(d.dtype), d)
    return reduce(out) if reduce is not None else out


def _grouped_attn(cfg: LlamaConfig, q, keys, values, mask):
    """Grouped-query attention.

    q: [S, T, Hq, hd], keys/values head-major: [S, Hkv, Lk, hd],
    mask: [S, T, Lk] bool (True = attend). Returns [S, T, Hq, hd].

    Head counts come from the operand SHAPES, not cfg: under manual tensor
    parallelism (shard_map bodies — parallel.ring, parallel.overlap) each
    device carries Hq/tp and Hkv/tp heads, and the same math applies to
    the local group."""
    S, T, Hq = q.shape[0], q.shape[1], q.shape[2]
    Hkv, hd = keys.shape[1], cfg.hd
    g = Hq // Hkv
    qg = q.reshape(S, T, Hkv, g, hd)
    scores = jnp.einsum("stkgh,sklh->skgtl", qg, keys) / math.sqrt(hd)
    scores = scores.astype(jnp.float32)
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(values.dtype)
    out = jnp.einsum("skgtl,sklh->stkgh", probs, values)
    return out.reshape(S, T, Hq, hd)


def output_gate(attn, gate):
    """A per-element sigmoid gate on the attention output, from a projection
    of the layer's input (models.qwen3_next, models.afmoe)."""
    return attn * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(attn.dtype)


def rope_rows(rope, positions):
    """(cos, sin) [B, T, 1, hd/2]: the table's rows at the tokens' positions."""
    cos_t, sin_t = rope
    return cos_t[positions][:, :, None, :], sin_t[positions][:, :, None, :]


def embed(cfg: LlamaConfig, params: PyTree, tokens, embeds=None, scale=None):
    """The ``embed`` scope: the tokens' rows of the table in the compute
    dtype (under ``scale``, a family's multiplier on them, in float32 in
    front of the one rounding), or the caller's rows as they are (multimodal
    injection bypasses the token gather)."""
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope("embed"):
        if embeds is not None:
            return embeds.astype(dtype)
        if scale is None:
            return qnt.embed_rows(params["embed"], tokens, dtype)
        return (qnt.embed_rows(params["embed"], tokens, jnp.float32)
                * scale).astype(dtype)


def xla_attend(cfg: LlamaConfig, positions):
    """The XLA attend over the context kv_write exposes: what a prefill
    chunk runs (and a decode step under attn_impl: xla). A kernel passed in
    as ``attn`` brings its own scope (attn.paged_decode)."""
    scope = "attn.prefill" if positions.shape[1] > 1 else "attn.decode"

    def attn(q, keys, values, m):
        with jax.named_scope(scope):
            return _grouped_attn(cfg, q, keys, values, m)

    return attn


def attend_through(kv_write, attn, mask, kv, layer):
    """``attend(q, *new) -> (out, new stack)`` of cache layer ``layer`` for a
    layer body: ``kv_write`` scatters the layer's new rows (K and V, or one
    latent row) into the whole stack and exposes what ``attn`` reads of it;
    an attend that stored the rows itself hands the stack back (the paged
    decode kernel)."""
    def attend(q, *new, **how):
        new_kv, *seen = kv_write(kv, layer, *new)
        out = attn(q, *seen, mask, **how)
        if isinstance(out, tuple):  # the attend wrote the stack
            out, new_kv = out
        return out, new_kv

    return attend


def forward(
    cfg: LlamaConfig,
    params: PyTree,
    tokens: jax.Array,      # [B, T] int32
    positions: jax.Array,   # [B, T] int32 (absolute positions for RoPE)
    kv_write: Any,          # KV write policy (engine.kvcache):
                            # fn(kv_stack, layer, k, v) -> (new_kv_stack, keys, values)
    kv_stack: Any,          # stacked KV pytree [L, ...]: carried through the
                            # layer scan and written in place, never sliced
    mask: jax.Array,        # [B, T, Lk] bool attention mask
    rope: tuple[jax.Array, jax.Array],
    attn: Any = None,       # optional override: fn(q, keys, values, mask) -> out
                            # (Pallas flash kernels inject here; None = XLA),
                            # or -> (out, new_kv_stack) where kv_write left
                            # the layer's rows for the attend to store
    embeds: Optional[jax.Array] = None,  # [B, T, D] input embeddings override
                            # (multimodal injection bypasses the token gather)
    reduce: Any = None,     # manual-TP row-parallel reduction applied to the
                            # attention-out / mlp-down products inside a
                            # shard_map body (parallel.overlap: one psum
                            # each); None = single device / GSPMD
    live: Any = None,       # a prompt's last prefill chunk
                            # (``ModelRunner.chunk_rows``): (row counts,
                            # index of the one that covers the chunk's real
                            # tokens); what stands behind the attend runs
                            # on those rows alone, a branch a row count
) -> tuple[jax.Array, Any]:
    """Shared transformer trunk: returns (hidden [B, T, D], updated kv_stack).

    The layer loop is ``lax.scan`` over the stacked weights and the layer
    index, so XLA compiles one layer body regardless of depth. The stacked
    KV is the scan's CARRY beside the activations: ``kv_write`` scatters
    the layer's new rows into the whole stack and exposes what the attend
    needs of it (or leaves the rows to an attend that stores them itself
    and hands the stack back: the paged decode kernel). A donated argument
    that becomes a loop carry and then the
    output is what XLA aliases end to end, so the update is in place; a
    scan cannot alias an ``xs`` to a ``ys``, which is why the stack is not
    scanned (that costs a second stack of temp, and a slice-out and a
    restack per layer). tests/test_tpu_compile.py holds the compiled
    programs to no stack-sized temp and no layer-shaped copy.

    A looped decoder (``cfg.num_passes`` > 1) runs that scan inside ONE
    rolled loop over the passes, the final norm after every pass; (x, kv)
    is the carry of both loops and ``kv_write`` is handed the CACHE layer
    ``pass * layers + layer`` (``cfg.cache_layers`` of them). With one pass
    nothing of that is traced: the programs are what they were.
    """
    cos, sin = rope_rows(rope, positions)
    x = embed(cfg, params, tokens, embeds)
    if attn is None:
        attn = xla_attend(cfg, positions)
    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    scanned, stacks = params["layers"], None
    if live is not None:
        stacks = {k: v for k, v in scanned.items() if k in BEHIND_ATTEND}
        scanned = {k: v for k, v in scanned.items() if k not in stacks}

    def stack(x, kv, first=None):
        """The whole stack once; its layers write and read the cache layers
        ``first .. first + n_layers - 1`` (None: 0, and nothing is added)."""
        cache_layer = jnp.arange(n_layers, dtype=jnp.int32)
        if first is not None:
            cache_layer = first + cache_layer

        def body(carry, layer_in):
            x, kv = carry
            lp, layer = layer_in

            return _layer(
                cfg, x, lp, cos, sin,
                attend_through(kv_write, attn, mask, kv, layer), reduce=reduce,
                live=live and (*live, stacks,
                               layer if first is None else layer - first),
            ), None

        with jax.named_scope("layers"):
            (x, kv), _ = lax.scan(body, (x, kv), (scanned, cache_layer))
        return x, kv

    if cfg.num_passes == 1:
        x, new_kv_stack = stack(x, kv_stack)
        with jax.named_scope("final_norm"):
            x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return x, new_kv_stack

    # looped decoder: ONE rolled loop over the passes, its body the layer
    # scan and the final norm (which runs after EVERY pass: its output feeds
    # the next pass, and the last pass's goes to the head). (x, kv) is the
    # carry of both loops, so the cache is still written in place; pass t
    # of layer l owns cache layer ``t * n_layers + l``
    def one_pass(t, carry):
        with jax.named_scope("loop.pass"):
            x, kv = stack(*carry, t * n_layers)
        with jax.named_scope("loop.norm"):
            x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
        return x, kv

    return lax.fori_loop(0, cfg.num_passes, one_pass, (x, kv_stack))


def logits_from_hidden(cfg: LlamaConfig, params: PyTree, x: jax.Array) -> jax.Array:
    with jax.named_scope("lm_head"):
        if cfg.tie_word_embeddings:
            return qnt.matmul_t(x, params["embed"])
        return qnt.matmul(x, params["lm_head"])
