"""MiniCPM-SALA (``model_type: minicpm_sala``; openbmb MiniCPM-SALA 9B): a
dense decoder under MiniCPM's muP scalars whose mixer is, in most layers,
LIGHTNING linear attention (a decayed sum of outer products ``S <- lambda S +
k v^T`` read by the query, no softmax, a CONSTANT decay a (head, layer), RoPE
on q and k) and in the others MiniCPM4's grouped-query attention WITHOUT
positions whose queries, past ``dense_len`` tokens, attend a SELECTION of the
context's blocks (InfLLM-v2: parameter-free scores over mean-pooled keys, the
best ``topk`` blocks a (token, K/V head), first block and local window
forced); ``mixer_types`` is a LIST of the two kinds with no period.

What the serving engine holds of it (engine.runner):

  * the list is cut into RUNS of like layers (``plan``). A run of Lightning
    layers is one ``lax.scan`` over its rows of ``params["layers"]`` (the 24
    Lightning layers, a row each in stack order, read in place at the
    layer's row: a scanned slice would stage the layer's weights); a sparse
    layer is a piece of program of its own over ITS leaves, ``sa<n>_*`` at
    the top level with no leading axis (the published eight stand in runs
    of 1, 1, 2, 1 and 3 between four Lightning runs of 8, 6, 4 and 6). A
    Lightning head's log decay depends on the layer's place in the whole
    stack: it is a float32 BUFFER leaf, ``decay``, as the published code
    holds its slopes, filled by ``log_decay``;
  * the SPARSE layers alone cache K/V, in the paged pool under their own
    count (``cache_layers``). The pool's block IS the selection's block
    (``block_size`` tokens; the runner refuses another), so a stream's
    selected blocks are a short block table a (stream, K/V head) and the
    sparse attend is the pool's decode attend over that table
    (engine.kvcache ``select_decode``; ``select_blocks`` says how wide). A
    prefill chunk attends its span under the block mask of each row's own
    selection (``select_span_attend``);
  * per slot, beside the pool (``init_rec``): a Lightning layer's state ``S
    [H, dk, dv]`` float32; a sparse layer's COMPRESSED keys ``ck [Hkv,
    windows, hd]`` (window j the mean of keys ``stride j .. stride j +
    kernel - 1`` as cached) and the two running half-window sums ``seg``
    they are formed from as tokens arrive. The compressed keys are per-SLOT
    rows and not a third array of the pool because a window straddles two
    blocks (the last window that starts in a shared block ends in the
    sharer's own) and because a recurrent family's shared prefix restores
    the slot's rows anyway (engine.paged: a snapshot a registered prompt);
  * a Lightning prefill chunk is the chunked form of the recurrence
    (models.falcon_h1 ``recur``: the same shape of computation, a scalar
    decay a head) with float32 products; its decode step ops.gdn's kernel
    without the delta correction where attention's are kernels
    (``recur_in_place``), the XLA step otherwise. A token that is not real
    is the identity on every state.

The muP scalars stand where the model puts them, none folded into a weight:
``scale_emb`` on the embedding rows, ``scale_depth / sqrt(num_hidden_layers)``
on each branch's output in float32 in front of the residual's rounding, and
``dim_model_base / hidden_size`` on the final norm's output.

The plain reference is benchmark/reference/minicpm_sala_family.py and
tests/test_minicpm_sala.py holds this file to it. Scopes: ``lightning/
in_proj``, ``lightning/state`` (the state read, the recurrence, the state
written), ``lightning/out``; ``attn.qkv``, ``sparse/compress``,
``sparse/score``, ``sparse/select``, ``sparse/attend``, ``attn_gate``,
``attn.out``; ``mlp``.
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

from localai_tpu.models import falcon_h1 as fh
from localai_tpu.models import llama as mdl
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import LlamaConfig

F32 = jnp.float32
LIGHTNING, SPARSE = "lightning-attn", "minicpm4"
# tokens a prefill chunk's Lightning recurrence takes at once
LIGHTNING_CHUNK = 128
# a forced block's score: above any sum of ``q_per_kv`` probabilities
FORCED = 1e9


class Sparse(NamedTuple):
    """``sparse_config``: InfLLM-v2's sizes, in tokens but for ``topk`` and
    ``init_blocks`` (blocks)."""

    kernel_size: int = 32       # keys a compressed key is the mean of
    kernel_stride: int = 16     # ... and how far apart they start
    init_blocks: int = 1        # leading blocks every query attends
    block_size: int = 64        # the selection's unit
    window_size: int = 2048     # trailing tokens every query attends
    topk: int = 64              # blocks a query attends, the forced counted
    dense_len: int = 8192       # a query at a position below attends all


class Run(NamedTuple):
    """Consecutive layers of one kind: one scan."""

    kind: str
    rows: int
    first: int          # the first layer's index in the stack
    ordinal: int        # ... and among the layers of its kind


@functools.lru_cache(maxsize=None)
def plan(mixer_types: tuple) -> tuple:
    """``mixer_types`` as runs of like layers."""
    runs: list = []
    seen = {LIGHTNING: 0, SPARSE: 0}
    for i, kind in enumerate(mixer_types):
        if runs and runs[-1].kind == kind:
            runs[-1] = runs[-1]._replace(rows=runs[-1].rows + 1)
        else:
            runs.append(Run(kind, 1, i, seen[kind]))
        seen[kind] += 1
    return tuple(runs)


@dataclasses.dataclass(frozen=True)
class MiniCpmSalaConfig(LlamaConfig):
    """``LlamaConfig`` (the sparse layers' heads) with the keys the family
    adds."""

    mixer_types: tuple = ()
    lightning_nh: int = 32
    lightning_head_dim: int = 128
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    dim_model_base: int = 256
    sparse: Sparse = Sparse()

    recurrent: ClassVar[bool] = True
    family: ClassVar[str] = "minicpm_sala"

    def __post_init__(self):
        if len(self.mixer_types) != self.num_layers or set(
                self.mixer_types) - {LIGHTNING, SPARSE}:
            raise ValueError(
                f"minicpm_sala: mixer_types names {len(self.mixer_types)} "
                f"layers of kinds {sorted(set(self.mixer_types))}; "
                f"num_hidden_layers is {self.num_layers} and the kinds "
                f"served are {LIGHTNING} and {SPARSE}")
        sp = self.sparse
        if (sp.kernel_size != 2 * sp.kernel_stride
                or sp.block_size % sp.kernel_stride
                or sp.dense_len < sp.topk * sp.block_size
                or sp.topk < sp.init_blocks + sp.window_size
                // sp.block_size + 2):
            raise ValueError(
                f"minicpm_sala: sparse_config {sp._asdict()} is not served: "
                f"a compressed key is formed from two half-window sums "
                f"(kernel_size = 2 x kernel_stride), a block holds whole "
                f"strides, a row that selects has more than topk blocks "
                f"(dense_len >= topk x block_size) and topk holds the "
                f"forced blocks")

    @property
    def runs(self) -> tuple:
        return plan(self.mixer_types)

    @property
    def cache_layers(self) -> int:
        """K/V is cached by the sparse layers alone."""
        return self.mixer_types.count(SPARSE)

    @property
    def lightning_layers(self) -> int:
        return self.mixer_types.count(LIGHTNING)

    @property
    def windows(self) -> int:
        """Compressed keys a slot holds a (layer, K/V head): every stride of
        the served context starts one."""
        return -(-self.max_position_embeddings // self.sparse.kernel_stride)

    @property
    def select_blocks(self) -> tuple:
        """What the engine asks of a family whose attention layers attend a
        selection of the pool's blocks (engine.kvcache): (tokens a block,
        the most blocks a (stream, K/V head) attends: the width of its
        compacted table, a dense row's blocks counted, the first position
        whose row selects)."""
        sp = self.sparse
        return (sp.block_size,
                max(sp.topk, -(-sp.dense_len // sp.block_size)),
                sp.dense_len)

    @property
    def branch_scale(self) -> float:
        return self.scale_depth / math.sqrt(self.num_layers)

    @classmethod
    def from_hf(cls, hf: dict) -> "MiniCpmSalaConfig":
        """From published keys. ``sparse_config`` is MiniCPM4's own key (the
        sibling's published ``config.json`` states InfLLM-v2's sizes under
        it; this family's states none, and the defaults are the sibling's).
        A key that asks for what is not written is refused."""
        stated = (("attention_bias", False), ("attn_use_rope", False),
                  ("lightning_use_rope", True), ("qk_norm", True),
                  ("use_output_gate", True), ("use_output_norm", True),
                  ("attn_use_output_gate", True), ("hidden_act", "silu"),
                  ("lightning_scale", "1/sqrt(d)"), ("rope_scaling", None))
        for key, want in stated:
            if hf.get(key, want) != want:
                raise ValueError(
                    f"model_type minicpm_sala is served with {key} = "
                    f"{want!r} (what the published configuration states), "
                    f"not {hf[key]!r}")
        heads = hf["num_attention_heads"]
        hd = hf.get("head_dim") or hf["hidden_size"] // heads
        if (hf.get("lightning_nkv", hf["lightning_nh"]) != hf["lightning_nh"]
                or hf.get("lightning_head_dim", hd) != hd):
            raise ValueError(
                "model_type minicpm_sala is served with as many Lightning "
                "key heads as query heads, of the attention's head size")
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=heads,
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hd,
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-6),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            mixer_types=tuple(hf["mixer_types"]),
            lightning_nh=hf["lightning_nh"],
            lightning_head_dim=hf.get("lightning_head_dim", hd),
            scale_emb=float(hf.get("scale_emb", 1.0)),
            scale_depth=float(hf.get("scale_depth", 1.0)),
            dim_model_base=int(hf.get("dim_model_base", hf["hidden_size"])),
            sparse=Sparse(**(hf.get("sparse_config") or {})),
        )


CONFIG = MiniCpmSalaConfig
# what the family does not serve, the weight modes it does, and why
# (models.llama ``refusal``). ``int8``: every projection, the MLP and both
# tables through models.quant; norm gains as drawn, the state float32
UNSERVED = mdl.KEYS_ALONE | mdl.ONE_CHIP_POOL
WEIGHTS = ("int8",)
WHY = (f"model_type minicpm_sala: its Lightning layers {mdl.STATE_WHY} "
       f"(a shared prefix is served from the state kept at its boundary); "
       f"its sparse layers select blocks of a bfloat16 pool on one chip; "
       f"its projections are served in bfloat16 or as weight-only int8")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

# the decay is float32 whatever the compute dtype
FLOAT32_LEAVES = ("decay",)
_PREFIX = re.compile(r"^sa\d+_")


def base_name(name: str) -> str:
    """A leaf's name without its sparse layer's prefix."""
    return _PREFIX.sub("", name)


def sparse_prefix(n: int) -> str:
    return f"sa{n}_"


def layer_shapes(cfg: MiniCpmSalaConfig, kind: str) -> dict:
    """Shapes of ONE layer's leaves."""
    D, F, hd = cfg.hidden_size, cfg.intermediate_size, cfg.hd
    lightning = kind == LIGHTNING
    Hq = cfg.lightning_nh if lightning else cfg.num_heads
    Hkv = cfg.lightning_nh if lightning else cfg.num_kv_heads
    shapes = {
        "attn_norm": (D,), "mlp_norm": (D,),
        "w_gate": (D, F), "w_up": (D, F), "w_down": (F, D),
        "wq": (D, Hq * hd), "wk": (D, Hkv * hd), "wv": (D, Hkv * hd),
        "w_ogate": (D, Hq * hd), "wo": (Hq * hd, D),
        "q_norm": (hd,), "k_norm": (hd,),
    }
    if lightning:
        # the norm on the mixer's output, and log lambda a head: a BUFFER
        # (``log_decay`` of the layer's place in the stack), no weight
        shapes.update(out_norm=(Hq * hd,), decay=(Hq,))
    return shapes


def param_shapes(cfg: MiniCpmSalaConfig) -> dict:
    """Shapes of the parameter pytree: the Lightning layers a row each under
    ``layers``, in stack order; sparse layer n's leaves at the top level
    under ``sa<n>_``, no leading axis (eight layers of runs 1, 1, 2, 1, 3:
    each is its own piece of program either way, and a reader of ONE
    layer's weights reads one layer's)."""
    D = cfg.hidden_size
    shapes: dict = {"embed": (cfg.vocab_size, D), "final_norm": (D,)}
    Nl = cfg.lightning_layers
    if Nl:
        shapes["layers"] = {n: (Nl, *s) for n, s in
                            layer_shapes(cfg, LIGHTNING).items()}
    for n in range(cfg.cache_layers):
        shapes.update({sparse_prefix(n) + name: s for name, s in
                       layer_shapes(cfg, SPARSE).items()})
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


# The synthetic draw. Under the published scalars N(0, 0.02) matrices leave
# the logits flat and a reference check over them passes whatever is wrong
# (models.falcon_h1 says the same of its multipliers), so every matrix is
# drawn at the deviation that makes its OUTPUT, behind the scalars the model
# puts on it, of order 1 (``leaf_std``): each branch moves the residual by
# ``BRANCH_RMS`` behind ``branch_scale``, the letters' logits spread by
# ``LOGIT_STD`` behind ``dim_model_base / hidden``, the table's rows are of
# deviation 1 behind ``scale_emb``. The q and k norms' gains are
# ``QK_NORM_GAIN`` (scores that are PEAKED: a selection that is wrong then
# shows); the other gains 1 but for a few OUTLIER channels in the norm in
# front of the mixers and in the final norm (models.afmoe's draw: what makes
# a lower-precision ACTIVATION lossy).
BRANCH_RMS = 0.5
LOGIT_STD = 1.5
QK_NORM_GAIN = 1.5
OUTLIER_GAIN, OUTLIER_EVERY = mdl.OUTLIER_GAIN, mdl.OUTLIER_EVERY
OUTLIER_NORMS = ("attn_norm", "final_norm")


def leaf_std(cfg: MiniCpmSalaConfig, name: str) -> Optional[float]:
    """The deviation a synthetic MATRIX leaf is drawn at; None for a leaf
    that is no matrix (``init_leaf`` draws those)."""
    D = cfg.hidden_size
    fan_h = math.sqrt(D) * mdl.outlier_rms(D)   # behind attn_norm/final_norm
    width = cfg.num_heads * cfg.hd
    sparse = name != base_name(name)
    name = base_name(name)
    if name == "embed":
        return 1.0 / cfg.scale_emb
    if name == "lm_head":
        return LOGIT_STD * (D / cfg.dim_model_base) / fan_h
    if name in ("wq", "wk", "wv", "w_ogate"):
        return 1.0 / fan_h
    if name == "wo":
        # under a sigmoid gate: a normed head (RMS ~0.54), or a softmax's
        # output at ~0.6 of its values' RMS (~0.33)
        return BRANCH_RMS / ((0.33 if sparse else 0.54) * math.sqrt(width)
                             * cfg.branch_scale)
    if name in ("w_gate", "w_up"):
        return 1.0 / math.sqrt(D)
    if name == "w_down":    # silu(g) u of unit g, u has RMS ~0.6
        return BRANCH_RMS / (0.6 * math.sqrt(cfg.intermediate_size)
                             * cfg.branch_scale)
    return None


def log_decay(cfg: MiniCpmSalaConfig) -> np.ndarray:
    """log lambda [Lightning layers, H]: ``-2^(-8 (h + 1) / H) (1 - l / (L
    - 1) + 1e-5)``, h the head, l the layer's index in the WHOLE stack of L:
    Lightning Attention-2's slopes under its layer factor."""
    H = cfg.lightning_nh
    slope = 2.0 ** (-8.0 * (np.arange(H) + 1) / H)
    at = np.array([i for i, k in enumerate(cfg.mixer_types)
                   if k == LIGHTNING], np.float64)
    factor = 1.0 - at / max(cfg.num_layers - 1, 1) + 1e-5
    return (-slope[None, :] * factor[:, None]).astype(np.float32)


def init_leaf(key, shape, name: str, dtype, cfg: MiniCpmSalaConfig):
    """One synthetic leaf, for models.llama.init_params' loop: matrices
    N(0, ``leaf_std``); gains 1, ``QK_NORM_GAIN`` on q and k, and
    ``OUTLIER_GAIN`` on a seeded ``1 / OUTLIER_EVERY`` of the channels of
    ``OUTLIER_NORMS``; the decay what ``log_decay`` says, float32."""
    # one draw a leaf: the uses of ``key`` are branches of one choice
    std = leaf_std(cfg, name)
    name = base_name(name)
    if name == "decay":
        return jnp.asarray(log_decay(cfg))
    if std is not None:
        w = jax.random.normal(key, shape, F32) * std
    elif name in ("q_norm", "k_norm"):
        w = jnp.full(shape, QK_NORM_GAIN, F32)
    elif name in OUTLIER_NORMS and shape[-1] >= OUTLIER_EVERY:
        u = jax.random.uniform(  # jaxlint: disable=rng-key-reuse
            key, shape)
        kth = lax.top_k(u, shape[-1] // OUTLIER_EVERY)[0][..., -1:]
        w = jnp.where(u >= kth, OUTLIER_GAIN, 1.0)
    else:                   # mlp_norm, out_norm, a narrow norm
        w = jnp.ones(shape, F32)
    return w.astype(dtype)


def checkpoint_leaves(cfg: MiniCpmSalaConfig, get, body: str = "model."):
    """(leaf name, host array) for every layer's leaves and the final norm,
    one at a time, from an HF ``minicpm_sala`` checkpoint; ``get(name)``
    reads one tensor. Linear weights are transposed to right-multiply; the
    decay is computed (the published code holds it as a buffer that is not
    saved). The names are MiniCPM4's for what the two kinds share and the
    published code's FROM MEMORY for the Lightning mixer's gate and output
    norm (tests/test_minicpm_sala.py holds them by a checkpoint it
    writes)."""
    L = body + "layers.{i}."
    names = {"attn_norm": ("input_layernorm.weight", np.asarray),
             "mlp_norm": ("post_attention_layernorm.weight", np.asarray),
             "w_gate": ("mlp.gate_proj.weight", np.transpose),
             "w_up": ("mlp.up_proj.weight", np.transpose),
             "w_down": ("mlp.down_proj.weight", np.transpose),
             "wq": ("self_attn.q_proj.weight", np.transpose),
             "wk": ("self_attn.k_proj.weight", np.transpose),
             "wv": ("self_attn.v_proj.weight", np.transpose),
             "w_ogate": ("self_attn.o_gate.weight", np.transpose),
             "wo": ("self_attn.o_proj.weight", np.transpose),
             "q_norm": ("self_attn.q_norm.weight", np.asarray),
             "k_norm": ("self_attn.k_norm.weight", np.asarray),
             "out_norm": ("self_attn.o_norm.weight", np.asarray)}
    at = {kind: [i for i, k in enumerate(cfg.mixer_types) if k == kind]
          for kind in (LIGHTNING, SPARSE)}
    if at[LIGHTNING]:
        for leaf in layer_shapes(cfg, LIGHTNING):
            if leaf == "decay":
                yield leaf, log_decay(cfg)
                continue
            tail, fix = names[leaf]
            yield leaf, np.stack([fix(get(L.format(i=i) + tail))
                                  for i in at[LIGHTNING]])
    for n, i in enumerate(at[SPARSE]):
        for leaf in layer_shapes(cfg, SPARSE):
            tail, fix = names[leaf]
            yield sparse_prefix(n) + leaf, fix(get(L.format(i=i) + tail))
    yield "final_norm", np.asarray(get(body + "norm.weight"))


# ---------------------------------------------------------------------------
# Per-slot state beside the K/V pool
# ---------------------------------------------------------------------------

def init_rec(cfg: MiniCpmSalaConfig, num_slots: int) -> dict:
    """The state for ``num_slots`` slots, all zero: the Lightning layers'
    ``S [layers, slots, H, dk, dv]`` float32; the sparse layers' compressed
    keys ``ck [layers, slots, windows, Hkv hd]`` in the compute dtype (as
    the pool holds the keys they are means of; a window's K/V heads side by
    side in ONE row: the step's write is a row, the scores' matmul contracts
    the rows' minor axis, and neither restages the array) and the sums of the last
    whole and of the running half window, ``seg [layers, slots, Hkv, 2,
    hd]`` float32."""
    hd, Hkv = cfg.hd, cfg.num_kv_heads
    return {
        "S": jnp.zeros((cfg.lightning_layers, num_slots, cfg.lightning_nh,
                        hd, hd), F32),
        "ck": jnp.zeros((cfg.cache_layers, num_slots, cfg.windows, Hkv * hd),
                        jnp.dtype(cfg.dtype)),
        "seg": jnp.zeros((cfg.cache_layers, num_slots, Hkv, 2, hd), F32),
    }


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def norm(x, w, eps: float, scale: float = 1.0):
    """Plain RMSNorm, float32 inside, rounded once (``scale``: a scalar that
    stands on the norm's output)."""
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps) * w.astype(F32)
    return (out if scale == 1.0 else out * scale).astype(x.dtype)


def residual(cfg: MiniCpmSalaConfig, x, out):
    """x + ``branch_scale`` out, float32, rounded once."""
    return (x.astype(F32) + out.astype(F32) * cfg.branch_scale).astype(
        x.dtype)


def output_gate(o, gate):
    """``o * sigmoid(gate)``, float32; o [..., heads, hd], gate [..., heads
    hd]."""
    return o * jax.nn.sigmoid(gate.astype(F32)).reshape(o.shape)


def _lightning(cfg: MiniCpmSalaConfig, h, w, cos, sin, state_step, valid):
    """The Lightning mixer on normed h [B, T, D]: ``state_step(q, k, v, g)``
    steps the layer's state (``fh.recur`` on its S0, or the decode step's
    kernel on the carried array). Returns (out [B, T, D], ``state_step``'s
    state)."""
    B, T, _ = h.shape
    H, hd, eps = cfg.lightning_nh, cfg.lightning_head_dim, cfg.rms_norm_eps
    with jax.named_scope("in_proj"):
        q = qnt.matmul(h, w("wq"))
        k = qnt.matmul(h, w("wk"))
        v = qnt.matmul(h, w("wv"))
        gate = qnt.matmul(h, w("w_ogate"))
        # the head split stays off the dots (models.llama._layer says why)
        q, k, v, gate = lax.optimization_barrier((q, k, v, gate))
        q = mdl.apply_rope(norm(q.reshape(B, T, H, hd), w("q_norm"), eps),
                           cos, sin)
        k = mdl.apply_rope(norm(k.reshape(B, T, H, hd), w("k_norm"), eps),
                           cos, sin)
    with jax.named_scope("state"):
        # a token that is not real is the identity on S: no decay, no write
        live = valid[..., None]
        g = jnp.where(live, w("decay").astype(F32), 0.0)
        vl = jnp.where(live[..., None], v.reshape(B, T, H, hd).astype(F32),
                       0.0)
        S, y = state_step(q.astype(F32), k.astype(F32), vl, g)
    with jax.named_scope("out"):
        y = y * hd ** -0.5
        y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + eps)
        y = y * w("out_norm").astype(F32).reshape(H, hd)
        y = output_gate(y, gate).reshape(B, T, H * hd)
        return qnt.matmul(y.astype(h.dtype), w("wo")), S


def compress(cfg: MiniCpmSalaConfig, seg, k, offset, n):
    """The compressed keys that CLOSE among one row's new keys. seg [G, 2,
    hd] float32: the sums of the keys of the last whole half window (a
    stride of tokens) and of the running one; k [T, G, hd] the row's new
    keys as cached, the first ``n`` of them real, the first at position
    ``offset``. A window is two half windows: it closes with its second.
    Returns (c [halves, G, hd] float32, their window indices [halves] with
    ``cfg.windows`` (out of bounds: written nowhere) where none closed, the
    new seg)."""
    s, K = cfg.sparse.kernel_stride, cfg.sparse.kernel_size
    T = k.shape[0]
    halves = (s - 1 + T - 1) // s + 1       # half windows the row can touch
    t = jnp.arange(T)
    rel = (offset % s + t) // s
    onehot = (rel[None, :] == jnp.arange(halves)[:, None]) & (t < n)[None, :]
    sums = jnp.einsum("it,tgh->igh", onehot.astype(F32), k.astype(F32),
                      precision=lax.Precision.HIGHEST)
    sums = sums.at[0].add(seg[:, 1])
    segs = jnp.concatenate([seg[:, 0][None], sums])     # half -1, 0, 1, ..
    c = (segs[:-1] + segs[1:]) / K
    base = offset // s
    done = (offset + n) // s - base         # half windows the row completed
    j = base + jnp.arange(halves) - 1
    closed = (jnp.arange(halves) < done) & (j >= 0)
    running = jnp.where(done < halves, lax.dynamic_index_in_dim(
        sums, jnp.minimum(done, halves - 1), 0, keepdims=False), 0.0)
    whole = lax.dynamic_index_in_dim(segs, done, 0, keepdims=False)
    return (c, jnp.where(closed, j, cfg.windows),
            jnp.stack([whole, running], axis=1))


def block_scores(cfg: MiniCpmSalaConfig, q, ck, t, blocks: int):
    """InfLLM-v2's score of every block for queries q [..., G, g, hd] at
    positions t [...] over compressed keys ck [..., J, G hd] (leading axes
    broadcast; a window's heads side by side, so each query head meets its
    own K/V head's lanes and zeros elsewhere: one matmul over the rows as
    they lie): [..., G, blocks] float32. A window counts once it is whole
    (``stride j + kernel <= t + 1``); a K/V head's query heads each weigh
    the windows by a softmax and the group's weights are SUMMED; a block
    scores the best window that touches it; the first ``init_blocks`` and
    the blocks of the last ``window_size`` tokens score ``FORCED``, a block
    past the query's own -1."""
    sp = cfg.sparse
    s, K, bs = sp.kernel_stride, sp.kernel_size, sp.block_size
    J = ck.shape[-2]
    G, g, hd = q.shape[-3:]
    own = jnp.eye(G, dtype=q.dtype)[:, None, :, None]
    wide = (q[..., None, :] * own).reshape(*q.shape[:-3], G * g, G * hd)
    scores = jnp.einsum("...qc,...jc->...qj", wide, ck,
                        preferred_element_type=F32) * hd ** -0.5
    scores = scores.reshape(*scores.shape[:-2], G, g, J)
    tt = t[..., None, None, None]
    whole = s * jnp.arange(J) + K <= tt + 1
    scores = jnp.where(whole, scores, -jnp.inf)
    top = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(whole, jnp.exp(scores - jnp.where(
        jnp.isfinite(top), top, 0.0)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    r = jnp.sum(p, axis=-2)                             # [..., G, J]
    # block m is touched by windows per m - back .. per m + per - 1
    per, back = bs // s, (K - 1) // s
    pad = per * blocks - J
    if pad > 0:
        r = jnp.pad(r, [(0, 0)] * (r.ndim - 1) + [(0, pad)])
    r = r[..., :per * blocks].reshape(*r.shape[:-1], blocks, per)
    best = jnp.max(r, axis=-1)
    for d in range(1, back + 1):        # windows that start a block earlier
        before = jnp.concatenate(
            [jnp.zeros_like(r[..., :1, 0]), r[..., :-1, per - d]], axis=-1)
        best = jnp.maximum(best, before)
    m = jnp.arange(blocks)
    tb = t[..., None, None]
    forced = (m < sp.init_blocks) | (
        m >= jnp.maximum(tb - sp.window_size + 1, 0) // bs)
    return jnp.where(m > tb // bs, -1.0, jnp.where(forced, FORCED, best))


def select(cfg: MiniCpmSalaConfig, q, ck, t, blocks: int):
    """Which blocks a (query, K/V head) attends: [..., G, blocks] bool, the
    ``topk`` best by ``block_scores``, ties to the lower index. By RANK, no
    sort: a block is kept where fewer than ``topk`` others beat it (a
    compare of every pair, a sum; a sort of the scores a row is an order of
    magnitude slower on the chip, PERF.md section 6, PR 62)."""
    with jax.named_scope("sparse/score"):
        bs = block_scores(cfg, q, ck, t, blocks)
    with jax.named_scope("sparse/select"):
        m = jnp.arange(blocks)
        mine, other = bs[..., :, None], bs[..., None, :]
        beats = (other > mine) | ((other == mine) & (m[None, :] < m[:, None]))
        return jnp.sum(beats, axis=-1, dtype=jnp.int32) < cfg.sparse.topk


def compacted(keep, width: int):
    """The kept blocks' indices, ascending, in the first places of [...,
    width] i32 (the rest 0): entry p is the block with p kept ones before
    it. No scatter, no sort: a one-hot sum."""
    with jax.named_scope("sparse/select"):
        at = jnp.cumsum(keep, axis=-1, dtype=jnp.int32) - 1
        m = jnp.arange(keep.shape[-1], dtype=jnp.int32)
        hit = keep[..., None, :] & (
            at[..., None, :] == jnp.arange(width, dtype=jnp.int32)[:, None])
        return jnp.sum(jnp.where(hit, m, 0), axis=-1)


def _sparse(cfg: MiniCpmSalaConfig, h, w, attend, ck_all, seg_all, o, slot,
            fresh, positions, valid, ctx: int):
    """MiniCPM4's attention on normed h [B, T, D] of sparse layer ``o``:
    a decode step (``slot`` None: row b is slot b, T = 1) or ONE slot's
    chunk (B = 1). The windows that close among the new keys are laid into
    the carried ``ck_all`` where they lie BEFORE the scores read the rows.
    Returns (out, new K/V, ``ck_all``, ``seg_all``)."""
    B, T, _ = h.shape
    Hq, G, hd, eps = cfg.num_heads, cfg.num_kv_heads, cfg.hd, cfg.rms_norm_eps
    sp = cfg.sparse
    blocks = ctx // sp.block_size
    step = slot is None
    with jax.named_scope("attn.qkv"):
        q = qnt.matmul(h, w("wq"))
        k = qnt.matmul(h, w("wk"))
        v = qnt.matmul(h, w("wv"))
        q, k, v = lax.optimization_barrier((q, k, v))
        q = norm(q.reshape(B, T, Hq, hd), w("q_norm"), eps)
        k = norm(k.reshape(B, T, G, hd), w("k_norm"), eps)
        v = v.reshape(B, T, G, hd)
    with jax.named_scope("sparse/compress"):
        seg0 = mdl.rec_read(seg_all, (o,), slot)
        if fresh is not None:
            seg0 = jnp.where(fresh, 0.0, seg0)
        c, at, seg = jax.vmap(functools.partial(compress, cfg))(
            seg0, k, positions[:, 0],
            jnp.sum(valid, axis=1).astype(jnp.int32))
        seg_all = mdl.rec_write(seg_all, seg, (o,), slot)
        # [rows, halves] rows of [G hd], each where its window lies (one
        # that did not close: out of bounds, written nowhere)
        who = jnp.arange(B)[:, None] if step else slot
        rows = c.reshape(*c.shape[:2], -1).astype(ck_all.dtype)
        ck_all = ck_all.at[o, who, at if step else at[0]].set(
            rows if step else rows[0], mode="drop")
    qg = q.reshape(B, T, G, Hq // G, hd)
    ck = mdl.rec_read(ck_all, (o,), slot)
    if step:
        t = positions[:, 0]
        width = cfg.select_blocks[1]
        chosen = compacted(select(cfg, qg[:, 0], ck, t, blocks), width)
        dense = (t < sp.dense_len)[:, None]
        how = {"select": (
            jnp.where(dense[..., None], jnp.arange(width, dtype=jnp.int32),
                      chosen),
            jnp.broadcast_to(
                jnp.where(dense, t[:, None] // sp.block_size + 1, sp.topk),
                (B, G)).astype(jnp.int32))}
    else:
        t = positions[0]

        def chosen_blocks():
            return (select(cfg, qg[0], ck[0], t, blocks)
                    | (t < sp.dense_len)[:, None, None])

        # a chunk that ends below ``dense_len`` scores nothing
        how = {"select": lax.cond(
            t[-1] >= sp.dense_len, chosen_blocks,
            lambda: jnp.ones((T, G, blocks), jnp.bool_))}
    with jax.named_scope("sparse/attend"):
        attn, new_kv = attend(q, k, v, **how)
    with jax.named_scope("attn_gate"):
        o_ = output_gate(attn.astype(F32), qnt.matmul(h, w("w_ogate")))
    with jax.named_scope("attn.out"):
        out = qnt.matmul(o_.reshape(B, T, Hq * hd).astype(h.dtype),
                         w("wo"))
    return out, new_kv, ck_all, seg_all


def _mlp(cfg: MiniCpmSalaConfig, x, w):
    with jax.named_scope("mlp"):
        f = norm(x, w("mlp_norm"), cfg.rms_norm_eps)
        gated = jax.nn.silu(qnt.matmul(f, w("w_gate"))) * qnt.matmul(
            f, w("w_up"))
        return residual(cfg, x, qnt.matmul(gated, w("w_down")))


def forward(
    cfg: MiniCpmSalaConfig,
    params: Any,
    tokens: jax.Array,      # [B, T] i32
    positions: jax.Array,   # [B, T] i32
    kv_write: Any,          # engine.kvcache write policy, a cache layer a
                            # SPARSE layer
    kv_stack: Any,          # stacked K/V of the sparse layers
    mask: jax.Array,        # [B, T, ctx]: its width alone is read
    rope: tuple[jax.Array, jax.Array],
    attn: Any = None,       # the layout's selecting attend
    embeds: Optional[jax.Array] = None,
    *,
    rec: dict,              # init_rec's arrays
    valid: jax.Array,       # [B, T] bool: the real tokens, a prefix a row
    slot: Any = None,       # the decode step's rows or ONE slot's chunk, and
    fresh: Any = None,      # whether that starts from zero state: the
                            # contract (models.llama ``family_module``)
    kernels: Optional[bool] = None,     # None: the Lightning decode step is
                            # XLA; else ops.gdn's kernel (the value:
                            # interpreted)
) -> tuple[jax.Array, Any, dict, None]:
    """models.llama.forward for this family: (hidden [B, T, D] under
    ``dim_model_base / hidden_size``, new K/V stack, new ``rec``, None: no
    routed work to count). One ``lax.scan`` a run of like layers; (x, K/V,
    S, ck, seg) is the carry, so pool and state are written in place."""
    if attn is None:
        raise ValueError(mdl.refusal(
            cfg, "a forward with no selecting attend (the contiguous K/V "
                 "layout)"))
    ctx = mask.shape[-1]
    if ctx > cfg.windows * cfg.sparse.kernel_stride:
        raise ValueError(
            f"minicpm_sala: a context of {ctx} tokens is past the "
            f"{cfg.windows} compressed keys a slot holds "
            f"(max_position_embeddings {cfg.max_position_embeddings})")
    cos, sin = mdl.rope_rows(rope, positions)
    x = mdl.embed(cfg, params, tokens, embeds, cfg.scale_emb)
    fused = kernels is not None and slot is None and tokens.shape[1] == 1
    eps = cfg.rms_norm_eps
    # a chunk's recurrence is a scan of at least two parts: as ONE part its
    # products hand their layout on to the carried state, and the compiler
    # restages all of it (1.5 GiB) on the way in and on the way out
    light_chunk = min(LIGHTNING_CHUNK, max(tokens.shape[1] // 2, 1))

    def lightning_run(run: Run, carry):
        layers = params["layers"]

        def layer(carry, o):
            x, S_all = carry

            def w(name):    # the layer's row, read where it lies
                return jax.tree.map(
                    lambda a: lax.dynamic_index_in_dim(a, o, 0,
                                                       keepdims=False),
                    layers[name])

            h = norm(x, w("attn_norm"), eps)
            with jax.named_scope("lightning"):
                with jax.named_scope("state"):
                    S0 = None if fused else mdl.rec_read(S_all, (o,), slot)
                    if fresh is not None:       # a chunk: never fused
                        S0 = jnp.where(fresh, 0.0, S0)
                    state_step = (
                        functools.partial(fh.recur_in_place, S_all, o,
                                          kernels)
                        if fused else functools.partial(
                            fh.recur, S0, chunk=light_chunk))
                out, S = _lightning(cfg, h, w, cos, sin, state_step, valid)
                with jax.named_scope("state"):
                    S_all = S if fused else mdl.rec_write(S_all, S, (o,),
                                                          slot)
            return (_mlp(cfg, residual(cfg, x, out), w), S_all), None

        x, kv, S_all, ck_all, seg_all = carry
        (x, S_all), _ = lax.scan(
            layer, (x, S_all),
            run.ordinal + jnp.arange(run.rows, dtype=jnp.int32))
        return x, kv, S_all, ck_all, seg_all

    def sparse_layer(n: int, carry):
        x, kv, S_all, ck_all, seg_all = carry

        def w(name):
            return params[sparse_prefix(n) + name]

        h = norm(x, w("attn_norm"), eps)
        out, kv, ck_all, seg_all = _sparse(
            cfg, h, w, mdl.attend_through(kv_write, attn, mask, kv,
                                          jnp.int32(n)),
            ck_all, seg_all, n, slot, fresh, positions, valid, ctx)
        return (_mlp(cfg, residual(cfg, x, out), w), kv, S_all, ck_all,
                seg_all)

    carry = (x, kv_stack, rec["S"], rec["ck"], rec["seg"])
    with jax.named_scope("layers"):
        for run in cfg.runs:
            if run.kind == LIGHTNING:
                carry = lightning_run(run, carry)
            else:
                for n in range(run.ordinal, run.ordinal + run.rows):
                    carry = sparse_layer(n, carry)
    x, kv_stack, S_all, ck_all, seg_all = carry
    with jax.named_scope("final_norm"):
        x = norm(x, params["final_norm"], eps,
                 cfg.dim_model_base / cfg.hidden_size)
    return x, kv_stack, {**rec, "S": S_all, "ck": ck_all,
                         "seg": seg_all}, None
