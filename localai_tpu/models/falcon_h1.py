"""Falcon-H1 (``model_type: falcon_h1``): a decoder whose EVERY layer runs a
Mamba-2 state-space mixer (arXiv:2405.21060: a scalar decay a head, B and C
shared by the heads of a group) and grouped-query attention side by side on
the same normed input, adds the two to the residual and follows them with a
dense gated MLP, all under muP multipliers that are part of the mathematics.

What the serving engine holds of it (engine.runner):

  * the stack is ONE ``lax.scan`` over like layers; every layer owns BOTH a
    cache layer of the paged K/V pool (``cache_layers`` = ``num_layers``: the
    pool, its write policies and the paged decode kernel as every other model
    uses them) and a row of recurrent state;
  * a mixer's state is NOT keys: per slot a float32 matrix a head,
    ``S [H, N, P]`` (the published S [P, N] transposed: a head's x is a row
    along the lanes, B and C are columns, as ops.gdn's kernel holds a
    DeltaNet head), and the last ``K - 1`` rows of the conv's input. It lives
    beside the pool as two dense per-SLOT arrays (``init_rec``) that the
    forward carries through the scan and updates in place; a token that is
    not real (an empty slot of a decode step, a padded row of a chunk) is the
    identity on both;
  * a prefill chunk's recurrence is the chunked (SSD) form, ``ssd_chunk``
    over ``mamba_chunk_size`` tokens at a time with its products at float32
    precision: no step a token (``ssm_step`` a token is what it writes out,
    and what the reference runs);
  * the decode step's recurrence is ops.gdn's kernel without the delta
    correction (where attention's are kernels; ``ssm_step`` as XLA), on the
    carried array where it lies: k = B, q = C, v = dt x, a scalar log decay
    dt A a head; ``D x`` is added behind it.

The multipliers are applied where the model applies them, none folded into a
weight: on an activation in float32 beside a rounding that was there anyway.
Two sit elsewhere than the published code puts them, to the same product:
``ssm_multipliers`` over the conv's channels are applied to the conv's INPUT
rows as they are read (the slot's conv rows hold in_proj's output as the
matmul wrote it, rounded once), and ``lm_head_multiplier`` scales the final
norm's output in front of the head (the published 2^-7 is exact in bfloat16).

The plain reference is benchmark/reference/falcon_h1_family.py, and
tests/test_falcon_h1.py holds this file to it. Named scopes: ``ssm/in_proj``,
``ssm/conv``, ``ssm/state`` (the per-slot arrays read, the recurrence, the
arrays written back), ``ssm/gate_norm``, ``ssm/out_proj``; the attention's
and the MLP's are models.llama's.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, ClassVar, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from localai_tpu.models import llama as mdl
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import LlamaConfig

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class FalconH1Config(LlamaConfig):
    """``LlamaConfig`` with the keys the family adds."""

    mamba_n_heads: int = 32
    mamba_d_head: int = 128
    mamba_n_groups: int = 2
    mamba_d_state: int = 256
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 128     # tokens a prefill chunk's recurrence
                                    # takes at once (``ssd_chunk``)
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple = (1.0,) * 5     # over in_proj's [z|x|B|C|dt]
    mlp_multipliers: tuple = (1.0, 1.0)     # the gate's input, down's output

    recurrent: ClassVar[bool] = True
    family: ClassVar[str] = "falcon_h1"

    def __post_init__(self):
        if self.mamba_n_heads % self.mamba_n_groups:
            raise ValueError(
                f"falcon_h1: mamba_n_heads {self.mamba_n_heads} is no "
                f"multiple of mamba_n_groups {self.mamba_n_groups}")

    @property
    def d_ssm(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_dim(self) -> int:
        """Channels the causal conv runs over: [x; B; C]."""
        return self.d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def in_dim(self) -> int:
        """in_proj's outputs: [z; x; B; C; dt]."""
        return self.d_ssm + self.conv_dim + self.mamba_n_heads

    @property
    def segments(self) -> tuple:
        """Widths of in_proj's five segments, ``ssm_multipliers``' order."""
        gn = self.mamba_n_groups * self.mamba_d_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.mamba_n_heads)

    def over_segments(self, values) -> np.ndarray:
        """One float32 value a column of in_proj, a value a segment."""
        return np.concatenate([np.full(width, v, np.float32) for width, v
                               in zip(self.segments, values, strict=True)])

    @classmethod
    def from_hf(cls, hf: dict) -> "FalconH1Config":
        """From published keys; a key that asks for what is not written (a
        bias on a projection, no conv bias, no gated norm, the norm in front
        of the gate, a mixer width other than heads x head size, scaled
        RoPE) is refused."""
        stated = (("mamba_conv_bias", True), ("mamba_rms_norm", True),
                  ("mamba_norm_before_gate", False),
                  ("mamba_proj_bias", False), ("attention_bias", False),
                  ("mlp_bias", False), ("projectors_bias", False),
                  ("rope_scaling", None), ("attn_layer_indices", None))
        for key, want in stated:
            if hf.get(key, want) != want:
                raise ValueError(
                    f"model_type falcon_h1 is served with {key} = {want!r} "
                    f"(what the published configurations state), not "
                    f"{hf[key]!r}")
        d_ssm = hf["mamba_n_heads"] * hf["mamba_d_head"]
        if hf.get("mamba_d_ssm", d_ssm) != d_ssm:
            raise ValueError(
                f"falcon_h1: mamba_d_ssm {hf['mamba_d_ssm']} is not "
                f"mamba_n_heads x mamba_d_head = {d_ssm}")
        return cls(
            vocab_size=hf["vocab_size"],
            hidden_size=hf["hidden_size"],
            intermediate_size=hf["intermediate_size"],
            num_layers=hf["num_hidden_layers"],
            num_heads=hf["num_attention_heads"],
            num_kv_heads=hf["num_key_value_heads"],
            head_dim=hf.get("head_dim"),
            # (the published 100000000000 is an integer past 32 bits)
            rope_theta=float(hf.get("rope_theta", 10000.0)),
            rms_norm_eps=hf.get("rms_norm_eps", 1e-5),
            max_position_embeddings=hf.get("max_position_embeddings", 4096),
            tie_word_embeddings=hf.get("tie_word_embeddings", False),
            mamba_n_heads=hf["mamba_n_heads"],
            mamba_d_head=hf["mamba_d_head"],
            mamba_n_groups=hf["mamba_n_groups"],
            mamba_d_state=hf["mamba_d_state"],
            mamba_d_conv=hf.get("mamba_d_conv", 4),
            mamba_chunk_size=int(hf.get("mamba_chunk_size", 128)),
            embedding_multiplier=float(hf.get("embedding_multiplier", 1.0)),
            lm_head_multiplier=float(hf.get("lm_head_multiplier", 1.0)),
            attention_in_multiplier=float(
                hf.get("attention_in_multiplier", 1.0)),
            attention_out_multiplier=float(
                hf.get("attention_out_multiplier", 1.0)),
            key_multiplier=float(hf.get("key_multiplier", 1.0)),
            ssm_in_multiplier=float(hf.get("ssm_in_multiplier", 1.0)),
            ssm_out_multiplier=float(hf.get("ssm_out_multiplier", 1.0)),
            ssm_multipliers=tuple(
                float(m) for m in hf.get("ssm_multipliers", (1.0,) * 5)),
            mlp_multipliers=tuple(
                float(m) for m in hf.get("mlp_multipliers", (1.0, 1.0))),
        )


CONFIG = FalconH1Config
# what the family does not serve, the weight modes it does, and why
# (models.llama ``refusal``). ``int8``: every projection and both
# tables through models.quant; conv, ``A_log``, ``D``, ``dt_bias`` and the
# norm gains stay as they are
UNSERVED = mdl.KEYS_ALONE
WEIGHTS = ("int8",)
WHY = (f"model_type falcon_h1: its mixers {mdl.STATE_WHY}; its projections "
       f"are served in bfloat16 or as weight-only int8")


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def param_shapes(cfg: FalconH1Config) -> dict:
    """Shapes of the stacked-parameter pytree, a row a layer."""
    D, F, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    H, C = cfg.mamba_n_heads, cfg.conv_dim
    shapes = {
        "embed": (cfg.vocab_size, D),
        "final_norm": (D,),
        "layers": {
            "attn_norm": (L, D),            # in front of mixer AND attention
            # the mixer: [z; x; B; C; dt] from one projection, the depthwise
            # conv over [x; B; C] (row K-1 multiplies the token itself) and
            # its bias, a head's three scalars, the gated norm's gain
            "ssm_in": (L, D, cfg.in_dim),
            "ssm_conv": (L, cfg.mamba_d_conv, C),
            "ssm_conv_bias": (L, C),
            "ssm_A_log": (L, H),
            "ssm_D": (L, H),
            "ssm_dt_bias": (L, H),
            "ssm_norm": (L, cfg.d_ssm),
            "ssm_out": (L, cfg.d_ssm, D),
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
    if not cfg.tie_word_embeddings:
        shapes["lm_head"] = (D, cfg.vocab_size)
    return shapes


# The synthetic draw. With N(0, 0.02) matrices the published multipliers
# (``lm_head_multiplier`` 2^-7, ``attention_out_multiplier`` 0.0375, ...)
# leave the logits flat and the attention half mute, and a reference check
# over such weights passes whatever is wrong. So every matrix is drawn at
# the deviation that makes its OUTPUT, behind the multipliers the model puts
# on it, of order 1 (``leaf_std``): the three branches of a layer each move
# the residual by ``BRANCH_RMS``, the attention's scores spread by
# ``SCORE_STD``, B and C stand at ``BC_RMS`` so that what the state
# remembers weighs beside the ``D x`` skip, the letters' logits spread by
# ``LOGIT_STD``. Gains are 1 but for a few OUTLIER channels in the norm that
# feeds mixer and attention and in the final norm, as models.afmoe's draw
# has them (what makes a lower-precision ACTIVATION lossy: a per-token int8
# scale follows the outlier, bfloat16's relative rounding does not care).
BRANCH_RMS = 0.5
SCORE_STD = 2.0
BC_RMS = 1.7
LOGIT_STD = 1.5
CONV_TAP_STD, CONV_BIAS_STD = 0.5, 0.5
OUTLIER_GAIN, OUTLIER_EVERY = mdl.OUTLIER_GAIN, mdl.OUTLIER_EVERY
OUTLIER_NORMS = ("attn_norm", "final_norm")


def leaf_std(cfg: FalconH1Config, name: str):
    """The deviation a synthetic MATRIX leaf is drawn at: a number, or one a
    column of ``ssm_in`` (its five segments stand under five multipliers);
    None for a leaf that is no matrix (``init_leaf`` draws those)."""
    D = cfg.hidden_size
    h_rms = mdl.outlier_rms(D)                # behind attn_norm / final_norm
    fan_h = math.sqrt(D) * h_rms
    if name == "embed":
        return 1.0 / cfg.embedding_multiplier
    if name == "lm_head":
        return LOGIT_STD / (fan_h * cfg.lm_head_multiplier)
    if name == "ssm_in":
        want = (1.0, 1.0, BC_RMS, BC_RMS, 1.0)
        return cfg.over_segments(
            rms / (fan_h * cfg.ssm_in_multiplier * m)
            for rms, m in zip(want, cfg.ssm_multipliers, strict=True))
    if name == "ssm_out":
        return BRANCH_RMS / (math.sqrt(cfg.d_ssm) * cfg.ssm_out_multiplier)
    a_in = fan_h * cfg.attention_in_multiplier
    if name == "wq":
        return math.sqrt(SCORE_STD) / a_in
    if name == "wk":
        return math.sqrt(SCORE_STD) / (a_in * cfg.key_multiplier)
    if name == "wv":
        return 1.0 / a_in
    if name == "wo":        # a softmax's output has ~0.6 of its values' RMS
        return BRANCH_RMS / (0.6 * math.sqrt(cfg.num_heads * cfg.hd)
                             * cfg.attention_out_multiplier)
    if name == "w_gate":
        return 1.0 / (math.sqrt(D) * cfg.mlp_multipliers[0])
    if name == "w_up":
        return 1.0 / math.sqrt(D)
    if name == "w_down":    # silu(g) u of unit g, u has RMS ~0.6
        return BRANCH_RMS / (0.6 * math.sqrt(cfg.intermediate_size)
                             * cfg.mlp_multipliers[1])
    return None


def init_leaf(key, shape, name: str, dtype, cfg: FalconH1Config):
    """One synthetic leaf, for models.llama.init_params' loop: matrices
    N(0, ``leaf_std``); gains 1 with ``OUTLIER_GAIN`` on a seeded
    ``1 / OUTLIER_EVERY`` of the channels of ``OUTLIER_NORMS``;
    ``A = exp(A_log)`` uniform in [1, 16) and ``dt_bias`` the inverse
    softplus of a step log-uniform in [0.001, 0.1] (the state-space
    convention: a token decays a head's state by exp(-0.001) .. exp(-1.6));
    ``D`` 1; the conv's taps and bias N(0, 0.5)."""
    # one draw a leaf: the uses of ``key`` are branches of one choice
    std = leaf_std(cfg, name)
    if std is not None:
        w = jax.random.normal(key, shape, F32) * jnp.asarray(std)
    elif name == "ssm_A_log":
        w = jnp.log(jax.random.uniform(  # jaxlint: disable=rng-key-reuse
            key, shape, F32, 1.0, 16.0))
    elif name == "ssm_dt_bias":
        dt = jnp.exp(jax.random.uniform(  # jaxlint: disable=rng-key-reuse
            key, shape, F32, math.log(1e-3), math.log(1e-1)))
        w = dt + jnp.log(-jnp.expm1(-dt))
    elif name in ("ssm_conv", "ssm_conv_bias"):
        w = jax.random.normal(  # jaxlint: disable=rng-key-reuse
            key, shape, F32) * (CONV_TAP_STD if name == "ssm_conv"
                                else CONV_BIAS_STD)
    elif name in OUTLIER_NORMS and shape[-1] >= OUTLIER_EVERY:
        u = jax.random.uniform(  # jaxlint: disable=rng-key-reuse
            key, shape)
        kth = lax.top_k(u, shape[-1] // OUTLIER_EVERY)[0][..., -1:]
        w = jnp.where(u >= kth, OUTLIER_GAIN, 1.0)
    else:               # ssm_D, ssm_norm, mlp_norm, a narrow norm
        w = jnp.ones(shape, F32)
    return w.astype(dtype)


def checkpoint_leaves(cfg: FalconH1Config, get, body: str = "model."):
    """(leaf name, host array) for every ``layers`` leaf and the final norm,
    one at a time, from an HF ``falcon_h1`` checkpoint; ``get(name)`` reads
    one tensor. Linear weights are transposed to right-multiply; the
    depthwise conv's ``[C, 1, K]`` becomes ``[K, C]``; ``in_proj``'s rows are
    [z; x; B; C; dt] and the conv's channels [x; B; C] as published, which is
    the served order; the final norm is ``final_layernorm``."""
    L = body + "layers.{i}."
    names = {
        "attn_norm": ("input_layernorm.weight", np.asarray),
        "ssm_in": ("mamba.in_proj.weight", np.transpose),
        "ssm_conv": ("mamba.conv1d.weight", lambda a: a[:, 0, :].T),
        "ssm_conv_bias": ("mamba.conv1d.bias", np.asarray),
        "ssm_A_log": ("mamba.A_log", np.asarray),
        "ssm_D": ("mamba.D", np.asarray),
        "ssm_dt_bias": ("mamba.dt_bias", np.asarray),
        "ssm_norm": ("mamba.norm.weight", np.asarray),
        "ssm_out": ("mamba.out_proj.weight", np.transpose),
        "wq": ("self_attn.q_proj.weight", np.transpose),
        "wk": ("self_attn.k_proj.weight", np.transpose),
        "wv": ("self_attn.v_proj.weight", np.transpose),
        "wo": ("self_attn.o_proj.weight", np.transpose),
        "mlp_norm": ("pre_ff_layernorm.weight", np.asarray),
        "w_gate": ("feed_forward.gate_proj.weight", np.transpose),
        "w_up": ("feed_forward.up_proj.weight", np.transpose),
        "w_down": ("feed_forward.down_proj.weight", np.transpose),
    }
    for leaf, (tail, fix) in names.items():
        yield leaf, np.stack([fix(get(L.format(i=i) + tail))
                              for i in range(cfg.num_layers)])
    yield "final_norm", np.asarray(get(body + "final_layernorm.weight"))


# ---------------------------------------------------------------------------
# Recurrent state: two dense per-slot arrays beside the K/V pool
# ---------------------------------------------------------------------------

def init_rec(cfg: FalconH1Config, num_slots: int) -> dict:
    """The mixers' state for ``num_slots`` slots, all zero: ``S [L, slots,
    H, N, P]`` float32 and the conv's last K - 1 input rows ``conv [L,
    slots, K - 1, C]`` in the compute dtype."""
    L = cfg.num_layers
    return {
        "S": jnp.zeros((L, num_slots, cfg.mamba_n_heads, cfg.mamba_d_state,
                        cfg.mamba_d_head), F32),
        "conv": jnp.zeros((L, num_slots, cfg.mamba_d_conv - 1, cfg.conv_dim),
                          jnp.dtype(cfg.dtype)),
    }


# ---------------------------------------------------------------------------
# Layers
# ---------------------------------------------------------------------------

def norm(x, w, eps: float, scale: float = 1.0):
    """Plain RMSNorm, float32 inside, rounded once: x rsqrt(mean x^2 + eps)
    w (``scale``: a multiplier that stands on the norm's output)."""
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * lax.rsqrt(var + eps) * w.astype(F32)
    return (out if scale == 1.0 else out * scale).astype(x.dtype)


def norm_scale(h, m: float):
    """h times a multiplier that stands on a normed activation (nothing
    where it is 1; the published ones are powers of two, exact in
    bfloat16)."""
    return h if m == 1.0 else (h.astype(F32) * m).astype(h.dtype)


def ssm_step(S, C, B, xdt, g):
    """One token of the Mamba-2 recurrence, per head, float32:
    S <- exp(g) S + B (x) (dt x);  y = S^T C.
    S [..., N, P]; B, C [..., N]; xdt = dt x [..., P]; g = dt A [...]. A
    token with g = 0 and xdt = 0 is the identity on S."""
    S = (jnp.exp(g)[..., None, None] * S
         + B[..., :, None] * xdt[..., None, :])
    return S, jnp.einsum("...np,...n->...p", S, C)


def ssd_chunk(S, C, B, xdt, g):
    """``ssm_step`` over Q tokens at once, the chunked (SSD) form of
    arXiv:2405.21060 with every product at float32 precision: with c_t the
    running sum of the log decays g through token t,
      y_t = exp(c_t) S^T C_t + sum over s <= t of exp(c_t - c_s) (C_t . B_s)
            xdt_s,   S' = exp(c_Q) S + sum over s of exp(c_Q - c_s) B_s (x)
            xdt_s,
    which is the recurrence written out (every exponent is <= 0). S [b, H,
    N, P]; C, B [b, Q, H, N]; xdt [b, Q, H, P]; g [b, Q, H]."""
    hi = lax.Precision.HIGHEST
    c = jnp.cumsum(g, axis=1)                                   # [b, Q, H]
    ct = jnp.moveaxis(c, 1, 2)                                  # [b, H, Q]
    q = jnp.arange(c.shape[1])
    decay = jnp.exp(jnp.where(q[:, None] >= q[None, :],
                              ct[..., :, None] - ct[..., None, :], -jnp.inf))
    scores = jnp.einsum("bthn,bshn->bhts", C, B, precision=hi) * decay
    y = (jnp.einsum("bhts,bshp->bthp", scores, xdt, precision=hi)
         + jnp.einsum("bthn,bhnp->bthp", C * jnp.exp(c)[..., None], S,
                      precision=hi))
    to_end = jnp.exp(c[:, -1:] - c)[..., None]                  # [b, Q, H, 1]
    S = (jnp.exp(c[:, -1])[..., None, None] * S
         + jnp.einsum("bshn,bshp->bhnp", B * to_end, xdt, precision=hi))
    return S, y


def recur(S0, C, B, xdt, g, chunk: int):
    """The recurrence as XLA from state S0 [B, H, N, P] over the tokens of
    B, C [B, T, H, N], xdt [B, T, H, P], g [B, T, H]: (S after them, y [B, T,
    H, P]). One token is ``ssm_step``; a prefill chunk runs ``ssd_chunk``
    over ``chunk`` tokens at a time (all of them at once where ``chunk``
    does not divide them), the state handed from one to the next."""
    T = C.shape[1]
    if T == 1:              # the decode step: no loop
        S, y = ssm_step(S0, C[:, 0], B[:, 0], xdt[:, 0], g[:, 0])
        return S, y[:, None]
    if T <= chunk or T % chunk:
        return ssd_chunk(S0, C, B, xdt, g)

    def parts(t):           # [B, T, ...] -> [T / chunk, B, chunk, ...]
        return jnp.moveaxis(
            t.reshape(t.shape[0], T // chunk, chunk, *t.shape[2:]), 1, 0)

    S, y = lax.scan(lambda S, xs: ssd_chunk(S, *xs), S0,
                    tuple(parts(t) for t in (C, B, xdt, g)))
    y = jnp.moveaxis(y, 0, 1)
    return S, y.reshape(y.shape[0], T, *y.shape[3:])


# heads of one slot a grid step of the decode kernel holds: 16 heads of [N,
# P] float32 are the 2 MB block the DeltaNet's step settled on (ops.gdn)
SSM_HEAD_BLOCK = 16


def recur_in_place(S_all, layer, interpret: bool, C, B, xdt, g):
    """``recur`` for the decode step (T = 1, batch row b is slot b) as
    ops.gdn's kernel WITHOUT the delta correction on ``layer`` of the carried
    state ``S_all`` [L, slots, H, N, P]: (``S_all`` with the layer's rows
    stepped, in place; y [B, 1, H, P]). The kernel's beta is 1: a slot that
    holds no stream comes with g = 0 and xdt = 0, the identity as it is."""
    from localai_tpu.ops import gdn

    S_all, y = gdn.gdn_state_step(
        S_all, layer, None, *(t[:, 0] for t in (C, B, xdt, g)),
        jnp.ones_like(g[:, 0]), delta=False, head_block=SSM_HEAD_BLOCK,
        interpret=interpret)
    return S_all, y[:, None]


def heads_of_groups(x, heads: int):
    """B or C [B, T, G, N] as each head reads it [B, T, H, N]: head h reads
    group h // (H / G)."""
    return jnp.repeat(x, heads // x.shape[2], axis=2)


def log_decay(A_log, dt):
    """A head's log decay over a step dt: dt A, A = -exp(A_log)."""
    return -jnp.exp(A_log.astype(F32)) * dt


def skip(D, x):
    """The mixer's skip term D x, a scalar a head; x [B, T, H, P]."""
    return D.astype(F32)[:, None] * x


def causal_conv(cat, w, bias, T: int):
    """The depthwise causal conv over rows cat [B, K - 1 + T, C] (float32
    taps w [K, C]): token t's output reads rows t .. t + K - 1."""
    return bias.astype(F32) + sum(
        cat[:, i:i + T].astype(F32) * w[i] for i in range(w.shape[0]))


def gate_norm(y, z, w, groups: int, eps: float):
    """The mixer's output norm, float32: the gate silu(z) FIRST, then an
    RMSNorm over each of ``groups`` groups of channels, gain ``w``. y, z
    [..., H P]."""
    y = y * jax.nn.silu(z.astype(F32))
    yg = y.reshape(*y.shape[:-1], groups, -1)
    yg = yg * lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + eps)
    return yg.reshape(y.shape) * w.astype(F32)


def _mixer(cfg: FalconH1Config, h, lp, state_step, conv0, valid):
    """The Mamba-2 mixer on normed activations h [B, T, D] from the conv's
    rows conv0 [B, K-1, C] and the state ``state_step`` steps: ``recur`` on
    the layer's S0, or the decode step's kernel on the carried array;
    ``valid`` [B, T] marks the real tokens, a PREFIX of each row. Returns
    (out [B, T, D] float32 under ``ssm_out_multiplier``, ``state_step``'s
    state, conv)."""
    B_, T, _ = h.shape
    H, P, G, N = (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
                  cfg.mamba_d_state)
    K, C, ssm = cfg.mamba_d_conv, cfg.conv_dim, cfg.d_ssm
    mup = cfg.over_segments(cfg.ssm_multipliers)
    with jax.named_scope("in_proj"):
        p = qnt.matmul(norm_scale(h, cfg.ssm_in_multiplier), lp["ssm_in"])
        p = lax.optimization_barrier(p)
        z = p[..., :ssm].astype(F32) * mup[:ssm]
        xbc = p[..., ssm:ssm + C]           # as the matmul wrote it
        dt = p[..., ssm + C:].astype(F32) * mup[ssm + C:]
    with jax.named_scope("conv"):
        # [the slot's last K-1 inputs; the chunk's]: token t of the chunk is
        # row t + K - 1, and its output reads rows t .. t + K - 1; the
        # channels' multipliers stand on the rows as they are read
        cat = jnp.concatenate([conv0.astype(xbc.dtype), xbc], axis=1)
        w = lp["ssm_conv"].astype(F32) * mup[ssm:ssm + C]
        xbc = jax.nn.silu(causal_conv(cat, w, lp["ssm_conv_bias"], T))
        # (float32 from here on)
        new_conv = mdl.conv_rows(cat, jnp.sum(valid, axis=1).astype(jnp.int32),
                                 K)
    with jax.named_scope("state"):
        x = xbc[..., :ssm].reshape(B_, T, H, P)
        Bm = heads_of_groups(
            xbc[..., ssm:ssm + G * N].reshape(B_, T, G, N), H)
        Cm = heads_of_groups(xbc[..., ssm + G * N:].reshape(B_, T, G, N), H)
        dt = jax.nn.softplus(dt + lp["ssm_dt_bias"].astype(F32))
        # a token that is not real is the identity on S: no decay, no write
        live = valid[..., None]
        g = jnp.where(live, log_decay(lp["ssm_A_log"], dt), 0.0)
        xdt = jnp.where(live[..., None], dt[..., None] * x, 0.0)
        S, y = state_step(Cm, Bm, xdt, g)
        y = y + skip(lp["ssm_D"], x)
    with jax.named_scope("gate_norm"):
        y = gate_norm(y.reshape(B_, T, ssm), z, lp["ssm_norm"], G,
                      cfg.rms_norm_eps)
    with jax.named_scope("out_proj"):
        out = qnt.matmul(y.astype(h.dtype), lp["ssm_out"])
        out = out.astype(F32) * cfg.ssm_out_multiplier
    return out, S, new_conv


def _attention(cfg: FalconH1Config, h, lp, cos, sin, attend):
    """Grouped-query attention on the layer's normed h [B, T, D]: (out
    [B, T, D] float32 under ``attention_out_multiplier``, new K/V)."""
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    with jax.named_scope("attn.qkv"):
        a = norm_scale(h, cfg.attention_in_multiplier)
        q = qnt.matmul(a, lp["wq"])
        k = qnt.matmul(a, lp["wk"])
        v = qnt.matmul(a, lp["wv"])
        # the head split stays off the dots (models.llama._layer says why)
        q, k, v = lax.optimization_barrier((q, k, v))
        k = (k.astype(F32) * cfg.key_multiplier).astype(k.dtype)
        q = q.reshape(*q.shape[:-1], Hq, hd)
        k = k.reshape(*k.shape[:-1], Hkv, hd)
        v = v.reshape(*v.shape[:-1], Hkv, hd)
    with jax.named_scope("attn.rope"):
        q = mdl.apply_rope(q, cos, sin)
        k = mdl.apply_rope(k, cos, sin)
    attn, new_kv = attend(q, k, v)
    with jax.named_scope("attn.out"):
        out = qnt.matmul(attn.reshape(*attn.shape[:-2], Hq * hd), lp["wo"])
        out = out.astype(F32) * cfg.attention_out_multiplier
    return out, new_kv


def _mlp(cfg: FalconH1Config, x, lp):
    """x + the gated MLP of its norm, under ``mlp_multipliers``."""
    gate_m, down_m = cfg.mlp_multipliers
    with jax.named_scope("mlp"):
        f = norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        gated = (jax.nn.silu(qnt.matmul(f, lp["w_gate"]).astype(F32) * gate_m)
                 * qnt.matmul(f, lp["w_up"]).astype(F32)).astype(x.dtype)
        down = qnt.matmul(gated, lp["w_down"]).astype(F32) * down_m
        return (x.astype(F32) + down).astype(x.dtype)


def forward(
    cfg: FalconH1Config,
    params: Any,
    tokens: jax.Array,      # [B, T] i32
    positions: jax.Array,   # [B, T] i32
    kv_write: Any,          # engine.kvcache write policy, a cache layer each
    kv_stack: Any,          # stacked K/V
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
    kernels: Optional[bool] = None,     # None: the decode step's recurrence
                            # is XLA; else ops.gdn's kernel (the value:
                            # interpreted)
) -> tuple[jax.Array, Any, dict, None]:
    """models.llama.forward for this family: (hidden [B, T, D] under
    ``lm_head_multiplier``, new K/V stack, new ``rec``, None: no routed
    work to count). One ``lax.scan``
    over the layers; (x, K/V, S, conv) is its carry, so pool and state are
    written in place."""
    cos, sin = mdl.rope_rows(rope, positions)
    x = mdl.embed(cfg, params, tokens, embeds, cfg.embedding_multiplier)
    if attn is None:
        attn = mdl.xla_attend(cfg, positions)
    # the decode step (batch row b is slot b, one token): the recurrence is
    # ONE kernel a layer on the carried state, which is then never sliced
    fused = kernels is not None and slot is None and tokens.shape[1] == 1

    def layer(carry, xs):
        x, kv, S_all, conv_all = carry
        lp, i = xs
        h = norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        with jax.named_scope("ssm"):
            # the per-slot arrays are read and written under the scope of
            # the recurrence: ``ssm/state`` is all that moves state
            with jax.named_scope("state"):
                S0 = None if fused else mdl.rec_read(S_all, (i,), slot)
                conv0 = mdl.rec_read(conv_all, (i,), slot)
                if fresh is not None:       # a chunk: never fused
                    S0 = jnp.where(fresh, 0.0, S0)
                    conv0 = jnp.where(fresh, 0, conv0).astype(conv0.dtype)
                state_step = (
                    functools.partial(recur_in_place, S_all, i, kernels)
                    if fused else functools.partial(
                        recur, S0, chunk=cfg.mamba_chunk_size))
            m, S, conv = _mixer(cfg, h, lp, state_step, conv0, valid)
            with jax.named_scope("state"):
                S_all = S if fused else mdl.rec_write(S_all, S, (i,), slot)
                conv_all = mdl.rec_write(conv_all, conv, (i,), slot)

        a, kv = _attention(cfg, h, lp, cos, sin, mdl.attend_through(
            kv_write, attn, mask, kv, i))
        x = (x.astype(F32) + m + a).astype(x.dtype)
        return (_mlp(cfg, x, lp), kv, S_all, conv_all), None

    with jax.named_scope("layers"):
        (x, kv_stack, S_all, conv_all), _ = lax.scan(
            layer, (x, kv_stack, rec["S"], rec["conv"]),
            (params["layers"], jnp.arange(cfg.num_layers, dtype=jnp.int32)))
    with jax.named_scope("final_norm"):
        x = norm(x, params["final_norm"], cfg.rms_norm_eps,
                 cfg.lm_head_multiplier)
    return x, kv_stack, {**rec, "S": S_all, "conv": conv_all}, None
