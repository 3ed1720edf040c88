"""Pallas TPU kernel: int8-weight matmul with in-kernel dequant.

The decode hot loop is weight-HBM-bound: every step streams every weight
once. The XLA 'w8' path (models.quant.matmul: ``(x @ q.astype(bf16)) *
scale``) leaves XLA free to materialize the casted bf16 weight as its own
fusion — when it does, the weight bytes cross HBM ~3x (read int8, write
bf16, read bf16) and int8 serving loses its entire bandwidth advantage
(the r4 roofline-gap suspect, VERDICT #2). This kernel removes the
ambiguity: int8 blocks stream HBM→VMEM once, the cast to the activation
dtype happens in-register, the MXU runs the bf16 dot, and the
per-output-channel scale lands in the accumulator epilogue.

Layout: grid (N/bn, K/bk) with K minor (sequential accumulation into a
f32 VMEM scratch); weight blocks (bk, bn) int8 respect Mosaic's (32, 128)
int8 tiling; M pads to the bf16 sublane (16). ``transpose_w=True`` serves
the tied-embedding lm_head (x @ W.T with per-row scales) by swapping the
block index map and contracting on the weight block's minor axis — the
int8 table is still read in its native row-major layout.

Enabled from models.quant.matmul via LOCALAI_W8_KERNEL=1 (opt-in: no cell
of the benchmark turns it on; ROADMAP C11 decides it with A3).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, w_ref, s_ref, o_ref, acc_ref, *, n_k: int,
            transpose_w: bool):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]
    w = w_ref[...].astype(x.dtype)
    if transpose_w:
        # w block [bn, bk]: contract x's K with the block's minor axis
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
    else:
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = (acc_ref[...] * s_ref[...]).astype(o_ref.dtype)


def _pick(total: int, target: int, quantum: int) -> int:
    b = min(total, target)
    b -= b % quantum
    while b > quantum and total % b:
        b -= quantum
    return b if b and total % b == 0 else total


@functools.partial(jax.jit, static_argnames=("transpose_w", "interpret"))
def w8_matmul(x: jax.Array, q: jax.Array, scale: jax.Array, *,
              transpose_w: bool = False,
              interpret: bool = False) -> jax.Array:
    """x [M, K] (bf16/f32) x int8 weight → [M, N] in x.dtype.

    ``transpose_w=False``: q [K, N], scale [N] (per output column).
    ``transpose_w=True``:  q [N, K], scale [N] (per row — the tied
    lm_head table), computing x @ q.T.
    """
    M, K = x.shape
    N = q.shape[1] if not transpose_w else q.shape[0]
    # pad M to the bf16 sublane so tiny decode batches stay Mosaic-legal
    Mp = max(16, ((M + 15) // 16) * 16)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    bk = _pick(K, 512, 128)
    bn = _pick(N, 512, 128)
    n_k, n_n = K // bk, N // bn

    if transpose_w:
        w_spec = pl.BlockSpec((bn, bk), lambda n, k: (n, k))
    else:
        w_spec = pl.BlockSpec((bk, bn), lambda n, k: (k, n))

    out = pl.pallas_call(
        functools.partial(_kernel, n_k=n_k, transpose_w=transpose_w),
        grid=(n_n, n_k),
        in_specs=[
            pl.BlockSpec((Mp, bk), lambda n, k: (0, k)),
            w_spec,
            # scales ride as a [1, N] row: a 1-D f32 operand's XLA tiling
            # (T(1024)) is not the one Mosaic derives from a (bn,) block
            pl.BlockSpec((1, bn), lambda n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((Mp, bn), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((Mp, bn), jnp.float32)],
        interpret=interpret,
    )(x, q, scale.astype(jnp.float32).reshape(1, N))
    return out[:M]


def _w4_kernel(x_ref, q_ref, s_ref, o_ref, acc_ref, *, n_k: int,
               group: int):
    k = pl.program_id(1)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    x = x_ref[...]                                # [M, bk]
    w = q_ref[...].astype(x.dtype)                # [bk, bn]
    gc = w.shape[0] // group
    # s_ref holds ALL K/group scale rows of this N block (a (bk/group, bn)
    # block is below Mosaic's 8-sublane minimum); this K step's rows start
    # at k * gc and are read one at a time (a dynamic multi-row sublane
    # slice must start on a multiple of 8)
    # per-group scaled partial dots: y = sum_g (x_g @ w_g) * s_g — the
    # group count per block is small and static (e.g. 512/128 = 4)
    for gi in range(gc):
        part = jnp.dot(
            x[:, gi * group:(gi + 1) * group],
            w[gi * group:(gi + 1) * group],
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] += part * s_ref[pl.ds(k * gc + gi, 1), :]

    @pl.when(k == n_k - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def w4_matmul(x: jax.Array, q: jax.Array, scale: jax.Array, *,
              interpret: bool = False) -> jax.Array:
    """x [M, K] x group-wise int4 weight q [K, N] (scale [K/group, N]) →
    [M, N]. The int4 blocks stream HBM packed (two nibbles per byte),
    dequantizing per group in-register — int8's bandwidth halved again.
    The group size derives from the q/scale shapes (single source of
    truth for every caller)."""
    M, K = x.shape
    N = q.shape[1]
    group = K // scale.shape[0]
    Mp = max(16, ((M + 15) // 16) * 16)
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    bk = _pick(K, 512, group)
    bn = _pick(N, 512, 128)
    n_k, n_n = K // bk, N // bn

    out = pl.pallas_call(
        functools.partial(_w4_kernel, n_k=n_k, group=group),
        grid=(n_n, n_k),
        in_specs=[
            pl.BlockSpec((Mp, bk), lambda n, k: (0, k)),
            pl.BlockSpec((bk, bn), lambda n, k: (k, n)),
            pl.BlockSpec((K // group, bn), lambda n, k: (0, n)),
        ],
        out_specs=pl.BlockSpec((Mp, bn), lambda n, k: (0, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((Mp, bn), jnp.float32)],
        interpret=interpret,
    )(x, q, scale.astype(jnp.float32))
    return out[:M]


def w4_eligible(x_shape: tuple, q: jax.Array, scale: jax.Array) -> bool:
    """Gates for the grouped-int4 kernel: 2-D NATIVE-int4 weight, 2-D scale
    whose group size is 128-aligned and divides the K block, decode-sized
    M. The dtype gate mirrors ``eligible``'s int8 check: a mode='w4'
    tensor stored as int8 (e.g. an imported GGUF q4 kept unpacked) has
    different Mosaic tiling and must take the XLA path."""
    if q.ndim != 2 or scale.ndim != 2 or q.dtype != jnp.int4:
        return False
    K, N = q.shape
    if scale.shape[1] != N or K % scale.shape[0]:
        return False
    group = K // scale.shape[0]
    M = 1
    for d in x_shape[:-1]:
        M *= d
    return (x_shape[-1] == K and group % 128 == 0 and K % 128 == 0
            and N % 128 == 0 and M <= 256)


def eligible(x_shape: tuple, q: jax.Array, scale: jax.Array,
             transpose_w: bool) -> bool:
    """Shape gates: 2-D int8 weight, 128-aligned dims, 1-D scale, small M
    (decode/small-batch — prefill matmuls are compute-bound and stay XLA)."""
    if q.ndim != 2 or scale.ndim != 1 or q.dtype != jnp.int8:
        return False
    K = q.shape[1] if transpose_w else q.shape[0]
    N = q.shape[0] if transpose_w else q.shape[1]
    M = 1
    for d in x_shape[:-1]:
        M *= d
    return (x_shape[-1] == K and K % 128 == 0 and N % 128 == 0
            and M <= 256)
