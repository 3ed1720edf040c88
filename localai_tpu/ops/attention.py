"""Pallas TPU flash-attention kernels for the serving engine.

The XLA path (models.llama._grouped_attn) materializes the full score tensor
[S, Hkv, g, T, L] in float32 — at long context that is the HBM-bandwidth
bottleneck of decode. These kernels keep K/V in HBM and stream them through
VMEM in ``block_k`` chunks with double-buffered async DMA and an online
softmax (flash attention), so per (slot, kv-head) the VMEM working set is
O(block_k · hd) regardless of context length, and only blocks inside the
[sliding-window, causal/length] frontier are ever fetched.

Replaces (TPU-era) the reference's per-slot CPU attention inside llama.cpp's
``llama_decode`` hot loop (/root/reference/backend/cpp/llama/
grpc-server.cpp:1546-1990). Two shapes of one kernel over contiguous K/V,
and the serving path's own over the block pool:

  * ``decode_attention`` — q is one token per slot, KV is the slot cache
    head-major and stacked over layers [L, S, Hkv, C, hd], read at a layer
    index (per-head DMA slices are (context, hd) — the (sublane, lane)
    tiling Mosaic requires); grid (S, Hkv); the GQA group (g = Hq/Hkv
    queries) forms the row dimension of the MXU matmul. Masking comes from
    per-slot write positions, not a materialized mask.
  * ``prefill_attention`` — single-sequence causal attention [T, ...];
    grid (Hkv, T/block_q); rows are (q-position × group) pairs; KV blocks
    beyond the causal frontier or the real prompt length are not fetched.
  * ``paged_decode_attention`` — q is one token per slot, KV is the block
    pool [L, N, Hkv, bt, hd] read through per-slot block tables; grid (S,):
    ONE program a slot holds every local kv head. A table entry's pool row
    [Hkv, bt, hd] is contiguous and moves in one copy; a step of the walk
    starts the copies of several entries together and folds them as one
    [Hkv, P·bt, hd] online-softmax tile (both matmuls batched over the head
    axis), ``num_buffers`` steps are in flight, and a slot's last step
    starts the next slot's first, so only a call's first program waits on
    a copy nothing hides. P comes from the pool's shape and a VMEM budget
    (``paged_decode_tiling``).

All run under ``interpret=True`` on CPU for tests (tests/test_ops.py,
tests/test_paged.py) and compile to Mosaic on real TPU. A sliding window is
a static argument of every kernel: every layer's (a Mistral-style model) or,
where a stack has window and full layers (models.afmoe), the window layers'
alone, a call a kind; the decode kernels start their walk at the window.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the in-kernel dequant step for int4 KV pools: the ONE halves-layout
# unpacker (pure VPU shifts — models.quant has no ops imports at module
# level, so no cycle) shared with the pool writers; a drifted second
# copy would make the kernels silently dequantize differently from the
# scatter that packed the rows
from localai_tpu.models.quant import (
    unpack_int4_lastdim as _unpack_nibbles,
)

_NEG_INF = -1e30


def _pick_block(total: int, target: int) -> int:
    """Largest divisor of ``total`` that is ≤ target (keeps grids exact)."""
    b = min(total, target)
    while total % b:
        b -= 1
    return b


def _pick_block_aligned(total: int, target: int) -> int:
    """Like _pick_block, but when ``total`` is 128-divisible the block is
    too, so every dynamic DMA offset (i·block) stays lane/sublane-aligned
    for Mosaic (e.g. total=640: plain _pick_block gives 320 — offset 320 is
    not 128-aligned; this gives 128). Unaligned totals only reach the
    kernels in interpret mode (the runner gates max_ctx%128 on hardware)."""
    if total % 128:
        return _pick_block(total, target)
    b = (min(total, max(target, 128)) // 128) * 128
    while total % b:
        b -= 128
    return b


def _scale_rows(ks_ref, vs_ref):
    """(ks_block, vs_block) readers for ``_flash_loop`` over VMEM-resident
    [1, 1, n_blocks, block_k] scale slabs: block i's scales are row i."""
    return (lambda i: ks_ref[0, 0, pl.ds(i, 1), :],
            lambda i: vs_ref[0, 0, pl.ds(i, 1), :])


def _flash_loop(q, kv_slice, kbuf, vbuf, ksem, vsem, lo, nb, block_k,
                mask_for_block, scales=None):
    """Online-softmax loop over KV blocks [lo, nb) of a contiguous K/V with
    ping-pong double-buffered DMA (the contiguous decode kernel and the
    prefill kernel; the paged decode kernel has a loop of its own).

    q: [rows, hd] f32 (pre-scaled). ``kv_slice(hbm_ref, i)`` yields the
    [block_k, hd] HBM slice for block i; ``mask_for_block(i)`` the
    [rows or 1, block_k] keep-mask. Returns the attention output [rows, hd].

    ``scales`` fuses scaled-int8 KV dequantization into the loop:
    (ks_block, vs_block) functions yielding block i's [1, block_k] f32
    per-position scale row (read from VMEM-resident scale rows — one row
    per KV block, so the read is a sublane index, never a lane slice). The
    dequant never materializes K/V in bf16 — per-position K scales
    distribute over the score matmul columns (q·(k·s) = (q·k)·s) and V
    scales over the probability columns (p@(v·s) = (p·s)@v), so both apply
    as [1, block_k] row multiplies on the VPU while the MXU matmuls stay
    int8-sourced.
    """
    k_hbm, v_hbm = kv_slice
    rows, hd = q.shape
    if scales is not None:
        ks_block, vs_block = scales

    def start(i, slot):
        pltpu.make_async_copy(k_hbm(i), kbuf.at[slot], ksem.at[slot]).start()
        pltpu.make_async_copy(v_hbm(i), vbuf.at[slot], vsem.at[slot]).start()

    def wait(i, slot):
        pltpu.make_async_copy(k_hbm(i), kbuf.at[slot], ksem.at[slot]).wait()
        pltpu.make_async_copy(v_hbm(i), vbuf.at[slot], vsem.at[slot]).wait()

    start(lo, 0)    # one block in flight before the first fold

    def body(i, carry):
        m, l, acc = carry
        slot = lax.rem(i - lo, 2)

        @pl.when(i + 1 < nb)
        def _prefetch():
            start(i + 1, 1 - slot)

        wait(i, slot)
        k = kbuf[slot].astype(jnp.float32)
        v = vbuf[slot].astype(jnp.float32)
        s = q @ k.T  # [rows, block_k] — MXU
        if scales is not None:
            s = s * ks_block(i)
        s = jnp.where(mask_for_block(i), s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        # denominator sums the raw probabilities; V scales touch only the
        # weighted-value numerator
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        if scales is not None:
            p = p * vs_block(i)
        acc_new = acc * alpha + p @ v
        return m_new, l_new, acc_new

    m0 = jnp.full((rows, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((rows, 1), jnp.float32)
    acc0 = jnp.zeros((rows, hd), jnp.float32)
    m, l, acc = lax.fori_loop(lo, nb, body, (m0, l0, acc0))
    return acc / jnp.maximum(l, 1e-30)


# ---------------------------------------------------------------------------
# decode: one token per slot over the slot KV cache
# ---------------------------------------------------------------------------


def _decode_kernel(layer_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                   block_k: int, sm_scale: float,
                   sliding_window: Optional[int], quantized: bool):
    # k_ref/v_ref are the FULL stacked [L, S, Hkv, C, hd] cache in HBM
    # (Mosaic only allows whole-array ANY refs); layer (an SMEM scalar),
    # slot and head are picked in the DMA slice, so no layer is ever
    # sliced out of the stack for the kernel.
    # When quantized, ks/vs_ref are this (slot, head)'s f32 scales as
    # [C/block_k, block_k] rows, auto-loaded into VMEM by their BlockSpec
    # (≤32 KB even at 8k context — no manual DMA needed).
    if quantized:
        ks_ref, vs_ref, o_ref, kbuf, vbuf, ksem, vsem = rest
    else:
        o_ref, kbuf, vbuf, ksem, vsem = rest
    s_idx = pl.program_id(0)
    h_idx = pl.program_id(1)
    layer = layer_ref[0]
    pos = pos_ref[s_idx]
    q = q_ref[0, 0].astype(jnp.float32) * sm_scale  # [g, hd]
    ctx = k_ref.shape[3]

    nb = jnp.minimum(pos // block_k + 1, ctx // block_k)
    lo = jnp.int32(0)
    if sliding_window is not None:
        lo = jnp.maximum((pos - sliding_window + 1) // block_k, 0)

    def slice_of(ref):
        return lambda i: ref.at[layer, s_idx, h_idx,
                                pl.ds(i * block_k, block_k), :]

    def mask_for_block(i):
        idx = i * block_k + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        keep = idx <= pos
        if sliding_window is not None:
            keep &= idx > pos - sliding_window
        return keep

    scales = None
    if quantized:
        scales = _scale_rows(ks_ref, vs_ref)
    out = _flash_loop(q, (slice_of(k_ref), slice_of(v_ref)),
                      kbuf, vbuf, ksem, vsem, lo, nb, block_k, mask_for_block,
                      scales=scales)
    o_ref[0, 0] = out.astype(o_ref.dtype)


def decode_attention(
    q: jax.Array,            # [S, Hq, hd]
    k_cache: jax.Array,      # [L, S, Hkv, C, hd] stacked head-major cache
    v_cache: jax.Array,      # [L, S, Hkv, C, hd]
    layer: jax.Array,        # scalar i32 — the layer of the stack to read
    positions: jax.Array,    # [S] i32 — current token's KV write position
    k_scale: Optional[jax.Array] = None,  # [L, S, Hkv, C] f32 (int8 KV)
    v_scale: Optional[jax.Array] = None,
    *,
    sliding_window: Optional[int] = None,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Flash GQA decode attention over one layer of the stacked slot
    cache. Returns [S, Hq, hd]. The stack arrives whole and the layer is
    picked in the kernel's DMA slice: inside the layer scan the cache is
    a loop carry, and a per-layer slice handed to a custom call would be
    a copy of that layer.

    With ``k_scale``/``v_scale`` the cache is scaled int8 and dequantization
    fuses into the flash loop (scores/probs column scaling) — decode reads
    half the KV bytes of bf16 and never materializes a dequantized cache.
    """
    S, Hq, hd = q.shape
    Hkv, C = k_cache.shape[2], k_cache.shape[3]
    g = Hq // Hkv
    bk = _pick_block_aligned(C, block_k)
    qg = q.reshape(S, Hkv, g, hd)
    quantized = k_scale is not None

    kernel = functools.partial(
        _decode_kernel, block_k=bk, sm_scale=hd ** -0.5,
        sliding_window=sliding_window, quantized=quantized,
    )
    in_specs = [
        # SMEM blocks must cover the whole array; index by slot inside
        pl.BlockSpec((1,), lambda s, h: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((S,), lambda s, h: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, g, hd), lambda s, h: (s, h, 0, 0)),
        # K/V stay whole in HBM (ANY refs must be unblocked); the
        # kernel DMAs block_k slices per (layer, slot, head) itself
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    scratch = [
        pltpu.VMEM((2, bk, hd), k_cache.dtype),
        pltpu.VMEM((2, bk, hd), v_cache.dtype),
    ]
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    args = [layer, positions.astype(jnp.int32), qg, k_cache, v_cache]
    if quantized:
        # scales ride normal VMEM blocks, one [C/bk, bk] slab per (slot,
        # head) grid step: the last two block dims equal the array's, which
        # is what the Mosaic block rule asks of a non-(8,128) block. The
        # layer's scales are sliced out here (hd/4 times smaller than the
        # layer's K or V, which never is)
        spec = pl.BlockSpec((1, 1, C // bk, bk), lambda s, h: (s, h, 0, 0))
        in_specs += [spec, spec]
        args += [k_scale[layer[0]].reshape(S, Hkv, C // bk, bk),
                 v_scale[layer[0]].reshape(S, Hkv, C // bk, bk)]
    scratch += [pltpu.SemaphoreType.DMA((2,))] * 2
    out = pl.pallas_call(
        kernel,
        name="decode_attn",
        grid=(S, Hkv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, g, hd), lambda s, h: (s, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((S, Hkv, g, hd), q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(*args)
    return out.reshape(S, Hq, hd)


# ---------------------------------------------------------------------------
# prefill: single-sequence causal attention
# ---------------------------------------------------------------------------


def _prefill_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                    kbuf, vbuf, ksem, vsem, *, block_q: int, block_k: int,
                    groups: int, sm_scale: float,
                    sliding_window: Optional[int]):
    h_idx = pl.program_id(0)
    length = len_ref[0]
    qi = pl.program_id(1)
    hd = q_ref.shape[3]
    T = k_ref.shape[1]
    rows = block_q * groups
    q = q_ref[:, 0].astype(jnp.float32).reshape(rows, hd) * sm_scale
    # row r ↦ absolute q position
    qpos = qi * block_q + lax.broadcasted_iota(jnp.int32, (rows, 1), 0) // groups

    # trip range: causal frontier ∧ real length, minus sub-window blocks
    nb_causal = ((qi + 1) * block_q + block_k - 1) // block_k
    nb_len = (length + block_k - 1) // block_k
    nb = jnp.minimum(jnp.minimum(nb_causal, nb_len), T // block_k)
    lo = jnp.int32(0)
    if sliding_window is not None:
        lo = jnp.maximum((qi * block_q - sliding_window + 1) // block_k, 0)
    # rows entirely past `length` are garbage either way; keep the loop
    # non-empty so the DMA pipeline stays well-formed
    nb = jnp.maximum(nb, lo + 1)

    def slice_of(ref):
        return lambda i: ref.at[h_idx, pl.ds(i * block_k, block_k), :]

    def mask_for_block(i):
        kj = i * block_k + lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
        keep = (kj <= qpos) & (kj < length)
        if sliding_window is not None:
            keep &= kj > qpos - sliding_window
        return keep

    out = _flash_loop(q, (slice_of(k_ref), slice_of(v_ref)),
                      kbuf, vbuf, ksem, vsem, lo, nb, block_k, mask_for_block)
    o_ref[:] = out.reshape(block_q, 1, groups, hd).astype(o_ref.dtype)


def prefill_attention(
    q: jax.Array,         # [T, Hq, hd]
    k: jax.Array,         # [Hkv, T, hd] head-major chunk
    v: jax.Array,         # [Hkv, T, hd]
    length: jax.Array,    # scalar i32 — real (unpadded) sequence length
    *,
    sliding_window: Optional[int] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    """Flash causal GQA prefill attention. Returns [T, Hq, hd]."""
    T, Hq, hd = q.shape
    Hkv = k.shape[0]
    g = Hq // Hkv
    bq = _pick_block_aligned(T, block_q)
    bk = _pick_block_aligned(T, block_k)
    qg = q.reshape(T, Hkv, g, hd)

    kernel = functools.partial(
        _prefill_kernel, block_q=bq, block_k=bk, groups=g,
        sm_scale=hd ** -0.5, sliding_window=sliding_window,
    )
    out = pl.pallas_call(
        kernel,
        name="prefill_attn",
        grid=(Hkv, T // bq),
        in_specs=[
            pl.BlockSpec((1,), lambda h, i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((bq, 1, g, hd), lambda h, i: (i, h, 0, 0)),
            # K/V whole in HBM; the kernel DMAs per-head block_k slices
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((bq, 1, g, hd), lambda h, i: (i, h, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, Hkv, g, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, bk, hd), k.dtype),
            pltpu.VMEM((2, bk, hd), v.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        interpret=interpret,
    )(jnp.reshape(length, (1,)).astype(jnp.int32), qg, k, v)
    return out.reshape(T, Hq, hd)


# ---------------------------------------------------------------------------
# paged decode: one token per slot over a block pool via block tables
# ---------------------------------------------------------------------------


def gather_blocks(cache: jax.Array, tables: jax.Array,
                  layer: Optional[jax.Array] = None) -> jax.Array:
    """[N, H, bt, hd] block pool + [S, MB] i32 tables -> [S, H, MB*bt, hd]
    logical context rows — THE pool-gather used by the pure-lax paged
    attention path and the paged KV write policies (engine.kvcache).
    With ``layer`` the cache is the stacked [L, N, H, bt, hd] pool and the
    layer rides the SAME gather (``cache[layer, tables]``): slicing the
    layer out first would copy all its N blocks to read MB of them."""
    S, MB = tables.shape
    H, bt, hd = cache.shape[-3:]
    g = cache[tables] if layer is None else cache[layer, tables]
    return g.transpose(0, 2, 1, 3, 4).reshape(S, H, MB * bt, hd)


def gather_block_scales(scales: jax.Array, tables: jax.Array,
                        layer: Optional[jax.Array] = None) -> jax.Array:
    """[N, H, bt] scale pool ([L, N, H, bt] with ``layer``) + [S, MB]
    tables -> [S, H, MB*bt]."""
    S, MB = tables.shape
    H, bt = scales.shape[-2:]
    g = scales[tables] if layer is None else scales[layer, tables]
    return g.transpose(0, 2, 1, 3).reshape(S, H, MB * bt)


# K and V bytes the paged decode kernel keeps in VMEM for its copies: every
# buffer of the ring, every local kv head. A step's tile is cut to it, so a
# step holds the same few hundred KiB in flight whatever the model's local
# head count, block size or element size, and a deeper ring means smaller
# steps, not more VMEM (1 MiB: two steps of two 128 KiB rows at Mistral-7B's
# 8 kv heads, two steps of eight 32 KiB rows at a chip's 2 of the 24B's)
_PAGED_KV_VMEM_BYTES = 1 << 20
# ... and to this many table entries, each a copy of its own that the scalar
# core issues and waits for in unrolled code (on the chip, PR 29: a chip's 2
# heads of the 24B ran fastest at 8 entries a step, 16 lost 17%)
_PAGED_STEP_BLOCKS_MAX = 8
# steps in flight. On the chip (PR 29, us a layer at the cells' shapes, the
# ring at 1 MiB) a third step won nothing: 48.1 / 29.7 / 335 / 52.5 against
# two steps' 42.0 / 28.3 / 290 / 45.7
_PAGED_NUM_BUFFERS = 2


def paged_decode_tiling(kv_heads: int, block_tokens: int, row_lanes: int,
                        itemsize: int, max_blocks: int,
                        num_buffers: int = _PAGED_NUM_BUFFERS,
                        arrays: int = 2,
                        vmem_bytes: int = _PAGED_KV_VMEM_BYTES,
                        step_blocks_max: int = _PAGED_STEP_BLOCKS_MAX,
                        ) -> tuple[int, int, int]:
    """(blocks a step, steps in flight, VMEM bytes of the K/V ring) the
    paged decode kernel derives from the pool it is handed: ``kv_heads``
    local heads of ``row_lanes`` stored elements (hd, or hd/2 packed) of
    ``itemsize`` bytes, tables ``max_blocks`` wide. A step is the largest
    power of two of table entries, at most ``_PAGED_STEP_BLOCKS_MAX``, whose
    K and V rows, ``num_buffers`` steps deep, fit ``_PAGED_KV_VMEM_BYTES``;
    never under one block (a pool whose single row is over the budget gets
    one-block steps)."""
    depth = max(2, int(num_buffers))
    # K + V (``arrays`` 2), or the one array of a latent pool
    row = arrays * kv_heads * block_tokens * row_lanes * itemsize
    blocks = max(1, min(vmem_bytes // (depth * row), step_blocks_max,
                        max_blocks))
    blocks = 1 << (blocks.bit_length() - 1)
    return blocks, depth, depth * blocks * row


def _paged_decode_kernel(layer_ref, pos_ref, tbl_ref, q_ref, k_ref, v_ref,
                         *rest, block_tokens: int, blocks: int, depth: int,
                         sm_scale: float, sliding_window: Optional[int],
                         quantized: bool, int4: bool, mm_dtype,
                         write_rows: int = 0):
    # One program a slot, every local kv head in it. k_ref/v_ref are the
    # FULL stacked [L, N, Hkv, bt, hd] block pool in HBM; a table entry's
    # row pool[layer, tbl[s, i]] is one contiguous [Hkv, bt, hd] slab and
    # moves in ONE copy. A step of the walk covers ``blocks`` consecutive
    # table entries [t*blocks, (t+1)*blocks): their copies start together,
    # land side by side in a [Hkv, blocks*bt, hd] buffer of a ``depth``-deep
    # ring and fold as one online-softmax tile; entries outside [lo, nb)
    # are not copied (their columns are masked, over whatever the buffer
    # held). Before a slot's last fold the NEXT LIVE slot's first step is
    # started into the free buffer, so only the call's first live program
    # waits on a copy nothing hides; the ring position rides SMEM scratch
    # across grid steps (the grid is sequential: 'arbitrary').
    # A slot whose frontier entry tbl[s, pos // bt] is block 0, the trash
    # block (a released slot, an admission before its last chunk: block 0 is
    # never allocated), holds no stream: its program starts no copy, waits
    # for none, folds nothing, leaves the ring and its flags as they were
    # and writes zeros to its ``o`` block, which nothing reads. A part-full
    # batch pays for its live slots; a full one runs what it always ran.
    # Scales of int8/int4 pools arrive gathered in logical order, one
    # [Hkv, steps, blocks*bt] f32 slab a slot through their BlockSpec (a
    # [1, bt] row of the f32 pool is narrower than Mosaic's 128-lane DMA
    # tile): step t's scales are row t. int4 pools arrive nibble-packed
    # [L, N, Hkv, bt, hd/2] and unpack in VMEM after the wait.
    # With ``write_rows`` the kernel is also the step's WRITER (unscaled
    # pools): the slot's new K and V rows arrive as one [1, Hkv, hd] block,
    # the pool is aliased to two outputs, and the slot's last step, which
    # holds the frontier block tbl[s, pos // bt], lays the row over position
    # pos % bt of it in the ring before it folds, then copies the aligned
    # group of ``write_rows`` rows that holds the position (whole tiles,
    # every head in one strided copy, staged in a buffer of its own so the
    # ring is free at once) back to pool[layer, row]. Beside the one row the
    # group carries what was just read from those rows: this slot's older
    # tokens, or rows past its frontier that nothing reads. The write-back
    # in flight (``ring_ref[1]``) is the previous LIVE slot's: the next live
    # slot waits for it before it stages its own, the call's last program
    # waits for it whether or not it is live.
    if quantized:
        ks_ref, vs_ref, o_ref, kbuf, vbuf, ksem, vsem, ring_ref = rest
    elif write_rows:
        (knew_ref, vnew_ref, o_ref, kout_ref, vout_ref, kbuf, vbuf, ksem,
         vsem, ring_ref, kstage, vstage, wsem) = rest
    else:
        o_ref, kbuf, vbuf, ksem, vsem, ring_ref = rest
    s_idx = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    bt, P = block_tokens, blocks
    T = P * bt

    def walk(slot):
        """(pos, lo, nb, first step, steps) of ``slot``'s walk."""
        pos = pos_ref[slot]
        nb = jnp.minimum(pos // bt + 1, tbl_ref.shape[1])
        lo = jnp.int32(0)
        if sliding_window is not None:
            lo = jnp.clip((pos - sliding_window + 1) // bt, 0, nb - 1)
        t0 = lo // P
        return pos, lo, nb, t0, (nb + P - 1) // P - t0

    def k_copy(row, into, buf):
        return pltpu.make_async_copy(k_ref.at[layer, row], kbuf.at[into],
                                     ksem.at[buf])

    def v_copy(row, into, buf):
        return pltpu.make_async_copy(v_ref.at[layer, row], vbuf.at[into],
                                     vsem.at[buf])

    def entries(slot, lo, nb, t, buf, do):
        """``do(row, into, buf)`` for each table entry of step ``t`` that
        the walk [lo, nb) holds: its pool row, its place in the buffer."""
        for j in range(P):
            blk = t * P + j

            @pl.when((blk >= lo) & (blk < nb))
            def _(j=j, blk=blk):
                do(tbl_ref[slot, blk],
                   (buf, slice(None), pl.ds(j * bt, bt), slice(None)), buf)

    def start(slot, lo, nb, t, buf):
        entries(slot, lo, nb, t, buf,
                lambda *c: (k_copy(*c).start(), v_copy(*c).start()))

    def frontier_of(slot):
        """The block ``slot``'s position is in, the last entry of its walk:
        0, the trash block, for a slot that holds no stream."""
        return tbl_ref[slot, walk(slot)[2] - 1]

    def start_live_from(slot, buf):
        """The first step of the first live slot at or behind ``slot`` into
        ``buf``, if the call has one: a walk over the frontier entries in
        SMEM that ends at its first read where ``slot`` is live."""
        nxt = lax.while_loop(
            lambda s: (s < n_slots)
            & (frontier_of(jnp.minimum(s, n_slots - 1)) == 0),
            lambda s: s + 1, slot)

        @pl.when(nxt < n_slots)
        def _():
            _, lo_n, nb_n, t0_n, _ = walk(nxt)
            start(nxt, lo_n, nb_n, t0_n, buf)

    pos, lo, nb, t0, steps = walk(s_idx)
    frontier = frontier_of(s_idx)
    live = frontier != 0

    @pl.when(s_idx == 0)
    def _cold():
        # skipped entries leave a buffer's columns as they were: masked to
        # probability 0, which only a finite value multiplies to 0
        kbuf[...] = jnp.zeros(kbuf.shape, kbuf.dtype)
        vbuf[...] = jnp.zeros(vbuf.shape, vbuf.dtype)
        ring_ref[0] = 0
        if write_rows:
            ring_ref[1] = 0     # no write-back in flight
        # the call's first copies: the first live slot's, wherever it is
        start_live_from(s_idx, 0)

    q_dims = q_ref.shape[1:]                          # [Hkv, g, hd]

    def write_back(stage, out_ref, sem):
        return pltpu.make_async_copy(
            stage, out_ref.at[layer, frontier, :, pl.ds(group, write_rows), :],
            wsem.at[sem])

    def lay(new_ref, ring, buf, stage, out_ref, sem):
        """The slot's new row over its place in the ring (``buf``: the last
        step's buffer, which has landed), and its group of rows on the way
        back to the pool."""
        at = (buf, slice(None),
              pl.ds(pl.multiple_of(lax.rem(nb - 1, P) * bt + group,
                                   write_rows), write_rows), slice(None))
        hit = lax.broadcasted_iota(
            jnp.int32, (1, write_rows, 1), 1) == pos % bt - group
        rows = jnp.where(hit, new_ref[0][:, None, :], ring[at])
        ring[at] = rows
        stage[...] = rows
        write_back(stage, out_ref, sem).start()

    if write_rows:
        group = pl.multiple_of(pos % bt // write_rows * write_rows,
                               write_rows)

    @pl.when(live)
    def _attend():
        base = ring_ref[0]      # the buffer this slot's first step is in
        for j in range(1, depth - 1):
            @pl.when(j < steps)
            def _prime(j=j):
                start(s_idx, lo, nb, t0 + j, lax.rem(base + j, depth))

        q = q_ref[0].astype(mm_dtype)                 # [Hkv, g, hd]
        Hkv, g, hd = q_dims

        def fold(i, carry, last=False):
            m, l, acc = carry
            t = t0 + i
            buf = lax.rem(base + i, depth)
            entries(s_idx, lo, nb, t, buf, lambda *c: k_copy(*c).wait())
            if last and write_rows:
                # the staging buffers are the previous live slot's until
                # its copies have left them: a whole program ago or more
                @pl.when(ring_ref[1] == 1)
                def _():
                    write_back(kstage, kout_ref, 0).wait()
                    write_back(vstage, vout_ref, 1).wait()
                lay(knew_ref, kbuf, buf, kstage, kout_ref, 0)
            if int4:
                k = _unpack_nibbles(kbuf[buf], jnp.float32)
            else:
                k = kbuf[buf]
            s = jnp.einsum("hgd,htd->hgt", q, k.astype(mm_dtype),
                           preferred_element_type=jnp.float32) * sm_scale
            if quantized:
                s = s * ks_ref[0, :, pl.ds(t, 1), :]
            idx = t * T + lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
            keep = idx <= pos
            if sliding_window is not None:
                keep &= idx > pos - sliding_window
            s = jnp.where(keep, s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            # denominator sums the raw probabilities; V scales touch only
            # the weighted-value numerator
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                p = p * vs_ref[0, :, pl.ds(t, 1), :]
            entries(s_idx, lo, nb, t, buf, lambda *c: v_copy(*c).wait())
            if last and write_rows:
                lay(vnew_ref, vbuf, buf, vstage, vout_ref, 1)
            if int4:
                v = _unpack_nibbles(vbuf[buf], jnp.float32)
            else:
                v = vbuf[buf].astype(jnp.float32)
            acc_new = acc * alpha + jnp.einsum(
                "hgt,htd->hgd", p, v, preferred_element_type=jnp.float32)
            return m_new, l_new, acc_new

        def body(i, carry):
            @pl.when(i + depth - 1 < steps)
            def _prefetch():
                start(s_idx, lo, nb, t0 + i + depth - 1,
                      lax.rem(base + i + depth - 1, depth))
            return fold(i, carry)

        m0 = jnp.full((Hkv, g, 1), _NEG_INF, jnp.float32)
        l0 = jnp.zeros((Hkv, g, 1), jnp.float32)
        acc0 = jnp.zeros((Hkv, g, hd), jnp.float32)
        carry = lax.fori_loop(0, steps - 1, body, (m0, l0, acc0))

        # the last fold: its buffer's successor is free (every step before
        # it has been folded), so the next live slot's first copies go
        # there now
        after = lax.rem(base + steps, depth)
        start_live_from(s_idx + 1, after)
        ring_ref[0] = after
        _, l, acc = fold(steps - 1, carry, last=True)
        o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        if write_rows:
            # the next live slot waits for these copies before it stages
            # its own
            ring_ref[1] = 1

    @pl.when(~live)
    def _empty():
        # a slot on the trash block holds no stream: no copy, no wait, no
        # fold, the ring and its flags as they were. Nothing reads the row
        o_ref[0] = jnp.zeros(q_dims, o_ref.dtype)

    if write_rows:
        # the call's last program, live or not, waits for the write-back in
        # flight: the next call of this cache layer, one step later, reads
        # the row
        @pl.when((s_idx + 1 == n_slots) & (ring_ref[1] == 1))
        def _():
            write_back(kstage, kout_ref, 0).wait()
            write_back(vstage, vout_ref, 1).wait()


def paged_decode_attention(
    q: jax.Array,            # [S, Hq, hd]
    k_cache: jax.Array,      # [L, N, Hkv, bt, hd] stacked block pool
                             # (int4: nibble-packed [L, N, Hkv, bt, hd/2])
    v_cache: jax.Array,      # [L, N, Hkv, bt, hd]
    layer: jax.Array,        # scalar i32 — the layer of the stack to read
    tables: jax.Array,       # [S, MB] i32 per-slot block tables
    positions: jax.Array,    # [S] i32 — current token's KV write position
    k_scale: Optional[jax.Array] = None,  # [L, N, Hkv, bt] f32 (int8/int4)
    v_scale: Optional[jax.Array] = None,
    k_new: Optional[jax.Array] = None,  # [S, Hkv, hd], the pool's dtype:
    v_new: Optional[jax.Array] = None,  # the step's rows, for the kernel
                                        # to WRITE (unscaled pools)
    *,
    sliding_window: Optional[int] = None,
    interpret: bool = False,
    num_buffers: int = _PAGED_NUM_BUFFERS,
):
    """Flash GQA decode attention over one layer of the stacked paged
    block pool. Returns [S, Hq, hd]. One program a slot walks the slot's
    block table in SMEM; a table entry's row of the pool, every (local) kv
    head of it, is one contiguous copy, several entries are in flight a
    step and fold as one online-softmax tile, ``num_buffers`` steps are in
    flight, and a slot's last step starts the next slot's first
    (``paged_decode_tiling`` derives the step from the pool's shape) —
    identical math to ``decode_attention``, with the contiguous slot row
    replaced by gather-over-block-table.

    The pool arrives WHOLE, all layers, and ``layer`` picks the layer in
    the DMA slice: inside the layer scan (models.llama.forward) the pool
    is a loop carry written in place, and handing this custom call a
    per-layer slice would make XLA copy that layer (76 MB at Mistral-7B's
    289 blocks) before every call. tests/test_tpu_compile.py holds the
    compiled decode program to no such copy.

    Under a mesh the runner wraps this in ``shard_map`` with slots (q,
    tables, positions) on 'data' and head groups (q, pool) on 'model':
    the body is then the per-device single-chip kernel over the LOCAL kv
    heads (read from the pool's shape), so the pool's layer and block axes
    must arrive WHOLE on every device (table values are global physical
    block ids) and both head counts must divide the 'model' width
    (``ops.select_paged_attn_impl`` gates that).

    With ``k_new`` / ``v_new`` the kernel also WRITES the step: the pool is
    as it was BEFORE the step, the rows are each slot's new K and V in the
    pool's dtype, and the call returns ``(out, k_cache, v_cache)``, the
    pools aliased to their arguments and holding row ``s`` at
    ``[layer, tables[s, positions[s] // bt], :, positions[s] % bt]``,
    trash block aside (a slot whose frontier entry is block 0 writes
    nothing). The slot's program lays the row over the frontier block it
    has in VMEM anyway, attends over it as if a scatter had stored it
    first, and copies the aligned group of rows around the position back.
    The attention output stays the FIRST result (the benchmark's readers
    name an operation by the first shape of its result). Unscaled pools
    only: a scaled pool's f32 scale row is narrower than a DMA tile, and
    its policy scatters (engine.kvcache).

    Behind ONE trace a shape (``_paged_decode_call``, a ``jax.jit`` that is
    inlined where it is lowered: a program holds the operations it held):
    the kernel's body is the costliest thing a decode program traces (a
    quarter of a first dispatch on the chip's host: PERF.md 6, PR 64), and
    every program of a runner that holds a decode step, the multi-step one
    and a ride among them, calls it with the same shapes."""
    return _paged_decode_call(
        q, layer, positions, tables, k_cache, v_cache, k_scale, v_scale,
        k_new, v_new, sliding_window=sliding_window, interpret=interpret,
        num_buffers=num_buffers)


# (the arguments in the order the body first uses them: the call stands in
# the traced program as one equation, and a loop body's closed-over values
# are numbered by its operands' order: tests/test_neighbour_texts.py)
@functools.partial(jax.jit, inline=True, static_argnames=(
    "sliding_window", "interpret", "num_buffers"))
def _paged_decode_call(q, layer, positions, tables, k_cache, v_cache,
                       k_scale, v_scale, k_new, v_new, *, sliding_window,
                       interpret, num_buffers):
    """``paged_decode_attention``'s body."""
    S, Hq, hd = q.shape
    Hkv, bt = k_cache.shape[2], k_cache.shape[3]
    MB = tables.shape[1]
    g = Hq // Hkv
    qg = q.reshape(S, Hkv, g, hd)
    quantized = k_scale is not None
    writes = k_new is not None
    if writes and quantized:
        raise ValueError("the paged kernel writes unscaled pools only")
    # rows a write-back moves: whole (sublane) tiles of the pool's dtype
    # around the position, or the block where it holds no whole tile
    write_rows = 0
    if writes:
        write_rows = 32 // k_cache.dtype.itemsize
        if bt % write_rows:
            write_rows = bt
    # an int4 pool is self-describing: its last dim is the packed hd/2
    int4 = quantized and k_cache.shape[-1] * 2 == hd
    P, depth, _ = paged_decode_tiling(
        Hkv, bt, k_cache.shape[-1], k_cache.dtype.itemsize, MB, num_buffers)
    # K is bf16 (or small integers) in HBM and q bf16 from the model: their
    # products are exact in float32, so the score matmul takes them as they
    # are and accumulates in float32; any other pairing multiplies in f32
    exact = (q.dtype == jnp.bfloat16
             and k_cache.dtype in (jnp.bfloat16, jnp.int8))
    kernel = functools.partial(
        _paged_decode_kernel, block_tokens=bt, blocks=P, depth=depth,
        sm_scale=hd ** -0.5, sliding_window=sliding_window,
        quantized=quantized, int4=int4,
        mm_dtype=jnp.bfloat16 if exact else jnp.float32,
        write_rows=write_rows,
    )
    in_specs = [
        pl.BlockSpec((1,), lambda s: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((S,), lambda s: (0,), memory_space=pltpu.SMEM),
        pl.BlockSpec((S, MB), lambda s: (0, 0), memory_space=pltpu.SMEM),
        pl.BlockSpec((1, Hkv, g, hd), lambda s: (s, 0, 0, 0)),
        # the pool stays whole in HBM; rows are gathered by table DMA
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
    ]
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    args = [layer, positions.astype(jnp.int32), tables.astype(jnp.int32), qg,
            k_cache, v_cache]
    if quantized:
        # gather each slot's scale rows in XLA (small: S·MB·Hkv·bt f32),
        # layer and blocks in one gather, padded to whole steps: the kernel
        # gets one [Hkv, steps, P*bt] slab a slot
        steps = -(-MB // P)
        padded = jnp.pad(tables, ((0, 0), (0, steps * P - MB)))
        spec = pl.BlockSpec((1, Hkv, steps, P * bt), lambda s: (s, 0, 0, 0))
        in_specs += [spec, spec]
        args += [gather_block_scales(sc, padded, layer[0]).reshape(
                     S, Hkv, steps, P * bt) for sc in (k_scale, v_scale)]
    # int4 pools buffer the packed [.., hd/2] bytes (unpack happens after
    # the wait), so the ring mirrors the pool's last dim
    ring = (depth, Hkv, P * bt, k_cache.shape[-1])
    out_specs = [pl.BlockSpec((1, Hkv, g, hd), lambda s: (s, 0, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((S, Hkv, g, hd), q.dtype)]
    scratch = [
        pltpu.VMEM(ring, k_cache.dtype),
        pltpu.VMEM(ring, v_cache.dtype),
        pltpu.SemaphoreType.DMA((depth,)),
        pltpu.SemaphoreType.DMA((depth,)),
        # the ring position and, where the kernel writes, whether the
        # previous slot's write-back is in flight
        pltpu.SMEM((2,), jnp.int32),
    ]
    aliases = {}
    if writes:
        row = pl.BlockSpec((1, Hkv, hd), lambda s: (s, 0, 0))
        in_specs += [row, row]
        args += [k_new.astype(k_cache.dtype), v_new.astype(v_cache.dtype)]
        out_specs += [pl.BlockSpec(memory_space=pl.ANY)] * 2
        out_shape += [jax.ShapeDtypeStruct(c.shape, c.dtype)
                      for c in (k_cache, v_cache)]
        aliases = {4: 1, 5: 2}      # the pools: written where they lie
        stage = (Hkv, write_rows, hd)
        scratch += [pltpu.VMEM(stage, k_cache.dtype),
                    pltpu.VMEM(stage, v_cache.dtype),
                    pltpu.SemaphoreType.DMA((2,))]
    out, *pools = pl.pallas_call(
        kernel,
        name="paged_decode_attn",
        grid=(S,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases=aliases,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(*args)
    out = out.reshape(S, Hq, hd)
    return (out, *pools) if writes else out


def paged_decode_attention_ref(
    q: jax.Array,            # [S, Hq, hd]
    k_cache: jax.Array,      # [N, Hkv, bt, hd]
    v_cache: jax.Array,
    tables: jax.Array,       # [S, MB] i32
    positions: jax.Array,    # [S]
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    *,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Pure-lax paged decode attention (gather + masked softmax): the CPU
    fallback and the numerical reference the Pallas kernel is tested
    against. Handles f32/bf16, scaled-int8 and nibble-packed int4 pools
    (int4 detected from the pool's packed hd/2 last dim). Returns
    [S, Hq, hd]."""
    S, Hq, hd = q.shape
    Hkv, bt = k_cache.shape[1], k_cache.shape[2]
    MB = tables.shape[1]
    g = Hq // Hkv
    int4 = k_scale is not None and k_cache.shape[-1] * 2 == hd

    keys = gather_blocks(k_cache, tables)
    values = gather_blocks(v_cache, tables)
    if int4:
        keys = _unpack_nibbles(keys)
        values = _unpack_nibbles(values)
    keys = keys.astype(jnp.float32)
    values = values.astype(jnp.float32)
    if k_scale is not None:
        keys = keys * gather_block_scales(k_scale, tables)[..., None]
        values = values * gather_block_scales(v_scale, tables)[..., None]
    qg = q.reshape(S, Hkv, g, hd).astype(jnp.float32) * hd ** -0.5
    scores = jnp.einsum("skgh,sklh->skgl", qg, keys)
    idx = jnp.arange(MB * bt)[None, None, None, :]
    pos = positions[:, None, None, None]
    keep = idx <= pos
    if sliding_window is not None:
        keep &= idx > pos - sliding_window
    scores = jnp.where(keep, scores, _NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("skgl,sklh->skgh", probs, values)
    return out.reshape(S, Hq, hd).astype(q.dtype)


# ---------------------------------------------------------------------------
# heads narrower than a lane tile: several K/V heads share one pool row
# ---------------------------------------------------------------------------
#
# Mosaic copies 128-lane rows, so a K/V head of 64 has no compiled kernel of
# its own. A family whose heads are narrower (models.lfm2) hands pool and
# kernels a model of ``Hkv / f`` heads of ``f hd`` = 128 lanes:
# ``k.reshape(.., Hkv / f, f hd)`` IS ``[head f j | .. | head f j + f - 1]``,
# so the pool, its tables and write policies see nothing new and move the
# true model's bytes. A query head's ``hd`` values stand at its OWN K/V head's
# place in the row, zeros elsewhere: the scores are exact (the other heads'
# lanes meet zeros), the output's own ``hd`` lanes are the head's result, and
# the rest (the neighbours' values under this head's weights) is dropped.
# Every attend scales by the ROW's width, ``(f hd)^-1/2``: the family folds
# the missing ``f^1/2`` into q where it rounds q anyway. The matmul units do
# f times the products, in kernels that are bound by bytes.


def heads_per_row(num_kv_heads: int, head_dim: int) -> int:
    """K/V heads of ``head_dim`` that share one 128-lane pool row: 128 /
    head_dim where that is whole and divides the head count, else 1 (the
    heads stand alone: 128-aligned ones by right, others for the XLA attend
    alone, which ``ops.select_paged_attn_impl`` says at load)."""
    f = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
    return f if num_kv_heads % f == 0 else 1


def pack_kv(x: jax.Array, f: int) -> jax.Array:
    """k or v [.., Hkv, hd] as the pool's rows [.., Hkv / f, f hd]."""
    *lead, h, hd = x.shape
    return x.reshape(*lead, h // f, f * hd)


def pack_q(q: jax.Array, f: int, num_kv_heads: int) -> jax.Array:
    """q [.., Hq, hd] as [.., Hq, f hd]: a head's values at the lanes of its
    own K/V head in the packed row (head h reads K/V head h // (Hq / Hkv),
    which is part (..) % f of its row), zeros at the others'."""
    if f == 1:
        return q
    *lead, hq, hd = q.shape
    parts = q.reshape(*lead, num_kv_heads // f, f, hq // num_kv_heads, hd)
    pad = [(0, 0)] * (parts.ndim - 2)
    return jnp.stack(
        [jnp.pad(parts[..., j, :, :], [*pad, (j * hd, (f - 1 - j) * hd)])
         for j in range(f)], axis=-3).reshape(*lead, hq, f * hd)


def unpack_out(out: jax.Array, f: int, num_kv_heads: int) -> jax.Array:
    """The attend's [.., Hq, f hd] as [.., Hq, hd]: each head's own lanes."""
    if f == 1:
        return out
    *lead, hq, row = out.shape
    hd = row // f
    parts = out.reshape(*lead, num_kv_heads // f, f, hq // num_kv_heads, f,
                        hd)
    return jnp.stack([parts[..., j, :, j, :] for j in range(f)],
                     axis=-3).reshape(*lead, hq, hd)


# ---------------------------------------------------------------------------
# latent paged decode: one token per slot over a pool of LATENT rows
# ---------------------------------------------------------------------------


def latent_lanes(width: int) -> int:
    """Lanes a latent pool stores a row of ``width`` elements in: whole
    128-lane tiles, the rest zeros. A TPU array's minor dimension is tiled
    by 128 lanes in HBM whatever its declared size (a [.., 576] pool IS
    [.., 640] there, and Mosaic refuses a copy of the 576), so the pad is
    what the row costs anyway; stated, the kernel can copy it."""
    return -(-width // 128) * 128


# bytes of the latent kernel's ring, and table entries a step of its walk.
# Every query head of a slot (64 at the cell's shape) attends a step's rows,
# so a step's fixed costs (the scalar core's copies and waits, the online
# softmax's rescale of a [heads, lanes] accumulator) are paid a step, not a
# row: on the chip (PR 48, us a layer at 32 slots x 33 k rows of 640 lanes,
# 1670 at the HBM's peak) four entries a step (256 rows: the paged kernel's
# 1 MiB and its cap of 8) ran 2845, eight 2135, eight three steps deep
# 1931, sixteen (1024 rows, a ring of 2.5 MiB) 1827
_LATENT_VMEM_BYTES = 4 << 20
_LATENT_STEP_BLOCKS_MAX = 16


def latent_decode_tiling(block_tokens: int, row_lanes: int, itemsize: int,
                         max_blocks: int,
                         num_buffers: int = _PAGED_NUM_BUFFERS
                         ) -> tuple[int, int, int]:
    """``paged_decode_tiling`` for a latent pool: one array, no kv-head
    axis, so a table entry's copy is ``[bt, row_lanes]`` and the ring holds
    it once (K and V are the same rows), under a budget of its own."""
    return paged_decode_tiling(
        1, block_tokens, row_lanes, itemsize, max_blocks, num_buffers,
        arrays=1, vmem_bytes=_LATENT_VMEM_BYTES,
        step_blocks_max=_LATENT_STEP_BLOCKS_MAX)


def _latent_decode_kernel(layer_ref, pos_ref, tbl_ref, q_ref, c_ref, new_ref,
                          o_ref, cout_ref, buf, sem, ring_ref, stage, wsem, *,
                          block_tokens: int, blocks: int, depth: int,
                          sm_scale: float, v_lanes: int, mm_dtype,
                          write_rows: int):
    # ``_paged_decode_kernel``'s walk over ONE array with no kv-head axis:
    # c_ref is the FULL stacked [L, N, bt, W] latent pool in HBM, a table
    # entry's row pool[layer, tbl[s, i]] one contiguous [bt, W] slab. Every
    # query head of the slot (q_ref [1, H, W]) scores the SAME rows over all
    # W lanes and takes its values from the first ``v_lanes`` of them: the
    # rows are read once a slot, not once a head. The kernel is the step's
    # writer as the paged kernel is over an unscaled pool: the slot's new
    # row (new_ref [1, 1, W]) is laid over position pos % bt of the frontier
    # block in the ring before its fold, and the aligned group of
    # ``write_rows`` rows around it goes back to pool[layer, frontier]. A
    # slot on the trash block (row 0) writes nothing back.
    s_idx = pl.program_id(0)
    n_slots = pl.num_programs(0)
    layer = layer_ref[0]
    bt, P = block_tokens, blocks
    T = P * bt

    def walk(slot):
        pos = pos_ref[slot]
        nb = jnp.minimum(pos // bt + 1, tbl_ref.shape[1])
        return pos, nb, (nb + P - 1) // P

    def copy(row, into, b):
        return pltpu.make_async_copy(c_ref.at[layer, row], buf.at[into],
                                     sem.at[b])

    def entries(slot, nb, t, b, do):
        for j in range(P):
            blk = t * P + j

            @pl.when(blk < nb)
            def _(j=j, blk=blk):
                do(tbl_ref[slot, blk], (b, pl.ds(j * bt, bt), slice(None)), b)

    def start(slot, nb, t, b):
        entries(slot, nb, t, b, lambda *c: copy(*c).start())

    pos, nb, steps = walk(s_idx)

    @pl.when(s_idx == 0)
    def _cold():
        # skipped entries leave a buffer's rows as they were: masked to
        # probability 0, which only a finite value multiplies to 0
        buf[...] = jnp.zeros(buf.shape, buf.dtype)
        ring_ref[0] = 0
        ring_ref[1] = 0         # no write-back in flight
        start(s_idx, nb, 0, 0)

    base = ring_ref[0]
    for j in range(1, depth - 1):
        @pl.when(j < steps)
        def _prime(j=j):
            start(s_idx, nb, j, lax.rem(base + j, depth))

    q = q_ref[0].astype(mm_dtype)                       # [H, W]
    H = q.shape[0]
    frontier = tbl_ref[s_idx, nb - 1]       # the block the position is in
    group = pl.multiple_of(pos % bt // write_rows * write_rows, write_rows)

    def write_back():
        return pltpu.make_async_copy(
            stage, cout_ref.at[layer, frontier, pl.ds(group, write_rows), :],
            wsem.at[0])

    def fold(i, carry, last=False):
        m, l, acc = carry
        b = lax.rem(base + i, depth)
        entries(s_idx, nb, i, b, lambda *c: copy(*c).wait())
        if last:
            # the staging buffer is the previous slot's until its copy has
            # left it: a whole program ago
            @pl.when(ring_ref[1] == 1)
            def _():
                write_back().wait()
            at = (b, pl.ds(pl.multiple_of(
                lax.rem(nb - 1, P) * bt + group, write_rows), write_rows),
                slice(None))
            hit = lax.broadcasted_iota(
                jnp.int32, (write_rows, 1), 0) == pos % bt - group
            rows = jnp.where(hit, new_ref[0], buf[at])
            buf[at] = rows

            @pl.when(frontier != 0)
            def _():
                stage[...] = rows
                write_back().start()
        k = buf[b].astype(mm_dtype)                     # [T, W]
        s = jnp.einsum("hd,td->ht", q, k,
                       preferred_element_type=jnp.float32) * sm_scale
        idx = i * T + lax.broadcasted_iota(jnp.int32, (1, T), 1)
        s = jnp.where(idx <= pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jnp.einsum(
            "ht,td->hd", p.astype(mm_dtype), k[:, :v_lanes],
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    def body(i, carry):
        @pl.when(i + depth - 1 < steps)
        def _prefetch():
            start(s_idx, nb, i + depth - 1,
                  lax.rem(base + i + depth - 1, depth))
        return fold(i, carry)

    m0 = jnp.full((H, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((H, 1), jnp.float32)
    acc0 = jnp.zeros((H, v_lanes), jnp.float32)
    carry = lax.fori_loop(0, steps - 1, body, (m0, l0, acc0))

    # the last fold: its buffer's successor is free, so the next slot's
    # first copies go there now
    after = lax.rem(base + steps, depth)

    @pl.when(s_idx + 1 < n_slots)
    def _next_slot():
        _, nb_n, _ = walk(s_idx + 1)
        start(s_idx + 1, nb_n, 0, after)

    ring_ref[0] = after
    _, l, acc = fold(steps - 1, carry, last=True)
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
    # the next slot waits for this copy before it stages its own; the
    # call's last program waits itself
    ring_ref[1] = (frontier != 0).astype(jnp.int32)

    @pl.when((s_idx + 1 == n_slots) & (frontier != 0))
    def _():
        write_back().wait()


def latent_decode_attention(
    q: jax.Array,            # [S, H, W]: a head's query over a latent row
    cache: jax.Array,        # [L, N, bt, lanes] stacked latent block pool:
                             # a row's W elements and zeros up to whole
                             # 128-lane tiles (``latent_lanes``)
    layer: jax.Array,        # scalar i32
    tables: jax.Array,       # [S, MB] i32
    positions: jax.Array,    # [S] i32: the step's write position
    new: jax.Array,          # [S, W]: the step's rows, for the kernel to WRITE
    *,
    v_lanes: int,            # leading lanes of a row that are its VALUE
    sm_scale: float,
    interpret: bool = False,
    num_buffers: int = _PAGED_NUM_BUFFERS,
):
    """Flash decode attention of H query heads over ONE latent row a token
    (latent attention in its absorbed form: models.deepseek), through the
    block tables: scores over all W lanes of a row, values its first
    ``v_lanes``. Returns ``(out [S, H, v_lanes], cache)``, the pool aliased
    to its argument and holding row ``s`` at ``[layer, tables[s,
    positions[s] // bt], positions[s] % bt]``, trash block aside: the
    kernel writes the step as ``paged_decode_attention`` does with
    ``k_new`` (the pool arrives as it was BEFORE the step). The walk, the
    ring and the slot-to-slot prefetch are that kernel's; the rows are
    copied once a slot for all heads."""
    S, H, _ = q.shape
    bt, W, MB = cache.shape[2], cache.shape[3], tables.shape[1]
    # the pad lanes are zeros in q as in the pool: they add 0 to a score
    q = jnp.pad(q, ((0, 0), (0, 0), (0, W - q.shape[2])))
    new = jnp.pad(new, ((0, 0), (0, W - new.shape[1])))
    write_rows = 32 // cache.dtype.itemsize
    if bt % write_rows:
        write_rows = bt
    P, depth, _ = latent_decode_tiling(bt, W, cache.dtype.itemsize, MB,
                                       num_buffers)
    exact = q.dtype == jnp.bfloat16 and cache.dtype == jnp.bfloat16
    kernel = functools.partial(
        _latent_decode_kernel, block_tokens=bt, blocks=P, depth=depth,
        sm_scale=float(sm_scale), v_lanes=v_lanes,
        mm_dtype=jnp.bfloat16 if exact else jnp.float32,
        write_rows=write_rows)
    layer = jnp.reshape(layer, (1,)).astype(jnp.int32)
    out, pool = pl.pallas_call(
        kernel,
        name="latent_decode_attn",
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1,), lambda s: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((S,), lambda s: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((S, MB), lambda s: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((1, H, W), lambda s: (s, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),      # the pool, whole, in HBM
            pl.BlockSpec((1, 1, W), lambda s: (s, 0, 0)),
        ],
        out_specs=[pl.BlockSpec((1, H, v_lanes), lambda s: (s, 0, 0)),
                   pl.BlockSpec(memory_space=pl.ANY)],
        out_shape=[jax.ShapeDtypeStruct((S, H, v_lanes), q.dtype),
                   jax.ShapeDtypeStruct(cache.shape, cache.dtype)],
        input_output_aliases={4: 1},    # the pool: written where it lies
        scratch_shapes=[
            pltpu.VMEM((depth, P * bt, W), cache.dtype),
            pltpu.SemaphoreType.DMA((depth,)),
            pltpu.SMEM((2,), jnp.int32),
            pltpu.VMEM((write_rows, W), cache.dtype),
            pltpu.SemaphoreType.DMA((1,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(layer, positions.astype(jnp.int32), tables.astype(jnp.int32), q,
      cache, new.astype(cache.dtype)[:, None, :])
    return out, pool


def latent_decode_attention_ref(q, cache, tables, positions, *,
                                v_lanes: int, sm_scale: float):
    """Pure-lax latent decode attention over ONE layer's pool [N, bt, W]
    that already holds the step's rows (gather + masked softmax): the
    numerical reference of the kernel. Returns [S, H, v_lanes]."""
    S, MB = tables.shape
    bt, W = cache.shape[1], cache.shape[2]
    rows = cache[tables].reshape(S, MB * bt, W).astype(jnp.float32)
    scores = jnp.einsum("shd,sld->shl", q.astype(jnp.float32),
                        rows) * sm_scale
    keep = jnp.arange(MB * bt)[None, None, :] <= positions[:, None, None]
    probs = jax.nn.softmax(jnp.where(keep, scores, _NEG_INF), axis=-1)
    return jnp.einsum("shl,sld->shd", probs,
                      rows[..., :v_lanes]).astype(q.dtype)
