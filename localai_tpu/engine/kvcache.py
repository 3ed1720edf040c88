"""Slot-resident KV cache in HBM.

Replaces llama.cpp's per-slot KV management (kv_cache_clear / cache_tokens /
n_ctx-per-slot partitioning, /root/reference/backend/cpp/llama/
grpc-server.cpp:176,906,1546-1990) with a TPU-native layout: one statically
shaped tensor pair per model, stacked over layers, sliced per slot by
masking — never by ragged mutation.

Layout: k,v each [cache layers, num_slots, num_kv_heads, max_ctx, head_dim]
(paged: [cache layers, num_blocks, num_kv_heads, block_tokens, head_dim]);
cache layers = ``LlamaConfig.cache_layers``: the model's layers, times its
passes where the stack runs several times a token (pass-major).
Heads lead the context dim so the last two axes are (context, head_dim) —
the (sublane, lane) tiling Mosaic requires for the flash kernels' per-head
HBM→VMEM DMA slices (ops.attention), and a contiguous stream per head.

All updates are functional, and compiled they are in place. The stack is
the CARRY of the layer scan (models.llama.forward), donated by the runner's
jits. One contract for every write policy below:

    kv_write(kv_stack, layer, k_new, v_new) -> (new_kv_stack, keys, values)

``kv_stack`` is the whole stacked pytree (``stacked()``), ``layer`` the
i32 CACHE layer (the scan's layer index, plus pass x layers in a looped
model). A policy scatters ONLY the new rows into the stack
(``_write_rows``, ``_write_run``, ``_write_chunk``) and hands the attend
what it reads: the blocks or rows gathered straight from the 5-D array,
or a ``LayerView`` of the stack for an attend that picks its own reads: a
Pallas kernel that indexes the layer itself (``raw=True``), the paged
chunk's ``span_attend``, which gathers the span of the table row its
``offset`` asks for (PERF.md, PR 40). Nothing slices a layer out of the
stack to update it or to pass it on: such a slice, its re-layout and the
restack cost more than the rest of a decode step together (PERF.md, PR 26).

One policy leaves the write to its attend: ``paged_decode_write(raw=True)``
over an unscaled pool hands the stack back UNTOUCHED, its views carry the
step's rows (``LayerView.new``), and the paged kernel, which has the very
block the row belongs to in VMEM, lays it there and copies it back
(``kernel_attend``; ``models.llama.forward`` takes the stack an attend
hands back beside its output). A scatter's cost is its windows', ~100 ns
each whatever their bytes, S x H x 2 of them a layer (PERF.md, PR 38).
tests/test_tpu_compile.py compiles the runner's programs for a described
v5e and holds them to no cache-sized temp and no layer-shaped copy.

Which policy, attend and mask a program gets is a LAYOUT's to say
(``PagedLayout``, ``ContiguousLayout``, ``LatentLayout`` at the end of this
file): the runner builds one and runs one family of programs over it.

A LATENT pool (``LatentKVCache``; latent attention, models.deepseek) is the
block pool with another page: ONE array ``[cache layers, num_blocks,
block_tokens, lanes]``, a token's row its compressed latent and its one
shared rope key, no kv-head axis, keys and values the same rows. Allocator,
block tables and prefix sharing are the block pool's; its write policies
(``latent_decode_write``, ``latent_prefill_write``) keep the contract above
with ONE new-rows argument and one view (``LatentView``).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from localai_tpu import ops
from localai_tpu.models.llama import LlamaConfig, _grouped_attn
from localai_tpu.models.quant import (
    quantize_lastdim as _quant_chunk,
    quantize_lastdim4 as _quant_chunk4,
    unpack_int4_lastdim as _unpack4,
)
from localai_tpu.obs.profiler import scoped
from localai_tpu.ops.attention import (gather_block_scales, gather_blocks,
                                       latent_lanes)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    """k, v: [L, S, Hkv, C, hd]. When the cache dtype is int8, k/v hold
    symmetric per-(slot, head, position) quantized values and
    k_scale/v_scale hold the f32 scales [L, S, Hkv, C] — honest scaled
    int8, not a raw dtype cast (the scale adds hd⁻¹·4 bytes/elem ≈ 1.5%
    overhead against a 2× KV memory saving)."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @property
    def num_slots(self) -> int:
        return self.k.shape[1]

    @property
    def max_ctx(self) -> int:
        return self.k.shape[3]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def stacked(self):
        """The pytree models.llama.forward carries through its layer scan
        and the write policies update in place."""
        if self.k_scale is None:
            return (self.k, self.v)
        return (self.k, self.v, self.k_scale, self.v_scale)

    @staticmethod
    def from_stacked(t) -> "KVCache":
        return KVCache(*t)


def init_cache(
    cfg: LlamaConfig,
    num_slots: int,
    max_ctx: int,
    dtype: str = "bfloat16",
    sharding: Optional[jax.sharding.Sharding] = None,
) -> KVCache:
    shape = (cfg.cache_layers, num_slots, cfg.num_kv_heads, max_ctx, cfg.hd)
    dt = jnp.dtype(dtype)

    def zeros(shp, d, shd):
        if shd is not None:
            # one-shot jit is the idiom for allocating directly into a
            # sharded layout (device_put of a host zeros array would
            # materialize the full cache on one device first); init-time
            # only, so the throwaway compile cache is fine
            return jax.jit(  # jaxlint: disable=jit-in-loop
                lambda: jnp.zeros(shp, d), out_shardings=shd
            )()
        return jnp.zeros(shp, d)

    scale_sharding = None
    if dt == jnp.int8 and sharding is not None:
        # scales drop the head_dim axis; reuse the kv spec minus its last entry
        spec = sharding.spec
        scale_sharding = NamedSharding(sharding.mesh, P(*tuple(spec)[:4]))
    if dt == jnp.int8:
        return KVCache(
            k=zeros(shape, dt, sharding),
            v=zeros(shape, dt, sharding),
            k_scale=zeros(shape[:4], jnp.float32, scale_sharding),
            v_scale=zeros(shape[:4], jnp.float32, scale_sharding),
        )
    return KVCache(k=zeros(shape, dt, sharding), v=zeros(shape, dt, sharding))




# ---------------------------------------------------------------------------
# paged layout (vLLM-style block pool; host bookkeeping in engine.paged)
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PagedKVCache:
    """k, v: [L, N, Hkv, bt, hd] — one pool of N physical blocks of bt
    tokens each, shared by all slots through per-slot block tables
    ([S, max_blocks] i32, engine.paged.BlockAllocator). Block 0 is the
    trash block (garbage-write target for inactive slots). int8 caches
    carry f32 scales [L, N, Hkv, bt], same scaled-int8 scheme as KVCache.
    int4 pools store nibble-packed int8 with last dim hd/2 (halves layout,
    models.quant.quantize_lastdim4) and the SAME scale shape — the packed
    last dim is how every consumer detects int4, so the pool stays
    self-describing through the stacked pytree."""

    k: jax.Array
    v: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_tokens(self) -> int:
        return self.k.shape[3]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    def stacked(self):
        if self.k_scale is None:
            return (self.k, self.v)
        return (self.k, self.v, self.k_scale, self.v_scale)

    @staticmethod
    def from_stacked(t) -> "PagedKVCache":
        return PagedKVCache(*t)


def init_paged_cache(
    cfg: LlamaConfig,
    num_blocks: int,
    block_tokens: int,
    dtype: str = "bfloat16",
    sharding: Optional[jax.sharding.Sharding] = None,
) -> PagedKVCache:
    int4 = str(dtype) == "int4"
    if int4 and cfg.hd % 2:
        raise ValueError(f"int4 KV needs an even head_dim, got {cfg.hd}")
    # int4 pools store nibble-packed int8 along head_dim (hd/2 bytes/row)
    hd = cfg.hd // 2 if int4 else cfg.hd
    shape = (cfg.cache_layers, num_blocks, cfg.num_kv_heads, block_tokens, hd)
    dt = jnp.dtype("int8") if int4 else jnp.dtype(dtype)
    quantized = int4 or dt == jnp.int8

    def zeros(shp, d, shd):
        if shd is not None:
            # allocate straight into the sharded layout (same idiom as
            # init_cache: a host zeros array would materialize the whole
            # pool on one device first); init-time only
            return jax.jit(  # jaxlint: disable=jit-in-loop
                lambda: jnp.zeros(shp, d), out_shardings=shd
            )()
        return jnp.zeros(shp, d)

    scale_sharding = None
    if quantized and sharding is not None:
        # scale pool drops the head_dim axis; reuse the pool spec minus it
        scale_sharding = NamedSharding(
            sharding.mesh, P(*tuple(sharding.spec)[:4]))
    if quantized:
        return PagedKVCache(
            k=zeros(shape, dt, sharding),
            v=zeros(shape, dt, sharding),
            k_scale=zeros(shape[:4], jnp.float32, scale_sharding),
            v_scale=zeros(shape[:4], jnp.float32, scale_sharding),
        )
    return PagedKVCache(k=zeros(shape, dt, sharding),
                        v=zeros(shape, dt, sharding))


class LayerView(NamedTuple):
    """What a ``raw=True`` policy hands a Pallas decode kernel (and
    ``paged_prefill_write`` its ``span_attend``) in place of keys (and of
    values): the WHOLE stacked cache, the layer to read, and the stacked
    scales of a quantized cache. The kernel picks the layer in its DMA
    slice (ops.attention), the span attend in its gather, so no per-layer
    slice ever exists.
    ``new``: the step's rows in the cache's dtype, where the policy has NOT
    stored them and the kernel is to."""

    cache: jax.Array                    # [L, ...] all layers
    layer: jax.Array                    # scalar i32
    scale: Optional[jax.Array] = None   # [L, ...] f32, quantized caches
    new: Optional[jax.Array] = None     # [S, H, hd], for the kernel to write


def _is_int4(kv_stack, k_new) -> bool:
    """A paged pool is self-describing: int4 when its last dim is the
    packed hd/2 (``k_new`` carries the full head_dim)."""
    return len(kv_stack) == 4 and kv_stack[0].shape[-1] * 2 == k_new.shape[-1]


def _write(kv_stack, k_new, v_new, put):
    """The new stack, ``put(leaf, rows)`` applied to every leaf of
    ``kv_stack`` with the rows that leaf stores for ``k_new``/``v_new
    [..., hd]``: the rows in the cache's dtype, or, for a scaled int8/int4
    cache (a 4-tuple), the quantized rows and their ``[...]`` scale rows."""
    with jax.named_scope("kv_pool.write"):
        stored = (k_new, v_new)
        if len(kv_stack) == 4:
            quant = _quant_chunk4 if _is_int4(kv_stack, k_new) else _quant_chunk
            (kq, kscale), (vq, vscale) = quant(k_new), quant(v_new)
            stored = (kq, vq, kscale, vscale)
        return tuple(put(leaf, rows.astype(leaf.dtype))
                     for leaf, rows in zip(kv_stack, stored))


def _scatter_per_head(cache, idx, rows):
    """``cache[i0, i1, h(, i2)] = rows[..., h]`` for every head ``h``:
    ``cache [L, N, H, ...]``; ``idx`` the layer, the block or slot and,
    when rows and not blocks are written, the token, broadcast to one shape
    ``[...]``; ``rows [..., H, *window]``, the window being the cache's
    remaining axes.

    The heads are part of the INDEX, not of the update window, and their
    index is an iota the compiler can read, laid among the others by hand.
    The first keeps the stack row-major and the write in place (see
    ``_write_rows``). The second keeps the write on its own chip under a
    tensor-parallel mesh, where cache and rows are sharded over the heads:
    the partitioner proves from the iota that every shard writes its own
    heads only. Through ``.at[i0, i1, arange(H), i2]`` the CPU pipeline does
    not see it and all-gathers the rows and their indices, a collective a
    layer for K and for V; libtpu's happens to (tests/test_kv_contract.py
    ``test_policy_is_shard_local_over_the_heads``, tests/test_tpu_compile.py
    ``test_cell_programs_write_their_own_heads_on_a_tp4_mesh``)."""
    idx = jnp.broadcast_arrays(*idx)
    nb = idx[0].ndim
    shape = (*idx[0].shape, cache.shape[2], 1)
    cols = [jnp.broadcast_to(i.astype(jnp.int32)[..., None, None], shape)
            for i in idx]
    cols.insert(2, lax.broadcasted_iota(jnp.int32, shape, nb))
    into = tuple(range(len(cols)))
    dnums = lax.ScatterDimensionNumbers(
        update_window_dims=tuple(range(nb + 1, rows.ndim)),
        inserted_window_dims=into, scatter_dims_to_operand_dims=into)
    return lax.scatter(cache, lax.concatenate(cols, nb + 1), rows, dnums)


def _write_rows(kv_stack, layer, blk, off, k_new, v_new):
    """THE row writer of the scatter policies: ``k_new``/``v_new
    [..., H, hd]`` land at ``[layer, blk, :, off]`` of the stacked cache,
    ``blk``/``off`` shaped ``[...]``, everything else untouched.

    The heads are part of the INDEX (``_scatter_per_head``), not of the
    update window. With the window over (H, hd) and the token axis between
    them, XLA lays the scatter's operand out tokens-outside-heads, the
    Pallas kernels (and the row-major parameter) ask for
    heads-outside-tokens, and the difference is a layout copy of the whole
    stack per layer. With the window over hd alone the scatter keeps the
    row-major layout and updates the carried stack in place
    (tests/test_tpu_compile.py
    ``test_cell_programs_write_the_pool_in_place``)."""

    def put(cache, rows):
        return _scatter_per_head(cache, (layer, blk, off), rows)

    return _write(kv_stack, k_new, v_new, put)


def _run_blocks(table_row, offset, length, T: int, bt: int):
    """The blocks a run of ``T`` rows (the first ``length`` real) at
    positions ``[offset, offset + length)`` touches: (how many at most,
    which of their rows are the run's ``[nblk, bt]``, their pool ids
    ``[nblk]``: the trash block for one the run does not reach)."""
    MB = table_row.shape[0]
    nblk = -(-T // bt) + 1
    first = offset // bt
    pos = first * bt + jnp.arange(nblk * bt)
    real = ((pos >= offset) & (pos < offset + length)).reshape(nblk, bt)
    j = first + jnp.arange(nblk)
    ids = jnp.where((j < MB) & real.any(axis=1),
                    table_row[jnp.minimum(j, MB - 1)], 0)
    return nblk, real, ids


def _write_run(kv_stack, layer, table_row, offset, length, k_new, v_new):
    """Scatter one sequence's run of consecutive positions
    ``[offset, offset + length)``, rows ``k_new``/``v_new [T, H, hd]`` (the
    first ``length`` real), into the stacked pool through ``table_row``
    — a block at a time. A run of T tokens touches at most T/bt + 1 blocks:
    they are gathered, the new rows laid over them, and scattered back
    with the whole ``[bt, hd]`` block of one head as the update window:
    (T/bt + 1) x H windows a layer where a window per row and head is
    T x H, and XLA's TPU scatter walks its windows one by one (18 ms of an
    82 ms chunk of 512 at Mistral-7B against 1 ms, PERF.md PR 26). Rows
    outside the run keep what the blocks held; blocks the run does not
    reach are written back unchanged, to the trash block."""
    bt = kv_stack[0].shape[3]
    nblk, real, ids = _run_blocks(table_row, offset, length,
                                  k_new.shape[0], bt)

    def put(cache, rows):       # rows [T, H, hd] or, for scales, [T, H]
        frame = lax.dynamic_update_slice(
            jnp.zeros((nblk * bt, *rows.shape[1:]), rows.dtype), rows,
            (offset % bt,) + (0,) * (rows.ndim - 1))
        frame = jnp.moveaxis(                  # [nblk, H, bt(, hd)]
            frame.reshape(nblk, bt, *rows.shape[1:]), 1, 2)
        keep = real.reshape(nblk, 1, bt, *(1,) * (rows.ndim - 2))
        merged = jnp.where(keep, frame, cache[layer, ids])
        return _scatter_per_head(cache, (layer, ids), merged)

    return _write(kv_stack, k_new, v_new, put)


def _pairs(kv_stack):
    """((k, k_scale), (v, v_scale)) of a stacked cache; the scales are None
    unless it is scaled int8/int4 (a 4-tuple)."""
    if len(kv_stack) == 4:
        k, v, ks, vs = kv_stack
        return (k, ks), (v, vs)
    k, v = kv_stack
    return (k, None), (v, None)


def _views(kv_stack, layer):
    """(keys, values) for the ``raw=True`` policies."""
    return tuple(LayerView(cache, layer, scale)
                 for cache, scale in _pairs(kv_stack))


def _stacked(keys: LayerView, values: LayerView):
    """The stacked cache ``_views`` was given, from its two views."""
    if keys.scale is None:
        return (keys.cache, values.cache)
    return (keys.cache, values.cache, keys.scale, values.scale)


def _gather_context(kv_stack, layer, tables, k_new):
    """(keys, values) ``[S, H, MB*bt, hd]`` in ``k_new``'s dtype for the
    XLA attend over a block pool: the blocks the tables name, gathered
    straight from the stacked pool (layer and blocks in ONE gather),
    dequantized when the pool is scaled int8/int4."""
    dt, int4 = k_new.dtype, _is_int4(kv_stack, k_new)

    def one(cache, scales):
        g = gather_blocks(cache, tables, layer)
        if scales is None:
            return g.astype(dt)
        if int4:
            g = _unpack4(g)
        return (g.astype(dt)
                * gather_block_scales(scales, tables, layer)[..., None]
                .astype(dt))

    with jax.named_scope("kv_pool.gather"):
        return tuple(one(cache, scales) for cache, scales in _pairs(kv_stack))


def paged_decode_write(tables: jax.Array, positions: jax.Array,
                       raw: bool = False):
    """KV write policy for batched single-token decode over a block pool.

    tables: [S, MB] i32 block tables, positions: [S]. Writes k/v_new
    [S, 1, H, hd] at pool[layer, tables[s, pos//bt], :, pos%bt]. Released
    slots' table rows are all-zeros, so their (static-shape-mandated)
    garbage writes land in the trash block.

    ``raw=False`` exposes the gathered logical context [S, H, MB*bt, hd]
    for the XLA attend; ``raw=True`` hands the Pallas paged kernel (which
    walks the tables itself) a :class:`LayerView` of the stack. Over an
    unscaled pool (a 2-tuple stack) that kernel is the writer too: the
    stack goes back untouched and the views carry the rows, cast to what
    the pool stores. A scaled pool's f32 scale row ``[.., bt]`` is narrower
    than a DMA tile: it keeps the scatter, as the XLA attend does."""

    def write(kv_stack, layer, k_new, v_new):
        if raw and len(kv_stack) == 2:
            with jax.named_scope("kv_pool.write"):
                return (kv_stack, *(
                    LayerView(cache, layer, None, new[:, 0].astype(cache.dtype))
                    for cache, new in zip(kv_stack, (k_new, v_new))))
        bt = kv_stack[0].shape[3]
        s = jnp.arange(tables.shape[0])
        blk = tables[s, positions // bt]          # [S]
        new = _write_rows(kv_stack, layer, blk, positions % bt,
                          k_new[:, 0], v_new[:, 0])
        if raw:
            return (new, *_views(new, layer))
        return (new, *_gather_context(new, layer, tables, k_new))

    return write


def kernel_attend(kernel, tables: jax.Array, positions: jax.Array):
    """The ``attn`` of ``models.llama.forward`` over the views
    ``paged_decode_write(raw=True)`` hands out: ``kernel`` is
    ``ops.paged_decode_attention`` (under ``shard_map`` or not) by position,
    scales and new rows last, None where the views hold none. Where they
    carry the rows, the kernel wrote them and the stack it returns goes
    back beside the output."""

    def attn(q, keys, values, _mask):   # q [S, 1, Hq, hd]
        got = kernel(q[:, 0], keys.cache, values.cache, keys.layer, tables,
                     positions, keys.scale, values.scale, keys.new,
                     values.new)
        if keys.new is None:
            return got[:, None]
        out, *pools = got
        return out[:, None], tuple(pools)

    return attn


def span_ladder(bucket: int, ctx_pad: int, block_tokens: int) -> tuple[int, ...]:
    """The spans a ``bucket``-token chunk's attend may take: multiples of
    ``block_tokens`` that double from 512 (the bucket where that is larger)
    and end at ``ctx_pad`` (289 blocks of 64 on one chip: 512, 1024, 2048,
    4096). Short and static: each rung is one branch of every prefill
    program (``span_attend``)."""
    c = -(-max(512, bucket) // block_tokens) * block_tokens
    rungs = []
    while c < ctx_pad:
        rungs.append(c)
        c *= 2
    return (*rungs, ctx_pad)


def attend_rung(need, rungs: tuple[int, ...]):
    """Index of the smallest of ``rungs`` (a ``span_ladder``) that covers
    ``need`` = ``offset + bucket`` positions (the last where none does: a
    chunk never attends past ``ctx_pad``). ``need`` is the program's traced
    scalar or the host's integer: ONE expression serves both, so the span
    the ring row states is the span the device took."""
    return sum((need > c) * 1 for c in rungs[:-1])


def attend_span(offset: int, bucket: int, ctx_pad: int,
                block_tokens: int) -> int:
    """Positions the attend of a ``bucket``-token chunk behind ``offset``
    cached tokens spans (host integers; the flight ring's ``chunk_ctx``)."""
    rungs = span_ladder(bucket, ctx_pad, block_tokens)
    return rungs[attend_rung(offset + bucket, rungs)]


def paged_prefill_write(table_row: jax.Array, offset: jax.Array,
                        length: jax.Array):
    """KV write policy for one chunked-prefill dispatch into a block table.

    table_row: [MB] i32, offset: absolute start position of this chunk,
    length: real (unpadded) tokens in the chunk. Token t of the chunk
    lands at pool[layer, table_row[(offset+t)//bt], :, (offset+t)%bt];
    padding rows (t >= length) are written nowhere, so a padded bucket can
    never clobber the sequence's own reserved blocks (``_write_run``).
    Hands ``span_attend`` a :class:`LayerView` of the written stack: the
    attend gathers the span of the table row the chunk needs (the kept
    prefix + earlier chunks + itself, resume-style) and no more."""

    def write(kv_stack, layer, k_new, v_new):  # k_new [1, T, H, hd]
        new = _write_run(kv_stack, layer, table_row, offset, length,
                         k_new[0], v_new[0])
        return (new, *_views(new, layer))

    return write


def span_attend(cfg: LlamaConfig, table_row: jax.Array, offset: jax.Array,
                ctx_pad: int):
    """The ``attn`` of ``models.llama.forward`` over the views
    ``paged_prefill_write`` hands out: a chunk attends the prefix
    it has, not the width of the pool. ``lax.switch`` on the rung
    ``attend_rung`` picks from the traced ``offset``: branch ``c`` gathers
    the first ``c`` positions of the table row (``_gather_context``:
    layer and blocks in one gather, a scaled pool dequantised) and runs the
    grouped attend under ``mask[..., :c]`` (``resume_mask`` over
    ``ctx_pad``, sliced: a sliding window stays what it was). Every position
    it leaves out was masked for every row of the chunk and weighed
    ``exp(-1e30 - max) = 0``: a real row's result is the full span's up to
    the order of a float32 sum. One program a bucket, as before: the rungs
    are its branches. NOT cut, each as it was: the verify window
    (``paged_verify_write``: a slot its own prefix), the contiguous cache's
    resume (``resume_write``: the slot's whole row), the ring prefill (no
    pool gathered) and the pipeline-parallel forward (contiguous only)."""
    def attn(q, keys, values, mask):    # q [1, T, Hq, hd]; LayerViews
        bucket, bt = q.shape[1], keys.cache.shape[3]
        rungs = span_ladder(bucket, ctx_pad, bt)
        return lax.switch(attend_rung(offset + bucket, rungs),
                          [_rung(cfg, table_row, keys.layer, c // bt,
                                 "attn.prefill") for c in rungs],
                          q, _stacked(keys, values), mask)

    return attn


def _rung(cfg, table_row, layer, blocks: int, scope: str):
    """One branch of a chunk's attend: the first ``blocks`` entries of the
    table row gathered, the grouped attend under the mask sliced to them."""
    def run(q, stack, mask):
        k, v = _gather_context(stack, layer, table_row[None, :blocks], q)
        with jax.named_scope(scope):
            return _grouped_attn(cfg, q, k, v,
                                 mask[..., :blocks * stack[0].shape[3]])
    return run


@dataclasses.dataclass(frozen=True)
class KindView:
    """What the masks and attends here read of a config, for ONE kind of
    attention layer of a stack that has several (``LlamaConfig.attn_kinds``:
    the runner builds a mask and an attend a kind): the head size, and the
    kind's window (None: it sees every key)."""

    hd: int
    sliding_window: Optional[int]


def window_span(window: int, bucket: int, block_tokens: int) -> int:
    """Positions a ``bucket``-token chunk of a WINDOW layer gathers once its
    prefix is longer than the window: ``window + bucket`` in whole blocks,
    and one block more for a start inside a block."""
    return (-(-(window + bucket) // block_tokens) + 1) * block_tokens


def window_attend(view: KindView, table_row: jax.Array, offset: jax.Array,
                  ctx_pad: int):
    """``span_attend`` for a layer whose queries see ``view.sliding_window``
    keys: the chunk attends ITS WINDOW of the prefix, not the prefix. One
    more branch shape beside the ladder's: the rungs shorter than
    ``window_span`` serve a short prefix as they do everywhere (the mask,
    sliced, holds the window), and past them ONE branch gathers
    ``window_span`` positions from table entry ``(offset - window + 1) //
    bt`` on (a dynamic slice of the table row, a static width; clamped to the
    row) under the window's mask over the positions it gathered. A key it
    leaves out lies outside every row's window. Where the window spans the
    context (``window_span >= ctx_pad``) this is ``span_attend``."""
    window = view.sliding_window

    def attn(q, keys, values, mask):    # q [1, T, Hq, hd]; LayerViews
        bucket, bt = q.shape[1], keys.cache.shape[3]
        span = window_span(window, bucket, bt)
        if span >= ctx_pad:
            return span_attend(view, table_row, offset, ctx_pad)(
                q, keys, values, mask)
        rungs = tuple(c for c in span_ladder(bucket, ctx_pad, bt)
                      if c < span)

        def in_window(q, stack, _mask):
            nb = span // bt
            first = jnp.clip((offset - window + 1) // bt, 0,
                             table_row.shape[0] - nb)
            k, v = _gather_context(
                stack, keys.layer,
                lax.dynamic_slice(table_row, (first,), (nb,))[None], q)
            kpos = first * bt + jnp.arange(span)[None, None, :]
            qpos = offset + jnp.arange(bucket)[None, :, None]
            with jax.named_scope("attn.prefill_window"):
                return _grouped_attn(
                    view, q, k, v, (kpos <= qpos) & (kpos > qpos - window))

        return lax.switch(attend_rung(offset + bucket, (*rungs, span)),
                          [*(_rung(view, table_row, keys.layer, c // bt,
                                   "attn.prefill_window") for c in rungs),
                           in_window],
                          q, _stacked(keys, values), mask)

    return attn


# ---------------------------------------------------------------------------
# attends over a SELECTION of a stream's blocks (``LlamaConfig.select_blocks``)
# ---------------------------------------------------------------------------
#
# A family whose queries attend the best blocks of their context hands its
# attend the selection (``select=``): which blocks is the model's, how the
# pool is read under them is the layout's. The selection is one a (stream,
# K/V head), so the pool is read as a pool of ONE-head blocks, ``[L, N Hkv,
# 1, bt, hd]`` (the same bytes: N and Hkv are neighbours), whose block ``b
# Hkv + g`` is head g of block b, and a (stream, head)'s selected blocks are
# a short block table of its own: the decode attend over it is the attend
# every model runs, kernel or XLA, with rows = (stream, K/V head).

def _one_head(cache):
    """``[L, N, Hkv, bt, hd]`` as ``[L, N Hkv, 1, bt, hd]``."""
    return cache.reshape(cache.shape[0], -1, 1, *cache.shape[3:])


def select_tables(tables, positions, select, heads: int, bt: int):
    """(tables ``[S Hkv, W]``, positions ``[S Hkv]``) of the one-head pool
    for ``select`` = (the logical blocks a (stream, head) attends ``[S, Hkv,
    W]``, ascending, the stream's own block the LAST of the first ``n``; n
    ``[S, Hkv]``). A row's position is its token's place in the COMPACTED
    context: every block before the last is whole. A stream on the trash
    block stays there, whatever its head."""
    blocks, n = select
    # the table's entries at the selected places, as a one-hot sum: a gather
    # of a few thousand scattered words is a serial walk on this chip (82 us
    # a layer against ~10: PERF.md section 6, PR 62)
    at = blocks[..., None] == jnp.arange(tables.shape[1], dtype=blocks.dtype)
    phys = jnp.sum(jnp.where(at, tables[:, None, None, :], 0), axis=-1)
    rows = jnp.where(phys == 0, 0,
                     phys * heads + jnp.arange(heads)[None, :, None])
    pos = (n - 1) * bt + positions[:, None] % bt
    return rows.reshape(-1, rows.shape[-1]), pos.reshape(-1)


def select_decode(cfg: LlamaConfig, kernel, tables: jax.Array,
                  positions: jax.Array):
    """(write policy, attend) of a decode step whose streams attend a
    selection of their blocks. ``kernel`` (``ops.paged_decode_attention``,
    or None for XLA) runs over the compacted tables; where it is given it
    stores the step's rows too, as for every model (the stream's own block
    is the last of its table); else the policy scatters them."""
    def write(kv_stack, layer, k_new, v_new):
        if kernel is not None:
            return paged_decode_write(tables, positions, raw=True)(
                kv_stack, layer, k_new, v_new)
        bt = kv_stack[0].shape[3]
        blk = tables[jnp.arange(tables.shape[0]), positions // bt]
        new = _write_rows(kv_stack, layer, blk, positions % bt, k_new[:, 0],
                          v_new[:, 0])
        return (new, *_views(new, layer))

    def attn(q, keys, values, _mask, *, select):    # q [S, 1, Hq, hd]
        S, _, Hq, hd = q.shape
        G, bt = keys.cache.shape[2], keys.cache.shape[3]
        rows, pos = select_tables(tables, positions, select, G, bt)
        qr = q[:, 0].reshape(S * G, Hq // G, hd)
        if kernel is None:
            k, v = _gather_context(
                (_one_head(keys.cache), _one_head(values.cache)),
                keys.layer, rows, q)
            with jax.named_scope("attn.select_decode"):
                keep = jnp.arange(k.shape[2])[None, None, :] <= pos[:, None,
                                                                    None]
                out = _grouped_attn(cfg, qr[:, None], k, v, keep)
            return out.reshape(S, 1, Hq, hd)
        with jax.named_scope("attn.select_decode"):
            out, *pools = kernel(
                qr, _one_head(keys.cache), _one_head(values.cache),
                keys.layer, rows, pos, None, None,
                keys.new.reshape(S * G, 1, hd),
                values.new.reshape(S * G, 1, hd))
        return out.reshape(S, 1, Hq, hd), tuple(
            pool.reshape(cache.shape)
            for pool, cache in zip(pools, (keys.cache, values.cache)))

    return write, attn


# query rows of a selecting chunk that are attended at once (their scores
# over the whole span are float32: 64 rows of 32 heads over 32768 keys are
# 0.25 GiB)
SELECT_ROWS = 64


def select_span_attend(cfg: LlamaConfig, table_row: jax.Array,
                       offset: jax.Array, ctx_pad: int):
    """``span_attend`` for a chunk whose rows attend a selection of the
    prefix's blocks: ``select`` ``[T, Hkv, blocks]`` says which blocks each
    (row, K/V head) attends (all of them for a row that does not select),
    and a row attends the keys of those at or before its own position. The
    span is the ladder's rung, as everywhere; the mask is the rule's own
    (the caller's says nothing of heads)."""
    def attn(q, keys, values, _mask, *, select):    # q [1, T, Hq, hd]
        T, bt = q.shape[1], keys.cache.shape[3]
        G = keys.cache.shape[2]
        rungs = span_ladder(T, ctx_pad, bt)
        # tiles of at most SELECT_ROWS rows, and at least eight of them: a
        # loop of two is unrolled, and both tiles' scores are then live
        rows = math.gcd(T, min(SELECT_ROWS, max(8, T // 8)))

        def rung(c: int):
            def run(q, stack, keep):
                k, v = _gather_context(stack, keys.layer,
                                       table_row[None, :c // bt], q)
                kpos = jnp.arange(c)

                def tile(args):
                    qt, keep_t, qpos = args     # [rows, ...]
                    seen = jnp.repeat(keep_t[:, :, :c // bt], bt, axis=2) & (
                        kpos[None, None, :] <= qpos[:, None, None])
                    qg = qt.reshape(rows, G, -1, qt.shape[-1])
                    s = jnp.einsum("tkgh,klh->kgtl", qg, k[0]).astype(
                        jnp.float32) / math.sqrt(cfg.hd)
                    s = jnp.where(jnp.moveaxis(seen, 0, 1)[:, None], s, -1e30)
                    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
                    return jnp.einsum("kgtl,klh->tkgh", p, v[0]).reshape(
                        qt.shape)

                with jax.named_scope("attn.select_chunk"):
                    out = lax.map(tile, (
                        q[0].reshape(T // rows, rows, *q.shape[2:]),
                        keep.reshape(T // rows, rows, *keep.shape[1:]),
                        (offset + jnp.arange(T)).reshape(T // rows, rows)))
                return out.reshape(q.shape)
            return run

        return lax.switch(attend_rung(offset + T, rungs),
                          [rung(c) for c in rungs],
                          q, _stacked(keys, values), select)

    return attn


def paged_verify_write(tables: jax.Array, positions: jax.Array,
                       ctx_limit: int):
    """KV write policy for the batched speculative verify forward over a
    block pool — ``paged_decode_write`` generalized to T tokens per slot.

    Window token t of slot s lands at
    ``pool[layer, tables[s, (positions[s]+t)//bt], :, (positions[s]+t)%bt]``.
    Rows at or past ``ctx_limit`` (the runner's max_ctx) redirect to the
    trash block: near the context edge a window row beyond the last real
    position must never wrap onto the slot's own earlier rows via the
    clamped block index. Inactive/mid-prefill slots' device table rows
    are all-zeros, so their static-shape writes land in trash exactly
    like decode. Exposes the gathered logical context [S, H, MB*bt, hd]
    so window tokens attend over the prefix + the window so far.

    Rollback is a per-slot position rollback only: the rejected tail's
    rows (values AND int8 scale rows — they ride the same scatter) stay
    as garbage inside the slot's reserved speculation blocks and are
    overwritten by the next window/decode write before anything can
    attend to them."""

    def write(kv_stack, layer, k_new, v_new):  # k_new [S, T, H, hd]
        bt = kv_stack[0].shape[3]
        MB = tables.shape[1]
        S, T = k_new.shape[0], k_new.shape[1]
        s = jnp.arange(S)[:, None]
        pmat = positions[:, None] + jnp.arange(T)[None, :]   # [S, T]
        blk = jnp.where(pmat < ctx_limit,
                        tables[s, jnp.minimum(pmat // bt, MB - 1)], 0)
        new = _write_rows(kv_stack, layer, blk, pmat % bt, k_new, v_new)
        return (new, *_gather_context(new, layer, tables, k_new))

    return write


def verify_mask(cfg: LlamaConfig, positions: jax.Array, T: int,
                max_ctx: int) -> jax.Array:
    """[S, T, C] mask for the speculative verify forward: window token t
    (absolute position positions[s]+t) attends causally over the slot's
    prefix + the window so far."""
    c = jnp.arange(max_ctx)[None, None, :]
    pos = positions[:, None, None] + jnp.arange(T)[None, :, None]
    m = c <= pos
    if cfg.sliding_window:
        m &= c > pos - cfg.sliding_window
    return m


def _layer_context(kv_stack, layer, dt, slot=None):
    """(keys, values) for the XLA attend over the contiguous cache: the
    layer's rows [S, H, C, hd] (one slot's, [1, H, C, hd], with ``slot``)
    read from the stack and dequantized when it is scaled int8."""

    def rows(a):
        if slot is None:
            return lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
        return a[layer, jnp.reshape(slot, (1,))]

    def one(cache, scales):
        if scales is None:
            return rows(cache).astype(dt)
        return rows(cache).astype(dt) * rows(scales)[..., None].astype(dt)

    return tuple(one(cache, scales) for cache, scales in _pairs(kv_stack))


def verify_write(positions: jax.Array):
    """KV write policy for the batched speculative verify forward: writes
    the window chunk [S, T, H, hd] at cache[layer, s, :, positions[s] + t]
    and exposes the layer's cache as keys ([S, H, C, hd]) —
    ``decode_write`` generalized to T tokens per slot. Rejected positions
    leave garbage KV *above* each slot's accepted frontier, which the
    decode masks never read and later writes overwrite — rollback is free
    by construction (same invariant as the bucketed prefill paths)."""

    def write(kv_stack, layer, k_new, v_new):
        S, T = k_new.shape[0], k_new.shape[1]
        s = jnp.broadcast_to(jnp.arange(S)[:, None], (S, T))
        pmat = positions[:, None] + jnp.arange(T)[None, :]  # [S, T]
        new = _write_rows(kv_stack, layer, s, pmat, k_new, v_new)
        return (new, *_layer_context(new, layer, k_new.dtype))

    return write


def decode_write(positions: jax.Array, raw: bool = False):
    """KV write policy for batched single-token decode.

    positions: [S] — write location per slot. Returns a ``kv_write`` closure
    for models.llama.forward: writes k/v_new [S, 1, H, hd] at
    cache[layer, s, :, positions[s]] and exposes the layer's cache as keys
    ([S, H, C, hd]).

    ``raw=True`` (Pallas decode kernel): keys/values are
    :class:`LayerView`s of the stack — the kernel reads the layer (and
    dequantizes an int8 cache) itself, so neither a per-layer slice nor a
    [S, H, C, hd] bf16 copy is ever built."""

    def write(kv_stack, layer, k_new, v_new):
        s = jnp.arange(kv_stack[0].shape[1])
        new = _write_rows(kv_stack, layer, s, positions,
                          k_new[:, 0], v_new[:, 0])
        if raw:
            return (new, *_views(new, layer))
        return (new, *_layer_context(new, layer, k_new.dtype))

    return write


def _write_chunk(kv_stack, layer, slot, offset, k_hm, v_hm):
    """One slot's head-major chunk [1, H, T, hd] into the stacked
    contiguous cache at [layer, slot, :, offset:offset+T] (quantized
    first, with its scale rows, when the cache is scaled int8): one
    dynamic_update_slice a leaf, in place on the carried stack."""
    zero = jnp.zeros((), jnp.int32)
    idx = (layer, slot, zero, offset, zero)

    def put(stack, chunk):
        return lax.dynamic_update_slice(stack, chunk[None], idx[:stack.ndim])

    return _write(kv_stack, k_hm, v_hm, put)


def prefill_write(slot: jax.Array, offset: jax.Array):
    """KV write policy for single-sequence prefill into one slot.

    Writes the whole chunk [1, T, H, hd] at
    cache[layer, slot, :, offset:offset+T] and attends over the chunk
    itself (fresh context ⇒ T² attention, not T·C). Keys are exposed
    head-major: [1, H, T, hd] — the unquantized chunk even when the cache
    is int8 (quantization error only enters on later decode reads)."""

    def write(kv_stack, layer, k_new, v_new):
        k_hm = k_new.transpose(0, 2, 1, 3)  # [1, H, T, hd]
        v_hm = v_new.transpose(0, 2, 1, 3)
        return (_write_chunk(kv_stack, layer, slot, offset, k_hm, v_hm),
                k_hm, v_hm)

    return write


def resume_write(slot: jax.Array, offset: jax.Array):
    """KV write policy for suffix prefill into a slot that keeps a reused
    prefix (KV prefix-cache reuse; parity: llama.cpp ``common_part`` +
    slot cache_tokens, /root/reference/backend/cpp/llama/grpc-server.cpp:
    67-74,1651-1668).

    Writes the chunk [1, T, H, hd] at cache[layer, slot, :, offset:offset+T]
    like prefill_write, but exposes the slot's FULL cache row as keys
    ([1, H, C, hd]) so the new tokens attend over the kept prefix."""

    def write(kv_stack, layer, k_new, v_new):
        new = _write_chunk(kv_stack, layer, slot, offset,
                           k_new.transpose(0, 2, 1, 3),
                           v_new.transpose(0, 2, 1, 3))
        return (new, *_layer_context(new, layer, k_new.dtype, slot=slot))

    return write


def resume_mask(cfg: LlamaConfig, seq_len: int,
                offset: jax.Array, max_ctx: int) -> jax.Array:
    """[1, T, C] mask for suffix prefill: chunk token t (absolute position
    offset+t) attends causally over the kept prefix + the chunk. Padding
    rows (t ≥ tail length) write garbage KV beyond the sequence, exactly
    like prefill_mask — those positions are overwritten by later decode
    steps before anything can attend to them."""
    t = jnp.arange(seq_len)[None, :, None]
    c = jnp.arange(max_ctx)[None, None, :]
    pos = offset + t
    m = c <= pos
    if cfg.sliding_window:
        m &= c > pos - cfg.sliding_window
    return m


def decode_mask(cfg: LlamaConfig, positions: jax.Array, max_ctx: int) -> jax.Array:
    """[S, 1, C] attention mask for decode: attend to all written positions
    (≤ current), optionally sliding-window limited (``cfg``: the model's
    config, or a ``KindView`` of one kind of its layers)."""
    idx = jnp.arange(max_ctx)[None, None, :]
    pos = positions[:, None, None]
    m = idx <= pos
    if cfg.sliding_window:
        m &= idx > pos - cfg.sliding_window
    return m


def prefill_mask(cfg: LlamaConfig, seq_len: int, length: jax.Array) -> jax.Array:
    """[1, T, T] causal mask limited to the real (unpadded) length."""
    t = jnp.arange(seq_len)
    m = (t[None, :, None] >= t[None, None, :]) & (t[None, None, :] < length)
    if cfg.sliding_window:
        m &= t[None, None, :] > t[None, :, None] - cfg.sliding_window
    return m


# ---------------------------------------------------------------------------
# the latent page: one row a token a layer, keys and values the same rows
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LatentKVCache:
    """c: [L, N, bt, lanes]: the block pool of a model with latent attention.
    A token's row is ``cfg.latent_width`` elements (its compressed latent,
    then its rope key, shared by every head) in whole 128-lane tiles
    (``ops.attention.latent_lanes``: the pad is zeros and is what HBM's
    tiling costs anyway). Block 0 is the trash block, as the paged pool's.

    A model whose layers keep MORE THAN ONE KIND OF STATE a token
    (models.dots3; ``cfg.latent_states`` names them) holds an array for
    each, all under the ONE allocator and block table, each with a layer
    axis and a row width of its own: ``c`` its full layers' rows, ``w`` its
    window layers' rows, ``i`` its full layers' index keys."""

    c: jax.Array
    w: Optional[jax.Array] = None
    i: Optional[jax.Array] = None

    quantized = False

    def stacked(self):
        return tuple(a for a in (self.c, self.w, self.i) if a is not None)

    @staticmethod
    def from_stacked(t) -> "LatentKVCache":
        return LatentKVCache(*t)


def init_latent_cache(cfg: LlamaConfig, num_blocks: int, block_tokens: int,
                      dtype: str = "bfloat16") -> LatentKVCache:
    return LatentKVCache(*(jnp.zeros(
        (layers, num_blocks, block_tokens, latent_lanes(width)),
        jnp.dtype(dtype)) for _, layers, width in cfg.latent_states))


class LatentView(NamedTuple):
    """What a latent write policy hands its attend: the WHOLE stacked array
    of the pool it wrote and the layer to read (``LayerView``'s reasons), and
    the step's rows in the pool's dtype and lanes where the policy has NOT
    stored them and the kernel is to."""

    cache: jax.Array                    # [L, N, bt, lanes]
    layer: jax.Array                    # scalar i32
    new: Optional[jax.Array] = None     # [S, lanes]


@dataclasses.dataclass(frozen=True)
class LatentAttend:
    """The ``attn`` a latent layout hands the model's forward (one a KIND of
    layer where the stack has several). ``path`` says which form of the
    attention the model is to compute, ``run`` attends:

    ``absorbed`` (a decode step): the queries folded into the latent space,
    attended over the rows as they lie: ``run(q [B, T, H, W], view, mask, *,
    scale, v_lanes) -> out [B, T, H, v_lanes]``, or ``(out, stack)`` where
    the attend wrote the step's rows;
    ``decompressed`` (a chunk behind a cached prefix): keys and values
    rebuilt from the rows, a stretch of the span at a time: ``run(q [1, T,
    H, dq], view, mask, *, scale, expand, v_dim) -> out [1, T, H, v_dim]``,
    where ``expand(rows [n, W]) -> (k [n, H, dq], v [n, H, v_dim])`` is the
    model's.
    An attend that SELECTS the rows it attends (``latent_sparse_decode``,
    ``latent_sparse_chunk``) is handed ``index`` besides: the
    tokens' index queries ``q [B, T, Hi, di]``, their heads' weights ``w [B,
    T, Hi]`` and the pool's array of index ``keys``.
    """

    path: str
    run: Callable


def _pad_lanes(rows, lanes: int):
    return jnp.pad(rows, [(0, 0)] * (rows.ndim - 1)
                   + [(0, lanes - rows.shape[-1])])


def _with(stack: tuple, state: int, cache) -> tuple:
    return (*stack[:state], cache, *stack[state + 1:])


def latent_decode_write(tables: jax.Array, positions: jax.Array,
                        raw: bool = False):
    """``paged_decode_write`` for a latent pool: ``write(stack, layer, row
    [S, 1, W], state) -> (stack, view)``, ``state`` the array of the pool
    the row is of. ``raw``: the kernel writes (the stack goes back
    untouched, the view carries the rows); else one scatter, a row a slot,
    released slots' into the trash block."""

    def write(stack, layer, row, state: int = 0):
        cache = stack[state]
        with jax.named_scope("kv_pool.write"):
            new = _pad_lanes(row[:, 0].astype(cache.dtype), cache.shape[-1])
            if raw:
                return stack, LatentView(cache, layer, new)
            bt = cache.shape[2]
            blk = tables[jnp.arange(tables.shape[0]), positions // bt]
            cache = cache.at[layer, blk, positions % bt].set(new)
        return _with(stack, state, cache), LatentView(cache, layer)

    return write


def latent_kernel_attend(tables, positions, interpret: bool) -> LatentAttend:
    """The absorbed attend as ``ops.latent_decode_attention``, which writes
    the step's rows too."""
    def run(q, view, _mask, *, scale, v_lanes):     # q [S, 1, H, W]
        out, cache = ops.latent_decode_attention(
            q[:, 0], view.cache, view.layer, tables, positions, view.new,
            v_lanes=v_lanes, sm_scale=scale, interpret=interpret)
        return out[:, None], (cache,)

    return LatentAttend("absorbed", scoped("attn.latent_decode")(run))


def _absorbed(q, rows, keep, scale, v_lanes: int):
    """The absorbed attend of q [S, T, H, W] over ``rows [S, n, W]``, of
    which a query sees those of ``keep [S, T, n]``."""
    scores = jnp.einsum("sthd,sld->shtl", q, rows).astype(
        jnp.float32) * scale
    scores = jnp.where(keep[:, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(rows.dtype)
    return jnp.einsum("shtl,sld->sthd", probs, rows[..., :v_lanes])


def latent_xla_attend(tables) -> LatentAttend:
    """The absorbed attend as XLA over the rows the tables name (layer and
    blocks in one gather): the CPU path and the kernel's oracle."""
    def run(q, view, mask, *, scale, v_lanes):      # mask [S, T, C]
        S, MB = tables.shape
        with jax.named_scope("kv_pool.gather"):
            rows = view.cache[view.layer, tables]
            rows = rows.reshape(S, MB * rows.shape[2], rows.shape[3])
            rows = rows[..., :q.shape[-1]].astype(q.dtype)
        return _absorbed(q, rows, mask, scale, v_lanes)

    return LatentAttend("absorbed", scoped("attn.latent_decode")(run))


def latent_window_decode(tables, positions, window: int) -> LatentAttend:
    """The absorbed attend of a WINDOW layer's decode step: each slot
    gathers the blocks that hold its last ``window`` positions (the token
    itself counted; ``window_span`` of them, from table entry ``(position -
    window + 1) // bt`` on) and no other, and masks to the window."""
    def run(q, view, _mask, *, scale, v_lanes):     # q [S, 1, H, W]
        S, MB = tables.shape
        bt = view.cache.shape[2]
        nb = min(window_span(window, 1, bt) // bt, MB)
        first = jnp.clip((positions - window + 1) // bt, 0, MB - nb)
        with jax.named_scope("kv_pool.gather"):
            ids = jnp.take_along_axis(
                tables, first[:, None] + jnp.arange(nb)[None, :], axis=1)
            rows = view.cache[view.layer, ids]
            rows = rows.reshape(S, nb * bt, rows.shape[3])
            rows = rows[..., :q.shape[-1]].astype(q.dtype)
        kpos = first[:, None] * bt + jnp.arange(nb * bt)[None, :]
        pos = positions[:, None]
        keep = (kpos <= pos) & (kpos > pos - window)
        return _absorbed(q, rows, keep[:, None], scale, v_lanes)

    return LatentAttend("absorbed", scoped("attn.latent_window")(run))


# ---------------------------------------------------------------------------
# select before attend: an indexer's scores, the exact k best of them
# ---------------------------------------------------------------------------


def index_scores(q, w, keys):
    """``I = sum_j w_j ReLU(q_j . k)`` of index queries ``q [..., T, Hi,
    di]`` with head weights ``w [..., T, Hi]`` against ``keys [..., n,
    lanes]`` (their first di lanes): [..., T, n] float32."""
    keys = keys[..., :q.shape[-1]].astype(q.dtype)
    s = jnp.einsum("...thd,...nd->...thn", q, keys,
                   preferred_element_type=jnp.float32)
    return jnp.einsum("...thn,...th->...tn", jax.nn.relu(s),
                      w.astype(jnp.float32))


def _order_keys(scores, seen):
    """float32 scores as uint32 that order as the scores do, 0 where a
    position is not ``seen`` (under every score's key, -inf's included)."""
    # + 0.0: a negative zero orders as zero does
    bits = lax.bitcast_convert_type(scores.astype(jnp.float32) + 0.0,
                                    jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))
    return jnp.where(seen, keys, jnp.uint32(0))


def kth_key(keys, k):
    """The EXACT ``k``-th largest of ``keys [..., n]`` uint32 (``k [...]``
    >= 1), a bit at a time from the top: the largest value that ``k`` keys
    reach. 32 counts over the row; no sort."""
    def bit(b, kth):
        cand = kth | (jnp.uint32(1) << (31 - b).astype(jnp.uint32))
        reach = jnp.sum(keys >= cand[..., None], axis=-1, dtype=jnp.int32)
        return jnp.where(reach >= k, cand, kth)

    return lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))


# lanes of a step of the selection's dense forms: a running count along a row
# is a [128, 128] triangle on the MXU a group and a short sum over the groups,
# and a chosen row is found in ITS group: no long scan, no gather of scalars
# (on the chip a ``cumsum`` over 34 k and a binary search of 2048 ranks by
# gathered scalars ran 10.6 ms a layer at 32 streams; PERF.md section 6)
SELECT_GROUP = 128


def _groups(x):
    """``x [R, C]`` in groups of ``SELECT_GROUP`` lanes, zeros behind the
    row: [R, G, g]."""
    g = SELECT_GROUP
    return jnp.pad(x, ((0, 0), (0, -x.shape[-1] % g))).reshape(
        x.shape[0], -1, g)


def _running_count(x):
    """Inclusive running count of ``x [R, C]`` bool along its row, as
    (count inside its group [R, G, g] float32, the groups' totals [R, G]
    int32): exact (0 / 1 in bfloat16, float32 sums)."""
    g = SELECT_GROUP
    inside = jnp.einsum(
        "...i,ij->...j", _groups(x).astype(jnp.bfloat16),
        jnp.triu(jnp.ones((g, g), jnp.bfloat16)),
        preferred_element_type=jnp.float32)
    return inside, inside[..., -1].astype(jnp.int32)


def choose(keys, want):
    """Which of ``keys [R, C]`` (``_order_keys``) are a row's exact ``want
    [R]`` largest: every key over the ``want``-th largest (``kth_key``: a
    threshold, no sort) and, of those that TIE with it, the earliest
    positions that fit (``lax.top_k``'s order). [R, C] bool."""
    kth = kth_key(keys, jnp.maximum(want, 1))[:, None]
    above, ties = keys > kth, keys == kth
    room = want - jnp.sum(above, axis=-1, dtype=jnp.int32)
    inside, totals = _running_count(ties)
    before = jnp.cumsum(totals, axis=-1) - totals       # of earlier groups
    fits = inside + before[..., None].astype(jnp.float32) <= room[
        :, None, None].astype(jnp.float32)
    return above | (ties & fits.reshape(keys.shape[0], -1)[
        :, :keys.shape[1]])


def select_rows(scores, n, k: int):
    """The positions of the exact ``min(k, n)`` largest of ``scores [S, C]``
    among each row's first ``n [S]`` positions: (positions [S, k] i32 in
    rising order, which of them are real [S, k] bool). ``choose``, then a
    compaction by rank in dense steps: the j-th chosen position lies in the
    group whose running total first reaches j + 1 (a comparison against the
    groups' totals), at the lane whose count inside the group is what is
    left (that group's counts fetched by a one-hot product, exact). No
    sort: on the chip ``lax.top_k`` of 2048 from 34 k is a whole sort of the
    row."""
    C, g = scores.shape[-1], SELECT_GROUP
    want = jnp.minimum(k, n).astype(jnp.int32)
    chosen = choose(_order_keys(
        scores, jnp.arange(C)[None, :] < n[:, None]), want)
    inside, totals = _running_count(chosen)             # [S, G, g], [S, G]
    upto = jnp.cumsum(totals, axis=-1)                  # inclusive
    ranks = jnp.arange(1, k + 1, dtype=jnp.int32)
    # the group of rank j: how many groups end before it
    group = jnp.sum(upto[:, None, :] < ranks[None, :, None], axis=-1,
                    dtype=jnp.int32)                    # [S, k]
    one_hot = group[..., None] == jnp.arange(totals.shape[-1])[None, None, :]
    left = ranks[None, :] - jnp.sum(jnp.where(
        one_hot, (upto - totals)[:, None, :], 0), axis=-1)
    # that group's counts (0 where a lane is not chosen): <= 128, exact in
    # bfloat16
    counts = jnp.where(_groups(chosen), inside, 0.0).astype(jnp.bfloat16)
    mine = jnp.einsum("skg,sgl->skl", one_hot.astype(jnp.bfloat16), counts,
                      preferred_element_type=jnp.float32)
    lane = jnp.sum(jnp.where(mine == left[..., None].astype(jnp.float32),
                             jnp.arange(g, dtype=jnp.int32), 0), axis=-1)
    where = group * g + lane
    return (jnp.minimum(where, C - 1).astype(jnp.int32),
            ranks[None, :] <= want[:, None])


def table_entries(tables, index):
    """``tables[s, index[s, j]]`` for ``index [S, k]`` as a comparison and a
    sum over the table row: a gather of 65 k scalars ran 0.67 ms a layer on
    the chip."""
    one_hot = index[..., None] == jnp.arange(tables.shape[1])[None, None, :]
    return jnp.sum(jnp.where(one_hot, tables[:, None, :], 0), axis=-1)


def _attend_chosen(q, scores, n, k: int, view, tables, scope: str, scale,
                   v_lanes: int):
    """The absorbed attend of q [S, 1, H, W] over each stream's exact
    ``min(k, n)`` best-scored rows (``scores [S, C]`` over its first ``n
    [S]`` positions): chosen (``attn.select``), gathered through the
    stream's table row and attended alone (``scope``)."""
    bt = view.cache.shape[2]
    with jax.named_scope("attn.select"):
        where, real = select_rows(scores, n, k)
    with jax.named_scope(scope):
        blk = table_entries(tables, where // bt)
        rows = view.cache[view.layer, blk, where % bt]       # [S, k, lanes]
        rows = rows[..., :q.shape[-1]].astype(q.dtype)
        return _absorbed(q, rows, real[:, None], scale, v_lanes)


def latent_sparse_decode(tables, positions, topk: int) -> LatentAttend:
    """The absorbed attend of a layer with an indexer, a decode step: SCORE
    every cached index key of a slot through its table row
    (``attn.index``), SELECT the exact ``min(topk, n)`` best positions
    (``attn.select``), gather THOSE rows through the table and attend them
    alone (``attn.sparse_decode``). A slot with fewer than ``topk`` rows
    attends all of them: dense latent attention. The step's own row and key
    are in the pool (the policy wrote them)."""
    def run(q, view, _mask, *, scale, v_lanes, index):  # q [S, 1, H, W]
        S, MB = tables.shape
        bt = view.cache.shape[2]
        k = min(topk, MB * bt)
        with jax.named_scope("attn.index"):
            keys = index["keys"][view.layer, tables]
            keys = keys.reshape(S, MB * bt, keys.shape[3])
            scores = index_scores(index["q"], index["w"], keys)[:, 0]
        return _attend_chosen(q, scores, positions + 1, k, view, tables,
                              "attn.sparse_decode", scale, v_lanes)

    return LatentAttend("absorbed", run)


# queries of a chunk that select and gather their rows at a time: the chosen
# rows of 128 queries (2048 each, 640 lanes) are 320 MiB in bfloat16, their
# heads' scores 128 MiB in float32
SPARSE_CHUNK_QUERIES = 128


def latent_sparse_chunk(table_row: jax.Array, offset: jax.Array,
                        topk: int) -> LatentAttend:
    """The absorbed attend of a layer with an indexer, a chunk behind
    ``offset`` cached tokens: every query position is a decode step's
    stream over the ONE table row. A walk lays the queries' index scores
    over the span the chunk has (``attn.index``; [T, span] float32: 71 MiB
    at 512 x 34816); then, ``SPARSE_CHUNK_QUERIES`` queries at a time, each
    query's exact ``min(topk, t + 1)`` best positions (``attn.select``:
    ``select_rows``, the decode step's, so a chunk and a step choose the
    same rows to the tie) are gathered through the table and attended alone
    (``attn.sparse_chunk``). No row the queries do not attend is read: an
    admission behind a 32768-token document gathers 2048 rows a query where
    the decompressed walk rebuilt the keys and values of the whole span for
    every head (~48 ms a chunk and layer on the chip, PERF.md section 6).

    A chunk that ENDS inside the first ``topk`` positions selects nothing
    (every query attends all it sees): it attends the table's first
    ``topk`` rows once for all its queries (``attn.dense_chunk``), scores
    no index key and chooses nothing. The chunk's own rows and keys are in
    the pool (the policy wrote them)."""
    def run(q, view, _mask, *, scale, v_lanes, index):  # q [1, T, H, W]
        cache, layer = view.cache, view.layer
        bt, T, W = cache.shape[2], q.shape[1], q.shape[-1]
        MB = table_row.shape[0]
        k = min(topk, MB * bt)
        qpos = offset + jnp.arange(T)

        def every_row():
            nb = -(-k // bt)
            with jax.named_scope("attn.dense_chunk"):
                rows = cache[layer, table_row[:nb]].reshape(
                    nb * bt, -1)[:, :W].astype(q.dtype)
                keep = jnp.arange(nb * bt)[None, :] <= qpos[:, None]
                return _absorbed(q, rows[None], keep[None], scale, v_lanes)

        def chosen_rows():
            walk = latent_walk(bt)
            nb = walk // bt
            table = jnp.pad(table_row, (0, -MB % nb))   # whole steps: trash
            span = table.shape[0] * bt
            steps = jnp.minimum(latent_attend_span(offset, T, bt) // walk,
                                table.shape[0] // nb)

            def score(i, laid):
                ids = lax.dynamic_slice(table, (i * nb,), (nb,))
                keys = index["keys"][layer, ids]
                return lax.dynamic_update_slice(laid, index_scores(
                    index["q"][0], index["w"][0],
                    keys.reshape(walk, keys.shape[-1])), (0, i * walk))

            with jax.named_scope("attn.index"):
                laid = lax.fori_loop(0, steps, score,
                                     jnp.zeros((T, span), jnp.float32))
            G = math.gcd(T, SPARSE_CHUNK_QUERIES)
            tables = jnp.broadcast_to(table_row[None], (G, MB))

            def some(group):
                qg, scores, n = group           # [G, H, W], [G, span], [G]
                return _attend_chosen(
                    qg[:, None], scores, n, k, view, tables,
                    "attn.sparse_chunk", scale, v_lanes)[:, 0]

            out = lax.map(some, (
                q[0].reshape(T // G, G, *q.shape[2:]),
                laid.reshape(T // G, G, span), (qpos + 1).reshape(-1, G)))
            return out.reshape(1, T, *out.shape[2:])

        return lax.cond(offset + T <= k, every_row, chosen_rows)

    return LatentAttend("absorbed", scoped("attn.latent_chunk")(run))


def latent_prefill_write(table_row: jax.Array, offset: jax.Array,
                         length: jax.Array):
    """``paged_prefill_write`` for a latent pool: ``write(stack, layer, row
    [1, T, W], state)`` lays the chunk's first ``length`` rows at positions
    ``[offset, offset + length)`` of the pool's array ``state`` through
    ``table_row``, a block at a time (``_write_run``'s reasons: the blocks
    the run touches are gathered, the rows laid over them and whole blocks
    scattered back; blocks it does not reach go to the trash block
    unchanged)."""

    def write(stack, layer, row, state: int = 0):
        cache = stack[state]
        bt, lanes = cache.shape[2], cache.shape[3]
        nblk, real, ids = _run_blocks(table_row, offset, length,
                                      row.shape[1], bt)
        with jax.named_scope("kv_pool.write"):
            rows = _pad_lanes(row[0].astype(cache.dtype), lanes)
            frame = lax.dynamic_update_slice(
                jnp.zeros((nblk * bt, lanes), cache.dtype), rows,
                (offset % bt, 0)).reshape(nblk, bt, lanes)
            merged = jnp.where(real[..., None], frame, cache[layer, ids])
            cache = cache.at[layer, ids].set(merged)
        return _with(stack, state, cache), LatentView(cache, layer)

    return write


# positions of the span a chunk's decompressed attend rebuilds at a time
# (whole blocks): the keys and values of 64 heads over 1024 rows are 40 MiB
# in bfloat16, a 512-token chunk's scores over them 128 MiB in float32
LATENT_WALK_TOKENS = 1024


def latent_walk(block_tokens: int) -> int:
    """Rows a step of ``latent_span_attend`` covers: whole blocks."""
    return max(1, LATENT_WALK_TOKENS // block_tokens) * block_tokens


def latent_attend_span(offset: int, bucket: int, block_tokens: int) -> int:
    """``attend_span`` for a latent pool (host integers or the program's
    traced scalars: ONE expression serves both): the walk's whole steps
    that cover ``offset + bucket`` positions."""
    walk = latent_walk(block_tokens)
    return (offset + bucket + walk - 1) // walk * walk


def latent_span_attend(table_row: jax.Array, offset: jax.Array,
                       window: int = 0,
                       scope: str = "attn.latent_chunk") -> LatentAttend:
    """The decompressed attend of a chunk behind ``offset`` cached tokens:
    a rolled loop over the span, ``latent_walk`` rows a step (a traced trip
    count: the chunk attends the prefix it has, ``latent_attend_span``):
    each step gathers its rows through the table row, has the model rebuild
    their keys and values (``expand``) and folds them into an online
    softmax, so that neither a span's keys (1.3 GiB at 32768 rows of 64
    heads) nor a chunk's scores over it ever exist whole. A position past a
    row's own is masked: the walk needs no mask handed in.

    ``window``: a WINDOW layer's walk begins at the step that holds the
    first query's window and masks to ``window`` keys a query, itself
    counted."""
    def run(q, view, _mask, *, scale, expand, v_dim):   # q [1, T, H, dq]
        cache, layer = view.cache, view.layer
        bt, T, H = cache.shape[2], q.shape[1], q.shape[2]
        walk = latent_walk(bt)
        nb = walk // bt
        steps = latent_attend_span(offset, T, bt) // walk
        MB = table_row.shape[0]
        table = jnp.pad(table_row, (0, -MB % nb))   # whole steps: trash
        steps = jnp.minimum(steps, table.shape[0] // nb)
        qpos = offset + jnp.arange(T)
        qh = q[0].transpose(1, 0, 2)                # [H, T, dq]
        lo = 0
        if window:
            lo = jnp.maximum(offset - window + 1, 0) // walk

        def step(i, carry):
            m, l, acc = carry
            with jax.named_scope("kv_pool.gather"):
                ids = lax.dynamic_slice(table, (i * nb,), (nb,))
                rows = cache[layer, ids].reshape(walk, cache.shape[3])
            k, v = expand(rows)             # [walk, H, dq], [walk, H, dv]
            s = jnp.einsum("htd,lhd->htl", qh, k).astype(
                jnp.float32) * scale
            kpos = i * walk + jnp.arange(walk)
            keep = kpos[None, None, :] <= qpos[None, :, None]
            if window:
                keep &= kpos[None, None, :] > qpos[None, :, None] - window
            s = jnp.where(keep, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            if window:
                # a step may hold no row a query sees (its running maximum
                # is then the mask's value, and exp(0) counts every row)
                p = jnp.where(keep, p, 0.0)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc = acc * alpha + jnp.einsum(
                "htl,lhd->htd", p.astype(v.dtype), v).astype(jnp.float32)
            return m_new, l, acc

        _, l, acc = lax.fori_loop(lo, steps, step, (
            jnp.full((H, T, 1), -1e30, jnp.float32),
            jnp.zeros((H, T, 1), jnp.float32),
            jnp.zeros((H, T, v_dim), jnp.float32)))
        return (acc / l).astype(q.dtype).transpose(1, 0, 2)[None]

    return LatentAttend("decompressed", scoped(scope)(run))


# ---------------------------------------------------------------------------
# the layouts: how a runner's K/V is laid out, written, attended and masked
# ---------------------------------------------------------------------------
#
# One object a runner (``engine.runner.ModelRunner.layout``), built once from
# what the runner resolved at load. The runner's ONE family of programs asks
# it for the device state, for ``(write, attend, mask)`` of a decode step and
# of a chunk behind a cached prefix, for ``(write, mask)`` of a verify
# window, and for the cache type its stack goes back into; it names no
# kernel, no ``shard_map`` spec and no write policy itself. ``tables`` /
# ``table_row`` are the block pool's and None over the contiguous rows.


def kind_views(cfg: LlamaConfig) -> list:
    """(kind, what the masks and attends here read of the config for that
    kind of layer) for each of ``cfg.attn_kinds``."""
    return [(kind, KindView(cfg.hd, window))
            for kind, window in cfg.attn_kinds]


@dataclasses.dataclass
class ContiguousLayout:
    """Rows a slot (``KVCache``): what pipeline parallelism, self-extend,
    the mirror port and the draft runner still serve from."""

    cfg: LlamaConfig
    mesh: Any
    kv_dtype: str
    num_slots: int
    max_ctx: int
    attn_impl: str                  # ops.select_attn_impl: "pallas" | "xla"
    interpret: bool
    # (qpos, kpos) -> the attend where the Pallas kernel does not serve:
    # self-extend's, or None for the model's own XLA attend
    xla_attend: Callable

    kv_write_impl = "scatter"
    from_stacked = staticmethod(KVCache.from_stacked)

    def __post_init__(self):
        self.ctx = self.max_ctx     # the length masks are built to
        self.kv_sharding = None
        if self.mesh is not None:
            from localai_tpu.parallel import sharding as shd

            self.kv_sharding = NamedSharding(
                self.mesh, shd.kv_spec(self.cfg, self.mesh))

    def init(self):
        """(the cache, no tables), zeroed in the layout's sharding."""
        return init_cache(self.cfg, self.num_slots, self.max_ctx,
                          self.kv_dtype, sharding=self.kv_sharding), None

    def decode(self, kv: KVCache, tables, positions):
        cfg = self.cfg
        attn = None
        raw = self.attn_impl == "pallas"
        if raw:
            kernel = partial(
                ops.decode_attention,
                sliding_window=cfg.sliding_window,
                interpret=self.interpret,
            )
            if self.mesh is not None:
                # per-device kernel over (slots/'data', heads/'model'):
                # decode attention is independent across slots and head
                # groups, so the shard_map body is the single-device kernel
                # (the stacked cache's layer axis whole on every device)
                in_specs = [P("data", "model", None),
                            P(None, "data", "model", None, None),
                            P(None, "data", "model", None, None),
                            P(),
                            P("data")]
                if kv.quantized:
                    in_specs += [P(None, "data", "model", None),
                                 P(None, "data", "model", None)]
                kernel = shard_map(
                    kernel,
                    mesh=self.mesh,
                    in_specs=tuple(in_specs),
                    out_specs=P("data", "model", None),
                    check_vma=False,
                )

            @scoped("attn.decode")
            def attn(q, keys, values, _mask):  # q [S,1,Hq,hd]; LayerViews
                args = (q[:, 0], keys.cache, values.cache, keys.layer,
                        positions)
                if kv.quantized:  # f32 scale stacks — fused dequant
                    args += (keys.scale, values.scale)
                return kernel(*args)[:, None]

        if attn is None:
            attn = self.xla_attend(
                positions[:, None], jnp.arange(self.ctx, dtype=jnp.int32))
        mask = decode_mask(cfg, positions, self.ctx)
        return decode_write(positions, raw=raw), attn, mask

    def verify(self, tables, positions, T: int):
        return (verify_write(positions),
                verify_mask(self.cfg, positions, T, self.ctx))

    def chunk(self, table_row, slot, positions, offset, length):
        """The chunk attends the slot's whole row (XLA: keys span the cache
        row, which the fresh-chunk Pallas prefill kernel does not model;
        the span of a chunk's attend is cut over the pool alone)."""
        attn = self.xla_attend(
            positions, jnp.arange(self.ctx, dtype=jnp.int32))
        mask = resume_mask(self.cfg, positions.shape[1], offset, self.ctx)
        return resume_write(slot, offset), attn, mask


@dataclasses.dataclass
class PagedLayout:
    """The block pool (``PagedKVCache``) and the [S, MB] device mirror of
    the allocator's block tables: what every cell serves from."""

    cfg: LlamaConfig
    mesh: Any
    kv_dtype: str
    num_slots: int
    max_ctx: int
    attn_impl: str          # ops.select_paged_attn_impl: "pallas" | "xla"
    interpret: bool
    block_tokens: int
    max_blocks: int         # table entries a slot
    num_blocks: int         # blocks in the pool, the trash block included
    overlap_mode: str       # overlap.resolve_mode: "manual", "" = GSPMD

    from_stacked = staticmethod(PagedKVCache.from_stacked)

    def __post_init__(self):
        self.ctx = self.max_blocks * self.block_tokens
        self.kv_sharding = self.table_sharding = None
        sel = self.cfg.select_blocks
        if sel and sel[0] != self.block_tokens:
            raise ValueError(
                f"the model selects blocks of {sel[0]} tokens and the pool's "
                f"hold {self.block_tokens}: a selected block is a block of "
                f"the pool (engine.kv_block_tokens)")
        if self.mesh is not None:
            from localai_tpu.parallel import sharding as shd

            # pool kv-heads on 'model' (paged_kv_spec); the [S, MB] table
            # mirror carries the 'data' sharding instead — the pool has no
            # slot axis to put it on
            self.kv_sharding = NamedSharding(
                self.mesh, shd.paged_kv_spec(self.cfg, self.mesh))
            self.table_sharding = NamedSharding(
                self.mesh, shd.block_table_spec())

    @property
    def kv_write_impl(self) -> str:
        """Who writes a decode step's new rows into the pool: ``kernel``,
        the Pallas paged kernel that reads them (an unscaled pool:
        ``paged_decode_write``), else the policy's ``scatter``."""
        unscaled = self.kv_dtype not in ("int8", "int4")
        return "kernel" if self.attn_impl == "pallas" and unscaled else "scatter"

    def init(self):
        """(the pool, the table mirror: every row on the trash block),
        zeroed in the layout's shardings."""
        tables = jnp.zeros((self.num_slots, self.max_blocks), jnp.int32)
        if self.table_sharding is not None:
            tables = jax.device_put(tables, self.table_sharding)
        return init_paged_cache(
            self.cfg, self.num_blocks, self.block_tokens, self.kv_dtype,
            sharding=self.kv_sharding), tables

    def tp_trunk(self, params, rope, tokens, positions, kv: PagedKVCache,
                 tables):
        """The decode forward as a manual-TP trunk, one all-reduce a
        row-parallel product (parallel.overlap; where ``overlap_mode`` is
        set)."""
        from localai_tpu.parallel import overlap as ovl

        trunk = {k: params[k] for k in ovl.TRUNK_KEYS}
        return ovl.paged_decode_trunk(
            self.cfg, trunk, self.mesh, tokens, positions,
            kv.stacked(), tables, rope,
            ctx_pad=self.ctx,
            use_pallas=self.attn_impl == "pallas",
            interpret=self.interpret,
        )

    def _kernel(self, sliding_window):
        return partial(ops.paged_decode_attention,
                       sliding_window=sliding_window,
                       interpret=self.interpret)

    def decode(self, kv: PagedKVCache, tables, positions):
        cfg = self.cfg
        raw = self.attn_impl == "pallas"
        attn = None
        if cfg.select_blocks:
            # its streams attend a selection of their blocks: compacted
            # tables, rows = (stream, K/V head)
            write, attn = select_decode(
                cfg, self._kernel(None) if raw else None, tables, positions)
            return write, attn, decode_mask(cfg, positions, self.ctx)
        if raw:
            kernel = self._kernel(cfg.sliding_window)
            if self.mesh is not None:
                # per-device kernel over (slots/'data', heads/'model'):
                # the stacked pool's layer and block axes stay whole on
                # every device (table values are global block ids), its
                # kv-head axis shards on 'model', and each data shard walks
                # its own slots' SMEM table mirror — the shard_map body is
                # the single-device kernel (select_paged_attn_impl refuses
                # Pallas when the head groups don't split over tp)
                # Of the last four arguments a pool has two: the f32
                # scale stacks of a scaled one (fused dequant), or the
                # step's rows, which the kernel writes into each shard's
                # own heads of an unscaled one (the pools then come back,
                # aliased, beside the output)
                rows = P("data", "model", None)
                pool = P(None, None, "model", None, None)
                scale = P(None, None, "model", None)
                kernel = shard_map(
                    kernel,
                    mesh=self.mesh,
                    in_specs=(rows, pool, pool, P(), P("data", None),
                              P("data"),
                              *((scale, scale, None, None) if kv.quantized
                                else (None, None, rows, rows))),
                    out_specs=rows if kv.quantized else (rows, pool, pool),
                    check_vma=False,
                )
            attn = scoped("attn.paged_decode")(
                kernel_attend(kernel, tables, positions))
        mask = decode_mask(cfg, positions, self.ctx)
        if cfg.attn_kinds:
            # a mask and an attend a KIND of layer: a window layer's kernel
            # call walks its window's blocks alone, under a scope of its own
            views = kind_views(cfg)
            mask = {kind: decode_mask(view, positions, self.ctx)
                    for kind, view in views}
            if raw:
                attn = {kind: scoped(
                    "attn.window_decode" if view.sliding_window
                    else "attn.paged_decode")(kernel_attend(
                        self._kernel(view.sliding_window), tables, positions))
                    for kind, view in views}
        return paged_decode_write(tables, positions, raw=raw), attn, mask

    def verify(self, tables, positions, T: int):
        """The window's attend spans the padded context (every slot its own
        prefix: no one span serves the batch; ``span_attend`` is the
        single-sequence chunk's)."""
        return (paged_verify_write(tables, positions, self.max_ctx),
                verify_mask(self.cfg, positions, T, self.ctx))

    def chunk_span(self, offset: int, bucket: int) -> int:
        """Positions a chunk's attend spans (host integers; the flight
        ring's ``chunk_ctx``): the rung its program takes on the device."""
        return attend_span(offset, bucket, self.ctx, self.block_tokens)

    def chunk(self, table_row, slot, positions, offset, length):
        """The attend spans the rung of the ladder that covers ``offset`` +
        the bucket, picked on the device: the mask is sliced to it."""
        cfg, bucket = self.cfg, positions.shape[1]
        mask = resume_mask(cfg, bucket, offset, self.ctx)
        attn = (select_span_attend if cfg.select_blocks else span_attend)(
            cfg, table_row, offset, self.ctx)
        if cfg.attn_kinds:
            # a window layer's chunk gathers its window of the prefix
            views = kind_views(cfg)
            mask = {kind: resume_mask(view, bucket, offset, self.ctx)
                    for kind, view in views}
            attn = {kind: (window_attend if view.sliding_window
                           else span_attend)(
                view, table_row, offset, self.ctx)
                for kind, view in views}
        return paged_prefill_write(table_row, offset, length), attn, mask

    def ride(self, kv: PagedKVCache, tables, positions, table_row, slot,
             chunk_positions, offset, length):
        """A chunk's rows and a decode step's in ONE forward (``[1, bucket +
        S]``, the chunk in front: ``ModelRunner._decode_prefill_paged_fn``).
        Rows meet nowhere but in the attend, so the composite is the two
        policies side by side: the chunk's rows go where ``chunk`` sends
        them, the step's where ``decode`` does, with the arguments each is
        handed alone, and the two outputs are laid end to end. Both attends
        read the stack as BOTH writes left it (one buffer, written in
        place: the streams' blocks and the chunk's are not the same
        blocks), and the kernel that stores an unscaled pool's step rows
        hands the stack on. The masks travel as a pair."""
        cfg, T = self.cfg, chunk_positions.shape[1]
        assert not cfg.attn_kinds and self.mesh is None
        c_write, c_attn, c_mask = self.chunk(table_row, slot, chunk_positions,
                                             offset, length)
        d_write, d_attn, d_mask = self.decode(kv, tables, positions)
        if d_attn is None:      # the XLA attend ``forward`` would bring

            def d_attn(q, keys, values, m):
                with jax.named_scope("attn.decode"):
                    return _grouped_attn(cfg, q, keys, values, m)

        def write(kv_stack, layer, k_new, v_new):   # k_new [1, T + S, H, hd]
            new, *_ = c_write(kv_stack, layer, k_new[:, :T], v_new[:, :T])
            new, d_keys, d_values = d_write(new, layer, k_new[0, T:, None],
                                            v_new[0, T:, None])
            c_keys, c_values = _views(new, layer)
            return new, (c_keys, d_keys), (c_values, d_values)

        def attn(q, keys, values, mask):            # q [1, T + S, Hq, hd]
            (c_keys, d_keys), (c_values, d_values) = keys, values
            out = c_attn(q[:, :T], c_keys, c_values, mask[0])
            step = d_attn(q[0, T:, None], d_keys, d_values, mask[1])
            stack = None
            if isinstance(step, tuple):     # the kernel wrote the stack
                step, stack = step
            out = jnp.concatenate([out, step[:, 0][None]], axis=1)
            return out if stack is None else (out, stack)

        return write, attn, (c_mask, d_mask)


@dataclasses.dataclass
class LatentLayout:
    """The latent block pool (``LatentKVCache``) behind the block pool's
    tables: what a model with latent attention serves from, on one chip.
    Its attends say which FORM of the attention the model computes: a
    decode step the absorbed one over the rows as they lie (the Pallas
    kernel, which writes the step's rows, or XLA), a chunk the decompressed
    one over the span it has (``latent_span_attend``).

    A stack with several KINDS of latent layer (``cfg.attn_kinds``;
    models.dots3) is handed an attend a kind: a layer with an indexer
    (``cfg.index_topk``) selects the rows it attends, gathers them and
    attends them in the absorbed form, in a decode step and in a chunk
    (``latent_sparse_decode``, ``latent_sparse_chunk``), a window layer
    reads its window's blocks (``latent_window_decode``; a chunk's walk
    from the window's first step). Those are XLA; the policy writes the
    step's rows."""

    cfg: LlamaConfig
    kv_dtype: str
    num_slots: int
    max_ctx: int
    attn_impl: str          # ops.select_latent_attn_impl: "pallas" | "xla"
    interpret: bool
    block_tokens: int
    max_blocks: int
    num_blocks: int

    from_stacked = staticmethod(LatentKVCache.from_stacked)

    def __post_init__(self):
        self.ctx = self.max_blocks * self.block_tokens
        # the pool's arrays by name, and the real elements of a row of each
        self.widths = {name: width
                       for name, _, width in self.cfg.latent_states}

    @property
    def kv_write_impl(self) -> str:
        raw = self.attn_impl == "pallas" and not self.cfg.attn_kinds
        return "kernel" if raw else "scatter"

    def init(self):
        return init_latent_cache(
            self.cfg, self.num_blocks, self.block_tokens,
            self.kv_dtype), jnp.zeros(
                (self.num_slots, self.max_blocks), jnp.int32)

    def decode(self, kv: LatentKVCache, tables, positions):
        if self.cfg.attn_kinds:
            attn = {kind: (latent_window_decode(tables, positions, window)
                           if window else latent_sparse_decode(
                               tables, positions, self.cfg.index_topk))
                    for kind, window in self.cfg.attn_kinds}
            return latent_decode_write(tables, positions), attn, None
        raw = self.attn_impl == "pallas"
        attn = (latent_kernel_attend(tables, positions, self.interpret)
                if raw else latent_xla_attend(tables))
        mask = decode_mask(KindView(0, None), positions, self.ctx)
        return latent_decode_write(tables, positions, raw=raw), attn, mask

    def chunk(self, table_row, slot, positions, offset, length):
        """The attend walks the span the chunk has (``chunk_span``) and
        masks by position: no mask is built."""
        write = latent_prefill_write(table_row, offset, length)
        if self.cfg.attn_kinds:
            return write, {kind: (
                latent_span_attend(table_row, offset, window=window,
                                   scope="attn.latent_window")
                if window else latent_sparse_chunk(
                    table_row, offset, self.cfg.index_topk))
                for kind, window in self.cfg.attn_kinds}, None
        return write, latent_span_attend(table_row, offset), None

    def chunk_span(self, offset: int, bucket: int) -> int:
        """Positions a chunk's walk covers (the flight ring's
        ``chunk_ctx``): whole steps, never past the table's."""
        walk = latent_walk(self.block_tokens)
        return min(latent_attend_span(offset, bucket, self.block_tokens),
                   -(-self.ctx // walk) * walk)

    def scratch(self, bucket: int):
        """(stack, table row) of a throwaway one-sequence pool of ``bucket``
        positions: the embeddings path's."""
        nb = -(-bucket // self.block_tokens)
        return (init_latent_cache(self.cfg, nb + 1, self.block_tokens,
                                  self.kv_dtype).stacked(),
                1 + jnp.arange(nb, dtype=jnp.int32))

    # a block's rows to and from the host, and a slot's first rows in the
    # export format ([L, n, W] an array of the pool: the real lanes,
    # whatever the tiling pads), under the arrays' names

    def _arrays(self, kv: LatentKVCache) -> dict:
        return dict(zip(self.widths, kv.stacked()))

    def pack_block(self, kv: LatentKVCache, bid: int) -> dict:
        import numpy as np

        return {name: np.asarray(a[:, bid])
                for name, a in self._arrays(kv).items()}

    def load_block(self, kv: LatentKVCache, bid: int, payload: dict):
        return LatentKVCache(*(
            a.at[:, bid].set(jnp.asarray(payload[name], a.dtype))
            for name, a in self._arrays(kv).items()))

    def export_rows(self, kv: LatentKVCache, blocks, n: int) -> dict:
        out = {}
        for name, a in self._arrays(kv).items():
            g = a[:, blocks]                        # [L, nb, bt, lanes]
            out[name] = g.reshape(g.shape[0], -1, g.shape[-1])[
                :, :n, :self.widths[name]]
        return out

    def import_rows(self, kv: LatentKVCache, blk, off, arrays: dict,
                    n: int):
        """The pool with exported rows ``arrays[name] [L, n, W]`` at
        (``blk``, ``off``) [n]; None where they are not this pool's."""
        loaded = []
        for name, a in self._arrays(kv).items():
            rows = arrays.get(name)
            want = (a.shape[0], n, self.widths[name])
            if rows is None or tuple(rows.shape) != want:
                return None
            loaded.append(a.at[:, blk, off].set(
                _pad_lanes(jnp.asarray(rows, a.dtype), a.shape[-1])))
        return LatentKVCache(*loaded)
