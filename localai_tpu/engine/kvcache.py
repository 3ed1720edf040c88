"""Slot-resident KV cache in HBM.

Replaces llama.cpp's per-slot KV management (kv_cache_clear / cache_tokens /
n_ctx-per-slot partitioning, /root/reference/backend/cpp/llama/
grpc-server.cpp:176,906,1546-1990) with a TPU-native layout: one statically
shaped tensor pair per model, stacked over layers so the layer loop can
``lax.scan`` it, sliced per slot by masking — never by ragged mutation.

Layout: k,v each [num_layers, num_slots, num_kv_heads, max_ctx, head_dim].
Heads lead the context dim so the last two axes are (context, head_dim) —
the (sublane, lane) tiling Mosaic requires for the flash kernels' per-head
HBM→VMEM DMA slices (ops.attention), and a contiguous stream per head.
All updates are functional. jit donation lets XLA reuse the cache's buffers;
it does not yet make the update in-place: compiled for v5e, the layer scan
that carries the cache (models.llama.forward) holds a second, cache-sized
temp (PERF.md).
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from localai_tpu.models.llama import LlamaConfig
from localai_tpu.models.quant import (
    quantize_lastdim as _quant_chunk,
    quantize_lastdim4 as _quant_chunk4,
    unpack_int4_lastdim as _unpack4,
)
from localai_tpu.ops.attention import gather_block_scales, gather_blocks


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
        """The pytree scanned alongside layers in models.llama.forward."""
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
    shape = (cfg.num_layers, num_slots, cfg.num_kv_heads, max_ctx, cfg.hd)
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
        from jax.sharding import NamedSharding, PartitionSpec as P

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
    shape = (cfg.num_layers, num_blocks, cfg.num_kv_heads, block_tokens, hd)
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
        from jax.sharding import NamedSharding, PartitionSpec as P

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


def _pool_quant(layer_kv, k_new):
    """The quantizer matching a paged pool's storage: int4 when the pool's
    last dim is the packed hd/2 (self-describing layout), else int8.
    ``k_new`` carries the full head_dim."""
    int4 = layer_kv[0].shape[-1] * 2 == k_new.shape[-1]
    return (_quant_chunk4 if int4 else _quant_chunk), int4


def _gather_dequant(cache, scales, tables, dt, int4: bool):
    """Gather + dequantize a quantized pool's logical context for the XLA
    attend: [S, H, MB*bt, hd] in ``dt`` (int4 pools unpack first)."""
    g = gather_blocks(cache, tables)
    if int4:
        g = _unpack4(g)
    return (g.astype(dt)
            * gather_block_scales(scales, tables)[..., None].astype(dt))


def paged_decode_write(tables: jax.Array, positions: jax.Array,
                       raw: bool = False):
    """KV write policy for batched single-token decode over a block pool.

    tables: [S, MB] i32 block tables, positions: [S]. Writes k/v_new
    [S, 1, H, hd] at pool[tables[s, pos//bt], :, pos%bt]. Released slots'
    table rows are all-zeros, so their (static-shape-mandated) garbage
    writes land in the trash block.

    ``raw=False`` exposes the gathered logical context [S, H, MB*bt, hd]
    for the XLA attend; ``raw=True`` passes the pool through untouched for
    the Pallas paged kernel (which walks the tables itself)."""

    def write(layer_kv, k_new, v_new):
        dt = k_new.dtype
        bt = layer_kv[0].shape[2]
        s = jnp.arange(tables.shape[0])
        blk = tables[s, positions // bt]          # [S]
        off = positions % bt
        if len(layer_kv) == 4:  # scaled int8/int4 pool
            k_layer, v_layer, ks_layer, vs_layer = layer_kv
            quant, int4 = _pool_quant(layer_kv, k_new)
            with jax.named_scope("kv_pool.write"):
                kq, ks = quant(k_new[:, 0])    # [S, H, hd or hd/2], [S, H]
                vq, vs = quant(v_new[:, 0])
                new_k = k_layer.at[blk, :, off].set(kq)
                new_v = v_layer.at[blk, :, off].set(vq)
                new_ks = ks_layer.at[blk, :, off].set(ks)
                new_vs = vs_layer.at[blk, :, off].set(vs)
            new_kv = (new_k, new_v, new_ks, new_vs)
            if raw:
                return new_kv, (new_k, new_ks), (new_v, new_vs)
            with jax.named_scope("kv_pool.gather"):
                keys = _gather_dequant(new_k, new_ks, tables, dt, int4)
                values = _gather_dequant(new_v, new_vs, tables, dt, int4)
            return new_kv, keys, values
        k_layer, v_layer = layer_kv               # [N, H, bt, hd]
        kdt = k_layer.dtype
        with jax.named_scope("kv_pool.write"):
            new_k = k_layer.at[blk, :, off].set(k_new[:, 0].astype(kdt))
            new_v = v_layer.at[blk, :, off].set(v_new[:, 0].astype(kdt))
        if raw:
            return (new_k, new_v), new_k, new_v
        with jax.named_scope("kv_pool.gather"):
            return ((new_k, new_v), gather_blocks(new_k, tables).astype(dt),
                    gather_blocks(new_v, tables).astype(dt))

    return write


def paged_prefill_write(table_row: jax.Array, offset: jax.Array,
                        length: jax.Array):
    """KV write policy for one chunked-prefill dispatch into a block table.

    table_row: [MB] i32, offset: absolute start position of this chunk,
    length: real (unpadded) tokens in the chunk. Token t of the chunk
    lands at pool[table_row[(offset+t)//bt], :, (offset+t)%bt]; padding
    rows (t >= length) are redirected to the trash block so a padded
    bucket can never clobber the sequence's own reserved blocks. Exposes
    the gathered FULL logical context [1, H, MB*bt, hd] so chunk tokens
    attend over the kept prefix + earlier chunks (resume-style)."""

    def write(layer_kv, k_new, v_new):  # k_new [1, T, H, hd]
        dt = k_new.dtype
        bt = layer_kv[0].shape[2]
        MB = table_row.shape[0]
        T = k_new.shape[1]
        t = jnp.arange(T)
        pos = offset + t
        valid = t < length
        blk = jnp.where(valid, table_row[jnp.minimum(pos // bt, MB - 1)], 0)
        off = pos % bt
        row = table_row[None]                     # [1, MB]
        if len(layer_kv) == 4:  # scaled int8/int4 pool
            k_layer, v_layer, ks_layer, vs_layer = layer_kv
            quant, int4 = _pool_quant(layer_kv, k_new)
            with jax.named_scope("kv_pool.write"):
                kq, ks = quant(k_new[0])   # [T, H, hd or hd/2], [T, H]
                vq, vs = quant(v_new[0])
                new_k = k_layer.at[blk, :, off].set(kq)
                new_v = v_layer.at[blk, :, off].set(vq)
                new_ks = ks_layer.at[blk, :, off].set(ks)
                new_vs = vs_layer.at[blk, :, off].set(vs)
            with jax.named_scope("kv_pool.gather"):
                keys = _gather_dequant(new_k, new_ks, row, dt, int4)
                values = _gather_dequant(new_v, new_vs, row, dt, int4)
            return (new_k, new_v, new_ks, new_vs), keys, values
        k_layer, v_layer = layer_kv
        kdt = k_layer.dtype
        with jax.named_scope("kv_pool.write"):
            new_k = k_layer.at[blk, :, off].set(k_new[0].astype(kdt))
            new_v = v_layer.at[blk, :, off].set(v_new[0].astype(kdt))
        with jax.named_scope("kv_pool.gather"):
            return ((new_k, new_v), gather_blocks(new_k, row).astype(dt),
                    gather_blocks(new_v, row).astype(dt))

    return write


def verify_write(positions: jax.Array):
    """KV write policy for the batched speculative verify forward: writes
    the window chunk [S, T, H, hd] at cache[s, :, positions[s] + t] and
    exposes the full per-layer cache as keys ([S, H, C, hd]) —
    ``decode_write`` generalized to T tokens per slot. Rejected positions
    leave garbage KV *above* each slot's accepted frontier, which the
    decode masks never read and later writes overwrite — rollback is free
    by construction (same invariant as the bucketed prefill paths)."""

    def write(layer_kv, k_new, v_new):
        dt = k_new.dtype
        S, T = k_new.shape[0], k_new.shape[1]
        s = jnp.arange(S)[:, None]
        pmat = positions[:, None] + jnp.arange(T)[None, :]  # [S, T]
        if len(layer_kv) == 4:  # scaled int8 cache
            k_layer, v_layer, ks_layer, vs_layer = layer_kv
            kq, ks = _quant_chunk(k_new)  # [S, T, H, hd], [S, T, H]
            vq, vs = _quant_chunk(v_new)
            new_k = k_layer.at[s, :, pmat].set(kq)
            new_v = v_layer.at[s, :, pmat].set(vq)
            new_ks = ks_layer.at[s, :, pmat].set(ks)
            new_vs = vs_layer.at[s, :, pmat].set(vs)
            keys = new_k.astype(dt) * new_ks[..., None].astype(dt)
            values = new_v.astype(dt) * new_vs[..., None].astype(dt)
            return (new_k, new_v, new_ks, new_vs), keys, values
        k_layer, v_layer = layer_kv
        kdt = k_layer.dtype
        new_k = k_layer.at[s, :, pmat].set(k_new.astype(kdt))
        new_v = v_layer.at[s, :, pmat].set(v_new.astype(kdt))
        return (new_k, new_v), new_k.astype(dt), new_v.astype(dt)

    return write


def paged_verify_write(tables: jax.Array, positions: jax.Array,
                       ctx_limit: int):
    """KV write policy for the batched speculative verify forward over a
    block pool — ``paged_decode_write`` generalized to T tokens per slot.

    Window token t of slot s lands at
    ``pool[tables[s, (positions[s]+t)//bt], :, (positions[s]+t)%bt]``.
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

    def write(layer_kv, k_new, v_new):  # k_new [S, T, H, hd]
        dt = k_new.dtype
        bt = layer_kv[0].shape[2]
        MB = tables.shape[1]
        S, T = k_new.shape[0], k_new.shape[1]
        s = jnp.arange(S)[:, None]
        pmat = positions[:, None] + jnp.arange(T)[None, :]   # [S, T]
        safe = pmat < ctx_limit
        blk = jnp.where(
            safe, tables[s, jnp.minimum(pmat // bt, MB - 1)], 0)
        off = pmat % bt
        if len(layer_kv) == 4:  # scaled int8/int4 pool
            k_layer, v_layer, ks_layer, vs_layer = layer_kv
            quant, int4 = _pool_quant(layer_kv, k_new)
            kq, ks = quant(k_new)       # [S, T, H, hd or hd/2], [S, T, H]
            vq, vs = quant(v_new)
            new_k = k_layer.at[blk, :, off].set(kq)
            new_v = v_layer.at[blk, :, off].set(vq)
            new_ks = ks_layer.at[blk, :, off].set(ks)
            new_vs = vs_layer.at[blk, :, off].set(vs)
            keys = _gather_dequant(new_k, new_ks, tables, dt, int4)
            values = _gather_dequant(new_v, new_vs, tables, dt, int4)
            return (new_k, new_v, new_ks, new_vs), keys, values
        k_layer, v_layer = layer_kv               # [N, H, bt, hd]
        kdt = k_layer.dtype
        new_k = k_layer.at[blk, :, off].set(k_new.astype(kdt))
        new_v = v_layer.at[blk, :, off].set(v_new.astype(kdt))
        return ((new_k, new_v), gather_blocks(new_k, tables).astype(dt),
                gather_blocks(new_v, tables).astype(dt))

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


def decode_write(positions: jax.Array, raw: bool = False):
    """KV write policy for batched single-token decode.

    positions: [S] — write location per slot. Returns a ``kv_write`` closure
    for models.llama.forward: writes k/v_new [S, 1, H, hd] at
    cache[s, :, positions[s]] and exposes the full per-layer cache as keys
    ([S, H, C, hd]).

    ``raw=True`` (int8 cache + Pallas decode kernel): keys/values are passed
    through as ``(int8 cache, f32 scales)`` tuples — dequantization happens
    inside the flash kernel, so no [S, H, C, hd] bf16 copy is ever built."""

    def write(layer_kv, k_new, v_new):
        dt = k_new.dtype
        s = jnp.arange(layer_kv[0].shape[0])
        if len(layer_kv) == 4:  # scaled int8 cache
            k_layer, v_layer, ks_layer, vs_layer = layer_kv
            kq, ks = _quant_chunk(k_new[:, 0])  # [S, H, hd], [S, H]
            vq, vs = _quant_chunk(v_new[:, 0])
            # advanced indices (s, positions) separated by the head slice →
            # result dims [S, H, ...]
            new_k = k_layer.at[s, :, positions].set(kq)
            new_v = v_layer.at[s, :, positions].set(vq)
            new_ks = ks_layer.at[s, :, positions].set(ks)
            new_vs = vs_layer.at[s, :, positions].set(vs)
            new_kv = (new_k, new_v, new_ks, new_vs)
            if raw:
                return new_kv, (new_k, new_ks), (new_v, new_vs)
            keys = new_k.astype(dt) * new_ks[..., None].astype(dt)
            values = new_v.astype(dt) * new_vs[..., None].astype(dt)
            return new_kv, keys, values
        k_layer, v_layer = layer_kv  # [S, H, C, hd]
        kdt = k_layer.dtype
        new_k = k_layer.at[s, :, positions].set(k_new[:, 0].astype(kdt))
        new_v = v_layer.at[s, :, positions].set(v_new[:, 0].astype(kdt))
        return (new_k, new_v), new_k.astype(dt), new_v.astype(dt)

    return write


def prefill_write(slot: jax.Array, offset: jax.Array):
    """KV write policy for single-sequence prefill into one slot.

    Writes the whole chunk [1, T, H, hd] at cache[slot, :, offset:offset+T]
    and attends over the chunk itself (fresh context ⇒ T² attention, not
    T·C). Keys are exposed head-major: [1, H, T, hd]."""

    def write(layer_kv, k_new, v_new):
        k_hm = k_new.transpose(0, 2, 1, 3)  # [1, H, T, hd]
        v_hm = v_new.transpose(0, 2, 1, 3)
        zero = jnp.zeros((), jnp.int32)
        idx = (slot, zero, offset, zero)
        if len(layer_kv) == 4:  # scaled int8 cache
            k_layer, v_layer, ks_layer, vs_layer = layer_kv
            kq, ks = _quant_chunk(k_hm)  # [1, H, T, hd], [1, H, T]
            vq, vs = _quant_chunk(v_hm)
            new_k = lax.dynamic_update_slice(k_layer, kq, idx)
            new_v = lax.dynamic_update_slice(v_layer, vq, idx)
            new_ks = lax.dynamic_update_slice(ks_layer, ks, (slot, zero, offset))
            new_vs = lax.dynamic_update_slice(vs_layer, vs, (slot, zero, offset))
            # fresh-context prefill attends over the chunk itself, so the
            # exposed keys/values are the unquantized chunk — quantization
            # error only enters on later decode reads
            return (new_k, new_v, new_ks, new_vs), k_hm, v_hm
        k_layer, v_layer = layer_kv  # [S, H, C, hd]
        kdt = k_layer.dtype
        new_k = lax.dynamic_update_slice(k_layer, k_hm.astype(kdt), idx)
        new_v = lax.dynamic_update_slice(v_layer, v_hm.astype(kdt), idx)
        return (new_k, new_v), k_hm, v_hm

    return write


def resume_write(slot: jax.Array, offset: jax.Array):
    """KV write policy for suffix prefill into a slot that keeps a reused
    prefix (KV prefix-cache reuse; parity: llama.cpp ``common_part`` +
    slot cache_tokens, /root/reference/backend/cpp/llama/grpc-server.cpp:
    67-74,1651-1668).

    Writes the chunk [1, T, H, hd] at cache[slot, :, offset:offset+T] like
    prefill_write, but exposes the slot's FULL cache row as keys
    ([1, H, C, hd]) so the new tokens attend over the kept prefix."""

    def write(layer_kv, k_new, v_new):
        k_hm = k_new.transpose(0, 2, 1, 3)  # [1, H, T, hd]
        v_hm = v_new.transpose(0, 2, 1, 3)
        zero = jnp.zeros((), jnp.int32)
        idx = (slot, zero, offset, zero)
        dt = k_new.dtype

        def row(cache, scales=None):
            r = lax.dynamic_index_in_dim(cache, slot, 0, keepdims=True)
            if scales is None:
                return r.astype(dt)
            s = lax.dynamic_index_in_dim(scales, slot, 0, keepdims=True)
            return r.astype(dt) * s[..., None].astype(dt)

        if len(layer_kv) == 4:  # scaled int8 cache
            k_layer, v_layer, ks_layer, vs_layer = layer_kv
            kq, ks = _quant_chunk(k_hm)
            vq, vs = _quant_chunk(v_hm)
            new_k = lax.dynamic_update_slice(k_layer, kq, idx)
            new_v = lax.dynamic_update_slice(v_layer, vq, idx)
            new_ks = lax.dynamic_update_slice(ks_layer, ks, (slot, zero, offset))
            new_vs = lax.dynamic_update_slice(vs_layer, vs, (slot, zero, offset))
            return ((new_k, new_v, new_ks, new_vs),
                    row(new_k, new_ks), row(new_v, new_vs))
        k_layer, v_layer = layer_kv
        kdt = k_layer.dtype
        new_k = lax.dynamic_update_slice(k_layer, k_hm.astype(kdt), idx)
        new_v = lax.dynamic_update_slice(v_layer, v_hm.astype(kdt), idx)
        return (new_k, new_v), row(new_k), row(new_v)

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
    (≤ current), optionally sliding-window limited (Mistral-style)."""
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
