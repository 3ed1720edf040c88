"""Collective/compute overlap for the meshed paged decode hot path.

The GSPMD meshed decode (pjit + NamedSharding, the PR 8 default) leaves
the two per-layer Megatron psums — after attention-out and after mlp-down
— as monolithic all-reduces whose ICI latency sits on the critical path
of every decoded token. This module runs the SAME trunk math as a manual
shard_map over the mesh (like parallel.ring does for sequence-parallel
prefill) so the reduction can be *decomposed*: each row-parallel product
splits into chunks along the hidden dim, every chunk goes through
``psum_scatter`` (each device sums only its D/tp tile —
parallel.sharding.overlap_intermediate_spec is the scattered layout)
followed by a tiled ``all_gather``, and because the chunks are
independent collectives instead of one fused all-reduce, XLA's
latency-hiding scheduler can start chunk ``i``'s ICI transfer while
chunk ``i+1``'s partial product (and the next layer-region matmul) is
still on the MXU. Communication volume is identical to the plain psum
(reduce-scatter + all-gather IS the canonical all-reduce decomposition);
only the exposure of the latency changes.

Numerics: ``psum_scatter`` + ``all_gather`` computes the same per-element
device sums as ``psum`` — on a 2-wide 'model' axis there is exactly one
addition per element, so greedy decode is BYTE-IDENTICAL between
``mode="overlap"`` and ``mode="psum"`` (pinned by tests/test_overlap.py);
on wider meshes the summation tree may differ at the ULP level, the same
caveat every all-reduce implementation carries.

Scope gates (``resolve_mode``): paged KV, 'model' the only busy mesh axis
(data/seq/expert/pipe == 1 — the pool writes of distinct data shards
cannot be reconciled manually without an extra collective), dense MLP,
and tp dividing heads/kv-heads/ffn/hidden. Everything else keeps the
GSPMD path. Knob, read by ``resolve_mode`` alone: ``LOCALAI_MESH_OVERLAP``
= auto/1 (overlap when supported, the default), ``psum`` (manual shard_map,
undecomposed psum — the parity reference), ``0`` (GSPMD, the pre-overlap
behavior).
"""

from __future__ import annotations

import logging
import os
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from localai_tpu.models import llama as mdl
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import LlamaConfig

log = logging.getLogger(__name__)

TRUNK_KEYS = ("embed", "final_norm", "layers")

# independent psum_scatter+all_gather pairs a row-parallel product splits
# into along the hidden dim
CHUNKS = 4


def resolve_mode(cfg: LlamaConfig, mesh: Optional[Mesh],
                 requested: Optional[str] = None) -> tuple[str, str]:
    """The overlap-path decision: ("overlap" | "psum" | "", reason).

    ``requested`` defaults to ``LOCALAI_MESH_OVERLAP`` (unset: "auto").
    "" keeps the GSPMD decode; the reason explains any gate that fired
    (empty when the requested mode is simply honored)."""
    if requested is None:
        requested = os.environ.get("LOCALAI_MESH_OVERLAP", "")
    req = (requested or "auto").strip().lower()
    if req in ("0", "off", "none"):
        return "", ""
    if req not in ("auto", "1", "overlap", "psum"):
        return "", f"unknown LOCALAI_MESH_OVERLAP value {requested!r}"
    want = "psum" if req == "psum" else "overlap"
    if mesh is None:
        return "", ""
    tp = mesh.shape.get("model", 1)
    if tp <= 1:
        return "", ""
    busy = [ax for ax in ("data", "seq", "expert", "pipe")
            if mesh.shape.get(ax, 1) > 1]
    if busy:
        return "", (f"mesh also shards {busy}; manual-TP overlap needs "
                    "'model' as the only busy axis")
    if cfg.num_experts:
        return "", "MoE decode stays on the GSPMD path"
    if (cfg.num_heads % tp or cfg.num_kv_heads % tp
            or cfg.intermediate_size % tp or cfg.hidden_size % tp):
        return "", (
            f"heads ({cfg.num_heads} q / {cfg.num_kv_heads} kv), ffn "
            f"({cfg.intermediate_size}) or hidden ({cfg.hidden_size}) "
            f"not divisible by tensor_parallel {tp}")
    return want, ""


def make_reduce(mode: str, tp: int, chunks: int = CHUNKS,
                axis_name: str = "model"):
    """The row-parallel reduction for the manual-TP trunk.

    "psum": one fused all-reduce (the parity reference). "overlap": split
    the product into ``chunks`` independent psum_scatter+all_gather pairs
    along the hidden dim so their ICI transfers overlap neighboring
    compute. Falls back chunk-by-chunk to the largest split the dim
    supports; an indivisible dim degrades to the plain psum."""
    if tp <= 1:
        return None
    if mode == "psum":
        return lambda x: lax.psum(x, axis_name)

    def overlap_reduce(x):
        d = x.shape[-1]
        n = max(1, min(chunks, d))
        while n > 1 and d % (n * tp):
            n -= 1
        if d % tp:
            return lax.psum(x, axis_name)
        dim = x.ndim - 1
        pieces = jnp.split(x, n, axis=-1) if n > 1 else [x]
        out = [
            lax.all_gather(
                lax.psum_scatter(p, axis_name, scatter_dimension=dim,
                                 tiled=True),
                axis_name, axis=dim, tiled=True)
            for p in pieces
        ]
        return jnp.concatenate(out, axis=-1) if n > 1 else out[0]

    return overlap_reduce


def _embed_local(table, ids, dtype, axis_name: str = "model"):
    """Token gather under a vocab-sharded embedding: local rows + psum
    (same idiom as parallel.ring's sequence-parallel embed)."""
    v_local = table.shape[0]
    offset = lax.axis_index(axis_name) * v_local
    local = jnp.clip(ids - offset, 0, v_local - 1)
    rows = qnt.embed_rows(table, local, dtype)
    in_range = ((ids >= offset) & (ids < offset + v_local))[..., None]
    return lax.psum(jnp.where(in_range, rows, 0), axis_name)


def paged_decode_trunk(
    cfg: LlamaConfig,
    trunk: Any,              # {embed, final_norm, layers} param subset
    mesh: Mesh,
    tokens: jax.Array,       # [S] i32
    positions: jax.Array,    # [S] i32
    kv_stacked: tuple,       # PagedKVCache.stacked() — pool (+ scales)
    tables: jax.Array,       # [S, MB] i32 device table mirror
    rope: tuple[jax.Array, jax.Array],
    *,
    ctx_pad: int,
    mode: str = "overlap",
    use_pallas: bool = False,
    interpret: bool = False,
) -> tuple[jax.Array, tuple]:
    """One batched single-token paged decode FORWARD under manual tensor
    parallelism: returns (hidden [S, 1, D] replicated, new kv_stacked pool
    sharded as it arrived). Sampling/logits stay outside (the caller's
    ``_decode_tail`` — vocab-sharded logits keep their GSPMD path).

    The shard_map body is the per-device slice of the trunk: Megatron
    column/row-parallel matmuls over the local head/ffn shard, the paged
    attention (Pallas kernel or the gather ref) over the local kv-head
    shard of the pool, the KV write through the (replicated, data==1)
    block tables into the local shard (by the kernel itself on an unscaled
    pool, else the policy's scatter), and the two per-layer reductions
    via :func:`make_reduce` — decomposed when ``mode="overlap"``."""
    from localai_tpu.engine import kvcache as kvc
    from localai_tpu.parallel import sharding as shd

    tp = mesh.shape["model"]
    pspec = shd.tp_param_specs(cfg, mesh, trunk)
    embed_spec = pspec["embed"].q if hasattr(pspec["embed"], "q") \
        else pspec["embed"]
    embed_sharded = tuple(embed_spec)[:1] == ("model",)
    dtype = jnp.dtype(cfg.dtype)
    quantized = len(kv_stacked) == 4
    heads = "model" if cfg.num_kv_heads % tp == 0 else None
    pool_spec = P(None, None, heads, None, None)
    scale_spec = P(None, None, heads, None)
    kv_specs = ((pool_spec, pool_spec, scale_spec, scale_spec)
                if quantized else (pool_spec, pool_spec))

    def local_fn(trunk, tokens, positions, kv_stacked, tables,
                 cos_t, sin_t):
        reduce = make_reduce(mode, tp)
        mask = kvc.decode_mask(cfg, positions, ctx_pad)
        write = kvc.paged_decode_write(tables, positions, raw=use_pallas)
        if embed_sharded:
            x = _embed_local(trunk["embed"], tokens[:, None], dtype)
        else:
            x = qnt.embed_rows(trunk["embed"], tokens[:, None], dtype)
        attn = None
        if use_pallas:
            from localai_tpu import ops

            # over kvc.LayerViews of the local pool shard: each chip's
            # kernel writes the step's rows of its own heads (unscaled
            # pools; a scaled one's policy has scattered them)
            attn = kvc.kernel_attend(
                partial(ops.paged_decode_attention,
                        sliding_window=cfg.sliding_window,
                        interpret=interpret),
                tables, positions)

        hidden, new_stack = mdl.forward(
            cfg, trunk, tokens[:, None], positions[:, None],
            write, kv_stacked, mask, (cos_t, sin_t),
            attn=attn, embeds=x, reduce=reduce,
        )
        return hidden, new_stack

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(pspec, P(None), P(None), kv_specs, P(None, None),
                  P(), P()),
        out_specs=(P(None, None, None), kv_specs),
        check_vma=False,
    )
    return fn(trunk, tokens, positions, kv_stacked, tables,
              rope[0], rope[1])
