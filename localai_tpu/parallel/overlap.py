"""The manual tensor-parallel trunk of the meshed paged decode step.

The meshed decode runs the SAME trunk math as GSPMD's (pjit +
NamedSharding) as a manual shard_map over the mesh, like parallel.ring
does for sequence-parallel prefill: each chip's paged kernel attends and
writes its own kv heads of the pool, and the two row-parallel products of
a layer (attention-out, mlp-down) are summed by ONE ``lax.psum`` each
under the scope ``mesh.reduce`` (:func:`make_reduce`): two all-reduces a
layer, which is what a profiler's trace shows of the mesh. At one token a
slot the message is [S, 1, D] and latency-bound (~10 us on a v5e 2x2):
there is no matmul under which a piece of it could hide, so it is not
split. The TPU compiler folds a chunked ``psum_scatter`` + ``all_gather``
back into one all-reduce and then gathers, a chunk at a time, what every
chip already holds: a quarter of the step (PERF.md 6, PR 49).

Scope gates (``resolve_mode``): paged KV, 'model' the only busy mesh axis
(data/seq/expert/pipe == 1 — the pool writes of distinct data shards
cannot be reconciled manually without an extra collective), dense MLP,
and tp dividing heads/kv-heads/ffn/hidden. Everything else keeps the
GSPMD path. Knob, read by ``resolve_mode`` alone: ``LOCALAI_MESH_OVERLAP``
= auto/1 (the manual trunk where supported, the default) or ``0`` (GSPMD:
the tests' parity reference).
"""

from __future__ import annotations

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

TRUNK_KEYS = ("embed", "final_norm", "layers")


def resolve_mode(cfg: LlamaConfig, mesh: Optional[Mesh],
                 requested: Optional[str] = None) -> tuple[str, str]:
    """Who serves the meshed decode trunk: ("manual" | "", reason).

    ``requested`` defaults to ``LOCALAI_MESH_OVERLAP`` (unset: "auto").
    "" keeps the GSPMD decode; the reason explains any gate that fired
    (empty when the request is simply honored)."""
    if requested is None:
        requested = os.environ.get("LOCALAI_MESH_OVERLAP", "")
    req = (requested or "auto").strip().lower()
    if req in ("0", "off", "none"):
        return "", ""
    if req not in ("auto", "1"):
        return "", f"unknown LOCALAI_MESH_OVERLAP value {requested!r}"
    if mesh is None:
        return "", ""
    tp = mesh.shape.get("model", 1)
    if tp <= 1:
        return "", ""
    busy = [ax for ax in ("data", "seq", "expert", "pipe")
            if mesh.shape.get(ax, 1) > 1]
    if busy:
        return "", (f"mesh also shards {busy}; the manual-TP trunk needs "
                    "'model' as the only busy axis")
    if cfg.num_experts:
        return "", "MoE decode stays on the GSPMD path"
    if (cfg.num_heads % tp or cfg.num_kv_heads % tp
            or cfg.intermediate_size % tp or cfg.hidden_size % tp):
        return "", (
            f"heads ({cfg.num_heads} q / {cfg.num_kv_heads} kv), ffn "
            f"({cfg.intermediate_size}) or hidden ({cfg.hidden_size}) "
            f"not divisible by tensor_parallel {tp}")
    return "manual", ""


def make_reduce(tp: int, axis_name: str = "model"):
    """The row-parallel reduction of the manual-TP trunk: one all-reduce a
    product, under a scope of its own so that a trace holds it apart from
    the matmul in front of it. None on one device."""
    if tp <= 1:
        return None

    def reduce(x):
        with jax.named_scope("mesh.reduce"):
            return lax.psum(x, axis_name)

    return reduce


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
    via :func:`make_reduce`."""
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
        reduce = make_reduce(tp)
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
