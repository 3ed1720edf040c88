"""Sharding rules: how the llama engine lays out over the device mesh.

This module is the compiled-SPMD replacement for the reference's entire
multi-device story — llama.cpp tensor_split/main_gpu
(/root/reference/core/config/backend_config.go:116-117, backend/cpp/llama/
grpc-server.cpp:2240-2262), the RPC weight-sharding worker mode
(grpc-server.cpp:2233-2236), and vLLM's tensor_parallel_size passthrough
(backend/python/vllm/backend.py:102-103). Instead of shipping tensors over
TCP, we annotate NamedShardings and let XLA insert ICI collectives.

Layout (Megatron-style TP on the 'model' axis, slots on 'data'):

  wq/wk/wv  [L, D, H*hd]   → P(None, None, 'model')   column-parallel
  wo        [L, H*hd, D]   → P(None, 'model', None)   row-parallel
  w_gate/up [L, D, F]      → P(None, None, 'model')
  w_down    [L, F, D]      → P(None, 'model', None)
  embed     [V, D]         → P('model', None)         vocab-sharded
  lm_head   [D, V]         → P(None, 'model')         vocab-sharded logits
  norms                    → replicated
  KV cache  [L, S, Hkv, C, hd] → P(None, 'data', 'model', None, None)
  counts/bias [S, V]       → P('data', 'model')

With this layout one decode step needs exactly two psums per layer (after
attention-out and after mlp-down) plus one all-gather for sampled logits'
top-k — the standard Megatron inference communication pattern, riding ICI.
"""

from __future__ import annotations

import logging
from typing import Any, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from localai_tpu.models.llama import LlamaConfig

log = logging.getLogger(__name__)


def _sanitize(spec: P, shape: tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes whose size does not divide the tensor dim (replicate
    that dim instead) — keeps odd vocab/ffn sizes loadable on any mesh."""
    out = []
    for i, axis in enumerate(spec):
        if axis is None:
            out.append(None)
            continue
        size = mesh.shape[axis]
        if shape[i] % size != 0:
            log.warning(
                "dim %d of shape %s not divisible by mesh axis %r (%d); "
                "replicating", i, shape, axis, size,
            )
            out.append(None)
        else:
            out.append(axis)
    return P(*out)


def param_specs(
    cfg: LlamaConfig, mesh: Mesh, shapes: Optional[dict] = None
) -> dict:
    """PartitionSpec pytree matching models.llama.param_shapes (divisibility-
    sanitized against the mesh)."""
    if cfg.recurrent:
        # periods of DeltaNet and gated-attention layers with routed experts
        # (models.qwen3_next): the experts over 'expert', everything else of
        # a layer whole on every chip (data-parallel attention beside
        # expert-parallel experts), the vocabulary over 'model'. The specs
        # of a deployment; engine.runner takes no mesh for such a model yet
        from localai_tpu.models import qwen3_next

        shapes = shapes or qwen3_next.param_shapes(cfg)
        expert = {n: P(None, None, "expert", None, None)
                  for n in qwen3_next.EXPERT_LEAVES}
        specs = {"embed": P("model", None), "final_norm": P(),
                 "lm_head": P(None, "model"),
                 "layers": {n: expert.get(n, P())
                            for n in shapes["layers"]}}
        return jax.tree.map(
            lambda sh, sp: _sanitize(sp, sh, mesh), shapes,
            {k: specs[k] for k in shapes},
            is_leaf=lambda x: isinstance(x, tuple))
    tp = mesh.shape["model"]
    if cfg.num_heads % tp != 0:
        raise ValueError(
            f"num_heads {cfg.num_heads} not divisible by tensor_parallel {tp}"
        )
    specs: dict[str, Any] = {
        "embed": P("model", None),
        "final_norm": P(),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, None, "model"),
            "wk": P(None, None, "model"),
            "wv": P(None, None, "model"),
            "wo": P(None, "model", None),
            "mlp_norm": P(None, None),
            "w_gate": P(None, None, "model"),
            "w_up": P(None, None, "model"),
            "w_down": P(None, "model", None),
        },
    }
    if cfg.post_norm:
        specs["layers"]["attn_post_norm"] = P(None, None)
        specs["layers"]["mlp_post_norm"] = P(None, None)
    if cfg.attention_bias:
        specs["layers"]["bq"] = P(None, "model")
        specs["layers"]["bk"] = P(None, "model")
        specs["layers"]["bv"] = P(None, "model")
    if cfg.num_experts:
        # Mixtral-class MoE: experts over 'expert' (expert parallelism),
        # ffn width over 'model' (TP) — the two compose; the router is tiny
        # and replicated
        specs["layers"]["moe_gate"] = P(None, None, None)
        specs["layers"]["w_gate"] = P(None, "expert", None, "model")
        specs["layers"]["w_up"] = P(None, "expert", None, "model")
        specs["layers"]["w_down"] = P(None, "expert", "model", None)
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, "model")

    from localai_tpu.models.llama import param_shapes

    shapes = shapes or param_shapes(cfg)
    return jax.tree.map(
        lambda sp, sh: _sanitize(sp, sh, mesh),
        specs, shapes,
        is_leaf=lambda x: isinstance(x, (P, tuple)) and not isinstance(x, dict),
    )


def kv_spec(cfg: LlamaConfig, mesh: Mesh) -> P:
    """KV cache [L, S, Hkv, C, hd]: layers on 'pipe' (pipeline capacity
    mode), slots on 'data', kv heads on 'model'.

    When tp does not divide the kv-head count (deep-GQA models on wide
    meshes), the kv heads are replicated instead — attention q-heads stay
    sharded and XLA broadcasts the cache reads.
    """
    tp = mesh.shape["model"]
    heads = "model" if cfg.num_kv_heads % tp == 0 and tp <= cfg.num_kv_heads else None
    if heads is None and tp > 1:
        log.warning(
            "kv heads (%d) not divisible by tensor_parallel (%d); "
            "replicating KV cache", cfg.num_kv_heads, tp,
        )
    layers = ("pipe" if mesh.shape.get("pipe", 1) > 1
              and cfg.num_layers % mesh.shape["pipe"] == 0 else None)
    return P(layers, "data", heads, None, None)


def paged_kv_spec(cfg: LlamaConfig, mesh: Mesh) -> P:
    """Paged block pool [L, num_blocks, Hkv, block_tokens, hd]: kv heads on
    'model', everything else replicated.

    The pool has no slot axis — blocks are shared by all slots through the
    host-side block tables — so unlike the contiguous cache there is
    nothing to put on 'data'; the [S, MB] device table mirror carries the
    'data' sharding instead (runner.block_tables). The block axis stays
    unsharded on purpose: table values are global physical block ids, and
    every device must be able to walk any slot's table against its own
    head shard. Same deep-GQA fallback as kv_spec: when tp does not
    divide the kv-head count the pool replicates and q-heads stay
    sharded."""
    tp = mesh.shape["model"]
    heads = ("model" if cfg.num_kv_heads % tp == 0 and tp <= cfg.num_kv_heads
             else None)
    if heads is None and tp > 1:
        log.warning(
            "kv heads (%d) not divisible by tensor_parallel (%d); "
            "replicating the paged KV pool", cfg.num_kv_heads, tp,
        )
    return P(None, None, heads, None, None)


def block_table_spec() -> P:
    """Device mirror of the allocator's block tables [S, MB]: slots on
    'data' alongside DecodeState, columns replicated."""
    return P("data", None)


def tp_param_specs(cfg: LlamaConfig, mesh: Mesh, params: Any) -> Any:
    """Per-leaf PartitionSpecs for (a subset of) the trunk params under
    manual tensor parallelism ('model' axis), mirroring shard_params'
    placement — quantized leaves expand to (q, scale) specs. The single
    spec source for every manual-SPMD shard_map over the trunk
    (parallel.ring sequence-parallel prefill, parallel.overlap decode)."""
    specs = param_specs(cfg, mesh, shapes=None)
    # drop spec entries (e.g. lm_head) the caller's param subset omits
    specs = {k: v for k, v in specs.items() if k in params}
    return jax.tree.map(
        lambda sp, arr: expand_quantized_spec(sp, arr, mesh),
        specs, {k: params[k] for k in specs},
        is_leaf=lambda x: isinstance(x, P),
    )


def state_specs(mesh: Mesh) -> dict:
    """PartitionSpecs for DecodeState fields (see engine.runner)."""
    return {
        "tokens": P("data"),
        "positions": P("data"),
        "active": P("data"),
        "keys": P("data"),
        "counts": P("data", "model"),
        "bias": P("data", "model"),
        "params": P("data"),
    }


def expand_quantized_spec(spec_leaf: P, arr: Any, mesh: Mesh) -> Any:
    """Spec for one param leaf: plain arrays keep ``spec_leaf``; quantized
    weights (models.quant.QuantizedTensor) expand to a QuantizedTensor of
    specs — q with the weight's spec, the per-output-channel scale with the
    same spec minus the contracted axis — so a 'model'-sharded weight keeps
    its scales sharded alongside its output channels and the dequant
    epilogue stays local. The single source of truth for both placement
    (shard_params) and manual-SPMD in_specs (parallel.ring)."""
    from localai_tpu.models.quant import QuantizedTensor, quantized_spec

    if isinstance(arr, QuantizedTensor):
        s_spec = _sanitize(
            quantized_spec(spec_leaf, arr.axis, grouped=arr.mode == "w4"),
            arr.scale.shape, mesh,
        )
        # carry ALL metadata from the source tensor: tree.map pairs this
        # spec tree with the param tree, and aux data (axis, mode,
        # kernel_ok) is part of treedef equality
        return QuantizedTensor(
            q=spec_leaf, scale=s_spec, axis=arr.axis, mode=arr.mode,
            kernel_ok=arr.kernel_ok)
    return spec_leaf


def shard_params(
    params: Any, cfg: LlamaConfig, mesh: Mesh
) -> Any:
    """Place an already-loaded param pytree onto the mesh, leaf by leaf
    through :class:`ParamPlacement`. Tests and tools only: the serving load
    path never holds the unsharded tree on a device in the first place."""
    from localai_tpu.models.quant import QuantizedTensor

    place = ParamPlacement(cfg, mesh)
    return jax.tree_util.tree_map_with_path(
        lambda kp, leaf: place.put(tuple(k.key for k in kp), leaf), params,
        is_leaf=lambda x: isinstance(x, QuantizedTensor))


class ParamPlacement:
    """Where each leaf of the llama param pytree lives, decided BEFORE the
    leaf exists so the load path can create it there: ``put`` sends a host
    leaf (numpy, or a QuantizedTensor of numpy) shard by shard to its
    devices, and ``shardings`` hands a generator the ``out_shardings`` to
    build a synthetic leaf in place. Nothing unsharded ever lands on one
    chip (a whole bf16 8B model is 16 GB — a v5e chip's entire HBM).

    Without a mesh every leaf goes to the default device."""

    def __init__(self, cfg: LlamaConfig, mesh: Optional[Mesh] = None):
        self.mesh = mesh
        self.specs: Optional[dict] = None
        if mesh is not None:
            if mesh.shape.get("pipe", 1) > 1:
                # layer-sharded capacity mode (parallel.pipeline)
                from localai_tpu.parallel.pipeline import pp_param_specs

                self.specs = pp_param_specs(cfg, mesh)
            else:
                self.specs = param_specs(cfg, mesh)

    def shardings(self, path: tuple[str, ...], leaf: Any) -> Any:
        """Sharding pytree for the leaf at ``path`` (``leaf`` may hold
        arrays, numpy or ShapeDtypeStructs — only its structure and scale
        shape are read); None means the default device."""
        if self.mesh is None:
            return None
        node: Any = self.specs
        for key in path:
            node = node[key]
        spec = expand_quantized_spec(node, leaf, self.mesh)
        return jax.tree.map(lambda s: NamedSharding(self.mesh, s), spec,
                            is_leaf=lambda x: isinstance(x, P))

    def put(self, path: tuple[str, ...], leaf: Any) -> Any:
        sh = self.shardings(path, leaf)
        return jax.device_put(leaf) if sh is None else jax.device_put(leaf, sh)


def slots_per_data_shard(num_slots: int, mesh: Mesh) -> int:
    dp = mesh.shape["data"]
    if num_slots % dp != 0:
        raise ValueError(f"num_slots {num_slots} not divisible by data={dp}")
    return num_slots // dp
