"""Weight ingestion: HF safetensors → stacked JAX param pytrees.

The TPU replacement for GGUF ingestion (the reference's weight path is
llama.cpp's GGUF mmap, /root/reference/pkg/model + gguf autoconfig
core/config/guesser.go:13-246): we ingest the HF safetensors layout
directly, transpose to right-multiply convention, and stack per-layer
tensors along a leading axis so the model can lax.scan over layers.
"""

from __future__ import annotations

import dataclasses
import json
import logging
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from localai_tpu.models.llama import (LlamaConfig, family_module,
                                      param_shapes, refuse_quantization)

log = logging.getLogger(__name__)


def read_hf_config(model_dir: str | Path) -> dict:
    with open(Path(model_dir) / "config.json") as f:
        return json.load(f)


def load_hf_config(model_dir: str | Path) -> LlamaConfig:
    hf = read_hf_config(model_dir)
    if hf.get("model_type") == "llava":
        # LLaVA composite checkpoint: the language model is described by
        # text_config and stored under the language_model. prefix
        return LlamaConfig.from_hf(hf.get("text_config", {}))
    return LlamaConfig.from_hf(hf)


def _open_safetensors(model_dir: Path) -> dict[str, Any]:
    """Return name → lazy tensor accessor across all shards."""
    from safetensors import safe_open

    tensors: dict[str, Any] = {}
    files = sorted(model_dir.glob("*.safetensors"))
    if not files:
        raise FileNotFoundError(f"no .safetensors files in {model_dir}")
    for fp in files:
        handle = safe_open(str(fp), framework="numpy")
        for name in handle.keys():
            tensors[name] = (handle, name)
    return tensors


def _get(tensors: dict, name: str) -> np.ndarray:
    handle, key = tensors[name]
    arr = handle.get_tensor(key)
    # bfloat16 arrives as uint16 view from some writers; reinterpret via ml_dtypes
    if arr.dtype == np.uint16:
        import ml_dtypes

        arr = arr.view(ml_dtypes.bfloat16)
    return arr


def load_llama_params(
    model_dir: str | Path,
    cfg: Optional[LlamaConfig] = None,
    dtype: str = "bfloat16",
    hf: Optional[dict] = None,
    quantization: str = "",
    placement=None,
) -> tuple[LlamaConfig, Any]:
    """Load an HF llama/mistral/qwen2/ouro/qwen3_next/afmoe checkpoint into the
    stacked pytree, one leaf at a time: read and stack on the host, cast (or,
    with ``quantization``, quantize — models.quant.quantize_tensor_host) on
    the host, then hand the SERVED form to ``placement.put``
    (parallel.sharding.ParamPlacement), which sends each device its shard.
    Neither the bf16 model nor an f32 copy of any leaf ever exists on a
    device, and the host holds one stacked leaf at a time. ``hf`` is the
    already-parsed config.json (avoids re-reading when the caller has it).
    """
    from localai_tpu.models.quant import quantize_plan, quantize_tensor_host

    model_dir = Path(model_dir)
    if hf is None:
        hf = read_hf_config(model_dir)
    # tensor-name layout: plain llama vs llava composite (classic
    # language_model.model.* layout, or model.language_model.* in
    # transformers ≥4.52 exports)
    body, head = "model.", "lm_head.weight"
    is_llava = hf.get("model_type") == "llava"
    if is_llava:
        body, head = "language_model.model.", "language_model.lm_head.weight"
    if cfg is None:
        cfg = LlamaConfig.from_hf(hf.get("text_config", {}) if is_llava else hf)
    tensors = _open_safetensors(model_dir)
    if body + "embed_tokens.weight" not in tensors:
        if "model.language_model.embed_tokens.weight" in tensors:
            body, head = "model.language_model.", "lm_head.weight"
    if not cfg.tie_word_embeddings and head not in tensors:
        cfg = dataclasses.replace(cfg, tie_word_embeddings=True)
    np_dtype = np.dtype(jnp.dtype(dtype))
    expected = param_shapes(cfg)
    fam = family_module(cfg)

    def place(path: tuple[str, ...], a: np.ndarray):
        want = expected[path[0]] if len(path) == 1 else expected[path[0]][path[1]]
        if tuple(a.shape) != tuple(want):
            raise ValueError(
                f"param {path}: shape {a.shape} != expected {want}")
        plan = quantize_plan(path, a.ndim, quantization) if quantization else None
        # (a family that prefixes a leaf's name by its group says how to
        # take the prefix off: ``base_name``)
        if (getattr(fam, "base_name", str)(path[-1])
                in getattr(fam, "FLOAT32_LEAVES", ())):
            leaf = a.astype(np.float32)     # as published, whatever ``dtype``
        elif plan is None:
            # source dtype stays on the host until here (bf16 checkpoints
            # stay 2 bytes/elem); the cast is a host pass too
            leaf = a.astype(np_dtype, copy=False)
        else:
            leaf = quantize_tensor_host(a, *plan)
        if placement is None:
            return jax.device_put(leaf)
        return placement.put(path, leaf)

    def stack(fmt: str, transpose: bool) -> np.ndarray:
        mats = []
        for i in range(cfg.num_layers):
            a = _get(tensors, fmt.format(i=i))
            mats.append(a.T if transpose else a)
        return np.stack(mats)

    if fam is not None:
        # a family with a pytree of its own (models.qwen3_next: periods of
        # DeltaNet and gated-attention layers; models.afmoe: a dense prefix
        # beside rows of window and full layers): its own names and
        # regrouping. A leaf that is no ``layers`` leaf is a top-level one
        refuse_quantization(cfg, quantization)
        layers, top = {}, {}
        for name, host in fam.checkpoint_leaves(
                cfg, lambda n: _get(tensors, n), body):
            if name in expected["layers"]:
                layers[name] = place(("layers", name), host)
            else:
                top[name] = place((name,), host)
        if "final_norm" not in top:     # (a family may bring its own name)
            top["final_norm"] = place(("final_norm",),
                                      _get(tensors, body + "norm.weight"))
        return cfg, {
            "embed": place(("embed",),
                           _get(tensors, body + "embed_tokens.weight")),
            "layers": layers, **top,
            **({} if cfg.tie_word_embeddings else {
                "lm_head": place(("lm_head",), _get(tensors, head).T)})}

    L = body + "layers.{i}."
    layer_src: dict[str, Any] = {
        "attn_norm": (L + "input_layernorm.weight", False),
        "wq": (L + "self_attn.q_proj.weight", True),
        "wk": (L + "self_attn.k_proj.weight", True),
        "wv": (L + "self_attn.v_proj.weight", True),
        "wo": (L + "self_attn.o_proj.weight", True),
        "mlp_norm": (L + "post_attention_layernorm.weight", False),
    }
    if cfg.post_norm:
        # the looped decoder's sandwich layer: each branch's output norm
        layer_src["attn_post_norm"] = (L + "input_layernorm_2.weight", False)
        layer_src["mlp_post_norm"] = (
            L + "post_attention_layernorm_2.weight", False)
    if cfg.num_experts:
        # Mixtral layout: block_sparse_moe.gate (router) +
        # experts.{j}.w1/w3/w2 (gate/up/down) → expert-stacked [L, E, K, N]
        def stack_experts(wname: str) -> np.ndarray:
            outer = []
            for i in range(cfg.num_layers):
                outer.append(np.stack([
                    _get(tensors,
                         f"{body}layers.{i}.block_sparse_moe."
                         f"experts.{j}.{wname}.weight").T
                    for j in range(cfg.num_experts)
                ]))
            return np.stack(outer)

        layer_src["moe_gate"] = (L + "block_sparse_moe.gate.weight", True)
        layer_src.update(w_gate="w1", w_up="w3", w_down="w2")
    else:
        layer_src["w_gate"] = (L + "mlp.gate_proj.weight", True)
        layer_src["w_up"] = (L + "mlp.up_proj.weight", True)
        layer_src["w_down"] = (L + "mlp.down_proj.weight", True)
    if cfg.attention_bias:
        layer_src["bq"] = (L + "self_attn.q_proj.bias", False)
        layer_src["bk"] = (L + "self_attn.k_proj.bias", False)
        layer_src["bv"] = (L + "self_attn.v_proj.bias", False)

    # one leaf at a time: build on the host, place, drop the host copy
    layers = {}
    for name, src in layer_src.items():
        host = stack_experts(src) if isinstance(src, str) else stack(*src)
        layers[name] = place(("layers", name), host)
        del host
    params: dict[str, Any] = {
        "embed": place(("embed",), _get(tensors, body + "embed_tokens.weight")),
        "final_norm": place(("final_norm",), _get(tensors, body + "norm.weight")),
        "layers": layers,
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = place(("lm_head",), _get(tensors, head).T)
    return cfg, params
