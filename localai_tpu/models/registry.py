"""Model resolution: a model ref → (config, params, tokenizer).

The TPU analogue of backend selection + GGUF autoconfig
(/root/reference/pkg/model/initializers.go:65-267 and
core/config/guesser.go): instead of scanning binary variants per CPU flag,
we resolve a weights ref to one JAX model family and load it.

Refs:
  * a local dir with config.json + *.safetensors  → HF checkpoint
  * "debug:tiny" / "debug:small" / "debug:1b" ... → random-weight presets
    (byte tokenizer; used by tests, chip_smoke.py and synthetic benchmarks)
"""

from __future__ import annotations

import dataclasses
from functools import partial
from pathlib import Path
from typing import Any, Optional

import jax
import jax.numpy as jnp

from localai_tpu.models.llama import LlamaConfig
from localai_tpu.utils.tokenizer import ByteTokenizer, Tokenizer, load_tokenizer

# Synthetic presets: shapes only, random weights. "llama3-8b" matches
# Llama-3-8B dims for honest perf measurement without weight downloads.
DEBUG_PRESETS: dict[str, LlamaConfig] = {
    "tiny": LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=128, num_layers=2,
        num_heads=4, num_kv_heads=2, max_position_embeddings=512,
        rope_theta=10000.0,
    ),
    "small": LlamaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512, num_layers=4,
        num_heads=8, num_kv_heads=4, max_position_embeddings=2048,
    ),
    "tiny-moe": LlamaConfig(
        vocab_size=512, hidden_size=64, intermediate_size=96, num_layers=2,
        num_heads=4, num_kv_heads=2, max_position_embeddings=512,
        num_experts=4, num_experts_per_tok=2,
    ),
    # a looped decoder (model_type ouro): 2 sandwich layers run 3 times a
    # token, 6 cache layers; plain multi-head at the compiled kernels'
    # head_dim (chip_smoke.py serves it beside the 8B)
    "tiny-loop": LlamaConfig(
        vocab_size=512, hidden_size=256, intermediate_size=512, num_layers=2,
        num_heads=2, num_kv_heads=2, head_dim=128,
        max_position_embeddings=512, rms_norm_eps=1e-6,
        num_passes=3, post_norm=True,
    ),
    "1b": LlamaConfig(
        vocab_size=128256, hidden_size=2048, intermediate_size=8192,
        num_layers=16, num_heads=32, num_kv_heads=8,
        max_position_embeddings=8192, rope_theta=500000.0,
        tie_word_embeddings=True,
    ),
    "llama3-8b": LlamaConfig(
        vocab_size=128256, hidden_size=4096, intermediate_size=14336,
        num_layers=32, num_heads=32, num_kv_heads=8,
        max_position_embeddings=8192, rope_theta=500000.0,
    ),
}


@dataclasses.dataclass
class LoadedModel:
    cfg: LlamaConfig
    params: Any
    tokenizer: Tokenizer
    ref: str
    model_dir: Optional[Path] = None   # resolved checkpoint dir (None: debug)
    hf_type: str = ""                  # config.json model_type ("llava", ...)
    image_token_id: Optional[int] = None  # HF image_token_index when present


def resolve_config(ref: str, model_path: str | Path = "models",
                   dtype: str = "bfloat16") -> LlamaConfig:
    """The model's shape without its weights — what the manager needs to
    lay out the mesh and the per-leaf placement BEFORE anything is loaded."""
    if ref.startswith("debug:"):
        name = ref.split(":", 1)[1]
        if name not in DEBUG_PRESETS:
            raise ValueError(
                f"unknown debug preset {name!r}; have {sorted(DEBUG_PRESETS)}"
            )
        return dataclasses.replace(DEBUG_PRESETS[name], dtype=dtype)
    cand = _checkpoint_dir(ref, model_path)
    from localai_tpu.models.loader import load_hf_config

    return dataclasses.replace(load_hf_config(cand), dtype=dtype)


def _checkpoint_dir(ref: str, model_path: str | Path) -> Path:
    for cand in (Path(ref), Path(model_path) / ref):
        if (cand / "config.json").exists():
            return cand
    raise FileNotFoundError(
        f"model ref {ref!r} not found (looked for config.json under {ref} and "
        f"{Path(model_path) / ref})"
    )


def resolve_model(
    ref: str,
    model_path: str | Path = "models",
    dtype: str = "bfloat16",
    seed: int = 0,
    quantization: str = "",
    placement=None,
) -> LoadedModel:
    """Load (or synthesize) a model in its SERVED form: with
    ``quantization`` set the params come back quantized, and with a
    ``placement`` (parallel.sharding.ParamPlacement) every leaf is created
    on the devices that will hold it — checkpoint leaves are quantized on
    the host and sent shard by shard, debug presets are generated in place.
    The bf16 model is never resident on a device when a quantized one was
    asked for."""
    if ref.startswith("debug:"):
        cfg = resolve_config(ref, model_path, dtype)
        params = synthetic_params(cfg, quantization, seed=seed,
                                  placement=placement)
        return LoadedModel(cfg, params, ByteTokenizer(), ref)

    cand = _checkpoint_dir(ref, model_path)
    from localai_tpu.models.loader import load_llama_params, read_hf_config

    hf = read_hf_config(cand)
    cfg, params = load_llama_params(
        cand, dtype=dtype, hf=hf, quantization=quantization,
        placement=placement,
    )
    cfg = dataclasses.replace(cfg, dtype=dtype)
    return LoadedModel(
        cfg, params, load_tokenizer(cand), ref,
        model_dir=cand,
        hf_type=hf.get("model_type", ""),
        image_token_id=hf.get("image_token_index"),
    )


def synthetic_params(cfg: LlamaConfig, quantization: str = "", *,
                     seed: int = 0, placement=None) -> Any:
    """Seeded random weights for a debug preset, generated leaf by leaf
    DIRECTLY in their served form and on the devices that will hold them
    (each leaf is one jitted program whose ``out_shardings`` come from
    ``placement``). An 8B-class bf16 init (16 GB) does not fit a v5e chip,
    its int8 form (8 GB) does; this is how the server loads
    ``debug:llama3-8b`` with ``engine.quantization`` set.

    Without ``quantization`` this is models.llama.init_params. Quantized
    leaves are uniform random integers over the full range with a constant
    scale chosen so the dequantized weights keep init_params' 0.02
    amplitude — activations stay in a realistic range without ever
    materializing a float copy to quantize; norm gains are 1, biases 0."""
    from localai_tpu.models import llama as mdl
    from localai_tpu.models.quant import (QuantizedTensor, _group_size,
                                          quantize_plan)

    if not quantization:
        return mdl.init_params(jax.random.key(seed), cfg, placement)
    fam = mdl.family_module(cfg)
    mdl.refuse_quantization(cfg, quantization)
    shapes = mdl.param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(jax.random.key(seed), len(flat))
    dtype = jnp.dtype(cfg.dtype)

    def plain(key, shape, name):
        if fam is not None:     # the family's own draw of what stays plain
            return fam.init_leaf(key, shape, name, dtype, cfg)
        if name.endswith("norm"):
            return jnp.ones(shape, dtype)
        if name in ("bq", "bk", "bv"):
            return jnp.zeros(shape, dtype)
        # the tiny MoE router stays in the compute dtype
        return (jax.random.normal(key, shape, jnp.float32) * 0.02
                ).astype(dtype)

    def quantized(key, shape, name, axis, mode):
        bits = 4 if mode == "int4" else 8
        lim = 7 if bits == 4 else 127
        # init_params' 0.02 amplitude; a family that serves a quantised
        # mode states its matrices' deviations (``leaf_std``: a number, or
        # one an output column), and uniform integers of amplitude ``lim``
        # have deviation lim / sqrt 3
        amp = 0.02 if fam is None else jnp.asarray(
            fam.leaf_std(cfg, name), jnp.float32) * 3 ** 0.5
        # raw uint8 bits reinterpreted as int8 — no int32 intermediates
        # (randint would spike 4× the tensor size during generation)
        v = jax.lax.bitcast_convert_type(
            jax.random.bits(key, shape, jnp.uint8), jnp.int8)
        if bits == 4:
            q = jnp.maximum(v >> 4, -7).astype(jnp.int4)
            K = shape[axis]
            sshape = (shape[:axis] + (K // _group_size(K, 128),)
                      + shape[axis + 1:])
        else:
            q = jnp.maximum(v, -127)
            sshape = shape[:axis] + shape[axis + 1:]
        mm = {"int4": "w4", "int8_w8a8": "w8a8"}.get(mode, "w8")
        return QuantizedTensor(
            q=q, scale=jnp.broadcast_to(amp / lim, sshape).astype(jnp.float32),
            axis=axis, mode=mm)

    leaves = []
    for key, (kpath, shape) in zip(keys, flat):
        path = tuple(k.key for k in kpath)
        plan = quantize_plan(path, len(shape), quantization)
        if plan is None:
            make = partial(plain, key, shape, path[-1])
        else:
            make = partial(quantized, key, shape, path[-1], *plan)
        shardings = (placement.shardings(path, jax.eval_shape(make))
                     if placement is not None else None)
        # one program per leaf: XLA fuses generation into the (sharded)
        # output, so a leaf's working set is the leaf itself
        leaves.append(jax.jit(  # jaxlint: disable=jit-in-loop
            make, out_shardings=shardings)())
    return jax.tree.unflatten(treedef, leaves)


def resolve_tokenizer(ref: str, model_path: str | Path = "models"):
    """Tokenizer-only resolution — never touches weights (the tokenize CLI
    and API must not pull GBs of params into RAM to encode a string)."""
    if ref.startswith("debug:"):
        return ByteTokenizer()
    for cand in (Path(ref), Path(model_path) / ref):
        if cand.is_dir():
            return load_tokenizer(cand)
    raise FileNotFoundError(f"model ref {ref!r} not found under {model_path}")
