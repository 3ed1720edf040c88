"""TPU kernels (Pallas) and their selection policy.

The engine has three attention implementations:
  * "pallas"  — flash kernels (ops.attention), compiled by Mosaic; TPU only
  * "xla"     — pure-XLA grouped attention (models.llama._grouped_attn) and
                the gather-based paged reference; the default off-TPU and
                the numerical reference
  * "pallas_interpret" — the flash kernels in the Pallas interpreter (CPU
                tests and debugging; never chosen automatically)

``auto`` means "pallas" on a TPU backend and "xla" everywhere else. The
selectors never trade a kernel for XLA behind the caller's back: on a TPU
a shape the kernels cannot take is an error that names the gate and the
explicit ``attn_impl: xla`` override, and "pallas" without a TPU backend is
an error that names "pallas_interpret". Every combination a selector
answers "pallas" for is compiled for v5e in tests/test_tpu_compile.py.

The one input is the runner's ``attn_impl=`` (YAML ``engine.attn_impl``).
The routed experts of a model that has them as a loop over the touched ones
(models.qwen3_next) go the same way on the same input: ops.moe's grouped
kernel where attention's are kernels, the XLA loop under ``xla``
(``select_moe_impl``); the decode step of its DeltaNet layers goes with them
(ops.gdn's kernel, or ``gdn_step`` as XLA).
"""

from __future__ import annotations

import jax

from localai_tpu.ops.attention import (
    decode_attention,
    latent_decode_attention,
    paged_decode_attention,
    paged_decode_attention_ref,
    prefill_attention,
)

__all__ = [
    "decode_attention",
    "latent_decode_attention",
    "paged_decode_attention",
    "paged_decode_attention_ref",
    "prefill_attention",
    "resolve_attn_impl",
    "select_attn_impl",
    "select_latent_attn_impl",
    "select_moe_impl",
    "select_paged_attn_impl",
]

_OVERRIDE = ("set engine.attn_impl: xla to serve this shape with XLA "
             "attention instead")


def resolve_attn_impl(requested: str = "auto",
                      backend: str | None = None) -> tuple[str, bool]:
    """Returns (impl, interpret) with impl in {"xla", "pallas"}: auto →
    the backend default; validates the name; "pallas" off-TPU is an error,
    never a quiet switch to the interpreter."""
    impl, backend = requested, backend or jax.default_backend()
    if impl in ("auto", ""):
        impl = "pallas" if backend == "tpu" else "xla"
    if impl == "pallas_interpret":
        return "pallas", True
    if impl not in ("pallas", "xla"):
        raise ValueError(f"unknown attention impl {impl!r}")
    if impl == "pallas" and backend != "tpu":
        raise ValueError(
            f"attention impl 'pallas' needs a TPU backend (this one is "
            f"{backend!r}); ask for 'pallas_interpret' to run the kernels "
            f"in the Pallas interpreter")
    return impl, False


def _unpacked(head_dim: int) -> str:
    """What a refusal adds for heads that COULD share a 128-lane row
    (``ops.attention.heads_per_row``): the selectors are told the POOL's
    rows, so such heads arrive here only where nothing packed them."""
    if head_dim >= 128 or 128 % head_dim:
        return ""
    return (f": {128 // head_dim} K/V heads of {head_dim} share a 128-lane "
            f"row where the family packs them "
            f"(ops.attention.heads_per_row) and their count is a multiple "
            f"of {128 // head_dim}; these stand alone")


def _check_tp_heads(num_heads: int, num_kv_heads: int, tp: int) -> None:
    # under a mesh the flash kernels run per-device via shard_map (slots
    # on 'data', heads on 'model') — both head counts must split evenly or
    # the per-shard GQA grouping misaligns (a replicated-KV pool has no
    # per-shard head group to walk)
    if tp > 1 and (num_heads % tp or num_kv_heads % tp):
        raise ValueError(
            f"Pallas attention under tensor_parallel {tp} needs both head "
            f"counts ({num_heads} q / {num_kv_heads} kv) divisible by it; "
            f"{_OVERRIDE}")


def select_attn_impl(requested: str, *, num_heads: int, num_kv_heads: int,
                     head_dim: int, max_ctx: int, tp: int = 1,
                     backend: str | None = None) -> tuple[str, bool]:
    """The FULL engine attention-impl decision for the contiguous-cache
    programs (prefill_attention, decode_attention) — resolve_attn_impl
    plus every shape gate, as one pure function so CI can assert which
    path a given (model, mesh) lands on at hardware shapes.

    Returns (impl, interpret). Raises ValueError when the resolved impl is
    the compiled kernel and the shape cannot take it.
    """
    impl, interpret = resolve_attn_impl(requested, backend)
    if impl != "pallas":
        return impl, interpret
    _check_tp_heads(num_heads, num_kv_heads, tp)
    if not interpret and (head_dim % 128 or max_ctx % 128):
        # Mosaic DMA slices are 128 lanes wide: a head that is no multiple
        # of them and an unaligned context have no compiled kernel
        raise ValueError(
            f"Pallas attention needs head_dim and context 128-aligned "
            f"(head_dim={head_dim} ctx={max_ctx}){_unpacked(head_dim)}; "
            f"{_OVERRIDE}")
    return impl, interpret


def select_paged_attn_impl(requested: str, *, num_heads: int,
                           num_kv_heads: int, head_dim: int,
                           block_tokens: int, tp: int = 1,
                           kv_dtype: str = "bfloat16",
                           backend: str | None = None) -> tuple[str, bool]:
    """Attention-impl decision for the PAGED decode path (the paged analogue
    of ``select_attn_impl``). Returns (impl, interpret); raises ValueError
    when the resolved impl is the compiled kernel and the shape cannot take
    it.

    The Pallas paged kernel DMAs a table entry's [kv_heads, block_tokens,
    head_dim] pool row, every local kv head in one copy, into a block_tokens
    slice of its step's [kv_heads, P·block_tokens, head_dim] buffer (several
    entries an online-softmax step), so on hardware it needs Mosaic-tileable
    blocks: head_dim 128-aligned and block_tokens a multiple of 32 (the
    int8 sublane tile; every such size is compiled for v5e in the tests).
    ``num_kv_heads`` and ``head_dim`` are the POOL's rows: a family whose
    heads are 64 wide packs two to a row (ops.attention ``heads_per_row``)
    and is answered as 128-wide heads are; an odd count of
    such heads, or a width that packs to no 128, arrives as it is and is
    refused.
    int4 pools are nibble-packed along head_dim, so their DMA'd last dim is
    head_dim/2 — that needs head_dim 256-aligned, and below it the packed
    rows are also padded back to 128 lanes in HBM, so an hd-128 int4 pool
    would cost what the int8 pool costs. The ``gather + XLA`` path
    (ops.paged_decode_attention_ref wired through the paged write
    policies) has no shape constraints and is the CPU/test path.
    """
    impl, interpret = resolve_attn_impl(requested, backend)
    if impl != "pallas":
        return impl, interpret
    _check_tp_heads(num_heads, num_kv_heads, tp)
    if num_heads % num_kv_heads:
        raise ValueError(
            f"Pallas paged attention needs grouped heads ({num_heads} q / "
            f"{num_kv_heads} kv); {_OVERRIDE}")
    if not interpret:
        if head_dim % 128 or block_tokens % 32:
            raise ValueError(
                f"Pallas paged attention needs Mosaic-tileable blocks "
                f"(head_dim % 128 == 0, block_tokens % 32 == 0; got "
                f"head_dim={head_dim} block_tokens={block_tokens})"
                f"{_unpacked(head_dim)}; {_OVERRIDE}. (A pool whose rows "
                f"are off 128 lanes by "
                f"nature is the LATENT layout's: one 576-element row a "
                f"token in 640 lanes, ops.latent_decode_attention, for a "
                f"model with latent attention; this K/V-a-head pool has "
                f"no such page.)")
        if kv_dtype == "int4" and head_dim % 256:
            raise ValueError(
                f"an int4 KV pool packs head_dim {head_dim} into "
                f"{head_dim // 2}-lane rows, which Mosaic cannot DMA and "
                f"which HBM tiling pads back to 128 lanes (no saving over "
                f"int8); use kv_dtype: int8, or {_OVERRIDE}")
    return impl, interpret


def select_latent_attn_impl(requested: str, *, block_tokens: int,
                            kv_dtype: str = "bfloat16",
                            backend: str | None = None) -> tuple[str, bool]:
    """Attention-impl decision for the decode step over a LATENT pool
    (``ops.latent_decode_attention``). A row's width is no gate: the pool
    stores it in whole 128-lane tiles (``ops.attention.latent_lanes``).
    The kernel copies a table entry's ``[block_tokens, lanes]`` slab and
    writes back whole sublane tiles of an unscaled pool: block_tokens a
    multiple of 32, no int8 / int4 rows."""
    impl, interpret = resolve_attn_impl(requested, backend)
    if impl == "pallas" and not interpret and block_tokens % 32:
        raise ValueError(
            f"Pallas latent attention needs block_tokens % 32 == 0 (got "
            f"{block_tokens}); {_OVERRIDE}")
    if impl == "pallas" and kv_dtype in ("int8", "int4"):
        raise ValueError(
            f"the latent decode kernel reads and writes unscaled rows; a "
            f"{kv_dtype} latent pool is not served")
    return impl, interpret


def select_moe_impl(requested: str, *, hidden: int, intermediate: int,
                    backend: str | None = None) -> tuple[str, bool]:
    """The routed experts' path (ops.moe.moe_experts or the XLA loop of
    models.qwen3_next._moe), decided as attention's is and from the same
    request. Returns (impl, interpret); raises ValueError when the resolved
    impl is the compiled kernel and the widths cannot take it: an expert's
    matrices are its blocks, [hidden, intermediate] and back, and Mosaic
    tiles both dims by 128 lanes."""
    impl, interpret = resolve_attn_impl(requested, backend)
    if impl == "pallas" and not interpret and (hidden % 128
                                               or intermediate % 128):
        raise ValueError(
            f"the grouped expert kernel needs hidden and expert widths "
            f"128-aligned (hidden={hidden} intermediate={intermediate}); "
            f"set engine.attn_impl: xla to serve the experts as the XLA "
            f"loop instead")
    return impl, interpret
