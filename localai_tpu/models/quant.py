"""Weight-only int8 quantization for the llama engine.

TPU-era replacement for the reference's quantized-serving story (its default
text config is a q4 GGUF served by llama.cpp; the autogptq/exllama2 Python
backends serve GPTQ/EXL2 — /root/reference/aio/cpu/text-to-text.yaml,
backend/python/autogptq/backend.py). GGUF block formats are llama.cpp-native
and gain nothing on TPU; the idiomatic design is symmetric **per-channel
int8** kept quantized in HBM and dequantized inside the matmul:

    y = (x @ q.astype(bf16)) * scale        # scale per output channel

which XLA fuses into the matmul epilogue — the weight HBM read (the decode
bottleneck; see BENCH notes) is halved, while the MXU still runs bf16.

Granularity: one f32 scale per output channel (per matmul column, per
embedding row), the same granularity llama.cpp uses per 32-elem block but
without the block bookkeeping that would defeat XLA tiling.

``QuantizedTensor`` is a pytree node whose leaves (q, scale) stack/scan like
plain arrays, so the stacked-layer ``lax.scan`` in models.llama and the
NamedSharding placement in parallel.sharding both work unchanged.
"""

from __future__ import annotations

import dataclasses
import os
import re
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp

PyTree = Any


def block_w8_kernel_params(params: PyTree, reason: str = "") -> PyTree:
    """Mark every QuantizedTensor in ``params`` kernel-blocked.

    The Pallas call carries no partitioning rule, so under a multi-device
    mesh GSPMD would replicate (all-gather) the full weight per step — a
    meshed ModelRunner blocks the kernel for ITS OWN weights at init. The
    block rides the tensors (``kernel_ok`` pytree metadata), not process
    state: a single-device runner built later — a draft model, a second
    served model — keeps the opt-in kernel (ADVICE r5 #1 replaced the old
    one-way process-global latch with this)."""
    if os.environ.get("LOCALAI_W8_KERNEL"):
        import logging

        logging.getLogger(__name__).warning(
            "LOCALAI_W8_KERNEL disabled for these weights: %s",
            reason or "meshed serving")

    def mark(leaf):
        if isinstance(leaf, QuantizedTensor) and leaf.kernel_ok:
            return dataclasses.replace(leaf, kernel_ok=False)
        return leaf

    return jax.tree.map(
        mark, params, is_leaf=lambda x: isinstance(x, QuantizedTensor)
    )


def _w8_kernel_mode() -> str:
    """'' (off) | 'tpu' | 'interpret' — the Pallas dequant-matmul opt-in
    (ops.qmatmul; LOCALAI_W8_KERNEL=1 enables on TPU, =interpret for CPU
    tests; any other value is off). Read per call: tests flip it at
    runtime. Per-tensor blocking (meshed weights) is carried by
    ``QuantizedTensor.kernel_ok``, checked at the matmul call sites."""
    v = os.environ.get("LOCALAI_W8_KERNEL", "").strip().lower()
    if v in ("1", "tpu"):
        return "tpu"
    if v == "interpret":
        return "interpret"
    return ""


@partial(
    jax.tree_util.register_dataclass,
    data_fields=("q", "scale"),
    meta_fields=("axis", "mode", "kernel_ok"),
)
@dataclasses.dataclass
class QuantizedTensor:
    """Symmetric per-channel int8 weight.

    q:     int8, the original weight shape.
    scale: f32, the weight shape with ``axis`` (the matmul contraction dim)
           removed — one scale per output channel.
    axis:  which original axis was reduced (static metadata; used for
           sharding-spec derivation, not in the compute path).
    mode:  'w8'   — weight-only: q is cast to the activation dtype in the
                    matmul (bit-exact dequant, but XLA materializes the cast
                    so the HBM saving is partial);
           'w8a8' — activations are dynamically quantized per-token and the
                    MXU runs a native int8×int8→int32 dot: the weight stays
                    int8 all the way from HBM to the systolic array (the
                    full 2× bandwidth + int8-MXU win; adds per-token
                    activation rounding error);
           'w4'   — group-wise int4 weight-only (native jnp.int4 storage —
                    XLA packs two nibbles per byte in HBM, halving the int8
                    read again). scale keeps the contraction axis at
                    K/group size, one scale per (group, output channel) —
                    the GPTQ/q4 granularity (parity: the reference's
                    default q4 GGUF, aio/cpu/text-to-text.yaml, and its
                    autogptq/exllama2 backends) without block bookkeeping.
    """

    q: jax.Array
    scale: jax.Array
    axis: int
    mode: str = "w8"
    # False when these weights live on a runner whose mesh makes the
    # Pallas kernel a pessimization (see block_w8_kernel_params) — static
    # pytree metadata, so the block scopes to the runner, not the process
    kernel_ok: bool = True

    @property
    def shape(self) -> tuple[int, ...]:
        return tuple(self.q.shape)

    @property
    def dtype(self):
        return self.q.dtype

    @property
    def group(self) -> int:
        """Contraction-axis group size (w4 modes); 0 for per-channel int8."""
        if self.mode not in ("w4",):
            return 0
        return self.q.shape[self.axis] // self.scale.shape[self.axis]


def quantize_tensor(w, axis: int) -> QuantizedTensor:
    """Symmetric per-channel int8: scale = amax|w| / 127 over ``axis``."""
    wf = jnp.asarray(w).astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=axis)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(
        jnp.round(wf / jnp.expand_dims(scale, axis)), -127, 127
    ).astype(jnp.int8)
    return QuantizedTensor(q=q, scale=scale, axis=axis)


def _group_size(K: int, group: int) -> int:
    """Largest divisor of K that is ≤ group (small debug dims stay exact)."""
    g = min(K, group)
    while K % g:
        g -= 1
    return g


def quantize_tensor4(w, axis: int, group: int = 128) -> QuantizedTensor:
    """Symmetric group-wise int4: the contraction axis splits into groups of
    ``group``; scale = amax|w| / 7 per (group, output channel). q is native
    jnp.int4 in [-7, 7]; scale keeps the axis at size K/group."""
    wf = jnp.asarray(w).astype(jnp.float32)
    shape = wf.shape
    K = shape[axis]
    g = _group_size(K, group)
    gc = K // g
    grouped = wf.reshape(shape[:axis] + (gc, g) + shape[axis + 1:])
    amax = jnp.max(jnp.abs(grouped), axis=axis + 1)        # [..., gc, ...]
    scale = jnp.maximum(amax, 1e-8) / 7.0
    q = jnp.clip(
        jnp.round(grouped / jnp.expand_dims(scale, axis + 1)), -7, 7
    ).astype(jnp.int4)
    return QuantizedTensor(
        q=q.reshape(shape), scale=scale, axis=axis, mode="w4"
    )


def quantize_tensor_host(w, axis: int, mode: str = "int8",
                         group: int = 128) -> QuantizedTensor:
    """numpy twin of :func:`quantize_tensor` / :func:`quantize_tensor4` for
    the load path: a checkpoint leaf is quantized on the HOST and only its
    served form crosses to the device (an f32 copy of a stacked 8B leaf is
    7.5 GB — on the chip that alone is half of HBM). Same arithmetic in the
    same order, so the result equals the device version bit for bit
    (tests/test_load_path.py). Leaves with a leading layer axis quantize one
    layer at a time, bounding the host f32 working set to one layer."""
    import numpy as np

    w = np.asarray(w)
    if w.ndim >= 3 and axis >= 1:
        parts = [quantize_tensor_host(w[i], axis - 1, mode, group)
                 for i in range(w.shape[0])]
        return QuantizedTensor(
            q=np.stack([p.q for p in parts]),
            scale=np.stack([p.scale for p in parts]),
            axis=axis, mode=parts[0].mode)
    wf = w.astype(np.float32)
    if mode == "int4":
        import ml_dtypes

        shape = wf.shape
        K = shape[axis]
        g = _group_size(K, group)
        grouped = wf.reshape(shape[:axis] + (K // g, g) + shape[axis + 1:])
        amax = np.max(np.abs(grouped), axis=axis + 1)
        scale = np.maximum(amax, np.float32(1e-8)) / np.float32(7.0)
        q = np.clip(np.round(grouped / np.expand_dims(scale, axis + 1)),
                    -7, 7).astype(np.int8).astype(ml_dtypes.int4)
        return QuantizedTensor(q=q.reshape(shape), scale=scale, axis=axis,
                               mode="w4")
    if mode not in ("int8", "int8_w8a8"):
        raise ValueError(f"unsupported quantization mode {mode!r}")
    amax = np.max(np.abs(wf), axis=axis)
    scale = np.maximum(amax, np.float32(1e-8)) / np.float32(127.0)
    q = np.clip(np.round(wf / np.expand_dims(scale, axis)),
                -127, 127).astype(np.int8)
    return QuantizedTensor(q=q, scale=scale, axis=axis,
                           mode="w8a8" if mode == "int8_w8a8" else "w8")


def _grouped_dequant(qt: QuantizedTensor, dtype) -> jax.Array:
    """w4 dequant to ``dtype``: expand scale over its groups."""
    shape = qt.q.shape
    gc = qt.scale.shape[qt.axis]
    g = shape[qt.axis] // gc
    grouped = qt.q.reshape(
        shape[:qt.axis] + (gc, g) + shape[qt.axis + 1:]
    ).astype(dtype)
    out = grouped * jnp.expand_dims(qt.scale, qt.axis + 1).astype(dtype)
    return out.reshape(shape)


def quantize_lastdim(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Dynamic symmetric int8 over the last axis: x [..., K] →
    (q int8 [..., K], scale f32 [...]). The shared recipe for activation
    quantization (w8a8 matmuls) and the scaled int8 KV cache."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


_quant_activations = quantize_lastdim


def quantize_lastdim4(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Dynamic symmetric int4 over the last axis, nibble-packed: x [..., K]
    (K even) → (packed int8 [..., K/2], scale f32 [...]). The scaled-int4
    KV pool recipe (engine.kvcache): scale = amax|x| / 7 per row, values
    clipped to [-7, 7]. Packing is HALVES layout — element i of the first
    half lands in the LOW nibble of byte i, element i of the second half
    in the HIGH nibble — so :func:`unpack_int4_lastdim` is two shifts and
    a concat (no interleave/relayout on the TPU lane axis)."""
    xf = x.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1), 1e-8) / 7.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -7, 7).astype(jnp.int8)
    half = q.shape[-1] // 2
    lo = q[..., :half]
    hi = q[..., half:]
    packed = jnp.bitwise_or(
        jnp.bitwise_and(lo, jnp.int8(0x0F)),
        jnp.left_shift(hi, 4).astype(jnp.int8),
    )
    return packed, scale


def unpack_int4_lastdim(packed: jax.Array, dtype=jnp.int8) -> jax.Array:
    """Inverse of the :func:`quantize_lastdim4` packing: int8 [..., K/2] →
    ``dtype`` [..., K] holding values in [-8, 7]. The shifts run in int32:
    Mosaic has no int8 vector shift on v5e (``arith.shli`` on i8 fails to
    legalize), and XLA fuses the widening away. Low nibbles sign-extend
    via the left/right arithmetic-shift pair; the widened byte's own sign
    extension makes the high nibble a plain arithmetic right shift. The
    kernels pass ``dtype=float32`` to skip an int8 round trip."""
    p32 = packed.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(p32, 28), 28)
    hi = jnp.right_shift(p32, 4)
    return jnp.concatenate([lo, hi], axis=-1).astype(dtype)


def _int8_dot(xq: jax.Array, wq: jax.Array, transpose_w: bool) -> jax.Array:
    """Native int8×int8→int32 dot over the last axis of xq."""
    k_axis = 1 if transpose_w else 0
    return jax.lax.dot_general(
        xq, wq,
        (((xq.ndim - 1,), (k_axis,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


def matmul(x: jax.Array, w) -> jax.Array:
    """x @ w for plain or quantized weights.

    'w8': the int8 weight is cast to x.dtype inside the matmul and the
    per-output-channel scale applied to the product — exactly
    x @ (q * scale) with the scale factored out of the contraction.
    'w8a8': x is dynamically quantized per token and the dot runs on the
    int8 MXU path; both scales are applied to the int32 accumulator.
    'w4': group-wise scales can't factor out of the whole contraction, so
    the dot runs per group (a [Gc]-batched matmul with G-deep contractions
    — still MXU-shaped at G=128) and the scaled partials sum.
    """
    if not isinstance(w, QuantizedTensor):
        return x @ w
    if w.mode == "w4":
        mode = _w8_kernel_mode() if w.kernel_ok else ""
        if mode:
            from localai_tpu.ops import qmatmul

            if qmatmul.w4_eligible(x.shape, w.q, w.scale):
                x2 = x.reshape(-1, x.shape[-1])
                y = qmatmul.w4_matmul(x2, w.q, w.scale,
                                      interpret=mode == "interpret")
                return y.reshape(*x.shape[:-1], y.shape[-1])
        K, N = w.q.shape[-2], w.q.shape[-1]
        gc = w.scale.shape[-2]
        wg = w.q.reshape(gc, K // gc, N).astype(x.dtype)
        xg = x.reshape(*x.shape[:-1], gc, K // gc)
        acc = jnp.einsum("...gk,gkn->...gn", xg, wg)
        return (acc * w.scale.astype(x.dtype)).sum(-2)
    if w.mode == "w8a8":
        xq, xs = _quant_activations(x)
        acc = _int8_dot(xq, w.q, transpose_w=False).astype(jnp.float32)
        return (acc * xs[..., None] * w.scale).astype(x.dtype)
    mode = _w8_kernel_mode() if w.kernel_ok else ""
    if mode:
        from localai_tpu.ops import qmatmul

        if qmatmul.eligible(x.shape, w.q, w.scale, transpose_w=False):
            x2 = x.reshape(-1, x.shape[-1])
            y = qmatmul.w8_matmul(x2, w.q, w.scale,
                                  interpret=mode == "interpret")
            return y.reshape(*x.shape[:-1], y.shape[-1])
    return (x @ w.q.astype(x.dtype)) * w.scale.astype(x.dtype)


def matmul_t(x: jax.Array, w) -> jax.Array:
    """x @ w.T (tied-embedding lm_head). Per-row scales become per-output-
    column scales under the transpose, so the factoring still holds."""
    if not isinstance(w, QuantizedTensor):
        return x @ w.T.astype(x.dtype)
    # no 'w4' branch: quantize_params keeps embedding tables per-row int8
    # even in int4 mode (gather + tied-logits exactness; ~2% of 4-bit 8B),
    # so a w4 table can never reach the transposed path
    if w.mode == "w8a8":
        xq, xs = _quant_activations(x)
        acc = _int8_dot(xq, w.q, transpose_w=True).astype(jnp.float32)
        return (acc * xs[..., None] * w.scale).astype(x.dtype)
    mode = _w8_kernel_mode() if w.kernel_ok else ""
    if mode:
        from localai_tpu.ops import qmatmul

        if qmatmul.eligible(x.shape, w.q, w.scale, transpose_w=True):
            x2 = x.reshape(-1, x.shape[-1])
            y = qmatmul.w8_matmul(x2, w.q, w.scale, transpose_w=True,
                                  interpret=mode == "interpret")
            return y.reshape(*x.shape[:-1], y.shape[-1])
    return (x @ w.q.T.astype(x.dtype)) * w.scale.astype(x.dtype)


def moe_up(x: jax.Array, w) -> jax.Array:
    """x [..., D] against expert-stacked w [E, D, F] → [..., E, F].

    MoE expert weights quantize per-channel int8 only (mode 'w8'): the
    expert einsum layout is fixed here, so the (post-scan-slice) axis
    metadata a w4 group dequant would need never comes into play."""
    if not isinstance(w, QuantizedTensor):
        return jnp.einsum("...d,edf->...ef", x, w)
    acc = jnp.einsum("...d,edf->...ef", x, w.q.astype(x.dtype))
    return acc * w.scale.astype(x.dtype)          # scale [E, F]


def moe_down(a: jax.Array, w) -> jax.Array:
    """a [..., E, F] against expert-stacked w [E, F, D] → [..., E, D]."""
    if not isinstance(w, QuantizedTensor):
        return jnp.einsum("...ef,efd->...ed", a, w)
    acc = jnp.einsum("...ef,efd->...ed", a, w.q.astype(a.dtype))
    return acc * w.scale.astype(a.dtype)          # scale [E, D]


def embed_rows(w, tokens: jax.Array, dtype) -> jax.Array:
    """Embedding gather for plain or per-row-quantized tables."""
    if isinstance(w, QuantizedTensor):
        return w.q[tokens].astype(dtype) * w.scale[tokens][..., None].astype(dtype)
    return w[tokens].astype(dtype)


# Which params get quantized, and the contraction axis for each.
# Norm gains and qkv biases stay in their source dtype (tiny, 1-D).
_LAYER_AXES = {
    "wq": 1, "wk": 1, "wv": 1, "wo": 1,
    "w_gate": 1, "w_up": 1, "w_down": 1,
    "ssm_in": 1, "ssm_out": 1,      # a state-space mixer's two projections
    "w_ogate": 1,                   # an output gate the width of wq
}
# a layer that is no row of the stack: its leaves lie at the top level under
# a prefix of their own, no leading axis (models.minicpm_sala: ``sa<n>_``)
_LONE_LAYER = re.compile(r"^sa\d+_(.+)$")


def quantize_plan(path: tuple[str, ...], ndim: int,
                  mode: str) -> Optional[tuple[int, str]]:
    """(contraction axis, effective mode) for the param leaf at ``path``,
    or None when it stays in its source dtype (norm gains, qkv biases, the
    MoE router). THE single statement of which params quantize how — the
    device path (:func:`quantize_params`), the host load path
    (models.loader) and the synthetic generator (models.registry) all read
    it.

    embed quantizes per-row (axis 1) so both the gather and the
    tied-embedding logits matmul stay exact per-channel, and stays int8
    even in int4 mode (gather accuracy is cheap — int8 embed is 2% of a
    4-bit 8B — and the tied-logits path keeps its exact per-channel form);
    lm_head per output column (axis 0). Stacked layer weights [L, K, N]
    quantize over K (axis 1) so scales stack [L, N] and scan alongside the
    weights. Expert-stacked [L, E, K, N] weights contract over axis 2 and
    are per-channel int8 regardless of mode (moe_up/moe_down fix the einsum
    layout — group-wise w4 metadata wouldn't survive the scan slice)."""
    if mode not in ("int8", "int8_w8a8", "int4"):
        raise ValueError(f"unsupported quantization mode {mode!r}")
    if path == ("embed",):
        return 1, ("int8" if mode == "int4" else mode)
    if path == ("lm_head",):
        return 0, mode
    lone = _LONE_LAYER.match(path[0]) if len(path) == 1 else None
    if lone and lone.group(1) in _LAYER_AXES and ndim == 2:
        return _LAYER_AXES[lone.group(1)] - 1, mode
    if len(path) == 2 and path[0] == "layers" and path[1] in _LAYER_AXES:
        if ndim == 4:
            return 2, "int8"
        return _LAYER_AXES[path[1]], mode
    return None


def quantize_params(params: PyTree, mode: str = "int8",
                    group: int = 128) -> PyTree:
    """Quantize a llama param pytree's matmul weights in place of bf16, on
    the device they live on (tests and tools; the serving load path
    quantizes on the host, leaf by leaf — :func:`quantize_tensor_host`).

    mode: 'int8' (weight-only), 'int8_w8a8' (+ dynamic activation quant,
    native int8 MXU dot), or 'int4' (group-wise int4 weight-only, the
    TPU analogue of the reference's default q4 serving — see
    QuantizedTensor). Which leaf quantizes how is :func:`quantize_plan`.
    """

    def qt(path, w):
        plan = quantize_plan(path, w.ndim, mode)
        if plan is None:
            return w
        axis, leaf_mode = plan
        if leaf_mode == "int4":
            return quantize_tensor4(w, axis, group=group)
        return dataclasses.replace(
            quantize_tensor(w, axis),
            mode="w8a8" if leaf_mode == "int8_w8a8" else "w8")

    out = {k: qt((k,), v) for k, v in params.items() if k != "layers"}
    out["layers"] = {k: qt(("layers", k), v)
                     for k, v in params["layers"].items()}
    return out


def dequantize_tensor(qt: QuantizedTensor, dtype="float32") -> jax.Array:
    if qt.mode == "w4":
        return _grouped_dequant(qt, dtype)
    return qt.q.astype(dtype) * jnp.expand_dims(qt.scale, qt.axis).astype(dtype)


def quantized_spec(qt_path_spec, axis: int, grouped: bool = False):
    """Derive the scale PartitionSpec from the weight spec: drop the
    contracted axis (per-channel int8) or keep it (group-wise w4 — the
    scale's group axis tiles the weight's contraction axis, so it shards
    the same way when divisible; parallel.sharding sanitizes the rest)."""
    from jax.sharding import PartitionSpec as P

    if grouped:
        return P(*qt_path_spec)
    entries = list(qt_path_spec)
    # P shorter than rank means trailing dims replicated; pad first
    while len(entries) < axis + 1:
        entries.append(None)
    del entries[axis]
    return P(*entries)
