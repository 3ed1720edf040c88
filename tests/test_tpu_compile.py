"""Compile for a TPU v5e with no chip attached.

libtpu can compile for a DESCRIBED topology (``jax.experimental.topologies``):
trace with ShapeDtypeStructs whose sharding sits on a topology device, lower
for the "tpu" platform, compile. Mosaic runs for real, so a Pallas kernel it
refuses — a block shape off the (8,128) rule, a DMA slice narrower than the
128-lane tile, an op v5e cannot legalize — fails HERE, on the CPU, instead
of on the first chip run. The rule this file holds the selectors to:

    every (kernel, KV dtype, block size, head shape) that ops.select_attn_impl
    / ops.select_paged_attn_impl answer "pallas" for on a TPU compiles for
    v5e; what cannot compile, the selector refuses.

The last test compiles the whole programs chip_smoke.py's server dispatches
(debug:llama3-8b int8, its slots and context) and holds their HBM to the
chip's, so the smoke's context cannot silently stop fitting.

Skipped when libtpu is absent or cannot describe a topology.
"""

import dataclasses
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from localai_tpu import ops
from localai_tpu.ops import qmatmul

# Llama-3-8B head shapes: 32 q heads / 8 kv heads / head_dim 128, 8 slots
S, HQ, HKV, HD = 8, 32, 8, 128
HBM_BYTES = 15.75 * 2**30          # one v5e chip, as libtpu reports it

bf16, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu / no topology support
        pytest.skip(f"libtpu cannot describe a v5e topology here: {e}")


def compile_for(topo, fn, *args):
    """Compile ``fn`` for the topology; args are (shape, dtype) pairs, or
    pytrees of ShapeDtypeStruct. Returns the compiled executable."""
    sharding = SingleDeviceSharding(topo.devices[0])

    def aval(a):
        if isinstance(a, tuple) and len(a) == 2 and isinstance(a[0], tuple):
            return jax.ShapeDtypeStruct(a[0], a[1], sharding=sharding)
        return a

    return jax.jit(fn).trace(*[aval(a) for a in args]).lower(
        lowering_platforms=("tpu",)).compile()


# ---------------------------------------------------------------------------
# contiguous-cache kernels


@pytest.mark.parametrize("ctx", [2048, 8192])
def test_contiguous_kernels_compile(topo, ctx):
    assert ops.select_attn_impl(
        "auto", num_heads=HQ, num_kv_heads=HKV, head_dim=HD, max_ctx=ctx,
        backend="tpu") == ("pallas", False)
    kv = ((S, HKV, ctx, HD), bf16)
    compile_for(topo, ops.decode_attention,
                ((S, HQ, HD), bf16), kv, kv, ((S,), i32))
    kv8, sc = ((S, HKV, ctx, HD), i8), ((S, HKV, ctx), f32)
    compile_for(topo, ops.decode_attention,
                ((S, HQ, HD), bf16), kv8, kv8, ((S,), i32), sc, sc)
    # every prefill bucket the runner builds for this context
    for T in [b for b in (128, 512, 2048, 8192) if b <= ctx]:
        compile_for(topo, ops.prefill_attention, ((T, HQ, HD), bf16),
                    ((HKV, T, HD), bf16), ((HKV, T, HD), bf16), ((), i32))


def test_sliding_window_kernels_compile(topo):
    """Mistral-class masking is a static kernel variant."""
    import functools

    kv = ((S, HKV, 2048, HD), bf16)
    compile_for(topo, functools.partial(ops.decode_attention,
                                        sliding_window=1024),
                ((S, HQ, HD), bf16), kv, kv, ((S,), i32))
    pool = ((65, HKV, 64, HD), bf16)
    compile_for(topo, functools.partial(ops.paged_decode_attention,
                                        sliding_window=1024),
                ((S, HQ, HD), bf16), pool, pool, ((S, 32), i32), ((S,), i32))


# ---------------------------------------------------------------------------
# the paged decode kernel: every pool the selector allows


def paged_args(kv_dtype, bt, hq, hkv, hd, ctx=2048, n_blocks=65):
    packed = hd // 2 if kv_dtype == "int4" else hd
    dt = {"bfloat16": bf16, "float32": f32}.get(kv_dtype, i8)
    pool = ((n_blocks, hkv, bt, packed), dt)
    args = [((S, hq, hd), bf16), pool, pool, ((S, ctx // bt), i32),
            ((S,), i32)]
    if kv_dtype in ("int8", "int4"):
        args += [((n_blocks, hkv, bt), f32)] * 2
    return args


@pytest.mark.parametrize("num_buffers", [2, 3])
@pytest.mark.parametrize("bt", [16, 32, 64, 128, 256])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "float32", "int8", "int4"])
@pytest.mark.parametrize("hd", [64, 128, 256])
def test_paged_kernel_compiles_wherever_the_selector_says_pallas(
        topo, hd, kv_dtype, bt, num_buffers):
    import functools

    hq, hkv = HQ * HD // hd, HKV * HD // hd      # same model width
    try:
        answer = ops.select_paged_attn_impl(
            "auto", num_heads=hq, num_kv_heads=hkv, head_dim=hd,
            block_tokens=bt, kv_dtype=kv_dtype, backend="tpu")
    except ValueError as e:
        # refused at load, with the override named — never a quiet gather
        assert "attn_impl: xla" in str(e)
        return
    assert answer == ("pallas", False)
    compile_for(
        topo,
        functools.partial(ops.paged_decode_attention,
                          num_buffers=num_buffers),
        *paged_args(kv_dtype, bt, hq, hkv, hd))


def test_selector_refuses_what_mosaic_refuses(topo):
    """The two refusals that exist because of the compiler, checked against
    the compiler: were Mosaic to start accepting them, the gates are stale."""
    for kv_dtype, hd in (("int4", 128), ("bfloat16", 64)):
        with pytest.raises(ValueError):
            ops.select_paged_attn_impl(
                "auto", num_heads=HQ, num_kv_heads=HKV, head_dim=hd,
                block_tokens=64, kv_dtype=kv_dtype, backend="tpu")
        with pytest.raises(Exception, match="aligned to tiling"):
            compile_for(topo, ops.paged_decode_attention,
                        *paged_args(kv_dtype, 64, HQ, HKV, hd))


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_kernel_compiles_under_shard_map_tp4(topo, kv_dtype):
    """The meshed runner's wrapping (engine.runner._decode_paged_fn): slots
    on 'data', heads on 'model', the pool's block axis whole."""
    assert ops.select_paged_attn_impl(
        "auto", num_heads=HQ, num_kv_heads=HKV, head_dim=HD, block_tokens=64,
        tp=4, kv_dtype=kv_dtype, backend="tpu") == ("pallas", False)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    specs = [P("data", "model", None), P(None, "model", None, None),
             P(None, "model", None, None), P("data", None), P("data")]
    if kv_dtype == "int8":
        specs += [P(None, "model", None)] * 2
    kernel = jax.shard_map(
        ops.paged_decode_attention, mesh=mesh, in_specs=tuple(specs),
        out_specs=P("data", "model", None), check_vma=False)
    avals = [jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, sp))
             for (shape, dt), sp in zip(
                 paged_args(kv_dtype, 64, HQ, HKV, HD), specs)]
    compile_for(topo, kernel, *avals)


# ---------------------------------------------------------------------------
# the opt-in dequant matmuls (LOCALAI_W8_KERNEL)


@pytest.mark.parametrize("m", [1, 8, 256])
@pytest.mark.parametrize("k,n", [(4096, 14336), (14336, 4096), (4096, 4096),
                                 (4096, 1024)])
def test_qmatmul_kernels_compile(topo, m, k, n):
    x, q, s = ((m, k), bf16), ((k, n), i8), ((n,), f32)
    assert qmatmul.eligible(x[0], jax.ShapeDtypeStruct(*q),
                            jax.ShapeDtypeStruct(*s), False)
    compile_for(topo, qmatmul.w8_matmul, x, q, s)
    q4, s4 = ((k, n), jnp.int4), ((k // 128, n), f32)
    assert qmatmul.w4_eligible(x[0], jax.ShapeDtypeStruct(*q4),
                               jax.ShapeDtypeStruct(*s4))
    compile_for(topo, qmatmul.w4_matmul, x, q4, s4)


def test_qmatmul_lm_head_compiles(topo):
    """The 128256-row vocabulary: plain (untied head) and transposed (tied
    embedding table, per-row scales)."""
    import functools

    compile_for(topo, qmatmul.w8_matmul, ((8, 4096), bf16),
                ((4096, 128256), i8), ((128256,), f32))
    compile_for(topo, functools.partial(qmatmul.w8_matmul, transpose_w=True),
                ((8, 2048), bf16), ((128256, 2048), i8), ((128256,), f32))


# ---------------------------------------------------------------------------
# the smoke's whole programs, and their HBM


def test_smoke_programs_fit_one_chip(topo, monkeypatch):
    """Every program the scheduler dispatches for chip_smoke.py's server —
    debug:llama3-8b, int8 weights, its SLOTS and CONTEXT, bf16 paged KV —
    compiles for v5e and fits the chip. Weights are abstract (no 8 GB on the
    host); the pool is eval_shape'd."""
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    import chip_smoke
    from localai_tpu.engine import kvcache as kvc
    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models.registry import DEBUG_PRESETS, synthetic_params

    sh = SingleDeviceSharding(topo.devices[0])
    cfg = dataclasses.replace(DEBUG_PRESETS["llama3-8b"], dtype="bfloat16")
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.hd) == (HQ, HKV, HD)

    def abstract(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree)

    params = abstract(jax.eval_shape(lambda: synthetic_params(cfg, "int8")))
    real_init = kvc.init_paged_cache
    monkeypatch.setattr(
        kvc, "init_paged_cache",
        lambda *a, **k: jax.eval_shape(lambda: real_init(*a, **k)))
    r = ModelRunner(cfg, params, num_slots=chip_smoke.SLOTS,
                    max_ctx=chip_smoke.CONTEXT, paged=True,
                    attn_impl="pallas_interpret")
    # as the TPU selector would have it: the compiled kernel
    assert ops.select_paged_attn_impl(
        "auto", num_heads=HQ, num_kv_heads=HKV, head_dim=HD,
        block_tokens=r.block_tokens, backend="tpu") == ("pallas", False)
    r._paged_attn_interpret = r._attn_interpret = False
    kv, state, tables = abstract(r.kv), abstract(r.state), abstract(
        r.block_tables)
    scalar = jax.ShapeDtypeStruct((), i32, sharding=sh)

    def hbm(fn, *args, donate=(1, 2), **static):
        c = jax.jit(fn, donate_argnums=donate,
                    static_argnames=tuple(static)).trace(
            *args, **static).lower(lowering_platforms=("tpu",)).compile()
        m = c.memory_analysis()
        return (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes
                + m.generated_code_size_in_bytes)

    need = {
        "decode": hbm(r._decode_paged_fn, params, kv, state, tables),
        # the scheduler's default dispatch: 16 steps in one program
        "decode_n": hbm(r._decode_paged_n_fn, params, kv, state, tables,
                        n=16),
        # the n-gram speculation lane, default gamma 4
        "verify": hbm(r._verify_paged_fn, params, kv, state, tables,
                      jax.ShapeDtypeStruct((chip_smoke.SLOTS, 4), i32,
                                           sharding=sh)),
    }
    for bucket in (128, 512):
        for sample in (False, True):
            need[f"prefill_chunk {bucket} sample={sample}"] = hbm(
                r._prefill_paged_fn, params, kv, state,
                jax.ShapeDtypeStruct((1, bucket), i32, sharding=sh),
                scalar, scalar,
                jax.ShapeDtypeStruct((r.max_blocks,), i32, sharding=sh),
                scalar,
                jax.ShapeDtypeStruct((cfg.vocab_size,), i32, sharding=sh),
                bucket=bucket, sample=sample)
    worst = max(need, key=need.get)
    assert need[worst] < HBM_BYTES, (
        f"{worst} needs {need[worst] / 2**30:.2f} GiB of "
        f"{HBM_BYTES / 2**30:.2f}: chip_smoke.CONTEXT no longer fits")
