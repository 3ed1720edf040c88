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

# Llama-3-8B head shapes: 32 q heads / 8 kv heads / head_dim 128, 8 slots;
# the kernels take the cache stacked over LAYERS layers and a layer index
S, HQ, HKV, HD = 8, 32, 8, 128
LAYERS = 2
HBM_BYTES = 15.75 * 2**30          # one v5e chip, as libtpu reports it

bf16, i8, f32, i32 = jnp.bfloat16, jnp.int8, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    """The described topology. While a module holds it, nothing is WRITTEN
    to the run's compilation cache (tests/conftest.py): libtpu's compile-only
    client serializes an executable and cannot load one (``UNIMPLEMENTED:
    DeserializeLoadedExecutable``), so an entry would cost its write and then
    a warning and the same compile at every repeat."""
    try:
        from jax.experimental import topologies

        desc = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu / no topology support
        pytest.skip(f"libtpu cannot describe a v5e topology here: {e}")
    floor = jax.config.jax_persistent_cache_min_compile_time_secs
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      float("inf"))
    yield desc
    jax.config.update("jax_persistent_cache_min_compile_time_secs", floor)


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
    kv = ((LAYERS, S, HKV, ctx, HD), bf16)
    compile_for(topo, ops.decode_attention,
                ((S, HQ, HD), bf16), kv, kv, ((), i32), ((S,), i32))
    kv8 = ((LAYERS, S, HKV, ctx, HD), i8)
    sc = ((LAYERS, S, HKV, ctx), f32)
    compile_for(topo, ops.decode_attention,
                ((S, HQ, HD), bf16), kv8, kv8, ((), i32), ((S,), i32), sc, sc)
    # every prefill bucket the runner builds for this context
    for T in [b for b in (128, 512, 2048, 8192) if b <= ctx]:
        compile_for(topo, ops.prefill_attention, ((T, HQ, HD), bf16),
                    ((HKV, T, HD), bf16), ((HKV, T, HD), bf16), ((), i32))


def test_sliding_window_kernels_compile(topo):
    """Mistral-class masking is a static kernel variant."""
    import functools

    kv = ((LAYERS, S, HKV, 2048, HD), bf16)
    compile_for(topo, functools.partial(ops.decode_attention,
                                        sliding_window=1024),
                ((S, HQ, HD), bf16), kv, kv, ((), i32), ((S,), i32))
    pool = ((LAYERS, 65, HKV, 64, HD), bf16)
    compile_for(topo, functools.partial(ops.paged_decode_attention,
                                        sliding_window=1024),
                ((S, HQ, HD), bf16), pool, pool, ((), i32), ((S, 32), i32),
                ((S,), i32))


# ---------------------------------------------------------------------------
# the paged decode kernel: every pool the selector allows


def paged_args(kv_dtype, bt, hq, hkv, hd, ctx=2048, n_blocks=65):
    packed = hd // 2 if kv_dtype == "int4" else hd
    dt = {"bfloat16": bf16, "float32": f32}.get(kv_dtype, i8)
    pool = ((LAYERS, n_blocks, hkv, bt, packed), dt)
    args = [((S, hq, hd), bf16), pool, pool, ((), i32),
            ((S, ctx // bt), i32), ((S,), i32)]
    if kv_dtype in ("int8", "int4"):
        args += [((LAYERS, n_blocks, hkv, bt), f32)] * 2
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
    on 'data', heads on 'model', the pool's layer and block axes whole."""
    assert ops.select_paged_attn_impl(
        "auto", num_heads=HQ, num_kv_heads=HKV, head_dim=HD, block_tokens=64,
        tp=4, kv_dtype=kv_dtype, backend="tpu") == ("pallas", False)
    compile_under_shard_map_tp4(topo, paged_args(kv_dtype, 64, HQ, HKV, HD))


def compile_under_shard_map_tp4(topo, args):
    """``ops.paged_decode_attention`` over ``args`` ((shape, dtype) pairs,
    scales last if any) as the meshed runner wraps it, compiled for a
    1 x 4 mesh of the topology's chips."""
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    specs = [P("data", "model", None), P(None, None, "model", None, None),
             P(None, None, "model", None, None), P(), P("data", None),
             P("data")] + [P(None, None, "model", None)] * (len(args) - 6)
    kernel = jax.shard_map(
        ops.paged_decode_attention, mesh=mesh, in_specs=tuple(specs),
        out_specs=P("data", "model", None), check_vma=False)
    return compile_for(topo, kernel, *[
        jax.ShapeDtypeStruct(shape, dt, sharding=NamedSharding(mesh, sp))
        for (shape, dt), sp in zip(args, specs)])


@pytest.mark.parametrize("cell_shape", [
    # S, kv heads on a chip, table width, pool blocks, layers, tp
    ("m7b", 16, 8, 64, 289, 32, 1),
    ("ms24b-tp4", 32, 2, 128, 641, 40, 4)])
def test_paged_kernel_at_the_cells_shapes(topo, cell_shape):
    """The two served shapes of the kernel (benchmark/configs: Mistral-7B on
    one chip, every one of its 8 kv heads in a program; Mistral-Small-24B
    at tp=4 under ``shard_map``, a chip's 2): the step it derives is the
    one PERF.md's numbers were read at, its K/V ring is inside the budget
    the derivation cuts it to, and Mosaic takes it."""
    from localai_tpu.ops import attention as att

    _, S, hkv, MB, N, L, tp = cell_shape
    blocks, depth, ring = att.paged_decode_tiling(hkv, 64, HD, 2, MB)
    assert (blocks, depth) == ({8: 2, 2: 8}[hkv], 2)
    assert ring == depth * blocks * 2 * hkv * 64 * HD * 2
    assert ring <= att._PAGED_KV_VMEM_BYTES
    # a third step in flight shrinks the step, not the budget
    assert att.paged_decode_tiling(hkv, 64, HD, 2, MB, 3)[2] <= ring
    pool = ((L, N, hkv * tp, 64, HD), bf16)
    args = [((S, 4 * hkv * tp, HD), bf16), pool, pool, ((), i32),
            ((S, MB), i32), ((S,), i32)]
    if tp == 1:
        text = compile_for(topo, ops.paged_decode_attention, *args).as_text()
    else:
        text = compile_under_shard_map_tp4(topo, args).as_text()
    # ONE custom call, the one benchmark/layers/paged_decode_attn_roofline.py
    # sums: a helper kernel beside it would be counted into it
    assert text.count('custom_call_target="tpu_custom_call"') == 1
    assert "paged_decode_attn" in text


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


def abstract_runner(topo, monkeypatch, cfg, tp=1, quantization="int8",
                    **runner_kw):
    """A paged ModelRunner over abstract int8 weights (or as ``quantization``
    says: "" is the compute dtype's) and an eval_shape'd
    pool (nothing of model size on the host), its paged kernel resolved to
    the compiled one as the TPU selector would. With ``tp`` > 1 it is built
    over a 1 x tp mesh ('model' = tp): of CPU devices while it places its
    small state, of the topology's devices for what it compiles. Returns
    (runner, the argument avals of its programs as a dict)."""
    from localai_tpu.engine import kvcache as kvc
    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models.quant import QuantizedTensor
    from localai_tpu.models.registry import synthetic_params
    from localai_tpu.parallel import sharding as shd
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    mesh = None
    if tp > 1:
        mesh = build_mesh(MeshPlan(model=tp), devices=topo.devices[:tp])
        runner_kw["mesh"] = build_mesh(MeshPlan(model=tp),
                                       devices=jax.devices()[:tp])

    def on(spec=P()):
        if mesh is None:
            return SingleDeviceSharding(topo.devices[0])
        return NamedSharding(mesh, spec)

    def abstract(tree, spec_of=lambda a: a.sharding.spec):
        """``tree``'s arrays as avals on the topology's devices, sharded
        as the runner placed them."""
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=on(spec_of(a) if mesh is not None else P())),
            tree)

    place = shd.ParamPlacement(cfg, mesh)

    def placed(kp, leaf):
        where = (place.shardings(tuple(k.key for k in kp), leaf)
                 or jax.tree.map(lambda _: on(), leaf))
        return jax.tree.map(
            lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
            leaf, where)

    params = jax.tree_util.tree_map_with_path(
        placed, jax.eval_shape(lambda: synthetic_params(cfg, quantization)),
        is_leaf=lambda x: isinstance(x, QuantizedTensor))
    for init in ("init_paged_cache", "init_latent_cache"):
        monkeypatch.setattr(
            kvc, init, lambda *a, _real=getattr(kvc, init), **k:
            jax.eval_shape(lambda: _real(*a, **k)))
    r = ModelRunner(cfg, params, paged=True, attn_impl="pallas_interpret",
                    **runner_kw)
    # as the TPU selector would have it: the compiled kernel
    if cfg.latent:
        assert ops.select_latent_attn_impl(
            "auto", block_tokens=r.block_tokens,
            backend="tpu") == ("pallas", False)
    else:
        assert ops.select_paged_attn_impl(
            "auto", num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.hd, block_tokens=r.block_tokens, tp=tp,
            backend="tpu") == ("pallas", False)
    r._paged_attn_interpret = r._attn_interpret = False
    r.layout.interpret = False
    if r.routed:        # and its routed experts: the compiled kernel too
        assert ops.select_moe_impl(
            "auto", hidden=cfg.hidden_size,
            intermediate=cfg.moe_intermediate_size,
            backend="tpu") == ("pallas", False)
        assert r.family_kernels is True
        r.family_kernels = False
    elif r.recurrent:   # a state-space mixer's decode step: ops.gdn's kernel
        assert r.family_kernels is True
        r.family_kernels = False
    if mesh is not None:
        r.mesh = r.layout.mesh = mesh
    pool_spec = None if mesh is None else tuple(r.layout.kv_sharding.spec)
    scalar = jax.ShapeDtypeStruct((), i32, sharding=on())
    avals = {
        "params": params,
        # the eval_shape'd pool carries no sharding: the runner's own spec
        "kv": abstract(r.kv, lambda a: P(*pool_spec[:a.ndim])),
        "state": abstract(r.state), "tables": abstract(r.block_tables),
        "scalar": scalar,
        "proposals": jax.ShapeDtypeStruct((r.num_slots, 4), i32,
                                          sharding=on()),
    }

    def chunk(bucket):
        """_prefill_paged_fn's arguments after (params, kv, state)."""
        return (jax.ShapeDtypeStruct((1, bucket), i32, sharding=on()),
                scalar, scalar,
                jax.ShapeDtypeStruct((r.max_blocks,), i32, sharding=on()),
                scalar,
                jax.ShapeDtypeStruct((cfg.vocab_size,), i32, sharding=on()))

    avals["chunk"] = chunk
    return r, avals


def collectives(text):
    """(opcode, result, HLO line) of every collective of a compiled
    program's text."""
    import re

    return [(m.group(2), m.group(1), ln.strip()) for ln in text.splitlines()
            if (m := re.search(
                r" = (.*?) (all-gather|all-reduce|all-to-all"
                r"|collective-permute|reduce-scatter|collective-broadcast)"
                r"[\w\-]*\(", ln))]


def lower_program(fn, *args, **static):
    """A runner program lowered for the topology as the runner jits it
    (KV and decode state donated)."""
    return jax.jit(fn, donate_argnums=(1, 2),
                   static_argnames=tuple(static)).trace(
        *args, **static).lower(lowering_platforms=("tpu",))


def compile_program(fn, *args, **static):
    return lower_program(fn, *args, **static).compile()


def compile_cell_program(r, a, program, build=compile_program):
    """One of a cell's dispatched programs over ``abstract_runner``'s
    (runner, avals): ``decode``, ``decode_n2``, ``prefill_chunk_<bucket>``
    (``..._sample``: the chunk that ends a prompt and samples) or
    ``decode_prefill_<bucket>`` (that chunk and the step as one program)."""
    base = (a["params"], a["kv"], a["state"])
    if program == "decode":
        return build(r._decode_paged_fn, *base, a["tables"])
    if program == "decode_n2":
        return build(r._decode_paged_n_fn, *base, a["tables"], n=2)
    bucket = int(program.split("_")[2])
    if program.startswith("decode_prefill_"):
        return build(r._decode_prefill_paged_fn, *base, a["tables"],
                     *a["chunk"](bucket), bucket=bucket)
    return build(
        r._prefill_paged_fn, *base, *a["chunk"](bucket), bucket=bucket,
        sample=program.endswith("_sample"))


def quartered(r, program):
    """Whether ``program`` is a chunk program that holds the switch over its
    bucket's live quarters (``ModelRunner.chunk_rows``)."""
    if not program.startswith("prefill_chunk_"):
        return False
    return len(r.chunk_rows(int(program.split("_")[2]),
                            program.endswith("_sample"))) > 1


def test_smoke_programs_fit_one_chip(topo, monkeypatch):
    """Every program the scheduler dispatches for chip_smoke.py's server —
    debug:llama3-8b, int8 weights, its SLOTS and CONTEXT, bf16 paged KV —
    compiles for v5e and fits the chip. Weights are abstract (no 8 GB on the
    host); the pool is eval_shape'd."""
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))
    import chip_smoke
    from localai_tpu.models.registry import DEBUG_PRESETS

    cfg = dataclasses.replace(DEBUG_PRESETS["llama3-8b"], dtype="bfloat16")
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.hd) == (HQ, HKV, HD)
    r, a = abstract_runner(topo, monkeypatch, cfg, num_slots=chip_smoke.SLOTS,
                           max_ctx=chip_smoke.CONTEXT)
    base = (a["params"], a["kv"], a["state"])

    def hbm(fn, *args, **static):
        m = compile_program(fn, *args, **static).memory_analysis()
        return (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes
                + m.generated_code_size_in_bytes)

    need = {
        "decode": hbm(r._decode_paged_fn, *base, a["tables"]),
        # the scheduler's default dispatch: 16 steps in one program
        "decode_n": hbm(r._decode_paged_n_fn, *base, a["tables"], n=16),
        # the n-gram speculation lane, default gamma 4
        "verify": hbm(r._verify_paged_fn, *base, a["tables"], a["proposals"]),
    }
    for bucket in (128, 512):
        for sample in (False, True):
            need[f"prefill_chunk {bucket} sample={sample}"] = hbm(
                r._prefill_paged_fn, *base, *a["chunk"](bucket),
                bucket=bucket, sample=sample)
    worst = max(need, key=need.get)
    assert need[worst] < HBM_BYTES, (
        f"{worst} needs {need[worst] / 2**30:.2f} GiB of "
        f"{HBM_BYTES / 2**30:.2f}: chip_smoke.CONTEXT no longer fits")


# ---------------------------------------------------------------------------
# the benchmark cell's programs write the KV pool in place


M7B, MS24B = "mistral-7b-v0.3-int8", "mistral-small-24b-int8-tp4"
OURO = "ouro-2.6b-int8"
QN80 = "qwen3-next-80b-a3b-ep8"
TRL = "trinity-large-ep8"
AXK1 = "axk1-ep16"
SMT = "smallthinker-21b-a3b-pp4"


@pytest.fixture
def cell(request):
    """benchmark/configs/<name>.json (the one-chip 7B unless a test names
    another): the published keys as a LlamaConfig, and the engine's sizes."""
    import json

    from localai_tpu.models.llama import LlamaConfig

    root = Path(__file__).resolve().parent.parent
    name = getattr(request, "param", M7B)
    doc = json.loads(
        (root / f"benchmark/configs/{name}.json").read_text())
    # from_hf reads the published keys it knows and no other
    cfg = dataclasses.replace(LlamaConfig.from_hf(doc), dtype="bfloat16")
    return cfg, doc


@pytest.mark.parametrize("program", ["decode", "decode_n2",
                                     "prefill_chunk_512",
                                     "prefill_chunk_512_sample",
                                     "prefill_chunk_128",
                                     "prefill_chunk_128_sample"])
def test_cell_programs_write_the_pool_in_place(topo, monkeypatch, cell,
                                               program):
    """The guard of PERF.md's PR 26: at the benchmark cell's shapes (32
    layers, 289 blocks x 64 tokens, 8 kv heads, head_dim 128, 16 slots, int8
    weights, the compiled paged kernel) the decode step, two steps in one
    dispatch and a prefill chunk of either bucket hold no second pool and
    copy no layer of it: not with the chunk's attend a ``conditional`` inside
    the layer scan either, a branch a span (PR 40; the pool is its operand
    and no branch's result). The stacked pool is the layer scan's carry, the
    write policies scatter rows into it, the kernel reads it by layer index: one
    per-layer slice anywhere (a policy's ``k[layer]``, a kernel that takes a
    4-D pool, a scatter XLA lays out unlike its reader) brings back a
    pool-sized temp and a 76 MB copy a layer, which only the compiler shows.
    """
    cfg, doc = cell
    eng = doc["engine"]
    r, a = abstract_runner(
        topo, monkeypatch, cfg, num_slots=eng["max_slots"],
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    pool = a["kv"].k.shape
    assert pool == (32, 289, 8, 64, 128) and a["kv"].k.dtype == bf16
    c = compile_cell_program(r, a, program)
    assert_in_place(program, c, pool)
    # the decode programs' one Pallas call is the paged kernel (the roofline
    # reader sums every ``tpu_custom_call`` of theirs), which writes the
    # step's rows too; chunked prefill attends through XLA, holds none, and
    # writes through the policy's scatter as before
    text = c.as_text()
    assert_who_writes(program, text, pool, spans(r, program),
                      quartered=quartered(r, program))
    if program.startswith("prefill"):
        # PR 54: the last chunk's 512-row program holds the switch over its
        # live quarters on one chip too, its branches handed the stacks; no
        # chunk program stages a stack of what they read (wo's is 512 MiB)
        assert_live_quarters(r, program, text, cfg, pool)
        assert c.memory_analysis().temp_size_in_bytes < (
            cfg.num_layers * cfg.hidden_size ** 2)


def assert_in_place(program, c, pool):
    """``c``, compiled, holds no second pool (``pool``: one device's K or V
    stack) and copies no layer of it."""
    import re

    pool_bytes = 2 * np.prod(pool) * 2
    temp = c.memory_analysis().temp_size_in_bytes
    assert temp < pool_bytes / 2, (
        f"{program}: temp {temp / 2**30:.2f} GiB, the pool is "
        f"{pool_bytes / 2**30:.2f}: something holds a second pool")
    # nothing may PRODUCE the pool, one layer of it or a layer's slice but
    # the policies' scatters, fused and aliased onto their operand (in
    # place), and what only names a buffer
    shapes = "|".join(
        r"\[" + ",".join(map(str, s)) + r"\]"
        for s in (pool, (1,) + pool[1:], pool[1:]))
    produces = re.compile(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = \w+(?:" + shapes
        + r")(?:\{[^}]*\})? ([\w\-]+)\((.*)$", re.M)
    free = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "scatter"}           # a scatter line is a fusion's own body
    moved = [
        (op, name) for name, op, rest in produces.findall(c.as_text())
        if op not in free and not (
            op == "fusion" and 'kv_pool.write/scatter"' in rest
            and '"aliasing_operands"' in rest)]
    assert not moved, f"{program} moves the pool or a layer of it: {moved}"


def spans(r, program):
    """The spans a chunk program's attend may take (None: a decode
    program)."""
    from localai_tpu.engine import kvcache as kvc

    if program in ("decode", "decode_n2"):
        return None
    return kvc.span_ladder(int(program.split("_")[2]), r.ctx_pad,
                           r.block_tokens)


def assert_who_writes(program, text, pool, ladder=None, quartered=False):
    """PR 38. A DECODE program holds no scatter into the pool: its one
    Pallas call takes the step's K and V rows as its last operands and
    hands both pools (``pool``: one device's K or V stack) back aliased to
    the operands they came in as, the attention output staying its FIRST
    result (the benchmark's readers name an operation by the first shape of
    its result; a call named by the pool's would be read as a move of it).
    A PREFILL program holds no Pallas call and its policy's scatters, fused
    and aliased onto the pool (``assert_in_place`` lets nothing else
    produce a pool-shaped result in either, so no copy stands before or
    behind the aliased call). PR 40: its attend is ONE ``conditional`` (in
    the layer scan's body), a branch for every span of ``ladder``, each
    gathering that span's positions of a chip's kv heads and no more. PR 59:
    a chunk that RIDES the step (``decode_prefill_<bucket>``) is held to
    both: the chunk's scatters and its one conditional, and the step's one
    Pallas call, which writes the step's rows."""
    import re

    # (a routed family's grouped expert kernel and a DeltaNet step's aside:
    # they touch no pool; tests/test_lfm2_compile.py and the hybrid cell's
    # cases below count their calls)
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and "moe/experts/moe_experts" not in ln
             and "gdn/state/gdn_state_step" not in ln]
    rides = program.startswith("decode_prefill_")
    if rides or not program.startswith("decode"):
        assert rides or not calls
        assert 'kv_pool.write/scatter"' in text
        # (the 512-row program of a prompt's last chunk holds a second
        # conditional, of three branches, over its row counts: PR 52 on a
        # mesh, PR 54 on one chip, the tests at the end)
        switch = [ln.split("branch_computations={")[1].split("}")[0].split(",")
                  for ln in text.splitlines() if " conditional(" in ln]
        assert sorted(map(len, switch)) == sorted(
            [len(ladder)] + [3] * quartered) and len(ladder) > 1, switch
        for span in ladder:     # K's gather and V's: span / bt whole blocks
            blocks = f"bf16[{span // pool[3]},{','.join(map(str, pool[2:]))}]"
            assert len(re.findall(
                "= " + re.escape(blocks) + r"\S* gather\(", text)) == 2, span
        if not rides:
            return
    assert len(calls) == 1 and "paged_decode_attn" in calls[0]
    assert rides or "kv_pool.write/scatter" not in text, (
        f"{program} still scatters the step's rows")
    shape = "bf16[" + ",".join(map(str, pool)) + "]"
    rows = f"bf16[{{}},{pool[2]},{pool[4]}]"
    results = calls[0].split(" custom-call(")[0]
    operands = calls[0].split("operand_layout_constraints={")[1].split("}, ")
    assert "output_to_operand_aliasing={{1}: (4, {}), {2}: (5, {})}" in calls[0]
    assert results.count(shape) == 2 and not results.split("= (")[1].startswith(
        shape), results
    assert operands[4].startswith(shape) and operands[5].startswith(shape)
    slots = operands[1].split("[")[1].split("]")[0]
    assert operands[6].startswith(rows.format(slots)), operands[6]
    assert operands[7].startswith(rows.format(slots)), operands[7]


@pytest.mark.parametrize("cell", [OURO], indirect=True)
@pytest.mark.parametrize("program", ["decode", "prefill_chunk_128",
                                     "prefill_chunk_512",
                                     "prefill_chunk_512_sample"])
def test_looped_cell_programs_write_the_pool_in_place(topo, monkeypatch,
                                                      cell, program):
    """PR 37: the looped decoder's programs (48 layers run 4 times a token,
    a cache layer for every (pass, layer) pair: 192 x 101 blocks x 16 kv
    heads x 64 x 128) are held to what the others are. The (x, kv) carry
    goes through the loop over passes AND the layer scan inside it, so the
    9.5 GiB pool is still one buffer from argument to output: no pool-sized
    temp, no layer-shaped copy; the weight stacks are read where they lie
    by every pass (no stack-sized temp: an unrolled loop, or a scan that
    takes the stack as ``xs`` of the OUTER loop, would stage one); and a
    decode step holds ONE paged kernel call, inside both loops, which picks
    cache layer ``pass * 48 + layer`` in its DMA slice."""
    cfg, doc = cell
    eng = doc["engine"]
    assert (cfg.num_passes, cfg.num_layers, cfg.cache_layers) == (4, 48, 192)
    r, a = abstract_runner(
        topo, monkeypatch, cfg, num_slots=eng["max_slots"],
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    pool = a["kv"].k.shape
    assert pool == (192, 101, 16, 64, 128) and a["kv"].k.dtype == bf16
    c = compile_cell_program(r, a, program)
    assert_in_place(program, c, pool)
    text = c.as_text()
    assert_who_writes(program, text, pool, spans(r, program),
                      quartered=quartered(r, program))
    if program.startswith("prefill"):
        # PR 54: the switch over the live quarters sits inside the loop over
        # the passes with the layer scan; nothing is copied a pass
        assert_live_quarters(r, program, text, cfg, pool)
    m = c.memory_analysis()
    # no weight stack staged: the smallest stacked leaf is [48, 2048, 2048]
    assert m.temp_size_in_bytes < cfg.num_layers * cfg.hidden_size ** 2, (
        f"{program}: temp {m.temp_size_in_bytes / 2**20:.0f} MiB holds a "
        f"weight stack")
    # the loop is rolled: one layer body, not one a pass (the scope names
    # every operation of a layer carries appear under ONE loop.pass)
    assert text.count("loop.pass/layers") > 0
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)
    print(f"HBM {program}: arguments {m.argument_size_in_bytes / 2**30:.3f} "
          f"temp {m.temp_size_in_bytes / 2**30:.4f} in all "
          f"{need / 2**30:.3f} GiB")
    assert need < HBM_BYTES


@pytest.mark.parametrize("cell", [QN80], indirect=True)
@pytest.mark.parametrize("program", ["decode", "decode_n2",
                                     "prefill_chunk_128",
                                     "prefill_chunk_512_sample"])
def test_hybrid_cell_programs_fit_one_chip(topo, monkeypatch, cell, program):
    """PR 41: the configuration FILE of the sparse hybrid decoder (three
    periods of 3 DeltaNet layers + 1 gated full-attention layer, 64 held
    experts a block, bfloat16 weights, 32 slots of float32 state beside a
    3 x 641-block pool of head_dim 256) compiles for one v5e chip and fits
    it, with the numbers its ``hbm`` block restates. The pool and the
    per-slot state are the period scan's carry, written in place, and the
    weights are read in place: no second pool, no second state (590 MiB), no
    period of experts (1.5 GiB), no block's (128 MiB) and no period of
    DeltaNet projections (144 MiB) staged: temps under 72 MiB. PR 42: what
    Mosaic compiled of a decode program is the paged kernel, once in the
    period scan's body, and the routed experts' grouped kernel
    (ops/moe.py ``moe_experts``: once an expert block of a period, the three
    expert leaves its operands WHOLE); of a prefill chunk the ``moe_experts``
    calls alone, its rows the bucket's. PR 46: and of a decode program the
    DeltaNet step (ops/gdn.py ``gdn_state_step``: once a DeltaNet layer of
    the period, the carried state its operand WHOLE and its aliased result),
    so no decode program slices a layer's state out of the carry or lays it
    back; a chunk's token scan and the conv are XLA.
    (benchmark/layers/paged_decode_attn_roofline.py sums every
    ``tpu_custom_call`` of a slice: in this cell it now sums all three
    kernels, PERF.md section 7.)"""
    cfg, doc = cell
    eng = doc["engine"]
    assert cfg.recurrent and not eng.get("quantization")
    r, a = abstract_runner(
        topo, monkeypatch, cfg, quantization="",
        num_slots=eng["max_slots"], max_ctx=doc["context_size"],
        kv_num_blocks=eng["kv_num_blocks"], kv_block_tokens=64)
    pool = a["kv"].k.shape
    assert pool == (3, 641, 2, 64, 256) and a["kv"].k.dtype == bf16
    state = a["state"].rec["S"]
    assert state.shape == (3, 3, 32, 32, 128, 128) and state.dtype == f32
    c = compile_cell_program(r, a, program)
    text = c.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    experts = [ln for ln in calls if "moe/experts/moe_experts" in ln]
    rows = (eng["max_slots"] if program.startswith("decode")
            else int(program.split("_")[2]))
    leaves = tuple(a["params"]["layers"][n] for n in ("w_gate", "w_up",
                                                      "w_down"))
    assert [w.shape for w in leaves] == [
        (3, 4, 64, 2048, 512), (3, 4, 64, 2048, 512), (3, 4, 64, 512, 2048)]
    # one call an expert block of the (rolled) period, on the program's rows,
    # the leaves its operands as they lie
    assert len(experts) == cfg.full_attention_interval
    for ln in experts:
        assert f"f32[{rows},2048]" in ln.split("custom-call(")[0]
        for w in leaves:
            assert f"bf16[{','.join(map(str, w.shape))}]" in ln
    steps = [ln for ln in calls if "gdn/state/gdn_state_step" in ln]
    rest = [ln for ln in calls if ln not in experts + steps]
    if program.startswith("decode"):
        assert len(rest) == 1 and "paged_decode_attn" in rest[0]
        # one call a DeltaNet layer of the (rolled) period: the carried
        # state goes in whole and comes back as the same buffer
        carried = "f32[3,3,32,32,128,128]"
        assert len(steps) == cfg.gdn_per_period
        for ln in steps:
            results, operands = ln.split(" custom-call(")
            assert results.split("= (")[1].startswith(carried)
            assert operands.split("operand_layout_constraints={")[1].split(
                "}, ")[1].startswith(carried)
            assert "output_to_operand_aliasing={{0}: (1, {})}" in ln
        # nothing slices a layer's state out of the carry or lays it back,
        # nothing stages a period's DeltaNet layers of state (192 MiB: an
        # index by the period in front of the layer's did), nor a period's
        # DeltaNet projections (144 MiB: the scan's slice of a [P, G, ...]
        # leaf did, copied whole before a layer's was taken)
        assert "f32[32,32,128,128]" not in text
        assert not [ln for ln in text.splitlines()
                    if f"= {carried}" in ln and "dynamic-update-slice(" in ln]
        assert "f32[3,32,32,128,128]" not in text
        assert "bf16[3,2048,12288]" not in text
    else:
        assert not rest and not steps
    # no block's experts staged for the kernel, nor a period's
    for staged in ("bf16[64,2048,512]", "bf16[64,512,2048]",
                   "bf16[4,64,2048,512]", "bf16[4,64,512,2048]"):
        assert staged not in text
    m = c.memory_analysis()
    state_bytes = int(np.prod(state.shape)) * 4
    assert m.temp_size_in_bytes < state_bytes / 8, (
        f"{program}: temp {m.temp_size_in_bytes / 2**20:.0f} MiB holds a "
        f"second state, a period's experts or a period's projections")
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)
    print(f"HBM {program}: arguments {m.argument_size_in_bytes / 2**30:.3f} "
          f"temp {m.temp_size_in_bytes / 2**30:.4f} in all "
          f"{need / 2**30:.3f} GiB")
    hbm = doc["hbm"]
    # (a chunk that samples nothing takes no head: 0.07 GiB fewer)
    assert (hbm["arguments_gib"] - 0.08 < m.argument_size_in_bytes / 2**30
            <= hbm["arguments_gib"] + 0.005)
    # the block's largest program is PR 41's text of the 512-token chunk that
    # samples (6.328, temps 0.034). With the experts' kernels in it that
    # chunk's temps read 0.045 and the program 6.338: the kernel has no temp
    # of its own, but where XLA keeps the token scan's 18 MiB carry, in VMEM
    # or in HBM, goes with what the custom calls reserve of VMEM (PERF.md
    # section 7 (p): the block is a ``benchmark`` PR's to restate). Every
    # other program stays under the block's number
    over = 0.011 if program == "prefill_chunk_512_sample" else 0.001
    assert need / 2**30 <= hbm["largest_program_gib"] + over
    # over the floor a new cell is held to: a quarter of the chip
    assert 0.25 * HBM_BYTES < need < HBM_BYTES


@pytest.mark.parametrize("cell", [QN80], indirect=True)
def test_hybrid_cells_last_chunk_rides_the_step(topo, monkeypatch, cell):
    """PR 64: at the hybrid cell's served shapes (32 slots, bucket 128,
    ``kv_num_blocks`` 641) a prompt's last chunk and the decode step are ONE
    program through the family's own forward (``_decode_prefill_paged_fn``,
    ``models.qwen3_next.forward(ride=128)``), held to what PR 59's is for
    the dense stack (no second pool, the chunk's scatters and ONE
    conditional over its spans, ONE paged kernel call that writes the step's
    rows) and to what this cell's programs are above: the grouped expert
    kernel once an expert block of the (rolled) period over the 160 rows of
    both halves, the DeltaNet step's kernel once a DeltaNet layer on the
    carried state WHOLE and aliased, and behind it the chunk's ``rec_write``
    onto the same buffer: one slot's rows, never a ``[P, G, slots, ...]``
    copy between the two; the DeltaNet projections over 160 rows and over no
    half alone; no period of experts or of projections staged, and no
    weight-shaped copy that its halves' own programs do not make. Its
    arguments are the decode step's (6.271 GiB); its temps read 5.62 MiB
    beside the 128 chunk's 3.85 and the step's 3.20, its need 6.301 GiB
    beside 6.291 and 6.286, under the file's ``largest_program_gib``."""
    import re

    cfg, doc = cell
    eng = doc["engine"]
    slots = eng["max_slots"]
    r, a = abstract_runner(
        topo, monkeypatch, cfg, quantization="", num_slots=slots,
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    assert r.rides and r.own_forward
    rows = 128 + slots
    pool = a["kv"].k.shape
    need, temps, texts = {}, {}, {}
    for program in ("decode_prefill_128", "prefill_chunk_128_sample",
                    "decode"):
        c = compile_cell_program(r, a, program)
        texts[program] = c.as_text()
        m = c.memory_analysis()
        temps[program] = m.temp_size_in_bytes
        need[program] = (m.argument_size_in_bytes + m.temp_size_in_bytes
                         + m.output_size_in_bytes - m.alias_size_in_bytes
                         + m.generated_code_size_in_bytes)
        if program == "decode_prefill_128":
            ride, args = c, m.argument_size_in_bytes
    print({k: f"need {need[k] / 2**30:.3f} GiB temp {v / 2**20:.2f} MiB"
           for k, v in temps.items()},
          f"ride arguments {args / 2**30:.3f} GiB")
    assert_in_place("decode_prefill_128", ride, pool)
    text = texts["decode_prefill_128"]
    assert_who_writes("decode_prefill_128", text, pool,
                      spans(r, "decode_prefill_128"))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    experts = [ln for ln in calls if "moe/experts/moe_experts" in ln]
    steps = [ln for ln in calls if "gdn/state/gdn_state_step" in ln]
    assert (len(experts), len(steps), len(calls)) == (
        cfg.full_attention_interval, cfg.gdn_per_period,
        cfg.full_attention_interval + cfg.gdn_per_period + 1)
    for ln in experts:                              # both halves' rows
        assert ln.split(" custom-call(")[0].count(f"f32[{rows},2048]") == 1
    # the step's kernel takes the carried state whole and hands it back as
    # the same buffer; the chunk's rows are then laid onto that buffer (one
    # slot's: ``f32[1,1,1,32,128,128]``), and nothing copies the carry
    carried = "f32[3,3,32,32,128,128]"
    for ln in steps:
        assert ln.split("= (")[1].startswith(carried)
        assert "output_to_operand_aliasing={{0}: (1, {})}" in ln
    assert not [ln for ln in text.splitlines()
                if f"= {carried}" in ln and re.search(r" copy(-start)?\(", ln)]
    # nor is a layer's state of every slot, or a period's, sliced out of it
    assert "f32[32,32,128,128]" not in text
    assert "f32[3,32,32,128,128]" not in text
    # the DeltaNet's projections run every row once, no half alone
    W = cfg.conv_dim + cfg.value_dim
    assert re.search(rf"bf16\[(1,)?{rows},{W}\]", text)
    assert not re.search(rf"bf16\[(1,)?(128|{slots})(,1)?,{W}\]", text)
    # no period of DeltaNet projections, no block's experts nor a period's
    for staged in ("bf16[3,2048,12288]", "bf16[64,2048,512]",
                   "bf16[64,512,2048]", "bf16[4,64,2048,512]",
                   "bf16[4,64,512,2048]"):
        assert staged not in text
    # and no leaf's matrix copied on its way to a product that its two
    # halves' own programs do not copy (a copy of a weight's shape is a
    # read of it)
    import jax

    matrices = {leaf.shape[-2:] for leaf in jax.tree.leaves(a["params"])
                if leaf.ndim >= 2 and leaf.shape[-2] * leaf.shape[-1] >= 2**20}
    assert (2048, W) in matrices and (2048, 512) in matrices

    def copied(text):
        return sorted(
            ln.split(" = ")[1].split("{")[0].lstrip("(")
            for ln in text.splitlines()
            if re.search(r" copy(-start|-done)?\(", ln) and any(
                re.search(rf"\[([0-9]+,)*{m},{n}\]", ln.split(" copy")[0])
                for m, n in matrices))

    assert set(copied(text)) <= (
        set(copied(texts["prefill_chunk_128_sample"]))
        | set(copied(texts["decode"])))
    state_bytes = int(np.prod(a["state"].rec["S"].shape)) * 4
    assert temps["decode_prefill_128"] < (
        temps["prefill_chunk_128_sample"] + temps["decode"] + 16 * 2**20)
    assert temps["decode_prefill_128"] < state_bytes / 8
    hbm = doc["hbm"]
    assert (hbm["arguments_gib"] - 0.01 < args / 2**30
            <= hbm["arguments_gib"] + 0.005)
    assert need["decode_prefill_128"] / 2**30 <= (
        hbm["largest_program_gib"] + 0.001)


@pytest.mark.parametrize("cell", [TRL], indirect=True)
@pytest.mark.parametrize("program", ["decode", "prefill_chunk_128_sample",
                                     "prefill_chunk_512",
                                     "prefill_chunk_512_sample"])
def test_mixed_attention_cell_programs_fit_one_chip(topo, monkeypatch, cell,
                                                    program):
    """PR 44: the configuration FILE of the window / full attention stack
    with its dense layer and its sigmoid-routed experts (1 dense + 1 row of
    4 expert layers, 32 held experts a layer, bfloat16 weights, a 5 x
    2048-block pool, 18432 positions) compiles for one v5e chip and fits it,
    with the numbers its ``hbm`` block restates. What Mosaic compiled of a
    decode program: the paged kernel three times (the dense layer's window
    call, and in the row scan's body ONE window call for its three window
    layers' places each and one full call: five calls in the text, four
    under ``attn.window_decode`` with the window's bound, one under
    ``attn.paged_decode``), and ``moe_experts`` once an expert layer, the
    three expert leaves its operands WHOLE; of a prefill chunk the
    ``moe_experts`` calls alone. The pool is the scan's carry: no
    pool-shaped temp, no row of experts (7 GiB) or of attention weights
    staged. The chunk's attends are XLA: a full layer's widest branch spans
    the 18432 positions, a window layer's ``window_span`` = 4672 + the
    bucket's blocks."""
    cfg, doc = cell
    eng = doc["engine"]
    assert cfg.attn_kinds == (("sliding_attention", 4096),
                              ("full_attention", None))
    assert cfg.routed and not cfg.recurrent and not eng.get("quantization")
    r, a = abstract_runner(
        topo, monkeypatch, cfg, quantization="",
        num_slots=eng["max_slots"], max_ctx=doc["context_size"],
        kv_num_blocks=eng["kv_num_blocks"], kv_block_tokens=64)
    pool = a["kv"].k.shape
    assert pool == (5, eng["kv_num_blocks"], 8, 64, 128)
    assert a["kv"].k.dtype == bf16 and r.max_blocks == 288
    c = compile_cell_program(r, a, program)
    text = c.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    experts = [ln for ln in calls if "moe/experts/moe_experts" in ln]
    rows = (eng["max_slots"] if program == "decode"
            else int(program.split("_")[2]))
    leaves = tuple(a["params"]["layers"][n] for n in ("w_gate", "w_up",
                                                      "w_down"))
    assert [w.shape for w in leaves] == [
        (1, 4, 32, 3072, 3072)] * 3
    # (a chunk that samples nothing needs the last layer's K/V and not its
    # experts' output: the compiler drops that call)
    assert len(experts) == (3 if program == "prefill_chunk_512" else 4)
    for ln in experts:
        assert f"f32[{rows},3072]" in ln.split("custom-call(")[0]
        assert "bf16[1,4,32,3072,3072]" in ln
    rest = [ln for ln in calls if ln not in experts]
    if program == "decode":
        window = [ln for ln in rest
                  if "attn.window_decode/paged_decode_attn" in ln]
        full = [ln for ln in rest
                if "attn.paged_decode/paged_decode_attn" in ln]
        assert (len(window), len(full), len(rest)) == (4, 1, 5)
    else:
        assert not rest
        assert "attn.prefill_window" in text
    # no row's experts or attention weights staged, no second pool
    for staged in ("bf16[32,3072,3072]", "bf16[4,32,3072,3072]",
                   "bf16[4,3072,6144]", "bf16[4,6144,3072]",
                   f"bf16[5,{eng['kv_num_blocks']},8,64,128]{{"):
        assert staged not in text.replace("parameter(", "").split(
            "ENTRY")[0] or staged.startswith("bf16[5,")
    m = c.memory_analysis()
    pool_bytes = int(np.prod(pool)) * 2
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)
    print(f"HBM {program}: arguments {m.argument_size_in_bytes / 2**30:.3f} "
          f"temp {m.temp_size_in_bytes / 2**30:.4f} in all "
          f"{need / 2**30:.3f} GiB")
    # the pool is written in place: a program's temps hold no second one
    assert m.temp_size_in_bytes < 2 * pool_bytes
    if program == "decode":
        assert m.temp_size_in_bytes < 0.25 * 2**30
    hbm = doc["hbm"]
    assert (hbm["arguments_gib"] - 0.16 < m.argument_size_in_bytes / 2**30
            <= hbm["arguments_gib"] + 0.005)
    assert need / 2**30 <= hbm["largest_program_gib"] + 0.001
    assert 0.25 * HBM_BYTES < need < HBM_BYTES


@pytest.mark.parametrize("cell", [SMT], indirect=True)
@pytest.mark.parametrize("program", ["decode", "prefill_chunk_128_sample",
                                     "prefill_chunk_512_sample"])
def test_router_first_cell_programs_fit_one_chip(topo, monkeypatch, cell,
                                                 program):
    """PR 65: the configuration FILE of the stack whose router stands in
    front of attention (3 rows of F W W W, 64 ReLU-gated experts a layer all
    held, bfloat16 weights, a 12 x 2048-block pool, 14336 positions, 28
    query heads in groups of 7 a K/V head, a vocabulary of 151936) compiles
    for one v5e chip and fits it, with the numbers its ``hbm`` block
    restates. What Mosaic compiled of a decode program: in the row scan's
    body the paged kernel ONCE under ``attn.paged_decode`` (the full layer)
    and three times under ``attn.window_decode``, and ``moe_experts`` once a
    layer with the three expert leaves its operands WHOLE (an expert's 11.8
    MB lie in VMEM whole, twice: no F axis); of a prefill chunk the
    ``moe_experts`` calls alone. The pool is the scan's carry: no
    pool-shaped temp, no layer's experts (755 MB) staged."""
    from localai_tpu.ops import moe

    cfg, doc = cell
    eng = doc["engine"]
    assert cfg.attn_kinds == (("sliding_attention", 4096),
                              ("full_attention", None))
    assert cfg.row_kinds == ("full_attention",) + ("sliding_attention",) * 3
    assert (cfg.rows, cfg.q_per_kv, cfg.vocab_size) == (3, 7, 151936)
    assert cfg.routed and not cfg.recurrent and not eng.get("quantization")
    assert moe.f_tile(2560, 768, 2) == 768
    r, a = abstract_runner(
        topo, monkeypatch, cfg, quantization="",
        num_slots=eng["max_slots"], max_ctx=doc["context_size"],
        kv_num_blocks=eng["kv_num_blocks"], kv_block_tokens=64)
    pool = a["kv"].k.shape
    assert pool == (12, eng["kv_num_blocks"], 4, 64, 128)
    assert a["kv"].k.dtype == bf16 and r.max_blocks == 224
    c = compile_cell_program(r, a, program)
    text = c.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    experts = [ln for ln in calls if "moe/experts/moe_experts" in ln]
    rows = (eng["max_slots"] if program == "decode"
            else int(program.split("_")[2]))
    leaves = tuple(a["params"]["layers"][n] for n in ("w_gate", "w_up",
                                                      "w_down"))
    assert [w.shape for w in leaves] == [
        (12, 64, 2560, 768)] * 2 + [(12, 64, 768, 2560)]
    assert len(experts) == 4
    for ln in experts:
        assert f"f32[{rows},2560]" in ln.split("custom-call(")[0]
        # the stack WHOLE, through the bitcast [rows, M, E, ...]
        assert "bf16[3,4,64,2560,768]" in ln
    rest = [ln for ln in calls if ln not in experts]
    if program == "decode":
        window = [ln for ln in rest
                  if "attn.window_decode/paged_decode_attn" in ln]
        full = [ln for ln in rest
                if "attn.paged_decode/paged_decode_attn" in ln]
        assert (len(window), len(full), len(rest)) == (3, 1, 4)
    else:
        assert not rest
        assert "attn.prefill_window" in text
    # no layer's or row's experts staged, no second pool
    for staged in ("bf16[64,2560,768]", "bf16[4,64,2560,768]",
                   "bf16[1,64,2560,768]"):
        assert staged not in text.replace("parameter(", "").split(
            "ENTRY")[0]
    m = c.memory_analysis()
    pool_bytes = int(np.prod(pool)) * 2
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)
    print(f"HBM {program}: arguments {m.argument_size_in_bytes / 2**30:.3f} "
          f"temp {m.temp_size_in_bytes / 2**30:.4f} in all "
          f"{need / 2**30:.3f} GiB")
    # the pool is written in place: a program's temps hold no second one
    assert m.temp_size_in_bytes < pool_bytes
    if program == "decode":
        assert m.temp_size_in_bytes < 0.25 * 2**30
    hbm = doc["hbm"]
    assert (hbm["arguments_gib"] - 0.16 < m.argument_size_in_bytes / 2**30
            <= hbm["arguments_gib"] + 0.005)
    assert need / 2**30 <= hbm["largest_program_gib"] + 0.001
    assert 0.25 * HBM_BYTES < need < HBM_BYTES


@pytest.mark.parametrize("cell", [AXK1], indirect=True)
@pytest.mark.parametrize("program", ["decode", "prefill_chunk_128_sample",
                                     "prefill_chunk_512",
                                     "prefill_chunk_512_sample"])
def test_latent_attention_cell_programs_fit_one_chip(topo, monkeypatch, cell,
                                                     program):
    """PR 48: the configuration FILE of the latent-attention stack (1 dense +
    6 expert layers, 12 held experts of 7168 x 2048 a layer, bfloat16
    weights, a 7 x 3072-block pool of 640-lane rows, 34816 positions)
    compiles for one v5e chip and fits it, with the numbers its ``hbm`` block
    restates. What Mosaic compiled of a decode program: the latent kernel
    twice (the dense layer's call and ONE in the layer scan's body), under
    ``attn.latent_decode``, the pool aliased through it, and ``moe_experts``
    once, the three expert leaves its operands WHOLE; of a prefill chunk the
    ``moe_experts`` call alone: its attend is XLA, a rolled loop over 1024
    rows of the span at a time under ``attn.latent_chunk`` with the
    decompression (``mla/kv_b``) inside. The pool is the scan's carry: no
    pool-shaped temp."""
    cfg, doc = cell
    eng = doc["engine"]
    assert cfg.latent and cfg.routed and not cfg.recurrent
    assert cfg.latent_width == 576 and cfg.hd == 192
    r, a = abstract_runner(
        topo, monkeypatch, cfg, quantization="",
        num_slots=eng["max_slots"], max_ctx=doc["context_size"],
        kv_num_blocks=eng["kv_num_blocks"], kv_block_tokens=64)
    pool = a["kv"].c.shape
    assert pool == (7, eng["kv_num_blocks"], 64, 640)
    assert a["kv"].c.dtype == bf16 and r.max_blocks == 544
    c = compile_cell_program(r, a, program)
    text = c.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    experts = [ln for ln in calls if "moe/experts/moe_experts" in ln]
    leaves = tuple(a["params"]["layers"][n] for n in ("w_gate", "w_up",
                                                      "w_down"))
    assert [w.shape for w in leaves] == [
        (6, 1, 12, 7168, 2048), (6, 1, 12, 7168, 2048),
        (6, 1, 12, 2048, 7168)]
    assert len(experts) == 1
    assert "bf16[6,1,12,7168,2048]" in experts[0]
    rest = [ln for ln in calls if ln not in experts]
    if program == "decode":
        assert len(rest) == 2 and all(
            "attn.latent_decode/latent_decode_attn" in ln for ln in rest)
    else:
        assert not rest
        assert "attn.latent_chunk" in text and "mla/kv_b" in text
    # no layer's experts staged, no second pool
    for staged in ("bf16[12,7168,2048]", "bf16[12,2048,7168]"):
        assert staged not in text
    m = c.memory_analysis()
    pool_bytes = int(np.prod(pool)) * 2
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)
    print(f"HBM {program}: arguments {m.argument_size_in_bytes / 2**30:.3f} "
          f"temp {m.temp_size_in_bytes / 2**30:.4f} in all "
          f"{need / 2**30:.3f} GiB")
    assert m.temp_size_in_bytes < pool_bytes
    if program == "decode":
        assert m.temp_size_in_bytes < 0.25 * 2**30
    hbm = doc["hbm"]
    # (a chunk that samples nothing takes no head: 0.27 GiB fewer)
    assert (hbm["arguments_gib"] - 0.28 < m.argument_size_in_bytes / 2**30
            <= hbm["arguments_gib"] + 0.005)
    assert need / 2**30 <= hbm["largest_program_gib"] + 0.001
    assert 0.25 * HBM_BYTES < need < HBM_BYTES


@pytest.mark.parametrize("num_buffers", [2, 3])
@pytest.mark.parametrize("bt", [16, 32, 64, 128])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_latent_kernel_compiles_wherever_the_selector_says_pallas(
        topo, dtype, bt, num_buffers):
    """``ops.latent_decode_attention`` at the cell's row (576 elements in
    640 lanes, 64 heads, values the first 512 lanes) for every block size
    ``ops.select_latent_attn_impl`` answers "pallas" for; what it refuses
    (a block under the write-back's sublane tile) names the override."""
    import functools

    from localai_tpu.ops.attention import latent_lanes

    try:
        answer = ops.select_latent_attn_impl("auto", block_tokens=bt,
                                             backend="tpu")
    except ValueError as e:
        assert "attn_impl: xla" in str(e) and bt % 32
        return
    assert answer == ("pallas", False)
    dt = {"bfloat16": bf16, "float32": f32}[dtype]
    lanes = latent_lanes(576)
    compile_for(
        topo,
        functools.partial(ops.latent_decode_attention, v_lanes=512,
                          sm_scale=0.13, num_buffers=num_buffers),
        ((S, 64, 576), dt), ((LAYERS, 65, bt, lanes), dt), ((), i32),
        ((S, 2048 // bt), i32), ((S,), i32), ((S, 576), dt))


@pytest.mark.parametrize("cell, program, overlap", [
    (M7B, "decode", "0"), (M7B, "decode", "auto"),
    (M7B, "prefill_chunk_512", "auto"),
    (MS24B, "decode", "auto"), (MS24B, "decode_n2", "auto"),
    (MS24B, "prefill_chunk_512", "auto"),
    (MS24B, "prefill_chunk_512_sample", "auto"),
    (MS24B, "prefill_chunk_128", "auto")], indirect=["cell"])
def test_cell_programs_write_their_own_heads_on_a_tp4_mesh(
        topo, monkeypatch, cell, program, overlap):
    """A configuration file's programs over a 1 x 4 mesh, the pool sharded
    over its kv heads ('model'): under GSPMD (``LOCALAI_MESH_OVERLAP=0``,
    and every prefill) as inside the manual-TP trunk's shard_map (``auto``)
    each chip writes the rows of its own two heads into its own shard, in
    place. The partitioner has to SEE that: the policies index the head axis
    with an iota (``kvcache._scatter_per_head``), and a scatter it cannot
    prove shard-local gathers the new rows, or the blocks a chunk touches,
    from all chips, a collective a layer for K and for V. The 7B's file is
    the shared code's guard; the 24B's is the four-chip cell itself (40
    layers, 641 blocks, 32 slots), whose programs the chip sees in one cell
    at four chips' cost."""
    import re

    cfg, doc = cell
    eng = doc["engine"]
    monkeypatch.setenv("LOCALAI_MESH_OVERLAP", overlap)
    r, a = abstract_runner(
        topo, monkeypatch, cfg, tp=4, num_slots=eng["max_slots"],
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    assert bool(r.overlap_mode) == (overlap == "auto")
    shard = (cfg.num_layers, eng["kv_num_blocks"], cfg.num_kv_heads // 4,
             64, cfg.hd)
    assert shard[2:] == (2, 64, 128)
    assert a["kv"].k.sharding.shard_shape(a["kv"].k.shape) == shard
    c = compile_cell_program(r, a, program)
    assert_in_place(program, c, shard)
    text = c.as_text()
    collectives = [
        ln.strip()[:160] for ln in text.splitlines()
        if ("kv_pool." in ln or "attn.paged_decode" in ln) and re.search(
            r" (all-gather|all-reduce|all-to-all|collective-permute"
            r"|reduce-scatter)[\w\-]*\(", ln)]
    assert not collectives, f"{program}: the pool's write talks: {collectives}"
    # one Pallas call in a decode program, the paged kernel inside its
    # shard_map, handed the step's rows of the chip's own two heads (no
    # gather of rows in front of it: the shapes are the shard's); none in a
    # chunk, which scatters, and whose attend's branches (the rung is a
    # replicated scalar) gather the chip's own two heads of their span
    assert_who_writes(program, text, shard, spans(r, program),
                      quartered=quartered(r, program))


# ---------------------------------------------------------------------------
# the cells' decode programs read wq, wk and wv where they lie


@pytest.mark.parametrize("cell, tp, program", [
    (M7B, 1, "decode"), (M7B, 1, "decode_n2"),
    (MS24B, 4, "decode"), (MS24B, 4, "decode_n2")], indirect=["cell"])
def test_cell_decode_programs_read_qkv_weights_in_place(
        topo, monkeypatch, cell, tp, program):
    """The guard of PERF.md's PR 32 (ROADMAP A3): the layer scan hands
    ``_layer`` one layer's ``[D, H*hd]`` int8 slice of each stacked ``[L, D,
    H*hd]`` leaf, and the q, k and v dots read it there, as the MLP's and
    ``wo``'s do. With the head split folded into the dot XLA wants the
    weight ``[H, hd, D]``: it transposes each whole stack once a dispatch (a
    ``copy`` of ``s8[L, D, H*hd]`` hoisted out of the step loop, 0.75 GiB of
    temps at the 7B's n = 2) and copies each layer's ``s8[1, D, H*hd]``
    slice before the dot: 2.1 ms of a 14.4 ms step on the chip. Only the
    compiler shows it. Under tp = 4 the shards carry the LOCAL head counts
    (the 24B: ``[40, 5120, 1024]`` and ``[40, 5120, 256]``)."""
    import re

    cfg, doc = cell
    eng = doc["engine"]
    if tp > 1:
        monkeypatch.setenv("LOCALAI_MESH_OVERLAP", "auto")
    r, a = abstract_runner(
        topo, monkeypatch, cfg, tp=tp, num_slots=eng["max_slots"],
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    c = compile_cell_program(r, a, program)
    L, D = cfg.num_layers, cfg.hidden_size
    widths = {cfg.num_heads * cfg.hd // tp, cfg.num_kv_heads * cfg.hd // tp}
    layers = a["params"]["layers"]
    assert {layers[w].q.sharding.shard_shape(layers[w].q.shape)
            for w in ("wq", "wk", "wv")} == {(L, D, n) for n in widths}
    # nothing may PRODUCE a whole stack or one layer of it by moving bytes:
    # a dot's own fusion slices the stack it is handed (a ``dynamic-slice``
    # inside the fused computation) and names no such result
    shapes = "|".join(rf"\[{lead},{D},{n}\]"
                      for lead in (L, 1) for n in sorted(widths))
    moved = re.findall(
        r"^\s*(?:ROOT )?%?([\w.\-]+) = s8(?:" + shapes + r")(?:\{[^}]*\})? "
        r"(copy|copy-start|copy-done|transpose|fusion)\(", c.as_text(), re.M)
    assert not moved, (
        f"{program} copies the attention weights before use: {moved}")
    temp = c.memory_analysis().temp_size_in_bytes
    assert temp < L * D * min(widths), (
        f"{program}: temp {temp / 2**20:.0f} MiB holds a weight stack")


# ---------------------------------------------------------------------------
# the slot's lifecycle programs, beside the cells' own


@pytest.mark.parametrize("cell, tp", [(M7B, 1), (MS24B, 4)],
                         indirect=["cell"])
def test_cell_slot_lifecycle_programs_compile_in_place(topo, monkeypatch,
                                                       cell, tp):
    """PR 35: the one program that arms a slot (sampling state, seed, bias
    row, block-table row) and the one that releases it compile for the 7B on
    one chip and the 24B at tp = 4. Both update the donated state and tables
    where they lie (no second ``counts`` or ``bias``), hand every leaf back
    as it was sharded, so the decode program behind them is the one warm-up
    compiled, and bear names the benchmark's readers do not select: they
    pick programs by ``prefill`` and ``decode``."""
    from localai_tpu.engine import sampling as smp

    cfg, doc = cell
    eng = doc["engine"]
    if tp > 1:
        monkeypatch.setenv("LOCALAI_MESH_OVERLAP", "auto")
    r, a = abstract_runner(
        topo, monkeypatch, cfg, tp=tp, num_slots=eng["max_slots"],
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    ints, floats = smp.SamplingParams.pack()
    where = a["scalar"].sharding

    def host(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=where)

    state_bytes = sum(
        np.prod(s.sharding.shard_shape(s.shape)) * (
            8 if jax.dtypes.issubdtype(s.dtype, jax.dtypes.prng_key)
            else s.dtype.itemsize)
        for s in jax.tree.leaves(a["state"]))
    programs = {
        "_arm_slot_fn": (
            host((3 + len(ints),), i32), host((len(floats),), f32),
            host((cfg.vocab_size,), f32), host((r.max_blocks,), i32)),
        "_release_slot_fn": (a["scalar"],),
    }
    def compiled(name, args):
        return jax.jit(getattr(r, name), donate_argnums=(0, 1)).trace(
            a["state"], a["tables"], *args).lower(
                lowering_platforms=("tpu",)).compile()

    for name, args in programs.items():
        assert "prefill" not in name and "decode" not in name
        c = compiled(name, args)
        assert c.memory_analysis().temp_size_in_bytes < state_bytes / 4, name
        out_state, out_tables = c.output_shardings
        for given, got in zip(jax.tree.leaves(a["state"]),
                              jax.tree.leaves(out_state)):
            assert got.is_equivalent_to(given.sharding, given.ndim), (
                name, given, got)
        assert out_tables.is_equivalent_to(
            a["tables"].sharding, a["tables"].ndim), name


# ---------------------------------------------------------------------------
# the sampler sorts no vocabulary


@pytest.mark.parametrize("cell, tp", [(QN80, 1), (TRL, 1), (MS24B, 4)],
                         indirect=["cell"])
def test_cell_decode_programs_sort_no_vocabulary(topo, monkeypatch, cell, tp):
    """PR 45: the decode step's sampler finds its 256 candidates in two
    stages (``engine/sampling.py top_candidates``: the best chunks of a row,
    then the best of those), so the compiled decode program of the two
    expert cells (vocabularies of 18992 and 25024 a chip: 10% of their steps
    went to one sort of the row block) and of the four-chip cell holds no
    ``sort`` and no ``TopK`` over anything as wide as the ``[S, V]`` logits
    a chip holds. On four chips the logits are sharded over the vocabulary:
    each chip takes the stages over its own 32768 and the chips exchange
    their 256 candidates a row (two all-gathers of ``[32, 1024]``), so no
    collective's result is as wide as a chip's logits either (the
    partitioner, left alone with the stages, re-shards them through 27
    collectives: which is why ``sample`` is handed the mesh)."""
    import re

    cfg, doc = cell
    eng = doc["engine"]
    if tp > 1:
        monkeypatch.setenv("LOCALAI_MESH_OVERLAP", "auto")
    r, a = abstract_runner(
        topo, monkeypatch, cfg, tp=tp,
        quantization=eng.get("quantization") or "",
        num_slots=eng["max_slots"], max_ctx=doc["context_size"],
        kv_num_blocks=eng["kv_num_blocks"], kv_block_tokens=64)
    text = compile_cell_program(r, a, "decode").as_text()
    wide = eng["max_slots"] * cfg.vocab_size // tp

    def widest(line):
        return max(int(np.prod([int(d) for d in dims.split(",")]))
                   for dims in re.findall(r"\[(\d+(?:,\d+)*)\]", line))

    sorts = [ln.strip() for ln in text.splitlines() if re.search(
        r' sort\(|custom_call_target="TopK"', ln)]
    # (under a mesh the stages sit inside a ``shard_map``, which the trace's
    # reader drops from a scope path: ``decode/sample/topk`` on both)
    assert any(re.search(r"/sample/(shard_map/)?topk/", ln) for ln in sorts)
    assert re.search(r"/sample/(shard_map/)?chunk_max/", text)
    for ln in sorts:
        assert widest(ln.split(", metadata=")[0]) < wide, ln[:200]
    if tp > 1:
        talk = collectives(text)
        for _, result, ln in talk:
            assert widest(result) < wide, ln[:200]
        sampler = [ln for _, _, ln in talk if "/sample/" in ln]
        assert len(sampler) == 2 and all(
            " all-gather" in ln and "/sample/shard_map/merge/" in ln
            for ln in sampler), [ln[:160] for ln in sampler]


# ---------------------------------------------------------------------------
# the mesh: a decode step's reduction is one all-reduce a product


@pytest.mark.parametrize("cell, program", [
    (MS24B, "decode"), (MS24B, "decode_n2")], indirect=["cell"])
def test_tp4_decode_layers_hold_two_all_reduces(topo, monkeypatch, cell,
                                                program):
    """PR 49: the four-chip cell's decode programs hold, in the layer scan's
    body, exactly two collectives: the all-reduce of ``[32, 1, 5120]`` after
    ``attn.out``'s product and the one after ``mlp``'s down product, each
    under ``mesh.reduce`` (80 a step over 40 layers). The chunked
    ``psum_scatter`` + ``all_gather`` form the trunk had before reached the
    chip as two all-reduces over tuples of four ``[32, 1, 1280]`` with no
    ``op_name`` and EIGHT all-gathers a layer of what every chip already held
    (400 collectives a step). Every collective of the program carries its
    scope, so a trace's reader finds none under a shape for a name; outside
    the layers the embedding's ``psum``, the step's ``reduce_and`` and the
    sampler's two all-gathers are what PR 45 left."""
    import re

    cfg, doc = cell
    eng = doc["engine"]
    monkeypatch.setenv("LOCALAI_MESH_OVERLAP", "auto")
    r, a = abstract_runner(
        topo, monkeypatch, cfg, tp=4, num_slots=eng["max_slots"],
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    assert r.overlap_mode
    text = compile_cell_program(r, a, program).as_text()
    talk = [(op, result, name[1] if (name := re.search(
        r'op_name="([^"]*)"', ln)) else "")
        for op, result, ln in collectives(text)]
    assert all(name for _, _, name in talk), [t for t in talk if not t[2]]
    S, D = eng["max_slots"], cfg.hidden_size
    layers = [t for t in talk if "/layers/" in t[2]]
    assert sorted((op, re.sub(r"\{[^}]*\}", "", res)) for op, res, _ in layers
                  ) == [("all-reduce", f"bf16[{S},1,{D}]")] * 2, layers
    assert sorted(re.search(r"/(attn\.out|mlp)/mesh\.reduce/", name)[1]
                  for _, _, name in layers) == ["attn.out", "mlp"], layers
    rest = sorted(op for op, _, name in talk if "/layers/" not in name)
    assert rest == ["all-gather", "all-gather", "all-reduce", "all-reduce"], [
        t for t in talk if "/layers/" not in t[2]]


# ---------------------------------------------------------------------------
# the mesh: a chunk reduces the quarters of its bucket that hold a token


@pytest.mark.parametrize("cell, program", [
    (MS24B, "prefill_chunk_512"), (MS24B, "prefill_chunk_512_sample"),
    (MS24B, "prefill_chunk_128")], indirect=["cell"])
def test_tp4_chunk_layers_reduce_their_live_quarters(topo, monkeypatch, cell,
                                                     program):
    """PR 52: the four-chip cell's 512-bucket program of a prompt's LAST
    chunk (the one that samples: the only 512-row chunk that can be part
    full) holds, in the layer scan's body behind the attend, ONE conditional
    of three branches,
    a branch a row count (256, 384, 512: the quarters of the bucket that
    can hold a real token), and each branch the parent's two all-reduces a
    layer over ITS rows: ``bf16[1, rows, 5120]`` after ``attn.out``'s
    product and after ``mlp``'s down product. A chunk of up to 256 real
    tokens pays for 256 rows of products and 2.6 MB a reduction where it
    paid for 512 and 5.2. The branches are handed the stacked ``[40, ...]``
    leaves and the layer's number and cut the layer's weights inside, where
    the dots read them in place: handed the layer's slices (what closing
    over the scanned leaves gives) the conditional has them copied out of
    the stacks first, 131 MB a layer, which only the compiler shows. The
    pool stays the scan's carry and no branch's operand. Every all-reduce
    is a synchronous ``all-reduce``, as in the parent's program: this
    compiler schedules none as a start and a done, and combines two row
    parts' all-reduces into one over a tuple (PERF.md 6, PR 52: why the
    parts do not overlap). The 128-bucket program and the 512-bucket
    program of a chunk that is not the last (512 tokens: every quarter live)
    hold no such conditional and the parent's two whole all-reduces: a
    conditional's result comes back through HBM, ~1 ms of a 41 ms chunk."""
    import re

    cfg, doc = cell
    eng = doc["engine"]
    monkeypatch.setenv("LOCALAI_MESH_OVERLAP", "auto")
    r, a = abstract_runner(
        topo, monkeypatch, cfg, tp=4, num_slots=eng["max_slots"],
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    bucket = int(program.split("_")[2])
    text = compile_cell_program(r, a, program).as_text()
    rows = assert_live_quarters(r, program, text, cfg,
                                a["kv"].k.sharding.shard_shape(a["kv"].k.shape))
    D = cfg.hidden_size
    talk = [(re.sub(r"\{[^}]*\}", "", result), name[1] if (name := re.search(
        r'op_name="([^"]*)"', ln)) else "")
        for op, result, ln in collectives(text) if op == "all-reduce"]
    assert not re.search(r" all-reduce-(start|done)\(", text)
    layers = sorted((res, re.search(r"/(attn\.out|mlp)/", name)[1])
                    for res, name in talk if "/layers/" in name)
    assert layers == sorted((f"bf16[1,{n},{D}]", scope) for n in rows
                            for scope in ("attn.out", "mlp")), layers


def assert_live_quarters(r, program, text, cfg, pool):
    """The row counts the rule gives chunk ``program`` (256, 384 and 512 for
    the 512-row program of a prompt's last chunk, the bucket alone for every
    other), and its compiled ``text`` held to them: ONE conditional whose
    result is the layer's ``bf16[1, bucket, D]``, a branch a row count (none
    where the rows are the bucket alone). Each branch is handed the four
    stacked ``s8[L, ...]`` leaves behind the attend and the layer's number,
    never the pool (``pool``: one device's K or V stack), and produces no
    int8 array of its own: the dots read the layer's weights where they lie
    in the stacks. Returns the row counts."""
    import re

    bucket = int(program.split("_")[2])
    L, D = cfg.num_layers, cfg.hidden_size
    rows = r.chunk_rows(bucket, program.endswith("_sample"))
    assert rows == ((256, 384, 512) if program == "prefill_chunk_512_sample"
                    else (bucket,))
    switch = [ln for ln in text.splitlines() if re.search(
        rf"bf16\[1,{bucket},{D}\]\S* conditional\(", ln)]
    if len(rows) == 1:
        assert not switch
        return rows
    assert len(switch) == 1, switch
    branches = switch[0].split("branch_computations={")[1].split("}")[0]
    branches = [b.strip().lstrip("%") for b in branches.split(",")]
    assert len(branches) == len(rows)
    pool = "[" + ",".join(map(str, pool)) + "]"
    lines = text.splitlines()
    for name in branches:
        at = next(i for i, ln in enumerate(lines)
                  if ln.startswith(f"%{name} ("))
        handed = lines[at].split(") -> ")[0]
        weights = re.findall(r"s8\[([\d,]+)\]", handed)
        assert len(weights) == 4 and all(
            w.startswith(f"{L},") for w in weights), handed
        assert pool not in handed, handed
        body = lines[at + 1:lines.index("}", at)]
        made = [ln.strip()[:120] for ln in body if re.search(
            r" = s8\[[\d,]+\]\S* (?!get-tuple-element\()[\w\-]+\(", ln)]
        assert not made, f"{name} stages int8 weights: {made}"
    return rows


@pytest.mark.parametrize("cell", [M7B, OURO], indirect=True)
def test_one_chip_chunk_layers_run_their_live_quarters(topo, monkeypatch,
                                                       cell):
    """PR 54: the rule PR 52 held to a mesh reads the bucket and the chunk,
    so of the one-chip cells' four chunk programs ONE changes: the 512-row
    program of a prompt's LAST chunk. The other three (the 128 bucket, and a
    512-row chunk that is not the last) lower to the text they lower to
    with the rule taken away: the parent's. What the changed program holds
    once compiled (``assert_live_quarters``: the three row counts as
    branches, handed the stacks, no pool and no weight slice staged, under
    plain jit as under GSPMD; Ouro's inside its loop over four passes, the
    layer's number the cache layer's less ``pass * 48``) is held where the
    cells' programs are compiled anyway:
    ``test_cell_programs_write_the_pool_in_place`` and
    ``test_looped_cell_programs_write_the_pool_in_place``."""
    from localai_tpu.engine import runner as rmod

    cfg, doc = cell
    eng = doc["engine"]
    r, a = abstract_runner(
        topo, monkeypatch, cfg, num_slots=eng["max_slots"],
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    assert r.mesh is None
    programs = ("prefill_chunk_512_sample", "prefill_chunk_512",
                "prefill_chunk_128", "prefill_chunk_128_sample")

    def lowered():
        return {p: compile_cell_program(r, a, p, build=lower_program).as_text()
                for p in programs}

    now = lowered()
    assert [quartered(r, p) for p in programs] == [True, False, False, False]
    monkeypatch.setattr(rmod, "CHUNK_QUARTERED", 1 << 30)
    assert not any(quartered(r, p) for p in programs)
    parent = lowered()
    assert [p for p in programs if now[p] != parent[p]] == [
        "prefill_chunk_512_sample"]


@pytest.mark.parametrize("cell", [M7B, OURO], indirect=True)
def test_a_last_chunk_rides_the_step_in_one_program(topo, monkeypatch, cell):
    """PR 59: at the 7B cell's shapes (and the looped decoder's: four passes
    over the composite attend, no family code) a prompt's 128-row last chunk
    and the decode step are ONE program (``_decode_prefill_paged_fn``), and
    it is held to what each half is alone: no second pool and no layer of
    it copied (the chunk's scatter, its gathers and the kernel that stores
    the step's rows follow each other on the one buffer: a pool-shaped
    ``copy`` between them would cost more than the second weight read
    saved), the chunk's scatters and ONE conditional over its spans, ONE
    Pallas call that writes the step's rows. Its temps stand beside the
    chunk's and the step's own (7B: 3.98 MiB beside 3.28 and 1.69; Ouro:
    18.6 beside 4.06 and 1.23): rows of activations, no weight stack
    staged. The MLP's products run bucket + slots rows: every layer's
    weights are read once for both."""
    import re

    cfg, doc = cell
    eng = doc["engine"]
    r, a = abstract_runner(
        topo, monkeypatch, cfg, num_slots=eng["max_slots"],
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    assert r.rides
    pool = a["kv"].k.shape
    temps = {}
    for program in ("decode_prefill_128", "prefill_chunk_128_sample",
                    "decode"):
        c = compile_cell_program(r, a, program)
        temps[program] = c.memory_analysis().temp_size_in_bytes
        if program == "decode_prefill_128":
            ride = c
    print({k: f"{v / 2**20:.2f} MiB" for k, v in temps.items()})
    assert_in_place("decode_prefill_128", ride, pool)
    text = ride.as_text()
    assert_who_writes("decode_prefill_128", text, pool,
                      spans(r, "decode_prefill_128"))
    assert temps["decode_prefill_128"] < (
        temps["prefill_chunk_128_sample"] + temps["decode"] + 16 * 2**20)
    assert temps["decode_prefill_128"] < cfg.num_layers * cfg.hidden_size ** 2
    # the MLP's products hold the chunk's rows and the step's side by side
    rows = 128 + eng["max_slots"]
    assert re.search(rf"bf16\[{rows},{cfg.intermediate_size}\]", text)
    assert not re.search(rf"bf16\[(128|{eng['max_slots']}),"
                         rf"{cfg.intermediate_size}\]", text)
