"""Paged KV cache tests: block allocator, prefix-block sharing, paged
attention parity vs the contiguous path, chunked-prefill scheduling, and
pool-exhaustion admission control. All on the CPU backend (the Pallas
paged kernel runs in interpret mode)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu import ops
from localai_tpu.engine.paged import BlockAllocator
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.engine.scheduler import GenRequest, Scheduler
from localai_tpu.models.registry import resolve_model
from localai_tpu.obs.flight import FlightRecorder
from localai_tpu.utils.tokenizer import ByteTokenizer


# ---------------------------------------------------------------------------
# BlockAllocator (host bookkeeping)
# ---------------------------------------------------------------------------


def test_allocator_alloc_free_accounting():
    a = BlockAllocator(num_blocks=9, block_tokens=4, max_blocks_per_seq=8)
    st = a.stats()
    assert st.total == 8 and st.free == 8 and st.used == 0

    assert a.allocate(0, tokens=10) == 0          # 3 blocks, no sharing
    assert a.allocate(1, tokens=4) == 0           # 1 block
    st = a.stats()
    assert st.used == 4 and st.free == 4
    assert len(a.tables[0]) == 3 and len(a.tables[1]) == 1
    assert 0 not in a.tables[0] + a.tables[1]     # trash block never handed out

    a.release(0)
    a.release(1)
    st = a.stats()
    # no pool registration happened — everything returns to the free list
    assert st.free == 8 and st.used == 0 and st.cached == 0

    # interleaved alloc/free must never leak or double-free blocks
    # (paging has no external fragmentation; accounting is the invariant)
    rng = np.random.default_rng(0)
    live = {}
    for i in range(200):
        if live and rng.random() < 0.5:
            seq = rng.choice(list(live))
            a.release(int(seq))
            del live[seq]
        else:
            seq = 100 + i
            if a.allocate(seq, tokens=int(rng.integers(1, 20))) is not None:
                live[seq] = True
    for seq in live:
        a.release(int(seq))
    st = a.stats()
    assert st.free == 8 and st.used == 0


def test_allocator_exhaustion_and_extend():
    a = BlockAllocator(num_blocks=5, block_tokens=4, max_blocks_per_seq=4)
    assert a.allocate(0, tokens=12) == 0          # 3 of 4 blocks
    assert a.allocate(1, tokens=8) is None        # needs 2, only 1 free
    assert a.allocate(1, tokens=4) == 0
    assert not a.extend(0, tokens=16)             # no blocks left
    a.release(1)
    assert a.extend(0, tokens=16)
    assert len(a.tables[0]) == 4


def test_allocator_prefix_sharing_and_refcounts():
    a = BlockAllocator(num_blocks=17, block_tokens=4, max_blocks_per_seq=8)
    prompt = list(range(100, 111))                # 11 tokens → 2 full blocks
    assert a.allocate(0, tokens=16, prompt=prompt) == 0
    assert a.register_prefix(0, prompt) == 2
    st = a.stats()
    assert st.cached == 0                         # cached but still referenced
    shared_blocks = a.tables[0][:2]

    # a second sequence with the same prompt shares both full blocks
    assert a.allocate(1, tokens=16, prompt=prompt) == 8
    assert a.tables[1][:2] == shared_blocks
    assert a.shared_blocks[1] == 2

    # diverging prompt shares only the first block
    div = prompt[:6] + [999, 998, 997, 996, 995]
    assert a.allocate(2, tokens=16, prompt=div) == 4
    assert a.tables[2][0] == shared_blocks[0]
    assert a.tables[2][1] not in shared_blocks

    a.release(0)
    a.release(1)
    a.release(2)
    st = a.stats()
    assert st.cached == 2                         # pool keeps the prefix
    assert st.used == 0

    # pool-cached blocks are reclaimed under pressure (LRU eviction)
    assert a.allocate(3, tokens=16 * 4) == 0      # forces eviction
    assert a.evictions_total >= 1


def test_allocator_eviction_never_steals_matched_shared_block():
    """A pool-only (ref==1) block matched as shared prefix for the very
    allocation being built must not be picked as an LRU eviction victim —
    it would land in the table twice (read-only AND writable)."""
    a = BlockAllocator(num_blocks=6, block_tokens=4, max_blocks_per_seq=8)
    pa = list(range(10, 18))                     # prompt A: 1 cacheable block
    pb = list(range(50, 58))                     # prompt B: 1 cacheable block
    a.allocate(0, tokens=8, prompt=pa)
    a.register_prefix(0, pa)
    a.allocate(1, tokens=8, prompt=pb)
    a.register_prefix(1, pb)
    blk_a = a.tables[0][0]
    blk_b = a.tables[1][0]
    a.release(0)
    a.release(1)
    st = a.stats()
    assert st.cached == 2 and st.free == 3

    # needs 5 blocks: 1 shared (A's cached block, LRU-oldest) + 4 fresh —
    # only 3 free, so one eviction must fire and it must pick B's block
    shared = a.allocate(2, tokens=20, prompt=pa)
    assert shared == 4
    table = a.tables[2]
    assert table[0] == blk_a
    assert table.count(blk_a) == 1, "shared block was also handed out fresh"
    assert blk_b in table[1:]                    # B's block was the victim
    assert a.evictions_total == 1
    a.release(2)
    st = a.stats()
    assert st.used == 0 and st.free + st.cached == 5


def test_allocator_never_shares_final_prompt_token_block():
    a = BlockAllocator(num_blocks=9, block_tokens=4, max_blocks_per_seq=8)
    prompt = list(range(8))                       # exactly 2 blocks
    a.allocate(0, tokens=12, prompt=prompt)
    a.register_prefix(0, prompt)
    # (n-1)//bt = 1: the block holding the final token is never shared —
    # its logits must be recomputed to seed sampling
    assert a.match_prefix(prompt) == a.tables[0][:1]


# ---------------------------------------------------------------------------
# paged attention parity (the acceptance-criteria check)
# ---------------------------------------------------------------------------


def test_paged_attention_matches_contiguous_two_lengths(in_stack):
    """Two sequences at different lengths sharing one block pool: paged
    decode attention (lax reference AND Pallas interpret kernel) must
    match the contiguous flash/XLA path to <= 1e-2."""
    rng = np.random.default_rng(7)
    S, Hq, Hkv, hd, bt, MB = 2, 8, 4, 32, 16, 4
    max_ctx = MB * bt
    N = S * MB + 1
    positions = jnp.asarray([13, 55], jnp.int32)   # different lengths

    q = jnp.asarray(rng.normal(size=(S, Hq, hd)), jnp.float32)
    pool_k = jnp.asarray(rng.normal(size=(N, Hkv, bt, hd)), jnp.float32)
    pool_v = jnp.asarray(rng.normal(size=(N, Hkv, bt, hd)), jnp.float32)
    # interleaved physical blocks: slot 0 and 1 alternate through the pool
    tables = jnp.asarray([[1, 3, 5, 7], [2, 4, 6, 8]], jnp.int32)

    # contiguous mirror of the same logical rows
    contig_k = np.zeros((S, Hkv, max_ctx, hd), np.float32)
    contig_v = np.zeros((S, Hkv, max_ctx, hd), np.float32)
    for s in range(S):
        for b in range(MB):
            blk_k = np.asarray(pool_k[int(tables[s, b])])  # [H, bt, hd]
            blk_v = np.asarray(pool_v[int(tables[s, b])])
            contig_k[s, :, b * bt:(b + 1) * bt] = blk_k
            contig_v[s, :, b * bt:(b + 1) * bt] = blk_v

    layer = jnp.int32(1)
    ref_contig = ops.decode_attention(
        q, in_stack(jnp.asarray(contig_k)), in_stack(jnp.asarray(contig_v)),
        layer, positions, interpret=True)
    out_lax = ops.paged_decode_attention_ref(
        q, pool_k, pool_v, tables, positions)
    out_pallas = ops.paged_decode_attention(
        q, in_stack(pool_k), in_stack(pool_v), layer, tables, positions,
        interpret=True)
    assert float(jnp.max(jnp.abs(out_lax - ref_contig))) <= 1e-2
    assert float(jnp.max(jnp.abs(out_pallas - ref_contig))) <= 1e-2


def _kernel_cases():
    """Every local head count against every pool, each with the cells'
    group and block (g 4, bt 64) and with the other pair under a sliding
    window, compared exactly (float32 q); the two pairs the other way
    round; then the cells' own pairing, bf16 q against a bf16 or integer
    pool, whose score matmul takes the operands as stored."""
    pools = ("bfloat16", "int8", "int4")
    cases = [(hkv, *rest, "float32") for hkv in (1, 2, 8) for kv in pools
             for rest in ((4, 64, kv, False), (1, 32, kv, True))]
    cases += [(8, 4, 32, "bfloat16", True, "float32"),
              (8, 1, 64, "bfloat16", False, "float32"),
              (2, 4, 32, "int8", True, "float32"),
              (2, 1, 64, "int8", False, "float32")]
    cases += [(8, 4, 64, "bfloat16", False, "bfloat16"),
              (2, 4, 64, "bfloat16", True, "bfloat16"),
              (8, 4, 64, "int8", True, "bfloat16"),
              (2, 4, 64, "int8", False, "bfloat16")]
    return cases


@pytest.mark.parametrize("hkv,g,bt,kv_dtype,windowed,q_dtype",
                         _kernel_cases())
def test_paged_kernel_matches_reference(hkv, g, bt, kv_dtype, windowed,
                                        q_dtype, in_stack):
    """The kernel (interpret mode) against ``paged_decode_attention_ref``
    at real head width, so that the step it derives (P table entries, from
    the local head count, the block and the element size) is the served
    one: frontiers on both sides of a block's and of a step's last row and
    on the table's last, ragged slots, empty slots on the trash block
    between live ones (what the cross-slot prefetch walks over: their rows
    come out exact zeros), and a call of one slot."""
    from localai_tpu.models.quant import quantize_lastdim, quantize_lastdim4
    from localai_tpu.ops.attention import paged_decode_tiling

    hd = 128
    rng = np.random.default_rng(hkv * 1000 + g * 100 + bt)
    pool_dt = {"bfloat16": jnp.bfloat16}.get(kv_dtype, jnp.int8)
    lanes = hd // 2 if kv_dtype == "int4" else hd
    # the table is wider than two steps and ends inside one
    P, _, _ = paged_decode_tiling(hkv, bt, lanes,
                                  jnp.dtype(pool_dt).itemsize, 1 << 20)
    MB = 2 * P + 1
    edges = [0, bt - 1, None, bt, P * bt - 1, None, P * bt, MB * bt - 1,
             (MB * bt) // 2 + 7]
    positions = np.asarray([p or 0 for p in edges], np.int32)
    live = np.asarray([p is not None for p in edges])
    need = [0 if p is None else p // bt + 1 for p in edges]
    N = sum(need) + 1
    free = list(rng.permutation(np.arange(1, N)))
    tables = np.zeros((len(edges), MB), np.int32)   # trash-padded
    for s, n in enumerate(need):
        tables[s, :n] = [free.pop() for _ in range(n)]
    tables, positions = jnp.asarray(tables), jnp.asarray(positions)
    window = bt + 5 if windowed else None   # a walk that starts mid-step

    q = jnp.asarray(rng.normal(size=(len(edges), hkv * g, hd)),
                    jnp.dtype(q_dtype))
    kf, vf = (jnp.asarray(rng.normal(size=(N, hkv, bt, hd)), jnp.float32)
              for _ in range(2))
    scales = ()
    if kv_dtype == "bfloat16":
        k, v = kf.astype(jnp.bfloat16), vf.astype(jnp.bfloat16)
    else:
        quant = quantize_lastdim4 if kv_dtype == "int4" else quantize_lastdim
        (k, ks), (v, vs) = quant(kf), quant(vf)
        scales = (ks, vs)

    ref = ops.paged_decode_attention_ref(
        q, k, v, tables, positions, *scales, sliding_window=window)
    pool, scales = ([in_stack(a) for a in xs] for xs in ((k, v), scales))
    out = ops.paged_decode_attention(
        q, *pool, jnp.int32(1), tables, positions, *scales,
        sliding_window=window, interpret=True)
    # float32 q: the same float32 arithmetic in another order; bf16 q: the
    # result is rounded to bf16 on both sides
    tol = 2e-5 if q_dtype == "float32" else 1e-2
    out = np.asarray(out, np.float32)
    np.testing.assert_allclose(out[live], np.asarray(ref, np.float32)[live],
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(out[~live], 0.0)
    if windowed:
        return
    one = slice(7, 8)          # S = 1: the first program is the last
    out1 = ops.paged_decode_attention(
        q[one], *pool, jnp.int32(1), tables[one], positions[one], *scales,
        interpret=True)
    np.testing.assert_allclose(np.asarray(out1, np.float32),
                               np.asarray(ref[one], np.float32),
                               rtol=tol, atol=tol)


def _writer_cases():
    """(pool dtype, kv heads, group, block tokens, windowed, cache layers,
    layer): both unscaled dtypes (their tiles hold 16 and 8 rows), g = 4
    and g = 1, a local head count of one chip of four, a block of one tile,
    a sliding window, and the first and last cache layers of a looped
    stack's passes (pass * 48 + layer of 192)."""
    cases = [("bfloat16", 8, 4, 64, False, 2, 1),
             ("bfloat16", 2, 4, 64, True, 2, 0),
             ("bfloat16", 4, 1, 32, False, 2, 1),
             ("bfloat16", 2, 1, 16, True, 3, 2),
             ("float32", 8, 4, 64, True, 2, 1),
             ("float32", 2, 1, 32, False, 2, 0),
             ("float32", 1, 4, 8, False, 2, 1)]
    cases += [("bfloat16", 2, 1, 32, False, 192, layer)
              for layer in (0, 47, 48, 191)]
    return cases


@pytest.mark.parametrize("kv_dtype,hkv,g,bt,windowed,layers,layer",
                         _writer_cases() + [("int8", 2, 4, 32, False, 2, 1)])
def test_paged_kernel_writes_the_row_it_reads(kv_dtype, hkv, g, bt, windowed,
                                              layers, layer):
    """PR 38: over an unscaled pool the decode policy (``raw=True``) hands
    the stack back untouched and the kernel writes the step's rows. After a
    step through that path the pool equals the pool after ``_write_rows``
    BIT FOR BIT outside block 0, in every layer (what is copied back beside
    the one row is what those rows held), block 0 is as it was (a slot on
    the trash block writes nothing), and the output is the kernel's over
    the scattered pool to the bit, and the reference's. Frontiers at rows
    0, 15, 16 and bt - 1 of a block, on both sides of a step's last row, on
    the table's last row, a released slot between live ones. A scaled pool
    keeps the scatter: the policy writes, the attend returns no stack."""
    from functools import partial

    from localai_tpu.engine import kvcache as kvc
    from localai_tpu.models.quant import quantize_lastdim
    from localai_tpu.ops.attention import paged_decode_tiling

    hd = 128
    rng = np.random.default_rng(hkv * 1000 + g * 100 + bt + layer)
    pool_dt = jnp.dtype("int8" if kv_dtype == "int8" else kv_dtype)
    P, _, _ = paged_decode_tiling(hkv, bt, hd, pool_dt.itemsize, 1 << 20)
    MB = 2 * P + 1
    rows = sorted({0, 15 % bt, 16 % bt, bt - 1})
    edges = [*rows, None, *(bt + r for r in rows), P * bt - 1, P * bt,
             None, MB * bt - 1, (MB * bt) // 2 + 7]
    if layers > 8:                      # the looped stack: a few slots do
        edges = [15 % bt, None, bt + 16 % bt, MB * bt - 1]
    positions = np.asarray([p or 0 for p in edges], np.int32)
    live = np.asarray([p is not None for p in edges])
    need = [0 if p is None else p // bt + 1 for p in edges]
    N = sum(need) + 1
    free = list(rng.permutation(np.arange(1, N)))
    tables = np.zeros((len(edges), MB), np.int32)   # trash-padded
    for s, n in enumerate(need):
        tables[s, :n] = [free.pop() for _ in range(n)]
    tables, positions = jnp.asarray(tables), jnp.asarray(positions)
    window = bt + 5 if windowed else None
    S = len(edges)

    q = jnp.asarray(rng.normal(size=(S, hkv * g, hd)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(S, 1, hkv, hd)),
                                jnp.float32) for _ in range(2))
    full = [jnp.asarray(rng.normal(size=(layers, N, hkv, bt, hd)),
                        jnp.float32) for _ in range(2)]
    if kv_dtype == "int8":
        (k, ks), (v, vs) = (quantize_lastdim(a) for a in full)
        stack = (k, v, ks, vs)
    else:
        stack = tuple(a.astype(pool_dt) for a in full)
    at = jnp.int32(layer)
    blk = tables[jnp.arange(S), positions // bt]
    want = kvc._write_rows(stack, at, blk, positions % bt,
                           k_new[:, 0], v_new[:, 0])

    kernel = partial(ops.paged_decode_attention, sliding_window=window,
                     interpret=True)
    write = kvc.paged_decode_write(tables, positions, raw=True)
    new, keys, values = write(stack, at, k_new, v_new)
    got = kvc.kernel_attend(kernel, tables, positions)(
        q[:, None], keys, values, None)
    parent = kernel(q, want[0], want[1], at, tables, positions, *want[2:])
    if kv_dtype == "int8":
        # the scatter path, as it was: the policy wrote, the views name the
        # new stack and carry no rows, the attend hands back no stack
        assert keys.new is None and values.new is None
        for a, b in zip(new, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert keys.cache is new[0] and keys.scale is new[2]
        np.testing.assert_array_equal(np.asarray(got[:, 0]),
                                      np.asarray(parent))
        return
    assert all(a is b for a, b in zip(new, stack))      # untouched
    assert keys.cache is stack[0] and values.cache is stack[1]
    assert keys.new.dtype == values.new.dtype == pool_dt
    out, pools = got
    assert len(pools) == 2
    for was, now, scattered in zip(stack, pools, want):
        was, now, scattered = (np.asarray(a, np.float32)
                               for a in (was, now, scattered))
        np.testing.assert_array_equal(now[:, 1:], scattered[:, 1:])
        np.testing.assert_array_equal(now[:, 0], was[:, 0])
    np.testing.assert_array_equal(np.asarray(out[live, 0]),
                                  np.asarray(parent[live]))
    ref = ops.paged_decode_attention_ref(
        q, want[0][layer], want[1][layer], tables, positions,
        sliding_window=window)
    tol = 2e-5 if kv_dtype == "float32" else 1e-2
    np.testing.assert_allclose(np.asarray(out[live, 0]),
                               np.asarray(ref[live]), rtol=tol, atol=tol)


def _empty_slot_batches(bt, ctx):
    """name -> a batch's frontiers, None for a slot on the trash block.
    Few distinct shapes (5, 11, 32 and 1 slots; 4 and 1 live), so that the
    cases of one pool, role and window share their compiled calls."""
    a, b, c, d = bt + 3, ctx - 1, 2 * bt, bt - 1
    return {
        "slot_0_empty": [None, a, b, c, d],
        "last_empty_behind_a_live_one": [a, b, c, d, None],
        "runs_of_empties": [a, None, None, None, b, None, None, None, None,
                            c, d],
        "one_live_of_32": [None] * 13 + [b] + [None] * 18,
        "every_slot_empty": [None] * 5,
        "one_slot_and_it_is_empty": [None],
    }


@functools.partial(jax.jit, static_argnames=("role", "window"))
def _step_through_the_kernel(stack, at, q, k_new, v_new, tables, positions,
                             *, role, window):
    """(output [S, Hq, hd], the stack after the step). ``reader``: the
    kernel alone over the stack as it is; ``writer``: through the decode
    policy (``raw=True``), where the kernel writes an unscaled pool's rows
    and a scaled pool's are scattered before it reads."""
    from localai_tpu.engine import kvcache as kvc

    kernel = functools.partial(ops.paged_decode_attention,
                               sliding_window=window, interpret=True)
    if role == "reader":
        return kernel(q, stack[0], stack[1], at, tables, positions,
                      *stack[2:]), stack
    new, keys, values = kvc.paged_decode_write(tables, positions, raw=True)(
        stack, at, k_new, v_new)
    got = kvc.kernel_attend(kernel, tables, positions)(
        q[:, None], keys, values, None)
    if len(stack) == 4:
        return got[:, 0], new
    return got[0][:, 0], got[1]


@pytest.mark.parametrize("windowed", [False, True], ids=["full", "window"])
@pytest.mark.parametrize("role", ["writer", "reader"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("batch", list(_empty_slot_batches(1, 1)))
def test_paged_kernel_does_nothing_for_a_slot_on_the_trash_block(
        batch, kv_dtype, role, windowed):
    """PR 50: a program whose slot's frontier entry is block 0 copies
    nothing, folds nothing and writes zeros; the cross-slot prefetch, the
    cold start and the write-back in flight go from live slot to live slot
    over it. So the live rows of the output, and the pool, equal TO THE BIT
    those of the same call over the live slots alone, in order; the empty
    rows are exact zeros; the live rows are the reference's. Where the
    kernel is the writer (an unscaled pool through the policy) the pool
    equals ``_write_rows``' bit for bit outside block 0 and block 0 is as
    it was: with the last slot empty the last program waits for a
    write-back that is not its own, with every slot empty nothing moves."""
    from localai_tpu.engine import kvcache as kvc
    from localai_tpu.models.quant import quantize_lastdim
    from localai_tpu.ops.attention import paged_decode_tiling

    hkv, g, bt, hd, layers, layer = 2, 4, 32, 128, 2, 1
    pool_dt = jnp.dtype(kv_dtype)
    MB = 3      # a narrow table: steps of two entries, the second ragged
    assert paged_decode_tiling(hkv, bt, hd, pool_dt.itemsize, MB)[0] == 2
    edges = _empty_slot_batches(bt, MB * bt)[batch]
    S, N = len(edges), 10
    rng = np.random.default_rng(S * 10 + windowed)
    live = np.asarray([p is not None for p in edges])
    positions = np.asarray([p or 0 for p in edges], np.int32)
    free = list(rng.permutation(np.arange(1, N)))
    tables = np.zeros((S, MB), np.int32)            # trash-padded
    for s, p in enumerate(edges):
        if p is not None:
            tables[s, :p // bt + 1] = [free.pop() for _ in range(p // bt + 1)]
    tables, positions = jnp.asarray(tables), jnp.asarray(positions)

    q = jnp.asarray(rng.normal(size=(S, hkv * g, hd)), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.normal(size=(S, 1, hkv, hd)),
                                jnp.float32) for _ in range(2))
    full = [jnp.asarray(rng.normal(size=(layers, N, hkv, bt, hd)),
                        jnp.float32) for _ in range(2)]
    if kv_dtype == "int8":
        (k, ks), (v, vs) = (quantize_lastdim(a) for a in full)
        stack = (k, v, ks, vs)
    else:
        stack = tuple(a.astype(pool_dt) for a in full)
    at = jnp.int32(layer)

    def call(keep):
        return _step_through_the_kernel(
            stack, at, q[keep], k_new[keep], v_new[keep], tables[keep],
            positions[keep], role=role,
            window=bt + 5 if windowed else None)

    out, after = call(np.arange(S))
    out = np.asarray(out)
    assert out.shape == (S, hkv * g, hd)
    np.testing.assert_array_equal(out[~live], 0.0)
    wrote = role == "writer" and kv_dtype != "int8"
    want = after
    if wrote:
        blk = tables[jnp.arange(S), positions // bt]
        want = kvc._write_rows(stack, at, blk, positions % bt,
                               k_new[:, 0], v_new[:, 0])
        for was, now, scattered in zip(stack, after, want):
            was, now, scattered = (np.asarray(a, np.float32)
                                   for a in (was, now, scattered))
            np.testing.assert_array_equal(now[:, 1:], scattered[:, 1:])
            np.testing.assert_array_equal(now[:, 0], was[:, 0])
    if not live.any():
        return
    alone, after_alone = call(np.flatnonzero(live))
    np.testing.assert_array_equal(out[live], np.asarray(alone))
    for a, b in zip(after, after_alone):
        a, b = (np.asarray(x, np.float32) for x in (a, b))
        np.testing.assert_array_equal(a[:, 1:], b[:, 1:])
        # (a scaled pool's scatter lays empty slots' rows on block 0; the
        # kernel lays nothing there)
        if wrote or role == "reader":
            np.testing.assert_array_equal(a[:, 0], b[:, 0])
    ref = ops.paged_decode_attention_ref(
        q, want[0][layer], want[1][layer], tables, positions,
        *(sc[layer] for sc in want[2:]),
        sliding_window=bt + 5 if windowed else None)
    np.testing.assert_allclose(out[live], np.asarray(ref)[live],
                               rtol=1e-2, atol=1e-2)


# The greedy pair (two prompts sharing the pool, token for token against the
# contiguous runner) is the ``decode`` case of tests/test_kv_contract.py
# ``test_both_layouts_emit_the_same_tokens_through_the_one_family``.


def test_paged_runner_pallas_kernel_matches_xla_end_to_end():
    """The Pallas paged-decode kernel (interpret mode on CPU) wired
    through the runner must reproduce the gather+XLA paged path."""
    tiny = resolve_model("debug:tiny", dtype="float32")
    outs = {}
    for impl in ("xla", "pallas_interpret"):
        r = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                        prefill_buckets=[16], kv_dtype="float32",
                        paged=True, kv_block_tokens=16, prefill_chunk=16,
                        attn_impl=impl)
        assert r.paged_attn_impl == ("pallas" if impl != "xla" else "xla")
        s = r.acquire_slot()
        t = r.admit(s, list(b"kernel parity"), temperature=0.0)
        outs[impl] = [t] + [int(r.step()[s]) for _ in range(6)]
    assert outs["pallas_interpret"] == outs["xla"]


def test_paged_runner_int8_kv_matches_contiguous():
    """Scaled-int8 pool: paged quantized decode must track the contiguous
    quantized path (identical quantization grid → identical tokens)."""
    tiny = resolve_model("debug:tiny", dtype="float32")
    rc = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                     prefill_buckets=[16], kv_dtype="int8")
    rp = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                     prefill_buckets=[16], kv_dtype="int8",
                     paged=True, kv_block_tokens=16, prefill_chunk=16)
    prompt = list(b"quantized kv")
    outs = {}
    for name, r in (("contig", rc), ("paged", rp)):
        s = r.acquire_slot()
        t = r.admit(s, prompt, temperature=0.0)
        outs[name] = [t] + [int(r.step()[s]) for _ in range(6)]
    assert outs["paged"] == outs["contig"]


def test_paged_prefix_pool_reuse_preserves_output():
    """Pool-shared prefix blocks must not change greedy output, and the
    second admission must actually reuse blocks."""
    tiny = resolve_model("debug:tiny", dtype="float32")
    r = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=96,
                    prefill_buckets=[16, 32], kv_dtype="float32",
                    paged=True, kv_block_tokens=16, prefill_chunk=16)
    prompt = list(b"shared system prompt here plus tail")
    s = r.acquire_slot()
    first = [r.admit(s, prompt, temperature=0.0)]
    first += [int(r.step()[s]) for _ in range(5)]
    r.release(s)
    assert r.allocator.stats().cached > 0

    s2 = r.acquire_slot()
    second = [r.admit(s2, prompt, temperature=0.0)]
    assert r.last_prefix_reused >= r.block_tokens
    assert r.last_prefill_path == "paged_shared"
    second += [int(r.step()[s2]) for _ in range(5)]
    assert second == first


# ---------------------------------------------------------------------------
# chunked prefill scheduling + admission control
# ---------------------------------------------------------------------------


def _paged_sched(tiny, flight=None, **kw):
    runner = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=96,
                         prefill_buckets=[16, 32], kv_dtype="float32",
                         paged=True, kv_block_tokens=16, prefill_chunk=16,
                         **kw)
    return Scheduler(runner, ByteTokenizer(), flight=flight)


@pytest.fixture(scope="module")
def tiny():
    return resolve_model("debug:tiny", dtype="float32")


def test_chunked_prefill_interleaves_with_decode(tiny):
    """A long prompt's chunks must not stall an active slot: decode
    dispatches appear BETWEEN its prefill_chunk dispatches in the flight
    timeline."""
    flight = FlightRecorder(256)
    s = _paged_sched(tiny, flight=flight)
    try:
        a = s.submit(GenRequest(prompt=list(b"warm"), max_new_tokens=48,
                                temperature=0.0))
        # wait until A is actively decoding
        while a.completion_tokens < 2:
            pass
        long_prompt = list(b"x" * 80)              # 5 chunks of 16
        b = s.submit(GenRequest(prompt=long_prompt, max_new_tokens=4,
                                temperature=0.0))
        a.result(timeout=60)
        b.result(timeout=60)
    finally:
        s.shutdown()
    progs = [rec["program"] for rec in flight.snapshot(limit=256)]
    chunk_idx = [i for i, p in enumerate(progs) if p == "prefill_chunk"]
    assert len(chunk_idx) >= 5, progs
    interleaved = any(
        any(p != "prefill_chunk" for p in progs[i + 1:j])
        for i, j in zip(chunk_idx, chunk_idx[1:])
    )
    assert interleaved, progs
    assert s.total_prefill_chunks >= 5


def test_pool_exhaustion_holds_request_until_blocks_free(tiny):
    """With a pool too small for two concurrent reservations, the second
    request waits (held, not errored) and completes after the first frees
    its blocks."""
    # 7 allocatable blocks of 16 = 112 rows; each request reserves
    # prompt + max_new + 1 capped at max_ctx (96 rows = 6 blocks)
    s = _paged_sched(tiny, kv_num_blocks=8)
    try:
        a = s.submit(GenRequest(prompt=list(b"first request"),
                                max_new_tokens=90, temperature=0.0))
        b = s.submit(GenRequest(prompt=list(b"second request"),
                                max_new_tokens=90, temperature=0.0))
        ra = a.result(timeout=120)
        rb = b.result(timeout=120)
        assert ra.finish_reason is not None
        assert rb.finish_reason is not None
        assert a.admit_index < b.admit_index
    finally:
        s.shutdown()


def test_cancel_races_pool_exhaustion_hold(tiny):
    """A request cancelled while parked in the scheduler's pool-
    exhaustion hold (``_held``) must resolve ``cancelled``, release its
    head-of-line place, and let a successor admit — with every block
    conserved afterwards."""
    import time

    s = _paged_sched(tiny, kv_num_blocks=8)
    try:
        a = s.submit(GenRequest(prompt=list(b"pool filler request"),
                                max_new_tokens=90, temperature=0.0))
        held = s.submit(GenRequest(prompt=list(b"about to be held"),
                                   max_new_tokens=90, temperature=0.0))
        deadline = time.monotonic() + 30
        while s._held is not held and time.monotonic() < deadline:
            time.sleep(0.005)
        assert s._held is held, "second request never parked in the hold"
        held.cancel()
        successor = s.submit(GenRequest(prompt=list(b"held successor"),
                                        max_new_tokens=8, temperature=0.0))
        held.result(timeout=60)
        assert held.finish_reason == "cancelled"
        a.result(timeout=120)
        successor.result(timeout=120)
        assert a.finish_reason is not None
        assert successor.finish_reason in ("stop", "length")
        # the cancelled hold left nothing behind: all blocks return and
        # the allocator's conservation invariants hold
        st = s.runner.allocator.stats()
        assert st.free + st.cached == st.total
        assert s.runner.allocator.check_invariants() == []
    finally:
        s.shutdown()


def test_paged_metrics_export_block_gauges(tiny):
    s = _paged_sched(tiny)
    try:
        s.generate(GenRequest(prompt=list(b"metrics"), max_new_tokens=4,
                              temperature=0.0), timeout=60)
        m = s.metrics()
        assert m["kv_block_tokens"] == 16
        assert m["kv_blocks_total"] > 0
        assert m["kv_blocks_free"] + m["kv_blocks_used"] == m["kv_blocks_total"]
        assert m["prefill_chunks"] >= 1
        assert "prefill_chunk_queue_depth" in m
        assert 0.0 <= m["kv_utilization"] <= 1.0

        from localai_tpu.obs import metrics as obs_metrics

        reg = obs_metrics.Registry()
        obs_metrics.update_engine_gauges("tiny", m, registry=reg)
        text = reg.render()
        assert 'localai_kv_blocks_free{model="tiny"}' in text
        assert 'localai_kv_blocks_used{model="tiny"}' in text
        assert 'localai_prefill_chunk_queue_depth{model="tiny"}' in text
        # the admission path's counters: one admission, no blocking device
        # read on it, the arming update and one chunk
        assert (m["admissions"], m["admit_blocking_reads"],
                m["admit_programs"]) == (1, 0, 2)
        for name, value in (("admissions", 1), ("admit_blocking_reads", 0),
                            ("admit_programs", 2)):
            assert (f'localai_{name}_total{{model="tiny"}} {value}'
                    in text), name
    finally:
        s.shutdown()


@pytest.mark.parametrize("kv_dtype, attn_impl, writer", [
    ("float32", "pallas_interpret", "kernel"),
    ("int8", "pallas_interpret", "scatter"),
    ("float32", "xla", "scatter")])
def test_metrics_say_who_writes_the_decode_rows(tiny, kv_dtype, attn_impl,
                                                writer):
    """``localai_paged_kv_write_impl{impl=kernel|scatter}``, one-hot beside
    ``localai_paged_kernel_impl``: the kernel writes an unscaled pool it
    attends over; a scaled pool and the XLA attend keep the scatter."""
    from localai_tpu.obs import metrics as obs_metrics

    runner = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=96,
                         prefill_buckets=[16, 32], kv_dtype=kv_dtype,
                         paged=True, kv_block_tokens=16, prefill_chunk=16,
                         attn_impl=attn_impl)
    assert runner.paged_kv_write_impl == writer
    s = Scheduler(runner, ByteTokenizer())
    try:
        s.generate(GenRequest(prompt=list(b"who writes"), max_new_tokens=3,
                              temperature=0.0), timeout=60)
        m = s.metrics()
    finally:
        s.shutdown()
    assert m["paged_kv_write_impl"] == writer
    reg = obs_metrics.Registry()
    obs_metrics.update_engine_gauges("tiny", m, registry=reg)
    text = reg.render()
    for label in ("kernel", "scatter"):
        assert (f'localai_paged_kv_write_impl{{impl="{label}",model="tiny"}} '
                f'{1.0 if label == writer else 0.0}') in text, text


def test_disk_prefix_export_transfers_across_layouts(tiny):
    """The disk prompt-cache export format is layout-independent: rows
    exported from a paged pool load into a contiguous cache and vice
    versa, and the resumed generation matches the original."""
    def mk(paged):
        kw = ({"kv_block_tokens": 16, "prefill_chunk": 16} if paged else {})
        return ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=96,
                           prefill_buckets=[16, 32], kv_dtype="float32",
                           paged=paged, **kw)

    prompt = list(b"a long shared system prompt for the cache")
    src = mk(True)
    s = src.acquire_slot()
    base = [src.admit(s, prompt, temperature=0.0)]
    base += [int(src.step()[s]) for _ in range(5)]
    arrays = src.export_prefix(s, len(prompt))

    for paged in (True, False):
        dst = mk(paged)
        s2 = dst.acquire_slot()
        assert dst.load_prefix(s2, arrays, len(prompt))
        t = dst.admit(s2, prompt, temperature=0.0,
                      resident=list(prompt), valid_n=len(prompt))
        assert dst.last_prefix_reused == len(prompt) - 1
        out = [t] + [int(dst.step()[s2]) for _ in range(5)]
        assert out == base, (paged, out, base)


def test_spec_decoder_accepts_paged_runner(tiny):
    """The PR 6 'SpecDecoder rejects paged runners' guard is gone: the
    block-native lane (localai_tpu.spec) verifies draft windows straight
    through the paged table mirror. Only a PAGED DRAFT stays rejected —
    its window scans run over contiguous slot rows."""
    from localai_tpu.engine.speculative import SKIP, SpecDecoder

    rp = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                     prefill_buckets=[16], kv_dtype="float32", paged=True)
    rc = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                     prefill_buckets=[16], kv_dtype="float32", paged=False)
    spec = SpecDecoder(rp, rc, gamma=2)
    slot = spec.acquire_slot()
    spec.admit(slot, list(b"paged spec"), temperature=0.0)
    rows = spec.step_spec()
    assert 1 <= int((rows[:, slot] != SKIP).sum()) <= 3
    assert not rp.allocator.check_invariants()

    rp2 = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                      prefill_buckets=[16], kv_dtype="float32", paged=True)
    with pytest.raises(ValueError, match="contiguous"):
        SpecDecoder(rc, rp2)


# ---------------------------------------------------------------------------
# meshed paged serving (ISSUE 8): the block pool sharded over a CPU mesh
# ---------------------------------------------------------------------------


def _tp_mesh():
    """data=4 × model=2 over the conftest's 8 virtual CPU devices: tiny's
    2 kv heads split over 'model', 4 slots over 'data'."""
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    return build_mesh(MeshPlan(data=4, model=2))


def test_runner_accepts_mesh_with_paged(tiny):
    """mesh != None with paged=True is a supported configuration (the PR 6
    'mesh forces contiguous' incompatibility is gone); only pipeline
    parallelism still forces the slot-contiguous layout."""
    from localai_tpu.parallel import sharding as shd
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    mesh = _tp_mesh()
    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    r = ModelRunner(tiny.cfg, params, num_slots=4, max_ctx=64,
                    prefill_buckets=[16], kv_dtype="float32", mesh=mesh,
                    paged=True, kv_block_tokens=16)
    assert r.paged and r.mesh is mesh

    from localai_tpu.parallel.pipeline import shard_params_pp

    import jax

    pp_mesh = build_mesh(MeshPlan(pipe=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="pipeline parallelism"):
        ModelRunner(tiny.cfg, shard_params_pp(tiny.params, tiny.cfg, pp_mesh),
                    num_slots=2, max_ctx=64, prefill_buckets=[16],
                    kv_dtype="float32", mesh=pp_mesh, paged=True)


def test_meshed_paged_matches_single_device_greedy(tiny):
    """Greedy parity: the head-sharded pool + data-sharded table mirror
    must reproduce the single-device paged engine token-for-token, two
    prompts of different lengths sharing the pool (chunked + short)."""
    from localai_tpu.parallel import sharding as shd

    mesh = _tp_mesh()
    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    kw = dict(num_slots=4, max_ctx=96, prefill_buckets=[16, 32],
              kv_dtype="float32", paged=True, kv_block_tokens=16,
              prefill_chunk=16)
    pa = list(b"the quick brown fox jumps over the dog")  # 3 chunks
    pb = list(b"hi")
    seqs = {}
    for name, r in (
        ("single", ModelRunner(tiny.cfg, tiny.params, **kw)),
        ("mesh", ModelRunner(tiny.cfg, params, mesh=mesh, **kw)),
    ):
        s1 = r.acquire_slot()
        t1 = r.admit(s1, pa, temperature=0.0)
        s2 = r.acquire_slot()
        t2 = r.admit(s2, pb, temperature=0.0)
        a, b = [t1], [t2]
        for _ in range(8):
            toks = r.step()
            a.append(int(toks[s1]))
            b.append(int(toks[s2]))
        seqs[name] = (a, b)
    assert seqs["mesh"] == seqs["single"]


def test_meshed_paged_int8_matches_single_device(tiny):
    """Scaled-int8 pool under the mesh: the f32 scale pool shards
    alongside the int8 values (same spec minus head_dim) and greedy
    decode tracks the single-device quantized path."""
    from localai_tpu.parallel import sharding as shd

    mesh = _tp_mesh()
    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    kw = dict(num_slots=4, max_ctx=64, prefill_buckets=[16, 32],
              kv_dtype="int8", paged=True, kv_block_tokens=16,
              prefill_chunk=16)
    prompt = list(b"quantized kv under a mesh")
    outs = {}
    for name, r in (
        ("single", ModelRunner(tiny.cfg, tiny.params, **kw)),
        ("mesh", ModelRunner(tiny.cfg, params, mesh=mesh, **kw)),
    ):
        s = r.acquire_slot()
        t = r.admit(s, prompt, temperature=0.0)
        outs[name] = [t] + [int(r.step()[s]) for _ in range(6)]
    assert outs["mesh"] == outs["single"]


def test_ring_paged_prefill_matches_contiguous_sp(tiny):
    """A long prompt on a 'seq' mesh takes the ring-attention paged path
    (one dispatch over all chips, K/V scattered through the block table)
    and must emit the same greedy stream as the contiguous SP engine —
    both prefills run the identical ring math, so this pins the paged
    scatter + paged decode halves."""
    import jax

    from localai_tpu.parallel import sharding as shd
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    mesh = build_mesh(MeshPlan(data=2, seq=2, model=2))
    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    rc = ModelRunner(tiny.cfg, params, num_slots=4, max_ctx=128,
                     prefill_buckets=[64], kv_dtype="float32", mesh=mesh,
                     sp_threshold=32)
    rp = ModelRunner(tiny.cfg, params, num_slots=4, max_ctx=128,
                     prefill_buckets=[64], kv_dtype="float32", mesh=mesh,
                     sp_threshold=32, paged=True, kv_block_tokens=16,
                     prefill_chunk=16)
    assert rp.sp_enabled
    prompt = list(range(1, 45))
    sc = rc.acquire_slot()
    tc = rc.admit(sc, prompt, temperature=0.0)
    assert rc.last_prefill_path == "sp"
    sp = rp.acquire_slot()
    tp = rp.admit(sp, prompt, temperature=0.0)
    assert rp.last_prefill_path == "paged_sp"
    a = [tc] + [int(rc.step()[sc]) for _ in range(6)]
    b = [tp] + [int(rp.step()[sp]) for _ in range(6)]
    assert a == b

    # short prompts stay on the chunked path (no seq-wide dispatch for a
    # prompt that fits one chunk)
    s2 = rp.acquire_slot()
    rp.admit(s2, list(b"short"), temperature=0.0)
    assert rp.last_prefill_path == "paged"


def test_kv_overcommit_ratio_scales_default_pool(tiny, monkeypatch):
    """LOCALAI_KV_OVERCOMMIT scales the default pool past (or under) the
    contiguous footprint; explicit kv_num_blocks still wins."""
    kw = dict(num_slots=2, max_ctx=64, prefill_buckets=[16],
              kv_dtype="float32", paged=True, kv_block_tokens=16)
    base = ModelRunner(tiny.cfg, tiny.params, **kw)
    assert base.kv_overcommit == 1.0
    contiguous_blocks = 2 * base.max_blocks + 1

    monkeypatch.setenv("LOCALAI_KV_OVERCOMMIT", "1.5")
    grown = ModelRunner(tiny.cfg, tiny.params, **kw)
    assert grown.kv_overcommit == 1.5
    assert grown.allocator.num_blocks == int(
        2 * base.max_blocks * 1.5) + 1 > contiguous_blocks

    monkeypatch.setenv("LOCALAI_KV_OVERCOMMIT", "0.5")
    shrunk = ModelRunner(tiny.cfg, tiny.params, **kw)
    assert shrunk.allocator.num_blocks < contiguous_blocks

    explicit = ModelRunner(tiny.cfg, tiny.params, kv_num_blocks=7, **kw)
    assert explicit.allocator.num_blocks == 7  # absolute count wins

    sched = Scheduler(base, ByteTokenizer())
    try:
        assert sched.metrics()["kv_overcommit_ratio"] == 1.0
    finally:
        sched.shutdown()
