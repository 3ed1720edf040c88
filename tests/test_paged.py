"""Paged KV cache tests, the half with no runner: block allocator,
prefix-block sharing, paged attention and the Pallas paged kernel (interpret
mode on the CPU backend) against their references. The runner, scheduler and
mesh cases are tests/test_paged_serving.py: two files, so that
``--dist loadfile`` can run the halves side by side."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu import ops
from localai_tpu.engine.paged import BlockAllocator


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
