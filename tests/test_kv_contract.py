"""The KV write contract: ``kv_write(kv_stack, layer, k_new, v_new)``.

Since the stacked cache rides the layer scan as a carry (models.llama
.forward), every policy of engine.kvcache takes the WHOLE stack and the
layer index, scatters only the new rows into it and exposes what the attend
reads. Each case below holds one policy, on one cache dtype, to a plain
row-by-row numpy writer: exactly the rows the per-layer policies wrote
(the trash block's too, where a policy sends rows there), in the one layer
named, and not one other element of the stack changed; what it exposes is
that layer of the new stack. The kernels are held to their references on
the layer they are given, over a stack whose other layers hold noise.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu import ops
from localai_tpu.engine import kvcache as kvc
from localai_tpu.models.quant import (quantize_lastdim, quantize_lastdim4,
                                      unpack_int4_lastdim)

L, LAYER = 3, 1
H, HD = 2, 16
BT, MB, N = 8, 3, 12            # paged: block tokens, blocks a slot, pool
S, C = 3, 24                    # contiguous: slots, context (= MB * BT)
DT = jnp.float32                # the activations' dtype


def _noise_stack(rng, kv_dtype, lead):
    """A stacked cache full of noise (so an element that changed shows),
    as the 2- or 4-tuple ``stacked()`` gives. ``lead`` = (N,) or (S,)."""
    tokens = BT if lead == (N,) else C
    shape = (L, *lead, H, tokens, HD // 2 if kv_dtype == "int4" else HD)
    if kv_dtype == "bfloat16":
        return tuple(jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
                     for _ in range(2))
    vals = [jnp.asarray(rng.integers(-100, 100, shape), jnp.int8)
            for _ in range(2)]
    scales = [jnp.asarray(rng.uniform(0.01, 0.02, shape[:4]), jnp.float32)
              for _ in range(2)]
    return (*vals, *scales)


def _stored(kv_dtype, rows):
    """(what the cache stores for ``rows [..., H, HD]``, its scales or None)."""
    if kv_dtype == "bfloat16":
        return np.asarray(rows.astype(jnp.bfloat16).astype(jnp.float32)), None
    quant = quantize_lastdim4 if kv_dtype == "int4" else quantize_lastdim
    q, scale = quant(rows)
    return np.asarray(q), np.asarray(scale)


def _expected(stack, kv_dtype, targets, k_rows, v_rows):
    """The stack after a plain writer put row i of ``k_rows``/``v_rows``
    ``[n, H, HD]`` at ``[LAYER, lead, :, tok]`` for ``targets[i] = (lead,
    tok)``: numpy, one row at a time."""
    out = [np.array(a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a)
           for a in stack]
    for which, rows in ((0, k_rows), (1, v_rows)):
        vals, scales = _stored(kv_dtype, rows)
        for i, (lead, tok) in enumerate(targets):
            out[which][LAYER, lead, :, tok] = vals[i]
            if scales is not None:
                out[which + 2][LAYER, lead, :, tok] = scales[i]
    return out


def _assert_stack(new, expected):
    assert len(new) == len(expected)
    for got, want in zip(new, expected):
        got = np.asarray(got.astype(jnp.float32)
                         if got.dtype == jnp.bfloat16 else got)
        np.testing.assert_array_equal(got, want)


def _dequant_layer(new, kv_dtype):
    """The named layer of the new stack as the XLA attend reads it."""
    def one(cache, scale=None):
        a = cache[LAYER]
        if kv_dtype == "int4":
            a = unpack_int4_lastdim(a)
        a = a.astype(DT)
        return a if scale is None else a * scale[LAYER][..., None].astype(DT)

    if len(new) == 4:
        return one(new[0], new[2]), one(new[1], new[3])
    return one(new[0]), one(new[1])


def _assert_views(new, keys, values):
    """``raw=True``: the kernel gets the stack itself and the layer (the
    new stack, or the one it is to write: ``LayerView.new``)."""
    scales = new[2:] if len(new) == 4 else (None, None)
    for view, cache, scale in zip((keys, values), new[:2], scales):
        assert isinstance(view, kvc.LayerView)
        assert view.scale is scale and int(view.layer) == LAYER
        assert view.cache is cache or view.new is not None


def _written_by_the_kernel(stack, new, keys, values, k_rows, v_rows):
    """``raw=True`` over an unscaled pool (PR 38): the policy hands the
    stack back untouched, the views carry the rows as the pool stores them,
    and the paged kernel writes them (here at a block narrower than a tile:
    the whole block goes back). Returns the stack the attend hands back."""
    from functools import partial

    assert all(a is b for a, b in zip(new, stack))
    for view, rows in zip((keys, values), (k_rows, v_rows)):
        np.testing.assert_array_equal(
            np.asarray(view.new.astype(jnp.float32)), _stored("bfloat16", rows)[0])
    attend = kvc.kernel_attend(
        partial(ops.paged_decode_attention, interpret=True),
        jnp.asarray(TABLES), jnp.asarray(POSITIONS))
    q = jnp.zeros((S, 1, 2 * H, HD), DT)
    _, written = attend(q, keys, values, None)
    # what the next layer's policy is handed: views of the written stack
    return written


def _rows(rng, *lead):
    return (jnp.asarray(rng.normal(size=(*lead, H, HD)), DT),
            jnp.asarray(rng.normal(size=(*lead, H, HD)), DT))


# ---------------------------------------------------------------------------
# paged policies

# slot 1 is released (all-zero table row: its write lands in the trash block)
TABLES = np.array([[1, 4, 7], [0, 0, 0], [9, 2, 5]], np.int32)


POSITIONS = np.array([13, 5, 23], np.int32)     # a decode step's frontiers


def _paged_decode(rng, raw):
    positions = POSITIONS
    k_new, v_new = _rows(rng, S, 1)
    write = kvc.paged_decode_write(jnp.asarray(TABLES), jnp.asarray(positions),
                                   raw=raw)
    targets = [(int(TABLES[s, positions[s] // BT]), int(positions[s] % BT))
               for s in range(S)]
    return write, k_new, v_new, targets, k_new[:, 0], v_new[:, 0], TABLES


def _paged_prefill(rng, offset=3):
    # an 8-token bucket holding 5 real tokens from ``offset``: rows 0-4 go
    # through the table (a run of consecutive positions, written a block at
    # a time); the 3 padding rows are written nowhere. From 19 the bucket
    # runs past the table's last block: the block after it is the trash
    table_row, length, T = TABLES[2], 5, 8
    k_new, v_new = _rows(rng, 1, T)
    write = kvc.paged_prefill_write(jnp.asarray(table_row), jnp.int32(offset),
                                    jnp.int32(length))
    targets = [(int(table_row[(offset + t) // BT]), (offset + t) % BT)
               for t in range(length)]
    return (write, k_new, v_new, targets, k_new[0, :length],
            v_new[0, :length], table_row[None])


def _paged_verify(rng):
    # slot 2's window crosses ctx_limit: its last row goes to the trash
    positions, T, ctx_limit = np.array([6, 2, 20], np.int32), 3, 22
    k_new, v_new = _rows(rng, S, T)
    write = kvc.paged_verify_write(jnp.asarray(TABLES), jnp.asarray(positions),
                                   ctx_limit)
    targets = []
    for s in range(S):
        for t in range(T):
            p = int(positions[s]) + t
            blk = int(TABLES[s, min(p // BT, MB - 1)]) if p < ctx_limit else 0
            targets.append((blk, p % BT))
    flat = lambda a: a.reshape(S * T, H, HD)  # noqa: E731
    return write, k_new, v_new, targets, flat(k_new), flat(v_new), TABLES


PAGED = {
    "decode_raw": lambda rng: _paged_decode(rng, raw=True),
    "decode_gathered": lambda rng: _paged_decode(rng, raw=False),
    "prefill": _paged_prefill,
    "prefill_table_end": lambda rng: _paged_prefill(rng, offset=19),
    "verify": _paged_verify,
}


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8", "int4"])
@pytest.mark.parametrize("policy", sorted(PAGED))
def test_paged_policy_writes_only_its_rows(policy, kv_dtype):
    rng = np.random.default_rng(3)
    stack = _noise_stack(rng, kv_dtype, (N,))
    write, k_new, v_new, targets, k_rows, v_rows, tables = PAGED[policy](rng)
    assert len(set(targets)) == len(targets)     # no two rows collide
    new, keys, values = write(stack, jnp.int32(LAYER), k_new, v_new)
    if policy == "decode_raw" and len(stack) == 2:
        new = _written_by_the_kernel(stack, new, keys, values, k_rows, v_rows)
        # the released slot's row goes nowhere: the trash block is as it was
        targets, k_rows, v_rows = (
            [x for x, (blk, _) in zip(xs, targets) if blk != 0]
            for xs in (targets, k_rows, v_rows))
        k_rows, v_rows = jnp.stack(k_rows), jnp.stack(v_rows)
    _assert_stack(new, _expected(stack, kv_dtype, targets, k_rows, v_rows))
    if policy == "decode_raw":
        _assert_views(new, keys, values)
        return
    if policy.startswith("prefill"):
        # the chunk's attend gathers its own span (kvcache.span_attend)
        # from the views: here the whole table row
        _assert_views(new, keys, values)
        keys, values = kvc._gather_context(new, keys.layer,
                                           jnp.asarray(tables), k_new)
    # the gathered logical context [S, H, MB*bt, hd] of the named layer
    for got, layer in zip((keys, values), _dequant_layer(new, kv_dtype)):
        want = np.asarray(layer)[tables]             # [S, MB, H, bt, hd]
        want = want.transpose(0, 2, 1, 3, 4).reshape(
            tables.shape[0], H, MB * BT, HD)
        np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# contiguous policies


def _contig_decode(rng, raw):
    positions = np.array([0, 11, 23], np.int32)
    k_new, v_new = _rows(rng, S, 1)
    write = kvc.decode_write(jnp.asarray(positions), raw=raw)
    targets = [(s, int(positions[s])) for s in range(S)]
    return write, k_new, v_new, targets, k_new[:, 0], v_new[:, 0], None


def _contig_verify(rng):
    positions, T = np.array([0, 9, 20], np.int32), 4
    k_new, v_new = _rows(rng, S, T)
    write = kvc.verify_write(jnp.asarray(positions))
    targets = [(s, int(positions[s]) + t) for s in range(S) for t in range(T)]
    flat = lambda a: a.reshape(S * T, H, HD)  # noqa: E731
    return write, k_new, v_new, targets, flat(k_new), flat(v_new), None


def _contig_chunk(rng, make):
    slot, offset, T = 2, 5, 8
    k_new, v_new = _rows(rng, 1, T)
    write = make(jnp.int32(slot), jnp.int32(offset))
    targets = [(slot, offset + t) for t in range(T)]
    return write, k_new, v_new, targets, k_new[0], v_new[0], slot


CONTIGUOUS = {
    "decode_raw": lambda rng: _contig_decode(rng, raw=True),
    "decode": lambda rng: _contig_decode(rng, raw=False),
    "verify": _contig_verify,
    "prefill": lambda rng: _contig_chunk(rng, kvc.prefill_write),
    "resume": lambda rng: _contig_chunk(rng, kvc.resume_write),
}


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("policy", sorted(CONTIGUOUS))
def test_contiguous_policy_writes_only_its_rows(policy, kv_dtype):
    rng = np.random.default_rng(4)
    stack = _noise_stack(rng, kv_dtype, (S,))
    write, k_new, v_new, targets, k_rows, v_rows, slot = CONTIGUOUS[policy](rng)
    new, keys, values = write(stack, jnp.int32(LAYER), k_new, v_new)
    _assert_stack(new, _expected(stack, kv_dtype, targets, k_rows, v_rows))
    if policy == "decode_raw":
        _assert_views(new, keys, values)
    elif policy == "prefill":
        # a fresh prefill attends over its own chunk, head-major, unquantized
        np.testing.assert_array_equal(
            np.asarray(keys), np.asarray(k_new.transpose(0, 2, 1, 3)))
        np.testing.assert_array_equal(
            np.asarray(values), np.asarray(v_new.transpose(0, 2, 1, 3)))
    else:
        # the named layer's rows: every slot's, or (resume) the one slot's
        for got, layer in zip((keys, values), _dequant_layer(new, kv_dtype)):
            want = np.asarray(layer)
            want = want[slot][None] if policy == "resume" else want
            np.testing.assert_array_equal(np.asarray(got), want)


# ---------------------------------------------------------------------------
# under tensor parallelism a chip writes its own heads


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("layout, policy", [
    *(("paged", p) for p in sorted(PAGED)),
    *(("contiguous", p) for p in sorted(CONTIGUOUS))])
def test_policy_is_shard_local_over_the_heads(layout, policy, kv_dtype):
    """The cache sharded over its kv heads on a 'model' mesh, the new rows
    over theirs (parallel.sharding.paged_kv_spec / kv_spec): the partitioner
    gives every device the write of its own heads and what the attend reads
    of them, with no collective. It can only when it SEES that the scatter's
    head index is the head's own number (``kvcache._scatter_per_head``'s
    iota); an index it cannot read makes it all-gather the rows, or the
    blocks a chunk touches, once a layer for K and for V."""
    import re

    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    rng = np.random.default_rng(7)
    mesh = Mesh(np.array(jax.devices()[:H]), ("model",))
    stack = _noise_stack(rng, kv_dtype, (N,) if layout == "paged" else (S,))
    write, k_new, v_new, *_, tables = (
        PAGED if layout == "paged" else CONTIGUOUS)[policy](rng)
    heads = lambda a, axis: NamedSharding(  # noqa: E731
        mesh, P(*[None] * axis, "model", *[None] * (a.ndim - axis - 1)))

    def write_and_read(stack, k_new, v_new):
        new, keys, values = write(stack, jnp.int32(LAYER), k_new, v_new)
        if layout == "paged" and policy.startswith("prefill"):
            # a chunk's attend gathers what it reads (kvcache.span_attend)
            keys, values = kvc._gather_context(
                new, keys.layer, jnp.asarray(tables), k_new)
        return new, keys, values

    compiled = jax.jit(
        write_and_read,
        in_shardings=(tuple(heads(a, 2) for a in stack),
                      heads(k_new, 2), heads(v_new, 2)),
    ).lower(stack, k_new, v_new).compile()
    collectives = re.findall(
        r" (?:all-gather|all-reduce|all-to-all|collective-permute"
        r"|reduce-scatter)[\w\-]*\(", compiled.as_text())
    assert not collectives, collectives


# ---------------------------------------------------------------------------
# the kernels read the layer they are given


def _kernel_stack(rng, kv_dtype, lead):
    tokens = BT if lead == (N,) else C
    full = jnp.asarray(rng.normal(size=(L, *lead, H, tokens, HD)), DT)
    if kv_dtype == "float32":
        return full, None
    quant = quantize_lastdim4 if kv_dtype == "int4" else quantize_lastdim
    return quant(full)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("kv_dtype", ["float32", "int8", "int4"])
def test_paged_kernel_reads_the_layer_it_is_given(kv_dtype, layer):
    rng = np.random.default_rng(5)
    q = jnp.asarray(rng.normal(size=(S, 2 * H, HD)), DT)
    (k, ks), (v, vs) = (_kernel_stack(rng, kv_dtype, (N,)) for _ in range(2))
    tables = jnp.asarray([[1, 4, 7], [3, 6, 8], [9, 2, 5]], jnp.int32)
    positions = jnp.asarray([13, 0, 23], jnp.int32)
    scales = () if ks is None else (ks, vs)
    out = ops.paged_decode_attention(
        q, k, v, jnp.int32(layer), tables, positions, *scales, interpret=True)
    ref = ops.paged_decode_attention_ref(
        q, k[layer], v[layer], tables, positions,
        *(s[layer] for s in scales))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_contiguous_kernel_reads_the_layer_it_is_given(kv_dtype, layer):
    rng = np.random.default_rng(6)
    q = jnp.asarray(rng.normal(size=(S, 2 * H, HD)), DT)
    (k, ks), (v, vs) = (_kernel_stack(rng, kv_dtype, (S,)) for _ in range(2))
    positions = jnp.asarray([13, 0, 23], jnp.int32)
    scales = () if ks is None else (ks, vs)
    out = ops.decode_attention(
        q, k, v, jnp.int32(layer), positions, *scales, block_k=8,
        interpret=True)
    # a layer [S, H, C, hd] is a pool of S one-block slots to the reference
    ref = ops.paged_decode_attention_ref(
        q, k[layer], v[layer], jnp.arange(S, dtype=jnp.int32)[:, None],
        positions, *(s[layer] for s in scales))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# PR 38: only the Pallas kernel over an unscaled pool writes for its policy


def _decode_write_before_the_kernel_wrote(tables, positions, raw=False):
    """``engine.kvcache.paged_decode_write`` as it stood before PR 38: it
    scatters the step's rows whatever the pool and whoever attends."""

    def write(kv_stack, layer, k_new, v_new):
        bt = kv_stack[0].shape[3]
        s = jnp.arange(tables.shape[0])
        blk = tables[s, positions // bt]
        new = kvc._write_rows(kv_stack, layer, blk, positions % bt,
                              k_new[:, 0], v_new[:, 0])
        if raw:
            return (new, *kvc._views(new, layer))
        return (new, *kvc._gather_context(new, layer, tables, k_new))

    return write


def _lowered(r):
    """The runner's paged programs, lowered: one decode step, two in one
    dispatch, a prefill chunk, a speculative verify window."""
    import jax

    base = (r.params, r.kv, r.state)
    chunk = (jnp.zeros((1, 32), jnp.int32), jnp.int32(5), jnp.int32(0),
             r.block_tables[0], jnp.int32(0),
             jnp.zeros(r.cfg.vocab_size, jnp.int32))
    return {
        "decode": jax.jit(r._decode_paged_fn).lower(
            *base, r.block_tables).as_text(),
        "decode_n2": jax.jit(
            r._decode_paged_n_fn, static_argnames=("n",)).lower(
                *base, r.block_tables, n=2).as_text(),
        "prefill": jax.jit(
            r._prefill_paged_fn, static_argnames=("bucket", "sample")).lower(
                *base, *chunk, bucket=32, sample=True).as_text(),
        "verify": jax.jit(r._verify_paged_fn).lower(
            *base, r.block_tables,
            jnp.zeros((r.num_slots, 4), jnp.int32)).as_text(),
    }


@pytest.mark.parametrize("kv_dtype, attn_impl, kernel_writes", [
    ("bfloat16", "pallas_interpret", True),
    ("float32", "pallas_interpret", True),
    ("int8", "pallas_interpret", False),
    ("int4", "pallas_interpret", False),
    ("bfloat16", "xla", False),
    ("int8", "xla", False)])
def test_only_the_kernel_over_an_unscaled_pool_writes(monkeypatch, kv_dtype,
                                                      attn_impl,
                                                      kernel_writes):
    """The switch is the stack's arity and ``raw``: with the policy of
    before PR 38 in its place (copied above), every program of a scaled
    pool or of the XLA attend lowers to the SAME text, to the letter, and
    so do every pool's prefill chunk and verify window; only the decode
    programs of the Pallas kernel over an unscaled pool differ: K's scatter
    and V's are gone from them (tests/test_tpu_compile.py reads the
    compiled cells' programs for the aliased call)."""
    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models.registry import resolve_model

    model = resolve_model("debug:tiny", dtype="bfloat16")

    def runner():
        return ModelRunner(model.cfg, model.params, num_slots=4, max_ctx=128,
                           paged=True, kv_block_tokens=16, kv_dtype=kv_dtype,
                           prefill_buckets=[32], attn_impl=attn_impl)

    now = _lowered(runner())
    monkeypatch.setattr(kvc, "paged_decode_write",
                        _decode_write_before_the_kernel_wrote)
    before = _lowered(runner())
    assert now["prefill"] == before["prefill"]
    assert now["verify"] == before["verify"]
    for program in ("decode", "decode_n2"):
        if not kernel_writes:
            assert now[program] == before[program]
            continue
        # K's scatter and V's are gone (the sampler keeps one of its own)
        op = '"stablehlo.scatter"('
        assert now[program].count(op) == before[program].count(op) - 2


# ---------------------------------------------------------------------------
# PR 47: ONE family of serving programs over a layout object. From the same
# prompts and seeds both layouts emit the same tokens through each program.

PA = list(b"the quick brown fox jumps over the dog")    # paged: three chunks
PB = list(b"hi")
PA2 = PA[:24] + list(b" leaps past a cat")       # a chunk behind PA's prefix
GREEDY = {"temperature": 0.0}
SEEDED = {"temperature": 0.8, "top_k": 40, "seed": 11, "repeat_penalty": 1.1}
GAMMA, STREAM = 3, 9


def _family_runner(layout):
    from localai_tpu.engine.runner import ModelRunner
    from localai_tpu.models.registry import resolve_model

    tiny = resolve_model("debug:tiny", dtype="float32")
    r = ModelRunner(tiny.cfg, tiny.params, num_slots=3, max_ctx=96,
                    prefill_buckets=[16, 32], kv_dtype="float32",
                    paged=layout == "paged", kv_block_tokens=16,
                    prefill_chunk=16)
    assert type(r.layout) is {"paged": kvc.PagedLayout,
                              "contiguous": kvc.ContiguousLayout}[layout]
    return r


def _admit_pair(r):
    """PA (greedy) and PB (seeded sampling) in two slots; their slots and
    first tokens. The speculation rows are the pool's to reserve."""
    a, b = r.acquire_slot(), r.acquire_slot()
    return a, b, [r.admit(a, PA, spec_tokens=GAMMA + 1, **GREEDY)], [
        r.admit(b, PB, spec_tokens=GAMMA + 1, **SEEDED)]


@pytest.fixture(scope="module")
def family_streams():
    """What each prompt's stream is: one decode step a dispatch over the
    block pool (the path every cell serves by)."""
    r = _family_runner("paged")
    a, b, sa, sb = _admit_pair(r)
    c = r.acquire_slot()
    sc = [r.admit(c, PA2, **SEEDED)]
    for _ in range(STREAM):
        toks = r.step()
        for s, out in ((a, sa), (b, sb), (c, sc)):
            out.append(int(toks[s]))
    return {"a": sa, "b": sb, "a2": sc}


def _decode(r, want):
    a, b, sa, sb = _admit_pair(r)
    for _ in range(8):
        toks = r.step()
        sa.append(int(toks[a]))
        sb.append(int(toks[b]))
    return {"a": sa, "b": sb}


def _decode_n(r, want):
    a, b, sa, sb = _admit_pair(r)
    for _ in range(2):
        toks = r.step_n(4)                              # [4, S]
        sa += [int(t) for t in toks[:, a]]
        sb += [int(t) for t in toks[:, b]]
    return {"a": sa, "b": sb}


def _frozen_n(r, want):
    """PB's slot frozen: one token a dispatch, where PA's rides for four."""
    a, b, sa, sb = _admit_pair(r)
    freeze = np.zeros(r.num_slots, bool)
    freeze[b] = True
    for _ in range(2):
        toks = r.step_frozen_n(freeze, 4)
        sa += [int(t) for t in toks[:, a]]
        sb.append(int(toks[0, b]))
    return {"a": sa, "b": sb}


def _verify(r, want):
    """Two windows: PA's drafts are its stream (all accepted, and the bonus
    token), PB's are wrong (the correction alone)."""
    from localai_tpu.engine.runner import SKIP

    a, b, sa, sb = _admit_pair(r)
    V = r.cfg.vocab_size
    for _ in range(2):
        props = np.zeros((r.num_slots, GAMMA), np.int32)
        props[a] = want["a"][len(sa):len(sa) + GAMMA]
        props[b] = [(t + 1) % V for t in want["b"][len(sb):len(sb) + GAMMA]]
        emitted = np.asarray(r.verify_async(props))      # [GAMMA + 1, S]
        sa += [int(t) for t in emitted[:, a] if t != SKIP]
        sb += [int(t) for t in emitted[:, b] if t != SKIP]
    assert len(sa) == 1 + 2 * (GAMMA + 1) and len(sb) == 3
    return {"a": sa, "b": sb}


def _chunk(r, want):
    """PA2 behind what PA left: the contiguous slot's own rows, the pool's
    shared block. Both go through the one chunk program."""
    s = r.acquire_slot()
    left = PA + [r.admit(s, PA, **GREEDY)]
    left.append(int(r.step()[s]))
    r.release(s)
    s = r.acquire_slot(s)
    out = [r.admit(s, PA2, resident=left, **SEEDED)]
    assert r.last_prefill_path == ("paged_shared" if r.paged else "resume")
    assert r.last_prefix_reused == (16 if r.paged else 24)
    out += [int(r.step()[s]) for _ in range(5)]
    return {"a2": out}


@pytest.mark.parametrize("program", [_decode, _decode_n, _frozen_n, _verify,
                                     _chunk], ids=lambda f: f.__name__[1:])
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_both_layouts_emit_the_same_tokens_through_the_one_family(
        family_streams, layout, program):
    """``decode`` holds the pair tests/test_paged.py held as
    ``test_paged_runner_matches_contiguous_greedy`` (its prompts, PA's
    stream greedy)."""
    r = _family_runner(layout)
    got = program(r, family_streams)
    for name, stream in got.items():
        assert stream == family_streams[name][:len(stream)], (name, layout)
    if r.paged:
        assert not r.allocator.check_invariants()
