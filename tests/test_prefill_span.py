"""A prefill chunk attends the prefix it has, not the width of the pool.

``engine.kvcache.span_attend`` cuts the attend of ``_prefill_paged_fn`` to
the smallest rung of ``span_ladder`` that covers ``offset + bucket``, picked
on the device from the ``offset`` the program receives. Held here: a chunk
through the real program equals the same chunk under the attend as it stood
(the FULL table row gathered, ``_grouped_attn`` under ``resume_mask`` over
``ctx_pad``) on the hidden states of its real rows and on every block of the
pool; the host's ``attend_span`` is the rung the traced program takes; the
flight ring's ``chunk_ctx`` states it. tests/test_tpu_compile.py holds the
compiled programs (one ``conditional``, no second pool, no Pallas call).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from localai_tpu.engine import kvcache as kvc
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models.llama import _grouped_attn
from localai_tpu.models.registry import DEBUG_PRESETS, synthetic_params

CTX, BT = 2048, 64          # the ladder of Ouro's cell: 512 / 1024 / 2048
BUCKETS = [128, 512]


def _mesh2():
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    return build_mesh(MeshPlan(model=2), devices=jax.devices()[:2])


def _runner(kind: str) -> ModelRunner:
    """A paged runner over 2048 positions in blocks of 64, its pool full of
    noise (a chunk behind ``offset`` attends whatever its table row holds
    there: noise is a prefix as good as any, and a block read that should
    not be, or not read that should, shows)."""
    preset, kv_dtype, changed, mesh = {
        "bf16": ("tiny", "bfloat16", {}, None),
        "f32": ("tiny", "float32", {}, None),
        "int8_pool": ("tiny", "int8", {}, None),
        "sliding_window": ("tiny", "bfloat16", {"sliding_window": 300}, None),
        "looped": ("tiny-loop", "bfloat16", {"num_passes": 2}, None),
        "model_mesh": ("tiny", "float32", {}, _mesh2()),
    }[kind]
    dtype = "float32" if kv_dtype == "float32" else "bfloat16"
    cfg = dataclasses.replace(DEBUG_PRESETS[preset], dtype=dtype,
                              max_position_embeddings=CTX, **changed)
    params = synthetic_params(cfg, None, seed=0)
    if mesh is not None:
        from localai_tpu.parallel import sharding as shd

        params = shd.shard_params(params, cfg, mesh)
    r = ModelRunner(cfg, params, num_slots=2, max_ctx=CTX, paged=True,
                    kv_block_tokens=BT, kv_dtype=kv_dtype, attn_impl="xla",
                    prefill_buckets=BUCKETS, mesh=mesh)
    assert (r.ctx_pad, r.max_blocks, r.pp_enabled) == (CTX, CTX // BT, False)
    rng = np.random.default_rng(5)

    def noise(a):
        if a.dtype == jnp.int8:
            got = rng.integers(-127, 128, a.shape).astype(np.int8)
        elif a.ndim == 4:               # the scales of a scaled pool
            got = rng.uniform(0.002, 0.02, a.shape).astype(np.float32)
        else:
            got = rng.standard_normal(a.shape).astype(np.float32)
        return jax.device_put(jnp.asarray(got, a.dtype), a.sharding)

    r.kv = jax.tree.map(noise, r.kv)
    return r


@pytest.fixture(scope="module")
def runners():
    made = {}

    def get(kind):
        if kind not in made:
            made[kind] = _runner(kind)
        return made[kind]

    return get


def _full_span_attend(cfg, table_row, offset, ctx_pad):
    """The attend of a paged chunk as it stood before the span was cut: the
    whole table row gathered, every one of ``ctx_pad`` positions scored."""
    del offset, ctx_pad

    def attn(q, keys, values, mask):
        k, v = kvc._gather_context(kvc._stacked(keys, values), keys.layer,
                                   table_row[None], q)
        return _grouped_attn(cfg, q, k, v, mask)

    return attn


def _chunk(r, offset, length, bucket, monkeypatch):
    """(hidden [bucket, D] after the final norm, the pool, the first token)
    of one sampled chunk of ``length`` real tokens at ``offset`` through
    ``_prefill_paged_fn``, the pool as the fixture left it."""
    seen = {}
    inner = r._forward

    def spy(*a, **k):
        hidden, stack = inner(*a, **k)
        seen["hidden"] = hidden
        return hidden, stack

    monkeypatch.setattr(r, "_forward", spy)

    def program(params, kv, state, *chunk):
        out = r._prefill_paged_fn(params, kv, state, *chunk, bucket=bucket,
                                  sample=True)
        return out, seen["hidden"]

    rng = np.random.default_rng(offset + length)
    tokens = np.zeros((1, bucket), np.int32)
    tokens[0, :length] = rng.integers(1, r.cfg.vocab_size, length)
    # a table row of distinct blocks, not in order
    table_row = 1 + rng.permutation(r.max_blocks).astype(np.int32)
    assert table_row.max() < r.kv.k.shape[1]
    run = jax.jit(program)      # one chunk, one program: traced per case
    (kv, _, tok), hidden = run(
        r.params, r.kv, r.state, tokens, np.int32(length), np.int32(offset),
        table_row, np.int32(1), np.zeros(r.cfg.vocab_size, np.int32))
    monkeypatch.undo()
    return (np.asarray(hidden[0], np.float32),
            [np.asarray(a, np.float32) for a in jax.tree.leaves(kv)],
            int(tok))


# (runner, offset, real tokens, bucket, the rung it must take)
CASES = [
    ("bf16", 0, 512, 512, 512),             # nothing cached: the first rung
    ("bf16", 0, 70, 128, 512),              # a 128 bucket takes 512 too
    ("bf16", 512, 512, 512, 1024),          # offset + bucket ON a rung
    ("bf16", 576, 300, 512, 2048),          # ... and one block past it
    ("bf16", 64, 500, 512, 1024),
    ("bf16", 1536, 512, 512, 2048),         # the last rung: the table's end
    ("bf16", 1837, 100, 128, 2048),         # a 128 bucket behind a long prefix
    ("bf16", 451, 128, 128, 1024),          # an offset inside a block
    ("f32", 896, 97, 128, 1024),
    ("f32", 512, 301, 512, 1024),
    ("sliding_window", 700, 512, 512, 2048),
    ("sliding_window", 200, 128, 128, 512),
    ("int8_pool", 512, 400, 512, 1024),
    ("int8_pool", 960, 128, 128, 2048),
    ("looped", 512, 512, 512, 1024),        # both passes' cache layers
    ("looped", 300, 90, 128, 512),
    ("model_mesh", 512, 333, 512, 1024),    # heads sharded over two devices
    ("model_mesh", 1100, 128, 128, 2048),
]


@pytest.mark.parametrize("kind, offset, length, bucket, span", CASES)
def test_a_chunk_under_its_span_is_the_chunk_under_the_full_context(
        runners, monkeypatch, kind, offset, length, bucket, span):
    r = runners(kind)
    assert r.chunk_span(offset, bucket) == span
    hidden, pool, tok = _chunk(r, offset, length, bucket, monkeypatch)
    monkeypatch.setattr(kvc, "span_attend", _full_span_attend)
    hidden_full, pool_full, tok_full = _chunk(r, offset, length, bucket,
                                              monkeypatch)
    # float32 end to end: the order of a sum; bfloat16: one rounding of it
    tol = 2e-5 if r.cfg.dtype == "float32" else 2e-2
    assert np.isfinite(hidden[:length]).all()
    np.testing.assert_allclose(hidden[:length], hidden_full[:length],
                               rtol=tol, atol=tol)
    # every cache layer (a looped model's second pass too), every block:
    # the chunk's rows as the full attend left them, nothing else touched
    assert len(pool) == (4 if kind == "int8_pool" else 2)
    assert pool[0].shape[0] == r.cfg.cache_layers
    for got, want in zip(pool, pool_full):
        if kind == "int8_pool" and got.ndim == 5:
            assert (np.abs(got - want) <= 1).all()      # one quantum
            assert (got != want).mean() < 1e-3
        else:
            np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    assert tok == tok_full


def test_the_span_cut_changes_the_program_and_the_full_one_is_the_parents(
        runners):
    """The comparison above is not of a thing with itself: the chunk's
    program holds one branch a rung, and gathers ``span / block_tokens``
    blocks in each, where the full-span attend holds none."""
    r = runners("bf16")
    chunk = (jnp.zeros((1, 128), jnp.int32), jnp.int32(5), jnp.int32(0),
             jnp.zeros(r.max_blocks, jnp.int32), jnp.int32(0),
             jnp.zeros(r.cfg.vocab_size, jnp.int32))
    text = jax.jit(r._prefill_paged_fn,
                   static_argnames=("bucket", "sample")).lower(
        r.params, r.kv, r.state, *chunk, bucket=128, sample=False).as_text()
    assert text.count('"stablehlo.case"(') == 1
    heads = r.cfg.num_kv_heads
    for span in kvc.span_ladder(128, CTX, BT):
        assert f"tensor<1x{heads}x{span}x{r.cfg.hd}xbf16>" in text


LADDERS = [
    # bucket, ctx_pad, block_tokens, the rungs
    (512, 4096, 64, (512, 1024, 2048, 4096)),       # the 7B's cell
    (128, 4096, 64, (512, 1024, 2048, 4096)),
    (512, 8192, 64, (512, 1024, 2048, 4096, 8192)),         # the 24B's
    (128, 2048, 64, (512, 1024, 2048)),                     # Ouro's
    (2048, 8192, 64, (2048, 4096, 8192)),       # a bucket over 512 starts it
    (512, 4160, 64, (512, 1024, 2048, 4096, 4160)),     # ends AT ctx_pad
    (128, 2112, 96, (576, 1152, 2112)),     # blocks that do not divide 512
    (32, 96, 16, (96,)),                # a context under 512: one rung
    (512, 512, 64, (512,)),
]


@pytest.mark.parametrize("bucket, ctx_pad, bt, rungs", LADDERS)
def test_the_host_and_the_device_take_the_same_rung(bucket, ctx_pad, bt,
                                                    rungs):
    """``attend_span`` on host integers is the rung the traced program
    takes for the same arguments (ONE expression, ``attend_rung``), a
    multiple of ``block_tokens``, never under ``offset + bucket`` and never
    over ``ctx_pad``; the ladder is short and ends at ``ctx_pad``."""
    assert ctx_pad % bt == 0
    ladder = kvc.span_ladder(bucket, ctx_pad, bt)
    assert ladder == rungs and ladder[-1] == ctx_pad and len(ladder) <= 6
    assert all(c % bt == 0 for c in ladder)
    assert all(b == 2 * a for a, b in zip(ladder[:-2], ladder[1:-1]))
    traced = jax.jit(lambda offset: kvc.attend_rung(offset + bucket, ladder))
    edges = {0, 1, bt - 1, bt, ctx_pad - bucket}
    for c in ladder:
        edges |= {c - bucket - 1, c - bucket, c - bucket + 1, c - bucket + bt}
    for offset in sorted(o for o in edges if 0 <= o <= ctx_pad - bucket):
        span = kvc.attend_span(offset, bucket, ctx_pad, bt)
        assert isinstance(span, int)
        assert span == ladder[int(traced(jnp.int32(offset)))], offset
        assert offset + bucket <= span <= ctx_pad
        # the smallest such rung
        assert all(c < offset + bucket for c in ladder if c < span)
    # a chunk whose padded rows run past the table attends the table
    assert kvc.attend_span(ctx_pad - 1, bucket, ctx_pad, bt) == ctx_pad
    assert int(traced(jnp.int32(ctx_pad - 1))) == len(ladder) - 1


def test_the_ring_row_states_the_span_of_each_chunk(runners):
    """A two-chunk admission: the first chunk (512 rows at offset 0) takes
    512 positions, the second (its remainder, in the 128 bucket behind 512
    cached tokens) 1024: ``chunk_ctx`` as ``launch_chunk`` hands it to the
    flight ring."""
    r = runners("bf16")
    assert r.prefill_chunk == 512
    slot = r.acquire_slot()
    prompt = list(np.random.default_rng(1).integers(1, 500, 600))
    adm = r.begin_admit(slot, prompt, temperature=0.0)
    held = []
    while True:
        last = adm.launch_chunk()
        held.append(dict(adm.last_chunk))
        if last:
            break
    adm.first_token()
    r.release(slot)
    assert held == [
        {"chunk_tokens": 512, "chunk_bucket": 512, "chunk_offset": 0,
         "chunk_ctx": 512, "chunk_parts": 1},
        {"chunk_tokens": 88, "chunk_bucket": 128, "chunk_offset": 512,
         "chunk_ctx": 1024, "chunk_parts": 1}]
    assert all(c["chunk_ctx"] == kvc.attend_span(
        c["chunk_offset"], c["chunk_bucket"], r.ctx_pad, r.block_tokens)
        for c in held)
