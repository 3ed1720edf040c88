"""dots3-note (``model_type: dots3_note``) on the normal serving path: latent
attention with an INDEXER on the full layers (score every cached index key,
attend the ``index_topk`` best rows alone), WINDOW layers with latent rows of
another width, a headwise gate on both, ``noaux_tc`` experts; a latent pool
of three arrays under one block table. CPU, tiny widths, seeded random
weights: D 64; full layers 4 heads of [16 | 8] over rank 32 (a row 40
elements), window layers 2 heads of [24 | 8] over rank 48 (56), 4 index heads
of 16, ``index_topk`` 12 and ``sliding_window_size`` 9, BOTH smaller than the
contexts here (37 to 47) so that both bind; F(dense) F S S S F, 8 of 16
experts held.

The served path is the runner's own programs, driven by ``admit`` and
``step`` and tapped for the LOGITS they sample from; the reference is the
benchmark's plain float32 family (benchmark/reference/dots3_family.py) run as
the benchmark runs it (harness/refcheck.py): the FULL forward over prompt +
served tokens, published form, ``lax.top_k`` for the selection, no cache.
"""

import dataclasses
import functools
import re
import time

import families
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from families import (agree, lowered_texts, reference_logits, served_logits,
                      tap)

from localai_tpu.engine import kvcache as kvc
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import deepseek as ds
from localai_tpu.models import dots3
from localai_tpu.models import experts as xp
from localai_tpu.models import llama as mdl
from localai_tpu.models.registry import synthetic_params

F, S = dots3.FULL, dots3.WINDOW
TYPES = [F, F] + [S, S, S, F] * 11
HF = {"model_type": "dots3_note", "vocab_size": 384, "hidden_size": 64,
      "intermediate_size": 96, "num_hidden_layers": 6, "layer_types": TYPES,
      "num_attention_heads": 4, "num_key_value_heads": 4,
      "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
      "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 80000000,
      "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2,
      "swa_q_lora_rank": 24, "swa_kv_lora_rank": 48,
      "swa_qk_nope_head_dim": 24, "swa_qk_rope_head_dim": 8,
      "swa_v_head_dim": 16, "swa_rope_theta": 50000,
      "sliding_window_size": 9, "index_n_heads": 4, "index_head_dim": 16,
      "index_topk": 12, "attention_gate_type": "headwise",
      "swa_attention_gate_type": "headwise",
      "apply_mla_qkv_lora_rescale": True, "rms_norm_eps": 1e-5,
      "max_position_embeddings": 512, "rope_scaling": None,
      "first_k_dense_replace": 1, "moe_layer_freq": 1,
      "n_routed_experts": 8, "num_experts_per_tok": 3,
      "moe_intermediate_size": 32, "n_shared_experts": 1,
      "norm_topk_prob": True, "routed_scaling_factor": 1,
      "scoring_func": "sigmoid", "topk_method": "noaux_tc",
      "hidden_act": "silu", "attention_bias": False,
      "tie_word_embeddings": False,
      "expert_parallel": {"size": 2, "rank": 1}}
RNG = np.random.default_rng(51)
PROMPT = RNG.integers(1, 380, 37).tolist()      # three chunks: 16 + 16 + 5
STEPS = 10                                      # contexts 37 .. 47
# float32 serving: what is left between the two is summation order (the
# absorbed form sums a score over the latent's lanes where the reference
# sums it over a head's; the index scores sum 4 heads of 16 in both). A
# selection that differed by ONE row would move a logit by ~1e-2
F32_TOL = 2e-5
# bfloat16 serving, logits up to ~2: activations rounded to 8 bits some
# thirty times through 6 layers, rows and index keys kept in bfloat16, a
# rounded index score or router near-tie flips a chosen row or expert: held
# by the mean, the worst to a bound a dropped term breaks
BF16_MEAN_TOL, BF16_TOL = 0.06, 0.8
DEEP = {"num_hidden_layers": 10}                # two periods: the scan runs


@pytest.fixture(scope="module")
def family():
    """The benchmark's family module; its walk pads the probes to whole
    multiples of 64 positions here (1152 where the harness runs it: the
    tests/test_bench_walk.py cases), the sequences here being 37 to 47."""
    fam = families.reference_family("dots3_family", "tests/test_dots3.py")
    fam.PROBE_PAD = 64
    return fam


config = functools.partial(families.config, HF)


def seeded_params(cfg, seed: int = 0):
    """The program's seeded weights with every norm gain (and the index
    key's LayerNorm) redrawn at 1 + 0.3 N, the matrices three times as
    large and the selection bias ten times, so that every term weighs on
    the logits."""
    rng = np.random.default_rng(seed + 1)

    def redraw(name, a):
        if name.endswith("norm"):
            return families.gain(rng, a)
        scale = 10.0 if name.endswith("expert_bias") else 3.0
        return (scale * a.astype(jnp.float32)).astype(a.dtype)

    return families.redrawn(mdl.init_params(jax.random.key(seed), cfg),
                            redraw)


@pytest.fixture(scope="module")
def drawn():
    """``init_params`` of the small configuration, drawn once for the cases
    that only hand them to a runner (most of which refuses them unread)."""
    return mdl.init_params(jax.random.key(0), config())


def runner_for(cfg, params, impl="xla", **kw) -> ModelRunner:
    kw = {"num_slots": 4, "max_ctx": 64, "paged": True, "kv_block_tokens": 8,
          "prefill_chunk": 16, "prefill_buckets": [16, 32],
          "attn_impl": impl, "kv_dtype": cfg.dtype, **kw}
    return ModelRunner(cfg, params, **kw)


# ---------------------------------------------------------------------------
# (a) the served path against the plain reference


@pytest.mark.parametrize("dtype, impl, deep", [
    ("float32", "xla", True), ("bfloat16", "pallas_interpret", False)])
def test_served_logits_match_the_reference(family, monkeypatch, dtype, impl,
                                           deep):
    """A prompt over three chunks (a chunk boundary at 16 and 32, block
    boundaries every 8, the last chunk with padded rows), then decode steps
    through the pool, with ``index_topk`` 12 and the window 9 both binding
    from the second chunk on: the logits each program samples from against
    the full forward. Under ``pallas_interpret`` the experts are ops.moe's
    kernel; the latent attends are XLA either way."""
    hf = {**HF, **(DEEP if deep else {})}
    cfg = config(dtype, **(DEEP if deep else {}))
    params = seeded_params(cfg)
    r = runner_for(cfg, params, impl)
    assert r.latent and r.routed and r.kinds and not r.recurrent
    assert isinstance(r.layout, kvc.LatentLayout)
    assert r.paged_kv_write_impl == "scatter"
    assert (r.family_kernels is not None) == (impl == "pallas_interpret")
    served, tokens = served_logits(r, tap(r), 1, PROMPT, STEPS)
    assert r.admit_programs == 1 + 3
    # THREE arrays under one table: full rows, window rows, index keys
    nf, nw = (4, 6) if deep else (3, 3)
    N, bt = r.allocator.num_blocks, r.block_tokens
    assert [a.shape for a in r.kv.stacked()] == [
        (nf, N, bt, 128), (nw, N, bt, 128), (nf, N, bt, 128)]
    assert cfg.latent_states == (("c", nf, 40), ("w", nw, 56), ("i", nf, 16))
    assert (cfg.lone_layers, cfg.period, cfg.periods) == (
        1, 4, 2 if deep else 1)
    ref = reference_logits(family, params, hf, PROMPT, tokens, monkeypatch)
    if dtype == "float32":
        agree(served, ref, F32_TOL)
        assert (served.argmax(-1) == ref.argmax(-1)).all()
    else:
        assert np.abs(ref).max() > 0.2
        assert np.abs(served - ref).mean() < BF16_MEAN_TOL
        assert np.abs(served - ref).max() < BF16_TOL


def test_the_prefix_pool_the_prompt_cache_and_the_host_tier_carry_all_three(
        family, monkeypatch):
    """``export_prefix`` hands out a slot's first rows of EACH array in its
    real lanes, ``load_prefix`` lays them into another runner's pool and the
    request that resumes behind them serves the whole prefill's logits; a
    block spills to the host and comes back as it was, all three arrays;
    and a request whose first 32 tokens (4 blocks of all three arrays) come
    from the PREFIX POOL prefills its 8-token tail alone, at offset 32 (the
    tail's queries score the shared index keys and attend the chosen shared
    rows), and serves the reference's logits."""
    cfg = config()
    params = seeded_params(cfg)
    a = runner_for(cfg, params)
    seen = tap(a)
    whole, tokens = served_logits(a, seen, 0, PROMPT, steps=3)
    arrays = a.export_prefix(0, 32)
    assert set(arrays) == {"kv_dtype", "kv_rope", "c", "w", "i"}
    assert [arrays[k].shape for k in "cwi"] == [(3, 32, 40), (3, 32, 56),
                                                (3, 32, 16)]
    b = runner_for(cfg, params)
    assert b.load_prefix(1, arrays, 32)
    resumed, again = served_logits(b, tap(b), 1, PROMPT, steps=3,
                                   resident=PROMPT[:32])
    assert b.last_prefill_path == "paged_resume"
    assert b.last_prefix_reused == 32 and again == tokens
    np.testing.assert_allclose(resumed, whole, atol=F32_TOL)
    # not this pool's rows: another width, an array missing
    assert not b.load_prefix(2, {**arrays, "w": arrays["w"][..., :55]}, 32)
    assert not b.load_prefix(2, {k: v for k, v in arrays.items()
                                 if k != "i"}, 32)
    bid = a.allocator.tables[0][1]
    packed = a.pack_block(bid)
    assert {k: v.shape for k, v in packed.items()} == {
        k: (3, 8, 128) for k in "cwi"}
    before = [np.asarray(x[:, bid]) for x in a.kv.stacked()]
    a.load_block(bid, {k: np.zeros_like(v) for k, v in packed.items()})
    assert not any(np.asarray(x[:, bid]).any() for x in a.kv.stacked())
    a.load_block(bid, packed)
    for x, was in zip(a.kv.stacked(), before):
        np.testing.assert_array_equal(np.asarray(x[:, bid]), was)
    a.release(0)
    other = PROMPT[:32] + RNG.integers(1, 380, 8).tolist()
    shared, tokens = served_logits(a, seen, 2, other, steps=6)
    assert a.last_prefill_path == "paged_shared"
    assert a.last_prefix_reused == 32
    agree(shared, reference_logits(family, params, HF, other, tokens,
                                   monkeypatch), F32_TOL)


# ---------------------------------------------------------------------------
# (b) one layer of each kind, and every term with a program that fails
# without it


def drawn_leaves(cfg, kind, rng) -> dict:
    """One ``kind`` layer's attention leaves: matrices 0.3 N, vectors 1 +
    N."""
    return {k: jnp.asarray(rng.standard_normal(s) * (0.3 if len(s) > 1 else 1)
                           + (len(s) == 1), jnp.float32)
            for k, s in dots3._kind_shapes(cfg, kind, ()).items()}


def one_layer(family, kind, hf=HF, mutate=None, T=20, seed=1):
    """(the program's, the reference's) attention of ONE ``kind`` layer on a
    random normed sequence h [T, 64], random weights (matrices 0.3 N, gains 1
    + N): the program's ``models.deepseek._attention`` under the kind's view,
    through the layout's chunk write and attend over a pool; ``mutate(cfg)``
    -> cfg departs first."""
    rng = np.random.default_rng(seed)
    cfg = config(**{k: v for k, v in hf.items() if HF.get(k) != v})
    w = drawn_leaves(cfg, kind, rng)
    h = jnp.asarray(rng.standard_normal((1, T, 64)), jnp.float32)
    cos, sin = family.rope_tables(hf, T)
    with jax.default_matmul_precision("highest"):
        ref = family.latent_attention(h[0], w, cos, sin, hf, kind)
    if mutate is not None:
        cfg = mutate(cfg) or cfg
    layout = kvc.LatentLayout(cfg, "float32", 1, 64, "xla", False, 8, 8, 12)
    kv, _ = layout.init()
    table = jnp.array([3, 1, 4, 2, 5, 7, 8, 0], jnp.int32)
    positions = jnp.arange(T)[None]
    rope = mdl.rope_table(cfg, 64)[kind]
    cos = rope[0][positions][:, :, None, :]
    sin = rope[1][positions][:, :, None, :]
    write, attn, mask = layout.chunk(table, jnp.int32(0), positions,
                                     jnp.int32(0), jnp.int32(T))

    def attend(q, row, index=None, **how):
        stack, view = write(kv.stacked(), jnp.int32(1), row,
                            dots3.STATE[kind])
        if index is not None:
            stack, keys = write(stack, jnp.int32(1), index["k"],
                                dots3.INDEX_STATE)
            how["index"] = {**index, "keys": keys.cache}
        return attn[kind].run(q, view, mask, **how), stack

    out, _ = ds._attention(
        cfg.kind(kind), h, w.__getitem__, cos, sin, attend, attn[kind].path,
        index=(dots3.indexer(cfg, w.__getitem__, cos, sin) if kind == F
               else None), gate=dots3.head_gate(w.__getitem__))
    return np.asarray(out[0]), np.asarray(ref)


@pytest.mark.parametrize("kind", [F, S])
def test_one_layer_of_each_kind_is_the_references(family, kind):
    """20 tokens, ``index_topk`` 12 and the window 9 binding: outputs of
    ~15, agreement to 3e-5 (2e-6 of the size)."""
    out, ref = one_layer(family, kind)
    assert np.abs(ref).max() > 5
    assert np.abs(out - ref).max() < 3e-5


def relu_dropped(monkeypatch, cfg):
    monkeypatch.setattr(jax.nn, "relu", lambda x: x)


def index_weights_all_one(monkeypatch, cfg):
    """The heads' learned weights dropped: a plain sum of ReLUs."""
    real = kvc.index_scores
    monkeypatch.setattr(kvc, "index_scores", lambda q, w, keys: real(
        q, jnp.ones_like(w), keys))


def layer_norm_bias_dropped(monkeypatch, cfg):
    real = dots3.layer_norm
    monkeypatch.setattr(dots3, "layer_norm", lambda x, w, b, eps: real(
        x, w, jnp.zeros_like(b), eps))


def index_rope_interleaved(monkeypatch, cfg):
    real = dots3.index_rope
    monkeypatch.setattr(dots3, "index_rope", lambda x, cos, sin, rope: real(
        jnp.concatenate([ds.rope_pairs(x[..., :rope]), x[..., rope:]], -1),
        cos, sin, rope))


def an_index_key_a_head(monkeypatch, cfg):
    """A program that scores head j against a key of its own (the ONE key
    scaled by the head's number)."""
    def scores(q, w, keys):
        keys = keys[..., :q.shape[-1]].astype(q.dtype)
        per_head = keys[..., None, :, :] * (1.0 + 0.2 * jnp.arange(
            q.shape[-2], dtype=q.dtype))[:, None, None]
        s = jnp.einsum("...thd,...hnd->...thn", q, per_head)
        return jnp.einsum("...thn,...th->...tn", jax.nn.relu(s), w)

    monkeypatch.setattr(kvc, "index_scores", scores)


def the_token_forced_in(monkeypatch, cfg):
    real = kvc.index_scores

    def scores(q, w, keys):
        s = real(q, w, keys)
        t, n = s.shape[-2], s.shape[-1]
        own = jnp.arange(n)[None, :] == jnp.arange(t)[:, None]
        return jnp.where(own, 1e9, s)

    monkeypatch.setattr(kvc, "index_scores", scores)


def selection_off(monkeypatch, cfg):
    return dataclasses.replace(cfg, index_topk=1000)


def one_row_more(monkeypatch, cfg):
    return dataclasses.replace(cfg, index_topk=13)


def gate_dropped(monkeypatch, cfg):
    monkeypatch.setattr(dots3, "head_gate", lambda w: lambda h, o: o)


def rescale_dropped(monkeypatch, cfg):
    return dataclasses.replace(cfg, lora_rescale=False)


def window_one_short(monkeypatch, cfg):
    return dataclasses.replace(cfg, sliding_window=8)


def window_one_long(monkeypatch, cfg):
    return dataclasses.replace(cfg, sliding_window=10)


def thetas_swapped(monkeypatch, cfg):
    return dataclasses.replace(cfg, rope_theta=cfg.swa_rope_theta,
                               swa_rope_theta=cfg.rope_theta)


FULL_DEPARTURES = [relu_dropped, index_weights_all_one,
                   layer_norm_bias_dropped, index_rope_interleaved,
                   an_index_key_a_head, the_token_forced_in, selection_off,
                   one_row_more, gate_dropped, rescale_dropped,
                   thetas_swapped]
WINDOW_DEPARTURES = [window_one_short, window_one_long, gate_dropped,
                     rescale_dropped, thetas_swapped]


@pytest.mark.parametrize("kind, mutate", [
    *((F, m) for m in FULL_DEPARTURES), *((S, m) for m in WINDOW_DEPARTURES)],
    ids=lambda x: x.__name__ if callable(x) else x[:4])
def test_a_layer_that_departs_from_the_equations_fails(family, monkeypatch,
                                                       kind, mutate):
    """Each equation's term left out of (or bent in) the PROGRAM moves the
    layer's output by 1000x the tolerance the sound program holds (3e-5 at
    outputs of ~15)."""
    out, ref = one_layer(family, kind,
                         mutate=lambda cfg: mutate(monkeypatch, cfg))
    assert np.abs(out - ref).max() > 3e-2, np.abs(out - ref).max()


def test_the_index_scores_are_the_references_and_their_scale_is_a_term(
        family, monkeypatch):
    """``64^-1/2 x 128^-1/2`` (here 4^-1/2 x 16^-1/2) is a positive constant
    on every score: it moves no selection, so no logit can hold it; the
    SCORES do. The program's float32 scores of 20 tokens against their own
    cached keys are the reference's I(t, s) to 1e-5 of ~3; with the constant
    dropped they are 8x."""
    rng = np.random.default_rng(7)
    cfg, T = config(), 20
    w = drawn_leaves(cfg, F, rng)
    h = jnp.asarray(rng.standard_normal((1, T, 64)), jnp.float32)
    cq = jnp.asarray(rng.standard_normal((1, T, 24)), jnp.float32)
    cos, sin = family.rope_tables(HF, T)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(family.index_scores(h[0], cq[0], w, cos[F], sin[F],
                                             HF))
    rope = mdl.rope_table(cfg, 64)[F]
    pos = jnp.arange(T)[None]

    def program():
        ix = dots3.indexer(cfg, w.__getitem__, rope[0][pos][:, :, None, :],
                           rope[1][pos][:, :, None, :])(h, cq)
        return np.asarray(kvc.index_scores(ix["q"][0], ix["w"][0],
                                           ix["k"][0]))

    causal = np.tril(np.ones((T, T), bool))
    got = program()
    assert np.abs(ref[causal]).max() > 1
    assert np.abs(got - ref)[causal].max() < 1e-5
    real = dots3.indexer

    def unscaled(cfg, w, cos, sin):
        def index(h, cq):
            out = real(cfg, w, cos, sin)(h, cq)
            return {**out, "w": out["w"] * 8.0}
        return index

    monkeypatch.setattr(dots3, "indexer", unscaled)
    np.testing.assert_allclose(program()[causal], 8 * ref[causal], rtol=1e-4)


def test_a_stream_shorter_than_index_topk_is_dense_latent_attention():
    """Four slots of a decode step, ``index_topk`` 12: the two whose
    contexts hold 5 and 12 rows get the dense XLA attend's output to the
    bit's neighbourhood (every row chosen), the two with 21 and 40 do not;
    and the chosen positions ARE ``lax.top_k``'s."""
    rng = np.random.default_rng(3)
    S_, H, W, bt, MB = 4, 4, 40, 8, 6
    pool = jnp.asarray(rng.standard_normal((2, 30, bt, 128)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((2, 30, bt, 128)), jnp.float32)
    tables = jnp.asarray(1 + rng.permutation(24).reshape(S_, MB), jnp.int32)
    pos = jnp.array([4, 11, 20, 39], jnp.int32)
    q = jnp.asarray(rng.standard_normal((S_, 1, H, W)), jnp.float32)
    index = {"q": jnp.asarray(rng.standard_normal((S_, 1, 4, 16)),
                              jnp.float32),
             "w": jnp.asarray(rng.standard_normal((S_, 1, 4)), jnp.float32),
             "keys": keys}
    view = kvc.LatentView(pool, jnp.int32(1))
    how = {"scale": 0.2, "v_lanes": 32}
    sparse = np.asarray(kvc.latent_sparse_decode(tables, pos, 12).run(
        q, view, None, index=index, **how))
    dense = np.asarray(kvc.latent_xla_attend(tables).run(
        q, view, kvc.decode_mask(kvc.KindView(0, None), pos, MB * bt),
        **how))
    assert np.abs(dense).max() > 0.1
    np.testing.assert_allclose(sparse[:2], dense[:2], atol=2e-6)
    assert np.abs(sparse[2:] - dense[2:]).max() > 1e-2
    rows = keys[1][tables].reshape(S_, MB * bt, 128)
    scores = kvc.index_scores(index["q"], index["w"], rows)[:, 0]
    where, real = kvc.select_rows(scores, pos + 1, 12)
    for s in range(S_):
        n = int(pos[s]) + 1
        want = np.sort(np.asarray(jax.lax.top_k(scores[s, :n],
                                                min(12, n))[1]))
        np.testing.assert_array_equal(np.asarray(where[s])[
            np.asarray(real[s])], want)


def test_the_selection_is_exact_to_the_tie():
    """``select_rows`` / ``choose`` against ``lax.top_k`` on scores with
    MANY exact ties (a ReLU's zeros, negative zeros among them) and -inf:
    the same positions, ties to the earlier one."""
    rng = np.random.default_rng(5)
    raw = rng.standard_normal((6, 50)).astype(np.float32)
    scores = np.where(rng.random((6, 50)) < 0.5, 0.0, raw)
    scores[0, :10] = -0.0
    scores[1, 3] = -np.inf
    n = jnp.array([50, 50, 31, 7, 1, 12], jnp.int32)
    where, real = kvc.select_rows(jnp.asarray(scores), n, 9)
    for s in range(6):
        k = min(9, int(n[s]))
        want = np.sort(np.asarray(jax.lax.top_k(
            jnp.asarray(scores[s, :int(n[s])]) + 0.0, k)[1]))
        np.testing.assert_array_equal(
            np.asarray(where[s])[np.asarray(real[s])], want)
        assert int(np.asarray(real[s]).sum()) == k


def test_a_decode_steps_selection_is_the_chunks_on_the_same_rows(family):
    """ONE full layer, the same token over the same 21 cached rows and index
    keys: the decode step's form (the stream's chosen rows gathered) and a
    one-token chunk's (``latent_sparse_chunk``: its query's) choose the
    same 12 rows and agree to summation order."""
    rng = np.random.default_rng(9)
    cfg = config()
    w = drawn_leaves(cfg, F, rng)
    layout = kvc.LatentLayout(cfg, "float32", 1, 32, "xla", False, 8, 4, 6)
    kv, _ = layout.init()
    table = jnp.array([[3, 1, 4, 0]], jnp.int32)
    at = jnp.arange(21)
    c = kv.c.at[1, table[0, at // 8], at % 8, :40].set(
        jnp.asarray(rng.standard_normal((21, 40)), jnp.float32))
    i = kv.i.at[1, table[0, at // 8], at % 8, :16].set(
        jnp.asarray(rng.standard_normal((21, 16)), jnp.float32))
    stack = (c, kv.w, i)
    h = jnp.asarray(rng.standard_normal((1, 1, 64)), jnp.float32)
    positions = jnp.array([[21]], jnp.int32)
    rope = mdl.rope_table(cfg, 32)[F]
    cos = rope[0][positions][:, :, None, :]
    sin = rope[1][positions][:, :, None, :]

    def run(write, attn, mask):
        def attend(q, row, index, **how):
            st, view = write(stack, jnp.int32(1), row, 0)
            st, keys = write(st, jnp.int32(1), index["k"], 2)
            return attn[F].run(q, view, mask, index={
                **index, "keys": keys.cache}, **how), st

        return ds._attention(
            cfg.kind(F), h, w.__getitem__, cos, sin, attend, attn[F].path,
            index=dots3.indexer(cfg, w.__getitem__, cos, sin),
            gate=dots3.head_gate(w.__getitem__))

    absorbed, pool_a = run(*layout.decode(kv, table, positions[0]))
    chunk, pool_c = run(*layout.chunk(
        table[0], jnp.int32(0), positions, jnp.int32(21), jnp.int32(1)))
    for a, b in zip(pool_a, pool_c):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert np.abs(np.asarray(chunk)).max() > 1
    assert np.abs(np.asarray(absorbed - chunk)).max() < 2e-5


@pytest.mark.parametrize("offset, topk", [(0, 16), (16, 12), (8, 48)])
def test_a_chunks_queries_choose_and_attend_as_decode_streams_do(offset,
                                                                 topk):
    """``latent_sparse_chunk`` over 8 queries behind ``offset`` cached rows
    against ``latent_sparse_decode`` with every query a stream of its own
    on the one table row: a chunk that ends inside ``index_topk`` (it
    attends every row, scoring nothing), one that ends past it (each query
    its own chosen rows) and one whose ``index_topk`` is more than the
    table holds."""
    rng = np.random.default_rng(offset + topk)
    T, bt, MB, W, H = 8, 8, 4, 40, 4
    c = jnp.asarray(rng.standard_normal((2, 6, bt, 128)), jnp.float32)
    keys = jnp.asarray(rng.standard_normal((2, 6, bt, 128)), jnp.float32)
    table = jnp.array([3, 1, 4, 2], jnp.int32)
    q = jnp.asarray(rng.standard_normal((1, T, H, W)), jnp.float32)
    index = {"q": jnp.asarray(rng.standard_normal((1, T, 4, 16)),
                              jnp.float32),
             "w": jnp.asarray(rng.standard_normal((1, T, 4)), jnp.float32),
             "keys": keys}
    view = kvc.LatentView(c, jnp.int32(1))
    how = {"scale": 0.2, "v_lanes": 32}
    chunk = kvc.latent_sparse_chunk(table, jnp.int32(offset), topk).run(
        q, view, None, index=index, **how)
    pos = offset + jnp.arange(T)
    steps = kvc.latent_sparse_decode(
        jnp.broadcast_to(table[None], (T, MB)), pos, topk).run(
            q[0][:, None], view, None, index={
                "q": index["q"][0][:, None], "w": index["w"][0][:, None],
                "keys": keys}, **how)
    assert chunk.shape == (1, T, H, 32)
    assert np.abs(np.asarray(chunk)).max() > 0.1
    np.testing.assert_allclose(np.asarray(chunk[0]), np.asarray(steps[:, 0]),
                               atol=2e-6)


def test_a_stacked_matrix_is_drawn_a_slice_at_a_time(monkeypatch):
    """``init_leaf`` over ``STACKED_DRAW`` elements: N(0, 0.02) in every
    slice, no two slices alike, the same leaf for the same key; vectors and
    the selection bias keep their own draws."""
    monkeypatch.setattr(dots3, "STACKED_DRAW", 1 << 10)
    key = jax.random.key(7)
    a = np.asarray(dots3.init_leaf(key, (2, 3, 32, 64), "w_gate",
                                   jnp.float32))
    assert a.shape == (2, 3, 32, 64) and abs(a.std() - 0.02) < 1e-3
    flat = a.reshape(6, -1)
    assert all(np.abs(flat[i] - flat[j]).max() > 0.01
               for i in range(6) for j in range(i))
    np.testing.assert_array_equal(a, np.asarray(dots3.init_leaf(
        key, (2, 3, 32, 64), "w_gate", jnp.float32)))
    gains = np.asarray(dots3.init_leaf(key, (2, 3, 512), "swa_q_norm",
                                       jnp.float32))
    assert (gains == ds.LATENT_NORM_GAIN).all()
    bias = np.asarray(dots3.init_leaf(key, (2, 3, 512), "expert_bias",
                                      jnp.float32))
    assert abs(bias.std() - dots3.BIAS_STD) < 1e-3


def test_the_bias_picks_and_the_score_weighs(family):
    """``noaux_tc`` with one group: the program's scoring rule against the
    reference's routing on 12 tokens over 16 experts, bias N(0, 0.3): equal;
    a rule that weighs with the bias inside, or selects without it, is
    not."""
    rng = np.random.default_rng(11)
    cfg = config(n_routed_experts=16, expert_parallel=None)
    hf = {**HF, "n_routed_experts": 16, "expert_parallel": None}
    logits = jnp.asarray(rng.standard_normal((12, 16)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.standard_normal(16), jnp.float32)
    # (the reference's router as the identity: its scores are the logits')
    want = np.asarray(family.routing(logits, jnp.eye(16), bias, hf))

    def dense(rule):
        topv, topi = rule(logits)
        return np.asarray(jnp.zeros((12, 16)).at[
            jnp.arange(12)[:, None], topi].set(topv))

    np.testing.assert_allclose(dense(dots3.scores(cfg, bias)), want,
                               atol=1e-6)
    assert np.abs(dense(dots3.scores(cfg, None)) - want).max() > 0.05

    def bias_inside(lg):
        s = jax.nn.sigmoid(lg) + bias
        topv, topi = jax.lax.top_k(s, 3)
        return topv / topv.sum(-1, keepdims=True), topi

    assert np.abs(dense(bias_inside) - want).max() > 0.02


def test_the_sixteen_shares_add_up_to_the_uncut_layer(family):
    """One expert block of the period, 16 experts, top-3 under the bias, cut
    over 16 ranks of ONE expert each (the cell's 16-way cut at test width):
    the routed parts of the sixteen shares, plus the shared expert ONCE, are
    the reference's uncut layer; every token's three pairs land somewhere."""
    E, size = 16, 16
    whole_hf = {**HF, "n_routed_experts": E, "expert_parallel": None}
    whole = config(n_routed_experts=E, expert_parallel=None)
    lay = seeded_params(whole, seed=3)["layers"]
    h = jnp.asarray(RNG.standard_normal((6, 64)), jnp.float32)
    valid = jnp.ones(6, bool)
    m = 2                                       # the period's third layer
    w = {n: np.asarray(lay[n][0, m], np.float32)
         for n in dots3.PER_LAYER + xp.EXPERT_LEAVES}
    shared_w = tuple(lay[n][0, m] for n in ("shared_gate", "shared_up",
                                            "shared_down"))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(family.experts(h, w, whole_hf))
        shared = np.asarray(xp.shared_expert(h, *shared_w))
        cut = config(n_routed_experts=E // size,
                     expert_parallel={"size": size, "rank": 0})
        assert cut.router_width == E

        @jax.jit
        def share(rank):
            held = tuple(jax.lax.dynamic_slice_in_dim(lay[n], rank, 1, 2)
                         for n in xp.EXPERT_LEAVES)
            out, n_touched, load = xp.moe_block(
                h, lay["moe_gate"][0, m],
                dots3.scores(cut, lay["expert_bias"][0, m]), held,
                jnp.int32(0), m, num_experts=cut.num_experts,
                ep_rank=rank, valid=valid,
                shared=lambda h: xp.shared_expert(h, *shared_w))
            return out, xp.counts(n_touched, load)[1]

        total, pairs = shared.copy(), 0
        for rank in range(size):
            out, landed = share(jnp.int32(rank))
            total += np.asarray(out) - shared
            pairs += int(landed)
    assert pairs == 6 * 3
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(total, want, atol=F32_TOL)


# ---------------------------------------------------------------------------
# (c) the stack, the config, the refusals


def test_the_stack_is_dense_lone_and_periods():
    cfg = config(**DEEP)
    shapes = mdl.param_shapes(cfg)
    assert shapes["dense_wq_b"] == (1, 24, 4 * 24)
    assert shapes["dense_idx_wq"] == (1, 24, 4 * 16)
    assert shapes["lone_w_gate"] == (1, 1, 8, 64, 32)
    lay = shapes["layers"]
    assert lay["attn_norm"] == (2, 4, 64) and lay["wg"] == (2, 64, 4)
    assert lay["swa_wkv_a"] == (2, 3, 64, 56) and lay["swa_wg"] == (2, 3, 64,
                                                                    2)
    assert lay["idx_wk"] == (2, 64, 16) and "swa_idx_wk" not in lay
    assert lay["w_down"] == (2, 4, 8, 32, 64)
    assert lay["expert_bias"] == (2, 4, 16)
    params = mdl.init_params(jax.random.key(0), cfg)
    assert params["layers"]["expert_bias"].dtype == jnp.float32
    assert float(jnp.abs(params["lone_idx_k_bias"]).max()) > 0
    full, window = cfg.kind(F), cfg.kind(S)
    assert (full.num_heads, full.hd, full.latent_width, full.index_topk,
            full.sliding_window) == (4, 24, 40, 12, None)
    assert (window.num_heads, window.hd, window.latent_width,
            window.index_topk, window.sliding_window) == (2, 32, 56, 0, 9)
    assert full.q_rescale == pytest.approx((64 / 24) ** 0.5)
    assert window.kv_rescale == pytest.approx((64 / 48) ** 0.5)
    assert (full.rope_theta, window.rope_theta) == (80000000, 50000)
    rope = mdl.rope_table(cfg, 16)
    assert set(rope) == {F, S} and rope[F][0].shape == (16, 4)
    assert not np.allclose(rope[F][0], rope[S][0])


@pytest.mark.parametrize("changed, says", [
    ({"num_hidden_layers": 4}, "ends inside a period of"),
    ({"num_hidden_layers": 7}, "ends inside a period of"),
    ({"first_k_dense_replace": 3}, "a window layer with a dense MLP"),
    ({"topk_method": "none"}, "topk_method 'none' is not served"),
    ({"n_group": 8}, "group-limited router"),
    ({"attention_gate_type": "elementwise"}, "attention_gate_type"),
    ({"swa_attention_gate_type": None}, "swa_attention_gate_type"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"index_topk": 0}, "no index_topk"),
    ({"sliding_window_size": None}, "no sliding_window_size"),
    ({"expert_parallel": {"size": 2, "rank": 2}}, "outside size"),
])
def test_a_config_the_family_cannot_hold_is_refused(changed, says):
    with pytest.raises(ValueError, match=says):
        config(**changed)


SENTENCE = ("is not served for model_type dots3_note: its full layers "
            "select rows by an indexer")


@pytest.mark.parametrize("what, kw", [
    ("the contiguous K/V layout", {"paged": False}),
    ("a int8 K/V pool", {"kv_dtype": "int8"}),
    ("a int4 K/V pool", {"kv_dtype": "int4"}),
    ("self-extend", {"ga_n": 2, "ga_w": 8}),
    ("a device mesh", {"mesh": {"model": 2}}),
    ("the ring prefill", {"mesh": {"seq": 2}}),
    ("pipeline parallelism", {"mesh": {"pipe": 2}}),
])
def test_what_the_three_arrays_cannot_be_served_through_is_refused(
        drawn, what, kw):
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    if "mesh" in kw:
        kw["mesh"] = build_mesh(MeshPlan(**kw["mesh"]),
                                devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=f"^{what} {SENTENCE}"):
        runner_for(config(), drawn, **kw)


def test_speculation_and_quantised_weights_are_refused(drawn):
    cfg = config()
    r = runner_for(cfg, drawn)
    with pytest.raises(ValueError, match=f"^speculative decoding {SENTENCE}"):
        r.verify_async(np.zeros((4, 2), np.int32))
    with pytest.raises(ValueError,
                       match=f"^engine.quantization 'int8' {SENTENCE}"):
        synthetic_params(cfg, "int8")
    with pytest.raises(ValueError, match="a forward with no latent attend"):
        dots3.forward(cfg, r.params, None, jnp.zeros((1, 1), jnp.int32),
                      None, None, None, r.rope, valid=None)


# ---------------------------------------------------------------------------
# (e) the scopes; the scheduler's counts


def test_the_programs_name_the_new_scopes():
    cfg = config("bfloat16")
    r = runner_for(cfg, mdl.init_params(jax.random.key(0), cfg),
                   prefill_chunk=32, prefill_buckets=[32])
    text = lowered_texts(r, ("decode", "prefill_1"), debug_info=True)
    for scope in ("mla/q", "mla/kv_a", "mla/o", "mla/gate", "dsa/q", "dsa/k",
                  "attn.index", "attn.select", "attn.sparse_decode",
                  "attn.latent_window", "moe/experts", "dense_mlp"):
        assert scope in text["decode"], scope
    assert "mla/kv_b" not in text["decode"]     # never decompressed
    assert "latent_decode_attn" not in text["decode"]
    # a chunk's full layers choose and gather as a step does (a branch of
    # the chunk's scope; the other attends every row of a short span) and
    # rebuild no key: only the window layers' walk decompresses
    at = r"attn\.latent_chunk/cond/branch_\d_fun/"
    # (the loop over a chunk's groups of queries is a call of its own in
    # this text: its scopes stand alone here and under the chunk's in the
    # compiled program's names)
    for scope in ("mla/gate", "dsa/q", "dsa/k", at + "attn.index",
                  "attn.select/", "attn.sparse_chunk/",
                  at + "attn.dense_chunk",
                  "attn.latent_window/while/body/mla/kv_b", "kv_pool.write"):
        assert re.search(scope, text["prefill_1"]), scope
    assert "attn.sparse_decode" not in text["prefill_1"]
    assert not re.search(r"attn\.latent_chunk[^\"]*mla/kv_b",
                         text["prefill_1"])


def test_the_flight_ring_and_the_counter_count_scored_and_attended_rows():
    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.obs import metrics as obs_metrics
    from localai_tpu.utils.tokenizer import ByteTokenizer

    cfg = config()
    r = runner_for(cfg, seeded_params(cfg))
    s = Scheduler(r, ByteTokenizer(), multi_step=2)
    try:
        text = "forty characters of prompt, and the BOS."    # 40 + BOS
        h = s.submit(GenRequest(prompt=ByteTokenizer().encode(text),
                                max_new_tokens=14, temperature=0.0,
                                ignore_eos=True))
        assert h._done.wait(60.0) and h.completion_tokens == 14
        deadline = time.monotonic() + 10.0
        while True:
            rows = s.flight.snapshot()
            decode = [x for x in rows if x["program"].startswith("decode")]
            if (sum(x["steps"] for x in decode) >= 13
                    or time.monotonic() > deadline):
                break
            time.sleep(0.05)
        assert decode
        for x in decode:
            # every context is past both: 12 rows chosen, 9 in the window
            assert x["live_slots"] == 1
            assert x["selected_tokens"] == 12 * x["steps"]
            assert x["window_tokens"] == 9 * x["steps"]
            assert x["attended_tokens"] > 40 * x["steps"]
        m = s.metrics()
        assert m["mla_attends"]["decompressed"] == 3
        scored, attended = m["dsa_rows"]["scored"], m["dsa_rows"]["attended"]
        # three full layers; counted at the ENQUEUE (a launch in flight has
        # no ring row yet)
        assert attended % (3 * 12) == 0
        assert attended >= 3 * sum(x["selected_tokens"] for x in decode)
        assert scored >= 3 * sum(x["attended_tokens"] for x in decode)
        assert 3.3 < scored / attended < 4.6        # ~41-54 rows over 12
        obs_metrics.update_engine_gauges("d3", m)
        text = obs_metrics.REGISTRY.render()
        assert (f'localai_dsa_rows_total{{kind="attended",model="d3"}} '
                f'{attended}') in text
        assert 'localai_dsa_rows_total{kind="scored",model="d3"} ' in text
    finally:
        s.shutdown()
