"""The DeepSeek-V3 block (``model_type: axk1``) on the normal serving path:
latent attention over the latent paged pool, ABSORBED in a decode step and
DECOMPRESSED in a prefill chunk, a dense layer in front of group-limited
sigmoid-routed experts with an ungated shared expert. CPU, tiny widths, seeded
random weights: D 64, 4 heads of [16 nope | 8 rope] queries and 16-wide values,
q rank 24, latent rank 32 (a cached row: 40 elements in 128 lanes), YaRN factor
4 over 16 positions (so every context here lies past the original length and
``m^2`` = 1.30), 1 dense + 2 expert layers, 8 of 16 experts held
(``expert_parallel`` size 2, rank 1) in 4 groups of which 2 are kept, top-3.

The served path is the runner's own programs (``_prefill_paged_fn`` /
``_decode_paged_fn``), driven by ``admit`` and ``step`` and tapped for the
LOGITS they sample from; the reference is the benchmark's plain float32 family
(benchmark/reference/deepseek_family.py, written from the DeepSeek-V3 text)
run as the benchmark runs it (harness/refcheck.py): the FULL forward over
prompt + served tokens, in the published, decompressed form, no cache.
"""

import dataclasses
import functools
import json
import time

import families
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from families import (ROOT, agree, lowered_texts, refcheck,
                      reference_logits, served_logits, spec, tap)

from localai_tpu import ops
from localai_tpu.engine import kvcache as kvc
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import deepseek as ds
from localai_tpu.models import experts as xp
from localai_tpu.models import llama as mdl
from localai_tpu.models.llama import LlamaConfig
from localai_tpu.models.registry import synthetic_params
from localai_tpu.ops import attention as att

YARN = {"type": "yarn", "factor": 4, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1}
HF = {"model_type": "axk1", "vocab_size": 384, "hidden_size": 64,
      "intermediate_size": 96, "num_hidden_layers": 3,
      "num_attention_heads": 4, "num_key_value_heads": 4,
      "q_lora_rank": 24, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
      "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_theta": 10000,
      "rms_norm_eps": 1e-6, "max_position_embeddings": 512,
      "rope_scaling": YARN, "first_k_dense_replace": 1, "moe_layer_freq": 1,
      "n_routed_experts": 8, "num_experts_per_tok": 3,
      "moe_intermediate_size": 32, "n_shared_experts": 1, "n_group": 4,
      "topk_group": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
      "scoring_func": "sigmoid", "topk_method": "none", "seq_aux": True,
      "ep_size": 1, "attention_bias": False, "tie_word_embeddings": False,
      "expert_parallel": {"size": 2, "rank": 1}}
RNG = np.random.default_rng(48)
PROMPT = RNG.integers(1, 380, 37).tolist()      # three chunks: 16 + 16 + 5
STEPS = 10                                      # contexts 37 .. 47
# float32 serving: what is left between the two is summation order (the
# absorbed form sums a score over the 32 latent lanes where the reference
# sums it over a head's 16: the same products, regrouped)
F32_TOL = 2e-5
# bfloat16 serving, logits up to ~2: every activation is rounded to 8 bits
# some twenty times in a row through 3 layers, the latent rows are kept in
# bfloat16 and the logits are written in bfloat16 (half an ulp at 1-2 is
# 0.004); a rounded router flips near-ties between experts (top-3 of 16),
# which moves the worst of 11 x 384 logits by a whole expert's weight while
# the mean stays small: held by the mean, the worst to a bound a dropped term
# breaks (the mutations below move the float32 logits by 0.01 to 1)
BF16_MEAN_TOL, BF16_TOL = 0.05, 0.6


@pytest.fixture(scope="module")
def family():
    return families.reference_family("deepseek_family",
                                     "tests/test_deepseek.py")


# two dense layers and three expert layers: more than one of each
DEEP = {"num_hidden_layers": 5, "first_k_dense_replace": 2}


config = functools.partial(families.config, HF)


def seeded_params(cfg, seed: int = 0):
    """The program's seeded weights with every norm gain redrawn at 1 + 0.3 N
    (at a constant, dropping or swapping a norm would change little) and the
    matrices three times as large, so that every branch weighs on the
    logits."""
    rng = np.random.default_rng(seed + 1)

    def redraw(name, a):
        return (families.gain(rng, a) if name.endswith("norm")
                else families.tripled(a))

    return families.redrawn(mdl.init_params(jax.random.key(seed), cfg),
                            redraw)


def runner_for(cfg, params, impl="xla", **kw) -> ModelRunner:
    """Under ``pallas_interpret`` fewer slots and larger blocks (a kernel
    instance in the interpreter compiles for seconds on the CPU, by slots
    and table entries)."""
    small = impl == "pallas_interpret"
    kw = {"num_slots": 2 if small else 4, "max_ctx": 64, "paged": True,
          "kv_block_tokens": 16 if small else 8, "prefill_chunk": 16,
          "prefill_buckets": [16, 32], "attn_impl": impl,
          "kv_dtype": cfg.dtype, **kw}
    return ModelRunner(cfg, params, **kw)


# ---------------------------------------------------------------------------
# (a) the served path against the plain reference


@pytest.mark.parametrize("dtype, impl, deep", [
    ("float32", "xla", True), ("float32", "pallas_interpret", False),
    ("bfloat16", "xla", False), ("bfloat16", "pallas_interpret", False)])
def test_served_logits_match_the_reference(family, monkeypatch, dtype, impl,
                                           deep):
    """A prompt over three chunks (the last with padded rows; each attends
    DECOMPRESSED over the span it has), then decode steps (ABSORBED over the
    pool as it lies): the logits each program samples from against the full
    forward in the published form. Under ``pallas_interpret`` the decode
    attend is the latent kernel, which writes the step's rows, and the
    experts ops.moe's kernel."""
    hf = {**HF, **(DEEP if deep else {})}
    cfg = config(dtype, **(DEEP if deep else {}))
    params = seeded_params(cfg)
    r = runner_for(cfg, params, impl)
    assert r.latent and r.routed and not r.recurrent and not r.kinds
    assert isinstance(r.layout, kvc.LatentLayout)
    assert r.paged_kv_write_impl == (
        "kernel" if impl == "pallas_interpret" else "scatter")
    assert (r.family_kernels is not None) == (impl == "pallas_interpret")
    served, tokens = served_logits(r, tap(r), 1, PROMPT, STEPS)
    assert r.admit_programs == 1 + 3            # the arming and three chunks
    # ONE array: a row a token a layer, 40 elements in 128 lanes, no heads
    assert r.kv.c.shape == (cfg.num_layers, r.allocator.num_blocks,
                            r.block_tokens, 128)
    assert cfg.latent_width == 40 and cfg.cache_layers == (5 if deep else 3)
    assert set(r.state.rec) == {"routed"} and r.state_bytes == 0
    ref = reference_logits(family, params, hf, PROMPT, tokens, monkeypatch)
    if dtype == "float32":
        agree(served, ref, F32_TOL)
        assert (served.argmax(-1) == ref.argmax(-1)).all()
    else:
        assert np.abs(ref).max() > 0.2
        assert np.abs(served - ref).mean() < BF16_MEAN_TOL
        assert np.abs(served - ref).max() < BF16_TOL


def test_a_span_of_several_steps_of_the_walk(family, monkeypatch):
    """The chunk's attend walks its span ``LATENT_WALK_TOKENS`` rows at a
    time under a traced trip count: at a walk of 32 rows (4 blocks of 8) a
    prompt of 150 tokens in ten chunks crosses four steps, the last chunks'
    walks five long; then decode."""
    monkeypatch.setattr(kvc, "LATENT_WALK_TOKENS", 32)
    cfg = config()
    params = seeded_params(cfg)
    r = runner_for(cfg, params, max_ctx=192, num_slots=2)
    assert kvc.latent_walk(8) == 32 and r.chunk_span(144, 16) == 160
    assert r.chunk_span(0, 16) == 32 and r.chunk_span(17, 16) == 64
    prompt = RNG.integers(1, 380, 150).tolist()
    served, tokens = served_logits(r, tap(r), 0, prompt, steps=3)
    agree(served, reference_logits(family, params, HF, prompt, tokens,
                                   monkeypatch), F32_TOL)


def test_absorbed_and_decompressed_agree_on_one_layer():
    """The two forms of ONE layer's attention for the same token over the
    same 21 cached rows: the decode step's (absorbed: W_uk folded into the
    query, attended over the latent, W_uv behind) and a one-token chunk's
    (decompressed: k and v rebuilt from the rows). Identical in exact
    arithmetic; in float32 they differ by the order of the sums (measured
    here: under 2e-7 at outputs of ~0.05)."""
    cfg = config()
    params = seeded_params(cfg)
    lay = params["layers"]
    layout = kvc.LatentLayout(cfg, "float32", 1, 32, "xla", False, 8, 4, 6)
    kv, _ = layout.init()
    rows = jnp.asarray(RNG.standard_normal((21, 40)), jnp.float32)
    table = jnp.array([[3, 1, 4, 0]], jnp.int32)
    pos = jnp.arange(21)
    pool = kv.c.at[1, table[0, pos // 8], pos % 8, :40].set(rows)
    h = jnp.asarray(RNG.standard_normal((1, 1, 64)), jnp.float32)
    rope = mdl.rope_table(cfg, 32)
    positions = jnp.array([[21]], jnp.int32)
    cos = rope[0][positions][:, :, None, :]
    sin = rope[1][positions][:, :, None, :]

    def w(name):
        return lay[name][0]

    def run(write, attn, mask):
        def attend(q, row, **how):
            stack, view = write((pool,), jnp.int32(1), row)
            return attn.run(q, view, mask, **how), stack

        return ds._attention(cfg, h, w, cos, sin, attend, attn.path)

    absorbed, (pool_a,) = run(*layout.decode(kv, table, positions[0]))
    chunk, (pool_c,) = run(*layout.chunk(
        table[0], jnp.int32(0), positions, jnp.int32(21), jnp.int32(1)))
    np.testing.assert_array_equal(np.asarray(pool_a), np.asarray(pool_c))
    assert np.abs(np.asarray(chunk)).max() > 0.05
    assert np.abs(np.asarray(absorbed - chunk)).max() < 2e-7


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_latent_kernel_is_the_xla_attend_and_the_scatter(dtype):
    """``ops.latent_decode_attention`` in the interpreter against the XLA
    attend over a pool the scatter policy wrote: four slots, one whose
    position opens a block (a block boundary), one in the middle of its
    third block, one on the TRASH block (released: its table row is zeros;
    the kernel writes nothing back for it), one at the last row of its
    table; layer 1 of 2."""
    dt = jnp.dtype(dtype)
    S, H, W, bt = 4, 4, 40, 16
    lanes = att.latent_lanes(W)
    k = jax.random.split(jax.random.key(0), 3)
    pool = jnp.zeros((2, 20, bt, lanes), dt).at[..., :W].set(
        jax.random.normal(k[0], (2, 20, bt, W)).astype(dt))
    q = jax.random.normal(k[1], (S, 1, H, W)).astype(dt)
    new = jax.random.normal(k[2], (S, 1, W)).astype(dt)
    tables = jnp.array([[1, 2, 3, 0, 0, 0], [4, 5, 0, 0, 0, 0],
                        [0, 0, 0, 0, 0, 0], [6, 7, 8, 9, 10, 11]], jnp.int32)
    pos = jnp.array([37, 16, 0, 95], jnp.int32)
    mask = kvc.decode_mask(kvc.KindView(0, None), pos, 6 * bt)
    how = {"scale": 0.2, "v_lanes": 32}
    stack, view = kvc.latent_decode_write(tables, pos, raw=True)(
        (pool,), jnp.int32(1), new)
    assert stack[0] is pool and view.new.shape == (S, lanes)
    out, (written,) = kvc.latent_kernel_attend(tables, pos, True).run(
        q, view, mask, **how)
    (want_pool,), want_view = kvc.latent_decode_write(tables, pos)(
        (pool,), jnp.int32(1), new)
    want = kvc.latent_xla_attend(tables).run(q, want_view, mask, **how)
    live = [0, 1, 3]
    tol = 2e-6 if dtype == "float32" else 2e-2      # bfloat16 outputs of ~1
    assert np.abs(np.asarray(want, np.float32)[live]).max() > 0.1
    np.testing.assert_allclose(np.asarray(out, np.float32)[live],
                               np.asarray(want, np.float32)[live], atol=tol)
    # the pools agree row for row outside the trash block, which the scatter
    # writes and the kernel leaves alone; layer 0 untouched
    np.testing.assert_array_equal(np.asarray(written[:, 1:], np.float32),
                                  np.asarray(want_pool[:, 1:], np.float32))
    np.testing.assert_array_equal(np.asarray(written[:, 0], np.float32),
                                  np.asarray(pool[:, 0], np.float32))
    np.testing.assert_array_equal(np.asarray(written[1, 5, 0, :W]),
                                  np.asarray(new[1, 0]))


# ---------------------------------------------------------------------------
# (b) every term, with a program that fails without it


def m2_dropped_from_the_scale(monkeypatch, cfg):
    return dataclasses.replace(cfg, softmax_mscale=1.0)


def yarn_dropped_from_the_frequencies(monkeypatch, cfg):
    return dataclasses.replace(cfg, rope_scaling=None)


def rope_pairs_taken_as_halves(monkeypatch, cfg):
    monkeypatch.setattr(ds, "rope_pairs", lambda x: x)
    return cfg


def a_rope_key_per_head(monkeypatch, cfg):
    """A program that rotates the shared key as if each head had its own
    (its position the head's number further on)."""
    real = ds.expand

    def expand(cfg, w_kvb, rows):
        k, v = real(cfg, w_kvb, rows)
        shift = jnp.arange(cfg.num_heads, dtype=k.dtype)[None, :, None]
        return k.at[..., cfg.qk_nope_head_dim:].multiply(
            1.0 + 0.05 * shift), v

    monkeypatch.setattr(ds, "expand", expand)
    return cfg


def latent_norms_dropped(monkeypatch, cfg):
    monkeypatch.setattr(ds, "latent_norm", lambda x, w, eps: x)
    return cfg


def group_limit_dropped(monkeypatch, cfg):
    return dataclasses.replace(cfg, n_group=1, topk_group=1)


def route_scale_dropped(monkeypatch, cfg):
    return dataclasses.replace(cfg, route_scale=1.0)


def renormalisation_dropped(monkeypatch, cfg):
    return dataclasses.replace(cfg, route_norm=False)


def shared_expert_gated(monkeypatch, cfg):
    def gated(h, w_gate, w_up, w_down):
        gate = jax.nn.sigmoid(jnp.sum(h.astype(jnp.float32), -1,
                                      keepdims=True))
        return gate * xp.swiglu(h, w_gate, w_up, w_down).astype(jnp.float32)

    monkeypatch.setattr(xp, "shared_expert", gated)
    return cfg


def dense_layer_routed_as_an_expert_layer_would_not_be(monkeypatch, cfg):
    """The first layer's MLP at half its width: not the dense SwiGLU."""
    real = xp.swiglu

    def swiglu(h, w_gate, w_up, w_down):
        if w_gate.shape[-1] == cfg.intermediate_size:
            half = cfg.intermediate_size // 2
            return real(h, w_gate[:, :half], w_up[:, :half], w_down[:half])
        return real(h, w_gate, w_up, w_down)

    monkeypatch.setattr(xp, "swiglu", swiglu)
    return cfg


MUTATIONS = [m2_dropped_from_the_scale, yarn_dropped_from_the_frequencies,
             rope_pairs_taken_as_halves, a_rope_key_per_head,
             latent_norms_dropped, group_limit_dropped, route_scale_dropped,
             renormalisation_dropped, shared_expert_gated,
             dense_layer_routed_as_an_expert_layer_would_not_be]


@pytest.mark.parametrize("mutate, impl", [
    *((m, "xla") for m in MUTATIONS),
    (m2_dropped_from_the_scale, "pallas_interpret")],
    ids=lambda x: getattr(x, "__name__", x))
def test_a_program_that_departs_from_the_equations_fails(
        family, monkeypatch, mutate, impl):
    """Each departure, in the PROGRAM alone (the reference keeps the
    published keys), moves the float32 logits by at least 100 x the
    tolerance of (a). Under the kernel the one case that is other code
    there: the scale, the latent kernel's own ``sm_scale``. (The rope key a
    head: the decompressed chunk alone is mutated, so it also shows that a
    chunk's rows are what the decode steps read.)"""
    cfg = mutate(monkeypatch, config())
    params = seeded_params(config())
    r = runner_for(cfg, params, impl)
    served, tokens = served_logits(r, tap(r), 1, PROMPT, steps=4)
    ref = reference_logits(family, params, HF, PROMPT, tokens, monkeypatch)
    assert np.abs(served - ref).max() > 100 * F32_TOL


@pytest.mark.parametrize("lower", ["a bfloat16 pool", "8-bit rows"])
def test_a_latent_row_kept_in_fewer_bits_fails_the_tolerance(
        family, monkeypatch, lower):
    """float32 weights and arithmetic over latent rows that are NOT float32:
    a bfloat16 pool, or rows rounded to 8 bits (per-row absmax / 127) on
    their way into it: at least 50 x the float32 tolerance away."""
    cfg = config()
    params = seeded_params(cfg)
    kw = {}
    if lower == "a bfloat16 pool":
        kw["kv_dtype"] = "bfloat16"
    else:
        real = kvc._pad_lanes

        def rounded(rows, lanes):
            scale = jnp.max(jnp.abs(rows), axis=-1, keepdims=True) / 127.0
            return real(jnp.round(rows / scale) * scale, lanes)

        monkeypatch.setattr(kvc, "_pad_lanes", rounded)
    r = runner_for(cfg, params, **kw)
    served, tokens = served_logits(r, tap(r), 1, PROMPT, steps=4)
    ref = reference_logits(family, params, HF, PROMPT, tokens, monkeypatch)
    assert np.abs(served - ref).max() > 50 * F32_TOL


def test_the_group_limit_is_an_argument_of_the_one_scoring_rule():
    """``models.experts.sigmoid_scores`` at the published shape (192 experts
    in 8 groups of 24, 4 kept, top-8): with ``n_group`` 1 it IS the plain
    top-k (the other reading of ``topk_method: "none"``: two keys away); at
    8 / 4 it picks differently on seeded scores, every choice lies in a kept
    group, and the weights are the choices' own scores over their sum, times
    2.5."""
    logits = jnp.asarray(RNG.standard_normal((64, 192)), jnp.float32)
    s = np.asarray(jax.nn.sigmoid(logits))
    plain_w, plain_i = xp.sigmoid_scores(8, None, True, 2.5)(logits)
    one_w, one_i = xp.sigmoid_scores(8, None, True, 2.5, n_group=1,
                                     topk_group=1)(logits)
    np.testing.assert_array_equal(np.asarray(plain_i), np.asarray(one_i))
    np.testing.assert_array_equal(np.asarray(plain_w), np.asarray(one_w))
    want = np.argsort(-s, axis=-1)[:, :8]
    assert (np.sort(np.asarray(plain_i)) == np.sort(want)).all()
    lim_w, lim_i = xp.sigmoid_scores(8, None, True, 2.5, n_group=8,
                                     topk_group=4)(logits)
    lim_i = np.asarray(lim_i)
    differs = (np.sort(lim_i) != np.sort(np.asarray(plain_i))).any(axis=-1)
    assert 10 < differs.sum() < 64          # most tokens, not all
    top2 = np.sort(s.reshape(64, 8, 24), axis=-1)[..., -2:].sum(-1)
    kept = np.argsort(-top2, axis=-1)[:, :4]
    for t in range(64):
        assert set(lim_i[t] // 24) <= set(kept[t])
        inside = np.where(np.isin(np.arange(192) // 24, kept[t]), s[t], 0)
        assert set(lim_i[t]) == set(np.argsort(-inside)[:8])
    chosen = np.take_along_axis(s, lim_i, axis=-1)
    np.testing.assert_allclose(
        np.asarray(lim_w), 2.5 * chosen / chosen.sum(-1, keepdims=True),
        rtol=1e-6)
    np.testing.assert_allclose(np.asarray(lim_w).sum(-1), 2.5, rtol=1e-6)


# ---------------------------------------------------------------------------
# (c) the share: the ranks' parts add up to the uncut layer


def test_the_sixteen_shares_add_up_to_the_uncut_layer(family):
    """One expert block, 16 experts in 4 groups (2 kept), top-3, cut over 16
    ranks of ONE expert each (the cell's 16-way cut at test width): the
    routed parts of the sixteen shares, plus the shared expert ONCE, are the
    reference's uncut layer; every token's three pairs land somewhere."""
    E, size = 16, 16
    whole_hf = {**HF, "n_routed_experts": E, "expert_parallel": None}
    whole = config(n_routed_experts=E, expert_parallel=None)
    params = seeded_params(whole, seed=3)
    lay = params["layers"]
    h = jnp.asarray(RNG.standard_normal((6, 64)), jnp.float32)
    valid = jnp.ones(6, bool)
    at = 1                                      # the second expert layer
    w = {n: np.asarray(a[at], np.float32) for n, a in lay.items()}
    shared_w = tuple(lay[n][at] for n in ("shared_gate", "shared_up",
                                          "shared_down"))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(family.experts(h, w, whole_hf))
        shared = np.asarray(xp.shared_expert(h, *shared_w))
        total, pairs = shared.copy(), 0
        for rank in range(size):
            cut = config(n_routed_experts=E // size,
                         expert_parallel={"size": size, "rank": rank})
            assert cut.router_width == E
            held = tuple(lay[n][:, :, rank:rank + 1]
                         for n in xp.EXPERT_LEAVES)
            out, n_touched, load = xp.moe_block(
                h, lay["moe_gate"][at], ds.scores(cut), held,
                jnp.int32(at), 0, num_experts=cut.num_experts,
                ep_rank=rank, valid=valid,
                shared=lambda h: xp.shared_expert(h, *shared_w))
            total += np.asarray(out) - shared
            pairs += int(xp.counts(n_touched, load)[1])
    assert pairs == 6 * 3
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(total, want, atol=F32_TOL)


# ---------------------------------------------------------------------------
# (d) the prefix pool and the prompt cache over latent rows


def test_a_prefix_from_the_pool_gives_the_whole_prefills_logits(
        family, monkeypatch):
    """A request whose first 32 tokens (4 blocks) come from the prefix pool
    prefills its 8-token tail alone, at offset 32 (decompressing the shared
    rows), and serves the logits of the same request prefilled whole, and
    the reference's."""
    cfg = config()
    params = seeded_params(cfg)
    other = PROMPT[:32] + RNG.integers(1, 380, 8).tolist()
    r = runner_for(cfg, params)
    seen = tap(r)
    served_logits(r, seen, 0, PROMPT, steps=1)
    r.release(0)
    shared, tokens = served_logits(r, seen, 2, other, steps=6)
    assert r.last_prefill_path == "paged_shared"
    assert r.last_prefix_reused == 32
    assert r.admit_programs == (1 + 3) + (1 + 1)    # one chunk: the tail
    fresh = runner_for(cfg, params)
    whole, again = served_logits(fresh, tap(fresh), 2, other, steps=6)
    assert fresh.last_prefix_reused == 0 and again == tokens
    np.testing.assert_allclose(shared, whole, atol=F32_TOL)
    agree(shared, reference_logits(family, params, HF, other, tokens,
                                   monkeypatch), F32_TOL)


def test_the_prompt_cache_exports_and_imports_latent_rows(family,
                                                          monkeypatch):
    """``export_prefix`` hands out a slot's first rows as ``c [L, n, 40]``
    (the real lanes, layout-independent data), ``load_prefix`` lays them
    into another runner's pool, and the request that resumes behind them
    serves the whole prefill's logits; a block spills to the host and comes
    back as it was."""
    cfg = config()
    params = seeded_params(cfg)
    a = runner_for(cfg, params)
    whole, tokens = served_logits(a, tap(a), 0, PROMPT, steps=3)
    arrays = a.export_prefix(0, 32)
    assert set(arrays) == {"kv_dtype", "kv_rope", "c"}
    assert arrays["c"].shape == (3, 32, 40)
    b = runner_for(cfg, params)
    assert b.load_prefix(1, arrays, 32)
    resumed, again = served_logits(b, tap(b), 1, PROMPT, steps=3,
                                   resident=PROMPT[:32])
    assert b.last_prefill_path == "paged_resume"
    assert b.last_prefix_reused == 32 and again == tokens
    np.testing.assert_allclose(resumed, whole, atol=F32_TOL)
    # not this pool's rows: another width, K/V a head
    assert not b.load_prefix(2, {**arrays, "c": arrays["c"][..., :39]}, 32)
    assert not b.load_prefix(2, {k: v for k, v in arrays.items()
                                 if k != "c"}, 32)
    bid = a.allocator.tables[0][1]
    packed = a.pack_block(bid)
    assert packed["c"].shape == (3, 8, 128)
    before = np.asarray(a.kv.c[:, bid])
    a.load_block(bid, {"c": np.zeros_like(packed["c"])})
    assert not np.asarray(a.kv.c[:, bid]).any()
    a.load_block(bid, packed)
    np.testing.assert_array_equal(np.asarray(a.kv.c[:, bid]), before)


@pytest.mark.parametrize("program", ["decode_n", "frozen_n"])
def test_the_latent_layout_serves_the_familys_other_decode_programs(program):
    """The runner's ONE family of programs over the third layout: four steps
    in one dispatch (``decode_n``), and with one slot frozen behind its
    first (``frozen_n``), emit what single steps emit; the allocator's
    invariants hold."""
    cfg = config()
    params = seeded_params(cfg)
    short = PROMPT[:5]
    seeded = {"temperature": 0.8, "top_k": 40, "seed": 11}

    def admit(r):
        return [r.admit(0, PROMPT, temperature=0.0)], [r.admit(1, short,
                                                               **seeded)]

    one = runner_for(cfg, params)
    want_a, want_b = admit(one)
    for _ in range(8):
        toks = one.step()
        want_a.append(int(toks[0]))
        want_b.append(int(toks[1]))
    r = runner_for(cfg, params)
    got_a, got_b = admit(r)
    freeze = np.zeros(r.num_slots, bool)
    freeze[1] = True
    for _ in range(2):
        if program == "decode_n":
            toks = r.step_n(4)
            got_b += [int(t) for t in toks[:, 1]]
        else:
            toks = r.step_frozen_n(freeze, 4)
            got_b.append(int(toks[0, 1]))
        got_a += [int(t) for t in toks[:, 0]]
    assert got_a == want_a and got_b == want_b[:len(got_b)]
    assert not r.allocator.check_invariants()


def test_the_embeddings_path_pools_the_reference_hidden_state(family):
    """``/v1/embeddings``' mean-pooled final hidden state, through a
    throwaway latent pool and the chunk's decompressed attend, against the
    reference's walk."""
    cfg = config()
    params = seeded_params(cfg)
    r = runner_for(cfg, params)
    got = r.embed(PROMPT[:20])
    tokens = np.array([PROMPT[:20]], np.int32)
    embed, layer, _ = refcheck.programs(family, HF, 20)
    with jax.default_matmul_precision("highest"):
        x = family.walk(
            embed(params, jnp.asarray(tokens)),
            lambda x, i: layer(x, params["layers"], jnp.int32(i)), 2,
            lambda name: params[name].astype(jnp.float32), HF)
        want = family.rms_norm(x[0], params["final_norm"], 1e-6).mean(0)
    np.testing.assert_allclose(got, np.asarray(want), atol=F32_TOL)


# ---------------------------------------------------------------------------
# (e) what is built, what is refused


def test_the_stack_is_a_dense_prefix_and_expert_layers():
    cfg = config(**DEEP)
    assert isinstance(cfg, ds.DeepseekConfig) and cfg.family == "deepseek"
    assert cfg.latent and cfg.routed and cfg.attn_kinds is None
    assert (cfg.hd, cfg.rotary_dim, cfg.latent_width) == (24, 8, 40)
    assert (cfg.expert_layers, cfg.router_width) == (3, 16)
    assert abs(cfg.softmax_mscale - (0.1 * np.log(4) + 1)) < 1e-12
    assert abs(cfg.softmax_scale - 24 ** -0.5 * cfg.softmax_mscale ** 2) < 1e-12
    # the tables carry m(mscale) / m(mscale_all_dim) = 1, not YaRN's factor
    assert cfg.rope_scaling["attention_factor"] == 1.0
    cos, sin = mdl.rope_table(cfg, 8)
    assert cos.shape == (8, 4) and float(cos[0, 0]) == 1.0
    shapes = mdl.param_shapes(cfg)
    dense = {n: s for n, s in shapes.items() if n.startswith("dense_")}
    assert {s[0] for s in dense.values()} == {2}
    assert dense["dense_w_gate"] == (2, 64, 96)
    assert dense["dense_wkv_a"] == (2, 64, 40)
    lay = shapes["layers"]
    assert lay["w_gate"] == (3, 1, 8, 64, 32)            # the HELD experts
    assert lay["moe_gate"] == (3, 64, 16)                # the FULL router
    assert lay["wq_b"] == (3, 24, 4 * 24) and lay["wkv_b"] == (3, 32, 4 * 32)
    assert lay["wo"] == (3, 4 * 16, 64)
    assert "expert_bias" not in lay                      # topk_method none


@pytest.mark.parametrize("changed, says", [
    ({"topk_method": "noaux_tc"}, "topk_method"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"moe_layer_freq": 2}, "moe_layer_freq"),
    ({"attention_bias": True}, "attention_bias"),
    ({"q_lora_rank": None}, "q_lora_rank"),
    ({"n_group": 3}, "do not split into"),
    ({"topk_group": 5}, "do not split into"),
    ({"first_k_dense_replace": 3}, "leaves no expert layer"),
    ({"expert_parallel": {"size": 2, "rank": 2}}, "outside size"),
])
def test_a_config_the_family_cannot_hold_is_refused(changed, says):
    with pytest.raises(ValueError, match=says):
        config(**changed)


SENTENCE = "is not served for model_type axk1: its latent attention reads"


@pytest.fixture(scope="module")
def four_layers():
    """An even number of layers, for the pipe: the configuration and its
    ``init_params``, drawn once for a runner that refuses them unread."""
    cfg = config(num_hidden_layers=4)
    return cfg, mdl.init_params(jax.random.key(0), cfg)


@pytest.mark.parametrize("what, kw", [
    ("the contiguous K/V layout", {"paged": False}),
    ("a int8 K/V pool", {"kv_dtype": "int8"}),
    ("a int4 K/V pool", {"kv_dtype": "int4"}),
    ("self-extend", {"ga_n": 2, "ga_w": 8}),
    ("a device mesh", {"mesh": {"model": 2}}),
    ("the ring prefill", {"mesh": {"seq": 2}}),
    ("pipeline parallelism", {"mesh": {"pipe": 2}}),
])
def test_what_latent_rows_cannot_be_served_through_is_refused(four_layers,
                                                              what, kw):
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    if "mesh" in kw:
        kw["mesh"] = build_mesh(MeshPlan(**kw["mesh"]),
                                devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=f"^{what} {SENTENCE}"):
        runner_for(*four_layers, **kw)


def test_speculation_and_quantised_weights_are_refused():
    cfg = config()
    r = runner_for(cfg, mdl.init_params(jax.random.key(0), cfg))
    with pytest.raises(ValueError, match=f"^speculative decoding {SENTENCE}"):
        r.verify_async(np.zeros((4, 2), np.int32))
    with pytest.raises(ValueError,
                       match=f"^engine.quantization 'int8' {SENTENCE}"):
        synthetic_params(cfg, "int8")
    with pytest.raises(ValueError, match="a forward with no latent attend"):
        ds.forward(cfg, r.params, None, jnp.zeros((1, 1), jnp.int32), None,
                   None, None, r.rope, valid=None)


def test_the_selectors_name_the_latent_layout():
    """A head size off 128 lanes is refused for the K/V-a-head pool by a
    sentence that names the latent layout; the latent selector gates on the
    block size and the pool's dtype alone."""
    with pytest.raises(ValueError, match="LATENT layout's: one 576-element"):
        ops.select_paged_attn_impl(
            "auto", num_heads=64, num_kv_heads=1, head_dim=576,
            block_tokens=64, backend="tpu")
    assert ops.select_latent_attn_impl(
        "auto", block_tokens=64, backend="tpu") == ("pallas", False)
    assert ops.select_latent_attn_impl(
        "auto", block_tokens=64, backend="cpu") == ("xla", False)
    with pytest.raises(ValueError, match="block_tokens % 32"):
        ops.select_latent_attn_impl("auto", block_tokens=16, backend="tpu")
    with pytest.raises(ValueError, match="int8 latent pool is not served"):
        ops.select_latent_attn_impl("pallas_interpret", block_tokens=64,
                                    kv_dtype="int8")
    assert att.latent_lanes(576) == 640 and att.latent_lanes(512) == 512
    assert att.latent_decode_tiling(64, 640, 2, 544) == (16, 2, 2621440)


def test_the_synthetic_gains_are_a_checkpoints_kind_of_draw():
    """``init_leaf``: the two low-rank norms' gains over 1, a seeded 1 in
    192 of the channels of the norms in front of the attention projections,
    of the final norm AND of the cached latent's norm at ``OUTLIER_GAIN``
    (none under 192 channels), the norm in front of the router and the
    experts at 1: what the benchmark's reference check needs to tell a lower
    precision, of an activation or of a cached row, apart."""
    cfg = config("bfloat16", hidden_size=384, kv_lora_rank=192,
                 q_lora_rank=192)
    params = mdl.init_params(jax.random.key(0), cfg)
    lay = params["layers"]
    for gains in (lay["attn_norm"], params["dense_attn_norm"],
                  params["final_norm"]):
        g = np.asarray(gains, np.float32).reshape(-1, 384)
        assert ((g == ds.OUTLIER_GAIN).sum(-1) == 2).all()
        assert ((g == 1).sum(-1) == 382).all()
    for gains in (lay["kv_norm"], params["dense_kv_norm"]):
        g = np.asarray(gains, np.float32).reshape(-1, 192)
        assert ((g == ds.OUTLIER_GAIN).sum(-1) == 1).all()
        assert ((g == ds.LATENT_NORM_GAIN).sum(-1) == 191).all()
    assert (np.asarray(lay["mlp_norm"], np.float32) == 1).all()
    assert (np.asarray(lay["q_norm"], np.float32)
            == ds.LATENT_NORM_GAIN).all()
    small = mdl.init_params(jax.random.key(0), config())
    assert (np.asarray(small["final_norm"]) == 1).all()
    assert (np.asarray(small["layers"]["kv_norm"])
            == ds.LATENT_NORM_GAIN).all()


def test_the_configuration_file_is_the_published_row_cut_as_stated(family):
    """benchmark/configs/axk1-ep16.json through the program's one door: the
    published widths, the share, and the family's count of what the program
    builds from it: 4,841,331,712 parameters."""
    doc = json.loads((ROOT / "benchmark" / "configs"
                      / "axk1-ep16.json").read_text())
    hf = {k: v for k, v in doc.items() if k not in spec.CONFIG_KEYS}
    cfg = LlamaConfig.from_hf(hf)
    assert isinstance(cfg, ds.DeepseekConfig)
    assert (cfg.hidden_size, cfg.num_heads, cfg.q_lora_rank, cfg.kv_lora_rank,
            cfg.hd, cfg.v_head_dim, cfg.latent_width) == (
                7168, 64, 1536, 512, 192, 128, 576)
    assert (cfg.num_layers, cfg.num_dense_layers, cfg.num_experts,
            cfg.router_width, cfg.n_group, cfg.topk_group,
            cfg.num_experts_per_tok, cfg.route_scale) == (
                7, 1, 12, 192, 8, 4, 8, 2.5)
    assert abs(cfg.softmax_mscale - 1.3466) < 1e-4
    shapes = mdl.param_shapes(cfg)
    built = sum(int(np.prod(s)) for s in jax.tree.leaves(
        shapes, is_leaf=lambda x: isinstance(x, tuple)))
    assert built == family.param_count(hf) == 4_841_331_712


# ---------------------------------------------------------------------------
# (f) the scopes; the scheduler's counts


def test_the_programs_name_the_two_paths_and_the_expert_scopes():
    cfg = config("bfloat16", moe_intermediate_size=128, hidden_size=128)
    r = runner_for(cfg, mdl.init_params(jax.random.key(0), cfg),
                   "pallas_interpret", kv_block_tokens=32, max_ctx=128,
                   prefill_chunk=32, prefill_buckets=[32])
    text = lowered_texts(r, ("decode", "prefill_1"), debug_info=True)
    for scope in ("mla/q", "mla/kv_a", "mla/o",
                  "attn.latent_decode/latent_decode_attn", "moe/router",
                  "moe/experts/moe_experts", "moe/shared", "dense_mlp"):
        assert scope in text["decode"], scope
    assert "attn.latent_chunk" not in text["decode"]
    assert "mla/kv_b" not in text["decode"]     # never decompressed
    for scope in ("mla/q", "mla/kv_a", "mla/o", "attn.latent_chunk/",
                  # the decompression inside the walk (harness/trace_reduce
                  # drops ``while`` / ``body`` from a scope path)
                  "attn.latent_chunk/while/body/mla/kv_b/dot_general",
                  "attn.latent_chunk/while/body/kv_pool.gather",
                  "kv_pool.write", "moe/experts/moe_experts"):
        assert scope in text["prefill_1"], scope
    assert "attn.latent_decode" not in text["prefill_1"]


def test_the_flight_ring_and_the_counter_count_both_paths():
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
            assert x["live_slots"] == 1 and x["window_tokens"] == 0
            assert x["attended_tokens"] > 40 * x["steps"]
            assert 0 < x["experts_touched"] <= x["local_assignments"] <= (
                x["steps"] * 2 * 3)
        chunks = [x for x in rows if x["program"] == "prefill_chunk"]
        assert [x["chunk_offset"] for x in chunks] == [0, 16, 32]
        # the span a chunk's walk covered: whole steps of 1024 rows
        assert {x["chunk_ctx"] for x in chunks} == {1024}
        m = s.metrics()
        assert m["mla_attends"]["decompressed"] == 3
        # counted at the ENQUEUE: a launch in flight has no ring row yet
        assert len(decode) <= m["mla_attends"]["absorbed"] <= len(decode) + 2
        assert m["moe_assignments"] > 0
        obs_metrics.update_engine_gauges("k1", m)
        text = obs_metrics.REGISTRY.render()
        assert 'localai_mla_attend_total{model="k1",path="absorbed"} ' in text
        assert ('localai_mla_attend_total{model="k1",path="decompressed"} 3'
                in text)
    finally:
        s.shutdown()
