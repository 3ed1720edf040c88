"""AFMoE (``model_type: afmoe``) on the normal serving path: window layers
(RoPE) and full layers (no positional encoding) in one stack, dense layers in
front of sigmoid-routed experts with a shared expert, four norms a layer.
CPU, tiny widths, seeded random weights: D 64, 4 query heads of 16 over 2 kv
heads, window 8, 1 dense layer + 1 row of 4 expert layers (kinds S | S S F S:
the published rule, and the cell's cut), 8 of 16 experts held
(``expert_parallel`` size 2, rank 1), top-2; one case at 2 dense layers + 2
rows. Contexts run to 48, so every window is crossed several times, by chunks
and by decode steps.

The served path is the runner's own programs (``_prefill_paged_fn`` /
``_decode_paged_fn``), driven by ``admit`` and ``step`` and tapped for the
logits they sample from; the reference is the benchmark's plain float32
family (benchmark/reference/afmoe_family.py, written from the published
description) run as the benchmark runs it (harness/refcheck.py): the FULL
forward over prompt + served tokens, no cache.
"""

import dataclasses
import functools
import time

import families
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from families import agree, reference_logits, served_logits, tap

from localai_tpu.engine import kvcache as kvc
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import afmoe as af
from localai_tpu.models import experts as xp
from localai_tpu.models import llama as mdl
from localai_tpu.models.llama import LlamaConfig
from localai_tpu.models.registry import synthetic_params

S, F = af.WINDOW, af.FULL
LAYERS = 5
HF = {"model_type": "afmoe", "vocab_size": 384, "hidden_size": 64,
      "intermediate_size": 96, "num_hidden_layers": LAYERS,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "rope_theta": 10000, "rms_norm_eps": 1e-5,
      "max_position_embeddings": 512, "tie_word_embeddings": False,
      "sliding_window": 8, "global_attn_every_n_layers": 4,
      "layer_types": [F if (i + 1) % 4 == 0 else S for i in range(LAYERS)],
      "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
      "moe_intermediate_size": 32, "num_shared_experts": 1,
      "score_func": "sigmoid", "route_norm": True, "route_scale": 2.448,
      "mup_enabled": True, "n_group": 1, "topk_group": 1,
      "num_expert_groups": 1, "num_limited_groups": 1,
      "expert_parallel": {"size": 2, "rank": 1}}
RNG = np.random.default_rng(44)
PROMPT = RNG.integers(1, 380, 37).tolist()      # three chunks: 16 + 16 + 5
STEPS = 10                                      # contexts 37 .. 47
# float32 serving: what is left between the two is summation order
F32_TOL = 2e-5
# bfloat16 serving, logits up to ~3: every activation is rounded to 8 bits
# some thirty times in a row through 5 layers of two normed branches, K/V is
# kept in bfloat16 and the logits are written in bfloat16 (half an ulp at 2-4
# is 0.008). A rounded router also flips near-ties between experts (top-2 of
# 16), which the float32 reference does not follow: the worst of 11 x 384
# logits then moves by a whole expert's weight (0.1-0.5 over three seeds)
# while the mean stays at 0.02-0.05, so the case is held by its mean, and
# its worst logit to a bound a dropped term breaks (the mutations below move
# the float32 logits by 0.05 to 2).
BF16_MEAN_TOL, BF16_TOL = 0.1, 1.0


@pytest.fixture(scope="module")
def family():
    return families.reference_family("afmoe_family", "tests/test_afmoe.py")


# 2 dense layers (S S) and 2 rows (S F S S): more than one of each
DEEP = {"num_hidden_layers": 10, "num_dense_layers": 2,
        "layer_types": [F if (i + 1) % 4 == 0 else S for i in range(10)]}


config = functools.partial(families.config, HF)


def seeded_params(cfg, seed: int = 0):
    """The program's seeded weights with every norm gain redrawn at 1 + 0.3 N
    (at 1, swapping two norms would change nothing), the selection bias at
    0.3 N (it must MOVE choices: the scores spread over ~0.2-0.8) and the
    matrices three times as large, so that every branch weighs on the
    logits."""
    rng = np.random.default_rng(seed + 1)

    def redraw(name, a):
        if name.endswith("norm"):
            return families.gain(rng, a)
        if name == "expert_bias":
            return families.gain(rng, a, centre=0.0)
        return families.tripled(a)

    return families.redrawn(mdl.init_params(jax.random.key(seed), cfg),
                            redraw)


def runner_for(cfg, params, impl="xla", **kw) -> ModelRunner:
    """Under ``pallas_interpret`` fewer slots and larger blocks: a kernel
    instance in the interpreter compiles for seconds on the CPU, by slots
    and table entries (a window of 8 still skips whole blocks of 16)."""
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
    """A prompt over three chunks (the last with padded rows; the second and
    third lie wholly behind the first's window), then decode steps: the
    logits each program samples from against the full forward. Under
    ``pallas_interpret`` the decode attends are the paged kernel, with the
    window on the window layers, and the experts ops.moe's kernel."""
    hf = {**HF, **(DEEP if deep else {})}
    cfg = config(dtype, **(DEEP if deep else {}))
    params = seeded_params(cfg)
    r = runner_for(cfg, params, impl)
    assert cfg.row_kinds == ((S, F, S, S) if deep else (S, S, F, S))
    assert r.kinds == ((S, 8), (F, None)) and r.routed and not r.recurrent
    assert (r.family_kernels is not None) == (impl == "pallas_interpret")
    served, tokens = served_logits(r, tap(r), 1, PROMPT, STEPS)
    assert r.admit_programs == 1 + 3            # the arming and three chunks
    assert r.kv.k.shape[0] == cfg.cache_layers == (10 if deep else 5)
    assert set(r.state.rec) == {"routed"} and r.state_bytes == 0
    ref = reference_logits(family, params, hf, PROMPT, tokens, monkeypatch)
    if dtype == "float32":
        agree(served, ref, F32_TOL)
        assert (served.argmax(-1) == ref.argmax(-1)).all()
    else:
        assert np.abs(ref).max() > 0.2
        assert np.abs(served - ref).mean() < BF16_MEAN_TOL
        assert np.abs(served - ref).max() < BF16_TOL


def test_a_window_longer_than_the_ladders_first_rung(family, monkeypatch):
    """At the cell's shape of things a window layer's chunk takes a rung of
    the ladder while its prefix is short and the window's own branch past
    it: blocks of 64, a window of 576 (``window_span`` 704 for a 64-token
    chunk, between the rungs 512 and 1024), a prompt of 1500 tokens in 24
    chunks. 1 dense layer and 1 row."""
    kinds = [S, S, F, S, S]
    hf = {**HF, "num_hidden_layers": 5, "num_dense_layers": 1,
          "layer_types": kinds, "sliding_window": 576,
          "max_position_embeddings": 4096}
    cfg = dataclasses.replace(LlamaConfig.from_hf(hf), dtype="float32")
    assert kvc.window_span(576, 64, 64) == 704
    assert kvc.span_ladder(64, 2048, 64) == (512, 1024, 2048)
    params = seeded_params(cfg)
    r = runner_for(cfg, params, max_ctx=2048, kv_block_tokens=64,
                   prefill_chunk=64, prefill_buckets=[64], num_slots=2)
    prompt = RNG.integers(1, 380, 1500).tolist()
    served, tokens = served_logits(r, tap(r), 0, prompt, steps=3)
    agree(served, reference_logits(family, params, hf, prompt, tokens,
                                   monkeypatch), 5e-5)


@pytest.mark.parametrize("bucket", [128, 512])
@pytest.mark.parametrize("offset", [0, 448, 3968, 4096, 4100, 9000, 16384,
                                    17920])
def test_the_window_attend_at_the_cells_geometry_is_the_masked_full_attend(
        offset, bucket):
    """``kvcache.window_attend`` at the cell's geometry (window 4096, blocks
    of 64, 18432 positions: the rungs 512 .. 4096 and then the window's own
    branch of 4288 / 4672 positions) against the attend over the WHOLE table
    row under the same window mask, on a pool full of noise: every row of the
    chunk, float32. Offsets on both sides of every boundary: inside the first
    rung, at the window, a start inside a block, the cell's 16384, and a
    chunk that ends at the context's end (the slice of the table row
    clamped)."""
    from localai_tpu.engine.kvcache import LayerView

    W, bt, ctx_pad, hd = 4096, 64, 18432, 16
    if offset + bucket > ctx_pad:
        pytest.skip("past the context")
    rng = np.random.default_rng(offset + bucket)
    blocks = ctx_pad // bt
    pool = tuple(jnp.asarray(rng.standard_normal((1, blocks + 1, 1, bt, hd)),
                             jnp.float32) for _ in range(2))
    table = jnp.asarray(1 + rng.permutation(blocks), jnp.int32)
    q = jnp.asarray(rng.standard_normal((1, bucket, 2, hd)), jnp.float32)
    view = kvc.KindView(hd, W)
    mask = kvc.resume_mask(view, bucket, jnp.int32(offset), ctx_pad)
    keys, values = (LayerView(c, jnp.int32(0), None, None) for c in pool)
    attend = jax.jit(lambda off: kvc.window_attend(view, table, off, ctx_pad)(
        q, keys, values, kvc.resume_mask(view, bucket, off, ctx_pad)))
    got = attend(jnp.int32(offset))
    k, v = kvc._gather_context(pool, jnp.int32(0), table[None], q)
    want = mdl._grouped_attn(view, q, k, v, mask)
    assert kvc.window_span(W, bucket, bt) == (4672 if bucket == 512
                                              else 4288)
    np.testing.assert_allclose(got, want, atol=2e-6)


# ---------------------------------------------------------------------------
# (b) mathematics left out, or put where it does not belong, fails (a)


def window_ignored(monkeypatch):
    monkeypatch.setattr(af.AfmoeConfig, "attn_kinds", property(
        lambda self: ((S, None), (F, None))))
    return {}


def rope_on_the_full_layers(monkeypatch):
    monkeypatch.setattr(af, "rope_on", lambda kind: True)
    return {}


def bias_in_the_weight(monkeypatch):
    def scores(cfg, bias):
        def score(logits):
            s = jax.nn.sigmoid(logits) + bias.astype(jnp.float32)
            top, chosen = jax.lax.top_k(s, cfg.num_experts_per_tok)
            top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
            return top * cfg.route_scale, chosen
        return score

    monkeypatch.setattr(af, "scores", scores)
    return {}


def bias_left_out_of_the_selection(monkeypatch):
    monkeypatch.setattr(af, "scores", lambda cfg, bias: xp.sigmoid_scores(
        cfg.num_experts_per_tok, jnp.zeros_like(bias), cfg.route_norm,
        cfg.route_scale))
    return {}


def route_scale_dropped(monkeypatch):
    return {"route_scale": 1.0}


def shared_expert_gated(monkeypatch):
    def gated(h, w_gate, w_up, w_down):
        gate = jax.nn.sigmoid(jnp.sum(h.astype(jnp.float32), -1,
                                      keepdims=True))
        return gate * xp.swiglu(h, w_gate, w_up, w_down).astype(jnp.float32)

    monkeypatch.setattr(xp, "shared_expert", gated)
    return {}


def embed_scale_dropped(monkeypatch):
    return {"mup_enabled": False}


def post_norms_dropped(monkeypatch):
    monkeypatch.setattr(af, "post_norm", lambda x, w, eps: x)
    return {}


MUTATIONS = [window_ignored, rope_on_the_full_layers, bias_in_the_weight,
             bias_left_out_of_the_selection, route_scale_dropped,
             shared_expert_gated, embed_scale_dropped, post_norms_dropped]


@pytest.mark.parametrize("mutate, impl", [
    *((m, "xla") for m in MUTATIONS), (window_ignored, "pallas_interpret")],
    ids=lambda x: getattr(x, "__name__", x))
def test_a_program_that_departs_from_the_equations_fails(
        family, monkeypatch, mutate, impl):
    """Each departure, in the PROGRAM alone (the reference keeps the
    published keys), moves the float32 logits by at least 100 x the
    tolerance of (a). Under the kernels the one case that is other code
    there: the window, the paged kernel's own bound."""
    cfg = config(**mutate(monkeypatch))
    params = seeded_params(config())
    r = runner_for(cfg, params, impl)
    served, tokens = served_logits(r, tap(r), 1, PROMPT, steps=4)
    ref = reference_logits(family, params, HF, PROMPT, tokens, monkeypatch)
    assert np.abs(served - ref).max() > 100 * F32_TOL


# ---------------------------------------------------------------------------
# (c) the share: the ranks' parts add up to the uncut layer


def test_the_eight_shares_add_up_to_the_uncut_layer(family):
    """One expert block, 16 experts top-2, cut over 8 ranks of 2: the routed
    parts the ranks give (each its block's output less the shared expert,
    which every rank computes alike and is counted once) add up to the uncut
    REFERENCE's layer; every token-expert pair lands on exactly one rank."""
    size, E = 8, 16
    whole_hf = {**HF, "num_experts": E, "expert_parallel": None}
    whole = config(num_experts=E, expert_parallel=None)
    params = seeded_params(whole, seed=3)
    lay = params["layers"]
    h = jnp.asarray(RNG.standard_normal((6, 64)), jnp.float32)
    valid = jnp.ones(6, bool)
    at = (0, 2)                                 # the row's third layer
    w = {n: np.asarray(a[at], np.float32) for n, a in lay.items()}
    with jax.default_matmul_precision("highest"):
        want = np.asarray(family.experts(h, w, whole_hf))
        shared = np.asarray(xp.shared_expert(
            h, *(lay[n][at] for n in ("shared_gate", "shared_up",
                                      "shared_down"))))
        total, pairs = shared.copy(), 0
        for rank in range(size):
            cut = config(num_experts=E // size,
                         expert_parallel={"size": size, "rank": rank})
            held = tuple(lay[n][:, :, rank * 2:(rank + 1) * 2]
                         for n in xp.EXPERT_LEAVES)
            out, n_touched, load = xp.moe_block(
                h, lay["moe_gate"][at], af.scores(cut, lay["expert_bias"][at]),
                held, jnp.int32(at[0]), at[1], num_experts=cut.num_experts,
                ep_rank=rank, valid=valid,
                shared=lambda h: xp.shared_expert(h, *(
                    lay[n][at] for n in ("shared_gate", "shared_up",
                                         "shared_down"))))
            total += np.asarray(out) - shared
            pairs += int(xp.counts(n_touched, load)[1])
    assert pairs == 6 * 2
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(total, want, atol=2e-5)


# ---------------------------------------------------------------------------
# (d) the prefix pool: shared blocks longer than the window


def test_a_prefix_from_the_pool_gives_the_whole_prefills_logits(
        family, monkeypatch, impl="xla"):
    """A request whose first 32 tokens (4 blocks, four windows) come from the
    prefix pool prefills its 8-token tail alone, at offset 32, and serves the
    logits of the same request prefilled whole, and the reference's."""
    cfg = config()
    params = seeded_params(cfg)
    other = PROMPT[:32] + RNG.integers(1, 380, 8).tolist()
    r = runner_for(cfg, params, impl)
    seen = tap(r)
    served_logits(r, seen, 0, PROMPT, steps=1)
    r.release(0)
    shared, tokens = served_logits(r, seen, 2, other, steps=6)
    assert r.last_prefill_path == "paged_shared"
    assert r.last_prefix_reused == 32 > 3 * cfg.sliding_window
    assert r.admit_programs == (1 + 3) + (1 + 1)    # one chunk: the tail
    fresh = runner_for(cfg, params, impl)
    whole, again = served_logits(fresh, tap(fresh), 2, other, steps=6)
    assert fresh.last_prefix_reused == 0 and again == tokens
    np.testing.assert_allclose(shared, whole, atol=F32_TOL)
    agree(shared, reference_logits(family, params, HF, other, tokens,
                                   monkeypatch), F32_TOL)


# ---------------------------------------------------------------------------
# (e) what is built, what is refused


def test_the_stack_is_a_dense_prefix_and_rows():
    cfg = config(**DEEP)
    assert isinstance(cfg, af.AfmoeConfig) and cfg.family == "afmoe"
    assert (cfg.rows, cfg.row_kinds, cfg.router_width) == (
        2, (S, F, S, S), 16)
    shapes = mdl.param_shapes(cfg)
    assert {s[:2] for s in shapes["layers"].values()} == {(2, 4)}
    dense = {n: s for n, s in shapes.items() if n.startswith("dense_")}
    assert {s[0] for s in dense.values()} == {2}
    assert dense["dense_w_gate"] == (2, 64, 96)
    assert shapes["layers"]["w_gate"] == (2, 4, 8, 64, 32)   # the HELD
    assert shapes["layers"]["moe_gate"] == (2, 4, 64, 16)    # the FULL router
    assert shapes["layers"]["expert_bias"] == (2, 4, 16)
    params = mdl.init_params(jax.random.key(0), cfg)
    bias = params["layers"]["expert_bias"]
    assert bias.dtype == jnp.float32 and float(jnp.abs(bias).min()) > 0
    assert params["layers"]["wq"].dtype == jnp.dtype(cfg.dtype)


@pytest.mark.parametrize("changed, says", [
    ({"n_group": 2}, "expert groups"),
    ({"score_func": "softmax"}, "score_func"),
    ({"num_hidden_layers": 4, "layer_types": HF["layer_types"][:4]},
     "whole rows"),
    ({**DEEP, "layer_types": [S] * 6 + [F, S, S, S]},
     "differ in their layer kinds"),
    ({"layer_types": HF["layer_types"][:4]}, "layer_types names 4"),
    ({"sliding_window": None}, "no sliding_window"),
])
def test_a_config_the_stack_cannot_hold_is_refused(changed, says):
    with pytest.raises(ValueError, match=says):
        config(**changed)


def test_a_checkpoint_in_the_published_layout_loads_to_the_served_leaves(
        tmp_path):
    """``models/loader.py`` for the family: a checkpoint written HERE in the
    published layout (tensor names of ``modeling_afmoe.py`` from memory,
    linear weights [out, in], every one of the 16 experts, the selection bias
    in float32) loads to the served leaves it was made from, 2 dense layers
    and 2 rows of them, the bias float32 under a bfloat16 load too; a rank of
    two loads its 8 experts of each layer and the whole router and bias."""
    from safetensors.numpy import save_file

    from localai_tpu.models.loader import load_llama_params

    whole_hf = {**HF, **DEEP, "num_experts": 16, "expert_parallel": None}
    whole = config(**DEEP, num_experts=16, expert_parallel=None)
    params = jax.tree.map(np.asarray, seeded_params(whole, seed=5))
    names = {"attn_norm": "input_layernorm", "mlp_norm": "pre_mlp_layernorm",
             "attn_post_norm": "post_attention_layernorm",
             "mlp_post_norm": "post_mlp_layernorm",
             "q_norm": "self_attn.q_norm", "k_norm": "self_attn.k_norm",
             "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
             "wv": "self_attn.v_proj", "wg": "self_attn.gate_proj",
             "wo": "self_attn.o_proj"}
    mlp = {"w_gate": "gate_proj", "w_up": "up_proj", "w_down": "down_proj"}
    out = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["final_norm"],
           "lm_head.weight": params["lm_head"].T}

    def put(name, a):
        out[name] = a.T if a.ndim == 2 else a

    for i in range(10):
        pre = f"model.layers.{i}."
        if i < 2:
            leaf = lambda n, i=i: params["dense_" + n][i]    # noqa: E731
            for ours, theirs in mlp.items():
                put(pre + f"mlp.{theirs}.weight", leaf(ours))
        else:
            at = divmod(i - 2, 4)
            leaf = lambda n, at=at: params["layers"][n][at]  # noqa: E731
            put(pre + "mlp.router.gate.weight", leaf("moe_gate"))
            out[pre + "mlp.expert_bias"] = leaf("expert_bias")
            for ours, theirs in mlp.items():
                put(pre + f"mlp.shared_experts.{theirs}.weight",
                    leaf("shared_" + ours[2:]))
                for e in range(16):
                    put(pre + f"mlp.experts.{e}.{theirs}.weight",
                        leaf(ours)[e])
        for ours, theirs in names.items():
            put(pre + theirs + ".weight", leaf(ours))
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()},
              str(tmp_path / "model.safetensors"))
    cfg, loaded = load_llama_params(tmp_path, dtype="float32", hf=whole_hf)
    assert cfg == dataclasses.replace(whole, dtype=cfg.dtype)
    jax.tree.map(np.testing.assert_array_equal, params,
                 jax.tree.map(np.asarray, loaded))
    cut, held = load_llama_params(
        tmp_path, dtype="bfloat16",
        hf={**whole_hf, "num_experts": 8,
            "expert_parallel": {"size": 2, "rank": 1}})
    assert (cut.num_experts, cut.router_width, cut.ep_rank) == (8, 16, 1)
    lay = held["layers"]
    assert lay["expert_bias"].dtype == jnp.float32
    assert lay["w_gate"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(lay["expert_bias"],
                                  params["layers"]["expert_bias"])
    np.testing.assert_array_equal(
        np.asarray(lay["w_up"], np.float32),
        np.asarray(jnp.asarray(params["layers"]["w_up"][:, :, 8:],
                               jnp.bfloat16), np.float32))
    assert lay["moe_gate"].shape == (2, 4, 64, 16)
    with pytest.raises(ValueError, match="quantization"):
        load_llama_params(tmp_path, hf=whole_hf, quantization="int8")


@pytest.fixture(scope="module")
def deep():
    """An even number of layers, for the pipe: the configuration and its
    ``init_params``, drawn once for a runner that refuses them unread."""
    cfg = config(**DEEP)
    return cfg, mdl.init_params(jax.random.key(0), cfg)


@pytest.mark.parametrize("what, kw", [
    ("the contiguous K/V layout", {"paged": False}),
    ("a int8 K/V pool", {"kv_dtype": "int8"}),
    ("self-extend", {"ga_n": 2, "ga_w": 8}),
    ("a device mesh", {"mesh": {"model": 2}}),
    ("pipeline parallelism", {"mesh": {"pipe": 2}}),
])
def test_what_the_kinds_cannot_be_served_through_is_refused(deep, what, kw):
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    if "mesh" in kw:
        kw["mesh"] = build_mesh(MeshPlan(**kw["mesh"]),
                                devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=f"^{what} is not served for "
                                         f"model_type afmoe"):
        runner_for(*deep, **kw)


def test_the_synthetic_gains_are_a_checkpoints_kind_of_draw():
    """``init_leaf``: q/k norm gains over 1, a seeded 1 in 192 of the
    channels of the norms in front of the attention projections and of the
    final norm at ``OUTLIER_GAIN`` (another set a layer; none under 192
    channels), the norm in front of the router and every output norm at 1,
    the selection bias float32, small and not zero: what the benchmark's
    reference check needs to tell a lower precision apart (the
    configuration's ``assumed.weights``)."""
    cfg = config("bfloat16", hidden_size=384, num_attention_heads=4,
                 head_dim=16)
    params = mdl.init_params(jax.random.key(0), cfg)
    lay = params["layers"]
    for gains in (lay["attn_norm"], params["dense_attn_norm"],
                  params["final_norm"]):
        g = np.asarray(gains, np.float32).reshape(-1, 384)
        assert ((g == af.OUTLIER_GAIN).sum(-1) == 2).all()
        assert ((g == 1).sum(-1) == 382).all()
    where = np.asarray(lay["attn_norm"], np.float32).reshape(-1, 384) > 1
    assert len({tuple(np.flatnonzero(w)) for w in where}) > 1
    for name in ("mlp_norm", "attn_post_norm", "mlp_post_norm"):
        assert (np.asarray(lay[name], np.float32) == 1).all()
    for name in ("q_norm", "k_norm"):
        assert (np.asarray(lay[name], np.float32) == af.QK_NORM_GAIN).all()
    bias = np.asarray(lay["expert_bias"])
    assert bias.dtype == np.float32 and 0 < np.abs(bias).max() < 0.05
    # under 192 channels: no outlier
    small = mdl.init_params(jax.random.key(0), config())
    assert (np.asarray(small["final_norm"]) == 1).all()


def test_speculation_and_quantization_are_refused():
    cfg = config()
    r = runner_for(cfg, mdl.init_params(jax.random.key(0), cfg))
    with pytest.raises(ValueError, match="^speculative decoding is not"):
        r.verify_async(np.zeros((4, 2), np.int32))
    with pytest.raises(ValueError, match="engine.quantization 'int8'"):
        synthetic_params(cfg, "int8")


# ---------------------------------------------------------------------------
# (f) the scopes; the scheduler's counts


def test_the_programs_name_the_kinds_attends_and_the_expert_scopes():
    cfg = config("bfloat16", head_dim=128, num_attention_heads=2,
                 num_key_value_heads=1, moe_intermediate_size=128)
    r = runner_for(cfg, mdl.init_params(jax.random.key(0), cfg),
                   "pallas_interpret", kv_block_tokens=32, max_ctx=128,
                   prefill_chunk=32, prefill_buckets=[32])
    decode = jax.jit(r._decode_paged_fn).lower(
        r.params, r.kv, r.state, r.block_tables).as_text(debug_info=True)
    for scope in ("attn.window_decode/paged_decode_attn",
                  "attn.paged_decode/paged_decode_attn", "moe/router",
                  "moe/experts/moe_experts", "moe/shared", "dense_mlp",
                  "attn_gate", "attn.rope"):
        assert scope in decode, scope
    chunk = (jnp.zeros((1, 32), jnp.int32), jnp.int32(5), jnp.int32(0),
             r.block_tables[0], jnp.int32(0),
             jnp.zeros(cfg.vocab_size, jnp.int32))
    prefill = jax.jit(
        r._prefill_paged_fn, static_argnames=("bucket", "sample")).lower(
            r.params, r.kv, r.state, *chunk, bucket=32,
            sample=True).as_text(debug_info=True)
    assert "attn.prefill_window/" in prefill and "attn.prefill/" in prefill


def test_the_flight_ring_counts_window_tokens_and_the_gauge_dead_ones():
    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.obs import metrics as obs_metrics
    from localai_tpu.utils.tokenizer import ByteTokenizer

    cfg = config()
    r = runner_for(cfg, seeded_params(cfg))
    s = Scheduler(r, ByteTokenizer(), multi_step=2)
    try:
        text = "a window of eight and a context of forty"     # 40 + BOS
        h = s.submit(GenRequest(prompt=ByteTokenizer().encode(text),
                                max_new_tokens=14, temperature=0.0,
                                ignore_eos=True))
        deadline = time.monotonic() + 60.0
        dead = 0
        while not h._done.is_set() and time.monotonic() < deadline:
            dead = max(dead, s.metrics().get("kv_window_dead_tokens", 0))
            time.sleep(0.01)
        assert h.completion_tokens == 14
        # a stream of 41 .. 54 tokens: 33 .. 46 lie behind the window of 8,
        # 32 or 40 of them in whole blocks of 8
        assert dead in (32, 40)
        deadline = time.monotonic() + 10.0
        while True:
            decode = [x for x in s.flight.snapshot()
                      if x["program"].startswith("decode")]
            if (sum(x["steps"] for x in decode) >= 13
                    or time.monotonic() > deadline):
                break
            time.sleep(0.05)
        assert decode
        for x in decode:
            assert x["live_slots"] == 1
            assert x["window_tokens"] == 8 * x["steps"]
            assert x["attended_tokens"] > 40 * x["steps"]
            assert 0 < x["experts_touched"] <= x["local_assignments"] <= (
                x["steps"] * 8 * 2)
        m = s.metrics()
        assert m["moe_assignments"] > 0 and "state_slots_armed" not in m
        obs_metrics.update_engine_gauges("af", m)
        assert 'localai_kv_window_dead_tokens{model="af"}' in (
            obs_metrics.REGISTRY.render())
    finally:
        s.shutdown()
