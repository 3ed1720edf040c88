"""SmallThinker (``model_type: smallthinker``) on the normal serving path: a
router that reads the ATTENTION's normed input and is spent a branch later,
ReLU-gated experts with no shared expert, full layers (no positional
encoding) and window layers (RoPE) 1 : 3 in one stack, the full layer FIRST
in a row. CPU, tiny widths, seeded random weights: D 64, 6 query heads of 16
over 2 kv heads (groups of 3: no power of two, as the published 7), window 8,
1 row of 4 layers (F W W W), 8 experts top-3, every one held; one case at 2
rows. Contexts run to 48, so every window is crossed several times, by chunks
and by decode steps.

The served path is the runner's own programs (``_prefill_paged_fn`` /
``_decode_paged_fn``), driven by ``admit`` and ``step`` and tapped for the
logits they sample from; the reference is the benchmark's plain float32
family (benchmark/reference/smallthinker_family.py, written from the
published description) run as the benchmark runs it (harness/refcheck.py):
the FULL forward over prompt + served tokens, no cache.
"""

import dataclasses
import functools
import time
import types

import families
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from families import agree, reference_logits, served_logits, tap

from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import experts as xp
from localai_tpu.models import llama as mdl
from localai_tpu.models import smallthinker as st
from localai_tpu.models.registry import synthetic_params

W, F = st.WINDOW, st.FULL
LAYERS = 4
HF = {"model_type": "smallthinker", "vocab_size": 384, "hidden_size": 64,
      "num_hidden_layers": LAYERS, "num_attention_heads": 6,
      "num_key_value_heads": 2, "head_dim": 16, "rope_theta": 1500000,
      "rope_scaling": None, "rms_norm_eps": 1e-6,
      "max_position_embeddings": 512, "tie_word_embeddings": False,
      "sliding_window_size": 8, "sliding_window_layout": [0, 1, 1, 1],
      "rope_layout": [0, 1, 1, 1], "moe_num_primary_experts": 8,
      "moe_num_active_primary_experts": 3, "moe_ffn_hidden_size": 32,
      "moe_primary_router_apply_softmax": True, "norm_topk_prob": True}
RNG = np.random.default_rng(65)
PROMPT = RNG.integers(1, 380, 37).tolist()      # three chunks: 16 + 16 + 5
STEPS = 10                                      # contexts 37 .. 47
# float32 serving: what is left between the two is summation order (the
# tolerance tests/test_afmoe.py holds)
F32_TOL = 2e-5
# bfloat16 serving, logits up to ~5: every activation is rounded to 8 bits
# some twenty times in a row through 4 layers of two branches, K/V is kept in
# bfloat16 and the logits are written in bfloat16 (half an ulp at 4-8 is
# 0.016). A rounded router also flips near-ties between experts (top-3 of 8),
# which the float32 reference does not follow: the worst of 11 x 384 logits
# then moves by a whole expert's weight while the mean stays small, so the
# case is held by its mean, and its worst logit to a bound a dropped term
# breaks.
BF16_MEAN_TOL, BF16_TOL = 0.1, 1.5


@pytest.fixture(scope="module")
def family():
    return families.reference_family("smallthinker_family",
                                     "tests/test_smallthinker.py")


# 2 rows (F W W W twice): more than one of each
DEEP = {"num_hidden_layers": 8, "sliding_window_layout": [0, 1, 1, 1] * 2,
        "rope_layout": [0, 1, 1, 1] * 2}


config = functools.partial(families.config, HF)


def seeded_params(cfg, seed: int = 0):
    """The program's seeded weights (each matrix at the deviation that makes
    its output of order 1: every branch weighs on the logits) with every
    norm gain redrawn at 1 + 0.3 N (at 1, swapping two norms would change
    nothing) and the router's logits brought to deviation 1 (the program
    draws them at ``ROUTER_STD`` = 4 for the benchmark's check, where the
    first choice weighs nearly all: here every one of the k choices has to
    weigh, and a softmax over logits of +-12 would multiply float32's last
    bits past the tolerance)."""
    rng = np.random.default_rng(seed + 1)

    def redraw(name, a):
        if name.endswith("norm"):
            return families.gain(rng, a)
        if name == "moe_gate":
            return (a.astype(jnp.float32) / st.ROUTER_STD).astype(a.dtype)
        return a

    return families.redrawn(mdl.init_params(jax.random.key(seed), cfg),
                            redraw)


def runner_for(cfg, params, impl="xla", **kw) -> ModelRunner:
    """Under ``pallas_interpret`` fewer slots and larger blocks (a kernel
    instance in the interpreter compiles for seconds on the CPU)."""
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
    window on the window layers, and the experts ops.moe's kernel with the
    ReLU gate."""
    hf = {**HF, **(DEEP if deep else {})}
    cfg = config(dtype, **(DEEP if deep else {}))
    params = seeded_params(cfg)
    r = runner_for(cfg, params, impl)
    assert cfg.row_kinds == (F, W, W, W) and cfg.rows == (2 if deep else 1)
    assert r.kinds == ((W, 8), (F, None)) and r.routed and not r.recurrent
    assert (r.family_kernels is not None) == (impl == "pallas_interpret")
    served, tokens = served_logits(r, tap(r), 1, PROMPT, STEPS)
    assert r.admit_programs == 1 + 3            # the arming and three chunks
    assert r.kv.k.shape[0] == cfg.cache_layers == (8 if deep else 4)
    assert set(r.state.rec) == {"routed"} and r.state_bytes == 0
    ref = reference_logits(family, params, hf, PROMPT, tokens, monkeypatch)
    if dtype == "float32":
        agree(served, ref, F32_TOL)
        assert (served.argmax(-1) == ref.argmax(-1)).all()
    else:
        assert np.abs(ref).max() > 0.2
        assert np.abs(served - ref).mean() < BF16_MEAN_TOL
        assert np.abs(served - ref).max() < BF16_TOL


def test_the_softmax_over_the_chosen_is_the_renormalised_softmax_over_all(
        family):
    """The reference weighs a choice by softmax over the k chosen LOGITS;
    the program calls ``softmax_scores(k, True)``, softmax over all and the
    k largest renormalised: the same weights on the same experts."""
    logits = jnp.asarray(RNG.standard_normal((40, 8)) * 2.0, jnp.float32)
    h, w_router = jnp.eye(40, dtype=jnp.float32), logits     # h W_r = logits
    want = np.asarray(family.routing(h, w_router, HF))
    topv, topi = st.scores(config())(logits)
    got = np.zeros_like(want)
    got[np.arange(40)[:, None], np.asarray(topi)] = np.asarray(topv)
    assert ((want > 0).sum(-1) == 3).all()
    np.testing.assert_allclose(want.sum(-1), 1.0, atol=1e-6)
    np.testing.assert_allclose(got, want, atol=1e-6)


# ---------------------------------------------------------------------------
# (b) mathematics left out, or put where it does not belong, fails (a)


def router_behind_attention(monkeypatch):
    """The routing made where every other family has it: from the tensor the
    experts read (the post-attention norm's)."""
    real = st.xp

    class Late:
        def __init__(self, args, kw):
            self.args, self.kw = args, kw

    def walk(h, late, *a, **kw):
        routed = real.route(h, *late.args, **late.kw)
        late.n_touched, late.load = routed.n_touched, routed.load
        return real.walk(h, routed, *a, **kw)

    monkeypatch.setattr(st, "xp", types.SimpleNamespace(**{
        **vars(real), "route": lambda h, *args, **kw: Late(args, kw), "walk": walk}))
    return {}


def silu_for_relu(monkeypatch):
    monkeypatch.setattr(st, "act", jax.nn.silu)
    return {}


def weights_not_renormalised(monkeypatch):
    monkeypatch.setattr(st, "scores", lambda cfg: xp.softmax_scores(
        cfg.num_experts_per_tok, False))
    return {}


def window_ignored(monkeypatch):
    monkeypatch.setattr(st.SmallThinkerConfig, "attn_kinds", property(
        lambda self: ((W, None), (F, None))))
    return {}


def rope_on_the_full_layers(monkeypatch):
    monkeypatch.setattr(st, "rope_on", lambda kind: True)
    return {}


def rope_left_off_the_window_layers(monkeypatch):
    monkeypatch.setattr(st, "rope_on", lambda kind: False)
    return {}


def full_layer_last_in_the_row(monkeypatch):
    return {"sliding_window_layout": [1, 1, 1, 0],
            "rope_layout": [1, 1, 1, 0]}


MUTATIONS = [router_behind_attention, silu_for_relu, weights_not_renormalised,
             window_ignored, rope_on_the_full_layers,
             rope_left_off_the_window_layers, full_layer_last_in_the_row]


@pytest.mark.parametrize("mutate, impl", [
    *((m, "xla") for m in MUTATIONS), (window_ignored, "pallas_interpret"),
    (silu_for_relu, "pallas_interpret")],
    ids=lambda x: getattr(x, "__name__", x))
def test_a_program_that_departs_from_the_equations_fails(
        family, monkeypatch, mutate, impl):
    """Each departure, in the PROGRAM alone (the reference keeps the
    published keys), moves the float32 logits by at least 100 x the
    tolerance of (a). Under the kernels the two cases that are other code
    there: the window (the paged kernel's own bound) and the gate's
    function (ops.moe's kernel)."""
    cfg = config(**mutate(monkeypatch))
    params = seeded_params(config())
    r = runner_for(cfg, params, impl)
    served, tokens = served_logits(r, tap(r), 1, PROMPT, steps=4)
    ref = reference_logits(family, params, HF, PROMPT, tokens, monkeypatch)
    assert np.abs(served - ref).max() > 100 * F32_TOL


# ---------------------------------------------------------------------------
# (c) the share: the ranks' parts add up to the uncut layer


def test_the_four_shares_add_up_to_the_uncut_layer(family):
    """One expert block, 8 experts top-3, cut over 4 ranks of 2: the parts
    the ranks give (no shared expert: nothing is counted twice) add up to
    the uncut REFERENCE's layer; every token-expert pair lands on exactly one
    rank."""
    size, E = 4, 8
    whole = config()
    params = seeded_params(whole, seed=3)
    lay = params["layers"]
    h_in, h = (jnp.asarray(RNG.standard_normal((6, 64)), jnp.float32)
               for _ in range(2))
    valid = jnp.ones(6, bool)
    at = 2                                      # the row's third layer
    with jax.default_matmul_precision("highest"):
        want = np.asarray(family.experts(
            h, family.routing(h_in, lay["moe_gate"][at], HF), HF,
            lambda name, lo, hi: lay[name][at][lo:hi]))
        total, pairs = np.zeros_like(want), 0
        for rank in range(size):
            cut = config(moe_num_primary_experts=E // size,
                         expert_parallel={"size": size, "rank": rank})
            assert (cut.num_experts, cut.router_width) == (2, E)
            held = tuple(lay[n][None, :, rank * 2:(rank + 1) * 2]
                         for n in xp.EXPERT_LEAVES)     # [rows, M, 2, ...]
            routed = xp.route(h_in, lay["moe_gate"][at], st.scores(cut),
                              cut.num_experts, rank, valid)
            total += np.asarray(xp.walk(h, routed, held, jnp.int32(0), at,
                                        act=st.act))
            pairs += int(xp.counts(routed.n_touched, routed.load)[1])
    assert pairs == 6 * 3
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(total, want, atol=2e-5)


def test_a_share_of_the_experts_is_served(family, monkeypatch):
    """``expert_parallel`` size 2, rank 1 through the runner: 4 of the 8
    experts held, the router at its full width, against the reference given
    the same share."""
    share = {"moe_num_primary_experts": 4,
             "expert_parallel": {"size": 2, "rank": 1}}
    cfg = config(**share)
    params = seeded_params(cfg)
    assert params["layers"]["w_gate"].shape == (4, 4, 64, 32)
    assert params["layers"]["moe_gate"].shape == (4, 64, 8)
    r = runner_for(cfg, params)
    served, tokens = served_logits(r, tap(r), 0, PROMPT, steps=3)
    agree(served, reference_logits(family, params, {**HF, **share}, PROMPT,
                                   tokens, monkeypatch), F32_TOL)


# ---------------------------------------------------------------------------
# (d) the prefix pool: shared blocks longer than the window


def test_a_prefix_from_the_pool_gives_the_whole_prefills_logits(
        family, monkeypatch):
    """A request whose first 32 tokens (4 blocks, four windows) come from the
    prefix pool prefills its 8-token tail alone, at offset 32, and serves the
    logits of the same request prefilled whole, and the reference's."""
    cfg = config()
    params = seeded_params(cfg)
    other = PROMPT[:32] + RNG.integers(1, 380, 8).tolist()
    r = runner_for(cfg, params)
    seen = tap(r)
    served_logits(r, seen, 0, PROMPT, steps=1)
    r.release(0)
    shared, tokens = served_logits(r, seen, 2, other, steps=6)
    assert r.last_prefill_path == "paged_shared"
    assert r.last_prefix_reused == 32 > 3 * cfg.sliding_window
    assert r.admit_programs == (1 + 3) + (1 + 1)    # one chunk: the tail
    fresh = runner_for(cfg, params)
    whole, again = served_logits(fresh, tap(fresh), 2, other, steps=6)
    assert fresh.last_prefix_reused == 0 and again == tokens
    np.testing.assert_allclose(shared, whole, atol=F32_TOL)
    agree(shared, reference_logits(family, params, HF, other, tokens,
                                   monkeypatch), F32_TOL)


# ---------------------------------------------------------------------------
# (e) what is built, what is refused


def test_the_stack_is_rows_that_start_with_the_full_layer():
    cfg = config(**DEEP)
    assert isinstance(cfg, st.SmallThinkerConfig)
    assert cfg.family == "smallthinker"
    assert (cfg.rows, cfg.row_layers, cfg.row_kinds, cfg.router_width) == (
        2, 4, (F, W, W, W), 8)
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.sliding_window) == (8, 3, 32, 8)
    shapes = mdl.param_shapes(cfg)
    assert {s[0] for s in shapes["layers"].values()} == {8}    # the LAYER
    assert shapes["layers"]["wq"] == (8, 64, 96)
    assert shapes["layers"]["w_gate"] == (8, 8, 64, 32)
    assert shapes["layers"]["moe_gate"] == (8, 64, 8)
    assert shapes["lm_head"] == (64, 384)
    # a stack of one kind is rows of one layer
    flat = config(sliding_window_layout=[0] * 4, rope_layout=[0] * 4)
    assert (flat.rows, flat.row_kinds, flat.attn_kinds) == (
        4, (F,), ((F, None),))


@pytest.mark.parametrize("changed, says", [
    ({"moe_primary_router_apply_softmax": False}, "apply_softmax"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"moe_num_secondary_experts": 4}, "secondary experts"),
    ({"rope_layout": [1, 1, 1, 1]}, "rope_layout differ"),
    ({"num_hidden_layers": 5}, "names 4 layers"),
    ({"sliding_window_size": None}, "no sliding_window_size"),
    ({"num_attention_heads": 5}, "no whole groups"),
    ({"expert_parallel": {"size": 2, "rank": 2}}, "rank 2 outside"),
])
def test_a_config_the_stack_cannot_hold_is_refused(changed, says):
    with pytest.raises(ValueError, match=says):
        config(**changed)


def test_a_checkpoint_in_the_published_layout_loads_to_the_served_leaves(
        tmp_path):
    """``models/loader.py`` for the family: a checkpoint written HERE in the
    published layout (tensor names of ``modeling_smallthinker.py`` from
    memory, linear weights [out, in], every one of the 8 experts) loads to
    the served leaves it was made from, 8 layers of them; a rank of two loads
    its 4 experts of each layer and the whole router."""
    from safetensors.numpy import save_file

    from localai_tpu.models.loader import load_llama_params

    whole_hf = {**HF, **DEEP}
    whole = config(**DEEP)
    params = jax.tree.map(np.asarray, seeded_params(whole, seed=5))
    names = {"attn_norm": "input_layernorm",
             "mlp_norm": "post_attention_layernorm",
             "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
             "wv": "self_attn.v_proj", "wo": "self_attn.o_proj",
             "moe_gate": "block_sparse_moe.primary_router"}
    mlp = {"w_gate": "gate", "w_up": "up", "w_down": "down"}
    out = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["final_norm"],
           "lm_head.weight": params["lm_head"].T}
    for i in range(8):
        pre, at = f"model.layers.{i}.", i
        for ours, theirs in names.items():
            a = params["layers"][ours][at]
            out[pre + theirs + ".weight"] = a.T if a.ndim == 2 else a
        for ours, theirs in mlp.items():
            for e in range(8):
                out[pre + f"block_sparse_moe.experts.{e}.{theirs}.weight"] = (
                    params["layers"][ours][at][e].T)
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()},
              str(tmp_path / "model.safetensors"))
    cfg, loaded = load_llama_params(tmp_path, dtype="float32", hf=whole_hf)
    assert cfg == dataclasses.replace(whole, dtype=cfg.dtype)
    jax.tree.map(np.testing.assert_array_equal, params,
                 jax.tree.map(np.asarray, loaded))
    cut, held = load_llama_params(
        tmp_path, dtype="bfloat16",
        hf={**whole_hf, "moe_num_primary_experts": 4,
            "expert_parallel": {"size": 2, "rank": 1}})
    assert (cut.num_experts, cut.router_width, cut.ep_rank) == (4, 8, 1)
    lay = held["layers"]
    assert lay["w_gate"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(lay["w_up"], np.float32),
        np.asarray(jnp.asarray(params["layers"]["w_up"][:, 4:],
                               jnp.bfloat16), np.float32))
    assert lay["moe_gate"].shape == (8, 64, 8)
    with pytest.raises(ValueError, match="quantization"):
        load_llama_params(tmp_path, hf=whole_hf, quantization="int8")


@pytest.mark.parametrize("what, kw", [
    ("the contiguous K/V layout", {"paged": False}),
    ("a int8 K/V pool", {"kv_dtype": "int8"}),
    ("self-extend", {"ga_n": 2, "ga_w": 8}),
    ("a device mesh", {"mesh": {"model": 2}}),
    ("pipeline parallelism", {"mesh": {"pipe": 2}}),
])
def test_what_the_kinds_cannot_be_served_through_is_refused(what, kw):
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    cfg = config()
    if "mesh" in kw:
        kw["mesh"] = build_mesh(MeshPlan(**kw["mesh"]),
                                devices=jax.devices()[:2])
    with pytest.raises(ValueError, match=f"^{what} is not served for "
                                         f"model_type smallthinker"):
        runner_for(cfg, mdl.init_params(jax.random.key(0), cfg), **kw)


def test_the_synthetic_draw_weighs_every_branch_and_keeps_the_router_plain():
    """``init_leaf``: a seeded 1 in 192 of the channels of the norm in front
    of the EXPERTS and of the final norm at ``OUTLIER_GAIN`` (another set a
    layer; none under 192 channels), the norm in front of attention AND
    router at 1; the matrices at the deviations that make router logits, q
    and k, and each branch's output of the order ``leaf_std`` states: what
    the benchmark's reference check needs to tell a lower precision apart
    (the configuration's ``assumed.weights``)."""
    cfg = config("bfloat16", hidden_size=384)
    params = mdl.init_params(jax.random.key(0), cfg)
    lay = params["layers"]
    for gains in (lay["mlp_norm"], params["final_norm"]):
        g = np.asarray(gains, np.float32).reshape(-1, 384)
        assert ((g == st.OUTLIER_GAIN).sum(-1) == 2).all()
        assert ((g == 1).sum(-1) == 382).all()
    where = np.asarray(lay["mlp_norm"], np.float32).reshape(-1, 384) > 1
    assert len({tuple(np.flatnonzero(w)) for w in where}) > 1
    assert (np.asarray(lay["attn_norm"], np.float32) == 1).all()
    std = {n: float(np.asarray(lay[n], np.float32).std())
           for n in ("wq", "wv", "moe_gate", "w_gate")}
    fan = np.sqrt(384)
    assert std["wq"] == pytest.approx(st.QK_GAIN / fan, rel=0.05)
    assert std["wv"] == pytest.approx(1 / fan, rel=0.05)
    assert std["moe_gate"] == pytest.approx(st.ROUTER_STD / fan, rel=0.05)
    behind = np.sqrt(1 + 2 / 384 * (st.OUTLIER_GAIN ** 2 - 1))
    assert std["w_gate"] == pytest.approx(1 / (fan * behind), rel=0.05)
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


def scope_order(jaxpr, tags) -> list:
    """The equations of ``jaxpr`` and of what it calls, in program order, by
    which of ``tags`` their name stack holds; runs of one tag folded."""
    seen: list = []

    def visit(jp):
        for eqn in jp.eqns:
            stack = str(eqn.source_info.name_stack)
            tag = next((t for t in tags if t in stack), None)
            if tag and (not seen or seen[-1] != tag):
                seen.append(tag)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                visit(sub)

    visit(jaxpr)
    return seen


def test_the_router_stands_in_front_of_attention_in_the_traced_programs():
    """In the traced decode program and chunk, every layer runs ``moe/router``
    in front of ``attn.qkv`` and ``moe/experts`` behind ``attn.out``; the
    window layers alone rotate; the attends carry their kinds' scopes."""
    cfg = config("bfloat16", head_dim=128, num_attention_heads=3,
                 num_key_value_heads=1, moe_ffn_hidden_size=128)
    r = runner_for(cfg, mdl.init_params(jax.random.key(0), cfg),
                   "pallas_interpret", kv_block_tokens=32, max_ctx=128,
                   prefill_chunk=32, prefill_buckets=[32])
    tags = ("moe/router", "attn.qkv", "attn.rope", "attn.out", "moe/experts")
    full = ["moe/router", "attn.qkv", "attn.out", "moe/experts"]
    window = full[:2] + ["attn.rope"] + full[2:]
    chunk = (jnp.zeros((1, 32), jnp.int32), jnp.int32(5), jnp.int32(0),
             r.block_tables[0], jnp.int32(0),
             jnp.zeros(cfg.vocab_size, jnp.int32))
    prefill = functools.partial(r._prefill_paged_fn, bucket=32, sample=True)
    for fn, args in ((r._decode_paged_fn, (r.block_tables,)),
                     (prefill, chunk)):
        jaxpr = jax.make_jaxpr(fn)(r.params, r.kv, r.state, *args)
        assert scope_order(jaxpr.jaxpr, tags) == full + 3 * window
    decode = jax.jit(r._decode_paged_fn).lower(
        r.params, r.kv, r.state, r.block_tables).as_text(debug_info=True)
    for scope in ("attn.window_decode/paged_decode_attn",
                  "attn.paged_decode/paged_decode_attn", "moe/router",
                  "moe/experts/moe_experts", "attn.rope"):
        assert scope in decode, scope
    text = jax.jit(prefill).lower(r.params, r.kv, r.state, *chunk).as_text(
        debug_info=True)
    assert "attn.prefill_window/" in text and "attn.prefill/" in text


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
            # one row, 3 of 8 experts in each of 4 layers, all of them here
            assert x["experts_touched"] == x["local_assignments"] == (
                x["steps"] * 4 * 3)
        m = s.metrics()
        assert m["moe_assignments"] > 0 and "state_slots_armed" not in m
        obs_metrics.update_engine_gauges("smt", m)
        assert 'localai_kv_window_dead_tokens{model="smt"}' in (
            obs_metrics.REGISTRY.render())
    finally:
        s.shutdown()
