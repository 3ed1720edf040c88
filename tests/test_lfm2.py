"""LFM2-MoE (``model_type: lfm2_moe``) on the normal serving path: a gated
short convolution or grouped-query attention by a LIST of layer kinds, dense
layers in front of sigmoid-routed experts with no shared expert; a cache layer
of the paged K/V pool an ATTENTION layer (heads of 64, two to a 128-lane pool
row) and two rows of per-slot state a CONVOLUTION layer. CPU, small widths (D
256, 4 / 2 heads of 64, 8 experts top-2 of width 32, the dense layers 96 wide)
under the PUBLISHED 24-entry ``layer_types`` with its 2 dense layers: three
scans (``c c`` | ``a c c c`` x 4 | ``a c c`` x 2), seeded weights of the
program's own draw.

The served path is the runner's own programs (``_prefill_paged_fn`` /
``_decode_paged_fn``), driven by ``admit`` and ``step`` and tapped for the
logits they sample from; the reference is the benchmark's plain float32
family (benchmark/reference/lfm2_family.py, written from the published keys)
run as the benchmark runs it (harness/refcheck.py): the FULL forward over
prompt + served tokens, no cache, no state carried.
"""

import dataclasses
import json
from functools import partial

import families
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from families import reference_logits, served_logits, tap

from localai_tpu import ops
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import experts as xp
from localai_tpu.models import lfm2
from localai_tpu.models import llama as mdl
from localai_tpu.ops import attention as att

C, A = lfm2.CONV, lfm2.FULL
PUBLISHED = [C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C,
             A, C, C]
HF = {"model_type": "lfm2_moe", "vocab_size": 384, "hidden_size": 256,
      "intermediate_size": 96, "num_hidden_layers": 24,
      "num_attention_heads": 4, "num_key_value_heads": 2,
      "layer_types": PUBLISHED, "num_dense_layers": 2, "conv_L_cache": 3,
      "conv_bias": False, "moe_intermediate_size": 32, "num_experts": 8,
      "num_experts_per_tok": 2, "norm_eps": 1e-5, "norm_topk_prob": True,
      "use_expert_bias": True, "routed_scaling_factor": 1,
      "rope_theta": 1000000, "max_position_embeddings": 512}
SLOTS = 4
RNG = np.random.default_rng(57)
PROMPT = RNG.integers(1, 380, 19).tolist()      # three chunks: 8 + 8 + 3 of 8
SHORT = RNG.integers(1, 380, 5).tolist()        # one chunk, 3 padded rows
STEPS = 4
# float32 serving: what is left between the two is summation order (the
# chunk's convolution is the reference's three shifted products); logits
# spread ~1.5 behind 24 layers
F32_TOL = 1e-4
# bfloat16 serving under the program's draw, on SEVEN layers (``c c | a c c |
# a c``: still a dense prefix, the stack and a tail) with EVERY expert chosen
# (top-8 of 8): read 0.157 at the worst of 5 x 384 logits and 0.020 in the
# mean (logits spread 1.1). Neither cut is the family's: a rounded router
# flips near-ties between experts (top-2 of 8 swaps HALF a block's output),
# which the float32 reference does not follow (the same seven layers at top-2
# read 2.5 and 0.23), and a gated convolution multiplies three roundings
# behind a residual that starts small, so 24 layers of it compound (top-8:
# 1.47 and 0.15). What the case holds is the bfloat16 path itself (the packed
# heads through the kernel, the expert kernel, rows and K/V kept in
# bfloat16), and it FAILS the float32 tolerance a thousandfold: a computation
# in a lower precision than a float32 configuration states fails ``F32_TOL``
BF16_HF = {"num_hidden_layers": 7, "layer_types": [C, C, A, C, C, A, C],
           "num_experts_per_tok": 8}
BF16_TOL, BF16_MEAN_TOL = 0.5, 0.06


@pytest.fixture(scope="module")
def family():
    return families.reference_family("lfm2_family", "tests/test_lfm2.py")


config = partial(families.config, HF)


@pytest.fixture(scope="module")
def params32():
    return mdl.init_params(jax.random.key(0), config())


def runner_for(cfg, params, attn_impl="xla", **kw) -> ModelRunner:
    kw = {"num_slots": SLOTS, "max_ctx": 64, "paged": True,
          "kv_block_tokens": 8, "prefill_chunk": 8, "prefill_buckets": [8],
          "attn_impl": attn_impl, "kv_dtype": cfg.dtype, **kw}
    return ModelRunner(cfg, params, **kw)


# logits that spread, not zeros
agree = partial(families.agree, spread=1.0)


# ---------------------------------------------------------------------------
# (i) the served path against the plain reference


def test_the_published_list_is_three_scans_and_the_cut_two():
    """``plan``: the dense prefix one row, a row at every attention layer,
    like rows one run; the benchmark's cut (the first 14) has no tail."""
    cfg = config()
    assert [(r.prefix, r.rows, "".join(k[0] for k in r.kinds), r.dense,
             r.conv0, r.attn0) for r in cfg.runs] == [
        ("dense_", 1, "cc", True, 0, 0), ("", 4, "fccc", False, 2, 0),
        ("tail1_", 2, "fcc", False, 14, 4)]
    assert (cfg.cache_layers, cfg.conv_layers) == (6, 18)
    cut = config(num_hidden_layers=14, layer_types=PUBLISHED[:14])
    assert [(r.prefix, r.rows) for r in cut.runs] == [("dense_", 1), ("", 3)]
    # any list builds: rows of one layer, a leading attention layer, no
    # dense prefix
    odd = config(num_hidden_layers=5, num_dense_layers=0,
                 layer_types=[A, A, C, A, C])
    assert [(r.prefix, r.rows, len(r.kinds)) for r in odd.runs] == [
        ("", 1, 1), ("tail1_", 2, 2)]
    with pytest.raises(ValueError, match="leaves no layer with experts"):
        config(num_dense_layers=24)
    with pytest.raises(ValueError, match="kinds served are conv and "
                                         "full_attention"):
        config(layer_types=[C] * 23 + ["sliding_attention"])
    for key, value in (("conv_bias", True),
                       ("rope_scaling", {"type": "linear", "factor": 2.0})):
        with pytest.raises(ValueError, match=key):
            config(**{key: value})


@pytest.mark.parametrize("dtype, attn_impl", [
    ("float32", "xla"), ("float32", "pallas_interpret"),
    ("bfloat16", "pallas_interpret")])
def test_served_logits_match_the_reference(family, monkeypatch, params32,
                                           dtype, attn_impl):
    """A prompt over three chunks of one bucket (8 + 8 + 3: the tail handed
    on at every hand-over, the last with padded rows), then decode steps: the
    logits each program samples from against the full forward over all 24
    layers, in float32 as XLA and through the kernels in the interpreter
    (the paged kernel over the PACKED heads, the grouped expert kernel), and
    in bfloat16."""
    hf = HF if dtype == "float32" else {**HF, **BF16_HF}
    cfg = families.config(hf, dtype)
    if dtype != "float32":
        params32 = mdl.init_params(jax.random.key(0), families.config(hf))
    # (the selection bias stays float32, as the loader keeps it)
    params = jax.tree_util.tree_map_with_path(
        lambda path, a: a if path[-1].key.endswith("expert_bias")
        else a.astype(dtype), params32)
    r = runner_for(cfg, params, attn_impl)
    assert (cfg.num_kv_heads, cfg.hd, cfg.kv_pack) == (1, 128, 2)
    assert (cfg.attn_kv_heads, cfg.attn_hd, cfg.rotary_dim) == (2, 64, 64)
    served, tokens = served_logits(r, tap(r), 1, PROMPT, STEPS)
    assert r.admit_programs == 1 + 3            # the arming and three chunks
    assert r.kv.k.shape[:3] == (cfg.cache_layers, r.allocator.num_blocks, 1)
    assert r.kv.k.shape[3:] == (8, 128)         # two heads of 64 a row
    assert sorted(r.state.rec) == ["conv", "routed"]
    assert r.state.rec["conv"].shape == (cfg.conv_layers, SLOTS, 2, 256)
    assert r.state_bytes == sum(a.nbytes for a in r.state.rec.values())
    traced = str(jax.make_jaxpr(r._decode_paged_fn)(
        r.params, r.kv, r.state, r.block_tables))
    kernels = attn_impl != "xla"
    # the scans are rolled: a kernel once a place in a run's row
    rows = [len(run.kinds) for run in cfg.runs if not run.dense]
    assert traced.count("name=paged_decode_attn") == len(rows) * kernels
    assert traced.count("name=moe_experts") == sum(rows) * kernels
    ref = reference_logits(family, params, hf, PROMPT, tokens, monkeypatch)
    if dtype == "float32":
        agree(served, ref, F32_TOL)
        assert (served.argmax(-1) == ref.argmax(-1)).all()
    else:
        agree(served, ref, BF16_TOL)
        assert np.abs(served - ref).mean() < BF16_MEAN_TOL
        assert np.abs(served - ref).max() > 100 * F32_TOL


def test_a_padded_row_and_an_idle_slot_move_nothing(family, monkeypatch,
                                                    params32):
    """5 real tokens in a bucket of 8: the 3 rows past ``length`` move no
    row of the slot's state, whatever they hold (the same prompt into
    another slot behind junk leaves that slot's rows bit for bit the
    first's); a decode step leaves the slots that hold no stream exactly as
    they were (zero); a slot that empties and is given to a new stream
    starts from zeros."""
    from localai_tpu.engine.runner import _prompt_counts_row

    cfg = config()
    r = runner_for(cfg, params32)
    seen = tap(r)
    served, tokens = served_logits(r, seen, 2, SHORT, steps=3)
    ref = reference_logits(family, params32, HF, SHORT, tokens, monkeypatch)
    agree(served, ref, F32_TOL)
    conv = np.asarray(r.state.rec["conv"])
    assert conv[:, 2].any() and not conv[:, [0, 1, 3]].any()

    def chunk_into(slot: int, junk: int):
        adm = r.begin_admit(slot, SHORT, temperature=0.0)
        row = np.asarray(r.allocator.table_row(slot), np.int32)
        r._arm(adm.arm_args, row)
        chunk = np.full((1, 8), junk, np.int32)
        chunk[0, :5] = SHORT
        r.kv, r.state, tok = r._prefill_paged(
            r.params, r.kv, r.state, chunk, np.int32(5), np.int32(0), row,
            np.int32(slot), _prompt_counts_row(cfg.vocab_size, SHORT),
            bucket=8, sample=True)
        return np.asarray(r.state.rec["conv"][:, slot]), int(tok[0])

    zeros, tok = chunk_into(0, 0)
    junk, tok_junk = chunk_into(3, 377)
    assert tok == tok_junk == tokens[0]
    np.testing.assert_array_equal(zeros, junk)
    # slot 2 held SHORT's stream for three steps: released and armed again,
    # the same prompt reads the same logits
    r.release(2)
    r._free_slots.remove(2)
    again, tokens_again = served_logits(r, seen, 2, SHORT, steps=3)
    assert tokens_again == tokens
    np.testing.assert_array_equal(again, served)


# ---------------------------------------------------------------------------
# (ii) one failing case a term: mathematics left out fails (i)'s tolerance


def oldest_tap_dropped(monkeypatch):
    """What a wrong hand-over between chunk and step looks like."""
    real = lfm2.short_conv
    monkeypatch.setattr(
        lfm2, "short_conv",
        lambda cat, taps, T: real(cat, taps.at[0].set(0), T))


def state_one_row_short(monkeypatch):
    """The slot keeps one row: the older of its two reads zero."""
    real = mdl.conv_rows
    monkeypatch.setattr(
        mdl, "conv_rows",
        lambda cat, n, K: real(cat, n, K).at[:, 0].set(0))


def output_gate_dropped(monkeypatch):
    real = lfm2.gated
    seen = []

    def second_is_plain(gate, x):
        seen.append(1)
        return real(gate, x) if len(seen) % 2 else x.astype(jnp.float32)

    monkeypatch.setattr(lfm2, "gated", second_is_plain)


def bias_weighs(monkeypatch):
    def score(cfg, bias):
        def with_bias(logits):
            s = jax.nn.sigmoid(logits) + bias.astype(jnp.float32)
            topv, topi = jax.lax.top_k(s, cfg.num_experts_per_tok)
            return topv / (jnp.sum(topv, -1, keepdims=True) + 1e-6), topi
        return with_bias

    monkeypatch.setattr(lfm2, "scores", score)


def bias_left_out_of_the_choice(monkeypatch):
    real = lfm2.scores
    monkeypatch.setattr(lfm2, "scores", lambda cfg, bias: real(cfg, None))


def qk_norm_dropped(monkeypatch):
    real = lfm2.norm
    monkeypatch.setattr(
        lfm2, "norm",
        lambda x, w, eps: x if w.shape[-1] == 64 else real(x, w, eps))


def q_without_the_rows_factor(monkeypatch):
    """The attends scale by (128)^-1/2 where the model says (64)^-1/2."""
    real = lfm2.rotate
    monkeypatch.setattr(lfm2, "rotate",
                        lambda x, cos, sin, scale=1.0: real(x, cos, sin))


LEFT_OUT = [oldest_tap_dropped, state_one_row_short, output_gate_dropped,
            bias_weighs, bias_left_out_of_the_choice, qk_norm_dropped,
            q_without_the_rows_factor]


@pytest.mark.parametrize("left_out", LEFT_OUT, ids=lambda f: f.__name__)
def test_mathematics_left_out_fails_the_tolerance(family, monkeypatch,
                                                  params32, left_out):
    """Each on the runner's own programs, the 5-token prompt and three
    steps: the logits leave the reference's by at least a hundred
    tolerances. ``state_one_row_short`` is right in the chunk (it starts
    from zeros) and wrong from the second step on."""
    # the bias of this case's weights moves choices (the draw's own 0.004
    # moves a tenth of the tokens': too few of 8 to count on)
    params = dict(params32, layers=dict(params32["layers"]))
    for name in ("expert_bias",):
        params["layers"][name] = params32["layers"][name] * 50.0
    left_out(monkeypatch)
    r = runner_for(config(), params)
    served, tokens = served_logits(r, tap(r), 0, SHORT, steps=3)
    monkeypatch.undo()
    ref = reference_logits(family, params, HF, SHORT, tokens, monkeypatch)
    assert np.abs(served - ref).max() > 100 * F32_TOL
    if left_out is state_one_row_short:
        assert np.abs(served[0] - ref[0]).max() < F32_TOL


# ---------------------------------------------------------------------------
# (iii) the experts: whole where nothing is said, shares that add up


def test_all_experts_held_is_the_whole_layer_and_shares_add_up(family,
                                                               params32):
    """``expert_parallel`` absent: every expert is held and the block's
    output IS the reference's whole layer (no shared expert to add). Cut
    over 2 and over 4 ranks, the parts the ranks give add up to it, and
    every token-expert pair lands on exactly one rank."""
    lay = params32["layers"]
    at = (1, 2)                                 # second row, third layer
    h = jnp.asarray(RNG.standard_normal((6, 256)), jnp.float32)
    valid = jnp.ones(6, bool)
    w = {n: np.asarray(lay[n][at], np.float32)
         for n in ("moe_gate", "expert_bias")}
    whole_cfg = config()
    assert (whole_cfg.ep_size, whole_cfg.router_width) == (1, 8)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(family.experts(
            h, w, HF, lambda name, lo, hi: np.asarray(
                lay[name][at][lo:hi], np.float32)))

        def part(cfg, rank, held):
            out, n_touched, load = xp.moe_block(
                h, lay["moe_gate"][at],
                lfm2.scores(cfg, lay["expert_bias"][at]), held,
                jnp.int32(at[0]), at[1], num_experts=cfg.num_experts,
                ep_rank=rank, valid=valid, shared=None)
            return np.asarray(out), int(jnp.sum(load)), int(n_touched)

        whole = tuple(lay[n] for n in xp.EXPERT_LEAVES)
        got, pairs, touched = part(whole_cfg, 0, whole)
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert pairs == 6 * 2 and 2 <= touched <= 8
        for size in (2, 4):
            n = 8 // size
            total, landed = np.zeros_like(want), 0
            for rank in range(size):
                cut = config(num_experts=n, expert_parallel={
                    "size": size, "rank": rank})
                assert cut.router_width == 8
                held = tuple(lay[name][:, :, rank * n:(rank + 1) * n]
                             for name in xp.EXPERT_LEAVES)
                out, p, _ = part(cut, rank, held)
                total, landed = total + out, landed + p
            np.testing.assert_allclose(total, want, atol=2e-5)
            assert landed == 6 * 2


def test_the_bias_changes_a_tenth_of_the_choices_at_the_published_width():
    """The draw's ``expert_bias`` (N(0, 0.004), float32) against router
    logits of deviation 1 over 32 experts, top-4: a token's set of experts
    differs with and without it for about a tenth of 4096 tokens, and no
    expert goes without a token among 128."""
    cfg = config(num_experts=32, num_experts_per_tok=4, hidden_size=2048,
                 num_attention_heads=32, num_key_value_heads=8)
    key_w, key_h, key_b = jax.random.split(jax.random.key(5), 3)
    w = lfm2.init_leaf(key_w, (2048, 32), "moe_gate", jnp.float32, cfg)
    bias = lfm2.init_leaf(key_b, (32,), "expert_bias", jnp.float32, cfg)
    assert bias.dtype == jnp.float32
    h = jax.random.normal(key_h, (4096, 2048))
    logits = h @ w
    assert 0.9 < float(jnp.std(logits)) < 1.1
    _, with_bias = lfm2.scores(cfg, bias)(logits)
    _, without = lfm2.scores(cfg, None)(logits)
    differ = (np.sort(np.asarray(with_bias), -1)
              != np.sort(np.asarray(without), -1)).any(-1).mean()
    assert 0.05 < differ < 0.25, differ
    load = np.bincount(np.asarray(with_bias[:128]).ravel(), minlength=32)
    assert load.min() >= 1 and load.sum() == 128 * 4


def test_outlier_channels_stand_in_the_norms_that_feed_the_mixers():
    cfg = config()
    draw = partial(lfm2.init_leaf, cfg=cfg)
    for name in ("op_norm", "dense_op_norm", "tail1_op_norm", "final_norm"):
        gain = np.asarray(draw(jax.random.key(1), (2, 384), name,
                               jnp.float32))
        assert ((gain == lfm2.OUTLIER_GAIN).sum(axis=-1) == 2).all()
        assert ((gain == 1.0).sum(axis=-1) == 382).all()
    assert (np.asarray(draw(jax.random.key(1), (2, 384), "ffn_norm",
                            jnp.float32)) == 1.0).all()
    assert (np.asarray(draw(jax.random.key(1), (2, 64), "tail1_q_norm",
                            jnp.float32)) == lfm2.QK_NORM_GAIN).all()
    taps = np.asarray(draw(jax.random.key(1), (4, 3, 3, 256), "conv_w",
                           jnp.float32))
    assert 0.4 < taps.std() < 0.6 and (np.abs(taps).mean(axis=(0, 1, 3))
                                       > 0.3).all()


# ---------------------------------------------------------------------------
# (iv) heads of 64 through the kernel, K/V written by the runner's own policies


def test_packed_heads_through_the_paged_kernel_match_the_gathered_attend(
        params32):
    """Two streams served through the kernels in the interpreter; then, over
    the pool AS THE RUNNER WROTE IT, queries of the model's own 64-wide
    heads: packed (each at its K/V head's half of a 128-lane row, times
    2^1/2) through ``paged_decode_attention`` and unpacked, against
    ``paged_decode_attention_ref`` over the pool read back as the model's 2
    K/V heads of 64 with the model's scale."""
    cfg = config()
    r = runner_for(cfg, params32, "pallas_interpret")
    r.admit(0, PROMPT, temperature=0.0)
    r.admit(2, SHORT, temperature=0.0)
    for _ in range(3):
        r.step()
    positions = jnp.asarray(np.asarray(r.slot_positions()), jnp.int32)
    assert positions[0] == len(PROMPT) + 3 and positions[2] == len(SHORT) + 3
    f, hkv, hd = cfg.kv_pack, cfg.attn_kv_heads, cfg.attn_hd
    q = jnp.asarray(RNG.standard_normal((SLOTS, 4, hd)), jnp.float32)
    layer = 4
    # the last written position of a slot is positions - 1
    got = np.asarray(att.unpack_out(ops.paged_decode_attention(
        att.pack_q(q * np.sqrt(f), f, hkv), r.kv.k, r.kv.v, jnp.int32(layer),
        r.block_tables, positions - 1, interpret=True), f, hkv))

    def own_heads(pool):        # [N, 1, bt, 128] -> [N, 2, bt, 64]
        n, rows, bt, _ = pool.shape
        return pool.reshape(n, rows, bt, f, hd).transpose(
            0, 1, 3, 2, 4).reshape(n, rows * f, bt, hd)

    want = np.asarray(ops.paged_decode_attention_ref(
        q, own_heads(r.kv.k[layer]), own_heads(r.kv.v[layer]),
        r.block_tables, positions - 1))
    assert np.abs(want[[0, 2]]).max() > 0.1
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], atol=2e-5)
    # pack and unpack are each other's inverse on a head's own lanes
    packed = att.pack_q(q, f, hkv)
    assert packed.shape == (SLOTS, 4, 128)
    np.testing.assert_array_equal(att.unpack_out(packed, f, hkv), q)
    # q heads 0, 1 read K/V head 0 (lanes 0-63), heads 2, 3 head 1
    packed = np.asarray(packed)
    assert not packed[:, :2, 64:].any() and not packed[:, 2:, :64].any()


# ---------------------------------------------------------------------------
# (v) the loader, the door's refusals, the scheduler


def test_a_checkpoint_in_the_published_layout_loads_to_the_served_leaves(
        tmp_path, params32):
    """``models/loader.py`` for the family: a checkpoint written HERE under
    the published names (``conv.in_proj``, ``conv.conv`` as [D, 1, K],
    ``self_attn.out_proj``, ``feed_forward.experts.<e>.w1``,
    ``embedding_norm``; no ``lm_head``: tied) loads to the leaves it was
    written from, all three runs of the published list."""
    from safetensors.numpy import save_file

    from localai_tpu.models.loader import load_llama_params

    cfg = config()
    tensors = {"model.embed_tokens.weight": params32["embed"],
               "model.embedding_norm.weight": params32["final_norm"]}
    names = {"op_norm": "operator_norm.weight", "ffn_norm": "ffn_norm.weight",
             "conv_in": "conv.in_proj.weight",
             "conv_out": "conv.out_proj.weight",
             "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
             "wv": "self_attn.v_proj.weight",
             "wo": "self_attn.out_proj.weight",
             "q_norm": "self_attn.q_layernorm.weight",
             "k_norm": "self_attn.k_layernorm.weight",
             "moe_gate": "feed_forward.gate.weight",
             "expert_bias": "feed_forward.expert_bias"}
    mlp = {"w_gate": "w1", "w_up": "w3", "w_down": "w2"}
    first = 0
    for run in cfg.runs:
        leaves = lfm2.run_leaves(params32, run)
        for r in range(run.rows):
            seen = {C: 0, A: 0}
            for m, kind in enumerate(run.kinds):
                j, i = seen[kind], first + r * len(run.kinds) + m
                seen[kind] += 1
                L = f"model.layers.{i}."
                for leaf, name in names.items():
                    if leaf not in leaves:
                        continue
                    own = leaf in lfm2.MIXER_LEAVES
                    if own and (leaf.startswith("conv")) != (kind == C):
                        continue
                    a = np.asarray(leaves[leaf][r, j if own else m])
                    tensors[L + name] = a.T if a.ndim == 2 else a
                if kind == C:
                    tensors[L + "conv.conv.weight"] = np.asarray(
                        leaves["conv_w"][r, j]).T[:, None, :]
                for leaf, name in mlp.items():
                    a = np.asarray(leaves[leaf][r, m])
                    if run.dense:
                        tensors[f"{L}feed_forward.{name}.weight"] = a.T
                        continue
                    for e in range(cfg.num_experts):
                        tensors[f"{L}feed_forward.experts.{e}.{name}"
                                f".weight"] = a[e].T
        first += run.rows * len(run.kinds)
    save_file({k: np.ascontiguousarray(np.asarray(v, np.float32))
               for k, v in tensors.items()}, tmp_path / "model.safetensors")
    (tmp_path / "config.json").write_text(json.dumps(HF))
    got_cfg, got = load_llama_params(tmp_path, dtype="float32")
    assert dataclasses.replace(got_cfg, dtype="float32") == cfg
    assert got_cfg.tie_word_embeddings and "lm_head" not in got
    assert jax.tree.structure(got) == jax.tree.structure(params32)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params32)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the selection bias stays float32 whatever is served, in every run
    _, bf16 = load_llama_params(tmp_path, dtype="bfloat16")
    assert bf16["layers"]["expert_bias"].dtype == jnp.float32
    assert bf16["tail1_expert_bias"].dtype == jnp.float32
    assert bf16["tail1_moe_gate"].dtype == jnp.bfloat16


def _mesh(**axes):
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    n = int(np.prod(list(axes.values())))
    return build_mesh(MeshPlan(**axes), devices=jax.devices()[:n])


@pytest.mark.parametrize("what, kw", [
    ("the contiguous K/V layout", {"paged": False}),
    ("the ring prefill", {"mesh": {"seq": 2}}),
    ("a device mesh", {"mesh": {"model": 2}}),
])
def test_what_recurrent_state_refuses_stays_refused(params32, what, kw):
    kw = dict(kw)
    if "mesh" in kw:
        kw["mesh"] = _mesh(**kw["mesh"])
    with pytest.raises(ValueError, match=f"^{what} is not served for "
                                         f"model_type lfm2_moe: .* carry "
                                         f"recurrent state"):
        runner_for(config(), params32, **kw)


def test_quantisation_speculation_and_the_prompt_cache_are_refused(params32):
    from localai_tpu.models.registry import synthetic_params

    with pytest.raises(ValueError, match="^engine.quantization 'int8' is not "
                                         "served for model_type lfm2_moe"):
        synthetic_params(config("bfloat16"), "int8")
    r = runner_for(config(), params32)
    with pytest.raises(ValueError, match="^speculative decoding is not"):
        r.verify_async(np.zeros((SLOTS, 2), np.int32))
    # the same prompt twice: the first's whole chunk is shared with the
    # second BECAUSE the convolution rows behind it were kept (PR 62,
    # engine.paged: a snapshot a registered prompt; no line of this
    # family's), and the token is the same
    first = r.admit(0, PROMPT, temperature=0.0)
    assert r.allocator.snapshots_taken == 1
    assert r.admit(1, PROMPT, temperature=0.0,
                   resident=list(PROMPT)) == first
    assert r.last_prefix_reused == r.total_prefix_reused > 0
    assert r.allocator.snapshots_restored == 1
    assert r.allocator.check_invariants() == []
    assert r.load_prefix(2, r.export_prefix(0, 8), 8) is False


def test_the_scheduler_serves_it_and_counts_its_routed_work(params32):
    """Through ``Scheduler`` as every model is (two steps a dispatch): the
    reply's tokens, the flight ring's routed work a launch (22 expert blocks
    a step, top-2 of 8 held: 44 pairs a live slot a step), the state's
    bytes."""
    import time

    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.utils.tokenizer import ByteTokenizer

    r = runner_for(config(), params32)
    s = Scheduler(r, ByteTokenizer(), multi_step=2)
    try:
        h = s.generate(GenRequest(
            prompt=ByteTokenizer().encode("short convolution"),
            max_new_tokens=6, temperature=0.0, ignore_eos=True), timeout=300)
        assert h.completion_tokens == 6
        deadline = time.monotonic() + 10.0
        while True:     # the dispatch in flight at the reply's end drains
            decode = [x for x in s.flight.snapshot()
                      if x["program"].startswith("decode")]
            if (sum(x["steps"] for x in decode) >= 5
                    or time.monotonic() > deadline):
                break
            time.sleep(0.05)
        assert decode and all(x["live_slots"] == 1 for x in decode)
        for x in decode:
            assert x["local_assignments"] == 22 * 2 * x["steps"]
            assert 22 * x["steps"] <= x["experts_touched"] <= (
                x["local_assignments"])
        m = s.metrics()
        assert m["state_slots_armed"] == 1
        assert m["state_bytes"] == r.state_bytes > 0
        assert m["moe_assignments"] >= 22 * 2 * 5
    finally:
        s.shutdown()


# ---------------------------------------------------------------------------
# (vii) a prompt's small last chunk rides the decode step through the family's
# own forward (PR 61; tests/test_paged_serving.py holds the dense stack's)

# ``c c | a c c | a c``: a dense prefix, the stack and a tail; convolution AND
# attention layers with experts behind them (5 expert blocks a row)
RIDE_HF = {"num_hidden_layers": 7, "layer_types": [C, C, A, C, C, A, C]}
EXPERT_BLOCKS, TOP_K = 5, HF["num_experts_per_tok"]
STREAMS = (RNG.integers(1, 380, 5).tolist(), RNG.integers(1, 380, 11).tolist())
LONG = RNG.integers(1, 380, 13).tolist()         # two chunks: 8, then 5 of 8


def _busy_runner(cfg, params, attn_impl, sampling):
    """Two streams three steps in (slots 0 and 1), a slot that held a stream
    that has ended (2: its rows are what the stream left) and one that never
    held any (3)."""
    r = runner_for(cfg, params, attn_impl, seed=3)
    assert r.rides and r.own_forward
    for prompt in (*STREAMS, SHORT):
        r.admit(r.acquire_slot(), prompt, **{**sampling, "seed": 7})
    for _ in range(3):
        r.step()
    r.release(2)
    conv = np.asarray(r.state.rec["conv"])
    assert conv[:, :3].any(axis=(0, 2, 3)).all() and not conv[:, 3].any()
    return r


@pytest.fixture(scope="module")
def ride_models():
    return {dtype: (cfg, mdl.init_params(jax.random.key(0), cfg))
            for dtype in ("float32", "bfloat16")
            for cfg in [config(dtype=dtype, **RIDE_HF)]}


GREEDY, SEEDED = dict(temperature=0.0), dict(temperature=0.8, top_p=0.95,
                                              seed=11)


@pytest.mark.parametrize("dtype, attn_impl, prompt, sampling", [
    ("float32", "xla", SHORT, GREEDY), ("float32", "xla", SHORT, SEEDED),
    ("float32", "xla", LONG, GREEDY), ("float32", "xla", LONG, SEEDED),
    # (the kernels in the interpreter: 20 s)
    ("bfloat16", "pallas_interpret", LONG, SEEDED)],
    ids=lambda v: {id(SHORT): "fresh", id(LONG): "resumed",
                   id(GREEDY): "greedy", id(SEEDED): "seeded"}.get(id(v), v))
def test_a_ride_leaves_what_the_step_then_the_chunk_leave(
        ride_models, dtype, attn_impl, prompt, sampling):
    """``_decode_prefill_paged_fn`` through ``lfm2.forward(ride=bucket)``
    against the two programs it stands for, on the same state: a decode step
    (the new slot not live: its row moves no state), then the prompt's last
    chunk; the new slot is the one a finished stream left its rows in, and
    the chunk is the prompt's only one (``fresh``: from zero) or its second
    (from what the first left). The same S tokens and first token, the same
    pool, convolution rows of EVERY slot and sampling state to the bit, the
    same streams afterwards; the launch's token-expert pairs are the step's
    plus the chunks', and the experts it touched at most the sum and at least
    the larger (one block's rows share what they touch: the one read)."""
    cfg, params = ride_models[dtype]

    def serve(ride):
        r = _busy_runner(cfg, params, attn_impl, sampling)
        adm = r.begin_admit(r.acquire_slot(2), prompt, **sampling)
        assert adm.slot == 2
        if len(prompt) > 8:
            assert adm.ride_bucket is None and adm.launch_chunk() is False
        assert adm.ride_bucket == 8
        if ride:
            assert adm.launch_chunk(ride=True) is True
            out = np.asarray(adm.first)
            step, first, routed = out[:SLOTS], int(out[SLOTS]), out[SLOTS + 1:]
            assert adm.first_token() == first
        else:
            out = np.asarray(r.step_async())
            step, routed = out[:SLOTS], out[SLOTS:]
            assert adm.launch_chunk() is True
            out = np.asarray(adm.first)
            first, routed = int(out[0]), np.stack([routed, out[1:]])
        after = [r.step() for _ in range(3)]
        return [step, first, *after], routed, families.host_state(r)

    (want, apart, want_state), (got, routed, got_state) = (
        serve(False), serve(True))
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert len(want_state) == len(got_state)
    for a, b in zip(want_state, got_state):
        np.testing.assert_array_equal(a, b)
    # two live streams and the prompt's real tokens, top-2 in 5 blocks: rows
    # past ``length`` and slots with no stream chose no expert
    assert routed[1] == apart[:, 1].sum() == (
        (2 + len(prompt)) * TOP_K * EXPERT_BLOCKS)
    assert apart[:, 0].max() <= routed[0] <= apart[:, 0].sum()
    conv = got_state[-2]
    assert conv.shape[1] == SLOTS and not conv[:, 3].any()


def test_a_rides_padded_rows_and_idle_slots_move_nothing(ride_models):
    """The ride's rows that are nobody's: the chunk's 3 rows past ``length``
    and the step's rows of the slots with no stream (the new slot's own
    among them), whatever token they hold, choose no expert and move no
    state: the pool's live blocks, every slot's convolution rows, the tokens
    and the routed count are the same to the bit."""
    from localai_tpu.engine.runner import _prompt_counts_row

    cfg, params = ride_models["float32"]

    def ride(junk):
        r = _busy_runner(cfg, params, "xla", dict(temperature=0.0))
        adm = r.begin_admit(r.acquire_slot(2), SHORT, temperature=0.0)
        row = np.asarray(r.allocator.table_row(adm.slot), np.int32)
        r._arm(adm.arm_args, row)
        # the tokens the idle slots' step rows feed
        r.state = dataclasses.replace(r.state, tokens=jnp.where(
            r.state.active, r.state.tokens, junk))
        chunk = np.full((1, 8), junk, np.int32)
        chunk[0, :5] = SHORT
        r.kv, r.state, out = r._decode_prefill_paged(
            r.params, r.kv, r.state, r.block_tables, chunk, np.int32(5),
            np.int32(0), row, np.int32(adm.slot),
            _prompt_counts_row(cfg.vocab_size, SHORT), bucket=8)
        live = sorted({b for s in (0, 1, 2)
                       for b in r.allocator.table_row(s) if b})
        out = np.asarray(out)
        keep = np.array([0, 1, SLOTS, SLOTS + 1, SLOTS + 2])
        return (out[keep], np.asarray(r.state.rec["conv"]),
                np.asarray(r.kv.k[:, live]), np.asarray(r.kv.v[:, live]))

    for a, b in zip(ride(0), ride(377)):
        np.testing.assert_array_equal(a, b)


def test_the_scheduler_rides_it_and_the_texts_are_the_same(ride_models):
    """Through ``Scheduler``: the same three requests with the arrival's
    chunk riding the decode step and with it stepped aside (a neighbour
    under a constraint that allows every token: the loop's synchronous
    branch, the chunk a launch of its own) return the same tokens at
    temperature 0, and the ride's one ``decode_chunk`` row carries the routed
    work of both halves behind its S + 1 tokens."""
    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.utils.tokenizer import ByteTokenizer

    cfg, params = ride_models["float32"]

    class Anything:
        done = False

        def allowed_mask(self):
            return np.zeros(cfg.vocab_size, np.float32)

        def advance(self, tid):
            pass

    def wait(pred):
        import time

        deadline = time.monotonic() + 120.0
        while not pred() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pred()

    def serve(aside):
        r = runner_for(cfg, params)
        s = Scheduler(r, ByteTokenizer(), multi_step=1)
        greedy = dict(temperature=0.0, ignore_eos=True)
        try:
            a = s.submit(GenRequest(prompt=STREAMS[0], max_new_tokens=40,
                                    **greedy))
            b = s.submit(GenRequest(
                prompt=STREAMS[1], max_new_tokens=40,
                constraint=Anything() if aside else None, **greedy))
            wait(lambda: min(a.completion_tokens, b.completion_tokens) >= 3)
            c = s.generate(GenRequest(prompt=SHORT, max_new_tokens=6,
                                      **greedy), timeout=300)
            texts = [h.result(300).token_ids for h in (a, b)] + [c.token_ids]
        finally:
            s.shutdown()
        rows = [x for x in s.flight.snapshot(limit=256)
                if x["program"] == "decode_chunk"]
        return texts, s.total_chunk_rides, s.total_prefill_chunks, rows

    rode, rides, chunks, rows = serve(False)
    aside, no_rides, chunks_aside, no_rows = serve(True)
    assert rode == aside and [len(t) for t in rode] == [40, 40, 6]
    # (the two streams' own chunks are 5 and 8 + 3 tokens: launches of their
    # own, the first into an idle engine; the second's last of 3 rode the
    # first's step where nothing stepped aside)
    assert (rides, no_rides, chunks, chunks_aside) == (2, 0, 4, 4)
    assert not no_rows and [x["chunk_tokens"] for x in rows] == [3, 5]
    row = rows[1]
    assert (row["steps"], row["live_slots"], row["chunk_tokens"],
            row["chunk_bucket"]) == (1, 2, 5, 8)
    assert row["local_assignments"] == (2 + 5) * TOP_K * EXPERT_BLOCKS
    assert EXPERT_BLOCKS <= row["experts_touched"] <= (
        row["local_assignments"])
