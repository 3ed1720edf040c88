"""Falcon-H1 (``model_type: falcon_h1``) on the normal serving path: a Mamba-2
mixer and grouped-query attention side by side in every layer, a dense gated
MLP behind them, muP multipliers everywhere; a cache layer of the paged K/V
pool AND a row of per-slot recurrent state a layer. CPU, tiny widths (D 64, 3
layers, 4 mixer heads of 8 in 2 groups, state 16, 4 / 2 attention heads),
seeded weights of the program's own draw, every multiplier off 1.

The served path is the runner's own programs (``_prefill_paged_fn`` /
``_decode_paged_fn``), driven by ``admit`` and ``step`` and tapped for the
logits they sample from; the reference is the benchmark's plain float32
family (benchmark/reference/falcon_h1_family.py, written from the published
keys) run as the benchmark runs it (harness/refcheck.py): the FULL forward
over prompt + served tokens, no cache, no state carried.
"""

import dataclasses
from functools import partial

import families
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from families import refcheck, reference_logits, served_logits, tap

from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import falcon_h1 as fh
from localai_tpu.models import llama as mdl
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import LlamaConfig
from localai_tpu.models.registry import synthetic_params

HF = {"model_type": "falcon_h1", "vocab_size": 384, "hidden_size": 64,
      "intermediate_size": 96, "num_hidden_layers": 3,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "rope_theta": 1e11, "rms_norm_eps": 1e-5,
      "max_position_embeddings": 512, "tie_word_embeddings": False,
      "mamba_n_heads": 4, "mamba_d_head": 8, "mamba_d_ssm": 32,
      "mamba_n_groups": 2, "mamba_d_state": 16, "mamba_d_conv": 4,
      "mamba_chunk_size": 4, "mamba_conv_bias": True, "mamba_rms_norm": True,
      "mamba_norm_before_gate": False, "mamba_proj_bias": False,
      "embedding_multiplier": 3.1, "lm_head_multiplier": 0.37,
      "attention_in_multiplier": 0.8, "attention_out_multiplier": 0.21,
      "key_multiplier": 0.3, "ssm_in_multiplier": 0.6,
      "ssm_out_multiplier": 0.45,
      "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.7],
      "mlp_multipliers": [0.4, 0.15]}
LAYERS, SLOTS = 3, 4
RNG = np.random.default_rng(55)
PROMPT = RNG.integers(1, 380, 11).tolist()      # two chunks: 8 + 3 of 8
SHORT = RNG.integers(1, 380, 5).tolist()        # one chunk, 3 padded rows
STEPS = 4
# float32 serving: what is left between the two is summation order (the
# chunk's recurrence is the reference's, token by token); logits spread ~1.5
F32_TOL = 3e-5
# bfloat16 serving under the program's draw (logits up to ~5): every
# activation rounded to 8 bits some thirty times through 3 layers of three
# branches, the conv rows kept in bfloat16, logits written in bfloat16; S
# stays float32. Read 0.065 at the worst of 5 x 384 logits, 0.012 in the
# mean; int8 weights are held against THEIR dequantised values (the
# reference runs on the served weights), so they add no error of their own
BF16_TOL, BF16_MEAN_TOL = 0.2, 0.04


@pytest.fixture(scope="module")
def family():
    return families.reference_family("falcon_h1_family",
                                     "tests/test_falcon_h1.py")


config = partial(families.config, HF)


@pytest.fixture(scope="module")
def params32():
    return mdl.init_params(jax.random.key(0), config())


def served_params(params32, dtype: str, quantization: str):
    """``init_params`` under ``dtype`` (it draws in float32 and casts), then
    models.quant's int8 where asked."""
    params = jax.tree.map(lambda a: a.astype(dtype), params32)
    return qnt.quantize_params(params, quantization) if quantization else (
        params)


STEP = ("xla", "kernel")


def runner_for(cfg, params, step="xla", **kw) -> ModelRunner:
    """``step``: the decode step's recurrence as ``ssm_step`` (XLA) or as
    ops.gdn's kernel in the Pallas interpreter under the same XLA attention
    (steered here, before the first program is traced: ``attn_impl`` would
    take attention to its kernels too)."""
    kw = {"num_slots": SLOTS, "max_ctx": 64, "paged": True,
          "kv_block_tokens": 8, "prefill_chunk": 8, "prefill_buckets": [8],
          "attn_impl": "xla", "kv_dtype": cfg.dtype, **kw}
    r = ModelRunner(cfg, params, **kw)
    assert r.family_kernels is None
    if step == "kernel":
        r.family_kernels = True
    return r


# logits that spread, not zeros
agree = partial(families.agree, spread=1.0)


# ---------------------------------------------------------------------------
# (i) the served path against the plain reference


@pytest.mark.parametrize("dtype, quantization, step", [
    ("float32", "", "xla"), ("bfloat16", "", "kernel"),
    ("bfloat16", "int8", "kernel")])
def test_served_logits_match_the_reference(family, monkeypatch, params32,
                                           dtype, quantization, step):
    """A prompt over two chunks (the second with padded rows), then decode
    steps: the logits each program samples from against the full forward,
    in float32, in bfloat16 and over int8 weights (float32 under the
    kernel: ``test_mathematics_left_out...``'s first case)."""
    cfg = config(dtype)
    params = served_params(params32, dtype, quantization)
    if quantization:
        for name in ("ssm_in", "ssm_out", "wq", "wk", "wv", "wo", "w_gate",
                     "w_up", "w_down"):
            assert params["layers"][name].q.dtype == jnp.int8, name
        assert params["embed"].q.dtype == params["lm_head"].q.dtype == jnp.int8
        for name in ("ssm_conv", "ssm_conv_bias", "ssm_A_log", "ssm_D",
                     "ssm_dt_bias", "ssm_norm", "attn_norm", "mlp_norm"):
            assert params["layers"][name].dtype == jnp.bfloat16, name
    r = runner_for(cfg, params, step)
    served, tokens = served_logits(r, tap(r), 1, PROMPT, STEPS)
    assert r.admit_programs == 1 + 2            # the arming and two chunks
    assert r.kv.k.shape[0] == LAYERS == cfg.cache_layers
    assert r.state.rec["S"].shape == (LAYERS, SLOTS, 4, 16, 8)
    assert r.state.rec["S"].dtype == jnp.float32
    assert r.state.rec["conv"].shape == (LAYERS, SLOTS, 3, 32 + 2 * 2 * 16)
    assert r.state_bytes == sum(a.nbytes for a in r.state.rec.values())
    # the decode program that served them holds the step's kernel once (the
    # layer scan is rolled), or not at all
    traced = str(jax.make_jaxpr(r._decode_paged_fn)(
        r.params, r.kv, r.state, r.block_tables))
    assert traced.count("name=ssm_state_step") == (step == "kernel")
    ref = reference_logits(family, params, HF, PROMPT, tokens, monkeypatch)
    if dtype == "float32":
        agree(served, ref, F32_TOL)
        assert (served.argmax(-1) == ref.argmax(-1)).all()
    else:
        agree(served, ref, BF16_TOL)
        assert np.abs(served - ref).mean() < BF16_MEAN_TOL


def test_a_padded_row_and_an_idle_slot_move_nothing(family, monkeypatch,
                                                    params32):
    """5 real tokens in a bucket of 8: the 3 rows past ``length`` are the
    identity on S and on the conv rows, whatever they hold (the same prompt
    into another slot behind junk leaves that slot's state bit for bit the
    first's); a decode step leaves the slots that hold no stream exactly as
    they were (zero); a slot that empties and refills starts from zero."""
    from localai_tpu.engine.runner import _prompt_counts_row

    cfg = config()
    r = runner_for(cfg, params32, "kernel")
    seen = tap(r)
    served, tokens = served_logits(r, seen, 2, SHORT, steps=3)
    ref = reference_logits(family, params32, HF, SHORT, tokens, monkeypatch)
    agree(served, ref, F32_TOL)
    for name in ("S", "conv"):
        assert np.asarray(r.state.rec[name][:, 2]).any()
        assert not np.asarray(r.state.rec[name][:, [0, 1, 3]]).any()

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
        return {n: np.asarray(r.state.rec[n][:, slot])
                for n in ("S", "conv")}, int(tok)

    zeros, tok = chunk_into(0, 0)
    junk, tok_junk = chunk_into(3, 377)
    assert tok == tok_junk == tokens[0]
    for name in ("S", "conv"):
        np.testing.assert_array_equal(zeros[name], junk[name])
    # slot 2 held SHORT's stream for three steps: released and armed again,
    # the same prompt reads the same logits
    r.release(2)
    r._free_slots.remove(2)
    again, tokens_again = served_logits(r, seen, 2, SHORT, steps=3)
    assert tokens_again == tokens
    np.testing.assert_array_equal(again, served)


# ---------------------------------------------------------------------------
# (ii) one failing case a term: mathematics left out fails (i)'s tolerance


FORCED = RNG.integers(1, 380, 3).tolist()       # fed a step, whatever is read


def direct_logits(cfg, params, kernels=True, steps=True):
    """models.falcon_h1.forward without the runner: SHORT as one chunk (3
    padded rows) into slot 1 of 2 over a contiguous cache, then (with
    ``steps``) FORCED one token a decode step (slot 0 idle), the step's
    recurrence as the kernel (interpreted) or as XLA: [1 + len(FORCED), V]
    logits, or the chunk's [1, V] alone."""
    from localai_tpu.engine import kvcache as kvc

    n, bucket, ctx, slots = len(SHORT), 8, 16, 2

    @jax.jit
    def run(params, chunk, forced):
        shape = (cfg.cache_layers, slots, cfg.num_kv_heads, ctx, cfg.hd)
        kv = (jnp.zeros(shape, jnp.float32), jnp.zeros(shape, jnp.float32))
        rope = mdl.rope_table(cfg, ctx)
        slot = jnp.int32(1)
        hidden, kv, rec, _ = fh.forward(
            cfg, params, chunk, jnp.arange(bucket)[None],
            kvc.prefill_write(slot, jnp.int32(0)), kv,
            kvc.prefill_mask(cfg, bucket, n), rope,
            rec=fh.init_rec(cfg, slots),
            valid=(jnp.arange(bucket) < n)[None], slot=slot,
            fresh=jnp.bool_(True), kernels=kernels)
        first = mdl.logits_from_hidden(cfg, params, hidden[0, n - 1])
        if not steps:
            return first[None]

        def step(carry, xs):
            kv, rec = carry
            token, i = xs
            positions = jnp.stack([jnp.int32(0), n + i])
            hidden, kv, rec, _ = fh.forward(
                cfg, params, jnp.stack([jnp.int32(0), token])[:, None],
                positions[:, None], kvc.decode_write(positions), kv,
                kvc.decode_mask(cfg, positions, ctx), rope, rec=rec,
                valid=jnp.array([[False], [True]]), kernels=kernels)
            return (kv, rec), mdl.logits_from_hidden(cfg, params,
                                                     hidden[1, 0])

        _, rest = jax.lax.scan(step, (kv, rec), (
            forced, jnp.arange(len(FORCED), dtype=jnp.int32)))
        return jnp.concatenate([first[None], rest])

    chunk = np.zeros((1, bucket), np.int32)
    chunk[0, :n] = SHORT
    return np.asarray(run(params, chunk, np.asarray(FORCED, np.int32)))


@pytest.fixture(scope="module")
def forced_reference(family, params32):
    """The family's full forward over SHORT + FORCED: the logits that read
    each of FORCED and the token behind them."""
    saved, refcheck.LETTERS = refcheck.LETTERS, slice(0, HF["vocab_size"])
    try:
        return refcheck.reference_logits(
            params32, family, HF, np.array([SHORT + FORCED], np.int32),
            1 + len(FORCED))[0]
    finally:
        refcheck.LETTERS = saved


def nothing(monkeypatch):
    return {}


def multiplier_left_out(key, index=None):
    """The program serves with the multiplier at 1; the reference keeps it."""
    def case(monkeypatch):
        if index is None:
            return {key: 1.0}
        ms = list(HF[key])
        ms[index] = 1.0
        return {key: ms}

    case.__name__ = f"no_{key}" + ("" if index is None else f"_{index}")
    return case


def no_decay(monkeypatch):
    monkeypatch.setattr(fh, "log_decay", lambda A_log, dt: jnp.zeros_like(dt))
    return {}


def no_skip_term(monkeypatch):
    monkeypatch.setattr(fh, "skip", lambda D, x: jnp.zeros_like(x))
    return {}


def gate_behind_the_norm(monkeypatch):
    def norm_first(y, z, w, groups, eps):
        yg = y.reshape(*y.shape[:-1], groups, -1)
        yg = yg * jax.lax.rsqrt(jnp.mean(yg * yg, axis=-1, keepdims=True)
                                + eps)
        return (yg.reshape(y.shape) * w.astype(jnp.float32)
                * jax.nn.silu(z.astype(jnp.float32)))

    monkeypatch.setattr(fh, "gate_norm", norm_first)
    return {}


def one_norm_over_both_groups(monkeypatch):
    real = fh.gate_norm
    monkeypatch.setattr(fh, "gate_norm",
                        lambda y, z, w, groups, eps: real(y, z, w, 1, eps))
    return {}


def one_groups_b_and_c_for_every_head(monkeypatch):
    monkeypatch.setattr(
        fh, "heads_of_groups",
        lambda x, heads: jnp.repeat(x[:, :, :1], heads, axis=2))
    return {}


def no_conv_bias(monkeypatch):
    real = fh.causal_conv
    monkeypatch.setattr(
        fh, "causal_conv",
        lambda cat, w, bias, T: real(cat, w, jnp.zeros_like(bias), T))
    return {}


def conv_state_one_row_short(monkeypatch):
    """The slot keeps K - 2 rows: the oldest of its K - 1 reads zero."""
    real = mdl.conv_rows
    monkeypatch.setattr(
        mdl, "conv_rows",
        lambda cat, n, K: real(cat, n, K).at[:, 0].set(0))
    return {}


def in_the_steps(left_out):
    """A term that only a decode step meets: the case runs them."""
    left_out.steps = True
    return left_out


conv_state_one_row_short = in_the_steps(conv_state_one_row_short)


@in_the_steps
def delta_correction_in_the_kernels_step(monkeypatch):
    """The DeltaNet's step where the Mamba-2 step belongs."""
    from localai_tpu.ops import gdn

    monkeypatch.setattr(gdn, "plain_step", gdn.head_step)
    return {}


LEFT_OUT = [
    *(multiplier_left_out(k) for k in (
        "embedding_multiplier", "lm_head_multiplier",
        "attention_in_multiplier", "attention_out_multiplier",
        "key_multiplier", "ssm_in_multiplier", "ssm_out_multiplier")),
    *(multiplier_left_out("ssm_multipliers", i) for i in range(5)),
    *(multiplier_left_out("mlp_multipliers", i) for i in range(2)),
    no_decay, no_skip_term, gate_behind_the_norm, one_norm_over_both_groups,
    one_groups_b_and_c_for_every_head, no_conv_bias,
    conv_state_one_row_short, delta_correction_in_the_kernels_step]


@pytest.mark.parametrize("kernels", [True, None], ids=["kernel", "xla"])
def test_the_forward_alone_matches_the_reference(params32, forced_reference,
                                                 kernels):
    """Nothing left out: the harness of the failing cases below reads the
    reference's logits, the step as the kernel and as XLA."""
    agree(direct_logits(config(), params32, kernels), forced_reference,
          F32_TOL)


@pytest.mark.parametrize("left_out", LEFT_OUT, ids=lambda f: f.__name__)
def test_mathematics_left_out_fails_the_tolerance(monkeypatch, params32,
                                                  forced_reference, left_out):
    """Each of the 14 multipliers, the decay, ``D x``, the gate's place, the
    norm's groups, the groups' B and C and the conv's bias, in the chunk
    (two sub-chunks of ``ssd_chunk``; what stands around the recurrence is
    the one code a decode step runs too); the conv state's oldest row and
    the DeltaNet's correction where none belongs, which only a decode step
    behind the chunk meets (under the kernel)."""
    steps = getattr(left_out, "steps", False)
    served = direct_logits(config(**left_out(monkeypatch)), params32,
                           steps=steps)
    ref = forced_reference[:len(served)]
    assert np.abs(served - ref).max() > 100 * F32_TOL
    if steps:       # the chunk's logit is right; the steps' are not
        assert np.abs(served[0] - ref[0]).max() < F32_TOL


# ---------------------------------------------------------------------------
# (iii) the runner's state hook, the synthetic draw, the loader


_SMALL = {"vocab_size": 384, "hidden_size": 64, "num_attention_heads": 4,
          "num_key_value_heads": 2, "max_position_embeddings": 512}
OTHER_HF = {
    "llama": {"model_type": "llama", **_SMALL, "intermediate_size": 96,
              "num_hidden_layers": 2},
    # tests/test_qwen3_next.py's and tests/test_afmoe.py's, one period each
    "qwen3_next": {
        "model_type": "qwen3_next", **_SMALL, "num_hidden_layers": 4,
        "head_dim": 32, "num_experts": 4, "num_experts_per_tok": 3,
        "full_attention_interval": 4, "linear_num_key_heads": 2,
        "linear_num_value_heads": 4, "linear_key_head_dim": 16,
        "linear_value_head_dim": 16, "partial_rotary_factor": 0.25,
        "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
        "expert_parallel": {"size": 2, "rank": 1}},
    "afmoe": {
        "model_type": "afmoe", **_SMALL, "intermediate_size": 96,
        "num_hidden_layers": 5, "head_dim": 16, "sliding_window": 8,
        "global_attn_every_n_layers": 4,
        "layer_types": ["sliding_attention"] * 4 + ["full_attention"],
        "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 2,
        "moe_intermediate_size": 32, "num_shared_experts": 1,
        "score_func": "sigmoid", "route_norm": True, "route_scale": 2.448,
        "expert_parallel": {"size": 2, "rank": 1}}}


@pytest.mark.parametrize("model_type, holds", [
    ("falcon_h1", ("S", "conv")), ("qwen3_next", ("S", "conv", "routed")),
    ("afmoe", ("routed",)), ("llama", None)])
def test_the_runner_builds_rec_through_the_familys_hook(model_type, holds):
    """``ModelRunner._init_rec`` names no family: a recurrent one's module
    builds its state (``init_rec``), a routed model gets the counter, every
    other model None."""
    import inspect

    hf = HF if model_type == "falcon_h1" else OTHER_HF[model_type]
    cfg = dataclasses.replace(LlamaConfig.from_hf(hf), dtype="float32")
    # (no program runs: leaves of the right shapes are all a runner asks)
    zeros = jax.tree.map(lambda shape: jnp.zeros(shape, jnp.float32),
                         mdl.param_shapes(cfg),
                         is_leaf=lambda x: isinstance(x, tuple))
    r = ModelRunner(cfg, zeros, num_slots=2, max_ctx=32, paged=True,
                    kv_block_tokens=8, attn_impl="xla", kv_dtype="float32")
    rec = r.state.rec
    assert (rec if holds is None else tuple(sorted(rec))) == (
        holds if holds is None else tuple(sorted(holds)))
    if cfg.recurrent:
        fam = mdl.family_module(cfg)
        want = fam.init_rec(cfg, 2)
        assert {k: (v.shape, v.dtype) for k, v in rec.items()} == {
            k: (v.shape, v.dtype) for k, v in want.items()}
        assert r.state_bytes == sum(a.nbytes for a in rec.values()) > 0
    source = inspect.getsource(ModelRunner._init_rec)
    assert "qwen3_next" not in source and "falcon_h1" not in source


def test_the_int8_draw_states_its_deviations():
    """``synthetic_params`` with ``int8``: every projection and both tables
    are int8 at the deviation ``leaf_std`` states (a column's, for
    ``ssm_in``), the rest the family's plain draw; the runner serves it."""
    cfg = config("bfloat16")
    params = synthetic_params(cfg, "int8", seed=3)
    shapes = mdl.param_shapes(cfg)
    assert {k: v.q.shape if hasattr(v, "q") else v.shape
            for k, v in params["layers"].items()} == shapes["layers"]
    for name in ("ssm_in", "w_down", "wk"):
        leaf = params["layers"][name]
        deq = np.asarray(qnt.dequantize_tensor(leaf))
        want = np.broadcast_to(np.asarray(fh.leaf_std(cfg, name)),
                               deq.shape[-1:])
        got = deq.reshape(-1, deq.shape[-1]).std(axis=0)
        # a column of 3 x 64 uniform integers: its deviation within a fifth
        np.testing.assert_allclose(got, want, rtol=0.2)
    np.testing.assert_array_equal(
        np.asarray(params["layers"]["ssm_D"], np.float32), 1.0)
    a = np.exp(np.asarray(params["layers"]["ssm_A_log"], np.float32))
    assert 1.0 <= a.min() and a.max() < 16.1
    with pytest.raises(ValueError, match="int8_w8a8.*falcon_h1"):
        synthetic_params(cfg, "int8_w8a8")


def test_outlier_channels_stand_in_the_norms_that_feed_the_projections():
    cfg = config(hidden_size=384, num_hidden_layers=1)
    draw = partial(fh.init_leaf, cfg=cfg)
    for name in fh.OUTLIER_NORMS:
        gain = np.asarray(draw(jax.random.key(1), (2, 384), name,
                               jnp.float32))
        assert ((gain == fh.OUTLIER_GAIN).sum(axis=-1) == 2).all()
        assert ((gain == 1.0).sum(axis=-1) == 382).all()
    assert (np.asarray(draw(jax.random.key(1), (2, 384), "mlp_norm",
                            jnp.float32)) == 1.0).all()
    # the deviations count the outliers in: the same logits' spread
    assert fh.leaf_std(cfg, "lm_head") < fh.leaf_std(
        config(num_hidden_layers=1), "lm_head") * (64 / 384) ** 0.5


def test_a_checkpoint_in_the_published_layout_loads_to_the_served_leaves(
        tmp_path, params32):
    """``models/loader.py`` for the family: a checkpoint written HERE under
    the published names (``mamba.in_proj``, ``mamba.conv1d`` as [C, 1, K],
    ``feed_forward.*``, ``pre_ff_layernorm``, ``final_layernorm``) loads to
    the leaves it was written from."""
    import json

    from safetensors.numpy import save_file

    from localai_tpu.models.loader import load_llama_params

    cfg = config()
    tensors = {"model.embed_tokens.weight": params32["embed"],
               "model.final_layernorm.weight": params32["final_norm"],
               "lm_head.weight": params32["lm_head"].T}
    names = {"attn_norm": "input_layernorm.weight",
             "ssm_in": "mamba.in_proj.weight",
             "ssm_conv_bias": "mamba.conv1d.bias", "ssm_A_log": "mamba.A_log",
             "ssm_D": "mamba.D", "ssm_dt_bias": "mamba.dt_bias",
             "ssm_norm": "mamba.norm.weight",
             "ssm_out": "mamba.out_proj.weight",
             "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
             "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
             "mlp_norm": "pre_ff_layernorm.weight",
             "w_gate": "feed_forward.gate_proj.weight",
             "w_up": "feed_forward.up_proj.weight",
             "w_down": "feed_forward.down_proj.weight"}
    for i in range(LAYERS):
        for leaf, name in names.items():
            a = np.asarray(params32["layers"][leaf][i])
            tensors[f"model.layers.{i}.{name}"] = a.T if a.ndim == 2 else a
        tensors[f"model.layers.{i}.mamba.conv1d.weight"] = np.asarray(
            params32["layers"]["ssm_conv"][i]).T[:, None, :]
    save_file({k: np.ascontiguousarray(np.asarray(v, np.float32))
               for k, v in tensors.items()}, tmp_path / "model.safetensors")
    (tmp_path / "config.json").write_text(json.dumps(HF))
    got_cfg, got = load_llama_params(tmp_path, dtype="float32")
    assert dataclasses.replace(got_cfg, dtype="float32") == cfg
    assert jax.tree.structure(got) == jax.tree.structure(params32)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(params32)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# (iv) what is refused, with one sentence each


def _mesh(**axes):
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    n = int(np.prod(list(axes.values())))
    return build_mesh(MeshPlan(**axes), devices=jax.devices()[:n])


@pytest.mark.parametrize("what, kw", [
    ("the contiguous K/V layout", {"paged": False}),
    ("pipeline parallelism", {"paged": False, "mesh": {"pipe": 3}}),
    ("the ring prefill", {"mesh": {"seq": 2}}),
    ("a device mesh", {"mesh": {"model": 2}}),
])
def test_what_recurrent_state_refuses_stays_refused(params32, what, kw):
    kw = dict(kw)
    if "mesh" in kw:
        kw["mesh"] = _mesh(**kw["mesh"])
    with pytest.raises(ValueError, match=f"^{what} is not served for "
                                         f"model_type falcon_h1: .* carry "
                                         f"recurrent state"):
        runner_for(config(), params32, **kw)


def test_speculation_and_a_slots_resident_rows_are_refused(params32):
    r = runner_for(config(), params32)
    with pytest.raises(ValueError, match="^speculative decoding is not"):
        r.verify_async(np.zeros((SLOTS, 2), np.int32))
    # the same prompt twice: the first's whole chunk (a block of 8) is shared
    # with the second BECAUSE the state behind it was kept (PR 62,
    # engine.paged: a snapshot a registered prompt, restored in front of the
    # tail; no line of this family's), and the token is the same; a SLOT's
    # resident record is still no reason to skip a token
    first = r.admit(0, PROMPT, temperature=0.0)
    assert r.allocator.snapshots_taken == 1
    assert r.admit(1, PROMPT, temperature=0.0,
                   resident=list(PROMPT)) == first
    assert (r.last_prefix_reused, r.total_prefix_reused) == (8, 8)
    assert r.allocator.snapshots_restored == 1
    assert r.allocator.check_invariants() == []
    assert r.reusable_prefix(2, list(PROMPT), list(PROMPT), valid_n=11) == 0
    # the prompt cache's import: rows of keys without the state behind them
    assert r.load_prefix(2, r.export_prefix(0, 8), 8) is False


def test_the_scheduler_serves_it_and_the_state_is_in_the_memory_view(
        params32):
    """Through ``Scheduler`` as every model is (two steps a dispatch): the
    reply's tokens, the flight ring's ``live_slots`` and ``steps``, the armed
    slot counted, the state's bytes beside the pool's in ``/debug/devices``
    and as a gauge."""
    import time

    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.obs import device as obs_device
    from localai_tpu.obs import metrics as obs_metrics
    from localai_tpu.utils.tokenizer import ByteTokenizer

    r = runner_for(config(), params32)
    s = Scheduler(r, ByteTokenizer(), multi_step=2)
    try:
        h = s.generate(GenRequest(
            prompt=ByteTokenizer().encode("state"), max_new_tokens=6,
            temperature=0.0, ignore_eos=True), timeout=120)
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
        assert s.metrics()["state_slots_armed"] == 1
        # /debug/devices' census and the localai_hbm_live_bytes gauge of
        # the same category: the state's arrays beside the pool's
        known = obs_device.known_arrays([r])
        assert sum(a.nbytes for a in known["recurrent_state"]) == (
            r.state_bytes) == s.metrics()["state_bytes"]
        census = obs_device.hbm_census(known, obs_metrics.Registry())
        assert census["by_category"]["recurrent_state"] >= r.state_bytes
    finally:
        s.shutdown()


@pytest.mark.parametrize("hf, built", [
    ({"model_type": "jamba_like", "hidden_size": 64, "mamba_d_state": 16},
     None),
    ({"model_type": "mistral", "hidden_size": 64, "num_hidden_layers": 2},
     "LlamaConfig"),
    ({"model_type": "some_dense_type", "hidden_size": 64},
     "LlamaConfig"),
    (HF, "FalconH1Config")])
def test_from_hf_refuses_an_unknown_type_with_a_mixers_keys(hf, built):
    """A ``model_type`` that ``from_hf`` does not know is a dense llama stack
    of the file's widths, as before, unless its keys carry a state-space
    mixer that such a stack would leave out."""
    if built is None:
        with pytest.raises(ValueError, match="state-space mixer's keys "
                                             r"\(mamba_d_state"):
            LlamaConfig.from_hf(hf)
    else:
        assert type(LlamaConfig.from_hf(hf)).__name__ == built


def test_keys_the_family_does_not_write_are_refused():
    for key, value in (("mamba_norm_before_gate", True),
                       ("mamba_conv_bias", False), ("mamba_proj_bias", True),
                       ("mamba_d_ssm", 48),
                       ("rope_scaling", {"type": "linear", "factor": 2.0})):
        with pytest.raises(ValueError, match=key):
            LlamaConfig.from_hf({**HF, key: value})
