"""Qwen3-Next (``model_type: qwen3_next``) on the normal serving path:
periods of three Gated DeltaNet layers and one gated full-attention layer,
each followed by routed experts with a shared expert; per-slot recurrent
state beside the paged K/V pool. CPU, tiny widths, seeded random weights
(norm gains, ``A_log`` and ``dt_bias`` included), 2 periods, 4 of 8 experts
held (``expert_parallel`` size 2, rank 1).

The served path is the runner's own programs (``_prefill_paged_fn`` /
``_decode_paged_fn``), driven by ``admit`` and ``step`` and tapped for the
logits they sample from; the reference is the benchmark's plain float32
family (benchmark/reference/qwen3_next_family.py, written from the published
description) run as the benchmark runs it (harness/refcheck.py): the FULL
forward over prompt + served tokens, no cache, no state carried.
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
from families import (ROOT, agree, reference_logits, served_logits, spec,
                      tap)

from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import llama as mdl
from localai_tpu.models import qwen3_next as qn
from localai_tpu.models.llama import LlamaConfig
from localai_tpu.models.registry import synthetic_params

HF = {"model_type": "qwen3_next", "vocab_size": 384, "hidden_size": 64,
      "num_hidden_layers": 8, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 32, "rope_theta": 1e7,
      "rms_norm_eps": 1e-6, "max_position_embeddings": 512,
      "tie_word_embeddings": False, "num_experts": 4,
      "num_experts_per_tok": 3, "full_attention_interval": 4,
      "linear_num_key_heads": 2, "linear_num_value_heads": 4,
      "linear_key_head_dim": 16, "linear_value_head_dim": 16,
      "linear_conv_kernel_dim": 4, "partial_rotary_factor": 0.25,
      "moe_intermediate_size": 32, "shared_expert_intermediate_size": 32,
      "norm_topk_prob": True, "expert_parallel": {"size": 2, "rank": 1}}
PERIODS, G = 2, 3
RNG = np.random.default_rng(41)
PROMPT = RNG.integers(1, 380, 23).tolist()      # two chunks: 16 + 7 of 16
SHORT = RNG.integers(1, 380, 9).tolist()        # one chunk, 7 padded rows
STEPS = 8
# float32 serving: what is left between the two is summation order (the
# chunk's recurrence is the reference's, token by token)
F32_TOL = 2e-5
# bfloat16 serving, logits up to ~2 under weights three times their drawn
# size: every activation is rounded to 8 bits (2^-9 of its size) some fifty
# times in a row through 8 layers of two branches each, the conv rows are
# kept in bfloat16 and the logits are written in bfloat16 (half an ulp at 1-2
# is 0.004); the state S stays float32. With EVERY expert chosen (top-8 of
# 8: the routing weights move smoothly) that reads 0.07-0.11 at the worst of
# 9 x 384 logits and 0.013-0.019 in the mean over three seeds. With top-3 of
# 8 a rounded router flips near-ties between experts, which the float32
# reference does not follow: the worst logit then reads 0.07-0.37 and the
# mean 0.014-0.064, so that case is held by its mean. (The mathematics left
# out below is held in float32, where it moves logits 10 x F32_TOL and more.)
BF16_TOL, BF16_MEAN_TOL = 0.25, 0.12


@pytest.fixture(scope="module")
def family():
    return families.reference_family("qwen3_next_family",
                                     "tests/test_qwen3_next.py")


config = functools.partial(families.config, HF)


def seeded_params(cfg, seed: int = 0):
    """The program's seeded weights with every zero-centred gain redrawn at
    0.3 (at the 0.02 they are drawn with, ``1 + w`` and ``w``... differ
    all the same, but swapping two norms would change little), the gated
    norm's plain gain at 1 + 0.3 N, and the matmul weights three times as
    large, so that every branch weighs on the logits."""
    rng = np.random.default_rng(seed + 1)

    def redraw(name, a):
        if name in qn.ZERO_CENTRED:
            return families.gain(rng, a, centre=0.0)
        if name == "gdn_out_norm":
            return families.gain(rng, a)
        if name in ("gdn_A_log", "gdn_dt_bias"):
            return a
        return families.tripled(a)

    return families.redrawn(mdl.init_params(jax.random.key(seed), cfg),
                            redraw)


EXPERTS = ("loop", "kernel")


def runner_for(cfg, params, experts="loop", **kw) -> ModelRunner:
    """``experts``: the routed experts as the XLA loop and the DeltaNet's
    decode step as ``gdn_step``, or both as their kernels (ops.moe's,
    ops.gdn's: ONE decision of the runner) in the Pallas interpreter under
    the same XLA attention (steered here, before the first program is
    traced: ``attn_impl`` would take attention to its kernels too)."""
    kw = {"num_slots": 4, "max_ctx": 128, "paged": True,
          "kv_block_tokens": 16, "prefill_chunk": 16,
          "prefill_buckets": [16, 32], "attn_impl": "xla",
          "kv_dtype": cfg.dtype, **kw}
    r = ModelRunner(cfg, params, **kw)
    assert r.family_kernels is None
    if experts == "kernel":
        r.family_kernels = True
    return r


# ---------------------------------------------------------------------------
# (i) the served path against the plain reference


@pytest.mark.parametrize("experts", EXPERTS)
@pytest.mark.parametrize("dtype, chosen", [
    ("float32", 3), ("bfloat16", 8), ("bfloat16", 3)])
def test_served_logits_match_the_reference(family, monkeypatch, dtype,
                                           chosen, experts):
    """A prompt over two chunks (the second with padded rows), then decode
    steps: the logits each program samples from against the full forward."""
    hf = {**HF, "num_experts_per_tok": chosen}
    cfg = config(dtype, num_experts_per_tok=chosen)
    params = seeded_params(cfg)
    r = runner_for(cfg, params, experts)
    served, tokens = served_logits(r, tap(r), 1, PROMPT, STEPS)
    assert r.admit_programs == 1 + 2            # the arming and two chunks
    assert r.kv.k.shape[0] == PERIODS == cfg.cache_layers
    assert r.state.rec["S"].shape == (PERIODS, G, 4, 4, 16, 16)
    assert r.state.rec["S"].dtype == jnp.float32
    assert r.state_bytes == sum(a.nbytes for a in r.state.rec.values())
    # the decode program that served them holds the DeltaNet's kernel once a
    # DeltaNet layer of the (rolled) period, or not at all
    traced = str(jax.make_jaxpr(r._decode_paged_fn)(
        r.params, r.kv, r.state, r.block_tables))
    assert traced.count("name=gdn_state_step") == (
        G if experts == "kernel" else 0)
    ref = reference_logits(family, params, hf, PROMPT, tokens, monkeypatch)
    if dtype == "float32":
        agree(served, ref, F32_TOL)
        assert (served.argmax(-1) == ref.argmax(-1)).all()
    elif chosen == 8:
        agree(served, ref, BF16_TOL)
    else:
        assert np.abs(served - ref).mean() < BF16_MEAN_TOL
        assert np.abs(served - ref).max() < 4 * BF16_TOL


def test_a_chunk_with_padded_rows_leaves_the_state_exact(family, monkeypatch):
    """9 real tokens in a bucket of 16: the 7 rows past ``length`` are the
    identity on S and on the conv rows, which hold tokens 6, 7, 8."""
    cfg = config()
    params = seeded_params(cfg)
    r = runner_for(cfg, params)
    served, tokens = served_logits(r, tap(r), 2, SHORT, steps=3)
    agree(served, reference_logits(family, params, HF, SHORT, tokens,
                                   monkeypatch), F32_TOL)
    # whatever the padded rows hold, the state the chunk leaves is the same
    # bit for bit, and it is the state of a bucket the prompt fills to the
    # row (up to the order of a float32 sum: other shapes, other programs)
    from localai_tpu.engine.runner import _prompt_counts_row

    def state_after(junk: int, bucket: int = 16, **kw):
        r = runner_for(cfg, params, **kw)
        adm = r.begin_admit(2, SHORT, temperature=0.0)
        row = np.asarray(r.allocator.table_row(2), np.int32)
        r._arm(adm.arm_args, row)
        chunk = np.full((1, bucket), junk, np.int32)
        chunk[0, :9] = SHORT
        r.kv, r.state, tok = r._prefill_paged(
            r.params, r.kv, r.state, chunk, np.int32(9), np.int32(0), row,
            np.int32(2), _prompt_counts_row(cfg.vocab_size, SHORT),
            bucket=bucket, sample=True)
        return {n: np.asarray(r.state.rec[n][:, :, 2])
                for n in ("S", "conv")}, int(tok[0])

    zeros, tok = state_after(0)
    junk, tok_junk = state_after(377)
    exact, _ = state_after(0, bucket=9, prefill_buckets=[9], prefill_chunk=9,
                           kv_block_tokens=9, max_ctx=126)
    assert tok == tok_junk == tokens[0]
    for name in ("S", "conv"):
        np.testing.assert_array_equal(zeros[name], junk[name])
        np.testing.assert_allclose(zeros[name], exact[name], atol=1e-5)


def test_a_slot_armed_again_after_release_starts_from_zero(family,
                                                           monkeypatch):
    cfg = config()
    params = seeded_params(cfg)
    r = runner_for(cfg, params)
    seen = tap(r)
    served_logits(r, seen, 1, PROMPT, steps=4)
    assert float(jnp.abs(r.state.rec["S"][:, :, 1]).max()) > 0
    r.release(1)
    r._free_slots.remove(1)
    served, tokens = served_logits(r, seen, 1, SHORT, steps=4)
    agree(served, reference_logits(family, params, HF, SHORT, tokens,
                                   monkeypatch), F32_TOL)


def test_two_streams_of_different_length_beside_empty_slots(family,
                                                            monkeypatch):
    """Slots 0 and 2 hold streams of 23 and 9 prompt tokens, 1 and 3 none:
    each stream's logits are its own reference's, and a decode step leaves
    the empty slots' state exactly as it was (zero)."""
    cfg = config()
    params = seeded_params(cfg)
    r = runner_for(cfg, params)
    seen = tap(r)
    first = {0: r.admit(0, PROMPT, temperature=0.0),
             2: r.admit(2, SHORT, temperature=0.0)}
    prefill = {0: seen[0][0], 2: seen[1][0]}
    rows = [r.step() for _ in range(STEPS)]
    for slot, prompt in ((0, PROMPT), (2, SHORT)):
        tokens = [first[slot]] + [int(row[slot]) for row in rows]
        served = np.stack([prefill[slot]] + [s[slot] for s in seen[2:]])
        agree(served, reference_logits(family, params, HF, prompt, tokens,
                                       monkeypatch), F32_TOL)
    for name in ("S", "conv"):
        assert not np.asarray(r.state.rec[name][:, :, [1, 3]]).any()
    # the routed work rides behind the S tokens: 8 expert blocks, at most 4
    # held experts each, two live tokens
    touched, pairs = rows[-1][-2:]
    assert 0 < touched <= pairs <= 2 * 8 * 3 and touched <= 8 * 4


# ---------------------------------------------------------------------------
# (ii) mathematics left out fails (i)'s tolerance


def no_decay(monkeypatch):
    def step(S, q, k, v, g, beta):
        u = jnp.einsum("...kv,...k->...v", S, k)
        S = S + k[..., :, None] * (beta[..., None] * (v - u))[..., None, :]
        return S, jnp.einsum("...kv,...k->...v", S, q)

    monkeypatch.setattr(qn, "gdn_step", step)
    return {}


def kernel_only(left_out):
    """A term left out of ops.gdn's ``head_step``: the decode steps of the
    ``kernel`` runner miss it, the ``loop`` runner's never ran it."""
    left_out.kernel_only = True
    return left_out


def _head_step_with(monkeypatch, change):
    from localai_tpu.ops import gdn

    real = gdn.head_step
    # (``qn.recur_in_place`` keeps ONE trace of the kernel a process: the
    # case that runs this asks for ``fresh_kernel_traces``)
    monkeypatch.setattr(gdn, "head_step",
                        lambda S, *rest: change(real, S, *rest))
    return {}


@kernel_only
def no_decay_in_the_kernels_step(monkeypatch):
    def change(real, S, kc, qc, v, decay, beta, kq):
        return real(S, kc, qc, v, jnp.ones_like(decay), beta, kq)

    return _head_step_with(monkeypatch, change)


@kernel_only
def no_outer_product_in_the_kernels_step(monkeypatch):
    """The state decays and is never written: ``k (x) d`` dropped."""
    def change(real, S, kc, qc, v, decay, beta, kq):
        return decay * S, real(S, kc, qc, v, decay, beta, kq)[1]

    return _head_step_with(monkeypatch, change)


def no_one_plus_in_the_norm(monkeypatch):
    def norm(x, w, eps):
        xf = x.astype(jnp.float32)
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        return (xf * jax.lax.rsqrt(var + eps)
                * w.astype(jnp.float32)).astype(x.dtype)

    monkeypatch.setattr(qn, "zc_norm", norm)
    return {}


def rope_on_every_dim(monkeypatch):
    return {"partial_rotary_factor": 1.0}


def no_output_gate(monkeypatch):
    monkeypatch.setattr(mdl, "output_gate", lambda attn, gate: attn)
    return {}


def no_silu_z(monkeypatch):
    def norm(o, z, w, eps):
        var = jnp.mean(o * o, axis=-1, keepdims=True)
        return o * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)

    monkeypatch.setattr(qn, "gated_norm", norm)
    return {}


def unnormalised_top_k(monkeypatch):
    return {"norm_topk_prob": False}


def no_shared_gate(monkeypatch):
    from localai_tpu.models import quant as qnt

    def shared(h, lp, m):
        y = (jax.nn.silu(qnt.matmul(h, lp["shared_gate"][m]))
             * qnt.matmul(h, lp["shared_up"][m]))
        return qnt.matmul(y, lp["shared_down"][m]).astype(jnp.float32)

    monkeypatch.setattr(qn, "shared_expert", shared)
    return {}


def plain_gain_on_the_gated_norm_as_one_plus(monkeypatch):
    real = qn.gated_norm
    monkeypatch.setattr(qn, "gated_norm",
                        lambda o, z, w, eps: real(o, z, 1.0 + w, eps))
    return {}


@pytest.mark.parametrize("left_out", [
    no_decay, no_one_plus_in_the_norm, rope_on_every_dim, no_output_gate,
    no_silu_z, unnormalised_top_k, no_shared_gate,
    plain_gain_on_the_gated_norm_as_one_plus, no_decay_in_the_kernels_step,
    no_outer_product_in_the_kernels_step])
@pytest.mark.parametrize("experts", EXPERTS)
def test_mathematics_left_out_fails_the_tolerance(
        family, monkeypatch, fresh_kernel_traces, left_out, experts):
    """``no_decay`` patches ``gdn_step``, which the ``kernel`` runner's decode
    steps no longer run (its chunks do): the two ``kernel_only`` cases leave
    a term out of the step a cell runs, and move nothing under ``loop``."""
    cfg = config()
    params = seeded_params(cfg)
    served_cfg = config(**left_out(monkeypatch))
    r = runner_for(served_cfg, params, experts)
    served, tokens = served_logits(r, tap(r), 1, PROMPT, STEPS)
    monkeypatch.undo()
    ref = reference_logits(family, params, HF, PROMPT, tokens, monkeypatch)
    if getattr(left_out, "kernel_only", False) and experts == "loop":
        agree(served, ref, F32_TOL)
    else:
        assert np.abs(served - ref).max() > 10 * F32_TOL


# ---------------------------------------------------------------------------
# (iii) the share: the ranks' routed parts and the shared expert ONCE


def test_the_ranks_routed_parts_and_one_shared_expert_are_the_uncut_layer():
    """The model-configs guide's share test: an expert block cut over
    ``size`` ranks (each holds E / size experts, routes over all E) against
    the same block whole."""
    size, E = 4, 8
    whole = config(num_experts=E, expert_parallel={"size": 1, "rank": 0})
    params = seeded_params(whole, seed=3)
    lp = jax.tree.map(lambda a: a[0], {
        k: v for k, v in params["layers"].items()
        if k not in qn.EXPERT_LEAVES})
    h = jnp.asarray(RNG.standard_normal((1, 6, 64)), jnp.float32)
    valid = jnp.ones((1, 6), bool)

    def block(cfg, experts):
        out, counts = qn._moe(cfg, h, lp, 2, experts, jnp.int32(0), valid)
        shared = qn.shared_expert(h.reshape(-1, 64), lp, 2).reshape(h.shape)
        return np.asarray(out), np.asarray(shared), np.asarray(counts)

    experts = tuple(params["layers"][n] for n in qn.EXPERT_LEAVES)
    uncut, shared, counts = block(whole, experts)
    assert counts[1] == 6 * 3                       # every pair lands
    parts, pairs = [], 0
    for rank in range(size):
        cut = config(num_experts=E // size,
                     expert_parallel={"size": size, "rank": rank})
        held = tuple(w[:, :, rank * 2:(rank + 1) * 2] for w in experts)
        out, sh, c = block(cut, held)
        np.testing.assert_array_equal(sh, shared)
        parts.append(out - sh)
        pairs += int(c[1])
    assert pairs == 6 * 3
    np.testing.assert_allclose(sum(parts) + shared, uncut, atol=1e-6)


# ---------------------------------------------------------------------------
# (iv) what is refused, with one sentence each


def _mesh(**axes):
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    n = int(np.prod(list(axes.values())))
    return build_mesh(MeshPlan(**axes), devices=jax.devices()[:n])


@pytest.fixture(scope="module")
def seeded():
    """``seeded_params`` of the small configuration, drawn once for a runner
    that refuses them unread."""
    return seeded_params(config())


@pytest.mark.parametrize("what, kw", [
    ("the contiguous K/V layout", {"paged": False}),
    ("pipeline parallelism", {"paged": False, "mesh": {"pipe": 2}}),
    ("the ring prefill", {"mesh": {"seq": 2}}),
    ("a device mesh", {"mesh": {"model": 2}}),
])
def test_layouts_that_take_a_sequence_for_its_keys_are_refused(seeded, what,
                                                               kw):
    kw = dict(kw)
    if "mesh" in kw:
        kw["mesh"] = _mesh(**kw["mesh"])
    with pytest.raises(ValueError, match=f"^{what} is not served"):
        runner_for(config(), seeded, **kw)


def test_speculation_and_a_slots_resident_rows_are_refused():
    cfg = config()
    r = runner_for(cfg, seeded_params(cfg))
    with pytest.raises(ValueError, match="^speculative decoding is not"):
        r.verify_async(np.zeros((4, 2), np.int32))
    # the same prompt twice: the first's whole chunk (a block of 16) is
    # shared with the second BECAUSE the state behind it was kept (PR 62,
    # engine.paged: a snapshot a registered prompt, restored in front of the
    # tail; no line of this family's), and the token is the same; a SLOT's
    # resident record is still no reason to skip a token
    first = r.admit(0, PROMPT, temperature=0.0)
    assert r.allocator.snapshots_taken == 1
    assert r.admit(1, PROMPT, temperature=0.0,
                   resident=list(PROMPT)) == first
    assert (r.last_prefix_reused, r.total_prefix_reused) == (16, 16)
    assert r.allocator.snapshots_restored == 1
    assert r.allocator.check_invariants() == []
    assert r.reusable_prefix(2, list(PROMPT), list(PROMPT), valid_n=23) == 0
    # the prompt cache's import: rows of keys without the state behind them
    assert r.load_prefix(2, r.export_prefix(0, 16), 16) is False
    with pytest.raises(ValueError, match="quantization"):
        synthetic_params(cfg, "int8")


# ---------------------------------------------------------------------------
# (v) the scheduler: routed work in the flight ring and in /metrics


def test_the_flight_ring_and_metrics_count_routed_work():
    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.obs import metrics as obs_metrics
    from localai_tpu.obs.flight import WORK_COLUMNS
    from localai_tpu.utils.tokenizer import ByteTokenizer

    assert {"experts_touched", "local_assignments"} <= set(WORK_COLUMNS)
    cfg = config()
    r = runner_for(cfg, seeded_params(cfg))
    s = Scheduler(r, ByteTokenizer(), multi_step=2)
    try:
        h = s.generate(GenRequest(
            prompt=ByteTokenizer().encode("state beside the pool"),
            max_new_tokens=12, temperature=0.0, ignore_eos=True),
            timeout=120)
        assert h.completion_tokens == 12
        deadline = time.monotonic() + 10.0
        while True:     # the dispatch in flight at the reply's end drains
            rows, m = s.flight.snapshot(), s.metrics()
            decode = [x for x in rows if x["program"].startswith("decode")]
            if (sum(x["steps"] for x in decode) >= 11
                    and m["moe_experts_touched"]
                    > sum(x["experts_touched"] for x in decode)
                    or time.monotonic() > deadline):
                break
            time.sleep(0.05)
        # one live token a step, 8 expert blocks, top-3 of 8 with 4 held
        for x in decode:
            assert 0 < x["experts_touched"] == x["local_assignments"]
            assert x["local_assignments"] <= x["steps"] * 8 * 3
        chunks = [x for x in rows if x["program"] == "prefill_chunk"]
        assert chunks and all(x["experts_touched"] == 0 for x in chunks)
        # the totals hold the chunks' routed work too (it came with the
        # first token): 21 prompt tokens over two chunks
        in_chunks = (m["moe_assignments"]
                     - sum(x["local_assignments"] for x in decode))
        assert 0 < in_chunks <= 21 * 8 * 3
        assert m["state_slots_armed"] == 1
        assert m["state_bytes"] == r.state_bytes > 0
        obs_metrics.update_engine_gauges("qn", m)
        text = obs_metrics.REGISTRY.render()
        for name, key in (("moe_experts_touched", "moe_experts_touched"),
                          ("moe_assignments", "moe_assignments"),
                          ("state_slots_armed", "state_slots_armed")):
            assert f'localai_{name}_total{{model="qn"}} {m[key]}' in text
    finally:
        s.shutdown()


# ---------------------------------------------------------------------------
# (vi) the published keys; the leaves and their specs


def test_from_hf_reads_the_published_keys_and_the_share():
    doc = json.loads((ROOT / "benchmark/configs/qwen3-next-80b-a3b-ep8.json"
                      ).read_text())
    cfg = LlamaConfig.from_hf({k: v for k, v in doc.items()
                               if k not in spec.CONFIG_KEYS})
    assert type(cfg) is qn.Qwen3NextConfig and cfg.recurrent
    assert (cfg.hidden_size, cfg.num_heads, cfg.num_kv_heads, cfg.hd) == (
        2048, 16, 2, 256)
    assert (cfg.linear_num_key_heads, cfg.linear_num_value_heads,
            cfg.linear_key_head_dim, cfg.linear_value_head_dim,
            cfg.linear_conv_kernel_dim) == (16, 32, 128, 128, 4)
    assert (cfg.rotary_dim, cfg.rope_theta) == (64, 1e7)
    assert (cfg.num_experts, cfg.router_width, cfg.num_experts_per_tok,
            cfg.ep_size, cfg.ep_rank) == (64, 512, 10, 8, 0)
    assert (cfg.num_layers, cfg.periods, cfg.cache_layers) == (12, 3, 3)
    assert (cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size) == (512, 512)
    assert cfg.vocab_size == 151936 // 8 and not cfg.tie_word_embeddings
    with pytest.raises(ValueError, match="whole periods"):
        LlamaConfig.from_hf({**HF, "num_hidden_layers": 6})
    # model_type decides the class: the same keys without it are a dense
    # decoder's, as they were
    assert type(LlamaConfig.from_hf(
        {k: v for k, v in HF.items() if k != "model_type"})) is LlamaConfig


def test_every_leaf_has_a_spec_and_the_experts_go_over_the_expert_axis():
    from jax.sharding import PartitionSpec as P

    from localai_tpu.parallel import sharding as shd

    cfg = config()
    specs = shd.param_specs(cfg, _mesh(expert=2, model=2))
    shapes = mdl.param_shapes(cfg)
    assert set(specs["layers"]) == set(shapes["layers"])
    for name in qn.EXPERT_LEAVES:
        assert specs["layers"][name] == P(None, None, "expert", None, None)
    assert specs["layers"]["gdn_in_qkvz"] == P()
    assert specs["embed"] == P("model", None)
    # every layers leaf leads with the period (what harness/refcheck.py
    # indexes a row by)
    assert {s[0] for s in shapes["layers"].values()} == {PERIODS}


def test_a_checkpoint_in_the_published_layout_loads_to_the_served_leaves(
        tmp_path):
    """``models/loader.py`` for the family: a checkpoint written HERE in the
    published layout (tensor names of ``modeling_qwen3_next.py``, linear
    weights [out, in], ``in_proj_qkvz`` / ``in_proj_ba`` grouped by key head,
    the conv as [C, 1, K], every one of the 8 experts) loads to the served
    leaves it was made from; a rank of two loads its 4 experts of each block
    and the whole router."""
    from safetensors.numpy import save_file

    from localai_tpu.models.loader import load_llama_params

    whole_hf = {**HF, "num_experts": 8,
                "expert_parallel": {"size": 1, "rank": 0}}
    whole = config(num_experts=8, expert_parallel={"size": 1, "rank": 0})
    params = jax.tree.map(np.asarray, seeded_params(whole, seed=5))
    lay = params["layers"]
    Hk, rep, dk, dv = 2, 2, 16, 16
    out = {"model.embed_tokens.weight": params["embed"],
           "model.norm.weight": params["final_norm"],
           "lm_head.weight": params["lm_head"].T}
    for i in range(8):
        p, r = divmod(i, 4)
        pre = f"model.layers.{i}."
        out[pre + "post_attention_layernorm.weight"] = lay["mlp_norm"][p, r]
        out[pre + "mlp.gate.weight"] = lay["moe_gate"][p, r].T
        out[pre + "mlp.shared_expert_gate.weight"] = (
            lay["shared_router"][p, r][None])
        for ours, theirs in (("gate", "gate_proj"), ("up", "up_proj"),
                             ("down", "down_proj")):
            out[pre + f"mlp.shared_expert.{theirs}.weight"] = (
                lay["shared_" + ours][p, r].T)
            for e in range(8):
                out[pre + f"mlp.experts.{e}.{theirs}.weight"] = (
                    lay["w_" + ours][p, r, e].T)
        if r == 3:
            out[pre + "input_layernorm.weight"] = lay["attn_norm"][p]
            for ours, theirs in (("wq", "q_proj"), ("wk", "k_proj"),
                                 ("wv", "v_proj"), ("wo", "o_proj")):
                out[pre + f"self_attn.{theirs}.weight"] = lay[ours][p].T
            out[pre + "self_attn.q_norm.weight"] = lay["q_norm"][p]
            out[pre + "self_attn.k_norm.weight"] = lay["k_norm"][p]
            continue
        out[pre + "input_layernorm.weight"] = lay["gdn_norm"][p, r]
        a = pre + "linear_attn."
        # flat [q; k; v; z] columns -> rows grouped a key head: its q, its
        # k, its two value heads' v, their z
        w = lay["gdn_in_qkvz"][p, r].T
        q, k, v, z = np.split(w, [Hk * dk, 2 * Hk * dk,
                                  2 * Hk * dk + Hk * rep * dv])
        out[a + "in_proj_qkvz.weight"] = np.concatenate([
            np.concatenate([q[h * dk:(h + 1) * dk], k[h * dk:(h + 1) * dk],
                            v[h * rep * dv:(h + 1) * rep * dv],
                            z[h * rep * dv:(h + 1) * rep * dv]])
            for h in range(Hk)])
        b, g = np.split(lay["gdn_in_ba"][p, r].T, 2)
        out[a + "in_proj_ba.weight"] = np.concatenate([
            np.concatenate([b[h * rep:(h + 1) * rep],
                            g[h * rep:(h + 1) * rep]]) for h in range(Hk)])
        out[a + "conv1d.weight"] = lay["gdn_conv"][p, r].T[:, None, :]
        out[a + "A_log"] = lay["gdn_A_log"][p, r]
        out[a + "dt_bias"] = lay["gdn_dt_bias"][p, r]
        out[a + "norm.weight"] = lay["gdn_out_norm"][p, r]
        out[a + "out_proj.weight"] = lay["gdn_wo"][p, r].T
    save_file({k: np.ascontiguousarray(v) for k, v in out.items()},
              str(tmp_path / "model.safetensors"))
    cfg, loaded = load_llama_params(tmp_path, dtype="float32", hf=whole_hf)
    assert cfg == dataclasses.replace(whole, dtype=cfg.dtype)
    jax.tree.map(np.testing.assert_array_equal, params,
                 jax.tree.map(np.asarray, loaded))
    cut, held = load_llama_params(
        tmp_path, dtype="float32",
        hf={**whole_hf, "num_experts": 4,
            "expert_parallel": {"size": 2, "rank": 1}})
    assert (cut.num_experts, cut.router_width, cut.ep_rank) == (4, 8, 1)
    for name in qn.EXPERT_LEAVES:
        np.testing.assert_array_equal(held["layers"][name],
                                      lay[name][:, :, 4:])
    np.testing.assert_array_equal(held["layers"]["moe_gate"], lay["moe_gate"])
    with pytest.raises(ValueError, match="quantization"):
        load_llama_params(tmp_path, hf=whole_hf, quantization="int8")


# ---------------------------------------------------------------------------
# (vii) a prompt's small last chunk rides the decode step through the family's
# own forward (PR 64; tests/test_lfm2.py holds ``lfm2_moe``'s, and
# tests/test_paged_serving.py the dense stack's)

SLOTS, EXPERT_BLOCKS = 4, HF["num_hidden_layers"]
STREAMS = (RNG.integers(1, 380, 5).tolist(), RNG.integers(1, 380, 11).tolist())
GREEDY, SEEDED = dict(temperature=0.0), dict(temperature=0.8, top_p=0.95,
                                              seed=11)
# the paged kernel takes heads of 128 lanes, ops.moe's experts of 128
WIDE = {"head_dim": 128, "moe_intermediate_size": 128}


def ride_runner(cfg, params, impl, **kw):
    """``impl``: "loop" and "kernel" are ``runner_for``'s (XLA attention under
    the experts' walk and ``gdn_step``, or under ops.moe's and ops.gdn's
    kernels interpreted); "pallas_interpret" is the runner's own choice by
    ``attn_impl`` (the paged kernel beside those two)."""
    if impl == "pallas_interpret":
        r = ModelRunner(cfg, params, num_slots=SLOTS, max_ctx=128, paged=True,
                        kv_block_tokens=16, prefill_chunk=16,
                        prefill_buckets=[16], attn_impl=impl,
                        kv_dtype=cfg.dtype, **kw)
        assert r.family_kernels is True
        return r
    return runner_for(cfg, params, impl, prefill_buckets=[16], **kw)


def _busy_runner(cfg, params, impl, sampling):
    """Two streams three steps in (slots 0 and 1), a slot that held a stream
    that has ended (2: its state is what the stream left) and one that never
    held any (3)."""
    r = ride_runner(cfg, params, impl, seed=3)
    assert r.rides and r.own_forward
    for prompt in (*STREAMS, SHORT):
        r.admit(r.acquire_slot(), prompt, **{**sampling, "seed": 7})
    for _ in range(3):
        r.step()
    r.release(2)
    for name in ("S", "conv"):
        rows = np.asarray(r.state.rec[name]).reshape(PERIODS * G, SLOTS, -1)
        assert rows[:, :3].any(axis=(0, 2)).all() and not rows[:, 3].any()
    return r


@pytest.fixture(scope="module")
def ride_models():
    return {(dtype, wide): (cfg, seeded_params(cfg))
            for dtype, wide in (("float32", False), ("bfloat16", False),
                                ("bfloat16", True))
            for cfg in [config(dtype, **(WIDE if wide else {}))]}


@pytest.mark.parametrize("dtype, impl, prompt, sampling", [
    ("float32", "loop", SHORT, GREEDY), ("float32", "loop", SHORT, SEEDED),
    ("float32", "loop", PROMPT, GREEDY), ("float32", "kernel", PROMPT, SEEDED),
    ("bfloat16", "loop", PROMPT, SEEDED), ("bfloat16", "kernel", SHORT, GREEDY),
    # (attention's kernel in the interpreter too: 25 s)
    ("bfloat16", "pallas_interpret", PROMPT, SEEDED)],
    ids=lambda v: {id(SHORT): "fresh", id(PROMPT): "resumed",
                   id(GREEDY): "greedy", id(SEEDED): "seeded"}.get(id(v), v))
def test_a_ride_leaves_what_the_step_then_the_chunk_leave(
        ride_models, dtype, impl, prompt, sampling):
    """``_decode_prefill_paged_fn`` through ``qn.forward(ride=bucket)``
    against the two programs it stands for, on the same state: a decode step
    (the new slot not live: its row moves no state), then the prompt's last
    chunk; the new slot is the one a finished stream left its state in, and
    the chunk is the prompt's only one (``fresh``: from zero) or its second
    (from what the first left). The same S tokens and first token, the same
    pool, S and convolution rows of EVERY slot and sampling state to the
    bit, the same streams afterwards; the launch's token-expert pairs are
    the step's plus the chunks', and the experts it touched at most the sum
    and at least the larger (one block's rows share what they touch: the one
    read)."""
    cfg, params = ride_models[dtype, impl == "pallas_interpret"]

    def serve(ride):
        r = _busy_runner(cfg, params, impl, sampling)
        adm = r.begin_admit(r.acquire_slot(2), prompt, **sampling)
        assert adm.slot == 2
        if len(prompt) > 16:
            assert adm.ride_bucket is None and adm.launch_chunk() is False
        assert adm.ride_bucket == 16
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
    # two live streams and the prompt's real tokens, top-3 of 8 in 8 blocks,
    # of which the pairs that chose one of the 4 experts held here land: rows
    # past ``length`` and slots with no stream chose no expert
    assert 0 < routed[1] == apart[:, 1].sum() <= (
        (2 + len(prompt)) * HF["num_experts_per_tok"] * EXPERT_BLOCKS)
    assert apart[:, 0].max() <= routed[0] <= apart[:, 0].sum()
    S, conv = got_state[-3:-1]          # ``rec``'s leaves: S, conv, routed
    assert S.shape[2] == conv.shape[2] == SLOTS
    assert not S[:, :, 3].any() and not conv[:, :, 3].any()


def test_a_rides_padded_rows_and_idle_slots_move_nothing(ride_models):
    """The ride's rows that are nobody's: the chunk's 7 rows past ``length``
    and the step's rows of the slots with no stream (the new slot's own
    among them), whatever token they hold, choose no expert and move no
    state: the pool's live blocks, every slot's S and convolution rows, the
    tokens and the routed count are the same to the bit; and the slot that
    never held a stream still holds zeros."""
    from localai_tpu.engine.runner import _prompt_counts_row

    cfg, params = ride_models["float32", False]

    def ride(junk):
        r = _busy_runner(cfg, params, "loop", GREEDY)
        adm = r.begin_admit(r.acquire_slot(2), SHORT, temperature=0.0)
        row = np.asarray(r.allocator.table_row(adm.slot), np.int32)
        r._arm(adm.arm_args, row)
        # the tokens the idle slots' step rows feed
        r.state = dataclasses.replace(r.state, tokens=jnp.where(
            r.state.active, r.state.tokens, junk))
        chunk = np.full((1, 16), junk, np.int32)
        chunk[0, :9] = SHORT
        r.kv, r.state, out = r._decode_prefill_paged(
            r.params, r.kv, r.state, r.block_tables, chunk, np.int32(9),
            np.int32(0), row, np.int32(adm.slot),
            _prompt_counts_row(cfg.vocab_size, SHORT), bucket=16)
        live = sorted({b for s in (0, 1, 2)
                       for b in r.allocator.table_row(s) if b})
        out = np.asarray(out)
        keep = np.array([0, 1, SLOTS, SLOTS + 1, SLOTS + 2])
        S, conv = (np.asarray(r.state.rec[n]) for n in ("S", "conv"))
        assert not S[:, :, 3].any() and not conv[:, :, 3].any()
        return (out[keep], S, conv, np.asarray(r.kv.k[:, live]),
                np.asarray(r.kv.v[:, live]))

    for a, b in zip(ride(0), ride(377)):
        np.testing.assert_array_equal(a, b)


def test_the_scheduler_rides_it_and_the_texts_are_the_same(ride_models):
    """Through ``Scheduler``: the same three requests with the arrival's
    chunk riding the decode step and with it stepped aside (a neighbour
    under a constraint that allows every token: the loop's synchronous
    branch, the chunk a launch of its own) return the same tokens at
    temperature 0, and the ride's ``decode_chunk`` row carries the routed
    work of both halves behind its S + 1 tokens."""
    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.utils.tokenizer import ByteTokenizer

    cfg, params = ride_models["float32", False]

    class Anything:
        done = False

        def allowed_mask(self):
            return np.zeros(cfg.vocab_size, np.float32)

        def advance(self, tid):
            pass

    def wait(pred):
        deadline = time.monotonic() + 120.0
        while not pred() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pred()

    def serve(aside):
        r = ride_runner(cfg, params, "loop")
        s = Scheduler(r, ByteTokenizer(), multi_step=1)
        greedy = dict(temperature=0.0, ignore_eos=True)
        try:
            a = s.submit(GenRequest(prompt=STREAMS[0], max_new_tokens=40,
                                    **greedy))
            b = s.submit(GenRequest(
                prompt=PROMPT, max_new_tokens=40,
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
    # (the two streams' own chunks are 5 and 16 + 7 tokens: launches of their
    # own, the first into an idle engine; the second's last of 7 rode the
    # first's step where nothing stepped aside)
    assert (rides, no_rides, chunks, chunks_aside) == (2, 0, 4, 4)
    assert not no_rows and [x["chunk_tokens"] for x in rows] == [7, 9]
    row = rows[1]
    assert (row["steps"], row["live_slots"], row["chunk_tokens"],
            row["chunk_bucket"]) == (1, 2, 9, 16)
    assert 0 < row["local_assignments"] <= (
        (2 + 9) * HF["num_experts_per_tok"] * EXPERT_BLOCKS)
    assert EXPERT_BLOCKS <= row["experts_touched"] <= (
        row["local_assignments"])


@pytest.mark.parametrize("n_real", [0, 1, 5, 12])
def test_recur_over_the_real_rows_is_the_scan_over_the_bucket(n_real):
    """Part 4 of PR 64: ``recur`` walks rows 0 .. n_real - 1 of a chunk (a
    loop whose trip count the program reads from ``valid``) where it scanned
    the bucket. Through ``_gdn_mix`` (the convolution in front, the gates
    masked by ``valid``) S, the rows the convolution keeps and every real
    row of ``o`` are, to the bit, what ``lax.scan`` of the same ``gdn_step``
    over all T rows leaves (the rows past ``n_real`` are the identity on S
    by ``g = 0``, ``beta = 0``); ``o`` of the rows nobody reads is zero."""
    from jax import lax

    cfg = config()
    T, Hv, dk, dv = 12, 4, 16, 16
    lp = jax.tree.map(lambda a: a[0], seeded_params(cfg)["layers"])
    rng = np.random.default_rng(n_real)
    S0, conv0, qkv, z, b, a = (
        jnp.asarray(rng.normal(size=s), jnp.float32) for s in (
            (1, Hv, dk, dv), (1, 3, cfg.conv_dim), (1, T, cfg.conv_dim),
            (1, T, Hv * dv), (1, T, Hv), (1, T, Hv)))
    valid = jnp.arange(T)[None] < n_real

    def scanned(S0, q, k, v, g, beta, valid):
        S, o = lax.scan(lambda S, xs: qn.gdn_step(S, *xs), S0, tuple(
            jnp.moveaxis(t, 1, 0) for t in (q, k, v, g, beta)))
        return S, jnp.moveaxis(o, 0, 1)

    # (traced over its arguments: closed over, XLA would fold them)
    @functools.partial(jax.jit, static_argnums=0)
    def mixed(step, S0, *xs):
        return qn._gdn_mix(cfg, *xs[:4], lp, 1,
                           functools.partial(step, S0, valid=xs[5]), *xs[4:])

    (want_o, want_S, want_conv), (got_o, got_S, got_conv) = (
        mixed(step, S0, qkv, z, b, a, conv0, valid)
        for step in (scanned, qn.recur))
    np.testing.assert_array_equal(got_S, want_S)
    np.testing.assert_array_equal(got_conv, want_conv)
    np.testing.assert_array_equal(got_o[:, :n_real], want_o[:, :n_real])
    assert not np.asarray(got_o[:, n_real:]).any()
    if n_real == 0:
        np.testing.assert_array_equal(got_S, S0)
        np.testing.assert_array_equal(got_conv, conv0)
    else:
        assert np.asarray(want_o[:, :n_real]).any()
        assert np.abs(np.asarray(got_S - S0)).max() > 1e-3
