"""The looped decoder (``model_type: ouro``) on the normal serving path: ONE
stack of sandwich layers (four norms) run ``total_ut_steps`` times a token,
the final norm after every pass, a K/V cache entry for every (pass, layer)
pair. CPU, tiny widths, seeded weights, 2 layers x 3 passes.

The served path is the runner's own programs (``_prefill_paged_fn`` /
``_decode_paged_fn`` over either layout, and the contiguous rows' fresh
``_prefill_fn``), driven by ``admit`` and
``step`` and tapped for the logits they sample from; the reference is the
benchmark's plain float32 family (benchmark/reference/ouro_family.py, written
from the published description) run as the benchmark runs it
(harness/refcheck.py): the FULL forward over prompt + served tokens, no cache.
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
from families import ROOT, tap
from jax import lax

from localai_tpu import ops
from localai_tpu.engine import kvcache as kvc
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import llama as mdl
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import LlamaConfig
from localai_tpu.models.registry import DEBUG_PRESETS, synthetic_params

HF = {"model_type": "ouro", "vocab_size": 512, "hidden_size": 64,
      "intermediate_size": 128, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
      "max_position_embeddings": 512, "rope_theta": 10000.0,
      "rms_norm_eps": 1e-6, "total_ut_steps": 3, "early_exit_threshold": 1}
L, PASSES = 2, 3
PROMPT = np.random.default_rng(37).integers(1, 500, 25).tolist()
STEPS = 8
# float32 serving: what is left between the two is summation order
F32_TOL = 1e-4
# bfloat16 serving, logits up to ~0.3 (a unit-RMS x against a head of 0.02 x
# sqrt(64)): a bfloat16 keeps 8 bits, so every rounding of an activation is
# up to 2^-9 of its size, 2 layers x 3 passes x (4 norms + 7 matmuls) of them
# each renormalised by the sandwich norms, and the logits themselves are
# written in bfloat16 (half an ulp at 0.25 is 0.001): read 0.003-0.005; the
# mathematics left out below moves them by 0.02 and more
BF16_TOL = 0.012


@pytest.fixture(scope="module")
def family():
    return families.reference_family("ouro_family", "tests/test_ouro.py")


config = functools.partial(families.config, HF)


def seeded_params(cfg: LlamaConfig):
    """The program's seeded int8 weights (norm gains 1), with every norm
    gain redrawn around 1: with gains alike, swapping two norms would change
    nothing."""
    params = synthetic_params(cfg, "int8", seed=0)
    rng = np.random.default_rng(1)

    def gain(a):
        return jnp.asarray(1.0 + 0.3 * rng.standard_normal(a.shape), a.dtype)

    params["final_norm"] = gain(params["final_norm"])
    for name in params["layers"]:
        if name.endswith("norm"):
            params["layers"][name] = gain(params["layers"][name])
    return params


def runner_for(cfg, params, paged: bool, **kw) -> ModelRunner:
    # a 16-token chunk: the 25-token prompt is prefilled in TWO chunks
    return ModelRunner(cfg, params, num_slots=2, max_ctx=128, paged=paged,
                       kv_block_tokens=16, prefill_chunk=16,
                       prefill_buckets=[16, 32], attn_impl="xla",
                       kv_dtype=cfg.dtype, **kw)


def served_logits(cfg, params, paged: bool, slot: int = 1):
    """Prefill then 8 decode steps through the cache: ([9, V] logits, the 9
    greedy tokens, the runner)."""
    r = runner_for(cfg, params, paged)
    logits, tokens = families.served_logits(r, tap(r), slot, PROMPT, STEPS)
    if paged:
        assert r.admit_programs == 1 + 2      # the arming and two chunks
    return logits, tokens, r


def reference_logits(family, params, hf, tokens, monkeypatch) -> np.ndarray:
    """The family's full forward over prompt + served tokens: [9, V]."""
    return families.reference_logits(family, params, hf, PROMPT, tokens,
                                     monkeypatch)


# ---------------------------------------------------------------------------
# (i) the served path against the plain reference


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "contiguous"])
@pytest.mark.parametrize("dtype, tol", [("float32", F32_TOL),
                                        ("bfloat16", BF16_TOL)])
def test_served_logits_match_the_reference(family, monkeypatch, paged, dtype,
                                           tol):
    cfg = config(dtype)
    params = seeded_params(cfg)
    served, tokens, r = served_logits(cfg, params, paged)
    assert r.kv.k.shape[0] == PASSES * L
    ref = reference_logits(family, params, HF, tokens, monkeypatch)
    assert np.abs(ref).max() > 0.2          # logits, not zeros
    assert np.abs(served - ref).max() < tol, np.abs(served - ref).max()
    if dtype == "float32":
        assert (served.argmax(-1) == ref.argmax(-1)).all()


# ---------------------------------------------------------------------------
# (ii) mathematics left out fails (i)'s tolerance


def drop_a_pass(cfg, params):
    """The program serves two passes of the three."""
    return dataclasses.replace(cfg, num_passes=PASSES - 1), params, None


def swap_n2_with_n3(cfg, params):
    """The program applies the MLP's input norm to the attention's output
    and the other way round."""
    layers = dict(params["layers"])
    layers["attn_post_norm"], layers["mlp_norm"] = (
        layers["mlp_norm"], layers["attn_post_norm"])
    return cfg, {**params, "layers": layers}, None


def skip_the_norm_between_passes(cfg, params):
    """The program's loop without the norm between two passes: its
    ``loop.norm`` applied only after the last (``rms_norm`` on the final
    norm's gain is counted, and only the last call of a forward kept)."""
    return cfg, params, "skip"


@pytest.mark.parametrize("left_out", [drop_a_pass, swap_n2_with_n3,
                                      skip_the_norm_between_passes])
def test_mathematics_left_out_fails_the_tolerance(family, monkeypatch,
                                                  left_out):
    cfg = config()
    params = seeded_params(cfg)
    served_cfg, served_params, patch = left_out(cfg, params)
    if patch == "skip":
        real = mdl.forward

        def forward(cfg, params, *a, **k):
            # the stack three times with no norm between: three one-pass
            # forwards would norm each; instead run the passes' layers as
            # one pass over a stack three times as deep
            deep = jax.tree.map(lambda w: jnp.concatenate([w] * PASSES),
                                params["layers"])
            return real(dataclasses.replace(cfg, num_passes=1),
                        {**params, "layers": deep}, *a, **k)

        monkeypatch.setattr(mdl, "forward", forward)
    served, tokens, _ = served_logits(served_cfg, served_params, paged=True)
    monkeypatch.undo()
    ref = reference_logits(family, params, HF, tokens, monkeypatch)
    assert np.abs(served - ref).max() > 10 * F32_TOL


# ---------------------------------------------------------------------------
# (iii) which cache layer a (pass, layer) pair writes


def test_pass_t_of_layer_l_writes_cache_layer_t_L_plus_l_and_no_other():
    """The forward over a write policy that records: the n-th layer
    application of a token's forward (pass-major) is handed cache layer n,
    each of the passes x layers exactly once."""
    cfg = config()
    params = seeded_params(cfg)
    assert cfg.cache_layers == PASSES * L == 6
    for init in (kvc.init_cache(cfg, 2, 32, "float32"),
                 kvc.init_paged_cache(cfg, 5, 16, "float32")):
        assert init.k.shape[0] == init.v.shape[0] == cfg.cache_layers

    def record(kv_stack, layer, k_new, v_new):
        order, writes, step = kv_stack
        return ((order.at[layer].set(step), writes.at[layer].add(1),
                 step + 1), k_new, v_new)

    stack = (jnp.full(cfg.cache_layers, -1, jnp.int32),
             jnp.zeros(cfg.cache_layers, jnp.int32), jnp.int32(0))
    tokens = jnp.asarray([PROMPT[:4]], jnp.int32)
    positions = jnp.arange(4, dtype=jnp.int32)[None]
    recorded = jax.jit(lambda p, s: mdl.forward(
        cfg, p, tokens, positions, record, s, None,
        mdl.rope_table(cfg, 32), attn=lambda q, k, v, m: q))
    _, (order, writes, step) = recorded(params, stack)
    assert int(step) == 6
    assert np.array_equal(order, np.arange(6)) and np.all(writes == 1)


@pytest.mark.parametrize("paged", [True, False], ids=["paged", "contiguous"])
def test_a_decode_step_writes_one_row_of_every_cache_layer(paged):
    """Through the runner's own decode program: one step adds one row (the
    slot's position) to each of the 6 cache layers, distinct from pass to
    pass, and touches nothing else."""
    cfg = config()
    params = seeded_params(cfg)
    r = runner_for(cfg, params, paged)
    r.admit(1, PROMPT, temperature=0.0)
    before = np.asarray(r.kv.k)
    r.step()
    after = np.asarray(r.kv.k)
    changed = np.argwhere(np.abs(after - before).sum(-1) > 0)
    pos = len(PROMPT)
    if paged:
        block, off = r.allocator.tables[1][pos // 16], pos % 16
        where = {(layer, block, h, off) for layer in range(6)
                 for h in range(4)}
        # the other (inactive) slot's row lands in the trash block 0
        assert {tuple(c) for c in changed if c[1] != 0} == where
        rows = after[:, block, :, off]
    else:
        assert {tuple(c) for c in changed if c[1] == 1} == {
            (layer, 1, h, pos) for layer in range(6) for h in range(4)}
        rows = after[:, 1, :, pos]
    for a in range(6):
        for b in range(a):
            assert np.abs(rows[a] - rows[b]).max() > 1e-3, (a, b)


# ---------------------------------------------------------------------------
# (iv) one pass traces nothing new


def _layer_before_passes(cfg, x, lp, cos, sin, attend, reduce=None):
    """``models.llama._layer`` as it stood before PR 37 (the dense path)."""
    Hq, Hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.hd
    with jax.named_scope("attn.qkv"):
        h = mdl.rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        q = qnt.matmul(h, lp["wq"])
        k = qnt.matmul(h, lp["wk"])
        v = qnt.matmul(h, lp["wv"])
        q, k, v = lax.optimization_barrier((q, k, v))
        q = q.reshape(*q.shape[:-1], Hq, hd)
        k = k.reshape(*k.shape[:-1], Hkv, hd)
        v = v.reshape(*v.shape[:-1], Hkv, hd)
    with jax.named_scope("attn.rope"):
        q = mdl.apply_rope(q, cos, sin)
        k = mdl.apply_rope(k, cos, sin)
    attn, new_kv = attend(q, k, v)
    with jax.named_scope("attn.out"):
        attn = attn.reshape(*attn.shape[:-2], Hq * hd)
        x = x + qnt.matmul(attn, lp["wo"])
    with jax.named_scope("mlp"):
        h = mdl.rms_norm(x, lp["mlp_norm"], cfg.rms_norm_eps)
        gated = (jax.nn.silu(qnt.matmul(h, lp["w_gate"]))
                 * qnt.matmul(h, lp["w_up"]))
        x = x + qnt.matmul(gated, lp["w_down"])
    return x, new_kv


def _forward_before_passes(cfg, params, tokens, positions, kv_write, kv_stack,
                           mask, rope, attn=None, embeds=None, reduce=None,
                           live=None):
    """``models.llama.forward`` as it stood before PR 37: it never heard of
    passes (nor of ``live``, which the runner hands it as None on one
    device: PR 52)."""
    cos_t, sin_t = rope
    cos = cos_t[positions][:, :, None, :]
    sin = sin_t[positions][:, :, None, :]
    with jax.named_scope("embed"):
        x = qnt.embed_rows(params["embed"], tokens, jnp.dtype(cfg.dtype))
    if attn is None:
        xla_scope = "attn.prefill" if positions.shape[1] > 1 else "attn.decode"

        def attn(q, keys, values, m):
            with jax.named_scope(xla_scope):
                return mdl._grouped_attn(cfg, q, keys, values, m)

    def body(carry, layer_in):
        x, kv = carry
        lp, layer = layer_in

        def attend(q, k_new, v_new):
            new_kv, keys, values = kv_write(kv, layer, k_new, v_new)
            out = attn(q, keys, values, mask)
            if isinstance(out, tuple):      # PR 38: the kernel wrote the rows
                out, new_kv = out
            return out, new_kv

        return _layer_before_passes(cfg, x, lp, cos, sin, attend), None

    n_layers = jax.tree.leaves(params["layers"])[0].shape[0]
    with jax.named_scope("layers"):
        (x, new_kv_stack), _ = lax.scan(
            body, (x, kv_stack),
            (params["layers"], jnp.arange(n_layers, dtype=jnp.int32)))
    with jax.named_scope("final_norm"):
        x = mdl.rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    return x, new_kv_stack


lowered = functools.partial(families.lowered_texts,
                            programs=("decode", "prefill_1"))


@pytest.mark.parametrize("attn_impl", ["xla", "pallas_interpret"])
def test_one_pass_lowers_to_the_text_it_had_before_passes(monkeypatch,
                                                          attn_impl):
    """A one-pass model's decode and prefill programs, lowered with the
    forward of this tree and with the forward as it stood before it knew of
    passes (copied above): the same text to the letter, and no scope of the
    loop's among the names the trace would show. A published
    ``total_ut_steps`` without ``model_type: ouro`` is not read."""
    hf = {k: v for k, v in HF.items() if k != "model_type"}
    cfg = dataclasses.replace(LlamaConfig.from_hf(hf), dtype="bfloat16")
    assert (cfg.num_passes, cfg.post_norm, cfg.cache_layers) == (1, False, L)
    assert cfg == dataclasses.replace(
        LlamaConfig.from_hf({k: v for k, v in hf.items()
                             if k != "total_ut_steps"}), dtype="bfloat16")
    params = synthetic_params(cfg, "int8", seed=0)

    def runner():
        return ModelRunner(cfg, params, num_slots=4, max_ctx=128, paged=True,
                           kv_block_tokens=16, attn_impl=attn_impl)

    now, named = lowered(runner()), lowered(runner(), debug_info=True)
    monkeypatch.setattr(mdl, "forward", _forward_before_passes)
    before = lowered(runner())
    assert now == before
    for text in named.values():
        assert "/layers" in text and "/final_norm" in text
        assert "loop.pass" not in text and "loop.norm" not in text


def test_the_looped_programs_hold_one_rolled_loop():
    """With passes, ONE loop over them around ONE layer scan (two nested
    ``while``s a forward), not a scan a pass; the scopes a reader of the
    trace finds are ``loop.pass/layers/...`` and ``loop.norm``."""
    cfg = config("bfloat16")
    looped = lowered(runner_for(cfg, seeded_params(cfg), paged=True))
    once_cfg = dataclasses.replace(cfg, num_passes=1)
    once = lowered(runner_for(once_cfg, seeded_params(once_cfg), paged=True))
    for program in ("decode", "prefill_1"):
        # the layer scan, and around it the loop over passes: one more loop
        # than the same model run once, however many passes
        assert (looped[program].count("stablehlo.while")
                == once[program].count("stablehlo.while") + 1)
    named = lowered(runner_for(cfg, seeded_params(cfg), paged=True),
                    debug_info=True)["decode"]
    assert "loop.pass/layers" in named and "loop.norm" in named
    assert "/final_norm" not in named       # the scope of the one-pass model


# ---------------------------------------------------------------------------
# (v) from_hf on the published keys; a prefix exported and imported again


def test_from_hf_reads_the_published_keys():
    doc = json.loads(
        (ROOT / "benchmark/configs/ouro-2.6b-int8.json").read_text())
    cfg = LlamaConfig.from_hf(doc)
    assert (cfg.num_layers, cfg.num_passes, cfg.cache_layers) == (48, 4, 192)
    assert cfg.post_norm and not cfg.attention_bias
    assert (cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size) == (
        2048, 5632, 49152)
    assert (cfg.num_heads, cfg.num_kv_heads, cfg.hd, cfg.q_per_kv) == (
        16, 16, 128, 1)
    assert (cfg.rope_theta, cfg.rms_norm_eps) == (1e6, 1e-6)
    assert cfg.sliding_window is None and not cfg.tie_word_embeddings
    shapes = mdl.param_shapes(cfg)["layers"]
    assert shapes["attn_post_norm"] == shapes["mlp_post_norm"] == (48, 2048)
    # every leaf the stack holds, counted: the family's 2,667,972,608
    total = sum(int(np.prod(s)) for s in jax.tree.leaves(
        mdl.param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))
    assert total == 2_667_972_608
    # the Mistral files read as before: one pass, two norms a layer
    m7b = LlamaConfig.from_hf(json.loads(
        (ROOT / "benchmark/configs/mistral-7b-v0.3-int8.json").read_text()))
    assert (m7b.num_passes, m7b.post_norm, m7b.cache_layers) == (1, False, 32)
    assert "attn_post_norm" not in mdl.param_shapes(m7b)["layers"]
    loop = DEBUG_PRESETS["tiny-loop"]
    assert (loop.num_passes, loop.cache_layers, loop.post_norm) == (3, 6, True)


def test_unquantised_post_norm_gains_are_one():
    """``init_params`` draws the stacked pre-norm gains at 0.02 as it always
    has; a branch's OUTPUT norm gets gain 1, or a pass would change nothing
    (and a dropped pass would pass every check)."""
    params = mdl.init_params(jax.random.key(0), config())
    assert np.all(np.asarray(params["layers"]["attn_post_norm"]) == 1)
    assert np.all(np.asarray(params["layers"]["mlp_post_norm"]) == 1)
    assert np.asarray(params["layers"]["wq"]).std() < 0.03


@pytest.mark.parametrize("src_paged, dst_paged", [
    (True, True), (True, False), (False, True)],
    ids=["paged-paged", "paged-contiguous", "contiguous-paged"])
def test_a_prefix_exported_and_imported_gives_the_same_next_logits(
        src_paged, dst_paged):
    """All 6 cache layers of a prompt's rows go out and come back: the
    runner that imported them prefills the last token alone and samples
    from the same logits, then decodes the same tokens."""
    cfg = config()
    params = seeded_params(cfg)
    base, tokens, src = served_logits(cfg, params, src_paged)
    exported = src.export_prefix(1, len(PROMPT))
    assert exported["k"].shape == (6, 4, len(PROMPT), 16)
    dst = runner_for(cfg, params, dst_paged)
    seen = tap(dst)
    assert dst.load_prefix(1, exported, len(PROMPT))
    out = [dst.admit(1, PROMPT, temperature=0.0, resident=list(PROMPT),
                     valid_n=len(PROMPT))]
    assert dst.last_prefix_reused == len(PROMPT) - 1
    out += [int(dst.step()[1]) for _ in range(3)]
    assert out == tokens[:4]
    again = np.stack([seen[0][0]] + [row[1] for row in seen[1:]])
    np.testing.assert_allclose(again, base[:4], atol=F32_TOL)
    # a cache of another depth is refused, not misread
    other = runner_for(config(total_ut_steps=2), params, dst_paged)
    assert not other.load_prefix(1, exported, len(PROMPT))


def test_modes_that_walk_the_stack_once_refuse_or_step_aside():
    """Pipeline parallelism's stage chain and the ring prefill run the layers
    once: the first refuses a looped model at load, the second is not
    chosen for one."""
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    cfg = config()
    params = synthetic_params(cfg, "", seed=0)
    with pytest.raises(ValueError, match="looped decoder"):
        ModelRunner(cfg, params, num_slots=2, max_ctx=64,
                    mesh=build_mesh(MeshPlan(pipe=2),
                                    devices=jax.devices()[:2]))
    seq = ModelRunner(cfg, params, num_slots=2, max_ctx=64,
                      mesh=build_mesh(MeshPlan(seq=2),
                                      devices=jax.devices()[:2]))
    assert not seq.sp_enabled


# ---------------------------------------------------------------------------
# the paged kernel at one query row a kv head, over 192 cache layers


@pytest.mark.parametrize("layer", [0, 47, 48, 191])
def test_paged_kernel_at_one_query_row_a_kv_head(layer):
    """``paged_decode_attn`` (interpreted) against the XLA attend at the
    looped configuration's head shape (16 query = 16 kv heads, head_dim 128)
    over a pool with 192 leading rows: the kernel picks the cache layer in
    its DMA slice, first and last of the first two passes and the last of
    all."""
    S, H, hd, bt, MB = 4, 16, 128, 16, 3
    n = S * MB + 1
    rng = np.random.default_rng(layer)

    def normal(shape):
        return jnp.asarray(rng.standard_normal(shape, np.float32),
                           jnp.bfloat16)

    kl, vl = normal((n, H, bt, hd)), normal((n, H, bt, hd))
    k = jnp.zeros((192, n, H, bt, hd), jnp.bfloat16).at[layer].set(kl)
    v = jnp.zeros((192, n, H, bt, hd), jnp.bfloat16).at[layer].set(vl)
    q = normal((S, H, hd))
    tables = jnp.asarray(rng.permutation(np.arange(1, n)).reshape(S, MB),
                         jnp.int32)
    positions = jnp.asarray([0, bt - 1, bt, MB * bt - 1], jnp.int32)
    got = ops.paged_decode_attention(q, k, v, jnp.int32(layer), tables,
                                     positions, interpret=True)
    with jax.default_matmul_precision("highest"):
        ref = ops.paged_decode_attention_ref(q, kl, vl, tables, positions)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


# ---------------------------------------------------------------------------
# counters: the flight ring's ``passes`` and /metrics


def test_the_flight_ring_and_metrics_count_passes():
    from localai_tpu.engine.scheduler import GenRequest, Scheduler
    from localai_tpu.obs import metrics as obs_metrics
    from localai_tpu.utils.tokenizer import ByteTokenizer

    cfg = config()
    r = runner_for(cfg, seeded_params(cfg), paged=True)
    s = Scheduler(r, ByteTokenizer(), multi_step=2)
    try:
        h = s.generate(GenRequest(prompt=ByteTokenizer().encode("loop"),
                                  max_new_tokens=12, temperature=0.0,
                                  ignore_eos=True), timeout=120)
        assert h.completion_tokens == 12
        # the dispatch in flight when the reply ended drains a moment later:
        # read ring and counter once both have it
        deadline = time.monotonic() + 10.0
        while True:
            rows, m = s.flight.snapshot(), s.metrics()
            if (m["loop_passes"] == sum(x["passes"] for x in rows)
                    or time.monotonic() > deadline):
                break
            time.sleep(0.05)
        decode = [x for x in rows if x["program"].startswith("decode")]
        assert decode and all(x["passes"] == PASSES * x["steps"]
                              for x in decode)
        chunks = [x for x in rows if x["program"] == "prefill_chunk"]
        assert chunks and all(x["passes"] == PASSES for x in chunks)
        assert m["loop_passes"] == sum(x["passes"] for x in rows)
        obs_metrics.update_engine_gauges("looped", m)
        text = obs_metrics.REGISTRY.render()
        assert (f'localai_loop_passes_total{{model="looped"}} '
                f'{m["loop_passes"]}') in text
    finally:
        s.shutdown()
