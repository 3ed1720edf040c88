"""MiniCPM-SALA (``model_type: minicpm_sala``) on the normal serving path:
Lightning linear attention in most layers (a float32 state a slot beside the
pool), MiniCPM4's attention without positions in the others, whose queries
past ``dense_len`` attend InfLLM-v2's selection of the pool's blocks, under
MiniCPM's muP scalars; and a shared prefix that carries the recurrent state
(engine.paged: a snapshot a registered prompt). CPU, tiny widths (D 64, 6
layers S L L S S L, 4 Lightning heads of 16, 4 / 2 attention heads), sizes at
which the selection bites inside a few hundred tokens (``dense_len`` 256,
blocks of 16, windows of 8 every 4, a local window of 64, top 8), seeded
weights of the program's own draw.

The served path is the runner's own programs (``_prefill_paged_fn`` /
``_decode_paged_fn``), driven by ``admit`` and ``step`` and tapped for the
logits they sample from; the reference is the benchmark's plain float32
family (benchmark/reference/minicpm_sala_family.py, written from the
published keys) run as the benchmark runs it (harness/refcheck.py): the FULL
forward over prompt + served tokens, no cache, no state carried.
"""

import dataclasses
from functools import partial

import families
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from families import served_logits, tap

from localai_tpu.engine import paged as pgd
from localai_tpu.engine.runner import ModelRunner
from localai_tpu.models import llama as mdl
from localai_tpu.models import minicpm_sala as sala
from localai_tpu.models import quant as qnt
from localai_tpu.models.llama import LlamaConfig

KINDS = ["minicpm4", "lightning-attn", "lightning-attn", "minicpm4",
         "minicpm4", "lightning-attn"]
SPARSE = {"kernel_size": 8, "kernel_stride": 4, "init_blocks": 1,
          "block_size": 16, "window_size": 64, "topk": 8, "dense_len": 256}
HF = {"model_type": "minicpm_sala", "vocab_size": 384, "hidden_size": 64,
      "intermediate_size": 96, "num_hidden_layers": 6,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
      "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
      "mixer_types": KINDS, "qk_norm": True, "rms_norm_eps": 1e-6,
      "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
      "dim_model_base": 16, "mup_denominator": 32, "rand_init": False,
      "tie_word_embeddings": False, "use_output_gate": True,
      "use_output_norm": True, "attn_use_output_gate": True,
      "attn_use_rope": False, "attention_bias": False, "hidden_act": "silu",
      "max_position_embeddings": 512, "sparse_config": SPARSE}
SLOTS, BLOCK = 16, 16
RNG = np.random.default_rng(62)
# past dense_len: 19 blocks of which a row attends 8 (first, the window's 5,
# the two best of the rest); chunks of 64, the last with padded rows
LONG = RNG.integers(1, 380, 300).tolist()
SHORT = RNG.integers(1, 380, 40).tolist()       # below it: plain GQA
STEPS = 4
# float32 serving: what is left between the two is summation order (the
# chunked recurrence against the token's, the half-window sums against a
# window's mean); logits spread ~5
F32_TOL = 5e-5
# a term left out moves some logit by at least this (read: 4e-3 at the
# least, the block beyond the top-k; 0.5 to 5 for the scalars and norms)
CONTROL = 1e-3
# bfloat16 serving over int8 weights under the program's draw: every
# activation rounded to 8 bits some sixty times through 6 layers, compressed
# keys and pool in bfloat16, logits written in bfloat16 (read 0.11 at the
# worst of 5 x 384 logits, 0.02 in the mean)
BF16_TOL, BF16_MEAN_TOL = 0.4, 0.08


@pytest.fixture(scope="module")
def family():
    return families.reference_family("minicpm_sala_family",
                                     "tests/test_minicpm_sala.py")


config = partial(families.config, HF)


@pytest.fixture(scope="module")
def params32():
    return mdl.init_params(jax.random.key(0), config())


def runner_for(cfg, params, impl="xla", **kw) -> ModelRunner:
    kw = {"num_slots": SLOTS, "max_ctx": 512, "paged": True,
          "kv_block_tokens": BLOCK, "prefill_chunk": 64,
          "prefill_buckets": [16, 64], "attn_impl": impl,
          "kv_dtype": cfg.dtype, **kw}
    return ModelRunner(cfg, params, **kw)


@pytest.fixture(scope="module")
def served(params32):
    """The long prompt through its chunks and four decode steps, float32,
    XLA: (runner, logits [1 + STEPS, V], tokens)."""
    r = runner_for(config(), params32)
    logits, tokens = served_logits(r, tap(r), 1, LONG, STEPS)
    return r, logits, tokens


def reference(family, params, hf, prompt, tokens, monkeypatch):
    return families.reference_logits(family, params, hf, prompt, tokens,
                                     monkeypatch)


# ---------------------------------------------------------------------------
# (i) the served path against the plain reference


def test_served_logits_match_the_reference_past_dense_len(
        family, monkeypatch, params32, served):
    r, logits, tokens = served
    cfg = r.cfg
    # the arming and five chunks: four of 64 (the state is kept behind the
    # fourth, the prompt's last whole chunk: no chunk is cut for it), the rest
    assert r.admit_programs == 1 + 5 and r.allocator.snapshots_taken == 1
    assert r.kv.k.shape[0] == 3 == cfg.cache_layers
    assert r.state.rec["S"].shape == (3, SLOTS, 4, 16, 16)
    assert r.state.rec["S"].dtype == jnp.float32
    assert r.state.rec["ck"].shape == (3, SLOTS, 128, 2 * 16)
    assert r.state.rec["seg"].shape == (3, SLOTS, 2, 2, 16)
    ref = reference(family, params32, HF, LONG, tokens, monkeypatch)
    assert np.abs(ref).max() > 1.0              # logits, not zeros
    assert np.abs(logits - ref).max() < F32_TOL


def test_a_short_prompt_is_plain_grouped_query_attention(
        family, monkeypatch, params32):
    r = runner_for(config(), params32)
    logits, tokens = served_logits(r, tap(r), 2, SHORT, STEPS)
    ref = reference(family, params32, HF, SHORT, tokens, monkeypatch)
    assert np.abs(logits - ref).max() < F32_TOL
    # ... and the selection's sizes say nothing below dense_len
    other = {**HF, "sparse_config": {**SPARSE, "topk": 9, "init_blocks": 0}}
    assert np.abs(reference(family, params32, other, SHORT, tokens,
                            monkeypatch) - ref).max() == 0.0


def test_the_kernels_serve_what_xla_serves(family, monkeypatch, params32,
                                           served):
    """``attn_impl: pallas_interpret``: the sparse attend is the paged
    decode kernel over the compacted tables (rows = (stream, K/V head), the
    kernel the step's writer), the Lightning step ops.gdn's kernel without
    the delta correction."""
    r = runner_for(config(), params32, "pallas_interpret")
    assert r.family_kernels is True and r.paged_attn_impl == "pallas"
    logits, tokens = served_logits(r, tap(r), 1, LONG, STEPS)
    assert tokens == served[2]
    ref = reference(family, params32, HF, LONG, tokens, monkeypatch)
    assert np.abs(logits - ref).max() < F32_TOL
    text = jax.jit(r._decode_paged_fn).lower(
        r.params, r.kv, r.state, r.block_tables).as_text(debug_info=True)
    assert "sparse/attend" in text and "lightning/state" in text
    assert text.count("paged_decode_attn") and text.count("ssm_state_step")


def test_bfloat16_over_int8_weights(family, monkeypatch, params32):
    cfg = config("bfloat16")
    params = qnt.quantize_params(
        jax.tree.map(lambda a: a.astype(
            a.dtype if a.dtype == jnp.float32 and a.ndim == 2
            and a.shape[-1] == 4 else "bfloat16"), params32), "int8")
    for name in ("wq", "wk", "wv", "w_ogate", "wo", "w_gate", "w_up",
                 "w_down"):
        assert params["layers"][name].q.dtype == jnp.int8, name
        assert params["sa1_" + name].q.dtype == jnp.int8, name
        assert params["sa1_" + name].scale.ndim == 1
    assert params["embed"].q.dtype == params["lm_head"].q.dtype == jnp.int8
    assert params["layers"]["out_norm"].dtype == jnp.bfloat16
    r = runner_for(cfg, params)
    logits, tokens = served_logits(r, tap(r), 1, LONG, STEPS)
    ref = reference(family, params, HF, LONG, tokens, monkeypatch)
    gap = np.abs(logits - ref)
    assert gap.max() < BF16_TOL and gap.mean() < BF16_MEAN_TOL


# ---------------------------------------------------------------------------
# (ii) a term left out shows: the served logits against a reference that
# reads the keys the other way


def _params(params32, **leaves):
    """``params32`` with some leaves replaced (``layers.<name>`` or a
    top-level name)."""
    out = {**params32, "layers": dict(params32["layers"])}
    for name, leaf in leaves.items():
        if name.startswith("layers."):
            out["layers"][name[7:]] = leaf
        else:
            out[name] = leaf
    return out


def _sparse(**changed):
    return {**HF, "sparse_config": {**SPARSE, **changed}}


def _patched(fam, monkeypatch, name, value):
    monkeypatch.setattr(fam, name, value)
    return HF


TERMS = {
    "the decay": lambda f, m, p: (HF, _params(p, **{
        "layers.decay": jnp.zeros_like(p["layers"]["decay"])})),
    "the decay's layer factor": lambda f, m, p: (HF, _params(p, **{
        "layers.decay": jnp.asarray(np.stack([f.log_decay(HF, i, False)
                                              for i in (1, 2, 5)]))})),
    "the Lightning RoPE": lambda f, m, p: (
        _patched(f, m, "rope", lambda x, cos, sin: x), p),
    "the q/k norm": lambda f, m, p: (_patched(
        f, m, "head_norm", lambda x, gain, eps, real=f.head_norm: (
            x * gain if np.ndim(gain) else real(x, gain, eps))), p),
    "the output norm": lambda f, m, p: (_patched(
        f, m, "head_norm", lambda x, gain, eps, real=f.head_norm: (
            real(x, gain, eps) if np.ndim(gain) else x)), p),
    "the Lightning gate": lambda f, m, p: (HF, _params(p, **{
        "layers.w_ogate": jnp.zeros_like(p["layers"]["w_ogate"])})),
    "the attention gate": lambda f, m, p: (HF, _params(p, **{
        f"sa{n}_w_ogate": jnp.zeros_like(p[f"sa{n}_w_ogate"])
        for n in range(3)})),
    "scale_emb": lambda f, m, p: ({**HF, "scale_emb": 6}, p),
    "scale_depth": lambda f, m, p: ({**HF, "scale_depth": 1.0}, p),
    "dim_model_base": lambda f, m, p: ({**HF, "dim_model_base": 32}, p),
    "the group's SUM": lambda f, m, p: (_patched(
        f, m, "chosen_blocks", partial(f.chosen_blocks, group_sum=False)), p),
    "the max over touching windows": lambda f, m, p: (_patched(
        f, m, "touches", lambda n, blocks, windows: (
            (n["kernel_stride"] * np.arange(windows)[None, :]
             // n["block_size"]) == np.arange(blocks)[:, None])), p),
    "the forced first block": lambda f, m, p: (_sparse(init_blocks=0), p),
    "the forced window": lambda f, m, p: (_sparse(window_size=16), p),
    "topk - 1": lambda f, m, p: (_sparse(topk=7), p),
    "topk + 1": lambda f, m, p: (_sparse(topk=9), p),
    "dense_len by the token": lambda f, m, p: (_sparse(dense_len=0), p),
}


@pytest.mark.parametrize("term", sorted(TERMS))
def test_a_term_left_out_shows(family, monkeypatch, params32, served, term):
    _, logits, tokens = served
    hf, params = TERMS[term](family, monkeypatch, params32)
    other = reference(family, params, hf, LONG, tokens, monkeypatch)
    assert np.abs(logits - other).max() > CONTROL, term


def test_keys_that_ask_for_what_is_not_written_are_refused():
    for key, value in (("attn_use_rope", True), ("lightning_use_rope", False),
                       ("qk_norm", False), ("use_output_gate", False),
                       ("lightning_nkv", 2)):
        with pytest.raises(ValueError, match="model_type minicpm_sala is "
                                             "served with"):
            LlamaConfig.from_hf({**HF, key: value})
    with pytest.raises(ValueError, match="sparse_config"):
        LlamaConfig.from_hf(_sparse(kernel_size=12))
    with pytest.raises(ValueError, match="selects blocks of 16 tokens"):
        runner_for(config(), None, kv_block_tokens=32)
    cfg = config()
    assert cfg.runs == (("minicpm4", 1, 0, 0), ("lightning-attn", 2, 1, 0),
                        ("minicpm4", 2, 3, 1), ("lightning-attn", 1, 5, 2))
    assert cfg.select_blocks == (16, 16, 256)
    # the decay buffer is what the reference says it must hold
    assert np.allclose(sala.log_decay(cfg)[2], -2.0 ** (
        -8 * (np.arange(4) + 1) / 4) * (1 - 5 / 5 + 1e-5))


def test_the_checkpoints_names_fill_the_pytree(params32):
    """``checkpoint_leaves`` reads a layer's tensors under the names it
    states and lays them where ``param_shapes`` says."""
    cfg = config()
    names = {"attn_norm": "input_layernorm", "mlp_norm":
             "post_attention_layernorm", "w_gate": "mlp.gate_proj",
             "w_up": "mlp.up_proj", "w_down": "mlp.down_proj",
             "wq": "self_attn.q_proj", "wk": "self_attn.k_proj",
             "wv": "self_attn.v_proj", "w_ogate": "self_attn.o_gate",
             "wo": "self_attn.o_proj", "q_norm": "self_attn.q_norm",
             "k_norm": "self_attn.k_norm", "out_norm": "self_attn.o_norm"}
    tensors = {"model.norm.weight": np.asarray(params32["final_norm"])}
    light = sparse = 0
    for i, kind in enumerate(KINDS):
        for leaf, tail in names.items():
            if kind == "minicpm4":
                if leaf == "out_norm":
                    continue
                a = np.asarray(params32[f"sa{sparse}_{leaf}"])
            else:
                a = np.asarray(params32["layers"][leaf][light])
            tensors[f"model.layers.{i}.{tail}.weight"] = a.T
        light += kind != "minicpm4"
        sparse += kind == "minicpm4"
    got = dict(sala.checkpoint_leaves(cfg, tensors.__getitem__))
    shapes = mdl.param_shapes(cfg)
    want = {**{k: v for k, v in params32.items()
               if k not in ("layers", "embed", "lm_head")},
            **params32["layers"]}
    assert sorted(got) == sorted(want)
    for name, a in got.items():
        assert a.shape == (shapes["layers"].get(name) or shapes[name]), name
        assert np.array_equal(a, np.asarray(want[name])), name


# ---------------------------------------------------------------------------
# (iii) a shared prefix carries the recurrent state


DOC = RNG.integers(1, 380, 321).tolist()        # five chunks of 64 and one
TAIL = RNG.integers(1, 380, 23).tolist()        # token: as a BOS makes it


def _slot(r, name, slot):
    return np.take(np.asarray(r.state.rec[name]), slot, axis=1)


def test_a_prompt_through_a_restored_prefix_is_the_prompt_served_cold(
        params32):
    cfg = config()
    r = runner_for(cfg, params32)
    seen = tap(r)
    assert r.allocator.snapshots == 2 and set(r.snaps) == {"S", "ck", "seg"}
    assert r._snap_axes() == {"S": 1, "ck": 1, "seg": 1}
    served_logits(r, seen, 0, DOC, 1)
    # kept ONCE, behind the last whole chunk (five of 64, then the last
    # token: six, and the arming)
    assert r.allocator.snapshots_taken == 1 and r.admit_programs == 6 + 1
    assert r.allocator.check_invariants() == []
    hot, hot_tokens = served_logits(r, seen, 1, DOC + TAIL, STEPS)
    assert (r.last_prefill_path, r.last_prefix_reused) == ("paged_shared",
                                                           320)
    assert r.allocator.snapshots_restored == 1
    # the tail saves too little to be kept: nothing is cut, nothing taken
    assert r.allocator.snapshots_taken == 1
    cold_r = runner_for(cfg, params32)
    cold, cold_tokens = served_logits(cold_r, tap(cold_r), 1, DOC + TAIL,
                                      STEPS)
    assert cold_r.last_prefix_reused == 0
    assert hot_tokens == cold_tokens
    assert np.abs(hot - cold).max() < F32_TOL
    n = len(DOC + TAIL) + STEPS
    windows = (n - SPARSE["kernel_size"]) // SPARSE["kernel_stride"] + 1
    for name, cut in (("S", slice(None)), ("seg", slice(None)),
                      ("ck", slice(0, windows))):
        a, b = _slot(r, name, 1), _slot(cold_r, name, 1)
        assert np.abs(a[:, cut] - b[:, cut]).max() < 1e-4, name
    # the pool's rows of the tail, through each runner's own table
    for r_, rows in ((r, None), (cold_r, None)):
        assert r_.allocator.check_invariants() == []
    t_hot = r.allocator.tables[1][n // BLOCK]
    t_cold = cold_r.allocator.tables[1][n // BLOCK]
    assert np.abs(np.asarray(r.kv.k[:, t_hot], np.float32)
                  - np.asarray(cold_r.kv.k[:, t_cold], np.float32)
                  ).max() < 1e-4
    # with the restore skipped (state zero) the same admission reads wrong
    skip = runner_for(cfg, params32)
    skip_seen = tap(skip)
    served_logits(skip, skip_seen, 0, DOC, 1)
    skip.restore_snapshot = lambda slot, row: None
    wrong, _ = served_logits(skip, skip_seen, 1, DOC + TAIL, STEPS)
    assert np.abs(wrong - cold).max() > 0.1


def test_the_allocator_shares_what_ends_on_a_snapshot():
    a = pgd.BlockAllocator(64, 4, 16, snapshots=2)
    doc1, doc2, doc3 = ([d] * 17 for d in (1, 2, 3))

    def admit(seq, prompt, chunk=8):
        shared = a.allocate(seq, len(prompt) + 4, prompt=prompt)
        snap = a.begin_snapshot(seq, prompt, shared, chunk)
        a.register_prefix(seq, prompt) if a.snapshot_pending(seq) else None
        return shared, snap

    # a row, and the prompt's last whole chunk (two of 8: four blocks)
    assert admit(0, doc1) == (0, (0, 16))
    assert admit(1, doc2) == (0, (1, 16))
    assert a.snapshots_taken == 2 and a.check_invariants() == []
    # a match that ends on a snapshot is shared, and says which row
    assert admit(2, doc1 + [9] * 5) == (16, None) and a.restore_row[2] == 0
    # ... one that ends on none reuses 0: blocks registered, snapshot gone
    a.release(0), a.release(2)
    key = a._block_key[a.match_prefix(doc1 + [0])[-1]]
    a._snap_free.append(a._snap.pop(key)), a._snap_at.pop(key)
    assert a.match_prefix(doc1 + [0]) == []
    # (and, its prefill worth keeping, takes the chain's snapshot anew: a
    # tail of less than a chunk behind the document keeps the DOCUMENT's)
    assert admit(3, doc1 + [9] * 6) == (0, (0, 16))
    assert a.snapshots_taken == 3
    a.release(3)
    assert admit(3, doc1 + [8] * 6) == (16, None)
    a.release(3)
    assert a.check_invariants() == []
    # no row free: the LRU snapshot no sequence pins goes WITH its block
    a.release(1)
    before = a.evictions_total
    # (never for a SHORTER prompt: one chunk's state against two chunks')
    assert admit(4, [7] * 9) == (0, None)
    a.release(4)
    assert admit(4, doc3)[1] is not None        # evicts doc2's, the older
    assert a.snapshot_evictions == 1 and a.evictions_total == before + 1
    assert a.match_prefix(doc2 + [0]) == []
    assert len(a.match_prefix(doc1 + [0])) == 4
    assert admit(5, [4] * 17)[1] is not None    # ... then doc1's
    assert a.snapshot_evictions == 2 and a.match_prefix(doc1 + [0]) == []
    # a pinned one stays: seq 4 and 5 hold theirs, a third finds no row
    assert admit(6, [5] * 17) == (0, None)
    assert a.check_invariants() == []
    # a block evicted for room takes its snapshot along
    a.release(4), a.release(5), a.release(6)
    taken = [a.allocate(10 + i, 48) for i in range(5)] + [a.allocate(20, 9)]
    assert None not in taken and a.snapshot_evictions == 4     # all 63
    assert sorted(a._snap_free) == [0, 1] and a.check_invariants() == []
    # keys alone: nothing of it is touched
    plain = pgd.BlockAllocator(64, 4, 16)
    assert plain.begin_snapshot(0, doc1, 0, 1) is None
    plain.allocate(0, 21, prompt=doc1), plain.register_prefix(0, doc1)
    assert len(plain.match_prefix(doc1 + [0])) == 4
    assert plain.check_invariants() == []


@pytest.mark.parametrize("model_type", ["falcon_h1", "lfm2_moe",
                                        "qwen3_next"])
def test_every_recurrent_family_gains_it_through_the_same_door(model_type):
    """No line of their own: the per-slot arrays of their ``init_rec`` are
    what a snapshot copies, the slot axis read off the arrays."""
    import test_family_table as table

    cfg = families.config(table.HF[model_type])
    r = table.runner_for(cfg, num_slots=3)
    axes = r._snap_axes()
    rec = r.state.rec
    assert axes and "routed" not in axes
    assert all(rec[name].shape[ax] == 3 for name, ax in axes.items())
    assert all(r.snaps[name].shape[ax] == 2 for name, ax in axes.items())
    r.state = dataclasses.replace(r.state, rec={
        **rec, **{name: jnp.ones_like(rec[name]) for name in axes}})
    r.take_snapshot(2, 1)
    r.state = dataclasses.replace(r.state, rec=rec)
    r.restore_snapshot(0, 1)
    for name, ax in axes.items():
        got = np.asarray(r.state.rec[name], np.float32)
        assert np.take(got, 0, axis=ax).min() == 1.0, name
        assert np.take(got, 1, axis=ax).max() == 0.0, name
