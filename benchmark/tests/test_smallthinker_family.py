"""The router-first decoder's family (``reference/smallthinker_family.py``),
its configuration and its cell, added by files alone (PR 65): the reference
against a few-line evaluation of one layer written here, the hand arithmetic
of the published keys at the cut the file states, the file against the
catalog's row key by key, the served pytree's shapes as the program builds
them (a LAYER the leading index), what the new cell reports, the eight ``smt.*`` readers on a hand-made
slice; and, end to end on the CPU, a small model of the family served by the
program and judged ``correct`` by its family, with the control that fails:
the same server judged by a family whose router reads the experts' input."""

import json
import types

import numpy as np
import pytest

import test_walk as tw
from conftest import ROOT, add_architecture
from harness import spec

CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "smallthinker-21b-a3b-pp4.json").read_text())
HF = {k: v for k, v in CONFIG.items() if k not in spec.CONFIG_KEYS}
CELL = "smt-longshort-decode"
MiB, GiB = 2 ** 20, 2 ** 30


def family(root=ROOT, name="smallthinker_family"):
    return spec.load_family(spec.family_file(
        {"reference": {"family": name}}, "a test", root))


def test_one_layer_is_the_five_equations_written_out():
    """The family's ``layer`` against the equations evaluated here in numpy,
    token by token and expert by expert (no vmap, no groups, no weights that
    are 0): a window layer with RoPE and a full layer without, the router on
    the ATTENTION's input, ReLU on the gate, softmax over the chosen three."""
    import jax

    fam = family()
    hf = {"hidden_size": 32, "num_hidden_layers": 2,
          "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 8,
          "rope_theta": 1500000, "rms_norm_eps": 1e-6,
          "sliding_window_size": 4, "sliding_window_layout": [0, 1],
          "rope_layout": [0, 1], "moe_num_primary_experts": 5,
          "moe_num_active_primary_experts": 3, "moe_ffn_hidden_size": 16,
          "vocab_size": 64}
    rng = np.random.default_rng(65)
    T, D, E, F, Hq, Hkv, hd = 9, 32, 5, 16, 6, 2, 8
    w = {"attn_norm": 1 + 0.3 * rng.standard_normal(D),
         "mlp_norm": 1 + 0.3 * rng.standard_normal(D),
         "wq": rng.standard_normal((D, Hq * hd)) * 0.3,
         "wk": rng.standard_normal((D, Hkv * hd)) * 0.3,
         "wv": rng.standard_normal((D, Hkv * hd)) * 0.3,
         "wo": rng.standard_normal((Hq * hd, D)) * 0.3,
         "moe_gate": rng.standard_normal((D, E)),
         "w_gate": rng.standard_normal((E, D, F)) * 0.3,
         "w_up": rng.standard_normal((E, D, F)) * 0.3,
         "w_down": rng.standard_normal((E, F, D)) * 0.3}
    x = rng.standard_normal((T, D))

    def norm(v, g):
        return v / np.sqrt(np.mean(v * v, -1, keepdims=True) + 1e-6) * g

    def rotate(v, pos):         # v [hd]: rotate-half, pairs (i, i + hd/2)
        inv = 1.0 / (1500000 ** (np.arange(0, hd, 2) / hd))
        c, s = np.cos(pos * inv), np.sin(pos * inv)
        a, b = v[:hd // 2], v[hd // 2:]
        return np.concatenate([a * c - b * s, b * c + a * s])

    def by_hand(windowed):
        h = norm(x, w["attn_norm"])
        q = (h @ w["wq"]).reshape(T, Hq, hd)
        k = (h @ w["wk"]).reshape(T, Hkv, hd)
        v = (h @ w["wv"]).reshape(T, Hkv, hd)
        out = np.zeros((T, Hq, hd))
        for i in range(T):
            first = max(0, i - 4 + 1) if windowed else 0
            for head in range(Hq):
                kv = head // (Hq // Hkv)
                qi = rotate(q[i, head], i) if windowed else q[i, head]
                s = np.array([
                    qi @ (rotate(k[j, kv], j) if windowed else k[j, kv])
                    for j in range(first, i + 1)]) / np.sqrt(hd)
                p = np.exp(s - s.max())
                out[i, head] = (p / p.sum()) @ v[first:i + 1, kv]
        x1 = x + out.reshape(T, Hq * hd) @ w["wo"]
        h2 = norm(x1, w["mlp_norm"])
        y = np.zeros_like(x1)
        for i in range(T):
            r = h[i] @ w["moe_gate"]            # the ATTENTION's input
            chosen = np.argsort(-r)[:3]
            p = np.exp(r[chosen] - r[chosen].max())
            for e, weight in zip(chosen, p / p.sum()):
                y[i] += weight * ((np.maximum(h2[i] @ w["w_gate"][e], 0)
                                   * (h2[i] @ w["w_up"][e]))
                                  @ w["w_down"][e])
        return x1 + y

    cos, sin = fam.rope_tables(hf, T)
    w32 = {n: np.asarray(a, np.float32) for n, a in w.items()}
    with jax.default_matmul_precision("highest"):
        for windowed in (False, True):
            got = np.asarray(fam.layer(np.asarray(x, np.float32), w32, cos,
                                       sin, hf, windowed))
            want = by_hand(windowed)
            assert np.abs(want - x).max() > 0.5
            np.testing.assert_allclose(got, want, atol=2e-5)
        # ... and the harness's reader gets a layer under BOTH kinds, of which
        # the walk keeps the one the layout names: full, then window
        both = np.asarray(fam.decoder_layer(np.asarray(x, np.float32), w32,
                                            cos, sin, hf))
        np.testing.assert_allclose(both[0], by_hand(False), atol=2e-5)
        np.testing.assert_allclose(both[1], by_hand(True), atol=2e-5)
        seen = []

        def one_layer(xs, index):
            seen.append(index)
            return np.stack([xs + 1.0, xs + 10.0], axis=1)  # [B, 2, T, D]

        out = fam.walk(np.zeros((3, T, D), np.float32), one_layer, 2, None,
                       hf)
        assert seen == [0, 1] and (out == 11.0).all()   # full, then window
        with pytest.raises(ValueError, match="holds 3 layers"):
            fam.walk(np.zeros((3, T, D), np.float32), one_layer, 3, None, hf)


def test_the_hand_arithmetic_of_the_router_first_stacks_published_keys():
    """ISSUE 65's numbers, from the configuration file as committed."""
    fam = family()
    n = fam.dims(HF)
    assert (n["L"], n["full"], n["windowed"]) == (12, 3, 9)
    assert n["kinds"] == (False, True, True, True) * 3
    assert fam.cache_layers(HF) == 12
    # q and o at 28 x 128; k and v at 4 x 128
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert fam.attn_params(HF) == attn == 9_175_040 * 2 + 1_310_720 * 2
    expert = 3 * 2560 * 768
    assert fam.expert_params(HF) == expert == 5_898_240
    fixed = attn + 2560 * 64
    assert fam.block_fixed_params(HF) == fixed == 21_135_360
    assert fam.layer_params(HF) == fixed + 64 * expert
    layer = fixed + 64 * expert + 2 * 2560
    assert layer == 398_627_840
    tables = 2 * 151936 * 2560
    assert tables == 777_912_320
    assert fam.param_count(HF) == 12 * layer + tables + 2560
    assert fam.param_count(HF) == 5_561_448_960
    assert round(fam.param_count(HF) * 2 / GiB, 2) == 10.36
    # the published depth: 52 layers, "21B", ~3 B a token with the head
    whole = {**HF, "num_hidden_layers": 52,
             "sliding_window_layout": [0, 1, 1, 1] * 13,
             "rope_layout": [0, 1, 1, 1] * 13}
    assert 2.14e10 < fam.param_count(whole) < 2.16e10
    assert 2.9e9 < fam.token_params(whole) + 2560 * 151936 < 3.4e9
    # a token's forward multiplies 6 experts a layer here
    assert fam.token_params(HF) == 12 * (fixed + 6 * expert)
    # a step of 32 tokens is EXPECTED to touch 61.3 of the 64 a layer
    touched = 64 * (1 - (1 - 6 / 64) ** 32)
    assert fam.experts_touched(HF, 32) == pytest.approx(touched)
    assert 61.2 < touched < 61.4
    head = 2560 * 151936
    assert fam.step_params(HF, 32) == pytest.approx(
        12 * (fixed + touched * expert) + head)
    # ~4.98 B weights a step: 9.96 GB in bfloat16, 12.2 ms at 819 GB/s
    assert abs(fam.step_params(HF, 32) * 2 / 819e9 - 0.01217) < 1e-4
    # THE COUNTS THE HARNESS MULTIPLIES BY CLIENT-SIDE TOKENS take the FULL
    # layers alone: 3 x 4 x 128 x 2 x 2 B = 6 KiB a token
    assert fam.kv_bytes_per_token(HF, 2.0) == 6144
    assert fam.q_elements_per_token(HF) == 3 * 28 * 128
    assert fam.attn_flops(HF, 10) == 4.0 * 3 * 28 * 128 * 10
    # the window layers: 9 x 2 KiB a token of each stream's window
    assert fam.window_bytes(HF, 1) == 9 * 2048
    assert fam.window_flops(HF, 10) == 4.0 * 9 * 28 * 128 * 10
    assert fam.expert_bytes(HF, 61) == 61 * expert * 2
    # the file: the cut, the engine's sizes
    assert CONFIG["reference"]["family"] == "smallthinker_family"
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "sliding_window_layout", "rope_layout",
        "max_position_embeddings"}
    assert "expert_parallel" not in CONFIG
    eng = CONFIG["engine"]
    assert (eng["max_slots"], eng["kv_num_blocks"], eng["spec"],
            eng.get("quantization")) == (32, 2048, False, None)
    # a block of 64 tokens over 12 layers: 1.5 MiB; the pool 3 GiB
    block = 64 * 12 * 4 * 128 * 2 * 2
    assert block == 1.5 * MiB and 2048 * block == 3 * GiB
    assert CONFIG["hbm"]["kv_per_token_kib"] * 1024 * 64 == block
    # the sum the ``hbm`` block states: documents, reservations, spare
    long_blocks = -(-(12288 + 1 + 16 + 1024 + 1) // 64)
    short_blocks = -(-(257 + 1024 + 1) // 64)
    assert (long_blocks, short_blocks) == (209, 21)
    assert 1 + 4 * 192 + 32 * 21 + 607 == 2048
    assert CONFIG["context_size"] == 14336 >= 12288 + 1 + 16 + 1024


def test_every_published_number_of_the_catalog_row_is_in_the_file():
    """Every key of the published config stands in the file, unchanged but
    for the ones ``reduced`` names; no width is among those."""
    layout = [0, 1, 1, 1] * 13
    published = {
        "head_dim": 128, "hidden_size": 2560,
        "max_position_embeddings": 16384,
        "model_name": "smallthinker_21b_instruct",
        "moe_ffn_hidden_size": 768, "moe_num_active_primary_experts": 6,
        "moe_num_primary_experts": 64,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "num_attention_heads": 28, "num_hidden_layers": 52,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_layout": layout, "rope_scaling": None, "rope_theta": 1500000,
        "sliding_window_layout": layout, "sliding_window_size": 4096,
        "tie_word_embeddings": False, "vocab_size": 151936}
    changed = {k for k, v in published.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "sliding_window_layout",
                       "rope_layout"}
    assert changed < set(CONFIG["reduced"])
    assert CONFIG["sliding_window_layout"] == layout[:12] == CONFIG[
        "rope_layout"]
    # the one key beside the row's: the type the program enters the family by
    assert set(HF) - set(published) == {"model_type"}
    entry = next(c for c in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["configs"]
        if c["name"] == CONFIG["name"])
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"]
    assert entry["file"] == "benchmark/configs/smallthinker-21b-a3b-pp4.json"


def test_the_served_pytree_is_layers_under_the_familys_names():
    """What harness/refcheck.py rests on: every leaf of the served ``layers``
    pytree leads with the LAYER (the check copies one leading index of every
    leaf out of the stack: a layer's 64 experts, not a row's 256), under the
    names the family reads; and the family's count is the program's, to the
    parameter."""
    import jax

    from harness import refcheck
    from localai_tpu.models import llama as mdl
    from localai_tpu.models.llama import LlamaConfig

    fam = family()
    cfg = LlamaConfig.from_hf(HF)
    assert type(cfg).__name__ == "SmallThinkerConfig"
    shapes = mdl.param_shapes(cfg)
    layers = shapes["layers"]
    assert {s[0] for s in layers.values()} == {12}
    assert set(layers) == {"attn_norm", "mlp_norm", "wq", "wk", "wv", "wo",
                           "moe_gate"} | set(fam.EXPERT_LEAVES)
    assert layers["wq"] == (12, 2560, 28 * 128)
    assert layers["moe_gate"] == (12, 2560, 64)
    assert layers["w_gate"] == (12, 64, 2560, 768)
    assert shapes["embed"] == (151936, 2560) == shapes["lm_head"][::-1]
    abstract = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "bfloat16"), shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    assert refcheck.served_param_count(abstract) == fam.param_count(HF)
    # the published depth builds too: thirteen rows for the forward's scan
    whole = LlamaConfig.from_hf({
        **HF, "num_hidden_layers": 52,
        "sliding_window_layout": [0, 1, 1, 1] * 13,
        "rope_layout": [0, 1, 1, 1] * 13})
    assert (whole.rows, whole.row_layers) == (13, 4)


def test_the_router_first_cell_reports_what_the_issue_names():
    """BENCHMARK.json as committed: ``smt-longshort-decode`` is the new
    configuration under the new mix, 32 callers on one chip. Of the
    end-to-end metrics it reports TPOT and set-up; per layer what
    ``trl-ep8-longshort-decode`` reports of TPOT's movers but ITS four
    readers, and the eight ``smt.*`` readers, which no other cell reports.
    Twelve cells, one on four chips; nothing that was there is changed."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert len(bench["workloads"]) == 12 and len(bench["configs"]) == 11
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    new, old = spec.load_cell(CELL), spec.load_cell("trl-ep8-longshort-decode")
    assert new.chips == 1 and new.config_name == "smallthinker-21b-a3b-pp4"
    assert {k: v for k, v in new.traffic.items() if k not in ("who", "prefix")
            } == {k: v for k, v in old.traffic.items()
                  if k not in ("who", "prefix")}
    assert new.traffic["prefix"] == {"share": 0.5, "pool": 4, "tokens": 12288,
                                     "fill_in_setup": True}
    assert old.traffic["prefix"] == {**new.traffic["prefix"], "tokens": 16384}
    assert new.drive["clients"] == new.max_slots == 32
    assert new.drive["limits"] == {"ttft_ms": 2000, "tpot_ms": 60}
    assert new.drive["ramp_s"] == 5.0
    assert {m["name"] for m in new.end_to_end} == {"tpot_ms_p90", "setup_s"}
    mine = {"smt.expert_bw_share", "smt.experts_touched_mean",
            "smt.expert_rows_mean", "smt.window_bw_share",
            "smt.full_bw_share", "smt.window_dead_share", "smt.router_share",
            "smt.chunk_expert_share"}
    theirs = {"swa.window_bw_share", "swa.full_bw_share",
              "swa.expert_bw_share", "swa.window_dead_share"}
    assert {m["name"] for m in new.per_layer} == (
        {m["name"] for m in old.per_layer} - theirs) | mine
    assert [m["name"] for m in bench["per_layer"]][-8:] == [
        "smt.expert_bw_share", "smt.experts_touched_mean",
        "smt.expert_rows_mean", "smt.window_bw_share", "smt.full_bw_share",
        "smt.window_dead_share", "smt.router_share",
        "smt.chunk_expert_share"]
    for m in bench["per_layer"]:
        if m["name"] in mine:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p90"
    # the pool's shape the write reader looks for: 12 cache layers
    dims = spec.load_reader("runner.kv_move_share").__globals__["pool_dims"]
    assert tuple(sorted((12, 2048, 4, 64, 128))) in dims(new)
    for w in bench["workloads"][:-1]:
        assert not mine & {m["name"]
                           for m in spec.load_cell(w["name"]).per_layer}


def flight_row(ts, steps, live=32, columns=True, program="decode"):
    """A launch of ``live`` streams, half at 13 000 tokens and half at 300."""
    row = {"ts_unix": ts, "program": program, "steps": steps,
           "compile": False, "tokens": live * steps, "live_slots": live}
    if program.startswith("decode"):
        row["attended_tokens"] = steps * (live // 2) * (13_000 + 300)
    if columns and program.startswith("decode"):
        row["window_tokens"] = steps * (live // 2) * (4096 + 300)
        row["experts_touched"] = steps * 12 * 58
        row["local_assignments"] = steps * 12 * 32 * 6
    return row


@pytest.mark.parametrize("with_columns", [True, False])
def test_the_smt_readers_read_the_ring_and_the_scopes(with_columns):
    """A hand-made slice: the three shares of a roofline (window layers:
    ``window_tokens`` x 9 layers x 2 KiB; full layers: ``attended_tokens`` x
    6 KiB; experts: ``experts_touched`` x 11.8 MB) over the decode programs'
    device seconds under ``attn.window_decode`` / ``attn.paged_decode`` /
    ``moe/experts``; the two means and the dead share from the ring; the
    routers' share of the decode programs' time; the chunks' experts' share
    of the slice's busy time. Against a program whose ring has no such
    columns and whose trace no such scopes (the parent), all eight return
    None and raise nothing."""
    from harness.peaks import PEAKS

    fam = family()
    rows = [flight_row(10.0 + i, 1, columns=with_columns) for i in range(10)]
    rows += [flight_row(12.5, 2, columns=with_columns, program="decode_n"),
             flight_row(13.5, 0, columns=with_columns,
                        program="prefill_chunk")]
    pre = "decode/layers/"
    win = pre + ("attn.window_decode/paged_decode_attn" if with_columns
                 else "attn.qkv")
    full = pre + ("attn.paged_decode/paged_decode_attn" if with_columns
                  else "attn.out")
    mid = pre + ("moe/experts/moe_experts" if with_columns else "mlp")
    router = pre + ("moe/router" if with_columns else "mlp")
    chunk = "prefill/layers/" + ("moe/experts/moe_experts" if with_columns
                                 else "mlp")
    cell = types.SimpleNamespace(
        family=fam, published=HF, chips=1, config=CONFIG)
    ctx = {
        "anchor": (0.0, 0.0), "cell": cell, "peak": PEAKS["TPU v5 lite"],
        "window": types.SimpleNamespace(t_open=9.0, t_close=30.0),
        "traced": {"flight": rows},
        "trace": {"start_unix": 10.0, "window_at_s": (0.0, 4.5),
                  "busy_s": 0.2, "op_rows": [
            ("jit__decode_paged_fn", win, "paged_decode_attn.1", 0.008),
            ("jit__decode_paged_n_fn", win, "paged_decode_attn.1", 0.004),
            ("jit__decode_paged_fn", full, "paged_decode_attn.2", 0.012),
            ("jit__decode_paged_fn", mid, "moe_experts.1", 0.08),
            ("jit__decode_paged_fn", router, "fusion.4", 0.004),
            ("jit__decode_paged_fn", "decode/lm_head", "fusion.9", 0.002),
            ("jit__prefill_paged_fn", "prefill/layers/attn.prefill_window",
             "fusion.5", 0.05),
            ("jit__prefill_paged_fn", chunk, "moe_experts.2", 0.03)]}}
    names = ("smt.window_bw_share", "smt.full_bw_share",
             "smt.expert_bw_share", "smt.experts_touched_mean",
             "smt.expert_rows_mean", "smt.window_dead_share",
             "smt.router_share", "smt.chunk_expert_share")
    readers = {n: spec.load_reader(n) for n in names}
    got = {n: read(ctx) for n, read in readers.items()}
    if not with_columns:
        assert got == dict.fromkeys(names)
        return
    # the slice [10, 14.5) holds rows 10 .. 14 and the two-step row: 7 steps
    assert got["smt.window_bw_share"] == pytest.approx(
        100 * (7 * 16 * 4396 * 9 * 2048 / 819e9) / 0.012)
    assert got["smt.full_bw_share"] == pytest.approx(
        100 * (7 * 16 * 13_300 * 6144 / 819e9) / 0.012)
    assert got["smt.expert_bw_share"] == pytest.approx(
        100 * (7 * 12 * 58 * 5_898_240 * 2 / 819e9) / 0.08)
    for name in names[:3]:
        assert 0 < got[name] < 100
    # the window [9, 30) holds all 12 steps
    assert got["smt.experts_touched_mean"] == pytest.approx(58.0)
    assert got["smt.expert_rows_mean"] == pytest.approx(32 * 6 / 58)
    # 8 904 of 13 300 tokens a pair of streams lie behind the window, on 9 of
    # the pool's 12 layers
    assert got["smt.window_dead_share"] == pytest.approx(
        100 * (13_300 - 4396) / 13_300 * 9 / 12)
    assert got["smt.router_share"] == pytest.approx(100 * 0.004 / 0.11)
    assert got["smt.chunk_expert_share"] == pytest.approx(100 * 0.03 / 0.2)
    # with no trace at all (--trace 0 never asks; a voided slice does)
    for name in ("smt.window_bw_share", "smt.full_bw_share",
                 "smt.expert_bw_share", "smt.router_share",
                 "smt.chunk_expert_share"):
        assert readers[name]({**ctx, "trace": None}) is None
    # five are accepted readers under this cell's names
    for mine, theirs in (("smt.expert_bw_share", "moe.expert_bw_share"),
                         ("smt.experts_touched_mean",
                          "moe.experts_touched_mean"),
                         ("smt.expert_rows_mean", "lfm2.expert_rows_mean"),
                         ("smt.window_bw_share", "swa.window_bw_share"),
                         ("smt.full_bw_share", "swa.full_bw_share"),
                         ("smt.window_dead_share", "swa.window_dead_share")):
        assert got[mine] == spec.load_reader(theirs)(ctx)


# a model of the family at the test's size, served in float32: one row
# (F W W W), window 8, 8 experts top-3 all held
SMALL = {
    "model_type": "smallthinker", "hidden_size": 128,
    "num_hidden_layers": 4, "num_attention_heads": 6,
    "num_key_value_heads": 2, "head_dim": 32, "rope_theta": 1500000,
    "rms_norm_eps": 1e-6, "sliding_window_size": 8,
    "sliding_window_layout": [0, 1, 1, 1], "rope_layout": [0, 1, 1, 1],
    "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 3,
    "moe_ffn_hidden_size": 64, "moe_primary_router_apply_softmax": True,
    "norm_topk_prob": True,
    "engine": {"max_slots": 4, "attn_impl": "xla", "prefill_chunk": 64,
               "spec": False, "decode_steps_per_dispatch": 2,
               "dtype": "float32", "kv_dtype": "float32"},
    "reference": {"epsilon": 0.006, "why": "a test"}}


def test_a_router_first_model_runs_by_files_alone(bench_copy, cpu_peaks,
                                                  capsys):
    """A small model of the family, served by the program's normal path (the
    scheduler, chunked prefill with the window layers' own gather, the paged
    pool with a cache layer a layer) from its published keys, judged by its
    family: new files, none edited, ``correct``, and the parameter count is
    the family's. The probes (16 to ~500 tokens) cross the window of 8 many
    times."""
    add_architecture(bench_copy, "tiny-smt", "smallthinker_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-smt", 5)
    assert out["correct"] is True and out["failed"] == 0
    assert check["ok"] is True and check["positions"] == 64
    layer = (2 * 128 * 192 + 2 * 128 * 64 + 128 * 8 + 2 * 128
             + 8 * 3 * 128 * 64)
    assert check["params_served"] == check["params_described"] == (
        4 * layer + 2 * 512 * 128 + 128)
    assert check["max_shortfall"] < check["epsilon"] / 3


def test_the_control_fails_a_family_whose_router_reads_the_experts_input(
        bench_copy, cpu_peaks, capsys):
    """THE FAILING CONTROL: the same server judged by the family with the
    routing taken from the post-attention normed tensor, where every other
    family has it (a copy of the family file with that one call moved): the
    weights are the same, so the count agrees; the tokens are another
    model's, so the run is not ``correct``."""
    src = (bench_copy / "benchmark" / "reference"
           / "smallthinker_family.py").read_text()
    early = ('    route = routing(h, w["moe_gate"], hf)       '
             '# from the ATTENTION\'s input\n')
    late = ('    return x + experts(rms_norm(x, w["mlp_norm"], eps), route, '
            'hf, held)\n')
    assert src.count(early) == 1 and src.count(late) == 1
    (bench_copy / "benchmark" / "reference"
     / "late_router_family.py").write_text(
        src.replace(early, "").replace(late, (
            '    h = rms_norm(x, w["mlp_norm"], eps)\n'
            '    return x + experts(h, routing(h, w["moe_gate"], hf), hf, '
            'held)\n')))
    add_architecture(bench_copy, "tiny-smt", "late_router_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-smt", 5)
    assert out["failed"] == 0 and out["correct"] is False
    assert check["params_served"] == check["params_described"]
    assert check["max_shortfall"] > 3 * check["epsilon"], check
