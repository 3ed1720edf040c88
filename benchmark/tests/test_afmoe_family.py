"""The window / full attention decoder's family (``reference/afmoe_family.py``),
its configuration and its cell, added by files alone (PR 44): the hand
arithmetic of the published keys at the cut the file states, the served
pytree's shapes as the program builds them (a dense prefix beside rows), what
the new cell reports, the four new readers; and, end to end on the CPU, a
small model of the family served by the program and judged ``correct`` by its
family, with the control that fails: the same server judged by a family whose
window layers see every key."""

import json
import types

import pytest

import test_walk as tw
from conftest import ROOT, add_architecture
from harness import spec

CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "trinity-large-ep8.json").read_text())
HF = {k: v for k, v in CONFIG.items() if k not in spec.CONFIG_KEYS}
CELL = "trl-ep8-longshort-decode"
MiB, GiB = 2 ** 20, 2 ** 30
S, F = "sliding_attention", "full_attention"


def family(root=ROOT, name="afmoe_family"):
    return spec.load_family(spec.family_file(
        {"reference": {"family": name}}, "a test", root))


def test_the_hand_arithmetic_of_the_mixed_stacks_published_keys():
    """ISSUE 44's numbers, from the configuration file as committed."""
    fam = family()
    n = fam.dims(HF)
    assert (n["L"], n["nd"], n["full"], n["windowed"]) == (5, 1, 1, 4)
    assert fam.cache_layers(HF) == 5 and fam.row_kinds(HF) == (S, S, F, S)
    # q, its gate and o at 48 x 128; k and v at 8 x 128
    attn = 3 * 3072 * 6144 + 2 * 3072 * 1024
    assert fam.attn_params(HF) == attn == 62_914_560
    expert = 3 * 3072 * 3072
    assert fam.expert_params(HF) == expert == 28_311_552
    dense = attn + 3 * 3072 * 12288
    assert fam.dense_params(HF) == dense == 176_160_768
    # an expert layer outside its experts: attention, the router at its FULL
    # width 256, the shared expert
    fixed = attn + 3072 * 256 + expert
    assert fam.block_fixed_params(HF) == fixed == 92_012_544
    vectors = 4 * 3072 + 2 * 128            # four norms, q/k norm
    tables = 2 * 25024 * 3072 + 3072
    assert (dense + vectors, fixed + vectors + 256 + 32 * expert, tables) == (
        176_173_312, 997_995_008, 153_750_528)
    # (ISSUE 44's table adds its attention layer up to 63,913,472: its own
    # terms, q 18,874,368 + k, v 2 x 3,145,728 + o + gate + 256, give
    # 62,914,816, and every sum below it is 998,656 a layer lower)
    assert fam.param_count(HF) == 176_173_312 + 4 * 997_995_008 + tables
    assert fam.param_count(HF) == 4_321_903_872
    assert round(fam.param_count(HF) * 2 / GiB, 3) == 8.050
    assert fam.layer_params(HF) == (dense + 4 * (fixed + 32 * expert)) / 5
    # a token's forward multiplies 4 / 8 of an expert a layer here
    stack = dense + 4 * fixed
    assert fam.token_params(HF) == stack + 4 * 0.5 * expert
    # a step of 32 tokens is EXPECTED to touch 12.7 of the 32 held a layer
    touched = 32 * (1 - (1 - 4 / 256) ** 32)
    assert fam.experts_touched(HF, 32) == pytest.approx(touched)
    assert 12.6 < touched < 12.75
    head = 3072 * 25024
    assert fam.step_params(HF, 32) == pytest.approx(
        stack + 4 * touched * expert + head)
    # ~2.06 B weights a step: 4.1 GB in bfloat16, 5.0 ms at 819 GB/s
    assert abs(fam.step_params(HF, 32) * 2 / 819e9 - 0.0050) < 1e-4
    # THE COUNTS THE HARNESS MULTIPLIES BY CLIENT-SIDE TOKENS take the FULL
    # layer alone: 1 x 8 x 128 x 2 x 2 B = 4 KiB a token
    assert fam.kv_bytes_per_token(HF, 2.0) == 4096
    assert fam.q_elements_per_token(HF) == 48 * 128
    assert fam.attn_flops(HF, 10) == 4.0 * 48 * 128 * 10
    # the window layers: 4 x 4 KiB a token of each stream's window
    assert fam.window_bytes(HF, 1) == 4 * 4096
    assert fam.window_flops(HF, 10) == 4.0 * 4 * 48 * 128 * 10
    assert fam.expert_bytes(HF, 12) == 12 * expert * 2
    # counted over all 5 layers, 16 streams of 17 k would be charged 1.7 GB
    # a step where the window layers need 16 x 4096: the overstatement that
    # would read over 100%
    long = 16 * 17_000
    assert 5 * long * 4096 > 1.5 * (long * 4096 + fam.window_bytes(
        HF, 16 * 4096))
    # the file: the cut, the share, the engine's sizes
    assert CONFIG["reference"]["family"] == "afmoe_family"
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types",
        "num_experts", "vocab_size", "max_position_embeddings"}
    assert CONFIG["expert_parallel"] == {"size": 8, "rank": 0}
    assert CONFIG["layer_types"] == [S, S, S, F, S]
    eng = CONFIG["engine"]
    assert (eng["max_slots"], eng["kv_num_blocks"], eng["spec"],
            eng.get("quantization")) == (32, 2048, False, None)
    # a block of 64 tokens over 5 layers: 1.25 MiB; the pool 2.5 GiB
    block = 64 * 5 * fam.kv_bytes_per_token(HF, 2.0)
    assert block == 1.25 * MiB and 2048 * block == 2.5 * GiB
    # the sum the ``hbm`` block states: documents, reservations, spare
    long_blocks = -(-(16401 + 1024 + 1) // 64)
    short_blocks = -(-(257 + 1024 + 1) // 64)
    assert (long_blocks, short_blocks) == (273, 21)
    assert 1 + 4 * 256 + 32 * 21 + 351 == 2048
    assert CONFIG["context_size"] == 18432 >= 16401 + 1024


def test_every_published_number_of_the_mixed_stacks_catalog_row_is_in_the_file():
    """Every key of the published config stands in the file, unchanged but
    for the ones ``reduced`` names; no width is among those."""
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 3072,
        "intermediate_size": 12288,
        "layer_types": [F if (i + 1) % 4 == 0 else S for i in range(60)],
        "load_balance_coeff": 5e-05, "max_position_embeddings": 262144,
        "model_type": "afmoe", "moe_intermediate_size": 3072,
        "mup_enabled": True, "n_group": 1, "num_attention_heads": 48,
        "num_dense_layers": 6, "num_expert_groups": 1, "num_experts": 256,
        "num_experts_per_tok": 4, "num_hidden_layers": 60,
        "num_key_value_heads": 8, "num_limited_groups": 1,
        "num_shared_experts": 1, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "route_norm": True, "route_scale": 2.448,
        "score_func": "sigmoid", "sliding_window": 4096,
        "tie_word_embeddings": False, "topk_group": 1,
        "use_grouped_mm": True, "vocab_size": 200192}
    changed = {k for k, v in published.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "num_dense_layers", "layer_types",
                       "num_experts", "vocab_size"}
    assert changed < set(CONFIG["reduced"])
    assert CONFIG["layer_types"] == published["layer_types"][:5]
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert CONFIG["num_experts"] * 8 == published["num_experts"]
    entry = next(c for c in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["configs"]
        if c["name"] == CONFIG["name"])
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"]
    # the published depth is counted, though no chip here holds it: 6 dense
    # layers, 54 expert layers of the WHOLE 256 experts, the whole vocabulary
    fam = family()
    whole = {**published, "expert_parallel": None}
    assert 3.98e11 < fam.param_count(whole) < 4.0e11        # "400B"
    assert 1.2e10 < fam.token_params(whole) + 3072 * 200192 < 1.35e10  # A13B


def test_the_served_pytree_is_a_dense_prefix_beside_rows():
    """What harness/refcheck.py and the family's ``walk`` rest on: every
    leaf of the served ``layers`` pytree leads with the ROW (refcheck indexes
    every leaf at one row and hands it to ``decoder_layer``), the dense
    prefix's leaves are top-level tensors the walk reads one at a time, all
    under the names the family reads; and the family's count of the held
    share is the program's, to the parameter."""
    import jax

    from harness import refcheck
    from localai_tpu.models import llama as mdl
    from localai_tpu.models.llama import LlamaConfig

    fam = family()
    cfg = LlamaConfig.from_hf(HF)
    shapes = mdl.param_shapes(cfg)
    layers = shapes["layers"]
    assert {s[:2] for s in layers.values()} == {(1, 4)}
    assert set(layers) == set(fam.ATTN_LEAVES) | {
        "moe_gate", "expert_bias", "w_gate", "w_up", "w_down",
        "shared_gate", "shared_up", "shared_down"}
    assert {n for n in shapes if n.startswith("dense_")} == {
        "dense_" + n for n in fam.DENSE_LEAVES}
    assert shapes["dense_w_gate"] == (1, 3072, 12288)
    assert layers["wq"] == layers["wg"] == (1, 4, 3072, 48 * 128)
    assert layers["moe_gate"] == (1, 4, 3072, 256)       # the FULL router
    assert layers["w_gate"] == (1, 4, 32, 3072, 3072)    # the HELD experts
    abstract = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "bfloat16"), shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    assert refcheck.served_param_count(abstract) == fam.param_count(HF)
    # the published depth leaves two expert layers over whole rows: refused
    # with one sentence when the config is built
    with pytest.raises(ValueError, match="whole rows"):
        LlamaConfig.from_hf({**HF, "num_hidden_layers": 60,
                             "num_dense_layers": 6,
                             "layer_types": [F if (i + 1) % 4 == 0 else S
                                             for i in range(60)]})


def test_the_mixed_cell_reports_what_the_issue_names():
    """BENCHMARK.json as committed: ``trl-ep8-longshort-decode`` is the new
    configuration under the new mix, 32 callers on one chip. Of the
    end-to-end metrics it reports TPOT and set-up; per layer what
    ``qn80-ep8-decode`` reports of TPOT's movers but ITS three readers, and
    the four ``swa.*`` readers, which no other cell reports. Six cells, one
    on four chips; nothing that was there is changed."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert len(bench["workloads"]) == 6 and len(bench["configs"]) == 5
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    new, old = spec.load_cell(CELL), spec.load_cell("qn80-ep8-decode")
    assert new.chips == 1 and new.config_name == "trinity-large-ep8"
    decode_heavy = spec.load_cell("m7b-decode").traffic
    assert new.traffic["classes"] == decode_heavy["classes"]
    assert new.traffic["sampling"] == decode_heavy["sampling"]
    assert new.traffic["loop"] == "closed"
    assert new.traffic["prefix"] == {"share": 0.5, "pool": 4, "tokens": 16384,
                                     "fill_in_setup": True}
    assert new.drive["clients"] == new.max_slots == 32
    assert new.drive["limits"] == {"ttft_ms": 2000, "tpot_ms": 60}
    assert new.drive["ramp_s"] == 5.0
    assert {m["name"] for m in new.end_to_end} == {"tpot_ms_p90", "setup_s"}
    mine = {"swa.window_bw_share", "swa.full_bw_share",
            "swa.expert_bw_share", "swa.window_dead_share"}
    theirs = {"moe.expert_bw_share", "gdn.state_bw_share",
              "moe.experts_touched_mean"}
    assert {m["name"] for m in new.per_layer} == (
        {m["name"] for m in old.per_layer} - theirs) | mine
    for m in bench["per_layer"]:
        if m["name"] in mine:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p90"
    # the pool's shape the write reader looks for: 5 cache layers
    dims = spec.load_reader("runner.kv_move_share").__globals__["pool_dims"]
    assert tuple(sorted((5, 2048, 8, 64, 128))) in dims(new)
    for name in ("m7b-chat", "m7b-decode", "ms24b-tp4-chat", "ouro-decode",
                 "qn80-ep8-decode"):
        assert not mine & {m["name"] for m in spec.load_cell(name).per_layer}


def flight_row(ts, steps, live=32, columns=True, program="decode"):
    """A launch of ``live`` streams, half at 17 000 tokens and half at 300."""
    row = {"ts_unix": ts, "program": program, "steps": steps,
           "compile": False, "tokens": live * steps, "live_slots": live}
    if program.startswith("decode"):
        row["attended_tokens"] = steps * (live // 2) * (17_000 + 300)
    if columns and program.startswith("decode"):
        row["window_tokens"] = steps * (live // 2) * (4096 + 300)
        row["experts_touched"] = steps * 50
        row["local_assignments"] = steps * 64
    return row


@pytest.mark.parametrize("with_columns", [True, False])
def test_the_swa_readers_read_the_ring_and_the_scopes(with_columns):
    """The three shares: the bytes the slice's decode rows needed (window
    layers: ``window_tokens`` x 4 layers x 4 KiB; full layer:
    ``attended_tokens`` x 4 KiB; experts: ``experts_touched`` x 56.6 MB) over
    the decode programs' device seconds under ``attn.window_decode`` /
    ``attn.paged_decode`` / ``moe/experts``, against the HBM peak.
    ``swa.window_dead_share``: the window's decode rows' tokens behind the
    window over all their tokens, times 4 / 5. Against a program whose ring
    has no such columns and whose trace no such scopes (the parent), and
    against a family that prices no window, all four return None and raise
    nothing."""
    from harness.peaks import PEAKS

    fam = family()
    rows = [flight_row(10.0 + i, 1, columns=with_columns) for i in range(10)]
    rows += [flight_row(12.5, 2, columns=with_columns, program="decode_n"),
             flight_row(13.5, 0, columns=with_columns,
                        program="prefill_chunk")]
    win = "decode/layers/" + ("attn.window_decode/paged_decode_attn"
                              if with_columns else "attn.qkv")
    full = "decode/layers/" + ("attn.paged_decode/paged_decode_attn"
                               if with_columns else "attn.out")
    mid = "decode/layers/" + ("moe/experts" if with_columns else "mlp")
    cell = types.SimpleNamespace(
        family=fam, published=HF, chips=1, config=CONFIG)
    ctx = {
        "anchor": (0.0, 0.0), "cell": cell, "peak": PEAKS["TPU v5 lite"],
        "window": types.SimpleNamespace(t_open=9.0, t_close=30.0),
        "traced": {"flight": rows},
        "trace": {"start_unix": 10.0, "window_at_s": (0.0, 4.5), "op_rows": [
            ("jit__decode_paged_fn", win, "paged_decode_attn.1", 0.012),
            ("jit__decode_paged_n_fn", win, "paged_decode_attn.1", 0.006),
            ("jit__decode_paged_fn", full, "paged_decode_attn.2", 0.016),
            ("jit__decode_paged_fn", mid, "moe_experts.1", 0.03),
            ("jit__decode_paged_fn", "decode/layers/moe/shared", "fusion.4",
             0.5),
            ("jit__prefill_paged_fn", "prefill/layers/attn.prefill_window",
             "fusion.5", 0.5),
            ("jit__prefill_paged_fn", "prefill/layers/moe/experts",
             "moe_experts.2", 0.5)]}}
    names = ("swa.window_bw_share", "swa.full_bw_share",
             "swa.expert_bw_share", "swa.window_dead_share")
    readers = {n: spec.load_reader(n) for n in names}
    got = {n: read(ctx) for n, read in readers.items()}
    if not with_columns:
        assert got == dict.fromkeys(names)
        return
    # the slice [10, 14.5) holds rows 10 .. 14 and the two-step row: 7 steps
    assert got["swa.window_bw_share"] == pytest.approx(
        100 * (7 * 16 * 4396 * 4 * 4096 / 819e9) / 0.018)
    assert got["swa.full_bw_share"] == pytest.approx(
        100 * (7 * 16 * 17_300 * 4096 / 819e9) / 0.016)
    assert got["swa.expert_bw_share"] == pytest.approx(
        100 * (7 * 50 * 28_311_552 * 2 / 819e9) / 0.03)
    for name in names[:3]:
        assert 0 < got[name] < 100
    # the window [9, 30) holds all 12 steps: 12 904 of 17 300 tokens a pair
    # of streams lie behind the window, on 4 of the pool's 5 layers
    assert got["swa.window_dead_share"] == pytest.approx(
        100 * (17_300 - 4396) / 17_300 * 4 / 5)
    # with no trace at all (--trace 0 never asks; a voided slice does)
    for name in names[:3]:
        assert readers[name]({**ctx, "trace": None}) is None
    # a family that prices no window: another configuration's. The experts'
    # share is ``moe.expert_bw_share``'s reader itself, so it reads wherever
    # that one does (its entry's ``workloads`` names this cell alone)
    theirs = spec.load_reader("moe.expert_bw_share")
    assert got["swa.expert_bw_share"] == theirs(ctx)
    for other in ("llama_family", "qwen3_next_family"):
        plain = types.SimpleNamespace(
            family=family(name=other), published=HF, chips=1, config=CONFIG)
        for name in names:
            assert readers[name]({**ctx, "cell": plain}) == (
                theirs({**ctx, "cell": plain})
                if name == "swa.expert_bw_share" else None)


# a model of the family at the test's size, served in float32: a dense layer
# and a row (S | S S F S), window 8, 4 of 8 experts held (rank 1 of 2), top-2
SMALL = {
    "model_type": "afmoe", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 5, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "rope_theta": 10000,
    "rms_norm_eps": 1e-5, "sliding_window": 8,
    "global_attn_every_n_layers": 4, "layer_types": [S, S, S, F, S],
    "num_dense_layers": 1, "num_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 64, "num_shared_experts": 1,
    "score_func": "sigmoid", "route_norm": True, "route_scale": 2.448,
    "mup_enabled": True, "n_group": 1, "topk_group": 1,
    "expert_parallel": {"size": 2, "rank": 1},
    "engine": {"max_slots": 4, "attn_impl": "xla", "prefill_chunk": 64,
               "spec": False, "decode_steps_per_dispatch": 2,
               # the pool float32 too: under the family's peaked attention
               # (q/k norm gains 1.5) a bfloat16 pool alone reads 0.035
               "dtype": "float32", "kv_dtype": "float32"},
    "reference": {"epsilon": 0.006, "why": "a test"}}


def test_a_mixed_attention_model_runs_by_files_alone(bench_copy, cpu_peaks,
                                                     capsys):
    """A small model of the family, served by the program's normal path (the
    scheduler, chunked prefill with the window layers' own gather, the paged
    pool with a cache layer a layer) from its published keys, judged by its
    family: new files, none edited, ``correct``, and the parameter count is
    the family's count of the HELD share. The probes (16 to ~500 tokens)
    cross the window of 8 many times."""
    add_architecture(bench_copy, "tiny-af", "afmoe_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-af", 5)
    assert out["correct"] is True and out["failed"] == 0
    assert check["ok"] is True and check["positions"] == 64
    attn = 3 * 128 * 128 + 2 * 128 * 64 + 4 * 128 + 2 * 32
    dense = attn + 3 * 128 * 256
    block = attn + 128 * 8 + 8 + 3 * 128 * 64 + 4 * 3 * 128 * 64
    assert check["params_served"] == check["params_described"] == (
        dense + 4 * block + 2 * 512 * 128 + 128)
    assert check["max_shortfall"] < check["epsilon"] / 3


def test_the_control_fails_a_family_whose_window_layers_see_every_key(
        bench_copy, cpu_peaks, capsys):
    """THE FAILING CONTROL: the same server judged by the family with the
    window left out of its mask (a copy of the family file with that one line
    changed): the weights are the same, so the count agrees; the tokens are
    another model's, so the run is not ``correct``."""
    src = (bench_copy / "benchmark" / "reference"
           / "afmoe_family.py").read_text()
    line = "        visible &= j > i - window\n"
    assert src.count(line) == 1
    (bench_copy / "benchmark" / "reference"
     / "no_window_family.py").write_text(src.replace(line, "        pass\n"))
    add_architecture(bench_copy, "tiny-af", "no_window_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-af", 5)
    assert out["failed"] == 0 and out["correct"] is False
    assert check["params_served"] == check["params_described"]
    assert check["max_shortfall"] > 3 * check["epsilon"], check
