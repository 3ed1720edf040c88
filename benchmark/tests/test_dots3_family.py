"""The family of the stack with an indexer (``reference/dots3_family.py``),
its configuration and its cell, added by files alone (PR 51): the hand
arithmetic of the published keys at the cut the file states, the catalog's
numbers in the file, the served pytree's shapes as the program builds them
(dense and lone layers beside a row a period), what the new cell reports, the
five new readers; and, end to end on the CPU, a small model of the family
served by the program with the selection and the window both binding and
judged ``correct`` by its family, with the control that fails: the same
server judged by a family whose full layers attend every row."""

import json
import types

import pytest

import test_walk as tw
from conftest import ROOT, add_architecture
from harness import spec

CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "dots3-note-ep16.json").read_text())
HF = {k: v for k, v in CONFIG.items() if k not in spec.CONFIG_KEYS}
CELL = "dots3-ep16-longdoc-decode"
GiB = 2 ** 30
F, S = "full_attention", "sliding_attention"
NEW = ("dsa.index_bw_share", "dsa.select_share",
       "dsa.sparse_attend_roofline", "dsa.chunk_attend_share",
       "mla.window_roofline")
# an accepted entry whose list this cell was appended to (the experts' scope
# and the ring's column are the ones qn80's cell has)
JOINED = "moe.expert_bw_share"


def family(root=ROOT, name="dots3_family"):
    return spec.load_family(spec.family_file(
        {"reference": {"family": name}}, "a test", root))


def test_the_hand_arithmetic_of_the_sparse_stacks_published_keys():
    """ISSUE 51's numbers, from the configuration file as committed."""
    fam = family()
    n = fam.dims(HF)
    assert (n["L"], n["nd"], n["nl"], n["M"], n["P"], n["full"],
            n["windowed"], n["window"]) == (6, 1, 1, 4, 1, 3, 3, 513)
    assert n["types"] == (F, F, S, S, S, F)
    assert (n["E"], n["size"], n["topk"], n["Hi"], n["di"],
            n["topk_rows"]) == (16, 16, 8, 64, 128, 2048)
    assert fam.latent_width(HF, F) == 576 and fam.latent_width(HF, S) == 1088
    # a full layer: q down and up, the latent's down and up, o, the gate
    full = (5120 * 1024, 1024 * 128 * 192, 5120 * 576, 512 * 128 * 256,
            128 * 128 * 5120, 5120 * 128)
    index = (1024 * 64 * 128, 5120 * 128, 5120 * 64)
    assert sum(index) + 2 * 128 == 9_371_904        # with the LayerNorm
    assert fam.index_params(HF) == sum(index)
    assert fam.attn_params(HF, F) == sum(full) + sum(index)
    assert fam.attn_params(HF, F) + fam.attn_vectors(HF, F) == 144_060_160
    window = (5120 * 1024, 1024 * 64 * 256, 5120 * 1088, 1024 * 64 * 320,
              64 * 128 * 5120, 5120 * 64)
    assert fam.attn_params(HF, S) == sum(window)
    assert fam.attn_params(HF, S) + fam.attn_vectors(HF, S) == 90_845_184
    expert = 3 * 5120 * 1536
    assert fam.expert_params(HF) == expert == 23_592_960
    # router at its FULL width 256, its bias, the shared expert
    assert fam.block_fixed_params(HF) + 256 == 24_903_936
    assert 3 * 5120 * 13824 == 212_336_640
    tables = 2 * 19008 * 5120 + 5120
    assert fam.table_params(HF) == tables == 194_647_040
    layer0 = 144_060_160 + 212_336_640
    full_layer = 144_060_160 + 24_903_936 + 16 * expert
    window_layer = 90_845_184 + 24_903_936 + 16 * expert
    assert (layer0, full_layer, window_layer) == (
        356_396_800, 546_451_456, 493_236_480)
    assert fam.param_count(HF) == (layer0 + 2 * full_layer + 3 * window_layer
                                   + tables) == 3_123_656_192
    assert round(fam.param_count(HF) * 2 / GiB, 2) == 5.82
    # a token's forward multiplies 8 / 16 of an expert a layer here
    assert fam.token_params(HF) == fam._stack_params(HF, 0.5)
    # a step of 32 tokens is EXPECTED to touch 10.2 of the 16 held a layer
    touched = 16 * (1 - (1 - 8 / 256) ** 32)
    assert fam.experts_touched(HF, 32) == pytest.approx(touched)
    assert 10.1 < touched < 10.3
    head = 5120 * 19008
    fixed = fam._stack_params(HF, 0)
    assert fam.step_params(HF, 32) == pytest.approx(
        fixed + 5 * touched * expert + head)
    # ISSUE 51's prediction: 2.28 GB outside the experts, 2.41 GB of experts
    assert abs((fixed + head) * 2 / 1e9 - 2.28) < 0.01
    assert abs(5 * touched * expert * 2 / 1e9 - 2.41) < 0.01
    # what a step reads of EVERY attended token: the full layers' index keys
    assert fam.kv_bytes_per_token(HF, 2.0) == 3 * 128 * 2
    assert fam.attn_flops(HF, 10) == fam.index_flops(HF, 10) == (
        2.0 * 3 * 64 * 128 * 10)
    assert fam.q_elements_per_token(HF) == 3 * 64 * 128 // 2
    # the cell's decode step, by its needs: 32 streams at ~33.4 k rows
    rows, chosen = 32 * 33_400, 32 * 2048
    assert abs(fam.index_bytes(HF, rows, 32) / 3 / 1e6 - 274) < 1  # a layer
    assert abs(fam.select_bytes(HF, chosen, 32) / 3 / 1e6 - 78) < 1
    assert abs(fam.window_bytes(HF, 32 * 513) / 3 / 1e6 - 35.7) < 0.1
    # the published form's flops: the smaller count, never overstated
    assert fam.select_flops(HF, 10) == 2.0 * 3 * 128 * 320 * 10
    assert fam.window_flops(HF, 10) == 2.0 * 3 * 64 * 384 * 10
    assert 2.0 * 3 * 128 * (576 + 512) * 10 > fam.select_flops(HF, 10)
    assert fam.expert_bytes(HF, 9) == 9 * expert * 2
    assert fam.cache_layers(HF) == 6
    # the file: the cut, the share, the engine's sizes, the pool's arithmetic
    assert CONFIG["reference"]["family"] == "dots3_family"
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    assert CONFIG["expert_parallel"] == {"size": 16, "rank": 0}
    assert {"rope pairs", "apply_mla_qkv_lora_rescale", "sliding_window_size",
            "attention_gate_type", "indexer", "weights"} <= set(
                CONFIG["assumed"])
    eng = CONFIG["engine"]
    assert (eng["max_slots"], eng["kv_num_blocks"], eng["spec"],
            eng.get("quantization")) == (32, 3072, False, None)
    assert 1 + 4 * 512 + 32 * 21 + 351 == 3072
    lanes = 3 * (640 + 128) + 3 * 1152
    assert lanes * 2 == 11_520 and 3 * (576 + 128) + 3 * 1088 == 5376
    assert round(3072 * 64 * lanes * 2 / GiB, 2) == 2.11
    assert CONFIG["hbm"]["kv_per_token_kib"] == 10.5 == 5376 * 2 / 1024
    assert CONFIG["context_size"] == 34816 >= 1 + 32768 + 16 + 1024
    assert CONFIG["context_size"] % 1024 == 0


def test_every_published_number_of_the_sparse_stacks_catalog_row_is_in_the_file():
    """Every key of the published config stands in the file, unchanged but
    for the ones ``reduced`` names; no width is among those."""
    published = {
        "apply_mla_qkv_lora_rescale": True, "attention_bias": False,
        "attention_gate_type": "headwise", "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 5120, "index_head_dim": 128,
        "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 13824,
        "kv_lora_rank": 512, "layer_types": [F, F] + [S, S, S, F] * 11,
        "max_position_embeddings": 524288, "model_type": "dots3_note",
        "moe_intermediate_size": 1536, "moe_layer_freq": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 46,
        "num_key_value_heads": 128, "q_lora_rank": 1024,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 80000000,
        "routed_scaling_factor": 1, "scoring_func": "sigmoid",
        "sliding_window_size": 513, "swa_attention_gate_type": "headwise",
        "swa_kv_lora_rank": 1024, "swa_num_attention_heads": 64,
        "swa_num_key_value_heads": 64, "swa_q_lora_rank": 1024,
        "swa_qk_nope_head_dim": 192, "swa_qk_rope_head_dim": 64,
        "swa_rope_theta": 50000, "swa_v_head_dim": 128,
        "tie_word_embeddings": False, "topk_method": "noaux_tc",
        "v_head_dim": 128, "vocab_size": 152064}
    changed = {k for k, v in published.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert changed < set(CONFIG["reduced"])
    assert CONFIG["vocab_size"] * 8 == published["vocab_size"]
    assert CONFIG["n_routed_experts"] * 16 == published["n_routed_experts"]
    entry = next(c for c in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["configs"]
        if c["name"] == CONFIG["name"])
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"]
    # the published depth is counted, though no chip here holds it: 13 full
    # and 33 window layers, the WHOLE 256 experts, the whole vocabulary
    fam = family()
    whole = {**published, "expert_parallel": None}
    n = fam.dims(whole)
    assert (n["full"], n["windowed"], n["P"], n["nl"]) == (13, 33, 11, 1)
    # 279.6 B and 15.5 B a token with the head: the card's "288B-A17B"
    # counts the towers and the multi-token-prediction module besides, which
    # no key of the config names and nothing here serves
    assert 2.79e11 < fam.param_count(whole) < 2.80e11
    active = fam.token_params(whole) + 5120 * 152064
    assert 1.5e10 < active < 1.6e10     # 8 + 1 experts of 257 a layer


def test_the_served_pytree_is_dense_and_lone_layers_beside_a_row_a_period():
    """What harness/refcheck.py and the family's ``walk`` rest on: every
    leaf of the served ``layers`` pytree leads with the PERIOD (refcheck
    indexes every leaf at one row and hands it to ``decoder_layer``), the
    dense and the lone layers' leaves are top-level tensors the walk reads
    through ``leaf``, all under the names the family reads; and the family's
    count of the held share is the program's, to the parameter."""
    import jax

    from harness import refcheck
    from localai_tpu.models import llama as mdl
    from localai_tpu.models.llama import LlamaConfig

    fam = family()
    cfg = LlamaConfig.from_hf(HF)
    shapes = mdl.param_shapes(cfg)
    layers = shapes["layers"]
    assert {s[0] for s in layers.values()} == {1}
    attn = set(fam.ATTN_LEAVES)
    assert set(layers) == attn | set(fam.INDEX_LEAVES) | {
        "swa_" + n for n in attn} | {
        "attn_norm", "mlp_norm", "moe_gate", "expert_bias", "w_gate", "w_up",
        "w_down", "shared_gate", "shared_up", "shared_down"}
    front = {"attn_norm", "mlp_norm"} | attn | set(fam.INDEX_LEAVES)
    assert {n for n in shapes if n.startswith("dense_")} == {
        "dense_" + n for n in front | set(fam.EXPERT_LEAVES)}
    assert {n for n in shapes if n.startswith("lone_")} == {
        "lone_" + n for n in front | {
            "moe_gate", "expert_bias", "w_gate", "w_up", "w_down",
            "shared_gate", "shared_up", "shared_down"}}
    assert shapes["dense_w_gate"] == (1, 5120, 13824)
    assert shapes["lone_w_gate"] == (1, 1, 16, 5120, 1536)
    assert layers["wkv_a"] == (1, 5120, 576)
    assert layers["swa_wkv_a"] == (1, 3, 5120, 1088)
    assert layers["swa_wkv_b"] == (1, 3, 1024, 64 * 320)
    assert layers["idx_wq"] == (1, 1024, 64 * 128)
    assert layers["moe_gate"] == (1, 4, 5120, 256)       # the FULL router
    assert layers["w_gate"] == (1, 4, 16, 5120, 1536)    # the HELD experts
    abstract = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "bfloat16"), shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    assert refcheck.served_param_count(abstract) == fam.param_count(HF)


def test_the_sparse_cell_reports_what_the_issue_names():
    """BENCHMARK.json as committed: ``dots3-ep16-longdoc-decode`` is the new
    configuration under ``axk1-ep16-longdoc-decode``'s mix AS IT STANDS, 32
    callers on one chip. Of the end-to-end metrics it reports TPOT and
    set-up; per layer what the axk1 cell reports of TPOT's movers but ITS
    two readers, and the five new ones, which no other cell reports. One
    cell on four chips, as before; every entry that was there stands."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [w["name"] for w in bench["workloads"]]
    assert CELL in names and names[:7] == [
        "m7b-chat", "m7b-decode", "ms24b-tp4-chat", "ouro-decode",
        "qn80-ep8-decode", "trl-ep8-longshort-decode",
        "axk1-ep16-longdoc-decode"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    new = spec.load_cell(CELL)
    old = spec.load_cell("axk1-ep16-longdoc-decode")
    assert new.chips == 1 and new.config_name == "dots3-note-ep16"
    assert new.traffic == old.traffic
    assert new.drive["clients"] == new.max_slots == 32
    assert new.drive["limits"] == {"ttft_ms": 3000, "tpot_ms": 80}
    assert new.drive["ramp_s"] == 5.0 and "drain_s" not in new.drive
    assert {m["name"] for m in new.end_to_end} == {"tpot_ms_p90", "setup_s"}
    theirs = {"mla.decode_roofline", "mla.chunk_attend_share"}
    assert {m["name"] for m in new.per_layer} == (
        {m["name"] for m in old.per_layer} - theirs) | set(NEW) | {JOINED}
    sources = {"dsa.chunk_attend_share": ("device_trace", "model", "lower"),
               "dsa.select_share": ("device_trace", "model", "lower")}
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p90"
            assert m["unit"] == "%"
            assert (m["source"], m["layer"], m["better"]) == sources.get(
                m["name"], ("device_trace", "kernels", "higher"))
        if m["name"] == JOINED:
            assert m["workloads"] == ["qn80-ep8-decode", CELL]
    for name in names:
        if name != CELL:
            assert not set(NEW) & {m["name"]
                                   for m in spec.load_cell(name).per_layer}


def flight_row(ts, steps, live=32, program="decode", dsa=True):
    """A launch of ``live`` streams at 33 400 tokens of context."""
    row = {"ts_unix": ts, "program": program, "steps": steps,
           "compile": False, "tokens": live * steps, "live_slots": live}
    if program.startswith("decode"):
        row["attended_tokens"] = steps * live * 33_400
        row["experts_touched"] = steps * 51     # ~10.2 of 16 x 5 blocks
        if dsa:
            row["selected_tokens"] = steps * live * 2048
            row["window_tokens"] = steps * live * 513
    return row


@pytest.mark.parametrize("with_scopes", [True, False])
def test_the_five_readers_read_the_ring_and_the_scopes(with_scopes):
    """Each reader's need over its scope's seconds in the decode programs,
    against the chip's peaks (all bytes-bound), and the two that are shares
    of the slice's busy seconds: ``dsa.select_share`` (the decode programs'
    ``attn.select``) and ``dsa.chunk_attend_share`` (the prefill programs'
    ``attn.latent_chunk``, whatever is staged inside it). Against a program
    whose trace names no such scopes and whose ring has no such columns (the
    parent, every other configuration) all five return None and raise
    nothing. The accepted ``moe.expert_bw_share`` prices this family's
    touched experts too."""
    from harness.peaks import PEAKS

    fam = family()
    rows = [flight_row(10.0 + i, 1, dsa=with_scopes) for i in range(10)]
    rows += [flight_row(12.5, 2, program="decode_n", dsa=with_scopes),
             flight_row(13.5, 0, program="prefill_chunk")]
    at = "decode/layers/"
    scopes = (("attn.index", "attn.select", "attn.sparse_decode",
               "attn.latent_window") if with_scopes else
              ("attn.latent_decode",) * 4)
    cell = types.SimpleNamespace(
        family=fam, published=HF, chips=1, config=CONFIG)
    ctx = {
        "anchor": (0.0, 0.0), "cell": cell, "peak": PEAKS["TPU v5 lite"],
        "window": types.SimpleNamespace(t_open=9.0, t_close=30.0),
        "traced": {"flight": rows},
        "trace": {"start_unix": 10.0, "window_at_s": (0.0, 4.5),
                  "busy_s": 4.0, "op_rows": [
            ("jit__decode_paged_fn", at + scopes[0], "fusion.1", 0.02),
            ("jit__decode_paged_n_fn", at + scopes[0] + "/kv_pool.gather",
             "gather.1", 0.01),
            ("jit__decode_paged_fn", at + scopes[1] + "/while/body",
             "fusion.2", 0.016),
            ("jit__decode_paged_fn", at + scopes[2], "fusion.3", 0.008),
            ("jit__decode_paged_fn", at + scopes[3], "fusion.4", 0.004),
            ("jit__decode_paged_fn", at + "moe/experts", "moe_experts.1",
             0.5),
            ("jit__prefill_paged_fn", "prefill/layers/" + (
                "attn.latent_chunk/" if with_scopes else "") + scopes[0],
             "fusion.5", 0.12),
            ("jit__prefill_paged_fn", "prefill/layers/" + scopes[3],
             "fusion.6", 0.08)]}}
    readers = {n: spec.load_reader(n) for n in NEW}
    got = {n: read(ctx) for n, read in readers.items()}
    if not with_scopes:
        assert got == dict.fromkeys(NEW)
        return
    # the slice [10, 14.5) holds rows 10 .. 14 and the two-step row: 7 steps
    # of 32 streams
    steps = 7 * 32
    index = steps * (33_400 * 3 * 256 + 3 * 64 * (256 + 4))
    assert got["dsa.index_bw_share"] == pytest.approx(
        100 * (index / 819e9) / 0.03)
    chosen = 3 * steps * (2048 * 1152 + 2 * 128 * 320)
    assert chosen / 819e9 > fam.select_flops(HF, steps * 2048) / 197e12
    assert got["dsa.sparse_attend_roofline"] == pytest.approx(
        100 * (chosen / 819e9) / 0.008)
    window = 3 * steps * 513 * 2176
    assert got["mla.window_roofline"] == pytest.approx(
        100 * (window / 819e9) / 0.004)
    assert got["dsa.select_share"] == pytest.approx(100 * 0.016 / 4.0)
    # the PREFILL programs' rows under the chunk's scope, the index scoring
    # inside it included; the decode programs' ``attn.index`` is not
    assert got["dsa.chunk_attend_share"] == pytest.approx(100 * 0.12 / 4.0)
    assert all(0 < v < 100 for v in got.values()), got
    # 7 steps x 51 touched experts of three matrices each, over moe/experts
    assert spec.load_reader(JOINED)(ctx) == pytest.approx(
        100 * (7 * 51 * 3 * 5120 * 1536 * 2 / 819e9) / 0.5)
    # with no trace at all (--trace 0 never asks; a voided slice does)
    for name in NEW:
        assert readers[name]({**ctx, "trace": None}) is None


# a model of the family at the test's size, served in float32: F(dense) F S S
# S F, 4 of 8 experts held (rank 1 of 2), top-3 under a selection bias;
# index_topk 48 and a window of 33, so that the probes (16 to ~500 tokens)
# cross both
SMALL = {
    "model_type": "dots3_note", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 6, "layer_types": [F, F] + [S, S, S, F] * 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "q_lora_rank": 48,
    "kv_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
    "v_head_dim": 32, "rope_theta": 80000000,
    "swa_num_attention_heads": 2, "swa_num_key_value_heads": 2,
    "swa_q_lora_rank": 48, "swa_kv_lora_rank": 96,
    "swa_qk_nope_head_dim": 48, "swa_qk_rope_head_dim": 16,
    "swa_v_head_dim": 32, "swa_rope_theta": 50000,
    "sliding_window_size": 33, "index_n_heads": 8, "index_head_dim": 32,
    "index_topk": 48, "attention_gate_type": "headwise",
    "swa_attention_gate_type": "headwise",
    "apply_mla_qkv_lora_rescale": True, "rms_norm_eps": 1e-5,
    "rope_scaling": None, "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "n_routed_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 64, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "expert_parallel": {"size": 2, "rank": 1},
    "engine": {"max_slots": 4, "attn_impl": "xla", "prefill_chunk": 64,
               "spec": False, "decode_steps_per_dispatch": 2,
               "dtype": "float32", "kv_dtype": "float32"},
    "reference": {"epsilon": 0.006, "why": "a test"}}


def test_a_sparse_attention_model_runs_by_files_alone(bench_copy, cpu_peaks,
                                                      capsys):
    """A small model of the family, served by the program's normal path (the
    scheduler, chunked prefill under the marks, select-then-attend decode
    over the three arrays) from its published keys, judged by its family:
    new files, none edited, ``correct``, and the parameter count is the
    family's count of the HELD share."""
    path = add_architecture(bench_copy, "tiny-d3", "dots3_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-d3", 5)
    assert out["correct"] is True and out["failed"] == 0
    assert check["ok"] is True and check["positions"] == 64
    written = json.loads(path.read_text())
    hf = {k: v for k, v in written.items() if k not in spec.CONFIG_KEYS}
    assert check["params_served"] == check["params_described"] == (
        family().param_count(hf))
    assert check["max_shortfall"] < check["epsilon"] / 3


def test_the_control_fails_a_family_whose_full_layers_attend_every_row(
        bench_copy, cpu_peaks, capsys):
    """THE FAILING CONTROL: the same server judged by the family with the
    selection left out (a copy of the family file whose ``selection`` allows
    every causal row): the weights are the same, so the count agrees; the
    tokens are another model's, so the run is not ``correct``."""
    src = (bench_copy / "benchmark" / "reference"
           / "dots3_family.py").read_text()
    line = "    if topk >= t:\n"
    assert src.count(line) == 1
    (bench_copy / "benchmark" / "reference"
     / "no_selection_family.py").write_text(src.replace(line,
                                                        "    if True:\n"))
    add_architecture(bench_copy, "tiny-d3", "no_selection_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-d3", 5)
    assert out["failed"] == 0 and out["correct"] is False
    assert check["params_served"] == check["params_described"]
    assert check["max_shortfall"] > 3 * check["epsilon"], check
