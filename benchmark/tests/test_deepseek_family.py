"""The latent-attention decoder's family (``reference/deepseek_family.py``),
its configuration and its cell, added by files alone (PR 48): the hand
arithmetic of the published keys at the cut the file states, the catalog's
numbers in the file, the served pytree's shapes as the program builds them (a
dense prefix beside the expert layers), what the new cell reports, the two new
readers; and, end to end on the CPU, a small model of the family served by the
program and judged ``correct`` by its family, with the control that fails: the
same server judged by a family whose router knows no groups."""

import json
import types

import pytest

import test_walk as tw
from conftest import ROOT, add_architecture
from harness import spec

CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "axk1-ep16.json").read_text())
HF = {k: v for k, v in CONFIG.items() if k not in spec.CONFIG_KEYS}
CELL = "axk1-ep16-longdoc-decode"
MiB, GiB = 2 ** 20, 2 ** 30


def family(root=ROOT, name="deepseek_family"):
    return spec.load_family(spec.family_file(
        {"reference": {"family": name}}, "a test", root))


def test_the_hand_arithmetic_of_the_latent_stacks_published_keys():
    """ISSUE 48's numbers, from the configuration file as committed."""
    fam = family()
    n = fam.dims(HF)
    assert (n["L"], n["nd"], n["H"], n["E"], n["size"], n["groups"],
            n["kept"], n["topk"]) == (7, 1, 64, 12, 16, 8, 4, 8)
    assert fam.cache_layers(HF) == 7 and fam.latent_width(HF) == 576
    # q down and up, the latent's down (with the rope key) and up, o
    parts = (7168 * 1536, 1536 * 64 * 192, 7168 * 576, 512 * 64 * 256,
             64 * 128 * 7168)
    assert parts == (11_010_048, 18_874_368, 4_128_768, 8_388_608,
                     58_720_256)
    assert fam.attn_params(HF) == sum(parts) == 101_122_048
    assert sum(parts) + 1536 + 512 == 101_124_096      # with Nq and Nkv
    expert = 3 * 7168 * 2048
    assert fam.expert_params(HF) == expert == 44_040_192
    dense = sum(parts) + 3 * 7168 * 18432
    assert fam.dense_params(HF) == dense
    # an expert layer outside its experts: attention, the router at its FULL
    # width 192, the shared expert
    fixed = sum(parts) + 7168 * 192 + expert
    assert fam.block_fixed_params(HF) == fixed
    vectors = 2 * 7168 + 1536 + 512         # two norms, the low-rank norms
    tables = 2 * 20480 * 7168 + 7168
    assert (dense + vectors, fixed + vectors + 12 * expert, tables) == (
        497_500_160, 675_037_184, 293_608_448)
    assert fam.param_count(HF) == 497_500_160 + 6 * 675_037_184 + tables
    assert fam.param_count(HF) == 4_841_331_712
    assert round(fam.param_count(HF) * 2 / GiB, 2) == 9.02
    assert fam.layer_params(HF) == (dense + 6 * (fixed + 12 * expert)) / 7
    # a token's forward multiplies 8 / 16 of an expert a layer here
    stack = dense + 6 * fixed
    assert fam.token_params(HF) == stack + 6 * 0.5 * expert
    # a step of 32 tokens is EXPECTED to touch 8.9 of the 12 held a layer
    touched = 12 * (1 - (1 - 8 / 192) ** 32)
    assert fam.experts_touched(HF, 32) == pytest.approx(touched)
    assert 8.9 < touched < 8.95
    head = 7168 * 20480
    assert fam.step_params(HF, 32) == pytest.approx(
        stack + 6 * touched * expert + head)
    # ~3.87 B weights a step: 7.7 GB in bfloat16, 9.4 ms at 819 GB/s
    assert abs(fam.step_params(HF, 32) * 2 / 819e9 - 0.00944) < 1e-4
    # the cache: 576 elements a token a layer, 1152 B in bfloat16, whatever
    # lanes a pool pads them to; a block of 64 tokens over 7 layers 504 KiB
    assert fam.kv_bytes_per_token(HF, 2.0) == 7 * 1152
    assert 64 * fam.kv_bytes_per_token(HF, 2.0) == 504 * 1024
    # q and o in the PUBLISHED form: 192 and 128 a head, their mean counted
    # twice by the harness; the flops 2 x (192 + 128) a head a pair
    assert fam.q_elements_per_token(HF) == 7 * 64 * 160
    assert fam.attn_flops(HF, 10) == 2.0 * 7 * 64 * 320 * 10
    # the absorbed form's count is the larger: never the need
    assert 2.0 * 7 * 64 * (576 + 512) * 10 > fam.attn_flops(HF, 10)
    assert fam.expert_bytes(HF, 9) == 9 * expert * 2
    # the decode step of the cell, by its needs: 32 streams at ~33.4 k rows
    rows = 32 * 33_400
    assert abs(rows * 1152 / 819e9 - 1.503e-3) < 1e-5       # a layer's rows
    assert abs(fam.attn_flops(HF, rows) / 7 / 197e12 - 0.222e-3) < 1e-5
    # the softmax scale: 192^-1/2 x (0.1 ln 32 + 1)^2
    assert fam.softmax_scale(HF) == pytest.approx(192 ** -0.5 * 1.34657 ** 2,
                                                  rel=1e-5)
    # the file: the cut, the share, the engine's sizes
    assert CONFIG["reference"]["family"] == "deepseek_family"
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size",
        "max_position_embeddings"}
    assert CONFIG["expert_parallel"] == {"size": 16, "rank": 0}
    assert {"topk_method", "rope pairs", "weights"} <= set(CONFIG["assumed"])
    eng = CONFIG["engine"]
    assert (eng["max_slots"], eng["kv_num_blocks"], eng["spec"],
            eng.get("quantization")) == (32, 3072, False, None)
    # the sum the ``hbm`` block states: documents, reservations, spare
    own = -(-(1 + 32768 + 16 + 1024 + 1) // 64) - 512
    assert own == 17 and 1 + 4 * 512 + 32 * 21 + 351 == 3072
    assert CONFIG["context_size"] == 34816 >= 1 + 32768 + 16 + 1024
    assert CONFIG["context_size"] % 1024 == 0


def test_every_published_number_of_the_latent_stacks_catalog_row_is_in_the_file():
    """Every key of the published config stands in the file, unchanged but
    for the ones ``reduced`` names; no width is among those."""
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "hidden_act": "silu", "hidden_size": 7168,
        "intermediate_size": 18432, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "axk1",
        "moe_intermediate_size": 2048, "moe_layer_freq": 1, "n_group": 8,
        "n_routed_experts": 192, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts_per_tok": 8, "num_hidden_layers": 61,
        "num_key_value_heads": 64, "q_lora_rank": 1536,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 32,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "seq_aux": True, "tie_word_embeddings": False, "topk_group": 4,
        "topk_method": "none", "v_head_dim": 128, "vocab_size": 163840}
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
    # the published depth is counted, though no chip here holds it: 1 dense
    # layer, 60 expert layers of the WHOLE 192 experts, the whole vocabulary
    fam = family()
    whole = {**published, "expert_parallel": None}
    assert 5.18e11 < fam.param_count(whole) < 5.20e11       # "519B"
    active = fam.token_params(whole) + 7168 * 163840
    assert 3.1e10 < active < 3.3e10     # ~32 B a token: 8 + 1 experts of 193


def test_the_served_pytree_is_a_dense_prefix_beside_the_expert_layers():
    """What harness/refcheck.py and the family's ``walk`` rest on: every
    leaf of the served ``layers`` pytree leads with the LAYER (refcheck
    indexes every leaf at one row and hands it to ``decoder_layer``), the
    dense prefix's leaves are top-level tensors the walk reads one at a
    time, all under the names the family reads; and the family's count of
    the held share is the program's, to the parameter."""
    import jax

    from harness import refcheck
    from localai_tpu.models import llama as mdl
    from localai_tpu.models.llama import LlamaConfig

    fam = family()
    cfg = LlamaConfig.from_hf(HF)
    shapes = mdl.param_shapes(cfg)
    layers = shapes["layers"]
    assert {s[0] for s in layers.values()} == {6}
    assert set(layers) == set(fam.ATTN_LEAVES) | {
        "moe_gate", "w_gate", "w_up", "w_down", "shared_gate", "shared_up",
        "shared_down"}
    assert {n for n in shapes if n.startswith("dense_")} == {
        "dense_" + n for n in fam.ATTN_LEAVES + fam.EXPERT_LEAVES}
    assert shapes["dense_w_gate"] == (1, 7168, 18432)
    assert layers["wkv_a"] == (6, 7168, 576)
    assert layers["wkv_b"] == (6, 512, 64 * 256)
    assert layers["moe_gate"] == (6, 7168, 192)          # the FULL router
    assert layers["w_gate"] == (6, 1, 12, 7168, 2048)    # the HELD experts
    abstract = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "bfloat16"), shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    assert refcheck.served_param_count(abstract) == fam.param_count(HF)


def test_the_latent_cell_reports_what_the_issue_names():
    """BENCHMARK.json as committed: ``axk1-ep16-longdoc-decode`` is the new
    configuration under the new mix, 32 callers on one chip. Of the
    end-to-end metrics it reports TPOT and set-up; per layer what
    ``trl-ep8-longshort-decode`` reports of TPOT's movers but ITS four
    readers, and the two ``mla.*`` readers, which no other cell reports.
    One cell on four chips, as before; every entry that was there stands."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = [w["name"] for w in bench["workloads"]]
    assert CELL in names and names[:6] == [
        "m7b-chat", "m7b-decode", "ms24b-tp4-chat", "ouro-decode",
        "qn80-ep8-decode", "trl-ep8-longshort-decode"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    new = spec.load_cell(CELL)
    old = spec.load_cell("trl-ep8-longshort-decode")
    assert new.chips == 1 and new.config_name == "axk1-ep16"
    decode_heavy = spec.load_cell("m7b-decode").traffic
    assert new.traffic["classes"] == decode_heavy["classes"]
    assert new.traffic["sampling"] == decode_heavy["sampling"]
    assert new.traffic["loop"] == "closed"
    assert new.traffic.get("shape_seed") is None
    assert new.traffic["prefix"] == {"share": 1.0, "pool": 4, "tokens": 32768,
                                     "fill_in_setup": True}
    assert new.drive["clients"] == new.max_slots == 32
    assert new.drive["limits"] == {"ttft_ms": 3000, "tpot_ms": 80}
    assert new.drive["ramp_s"] == 5.0 and "drain_s" not in new.drive
    assert {m["name"] for m in new.end_to_end} == {"tpot_ms_p90", "setup_s"}
    mine = {"mla.decode_roofline", "mla.chunk_attend_share"}
    theirs = {"swa.window_bw_share", "swa.full_bw_share",
              "swa.expert_bw_share", "swa.window_dead_share"}
    assert {m["name"] for m in new.per_layer} == (
        {m["name"] for m in old.per_layer} - theirs) | mine
    for m in bench["per_layer"]:
        if m["name"] in mine:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p90"
            assert m["source"] == "device_trace" and m["unit"] == "%"
    for name in names:
        if name != CELL:
            assert not mine & {m["name"]
                               for m in spec.load_cell(name).per_layer}


def flight_row(ts, steps, live=32, program="decode"):
    """A launch of ``live`` streams at 33 400 tokens of context."""
    row = {"ts_unix": ts, "program": program, "steps": steps,
           "compile": False, "tokens": live * steps, "live_slots": live}
    if program.startswith("decode"):
        row["attended_tokens"] = steps * live * 33_400
        row["experts_touched"] = steps * 50
    return row


@pytest.mark.parametrize("with_scopes", [True, False])
def test_the_mla_readers_read_the_ring_and_the_scopes(with_scopes):
    """``mla.decode_roofline``: the latent rows the slice's decode rows
    attended x 7 layers x 1152 B, q and o of their query tokens, and the
    published form's flops of the same pairs, over the decode programs'
    device seconds under ``attn.latent_decode``, against the chip's peaks
    (bytes-bound: 1152 B a row against 2 x 64 x 320 flops).
    ``mla.chunk_attend_share``: the prefill programs' seconds under
    ``attn.latent_chunk`` over the slice's busy seconds. Against a program
    whose trace names no such scopes (the parent, every other
    configuration) both return None and raise nothing."""
    from harness.peaks import PEAKS

    fam = family()
    rows = [flight_row(10.0 + i, 1) for i in range(10)]
    rows += [flight_row(12.5, 2, program="decode_n"),
             flight_row(13.5, 0, program="prefill_chunk")]
    attn = "decode/layers/" + ("attn.latent_decode/latent_decode_attn"
                               if with_scopes else "attn.paged_decode")
    chunk = "prefill/layers/" + ("attn.latent_chunk" if with_scopes
                                 else "attn.prefill")
    cell = types.SimpleNamespace(
        family=fam, published=HF, chips=1, config=CONFIG)
    ctx = {
        "anchor": (0.0, 0.0), "cell": cell, "peak": PEAKS["TPU v5 lite"],
        "window": types.SimpleNamespace(t_open=9.0, t_close=30.0),
        "traced": {"flight": rows},
        "trace": {"start_unix": 10.0, "window_at_s": (0.0, 4.5),
                  "busy_s": 4.0, "op_rows": [
            ("jit__decode_paged_fn", attn, "latent_decode_attn.1", 0.08),
            ("jit__decode_paged_n_fn", attn, "latent_decode_attn.1", 0.04),
            ("jit__decode_paged_fn", "decode/layers/moe/experts",
             "moe_experts.1", 0.5),
            ("jit__prefill_paged_fn", chunk + "/mla/kv_b", "fusion.5", 0.12),
            ("jit__prefill_paged_fn", chunk, "fusion.6", 0.08),
            ("jit__prefill_paged_fn", "prefill/layers/mla/q", "fusion.7",
             0.5)]}}
    names = ("mla.decode_roofline", "mla.chunk_attend_share")
    readers = {n: spec.load_reader(n) for n in names}
    got = {n: read(ctx) for n, read in readers.items()}
    if not with_scopes:
        assert got == dict.fromkeys(names)
        return
    # the slice [10, 14.5) holds rows 10 .. 14 and the two-step row: 7 steps
    # of 32 streams; bytes-bound
    need = (7 * 32 * 33_400 * 7 * 1152 + 7 * 32 * 2 * 2.0 * 7 * 64 * 160)
    assert need / 819e9 > fam.attn_flops(HF, 7 * 32 * 33_400) / 197e12
    assert got["mla.decode_roofline"] == pytest.approx(
        100 * (need / 819e9) / 0.12)
    assert 0 < got["mla.decode_roofline"] < 100
    assert got["mla.chunk_attend_share"] == pytest.approx(100 * 0.2 / 4.0)
    # with no trace at all (--trace 0 never asks; a voided slice does)
    for name in names:
        assert readers[name]({**ctx, "trace": None}) is None


# a model of the family at the test's size, served in float32: a dense layer
# and two expert layers, 4 of 8 experts held (rank 1 of 2) in 4 groups of
# which 2 are kept, top-3; YaRN factor 4 over 16 positions, so the probes (16
# to ~500 tokens) lie past the original length and m^2 = 1.30 is on every score
SMALL = {
    "model_type": "axk1", "hidden_size": 128, "intermediate_size": 256,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 64,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
    "rope_theta": 10000, "rms_norm_eps": 1e-6,
    "rope_scaling": {"type": "yarn", "factor": 4, "mscale": 1,
                     "mscale_all_dim": 1, "beta_fast": 32, "beta_slow": 1,
                     "original_max_position_embeddings": 16},
    "first_k_dense_replace": 1, "moe_layer_freq": 1, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 64,
    "n_shared_experts": 1, "n_group": 4, "topk_group": 2,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "none",
    "expert_parallel": {"size": 2, "rank": 1},
    "engine": {"max_slots": 4, "attn_impl": "xla", "prefill_chunk": 64,
               "spec": False, "decode_steps_per_dispatch": 2,
               "dtype": "float32", "kv_dtype": "float32"},
    "reference": {"epsilon": 0.006, "why": "a test"}}


def test_a_latent_attention_model_runs_by_files_alone(bench_copy, cpu_peaks,
                                                      capsys):
    """A small model of the family, served by the program's normal path (the
    scheduler, chunked prefill decompressing the span it has, absorbed decode
    over the latent pool) from its published keys, judged by its family: new
    files, none edited, ``correct``, and the parameter count is the family's
    count of the HELD share."""
    add_architecture(bench_copy, "tiny-k1", "deepseek_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-k1", 5)
    assert out["correct"] is True and out["failed"] == 0
    assert check["ok"] is True and check["positions"] == 64
    attn = (128 * 48 + 48 * 4 * 48 + 128 * 80 + 64 * 4 * 64 + 4 * 32 * 128
            + 2 * 128 + 48 + 64)
    dense = attn + 3 * 128 * 256
    block = attn + 128 * 8 + 3 * 128 * 64 + 4 * 3 * 128 * 64
    assert check["params_served"] == check["params_described"] == (
        dense + 2 * block + 2 * 512 * 128 + 128)
    assert check["max_shortfall"] < check["epsilon"] / 3


def test_the_control_fails_a_family_whose_router_knows_no_groups(
        bench_copy, cpu_peaks, capsys):
    """THE FAILING CONTROL: the same server judged by the family with the
    group limit left out of its selection (a copy of the family file with
    that one line changed: the OTHER reading of ``topk_method: "none"``):
    the weights are the same, so the count agrees; the tokens are another
    model's, so the run is not ``correct``."""
    src = (bench_copy / "benchmark" / "reference"
           / "deepseek_family.py").read_text()
    line = '    if n["groups"] > 1:\n'
    assert src.count(line) == 1
    (bench_copy / "benchmark" / "reference"
     / "no_groups_family.py").write_text(src.replace(line, "    if False:\n"))
    add_architecture(bench_copy, "tiny-k1", "no_groups_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-k1", 5)
    assert out["failed"] == 0 and out["correct"] is False
    assert check["params_served"] == check["params_described"]
    assert check["max_shortfall"] > 3 * check["epsilon"], check
