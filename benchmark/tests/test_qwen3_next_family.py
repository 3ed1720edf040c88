"""The sparse hybrid decoder's family (``reference/qwen3_next_family.py``),
its configuration and its cell, added by files alone (PR 41): the hand
arithmetic of the published keys at the cut the file states, the period's
leaf shapes as the program serves them, what the new cell reports, the three
new readers; and, end to end on the CPU, a small model of the family served
by the program and judged ``correct`` by its family, with the control that
fails: the same server judged by a family that leaves the decay out."""

import json
import types

import numpy as np
import pytest

import test_walk as tw
from conftest import ROOT, add_architecture
from harness import spec

CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "qwen3-next-80b-a3b-ep8.json").read_text())
HF = {k: v for k, v in CONFIG.items() if k not in spec.CONFIG_KEYS}
CELL = "qn80-ep8-decode"
MiB = 2 ** 20


def family(root=ROOT, name="qwen3_next_family"):
    return spec.load_family(spec.family_file(
        {"reference": {"family": name}}, "a test", root))


def test_the_hand_arithmetic_of_the_published_keys():
    """ISSUE 41's numbers, from the configuration file as committed."""
    fam = family()
    assert fam.cache_layers(HF) == 3 and fam.dims(HF)["rot"] == 64
    expert = 3 * 2048 * 512
    assert fam.expert_params(HF) == expert == 3_145_728
    # a DeltaNet mixer: in_proj_qkvz, in_proj_ba, the conv's taps, out_proj
    gdn = 2048 * 12288 + 2048 * 64 + 4 * 8192 + 4096 * 2048
    assert fam.gdn_params(HF) == gdn == 33_718_272
    # the gated attention: q with its gate, k, v, o
    attn = 2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    assert fam.attn_params(HF) == attn == 27_262_976
    # an expert block outside its experts: the router at its FULL width 512,
    # the shared expert and its gate
    fixed = 2048 * 512 + expert + 2048
    assert fam.block_fixed_params(HF) == fixed == 4_196_352
    period = (3 * (gdn + 2 * 32 + 128) + attn + 2 * 256
              + 4 * (2 * 2048 + fixed + 64 * expert))
    tables = 2 * 18992 * 2048 + 2048
    assert fam.param_count(HF) == 3 * period + tables == 2_929_374_400
    assert fam.layer_params(HF) == (3 * gdn + attn) / 4 + fixed + 64 * expert
    # a token's forward multiplies 10 / 8 experts a block here
    stack = 3 * (3 * gdn + attn + 4 * fixed)
    assert fam.token_params(HF) == stack + 12 * 1.25 * expert
    # a step of 32 tokens is EXPECTED to touch 30.0 of the 64 held a block
    touched = 64 * (1 - (1 - 10 / 512) ** 32)
    assert fam.experts_touched(HF, 32) == pytest.approx(touched)
    assert 29.9 < touched < 30.1
    head = 2048 * 18992
    assert fam.step_params(HF, 32) == pytest.approx(
        stack + 12 * touched * expert + head)
    # ~1.60 B weights a step: 3.2 GB in bfloat16, 3.9 ms at 819 GB/s
    assert abs(fam.step_params(HF, 32) * 2 / 819e9 - 0.0039) < 1e-4
    # one token alone touches 10 / 8 of them
    assert fam.experts_touched(HF, 1) == pytest.approx(1.25)
    # K and V of 3 cache layers x 2 kv heads x 256: 6 KiB in bfloat16
    assert fam.kv_bytes_per_token(HF, 2.0) == 6144
    assert fam.q_elements_per_token(HF) == 3 * 16 * 256
    assert fam.attn_flops(HF, 10) == 4.0 * 3 * 16 * 256 * 10
    # what the two new shares count: an expert's three matrices in bfloat16;
    # a slot's state a step, 9 layers x (S read and written in float32, the
    # conv rows read and written in bfloat16)
    assert fam.expert_bytes(HF, 30) == 30 * expert * 2
    assert fam.state_bytes(HF, 32) == 32 * 9 * (
        2 * 32 * 128 * 128 * 4 + 2 * 3 * 8192 * 2) == 32 * 9 * (
            4 * MiB + 96 * 1024)
    # the file: the cut, the share, the engine's sizes
    assert CONFIG["reference"]["family"] == "qwen3_next_family"
    assert set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size",
        "max_position_embeddings"}
    assert CONFIG["expert_parallel"] == {"size": 8, "rank": 0}
    assert (CONFIG["num_hidden_layers"], CONFIG["num_experts"],
            CONFIG["vocab_size"] * 8, CONFIG["max_position_embeddings"]) == (
                12, 64, 151936, 262144)
    eng = CONFIG["engine"]
    assert (eng["max_slots"], eng["kv_num_blocks"], eng["spec"],
            eng.get("quantization")) == (32, 641, False, None)
    # 640 usable blocks of 64 tokens at 6 KiB a token: 240 MiB; 32 slots of
    # 9 layers x (2 MiB + 48 KiB): 590 MiB
    assert 640 * 64 * fam.kv_bytes_per_token(HF, 2.0) == 240 * MiB
    assert fam.state_bytes(HF, 32) / 2 == 9 * 32 * (2 * MiB + 48 * 1024)


def test_every_published_number_of_the_catalog_is_in_the_file():
    """Every key of the published config stands in the file, unchanged but
    for the ones ``reduced`` names; no width is among those."""
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4,
        "head_dim": 256, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5120, "linear_conv_kernel_dim": 4,
        "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [],
        "model_type": "qwen3_next", "moe_intermediate_size": 512,
        "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts": 512, "num_experts_per_tok": 10,
        "num_hidden_layers": 48, "num_key_value_heads": 2,
        "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
        "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "use_sliding_window": False,
        "vocab_size": 151936}
    changed = {k for k, v in published.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "num_experts", "vocab_size"}
    assert changed < set(CONFIG["reduced"])
    entry = next(c for c in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["configs"]
        if c["name"] == CONFIG["name"])
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"]


def test_the_served_stack_is_a_stack_of_periods():
    """What harness/refcheck.py rests on: every leaf of the served
    ``layers`` pytree leads with the PERIOD (it indexes every leaf at one
    row and hands the row to ``decoder_layer``), under the names the family
    reads; and the family's count of the held share is the program's."""
    import jax

    from harness import refcheck
    from localai_tpu.models import llama as mdl
    from localai_tpu.models.llama import LlamaConfig

    fam = family()
    cfg = LlamaConfig.from_hf(HF)
    shapes = mdl.param_shapes(cfg)
    layers = shapes["layers"]
    assert {s[0] for s in layers.values()} == {3}
    assert set(layers) == set(fam.GDN_LEAVES) | set(fam.MOE_LEAVES) | {
        "attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo"}
    assert layers["gdn_in_qkvz"] == (3, 3, 2048, 12288)
    assert layers["gdn_conv"] == (3, 3, 4, 8192)
    assert layers["wq"] == (3, 2048, 16 * 2 * 256)
    assert layers["moe_gate"] == (3, 4, 2048, 512)       # the FULL router
    assert layers["w_gate"] == (3, 4, 64, 2048, 512)     # the HELD experts
    assert layers["shared_router"] == (3, 4, 2048)
    abstract = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "bfloat16"), shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    assert refcheck.served_param_count(abstract) == fam.param_count(HF)


def test_the_new_cell_reports_what_the_issue_names():
    """BENCHMARK.json as committed: ``qn80-ep8-decode`` is ``m7b-decode``'s
    mix on the sparse hybrid configuration, 32 callers. Of the end-to-end
    metrics it reports TPOT and set-up; per layer what ``ouro-decode``
    reports of TPOT's movers but the two loop readers and the gap tail, and
    the three new readers, which no other cell reports."""
    new, old = spec.load_cell(CELL), spec.load_cell("m7b-decode")
    assert new.chips == 1 and new.config_name == "qwen3-next-80b-a3b-ep8"
    assert new.traffic == old.traffic
    assert new.drive["clients"] == new.max_slots == 32
    assert new.drive["limits"] == old.drive["limits"]
    assert {m["name"] for m in new.end_to_end} == {"tpot_ms_p90", "setup_s"}
    mine = {"moe.expert_bw_share", "gdn.state_bw_share",
            "moe.experts_touched_mean"}
    assert {m["name"] for m in new.per_layer} == {
        m["name"] for m in old.per_layer
        if m["moves"] != "stall_ms_p98"} | mine
    assert {"model.decode_bw_share", "paged_decode_attn_roofline",
            "runner.kv_move_share"} <= {m["name"] for m in new.per_layer}
    # the pool's shape the write reader looks for: 3 cache layers
    dims = spec.load_reader("runner.kv_move_share").__globals__["pool_dims"]
    assert tuple(sorted((3, 641, 2, 64, 256))) in dims(new)
    for name in ("m7b-chat", "m7b-decode", "ms24b-tp4-chat", "ouro-decode"):
        assert not mine & {m["name"] for m in spec.load_cell(name).per_layer}


def flight_row(ts, steps, live=32, touched=None, program="decode"):
    row = {"ts_unix": ts, "program": program, "steps": steps,
           "compile": False, "tokens": live * steps, "live_slots": live}
    if touched is not None:
        row["experts_touched"] = touched
        row["local_assignments"] = touched + 7
    return row


@pytest.mark.parametrize("with_columns", [True, False])
def test_the_new_readers_read_the_ring_and_the_scopes(with_columns):
    """``moe.experts_touched_mean``: touched over steps x blocks of the
    window's decode rows. ``moe.expert_bw_share`` / ``gdn.state_bw_share``:
    the bytes the slice's rows needed over the decode programs' device
    seconds under ``moe/experts`` / ``gdn/state``, against the HBM peak.
    Against a program whose ring has no such columns and whose trace no such
    scopes (the parent), and against a family that prices neither, all
    three return None and raise nothing."""
    from harness.peaks import PEAKS

    fam = family()
    t = 360 if with_columns else None
    rows = [flight_row(10.0 + i, 1, touched=t) for i in range(10)]
    rows += [flight_row(12.5, 2, touched=t and 2 * t, program="decode_n"),
             flight_row(13.5, 0, touched=t and 9, program="prefill_chunk")]
    mid = "decode/layers/" + ("moe/experts" if with_columns else "mlp")
    state = "decode/layers/" + ("gdn/state" if with_columns else "attn.qkv")
    cell = types.SimpleNamespace(
        family=fam, published=HF, chips=1, config=CONFIG)
    ctx = {
        "anchor": (0.0, 0.0), "cell": cell, "peak": PEAKS["TPU v5 lite"],
        "window": types.SimpleNamespace(t_open=9.0, t_close=30.0),
        "traced": {"flight": rows},
        "trace": {"start_unix": 10.0, "window_at_s": (0.0, 4.5), "op_rows": [
            ("jit__decode_paged_fn", mid, "fusion.1", 0.02),
            ("jit__decode_paged_n_fn", mid, "fusion.1", 0.01),
            ("jit__decode_paged_fn", state, "fusion.2", 0.016),
            ("jit__decode_paged_fn", "decode/layers/gdn/proj", "fusion.3",
             0.5),
            ("jit__decode_paged_fn", "decode/layers/moe/shared", "fusion.4",
             0.5),
            ("jit__prefill_paged_fn", "prefill/layers/moe/experts",
             "fusion.5", 0.5),
            ("jit__prefill_paged_fn", "prefill/layers/gdn/state",
             "fusion.6", 0.5)]}}
    readers = {n: spec.load_reader(n) for n in (
        "moe.experts_touched_mean", "moe.expert_bw_share",
        "gdn.state_bw_share")}
    got = {n: read(ctx) for n, read in readers.items()}
    if not with_columns:
        assert got == dict.fromkeys(readers)
        return
    assert got["moe.experts_touched_mean"] == (10 * 360 + 720) / (12 * 12)
    # the slice [10, 14.5) holds rows 10 .. 14 and the two-step row: 7 steps
    assert got["moe.expert_bw_share"] == pytest.approx(
        100 * (7 * 360 * 3_145_728 * 2 / 819e9) / 0.03)
    assert got["gdn.state_bw_share"] == pytest.approx(
        100 * (fam.state_bytes(HF, 7 * 32) / 819e9) / 0.016)
    assert 0 < got["moe.expert_bw_share"] < 100
    assert 0 < got["gdn.state_bw_share"] < 100
    # with no trace at all (--trace 0 never asks; a voided slice does)
    for name in ("moe.expert_bw_share", "gdn.state_bw_share"):
        assert readers[name]({**ctx, "trace": None}) is None
    # a family that prices no expert and no state: another configuration's
    dense = types.SimpleNamespace(
        family=family(name="llama_family"), published=HF, chips=1,
        config=CONFIG)
    for name in ("moe.expert_bw_share", "gdn.state_bw_share"):
        assert readers[name]({**ctx, "cell": dense}) is None


# a model of the family at the test's size, served in float32: 2 periods,
# 4 of 8 experts held (rank 1 of 2), top-3
SMALL = {
    "model_type": "qwen3_next", "hidden_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "rope_theta": 1e7, "rms_norm_eps": 1e-6, "full_attention_interval": 4,
    "linear_num_key_heads": 2, "linear_num_value_heads": 4,
    "linear_key_head_dim": 32, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "partial_rotary_factor": 0.25,
    "moe_intermediate_size": 64, "shared_expert_intermediate_size": 64,
    "num_experts": 4, "num_experts_per_tok": 3, "norm_topk_prob": True,
    "expert_parallel": {"size": 2, "rank": 1},
    "engine": {"max_slots": 4, "attn_impl": "xla", "prefill_chunk": 64,
               "spec": False, "decode_steps_per_dispatch": 2,
               "dtype": "float32"},
    "reference": {"epsilon": 0.006, "why": "a test"}}


def test_a_hybrid_model_runs_by_files_alone(bench_copy, cpu_peaks, capsys):
    """A small model of the family, served by the program's normal path (the
    scheduler, chunked prefill, the paged pool with a cache layer a period,
    per-slot state) from its published keys, judged by its family: new
    files, none edited, ``correct``, and the parameter count is the
    family's count of the HELD share."""
    add_architecture(bench_copy, "tiny-qn", "qwen3_next_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-qn", 5)
    assert out["correct"] is True and out["failed"] == 0
    assert check["ok"] is True and check["positions"] == 64
    gdn = 128 * (2 * 64 + 2 * 128) + 128 * 8 + 4 * 256 + 128 * 128 + 8 + 32
    attn = 128 * 256 + 2 * 128 * 64 + 128 * 128 + 2 * 32
    block = 2 * 128 + 128 * 8 + 3 * 128 * 64 + 128 + 4 * 3 * 128 * 64
    assert check["params_served"] == check["params_described"] == (
        2 * (3 * gdn + attn + 4 * block) + 2 * 512 * 128 + 128)
    assert check["max_shortfall"] < check["epsilon"] / 3


def test_the_control_fails_a_family_without_the_decay(bench_copy, cpu_peaks,
                                                      capsys):
    """THE FAILING CONTROL: the same server judged by the family with the
    decay ``exp(g)`` left out of its recurrence (a copy of the family file
    with that one line changed): the weights are the same, so the count
    agrees; the tokens are another model's, so the run is not ``correct``."""
    src = (bench_copy / "benchmark" / "reference"
           / "qwen3_next_family.py").read_text()
    line = "        S = jnp.exp(g_t)[:, None, None] * S\n"
    assert src.count(line) == 1
    (bench_copy / "benchmark" / "reference"
     / "no_decay_family.py").write_text(src.replace(line, ""))
    add_architecture(bench_copy, "tiny-qn", "no_decay_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-qn", 5)
    assert out["failed"] == 0 and out["correct"] is False
    assert check["params_served"] == check["params_described"]
    assert check["max_shortfall"] > 3 * check["epsilon"], check
