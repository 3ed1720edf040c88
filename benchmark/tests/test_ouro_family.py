"""The looped decoder's family (``reference/ouro_family.py``), its
configuration and its cell, added by files alone (PR 37): the hand arithmetic
of the published keys, the walk against the forward composed by hand, what
the new cell reports, the two new readers; and, end to end on the CPU, a
small looped model served by the program and judged ``correct`` by its
family, with the control that fails: the family that runs a plain stack
twice, against the same server."""

import json
import types

import numpy as np
import pytest

import test_walk as tw
from conftest import ROOT, add_architecture
from harness import refcheck, spec

CONFIG = json.loads(
    (ROOT / "benchmark" / "configs" / "ouro-2.6b-int8.json").read_text())
HF = {k: v for k, v in CONFIG.items() if k not in spec.CONFIG_KEYS}
# a looped model at the test's size: 3 layers run 3 times a token
SMALL = {**tw.HF, "model_type": "ouro", "total_ut_steps": 3,
         "num_key_value_heads": 4, "rms_norm_eps": 1e-6}


def ouro(root=ROOT):
    return spec.load_family(spec.family_file(
        {"reference": {"family": "ouro_family"}}, "a test", root))


def test_the_hand_arithmetic_of_the_published_keys():
    """ISSUE 37's numbers, from the configuration file as committed."""
    fam = ouro()
    assert fam.passes(HF) == 4 and fam.cache_layers(HF) == 192
    layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert fam.layer_params(HF) == layer == 51_380_224
    # a layer with its four gains, 48 of them; two tables and the final norm
    assert 48 * (layer + 4 * 2048) == 2_466_643_968
    assert 2 * 49152 * 2048 + 2048 == 201_328_640
    assert fam.param_count(HF) == 2_667_972_608      # without the gate's 2049
    # a token multiplies, and a decode step reads, the stack once a PASS
    assert fam.token_params(HF) == 4 * 48 * layer == 9_865_003_008
    head = 2048 * 49152
    assert fam.step_params(HF, 8) == fam.step_params(HF, 1) == (
        fam.token_params(HF) + head) == 9_965_666_304
    # 12.2 ms a step at 819 GB/s in int8, whatever the batch
    assert abs(fam.step_params(HF, 8) / 819e9 - 0.01217) < 1e-4
    # K and V of 192 cache layers x 16 kv heads x 128: 1.5 MiB in bfloat16
    assert fam.kv_bytes_per_token(HF, 2.0) == 1_572_864 == 1.5 * 2**20
    assert fam.q_elements_per_token(HF) == 192 * 16 * 128
    assert fam.attn_flops(HF, 10) == 4.0 * 192 * 16 * 128 * 10
    # the file: nothing cut but the served context; the engine's sizes
    assert CONFIG["reference"]["family"] == "ouro_family"
    assert set(CONFIG["reduced"]) == {"max_position_embeddings"}
    assert CONFIG["max_position_embeddings"] == 65536
    eng = CONFIG["engine"]
    assert (eng["max_slots"], eng["kv_num_blocks"], eng["quantization"],
            eng["spec"]) == (8, 101, "int8", False)
    # 100 usable blocks of 64 tokens at 1.5 MiB a token: 9.375 GiB
    assert 100 * 64 * fam.kv_bytes_per_token(HF, 2.0) == 9.375 * 2**30


def test_the_walk_is_every_pass_in_order_with_the_norm_between():
    """Three passes over three rows, the final norm between two passes (and
    in ``logits`` after the last): what the harness runs is what the walk
    asks for, equals the forward composed by hand from the family's own
    layer, and is another model than two passes, or than no norm between."""
    fam = ouro()
    params = tw.served_params(SMALL)
    assert set(params["layers"]) >= {"attn_post_norm", "mlp_post_norm"}
    calls: list = []
    got = refcheck.reference_logits(params, tw.recording(fam, calls), SMALL,
                                    tw.TOKENS, tw.LAST)
    once = [0, 1, 2]
    assert calls == once * 3
    by_hand = tw.by_hand(fam, params, SMALL,
                         once + ["norm"] + once + ["norm"] + once)
    np.testing.assert_allclose(got, by_hand, rtol=1e-4, atol=1e-5)
    two = refcheck.reference_logits(
        params, fam, {**SMALL, "total_ut_steps": 2}, tw.TOKENS, tw.LAST)
    assert np.abs(got - two).max() > 1e-3
    unnormed = types.SimpleNamespace(**{
        **vars(fam), "walk": lambda x, layer, rows, leaf, hf:
        refcheck.in_order(refcheck.in_order(refcheck.in_order(
            x, layer, rows, leaf, hf), layer, rows, leaf, hf),
            layer, rows, leaf, hf)})
    assert np.abs(got - refcheck.reference_logits(
        params, unnormed, SMALL, tw.TOKENS, tw.LAST)).max() > 1e-3


def test_the_new_cell_reports_what_the_issue_names():
    """BENCHMARK.json as committed: ``ouro-decode`` is ``m7b-decode``'s mix
    on the looped configuration. Of its end-to-end metrics it reports TPOT
    alone (its 25 replies a window make ``out_tok_s`` follow the seed, and
    its p98 gap lies outside the admissions' cluster: PERF.md section 2);
    per layer what ``m7b-decode`` reports of TPOT's movers, the gap tail
    as ``sched.stall_ms_p98``, and the two loop readers."""
    new, old = spec.load_cell("ouro-decode"), spec.load_cell("m7b-decode")
    assert new.chips == 1 and new.config_name == "ouro-2.6b-int8"
    assert new.traffic == old.traffic
    assert new.drive["clients"] == new.max_slots == 8
    assert new.drive["limits"] == old.drive["limits"]
    assert {m["name"] for m in new.end_to_end} == {"tpot_ms_p90", "setup_s"}
    loop = {"model.loop_pass_ms", "model.loop_passes_mean"}
    assert {m["name"] for m in new.per_layer} == {
        m["name"] for m in old.per_layer
        if m["moves"] != "stall_ms_p98"} | loop | {"sched.stall_ms_p98"}
    assert {"model.decode_bw_share", "paged_decode_attn_roofline",
            "runner.kv_move_share"} <= {m["name"] for m in new.per_layer}
    # the pool's shape the write reader looks for: 192 cache layers
    dims = spec.load_reader("runner.kv_move_share").__globals__["pool_dims"]
    assert tuple(sorted((192, 101, 16, 64, 128))) in dims(new)
    for name in ("m7b-chat", "m7b-decode", "ms24b-tp4-chat"):
        assert not loop & {m["name"] for m in spec.load_cell(name).per_layer}


def flight_row(ts, steps, passes=None, program="decode"):
    row = {"ts_unix": ts, "program": program, "steps": steps,
           "compile": False, "tokens": 8 * steps}
    if passes is not None:
        row["passes"] = passes
    return row


@pytest.mark.parametrize("with_column", [True, False])
def test_the_loop_readers_read_the_ring_and_the_scope(with_column):
    """``model.loop_passes_mean``: passes over steps of the window's decode
    rows; ``model.loop_pass_ms``: device seconds under ``loop.pass`` of the
    decode programs over the slice's passes. Against a program whose ring has
    no ``passes`` column and whose trace no such scope (the parent), both
    return None and raise nothing."""
    rows = [flight_row(10.0 + i, 1, 4 if with_column else None)
            for i in range(10)]
    rows += [flight_row(12.5, 2, 8 if with_column else None, "decode_n"),
             flight_row(13.5, 0, 4 if with_column else None,
                        "prefill_chunk")]
    scope = "decode/loop.pass/layers/" if with_column else "decode/layers/"
    ctx = {
        "anchor": (0.0, 0.0),
        "window": types.SimpleNamespace(t_open=9.0, t_close=30.0),
        "traced": {"flight": rows},
        "trace": {"start_unix": 10.0, "window_at_s": (0.0, 4.5), "op_rows": [
            ("jit__decode_paged_fn", scope + "mlp", "fusion.1", 0.06),
            ("jit__decode_paged_fn", scope + "attn.paged_decode",
             "paged_decode_attn.2", 0.03),
            ("jit__decode_paged_fn", "decode/loop.norm", "fusion.3", 0.5),
            ("jit__decode_paged_fn", "decode/lm_head", "fusion.4", 0.5),
            ("jit__prefill_paged_fn", "prefill/loop.pass/layers/mlp",
             "fusion.5", 0.5)]}}
    mean = spec.load_reader("model.loop_passes_mean")(ctx)
    per_pass = spec.load_reader("model.loop_pass_ms")(ctx)
    if not with_column:
        assert mean is None and per_pass is None
        return
    assert mean == (10 * 4 + 8) / (10 + 2) == 4.0
    # the slice [10, 14.5) holds rows 10 .. 14 and the two-step row: 7 steps
    assert per_pass == pytest.approx(1e3 * 0.09 / 28)
    # and with no trace at all (--trace 0 never asks; a voided slice does)
    assert spec.load_reader("model.loop_pass_ms")({**ctx, "trace": None}
                                                  ) is None


# a looped model wide and deep enough that a pass moves the logits, served in
# float32 (see test_walk.WIDE: 8 query heads over the tiny file's 2 kv heads)
LOOPED = {**tw.WIDE, "model_type": "ouro", "total_ut_steps": 3,
          "num_hidden_layers": 4, "rms_norm_eps": 1e-6}


def test_a_looped_model_runs_by_files_alone(bench_copy, cpu_peaks, capsys):
    """A small looped decoder, served by the program's normal path (the
    scheduler, the paged pool with passes x layers cache layers) from its
    published keys, judged by ``ouro_family``: new files, none edited,
    ``correct``, and the parameter count is the family's."""
    add_architecture(bench_copy, "tiny-ouro", "ouro_family", **LOOPED)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-ouro", 5)
    assert out["correct"] is True and out["failed"] == 0
    assert check["ok"] is True and check["positions"] == 64
    # 4 layers of 512 x 512 x (1 + 1/4 + 1/4 + 1) (2 kv heads of 8) + 3 x 512
    # x 1024 and four gains, the two tables and the final norm
    assert check["params_served"] == check["params_described"] == (
        4 * (512 * 512 * 5 // 2 + 3 * 512 * 1024 + 4 * 512)
        + 2 * 512 * 512 + 512) == 9445888
    assert check["max_shortfall"] < check["epsilon"] / 3


def test_the_control_fails_another_familys_walk(bench_copy, cpu_peaks,
                                                capsys):
    """THE FAILING CONTROL: the same looped server judged by
    ``twice_family`` (a plain two-norm stack run twice): another model's
    mathematics and another parameter count, so the run is not ``correct``."""
    add_architecture(bench_copy, "tiny-ouro", "twice_family", **LOOPED)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-ouro", 5)
    assert out["failed"] == 0 and out["correct"] is False
    assert check["params_served"] != check["params_described"]
    assert check["max_shortfall"] > 3 * check["epsilon"], check
