"""The state-space hybrid's family (``reference/falcon_h1_family.py``), its
configuration and its cell, added by files alone (PR 55): the hand arithmetic
of the published keys at the cut the file states, the catalog row in the
file, the served stack's leaf shapes, what the new cell reports, the two new
readers; and, end to end on the CPU, a small model of the family served by
the program (int8 weights) and judged ``correct`` by its family, with the
control that fails: the same server judged by a family that leaves the decay
out."""

import json
import types

import pytest

import test_walk as tw
from conftest import ROOT, add_architecture
from harness import spec

CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "falcon-h1-34b-int8.json").read_text())
HF = {k: v for k, v in CONFIG.items() if k not in spec.CONFIG_KEYS}
CELL = "fh1-34b-decode"
KiB, MiB, GiB = 2 ** 10, 2 ** 20, 2 ** 30


def family(root=ROOT, name="falcon_h1_family"):
    return spec.load_family(spec.family_file(
        {"reference": {"family": name}}, "a test", root))


def test_the_hand_arithmetic_of_the_state_space_hybrids_published_keys():
    """ISSUE 55's numbers, recounted from the configuration file as
    committed."""
    fam = family()
    n = fam.dims(HF)
    assert (n["ssm"], n["C"], n["in"]) == (4096, 5120, 9248)
    mlp = 3 * 5120 * 21504
    assert mlp == 330_301_440
    # a mixer: in_proj, the conv's taps, out_proj; its vectors: the conv's
    # bias, A_log, D, dt_bias, the gated norm's gain
    mixer = 5120 * 9248 + 4 * 5120 + 4096 * 5120
    assert fam.mixer_params(HF) == mixer == 47_349_760 + 20_480 + 20_971_520
    assert fam.mixer_vectors(HF) == 5120 + 96 + 4096
    attn = 2 * 5120 * 2560 + 2 * 5120 * 512
    assert fam.attn_params(HF) == attn == 2 * 13_107_200 + 5_242_880
    layer = mixer + attn + mlp
    assert fam.layer_params(HF) == layer == 430_100_480
    whole = layer + 5120 + 96 + 4096 + 2 * 5120
    assert whole == 430_120_032                     # "~430.1 M a layer"
    table = 261120 * 5120
    assert table == 1_336_934_400
    assert fam.param_count(HF) == 12 * whole + 2 * table + 5120
    assert fam.param_count(HF) == 7_835_314_304
    # the model whole: 72 layers, 33.6 B; 62.7 GiB in bfloat16, 31.3 in int8
    model = fam.param_count({**HF, "num_hidden_layers": 72})
    assert round(model / 1e9, 1) == 33.6
    assert round(2 * model / GiB, 1) == 62.7 and round(model / GiB, 1) == 31.3
    # the cut's weights in int8 (the vectors and gains in bfloat16 are 0.3
    # MB of it): 12 x 0.4006 + 2 x 1.245 = 7.30 GiB
    assert round(whole / GiB, 4) == 0.4006 and round(table / GiB, 3) == 1.245
    assert round(fam.param_count(HF) / GiB, 2) == 7.30
    assert fam.token_params(HF) == 12 * layer
    assert fam.step_params(HF, 64) == fam.step_params(HF, 1) == (
        12 * layer + table)
    assert round(fam.step_params(HF, 64) / GiB, 2) == 6.05
    # K and V of 12 layers x 4 K/V heads x 128: 24 KiB a token in bfloat16
    assert fam.kv_bytes_per_token(HF, 2.0) == 24 * KiB
    assert fam.q_elements_per_token(HF) == 12 * 20 * 128
    assert fam.attn_flops(HF, 10) == 4.0 * 12 * 20 * 128 * 10
    # a slot's state a layer: S 32 x 128 x 256 float32 = 4 MiB, 3 conv rows
    # x 5120 x 2 B = 30 KiB; a step reads and writes both
    assert 32 * 128 * 256 * 4 == 4 * MiB and 3 * 5120 * 2 == 30 * KiB
    assert fam.state_bytes(HF, 64) / 2 == 12 * 64 * (4 * MiB + 30 * KiB)
    assert round(fam.state_bytes(HF, 64) / 2 / GiB, 2) == 3.02
    # a decode step's bytes at 64 live slots: the state is half of it
    kv = 64 * 360 * 24 * KiB
    step = fam.step_params(HF, 64) + fam.state_bytes(HF, 64) + kv
    assert round(step / GiB, 1) == 12.6
    assert 0.47 < fam.state_bytes(HF, 64) / step < 0.49
    assert 16.4e-3 < step / 819e9 < 16.7e-3
    # the file: the cut, the engine's sizes
    assert CONFIG["reference"]["family"] == "falcon_h1_family"
    assert set(CONFIG["reduced"]) == {"num_hidden_layers",
                                      "max_position_embeddings"}
    eng = CONFIG["engine"]
    assert (eng["max_slots"], eng["kv_num_blocks"], eng["spec"],
            eng["quantization"]) == (64, 1281, False, "int8")
    # 64 streams x 20 blocks (256 + 1024 tokens) + the trash block
    assert 64 * -(-(256 + 1024) // 64) + 1 == 1281
    assert round(1281 * 64 * 24 * KiB / GiB, 2) == 1.88
    for key in ("deployment", "assumed", "hbm", "notes"):
        assert CONFIG[key], key


def test_every_published_number_of_the_state_space_hybrids_catalog_row_is_in_the_file():
    """Every key of the published config stands in the file, unchanged but
    for the ones ``reduced`` names; no width is among those."""
    published = {
        "attention_bias": False, "attention_in_multiplier": 1,
        "attention_out_multiplier": 0.0375, "attn_layer_indices": None,
        "embedding_multiplier": 5.656854249492381, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 5120,
        "intermediate_size": 21504, "key_multiplier": 0.011048543456039804,
        "lm_head_multiplier": 0.0078125, "mamba_chunk_size": 128,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 128,
        "mamba_d_ssm": 4096, "mamba_d_state": 256, "mamba_expand": 2,
        "mamba_n_groups": 2, "mamba_n_heads": 32,
        "mamba_norm_before_gate": False, "mamba_proj_bias": False,
        "mamba_rms_norm": True, "mamba_use_mlp": True,
        "max_position_embeddings": 262144, "mlp_bias": False,
        "mlp_expansion_factor": 8,
        "mlp_multipliers": [0.1767766952966369, 0.011160714285714284],
        "model_type": "falcon_h1", "num_attention_heads": 20,
        "num_hidden_layers": 72, "num_key_value_heads": 4,
        "num_logits_to_keep": 1, "projectors_bias": False,
        "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 100000000000, "ssm_in_multiplier": 0.25,
        "ssm_multipliers": [0.3535533905932738, 0.25, 0.1767766952966369,
                            0.5, 0.3535533905932738],
        "ssm_out_multiplier": 0.08838834764831845,
        "tie_word_embeddings": False, "vocab_size": 261120}
    assert len(published) == 42
    changed = {k for k, v in published.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers"}
    assert changed < set(CONFIG["reduced"])
    assert (CONFIG["num_hidden_layers"], CONFIG["context_size"]) == (12, 4096)
    entry = next(c for c in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["configs"]
        if c["name"] == CONFIG["name"])
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"]


def test_the_served_stack_is_a_row_a_layer_with_pool_and_state():
    """What harness/refcheck.py rests on: every leaf of the served ``layers``
    pytree leads with the layer, under the names the family reads; the
    family's count is the program's; and a layer owns a cache layer AND a
    row of state of the bytes ``state_bytes`` prices."""
    import jax

    from harness import refcheck
    from localai_tpu.models import falcon_h1 as fh
    from localai_tpu.models import llama as mdl
    from localai_tpu.models.llama import LlamaConfig

    fam = family()
    cfg = LlamaConfig.from_hf(HF)
    assert (cfg.recurrent, cfg.routed, cfg.cache_layers) == (True, False, 12)
    shapes = mdl.param_shapes(cfg)
    layers = shapes["layers"]
    assert {s[0] for s in layers.values()} == {12}
    assert layers["ssm_in"] == (12, 5120, 9248)
    assert layers["ssm_conv"] == (12, 4, 5120)
    assert layers["ssm_norm"] == (12, 4096)
    assert layers["wk"] == (12, 5120, 512)
    assert layers["w_down"] == (12, 21504, 5120)
    abstract = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "bfloat16"), shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    assert refcheck.served_param_count(abstract) == fam.param_count(HF)
    rec = jax.eval_shape(lambda: fh.init_rec(cfg, 64))
    assert rec["S"].shape == (12, 64, 32, 256, 128)
    assert rec["conv"].shape == (12, 64, 3, 5120)
    held = sum(a.size * a.dtype.itemsize for a in rec.values())
    assert 2 * held == fam.state_bytes(HF, 64)


def test_the_state_space_cell_reports_what_the_issue_names():
    """BENCHMARK.json as committed: ``fh1-34b-decode`` is ``m7b-decode``'s
    mix on the new configuration, 64 callers. Of the end-to-end metrics it
    reports TPOT and set-up; per layer what ``qn80-ep8-decode`` reports of
    TPOT's movers that name no cells, and the two new readers, which no other
    cell reports."""
    new, old = spec.load_cell(CELL), spec.load_cell("m7b-decode")
    assert new.chips == 1 and new.config_name == "falcon-h1-34b-int8"
    assert new.traffic == old.traffic
    assert new.drive["clients"] == new.max_slots == 64
    assert new.drive["limits"] == old.drive["limits"]
    assert new.drive["ramp_s"] == old.drive["ramp_s"] == 5.0
    assert {m["name"] for m in new.end_to_end} == {"tpot_ms_p90", "setup_s"}
    mine = {"ssm.state_bw_share", "ssm.mixer_share"}
    assert {m["name"] for m in new.per_layer} == {
        m["name"] for m in old.per_layer
        if m["moves"] in ("tpot_ms_p90", "setup_s")
        and "workloads" not in m} | mine
    assert {"model.decode_bw_share", "paged_decode_attn_roofline",
            "runner.kv_move_share"} <= {m["name"] for m in new.per_layer}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["per_layer"]:
        if m["name"] in mine:
            assert m["workloads"] == [CELL] and m["moves"] == "tpot_ms_p90"
        else:
            assert CELL not in m.get("workloads", [])
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == "falcon-h1-34b-int8"


def flight_row(ts, steps, live=64, program="decode"):
    return {"ts_unix": ts, "program": program, "steps": steps,
            "compile": False, "tokens": live * steps, "live_slots": live}


@pytest.mark.parametrize("with_scopes", [True, False])
def test_the_ssm_readers_read_the_ring_and_the_scopes(with_scopes):
    """``ssm.state_bw_share``: the bytes the slice's (live slot, step) pairs
    needed over the decode programs' device seconds under ``ssm/state``,
    against the HBM peak. ``ssm.mixer_share``: the decode programs' seconds
    under ``ssm/`` over all of theirs. Against a program whose trace names no
    such scope (the parent, every other configuration) and against a family
    that prices no state, both return None and raise nothing."""
    from harness.peaks import PEAKS

    fam = family()
    rows = [flight_row(10.0 + i, 1) for i in range(10)]
    rows += [flight_row(12.5, 2, program="decode_n"),
             flight_row(13.5, 0, program="prefill_chunk")]
    s = "ssm/" if with_scopes else "gdn/"
    cell = types.SimpleNamespace(
        family=fam, published=HF, chips=1, config=CONFIG)
    ctx = {
        "anchor": (0.0, 0.0), "cell": cell, "peak": PEAKS["TPU v5 lite"],
        "window": types.SimpleNamespace(t_open=9.0, t_close=30.0),
        "traced": {"flight": rows},
        "trace": {"start_unix": 10.0, "window_at_s": (0.0, 4.5), "op_rows": [
            ("jit__decode_paged_fn", f"decode/layers/{s}state",
             "ssm_state_step", 0.06),
            ("jit__decode_paged_n_fn", f"decode/layers/{s}state/copy",
             "fusion.1", 0.02),
            ("jit__decode_paged_fn", f"decode/layers/{s}in_proj", "fusion.2",
             0.01),
            ("jit__decode_paged_fn", f"decode/layers/{s}out_proj",
             "fusion.3", 0.01),
            ("jit__decode_paged_fn", "decode/layers/mlp", "fusion.4", 0.06),
            ("jit__decode_paged_fn", "decode/layers/attn.paged_decode",
             "paged_decode_attn", 0.04),
            ("jit__prefill_paged_fn", f"prefill/layers/{s}state",
             "fusion.5", 0.5)]}}
    readers = {n: spec.load_reader(n) for n in (
        "ssm.state_bw_share", "ssm.mixer_share")}
    got = {n: read(ctx) for n, read in readers.items()}
    if not with_scopes:
        assert got == dict.fromkeys(readers)
        return
    # the slice [10, 14.5) holds rows 10 .. 14 and the two-step row: 7 steps
    assert got["ssm.state_bw_share"] == pytest.approx(
        100 * (fam.state_bytes(HF, 7 * 64) / 819e9) / 0.08)
    assert got["ssm.mixer_share"] == pytest.approx(100 * 0.10 / 0.20)
    assert 0 < got["ssm.state_bw_share"] < 100
    for name, read in readers.items():      # --trace 0; a voided slice
        assert read({**ctx, "trace": None}) is None
    dense = types.SimpleNamespace(
        family=family(name="llama_family"), published=HF, chips=1,
        config=CONFIG)
    assert readers["ssm.state_bw_share"]({**ctx, "cell": dense}) is None


# a model of the family at the test's size, int8 weights, every multiplier
# off 1: 3 layers, 4 mixer heads of 16 in 2 groups, state 32
SMALL = {
    "model_type": "falcon_h1", "hidden_size": 128, "intermediate_size": 192,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "rope_theta": 1e11,
    "rms_norm_eps": 1e-5, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_d_ssm": 64, "mamba_n_groups": 2, "mamba_d_state": 32,
    "mamba_d_conv": 4, "mamba_conv_bias": True, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "embedding_multiplier": 3.1,
    "lm_head_multiplier": 0.37, "attention_in_multiplier": 0.8,
    "attention_out_multiplier": 0.21, "key_multiplier": 0.3,
    "ssm_in_multiplier": 0.6, "ssm_out_multiplier": 0.45,
    "ssm_multipliers": [0.35, 0.25, 0.18, 0.5, 0.7],
    "mlp_multipliers": [0.4, 0.15],
    "engine": {"max_slots": 4, "attn_impl": "xla", "prefill_chunk": 64,
               "spec": False, "decode_steps_per_dispatch": 2,
               "dtype": "float32", "quantization": "int8"},
    "reference": {"epsilon": 0.006, "why": "a test"}}


def test_a_state_space_hybrid_runs_by_files_alone(bench_copy, cpu_peaks,
                                                  capsys):
    """A small model of the family, served by the program's normal path (the
    scheduler, chunked prefill, the paged pool with a cache layer a layer,
    per-slot state, int8 weights) from its published keys, judged by its
    family on the served weights dequantised: new files, none edited,
    ``correct``, and the parameter count is the family's."""
    add_architecture(bench_copy, "tiny-fh", "falcon_h1_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-fh", 5)
    assert out["correct"] is True and out["failed"] == 0
    assert check["ok"] is True and check["positions"] == 64
    # the mixer: in_proj to z 64 + [x 64; B 64; C 64] + dt 4, the conv's
    # taps and bias over 192 channels, out_proj, three scalars a head, the
    # gated norm's gain
    mixer = 128 * 260 + 4 * 192 + 64 * 128 + 192 + 3 * 4 + 64
    attn = 2 * 128 * 128 + 2 * 128 * 64
    layer = mixer + attn + 3 * 128 * 192 + 2 * 128
    assert check["params_served"] == check["params_described"] == (
        3 * layer + 2 * 512 * 128 + 128)
    assert check["max_shortfall"] < check["epsilon"] / 3


def test_the_control_fails_a_family_whose_state_never_decays(
        bench_copy, cpu_peaks, capsys):
    """THE FAILING CONTROL: the same server judged by the family with the
    decay ``exp(dt A)`` left out of its recurrence (a copy of the family file
    with that one factor gone): the weights are the same, so the count
    agrees; the tokens are another model's, so the run is not ``correct``."""
    src = (bench_copy / "benchmark" / "reference"
           / "falcon_h1_family.py").read_text()
    factor = "jnp.exp(dt_t * A)[:, None, None] * S"
    assert src.count(factor) == 1
    (bench_copy / "benchmark" / "reference"
     / "no_ssm_decay_family.py").write_text(src.replace(factor, "S"))
    add_architecture(bench_copy, "tiny-fh", "no_ssm_decay_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-fh", 5)
    assert out["failed"] == 0 and out["correct"] is False
    assert check["params_served"] == check["params_described"]
    assert check["max_shortfall"] > 3 * check["epsilon"], check
