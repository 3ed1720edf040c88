"""The convolution hybrid's family (``reference/lfm2_family.py``), its
configuration and its cell, added by files alone (PR 57): the hand arithmetic
of the published keys at the cut the file states and whole, the catalog row
in the file, the reference against a second, slower writing of itself (token
by token, a head and an expert at a time), the served stack's leaf shapes,
what the new cell reports, the three new readers; and, end to end on the CPU,
a small model of the family under a list with a TAIL served by the program
and judged ``correct`` by its family, with the control that fails: the same
server judged by a family whose convolution forgets its oldest tap."""

import json
import types

import numpy as np
import pytest

import test_walk as tw
from conftest import ROOT, add_architecture
from harness import spec

CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "lfm2-8b-a1b-pp2.json").read_text())
HF = {k: v for k, v in CONFIG.items() if k not in spec.CONFIG_KEYS}
CELL = "lfm2-pp2-decode"
KiB, MiB, GiB = 2 ** 10, 2 ** 20, 2 ** 30
C, A = "conv", "full_attention"
PUBLISHED = [C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C, C, A, C, C,
             A, C, C]


def family(root=ROOT, name="lfm2_family"):
    return spec.load_family(spec.family_file(
        {"reference": {"family": name}}, "a test", root))


def test_the_hand_arithmetic_of_the_convolution_hybrids_published_keys():
    """ISSUE 57's numbers, recounted from the configuration file as
    committed."""
    fam = family()
    n = fam.dims(HF)
    assert (n["hd"], n["conv"], n["full"], n["nd"]) == (64, 11, 3, 2)
    conv = 2048 * 6144 + 2048 * 2048 + 3 * 2048
    assert fam.conv_params(HF) == conv == 16_783_360
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert fam.attn_params(HF) + 2 * 64 == attn + 128 == 10_485_888
    dense = 3 * 2048 * 7168
    assert fam.dense_params(HF) == dense == 44_040_192
    block = 32 * 3 * 2048 * 1792 + 2048 * 32 + 32
    assert (32 * fam.expert_params(HF) + fam.block_fixed_params(HF)
            == block == 352_387_104)
    table = 65536 * 2048
    assert table == 134_217_728 and fam.table_params(HF) == table + 2048
    gains = 14 * 2 * 2048 + 2048
    assert gains == 59_392
    cut = (12 * block + 2 * (conv + dense) + 3 * (attn + 128) + 9 * conv
           + table + gains)
    assert fam.param_count(HF) == cut == 4_667_077_376
    assert round(2 * cut / 1e9, 2) == 9.33 and round(2 * cut / GiB, 2) == 8.69
    # the model whole: 24 layers, tied 8.34 B (the published "8.3B"), untied
    # 8.47 B; 16.7 GB in bfloat16, which no v5e chip holds
    whole_hf = {**HF, "num_hidden_layers": 24, "layer_types": PUBLISHED}
    whole = fam.param_count(whole_hf)
    assert whole == (22 * block + 2 * dense + 18 * conv + 6 * (attn + 128)
                     + table + 24 * 2 * 2048 + 2048)
    assert round(whole / 1e9, 2) == 8.34
    assert round(fam.param_count(
        {**whole_hf, "tie_word_embeddings": False}) / 1e9, 2) == 8.47
    assert round(2 * whole / 1e9, 1) == 16.7
    # a decode step at 128 rows touches every expert ((28/32)^128 is 4e-8;
    # at the cell's 96 rows 3e-6) and reads every weight once: the experts
    # are 8.46 of its 9.33 GB
    assert fam.experts_touched(HF, 128) > 31.99999
    assert fam.experts_touched(HF, 96) > 31.9999
    step = fam.step_params(HF, 128)
    assert abs(step - (cut - gains - 3 * 128)) < 1000
    experts = 12 * 32 * fam.expert_params(HF)
    assert round(2 * experts / 1e9, 2) == 8.46
    assert round(2 * 11 * conv / 1e9, 2) == 0.37
    assert 0.90 < experts / step < 0.92
    assert 11.3e-3 < 2 * step / 819e9 < 11.5e-3
    # one token multiplies 4 experts a block
    assert fam.token_params(HF) == (
        11 * conv + 3 * attn + 2 * dense
        + 12 * (fam.block_fixed_params(HF) + 4 * fam.expert_params(HF)))
    assert fam.layer_params(HF) * 14 == fam.token_params(HF) + 12 * 28 * (
        fam.expert_params(HF))
    # K and V of 3 layers x 8 K/V heads x 64: 6 KiB a token in bfloat16
    assert fam.kv_bytes_per_token(HF, 2.0) == 6 * KiB
    assert fam.q_elements_per_token(HF) == 3 * 32 * 64
    assert fam.attn_flops(HF, 10) == 4.0 * 3 * 32 * 64 * 10
    assert fam.cache_layers(HF) == 3
    # a slot's state a convolution layer: 2 rows x 2048 x 2 B = 8 KiB; a
    # step reads and writes it
    assert fam.state_bytes(HF, 128) / 2 == 11 * 128 * 8 * KiB == 11 * MiB
    assert fam.state_bytes(HF, 96) / 2 == 8.25 * MiB
    assert fam.expert_bytes(HF, 12 * 32) == 2 * experts
    # the file: the cut, the engine's sizes
    assert CONFIG["reference"]["family"] == "lfm2_family"
    assert set(CONFIG["reduced"]) == {"num_hidden_layers", "layer_types",
                                      "max_position_embeddings"}
    eng = CONFIG["engine"]
    slots = eng["max_slots"]
    assert eng["spec"] is False and not eng.get("quantization")
    # 96 streams, ISSUE 57's ONE fallback: two sets of six at its 128 spread
    # ``tpot_ms_p90`` by 4.00 and 8.81%, over half its bound (the cell's
    # ``why`` and PERF.md 6 hold the readings); each reserves 20 blocks
    # (256 + 1024 tokens), and one is the trash block
    assert slots == 96
    assert eng["kv_num_blocks"] == slots * -(-(256 + 1024) // 64) + 1 == 1921
    assert round(1921 * 64 * 6 * KiB / GiB, 2) == 0.70
    for key in ("deployment", "assumed", "hbm", "notes"):
        assert CONFIG[key], key


def test_every_published_number_of_the_convolution_hybrids_catalog_row_is_in_the_file():
    """Every key of the published config stands in the file, unchanged but
    for the ones ``reduced`` names; no width is among those."""
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 7168, "layer_types": PUBLISHED,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe",
        "moe_intermediate_size": 1792, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 24, "num_key_value_heads": 8,
        "rope_theta": 1000000, "routed_scaling_factor": 1,
        "use_expert_bias": True, "vocab_size": 65536}
    assert len(published) == 20
    changed = {k for k, v in published.items() if CONFIG.get(k, "absent") != v}
    assert changed == {"num_hidden_layers", "layer_types"}
    assert changed < set(CONFIG["reduced"])
    assert CONFIG["layer_types"] == PUBLISHED[:14]
    assert (CONFIG["num_hidden_layers"], CONFIG["context_size"]) == (14, 4096)
    entry = next(c for c in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["configs"]
        if c["name"] == CONFIG["name"])
    assert sorted(entry["reduced"]) == sorted(CONFIG["reduced"])
    assert entry["source"] == CONFIG["source"]


TINY = {"model_type": "lfm2_moe", "vocab_size": 64, "hidden_size": 32,
        "intermediate_size": 48, "num_hidden_layers": 7,
        "layer_types": [C, C, A, C, C, A, C], "num_dense_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "conv_L_cache": 3, "conv_bias": False, "moe_intermediate_size": 16,
        "num_experts": 8, "num_experts_per_tok": 2, "norm_eps": 1e-5,
        "norm_topk_prob": True, "use_expert_bias": True,
        "routed_scaling_factor": 1.5, "rope_theta": 10000}


def test_the_reference_agrees_with_a_slower_writing_of_itself():
    """The family's forward (three shifted products over the whole sequence,
    attention a matrix, the experts a group at a time) against the same
    equations written out a second time, token by token: the convolution as
    a loop over t reading u[t-2], u[t-1], u[t]; attention a query and a head
    at a time over its own K/V head; the experts a chosen expert at a time.
    Float64-free: both float32 at highest precision, agreeing to rounding."""
    import jax
    import jax.numpy as jnp

    fam = family()
    rng = np.random.default_rng(57)
    n = fam.dims(TINY)
    D, T, hd = n["D"], 9, n["hd"]

    def draw(*shape, scale=1.0):
        return np.asarray(rng.standard_normal(shape) * scale, np.float32)

    def sigmoid(z):
        return 1.0 / (1.0 + np.exp(-z))

    def silu(z):
        return z * sigmoid(z)

    def norm(x, w):
        return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + 1e-5) * w

    def rot(x, t):      # x [hd] at position t
        inv = 1.0 / (10000.0 ** (np.arange(0, hd, 2) / hd))
        c, s = np.cos(t * inv), np.sin(t * inv)
        a, b = x[:hd // 2], x[hd // 2:]
        return np.concatenate([a * c - b * s, b * c + a * s])

    def slow_layer(x, w, kind, dense):
        h = norm(x, w["op_norm"])
        if kind == C:
            p = h @ w["conv_in"]
            b, c, xs = p[:, :D], p[:, D:2 * D], p[:, 2 * D:]
            u = b * xs
            out = np.zeros_like(x)
            for t in range(T):
                v = w["conv_w"][2] * u[t]
                if t >= 1:
                    v = v + w["conv_w"][1] * u[t - 1]
                if t >= 2:
                    v = v + w["conv_w"][0] * u[t - 2]
                out[t] = (c[t] * v) @ w["conv_out"]
        else:
            q = (h @ w["wq"]).reshape(T, n["Hq"], hd)
            k = (h @ w["wk"]).reshape(T, n["Hkv"], hd)
            v = (h @ w["wv"]).reshape(T, n["Hkv"], hd)
            q, k = norm(q, w["q_norm"]), norm(k, w["k_norm"])
            o = np.zeros((T, n["Hq"], hd), np.float32)
            for t in range(T):
                for head in range(n["Hq"]):
                    g = head // (n["Hq"] // n["Hkv"])
                    sc = np.array([rot(q[t, head], t) @ rot(k[s, g], s)
                                   for s in range(t + 1)]) / np.sqrt(hd)
                    pr = np.exp(sc - sc.max())
                    o[t, head] = (pr / pr.sum()) @ v[:t + 1, g]
            out = o.reshape(T, -1) @ w["wo"]
        x = x + out
        h = norm(x, w["ffn_norm"])
        if dense:
            return x + (silu(h @ w["w_gate"]) * (h @ w["w_up"])) @ w["w_down"]
        out = np.zeros_like(x)
        for t in range(T):
            s = sigmoid(h[t] @ w["moe_gate"])
            chosen = np.argsort(-(s + w["expert_bias"]), kind="stable")[:2]
            for e in chosen:
                y = (silu(h[t] @ w["w_gate"][e]) * (h[t] @ w["w_up"][e])
                     ) @ w["w_down"][e]
                out[t] += s[e] / (s[chosen].sum() + 1e-6) * 1.5 * y
        return x + out

    x = draw(T, D)
    cos, sin = fam.rope_tables(TINY, T)
    want, got = x.copy(), jnp.asarray(x)
    assert [(p, r, "".join(k[0] for k in ks), d)
            for p, r, ks, d in fam.runs(TINY)] == [
        ("dense_", 1, "cc", True), ("", 1, "fcc", False),
        ("tail1_", 1, "fc", False)]
    with jax.default_matmul_precision("highest"):
        for _, _, kinds, dense in fam.runs(TINY):
            M, nc, na = len(kinds), kinds.count(C), kinds.count(A)
            w = {"op_norm": 1 + draw(M, D, scale=0.3),
                 "ffn_norm": 1 + draw(M, D, scale=0.3),
                 "conv_in": draw(nc, D, 3 * D, scale=0.3),
                 "conv_w": draw(nc, 3, D),
                 "conv_out": draw(nc, D, D, scale=0.2),
                 "wq": draw(na, D, n["Hq"] * hd, scale=0.3),
                 "wk": draw(na, D, n["Hkv"] * hd, scale=0.3),
                 "wv": draw(na, D, n["Hkv"] * hd, scale=0.3),
                 "wo": draw(na, n["Hq"] * hd, D, scale=0.2),
                 "q_norm": 1 + draw(na, hd, scale=0.3),
                 "k_norm": 1 + draw(na, hd, scale=0.3)}
            if dense:
                w.update(w_gate=draw(M, D, 48, scale=0.3),
                         w_up=draw(M, D, 48, scale=0.3),
                         w_down=draw(M, 48, D, scale=0.2))
            else:
                w.update(moe_gate=draw(M, D, 8), expert_bias=draw(M, 8),
                         w_gate=draw(M, 8, D, 16, scale=0.3),
                         w_up=draw(M, 8, D, 16, scale=0.3),
                         w_down=draw(M, 8, 16, D, scale=0.3))
            got = fam.row(got, w, cos, sin, TINY, kinds, dense)
            seen = {C: 0, A: 0}
            for m, kind in enumerate(kinds):
                j = seen[kind]
                seen[kind] += 1
                mixers = sum(fam.MIXER_LEAVES.values(), ())
                one = {name: a[m] for name, a in w.items()
                       if name not in mixers}
                one.update({name: w[name][j]
                            for name in fam.MIXER_LEAVES[kind]})
                want = slow_layer(want, one, kind, dense)
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4, atol=2e-4)


def test_the_served_stack_is_rows_of_like_layers_with_pool_and_state():
    """What harness/refcheck.py rests on: every leaf of the served ``layers``
    pytree leads with the ROW (three of ``a c c c``), under the names the
    family reads, the dense prefix beside it under ``dense_``; the family's
    count is the program's; the pool holds the attention layers alone, two
    64-wide heads a 128-lane row, and the state is two rows a slot a
    convolution layer, of the bytes ``state_bytes`` prices."""
    import jax

    from harness import refcheck
    from localai_tpu.models import lfm2
    from localai_tpu.models import llama as mdl
    from localai_tpu.models.llama import LlamaConfig

    fam = family()
    cfg = LlamaConfig.from_hf(HF)
    assert (cfg.recurrent, cfg.routed, cfg.cache_layers) == (True, True, 3)
    assert (cfg.num_kv_heads, cfg.hd, cfg.kv_pack) == (4, 128, 2)
    assert cfg.tie_word_embeddings
    assert [(p, r, ks, d) for p, r, ks, d in fam.runs(HF)] == [
        (r.prefix, r.rows, r.kinds, r.dense) for r in cfg.runs]
    shapes = mdl.param_shapes(cfg)
    layers = shapes["layers"]
    assert {s[0] for s in layers.values()} == {3}
    assert layers["conv_in"] == (3, 3, 2048, 6144)
    assert layers["conv_w"] == (3, 3, 3, 2048)
    assert layers["wk"] == (3, 1, 2048, 512)
    assert layers["q_norm"] == (3, 1, 64)
    assert layers["moe_gate"] == (3, 4, 2048, 32)
    assert layers["w_down"] == (3, 4, 32, 1792, 2048)
    assert shapes["dense_w_gate"] == (1, 2, 2048, 7168)
    assert shapes["dense_conv_in"] == (1, 2, 2048, 6144)
    assert "lm_head" not in shapes and "dense_wq" not in shapes
    assert sorted(set(shapes) - {"embed", "final_norm", "layers"}) == sorted(
        "dense_" + n for n in fam.run_leaf_names(HF, (C, C), True))
    assert sorted(layers) == sorted(
        fam.run_leaf_names(HF, (A, C, C, C), False))
    abstract = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "bfloat16"), shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    assert refcheck.served_param_count(abstract) == fam.param_count(HF)
    rec = jax.eval_shape(lambda: lfm2.init_rec(cfg, 128))
    assert rec["conv"].shape == (11, 128, 2, 2048)
    assert 2 * rec["conv"].size * 2 == fam.state_bytes(HF, 128)


def test_the_convolution_cell_reports_what_the_issue_names():
    """BENCHMARK.json as committed: ``lfm2-pp2-decode`` is ``m7b-decode``'s
    mix on the new configuration, 96 callers, a caller a slot. Of the end-to-end metrics it
    reports TPOT and set-up; per layer what ``m7b-decode`` reports of TPOT's
    movers that name no cells, and the three new readers, which no other
    cell reports."""
    new, old = spec.load_cell(CELL), spec.load_cell("m7b-decode")
    assert new.chips == 1 and new.config_name == "lfm2-8b-a1b-pp2"
    assert new.traffic == old.traffic
    assert new.drive["clients"] == new.max_slots == 96
    assert new.drive["limits"] == old.drive["limits"]
    assert new.drive["ramp_s"] == old.drive["ramp_s"] == 5.0
    assert {m["name"] for m in new.end_to_end} == {"tpot_ms_p90", "setup_s"}
    mine = {"sconv.mixer_share", "lfm2.expert_bw_share",
            "lfm2.expert_rows_mean"}
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
    # appended behind what was there, as one block each (a later PR appends
    # behind these in turn: nothing here holds them to be the LAST)
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index("sconv.mixer_share")
    assert names[at:at + 3] == ["sconv.mixer_share", "lfm2.expert_bw_share",
                                "lfm2.expert_rows_mean"]
    assert names[at - 1] == "ssm.mixer_share"
    cells = [w["name"] for w in bench["workloads"]]
    assert cells[cells.index(CELL) - 1] == "fh1-34b-decode"
    configs = [c["name"] for c in bench["configs"]]
    assert configs[configs.index("lfm2-8b-a1b-pp2") - 1] == (
        "falcon-h1-34b-int8")


def flight_row(ts, steps, live=96, program="decode", routed=True):
    row = {"ts_unix": ts, "program": program, "steps": steps,
           "compile": False, "tokens": live * steps, "live_slots": live}
    if routed:      # 12 blocks a step, every expert touched, 4 pairs a row
        row.update(experts_touched=12 * 32 * steps,
                   local_assignments=12 * 4 * live * steps)
    return row


@pytest.mark.parametrize("with_scopes", [True, False])
def test_the_convolution_cells_readers_read_the_ring_and_the_scopes(
        with_scopes):
    """``sconv.mixer_share``: the decode programs' seconds under ``sconv/``
    over all of theirs. ``lfm2.expert_bw_share``: the bytes of the experts
    the slice's decode launches touched over the decode programs' seconds
    under ``moe/experts``, against the HBM peak. ``lfm2.expert_rows_mean``:
    pairs over experts touched in the window. Against a program whose trace
    names no such scope and whose ring has no such column (the parent, a
    dense configuration) all three return None and raise nothing."""
    from harness.peaks import PEAKS

    fam = family()
    rows = [flight_row(10.0 + i, 1, routed=with_scopes) for i in range(10)]
    rows += [flight_row(12.5, 2, program="decode_n", routed=with_scopes),
             flight_row(13.5, 0, program="prefill_chunk", routed=False)]
    s, e = ("sconv/", "moe/experts") if with_scopes else ("ssm/", "mlp")
    cell = types.SimpleNamespace(
        family=fam, published=HF, chips=1, config=CONFIG)
    ctx = {
        "anchor": (0.0, 0.0), "cell": cell, "peak": PEAKS["TPU v5 lite"],
        "window": types.SimpleNamespace(t_open=9.0, t_close=30.0),
        "traced": {"flight": rows},
        "trace": {"start_unix": 10.0, "window_at_s": (0.0, 4.5), "op_rows": [
            ("jit__decode_paged_fn", f"decode/layers/{s}in_proj", "fusion.1",
             0.006),
            ("jit__decode_paged_n_fn", f"decode/layers/{s}conv", "fusion.2",
             0.002),
            ("jit__decode_paged_fn", f"decode/layers/{s}out_proj",
             "fusion.3", 0.002),
            ("jit__decode_paged_fn", f"decode/layers/{e}/moe_experts",
             "moe_experts", 0.1),
            ("jit__decode_paged_fn", "decode/layers/moe/router", "fusion.4",
             0.01),
            ("jit__decode_paged_fn", "decode/layers/attn.paged_decode",
             "paged_decode_attn", 0.005),
            ("jit__prefill_paged_fn", f"prefill/layers/{s}conv",
             "fusion.5", 0.5)]}}
    readers = {n: spec.load_reader(n) for n in (
        "sconv.mixer_share", "lfm2.expert_bw_share", "lfm2.expert_rows_mean")}
    got = {n: read(ctx) for n, read in readers.items()}
    if not with_scopes:
        assert got == dict.fromkeys(readers)
        return
    assert got["sconv.mixer_share"] == pytest.approx(100 * 0.010 / 0.125)
    # the slice [10, 14.5) holds rows 10 .. 14 and the two-step row: 7 steps
    assert got["lfm2.expert_bw_share"] == pytest.approx(
        100 * (fam.expert_bytes(HF, 7 * 12 * 32) / 819e9) / 0.1)
    assert 0 < got["lfm2.expert_bw_share"] < 100
    assert got["lfm2.expert_rows_mean"] == 12.0     # 96 x 4 / 32
    for name in ("sconv.mixer_share", "lfm2.expert_bw_share"):
        assert readers[name]({**ctx, "trace": None}) is None    # --trace 0
    dense = types.SimpleNamespace(
        family=family(name="llama_family"), published=HF, chips=1,
        config=CONFIG)
    assert readers["lfm2.expert_bw_share"]({**ctx, "cell": dense}) is None


# a model of the family at the test's size: 7 layers (a dense prefix, the
# stack, a TAIL), heads of 64 packed two to a row, 8 experts top-2
SMALL = {
    "model_type": "lfm2_moe", "hidden_size": 128, "intermediate_size": 192,
    "num_hidden_layers": 7, "layer_types": [C, C, A, C, C, A, C],
    "num_dense_layers": 2, "num_attention_heads": 2,
    "num_key_value_heads": 2, "conv_L_cache": 3, "conv_bias": False,
    "moe_intermediate_size": 64, "num_experts": 8, "num_experts_per_tok": 2,
    "norm_eps": 1e-5, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "rope_theta": 1000000,
    "tie_word_embeddings": True,    # (the test's base file says false)
    "engine": {"max_slots": 4, "attn_impl": "xla", "prefill_chunk": 64,
               "spec": False, "decode_steps_per_dispatch": 2,
               # float32 K/V too: a bfloat16 pool under float32 weights
               # rounds keys by 2^-9, which flips a router's near-tie in one
               # run of two here (read: 0.024 and 0.63 at seeds 5 and 8)
               "dtype": "float32", "kv_dtype": "float32"},
    "reference": {"epsilon": 0.003, "why": "a test"}}


def test_a_convolution_hybrid_runs_by_files_alone(bench_copy, cpu_peaks,
                                                  capsys):
    """A small model of the family, served by the program's normal path (the
    scheduler, chunked prefill, the paged pool with a cache layer an
    attention layer, two rows of state a slot a convolution layer, every
    expert held, tied tables) from its published keys, judged by its family:
    new files, none edited, ``correct``, and the parameter count is the
    family's."""
    add_architecture(bench_copy, "tiny-lfm2", "lfm2_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-lfm2", 5)
    assert out["correct"] is True and out["failed"] == 0, check
    assert check["ok"] is True and check["positions"] == 64
    conv = 128 * 384 + 3 * 128 + 128 * 128
    attn = 2 * 128 * 128 + 2 * 128 * 128 + 2 * 64
    block = 8 * 3 * 128 * 64 + 128 * 8 + 8
    assert check["params_served"] == check["params_described"] == (
        5 * conv + 2 * attn + 2 * 3 * 128 * 192 + 5 * block
        + 7 * 2 * 128 + 512 * 128 + 128)
    assert check["max_shortfall"] < check["epsilon"] / 3


def test_the_control_fails_a_family_whose_convolution_forgets_a_tap(
        bench_copy, cpu_peaks, capsys):
    """THE FAILING CONTROL: the same server judged by the family with the
    tap on u[t-2] left out of its convolution (a copy of the family file
    whose sum starts at the second tap): the weights are the same, so the
    count agrees; the tokens are another model's, so the run is not
    ``correct``."""
    src = (bench_copy / "benchmark" / "reference"
           / "lfm2_family.py").read_text()
    taps = "for i in range(k))"
    assert src.count(taps) == 1
    (bench_copy / "benchmark" / "reference"
     / "two_tap_family.py").write_text(
        src.replace(taps, "for i in range(1, k))"))
    add_architecture(bench_copy, "tiny-lfm2", "two_tap_family", **SMALL)
    out, check = tw.run_cell(bench_copy, capsys, "tiny-lfm2", 5)
    assert out["failed"] == 0 and out["correct"] is False
    assert check["params_served"] == check["params_described"]
    assert check["max_shortfall"] > 3 * check["epsilon"], check
