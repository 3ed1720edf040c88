"""The Lightning / block-sparse hybrid's family (``reference/
minicpm_sala_family.py``), its configuration and its cell, added by files
alone (PR 62): the hand arithmetic of the published keys (ALL 32 layers: the
file cuts no depth), the catalog row in the file, the reference against a
slower writing of its selection, the served pytree's leaf shapes, what the
new cell reports (its entries by NAME: not that they are the last, nor that
no later metric lists the cell), the five new readers."""

import json
import types

import numpy as np
import pytest

from conftest import ROOT
from harness import spec

CONFIG = json.loads((ROOT / "benchmark" / "configs"
                     / "minicpm-sala-9b-int8.json").read_text())
HF = {k: v for k, v in CONFIG.items() if k not in spec.CONFIG_KEYS}
CELL = "sala-longdoc-decode"
KiB, MiB, GiB = 2 ** 10, 2 ** 20, 2 ** 30
MINE = {"lightning.state_bw_share", "sala.sparse_attend_roofline",
        "sala.select_share", "sala.sparse_rows_share",
        "rec.prefix_restore_share"}


def family(root=ROOT, name="minicpm_sala_family"):
    return spec.load_family(spec.family_file(
        {"reference": {"family": name}}, "a test", root))


def test_the_hand_arithmetic_of_the_lightning_hybrids_published_keys():
    """ISSUE 62's numbers, recounted from the configuration file as
    committed."""
    fam = family()
    n = fam.dims(HF)
    assert (n["light"], n["sparse"], n["L"]) == (24, 8, 32)
    assert [i for i, k in enumerate(n["kinds"]) if k == "minicpm4"] == [
        0, 9, 16, 17, 22, 29, 30, 31]
    mlp = 3 * 4096 * 16384
    assert fam.mlp_params(HF) == mlp == 201_326_592
    assert fam.lightning_params(HF) == 5 * 4096 * 4096 + mlp == 285_212_672
    assert fam.sparse_params(HF) == (3 * 4096 * 4096 + 2 * 4096 * 256
                                     + mlp) == 253_755_392
    table = 73448 * 4096
    assert table == 300_843_008
    weights = 24 * 285_212_672 + 8 * 253_755_392 + 2 * table
    assert weights == 9_476_833_280
    # gains: two [4096] norms a layer, q and k norms of 128, a Lightning
    # layer's output norm [4096], the final norm; the decay buffer 24 x 32
    gains = 24 * (2 * 4096 + 2 * 128 + 4096) + 8 * (2 * 4096 + 2 * 128) + 4096
    assert gains == 372_736
    assert fam.vectors(HF) == gains + 24 * 32
    assert fam.param_count(HF) == weights + gains + 768
    assert fam.token_params(HF) == weights - 2 * table
    assert fam.layer_params(HF) == 285_212_672
    assert fam.step_params(HF, 32) == fam.step_params(HF, 1) == (
        weights - table)
    # int8: the whole model on one chip; bfloat16 would hold 18 layers
    assert round(weights / GiB, 2) == 8.83
    assert round(fam.step_params(HF, 32) / 1e9, 2) == 9.18
    # K and V of 8 layers x 2 K/V heads x 128: 8 KiB a token in bfloat16 is
    # what a token ADDS; what a step must READ of a cached token at the
    # served context is topk x block_size of 34816 of that
    assert 2 * 8 * 2 * 128 * 2 == 8 * KiB
    assert fam.kv_bytes_per_token(HF, 2.0) == 8 * KiB * 4096 / 34816
    assert fam.q_elements_per_token(HF) == 8 * 32 * 128
    assert fam.attn_flops(HF, 10) == 4.0 * 8 * 32 * 128 * 10
    assert fam.cache_layers(HF) == 8
    # a slot's Lightning state: 24 layers x 32 heads x 128 x 128 float32
    assert fam.state_bytes(HF, 1) == 2 * 48 * MiB
    assert round(fam.state_bytes(HF, 32) / 1e9, 1) == 3.2
    # the selected blocks' K/V a step: 8 layers x 32 streams x 2 heads x 64
    # blocks x 32 KiB (K and V of 64 tokens x 128 x bf16) = 1.0 GiB, where a
    # dense attend of the same contexts would read 8.6 GB
    block = 2 * 64 * 128 * 2
    assert block == 32 * KiB
    kv = 8 * 32 * 2 * 64 * block
    assert kv == GiB
    qo = 32 * 8 * 2 * 2 * 32 * 128
    assert fam.selected_kv_bytes(HF, 32) == kv + qo
    assert round(32 * 33000 * 8 * KiB / 1e9, 1) == 8.7
    # the scoring: 2080 compressed keys a stream a head a layer at 33.3 k
    assert round(fam.compressed_key_bytes(HF, 32, 33280) / 1e9, 2) == 0.27
    # a step at 32 streams: weights 11.2 ms, state 3.9, attend 1.3 at 819 GB/s
    assert 11.1e-3 < fam.step_params(HF, 32) / 819e9 < 11.3e-3
    assert 3.8e-3 < fam.state_bytes(HF, 32) / 819e9 < 4.0e-3
    assert 1.2e-3 < kv / 819e9 < 1.4e-3
    # the decay: head 0 of layer 1 forgets fastest of the Lightning layers
    d = fam.log_decay(HF, 1)
    assert d.shape == (32,) and d[0] == pytest.approx(
        -2.0 ** (-8 / 32) * (1 - 1 / 31 + 1e-5), rel=1e-6)
    assert fam.log_decay(HF, 31, factor=False)[31] == pytest.approx(-2.0 ** -8)
    # the file: no depth cut, the engine's sizes
    assert CONFIG["reference"]["family"] == "minicpm_sala_family"
    assert set(CONFIG["reduced"]) == {"max_position_embeddings"}
    eng = CONFIG["engine"]
    assert (eng["max_slots"], eng["kv_num_blocks"], eng["spec"],
            eng["quantization"]) == (32, 3072, False, "int8")
    assert CONFIG["context_size"] == 34816 == 544 * 64
    # the pool: 4 documents x 512 blocks shared + 32 streams x 21 + spare
    assert 4 * 512 + 32 * 21 < 3072
    assert round(3072 * 64 * 8 * KiB / GiB, 2) == 1.5
    for key in ("deployment", "assumed", "hbm", "notes"):
        assert CONFIG[key], key
    for key in ("sparse_config", "decay", "selection rule"):
        assert key in CONFIG["assumed"], key


def test_every_published_number_of_the_lightning_hybrids_catalog_row_is_in_the_file():
    """Every key of the published config stands in the file, unchanged but
    for the one ``reduced`` names; ``sparse_config`` is the one key more."""
    kinds = ["lightning-attn"] * 32
    for i in (0, 9, 16, 17, 22, 29, 30, 31):
        kinds[i] = "minicpm4"
    published = {
        "attention_bias": False, "attn_use_rope": False, "head_dim": 128,
        "hidden_act": "silu", "hidden_size": 4096,
        "intermediate_size": 16384, "lightning_head_dim": 128,
        "lightning_nh": 32, "lightning_nkv": 32,
        "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
        "max_position_embeddings": 524288, "model_type": "minicpm_sala",
        "mixer_types": kinds, "num_attention_heads": 32,
        "num_hidden_layers": 32, "num_key_value_heads": 2, "qk_norm": True,
        "rand_init": False, "rms_norm_eps": 1e-06, "vocab_size": 73448,
        "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
        "mup_denominator": 32, "dim_model_base": 256,
        "tie_word_embeddings": False, "use_output_gate": True,
        "use_output_norm": True, "attn_use_output_gate": True}
    changed = {k for k, v in published.items() if HF.get(k) != v}
    assert changed == {"max_position_embeddings"} == set(CONFIG["reduced"])
    assert HF["max_position_embeddings"] == CONFIG["context_size"]
    assert set(HF) - set(published) == {"sparse_config"}
    assert HF["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "init_blocks": 1,
        "block_size": 64, "window_size": 2048, "topk": 64,
        "dense_len": 8192}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"]
                 if c["name"] == "minicpm-sala-9b-int8")
    assert entry["source"] == CONFIG["source"] == (
        "https://huggingface.co/openbmb/MiniCPM-SALA/blob/main/config.json")
    assert entry["reduced"] == ["max_position_embeddings"]


SMALL = {**HF, "hidden_size": 32, "intermediate_size": 48,
         "num_hidden_layers": 2, "mixer_types": ["minicpm4",
                                                 "lightning-attn"],
         "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 8,
         "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 8,
         "sparse_config": {"kernel_size": 8, "kernel_stride": 4,
                           "init_blocks": 1, "block_size": 16,
                           "window_size": 32, "topk": 4, "dense_len": 64}}


def test_the_selection_agrees_with_a_slower_writing_of_itself():
    """``chosen_blocks`` (a softmax, a masked max, a stable sort) against
    loops over windows and blocks in numpy, for queries on both sides of
    every boundary the rule has."""
    import jax.numpy as jnp

    fam = family()
    n = fam.dims(SMALL)
    rng = np.random.default_rng(3)
    G, g, hd, T = 2, 2, 8, 150
    k = rng.standard_normal((T, G, hd)).astype(np.float32)
    for t in (64, 79, 99, 127, 149):
        q = rng.standard_normal((G, g, hd)).astype(np.float32) * 2
        J = (t + 1 - 8) // 4 + 1
        c = np.stack([k[4 * j:4 * j + 8].mean(0) for j in range(J)], 1)
        blocks = -(-(t + 1) // 16)
        got = np.asarray(fam.chosen_blocks(jnp.asarray(q), jnp.asarray(c),
                                           t, n, blocks))
        for head in range(G):
            s = np.einsum("gh,jh->gj", q[head], c[head]) / np.sqrt(hd)
            p = np.exp(s - s.max(-1, keepdims=True))
            r = (p / p.sum(-1, keepdims=True)).sum(0)
            score = np.zeros(blocks)
            for m in range(blocks):
                touching = [j for j in range(J)
                            if 4 * j < 16 * (m + 1) and 4 * j + 8 > 16 * m]
                score[m] = max((r[j] for j in touching), default=0.0)
                if m == 0 or 16 * (m + 1) > t - 32 + 1:
                    score[m] = np.inf
            want = sorted(sorted(range(blocks),
                                 key=lambda m: (-score[m], m))[:4])
            assert sorted(np.flatnonzero(got[head])) == want, (t, head)
            assert got[head].sum() == 4


def test_the_served_pytree_is_lightning_rows_beside_lone_sparse_layers():
    """The program's pytree for the file's keys: the 24 Lightning layers a
    row each under ``layers`` (what the harness's one-layer program is
    handed a row of), each sparse layer's leaves at the top level under
    ``sa<n>_``; every leaf ``param_count`` counts, and the per-slot state
    the readers price."""
    import dataclasses

    import jax

    from harness import refcheck
    from localai_tpu.models import llama as mdl
    from localai_tpu.models import minicpm_sala as sala
    from localai_tpu.models.llama import LlamaConfig

    fam = family()
    cfg = dataclasses.replace(LlamaConfig.from_hf(HF), dtype="bfloat16")
    assert type(cfg) is sala.MiniCpmSalaConfig and cfg.cache_layers == 8
    assert [(r.kind == "minicpm4", r.rows) for r in cfg.runs] == [
        (True, 1), (False, 8), (True, 1), (False, 6), (True, 2), (False, 4),
        (True, 1), (False, 6), (True, 3)]
    assert cfg.select_blocks == (64, 128, 8192)
    shapes = mdl.param_shapes(cfg)
    assert shapes["layers"]["wk"] == (24, 4096, 4096)
    assert shapes["layers"]["decay"] == (24, 32)
    assert shapes["sa7_wk"] == (4096, 256)
    assert shapes["sa0_w_ogate"] == (4096, 4096)
    assert "sa0_out_norm" not in shapes and "sa8_wq" not in shapes
    assert set(fam.SPARSE_LEAVES) == {
        k[4:] for k in shapes if k.startswith("sa0_")}
    abstract = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s, "bfloat16"), shapes,
        is_leaf=lambda x: isinstance(x, tuple))
    assert refcheck.served_param_count(abstract) == fam.param_count(HF)
    rec = jax.eval_shape(lambda: sala.init_rec(cfg, 32))
    assert rec["S"].shape == (24, 32, 32, 128, 128)
    assert rec["ck"].shape == (8, 32, 2176, 256)
    assert 2 * rec["S"].size * 4 == fam.state_bytes(HF, 32)
    # a snapshot: one slot's rows of all three
    one = sum(a.size * a.dtype.itemsize for a in rec.values()) / 32
    assert round(one / MiB, 1) == 56.5


def test_the_lightning_cell_reports_what_the_issue_names():
    """BENCHMARK.json as committed: ``sala-longdoc-decode`` is the two
    long-document cells' mix to the letter on the new configuration, 32
    callers. Of the end-to-end metrics it reports TPOT and set-up; per layer
    TPOT's and set-up's movers that name no cells, and its five readers."""
    new = spec.load_cell(CELL)
    assert new.chips == 1 and new.config_name == "minicpm-sala-9b-int8"
    for other in ("axk1-ep16-longdoc-decode", "dots3-ep16-longdoc-decode"):
        old = spec.load_cell(other)
        assert new.traffic == old.traffic
        assert new.drive["limits"] == old.drive["limits"]
        assert new.drive["ramp_s"] == old.drive["ramp_s"] == 5.0
        assert new.drive["clients"] == old.drive["clients"]
    assert new.drive["clients"] == new.max_slots == 32
    assert {m["name"] for m in new.end_to_end} == {"tpot_ms_p90", "setup_s"}
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    unlisted = {m["name"] for m in bench["per_layer"]
                if "workloads" not in m
                and m["moves"] in ("tpot_ms_p90", "setup_s")}
    assert MINE | unlisted <= {m["name"] for m in new.per_layer}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in MINE:
        assert CELL in by_name[name]["workloads"], name
        assert by_name[name]["moves"] == "tpot_ms_p90"
    assert by_name["sala.select_share"]["better"] == "lower"
    assert {by_name[n]["source"] for n in MINE} == {"device_trace",
                                                    "program_counter"}
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["traffic"], entry["chips"]) == ("longdoc-decode", 1)


def flight_row(ts, steps, live=32, program="decode", **more):
    return {"ts_unix": ts, "program": program, "steps": steps,
            "compile": False, "tokens": live * steps, "live_slots": live,
            **more}


@pytest.mark.parametrize("with_scopes", [True, False])
def test_the_five_readers_read_the_ring_and_the_scopes(with_scopes):
    """Each reader finds its operations under ITS scope and its counts in
    its columns of the ring; against a program that names no such scope and
    a ring without the columns (the parent, every other configuration) each
    returns None and raises nothing."""
    from harness.peaks import PEAKS

    fam = family()
    more = {"sparse_rows": 32} if with_scopes else {}
    rows = [flight_row(10.0 + i, 1, **more) for i in range(10)]
    rows += [flight_row(12.5, 2, program="decode_n", **(
        {"sparse_rows": 48} if with_scopes else {}))]
    states = (3, 2, 3, 1) if with_scopes else (0, 0, 0, 0)
    rows += [flight_row(11.2 + i / 10, 0, program="prefill_chunk",
                        **({"chunk_state": s} if s else {}))
             for i, s in enumerate(states)]
    a, b = ("lightning/", "sparse/") if with_scopes else ("ssm/", "dsa/")
    cell = types.SimpleNamespace(
        family=fam, published=HF, chips=1, config=CONFIG)
    ops = [("jit__decode_paged_fn", f"decode/layers/{a}state",
            "ssm_state_step", 0.06),
           ("jit__decode_paged_fn", f"decode/layers/{a}in_proj", "fusion.1",
            0.05),
           ("jit__decode_paged_fn", f"decode/layers/{b}compress/scatter",
            "fusion.2", 0.002),
           ("jit__decode_paged_fn", f"decode/layers/{b}score", "fusion.3",
            0.006),
           ("jit__decode_paged_n_fn", f"decode/layers/{b}select", "sort.1",
            0.004),
           ("jit__decode_paged_fn",
            f"decode/layers/{b}attend/attn.select_decode",
            "paged_decode_attn", 0.03),
           ("jit__decode_paged_fn", "decode/layers/mlp", "fusion.4", 0.1),
           ("jit__prefill_paged_fn", f"prefill/layers/{a}state", "fusion.5",
            0.5),
           ("jit__prefill_paged_fn", f"prefill/layers/{b}score", "fusion.6",
            0.5)]
    ctx = {"anchor": (0.0, 0.0), "cell": cell, "peak": PEAKS["TPU v5 lite"],
           "window": types.SimpleNamespace(t_open=9.0, t_close=30.0),
           "traced": {"flight": rows},
           "trace": {"start_unix": 10.0, "window_at_s": (0.0, 4.5),
                     "busy_s": 0.4, "op_rows": ops}}
    readers = {n: spec.load_reader(n) for n in sorted(MINE)}
    got = {n: read(ctx) for n, read in readers.items()}
    if not with_scopes:
        assert got == dict.fromkeys(readers)
        return
    # the slice [10, 14.5) holds rows 10 .. 14 and the two-step row: 7 steps
    assert got["lightning.state_bw_share"] == pytest.approx(
        100 * (fam.state_bytes(HF, 7 * 32) / 819e9) / 0.06)
    assert got["sala.sparse_attend_roofline"] == pytest.approx(
        100 * (fam.selected_kv_bytes(HF, 5 * 32 + 48, 2.0) / 819e9) / 0.03)
    assert got["sala.select_share"] == pytest.approx(100 * 0.012 / 0.4)
    # the window holds all eleven decode rows: 12 steps of 32, 368 sparse
    assert got["sala.sparse_rows_share"] == pytest.approx(
        100 * (10 * 32 + 48) / (12 * 32))
    # admissions: first chunks read 1 or 3; two restored, one cold
    assert got["rec.prefix_restore_share"] == pytest.approx(100 * 2 / 3)
    assert 0 < got["lightning.state_bw_share"] < 100
    assert 0 < got["sala.sparse_attend_roofline"] < 100
    for name in ("lightning.state_bw_share", "sala.sparse_attend_roofline",
                 "sala.select_share"):       # --trace 0; a voided slice
        assert readers[name]({**ctx, "trace": None}) is None
    dense = types.SimpleNamespace(
        family=family(name="llama_family"), published=HF, chips=1,
        config=CONFIG)
    for name in ("lightning.state_bw_share", "sala.sparse_attend_roofline"):
        assert readers[name]({**ctx, "cell": dense}) is None


# a model of the family at the test's size (sizes at which the selection
# bites inside a few hundred tokens), int8 weights, behind a shared document
TINY = {
    "model_type": "minicpm_sala", "vocab_size": 512, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 4,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "minicpm4"],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "lightning_scale": "1/sqrt(d)", "lightning_use_rope": True,
    "qk_norm": True, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 16,
    "use_output_gate": True, "use_output_norm": True,
    "attn_use_output_gate": True, "attn_use_rope": False,
    "max_position_embeddings": 1536, "context_size": 1536,
    "sparse_config": {"kernel_size": 8, "kernel_stride": 4, "init_blocks": 1,
                      "block_size": 16, "window_size": 64, "topk": 8,
                      "dense_len": 256},
    "engine": {"max_slots": 4, "attn_impl": "xla", "prefill_chunk": 64,
               # (room for the documents beside the check's probes: a
               # pool under pressure evicts a document's blocks, and its
               # snapshot with them)
               "kv_block_tokens": 16, "kv_num_blocks": 1024, "spec": False,
               "decode_steps_per_dispatch": 2, "dtype": "float32",
               "quantization": "int8"},
    "reference": {"epsilon": 0.006, "why": "a test"}}
MIX = {"who": "a test", "loop": "closed",
       "classes": [{"weight": 1.0,
                    "prompt_tokens": {"dist": "fixed", "value": 20},
                    "output_tokens": {"dist": "lognormal", "median": 16,
                                      "sigma": 0.4, "min": 8, "max": 32}}],
       "sampling": {"temperature": 0.8, "top_p": 0.95},
       # (longer than the check's longest probe: a shorter prompt never
       # costs a longer one its snapshot, a longer one may; and whole
       # chunks of 64, so that a prompt's last whole chunk ends in it)
       "prefix": {"share": 1.0, "pool": 2, "tokens": 1216,
                  "fill_in_setup": True}}


def test_a_lightning_hybrid_behind_a_shared_document_runs_by_files_alone(
        bench_copy, cpu_peaks, capsys, monkeypatch):
    """A small model of the family, served by the program's normal path (the
    scheduler, chunked prefill, the paged pool, per-slot state, int8
    weights) behind two shared 1216-token documents that set-up cached: the
    probes of 600 and 1100 tokens select their blocks, every admission of
    the window restores a document's state from its snapshot, and the run is
    ``correct`` by its family on the served weights."""
    import run as bench
    from conftest import add_architecture, result_line

    add_architecture(bench_copy, "tiny-sala", "minicpm_sala_family", **TINY)
    (bench_copy / "benchmark" / "traffic" / "tiny-doc.json").write_text(
        json.dumps(MIX))
    path = bench_copy / "BENCHMARK.json"
    entries = json.loads(path.read_text())
    cell = next(w for w in entries["workloads"]
                if w["name"] == "tiny-sala-closed")
    cell["traffic"] = "tiny-doc"
    for m in entries["per_layer"]:
        if m["name"] in MINE:
            m["workloads"].append("tiny-sala-closed")
    path.write_text(json.dumps(entries, indent=1))
    # the CPU's profile has no device plane: the slice reduces to nothing,
    # the device-trace readers find nothing, the ring's readers read
    from harness import trace_reduce

    import collections

    monkeypatch.setattr(
        trace_reduce, "reduce_run", lambda *a: collections.defaultdict(
            float, notes={}, breakdown={}))
    rc = bench.main(["--workload", "tiny-sala-closed", "--seed", "2147480062",
                     "--seconds", "4", "--trace", "2"], platform="cpu",
                    root=bench_copy)
    assert rc == 0
    out = result_line(capsys)
    assert out["correct"] is True and out["failed"] == 0
    run_dir = bench_copy / "benchmark" / ".run" / "tiny-sala-closed"
    check = json.loads(next(run_dir.glob("raw-*.json")).read_text())["check"]
    assert check["ok"] is True and check["positions"] == 64
    assert check["params_served"] == check["params_described"]
    got = out["metrics"]
    assert got["rec.prefix_restore_share"]["value"] == 100.0
    assert got["sala.sparse_rows_share"]["value"] == 100.0
    assert {"tpot_ms_p90", "setup_s"} <= set(got)
