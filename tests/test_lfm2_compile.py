"""The topology compile of ``lfm2-pp2-decode``'s programs and kernels (libtpu
compiles for a described v5e with no chip: tests/test_tpu_compile.py has the
helpers and the other cells' cases). A file of its own, as
tests/test_falcon_h1_compile.py is: these compiles run beside that file's two
hundred and not behind them."""

import pytest

from test_tpu_compile import (  # noqa: F401 — ``topo`` and ``cell`` are
    HBM_BYTES, abstract_runner, assert_in_place, assert_who_writes,  # fixtures
    bf16, cell, compile_cell_program, compile_for, f32, i32, spans, topo)

LFM2 = "lfm2-8b-a1b-pp2"


@pytest.mark.parametrize("cell", [LFM2], indirect=True)
@pytest.mark.parametrize("program", ["decode", "prefill_chunk_512_sample"])
def test_short_conv_cell_programs_fit_one_chip(topo, monkeypatch, cell,
                                               program):
    """PR 57: the configuration FILE of the convolution hybrid (2 dense and
    12 expert layers at every published width, bfloat16, every one of a
    layer's 32 experts, the file's slots of two convolution rows beside a
    3-layer pool of 20 blocks a slot of PACKED 64-wide heads) compiles for
    one v5e chip under
    ``attn_impl: auto`` and fits it, with the numbers its ``hbm`` block
    restates. Of a decode program Mosaic compiled the paged kernel (once:
    the attention layers are the rolled scan's) and the grouped expert
    kernel (once a place in the row); a chunk holds the expert kernel alone
    (its attend is the span gather, every cell's)."""
    cfg, doc = cell
    eng = doc["engine"]
    assert cfg.recurrent and cfg.routed and not eng.get("quantization")
    assert (cfg.num_kv_heads, cfg.hd, cfg.kv_pack) == (4, 128, 2)
    assert (cfg.attn_kv_heads, cfg.attn_hd) == (8, 64)
    r, a = abstract_runner(
        topo, monkeypatch, cfg, quantization="",
        num_slots=eng["max_slots"], max_ctx=doc["context_size"],
        kv_num_blocks=eng["kv_num_blocks"], kv_block_tokens=64)
    slots = eng["max_slots"]
    assert eng["kv_num_blocks"] == 20 * slots + 1
    assert a["kv"].k.shape == (3, 20 * slots + 1, 4, 64, 128)
    assert a["kv"].k.dtype == bf16
    rec = a["state"].rec
    assert sorted(rec) == ["conv", "routed"]
    assert rec["conv"].shape == (11, slots, 2, 2048)
    assert rec["conv"].dtype == bf16
    assert a["params"]["layers"]["w_gate"].shape == (3, 4, 32, 2048, 1792)
    assert a["params"]["layers"]["expert_bias"].dtype == f32
    assert "lm_head" not in a["params"]         # tied
    c = compile_cell_program(r, a, program)
    text = c.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    experts = [ln for ln in calls if "moe/experts/moe_experts" in ln]
    rest = [ln for ln in calls if ln not in experts]
    assert len(experts) == 4            # a place in the row a c c c
    if program == "decode":
        assert len(rest) == 1 and "paged_decode_attn" in rest[0]
    else:
        assert not rest
    m = c.memory_analysis()
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)
    print(f"HBM {program}: arguments {m.argument_size_in_bytes / 2**30:.3f} "
          f"temp {m.temp_size_in_bytes / 2**30:.4f} in all "
          f"{need / 2**30:.3f} GiB")
    # no second copy of a layer's experts (0.66 GiB) among the temps
    assert m.temp_size_in_bytes < 0.6 * 2**30
    hbm = doc["hbm"]
    assert (hbm["arguments_gib"] - 0.01 < m.argument_size_in_bytes / 2**30
            <= hbm["arguments_gib"] + 0.005)
    assert need / 2**30 <= hbm["largest_program_gib"] + 0.001
    # over the floor a new cell is held to (a quarter of the chip), and with
    # room for the reference check's float32 experts and activations
    assert 0.25 * HBM_BYTES < need < HBM_BYTES - 2.0 * 2**30


@pytest.mark.parametrize("cell", [LFM2], indirect=True)
def test_a_last_chunk_rides_the_step_in_one_program(topo, monkeypatch, cell):
    """PR 61: at the cell's served widths (96 slots, bucket 128) a prompt's
    last chunk and the decode step are ONE program through the family's own
    forward (``_decode_prefill_paged_fn``, ``models.lfm2.forward(ride=128)``)
    and it is held to what PR 59's is for the dense stack
    (tests/test_tpu_compile.py): no second pool and no layer of it copied,
    the chunk's scatters and ONE conditional over its spans, ONE paged
    kernel call that writes the step's rows; and to what this family's
    programs are: the grouped expert kernel once a place in the row, over
    the 224 rows of both halves (ONE row tile: every touched expert is read
    once for the chunk and the step), ``in_proj`` and ``out_proj`` over 224
    rows and over no half alone, no second copy of a layer's experts (0.66
    GiB) or of any weight stack among the temps. Its arguments are the
    decode step's and its need stands beside the step's 9.481 GiB and the
    512 chunk's 9.605, under the file's ``largest_program_gib``."""
    import re

    from localai_tpu.ops import moe

    cfg, doc = cell
    eng = doc["engine"]
    slots = eng["max_slots"]
    r, a = abstract_runner(
        topo, monkeypatch, cfg, quantization="", num_slots=slots,
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    assert r.rides and r.own_forward
    rows = 128 + slots
    assert rows <= moe.ROW_TILE and rows % 16 == 0
    pool = a["kv"].k.shape
    need, temps, texts = {}, {}, {}
    for program in ("decode_prefill_128", "prefill_chunk_128_sample",
                    "decode"):
        c = compile_cell_program(r, a, program)
        texts[program] = c.as_text()
        m = c.memory_analysis()
        temps[program] = m.temp_size_in_bytes
        need[program] = (m.argument_size_in_bytes + m.temp_size_in_bytes
                         + m.output_size_in_bytes - m.alias_size_in_bytes
                         + m.generated_code_size_in_bytes)
        if program == "decode_prefill_128":
            ride, args = c, m.argument_size_in_bytes
    print({k: f"need {need[k] / 2**30:.3f} GiB temp {v / 2**20:.2f} MiB"
           for k, v in temps.items()},
          f"ride arguments {args / 2**30:.3f} GiB")
    assert_in_place("decode_prefill_128", ride, pool)
    text = texts["decode_prefill_128"]
    assert_who_writes("decode_prefill_128", text, pool,
                      spans(r, "decode_prefill_128"))
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    experts = [ln for ln in calls if "moe/experts/moe_experts" in ln]
    assert len(experts) == 4 and len(calls) == 5    # a place in the row
    for ln in experts:                              # both halves' rows
        assert ln.split(" custom-call(")[0].count(f"f32[{rows},2048]") == 1
    # the convolution's two products run every row once, no half alone
    D = cfg.hidden_size
    assert re.search(rf"bf16\[(1,)?{rows},{3 * D}\]", text)
    assert not re.search(rf"bf16\[(1,)?(128|{slots})(,1)?,{3 * D}\]", text)
    # rows of activations, no weight stack staged, and no leaf's matrix
    # copied on its way to a product that its two halves' own programs do
    # not copy (A16's lesson: a copy of a weight's shape is a read of it)
    import jax

    matrices = {leaf.shape[-2:] for leaf in jax.tree.leaves(a["params"])
                if leaf.ndim >= 2 and leaf.shape[-2] * leaf.shape[-1] >= 2**20}
    assert (2048, 6144) in matrices and (2048, 1792) in matrices
    def copied(text):
        return sorted(
            ln.split(" = ")[1].split("{")[0].lstrip("(")
            for ln in text.splitlines()
            if re.search(r" copy(-start|-done)?\(", ln) and any(
                re.search(rf"\[([0-9]+,)*{m},{n}\]", ln.split(" copy")[0])
                for m, n in matrices))

    # what XLA prefetches of the dense prefix's two layers (one row, so no
    # scan: ``bf16[1,2,2048,...]``, in the step's and the chunk's programs
    # alike) and nothing else: no expert's matrix, no leaf of the scanned runs
    assert copied(text) == copied(texts["prefill_chunk_128_sample"])
    assert set(copied(texts["decode"])) < set(copied(text)) == {
        "bf16[1,2,2048,2048]", "bf16[1,2,2048,6144]", "bf16[1,2,2048,7168]"}
    assert temps["decode_prefill_128"] < (
        temps["prefill_chunk_128_sample"] + temps["decode"] + 16 * 2**20)
    assert temps["decode_prefill_128"] < 0.6 * 2**30
    hbm = doc["hbm"]
    assert (hbm["arguments_gib"] - 0.01 < args / 2**30
            <= hbm["arguments_gib"] + 0.005)
    assert need["decode_prefill_128"] / 2**30 <= (
        hbm["largest_program_gib"] + 0.001)


def test_expert_kernel_compiles_at_f_in_two_tiles(topo):
    """``ops/moe.py moe_experts`` at the published D 2048, F 1792: an
    expert's three matrices are 22 MB, over ``BLOCK_BYTES`` twice-buffered,
    so ``f_tile`` walks F in two tiles of 896; at 64, 96 (the cell's) and 128
    rows (a decode step), 224 (a chunk of 128 that rides the cell's step)
    and 512 (a chunk)."""
    from localai_tpu.ops import moe

    assert moe.f_tile(2048, 1792, 2) == 896

    def experts(h, weights, order, n_touched, w_gate, w_up, w_down, p, m):
        return moe.moe_experts(h, weights, order, n_touched,
                               (w_gate, w_up, w_down), p, m)

    up, down = (3, 4, 32, 2048, 1792), (3, 4, 32, 1792, 2048)
    for rows in (64, 96, 128, 224, 512):
        c = compile_for(
            topo, experts, ((rows, 2048), bf16), ((rows, 32), f32),
            ((32,), i32), ((), i32), (up, bf16), (up, bf16), (down, bf16),
            ((), i32), ((), i32))
        assert "moe_experts" in c.as_text()


def test_paged_kernel_compiles_at_packed_heads(topo):
    """``paged_decode_attention`` at the pool the packed heads make: 32
    query heads over 4 K/V rows of 128 lanes (two 64-wide heads each), the
    cell's 96 slots, blocks of 64."""
    from localai_tpu import ops
    from localai_tpu.ops.attention import heads_per_row

    assert heads_per_row(8, 64) == 2 and heads_per_row(7, 64) == 1
    assert heads_per_row(8, 96) == 1 and heads_per_row(8, 128) == 1
    assert ops.select_paged_attn_impl(
        "auto", num_heads=32, num_kv_heads=4, head_dim=128, block_tokens=64,
        backend="tpu") == ("pallas", False)
    pool = ((3, 1921, 4, 64, 128), bf16)
    c = compile_for(topo, ops.paged_decode_attention, ((96, 32, 128), bf16),
                    pool, pool, ((), i32), ((96, 64), i32), ((96,), i32))
    assert "paged_decode_attn" in c.as_text()
