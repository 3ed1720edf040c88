"""The topology compile of ``sala-longdoc-decode``'s programs (libtpu
compiles for a described v5e with no chip: tests/test_tpu_compile.py has the
helpers and the other cells' cases). A file of its own, as
tests/test_falcon_h1_compile.py is: these three compiles (~35 s each) run
beside that file's and not behind them."""

import numpy as np
import pytest

from test_tpu_compile import (  # noqa: F401 — ``topo`` and ``cell`` are
    HBM_BYTES, abstract_runner, bf16, cell, compile_cell_program,  # fixtures
    f32, i8, topo)

SALA = "minicpm-sala-9b-int8"


@pytest.mark.parametrize("cell", [SALA], indirect=True)
@pytest.mark.parametrize("program", ["decode", "prefill_chunk_128_sample",
                                     "prefill_chunk_512"])
def test_lightning_hybrid_cell_programs_fit_one_chip(topo, monkeypatch, cell,
                                                     program):
    """PR 62: the configuration FILE of the Lightning / block-sparse hybrid
    (ALL 32 layers in int8, 32 slots of float32 Lightning state and
    compressed keys beside an 8 x 3072-block pool) compiles for one v5e chip
    and fits it, with the numbers its ``hbm`` block restates. Pool and state
    are the programs' carries, written in place: of a decode program Mosaic
    compiled the paged kernel once a sparse layer (eight pieces of program:
    the compacted tables, rows = (stream, K/V head) over the pool read as
    one-head blocks) and the Lightning step once a run of Lightning layers
    (ops/gdn.py's kernel without the delta correction, the carried state its
    operand WHOLE and its aliased result); no program restages the state,
    the compressed keys or a layer of the pool, and none holds a layer's
    weights a second time. A chunk's recurrence (the chunked form) and its
    block-masked span attend are XLA."""
    cfg, doc = cell
    eng = doc["engine"]
    assert cfg.recurrent and not cfg.routed and eng["quantization"] == "int8"
    r, a = abstract_runner(
        topo, monkeypatch, cfg, quantization="int8",
        num_slots=eng["max_slots"], max_ctx=doc["context_size"],
        kv_num_blocks=eng["kv_num_blocks"], kv_block_tokens=64)
    pool = a["kv"].k.shape
    assert pool == (8, 3072, 2, 64, 128) and a["kv"].k.dtype == bf16
    rec = a["state"].rec
    assert rec["S"].shape == (24, 32, 32, 128, 128) and rec["S"].dtype == f32
    assert rec["ck"].shape == (8, 32, 2176, 256) and rec["ck"].dtype == bf16
    assert rec["seg"].shape == (8, 32, 2, 2, 128)
    assert r.allocator.snapshots == 4
    assert a["params"]["layers"]["wk"].q.dtype == i8
    assert a["params"]["sa7_w_down"].q.shape == (16384, 4096)
    assert a["params"]["sa7_w_down"].q.dtype == i8
    assert a["params"]["layers"]["decay"].dtype == f32
    c = compile_cell_program(r, a, program)
    text = c.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    steps = [ln for ln in calls if "lightning/state/ssm_state_step" in ln]
    attends = [ln for ln in calls if "sparse/attend" in ln]
    carried = "f32[24,32,32,128,128]"
    if program == "decode":
        assert len(steps) == 4 and len(attends) == 8
        assert len(calls) == 12
        for ln in attends:
            assert "paged_decode_attn" in ln
        for ln in steps:
            results, _ = ln.split(" custom-call(")
            assert results.split("= (")[1].startswith(carried)
            assert "output_to_operand_aliasing={{0}: (1, {})}" in ln
    else:
        assert not calls
    lines = text.splitlines()
    # nothing restages the state, the compressed keys or the pool ...
    for whole in (carried, "bf16[8,32,2176,256]", "bf16[8,3072,2,64,128]"):
        assert not [ln for ln in lines
                    if f"= {whole}" in ln and " copy(" in ln], whole
    # ... nor a slot-layer of either (a decode step reads them where they
    # lie), nor a layer's largest weight
    for part in ("f32[32,32,128,128]", "bf16[32,2176,256]",
                 "s8[4096,16384]", "bf16[4096,16384]"):
        if program != "decode" and part.startswith(("f32[32", "bf16[32")):
            continue
        assert not [ln for ln in lines
                    if f"= {part}" in ln and " copy(" in ln], part
    m = c.memory_analysis()
    state_bytes = int(np.prod(rec["S"].shape)) * 4
    assert m.temp_size_in_bytes < state_bytes / 2, (
        f"{program}: temp {m.temp_size_in_bytes / 2**20:.0f} MiB holds a "
        f"second state or a layer's weights in bfloat16")
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)
    print(f"HBM {program}: arguments {m.argument_size_in_bytes / 2**30:.3f} "
          f"temp {m.temp_size_in_bytes / 2**30:.4f} in all "
          f"{need / 2**30:.3f} GiB")
    hbm = doc["hbm"]
    if program != "prefill_chunk_512":      # (a chunk that does not sample
        # is not handed the sampling state's counts and bias)
        assert (hbm["arguments_gib"] - 0.01
                < m.argument_size_in_bytes / 2**30
                <= hbm["arguments_gib"] + 0.005)
    assert need / 2**30 <= hbm["largest_program_gib"] + 0.001
    # over the floor a new cell is held to (a quarter of the chip), and with
    # room for the reference check's float32 layer (1.1 GiB) beside it
    assert 0.25 * HBM_BYTES < need < HBM_BYTES - 1.2 * 2**30
