"""The topology compile of ``fh1-34b-decode``'s programs (libtpu compiles for
a described v5e with no chip: tests/test_tpu_compile.py has the helpers and
the other cells' cases). A file of its own, as tests/test_dots3_compile.py
is: these two compiles (~45 s) run beside that file's two hundred and not
behind them; the suite's last worker is the one that holds it."""

import numpy as np
import pytest

from test_tpu_compile import (  # noqa: F401 — ``topo`` and ``cell`` are
    HBM_BYTES, abstract_runner, bf16, cell, compile_cell_program,  # fixtures
    f32, i8, topo)

FH1 = "falcon-h1-34b-int8"


@pytest.mark.parametrize("cell", [FH1], indirect=True)
@pytest.mark.parametrize("program", ["decode", "prefill_chunk_512_sample"])
def test_state_space_cell_programs_fit_one_chip(topo, monkeypatch, cell,
                                                program):
    """PR 55: the configuration FILE of the state-space hybrid (12 layers of
    a Mamba-2 mixer beside grouped-query attention and a 21504-wide MLP,
    int8 weights, 64 slots of float32 state beside a 12 x 1281-block pool)
    compiles for one v5e chip and fits it, with the numbers its ``hbm`` block
    restates. Pool and state are the layer scan's carry, written in place: of
    a decode program Mosaic compiled the paged kernel and the mixer's step
    (ops/gdn.py's kernel without the delta correction, ``ssm_state_step``:
    once in the rolled scan's body, the carried state its operand WHOLE and
    its aliased result), so nothing slices a layer's state (256 MiB) out of
    the carry or lays it back; a chunk's recurrence (the chunked SSD form)
    and the conv are XLA on ONE slot's rows."""
    cfg, doc = cell
    eng = doc["engine"]
    assert cfg.recurrent and not cfg.routed and eng["quantization"] == "int8"
    r, a = abstract_runner(
        topo, monkeypatch, cfg, quantization="int8",
        num_slots=eng["max_slots"], max_ctx=doc["context_size"],
        kv_num_blocks=eng["kv_num_blocks"], kv_block_tokens=64)
    pool = a["kv"].k.shape
    assert pool == (12, 1281, 4, 64, 128) and a["kv"].k.dtype == bf16
    state = a["state"].rec["S"]
    assert state.shape == (12, 64, 32, 256, 128) and state.dtype == f32
    assert a["state"].rec["conv"].shape == (12, 64, 3, 5120)
    assert a["params"]["layers"]["ssm_in"].q.dtype == i8
    assert a["params"]["layers"]["ssm_conv"].dtype == bf16
    c = compile_cell_program(r, a, program)
    text = c.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    steps = [ln for ln in calls if "ssm/state/ssm_state_step" in ln]
    rest = [ln for ln in calls if ln not in steps]
    carried = "f32[12,64,32,256,128]"
    if program == "decode":
        assert len(rest) == 1 and "paged_decode_attn" in rest[0]
        assert len(steps) == 1
        results, operands = steps[0].split(" custom-call(")
        assert results.split("= (")[1].startswith(carried)
        assert "output_to_operand_aliasing={{0}: (1, {})}" in steps[0]
        # no layer's state sliced out of the carry or laid back
        assert "f32[64,32,256,128]" not in text
        assert not [ln for ln in text.splitlines()
                    if f"= {carried}" in ln and "dynamic-update-slice(" in ln]
    else:
        assert not calls
    m = c.memory_analysis()
    state_bytes = int(np.prod(state.shape)) * 4
    assert m.temp_size_in_bytes < state_bytes / 4, (
        f"{program}: temp {m.temp_size_in_bytes / 2**20:.0f} MiB holds a "
        f"second state or a layer's weights in bfloat16")
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)
    print(f"HBM {program}: arguments {m.argument_size_in_bytes / 2**30:.3f} "
          f"temp {m.temp_size_in_bytes / 2**30:.4f} in all "
          f"{need / 2**30:.3f} GiB")
    hbm = doc["hbm"]
    assert (hbm["arguments_gib"] - 0.01 < m.argument_size_in_bytes / 2**30
            <= hbm["arguments_gib"] + 0.005)
    assert need / 2**30 <= hbm["largest_program_gib"] + 0.001
    # over the floor a new cell is held to (a quarter of the chip), and with
    # room for the reference check's float32 layer (1.6 GiB) beside it
    assert 0.25 * HBM_BYTES < need < HBM_BYTES - 1.7 * 2**30


# the parent's ``decode`` program of this cell (topology compile, PR 63, the
# parent 9df3b47 under this installation): what the sampler's front stage
# may add to
PARENT_DECODE = {"temp_bytes": 172988416, "code_bytes": 12248576}


@pytest.mark.parametrize("cell", [FH1], indirect=True)
def test_wide_vocabulary_decode_program_takes_the_front_stage(
        topo, monkeypatch, cell):
    """PR 63: 64 slots x 261120 columns are 16320 chunks a row, over
    ``sampling.TILE_FROM``, so the cell's ``decode`` program holds the front
    stage (``sample/tile_max``: the penalized block written as int32 keys
    as it lies, and the 2040 tiles' maxima a row; ``sample/tile_topk``: their
    sort and the gather of 256 tiles) and the two older stages over the gathered
    ``[64, 32768]``: no ``sort`` and no ``TopK`` holds anything as wide as
    the ``[64, 16320]`` maxima the parent sorted, nothing copies the block
    into another order, and temps and generated code stand within the
    stage's own buffers of the parent's (REVIEW 45: a sort unrolls into its
    code; the gathered block and its keys are 8 MiB each)."""
    import re

    from localai_tpu.engine import sampling as smp

    cfg, doc = cell
    eng = doc["engine"]
    S, V = eng["max_slots"], cfg.vocab_size
    assert -(-V // smp.TOPK_CHUNK) > smp.TILE_FROM and S % 8 == 0
    r, a = abstract_runner(
        topo, monkeypatch, cfg, quantization="int8", num_slots=S,
        max_ctx=doc["context_size"], kv_num_blocks=eng["kv_num_blocks"],
        kv_block_tokens=64)
    c = compile_cell_program(r, a, "decode")
    text = c.as_text()
    for scope in ("tile_max", "tile_topk", "chunk_max", "topk"):
        assert f"/sample/{scope}/" in text, scope

    def widest(line):
        return max((int(np.prod([int(d) for d in dims.split(",")]))
                    for dims in re.findall(r"\[(\d+(?:,\d+)*)\]", line)),
                   default=1)

    sorts = [ln.strip() for ln in text.splitlines() if re.search(
        r' sort\(|custom_call_target="TopK"', ln)]
    assert len(sorts) == 5 and all("/sample/" in ln for ln in sorts)
    for ln in sorts:
        assert widest(ln.split(", metadata=")[0]) < S * (
            V // smp.TOPK_CHUNK), ln[:200]
    # the block is reduced and gathered as it lies: no copy of [64, 261120]
    block = S * V
    copies = [ln.strip() for ln in text.splitlines()
              if re.search(r" (copy|transpose)\(", ln)
              and widest(ln.split(", metadata=")[0]) >= block]
    assert not copies, [ln[:160] for ln in copies]
    m = c.memory_analysis()
    print(f"decode: temp {m.temp_size_in_bytes} code "
          f"{m.generated_code_size_in_bytes}")
    gathered = S * smp.MAX_TOPK * smp.TOPK_TILE * 4
    assert m.temp_size_in_bytes <= PARENT_DECODE["temp_bytes"] + 2 * gathered
    assert m.generated_code_size_in_bytes <= (
        PARENT_DECODE["code_bytes"] + 2**20)
