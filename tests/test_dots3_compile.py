"""The topology compile of ``dots3-ep16-longdoc-decode``'s programs (libtpu
compiles for a described v5e with no chip: tests/test_tpu_compile.py has the
helpers and the other cells' cases). A file of its own so that these four
compiles run beside that file's two hundred and not behind them: the suite's
last worker is the one that holds it."""

import numpy as np
import pytest

from test_tpu_compile import (  # noqa: F401 — ``topo`` and ``cell`` are
    HBM_BYTES, abstract_runner, bf16, cell, compile_cell_program,  # fixtures
    topo)

DOTS3 = "dots3-note-ep16"


@pytest.mark.parametrize("cell", [DOTS3], indirect=True)
@pytest.mark.parametrize("program", ["decode", "prefill_chunk_128_sample",
                                     "prefill_chunk_512",
                                     "prefill_chunk_512_sample"])
def test_sparse_latent_cell_programs_fit_one_chip(topo, monkeypatch, cell,
                                                  program):
    """PR 51: the configuration FILE of the stack with an indexer (F(dense) F
    S S S F at the published widths, 16 held experts of 5120 x 1536 a layer,
    bfloat16 weights, a 3072-block pool of THREE arrays: full rows in 640
    lanes, window rows in 1152, index keys in 128; 34816 positions) compiles
    for one v5e chip and fits it, with the numbers its ``hbm`` block
    restates. The one Pallas call of its programs is ``moe_experts`` (the
    lone layer's and the period's, the stacked leaves its operands whole);
    the attends are XLA under ``attn.index`` / ``attn.select`` /
    ``attn.sparse_decode`` / ``attn.latent_window`` in a decode step and
    under ``attn.latent_chunk`` (with ``attn.index`` / ``attn.select`` /
    ``attn.sparse_chunk`` inside: a chunk's full layers gather the rows
    their queries chose and rebuild no key) / ``attn.latent_window`` (whose
    walk does: ``mla/kv_b``) in a chunk. The pool's arrays are the scan's
    carry: no pool-shaped temp."""
    cfg, doc = cell
    eng = doc["engine"]
    assert cfg.latent and cfg.routed and not cfg.recurrent
    assert [s[1:] for s in cfg.latent_states] == [(3, 576), (3, 1088),
                                                  (3, 128)]
    r, a = abstract_runner(
        topo, monkeypatch, cfg, quantization="",
        num_slots=eng["max_slots"], max_ctx=doc["context_size"],
        kv_num_blocks=eng["kv_num_blocks"], kv_block_tokens=64)
    N = eng["kv_num_blocks"]
    assert [x.shape for x in a["kv"].stacked()] == [
        (3, N, 64, 640), (3, N, 64, 1152), (3, N, 64, 128)]
    assert a["kv"].c.dtype == bf16 and r.max_blocks == 544
    c = compile_cell_program(r, a, program)
    text = c.as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert calls and all("moe/experts/moe_experts" in ln for ln in calls)
    assert "bf16[1,4,16,5120,1536]" in text
    if program == "decode":
        for scope in ("attn.index", "attn.select", "attn.sparse_decode",
                      "attn.latent_window", "mla/gate", "dsa/q", "dsa/k"):
            assert scope in text, scope
    else:
        for scope in ("attn.latent_chunk", "attn.index", "attn.select",
                      "attn.sparse_chunk", "attn.dense_chunk",
                      "attn.latent_window", "mla/kv_b"):
            assert scope in text, scope
        assert not [ln for ln in text.splitlines()
                    if "attn.latent_chunk" in ln and "mla/kv_b" in ln]
    for staged in ("bf16[16,5120,1536]", "bf16[16,1536,5120]"):
        assert staged not in text
    m = c.memory_analysis()
    pool_bytes = sum(int(np.prod(x.shape)) * 2 for x in a["kv"].stacked())
    need = (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes
            + m.generated_code_size_in_bytes)
    print(f"HBM {program}: arguments {m.argument_size_in_bytes / 2**30:.3f} "
          f"temp {m.temp_size_in_bytes / 2**30:.4f} in all "
          f"{need / 2**30:.3f} GiB")
    # no second copy of the smallest of the pool's arrays' sum
    assert m.temp_size_in_bytes < pool_bytes / 2
    hbm = doc["hbm"]
    # (a chunk that samples nothing takes no head: 0.18 GiB fewer)
    assert (hbm["arguments_gib"] - 0.2 < m.argument_size_in_bytes / 2**30
            <= hbm["arguments_gib"] + 0.005)
    assert need / 2**30 <= hbm["largest_program_gib"] + 0.001
    assert 0.25 * HBM_BYTES < need < HBM_BYTES
