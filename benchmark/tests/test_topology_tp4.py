"""The four-chip configuration's programs, compiled for a DESCRIBED v5e:2x2
with no chip attached (libtpu's compiler runs for real; nothing executes), from
the configuration FILE: the decode step, two steps in one dispatch and a
512-token prefill chunk at tensor parallel 4 fit a chip, and the file's
``hbm`` block says what the compiler says. A compiler's account, never a time.

The abstract runner is tier-1's (tests/test_tpu_compile.py ``abstract_runner``,
loaded by path): it builds the program's own ModelRunner over abstract int8
weights on the topology's devices. ISSUE 27 asked for this case in that file;
a benchmark PR may not touch it (PERF.md section 7). One process at a time may
hold libtpu: run this file alone, or with ALLOW_MULTIPLE_LIBTPU_LOAD=1 beside
another that compiles.
"""

import dataclasses
import importlib.util
import json
import sys

import pytest

from conftest import BENCH, ROOT

GIB = 2**30
HBM_BYTES = 15.75 * GIB         # one v5e chip, as libtpu reports it
CONFIG = BENCH / "configs" / "mistral-small-24b-int8-tp4.json"


@pytest.fixture(scope="module")
def compiled():
    """{program: the compiler's memory analysis} at the file's shapes."""
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc("v5e:2x2", "tpu")
    except Exception as e:  # noqa: BLE001 -- no libtpu / no topology support
        pytest.skip(f"libtpu cannot describe a v5e topology here: {e}")
    sys.path.insert(0, str(ROOT))
    spec = importlib.util.spec_from_file_location(
        "tier1_tpu_compile", ROOT / "tests" / "test_tpu_compile.py")
    t1 = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(t1)
    from localai_tpu.models.llama import LlamaConfig

    doc = json.loads(CONFIG.read_text())
    cfg = dataclasses.replace(LlamaConfig.from_hf(doc), dtype="bfloat16")
    eng = doc["engine"]
    with pytest.MonkeyPatch.context() as mp:
        r, a = t1.abstract_runner(
            topo, mp, cfg, tp=doc["sharding"]["tensor_parallel_size"],
            num_slots=eng["max_slots"], max_ctx=doc["context_size"],
            kv_num_blocks=eng["kv_num_blocks"], kv_block_tokens=64)
        assert r.overlap_mode       # the manual-TP trunk, as on the chip
        shard = a["kv"].k.sharding.shard_shape(a["kv"].k.shape)
        base = (a["params"], a["kv"], a["state"])
        programs = {
            "decode": t1.compile_program(r._decode_paged_fn, *base,
                                         a["tables"]),
            "decode_paged_n": t1.compile_program(
                r._decode_paged_n_fn, *base, a["tables"], n=2),
            "prefill_chunk_512": t1.compile_program(
                r._prefill_paged_fn, *base, *a["chunk"](512), bucket=512,
                sample=True),
        }
    return doc, shard, {k: c.memory_analysis() for k, c in programs.items()}


def test_the_24b_programs_fit_a_chip_at_tp4(compiled):
    doc, shard, mem = compiled
    # a chip holds 2 of the 8 kv heads of every layer and block
    assert shard == (40, doc["engine"]["kv_num_blocks"], 2, 64, 128)
    need = {k: (m.argument_size_in_bytes + m.temp_size_in_bytes
                + m.output_size_in_bytes - m.alias_size_in_bytes
                + m.generated_code_size_in_bytes) for k, m in mem.items()}
    worst = max(need, key=need.get)
    assert need[worst] < HBM_BYTES
    hbm = doc["hbm"]
    # the file's arithmetic is the compiler's, to a hundredth of a GiB
    args = max(m.argument_size_in_bytes for m in mem.values()) / GIB
    assert args == pytest.approx(hbm["arguments_gib_per_chip"], abs=0.01)
    assert need[worst] / GIB == pytest.approx(
        hbm["largest_program_gib_per_chip"], abs=0.01)
    # no program holds a second pool (PR 26): temps are far under its 1.57
    assert max(m.temp_size_in_bytes for m in mem.values()) < 0.5 * GIB
