"""The order in which layers run is the family's (harness/spec.py's optional
names): ``walk`` and ``cache_layers``. Each is stated by NEW files alone, in a
temporary copy of the benchmark: the test-data families under
``data/reference/`` are no model's. The reference is compared with the
forward composed by hand here, row by row, from the family's own
``decoder_layer``; a family without a walk
with the loop the harness had before it had walks, written out. And the
control fails: a walk that runs the stack twice, against a server that runs
it once, is not ``correct``."""

import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as bench
from conftest import DATA, add_architecture, result_line
from harness import refcheck, spec

HF = {"vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
      "num_hidden_layers": 3, "num_attention_heads": 4,
      "num_key_value_heads": 2, "max_position_embeddings": 512,
      "rope_theta": 10000.0, "rms_norm_eps": 1e-5}
LAST = 4
TOKENS = np.random.default_rng(7).integers(32, 127, (2, 24)).astype(np.int32)


def served_params(hf: dict) -> dict:
    """The program's own seeded int8 weights at these keys: the pytree the
    reference check is handed on the chip."""
    from localai_tpu.models.llama import LlamaConfig
    from localai_tpu.models.registry import synthetic_params

    cfg = dataclasses.replace(LlamaConfig.from_hf(hf), dtype="float32")
    return synthetic_params(cfg, "int8", seed=0)


@pytest.fixture(scope="module")
def params():
    return served_params(HF)


def family(root, name: str):
    """A family of the copy, found as a configuration file would name it."""
    return spec.load_family(spec.family_file(
        {"reference": {"family": name}}, "a test", root))


def f32(leaf) -> np.ndarray:
    """A whole served leaf dequantised, by hand."""
    if not hasattr(leaf, "q"):
        return np.asarray(leaf, np.float32)
    return (np.asarray(leaf.q, np.float32)
            * np.expand_dims(np.asarray(leaf.scale, np.float32), leaf.axis))


def by_hand(fam, params: dict, hf: dict, steps: list) -> np.ndarray:
    """The forward composed step by step, one sequence at a time, nothing
    jitted: ``steps`` are rows of the stack or "norm" (the final norm)."""
    cos, sin = fam.rope_tables(hf, TOKENS.shape[1])
    final_norm = f32(params["final_norm"])
    out = []
    for x in f32(params["embed"])[TOKENS]:
        for step in steps:
            if step == "norm":
                x = fam.rms_norm(x, final_norm, fam.norm_eps(hf))
                continue
            w = jax.tree_util.tree_map(
                lambda leaf: f32(leaf)[step], params["layers"],
                is_leaf=refcheck._quantised)
            x = fam.decoder_layer(x, w, cos, sin, hf)
        out.append(fam.logits(x[-LAST:], final_norm,
                              f32(params["lm_head"])[:, refcheck.LETTERS],
                              hf))
    return np.stack(out)


def recording(fam, calls: list):
    """The family with every row its walk asks of the harness recorded."""
    def walk(x, layer, rows, leaf, hf):
        def asked(x, index):
            calls.append(index)
            return layer(x, index)
        return fam.walk(x, asked, rows, leaf, hf)

    return types.SimpleNamespace(**{**vars(fam), "walk": walk})


def test_the_layers_run_in_the_order_the_walk_gives(bench_copy, params):
    """Twice through the stack, the final norm between: what the harness
    runs is what the walk asked for, and equals the forward by hand."""
    twice = family(bench_copy, "twice_family")
    calls: list = []
    got = refcheck.reference_logits(params, recording(twice, calls), HF,
                                    TOKENS, LAST)
    once = [0, 1, 2]
    assert calls == once + once
    np.testing.assert_allclose(
        got, by_hand(twice, params, HF, once + ["norm"] + once),
        rtol=1e-4, atol=1e-5)
    # and it is another model than the stack run once
    plain = refcheck.reference_logits(
        params, family(bench_copy, "llama_family"), HF, TOKENS, LAST)
    assert np.abs(got - plain).max() > 1e-3


def test_a_family_without_a_walk_gets_the_loop_it_had(bench_copy, params):
    """Bit for bit: the parent's loop, written out over the same three
    programs; a walk that spells that order out gives the same bits."""
    llama = family(bench_copy, "llama_family")
    assert not hasattr(llama, "walk")
    embed, layer, logits = (jax.jit(f) for f in refcheck.programs(
        llama, HF, TOKENS.shape[1]))
    n_layers = jax.tree_util.tree_leaves(params["layers"])[0].shape[0]
    with jax.default_matmul_precision("highest"):
        x = embed(params, jnp.asarray(TOKENS, jnp.int32))
        for index in range(n_layers):
            x = layer(x, params["layers"], jnp.int32(index))
        parent = np.asarray(logits(params, x[:, -LAST:]), np.float32)
    got = refcheck.reference_logits(params, llama, HF, TOKENS, LAST)
    assert n_layers == 3 and np.array_equal(got, parent)
    spelled = family(bench_copy, "spelled_family")
    assert np.array_equal(refcheck.reference_logits(
        params, spelled, HF, TOKENS, LAST), parent)
    np.testing.assert_allclose(
        got, by_hand(llama, params, HF, [0, 1, 2]), rtol=1e-4, atol=1e-5)


def test_a_row_the_model_does_not_hold_is_an_error(bench_copy, params):
    """A traced index out of range would be clamped in silence."""
    spelled = family(bench_copy, "spelled_family")
    beyond = types.SimpleNamespace(**{
        **vars(spelled), "walk": lambda x, layer, rows, leaf, hf:
        layer(x, rows)})
    with pytest.raises(IndexError, match="row 3; the served model holds 3"):
        refcheck.reference_logits(params, beyond, HF, TOKENS, LAST)


def load_pool_dims(root):
    return spec.load_reader("runner.kv_move_share", root).__globals__[
        "pool_dims"]


@pytest.mark.parametrize("name, layers", [
    ("llama_family", 2), ("spelled_family", 2), ("twice_family", 4)])
def test_the_pools_leading_dimension_is_the_familys(bench_copy, name,
                                                    layers):
    """``cache_layers`` changes the shape the reader looks for (a cache entry
    a pass and layer); a family without it keeps the published depth."""
    add_architecture(bench_copy, "tiny-walk", name, engine={
        "max_slots": 4, "kv_num_blocks": 9, "kv_block_tokens": 16})
    cell = spec.load_cell("tiny-walk-closed", bench_copy)
    assert hasattr(cell.family, "cache_layers") == (name == "twice_family")
    # tiny: 2 layers, 2 kv heads of head_dim 16
    one = (2, 9, 16, 16)
    assert load_pool_dims(bench_copy)(cell) == {
        tuple(sorted(one)), tuple(sorted((layers,) + one)),
        tuple(sorted((1,) + one))}


def test_an_optional_name_that_is_no_function_is_an_error(bench_copy):
    (bench_copy / "benchmark" / "reference" / "flat_family.py").write_text(
        "".join(f"from reference.llama_family import {n}\n"
                  for n in spec.FAMILY_CONTRACT)
        + "cache_layers = 4\n")
    with pytest.raises(spec.SpecError, match=r"flat_family.py.*cache_layers"):
        spec.family_file({"reference": {"family": "flat_family"}}, "a test",
                         bench_copy)


# wide and deep enough that a second pass over the stack moves the logits
# (the generator's weights have amplitude 0.02 whatever the width: at the tiny
# configuration's 64 x 2 layers a second pass, which starts from a normalised
# x, flips no letter of 64), served in float32 (the tiny engine block and
# ``dtype``), so that what is left between the served path and the reference is
# summation order, and a limit between the two readings: the spelled-out
# walk's largest shortfall and the twice-run walk's, on the same server and
# the same probes
TINY = json.loads((DATA / "configs" / "tiny.json").read_text())
WIDE = {"hidden_size": 512, "intermediate_size": 1024,
        "num_hidden_layers": 8, "num_attention_heads": 8,
        "engine": {**TINY["engine"], "dtype": "float32"},
        "reference": {"epsilon": 0.006, "why": "a test"}}


def run_cell(bench_copy, capsys, name: str, seed: int) -> tuple[dict, dict]:
    """(the result line, the check as the raw file keeps it)."""
    rc = bench.main(["--workload", f"{name}-closed", "--seed", str(seed),
                     "--seconds", "3", "--trace", "0"], platform="cpu",
                    root=bench_copy)
    assert rc == 0
    run_dir = bench_copy / "benchmark" / ".run" / f"{name}-closed"
    check = json.loads(next(run_dir.glob("raw-*.json")).read_text())["check"]
    return result_line(capsys), check


def test_a_spelled_out_walk_runs_by_files_alone(bench_copy, cpu_peaks,
                                                capsys):
    """A small dense model, served by the program, judged by a family whose
    walk spells out today's order: new files, none edited, ``correct``."""
    add_architecture(bench_copy, "tiny-walk", "spelled_family", **WIDE)
    out, check = run_cell(bench_copy, capsys, "tiny-walk", 5)
    assert out["correct"] is True and out["failed"] == 0
    assert check["ok"] is True and check["positions"] == 64
    # 8 layers of 2229248 (four projections, the MLP, two norm gains), the
    # two tables and the final norm
    assert check["params_served"] == check["params_described"] == 18358784
    assert check["max_shortfall"] < check["epsilon"] / 3     # read: 0.0


def test_the_control_fails_a_walk_the_server_does_not_run(bench_copy,
                                                          cpu_peaks, capsys):
    """THE FAILING CONTROL: the same server, judged by the family that runs
    the stack twice. The weights are the same, so the count agrees; the
    tokens are another model's, so the run is not ``correct``."""
    add_architecture(bench_copy, "tiny-walk", "twice_family", **WIDE)
    out, check = run_cell(bench_copy, capsys, "tiny-walk", 5)
    assert out["failed"] == 0 and out["correct"] is False
    assert check["params_served"] == check["params_described"]
    assert check["ok"] is False
    # not by a hair (read: 0.078, 12 of 64 letters flipped)
    assert check["max_shortfall"] > 3 * check["epsilon"], check
