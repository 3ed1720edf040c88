"""Cases of ``benchmark/tests/`` that need no server, run AS THEY STAND so
that tier-1 counts them (PERF.md section 7 (A); ``benchmark/tests/`` itself is
not part of tier-1): the order a family's ``walk`` gives, the loop a family
without one keeps, a row the model does not hold, ``cache_layers``, an optional
name that is no function (``test_walk.py``); the flight ring paged forward
(``test_contract.py``); what the committed BENCHMARK.json names; the
join of a slice's launches to its executions (``test_launches.py``); and the
sparse hybrid family's file, cell and readers, with a small model of it
served through the harness and the control that fails
(``test_qwen3_next_family.py``); the same for the window / full attention
family (``test_afmoe_family.py``). The latent-attention families' and the
state-space hybrid's are tests/test_bench_walk_latent.py: a small model
served and its control are minutes a family, and two files run side by side
under ``--dist loadfile``.

The modules are loaded by path with ``benchmark/`` and ``benchmark/tests/`` on
``sys.path`` (as tests/test_bench_trace.py does it) and the benchmark's own
``conftest`` under that name while they import: their ``from conftest import
...`` means the benchmark's, and ``conftest`` here is tier-1's."""

import importlib.util
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "benchmark"
sys.path[:0] = [str(BENCH), str(BENCH / "tests")]


def _load(name: str, **modules):
    """``benchmark/tests/<name>.py`` as a module of its own name space, with
    ``modules`` standing in ``sys.modules`` while it imports."""
    saved = {k: sys.modules.get(k) for k in modules}
    sys.modules.update(modules)
    try:
        spec = importlib.util.spec_from_file_location(
            f"benchmark_tests_{name}", BENCH / "tests" / f"{name}.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    finally:
        for k, v in saved.items():
            if v is None:
                sys.modules.pop(k, None)
            else:
                sys.modules[k] = v
    return mod


def committed_without(mod, tmp_path, monkeypatch, names):
    """``mod`` (a module of ``benchmark/tests/``) reads the committed
    BENCHMARK.json WITHOUT the per-layer entries ``names``, which later PRs
    appended for cells of theirs: through a root that links the benchmark's
    directory in. A case that holds "this cell reports what ``m7b-decode``
    reports and its own, and nothing else lists it" is the benchmark's and
    no later PR's to edit, and an entry appended since is held where it was
    appended (tests/test_bench_trace.py)."""
    import functools
    import types

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    assert set(names) <= {m["name"] for m in bench["per_layer"]}
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] not in names]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmark").symlink_to(ROOT / "benchmark")
    monkeypatch.setattr(mod, "spec", types.SimpleNamespace(**{
        **vars(mod.spec),
        "load_cell": functools.partial(mod.spec.load_cell, root=tmp_path)}))
    monkeypatch.setattr(mod, "ROOT", tmp_path)


_conftest = _load("conftest")
_walk = _load("test_walk", conftest=_conftest)
_contract = _load("test_contract", conftest=_conftest)
_ouro = _load("test_ouro_family", conftest=_conftest)
_launches = _load("test_launches", conftest=_conftest)
_qn = _load("test_qwen3_next_family", conftest=_conftest, test_walk=_walk)
_af = _load("test_afmoe_family", conftest=_conftest, test_walk=_walk)

# the fixtures those cases ask for
bench_copy = _conftest.bench_copy
cpu_peaks = _conftest.cpu_peaks
params = _walk.params

test_the_layers_run_in_the_order_the_walk_gives = (
    _walk.test_the_layers_run_in_the_order_the_walk_gives)
test_a_family_without_a_walk_gets_the_loop_it_had = (
    _walk.test_a_family_without_a_walk_gets_the_loop_it_had)
test_a_row_the_model_does_not_hold_is_an_error = (
    _walk.test_a_row_the_model_does_not_hold_is_an_error)
test_the_pools_leading_dimension_is_the_familys = (
    _walk.test_the_pools_leading_dimension_is_the_familys)
test_an_optional_name_that_is_no_function_is_an_error = (
    _walk.test_an_optional_name_that_is_no_function_is_an_error)
test_the_flight_ring_is_paged_forward_until_a_short_page = (
    _contract.test_the_flight_ring_is_paged_forward_until_a_short_page)
test_the_four_chip_cell_reports_what_a_chat_cell_and_a_mesh_report = (
    _contract
    .test_the_four_chip_cell_reports_what_a_chat_cell_and_a_mesh_report)
test_benchmark_json_names_only_files_that_exist = (
    _contract.test_benchmark_json_names_only_files_that_exist)
# PR 37's files: the looped family's hand arithmetic, its walk, its cell, its readers
test_the_hand_arithmetic_of_the_published_keys = (
    _ouro.test_the_hand_arithmetic_of_the_published_keys)
test_the_walk_is_every_pass_in_order_with_the_norm_between = (
    _ouro.test_the_walk_is_every_pass_in_order_with_the_norm_between)


def test_the_new_cell_reports_what_the_issue_names(tmp_path, monkeypatch):
    """PR 37's case as it stands: the cell reports what ``m7b-decode``
    reports of TPOT's movers and its own two. Read without
    ``sample.device_share`` (PR 63), which lists ``m7b-decode`` and not this
    cell."""
    committed_without(_ouro, tmp_path, monkeypatch, ["sample.device_share"])
    _ouro.test_the_new_cell_reports_what_the_issue_names()


test_the_loop_readers_read_the_ring_and_the_scope = (
    _ouro.test_the_loop_readers_read_the_ring_and_the_scope)
# PR 39's file: a slice's launches joined to its executions, by hand
test_the_ith_launch_is_the_ith_execution_launched_in_the_capture = (
    _launches.test_the_ith_launch_is_the_ith_execution_launched_in_the_capture)
test_a_join_that_does_not_hold_voids_the_slice_and_says_why = (
    _launches.test_a_join_that_does_not_hold_voids_the_slice_and_says_why)
test_matched_gives_a_kinds_rows_with_their_device_seconds = (
    _launches.test_matched_gives_a_kinds_rows_with_their_device_seconds)
# PR 41's file: the sparse hybrid family's hand arithmetic, the period's
# leaves, its cell, its readers, and a small model through the harness
test_the_hand_arithmetic_of_the_hybrid_familys_published_keys = (
    _qn.test_the_hand_arithmetic_of_the_published_keys)
test_every_published_number_of_the_catalog_is_in_the_file = (
    _qn.test_every_published_number_of_the_catalog_is_in_the_file)
test_the_served_stack_is_a_stack_of_periods = (
    _qn.test_the_served_stack_is_a_stack_of_periods)


def test_the_hybrid_cell_reports_what_the_issue_names(tmp_path, monkeypatch):
    """PR 41's case as it stands, read as the looped cell's above, and
    without the entry appended for this cell since (PR 64's
    ``gdn.chunk_ride_share``, which tests/test_bench_trace.py holds to be
    there as appended)."""
    committed_without(_qn, tmp_path, monkeypatch,
                      ["sample.device_share", "gdn.chunk_ride_share"])
    _qn.test_the_new_cell_reports_what_the_issue_names()


test_the_new_readers_read_the_ring_and_the_scopes = (
    _qn.test_the_new_readers_read_the_ring_and_the_scopes)
test_a_hybrid_model_runs_by_files_alone = (
    _qn.test_a_hybrid_model_runs_by_files_alone)
test_the_control_fails_a_family_without_the_decay = (
    _qn.test_the_control_fails_a_family_without_the_decay)
# PR 44's file: the window / full attention family's hand arithmetic, the
# dense prefix beside the rows, its cell, its readers, and a small model
# through the harness with the control that fails
test_the_hand_arithmetic_of_the_mixed_stacks_published_keys = (
    _af.test_the_hand_arithmetic_of_the_mixed_stacks_published_keys)
test_every_published_number_of_the_mixed_stacks_catalog_row_is_in_the_file = (
    _af
    .test_every_published_number_of_the_mixed_stacks_catalog_row_is_in_the_file)
test_the_served_pytree_is_a_dense_prefix_beside_rows = (
    _af.test_the_served_pytree_is_a_dense_prefix_beside_rows)


def test_the_mixed_cell_reports_what_the_issue_names(tmp_path, monkeypatch):
    """PR 44's case as it stands. It COUNTS BENCHMARK.json's cells and
    configurations (six, five) and takes its own for the last, and no later
    PR may edit a benchmark file: so it reads the committed file WITHOUT the
    entries appended behind its cell (PR 48's cell and configuration; the
    per-layer metrics stay, each names its own cells), which is what its
    "nothing that was there is changed" holds; and it reads its cells
    without the entry PR 64 appended for ``qn80-ep8-decode``, the cell it
    compares its own to (``committed_without``)."""
    full = tmp_path / "full"
    full.mkdir()
    committed_without(_af, full, monkeypatch, ["gdn.chunk_ride_share"])
    bench = json.loads((full / "BENCHMARK.json").read_text())
    last = [w["name"] for w in bench["workloads"]].index(_af.CELL) + 1
    bench["workloads"] = bench["workloads"][:last]
    used = {w["config"] for w in bench["workloads"]}
    bench["configs"] = [c for c in bench["configs"] if c["name"] in used]
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    monkeypatch.setattr(_af, "ROOT", tmp_path)
    _af.test_the_mixed_cell_reports_what_the_issue_names()


test_the_swa_readers_read_the_ring_and_the_scopes = (
    _af.test_the_swa_readers_read_the_ring_and_the_scopes)
test_a_mixed_attention_model_runs_by_files_alone = (
    _af.test_a_mixed_attention_model_runs_by_files_alone)
test_the_control_fails_a_family_whose_window_layers_see_every_key = (
    _af.test_the_control_fails_a_family_whose_window_layers_see_every_key)
