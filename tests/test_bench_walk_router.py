"""tests/test_bench_walk.py's third part, loaded the same way: the cases of
``benchmark/tests/test_smallthinker_family.py`` (PR 65), the family whose
router stands in front of attention: the reference against the equations
written out, the hand arithmetic, the catalog row in the file, layers under
the family's names, its cell, its eight readers, and a small model
through the harness with the control that fails (the family file with the
routing moved behind attention)."""

from test_bench_walk import _conftest, _load, _walk

_st = _load("test_smallthinker_family", conftest=_conftest, test_walk=_walk)

# the fixtures those cases ask for
bench_copy = _conftest.bench_copy
cpu_peaks = _conftest.cpu_peaks

test_one_layer_is_the_five_equations_written_out = (
    _st.test_one_layer_is_the_five_equations_written_out)
test_the_hand_arithmetic_of_the_router_first_stacks_published_keys = (
    _st.test_the_hand_arithmetic_of_the_router_first_stacks_published_keys)
test_every_published_number_of_the_catalog_row_is_in_the_file = (
    _st.test_every_published_number_of_the_catalog_row_is_in_the_file)
test_the_served_pytree_is_layers_under_the_familys_names = (
    _st.test_the_served_pytree_is_layers_under_the_familys_names)
test_the_router_first_cell_reports_what_the_issue_names = (
    _st.test_the_router_first_cell_reports_what_the_issue_names)
test_the_smt_readers_read_the_ring_and_the_scopes = (
    _st.test_the_smt_readers_read_the_ring_and_the_scopes)
test_a_router_first_model_runs_by_files_alone = (
    _st.test_a_router_first_model_runs_by_files_alone)
test_the_control_fails_a_family_whose_router_reads_the_experts_input = (
    _st.test_the_control_fails_a_family_whose_router_reads_the_experts_input)
