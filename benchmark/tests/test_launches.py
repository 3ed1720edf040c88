"""The join of a slice's launches to its device executions
(harness/launches.py, PR 39), on hand-made reductions: no server, no trace
file. A launch is ``(number, start)`` of its ``sched.launch/<n>``, an
execution ``(program, start, end)`` on chip 0; seconds are made up."""

import types

import pytest

from harness import launches as lch

DECODE, CHUNK, VERIFY = ("jit__decode_paged_fn(7)", "jit__prefill_paged_fn(8)",
                         "jit__verify_paged_fn(9)")


def ctx_of(launched, runs, programs, depth=None) -> dict:
    """``launched``: [(n, start)]; ``runs``: [(module, start, end)];
    ``programs``: {n: the row's program} (a number left out has no row);
    ``depth``: the configuration's ``engine.pipeline_depth``, if it says."""
    engine = {} if depth is None else {"pipeline_depth": depth}
    return {
        "cell": types.SimpleNamespace(config={"engine": engine}),
        "trace": {"phases": [(0.0, 99.0, "sched.decode_launch")] + [
            (at, at + 0.5, f"sched.launch/{n}") for n, at in launched],
            "modules": [(s, e, name) for name, s, e in runs]},
        "traced": {"flight": [{"launch": n, "program": p}
                              for n, p in programs.items()]}}


CASES = {
    # the device one step behind the host: the step in flight when the
    # capture began starts before the first launch, the last launch's
    # execution falls behind the capture's end; each step ends while the
    # host waits for it with the next one enqueued, 1 before the launch
    # after that begins
    "one_step_behind": dict(
        launched=[(5, 9), (6, 11), (7, 21), (8, 31)],
        runs=[(DECODE, 0, 10), (DECODE, 10, 20), (DECODE, 20, 30),
              (DECODE, 30, 40)],
        programs={5: "decode", 6: "decode", 7: "decode", 8: "decode"},
        pairs=[(5, 10), (6, 20), (7, 30)], early=1, late=0, unrun=[8],
        unrowed=0, slack=[1e3, 1e3]),
    # chunks between the steps: a chunk has ended when the step launched
    # behind it has been read
    "chunks_between_the_steps": dict(
        launched=[(5, 9), (6, 11), (7, 12), (8, 21), (9, 31)],
        runs=[(DECODE, 0, 10), (DECODE, 10, 20), (CHUNK, 20, 24),
              (DECODE, 24, 30), (DECODE, 30, 40)],
        programs={5: "decode", 6: "prefill_chunk", 7: "decode", 8: "decode",
                  9: "decode"},
        pairs=[(5, 10), (6, 20), (7, 24), (8, 30)], early=1, late=0,
        unrun=[9], unrowed=0, slack=[1e3, 1e3]),
    # a speculative window among the launches (it has a row, and runs a
    # program this file does not join), a launch as the capture stopped
    # whose row the ring did not hold yet, and an execution behind it
    "a_window_between_and_an_unrowed_launch_behind": dict(
        launched=[(5, 9), (6, 25), (7, 33)],
        runs=[(DECODE, 10, 20), (VERIFY, 20, 24), (CHUNK, 26, 30),
              (DECODE, 34, 38)],
        programs={5: "decode", 6: "prefill_chunk", 4: "spec"},
        pairs=[(5, 10), (6, 26)], early=0, late=1, unrun=[], unrowed=1,
        slack=[1e3, None]),
    # a frozen-slot dispatch runs a decode program
    "a_frozen_dispatch_is_a_decode_execution": dict(
        launched=[(5, 9), (6, 21)],
        runs=[("jit__decode_paged_frozen_n_fn(3)", 10, 20), (DECODE, 22, 30)],
        programs={5: "decode_frozen_n", 6: "decode"},
        pairs=[(5, 10), (6, 22)], early=0, late=0, unrun=[], unrowed=0,
        slack=[1e3, None]),
    # a pipeline three deep: a step may run on while two more are launched
    "a_deeper_pipeline_says_so": dict(
        depth=3,
        launched=[(5, 9), (6, 10), (7, 11), (8, 21)],
        runs=[(DECODE, 12, 20), (DECODE, 20, 30), (DECODE, 30, 40)],
        programs={5: "decode", 6: "decode", 7: "decode", 8: "decode"},
        pairs=[(5, 12), (6, 20), (7, 30)], early=0, late=0, unrun=[8],
        unrowed=0, slack=[3e3, 1e3]),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_ith_launch_is_the_ith_execution_launched_in_the_capture(case):
    c = CASES[case]
    ctx = ctx_of(c["launched"], c["runs"], c["programs"], c.get("depth"))
    joined = lch.join(ctx)
    assert [(row["launch"], s) for row, _, s, _ in joined["pairs"]] == (
        c["pairs"])
    assert all(kind == lch.row_kind(row["program"]) == lch.module_kind(
        next(m for m, s, _ in c["runs"] if s == start))
        for row, kind, start, _ in joined["pairs"])
    counts = {k: c[k] for k in ("early", "late", "unrun", "unrowed")}
    assert {k: joined[k] for k in counts} == counts
    notes = ctx["trace"]["notes"]["launches"]
    assert notes["executions"] == sum(
        lch.module_kind(m) is not None for m, _, _ in c["runs"])
    assert notes["unrun"] == c["unrun"] and notes["slack_ms"] == c["slack"]


ROWS_5_TO_14 = {n: "decode" for n in range(5, 15)}
MISMATCHES = {
    # the ring says launch 6 was a chunk and the device ran decode steps
    "a_row_of_another_kind_than_its_execution": dict(
        launched=[(n, 10 * n - 1) for n in range(5, 15)],
        runs=[(DECODE, 10 * n, 10 * n + 9) for n in range(5, 15)],
        programs={**ROWS_5_TO_14, 6: "prefill_chunk"},
        why="a prefill launch against a decode execution", launch=6),
    # the device plane began after the first launch's execution had: every
    # pair has slipped by one, the kinds all agree and every execution
    # starts behind its enqueue, but launch 5's is still running when the
    # loop has read it and lets launch 7 go
    "the_first_execution_was_not_recorded": dict(
        launched=[(5, 9), (6, 11), (7, 21), (8, 31)],
        runs=[(DECODE, 20, 30), (DECODE, 30, 40)],
        programs=ROWS_5_TO_14,
        why="an execution still running when the loop had read it",
        launch=5),
    # a chunk enqueued before the capture began, queued behind the step in
    # flight: it STARTS after the first launch, and is no launch's
    "a_chunk_queued_from_before_the_capture": dict(
        launched=[(5, 9), (6, 21)],
        runs=[(DECODE, 0, 10), (CHUNK, 10, 20), (DECODE, 20, 30),
              (DECODE, 30, 40)],
        programs={5: "decode", 6: "decode_n"},
        why="a decode launch against a prefill execution", launch=5),
    # two steps in the queue when the capture began and only decode steps:
    # launch 5 would be given the step enqueued before it
    "a_step_queued_from_before_the_capture": dict(
        launched=[(5, 9), (6, 21), (7, 31), (8, 41)],
        runs=[(DECODE, 0, 10), (DECODE, 10, 20), (DECODE, 20, 30),
              (DECODE, 30, 40), (DECODE, 40, 50)],
        programs=ROWS_5_TO_14,
        why="an execution that starts before its enqueue", launch=6),
    # a launch in the middle of the slice that the ring has no row for:
    # what it ran is not known, so neither is whose the next execution is
    "a_launch_with_no_row_between_two_that_have_one": dict(
        launched=[(5, 9), (6, 12), (7, 25)],
        runs=[(DECODE, 10, 20), (DECODE, 26, 30)],
        programs={5: "decode", 7: "decode"},
        why="a launch with no ring row in front of one with a row",
        launch=6),
}


@pytest.mark.parametrize("case", sorted(MISMATCHES))
def test_a_join_that_does_not_hold_voids_the_slice_and_says_why(case):
    """No number is read and nothing raises: the run goes on, and its
    ``trace`` line says what did not fit and at which launch."""
    c = MISMATCHES[case]
    ctx = ctx_of(c["launched"], c["runs"], c["programs"])
    assert lch.join(ctx) is None
    assert lch.matched(ctx, ("decode", "decode_n", "prefill_chunk")) == []
    assert ctx["trace"]["notes"]["launches"] == {
        "mismatch": c["why"], "launch": c["launch"]}


def test_matched_gives_a_kinds_rows_with_their_device_seconds():
    c = CASES["a_window_between_and_an_unrowed_launch_behind"]
    ctx = ctx_of(c["launched"], c["runs"], c["programs"])
    assert [(r["launch"], sec) for r, sec in lch.matched(
        ctx, ("decode", "decode_n"))] == [(5, 10)]
    assert [(r["launch"], sec) for r, sec in lch.matched(
        ctx, ("prefill_chunk",))] == [(6, 4)]
    assert lch.matched({"trace": None}, ("decode",)) == []
    assert lch.real_pairs({"chunk_tokens": 4, "chunk_offset": 10}) == 50
