"""The metric arithmetic on hand-made timelines: percentiles, pooled gaps,
window edges, a failed request."""

import pytest

from harness import metrics as mtr

W = mtr.Window(t_open=10.0, t_close=20.0, t_end=30.0)


def rec(idx, due, times, *, stream="window", max_tokens=None, counts=None,
        error="", sent=None, prompt=10):
    counts = counts or [1] * len(times)
    n = sum(counts)
    return mtr.Record(
        idx=idx, stream=stream, due=due, max_tokens=max_tokens or n,
        sent=due if sent is None else sent, status=200, times=list(times),
        counts=counts, done=times[-1] if times else None,
        ended=times[-1] if times else due + 0.1,
        finish_reason="length" if times else None, prompt_tokens=prompt,
        completion_tokens=n, text="a" * n, error=error)


def test_ttft_is_timed_from_the_due_time_not_from_the_send():
    r = rec(0, due=10.0, times=[10.5, 10.6], sent=10.3)
    assert r.ttft() == pytest.approx(0.5)
    assert r.tpot() == pytest.approx(0.1)


def test_window_edges_open_loop_scores_by_due_time():
    recs = [rec(0, 9.99, [10.2, 10.3]),             # due before the window
            rec(1, 10.0, [10.2, 10.3]),             # on the opening edge: in
            rec(2, 19.99, [21.0, 21.1]),            # finishes after: still in
            rec(3, 20.0, [20.1, 20.2]),             # on the closing edge: out
            rec(4, 12.0, [12.1, 12.2], stream="ramp")]
    assert [r.idx for r in mtr.scored(recs, W, "open")] == [1, 2]
    assert mtr.counts(recs, W, "open") == (2, 0)


def test_closed_loop_scores_what_ended_in_the_window():
    recs = [rec(0, 9.0, [9.5, 12.0]),               # began in the ramp: in
            rec(1, 12.0, [12.5, 13.0]),
            rec(2, 19.0, [19.5, 20.5]),             # ended after the close
            rec(3, 15.0, [], error="http 500")]     # failed inside: counts
    cut = rec(4, 19.5, [19.9])
    cut.ended = cut.done = None                     # cut off at the close
    assert [r.idx for r in mtr.scored(recs + [cut], W, "closed")] == [0, 1, 3]
    assert mtr.counts(recs + [cut], W, "closed") == (3, 1)


def test_percentiles_interpolate_between_order_statistics():
    recs = [rec(i, 10.0 + i * 0.1, [10.0 + i * 0.1 + 0.1 * (i + 1),
                                    10.0 + i * 0.1 + 0.1 * (i + 1) + 0.01])
            for i in range(11)]                     # TTFT 0.1, 0.2, ... 1.1 s
    e2e = mtr.end_to_end(recs, W, "open", setup_s=42.0)
    assert e2e["ttft_ms_mean"] == pytest.approx(600.0)
    assert e2e["ttft_ms_p50"] == pytest.approx(600.0)
    assert e2e["ttft_ms_p90"] == pytest.approx(1000.0)
    assert e2e["ttft_ms_p99"] == pytest.approx(1090.0)
    assert e2e["tpot_ms_p90"] == pytest.approx(10.0)
    assert e2e["tpot_ms_mean"] == pytest.approx(10.0)
    assert e2e["setup_s"] == 42.0


def test_gaps_are_pooled_over_streams_and_cut_at_the_window():
    recs = [rec(0, 9.0, [9.5, 9.9, 10.4, 11.4], stream="ramp"),
            rec(1, 11.0, [12.0, 12.1, 12.2, 19.9, 20.3])]
    gaps = sorted(mtr.gaps_in(recs, W))
    # the ramp stream's gaps that END in the window count; 9.5->9.9 ended
    # before it, 19.9->20.3 after it
    assert gaps == pytest.approx([0.1, 0.1, 0.5, 1.0, 7.7])
    e2e = mtr.end_to_end(recs, W, "open", 0.0)
    assert e2e["stall_ms_p98"] == pytest.approx(
        1e3 * mtr.percentile(gaps, 98))


def test_tokens_per_second_counts_what_arrived_inside_the_window():
    recs = [rec(0, 9.0, [9.9, 10.0, 15.0, 20.0], counts=[1, 2, 3, 4],
                stream="ramp")]
    assert mtr.tokens_in(recs, W.t_open, W.t_close) == 5
    assert mtr.end_to_end(recs, W, "open", 0.0)["out_tok_s"] == 0.5


def test_a_failed_request_counts_and_cannot_improve_a_tail():
    good = [rec(i, 10.0 + i, [10.1 + i, 10.2 + i]) for i in range(9)]
    bad = rec(9, 19.0, [], error="http 429")
    short = rec(10, 19.5, [19.6, 19.7], max_tokens=5)   # asked 5, got 2
    assert short.problem().startswith("reply is 2 characters")
    assert mtr.counts(good + [bad, short], W, "open") == (11, 2)
    e2e = mtr.end_to_end(good + [bad], W, "open", 0.0)
    assert e2e["ttft_ms_p99"] > 9000        # the time the run waited for it
    assert e2e["ttft_ms_mean"] > 1100       # (9 x 100 + 11000) / 10
    assert mtr.attained(good + [bad], W, "open",
                        {"ttft_ms": 500, "tpot_ms": 200}) == 0.9


def test_problem_names_what_was_wrong():
    r = rec(0, 10.0, [10.1, 10.2])
    assert r.problem() == ""
    r.finish_reason = "stop"
    assert "finish_reason" in r.problem()
    r = rec(0, 10.0, [10.1, 10.2])
    r.text = "a1"
    assert "letters" in r.problem()
    r = rec(0, 10.0, [10.1, 10.2])
    r.completion_tokens = 3
    assert "usage" in r.problem()


def test_waiting_at_counts_the_queue_as_clients_see_it():
    recs = [rec(0, 10.0, [11.0, 11.1]), rec(1, 10.5, [10.6, 10.7]),
            rec(2, 10.9, [])]
    assert mtr.waiting_at(recs, 10.95) == 2
    assert mtr.waiting_at(recs, 11.05) == 1
