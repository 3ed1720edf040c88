"""The generator: a pure function of the seed, timed from due times, a fixed
amount of work whatever the seed, and strict about its mix files."""

import json

import numpy as np
import pytest

from conftest import BENCH
from harness import traffic as trf
from harness.spec import SpecError


def mix(name="chat"):
    return trf.validate(json.loads((BENCH / "traffic" / f"{name}.json")
                                   .read_text()), name)


def flat(schedule):
    return [(r.stream, r.due, t.text, t.max_tokens, t.seed)
            for r in schedule for t in r.turns]


def test_schedule_is_a_pure_function_of_the_seed():
    a = trf.open_schedule(mix(), 2.0, 5.0, 40.0, seed=7)
    b = trf.open_schedule(mix(), 2.0, 5.0, 40.0, seed=7)
    c = trf.open_schedule(mix(), 2.0, 5.0, 40.0, seed=8)
    assert flat(a) == flat(b)
    assert flat(a) != flat(c)


def test_every_seed_offers_the_same_work():
    """Same number of arrivals in the window, same multiset of gaps, prompt
    and output lengths: seeds differ in order and pairing only."""
    def work(seed):
        win = [r for r in trf.open_schedule(mix(), 2.0, 5.0, 40.0, seed)
               if r.stream == "window"]
        due = np.array([r.due for r in win])
        # the first arrival comes half its gap after the window opens
        gaps = np.concatenate([[2 * (due[0] - 5.0)], np.diff(due)])
        return (len(win), np.sort(gaps),
                sorted(r.turns[0].user_tokens for r in win),
                sorted(r.turns[0].max_tokens for r in win))

    a, b = work(1), work(2)
    assert a[0] == 80 and a[0] == b[0]
    assert a[2] == b[2] and a[3] == b[3]
    assert np.allclose(a[1], b[1], atol=1e-9) and a[1].sum() == pytest.approx(
        40.0)


def test_arrivals_fill_their_span_and_lengths_keep_their_limits():
    sched = trf.open_schedule(mix(), 2.0, 5.0, 40.0, seed=3)
    ramp = [r.due for r in sched if r.stream == "ramp"]
    win = [r for r in sched if r.stream == "window"]
    assert len(ramp) == 10 and all(0 <= d < 5 for d in ramp)
    assert all(5.0 <= r.due < 45.0 for r in win)
    assert [r.due for r in win] == sorted(r.due for r in win)
    for r in win:
        t = r.turns[0]
        assert 32 <= t.user_tokens <= 3072 and 16 <= t.max_tokens <= 512
        assert len(t.text) == t.user_tokens == len(t.text.encode())
        assert t.text.isascii() and t.text.isprintable()
    med = np.median([r.turns[0].user_tokens for r in win])
    assert 450 < med < 580                      # the mix's median 512


def test_no_two_requests_share_a_prefix_and_warm_up_is_disjoint():
    sched = trf.open_schedule(mix(), 2.0, 5.0, 40.0, seed=3)
    warm = trf.warm_sample(mix(), 12, 3, 1, cap_output=48)
    heads = [t.text[:16] for r in sched + warm for t in r.turns]
    assert len(set(heads)) == len(heads)


def test_warm_sample_holds_the_shortest_and_longest_prompt():
    warm = trf.warm_sample(mix(), 12, seed=5, block=1, cap_output=48)
    lens = sorted(r.turns[0].user_tokens for r in warm)
    assert lens[0] == 32 and lens[-1] == 3072
    assert all(r.turns[0].max_tokens <= 48 for r in warm)


def test_bursty_arrivals_keep_the_rate():
    m = mix()
    m["arrival"] = {"cv": 3.0}
    rng = np.random.default_rng(0)
    due = trf.arrivals(m, 2.0, 40.0, rng)
    gaps = np.diff(due)
    assert len(due) == 80 and due[-1] < 40.0
    assert np.std(gaps) / np.mean(gaps) > 1.8       # burstier than Poisson


def test_closed_stream_spreads_the_first_completions():
    m = mix("decode-heavy")
    first = [r for r, _ in zip(trf.closed_stream(m, 16, seed=1), range(40))]
    assert [r.idx for r in first] == list(range(40))
    cut = [r.turns[0].max_tokens for r in first[:16]]
    full = [r.turns[0].max_tokens for r in first[16:]]
    assert min(full) >= 256 and cut[0] < 256 / 4 and cut[15] >= 256
    again = [r for r, _ in zip(trf.closed_stream(m, 16, seed=1), range(40))]
    assert flat(first) == flat(again)


def test_classes_prefixes_and_sessions():
    m = mix()
    m["classes"] = [
        {"weight": 0.8, "prompt_tokens": {"dist": "fixed", "value": 128},
         "output_tokens": {"dist": "fixed", "value": 64}},
        {"weight": 0.2, "prompt_tokens": {"dist": "fixed", "value": 3072},
         "output_tokens": {"dist": "fixed", "value": 256}}]
    m["prefix"] = {"share": 0.5, "pool": 2, "tokens": 64,
                   "fill_in_setup": True}
    m["session"] = {"turns": [2, 3], "think_s": [1.0, 2.0]}
    m = trf.validate(m, "test")
    bodies = trf.bodies(m, 50, seed=1, stream="window")
    assert sum(len(t) for t in bodies) > 100 and all(
        2 <= len(t) <= 3 for t in bodies)
    assert sum(t[0].max_tokens == 256 for t in bodies) == 10    # 20% of 50
    pre = trf.prefixes(m)
    shared = sum(t[0].text.startswith(tuple(pre)) for t in bodies)
    assert len(pre) == 2 and 10 < shared < 40
    assert all(t[0].think_s == 0 and 1.0 <= t[1].think_s <= 2.0
               for t in bodies)


def test_a_shape_seed_fixes_the_schedule_and_leaves_the_bytes_to_the_seed():
    m = dict(mix("decode-heavy"), loop="open", arrival={"cv": 1.0})
    free = [trf.open_schedule(m, 2.0, 5.0, 40.0, seed) for seed in (1, 2)]
    m["shape_seed"] = 9
    fixed = [trf.open_schedule(trf.validate(m, "test"), 2.0, 5.0, 40.0, seed)
             for seed in (1, 2)]

    def shape(sched):
        return [(r.due, r.turns[0].user_tokens, r.turns[0].max_tokens)
                for r in sched]

    assert shape(free[0]) != shape(free[1])
    assert shape(fixed[0]) == shape(fixed[1])
    assert [r.turns[0].text for r in fixed[0]] != [
        r.turns[0].text for r in fixed[1]]
    assert [r.turns[0].seed for r in fixed[0]] != [
        r.turns[0].seed for r in fixed[1]]


@pytest.mark.parametrize("broken", [
    {"rate": 3}, {"arrival": {"cv": 1, "burst": 2}}, {"loop": "half-open"},
    {"shape_seed": "x"},
    {"classes": []},
    {"classes": [{"weight": 1, "prompt_tokens": {"dist": "zipf"},
                  "output_tokens": {"dist": "fixed", "value": 1}}]}])
def test_unknown_keys_in_a_mix_file_are_an_error(broken):
    m = json.loads((BENCH / "traffic" / "chat.json").read_text())
    m.update(broken)
    with pytest.raises(SpecError):
        trf.validate(m, "test")
