"""Scheduler tests: continuous batching, streaming, stop handling — on the
tiny debug model (no downloads; SURVEY.md §4 fixture strategy)."""

import numpy as np
import pytest

from localai_tpu.engine.runner import ModelRunner
from localai_tpu.engine.scheduler import (
    PRIORITY_BATCH,
    GenRequest,
    Scheduler,
)
from localai_tpu.engine.stream import IncrementalDetokenizer, StopChecker
from localai_tpu.models.registry import resolve_model
from localai_tpu.utils.tokenizer import ByteTokenizer


@pytest.fixture(scope="module")
def sched():
    tiny = resolve_model("debug:tiny", dtype="float32")
    runner = ModelRunner(
        tiny.cfg, tiny.params, num_slots=4, max_ctx=96,
        prefill_buckets=[16, 32], kv_dtype="float32",
    )
    s = Scheduler(runner, ByteTokenizer())
    yield s
    s.shutdown()


def _req(text: str, **kw) -> GenRequest:
    tok = ByteTokenizer()
    return GenRequest(prompt=tok.encode(text), **kw)


def test_basic_generation(sched):
    h = sched.generate(_req("hello", max_new_tokens=8, temperature=0.0))
    assert h.finish_reason in ("length", "stop")
    assert h.completion_tokens <= 8
    assert h.prompt_tokens == 5


def test_streaming_deltas_concatenate_to_text(sched):
    h = sched.submit(_req("stream me", max_new_tokens=12, temperature=0.0))
    parts = [item.delta for item in h]
    assert "".join(parts) == h.text
    assert h.finish_reason is not None


def test_concurrent_requests_batch(sched):
    handles = [
        sched.submit(_req(f"request number {i}", max_new_tokens=10,
                          temperature=0.0))
        for i in range(6)  # > num_slots: exercises queueing
    ]
    for h in handles:
        h.result(timeout=60)
        assert h.finish_reason is not None
    # same prompt → same greedy output regardless of batch composition
    a = sched.generate(_req("determinism", max_new_tokens=6, temperature=0.0))
    b = sched.generate(_req("determinism", max_new_tokens=6, temperature=0.0))
    assert a.token_ids == b.token_ids


def test_max_tokens_respected(sched):
    h = sched.generate(_req("abc", max_new_tokens=3, temperature=0.0))
    assert h.completion_tokens <= 3


def test_usage_metrics(sched):
    before = sched.metrics()["total_generated_tokens"]
    h = sched.generate(_req("usage", max_new_tokens=4, temperature=0.0))
    m = sched.metrics()
    assert m["total_generated_tokens"] >= before + h.completion_tokens
    assert m["num_slots"] == 4


def test_cancellation(sched):
    h = sched.submit(_req("cancel me", max_new_tokens=500, temperature=0.0))
    h.cancel()
    h.result(timeout=60)
    assert h.finish_reason == "cancelled"


def test_logit_bias_forces_token(sched):
    # +100 bias on one byte forces greedy decode to pick it every step
    h = sched.generate(
        _req("force", max_new_tokens=4, temperature=0.0,
             logit_bias={65: 100.0})
    )
    assert all(t == 65 for t in h.token_ids)
    assert "AAAA".startswith(h.text[:4])


def test_stop_sequence():
    det = IncrementalDetokenizer(ByteTokenizer().decode)
    out = "".join(det.push(b) for b in b"hello STOP world")
    assert out == "hello STOP world"

    sc = StopChecker(["STOP"])
    emitted = sc.push("hello ST")
    assert "STOP"[: len("hello ST") - len(emitted)]  # holdback active
    emitted += sc.push("OP world")
    assert sc.stopped == "STOP"
    assert emitted == "hello "


def test_stop_checker_no_false_holdback():
    sc = StopChecker(["\n\n"])
    assert sc.push("abc") == "abc"
    assert sc.push("d\n") == "d"      # holds back the lone newline
    assert sc.push("e") == "\ne"      # released once disambiguated
    assert sc.stopped is None
    assert sc.flush() == ""


def test_incremental_detok_utf8_boundary():
    det = IncrementalDetokenizer(ByteTokenizer().decode)
    snowman = "☃".encode()  # 3 bytes
    outs = [det.push(b) for b in snowman]
    assert outs[0] == "" and outs[1] == ""
    assert outs[2] == "☃"


def test_constraint_masking(sched):
    class OnlyToken:
        """Allow exactly token 66 for 3 steps, then done."""

        def __init__(self, vocab):
            self.row = np.full(vocab, -1e30, np.float32)
            self.row[66] = 0.0
            self.steps = 0

        def allowed_mask(self):
            return self.row

        def advance(self, tid):
            self.steps += 1

        @property
        def done(self):
            return self.steps >= 3

    c = OnlyToken(512)
    h = sched.generate(
        _req("constrained", max_new_tokens=10, temperature=0.0, constraint=c)
    )
    assert h.token_ids == [66, 66, 66]
    assert h.finish_reason == "stop"


def test_mixed_constrained_and_unconstrained_batch(sched):
    """Per-slot constraint gating: a constrained request sharing the batch
    with unconstrained ones (the step_frozen_n path) must produce exactly its
    masked tokens — no duplicates from the frozen rows — while the
    unconstrained requests complete normally."""

    class OnlyToken:
        def __init__(self, vocab, tid, steps):
            self.row = np.full(vocab, -1e30, np.float32)
            self.row[tid] = 0.0
            self.limit = steps
            self.steps = 0

        def allowed_mask(self):
            return self.row

        def advance(self, tid):
            self.steps += 1

        @property
        def done(self):
            return self.steps >= self.limit

    free = [
        sched.submit(_req(f"free {i}", max_new_tokens=20, temperature=0.0))
        for i in range(2)
    ]
    con = sched.submit(
        _req("tool", max_new_tokens=10, temperature=0.0,
             constraint=OnlyToken(512, 66, 5))
    )
    assert con.result(60).token_ids == [66, 66, 66, 66, 66]
    for h in free:
        h.result(60)
        assert h.finish_reason is not None
        assert h.completion_tokens > 0


def test_seeded_output_independent_of_batch_composition(sched):
    """A seeded sampled request must emit the same tokens whether it runs
    alone or concurrently with other requests (PRNG key advances == tokens
    sampled). The regression this pins: a seeded+constrained slot riding a
    step_frozen_n dispatch used to advance its key on every frozen inner
    step (multi_step advances per consumed token) instead of once."""

    class AllowBand:
        """Allow a 20-token band (sampled, not forced) for `limit` steps."""

        def __init__(self, vocab, limit):
            self.row = np.full(vocab, -1e30, np.float32)
            self.row[60:80] = 0.0
            self.limit = limit
            self.steps = 0

        def allowed_mask(self):
            return self.row

        def advance(self, tid):
            self.steps += 1

        @property
        def done(self):
            return self.steps >= self.limit

    def run_seeded():
        return sched.generate(
            _req("seeded", max_new_tokens=6, temperature=1.0, seed=1234,
                 constraint=AllowBand(512, 6))
        ).token_ids

    solo = run_seeded()
    # noise requests large enough to stay in flight for the whole seeded
    # run, so the seeded slot really takes the frozen path; cancelled after
    noise = [
        sched.submit(_req(f"noise {i}", max_new_tokens=500, temperature=0.0))
        for i in range(2)
    ]
    mixed = run_seeded()
    for h in noise:
        h.cancel()
    for h in noise:
        h.result(60)
    assert len(solo) == 6
    assert all(60 <= t < 80 for t in solo)
    assert mixed == solo


def test_slot_reuse_resets_sampling_params(sched):
    """A reused slot must not inherit the previous request's options
    (regression: with_slot used to skip None fields)."""
    # saturate all 4 slots with greedy requests, then run a default-sampling
    # request; if temperature leaked it would decode greedily every time
    for _ in range(4):
        sched.generate(_req("warm", max_new_tokens=2, temperature=0.0))
    outs = {
        tuple(
            sched.generate(_req("q", max_new_tokens=6, seed=i)).token_ids
        )
        for i in range(6)
    }
    assert len(outs) > 1  # default temperature=1.0 sampling, not greedy


def test_constraint_mask_cleared_when_none(sched):
    class MaskThenFree:
        """Token 66 for 2 steps, then unconstrained (mask=None)."""

        def __init__(self, vocab):
            self.row = np.full(vocab, -1e30, np.float32)
            self.row[66] = 0.0
            self.steps = 0

        def allowed_mask(self):
            return self.row if self.steps < 2 else None

        def advance(self, tid):
            self.steps += 1

        @property
        def done(self):
            return False

    h = sched.generate(
        _req("free region", max_new_tokens=8, temperature=0.0,
             constraint=MaskThenFree(512))
    )
    assert h.token_ids[:2] == [66, 66]
    # after the mask clears, greedy decode must be able to leave token 66
    assert any(t != 66 for t in h.token_ids[2:])


def test_constrained_generation_valid_json(sched):
    """End-to-end grammar constraint through the live engine: the tiny
    random-weight model MUST emit schema-valid JSON when masked."""
    import json

    from localai_tpu.functions import constraint_for_schema

    schema = {
        "type": "object",
        "properties": {
            "name": {"const": "answer"},
            "arguments": {
                "type": "object",
                "properties": {"message": {"type": "string",
                                           "maxLength": 12}},
            },
        },
    }
    c = constraint_for_schema(schema, ByteTokenizer())
    h = sched.generate(
        _req("call a tool", max_new_tokens=120, temperature=0.8, seed=7,
             constraint=c),
        timeout=120,
    )
    obj = json.loads(h.text)
    assert obj["name"] == "answer"
    assert "message" in obj["arguments"]


# ---------------------------------------------------------------------------
# two-lane admission (interactive vs background batch)


def _wait(pred, timeout=60.0):
    import time as _time

    deadline = _time.monotonic() + timeout
    while _time.monotonic() < deadline:
        if pred():
            return True
        _time.sleep(0.01)
    return False


def test_batch_priority_request_completes(sched):
    # long enough to span several 16-step dispatches, so at least one
    # drain records the slot while the batch request still occupies it
    h = sched.generate(_req("background", max_new_tokens=48,
                            temperature=0.0, ignore_eos=True,
                            priority=PRIORITY_BATCH))
    assert h.finish_reason in ("length", "stop")
    assert h.completion_tokens > 0
    # the lane is tagged through to the flight ring
    assert any(r["batch_slots"] > 0 for r in sched.flight.snapshot())


def test_interactive_admitted_before_batch_under_full_queue(sched):
    """Admit ordering: with every slot occupied and both lanes queued,
    freed slots go to EVERY waiting interactive request before any batch
    line — batch work only fills slots when interactive queue depth is
    zero."""
    hold = [
        sched.submit(_req(f"hold {i}", max_new_tokens=500, temperature=0.0))
        for i in range(4)
    ]
    assert _wait(lambda: len(sched.metrics()["active_slots"]) == 4)
    # queue batch FIRST, interactive second — FIFO would admit the batch
    # lines first, the lane policy must not
    batch = [
        sched.submit(_req(f"batch {i}", max_new_tokens=4, temperature=0.0,
                          priority=PRIORITY_BATCH))
        for i in range(3)
    ]
    inter = [
        sched.submit(_req(f"inter {i}", max_new_tokens=4, temperature=0.0))
        for i in range(2)
    ]
    m = sched.metrics()
    assert m["batch_queue_depth"] >= 1  # lanes are accounted separately
    for h in hold:
        h.cancel()
    for h in inter + batch + hold:
        h.result(60)
    assert all(h.admit_index is not None for h in inter + batch)
    assert max(h.admit_index for h in inter) < \
        min(h.admit_index for h in batch)


def test_busy_covers_batch_lane(sched):
    assert not sched.busy
    h = sched.submit(_req("lane busy", max_new_tokens=4, temperature=0.0,
                          priority=PRIORITY_BATCH))
    assert sched.busy  # queued on the batch lane counts as busy
    h.result(60)
    assert _wait(lambda: not sched.busy)


def test_metrics_report_batch_lane_fields(sched):
    assert _wait(lambda: not sched.busy)
    m = sched.metrics()
    assert m["batch_queue_depth"] == 0 and m["batch_slots"] == 0


# ---------------------------------------------------------------------------
# adaptive streaming dispatch (delivery-lag bound)


def _bare_scheduler(multi_step=16, pipeline_depth=2, target=0.1):
    """Scheduler shell for unit-testing _effective_steps without an engine
    thread (the logic reads only these fields)."""
    import threading

    s = Scheduler.__new__(Scheduler)
    s.multi_step = multi_step
    s.pipeline_depth = pipeline_depth
    s.stream_latency_target = target
    s._step_ema = None
    s._host_ema = None
    s._lock = threading.Lock()
    s._slots = {}
    return s


def _fake_slot(stream: bool):
    from types import SimpleNamespace

    return SimpleNamespace(
        handle=SimpleNamespace(request=SimpleNamespace(stream=stream))
    )


# multi_step 16, depth 2, target 100 ms: the budget is 50 ms a dispatch.
# (streams of the slots, step_ema, host_ema, k pipelined, k synchronous)
_STEPS_CASES = {
    # no stream attached: the full multi_step, whatever the timings
    "idle engine": ((), None, None, 16, 16),
    "batch-only, slow steps": ((False,), 0.05, None, 16, 16),
    "batch-only, host hidden": ((False,), 0.010, 0.002, 16, 16),
    # no timing sample yet: latency-safe single step
    "stream, no step sample": ((True,), None, None, 1, 1),
    "stream, no host sample": ((True,), 0.001, None, 1, 16),
    # the budget is the ceiling: the host wants more than it allows
    "1 ms steps, host 20 ms: capped at multi_step": (
        (True,), 0.001, 0.020, 16, 16),
    "10 ms steps, host 100 ms: 5 fit, round DOWN": (
        (True,), 0.010, 0.100, 4, 4),
    "50 ms steps: single-step dispatches": ((True,), 0.050, 0.100, 1, 1),
    "a mixed batch: one stream bounds the lag for everyone": (
        (True, False), 0.050, 0.100, 1, 1),
    # the host hidden behind one step: a faster step never raises k
    "15 ms steps, host 2 ms": ((True,), 0.015, 0.002, 1, 2),
    "10 ms steps, host 2 ms": ((True,), 0.010, 0.002, 1, 4),
    "5 ms steps, host 2 ms": ((True,), 0.005, 0.002, 1, 8),
    # a host slower than the dispatch: the smallest k that hides it
    "10 ms steps, host 12 ms": ((True,), 0.010, 0.012, 2, 4),
    "5 ms steps, host 12 ms": ((True,), 0.005, 0.012, 4, 8),
    "1 ms steps, host 6 ms: a small model keeps its long dispatch": (
        (True,), 0.001, 0.006, 8, 16),
    "a dispatch exactly as long as the host's work": (
        (True,), 0.004, 0.008, 2, 8),
    # ... and not past the budget
    "20 ms steps, host 90 ms": ((True,), 0.020, 0.090, 2, 2),
}


@pytest.mark.parametrize("case", list(_STEPS_CASES))
def test_effective_steps(case):
    """With a stream attached a pipelined dispatch holds the FEWEST steps
    that keep the device busy while the host handles one (k×step_ema >=
    host_ema, a power of two), under the latency budget's ceiling (the
    largest power of two with k×depth×step_ema <= target, multi_step at
    most); the synchronous path hides nothing and takes the ceiling;
    batch-only traffic takes multi_step."""
    streams, step_ema, host_ema, k_pipelined, k_sync = _STEPS_CASES[case]
    s = _bare_scheduler()
    for i, stream in enumerate(streams):
        s._slots[i] = _fake_slot(stream=stream)
    s._step_ema, s._host_ema = step_ema, host_ema
    assert s._effective_steps() == k_pipelined
    assert s._effective_steps(pipelined=False) == k_sync
    assert k_pipelined <= k_sync


@pytest.mark.parametrize("host_ema", [0.002, 0.012, 0.030])
def test_a_faster_step_never_lengthens_a_hidden_dispatch(host_ema):
    """The regression of PERF.md's PR 31, by construction: as the step
    gets faster the dispatch's wall time (k×step_ema) never grows past
    what the host needs or one step, whichever is longer, and k never
    passes the budget's."""
    s = _bare_scheduler()
    s._slots[0] = _fake_slot(stream=True)
    s._host_ema = host_ema
    for step_ms in range(60, 0, -1):
        s._step_ema = step_ms * 1e-3
        k = s._effective_steps()
        assert k <= s._effective_steps(pipelined=False)
        # hidden already at k/2 (or k = 1): no longer than it has to be
        assert k == 1 or (k // 2) * s._step_ema < host_ema


def test_host_ema_is_fed_by_pipelined_decode_dispatches(sched):
    """The EMA beside ``_step_ema``: host seconds a pipelined decode
    dispatch (what the flight ring splits out as gap + sched + launch),
    present once a post-compile dispatch has drained and no larger than a
    dispatch's wall."""
    h = sched.submit(_req("host ema", max_new_tokens=24, temperature=0.0,
                          ignore_eos=True, stream=True))
    list(h)
    assert _wait(lambda: not sched.busy)
    assert sched._host_ema is not None and sched._host_ema >= 0.0
    rows = [r for r in sched.flight.snapshot()
            if r["program"] in ("decode", "decode_n") and not r["compile"]]
    assert rows
    assert sched._host_ema <= max(r["dispatch_ms"] for r in rows) * 1e-3


def test_streaming_request_bounds_delivery_lag(sched):
    """End-to-end: with an SSE stream attached, inter-delta delivery lag
    stays bounded (the dispatch size adapts down from multi_step=16)."""
    import time as _time

    h = sched.submit(_req("stream latency", max_new_tokens=24,
                          temperature=0.0, ignore_eos=True, stream=True))
    arrivals = []
    for item in h:
        arrivals.append(_time.monotonic())
    assert h.finish_reason is not None
    # the engine must have taken the adaptive path (a power of two ≤ 16),
    # and its own lag model — steps×depth×ema — must fit the target with
    # the step size it chose
    steps = sched.last_dispatch_steps
    assert steps in (1, 2, 4, 8, 16)
    if sched._step_ema is not None and steps > 1:
        assert steps * sched.pipeline_depth * sched._step_ema <= \
            2 * sched.stream_latency_target
    gaps = [b - a for a, b in zip(arrivals, arrivals[1:])]
    # generous wall-clock bound (CPU test machine, first-compile excluded
    # via median): the old fixed 16×2 dispatch would burst, not trickle
    gaps.sort()
    assert gaps[len(gaps) // 2] < 1.0
