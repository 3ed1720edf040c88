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


def test_streaming_request_bounds_delivery_lag(sched, monkeypatch):
    """End-to-end: with an SSE stream attached, inter-delta delivery lag
    stays bounded (the dispatch size adapts down from multi_step=16)."""
    import time as _time

    # every choice of the rule beside the step time it was made WITH: read
    # afterwards, ``_step_ema`` holds dispatches the choice never saw, and
    # on a loaded machine (tier-1 runs six workers) one slow dispatch more
    # than doubles it: that comparison came and went with the load (the
    # second timed case of PERF.md section 7 (B), found in PR 37)
    real, chosen = sched._effective_steps, []

    def choose(pipelined=True):
        with sched._lock:
            streaming = any(c.handle.request.stream
                            for c in sched._slots.values())
        ema, k = sched._step_ema, real(pipelined)
        chosen.append((k, ema, streaming))
        return k

    monkeypatch.setattr(sched, "_effective_steps", choose)
    h = sched.submit(_req("stream latency", max_new_tokens=24,
                          temperature=0.0, ignore_eos=True, stream=True))
    arrivals = []
    for item in h:
        arrivals.append(_time.monotonic())
    assert h.finish_reason is not None
    # the engine must have taken the adaptive path (a power of two ≤ 16),
    # and its own lag model — steps×depth×ema — must fit the target with
    # the step size it chose, at the step time it chose it by
    assert sched.last_dispatch_steps in (1, 2, 4, 8, 16)
    with_stream = [(k, ema) for k, ema, streaming in chosen if streaming]
    assert with_stream
    for k, ema in with_stream:
        assert k in (1, 2, 4, 8, 16)
        if ema is not None and k > 1:
            assert (k * sched.pipeline_depth * ema
                    <= sched.stream_latency_target), (k, ema)
    gaps = [b - a for a, b in zip(arrivals, arrivals[1:])]
    # generous wall-clock bound (CPU test machine, first-compile excluded
    # via median): the old fixed 16×2 dispatch would burst, not trickle
    gaps.sort()
    assert gaps[len(gaps) // 2] < 1.0


# ---------------------------------------------------------------------------
# the paged admission path: no blocking device read, one program to arm a
# slot, the first token back through the pipeline


PAGED_KW = dict(num_slots=4, max_ctx=96, prefill_buckets=[16, 32],
                kv_dtype="float32", paged=True, kv_block_tokens=16,
                prefill_chunk=16)


@pytest.fixture(scope="module")
def tiny():
    return resolve_model("debug:tiny", dtype="float32")


@pytest.fixture(scope="module")
def paged(tiny):
    """A paged scheduler whose runner refuses the blocking frontier read,
    and a second runner of the same shape for one-request-at-a-time
    references."""
    runner = ModelRunner(tiny.cfg, tiny.params, **PAGED_KW)

    def no_read():
        raise AssertionError("slot_positions(): a blocking device read on "
                             "a paged admission")

    runner.slot_positions = no_read
    s = Scheduler(runner, ByteTokenizer(), multi_step=4)
    yield s, ModelRunner(tiny.cfg, tiny.params, **PAGED_KW)
    s.shutdown()


GREEDY = dict(temperature=0.0)
SEEDED = dict(temperature=0.8, top_p=0.95, seed=2147503333)


def _alone(ref: ModelRunner, text: str, n: int, sampling: dict,
           logit_bias=None) -> list[int]:
    """The request's tokens served alone and synchronously: admit() then
    step(), each read at once. The scheduler bans the ids the byte
    tokenizer cannot produce; so does this."""
    row = np.zeros(ref.cfg.vocab_size, np.float32)
    row[ByteTokenizer().vocab_size:] = -1e30
    slot = ref.acquire_slot()
    out = [ref.admit(slot, ByteTokenizer().encode(text), bias_row=row,
                     logit_bias=logit_bias, **sampling)]
    while len(out) < n:
        out.append(int(ref.step()[slot]))
    ref.release(slot)
    return out


def _keeper(s: Scheduler):
    """A long stream that keeps the batch decoding while others arrive."""
    h = s.submit(_req("keeper", max_new_tokens=80, stream=True,
                      ignore_eos=True, **GREEDY))
    assert _wait(lambda: h.completion_tokens >= 3)
    return h


def _hold(monkeypatch, s: Scheduler, hold) -> None:
    """Let the engine thread admit nothing while ``hold`` is set, so that
    what is submitted meanwhile is found in ONE iteration."""
    real = s._admit_pending
    monkeypatch.setattr(
        s, "_admit_pending", lambda: False if hold.is_set() else real())


class _Band:
    """Allow a band of tokens (sampled, not forced) for ``limit`` steps."""

    def __init__(self, limit):
        self.row = np.full(512, -1e30, np.float32)
        self.row[60:80] = 0.0
        self.limit, self.steps = limit, 0

    def allowed_mask(self):
        return self.row

    def advance(self, tid):
        self.steps += 1

    @property
    def done(self):
        return self.steps >= self.limit


@pytest.mark.parametrize("sampling", [GREEDY, SEEDED],
                         ids=["greedy", "seeded"])
@pytest.mark.parametrize("case", [
    "arrival_mid_decode", "two_arrivals_one_iteration", "multi_chunk_prompt",
    "first_token_stops", "max_tokens_1", "cancel_before_first_token_read",
    "constrained_beside_free"])
def test_streams_equal_the_request_served_alone(paged, monkeypatch, case,
                                                sampling):
    """However an admission interleaves with the batch, each stream is the
    one its request gives alone, read synchronously: the pipelined first
    token and the dropped reads change WHEN the host sees a token, never
    which. (The arming program is held to the parent's eager updates in
    test_one_program_arms_a_slot_as_the_eager_updates_did.)"""
    import threading

    s, ref = paged
    keeper = _keeper(s)
    kw = dict(max_new_tokens=10, ignore_eos=True, stream=True, **sampling)
    texts = ["first arrival"]
    extra = {}
    if case == "two_arrivals_one_iteration":
        texts.append("second arrival")
    elif case == "multi_chunk_prompt":      # 41 tokens: three chunks of 16
        # (its first block differs by sampling: no pooled prefix to share)
        texts = [f"{len(sampling)} prompt long enough to take three chunks"]
    elif case == "first_token_stops":
        extra = dict(logit_bias={65: 100.0}, stop=["A"])
    elif case == "max_tokens_1":
        kw["max_new_tokens"] = 1
    elif case == "cancel_before_first_token_read":
        real = s._install_slot

        def cancel_at_launch(slot, handle, base, mask_set):
            ctx = real(slot, handle, base, mask_set)
            if handle is not keeper and handle.request.stream:
                handle.cancel()     # after the final chunk's launch
            return ctx

        monkeypatch.setattr(s, "_install_slot", cancel_at_launch)
    hold = threading.Event()
    hold.set()
    _hold(monkeypatch, s, hold)
    chunks0 = s.total_prefill_chunks
    handles = [s.submit(_req(t, **kw, **extra)) for t in texts]
    con = None
    if case == "constrained_beside_free":
        con = s.submit(_req("tool", max_new_tokens=6, temperature=1.0,
                            seed=1234, constraint=_Band(6)))
    hold.clear()
    for h, t in zip(handles, texts):
        h.result(60)
        want = _alone(ref, t, kw["max_new_tokens"], sampling,
                      extra.get("logit_bias"))
        if case == "first_token_stops":
            assert (h.finish_reason, h.token_ids, h.text) == (
                "stop", want[:1], "")
            assert want[0] == 65
        elif case == "cancel_before_first_token_read":
            assert (h.finish_reason, h.token_ids) == ("cancelled", [])
        else:
            assert (h.finish_reason, h.token_ids) == ("length", want)
    if case == "multi_chunk_prompt":
        assert s.total_prefill_chunks - chunks0 == 3
    if con is not None:
        got = con.result(60).token_ids
        monkeypatch.undo()
        alone = s.generate(_req("tool", max_new_tokens=6, temperature=1.0,
                                seed=1234, constraint=_Band(6))).token_ids
        assert len(got) == 6 and got == alone
    keeper.cancel()
    # the keeper decoded on through every admission: its own stream too
    got = keeper.result(60).token_ids
    assert got == _alone(ref, "keeper", len(got), GREEDY)


def test_prompt_cache_hit_gives_the_stream_of_a_full_prefill(tiny, tmp_path):
    """A disk prompt-cache hit moves a FREE slot's frontier (load_prefix):
    the host's mirror follows it, the admission resumes past the loaded
    rows, and the stream is the full prefill's."""
    from localai_tpu.engine.promptcache import PromptKVCache

    prompt = "the shared system prompt that should be cached once, and more"
    kw = dict(max_new_tokens=8, ignore_eos=True, **SEEDED)
    outs = []
    for _ in range(2):      # the second scheduler starts cold, cache warm
        cache = PromptKVCache(tmp_path / "pc")
        runner = ModelRunner(tiny.cfg, tiny.params, **PAGED_KW)
        runner.slot_positions = None    # calling it would raise
        s = Scheduler(runner, ByteTokenizer(), prompt_cache=cache)
        try:
            outs.append(s.generate(_req(prompt, **kw), timeout=120).token_ids)
        finally:
            s.shutdown()
    assert cache.hits == 1 and runner.total_prefix_reused > 0
    assert outs[0] == outs[1]
    assert s.metrics()["admit_blocking_reads"] == 0


def _arrival_log(s: Scheduler, monkeypatch, steps=None,
                 spy_on_step_n: bool = True):
    """An arrival beside a decoding stream, with the engine's launches,
    reads and deliveries logged in order. ``steps`` pins what
    ``_effective_steps`` answers (None: its own rule, which under a loaded
    host picks 2 or 4 for the tiny model: the host is then slow against the
    step); a dispatch of k > 1 steps is launched through
    ``runner.step_n_async``, one of k = 1 through ``step_async``, so a spy
    that is to see every decode launch sits on BOTH. Returns (log, counters
    before, counters after, the keeper's tokens at the arrival's end)."""
    keeper = _keeper(s)
    log = []
    real_launch, real_launch_n = s.runner.step_async, s.runner.step_n_async
    real_first, real_rows = s._first_token, s._process_rows
    real_install = s._install_slot

    def launch():
        log.append(("decode_launch", s._dispatch_seq))
        return real_launch()

    def launch_n(n):
        log.append(("decode_launch", s._dispatch_seq))
        return real_launch_n(n)

    def first(pf, tok):
        log.append(("first_token_read", None))
        return real_first(pf, tok)

    def rows(r, seq, frozen=None):
        log.append(("rows", seq))
        return real_rows(r, seq, frozen)

    def install(*a):
        log.append(("final_chunk_launched", s._dispatch_seq))
        return real_install(*a)

    monkeypatch.setattr(s.runner, "step_async", launch)
    if spy_on_step_n:
        monkeypatch.setattr(s.runner, "step_n_async", launch_n)
    if steps is not None:
        monkeypatch.setattr(s, "_effective_steps",
                            lambda pipelined=True: steps)
    monkeypatch.setattr(s, "_first_token", first)
    monkeypatch.setattr(s, "_process_rows", rows)
    monkeypatch.setattr(s, "_install_slot", install)
    before = s.metrics()
    h = s.generate(_req("arrival", max_new_tokens=6, stream=True,
                        ignore_eos=True, **GREEDY))
    assert h.finish_reason == "length"
    # the scene needs the keeper still decoding when the arrival is done:
    # one that had spent its 80 tokens left the arrival alone in the batch
    spent = keeper.completion_tokens
    keeper.cancel()
    keeper.result(60)
    monkeypatch.undo()
    return log, before, s.metrics(), spent


def _around_the_chunk(log):
    """(names, index of the final chunk's launch, index of its token's
    read, the dispatch counter at the launch)."""
    names = [n for n, _ in log]
    armed, read = (names.index("final_chunk_launched"),
                   names.index("first_token_read"))
    return names, armed, read, log[armed][1]


@pytest.mark.parametrize("steps", [None, 1, 2, 4])
def test_first_token_returns_through_the_pipeline_in_order(paged,
                                                           monkeypatch,
                                                           steps):
    """An arrival beside a decoding stream. (1) The decode step behind the
    final chunk is launched BEFORE that chunk's token is read on the host:
    nothing on the path waits for the device. (2) The rows of every
    dispatch launched before the chunk (the one in flight at the arrival
    among them) are delivered BEFORE the new request's first token, those
    launched behind it after. (3) The counters say so: no blocking read,
    two programs for this one-chunk admission. Whatever the dispatch's step
    count: the scheduler's own choice (which follows the host's load), and
    1, 2 and 4 pinned."""
    s, _ = paged
    log, before, after, spent = _arrival_log(s, monkeypatch, steps)
    assert spent < 80, "the keeper ran out before the arrival was served"
    names, armed, read, seq = _around_the_chunk(log)
    assert "decode_launch" in names[armed:read], log[armed:read + 1]
    assert all(q <= seq for n, q in log[:read] if n == "rows")
    assert [q for n, q in log[read:] if n == "rows"][0] == seq + 1
    # a dispatch WAS in flight at the arrival, and was read after the launch
    assert any(n == "rows" and q <= seq for n, q in log[armed:read])
    assert after["admissions"] - before["admissions"] == 1
    assert after["admit_blocking_reads"] == before["admit_blocking_reads"]
    assert after["admit_programs"] - before["admit_programs"] == 2
    assert s.flight.snapshot(limit=400) and all(
        r["sync_ms"] == 0.0 for r in s.flight.snapshot(limit=400)
        if r["program"] == "prefill_chunk")


def test_a_spy_on_step_async_alone_misses_a_two_step_dispatch(paged,
                                                              monkeypatch):
    """Why the test above came and went with the machine's load until
    PR 37: its spy sat on ``runner.step_async`` ALONE. With two steps a
    dispatch (pinned here; a loaded host makes ``_effective_steps`` choose
    it) every decode launch goes through ``step_n_async``, the log holds no
    launch at all, and (1) above reads as broken though the order is sound:
    the rows still arrive around the first token in dispatch order."""
    s, _ = paged
    log, _, _, spent = _arrival_log(s, monkeypatch, steps=2,
                                    spy_on_step_n=False)
    assert spent < 80
    names, armed, read, seq = _around_the_chunk(log)
    assert "decode_launch" not in names
    assert all(q <= seq for n, q in log[:read] if n == "rows")
    assert [q for n, q in log[read:] if n == "rows"][0] == seq + 1


def test_a_constrained_first_token_is_waited_for_and_counted(paged):
    """Where the next dispatch needs the token (an FSM's mask), the host
    still waits for it, and the counter says it did."""
    s, _ = paged
    before = s.metrics()["admit_blocking_reads"]
    h = s.generate(_req("tool", max_new_tokens=6, temperature=0.0,
                        constraint=_Band(3)))
    assert len(h.token_ids) == 3 and all(60 <= t < 80 for t in h.token_ids)
    assert s.metrics()["admit_blocking_reads"] == before + 1


def test_contiguous_admission_keeps_and_counts_its_reads(sched):
    """The contiguous layout cannot know a free slot's frontier on the
    host and returns its first token from admit(): two blocking reads an
    admission, counted."""
    before = sched.metrics()
    sched.generate(_req("contiguous", max_new_tokens=3, temperature=0.0))
    after = sched.metrics()
    assert after["admissions"] - before["admissions"] == 1
    assert (after["admit_blocking_reads"]
            - before["admit_blocking_reads"]) == 2


def test_host_mirror_of_free_frontiers_equals_the_device(tiny):
    """free_frontiers() answers from the host what slot_positions() reads
    from the device, for every free slot: after a release, after
    load_prefix moved a free slot's frontier, after a rebuild."""
    r = ModelRunner(tiny.cfg, tiny.params, **PAGED_KW)

    def agree(slots):
        host, device = r.free_frontiers(), r.slot_positions()
        assert [int(host[s]) for s in slots] == [
            int(device[s]) for s in slots]
        return [int(host[s]) for s in slots]

    assert agree(r.free_slots()) == [0, 0, 0, 0]
    a, b = r.acquire_slot(), r.acquire_slot()
    prompt = list(b"rows that outlive their slot in a file")
    r.admit(a, prompt, temperature=0.0)
    r.admit(b, list(b"another"), temperature=0.0)
    for _ in range(3):
        r.step()
    exported = r.export_prefix(a, 32)
    r.release(a)
    assert agree(r.free_slots()) == [0, 0, 0]      # b still decodes at 10
    assert r.load_prefix(a, exported, 32)
    assert agree([a]) == [32]
    r.release(a)        # what the scheduler does when the admission fails
    assert agree([a]) == [0]
    r.release(b)
    r.reinit()
    assert agree(r.free_slots()) == [0, 0, 0, 0]


@pytest.mark.parametrize("seed", [None, 0, 2147503333, 2**32 + 9])
def test_one_program_arms_a_slot_as_the_eager_updates_did(tiny, seed):
    """The arming program's state is, leaf for leaf, what the parent's
    eager updates left: seven ``.at[slot].set`` of SamplingParams.with_slot,
    the key of the seed (the slot's own where there is none), the bias row,
    the slot's block-table row."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    r = ModelRunner(tiny.cfg, tiny.params, **PAGED_KW)
    slot, kw = 2, dict(temperature=0.7, top_k=11, min_p=0.05,
                       presence_penalty=0.25)
    row = np.linspace(-1, 1, r.cfg.vocab_size).astype(np.float32)
    table_row = np.arange(r.max_blocks, dtype=np.int32)[::-1].copy()
    st = r.state
    want = dataclasses.replace(
        st, params=st.params.with_slot(slot, **kw),
        keys=(st.keys if seed is None
              else st.keys.at[slot].set(jax.random.key(seed))),
        bias=st.bias.at[slot].set(jnp.asarray(row)))
    want_tables = r.block_tables.at[slot].set(jnp.asarray(table_row))
    want = jax.tree.map(
        lambda a: np.asarray(jax.random.key_data(a) if jnp.issubdtype(
            a.dtype, jax.dtypes.prng_key) else a), want)
    want_tables = np.asarray(want_tables)
    r._arm(r._arm_args(slot, seed=seed, bias_row=row, **kw), table_row)
    got = jax.tree.map(
        lambda a: np.asarray(jax.random.key_data(a) if jnp.issubdtype(
            a.dtype, jax.dtypes.prng_key) else a), r.state)
    for w, g in zip(jax.tree.leaves(want), jax.tree.leaves(got)):
        np.testing.assert_array_equal(w, g)
    np.testing.assert_array_equal(want_tables, np.asarray(r.block_tables))


def test_an_admission_launches_two_programs_and_a_release_one(tiny,
                                                              monkeypatch):
    """A one-chunk admission is the arming update and the chunk, a release
    one program, and nothing runs eagerly beside them (every eager
    ``.at[].set`` or conversion was a program of its own on every chip)."""
    from jax._src import dispatch

    r = ModelRunner(tiny.cfg, tiny.params, **PAGED_KW)
    slot = r.acquire_slot()
    r.admit(slot, list(b"warm the programs"), temperature=0.5, seed=3)
    r.step()
    r.release(slot)
    launched = []
    for name in ("_arm_slot", "_release_slot", "_prefill_paged",
                 "_decode_paged"):
        def counted(*a, _f=getattr(r, name), _n=name, **k):
            launched.append(_n)
            return _f(*a, **k)
        monkeypatch.setattr(r, name, counted)
    real = dispatch.xla_primitive_callable

    def eager(prim, **params):
        launched.append(f"eager:{prim.name}")
        return real(prim, **params)

    monkeypatch.setattr(dispatch, "xla_primitive_callable", eager)
    slot = r.acquire_slot()
    programs = r.admit_programs
    adm = r.begin_admit(slot, list(b"one chunk"), temperature=0.5, seed=4,
                        bias_row=np.zeros(r.cfg.vocab_size, np.float32))
    assert launched == []           # begin_admit is the host's alone
    assert adm.launch_chunk()
    assert launched == ["_arm_slot", "_prefill_paged"]
    assert r.admit_programs - programs == 2
    r.step_async()
    del launched[:]
    r.release(slot)
    assert launched == ["_release_slot"]
    monkeypatch.undo()
    assert adm.first_token() >= 0


# -- what a launch holds (PR 39): the flight ring's launch number and counts --


_HELD_RUNS = iter(range(1000))


def _held_run(s: Scheduler, monkeypatch, steps):
    """Three prompts of known lengths, the second sharing the first's two
    leading blocks, served one after the other beside a decoding keeper,
    with every decode launch logged AS THE DEVICE STOOD when its program
    was enqueued: each live slot's frontier, read from the state the newest
    program returns (the read waits for what is in flight; the host's
    mirrors still lack it). Returns (the ring rows of this run by launch
    number, the log by launch number: (k, [(handle, frontier, tokens the
    host had counted)]), [(prompt tokens, tokens the prefix pool served)])."""
    if steps is not None:
        monkeypatch.setattr(s, "_effective_steps",
                            lambda pipelined=True: steps)
    log = {}

    def spy(real):
        def launch(*n):
            at = np.asarray(s.runner.state.positions)
            log[s._launch_seq] = (n[0] if n else 1, [
                (c.handle, int(at[slot]), c.handle.completion_tokens)
                for slot, c in s._slots.items()])
            return real(*n)
        return launch

    monkeypatch.setattr(s.runner, "step_async", spy(s.runner.step_async))
    monkeypatch.setattr(s.runner, "step_n_async",
                        spy(s.runner.step_n_async))
    # (a last chunk that rides a step is launched with it: the new slot is
    # not among the step's, and is installed behind the launch)
    ride = s._launch_ride
    monkeypatch.setattr(s, "_launch_ride",
                        lambda pf, held: spy(lambda: ride(pf, held))())
    mark = s._launch_seq
    keeper = _keeper(s)
    # the prefix pool outlives a run: each run's prompts open with its own tag
    tag = f"run {next(_HELD_RUNS):03d} "
    first = tag + "a prompt of forty tokens, shared"    # 40 bytes
    assert len(first) == 40
    prompts = [first, first[:32] + "then its own", tag + "x" * 62]
    served = []
    for text in prompts:
        reused = s.runner.total_prefix_reused
        h = s.generate(_req(text, max_new_tokens=5, ignore_eos=True,
                            **GREEDY), timeout=120)
        assert h.finish_reason == "length"
        served.append((h.prompt_tokens, s.runner.total_prefix_reused - reused))
    keeper.cancel()
    keeper.result(60)
    assert _wait(lambda: not s.busy)
    rows = {r["launch"]: r for r in s.flight.snapshot() if r["launch"] > mark}
    return rows, log, served


@pytest.mark.parametrize("steps", [None, 2], ids=["own_k", "k_2"])
def test_a_decode_row_holds_what_its_launch_held(paged, monkeypatch, steps):
    """``live_slots`` and ``attended_tokens`` of every decode row are the
    device's own counts AT THE LAUNCH (a slot whose frontier stands at p
    attends p + 1 + j in step j), not the drain's one pipelined dispatch
    later, and not the host mirror's, which lacks what is still in flight;
    a stream whose last token comes in the dispatch is in it; every other
    count of a decode row is 0."""
    s, _ = paged
    rows, log, _ = _held_run(s, monkeypatch, steps)
    decode = {n: r for n, r in rows.items() if r["program"].startswith("decode")}
    assert len(decode) > 10 and set(decode) <= set(log)
    ended_inside = lagged = 0
    for n, row in decode.items():
        k, held = log[n]
        assert row["steps"] == k and (steps is None or k == steps)
        assert row["live_slots"] == len(held) > 0
        assert row["attended_tokens"] == sum(
            at + 1 + j for _, at, _ in held for j in range(k))
        # every other count of a decode row is 0; a row whose step carried
        # a prompt's last chunk (``decode_chunk``) holds the chunk's too
        assert all(bool(row[c]) == (row["program"] == "decode_chunk")
                   for c in ("chunk_tokens", "chunk_bucket", "chunk_ctx",
                             "chunk_parts"))
        done = [(h, at + 1 - h.prompt_tokens, seen) for h, at, seen in held]
        ended_inside += sum(
            h.finish_reason == "length" and g < h.completion_tokens == 5 <= g + k
            for h, g, _ in done)            # the keeper's limit is 80
        lagged += any(g > seen for _, g, seen in done)
    assert ended_inside == 3       # each of the three requests' last token
    assert lagged                  # the mirror alone would have undercounted
    # the drain would have counted otherwise: some row's launch held MORE
    # streams than were left when its tokens had been processed
    assert any(r["live_slots"] > round(r["occupancy"] * s.runner.num_slots)
               for r in decode.values())


@pytest.mark.parametrize("steps", [None, 2], ids=["own_k", "k_2"])
def test_live_slots_are_the_rows_off_the_trash_block(paged, monkeypatch,
                                                     steps):
    """PR 50: the paged kernel does no work for a slot whose frontier entry
    ``tables[s, pos // bt]`` is block 0. At every decode launch that test,
    made on the device table and frontiers as the launch finds them, names
    as many slots as the ring's ``live_slots``: an admission between two
    of its chunks is still on the trash block (its row is installed with
    the arming, before the last chunk), a released slot is back on it, a
    slot armed again is off it. So 100 less ``runner.occupancy_mean`` is
    the share of programs the kernel skips."""
    s, _ = paged
    r = s.runner
    bt = r.allocator.block_tokens
    off_trash = {}

    def spy(real):
        def launch(*n):
            tables = np.asarray(r.block_tables)
            at = np.minimum(np.asarray(r.state.positions) // bt,
                            tables.shape[1] - 1)
            off_trash[s._launch_seq] = tables[np.arange(len(at)), at] != 0
            return real(*n)
        return launch

    monkeypatch.setattr(r, "step_async", spy(r.step_async))
    monkeypatch.setattr(r, "step_n_async", spy(r.step_n_async))
    # (a chunk that rides a step: its slot's table row goes up with the
    # launch and the program puts it back on the trash block for the step)
    ride = s._launch_ride
    monkeypatch.setattr(s, "_launch_ride",
                        lambda pf, held: spy(lambda: ride(pf, held))())
    rows, log, _ = _held_run(s, monkeypatch, steps)
    decode = {n: row for n, row in rows.items()
              if row["program"].startswith("decode")}
    assert len(decode) > 10 and set(decode) <= set(off_trash)
    for n, row in decode.items():
        assert row["live_slots"] == off_trash[n].sum() == len(log[n][1]) > 0
    # between two chunks of one prompt a decode step ran, the prompt's slot
    # not among its live ones; slots left the batch and came back
    order = sorted(rows)
    programs = [rows[n]["program"] for n in order]
    between = [n for i, n in enumerate(order[1:-1], 1)
               if n in decode and programs[i - 1] == programs[i + 1]
               == "prefill_chunk" and rows[order[i + 1]]["chunk_offset"] > 0]
    assert between
    held = np.stack([off_trash[n] for n in sorted(decode)]).astype(int)
    assert held.sum(1).min() == 1 and held.sum(1).max() == 2
    assert (np.diff(held, axis=0) == 1).sum() >= 3      # armed (again)


@pytest.mark.parametrize("steps", [None, 2], ids=["own_k", "k_2"])
def test_a_prefill_row_holds_its_chunk(paged, monkeypatch, steps):
    """Sum of ``chunk_tokens`` = the prompts' tokens less what the prefix
    pool served; a prompt's chunks run from the tokens the pool served in
    steps of ``prefill_chunk``; the bucket is the runner's and holds the
    chunk; the attend spans the padded context; ``launch`` is unique and
    increasing over all rows, whatever their kind."""
    s, _ = paged
    r = s.runner
    rows, _, served = _held_run(s, monkeypatch, steps)
    order = sorted(rows)
    assert order == [rows[n]["launch"] for n in order] and order[0] > 0
    assert len(set(order)) == len(rows)
    # (a prompt's last chunk may have ridden a decode step: ``decode_chunk``)
    kinds = ("prefill_chunk", "decode_chunk")
    ts = [rows[n]["ts"] for n in order if rows[n]["program"] == "prefill_chunk"]
    assert ts == sorted(ts)
    chunks = [rows[n] for n in order if rows[n]["program"] in kinds]
    assert served[1][1] == 32 and served[0][1] == served[2][1] == 0
    want = [(6, 0)]                                 # the keeper's one chunk
    for prompt, reused in served:
        want += [(min(r.prefill_chunk, prompt - at), at)
                 for at in range(reused, prompt, r.prefill_chunk)]
    assert [(c["chunk_tokens"], c["chunk_offset"]) for c in chunks] == want
    assert sum(c["chunk_tokens"] for c in chunks) == 6 + sum(
        p - reused for p, reused in served)
    for c in chunks:
        assert c["chunk_bucket"] in r.buckets
        assert c["chunk_bucket"] == r.bucket_for(c["chunk_tokens"])
        assert c["chunk_ctx"] == r.ctx_pad == 96
        if c["program"] == "prefill_chunk":
            assert c["live_slots"] == c["attended_tokens"] == 0
        else:
            assert c["live_slots"] > 0 and c["chunk_bucket"] <= 128


# ---------------------------------------------------------------------------
# a ring row accounts for its own wall (PR 53): the measured parts of gap,
# and the engine thread's own clocks


def _lost_s(s: Scheduler) -> float:
    """What a test loses in ONE dispatch to see it counted slow: 60 ms, and
    on a machine so loaded that a step of ``debug:tiny`` is over 12 ms, five
    of its steps by the scheduler's own EMA (a row is slow past
    ``SLOW_FACTOR`` = 4 times its steps' time)."""
    return max(0.06, 5.0 * (s._step_ema_prior or 0.0))


def _states(r) -> float:
    return (r["wait_ms"] + r["idle_ms"] + r["cpu_ms"] + (r["runq_ms"] or 0.0)
            + r["blocked_ms"])


def _served_rows(s: Scheduler, text: str, n: int = 12) -> list[dict]:
    """The ring rows of one request served beside a keeper."""
    mark = s._launch_seq
    keeper = _keeper(s)
    h = s.generate(_req(text, max_new_tokens=n, stream=True, ignore_eos=True,
                        **GREEDY), timeout=120)
    assert h.finish_reason == "length"
    keeper.cancel()
    keeper.result(60)
    assert _wait(lambda: not s.busy)
    return [r for r in s.flight.snapshot() if r["launch"] > mark]


def test_every_row_of_a_served_request_accounts_for_its_wall(paged):
    """``process_ms + book_ms + free_ms <= gap_ms`` and ``wait + idle + cpu + runq +
    blocked == span`` to a microsecond for every row, chunks and decode
    steps alike (1e-3 ms: the snapshot's rounding); a decode row measured
    both parts, and its wait is the drain's own ``sync_ms`` unless a first
    token was read in its span; a chunk's row leaves the parts to the next
    decode row and tiles its wall into staging and enqueue."""
    s, _ = paged
    rows = _served_rows(s, "a prompt of two chunks, to be staged")
    kinds = {r["program"] for r in rows}
    assert "prefill_chunk" in kinds and kinds & {"decode", "decode_n"}
    for r in rows:
        assert (r["process_ms"] + r["book_ms"] + r["free_ms"]
                <= r["gap_ms"] + 1e-3), r
        assert _states(r) == pytest.approx(r["span_ms"], abs=1e-3), r
        assert r["span_ms"] > 0.0 and r["proc_cpu_ms"] >= 0.0
    decode = [r for r in rows if r["program"].startswith("decode")
              and not r["compile"]]
    assert len(decode) > 10
    assert sum(r["process_ms"] > 0 and r["book_ms"] > 0
               for r in decode) > len(decode) // 2
    assert sum(abs(r["wait_ms"] - r["sync_ms"]) < 2e-3
               for r in decode) > len(decode) // 2
    for c in (r for r in rows if r["program"] == "prefill_chunk"):
        assert c["process_ms"] == c["book_ms"] == c["free_ms"] == 0.0
        assert c["gap_ms"] == 0.0
        assert c["sched_ms"] + c["launch_ms"] == pytest.approx(
            c["dispatch_ms"], abs=2e-3)
    m = s.metrics()
    assert set(m["engine_thread_seconds"]) == {
        "cpu", "runq", "blocked", "wait", "idle"}
    assert m["engine_thread_seconds"]["cpu"] > 0
    assert set(m["dispatch_phase_ms"]) >= {"gap", "process", "book", "free"}


class _Tokens:
    """A launch's result that takes ``wait_s`` of the scripted clock to
    arrive."""

    def __init__(self, real, clock, wait_s):
        self.real, self.clock, self.wait_s = real, clock, wait_s

    def copy_to_host_async(self):
        self.real.copy_to_host_async()

    def __array__(self, *a, **kw):
        self.clock.t += self.wait_s
        return np.asarray(self.real)


def test_the_old_time_columns_are_the_parents_under_a_scripted_clock(
        paged, monkeypatch):
    """``time.monotonic`` scripted: it moves only where this test moves it
    (an admission 0.5 ms, an enqueue 2 ms, a result 5 ms, the tokens'
    processing 1 ms), so the number of times the loop READS it changes
    nothing. A steady pipelined dispatch is then 8.5 ms: sync 5, launch 2,
    sched 0.5, gap 1.0, and the sample given to ``_observe_host_time`` 3.5
    ms; the request's first decode row (two launches and the chunk before
    one record) and its last (drained with no slot left: timed from its
    issue, over the step before it) read what the parent's clamps give:
    the same digits as commit 5591afe gives this test's body, run there."""
    import time as real_time

    from localai_tpu.engine import scheduler as sched_mod

    s, _ = paged
    assert _wait(lambda: not s.busy)

    class Script:
        t = 5000.0

        def monotonic(self):
            return self.t

        def __getattr__(self, name):
            return getattr(real_time, name)

    clock = Script()

    def moves(real, by, wrap=None):
        def moved(*a, **kw):
            out = real(*a, **kw)
            clock.t += by
            return wrap(out) if wrap else out
        return moved

    monkeypatch.setattr(sched_mod, "time", clock)
    admit = s._admit_pending
    monkeypatch.setattr(
        s, "_admit_pending",
        lambda: (admit(), setattr(clock, "t", clock.t + 0.0005))[0])
    for name in ("step_async", "step_n_async"):
        monkeypatch.setattr(s.runner, name, moves(
            getattr(s.runner, name), 0.002,
            lambda toks: _Tokens(toks, clock, 0.005)))
    monkeypatch.setattr(s, "_process_rows", moves(s._process_rows, 0.001))
    # one step a dispatch: the EMAs the tests before this one left, under a
    # loaded host, can choose k = 2 for the first dispatches (_arrival_log)
    monkeypatch.setattr(s, "_effective_steps", lambda pipelined=True: 1)
    samples = []
    observe = s._observe_host_time
    monkeypatch.setattr(s, "_observe_host_time",
                        lambda v: (samples.append(v), observe(v))[1])
    mark = s._launch_seq
    h = s.generate(_req("scripted", max_new_tokens=8, stream=True,
                        ignore_eos=True, **GREEDY), timeout=120)
    assert h.finish_reason == "length" and _wait(lambda: not s.busy)
    # the step that was in flight when the stream ended writes its row too
    assert _wait(lambda: s.flight.snapshot()[-1]["launch"] == s._launch_seq)
    rows = [r for r in s.flight.snapshot() if r["launch"] > mark
            and r["program"] == "decode" and not r["compile"]]
    old = [tuple(r[c] for c in ("dispatch_ms", "gap_ms", "sched_ms",
                                "launch_ms", "sync_ms")) for r in rows]
    first, steady, last = ((7.5, 0.0, 0.0, 2.5, 5.0),
                           (8.5, 1.0, 0.5, 2.0, 5.0),
                           (13.5, 8.0, 0.5, 0.0, 5.0))
    assert len(samples) == len(old)     # one a non-compile decode row
    host_ms = [round(v * 1e3, 6) for v in samples]
    if old[0] == first:     # the k = 1 program had run before: no compile
        assert host_ms[0] == 2.5
        old, host_ms, rows = old[1:], host_ms[1:], rows[1:]
    assert len(old) >= 5 and set(old[:-1]) == {steady} and old[-1] == last
    assert host_ms == [3.5] * (len(old) - 1) + [8.5]
    # and the new columns beside them: the scripted millisecond of token
    # processing, measured, inside gap
    assert {r["process_ms"] for r in rows} == {1.0}
    assert {r["book_ms"] + r["free_ms"] for r in rows} == {0.0}     # stood still


@pytest.mark.parametrize("owner", ["wait", "blocked", "cpu"])
def test_a_stalled_row_names_its_owner(paged, monkeypatch, owner):
    """60 ms lost once in a decode dispatch: inside the drain's wait (the
    device answered late), asleep in the tokens' processing (a lock, the
    GIL), or computing there (our Python). The ring's worst row says which,
    ``process_ms`` holds the two that were in ``_process_rows``, and
    ``localai_slow_dispatch_total{owner}`` counts the row once.

    What a loaded machine decides is held to what the test injects: the loss
    is 60 ms or five of THIS machine's steps (``_lost_s``), and a thread
    that computes beside others waits for a core about as long as it
    computes, so the row it computed in may be ``runq``'s, counted once all
    the same."""
    import time

    from localai_tpu import faults
    from localai_tpu.faults import FaultSpec
    from localai_tpu.obs.metrics import Registry, update_engine_gauges

    s, _ = paged
    assert _wait(lambda: not s.busy)
    before = dict(s.slow_dispatches)
    keeper = _keeper(s)
    mark = s.flight.snapshot()[-1]["ts"]
    done = []

    def lose_60ms(*a, _real=s._process_rows, **kw):
        if not done:
            done.append(1)
            if owner == "blocked":
                time.sleep(_lost_s(s))
            else:
                t0, lost = time.thread_time(), _lost_s(s)
                while time.thread_time() - t0 < lost:
                    pass
        return _real(*a, **kw)

    try:
        if owner == "wait":
            faults.arm(FaultSpec(site="engine.drain", mode="sleep",
                                 delay_s=_lost_s(s), times=1))
        else:
            monkeypatch.setattr(s, "_process_rows", lose_60ms)
        assert _wait(lambda: any(
            r["span_ms"] - r["idle_ms"] >= 60.0
            for r in s.flight.snapshot(since=mark)))
        # read before the keeper goes: a slot's first release may compile
        rows = s.flight.snapshot(since=mark)
        gained = {st: n - before[st] for st, n in s.slow_dispatches.items()}
    finally:
        faults.clear()
        keeper.cancel()
        keeper.result(60)
    assert _wait(lambda: not s.busy)
    states = ("wait", "cpu", "runq", "blocked")
    owners = ("cpu", "runq") if owner == "cpu" else (owner,)

    def owned(r) -> bool:
        return max(states, key=lambda st: r[f"{st}_ms"] or 0.0) in owners

    worst = max(rows, key=lambda r: r["span_ms"] - r["idle_ms"])
    assert worst["program"].startswith("decode")
    assert worst[f"{owner}_ms"] >= 59.0 and owned(worst)
    assert worst[f"{owner}_ms"] == max(
        worst[f"{st}_ms"] or 0.0 for st in states if st != "runq")
    assert _states(worst) == pytest.approx(worst["span_ms"], abs=1e-3)
    if owner == "wait":
        assert worst["sync_ms"] >= 59.0 and worst["process_ms"] < 50.0
    else:
        assert worst["process_ms"] >= 50.0 and worst["gap_ms"] >= 59.0
    # once a row: as many as the rows that lost 50 ms and more to that state
    # (one; a collection of this process's garbage collector may add its own)
    lost = [r for r in rows if not r["compile"]
            and r["program"].startswith("decode")
            and (r[f"{owner}_ms"] or 0.0) >= 50.0 and owned(r)]
    assert sum(gained[st] for st in owners) == len(lost) >= 1, (gained, lost)
    reg = Registry()
    update_engine_gauges("tiny", s.metrics(), registry=reg)
    text = reg.render()
    counted = max(owners, key=s.slow_dispatches.get)
    assert (f'localai_slow_dispatch_total{{model="tiny",owner="{counted}"}} '
            f'{s.slow_dispatches[counted]}\n') in text
    assert 'localai_engine_thread_seconds_total{model="tiny",state="wait"}' \
        in text


def test_a_synchronous_row_reads_its_device_wait_as_wait(paged, monkeypatch):
    """A constrained stream decodes through the runner's synchronous step,
    which waits for the device inside the call: the row's ``wait_ms`` is the
    runner's own ``last_sync_ms``, as a pipelined row's is its drain, and a
    device that answers 60 ms late there (``_lost_s``: five steps, where
    this machine's steps are that long) is a slow dispatch that ``wait``
    owns, not a starved engine thread."""
    import time

    s, _ = paged
    assert _wait(lambda: not s.busy)
    before, mark = dict(s.slow_dispatches), s._launch_seq
    step, late = s.runner.step, []

    def slow_device():
        out = step()
        if mark + 4 < s._launch_seq and not late:
            late.append(_lost_s(s))
            time.sleep(late[0])
            s.runner.last_sync_ms += late[0] * 1e3
        return out

    monkeypatch.setattr(s.runner, "step", slow_device)
    h = s.generate(_req("one constrained stream", max_new_tokens=12,
                        constraint=_Band(12), **GREEDY), timeout=120)
    assert h.completion_tokens == 12 and late and _wait(lambda: not s.busy)
    rows = [r for r in s.flight.snapshot() if r["launch"] > mark
            and r["program"] == "decode" and not r["compile"]]
    assert len(rows) >= 10
    for r in rows:      # the first row's span holds the first token's read
        assert r["wait_ms"] >= r["sync_ms"] - 2e-3 and r["sync_ms"] > 0.0, r
        assert _states(r) == pytest.approx(r["span_ms"], abs=1e-3), r
    assert sum(abs(r["wait_ms"] - r["sync_ms"]) < 2e-3
               for r in rows) >= len(rows) - 1
    worst = max(rows[1:], key=lambda r: r["span_ms"])
    assert worst["wait_ms"] >= 60.0 > worst["blocked_ms"]
    gained = {st: n - before[st] for st, n in s.slow_dispatches.items()}
    assert gained["wait"] >= 1, gained


def test_the_thread_clock_is_reopened_by_a_rebuild_and_may_be_unreadable(
        paged, monkeypatch, tmp_path):
    """``thread-self`` resolves at ``open``: each engine thread opens its own
    descriptor at the start of its ``_run`` and closes it at the end, a
    rebuild's fresh thread among them. Where the kernel's file is not there
    the rows read ``runq_ms`` null, the five states still sum to the span
    and nothing raises. LAST in this file: a rebuild empties the pool."""
    from localai_tpu.obs import flight as obs_flight

    s, _ = paged
    assert _wait(lambda: not s.busy)
    first = s._clock
    assert first._fd is not None
    assert all(r["runq_ms"] is not None
               for r in _served_rows(s, "the kernel's file is read", 4))
    monkeypatch.setattr(obs_flight, "SCHEDSTAT", str(tmp_path / "absent"))
    s.rebuild()
    rows = _served_rows(s, "and here it is not there", 4)
    assert s._clock is not first and first._fd is None     # closed
    assert s._clock._fd is None
    assert rows and all(r["runq_ms"] is None for r in rows)
    for r in rows:
        assert _states(r) == pytest.approx(r["span_ms"], abs=1e-3)
    monkeypatch.undo()
    s.rebuild()
    assert _served_rows(s, "read again", 4)[-1]["runq_ms"] is not None
    assert s._clock._fd is not None


# ---------------------------------------------------------------------------
# a prompt's small last chunk rides the decode step (PR 59): who steps aside


def _aside_runner(tiny, monkeypatch, why):
    import jax

    kw = dict(PAGED_KW)
    cfg, params = tiny.cfg, tiny.params
    if why == "own_forward":        # a family's own forward: recurrent state
        import families
        from test_falcon_h1 import HF

        from localai_tpu.models import llama as mdl

        cfg = families.config(HF, "float32")
        params = mdl.init_params(jax.random.key(0), cfg)
        kw["attn_impl"] = "xla"
    elif why == "overlap_mode":     # the manual-TP trunk of a mesh
        from localai_tpu.parallel import sharding as shd
        from localai_tpu.parallel.mesh import MeshPlan, build_mesh

        monkeypatch.setenv("LOCALAI_MESH_OVERLAP", "auto")
        kw["mesh"] = build_mesh(MeshPlan(model=2), devices=jax.devices()[:2])
        params = shd.shard_params(params, cfg, kw["mesh"])
    elif why == "contiguous":
        kw = dict(num_slots=4, max_ctx=96, prefill_buckets=[16, 32],
                  kv_dtype="float32")
    elif why == "bucket_512":
        kw.update(max_ctx=768, prefill_buckets=[128, 512], prefill_chunk=512,
                  kv_block_tokens=64)
    r = ModelRunner(cfg, params, **kw)
    assert r.own_forward == (why == "own_forward")
    assert bool(r.overlap_mode) == (why == "overlap_mode")
    assert r.rides == (why not in ("own_forward", "overlap_mode",
                                   "contiguous"))
    return r


@pytest.mark.parametrize("why", [
    "own_forward", "overlap_mode", "contiguous", "bucket_512", "k_2",
    "constrained", "constrained_neighbour", "idle", None])
def test_who_steps_aside_from_the_ride(tiny, monkeypatch, why):
    """One rule, by what the loop holds when a prompt's last chunk is the
    head of the queue: the chunk rides the decode step (None: the control)
    unless the runner's programs cannot (a family's own forward, the
    manual-TP trunk, the contiguous cache), its bucket is over
    ``RIDE_ROWS``, the dispatch is of more than one step, the request or a
    neighbour is under a constraint (the synchronous branch), or no stream
    is decoding. Whoever steps aside is served as before: ``chunk_rides``
    stays 0 and ``prefill_chunks`` counts."""
    r = _aside_runner(tiny, monkeypatch, why)
    s = Scheduler(r, ByteTokenizer(), multi_step=1)
    if why == "k_2":
        monkeypatch.setattr(s, "_effective_steps", lambda pipelined=True: 2)
    paged = why != "contiguous"
    try:
        keeper = None
        if why != "idle":
            keeper = s.submit(_req(
                "keeper", max_new_tokens=70, stream=True, ignore_eos=True,
                constraint=(_Band(70) if why == "constrained_neighbour"
                            else None), **GREEDY))
            assert _wait(lambda: keeper.completion_tokens >= 3)
        text = "x" * 150 if why == "bucket_512" else "an arrival"
        h = s.generate(_req(
            text, max_new_tokens=4, ignore_eos=True,
            constraint=_Band(4) if why == "constrained" else None, **GREEDY),
            timeout=120)
        assert h.finish_reason in ("length", "stop")
        assert len(h.token_ids) == 4
        if keeper is not None:
            keeper.cancel()
            keeper.result(60)
        chunks = s.total_prefill_chunks
        m = s.metrics()
    finally:
        s.shutdown()
    rides = int(why is None)
    assert s.total_chunk_rides == rides
    assert chunks == (2 - (why == "idle") if paged else 0)
    if paged:
        assert (m["chunk_rides"], m["prefill_chunks"]) == (rides, chunks)
    programs = [row["program"] for row in s.flight.snapshot()]
    assert ("decode_chunk" in programs) == bool(rides)


def test_a_ride_is_no_sample_of_a_steps_time_or_of_its_host_work(
        tiny, monkeypatch):
    """The two EMAs that size the next dispatch (``_effective_steps``) are
    fed by plain pipelined steps alone: a ``decode_chunk`` row lasts a chunk
    longer on the device AND holds the admission's host work in its launch
    (PR 61: with the host just under the step, rides in a burst carried
    ``_host_ema`` over ``_step_ema`` and the loop compiled a two-step
    program inside the measured window)."""
    r = ModelRunner(tiny.cfg, tiny.params, **PAGED_KW)
    s = Scheduler(r, ByteTokenizer(), multi_step=1)
    fed = {"_observe_step_time": 0, "_observe_host_time": 0}
    for name in fed:
        real = getattr(s, name)
        monkeypatch.setattr(s, name, lambda v, name=name, real=real: (
            fed.__setitem__(name, fed[name] + 1), real(v))[1])
    try:
        keeper = s.submit(_req("keeper", max_new_tokens=40, stream=True,
                               ignore_eos=True, **GREEDY))
        assert _wait(lambda: keeper.completion_tokens >= 3)
        for text in ("one arrival", "and another"):
            s.generate(_req(text, max_new_tokens=3, ignore_eos=True,
                            **GREEDY), timeout=120)
        keeper.result(60)
        assert _wait(lambda: not s.busy)
        assert _wait(
            lambda: s.flight.snapshot()[-1]["launch"] == s._launch_seq)
    finally:
        s.shutdown()
    rows = [x for x in s.flight.snapshot(limit=512) if not x["compile"]]
    plain = sum(x["program"] == "decode" for x in rows)
    assert s.total_chunk_rides == 2
    assert sum(x["program"] == "decode_chunk" for x in rows) >= 1
    assert fed["_observe_host_time"] == plain
    assert fed["_observe_step_time"] <= plain


def test_a_cancelled_head_admission_is_dropped_not_ridden(tiny, monkeypatch):
    """An admission cancelled while its last chunk waits at the head of the
    queue, streams decoding beside it: it is dropped where a cancelled
    admission always was (``_step_prefill_chunk``: blocks freed, slot back,
    ``cancelled``), not launched with the step."""
    r = ModelRunner(tiny.cfg, tiny.params, **PAGED_KW)
    s = Scheduler(r, ByteTokenizer(), multi_step=1)
    start = s._start

    def cancel_once_queued(slot, handle, positions=None):
        ok = start(slot, handle, positions)
        if ok and handle.request.max_new_tokens == 5:
            assert s._prefills[-1].adm.ride_bucket == 16 and s._slots
            handle.cancel()
        return ok

    monkeypatch.setattr(s, "_start", cancel_once_queued)
    try:
        keeper = s.submit(_req("keeper", max_new_tokens=60, stream=True,
                               ignore_eos=True, **GREEDY))
        assert _wait(lambda: keeper.completion_tokens >= 3)
        free = r.allocator.stats().free
        h = s.submit(_req("cancelled", max_new_tokens=5, **GREEDY))
        assert h.result(60).finish_reason == "cancelled" and not h.token_ids
        assert _wait(lambda: r.allocator.stats().free == free)
        assert len(r.free_slots()) == 3
        after = s.generate(_req("the next one", max_new_tokens=4,
                                ignore_eos=True, **GREEDY), timeout=60)
        assert after.finish_reason == "length"
        keeper.cancel()
        keeper.result(60)
    finally:
        s.shutdown()
    # the cancelled admission launched nothing; the next one rode
    assert (s.total_chunk_rides, s.total_prefill_chunks) == (1, 2)
    assert r.allocator.check_invariants() == []


def test_a_ride_that_fails_to_launch_leaves_its_admission_queued(
        tiny, monkeypatch):
    """The ride's launch raises (a program that does not compile): the
    engine fails the streams that were decoding, as for any decode launch
    that raises, and the admission, still at the head of the queue, is
    served by the plain chunk into the now idle engine."""
    r = ModelRunner(tiny.cfg, tiny.params, **PAGED_KW)
    s = Scheduler(r, ByteTokenizer(), multi_step=1)
    calls = []

    def no_program(*a, **k):
        calls.append(k["bucket"])
        raise RuntimeError("the ride does not compile")

    monkeypatch.setattr(r, "_decode_prefill_paged", no_program)
    try:
        keeper = s.submit(_req("keeper", max_new_tokens=60, stream=True,
                               ignore_eos=True, **GREEDY))
        assert _wait(lambda: keeper.completion_tokens >= 3)
        h = s.generate(_req("an arrival", max_new_tokens=4, ignore_eos=True,
                            **GREEDY), timeout=60)
        assert keeper.result(60).finish_reason == "error"
        assert (h.finish_reason, len(h.token_ids)) == ("length", 4)
    finally:
        s.shutdown()
    assert calls == [16]
    assert (s.total_chunk_rides, s.total_prefill_chunks) == (0, 2)
    assert r.allocator.check_invariants() == []
