"""Block-native speculative decoding (localai_tpu.spec, ISSUE 11).

The paged draft lane: drafters propose through one Drafter protocol
(self-drafting n-gram lookup, co-located draft model), ONE verify-k
target dispatch scores the window through the block-table mirror, and
the accept scan rolls each slot's frontier back independently. Emitted
tokens come from the target's own sampler chain, so greedy paged+spec
output must equal greedy non-spec paged output exactly — on one device
and under a mesh."""

import numpy as np
import pytest

from localai_tpu.engine.runner import SKIP, ModelRunner
from localai_tpu.models.registry import resolve_model
from localai_tpu.spec import ModelDrafter, NGramDrafter, SpecEngine

REPEAT = list(b"abcabcabcabcabcabc")


@pytest.fixture(scope="module")
def tiny():
    return resolve_model("debug:tiny", dtype="float32")


@pytest.fixture(scope="module")
def small():
    return resolve_model("debug:small", dtype="float32")


def _mk(model, *, paged=True, num_slots=2, max_ctx=128, **kw):
    kw.setdefault("prefill_buckets", [32])
    kw.setdefault("kv_dtype", "float32")
    if paged:
        kw.setdefault("kv_block_tokens", 16)
    return ModelRunner(model.cfg, model.params, num_slots=num_slots,
                       max_ctx=max_ctx, paged=paged, **kw)


def _plain_tokens(runner, prompt, n, slot=None):
    s = runner.acquire_slot(slot)
    out = [runner.admit(s, prompt, temperature=0.0)]
    for _ in range(n):
        out.append(int(runner.step()[s]))
    return out


def _spec_tokens(eng, prompt, n, max_windows=60):
    """Drive the engine like the scheduler: spec window when the drafter
    has proposals, plain decode otherwise."""
    slot = eng.acquire_slot()
    out = [eng.admit(slot, prompt, temperature=0.0)]
    windows = 0
    while len(out) <= n and windows < max_windows:
        windows += 1
        rows = eng.step_spec_async()
        if rows is None:  # drafter declined — plain fallback
            tok = int(eng.target.step()[slot])
            out.append(tok)
            eng.drafter.observe(slot, [tok])
            continue
        host = np.asarray(rows)
        eng.observe_window(host)
        for t in range(host.shape[0]):
            if host[t, slot] != SKIP:
                out.append(int(host[t, slot]))
    return out[:n + 1]


class PlannedDrafter:
    """Deterministic test drafter: proposes scripted windows (slot 0)."""

    name = "planned"
    device_proposals = False

    def __init__(self, num_slots, gamma, windows):
        self.num_slots = num_slots
        self.gamma = gamma
        self.windows = list(windows)   # each: list[gamma] proposals

    def propose(self, tokens, positions):
        if not self.windows:
            return None
        props = np.zeros((self.num_slots, self.gamma), np.int32)
        props[0] = self.windows.pop(0)
        return props

    def admit(self, slot, prompt, first, positions):
        pass

    def observe(self, slot, emitted):
        pass

    def resync(self, slot, resident, positions):
        pass

    def release(self, slot):
        pass

    def reinit(self):
        self.windows.clear()

    def stats(self):
        return {"drafter": self.name}


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------


def test_ngram_lookup_proposes_continuation():
    d = NGramDrafter(num_slots=2, gamma=3)
    d.admit(0, [1, 2, 3, 4, 1, 2], 3, None)   # history ..., 1, 2, 3
    props = d.propose(None, None)
    assert props is not None
    # frontier trigram [1, 2, 3] occurred before, followed by 4, 1, 2
    assert props[0].tolist() == [4, 1, 2]
    # no history for slot 1 → zero filler row, but the window still fires
    assert props[1].tolist() == [0, 0, 0]


def test_ngram_declines_without_repetition():
    d = NGramDrafter(num_slots=1, gamma=3)
    d.admit(0, [5, 9, 2, 7], 11, None)  # no repeated n-gram
    assert d.propose(None, None) is None
    assert d.stats()["lookup_misses"] > 0


def test_ngram_resync_and_release():
    d = NGramDrafter(num_slots=1, gamma=2)
    d.admit(0, [1, 2], 3, None)
    d.resync(0, [7, 8, 7, 8], None)
    props = d.propose(None, None)
    assert props is not None and props[0][0] == 7
    d.release(0)
    assert d.propose(None, None) is None


# ---------------------------------------------------------------------------
# greedy parity: paged+spec == paged plain (the acceptance criterion)
# ---------------------------------------------------------------------------


def test_paged_ngram_greedy_parity(tiny):
    ref = _plain_tokens(_mk(tiny), REPEAT, 24)
    eng = SpecEngine(_mk(tiny), NGramDrafter(2, gamma=4), gamma=4)
    got = _spec_tokens(eng, REPEAT, 24)
    assert got == ref
    # the verify-k dispatch actually amortized: >1 token per window once
    # the stream cycles
    assert eng.tokens_per_dispatch > 1.0
    assert eng.accept_rate > 0.0
    assert not eng.target.allocator.check_invariants()


def test_paged_model_drafter_greedy_parity(small, tiny):
    """Stub draft model (different weights — imperfect proposals) over a
    paged target: emitted tokens still come from the target's sampler."""
    ref = _plain_tokens(_mk(small), REPEAT, 16)
    target = _mk(small)
    draft = _mk(tiny, paged=False)
    eng = SpecEngine(target, ModelDrafter(draft, gamma=3), gamma=3)
    got = _spec_tokens(eng, REPEAT, 16)
    assert got == ref
    assert not target.allocator.check_invariants()


def test_paged_spec_int8_kv(tiny):
    """Verify writes ride the scaled-int8 pool (values + scale rows) and
    stay byte-identical to plain int8 paged decode."""
    ref = _plain_tokens(_mk(tiny, kv_dtype="int8"), REPEAT, 16)
    eng = SpecEngine(_mk(tiny, kv_dtype="int8"),
                     NGramDrafter(2, gamma=3), gamma=3)
    got = _spec_tokens(eng, REPEAT, 16)
    assert got == ref


def test_meshed_paged_spec_greedy_parity(tiny):
    """2-virtual-device data mesh: the sharded table mirror + pool serve
    the same verify windows token-for-token as the single-device lane."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    from localai_tpu.parallel import sharding as shd
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    ref = _plain_tokens(_mk(tiny), REPEAT, 16)
    mesh = build_mesh(MeshPlan(data=2), devices=jax.devices()[:2])
    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    target = ModelRunner(tiny.cfg, params, num_slots=2, max_ctx=128,
                         prefill_buckets=[32], kv_dtype="float32",
                         paged=True, kv_block_tokens=16, mesh=mesh)
    eng = SpecEngine(target, NGramDrafter(2, gamma=4), gamma=4)
    got = _spec_tokens(eng, REPEAT, 16)
    assert got == ref


def test_meshed_model_drafter_parity(small, tiny):
    """Co-located draft model sharing the mesh's data axis (ISSUE 11
    tentpole b): dp-sharded target AND draft reproduce the single-device
    stream."""
    import jax

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    from localai_tpu.parallel import sharding as shd
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    ref_eng = SpecEngine(_mk(small), ModelDrafter(_mk(tiny, paged=False),
                                                  gamma=3), gamma=3)
    ref = _spec_tokens(ref_eng, REPEAT, 12)

    mesh = build_mesh(MeshPlan(data=2), devices=jax.devices()[:2])

    def mk_mesh(model, paged):
        params = shd.shard_params(model.params, model.cfg, mesh)
        return ModelRunner(model.cfg, params, num_slots=2, max_ctx=128,
                           prefill_buckets=[32], kv_dtype="float32",
                           paged=paged, mesh=mesh,
                           **({"kv_block_tokens": 16} if paged else {}))

    eng = SpecEngine(mk_mesh(small, True),
                     ModelDrafter(mk_mesh(tiny, False), gamma=3), gamma=3)
    got = _spec_tokens(eng, REPEAT, 12)
    assert got == ref


# ---------------------------------------------------------------------------
# rollback + reservation accounting
# ---------------------------------------------------------------------------


def test_rollback_after_partial_accept_block_accounting(tiny):
    """Scripted windows: full reject then partial accept. Output must
    equal plain decode (corrections are the target's own samples), the
    frontier rolls back per window, and the allocator's speculation
    reservation conserves blocks throughout."""
    ref = _plain_tokens(_mk(tiny), REPEAT, 8)
    target = _mk(tiny)
    v = tiny.cfg.vocab_size
    windows = [
        [(ref[1] + 1) % v] * 3,           # all wrong → emit 1 (correction)
        [ref[2], (ref[3] + 1) % v, 0],    # 1 accepted + correction → emit 2
        [ref[4], ref[5], (ref[6] + 1) % v],  # 2 accepted + correction
    ]
    eng = SpecEngine(target, PlannedDrafter(2, 3, windows), gamma=3)
    slot = eng.acquire_slot()
    out = [eng.admit(slot, REPEAT, temperature=0.0,
                     reserve_tokens=len(REPEAT) + 32)]
    p0 = len(REPEAT)
    expect_emitted = [1, 2, 3]
    for want in expect_emitted:
        rows = eng.step_spec()
        got = int((rows[:, slot] != SKIP).sum())
        assert got == want
        out.extend(int(x) for x in rows[:, slot][rows[:, slot] != SKIP])
        p0 += got
        # per-slot rollback: the frontier advanced by exactly the emitted
        # count, never by the full window width
        assert eng.slot_position(slot) == p0
        assert not target.allocator.check_invariants()
    assert out == ref[:len(out)]
    eng.release(slot)
    st = target.allocator.stats()
    assert st.free + st.cached == st.total  # nothing leaked
    assert st.spec_reserved == 0


def test_spec_reservation_accounting(tiny):
    """begin_admit(spec_tokens=) records speculation blocks separately
    and check_invariants audits them (tail-of-table, never pool-shared)."""
    r = _mk(tiny, max_ctx=128)
    adm = r.begin_admit(0, list(range(1, 20)), reserve_tokens=33,
                        spec_tokens=16, temperature=0.0)
    assert adm is not None
    while adm.step_chunk() is None:
        pass
    alloc = r.allocator
    # 33 base rows → 3 blocks of 16; +16 spec rows → 1 more block
    assert alloc.spec_blocks[0] == 1
    assert alloc.stats().spec_reserved == 1
    assert not alloc.check_invariants()
    # corrupting the reservation record is caught
    alloc.spec_blocks[0] = len(alloc.tables[0]) + 7
    assert any("speculation" in p for p in alloc.check_invariants())
    alloc.spec_blocks[0] = 1
    r.release(0)
    assert alloc.stats().spec_reserved == 0
    assert not alloc.check_invariants()


def test_pool_exhaustion_with_spec_reservation(tiny):
    """A pool whose remaining blocks cover the base reservation but not
    base+spec holds the admission (returns None) instead of admitting a
    slot whose draft windows could overrun — and the hold clears when
    the co-resident's speculation blocks free."""
    # 9 allocatable blocks of 16 rows
    r = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=128,
                    prefill_buckets=[32], kv_dtype="float32", paged=True,
                    kv_block_tokens=16, kv_num_blocks=10)
    prompt = list(range(1, 30))
    # slot 0: 65 base + 16 spec rows → 6 blocks (1 of them speculation)
    adm = r.begin_admit(0, prompt, reserve_tokens=65, spec_tokens=16,
                        temperature=0.0)
    assert adm is not None
    while adm.step_chunk() is None:
        pass
    assert r.allocator.stats().spec_reserved == 1
    # slot 1 (distinct prompt — no pool sharing): base 33 rows → 3
    # blocks would fit the 3 free ones, but the +16-row speculation
    # lookahead needs a 4th → held (None), no leak
    p2 = list(range(100, 129))
    assert r.begin_admit(1, p2, reserve_tokens=33, spec_tokens=16,
                         temperature=0.0) is None
    assert 1 not in r.allocator.tables
    assert r.begin_admit(1, p2, reserve_tokens=33,
                         temperature=0.0) is not None
    r.release(1)
    r.release(0)
    st = r.allocator.stats()
    assert st.free + st.cached == st.total
    assert st.spec_reserved == 0
    assert not r.allocator.check_invariants()


def test_nan_guard_in_verify_window(tiny):
    """The accept scan carries the per-row NaN/inf guard (speculation is
    the default lane — skipping it would reopen the silent-poison class
    the plain decode path closed): a non-finite logits row emits the
    NAN_TOKEN sentinel, ends the slot's window, and never enters the
    drafter history or the emitted telemetry."""
    from localai_tpu.engine.runner import NAN_TOKEN

    target = _mk(tiny)
    eng = SpecEngine(target, PlannedDrafter(2, 3, [[1, 2, 3]]), gamma=3)
    slot = eng.acquire_slot()
    eng.admit(slot, REPEAT, temperature=0.0)
    eng.set_bias(slot, np.full(tiny.cfg.vocab_size, np.nan, np.float32))
    rows = eng.step_spec()
    col = rows[:, slot].tolist()
    assert col[0] == NAN_TOKEN
    assert all(t < 0 for t in col[1:])  # window ended at the sentinel
    assert eng.total_emitted == 0       # sentinels are not tokens


def test_scheduler_spec_nan_fault_fails_only_target(tiny):
    """decode.nan chaos through a spec-enabled scheduler: the poisoned
    request fails with a clean error (caught inside the verify window or
    the plain fallback — both guard), the engine keeps serving."""
    from localai_tpu import faults
    from localai_tpu.engine.scheduler import GenRequest

    target = _mk(tiny)
    spec = SpecEngine(target, NGramDrafter(2, gamma=4))
    sched = _sched(target, tiny.tokenizer, spec=spec)
    try:
        faults.arm(faults.FaultSpec(site="decode.nan", mode="nan",
                                    match="spec-poison", times=1))
        h = sched.submit(GenRequest(prompt=REPEAT,
                                    correlation_id="spec-poison",
                                    **CYCLIC))
        h.result(120)
        assert h.finish_reason == "error"
        assert sched.nan_rows >= 1
        # the engine survives and keeps serving correct output
        h2 = sched.generate(GenRequest(prompt=REPEAT, **CYCLIC),
                            timeout=120)
        assert h2.finish_reason in ("stop", "length")
        assert not target.allocator.check_invariants()
    finally:
        faults.clear()
        sched.shutdown()


def test_extend_spec_accounting(tiny):
    """extend() records the speculation reservation only when blocks were
    actually added, and drops it when the retained table subsumes the
    new reservation (the audit must never point at unrelated old tail
    blocks)."""
    from localai_tpu.engine.paged import BlockAllocator

    alloc = BlockAllocator(num_blocks=10, block_tokens=16,
                           max_blocks_per_seq=8)
    assert alloc.allocate(0, 33) == 0          # 3 blocks
    assert alloc.extend(0, 33, spec_tokens=16)  # +1 spec block
    assert alloc.spec_blocks[0] == 1
    assert not alloc.check_invariants()
    # retained table (4 blocks) already covers a smaller reservation:
    # the speculation record is dropped, not pointed at old blocks
    assert alloc.extend(0, 17, spec_tokens=16)
    assert 0 not in alloc.spec_blocks
    assert not alloc.check_invariants()
    # exhaustion must not leave a phantom reservation behind
    assert alloc.allocate(1, 65) == 0          # 5 blocks → pool full
    assert not alloc.extend(0, 129, spec_tokens=16)
    assert 0 not in alloc.spec_blocks
    assert alloc.stats().spec_reserved == 0
    assert not alloc.check_invariants()


def test_acceptance_backoff_suppresses_windows(tiny):
    """A drafter whose proposals never get accepted trips the
    acceptance-floor backoff: speculation self-suppresses for the
    cooldown instead of paying a gamma+1-wide verify per emitted token."""

    class AlwaysWrong(PlannedDrafter):
        def __init__(self, num_slots, gamma, vocab):
            super().__init__(num_slots, gamma, [])
            self.vocab = vocab

        def propose(self, tokens, positions):
            # proposals the target can never greedily sample: outside
            # the model's actual argmax by construction is impossible to
            # guarantee, so just rotate the whole vocab — acceptance is
            # ~1/vocab per position, effectively zero
            props = np.full((self.num_slots, self.gamma),
                            self.vocab - 1, np.int32)
            return props

    target = _mk(tiny)
    eng = SpecEngine(target, AlwaysWrong(2, 3, tiny.cfg.vocab_size),
                     gamma=3, min_accept=0.5, cooldown=10)
    slot = eng.acquire_slot()
    out = [eng.admit(slot, REPEAT, temperature=0.0)]
    suppressed_seen = 0
    for _ in range(40):
        rows = eng.step_spec_async()
        if rows is None:
            suppressed_seen += 1
            tok = int(target.step()[slot])
            out.append(tok)
            continue
        host = np.asarray(rows)
        eng.observe_window(host)
        out.extend(int(x) for x in host[:, slot][host[:, slot] != SKIP])
    # the recent-window tracker (16 windows) filled, the floor tripped,
    # and the cooldown routed dispatches to plain decode
    assert eng.total_suppressed > 0
    assert suppressed_seen == eng.total_suppressed
    # output still exactly the plain greedy stream
    ref = _plain_tokens(_mk(tiny), REPEAT, len(out) - 1)
    assert out == ref


# ---------------------------------------------------------------------------
# scheduler end-to-end (the default paged hot path)
# ---------------------------------------------------------------------------


def _sched(runner, tokenizer, **kw):
    from localai_tpu.engine.scheduler import Scheduler

    kw.setdefault("multi_step", 4)
    return Scheduler(runner, tokenizer, **kw)


# greedy decode under the scheduler's padded-vocab ban takes a while to
# enter a cycle; a huge logit bias forces one immediately, making the
# n-gram lane's acceptance deterministic for the telemetry asserts
CYCLIC = dict(logit_bias={97: 1e4}, max_new_tokens=24, temperature=0.0,
              ignore_eos=True)


def test_scheduler_paged_spec_matches_plain(tiny):
    """End-to-end: a paged+spec scheduler's greedy byte stream equals the
    non-spec paged scheduler's (spec windows and plain fallbacks both),
    and the spec telemetry is live."""
    from localai_tpu.engine.scheduler import GenRequest

    req = dict(prompt=REPEAT, max_new_tokens=24, temperature=0.0,
               ignore_eos=True)
    plain = _sched(_mk(tiny), tiny.tokenizer)
    try:
        ref = plain.generate(GenRequest(**req), timeout=120)
        ref_cyc = plain.generate(GenRequest(prompt=REPEAT, **CYCLIC),
                                 timeout=120)
    finally:
        plain.shutdown()

    target = _mk(tiny)
    spec = SpecEngine(target, NGramDrafter(2, gamma=4), gamma=4)
    sched = _sched(target, tiny.tokenizer, spec=spec)
    try:
        got = sched.generate(GenRequest(**req), timeout=120)
        assert got.token_ids == ref.token_ids
        assert got.text == ref.text
        # a forced-cyclic stream makes the lookup hit deterministically
        got_cyc = sched.generate(GenRequest(prompt=REPEAT, **CYCLIC),
                                 timeout=120)
        assert got_cyc.token_ids == ref_cyc.token_ids
        m = sched.metrics()
        assert m["spec_windows"] > 0
        assert m["spec_draft_tokens"] > 0
        assert m["spec_accepted_tokens"] > 0
        assert m["spec_accept_rate"] > 0.0
        assert m["spec_tokens_per_dispatch"] > 1.0
        assert m["spec_drafter"] == "ngram"
        # per-dispatch accept counts land in the flight ring
        recs = sched.flight.snapshot()
        spec_recs = [x for x in recs if x["program"] == "spec"]
        assert spec_recs and any(x["spec_proposed"] > 0 for x in spec_recs)
        assert any(x["spec_accepted"] > 0 for x in spec_recs)
        # spec dispatches feed the step-time percentiles (steps > 0)
        assert all(x["steps"] > 0 for x in spec_recs)
        assert m["kv_blocks_spec_reserved"] >= 0
    finally:
        sched.shutdown()


def test_scheduler_spec_metrics_exported(tiny):
    """update_engine_gauges renders the localai_spec_* series from the
    scheduler's metrics surface."""
    from localai_tpu.engine.scheduler import GenRequest
    from localai_tpu.obs.metrics import Registry, update_engine_gauges

    target = _mk(tiny)
    spec = SpecEngine(target, NGramDrafter(2, gamma=3), gamma=3)
    sched = _sched(target, tiny.tokenizer, spec=spec)
    try:
        sched.generate(GenRequest(prompt=REPEAT, **CYCLIC), timeout=120)
        reg = Registry()
        update_engine_gauges("m", sched.metrics(), registry=reg)
        text = reg.render()
        assert 'localai_spec_accept_rate{model="m"}' in text
        assert 'localai_spec_draft_tokens_total{model="m"}' in text
        assert 'localai_spec_accepted_tokens_total{model="m"}' in text
        assert 'localai_spec_tokens_per_dispatch{model="m"}' in text
    finally:
        sched.shutdown()


def test_spec_draft_fault_garbles_but_stays_correct(tiny):
    """spec.draft chaos site: garbled proposals collapse acceptance but
    the greedy stream stays byte-identical (corrections are the target's
    own samples) and blocks conserve."""
    from localai_tpu import faults
    from localai_tpu.engine.scheduler import GenRequest

    req = dict(prompt=REPEAT, **CYCLIC)
    plain = _sched(_mk(tiny), tiny.tokenizer)
    try:
        ref = plain.generate(GenRequest(**req), timeout=120)
    finally:
        plain.shutdown()

    target = _mk(tiny)
    spec = SpecEngine(target, NGramDrafter(2, gamma=4), gamma=4)
    sched = _sched(target, tiny.tokenizer, spec=spec)
    try:
        faults.arm(faults.FaultSpec(site="spec.draft", mode="garble",
                                    times=0))
        got = sched.generate(GenRequest(**req), timeout=120)
        assert got.token_ids == ref.token_ids
        assert not target.allocator.check_invariants()
        assert any(s["site"] == "spec.draft" and s["fired"] > 0
                   for s in faults.snapshot())
    finally:
        faults.clear()
        sched.shutdown()


def test_build_spec_engine_knobs(tiny, monkeypatch):
    from localai_tpu.spec import build_spec_engine

    monkeypatch.setenv("LOCALAI_SPEC_GAMMA", "6")
    eng = build_spec_engine(_mk(tiny), drafter="ngram")
    assert eng.gamma == 6 and eng.drafter.name == "ngram"
    with pytest.raises(ValueError, match="draft_model"):
        build_spec_engine(_mk(tiny), drafter="model")
    with pytest.raises(ValueError, match="unknown drafter"):
        build_spec_engine(_mk(tiny), drafter="bogus")


def test_manager_spec_default_on_for_paged(tmp_path):
    """Config → engine: a plain paged model gets the n-gram lane by
    default; LOCALAI_SPEC=0 kills it."""
    import os

    from localai_tpu.config.app_config import AppConfig
    from localai_tpu.config.model_config import ModelConfig
    from localai_tpu.models.manager import build_serving_model

    mcfg = ModelConfig.model_validate({
        "name": "spec-default",
        "model": "debug:tiny",
        "context_size": 128,
        "parameters": {"max_tokens": 16},
        "engine": {
            "max_slots": 2,
            "prefill_buckets": [32],
            "dtype": "float32",
            "kv_dtype": "float32",
            "kv_block_tokens": 16,
        },
    })
    app = AppConfig(model_path=str(tmp_path))
    old = os.environ.pop("LOCALAI_SPEC", None)
    try:
        sm = build_serving_model(mcfg, app)
        try:
            assert sm.scheduler.spec is not None
            assert sm.scheduler.spec.drafter.name == "ngram"
            assert sm.scheduler.spec.paged
        finally:
            sm.scheduler.shutdown()
        os.environ["LOCALAI_SPEC"] = "0"
        sm = build_serving_model(mcfg, app)
        try:
            assert sm.scheduler.spec is None
        finally:
            sm.scheduler.shutdown()
    finally:
        if old is None:
            os.environ.pop("LOCALAI_SPEC", None)
        else:
            os.environ["LOCALAI_SPEC"] = old
