"""Paged KV cache tests, the half that serves: the paged runner against the
contiguous one and its own XLA path, chunked-prefill scheduling,
pool-exhaustion admission control, the pool's metrics, and the paged pool on
a mesh. All on the CPU backend at ``debug:tiny``. The allocator and the
kernels against their references are tests/test_paged.py."""

import jax
import pytest

from localai_tpu.engine.runner import ModelRunner
from localai_tpu.engine.scheduler import GenRequest, Scheduler
from localai_tpu.models.registry import resolve_model
from localai_tpu.obs.flight import FlightRecorder
from localai_tpu.utils.tokenizer import ByteTokenizer

# The greedy pair (two prompts sharing the pool, token for token against the
# contiguous runner) is the ``decode`` case of tests/test_kv_contract.py
# ``test_both_layouts_emit_the_same_tokens_through_the_one_family``.


def test_paged_runner_pallas_kernel_matches_xla_end_to_end():
    """The Pallas paged-decode kernel (interpret mode on CPU) wired
    through the runner must reproduce the gather+XLA paged path."""
    tiny = resolve_model("debug:tiny", dtype="float32")
    outs = {}
    for impl in ("xla", "pallas_interpret"):
        r = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                        prefill_buckets=[16], kv_dtype="float32",
                        paged=True, kv_block_tokens=16, prefill_chunk=16,
                        attn_impl=impl)
        assert r.paged_attn_impl == ("pallas" if impl != "xla" else "xla")
        s = r.acquire_slot()
        t = r.admit(s, list(b"kernel parity"), temperature=0.0)
        outs[impl] = [t] + [int(r.step()[s]) for _ in range(6)]
    assert outs["pallas_interpret"] == outs["xla"]


def test_paged_runner_int8_kv_matches_contiguous():
    """Scaled-int8 pool: paged quantized decode must track the contiguous
    quantized path (identical quantization grid → identical tokens)."""
    tiny = resolve_model("debug:tiny", dtype="float32")
    rc = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                     prefill_buckets=[16], kv_dtype="int8")
    rp = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                     prefill_buckets=[16], kv_dtype="int8",
                     paged=True, kv_block_tokens=16, prefill_chunk=16)
    prompt = list(b"quantized kv")
    outs = {}
    for name, r in (("contig", rc), ("paged", rp)):
        s = r.acquire_slot()
        t = r.admit(s, prompt, temperature=0.0)
        outs[name] = [t] + [int(r.step()[s]) for _ in range(6)]
    assert outs["paged"] == outs["contig"]


def test_paged_prefix_pool_reuse_preserves_output():
    """Pool-shared prefix blocks must not change greedy output, and the
    second admission must actually reuse blocks."""
    tiny = resolve_model("debug:tiny", dtype="float32")
    r = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=96,
                    prefill_buckets=[16, 32], kv_dtype="float32",
                    paged=True, kv_block_tokens=16, prefill_chunk=16)
    prompt = list(b"shared system prompt here plus tail")
    s = r.acquire_slot()
    first = [r.admit(s, prompt, temperature=0.0)]
    first += [int(r.step()[s]) for _ in range(5)]
    r.release(s)
    assert r.allocator.stats().cached > 0

    s2 = r.acquire_slot()
    second = [r.admit(s2, prompt, temperature=0.0)]
    assert r.last_prefix_reused >= r.block_tokens
    assert r.last_prefill_path == "paged_shared"
    second += [int(r.step()[s2]) for _ in range(5)]
    assert second == first


# ---------------------------------------------------------------------------
# chunked prefill scheduling + admission control
# ---------------------------------------------------------------------------


def _paged_sched(tiny, flight=None, **kw):
    runner = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=96,
                         prefill_buckets=[16, 32], kv_dtype="float32",
                         paged=True, kv_block_tokens=16, prefill_chunk=16,
                         **kw)
    return Scheduler(runner, ByteTokenizer(), flight=flight)


@pytest.fixture(scope="module")
def tiny():
    return resolve_model("debug:tiny", dtype="float32")


def test_chunked_prefill_interleaves_with_decode(tiny):
    """A long prompt's chunks must not stall an active slot: decode
    dispatches appear BETWEEN its prefill_chunk dispatches in the flight
    timeline."""
    flight = FlightRecorder(256)
    s = _paged_sched(tiny, flight=flight)
    try:
        a = s.submit(GenRequest(prompt=list(b"warm"), max_new_tokens=48,
                                temperature=0.0))
        # wait until A is actively decoding
        while a.completion_tokens < 2:
            pass
        long_prompt = list(b"x" * 80)              # 5 chunks of 16
        b = s.submit(GenRequest(prompt=long_prompt, max_new_tokens=4,
                                temperature=0.0))
        a.result(timeout=60)
        b.result(timeout=60)
    finally:
        s.shutdown()
    progs = [rec["program"] for rec in flight.snapshot(limit=256)]
    # (the prompt's LAST chunk may ride a decode step: ``decode_chunk``)
    chunks = ("prefill_chunk", "decode_chunk")
    chunk_idx = [i for i, p in enumerate(progs) if p in chunks]
    assert len(chunk_idx) >= 5, progs
    interleaved = any(
        any(p not in chunks for p in progs[i + 1:j])
        for i, j in zip(chunk_idx, chunk_idx[1:])
    )
    assert interleaved, progs
    assert s.total_prefill_chunks >= 5


def test_pool_exhaustion_holds_request_until_blocks_free(tiny):
    """With a pool too small for two concurrent reservations, the second
    request waits (held, not errored) and completes after the first frees
    its blocks."""
    # 7 allocatable blocks of 16 = 112 rows; each request reserves
    # prompt + max_new + 1 capped at max_ctx (96 rows = 6 blocks)
    s = _paged_sched(tiny, kv_num_blocks=8)
    try:
        a = s.submit(GenRequest(prompt=list(b"first request"),
                                max_new_tokens=90, temperature=0.0))
        b = s.submit(GenRequest(prompt=list(b"second request"),
                                max_new_tokens=90, temperature=0.0))
        ra = a.result(timeout=120)
        rb = b.result(timeout=120)
        assert ra.finish_reason is not None
        assert rb.finish_reason is not None
        assert a.admit_index < b.admit_index
    finally:
        s.shutdown()


def test_cancel_races_pool_exhaustion_hold(tiny):
    """A request cancelled while parked in the scheduler's pool-
    exhaustion hold (``_held``) must resolve ``cancelled``, release its
    head-of-line place, and let a successor admit — with every block
    conserved afterwards."""
    import time

    s = _paged_sched(tiny, kv_num_blocks=8)
    try:
        a = s.submit(GenRequest(prompt=list(b"pool filler request"),
                                max_new_tokens=90, temperature=0.0))
        held = s.submit(GenRequest(prompt=list(b"about to be held"),
                                   max_new_tokens=90, temperature=0.0))
        deadline = time.monotonic() + 30
        while s._held is not held and time.monotonic() < deadline:
            time.sleep(0.005)
        assert s._held is held, "second request never parked in the hold"
        held.cancel()
        successor = s.submit(GenRequest(prompt=list(b"held successor"),
                                        max_new_tokens=8, temperature=0.0))
        held.result(timeout=60)
        assert held.finish_reason == "cancelled"
        a.result(timeout=120)
        successor.result(timeout=120)
        assert a.finish_reason is not None
        assert successor.finish_reason in ("stop", "length")
        # the cancelled hold left nothing behind: all blocks return and
        # the allocator's conservation invariants hold
        st = s.runner.allocator.stats()
        assert st.free + st.cached == st.total
        assert s.runner.allocator.check_invariants() == []
    finally:
        s.shutdown()


def test_paged_metrics_export_block_gauges(tiny):
    s = _paged_sched(tiny)
    try:
        s.generate(GenRequest(prompt=list(b"metrics"), max_new_tokens=4,
                              temperature=0.0), timeout=60)
        m = s.metrics()
        assert m["kv_block_tokens"] == 16
        assert m["kv_blocks_total"] > 0
        assert m["kv_blocks_free"] + m["kv_blocks_used"] == m["kv_blocks_total"]
        assert m["prefill_chunks"] >= 1
        assert "prefill_chunk_queue_depth" in m
        assert 0.0 <= m["kv_utilization"] <= 1.0

        from localai_tpu.obs import metrics as obs_metrics

        reg = obs_metrics.Registry()
        obs_metrics.update_engine_gauges("tiny", m, registry=reg)
        text = reg.render()
        assert 'localai_kv_blocks_free{model="tiny"}' in text
        assert 'localai_kv_blocks_used{model="tiny"}' in text
        assert 'localai_prefill_chunk_queue_depth{model="tiny"}' in text
        # the admission path's counters: one admission, no blocking device
        # read on it, the arming update and one chunk
        assert (m["admissions"], m["admit_blocking_reads"],
                m["admit_programs"]) == (1, 0, 2)
        for name, value in (("admissions", 1), ("admit_blocking_reads", 0),
                            ("admit_programs", 2)):
            assert (f'localai_{name}_total{{model="tiny"}} {value}'
                    in text), name
    finally:
        s.shutdown()


@pytest.mark.parametrize("kv_dtype, attn_impl, writer", [
    ("float32", "pallas_interpret", "kernel"),
    ("int8", "pallas_interpret", "scatter"),
    ("float32", "xla", "scatter")])
def test_metrics_say_who_writes_the_decode_rows(tiny, kv_dtype, attn_impl,
                                                writer):
    """``localai_paged_kv_write_impl{impl=kernel|scatter}``, one-hot beside
    ``localai_paged_kernel_impl``: the kernel writes an unscaled pool it
    attends over; a scaled pool and the XLA attend keep the scatter."""
    from localai_tpu.obs import metrics as obs_metrics

    runner = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=96,
                         prefill_buckets=[16, 32], kv_dtype=kv_dtype,
                         paged=True, kv_block_tokens=16, prefill_chunk=16,
                         attn_impl=attn_impl)
    assert runner.paged_kv_write_impl == writer
    s = Scheduler(runner, ByteTokenizer())
    try:
        s.generate(GenRequest(prompt=list(b"who writes"), max_new_tokens=3,
                              temperature=0.0), timeout=60)
        m = s.metrics()
    finally:
        s.shutdown()
    assert m["paged_kv_write_impl"] == writer
    reg = obs_metrics.Registry()
    obs_metrics.update_engine_gauges("tiny", m, registry=reg)
    text = reg.render()
    for label in ("kernel", "scatter"):
        assert (f'localai_paged_kv_write_impl{{impl="{label}",model="tiny"}} '
                f'{1.0 if label == writer else 0.0}') in text, text


def test_disk_prefix_export_transfers_across_layouts(tiny):
    """The disk prompt-cache export format is layout-independent: rows
    exported from a paged pool load into a contiguous cache and vice
    versa, and the resumed generation matches the original."""
    def mk(paged):
        kw = ({"kv_block_tokens": 16, "prefill_chunk": 16} if paged else {})
        return ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=96,
                           prefill_buckets=[16, 32], kv_dtype="float32",
                           paged=paged, **kw)

    prompt = list(b"a long shared system prompt for the cache")
    src = mk(True)
    s = src.acquire_slot()
    base = [src.admit(s, prompt, temperature=0.0)]
    base += [int(src.step()[s]) for _ in range(5)]
    arrays = src.export_prefix(s, len(prompt))

    for paged in (True, False):
        dst = mk(paged)
        s2 = dst.acquire_slot()
        assert dst.load_prefix(s2, arrays, len(prompt))
        t = dst.admit(s2, prompt, temperature=0.0,
                      resident=list(prompt), valid_n=len(prompt))
        assert dst.last_prefix_reused == len(prompt) - 1
        out = [t] + [int(dst.step()[s2]) for _ in range(5)]
        assert out == base, (paged, out, base)


def test_spec_decoder_accepts_paged_runner(tiny):
    """The PR 6 'SpecDecoder rejects paged runners' guard is gone: the
    block-native lane (localai_tpu.spec) verifies draft windows straight
    through the paged table mirror. Only a PAGED DRAFT stays rejected —
    its window scans run over contiguous slot rows."""
    from localai_tpu.engine.speculative import SKIP, SpecDecoder

    rp = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                     prefill_buckets=[16], kv_dtype="float32", paged=True)
    rc = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                     prefill_buckets=[16], kv_dtype="float32", paged=False)
    spec = SpecDecoder(rp, rc, gamma=2)
    slot = spec.acquire_slot()
    spec.admit(slot, list(b"paged spec"), temperature=0.0)
    rows = spec.step_spec()
    assert 1 <= int((rows[:, slot] != SKIP).sum()) <= 3
    assert not rp.allocator.check_invariants()

    rp2 = ModelRunner(tiny.cfg, tiny.params, num_slots=2, max_ctx=64,
                      prefill_buckets=[16], kv_dtype="float32", paged=True)
    with pytest.raises(ValueError, match="contiguous"):
        SpecDecoder(rc, rp2)


# ---------------------------------------------------------------------------
# a prompt's small last chunk rides the decode step (PR 59)
# ---------------------------------------------------------------------------


def _state_and_pool(r):
    """Every leaf of the pool and of the decode state, on the host."""
    import numpy as np

    state = r.state
    leaves = jax.tree.leaves((r.kv, state.tokens, state.positions,
                              state.active, state.counts, state.bias,
                              state.params, jax.random.key_data(state.keys)))
    return [np.asarray(a) for a in leaves]


@pytest.mark.parametrize("sampling", [
    dict(temperature=0.0), dict(temperature=0.8, top_p=0.95, seed=11)],
    ids=["greedy", "seeded"])
@pytest.mark.parametrize("weights, kv_dtype, attn_impl", [
    ("int8", "bfloat16", "pallas_interpret"),   # the 7B cell's: the kernel
    ("", "bfloat16", "xla"),                    # writes the step's rows
    ("", "int8", "pallas_interpret")])          # a scaled pool: the scatter
def test_a_ride_leaves_what_the_step_then_the_chunk_leave(
        weights, kv_dtype, attn_impl, sampling):
    """``_decode_prefill_paged_fn`` against the two programs it stands for,
    on the same state: a decode step (the new slot not yet armed: its rows
    go to the trash block), then the arming update and the prompt's last
    chunk. Same pool to the bit, same decode state, same S tokens, same
    first token, and the same streams afterwards."""
    import numpy as np

    model = resolve_model("debug:tiny", dtype="bfloat16",
                          quantization=weights)

    def serve(ride):
        r = ModelRunner(model.cfg, model.params, num_slots=4, max_ctx=96,
                        prefill_buckets=[16, 32], kv_dtype=kv_dtype,
                        paged=True, kv_block_tokens=16, prefill_chunk=16,
                        attn_impl=attn_impl, seed=3)
        assert r.rides
        for text in (b"the first stream", b"second"):
            r.admit(r.acquire_slot(), list(text), **{**sampling, "seed": 7})
        for _ in range(3):
            r.step()
        adm = r.begin_admit(r.acquire_slot(),
                            list(b"a third prompt of two chunks"), **sampling)
        assert adm.ride_bucket is None      # 28 tokens: not the last chunk
        assert adm.launch_chunk() is False
        assert adm.ride_bucket == 16
        if ride:
            assert adm.launch_chunk(ride=True) is True
            out = np.asarray(adm.first)
            step, first = out[:-1], int(out[-1])
            assert adm.first_token() == first
        else:
            step = r.step()
            first = adm.step_chunk()
        after = [r.step() for _ in range(3)]
        return [np.asarray(step), first, *after], _state_and_pool(r)

    (want, want_state), (got, got_state) = serve(False), serve(True)
    for a, b in zip(want, got):
        np.testing.assert_array_equal(a, b)
    assert len(want_state) == len(got_state)
    for a, b in zip(want_state, got_state):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("sampling", [
    dict(temperature=0.0), dict(temperature=0.8, top_p=0.95, seed=5)],
    ids=["greedy", "seeded"])
def test_streams_are_the_same_with_and_without_a_ride(tiny, sampling):
    """Through the scheduler: a stream's tokens are the same whether it was
    admitted into an idle engine (its chunk a launch of its own) or into a
    busy one (its chunk rides a neighbour's decode step), and the
    neighbour's are the same with and without an admission riding. The ride
    is one ``decode_chunk`` row: a decode row's counts and the chunk's."""
    import time

    from localai_tpu.engine import kvcache as kvc

    def serve(neighbour, arrival):
        flight = FlightRecorder(256)
        runner = ModelRunner(tiny.cfg, tiny.params, num_slots=4, max_ctx=96,
                             prefill_buckets=[16, 32], kv_dtype="float32",
                             paged=True, kv_block_tokens=16, prefill_chunk=16)
        s = Scheduler(runner, ByteTokenizer(), flight=flight, multi_step=1)
        a = b = None
        try:
            if neighbour:
                a = s.submit(GenRequest(prompt=list(b"neighbour"),
                                        max_new_tokens=60, ignore_eos=True,
                                        temperature=0.7, seed=1))
                while a.completion_tokens < 2:
                    time.sleep(0.001)
            if arrival:
                b = s.submit(GenRequest(prompt=list(b"a new prompt arrives"),
                                        max_new_tokens=8, ignore_eos=True,
                                        **sampling))
                b.result(timeout=60)
            if a is not None:
                a.result(timeout=60)
            m = s.metrics()
        finally:
            s.shutdown()
        rows = [r for r in flight.snapshot(limit=256)
                if r["program"] == "decode_chunk"]
        return (a and a.token_ids, b and b.token_ids, m, rows)

    _, alone, m_idle, rows_idle = serve(False, True)
    quiet, _, m_quiet, _ = serve(True, False)
    beside, rode, m_busy, rows_busy = serve(True, True)
    assert rode == alone and beside == quiet
    # 20 tokens: a chunk of 16, then the last of 4 in the 16 bucket
    assert (m_idle["chunk_rides"], m_idle["prefill_chunks"]) == (0, 2)
    assert (m_quiet["chunk_rides"], m_quiet["prefill_chunks"]) == (0, 1)
    assert (m_busy["chunk_rides"], m_busy["prefill_chunks"]) == (1, 3)
    assert not rows_idle and len(rows_busy) == 1
    row = rows_busy[0]
    assert (row["steps"], row["live_slots"]) == (1, 1)
    assert row["attended_tokens"] > 9       # the neighbour's context
    assert (row["chunk_tokens"], row["chunk_bucket"], row["chunk_offset"],
            row["chunk_parts"]) == (4, 16, 16, 1)
    assert row["chunk_ctx"] == kvc.attend_span(16, 16, 96, 16)

    from localai_tpu.obs import metrics as obs_metrics

    reg = obs_metrics.Registry()
    obs_metrics.update_engine_gauges("tiny", m_busy, registry=reg)
    assert ('localai_prefill_chunk_rides_total{model="tiny"} 1'
            in reg.render())


# ---------------------------------------------------------------------------
# meshed paged serving (ISSUE 8): the block pool sharded over a CPU mesh
# ---------------------------------------------------------------------------


def _tp_mesh():
    """data=4 × model=2 over the conftest's 8 virtual CPU devices: tiny's
    2 kv heads split over 'model', 4 slots over 'data'."""
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    return build_mesh(MeshPlan(data=4, model=2))


def test_runner_accepts_mesh_with_paged(tiny):
    """mesh != None with paged=True is a supported configuration (the PR 6
    'mesh forces contiguous' incompatibility is gone); only pipeline
    parallelism still forces the slot-contiguous layout."""
    from localai_tpu.parallel import sharding as shd
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    mesh = _tp_mesh()
    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    r = ModelRunner(tiny.cfg, params, num_slots=4, max_ctx=64,
                    prefill_buckets=[16], kv_dtype="float32", mesh=mesh,
                    paged=True, kv_block_tokens=16)
    assert r.paged and r.mesh is mesh

    from localai_tpu.parallel.pipeline import shard_params_pp

    import jax

    pp_mesh = build_mesh(MeshPlan(pipe=2), devices=jax.devices()[:2])
    with pytest.raises(ValueError, match="pipeline parallelism"):
        ModelRunner(tiny.cfg, shard_params_pp(tiny.params, tiny.cfg, pp_mesh),
                    num_slots=2, max_ctx=64, prefill_buckets=[16],
                    kv_dtype="float32", mesh=pp_mesh, paged=True)


def test_meshed_paged_matches_single_device_greedy(tiny):
    """Greedy parity: the head-sharded pool + data-sharded table mirror
    must reproduce the single-device paged engine token-for-token, two
    prompts of different lengths sharing the pool (chunked + short)."""
    from localai_tpu.parallel import sharding as shd

    mesh = _tp_mesh()
    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    kw = dict(num_slots=4, max_ctx=96, prefill_buckets=[16, 32],
              kv_dtype="float32", paged=True, kv_block_tokens=16,
              prefill_chunk=16)
    pa = list(b"the quick brown fox jumps over the dog")  # 3 chunks
    pb = list(b"hi")
    seqs = {}
    for name, r in (
        ("single", ModelRunner(tiny.cfg, tiny.params, **kw)),
        ("mesh", ModelRunner(tiny.cfg, params, mesh=mesh, **kw)),
    ):
        s1 = r.acquire_slot()
        t1 = r.admit(s1, pa, temperature=0.0)
        s2 = r.acquire_slot()
        t2 = r.admit(s2, pb, temperature=0.0)
        a, b = [t1], [t2]
        for _ in range(8):
            toks = r.step()
            a.append(int(toks[s1]))
            b.append(int(toks[s2]))
        seqs[name] = (a, b)
    assert seqs["mesh"] == seqs["single"]


def test_meshed_paged_int8_matches_single_device(tiny):
    """Scaled-int8 pool under the mesh: the f32 scale pool shards
    alongside the int8 values (same spec minus head_dim) and greedy
    decode tracks the single-device quantized path."""
    from localai_tpu.parallel import sharding as shd

    mesh = _tp_mesh()
    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    kw = dict(num_slots=4, max_ctx=64, prefill_buckets=[16, 32],
              kv_dtype="int8", paged=True, kv_block_tokens=16,
              prefill_chunk=16)
    prompt = list(b"quantized kv under a mesh")
    outs = {}
    for name, r in (
        ("single", ModelRunner(tiny.cfg, tiny.params, **kw)),
        ("mesh", ModelRunner(tiny.cfg, params, mesh=mesh, **kw)),
    ):
        s = r.acquire_slot()
        t = r.admit(s, prompt, temperature=0.0)
        outs[name] = [t] + [int(r.step()[s]) for _ in range(6)]
    assert outs["mesh"] == outs["single"]


def test_ring_paged_prefill_matches_contiguous_sp(tiny):
    """A long prompt on a 'seq' mesh takes the ring-attention paged path
    (one dispatch over all chips, K/V scattered through the block table)
    and must emit the same greedy stream as the contiguous SP engine —
    both prefills run the identical ring math, so this pins the paged
    scatter + paged decode halves."""
    import jax

    from localai_tpu.parallel import sharding as shd
    from localai_tpu.parallel.mesh import MeshPlan, build_mesh

    mesh = build_mesh(MeshPlan(data=2, seq=2, model=2))
    params = shd.shard_params(tiny.params, tiny.cfg, mesh)
    rc = ModelRunner(tiny.cfg, params, num_slots=4, max_ctx=128,
                     prefill_buckets=[64], kv_dtype="float32", mesh=mesh,
                     sp_threshold=32)
    rp = ModelRunner(tiny.cfg, params, num_slots=4, max_ctx=128,
                     prefill_buckets=[64], kv_dtype="float32", mesh=mesh,
                     sp_threshold=32, paged=True, kv_block_tokens=16,
                     prefill_chunk=16)
    assert rp.sp_enabled
    prompt = list(range(1, 45))
    sc = rc.acquire_slot()
    tc = rc.admit(sc, prompt, temperature=0.0)
    assert rc.last_prefill_path == "sp"
    sp = rp.acquire_slot()
    tp = rp.admit(sp, prompt, temperature=0.0)
    assert rp.last_prefill_path == "paged_sp"
    a = [tc] + [int(rc.step()[sc]) for _ in range(6)]
    b = [tp] + [int(rp.step()[sp]) for _ in range(6)]
    assert a == b

    # short prompts stay on the chunked path (no seq-wide dispatch for a
    # prompt that fits one chunk)
    s2 = rp.acquire_slot()
    rp.admit(s2, list(b"short"), temperature=0.0)
    assert rp.last_prefill_path == "paged"


def test_kv_overcommit_ratio_scales_default_pool(tiny, monkeypatch):
    """LOCALAI_KV_OVERCOMMIT scales the default pool past (or under) the
    contiguous footprint; explicit kv_num_blocks still wins."""
    kw = dict(num_slots=2, max_ctx=64, prefill_buckets=[16],
              kv_dtype="float32", paged=True, kv_block_tokens=16)
    base = ModelRunner(tiny.cfg, tiny.params, **kw)
    assert base.kv_overcommit == 1.0
    contiguous_blocks = 2 * base.max_blocks + 1

    monkeypatch.setenv("LOCALAI_KV_OVERCOMMIT", "1.5")
    grown = ModelRunner(tiny.cfg, tiny.params, **kw)
    assert grown.kv_overcommit == 1.5
    assert grown.allocator.num_blocks == int(
        2 * base.max_blocks * 1.5) + 1 > contiguous_blocks

    monkeypatch.setenv("LOCALAI_KV_OVERCOMMIT", "0.5")
    shrunk = ModelRunner(tiny.cfg, tiny.params, **kw)
    assert shrunk.allocator.num_blocks < contiguous_blocks

    explicit = ModelRunner(tiny.cfg, tiny.params, kv_num_blocks=7, **kw)
    assert explicit.allocator.num_blocks == 7  # absolute count wins

    sched = Scheduler(base, ByteTokenizer())
    try:
        assert sched.metrics()["kv_overcommit_ratio"] == 1.0
    finally:
        sched.shutdown()
