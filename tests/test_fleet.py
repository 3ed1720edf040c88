"""Fleet router: placement, failover, shed route-around, disaggregation.

Placement invariants run against stub replicas (pure host arithmetic);
serving invariants run against a real 2-decode + 1-prefill in-process
fleet of the tiny debug model; the wire contract (PrefillPrefix →
TransferPrefix) runs against two real in-process gRPC workers — the
acceptance matrix of ISSUE 7 on CPU."""

import threading
import time

import numpy as np
import pytest

from localai_tpu.config.app_config import AppConfig
from localai_tpu.config.model_config import ModelConfig
from localai_tpu.engine.scheduler import GenRequest
from localai_tpu.fleet.prefix import PrefixCache, assemble_chunks, pack_chunks
from localai_tpu.fleet.replica import BaseReplica, _Reply
from localai_tpu.fleet.router import FleetUnavailable, Router, affinity_key

TINY = {
    "name": "ftiny", "model": "debug:tiny", "context_size": 256,
    "parameters": {"temperature": 0.0, "max_tokens": 8},
    "engine": {"max_slots": 2, "prefill_buckets": [16, 32, 64, 128],
               "dtype": "float32", "kv_dtype": "float32",
               "kv_block_tokens": 16},
}


# ---------------------------------------------------------------------------
# wire codec + prefix cache (no engines)


def _fake_arrays(n=24, bf16=False):
    k = np.arange(2 * 3 * n * 4, dtype=np.float32).reshape(2, 3, n, 4)
    out = {"k": k, "v": k + 1.0,
           "kv_dtype": np.asarray("float32"), "kv_rope": np.asarray("roped")}
    if bf16:
        out["k"] = out["k"].astype(np.uint16)
        out["k_bf16"] = np.int8(1)
    return out


def test_chunk_roundtrip_and_ordering():
    tokens = list(range(100, 140))
    arrays = _fake_arrays(bf16=True)
    chunks = list(pack_chunks(tokens, arrays, chunk_bytes=256))
    assert len(chunks) > 1 and chunks[-1]["last"]
    assert chunks[0]["tokens"] == tokens and chunks[0]["n_tokens"] == 24
    got_tokens, got = assemble_chunks(iter(chunks))
    assert got_tokens == tokens
    np.testing.assert_array_equal(got["k"], arrays["k"])
    np.testing.assert_array_equal(got["v"], arrays["v"])
    assert "k_bf16" in got  # dtype markers survive the wire

    # out-of-order and truncated streams are refused, not mis-assembled
    with pytest.raises(ValueError, match="out-of-order"):
        assemble_chunks(iter([chunks[1]]))
    with pytest.raises(ValueError, match="truncated"):
        assemble_chunks(iter(chunks[:-1]))


def test_prefix_cache_lcp_and_wait():
    cache = PrefixCache(min_prefix=8)
    tokens = list(range(24))
    cache.store(tokens, _fake_arrays())
    # full-prompt hit still leaves the 1-token recompute tail
    hit = cache.lookup(tokens + [99])
    assert hit is not None and hit.n == 24 and hit.tokens == tokens
    assert cache.lookup(list(range(50, 60))) is None  # no shared prefix
    # a store below min_prefix never lands
    cache.store([1, 2, 3], _fake_arrays(n=3))
    assert cache.stats()["stores"] == 1

    # wait_for unblocks a waiter when the writer thread stores
    got = {}

    def waiter():
        got["arrays"] = cache.wait_for(list(range(200, 224)), timeout=5.0)

    t = threading.Thread(target=waiter)
    t.start()
    cache.store(list(range(200, 224)), _fake_arrays())
    t.join(5.0)
    assert got["arrays"] is not None


def test_assemble_rejects_corrupt_payload():
    # a garbled npz body must surface as ValueError (the TransferPrefix
    # handler maps that to INVALID_ARGUMENT), never zipfile.BadZipFile
    chunks = [{"transfer_id": "t", "seq": 0, "last": True,
               "data": b"PK\x03\x04 definitely not an npz",
               "tokens": list(range(20)), "n_tokens": 20}]
    with pytest.raises(ValueError, match="corrupt"):
        assemble_chunks(chunks)


def test_prefix_cache_byte_budget_and_disk_fallthrough(tmp_path):
    # byte budget: evict LRU past max_bytes, keep the newest entry even
    # when it alone exceeds the budget (the exporter blocks on it)
    small = PrefixCache(min_prefix=8, max_bytes=1)
    small.store(list(range(24)), _fake_arrays())
    assert small.stats()["entries"] == 1
    small.store(list(range(100, 124)), _fake_arrays())
    assert small.stats()["entries"] == 1  # first evicted, newest kept

    # fallthrough: stores forward to a disk tier; a RAM miss falls
    # through to it (a fleet replica with a configured disk prompt cache
    # keeps both reuse tiers — scheduler.attach_prompt_cache layer=True)
    from localai_tpu.engine.promptcache import PromptKVCache

    disk = PromptKVCache(tmp_path, min_prefix=8)
    ram = PrefixCache(min_prefix=8, fallthrough=disk, max_entries=1)
    ram.store(list(range(24)), _fake_arrays())
    assert disk.stats()["stores"] == 1
    ram.store(list(range(200, 224)), _fake_arrays())  # evicts the first
    hit = ram.lookup(list(range(24)) + [99])          # RAM miss → disk hit
    assert hit is not None and hit.n == 24


# ---------------------------------------------------------------------------
# router placement (stub replicas)


class _StubReplica:
    def __init__(self, rid, role="decode", state="healthy", inflight=0):
        self.id, self.role, self.state = rid, role, state
        self.inflight = inflight
        self.dispatched = 0

    @property
    def load(self):
        return (self.inflight, self.dispatched)


class _StubPool:
    def __init__(self, replicas):
        self.replicas = replicas

    def healthy(self, role="decode"):
        return [r for r in self.replicas
                if r.state == "healthy" and r.role == role]


def _prompt(seed, tail=0):
    return [seed] * 64 + list(range(tail))


def test_affinity_keeps_same_prefix_on_one_replica():
    pool = _StubPool([_StubReplica(f"m/r{i}") for i in range(3)])
    router = Router(pool, None, block_tokens=16)
    # same first blocks, different tails → same replica every time
    picks = {router.route(_prompt(7, tail=t))[0].id for t in (0, 5, 11, 23)}
    assert len(picks) == 1
    assert router.routed["affinity"] == 4
    # a short prompt (no full block) has no affinity signal
    _, reason = router.route([1, 2, 3])
    assert reason == "least_loaded"


def test_consistent_hashing_remaps_only_the_lost_replica():
    ids = [f"m/r{i}" for i in range(3)]
    full = _StubPool([_StubReplica(r) for r in ids])
    prompts = [_prompt(s) for s in range(40)]
    before = {tuple(p): Router(full, None, block_tokens=16).route(p)[0].id
              for p in prompts}
    lost = ids[2]
    smaller = _StubPool([_StubReplica(r) for r in ids[:2]])
    router = Router(smaller, None, block_tokens=16)
    moved = sum(
        1 for p in prompts
        if before[tuple(p)] != lost
        and router.route(p)[0].id != before[tuple(p)]
    )
    assert moved == 0  # only the lost replica's keys remap


def test_shed_replica_routed_around():
    pool = _StubPool([_StubReplica(f"m/r{i}") for i in range(3)])
    router = Router(pool, None, block_tokens=16)
    target = router.route(_prompt(3))[0]

    class _Shed:
        def __init__(self, shed):
            self.shed = shed

        def shedding(self, rid):
            return rid in self.shed

    router = Router(pool, _Shed({target.id}), block_tokens=16)
    pick, reason = router.route(_prompt(3))
    assert pick.id != target.id and reason == "affinity"
    assert router.routed_around == 1
    # every replica shedding: degrade to serving, not a fleet-wide 503
    router = Router(pool, _Shed({r.id for r in pool.replicas}),
                    block_tokens=16)
    assert router.route(_prompt(3))[0] is not None


def test_failover_excludes_and_exhausts():
    pool = _StubPool([_StubReplica("m/r0"), _StubReplica("m/r1")])
    router = Router(pool, None, block_tokens=16)
    p = _prompt(9)
    first = router.route(p)[0]
    second, reason = router.route(p, exclude={first.id}, failover=True)
    assert second.id != first.id and reason == "failover"
    with pytest.raises(FleetUnavailable):
        router.route(p, exclude={first.id, second.id})


def test_affinity_key_block_granularity():
    assert affinity_key(list(range(10)), block_tokens=16) is None
    a = affinity_key(list(range(100)), block_tokens=16, blocks=4)
    b = affinity_key(list(range(64)) + [999] * 36, block_tokens=16, blocks=4)
    assert a == b  # only the first K blocks participate
    assert a != affinity_key([5] + list(range(1, 100)), block_tokens=16)


# ---------------------------------------------------------------------------
# in-process fleet (real engines)


def _build_fleet(replicas=2, prefill=1, threshold=48):
    from localai_tpu.fleet import FleetServingModel
    from localai_tpu.fleet.replica import InProcessReplica
    from localai_tpu.models.manager import build_serving_model

    app = AppConfig()
    mcfg = ModelConfig.model_validate(TINY)

    def factory(rid, role):
        return InProcessReplica(
            rid, role, lambda: build_serving_model(mcfg, app))

    return FleetServingModel(mcfg, app, factory, replicas=replicas,
                             prefill_replicas=prefill,
                             disagg_threshold=threshold)


@pytest.fixture(scope="module")
def fleet():
    fm = _build_fleet()
    yield fm
    fm.close()


def _gen(fm, text, max_new=6, **kw):
    h = fm.scheduler.submit(GenRequest(
        prompt=fm.tokenizer.encode(text), max_new_tokens=max_new,
        temperature=0.0, **kw))
    h.result(timeout=300)
    return h


def test_fleet_affinity_placement_serves_one_replica(fleet):
    prompt = "the same shared prompt prefix, different request"  # ≥ 1 block
    texts = set()
    for _ in range(3):
        h = _gen(fleet, prompt)
        assert h.finish_reason in ("stop", "length")
        texts.add(h.text)
    assert len(texts) == 1  # greedy determinism through the fleet
    # all three landed on one replica (prefix reuse survives scale-out)
    served = [r for r in fleet.pool.replicas
              if r.role == "decode" and r.dispatched > 0]
    assert len(served) == 1
    # request 1 is a ring pick; repeats may route by the prefix
    # directory instead (same replica, reason "directory")
    routed = fleet.router.routed
    assert routed["affinity"] + routed.get("directory", 0) >= 3


def test_disaggregated_handoff_matches_single_engine(fleet):
    from localai_tpu.models.manager import build_serving_model

    long_prompt = "disaggregate this long prompt please " * 5  # ≥ threshold
    before = fleet.scheduler.prefix_transfers
    h = _gen(fleet, long_prompt, max_new=8)
    assert h.finish_reason in ("stop", "length")
    assert fleet.scheduler.prefix_transfers == before + 1
    assert fleet.scheduler.prefix_transfer_bytes > 0

    # byte-identical greedy completion vs one single paged engine
    single = build_serving_model(ModelConfig.model_validate(TINY),
                                 AppConfig())
    try:
        ref = single.scheduler.submit(GenRequest(
            prompt=single.tokenizer.encode(long_prompt),
            max_new_tokens=8, temperature=0.0))
        ref.result(timeout=300)
        assert ref.text == h.text
    finally:
        single.scheduler.shutdown()


def test_dead_replica_failover_and_respawn(fleet):
    prompt = "failover probe prompt, affinity-long"  # 1 block, < threshold
    target, _ = fleet.router.route(fleet.tokenizer.encode(prompt))
    target.kill()
    # dispatch to the corpse fails instantly (no tokens streamed) → the
    # request fails over and completes on another replica
    h = _gen(fleet, prompt)
    assert h.finish_reason in ("stop", "length")
    assert fleet.scheduler.failovers >= 1
    assert target.state in ("dead", "respawning", "healthy")
    # subsequent requests route around the dead replica
    if target.state != "healthy":
        pick, _ = fleet.router.route(fleet.tokenizer.encode(prompt))
        assert pick.id != target.id
    # ... until its respawn passes health and it rejoins the ring
    deadline = time.monotonic() + 180
    while target.state != "healthy" and time.monotonic() < deadline:
        time.sleep(0.1)
    assert target.state == "healthy"
    # the crash left an error burst in the replica's SLO window, so the
    # router keeps routing AROUND it (shedding) until the window drains —
    # prove both halves: traffic still lands somewhere healthy now, and
    # affinity returns the moment the burst is gone (reset = time passing)
    pick, _ = fleet.router.route(fleet.tokenizer.encode(prompt))
    assert pick.state == "healthy"
    fleet.slo.reset()
    pick, reason = fleet.router.route(fleet.tokenizer.encode(prompt))
    # the prefix directory may (correctly) keep preferring the replica
    # that served the failover traffic — ITS copy of the KV is the warm
    # one. Drop that record to prove the ring itself forgot nothing:
    if reason == "directory" and fleet.scheduler.directory is not None:
        fleet.scheduler.directory.drop_replica(pick.id)
        pick, reason = fleet.router.route(fleet.tokenizer.encode(prompt))
    assert pick.id == target.id  # ring affinity restored after recovery


def test_kill_mid_request_fleet_keeps_serving(fleet):
    prompt = "stream then die midway through here"  # 1 block, < threshold
    target, _ = fleet.router.route(fleet.tokenizer.encode(prompt))
    h = fleet.scheduler.submit(GenRequest(
        prompt=fleet.tokenizer.encode(prompt), max_new_tokens=200,
        temperature=0.0, ignore_eos=True, stream=True))
    for item in h:
        if item.delta:
            target.kill()
            break
    h.result(timeout=120)
    # the kill races the (fast) tiny engine: either it landed mid-stream
    # (clean error, streamed deltas still counted) or the stream had
    # already finished — never a hang, never a zero-token "success"
    assert h.finish_reason in ("error", "length", "stop")
    assert h.completion_tokens > 0
    # the fleet keeps serving while the corpse respawns
    h2 = _gen(fleet, "the fleet survives a replica death")
    assert h2.finish_reason in ("stop", "length")
    deadline = time.monotonic() + 180
    while target.state != "healthy" and time.monotonic() < deadline:
        time.sleep(0.1)
    assert target.state == "healthy"
    fleet.slo.reset()  # drain the crash burst for later tests


# ---------------------------------------------------------------------------
# failover semantics, pinned deterministically with scripted replicas


class _ScriptedReplica(BaseReplica):
    """Stub replica whose predict_stream plays a script: "delta" yields
    one message, "raise" dies mid-transport, anything else ends the
    stream with a usage reply."""

    def __init__(self, rid, role):
        super().__init__(rid, role)
        self.dead_flag = False
        self.script = []

    def start(self):
        pass

    def _dial(self, timeout):
        return not self.dead_flag

    def process_alive(self):
        return not self.dead_flag

    def predict_stream(self, opts, trace_id="", tenant=""):
        steps = self.script.pop(0) if self.script else ["final"]
        for step in steps:
            if step == "delta":
                yield _Reply(b"x")
            elif step == "raise":
                self.dead_flag = True
                raise RuntimeError("scripted transport death")
            else:
                yield _Reply(b"", 3, 5, "stop")

    def metrics(self):
        return {}

    def stop(self):
        pass


def _scripted_fleet():
    from types import SimpleNamespace

    from localai_tpu.fleet.pool import ReplicaPool
    from localai_tpu.fleet.serving import FleetScheduler
    from localai_tpu.obs.slo import SLOTracker

    pool = ReplicaPool("scripted", _ScriptedReplica, replicas=2,
                       health_interval=3600.0)
    pool.start()
    router = Router(pool, None, block_tokens=16)
    sched = FleetScheduler(
        SimpleNamespace(name="scripted"), pool, router,
        SLOTracker(targets={"e2e_ms": float("inf")}),
        disagg_threshold=1 << 30)
    return pool, router, sched


def test_prestream_death_fails_over_transparently():
    pool, router, sched = _scripted_fleet()
    try:
        prompt = list(range(32))
        target, _ = router.route(prompt)
        target.script = [["raise"]]          # dies before any delta
        h = sched.submit(GenRequest(prompt=prompt, max_new_tokens=4))
        h.result(timeout=30)
        assert h.finish_reason == "stop"     # the other replica finished it
        assert sched.failovers == 1
        assert target.state in ("dead", "respawning")
    finally:
        pool.shutdown()


def test_midstream_death_is_a_clean_error():
    pool, router, sched = _scripted_fleet()
    try:
        prompt = list(range(32))
        target, _ = router.route(prompt)
        target.script = [["delta", "delta", "raise"]]  # dies mid-stream
        h = sched.submit(GenRequest(prompt=prompt, max_new_tokens=4))
        h.result(timeout=30)
        # tokens already reached the client: not transparently resumable —
        # a clean error, with the streamed work still counted
        assert h.finish_reason == "error"
        assert h.completion_tokens == 2
        assert sched.failovers == 0
        # the fleet itself keeps serving on the survivor
        h2 = sched.submit(GenRequest(prompt=prompt, max_new_tokens=4))
        h2.result(timeout=30)
        assert h2.finish_reason == "stop"
    finally:
        pool.shutdown()


def test_fleet_metrics_and_gauges(fleet):
    from localai_tpu.obs.metrics import REGISTRY

    m = fleet.engine_metrics()
    assert m["total_generated_tokens"] > 0
    assert m["fleet"]["replicas"].get("healthy", 0) >= 1
    assert sum(m["fleet"]["routed"].values()) > 0
    status = fleet.fleet_status()
    assert {r["id"] for r in status["replicas"]} == \
        {r.id for r in fleet.pool.replicas}
    fleet.scheduler.export_gauges()
    expo = REGISTRY.render()
    assert 'localai_fleet_replicas{model="ftiny",state="healthy"}' in expo
    assert 'localai_fleet_routed_total{model="ftiny"' in expo
    assert ('localai_fleet_prefix_transfer_bytes_total{model="ftiny"}'
            in expo)


# ---------------------------------------------------------------------------
# the real thing: spawned worker processes, kill -9, respawn


@pytest.mark.slow
def test_worker_fleet_kill9_failover_and_respawn(tmp_path):
    """kill -9 of one worker replica mid-stream: the request fails over
    (or errors cleanly if tokens already streamed), the serving process
    stays up, subsequent requests route around the corpse, and the
    replica rejoins after its respawn passes health."""
    from localai_tpu.fleet import FleetServingModel
    from localai_tpu.fleet.replica import WorkerReplica

    app = AppConfig(model_path=str(tmp_path),
                    worker_env={"JAX_PLATFORMS": "cpu"})
    mcfg = ModelConfig.model_validate({**TINY, "context_size": 96})

    def factory(rid, role):
        return WorkerReplica(rid, role, mcfg, app, env=app.worker_env)

    fm = FleetServingModel(mcfg, app, factory, replicas=2)
    try:
        prompt = "kill nine this worker replica mid-stream"
        target, _ = fm.router.route(fm.tokenizer.encode(prompt))
        h = fm.scheduler.submit(GenRequest(
            prompt=fm.tokenizer.encode(prompt), max_new_tokens=80,
            temperature=0.0, ignore_eos=True, stream=True))
        killed = False
        for item in h:
            if item.delta and not killed:
                target.kill()  # SIGKILL the worker process
                killed = True
            if item.finish_reason is not None:
                break
        assert killed
        h.result(timeout=240)
        # mid-stream → clean error; if the tiny engine outran the kill,
        # a natural finish — never a hang, never a 0-token success
        assert h.finish_reason in ("error", "length", "stop")
        assert h.completion_tokens > 0

        # the serving process survives and the fleet keeps serving
        h2 = _gen(fm, "the fleet is still serving after kill -9")
        assert h2.finish_reason in ("stop", "length")
        if target.state != "healthy":
            pick, _ = fm.router.route(fm.tokenizer.encode(prompt))
            assert pick.id != target.id  # routed around the corpse

        # ...until the respawned process passes health + LoadModel again
        deadline = time.monotonic() + 300
        while target.state != "healthy" and time.monotonic() < deadline:
            time.sleep(0.2)
        assert target.state == "healthy"
        fm.slo.reset()  # the crash burst has served its purpose
        h3 = _gen(fm, prompt)
        assert h3.finish_reason in ("stop", "length")
    finally:
        fm.close()


# ---------------------------------------------------------------------------
# the wire contract: PrefillPrefix → TransferPrefix across real gRPC workers


def test_prefix_transfer_over_grpc_workers():
    import yaml

    from localai_tpu.worker import WorkerClient
    from localai_tpu.worker import backend_pb2 as pb
    from localai_tpu.worker.server import BackendServicer, serve_worker

    cfg_yaml = yaml.safe_dump({**TINY, "context_size": 96})
    servers = []
    clients = []
    try:
        for _ in range(2):
            servicer = BackendServicer()
            server, port = serve_worker("127.0.0.1:0", servicer=servicer,
                                        block=False)
            client = WorkerClient(f"127.0.0.1:{port}")
            assert client.load_model(config_yaml=cfg_yaml).success
            servers.append((server, servicer))
            clients.append(client)
        prefill, decode = clients
        prompt = "transfer this prefix over the wire please!"  # > 16 tokens

        # prefill worker exports; the relay feeds the decode worker
        chunks = prefill.prefill_prefix(pb.PredictOptions(
            prompt=prompt, max_tokens=8, temperature=0.0))
        res = decode.transfer_prefix(chunks)
        assert res.success and "rows" in res.message

        # the decode worker resumes from the transferred prefix and emits
        # the same greedy completion as the prefill worker would natively
        got = decode.predict(pb.PredictOptions(
            prompt=prompt, max_tokens=6, temperature=0.0))
        ref = prefill.predict(pb.PredictOptions(
            prompt=prompt, max_tokens=6, temperature=0.0))
        assert got.message == ref.message
        assert got.finish_reason in ("stop", "length")
    finally:
        for c in clients:
            c.close()
        for server, servicer in servers:
            servicer.shutdown()
            server.stop(grace=None)


def test_queue_override_degrades_affinity_to_least_loaded():
    """A drowning affinity target (reported decode queue depth past
    LOCALAI_FLEET_QUEUE_OVERRIDE) loses its affinity claim: the request
    places least-loaded with reason queue_override; below the threshold
    the affinity placement stands."""
    pool = _StubPool([_StubReplica(f"m/r{i}") for i in range(3)])
    router = Router(pool, None, block_tokens=16, queue_override=4)
    p = _prompt(9)
    target = router.route(p)[0]
    assert router.routed["affinity"] == 1

    target.queue_depth = 4          # at the threshold: affinity holds
    assert router.route(p)[0] is target

    target.queue_depth = 5          # past it: least-loaded wins
    target.inflight = 3             # make the target clearly NOT least-loaded
    pick, reason = router.route(p)
    assert pick is not target and reason == "queue_override"
    assert router.routed["queue_override"] == 1

    # threshold off (0) ignores queue depth entirely
    router0 = Router(pool, None, block_tokens=16)
    assert router0.route(p)[0] is target


def test_queue_override_noop_when_target_is_least_loaded():
    """When the affinity target is simultaneously the least-loaded
    replica, the override keeps it (and keeps the affinity accounting —
    nothing actually moved)."""
    reps = [_StubReplica(f"m/r{i}", inflight=5) for i in range(3)]
    pool = _StubPool(reps)
    router = Router(pool, None, block_tokens=16, queue_override=1)
    p = _prompt(9)
    target = router.route(p)[0]
    target.queue_depth = 10
    target.inflight = 0             # drowning by depth, idle by inflight
    pick, reason = router.route(p)
    assert pick is target and reason == "affinity"


def test_pool_monitor_tracks_queue_depth():
    """With tracking on, the dial sweep refreshes each healthy replica's
    reported queue depth from its metrics dict."""
    from localai_tpu.fleet.pool import ReplicaPool

    class _R(BaseReplica):
        def __init__(self, rid):
            super().__init__(rid, "decode")
            self.state = "healthy"

        def start(self):
            pass

        def _dial(self, timeout):
            return True

        def process_alive(self):
            return True

        def metrics(self):
            return {"queue_depth": 7, "occupancy": 1.0}

        def stop(self):
            pass

    pool = ReplicaPool("m", lambda rid, role: _R(rid), replicas=0,
                       track_queue_depth=True)
    r = _R("m/r0")
    pool.replicas.append(r)
    pool.poll_once()
    assert r.queue_depth == 7


def test_respawn_backoff_grows_caps_and_resets():
    """A replica whose respawn keeps failing is retried on jittered
    exponential backoff (strictly growing across the first doublings,
    never past the cap, skipped until the hold expires); a successful
    rejoin resets the clock and zeroes the gauge."""
    from localai_tpu.fleet.pool import ReplicaPool
    from localai_tpu.obs.metrics import REGISTRY

    class _Flaky(BaseReplica):
        def __init__(self, rid, role="decode"):
            super().__init__(rid, role)
            self.fail_starts = 0
            self.up = True

        def start(self):
            if self.fail_starts > 0:
                self.fail_starts -= 1
                raise RuntimeError("boot refused")
            self.up = True

        def _dial(self, timeout):
            return self.up

        def process_alive(self):
            return self.up

        def metrics(self):
            return {}

        def stop(self):
            pass

    pool = ReplicaPool("backoff", lambda rid, role: _Flaky(rid, role),
                       replicas=1, health_interval=3600.0)
    pool.respawn_backoff_base = 0.05
    pool.respawn_backoff_cap = 0.15
    pool.start()
    try:
        r = pool.replicas[0]
        r.fail_starts = 3
        r.up = False
        pool.note_failure(r)
        backoffs = []
        deadline = time.monotonic() + 30
        while len(backoffs) < 3 and time.monotonic() < deadline:
            pool.poll_once()
            b = pool.respawn_backoff_s.get(r.id)
            if b is not None and (not backoffs or b != backoffs[-1]):
                backoffs.append(b)
            time.sleep(0.01)
        assert len(backoffs) == 3, backoffs
        # ±25% jitter bands of 0.05/0.10 are disjoint → strict growth;
        # the third doubling (0.20) must clip to the 0.15 cap
        assert backoffs[1] > backoffs[0], backoffs
        assert all(b <= pool.respawn_backoff_cap for b in backoffs)
        deadline = time.monotonic() + 30
        while r.state != "healthy" and time.monotonic() < deadline:
            pool.poll_once()
            time.sleep(0.01)
        assert r.state == "healthy"
        assert r.id not in pool.respawn_backoff_s  # clock reset on rejoin
        assert pool.snapshot()["respawn_backoff_s"] == {}
        text = REGISTRY.render()
        assert ('localai_fleet_respawn_backoff_s'
                '{model="backoff",replica="backoff/r0"} 0.0') in text
    finally:
        pool.shutdown()


# ---------------------------------------------------------------------------
# cross-host fleet: remote replica adoption, eviction/redial, RPC deadlines


def _grpc_workers(n):
    """n in-thread gRPC workers on 127.0.0.1 ports (the cross-host shape
    on loopback). Returns ([(server, servicer)], [addr])."""
    from localai_tpu.worker.server import BackendServicer, serve_worker

    workers, addrs = [], []
    for _ in range(n):
        sv = BackendServicer()
        server, port = serve_worker("127.0.0.1:0", servicer=sv,
                                    block=False)
        workers.append((server, sv))
        addrs.append(f"127.0.0.1:{port}")
    return workers, addrs


def _stop_grpc_workers(workers):
    for server, sv in workers:
        sv.shutdown()
        server.stop(grace=None)


def _remote_fleet(addrs, **kw):
    from localai_tpu.fleet import FleetServingModel

    app = AppConfig()
    mcfg = ModelConfig.model_validate({**TINY, "context_size": 96})
    return FleetServingModel(mcfg, app, lambda rid, role: None,
                             replicas=0, remote_hosts=list(addrs),
                             disagg_threshold=1 << 30, **kw)


def test_remote_adoption_from_fleet_hosts_and_registry_join():
    """Static adoption (the LOCALAI_FLEET_HOSTS path) boots remote
    workers into the pool as non-respawnable RemoteReplicas; a
    mid-traffic adopt_remote (the /federated/register path) joins
    another, under traffic, with the adoption counter moving and the
    newcomer taking least-loaded requests."""
    workers, addrs = _grpc_workers(2)
    fm = None
    try:
        fm = _remote_fleet(addrs[:1])
        assert [r.state for r in fm.pool.replicas] == ["healthy"]
        assert not fm.pool.replicas[0].respawnable
        h = _gen(fm, "served across the wire by an adopted remote")
        assert h.finish_reason in ("stop", "length")
        snap = fm.pool.snapshot()
        assert snap["replicas"][0]["remote"] is True
        assert snap["replicas"][0]["address"] == addrs[0]

        # registry join mid-traffic: requests keep completing around it
        h_live = fm.scheduler.submit(GenRequest(
            prompt=fm.tokenizer.encode("in flight during the join"),
            max_new_tokens=24, temperature=0.0))
        verdict = fm.adopt_remote(addrs[1])
        assert verdict["adopted"] and verdict["state"] == "healthy"
        assert fm.pool.adoptions == 2  # the static host counts too
        h_live.result(timeout=120)
        assert h_live.finish_reason in ("stop", "length")
        # a duplicate join is refused, not doubled
        assert fm.adopt_remote(addrs[1])["adopted"] is False
        # the fresh peer (0 dispatched) absorbs least-loaded traffic
        joined = fm.pool.get(verdict["id"])
        for i in range(3):
            assert _gen(fm, f"[{i}]", max_new=3).finish_reason in (
                "stop", "length")
        assert joined.dispatched >= 1
    finally:
        if fm is not None:
            fm.close()
        _stop_grpc_workers(workers)


def test_partition_evicts_remote_with_zero_lost_requests():
    """fleet.dial + fleet.transport faults against one remote = a
    network partition: every request completes via route-around, the
    victim is EVICTED (distinct from local death/respawn), and healing
    the partition redials it back with the backoff clock reset."""
    from localai_tpu import faults

    workers, addrs = _grpc_workers(2)
    fm = None
    try:
        fm = _remote_fleet(addrs)
        pool = fm.pool
        pool.redial_backoff_base = 0.1
        pool.redial_backoff_cap = 0.5
        for i in range(2):
            _gen(fm, f"[w{i}]")  # both peers warm
        victim = pool.replicas[0]
        faults.arm(faults.FaultSpec(site="fleet.transport", mode="raise",
                                    match=victim.id, times=0))
        faults.arm(faults.FaultSpec(site="fleet.dial", mode="raise",
                                    match=victim.id, times=0))
        handles = [fm.scheduler.submit(GenRequest(
            prompt=fm.tokenizer.encode(
                f"partitioned request {i} with a full block of prompt"),
            max_new_tokens=5, temperature=0.0)) for i in range(5)]
        for h in handles:
            h.result(timeout=120)
        assert all(h.finish_reason in ("stop", "length")
                   for h in handles), [h.finish_reason for h in handles]
        deadline = time.monotonic() + 30
        while victim.state != "evicted" and time.monotonic() < deadline:
            pool.poll_once()
            time.sleep(0.05)
        assert victim.state == "evicted"
        assert pool.evictions == 1
        # partition heals → backed-off redial rejoins and resets
        faults.clear()
        deadline = time.monotonic() + 60
        while victim.state != "healthy" and time.monotonic() < deadline:
            pool.poll_once()
            time.sleep(0.05)
        assert victim.state == "healthy"
        assert pool.redials == 1
        assert victim.id not in pool.redial_backoff_s
    finally:
        faults.clear()
        if fm is not None:
            fm.close()
        _stop_grpc_workers(workers)


def test_redial_backoff_grows_caps_and_resets():
    """An evicted remote whose redials keep failing walks the jittered
    exponential hold schedule (growing, capped) and a successful rejoin
    zeroes the gauge — the remote twin of respawn backoff."""
    from localai_tpu import faults
    from localai_tpu.fleet.pool import ReplicaPool
    from localai_tpu.obs.metrics import REGISTRY

    class _Remote(BaseReplica):
        respawnable = False

        def __init__(self, rid, role="decode"):
            super().__init__(rid, role)
            self.state = "healthy"

        def start(self):
            pass

        def _dial(self, timeout):
            return True

        def process_alive(self):
            return True

        def metrics(self):
            return {}

        def stop(self):
            pass

    pool = ReplicaPool("redial", lambda rid, role: None, replicas=0,
                       health_interval=3600.0)
    pool.redial_backoff_base = 0.05
    pool.redial_backoff_cap = 0.15
    r = _Remote("redial/peer")
    pool.replicas.append(r)
    pool._started = True
    try:
        faults.arm(faults.FaultSpec(site="fleet.dial", mode="raise",
                                    match=r.id, times=4))
        pool.note_failure(r)
        assert r.state == "evicted"
        backoffs = []
        deadline = time.monotonic() + 30
        while len(backoffs) < 3 and time.monotonic() < deadline:
            pool.poll_once()
            b = pool.redial_backoff_s.get(r.id)
            if b is not None and (not backoffs or b != backoffs[-1]):
                backoffs.append(b)
            time.sleep(0.01)
        assert len(backoffs) == 3, backoffs
        assert backoffs[1] > backoffs[0], backoffs
        assert all(b <= pool.redial_backoff_cap for b in backoffs)
        deadline = time.monotonic() + 30
        while r.state != "healthy" and time.monotonic() < deadline:
            pool.poll_once()
            time.sleep(0.01)
        assert r.state == "healthy"
        assert r.id not in pool.redial_backoff_s
        assert pool.evictions == 1 and pool.redials == 1
        text = REGISTRY.render()
        assert ('localai_fleet_redial_backoff_s'
                '{model="redial",replica="redial/peer"} 0.0') in text
        assert 'localai_fleet_evictions_total' in text
        assert 'localai_fleet_redials_total' in text
    finally:
        faults.clear()
        pool.shutdown()


def test_slow_link_deadline_fires_and_fails_over():
    """A replica whose stream stays silent past the fleet RPC deadline:
    the bounded pump raises, the dispatch fails over pre-stream, and the
    request completes on the healthy peer — a dead remote can never hang
    the dispatch thread."""
    from types import SimpleNamespace

    from localai_tpu.fleet.pool import ReplicaPool
    from localai_tpu.fleet.serving import FleetScheduler
    from localai_tpu.obs.slo import SLOTracker

    class _SlowReplica(_ScriptedReplica):
        slow = False

        def predict_stream(self, opts, trace_id="", tenant=""):
            if self.slow:
                time.sleep(5.0)  # silence, not an error — like a
                #                  partitioned peer
            yield _Reply(b"x")
            yield _Reply(b"", 3, 5, "stop")

    pool = ReplicaPool("slow", _SlowReplica, replicas=2,
                       health_interval=3600.0)
    pool.start()
    router = Router(pool, None, block_tokens=16)
    sched = FleetScheduler(
        SimpleNamespace(name="slow"), pool, router,
        SLOTracker(targets={"e2e_ms": float("inf")}),
        disagg_threshold=1 << 30, rpc_timeout_s=0.5)
    try:
        prompt = list(range(32))
        victim, _ = router.route(prompt)
        victim.slow = True
        t0 = time.monotonic()
        h = sched.submit(GenRequest(prompt=prompt, max_new_tokens=4))
        h.result(timeout=30)
        assert h.finish_reason == "stop"      # the healthy peer finished
        assert sched.failovers == 1
        assert time.monotonic() - t0 < 4.0    # deadline, not the 5 s nap
    finally:
        pool.shutdown()


def test_bounded_stream_deadline_and_passthrough():
    from localai_tpu.fleet import net

    # passthrough: items come through in order, completion is clean
    assert list(net.bounded_stream(iter([1, 2, 3]), 5.0)) == [1, 2, 3]

    # an upstream exception is relayed, not swallowed
    def boom():
        yield 1
        raise RuntimeError("mid-stream death")

    it = net.bounded_stream(boom(), 5.0)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="mid-stream death"):
        next(it)

    # silence past the deadline raises RpcDeadlineExceeded
    def stall():
        yield 1
        time.sleep(10.0)
        yield 2

    it = net.bounded_stream(stall(), 0.3, rid="m/slow")
    assert next(it) == 1
    with pytest.raises(net.RpcDeadlineExceeded, match="m/slow"):
        next(it)


def test_call_with_retries_is_bounded_and_jittered():
    from localai_tpu.fleet import net

    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise ConnectionError("flap")
        return "ok"

    assert net.call_with_retries(flaky, retries=3,
                                 base_delay=0.01) == "ok"
    assert calls["n"] == 3

    def always_down():
        raise ConnectionError("down")

    with pytest.raises(ConnectionError):
        net.call_with_retries(always_down, retries=2, base_delay=0.01)


# ---------------------------------------------------------------------------
# per-replica device pinning presets (--fleet-device-pinning)


def test_pinning_env_partitions_tpu_hosts():
    from localai_tpu.fleet.pinning import pinning_env

    envs = [pinning_env(i, 4, platform="tpu", n_devices=8)
            for i in range(4)]
    slices = [e["TPU_VISIBLE_CHIPS"] for e in envs]
    assert slices == ["0,1", "2,3", "4,5", "6,7"]  # disjoint, covering
    # each worker is its own single-process topology over its slice (a
    # pod-sliced parent env must not leak in; without the bounds libtpu's
    # one-process-per-host lockfile aborts all but one worker)
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "2,1,1" for e in envs)
    assert all(e["JAX_PLATFORMS"] == "tpu" for e in envs)

    # one chip each on a four-chip host: the layout chip_smoke.py runs
    envs = [pinning_env(i, 4, platform="tpu", n_devices=4)
            for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1" for e in envs)

    # uneven split: remainder devices stay unused, never skew one replica
    envs = [pinning_env(i, 3, platform="tpu", n_devices=8)
            for i in range(3)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == \
        ["0,1", "2,3", "4,5"]


def test_pinned_tpu_worker_does_not_inherit_the_servers_cpu_platform(
        monkeypatch):
    """The fleet runbook: the server runs under --platform cpu so it holds
    no chip, and its pinned workers own the TPUs. The worker's spawn env is
    a copy of the server's os.environ plus the pinning keys — so the flag
    must not live in os.environ, and the pinning must name the platform
    itself, or every worker silently serves from the CPU."""
    import os
    import sys

    from localai_tpu.cli import main as cli
    from localai_tpu.fleet import pinning
    from localai_tpu.worker.process import WorkerProcess

    # 1. --platform no longer writes the process environment
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    served = {}
    monkeypatch.setitem(
        sys.modules, "localai_tpu.api.server",
        type(sys)("fake_server"))
    sys.modules["localai_tpu.api.server"].serve = (
        lambda cfg: served.setdefault("platform", cfg.platform))
    import jax

    before = jax.config.jax_platforms
    try:
        assert cli.main(["run", "--platform", "cpu"]) == 0
    finally:
        jax.config.update("jax_platforms", before)
    assert served["platform"] == "cpu"
    assert "JAX_PLATFORMS" not in os.environ

    # 2. even an ambient JAX_PLATFORMS=cpu (this sandbox's) loses to the
    # pinning env in the worker's spawn environment
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("LOCALAI_FLEET_PIN_PLATFORM", "tpu")
    monkeypatch.setenv("LOCALAI_FLEET_PIN_DEVICES", "4")
    env = pinning.pinned_worker_env(None, 2, 4)
    spawned = {}

    class FakePopen:
        def __init__(self, cmd, env=None, **kw):
            spawned.update(env)
            raise RuntimeError("spawn captured")

    monkeypatch.setattr("localai_tpu.worker.process.subprocess.Popen",
                        FakePopen)
    with pytest.raises(RuntimeError, match="spawn captured"):
        WorkerProcess("m/r2", env=env).start()
    assert spawned["JAX_PLATFORMS"] == "tpu"
    assert spawned["TPU_VISIBLE_CHIPS"] == "2"


def test_worker_on_the_wrong_device_is_refused():
    """A worker reports where its model actually loaded; a replica that
    was spawned for a TPU and came up elsewhere is refused."""
    import json

    from localai_tpu.worker.process import check_worker_device

    on = lambda platform: json.dumps({"device": {  # noqa: E731
        "platform": platform, "device_kind": "k", "device_count": 1}})
    assert check_worker_device(on("tpu"), {"JAX_PLATFORMS": "tpu"},
                               "w")["platform"] == "tpu"
    with pytest.raises(RuntimeError, match="spawned for platform 'tpu'"):
        check_worker_device(on("cpu"), {"JAX_PLATFORMS": "tpu"}, "w")
    # no declared platform, or a third-party worker that reports nothing
    assert check_worker_device(on("cpu"), None, "w")["platform"] == "cpu"
    assert check_worker_device("ok", {"JAX_PLATFORMS": "tpu"}, "w") == {}


def test_pinning_env_cpu_and_unknown_platforms():
    from localai_tpu.fleet.pinning import pinning_env

    env = pinning_env(1, 2, platform="cpu", n_devices=8)
    assert env["JAX_PLATFORMS"] == "cpu"
    assert "device_count=4" in env["XLA_FLAGS"]
    # no convention for gpu plugins → unpinned (operator keeps worker_env)
    assert pinning_env(0, 2, platform="gpu", n_devices=8) == {}
    # more replicas than devices → unpinned rather than empty slices
    assert pinning_env(0, 4, platform="tpu", n_devices=2) == {}
    with pytest.raises(ValueError):
        pinning_env(5, 4, platform="tpu", n_devices=8)


def test_pinned_worker_env_operator_keys_win():
    from localai_tpu.fleet import pinning

    orig = pinning.derive_pinning_env
    pinning.derive_pinning_env = lambda i, n: {
        "TPU_VISIBLE_CHIPS": "0,1", "TPU_PROCESS_BOUNDS": "1,1,1"}
    try:
        merged = pinning.pinned_worker_env(
            {"TPU_VISIBLE_CHIPS": "6,7", "MY_FLAG": "1"}, 0, 2)
    finally:
        pinning.derive_pinning_env = orig
    assert merged["TPU_VISIBLE_CHIPS"] == "6,7"    # explicit wins
    assert merged["MY_FLAG"] == "1"
    assert merged["TPU_PROCESS_BOUNDS"] == "1,1,1"  # derived fills gaps


def test_pinning_env_declared_topology_beats_backend_probe(monkeypatch):
    """With LOCALAI_FLEET_PIN_PLATFORM/_DEVICES set, derivation never
    touches the parent's JAX backend — the server can run --platform cpu
    on a TPU host and still pin its workers to the real chips."""
    from localai_tpu.fleet import pinning

    monkeypatch.setenv("LOCALAI_FLEET_PIN_PLATFORM", "tpu")
    monkeypatch.setenv("LOCALAI_FLEET_PIN_DEVICES", "8")
    env = pinning.derive_pinning_env(1, 4)
    assert env["TPU_VISIBLE_CHIPS"] == "2,3"  # not this process's CPUs


def test_pinning_without_a_declared_topology_is_an_error(monkeypatch):
    """No fallback to jax.devices() in the server: probing would take
    every chip the workers are about to be pinned to."""
    from localai_tpu.fleet import pinning

    monkeypatch.delenv("LOCALAI_FLEET_PIN_PLATFORM", raising=False)
    monkeypatch.delenv("LOCALAI_FLEET_PIN_DEVICES", raising=False)
    with pytest.raises(ValueError, match="LOCALAI_FLEET_PIN_PLATFORM"):
        pinning.derive_pinning_env(0, 4)
