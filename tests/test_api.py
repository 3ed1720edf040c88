"""HTTP API tests: full in-process server against the tiny debug model —
the analogue of the reference's in-process API suite
(/root/reference/core/http/app_test.go: boots the fiber app against a temp
models dir and drives it with real OpenAI clients)."""

import asyncio
import json
import threading

import httpx
import pytest

from localai_tpu.api.server import AppState, create_app
from localai_tpu.config.app_config import AppConfig
from localai_tpu.config.loader import ConfigLoader

TINY_YAML = """\
name: tiny
model: "debug:tiny"
context_size: 96
embeddings: true
parameters:
  temperature: 0.0
  max_tokens: 16
engine:
  max_slots: 4
  prefill_buckets: [16, 32]
  dtype: float32
  kv_dtype: float32
"""


class _ServerThread:
    """Real aiohttp server on a random port, in its own loop thread."""

    def __init__(self, state: AppState):
        self.state = state
        self.port = None
        self._loop = asyncio.new_event_loop()
        self._started = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        assert self._started.wait(30), "server failed to start"

    def _run(self):
        from aiohttp import web

        asyncio.set_event_loop(self._loop)

        async def boot():
            app = create_app(self.state)
            runner = web.AppRunner(app)
            await runner.setup()
            site = web.TCPSite(runner, "127.0.0.1", 0)
            await site.start()
            self.port = runner.addresses[0][1]
            self._runner = runner
            self._started.set()

        self._loop.run_until_complete(boot())
        self._loop.run_forever()

    @property
    def base(self) -> str:
        return f"http://127.0.0.1:{self.port}"

    def stop(self):
        async def down():
            await self._runner.cleanup()

        fut = asyncio.run_coroutine_threadsafe(down(), self._loop)
        fut.result(10)
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(10)


def make_state(models_dir, *, write_tiny: bool = False) -> AppState:
    """AppState over a models dir (shared with test_gallery). Upload and
    config dirs live NEXT TO the models dir (a tmp path) — the durable
    file/batch registries must never leak into the repo working dir."""
    from pathlib import Path

    models_dir = Path(models_dir)
    if write_tiny:
        (models_dir / "tiny.yaml").write_text(TINY_YAML)
    cfg = AppConfig(
        model_path=str(models_dir),
        # sibling dirs named after the (unique) tmp models dir, so states
        # built from different tmp paths never share durable registries
        upload_path=str(models_dir) + "_uploads",
        config_path=str(models_dir) + "_conf",
    )
    loader = ConfigLoader(models_dir)
    loader.load_from_path(context_size=cfg.context_size)
    return AppState(cfg, loader)


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    models = tmp_path_factory.mktemp("models")
    state = make_state(models, write_tiny=True)
    srv = _ServerThread(state)
    yield srv
    srv.stop()


@pytest.fixture(scope="module")
def client(server):
    with httpx.Client(base_url=server.base, timeout=120.0) as c:
        yield c


def test_welcome_and_health(client):
    assert client.get("/healthz").json()["status"] == "ok"
    r = client.get("/readyz").json()
    assert r["models_configured"] == 1
    root = client.get("/").json()
    assert "tiny" in root["models"]


def test_list_models(client):
    data = client.get("/v1/models").json()
    assert data["object"] == "list"
    assert [m["id"] for m in data["data"]] == ["tiny"]
    filtered = client.get("/v1/models", params={"filter": "nope"}).json()
    assert filtered["data"] == []


def test_chat_completion(client):
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "hello there"}],
        "max_tokens": 8,
    })
    assert r.status_code == 200, r.text
    body = r.json()
    assert body["object"] == "chat.completion"
    choice = body["choices"][0]
    assert choice["message"]["role"] == "assistant"
    assert choice["finish_reason"] in ("stop", "length")
    assert body["usage"]["prompt_tokens"] > 0
    assert body["usage"]["completion_tokens"] <= 8


def test_chat_default_model_resolution(client):
    r = client.post("/v1/chat/completions", json={
        "messages": [{"role": "user", "content": "no model given"}],
        "max_tokens": 4,
    })
    assert r.status_code == 200
    assert r.json()["model"] == "tiny"


def test_chat_streaming_sse(client):
    deltas, finals = [], []
    with client.stream("POST", "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "stream this"}],
        "max_tokens": 6,
        "stream": True,
    }) as r:
        assert r.status_code == 200
        assert r.headers["content-type"].startswith("text/event-stream")
        for line in r.iter_lines():
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                finals.append("DONE")
                continue
            chunk = json.loads(payload)
            assert chunk["object"] == "chat.completion.chunk"
            deltas.append(chunk["choices"][0])
    assert finals == ["DONE"]
    assert deltas[0]["delta"].get("role") == "assistant"
    assert deltas[-1]["finish_reason"] in ("stop", "length")


def test_chat_n_choices(client):
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "variants"}],
        "max_tokens": 4,
        "n": 2,
    })
    body = r.json()
    assert [c["index"] for c in body["choices"]] == [0, 1]


def test_chat_with_tools_returns_tool_calls(client):
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "weather in Oslo?"}],
        "max_tokens": 120,
        "temperature": 0.8,
        "seed": 11,
        "tools": [{
            "type": "function",
            "function": {
                "name": "get_weather",
                "parameters": {
                    "type": "object",
                    "properties": {"city": {"type": "string",
                                            "maxLength": 8}},
                    "required": ["city"],
                },
            },
        }],
    })
    assert r.status_code == 200, r.text
    choice = r.json()["choices"][0]
    msg = choice["message"]
    # grammar-constrained: either a real tool call or the no-action answer
    if msg.get("tool_calls"):
        assert choice["finish_reason"] == "tool_calls"
        call = msg["tool_calls"][0]["function"]
        assert call["name"] == "get_weather"
        json.loads(call["arguments"])
    else:
        assert msg["content"]


def test_chat_json_mode(client):
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "give me json"}],
        "max_tokens": 100,
        "temperature": 0.8,
        "seed": 3,
        "response_format": {"type": "json_object"},
    })
    content = r.json()["choices"][0]["message"]["content"]
    json.loads(content)  # must be valid JSON under the constraint


def test_completions(client):
    r = client.post("/v1/completions", json={
        "model": "tiny",
        "prompt": "Once upon a time",
        "max_tokens": 6,
    })
    body = r.json()
    assert body["object"] == "text_completion"
    assert body["choices"][0]["finish_reason"] in ("stop", "length")


def test_completions_echo_and_list_prompt(client):
    r = client.post("/v1/completions", json={
        "model": "tiny",
        "prompt": ["alpha", "beta"],
        "max_tokens": 3,
        "echo": True,
    })
    choices = r.json()["choices"]
    assert len(choices) == 2
    assert choices[0]["text"].startswith("alpha")
    assert choices[1]["text"].startswith("beta")


def test_edits(client):
    r = client.post("/v1/edits", json={
        "model": "tiny",
        "prompt": "helo wrld",
        "instruction": "fix spelling",
        "max_tokens": 6,
    })
    assert r.json()["object"] == "edit"


def test_embeddings(client):
    r = client.post("/v1/embeddings", json={
        "model": "tiny",
        "input": ["first text", "second text"],
    })
    body = r.json()
    assert body["object"] == "list"
    assert len(body["data"]) == 2
    dim = len(body["data"][0]["embedding"])
    assert dim == 64  # tiny hidden size
    assert body["data"][1]["index"] == 1
    # deterministic: same input → same vector
    r2 = client.post("/v1/embeddings", json={
        "model": "tiny", "input": "first text",
    })
    assert r2.json()["data"][0]["embedding"] == pytest.approx(
        body["data"][0]["embedding"]
    )


def test_tokenize(client):
    r = client.post("/v1/tokenize", json={
        "model": "tiny", "content": "hi",
    })
    assert r.json()["tokens"] == [104, 105]


def test_system_and_metrics(client):
    sysinfo = client.get("/system").json()
    assert sysinfo["devices"]
    assert "tiny" in sysinfo["configured_models"]
    metrics = client.get("/metrics").text
    assert "localai_api_call_seconds" in metrics
    assert 'path="/v1/chat/completions"' in metrics


def test_backend_monitor_and_shutdown(client):
    mon = client.post("/backend/monitor", json={"model": "tiny"}).json()
    assert mon["loaded"] is True
    assert mon["num_slots"] == 4
    shut = client.post("/backend/shutdown", json={"model": "tiny"}).json()
    assert shut["shutdown"] is True
    mon = client.post("/backend/monitor", json={"model": "tiny"}).json()
    assert mon["loaded"] is False
    # next request transparently reloads
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "reload"}],
        "max_tokens": 2,
    })
    assert r.status_code == 200


def test_unknown_model_404(client):
    r = client.post("/v1/chat/completions", json={
        "model": "missing",
        "messages": [{"role": "user", "content": "x"}],
    })
    assert r.status_code == 404
    assert r.json()["error"]["type"] == "invalid_request_error"


def test_bad_json_400(client):
    r = client.post("/v1/chat/completions", content=b"{not json")
    assert r.status_code == 400


def test_schema_mismatch_400(client):
    """Valid JSON, wrong shape → 400 invalid_request_error, never a 500."""
    r = client.post("/v1/chat/completions", json={"messages": "hi"})
    assert r.status_code == 400


def test_metrics_token_series(client):
    client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "count me"}],
        "max_tokens": 4,
    })
    r = client.get("/metrics")
    assert r.status_code == 200
    body = r.text
    assert 'localai_tokens_generated_total{model="tiny"}' in body
    assert 'localai_prompt_tokens_total{model="tiny"}' in body
    # histogram series must be labeled by route pattern, not raw path
    assert 'path="/v1/chat/completions"' in body


def test_metrics_engine_series(client):
    """/metrics carries the obs engine series after a generation: batch
    occupancy, cache-hit-rate family, speculative family, compile time."""
    client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "occupancy"}],
        "max_tokens": 4,
    })
    body = client.get("/metrics").text
    assert 'localai_batch_occupancy{model="tiny"}' in body
    assert 'localai_kv_slot_utilization{model="tiny"}' in body
    assert 'localai_ttft_seconds_count{model="tiny"}' in body
    assert 'localai_queue_wait_seconds_count{model="tiny"}' in body
    assert 'localai_requests_total{' in body
    assert 'localai_decode_dispatches_total{model="tiny"}' in body
    # compile time recorded by the runner's watched jit entry points —
    # the paged default prefills through the chunk program, contiguous
    # engines through "prefill"
    assert ('localai_xla_compile_seconds_total{program="prefill_chunk"}' in body
            or 'localai_xla_compile_seconds_total{program="prefill"}' in body)
    # family names present even with no series yet (scrape stability)
    assert "# TYPE localai_prompt_cache_hit_rate gauge" in body
    assert "# TYPE localai_speculative_accept_rate gauge" in body


def test_traces_endpoint_returns_span_tree(client):
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "trace tree"}],
        "max_tokens": 6,
    }, headers={"X-Trace-ID": "trace-span-tree"})
    assert r.status_code == 200
    assert r.headers.get("X-Trace-ID") == "trace-span-tree"
    data = client.get("/v1/traces", params={"limit": 100}).json()
    mine = [t for t in data["traces"] if t["trace_id"] == "trace-span-tree"]
    kinds = {t["kind"] for t in mine}
    assert "request" in kinds and "http" in kinds
    engine = next(t for t in mine if t["kind"] == "request")
    names = [c["name"] for c in engine["children"]]
    for phase in ("queued", "prefill", "decode"):
        assert phase in names
    assert engine["attrs"]["ttft_ms"] is not None
    assert engine["attrs"]["tpot_ms"] is not None
    assert engine["attrs"]["finish_reason"] in ("stop", "length")


def test_debug_timeline_merges_http_and_engine(client):
    client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "timeline"}],
        "max_tokens": 4,
    }, headers={"X-Trace-ID": "trace-timeline-1"})
    r = client.get("/debug/timeline/trace-timeline-1")
    assert r.status_code == 200
    body = r.json()
    sources = {e["kind"] for e in body["timeline"]}
    assert sources == {"http", "request"}
    offsets = [e["offset_ms"] for e in body["timeline"]]
    assert offsets == sorted(offsets) and offsets[0] == 0.0
    # unknown ids 404 rather than returning an empty timeline
    assert client.get("/debug/timeline/never-seen").status_code == 404


def test_streaming_first_token_event_recorded(client):
    with client.stream("POST", "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "first token"}],
        "max_tokens": 6,
        "stream": True,
    }, headers={"X-Trace-ID": "trace-sse-first"}) as r:
        assert r.status_code == 200
        for _line in r.iter_lines():
            pass
    body = client.get("/debug/timeline/trace-sse-first").json()
    assert any(e["name"] == "first_sse_write" for e in body["timeline"])


def test_auth_enforced(tmp_path):
    models = tmp_path / "models"
    models.mkdir()
    (models / "tiny.yaml").write_text(TINY_YAML)
    cfg = AppConfig(model_path=str(models), api_keys=["sekret"])
    loader = ConfigLoader(models)
    loader.load_from_path()
    state = AppState(cfg, loader)
    srv = _ServerThread(state)
    try:
        with httpx.Client(base_url=srv.base, timeout=30.0) as c:
            assert c.get("/healthz").status_code == 200  # exempt
            r = c.get("/v1/models")
            assert r.status_code == 401
            r = c.get("/v1/models",
                      headers={"Authorization": "Bearer wrong"})
            assert r.status_code == 401
            r = c.get("/v1/models",
                      headers={"Authorization": "Bearer sekret"})
            assert r.status_code == 200
    finally:
        srv.stop()


def test_completions_streaming_list_prompt_serves_all(client):
    """A list prompt streams EVERY prompt, each on its own choice index
    (previously only templated[0] streamed and the rest silently dropped)."""
    seen = {}
    finishes = {}
    usage = None
    with client.stream("POST", "/v1/completions", json={
        "model": "tiny",
        "prompt": ["alpha", "beta"],
        "max_tokens": 6,
        "stream": True,
    }) as r:
        assert r.status_code == 200
        for line in r.iter_lines():
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                break
            chunk = json.loads(payload)
            ch = chunk["choices"][0]
            idx = ch["index"]
            if ch["finish_reason"] is not None:
                finishes[idx] = ch["finish_reason"]
            else:
                seen[idx] = seen.get(idx, "") + ch["text"]
    assert set(finishes) == {0, 1}
    assert all(f in ("stop", "length") for f in finishes.values())
    assert set(seen) <= {0, 1}


def test_correlation_id_echoed_and_traced(client):
    """X-Correlation-ID flows from the request header into the scheduler's
    request (visible in engine metrics) and back out on the response
    (parity: chat.go:164-169)."""
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "trace me"}],
        "max_tokens": 4,
    }, headers={"X-Correlation-ID": "trace-abc-123"})
    assert r.status_code == 200
    assert r.headers.get("X-Correlation-ID") == "trace-abc-123"
    # without the header, the generated request id is echoed instead
    r2 = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "no header"}],
        "max_tokens": 4,
    })
    assert r2.headers.get("X-Correlation-ID", "").startswith("chatcmpl-")


def test_chat_streaming_n_choices(client):
    """stream + n>1: every choice streams on its own index and finishes."""
    finishes = {}
    usage = None
    roles = set()
    with client.stream("POST", "/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "variants"}],
        "max_tokens": 5,
        "n": 3,
        "stream": True,
    }) as r:
        assert r.status_code == 200
        for line in r.iter_lines():
            if not line.startswith("data: "):
                continue
            payload = line[len("data: "):]
            if payload == "[DONE]":
                break
            frame = json.loads(payload)
            if not frame["choices"]:
                usage = frame.get("usage")
                continue
            ch = frame["choices"][0]
            if ch["delta"].get("role"):
                roles.add(ch["index"])
            if ch["finish_reason"] is not None:
                finishes[ch["index"]] = ch["finish_reason"]
    assert set(finishes) == {0, 1, 2}
    assert roles == {0, 1, 2}
    assert all(f in ("stop", "length") for f in finishes.values())
    # one usage frame, prompt tokens counted once
    assert usage is not None
    assert usage["completion_tokens"] <= 15
    assert 0 < usage["prompt_tokens"] < 40


def test_backend_trace_capture(tmp_path):
    """POST /backend/trace captures a jax profiler trace to disk; bad
    input is a client error (400), a concurrent capture a conflict (409)."""
    state = make_state(tmp_path, write_tiny=True)
    srv = _ServerThread(state)
    try:
        import httpx

        with httpx.Client(base_url=srv.base, timeout=120.0) as c:
            r = c.post("/backend/trace", json={"seconds": 0.2})
            assert r.status_code == 200
            out = r.json()["trace_dir"]
            import pathlib

            assert pathlib.Path(out).exists()
            assert c.post("/backend/trace",
                          json={"seconds": 999}).status_code == 400
            assert c.post("/backend/trace",
                          json={"seconds": 0.2, "dir": "../../x"}
                          ).status_code == 400
            # malformed JSON body → 400, not an unhandled 500
            r = c.post("/backend/trace", content=b"{not json",
                       headers={"Content-Type": "application/json"})
            assert r.status_code == 400
            assert c.post("/backend/trace",
                          json=[1, 2]).status_code == 400
            assert c.post("/backend/trace",
                          json={"seconds": "soon"}).status_code == 400
            # one capture at a time: the profiler's shared capture lock
            # held (an anomaly capture in flight) → 409 Conflict
            from localai_tpu.obs.profiler import PROFILER

            assert PROFILER.acquire_capture()
            try:
                r = c.post("/backend/trace", json={"seconds": 0.2})
                assert r.status_code == 409
                assert "already running" in r.json()["error"]["message"]
            finally:
                PROFILER.release_capture()
    finally:
        srv.stop()


# -- introspection endpoints (obs round 6) -----------------------------------


def test_debug_devices_reports_health_and_census(client):
    r = client.get("/debug/devices", params={"probe_timeout": 60})
    assert r.status_code == 200
    data = r.json()
    assert data["devices"] and data["devices"][0]["platform"] == "cpu"
    # the CPU backend has no allocator stats; the field must be present
    # (and null) rather than absent, so dashboards can key on it
    assert "memory" in data["devices"][0]
    census = data["census"]
    assert census["arrays"] > 0
    # the loaded tiny model's weights and KV cache are attributed
    assert census["by_category"]["weights"] > 0
    assert census["by_category"]["kv_cache"] > 0
    assert data["probe"]["ok"] is True
    assert data["probe"]["seconds"] > 0
    assert "roofline" not in data
    assert isinstance(data["watchdog"], dict)


def test_debug_devices_probe_skippable(client):
    data = client.get("/debug/devices", params={"probe": "0"}).json()
    assert "probe" not in data
    assert client.get(
        "/debug/devices", params={"probe_timeout": "nan-ish"}
    ).status_code == 400


def test_debug_programs_reports_cost(client):
    # make sure the decode program has dispatched
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "cost catalog"}],
        "max_tokens": 24,
    })
    assert r.status_code == 200
    data = client.get("/debug/programs").json()
    assert "roofline" not in data
    programs = data["programs"]
    assert programs
    decode = [p for p in programs
              if p["program"].startswith("decode") and p.get("flops")]
    assert decode, f"no decode cost entry in {programs}"
    d = decode[0]
    # nonzero FLOPs/bytes for the decode-step program, and no rate or
    # fraction: a dispatch's wall is a host clock, not a device share
    assert d["flops"] > 0 and d["bytes_accessed"] > 0
    assert all("achieved_gbps" not in p and "bandwidth_fraction" not in p
               for p in decode)
    # filter to live instances: the backend-shutdown test earlier in this
    # module unloads/reloads the model, leaving dead catalog entries
    # (cost_error="program no longer live") next to the live ones.
    # Paged engines (the serving default) compile their prefill under the
    # chunked-prefill label; contiguous engines under "prefill".
    prefill = [p for p in programs
               if p["program"] in ("prefill", "prefill_chunk")
               and p.get("flops")]
    assert prefill and prefill[0]["flops"] > 0


def test_debug_stacks_lists_threads(client):
    data = client.get("/debug/stacks").json()
    assert data["threads"]
    names = {t["thread"] for t in data["threads"]}
    assert "MainThread" in names
    assert all("stack" in t for t in data["threads"])


def test_simulated_hung_dispatch_full_stall_lifecycle(client):
    """Acceptance: a test-injected blocking callable trips the watchdog
    within its deadline, sets engine_stalled=1 at /metrics, records a
    thread-stack forensic span retrievable via GET /v1/traces, and clears
    on recovery."""
    import threading as _threading
    import time as _time

    from localai_tpu.obs import Watchdog

    # default registry/store = the process-wide ones the server exposes
    wd = Watchdog(deadline=0.15, poll_interval=0.03)
    wd.start()
    release = _threading.Event()
    tripped = _threading.Event()
    wd.on_stall(lambda e: e.kind == "stall" and tripped.set())

    def hung_dispatch():
        with wd.guard("hung-dispatch"):
            release.wait(10.0)

    t = _threading.Thread(target=hung_dispatch, daemon=True)
    t.start()
    try:
        assert tripped.wait(3.0), "watchdog did not trip within deadline"
        text = client.get("/metrics").text
        assert 'localai_engine_stalled{channel="hung-dispatch"} 1' in text
        assert 'localai_stalls_total{channel="hung-dispatch"}' in text
        traces = client.get(
            "/v1/traces", params={"kind": "stall", "limit": 20}).json()
        mine = [tr for tr in traces["traces"]
                if tr["attrs"].get("channel") == "hung-dispatch"]
        assert mine, "forensic stall span not retrievable via /v1/traces"
        dump = mine[0]
        assert dump["attrs"]["threads"] >= 1
        stacks = [c["attrs"]["stack"] for c in dump["children"]
                  if c["name"] == "thread"]
        assert any("hung_dispatch" in s for s in stacks), (
            "stack dump must show the hung frame")
    finally:
        release.set()
        t.join(5.0)
    deadline = _time.monotonic() + 3.0
    while wd.stalled("hung-dispatch") and _time.monotonic() < deadline:
        _time.sleep(0.02)
    assert not wd.stalled("hung-dispatch")
    assert ('localai_engine_stalled{channel="hung-dispatch"} 0'
            in client.get("/metrics").text)
    wd.stop()


def test_metrics_exposes_device_health_series(client):
    text = client.get("/metrics").text
    # scrape-time refresh: live-bytes census always present; device_ok
    # appears once any probe ran (the /debug/devices test above)
    assert "# TYPE localai_hbm_live_bytes gauge" in text
    assert 'localai_hbm_live_bytes{category="kv_cache"}' in text
    assert "# TYPE localai_engine_stalled gauge" in text


# -- flight recorder + SLO observatory (obs round 7) -------------------------


def test_debug_flight_reports_dispatch_records(client):
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "flight record"}],
        "max_tokens": 24,
    })
    assert r.status_code == 200
    data = client.get("/debug/flight").json()
    assert "tiny" in data["models"]
    ring = data["models"]["tiny"]
    assert ring["records"], "flight ring empty after a generation"
    rec = ring["records"][-1]
    for key in ("ts", "ts_unix", "program", "steps", "dispatch_ms",
                "occupancy", "queue_depth", "kv_utilization", "tokens",
                "preemptions", "compile"):
        assert key in rec
    assert ring["dispatches"] >= len(ring["records"])
    assert ring["tokens_total"] > 0
    assert ring["capacity"] > 0
    assert "step_ms_p50" in ring["percentiles"]
    # ?since= windows the poll: everything before "now" filters out
    later = client.get("/debug/flight",
                       params={"since": data["now_monotonic"] + 100}).json()
    assert later["models"].get("tiny", {}).get("records") == []
    mid = rec["ts"] - 1e-9
    newer = client.get("/debug/flight", params={"since": mid}).json()
    assert newer["models"]["tiny"]["records"]
    assert client.get("/debug/flight",
                      params={"since": "soon"}).status_code == 400
    assert client.get("/debug/flight",
                      params={"limit": "many"}).status_code == 400


def test_the_endpoints_serve_a_rows_own_account(client):
    """PR 53: /debug/flight rows carry the measured parts of gap and the
    engine thread's clocks; /debug/anatomy the two parts (inside gap: in no
    sum) and the ``thread`` block; /metrics the two phase labels and the two
    counter families."""
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "account for the wall"}],
        "max_tokens": 24,
    })
    assert r.status_code == 200
    rows = client.get("/debug/flight").json()["models"]["tiny"]["records"]
    timed = ("process_ms", "book_ms", "free_ms", "span_ms", "wait_ms", "idle_ms",
             "cpu_ms", "runq_ms", "blocked_ms", "proc_cpu_ms")
    assert all(set(timed) <= set(row) for row in rows)
    row = next(r for r in reversed(rows) if r["program"].startswith("decode"))
    assert row["span_ms"] > 0
    assert (row["wait_ms"] + row["idle_ms"] + row["cpu_ms"]
            + (row["runq_ms"] or 0.0) + row["blocked_ms"]) == pytest.approx(
        row["span_ms"], abs=1e-3)
    data = client.get("/debug/anatomy", params={"window": 0}).json()
    assert data["phases"] == ["gap", "sched", "launch", "sync"]
    assert data["parts"] == ["process", "book", "free"]
    tiny = data["models"]["tiny"]
    assert set(tiny["part_share"]) == {"process", "book", "free"}
    assert {"process", "book", "free"} <= set(tiny["definitions"])
    assert tiny["process_ms_p50"] is not None
    assert sum(tiny["part_share"].values()) <= (
        tiny["phase_share"]["gap"] + 1e-3)
    assert set(tiny["thread"]["share"]) == {"cpu", "runq", "blocked", "wait",
                                            "idle"}
    assert tiny["thread"]["span_ms_total"] > 0
    text = client.get("/metrics").text
    for series in (
            'localai_dispatch_phase_ms{model="tiny",phase="process",'
            'quantile="p50"}',
            'localai_dispatch_phase_ms{model="tiny",phase="book",'
            'quantile="p90"}',
            'localai_dispatch_phase_ms{model="tiny",phase="free",'
            'quantile="p99"}',
            'localai_engine_thread_seconds_total{model="tiny",state="cpu"}',
            'localai_engine_thread_seconds_total{model="tiny",state="idle"}',
            'localai_slow_dispatch_total{model="tiny",owner="wait"}',
            'localai_slow_dispatch_total{model="tiny",owner="blocked"}'):
        assert series in text, series


def test_trace_detail_stitched_waterfall(client):
    # a single-engine model still renders the one-waterfall view (no
    # replica panes to harvest; front-door + engine spans untagged)
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "stitch detail"}],
        "max_tokens": 6,
    }, headers={"X-Trace-ID": "trace-detail-1"})
    assert r.status_code == 200
    body = client.get("/v1/traces/trace-detail-1").json()
    assert body["trace_id"] == "trace-detail-1"
    assert body["replicas"] == {}
    names = [e["name"] for e in body["waterfall"]]
    assert "decode" in names
    offsets = [e["offset_ms"] for e in body["waterfall"]]
    assert offsets == sorted(offsets)
    assert all(e["replica"] == "" for e in body["waterfall"])
    # unknown trace id → 404, not an empty waterfall
    assert client.get("/v1/traces/trace-nope-404").status_code == 404


def test_debug_fleet_flight_and_profiles(client):
    # no fleet-served model loaded: the merged view answers with an
    # empty models map (never errors), and the profile manifest renders
    # its (disarmed) state
    data = client.get("/debug/fleet/flight").json()
    assert data["models"] == {}
    assert client.get("/debug/fleet/flight",
                      params={"since": "soon"}).status_code == 400
    assert client.get("/debug/fleet/flight",
                      params={"limit": "many"}).status_code == 400
    prof = client.get("/debug/profiles").json()
    assert prof["enabled"] is False  # LOCALAI_PROFILE_ON_ANOMALY unset
    assert prof["profiles"] == [] and "cooldown_s" in prof


def test_metrics_exports_trace_ring_size(client):
    body = client.get("/metrics").text
    assert "localai_trace_ring_size 256" in body


def test_debug_kv_reports_block_audit(client):
    client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "kv audit"}],
        "max_tokens": 4,
    })
    data = client.get("/debug/kv").json()
    tiny = data["models"]["tiny"]
    blocks = tiny["blocks"]
    # conservation holds with all traffic drained
    assert blocks["free"] + blocks["used"] + blocks["cached"] \
        == blocks["total"]
    assert tiny["invariant_violations"] == []
    assert tiny["block_tokens"] >= 8
    assert "violations_seen" in tiny


def test_debug_faults_arm_list_clear(client):
    from localai_tpu import faults

    try:
        data = client.get("/debug/faults").json()
        assert data["active"] is False and data["armed"] == []
        assert "engine.drain" in data["sites"]
        # the in-process supervisor attached by build_serving_model shows
        assert data["supervisors"]["tiny"]["failed"] is False
        r = client.post("/debug/faults", json={
            "site": "engine.dispatch", "mode": "raise", "after": 3,
            "times": 1, "match": "decode"})
        assert r.status_code == 200
        data = client.get("/debug/faults").json()
        assert data["active"] is True
        assert data["armed"][0]["site"] == "engine.dispatch"
        assert client.post("/debug/faults", json={
            "site": "no.such.site"}).status_code == 400
        assert client.post("/debug/faults", json={
            "site": "engine.dispatch", "bogus": 1}).status_code == 400
        assert client.post("/debug/faults", json=[1, 2]).status_code == 400
        cleared = client.delete("/debug/faults").json()
        assert cleared["cleared"] == 1
        assert client.get("/debug/faults").json()["active"] is False
    finally:
        faults.clear()


def test_v1_slo_reports_windows(client):
    client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "slo window"}],
        "max_tokens": 4,
    })
    data = client.get("/v1/slo").json()
    assert data["windows"] == ["1m", "5m", "30m"]
    assert "targets" in data and "burn_threshold" in data
    tiny = data["models"]["tiny"]
    assert tiny["shedding"] is False
    agg = tiny["windows"]["1m"]
    assert agg["count"] >= 1
    assert agg["ttft_ms"] is not None and agg["ttft_ms"]["p95"] > 0
    assert agg["e2e_ms"]["p95"] >= agg["ttft_ms"]["p50"]


def test_overload_sheds_with_429_and_recovers(client):
    """Acceptance: a simulated overload (impossible TTFT target) flips
    localai_overload_shedding, 429s new generation work with Retry-After,
    counts the shed at /metrics and in the scheduler's metrics dict, and
    admits again once the observatory recovers."""
    from localai_tpu.obs import slo as obs_slo

    SLO = obs_slo.SLO
    saved = dict(targets=dict(SLO.targets), burn_threshold=SLO.burn_threshold,
                 recover_burn=SLO.recover_burn, min_events=SLO.min_events)
    SLO.reset()
    SLO.configure(targets={"ttft_ms": 1e-6}, burn_threshold=1.0,
                  recover_burn=1.0, min_events=2)
    try:
        # two completions violate the impossible target → both windows hot
        for i in range(2):
            r = client.post("/v1/chat/completions", json={
                "model": "tiny",
                "messages": [{"role": "user", "content": f"burn {i}"}],
                "max_tokens": 2,
            })
            assert r.status_code == 200
        r = client.post("/v1/chat/completions", json={
            "model": "tiny",
            "messages": [{"role": "user", "content": "shed me"}],
            "max_tokens": 2,
        })
        assert r.status_code == 429
        assert r.headers.get("Retry-After") == str(SLO.retry_after_s)
        assert "shedding load" in r.json()["error"]["message"]
        # streaming completions shed identically (same admission hook)
        r = client.post("/v1/completions", json={
            "model": "tiny", "prompt": "shed", "max_tokens": 2,
        })
        assert r.status_code == 429
        text = client.get("/metrics").text
        assert 'localai_overload_shedding{model="tiny"} 1' in text
        assert 'localai_requests_shed_total{model="tiny"} 2' in text
        assert 'localai_slo_burn_rate{model="tiny",window="1m"}' in text
        # the scheduler's JSON mirror counted both refusals
        em = client.get("/backend/metrics").json()
        assert em["tiny"]["shed_total"] == 2
        assert client.get("/v1/slo").json()["models"]["tiny"]["shedding"]
        # recovery: clear the objectives (operator action) → admitted again
        SLO.configure(targets={})
        r = client.post("/v1/chat/completions", json={
            "model": "tiny",
            "messages": [{"role": "user", "content": "recovered"}],
            "max_tokens": 2,
        })
        assert r.status_code == 200
        assert ('localai_overload_shedding{model="tiny"} 0'
                in client.get("/metrics").text)
    finally:
        SLO.configure(**saved)
        SLO.reset()


def test_slo_ui_page_served(client):
    r = client.get("/slo", headers={"Accept": "text/html"})
    assert r.status_code == 200
    assert "SLO observatory" in r.text
    assert "Flight recorder" in r.text


def test_debug_devices_probe_timeout_validated(client):
    # NaN/zero/negative → 400; inf is accepted but clamped server-side so
    # a wedged device can't pin an executor thread forever
    for bad in ("nan", "0", "-3"):
        assert client.get("/debug/devices",
                          params={"probe_timeout": bad}).status_code == 400
    assert client.get("/debug/devices",
                      params={"probe_timeout": "inf"}).status_code == 200


# ---------------------------------------------------------------------------
# offline batch API (localai_tpu.batch)


def _upload_batch_file(client, lines, name="batch_input.jsonl"):
    payload = ("\n".join(json.dumps(l) for l in lines) + "\n").encode()
    r = client.post("/v1/files", files={"file": (name, payload)},
                    data={"purpose": "batch"})
    assert r.status_code == 200, r.text
    return r.json()


def test_batch_api_end_to_end(client):
    """Acceptance: a job submitted via /v1/files + /v1/batches runs to
    completed with a downloadable per-line output file, while a concurrent
    interactive request keeps being served."""
    import time as _time

    f = _upload_batch_file(client, [
        {"custom_id": f"req-{i}", "method": "POST",
         "url": "/v1/chat/completions",
         "body": {"model": "tiny", "max_tokens": 4, "temperature": 0.0,
                  "messages": [{"role": "user",
                                "content": f"batch line {i}"}]}}
        for i in range(5)
    ])
    assert f["purpose"] == "batch"
    r = client.post("/v1/batches", json={
        "endpoint": "/v1/chat/completions",
        "input_file_id": f["id"],
        "metadata": {"suite": "test_api"},
    })
    assert r.status_code == 200, r.text
    job = r.json()
    assert job["object"] == "batch" and job["status"] == "validating"
    # a concurrent interactive request is admitted ahead of pending batch
    # lines (the lane policy) — and must simply succeed here
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "interactive wins"}],
        "max_tokens": 4,
    })
    assert r.status_code == 200
    deadline = _time.monotonic() + 120
    while _time.monotonic() < deadline:
        job = client.get(f"/v1/batches/{job['id']}").json()
        if job["status"] in ("completed", "failed", "cancelled", "expired"):
            break
        _time.sleep(0.2)
    assert job["status"] == "completed", job
    assert job["request_counts"] == {"total": 5, "completed": 5,
                                     "failed": 0}
    # listed, and the per-line output downloads through the file registry
    listed = client.get("/v1/batches").json()
    assert job["id"] in [j["id"] for j in listed["data"]]
    out = client.get(f"/v1/files/{job['output_file_id']}/content")
    assert out.status_code == 200
    records = [json.loads(l) for l in out.text.splitlines()]
    assert {rec["custom_id"] for rec in records} == {f"req-{i}"
                                                     for i in range(5)}
    for rec in records:
        assert rec["response"]["status_code"] == 200
        body = rec["response"]["body"]
        assert body["choices"][0]["message"]["content"] is not None
    meta = client.get(f"/v1/files/{job['output_file_id']}").json()
    assert meta["purpose"] == "batch_output"
    # batch series render at /metrics; the lane is not paused
    text = client.get("/metrics").text
    assert 'localai_batch_jobs{state="completed"} 1' in text
    assert 'localai_batch_lane_paused 0' in text
    # cancel on a terminal job is a no-op, unknown id is 404
    r = client.post(f"/v1/batches/{job['id']}/cancel")
    assert r.status_code == 200 and r.json()["status"] == "completed"
    assert client.post("/v1/batches/batch_999/cancel").status_code == 404


def test_batch_create_validation(client):
    r = client.post("/v1/batches", json={"endpoint": "/v1/images",
                                         "input_file_id": "file-1"})
    assert r.status_code == 400
    r = client.post("/v1/batches", json={
        "endpoint": "/v1/chat/completions", "input_file_id": "file-999"})
    assert r.status_code == 404
    # a file uploaded for assistants cannot seed a batch job
    payload = b'{"custom_id": "a"}\n'
    f = client.post("/v1/files",
                    files={"file": ("not_batch.jsonl", payload)},
                    data={"purpose": "assistants"}).json()
    r = client.post("/v1/batches", json={
        "endpoint": "/v1/chat/completions", "input_file_id": f["id"]})
    assert r.status_code == 400
    assert "purpose" in r.json()["error"]["message"]
    assert client.get("/v1/batches/batch_999").status_code == 404
    # list limit must be a positive integer (limit=-1 would silently
    # drop the newest job)
    assert client.get("/v1/batches",
                      params={"limit": "-1"}).status_code == 400
    assert client.get("/v1/batches",
                      params={"limit": "x"}).status_code == 400


def test_batches_ui_page_served(client):
    r = client.get("/batches", headers={"Accept": "text/html"})
    assert r.status_code == 200
    assert "Batch jobs" in r.text


def test_fleet_register_endpoint_guards(server, client):
    """POST /federated/register on the serving instance (fleet-tier
    registry join): unroutable-by-construction addresses are 400, the
    peer_token guard answers 401, and with no fleet-served model loaded
    a well-formed join is a clean 409 — never a silent no-op."""
    # constructionally unroutable: rejected before any model is consulted
    for bad in ("127.0.0.1:0", ":8080", "0.0.0.0:1234", "host:nope"):
        r = client.post("/federated/register", json={"address": bad})
        assert r.status_code == 400, (bad, r.status_code)
    assert client.post("/federated/register",
                       json={}).status_code == 400
    r = client.post("/federated/register",
                    json={"address": "127.0.0.1:19999",
                          "role": "supervisor"})
    assert r.status_code == 400  # unknown role
    # no fleet-served model in this (single-engine) server
    r = client.post("/federated/register",
                    json={"address": "127.0.0.1:19999"})
    assert r.status_code == 409
    # the shared peer_token guards the join exactly like the router's
    # registry guards registration
    server.state.config.peer_token = "sekrit"
    try:
        r = client.post("/federated/register",
                        json={"address": "127.0.0.1:19999"})
        assert r.status_code == 401
        r = client.post("/federated/register",
                        json={"address": "127.0.0.1:19999"},
                        headers={"Authorization": "Bearer sekrit"})
        assert r.status_code == 409  # authorized, still no fleet model
    finally:
        server.state.config.peer_token = ""


def test_fleet_swap_endpoint_guards(server, client):
    """POST /v1/fleet/{model}/swap guard matrix: peer_token answers 401,
    malformed bodies are 400, an unknown model is 404, and a loaded but
    single-engine (non-fleet) model is a clean 409 — the deploy
    primitive never silently no-ops."""
    # malformed bodies are rejected before any model is consulted
    r = client.post("/v1/fleet/tiny/swap", content=b"{not json",
                    headers={"Content-Type": "application/json"})
    assert r.status_code == 400
    assert client.post("/v1/fleet/tiny/swap",
                       json=["checkpoint"]).status_code == 400
    assert client.post("/v1/fleet/tiny/swap",
                       json={"checkpoint": 7}).status_code == 400
    # unknown model
    assert client.post("/v1/fleet/nope/swap",
                       json={}).status_code == 404
    # loaded single-engine model has no fleet to swap
    server.state.manager.get("tiny")
    r = client.post("/v1/fleet/tiny/swap", json={})
    assert r.status_code == 409
    assert "not fleet-served" in r.json()["error"]
    # the shared peer_token guards the swap like every capacity mutation
    server.state.config.peer_token = "sekrit"
    try:
        assert client.post("/v1/fleet/tiny/swap",
                           json={}).status_code == 401
        r = client.post("/v1/fleet/tiny/swap", json={},
                        headers={"Authorization": "Bearer sekrit"})
        assert r.status_code == 409  # authorized, still not fleet-served
    finally:
        server.state.config.peer_token = ""


def test_embeddings_and_rerank_shed_under_overload(client):
    """Satellite: the SLO admission hook covers embeddings and rerank too,
    with the same preserved Retry-After header."""
    from localai_tpu.obs import slo as obs_slo

    SLO = obs_slo.SLO
    saved = dict(targets=dict(SLO.targets),
                 burn_threshold=SLO.burn_threshold,
                 recover_burn=SLO.recover_burn, min_events=SLO.min_events)
    SLO.reset()
    SLO.configure(targets={"ttft_ms": 1e-6}, burn_threshold=1.0,
                  recover_burn=1.0, min_events=2)
    try:
        for i in range(2):  # violate the impossible target → both windows
            assert client.post("/v1/chat/completions", json={
                "model": "tiny",
                "messages": [{"role": "user", "content": f"burn {i}"}],
                "max_tokens": 2,
            }).status_code == 200
        r = client.post("/v1/embeddings", json={
            "model": "tiny", "input": "refuse me"})
        assert r.status_code == 429
        assert r.headers.get("Retry-After") == str(SLO.retry_after_s)
        r = client.post("/v1/rerank", json={
            "model": "tiny", "query": "q", "documents": ["a", "b"]})
        assert r.status_code == 429
        assert r.headers.get("Retry-After") == str(SLO.retry_after_s)
        # recovery readmits both endpoints
        SLO.configure(targets={})
        assert client.post("/v1/embeddings", json={
            "model": "tiny", "input": "ok now"}).status_code == 200
    finally:
        SLO.configure(**saved)
        SLO.reset()


# -- usage accounting plane (/v1/usage, /debug/history, /usage UI) -----------
# The LEDGER/HISTORY singletons are process-global and fed by every test
# in this run, so these assert presence and shape, never exact counts.


def test_v1_usage_reports_anonymous_pane(client):
    """Auth-off traffic lands in the ``anonymous`` tenant bucket with the
    full cost pane (delivered tokens, dispatch ms, queue wait, KV-block-
    seconds) plus the goodput/waste decomposition."""
    r = client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "bill me"}],
        "max_tokens": 4,
    })
    assert r.status_code == 200
    d = client.get("/v1/usage").json()
    assert d["object"] == "usage"
    for key in ("data", "waste", "goodput", "tenant_lru"):
        assert key in d, key
    panes = [p for p in d["data"]
             if p["tenant"] == "anonymous" and p["model"] == "tiny"]
    assert panes, d["data"]
    pane = panes[0]
    assert pane["lane"] == "interactive"
    assert pane["requests"] >= 1
    assert pane["delivered_tokens"] >= 1
    for key in ("prompt_tokens", "dispatch_ms", "queue_wait_ms",
                "kv_block_seconds", "waste_tokens", "waste_requests"):
        assert key in pane, key
    g = d["goodput"]
    assert 0.0 <= g["goodput_ratio"] <= 1.0
    assert g["delivered_tokens"] >= pane["delivered_tokens"]
    lru = d["tenant_lru"]
    assert lru["max_tenants"] >= lru["tenants"] >= 1


def test_v1_usage_windowed_and_bad_params(client):
    d = client.get("/v1/usage", params={"window": 3600}).json()
    assert d["object"] == "usage"
    # the windowed answer says how far back its event ring reaches
    assert "coverage_start" in d and "events" in d
    assert d["start_time"] is not None
    for bad in ({"since": "soon"}, {"window": "wat"}):
        assert client.get("/v1/usage", params=bad).status_code == 400


def test_authenticated_tenant_is_hashed_never_raw(client, server):
    """With API keys on, the auth middleware stamps derive_tenant(key) —
    the raw key must never appear in /v1/usage or the exposition."""
    from localai_tpu.obs.ledger import derive_tenant

    key = "sk-usage-raw-key-material"
    server.state.config.api_keys = [key]
    hdr = {"Authorization": f"Bearer {key}"}
    try:
        r = client.post("/v1/chat/completions", json={
            "model": "tiny",
            "messages": [{"role": "user", "content": "tenant bill"}],
            "max_tokens": 4,
        }, headers=hdr)
        assert r.status_code == 200
        # the key gates /v1/usage too
        assert client.get("/v1/usage").status_code == 401
        d = client.get("/v1/usage", headers=hdr).json()
        metrics = client.get("/metrics", headers=hdr).text
    finally:
        server.state.config.api_keys = []
    bucket = derive_tenant(key)
    assert bucket.startswith("t-") and key not in bucket
    panes = [p for p in d["data"] if p["tenant"] == bucket]
    assert panes and panes[0]["requests"] >= 1
    assert key not in json.dumps(d)
    assert key not in metrics
    assert (f'localai_tenant_tokens_total{{lane="interactive",'
            f'model="tiny",tenant="{bucket}"}}') in metrics


def test_metrics_exports_tenant_and_goodput_series(client):
    client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "export me"}],
        "max_tokens": 4,
    })
    body = client.get("/metrics").text
    assert ('localai_tenant_requests_total{lane="interactive",'
            'model="tiny",tenant="anonymous"}') in body
    assert 'localai_goodput_tokens_total{model="tiny"}' in body
    assert 'localai_goodput_ratio{model="tiny"}' in body
    assert "# TYPE localai_waste_tokens_total counter" in body
    assert "# TYPE localai_tenant_lru_evictions_total counter" in body


def test_debug_history_index_and_series(client):
    """Every /metrics scrape doubles as a history sampling tick — after
    one, the ring geometry and the curated engine/ledger series must be
    queryable at every resolution."""
    client.post("/v1/chat/completions", json={
        "model": "tiny",
        "messages": [{"role": "user", "content": "history"}],
        "max_tokens": 4,
    })
    client.get("/metrics")                       # the sampling tick
    idx = client.get("/debug/history").json()
    assert idx["resolutions_s"] == [1, 10, 300]
    assert idx["capacity"] == {"1": 600, "10": 720, "300": 576}
    assert "tokens_generated.tiny" in idx["series"]
    assert "tenant_tokens.anonymous" in idx["series"]
    q = client.get("/debug/history/tokens_generated.tiny",
                   params={"res": 1}).json()
    assert q["kind"] == "counter"
    assert q["resolution_s"] == 1 and q["capacity"] == 600
    assert q["points"] and q["points"][-1]["value"] >= 1
    # res snaps to the nearest ring rather than erroring
    snapped = client.get("/debug/history/tokens_generated.tiny",
                         params={"res": 7}).json()
    assert snapped["resolution_s"] == 10
    assert client.get("/debug/history/no-such-series").status_code == 404
    assert client.get("/debug/history/tokens_generated.tiny",
                      params={"res": "x"}).status_code == 400
    assert client.get("/debug/history/tokens_generated.tiny",
                      params={"since": "x"}).status_code == 400


def test_usage_ui_page_served(client):
    r = client.get("/usage", headers={"Accept": "text/html"})
    assert r.status_code == 200
    assert "Usage" in r.text
    assert "Waste decomposition" in r.text
