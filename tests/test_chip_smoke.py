"""chip_smoke.py's steps, driven at debug:tiny on the CPU — and the proof that
run plainly it accepts nothing but a TPU.

The chip is reached only through the chip tool; what tier-1 can hold is the
script's own logic: the kernel phase's cases and references (in the Pallas
interpreter, asked for explicitly), the server phase's requests and
assertions against a real ``localai_tpu.cli.main run`` child, and the
no-path-from-a-failure-to-exit-0 contract."""

import json
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

# debug:tiny: 4 q heads / 2 kv heads / head_dim 16, max context 512
TINY = dict(model="debug:tiny", context=512, slots=4)
TINY_HEADS = {"num_heads": 4, "num_kv_heads": 2, "head_dim": 16}
# the looped case at tiny's head_dim: 4 = 4 heads, 2 passes x 3 layers
TINY_LOOPED = {"heads": 4, "cache_layers": 6, "picks": [0, 2, 3, 5]}


@pytest.fixture()
def smoke(tmp_path):
    s = chip_smoke.Smoke(tmp_path / "out", expect_platform="cpu")
    yield s
    s.close()


def test_kernel_phase_steps_on_cpu(smoke, capfd):
    report = chip_smoke.kernel_phase(
        smoke, context=256, slots=4, heads=TINY_HEADS, ffn=128,
        prefill_buckets=(128,), interpret=True, looped=TINY_LOOPED)
    assert smoke.device["platform"] == "cpu"
    # parent and child together: every stdout line names the device
    lines = capfd.readouterr().out.strip().splitlines()
    assert len(lines) > 5 and all(
        line.startswith("[platform=") for line in lines)
    names = [c["case"] for c in report["cases"]]
    # every entry point the selectors can answer "pallas" for
    for want in ("paged_decode bfloat16", "paged_decode int8",
                 "paged_decode int4",
                 # PR 38: the kernel as the step's writer, against the scatter
                 "paged_decode bfloat16 bt=64 hd=16 writes: output",
                 "paged_decode bfloat16 bt=64 hd=16 writes: pool",
                 "paged_decode looped q_per_kv=1 layer 5 of 6 writes: pool",
                 # PR 50: part-full batches, the empty slots' rows zeros
                 "paged_decode bfloat16 bt=64 hd=16 5 live of 16 slots",
                 "paged_decode bfloat16 bt=64 hd=16 6 live of 32 slots "
                 "writes: pool",
                 "paged_decode bfloat16 bt=64 hd=16 5 live of 16 slots "
                 "window 96",
                 "paged_decode bfloat16 bt=64 hd=16 5 live of 16 slots "
                 "window 96 writes: pool",
                 "paged_decode looped q_per_kv=1 layer 0 of 6",
                 "paged_decode looped q_per_kv=1 layer 5 of 6",
                 "decode bfloat16", "decode int8",
                 "prefill T=128", "w8_matmul", "w8_matmul transposed",
                 "w4_matmul"):
        assert any(n.startswith(want) for n in names), (want, names)
    assert all(c["ok"] for c in report["cases"])
    assert report["interpret"] is True


def test_mesh_kernel_phase_steps_on_cpu(smoke):
    """``--chips 4``'s extra child, over two virtual CPU devices: the kernel
    under ``shard_map`` writes each shard's own heads; the pool it hands
    back is the scatter's to the bit."""
    report = chip_smoke.mesh_kernel_phase(
        smoke, chips=2, context=256, slots=4, heads=TINY_HEADS,
        interpret=True,
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert [c["case"].split(" writes: ")[1] for c in report["cases"]] == [
        "output", "pool against the scatter's"]
    assert all(c["ok"] for c in report["cases"])
    assert report["cases"][1]["max_abs_err"] == 0.0


def test_server_phase_steps_on_cpu(smoke, capsys):
    phase = chip_smoke.server_phase(
        smoke, chips=1, long_prompt=200, expect_impl="lax",
        # a 64-token prefill chunk so the "longer than the chunk" request
        # is still chunked at tiny's context
        engine={"prefill_chunk": 64}, **TINY)
    assert phase["requests"]["speculative_windows"] >= 1
    assert phase["requests"]["prefix_tokens_reused"] >= 64
    assert {"prefill_chunk", "decode_n", "verify"} <= set(
        phase["compile_count"])
    # every printed line names the device
    lines = capsys.readouterr().out.strip().splitlines()
    assert lines and all(
        line.startswith("[platform=") and " kind=" in line
        and " count=" in line for line in lines)
    assert "platform=cpu kind=cpu count=1" in lines[-1]
    log = (smoke.out_dir / "server_1chip.log").read_text()
    assert "loaded model smoke (debug:tiny)" in log


def test_looped_server_phase_steps_on_cpu(smoke):
    """The second server phase: ``debug:tiny-loop`` (2 sandwich layers run 3
    times a token) through the same requests and assertions: chunked
    prefill, a full batch, shared prefix blocks and the speculation lane
    over 6 cache layers."""
    phase = chip_smoke.server_phase(
        smoke, chips=1, expect_impl="lax", **chip_smoke.LOOPED_SERVER)
    assert phase["requests"]["speculative_windows"] >= 1
    assert phase["requests"]["prefix_tokens_reused"] >= 64
    log = (smoke.out_dir / "server_looped.log").read_text()
    assert "loaded model smoke (debug:tiny-loop)" in log


def test_server_phase_fails_on_the_wrong_kernel_impl(smoke):
    """No path from a failed assertion to a passing phase: the CPU server
    serves gather+XLA, so expecting the compiled kernel must raise (and the
    child must not be left running)."""
    with pytest.raises(chip_smoke.SmokeFailure, match="kernel impl"):
        chip_smoke.server_phase(
            smoke, chips=1, long_prompt=200, expect_impl="pallas",
            engine={"prefill_chunk": 64}, **TINY)
    assert "server_1chip" not in smoke.report["phases"]


def test_server_phase_fails_when_the_model_cannot_load(smoke):
    """A load that fails is fatal: the server exits instead of serving with
    nothing loaded, and the smoke says so."""
    with pytest.raises(chip_smoke.SmokeFailure, match="before it was ready"):
        chip_smoke.server_phase(
            smoke, chips=1, model="debug:no-such-preset", context=512,
            slots=4, load_timeout=120)


def test_fleet_phase_steps_on_cpu(smoke):
    """Two pinned one-device workers behind the router, the server itself
    kept off them (--platform cpu): each worker reports its device, the
    burst reaches both, SIGTERM ends everything cleanly."""
    phase = chip_smoke.fleet_phase(smoke, replicas=2, expect_impl="lax",
                                   **TINY)
    assert [r["device"]["platform"] for r in phase["replicas"]] == [
        "cpu", "cpu"]
    assert all(r["device"]["device_count"] == 1 for r in phase["replicas"])
    assert all(n > 0 for n in phase["served"].values())


def test_plain_run_exits_nonzero_without_a_tpu(tmp_path):
    """`python chip_smoke.py` in this sandbox: no accelerator, so a non-zero
    exit within seconds and no result line — never a CPU-served pass."""
    proc = subprocess.run(
        [sys.executable, str(ROOT / "chip_smoke.py"), "--out",
         str(tmp_path / "out")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    for line in proc.stdout.splitlines():
        assert not line.startswith("{"), line
    assert '"ok"' not in proc.stdout
    assert "kernel phase child exited" in proc.stderr


def test_result_line_shape(smoke, monkeypatch, capsys):
    """The last stdout line of a passing run is the JSON the driver reads."""
    monkeypatch.setattr(chip_smoke, "Smoke", lambda out: smoke)
    monkeypatch.setattr(
        chip_smoke, "kernel_phase",
        lambda s: s.device.update(platform="tpu", kind="TPU v5 lite",
                                  count=1))
    monkeypatch.setattr(chip_smoke, "server_phase", lambda s, **kw: {})
    assert chip_smoke.main([]) == 0
    assert not smoke.cache_dir.exists()      # nothing left outside the tree
    lines = capsys.readouterr().out.strip().splitlines()
    # exactly these keys: the driver refuses a result line with any other
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}}
    assert lines[-2].endswith('"claim": null}')
    report = json.loads((smoke.out_dir / "report.json").read_text())
    assert report["claim"] is None and report["chips"] == 1
