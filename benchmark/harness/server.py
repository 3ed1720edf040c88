"""The child server from the parent's side: start it, ask it, stop it.

The parent never imports JAX (a chip belongs to one process at a time). The
launcher, the ``/readyz`` wait and the ``/metrics`` parser are chip_smoke.py's,
copied (the original stays the bring-up gate; PERF.md lists it for a later PR).
"""

from __future__ import annotations

import importlib.util
import json
import os
import signal
import socket
import subprocess
import sys
import time
import urllib.error
import urllib.request
from pathlib import Path

from harness.spec import Cell, bench_dir


class HarnessFailure(RuntimeError):
    pass


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(method: str, url: str, body=None, timeout: float = 600.0) -> str:
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, method=method,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.read().decode()


def metric_samples(text: str) -> list[tuple[str, dict, float]]:
    """Prometheus exposition -> [(name, labels, value)]."""
    out = []
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        head, _, value = line.rpartition(" ")
        name, _, rest = head.partition("{")
        labels = {}
        for part in rest.rstrip("}").split(","):
            if "=" in part:
                k, _, v = part.partition("=")
                labels[k] = v.strip('"')
        out.append((name, labels, float(value)))
    return out


def compiles(samples) -> int:
    """First dispatches of new program shapes so far: the runner's watched
    programs in ``localai_xla_compile_total`` (labels that start with "/" are
    jax.monitoring's own events, not programs)."""
    return int(sum(v for n, lab, v in samples
                   if n == "localai_xla_compile_total"
                   and not lab.get("program", "").startswith("/")))


def one_chip_env(root: Path) -> dict:
    """Pin the child to chip 0, so four visible chips do not turn a one-chip
    cell into a meshed one: the fleet's own recipe (fleet/pinning.py), loaded
    by path, since importing the package would import JAX into the parent."""
    spec = importlib.util.spec_from_file_location(
        "_pinning", root / "localai_tpu" / "fleet" / "pinning.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.tpu_process_env([0])


def child_env(cell: Cell, root: Path, platform: str) -> dict:
    """The same with --trace 0 and --trace 1. No process-wide JAX flag that
    changes a compiled serving program: the cell runs what users run."""
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": platform,
        "PYTHONPATH": str(root),
        "PYTHONUNBUFFERED": "1",
        # long enough rings for a whole window
        "LOCALAI_FLIGHT_CAPACITY": "16384",
        "LOCALAI_TRACE_CAPACITY": "4096",
        # every program is kept, the leaf generators of the synthetic load
        # (under a second each) included: they are most of a warm set-up
        "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
    })
    # a fixed path inside the checkout (the path is part of the cache's key).
    # Where the machine sets the variable, that directory is kept and no other
    # is set here: the chip tool hands a cache from one call to the next that
    # way, and the builder's instructions ask for it
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   str(bench_dir(root) / ".cache" / "jax"))
    if platform == "tpu" and cell.chips == 1:
        env.update(one_chip_env(root))
    return env


class Server:
    """``benchmark/serve.py`` as a child process, in its own run directory
    (the program writes its asset dirs and the profiler's trace there)."""

    def __init__(self, cell: Cell, root: Path, run_dir: Path, *,
                 platform: str = "tpu"):
        self.cell, self.name = cell, cell.config_name
        self.run_dir = run_dir
        run_dir.mkdir(parents=True, exist_ok=True)
        self.port, self.control_port = free_port(), free_port()
        self.base = f"http://127.0.0.1:{self.port}"
        self.log_path = run_dir / "server.log"
        self._log = open(self.log_path, "w")
        self.t_start = time.monotonic()
        self.proc = subprocess.Popen(
            [sys.executable, str(bench_dir(root) / "serve.py"),
             "--config", str(cell.config_file),
             "--name", self.name, "--port", str(self.port),
             "--control-port", str(self.control_port),
             "--models-path", str(run_dir / "models")],
            env=child_env(cell, root, platform), stdout=self._log,
            stderr=subprocess.STDOUT, cwd=str(run_dir))

    def get(self, path: str, **kw):
        return json.loads(http("GET", self.base + path, **kw))

    def metrics(self) -> list[tuple[str, dict, float]]:
        return metric_samples(http("GET", self.base + "/metrics"))

    def reference(self, probes: list[dict], timeout: float = 300.0) -> dict:
        try:
            return json.loads(http(
                "POST", f"http://127.0.0.1:{self.control_port}/reference",
                {"probes": probes}, timeout=timeout))
        except urllib.error.HTTPError as e:
            raise HarnessFailure(
                f"reference check failed in the child: {e.read().decode()}; "
                f"see {self.log_path}") from None

    def wait_loaded(self, timeout: float) -> float:
        """Until /readyz lists the model; seconds since the child started."""
        while time.monotonic() - self.t_start < timeout:
            if self.proc.poll() is not None:
                raise HarnessFailure(
                    f"server exited {self.proc.returncode} before it was "
                    f"ready; see {self.log_path}\n{self.log_tail()}")
            try:
                if self.name in self.get("/readyz", timeout=5)[
                        "models_loaded"]:
                    return time.monotonic() - self.t_start
            except (urllib.error.URLError, ConnectionError, OSError):
                pass
            time.sleep(0.25)
        raise HarnessFailure(f"model not loaded after {timeout}s; see "
                             f"{self.log_path}\n{self.log_tail()}")

    def log_tail(self, n: int = 30) -> str:
        self._log.flush()
        return "\n".join(self.log_path.read_text(
            errors="replace").splitlines()[-n:])

    def stop(self) -> None:
        """SIGTERM and a clean exit; waits until the child has ended."""
        if self.proc.poll() is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                self.proc.wait(60)
            except subprocess.TimeoutExpired:
                self.kill()
                raise HarnessFailure("server ignored SIGTERM for 60 s")
        self._log.close()
        if self.proc.returncode != 0:
            raise HarnessFailure(
                f"server exited {self.proc.returncode} on SIGTERM; see "
                f"{self.log_path}")

    def kill(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
            self.proc.wait(30)
        if not self._log.closed:
            self._log.close()


def check_devices(system: dict, chips: int, platform: str) -> dict:
    """The devices as the child's JAX reports them must be what the cell asks
    for. Returns {"platform", "kind", "count"}."""
    devices = system["devices"]
    if len(devices) != chips:
        raise HarnessFailure(
            f"the cell asks for {chips} chip(s); the server sees "
            f"{len(devices)}")
    for d in devices:
        if d["platform"] != platform:
            raise HarnessFailure(f"device {d} is not {platform}")
    return {"platform": devices[0]["platform"], "kind": devices[0]["kind"],
            "count": len(devices)}
