"""Worker process lifecycle: spawn, health-gate, respawn, watchdog.

Parity with the reference's model-lifecycle layer:
  * spawn + stdout/stderr tailing — pkg/model/process.go:73+
  * free-port allocation + N health attempts before failing —
    pkg/model/initializers.go:271-407 (grpcModel)
  * health-check-and-respawn of stale handles — pkg/model/loader.go:170-206
  * busy/idle watchdog killing hung or RAM-hogging workers —
    pkg/model/watchdog.go:19-156
  * external backends registered by address — external_backends.json,
    core/startup/config_file_watcher.go

The TPU twist: a worker is a Python process owning a JAX engine; on
multi-chip hosts each worker claims devices via env (JAX visible-device
pinning) rather than CUDA_VISIBLE_DEVICES.
"""

from __future__ import annotations

import logging
import os
import subprocess
import sys
import threading
import time
from typing import Optional

from localai_tpu.worker.client import WorkerClient

log = logging.getLogger(__name__)


def check_worker_device(message: str, env: Optional[dict],
                        name: str) -> dict:
    """Read the device a worker's LoadModel reported and refuse the worker
    when it is not on the platform its spawn env asked for
    (``JAX_PLATFORMS``; device pinning always sets it). A worker that was
    meant for a TPU and quietly came up on the CPU would serve — slowly and
    without a word. Returns the reported device ({} from a third-party
    worker that reports none)."""
    import json

    try:
        device = json.loads(message).get("device") or {}
    except (ValueError, AttributeError):
        device = {}
    want = ((env or {}).get("JAX_PLATFORMS") or "").split(",")[0]
    got = device.get("platform", "")
    if want and got and got != want:
        raise RuntimeError(
            f"worker {name} was spawned for platform {want!r} but loaded "
            f"its model on {got!r} ({device.get('device_kind', '')} x"
            f"{device.get('device_count', '?')}); refusing the replica")
    if device:
        log.info("worker %s serves from %s (%s x%s)", name, got,
                 device.get("device_kind", ""),
                 device.get("device_count", "?"))
    return device


class WorkerProcess:
    """One spawned worker and its client handle."""

    def __init__(self, name: str, *, env: Optional[dict] = None,
                 health_attempts: int = 60, health_interval: float = 1.0,
                 parallel: bool = True, watchdog: "Watchdog | None" = None):
        self.name = name
        self.proc: Optional[subprocess.Popen] = None
        self.client: Optional[WorkerClient] = None
        self.port = 0
        self._env = env or {}
        self._health_attempts = health_attempts
        self._health_interval = health_interval
        self._parallel = parallel
        self._watchdog = watchdog
        self._log_thread: Optional[threading.Thread] = None

    def start(self) -> WorkerClient:
        env = dict(os.environ)
        env.update(self._env)
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "localai_tpu.worker.server",
             "--addr", "127.0.0.1:0"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            env=env, text=True, bufsize=1,
        )
        # the tail thread scans for WORKER_READY and forwards everything
        # else into our log (parity: process.go stdout/stderr tailing);
        # waiting on an Event keeps startup bounded even if the child
        # hangs silently before binding.
        self._ready_evt = threading.Event()
        self._ready_port = 0
        self._log_thread = threading.Thread(
            target=self._tail_log, daemon=True,
            name=f"worker-log-{self.name}",
        )
        self._log_thread.start()
        timeout = self._health_attempts * self._health_interval
        if not self._ready_evt.wait(timeout) or not self._ready_port:
            rc = self.proc.poll()
            self.stop()
            raise RuntimeError(
                f"worker {self.name} never reported a port"
                + (f" (exited rc={rc})" if rc is not None else "")
            )
        self.port = self._ready_port

        client = WorkerClient(f"127.0.0.1:{self.port}", parallel=self._parallel,
                              watchdog=self._watchdog)
        # health gate with retries (initializers.go:360-383)
        for _ in range(self._health_attempts):
            if client.health(timeout=2.0):
                self.client = client
                return client
            if self.proc.poll() is not None:
                break
            time.sleep(self._health_interval)
        self.stop()
        raise RuntimeError(f"worker {self.name} failed health check")

    def _tail_log(self) -> None:
        assert self.proc is not None and self.proc.stdout is not None
        for line in self.proc.stdout:
            if line.startswith("WORKER_READY port="):
                self._ready_port = int(line.strip().split("=", 1)[1])
                self._ready_evt.set()
                continue
            log.info("[%s] %s", self.name, line.rstrip())
        self._ready_evt.set()  # EOF: unblock a waiting start()

    @property
    def alive(self) -> bool:
        return self.proc is not None and self.proc.poll() is None

    def healthy(self) -> bool:
        return self.alive and self.client is not None and self.client.health()

    def stop(self, grace: float = 5.0) -> None:
        if self.client is not None:
            self.client.close()
            self.client = None
        if self.proc is not None and self.proc.poll() is None:
            self.proc.terminate()
            try:
                self.proc.wait(grace)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait(5.0)
        self.proc = None


class Watchdog:
    """Busy/idle watchdog over worker addresses (watchdog.go:19-156).

    ``mark``/``unmark`` are called by WorkerClient around every RPC; the
    loop kills workers busy longer than busy_timeout (hung engine) or idle
    longer than idle_timeout (HBM/RAM hog)."""

    def __init__(self, *, busy_timeout: float = 300.0,
                 idle_timeout: float = 900.0, interval: float = 5.0):
        self.busy_timeout = busy_timeout
        self.idle_timeout = idle_timeout
        self.interval = interval
        self._busy_since: dict[str, float] = {}
        self._busy_count: dict[str, int] = {}
        self._idle_since: dict[str, float] = {}
        self._kill: dict[str, callable] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def register(self, address: str, kill_fn) -> None:
        with self._lock:
            self._kill[address] = kill_fn
            self._idle_since[address] = time.monotonic()

    def unregister(self, address: str) -> None:
        with self._lock:
            self._kill.pop(address, None)
            self._busy_since.pop(address, None)
            self._busy_count.pop(address, None)
            self._idle_since.pop(address, None)

    def mark(self, address: str) -> None:
        """Refcounted: a worker serving N overlapping RPCs stays busy until
        the last one finishes (the gRPC server handles 32 concurrently)."""
        with self._lock:
            n = self._busy_count.get(address, 0)
            self._busy_count[address] = n + 1
            if n == 0:
                self._busy_since[address] = time.monotonic()
            self._idle_since.pop(address, None)

    def unmark(self, address: str) -> None:
        with self._lock:
            n = self._busy_count.get(address, 0) - 1
            if n > 0:
                self._busy_count[address] = n
                return
            self._busy_count.pop(address, None)
            self._busy_since.pop(address, None)
            self._idle_since[address] = time.monotonic()

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="worker-watchdog")
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(self.interval * 2)

    def _run(self) -> None:
        while not self._stop.wait(self.interval):
            now = time.monotonic()
            doomed: list[str] = []
            with self._lock:
                if self.busy_timeout:
                    doomed += [a for a, t in self._busy_since.items()
                               if now - t > self.busy_timeout]
                if self.idle_timeout:
                    doomed += [a for a, t in self._idle_since.items()
                               if now - t > self.idle_timeout]
                kills = [(a, self._kill.get(a)) for a in doomed]
            for addr, kill in kills:
                if kill is None:
                    continue
                log.warning("watchdog killing worker at %s", addr)
                try:
                    kill()
                finally:
                    self.unregister(addr)


class WorkerPool:
    """name → worker, with health-check-and-respawn on access
    (loader.go:170-206) and external-backend registration."""

    def __init__(self, *, watchdog: Optional[Watchdog] = None):
        self._workers: dict[str, WorkerProcess] = {}
        self._external: dict[str, WorkerClient] = {}
        self._lock = threading.Lock()          # guards the maps only
        self._name_locks: dict[str, threading.Lock] = {}
        self._watchdog = watchdog

    def _name_lock(self, name: str) -> threading.Lock:
        with self._lock:
            lk = self._name_locks.get(name)
            if lk is None:
                lk = self._name_locks[name] = threading.Lock()
            return lk

    def register_external(self, name: str, address: str) -> WorkerClient:
        """An externally managed worker speaking the same proto (parity:
        external gRPC backends, initializers.go externalBackends).
        Idempotent: re-registering the same name+address reuses the
        existing channel."""
        with self._lock:
            ext = self._external.get(name)
            if ext is not None and ext.address == address:
                return ext
        client = WorkerClient(address, watchdog=self._watchdog)
        with self._lock:
            self._external[name] = client
        # the displaced client (address change) is deliberately NOT closed:
        # another thread may be mid-stream on it; the channel is reclaimed
        # when its last in-flight RPC finishes and the object is collected
        return client

    def get(self, name: str, *, env: Optional[dict] = None) -> WorkerClient:
        # per-name lock: a cold spawn of one model (subprocess + engine
        # load, tens of seconds) must not serialize lookups of others
        with self._name_lock(name):
            with self._lock:
                ext = self._external.get(name)
                if ext is not None:
                    return ext
                wp = self._workers.get(name)
            if wp is not None:
                if wp.healthy():
                    return wp.client  # type: ignore[return-value]
                log.warning("worker %s unhealthy; respawning", name)
                with self._lock:
                    self._drop_locked(name)
            wp = WorkerProcess(name, env=env, watchdog=self._watchdog)
            client = wp.start()
            if self._watchdog is not None:
                self._watchdog.register(client.address, wp.stop)
            with self._lock:
                self._workers[name] = wp
            return client

    def _drop_locked(self, name: str) -> None:  # jaxlint: guarded-by(_lock)
        wp = self._workers.pop(name, None)
        if wp is not None:
            if self._watchdog is not None and wp.client is not None:
                self._watchdog.unregister(wp.client.address)
            wp.stop()

    def shutdown(self, name: str) -> bool:
        with self._lock:
            if name in self._workers:
                self._drop_locked(name)
                return True
            ext = self._external.pop(name, None)
        if ext is not None:
            ext.close()  # eviction only fires when idle — safe to close
            return True
        return False

    def shutdown_all(self) -> None:
        with self._lock:
            for name in list(self._workers):
                self._drop_locked(name)
            for client in self._external.values():
                client.close()
            self._external.clear()

    def names(self) -> list[str]:
        with self._lock:
            return sorted(set(self._workers) | set(self._external))
