"""Device health: timeout-guarded liveness probe + HBM accounting.

Three independent questions, three tools:

  * **Is the device answering at all?** :func:`probe_device` dispatches a
    tiny jitted add from a SIDE thread and joins it with a timeout — the
    only safe way to ask, because a device that stopped answering makes
    the dispatch block forever and a blocked probe must never take the
    caller (an HTTP handler) down with it. The probe program is compiled
    once per process; repeat probes are a microsecond dispatch.
  * **How full is it?** :func:`device_memory` reads per-device
    ``memory_stats()`` (bytes_in_use / peak / limit — absent on CPU, where
    jax returns None) into gauges. Pure host metadata, no dispatch: safe
    at /metrics scrape time.
  * **Who is holding it?** :func:`hbm_census` walks ``jax.live_arrays()``
    and attributes bytes to KV cache vs weights vs other using identity
    sets supplied by the caller (the /debug/devices handler passes each
    loaded runner's ``kv`` leaves and param leaves). ``nbytes`` is
    metadata; the census never syncs.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Iterable, Optional

from localai_tpu.obs.metrics import REGISTRY, Registry

def device_report() -> dict:
    """The backend this process computes on, as jax reports it:
    ``{"platform", "device_kind", "device_count"}``. A spawned worker
    returns this from LoadModel so its parent can refuse a replica that
    came up on the wrong device (worker.process.check_worker_device)."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": getattr(devices[0], "device_kind", ""),
        "device_count": len(devices),
    }


# -- liveness probe ---------------------------------------------------------

@dataclasses.dataclass
class ProbeResult:
    ok: bool
    seconds: float
    error: str = ""
    device: str = ""

    def to_dict(self) -> dict:
        return {"ok": self.ok, "seconds": round(self.seconds, 4),
                "error": self.error, "device": self.device}


_probe_lock = threading.Lock()
_probe_fn = None  # compiled once; a probe must not re-pay trace+compile
# single-flight latch for the default probe: against a wedged device every
# probe thread blocks FOREVER, and a dashboard auto-refreshing
# /debug/devices would otherwise leak one such thread per request. While
# one default probe is in flight, later callers join IT instead of
# spawning another — at most one thread is ever parked on a dead dispatch.
_probe_inflight: dict = {"thread": None, "box": None}


def _default_probe() -> None:
    """Tiny device round-trip: dispatch + materialize one [8] add."""
    global _probe_fn
    import jax
    import jax.numpy as jnp

    with _probe_lock:
        if _probe_fn is None:
            _probe_fn = jax.jit(lambda a: a + 1)
    out = _probe_fn(jnp.arange(8, dtype=jnp.int32))
    jax.block_until_ready(out)


def probe_device(timeout: float = 5.0, *,
                 registry: Optional[Registry] = None,
                 fn: Optional[Any] = None) -> ProbeResult:
    """Run a liveness round-trip in a side thread; join with ``timeout``.

    A device that never answers leaves the probe thread blocked (daemon —
    it dies with the process) and returns ok=False error="timeout" in ``timeout``
    seconds instead of hanging the caller. ``fn`` is a test hook
    (inject a blocking callable to exercise the timeout path)."""
    reg = registry or REGISTRY
    probe = fn or _default_probe

    def make_thread(box: dict) -> threading.Thread:
        def run() -> None:
            t0 = time.monotonic()
            try:
                probe()
                box["seconds"] = time.monotonic() - t0
            except Exception as e:  # noqa: BLE001 — a sick device is a
                # result, not a crash
                box["error"] = f"{type(e).__name__}: {e}"
                box["seconds"] = time.monotonic() - t0

        return threading.Thread(target=run, name="device-probe",
                                daemon=True)

    started = False
    if fn is None:
        with _probe_lock:
            t = _probe_inflight["thread"]
            if t is not None and t.is_alive():
                box = _probe_inflight["box"]  # join the in-flight probe
            else:
                box = {}
                t = make_thread(box)
                _probe_inflight.update(thread=t, box=box)
                started = True
    else:  # test-injected probes stay independent of the latch
        box = {}
        t = make_thread(box)
        started = True
    t0 = time.monotonic()
    if started:
        t.start()
    t.join(timeout)
    kind = ""
    try:
        import jax

        kind = getattr(jax.devices()[0], "device_kind", "") or "cpu"
    except Exception:  # noqa: BLE001
        pass
    if t.is_alive():
        res = ProbeResult(False, time.monotonic() - t0,
                          f"timeout after {timeout}s", kind)
    elif "error" in box:
        res = ProbeResult(False, box.get("seconds", 0.0), box["error"], kind)
    else:
        res = ProbeResult(True, box.get("seconds", 0.0), "", kind)
    reg.device_ok.set(1 if res.ok else 0)
    reg.device_probe_seconds.set(round(res.seconds, 4))
    return res


# -- memory stats + live-array census ---------------------------------------

def device_memory(registry: Optional[Registry] = None) -> list[dict]:
    """Per-device ``memory_stats()`` snapshot (gauges refreshed as a side
    effect). CPU devices report ``memory: null`` — jax has no allocator
    stats there."""
    reg = registry or REGISTRY
    out: list[dict] = []
    try:
        import jax

        devices = jax.devices()
    except Exception as e:  # noqa: BLE001
        return [{"error": f"{type(e).__name__}: {e}"}]
    for d in devices:
        stats = None
        try:
            stats = d.memory_stats()
        except Exception:  # noqa: BLE001 — optional per backend
            stats = None
        entry: dict = {
            "id": d.id,
            "platform": d.platform,
            "kind": getattr(d, "device_kind", ""),
            "memory": None,
        }
        if stats:
            mem = {
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "bytes_limit": stats.get("bytes_limit"),
            }
            entry["memory"] = mem
            dev = str(d.id)
            if mem["bytes_in_use"] is not None:
                reg.hbm_bytes_in_use.set(mem["bytes_in_use"], device=dev)
            if mem["peak_bytes_in_use"] is not None:
                reg.hbm_peak_bytes.set(mem["peak_bytes_in_use"], device=dev)
            if mem["bytes_limit"] is not None:
                reg.hbm_bytes_limit.set(mem["bytes_limit"], device=dev)
        out.append(entry)
    return out


def _id_set(arrays: Iterable[Any]) -> set[int]:
    return {id(a) for a in arrays}


def hbm_census(known: Optional[dict[str, Iterable[Any]]] = None,
               registry: Optional[Registry] = None) -> dict:
    """Attribute live jax array bytes to categories.

    ``known`` maps category → iterable of arrays ("kv_cache": the runners'
    cache leaves, "weights": their param leaves); everything else counts as
    "other". Identity is by ``id()`` of the snapshot the caller holds — a
    donation race merely shifts a buffer into "other" for one reading."""
    reg = registry or REGISTRY
    cats = {name: _id_set(arrs) for name, arrs in (known or {}).items()}
    totals = {name: 0 for name in cats}
    totals["other"] = 0
    count = 0
    try:
        import jax

        live = jax.live_arrays()
    except Exception as e:  # noqa: BLE001
        return {"error": f"{type(e).__name__}: {e}"}
    for arr in live:
        nbytes = getattr(arr, "nbytes", 0) or 0
        count += 1
        for name, ids in cats.items():
            if id(arr) in ids:
                totals[name] += nbytes
                break
        else:
            totals["other"] += nbytes
    out = {"arrays": count, "total_bytes": sum(totals.values()),
           "by_category": totals}
    for name, nbytes in totals.items():
        reg.hbm_live_bytes.set(nbytes, category=name)
    return out


def known_arrays(runners: Iterable[Any]) -> dict[str, list]:
    """Build the census ``known`` mapping from ModelRunner-shaped objects
    (anything with ``.kv`` and ``.params``); non-conforming entries are
    skipped."""
    kv: list = []
    weights: list = []
    state: list = []
    for r in runners:
        rec = getattr(getattr(r, "state", None), "rec", None)
        if rec is not None:     # per-slot recurrent state beside the pool
            state.extend(rec.values())
        cache = getattr(r, "kv", None)
        if cache is not None:
            try:
                import jax

                kv.extend(jax.tree.leaves(cache.stacked()))
            except Exception:  # noqa: BLE001
                pass
        params = getattr(r, "params", None)
        if params is not None:
            try:
                import jax

                weights.extend(jax.tree.leaves(params))
            except Exception:  # noqa: BLE001
                pass
    return {"kv_cache": kv, "weights": weights,
            **({"recurrent_state": state} if state else {})}


def update_device_gauges(runners: Iterable[Any] = (),
                         registry: Optional[Registry] = None) -> None:
    """Scrape-time refresh (no device dispatch): memory_stats + census.
    The probe is deliberately NOT here — /metrics must never push work onto
    a possibly-wedged device; probes run from /debug/devices or an
    operator."""
    device_memory(registry)
    hbm_census(known_arrays(runners), registry)
