"""Per-replica device pinning presets (--fleet-device-pinning).

``--fleet-replicas N`` with worker-backed replicas spawns N engine
processes — but without device pinning every worker initializes the SAME
accelerators and the second LoadModel dies on a held TPU chip. The manual
escape is hand-writing ``worker_env`` per deployment; this module derives
it instead: the host's visible devices are partitioned into N contiguous
equal slices (ICI-contiguous in ``jax.devices()`` order, so each replica's
chips form a ring for its own auto-mesh) and each replica's spawn env pins
its slice.

Env derivation by platform:

  * **tpu** — what libtpu needs to run an independent process on a
    subset of a host's chips (:func:`tpu_process_env`):
    ``TPU_VISIBLE_CHIPS=<ids>`` plus the slice's own single-process
    topology (``TPU_CHIPS_PER_PROCESS_BOUNDS`` = the slice's chip grid,
    ``TPU_PROCESS_BOUNDS=1,1,1``), and ``JAX_PLATFORMS=tpu`` — set
    explicitly, because the worker inherits the server's environment and
    the recommended server runs on the CPU; a worker that cannot get its
    chip must fail, not serve from the CPU.
  * **cpu** — ``JAX_PLATFORMS=cpu`` plus
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<per>`` (virtual
    CPU devices; the CI/test shape).
  * anything else (gpu plugins) — ``JAX_PLATFORMS`` passthrough only; no
    portable visible-device convention to derive, so pinning is a no-op
    and the operator keeps ``worker_env``.

The pure core (:func:`pinning_env`) takes platform/device-count
explicitly so tests pin the partition math without touching a backend.
The topology is declared, never probed:
``LOCALAI_FLEET_PIN_PLATFORM=tpu LOCALAI_FLEET_PIN_DEVICES=8``. A chip
belongs to one process, so the API server process must not initialize the
accelerators its workers are about to be pinned to (see
:func:`derive_pinning_env`).
"""

from __future__ import annotations

import logging
from typing import Optional

log = logging.getLogger(__name__)


def pinning_env(index: int, replicas: int, *, platform: str,
                n_devices: int) -> dict[str, str]:
    """Spawn-env additions for replica ``index`` of ``replicas`` on a host
    with ``n_devices`` ``platform`` accelerators. Pure — no jax import.

    Devices partition into ``replicas`` contiguous slices of
    ``n_devices // replicas`` (device order is ICI-contiguous, so a slice
    is a valid ring for the replica's own auto-mesh); the remainder
    devices stay unused rather than skewing one replica. Returns {} when
    the partition is impossible (fewer devices than replicas) or the
    platform has no pinning convention."""
    if not 0 <= index < replicas:
        raise ValueError(f"replica index {index} outside fleet size "
                         f"{replicas}")
    per = n_devices // replicas
    if per < 1:
        log.warning(
            "device pinning: %d replicas over %d %s device(s) — cannot "
            "partition; replicas spawn unpinned", replicas, n_devices,
            platform)
        return {}
    if n_devices % replicas:
        log.warning(
            "device pinning: %d %s devices do not divide evenly over %d "
            "replicas; %d device(s) stay unused", n_devices, platform,
            replicas, n_devices % replicas)
    ids = range(index * per, (index + 1) * per)
    if platform == "tpu":
        return tpu_process_env(ids)
    if platform == "cpu":
        return {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": f"--xla_force_host_platform_device_count={per}",
        }
    log.warning(
        "device pinning: no visible-device convention for platform %r; "
        "replica %d spawns unpinned (set worker_env explicitly)",
        platform, index)
    return {}


# a slice's chip grid as libtpu wants it (x,y,z); v5e/v6e hosts are 2-D.
# Only the one-chip row has run on hardware (PERF.md, PR 21).
_TPU_CHIP_BOUNDS = {1: "1,1,1", 2: "2,1,1", 4: "2,2,1", 8: "2,4,1"}


def tpu_process_env(chip_ids) -> dict[str, str]:
    """Env for ONE independent JAX process owning ``chip_ids`` of a TPU
    host (pure — no jax import); chip_smoke.py pins its one-chip leg with
    this same recipe. Established on a v5litepod-4 host (libtpu 0.0.34,
    four concurrent one-chip processes, PR 21): ``TPU_VISIBLE_CHIPS`` alone
    is not enough — three of four processes abort on libtpu's
    one-process-per-host lockfile; declaring the process's own topology
    (chips-per-process bounds that are a subset of the host, process
    bounds 1,1,1) is what lifts that lock. Per-process runtime ports
    (``TPU_PROCESS_PORT``/``_ADDRESSES``) are not needed: the processes
    never talk to each other. Each process sees its chip as device 0."""
    ids = [int(i) for i in chip_ids]
    if len(ids) not in _TPU_CHIP_BOUNDS:
        raise ValueError(
            f"no TPU process topology for a {len(ids)}-chip slice "
            f"(have {sorted(_TPU_CHIP_BOUNDS)})")
    return {
        "JAX_PLATFORMS": "tpu",
        "TPU_VISIBLE_CHIPS": ",".join(str(i) for i in ids),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": _TPU_CHIP_BOUNDS[len(ids)],
        "TPU_PROCESS_BOUNDS": "1,1,1",
    }


def derive_pinning_env(index: int, replicas: int) -> dict[str, str]:
    """:func:`pinning_env` for this host's accelerators.

    Topology comes from ``LOCALAI_FLEET_PIN_PLATFORM`` +
    ``LOCALAI_FLEET_PIN_DEVICES`` — the operator-declared truth. There is
    no fallback to this process's own backend: asking ``jax.devices()``
    here would initialize libtpu in the server and take every chip the
    workers are about to be pinned to (a chip belongs to one process), and
    under ``--platform cpu`` it would report the wrong platform."""
    import os

    platform = os.environ.get("LOCALAI_FLEET_PIN_PLATFORM", "")
    nd = os.environ.get("LOCALAI_FLEET_PIN_DEVICES", "")
    if not (platform and nd):
        raise ValueError(
            "--fleet-device-pinning needs the host topology declared: set "
            "LOCALAI_FLEET_PIN_PLATFORM (tpu|cpu) and "
            "LOCALAI_FLEET_PIN_DEVICES (accelerators on this host); the "
            "server does not probe devices its workers will own")
    return pinning_env(index, replicas, platform=platform,
                       n_devices=int(nd))


def pinned_worker_env(base: Optional[dict], index: int,
                      replicas: int) -> dict[str, str]:
    """Merge the derived pinning slice over the operator's worker_env
    (explicit keys win — an operator pinning by hand keeps their layout,
    and the derived keys fill only the gaps)."""
    derived = derive_pinning_env(index, replicas)
    out = dict(derived)
    out.update(base or {})
    return out
