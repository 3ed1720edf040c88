"""XLA compile-time telemetry + the compiled-program cost observatory.

Capture paths, matching what this jax build actually exposes:

  * :func:`watch` wraps a jitted entry point (``engine/runner.py`` wraps
    all of its programs). jax compiles synchronously on the first dispatch
    of each static-argument shape while *execution* is async, so the wall
    time of that first call is trace+lower+compile to within one program
    execution — the same reasoning the scheduler uses to exclude fresh
    shapes from its step-time EMA. Later dispatches of a seen shape pass
    straight through with one set lookup + counter bump of overhead.
  * :func:`install` registers a ``jax.monitoring`` duration listener for
    compilation events. On this jax version only the persistent
    compilation cache emits them, so the listener is a supplement; newer
    versions emit real backend-compile durations and will land in the same
    series. Gated: a jax without ``jax.monitoring`` just skips it.

Both feed ``localai_xla_compile_total`` / ``localai_xla_compile_seconds_total``.

**Cost observatory** (``GET /debug/programs``): every watched program+shape
lands in the process-wide :data:`CATALOG` as its abstract signature
(``ShapeDtypeStruct`` leaves — no buffers pinned, donated args included).
``cost_analysis()``/``memory_analysis()`` are harvested LAZILY on the first
catalog report, by re-lowering from the stored avals: re-compiling at first
dispatch would double every compile on the serving path, so the observatory
pays that price only when an operator actually asks "where does the
bandwidth go". The report is XLA's account of each program (FLOPs, bytes
accessed, memory); it sets no dispatch's wall against it: a host clock is not
a device share, and the benchmark's trace readers measure those.
"""

from __future__ import annotations

import itertools
import threading
import time
import weakref
from typing import Any, Callable, Optional

from localai_tpu.faults import registry as _faults
from localai_tpu.obs.metrics import REGISTRY, Registry

_install_lock = threading.Lock()
_installed = False
# every registry that ever asked for compile events: ONE jax.monitoring
# listener fans out to all of them (jax offers registration but no
# deregistration, so per-registry listeners would leak). Weak refs keep
# short-lived test registries collectable.
_registries: "weakref.WeakSet[Registry]" = weakref.WeakSet()


def _avalize(x: Any) -> Any:
    """Array → ShapeDtypeStruct (identity for non-arrays): the lowering
    signature the catalog stores instead of live buffers — holding real
    args would pin donated HBM and model params past unload."""
    if hasattr(x, "shape") and hasattr(x, "dtype") and hasattr(x, "ndim"):
        import jax

        return jax.ShapeDtypeStruct(x.shape, x.dtype)
    return x


class ProgramEntry:
    """One (program, shape-key): signature + counters + lazy cost."""

    def __init__(self, program: str, key: tuple, fn: Callable,
                 avals: tuple, statics: dict, compile_seconds: float):
        self.program = program
        self.key = key
        try:
            self.fn_ref = weakref.ref(fn)
        except TypeError:  # unweakrefable callables: better pinned than lost
            self.fn_ref = lambda fn=fn: fn
        self.avals = avals
        self.statics = statics
        self.compile_seconds = compile_seconds
        self.dispatches = 0
        self.cost: Optional[dict] = None       # lazily harvested, cached
        self.cost_error: str = ""


def _normalize_cost(analysis: Any) -> dict:
    """cost_analysis() returns a dict or a per-computation list of dicts
    depending on backend/version; fold to one {flops, bytes_accessed}."""
    if analysis is None:
        return {}
    entries = analysis if isinstance(analysis, (list, tuple)) else [analysis]
    flops = 0.0
    byts = 0.0
    for e in entries:
        if not isinstance(e, dict):
            continue
        flops += float(e.get("flops", 0.0) or 0.0)
        byts += float(e.get("bytes accessed", 0.0) or 0.0)
    return {"flops": flops, "bytes_accessed": byts}


class ProgramCatalog:
    """Process-wide compiled-program registry behind /debug/programs.

    Entries are keyed (program, watch-instance, shape-key): two loaded
    models both watch a "decode" program whose top-level args are pytrees
    (identical shape keys), and without the per-``watch()`` instance id
    the second model's entries would overwrite the first's."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._entries: dict[tuple, ProgramEntry] = {}

    def record(self, program: str, key: tuple, fn: Callable,
               args: tuple, kwargs: dict, compile_seconds: float) -> None:
        try:
            import jax

            avals = jax.tree.map(_avalize, args)
        except Exception:  # noqa: BLE001 — the catalog is best-effort
            avals = None
        entry = ProgramEntry(program, key, fn, avals, dict(kwargs),
                             compile_seconds)
        with self._lock:
            entry.dispatches = 1
            self._entries[(program, key)] = entry

    def dispatched(self, program: str, key: tuple) -> None:
        with self._lock:
            e = self._entries.get((program, key))
            if e is not None:
                e.dispatches += 1

    def _harvest(self, entry: ProgramEntry) -> None:
        """Lower+compile from the stored avals and cache the analysis.
        This is the one deliberately expensive call in the subsystem —
        report()-time only, guarded, and cached per entry."""
        fn = entry.fn_ref()
        if fn is None:
            entry.cost_error = "program no longer live (model unloaded)"
            return
        if entry.avals is None:
            entry.cost_error = "signature capture failed"
            return
        try:
            compiled = fn.lower(*entry.avals, **entry.statics).compile()
            cost = _normalize_cost(compiled.cost_analysis())
            try:
                mem = compiled.memory_analysis()
                cost.update(
                    argument_bytes=getattr(mem, "argument_size_in_bytes", None),
                    output_bytes=getattr(mem, "output_size_in_bytes", None),
                    temp_bytes=getattr(mem, "temp_size_in_bytes", None),
                    generated_code_bytes=getattr(
                        mem, "generated_code_size_in_bytes", None),
                )
            except Exception:  # noqa: BLE001 — memory stats are optional
                pass
            entry.cost = cost
        except Exception as e:  # noqa: BLE001 — a meshed program may not
            # re-lower from bare avals (sharding was on the buffers)
            entry.cost_error = f"{type(e).__name__}: {e}"

    def report(self, *, harvest: bool = True) -> list[dict]:
        """Catalog view: each program with XLA's cost and memory analysis.
        ``harvest=False`` skips lazy compilation (cheap listing)."""
        with self._lock:
            entries = list(self._entries.values())
        out = []
        for e in entries:
            if harvest and e.cost is None and not e.cost_error:
                self._harvest(e)
            row: dict = {
                "program": e.program,
                # which watch() wrapper (≈ which runner) this entry is —
                # two loaded models both have a "decode"
                "instance": e.key[0] if e.key else 0,
                "statics": {k: v for k, v in e.statics.items()},
                "first_dispatch_seconds": round(e.compile_seconds, 4),
                "dispatches": e.dispatches,
            }
            if e.cost:
                row.update(e.cost)
            elif e.cost_error:
                row["cost_error"] = e.cost_error
            out.append(row)
        out.sort(key=lambda r: (r["program"], r["instance"],
                                str(r["statics"])))
        return out


CATALOG = ProgramCatalog()


# one id per watch() wrapper: it disambiguates catalog entries when two
# runners (two loaded models) watch same-named programs whose top-level
# args are pytrees and therefore produce identical shape keys
_WATCH_SEQ = itertools.count(1)


def watch(fn: Callable, program: str,
          registry: Optional[Registry] = None) -> Callable:
    """Wrap a jitted callable: the first call per static-kwargs shape is
    timed and recorded as a compilation of ``program`` (and catalogued for
    the cost observatory); later calls bump the dispatch counter."""
    reg = registry or REGISTRY
    seen: set = set()
    lock = threading.Lock()
    wid = next(_WATCH_SEQ)

    def wrapped(*args: Any, **kwargs: Any) -> Any:
        # program identity = watch instance + static kwargs + argument
        # shapes (array args with a new shape retrace even when the
        # statics repeat — e.g. the multimodal prefill keyed by embedding
        # row count)
        key = ((wid,)
               + tuple(getattr(a, "shape", None) for a in args)
               + tuple(sorted(kwargs.items())))
        with lock:
            fresh = key not in seen
            if fresh:
                seen.add(key)
        if not fresh:
            CATALOG.dispatched(program, key)
            return fn(*args, **kwargs)
        if _faults.ACTIVE:
            # chaos: a compile failure is a first-dispatch failure — the
            # site raises here, before the program is traced/compiled
            _faults.apply("engine.compile", key=program)
        t0 = time.monotonic()
        out = fn(*args, **kwargs)
        dt = time.monotonic() - t0
        reg.compile_count.inc(program=program)
        reg.compile_seconds.inc(dt, program=program)
        CATALOG.record(program, key, fn, args, kwargs, dt)
        return out

    wrapped.__name__ = getattr(fn, "__name__", program)
    return wrapped


def install(registry: Optional[Registry] = None) -> bool:
    """Register ``registry`` (default: the process-wide one) to receive
    jax.monitoring compile events; the single listener is installed on
    first call. Returns True when the listener is live."""
    global _installed
    with _install_lock:
        _registries.add(registry or REGISTRY)
        if _installed:
            return True
        try:
            from jax import monitoring
        except ImportError:
            return False

        def _on_duration(event: str, duration: float, **_kw: Any) -> None:
            if "compil" in event:
                for reg in list(_registries):
                    reg.compile_count.inc(program=event)
                    reg.compile_seconds.inc(duration, program=event)

        try:
            monitoring.register_event_duration_secs_listener(_on_duration)
        except Exception:  # noqa: BLE001 — telemetry must never break serving
            return False
        _installed = True
        return True
