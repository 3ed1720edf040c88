"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle time,
time per operation name, the programs' device intervals, collectives, and the
longest idle gaps. Read with nothing but JAX (``jax.profiler.ProfileData``),
after the child has exited. Checked on a small recorded trace and on hand-made
ones (benchmark/tests/test_trace_reduce.py).

What a TPU trace holds (looked at by hand, PR 23): one plane per chip named
``/device:TPU:<n>``, with a line ``XLA Ops`` (every HLO operation the
TensorCore ran: fusions, custom calls = Pallas kernels, copies, collectives;
a ``while`` encloses its body's operations, so times are taken as SELF time)
and a line ``XLA Modules`` (one event per program execution, named
``jit_<function>(<fingerprint>)``). An operation's name is its whole HLO
line (``short_name`` cuts it down). Event times count from the profile's
start; the plane ``Task Environment`` gives that start and stop as Unix
nanoseconds, which puts the trace on the same clock as the client's timeline
and the flight ring (``start_unix`` + ``window_at_s``).
"""

from __future__ import annotations

import re
from pathlib import Path
from typing import Iterable, Optional

import numpy as np

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast", re.I)


def union_seconds(intervals: Iterable[tuple[float, float]]) -> float:
    """Length of the union of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def subtract_seconds(a: list[tuple[float, float]],
                     b: list[tuple[float, float]]) -> float:
    """Length of union(a) not covered by union(b)."""
    return union_seconds(a + b) - union_seconds(b)


def self_times(events: list[tuple[float, float, str]]) -> dict[str, float]:
    """Per-name SELF seconds of properly nested events on one line: an
    enclosing event (a ``while`` around its body) is charged only the time
    none of its children cover."""
    out: dict[str, float] = {}
    stack: list[list] = []      # [end, name, self]

    def close(upto: float) -> None:
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            out[name] = out.get(name, 0.0) + max(0.0, own)

    for s, e, name in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(s)
        if stack:
            stack[-1][2] -= min(e, stack[-1][0]) - s
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float,
         top: int) -> list[tuple[float, float]]:
    """The ``top`` longest idle gaps (start, seconds) inside [lo, hi]."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, s - cur))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi - cur))
    return sorted(out, key=lambda g: -g[1])[:top]


HLO = re.compile(r"^%?(?P<name>[\w.\-]+) = (?P<shape>\(?[a-z]\w*\[[\d,]*\])"
                 r".*?\s(?P<op>[a-z][\w\-]*)\(")


def short_name(text: str) -> str:
    """A TPU trace names an operation by its whole HLO line; keep the name,
    the opcode and the result's shape: ``copy.163 copy bf16[32,289,8,64,128]``.
    A Pallas kernel is a ``custom-call`` whose target is ``tpu_custom_call``
    (the program gives its kernels no name yet)."""
    m = HLO.match(text)
    if not m:
        return text[:96]
    op = "custom-call:tpu_custom_call" if (
        m["op"] == "custom-call" and "tpu_custom_call" in text) else m["op"]
    return f"{m['name']} {op} {m['shape'].lstrip('(')}"


def _line(plane, name: str):
    return next((ln for ln in plane.lines if ln.name == name), None)


def _events(line) -> list[tuple[float, float, str]]:
    return [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
             short_name(ev.name)) for ev in line.events
            ] if line is not None else []


def reduce(path: Path, device_plane: str = DEVICE_PLANE) -> dict:
    """The whole reduction of one ``.xplane.pb``. Raises when no operation ran
    on any device plane: a traced run that drove no device is refused."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = sorted((p for p in data.planes
                     if p.name.startswith(device_plane)),
                    key=lambda p: p.name)
    env = next((dict(p.stats) for p in data.planes
                if p.name == "Task Environment"), {})
    start_ns = env.get("profile_start_time")
    per_chip, modules = [], []
    ops_total: dict[str, float] = {}
    coll_s, coll_exposed_s = [], []
    first, last = float("inf"), 0.0
    for plane in planes:
        ops = _events(_line(plane, OPS_LINE))
        iv = [(s, e) for s, e, _ in ops]
        if iv:
            first = min(first, min(s for s, _ in iv))
            last = max(last, max(e for _, e in iv))
        per_chip.append({"plane": plane.name, "events": len(ops),
                         "busy_s": union_seconds(iv), "intervals": iv})
        for name, sec in self_times(ops).items():
            ops_total[name] = ops_total.get(name, 0.0) + sec
        coll = [(s, e) for s, e, n in ops if COLLECTIVE.search(n)]
        rest = [(s, e) for s, e, n in ops if not COLLECTIVE.search(n)
                and not n.startswith(("while", "conditional", "call"))]
        coll_s.append(union_seconds(coll))
        coll_exposed_s.append(subtract_seconds(coll, rest))
        if not modules:
            modules = _events(_line(plane, MODULES_LINE))
    if not per_chip or not any(c["events"] for c in per_chip):
        raise RuntimeError(
            f"{path}: no operation on any {device_plane}* plane; planes are "
            f"{[(p.name, [ln.name for ln in p.lines]) for p in data.planes]}")
    # the traced window is the span in which the profiler RECORDED the
    # device, first operation to last: between the profile's start and stop
    # and those there are a few ms and ~0.25 s in which the device runs and
    # nothing is recorded (v5e, PR 23), which would read as idle time
    window_s, lo, hi = last - first, first, last
    n = len(per_chip)
    busy = [c["busy_s"] for c in per_chip]
    worst = int(np.argmin(busy))
    top_ops = sorted(ops_total.items(), key=lambda kv: -kv[1])[:10]
    idle = gaps(per_chip[worst]["intervals"], lo, hi, 10)
    return {
        "path": str(path), "chips": n, "window_s": window_s,
        # the profile's start on the Unix clock, and the window inside it
        "start_unix": None if start_ns is None else start_ns * 1e-9,
        "window_at_s": (lo, hi),
        "busy_s": float(np.mean(busy)), "busy_by_chip": busy,
        "idle_share": 1.0 - float(np.mean(busy)) / window_s,
        "idle_share_worst_chip": 1.0 - min(busy) / window_s,
        "ops": {k: v / n for k, v in ops_total.items()},    # mean per chip
        "modules": modules,                                 # first chip's
        "collective_s": float(np.mean(coll_s)),
        "collective_exposed_s": float(np.mean(coll_exposed_s)),
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in top_ops],
            # nothing in the program names what the host was doing yet
            # (no TraceAnnotation): every gap is unattributed
            "idle_gaps": [["unattributed", sec] for _, sec in idle],
        },
        "notes": {"planes": [c["plane"] for c in per_chip],
                  "events": [c["events"] for c in per_chip],
                  "idle_share_worst_chip": 1.0 - min(busy) / window_s,
                  "longest_gaps_at_s": [round(s, 4) for s, _ in idle]},
    }


def module_seconds(trace: dict, pattern: str) -> tuple[float, int]:
    """(device seconds, executions) of the programs whose name matches."""
    rx = re.compile(pattern)
    hit = [(s, e) for s, e, name in trace["modules"] if rx.search(name)]
    return sum(e - s for s, e in hit), len(hit)


def op_seconds(trace: dict, pattern: str) -> tuple[float, list[str]]:
    """(mean-per-chip self seconds, names) of the operations matching."""
    rx = re.compile(pattern)
    hit = {k: v for k, v in trace["ops"].items() if rx.search(k)}
    return sum(hit.values()), sorted(hit)


def find_xplane(run_dir: Path, traced: dict) -> Optional[Path]:
    root = Path(traced.get("trace_dir", ""))
    root = root if root.is_absolute() else run_dir / root
    found = sorted(root.rglob("*.xplane.pb")) if root.exists() else []
    return found[-1] if found else None


def reduce_run(run_dir: Path, traced: dict) -> dict:
    path = find_xplane(run_dir, traced)
    if path is None:
        raise RuntimeError(f"no .xplane.pb under {traced.get('trace_dir')}")
    out = reduce(path)
    out["notes"]["asked_to_start_s"] = (
        None if out["start_unix"] is None
        else out["start_unix"] - traced["asked_unix"])
    return out
