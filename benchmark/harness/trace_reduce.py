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

Since PR 25 the program names what it runs. Device side: an operation's
event METADATA carries ``tf_op``, the path of ``jax.named_scope``s it was
staged under (``jit(_decode_paged_fn)/jit(main)/decode/layers/while/body/
mlp/dot_general``), and ``program_id``, the fingerprint in its module's
name. ``ProfileData`` shows no metadata, so ``op_metadata`` reads those two
off the file's own bytes. Host side: the plane ``/host:CPU`` has one line a
thread, and the engine thread's holds the scheduler's ``sched.*``
TraceAnnotations, on the profiler's clock like the device's operations.
"""

from __future__ import annotations

import bisect
import re
from pathlib import Path
from typing import Any, Iterable, Iterator, Optional

import numpy as np

DEVICE_PLANE = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
PHASE = "sched."            # the scheduler's TraceAnnotations
# phases in which the scheduler waits and does not work: device idle under
# them is not the scheduler's
WAITING = ("sched.wait_device", "sched.idle")
# parts of a ``tf_op`` path that no jax.named_scope made: transforms
# (``jit(f)``) and the control flow they lower to
STRUCTURE = re.compile(
    r"^(\w+\(.*\)|while|body|cond|closed_call|core_call|checkpoint|remat|"
    r"shard_map|pjit|custom_[jv][jv]p_call|branch_\d+_fun)$")
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


def overlap_seconds(a: list[tuple[float, float]],
                    b: list[tuple[float, float]]) -> float:
    """Length of union(a) that union(b) covers."""
    return union_seconds(a) + union_seconds(b) - union_seconds(a + b)


def self_times(events: list[tuple[float, float, Any]]) -> dict[Any, float]:
    """Per-name SELF seconds of properly nested events on one line: an
    enclosing event (a ``while`` around its body) is charged only the time
    none of its children cover. A name is any hashable."""
    out: dict[Any, float] = {}
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


def idle_intervals(intervals: list[tuple[float, float]], lo: float,
                   hi: float) -> list[tuple[float, float]]:
    """The idle intervals [start, end) inside [lo, hi], in order."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def gaps(intervals: list[tuple[float, float]], lo: float, hi: float,
         top: int) -> list[tuple[float, float]]:
    """The ``top`` longest idle gaps (start, seconds) inside [lo, hi]."""
    return sorted(((s, e - s) for s, e in idle_intervals(intervals, lo, hi)),
                  key=lambda g: -g[1])[:top]


HLO = re.compile(r"^%?(?P<name>[\w.\-]+) = (?P<shape>\(?[a-z]\w*\[[\d,]*\])"
                 r".*?\s(?P<op>[a-z][\w\-]*)\(")


def short_name(text: str) -> str:
    """A TPU trace names an operation by its whole HLO line; keep the name,
    the opcode and the result's shape: ``copy.163 copy bf16[32,289,8,64,128]``.
    A Pallas kernel is a ``custom-call`` whose target is ``tpu_custom_call``;
    the HLO name is the kernel's own (``paged_decode_attn.9``)."""
    m = HLO.match(text)
    if not m:
        return text[:96]
    op = "custom-call:tpu_custom_call" if (
        m["op"] == "custom-call" and "tpu_custom_call" in text) else m["op"]
    return f"{m['name']} {op} {m['shape'].lstrip('(')}"


def scope_path(tf_op: str) -> str:
    """The jax.named_scopes of a ``tf_op`` path, outermost first: the path
    without its transforms, its control flow and the primitive at its end
    (``jit(f)/jit(main)/decode/layers/while/body/mlp/dot_general:`` ->
    ``decode/layers/mlp``); "" where the program named nothing."""
    parts = tf_op.rstrip(":").split("/")[:-1]
    return "/".join(p for p in parts if p and not STRUCTURE.match(p))


def _fields(buf: memoryview) -> Iterator[tuple[int, Any]]:
    """(field number, value) of one protobuf message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields are skipped."""
    i, n = 0, len(buf)

    def varint() -> int:
        nonlocal i
        value = shift = 0
        while True:
            b = buf[i]
            i += 1
            value |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return value

    while i < n:
        key = varint()
        kind = key & 7
        if kind == 0:
            yield key >> 3, varint()
        elif kind == 2:
            size = varint()
            yield key >> 3, buf[i:i + size]
            i += size
        else:
            i += 8 if kind == 1 else 4


def op_metadata(raw: bytes, device_plane: str = DEVICE_PLANE
                ) -> dict[tuple[int, str], str]:
    """(program fingerprint, operation's event name) -> scope path, for the
    operations of the device planes whose metadata carries a ``tf_op``
    (tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1; XPlane.name = 2,
    .event_metadata = 4, .stat_metadata = 5; XEventMetadata.name = 2,
    .stats = 5; XStat.metadata_id = 1, .uint64/.int64/.str/.ref_value =
    3/4/5/7; XStatMetadata.name = 2). Only the metadata maps are walked, a
    few thousand entries: the events stay with ProfileData."""
    out: dict[tuple[int, str], str] = {}
    for num, plane in _fields(memoryview(raw)):
        if num != 1:
            continue
        name, events, stats = "", [], {}
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:                        # map entry: key 1, value 2
                events.append(dict(_fields(v))[2])
            elif f == 5:
                entry = dict(_fields(v))
                stats[entry[1]] = bytes(
                    dict(_fields(entry[2])).get(2, b"")).decode()
        if not name.startswith(device_plane):
            continue
        for meta in events:
            ev_name, tf_op, program = "", None, 0
            for f, v in _fields(meta):
                if f == 2:
                    ev_name = bytes(v).decode()
                elif f == 5:
                    st = dict(_fields(v))
                    which = stats.get(st.get(1))
                    if which == "tf_op":
                        tf_op = (bytes(st[5]).decode() if 5 in st
                                 else stats.get(st.get(7), ""))
                    elif which == "program_id":
                        program = st.get(3, st.get(4, 0))
            if tf_op:
                out[(program, ev_name)] = scope_path(tf_op)
    return out


def _line(plane, name: str):
    return next((ln for ln in plane.lines if ln.name == name), None)


def _events(line) -> list[tuple[float, float, str]]:
    return [(ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9,
             ev.name) for ev in line.events] if line is not None else []


def _named_ops(ops: list, modules: list, scopes: dict) -> list:
    """Each operation's raw name replaced by (program, scope path, short
    name): the program is the module execution that holds the operation's
    start, and with its fingerprint the operation's metadata is found."""
    modules = sorted(modules)
    starts = [m[0] for m in modules]
    names: dict[tuple[str, str], tuple[str, str, str]] = {}
    out = []
    for s, e, raw in ops:
        i = bisect.bisect_right(starts, s) - 1
        program = modules[i][2] if i >= 0 and s < modules[i][1] else ""
        if (program, raw) not in names:     # a few thousand, of ~10^5 events
            m = re.search(r"\((\d+)\)$", program)
            names[program, raw] = (
                program.split("(")[0],
                scopes.get((int(m[1]) if m else 0, raw), ""), short_name(raw))
        out.append((s, e, names[program, raw]))
    return out


def engine_phases(data) -> tuple[str, list[tuple[float, float, str]]]:
    """The scheduler's ``sched.*`` annotations: (line name, [(start, end,
    phase)]) of the host line that holds most of them, the engine thread's;
    ("", []) where the program wrote none."""
    best: tuple[str, list] = ("", [])
    for plane in data.planes:
        if plane.name != HOST_PLANE:
            continue
        for ln in plane.lines:
            evs = [ev for ev in _events(ln) if ev[2].startswith(PHASE)]
            if len(evs) > len(best[1]):
                best = (ln.name, evs)
    return best


def gap_owner(gap: tuple[float, float], phases: list) -> str:
    """The phase that covers most of an idle gap (start, seconds)."""
    lo, hi = gap[0], gap[0] + gap[1]
    cover: dict[str, float] = {}
    for s, e, name in phases:
        if s < hi and e > lo:
            cover[name] = cover.get(name, 0.0) + min(e, hi) - max(s, lo)
    return max(cover, key=cover.get) if cover else "unattributed"


def _labels(rows: dict) -> dict[str, float]:
    """Device seconds by what the breakdown prints: an operation goes under
    its scope path; one staged directly under a scope that also has scopes
    inside it (the slices and restacks of ``decode/layers``) keeps its
    result's shape; one the program named nothing for keeps its HLO name."""
    scopes = {scope for _, scope, _ in rows}
    parents = {s for s in scopes if any(o.startswith(s + "/") for o in scopes)}
    out: dict[str, float] = {}
    for (_, scope, short), sec in rows.items():
        label = (short if not scope else scope if scope not in parents
                 else f"{scope} {short.split(' ')[-1]}")
        out[label] = out.get(label, 0.0) + sec
    return out


def reduce(path: Path, device_plane: str = DEVICE_PLANE) -> dict:
    """The whole reduction of one ``.xplane.pb``. Raises when no operation ran
    on any device plane: a traced run that drove no device is refused."""
    from jax.profiler import ProfileData

    raw = Path(path).read_bytes()
    data = ProfileData.from_serialized_xspace(raw)
    scopes = op_metadata(raw, device_plane)
    planes = sorted((p for p in data.planes
                     if p.name.startswith(device_plane)),
                    key=lambda p: p.name)
    env = next((dict(p.stats) for p in data.planes
                if p.name == "Task Environment"), {})
    start_ns = env.get("profile_start_time")
    per_chip, modules = [], []
    rows: dict[tuple[str, str, str], float] = {}
    coll_s, coll_exposed_s = [], []
    first, last = float("inf"), 0.0
    for plane in planes:
        mods = _events(_line(plane, MODULES_LINE))
        ops = _named_ops(_events(_line(plane, OPS_LINE)), mods, scopes)
        iv = [(s, e) for s, e, _ in ops]
        if iv:
            first = min(first, min(s for s, _ in iv))
            last = max(last, max(e for _, e in iv))
        per_chip.append({"plane": plane.name, "events": len(ops),
                         "busy_s": union_seconds(iv), "intervals": iv})
        for key, sec in self_times(ops).items():
            rows[key] = rows.get(key, 0.0) + sec
        coll = [(s, e) for s, e, k in ops if COLLECTIVE.search(k[2])]
        rest = [(s, e) for s, e, k in ops if not COLLECTIVE.search(k[2])
                and not k[2].startswith(("while", "conditional", "call"))]
        coll_s.append(union_seconds(coll))
        coll_exposed_s.append(subtract_seconds(coll, rest))
        if not modules:
            modules = mods
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
    rows = {k: v / n for k, v in rows.items()}              # mean per chip
    ops_total: dict[str, float] = {}
    for (_, _, short), sec in rows.items():
        ops_total[short] = ops_total.get(short, 0.0) + sec
    busy = [c["busy_s"] for c in per_chip]
    worst = int(np.argmin(busy))
    top_ops = sorted(_labels(rows).items(), key=lambda kv: -kv[1])[:10]
    idle = gaps(per_chip[worst]["intervals"], lo, hi, 10)
    # host and device are on the profiler's one clock: no anchor arithmetic
    engine_line, phases = engine_phases(data)
    owned = [(s, e) for s, e, name in phases if name not in WAITING]
    return {
        "path": str(path), "chips": n, "window_s": window_s,
        # the profile's start on the Unix clock, and the window inside it
        "start_unix": None if start_ns is None else start_ns * 1e-9,
        "window_at_s": (lo, hi),
        "busy_s": float(np.mean(busy)), "busy_by_chip": busy,
        "idle_share": 1.0 - float(np.mean(busy)) / window_s,
        "idle_share_worst_chip": 1.0 - min(busy) / window_s,
        "ops": ops_total,               # by HLO name, mean per chip
        # [(program, scope path, HLO name, seconds)], mean per chip
        "op_rows": [(*k, v) for k, v in rows.items()],
        "modules": [(s, e, short_name(m)) for s, e, m in modules],  # chip 0's
        "collective_s": float(np.mean(coll_s)),
        "collective_exposed_s": float(np.mean(coll_exposed_s)),
        # the scheduler's phases, and the device-idle seconds (mean over
        # chips) under a phase in which the scheduler works; None where the
        # program annotates nothing
        "phases": phases,
        "idle_owned_s": None if not phases else float(np.mean(
            [overlap_seconds(idle_intervals(c["intervals"], lo, hi), owned)
             for c in per_chip])),
        "breakdown": {
            "device_ops": [[k, v] for k, v in top_ops],
            "idle_gaps": [[gap_owner(g, phases), g[1]] for g in idle],
        },
        "notes": {"planes": [c["plane"] for c in per_chip],
                  "events": [c["events"] for c in per_chip],
                  "idle_share_worst_chip": 1.0 - min(busy) / window_s,
                  "longest_gaps_at_s": [round(s, 4) for s, _ in idle],
                  "engine_line": engine_line, "phases": len(phases),
                  "scoped_ops": sum(bool(k[1]) for k in rows)},
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
    # the capture answers when the trace is written: seconds past its own
    out["notes"]["answered_after_s"] = (
        traced["answered_unix"] - traced["asked_unix"] - traced["seconds"])
    return out
