"""A minimal writer of the profiler's XSpace protobuf (tsl/profiler/protobuf/
xplane.proto), so tests can hand-make a trace with known intervals and read it
back through the same ``jax.profiler.ProfileData`` the reduction uses."""


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        out.append(b | (0x80 if n else 0))
        if not n:
            return bytes(out)


def _field(num: int, value) -> bytes:
    if isinstance(value, int):
        return _varint(num << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(num << 3 | 2) + _varint(len(value)) + value


def _map_entry(num: int, key: int, value: bytes) -> bytes:
    return _field(num, _field(1, key) + _field(2, value))


def plane(name: str, lines: dict, stats: dict | None = None,
          meta: dict | None = None) -> bytes:
    """lines: {line name: [(event name, start_ns, duration_ns), ...]};
    stats: {stat name: unsigned value} on the plane itself; meta: {event
    name: {stat name: str or unsigned value}} on the events' METADATA, where
    a TPU trace keeps an operation's ``tf_op`` and ``program_id``."""
    names = sorted({ev[0] for evs in lines.values() for ev in evs})
    ids = {n: i + 1 for i, n in enumerate(names)}
    stats = dict(stats or {})
    stat_ids = {n: i + 1 for i, n in enumerate(
        list(stats) + sorted({k for m in (meta or {}).values() for k in m}
                             - set(stats)))}
    body = _field(2, name)
    for i, (line_name, events) in enumerate(lines.items()):
        line = _field(1, i + 1) + _field(2, line_name) + _field(3, 0)
        for ev_name, start_ns, dur_ns in events:
            line += _field(4, _field(1, ids[ev_name])
                           + _field(2, start_ns * 1000)
                           + _field(3, dur_ns * 1000))
        body += _field(3, line)
    for n, i in ids.items():
        entry = _field(1, i) + _field(2, n)
        for stat, value in (meta or {}).get(n, {}).items():
            entry += _field(5, _field(1, stat_ids[stat]) + _field(
                3 if isinstance(value, int) else 5, value))
        body += _map_entry(4, i, entry)
    for stat, i in stat_ids.items():
        body += _map_entry(5, i, _field(1, i) + _field(2, stat))
    for stat, value in stats.items():
        body += _field(6, _field(1, stat_ids[stat]) + _field(3, value))
    return body


def space(planes: list[bytes]) -> bytes:
    return b"".join(_field(1, p) for p in planes)
