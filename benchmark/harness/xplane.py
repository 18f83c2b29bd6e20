"""Reading the profiler's ``.xplane.pb`` without a schema.

``jax.profiler.ProfileData`` gives planes, lines and events, but not the
stats of an event's *metadata*, and the scope of a TPU operation (XLA's
``op_name``: ``jit(step)/transpose(jvp(GPT))/block3/attn/qkv/dot_general:``)
is one of those, under the stat name ``tf_op``.  The file is a protobuf
(tsl/profiler/protobuf/xplane.proto); the few fields wanted are decoded
here directly and every other field is skipped by its length.

The benchmark's own copy of what ``horovod_tpu/obs/profile.py:read_xplane``
does, on purpose: the program's reduction cannot move the yardstick.

    XSpace  1: planes
    XPlane  2: name  3: lines  4: event_metadata (map)  5: stat_metadata (map)
    XLine   2: name  3: timestamp_ns  4: events
    XEvent  1: metadata_id  2: offset_ps  3: duration_ps
    XEventMetadata  1: id  2: name  5: stats
    XStatMetadata   1: id  2: name
    XStat   1: metadata_id  5: str_value  7: ref_value (a stat_metadata id)
"""

from __future__ import annotations

from typing import Callable, Dict, Iterator, List, Tuple

SCOPE_STAT = "tf_op"
Span = Tuple[int, int]


def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if b < 0x80:
            return result, i
        shift += 7


def fields(buf: bytes, lo: int, hi: int) -> Iterator[tuple]:
    """``(field number, value)`` of the message in ``buf[lo:hi]``: an int
    for a varint, ``(lo, hi)`` offsets for a length-delimited field, raw
    bytes for a fixed-width one."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value = (i, i + n)
            i += n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            value = buf[i:i + n]
            i += n
        else:
            raise ValueError(f"xplane: wire type {wire} at byte {i}")
        yield key >> 3, value


def _text(buf: bytes, span: Span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _map(buf: bytes, entries: List[Span]) -> Dict[int, Span]:
    """Protobuf map entries (``1: key  2: value``) -> key -> the value."""
    out = {}
    for entry in entries:
        got = dict(fields(buf, *entry))
        if 2 in got:
            out[got.get(1, 0)] = got[2]
    return out


def _event_names(buf: bytes, plane: dict) -> Dict[int, Tuple[str, str]]:
    """Event metadata id -> (the event's name, its scope stat or '')."""
    stat_names = {}
    for key, value in _map(buf, plane["stat_md"]).items():
        for number, v in fields(buf, *value):
            if number == 2:
                stat_names[key] = _text(buf, v)
    want = {k for k, n in stat_names.items() if n == SCOPE_STAT}
    out = {}
    for key, value in _map(buf, plane["event_md"]).items():
        name, scope = "", ""
        for number, v in fields(buf, *value):
            if number == 2:
                name = _text(buf, v)
            elif number == 5 and want and not scope:
                stat = dict(fields(buf, *v))
                if stat.get(1) in want:
                    if 5 in stat:
                        scope = _text(buf, stat[5])
                    elif 7 in stat:
                        scope = stat_names.get(stat[7], "")
        out[key] = (name, scope)
    return out


def planes(path: str, want_plane: Callable[[str], bool],
           want_line: Callable[[str, str], bool],
           want_event: Callable[[str, str], bool]) -> List[dict]:
    """``[{"name", "lines": [{"name", "events": [(event name, start_ns,
    duration_ns, scope)]}]}]`` of what is wanted: ``want_plane(plane
    name)``, ``want_line(plane name, line name)``, ``want_event(plane
    name, event name)``.  The rest is never decoded (a device plane has
    per-step and per-module lines too, and the host plane holds every
    thread of the process with the runtime's own events by the
    thousand)."""
    with open(path, "rb") as f:
        buf = f.read()
    out = []
    for number, span in fields(buf, 0, len(buf)):
        if number != 1:
            continue
        plane = {"name": "", "lines": [], "event_md": [], "stat_md": []}
        for n, v in fields(buf, *span):
            if n == 2:
                plane["name"] = _text(buf, v)
            elif n == 3:
                plane["lines"].append(v)
            elif n == 4:
                plane["event_md"].append(v)
            elif n == 5:
                plane["stat_md"].append(v)
        if not want_plane(plane["name"]):
            continue
        names = None
        lines = []
        for line in plane["lines"]:
            line_name, t0_ns, events = "", 0, []
            for n, v in fields(buf, *line):
                if n == 2:
                    line_name = _text(buf, v)
                elif n == 3:
                    t0_ns = v
                elif n == 4:
                    events.append(v)
            if not want_line(plane["name"], line_name):
                continue
            if names is None:
                names = {k: v for k, v in _event_names(buf, plane).items()
                         if want_event(plane["name"], v[0])}
            rows = []
            for ev in events:
                # XEvent.metadata_id is the event's first field
                tag, i = _varint(buf, ev[0])
                if tag == 8 and _varint(buf, i)[0] not in names:
                    continue
                got = dict(fields(buf, *ev))
                if got.get(1, 0) not in names:
                    continue
                name, scope = names[got.get(1, 0)]
                rows.append((name, t0_ns + got.get(2, 0) / 1e3,
                             got.get(3, 0) / 1e3, scope))
            lines.append({"name": line_name, "events": rows})
        out.append({"name": plane["name"], "lines": lines})
    return out
