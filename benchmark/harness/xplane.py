"""Reads a profiler trace (`*.xplane.pb`) without JAX: a small decoder of the
protobuf wire format for the XSpace / XPlane / XLine / XEvent messages, enough for
names, starts and durations. Only the planes asked for are decoded; a host plane with
millions of Python-tracer events is skipped field by field.

Field numbers (tsl/profiler/protobuf/xplane.proto): XSpace.planes=1; XPlane.id=1
name=2 lines=3 event_metadata=4 (map: key=1 value=2); XLine.id=1 name=2
timestamp_ns=3 events=4 display_name=11; XEvent.metadata_id=1 offset_ps=2
duration_ps=3; XEventMetadata.id=1 name=2 display_name=4.
"""

from __future__ import annotations

import numpy as np


def _varint(buf, pos: int):
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _fields(buf, pos: int, end: int):
    """(field number, wire type, value) of one message; a length-delimited value is
    its (start, end) inside `buf`."""
    while pos < end:
        key, pos = _varint(buf, pos)
        no, wt = key >> 3, key & 7
        if wt == 0:
            val, pos = _varint(buf, pos)
        elif wt == 2:
            n, pos = _varint(buf, pos)
            val = (pos, pos + n)
            pos += n
        elif wt == 1:
            val, pos = None, pos + 8
        elif wt == 5:
            val, pos = None, pos + 4
        else:
            raise ValueError(f"wire type {wt} at byte {pos}")
        yield no, wt, val


def _text(buf, span) -> str:
    return bytes(buf[span[0]: span[1]]).decode("utf-8", "replace")


def _plane_name(buf, span) -> str:
    for no, wt, val in _fields(buf, *span):
        if no == 2 and wt == 2:
            return _text(buf, val)
    return ""


def _event(buf, span):
    meta = offset = dur = 0
    for no, wt, val in _fields(buf, *span):
        if wt == 0:
            if no == 1:
                meta = val
            elif no == 2:
                offset = val
            elif no == 3:
                dur = val
    return meta, offset, dur


def _line(buf, span) -> dict:
    name = display = ""
    t_ns = 0
    events = []
    for no, wt, val in _fields(buf, *span):
        if no == 2 and wt == 2:
            name = _text(buf, val)
        elif no == 11 and wt == 2:
            display = _text(buf, val)
        elif no == 3 and wt == 0:
            t_ns = val
        elif no == 4 and wt == 2:
            events.append(_event(buf, val))
    ev = np.array(events, np.int64).reshape(-1, 3)
    return {"name": display or name, "timestamp_ns": t_ns, "meta": ev[:, 0],
            "start_ns": t_ns + ev[:, 1] / 1000.0, "dur_ns": ev[:, 2] / 1000.0}


def _plane(buf, span) -> dict:
    names: dict = {}
    lines = []
    for no, wt, val in _fields(buf, *span):
        if no == 3 and wt == 2:
            lines.append(_line(buf, val))
        elif no == 4 and wt == 2:
            key, meta_name = 0, ""
            for n2, w2, v2 in _fields(buf, *val):
                if n2 == 1 and w2 == 0:
                    key = v2
                elif n2 == 2 and w2 == 2:
                    for n3, w3, v3 in _fields(buf, *v2):
                        if n3 == 2 and w3 == 2:
                            meta_name = _text(buf, v3)
            names[key] = meta_name
    for line in lines:
        line["names"] = [names.get(int(m), "") for m in line["meta"]]
    return {"lines": {line["name"]: line for line in lines}}


def read_planes(path: str, prefix: str) -> dict:
    """{plane name: {"lines": {line name: {"names", "start_ns", "dur_ns"}}}} of the
    planes whose name starts with `prefix`. Starts are nanoseconds on the profiler's
    clock, durations nanoseconds."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for no, wt, val in _fields(buf, 0, len(buf)):
        if no == 1 and wt == 2:
            name = _plane_name(buf, val)
            if name.startswith(prefix):
                out[name] = _plane(buf, val)
    return out


def plane_names(path: str) -> list:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_plane_name(buf, val) for no, wt, val in _fields(buf, 0, len(buf))
            if no == 1 and wt == 2]


def profile_times(path: str):
    """(start, stop) of the profiler session in nanoseconds since the epoch, from the
    `Task Environment` plane's `profile_start_time` and `profile_stop_time`; None for
    one that is not there. Event starts count from the session's start."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    found = {}
    for no, wt, val in _fields(buf, 0, len(buf)):
        if no != 1 or wt != 2 or _plane_name(buf, val) != "Task Environment":
            continue
        ids, values = {}, {}
        for n2, w2, v2 in _fields(buf, *val):
            if n2 == 5 and w2 == 2:    # stat_metadata entry: key=1, value{id=1, name=2}
                for n3, w3, v3 in _fields(buf, *v2):
                    if n3 == 2 and w3 == 2:
                        meta = {a: c for a, b, c in _fields(buf, *v3)}
                        ids[meta.get(1)] = _text(buf, meta[2]) if 2 in meta else ""
            elif n2 == 6 and w2 == 2:  # XStat: metadata_id=1, uint64_value=3, int64_value=4
                stat = {a: c for a, b, c in _fields(buf, *v2) if b == 0}
                values[stat.get(1)] = stat.get(3, stat.get(4))
        found = {name: values.get(key) for key, name in ids.items()}
    return found.get("profile_start_time"), found.get("profile_stop_time")


def copy_planes(src: str, dst: str, keep) -> int:
    """Writes the planes of `src` whose name `keep(name)` accepts to `dst`, byte for
    byte: a recorded trace without the host's Python-tracer plane. Returns the size."""
    with open(src, "rb") as f:
        buf = memoryview(f.read())
    out = bytearray()
    for no, wt, val in _fields(buf, 0, len(buf)):
        if no != 1 or wt != 2 or not keep(_plane_name(buf, val)):
            continue
        out.append(0x0A)  # field 1, length-delimited
        n = val[1] - val[0]
        while n > 0x7F:
            out.append((n & 0x7F) | 0x80)
            n >>= 7
        out.append(n)
        out += buf[val[0]: val[1]]
    with open(dst, "wb") as f:
        f.write(out)
    return len(out)
