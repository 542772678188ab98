"""A reader for the profiler's ``.xplane.pb`` that needs nothing but Python.

``jax.profiler.ProfileData`` gives events and their own stats, but not the
stats of an event's METADATA, and on this runtime that is where XLA keeps
what survives of ``jax.named_scope``: the ``tf_op`` stat of an operation's
metadata holds its full scope path (``jit(train_window)/window_fwd_bwd/...``)
(my chip run, PR 23: ``tests/record_trace.py``). So this decodes the protobuf
wire format itself, for the fields the reduction in ``trace.py`` uses
(tsl/profiler/protobuf/xplane.proto: XSpace, XPlane, XLine, XEvent, XStat,
XEventMetadata, XStatMetadata).
"""

import dataclasses
import struct


def _varint(buf, i):
    shift = result = 0
    while True:
        b = buf[i]
        i += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value comes as a memoryview."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            val, i = _varint(buf, i)
        elif wt == 2:
            size, i = _varint(buf, i)
            val = buf[i:i + size]
            i += size
        elif wt == 1:
            val = bytes(buf[i:i + 8])
            i += 8
        elif wt == 5:
            val = bytes(buf[i:i + 4])
            i += 4
        else:
            raise ValueError(f"wire type {wt} in an xplane file")
        yield num, wt, val


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf):
    """(metadata id, value); a ref value comes as ('ref', id)."""
    mid, val = 0, None
    for num, _wt, v in _fields(buf):
        if num == 1:
            mid = v
        elif num == 2:
            val = struct.unpack("<d", v)[0]
        elif num == 3:
            val = v
        elif num == 4:
            val = _signed(v)
        elif num == 5:
            val = bytes(v).decode("utf-8", "replace")
        elif num == 6:
            val = bytes(v)
        elif num == 7:
            val = ("ref", v)
    return mid, val


@dataclasses.dataclass
class Event:
    name: str          # the metadata's display name, else its name
    long_name: str     # the metadata's name (for an XLA op: its HLO text)
    start_ps: int      # on the clock all planes of one file share
    duration_ps: int
    meta: dict         # the metadata's stats by name (``tf_op``, ...)

    @property
    def end_ps(self):
        return self.start_ps + self.duration_ps


@dataclasses.dataclass
class Line:
    name: str
    events: list


@dataclasses.dataclass
class Plane:
    name: str
    lines: list


def _map_entry(buf):
    key, val = 0, b""
    for num, _wt, v in _fields(buf):
        if num == 1:
            key = v
        elif num == 2:
            val = v
    return key, val


def _plane(buf, want_line, want_event):
    name, raw_lines, event_meta, stat_names = "", [], {}, {}
    for num, _wt, v in _fields(buf):
        if num == 2:
            name = bytes(v).decode()
        elif num == 3:
            raw_lines.append(v)
        elif num == 4:
            _key, em = _map_entry(v)
            mid, ename, display, stats = 0, "", "", []
            for n2, _w2, v2 in _fields(em):
                if n2 == 1:
                    mid = v2
                elif n2 == 2:
                    ename = bytes(v2).decode("utf-8", "replace")
                elif n2 == 4:
                    display = bytes(v2).decode("utf-8", "replace")
                elif n2 == 5:
                    stats.append(_stat(v2))
            event_meta[mid] = (ename, display, stats)
        elif num == 5:
            _key, sm = _map_entry(v)
            sid, sname = 0, ""
            for n2, _w2, v2 in _fields(sm):
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = bytes(v2).decode()
            stat_names[sid] = sname

    resolved = {}
    for mid, (ename, display, stats) in event_meta.items():
        meta = {}
        for sid, val in stats:
            if isinstance(val, tuple):
                val = stat_names.get(val[1], "")
            meta[stat_names.get(sid, str(sid))] = val
        resolved[mid] = (display or ename, ename, meta)

    lines = []
    for raw in raw_lines:
        lname, t0_ns, raw_events = "", 0, []
        for num, _wt, v in _fields(raw):
            if num == 2:
                lname = bytes(v).decode()
            elif num == 3:
                t0_ns = _signed(v)
            elif num == 4:
                raw_events.append(v)
        if not want_line(name, lname):
            continue
        events = []
        for ev in raw_events:
            mid = off = dur = 0
            for num, wt, v in _fields(ev):
                if wt != 0:
                    continue
                if num == 1:
                    mid = v
                elif num == 2:
                    off = v
                elif num == 3:
                    dur = v
            disp, ename, meta = resolved.get(mid, (str(mid), str(mid), {}))
            if not want_event(name, disp):
                continue
            events.append(Event(disp, ename, t0_ns * 1000 + off, dur, meta))
        lines.append(Line(lname, events))
    return Plane(name, lines)


def read(path, want_line=lambda plane, line: True,
         want_event=lambda plane, event: True):
    """The planes of ``path``. ``want_line(plane name, line name)`` picks the
    lines whose events are decoded, the others are skipped unread;
    ``want_event(plane name, event name)`` picks the events that are kept."""
    with open(path, "rb") as fd:
        buf = memoryview(fd.read())
    return [_plane(v, want_line, want_event) for num, _wt, v in _fields(buf) if num == 1]
