"""Minimal XSpace (xplane.pb) reader — no proto toolchain required.

``jax.profiler`` traces serialize as XSpace protos; reading them back
normally needs ``jax.profiler.ProfileData`` (absent on older jax) or the
tensorflow/tensorboard proto stack (absent here by design — the repo's
observability layer is dependency-free, see ``tb_writer.py`` which hand-
ENCODES the TB event protos). This module is the decoding mirror: a wire-
format parser for exactly the XSpace fields the timeline tools read —

* ``XSpace.planes`` (1) → ``XPlane``: ``name`` (2), ``lines`` (3),
  ``event_metadata`` (4, map<int64, XEventMetadata>), ``stat_metadata``
  (5, map<int64, XStatMetadata>);
* ``XLine``: ``name`` (2), ``timestamp_ns`` (3), ``events`` (4);
* ``XEvent``: ``metadata_id`` (1), ``offset_ps`` (2), ``duration_ps`` (3),
  ``stats`` (4);
* ``XEventMetadata``: ``id`` (1), ``name`` (2), ``display_name`` (4),
  ``stats`` (5);
* ``XStatMetadata``: ``id`` (1), ``name`` (2); ``XStat``: ``metadata_id``
  (1) and one of ``double_value`` (2), ``uint64_value`` (3),
  ``int64_value`` (4), ``str_value`` (5), ``bytes_value`` (6),
  ``ref_value`` (7, the id of a stat metadata whose name is the value).

An event's own stats (:attr:`TraceEvent.stats`) are what a host span's
``TraceAnnotation`` keyword arguments become; its metadata's stats
(:attr:`TraceEvent.meta`) are what the TPU runtime says of a device
operation once for all its runs — among them ``tf_op``, the operation's
``op_name``, which holds the ``jax.named_scope`` path that the
instruction's own name (``fusion.106``) does not.

Event start times are absolute nanoseconds (``line.timestamp_ns +
offset_ps/1000``), matching ``ProfileData``'s ``start_ns`` convention, so
:mod:`.meters` and ``tools/timeline_report.py`` see one interface on every
jax version.
"""

from __future__ import annotations

import dataclasses
import os
import struct
from typing import Any, Dict, Iterator, List, Tuple

__all__ = ["TraceEvent", "TraceLine", "TracePlane", "parse_xspace",
           "load_trace_planes", "encode_xspace"]


@dataclasses.dataclass
class TraceEvent:
    name: str
    start_ns: float
    duration_ns: float
    stats: Dict[str, Any] = dataclasses.field(default_factory=dict)
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.duration_ns


@dataclasses.dataclass
class TraceLine:
    name: str
    timestamp_ns: int
    events: List[TraceEvent]


@dataclasses.dataclass
class TracePlane:
    name: str
    lines: List[TraceLine]


# --- protobuf wire-format primitives ---------------------------------------

def _read_varint(buf: bytes, pos: int) -> Tuple[int, int]:
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _fields(buf: bytes) -> Iterator[Tuple[int, int, bytes]]:
    """Yield ``(field_number, wire_type, payload)`` triples; varint payloads
    arrive pre-decoded as ints re-encoded positionally (returned raw int)."""
    pos, n = 0, len(buf)
    while pos < n:
        tag, pos = _read_varint(buf, pos)
        field, wire = tag >> 3, tag & 0x7
        if wire == 0:                       # varint
            val, pos = _read_varint(buf, pos)
            yield field, wire, val
        elif wire == 1:                     # fixed64
            yield field, wire, buf[pos:pos + 8]
            pos += 8
        elif wire == 2:                     # length-delimited
            ln, pos = _read_varint(buf, pos)
            yield field, wire, buf[pos:pos + ln]
            pos += ln
        elif wire == 5:                     # fixed32
            yield field, wire, buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"unsupported wire type {wire} at {pos}")


def _signed(v: int) -> int:
    """A varint read as int64 (two's complement)."""
    return v - (1 << 64) if v >= 1 << 63 else v


def _parse_stat(buf: bytes) -> Tuple[int, Any]:
    """``(stat metadata id, value)``; a ``ref_value`` comes back as
    ``("ref", id)`` for the plane to resolve."""
    mid, value = 0, None
    for field, wire, val in _fields(buf):
        if field == 1 and wire == 0:
            mid = val
        elif field == 2 and wire == 1:
            value = struct.unpack("<d", val)[0]
        elif field == 3 and wire == 0:
            value = val
        elif field == 4 and wire == 0:
            value = _signed(val)
        elif field == 5 and wire == 2:
            value = val.decode("utf-8", "replace")
        elif field == 6 and wire == 2:
            value = bytes(val)
        elif field == 7 and wire == 0:
            value = ("ref", val)
    return mid, value


def _parse_event(buf: bytes) -> Tuple[int, int, int, list]:
    metadata_id = offset_ps = duration_ps = 0
    stats = []
    for field, wire, val in _fields(buf):
        if field == 1 and wire == 0:
            metadata_id = val
        elif field == 2 and wire == 0:
            offset_ps = val
        elif field == 3 and wire == 0:
            duration_ps = val
        elif field == 4 and wire == 2:
            stats.append(_parse_stat(val))
    return metadata_id, offset_ps, duration_ps, stats


def _parse_line(buf: bytes) -> Tuple[str, int, List[tuple]]:
    name, timestamp_ns, events = "", 0, []
    for field, wire, val in _fields(buf):
        if field == 2 and wire == 2:
            name = val.decode("utf-8", "replace")
        elif field == 3 and wire == 0:
            timestamp_ns = val
        elif field == 4 and wire == 2:
            events.append(_parse_event(val))
    return name, timestamp_ns, events


def _parse_event_metadata(buf: bytes) -> Tuple[int, str, list]:
    mid, name, display, stats = 0, "", "", []
    for field, wire, val in _fields(buf):
        if field == 1 and wire == 0:
            mid = val
        elif field == 2 and wire == 2:
            name = val.decode("utf-8", "replace")
        elif field == 4 and wire == 2:
            display = val.decode("utf-8", "replace")
        elif field == 5 and wire == 2:
            stats.append(_parse_stat(val))
    return mid, display or name, stats


def _parse_metadata_entry(buf: bytes) -> Tuple[int, str, list]:
    """One map<int64, XEventMetadata> entry (key=1, value=2); the same
    shape serves map<int64, XStatMetadata> (id=1, name=2, no stats)."""
    key, name, stats = 0, "", []
    for field, wire, val in _fields(buf):
        if field == 1 and wire == 0:
            key = val
        elif field == 2 and wire == 2:
            mid, name, stats = _parse_event_metadata(val)
            key = key or mid
    return key, name, stats


def _parse_plane(buf: bytes) -> TracePlane:
    name = ""
    raw_lines: List[Tuple[str, int, List[tuple]]] = []
    metadata: Dict[int, Tuple[str, list]] = {}
    stat_names: Dict[int, str] = {}
    for field, wire, val in _fields(buf):
        if field == 2 and wire == 2:
            name = val.decode("utf-8", "replace")
        elif field == 3 and wire == 2:
            raw_lines.append(_parse_line(val))
        elif field == 4 and wire == 2:
            key, mname, mstats = _parse_metadata_entry(val)
            metadata[key] = (mname, mstats)
        elif field == 5 and wire == 2:
            key, sname, _ = _parse_metadata_entry(val)
            stat_names[key] = sname

    def named(stats):
        return {stat_names.get(mid, f"stat:{mid}"):
                (stat_names.get(v[1], "") if isinstance(v, tuple) else v)
                for mid, v in stats}

    meta_stats = {mid: named(st) for mid, (_, st) in metadata.items()}
    lines = []
    for lname, ts, raw_events in raw_lines:
        events = [TraceEvent(
            name=metadata.get(mid, (f"metadata:{mid}",))[0],
            start_ns=ts + off_ps / 1e3, duration_ns=dur_ps / 1e3,
            stats=named(stats), meta=meta_stats.get(mid, {}))
            for mid, off_ps, dur_ps, stats in raw_events]
        lines.append(TraceLine(name=lname, timestamp_ns=ts, events=events))
    return TracePlane(name=name, lines=lines)


def parse_xspace(data: bytes) -> List[TracePlane]:
    """Parse one serialized XSpace into its planes."""
    return [_parse_plane(val) for field, wire, val in _fields(data)
            if field == 1 and wire == 2]


# --- encoder (synthetic traces for tests and offline fixtures) -------------

def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _field(num: int, wire: int, payload: bytes) -> bytes:
    return _varint(num << 3 | wire) + payload


def _msg(num: int, payload: bytes) -> bytes:
    return _field(num, 2, _varint(len(payload)) + payload)


def _encode_stat(mid: int, value: Any) -> bytes:
    head = _field(1, 0, _varint(mid))
    if isinstance(value, float):
        return head + _field(2, 1, struct.pack("<d", value))
    if isinstance(value, (bool, int)):
        return head + _field(4, 0, _varint(int(value) & ((1 << 64) - 1)))
    if isinstance(value, bytes):
        return head + _msg(6, value)
    return head + _msg(5, str(value).encode())


def encode_xspace(planes: List[TracePlane]) -> bytes:
    """Serialize planes back to XSpace wire format (inverse of
    :func:`parse_xspace`, same field subset). Lets tests and fixtures
    fabricate device planes without a real TPU capture. Events that share
    a name share a metadata entry, so they must share their ``meta``."""
    out = bytearray()
    for plane in planes:
        names, stat_ids = {}, {}
        for line in plane.lines:
            for ev in line.events:
                mid = names.setdefault(ev.name, (len(names) + 1, ev.meta))
                if mid[1] != ev.meta:
                    raise ValueError(
                        f"events named {ev.name!r} differ in meta")
                for key in list(ev.stats) + list(ev.meta):
                    stat_ids.setdefault(key, len(stat_ids) + 1)

        def stats(num, d):
            return b"".join(_msg(num, _encode_stat(stat_ids[k], v))
                            for k, v in d.items())

        pbuf = bytearray(_msg(2, plane.name.encode()))
        for line in plane.lines:
            lbuf = bytearray(_msg(2, line.name.encode()))
            lbuf += _field(3, 0, _varint(line.timestamp_ns))
            for ev in line.events:
                ebuf = (_field(1, 0, _varint(names[ev.name][0]))
                        + _field(2, 0, _varint(
                            int((ev.start_ns - line.timestamp_ns) * 1e3)))
                        + _field(3, 0, _varint(int(ev.duration_ns * 1e3)))
                        + stats(4, ev.stats))
                lbuf += _msg(4, bytes(ebuf))
            pbuf += _msg(3, bytes(lbuf))
        for name, (mid, meta) in names.items():
            body = (_field(1, 0, _varint(mid)) + _msg(2, name.encode())
                    + stats(5, meta))
            pbuf += _msg(4, _field(1, 0, _varint(mid)) + _msg(2, body))
        for key, sid in stat_ids.items():
            body = _field(1, 0, _varint(sid)) + _msg(2, key.encode())
            pbuf += _msg(5, _field(1, 0, _varint(sid)) + _msg(2, body))
        out += _msg(1, bytes(pbuf))
    return bytes(out)


def load_trace_planes(logdir: str) -> List[TracePlane]:
    """All planes from every ``*.xplane.pb`` under a ``profile_trace``
    capture directory (one file per host per session)."""
    planes: List[TracePlane] = []
    for root, _, files in os.walk(logdir):
        for fname in sorted(files):
            if fname.endswith(".xplane.pb"):
                with open(os.path.join(root, fname), "rb") as f:
                    planes.extend(parse_xspace(f.read()))
    return planes
