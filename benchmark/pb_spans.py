"""From a profiler capture to the program's own spans and scopes.

``pb_trace`` reads what any program leaves in a capture (device busy time,
operations, programs, the harness's spans). This file reads what *this*
program puts there (``pipe_tpu/obs/events.py``): its host spans
(``serve.*``, ``train.*``, ``step``) with their stats, and for every device
operation the ``jax.named_scope`` it was traced under.

Where a capture says that (looked at by hand on a v5e capture, PR 25): a
host span is an event of a ``/host:CPU`` line whose own stats are the
span's attributes. A device operation is an event of the line ``XLA Ops``
of ``/device:TPU:<n>``; the event carries only times, but its *metadata*
(one entry per instruction, shared by all its runs) has the stats
``tf_op`` (the instruction's ``op_name``:
``jit(step)/jvp(attention)/.../dot_general:``), ``program_id`` and the
instruction's name and HLO line. ``jax.profiler.ProfileData`` does not
show a metadata's stats, so the file is read here in its wire format
(XSpace, ``tsl/profiler/protobuf/xplane.proto``), with nothing but the
standard library. ``XLA Modules`` has one event per run of a program,
named ``<module>(<program_id>)``.

The capture is parsed once a run: ``read(facts)`` memoises by path. It
returns None where there is no capture; each reader under ``layers/`` then
returns None too. A program without these spans (a parent commit) gives
empty lists, and the readers return None."""

from __future__ import annotations

import gc
import os
import re
import struct

import pb_trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# pipe_tpu.obs.events.DEVICE_SCOPES and REMAT_SCOPE (a test holds the copy
# to the original; a parent commit has neither)
DEVICE_SCOPES = ("embed", "attention", "ffn", "head", "loss", "optimizer",
                 "kv_cache")
REMAT_MARKER = "rematted_computation"
PROGRAM_SPAN = re.compile(r"^(serve\.|train\.|step$)")

# a path component that is a scope's name, bare or inside the wrappers a
# transformation puts around the names open when it was applied
# (``jvp(head)``, ``transpose(jvp(attention))``, ``vmap(attention)``); a
# jitted function that happens to be called ``loss`` is not a scope
_WRAPPED = r"(?:(?!p?jit\()\w+\()*(%s)\)*"
_SCOPE = re.compile(r"(?:^|/)" + _WRAPPED % "|".join(DEVICE_SCOPES)
                    + r"(?=[/:]|$)")
_REMAT = re.compile(r"(?:^|/)((?:\w+\()*)" + REMAT_MARKER)
_WHILE = re.compile(r"^%?while[.\d\w]* ")
_COPY = re.compile(r"^copy(\.\w+)*$")
_MODULE = re.compile(r"^(.*)\((-?\d+)\)$")


def _u64(value):
    """A program id as the unsigned number it is, however it was kept."""
    return None if value is None else int(value) & ((1 << 64) - 1)


def scope_of(op_name: str):
    """The innermost ``DEVICE_SCOPES`` name on an operation's path."""
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else None


def is_remat(op_name: str) -> bool:
    """Whether the operation is a forward that runs again for its backward:
    its path has the marker, and not inside a ``transpose(`` (the backward
    of a manually recomputed forward carries ``transpose(jvp(marker))``;
    ``jax.checkpoint`` writes the bare marker under its own transpose)."""
    m = _REMAT.search(op_name or "")
    return bool(m) and "transpose(" not in m.group(1)


# ---------------------------------------------------------------------------
# the wire format: just what is read


def _varint(buf, pos):
    result = shift = 0
    while True:
        b = buf[pos]
        pos += 1
        result |= (b & 0x7F) << shift
        if not b & 0x80:
            return result, pos
        shift += 7


def _fields(buf):
    """``(field, wire type, value)``: varints as ints, the rest as bytes."""
    pos, n = 0, len(buf)
    while pos < n:
        tag, pos = _varint(buf, pos)
        field, wire = tag >> 3, tag & 7
        if wire == 0:
            val, pos = _varint(buf, pos)
        elif wire == 2:
            ln, pos = _varint(buf, pos)
            val = buf[pos:pos + ln]
            pos += ln
        elif wire == 1:
            val = buf[pos:pos + 8]
            pos += 8
        elif wire == 5:
            val = buf[pos:pos + 4]
            pos += 4
        else:
            raise ValueError(f"wire type {wire} at byte {pos}")
        yield field, wire, val


def _stat(buf):
    """``(stat metadata id, value)``; a reference comes as ``("ref", id)``."""
    mid, value = 0, None
    for f, w, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            value = struct.unpack("<d", v)[0]
        elif f == 3:
            value = v
        elif f == 4:
            value = v - (1 << 64) if v >= 1 << 63 else v
        elif f == 5:
            value = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            value = ("ref", v)
    return mid, value


def _map_entry(buf):
    """The value (field 2) of one map entry."""
    for f, w, v in _fields(buf):
        if f == 2 and w == 2:
            return v
    return b""


class _Plane:
    """One XPlane: its name, its stat names, its event metadata
    ``{id: (name, display name, {stat: value})}`` and its lines
    ``{name: [(start_ns, -end_ns, metadata id, raw stats)]}``: of a
    chip's plane the lines ``XLA Ops`` and ``XLA Modules``, of a host's
    plane every line, of any other plane none."""

    def __init__(self, buf):
        self.name = ""
        stat_names, raw_meta, raw_lines = {}, [], []
        for f, w, v in _fields(buf):
            if f == 2 and w == 2:
                self.name = bytes(v).decode("utf-8", "replace")
            elif f == 3 and w == 2:
                raw_lines.append(v)
            elif f == 4 and w == 2:
                raw_meta.append(_map_entry(v))
            elif f == 5 and w == 2:
                sid, sname = 0, ""
                for f2, w2, v2 in _fields(_map_entry(v)):
                    if f2 == 1:
                        sid = v2
                    elif f2 == 2:
                        sname = bytes(v2).decode("utf-8", "replace")
                stat_names[sid] = sname
        self.stat_names = stat_names
        self.metadata = {}
        for buf2 in raw_meta:
            mid, name, display, stats = 0, "", "", []
            for f, w, v in _fields(buf2):
                if f == 1:
                    mid = v
                elif f == 2:
                    name = bytes(v).decode("utf-8", "replace")
                elif f == 4:
                    display = bytes(v).decode("utf-8", "replace")
                elif f == 5:
                    stats.append(_stat(v))
            self.metadata[mid] = (name, display, self.named(stats))
        self.lines = {}
        on_chip = bool(pb_trace.DEVICE_PLANE.match(self.name))
        if not on_chip and not self.name.startswith("/host:"):
            return
        for buf2 in raw_lines:
            # only a host span's stats are read; a device event's are its
            # times once more
            lname, events = _line(buf2, keep_stats=not on_chip)
            if not on_chip or lname in (pb_trace.OPS_LINE,
                                        pb_trace.MODULES_LINE):
                self.lines.setdefault(lname, []).extend(events)

    def named(self, stats):
        names = self.stat_names
        return {names.get(mid, f"stat:{mid}"):
                (names.get(v[1], "") if isinstance(v, tuple) else v)
                for mid, v in stats}


def _line(buf, keep_stats):
    """One XLine: ``(name, [(start_ns, -end_ns, metadata id, [raw stat,
    ...] or None)])``, a shape that sorts an operation before those it
    holds without a key function. A capture holds a million events, so the
    line and its events are read in place and not through ``_fields``;
    fields come in the order of their numbers (a line's timestamp before
    its events; an event's id, offset, duration, then stats), so a device
    event is left at its first stat."""
    name, t0, t0_at, raw = "", 0, 0, []
    pos, n = 0, len(buf)
    while pos < n:
        tag = buf[pos]
        pos += 1
        if tag >= 0x80:                 # a field over 15: none is read
            while buf[pos] >= 0x80:
                pos += 1
            pos += 1
        wire = tag & 7
        if wire == 0 or wire == 2:
            val = buf[pos]
            pos += 1
            if val >= 0x80:
                val &= 0x7F
                shift = 7
                while True:
                    b = buf[pos]
                    pos += 1
                    val |= (b & 0x7F) << shift
                    if b < 0x80:
                        break
                    shift += 7
            if wire == 0:
                if tag == 24:           # field 3: timestamp_ns
                    t0, t0_at = val, len(raw)
                continue
            end = pos + val
            if tag == 18:               # field 2: name
                name = bytes(buf[pos:end]).decode("utf-8", "replace")
            elif tag == 34:             # field 4: an event
                mid = off = dur = 0
                stats = None
                while pos < end:
                    etag = buf[pos]
                    pos += 1
                    ewire = etag & 7
                    if ewire == 0 or ewire == 2:
                        v = buf[pos]
                        pos += 1
                        if v >= 0x80:
                            v &= 0x7F
                            shift = 7
                            while True:
                                b = buf[pos]
                                pos += 1
                                v |= (b & 0x7F) << shift
                                if b < 0x80:
                                    break
                                shift += 7
                        if ewire == 0:
                            if etag == 8:       # field 1: metadata id
                                mid = v
                            elif etag == 16:    # field 2: offset, ps
                                off = v
                            elif etag == 24:    # field 3: duration, ps
                                dur = v
                        elif etag == 34:        # field 4: a stat
                            if not keep_stats:
                                break
                            if stats is None:
                                stats = []
                            stats.append(buf[pos:pos + v])
                            pos += v
                        else:
                            pos += v
                    elif ewire == 1:
                        pos += 8
                    elif ewire == 5:
                        pos += 4
                    else:
                        raise ValueError(f"wire type {ewire} in an event")
                start = t0 + off / 1e3
                raw.append((start, -(start + dur / 1e3), mid, stats))
            pos = end
        elif wire == 1:
            pos += 8
        elif wire == 5:
            pos += 4
        else:
            raise ValueError(f"wire type {wire} in a line")
    if t0_at:                           # a timestamp after some events
        raw[:t0_at] = [(s + t0, e - t0, mid, stats)
                       for s, e, mid, stats in raw[:t0_at]]
    return name, raw


# ---------------------------------------------------------------------------
# the reduction


class Span:
    """A host span of the program: ``name``, ``start``/``end`` in ns on the
    capture's clock, ``stats`` (the span's attributes)."""

    __slots__ = ("name", "start", "end", "stats")

    def __init__(self, name, start, end, stats):
        self.name, self.start, self.end, self.stats = name, start, end, stats


class Op:
    """A device instruction's runs inside the window, those a ``while``
    holds apart from those none does: ``name`` (the instruction's), ``hlo``
    (name and result shape), ``text`` (its whole HLO line, operands and
    all), ``op_name`` (its ``tf_op``), ``runs``,
    ``self_ns`` (their time less that of the operations they hold: a
    ``while`` holds its body's), ``scope`` (innermost ``DEVICE_SCOPES``
    name or None), ``remat``, ``in_while``, ``is_while``, ``program`` (the
    id in the ``XLA Modules`` names). A capture holds a million runs of a
    few thousand instructions, so the runs are summed as they are read."""

    __slots__ = ("name", "hlo", "text", "op_name", "scope", "remat",
                 "is_while", "program", "in_while", "self_ns", "runs")


class Capture:
    """``window`` (ns); ``spans`` ``{name: [Span]}`` inside the window, in
    time order; ``ops`` (first chip, by instruction); ``busy`` (merged
    ``[start, end]`` of that chip's operations); ``modules`` ``[(name, program id,
    start, end)]`` clipped; ``chips``; ``scoped`` (whether any operation
    carries a ``DEVICE_SCOPES`` name: false for executables compiled before
    the scopes existed, whose whole step would else read as unscoped)."""

    def __init__(self, planes):
        host, devices = [], {}
        for p in planes:
            m = pb_trace.DEVICE_PLANE.match(p.name)
            if m and pb_trace.OPS_LINE in p.lines:
                devices[int(m.group(1))] = p
            elif not m:
                host.append(p)
        self.window = None
        raw_spans = []
        for p in host:
            for events in p.lines.values():
                for start, neg_end, mid, stats in events:
                    end = -neg_end
                    name = p.metadata.get(mid, ("",))[0]
                    if name == pb_trace.WINDOW_SPAN:
                        self.window = (start, end)
                    elif PROGRAM_SPAN.match(name):
                        raw_spans.append((p, name, start, end, stats))
        if self.window is None:
            raise ValueError(
                f"no host span {pb_trace.WINDOW_SPAN!r} in the capture")
        lo, hi = self.window
        self.spans = {}
        for p, name, start, end, stats in sorted(
                raw_spans, key=lambda r: r[2]):
            if end <= lo or start >= hi:
                continue
            named = p.named(_stat(s) for s in stats) if stats else {}
            self.spans.setdefault(name, []).append(
                Span(name, max(start, lo), min(end, hi), named))
        self.chips = len(devices)
        self.ops, self.modules, self.busy = [], [], []
        if devices:
            first = devices[min(devices)]
            self._read_device(first, lo, hi)
        self.scoped = any(op.scope for op in self.ops)

    def _read_device(self, plane, lo, hi):
        def describe(mid, in_while):
            name, display, stats = plane.metadata.get(mid, ("", "", {}))
            op = Op()
            op.text = name
            op.hlo = pb_trace.short_name(name)
            op.name = display or op.hlo.split(" ")[0]
            op.op_name = str(stats.get("tf_op", ""))
            op.scope, op.remat = scope_of(op.op_name), is_remat(op.op_name)
            op.is_while = bool(_WHILE.match(name))
            op.program = _u64(stats.get("program_id"))
            op.in_while, op.self_ns, op.runs = in_while, 0.0, 0
            return op

        events = plane.lines[pb_trace.OPS_LINE]
        events.sort()              # by start; the longer first at a tie
        rows = {}                  # (metadata id, in a while) -> Op
        stack = []                 # open operations: (end, Op)
        busy, at = self.busy, None
        for start, neg_end, mid, _ in events:
            end = -neg_end
            if start < lo:
                start = lo
            if end > hi:
                end = hi
            if end <= start:
                continue
            while stack and stack[-1][0] <= start:
                stack.pop()
            if stack:
                holder = stack[-1][1]
                holder.self_ns -= end - start
                key = (mid, holder.in_while or holder.is_while)
            else:
                key = (mid, False)
                if at is not None and start <= at[1]:
                    if end > at[1]:
                        at[1] = end
                else:
                    at = [start, end]
                    busy.append(at)
            op = rows.get(key)
            if op is None:
                op = rows[key] = describe(*key)
            op.self_ns += end - start
            op.runs += 1
            stack.append((end, op))
        self.ops = list(rows.values())
        for start, neg_end, mid, _ in sorted(
                plane.lines.get(pb_trace.MODULES_LINE, ())):
            start, end = max(start, lo), min(-neg_end, hi)
            if end > start:
                name = plane.metadata.get(mid, ("",))[0]
                m = _MODULE.match(name)
                self.modules.append((name, _u64(m.group(2)) if m else None,
                                     start, end))

    # -- what the readers share --------------------------------------------

    def scope_ns(self, *scopes):
        """Device self-time of the operations whose scope is one of
        ``scopes`` (None: under no scope)."""
        return sum(op.self_ns for op in self.ops if op.scope in scopes)

    def runs(self, pattern):
        """``[(program id, start, end)]`` of the runs, in time order, of
        the programs whose name matches."""
        rx = re.compile(pattern)
        return [(pid, start, end) for name, pid, start, end in self.modules
                if rx.search(name)]

    def programs(self, pattern):
        """``({program id}, runs, ns)`` of the programs whose name
        matches."""
        runs = self.runs(pattern)
        return ({pid for pid, _, _ in runs}, len(runs),
                sum(end - start for _, start, end in runs))

    def gaps_by_span(self, within=None):
        """The first chip's idle time inside the window, in ns, by the
        innermost program span over each gap's middle (``pb_trace``'s
        rule); ``None`` keys what no program span covers. With ``within``,
        only the gaps whose middle a span of that name covers."""
        lo, hi = self.window
        gaps, at = [], lo
        for s, e in self.busy:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if hi > at:
            gaps.append((at, hi))
        spans = sorted((sp for group in self.spans.values() for sp in group
                        if sp.end > sp.start), key=lambda sp: sp.start)
        out, nxt, open_ = {}, 0, []
        for s, e in gaps:           # in time order: one sweep
            mid = (s + e) / 2
            while nxt < len(spans) and spans[nxt].start <= mid:
                open_.append(spans[nxt])
                nxt += 1
            open_ = [sp for sp in open_ if sp.end > mid]
            if within and not any(sp.name == within for sp in open_):
                continue
            name = (min(open_, key=lambda sp: sp.end - sp.start).name
                    if open_ else None)
            out[name] = out.get(name, 0.0) + (e - s)
        return out


_memo = {}


def trace_dir(facts) -> str:
    return facts.get("trace_dir") or os.path.join(ROOT, "benchmark_out",
                                                  "trace")


def read(facts):
    """The run's capture, parsed once; None where there is none."""
    files = pb_trace.xplane_files(trace_dir(facts))
    if not files:
        return None
    key = tuple((f, os.path.getmtime(f), os.path.getsize(f)) for f in files)
    if key not in _memo:
        _memo.clear()
        # a million small tuples: the collector would walk them over and
        # over for nothing (none is part of a cycle)
        collecting = gc.isenabled()
        gc.disable()
        try:
            planes = []
            for path in files:
                with open(path, "rb") as f:
                    data = memoryview(f.read())
                planes.extend(_Plane(v)
                              for fld, w, v in _fields(data)
                              if fld == 1 and w == 2)
            _memo[key] = Capture(planes)
        finally:
            if collecting:
                gc.enable()
    return _memo[key]


# ---------------------------------------------------------------------------
# shared by the readers under layers/

TRAIN_STEP = r"train_step"
DECODE_PROGRAMS = r"resident|decode"


def scope_ms_per_step(facts, *scopes, remat=False):
    """Device milliseconds a step under ``scopes`` (None: under none; with
    ``remat``, of the operations that carry the remat marker whatever
    their scope), over the step programs run in the window. None without
    a capture, without a step program, or where no operation carries any
    scope (executables older than the scopes)."""
    cap = read(facts)
    if cap is None or not cap.scoped:
        return None
    _, runs, _ = cap.programs(TRAIN_STEP)
    if not runs:
        return None
    if remat:
        ns = sum(op.self_ns for op in cap.ops if op.remat)
    else:
        ns = cap.scope_ns(*scopes)
    return ns / 1e6 / runs


def decode_share(facts, pick):
    """100 x the device self-time of the decode programs' operations that
    ``pick`` accepts over those programs' device time."""
    cap = read(facts)
    if cap is None or not cap.scoped:
        return None
    ids, runs, ns = cap.programs(DECODE_PROGRAMS)
    if not runs:
        return None
    part = sum(op.self_ns for op in cap.ops
               if op.program in ids and pick(op))
    return 100.0 * part / ns


def is_copy(op) -> bool:
    return bool(_COPY.match(op.name))


def decode_done(facts):
    """The ``serve.decode.done`` spans of the window, or None."""
    cap = read(facts)
    if cap is None:
        return None
    return cap.spans.get("serve.decode.done") or None
