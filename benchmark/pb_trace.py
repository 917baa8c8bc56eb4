"""From a profiler trace to numbers: the device's busy and idle time, time
per device operation and per compiled program, and the idle gaps by what the
host was doing. Read with ``jax.profiler.ProfileData`` and nothing else.

What a trace of this system holds (looked at by hand on a v5e capture): one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event
per executed operation and whose line ``XLA Modules`` has one event per run
of a compiled program; and host planes whose lines are threads, holding the
``jax.profiler.TraceAnnotation`` spans the harness writes. All on one clock,
in nanoseconds."""

from __future__ import annotations

import contextlib
import glob
import os
import re
import shutil

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "traced_window"
# an operation that only holds others (its time is its body's, listed too)
CONTROL_FLOW = re.compile(r"^(while|conditional|call)[.\d]* ")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


@contextlib.contextmanager
def capture(logdir: str, on: bool = True):
    """Profile what runs inside, under the host span ``traced_window``;
    with ``on`` false, do nothing."""
    if not on:
        yield
        return
    import jax
    shutil.rmtree(logdir, ignore_errors=True)
    os.makedirs(logdir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0      # no span per Python call
    with jax.profiler.trace(logdir, profiler_options=options):
        with jax.profiler.TraceAnnotation(WINDOW_SPAN):
            yield


def span(name: str, on: bool = True):
    """A host span of the trace; with ``on`` false, nothing."""
    if not on:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


_HLO = re.compile(r"^%?([\w.\-]+) = \(?(\w+\[[\d,]*\])?")


def short_name(name: str) -> str:
    """A device operation's trace name is its whole HLO line; keep the
    instruction's name and its (first) result shape."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    return m.group(1) + (" " + m.group(2) if m.group(2) else "")


def union(intervals):
    """Merged ``[start, end)`` intervals, sorted."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _clip(s, e, lo, hi):
    return max(s, lo), min(e, hi)


class TraceSummary:
    """The reduction of one capture.

    ``window_s``; ``busy_s`` (mean over device planes of the union of the
    operations' intervals inside the window); ``device_busy`` per plane;
    ``op_seconds`` ``{operation: seconds}`` and ``module_seconds``
    ``{program: (runs, seconds)}``, each a mean over the chips;
    ``gap_seconds`` ``{host span: idle seconds of the first chip under
    it}``."""

    def __init__(self, planes, span_names):
        window = None
        host_spans = []           # (start, end, name)
        devices = {}
        for plane in planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                lines = {ln.name: ln for ln in plane.lines}
                if OPS_LINE not in lines:
                    raise ValueError(
                        f"plane {plane.name} has no line {OPS_LINE!r}; it "
                        f"has {sorted(lines)}")
                devices[int(m.group(1))] = lines
                continue
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                    elif ev.name in span_names:
                        host_spans.append(
                            (ev.start_ns, ev.start_ns + ev.duration_ns,
                             ev.name))
        if window is None:
            raise ValueError(f"no host span {WINDOW_SPAN!r} in the trace")
        if not devices:
            raise ValueError("no /device:TPU:<n> plane in the trace")
        lo, hi = window
        self.window_s = (hi - lo) / 1e9
        self.device_busy = {}
        self.op_seconds = {}
        self.module_seconds = {}
        n = len(devices)
        first_busy = None
        for idx in sorted(devices):
            lines = devices[idx]
            ivals = []
            for ev in lines[OPS_LINE].events:
                s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                             lo, hi)
                if e > s:
                    ivals.append((s, e))
                    op = short_name(ev.name)
                    if not CONTROL_FLOW.match(op):
                        self.op_seconds[op] = self.op_seconds.get(
                            op, 0.0) + (e - s) / 1e9 / n
            merged = union(ivals)
            if first_busy is None:
                first_busy = merged
            self.device_busy[idx] = sum(e - s for s, e in merged) / 1e9
            if MODULES_LINE in lines:
                for ev in lines[MODULES_LINE].events:
                    s, e = _clip(ev.start_ns, ev.start_ns + ev.duration_ns,
                                 lo, hi)
                    if e > s:
                        runs, secs = self.module_seconds.get(
                            ev.name, (0.0, 0.0))
                        self.module_seconds[ev.name] = (
                            runs + 1.0 / n, secs + (e - s) / 1e9 / n)
        if not any(self.device_busy.values()):
            raise ValueError("no operation ran on a device inside the "
                             "traced window")
        self.busy_s = sum(self.device_busy.values()) / n
        self.gap_seconds = self._gaps(first_busy, lo, hi, host_spans)

    @staticmethod
    def _gaps(busy, lo, hi, host_spans):
        """Idle seconds of the first chip, by the innermost harness span
        that covers each gap's middle (the window itself where none does)."""
        gaps, at = [], lo
        for s, e in busy:
            if s > at:
                gaps.append((at, s))
            at = max(at, e)
        if hi > at:
            gaps.append((at, hi))
        out = {}
        for s, e in gaps:
            mid = (s + e) / 2
            cover = [(he - hs, name) for hs, he, name in host_spans
                     if hs <= mid < he]
            name = min(cover)[1] if cover else WINDOW_SPAN
            out[name] = out.get(name, 0.0) + (e - s) / 1e9
        return out

    def module_time(self, pattern: str):
        """``(runs, seconds)`` of the programs whose name matches."""
        rx = re.compile(pattern)
        runs = secs = 0.0
        for name, (r, s) in self.module_seconds.items():
            if rx.search(name):
                runs, secs = runs + r, secs + s
        return runs, secs

    def breakdown(self, top: int = 10) -> dict:
        def head(d):
            return [[k, v] for k, v in sorted(
                d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": head(self.op_seconds),
                "idle_gaps": head(self.gap_seconds)}


def xplane_files(logdir: str):
    return sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))


def summarize(logdir: str, span_names) -> TraceSummary:
    from jax.profiler import ProfileData
    files = xplane_files(logdir)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    planes = []
    for path in files:
        planes.extend(ProfileData.from_file(path).planes)
    return TraceSummary(planes, set(span_names))

