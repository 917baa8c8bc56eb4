"""Profiling and observability utilities.

Capability parity with the reference's two tracing mechanisms (SURVEY §5):

* kernel-level spans ``record_function("chunk%d-part%d")`` around every task
  (reference ``pipeline.py:205-210``, removed by the local edit but
  documented at ``README.md:263,408``) → :func:`~.events.stage_scope`
  emits ``jax.named_scope("chunk{i}-stage{j}")``, which survives into XLA
  HLO op names and Perfetto traces (the emulator and ``hetero.py`` wrap
  every task in it);
* driver-level ``torch.profiler`` with TensorBoard handler
  (``main.py:196-204``) → :func:`profile_trace` wraps ``jax.profiler``;
* CUDA memory-history snapshots (``main.py:263-271``) →
  :func:`device_memory_report` via ``jax.profiler.device_memory_profile``;
* the BASELINE.md north-star pipeline-bubble %% → :class:`BubbleMeter`
  (analytic model now; per-stage idle extraction from traces is the
  measured upgrade, SURVEY §7 hard part #4).
"""

from __future__ import annotations

import contextlib
import dataclasses
import re
from typing import Dict, List, Optional, Tuple

import jax

from ..core.schedule import Schedule, bubble_fraction
from .events import stage_scope
from .xplane import load_trace_planes

__all__ = ["stage_scope", "profile_trace", "device_memory_report",
           "BubbleMeter", "stage_busy_from_trace",
           "stage_timeline_from_trace", "measured_bubble_slope",
           "measured_bubble_two_point"]


@contextlib.contextmanager
def profile_trace(logdir: str, *, host_tracer_level: int = 2):
    """Capture a profiler trace viewable in TensorBoard/Perfetto/XProf.

    ``ProfileOptions`` is a recent jax addition; older releases take no
    options and trace at their default host level — same capture files.
    """
    try:
        options = jax.profiler.ProfileOptions()
        options.host_tracer_level = host_tracer_level
        jax.profiler.start_trace(logdir, profiler_options=options)
    except AttributeError:
        jax.profiler.start_trace(logdir)
    try:
        yield logdir
    finally:
        jax.profiler.stop_trace()


def device_memory_report(device: Optional[jax.Device] = None) -> str:
    """Human-readable live-buffer summary (pprof textproto under the hood)."""
    import gzip

    device = device or jax.devices()[0]
    raw = jax.profiler.device_memory_profile()
    try:
        raw = gzip.decompress(raw)
    except OSError:
        pass
    lines = [f"device memory profile ({device}):",
             f"  raw pprof bytes: {len(raw)}"]
    stats = getattr(device, "memory_stats", lambda: None)()
    if stats:
        for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit"):
            if k in stats:
                lines.append(f"  {k}: {stats[k] / 2**30:.3f} GiB")
    return "\n".join(lines)


@dataclasses.dataclass
class BubbleMeter:
    """Pipeline-bubble accounting for a (chunks m, stages n) configuration.

    ``analytic`` is the fill–drain model (n-1)/(m+n-1) (reference
    ``_clock_cycles`` cost model, ``pipeline.py:63-79``); ``measured`` can be
    filled from per-stage busy times (e.g. extracted from a profiler trace)
    to report the honest number next to the model.
    """

    chunks: int
    n_stages: int
    schedule: Optional[Schedule] = None

    @property
    def analytic(self) -> float:
        if self.schedule is not None:
            return self.schedule.bubble(self.chunks, self.n_stages)
        return bubble_fraction(self.chunks, self.n_stages)

    def measured(self, stage_busy_sec, wall_sec: float) -> float:
        """1 - busy/total from per-stage busy seconds and the step wall time."""
        total = self.n_stages * wall_sec
        busy = float(sum(stage_busy_sec))
        return max(0.0, 1.0 - busy / total) if total > 0 else 0.0

    def report(self) -> str:
        return (f"bubble[m={self.chunks}, n={self.n_stages}] "
                f"analytic={self.analytic:.2%}")


_OPS_LINE = "XLA Ops"     # one event per executed device operation


def _merge_intervals(events: List[Tuple[float, float]]
                     ) -> List[Tuple[float, float]]:
    """Union of [start, end) intervals (events overlap across lines)."""
    events = sorted(events)
    merged: List[Tuple[float, float]] = []
    for s, e in events:
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def _merge_busy_ns(events: List[Tuple[float, float]]) -> float:
    """Union length of [start, end) intervals."""
    return sum(e - s for s, e in _merge_intervals(events))


def stage_busy_from_trace(logdir: str) -> Dict[str, float]:
    """Per-device busy seconds from a :func:`profile_trace` capture.

    Parses the xplane protos (dependency-free, any jax version — see
    :mod:`.xplane`) and merges the op-event intervals of every
    ``/device:*`` plane — the trace-driven counterpart of the reference
    author's TensorBoard-trace verification
    (``/root/reference/README.md:559-567``). Where a plane has a line
    ``XLA Ops`` (a TPU's has, beside ``Steps`` and ``XLA Modules`` whose
    events span whole programs, idle gaps included) only that line is
    read; a plane without one is read whole. Returns ``{plane_name:
    busy_sec}`` plus a ``"_span"`` key holding the read events' wall span
    in seconds. Device planes exist for real accelerators
    (``/device:TPU:0`` ...); the virtual CPU platform reports only host
    threads, for which :func:`measured_bubble_slope` is the fallback.
    """
    busy: Dict[str, float] = {}
    lo, hi = float("inf"), 0.0
    for plane in load_trace_planes(logdir):
        if not plane.name.startswith("/device:"):
            continue
        events: List[Tuple[float, float]] = []
        ops = [ln for ln in plane.lines if ln.name == _OPS_LINE]
        for line in ops or plane.lines:
            for ev in line.events:
                events.append((ev.start_ns, ev.end_ns))
                lo, hi = min(lo, ev.start_ns), max(hi, ev.end_ns)
        if events:
            busy[plane.name] = busy.get(plane.name, 0.0) + \
                _merge_busy_ns(events) / 1e9
    busy["_span"] = (hi - lo) / 1e9 if hi > lo else 0.0
    return busy


_SCOPE_RE = re.compile(r"chunk(\d+)-stage(\d+)")


def stage_timeline_from_trace(logdir: str) -> Dict[str, object]:
    """Per-stage busy/idle attribution bucketed by the ``chunk{i}-stage{j}``
    named scopes (:func:`stage_scope` — they survive into XLA op names:
    the event's name on the CPU backend, its ``tf_op`` stat on a TPU).

    Extends :func:`stage_busy_from_trace` from per-plane to per-stage: every
    event whose name carries a scope tag is credited to that (stage,
    micro-batch) bucket, intervals unioned per bucket. Prefers ``/device:*``
    planes; when none exist (virtual CPU platform) it falls back to host
    planes carrying scope-tagged events, and reports which source it used so
    callers can label the numbers honestly.

    Returns::

        {"source": "device" | "host" | None,      # None: no tagged events
         "span": (lo_ns, hi_ns),                   # over tagged events
         "stages": {j: {"busy_sec": float,
                        "intervals": [(s_ns, e_ns), ...],   # merged
                        "chunks": {i: busy_sec}}}}
    """
    planes = load_trace_planes(logdir)
    for source, keep in (("device", lambda p: p.name.startswith("/device:")),
                         ("host", lambda p: True)):
        raw: Dict[int, List[Tuple[float, float]]] = {}
        per_chunk: Dict[int, Dict[int, float]] = {}
        lo, hi = float("inf"), 0.0
        for plane in planes:
            if not keep(plane):
                continue
            for line in plane.lines:
                for ev in line.events:
                    # a TPU op's name is its instruction's (fusion.106);
                    # the scope path is in its metadata's tf_op stat
                    m = _SCOPE_RE.search(ev.name) or _SCOPE_RE.search(
                        str(ev.meta.get("tf_op", "")))
                    if not m:
                        continue
                    chunk, stage = int(m.group(1)), int(m.group(2))
                    raw.setdefault(stage, []).append((ev.start_ns, ev.end_ns))
                    ch = per_chunk.setdefault(stage, {})
                    ch[chunk] = ch.get(chunk, 0.0) + ev.duration_ns / 1e9
                    lo, hi = min(lo, ev.start_ns), max(hi, ev.end_ns)
        if raw:
            stages = {}
            for stage, events in sorted(raw.items()):
                merged = _merge_intervals(events)
                stages[stage] = {
                    "busy_sec": sum(e - s for s, e in merged) / 1e9,
                    "intervals": merged,
                    "chunks": dict(sorted(per_chunk[stage].items())),
                }
            return {"source": source, "span": (lo, hi), "stages": stages}
    return {"source": None, "span": (0.0, 0.0), "stages": {}}


def measured_bubble_slope(t_m: float, t_2m: float, m: int) -> float:
    """Measured bubble from two step timings at ``m`` and ``2m`` micro-batches.

    With per-micro-batch work held constant, a clock-cycle pipeline costs
    ``t(m) = c + a*(m + n - 1)``; the slope ``a = (t(2m) - t(m)) / m`` is the
    real per-cycle cost (compute + ppermute + scan machinery, as executed).
    The measured bubble is the step-time fraction not spent on the ``m``
    useful cycles::

        bubble = 1 - m*a / t(m)

    which reduces to the analytic ``(n-1)/(m+n-1)`` when per-cycle cost
    dominates, and additionally exposes constant dispatch/gather overhead
    (at n=1 the analytic model says 0; this reports the honest residue).
    Timing-based, so it works on any platform — the trace-based
    :func:`stage_busy_from_trace` + :meth:`BubbleMeter.measured` pair is the
    per-stage-attributed alternative on real device planes.
    """
    return measured_bubble_two_point(t_m, m, t_2m, 2 * m)


def measured_bubble_two_point(t_ref: float, m_ref: int,
                              t_other: float, m_other: int) -> float:
    """:func:`measured_bubble_slope` generalized to any two micro-batch
    counts: the bubble is reported at the REFERENCE point ``(t_ref,
    m_ref)``; the other point only fixes the slope. Lets the probe use
    FEWER micro-batches than the headline run (e.g. m/2 vs m) when a 2m
    program would not fit — the straight-line d=1 specialization's HLO temp
    footprint grows with the unroll length, so probing downward keeps the
    slope measurable at the memory ceiling.

    Caveat: the premise is that step time is affine in the micro-batch
    count. Fixed per-step costs that do NOT scale with m (optimizer update,
    dispatch latency) bias the slope low and the bubble high, so prefer
    the trace-based busy fraction (:func:`stage_busy_from_trace`) whenever
    a real device plane is available."""
    if t_ref <= 0 or m_other == m_ref:
        return 0.0
    a = max((t_other - t_ref) / (m_other - m_ref), 0.0)
    return max(0.0, 1.0 - (m_ref * a) / t_ref)
