"""The launch cycle of the serve engine, read from a traced stretch.

The engine accounts for the time from one decode launch's dispatch to the
next one's in four phases (``pipe_tpu/obs/events.py`` ``CYCLE_PHASES``):
``wait`` (the device runs, then the round count's trip: up to the end of
``serve.decode.wait``), ``fetch`` (the blocking reads: ``serve.decode.fetch``),
``turn`` (the rest of the host's work inside ``serve.tick`` spans) and
``caller`` (between two ``serve.tick`` spans). It keeps them as registry
timers ``serve.engine.cycle.<phase>_sec`` in every run and as spans under
the profiler. This file reads both, for the seven ``engine.idle_*``,
``engine.ttft_*`` and ``engine.traced_gap_ratio`` readers under ``layers/``:

* the **idle split by overlap**: every idle gap of the first chip inside the
  stretch is cut at the phases' bounds and each piece booked to its phase,
  so the four add up to the stretch's idle time (``pb_spans.gaps_by_span``
  gives a whole gap to the one span over its middle);
* the **phases' own lengths** a launch, from the spans;
* a request's **first token by stage**, from ``serve.first_token``;
* the **process's** mean of ``fetch + turn + caller`` a launch from the
  timers, stalled phases (``serve.engine.stall_sec{phase=}``, of which a
  set-up's compiles are most) left out, to hold the stretch's against.

A program without the spans (a parent commit) reads None everywhere. Run as
a script it prints the whole table for a cell, one that lists none of the
seven too::

    python3 benchmark/pb_cycle.py --workload <cell> [--seed N --seconds S]

with ``--seed`` after a traced run of the cell in this process (so the
timers are that run's), else from the capture the last ``--trace 1`` run
left (no timers, no ratio)."""

from __future__ import annotations

import bisect

import pb_spans

PHASES = ("wait", "fetch", "turn", "caller")
TICK, LAUNCH = "serve.tick", "serve.decode.launch"
WAIT, FETCH = "serve.decode.wait", "serve.decode.fetch"
DONE, FIRST = "serve.decode.done", "serve.first_token"


def _overlap(a, b) -> float:
    """Summed overlap of two lists of ``(start, end)``, each in time order
    and free of overlaps within itself."""
    total, j = 0.0, 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            total += min(e, b[k][1]) - max(s, b[k][0])
            k += 1
    return total


def _bounds(cap, name):
    return [(sp.start, sp.end) for sp in cap.spans.get(name, ())
            if sp.end > sp.start]


def idle_gaps(cap):
    """The first chip's idle ``(start, end)``s inside the stretch."""
    lo, hi = cap.window
    gaps, at = [], lo
    for s, e in cap.busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    return gaps


def idle_by_phase(facts):
    """``({phase: idle ns of the first chip}, launches)`` over the traced
    stretch, or None: no capture, no ``serve.decode.wait`` span (a program
    older than the phases), no launch."""
    cap = pb_spans.read(facts)
    if cap is None or not cap.spans.get(WAIT):
        return None
    done = cap.spans.get(DONE)
    if not done:
        return None
    gaps = idle_gaps(cap)
    in_tick = _overlap(gaps, _bounds(cap, TICK))
    wait = _overlap(gaps, _bounds(cap, WAIT))
    fetch = _overlap(gaps, _bounds(cap, FETCH))
    idle = sum(e - s for s, e in gaps)
    return ({"wait": wait, "fetch": fetch, "turn": in_tick - wait - fetch,
             "caller": idle - in_tick}, len(done))


def idle_ms_per_launch(facts, phase):
    split = idle_by_phase(facts)
    if split is None:
        return None
    idle, launches = split
    return idle[phase] / 1e6 / launches


def cycles(facts):
    """The stretch's whole launch cycles, from the spans: ``[{phase: ns}]``,
    one for each pair of consecutive dispatches (a dispatch is the end of a
    ``serve.decode.launch``) with its wait and fetch between them. None
    without the spans."""
    cap = pb_spans.read(facts)
    if cap is None or not cap.spans.get(WAIT):
        return None
    lo, hi = cap.window
    launches = [sp for sp in cap.spans.get(LAUNCH, ()) if sp.end < hi]
    waits, fetches = cap.spans[WAIT], cap.spans.get(FETCH, [])
    ticks = _bounds(cap, TICK)
    out, w, f = [], 0, 0
    for a, b in zip(launches, launches[1:]):
        while w < len(waits) and waits[w].start < a.end:
            w += 1
        while f < len(fetches) and fetches[f].start < a.end:
            f += 1
        if w == len(waits) or f == len(fetches) \
                or fetches[f].end > b.start:
            continue
        whole = b.end - a.end
        wait = waits[w].end - a.end
        fetch = fetches[f].end - fetches[f].start
        caller = whole - _overlap([(a.end, b.end)], ticks)
        out.append({"wait": wait, "fetch": fetch, "caller": caller,
                    "turn": whole - wait - fetch - caller})
    return out


def traced_gap_ns(facts):
    """The stretch's mean of ``fetch + turn + caller`` a launch, or None."""
    found = cycles(facts)
    if not found:
        return None
    return sum(c["fetch"] + c["turn"] + c["caller"]
               for c in found) / len(found)


def process_timers():
    """``{phase: (cycles, seconds)}`` of the engine's four timers and,
    under ``stalled``, ``{phase: (stalls, seconds)}``: the whole process's,
    set-up included. None where the program keeps none."""
    from pipe_tpu.obs.telemetry import get_registry, labelled
    reg = get_registry()
    if not reg.timer("serve.engine.cycle.wait_sec").count:
        return None
    out = {"stalled": {}}
    for phase in PHASES:
        timer = reg.timer(f"serve.engine.cycle.{phase}_sec")
        stalls = reg.timer(labelled("serve.engine.stall_sec", phase=phase))
        out[phase] = (timer.count, timer.total)
        out["stalled"][phase] = (stalls.count, stalls.total)
    return out


def process_gap_ns():
    """The process's mean of ``fetch + turn + caller`` a launch from the
    timers, the stalled phases and their cycles left out, or None."""
    timers = process_timers()
    if timers is None:
        return None
    host = ("fetch", "turn", "caller")
    n = timers["wait"][0] - sum(timers["stalled"][p][0] for p in host)
    if n <= 0:
        return None
    sec = sum(timers[p][1] - timers["stalled"][p][1] for p in host)
    return 1e9 * sec / n


def traced_gap_ratio(facts):
    traced, process = traced_gap_ns(facts), process_gap_ns()
    if traced is None or not process:
        return None
    return traced / process


def first_token_ms(facts, stage):
    """Mean ``stage`` (``queued_ms``, ``admit_ms``, ``launch_ms``,
    ``ttft_ms``) of the stretch's ``serve.first_token`` spans, or None."""
    cap = pb_spans.read(facts)
    firsts = cap.spans.get(FIRST) if cap is not None else None
    if not firsts:
        return None
    return sum(sp.stats[stage] for sp in firsts) / len(firsts)


def report(facts) -> dict:
    """The whole table of one traced stretch, as plain data."""
    cap = pb_spans.read(facts)
    if cap is None:
        return {}
    lo, hi = cap.window
    done = cap.spans.get(DONE, [])
    # a plain round's first token is its prefill's, read under its own span
    tokens = sum(sp.stats["emitted"] for sp in done) + len(
        cap.spans.get("serve.prefill.sync", ()))
    out = {"stretch_s": (hi - lo) / 1e9, "launches": len(done),
           "idle_share": 1 - sum(e - s for s, e in cap.busy) / (hi - lo),
           "stretch_tokens_per_s": 1e9 * tokens / (hi - lo)}
    split = idle_by_phase(facts)
    if split is not None:
        out["idle_ms_per_launch"] = {
            p: ns / 1e6 / split[1] for p, ns in split[0].items()}
        # of the idle under the waits, what lies at their ends: the device
        # done and the host not yet told (the rest lies at their starts:
        # the launch queued and the device not yet running)
        gaps = idle_gaps(cap)
        starts = [s for s, _ in gaps]
        tail = 0.0
        for sp in cap.spans[WAIT]:
            i = bisect.bisect_left(starts, sp.end) - 1
            if i >= 0 and sp.end <= gaps[i][1]:
                tail += sp.end - max(sp.start, gaps[i][0])
        out["idle_wait_tail_ms_per_launch"] = tail / 1e6 / split[1]
    found = cycles(facts)
    if found:
        out["phase_ms_per_launch"] = {
            p: sum(c[p] for c in found) / 1e6 / len(found) for p in PHASES}
        out["whole_cycles"] = len(found)
    if cap.spans.get(FIRST):
        out["first_token_ms"] = {
            s: first_token_ms(facts, s)
            for s in ("queued_ms", "admit_ms", "launch_ms", "ttft_ms")}
        out["first_token_max_ms"] = {
            s: max(sp.stats[s] for sp in cap.spans[FIRST])
            for s in ("queued_ms", "admit_ms", "launch_ms", "ttft_ms")}
        out["first_tokens"] = len(cap.spans[FIRST])
    timers = process_timers()
    if timers is not None:
        out["process_ms_per_launch"] = {
            p: 1e3 * timers[p][1] / timers[p][0] for p in PHASES}
        out["process_stalls"] = {p: list(v) for p, v in
                                 timers["stalled"].items() if v[0]}
        gap = process_gap_ns()
        out["process_gap_ms"] = gap and gap / 1e6
        out["traced_gap_ratio"] = traced_gap_ratio(facts)
    return out


def main(argv=None) -> int:
    import argparse
    import json
    import os
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float, default=45.0)
    args = ap.parse_args(argv)
    facts, line = {}, {}
    if args.seed is not None:
        import pb_core
        import run
        cell = pb_core.Cell(args.workload)
        devices = pb_core.tpu_devices(cell, who="pb_cycle")
        if devices is None:
            return 3
        out_dir = os.path.join(pb_spans.ROOT, "benchmark_out")
        line = run.run_cell(cell, seed=args.seed, seconds=args.seconds,
                            trace=1, devices=devices, out_dir=out_dir)
        side = os.path.join(
            out_dir, f"{cell.name}.seed{args.seed}.trace1.json")
        with open(side) as f:
            record = json.load(f)
        line.update(window_tokens_per_s=record["serve_tokens_per_s"],
                    setup_s=record["setup_s"])
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "cycle": report(facts), "run": line}), flush=True)
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
