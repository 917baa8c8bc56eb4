"""Where the time goes, from one capture: what PERF.md's section 5 is
written from.

    python3 benchmark/tools/span_report.py [<trace dir>] [--top N]
                                           [--workload CELL]

reads the capture a ``--trace 1`` run left (``benchmark_out/trace`` unless a
directory is given) through ``pb_spans`` and prints one JSON object: with
``--workload``, under ``metrics``, what the cell's readers of
``layers/proposed_per_layer.json`` read there (the per-layer metrics of the
program's spans and scopes, which ``BENCHMARK.json`` does not list yet; a
reader that finds nothing is left out, and so is one of a counter, which
only the run's own process can read); the device's time by scope (and how much of each carries the remat marker), the
longest operations under no scope with their ``op_name`` (and, for the
compiler's own asynchronous copies, whose scope waits for them), the ``copy``
instructions by whether a ``while`` holds them, the programs, the program's
host spans (count and seconds by name), the device's idle gaps by the
innermost program span, for an engine what it produced inside the window,
and for a trainer the steps in flight at each step
program's start with the host span open when the depth was at its lowest."""

from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

import pb_core  # noqa: E402
import pb_spans  # noqa: E402

PROPOSED = os.path.join(BENCH, "layers", "proposed_per_layer.json")


def _ms(ns: float) -> float:
    return round(ns / 1e6, 4)


def by_scope(cap) -> dict:
    out = {}
    for op in cap.ops:
        row = out.setdefault(op.scope or "unscoped",
                             {"ms": 0.0, "remat_ms": 0.0, "runs": 0})
        row["ms"] += op.self_ns / 1e6
        row["runs"] += op.runs
        if op.remat:
            row["remat_ms"] += op.self_ns / 1e6
    return {k: {kk: round(v, 4) for kk, v in row.items()}
            for k, row in sorted(out.items(), key=lambda kv: -kv[1]["ms"])}


def longest(cap, top: int, pick) -> list:
    """The instructions ``pick`` accepts: self-time, runs, whether a
    ``while`` holds them, their program and ``op_name``."""
    rows = [{"op": op.hlo, "program": op.program,
             "ms": round(op.self_ns / 1e6, 4), "runs": op.runs,
             "in_while": op.in_while, "scope": op.scope,
             "op_name": op.op_name[-160:]}
            for op in cap.ops if pick(op)]
    return sorted(rows, key=lambda r: -r["ms"])[:top]


def by_kind(cap, top: int, pick) -> list:
    """The instructions ``pick`` accepts, summed by kind: the
    instruction's name without its numbers (``copy-done``,
    ``add_add_fusion``), its result's element type, and the last two
    components of its ``op_name``."""
    rows = {}
    for op in cap.ops:
        if pick(op):
            kind = re.sub(r"[.\d]+(clone)?", "", op.name)
            dtype = (op.hlo.split(" ")[1].split("[")[0]
                     if " " in op.hlo else "")
            tail = "/".join(op.op_name.rstrip(":").split("/")[-2:])
            row = rows.setdefault((kind, dtype, tail), {
                "kind": kind, "dtype": dtype, "op_name_tail": tail,
                "ms": 0.0, "runs": 0, "instructions": 0})
            row["ms"] += op.self_ns / 1e6
            row["runs"] += op.runs
            row["instructions"] += 1
    out = sorted(rows.values(), key=lambda r: -r["ms"])[:top]
    for row in out:
        row["ms"] = round(row["ms"], 4)
    return out


_OPERAND = re.compile(r"%([\w.\-]+)")
_WAIT = re.compile(r"^(copy|slice|all-gather|collective-permute)-done")


def waits_by_consumer(cap, hops: int = 4) -> dict:
    """The compiler's own asynchronous copies carry no ``op_name``, so no
    scope reaches them; their ``-done`` half on the ``XLA Ops`` line is the
    core's wait for the copy. Whose wait? Each unscoped ``-done``
    instruction's time goes to the scope of the first instruction of its
    program that names it as an operand (followed through at most ``hops``
    unscoped instructions), read from the HLO lines the capture holds.
    Returns ``{scope or "none found": ms}``."""
    by_program = {}
    for op in cap.ops:
        by_program.setdefault(op.program, []).append(op)
    out = {}
    for ops in by_program.values():
        users = {}
        for op in ops:
            _, _, operands = op.text.partition("=")
            for name in set(_OPERAND.findall(operands)):
                users.setdefault(name, []).append(op)

        def scope_of_use(op, left):
            for user in users.get(op.name, ()):
                if user is op:
                    continue
                if user.scope:
                    return user.scope
                if left:
                    found = scope_of_use(user, left - 1)
                    if found:
                        return found
            return None

        for op in ops:
            if op.scope is None and _WAIT.match(op.name):
                key = scope_of_use(op, hops) or "none found"
                out[key] = out.get(key, 0.0) + op.self_ns / 1e6
    return {k: round(v, 4) for k, v in sorted(out.items(),
                                              key=lambda kv: -kv[1])}


def spans(cap) -> dict:
    return {name: {"count": len(group),
                   "ms": _ms(sum(sp.end - sp.start for sp in group))}
            for name, group in sorted(cap.spans.items())}


def served(cap) -> dict:
    """What the engine produced inside the window, from its own spans:
    launches, steps, prefills, and tokens (a request's first token comes
    with its admission, the rest are ``emitted``) per second."""
    done = cap.spans.get("serve.decode.done", ())
    admits = cap.spans.get("serve.admit", ())
    if not done:
        return {}
    lo, hi = cap.window
    tokens = sum(sp.stats["emitted"] for sp in done) + len(admits)
    return {"launches": len(done), "admitted": len(admits),
            "steps": sum(sp.stats["steps"] for sp in done),
            "chunks": sum(sp.stats["chunks"] for sp in done),
            "early_exits": sum(sp.stats["early_exit"] for sp in done),
            "tokens": tokens, "tokens_per_s": tokens / ((hi - lo) / 1e9)}


def in_flight(cap) -> dict:
    """Steps handed over and not finished at each step program's start, and
    the program span open on the host when the depth was at its lowest."""
    dispatched = sorted(sp.end for sp in cap.spans.get("train.dispatch", ()))
    runs = cap.runs(pb_spans.TRAIN_STEP)
    if not dispatched or not runs:
        return {}
    ended = sorted(end for _, _, end in runs)
    depth = [bisect.bisect_right(dispatched, start)
             - bisect.bisect_right(ended, start) for _, start, _ in runs]
    low = min(depth)
    host = sorted((sp for group in cap.spans.values() for sp in group),
                  key=lambda sp: sp.start)
    at_low = {}
    for (_, start, _), d in zip(runs, depth):
        if d == low:
            cover = [sp for sp in host if sp.start <= start < sp.end]
            name = (min(cover, key=lambda sp: sp.end - sp.start).name
                    if cover else None)
            at_low[str(name)] = at_low.get(str(name), 0) + 1
    hist = {}
    for d in depth:
        hist[str(d)] = hist.get(str(d), 0) + 1
    return {"mean": sum(depth) / len(depth), "histogram": hist,
            "host_span_at_lowest": at_low}


def metrics(trace_dir: str, workload: str) -> dict:
    """The proposed per-layer metrics of ``workload`` on this capture."""
    facts = {"trace_dir": trace_dir,
             "traffic": pb_core.Cell(workload).traffic}
    out = {}
    for m in pb_core.read_json(PROPOSED):
        # a counter is its run's own: this process has none to read
        if workload in m["workloads"] and m["source"] != "program_counter":
            value = pb_core.load_by_path(
                f"layers/{m['name']}.py").read(facts)
            if value is not None:
                out[m["name"]] = value
    return out


def report(trace_dir: str, top: int, workload: str = None) -> dict:
    cap = pb_spans.read({"trace_dir": trace_dir})
    if cap is None:
        raise SystemExit(f"no capture under {trace_dir}")
    lo, hi = cap.window
    busy = sum(e - s for s, e in cap.busy)
    programs = {}
    for name, pid, start, end in cap.modules:
        row = programs.setdefault(name, {"runs": 0, "ms": 0.0})
        row["runs"] += 1
        row["ms"] += (end - start) / 1e6
    gaps = cap.gaps_by_span()
    return {
        **({"metrics": metrics(trace_dir, workload)} if workload else {}),
        "window_ms": _ms(hi - lo), "busy_ms": _ms(busy), "chips": cap.chips,
        "instructions": len(cap.ops),
        "op_runs": sum(op.runs for op in cap.ops), "scoped": cap.scoped,
        "by_scope": by_scope(cap),
        "remat_ms": _ms(sum(op.self_ns for op in cap.ops if op.remat)),
        "unscoped": longest(cap, top, lambda op: op.scope is None),
        "unscoped_by_kind": by_kind(cap, top, lambda op: op.scope is None),
        "unscoped_waits_by_consumer_scope": waits_by_consumer(cap),
        "copies": longest(cap, top, pb_spans.is_copy),
        "programs": {k: {"runs": v["runs"], "ms": round(v["ms"], 4)}
                     for k, v in sorted(programs.items(),
                                        key=lambda kv: -kv[1]["ms"])},
        "spans": spans(cap),
        "idle_gaps_ms": {str(k): _ms(v) for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])},
        "idle_in_serve_tick_ms": _ms(sum(
            cap.gaps_by_span(within="serve.tick").values())),
        "steps_in_flight": in_flight(cap),
        "served": served(cap),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_dir", nargs="?", default=pb_spans.trace_dir({}))
    ap.add_argument("--top", type=int, default=25)
    ap.add_argument("--workload")
    args = ap.parse_args(argv)
    print(json.dumps(report(args.trace_dir, args.top, args.workload)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
