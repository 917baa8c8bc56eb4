"""Medians and spreads of a set of runs, as the bounds' rule reads them:

    python3 benchmark/tools/spread.py chiprun_out/<tag>.*.out

For each metric of the runs' last lines: the median, and the spread (the
distance between the first and third quartile of
``statistics.quantiles(values, n=4)`` as a share of the median), also with
the run farthest from the median left out. Also whether every run was
correct, and the largest value of each number compared."""

from __future__ import annotations

import json
import statistics
import sys


def last_line(path):
    lines = [ln for ln in open(path).read().splitlines()
             if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def spread(values):
    q = statistics.quantiles(values, n=4)
    median = statistics.median(values)
    return (q[2] - q[0]) / median if median else 0.0


def trimmed(values):
    """The spread without the run farthest from the median, as the check
    reads a set for tightness."""
    median = statistics.median(values)
    far = max(values, key=lambda v: abs(v - median))
    rest = list(values)
    rest.remove(far)
    return spread(rest)


def main(paths):
    runs = [(p, last_line(p)) for p in paths]
    for p, r in runs:
        if r is None:
            print(f"{p}: no result line")
    runs = [r for _, r in runs if r is not None]
    print(f"{len(runs)} runs, correct: {[r['correct'] for r in runs]}, "
          f"failed: {[r['failed'] for r in runs]}")
    for name in runs[0]["metrics"]:
        v = [r["metrics"][name]["value"] for r in runs
             if name in r["metrics"]]
        s = (f"{100 * spread(v):.4f}% (without the farthest run "
             f"{100 * trimmed(v):.4f}%)" if len(v) > 3 else "-")
        print(f"  {name}: median {statistics.median(v):.6g}, spread {s}, "
              f"values {[float(f'{x:.6g}') for x in v]}")
    checks = {}
    for r in runs:
        for k, c in r["checks"].items():
            checks.setdefault(k, []).append((c["value"], c["limit"]))
    for k, v in checks.items():
        print(f"  check {k}: max {max(x for x, _ in v):.4g} "
              f"(limit {v[0][1]:.4g})")


if __name__ == "__main__":
    main(sys.argv[1:])
