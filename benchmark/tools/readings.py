"""Readings that limits are set from, many seeds in one process:

    python3 benchmark/tools/readings.py --workload <name> --seeds 1,2,3 [--seconds s]

prints one JSON line per seed with the program's numbers against the plain
reference, the control's (the reference in the nearest lower precision) and
each planted fault's, every side judged by the cell's committed limits
(``benchmark/limits/<workload>.json``), and then one line per side:
``correct`` has to read true on every seed for the program and false on
every seed for each other side. Exit code 1 where it does not. Needs the
chip the cell asks for; writes ``chiprun_out/readings.<workload>.jsonl`` as
well. The benchmark's own runs never call this."""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import pb_core  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", type=int, default=1)
    ap.add_argument("--faults", type=int, default=1)
    args = ap.parse_args(argv)
    cell = pb_core.Cell(args.workload)
    devices = pb_core.tpu_devices(cell, who="readings")
    if devices is None:
        return 3
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = cell.driver.readings(
        cell, seeds, devices, control=bool(args.control),
        faults=bool(args.faults), seconds=args.seconds)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"readings.{cell.name}.jsonl"), "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    return 0 if verdicts(rows) else 1


def verdicts(rows) -> bool:
    """One line per side with its ``correct`` on every seed; true when the
    program reads correct on all and every other side on none."""
    sides = {}
    for row in rows:
        for key, value in row.items():
            if isinstance(value, dict) and "correct" in value:
                sides.setdefault(key, []).append(value["correct"])
            elif key.endswith("_correct"):
                sides.setdefault(key[:-len("_correct")], []).append(value)
    good = bool(sides)
    for side, seen in sides.items():
        want = side == "program"
        good = good and all(c is want for c in seen)
        print(f"{side}: correct {seen} (has to be {want} on every seed)",
              flush=True)
    return good


if __name__ == "__main__":
    sys.exit(main())
