"""Readings of a serve cell whose reference can plant faults in itself:

    python3 benchmark/tools/fault_readings.py --workload <name> --seeds 1,2,3 [--seconds s]

For each seed: a short window at the cell's own load (the ``serve_closed``
driver's), then the served tokens' widest gap below the plain reference's
best logit, the float8 control's, and the gap against the reference with each
of its ``FAULTS`` planted (``cfg["fault"]``), every side judged by the cell's
committed limits. Prints one JSON line per seed and, as ``tools/readings.py``
does, one line per side: ``correct`` has to read true on every seed for the
program and false for the control and every fault. Exit code 1 where it does
not. Needs the chip the cell asks for; appends to
``chiprun_out/readings.<workload>.jsonl``. The benchmark's own runs never call
this; ``tools/readings.py`` stays the tool for cells without planted faults
(its driver hook takes none for a serve cell)."""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import pb_core  # noqa: E402


def with_fault(cell, fault):
    """The cell with ``fault`` planted in its configuration (a shallow copy:
    the reference reads ``cfg["fault"]``)."""
    other = copy.copy(cell)
    other.cfg = dict(cell.cfg, fault=fault)
    return other


def readings(cell, seeds, seconds=8.0, log=print):
    drv = cell.driver
    faults = getattr(cell.family.reference, "FAULTS", ())
    out = []
    for seed in seeds:
        w = drv.serve_window(cell, seed, seconds)
        done, sent = w["done"], w["loop"].sent
        del w
        gc.collect()
        sample = drv.sample_finished(done, sent, seed,
                                     cell.traffic["check_requests"])
        gaps = drv.reference_gaps(cell, seed, sample, sent, precision="fp8")
        row = dict(gaps, seed=seed, finished=len(done))
        sides = {"program": gaps["served_logit_gap"],
                 "control_fp8": gaps["control_logit_gap"]}
        for fault in faults:
            got = drv.reference_gaps(with_fault(cell, fault), seed, sample,
                                     sent)
            row[f"fault_{fault}_gap"] = got["served_logit_gap"]
            sides[f"fault_{fault}"] = got["served_logit_gap"]
        for side, gap in sides.items():
            checks = pb_core.Checks()
            drv.compare(checks, done, sent,
                        gap if row["requests"] else None, cell.limits)
            row[side + "_correct"] = checks.correct
        out.append(row)
        log(json.dumps(row))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    cell = pb_core.Cell(args.workload)
    if cell.traffic["kind"] != "serve_closed":
        raise SystemExit("fault_readings reads serve_closed cells")
    if pb_core.tpu_devices(cell, who="fault_readings") is None:
        return 3
    rows = readings(cell, [int(s) for s in args.seeds.split(",")],
                    seconds=args.seconds,
                    log=lambda line: print(line, flush=True))
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"readings.{cell.name}.jsonl"), "a") as f:
        for row in rows:
            f.write(json.dumps(row) + "\n")
    verdicts = pb_core.load_by_path("tools/readings.py").verdicts
    return 0 if verdicts(rows) else 1


if __name__ == "__main__":
    sys.exit(main())
