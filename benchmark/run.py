"""The benchmark's one command.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

reads the cell from ``BENCHMARK.json``, its configuration from the file the
entry names, its traffic from ``benchmark/traffic/<traffic>.json``, the
window's driver from ``benchmark/drivers/<kind>.py`` by the traffic file's
``kind``, the model family from ``benchmark/families/<family>.py`` by the
configuration's ``family``, and each per-layer metric's reader from
``benchmark/layers/<metric>.py``. A new cell, configuration, traffic mix,
traffic kind, family or per-layer metric is a new file and a new entry.

It runs on the machine it is started on, needs a TPU with as many chips as
the cell asks for (else: exit code 3 and no result line), keeps JAX's compile
cache inside the checkout, and prints as its last line of standard output one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
with ``--trace 1`` also ``breakdown``, and last ``checks``, the numbers
compared beside their limits (also the last lines of standard error)."""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (ROOT, HERE):
    if p not in sys.path:
        sys.path.insert(0, p)

import pb_core  # noqa: E402
import pb_trace  # noqa: E402


class Context:
    """What a driver gets: the cell, the arguments, the devices, the compile
    clock, the checks, and where to write."""

    def __init__(self, cell, seed, seconds, trace, devices, clock, out_dir,
                 t_start):
        self.cell, self.seed, self.seconds = cell, int(seed), float(seconds)
        self.trace, self.devices, self.clock = bool(trace), devices, clock
        self.out_dir, self.t_start = out_dir, t_start
        self.trace_dir = os.path.join(out_dir, "trace")
        self.checks = pb_core.Checks()
        self.setup_s = None
        self.setup_compile = None
        self.t_window = None
        self.marks = []            # [phase of set-up, seconds since start]

    def mark(self, phase: str):
        """The driver names a phase of set-up as it ends (side file only)."""
        self.marks.append([phase,
                           round(time.perf_counter() - self.t_start, 3)])

    def setup_done(self):
        """The driver calls this as its last act before the window."""
        self.t_window = time.perf_counter()
        self.setup_s = self.t_window - self.t_start
        self.setup_compile = (self.clock.trace_s, self.clock.compile_s,
                              self.clock.cache_hits, self.clock.cache_misses)

    def side_file(self, record: dict):
        """One run's record beside the result, never in it."""
        os.makedirs(self.out_dir, exist_ok=True)
        name = (f"{self.cell.name}.seed{self.seed}."
                f"trace{int(self.trace)}.json")
        record = dict(record, workload=self.cell.name, seed=self.seed,
                      seconds=self.seconds, setup_s=self.setup_s,
                      setup_marks=self.marks)
        with open(os.path.join(self.out_dir, name), "w") as f:
            json.dump(record, f)
            f.write("\n")


def fmt4(x: float) -> float:
    """Four significant digits, so that a small share does not read 0."""
    return float(f"{x:.4g}")


def run_cell(cell, *, seed, seconds, trace, devices, out_dir,
             t_start=None, marks=()) -> dict:
    """Everything of a run after the look for a chip: the driver's window,
    the metrics, the result line's object. ``marks`` are the phases of
    set-up that ended before this call."""
    clock = pb_core.CompileClock()
    ctx = Context(cell, seed, seconds, trace, devices, clock, out_dir,
                  T_START if t_start is None else t_start)
    ctx.marks.extend(marks)
    out = cell.driver.run(ctx)
    if ctx.setup_s is None:
        raise RuntimeError("the driver never called setup_done()")
    facts = out["facts"]
    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": cell.chips,
              "memory_peak_bytes": out["memory_peak_bytes"]}
    # a driver whose traced stretch follows the window says where it ended
    t_end = facts.get("t_end", ctx.t_window + facts["window_s"])
    facts.update(
        cell=cell, cfg=cell.cfg, traffic=cell.traffic, chips=cell.chips,
        device_kind=dev0.device_kind, platform=dev0.platform,
        memory_peak_bytes=out["memory_peak_bytes"], setup_s=ctx.setup_s,
        setup_trace_s=ctx.setup_compile[0],
        setup_compile_s=ctx.setup_compile[1],
        window_compiles=clock.compiles_between(ctx.t_window, t_end))
    result = {"correct": ctx.checks.correct,
              "attempted": int(out["attempted"]),
              "failed": int(out["failed"])}
    metrics = {}
    if not trace:
        values = dict(out["end_to_end"], setup_s=ctx.setup_s)
        for m in cell.metrics("end_to_end"):
            if m["name"] not in values:
                raise RuntimeError(
                    f"driver {cell.traffic['kind']!r} gave no "
                    f"{m['name']!r} for workload {cell.name!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        facts["peaks"] = pb_core.peaks_for(dev0.device_kind)
        summary = pb_trace.summarize(ctx.trace_dir, facts["spans"])
        facts["trace"] = summary
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        for m in cell.metrics("per_layer"):
            value = pb_core.load_by_path(
                f"layers/{m['name']}.py").read(facts)
            if value is not None:
                shown = fmt4(value) if m["unit"] == "%" else value
                metrics[m["name"]] = {"value": shown, "unit": m["unit"]}
        result["breakdown"] = summary.breakdown()
    result["metrics"] = metrics
    result["device"] = device
    if facts.get("not_compared"):
        result["not_compared"] = facts["not_compared"]
    result["checks"] = ctx.checks.as_dict()
    for line in ctx.checks.lines():
        print(line, file=sys.stderr)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = pb_core.Cell(args.workload)

    # the process's start is what varies most in a warm set-up: the side
    # file says how much of it is Python's imports and how much the chip's
    import jax  # noqa: F401
    import pipe_tpu.utils.platform  # noqa: F401
    marks = [["python_imports", round(time.perf_counter() - T_START, 3)]]
    devices = pb_core.tpu_devices(cell)
    if devices is None:
        return 3
    marks.append(["chip_start", round(time.perf_counter() - T_START, 3)])
    out_dir = os.path.join(ROOT, "benchmark_out")
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=args.trace, devices=devices, out_dir=out_dir,
                      marks=marks)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
