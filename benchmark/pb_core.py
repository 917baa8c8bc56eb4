"""What every run of the benchmark shares: finding a cell's files by the
names in ``BENCHMARK.json``, loading drivers, families and layer readers by
path, JAX's compile events, the table of peaks, the device's report, and the
comparison arithmetic that decides ``correct``."""

from __future__ import annotations

import importlib.util
import json
import os
import statistics
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

_loaded = {}


def load_by_path(rel: str):
    """Import ``benchmark/<rel>`` as a module of its own (names there may
    hold dots, and nothing has to be a package)."""
    path = os.path.join(HERE, rel)
    if path not in _loaded:
        if not os.path.isfile(path):
            raise FileNotFoundError(
                f"the benchmark names {rel!r}, and {path} is not there")
        name = "pb_" + rel.replace("/", "__").replace(".", "_")
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return _loaded[path]


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with its configuration and traffic."""

    def __init__(self, name: str, bench: dict = None):
        self.bench = bench or read_json(os.path.join(ROOT, "BENCHMARK.json"))
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                             f"there are {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.cfg = read_json(os.path.join(ROOT, self.config_entry["file"]))
        self.traffic = read_json(os.path.join(
            HERE, "traffic", self.entry["traffic"] + ".json"))
        # the cell's own file: each number compared and its limit
        self.limits = read_json(os.path.join(HERE, "limits", name + ".json"))

    def metrics(self, group: str):
        """The cell's metrics of ``end_to_end`` or ``per_layer``: those with
        no ``workloads`` key, and those that list this cell."""
        return [m for m in self.bench[group]
                if "workloads" not in m or self.name in m["workloads"]]

    @property
    def driver(self):
        return load_by_path(f"drivers/{self.traffic['kind']}.py")

    @property
    def family(self):
        return load_by_path(f"families/{self.cfg['family']}.py")


class CompileClock:
    """Sums JAX's own compile events (copied from ``chip_smoke.py``):
    seconds tracing and lowering, seconds in the backend compiler
    (persistent-cache retrieval included), cache hits and misses, and the
    wall time of each backend compile, so that one inside the measured
    window can be told from one in set-up."""

    def __init__(self):
        import jax
        self.trace_s = self.compile_s = 0.0
        self.cache_hits = self.cache_misses = 0
        self.compiles = []          # (perf_counter at its end, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
            self.compiles.append((time.perf_counter(), secs))
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.trace_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def compiles_between(self, t0: float, t1: float) -> int:
        return sum(1 for t, _ in self.compiles if t0 <= t <= t1)


def tpu_devices(cell, who: str = "benchmark"):
    """JAX's devices, with the compile cache set first; None, and a line on
    standard error, unless they are TPU chips and as many as ``cell`` asks
    for. The benchmark measures the chip and runs on nothing else."""
    from pipe_tpu.utils.platform import configure_compile_cache
    configure_compile_cache()
    import jax
    # cache every program, not only those that took a second to compile: a
    # program near that threshold is cached in one run and not in the next,
    # and set-up then differs by seconds between runs of the same code
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"{who}: workload {cell.name!r} needs {cell.chips} TPU "
              f"chip(s); JAX found {len(devices)} device(s) of platform "
              f"{devices[0].platform!r} ({devices[0].device_kind}). It "
              f"measures the chip and runs on nothing else.",
              file=sys.stderr)
        return None
    return devices


def peaks_for(device_kind: str) -> dict:
    table = read_json(os.path.join(HERE, "peaks.json"))
    if device_kind not in table or device_kind.startswith("_"):
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: add it to "
            f"benchmark/peaks.json with its source")
    return table[device_kind]


def memory_peak_bytes(devices) -> int:
    """The peak on the fullest of ``devices``, as the backend reports it.
    The CPU backend of the test rehearsals reports none: 0 there, which no
    real run can print (the command refuses to start without a TPU)."""
    peaks = []
    for d in devices:
        if d.platform == "cpu":
            return 0
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" not in stats:
            raise RuntimeError(f"{d} reports no peak_bytes_in_use")
        peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks)


# ---------------------------------------------------------------------------
# the comparison


class Checks:
    """The numbers compared, each beside its limit. ``correct`` is true when
    every number is within its limit and none is missing."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value, limit: float):
        ok = value is not None and value == value and value <= limit
        self.rows.append((name, None if value is None else float(value),
                          float(limit), bool(ok)))

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)

    def as_dict(self) -> dict:
        return {n: {"value": v, "limit": l} for n, v, l, _ in self.rows}

    def lines(self):
        return [f"check {n}: {v!r} (limit {l!r}) "
                f"{'ok' if ok else 'NOT WITHIN ITS LIMIT'}"
                for n, v, l, ok in self.rows]


def judge(values: dict, limits: dict) -> Checks:
    """``values`` (a side's numbers by name, as a readings row holds them)
    against the cell's ``limits``: every limit needs its number."""
    checks = Checks()
    for name, limit in limits.items():
        checks.add(name, values.get(name), limit)
    return checks


def worst_leaf_gap(program: dict, reference: dict, skip=None):
    """The worst leaf's gap between the program's norm and the reference's,
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. ``program`` and ``reference`` are ``{name: array of
    norms}``; ``skip`` is ``{name: bool array}`` of leaves left out. Returns
    ``(gap, "name[index]")``."""
    names = sorted(reference)
    flat = np.concatenate([np.ravel(reference[n]) for n in names])
    keep = np.concatenate([
        ~np.ravel(skip[n]) if skip is not None
        else np.ones(np.size(reference[n]), bool) for n in names])
    median = statistics.median(flat[keep].tolist())
    worst, where = 0.0, None
    for n in names:
        r = np.ravel(reference[n])
        p = np.ravel(program[n])
        if p.shape != r.shape:
            raise ValueError(f"leaf {n}: program has {p.shape} norms, the "
                             f"reference {r.shape}")
        gap = np.abs(p - r) / np.maximum(r, median)
        if skip is not None:
            gap = np.where(np.ravel(skip[n]), 0.0, gap)
        i = int(np.argmax(gap))
        if not np.isfinite(gap).all():
            return float("inf"), f"{n}[not finite]"
        if gap[i] >= worst:
            worst, where = float(gap[i]), f"{n}[{i}]"
    return worst, where


def near_zero_norm(ref_grad_norms: dict, share: float = 1e-3) -> float:
    """``share`` of the median leaf's gradient norm in the reference: a leaf
    under it has a gradient that is nought to rounding."""
    flat = np.concatenate([np.ravel(v) for v in ref_grad_norms.values()])
    return share * statistics.median(flat.tolist())


def near_zero_leaves(ref_grad_norms: dict) -> dict:
    """Leaves whose gradient in the reference is under ``near_zero_norm``:
    Adam moves them by round-off alone, so their change is not compared."""
    cut = near_zero_norm(ref_grad_norms)
    return {k: np.asarray(v) < cut for k, v in ref_grad_norms.items()}


def leaf_angles(program: dict, reference: dict, skip_below: float) -> dict:
    """``{leaf: |p/|p| - r/|r||}``, the angle (for small ones) between the
    program's array and the reference's, over the reference's leaves whose
    norm is at least ``skip_below``. A common factor on either side, such as
    the one a clip by global norm applies, does not enter."""
    out = {}
    for name in sorted(reference):
        r = np.asarray(reference[name], np.float64)
        nr = float(np.linalg.norm(r))
        if nr >= skip_below:
            p = np.asarray(program[name], np.float64)
            n_p = float(np.linalg.norm(p))
            out[name] = (float(np.linalg.norm(p / n_p - r / nr))
                         if n_p > 0 and np.isfinite(n_p) else float("inf"))
    return out
