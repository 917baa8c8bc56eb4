"""Traffic kind ``train``: a window of ``Trainer.train_epoch``.

Set-up builds one trainer with its state from the seed, drives it through
its first three steps by the window's own call (one ``train_epoch`` call to
a step, so each step's loss comes back in full), reads from the state what
the comparison needs, warms the window's call, and hands the same trainer
and state to the window: ONE ``train_epoch`` call of N steps that ends in
its own sync. Tokens and wall time of that whole call give the rate. After
the window the state is freed and the plain reference follows the same three
steps on the same weights and rows."""

from __future__ import annotations

import dataclasses
import gc
import json
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

import pb_core
import pb_trace
import pb_traffic

SPANS = ("train_epoch",)
CHECK_STEPS = 3


def _seed31(seed: int) -> int:
    return int(seed) & 0x7FFFFFFF


class _Sampler(threading.Thread):
    """Every ``period`` seconds: the wall clock and the trainer's own count
    of dispatched steps (``train.steps``). A stall shows as a flat stretch.
    It touches no device and takes the interpreter lock for microseconds."""

    def __init__(self, counter, period: float = 0.25):
        super().__init__(daemon=True)
        self.counter, self.period = counter, period
        self.samples = []
        self._halt = threading.Event()

    def run(self):
        while not self._halt.wait(self.period):
            self.samples.append((time.perf_counter(), self.counter.value))

    def stop(self):
        self._halt.set()
        self.join()


def _batch(source, b, seq):
    i = b * seq
    return (np.ascontiguousarray(source[i:i + seq].T),
            np.ascontiguousarray(source[i + 1:i + 1 + seq].T))


def _step_key(seed, b, impl):
    """The key of step ``b`` of epoch 0: the trainer's seed, the epoch and
    the global batch index folded in, in the key implementation the traffic
    file states for the platform."""
    key = jax.random.key(_seed31(seed), impl=impl)
    return jax.random.fold_in(jax.random.fold_in(key, 0), b)


def reference_readings(cell, seed, source, precision="f32", fault=None):
    """Losses of the first steps, the first gradient's norms as Adam gets
    it, and the norms of the parameters' change, by the plain reference."""
    cfg, tr = cell.cfg, cell.traffic
    t = tr["trainer"]
    ref = cell.family.reference
    impl = t["prng_impl"].get(jax.default_backend())
    weights = ref.make_weights(cfg, seed)
    opt = ref.init_opt(weights)
    losses, grad_norms = [], None
    for b in range(CHECK_STEPS):
        tokens, targets = _batch(source, b, tr["seq"])
        weights, opt, loss, clipped = ref.train_step(
            weights, opt, tokens, targets, _step_key(seed, b, impl), cfg,
            lr=t["lr"], clip=t["grad_clip"], chunks=t["chunks"],
            n_stages=t["n_stages"], precision=precision, fault=fault)
        losses.append(float(loss))
        if b == 0:
            grad_norms = ref.leaf_norms(clipped)
            grad_sample = {k: np.asarray(v) for k, v in
                           ref.grad_sample(clipped).items()}
        del clipped
    del opt
    start = ref.make_weights(cfg, seed)
    change = ref.leaf_norms(jax.tree_util.tree_map(jnp.subtract, weights,
                                                   start))
    del weights, start
    return {"loss": losses, "grad_norms": grad_norms,
            "grad_sample": grad_sample, "change_norms": change}


def compare(checks, program, reference, limits):
    """Each step's loss, the worst leaf's gradient norm, the median leaf's
    gradient angle and the worst leaf's change, each beside its limit from
    the cell's limits file. A step's ``loss_rel_step<n>`` that the file does
    not name has no upper reading (PERF.md) and is not compared; its gap is
    returned among the readings."""
    not_compared = {}
    for b, (lp, lr) in enumerate(zip(program["loss"], reference["loss"])):
        gap = abs(lp - lr) / abs(lr) if math.isfinite(lp) else math.inf
        name = f"loss_rel_step{b + 1}"
        if name in limits:
            checks.add(name, gap, limits[name])
        else:
            not_compared[name] = gap
    g, where = pb_core.worst_leaf_gap(program["grad_norms"],
                                      reference["grad_norms"])
    checks.add("grad_norm_gap", g, limits["grad_norm_gap"])
    angles = pb_core.leaf_angles(
        program["grad_sample"], reference["grad_sample"],
        skip_below=pb_core.near_zero_norm(reference["grad_norms"]))
    checks.add("grad_angle_median", float(np.median(list(angles.values()))),
               limits["grad_angle_median"])
    skip = pb_core.near_zero_leaves(reference["grad_norms"])
    c, where_c = pb_core.worst_leaf_gap(program["change_norms"],
                                        reference["change_norms"], skip)
    checks.add("change_norm_gap", c, limits["change_norm_gap"])
    return {"grad_norm_gap_leaf": where, "change_norm_gap_leaf": where_c,
            "grad_angles": angles, "not_compared": not_compared}


class Rig:
    """One trainer, and a state for any seed: the trainer's own state with
    its weights replaced by the benchmark's (made on the device from the
    seed in the trainer's layout and placed as the trainer placed its own).
    """

    def __init__(self, cell, devices):
        from pipe_tpu.train.loop import Trainer, TrainerConfig
        self.cell = cell
        tr = cell.traffic
        t = tr["trainer"]
        self.n_stages = t["n_stages"]
        tcfg = TrainerConfig(
            n_stages=self.n_stages, n_data=cell.chips // self.n_stages,
            schedule=t["schedule"], checkpoint=t["checkpoint"],
            batch_size=tr["batch"], bptt=tr["seq"], chunks=t["chunks"],
            lr=t["lr"], grad_clip=t["grad_clip"])
        self.trainer = Trainer(cell.family.model_config(cell.cfg), tcfg,
                               devices=devices[:cell.chips])
        self.shardings = None

    def placed_weights(self, seed):
        return jax.tree_util.tree_map(
            jax.device_put,
            self.cell.family.make_train_params(self.cell.cfg, seed,
                                               self.n_stages),
            self.shardings)

    def fresh_state(self, seed):
        tr = self.trainer
        # the seed of the trainer's dropout keys is this run's
        tr.cfg = dataclasses.replace(tr.cfg, seed=_seed31(seed))
        state = tr.init_state()
        if self.shardings is None:
            self.shardings = jax.tree_util.tree_map(
                lambda a: a.sharding, state.params)
        state = dataclasses.replace(state, params=None)
        return dataclasses.replace(state, params=self.placed_weights(seed))

    def first_steps(self, state, seed, source):
        """The first steps by the window's own call, one call to a step;
        returns the state and what the comparison reads of the program."""
        fam = self.cell.family
        program = {"loss": []}
        for b in range(CHECK_STEPS):
            state, info = self.trainer.train_epoch(
                source, state=state, max_steps=b + 1, start_step=b,
                log_every=0)
            program["loss"].append(info["loss"])
            if b == 0:
                # Adam's first moment after one step is (1 - b1) x the
                # gradient it was given
                mu, scale = state.opt_state[1].mu, 1.0 / (1.0 - 0.9)
                program["grad_norms"] = fam.train_leaf_norms(mu, scale=scale)
                program["grad_sample"] = fam.train_grad_sample(
                    mu, self.cell.cfg["n_layers"], scale=scale)
                del mu
        program["change_norms"] = fam.train_change_norms(
            state.params, self.placed_weights(seed))
        return state, program


def first_corpus(cell, seed):
    return pb_traffic.corpus(
        cell.traffic, seed,
        CHECK_STEPS + int(cell.traffic["trainer"]["warmup_steps"]))


def run(ctx) -> dict:
    from pipe_tpu.obs.telemetry import get_registry

    cell, seed = ctx.cell, ctx.seed
    tr = cell.traffic
    devices = ctx.devices[:cell.chips]
    ctx.mark("imports")
    rig = Rig(cell, devices)
    trainer = rig.trainer
    state = rig.fresh_state(seed)
    n_params = trainer.num_params(state)
    ctx.mark("trainer_and_state")
    warm = int(tr["trainer"]["warmup_steps"])
    first = first_corpus(cell, seed)
    state, program = rig.first_steps(state, seed, first)
    ctx.mark("first_steps")

    # the window's own call, warm: every program it needs is behind it
    state, info = trainer.train_epoch(
        first, state=state, max_steps=CHECK_STEPS + warm,
        start_step=CHECK_STEPS, log_every=0)
    step_s = info["sec_per_step"]
    seconds = ctx.seconds if not ctx.trace else min(
        ctx.seconds, tr["trace_seconds"])
    n_steps = max(2, int(seconds / step_s))
    ctx.mark("warm_call")
    source = pb_traffic.corpus(tr, seed + 1, n_steps)
    gc.collect()
    gc.freeze()

    steps_ctr = get_registry().counter("train.steps")
    sampler = _Sampler(steps_ctr)
    ctx.setup_done()
    sampler.start()
    with pb_trace.capture(ctx.trace_dir, on=ctx.trace), \
            pb_trace.span("train_epoch", on=ctx.trace):
        t0 = time.perf_counter()
        state, info = trainer.train_epoch(
            source, state=state, max_steps=n_steps, log_every=0)
        t1 = time.perf_counter()
    sampler.stop()
    gc.unfreeze()

    window_s = t1 - t0
    steps = int(info["steps"])
    tokens = steps * tr["batch"] * tr["seq"]
    finite = math.isfinite(info["loss"])
    peak = pb_core.memory_peak_bytes(devices)
    ctx.side_file({
        "window": [t0, t1], "steps": steps, "step_s_warm": step_s,
        "final_loss": info["loss"], "program_loss": program["loss"],
        "dispatched": [[round(ts - t0, 4), n - (steps_ctr.value - steps)]
                       for ts, n in sampler.samples],
        "compiles": [[round(ts - t0, 4), secs]
                     for ts, secs in ctx.clock.compiles]})

    del state, trainer, rig
    gc.collect()
    reference = reference_readings(cell, seed, first)
    where = compare(ctx.checks, program, reference, cell.limits)

    return {
        "attempted": steps, "failed": 0 if finite else steps,
        "end_to_end": {
            "train_tokens_per_s_per_chip": tokens / window_s / cell.chips},
        "memory_peak_bytes": peak,
        "facts": {
            "kind": "train", "window_s": window_s, "tokens": tokens,
            "steps": steps, "rows": tr["batch"], "n_params": n_params,
            "spans": SPANS,
            "not_compared": where["not_compared"]},
    }


def readings(cell, seeds, devices, control=True, faults=True, seconds=None):
    """For ``tools/readings.py``: in one process, for each seed, the numbers
    of the program, of the control (the reference at float8) and of the
    faults 'half of the batch left out' and 'state left unchanged' (planted
    in the reference), each compared with the float32 reference under the
    cell's committed limits: ``correct`` has to read true for the program
    and false for every other side. No window is measured (``seconds`` is
    for kinds whose readings need one)."""
    del seconds
    rig = Rig(cell, devices)
    out = []
    for seed in seeds:
        source = first_corpus(cell, seed)
        state = rig.fresh_state(seed)
        state, program = rig.first_steps(state, seed, source)
        del state
        gc.collect()
        reference = reference_readings(cell, seed, source)
        row = {"seed": seed}
        sides = {"program": program}
        if control:
            sides["control_fp8"] = reference_readings(cell, seed, source,
                                                      precision="fp8")
        if faults:
            for fault in ("half_batch", "frozen"):
                sides["fault_" + fault] = reference_readings(
                    cell, seed, source, fault=fault)
        for name, side in sides.items():
            checks = pb_core.Checks()
            where = compare(checks, side, reference, cell.limits)
            row[name] = {k: v["value"] for k, v in checks.as_dict().items()}
            row[name].update(where["not_compared"])
            row[name].update(where, correct=checks.correct)
            del row[name]["not_compared"]
        row["reference_loss"] = reference["loss"]
        out.append(row)
        print(json.dumps(row), flush=True)
    return out
