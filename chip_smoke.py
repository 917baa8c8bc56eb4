"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once through the entry points
a user would call, at the full width of the tutorial LM (``LMConfig()``:
vocab 28782, d_model 2048, 32 heads, d_ff 2048, 16 layers, seq 128; 520.9M
parameters, random weights from a seed), and checks what comes out:

* **kernel** — ``ops/pallas_attention.flash_attention`` compiled (never
  interpreted): forward and both backward kernels, bf16 and f32, head_dim 64,
  s = 256/512/1024, with and without in-kernel dropout, against the XLA
  reference (``tools/tpu_validate.validate_flash``). The tutorial step at
  s=128 never reaches this kernel, so it has a phase of its own.
* **serve** — ``ServeEngine`` over ``SingleDeviceSlotBackend`` built as
  ``apps/serve.py`` builds it, answering seeded requests; tokens against
  ``Generator`` on the same chip.
* **trainer** — ``Trainer(n_stages=1, schedule="1f1b")`` takes a few steps
  through ``train_epoch`` on the seeded synthetic corpus.
* **multichip** — with four or more devices, the same trainer at
  ``n_stages=4`` under ``1f1b`` (overlap and phase compile as the
  accelerator selects them) and ``gpipe``, against the one-stage run.

One process: a chip belongs to one process at a time. It exits non-zero at
once, printing no result, when the first JAX device is not a TPU. Every phase
runs even after an earlier one failed (a chip call is expensive; all the
findings come back at once), and any failed check or raised exception makes
the exit code 1. The last line of stdout is one JSON object,
``{"ok": ..., "device": {"platform", "kind", "count"}}``. It writes into the
checkout only the compile cache (``utils.platform.configure_compile_cache``)
and ``chiprun_out/chip_smoke.json`` (the per-phase report).
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np

from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
from pipe_tpu.obs.telemetry import device_memory_peaks, get_registry
from pipe_tpu.utils.platform import configure_compile_cache
from pipe_tpu.utils.rng import make_key

HERE = os.path.dirname(os.path.abspath(__file__))

# Greedy tokens of the engine and of Generator may part at a near-tie: the
# two run the same bf16 math at different shapes (4 slots against batch 1,
# bucket-padded against exact-length prefill), the MXU's accumulation order
# follows the shape, and a bf16 rounding boundary crossed anywhere in 16
# layers moves a logit. With these random weights logits have std 0.57 and a
# median top-two gap of 0.07; bf16 against f32 compute moves one logit by
# 0.013 on average, 0.10 at the 99.9th percentile, 0.20 at most (counted on
# the CPU at full width: rounding, not a device metric). A first difference
# is accepted only when both tokens sit within this much of the best logit
# of the reference forward; a wrong cache row or position lands ~2.5 below.
NEAR_TIE_LOGIT_GAP = 0.2

# First- and last-step loss, four stages against one: the relative tolerance
# the CPU suite uses wherever it compares bf16 against a reference
# (tests/test_pallas_attention.py, tests/test_ring_attention.py: rtol 5e-2).
BF16_RTOL = 5e-2


class Phase:
    """One phase's checks: every check prints, a failed one is kept."""

    def __init__(self):
        self.failures = []
        self.info = {}

    def check(self, ok, what: str) -> bool:
        print(f"  [{'ok' if ok else 'FAIL'}] {what}", flush=True)
        if not ok:
            self.failures.append(what)
        return bool(ok)


class CompileClock:
    """Sums JAX's own compile events: seconds tracing and lowering, seconds
    in the backend compiler (persistent-cache retrieval included, so a warm
    cache shows as fewer seconds), and persistent-cache hits and misses."""

    def __init__(self):
        self.trace_s = self.compile_s = 0.0
        self.cache_hits = self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs
        elif event in ("/jax/core/compile/jaxpr_trace_duration",
                       "/jax/core/compile/jaxpr_to_mlir_module_duration"):
            self.trace_s += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self):
        return (self.trace_s, self.compile_s, self.cache_hits,
                self.cache_misses)


def memory_report(devices, phase: Phase, require: bool = True):
    """``obs.telemetry.device_memory_peaks`` for ``devices``. The peak is
    the process's peak so far, so phases run in order of expected peak. A
    device that reports nothing fails the phase when ``require``."""
    peaks = device_memory_peaks()
    out = {}
    for d in devices:
        stats = peaks.get(str(d))
        if stats:
            out[str(d)] = stats
        elif require:
            phase.check(False, f"{d} reports memory_stats()")
    return out


def print_memory(mem):
    for dev, m in mem.items():
        print(f"  memory {dev}: in use {m['bytes_in_use'] / 2**30:.2f} GiB, "
              f"peak {m['peak_bytes_in_use'] / 2**30:.2f} GiB of "
              f"{m['bytes_limit'] / 2**30:.2f} GiB", flush=True)


# ---------------------------------------------------------------------------
# kernel


def kernel_phase(phase: Phase, shapes=None, dtypes=None):
    from tools.tpu_validate import DTYPES, SHAPES, validate_flash
    reg = get_registry()
    compiled0 = reg.counter("ops.flash_attention.compiled").value
    for shape in shapes or SHAPES:
        for dtype in dtypes or DTYPES:
            r = validate_flash(shape, dtype)
            bad = [k for k, c in r["checks"].items() if not c["pass"]]
            phase.check(
                r["pass"],
                f"flash fwd + dQ + dK/dV + dropout, {r['dtype']} "
                f"(b,s,h,d)={tuple(shape)}"
                + (f": failed {bad}: "
                   f"{json.dumps({k: r['checks'][k] for k in bad})}"
                   if bad else ""))
            phase.info[f"{r['dtype']}-s{shape[1]}"] = r["checks"]
    phase.check(
        reg.counter("ops.flash_attention.compiled").value > compiled0,
        "the kernels were compiled for the device")


# ---------------------------------------------------------------------------
# serve


def full_logits(model, params, tokens):
    """The training-path forward in eval mode: pre_fn, every stage's
    blocks, the head (the suite's reference for cached decoding,
    tests/test_generate.py)."""
    from pipe_tpu.core.partition import StageCtx
    sp, pre, post = params
    ctx = StageCtx(train=False)
    h = model.pre_fn(pre, tokens, ctx)
    for blocks in sp:
        h = model.stage_fn(blocks, h, ctx)
    return model.post_fn(post, h, ctx)


def serve_phase(phase: Phase, model_cfg: LMConfig, *, n_requests: int = 6,
                slots: int = 4, max_new: int = 32, seed: int = 0):
    from pipe_tpu.inference import GenerationConfig, Generator
    from pipe_tpu.serve import (BucketSpec, RequestQueue, ServeEngine,
                                SingleDeviceSlotBackend)

    # as apps/serve.py: seeded prompts of 8-32 tokens, fresh seeded weights,
    # pow2 buckets from 8, slab KV, decode_chunk 4, resident "auto"
    model = PipelinedLM(model_cfg, 1)
    params = model.init(jax.random.key(seed))
    rng = np.random.RandomState(seed)
    lens = rng.choice((8, 12, 16, 24, 32), size=n_requests)
    prompts = [rng.randint(1, model_cfg.vocab, size=int(n)).tolist()
               for n in lens]
    gen_cfg = GenerationConfig(max_new_tokens=max_new, temperature=0.0)
    buckets = BucketSpec.pow2(min_len=8,
                              max_len=max(len(p) for p in prompts))
    backend = SingleDeviceSlotBackend(
        model, params, num_slots=slots, max_len=buckets.max_len + max_new,
        gen=gen_cfg, buckets=buckets, decode_chunk=4, resident="auto",
        resident_chunks=8)
    phase.check(backend.resident_chunks == 8,
                "resident='auto' gave the decode launch its 8-chunk horizon")
    eng = ServeEngine(backend, RequestQueue(capacity=64, policy="fifo"))

    reg = get_registry()

    def traces():
        return {k: reg.counter(f"serve.engine.{k}").value
                for k in ("decode_traces", "resident_traces",
                          "prefill_traces")}

    def serve_round():
        ids = [eng.submit(p, seed=seed + i).id
               for i, p in enumerate(prompts)]
        eng.run_until_idle()
        return [eng.response(rid) for rid in ids]

    def admissions():
        return [reg.counter(f"serve.engine.{k}").value
                for k in ("admitted", "first_tokens_overlapped")]

    admitted0, overlapped0 = admissions()
    t0 = time.perf_counter()
    warm = serve_round()
    t1 = time.perf_counter()
    traces_warm = traces()
    steady = serve_round()
    t2 = time.perf_counter()
    phase.info["warmup_round_s"] = round(t1 - t0, 3)
    phase.info["steady_round_s"] = round(t2 - t1, 3)
    phase.info["traces"] = traces_warm
    phase.check(traces() == traces_warm,
                f"no new decode/resident/prefill traces after warm-up "
                f"({traces_warm} -> {traces()})")
    phase.check(sum(traces_warm.values()) > 0, "the engine traced programs")
    admitted, overlapped = admissions()
    phase.check(admitted - admitted0 == overlapped - overlapped0
                == 2 * n_requests,
                f"every admission's first token was read after its tick's "
                f"decode launch was queued ({overlapped - overlapped0} of "
                f"{admitted - admitted0})")

    gen = Generator(model, gen_cfg)
    refs = [np.asarray(gen.generate(
                params, jnp.asarray(p, jnp.int32)[None],
                jax.random.key(seed + i)))[0]
            for i, p in enumerate(prompts)]
    logits_fn = jax.jit(lambda p, t: full_logits(model, p, t))
    exact = near_ties = 0
    for name, responses in (("warm-up", warm), ("steady", steady)):
        for i, (prompt, ref, resp) in enumerate(
                zip(prompts, refs, responses)):
            what = f"{name} request {i} (prompt {len(prompt)})"
            if not phase.check(
                    resp.status == "ok" and len(resp.tokens) == max_new,
                    f"{what}: status ok with {max_new} tokens (got "
                    f"{resp.status}/{resp.finish_reason}, "
                    f"{len(resp.tokens)} tokens)"):
                continue
            got = np.asarray(resp.tokens)
            diff = np.nonzero(got != ref)[0]
            if diff.size == 0:
                exact += 1
                continue
            j = int(diff[0])
            ctx = jnp.asarray(prompt + ref[:j].tolist(), jnp.int32)[None]
            lg = np.asarray(logits_fn(params, ctx))[0, -1]
            gap = float(lg.max() - min(lg[ref[j]], lg[got[j]]))
            near_ties += phase.check(
                gap < NEAR_TIE_LOGIT_GAP,
                f"{what}: first differs from Generator at token {j} "
                f"({got[j]} vs {ref[j]}); both within {gap:.4f} of the "
                f"reference's best logit (tolerance {NEAR_TIE_LOGIT_GAP})")
    phase.info["token_exact"] = exact
    phase.info["token_near_tie"] = near_ties
    print(f"  tokens: {exact} requests equal Generator's exactly, "
          f"{near_ties} part at a near-tie", flush=True)
    if eng.last_error is not None:
        phase.check(False, f"engine contained a backend error: "
                           f"{type(eng.last_error).__name__}: "
                           f"{eng.last_error}")


# ---------------------------------------------------------------------------
# trainer


def corpus_batches(batch_size: int):
    """The seeded synthetic corpus (no network), batchified as
    apps/lm_tutorial.py does."""
    from pipe_tpu.data import lm_text
    train_lines, _, _ = lm_text.load_corpus(None)
    vocab = lm_text.Vocab(map(lm_text.basic_english_tokenize, train_lines))
    return lm_text.batchify(lm_text.data_process(train_lines, vocab),
                            batch_size)


def run_trainer(phase: Phase, model_cfg: LMConfig, data, *, n_stages: int,
                schedule: str, steps: int, devices):
    """Build the Trainer, take ``steps`` steps through ``train_epoch`` over
    ``data`` (``corpus_batches``); returns ``(trainer, state, first_loss,
    last_loss, mem_after_init)``."""
    from pipe_tpu.train.loop import Trainer, TrainerConfig

    # lr: TrainerConfig's default is the reference's 5.0, which diverges at
    # this size; 1e-4 is what bench.py trains with
    tcfg = TrainerConfig(n_stages=n_stages, schedule=schedule,
                         checkpoint="except_last", batch_size=data.shape[1],
                         bptt=model_cfg.seq_len, chunks=4, lr=1e-4)
    trainer = Trainer(model_cfg, tcfg, devices=devices)
    state = trainer.init_state()
    gc.collect()
    mem_init = memory_report(devices, phase, require=False)
    t0 = time.perf_counter()
    state, first = trainer.train_epoch(data, state=state, max_steps=1,
                                       log_every=1)
    t1 = time.perf_counter()
    state, last = trainer.train_epoch(data, state=state, max_steps=steps,
                                      start_step=1, log_every=1)
    tag = f"{schedule}-d{n_stages}-dropout{model_cfg.dropout}"
    phase.info[tag] = {
        "params": trainer.num_params(state),
        "first_step_s_compile_included": round(t1 - t0, 3),
        "steady_sec_per_step": round(last["sec_per_step"], 4),
        "first_loss": first["loss"], "last_loss": last["loss"]}
    what = (f"Trainer {schedule} n_stages={n_stages} "
            f"dropout={model_cfg.dropout}")
    phase.check(np.isfinite(first["loss"]) and np.isfinite(last["loss"]),
                f"{what}: loss finite (step 1 {first['loss']:.4f}, "
                f"step {steps} {last['loss']:.4f})")
    phase.check(last["loss"] < first["loss"],
                f"{what}: loss lower at step {steps} than at step 1")
    return trainer, state, first["loss"], last["loss"], mem_init


def trainer_phase(phase: Phase, model_cfg: LMConfig, *, steps: int = 6,
                  batch_size: int = 32):
    trainer, state, _, _, _ = run_trainer(
        phase, model_cfg, corpus_batches(batch_size), n_stages=1,
        schedule="1f1b", steps=steps, devices=jax.devices()[:1])
    print(f"  parameters: {trainer.num_params(state):,}", flush=True)


# ---------------------------------------------------------------------------
# multichip


def multichip_phase(phase: Phase, model_cfg: LMConfig, *, n_stages: int = 4,
                    steps: int = 4, batch_size: int = 32):
    devices = jax.devices()[:n_stages]
    reg = get_registry()
    # Dropout off for the comparison across stage counts: the executors
    # fold (micro-batch, stage) into the step key and the stage body folds
    # the layer's index WITHIN its stage, so the masks differ with the
    # stage count by construction — whatever the key implementation (rbg on
    # the TPU). Weights do not: init folds the global layer index.
    cfg0 = dataclasses.replace(model_cfg, dropout=0.0)
    data = corpus_batches(batch_size)
    _, _, ref_first, ref_last, _ = run_trainer(
        phase, cfg0, data, n_stages=1, schedule="1f1b", steps=steps,
        devices=devices[:1])
    gc.collect()

    for schedule in ("1f1b", "gpipe"):
        counters0 = {k: reg.counter(k).value for k in
                     ("scheduled.phase.compiled",
                      "scheduled.phase.rejected")}
        trainer, state, first, last, mem_init = run_trainer(
            phase, cfg0, data, n_stages=n_stages, schedule=schedule,
            steps=steps, devices=devices)
        what = f"{schedule} n_stages={n_stages}"
        for name, got, want in (("first", first, ref_first),
                                ("last", last, ref_last)):
            phase.check(
                abs(got - want) <= BF16_RTOL * abs(want),
                f"{what}: {name}-step loss {got:.5f} agrees with the "
                f"one-stage run's {want:.5f} (diff {got - want:+.5f}, "
                f"rtol {BF16_RTOL})")

        # placement: every stage-stacked leaf holds one stage per device
        spread = all(
            sorted(s.index[0].start for s in leaf.addressable_shards)
            == list(range(n_stages))
            and all(s.data.shape[0] == 1 for s in leaf.addressable_shards)
            for leaf in jax.tree_util.tree_leaves(state.params[0]))
        phase.check(spread, f"{what}: each device holds one stage's "
                            f"slice of every block weight")
        leaves = jax.tree_util.tree_leaves
        block_b = sum(a.nbytes for a in leaves(state.params[0]))
        repl_b = sum(a.nbytes for a in leaves(state.params[1:]))
        # params + Adam's two f32 moments, one stage's share per device
        expect = 3 * (repl_b + block_b / n_stages)
        whole = 3 * (repl_b + block_b)
        for dev, m in mem_init.items():
            phase.check(
                0.9 * expect <= m["bytes_in_use"] <= 1.3 * expect,
                f"{what}: {dev} holds {m['bytes_in_use'] / 2**30:.2f} GiB after "
                f"init_state (a stage's share is "
                f"{expect / 2**30:.2f} GiB; the whole state is "
                f"{whole / 2**30:.2f} GiB)")
        phase.check(len(mem_init) == n_stages,
                    f"{what}: {n_stages} devices reported memory after init")
        mem = memory_report(devices, phase)
        print_memory(mem)
        info = phase.info[f"{schedule}-d{n_stages}-dropout0.0"]
        info["memory"] = mem

        # the compiled program moves activations between chips
        from pipe_tpu.data import lm_text
        x, w = trainer._make_x(*lm_text.get_batch(
            data, 0, trainer.cfg.bptt))
        key = jax.random.fold_in(make_key(trainer.cfg.seed), 0)
        hlo = trainer._step_fn.lower(
            state, x, w, jax.random.fold_in(key, 0),
            jnp.float32(trainer.cfg.lr)).compile().as_text()
        n_cp = hlo.count("collective-permute")
        phase.check(n_cp > 0, f"{what}: compiled HLO holds "
                              f"collective-permute ({n_cp} mentions)")
        info["collective_permute"] = n_cp

        if schedule == "1f1b":
            # the table executor's accelerator defaults, read where it
            # publishes them: a fallback here fails the phase
            compiled = (reg.counter("scheduled.phase.compiled").value
                        - counters0["scheduled.phase.compiled"])
            rejected = (reg.counter("scheduled.phase.rejected").value
                        - counters0["scheduled.phase.rejected"])
            active = reg.gauge("scheduled.phase.active").value
            overlap = reg.gauge("scheduled.transport.overlap").value
            phase.info["1f1b-defaults"] = {
                "phase_compiled": compiled, "phase_rejected": rejected,
                "phase_active": active, "overlap": overlap}
            phase.check(rejected == 0 and compiled >= 1 and active == 1,
                        f"{what}: phase compiler accepted the table and "
                        f"the phased program ran (compiled {compiled}, "
                        f"rejected {rejected}, active {active})")
            phase.check(overlap == 1,
                        f"{what}: overlapped packed transport engaged "
                        f"(scheduled.transport.overlap {overlap})")
        del trainer, state
        gc.collect()

    # and the program a user gets by default: dropout on, rbg keys drawn
    # inside the four-device shard_map
    run_trainer(phase, model_cfg, data, n_stages=n_stages, schedule="1f1b",
                steps=3, devices=devices)


# ---------------------------------------------------------------------------
# driver


def versions():
    from importlib.metadata import PackageNotFoundError, version
    out = {}
    for pkg in ("jax", "jaxlib", "libtpu"):
        try:
            out[pkg] = version(pkg)
        except PackageNotFoundError:
            out[pkg] = None
    return out


def run_phases(phases) -> dict:
    """Run ``[(name, fn)]``; returns the report. A phase that raises is a
    failed phase with its traceback, and the next one still runs."""
    clock = CompileClock()
    report = {}
    for name, fn in phases:
        print(f"== {name}", flush=True)
        phase = Phase()
        c0, t0 = clock.snapshot(), time.perf_counter()
        try:
            fn(phase)
        except Exception as e:  # noqa: BLE001 — reported, fails the run
            tb = traceback.format_exc()
            print(tb, flush=True)
            phase.failures.append(
                f"raised {type(e).__name__}: {str(e)[:2000]}")
            phase.info["traceback"] = tb
        wall = time.perf_counter() - t0
        trace_s, compile_s, hits, misses = (
            b - a for a, b in zip(c0, clock.snapshot()))
        gc.collect()
        mem = memory_report(jax.local_devices(), phase, require=False)
        report[name] = {
            "ok": not phase.failures, "failures": phase.failures,
            "wall_s": round(wall, 2), "trace_s": round(trace_s, 2),
            "compile_s": round(compile_s, 2),
            "run_s": round(wall - trace_s - compile_s, 2),
            "cache_hits": hits, "cache_misses": misses,
            "memory": mem, "info": phase.info}
        print(f"  {name}: {'ok' if not phase.failures else 'FAILED'} in "
              f"{wall:.1f} s (tracing {trace_s:.1f} s, compiling "
              f"{compile_s:.1f} s with {hits} cache hits and {misses} "
              f"misses, the rest {wall - trace_s - compile_s:.1f} s)",
              flush=True)
        print_memory(mem)
    return report


def main() -> int:
    configure_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found — jax.devices()[0] is "
              f"{dev.platform!r} ({dev.device_kind}), JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r}. This script checks the "
              f"program on the chip and does not run on anything else.",
              file=sys.stderr)
        return 2
    n_dev = len(jax.devices())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": n_dev}
    vers = versions()
    print(f"platform: {dev.platform}  device_kind: {dev.device_kind}  "
          f"devices: {n_dev}  "
          + "  ".join(f"{k} {v}" for k, v in vers.items()))
    print("devices: " + ", ".join(
        f"{d.id}@{getattr(d, 'coords', None)}" for d in jax.devices()))
    print(f"compile cache: "
          f"{jax.config.jax_compilation_cache_dir}", flush=True)

    cfg = LMConfig(compute_dtype=jnp.bfloat16)
    # in order of expected peak memory (the device reports one running peak)
    phases = [("kernel", kernel_phase),
              ("serve", lambda p: serve_phase(p, cfg)),
              ("trainer", lambda p: trainer_phase(p, cfg))]
    if n_dev >= 4:
        phases.append(("multichip", lambda p: multichip_phase(p, cfg)))
    report = run_phases(phases)
    if n_dev < 4:
        print(f"multichip: not run ({n_dev} device)")

    interpreted = get_registry().counter(
        "ops.flash_attention.interpreted").value
    ok = all(r["ok"] for r in report.values()) and interpreted == 0
    if interpreted:
        print(f"FAIL: {interpreted} Pallas kernel trace(s) ran with "
              f"interpret=True")
    for name, r in report.items():
        for f in r["failures"]:
            print(f"FAILED {name}: {f}")
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump({"ok": ok, "device": device, "versions": vers,
                   "phases": report}, f, indent=1, default=str)
        f.write("\n")
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
