"""Benchmark: tutorial-parity Transformer LM training throughput.

Workload = the reference's headline config (``/root/reference/main.py:101-120``:
WikiText-2 LM, batch 32, bptt 128, emsize 2048, nhid 2048, nlayers 16,
nhead 32, chunks 4, checkpoint=except_last) driven through the framework's
training hot path — the schedule-table executor (``ScheduledPipeline``,
schedule='1f1b': hand-scheduled forward+backward, exact per-micro-batch
checkpoint policy; at one device the tables specialize to straight-line code
at trace time) — full train step (forward + in-pipeline loss + backward +
grad-clip + Adam).

Prints ONE JSON line:
``{"metric": ..., "value": N, "unit": ..., "vs_baseline": N}``.
``vs_baseline`` is pipelined throughput / plain single-chip throughput of
the identical computation: the plain step processes the same ``CHUNKS``
micro-batches by gradient accumulation (what a single-device user runs when
the full batch does not fit). Both honest accumulation programs are timed —
scan with uniform remat, and a Python-unrolled loop with the exact
per-micro-batch policy — and the FASTER one is the denominator, so the
ratio never flatters the pipeline; >= 1.0 means the machinery adds no
overhead on top of the best plain program (per-style timings in the
``baseline_sec_per_step`` key). ``vs_fullbatch`` (extra key) compares
against one full-batch step instead (granularity difference included). The
reference publishes no numbers (BASELINE.md), so baselines are measured,
not copied.

Note on the optimizer: the tutorial driver uses Adam at lr=5.0 (reference
``main.py:183``, reproduced faithfully as the Trainer default and divergent
at full scale — see ``--lr`` help); throughput is lr-independent, so this
benchmark uses adam(1e-4) purely so ``final_loss`` stays finite and the
convergence sanity check means something.
"""

from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import optax

from pipe_tpu.core import microbatch as mb
from pipe_tpu.core.schedule import bubble_fraction
from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
# The MFU arithmetic lives in obs.telemetry (shared with live-training
# StepReports); tools/ import it from here.
from pipe_tpu.obs.telemetry import (StepReport, device_memory_peaks,
                                    peak_flops_per_chip,
                                    train_flops_per_token)
from pipe_tpu.parallel.mesh import make_mesh
from pipe_tpu.parallel.scheduled import ScheduledPipeline
from pipe_tpu.parallel.spmd import stack_stage_params
from pipe_tpu.utils.platform import configure_compile_cache
from pipe_tpu.utils.rng import make_key

CHUNKS = int(os.environ.get("BENCH_CHUNKS", "4"))
BATCH = int(os.environ.get("BENCH_BATCH", "32"))
# `python main.py except_last` parity: at 520M params the no-remat config
# does not fit one 16G chip (the reference used 2 larger GPUs), so remat is
# the realistic headline mode; override with BENCH_CHECKPOINT=never etc.
CHECKPOINT = os.environ.get("BENCH_CHECKPOINT", "except_last")
# Selective remat for the RECOMPUTE micro-batches (a jax.checkpoint_policies
# member name, e.g. "dots_saveable"): saves matmul outputs at forward,
# recomputes only the elementwise remainder at backward — trades a little
# HBM for most of the recompute FLOPs while keeping the exact per-micro-
# batch mode semantics. "none" disables (full recompute, the reference's
# all-or-nothing behavior).
REMAT_POLICY = os.environ.get("BENCH_REMAT_POLICY", "dots_saveable")


def tutorial_config(platform: str) -> LMConfig:
    """The full 520M-parameter tutorial config. The benchmark's numbers are
    device numbers, so anything but a TPU is an error — never a smaller
    model under the same metric name."""
    if platform != "tpu":
        raise RuntimeError(
            f"this benchmark measures the 520M tutorial LM on a TPU, but "
            f"the JAX backend is {platform!r}; run it on a machine with a "
            f"chip (JAX_PLATFORMS unset or 'tpu')")
    return LMConfig(compute_dtype=jnp.bfloat16)


def make_step(model, sched, tx):
    def train_step(params, opt_state, x, w, key):
        sp, prep, postp = params
        loss, grads = sched.loss_and_grad(sp, prep, postp, x, w, key=key)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(train_step, donate_argnums=(0, 1))


def make_plain_step(model, tx, microbatches: int = 1, style: str = "scan"):
    """The unpipelined ideal: same model, no pipeline machinery.

    ``microbatches > 1`` processes the batch as that many gradient-
    accumulation steps — the single-device equivalent of the pipeline's
    micro-batching, with identical matmul shapes. Two honest variants, both
    timed by main() with the FASTER one as the ``vs_baseline`` denominator:

    * ``style='scan'`` — what a single-device user actually writes:
      ``lax.scan`` over micro-batches with a uniform remat policy (a scan
      body cannot vary remat per iteration — the exact per-micro-batch
      except_last policy is precisely what the schedule-table executor adds
      over this program).
    * ``style='unrolled'`` — a Python-unrolled loop with the exact
      per-micro-batch policy (equal recompute to the pipelined step;
      measured slower than 'scan' on v5e at tutorial scale despite doing
      ~1/m less recompute — XLA schedules the rolled loop better).
    """

    def make_forward(remat: bool):
        def forward(params, tokens, targets, key):
            from pipe_tpu.core.partition import StageCtx
            sp, prep, postp = params
            ctx = StageCtx(key=key, train=True)
            h = model.pre_fn(prep, tokens, ctx)

            def block_fn(blocks, k, h):
                return model.stage_fn(blocks, h, StageCtx(key=k, train=True))

            body = jax.checkpoint(block_fn) if remat else block_fn
            for j, blocks in enumerate(sp):
                h = body(blocks, ctx.fold(j).key, h)
            per_row = model.loss_post_fn(postp, h, {"targets": targets},
                                         ctx.fold(99))
            return jnp.mean(per_row)

        return jax.value_and_grad(forward)

    grad_remat = make_forward(CHECKPOINT != "never")
    grad_exact_last = make_forward(False)

    def grad_for(i):
        if CHECKPOINT == "except_last" and i == microbatches - 1:
            return grad_exact_last
        return grad_remat

    def train_step(params, opt_state, tokens, targets, key):
        if microbatches == 1:
            loss, grads = grad_for(0)(params, tokens, targets, key)
        elif style == "scan":
            mb_tok = tokens.reshape(microbatches, -1, tokens.shape[-1])
            mb_tgt = targets.reshape(microbatches, -1, targets.shape[-1])

            def acc(carry, inp):
                g_sum, l_sum = carry
                t, tg, i = inp
                l, g = grad_remat(params, t, tg, jax.random.fold_in(key, i))
                return (jax.tree_util.tree_map(jnp.add, g_sum, g),
                        l_sum + l), None

            zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
            (grads, l_sum), _ = jax.lax.scan(
                acc, (zeros, 0.0),
                (mb_tok, mb_tgt, jnp.arange(microbatches)))
            grads = jax.tree_util.tree_map(
                lambda g: g / microbatches, grads)
            loss = l_sum / microbatches
        else:
            mb_tok = tokens.reshape(microbatches, -1, tokens.shape[-1])
            mb_tgt = targets.reshape(microbatches, -1, targets.shape[-1])
            grads = jax.tree_util.tree_map(jnp.zeros_like, params)
            loss = 0.0
            for i in range(microbatches):
                l, g = grad_for(i)(params, mb_tok[i], mb_tgt[i],
                                   jax.random.fold_in(key, i))
                grads = jax.tree_util.tree_map(jnp.add, grads, g)
                loss = loss + l
            grads = jax.tree_util.tree_map(
                lambda g: g / microbatches, grads)
            loss = loss / microbatches
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(train_step, donate_argnums=(0, 1))


def time_steps(step_fn, params, opt_state, args, warmup=2, iters=8):
    """Per-step wall time with a ONE-STEP-LAGGED host value fetch.

    Every step's loss is read back to the host (real computed data, so the
    timing cannot end before the device does), but step i's fetch happens
    while step i+1 executes, so the host round-trip overlaps compute
    instead of serializing after it.
    """
    for _ in range(warmup):
        params, opt_state, loss = step_fn(params, opt_state, *args)
    float(loss)
    prev = None
    t0 = time.perf_counter()
    for _ in range(iters):
        params, opt_state, loss = step_fn(params, opt_state, *args)
        if prev is not None:
            float(prev)
        prev = loss
    last = float(prev)
    return (time.perf_counter() - t0) / iters, last


HERE = os.path.dirname(os.path.abspath(__file__))


def run_cpu_child(argv, cwd=None):
    """Run one CPU drill as a child process; returns its stdout.

    This parent holds the chip, and a chip belongs to one process, so
    every child is started on the CPU — none of the drills needs the
    accelerator. A child that exits non-zero fails the run. The checkout
    goes in front of the caller's PYTHONPATH, which is kept.
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (HERE, os.environ.get("PYTHONPATH")) if p)
    out = subprocess.run([sys.executable, *argv], capture_output=True,
                         text=True, timeout=900, env=env, cwd=cwd)
    if out.returncode != 0:
        raise RuntimeError(
            f"{' '.join(argv)} exited {out.returncode}:\n"
            f"{out.stdout[-2000:]}\n{out.stderr[-2000:]}")
    return out.stdout


def cpu_tool_summary(tool, *args):
    """The last stdout line of ``tools/<tool>`` (a CPU child), as JSON."""
    out = run_cpu_child([os.path.join(HERE, "tools", tool), *args])
    return json.loads(out.strip().splitlines()[-1])


def main():
    configure_compile_cache()
    # Hard-disable telemetry for every program this process times: the
    # null registry hands back shared no-op instruments, so not even
    # trace-time counter bumps ride the bench hot path, and the claim
    # "disabled telemetry is zero-cost here" is enforced rather than
    # assumed (tests/test_overlap_transport.py pins the lowered HLO of a
    # step as byte-identical under default vs null registry).
    from pipe_tpu.obs.telemetry import null_registry, set_registry
    set_registry(null_registry())

    platform = jax.default_backend()
    n_chips = jax.device_count()
    cfg = tutorial_config(platform)
    n_stages = 1  # bench chip count decides the pipeline depth
    for cand in (8, 4, 2, 1):
        if n_chips % cand == 0 and cand <= n_chips and cfg.n_layers % cand == 0:
            n_stages = cand
            break
    mesh = make_mesh(n_stages, 1, devices=jax.devices()[:n_stages])

    model = PipelinedLM(cfg, n_stages)
    stage_params, pre_params, post_params = model.init(jax.random.key(0))
    # plain_params is the never-donated master copy; every timed step gets
    # fresh buffers from it (steps donate their inputs).
    plain_params = (stage_params, pre_params, post_params)

    def fresh(stacked: bool):
        # jnp.stack already allocates new buffers for the stage tree, so
        # only the (donated) pre/post trees need explicit copies there.
        copy = lambda t: jax.tree_util.tree_map(
            lambda a: jnp.array(a, copy=True), t)
        if stacked:
            return (stack_stage_params(plain_params[0]),
                    copy(plain_params[1]), copy(plain_params[2]))
        return copy(plain_params)

    def timed(step_fn, stacked, args):
        p = fresh(stacked)
        return time_steps(step_fn, p, tx.init(p), args)

    n_params = model.num_params(plain_params)
    policy = None
    if REMAT_POLICY not in ("none", "") and CHECKPOINT != "never" \
            and n_stages == 1:
        policy = getattr(jax.checkpoint_policies, REMAT_POLICY)
    sched = ScheduledPipeline(mesh, model.stage_fn, pre_fn=model.pre_fn,
                              post_fn=model.loss_post_fn,
                              checkpoint=CHECKPOINT, schedule="1f1b",
                              remat_policy=policy)
    # Adam first-moment dtype, applied to the pipelined step AND the
    # single-device baselines alike, so vs_baseline stays like-for-like
    # (its effect on step time: not measured on the current
    # installation). Override with BENCH_MU_DTYPE=float32.
    mu_dtype = jnp.dtype(os.environ.get("BENCH_MU_DTYPE", "bfloat16"))
    tx = optax.chain(optax.clip_by_global_norm(0.5),
                     optax.adam(1e-4, mu_dtype=mu_dtype))

    tokens = jax.random.randint(jax.random.key(1), (BATCH, cfg.seq_len),
                                0, cfg.vocab, jnp.int32)
    targets = jnp.roll(tokens, -1, axis=-1)
    x, n_rows = mb.stack_scatter({"tokens": tokens, "targets": targets},
                                 CHUNKS)
    w = mb.valid_row_mask(x, n_rows)
    # Backend-tuned key impl (rbg on TPU) — see utils/rng.py.
    key = make_key(2)

    step = make_step(model, sched, tx)
    sec_per_step, loss = timed(step, True, (x, w, key))
    tokens_per_step = BATCH * cfg.seq_len
    pipe_tps_chip = tokens_per_step / sec_per_step / n_stages

    # Measured bubble, trace-based: capture a short profiler trace and
    # report 1 - device_busy/span, the per-device idle fraction (the
    # reference author's TensorBoard-trace method, README.md:559-567). A
    # chip's trace has a device plane; one without is an error.
    from pipe_tpu.obs.meters import profile_trace, stage_busy_from_trace
    with tempfile.TemporaryDirectory() as trace_dir:
        p = fresh(True)
        opt = tx.init(p)
        with profile_trace(trace_dir):
            loss_ = None
            for _ in range(3):
                p, opt, loss_ = step(p, opt, x, w, key)
            float(loss_)
        del p, opt
        busy = stage_busy_from_trace(trace_dir)
    span = busy.pop("_span", 0.0)
    dev = [v for k, v in busy.items() if k.startswith("/device:")]
    if not dev or span <= 0:
        raise RuntimeError(
            f"profiler trace holds no device plane (planes: "
            f"{sorted(busy)}); cannot measure the bubble")
    measured_bubble = max(0.0, 1.0 - sum(dev) / (span * len(dev)))
    bubble_method = "trace_busy"

    # The CPU drills below run as children forced onto the CPU (see
    # run_cpu_child); any that fails, fails this run.

    # Multi-stage bubble probe: a 4-stage pipeline on the virtual 8-CPU
    # mesh — the quick mode of the multistage probe, which also records
    # serialized vs packed-overlapped boundary transport side by side.
    bubble_multistage = cpu_tool_summary("multistage_probe.py",
                                         "--quick", "4", "8")

    # Zero-bubble split probe: 1f1b vs the structural B/W split rows
    # (hand-rolled TP triple + the auto-derived split) on the cpu8 mesh —
    # the per-round record behind the zb-h1 cost story
    # (ZB_SPLIT_PROBE_r{N}.json is the full-size committed artifact).
    zb_split_summary = cpu_tool_summary("zb_split_probe.py", "--quick")

    # Front-door adapter tax (Pipe(mesh=) vs raw executor): the probe's
    # last stdout line is its summary with the tax_*_vs_raw ratios (cpu8).
    summary = cpu_tool_summary("front_door_probe.py")
    front_door_tax = {
        "tax_uniform_vs_raw": summary["tax_uniform_vs_raw"],
        "tax_phase_vs_raw_phase": summary.get("tax_phase_vs_raw_phase"),
        "tax_switch_vs_raw": summary["tax_switch_vs_raw"],
        "raw_sec_per_step": summary["results"]["raw"]["sec_per_step"],
    }

    # Serving probe: the continuous-batching engine's steady-state
    # tokens/s vs the fixed-batch Generator at equal live-slot count,
    # plus TTFT p50/p99 under 0.7x-capacity Poisson load (cpu8, quick
    # mode of tools/serve_bench.py; SERVE_r{N}.json is the full record).
    serve_summary = cpu_tool_summary("serve_bench.py", "--quick")
    # Paged KV must not lose to the slab at equal live slots on the
    # shared-prefix workload (its 2x-slots-same-memory win is on
    # top of, not instead of, per-slot throughput).
    assert serve_summary["kv_paged_vs_slab_equal_slots"] >= 1.0, (
        "paged KV slower than slab at equal live slots: "
        f"{serve_summary['kv_paged_vs_slab_equal_slots']}x")
    # The radix tree's reason to exist: on the multi-tenant
    # workload (divergent full-block tails) it must reuse strictly
    # more blocks than the gen-1 whole-prefix counterfactual — and
    # the offload round trip must never change a token.
    assert (serve_summary["kv_radix_hit_block_fraction"]
            > serve_summary["kv_whole_prefix_hit_fraction"]), (
        "radix prefix reuse no better than a whole-prefix cache: "
        f"{serve_summary['kv_radix_hit_block_fraction']} vs "
        f"{serve_summary['kv_whole_prefix_hit_fraction']}")
    assert serve_summary["kv_offload_bitwise"], (
        "KV offload drill produced different tokens than the "
        "unpressured run")
    # The resident while_loop exists to remove per-chunk host
    # round-trips; it must not LOSE tokens/s at equal live slots.
    assert serve_summary["resident_vs_nonresident_tokens_s"] >= 1.0, (
        "resident serve loop slower than single-chunk ticks at "
        "equal live slots: "
        f"{serve_summary['resident_vs_nonresident_tokens_s']}x")
    # Gen-2 speculative lane: every draft source must stay bitwise
    # the Generator; the truncated-pipeline draft must clear the
    # n-gram baseline decisively on aperiodic prompts (the reason
    # model-based drafts exist); and whenever measured acceptance
    # clears the breakeven the planner computes from this host's
    # OWN measured chunk-cost ratio, spec must not lose tokens/s to
    # the non-spec resident loop at equal live slots.
    assert serve_summary["spec_bitwise"], (
        "a speculative draft source changed tokens vs the "
        "Generator")
    assert (serve_summary["spec_acceptance_truncated"] >= 0.3
            and serve_summary["spec_acceptance_truncated"]
            > serve_summary["spec_acceptance_ngram"]), (
        "truncated-pipeline draft acceptance "
        f"{serve_summary['spec_acceptance_truncated']} did not "
        "clear the n-gram baseline "
        f"{serve_summary['spec_acceptance_ngram']}")
    if (serve_summary["spec_acceptance_truncated"]
            > serve_summary["spec_breakeven_acceptance"]):
        assert serve_summary["spec_vs_nonspec_tokens_s"] >= 1.0, (
            "acceptance cleared the measured breakeven "
            f"({serve_summary['spec_acceptance_truncated']} > "
            f"{serve_summary['spec_breakeven_acceptance']}) but "
            "spec decode lost to the non-spec loop: "
            f"{serve_summary['spec_vs_nonspec_tokens_s']}x")
    assert serve_summary["spec_steady_new_traces"] == 0, (
        "the spec resident program retraced inside the measured "
        f"window ({serve_summary['spec_steady_new_traces']} new "
        "traces) — steady state must not recompile")

    # Chaos probe: one injected fault per layer (train NaN, transport
    # drop, serve backend raise, data raise) through the recovery
    # machinery — all_recovered must stay true every round (cpu8, quick
    # mode of tools/chaos_bench.py; CHAOS_r{N}.json is the full record).
    chaos_summary = cpu_tool_summary("chaos_bench.py", "--quick")

    # Fleet probe: replica-count goodput scaling plus the
    # kill-one-of-3 failover proof over REAL child processes (SIGKILL
    # a replica process mid-stream: recovery + exactly-once ledger),
    # the async-tick straggler win, the session-remap KV handoff
    # TTFT, the disagg-vs-mixed SLO goodput drill plus the
    # phase-specialized SIGKILL drills (kill one prefill child, then
    # one decode child — exactly-once across the KV handoff), the
    # saturation sweep, and the observability plane over the SIGKILL
    # drill (delivered-token reconciliation + trace stitching) —
    # fleet_ok must stay true every round (quick mode of
    # tools/fleet_bench.py --fleet proc; FLEET_r{N}.json is the full
    # committed record, which this run does not overwrite).
    fleet_summary = cpu_tool_summary("fleet_bench.py", "--quick",
                                     "--fleet", "proc")
    # Per-replica tick threads exist to confine a straggler's
    # stall to its own replica; at N=3 with one straggler the
    # async fleet must not LOSE steady-state goodput to the
    # serial tick loop.
    assert fleet_summary["async_beats_serial"], (
        "async-tick fleet goodput fell below the serial tick loop "
        f"at N=3: {fleet_summary['async_speedup']}x")
    # The stitched traces must reconstruct EVERY submitted id from
    # the SIGKILL drill exactly once — parent-side skeleton events
    # guarantee a timeline even when a child's events die with it,
    # and trace ids minted once at submit keep a failed-over id in
    # ONE trace (two placements, not two traces).
    assert fleet_summary["trace_stitch_frac"] == 1.0, (
        "trace stitching lost request ids in the proc kill drill: "
        f"frac={fleet_summary['trace_stitch_frac']}")
    assert fleet_summary["trace_stitch_exactly_once"], (
        "a request id appeared in more than one stitched trace")
    assert fleet_summary["tokens_reconciled"], (
        "per-replica delivered-token counters no longer sum to "
        "the parent ledger's delivered total")
    # Disaggregation's entry fee: shipping a cached prefix must
    # beat recomputing it, every round — otherwise the
    # prefill→decode handoff is pure overhead.
    assert fleet_summary["handoff_beats_reprefill"], (
        "KV handoff TTFT no longer beats re-prefill TTFT: "
        f"win={fleet_summary['ttft_win_s']}s")
    # And the split must pay at equal chips: the phase-specialized
    # pair's SLO goodput (decode cadence protected from prefill
    # burst interference) must not lose to 2 mixed replicas under
    # the prefill-heavy two-class workload.
    assert fleet_summary["disagg_beats_mixed"], (
        "disagg fleet lost SLO goodput to mixed at equal chips: "
        f"{fleet_summary['disagg_goodput_tokens_s']} < "
        f"{fleet_summary['mixed_goodput_tokens_s']} tokens/s")
    # Phase-specialized SIGKILL drills: killing a prefill child or
    # a decode child mid-handoff must still deliver every id
    # exactly once.
    assert fleet_summary["disagg_kill_prefill_exactly_once"], (
        "ids lost or duplicated after SIGKILL of a prefill replica")
    assert fleet_summary["disagg_kill_decode_exactly_once"], (
        "ids lost or duplicated after SIGKILL of a decode replica")
    # Round-20 wire hardening: a timed partition on one replica's
    # proc wire must heal losslessly (retained-frame replay, seq
    # dedup), and a corrupt-frame storm must be rejected whole at
    # the CRC — never half-parsed — with every id still answered
    # exactly once.
    assert fleet_summary["partition_heals_exactly_once"], (
        "ids lost or duplicated across a 2s wire partition")
    assert fleet_summary["corrupt_storm_ok"], (
        "wire corruption storm lost ids or never tripped the CRC: "
        f"rejects={fleet_summary['wire_crc_rejects']}")
    # Round-20 tentpole: SIGKILL the CONTROLLER mid-stream, rebuild
    # it from the fsync'd request journal, re-dial the orphaned
    # children in rejoin mode — exactly one terminal per id across
    # the two controller lives, mixed and disagg fleets both.
    assert fleet_summary["ctl_restart_exactly_once"], (
        "ids lost or duplicated across a controller SIGKILL+restart")
    assert fleet_summary["ctl_restart_disagg_exactly_once"], (
        "ids lost or duplicated across a disagg controller "
        "SIGKILL+restart")

    # Elastic probe: kill 1 of 4 stages mid-run -> heartbeat detection,
    # re-plan to 3, buddy restore, and the bitwise pin against the
    # from-snapshot reference — all_ok must stay true every round
    # (quick mode of tools/elastic_bench.py; ELASTIC_r{N}.json is the
    # full record).
    elastic_summary = cpu_tool_summary("elastic_bench.py", "--quick")

    # Planner probe: calibrate -> search -> measure on the cpu8 probe
    # (quick mode of tools/plan_bench.py). plan_ok asserts the chosen
    # plan is no slower than the hand-tuned 1f1b m=8 baseline within
    # noise, and that every emitted plan's op table re-proved itself
    # (PLAN_r{N}.json is the full committed record).
    full = cpu_tool_summary("plan_bench.py", "--quick")
    plan_summary = {
        "plan_ok": full["plan_ok"],
        "all_plans_verified": full["all_plans_verified"],
        "top": {k: full["plan"][k] for k in
                ("schedule", "m", "v", "split_stage")},
        "top_rel_err": full["top_measured"][0]["rel_err"],
        "top_vs_baseline_per_row": full["top_vs_baseline_per_row"],
        "calibration_rel_residual": full["calibration"]["rel_residual"],
    }

    # Chaos smoke lane: the pytest-marked elastic drill (kill stage 1/4,
    # resumed loss trajectory vs the unkilled run) plus one wire-chaos
    # drill (corrupt frame rejected whole at the framing layer) as the
    # repo's own test suite runs them — the bench proves the committed
    # tests pass, not just the bench-local drills.
    smoke_tests = [
        os.path.join("tests", "test_elastic.py")
        + "::test_elastic_drill_loss_trajectory",
        os.path.join("tests", "test_fleet_journal.py")
        + "::test_wire_corrupt_frame_is_rejected_whole_never_half_parsed",
    ]
    t0 = time.time()
    run_cpu_child(["-m", "pytest", "-m", "chaos", "-q",
                   "-p", "no:cacheprovider"] + smoke_tests, cwd=HERE)
    chaos_smoke = {"ok": True, "tests": smoke_tests,
                   "wall_s": round(time.time() - t0, 1)}

    # vs_baseline denominator = the FASTER of the two honest accumulation
    # programs (see make_plain_step), so the ratio never flatters the
    # pipeline by comparing against a strawman. A baseline that fails
    # (OOM included) fails the run: a ratio against nothing is not 0.0.
    baseline_styles = {}
    # at CHUNKS == 1 both styles collapse to the same single-step program
    for style in (("scan",) if CHUNKS == 1 else ("scan", "unrolled")):
        plain_acc = make_plain_step(model, tx, microbatches=CHUNKS,
                                    style=style)
        acc_sec, _ = timed(plain_acc, False, (tokens, targets, key))
        baseline_styles[style] = round(acc_sec, 5)
    best_sec = min(baseline_styles.values())
    vs_baseline = pipe_tps_chip / (tokens_per_step / best_sec)
    if CHUNKS > 1:
        plain = make_plain_step(model, tx)
        plain_sec, _ = timed(plain, False, (tokens, targets, key))
        vs_fullbatch = pipe_tps_chip / (tokens_per_step / plain_sec)
    else:
        vs_fullbatch = vs_baseline

    # dots_saveable saves EVERY matmul output, so its recompute re-runs only
    # elementwise ops — zero extra MACs, hardware FLOPs = required. Other
    # policies re-run some matmuls; without a per-policy MAC model, keep the
    # mode's full-recompute count as the honest upper bound.
    hw_mode = ("never" if policy is not None
               and REMAT_POLICY == "dots_saveable" else CHECKPOINT)
    req_tok, hw_tok = train_flops_per_token(cfg, hw_mode, CHUNKS)
    model_flops = req_tok * tokens_per_step
    peak = peak_flops_per_chip()
    mfu = (req_tok * pipe_tps_chip) / peak
    hfu = (hw_tok * pipe_tps_chip) / peak

    # The same numbers as a StepReport: the bubble/MFU/memory fields in
    # the exact schema live training emits.
    report = StepReport.compute(
        step=0, wall_sec=sec_per_step, tokens=tokens_per_step,
        n_stages=n_stages, chunks=CHUNKS, checkpoint=hw_mode,
        schedule="1f1b", loss=loss, model_cfg=cfg,
        analytic_bubble=bubble_fraction(CHUNKS, n_stages),
        measured_bubble=measured_bubble,
        measured_bubble_method=bubble_method,
        memory=device_memory_peaks(), platform=platform,
        device_kind=jax.devices()[0].device_kind)

    print(json.dumps({
        "metric": "train_tokens_per_sec_per_chip",
        "value": round(pipe_tps_chip, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(vs_baseline, 4),
        "vs_fullbatch": round(vs_fullbatch, 4),
        "baseline_sec_per_step": baseline_styles,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_stages": n_stages,
        "chunks": CHUNKS,
        "checkpoint": CHECKPOINT,
        "remat_policy": REMAT_POLICY if policy is not None else "none",
        "mu_dtype": str(mu_dtype),
        "params": n_params,
        "model_flops": model_flops,
        "mfu": round(mfu, 4),
        "hfu": round(hfu, 4),
        "analytic_bubble": round(bubble_fraction(CHUNKS, n_stages), 4),
        "measured_bubble": round(measured_bubble, 4),
        "measured_bubble_method": bubble_method,
        "measured_bubble_multistage": bubble_multistage,
        "front_door_tax": front_door_tax,
        "zb_split": zb_split_summary,
        "serve": serve_summary,
        "chaos": chaos_summary,
        "fleet": fleet_summary,
        "elastic": elastic_summary,
        "plan": plan_summary,
        "chaos_smoke": chaos_smoke,
        "final_loss": round(loss, 4),
        "step_report": report.to_json(),
        "config": dataclasses.asdict(
            dataclasses.replace(cfg, compute_dtype=str(cfg.compute_dtype))),
    }))


if __name__ == "__main__":
    main()
