"""Training loop for the pipelined Transformer LM (tutorial parity).

Reference driver semantics (``main.py:180-234,273``): Adam(lr=5.0) +
StepLR(step=1, gamma=0.95), grad-clip 0.5, CrossEntropy on the last stage,
~8·bptt tokens per "epoch", train per checkpoint mode. Re-idiomized: one
jitted train step (forward pipeline + in-pipeline loss + backward + clip +
Adam) over the SPMD executor, metrics to stdout — step loss, tokens/s,
and the analytic pipeline-bubble fraction (the BASELINE.md north-star).
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
import warnings
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ..core import microbatch as mb
from ..core.schedule import bubble_fraction
from ..models.transformer_lm import LMConfig, PipelinedLM
from ..obs import events as ev
from ..obs.meters import profile_trace
from ..obs.telemetry import (StepReport, device_memory_peaks, get_registry,
                             peak_flops_per_chip, record_stall)
from ..parallel.mesh import make_mesh
from ..parallel.spmd import SpmdPipeline, stack_stage_params
from ..data import lm_text
from ..utils.platform import sync_if_forced_cpu
from ..utils.rng import make_key
from .state import TrainState

__all__ = ["TrainerConfig", "Trainer"]


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    """Driver hyperparameters (reference ``main.py:101-120,182-185``)."""

    batch_size: int = 32
    # Reference uses 10 (main.py:84); default 8 here so eval batches divide
    # into `chunks` micro-batches without zero-padding skewing the mean loss.
    eval_batch_size: int = 8
    bptt: int = 128
    chunks: int = 4
    checkpoint: str = "except_last"
    n_stages: int = 2
    n_data: int = 1
    lr: float = 5.0            # reference main.py:183 (Adam at lr=5.0, sic)
    lr_gamma: float = 0.95     # StepLR(1.0, gamma=0.95), main.py:185
    grad_clip: float = 0.5     # main.py:219
    seed: int = 1234
    schedule: str = "gpipe"    # gpipe | 1f1b | zb-h1 | interleaved
                               # | interleaved-1f1b
    # Adam first-moment storage dtype: 'bfloat16' halves the m-moment HBM
    # traffic (docs/mfu_roofline.md; its step-time effect is not measured
    # on the current installation); None keeps f32.
    mu_dtype: Optional[str] = None
    interleave: int = 2        # virtual stages per device (interleaved only)
    # Directory for TensorBoard scalar event files (SURVEY §5 "stdout +
    # TensorBoard scalars"); None disables. Scalars mirror the stdout log
    # lines (train/loss, train/ppl, train/tok_s, train/ms_batch, train/lr,
    # pipeline/bubble) plus per-epoch train/epoch_loss and eval/loss.
    tb_dir: Optional[str] = None
    # ZeRO-1: shard Adam's moments over the data axis (each data replica
    # owns 1/n_data of the optimizer state; the update runs sharded and the
    # refreshed params are all-gathered — see train/zero.py). Layout-only:
    # matches the replicated optimizer up to float reduction order.
    zero: bool = False
    # Ring depth for the native batch prefetcher (C++ producer thread
    # assembling batches off the hot loop, data/native.py BatchPrefetcher);
    # 0 = assemble inline with get_batch (identical batches either way —
    # asserted in tests/test_prefetch.py). Falls back to inline assembly
    # when no C++ toolchain is available.
    prefetch_depth: int = 0
    # Unified telemetry (docs/observability.md): directory receiving the
    # structured JSONL event log (`events.jsonl` — step spans + per-step
    # StepReport records) and periodic profiler traces. None disables —
    # the loop then talks to no-op sinks (no file writes, no clock reads).
    telemetry_dir: Optional[str] = None
    # With telemetry_dir set: capture a profiler trace of one step every N
    # steps into telemetry_dir/trace_step{N} (0 disables). Feed captures to
    # tools/timeline_report.py for per-stage busy/idle attribution.
    profile_every: int = 0
    # Anomaly detection + recovery (docs/resilience.md): a
    # resilience.ResilienceConfig arms the guarded train step (in-jit
    # finiteness/loss-spike check, where-select skip-step), the bounded
    # rewind controller and data-iterator retry. None — the default —
    # keeps the train step program byte-identical to the unguarded build
    # (pinned in tests/test_resilience.py).
    resilience: Optional[Any] = None
    # Elastic degraded-mode training (docs/resilience.md): a
    # resilience.ElasticConfig arms the elastic train step — the guarded
    # step plus a traced stage-kill channel and a per-stage gradient
    # heartbeat in the aux carry — and the buddy-replication controller
    # that snapshots every stage's shard to its ring neighbor. On a
    # persistently-silent stage the epoch raises
    # resilience.StageLost; resilience.replan_after_loss rebuilds the
    # run over the n-1 survivors (see tools/elastic_bench.py). Requires
    # ``resilience``; None — the default — adds nothing to the program
    # (pinned in tests/test_elastic.py).
    elastic: Optional[Any] = None
    # Auto-planner front door (core/planner.py, docs/planning.md): a
    # planner ``Plan``, a path to a saved PLAN json, or the string
    # "auto". Resolved in Trainer.__init__ BEFORE executor dispatch: the
    # plan's schedule / chunks (m) / interleave / checkpoint replace the
    # corresponding fields here. "auto" searches the Trainer-supported
    # schedule families over an analytic uniform profile — PipelinedLM's
    # stage bodies are homogeneous, so uniform relative costs are exact —
    # at this config's stage count, batch size and checkpoint mode.
    # None (default): the hand-picked fields below stand.
    plan: Optional[Any] = None
    # Per-device memory cap (bytes) handed to the planner's search when
    # plan="auto"; None = uncapped.
    plan_memory_cap: Optional[int] = None


def _resolve_plan_config(model_cfg: LMConfig,
                         cfg: TrainerConfig) -> TrainerConfig:
    """Fold a planner Plan into the TrainerConfig (cfg.plan is set).

    "auto" runs the search here — schedule family × m × interleave over
    an analytic uniform profile (homogeneous PipelinedLM stage bodies),
    serialized cost mode on CPU hosts, parallel on real accelerators —
    restricted to the families this Trainer can execute. A Plan object or
    saved-plan path is adopted as-is (its schedule must be one the
    Trainer dispatches on)."""
    from ..core.planner import Plan, search, uniform_profile

    plan = cfg.plan
    if isinstance(plan, str) and plan != "auto":
        plan = Plan.load(plan)
    if plan == "auto":
        mode = ("serialized"
                if jax.devices()[0].platform == "cpu" else "parallel")
        # Per-layer analytic sizes: one boundary activation row is
        # [bptt, d_model] f32; transformer-block params are the attention
        # (4 d^2) + FFN (2 d d_ff) matmuls.
        act = cfg.bptt * model_cfg.d_model * 4
        p_layer = (4 * model_cfg.d_model ** 2
                   + 2 * model_cfg.d_model * model_cfg.d_ff) * 4
        prof = uniform_profile(
            model_cfg.n_layers, rows=1, mode=mode,
            layer_act_bytes=act, layer_param_bytes=p_layer)
        m_cands = sorted({m for m in (2, 4, 8, 16, 32, cfg.chunks)
                          if m > 0 and cfg.batch_size % m == 0})
        plans = search(
            prof, n_devices=cfg.n_stages, m_candidates=m_cands,
            batch_rows=cfg.batch_size,
            schedules=("gpipe", "1f1b", "zb-h1", "interleaved-1f1b"),
            interleave_candidates=(cfg.interleave,),
            checkpoint=cfg.checkpoint,
            memory_cap_bytes=cfg.plan_memory_cap,
            uniform_only=True)
        if not plans:
            raise ValueError(
                "plan='auto' found no feasible plan: every candidate "
                "failed verification, phase compilation, or the "
                "plan_memory_cap — raise the cap or hand-pick a config")
        plan = plans[0]
    widths = set(plan.balance)
    if len(widths) > 1:
        warnings.warn(
            f"plan balance {list(plan.balance)} is non-uniform; the "
            f"Trainer's PipelinedLM factors layers uniformly over "
            f"virtual stages, so only the plan's stage COUNT is honored "
            f"here (drive Pipe(plan=...) for heterogeneous cuts)",
            stacklevel=3)
    kw: Dict[str, Any] = {"plan": plan, "schedule": plan.schedule,
                          "chunks": plan.m, "checkpoint": plan.checkpoint,
                          "n_stages": plan.n_devices}
    if plan.v > 1:
        kw["interleave"] = plan.v
    return dataclasses.replace(cfg, **kw)


class Trainer:
    """Builds the mesh, model, optimizer and the jitted step; runs epochs."""

    def __init__(self, model_cfg: LMConfig, cfg: TrainerConfig,
                 devices: Optional[List[jax.Device]] = None,
                 chaos=None):
        self.model_cfg = model_cfg
        if cfg.plan is not None:
            cfg = _resolve_plan_config(model_cfg, cfg)
        self.cfg = cfg
        # Fault injection (resilience.ChaosPlan): the activation hook
        # wraps the model's pre_fn ONLY when a plan is supplied, so the
        # default build traces the exact original functions.
        self.chaos = chaos

        def _mk_model(n_stages: int) -> PipelinedLM:
            m = PipelinedLM(model_cfg, n_stages)
            if chaos is not None:
                from ..resilience.chaos import wrap_pre_fn, wrap_stage_fn
                m.pre_fn = wrap_pre_fn(m.pre_fn)
                m.stage_fn = wrap_stage_fn(m.stage_fn)
            return m

        self.mesh = make_mesh(cfg.n_stages, cfg.n_data, devices=devices)
        if cfg.schedule == "interleaved":
            # n_stages devices, each hosting `interleave` virtual stages:
            # the model factors into n_stages*interleave stage bodies.
            from ..parallel.interleaved import InterleavedSpmdPipeline
            self.n_virtual = cfg.n_stages * cfg.interleave
            self.model = _mk_model(self.n_virtual)
            self.pipe = InterleavedSpmdPipeline(
                self.mesh, self.model.stage_fn, v=cfg.interleave,
                pre_fn=self.model.pre_fn, post_fn=self.model.loss_post_fn,
                post_with_batch=True, checkpoint=cfg.checkpoint)
        elif cfg.schedule in ("1f1b", "interleaved-1f1b", "zb-h1"):
            # True 1F1B: the manual fwd+bwd executor caps live activations at
            # min(chunks, n_stages) per stage and applies the exact
            # per-micro-batch checkpoint policy (parallel.scheduled).
            # interleaved-1f1b hosts `interleave` virtual stages per device
            # (both passes from one static table; see core.schedule).
            from ..core.schedule import InterleavedOneFOneBSchedule
            from ..parallel.scheduled import ScheduledPipeline
            split_kw = {}
            if cfg.schedule == "interleaved-1f1b":
                sched = InterleavedOneFOneBSchedule(
                    interleave=cfg.interleave)
                self.n_virtual = cfg.n_stages * cfg.interleave
            else:
                # "1f1b" or "zb-h1" (split-backward zero-bubble tables).
                # zb-h1's recommendation is GATED on the committed cost
                # model (docs/zb_crossover.md): it beats 1f1b on parallel
                # hardware only when the measured split overhead sigma is
                # below the config's breakeven sigma*. With the structural
                # B/W split (split_stage="auto", core/remat.py) the cpu8
                # recalibration measures sigma <= 1.41 — below every swept
                # breakeven (ZB_CROSSOVER_r05.json) — so the Trainer
                # engages the split whenever the checkpoint mode allows
                # it; recompute modes fall back to the fused backward at
                # B (W slots idle) and warn.
                if cfg.schedule == "zb-h1":
                    if cfg.checkpoint == "never":
                        split_kw["split_stage"] = "auto"
                    else:
                        from ..obs.zb_model import crossover
                        row = crossover(cfg.chunks, cfg.n_stages,
                                        sigma=1.0)
                        warnings.warn(
                            f"zb-h1 at (m={cfg.chunks}, "
                            f"n={cfg.n_stages}) with "
                            f"checkpoint={cfg.checkpoint!r}: the "
                            f"structural B/W split needs "
                            f"checkpoint='never', so the fused backward "
                            f"runs at B and the zero-bubble advantage "
                            f"(breakeven sigma* "
                            f"{row['breakeven_sigma']:.2f}, measured "
                            f"split sigma <= 1.41 — docs/zb_crossover.md) "
                            f"is forfeited.", stacklevel=2)
                sched = cfg.schedule
                self.n_virtual = cfg.n_stages
            self.model = _mk_model(self.n_virtual)
            self.pipe = ScheduledPipeline(
                self.mesh, self.model.stage_fn, pre_fn=self.model.pre_fn,
                post_fn=self.model.loss_post_fn, checkpoint=cfg.checkpoint,
                schedule=sched, **split_kw)
        elif cfg.schedule == "gpipe":
            self.n_virtual = cfg.n_stages
            self.model = _mk_model(cfg.n_stages)
            self.pipe = SpmdPipeline(
                self.mesh, self.model.stage_fn, pre_fn=self.model.pre_fn,
                post_fn=self.model.loss_post_fn, post_with_batch=True,
                checkpoint=cfg.checkpoint)
        else:
            raise ValueError(f"unknown schedule {cfg.schedule!r}")
        self._scheduled = cfg.schedule in ("1f1b", "interleaved-1f1b",
                                           "zb-h1")
        if self._scheduled:
            # The manual executor is training-only; eval (no grads, no remat)
            # runs an AD forward executor on the same mesh and params. The
            # executor must match the param layout: interleaved stacking
            # ([v, ...] per device) needs the interleaved executor — a plain
            # SpmdPipeline would read only group 0's slice and silently
            # evaluate d of the v*d virtual stages.
            if cfg.schedule == "interleaved-1f1b":
                from ..parallel.interleaved import InterleavedSpmdPipeline
                self.eval_pipe = InterleavedSpmdPipeline(
                    self.mesh, self.model.stage_fn, v=cfg.interleave,
                    pre_fn=self.model.pre_fn,
                    post_fn=self.model.loss_post_fn, post_with_batch=True,
                    checkpoint="never")
            else:
                self.eval_pipe = SpmdPipeline(
                    self.mesh, self.model.stage_fn, pre_fn=self.model.pre_fn,
                    post_fn=self.model.loss_post_fn, post_with_batch=True,
                    checkpoint="never")
        else:
            self.eval_pipe = dataclasses.replace(self.pipe,
                                                 checkpoint="never") \
                if cfg.checkpoint != "never" else self.pipe

        # StepLR per epoch (reference main.py:185): the per-epoch learning
        # rate is a traced argument of the jitted step, not a Python
        # closure — closures bake at trace time.
        self.tx = optax.chain(
            optax.clip_by_global_norm(cfg.grad_clip),
            optax.scale_by_adam(
                mu_dtype=jnp.dtype(cfg.mu_dtype) if cfg.mu_dtype else None),
        )
        # ZeRO-1 layout trees; populated by init_state (they need concrete
        # placed params). The jitted step traces on first call, after that.
        self._zero_shardings = None
        self._param_shardings = None
        if cfg.elastic is not None:
            if cfg.resilience is None:
                raise ValueError(
                    "TrainerConfig.elastic requires resilience= (the "
                    "elastic rung extends the guarded step's ladder)")
            if cfg.schedule in ("interleaved", "interleaved-1f1b"):
                raise ValueError(
                    "elastic training needs one stage per device "
                    f"(schedule {cfg.schedule!r} interleaves "
                    f"{cfg.interleave} virtual stages per device)")
            self._step_fn = jax.jit(self._train_step_elastic,
                                    donate_argnums=(0,))
        elif cfg.resilience is not None:
            self._step_fn = jax.jit(self._train_step_guarded,
                                    donate_argnums=(0,))
        else:
            self._step_fn = jax.jit(self._train_step, donate_argnums=(0,))
        self._eval_fn = jax.jit(self._eval_loss)
        if cfg.tb_dir is not None:
            from ..obs.tb_writer import ScalarWriter
            self.tb: Optional["ScalarWriter"] = ScalarWriter(cfg.tb_dir)
        else:
            self.tb = None
        # Telemetry sinks: the process-local registry (cheap counters the
        # executors also feed) and the structured event log. With no
        # telemetry_dir the event log is the shared null sink — call sites
        # stay unconditional, writes cost nothing.
        self.registry = get_registry()
        if cfg.telemetry_dir is not None:
            os.makedirs(cfg.telemetry_dir, exist_ok=True)
            self.events: Any = ev.EventLog(
                os.path.join(cfg.telemetry_dir, "events.jsonl"))
        else:
            self.events = ev.NULL_EVENT_LOG
        # the step's clock, and the running mean of a step's wall time from
        # the second step on (the first compiles): what a phase that waits
        # for the device is held against (``_check_stall``)
        self._clock: Callable[[], float] = time.perf_counter
        self._step_ewma: Optional[float] = None

    # --- state ---

    def init_state(self, key: Optional[jax.Array] = None) -> TrainState:
        key = key if key is not None else make_key(self.cfg.seed)
        sp, prep, postp = self.model.init(key)
        if self.cfg.schedule in ("interleaved", "interleaved-1f1b"):
            from ..parallel.interleaved import stack_interleaved_params
            stacked = stack_interleaved_params(sp, self.cfg.n_stages)
        else:
            stacked = stack_stage_params(sp)
        params = self._place((stacked, prep, postp))
        # tx.init's zeros_like inherits the placement; freshly-created leaves
        # (adam's count, the step counter) get replicated explicitly. Every
        # leaf then carries a mesh sharding — required both for checkpoint
        # restore (the template's shardings drive orbax) and for multi-chip.
        opt_state = self._replicate_unsharded(self.tx.init(params))
        if self.cfg.zero:
            from . import zero
            self._zero_shardings = zero.moment_shardings(
                self.mesh, params, opt_state)
            self._param_shardings = jax.tree_util.tree_map(
                lambda a: a.sharding, params)
            opt_state = zero.shard_moments(opt_state, self._zero_shardings)
        step = self._replicate_unsharded(jnp.zeros((), jnp.int32))
        return TrainState(params=params, opt_state=opt_state, step=step)

    def _replicate_unsharded(self, tree):
        from jax.sharding import NamedSharding, PartitionSpec as P

        repl = NamedSharding(self.mesh, P())

        def fix(a):
            if isinstance(a, jax.Array) and not isinstance(a.sharding,
                                                           NamedSharding):
                return jax.device_put(a, repl)
            return a

        return jax.tree_util.tree_map(fix, tree)

    def _place(self, params):
        """Commit params to their mesh shardings (stage-stacked / replicated)."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import STAGE_AXIS

        sp, prep, postp = params
        staged = NamedSharding(self.mesh, P(STAGE_AXIS))
        repl = NamedSharding(self.mesh, P())
        sp = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, staged), sp)
        prep, postp = jax.tree_util.tree_map(
            lambda a: jax.device_put(a, repl), (prep, postp))
        return (sp, prep, postp)

    def num_params(self, state: TrainState) -> int:
        return sum(int(a.size) for a in jax.tree_util.tree_leaves(
            state.params))

    def install_autosave(self, directory: str,
                         signals: Optional[List[int]] = None) -> None:
        """Preemption-aware checkpointing: on SIGTERM (the signal cloud
        schedulers send before reclaiming a TPU VM), finish the in-flight
        step, save via :meth:`save`, and stop the epoch loop cleanly.

        The reference has no elastic story at all (SURVEY §5: "multi-host
        failure = job restart from checkpoint"); this supplies the half
        that makes restarts cheap — the checkpoint exists when the
        preemption lands, resume via ``init_state`` + ``restore_checkpoint``.
        The handler only sets a flag: all saving happens on the training
        thread between steps (signal-safe by construction).
        """
        import signal as _signal

        self._autosave_dir = directory
        self._stop_requested = False

        def _handler(signum, frame):
            self._stop_requested = True

        for sig in (signals if signals is not None
                    else [_signal.SIGTERM]):
            _signal.signal(sig, _handler)

    def _autosave_pending(self) -> bool:
        return bool(getattr(self, "_stop_requested", False))

    def _autosave(self, state: TrainState,
                  log_fn: Callable[[str], None]) -> None:
        self.save(self._autosave_dir, state)
        log_fn(f"| autosave: step {int(state.step)} checkpointed to "
               f"{self._autosave_dir} (stop requested)")

    def generate(self, state: TrainState, prompt, *,
                 max_new_tokens: int = 32, temperature: float = 0.0,
                 top_k: Optional[int] = None, num_beams: int = 1,
                 key: Optional[jax.Array] = None):
        """Sample continuations from the trained weights — the train-state
        params (stage-stacked, mesh-placed) unstack straight into the
        KV-cached generator; no conversion, no checkpoint round-trip."""
        from ..inference import GenerationConfig, Generator

        sp, pre, post = jax.tree_util.tree_map(np.asarray, state.params)
        if self.cfg.schedule in ("interleaved", "interleaved-1f1b"):
            from ..parallel.interleaved import unstack_interleaved_params
            per_stage = unstack_interleaved_params(sp, self.cfg.n_stages)
        else:
            from ..parallel.spmd import unstack_stage_params
            per_stage = unstack_stage_params(sp, self.n_virtual)
        gen = Generator(self.model,
                        GenerationConfig(max_new_tokens=max_new_tokens,
                                         temperature=temperature,
                                         top_k=top_k, num_beams=num_beams))
        # the generator flattens blocks itself; hand it one "stage" per
        # virtual stage in true layer order
        return gen.generate((per_stage, pre, post), prompt, key=key)

    def save(self, directory: str, state: TrainState,
             step: Optional[int] = None) -> None:
        """Checkpoint with the stage-stack layout recorded (so serving can
        reconstruct layer order; interleaved schedules stack the virtual
        stages device-major-permuted)."""
        from .state import save_checkpoint

        cfg = self.cfg
        interleaved = cfg.schedule in ("interleaved", "interleaved-1f1b")
        layout = {
            "stacking": "interleaved" if interleaved else "stage",
            "n_stages": cfg.n_stages,
            "interleave": cfg.interleave if interleaved else 1,
        }
        save_checkpoint(directory, state,
                        int(state.step) if step is None else step,
                        layout=layout)

    def analytic_bubble(self) -> float:
        cfg = self.cfg
        if cfg.schedule == "interleaved":
            from ..core.schedule import InterleavedSchedule
            return InterleavedSchedule(v=cfg.interleave).device_bubble(
                cfg.chunks, cfg.n_stages)
        if cfg.schedule == "interleaved-1f1b":
            from ..core.schedule import InterleavedOneFOneBSchedule
            return InterleavedOneFOneBSchedule(
                interleave=cfg.interleave).bubble(cfg.chunks, cfg.n_stages)
        return bubble_fraction(cfg.chunks, cfg.n_stages)

    # --- steps ---

    def _loss(self, params, x, w, key, train):
        """Row-masked mean loss: ``w`` zeroes the rows ``stack_scatter``
        zero-padded for non-divisible batches, so fake rows never contaminate
        loss or gradients (VERDICT r1 #7)."""
        sp, prep, postp = params
        if train and self._scheduled:
            # The manual executor has no forward-only path; its loss comes
            # with grads attached (the hot path, _train_step, uses both).
            loss, _ = self.pipe.loss_and_grad(sp, prep, postp, x, w, key=key)
            return loss
        pipe = self.pipe if train else self.eval_pipe
        per_row = pipe(sp, prep, postp, x, key=key, train=train)
        return jnp.sum(per_row * w) / jnp.sum(w)

    def _compute_update(self, state: TrainState, x, w, key, lr,
                        inject=None, magnitude=None):
        """Shared step body: loss+grads, optional fault injection,
        optimizer update. Returns ``(params, opt_state, loss, grads)``;
        with ``inject=None`` (the unguarded step) it traces the exact
        pre-resilience program."""
        if self._scheduled:
            sp, prep, postp = state.params
            loss, grads = self.pipe.loss_and_grad(sp, prep, postp, x, w,
                                                  key=key)
        else:
            loss, grads = jax.value_and_grad(self._loss)(
                state.params, x, w, key, True)
        if inject is not None:
            from ..resilience.chaos import apply_train_faults
            loss, grads = apply_train_faults(inject, magnitude, loss, grads)
        with ev.device_scope(ev.OPTIMIZER):       # the clip is in tx
            updates, opt_state = self.tx.update(grads, state.opt_state,
                                                state.params)
            updates = jax.tree_util.tree_map(lambda u: -lr * u, updates)
            params = optax.apply_updates(state.params, updates)
        if self.cfg.zero:
            # ZeRO-1 layout pins: new moments stay data-sharded (XLA then
            # partitions the Adam update over the data axis), new params
            # return to their data-replicated placement (XLA inserts the
            # ZeRO all-gather here).
            from . import zero
            if self._zero_shardings is None:
                raise RuntimeError(
                    "TrainerConfig(zero=True) requires init_state() to run "
                    "before the first step (it derives the ZeRO layout from "
                    "the placed params)")
            opt_state = zero.constrain_moments(opt_state,
                                               self._zero_shardings)
            params = jax.tree_util.tree_map(
                lambda a, s: jax.lax.with_sharding_constraint(a, s),
                params, self._param_shardings)
        return params, opt_state, loss, grads

    def _sync(self, loss, step: int) -> float:
        """A blocking read of a loss: the host waits for that step."""
        t0, cpu0 = self._clock(), time.process_time()
        with self.events.span(ev.TRAIN_SYNC, step=step):
            value = float(loss)
        self._check_stall(step, ev.TRAIN_SYNC, self._clock() - t0, cpu0,
                          waits=True)
        return value

    def _check_stall(self, step: int, phase: str, wall: float, cpu0: float,
                     waits: bool = False) -> None:
        """The serve engine's stall rule on a phase of the train step: over
        ``events.STALL_SEC``, and for a phase that ``waits`` for the device
        three times a step's running mean more, it is counted
        (``train.stalls``, ``train.stall_sec{phase=}``) and named once.
        Before there is a mean (the first step compiles) nothing is."""
        mean = self._step_ewma
        if mean is not None and wall > ev.STALL_SEC + (3 * mean if waits
                                                       else 0.0):
            record_stall(self.registry, "train", f"trainer: step {step}",
                         phase, wall, time.process_time() - cpu0)

    def _count_step_trace(self):
        """Runs at trace time only (as the serve programs' ``*_traces``
        do): how often a step program was traced, retraces included."""
        self.registry.counter("train.step_traces").inc()

    def _train_step(self, state: TrainState, x, w, key, lr):
        self._count_step_trace()
        params, opt_state, loss, _ = self._compute_update(state, x, w,
                                                          key, lr)
        return TrainState(params=params, opt_state=opt_state,
                          step=state.step + 1), loss

    def _train_step_guarded(self, state: TrainState, aux, x, w, key, lr,
                            inject, magnitude):
        """The resilient step: same update as :meth:`_train_step` plus
        (a) chaos injection selected by the traced ``inject`` code and
        (b) the fused anomaly check whose verdict ``where``-selects the
        pre-step params/opt_state back in on a bad step (skip-step — the
        step counter still advances, so the LR/PRNG walk is unaffected).
        ``aux`` carries ``(loss EWMA, consecutive anomalies, total
        anomalies)`` on device; the host reads it on its own cadence
        (``ResilienceConfig.check_every``) — no extra sync here."""
        from ..resilience.chaos import inject_scope
        from ..resilience.detect import step_guard

        self._count_step_trace()
        rc = self.cfg.resilience
        ewma, consec, total = aux
        with inject_scope(inject):
            params, opt_state, loss, grads = self._compute_update(
                state, x, w, key, lr, inject=inject, magnitude=magnitude)
        ok, new_ewma = step_guard(
            loss, grads, ewma, state.step, spike_factor=rc.spike_factor,
            warmup_steps=rc.warmup_steps, ewma_alpha=rc.ewma_alpha)

        def select(new, old):
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(ok, a, b), new, old)

        params = select(params, state.params)
        opt_state = select(opt_state, state.opt_state)
        bad = (~ok).astype(jnp.int32)
        new_aux = (new_ewma, jnp.where(ok, jnp.int32(0), consec + 1),
                   total + bad)
        return TrainState(params=params, opt_state=opt_state,
                          step=state.step + 1), loss, new_aux

    def _train_step_elastic(self, state: TrainState, aux, x, w, key, lr,
                            inject, magnitude, kill):
        """The elastic step: the guarded step plus (a) a traced ``kill``
        code (a stage index, or KILL_NONE) that zeroes the killed
        stage's output through the wrapped stage fn, and (b) a
        per-stage gradient heartbeat appended to the aux carry — a
        ``[n_stages]`` int32 silent-streak vector the elastic
        controller reads on its host cadence. Killing stage ``j``
        silences grads for every stage ``<= j`` (the zero scale
        annihilates the backward signal), so the controller localizes
        the kill as the largest persistently-silent index. Streaks fold
        only guard-accepted steps: a NaN/spike step must escalate
        through the numeric ladder, never masquerade as a dead stage."""
        from ..resilience.chaos import inject_scope, kill_scope
        from ..resilience.detect import stage_heartbeat, step_guard

        self._count_step_trace()
        rc = self.cfg.resilience
        ewma, consec, total, hb = aux
        with inject_scope(inject), kill_scope(kill):
            params, opt_state, loss, grads = self._compute_update(
                state, x, w, key, lr, inject=inject, magnitude=magnitude)
        ok, new_ewma = step_guard(
            loss, grads, ewma, state.step, spike_factor=rc.spike_factor,
            warmup_steps=rc.warmup_steps, ewma_alpha=rc.ewma_alpha)
        beat = stage_heartbeat(grads[0], self.n_virtual)
        silent = beat == jnp.float32(0.0)
        new_hb = jnp.where(ok, jnp.where(silent, hb + 1, jnp.int32(0)), hb)

        def select(new, old):
            return jax.tree_util.tree_map(
                lambda a, b: jnp.where(ok, a, b), new, old)

        params = select(params, state.params)
        opt_state = select(opt_state, state.opt_state)
        bad = (~ok).astype(jnp.int32)
        new_aux = (new_ewma, jnp.where(ok, jnp.int32(0), consec + 1),
                   total + bad, new_hb)
        return TrainState(params=params, opt_state=opt_state,
                          step=state.step + 1), loss, new_aux

    def elastic_store(self):
        """The trainer's buddy-replication store, created on first use.
        Lives on the Trainer (not the epoch) so the snapshot survives
        the ``StageLost`` raise and ``replan_after_loss`` can restore
        from it."""
        if getattr(self, "_buddy_store", None) is None:
            from ..resilience.elastic import BuddyStore
            self._buddy_store = BuddyStore(
                self.mesh, self.cfg.n_stages,
                verify=getattr(self.cfg.elastic, "verify_replication", True),
                registry=self.registry, events=self.events,
                snapshot_dir=getattr(self.cfg.elastic, "snapshot_dir", None))
        return self._buddy_store

    def _eval_loss(self, params, x, w):
        return self._loss(params, x, w, make_key(0), False)

    # --- data plumbing ---

    def _make_x(self, data: np.ndarray, target: np.ndarray):
        """Stack-scatter the batch; return it with the valid-row mask."""
        x = {"tokens": jnp.asarray(data), "targets": jnp.asarray(target)}
        stacked, n_rows = mb.stack_scatter(x, self.cfg.chunks)
        return stacked, mb.valid_row_mask(stacked, n_rows)

    def _batches(self, source: np.ndarray, n: int, start: int = 0):
        """Yield full (data, target) batches ``start`` .. ``n``-1.

        With ``prefetch_depth > 0`` (and a toolchain), assembly runs on the
        native producer thread; the yielded slot views are copied before
        handing out because jax CPU arrays may alias aligned host numpy
        buffers, and a slot may be overwritten as soon as the iterator
        advances past it — a small memcpy, the transpose gather stays off
        the hot loop.
        Otherwise: inline ``get_batch`` (the reference's walk), stopping at
        the first short tail batch to keep shapes static.

        ``start`` skips the first batches — the resume hook for
        :class:`~..resilience.RetryingIterator`, which rebuilds a failed
        iterator at its position.
        """
        cfg = self.cfg
        if cfg.prefetch_depth > 0:
            from ..data.native import BatchPrefetcher, prefetch_available
            if prefetch_available():
                with BatchPrefetcher(source, cfg.bptt,
                                     depth=cfg.prefetch_depth) as pf:
                    for i, (d, t) in enumerate(pf):
                        if i >= n:
                            break
                        if i < start:
                            continue
                        yield d.copy(), t.copy()
                return
        for b in range(start, n):
            data, target = lm_text.get_batch(source, b * cfg.bptt, cfg.bptt)
            if data.shape[1] < cfg.bptt:  # tail batch: keep shapes static
                return
            yield data, target

    # --- epochs ---

    def train_epoch(self, source: np.ndarray, epoch: int = 0,
                    state: Optional[TrainState] = None,
                    max_steps: Optional[int] = None,
                    log_every: int = 10,
                    log_fn: Callable[[str], None] = print,
                    start_step: int = 0):
        """One pass over ``source`` (a ``batchify``'d id matrix).

        ``start_step`` resumes the epoch mid-pass at a global batch
        index (the elastic recovery hook): batches, per-step PRNG folds
        and chaos indices all replay from the GLOBAL index, so a run
        rewound to step ``s`` and resumed with ``start_step=s`` walks
        the identical tape an uninterrupted run would.
        """
        cfg = self.cfg
        state = state if state is not None else self.init_state()
        lr = cfg.lr * cfg.lr_gamma ** epoch  # StepLR, main.py:185
        n = lm_text.num_batches(source, cfg.bptt)
        if max_steps is not None:
            n = min(n, max_steps)
        key = jax.random.fold_in(make_key(cfg.seed), epoch)

        tokens_per_step = cfg.batch_size * cfg.bptt
        # Per-step telemetry: registry instruments are live regardless (a
        # disabled registry hands back no-ops); StepReports and spans go to
        # the JSONL event log only when telemetry_dir is configured.
        telemetry_on = self.events is not ev.NULL_EVENT_LOG
        steps_ctr = self.registry.counter("train.steps")
        tokens_ctr = self.registry.counter("train.tokens")
        tps_gauge = self.registry.gauge("train.tokens_per_sec")
        peak = peak_flops_per_chip() if telemetry_on else None
        device_kind = jax.devices()[0].device_kind if telemetry_on else None

        # Resilience plumbing — all of it gated on cfg.resilience so the
        # default loop touches none of these objects.
        rc = cfg.resilience
        resil = None
        elastic = None
        aux = None
        if rc is not None:
            from ..resilience.recover import (ResilienceController,
                                              RetryingIterator)
            resil = ResilienceController(rc, self.registry, self.events,
                                         log_fn=log_fn)
            aux = (jnp.float32(0.0), jnp.int32(0), jnp.int32(0))
            if cfg.elastic is not None:
                from ..resilience.elastic import ElasticController
                elastic = ElasticController(
                    cfg.elastic, self.elastic_store(),
                    registry=self.registry, events=self.events,
                    log_fn=log_fn)
                aux = aux + (jnp.zeros((self.n_virtual,), jnp.int32),)
            batch_iter = RetryingIterator(
                lambda pos: self._batches(source, n, start=pos),
                retries=rc.data_retries, backoff_s=rc.data_backoff_s,
                chaos=self.chaos, registry=self.registry,
                events=self.events, start=start_step)
        else:
            batch_iter = self._batches(source, n, start=start_step)

        t_first = t0 = time.perf_counter()
        losses = []
        w = None
        for i, (data, target) in enumerate(batch_iter):
            # b is the GLOBAL batch index (data position, PRNG fold,
            # chaos index); i counts this call's iterations (compile
            # sync, steady-state timing).
            b = start_step + i
            tracing = bool(telemetry_on and cfg.profile_every
                           and (b + 1) % cfg.profile_every == 0)
            t_step, cpu_step = self._clock(), time.process_time()
            with contextlib.ExitStack() as scopes:
                if tracing:
                    trace_dir = os.path.join(cfg.telemetry_dir,
                                             f"trace_step{b + 1}")
                    scopes.enter_context(profile_trace(trace_dir))
                scopes.enter_context(self.events.span(ev.STEP, step=b,
                                                      epoch=epoch))
                # everything the host does for a step besides the call
                with self.events.span(ev.TRAIN_BATCH, step=b):
                    x, mask = self._make_x(data, target)
                    # Row count is constant until the tail-batch break, so
                    # the valid-row mask is too — build it once, not per
                    # step.
                    w = mask if w is None else w
                    args = (x, w, jax.random.fold_in(key, b),
                            jnp.float32(lr))
                    if rc is not None:
                        inject, mag = (self.chaos.train_inject(b)
                                       if self.chaos is not None
                                       else (0, 1.0))
                        args += (jnp.int32(inject), jnp.float32(mag))
                    if elastic is not None:
                        from ..resilience.chaos import KILL_NONE
                        kill = (self.chaos.train_kill(b)
                                if self.chaos is not None else KILL_NONE)
                        args += (jnp.int32(kill),)
                t_batch = self._clock()
                # the call: enqueue time on an asynchronous backend, not
                # step time
                with self.events.span(ev.TRAIN_DISPATCH, step=b):
                    if rc is not None:
                        state, loss, aux = self._step_fn(state, aux, *args)
                    else:
                        state, loss = self._step_fn(state, *args)
                # Virtual-CPU platform: serialize steps (see
                # sync_if_forced_cpu — interleaved async runs livelock the
                # collective rendezvous there). No-op on real TPU.
                sync_if_forced_cpu(loss)
                if tracing:
                    jax.block_until_ready(loss)  # capture the whole step
            wall = self._clock() - t_step
            self._check_stall(b, ev.TRAIN_BATCH, t_batch - t_step, cpu_step)
            self._check_stall(b, ev.TRAIN_DISPATCH, wall - (t_batch - t_step),
                              cpu_step, waits=True)
            if i >= 1:
                self._step_ewma = wall if self._step_ewma is None \
                    else 0.8 * self._step_ewma + 0.2 * wall
            steps_ctr.inc()
            tokens_ctr.inc(tokens_per_step)
            if wall > 0:
                tps_gauge.set(tokens_per_step / wall)
            losses.append(loss)
            at_log = bool(log_every and (b + 1) % log_every == 0)
            if telemetry_on:
                # Caveat: on async-dispatch backends per-step wall time is
                # honest only at sync points (forced-CPU syncs every step;
                # elsewhere log/trace steps sync). compile_inclusive marks
                # the step-0 outlier.
                if tracing:
                    self.events.event("profile_trace", step=b,
                                      path=trace_dir)
                report = StepReport.compute(
                    step=int(state.step), wall_sec=wall,
                    tokens=tokens_per_step, n_stages=cfg.n_stages,
                    chunks=cfg.chunks, checkpoint=cfg.checkpoint,
                    schedule=cfg.schedule,
                    loss=self._sync(loss, b) if at_log else None,
                    model_cfg=self.model_cfg,
                    analytic_bubble=self.analytic_bubble(),
                    memory=(device_memory_peaks()
                            if at_log or i == 0 else {}),
                    compile_inclusive=(i == 0), peak_flops=peak,
                    platform=jax.default_backend(),
                    device_kind=device_kind, epoch=epoch)
                self.events.step_report(report)
                if self.tb is not None and at_log:
                    for tag, val in report.scalar_items():
                        self.tb.add_scalar(tag, val, int(state.step))
            if resil is not None:
                # Rewind/abort policy on the host cadence; may replace
                # (state, aux) with known-good copies or raise
                # TrainingAborted after the rewind budget. The elastic
                # heartbeat streak rides outside the numeric triple —
                # it survives a numeric rewind untouched.
                if elastic is not None:
                    state, aux3 = resil.after_step(b, state, aux[:3])
                    aux = aux3 + (aux[3],)
                    # Buddy capture on healthy cadence; raises StageLost
                    # once a stage's silent streak crosses dead_after.
                    state, aux = elastic.after_step(b, state, aux)
                else:
                    state, aux = resil.after_step(b, state, aux)
            if self._autosave_pending():
                self._autosave(state, log_fn)
                break
            if i == 0:
                self._sync(loss, b)       # sync out the compile
                t0 = time.perf_counter()  # steady-state timing from step 2
            if at_log:
                l = self._sync(losses[-1], b)
                # Steady-state ms/batch from step 2 on; the step-1 line has no
                # steady-state sample yet, so it reports the compile-inclusive
                # first-step time instead of a meaningless ~0.
                dt = ((time.perf_counter() - t0) / i if i >= 1
                      else time.perf_counter() - t_first)
                log_fn(f"| epoch {epoch} | step {b+1}/{n} "
                       f"| lr {lr:.3f} "
                       f"| ms/batch {dt*1000:.1f} "
                       f"| tok/s {tokens_per_step/dt:,.0f} "
                       f"| loss {l:.3f} | ppl {np.exp(min(l, 20.0)):.2f} "
                       f"| bubble {self.analytic_bubble():.1%}")
                if self.tb is not None:
                    gstep = int(state.step)
                    self.tb.add_scalar("train/loss", l, gstep)
                    self.tb.add_scalar("train/ppl",
                                       float(np.exp(min(l, 20.0))), gstep)
                    self.tb.add_scalar("train/tok_s",
                                       tokens_per_step / dt, gstep)
                    self.tb.add_scalar("train/ms_batch", dt * 1000, gstep)
                    self.tb.add_scalar("train/lr", lr, gstep)
                    self.tb.add_scalar("pipeline/bubble",
                                       self.analytic_bubble(), gstep)
                    self.tb.flush()  # visible live; crash loses nothing
        final = (self._sync(losses[-1], start_step + len(losses) - 1)
                 if losses else float("nan"))
        if self.tb is not None and losses:
            self.tb.add_scalar("train/epoch_loss", final, int(state.step))
            self.tb.flush()
        if telemetry_on:
            self.events.metrics_snapshot(self.registry)
            self.events.flush()
        # t0 was reset after step 0, so elapsed covers len(losses)-1 steps
        info = {"loss": final,
                "steps": len(losses),
                "sec_per_step": (time.perf_counter() - t0)
                / max(len(losses) - 1, 1)}
        if resil is not None:
            info["anomalies"] = resil.anomalies
            info["rewinds"] = resil.rewinds
            info["loss_ewma"] = float(aux[0])
        if elastic is not None:
            info["buddy_snapshots"] = elastic.snapshots
            # per-step loss series keyed by GLOBAL batch index, so a
            # resumed segment's trajectory can be compared against an
            # uninterrupted run's (tests + tools/elastic_bench.py)
            info["loss_by_step"] = {start_step + i: float(l)
                                    for i, l in enumerate(losses)}
        return state, info

    def evaluate(self, source: np.ndarray, state: TrainState,
                 max_steps: Optional[int] = None) -> float:
        """Mean eval loss over ``source`` (reference ``evaluate``,
        ``main.py:275-289``, there commented out). Logged to
        ``eval/loss`` when a TB writer is configured."""
        cfg = self.cfg
        n = lm_text.num_batches(source, cfg.bptt)
        if max_steps is not None:
            n = min(n, max_steps)
        total, count = 0.0, 0
        w = None
        for b in range(n):
            data, target = lm_text.get_batch(source, b * cfg.bptt, cfg.bptt)
            if data.shape[1] < cfg.bptt:
                break
            x, mask = self._make_x(data, target)
            w = mask if w is None else w
            loss = self._eval_fn(state.params, x, w)
            total += float(loss) * data.size
            count += data.size
        mean = total / max(count, 1)
        if self.tb is not None and count:
            self.tb.add_scalar("eval/loss", mean, int(state.step))
            self.tb.flush()
        return mean
