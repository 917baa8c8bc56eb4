"""Elastic degraded-mode training: survive stage loss, re-plan, resume.

The top rung of the recovery ladder (docs/resilience.md): skip-step
(PR 5) drops one poisoned update, rewind restores a known-good
snapshot, and THIS module survives the fault class neither can — a
pipeline stage that dies and stays dead. Three cooperating pieces:

* :class:`BuddyStore` — buddy replication. On a healthy-step cadence,
  every stage's shard of the stacked params + optimizer moments rides
  one extra ppermute hop to its ring neighbor ``(j+1) % n`` and is
  host-fetched there, with per-stage sha256 manifests
  (:func:`~pipe_tpu.train.state.stage_shard_manifest`) pinning the
  copy bitwise against the source shard. Any single stage loss is then
  recoverable from the survivors: stage ``j``'s state lives on buddy
  ``j+1``, and all shards carry the same step, so the reassembled
  state is consistent by construction.

* :class:`ElasticController` — detection. The elastic train step
  (``Trainer._train_step_elastic``) carries a per-stage gradient
  heartbeat in the device aux state; the controller reads it on the
  host cadence and raises :class:`StageLost` once a stage stays silent
  ``dead_after`` accepted steps. No host sync on the healthy path —
  the heartbeat rides the same aux fetch the numeric ladder already
  reads.

* :func:`replan_after_loss` — recovery. Re-cut the layer balance over
  the ``n-1`` survivors (:func:`~pipe_tpu.core.balance
  .rebalance_stage_loss`), re-emit and re-verify the op table for the
  new width (:func:`~pipe_tpu.core.schedule.replan_stage_loss` —
  schedules as data mean recovery is a fresh emission plus the same
  proofs every table must pass), rebuild the Trainer on the survivor
  devices, restore from the buddy snapshot, regroup the stage stacking
  (:func:`restack_state` — init keys are GLOBAL-layer-indexed, so the
  regrouped params are bitwise the params a born-``n-1``-stage run
  would hold), and resume mid-epoch at the snapshot step.

:func:`train_elastic` drives the whole ladder: train → StageLost →
re-plan → resume, bounded by ``max_replans``, aborting loudly
(:class:`~.recover.TrainingAborted`) when no survivor topology exists.
``TrainerConfig.elastic=None`` (the default) constructs none of this
and the train step lowers byte-identical to the non-elastic build
(pinned in tests/test_elastic.py).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List, Optional

import jax
import numpy as np

from ..obs.events import RECOVERY

__all__ = ["ElasticConfig", "StageLost", "BuddyStore", "ElasticController",
           "restack_state", "replan_after_loss", "train_elastic"]


class StageLost(RuntimeError):
    """A pipeline stage is persistently silent — escalate to re-plan."""

    def __init__(self, stage: int, detected_step: int,
                 snapshot_step: Optional[int]):
        super().__init__(
            f"pipeline stage {stage} persistently silent at step "
            f"{detected_step} (last buddy snapshot: "
            f"{'step ' + str(snapshot_step) if snapshot_step is not None else 'none'})")
        self.stage = stage
        self.detected_step = detected_step
        self.snapshot_step = snapshot_step


@dataclasses.dataclass(frozen=True)
class ElasticConfig:
    """Elastic-training knobs (``TrainerConfig.elastic``; None — the
    default — keeps the train step bitwise identical to the guarded
    build and constructs no buddy machinery)."""

    # buddy-replication cadence (accepted steps between captures; a
    # capture is skipped while any anomaly or silent streak is live —
    # only an all-healthy state is worth replicating)
    snapshot_every: int = 10
    # consecutive guard-accepted steps a stage's gradient heartbeat
    # must stay at exactly zero before the controller declares it dead
    dead_after: int = 2
    # host cadence for reading the heartbeat vector (shares the sync
    # the numeric ladder already pays at its own check_every)
    check_every: int = 1
    # verify every buddy capture bitwise against the source shards
    # (per-stage sha256; cheap at snapshot cadence, and the pin that
    # makes restore-from-buddy trustworthy)
    verify_replication: bool = True
    # how many stage losses one run may survive before aborting
    max_replans: int = 1
    # optional directory receiving a fsync'd buddy manifest JSON per
    # capture (train.state.write_buddy_manifest) for post-crash audit
    snapshot_dir: Optional[str] = None

    def __post_init__(self):
        if self.snapshot_every < 1 or self.dead_after < 1 \
                or self.check_every < 1:
            raise ValueError(
                "snapshot_every, dead_after and check_every must all "
                "be >= 1")
        if self.max_replans < 0:
            raise ValueError(
                f"max_replans must be >= 0, got {self.max_replans}")


def _is_staged(leaf, n_stages: int) -> bool:
    """True when ``leaf`` is mesh-placed with the stage axis leading —
    the shards the buddy ring must replicate. Replicated leaves (prep,
    postp, Adam's count, the step counter) every survivor already
    holds."""
    from jax.sharding import NamedSharding

    from ..parallel.mesh import STAGE_AXIS

    if not isinstance(leaf, jax.Array):
        return False
    sharding = getattr(leaf, "sharding", None)
    if not isinstance(sharding, NamedSharding):
        return False
    spec = sharding.spec
    return len(spec) > 0 and spec[0] == STAGE_AXIS


class BuddyStore:
    """Distributed in-memory checkpoint: each stage's shard, captured
    via one ppermute hop to its ring buddy and pinned by per-stage
    sha256 manifests. One store per Trainer (``Trainer.elastic_store``)
    so the snapshot survives the :class:`StageLost` raise."""

    def __init__(self, mesh, n_stages: int, *, verify: bool = True,
                 registry=None, events=None,
                 snapshot_dir: Optional[str] = None):
        self.mesh = mesh
        self.n = int(n_stages)
        self.verify = verify
        self.registry = registry
        self.events = events
        self.snapshot_dir = snapshot_dir
        self.snapshots = 0
        self._ring = None
        self._step: Optional[int] = None
        self._treedef = None
        self._staged_idx: Optional[List[int]] = None
        self._buddy: Optional[List[np.ndarray]] = None
        self._repl: Optional[List[Any]] = None
        self._manifest: Optional[dict] = None

    # -- the buddy ring ------------------------------------------------------

    def _ring_fn(self):
        """One jitted ppermute shifting every stage's shard to ring
        neighbor ``(j+1) % n`` along the stage axis — the same
        collective the boundary transport rides, as a separate
        low-frequency ring."""
        if self._ring is None:
            from jax.sharding import PartitionSpec as P

            from ..parallel.mesh import STAGE_AXIS

            perm = [(i, (i + 1) % self.n) for i in range(self.n)]

            def send(xs):
                return [jax.lax.ppermute(x, STAGE_AXIS, perm) for x in xs]

            self._ring = jax.jit(jax.shard_map(
                send, mesh=self.mesh, in_specs=P(STAGE_AXIS),
                out_specs=P(STAGE_AXIS), check_vma=False))
        return self._ring

    # -- capture / restore ---------------------------------------------------

    @property
    def step(self) -> Optional[int]:
        """Global batch index of the captured snapshot (None = none)."""
        return self._step

    @property
    def has_snapshot(self) -> bool:
        return self._step is not None

    def capture(self, state, step: int) -> None:
        """Replicate every stage-sharded leaf of ``state`` to its buddy
        and host-fetch the copies, with the replicated remainder (and a
        per-stage manifest) alongside. With ``verify`` the buddy copies
        are re-hashed against the source shards — a diverged hop fails
        loudly at capture time, never at restore."""
        from ..train.state import stage_shard_manifest, write_buddy_manifest

        flat, treedef = jax.tree_util.tree_flatten(state)
        staged_idx = [k for k, leaf in enumerate(flat)
                      if _is_staged(leaf, self.n)]
        staged_set = set(staged_idx)
        staged = [flat[k] for k in staged_idx]
        if not staged:
            raise RuntimeError(
                "BuddyStore.capture: no stage-sharded leaves in the "
                "state — is this trainer's mesh stage-partitioned?")
        rolled = self._ring_fn()(staged)
        buddy = [np.asarray(x) for x in rolled]
        repl = [np.asarray(flat[k]) if isinstance(flat[k], jax.Array)
                else flat[k]
                for k in range(len(flat)) if k not in staged_set]
        unroll = [(j + 1) % self.n for j in range(self.n)]
        recovered = [np.take(a, unroll, axis=0) for a in buddy]
        manifest = stage_shard_manifest(recovered, self.n)
        if self.verify:
            src = stage_shard_manifest([np.asarray(x) for x in staged],
                                       self.n)
            for j in range(self.n):
                if manifest[str(j)] != src[str(j)]:
                    raise RuntimeError(
                        f"buddy copy of stage {j}'s shard diverged from "
                        f"the source at capture (step {step}) — the "
                        f"replication ring is corrupting data")
        self._treedef = treedef
        self._staged_idx = staged_idx
        self._buddy = buddy
        self._repl = repl
        self._manifest = manifest
        self._step = int(step)
        self.snapshots += 1
        if self.registry is not None:
            self.registry.counter("resilience.elastic.snapshots").inc()
        if self.events is not None:
            self.events.event(RECOVERY, action="buddy_capture", step=step,
                              stages=self.n, verified=self.verify)
        if self.snapshot_dir is not None:
            write_buddy_manifest(self.snapshot_dir, int(step), manifest,
                                 self.n)

    def restore_state(self):
        """Reassemble the FULL state at the snapshot step from the
        buddy copies (stage ``j``'s shard read back from ring position
        ``(j+1) % n``), re-verified against the capture manifest.
        Returns a host (numpy-leaved) pytree in the captured tree
        structure — feed it to :func:`restack_state` + device_put."""
        from ..train.state import stage_shard_manifest

        if not self.has_snapshot:
            raise RuntimeError("no buddy snapshot captured yet")
        unroll = [(j + 1) % self.n for j in range(self.n)]
        recovered = [np.take(a, unroll, axis=0) for a in self._buddy]
        got = stage_shard_manifest(recovered, self.n)
        for j in range(self.n):
            if got[str(j)] != self._manifest[str(j)]:
                raise RuntimeError(
                    f"buddy shard for stage {j} failed its manifest pin "
                    f"at restore (snapshot step {self._step}) — refusing "
                    f"to resume on corrupt state")
        flat: List[Any] = []
        staged_it = iter(recovered)
        repl_it = iter(self._repl)
        staged_set = set(self._staged_idx)
        for k in range(self._treedef.num_leaves):
            flat.append(next(staged_it) if k in staged_set
                        else next(repl_it))
        if self.registry is not None:
            self.registry.counter("resilience.elastic.restores").inc()
        if self.events is not None:
            self.events.event(RECOVERY, action="buddy_restore",
                              step=self._step, stages=self.n)
        return jax.tree_util.tree_unflatten(self._treedef, flat)


class ElasticController:
    """Host half of the elastic rung: buddy-capture cadence and the
    dead-stage verdict. ``after_step`` mirrors
    ``ResilienceController.after_step`` and runs right after it."""

    def __init__(self, cfg: ElasticConfig, store: BuddyStore, *,
                 registry=None, events=None,
                 log_fn: Callable[[str], None] = print):
        self.cfg = cfg
        self.store = store
        self.registry = registry
        self.events = events
        self.log_fn = log_fn

    @property
    def snapshots(self) -> int:
        return self.store.snapshots

    def after_step(self, b: int, state, aux):
        """Read the heartbeat on the check cadence. Captures a buddy
        snapshot when the state is all-healthy on the snapshot cadence;
        raises :class:`StageLost` when any stage's silent streak
        reaches ``dead_after``. Returns ``(state, aux)`` unchanged
        otherwise."""
        cfg = self.cfg
        if (b + 1) % cfg.check_every:
            return state, aux
        hb = np.asarray(aux[3])     # the host sync point (check cadence)
        consec = int(aux[1])
        dead = np.nonzero(hb >= cfg.dead_after)[0]
        if dead.size:
            # A kill at stage j silences every stage <= j (zero output
            # kills the backward signal upstream of the cut): the
            # LARGEST silent index localizes the dead stage.
            stage = int(dead.max())
            snap = self.store.step
            if self.registry is not None:
                self.registry.counter("resilience.elastic.stage_lost").inc()
            if self.events is not None:
                self.events.event(RECOVERY, action="stage_lost",
                                  stage=stage, step=b, snapshot_step=snap,
                                  silent_steps=int(hb[stage]))
            self.log_fn(
                f"| elastic: stage {stage} silent {int(hb[stage])} "
                f"accepted steps at step {b} -> StageLost "
                f"(buddy snapshot @ {snap})")
            raise StageLost(stage, b, snap)
        if consec == 0 and not hb.any():
            if not self.store.has_snapshot \
                    or (b + 1) % cfg.snapshot_every == 0:
                self.store.capture(state, b)
        return state, aux


# ---------------------------------------------------------------------------
# Restacking: regroup an n-stage stacked state over n-1 stages
# ---------------------------------------------------------------------------

def _restack_blocks(stacked: List[Any], n_old: int, n_new: int) -> List[Any]:
    """Regroup a stage-stacked block list (``len = layers_per_stage``
    entries, every leaf leading with ``n_old``) over ``n_new`` stages.
    Pure host-side reshuffling: global layer ``g = s * lps + l`` keeps
    its exact bytes, only the (stage, slot) coordinates move."""
    lps_old = len(stacked)
    total = n_old * lps_old
    if total % n_new:
        raise ValueError(
            f"{total} layers do not regroup over {n_new} stages "
            f"(uniform stage bodies need n_layers % n_stages == 0)")
    lps_new = total // n_new
    layers = []
    for s in range(n_old):
        for l in range(lps_old):
            layers.append(jax.tree_util.tree_map(
                lambda a, _s=s: np.asarray(a)[_s], stacked[l]))
    out = []
    for l in range(lps_new):
        blocks = [layers[s * lps_new + l] for s in range(n_new)]
        out.append(jax.tree_util.tree_map(
            lambda *xs: np.stack(xs, 0), *blocks))
    return out


def _restack_params_like(tpl, n_old: int, n_new: int):
    sp, pre, post = tpl
    return (_restack_blocks(list(sp), n_old, n_new), pre, post)


def restack_state(state, n_old: int, n_new: int):
    """Regroup a host-side n_old-stage TrainState over ``n_new`` stages:
    the stacked params AND the Adam moments mirroring them (found
    structurally — any optax chain entry carrying ``mu``/``nu``);
    replicated leaves (prep/postp, count, step) pass through untouched.

    Because ``PipelinedLM.init`` keys every block by its GLOBAL layer
    index, the restacked params are bitwise the params a freshly-built
    ``n_new``-stage model would initialize to had it trained the same
    tape — the property the elastic acceptance pin rides.
    """
    from ..train.state import TrainState

    params = _restack_params_like(state.params, n_old, n_new)
    new_opt = []
    for entry in state.opt_state:
        if hasattr(entry, "mu") and hasattr(entry, "nu"):
            entry = entry._replace(
                mu=_restack_params_like(entry.mu, n_old, n_new),
                nu=_restack_params_like(entry.nu, n_old, n_new))
        new_opt.append(entry)
    return TrainState(params=params, opt_state=tuple(new_opt),
                      step=state.step)


# ---------------------------------------------------------------------------
# Recovery driver
# ---------------------------------------------------------------------------

def replan_after_loss(trainer, lost: StageLost, *,
                      log_fn: Callable[[str], None] = print):
    """Rebuild the run over the ``n-1`` survivors after a stage loss.

    Verifies the degraded topology (op table emission + proofs via
    :func:`~pipe_tpu.core.schedule.replan_stage_loss`, balance re-cut),
    constructs a new Trainer on the survivor devices (the dead stage's
    mesh row is dropped), restores + restacks the buddy snapshot, and
    returns ``(new_trainer, restored_state, start_step)`` ready for
    ``train_epoch(..., start_step=start_step)``. Raises
    :class:`~.recover.TrainingAborted` when no survivor topology
    exists — the final rung of the ladder.
    """
    from ..core.schedule import replan_stage_loss
    from .recover import TrainingAborted

    t0 = time.perf_counter()
    cfg = trainer.cfg
    n = cfg.n_stages
    n_new = n - 1
    store = trainer.elastic_store()
    if n_new < 2:
        raise TrainingAborted(
            f"stage {lost.stage} lost with only {n} stages — no pipeline "
            f"survives the re-plan")
    n_layers = trainer.model_cfg.n_layers
    if n_layers % n_new:
        raise TrainingAborted(
            f"stage {lost.stage} lost but {n_layers} layers do not "
            f"regroup over {n_new} survivors (uniform stage bodies)")
    if not store.has_snapshot:
        raise TrainingAborted(
            f"stage {lost.stage} lost at step {lost.detected_step} before "
            f"the first buddy snapshot — nothing to restore from")
    plan = replan_stage_loss(
        cfg.chunks, n, lost.stage, schedule=cfg.schedule,
        balance=[n_layers // n] * n)
    # Survivor devices: drop the dead stage's row from the mesh so the
    # new (n-1)-stage mesh reuses exactly the chips that still answer.
    surv = np.delete(np.asarray(trainer.mesh.devices), lost.stage,
                     axis=0).reshape(-1).tolist()
    new_cfg = dataclasses.replace(cfg, n_stages=n_new)
    new_chaos = (trainer.chaos.without("kill_stage")
                 if trainer.chaos is not None else None)
    new_tr = type(trainer)(trainer.model_cfg, new_cfg, devices=surv,
                           chaos=new_chaos)
    template = new_tr.init_state()
    host = store.restore_state()
    host_new = restack_state(host, n, n_new)
    restored = jax.tree_util.tree_map(
        lambda h, t: (jax.device_put(np.asarray(h), t.sharding)
                      if isinstance(t, jax.Array) else h),
        host_new, template)
    start_step = store.step + 1
    lost_steps = lost.detected_step - store.step
    dt = time.perf_counter() - t0
    registry = trainer.registry
    registry.counter("resilience.elastic.replans").inc()
    registry.counter("resilience.elastic.lost_steps").inc(max(lost_steps, 0))
    registry.gauge("resilience.elastic.recovery_s").set(dt)
    trainer.events.event(
        RECOVERY, action="replan", stage=lost.stage, n_stages=n_new,
        balance=list(plan.balance or ()), schedule=cfg.schedule,
        phase_ok=plan.phase.accepted, snapshot_step=store.step,
        resume_step=start_step, lost_steps=lost_steps, recovery_s=dt)
    log_fn(f"| elastic: re-planned {n}->{n_new} stages after losing "
           f"stage {lost.stage} (balance {list(plan.balance or ())}, "
           f"table verified, phase "
           f"{'ok' if plan.phase.accepted else 'rejected'}); resuming "
           f"from buddy snapshot @ step {store.step} "
           f"({lost_steps} steps lost, {dt:.2f}s recovery)")
    return new_tr, restored, start_step


def train_elastic(trainer, source, *, epoch: int = 0, state=None,
                  max_steps: Optional[int] = None, log_every: int = 0,
                  log_fn: Callable[[str], None] = print):
    """Run an epoch under the full ladder: train, and on
    :class:`StageLost` re-plan over the survivors and resume, up to
    ``ElasticConfig.max_replans`` times (then
    :class:`~.recover.TrainingAborted`). Returns ``(trainer, state,
    info)`` — the trainer may be a NEW, narrower instance after a
    recovery; ``info['recoveries']`` records each one."""
    from .recover import TrainingAborted

    start = 0
    history: List[dict] = []
    while True:
        try:
            state, info = trainer.train_epoch(
                source, epoch, state, max_steps=max_steps,
                log_every=log_every, log_fn=log_fn, start_step=start)
            info["replans"] = len(history)
            info["recoveries"] = history
            return trainer, state, info
        except StageLost as lost:
            max_replans = getattr(trainer.cfg.elastic, "max_replans", 1)
            if len(history) >= max_replans:
                raise TrainingAborted(
                    f"stage {lost.stage} lost at step "
                    f"{lost.detected_step} after {len(history)} re-plans "
                    f"(max_replans={max_replans})") from lost
            t0 = time.perf_counter()
            trainer, state, start = replan_after_loss(trainer, lost,
                                                      log_fn=log_fn)
            history.append({
                "stage": lost.stage,
                "detected_step": lost.detected_step,
                "snapshot_step": lost.snapshot_step,
                "resume_step": start,
                "lost_steps": lost.detected_step - (lost.snapshot_step or 0),
                "n_stages": trainer.cfg.n_stages,
                "recovery_s": time.perf_counter() - t0,
            })
