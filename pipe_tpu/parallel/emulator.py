"""Serial clock-cycle executor: the pipeline semantics without the mesh.

This is the TPU build's rebirth of the reference's CPU-sentinel-stream trick
(``AbstractStream`` admitting a CPU fallback, reference ``pipe.py:22``,
``pipeline.py:22``): the full scheduler — wavefront order, per-microbatch remat,
skip carries, ctx/RNG threading — runs on one device with no collectives, so
transparency tests (pipelined loss == unpipelined loss) and heterogeneous-stage
models need no mesh at all. The whole executor is pure and jit-able; the Python
loops unroll into one XLA program.

Where the reference needed ``fence`` (Copy/Wait stream ops + fork/join phony
edges, ``pipeline.py:119-142``) between ``compute`` dispatches, here the data
dependence between cycle k and k+1 is simply function composition — XLA sees
the true dependency graph, and backward order falls out of ``jax.grad``.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

from ..core import microbatch as mb
from ..core.partition import Stage, StageCtx
from ..core.remat import apply_remat, checkpoint_stop, validate_mode
from ..core.schedule import GPipeSchedule, Schedule
from ..obs.events import stage_scope

__all__ = ["run"]


def _compute_one(stage: Stage, params: Any, batch: mb.Batch, ctx: StageCtx,
                 remat: bool, remat_policy, skip_tracker=None) -> mb.Batch:
    """Run one (microbatch, stage) task, optionally under jax.checkpoint.

    The PRNG key rides as an explicit argument of the remat'd function so the
    recomputed forward sees the identical key — the reference's
    ``save/restore_rng_states`` (``README.md:528-537``) with no runtime state.

    Skip values cross the task (and hence the ``jax.checkpoint``) boundary as
    explicit inputs/outputs: incoming pops are loaded from the persistent
    tracker and fed in, outgoing stashes are returned and saved back. Tracers
    must not leak out of a remat trace via Python state, so a fresh per-task
    tracker serves the in-stage stash/pop calls — the TPU-native stand-in for
    the reference's portal machinery threading skips through the
    ``Checkpointing`` graph (``pipeline.py:136-138,201,208``).
    """
    key = ctx.key
    layout = (getattr(skip_tracker, "layout", None)
              if skip_tracker is not None else None)
    pop_keys = layout.pops_of(ctx.stage) if layout else ()
    stash_keys = layout.stashes_of(ctx.stage) if layout else ()

    def call_payload(p, k, *inputs):
        inner = StageCtx(key=k, train=ctx.train,
                         microbatch=ctx.microbatch, stage=ctx.stage)
        return stage(p, *inputs, ctx=inner)

    if skip_tracker is None:
        def task(p, k, *inputs):
            return call_payload(p, k, *inputs)

        task = apply_remat(task, enabled=remat, policy=remat_policy)
        with stage_scope(ctx.microbatch, ctx.stage):
            return batch.call(lambda *inputs: task(params, key, *inputs))

    from ..extras.skip import SkipTracker

    pop_vals = [skip_tracker.load(ctx.microbatch, ns, name)
                for ns, name in pop_keys]

    def task(p, k, pop_vals, *inputs):
        local = SkipTracker(layout)
        for (ns, name), v in zip(pop_keys, pop_vals):
            local.save(ctx.microbatch, ns, name, v)
        with local.scope(ctx.microbatch, ctx.stage):
            out = call_payload(p, k, *inputs)
        stash_vals = [local.load(ctx.microbatch, ns, name)
                      for ns, name in stash_keys]
        # Stat accumulators (deferred BN) also cross the remat boundary as
        # explicit outputs; dict keys are static by the end of the trace.
        return out, stash_vals, dict(local.accum)

    task = apply_remat(task, enabled=remat, policy=remat_policy)
    with stage_scope(ctx.microbatch, ctx.stage):
        result, stash_vals, accums = task(params, key, pop_vals,
                                          *batch.values)
    for (ns, name), v in zip(stash_keys, stash_vals):
        skip_tracker.save(ctx.microbatch, ns, name, v)
    for (ns, name), v in accums.items():
        skip_tracker.accumulate(ns, name, v)
    if isinstance(result, (tuple, list)):
        return mb.Batch(tuple(result), atomic=False)
    return mb.Batch(result, atomic=True)


def _corrupt_hop(batch: mb.Batch, mode: str) -> mb.Batch:
    """Chaos-plan transport fault on a stage-boundary hop: 'drop' zeroes
    the payload (a lost transfer), 'corrupt' scales it by NaN (a torn
    one). Structural at trace time — with no plan the program is
    untouched."""
    import jax.numpy as jnp

    def one(v):
        if not mb.is_array(v):
            return v                      # NoChunk riders pass through
        if mode == "drop":
            return jnp.zeros_like(v)
        if jnp.issubdtype(v.dtype, jnp.inexact):
            return v * jnp.asarray(jnp.nan, v.dtype)
        return jnp.full_like(v, -1)       # int payload: garbage fill

    def hit(*vals):
        out = tuple(one(v) for v in vals)
        return out[0] if len(out) == 1 else out

    return batch.call(hit)


def run(stages: Sequence[Stage],
        params_per_stage: Sequence[Any],
        batches: List[mb.Batch],
        *,
        schedule: Optional[Schedule] = None,
        checkpoint: str = "never",
        train: bool = False,
        key: Optional[jax.Array] = None,
        remat_policy=None,
        skip_tracker=None,
        chaos=None,
        hop_health=None) -> List[mb.Batch]:
    """Execute the clock-cycle schedule serially; returns transformed batches.

    Mirrors ``Pipeline.run`` (reference ``pipeline.py:100-117``): iterate the
    wavefront; for each (i, j) run stage j on micro-batch i, rematerializing
    when ``i < checkpoint_stop`` (``pipeline.py:195-214``). The first stage
    failure propagates immediately (eager Python → strictly earlier than the
    reference's hold-and-drain, ``pipeline.py:239-247``, which existed only
    because of worker threads).

    ``chaos`` (a :class:`~pipe_tpu.resilience.ChaosPlan`) injects
    transport faults: after stage ``j`` produces micro-batch ``i``, a
    planned ``transport_drop``/``transport_corrupt`` at ``(i, j)``
    zeroes/NaN-poisons the hop before stage ``j+1`` consumes it —
    deterministic, and absent from the program when no plan is given.
    A ``persistent_hop_drop`` fault matches every micro-batch crossing
    its hop. ``hop_health`` (a
    :class:`~pipe_tpu.resilience.HopHealth`) records every crossing —
    faulted or clean — so persistent hop failure accumulates a streak
    the elastic controller can escalate on, while one-shot faults reset.
    """
    validate_mode(checkpoint)
    schedule = schedule or GPipeSchedule()
    m, n = len(batches), len(stages)
    stop = checkpoint_stop(checkpoint, m, train)
    batches = list(batches)

    for cycle in schedule.cycles(m, n):
        for (i, j) in cycle:
            if not (0 <= i < m and 0 <= j < n):
                raise IndexError(
                    f"schedule {schedule.name!r} emitted task (microbatch={i}, "
                    f"stage={j}) outside the {m}x{n} grid")
            ctx = StageCtx(key=key, train=train, microbatch=i, stage=j)
            ctx = ctx.fold(i, j) if key is not None else ctx
            batches[i] = _compute_one(
                stages[j], params_per_stage[j], batches[i], ctx,
                remat=i < stop, remat_policy=remat_policy,
                skip_tracker=skip_tracker)
            if j < n - 1:
                mode = (chaos.transport_fault(i, j)
                        if chaos is not None else None)
                if mode is not None:
                    batches[i] = _corrupt_hop(batches[i], mode)
                if hop_health is not None:
                    hop_health.record(j, mode is not None)
    return batches
