"""Schedule-table executor: hand-scheduled forward+backward in ONE scan.

The reference has no backward scheduler at all — backward order is discovered
at runtime by the C++ autograd engine walking fork/join/Copy/Wait nodes
(``pipeline.py:128-132``; ``README.md:106-183,219-237``), which is precisely
why its 1F1B-style memory release works: each micro-batch's backward runs as
soon as its gradient arrives, freeing activations early. The AD executor
(:mod:`.spmd`) gets correctness from ``jax.grad``-of-``scan`` but inherits
GPipe's O(m) activation liveness: every micro-batch's residuals survive until
the scan's backward.

This module instead compiles the *whole* training step — forward, backward,
loss, gradient accumulation — as one ``lax.scan`` over uniform clock slots,
driven by static (cycle, device) → (op, micro-batch, group) tables emitted
by :meth:`core.schedule.Schedule.op_tables`. Per cycle each device either

* **FWD**: runs one of its stage bodies on one micro-batch (stashing the
  stage *input* in a ring buffer), or
* **BWD**: re-runs the stage from the stashed input under ``jax.vjp`` and
  applies the cotangent arriving from the next stage (manual remat — the
  compiled analogue of ``Recompute.backward`` re-running forward just before
  ``Checkpoint.backward`` consumes it, ``README.md:450-537``), or
* **IDLE**: passes through (a fill/drain bubble slot).

Transport is two ``ppermute`` rings — activations one hop forward,
cotangents one hop backward — shifted every cycle; the tables guarantee a
value is consumed exactly when it arrives (gradients) or is parked in the
stash until its cycle (activations).

What this buys over the AD executors:

* **True 1F1B**: with ``schedule='1f1b'`` the stashed-input buffer holds at
  most ``min(m, n)`` micro-batches (vs GPipe's ``m``) — the activation-memory
  cap that is the entire point of the reference's fork/join machinery.
* **Interleaved 1F1B** (``schedule='interleaved-1f1b'``): each device hosts
  ``v`` non-adjacent virtual stages (virtual stage ``s`` on device
  ``s % d``), every boundary is one hop on the WRAPAROUND ring, and both
  passes come from the same static table — the fill bubble shrinks vs plain
  1F1B of the same depth while keeping the 1F1B memory story
  (:class:`~pipe_tpu.core.schedule.InterleavedOneFOneBSchedule`).
* **Exact ``except_last``**: per-micro-batch remat policy with *uniform*
  per-cycle code: micro-batch m-1's vjp residuals are saved at forward time
  (a flattened-``vjp_fn`` pytree carried in the scan), every other micro-batch
  recomputes — sidestepping the jax 0.9.0 ``cond``+remat+PRNG bug that forces
  the AD executor's static remat (see ``spmd.py`` module docstring). Matches
  the reference mode map ``pipe.py:354`` exactly on the compiled path.
* **Schedules as data**: any table satisfying the
  :mod:`core.schedule` verifiers runs unmodified.

Checkpoint-mode → storage map (per device; ``Sg`` = per-virtual-stage stash
slots = ``schedule.stash_slots(m, d)``, ``v`` = interleave depth):

=============  =====================  ==========================
mode           stashed inputs         stored vjp residuals
=============  =====================  ==========================
always         v·Sg slots             none (recompute all)
except_last    v·Sg slots             v slots (micro-batch m-1)
never          v·Sg slots             v·Sg slots (recompute none)
=============  =====================  ==========================

plus ``Sg`` activation-sized slots parking the last virtual stage's outputs.
The post (decode/loss) is NEVER part of the stored residuals — its vjp is
rebuilt fresh at backward time from the parked output, because post residuals
are vocab-scale (a [rows, seq, vocab] logits tensor plus a weight-cast copy,
hundreds of MB at tutorial scale) and slot structure replicates across every
slot; folding the post in OOMed a 16G v5e on the 520M tutorial config.

Parameter layout: the stage axis stacks all ``v·d`` virtual stages
device-major (``stack_interleaved_params`` ordering: global row ``p·v + g``
= virtual stage ``g·d + p``), so each device's shard is its ``v`` groups in
order; ``v = 1`` reduces to plain per-stage stacking and reproduces the
non-interleaved executor exactly (same tables, same key folds).
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core
from jax.sharding import Mesh, PartitionSpec as P

from ..core.partition import StageCtx
from ..core.remat import validate_mode
from ..core.schedule import (BWD, FWD, IDLE, WGRAD, GPipeSchedule,
                             InterleavedOneFOneBSchedule, OneFOneBSchedule,
                             Schedule, compile_phases, get_schedule,
                             shift_comm_tables, verify_shifted_op_tables,
                             overlap_joint_capacity, _times_by_code)
from .buffers import pack_words, packed_words, unpack_words
from .mesh import DATA_AXIS, MODEL_AXIS, STAGE_AXIS
from ..obs.events import REMAT_SCOPE, device_scope
from ..obs.telemetry import get_registry
from ..utils.rng import make_key

__all__ = ["ScheduledPipeline", "SplitBackwardStage", "SkipLanes"]


@dataclasses.dataclass(frozen=True)
class SkipLanes:
    """Cross-stage ``@skippable`` carries through the table executor.

    The wavefront executor's skip lanes (``hetero.py``) need no parking:
    device ``j`` computes micro-batch ``i`` at cycle ``i+j``, so a value
    emitted at the source is consumed the cycle it arrives. Table
    schedules (1F1B) interleave B ops, so arrival and consumption
    decouple — the compiled analogue of the reference's portals riding
    copy streams inside the training fence (``pipeline.py:136-138``).
    Mechanism, all static at trace time:

    * forward: the stash value boards a per-lane register and takes ONE
      direct ``ppermute`` hop ``src % d -> dst % d`` (the lane has its
      own permute, so it never relays through intermediate devices —
      less ICI traffic than a hop-per-cycle ring, and on wrapped
      interleaved placements a transiting value cannot collide with a
      fresh stash at its source device, which is what previously kept
      skips off v > 1). It is captured into a FIFO park at the
      destination at its host-computed arrival cycle (``FWD(i, src) + 1``)
      and read at FWD(i, dst) — and re-read at BWD(i, dst) under
      recompute modes, exactly like the activation stash. Lanes whose
      endpoints share a device (possible when v > 1) skip the permute:
      the register IS the transport;
    * backward: BWD(i, dst)'s vjp yields the pop cotangent, which takes
      the reverse direct hop to the source and seeds the stash output of
      BWD(i, src)'s vjp — the compiled ``PortalOrange``/``PortalBlue``
      pair;
    * park sizes are the smallest FIFO depths with no live-window
      collision, computed from the op tables per lane.

    With lanes configured the stage contract becomes
    ``stage_fn(params_g, h, ctx, pops) -> (h, stashes)`` where ``pops``/
    ``stashes`` are tuples over lanes — a stage reads only the lanes it
    pops and must return zeros (of the lane spec) for lanes it does not
    stash. Requires a non-split-backward schedule.

    ``pairs[l] = (src, dst)`` virtual stage indices (``src < dst``);
    ``specs[l]`` is the lane's value pytree of ShapeDtypeStructs.
    """

    pairs: tuple
    specs: tuple


@dataclasses.dataclass(frozen=True)
class SplitBackwardStage:
    """Structural B/W split of a stage body (zero-bubble's real contract).

    The round-3 audit (docs/architecture.md) measured that applying a
    stored vjp at both B and W executes the FULL transpose twice — XLA
    does not prune the unused outputs inside switch branches. This
    protocol makes the split structural instead of hoped-for:

    * ``tapped_fn(params_g, h, ctx, zs) -> (h_out, taps)`` — the stage
      forward with a zero pytree ``zs`` injected at every param-consuming
      op's OUTPUT and the per-op INPUTS returned as ``taps``;
    * the executor takes ``jax.vjp`` w.r.t. ``(pre, h, zs)`` with the
      stage params CLOSED OVER AS CONSTANTS — the stored transpose
      therefore contains zero weight-grad contractions by construction
      (verified by HLO dot census in tests), and applying it at B yields
      the input-grad chain plus ``g_zs``, the per-op output cotangents;
    * ``wgrad_fn(taps, gzs) -> params_g-structured grads`` — the W op:
      nothing but the weight-grad contractions themselves.

    Pair with ``checkpoint='never'`` and a ``splits_backward`` schedule
    (zb-h1); the executor rejects other combinations. Memory: ``taps``
    ride ``Sg`` FIFO slots (FWD -> W window) and ``g_zs`` ride the
    ``Wg`` cotangent-park window — both activation-scale.

    ``zs_fn(params_g, h) -> zeros pytree`` sizes the injection points.
    """

    tapped_fn: Any
    wgrad_fn: Any
    zs_fn: Any

# Auto cutoff for the d == 1 trace-time unroll (ScheduledPipeline
# .static_unroll=None): tables longer than this use the dynamic scan — HLO
# size and temp memory grow with the unroll (observed: 16 unrolled cycles
# OOM a 16G v5e at the 520M tutorial config where 8 fit comfortably).
_STATIC_UNROLL_MAX_CYCLES = 12


def _index(tree, i):
    return jax.tree_util.tree_map(
        lambda l: jax.lax.dynamic_index_in_dim(l, i, 0, keepdims=False), tree)


def _vjp_leaves(vjp_fn, specs):
    """Flatten ``vjp_fn`` into its residual leaves. One flattener for BOTH
    residual stores (full and policy-shaped) so slot layout and the
    structure-drift assert cannot diverge between them. The actual store
    write happens once, post-switch, in the cycle body (sentinel-masked) —
    branches only hand back the leaves, never an updated store, so XLA can
    alias the store across scan iterations."""
    leaves = jax.tree_util.tree_leaves(vjp_fn)
    assert [(l.shape, l.dtype) for l in leaves] == \
        [(sp_.shape, sp_.dtype) for sp_ in specs], \
        "vjp residual structure drifted from abstract spec"
    return leaves


def _load_vjp(store, treedef, slot):
    """Gather ``slot``'s leaves from ``store`` and rebuild the vjp callable
    — the read twin of :func:`_vjp_leaves`."""
    leaves = [jax.lax.dynamic_index_in_dim(st, slot, 0, keepdims=False)
              for st in store]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _is_diff(spec) -> bool:
    return jnp.issubdtype(jnp.asarray(spec).dtype
                          if not hasattr(spec, "dtype") else spec.dtype,
                          jnp.inexact)


def _ring_to_seed(ring_tree, primal_spec):
    """Ring cotangent -> vjp seed: integer (non-differentiable) primal
    lanes — e.g. token ids riding the packed boundary carrier — expect
    ``float0`` cotangents from ``jax.vjp``; the ring parks placeholder
    zeros of the primal dtype for them (see :func:`_vjp_to_ring`)."""
    return jax.tree_util.tree_map(
        lambda rv, sp_: (rv if _is_diff(sp_)
                         else np.zeros(sp_.shape, jax.dtypes.float0)),
        ring_tree, primal_spec)


def _vjp_to_ring(ct_tree, primal_spec):
    """vjp cotangent -> ring value: ``float0`` leaves (int primal lanes)
    become concrete zeros of the PRIMAL dtype so the carry/ppermute pytree
    stays uniform. The zeros are inert — every consumer converts back via
    :func:`_ring_to_seed` before seeding a vjp."""
    return jax.tree_util.tree_map(
        lambda ct, sp_: (jnp.zeros(sp_.shape, sp_.dtype)
                         if ct.dtype == jax.dtypes.float0 else ct),
        ct_tree, primal_spec)


@dataclasses.dataclass
class ScheduledPipeline:
    """Training executor: ``loss_and_grad`` on a ``(stage[, data])`` mesh.

    Args:
      mesh: mesh with a ``stage`` axis (and optionally ``data``/others).
        The stage axis size is the DEVICE count d; with an interleaved
        schedule the model must factor into ``v*d`` virtual stage bodies.
      stage_fn: ``(params_g, h, ctx) -> h`` homogeneous stage body (ring
        invariant: input/output activation shapes identical).
      pre_fn: ``(pre_params, x_mb, ctx) -> h``, run on virtual stage 0.
      post_fn: ``(post_params, h, x_mb, ctx) -> per-row loss [rows]``, run on
        the last virtual stage. Training executors always compute loss
        in-pipeline (the reference moves targets to the last GPU for the
        same reason, ``main.py:216``).
      checkpoint: ``always | except_last | never`` — exact per-micro-batch
        policy (reference ``pipe.py:354``).
      schedule: ``'gpipe' | '1f1b' | 'interleaved-1f1b'`` or a
        :class:`Schedule` with op tables.
    """

    mesh: Mesh
    stage_fn: Callable
    pre_fn: Callable
    post_fn: Callable
    checkpoint: str = "except_last"
    schedule: Any = "1f1b"
    context_axis: Optional[str] = None
    context_dim: int = 2
    # Trace-time static specialization of the tables when the stage axis has
    # ONE device (see _device_program_static): None = auto (on when the
    # table has <= _STATIC_UNROLL_MAX_CYCLES cycles), True = force, False =
    # always use the dynamic scan. The static program is branch-free (2.3x
    # faster at tutorial scale: no conditional-copy traffic) but its HLO
    # size and temp footprint grow with the unroll — at m=8 on the 520M
    # config it exceeds a 16G chip where the dynamic path fits; set False
    # (or rely on the cycle cap) in that regime.
    static_unroll: Optional[bool] = None
    # Per-leaf PartitionSpecs for ONE stage's param tree over the leaf's
    # OWN dims (tensor parallelism): e.g. a Megatron block's
    # ``{"wqkv": P(None, None, 'model', None), ...}`` — the executor
    # prepends the stage axis for the stacked layout, hands each device
    # its local shard inside shard_map, and NEVER reduces gradients over
    # the model axis (the TP grad contract: sharded leaves' grads are
    # local by construction, replicated leaves' grads are model-identical
    # via the block's tp_enter operator — see ops/tp_layers.py). None =
    # every leaf replicated over non-stage axes (the homogeneous default).
    stage_param_specs: Optional[Any] = None
    # Structural B/W split of the stage body for zero-bubble schedules —
    # see :class:`SplitBackwardStage`. Requires checkpoint='never' and a
    # splits_backward schedule; replaces stage_fn for fwd/bwd purposes.
    # The string "auto" derives the split from stage_fn by jaxpr surgery
    # (core.remat.split_backward_stage) — works for any stage body whose
    # params enter linearly (matmuls/scales/casts; see SplitUnsupported).
    split_stage: Optional[Any] = None
    # Selective rematerialization for the RECOMPUTE micro-batches (a
    # ``jax.checkpoint_policies`` member, e.g. ``dots_saveable``): instead
    # of stashing the stage input and re-running the whole forward at
    # backward time, the forward stores the policy-saved residual subset
    # (matmul outputs) and the backward recomputes only the cheap
    # elementwise remainder — the FLOPs-vs-HBM dial the reference's
    # all-or-nothing Checkpoint lacks. The per-micro-batch mode semantics
    # are unchanged: SAVED micro-batches (never: all; except_last: m-1)
    # still store full residuals. Works on the d=1 static program AND the
    # d>1 dynamic scan: the policy-saved residual pytree differs from the
    # full set, so the dynamic path carries TWO slot stores — the full
    # store (saved micro-batches) and a policy-shaped store (recompute
    # micro-batches) — each internally uniform, with cond-gated
    # writes/reads selecting between them per micro-batch. Inert (a
    # warning) under checkpoint='never', where every micro-batch stores
    # full residuals anyway.
    remat_policy: Optional[Any] = None
    # Cross-stage @skippable carries — see :class:`SkipLanes`. Changes the
    # stage_fn contract to (params_g, h, ctx, pops) -> (h, stashes).
    skip_lanes: Optional[SkipLanes] = None
    # Per-step stat lanes (deferred BatchNorm, reference batchnorm.py via
    # pipe.py:341-342): a pytree spec of per-step accumulators, uniform
    # across stages (each stage fills only its own slots, zeros elsewhere;
    # values must be stop_gradient'd at source). The stage contract
    # appends a stats output — (h[, stashes], stats) — and loss_and_grad
    # returns ``(loss, grads, stats)``; stats accumulate over FWD ops ONLY
    # (a BWD recompute re-computes and discards them, so recompute modes
    # cannot double-count) and are summed over the stage/data axes.
    stat_spec: Optional[Any] = None
    # Overlapped (software-pipelined) boundary transport: each direction's
    # boundary pytree (activations + riding skip lanes forward, cotangents
    # + reverse lanes backward) packs into ONE flat uint32 buffer
    # (buffers.pack_words), the scan carry double-buffers it, and the
    # single per-direction ppermute launches at the START of each cycle —
    # moving cycle t-1's sends while cycle t computes. Requires the comm-
    # shifted op tables (core.schedule.shift_comm_tables): every consumer
    # is retimed >= 2 cycles behind its producer and the shifted tables are
    # re-verified at trace time (verify_shifted_op_tables). None = auto: ON
    # for d > 1 on accelerator backends (async collectives overlap
    # compute), OFF on CPU meshes (XLA:CPU's ppermute is a blocking
    # rendezvous, so the longer shifted tables only add cycles) and always
    # OFF at d == 1 (no transport). True/False force it for d > 1. Results
    # are bitwise-identical to the serialized path: the retimer preserves
    # per-device op order, and packing is a pure bitcast.
    overlap_transport: Optional[bool] = None
    # Phase-compiled execution (core.schedule.compile_phases): the op table
    # is re-timed into cycle-uniform phases and each phase lowers
    # separately — warmup/cooldown ramps unroll to straight-line code
    # (each cycle's single op code is a trace-time constant; partially
    # idle cycles mask their stores/accumulators by data selects), and the
    # dense periodic steady state lowers to a fixed-body ``lax.scan``
    # whose body is the period's concrete (fwd, bwd[, wgrad]) sequence —
    # NO ``lax.switch`` dispatch and NO sentinel-masked no-op branches:
    # every device runs real work every steady cycle. Rides the packed
    # double-buffered overlap transport (the aligner emits hop-2 tables)
    # and the (values, slot) store discipline, so XLA buffer aliasing
    # survives. None = auto: ON for d > 1 on accelerator backends when the
    # compiler accepts the table, OFF on CPU meshes (explicit True forces
    # it anywhere, which is how the cpu8 probes run it). Tables the
    # compiler rejects fall back loudly to the interpreted executor, under
    # auto as under True (warnings.warn + the scheduled.phase.rejected
    # counter). Bitwise parity with the interpreted executor: the aligner
    # preserves each (stage, op-code) stream's order — F ops feed
    # loss/stats and B/W ops feed the grad accumulators, disjoint state —
    # so every accumulation order is preserved even though F/B
    # interleaving changes.
    phase_compile: Optional[bool] = None

    def __post_init__(self):
        validate_mode(self.checkpoint)
        if STAGE_AXIS not in self.mesh.axis_names:
            raise ValueError(f"mesh must have a {STAGE_AXIS!r} axis")
        if isinstance(self.schedule, str):
            self.schedule = get_schedule(self.schedule)
        if not isinstance(self.schedule, (GPipeSchedule, OneFOneBSchedule,
                                          InterleavedOneFOneBSchedule)):
            # anything emitting valid op tables works; these are shipped
            if not hasattr(self.schedule, "op_tables"):
                raise ValueError(
                    f"schedule {self.schedule!r} has no op_tables")
        self.n_stages = self.mesh.shape[STAGE_AXIS]      # devices d
        if isinstance(self.split_stage, str):
            if self.split_stage != "auto":
                raise ValueError(
                    f"split_stage must be a SplitBackwardStage or 'auto', "
                    f"got {self.split_stage!r}")
            # derive the tapped/wgrad/zs triple from the stage fn itself
            # (core.remat.split_backward_stage) — any model, no hand-rolled
            # tapped forward
            from ..core.remat import split_backward_stage
            self.split_stage = split_backward_stage(self.stage_fn)
        if self.split_stage is not None:
            if not getattr(self.schedule, "splits_backward", False):
                raise ValueError(
                    "split_stage requires a splits_backward schedule "
                    "(zb-h1): B/W table ops are where the split executes")
            if self.checkpoint != "never":
                raise ValueError(
                    "split_stage requires checkpoint='never': the stored "
                    "params-constant vjp IS the activation store")
            if self.stage_param_specs is not None:
                raise ValueError(
                    "split_stage does not compose with stage_param_specs "
                    "(tensor-parallel sharded stage params): the tapped/"
                    "wgrad fns are written for unsharded math and would "
                    "silently drop the cross-shard psums")
            if self.remat_policy is not None:
                raise ValueError(
                    "split_stage already defines its storage (full "
                    "residuals + taps); remat_policy would be silently "
                    "inert — drop one of the two")
        if self.skip_lanes is not None and not self.skip_lanes.pairs:
            self.skip_lanes = None          # empty lanes = no skips
        if self.skip_lanes is not None:
            if getattr(self.schedule, "splits_backward", False):
                raise NotImplementedError(
                    "skip lanes do not compose with split-backward "
                    "schedules (zb-h1): the W op's params-only grads "
                    "cannot seed the reverse skip ring")
            if self.split_stage is not None:
                raise ValueError(
                    "split_stage's tapped/wgrad fns have no pop/stash "
                    "arguments; skip models use plain stage bodies")
            if self.n_stages < 2:
                raise ValueError(
                    "cross-stage skip lanes need a >=2-device stage axis")
            S = self.schedule.v * self.n_stages
            for (src, dst) in self.skip_lanes.pairs:
                if not (0 <= src < dst < S):
                    raise ValueError(
                        f"skip lane ({src}, {dst}) out of range for "
                        f"{S} stages (need 0 <= src < dst < {S})")
            for sp_ in jax.tree_util.tree_leaves(self.skip_lanes.specs):
                if hasattr(sp_, "dtype") and not jnp.issubdtype(
                        sp_.dtype, jnp.inexact):
                    raise NotImplementedError(
                        f"skip lane values must be float (got "
                        f"{sp_.dtype}): integer lanes would need the "
                        "float0 cotangent plumbing the h carrier has "
                        "(_ring_to_seed/_vjp_to_ring) on the reverse "
                        "skip ring too")
        if self.stat_spec is not None:
            if self.split_stage is not None:
                raise ValueError(
                    "split_stage's tapped/wgrad fns have no stats output; "
                    "stat lanes need plain stage bodies")
            if getattr(self.schedule, "splits_backward", False):
                raise NotImplementedError(
                    "stat lanes do not compose with split-backward "
                    "schedules (zb-h1): the W op's seed has no stats slot")
        if self.remat_policy is not None and self.checkpoint == "never":
            warnings.warn(
                "remat_policy is inert under checkpoint='never': every "
                "micro-batch stores its full residual set and nothing is "
                "recomputed. Use 'always' or 'except_last' to engage the "
                "policy.", stacklevel=2)
        if (getattr(self.schedule, "splits_backward", False)
                and self.checkpoint != "never"):
            warnings.warn(
                f"schedule {self.schedule.name!r} splits backward into B/W "
                f"ops to fill bubble slots with weight-grad compute, but "
                f"checkpoint={self.checkpoint!r} recomputes the forward at "
                f"B and the full backward runs there — the W slots carry no "
                f"compute and the zero-bubble advantage is lost. Pair "
                f"zero-bubble schedules with checkpoint='never'.",
                stacklevel=2)
        self.v = self.schedule.v
        self.n_virtual = self.v * self.n_stages
        self.has_data_axis = DATA_AXIS in self.mesh.axis_names
        # see spmd.SpmdPipeline.bn_axis
        self.bn_axis = (DATA_AXIS if self.has_data_axis
                        and self.mesh.shape[DATA_AXIS] > 1 else None)
        if self.context_axis and self.context_axis not in self.mesh.axis_names:
            raise ValueError(
                f"mesh has no {self.context_axis!r} axis for context_axis")
        # per-m phase-compiler verdicts (host-side analysis, ms-scale, but
        # the reject warning must fire once per (pipeline, m), not per
        # retrace)
        self._phase_cache = {}

    # -----------------------------------------------------------------
    def memory_plan(self, m: int) -> dict:
        """Static per-device buffer counts — the memory story, inspectable.
        Reflects the ACTIVE transport: under overlapped transport the slot
        counts come from the comm-shifted tables (stash windows widen by
        the extra in-flight cycle; a small grad park appears). The
        checkpoint-mode → slot-count arithmetic is the SHARED formula in
        ``core/memplan.py`` — the same one the auto-planner prices
        candidate configs with (``estimate_memory``), so the two cannot
        drift."""
        from ..core.memplan import MemoryPlanInputs, activation_slot_plan
        d, v = self.n_stages, self.v
        phased = self._phase_program(m)
        overlap = phased is not None or self._overlap_enabled()
        Gg = 0
        if phased is not None:
            (op_np, mb_np, grp_np, _, _), _, Sg, Gg, Wg, _, _ = \
                self._host_tables_phased(m)
        elif overlap:
            (op_np, mb_np, grp_np, _, _), _, Sg, Gg, Wg, _, _ = \
                self._host_tables_overlap(m)
        else:
            Sg = self.schedule.stash_slots(m, d)
            Wg = self.schedule.wstash_slots(m, d)
        plan = {"cycles": self._cycles(m),
                **activation_slot_plan(MemoryPlanInputs(
                    v=v, stash_slots=Sg, wstash_slots=Wg,
                    checkpoint=self.checkpoint,
                    has_remat_policy=self.remat_policy is not None,
                    split_stage=self.split_stage is not None,
                    overlap=overlap, grad_park_slots=Gg)),
                "transport": ("phase-compiled" if phased is not None
                              else "overlapped" if overlap
                              else "serialized")}
        if phased is not None:
            plan["phase_segments"] = tuple(
                (s_.kind, s_.t0, s_.t1, s_.period)
                for s_ in phased.segments)
            plan["phase_unrolled_cycles"] = phased.unrolled_cycles
            plan["phase_scan_cycles"] = phased.scan_cycles
        if self.skip_lanes is not None:
            if not overlap:
                tables = self.schedule.op_tables(m, d)
                op_np, mb_np = tables[0], tables[1]
                grp_np = (tables[2] if len(tables) > 2
                          else np.zeros_like(op_np))
            _, _, Kf, Kg = self._skip_tables(m, op_np, mb_np, grp_np,
                                             overlap=overlap)
            plan["skip_lanes"] = len(self.skip_lanes.pairs)
            plan["skip_fwd_park_slots"] = sum(Kf)
            plan["skip_bwd_park_slots"] = sum(Kg)
        return plan

    def _cycles(self, m: int) -> int:
        if self._phase_program(m) is not None:
            return self._phase_program(m).cycles
        if self._overlap_enabled():
            return self._host_tables_overlap(m)[1]
        tables = self.schedule.op_tables(m, self.n_stages)
        return tables[0].shape[0]

    def _overlap_enabled(self) -> bool:
        """Resolve the ``overlap_transport`` tri-state (see field comment).
        Always False at d == 1 — there is no boundary transport to shift."""
        if self.n_stages <= 1:
            return False
        if self.overlap_transport is not None:
            return bool(self.overlap_transport)
        return self.mesh.devices.flat[0].platform != "cpu"

    def _phase_verdict(self, m):
        """Phase-compile this pipeline's table at m (cached per m). Only
        reached when phase compilation is on (asked for, or auto on an
        accelerator), so a rejection is never silent: bump the fallback
        counter and warn once, naming the reason."""
        if m not in self._phase_cache:
            tables = self.schedule.op_tables(m, self.n_stages)
            op0, mb0 = tables[0], tables[1]
            grp0 = tables[2] if len(tables) > 2 else None
            verdict = compile_phases(op0, mb0, grp0, m=m, d=self.n_stages,
                                     v=self.v)
            if verdict.accepted:
                get_registry().counter("scheduled.phase.compiled").inc()
            else:
                get_registry().counter("scheduled.phase.rejected").inc()
                warnings.warn(
                    f"phase_compile={self.phase_compile} but the phase "
                    f"compiler rejected the {self.schedule.name!r} op "
                    f"table at m={m} ({verdict.reason}); falling back to "
                    f"the interpreted table executor", stacklevel=3)
            self._phase_cache[m] = verdict
        return self._phase_cache[m]

    def _phase_program(self, m):
        """Resolve the ``phase_compile`` tri-state to an accepted
        :class:`~pipe_tpu.core.schedule.PhaseProgram`, or None for the
        interpreted executor (disabled, d == 1, auto-off on CPU, or the
        compiler rejected the table — the loud path in _phase_verdict)."""
        if self.n_stages <= 1 or self.phase_compile is False:
            return None
        if (self.phase_compile is None
                and self.mesh.devices.flat[0].platform == "cpu"):
            return None
        verdict = self._phase_verdict(m)
        return verdict.program if verdict.accepted else None

    # -----------------------------------------------------------------
    def loss_and_grad(self, stage_params, pre_params, post_params, x, w,
                      *, key: Optional[jax.Array] = None):
        """One pipelined step: returns ``(loss, (g_stage, g_pre, g_post))``.

        ``x``: pytree of ``[m, rows, ...]`` micro-batched arrays;
        ``w``: ``[m, rows]`` per-row loss weights (0 for padding rows — the
        loss is ``sum(w * per_row) / sum(w)``).
        ``stage_params``: all ``v*d`` virtual stages stacked device-major on
        the leading axis (``stack_stage_params`` for v=1,
        ``stack_interleaved_params`` otherwise).
        """
        x_leaves = jax.tree_util.tree_leaves(x)
        if not x_leaves:
            raise TypeError("x must contain at least one array leaf")
        m = x_leaves[0].shape[0]
        key = key if key is not None else make_key(0)
        data = DATA_AXIS if self.has_data_axis else None
        # Lowering counters: these fire at TRACE time (this method runs
        # inside the caller's jit trace), so they count compiles/retraces,
        # not executions — a growing count on a steady workload is the
        # compile-cache-miss signal.
        get_registry().counter("scheduled.loss_and_grad.lowerings").inc()
        get_registry().gauge("scheduled.cycles").set(self._cycles(m))
        phased = self._phase_program(m)
        overlap = phased is not None or self._overlap_enabled()
        get_registry().gauge("scheduled.transport.overlap").set(int(overlap))
        get_registry().gauge("scheduled.phase.active").set(
            int(phased is not None))
        if phased is not None:
            get_registry().gauge("scheduled.phase.scan_cycles").set(
                phased.scan_cycles)
            get_registry().gauge("scheduled.phase.unrolled_cycles").set(
                phased.unrolled_cycles)
        if self.n_stages > 1:
            # per-cycle collective count: the overlapped path packs every
            # boundary leaf and lane into one buffer per direction;
            # serialized adds each skip-lane perm group's own permutes
            ncoll = 2
            if not overlap and self.skip_lanes is not None:
                fps, bps = self._lane_perms()
                ncoll += len({tuple(pf) for pf in fps if pf is not None})
                ncoll += len({tuple(pb) for pb in bps if pb is not None})
        else:
            ncoll = 0
        get_registry().gauge(
            "scheduled.transport.collectives_per_cycle").set(ncoll)
        # Total loss weight, computed OUTSIDE the device program (w is the
        # full global array here) and passed in replicated. Keeping this as
        # an in-program psum over the data axis made it the one SUBGROUP
        # collective racing the stage-ring ppermutes — a combination that
        # intermittently starves XLA:CPU's blocking rendezvous into deadlock
        # on the single-core virtual-device test platform. Hoisting it is
        # also simply cheaper: one host-side reduction per step.
        wsum = jnp.sum(w).astype(jnp.float32)

        def x_spec(l):
            spec = [None, data] + [None] * (l.ndim - 2)
            if self.context_axis and l.ndim > self.context_dim:
                spec[self.context_dim] = self.context_axis
            return P(*spec)

        sp_specs = self._stage_param_in_specs(stage_params)
        in_specs = (
            sp_specs,
            jax.tree_util.tree_map(lambda _: P(), pre_params),
            jax.tree_util.tree_map(lambda _: P(), post_params),
            jax.tree_util.tree_map(x_spec, x),
            P(None, data),                # w
            P(),                          # wsum (precomputed, replicated)
            P(),                          # key
        )
        out_specs = (
            P(),                          # loss
            (sp_specs,
             jax.tree_util.tree_map(lambda _: P(), pre_params),
             jax.tree_util.tree_map(lambda _: P(), post_params)),
        )
        if self.stat_spec is not None:    # stats: psum'd in-program
            out_specs = out_specs + (
                jax.tree_util.tree_map(lambda _: P(), self.stat_spec),)
        run = jax.shard_map(
            functools.partial(self._device_program, m=m),
            mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)
        return run(stage_params, pre_params, post_params, x, w, wsum, key)

    # -----------------------------------------------------------------
    def forward(self, stage_params, pre_params, x, *,
                key: Optional[jax.Array] = None, train: bool = False,
                out_fn: Optional[Callable] = None):
        """FWD-only execution of the op tables: BWD/WGRAD rows masked to
        IDLE — the compiled analogue of the reference running eval through
        the same pipeline with checkpointing off (``pipeline.py:153-155``).
        This is the forward/eval path for interleaved placements (v > 1),
        which the wavefront executor cannot host. Returns the last virtual
        stage's outputs ``[m, rows, ...]`` (no post/loss applied).

        ``out_fn(h) -> pytree of [rows, ...]`` post-processes the final
        stage's activation before collection (e.g. unpacking a packed ring
        carrier into row-major values) — collected outputs must have ROWS
        as their leading dim so the data axis lands on it. Identity by
        default.

        With ``stat_spec`` the stage contract appends a stats output
        (``(h, stats)``) and the return becomes ``(outputs, stats)``: stats
        accumulate over the FWD ops (each micro-batch runs exactly once per
        stage here — no recompute, no double-count) and are psum'd over the
        stage/data axes, giving deferred BatchNorm a train-mode forward on
        interleaved (v > 1) placements. With ``skip_lanes`` the stage
        contract gains pops/stashes (see :class:`SkipLanes`); stashes take
        their direct lane hop into the FIFO park and are popped at
        FWD(i, dst) — no reverse lanes (no backward here).
        """
        if self.split_stage is not None:
            raise NotImplementedError(
                "forward() does not use the split-backward protocol")
        x_leaves = jax.tree_util.tree_leaves(x)
        if not x_leaves:
            raise TypeError("x must contain at least one array leaf")
        m = x_leaves[0].shape[0]
        key = key if key is not None else make_key(0)
        data = DATA_AXIS if self.has_data_axis else None
        get_registry().counter("scheduled.forward.lowerings").inc()
        out_fn = out_fn if out_fn is not None else (lambda h: h)

        def x_spec(l):
            spec = [None, data] + [None] * (l.ndim - 2)
            if self.context_axis and l.ndim > self.context_dim:
                spec[self.context_dim] = self.context_axis
            return P(*spec)

        sp_specs = self._stage_param_in_specs(stage_params)
        ctx0 = StageCtx(key=None, train=train)
        # per-micro-batch LOCAL specs (this runs at host level, before
        # shard_map splits the rows/context dims)
        n_data = self.mesh.shape[DATA_AXIS] if self.has_data_axis else 1

        def x_mb_sds(l):
            shape = list(l.shape[1:])     # drop the m dim
            shape[0] //= n_data
            if self.context_axis and l.ndim > self.context_dim:
                shape[self.context_dim - 1] //= \
                    self.mesh.shape[self.context_axis]
            return jax.ShapeDtypeStruct(tuple(shape), l.dtype)

        x_mb_spec = jax.tree_util.tree_map(x_mb_sds, x)
        h_spec = jax.eval_shape(
            lambda p, a: self.pre_fn(p, a, ctx0), pre_params, x_mb_spec)
        out_sds = jax.eval_shape(out_fn, h_spec)
        in_specs = (
            sp_specs,
            jax.tree_util.tree_map(lambda _: P(), pre_params),
            jax.tree_util.tree_map(x_spec, x),
            P(),                          # key
        )
        out_specs = jax.tree_util.tree_map(
            lambda sp_: P(*([STAGE_AXIS, None, data]
                            + [None] * (len(sp_.shape) - 1))), out_sds)
        if self.stat_spec is not None:   # stats: psum'd in-program
            out_specs = (out_specs, jax.tree_util.tree_map(
                lambda _: P(), self.stat_spec))
        run = jax.shard_map(
            functools.partial(self._device_forward, m=m, train=train,
                              out_fn=out_fn),
            mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)
        res = run(stage_params, pre_params, x, key)
        out, stats = res if self.stat_spec is not None else (res, None)
        # the last virtual stage lives on device d-1 (v=1: linear chain;
        # v>1: stage S-1 = (v-1)*d + (d-1) is on device d-1 either way)
        out = jax.tree_util.tree_map(lambda o: o[-1], out)
        return out if self.stat_spec is None else (out, stats)

    def _device_forward(self, stage_params, pre_params, x, key, *, m,
                        train, out_fn):
        d, v = self.n_stages, self.v
        S = self.n_virtual
        j = jax.lax.axis_index(STAGE_AXIS)
        params_dev = stage_params

        ctx0 = StageCtx(key=None, train=train)
        x_mb_spec = jax.eval_shape(lambda a: _index_spec(a), x)
        h_spec = jax.eval_shape(
            lambda p, a: self.pre_fn(p, a, ctx0), pre_params, x_mb_spec)

        (op_np, mb_np, grp_np, rxslot_np), T, Sg, sentinel = \
            self._host_tables(m)
        # eval: checkpointing (hence backward) does not exist — mask every
        # non-FWD op to IDLE; the FWD entries' relative timing already
        # satisfies the ring transport constraints the full table verified
        op_np = np.where(op_np == FWD, FWD, IDLE)
        lanes = self.skip_lanes
        if lanes is not None:
            capf_np, _, Kf, _ = self._skip_tables(m, op_np, mb_np, grp_np,
                                                  fwd_only=True)
            lane_fwd_perms, _ = self._lane_perms()
            xs = (jnp.asarray(op_np), jnp.asarray(mb_np),
                  jnp.asarray(grp_np), jnp.asarray(rxslot_np),
                  jnp.asarray(capf_np))
        else:
            Kf = ()
            xs = (jnp.asarray(op_np), jnp.asarray(mb_np),
                  jnp.asarray(grp_np), jnp.asarray(rxslot_np))

        def zeros_of(spec):
            return jnp.zeros(spec.shape, spec.dtype)

        def slots_of(spec, k):
            return jnp.zeros((k + 1,) + tuple(spec.shape), spec.dtype)

        out_sds = jax.eval_shape(out_fn, h_spec)
        h_ring = jax.tree_util.tree_map(zeros_of, h_spec)
        stash = jax.tree_util.tree_map(
            lambda s_: slots_of(s_, v * Sg), h_spec)
        # one output slot per micro-batch + a sentinel for non-last stages
        outbuf = jax.tree_util.tree_map(
            lambda s_: slots_of(s_, m), out_sds)
        if lanes is not None:
            sk_ring0 = tuple(jax.tree_util.tree_map(zeros_of, sp_)
                             for sp_ in lanes.specs)
            sk_park0 = tuple(
                jax.tree_util.tree_map(
                    lambda s_, k=k: slots_of(s_, k), sp_)
                for sp_, k in zip(lanes.specs, Kf))
        else:
            sk_ring0 = sk_park0 = ()

        if v == 1:
            fwd_perm = [(k, k + 1) for k in range(d - 1)]
        else:
            fwd_perm = [(q, (q + 1) % d) for q in range(d)]

        def cycle(carry, row):
            h_ring, stash, outbuf, stats_acc, sk_ring, sk_park = carry
            if lanes is not None:
                op_r, mb_r, grp_r, rx_r, capf_r = row
            else:
                op_r, mb_r, grp_r, rx_r = row
            opj = jax.lax.dynamic_index_in_dim(op_r, j, 0, keepdims=False)
            i = jax.lax.dynamic_index_in_dim(mb_r, j, 0, keepdims=False)
            g = jax.lax.dynamic_index_in_dim(grp_r, j, 0, keepdims=False)
            rslot = jax.lax.dynamic_index_in_dim(rx_r, j, 0, keepdims=False)
            s = g * d + j

            stash = jax.tree_util.tree_map(
                lambda st, hr: jax.lax.dynamic_update_index_in_dim(
                    st, hr, rslot, 0), stash, h_ring)
            if lanes is not None:
                # capture arriving lane values into their FIFO parks at
                # the host-planned slots (sentinel writes are no-ops into
                # the spare slot)
                fslots = [jax.lax.dynamic_index_in_dim(
                    capf_r[l], j, 0, keepdims=False)
                    for l in range(len(lanes.pairs))]
                sk_park = tuple(
                    jax.tree_util.tree_map(
                        lambda st, reg, sl=sl:
                        jax.lax.dynamic_update_index_in_dim(st, reg, sl, 0),
                        pk, rg)
                    for pk, rg, sl in zip(sk_park, sk_ring, fslots))
            kis = jax.random.fold_in(jax.random.fold_in(key, i), s)
            x_mb = _index(x, i)
            params_g = (_index(params_dev, 0) if v == 1
                        else _index(params_dev, g))
            h_in = jax.tree_util.tree_map(
                lambda st: jax.lax.dynamic_index_in_dim(
                    st, g * Sg + i % Sg, 0, keepdims=False), stash)
            pops = (tuple(
                jax.tree_util.tree_map(
                    lambda st, k=k: jax.lax.dynamic_index_in_dim(
                        st, i % k, 0, keepdims=False), pk)
                for pk, k in zip(sk_park, Kf))
                if lanes is not None else None)

            def fwd_branch():
                h0 = jax.lax.cond(
                    s == 0,
                    lambda: self.pre_fn(
                        pre_params, x_mb,
                        StageCtx(key=jax.random.fold_in(kis, 0),
                                 train=train, data_axis=self.bn_axis)),
                    lambda: h_in)
                ctx = StageCtx(key=jax.random.fold_in(kis, 1), train=train,
                               stage=s, data_axis=self.bn_axis)
                out = (self.stage_fn(params_g, h0, ctx, pops)
                       if lanes is not None
                       else self.stage_fn(params_g, h0, ctx))
                h1, stashes, st = self._split_out(out)
                stats2 = (jax.tree_util.tree_map(jnp.add, stats_acc, st)
                          if self.stat_spec is not None else stats_acc)
                if lanes is not None:
                    # fresh stashes board their lanes at the source stage
                    tx_sk = tuple(
                        jax.tree_util.tree_map(
                            lambda sv, reg, src=src: jnp.where(
                                jnp.asarray(s == src), sv, reg), svv, rg)
                        for (src, _), svv, rg in zip(lanes.pairs, stashes,
                                                     sk_ring))
                else:
                    tx_sk = sk_ring
                widx = jnp.where(s == S - 1, i, m)   # sentinel elsewhere
                new_out = jax.tree_util.tree_map(
                    lambda buf, l: jax.lax.dynamic_update_index_in_dim(
                        buf, l, widx, 0), outbuf, out_fn(h1))
                return new_out, h1, stats2, tx_sk

            def idle_branch():
                return outbuf, h_ring, stats_acc, sk_ring

            outbuf2, tx_h, stats2, tx_sk = jax.lax.switch(
                jnp.clip(opj, 0, 1), [idle_branch, fwd_branch])
            if d > 1:
                tx_h = jax.tree_util.tree_map(
                    lambda a: jax.lax.ppermute(a, STAGE_AXIS, fwd_perm),
                    tx_h)
                if lanes is not None:
                    tx_sk = tuple(
                        (jax.tree_util.tree_map(
                            lambda a, pf=pf: jax.lax.ppermute(
                                a, STAGE_AXIS, pf), lv)
                         if pf is not None else lv)
                        for lv, pf in zip(tx_sk, lane_fwd_perms))
            return (tx_h, stash, outbuf2, stats2, tx_sk, sk_park), None

        stats0 = (self._zero_seed_like(self.stat_spec)
                  if self.stat_spec is not None else ())
        (_, _, outbuf, stats_out, _, _), _ = jax.lax.scan(
            cycle, (h_ring, stash, outbuf, stats0, sk_ring0, sk_park0), xs)
        outs = jax.tree_util.tree_map(lambda b: b[None, :m], outbuf)
        if self.stat_spec is None:
            return outs
        # each virtual stage fills only its own slots (zeros elsewhere);
        # data shards hold per-shard partial sums — psum collects both
        stat_axes = ((STAGE_AXIS, DATA_AXIS) if self.has_data_axis
                     else (STAGE_AXIS,))
        stats_out = jax.tree_util.tree_map(
            lambda a: jax.lax.psum(a, stat_axes), stats_out)
        return outs, stats_out

    # -----------------------------------------------------------------
    def _stage_param_in_specs(self, stage_params):
        """Stacked-layout PartitionSpecs: P(stage) per leaf, or
        P(stage, *leaf_spec) when ``stage_param_specs`` names per-leaf
        shardings (tensor parallelism)."""
        if self.stage_param_specs is None:
            return jax.tree_util.tree_map(lambda _: P(STAGE_AXIS),
                                          stage_params)
        is_p = lambda v: isinstance(v, P)
        specs = jax.tree_util.tree_map(
            lambda s_: P(STAGE_AXIS, *s_), self.stage_param_specs,
            is_leaf=is_p)
        got = jax.tree_util.tree_structure(specs)
        want = jax.tree_util.tree_structure(stage_params)
        if got != want:
            raise ValueError(
                f"stage_param_specs structure {got} does not match the "
                f"stacked stage params {want}")
        return specs

    def _grad_reduce_axes(self):
        """Mesh axes grads sum over: every non-stage axis EXCEPT the model
        axis (TP grad contract — see ``stage_param_specs``)."""
        return tuple(a for a in self.mesh.axis_names
                     if a not in (STAGE_AXIS, MODEL_AXIS))

    # -----------------------------------------------------------------
    def _f_body(self, params_g, prep, h_in, x_mb, kis, s, pops=None):
        """The per-(cycle, device) forward for virtual stage ``s``: pre
        (stage 0 only) → stage body. Everything the backward needs to
        differentiate is an explicit argument — no closure over device state
        (in particular no collective-derived values like the global weight
        sum, which would change the vjp residual structure under shard_map) —
        so the residual structure is derivable abstractly.

        With :class:`SkipLanes`, ``pops`` is the per-lane tuple of popped
        values and the return is ``(h_out, stashes)``.

        The post (decode/loss) is deliberately NOT part of this function:
        its vjp residuals are vocab-scale ([rows, seq, vocab] logits plus a
        weight-cast copy — hundreds of MB at tutorial scale) and the residual
        store replicates slot structure across every (virtual stage, slot),
        so folding the post into the stored vjp OOMs a 16G chip. Instead the
        executor stashes the last stage's ~activation-sized output and
        rebuilds the post vjp fresh at backward time (:meth:`_post_contrib`)
        — the compiled analogue of the reference keeping the loss OUTSIDE
        ``Pipe`` and feeding its gradient into the recorded graph
        (``main.py:216-218``)."""
        train = True
        h0 = jax.lax.cond(
            s == 0,
            lambda: self.pre_fn(prep, x_mb,
                                StageCtx(key=jax.random.fold_in(kis, 0),
                                         train=train,
                                         data_axis=self.bn_axis)),
            lambda: h_in)
        # ctx.stage carries the VIRTUAL stage index (traced on the d>1 path,
        # a Python int on the d=1 static path) so heterogeneous adapters can
        # switch their per-stage bodies on it (parallel.hetero_scheduled).
        ctx = StageCtx(key=jax.random.fold_in(kis, 1),
                       train=train, stage=s, data_axis=self.bn_axis)
        if self.skip_lanes is not None:
            return self.stage_fn(params_g, h0, ctx, pops)
        return self.stage_fn(params_g, h0, ctx)

    def _split_out(self, out):
        """Destructure a stage output into ``(h, stashes, stats)`` per the
        configured extras (None for the absent ones) — the single decoder
        for every (skip_lanes x stat_spec) combination."""
        if self.skip_lanes is not None and self.stat_spec is not None:
            h, sk, st = out
            return h, sk, st
        if self.skip_lanes is not None:
            h, sk = out
            return h, sk, None
        if self.stat_spec is not None:
            h, st = out
            return h, None, st
        return out, None, None

    def _zero_seed_like(self, spec_tree):
        return jax.tree_util.tree_map(
            lambda sp_: jnp.zeros(sp_.shape, sp_.dtype), spec_tree)

    def _make_seed(self, seed_h, seed_sk):
        """Assemble the vjp seed matching the stage output structure:
        stats always get zero cotangents (stop_gradient'd at source)."""
        if self.skip_lanes is not None and self.stat_spec is not None:
            return (seed_h, seed_sk, self._zero_seed_like(self.stat_spec))
        if self.skip_lanes is not None:
            return (seed_h, seed_sk)
        if self.stat_spec is not None:
            return (seed_h, self._zero_seed_like(self.stat_spec))
        return seed_h

    def _post_contrib(self, postp, h1, x_mb, w_mb, kis):
        """UNNORMALIZED loss contribution ``sum(w * per_row)`` of one
        micro-batch; the executor divides by the global ``sum(w)`` and seeds
        its backward with ``1/sum(w)``."""
        return jnp.sum(
            w_mb * self.post_fn(postp, h1, x_mb,
                                StageCtx(key=jax.random.fold_in(kis, 2),
                                         train=True,
                                         data_axis=self.bn_axis))
        ).astype(jnp.float32)

    def _vjp_wrt(self, params_g, prep, h_in, x_mb, kis, s, pops=None):
        """vjp of :meth:`_f_body` w.r.t. (group params, pre, h_in[, pops]).

        With skip lanes the primal out is ``(h, stashes)``, the seed is
        ``(g_h, g_stashes)`` and the cotangents gain ``g_pops``."""
        if self.skip_lanes is not None:
            return jax.vjp(
                lambda a, b, dd, pp: self._f_body(a, b, dd, x_mb, kis, s,
                                                  pops=pp),
                params_g, prep, h_in, pops)
        return jax.vjp(
            lambda a, b, dd: self._f_body(a, b, dd, x_mb, kis, s),
            params_g, prep, h_in)

    def _f_body_split(self, params_g, prep, h_in, x_mb, kis, s, zs):
        """Split-backward twin of :meth:`_f_body`: pre (stage 0 only) then
        the TAPPED stage body. Returns ``(h_out, taps)``."""
        train = True
        h0 = jax.lax.cond(
            s == 0,
            lambda: self.pre_fn(prep, x_mb,
                                StageCtx(key=jax.random.fold_in(kis, 0),
                                         train=train,
                                         data_axis=self.bn_axis)),
            lambda: h_in)
        return self.split_stage.tapped_fn(
            params_g, h0,
            StageCtx(key=jax.random.fold_in(kis, 1), train=train, stage=s,
                     data_axis=self.bn_axis), zs)

    def _vjp_wrt_split(self, params_g, prep, h_in, x_mb, kis, s):
        """Params-constant vjp of the tapped body w.r.t. (pre, h, zs):
        ``(h1, vjp_fn, taps)``; ``vjp_fn(seed) -> (gpre, gh, gzs)``."""
        zs = self.split_stage.zs_fn(params_g, h_in)
        return jax.vjp(
            lambda b, dd, zz: self._f_body_split(
                params_g, b, dd, x_mb, kis, s, zz),
            prep, h_in, zs, has_aux=True)

    def _vjp_wrt_policy(self, params_g, prep, h_in, x_mb, kis, s,
                        pops=None):
        """Policy-selective vjp: residuals are only what ``remat_policy``
        saves (the backward recomputes the rest in place)."""
        if self.skip_lanes is not None:
            wrapped = jax.checkpoint(
                lambda a, b, dd, pp: self._f_body(a, b, dd, x_mb, kis, s,
                                                  pops=pp),
                policy=self.remat_policy)
            return jax.vjp(wrapped, params_g, prep, h_in, pops)
        wrapped = jax.checkpoint(
            lambda a, b, dd: self._f_body(a, b, dd, x_mb, kis, s),
            policy=self.remat_policy)
        return jax.vjp(wrapped, params_g, prep, h_in)

    # -----------------------------------------------------------------
    def _host_tables(self, m):
        """Static (cycle, device) tables + receive-slot plan, host-side."""
        d, v = self.n_stages, self.v
        S = v * d
        Sg = self.schedule.stash_slots(m, d)
        tables = self.schedule.op_tables(m, d)
        if len(tables) == 2:            # non-interleaved: group is always 0
            op_np, mb_np = tables
            grp_np = np.zeros_like(op_np)
        else:
            op_np, mb_np, grp_np = tables
        T = op_np.shape[0]
        sentinel = v * Sg
        # rxslot[t, p]: stash slot for the value arriving at device p at
        # cycle t (the upstream device's cycle-(t-1) output), sentinel when
        # it is not a real activation (IDLE/BWD upstream, or the last
        # virtual stage's output, which has no consumer).
        rxslot_np = np.full((T, d), sentinel, np.int32)
        for t in range(1, T):
            for p in range(d):
                q = (p - 1) % d
                if self.v == 1 and p == 0:
                    continue            # linear ring: nothing enters stage 0
                if op_np[t - 1, q] != FWD:
                    continue
                s_up = grp_np[t - 1, q] * d + q
                if s_up >= S - 1:
                    continue
                g2 = (s_up + 1) // d
                rxslot_np[t, p] = g2 * Sg + (mb_np[t - 1, q] % Sg)
        return (op_np, mb_np, grp_np, rxslot_np), T, Sg, sentinel

    def _host_tables_overlap(self, m):
        """Comm-shifted tables + receive/grad-park plans for overlapped
        transport (host-side, all static).

        The serialized tables are retimed by :func:`shift_comm_tables` so a
        value produced at cycle t is permuted at the START of body t+1 and
        parked there AFTER that body's compute — first legal read t+2 (hop
        latency 2). Slot capacities are then re-derived from the shifted
        timings under the park-after-compute window rule
        (:func:`overlap_joint_capacity`): one joint ``Sg`` covers the
        arriving-input stash, the in-branch residual/taps stores and the
        last stage's ``h_last`` park (they share the ``g*Sg + i % Sg`` /
        ``i % Sg`` slot arithmetic); ``Gg`` sizes the NEW grad park — under
        serialized transport the reverse ring is rigid (a cotangent is
        consumed the cycle it arrives), under overlap it is elastic and
        arriving cotangents park in a small FIFO until their BWD.
        ``verify_shifted_op_tables`` re-proves the whole contract before
        the tables reach the executor.

        ``rxslot`` keeps the serialized arithmetic unchanged: in both
        modes the value parked at body t was produced by the upstream
        compute at body t-1 (serialized: end-of-body permute; overlapped:
        start-of-next-body permute). ``gxslot`` is its reverse-direction
        twin for the grad park."""
        tables = self.schedule.op_tables(m, self.n_stages)
        if len(tables) == 2:
            op0, mb0 = tables
            grp0 = None
        else:
            op0, mb0, grp0 = tables
        op_np, mb_np, grp_np = shift_comm_tables(
            op0, mb0, grp0, m=m, d=self.n_stages, v=self.v)
        return self._overlap_plans(op_np, mb_np, grp_np, m,
                                   has_grp=grp0 is not None)

    def _host_tables_phased(self, m):
        """Plans for the phase-compiled executor: identical structure to
        :meth:`_host_tables_overlap` (the aligner emits hop-2 tables that
        honor the same park-after-compute transport contract), but the
        tables come from :func:`~pipe_tpu.core.schedule.compile_phases` —
        cycle-uniform, segmented into ramps and dense periodic windows.
        Callers must only reach here with an accepted verdict."""
        prog = self._phase_program(m)
        if prog is None:
            raise AssertionError(
                "_host_tables_phased called without an accepted phase "
                "program — the caller must fall back to the interpreter")
        return self._overlap_plans(prog.op, prog.mbi, prog.grp, m,
                                   has_grp=self.v > 1)

    def _overlap_plans(self, op_np, mb_np, grp_np, m, *, has_grp):
        """Capacity + park plans for hop-2 (overlapped-transport) tables —
        shared by the comm-shifted and phase-aligned paths."""
        d, v = self.n_stages, self.v
        S = v * d
        T = op_np.shape[0]
        t_f, t_b, t_w = _times_by_code(op_np, mb_np, grp_np, m, d, v)
        read_last = np.maximum(t_f, np.maximum(t_b, t_w))
        wins = [(t_f[:, s - 1] + 1, read_last[:, s]) for s in range(1, S)]
        wins += [(t_f[:, s], read_last[:, s]) for s in range(S)]
        wins += [(t_f[:, S - 1], t_b[:, S - 1])]        # h_last park
        Sg = overlap_joint_capacity(wins, m)
        gw = [(t_b[:, s + 1] + 1, t_b[:, s]) for s in range(S - 1)]
        Gg = overlap_joint_capacity(gw, m) if gw else 1
        has_w = bool((op_np == WGRAD).any())
        split_dce = has_w and self.checkpoint == "never"
        Wg = (overlap_joint_capacity(
            [(t_b[:, s], t_w[:, s]) for s in range(S)], m)
            if split_dce else 0)
        verify_shifted_op_tables(
            op_np, mb_np, grp_np if has_grp else None,
            m=m, d=d, v=v, splits_backward=has_w, stash_slots=Sg,
            grad_slots=Gg, wstash_slots=Wg if split_dce else None)
        sentinel = v * Sg
        gsentinel = v * Gg
        rxslot_np = np.full((T, d), sentinel, np.int32)
        gxslot_np = np.full((T, d), gsentinel, np.int32)
        for t in range(1, T):
            for p in range(d):
                q = (p - 1) % d
                if not (v == 1 and p == 0) and op_np[t - 1, q] == FWD:
                    s_up = grp_np[t - 1, q] * d + q
                    if s_up < S - 1:
                        g2 = (s_up + 1) // d
                        rxslot_np[t, p] = g2 * Sg + (mb_np[t - 1, q] % Sg)
                q = (p + 1) % d
                if not (v == 1 and p == d - 1) and op_np[t - 1, q] == BWD:
                    s_up = grp_np[t - 1, q] * d + q
                    if s_up > 0:
                        g2 = (s_up - 1) // d
                        gxslot_np[t, p] = g2 * Gg + (mb_np[t - 1, q] % Gg)
        return ((op_np, mb_np, grp_np, rxslot_np, gxslot_np), T, Sg, Gg,
                Wg, sentinel, gsentinel)

    def _lane_hops(self):
        """Physical hop count per skip lane on the ring: ``(dst%d - src%d)
        % d``. Under overlapped transport a lane with >= 1 hops rides the
        packed carriers as an H-slot shift register (one relay hop per
        cycle); 0-hop lanes (same device, v > 1) keep their register — a
        permute would move them off-device."""
        d = self.n_stages
        return tuple(((dst % d) - (src % d)) % d
                     for (src, dst) in self.skip_lanes.pairs)

    def _skip_tables(self, m, op_np, mb_np, grp_np, *, fwd_only=False,
                     overlap=False):
        """Host-side skip-lane plan from the op tables.

        Per lane ``l = (src, dst)`` (VIRTUAL stage indices; the physical
        endpoints are ``src % d`` / ``dst % d``):

        * ``capf[t, l, p]``: FIFO slot at device ``p`` parking the value
          arriving on the forward lane hop at cycle ``t`` (sentinel
          ``Kf[l]`` when nothing real arrives). Arrival is deterministic:
          the stash emitted at FWD(i, src) takes the lane's single direct
          permute, reaching ``dst % d`` at cycle ``fwd(i, src) + 1``.
        * ``capg[t, l, p]``: same for the pop cotangent taking the reverse
          hop from BWD(i, dst) to ``src % d``.
        * ``Kf[l]`` / ``Kg[l]``: smallest FIFO depths such that slot
          ``i % K`` never collides across overlapping live windows. The
          forward live window extends to BWD(i, dst) under recompute
          modes (the re-run needs the pops again), mirroring the
          activation stash.

        ``fwd_only=True`` plans for the FWD-masked eval tables: windows
        end at FWD(i, dst) (no reread — eval has no backward) and the
        reverse plan is skipped (``capg=None, Kg=()``).

        ``overlap=True`` plans for the comm-shifted tables: lanes ride the
        packed carriers as per-cycle relays, so arrival is ``max(H, 1)``
        cycles after boarding (H = physical hops; 0-hop register lanes
        still capture one cycle later), and because arrivals park AFTER
        the cycle's compute the consumer must be STRICTLY later than the
        arrival.
        """
        d = self.n_stages
        hops = self._lane_hops() if overlap else None
        S = self.n_virtual
        T = op_np.shape[0]
        pairs = self.skip_lanes.pairs
        fwd_c = np.full((m, S), -1, np.int64)
        bwd_c = np.full((m, S), -1, np.int64)
        for t in range(T):
            for p in range(d):
                s = grp_np[t, p] * d + p
                if op_np[t, p] == FWD:
                    fwd_c[mb_np[t, p], s] = t
                elif op_np[t, p] == BWD:
                    bwd_c[mb_np[t, p], s] = t

        def fifo_depth(windows):
            for K in range(1, m + 1):
                ok = all(
                    windows[i][1] < windows[i2][0]
                    for i in range(m) for i2 in range(i + K, m, K))
                if ok:
                    return K
            return m

        Kf, Kg = [], []
        f_events, g_events = [], []   # (t, lane, device, slot)
        for lidx, (src, dst) in enumerate(pairs):
            lag = max(hops[lidx], 1) if overlap else 1
            slack = 1 if overlap else 0   # park-after-compute: strict <
            wf, wg = [], []
            for i in range(m):
                arr_f = fwd_c[i, src] + lag
                use_f = fwd_c[i, dst]
                # host-side plan invariants raise (not assert: python -O
                # must not turn a timing violation into silent corruption)
                if not (0 <= fwd_c[i, src] and arr_f + slack <= use_f):
                    raise ValueError(
                        f"skip lane ({src},{dst}): stash for micro-batch "
                        f"{i} arrives at cycle {arr_f} after its FWD "
                        f"{use_f} — the schedule violates the "
                        f"{'relay' if overlap else 'direct-hop'} "
                        f"timing assumption")
                reread = (not fwd_only
                          and self.remat_policy is None
                          and (self.checkpoint == "always"
                               or (self.checkpoint == "except_last"
                                   and i != m - 1)))
                wf.append((arr_f, bwd_c[i, dst] if reread else use_f))
                if fwd_only:
                    continue
                arr_g = bwd_c[i, dst] + lag
                use_g = bwd_c[i, src]
                if not (0 <= bwd_c[i, dst] and arr_g + slack <= use_g):
                    raise ValueError(
                        f"skip lane ({src},{dst}): cotangent for "
                        f"micro-batch {i} arrives at cycle {arr_g} after "
                        f"its BWD {use_g} — the schedule violates the "
                        f"{'relay' if overlap else 'direct-hop'} "
                        f"timing assumption")
                wg.append((arr_g, use_g))
            kf = fifo_depth(wf)
            Kf.append(kf)
            for i in range(m):
                f_events.append((wf[i][0], lidx, dst % d, i % kf))
            if not fwd_only:
                kg = fifo_depth(wg)
                Kg.append(kg)
                for i in range(m):
                    g_events.append((wg[i][0], lidx, src % d, i % kg))
        capf = np.zeros((T, len(pairs), d), np.int32)
        for lidx in range(len(pairs)):
            capf[:, lidx, :] = Kf[lidx]      # sentinel
        for (t, lidx, p, slot) in f_events:
            capf[t, lidx, p] = slot
        if fwd_only:
            return capf, None, Kf, ()
        capg = np.zeros((T, len(pairs), d), np.int32)
        for lidx in range(len(pairs)):
            capg[:, lidx, :] = Kg[lidx]
        for (t, lidx, p, slot) in g_events:
            capg[t, lidx, p] = slot
        return capf, capg, Kf, Kg

    def _lane_perms(self):
        """Per-lane direct permute endpoints, MERGED across disjoint lanes.

        Base form: lane ``(src, dst)`` takes one hop ``src % d -> dst % d``
        (``None`` when both virtual stages share a device — the lane
        register itself is the transport, no collective needed).

        Merge: lanes whose endpoint pairs are pairwise disjoint (no shared
        source, no shared destination) are grouped, and every lane in a
        group gets the group's UNION perm list. Identical perm lists let
        XLA's collective-permute combiner fuse the group's per-lane
        permutes into one collective per cycle instead of L. Soundness: a
        lane's register riding another pair's route only changes which
        garbage lands at non-capture devices — un-listed destinations
        already receive zeros from ``ppermute``, and the host capture
        tables (``_skip_tables``) park anything not scheduled into the
        sentinel slot either way.
        """
        d = self.n_stages

        def merged(pairs_mod):
            # greedy grouping: first group whose used srcs/dsts are
            # disjoint from this lane's pair
            groups: List[dict] = []
            assign = [None] * len(pairs_mod)
            for l, pm in enumerate(pairs_mod):
                if pm is None:
                    continue
                ps, pd = pm
                for gi, grp in enumerate(groups):
                    if ps not in grp["src"] and pd not in grp["dst"]:
                        grp["src"].add(ps)
                        grp["dst"].add(pd)
                        grp["perm"].append((ps, pd))
                        assign[l] = gi
                        break
                else:
                    groups.append({"src": {ps}, "dst": {pd},
                                   "perm": [(ps, pd)]})
                    assign[l] = len(groups) - 1
            return [None if a is None else groups[a]["perm"]
                    for a in assign]

        fwd_pairs = [None if (src % d) == (dst % d)
                     else (src % d, dst % d)
                     for (src, dst) in self.skip_lanes.pairs]
        bwd_pairs = [None if pm is None else (pm[1], pm[0])
                     for pm in fwd_pairs]
        return merged(fwd_pairs), merged(bwd_pairs)

    def _use_static(self, m: int) -> bool:
        if self.static_unroll is not None:
            return self.static_unroll
        return self._cycles(m) <= _STATIC_UNROLL_MAX_CYCLES

    # -----------------------------------------------------------------
    def _device_program_static(self, stage_params, pre_params, post_params,
                               x, w, wsum, key, *, m):
        """Single-stage-device specialization: the tables unrolled at trace
        time into straight-line code.

        With ``d == 1`` every table entry ``op[t, 0]`` is a static Python
        int, so the per-cycle ``lax.switch``/slot machinery of the dynamic
        path is unnecessary — and measurably hostile: XLA's copy-insertion
        around conditionals inside the scan copies the pass-through grad
        accumulators (the full per-device param tree) almost every cycle,
        measured at 123 ms/step of pure ``copy`` on the 520M tutorial config
        (2.0x the AD executor). Here ops specialize at trace time: stash,
        residual store and the cotangent hand-off become Python dicts of
        traced values, grads accumulate with straight adds, and the emitted
        program matches hand-written gradient accumulation with the exact
        per-micro-batch checkpoint policy interleaved in table order. The
        dynamic scan path remains the d > 1 program.
        """
        v = self.v
        S = self.n_virtual
        mode = self.checkpoint
        inv_wsum = 1.0 / wsum

        tables = self.schedule.op_tables(m, 1)
        op_np, mb_np = tables[0], tables[1]
        grp_np = tables[2] if len(tables) == 3 else np.zeros_like(op_np)

        split_w = bool((op_np == WGRAD).any())
        stash = {}     # (i, s) -> stage input (released at B, or W if split)
        res = {}       # (i, g) -> vjp_fn (policy-gated)
        h_last = {}    # i -> last virtual stage's output (pops at BWD)
        gbuf = {}      # (i, s) -> cotangent from stage s+1 (pops at BWD)
        wpend = {}     # (i, g) -> deferred (gp, gpre) or (structural
        #                split) the per-op output cotangents g_zs
        tapsd = {}     # (i, g) -> taps (structural split only)
        g_per_group = {}
        g_pre = jax.tree_util.tree_map(jnp.zeros_like, pre_params)
        g_post = jax.tree_util.tree_map(jnp.zeros_like, post_params)
        loss = jnp.zeros((), jnp.float32)
        stats_acc = None   # lazily set from the first FWD's stats output
        add = functools.partial(jax.tree_util.tree_map, jnp.add)

        for t in range(op_np.shape[0]):
            opj = int(op_np[t, 0])
            if opj == 0:          # IDLE
                continue
            i = int(mb_np[t, 0])
            g = int(grp_np[t, 0])
            s = g                 # d == 1: virtual stage == group
            kis = jax.random.fold_in(jax.random.fold_in(key, i), s)
            x_mb = _index(x, i)
            w_mb = _index(w, i)
            params_g = _index(stage_params, g)
            # Read (not pop) at FWD: recompute modes re-read the same input
            # at this stage's BWD, which is where the entry is released.
            h_in = stash.get((i, s))
            if h_in is None:      # stage 0 consumes x via pre inside _f_body
                h_in = jax.tree_util.tree_map(
                    lambda l: jnp.zeros(l.shape, l.dtype),
                    jax.eval_shape(lambda p, a: self.pre_fn(
                        p, a, StageCtx(key=None, train=True)),
                        pre_params, x_mb))
            if opj == FWD:
                save = (mode == "never"
                        or (mode == "except_last" and i == m - 1))
                if self.split_stage is not None:   # never mode guaranteed
                    h1, vjp_fn, taps = self._vjp_wrt_split(
                        params_g, pre_params, h_in, x_mb, kis, s)
                    res[(i, g)] = vjp_fn
                    tapsd[(i, g)] = taps
                elif save:
                    out, vjp_fn = self._vjp_wrt(
                        params_g, pre_params, h_in, x_mb, kis, s)
                    h1, _, stats_t = self._split_out(out)
                    res[(i, g)] = vjp_fn
                elif self.remat_policy is not None:
                    # selective remat: store the policy-saved residual
                    # subset now; backward recomputes only the remainder
                    out, vjp_fn = self._vjp_wrt_policy(
                        params_g, pre_params, h_in, x_mb, kis, s)
                    h1, _, stats_t = self._split_out(out)
                    res[(i, g)] = vjp_fn
                else:
                    out = self._f_body(params_g, pre_params, h_in, x_mb,
                                       kis, s)
                    h1, _, stats_t = self._split_out(out)
                if self.stat_spec is not None:
                    stats_acc = (add(stats_acc, stats_t)
                                 if stats_acc is not None else stats_t)
                if s == S - 1:
                    loss = loss + self._post_contrib(post_params, h1, x_mb,
                                                     w_mb, kis)
                    h_last[i] = h1
                else:
                    stash[(i, s + 1)] = h1
            elif opj == BWD:
                if s == S - 1:
                    _, post_vjp = jax.vjp(
                        lambda pp, hh: self._post_contrib(
                            pp, hh, x_mb, w_mb, kis),
                        post_params, h_last.pop(i))
                    gpost, seed_h = post_vjp(inv_wsum)
                    g_post = add(g_post, gpost)
                else:
                    seed_h = gbuf.pop((i, s))
                if self.split_stage is not None:
                    # structural split: stored params-constant vjp — the
                    # input-grad chain only; per-op cotangents park for W
                    gpre, gh, gzs = res.pop((i, g))(seed_h)
                    g_pre = add(g_pre, gpre)
                    wpend[(i, g)] = gzs
                    if s > 0:
                        gbuf[(i, s - 1)] = gh
                    continue
                vjp_fn = res.pop((i, g), None)
                if vjp_fn is None:
                    # the manual re-forward, under the name jax.checkpoint
                    # gives its own, so that a trace tells it from the
                    # first forward
                    with device_scope(REMAT_SCOPE):
                        _, vjp_fn = self._vjp_wrt(
                            params_g, pre_params, h_in, x_mb, kis, s)
                gp, gpre, gh = vjp_fn(self._make_seed(seed_h, None))
                if split_w:
                    # B/W split table (zb-h1): the weight/pre grads computed
                    # here are traced values — defer only their ACCUMULATION
                    # to the W slot (straight-line code, so no recompute;
                    # ordering is immaterial at d == 1 where there is no
                    # bubble to fill, but the table contract is honored).
                    wpend[(i, g)] = (gp, gpre)
                else:
                    g_per_group[g] = (add(g_per_group[g], gp)
                                      if g in g_per_group else gp)
                    g_pre = add(g_pre, gpre)
                if s > 0:
                    gbuf[(i, s - 1)] = gh
                if not split_w:
                    stash.pop((i, s), None)
            else:                 # WGRAD
                if self.split_stage is not None:
                    # structural split: pure weight-grad contractions
                    gp = self.split_stage.wgrad_fn(tapsd.pop((i, g)),
                                                   wpend.pop((i, g)))
                else:
                    gp, gpre = wpend.pop((i, g))
                    g_pre = add(g_pre, gpre)
                g_per_group[g] = (add(g_per_group[g], gp)
                                  if g in g_per_group else gp)
                stash.pop((i, s), None)
        assert not stash and not res and not h_last and not gbuf \
            and not wpend and not tapsd, \
            "static schedule left unconsumed state"

        g_sp = jax.tree_util.tree_map(
            lambda *rows: jnp.stack(rows, axis=0),
            *[g_per_group[g] for g in range(v)])

        other_axes = self._grad_reduce_axes()
        if other_axes:
            g_sp = jax.tree_util.tree_map(
                lambda gg: jax.lax.psum(gg, other_axes), g_sp)
            g_pre = jax.tree_util.tree_map(
                lambda gg: jax.lax.psum(gg, other_axes), g_pre)
            g_post = jax.tree_util.tree_map(
                lambda gg: jax.lax.psum(gg, other_axes), g_post)
        loss_axes = (DATA_AXIS,) if self.has_data_axis else ()
        if loss_axes:
            loss = jax.lax.psum(loss, loss_axes)
        if self.stat_spec is not None:
            if stats_acc is None:
                stats_acc = self._zero_seed_like(self.stat_spec)
            if loss_axes:
                stats_acc = jax.tree_util.tree_map(
                    lambda a: jax.lax.psum(a, loss_axes), stats_acc)
            return loss * inv_wsum, (g_sp, g_pre, g_post), stats_acc
        return loss * inv_wsum, (g_sp, g_pre, g_post)

    # -----------------------------------------------------------------
    def _device_program(self, stage_params, pre_params, post_params, x, w,
                        wsum, key, *, m):
        d, v = self.n_stages, self.v
        S = self.n_virtual
        if d == 1 and self._use_static(m):
            get_registry().counter("scheduled.program.static_unroll").inc()
            return self._device_program_static(
                stage_params, pre_params, post_params, x, w, wsum, key, m=m)
        phased_prog = self._phase_program(m)
        if phased_prog is not None:
            get_registry().counter("scheduled.program.phase_compiled").inc()
        else:
            get_registry().counter("scheduled.program.dynamic_scan").inc()
        # The phased path IS an overlap-transport program: the aligner
        # emits hop-2 tables and the body reuses the packed double-buffered
        # carriers, parks and capacities unchanged.
        overlap = phased_prog is not None or self._overlap_enabled()
        j = jax.lax.axis_index(STAGE_AXIS)
        # This device's shard: [v, ...] — its interleave groups in order.
        params_dev = stage_params
        mode = self.checkpoint

        # --- local shape specs -------------------------------------------
        ctx0 = StageCtx(key=None, train=True)
        x_mb_spec = jax.eval_shape(lambda a: _index_spec(a), x)
        h_spec = jax.eval_shape(
            lambda p, a: self.pre_fn(p, a, ctx0), pre_params, x_mb_spec)
        params_g_spec = jax.eval_shape(lambda p: _index_spec(p), params_dev)

        # Canonical vjp structure (abstract — no tracers leak in):
        i32 = jax.ShapeDtypeStruct((), jnp.int32)
        # mirror the CALLER's key impl (rbg on TPU via utils/rng.make_key,
        # threefry elsewhere): the key rides the stored vjp residuals, and
        # a hardcoded jax.random.key(0) spec (always threefry) would make
        # the abstract residual structure drift from the traced one on any
        # platform whose tuned impl differs
        key_spec = jax.eval_shape(lambda k: k, key)
        lanes = self.skip_lanes
        pops_spec = lanes.specs if lanes is not None else None
        if self.split_stage is not None:
            zs_spec = jax.eval_shape(self.split_stage.zs_fn,
                                     params_g_spec, h_spec)
            _, vjp_fn_spec, taps_spec = jax.eval_shape(
                self._vjp_wrt_split, params_g_spec, pre_params, h_spec,
                x_mb_spec, key_spec, i32)
        else:
            zs_spec = taps_spec = None
            _, vjp_fn_spec = jax.eval_shape(
                self._vjp_wrt, params_g_spec, pre_params, h_spec,
                x_mb_spec, key_spec, i32, pops_spec)
        res_specs, res_treedef = jax.tree_util.tree_flatten(vjp_fn_spec)
        # Structural split: the stored B-vjp's residual leaves include pure
        # PASSTHROUGHS of values the B cycle can already see — the stage
        # weights (vjp consts: dx = gy @ W^T needs W), the pre params, the
        # stashed h_in, x_mb. Streaming those through the slot store costs
        # full leaf-size writes EVERY cycle (the sentinel-write discipline)
        # for values that never change between F and B; on the serialized
        # cpu8 probe the weight copies alone are ~30% of the split's res
        # traffic. Detect them structurally (jaxpr outvar == invar) and
        # rebuild at B from the branch environment instead of storing.
        split_res_pt = None
        if self.split_stage is not None:
            def _res_leaves_of(pg, pre, hh, xx, kk, ss):
                _, vjp_fn, _ = self._vjp_wrt_split(pg, pre, hh, xx, kk, ss)
                return tuple(jax.tree_util.tree_leaves(vjp_fn))

            jpr = jax.make_jaxpr(_res_leaves_of)(
                params_g_spec, pre_params, h_spec, x_mb_spec, key_spec, i32)
            srcs = [("pg", params_g_spec), ("pre", pre_params),
                    ("h", h_spec), ("x", x_mb_spec)]
            src_of, pos = {}, 0
            for kind, tree in srcs:
                leaves_k = jax.tree_util.tree_leaves(tree)
                for k, iv in enumerate(
                        jpr.jaxpr.invars[pos:pos + len(leaves_k)]):
                    src_of[iv] = (kind, k)
                pos += len(leaves_k)
            split_res_pt = {}
            for idx, ov in enumerate(jpr.jaxpr.outvars):
                hit = (None if isinstance(ov, jex_core.Literal)
                       else src_of.get(ov))
                if hit is not None:
                    sp_ = res_specs[idx]
                    lv = jax.tree_util.tree_leaves(dict(srcs)[hit[0]])[
                        hit[1]]
                    assert (tuple(sp_.shape), sp_.dtype) == \
                        (tuple(lv.shape), lv.dtype), \
                        "passthrough residual aval drifted from its source"
                    split_res_pt[idx] = hit
            n_res_leaves_full = len(res_specs)
            res_specs = [sp_ for idx, sp_ in enumerate(res_specs)
                         if idx not in split_res_pt]
        # Policy-selective remat: the policy vjp's residual pytree (what
        # jax.checkpoint's policy saves) differs from the full set, so the
        # recompute micro-batches get their OWN uniform slot store. At
        # 'never' every micro-batch is saved-full and the policy is inert
        # (warned at init); guard on mode so no dead store rides the carry.
        use_policy = (self.remat_policy is not None
                      and self.checkpoint != "never")
        if use_policy:
            _, pvjp_fn_spec = jax.eval_shape(
                self._vjp_wrt_policy, params_g_spec, pre_params, h_spec,
                x_mb_spec, key_spec, i32, pops_spec)
            pres_specs, pres_treedef = jax.tree_util.tree_flatten(
                pvjp_fn_spec)
        else:
            pres_specs, pres_treedef = [], None
        inv_wsum = 1.0 / wsum

        # --- schedule tables (static data → scan xs) ---------------------
        if overlap:
            ((op_np, mb_np, grp_np, rxslot_np, gxslot_np), T, Sg, Gg,
             Wg_ov, sentinel, gsentinel) = (
                 self._host_tables_phased(m) if phased_prog is not None
                 else self._host_tables_overlap(m))
            base_xs = [jnp.asarray(op_np), jnp.asarray(mb_np),
                       jnp.asarray(grp_np), jnp.asarray(rxslot_np),
                       jnp.asarray(gxslot_np)]
        else:
            (op_np, mb_np, grp_np, rxslot_np), T, Sg, sentinel = \
                self._host_tables(m)
            base_xs = [jnp.asarray(op_np), jnp.asarray(mb_np),
                       jnp.asarray(grp_np), jnp.asarray(rxslot_np)]
        if lanes is not None:
            capf_np, capg_np, Kf, Kg = self._skip_tables(
                m, op_np, mb_np, grp_np, overlap=overlap)
            lane_fwd_perms, lane_bwd_perms = self._lane_perms()
            lane_hops = self._lane_hops()
            xs = tuple(base_xs + [jnp.asarray(capf_np),
                                  jnp.asarray(capg_np)])
        else:
            Kf = Kg = ()
            lane_hops = ()
            xs = tuple(base_xs)
        if phased_prog is not None:
            # host-side row columns for the per-phase lowering: unrolled
            # cycles slice single rows, scan segments reshape to
            # (iterations, period, ...) stacks
            cols_np = [op_np, mb_np, grp_np, rxslot_np, gxslot_np]
            if lanes is not None:
                cols_np += [capf_np, capg_np]
        # Split-backward (zero-bubble) tables carry WGRAD ops: B computes
        # the input grad only (and parks its cotangent); W consumes the
        # parked cotangent for the weight grads. Static: shapes the carry
        # and the branch list.
        has_w = bool((op_np == WGRAD).any())
        # Stored-residual mode: the one stored vjp serves both halves (XLA
        # DCE prunes weight-grad matmuls from B and input-grad matmuls from
        # W), so B parks its cotangent for W. Recompute modes: the vjp only
        # exists once the forward re-runs at B, so the FULL backward
        # accumulates there and W is a no-op — recompute-once, no park.
        split_dce = has_w and mode == "never"
        Wg = ((Wg_ov if overlap else self.schedule.wstash_slots(m, d))
              if split_dce else 0)

        # --- carry -------------------------------------------------------
        def zeros_of(spec):
            return jnp.zeros(spec.shape, spec.dtype)

        def slots_of(spec, k):
            # One extra sentinel slot so masked writes need no read-back.
            # EVERY slot store uses this form: the cycle body writes each
            # store exactly once, unconditionally, after the op switch
            # (non-writing ops target the sentinel). Cond-gated writes or
            # stores returned through lax.switch defeat XLA's while-loop
            # buffer aliasing and re-copy the whole store every cycle —
            # one sentinel slot of extra memory buys O(stores) MB/cycle
            # of removed copies.
            return jnp.zeros((k + 1,) + tuple(spec.shape), spec.dtype)

        h_ring = jax.tree_util.tree_map(zeros_of, h_spec)
        g_ring = jax.tree_util.tree_map(zeros_of, h_spec)
        stash = jax.tree_util.tree_map(
            lambda s_: slots_of(s_, v * Sg), h_spec)
        # Last virtual stage's outputs, parked until their backward rebuilds
        # the post vjp (activation-sized — the whole point of keeping the
        # post out of res_store; see _f_body docstring). Sg slots suffice:
        # h1 of micro-batch i goes live at FWD(i, S-1), no earlier than the
        # stash arrival the Sg FIFO proof bounds, and frees at the same
        # BWD(i, S-1).
        h_last = jax.tree_util.tree_map(
            lambda s_: slots_of(s_, Sg), h_spec)
        # Deferred-W park (B -> W window), activation-scale slots: the
        # downstream cotangent seed (legacy stored-vjp split) or the
        # per-op output cotangents g_zs (structural split).
        wpark_spec = zs_spec if self.split_stage is not None else h_spec
        wstash = (jax.tree_util.tree_map(
            lambda s_: slots_of(s_, v * Wg), wpark_spec)
            if split_dce else ())
        # Structural split: per-op input taps, FWD -> W FIFO window.
        taps_store = (jax.tree_util.tree_map(
            lambda s_: slots_of(s_, v * Sg), taps_spec)
            if self.split_stage is not None else ())
        n_res = self.memory_plan(m)["residual_slots"]
        res_store = ([slots_of(s_, n_res) for s_ in res_specs]
                     if mode != "always" else [])
        # Recompute micro-batches' policy-saved residuals: FWD -> BWD FIFO,
        # same window as the stash (slot g*Sg + i % Sg).
        pres_store = ([slots_of(s_, v * Sg) for s_ in pres_specs]
                      if use_policy else [])
        # Skip lanes: one forward + one reverse ring register per lane and
        # a sentinel-slotted FIFO park at each end (capture writes use the
        # host-computed slot tables, so the sentinel form applies).
        if lanes is not None:
            sk_ring = tuple(jax.tree_util.tree_map(zeros_of, sp_)
                            for sp_ in lanes.specs)
            gk_ring = tuple(jax.tree_util.tree_map(zeros_of, sp_)
                            for sp_ in lanes.specs)
            sk_park = tuple(
                jax.tree_util.tree_map(
                    lambda s_, k=k: slots_of(s_, k), sp_)
                for sp_, k in zip(lanes.specs, Kf))
            gk_park = tuple(
                jax.tree_util.tree_map(
                    lambda s_, k=k: slots_of(s_, k), sp_)
                for sp_, k in zip(lanes.specs, Kg))
        else:
            sk_ring = gk_ring = sk_park = gk_park = ()
        if overlap:
            # Packed double-buffered boundary carriers: ONE uint32 vector
            # per direction holds the in-flight boundary pytree — the h
            # ring value plus, per riding skip lane (>= 1 physical hops),
            # an H-slot shift register relaying the lane value one hop per
            # cycle (slot 0 = freshly boarded, slot H-1 = arriving). 0-hop
            # lanes (same device, v > 1) keep their flat register carry —
            # a permute would move them off-device.
            ride = tuple(h >= 1 for h in lane_hops)
            reg_idx = tuple(l for l in range(len(lane_hops))
                            if not ride[l])

            def lane_stack_spec(l):
                if not ride[l]:
                    return ()
                return jax.tree_util.tree_map(
                    lambda sp_: jax.ShapeDtypeStruct(
                        (lane_hops[l],) + tuple(sp_.shape), sp_.dtype),
                    lanes.specs[l])

            lane_stacks_spec = tuple(lane_stack_spec(l)
                                     for l in range(len(lane_hops)))
            pend_spec = (h_spec, lane_stacks_spec)
            pend_words = packed_words(pend_spec)
            pend_f0 = jnp.zeros((pend_words,), jnp.uint32)
            pend_g0 = jnp.zeros((pend_words,), jnp.uint32)
            # Elastic reverse ring: arriving cotangents park here until
            # their BWD (serialized transport consumes them on arrival —
            # its reverse ring is rigid and needs no park).
            gpark = jax.tree_util.tree_map(
                lambda s_: slots_of(s_, v * Gg), h_spec)
            sk_reg = tuple(jax.tree_util.tree_map(zeros_of, lanes.specs[l])
                           for l in reg_idx)
            gk_reg = tuple(jax.tree_util.tree_map(zeros_of, lanes.specs[l])
                           for l in reg_idx)
            reg_pos = {l: k for k, l in enumerate(reg_idx)}
            get_registry().gauge(
                "scheduled.transport.packed_words_per_direction").set(
                pend_words)
        g_sp = jax.tree_util.tree_map(jnp.zeros_like, params_dev)
        g_pre = jax.tree_util.tree_map(jnp.zeros_like, pre_params)
        g_post = jax.tree_util.tree_map(jnp.zeros_like, post_params)
        loss0 = jnp.zeros((), jnp.float32)

        if v == 1:
            fwd_perm = [(k, k + 1) for k in range(d - 1)]
            bwd_perm = [(k + 1, k) for k in range(d - 1)]
        else:
            fwd_perm = [(q, (q + 1) % d) for q in range(d)]
            bwd_perm = [(q, (q - 1) % d) for q in range(d)]

        def res_slot_for(i, g):
            """Where (micro-batch i, group g)'s residuals live. Non-saving
            forwards route their (zero) values to the sentinel slot, so
            this is only consulted for saved micro-batches."""
            if mode == "never":
                return g * Sg + i % Sg
            return g  # except_last: slot g holds micro-batch m-1

        # Zero write-values for ops that do not store into a given slot
        # store this cycle. The post-switch writer is unconditional — one
        # masked write per store per cycle, sentinel slot when inactive —
        # so every branch hands back a full (values, slot) set. Streaming
        # one zero value-set into a sentinel slot is the price of XLA
        # aliasing every store in place across the scan; cond-gated writes
        # and stores returned through lax.switch measurably re-copy the
        # whole store every cycle instead.
        res_zero = ([zeros_of(s_) for s_ in res_specs]
                    if mode != "always" else [])
        pres_zero = [zeros_of(s_) for s_ in pres_specs]
        taps_zero = (jax.tree_util.tree_map(zeros_of, taps_spec)
                     if self.split_stage is not None else ())
        w_zero = (jax.tree_util.tree_map(zeros_of, wpark_spec)
                  if split_dce else ())

        def cycle(carry, row, concrete=None, masked=False):
            """One table cycle. ``concrete=None``: interpreted — the op
            code is read from the row and dispatched via ``lax.switch``.
            ``concrete=<op code>`` (phase-compiled lowering): the branch is
            picked at TRACE time — no dispatch in the lowered body. Dense
            cycles (``masked=False``) run it as-is; ramp cycles with idle
            devices (``masked=True``) run the branch on garbage for the
            idle devices and mask the damage by data selects — store slots
            route to the sentinel, accumulators keep their prior value,
            lane registers keep their pass-through semantics. Transmitted
            garbage needs no mask: every park is driven by the host slot
            tables, which sentinel all unscheduled arrivals, and the
            double-buffered carriers never hold a value past its park."""
            if overlap:
                (pend_f, pend_g, stash, gpark, h_last, wstash, taps_store,
                 res_store, pres_store, sk_reg, gk_reg, sk_park, gk_park,
                 stats_acc, g_sp, g_pre, g_post, loss) = carry
            else:
                (h_ring, g_ring, stash, h_last, wstash, taps_store,
                 res_store, pres_store, sk_ring, gk_ring, sk_park, gk_park,
                 stats_acc, g_sp, g_pre, g_post, loss) = carry
            cols = list(row)
            op_r, mb_r, grp_r, rx_r = cols[:4]
            if overlap:
                gx_r = cols[4]
            if lanes is not None:
                capf_r, capg_r = cols[-2], cols[-1]
            opj = jax.lax.dynamic_index_in_dim(op_r, j, 0, keepdims=False)
            i = jax.lax.dynamic_index_in_dim(mb_r, j, 0, keepdims=False)
            g = jax.lax.dynamic_index_in_dim(grp_r, j, 0, keepdims=False)
            rslot = jax.lax.dynamic_index_in_dim(rx_r, j, 0, keepdims=False)
            s = g * d + j                 # this cycle's virtual stage
            if lanes is not None:
                fslots = [jax.lax.dynamic_index_in_dim(
                    capf_r[l], j, 0, keepdims=False)
                    for l in range(len(lanes.pairs))]
                gslots = [jax.lax.dynamic_index_in_dim(
                    capg_r[l], j, 0, keepdims=False)
                    for l in range(len(lanes.pairs))]

            if overlap:
                # Software pipeline: launch the collectives moving the
                # PREVIOUS cycle's packed sends NOW — nothing below this
                # cycle's switch reads them (the shifted tables prove every
                # consumer is >= 1 body behind the park), so the permutes
                # run alongside the compute instead of gating it.
                gslot = jax.lax.dynamic_index_in_dim(gx_r, j, 0,
                                                     keepdims=False)
                rx_f = jax.lax.ppermute(pend_f, STAGE_AXIS, fwd_perm)
                rx_g = jax.lax.ppermute(pend_g, STAGE_AXIS, bwd_perm)
                rx_h, rx_sks = unpack_words(rx_f, pend_spec)
                rx_gh, rx_gks = unpack_words(rx_g, pend_spec)
                # the names the shared branch code consumes: h_ring is
                # only a garbage filler for non-FWD tx_h; g_ring is the
                # parked cotangent seed for this (i, s)'s BWD; lane rings
                # are the arriving slot (riding lanes) or the register
                h_ring = rx_h
                g_ring = jax.tree_util.tree_map(
                    lambda st: jax.lax.dynamic_index_in_dim(
                        st, g * Gg + i % Gg, 0, keepdims=False), gpark)
                sk_ring = tuple(
                    (sk_reg[reg_pos[l]] if not ride[l]
                     else jax.tree_util.tree_map(lambda a: a[-1],
                                                 rx_sks[l]))
                    for l in range(len(lane_hops)))
                gk_ring = tuple(
                    (gk_reg[reg_pos[l]] if not ride[l]
                     else jax.tree_util.tree_map(lambda a: a[-1],
                                                 rx_gks[l]))
                    for l in range(len(lane_hops)))
            else:
                # 1) park the arriving activation (sentinel slot when not
                # real)
                stash = jax.tree_util.tree_map(
                    lambda st, hr: jax.lax.dynamic_update_index_in_dim(
                        st, hr, rslot, 0), stash, h_ring)
                # 1b) park arriving skip values / pop cotangents (host
                # tables mark the exact arrival cycles; sentinel slot
                # otherwise)
                if lanes is not None:
                    sk_park = tuple(
                        jax.tree_util.tree_map(
                            lambda st, reg, sl=sl:
                            jax.lax.dynamic_update_index_in_dim(
                                st, reg, sl, 0),
                            pk, rg)
                        for pk, rg, sl in zip(sk_park, sk_ring, fslots))
                    gk_park = tuple(
                        jax.tree_util.tree_map(
                            lambda st, reg, sl=sl:
                            jax.lax.dynamic_update_index_in_dim(
                                st, reg, sl, 0),
                            pk, rg)
                        for pk, rg, sl in zip(gk_park, gk_ring, gslots))

            kis = jax.random.fold_in(jax.random.fold_in(key, i), s)
            x_mb = _index(x, i)
            w_mb = _index(w, i)
            # v=1: the single group is hoisted statically (no per-cycle
            # gather); v>1: one gather per cycle selects the active group.
            params_g = (_index(params_dev, 0) if v == 1
                        else _index(params_dev, g))
            h_in = jax.tree_util.tree_map(
                lambda st: jax.lax.dynamic_index_in_dim(
                    st, g * Sg + i % Sg, 0, keepdims=False), stash)
            # Popped skip values for this (i, s): FIFO slot i % Kf per lane.
            # Every stage reads them (uniform code); only the lane's dst
            # stage body uses them. Recompute modes re-read at BWD, exactly
            # like h_in.
            pops = (tuple(
                jax.tree_util.tree_map(
                    lambda st, k=k: jax.lax.dynamic_index_in_dim(
                        st, i % k, 0, keepdims=False), pk)
                for pk, k in zip(sk_park, Kf))
                if lanes is not None else None)

            # Sentinel-routed (values, slot) pairs for branches that skip
            # a given store this cycle (full_like keeps the slot dtype
            # uniform across branches so lax.switch avals agree).
            no_res = (res_zero, jnp.full_like(i, n_res))
            no_pres = (pres_zero, jnp.full_like(i, v * Sg))
            no_taps = (taps_zero, jnp.full_like(i, v * Sg))
            no_w = (w_zero, jnp.full_like(i, v * Wg))
            hl_none = jnp.full_like(i, Sg)

            def apply_vjp(seed):
                """Cotangents from the stored or recomputed vjp per the
                checkpoint policy — shared by the B and W branches so slot
                layout and policy gating cannot drift between them. ``seed``
                is ``g_h`` (or ``(g_h, g_stashes)`` with skip lanes); the
                result gains ``g_pops`` with lanes."""
                def apply_stored():
                    return _load_vjp(res_store, res_treedef,
                                     res_slot_for(i, g))(seed)

                def apply_recomputed():
                    with device_scope(REMAT_SCOPE):
                        _, vjp_fn = self._vjp_wrt(
                            params_g, pre_params, h_in, x_mb, kis, s, pops)
                    return vjp_fn(seed)

                def apply_policy_stored():
                    return _load_vjp(pres_store, pres_treedef,
                                     g * Sg + i % Sg)(seed)

                if mode == "never":
                    return apply_stored()
                recompute = (apply_policy_stored if use_policy
                             else apply_recomputed)
                if mode == "always":
                    return recompute()
                # except_last: stored for m-1, recomputed otherwise
                return jax.lax.cond(i == m - 1, apply_stored, recompute)

            def scatter_gp(G, gp):
                """Accumulate group g's param grads into its row of G."""
                if v == 1:
                    return jax.tree_util.tree_map(
                        lambda G_, gg: G_ + gg[None], G, gp)
                return jax.tree_util.tree_map(
                    lambda G_, gg: jax.lax.dynamic_update_index_in_dim(
                        G_, jax.lax.dynamic_index_in_dim(
                            G_, g, 0, keepdims=False) + gg, g, 0),
                    G, gp)

            def fwd_branch():
                def vjp_and_store():
                    out, vjp_fn = self._vjp_wrt(
                        params_g, pre_params, h_in, x_mb, kis, s, pops)
                    return (out, (_vjp_leaves(vjp_fn, res_specs),
                                  res_slot_for(i, g)), no_pres, no_taps)

                def split_vjp_and_store():
                    # structural split: params-constant vjp + taps values;
                    # passthrough residual leaves (weights, pre params,
                    # h_in, x_mb) are dropped here and rebuilt at B from
                    # the branch environment — see split_res_pt above
                    out, vjp_fn, taps = self._vjp_wrt_split(
                        params_g, pre_params, h_in, x_mb, kis, s)
                    leaves = jax.tree_util.tree_leaves(vjp_fn)
                    stored = [l for idx, l in enumerate(leaves)
                              if idx not in split_res_pt]
                    assert [(l.shape, l.dtype) for l in stored] == \
                        [(sp_.shape, sp_.dtype) for sp_ in res_specs], \
                        "split vjp residual structure drifted from spec"
                    return (out, (stored, res_slot_for(i, g)), no_pres,
                            (taps, g * Sg + i % Sg))

                def policy_vjp_and_store():
                    # selective remat: forward hands back the policy-saved
                    # residual subset (its own uniform slot structure);
                    # backward recomputes only the cheap remainder
                    out, vjp_fn = self._vjp_wrt_policy(
                        params_g, pre_params, h_in, x_mb, kis, s, pops)
                    return (out, no_res,
                            (_vjp_leaves(vjp_fn, pres_specs),
                             g * Sg + i % Sg), no_taps)

                def body_only():
                    return (self._f_body(params_g, pre_params, h_in, x_mb,
                                         kis, s, pops), no_res, no_pres,
                            no_taps)

                recompute_fwd = (policy_vjp_and_store if use_policy
                                 else body_only)
                if self.split_stage is not None:   # never mode guaranteed
                    out, res_w, pres_w, taps_w = split_vjp_and_store()
                elif mode == "always":
                    out, res_w, pres_w, taps_w = recompute_fwd()
                elif mode == "never":
                    out, res_w, pres_w, taps_w = vjp_and_store()
                else:
                    # except_last: ONLY micro-batch m-1 pays the residual
                    # capture; the rest run the plain body (they recompute
                    # at BWD) or, under remat_policy, hand back just the
                    # policy-saved subset — their full-residual values are
                    # zeros bound for the sentinel slot.
                    out, res_w, pres_w, taps_w = jax.lax.cond(
                        i == m - 1, vjp_and_store, recompute_fwd)
                h1, stashes, stats_t = self._split_out(out)
                if lanes is not None:
                    # inject this stage's fresh stashes into their lanes;
                    # pass the arriving value onward everywhere else
                    tx_sk = tuple(
                        jax.tree_util.tree_map(
                            lambda sv, reg, src=src: jnp.where(
                                jnp.asarray(s == src), sv, reg), svv, rg)
                        for (src, _), svv, rg in zip(lanes.pairs, stashes,
                                                     sk_ring))
                else:
                    tx_sk = sk_ring
                # FWD ops run only on real (i, s) — no fill/drain garbage
                # to mask, and BWD recomputes discard their stats, so this
                # is the one accumulation point
                new_stats = (jax.tree_util.tree_map(jnp.add, stats_acc,
                                                    stats_t)
                             if self.stat_spec is not None else stats_acc)
                is_last = s == S - 1
                # loss contribution: forward value only (its vjp is rebuilt
                # at BWD time from the parked h1 — never stored)
                contrib = jax.lax.cond(
                    is_last,
                    lambda: self._post_contrib(post_params, h1, x_mb, w_mb,
                                               kis),
                    lambda: jnp.zeros((), jnp.float32))
                # h1 doubles as the h_last write value (tx_h); non-last
                # stages stream it into the sentinel slot
                hl_slot = jnp.where(is_last, i % Sg, Sg)
                return (hl_slot, no_w, taps_w, res_w, pres_w,
                        new_stats, g_sp, g_pre, g_post, loss + contrib, h1,
                        g_ring, tx_sk, gk_ring)

            def bwd_branch():
                is_last = s == S - 1

                # Last stage: rebuild the post vjp FRESH from the parked h1
                # (no vocab-scale residuals live in the carry; the compiled
                # analogue of the reference's loss living outside Pipe and
                # its gradient seeding the recorded graph, main.py:216-218).
                # Cotangent of the contribution: d(masked mean) = 1/sum(w).
                def post_seed():
                    h1 = jax.tree_util.tree_map(
                        lambda st: jax.lax.dynamic_index_in_dim(
                            st, i % Sg, 0, keepdims=False), h_last)
                    _, post_vjp = jax.vjp(
                        lambda pp, hh: self._post_contrib(pp, hh, x_mb, w_mb,
                                                          kis),
                        post_params, h1)
                    gpost_, gh1 = post_vjp(inv_wsum)
                    # int (non-differentiable) carrier lanes — e.g. token
                    # ids in the packed boundary — yield float0 cotangents;
                    # the ring carries concrete placeholder zeros for them
                    return gpost_, _vjp_to_ring(gh1, h_spec)

                def ring_seed():
                    return (jax.tree_util.tree_map(jnp.zeros_like,
                                                   post_params), g_ring)

                gpost, seed_h = jax.lax.cond(is_last, post_seed, ring_seed)
                add = functools.partial(jax.tree_util.tree_map, jnp.add)

                if lanes is not None:
                    # stash-output seeds: the pop cotangent that rode the
                    # reverse ring from BWD(i, dst), parked at this source
                    # device; zeros for lanes this stage does not stash
                    # (their stash outputs are constants anyway)
                    seed_sk = tuple(
                        jax.tree_util.tree_map(
                            lambda st, k=k, src=src: jnp.where(
                                jnp.asarray(s == src),
                                jax.lax.dynamic_index_in_dim(
                                    st, i % k, 0, keepdims=False),
                                jnp.zeros(st.shape[1:], st.dtype)),
                            pk)
                        for pk, k, (src, _) in zip(gk_park, Kg,
                                                   lanes.pairs))
                else:
                    seed_sk = None
                seed_f0 = _ring_to_seed(seed_h, h_spec)
                seed = self._make_seed(seed_f0, seed_sk)

                if self.split_stage is not None:
                    # structural split: the stored params-constant vjp IS
                    # the input-grad chain (zero weight-grad contractions
                    # in it by construction); per-op output cotangents
                    # park for W, pre grads accumulate here (edge-stage
                    # embed path only).
                    slot = res_slot_for(i, g)
                    stored = iter(
                        jax.lax.dynamic_index_in_dim(st, slot, 0,
                                                     keepdims=False)
                        for st in res_store)
                    env = {"pg": jax.tree_util.tree_leaves(params_g),
                           "pre": jax.tree_util.tree_leaves(pre_params),
                           "h": jax.tree_util.tree_leaves(h_in),
                           "x": jax.tree_util.tree_leaves(x_mb)}
                    leaves = [
                        (next(stored) if idx not in split_res_pt
                         else env[split_res_pt[idx][0]]
                         [split_res_pt[idx][1]])
                        for idx in range(n_res_leaves_full)]
                    vjp_fn = jax.tree_util.tree_unflatten(res_treedef,
                                                          leaves)
                    gpre, gh, gzs = vjp_fn(seed_f0)
                    gh = _vjp_to_ring(gh, h_spec)
                    return (hl_none, (gzs, g * Wg + i % Wg), no_taps,
                            no_res, no_pres, stats_acc, g_sp,
                            add(g_pre, gpre), add(g_post, gpost), loss,
                            h_ring, gh, sk_ring, gk_ring)

                if lanes is not None:
                    gp, gpre, gh, g_pops = apply_vjp(seed)
                    # pop cotangents board the reverse ring at their dst
                    # stage; everyone else forwards the arriving value
                    tx_gk = tuple(
                        jax.tree_util.tree_map(
                            lambda gv, reg, dst=dst: jnp.where(
                                jnp.asarray(s == dst), gv, reg), gvv, rg)
                        for (_, dst), gvv, rg in zip(lanes.pairs, g_pops,
                                                     gk_ring))
                else:
                    gp, gpre, gh = apply_vjp(seed)
                    tx_gk = gk_ring
                gh = _vjp_to_ring(gh, h_spec)
                if split_dce:
                    # split backward, stored residuals: B emits only the
                    # input grad (XLA DCE prunes the unused weight-grad
                    # matmuls from the stored-residual call); the cotangent
                    # parks for the W op.
                    return (hl_none, (seed_h, g * Wg + i % Wg), no_taps,
                            no_res, no_pres, stats_acc, g_sp, g_pre,
                            add(g_post, gpost), loss, h_ring, gh,
                            sk_ring, tx_gk)
                # combined backward (non-split tables), or a split table
                # under a recompute mode — the vjp was just built from the
                # single forward recompute, so weight grads accumulate here
                # and the table's W slot (if any) is a no-op.
                return (hl_none, no_w, no_taps, no_res, no_pres,
                        stats_acc, scatter_gp(g_sp, gp), add(g_pre, gpre),
                        add(g_post, gpost), loss, h_ring, gh,
                        sk_ring, tx_gk)

            def wgrad_branch():
                add = functools.partial(jax.tree_util.tree_map, jnp.add)
                if self.split_stage is not None:
                    # structural split: NOTHING here but the weight-grad
                    # contractions from (taps, per-op cotangents).
                    taps = jax.tree_util.tree_map(
                        lambda st: jax.lax.dynamic_index_in_dim(
                            st, g * Sg + i % Sg, 0, keepdims=False),
                        taps_store)
                    gzs = jax.tree_util.tree_map(
                        lambda st: jax.lax.dynamic_index_in_dim(
                            st, g * Wg + i % Wg, 0, keepdims=False), wstash)
                    gp = self.split_stage.wgrad_fn(taps, gzs)
                    return (hl_none, no_w, no_taps, no_res, no_pres,
                            stats_acc, scatter_gp(g_sp, gp),
                            g_pre, g_post, loss, h_ring, g_ring,
                            sk_ring, gk_ring)
                if not split_dce:
                    # recompute modes: full backward already ran at B.
                    return idle_branch()
                seed_h = jax.tree_util.tree_map(
                    lambda st: jax.lax.dynamic_index_in_dim(
                        st, g * Wg + i % Wg, 0, keepdims=False), wstash)
                gp, gpre, _ = apply_vjp(_ring_to_seed(seed_h, h_spec))
                return (hl_none, no_w, no_taps, no_res, no_pres,
                        stats_acc, scatter_gp(g_sp, gp), add(g_pre, gpre),
                        g_post, loss, h_ring, g_ring, sk_ring, gk_ring)

            def idle_branch():
                return (hl_none, no_w, no_taps, no_res, no_pres,
                        stats_acc, g_sp, g_pre, g_post, loss, h_ring,
                        g_ring, sk_ring, gk_ring)

            branches = [idle_branch, fwd_branch, bwd_branch]
            if has_w:
                branches.append(wgrad_branch)
            if concrete is None:
                branch_out = jax.lax.switch(opj, branches)
            else:
                branch_out = branches[concrete]()
            (hl_slot, (w_v, w_s), (taps_v, taps_s), (res_v, res_s),
             (pres_v, pres_s), stats2, g_sp2, g_pre2, g_post2, loss2,
             tx_h, tx_g, tx_sk, tx_gk) = branch_out
            if concrete is not None and masked and concrete != IDLE:
                # Partially idle ramp cycle: idle devices just ran the
                # cycle's branch on garbage inputs. Garbage VALUES are
                # inert (sentinel-driven parks, see cycle docstring);
                # garbage SLOTS and accumulator updates are not — route
                # the former to the sentinels and keep the latter.
                active = opj == concrete

                def keep(new, old):
                    return jax.tree_util.tree_map(
                        lambda a_, b_: jnp.where(active, a_, b_), new, old)

                hl_slot = jnp.where(active, hl_slot, Sg)
                w_s = jnp.where(active, w_s, v * Wg)
                taps_s = jnp.where(active, taps_s, v * Sg)
                res_s = jnp.where(active, res_s, n_res)
                pres_s = jnp.where(active, pres_s, v * Sg)
                stats2 = keep(stats2, stats_acc)
                g_sp2 = keep(g_sp2, g_sp)
                g_pre2 = keep(g_pre2, g_pre)
                g_post2 = keep(g_post2, g_post)
                loss2 = jnp.where(active, loss2, loss)
                # idle semantics for lane registers is pass-through: a
                # garbage overwrite here would clobber a live 0-hop
                # register between its stash and pop stages
                tx_sk = tuple(keep(t_, r_)
                              for t_, r_ in zip(tx_sk, sk_ring))
                tx_gk = tuple(keep(t_, r_)
                              for t_, r_ in zip(tx_gk, gk_ring))

            # THE slot-store writers: branches return (values, slot), and
            # each store takes exactly one unconditional masked write per
            # cycle here — never a whole updated store through the switch
            # — so XLA aliases every store in place across the scan
            # instead of re-copying it each cycle. tx_h doubles as the
            # h_last write value (h1 on FWD cycles; sentinel otherwise).
            h_last2 = jax.tree_util.tree_map(
                lambda st, l: jax.lax.dynamic_update_index_in_dim(
                    st, l, hl_slot, 0), h_last, tx_h)
            wstash2 = (jax.tree_util.tree_map(
                lambda st, l: jax.lax.dynamic_update_index_in_dim(
                    st, l, w_s, 0), wstash, w_v) if split_dce else ())
            taps2 = (jax.tree_util.tree_map(
                lambda st, l: jax.lax.dynamic_update_index_in_dim(
                    st, l, taps_s, 0), taps_store, taps_v)
                if self.split_stage is not None else ())
            res_store2 = [
                jax.lax.dynamic_update_index_in_dim(st, l, res_s, 0)
                for st, l in zip(res_store, res_v)]
            pres_store2 = [
                jax.lax.dynamic_update_index_in_dim(st, l, pres_s, 0)
                for st, l in zip(pres_store, pres_v)]

            if overlap:
                # Park this cycle's ARRIVALS only now — the compute above
                # read the pre-park carry, so the unpacked receives never
                # gate the switch (first legal read is the next body).
                stash2 = jax.tree_util.tree_map(
                    lambda st, hr: jax.lax.dynamic_update_index_in_dim(
                        st, hr, rslot, 0), stash, rx_h)
                gpark2 = jax.tree_util.tree_map(
                    lambda st, gr: jax.lax.dynamic_update_index_in_dim(
                        st, gr, gslot, 0), gpark, rx_gh)
                if lanes is not None:
                    # lane captures: riding lanes park their expiring
                    # shift-register slot, register lanes the register —
                    # both are what sk_ring/gk_ring already name
                    sk_park2 = tuple(
                        jax.tree_util.tree_map(
                            lambda st, reg, sl=sl:
                            jax.lax.dynamic_update_index_in_dim(
                                st, reg, sl, 0),
                            pk, rg)
                        for pk, rg, sl in zip(sk_park, sk_ring, fslots))
                    gk_park2 = tuple(
                        jax.tree_util.tree_map(
                            lambda st, reg, sl=sl:
                            jax.lax.dynamic_update_index_in_dim(
                                st, reg, sl, 0),
                            pk, rg)
                        for pk, rg, sl in zip(gk_park, gk_ring, gslots))
                    # relay: the freshly boarded value enters slot 0,
                    # everything in flight advances one hop
                    tx_stacks = tuple(
                        (() if not ride[l] else jax.tree_util.tree_map(
                            lambda bv, stk: jnp.concatenate(
                                [bv[None], stk[:-1]], axis=0),
                            tx_sk[l], rx_sks[l]))
                        for l in range(len(lane_hops)))
                    tg_stacks = tuple(
                        (() if not ride[l] else jax.tree_util.tree_map(
                            lambda bv, stk: jnp.concatenate(
                                [bv[None], stk[:-1]], axis=0),
                            tx_gk[l], rx_gks[l]))
                        for l in range(len(lane_hops)))
                    sk_reg2 = tuple(tx_sk[l] for l in reg_idx)
                    gk_reg2 = tuple(tx_gk[l] for l in reg_idx)
                else:
                    sk_park2, gk_park2 = sk_park, gk_park
                    tx_stacks = tg_stacks = ()
                    sk_reg2 = gk_reg2 = ()
                pend_f2 = pack_words((tx_h, tx_stacks))
                pend_g2 = pack_words((tx_g, tg_stacks))
                return (pend_f2, pend_g2, stash2, gpark2, h_last2, wstash2,
                        taps2, res_store2, pres_store2, sk_reg2, gk_reg2,
                        sk_park2, gk_park2, stats2, g_sp2, g_pre2, g_post2,
                        loss2), None

            if d > 1:
                tx_h = jax.tree_util.tree_map(
                    lambda a: jax.lax.ppermute(a, STAGE_AXIS, fwd_perm), tx_h)
                tx_g = jax.tree_util.tree_map(
                    lambda a: jax.lax.ppermute(a, STAGE_AXIS, bwd_perm), tx_g)
                if lanes is not None:
                    # each lane takes its OWN direct hop (src%d -> dst%d);
                    # same-device lanes keep the register as transport
                    tx_sk = tuple(
                        (jax.tree_util.tree_map(
                            lambda a, pf=pf: jax.lax.ppermute(
                                a, STAGE_AXIS, pf), lv)
                         if pf is not None else lv)
                        for lv, pf in zip(tx_sk, lane_fwd_perms))
                    tx_gk = tuple(
                        (jax.tree_util.tree_map(
                            lambda a, pb=pb: jax.lax.ppermute(
                                a, STAGE_AXIS, pb), lv)
                         if pb is not None else lv)
                        for lv, pb in zip(tx_gk, lane_bwd_perms))
            return (tx_h, tx_g, stash, h_last2, wstash2, taps2, res_store2,
                    pres_store2, tx_sk, tx_gk, sk_park, gk_park, stats2,
                    g_sp2, g_pre2, g_post2, loss2), None

        stats0 = (self._zero_seed_like(self.stat_spec)
                  if self.stat_spec is not None else ())
        if overlap:
            carry0 = (pend_f0, pend_g0, stash, gpark, h_last, wstash,
                      taps_store, res_store, pres_store, sk_reg, gk_reg,
                      sk_park, gk_park, stats0, g_sp, g_pre, g_post, loss0)
        else:
            carry0 = (h_ring, g_ring, stash, h_last, wstash, taps_store,
                      res_store, pres_store, sk_ring, gk_ring, sk_park,
                      gk_park, stats0, g_sp, g_pre, g_post, loss0)
        if phased_prog is not None:
            # Per-phase lowering: ramps unroll to straight-line cycles
            # (concrete op code each, idle devices masked), the dense
            # periodic steady state becomes a fixed-body scan — the body
            # is the period's concrete branch sequence, one sub-cycle per
            # period offset, fed by (iterations, period, ...) row stacks.
            # No lax.switch anywhere; no masked no-ops inside the scan.
            codes = phased_prog.cycle_codes
            dense = phased_prog.dense
            carry = carry0
            for seg in phased_prog.segments:
                if seg.kind == "unroll":
                    for t in range(seg.t0, seg.t1):
                        row = tuple(jnp.asarray(c[t]) for c in cols_np)
                        carry, _ = cycle(carry, row, concrete=codes[t],
                                         masked=not dense[t])
                    continue
                seg_xs = tuple(
                    jnp.asarray(c[seg.t0:seg.t1].reshape(
                        (seg.iters, seg.period) + c.shape[1:]))
                    for c in cols_np)

                def seg_body(carry, rows, _codes=seg.codes):
                    for k, code_k in enumerate(_codes):
                        sub = tuple(r_[k] for r_ in rows)
                        carry, _ = cycle(carry, sub, concrete=code_k)
                    return carry, None

                carry, _ = jax.lax.scan(seg_body, carry, seg_xs)
            final_carry = carry
        else:
            final_carry, _ = jax.lax.scan(cycle, carry0, xs)
        stats_out, g_sp, g_pre, g_post, loss = final_carry[-5:]

        # --- cross-device reductions ------------------------------------
        # stage grads: per-device shards stay put; replicas over other axes
        # sum (never the model axis — TP grad contract)
        other_axes = self._grad_reduce_axes()
        if other_axes:
            g_sp = jax.tree_util.tree_map(
                lambda gg: jax.lax.psum(gg, other_axes), g_sp)
        # pre/post grads + loss: only edge stages contributed; psum collects
        reduce_axes = (STAGE_AXIS,) + other_axes
        g_pre = jax.tree_util.tree_map(
            lambda gg: jax.lax.psum(gg, reduce_axes), g_pre)
        g_post = jax.tree_util.tree_map(
            lambda gg: jax.lax.psum(gg, reduce_axes), g_post)
        loss_axes = ((STAGE_AXIS, DATA_AXIS) if self.has_data_axis
                     else (STAGE_AXIS,))
        loss = jax.lax.psum(loss, loss_axes) * inv_wsum

        if self.stat_spec is not None:
            # each stage fills only its own slots (zeros elsewhere) and
            # data shards hold per-shard partial sums — NOT the model
            # axis, over which activations (hence stats) are replicated
            stats_out = jax.tree_util.tree_map(
                lambda a: jax.lax.psum(a, loss_axes), stats_out)
            return loss, (g_sp, g_pre, g_post), stats_out
        return loss, (g_sp, g_pre, g_post)


def _index_spec(tree):
    return jax.tree_util.tree_map(lambda l: l[0], tree)
