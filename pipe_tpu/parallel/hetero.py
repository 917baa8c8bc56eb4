"""Compiled SPMD executor for HETEROGENEOUS stage pipelines — Pipe's mesh path.

The reference's flagship API drives arbitrary ``nn.Sequential`` partitions on
the multi-device pipeline (``Pipe.__init__`` builds the multi-device
``Pipeline``, ``pipe.py:344-356``; ``forward`` runs it, ``pipe.py:431-494``) —
stages differ in parameter structure and in activation signature. The
homogeneous executor (:mod:`.spmd`) cannot express that: its ring invariant
needs one activation shape and one stacked parameter structure.

This executor keeps the single-program SPMD design and handles heterogeneity
with three devices-visible mechanisms, all static at trace time:

* **``lax.switch`` stage bodies**: device ``j`` selects branch ``j`` by
  ``axis_index``; each branch closes over its partition's layer composition
  statically. All branches are uniformly remat-wrapped (mixed remat/plain
  branches trip the jax 0.9.0 cond+remat+PRNG bug — uniform branches
  differentiate fine, verified in tests).
* **Packed ring carrier**: between stages, the (possibly multi-value,
  shape-varying) boundary pytree is flattened per dtype into fixed-capacity
  1-D buffers sized to the largest boundary — one static ``ppermute`` shape
  for the whole pipeline. Branch ``s`` unpacks boundary ``s`` and packs
  boundary ``s+1`` with statically-known layouts.
* **Skip lanes**: every cross-stage ``@skippable`` stash rides the same ring
  as an extra lane, written by its source branch and consumed by its
  destination branch ``dst - src`` hops later — the arrival cycle is exactly
  the destination's compute cycle for that micro-batch, so a single array per
  skip suffices (no slot buffers). This is the compiled lowering of the
  reference's portal machinery (``skip/portal.py`` via ``pipeline.py:136-138``)
  that round 1 left emulator-only.

Parameters come in two layouts:

* **Stage-sharded (the memory-scaling layout)**: :meth:`shard_params` packs
  each stage's param tree into per-dtype rows of a ``[n, cap]`` array
  sharded ``P('stage')`` (:class:`~pipe_tpu.core.packing.StageParamPack`) —
  each device holds ONLY its partition's weights plus per-dtype padding to
  the largest stage, matching the reference's partition-per-device placement
  (``_split_module``, reference ``pipe.py:191-218,344-356``). Branch ``j``
  unpacks its own row (static slice+reshape, aliased by XLA); grads come
  back in the same sharded layout with no stage-axis communication.
* **Replicated per-stage pytrees** (legacy/simple): every stage's tree on
  every device (``P()``); only branch ``j`` touches stage ``j``'s params,
  and the psum inserted by AD-of-``shard_map`` recovers exact gradients.
  Convenient at toy scale; OOMs at exactly the model scale where pipeline
  parallelism is the point — use :meth:`shard_params`.

Remat on this path is static per mode (``except_last``
remats all micro-batches like :mod:`.spmd`; the exact policy lives in
:mod:`.scheduled`).
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..core import microbatch as mb
from ..core.packing import PackPlan as _PackPlan, StageParamPack
from ..core.partition import StageCtx
from ..core.remat import apply_remat, checkpoint_stop, validate_mode
from .mesh import DATA_AXIS, STAGE_AXIS
from ..obs.events import stage_scope
from ..utils.rng import make_key

__all__ = ["HeteroSpmdPipeline"]


def _zeros_of(spec_tree):
    """Zero arrays from a tree of ShapeDtypeStructs."""
    return jax.tree_util.tree_map(
        lambda sp_: jnp.zeros(sp_.shape, sp_.dtype), spec_tree)


def _apply_train(part, p, *xs):
    """Train-mode apply for the stat-lane spec pass (key None ⇒ dropout
    no-op; only BN's accumulate channel distinguishes it from out_spec)."""
    return part.apply(p, *xs, ctx=StageCtx(train=True))


class HeteroSpmdPipeline:
    """Executor over a ``(stage[, data])`` mesh for Pipe's partitions."""

    def __init__(self, mesh: Mesh, partitions, skip_layout, chunks: int,
                 checkpoint: str = "except_last"):
        validate_mode(checkpoint)
        if STAGE_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh must have a {STAGE_AXIS!r} axis")
        self.mesh = mesh
        self.n_stages = mesh.shape[STAGE_AXIS]
        if len(partitions) != self.n_stages:
            raise ValueError(
                f"{len(partitions)} partitions for a {self.n_stages}-stage "
                f"mesh axis")
        self.partitions = list(partitions)
        self.layout = skip_layout
        self.chunks = chunks
        self.checkpoint = checkpoint
        self.has_data = DATA_AXIS in mesh.axis_names
        self.n_data = mesh.shape[DATA_AXIS] if self.has_data else 1
        # stable lane order for cross-stage skips
        self.lane_keys: List[Tuple[Any, str, int, int]] = []
        for (src, dst), names in skip_layout.by_src_dst:
            if src != dst:
                for ns, name in names:
                    self.lane_keys.append((ns, name, src, dst))
        # Established by shard_params(); None until then (replicated layout).
        self.param_pack: Optional[StageParamPack] = None
        # Deferred-BN: stat-bearing layers accumulate (sum, sum_sq, count)
        # per micro-batch; the executor threads those accumulators through
        # the scan as explicit lanes (reference batchnorm.py capability,
        # README.md:549-554).
        from ..extras.norm import BatchNorm, DeferredBatchNorm
        self.has_bn = any(isinstance(l, DeferredBatchNorm)
                          for part in self.partitions for l in part)
        # Any batch-statistics layer (plain OR deferred BN) makes padded
        # rows unacceptable in train mode: fake zero rows would enter the
        # normalization statistics.
        self.has_batch_stats = any(isinstance(l, BatchNorm)
                                   for part in self.partitions for l in part)

    # -----------------------------------------------------------------
    def shard_params(self, params_per_stage: Sequence[Any]):
        """Convert per-stage trees to the stage-sharded packed layout
        (``{dtype: [n, cap]}``, row j on stage j's devices) and remember the
        pack plans so subsequent calls accept the packed form."""
        if len(params_per_stage) != self.n_stages:
            raise ValueError(
                f"{len(params_per_stage)} per-stage trees for a "
                f"{self.n_stages}-stage pipeline")
        pack = StageParamPack(params_per_stage)
        packed = pack.shard(self.mesh, params_per_stage,
                            stage_axis=STAGE_AXIS)
        self.param_pack = pack  # only after shard() succeeded
        return packed

    def unshard_params(self, packed):
        """Packed params (or grads in the same layout) → per-stage trees."""
        if self.param_pack is None:
            raise ValueError("no StageParamPack: call shard_params() first")
        return self.param_pack.unshard(packed)

    # -----------------------------------------------------------------
    def __call__(self, params: Sequence[Any], *inputs,
                 key: Optional[jax.Array] = None,
                 train: bool = False, remat_policy=None):
        n = self.n_stages
        m = self.chunks
        # Packed stage-sharded params ({dtype: [n, cap]}) vs per-stage trees.
        packed = isinstance(params, dict)
        if packed:
            if self.param_pack is None:
                raise ValueError(
                    "packed params given but no StageParamPack on this "
                    "executor; call shard_params() (or Pipe.shard_params) "
                    "first")
            self.param_pack.check_packed(params)
        mb.check(*inputs)
        kinds = []
        for x in inputs:
            if isinstance(x, mb.NoChunk):
                kinds.append("nochunk")
            elif mb.is_array(x):
                kinds.append("array")
            else:
                kinds.append("static")
        static_vals = {p: x for p, (x, k) in
                       enumerate(zip(inputs, kinds)) if k == "static"}
        dyn = {str(p): x for p, (x, k) in enumerate(zip(inputs, kinds))
               if k != "static"}
        stacked, bs = mb.stack_scatter(dyn, m)
        true_rows = next(v.shape[1] for p, v in stacked.items()
                         if kinds[int(p)] == "array")
        # Rows must divide the data axis; zero-pad the shortfall (tiny
        # batches / batch < chunks) and slice it back off after gather.
        # Padded rows DO flow through the stages zeroed (as stack_scatter's
        # chunk padding already does): row-wise math is unaffected after the
        # slice, but cross-row batch statistics would see them — the same
        # class of hazard micro-batching itself poses to BatchNorm, which is
        # why Pipe routes stat-bearing models to deferred-BN (emulator-only).
        mb_rows = -(-true_rows // self.n_data) * self.n_data
        if mb_rows != true_rows:
            def pad_rows(p, v):
                if kinds[int(p)] != "array":
                    return v
                pad = [(0, 0), (0, mb_rows - true_rows)] + \
                    [(0, 0)] * (v.ndim - 2)
                return jnp.pad(v, pad)
            stacked = {p: pad_rows(p, v) for p, v in stacked.items()}
        local_rows = mb_rows // self.n_data

        # --- local per-micro-batch boundary chain (+ skip lane specs) ----
        def local_spec(p, v):
            if kinds[int(p)] == "array":
                return jax.ShapeDtypeStruct((local_rows,) + v.shape[2:],
                                            v.dtype)
            return jax.ShapeDtypeStruct(v.shape[1:], v.dtype)

        from ..extras.skip import SkipTracker, use_skip_tracker
        spec_tracker = SkipTracker(self.layout, spec_mode=True)
        vals0: List[Any] = []
        for p in range(len(inputs)):
            if p in static_vals:
                vals0.append(static_vals[p])
            else:
                vals0.append(local_spec(p, stacked[str(p)]))
        boundaries = [vals0]
        specs = vals0
        with use_skip_tracker(spec_tracker):
            for jdx, part in enumerate(self.partitions):
                p_j = (self.param_pack.abstract_tree(jdx) if packed
                       else params[jdx])
                out = part.out_spec(p_j, *specs)
                specs = list(out) if isinstance(out, (tuple, list)) else [out]
                boundaries.append(specs)
        lane_specs = [spec_tracker._store[(0, ns, name)]
                      for ns, name, _, _ in self.lane_keys]

        # Deferred-BN stat lanes: a train-mode spec pass per partition
        # discovers each stage's accumulator keys and shapes. Reuses the
        # same spec tracker so skip stash specs resolve; dropout is a no-op
        # (ctx.key is None), so only the stat channel differs from the
        # boundary walk above.
        stat_keys: List[list] = [[] for _ in range(n)]
        stat_specs: List[list] = [[] for _ in range(n)]
        collect_stats = self.has_bn and train
        if self.has_batch_stats and train and bs % (m * self.n_data):
            raise ValueError(
                f"BatchNorm needs the batch ({bs} rows) to divide evenly "
                f"into chunks*data ({m}*{self.n_data}): padded rows would "
                "contaminate the batch statistics")
        if collect_stats:
            with use_skip_tracker(spec_tracker):
                for jdx, part in enumerate(self.partitions):
                    seen = set(spec_tracker.accum)
                    p_j = (self.param_pack.abstract_tree(jdx) if packed
                           else params[jdx])
                    jax.eval_shape(
                        functools.partial(_apply_train, part),
                        p_j, *boundaries[jdx])
                    for k_ in spec_tracker.accum:
                        if k_ not in seen:
                            stat_keys[jdx].append(k_)
                            stat_specs[jdx].append(spec_tracker.accum[k_])

        # pack plans for boundaries 1..n-1 (stage inputs beyond stage 0)
        plans = [None] + [_PackPlan(boundaries[b]) for b in range(1, n)]
        capacities: dict = {}
        for plan in plans[1:]:
            for dt, sz in plan.per_dtype.items():
                capacities[dt] = max(capacities.get(dt, 0), sz)
        if not capacities:  # single stage: carrier still needs a leaf
            capacities = {"float32": 1}
        out_specs_local = boundaries[n]

        keyed = key is not None
        key = key if keyed else make_key(0)
        stop = checkpoint_stop(self.checkpoint, m, train)

        # --- shard_map specs --------------------------------------------
        data = DATA_AXIS if self.has_data else None

        def in_spec(p, v):
            if kinds[int(p)] == "array":
                return P(*([None, data] + [None] * (v.ndim - 2)))
            return P()

        x_specs = {p: in_spec(p, v) for p, v in stacked.items()}
        out_sp = tuple(
            P(*([STAGE_AXIS, None, data] + [None] * (len(s.shape) - 1)))
        for s in out_specs_local)

        if packed:
            # one row per device: only its own partition's weights live here
            p_arg = dict(params)
            p_spec = {dt: P(STAGE_AXIS, None) for dt in p_arg}
        else:
            p_arg = tuple(params)
            p_spec = jax.tree_util.tree_map(lambda _: P(), p_arg)
        stat_sp = tuple(
            tuple(jax.tree_util.tree_map(
                lambda _: (P(STAGE_AXIS, DATA_AXIS) if self.has_data
                           else P(STAGE_AXIS)), sp_)
                for sp_ in stage_specs)
            for stage_specs in stat_specs)
        run = jax.shard_map(
            functools.partial(
                self._device_program, m=m, plans=plans,
                capacities=capacities, lane_specs=lane_specs,
                out_specs_local=out_specs_local, train=train, keyed=keyed,
                remat_on=stop > 0, remat_policy=remat_policy,
                static_vals=static_vals, kinds=kinds, packed=packed,
                stat_keys=stat_keys, stat_specs=stat_specs),
            mesh=self.mesh,
            in_specs=(p_spec, x_specs, P()),
            out_specs=(out_sp, stat_sp),
            check_vma=False)
        stacked_out, stats_out = run(p_arg, stacked, key)
        # device n-1's slice holds the real outputs: [n, m, rows...] -> [m, ...]
        outs = tuple(o[-1] for o in stacked_out)
        if mb_rows != true_rows:  # drop data-axis padding before gather
            outs = tuple(o[:, :true_rows] for o in outs)
        gathered = tuple(mb.stack_gather(o, bs) for o in outs)
        result = gathered if len(gathered) > 1 else gathered[0]
        if not collect_stats:
            return result
        # Stage s's stats live in row s (zeros elsewhere); data shards sum
        # HOST-SIDE — no in-program subgroup collective (see scheduled.py's
        # wsum note for why that matters on the virtual CPU platform).
        stats: dict = {}
        for jdx in range(n):
            for k_, st in zip(stat_keys[jdx], stats_out[jdx]):
                stats[k_] = jax.tree_util.tree_map(
                    lambda a: (a[jdx].sum(axis=0) if self.has_data
                               else a[jdx]), st)
        return result, stats

    # -----------------------------------------------------------------
    def _make_branch(self, s, all_params, train, keyed, remat_on,
                     remat_policy, plans, capacities, out_specs_local,
                     static_vals, kinds, packed, stat_keys, stat_specs):
        from ..extras.skip import SkipTracker

        n = self.n_stages
        part = self.partitions[s]
        pops = self.layout.pops_of(s) if self.layout else ()
        stashes = self.layout.stashes_of(s) if self.layout else ()
        lane_index = {(ns, name): idx
                      for idx, (ns, name, _, _) in enumerate(self.lane_keys)}
        pop_idx = [lane_index[k] for k in pops]
        stash_idx = [lane_index[k] for k in stashes]

        def branch(x_t, carrier, lanes, kij):
            if s == 0:
                vals = []
                for p in range(len(kinds)):
                    if p in static_vals:
                        vals.append(static_vals[p])
                    else:
                        vals.append(x_t[str(p)])
            else:
                vals = plans[s].unpack(carrier)
            pop_vals = [lanes[i] for i in pop_idx]

            def task(p, k, pop_vals, *vals):
                local = SkipTracker(self.layout)
                for (ns, name), v in zip(pops, pop_vals):
                    local.save(0, ns, name, v)
                ctx = StageCtx(key=k if keyed else None, train=train,
                               data_axis=DATA_AXIS
                               if self.has_data and self.n_data > 1
                               else None)
                with local.scope(0, s), stage_scope(None, s):
                    out = part.apply(p, *vals, ctx=ctx)
                stash_vals = [local.load(0, ns, name) for ns, name in stashes]
                # This stage's deferred-BN stat contributions (explicit remat
                # outputs, like the stashes — stop_gradient'd at source)
                stat_vals = tuple(
                    (local.accum[k_] if k_ in local.accum
                     else _zeros_of(spec))
                    for k_, spec in zip(stat_keys[s], stat_specs[s]))
                return out, stash_vals, stat_vals

            wrapped = apply_remat(task, enabled=remat_on, policy=remat_policy)
            if packed:
                # local row [1, cap] per dtype → this stage's tree; only the
                # selected switch branch executes its unpack, and its
                # transpose scatters grads straight back into the local row.
                p_s = self.param_pack.unpack_stage(
                    {dt: a[0] for dt, a in all_params.items()}, s)
            else:
                p_s = all_params[s]
            out, stash_vals, stat_vals = wrapped(p_s, kij, pop_vals, *vals)
            out_vals = list(out) if isinstance(out, (tuple, list)) else [out]
            lanes2 = list(lanes)
            for idx, v in zip(stash_idx, stash_vals):
                lanes2[idx] = v
            if s == n - 1:
                out_t = tuple(out_vals)
                carrier2 = carrier
            else:
                out_t = tuple(jnp.zeros(sp.shape, sp.dtype)
                              for sp in out_specs_local)
                carrier2 = plans[s + 1].pack(out_vals, capacities)
            # uniform switch-branch structure: this stage's stats in slot s,
            # zeros for every other stage's slots (tiny trees)
            stat_t = tuple(
                stat_vals if s2 == s
                else tuple(_zeros_of(spec) for spec in stat_specs[s2])
                for s2 in range(n))
            return carrier2, tuple(lanes2), out_t, stat_t

        return branch

    # -----------------------------------------------------------------
    def _device_program(self, all_params, x, key, *, m, plans, capacities,
                        lane_specs, out_specs_local, train, keyed, remat_on,
                        remat_policy, static_vals, kinds, packed, stat_keys,
                        stat_specs):
        n = self.n_stages
        j = jax.lax.axis_index(STAGE_AXIS)

        branches = [
            self._make_branch(s, all_params, train, keyed, remat_on,
                              remat_policy, plans, capacities,
                              out_specs_local, static_vals, kinds, packed,
                              stat_keys, stat_specs)
            for s in range(n)]

        carrier0 = {dt: jnp.zeros((cap,), dtype=np.dtype(dt))
                    for dt, cap in capacities.items()}
        lanes0 = tuple(jnp.zeros(sp.shape, sp.dtype) for sp in lane_specs)
        outbuf0 = tuple(jnp.zeros((m + 1,) + tuple(sp.shape), sp.dtype)
                        for sp in out_specs_local)
        bn_acc0 = tuple(
            tuple(_zeros_of(spec) for spec in stage_specs)
            for stage_specs in stat_specs)
        fwd_perm = [(k, k + 1) for k in range(n - 1)]

        def index_x(t):
            return jax.tree_util.tree_map(
                lambda l: jax.lax.dynamic_index_in_dim(
                    l, t, 0, keepdims=False), x)

        def cycle(carry, t):
            carrier, lanes, outbuf, bn_acc = carry
            i = t - j
            x_t = index_x(jnp.clip(t, 0, m - 1))
            kij = jax.random.fold_in(jax.random.fold_in(key, i), j)
            carrier2, lanes2, out_t, stat_t = jax.lax.switch(
                j, branches, x_t, carrier, lanes, kij)
            valid = (j == n - 1) & (i >= 0) & (i < m)
            widx = jnp.where(valid, jnp.clip(i, 0, m - 1), m)
            outbuf = tuple(
                jax.lax.dynamic_update_index_in_dim(buf, o, widx, 0)
                for buf, o in zip(outbuf, out_t))
            # BN stats only from cycles where this device computes a REAL
            # micro-batch — fill/drain cycles run the branch on garbage
            # (zero carriers), whose statistics must not leak in.
            valid_c = (i >= 0) & (i < m)
            bn_acc = jax.tree_util.tree_map(
                lambda a, c: a + jnp.where(valid_c, c, 0), bn_acc, stat_t)
            if n > 1:
                carrier2 = jax.tree_util.tree_map(
                    lambda a: jax.lax.ppermute(a, STAGE_AXIS, fwd_perm),
                    carrier2)
                lanes2 = jax.tree_util.tree_map(
                    lambda a: jax.lax.ppermute(a, STAGE_AXIS, fwd_perm),
                    lanes2)
            return (carrier2, lanes2, outbuf, bn_acc), None

        (carrier, lanes, outbuf, bn_acc), _ = jax.lax.scan(
            cycle, (carrier0, lanes0, outbuf0, bn_acc0),
            jnp.arange(m + n - 1))
        # drop the garbage slot; stack under a stage axis for out_specs;
        # stats gain leading (stage[, data]) axes for host-side reduction
        lead = ((lambda l: l[None, None]) if self.has_data
                else (lambda l: l[None]))
        stats_out = jax.tree_util.tree_map(lead, bn_acc)
        return tuple(b[None, :m] for b in outbuf), stats_out
