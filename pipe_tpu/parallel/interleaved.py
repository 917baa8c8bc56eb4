"""Interleaved virtual-stage pipeline: bubble shrinks by the interleave depth.

The schedule capability behind BASELINE config #4 (interleaved 1F1B-style
placement). Each of ``d`` devices hosts ``v`` non-contiguous *virtual* stages
(Megatron assignment: virtual stage ``s`` lives on device ``s % d``), so the
fill/drain bubble is ``(d-1)/(m·v + d-1)`` — ``~v×`` smaller than GPipe's
``(d-1)/(m + d-1)`` at equal per-device work.

SPMD realization (one compiled program, same transport as ``spmd.py``):

* device ``p`` at cycle ``c`` runs task ``k = c - p`` of its private work
  queue — group ``g = k // m``, micro-batch ``i = k % m``, virtual stage
  ``s = g·d + p``; every device is busy every cycle between its fill and
  drain, ``m·v + d - 1`` cycles total;
* stage outputs shift one hop (+1 ring, ``lax.ppermute``) every cycle; the
  wraparound edge ``d-1 → 0`` *is* the jump to the next group, and arriving
  activations wait in a per-micro-batch slot buffer (an activation for
  micro-batch ``i`` is always consumed before its next-group replacement
  arrives, which requires ``m ≥ d`` — the standard interleaved-schedule
  constraint);
* backward and remat follow ``spmd.py``: AD reverses the ring, remat is a
  static per-mode ``jax.checkpoint`` of the stage body.

Parameter layout: :func:`stack_interleaved_params` permutes the ``S = v·d``
per-virtual-stage pytrees device-major, so the plain ``P(stage)`` sharding of
the leading axis gives device ``p`` exactly its groups ``g·d + p``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.partition import StageCtx
from ..core.remat import checkpoint_stop, validate_mode
from .mesh import DATA_AXIS, STAGE_AXIS
from ..utils.rng import make_key

__all__ = ["InterleavedSpmdPipeline", "stack_interleaved_params",
           "unstack_interleaved_params"]


def stack_interleaved_params(params_per_virtual_stage, n_devices: int):
    """Stack S=v·d same-structure pytrees device-major on a leading axis.

    Global row ``p·v + g`` holds virtual stage ``g·d + p``, so sharding the
    leading axis over ``stage`` hands device ``p`` rows ``[p·v, (p+1)·v)`` =
    its interleave groups in order.
    """
    S = len(params_per_virtual_stage)
    if S % n_devices:
        raise ValueError(f"{S} virtual stages not divisible by "
                         f"{n_devices} devices")
    v = S // n_devices
    order = [g * n_devices + p for p in range(n_devices) for g in range(v)]
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack([leaves[s] for s in order], axis=0),
        *params_per_virtual_stage)


def unstack_interleaved_params(stacked, n_devices: int):
    """Inverse of :func:`stack_interleaved_params`: a per-virtual-stage
    list in TRUE virtual-stage order (virtual stage ``g·d + p`` lives at
    stacked row ``p·v + g``). Keeps the permutation convention in this
    module — serving consumers must not re-derive it."""
    S = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    if S % n_devices:
        raise ValueError(f"{S} stacked rows not divisible by "
                         f"{n_devices} devices")
    v = S // n_devices
    return [jax.tree_util.tree_map(
                lambda a: a[(vs % n_devices) * v + vs // n_devices], stacked)
            for vs in range(S)]


@dataclasses.dataclass
class InterleavedSpmdPipeline:
    """Compiled interleaved pipeline over a ``(stage[, data])`` mesh.

    Same contract as :class:`~pipe_tpu.parallel.spmd.SpmdPipeline` (pre_fn on
    virtual stage 0, post_fn on virtual stage S-1, homogeneous ring-invariant
    stage body), plus ``v`` = interleave depth.
    """

    mesh: Any
    stage_fn: Callable
    v: int = 2
    pre_fn: Optional[Callable] = None
    post_fn: Optional[Callable] = None
    post_with_batch: bool = False
    checkpoint: str = "never"
    remat_policy: Any = None

    def __post_init__(self):
        validate_mode(self.checkpoint)
        if STAGE_AXIS not in self.mesh.axis_names:
            raise ValueError(f"mesh must have a {STAGE_AXIS!r} axis")
        if self.v < 1:
            raise ValueError("interleave depth v must be >= 1")
        self.n_devices = self.mesh.shape[STAGE_AXIS]
        self.has_data_axis = DATA_AXIS in self.mesh.axis_names
        # see spmd.SpmdPipeline.bn_axis
        self.bn_axis = (DATA_AXIS if self.has_data_axis
                        and self.mesh.shape[DATA_AXIS] > 1 else None)
        self._pre = self.pre_fn or (lambda p, x, ctx: x)
        if self.post_fn is None:
            self._post = lambda p, h, x_mb, ctx: h
        elif self.post_with_batch:
            self._post = self.post_fn
        else:
            self._post = lambda p, h, x_mb, ctx: self.post_fn(p, h, ctx)

    # -----------------------------------------------------------------
    def memory_plan(self, m: int) -> dict:
        """Static per-device buffer counts — the memory story, inspectable.

        The bubble/v win is bought with O(m) per-device buffers: every
        micro-batch needs an activation slot because each device revisits it
        once per interleave group (plus AD residuals across the
        ``m*v + d - 1``-cycle scan), and the schedule needs ``m >= d`` so a
        slot frees before its next-group replacement arrives. GPipe's AD
        executor carries no slot buffer at all (its O(m) liveness is in AD
        residuals); the memory-capped alternative is
        :class:`~pipe_tpu.parallel.scheduled.ScheduledPipeline` (1F1B,
        ``min(m, n)`` stashed inputs).
        """
        d, v = self.n_devices, self.v
        return {"cycles": m * v + d - 1, "activation_slots": m,
                "out_slots": m, "min_microbatches": d}

    # -----------------------------------------------------------------
    def __call__(self, stage_params, pre_params, post_params, x,
                 *, key: Optional[jax.Array] = None, train: bool = False):
        """Run on micro-batched ``x`` ([m, mb, ...] pytree); returns stacked
        post outputs [m, mb_out, ...] like ``SpmdPipeline``."""
        x_leaves = jax.tree_util.tree_leaves(x)
        if not x_leaves:
            raise TypeError("x must contain at least one array leaf")
        m = x_leaves[0].shape[0]
        d = self.n_devices
        if m < d:
            raise ValueError(
                f"interleaved schedule needs micro-batches >= devices "
                f"(m={m} < d={d}): an activation's buffer slot must free "
                f"before its next-group replacement arrives")
        stop = checkpoint_stop(self.checkpoint, m, train)
        key = key if key is not None else make_key(0)
        data = DATA_AXIS if self.has_data_axis else None
        ctx0 = StageCtx(key=None, train=train)

        x_mb_spec = jax.eval_shape(
            lambda a: jax.tree_util.tree_map(lambda l: l[0], a), x)
        h_spec = jax.eval_shape(
            lambda p, a: self._pre(p, a, ctx0), pre_params, x_mb_spec)
        out_spec = jax.eval_shape(
            lambda p, h, a: self._post(p, h, a, ctx0),
            post_params, h_spec, x_mb_spec)

        in_specs = (
            jax.tree_util.tree_map(lambda _: P(STAGE_AXIS), stage_params),
            jax.tree_util.tree_map(lambda _: P(), pre_params),
            jax.tree_util.tree_map(lambda _: P(), post_params),
            jax.tree_util.tree_map(
                lambda l: P(*([None, data] + [None] * (l.ndim - 2))), x),
            P(),
        )
        out_specs = jax.tree_util.tree_map(
            lambda s: P(*([STAGE_AXIS, None, data]
                          + [None] * (len(s.shape) - 1))),
            out_spec)

        run = jax.shard_map(
            functools.partial(self._device_program, m=m, stop=stop,
                              train=train),
            mesh=self.mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=False)
        stacked = run(stage_params, pre_params, post_params, x, key)
        return jax.tree_util.tree_map(lambda a: a[-1], stacked)

    # -----------------------------------------------------------------
    def _device_program(self, stage_params, pre_params, post_params, x, key,
                        *, m, stop, train):
        d, v = self.n_devices, self.v
        S = d * v
        p = jax.lax.axis_index(STAGE_AXIS)
        ctx0 = StageCtx(key=None, train=train)

        x_mb_spec = jax.eval_shape(
            lambda a: jax.tree_util.tree_map(lambda l: l[0], a), x)
        h_spec = jax.eval_shape(
            lambda pp, a: self._pre(pp, a, ctx0), pre_params, x_mb_spec)
        out_spec = jax.eval_shape(
            lambda pp, h, a: self._post(pp, h, a, ctx0),
            post_params, h_spec, x_mb_spec)

        from .buffers import drop_sentinel, masked_slot_write, slot_buffer

        zeros = lambda s: jnp.zeros(s.shape, s.dtype)
        # Slot m is the sentinel: masked writes go there unconditionally
        # instead of a per-cycle lax.cond around each buffer update.
        buf = slot_buffer(h_spec, m)
        outbuf = slot_buffer(out_spec, m)

        def idx_tree(tree, i):
            return jax.tree_util.tree_map(
                lambda l: jax.lax.dynamic_index_in_dim(l, i, 0,
                                                       keepdims=False), tree)

        def set_tree(tree, i, val, pred):
            return masked_slot_write(tree, val, i, pred, m)

        def body(params_g, k, h):
            return self.stage_fn(params_g, h,
                                 StageCtx(key=k, train=train,
                                          data_axis=self.bn_axis))

        if stop > 0:
            body = jax.checkpoint(body, policy=self.remat_policy) \
                if self.remat_policy is not None else jax.checkpoint(body)

        def cycle(carry, c):
            buf, outbuf = carry
            k = c - p
            active = (k >= 0) & (k < m * v)
            kc = jnp.clip(k, 0, m * v - 1)
            g = kc // m
            i = kc % m
            s = g * d + p
            ckey = jax.random.fold_in(jax.random.fold_in(key, i), s)

            x_i = idx_tree(x, i)
            h_in = jax.lax.cond(
                (s == 0) & active,
                lambda: self._pre(pre_params, x_i,
                                  StageCtx(key=jax.random.fold_in(ckey, 0),
                                           train=train,
                                           data_axis=self.bn_axis)),
                lambda: idx_tree(buf, i))

            params_g = idx_tree(stage_params, g)
            out = body(params_g, jax.random.fold_in(ckey, 1), h_in)

            emit = active & (s == S - 1)
            post_val = jax.lax.cond(
                emit,
                lambda: self._post(post_params, out, x_i,
                                   StageCtx(key=jax.random.fold_in(ckey, 2),
                                            train=train,
                                            data_axis=self.bn_axis)),
                lambda: jax.tree_util.tree_map(zeros, out_spec))
            outbuf = set_tree(outbuf, i, post_val, emit)

            # +1 ring shift (wraparound d-1 -> 0 advances to the next group)
            perm = [(q, (q + 1) % d) for q in range(d)]
            sent = jax.tree_util.tree_map(
                lambda a: jax.lax.ppermute(a, STAGE_AXIS, perm), out)

            # store the arriving activation into its micro-batch slot
            ps = (p - 1) % d
            ks = c - ps
            valid_s = (ks >= 0) & (ks < m * v)
            kcs = jnp.clip(ks, 0, m * v - 1)
            gs = kcs // m
            i_s = kcs % m
            s_s = gs * d + ps
            store = valid_s & (s_s != S - 1)
            buf = set_tree(buf, i_s, sent, store)
            return (buf, outbuf), None

        (buf, outbuf), _ = jax.lax.scan(
            cycle, (buf, outbuf), jnp.arange(m * v + d - 1))
        # drop the sentinel slot before stacking under the stage axis
        return jax.tree_util.tree_map(
            lambda b: b[None], drop_sentinel(outbuf, m))
