"""SPMD pipeline executor: one compiled program, ppermute transport over ICI.

This replaces the reference's entire runtime machine — per-device worker
threads and queues (``pipeline.py:98,237,240``; ``README.md:39-47,291-314``),
per-(stage,chunk) copy streams with ``Copy``/``Wait`` autograd ops
(``pipe.py:417-429``; ``README.md:185-237,324-369``), and fork/join phony
ordering edges (``pipeline.py:128-132``) — with a single ``shard_map``'d
``lax.scan`` over clock cycles:

* transport: ``jax.lax.ppermute`` (XLA ``collective-permute``) shifts the
  activation ring one stage forward per cycle — the D2D copy *and* its
  ordering, compiled;
* schedule: the scan index IS the clock cycle (``pipeline.py:63-79``); stage
  ``j`` works on micro-batch ``i = t - j``, idling (masked) during fill/drain;
* backward: ``jax.grad`` differentiates the scan — reverse ppermutes and
  reverse schedule fall out of AD (the moral equivalent of ``Copy.backward``/
  ``Wait.backward``, ``README.md:219-237,359-369``), and backward micro-batch
  ordering is compiled instead of discovered by a C++ graph walk;
* remat: ``jax.checkpoint`` on the stage body (modes ``always``/
  ``except_last``/``never``, reference ``pipe.py:354``), eval-mode off
  (``pipeline.py:153-155``). NOTE: on this compiled path the remat decision is
  *static* — ``except_last`` remats every micro-batch (numerically identical;
  memory ≤ the reference's except_last; ~1/m extra recompute). The exact
  per-microbatch policy needs ``lax.cond(i < stop, remat(body), body)``, which
  jax 0.9.0 cannot differentiate when the body consumes PRNG (cond branch
  residual join emits mismatched branch return types). The serial emulator
  path implements the exact per-microbatch policy;
* overlap: XLA's latency-hiding scheduler overlaps the collective-permute with
  stage compute — the role of the reference's dedicated copy streams.

Stage heterogeneity (SURVEY §7 hard part #2) is handled Encoder/Decoder-style:
the pipelined body is a *homogeneous* stage stack (params stacked on a leading
``[n_stages, ...]`` axis, sharded over the ``stage`` mesh axis), while an
optional ``pre_fn`` (e.g. embed+posenc) runs only on stage 0 and ``post_fn``
(e.g. decode or per-microbatch loss) only on stage n-1, their params
replicated. This matches the tutorial topology (Encoder + N×block + Decoder,
``main.py:139-157``) while keeping every ppermute a static same-shape ring
shift.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..core.partition import StageCtx
from ..core.remat import checkpoint_stop, validate_mode
from .mesh import DATA_AXIS, STAGE_AXIS
from ..utils.rng import make_key

__all__ = ["SpmdPipeline", "stack_stage_params"]


def stack_stage_params(params_per_stage):
    """Stack per-stage (identically-structured) pytrees on a leading stage axis."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *params_per_stage)


def unstack_stage_params(stacked, n_stages: int):
    """Inverse of :func:`stack_stage_params`: back to a per-stage list."""
    return [jax.tree_util.tree_map(lambda a: a[i], stacked)
            for i in range(n_stages)]


def _identity(params, x, ctx):
    return x


@dataclasses.dataclass
class SpmdPipeline:
    """GPipe pipeline compiled over a ``(stage[, data])`` mesh.

    Args:
      mesh: mesh containing ``stage`` (and optionally ``data``) axes.
      stage_fn: ``(params_j, h, ctx) -> h`` homogeneous stage body; input and
        output activation must have identical shape/dtype (ring invariant).
      pre_fn: ``(pre_params, x_mb, ctx) -> h`` run on stage 0 only (embed).
        ``x_mb`` is one micro-batch slice of the input pytree.
      post_fn: ``(post_params, h, ctx) -> out`` run on stage n-1 only (decode
        or per-example loss); with ``post_with_batch=True`` it is
        ``(post_params, h, x_mb, ctx)`` where ``x_mb`` is the micro-batch the
        output belongs to — e.g. targets for computing loss in-pipeline
        without materializing logits. ``out``'s leading dim must be the
        micro-batch rows (it is sharded over ``data``).
      checkpoint: ``always | except_last | never`` (reference ``pipe.py:354``).
    """

    mesh: Mesh
    stage_fn: Callable
    pre_fn: Optional[Callable] = None
    post_fn: Optional[Callable] = None
    post_with_batch: bool = False
    checkpoint: str = "never"
    remat_policy: Any = None
    # Remat the post (decode/loss) body during training: trades the
    # [rows, seq, vocab]-scale loss residuals (118 MB/micro-batch at tutorial
    # scale, saved for ALL m micro-batches by grad-of-scan) for a decoder
    # recompute at backward time. Numerically identical (same key replays).
    # Default OFF: measured on v5e at tutorial scale it is ~3% SLOWER
    # (160.4 vs 155.7 ms/step) — XLA's schedule absorbs the residual traffic
    # better than the recompute; turn on only when those residuals are what
    # OOMs the step.
    remat_post: bool = False
    # Context (sequence) parallelism: name of a mesh axis over which dim
    # ``context_dim`` of every input leaf with enough rank is sharded. Stage
    # bodies then see local sequence shards and use ring collectives
    # (ops.ring_attention) over that axis — PP x CP composition.
    # CONTRACT: with context_axis set, ``post_fn``'s output MUST be
    # context-invariant (reduce over the axis, e.g. ``lax.pmean`` like
    # ContextParallelLM.loss_post_fn) — out_specs assemble assuming context
    # replication and vma checking is off, so a still-sharded output (e.g.
    # raw per-token logits) would silently return one shard's values.
    context_axis: Optional[str] = None
    context_dim: int = 2
    # Debug mode for the context-invariance contract above: verify at run
    # time that post_fn's output really is identical across context shards
    # (vma checking is off, so a forgotten pmean would otherwise silently
    # return one shard's values). On violation every inexact output leaf is
    # poisoned with NaN and a debug line is printed — loud by construction.
    debug_context_check: bool = False

    def __post_init__(self):
        validate_mode(self.checkpoint)
        if STAGE_AXIS not in self.mesh.axis_names:
            raise ValueError(f"mesh must have a {STAGE_AXIS!r} axis")
        if self.context_axis and self.context_axis not in self.mesh.axis_names:
            raise ValueError(
                f"mesh has no {self.context_axis!r} axis for context_axis")
        if self.context_axis and self.post_fn is None:
            raise ValueError(
                "context_axis requires a post_fn whose output is context-"
                "invariant (e.g. a pmean'd loss); the identity post would "
                "silently return one context shard's activations")
        self.n_stages = self.mesh.shape[STAGE_AXIS]
        self.has_data_axis = DATA_AXIS in self.mesh.axis_names
        # Bound data axis for batch-statistics layers (BatchNorm psums its
        # normalization stats over it — mesh factorization must not change
        # the math); None when absent or size 1.
        self.bn_axis = (DATA_AXIS if self.has_data_axis
                        and self.mesh.shape[DATA_AXIS] > 1 else None)
        self._pre = self.pre_fn or _identity
        if self.post_fn is None:
            self._post = lambda p, h, x_mb, ctx: h
        elif self.post_with_batch:
            self._post = self.post_fn
        else:
            self._post = lambda p, h, x_mb, ctx: self.post_fn(p, h, ctx)
        # _post_spec: the unchecked form, for eval_shape outside shard_map
        # (the checker's pmean needs the mesh axis bound).
        self._post_spec = self._post
        if self.context_axis and self.debug_context_check:
            self._post = self._context_checked(self._post)

    def _context_checked(self, post):
        """Wrap post so a context-variant output turns into NaN + a print.

        A correct post ends in a collective over the context axis (pmean /
        psum), which by definition leaves every shard with the same value —
        so any cross-shard deviation is a contract violation, not noise.
        """
        axis = self.context_axis

        def checked(p, h, x_mb, ctx):
            out = post(p, h, x_mb, ctx)
            leaves = [o for o in jax.tree_util.tree_leaves(out)
                      if jnp.issubdtype(o.dtype, jnp.inexact)]
            if not leaves:
                return out
            delta = jnp.max(jnp.stack([
                jnp.max(jnp.abs((o - jax.lax.pmean(o, axis))
                                .astype(jnp.float32))) for o in leaves]))
            bad = delta > 1e-5
            jax.lax.cond(
                bad,
                lambda: jax.debug.print(
                    "pipe_tpu context-invariance VIOLATION: post_fn output "
                    "differs across context shards by {d:.3e}; it must end "
                    "in a pmean/psum over the context axis. Outputs are "
                    "poisoned with NaN.", d=delta),
                lambda: None)
            poison = jnp.where(bad, jnp.float32(jnp.nan), jnp.float32(0))
            return jax.tree_util.tree_map(
                lambda o: o + poison.astype(o.dtype)
                if jnp.issubdtype(o.dtype, jnp.inexact) else o, out)

        return checked

    # -----------------------------------------------------------------
    def __call__(self, stage_params, pre_params, post_params, x,
                 *, key: Optional[jax.Array] = None, train: bool = False):
        """Run the pipeline on micro-batched input ``x``: a [m, mb, ...] array
        or a pytree of such (e.g. ``{"tokens": ..., "targets": ...}``).

        Returns ``[m, mb_out, ...]`` stacked ``post_fn`` outputs (a global
        array whose data lives on the last stage's devices).
        """
        x_leaves = jax.tree_util.tree_leaves(x)
        if not x_leaves:
            raise TypeError("x must contain at least one array leaf")
        m = x_leaves[0].shape[0]
        n = self.n_stages
        stop = checkpoint_stop(self.checkpoint, m, train)
        # Key is threaded as data so remat replays identical dropout.
        key = key if key is not None else make_key(0)

        data = DATA_AXIS if self.has_data_axis else None
        ctx0 = StageCtx(key=None, train=train)

        # Global post-output spec (for the caller-visible shape only; local
        # buffer shapes are derived inside the device program on local shards).
        x_mb_spec = jax.eval_shape(
            lambda a: jax.tree_util.tree_map(lambda l: l[0], a), x)
        h_spec = jax.eval_shape(
            lambda p, a: self._pre(p, a, ctx0), pre_params, x_mb_spec)
        out_spec = jax.eval_shape(
            lambda p, h, a: self._post_spec(p, h, a, ctx0),
            post_params, h_spec, x_mb_spec)

        def x_spec(l):
            # [m, mb_rows, (seq,) ...]: rows sharded over data; with context
            # parallelism, dim ``context_dim`` also sharded over context.
            spec = [None, data] + [None] * (l.ndim - 2)
            if self.context_axis and l.ndim > self.context_dim:
                spec[self.context_dim] = self.context_axis
            return P(*spec)

        in_specs = (
            jax.tree_util.tree_map(lambda _: P(STAGE_AXIS), stage_params),
            jax.tree_util.tree_map(lambda _: P(), pre_params),
            jax.tree_util.tree_map(lambda _: P(), post_params),
            jax.tree_util.tree_map(x_spec, x),
            P(),                          # key
        )
        # result leaves: [stage, m, mb_rows_out, ...]
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
        # Only the last stage's slice holds real data: [n, m, ...] -> [m, ...]
        return jax.tree_util.tree_map(lambda a: a[-1], stacked)

    # -----------------------------------------------------------------
    def _device_program(self, stage_params, pre_params, post_params, x, key,
                        *, m, stop, train):
        """The per-device SPMD program (runs under shard_map)."""
        n = self.n_stages
        j = jax.lax.axis_index(STAGE_AXIS)
        # This device's stage slice: leading dim n/n_devices == 1 for GPipe.
        params_j = jax.tree_util.tree_map(lambda p: p[0], stage_params)

        # Local (per-shard) activation and output specs.
        ctx0 = StageCtx(key=None, train=train)
        x_mb_spec = jax.eval_shape(
            lambda a: jax.tree_util.tree_map(lambda l: l[0], a), x)
        h_spec = jax.eval_shape(
            lambda p, a: self._pre(p, a, ctx0), pre_params, x_mb_spec)
        out_spec = jax.eval_shape(
            lambda p, h, a: self._post_spec(p, h, a, ctx0),
            post_params, h_spec, x_mb_spec)

        from .buffers import drop_sentinel, masked_slot_write, slot_buffer

        h0 = jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), h_spec)
        # Sentinel slot: invalid cycles write unconditionally into slot m
        # (masked index instead of a per-cycle lax.cond around the update).
        outbuf = slot_buffer(out_spec, m)

        # Stage 0's ingest slices ride the scan's xs; the same buffer (its
        # first m slices) serves the last stage's x_i gathers — one copy,
        # padded with repeats of the final micro-batch for the drain cycles.
        x_fill = jax.tree_util.tree_map(
            lambda l: jnp.concatenate([l] + [l[-1:]] * (n - 1), axis=0)
            if n > 1 else l, x)

        def index_x(idx):
            return jax.tree_util.tree_map(
                lambda l: jax.lax.dynamic_index_in_dim(
                    l, idx, 0, keepdims=False), x_fill)

        def body(p, k, h):
            # ctx.stage carries this device's (traced) stage index so
            # stage-aware wrappers (resilience.chaos.wrap_stage_fn) can
            # target one stage; the model itself never reads it.
            return self.stage_fn(p, h, StageCtx(key=k, train=train,
                                                stage=j,
                                                data_axis=self.bn_axis))

        if stop > 0:
            # remat'd when the mode asks for any remat at all (static
            # selection; see module docstring for why not per-i)
            body = jax.checkpoint(body, policy=self.remat_policy) \
                if self.remat_policy is not None else jax.checkpoint(body)

        def post_body(p, h, x_mb, k):
            return self._post(p, h, x_mb,
                              StageCtx(key=k, train=train,
                                       data_axis=self.bn_axis))

        # see remat_post field docstring: drop the [rows, seq, vocab]-scale
        # loss residuals, recompute the decode at backward time
        post_fn = (jax.checkpoint(post_body)
                   if train and self.remat_post else post_body)

        def single_stage_cycle(_, xs_t):
            # n == 1: no ring, no fill/drain, every cycle valid — degrade to
            # straight-line micro-batch accumulation with zero schedule
            # machinery (this is what the vs_baseline contract measures).
            # x rides the scan's xs and out its stacked ys: no carry, no
            # per-cycle gathers or buffer updates.
            x_t, t = xs_t
            ctx_key = jax.random.fold_in(jax.random.fold_in(key, t), 0)
            h = self._pre(pre_params, x_t,
                          StageCtx(key=jax.random.fold_in(ctx_key, 0),
                                   train=train, data_axis=self.bn_axis))
            h = body(params_j, jax.random.fold_in(ctx_key, 1), h)
            out_t = post_fn(post_params, h, x_t,
                            jax.random.fold_in(ctx_key, 2))
            return None, out_t

        def cycle(carry, xs_t):
            h, outbuf = carry
            # --- stage 0 ingests micro-batch t (clamped during drain);
            # its slice rides the scan's xs, not a per-cycle gather ---
            x_t, t = xs_t
            i = t - j  # micro-batch index in flight on this device
            ctx_key = jax.random.fold_in(jax.random.fold_in(key, i), j)

            h = jax.lax.cond(
                j == 0,
                lambda: self._pre(pre_params,
                                  x_t,
                                  StageCtx(key=jax.random.fold_in(ctx_key, 0),
                                           train=train,
                                           data_axis=self.bn_axis)),
                lambda: h)

            h = body(params_j, jax.random.fold_in(ctx_key, 1), h)

            # --- last stage emits output for valid micro-batches (the x_i
            # gather lives inside the branch: only the last stage pays) ---
            valid = (j == n - 1) & (i >= 0) & (i < m)
            out_t = jax.lax.cond(
                valid,
                lambda: post_fn(post_params, h,
                                index_x(jnp.clip(i, 0, m - 1)),
                                jax.random.fold_in(ctx_key, 2)),
                lambda: jax.tree_util.tree_map(
                    lambda s: jnp.zeros(s.shape, s.dtype), out_spec))
            outbuf = masked_slot_write(outbuf, out_t,
                                       jnp.clip(i, 0, m - 1), valid, m)

            # --- ring shift: stage j -> j+1 (XLA collective-permute) ---
            perm = [(k, k + 1) for k in range(n - 1)]
            h = jax.tree_util.tree_map(
                lambda a: jax.lax.ppermute(a, STAGE_AXIS, perm), h)
            return (h, outbuf), None

        if n == 1:
            _, outs = jax.lax.scan(single_stage_cycle, None,
                                   (x, jnp.arange(m)))
            return jax.tree_util.tree_map(lambda b: b[None], outs)
        (h, outbuf), _ = jax.lax.scan(
            cycle, (h0, outbuf), (x_fill, jnp.arange(m + n - 1)))
        # Drop the sentinel slot; stack on a leading stage axis so
        # out_specs=P(stage,...) is exact (device j contributes its outbuf as
        # slice j; only j=n-1 is real).
        return jax.tree_util.tree_map(
            lambda b: b[None], drop_sentinel(outbuf, m))
