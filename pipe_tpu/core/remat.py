"""Activation-checkpoint (rematerialization) policy.

Capability parity with the reference checkpoint machinery — the
``Checkpointing``/``Checkpoint``/``Recompute`` autograd pair with RNG
save/restore and the phony-with-grad trick (reference ``pipeline.py:16,195-214,
256-260``; quoted module at ``README.md:450-537``; pptx slides 2–3) — collapsed
to its TPU-native essence: ``jax.checkpoint`` applied per micro-batch. The
entire runtime mechanism (deque handoff between Checkpoint.backward and
Recompute.backward, fork/join splicing, RNG state capture) disappears because

* recompute *ordering* is compiled: XLA places the rematerialized forward
  directly before its consuming backward ops;
* bit-identical dropout is free: the same explicit PRNG key is passed to the
  remat'd forward (reference needed ``save_rng_states``/``restore_rng_states``,
  ``README.md:528-537``);
* no phony tensors: ``jax.checkpoint`` differentiates fine with or without
  inputs that require gradients.

Three modes, same knob as reference ``pipe.py:255-260,354``:
``always`` / ``except_last`` / ``never`` → remat micro-batches
``[0, m)`` / ``[0, m-1)`` / ``[]``. Eval mode disables checkpointing entirely
(reference ``pipeline.py:153-155``). ``checkpoint_stop`` is computed against the
*actual* number of scattered micro-batches, fixing the non-divisible-chunks
off-by-one the reference README flags (``README.md:398``).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.extend import core as jc

__all__ = [
    "CHECKPOINT_MODES",
    "checkpoint_stop",
    "apply_remat",
    "split_backward_stage",
    "SplitUnsupported",
]

CHECKPOINT_MODES = ("always", "except_last", "never")


def validate_mode(checkpoint: str) -> str:
    if checkpoint not in CHECKPOINT_MODES:
        raise ValueError(
            f"checkpoint is not one of {' | '.join(CHECKPOINT_MODES)!r}: "
            f"{checkpoint!r}")
    return checkpoint


def checkpoint_stop(checkpoint: str, num_microbatches: int, train: bool) -> int:
    """First micro-batch index NOT rematerialized.

    Reference map ``pipe.py:354`` (always → chunks, except_last → chunks-1,
    never → 0) evaluated against the realized micro-batch count, with the
    eval-mode off-switch of ``pipeline.py:153-155`` folded in.
    """
    validate_mode(checkpoint)
    if not train:
        return 0
    m = num_microbatches
    return {"always": m, "except_last": max(m - 1, 0), "never": 0}[checkpoint]


def apply_remat(fn: Callable, *, enabled: bool,
                policy=None) -> Callable:
    """Wrap a stage body in ``jax.checkpoint`` when enabled.

    ``policy`` optionally forwards a ``jax.checkpoint_policies`` member for
    selective remat (e.g. ``dots_saveable``) — a capability beyond the
    reference's all-or-nothing Checkpoint, kept because on TPU the
    FLOPs-vs-HBM tradeoff is the whole point of remat.
    """
    if not enabled:
        return fn
    if policy is not None:
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


# ---------------------------------------------------------------------------
# Generic structural B/W split (zero-bubble's real contract, derived).
#
# ``models/tp_lm.py`` hand-rolls the tapped/zs/wgrad triple for ONE block.
# :func:`split_backward_stage` derives the same triple from ANY stage fn by
# jaxpr surgery, so every model in the zoo gets a params-constant B vjp and a
# contraction-only W without writing a tapped forward by hand.
#
# The analysis classifies every jaxpr variable:
#
#   C  closure/shape constants (depend on nothing),
#   P  param-derived only (param leaves, casts/reshapes of them),
#   D  data-derived only (activations, ctx key),
#   X  mixed (downstream of a param*data contraction).
#
# The W REGION is the set of equations with >= 1 P-class input: the
# param-side prep chain (dtype casts, scales) plus every param*data mixing
# op (matmuls, layernorm scale/shift, embedding gathers). Region outputs
# that escape to the data side get a zero INJECTED at them (``h + 0`` is a
# no-op forward, but ``jax.vjp`` w.r.t. the zeros hands back exactly those
# outputs' cotangents — ``g_zs``); region-internal edges whose every
# consumer is also in the region CHAIN through the replay instead. The
# region's data-side inputs (post-injection where applicable) are the TAPS.
#
# W then is ``jax.linear_transpose`` of the region replay as a function of
# the param leaves with taps closed over as constants: nothing but the
# weight-grad contractions, and it needs only param AVALS, never values.
#
# Injected region outputs are CUT in the replay: a region eqn consuming one
# reads its tap (constant), not the recomputed producer value. This is what
# keeps grads exact when params feed cascaded ops (ln gamma -> ffn w1): the
# cotangent arriving at an injection point is already the FULL dL/dv (B ran
# the whole data-side chain, including through downstream region ops with
# params held constant), so letting the replay ALSO route it into the
# producer would double-count.
# ---------------------------------------------------------------------------


class SplitUnsupported(ValueError):
    """The stage fn's param usage cannot be auto-split (nonlinear in
    params inside the W region, params leaking into the stage output, or a
    forward that closes over traced values). The message says which; fall
    back to a hand-rolled ``SplitBackwardStage`` (see ``ops/tp_layers``)."""


def _ctx_arrays(ctx):
    """The StageCtx fields that are jax values (traced or concrete), as an
    explicit arg list, plus a rebuild closure and a static-fields cache key.
    StageCtx is deliberately NOT a pytree (static fields steer tracing), so
    the split threads its dynamic leaves by hand."""
    dyn_names, dyn_vals, static = [], [], []
    for f in dataclasses.fields(ctx):
        v = getattr(ctx, f.name)
        if isinstance(v, (jax.Array, jax.core.Tracer)):
            dyn_names.append(f.name)
            dyn_vals.append(v)
        else:
            static.append((f.name, v))

    def rebuild(vals):
        return dataclasses.replace(ctx, **dict(zip(dyn_names, vals)))

    return dyn_vals, rebuild, (tuple(dyn_names), tuple(static))


def _aval_sig(leaves):
    return tuple((tuple(l.shape), str(l.dtype)) for l in leaves)


class _SplitPlan:
    """One traced-and-classified stage body: everything tapped/zs/wgrad
    need, computed once per (arg avals, static ctx) signature."""

    def __init__(self, closed, n_param_leaves: int, params_treedef,
                 out_tree):
        jaxpr = closed.jaxpr
        if any(isinstance(c, jax.core.Tracer) for c in closed.consts):
            raise SplitUnsupported(
                "stage fn closes over traced values (its jaxpr has tracer "
                "consts) — pass everything through params/h/ctx so the "
                "split's replay can be cached")
        self.closed = closed
        self.params_treedef = params_treedef
        self.out_tree = out_tree
        self.n_p = n_param_leaves

        cls: dict = {}
        for v in jaxpr.constvars:
            cls[v] = "C"
        for i, v in enumerate(jaxpr.invars):
            cls[v] = "P" if i < n_param_leaves else "D"
        consumers: dict = {}
        producer: dict = {}
        m_set = set()
        for k, eqn in enumerate(jaxpr.eqns):
            kinds = set()
            for a in eqn.invars:
                if isinstance(a, jc.Var):
                    kinds.add(cls[a])
                    consumers.setdefault(a, []).append(k)
            if "P" in kinds:
                m_set.add(k)
                out_cls = "P" if kinds <= {"P", "C"} else "X"
            elif "X" in kinds:
                out_cls = "X"
            elif "D" in kinds:
                out_cls = "D"
            else:
                out_cls = "C"
            for v in eqn.outvars:
                cls[v] = out_cls
                producer[v] = k
        self.cls = cls
        self._consumers = consumers
        self._producer = producer
        self._m_set = m_set

        outvar_set = {v for v in jaxpr.outvars if isinstance(v, jc.Var)}
        self._outvar_set = outvar_set
        for v in jaxpr.outvars:
            if isinstance(v, jc.Var) and cls[v] == "P":
                raise SplitUnsupported(
                    "stage fn returns a params-only value; its cotangent "
                    "would be dropped by the params-constant B pass")

        # Build with chaining first (fewest zs/taps), prove the transpose;
        # a probe failure WITHOUT a missing-transpose-rule proof usually
        # means a chained edge crossed a second param contraction (the
        # replay then multiplies two param-dependent values — jax's
        # bilinear transpose asserts). Injection is always gradient-exact
        # (chaining is only a zs/taps economy), so rebuild chain-free and
        # re-prove before giving up.
        self._build(allow_chain=True)
        err = self._probe_transpose()
        if err is not None:
            if self._nonlinear_proof(err) is not None:
                raise SplitUnsupported(
                    f"W region is not linear in the params (no "
                    f"transpose rule for an op on the param path: "
                    f"{self._nonlinear_proof(err)}); params may only pass "
                    f"through linear/structural ops before their first "
                    f"contraction with data — use a hand-rolled "
                    f"SplitBackwardStage for this stage fn") from err
            if self.chained:
                self._build(allow_chain=False)
                err = self._probe_transpose()
                if err is not None and \
                        self._nonlinear_proof(err) is not None:
                    raise SplitUnsupported(
                        f"W region is not linear in the params even with "
                        f"every region output injected "
                        f"({self._nonlinear_proof(err)}); use a "
                        f"hand-rolled SplitBackwardStage") from err
            # a residual inconclusive failure (pjit/custom_jvp bodies
            # that only transpose concretely) defers to wgrad()'s
            # runtime guard

    # chaining a param-dependent value into a consumer that combines it
    # with the param side is only linear when the combination is ADDITIVE
    # (ln: gamma*h -> +beta). A multiplicative consumer (dot, mul — the
    # attention q/k cascade) would square the param degree.
    _ADDITIVE = frozenset(["add", "add_any", "sub", "neg", "concatenate"])

    def _build(self, allow_chain: bool):
        """Pick inject-vs-chain for region outputs, prune the replay,
        collect taps. ``allow_chain=False`` injects EVERY inexact region
        output — more zs/taps, but the replay never recomputes a
        param-dependent value, so cascaded param contractions stay
        linear."""
        jaxpr = self.closed.jaxpr
        cls, consumers = self.cls, self._consumers
        producer, m_set = self._producer, self._m_set

        # chain-vs-inject for the region's mixed outputs
        inject, chained = [], set()
        for k in sorted(m_set):
            for v in jaxpr.eqns[k].outvars:
                if cls[v] != "X":
                    continue
                cons = consumers.get(v, [])
                if allow_chain and cons \
                        and all(c in m_set for c in cons) \
                        and all(jaxpr.eqns[c].primitive.name
                                in self._ADDITIVE for c in cons) \
                        and v not in self._outvar_set:
                    chained.add(v)
                elif jnp.issubdtype(v.aval.dtype, jnp.inexact):
                    inject.append(v)
                # non-inexact mixed outputs carry no cotangent: cut silently
        self.inject = inject
        self.inject_set = set(inject)
        self.chained = chained

        # prune the replay to eqns actually reaching an injection point
        needed = set()
        stack = [producer[v] for v in inject]
        while stack:
            k = stack.pop()
            if k in needed:
                continue
            needed.add(k)
            for a in jaxpr.eqns[k].invars:
                if not isinstance(a, jc.Var) or a not in producer:
                    continue
                if a in self.inject_set:
                    continue  # cut: replay reads the tap, not the producer
                if cls[a] in ("C", "P") or a in chained:
                    stack.append(producer[a])
        self.replay_eqns = sorted(needed)

        # taps: data-side inputs of replayed region eqns
        tap_vars, tap_set = [], set()
        for k in self.replay_eqns:
            if k not in m_set:
                continue
            for a in jaxpr.eqns[k].invars:
                if (isinstance(a, jc.Var) and cls[a] in ("D", "X")
                        and a not in chained and a not in tap_set):
                    tap_set.add(a)
                    tap_vars.append(a)
        self.tap_vars = tap_vars
        self.param_structs = [
            jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
            for v in jaxpr.invars[:self.n_p]]
        self.zs_structs = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                           for v in inject]
        self.tap_structs = [jax.ShapeDtypeStruct(v.aval.shape, v.aval.dtype)
                            for v in tap_vars]

    def _probe_transpose(self):
        """Prove the W region transposes NOW (abstractly), not at the
        first real W step: jax.linear_transpose only trips over a
        nonlinear param path (exp(w), w*w, ...) when the returned
        transpose is CALLED, so an eval_shape probe is the earliest
        honest check. Returns the exception on failure, None on proof."""
        if not self.inject:
            return None

        def _probe(gz, taps):
            t = jax.linear_transpose(
                lambda pl: self._replay(pl, taps), self.param_structs)
            return t(gz)

        try:
            jax.eval_shape(_probe, list(self.zs_structs),
                           list(self.tap_structs))
        except Exception as e:
            return e
        return None

    @staticmethod
    def _nonlinear_proof(err):
        """Walk the cause chain for a missing transpose rule — the only
        failure that PROVES a nonlinear param path. Other abstract-eval
        failures (bilinear asserts from chained edges, pjit quirks) are
        structural or inconclusive."""
        c = err
        while c is not None and not isinstance(c, NotImplementedError):
            c = c.__cause__
        return c

    # -- tapped forward: eval the whole jaxpr, adding zs at injection
    # points and recording taps. Mirrors jax.core.eval_jaxpr's bind loop so
    # pjit / custom_jvp_call / scan eqns run atomically and stay
    # differentiable (everything binds on the caller's tracers).
    def eval_tapped(self, args, zs):
        jaxpr = self.closed.jaxpr
        if len(zs) != len(self.inject):
            raise ValueError(
                f"zs has {len(zs)} leaves but this stage traces to "
                f"{len(self.inject)} injection points — zs must come from "
                f"this split's zs_fn (is the forward's structure "
                f"ctx-dependent?)")
        env: dict = {}

        def read(a):
            return a.val if isinstance(a, jc.Literal) else env[a]

        for v, c in zip(jaxpr.constvars, self.closed.consts):
            env[v] = c
        for v, val in zip(jaxpr.invars, args):
            env[v] = val
        zmap = {v: z for v, z in zip(self.inject, zs)}
        for eqn in jaxpr.eqns:
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            ans = eqn.primitive.bind(*subfuns, *map(read, eqn.invars),
                                     **bind_params)
            outs = ans if eqn.primitive.multiple_results else [ans]
            for v, val in zip(eqn.outvars, outs):
                if v in zmap:
                    val = val + zmap[v]
                env[v] = val
        out = [read(v) for v in jaxpr.outvars]
        taps = [env[v] for v in self.tap_vars]
        return out, taps

    # -- the W region replay: params -> pre-injection region outputs, with
    # taps as closure constants. Linear in params by construction (or the
    # transpose below fails loudly).
    def _replay(self, param_leaves, tap_vals):
        jaxpr = self.closed.jaxpr
        env: dict = {}
        taps = dict(zip(self.tap_vars, tap_vals))

        def read(a):
            if isinstance(a, jc.Literal):
                return a.val
            if a in self.inject_set:
                return taps[a]  # cut edge: constant, post-injection value
            return env[a] if a in env else taps[a]

        for v, c in zip(jaxpr.constvars, self.closed.consts):
            env[v] = c
        for v, val in zip(jaxpr.invars[:self.n_p], param_leaves):
            env[v] = val
        for k in self.replay_eqns:
            eqn = jaxpr.eqns[k]
            subfuns, bind_params = eqn.primitive.get_bind_params(eqn.params)
            ans = eqn.primitive.bind(*subfuns, *map(read, eqn.invars),
                                     **bind_params)
            outs = ans if eqn.primitive.multiple_results else [ans]
            for v, val in zip(eqn.outvars, outs):
                env[v] = val
        return [env[v] for v in self.inject]

    def wgrad(self, taps, gzs):
        def region(param_leaves):
            return self._replay(param_leaves, list(taps))

        try:
            transpose = jax.linear_transpose(region, self.param_structs)
            (gp_leaves,) = transpose(list(gzs))
        except Exception as e:  # pragma: no cover - plan-time probe
            # catches this first; kept for jax version drift
            raise SplitUnsupported(
                f"W region is not linear in the params "
                f"(jax.linear_transpose failed: {e}); use a hand-rolled "
                f"SplitBackwardStage for this stage fn") from e
        gp_leaves = [
            jnp.zeros(s.shape, s.dtype) if g is None else g
            for g, s in zip(gp_leaves, self.param_structs)]
        return jax.tree_util.tree_unflatten(self.params_treedef, gp_leaves)


def split_backward_stage(stage_fn: Callable, *,
                         canonical_key: Any = None):
    """Derive a ``SplitBackwardStage`` for ANY 3-arg stage fn.

    ``stage_fn(params_g, h, ctx) -> h_out`` is traced and classified per
    the module notes above; the returned object carries the protocol the
    scheduled executor's split path expects (``tapped_fn``/``wgrad_fn``/
    ``zs_fn``). ``ScheduledPipeline(split_stage="auto")`` calls this on its
    own ``stage_fn``.

    The analysis re-runs (and re-caches) per distinct (arg avals, static
    ctx fields) signature — microbatch shape changes or train/eval flips
    get their own plan. ``zs_fn(params_g, h)`` has no ctx, so it traces a
    CANONICAL one (train=True, a concrete PRNG key — the executor always
    feeds both); dropout and other key-consuming ops are data-side and
    cannot move the injection points, and ``tapped_fn`` cross-checks the
    zs structure against its own trace anyway.

    Limits (raise :class:`SplitUnsupported`): params must enter the
    forward LINEARLY up to the first param*data contraction (casts, scales
    fine; ``exp(w)`` not); the stage must not return a params-only value;
    stage fns whose zs sizing needs bound mesh axes (collectives inside)
    need a hand-rolled split. ``canonical_key`` overrides the zs_fn trace
    key (match the executor's key impl when tracing with typed keys).
    """
    plans: dict = {}

    def _plan(params_g, h, ctx):
        p_leaves, p_def = jax.tree_util.tree_flatten(params_g)
        h_leaves, h_def = jax.tree_util.tree_flatten(h)
        cvals, rebuild, static_sig = _ctx_arrays(ctx)
        sig = (_aval_sig(p_leaves + h_leaves + cvals), p_def, h_def,
               static_sig)
        plan = plans.get(sig)
        if plan is None:
            def wrapper(pl, hl, cl):
                p = jax.tree_util.tree_unflatten(p_def, pl)
                hh = jax.tree_util.tree_unflatten(h_def, hl)
                return stage_fn(p, hh, rebuild(cl))

            closed, out_shape = jax.make_jaxpr(wrapper, return_shape=True)(
                p_leaves, h_leaves, cvals)
            out_tree = jax.tree_util.tree_structure(out_shape)
            plan = _SplitPlan(closed, len(p_leaves), p_def, out_tree)
            plans[sig] = plan
            # wgrad sees only (taps, gzs): index the plan by their avals
            # too. A collision can only come from a same-shape retrace
            # (e.g. train/eval), whose W region is identical — last wins.
            plans[("w", _aval_sig(plan.tap_structs),
                   _aval_sig(plan.zs_structs))] = plan
        return plan, p_leaves + h_leaves + cvals

    def tapped_fn(params_g, h, ctx, zs):
        plan, args = _plan(params_g, h, ctx)
        zl = list(zs)
        out, taps = plan.eval_tapped(args, zl)
        return jax.tree_util.tree_unflatten(plan.out_tree, out), taps

    def wgrad_fn(taps, gzs):
        tl, gl = list(taps), list(gzs)
        plan = plans.get(("w", _aval_sig(tl), _aval_sig(gl)))
        if plan is None:
            raise ValueError(
                "wgrad_fn called before tapped_fn traced this stage "
                "signature — taps/gzs do not come from this split")
        return plan.wgrad(tl, gl)

    def zs_fn(params_g, h):
        from .partition import StageCtx
        key = canonical_key
        if key is None:
            from ..utils.rng import make_key
            key = make_key(0)
        plan, _ = _plan(params_g, h,
                        StageCtx(key=key, train=True, stage=0))
        return [jnp.zeros(s.shape, s.dtype) for s in plan.zs_structs]

    from ..parallel.scheduled import SplitBackwardStage
    return SplitBackwardStage(tapped_fn=tapped_fn, wgrad_fn=wgrad_fn,
                              zs_fn=zs_fn)
