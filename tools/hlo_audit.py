"""HLO audit of the schedule-table executor (VERDICT r2 #5 / #3).

``python tools/hlo_audit.py [--d=4] [--m=8] [--schedules=1f1b,zb-h1]
[--checkpoint=never] [--d-model=256]``

Compiles one ``ScheduledPipeline.loss_and_grad`` step per schedule on the
virtual cpu8 mesh, then reports per-program:

* ``flops`` — XLA's own cost model (``compiled.cost_analysis()``), the
  decisive number for "does the B/W split execute extra matmul work";
* ``bytes accessed`` — HBM-traffic proxy;
* optimized-HLO op censuses: ``copy`` (conditional-copy tax), ``dot``
  (matmul count), ``while``/``conditional`` structure;
* cycles in the schedule table, so overhead can be attributed per cycle.

Prints one JSON line; also used by docs/architecture.md's overhead table.

``--slab [--num-slots=8] [--topology=v5e:2x2] ...`` is the odd one out: it
compiles the serve engine's real resident and prefill programs at the
benchmark cell's sizes for a DESCRIBED TPU (no device, no weights) and
lists where the KV slab is copied, transposed or padded (:func:`slab`).
"""

from __future__ import annotations

import collections
import functools
import json
import math
import os
import re
import sys
from unittest import mock

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def audit(n_stages: int = 4, chunks: int = 8, checkpoint: str = "never",
          schedules=("1f1b", "zb-h1"), d_model: int = 256,
          d_ff: int = 512, seq_len: int = 64) -> dict:
    from pipe_tpu.utils.platform import force_cpu_platform
    force_cpu_platform(8)

    import jax
    import jax.numpy as jnp

    from pipe_tpu.core import microbatch as mb
    from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
    from pipe_tpu.parallel.mesh import make_mesh
    from pipe_tpu.parallel.scheduled import ScheduledPipeline
    from pipe_tpu.parallel.spmd import stack_stage_params

    cfg = LMConfig(vocab=512, d_model=d_model, nhead=4, d_ff=d_ff,
                   n_layers=n_stages, seq_len=seq_len, dropout=0.0)
    mesh = make_mesh(n_stages, 1, devices=jax.devices()[:n_stages])
    model = PipelinedLM(cfg, n_stages)
    sp, prep, postp = model.init(jax.random.key(0))
    sp = stack_stage_params(sp)

    m = chunks
    tokens = jax.random.randint(jax.random.key(1), (4 * m, cfg.seq_len),
                                0, cfg.vocab, jnp.int32)
    x, n_rows = mb.stack_scatter(
        {"tokens": tokens, "targets": jnp.roll(tokens, -1, -1)}, m)
    w = mb.valid_row_mask(x, n_rows)

    out = {"platform": "cpu8", "n_stages": n_stages, "chunks": m,
           "checkpoint": checkpoint, "d_model": d_model, "programs": {}}
    for name in schedules:
        pipe = ScheduledPipeline(
            mesh, model.stage_fn, pre_fn=model.pre_fn,
            post_fn=model.loss_post_fn, checkpoint=checkpoint,
            schedule=name)
        lowered = jax.jit(
            lambda s, pipe=pipe: pipe.loss_and_grad(s, prep, postp, x, w)
        ).lower(sp)
        compiled = lowered.compile()
        try:
            ca = compiled.cost_analysis()
            ca = ca[0] if isinstance(ca, (list, tuple)) else ca
        except Exception:  # cost model absent on some backends
            ca = {}
        hlo = compiled.as_text()
        census = {}
        for op in ("copy", "dot", "while", "conditional", "fusion",
                   "dynamic-update-slice", "dynamic-slice",
                   "collective-permute", "all-reduce"):
            # op names appear as `%foo.N = <type> op(`; the type may
            # contain spaces/parens (tuples), so anchor on ` op(` instead.
            census[op] = len(re.findall(rf" {op}\(", hlo)) + \
                len(re.findall(rf" {op}-start\(", hlo))
        out["programs"][name] = {
            "cycles": pipe._cycles(m),
            "flops": ca.get("flops"),
            "bytes_accessed": ca.get("bytes accessed"),
            "optimized_hlo_ops": census,
            "hlo_lines": hlo.count("\n"),
        }
    progs = out["programs"]
    if len(progs) == 2:
        a, b = list(progs)
        fa, fb = progs[a].get("flops"), progs[b].get("flops")
        if fa and fb:
            out["flops_ratio"] = round(fb / fa, 4)
    return out


def percycle(checkpoint: str = "except_last", d_model: int = 256,
             d_ff: int = 512, seq_len: int = 64, iters: int = 4) -> dict:
    """Per-cycle cost of each executor variant at IDENTICAL per-op work
    (one transformer layer per virtual stage, same shapes everywhere).

    For each variant, times one compiled step at m=4 and m=8 micro-batches;
    the slope over the known cycle-count delta is the marginal cost of one
    table cycle (op compute + scan/switch/slot machinery + ring hop), and
    comparing variants at the same per-op compute isolates the machinery:

    * ``d1_static``  — trace-time unrolled straight-line program (the
      branch-free baseline: pure op compute);
    * ``d1_dynamic`` — the same table through the dynamic scan (adds
      lax.switch + masked slot writes + carry copies);
    * ``d2``/``d4``  — the dynamic scan on a real stage ring. NOTE: the
      virtual cpu8 mesh serializes all devices onto this host's single
      core, so a cycle's cost is the SUM of active devices' op compute,
      not the max — d>1 slopes carry that serialization and upper-bound
      the real per-cycle machinery.
    """
    from pipe_tpu.utils.platform import force_cpu_platform
    force_cpu_platform(8)

    import time as _time

    import jax
    import jax.numpy as jnp

    from pipe_tpu.core import microbatch as mb
    from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
    from pipe_tpu.parallel.mesh import make_mesh
    from pipe_tpu.parallel.scheduled import ScheduledPipeline
    from pipe_tpu.parallel.spmd import stack_stage_params

    def step_time(pipe, model, sp, prep, postp, cfg, m):
        tokens = jax.random.randint(jax.random.key(1), (4 * m, cfg.seq_len),
                                    0, cfg.vocab, jnp.int32)
        x, n_rows = mb.stack_scatter(
            {"tokens": tokens, "targets": jnp.roll(tokens, -1, -1)}, m)
        w = mb.valid_row_mask(x, n_rows)
        lg = jax.jit(lambda s: pipe.loss_and_grad(s, prep, postp, x, w))
        jax.block_until_ready(lg(sp))
        t0 = _time.perf_counter()
        for _ in range(iters):
            r = lg(sp)
        jax.block_until_ready(r)
        return (_time.perf_counter() - t0) / iters

    out = {"platform": "cpu8", "checkpoint": checkpoint, "d_model": d_model,
           "per_op_work": "1 transformer layer", "variants": {}}
    variants = [("d1_static", 1, True), ("d1_dynamic", 1, False),
                ("d2", 2, None), ("d4", 4, None)]
    for name, d, unroll in variants:
        cfg = LMConfig(vocab=512, d_model=d_model, nhead=4, d_ff=d_ff,
                       n_layers=d, seq_len=seq_len, dropout=0.0)
        mesh = make_mesh(d, 1, devices=jax.devices()[:d])
        model = PipelinedLM(cfg, d)
        sp, prep, postp = model.init(jax.random.key(0))
        sp = stack_stage_params(sp)
        pipe = ScheduledPipeline(
            mesh, model.stage_fn, pre_fn=model.pre_fn,
            post_fn=model.loss_post_fn, checkpoint=checkpoint,
            schedule="1f1b", static_unroll=unroll)
        times, cycles = {}, {}
        for m in (4, 8):
            times[m] = step_time(pipe, model, sp, prep, postp, cfg, m)
            cycles[m] = pipe._cycles(m)
        slope = (times[8] - times[4]) / (cycles[8] - cycles[4])
        out["variants"][name] = {
            "t_m4_sec": round(times[4], 5), "t_m8_sec": round(times[8], 5),
            "cycles_m4": cycles[4], "cycles_m8": cycles[8],
            "per_cycle_ms": round(slope * 1e3, 3),
        }
    base = out["variants"]["d1_static"]["per_cycle_ms"]
    for v in out["variants"].values():
        v["machinery_tax_vs_static"] = round(v["per_cycle_ms"] / base, 3) \
            if base else None
    return out


def _hlo_computations(hlo: str):
    """Split optimized-HLO text into {computation_name: body_text}."""
    comps = {}
    name = None
    depth = 0
    buf: list = []
    for line in hlo.splitlines():
        if depth == 0:
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^)]*\))?.*\{",
                         line)
            if m:
                name = m.group(1)
                buf = [line]
                depth = line.count("{") - line.count("}")
                if depth == 0:
                    comps[name] = line
                    name = None
            continue
        buf.append(line)
        depth += line.count("{") - line.count("}")
        if depth <= 0 and name is not None:
            comps[name] = "\n".join(buf)
            name = None
            depth = 0
    return comps


def _called(body: str):
    """Computation names a body references (calls, control-flow regions)."""
    out = set()
    for key in ("to_apply", "body", "condition", "true_computation",
                "false_computation", "branch_computations", "calls"):
        for m in re.finditer(
                rf"{key}=\{{?([^,)\}}\s]+(?:,\s*[^,)\}}\s]+)*)\}}?", body):
            for nm in m.group(1).split(","):
                out.add(nm.strip().lstrip("%"))
    return out


def _conditional_census(text: str):
    """Count HLO conditionals by arity. XLA canonicalizes pred-form
    conditionals (``lax.cond``) to 2-branch ``branch_computations={a, b}``,
    so the text key alone cannot separate op DISPATCH (``lax.switch`` —
    one branch per op code, ≥3 for any real table) from the executor's
    2-branch edge-ROLE conds (pre_fn at s==0, loss-seed at is_last,
    except_last's i==m-1). Arity can."""
    dispatch = role = 0
    for m in re.finditer(r"branch_computations=\{([^}]*)\}", text):
        arity = len([b for b in m.group(1).split(",") if b.strip()])
        if arity >= 3:
            dispatch += 1
        else:
            role += 1
    for _ in re.finditer(r"true_computation=", text):
        role += 1
    return dispatch, role


def _reachable(comps, roots):
    """``roots`` (computation names) plus everything they call."""
    seen = set()
    frontier = [r for r in roots if r in comps]
    while frontier:
        nm = frontier.pop()
        if nm in seen:
            continue
        seen.add(nm)
        frontier.extend(c for c in _called(comps[nm])
                        if c in comps and c not in seen)
    return seen


def _region_census(hlo: str, roots):
    """Op census over ``roots`` computations plus everything they call."""
    comps = _hlo_computations(hlo)
    text = "\n".join(comps[nm] for nm in _reachable(comps, roots))
    dispatch, role = _conditional_census(text)
    return {
        # indexed (≥3-branch) HLO conditional — what lax.switch lowers to:
        # the op-dispatch construct the phase compiler exists to remove
        "dispatch_conditionals": dispatch,
        # 2-branch conditionals: the executor's edge-role conds, reported
        # transparently; they select a role, not an op
        "role_conditionals": role,
        "selects": len(re.findall(r" select\(", text)),
        "whiles": len(re.findall(r" while\(", text)),
    }


def phases(n_stages: int = 4, chunks: int = 8, checkpoint: str = "never",
           schedules=("1f1b", "zb-h1", "zb-h1-split", "gpipe"),
           d_model: int = 64, d_ff: int = 128, seq_len: int = 32) -> dict:
    """Census of the PHASE-COMPILED program vs the interpreted executor.

    For each schedule, compiles one ``loss_and_grad`` step with
    ``phase_compile=True`` and one with ``phase_compile=False`` and reports

    * whole-program dispatch-conditional counts (``branch_computations=``
      in optimized HLO — the indexed conditional ``lax.switch`` lowers
      to). The phased program must have ZERO anywhere;
    * per-while (= per steady-state scan segment) censuses of the phased
      program: zero dispatch conditionals and zero pred conditionals other
      than the executor's edge-role conds, which are listed so the claim
      stays honest ("switch-free" means no op dispatch, not no HLO
      conditional at all);
    * the phase program's segmentation (unrolled vs scan cycles).

    ASSERTS the acceptance invariant (steady-state scan bodies free of
    conditional dispatch) and exits non-zero on violation.
    """
    from pipe_tpu.utils.platform import force_cpu_platform
    force_cpu_platform(8)

    import jax
    import jax.numpy as jnp

    from pipe_tpu.core import microbatch as mb
    from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
    from pipe_tpu.parallel.mesh import make_mesh
    from pipe_tpu.parallel.scheduled import ScheduledPipeline
    from pipe_tpu.parallel.spmd import stack_stage_params

    cfg = LMConfig(vocab=128, d_model=d_model, nhead=4, d_ff=d_ff,
                   n_layers=n_stages, seq_len=seq_len, dropout=0.0)
    mesh = make_mesh(n_stages, 1, devices=jax.devices()[:n_stages])
    model = PipelinedLM(cfg, n_stages)
    sp, prep, postp = model.init(jax.random.key(0))
    sp = stack_stage_params(sp)

    m = chunks
    tokens = jax.random.randint(jax.random.key(1), (4 * m, cfg.seq_len),
                                0, cfg.vocab, jnp.int32)
    x, n_rows = mb.stack_scatter(
        {"tokens": tokens, "targets": jnp.roll(tokens, -1, -1)}, m)
    w = mb.valid_row_mask(x, n_rows)

    out = {"platform": "cpu8", "n_stages": n_stages, "chunks": m,
           "checkpoint": checkpoint, "d_model": d_model, "programs": {}}
    violations = []
    for name in schedules:
        # pseudo-schedule: "<name>-split" = the real schedule with the
        # auto-derived structural B/W split (W ops dispatch through the
        # same phased ramps/steady-state machinery)
        sched_kw = {"schedule": name}
        if name.endswith("-split"):
            sched_kw = {"schedule": name[:-len("-split")],
                        "split_stage": "auto"}
        row = {}
        for mode, phase in (("phased", True), ("interpreted", False)):
            pipe = ScheduledPipeline(
                mesh, model.stage_fn, pre_fn=model.pre_fn,
                post_fn=model.loss_post_fn, checkpoint=checkpoint,
                phase_compile=phase, **sched_kw)
            hlo = jax.jit(
                lambda s, pipe=pipe: pipe.loss_and_grad(s, prep, postp,
                                                        x, w)
            ).lower(sp).compile().as_text()
            comps = _hlo_computations(hlo)
            dispatch, role = _conditional_census(hlo)
            whole = {
                "dispatch_conditionals": dispatch,
                "role_conditionals": role,
                "whiles": len(re.findall(r" while\(", hlo)),
            }
            entry = {"whole_program": whole}
            if phase:
                prog = pipe._phase_program(m)
                entry["segments"] = [
                    (s_.kind, s_.t0, s_.t1, s_.period)
                    for s_ in prog.segments] if prog else None
                entry["scan_cycles"] = prog.scan_cycles if prog else 0
                entry["unrolled_cycles"] = (prog.unrolled_cycles
                                            if prog else 0)
                # every while body in the phased program is a steady-state
                # scan segment (ramps are straight-line)
                bodies = {}
                for comp_name, body in comps.items():
                    for mt in re.finditer(r"body=%?([\w.\-]+)", body):
                        bodies[mt.group(1)] = None
                per_while = {b: _region_census(hlo, [b]) for b in bodies}
                entry["steady_bodies"] = per_while
                bad = [b for b, c in per_while.items()
                       if c["dispatch_conditionals"]]
                if whole["dispatch_conditionals"] or bad:
                    violations.append(
                        f"{name}: dispatch conditional in phased program "
                        f"(whole={whole['dispatch_conditionals']}, "
                        f"bodies={bad})")
                if prog is None:
                    violations.append(
                        f"{name}: phase compiler rejected the table "
                        "(no phased program to audit)")
            row[mode] = entry
        out["programs"][name] = row
    out["violations"] = violations
    out["ok"] = not violations
    return out


def resident(num_slots: int = 2, max_len: int = 16,
             resident_chunks: int = 4, spec_tokens: int = 3,
             d_model: int = 32, d_ff: int = 64, n_layers: int = 4) -> dict:
    """Census of the RESIDENT serve whole-program (PR 11 acceptance pin).

    Lowers every resident decode program — single-device slab/paged,
    each with and without the speculative lane, plus the ring's
    slab/paged revolutions — and censuses its ``while`` bodies (the
    steady-state loop and everything it calls) with the same
    arity-based conditional classifier the phase audit uses.

    The pin: ZERO dispatch conditionals (indexed, >=3-branch — what
    ``lax.switch`` lowers to) anywhere in a steady-state body. The
    paged carry's regather fold is a 2-branch ``lax.cond`` — a role
    conditional, reported transparently; done-masking is pure masked
    arithmetic (selects). ASSERTS the invariant and exits non-zero on
    violation.
    """
    from pipe_tpu.utils.platform import force_cpu_platform
    force_cpu_platform(8)

    import jax
    import jax.numpy as jnp

    from pipe_tpu.inference import GenerationConfig
    from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
    from pipe_tpu.parallel.mesh import make_mesh
    from pipe_tpu.parallel.spmd import stack_stage_params
    from pipe_tpu.serve import BucketSpec, SingleDeviceSlotBackend
    from pipe_tpu.serve.ring import RingSlotBackend

    cfg = LMConfig(vocab=128, d_model=d_model, nhead=4, d_ff=d_ff,
                   n_layers=n_layers, seq_len=2 * max_len, dropout=0.0)
    model = PipelinedLM(cfg, n_stages=2)
    params = model.init(jax.random.key(0))
    gen = GenerationConfig(max_new_tokens=max_len // 2, temperature=0.0,
                           eos_token_id=1)

    def single(layout, spec):
        kw = dict(resident=True, resident_chunks=resident_chunks)
        if spec:
            kw["spec_tokens"] = spec_tokens
        if layout == "paged":
            kw.update(kv_block_size=4, prefill_chunk=4)
        else:
            kw["buckets"] = BucketSpec.of(max_len // 2)
        b = SingleDeviceSlotBackend(model, params, num_slots=num_slots,
                                    max_len=max_len, gen=gen, **kw)
        run, args = b.decode_program()
        return run.lower(*args).compile().as_text()

    def ring(layout):
        sp, pre, post = params
        mesh = make_mesh(2, 1)
        kw = dict(resident=True, resident_revolutions=resident_chunks)
        if layout == "paged":
            kw.update(kv_block_size=4, prefill_chunk=4)
        else:
            kw["buckets"] = BucketSpec.of(max_len // 2)
        b = RingSlotBackend(mesh, model, stack_stage_params(sp), pre,
                            post, max_len=max_len, gen=gen, **kw)
        kind = "resident_paged" if b.paged else "resident"
        n = b.n
        args = [b._stage_params, b._pre, b._post, b._caches, b._h,
                b._tok_ring, b._pos_local, b._key_local, jnp.int32(0),
                jnp.asarray(b._admit), jnp.zeros((n,), jnp.int32),
                jnp.asarray(b._tok_inject)]
        if b.paged:
            args.append(jnp.asarray(b.pool.table))
        args += [jnp.full((n,), gen.max_new_tokens, jnp.int32),
                 jnp.int32(resident_chunks)]
        return b._build(kind).lower(*args).compile().as_text()

    out = {"platform": "cpu8", "num_slots": num_slots,
           "max_len": max_len, "resident_chunks": resident_chunks,
           "spec_tokens": spec_tokens, "programs": {}}
    violations = []
    cases = [("single-slab", lambda: single("slab", False)),
             ("single-paged", lambda: single("paged", False)),
             ("single-slab-spec", lambda: single("slab", True)),
             ("single-paged-spec", lambda: single("paged", True)),
             ("ring-slab", lambda: ring("slab")),
             ("ring-paged", lambda: ring("paged"))]
    for name, build in cases:
        hlo = build()
        comps = _hlo_computations(hlo)
        dispatch, role = _conditional_census(hlo)
        bodies = {}
        for body in comps.values():
            for mt in re.finditer(r"body=%?([\w.\-]+)", body):
                bodies[mt.group(1)] = None
        per_body = {b_: _region_census(hlo, [b_]) for b_ in bodies}
        bad = [b_ for b_, c in per_body.items()
               if c["dispatch_conditionals"]]
        if dispatch or bad:
            violations.append(
                f"{name}: dispatch conditional in resident program "
                f"(whole={dispatch}, bodies={bad})")
        if not bodies:
            violations.append(
                f"{name}: no while body found — resident loop missing?")
        out["programs"][name] = {
            "whole_program": {"dispatch_conditionals": dispatch,
                              "role_conditionals": role,
                              "whiles": len(re.findall(r" while\(",
                                                       hlo))},
            "steady_bodies": per_body,
        }
    out["violations"] = violations
    out["ok"] = not violations
    return out


_HLO_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4, "s8": 1, "u8": 1,
                 "s32": 4, "u32": 4, "pred": 1}


def _tiled_bytes(shape: str) -> int:
    """Bytes of an HLO shape string with a TPU tiled layout, e.g.
    ``bf16[48,8,1600,640]{3,2,1,0:T(8,128)(2,1)}``: the two minor-most
    dimensions (by the layout's minor-to-major order) are padded up to
    the tile, ``T(a,b)`` times the sub-tile ``(c,1)`` that packs a
    narrow type into rows. No layout or no tile: the plain bytes."""
    m = re.match(r"(\w+)\[([\d,]*)\](?:\{([\d,]*)(?::([^}]*))?\})?", shape)
    dims = [int(d) for d in m.group(2).split(",") if d]
    order = ([int(d) for d in m.group(3).split(",") if d]
             if m.group(3) else list(range(len(dims) - 1, -1, -1)))
    tile = re.search(r"T\((\d+),(\d+)\)(?:\((\d+),1\))?", m.group(4) or "")
    if tile and len(order) >= 2:
        rows = int(tile.group(1)) * int(tile.group(3) or 1)
        lanes = int(tile.group(2))
        dims[order[0]] = -(-dims[order[0]] // lanes) * lanes
        dims[order[1]] = -(-dims[order[1]] // rows) * rows
    return _HLO_ITEMSIZE[m.group(1)] * math.prod(dims)


def _loop_computations(comps: dict):
    """The computations a ``while`` holds, however deeply."""
    return _reachable(comps, [
        mt.group(1) for body in comps.values()
        for mt in re.finditer(r"(?:body|condition)=%?([\w.\-]+)", body)])


def _slab_census(hlo: str, slab_elems: int, layers: int, dtype: str):
    """Where a compiled decode program moves the KV slab about: every
    ``copy`` or ``transpose`` whose result is a whole slab tensor or one
    layer of one (by element count and type, so a folded or unfolded
    form counts alike), split by whether a ``while`` body holds it
    (however deeply: fused computations and nested loops included) or
    the straight-line rest (the entry computation and what it calls
    outside any loop); and the distinct shapes-with-layout the slab
    takes anywhere, with their tiled bytes."""
    comps = _hlo_computations(hlo)
    in_loop = _loop_computations(comps)
    sizes = {slab_elems: "slab", slab_elems // layers: "layer"}
    moves = {"in_loops": [], "outside_loops": []}
    forms = {}
    for nm, body in comps.items():
        for line in body.splitlines():
            mt = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = "
                          r"((\w+)\[([\d,]*)\](?:\{[^}]*\})?) ([\w\-]+)\(",
                          line)
            if not mt or mt.group(3) != dtype:
                continue
            n = math.prod(int(d) for d in mt.group(4).split(",") if d)
            if n not in sizes:
                continue
            if sizes[n] == "slab":
                forms[mt.group(2)] = _tiled_bytes(mt.group(2))
            if mt.group(5) in ("copy", "transpose"):
                moves["in_loops" if nm in in_loop else "outside_loops"
                      ].append({"name": mt.group(1), "op": mt.group(5),
                                "what": sizes[n], "shape": mt.group(2),
                                "computation": nm})
    return moves, forms


def _io_census(hlo: str) -> dict:
    """A compiled program's entry arguments and outputs by shape, and
    which output is written over which argument (the module's
    ``input_output_alias``: a donated argument the compiler took): what
    a program that carries state in place must show, and a copy of the
    state in its stead must not."""
    entry = re.search(r"^ENTRY [^\n]*\{\n(.*?)^\}", hlo, re.M | re.S).group(1)
    params = {int(n): shape for shape, n in re.findall(
        r"= (\w+\[[\d,]*\])(?:\{[^}]*\})? parameter\((\d+)\)", entry)}
    root = re.search(r"ROOT [^\n]* = \((.*?)\) tuple\(", entry)
    outs = (re.findall(r"(\w+\[[\d,]*\])(?:\{[^}]*\})?", root.group(1))
            if root else [])
    head = hlo.split("\n", 1)[0]
    aliases = [{"output": int(o), "argument": int(a), "shape": params[int(a)]}
               for o, a in re.findall(
                   r"\{(\d+)\}: \((\d+), \{\}", head)]
    return {"arguments": dict(sorted(collections.Counter(
                params.values()).items())),
            "outputs": outs, "aliases": aliases}


def _big_copies(hlo: str, at_least: int):
    """Every ``copy`` of a compiled program whose result holds at least
    ``at_least`` bytes, with whether a ``while`` body holds it: where a
    program relays a weight matrix or a slab before or inside its loop."""
    comps = _hlo_computations(hlo)
    in_loop = _loop_computations(comps)
    found = []
    for nm, body in comps.items():
        for mt in re.finditer(
                r"^\s*(?:ROOT )?%?([\w.\-]+) = "
                r"((\w+)\[([\d,]*)\](?:\{[^}]*\})?) copy\(", body, re.M):
            if mt.group(3) not in _HLO_ITEMSIZE:
                continue
            n = _tiled_bytes(mt.group(2))
            if n >= at_least:
                found.append({"name": mt.group(1), "shape": mt.group(2),
                              "bytes": n, "in_loop": nm in in_loop})
    return found


def _grouped_products(hlo: str) -> dict:
    """The Mosaic kernels of a compiled program (``tpu_custom_call``s): how
    many are the compiler's own grouped product (named ``ragged-dot*``),
    and every other one, a Pallas kernel of the program's, with its result
    and the ``op_name`` a profile will show for it."""
    compilers, ours = 0, []
    for line in hlo.splitlines():
        mt = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+\[[\d,]*\])"
                      r"(?:\{[^}]*\})? custom-call\(", line)
        if not mt or 'custom_call_target="tpu_custom_call"' not in line:
            continue
        if mt.group(1).startswith("ragged-dot"):
            compilers += 1
            continue
        op = re.search(r'op_name="([^"]*)"', line)
        ours.append({"name": mt.group(1), "result": mt.group(2),
                     "op_name": op.group(1) if op else None})
    return {"ragged_dot": compilers, "pallas": ours}


SLAB_MODELS = {
    # the sizes of each model's serve cell (benchmark/traffic/)
    "gpt2": dict(num_slots=8, max_len=640, prefill_bucket=512),
    "laguna": dict(num_slots=16, max_len=2432, prefill_bucket=2048),
    "sdar": dict(num_slots=32, max_len=2048, prefill_bucket=1024),
}
HBM_BYTES = int(15.75 * 2**30)


def slab(num_slots: int = None, max_len: int = None, decode_chunk: int = 4,
         resident_chunks: int = 8, n_layers: int = 48, d_model: int = 1600,
         nhead: int = 25, d_ff: int = 6400, vocab: int = 50257,
         prefill_bucket: int = None, topology: str = "v5e:2x2",
         model: str = "gpt2") -> dict:
    """Where the KV slab lives in the serve engine's compiled programs,
    for a DESCRIBED TPU (no device; nothing runs): the check a cache
    layout PR makes before it spends chip time (PERF.md, PRs 26, 29).

    Builds ``SingleDeviceSlotBackend`` over a GPT-2 of the given sizes
    (defaults: the ``gpt2xl-serve-closed8`` cell's) under
    ``jax.eval_shape``, so no weight is ever made, and compiles its
    real decode program (``decode_program()``: ``_resident_fn``) and one
    ``_prefill_fn`` bucket for one chip of ``topology``. Reports, per
    program: every slab- or layer-sized ``copy``/``transpose`` inside a
    ``while`` body and outside one, the layouts the slab takes with
    their tiled bytes beside the data's own, ``memory_analysis()``, the
    count of each kind of HLO instruction, and under ``io`` its entry
    arguments and outputs by shape with the outputs written over an
    argument. ``ok`` asks what PR 29 asked: no such
    ``copy``/``transpose`` anywhere in the resident program, and no form
    of the slab over 1.05x its data; and what PR 31 asked: the prefill
    program, which arms its slot itself, writes the slab and the slots'
    ``tok``, ``pos`` and ``key_data`` in place. Exits non-zero
    otherwise.

    ``model="laguna"`` builds Laguna-S-2.1's share for one chip
    (``LagunaConfig()``: 10.4 GiB of weights, unmade) at its cell's sizes
    (16 slots of 2,432 rows, the 2,048 bucket). It has one slab a kind of
    cache (``slabs``: full layers at ``max_len`` rows, window layers a
    ring), each checked as above. ``big_copies`` lists every ``copy`` of
    64 MiB or more (a weight matrix relaid before the loop),
    ``grouped_products`` the expert layer's products by what implements
    them (the compiler's ``ragged-dot``s, Pallas custom calls), and ``ok``
    also asks that they come to under a twentieth of the arguments (at
    these sizes a copy of the weights does not fit) and that arguments
    and temporaries stay under the chip's 15.75 GiB. ``model="sdar"``:
    SDAR-30B-A3B-Chat's first stage (``SdarConfig()``: 8.1 GiB, unmade) at
    its cell's sizes (32 slots of 2,048 rows, the 1,024 bucket, a block a
    round); its prefill program arms the slot's block and mask in place."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from pipe_tpu.inference import GenerationConfig
    from pipe_tpu.serve import BucketSpec, SingleDeviceSlotBackend

    sizes = SLAB_MODELS[model]
    num_slots = sizes["num_slots"] if num_slots is None else num_slots
    max_len = sizes["max_len"] if max_len is None else max_len
    prefill_bucket = (sizes["prefill_bucket"] if prefill_bucket is None
                      else prefill_bucket)
    if model == "laguna":
        from pipe_tpu.models.laguna import LagunaConfig, PipelinedLaguna
        net = PipelinedLaguna(LagunaConfig(), 1)
    elif model == "sdar":
        from pipe_tpu.models.sdar import PipelinedSdar, SdarConfig
        net = PipelinedSdar(SdarConfig(), 1)
        decode_chunk = 1            # a round is one block
    else:
        from pipe_tpu.models.gpt2 import GPT2Config, PipelinedGPT2
        net = PipelinedGPT2(GPT2Config(
            vocab=vocab, d_model=d_model, nhead=nhead, d_ff=d_ff,
            n_layers=n_layers, dropout=0.0, seq_len=max(max_len, 1024),
            compute_dtype=jnp.bfloat16), 1)
    gen = GenerationConfig(max_new_tokens=max_len - prefill_bucket,
                           temperature=0.0)
    made = []

    def build():
        b = SingleDeviceSlotBackend(
            net, net.init(jax.random.key(0)), num_slots=num_slots,
            max_len=max_len, gen=gen, buckets=BucketSpec.of(prefill_bucket),
            decode_chunk=decode_chunk, resident=True,
            resident_chunks=resident_chunks)
        made.append(b)
        return b._caches

    caches = jax.eval_shape(build)        # the backend's arrays, unmade
    b = made[0]
    chip = SingleDeviceSharding(
        topologies.get_topology_desc(platform="tpu",
                                     topology_name=topology).devices[0])

    def on_chip(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    resident_fn, resident_args = b.decode_program()
    prefill_fn, prefill_args = b.prefill_program(prefill_bucket)
    resident_args, prefill_args = jax.tree_util.tree_map(
        lambda a: on_chip(a.shape, a.dtype), (resident_args, prefill_args))
    programs = {
        "resident": lambda: resident_fn.lower(*resident_args),
        f"prefill{prefill_bucket}": lambda: prefill_fn.lower(*prefill_args),
    }
    # one slab, or one a kind of cache (a model whose layers come in groups)
    slabs = ({"kv": caches["k"]} if "k" in caches else
             {kind: c["k"] for kind, c in caches.items() if kind != "counts"})
    k = next(iter(slabs.values()))
    data = math.prod(k.shape) * k.dtype.itemsize
    out = {"topology": topology, "model": model, "num_slots": num_slots,
           "max_len": max_len, "slab_shape": list(k.shape),
           "slab_data_bytes": data,
           "slabs": {kind: list(a.shape) for kind, a in slabs.items()},
           "programs": {}}
    violations = []
    # this process sees the CPU, where the expert layer's tiled product
    # would be traced as its interpreter: the chip's program has the kernel
    from pipe_tpu.ops import moe
    for name, lower in programs.items():
        with mock.patch.object(moe, "grouped_gated_mlp", functools.partial(
                moe.grouped_gated_mlp, interpret=False)):
            compiled = lower().compile()
        hlo = compiled.as_text()
        moves, forms = {"in_loops": [], "outside_loops": []}, {}
        fat = {}
        for a in slabs.values():
            elems = math.prod(a.shape)
            mv, fm = _slab_census(hlo, elems, a.shape[0], "bf16")
            for where in moves:
                moves[where] += mv[where]
            forms.update(fm)
            fat.update({f: n for f, n in fm.items()
                        if n > 1.05 * elems * a.dtype.itemsize})
        ma = compiled.memory_analysis()
        out["programs"][name] = {
            "slab_moves": moves, "slab_forms_bytes": forms,
            "big_copies": _big_copies(hlo, 64 * 2**20),
            "grouped_products": _grouped_products(hlo),
            # what a refactor compares with its parent's: equal counts
            # are the same program
            "hlo_ops": dict(sorted(collections.Counter(re.findall(
                r"^\s*(?:ROOT\s+)?%?[\w.\-]+ = .*?\s([a-z][a-z\-]*)\(",
                hlo, re.M)).items())),
            "memory": {f: getattr(ma, f) for f in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "alias_size_in_bytes", "temp_size_in_bytes")},
            "io": _io_census(hlo)}
        aliased = collections.Counter(
            a["shape"] for a in out["programs"][name]["io"]["aliases"])
        # a block round's tok is the slots' two blocks (the one that
        # awaits its commit, the one under denoising), beside the second's
        # mask and the flag that says the first is there
        block = getattr(b, "_block", None)
        tok_pos = ([f"s32[{num_slots}]"] * 2 if block is None else
                   [f"s32[{num_slots}]", f"s32[{num_slots},{2 * block[0]}]",
                    f"pred[{num_slots},{block[0]}]", f"pred[{num_slots}]"])
        if name != "resident" and not (
                all(aliased[s] >= tok_pos.count(s) for s in tok_pos)
                and aliased[f"u32[{num_slots},2]"] >= 1
                and sum(aliased.values()) >= 1 + len(tok_pos)
                + 2 * len(slabs)):
            violations.append(
                f"{name}: the slab and the slots' tok, pos and key_data "
                f"are not all written in place: aliased {dict(aliased)}")
        if model != "gpt2":
            need = (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                    + ma.output_size_in_bytes - ma.alias_size_in_bytes)
            copied = sum(c["bytes"]
                         for c in out["programs"][name]["big_copies"])
            if (copied > ma.argument_size_in_bytes / 20
                    or need > HBM_BYTES):
                violations.append(
                    f"{name}: {copied} B in copies of 64 MiB or more; "
                    f"arguments + temporaries + unaliased outputs {need} B "
                    f"of {HBM_BYTES}")
        if name == "resident" and (moves["in_loops"]
                                   or moves["outside_loops"] or fat):
            violations.append(
                f"{name}: {len(moves['in_loops'])} slab moves in loops, "
                f"{len(moves['outside_loops'])} outside, padded forms "
                f"{fat}")
    out["violations"] = violations
    out["ok"] = not violations
    return out


if __name__ == "__main__":
    kw = {}
    mode = audit
    for a in sys.argv[1:]:
        if a == "--percycle":
            mode = percycle
            continue
        if a == "--phases":
            mode = phases
            continue
        if a == "--resident":
            mode = resident
            continue
        if a == "--slab":
            mode = slab
            continue
        k, v = a.lstrip("-").split("=", 1)
        k = k.replace("-", "_")
        kw[k] = tuple(v.split(",")) if k == "schedules" else (
            v if k in ("checkpoint", "topology", "model") else int(v))
    res = mode(**kw)
    print(json.dumps(res))
    if mode in (phases, resident, slab) and not res["ok"]:
        sys.exit(1)
