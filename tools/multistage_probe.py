"""On-chip multi-stage probe: interleaved v>1 table programs at d=1.

On one chip the headline bench (`bench.py`) runs n_stages=1. But
interleaved placements (v virtual stages per device) put a REAL multi-stage
table program on the single chip: the 16-layer tutorial model factors into
v virtual stage bodies, the `interleaved-1f1b` op tables sequence
FWD/BWD per (micro-batch, virtual stage) pairs, and the executor runs its
full stash/residual/cotangent machinery — the same math as the single-stage
program, so the measured delta IS the table machinery + stash traffic
(no ICI, granted: at d=1 the ring hop is a self-permute).

``python tools/multistage_probe.py --quick [n_stages chunks]`` instead runs
the cpu8 bubble probe with the schedule + transport (serialized vs packed
overlapped ppermute) comparison — a CPU drill; this is the child process
bench.py embeds as ``measured_bubble_multistage``. Without ``--quick`` no
chip is an error (``bench.tutorial_config``).

``python tools/multistage_probe.py [v ...]`` (default: 1 2 4) — one JSON
line per variant:

* ``v=1``   — the headline 1f1b single-stage program (same-process anchor).
* ``v>=2``  — `InterleavedOneFOneBSchedule(interleave=v)` at d=1, both the
  dynamic per-cycle `lax.switch` scan and (where it fits) the trace-time
  static unroll, quantifying the switch tax on-chip at tutorial scale.

All variants: 520M tutorial config, chunks=4, checkpoint=except_last,
remat_policy=dots_saveable, bf16-mu Adam — the bench defaults — so numbers
land next to `bench.py`'s headline row. A variant that fails (the static
unroll can exceed HBM) prints its error and makes the exit code non-zero.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

if "--quick" in sys.argv:
    # --quick cpu8 mode (the bench.py multistage hook): no TPU required.
    # The platform MUST be forced before the jax import below binds a
    # backend — this is why the block sits mid-imports.
    from pipe_tpu.utils.platform import force_cpu_platform
    force_cpu_platform(8)

import jax
import jax.numpy as jnp
import optax

from bench import (BATCH, CHUNKS, make_step, peak_flops_per_chip,
                   time_steps, train_flops_per_token, tutorial_config)
from pipe_tpu.core import microbatch as mb
from pipe_tpu.core.schedule import InterleavedOneFOneBSchedule
from pipe_tpu.models.transformer_lm import PipelinedLM
from pipe_tpu.parallel.interleaved import stack_interleaved_params
from pipe_tpu.parallel.mesh import make_mesh
from pipe_tpu.parallel.scheduled import ScheduledPipeline
from pipe_tpu.utils.platform import configure_compile_cache
from pipe_tpu.utils.rng import make_key


def probe_variant(cfg, v: int, static_unroll, tx, tokens, targets):
    """Time one (v, static_unroll) variant; returns the result dict."""
    model = PipelinedLM(cfg, v)          # v virtual stage bodies at d=1
    params = model.init(jax.random.key(0))
    sp, prep, postp = params
    mesh = make_mesh(1, 1, devices=jax.devices()[:1])
    schedule = ("1f1b" if v == 1
                else InterleavedOneFOneBSchedule(interleave=v))
    sched = ScheduledPipeline(
        mesh, model.stage_fn, pre_fn=model.pre_fn,
        post_fn=model.loss_post_fn, checkpoint="except_last",
        schedule=schedule,
        remat_policy=jax.checkpoint_policies.dots_saveable,
        static_unroll=static_unroll)
    table = sched.schedule.op_tables(CHUNKS, 1)
    n_cycles = int(table[0].shape[0])

    x, n_rows = mb.stack_scatter({"tokens": tokens, "targets": targets},
                                 CHUNKS)
    w = mb.valid_row_mask(x, n_rows)
    key = make_key(2)
    step = make_step(model, sched, tx)

    stacked = (stack_interleaved_params(sp, 1),
               jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True),
                                      prep),
               jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True),
                                      postp))
    sec, loss = time_steps(step, stacked, tx.init(stacked), (x, w, key))
    tokens_per_step = BATCH * cfg.seq_len
    tps = tokens_per_step / sec
    req_tok, _ = train_flops_per_token(cfg, "never", CHUNKS)
    mfu = (req_tok * tps) / peak_flops_per_chip()
    return {
        "v": v,
        "schedule": "1f1b" if v == 1 else "interleaved-1f1b",
        "program": ("static" if (static_unroll is True
                                 or (static_unroll is None and v == 1))
                    else "dynamic"),
        "n_cycles": n_cycles,
        "sec_per_step": round(sec, 5),
        "tokens_per_sec_per_chip": round(tps, 2),
        "mfu": round(mfu, 4),
        "final_loss": round(loss, 4),
    }


def main(vs) -> int:
    configure_compile_cache()
    platform = jax.default_backend()
    cfg = tutorial_config(platform)
    header = {
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "chunks": CHUNKS, "batch": BATCH,
        "checkpoint": "except_last", "remat_policy": "dots_saveable",
        "d_model": cfg.d_model, "n_layers": cfg.n_layers,
        "seq_len": cfg.seq_len,
    }
    print(json.dumps({"header": header}), flush=True)

    tx = optax.chain(optax.clip_by_global_norm(0.5),
                     optax.adam(1e-4, mu_dtype=jnp.bfloat16))
    tokens = jax.random.randint(jax.random.key(1), (BATCH, cfg.seq_len),
                                0, cfg.vocab, jnp.int32)
    targets = jnp.roll(tokens, -1, axis=-1)

    anchor = None
    failed = 0
    for v in vs:
        if cfg.n_layers % v:
            print(json.dumps({"v": v, "skipped":
                              f"{cfg.n_layers} layers not divisible"}),
                  flush=True)
            continue
        programs = [None] if v == 1 else [False, True]
        for static in programs:
            try:
                r = probe_variant(cfg, v, static, tx, tokens, targets)
            except Exception as e:       # static unroll can exceed HBM
                failed += 1
                r = {"v": v,
                     "program": "static" if static else "dynamic",
                     "failed": str(e)[:200]}
                print(json.dumps(r), flush=True)
                continue
            if v == 1 and anchor is None:
                anchor = r["sec_per_step"]
            if anchor is not None and "sec_per_step" in r:
                r["overhead_vs_v1"] = round(r["sec_per_step"] / anchor, 4)
            print(json.dumps(r), flush=True)
    return 1 if failed else 0


def quick_main(n_stages: int = 4, chunks: int = 8):
    """cpu8 quick probe: the standing 4-stage/8-chunk bubble measurement
    plus the schedule AND transport (serialized vs packed-overlapped)
    comparison, one JSON line — what bench.py embeds as
    ``measured_bubble_multistage`` each round."""
    from pipe_tpu.obs.bubble_probe import main as bubble_main
    out = bubble_main(n_stages, chunks, compare_schedules=True,
                      compare_transport=True)
    out["mode"] = "quick-cpu8"
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    if "--quick" in sys.argv:
        pos = [int(a) for a in sys.argv[1:] if not a.startswith("--")]
        quick_main(*pos[:2])
    else:
        args = [int(a) for a in sys.argv[1:]] or [1, 2, 4]
        sys.exit(main(args))
