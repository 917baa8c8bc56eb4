"""MFU sweep on the chip: checkpoint x remat_policy on the bench
workload (520M tutorial config, chunks=4, d=1 static 1f1b program). No
chip is an error (``bench.tutorial_config``).

``python tools/mfu_sweep.py [policy ...]`` — times ONLY the pipelined
training step per configuration (no baselines/probes), printing one JSON
line per config; a config that fails (OOM included) prints its error and
makes the exit code non-zero. Used to pick bench.py's default policy
(VERDICT r2 #6).
"""

from __future__ import annotations

import json
import os
import sys

import jax
import jax.numpy as jnp
import optax

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from bench import (CHUNKS, BATCH, make_step, peak_flops_per_chip,
                   time_steps, train_flops_per_token, tutorial_config)
from pipe_tpu.core import microbatch as mb
from pipe_tpu.models.transformer_lm import PipelinedLM
from pipe_tpu.parallel.mesh import make_mesh
from pipe_tpu.parallel.scheduled import ScheduledPipeline
from pipe_tpu.parallel.spmd import stack_stage_params
from pipe_tpu.utils.platform import configure_compile_cache
from pipe_tpu.utils.rng import make_key


def main(configs) -> int:
    configure_compile_cache()
    platform = jax.default_backend()
    cfg = tutorial_config(platform)
    mesh = make_mesh(1, 1, devices=jax.devices()[:1])
    model = PipelinedLM(cfg, 1)
    sp, prep, postp = model.init(jax.random.key(0))
    tx = optax.chain(optax.clip_by_global_norm(0.5), optax.adam(1e-4))
    tokens = jax.random.randint(jax.random.key(1), (BATCH, cfg.seq_len),
                                0, cfg.vocab, jnp.int32)
    x, n_rows = mb.stack_scatter(
        {"tokens": tokens, "targets": jnp.roll(tokens, -1, -1)}, CHUNKS)
    w = mb.valid_row_mask(x, n_rows)
    key = make_key(2)
    peak = peak_flops_per_chip()
    tokens_per_step = BATCH * cfg.seq_len
    failed = 0

    for checkpoint, policy_name in configs:
        policy = (getattr(jax.checkpoint_policies, policy_name)
                  if policy_name != "none" else None)
        sched = ScheduledPipeline(
            mesh, model.stage_fn, pre_fn=model.pre_fn,
            post_fn=model.loss_post_fn, checkpoint=checkpoint,
            schedule="1f1b", remat_policy=policy)
        step = make_step(model, sched, tx)

        p = (stack_stage_params(sp),
             jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True), prep),
             jax.tree_util.tree_map(lambda a: jnp.array(a, copy=True), postp))
        try:
            sec, _ = time_steps(step, p, tx.init(p), (x, w, key))
        except Exception as e:  # noqa: BLE001 — a sweep point can OOM
            failed += 1
            print(json.dumps({"checkpoint": checkpoint,
                              "policy": policy_name,
                              "error": str(e)[:300]}), flush=True)
            continue
        finally:
            del p
        tps = tokens_per_step / sec
        # MFU's numerator is the required (no-recompute) FLOPs — checkpoint
        # mode and policy never change it
        req, _ = train_flops_per_token(cfg, "never", CHUNKS)
        print(json.dumps({
            "checkpoint": checkpoint, "policy": policy_name,
            "sec_per_step": round(sec, 5),
            "tok_s_chip": round(tps, 1),
            "mfu": round(req * tps / peak, 4),
        }), flush=True)
    return 1 if failed else 0


if __name__ == "__main__":
    if len(sys.argv) > 1:
        # "policy" (checkpoint defaults to except_last) or "checkpoint:policy"
        configs = [tuple(a.split(":", 1)) if ":" in a
                   else ("except_last", a) for a in sys.argv[1:]]
    else:
        configs = [("except_last", "dots_saveable"),
                   ("except_last", "dots_with_no_batch_dims_saveable"),
                   ("except_last", "none"),
                   ("never", "none")]
    sys.exit(main(configs))
