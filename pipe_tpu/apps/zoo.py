"""Model-zoo driver: train any BASELINE config family end-to-end.

``python -m pipe_tpu.apps.zoo gpt2|bert|vit [options]`` builds the family's
pipelined factorization, picks an executor by ``--schedule``, and runs a
short synthetic-data training loop — the zoo analogue of the tutorial
driver (``python main.py <mode>``, reference ``main.py:164-169``), with the
BASELINE.json compositions as defaults:

* ``gpt2``: causal LM (config #3; pair with ``--schedule 1f1b``);
* ``bert``: MLM pretraining with 80/10/10 masking (config #4; pair with
  ``--schedule interleaved-1f1b``);
* ``vit``: image classification (config #5).

``--tiny`` (with ``--cpu N``) keeps it CI-sized; full-size configs are the
real 124M/340M/304M models.
"""

from __future__ import annotations

import argparse
import time


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("family", choices=["gpt2", "bert", "vit"])
    p.add_argument("--checkpoint", default="except_last",
                   choices=["never", "except_last", "always"])
    p.add_argument("--schedule", default="1f1b",
                   choices=["gpipe", "1f1b", "zb-h1", "interleaved-1f1b"])
    p.add_argument("--stages", type=int, default=2)
    p.add_argument("--chunks", type=int, default=4)
    p.add_argument("--interleave", type=int, default=2,
                   help="virtual stages per device (interleaved-1f1b)")
    p.add_argument("--steps", type=int, default=8,
                   help="training steps (>= 1)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--cpu", type=int, default=0,
                   help="force N virtual CPU devices (testing without TPU)")
    return p


def main(argv=None) -> int:
    parser = build_argparser()
    args = parser.parse_args(argv)
    if args.steps < 1:
        parser.error("--steps must be >= 1")
    if args.stages < 1 or args.interleave < 1:
        parser.error("--stages and --interleave must be >= 1")
    from pipe_tpu.utils.platform import configure_compile_cache
    configure_compile_cache()
    if args.cpu:
        from pipe_tpu.utils.platform import force_cpu_platform
        force_cpu_platform(args.cpu)

    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from pipe_tpu.core import microbatch as mb
    from pipe_tpu.core.schedule import InterleavedOneFOneBSchedule
    from pipe_tpu.models import (BertConfig, GPT2Config, PipelinedBERT,
                                 PipelinedGPT2, PipelinedViT, ViTConfig,
                                 mask_tokens)
    from pipe_tpu.parallel.interleaved import stack_interleaved_params
    from pipe_tpu.parallel.mesh import make_mesh
    from pipe_tpu.parallel.scheduled import ScheduledPipeline
    from pipe_tpu.parallel.spmd import SpmdPipeline, stack_stage_params
    from pipe_tpu.utils.rng import make_key

    v = args.interleave if args.schedule == "interleaved-1f1b" else 1
    n_virtual = args.stages * v

    cfg_cls = {"gpt2": GPT2Config, "bert": BertConfig,
               "vit": ViTConfig}[args.family]
    cfg = cfg_cls()
    if args.tiny:
        cfg = cfg.tiny()
    # the model must factor into the virtual stage count
    if cfg.n_layers % n_virtual:
        adjusted = max(1, cfg.n_layers // n_virtual) * n_virtual
        print(f"note: n_layers {cfg.n_layers} -> {adjusted} to factor into "
              f"{n_virtual} virtual stages")
        cfg = dataclasses.replace(cfg, n_layers=adjusted)
    model_cls = {"gpt2": PipelinedGPT2, "bert": PipelinedBERT,
                 "vit": PipelinedViT}[args.family]
    model = model_cls(cfg, n_virtual)
    sp, prep, postp = model.init(make_key(0))
    stacked = (stack_interleaved_params(sp, args.stages) if v > 1
               else stack_stage_params(sp))

    mesh = make_mesh(args.stages, 1, devices=jax.devices()[:args.stages])

    def batch_for(step: int):
        key = make_key(1000 + step)
        if args.family == "vit":
            images = jax.random.normal(
                key, (args.batch, cfg.image_size, cfg.image_size,
                      cfg.channels))
            labels = jax.random.randint(jax.random.fold_in(key, 1),
                                        (args.batch,), 0, cfg.n_classes)
            return {"images": images, "labels": labels}
        tokens = jax.random.randint(key, (args.batch, cfg.seq_len),
                                    2, cfg.vocab, jnp.int32)
        if args.family == "bert":
            masked, weights = mask_tokens(jax.random.fold_in(key, 1),
                                          tokens, cfg)
            return {"tokens": masked, "targets": tokens,
                    "mlm_weights": weights}
        return {"tokens": tokens, "targets": jnp.roll(tokens, -1, -1)}

    tx = optax.adam(args.lr)
    params = (stacked, prep, postp)
    opt_state = tx.init(params)

    if args.schedule == "gpipe":
        pipe = SpmdPipeline(mesh, model.stage_fn, pre_fn=model.pre_fn,
                            post_fn=model.loss_post_fn, post_with_batch=True,
                            checkpoint=args.checkpoint)

        @jax.jit
        def step_fn(params, opt_state, x, w, key):
            def loss_fn(p):
                rows = pipe(p[0], p[1], p[2], x, key=key, train=True)
                return jnp.sum(rows * w) / jnp.sum(w)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss
    else:
        if args.schedule == "interleaved-1f1b" and v == 1:
            print("note: --interleave 1 makes interleaved-1f1b the plain "
                  "1f1b schedule")
        sched_obj = (InterleavedOneFOneBSchedule(interleave=v)
                     if v > 1 else args.schedule)
        sched = ScheduledPipeline(mesh, model.stage_fn, pre_fn=model.pre_fn,
                                  post_fn=model.loss_post_fn,
                                  checkpoint=args.checkpoint,
                                  schedule=sched_obj)

        @jax.jit
        def step_fn(params, opt_state, x, w, key):
            loss, grads = sched.loss_and_grad(params[0], params[1],
                                              params[2], x, w, key=key)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

    print(f"{args.family}: {model.num_params(params):,} params, "
          f"{n_virtual} virtual stages on {args.stages} devices, "
          f"schedule={args.schedule}, checkpoint={args.checkpoint}")
    t_start = t0 = time.perf_counter()
    for b in range(args.steps):
        stacked_x, n_rows = mb.stack_scatter(batch_for(b), args.chunks)
        # zero-weight the rows stack_scatter padded (VERDICT r1 #7)
        w = mb.valid_row_mask(stacked_x, n_rows)
        params, opt_state, loss = step_fn(params, opt_state, stacked_x, w,
                                          make_key(b))
        l = float(loss)
        if b == 0:
            t0 = time.perf_counter()  # timing from step 2 (skip compile)
        print(f"| step {b + 1}/{args.steps} | loss {l:.4f}")
    if args.steps > 1:
        ms = (time.perf_counter() - t0) / (args.steps - 1) * 1000
    else:
        ms = (time.perf_counter() - t_start) * 1000  # compile-inclusive
    print(f"final loss {l:.4f} ({ms:.1f} ms/step)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
