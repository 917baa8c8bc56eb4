"""Tutorial driver: the reference ``main.py`` flow, TPU-native.

Parity walkthrough (reference ``main.py``):
  corpus → tokenizer → vocab → batchify (``main.py:76-105``) →
  Transformer LM (emsize 2048, nhid 2048, nlayers 16, nhead 32, dropout 0.2,
  ``main.py:115-120``) → pipeline over stages with chunks=4
  (``main.py:162-171``) → Adam + StepLR + clip, ~8·bptt tokens
  (``main.py:182-234``) → optional profiler trace (``main.py:196-204``).

Usage (mirrors ``python main.py <checkpoint-mode>``, ``main.py:164-169``):
    python -m pipe_tpu.apps.lm_tutorial <never|except_last|always>
        [--corpus FILE] [--steps N] [--stages N] [--tiny] [--profile DIR]
        [--save DIR] [--resume DIR] [--cpu N]
"""

from __future__ import annotations

import argparse
import sys


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("checkpoint", choices=["never", "except_last", "always"],
                   help="activation-checkpoint mode (main.py:164-169)")
    p.add_argument("--corpus", default=None,
                   help="text file; default: deterministic synthetic corpus")
    p.add_argument("--steps", type=int, default=8,
                   help="train steps (~8·bptt tokens like main.py:194)")
    p.add_argument("--epochs", type=int, default=1)
    p.add_argument("--stages", type=int, default=2)
    p.add_argument("--chunks", type=int, default=4)
    p.add_argument("--schedule",
                   choices=["gpipe", "1f1b", "zb-h1", "interleaved",
                            "interleaved-1f1b"],
                   default="gpipe")
    p.add_argument("--lr", type=float, default=None,
                   help="override the reference's Adam lr=5.0 (main.py:183), "
                        "which diverges at full scale; try 1e-4")
    p.add_argument("--interleave", type=int, default=2,
                   help="virtual stages per device (interleaved schedule)")
    p.add_argument("--plan", default=None,
                   help="auto-planner front door (docs/planning.md): "
                        "'auto' searches schedule x chunks x interleave "
                        "under the planner's cost model and overrides "
                        "--schedule/--chunks; a path loads a saved "
                        "PLAN json (tools/plan_bench.py)")
    p.add_argument("--tiny", action="store_true",
                   help="tiny model config (CI / CPU-sized)")
    p.add_argument("--profile", default=None,
                   help="jax.profiler trace dir (main.py:196-204 equivalent)")
    p.add_argument("--save", default=None, help="checkpoint dir to save into")
    p.add_argument("--resume", default=None, help="checkpoint dir to resume")
    p.add_argument("--autosave", default=None,
                   help="checkpoint dir for preemption-aware autosave "
                        "(SIGTERM finishes the step, saves, exits cleanly)")
    p.add_argument("--cpu", type=int, default=0,
                   help="force N virtual CPU devices (testing without TPU)")
    return p


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    from pipe_tpu.utils.platform import configure_compile_cache
    configure_compile_cache()
    if args.cpu:
        from pipe_tpu.utils.platform import force_cpu_platform
        force_cpu_platform(args.cpu)

    import dataclasses

    import jax

    from pipe_tpu.data import lm_text
    from pipe_tpu.models.transformer_lm import LMConfig
    from pipe_tpu.train.loop import Trainer, TrainerConfig
    from pipe_tpu.train.state import restore_checkpoint

    train_lines, val_lines, _ = lm_text.load_corpus(args.corpus)
    vocab = lm_text.Vocab(map(lm_text.basic_english_tokenize, train_lines))
    train_ids = lm_text.data_process(train_lines, vocab)
    val_ids = lm_text.data_process(val_lines, vocab)

    model_cfg = LMConfig(vocab=max(len(vocab), 2))
    if args.tiny:
        model_cfg = dataclasses.replace(
            model_cfg.tiny(), vocab=max(len(vocab), 2),
            n_layers=2 * args.stages)
    cfg = TrainerConfig(chunks=args.chunks, checkpoint=args.checkpoint,
                        n_stages=args.stages, schedule=args.schedule,
                        interleave=args.interleave, plan=args.plan)
    if args.tiny:
        cfg = dataclasses.replace(cfg, batch_size=8, eval_batch_size=8,
                                  bptt=model_cfg.seq_len, lr=1e-3)
    if args.lr is not None:  # explicit --lr beats the tiny default
        cfg = dataclasses.replace(cfg, lr=args.lr)
    if args.schedule in ("interleaved", "interleaved-1f1b") and args.tiny:
        model_cfg = dataclasses.replace(
            model_cfg, n_layers=args.stages * args.interleave)

    train_data = lm_text.batchify(train_ids, cfg.batch_size)
    val_data = lm_text.batchify(val_ids, cfg.eval_batch_size)

    trainer = Trainer(model_cfg, cfg)
    if args.plan:
        rc = trainer.cfg
        line = (f"plan resolved: schedule={rc.schedule} chunks={rc.chunks} "
                f"interleave={rc.interleave} checkpoint={rc.checkpoint}")
        if rc.plan.profile_source != "uniform":
            # uniform (analytic) profiles rank in abstract units — only a
            # measured profile's prediction is honest wall time
            line += (f" (predicted {rc.plan.predicted_step_s * 1e3:.2f} "
                     f"ms/step, "
                     f"{rc.plan.predicted_peak_bytes / 1e6:.1f} MB/device)")
        print(line)
    if args.autosave:
        trainer.install_autosave(args.autosave)
    state = trainer.init_state()
    if args.resume:
        state = restore_checkpoint(args.resume, state)
        print(f"resumed from step {int(state.step)}")
    print(f"Total parameters in model: {trainer.num_params(state):,}")

    import contextlib

    from pipe_tpu.obs import profile_trace

    metrics = {"loss": float("nan"), "sec_per_step": float("nan")}
    with (profile_trace(args.profile) if args.profile
          else contextlib.nullcontext()):
        for epoch in range(args.epochs):
            state, metrics = trainer.train_epoch(
                train_data, epoch=epoch, state=state,
                max_steps=args.steps, log_every=max(args.steps // 4, 1))
            if trainer._autosave_pending():
                break  # preemption: checkpoint written, exit cleanly
    if args.profile:
        print(f"profiler trace written to {args.profile}")

    if val_data.shape[0] > cfg.bptt:
        val_loss = trainer.evaluate(val_data, state, max_steps=4)
        print(f"val loss {val_loss:.3f}")
    if args.save:
        trainer.save(args.save, state)  # records the stage-stack layout
        print(f"checkpoint saved to {args.save} @ step {int(state.step)}")
    print(f"final train loss {metrics['loss']:.3f} "
          f"({metrics['sec_per_step']*1000:.1f} ms/step)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
