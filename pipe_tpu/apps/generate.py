"""Generation driver: sample from a (trained or fresh) pipelined LM.

The reference has no inference path at all (``main.py`` trains and
evaluates loss only); this driver completes the loop: restore a
``train/state.py`` checkpoint (or init fresh weights), then sample
continuations with the KV-cached generator — single-device, or
ring-pipelined over a stage mesh when ``--stages > 1`` (the weights stay
in their stage-sharded training layout).

``--prompts-file`` (one comma-separated prompt per line) routes the
whole set through the continuous-batching serve engine
(``pipe_tpu/serve``) instead of naive per-prompt regeneration: mixed
lengths share a few bucketed prefill programs and ONE decode step, and
each response is still bitwise what a per-prompt generator call would
produce (the serve parity pin, ``tests/test_serve.py``).

Usage:
    python -m pipe_tpu.apps.generate [--resume DIR] [--prompt "ids,..."]
        [--prompts-file F] [--max-new N] [--temperature T] [--top-k K]
        [--eos ID] [--stages N] [--tiny] [--cpu N]
"""

from __future__ import annotations

import argparse
import os
import sys


class DriverError(Exception):
    """User-input problem: print the message, exit rc=2."""


def load_params(resume, model_cfg, _Model, n_stages, seed):
    """Fresh init, or params-only restore from a Trainer checkpoint into
    the SERVING stage layout (train and serve partitions need not
    match). Shared by the generate and serve drivers."""
    import jax
    import numpy as np

    model = _Model(model_cfg, n_stages)
    if not resume:
        return model.init(jax.random.key(seed))

    from ..parallel.spmd import stack_stage_params, unstack_stage_params
    from ..train.state import (checkpoint_params_layout,
                               read_params_layout, restore_params)
    # Trainer checkpoints hold stage-STACKED params in the layout of
    # the TRAINING stage count. Read that layout from metadata, restore
    # only the params subtree (optimizer state is training-only) with
    # an abstract template (no throwaway init), then regroup the flat
    # block sequence into the SERVING stage count.
    n_saved, lps_saved = checkpoint_params_layout(resume)
    if n_saved * lps_saved != model_cfg.n_layers:
        raise DriverError(
            f"checkpoint holds {n_saved}x{lps_saved} blocks but the "
            f"model has {model_cfg.n_layers} layers")
    saved_model = _Model(model_cfg, n_saved)

    def template_fn(key):
        sp, pre, post = saved_model.init(key)
        return (stack_stage_params(sp), pre, post)

    template = jax.eval_shape(template_fn, jax.random.key(0))
    ssp, pre, post = restore_params(resume, template)
    # detach from the TRAINING mesh placement the checkpoint recorded —
    # the serving mesh may have a different device count
    ssp, pre, post = jax.tree_util.tree_map(np.asarray, (ssp, pre, post))
    # flat layer order. Interleaved-schedule training stacks virtual
    # stages device-major-permuted; the layout record written by
    # Trainer.save tells us to invert that (the permutation convention
    # lives with its owner: parallel/interleaved.py). Without a
    # record, plain stage-major stacking is assumed.
    layout = read_params_layout(resume) or {}
    if layout.get("stacking") == "interleaved":
        from ..parallel.interleaved import unstack_interleaved_params
        d = n_saved // int(layout["interleave"])
        per_stage = unstack_interleaved_params(ssp, d)
    else:
        per_stage = unstack_stage_params(ssp, n_saved)
    flat = [blk for stage in per_stage for blk in stage]
    lps = model_cfg.n_layers // n_stages
    return ([flat[s * lps:(s + 1) * lps] for s in range(n_stages)],
            pre, post)


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--resume", default=None,
                   help="checkpoint dir (train/state.py layout); default: "
                        "fresh random init")
    p.add_argument("--prompt", default="1,2,3,4",
                   help="comma-separated prompt token ids (one sequence; "
                        "repeated to fill the batch)")
    p.add_argument("--prompts-file", default=None,
                   help="file with one comma-separated prompt per line; "
                        "the whole set is served through the "
                        "continuous-batching engine (overrides --prompt)")
    p.add_argument("--slots", type=int, default=4,
                   help="--prompts-file: decode slots for the serve "
                        "engine (single-device path; the ring always "
                        "uses one slot per stage)")
    p.add_argument("--eos", type=int, default=None,
                   help="eos token id: finished sequences stop early "
                        "(emit pad in the fixed-shape one-shot path)")
    p.add_argument("--batch", type=int, default=None,
                   help="batch size (default: stages, the ring group count)")
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0,
                   help="0 = greedy")
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--beams", type=int, default=1,
                   help=">1: beam search (deterministic; single-device "
                        "generator only)")
    p.add_argument("--int8", action="store_true",
                   help="int8 weight-only quantized block weights "
                        "(inference/quant.py)")
    p.add_argument("--family", choices=["lm", "gpt2"], default="lm",
                   help="model family: the tutorial-parity LM (sinusoid "
                        "positions, post-LN) or GPT-2 (learned positions, "
                        "pre-LN)")
    p.add_argument("--stages", type=int, default=1,
                   help=">1: ring-pipelined decode over a stage mesh")
    p.add_argument("--context-shards", type=int, default=1,
                   help=">1: context-sharded decode — the prompt KV cache "
                        "shards over a context axis (LM family only; "
                        "prompt length must divide)")
    p.add_argument("--tiny", action="store_true")
    p.add_argument("--cpu", type=int, default=0,
                   help="force N virtual CPU devices (testing without TPU)")
    p.add_argument("--seed", type=int, default=0)
    return p


def main(argv=None) -> int:
    args = build_argparser().parse_args(argv)
    from ..utils.platform import configure_compile_cache
    configure_compile_cache()
    if args.cpu:
        from ..utils.platform import force_cpu_platform
        force_cpu_platform(args.cpu)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..inference import GenerationConfig, Generator

    if args.family == "gpt2":
        from ..models.gpt2 import GPT2Config as _Cfg
        from ..models.gpt2 import PipelinedGPT2 as _Model
    else:
        from ..models.transformer_lm import LMConfig as _Cfg
        from ..models.transformer_lm import PipelinedLM as _Model

    model_cfg = _Cfg()
    if args.tiny:
        model_cfg = model_cfg.tiny()
    n_stages = max(args.stages, 1)

    # validate cheap inputs before any model/parameter materialization —
    # every bad argument exits via the clean rc=2 stderr path, never a
    # raw constructor traceback
    if model_cfg.n_layers % n_stages:
        print(f"--stages {n_stages} must divide the model's "
              f"{model_cfg.n_layers} layers", file=sys.stderr)
        return 2
    if args.prompts_file:
        if not os.path.isfile(args.prompts_file):
            print(f"--prompts-file {args.prompts_file}: no such file",
                  file=sys.stderr)
            return 2
        with open(args.prompts_file) as f:
            lines = [ln for ln in f if ln.strip()]
        sources = lines or ["" ]
    else:
        sources = [args.prompt]
    many = []
    for ln in sources:
        try:
            ids = [int(t) for t in ln.split(",") if t.strip()]
        except ValueError:
            print("prompt must be comma-separated integer token ids",
                  file=sys.stderr)
            return 2
        if not ids or any(i < 0 or i >= model_cfg.vocab for i in ids):
            print(f"prompt ids must be in [0, {model_cfg.vocab})",
                  file=sys.stderr)
            return 2
        many.append(ids)
    ids = many[0]
    if args.eos is not None and (args.eos < 0 or args.eos >= model_cfg.vocab):
        print(f"--eos must be in [0, {model_cfg.vocab})", file=sys.stderr)
        return 2
    if args.eos is not None and args.beams > 1:
        print("--eos with beam search is not implemented", file=sys.stderr)
        return 2
    if args.prompts_file and (args.beams > 1 or args.context_shards > 1):
        print("--prompts-file serves through the slot engine: beams and "
              "context shards are single-shot-generator-only",
              file=sys.stderr)
        return 2
    batch = args.batch if args.batch is not None else n_stages
    if n_stages > 1 and batch % n_stages:
        print(f"--batch {batch} must divide into --stages {n_stages} "
              "ring groups", file=sys.stderr)
        return 2
    if args.resume and not os.path.isdir(args.resume):
        print(f"--resume {args.resume}: no such directory", file=sys.stderr)
        return 2
    if args.beams > 1 and n_stages > 1:
        print("--beams > 1 is single-device only (the ring decoder does "
              "not reorder beams)", file=sys.stderr)
        return 2
    n_ctx = max(args.context_shards, 1)
    if n_ctx > 1:
        if (n_stages > 1 or args.beams > 1 or args.int8
                or args.family != "lm"):
            print("--context-shards composes only with the plain LM "
                  "single-stage float path", file=sys.stderr)
            return 2
        if len(ids) % n_ctx:
            print(f"prompt length {len(ids)} must divide over "
                  f"{n_ctx} context shards", file=sys.stderr)
            return 2

    model = _Model(model_cfg, n_stages)

    try:
        params = load_params(args.resume, model_cfg, _Model, n_stages,
                             args.seed)
    except DriverError as e:
        print(str(e), file=sys.stderr)
        return 2
    if args.int8:
        from ..inference.quant import quantize_params
        sp_q, pre_q, post_q = params
        params = (quantize_params(sp_q), pre_q, post_q)
    gen_cfg = GenerationConfig(max_new_tokens=args.max_new,
                               temperature=args.temperature,
                               top_k=args.top_k, num_beams=args.beams,
                               eos_token_id=args.eos)
    key = jax.random.key(args.seed + 1)

    if args.prompts_file:
        # the serve engine: bucketed prefill + one shared decode step
        # for the whole set, responses bitwise equal to per-prompt
        # generator calls (tests/test_serve.py)
        from ..serve import BucketSpec, ServeEngine
        buckets = BucketSpec.pow2(min_len=8,
                                  max_len=max(len(p) for p in many))
        max_len = buckets.max_len + args.max_new
        if n_stages > 1:
            from ..parallel.mesh import make_mesh
            from ..parallel.spmd import stack_stage_params
            from ..serve import RingSlotBackend
            sp, pre, post = params
            backend = RingSlotBackend(
                make_mesh(n_stages, 1), model, stack_stage_params(sp),
                pre, post, max_len=max_len, gen=gen_cfg, buckets=buckets)
        else:
            from ..serve import SingleDeviceSlotBackend
            backend = SingleDeviceSlotBackend(
                model, params, num_slots=args.slots, max_len=max_len,
                gen=gen_cfg, buckets=buckets)
        eng = ServeEngine(backend)
        seeds = [args.seed + 1] * len(many)
        for resp in eng.serve(many, seeds=seeds):
            print(",".join(str(int(t)) for t in resp.tokens))
        return 0

    prompt = jnp.asarray([ids] * batch, jnp.int32)

    if n_ctx > 1:
        from ..inference.long_context import ContextShardedGenerator
        from ..models.long_context_lm import ContextParallelLM
        from ..parallel.mesh import make_mesh
        cp = ContextParallelLM(model_cfg, n_stages)
        out = ContextShardedGenerator(
            make_mesh(1, 1, n_context=n_ctx), cp, gen_cfg).generate(
            params, prompt, key=key)
    elif n_stages > 1:
        from ..inference.pipelined import PipelinedGenerator
        from ..parallel.mesh import make_mesh
        from ..parallel.spmd import stack_stage_params
        sp, pre, post = params
        mesh = make_mesh(n_stages, 1)
        out = PipelinedGenerator(mesh, model, gen_cfg).generate(
            stack_stage_params(sp), pre, post, prompt, key=key)
    else:
        out = Generator(model, gen_cfg).generate(params, prompt, key=key)

    for row in np.asarray(out):
        print(",".join(str(int(t)) for t in row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
