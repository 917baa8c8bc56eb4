"""On-chip inference benchmark: KV-cached decode throughput for the 520M
tutorial LM on one chip. No chip is an error (``bench.tutorial_config``).

Measures, per configuration: prefill time (one batched causal pass over
the prompt) and steady-state decode tokens/s (the scan, amortized per
generated token per sequence, and aggregate across the batch). Greedy
sampling so the numbers are sampling-cost-free. The cached path's whole
point is turning O(t^2) re-forward into O(t) cache reads; the naive
re-forward equivalent at these lengths is too slow to be worth timing
per-run, so the comparison is architectural (see inference/generate.py).

Usage: python tools/gen_bench.py [batch ...]   (default: 1 8 32)
Prints one JSON line per batch size.
"""

from __future__ import annotations

import json
import os
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pipe_tpu.inference import GenerationConfig, Generator
from pipe_tpu.inference.quant import quantize_params
from pipe_tpu.models.transformer_lm import PipelinedLM

from bench import tutorial_config
from pipe_tpu.utils.platform import configure_compile_cache

PROMPT = int(os.environ.get("GEN_BENCH_PROMPT", "128"))
MAX_NEW = int(os.environ.get("GEN_BENCH_NEW", "128"))


def main(batches, int8=False, unroll=False):
    configure_compile_cache()
    platform = jax.default_backend()
    cfg = tutorial_config(platform)
    model = PipelinedLM(cfg, 1)
    sp, pre, post = model.init(jax.random.key(0))
    if int8:
        # Block weights only. Quantizing the vocab head was measured
        # COUNTERPRODUCTIVE (b=1: 33.5 ms/token vs 2.1 block-only): XLA
        # materializes the dequantized [d_model, vocab] f32 matrix every
        # step instead of fusing the dequant into the projection read.
        sp = quantize_params(sp)
    params = (sp, pre, post)
    gen = Generator(model, GenerationConfig(max_new_tokens=MAX_NEW,
                                            temperature=0.0),
                    layer_scan=not unroll)

    for b in batches:
        prompt = jax.random.randint(jax.random.key(1), (b, PROMPT),
                                    0, cfg.vocab, jnp.int32)
        # compile + warm
        jax.block_until_ready(gen.generate(params, prompt))
        iters = 4
        t0 = time.perf_counter()
        for _ in range(iters):
            jax.block_until_ready(gen.generate(params, prompt))
        sec = (time.perf_counter() - t0) / iters
        print(json.dumps({
            "platform": platform,
            "device_kind": jax.devices()[0].device_kind,
            "weights": "int8" if int8 else "native",
            "layers": "unrolled" if unroll else "scan",
            "batch": b, "prompt": PROMPT,
            "max_new": MAX_NEW,
            "sec_per_generate": round(sec, 4),
            "ms_per_token_per_seq": round(1000 * sec / MAX_NEW, 3),
            "decode_tok_s_aggregate": round(b * MAX_NEW / sec, 1),
        }), flush=True)


if __name__ == "__main__":
    args = sys.argv[1:]
    int8 = "--int8" in args
    unroll = "--unroll" in args
    unknown = [a for a in args
               if a.startswith("--") and a not in ("--int8", "--unroll")]
    if unknown:
        sys.exit(f"unknown flags: {unknown} (valid: --int8 --unroll)")
    args = [a for a in args if not a.startswith("--")]
    main([int(a) for a in args] or [1, 8, 32], int8=int8, unroll=unroll)
