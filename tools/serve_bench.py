"""Serving benchmark: the continuous-batching engine under synthetic load.

Three questions, answered on whatever backend is available (the numbers
of record are the committed ``SERVE_r08.json``):

1. **Slot tax** — steady-state decode tokens/s with every slot
   continuously full, vs the fixed-batch ``Generator`` at the same live
   count (batch = num_slots). The engine's decode step is the batched
   per-slot program (vmapped positions, per-slot key chains) plus one
   host round-trip per ``decode_chunk`` tokens; the acceptance bar is
   >= 0.9x the one-shot batch program.
2. **Latency under load** — seeded Poisson arrivals at a fraction of
   measured capacity; per-request TTFT p50/p99
   (:func:`pipe_tpu.obs.telemetry.percentile_exact` — the streaming
   histogram's bucketed quantiles are too coarse for a bench artifact).
3. **Goodput under 2x overload, backpressure on vs off** — "on" bounds
   the queue (excess rejected at submit, cheap), "off" admits everything
   (requests rot in the queue past their deadline and are reaped, or
   time out mid-decode after burning slot-steps). Goodput counts only
   tokens of requests that finished ``ok`` within their deadline.
4. **Resident loop A/B** (``SERVE_r14.json``; ``--resident`` adds the
   speculative section to the full run) — host-overhead-per-token and
   tokens/s at equal live slots, single-chunk ticks vs the fused
   ``lax.while_loop``, plus draft/verify acceptance on repetitive
   prompts with the bitwise-Generator-parity bit reported.
5. **KV gen-2** (``SERVE_r17.json``) — multi-tenant radix reuse
   (fleet-common base, per-tenant divergence, full-block random tails:
   the shape where a gen-1 whole-prefix cache scores zero) with the
   counterfactual hit fraction and the TTFT the skipped prefill buys,
   plus the offload drill: spill cold blocks to host under pool
   pressure, restore on re-reference, tokens bitwise the unpressured
   run.
6. **Speculative gen-2** (``SERVE_r18.json``) — real draft sources on
   the tied-head bench weights (see :func:`_spec_bench_params`):
   n-gram vs truncated-pipeline vs tree acceptance on APERIODIC
   prompts (the history lookup's worst case, the model drafts' home
   turf), every source bitwise the Generator; then the
   measured-breakeven closed loop — spec vs non-spec resident tokens/s
   at equal live slots, with the verify-chunk cost ratio measured from
   the two engines' own step/round rates and fed back through the
   planner's :func:`~pipe_tpu.core.planner.spec_breakeven_acceptance`
   so the artifact records predicted AND measured speedup.

Usage:
  python tools/serve_bench.py            # full run, pretty JSON to stdout
  python tools/serve_bench.py --quick    # small run, one JSON line
Progress goes to stderr; stdout is machine-readable (the last line is
always the summary object), so ``bench.py`` embeds the --quick summary.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from pipe_tpu.inference import GenerationConfig, Generator
from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
from pipe_tpu.obs.telemetry import percentile_exact
from pipe_tpu.serve import (BucketSpec, QueueFull, RequestQueue,
                            ServeEngine, SingleDeviceSlotBackend)

CFG = LMConfig(vocab=1024, d_model=128, nhead=8, d_ff=512, n_layers=4,
               seq_len=256, dropout=0.0)
BUCKETS = BucketSpec.of(32, 64)
MAX_NEW = 64
# Size the slot cache to the workload, exactly as Generator sizes its
# cache to prompt+max_new: attention cost scales with cache ROWS, not
# live tokens, so an oversized max_len taxes every decode step (measured
# ~0.6x the fixed-batch baseline at 2x the needed rows vs ~1.3x when
# sized to fit).
MAX_LEN = BUCKETS.max_len + MAX_NEW
# KV A/B workload: a long shared system prompt (112 tokens = 14 full
# blocks at block=8) with short per-request tails — the shape
# prefix caching exists for. Demand per request is 19 blocks but only 5
# are private once the prefix is cached, so a pool holding the slab's
# row budget for S slots carries 2S live requests; and the paged
# prefill recomputes ONE 16-token chunk where the slab runs the full
# 128-wide bucket program.
KV_BLOCK = 8
SHARED_LEN = 112
AB_TAILS = (4, 8)
AB_MAX_NEW = 32
AB_BUCKETS = BucketSpec.of(128)
AB_MAX_LEN = SHARED_LEN + max(AB_TAILS) + AB_MAX_NEW    # 152
# Multi-tenant radix workload (SERVE_r17): every tenant's preamble
# starts with one fleet-common base (8 full blocks) then diverges into
# a per-tenant segment (4 blocks); request tails are >= 1 block so the
# full prompt-block chain is NEVER entirely cached — a gen-1
# whole-prefix cache (exact full-chain match) scores zero here, while
# the radix tree still reuses the base + tenant blocks of every
# admission after the first per tenant.
MT_BASE_LEN = 64
MT_TENANT_LEN = 32
MT_TENANTS = 3
MT_TAILS = (8, 16)


def host_contention():
    """1-min load average vs CPU count: above ~75% the host is fighting
    itself and wall-clock goodput numbers are noise."""
    try:
        load1 = os.getloadavg()[0]
    except OSError:                               # pragma: no cover
        return {"host_load1": None, "cpu_count": os.cpu_count() or 1,
                "contended": False}
    cpus = os.cpu_count() or 1
    return {"host_load1": round(load1, 2), "cpu_count": cpus,
            "contended": bool(load1 > 0.75 * cpus)}


def _backend_kv_kwargs(kv, pool_blocks=None):
    if kv == "slab":
        return {}
    return {"kv_block_size": KV_BLOCK, "kv_pool_blocks": pool_blocks}


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def make_prompts(n, rng):
    lens = rng.choice((20, 32, 48, 64), size=n)
    return [rng.randint(1, CFG.vocab, size=int(p)).tolist() for p in lens]


def baseline_tokens_per_sec(model, params, slots, rng):
    """Fixed-batch Generator decode tokens/s at batch == num_slots.
    Two generation lengths at the largest bucket's prompt width (the
    Generator cache spans 80..144 rows vs the engine's fixed 128 — the
    closest apples-to-apples the shape-specialized cache allows); the
    slope isolates the decode scan from prefill + sampling setup, and
    min-of-3 rejects scheduler noise."""
    prompt = jnp.asarray(
        rng.randint(1, CFG.vocab, size=(slots, BUCKETS.max_len)),
        jnp.int32)
    times = {}
    for max_new in (16, 80):
        g = Generator(model, GenerationConfig(max_new_tokens=max_new,
                                              temperature=0.0))
        g.generate(params, prompt).block_until_ready()   # compile
        reps = []
        for _ in range(3):
            t0 = time.monotonic()
            g.generate(params, prompt).block_until_ready()
            reps.append(time.monotonic() - t0)
        times[max_new] = min(reps)
    per_tok = (times[80] - times[16]) / (80 - 16)
    return slots / per_tok


def steady_state_tokens_per_sec(model, params, slots, chunk, rng,
                                ticks=20, kv="slab"):
    """Saturated continuous batching: a deep queue keeps every slot
    full across retirements (requests finish, replacements prefill in
    the same tick). Token count from the engine's own emitted-token
    counter, so prefill/retire churn is charged to the number honestly."""
    from pipe_tpu.obs.telemetry import get_registry
    gen_cfg = GenerationConfig(max_new_tokens=MAX_NEW, temperature=0.0)
    backend = SingleDeviceSlotBackend(
        model, params, num_slots=slots, max_len=MAX_LEN, gen=gen_cfg,
        buckets=BUCKETS, decode_chunk=chunk, **_backend_kv_kwargs(kv))
    n_requests = slots * (2 + chunk * ticks // MAX_NEW)
    eng = ServeEngine(backend, RequestQueue(capacity=n_requests + slots))
    for p in make_prompts(n_requests, rng):
        eng.submit(p)
    for _ in range(3):
        eng.tick()              # compile both prefill buckets + decode
    assert eng.live_slots == slots
    counter = get_registry().counter("serve.engine.tokens")
    n0 = counter.value
    t0 = time.monotonic()
    for _ in range(ticks):
        eng.tick()
    dt = time.monotonic() - t0
    assert eng.live_slots == slots      # the queue never ran dry
    return (counter.value - n0) / dt


def make_shared_prefix_prompts(n, rng, shared):
    tails = rng.choice(AB_TAILS, size=n)
    return [shared + rng.randint(1, CFG.vocab, size=int(t)).tolist()
            for t in tails]


def kv_ab_steady_state(model, params, slots, chunk, seed, *, ticks=8,
                       reps=3):
    """Steady-state decode tokens/s on the shared-prefix workload at a
    fixed row budget (``slots * MAX_LEN`` — the slab's footprint at S
    slots): slab at S slots, paged at S slots, paged at 2S slots on the
    SAME memory. The paged pool resumes prefill past the cached prefix
    (one chunk instead of a full bucket) and reserves actual block
    demand instead of max_len rows per slot, so the row budget that
    gives the slab S slots carries 2S live requests. All three engines
    are warmed through their first retirement wave, then measurement
    windows are INTERLEAVED config-by-config with best-of-reps per
    config — scheduler noise on this shared box is bursty over seconds,
    so back-to-back windows of one config would eat a burst whole."""
    from pipe_tpu.obs.telemetry import get_registry
    reg = get_registry()
    counter = reg.counter("serve.engine.tokens")
    pool_blocks = slots * (-(-AB_MAX_LEN // KV_BLOCK)) + 1
    warm = 3 + AB_MAX_NEW // chunk
    cfgs = [("slab", "slab", slots, None),
            ("paged_equal_slots", "paged", slots, pool_blocks),
            ("paged_2x_slots_same_memory", "paged", 2 * slots,
             pool_blocks)]
    hits0 = reg.counter("serve.kv.prefix_hits").value
    miss0 = reg.counter("serve.kv.prefix_misses").value
    engines = {}
    for name, kv, s, pb in cfgs:
        rng = np.random.RandomState(seed)
        gen_cfg = GenerationConfig(max_new_tokens=AB_MAX_NEW,
                                   temperature=0.0)
        backend = SingleDeviceSlotBackend(
            model, params, num_slots=s, max_len=AB_MAX_LEN, gen=gen_cfg,
            buckets=AB_BUCKETS, decode_chunk=chunk,
            **_backend_kv_kwargs(kv, pb))
        n_req = s * (3 + chunk * (reps * ticks + warm) // AB_MAX_NEW)
        eng = ServeEngine(backend, RequestQueue(capacity=n_req + s))
        shared = rng.randint(1, CFG.vocab, size=SHARED_LEN).tolist()
        for p in make_shared_prefix_prompts(n_req, rng, shared):
            eng.submit(p)
        for _ in range(warm):
            eng.tick()
        assert eng.live_slots == s, (name, eng.live_slots, s)
        engines[name] = (eng, s)
    best = {name: 0.0 for name, *_ in cfgs}
    for _ in range(reps):
        for name, kv, s, pb in cfgs:
            eng, _ = engines[name]
            n0 = counter.value
            t0 = time.monotonic()
            for _ in range(ticks):
                eng.tick()
            dt = time.monotonic() - t0
            assert eng.live_slots == s  # the queue never ran dry
            best[name] = max(best[name], (counter.value - n0) / dt)
    hits = reg.counter("serve.kv.prefix_hits").value - hits0
    miss = reg.counter("serve.kv.prefix_misses").value - miss0
    out = {}
    for name, kv, s, pb in cfgs:
        out[name] = {"kv": kv, "live_slots": s,
                     "tokens_s": round(best[name], 1)}
        if kv == "paged":
            out[name]["pool_blocks"] = pb
    out["prefix_hit_rate"] = round(hits / max(hits + miss, 1), 4)
    return out, pool_blocks


def make_multi_tenant_prompts(n, rng, base, tenant_segs):
    out = []
    for i in range(n):
        seg = tenant_segs[i % len(tenant_segs)]
        tail = rng.randint(1, CFG.vocab,
                           size=int(rng.choice(MT_TAILS))).tolist()
        out.append(base + seg + tail)
    return out


def multi_tenant_radix(model, params, slots, chunk, seed, *, n_requests):
    """Gen-2 headline: block-level radix reuse on a multi-tenant
    workload vs the gen-1 whole-prefix counterfactual, plus the TTFT it
    buys. Every request shares the fleet base; tenants diverge after it;
    tails are full random blocks — so no request's full block chain is
    ever cached and a whole-prefix cache reuses NOTHING, while the radix
    tree reuses 12 of ~13 blocks per warm admission. The same prompts
    run again with the prefix cache off to price the reuse in TTFT
    (prefill past cached blocks is skipped, so first tokens come back
    from one chunk instead of a 128-wide bucket sweep)."""
    from pipe_tpu.obs.telemetry import get_registry
    reg = get_registry()
    rng = np.random.RandomState(seed)
    base = rng.randint(1, CFG.vocab, size=MT_BASE_LEN).tolist()
    segs = [rng.randint(1, CFG.vocab, size=MT_TENANT_LEN).tolist()
            for _ in range(MT_TENANTS)]
    prompts = make_multi_tenant_prompts(n_requests, rng, base, segs)
    gen_cfg = GenerationConfig(max_new_tokens=AB_MAX_NEW, temperature=0.0)

    keys = ("prefix_hits", "prefix_misses", "prefix_whole_hits")

    def run(prefix_cache):
        cfg = (gen_cfg if prefix_cache
               else GenerationConfig(max_new_tokens=AB_MAX_NEW,
                                     temperature=0.0, prefix_cache=False))
        backend = SingleDeviceSlotBackend(
            model, params, num_slots=slots, max_len=AB_MAX_LEN, gen=cfg,
            buckets=AB_BUCKETS, decode_chunk=chunk,
            **_backend_kv_kwargs("paged"))
        eng = ServeEngine(backend,
                          RequestQueue(capacity=n_requests + 2 * slots))
        # compile every program (prefill chunks, decode, COW fork)
        # outside the TTFT window; the warm chain is invalidated so the
        # measured run starts from a cold cache either way
        warm = rng.randint(1, CFG.vocab, size=MT_BASE_LEN).tolist()
        eng.serve([warm + [5], warm], seeds=[seed, seed])
        pool = eng.backend.pool
        pool.invalidate(pool.prefix_hashes(warm))
        c0 = {k: reg.counter(f"serve.kv.{k}").value for k in keys}
        resps = eng.serve(prompts, seeds=[seed] * len(prompts))
        return resps, {k: reg.counter(f"serve.kv.{k}").value - c0[k]
                       for k in keys}

    radix_resps, d = run(True)
    radix_ttfts = sorted(r.ttft for r in radix_resps)
    off_ttfts = sorted(r.ttft for r in run(False)[0])
    looked_up = max(d["prefix_hits"] + d["prefix_misses"], 1)
    return {
        "workload": {"base_blocks": MT_BASE_LEN // KV_BLOCK,
                     "tenant_blocks": MT_TENANT_LEN // KV_BLOCK,
                     "tenants": MT_TENANTS, "tails": list(MT_TAILS),
                     "requests": n_requests},
        "radix_hit_block_fraction": round(d["prefix_hits"] / looked_up, 4),
        "whole_prefix_hit_fraction": round(
            d["prefix_whole_hits"] / looked_up, 4),
        "radix_ttft_p50_s": round(
            percentile_exact(radix_ttfts, 0.50), 4),
        "prefix_off_ttft_p50_s": round(
            percentile_exact(off_ttfts, 0.50), 4),
        "ttft_speedup_radix_vs_off": round(
            percentile_exact(off_ttfts, 0.50)
            / max(percentile_exact(radix_ttfts, 0.50), 1e-9), 3),
    }


def kv_offload_drill(model, params, seed):
    """Pressure drill: a pool too small for the working set spills cold
    blocks to host and restores them on re-reference — and the tokens
    must be BITWISE what a roomy pool produces (offload payloads are raw
    storage bytes, never requantized). Serial submissions force the
    evict-then-restore sequence deterministically."""
    from pipe_tpu.obs.telemetry import get_registry
    reg = get_registry()
    rng = np.random.RandomState(seed)
    shared = rng.randint(1, CFG.vocab, size=4 * KV_BLOCK).tolist()
    fillers = [rng.randint(1, CFG.vocab, size=6 * KV_BLOCK).tolist()
               for _ in range(2)]
    prompts = [shared + [3, 5], fillers[0], shared + [7, 9],
               fillers[1], shared + [11]]
    gen_cfg = GenerationConfig(max_new_tokens=16, temperature=0.0)

    def run(pool_blocks, offload):
        backend = SingleDeviceSlotBackend(
            model, params, num_slots=2, max_len=80, gen=gen_cfg,
            kv_block_size=KV_BLOCK, kv_pool_blocks=pool_blocks,
            prefill_chunk=16, kv_offload=offload)
        eng = ServeEngine(backend)
        toks = []
        for p in prompts:
            rid = eng.submit(p, seed=seed).id
            eng.run_until_idle()
            toks.append(np.asarray(eng.response(rid).tokens))
        return toks

    want = run(64, False)                 # roomy: nothing ever spills
    keys = ("offload_out", "offload_restores", "offload_bytes",
            "evictions")
    c0 = {k: reg.counter(f"serve.kv.{k}").value for k in keys}
    got = run(11, True)                   # tight: spill + restore
    d = {k: reg.counter(f"serve.kv.{k}").value - c0[k] for k in keys}
    bitwise = all(np.array_equal(a, b) for a, b in zip(got, want))
    return {"pool_blocks": 11, "requests": len(prompts),
            "blocks_offloaded": d["offload_out"],
            "blocks_restored": d["offload_restores"],
            "offload_bytes": d["offload_bytes"],
            "evictions": d["evictions"],
            "bitwise_equal_to_unpressured": bool(bitwise)}


RES_HORIZON = 8


def resident_steady_state(model, params, slots, seed, *, resident,
                          rounds, reps=2):
    """Steady-state tokens/s + host-overhead-per-token for one engine
    at ``slots`` live slots, decode_chunk=1. ``rounds`` counts resident
    launches; the non-resident engine runs ``rounds * RES_HORIZON``
    single-chunk ticks so both cover the same token volume. Best of
    ``reps`` measurement windows (tokens/s max, overhead min — both
    reject scheduler noise in the same direction)."""
    from pipe_tpu.obs.telemetry import get_registry
    reg = get_registry()
    tok_c = reg.counter("serve.engine.tokens")
    host_t = reg.timer("serve.engine.host_sec")
    sync_c = reg.counter("serve.engine.host_syncs")
    gen_cfg = GenerationConfig(max_new_tokens=MAX_NEW, temperature=0.0)
    kw = (dict(resident=True, resident_chunks=RES_HORIZON)
          if resident else dict(resident=False))
    backend = SingleDeviceSlotBackend(
        model, params, num_slots=slots, max_len=MAX_LEN, gen=gen_cfg,
        buckets=BUCKETS, decode_chunk=1, **kw)
    ticks = rounds if resident else rounds * RES_HORIZON
    warm = 3 if resident else 3 * RES_HORIZON
    per_slot = (warm + reps * ticks) * (RES_HORIZON if resident else 1)
    n_req = slots * (4 + 2 * per_slot // MAX_NEW)
    rng = np.random.RandomState(seed)
    eng = ServeEngine(backend, RequestQueue(capacity=n_req + slots))
    for p in make_prompts(n_req, rng):
        eng.submit(p)
    for _ in range(warm):
        eng.tick()
    assert eng.live_slots == slots
    best_tps, best_oh, syncs_per_tok = 0.0, float("inf"), 0.0
    for _ in range(reps):
        n0, h0, s0 = tok_c.value, host_t.total, sync_c.value
        t0 = time.monotonic()
        for _ in range(ticks):
            eng.tick()
        dt = time.monotonic() - t0
        assert eng.live_slots == slots      # the queue never ran dry
        n = tok_c.value - n0
        best_tps = max(best_tps, n / dt)
        best_oh = min(best_oh, (host_t.total - h0) / max(n, 1))
        syncs_per_tok = (sync_c.value - s0) / max(n, 1)
    return {"tokens_s": round(best_tps, 1),
            "host_overhead_per_token_us": round(best_oh * 1e6, 2),
            "host_syncs_per_token": round(syncs_per_tok, 4),
            "live_slots": slots}


def resident_ab(model, params, slots, seed, *, rounds, reps=2):
    """The PR 11 A/B: launches of one chunk (``resident=False``) vs
    launches of ``RES_HORIZON`` chunks of the same program, at EQUAL
    live slots and equal token volume. The longer horizon's job is the
    host-overhead-per-token column; the tokens/s column is the
    no-regression bar."""
    non = resident_steady_state(model, params, slots, seed,
                                resident=False, rounds=rounds, reps=reps)
    res = resident_steady_state(model, params, slots, seed,
                                resident=True, rounds=rounds, reps=reps)
    return {
        "horizon_chunks": RES_HORIZON,
        "decode_chunk": 1,
        "nonresident": non,
        "resident": res,
        "resident_vs_nonresident_tokens_s": round(
            res["tokens_s"] / max(non["tokens_s"], 1e-9), 4),
        "host_overhead_reduction": round(
            non["host_overhead_per_token_us"]
            / max(res["host_overhead_per_token_us"], 1e-9), 2),
    }


def spec_acceptance(model, params, seed, *, n_prompts=4, max_new=32,
                    spec_tokens=3):
    """Speculative lane on draftable (repetitive) prompts: bitwise
    parity vs the per-prompt Generator, acceptance rate from the
    engine's own round/emission counters."""
    from pipe_tpu.obs.telemetry import get_registry
    reg = get_registry()
    rng = np.random.RandomState(seed)
    prompts = []
    for _ in range(n_prompts):
        pair = rng.randint(1, CFG.vocab, size=2).tolist()
        prompts.append(pair * 4)
    gen_cfg = GenerationConfig(max_new_tokens=max_new, temperature=0.0)
    g = Generator(model, gen_cfg)
    refs = [np.asarray(g.generate(
        params, jnp.asarray(p, jnp.int32)[None],
        jax.random.key(seed + i)))[0] for i, p in enumerate(prompts)]
    backend = SingleDeviceSlotBackend(
        model, params, num_slots=2, max_len=MAX_LEN, gen=gen_cfg,
        buckets=BUCKETS, resident=True, resident_chunks=RES_HORIZON,
        spec_tokens=spec_tokens)
    rounds0 = reg.counter("serve.engine.spec_rounds").value
    emitted0 = reg.counter("serve.engine.spec_emitted").value
    eng = ServeEngine(backend)
    resps = eng.serve(prompts,
                      seeds=[seed + i for i in range(n_prompts)])
    equal = all(
        np.array_equal(np.asarray(r.tokens), ref)
        for r, ref in zip(resps, refs))
    rounds = reg.counter("serve.engine.spec_rounds").value - rounds0
    emitted = reg.counter("serve.engine.spec_emitted").value - emitted0
    return {
        "spec_tokens": spec_tokens,
        "prompts": n_prompts,
        "max_new_tokens": max_new,
        "bitwise_equal_to_generator": bool(equal),
        "verify_rounds": int(rounds),
        "tokens_emitted": int(emitted),
        "tokens_per_round": round(emitted / max(rounds, 1), 3),
        # accepted drafts per offered draft (K-1 offered per round)
        "acceptance_rate": round(
            (emitted - rounds) / max(rounds * (spec_tokens - 1), 1), 4),
    }


SPEC_K = 4          # draft depth: 1 committed + K-1 offered per round
SPEC_STAGES = 4     # logical stages of the spec bench model (1-layer draft prefix)
SPEC_MAX_NEW = 32
_SPEC_EPS = 0.01


def _spec_bench_params(params, eps=_SPEC_EPS):
    """Derived weights for the gen-2 spec section. Two surgeries, both
    argmax-preserving for the FULL model:

    * the decoder is tied to the embedding table (``w = table.T``,
      ``b = 0``) — the same matrix the truncated/tree draft head
      scores tokens with;
    * every block's residual branch (attention out-projection, ffn
      second matmul) is scaled by ``eps`` — each post-LN block then
      nearly rescales its (already layer-normed) input instead of
      rotating it, so the hidden the stage-0 draft head reads already
      points at the argmax the full-depth verify head picks.

    Acceptance becomes a property of the DRAFT SOURCE rather than of
    prompt repetition: the model-based drafts track verify
    near-perfectly, while the next-token map stays position-driven
    (embedding + positional code) — an n-gram history lookup only
    scores where the emitted stream happens to revisit old contexts,
    a fraction of what the model drafts accept.
    """
    stages, pre, post = params
    out_stages = []
    for stage in stages:
        out_stage = []
        for bp in stage:
            bp = {k: dict(v) for k, v in bp.items()}
            bp["attn"]["wo"] = bp["attn"]["wo"] * eps
            bp["attn"]["bo"] = bp["attn"]["bo"] * eps
            bp["ff2"]["w"] = bp["ff2"]["w"] * eps
            bp["ff2"]["b"] = bp["ff2"]["b"] * eps
            out_stage.append(bp)
        out_stages.append(out_stage)
    table = pre["embed"]["table"]
    post = {"decoder": {
        "w": table.T.astype(post["decoder"]["w"].dtype),
        "b": jnp.zeros_like(post["decoder"]["b"])}}
    return out_stages, pre, post


def _spec_drive(model, params, prompts, seed, *, draft, branches=None):
    """Serve ``prompts`` through one draft source; acceptance from the
    engine's round/emission counters, parity vs the per-prompt
    Generator."""
    from pipe_tpu.obs.telemetry import get_registry
    reg = get_registry()
    gen_cfg = GenerationConfig(max_new_tokens=SPEC_MAX_NEW,
                               temperature=0.0)
    g = Generator(model, gen_cfg)
    refs = [np.asarray(g.generate(
        params, jnp.asarray(p, jnp.int32)[None],
        jax.random.key(seed + i)))[0] for i, p in enumerate(prompts)]
    pad = (branches or 1) * (SPEC_K - 1)    # rollback overwrite room
    backend = SingleDeviceSlotBackend(
        model, params, num_slots=2, max_len=MAX_LEN + pad, gen=gen_cfg,
        buckets=BUCKETS, resident=True, resident_chunks=RES_HORIZON,
        spec_tokens=SPEC_K, draft=draft, spec_branches=branches)
    r0 = reg.counter("serve.engine.spec_rounds").value
    e0 = reg.counter("serve.engine.spec_emitted").value
    eng = ServeEngine(backend)
    resps = eng.serve(prompts,
                      seeds=[seed + i for i in range(len(prompts))])
    equal = all(np.array_equal(np.asarray(r.tokens), ref)
                for r, ref in zip(resps, refs))
    rounds = reg.counter("serve.engine.spec_rounds").value - r0
    emitted = reg.counter("serve.engine.spec_emitted").value - e0
    out = {"bitwise_equal_to_generator": bool(equal),
           "verify_rounds": int(rounds),
           "tokens_per_round": round(emitted / max(rounds, 1), 3),
           "acceptance_rate": round(
               (emitted - rounds) / max(rounds * (SPEC_K - 1), 1), 4),
           "draft_cost_frac": round(float(
               reg.gauge("serve.spec.draft_cost_frac").value), 4)}
    if branches:
        out["branches"] = branches
    return out


def _spec_steady(model, params, slots, seed, *, spec_kw, max_len,
                 ticks, reps):
    """Steady-state (tokens/s, spec-rounds/s) for one resident engine —
    ``resident_steady_state``'s measurement loop with the spec lane's
    knobs threaded through and the round counter sampled alongside the
    token counter (the round rate is what prices the verify chunk)."""
    from pipe_tpu.obs.telemetry import get_registry
    reg = get_registry()
    tok_c = reg.counter("serve.engine.tokens")
    rnd_c = reg.counter("serve.engine.spec_rounds")
    gen_cfg = GenerationConfig(max_new_tokens=MAX_NEW, temperature=0.0)
    backend = SingleDeviceSlotBackend(
        model, params, num_slots=slots, max_len=max_len, gen=gen_cfg,
        buckets=BUCKETS, decode_chunk=1, resident=True,
        resident_chunks=RES_HORIZON, **spec_kw)
    k = spec_kw.get("spec_tokens") or 1
    per_slot = (3 + reps * ticks) * RES_HORIZON * k
    n_req = slots * (4 + 2 * per_slot // MAX_NEW)
    rng = np.random.RandomState(seed)
    eng = ServeEngine(backend, RequestQueue(capacity=n_req + slots))
    for p in make_prompts(n_req, rng):
        eng.submit(p)
    for _ in range(3):
        eng.tick()
    trc_c = reg.counter("serve.engine.resident_traces")
    trc0 = trc_c.value                      # warm compiled everything
    best_tps, best_rps = 0.0, 0.0
    for _ in range(reps):
        n0, r0 = tok_c.value, rnd_c.value
        t0 = time.monotonic()
        for _ in range(ticks):
            eng.tick()
        dt = time.monotonic() - t0
        # With acceptance ~1 a request retires every SECOND launch, so
        # unlike the nonspec sections a window end can land on the
        # retire tick itself (live drops until the next tick's
        # admission). The occupancy invariant that matters for the A/B
        # is that admission always had work waiting: the queue never
        # ran dry inside the window.
        assert len(eng.queue) > 0
        best_tps = max(best_tps, (tok_c.value - n0) / dt)
        best_rps = max(best_rps, (rnd_c.value - r0) / dt)
    return best_tps, best_rps, trc_c.value - trc0


# The ring needs >= 2 devices and this process already initialized the
# single-device backend, so the drill re-inits jax on the 2-virtual-chip
# CPU platform in a child interpreter (the conftest trick).
_RING_DRILL_SRC = r"""
import json, os, sys

sys.path.insert(0, os.environ["PIPE_TPU_ROOT"])
sys.path.insert(0, os.path.join(os.environ["PIPE_TPU_ROOT"], "tools"))
from pipe_tpu.utils.platform import force_cpu_platform
force_cpu_platform(num_devices=2)   # before backend init

import jax
import jax.numpy as jnp
import numpy as np

import serve_bench as sb
from pipe_tpu.inference import GenerationConfig, Generator
from pipe_tpu.obs.telemetry import get_registry
from pipe_tpu.parallel.mesh import make_mesh
from pipe_tpu.parallel.spmd import stack_stage_params
from pipe_tpu.serve import RingSlotBackend, ServeEngine

seed = int(sys.argv[1])
model = sb.PipelinedLM(sb.CFG, 2)          # one stage per ring chip
sp, pre, post = sb._spec_bench_params(model.init(jax.random.key(1)))
stacked = stack_stage_params(sp)
rng = np.random.RandomState(seed)
prompts = sb.make_prompts(3, rng)
reg = get_registry()


def drive(backend):
    # staggered arrivals: slot churn exercises relaunches + the
    # stale-round discard, not one clean batch
    eng = ServeEngine(backend)
    ids = [eng.submit(prompts[0], seed=seed).id]
    eng.tick()
    ids += [eng.submit(p, seed=seed).id for p in prompts[1:]]
    eng.run_until_idle()
    return [list(eng.response(i).tokens) for i in ids]


out = {"spec_tokens": sb.SPEC_K, "draft": "truncated",
       "prompts": len(prompts)}
for name, temp in (("greedy", 0.0), ("sampled", 0.8)):
    gen_cfg = GenerationConfig(max_new_tokens=16, temperature=temp,
                               top_k=12 if temp else None)
    g = Generator(model, gen_cfg)
    refs = [np.asarray(g.generate((sp, pre, post),
                                  jnp.asarray(p, jnp.int32)[None],
                                  jax.random.key(seed)))[0]
            for p in prompts]
    backend = RingSlotBackend(
        make_mesh(2, 1), model, stacked, pre, post,
        max_len=96 + sb.SPEC_K, gen=gen_cfg, buckets=sb.BUCKETS,
        resident=True, resident_revolutions=4,
        spec_tokens=sb.SPEC_K, draft="truncated")
    t0 = reg.counter("serve.ring.resident_traces").value
    r0 = reg.counter("serve.engine.spec_rounds").value
    e0 = reg.counter("serve.engine.spec_emitted").value
    got = drive(backend)
    warm = reg.counter("serve.ring.resident_traces").value - t0
    rounds = reg.counter("serve.engine.spec_rounds").value - r0
    emitted = reg.counter("serve.engine.spec_emitted").value - e0
    got2 = drive(backend)      # warm steady state: same traffic again
    out[name] = {
        "bitwise_equal_to_generator": bool(
            all(np.array_equal(np.asarray(a), r)
                for a, r in zip(got, refs)) and got2 == got),
        "verify_rounds": int(rounds),
        "acceptance_rate": round(
            (emitted - rounds) / max(rounds * (sb.SPEC_K - 1), 1), 4),
        "warm_traces": int(warm),
        "steady_state_new_traces": int(
            reg.counter("serve.ring.resident_traces").value - t0 - warm),
    }
print("RING_DRILL " + json.dumps(out))
"""


def _ring_spec_drill(seed):
    """Ring-backend spec on the same tied-head weights: truncated
    drafts ride the split-key chain through the revolutions, greedy AND
    sampled output stays bitwise the Generator, and re-serving the same
    traffic shape traces zero new ring programs."""
    import subprocess
    env = dict(os.environ,
               PIPE_TPU_ROOT=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))),
               JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", _RING_DRILL_SRC,
                           str(seed)], capture_output=True, text=True,
                          timeout=1800, env=env)
    for line in proc.stdout.splitlines():
        if line.startswith("RING_DRILL "):
            return json.loads(line[len("RING_DRILL "):])
    raise RuntimeError(f"ring spec drill produced no result "
                       f"(rc={proc.returncode}):\n"
                       f"{proc.stdout[-2000:]}{proc.stderr[-2000:]}")


def spec_gen2(slots, seed, *, quick):
    """Gen-2 speculative section: draft-source acceptance shoot-out +
    the measured-breakeven closed loop, on the tied-head bench weights
    (aperiodic prompts, greedy — every number is also a parity pin)."""
    from pipe_tpu.core.planner import (spec_breakeven_acceptance,
                                       spec_speedup)
    model = PipelinedLM(CFG, SPEC_STAGES)
    params = _spec_bench_params(model.init(jax.random.key(1)))
    rng = np.random.RandomState(seed)
    prompts = make_prompts(3 if quick else 4, rng)
    sources = [("ngram", None), ("truncated", None)]
    if not quick:
        sources.append(("tree", 3))
    per_source = {}
    for draft, branches in sources:
        log(f"  draft={draft}...")
        per_source[draft] = _spec_drive(model, params, prompts,
                                        seed, draft=draft,
                                        branches=branches)

    # Spec vs non-spec resident loop at EQUAL live slots, same
    # weights, same prompt mix. The verify-chunk cost ratio is
    # MEASURED, not assumed: non-spec emits one token per chunk step
    # (R1 = tokens/s), spec runs one K-row verify chunk per round
    # (R2 = rounds/s), and the round buys its draft on top — so
    # r = (R1/R2) * (1 - f). Feeding r back through the planner
    # closes the loop: the artifact records the breakeven acceptance
    # this host actually imposes next to the acceptance and speedup
    # it actually measured.
    ticks = 3 if quick else 8
    reps = 2 if quick else 3
    non_tps, _, _ = _spec_steady(model, params, slots, seed + 1,
                                 spec_kw={}, max_len=MAX_LEN,
                                 ticks=ticks, reps=reps)
    spec_tps, spec_rps, spec_traces = _spec_steady(
        model, params, slots, seed + 1,
        spec_kw=dict(spec_tokens=SPEC_K, draft="truncated"),
        max_len=MAX_LEN + SPEC_K - 1, ticks=ticks, reps=reps)
    f = per_source["truncated"]["draft_cost_frac"]
    a = per_source["truncated"]["acceptance_rate"]
    r = (non_tps / max(spec_rps, 1e-9)) * (1.0 - f)
    out_ring = None
    if not quick:
        log("  ring spec drill (subprocess, 2 virtual chips)...")
        out_ring = _ring_spec_drill(seed + 2)
    return {
        "spec_tokens": SPEC_K,
        "model_stages": SPEC_STAGES,
        "draft_stages": 1,
        "max_new_tokens": SPEC_MAX_NEW,
        "prompts": len(prompts),
        "draft_sources": per_source,
        "throughput": {
            "live_slots": slots,
            "nonspec_tokens_s": round(non_tps, 1),
            "spec_tokens_s": round(spec_tps, 1),
            "spec_vs_nonspec_tokens_s": round(
                spec_tps / max(non_tps, 1e-9), 4),
            "spec_rounds_s": round(spec_rps, 1),
            "acceptance": a,
            "draft_cost_frac": f,
            "chunk_cost_ratio_measured": round(r, 4),
            "breakeven_acceptance": round(
                spec_breakeven_acceptance(f, SPEC_K, r), 4),
            "predicted_speedup": round(
                spec_speedup(a, f, SPEC_K, r), 4),
            # measured-window recompiles of the spec resident program
            # (fixed K, no adaptive ladder in play -> must be zero)
            "steady_state_new_traces": int(spec_traces),
        },
        **({"ring": out_ring} if out_ring else {}),
    }


def drive_poisson(eng, prompts, arrivals, *, max_new, deadline_s):
    """Feed the engine a precomputed arrival schedule against the wall
    clock; tick until drained. Returns (responses, elapsed, rejected)."""
    t0 = time.monotonic()
    i, rejected, finished, peak_live = 0, 0, [], 0
    while i < len(arrivals) or not eng.idle:
        now = time.monotonic() - t0
        while i < len(arrivals) and arrivals[i] <= now:
            try:
                eng.submit(prompts[i], seed=i, max_new_tokens=max_new,
                           timeout_s=deadline_s)
            except QueueFull:
                rejected += 1
            i += 1
        if eng.idle and i < len(arrivals):
            time.sleep(min(arrivals[i] - now, 0.002))
            continue
        finished.extend(eng.tick())
        peak_live = max(peak_live, eng.live_slots)
    return finished, time.monotonic() - t0, rejected, peak_live


def load_run(model, params, slots, chunk, rng, *, n_requests, rate,
             max_new, deadline_s, capacity, kv="slab", pool_blocks=None,
             prompts=None, max_len=MAX_LEN, buckets=BUCKETS):
    gen_cfg = GenerationConfig(max_new_tokens=max_new, temperature=0.0)
    backend = SingleDeviceSlotBackend(
        model, params, num_slots=slots, max_len=max_len, gen=gen_cfg,
        buckets=buckets, decode_chunk=chunk,
        **_backend_kv_kwargs(kv, pool_blocks))
    eng = ServeEngine(backend, RequestQueue(capacity=capacity))
    # warm every program before the clock matters
    for p in ([1] * 20, [1] * 40):
        eng.submit(p, max_new_tokens=1)
    eng.run_until_idle()

    if prompts is None:
        prompts = make_prompts(n_requests, rng)
    arrivals = np.cumsum(rng.exponential(1.0 / rate, size=n_requests))
    from pipe_tpu.obs.telemetry import get_registry
    reg = get_registry()
    hits0 = reg.counter("serve.kv.prefix_hits").value
    miss0 = reg.counter("serve.kv.prefix_misses").value
    blocked0 = reg.counter("serve.kv.admission_blocked").value
    finished, elapsed, rejected, peak_live = drive_poisson(
        eng, prompts, arrivals, max_new=max_new, deadline_s=deadline_s)
    ok = [r for r in finished if r.status == "ok"]
    ttfts = sorted(r.ttft for r in ok)
    kv_stats = {}
    if kv == "paged":
        hits = reg.counter("serve.kv.prefix_hits").value - hits0
        miss = reg.counter("serve.kv.prefix_misses").value - miss0
        kv_stats = {
            "prefix_hit_rate": round(hits / max(hits + miss, 1), 4),
            "admission_blocked":
                reg.counter("serve.kv.admission_blocked").value - blocked0,
        }
    return {
        "requests": n_requests,
        "offered_rate_req_s": round(rate, 3),
        "elapsed_s": round(elapsed, 3),
        "ok": len(ok),
        "timeout": sum(r.status == "timeout" for r in finished),
        "cancelled": sum(r.status == "cancelled" for r in finished),
        "rejected": rejected,
        "goodput_tokens_s": round(
            sum(len(r.tokens) for r in ok) / elapsed, 1),
        "ttft_p50_s": round(percentile_exact(ttfts, 0.50), 4),
        "ttft_p99_s": round(percentile_exact(ttfts, 0.99), 4),
        "peak_live_slots": peak_live,
        **kv_stats,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="small run; single-line JSON summary")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=8,
                    help="decode_chunk: tokens per host round-trip")
    ap.add_argument("--kv", choices=("slab", "paged"), default="slab",
                    help="KV memory for the steady-state/latency "
                         "sections (the kv A/B section always runs both)")
    ap.add_argument("--resident", action="store_true",
                    help="full-size resident A/B + speculative-decode "
                         "section (quick mode always runs a small "
                         "resident A/B for the CI embed)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    rng = np.random.RandomState(args.seed)
    model = PipelinedLM(CFG, 1)
    params = model.init(jax.random.key(0))
    slots, chunk = args.slots, args.chunk

    log("baseline: fixed-batch Generator decode slope...")
    base_tps = baseline_tokens_per_sec(model, params, slots, rng)
    log(f"  {base_tps:.1f} tokens/s at batch={slots}")

    log(f"steady state: engine with every slot full (kv={args.kv})...")
    ticks = 8 if args.quick else 24
    serve_tps = steady_state_tokens_per_sec(model, params, slots, chunk,
                                            rng, ticks=ticks, kv=args.kv)
    ratio = serve_tps / base_tps
    log(f"  {serve_tps:.1f} tokens/s ({ratio:.3f}x fixed-batch)")

    # KV A/B on the shared-prefix workload at a FIXED row budget
    # (slots * AB_MAX_LEN rows == the slab's footprint at S slots): slab
    # at S slots, paged at S slots (the parity bar: paged must not lose
    # at equal concurrency), paged at 2S slots on the SAME memory — the
    # headline the pool buys. 2S only fits because the prefix blocks are
    # shared: 8 live requests need 14 shared + 8x5 private = 54 blocks
    # of the 76 allocatable, where private slabs would need 152.
    log("kv A/B: shared-prefix workload, slab vs paged...")
    ab, pool_blocks = kv_ab_steady_state(
        model, params, slots, chunk, args.seed + 2,
        ticks=8 if args.quick else 12, reps=3 if args.quick else 5)
    kv_slab = ab["slab"]
    kv_paged = ab["paged_equal_slots"]
    kv_paged_2x = ab["paged_2x_slots_same_memory"]
    kv_ab = {
        "workload": {"shared_prefix": SHARED_LEN,
                     "tails": list(AB_TAILS),
                     "max_new_tokens": AB_MAX_NEW,
                     "max_len": AB_MAX_LEN},
        "kv_memory_rows": slots * AB_MAX_LEN,
        "slab": kv_slab,
        "paged_equal_slots": kv_paged,
        "paged_2x_slots_same_memory": kv_paged_2x,
        "prefix_hit_rate": ab["prefix_hit_rate"],
        "paged_vs_slab_equal_slots": round(
            kv_paged["tokens_s"] / kv_slab["tokens_s"], 4),
        "paged_2x_vs_slab": round(
            kv_paged_2x["tokens_s"] / kv_slab["tokens_s"], 4),
        "live_slot_gain_same_memory": round(
            kv_paged_2x["live_slots"] / kv_slab["live_slots"], 2),
    }
    log(f"  slab {kv_slab['tokens_s']:.1f} tok/s @ {slots} slots; paged "
        f"{kv_paged['tokens_s']:.1f} tok/s @ {slots} slots "
        f"({kv_ab['paged_vs_slab_equal_slots']:.3f}x); paged "
        f"{kv_paged_2x['tokens_s']:.1f} tok/s @ {2 * slots} slots on the "
        f"same memory (hit rate {ab['prefix_hit_rate']:.3f})")

    # Gen-2 radix headline: multi-tenant reuse a whole-prefix cache
    # can't see, and the TTFT the skipped prefill buys.
    log("kv radix: multi-tenant workload vs whole-prefix "
        "counterfactual...")
    radix = multi_tenant_radix(model, params, slots, chunk,
                               args.seed + 6,
                               n_requests=12 if args.quick else 36)
    log(f"  radix hit fraction {radix['radix_hit_block_fraction']:.3f} "
        f"vs whole-prefix {radix['whole_prefix_hit_fraction']:.3f}; "
        f"ttft p50 {radix['radix_ttft_p50_s']:.4f}s vs "
        f"{radix['prefix_off_ttft_p50_s']:.4f}s cache-off "
        f"({radix['ttft_speedup_radix_vs_off']:.2f}x)")

    log("kv offload: evict-to-host + restore drill...")
    offload = kv_offload_drill(model, params, args.seed + 7)
    log(f"  spilled {offload['blocks_offloaded']} restored "
        f"{offload['blocks_restored']} blocks, bitwise="
        f"{offload['bitwise_equal_to_unpressured']}")

    # Resident loop A/B at equal live slots and equal token volume:
    # host-overhead-per-token is the number the fused loop exists to
    # shrink; tokens/s is the no-regression bar. Forced on explicitly —
    # "auto" keeps cpu on the single-chunk path, so this measures the
    # mechanism the accelerator default gets.
    log("resident A/B: single-chunk ticks vs the fused device loop...")
    res_ab = resident_ab(model, params, slots, args.seed + 4,
                         rounds=4 if args.quick else 10,
                         reps=2 if args.quick else 3)
    log(f"  non-resident {res_ab['nonresident']['tokens_s']:.1f} tok/s @ "
        f"{res_ab['nonresident']['host_overhead_per_token_us']:.1f} "
        f"us/tok host; resident {res_ab['resident']['tokens_s']:.1f} "
        f"tok/s @ {res_ab['resident']['host_overhead_per_token_us']:.1f} "
        f"us/tok ({res_ab['host_overhead_reduction']:.1f}x less host, "
        f"{res_ab['resident_vs_nonresident_tokens_s']:.3f}x tokens/s)")

    # Gen-2 speculative: draft-source shoot-out + measured breakeven
    # on the tied-head weights (both modes — bench.py gates the quick
    # fields; the full run is the SERVE_r18 record).
    log("spec gen-2: draft sources on tied-head weights...")
    spec2 = spec_gen2(slots, args.seed + 8, quick=args.quick)
    sp_src, sp_thr = spec2["draft_sources"], spec2["throughput"]
    log(f"  acceptance ngram {sp_src['ngram']['acceptance_rate']:.3f} "
        f"vs truncated {sp_src['truncated']['acceptance_rate']:.3f}"
        + (f" vs tree {sp_src['tree']['acceptance_rate']:.3f}"
           if "tree" in sp_src else "")
        + f"; spec {sp_thr['spec_vs_nonspec_tokens_s']:.3f}x non-spec "
        f"(breakeven a*={sp_thr['breakeven_acceptance']:.3f}, "
        f"predicted {sp_thr['predicted_speedup']:.3f}x)")

    # capacity in requests/s at the bench's request size
    max_new = MAX_NEW
    cap_req_s = serve_tps / max_new

    log("poisson @ 0.7x capacity...")
    n = 12 if args.quick else 48
    moderate = load_run(model, params, slots, chunk, rng,
                        n_requests=n, rate=0.7 * cap_req_s,
                        max_new=max_new, deadline_s=30.0,
                        capacity=4 * slots, kv=args.kv)

    host = host_contention()
    summary = {
        "bench": "serve_bench",
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        **host,
        "slots": slots,
        "decode_chunk": chunk,
        "kv": args.kv,
        "buckets": list(BUCKETS.lengths),
        "max_new_tokens": max_new,
        "baseline_fixed_batch_tokens_s": round(base_tps, 1),
        "steady_state_tokens_s": round(serve_tps, 1),
        "serve_vs_fixed_batch": round(ratio, 4),
        "kv_ab": kv_ab,
        "kv_radix_multi_tenant": radix,
        "kv_offload_drill": offload,
        "resident_ab": res_ab,
        "speculative_gen2": spec2,
        "poisson_0p7": moderate,
    }
    if args.quick:
        print(json.dumps({
            "steady_state_tokens_s": summary["steady_state_tokens_s"],
            "serve_vs_fixed_batch": summary["serve_vs_fixed_batch"],
            "ttft_p50_s": moderate["ttft_p50_s"],
            "ttft_p99_s": moderate["ttft_p99_s"],
            "goodput_tokens_s": moderate["goodput_tokens_s"],
            "kv_paged_vs_slab_equal_slots":
                kv_ab["paged_vs_slab_equal_slots"],
            "kv_paged_2x_vs_slab": kv_ab["paged_2x_vs_slab"],
            "kv_live_slot_gain": kv_ab["live_slot_gain_same_memory"],
            "kv_prefix_hit_rate": kv_ab["prefix_hit_rate"],
            "kv_radix_hit_block_fraction":
                radix["radix_hit_block_fraction"],
            "kv_whole_prefix_hit_fraction":
                radix["whole_prefix_hit_fraction"],
            "kv_ttft_speedup_radix_vs_off":
                radix["ttft_speedup_radix_vs_off"],
            "kv_offload_bitwise":
                offload["bitwise_equal_to_unpressured"],
            "kv_offload_restores": offload["blocks_restored"],
            "resident_vs_nonresident_tokens_s":
                res_ab["resident_vs_nonresident_tokens_s"],
            "host_overhead_reduction":
                res_ab["host_overhead_reduction"],
            "spec_bitwise": all(
                s["bitwise_equal_to_generator"]
                for s in sp_src.values()),
            "spec_acceptance_ngram": sp_src["ngram"]["acceptance_rate"],
            "spec_acceptance_truncated":
                sp_src["truncated"]["acceptance_rate"],
            "spec_vs_nonspec_tokens_s":
                sp_thr["spec_vs_nonspec_tokens_s"],
            "spec_breakeven_acceptance":
                sp_thr["breakeven_acceptance"],
            "spec_steady_new_traces":
                sp_thr["steady_state_new_traces"],
            "contended": host["contended"],
        }))
        return

    if args.resident:
        log("speculative decode: draft/verify on repetitive prompts...")
        spec = spec_acceptance(model, params, args.seed + 5)
        summary["speculative"] = spec
        log(f"  bitwise={spec['bitwise_equal_to_generator']} "
            f"acceptance={spec['acceptance_rate']:.3f} "
            f"({spec['tokens_per_round']:.2f} tokens/verify-round)")

    # 2x overload: backpressure bounds the queue so the engine only
    # accepts what it can finish inside the deadline; without it the
    # queue absorbs everything and requests expire waiting (reaped before
    # prefill) or mid-decode (slot-steps burnt for zero goodput).
    # Deadline sized so a bounded queue's wait (<= capacity/service
    # rate) fits comfortably but an unbounded queue's does not — the
    # regime where shedding at the door beats accepting work that will
    # die waiting or burn slot-steps before timing out mid-decode.
    log("overload 2x, backpressure ON (bounded queue)...")
    n_over = 96
    deadline = 1.0
    on = load_run(model, params, slots, chunk,
                  np.random.RandomState(args.seed + 1),
                  n_requests=n_over, rate=2.0 * cap_req_s,
                  max_new=max_new, deadline_s=deadline,
                  capacity=2 * slots)
    log("overload 2x, backpressure OFF (unbounded queue)...")
    off = load_run(model, params, slots, chunk,
                   np.random.RandomState(args.seed + 1),
                   n_requests=n_over, rate=2.0 * cap_req_s,
                   max_new=max_new, deadline_s=deadline,
                   capacity=100000)
    summary["overload_2x"] = {
        "deadline_s": deadline,
        "backpressure_on": on,
        "backpressure_off": off,
        "goodput_ratio_on_vs_off": round(
            on["goodput_tokens_s"] / max(off["goodput_tokens_s"], 1e-9),
            3),
    }

    # Shared-prefix Poisson A/B: identical prompts and arrival schedule
    # (common 112-token system prompt, Poisson arrivals at 0.55x the
    # paged-2S engine's measured steady-state capacity) against slab-S
    # and paged-2S engines on the SAME KV row budget. The admission gain
    # is structural and shows up directly: the paged run carries up to
    # 2S concurrent requests (peak_live_slots) on memory that caps the
    # slab at S, with every admission past the first a prefix-cache hit
    # and zero pool-admission blocks — at goodput parity. (On this
    # host-bound micro-model the extra concurrency buys headroom, not
    # extra tokens/s; the steady-state A/B above prices the throughput.)
    log("kv poisson: shared-prefix load, slab S vs paged 2S...")
    sh_rng = np.random.RandomState(args.seed + 3)
    shared = sh_rng.randint(1, CFG.vocab, size=SHARED_LEN).tolist()
    n_kv = 96
    kv_prompts = make_shared_prefix_prompts(n_kv, sh_rng, shared)
    kv_rate = 0.55 * kv_paged_2x["tokens_s"] / AB_MAX_NEW
    kv_slab_load = load_run(model, params, slots, chunk,
                            np.random.RandomState(args.seed + 3),
                            n_requests=n_kv, rate=kv_rate,
                            max_new=AB_MAX_NEW, deadline_s=30.0,
                            capacity=12 * slots, prompts=kv_prompts,
                            max_len=AB_MAX_LEN, buckets=AB_BUCKETS)
    kv_paged_load = load_run(model, params, 2 * slots, chunk,
                             np.random.RandomState(args.seed + 3),
                             n_requests=n_kv, rate=kv_rate,
                             max_new=AB_MAX_NEW, deadline_s=30.0,
                             capacity=12 * slots, kv="paged",
                             pool_blocks=pool_blocks, prompts=kv_prompts,
                             max_len=AB_MAX_LEN, buckets=AB_BUCKETS)
    summary["kv_poisson_shared_prefix"] = {
        "offered_rate_req_s": round(kv_rate, 3),
        "kv_memory_rows": slots * AB_MAX_LEN,
        "slab": kv_slab_load,
        "paged_2x_slots_same_memory": kv_paged_load,
        "goodput_ratio_paged_vs_slab": round(
            kv_paged_load["goodput_tokens_s"]
            / max(kv_slab_load["goodput_tokens_s"], 1e-9), 3),
        "live_slot_gain_same_memory": round(
            kv_paged_load["peak_live_slots"]
            / max(kv_slab_load["peak_live_slots"], 1), 2),
    }
    print(json.dumps(summary, indent=2))


if __name__ == "__main__":
    main()
