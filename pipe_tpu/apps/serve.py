"""Serving driver: the continuous-batching engine under a live workload.

Runs :class:`~pipe_tpu.serve.ServeEngine` over either slot backend —
``--stages 1`` (default) is the single-device backend with ``--slots``
decode slots; ``--stages N`` keeps the weights stage-sharded and serves
through the pipeline ring (slots == ring groups, kept full across
admissions). Workload: ``--prompts-file`` (comma-separated token-id
prompts, one per line, all arriving at once) or a synthetic seeded
Poisson stream (``--requests``/``--rate``). Per-request results stream
to stdout as JSON lines the moment each request retires; the final line
is a summary with the engine's ``serve.*`` metrics (admitted/retired/
rejected counters, TTFT percentiles, queue-depth/occupancy gauges).
``--events`` additionally writes the request-span EventLog
(docs/observability.md).

``--replicas N`` (with ``--stages 1``) serves through the fleet
instead: N replicas behind one front queue with health-gated failover;
the summary gains per-replica lines and a fleet rollup, and SIGTERM
drains the whole fleet. The fleet observability plane
(docs/observability.md, "Fleet observability") rides along:
``--metrics-port`` serves the merged fleet registry as Prometheus text
(``/metrics``; plus ``/slo`` and ``/fleet`` JSON — what
``tools/fleet_top.py`` polls), ``--slo-*`` declare targets scored into
a machine-readable ``summary["slo"]`` verdict, and ``--trace-out``
writes the stitched per-request trace timelines (parent + shipped
child events) as JSONL. ``--fleet`` picks the replica transport:

* ``inproc`` (default) — engine replicas in this process, ticked
  serially by the router (the PR 7 behavior, byte-for-byte);
* ``thread`` — same engines, each under its own tick thread
  (``Router(async_tick=True)``): a slow replica no longer stalls its
  siblings' decode loops;
* ``proc`` — each replica a real OS process
  (:class:`~pipe_tpu.fleet.ProcessReplicaTransport`) with its own
  engine/jit cache/KV pool behind a length-prefixed socket protocol;
  needs ``--family lm`` without ``--resume``/``--spec-tokens`` (the
  child rebuilds the model from the spec + seed).

Usage:
    python -m pipe_tpu.apps.serve [--resume DIR] [--requests N --rate R]
        [--prompts-file F] [--slots S] [--stages N] [--replicas N]
        [--fleet inproc|thread|proc] [--journal DIR]
        [--eos ID] [--queue-capacity C] [--policy fifo|priority]
        [--timeout-s T] [--decode-chunk K] [--events F.jsonl] [--tiny]
        [--metrics-port P] [--trace-out F.jsonl]
        [--slo-ttft-p50 S] [--slo-ttft-p99 S] [--slo-e2e-p99 S]
        [--slo-goodput-min F] [--slo-deadline-miss-max F]
        [--slo-shed-max F]
        [--resident auto|on|off] [--resident-chunks R] [--spec-tokens K]
        [--draft ngram|truncated|tree] [--draft-stages N]
        [--spec-branches B] [--spec-adaptive]
        [--cpu N]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from .generate import DriverError, load_params


def _start_metrics_server(port, registry_fn, slo, observer):
    """Daemon-thread HTTP server on 127.0.0.1 exposing the fleet
    observability plane: ``/metrics`` renders ``registry_fn()`` as
    Prometheus text, ``/slo`` the verdict JSON, ``/fleet`` the
    per-replica JSON view (``tools/fleet_top.py`` polls these).
    Returns the server (``.server_address[1]`` is the bound port)."""
    import http.server
    import threading

    from ..obs.fleet_obs import prometheus_text

    class _Handler(http.server.BaseHTTPRequestHandler):
        def do_GET(self):
            path = self.path.split("?", 1)[0].rstrip("/") or "/metrics"
            try:
                if path == "/metrics":
                    body = prometheus_text(registry_fn()).encode()
                    ctype = "text/plain; version=0.0.4"
                elif path == "/slo":
                    body = json.dumps(slo.verdict(registry_fn())).encode()
                    ctype = "application/json"
                elif path == "/fleet":
                    per = (observer.per_replica()
                           if observer is not None else {})
                    body = json.dumps(
                        {str(k): v for k, v in per.items()}).encode()
                    ctype = "application/json"
                else:
                    self.send_error(404)
                    return
            except Exception as e:               # surface, don't crash
                self.send_error(500, str(e))
                return
            self.send_response(200)
            self.send_header("Content-Type", ctype)
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def log_message(self, *a):               # keep stdout JSON-clean
            pass

    srv = http.server.ThreadingHTTPServer(("127.0.0.1", port), _Handler)
    srv.daemon_threads = True
    threading.Thread(target=srv.serve_forever, daemon=True,
                     name="metrics-http").start()
    return srv


def build_argparser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--resume", default=None,
                   help="checkpoint dir (train/state.py layout); default: "
                        "fresh random init")
    p.add_argument("--prompts-file", default=None,
                   help="serve these prompts (comma-separated ids per "
                        "line) instead of a synthetic stream")
    p.add_argument("--requests", type=int, default=16,
                   help="synthetic stream: number of requests")
    p.add_argument("--rate", type=float, default=0.0,
                   help="synthetic stream: Poisson arrivals/s "
                        "(0 = all at once)")
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--top-k", type=int, default=None)
    p.add_argument("--eos", type=int, default=None)
    p.add_argument("--stages", type=int, default=1,
                   help=">1: serve through the pipeline ring")
    p.add_argument("--replicas", type=int, default=1,
                   help=">1: run N engine replicas behind the fleet "
                        "Router (health-gated failover; single-device "
                        "backend only)")
    p.add_argument("--fleet", choices=["inproc", "thread", "proc"],
                   default="inproc",
                   help="replica transport with --replicas > 1: same-"
                        "process serial ticks (inproc), same-process "
                        "with one tick thread per replica (thread), or "
                        "one OS process per replica (proc)")
    p.add_argument("--slots", type=int, default=4,
                   help="decode slots (single-device backend; the ring "
                        "always has one slot per stage)")
    p.add_argument("--queue-capacity", type=int, default=64)
    p.add_argument("--policy", choices=["fifo", "priority"],
                   default="fifo")
    p.add_argument("--timeout-s", type=float, default=None,
                   help="per-request deadline")
    p.add_argument("--decode-chunk", type=int, default=None,
                   help="decode steps per host tick (ring: ring "
                        "revolutions per tick); default 4, and 1 for a "
                        "model that generates a block a step")
    p.add_argument("--resident", choices=["auto", "on", "off"],
                   default="auto",
                   help="the decode launch's horizon: on runs up to "
                        "--resident-chunks decode chunks per launch "
                        "(on-device done-masking and early exit), off "
                        "one (auto: on for accelerators, off on cpu)")
    p.add_argument("--resident-chunks", type=int, default=8,
                   help="max decode chunks per resident launch (ring: "
                        "revolutions)")
    p.add_argument("--spec-tokens", type=int, default=None,
                   help="speculative decode: K-token draft/verify per "
                        "round (both backends; the ring needs "
                        "--resident on/auto-on)")
    p.add_argument("--draft", choices=["ngram", "truncated", "tree"],
                   default="ngram",
                   help="draft source for --spec-tokens: prompt-history "
                        "n-gram lookup (free), truncated-pipeline "
                        "(first --draft-stages stages + tied embedding "
                        "head), or multi-branch tree (single-device "
                        "backend only)")
    p.add_argument("--draft-stages", type=int, default=1,
                   help="stage depth of the truncated/tree draft — a "
                        "STRICT prefix of the model (with --stages 1 "
                        "the model is partitioned into draft-stages+1 "
                        "logical stages to carve one)")
    p.add_argument("--spec-branches", type=int, default=None,
                   help="tree draft: parallel branches per round (>= 2)")
    p.add_argument("--spec-adaptive", action="store_true",
                   help="per-slot acceptance-EWMA adaptive K over a "
                        "pre-traced ladder (single-device backend only)")
    p.add_argument("--events", default=None,
                   help="write the request-span EventLog here (.jsonl)")
    p.add_argument("--journal", default=None,
                   help="directory for the durable request journal "
                        "(fsync'd lifecycle WAL; a crashed controller "
                        "restarts from it via FleetController."
                        "from_journal). --fleet proc only")
    p.add_argument("--metrics-port", type=int, default=None,
                   help="serve the merged fleet registry on "
                        "127.0.0.1:<port>: /metrics (Prometheus text), "
                        "/slo (verdict JSON), /fleet (per-replica JSON "
                        "view). 0 picks an ephemeral port (printed to "
                        "stderr)")
    p.add_argument("--trace-out", default=None,
                   help="with --replicas > 1: write the stitched "
                        "per-request trace timelines here (.jsonl)")
    p.add_argument("--slo-ttft-p50", type=float, default=None,
                   help="SLO target: TTFT p50 seconds")
    p.add_argument("--slo-ttft-p99", type=float, default=None,
                   help="SLO target: TTFT p99 seconds")
    p.add_argument("--slo-e2e-p99", type=float, default=None,
                   help="SLO target: end-to-end latency p99 seconds")
    p.add_argument("--slo-goodput-min", type=float, default=None,
                   help="SLO target: minimum ok/delivered fraction")
    p.add_argument("--slo-deadline-miss-max", type=float, default=None,
                   help="SLO target: max timed_out/delivered fraction")
    p.add_argument("--slo-shed-max", type=float, default=None,
                   help="SLO target: max shed/delivered fraction")
    p.add_argument("--tick-budget-s", type=float, default=None,
                   help="watchdog: count ticks slower than this "
                        "(resilience.watchdog_slow_ticks)")
    p.add_argument("--shed-ewma", type=float, default=None,
                   help="watchdog: deadline-miss EWMA above which "
                        "lowest-priority queued requests are shed")
    p.add_argument("--kv", choices=["slab", "paged"], default="slab",
                   help="KV memory: per-slot monolithic slab, or the "
                        "paged block pool (shared-prefix reuse + "
                        "chunked prefill)")
    p.add_argument("--kv-block-size", type=int, default=16,
                   help="rows per KV block with --kv paged")
    p.add_argument("--kv-pool-blocks", type=int, default=None,
                   help="pool size in blocks with --kv paged "
                        "(default: the slab's row footprint)")
    p.add_argument("--kv-offload", action="store_true",
                   help="with --kv paged: spill cold refcount-0 blocks "
                        "to host RAM under pressure and restore on "
                        "re-reference instead of hard-evicting")
    p.add_argument("--kv-offload-blocks", type=int, default=None,
                   help="host store capacity in blocks for --kv-offload "
                        "(default: the device pool size)")
    p.add_argument("--placement",
                   choices=["least_loaded", "session", "prefix"],
                   default="least_loaded",
                   help="fleet placement: least_loaded, session "
                        "pinning, or prefix (score replicas by matched "
                        "prefix depth x occupancy headroom)")
    p.add_argument("--kv-hot-refs", type=int, default=None,
                   help="fleet: replicate prefixes shared by at least "
                        "N live slots to a sibling proactively "
                        "(requires --kv paged; >= 2)")
    p.add_argument("--roles", default=None,
                   help="disaggregated fleet: comma-separated per-"
                        "replica roles (prefill|decode|mixed), length "
                        "== --replicas, e.g. 'prefill,decode,decode'. "
                        "Requests then flow prefill -> KV handoff -> "
                        "decode; 'auto' sizes the split with "
                        "suggest_roles. Pair with --kv paged so decode "
                        "replicas resume from shipped blocks")
    p.add_argument("--int8", action="store_true",
                   help="int8 weight-only quantized block weights")
    p.add_argument("--family", choices=["lm", "gpt2", "laguna", "sdar"],
                   default="lm",
                   help="laguna: Laguna-S-2.1's share for one chip (5 of "
                        "48 layers, 128 of 256 experts, half the "
                        "vocabulary; models/laguna.py); sdar: "
                        "SDAR-30B-A3B-Chat's first pipeline stage (6 of 48 "
                        "layers, each whole; models/sdar.py), generated by "
                        "diffusion over blocks, --decode-chunk 1. Both: "
                        "--stages 1, --kv slab, no --spec-tokens")
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

    import numpy as np

    from ..inference import GenerationConfig

    if args.family == "gpt2":
        from ..models.gpt2 import GPT2Config as _Cfg
        from ..models.gpt2 import PipelinedGPT2 as _Model
    elif args.family == "laguna":
        from ..models.laguna import LagunaConfig as _Cfg
        from ..models.laguna import PipelinedLaguna as _Model
    elif args.family == "sdar":
        from ..models.sdar import PipelinedSdar as _Model
        from ..models.sdar import SdarConfig as _Cfg
    else:
        from ..models.transformer_lm import LMConfig as _Cfg
        from ..models.transformer_lm import PipelinedLM as _Model

    model_cfg = _Cfg()
    if args.tiny:
        model_cfg = model_cfg.tiny()
    if args.decode_chunk is None:
        # a round of a model that generates by blocks is one block
        args.decode_chunk = 1 if getattr(model_cfg, "generation", None) \
            else 4
    n_stages = max(args.stages, 1)
    # Pipeline-prefix drafts run "the first stage(s)", so the model must
    # be partitioned with a strict prefix to carve. The ring already is;
    # --stages 1 serves an unpartitioned model, so split it into
    # draft-stages+1 logical stages (same weights, nested differently —
    # the single-device backend flattens the stage list anyway).
    n_model_stages = n_stages
    if args.draft != "ngram" and n_stages == 1:
        n_model_stages = max(args.draft_stages, 1) + 1
    if model_cfg.n_layers % n_model_stages:
        what = (f"--stages {n_stages}" if n_model_stages == n_stages
                else f"--draft {args.draft} with --stages 1 partitions "
                     f"the model into --draft-stages + 1 = "
                     f"{n_model_stages} logical stages, which")
        print(f"{what} must divide the model's "
              f"{model_cfg.n_layers} layers", file=sys.stderr)
        return 2
    replicas = max(args.replicas, 1)
    if replicas > 1 and n_stages > 1:
        print("--replicas > 1 requires --stages 1 (the fleet router "
              "shards single-device engines)", file=sys.stderr)
        return 2
    if replicas > 1 and args.fleet == "proc":
        import jax
        if jax.default_backend() != "cpu":
            # one process for each chip: this parent initialises the
            # weights on the default backend and so holds the chip, while
            # every replica child is started with JAX_PLATFORMS=cpu
            # (fleet/proc.py _spawn_env) — a fleet serving from the CPU
            # beside an idle accelerator
            print(f"--fleet proc is refused on the "
                  f"{jax.default_backend()!r} backend: a chip belongs to "
                  f"one process, this parent holds it, and the replica "
                  f"children run on the CPU. Use --fleet thread (one "
                  f"process drives every replica), or --cpu N for a CPU "
                  f"process fleet.", file=sys.stderr)
            return 2

    if args.prompts_file:
        if not os.path.isfile(args.prompts_file):
            print(f"--prompts-file {args.prompts_file}: no such file",
                  file=sys.stderr)
            return 2
        with open(args.prompts_file) as f:
            try:
                prompts = [[int(t) for t in ln.split(",") if t.strip()]
                           for ln in f if ln.strip()]
            except ValueError:
                print("prompts must be comma-separated integer token ids",
                      file=sys.stderr)
                return 2
        if not prompts or any(
                not p or any(i < 0 or i >= model_cfg.vocab for i in p)
                for p in prompts):
            print(f"prompt ids must be in [0, {model_cfg.vocab})",
                  file=sys.stderr)
            return 2
    else:
        rng = np.random.RandomState(args.seed)
        lens = rng.choice((8, 12, 16, 24, 32), size=args.requests)
        prompts = [rng.randint(1, model_cfg.vocab, size=int(n)).tolist()
                   for n in lens]

    model = _Model(model_cfg, n_model_stages)
    try:
        params = load_params(args.resume, model_cfg, _Model,
                             n_model_stages, args.seed)
    except DriverError as e:
        print(str(e), file=sys.stderr)
        return 2
    if args.int8:
        from ..inference.quant import quantize_params
        sp_q, pre_q, post_q = params
        params = (quantize_params(sp_q), pre_q, post_q)
    gen_cfg = GenerationConfig(max_new_tokens=args.max_new,
                               temperature=args.temperature,
                               top_k=args.top_k, eos_token_id=args.eos)

    from ..obs.events import EventLog, NULL_EVENT_LOG
    from ..obs.telemetry import get_registry
    from ..serve import BucketSpec, QueueFull, RequestQueue, ServeEngine
    buckets = BucketSpec.pow2(min_len=8,
                              max_len=max(len(p) for p in prompts))
    # spec lane: verify-write slack on top of the request cap — the
    # chunk writes branches x (K-1) rows past the accepted frontier
    # (tree chunks carry every branch; linear drafts have one)
    max_len = buckets.max_len + args.max_new + (
        (args.spec_branches or 1) * (args.spec_tokens - 1)
        if args.spec_tokens else 0)
    if (args.kv_offload or args.kv_hot_refs is not None
            or args.placement == "prefix") and args.kv != "paged":
        print("--kv-offload/--kv-hot-refs/--placement prefix need "
              "--kv paged (the slab has no blocks to spill, share, or "
              "advertise)", file=sys.stderr)
        return 2
    roles = None
    if args.roles:
        replicas_n = max(args.replicas, 1)
        if args.roles == "auto":
            from ..fleet import suggest_roles
            roles = suggest_roles(
                replicas_n,
                prompt_len=max(len(p) for p in prompts),
                max_new_tokens=args.max_new).roles
        else:
            roles = [r.strip() for r in args.roles.split(",")]
        bad = [r for r in roles if r not in ("prefill", "decode", "mixed")]
        if bad or len(roles) != replicas_n:
            print(f"--roles must name one of prefill|decode|mixed per "
                  f"replica ({replicas_n} expected, got {roles})",
                  file=sys.stderr)
            return 2
        if replicas_n < 2:
            print("--roles needs --replicas >= 2 (one replica cannot "
                  "be split by phase)", file=sys.stderr)
            return 2
    kv_kwargs = {} if args.kv == "slab" else {
        "kv_block_size": args.kv_block_size,
        "kv_pool_blocks": args.kv_pool_blocks,
        "kv_offload": args.kv_offload,
        "kv_offload_blocks": args.kv_offload_blocks}
    resident = {"auto": "auto", "on": True, "off": False}[args.resident]
    spec_kwargs = dict(spec_tokens=args.spec_tokens, draft=args.draft,
                       draft_stages=args.draft_stages,
                       spec_branches=args.spec_branches,
                       spec_adaptive=args.spec_adaptive)
    # invalid spec combos (tree on the ring, draft flags without
    # --spec-tokens, out-of-range draft depth, ...) are rejected by the
    # backend/drafter ctors — surface the message, don't trace back
    try:
        if n_stages > 1:
            from ..parallel.mesh import make_mesh
            from ..parallel.spmd import stack_stage_params
            from ..serve import RingSlotBackend
            sp, pre, post = params
            backend = RingSlotBackend(
                make_mesh(n_stages, 1), model, stack_stage_params(sp),
                pre, post, max_len=max_len, gen=gen_cfg, buckets=buckets,
                revolutions=args.decode_chunk, resident=resident,
                resident_revolutions=args.resident_chunks,
                **spec_kwargs, **kv_kwargs)
        else:
            from ..serve import SingleDeviceSlotBackend
            backend = SingleDeviceSlotBackend(
                model, params, num_slots=args.slots, max_len=max_len,
                gen=gen_cfg, buckets=buckets,
                decode_chunk=args.decode_chunk, resident=resident,
                resident_chunks=args.resident_chunks,
                **spec_kwargs, **kv_kwargs)
    except ValueError as e:
        print(str(e), file=sys.stderr)
        return 2

    trace_buf = None
    if args.events:
        events = EventLog(args.events)
    elif args.trace_out and replicas > 1:
        # --trace-out without --events: hold the parent-side request
        # skeleton (queued/placed/delivered) in memory, or the stitched
        # timelines would carry child-side stages only
        from ..obs.fleet_obs import TraceBuffer
        trace_buf = TraceBuffer(maxlen=200_000)
        events = trace_buf
    else:
        events = NULL_EVENT_LOG

    def _make_watchdog():
        if args.tick_budget_s is None and args.shed_ewma is None:
            return None
        from ..resilience import TickWatchdog
        return TickWatchdog(tick_budget_s=args.tick_budget_s,
                            shed_ewma_threshold=args.shed_ewma)

    journal = None
    if args.journal and not (replicas > 1 and args.fleet == "proc"):
        # the journal exists to recover a crashed fleet controller; the
        # in-process engines die WITH their controller, so journaling
        # them would promise a restart that cannot happen
        print("--journal requires --fleet proc with --replicas > 1 "
              "(only the process fleet survives its controller)",
              file=sys.stderr)
        return 2

    if replicas > 1 and args.fleet == "proc":
        # process fleet: each replica a fresh interpreter built from a
        # plain-data spec — only the deterministic-init lm family can be
        # reconstructed child-side.
        if args.family != "lm" or args.resume or args.spec_tokens:
            print("--fleet proc requires --family lm without --resume/"
                  "--spec-tokens (children rebuild the model from the "
                  "spec + seed)", file=sys.stderr)
            return 2
        import dataclasses as _dc

        from ..fleet import (DisaggController, FleetController,
                             ProcessReplicaTransport, ReplicaSpec,
                             RouterPolicy)
        spec = ReplicaSpec(
            lm_cfg={f: getattr(model_cfg, f)
                    for f in ("vocab", "d_model", "nhead", "d_ff",
                              "n_layers", "dropout", "seq_len")},
            n_stages=1, init_seed=args.seed, num_slots=args.slots,
            max_len=max_len, buckets=list(buckets.lengths),
            decode_chunk=args.decode_chunk,
            queue_capacity=args.queue_capacity,
            gen=dict(max_new_tokens=args.max_new,
                     temperature=args.temperature, top_k=args.top_k,
                     eos_token_id=args.eos),
            **({"kv_block_size": args.kv_block_size,
                "kv_pool_blocks": args.kv_pool_blocks,
                "kv_offload": args.kv_offload,
                "kv_offload_blocks": args.kv_offload_blocks,
                "kv_hot_refs": args.kv_hot_refs}
               if args.kv == "paged" else {}))
        if roles is not None:
            transports = [
                ProcessReplicaTransport(_dc.replace(spec, role=role))
                for role in roles]
        else:
            transports = [ProcessReplicaTransport(spec)
                          for _ in range(replicas)]
        queue = RequestQueue(capacity=args.queue_capacity,
                             policy=args.policy)
        if args.journal:
            from ..fleet import RequestJournal
            journal = RequestJournal(args.journal)
        ctl_cls = DisaggController if roles is not None else FleetController
        eng = ctl_cls(
            transports, queue,
            policy=RouterPolicy(placement=args.placement,
                                kv_hot_refs=args.kv_hot_refs),
            event_log=events, journal=journal)
        if journal is not None:
            # journal each child's wire coordinates (and refresh the
            # fleet.json snapshot) so a restarted controller can
            # re-dial the RUNNING children instead of spawning
            for i, tr in enumerate(transports):
                journal.record_replica(i, **tr.rejoin_info())
    elif replicas > 1:
        # in-process fleet: one front queue, N engines each with its own
        # queue/watchdog, the Router in between. The single-replica path
        # below stays byte-for-byte what it was — Router absent means
        # zero overhead. --fleet thread gives each replica its own tick
        # thread; placement/health/delivery stay on the caller's thread.
        from ..serve import Router, SingleDeviceSlotBackend
        backends = [backend] + [
            SingleDeviceSlotBackend(
                model, params, num_slots=args.slots, max_len=max_len,
                gen=gen_cfg, buckets=buckets,
                decode_chunk=args.decode_chunk, resident=resident,
                resident_chunks=args.resident_chunks,
                **spec_kwargs, **kv_kwargs)
            for _ in range(replicas - 1)]
        engines = [ServeEngine(b,
                               RequestQueue(capacity=args.queue_capacity),
                               event_log=events,
                               watchdog=_make_watchdog(),
                               phase=(roles[i] if roles is not None
                                      else "mixed"))
                   for i, b in enumerate(backends)]
        queue = RequestQueue(capacity=args.queue_capacity,
                             policy=args.policy)
        from ..serve import RouterPolicy
        if roles is not None:
            from ..fleet import DisaggController, InProcessTransport
            eng = DisaggController(
                [InProcessTransport(e,
                                    async_tick=(args.fleet == "thread"))
                 for e in engines],
                queue, event_log=events,
                policy=RouterPolicy(placement=args.placement,
                                    kv_hot_refs=args.kv_hot_refs))
        else:
            eng = Router(engines, queue, event_log=events,
                         policy=RouterPolicy(placement=args.placement,
                                             kv_hot_refs=args.kv_hot_refs),
                         async_tick=(args.fleet == "thread"))
    else:
        queue = RequestQueue(capacity=args.queue_capacity,
                             policy=args.policy)
        eng = ServeEngine(backend, queue, event_log=events,
                          watchdog=_make_watchdog())

    # Fleet observability plane: the observer merges shipped/shared
    # replica metrics into one rollup registry; the SLO monitor scores
    # it; --metrics-port exposes both live (what fleet_top polls).
    from ..obs.fleet_obs import FleetObserver, SloMonitor, SloTargets
    slo = SloMonitor(SloTargets(
        ttft_p50_s=args.slo_ttft_p50, ttft_p99_s=args.slo_ttft_p99,
        e2e_p99_s=args.slo_e2e_p99, goodput_min=args.slo_goodput_min,
        deadline_miss_max=args.slo_deadline_miss_max,
        shed_max=args.slo_shed_max))
    observer = FleetObserver(eng, parent_events=(args.events or trace_buf)) \
        if replicas > 1 else None

    def _fleet_registry():
        return observer.rollup() if observer is not None \
            else get_registry()

    metrics_server = None
    if args.metrics_port is not None:
        metrics_server = _start_metrics_server(
            args.metrics_port, _fleet_registry, slo, observer)
        print(f"metrics: http://127.0.0.1:"
              f"{metrics_server.server_address[1]}/metrics",
              file=sys.stderr, flush=True)

    # Graceful drain on SIGTERM/SIGINT: live slots finish, queued work is
    # shed back to callers, new admissions stop — then a clean summary.
    # With --replicas this drains the WHOLE fleet (every engine).
    import signal as _signal

    def _drain_handler(signum, frame):
        eng.drain()

    for _sig in (_signal.SIGTERM, _signal.SIGINT):
        try:
            _signal.signal(_sig, _drain_handler)
        except (ValueError, OSError):
            pass  # not the main thread (embedded use) — skip handlers

    if args.prompts_file or args.rate <= 0:
        arrivals = [0.0] * len(prompts)
    else:
        rng = np.random.RandomState(args.seed + 1)
        arrivals = np.cumsum(
            rng.exponential(1.0 / args.rate, size=len(prompts))).tolist()

    from ..serve import EngineDraining

    t0 = time.monotonic()
    i = rejected = done = errors = 0
    while i < len(prompts) or not eng.idle:
        if eng.draining:
            i = len(prompts)      # stop submitting; finish what's live
        now = time.monotonic() - t0
        while i < len(prompts) and arrivals[i] <= now:
            try:
                eng.submit(prompts[i], seed=args.seed + i,
                           timeout_s=args.timeout_s)
            except QueueFull:
                rejected += 1
            except EngineDraining:
                i = len(prompts)
                break
            i += 1
        if eng.idle and i < len(prompts):
            time.sleep(min(arrivals[i] - now, 0.005))
            continue
        for r in eng.tick():
            done += 1
            errors += r.status == "error"
            print(json.dumps({
                "request": r.request_id, "status": r.status,
                "finish_reason": r.finish_reason,
                "prompt_len": r.prompt_len, "tokens": r.tokens,
                "ttft_s": (round(r.ttft, 4)
                           if r.ttft is not None else None),
                "latency_s": round(r.latency, 4)}), flush=True)
    elapsed = time.monotonic() - t0

    from ..obs.telemetry import host_overhead_per_token
    snap = {k: v for k, v in get_registry().scalars().items()
            if k.startswith(("serve.", "resilience."))}
    summary = {
        "backend": (f"Fleet[{args.fleet}]({type(backend).__name__} x "
                    f"{replicas})"
                    if replicas > 1 else type(backend).__name__),
        "finished": done, "rejected": rejected, "errors": errors,
        "drained": eng.draining,
        "elapsed_s": round(elapsed, 3),
        "resident": bool(getattr(backend, "resident", False)),
        "host_overhead_per_token_us": round(
            1e6 * host_overhead_per_token(), 2),
        "buckets": list(buckets.lengths), "metrics": snap}
    summary["slo"] = slo.verdict(_fleet_registry())
    if replicas > 1:
        def _rep_line(rep):
            line = {"replica": rep.index, "state": rep.state}
            try:
                # transport surfaces work for in-process AND process
                # replicas (a retired process transport may be gone)
                line["queue_depth"] = rep.transport.queue_depth
                line["live_slots"] = rep.transport.live_slots
            except Exception:
                line["queue_depth"] = line["live_slots"] = None
            return line
        summary["fleet"] = {
            "transport": args.fleet,
            "rollup": eng.counts(),
            "per_replica": [_rep_line(rep) for rep in eng.replicas]}
        eng.close()   # stops tick threads / shuts replica processes down
        # after close: the proc children ship their FINAL obs deltas on
        # the shutdown RPC, and every obs_view/ledger read below is
        # parent-side state that survives the replicas
        if observer is not None:
            per = observer.per_replica()
            summary["fleet"]["staleness_s"] = {
                str(i): v["staleness_s"] for i, v in per.items()}
            summary["fleet"]["reconcile"] = observer.reconcile()
            summary["slo"] = slo.verdict(_fleet_registry())
            if args.trace_out:
                # flush parent events so stitch() reads a complete log
                events.flush()
                summary["fleet"]["trace_records"] = \
                    observer.write_stitched(args.trace_out)
    if journal is not None:
        # the loop above ran to quiescence (drain included): everything
        # submitted is terminal, so stamp clean_shutdown — a restart on
        # this journal skips reconciliation entirely
        journal.close(clean=True)
    print(json.dumps({"summary": summary}))
    events.close()
    if metrics_server is not None:
        metrics_server.shutdown()
    if errors:
        # the engine contains a backend failure (a compile error
        # included) to the requests it hit; the driver still fails
        last = getattr(eng, "last_error", None)
        print(f"{errors} request(s) ended in an engine error"
              + (f"; last: {type(last).__name__}: {last}"
                 if last is not None else ""), file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
