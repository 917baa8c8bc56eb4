"""Fleet bench: goodput, failover, async ticks, KV handoff, disagg.

Nine questions, answered with the tiny LM on whatever backend is
available (the numbers of record are the committed ``FLEET_r20.json``):

1. **Scaling** — saturated fleet goodput (ok tokens/s through the
   controller's exactly-once ledger) at N = 1, 2, 3 replicas, over the
   transport picked by ``--fleet``: same-process engines ticked
   serially (``inproc``), same-process engines each under a tick
   thread (``thread``), or one OS process per replica (``proc``, the
   :mod:`pipe_tpu.fleet.proc` socket transport). On a real pod each
   replica is its own device and the curve is ~linear; on the CPU host
   the replicas share one processor, so the artifact records the
   honest (flat-ish) curve plus per-N slot counts for context.
2. **Kill one of N** — N = 3 replicas, one dies mid-stream. In-process
   fleets inject a ``kill_replica`` chaos fault; the ``proc`` fleet
   kills the actual OS process (SIGKILL, no goodbye) and recovery runs
   through heartbeat loss + TransportError reclaim. Either way the
   delivery timeline is windowed before/failover/after: goodput must
   drop, NOT to zero, and recover as the controller re-places the dead
   replica's backlog onto the survivors — and every submitted id still
   yields exactly one terminal response.
3. **Async ticks vs serial** — N = 3 in-process replicas, one of them
   a deliberate straggler (decode sleeps). Serial router ticks pay the
   straggler's stall on EVERY fleet tick; per-replica tick threads
   confine it to its own replica. The bench asserts threaded goodput
   >= serial goodput — the claim ``async_tick`` exists to make.
4. **KV handoff TTFT** — a session remapped off its home replica
   either ships its cached prefix blocks to the new home
   (:meth:`FleetController._kv_handoff`) or re-prefills from scratch
   (export disabled). Measures TTFT of the first post-remap request
   both ways; the win is the prefill work the shipped blocks saved.
   The summary's ``handoff_beats_reprefill`` flag IS the disagg
   pipeline's entry fee: shipping a prefix must be cheaper than
   recomputing it, every round.
5. **Disagg vs mixed at equal chips** — 2 phase-specialized replicas
   (one prefill-only, one decode-only, KV shipped between them by
   :class:`~pipe_tpu.fleet.disagg.DisaggController`) against 2 mixed
   replicas, same slots, under a prefill-heavy deadlined workload.
   The metric is deadline goodput: ok tokens/s where ok means the
   request finished inside its ``timeout_s``. A mixed replica's tick
   interleaves multi-chunk host-blocking prefills with its decode
   chunks, so decode latency inherits the prefill burst variance and
   deadlines blow; the disagg decode replica's ticks hold only cheap
   cached-prefix resumes and decode chunks. Both arms run per-replica
   tick threads (the isolation async_tick exists to provide).
6. **Disagg SIGKILL drills** — 4 real child processes (2 prefill +
   2 decode), kill one PREFILL replica mid-stream, then (fresh fleet)
   one DECODE replica. Either death lands mid-handoff for some
   requests; the surviving role sibling absorbs the stream through
   the one park-or-finish reclaim gate and every submitted id still
   yields exactly one terminal — the exactly-once ledger, across the
   phase boundary.
7. **Wire chaos drills** — adversarial faults at the proc framing
   layer (:func:`pipe_tpu.fleet.proc.apply_wire_chaos`). A 2 s
   ``wire_partition`` on one replica's wire must heal losslessly: the
   child's re-dial lands in the listener's kernel backlog, retained
   response frames replay, the parent's sequence dedup swallows the
   duplicates — every id exactly one terminal. A ``wire_corrupt``
   storm (15 consecutive parent->child frames) must never half-parse:
   each bad frame is a CRC reject + connection drop + re-dial +
   replay, and the drill asserts the reject counters actually fired.
8. **Controller SIGKILL + restart** — the round-20 tentpole. The
   controller runs in a SEPARATE process (hidden ``--_ctl-worker``
   mode of this script) journaling every lifecycle transition to a
   :class:`~pipe_tpu.fleet.journal.RequestJournal`; the bench SIGKILLs
   it mid-stream (no goodbye, fsync'd WAL is all that survives), then
   replays the journal, re-dials the orphaned children in rejoin mode
   and rebuilds the controller with
   :meth:`~pipe_tpu.fleet.control.FleetController.from_journal`.
   Run twice — a mixed 3-replica fleet and a 2 prefill + 2 decode
   disagg fleet — and both times every submitted id must end with
   exactly one terminal response across the two controller lives.
9. **Saturation sweep** — steady-state goodput at N = 1..K replicas
   over the chosen transport; reports the front-queue bottleneck N
   (the smallest fleet within 10% of the sweep's best goodput) —
   past it, added replicas buy nothing because the shared host / the
   single front queue is the limit, not replica count.

The kill trials also exercise the fleet observability plane
(docs/observability.md, "Fleet observability"): the controller runs
under a :class:`~pipe_tpu.obs.fleet_obs.TraceBuffer` event log and a
:class:`~pipe_tpu.obs.fleet_obs.FleetObserver`, and the summary stamps
the delivered-token reconciliation (per-replica delivery-synchronized
token counters must sum to the parent ledger's delivered total — across
the SIGKILL), per-replica metric staleness, the SLO verdict over the
merged rollup, and trace-stitch stats: every submitted id must
reconstruct into exactly one stitched timeline, failed-over ids showing
both placements in one trace. ``bench.py --quick`` asserts those.

Every summary stamps host contention (1-min load average vs CPU count):
on a contended host the absolute numbers are noise — the flag says so
instead of letting the artifact lie.

Usage:
  python tools/fleet_bench.py                 # full run -> FLEET_r20.json
  python tools/fleet_bench.py --quick --fleet proc   # bench.py embed
Progress goes to stderr; the last stdout line is always the summary
object, so ``bench.py`` embeds the --quick summary.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import queue as queue_mod
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from pipe_tpu.fleet import (DisaggController, FleetController,  # noqa: E402
                            InProcessTransport, ProcessReplicaTransport,
                            ReplicaSpec, RequestJournal)
from pipe_tpu.inference import GenerationConfig  # noqa: E402
from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM  # noqa: E402
from pipe_tpu.obs.fleet_obs import (FleetObserver, SloMonitor,  # noqa: E402
                                    SloTargets, TraceBuffer)
from pipe_tpu.obs.telemetry import get_registry  # noqa: E402
from pipe_tpu.resilience import ChaosPlan, Fault, TickWatchdog  # noqa: E402
from pipe_tpu.serve import (BucketSpec, RequestQueue, Router,  # noqa: E402
                            RouterPolicy, ServeEngine,
                            SingleDeviceSlotBackend)

CFG = LMConfig(vocab=67, d_model=16, nhead=2, d_ff=32, n_layers=4,
               seq_len=64, dropout=0.0)
BUCKETS = BucketSpec.of(8, 16)
MAX_NEW = 32                 # engine cap; per-request budgets vary below
MAX_LEN = BUCKETS.max_len + MAX_NEW
SLOTS = 2
CHUNK = 4


def log(msg):
    print(msg, file=sys.stderr, flush=True)


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


def make_workload(n, rng):
    """(prompt, max_new) pairs with varied generation lengths, so
    retirements/admissions stagger across ticks and deliveries form a
    continuous stream instead of synchronized waves — the kill trial's
    windowing needs a nonzero pre-kill baseline."""
    lens = rng.choice((6, 8, 12, 16), size=n)
    news = rng.choice((8, 12, 16, 24, 32), size=n)
    return [(rng.randint(1, CFG.vocab, size=int(p)).tolist(), int(m))
            for p, m in zip(lens, news)]


def proc_spec():
    return ReplicaSpec(
        lm_cfg=dict(vocab=CFG.vocab, d_model=CFG.d_model, nhead=CFG.nhead,
                    d_ff=CFG.d_ff, n_layers=CFG.n_layers,
                    seq_len=CFG.seq_len, dropout=0.0),
        n_stages=1, init_seed=0, num_slots=SLOTS, max_len=MAX_LEN,
        gen=dict(max_new_tokens=MAX_NEW, temperature=0.0),
        buckets=list(BUCKETS.lengths), decode_chunk=CHUNK,
        heartbeat_interval_s=0.05)


def make_fleet(model, params, n_replicas, *, fleet="inproc", chaos=None,
               capacity=256, event_log=None):
    if fleet == "proc":
        transports = [ProcessReplicaTransport(proc_spec())
                      for _ in range(n_replicas)]
        return FleetController(
            transports, RequestQueue(capacity=capacity),
            policy=RouterPolicy(backoff_base_s=0.0,
                                heartbeat_timeout_s=5.0),
            event_log=event_log)
    gen_cfg = GenerationConfig(max_new_tokens=MAX_NEW, temperature=0.0)
    engines = []
    for _ in range(n_replicas):
        backend = SingleDeviceSlotBackend(
            model, params, num_slots=SLOTS, max_len=MAX_LEN, gen=gen_cfg,
            buckets=BUCKETS, decode_chunk=CHUNK)
        engines.append(ServeEngine(
            backend, RequestQueue(capacity=capacity),
            watchdog=TickWatchdog(stuck_slack_ticks=None)))
    return Router(engines, RequestQueue(capacity=capacity),
                  policy=RouterPolicy(backoff_base_s=0.0), chaos=chaos,
                  async_tick=(fleet == "thread"), event_log=event_log)


def warm(router, n_replicas):
    """Compile both prefill buckets + CHUNKED decode on every replica
    before the clock matters (least-loaded placement round-robins
    equal-load replicas, so 2N warm requests touch all of them;
    max_new > decode_chunk so the chunked decode graph compiles here,
    not inside a measured window)."""
    for _ in range(n_replicas):
        router.submit([1] * 8, max_new_tokens=2 * CHUNK)
        router.submit([1] * 16, max_new_tokens=2 * CHUNK)
    run_to_idle(router)


def run_to_idle(router, pace_s=0.01, timeout_s=600.0):
    deadline = time.monotonic() + timeout_s
    while not router.idle:
        router.tick()
        if pace_s:
            time.sleep(pace_s)
        assert time.monotonic() < deadline, "fleet never went idle"


def timed_run(router, workload, pace_s=0.0, on_tick=None):
    """Submit everything, tick to idle, stamp each delivery with the
    router tick index AND wall offset it arrived at. Returns (records,
    elapsed_s, total_ticks) where records are (tick, status, n_tokens,
    t_s). Also runs the exactly-once ledger check: every submitted id,
    one terminal response. ``pace_s`` throttles the sweep loop for
    self-ticking (thread/proc) replicas; ``on_tick(tick, router)`` is
    the chaos hook for trials that act mid-stream (e.g. kill a child
    process)."""
    submitted = [router.submit(p, max_new_tokens=m, seed=i).id
                 for i, (p, m) in enumerate(workload)]
    t0 = time.monotonic()
    records = []
    ticks = 0
    while not router.idle:
        tick = ticks
        ticks += 1
        if on_tick is not None:
            on_tick(tick, router, records)
        for r in router.tick():
            records.append((tick, r.status, len(r.tokens),
                            time.monotonic() - t0))
        if pace_s:
            time.sleep(pace_s)
        assert time.monotonic() - t0 < 600.0, "trial never went idle"
    elapsed = time.monotonic() - t0
    missing = [i for i in submitted if router.response(i) is None]
    assert not missing, f"requests with no terminal response: {missing}"
    return records, elapsed, ticks, submitted


def tokens_per_tick(records, lo, hi):
    """ok tokens delivered per tick over tick window [lo, hi)."""
    toks = sum(n for t, status, n, _ in records
               if status == "ok" and lo <= t < hi)
    return toks / max(hi - lo, 1)


def tokens_per_sec(records, lo_s, hi_s):
    """ok tokens delivered per second over wall window [lo_s, hi_s)."""
    toks = sum(n for _, status, n, t in records
               if status == "ok" and lo_s <= t < hi_s)
    return toks / max(hi_s - lo_s, 1e-9)


def ok_tokens(records):
    return sum(n for _, s, n, _ in records if s == "ok")


def obs_report(observer, submitted):
    """Observability-plane stamp for a kill trial: the delivered-token
    reconciliation, per-replica metric staleness, the SLO verdict over
    the merged fleet rollup, and trace-stitch stats — every submitted
    id must reconstruct into EXACTLY one stitched timeline (trace ids
    are minted once and survive failover), and failed-over ids must
    show both placements in one trace. Call AFTER router.close(): the
    proc children ship their final obs deltas on the shutdown RPC, and
    everything read here is parent-side state that survives them."""
    reconcile = observer.reconcile()
    per = observer.per_replica()
    stitched = observer.stitch_by_request()
    owners = {}
    for key, recs in observer.stitch().items():
        for r in recs:
            if r.get("request") is not None:
                owners.setdefault(int(r["request"]), set()).add(key)
    have = [i for i in submitted if i in stitched]
    exactly_once = all(len(owners.get(i, ())) == 1 for i in submitted)
    failed_over = sum(
        1 for i in submitted
        if len({r.get("attempts") for r in stitched.get(i, [])
                if r.get("stage") == "placed"}) >= 2)
    verdict = SloMonitor(SloTargets(goodput_min=0.5)).verdict(
        observer.rollup())
    return {
        "reconcile": reconcile,
        "staleness_s": {str(i): (None if v["staleness_s"] is None
                                 else round(v["staleness_s"], 3))
                        for i, v in per.items()},
        "trace_stitch": {
            "submitted": len(submitted),
            "stitched": len(have),
            "frac": round(len(have) / max(len(submitted), 1), 4),
            "exactly_once": bool(exactly_once),
            "failed_over_with_both_placements": failed_over,
        },
        "slo": verdict,
    }


def scaling_trial(model, params, n_replicas, n_requests, seed, fleet):
    rng = np.random.RandomState(seed)
    router = make_fleet(model, params, n_replicas, fleet=fleet)
    try:
        warm(router, n_replicas)
        records, elapsed, ticks, _ = timed_run(
            router, make_workload(n_requests, rng),
            pace_s=0.01 if fleet != "inproc" else 0.0)
    finally:
        router.close()
    ok = sum(1 for _, s, _, _ in records if s == "ok")
    return {
        "replicas": n_replicas,
        "transport": fleet,
        "slots_total": n_replicas * SLOTS,
        "requests": n_requests,
        "ok": ok,
        "ticks": ticks,
        "elapsed_s": round(elapsed, 3),
        "goodput_tokens_s": round(ok_tokens(records) / max(elapsed, 1e-9),
                                  1),
        "goodput_tokens_per_tick": round(
            ok_tokens(records) / max(ticks, 1), 2),
    }


def kill_trial(model, params, n_replicas, n_requests, seed, kill_tick,
               window, fleet):
    """N replicas, kill one mid-stream; window the delivery timeline
    around the kill to show degrade-and-recover. In-process fleets
    kill via the chaos plan at a router tick (tick wall time is
    roughly constant, so tick windows are deterministic). The proc
    fleet SIGKILLs the real child process and windows on SECONDS
    under a trickle-fed steady-state load: submitting the whole
    stream up front would make the first parent tick one giant
    placement-RPC burst and cluster every delivery at the end, so the
    feed keeps a bounded number of requests outstanding and the
    delivery timeline stays continuous through the kill."""
    rng = np.random.RandomState(seed)
    if fleet == "proc":
        return _kill_trial_proc(n_replicas, rng)
    chaos = ChaosPlan([Fault("kill_replica", step=kill_tick,
                             stage=n_replicas - 1)])
    trace_buf = TraceBuffer(maxlen=200_000)
    router = make_fleet(model, params, n_replicas, fleet=fleet,
                        chaos=chaos, event_log=trace_buf)
    try:
        warm(router, n_replicas)
        records, elapsed, ticks, submitted = timed_run(
            router, make_workload(n_requests, rng),
            pace_s=0.01 if fleet != "inproc" else 0.0)
        states = router.counts()
    finally:
        router.close()
    obs = obs_report(FleetObserver(router,
                                   parent_events=trace_buf.drain()),
                     submitted)
    assert ticks > kill_tick + window, (
        f"run finished in {ticks} ticks; needs > "
        f"{kill_tick + window} — raise the load")
    before = tokens_per_tick(records, max(kill_tick - window, 0),
                             kill_tick)
    during = tokens_per_tick(records, kill_tick, kill_tick + window)
    after = tokens_per_tick(records, kill_tick + window, ticks)
    by_status = {}
    for _, s, _, _ in records:
        by_status[s] = by_status.get(s, 0) + 1
    return {
        "replicas": n_replicas,
        "transport": fleet,
        "killed_replica": n_replicas - 1,
        "kill_mode": "chaos_fault",
        "kill_at": kill_tick,
        "window": window,
        "rate_unit": "tokens/tick",
        "requests": n_requests,
        "ticks": ticks,
        "elapsed_s": round(elapsed, 3),
        "rate_before": round(before, 2),
        "rate_failover": round(during, 2),
        "rate_after": round(after, 2),
        "drop_frac": round(1.0 - during / max(before, 1e-9), 3),
        "recovered_frac": round(after / max(before, 1e-9), 3),
        "survived_failover": during > 0.0 or after > 0.0,
        "responses_by_status": by_status,
        "exactly_once": len(records) == n_requests,
        "replica_states": states,
        "obs": obs,
    }


def _kill_trial_proc(n_replicas, rng, kill_after_s=2.0, duration_s=6.0,
                     max_outstanding=9):
    """SIGKILL one of N real child processes mid-stream. Steady-state
    feed: keep ``max_outstanding`` requests in flight, kill the last
    replica at ``kill_after_s``, keep feeding, then drain. Goodput in
    1 s windows before/during/after the kill shows the degrade (one
    replica's work vanishes and its in-flight set pays a retry) and
    the recovery (survivors absorb the stream)."""
    trace_buf = TraceBuffer(maxlen=200_000)
    router = make_fleet(None, None, n_replicas, fleet="proc",
                        event_log=trace_buf)
    # oversized pool: the feed must NOT run dry inside the measured
    # windows (a drained feed deflates the post-kill rate and reads as
    # a failed recovery)
    work = make_workload(4096, rng)
    submitted, records = [], []
    kill_t = None
    try:
        warm(router, n_replicas)
        t0 = time.monotonic()
        i = 0
        while time.monotonic() - t0 < duration_s:
            now = time.monotonic() - t0
            while len(submitted) - len(records) < max_outstanding \
                    and i < len(work):
                p, m = work[i]
                submitted.append(router.submit(
                    p, max_new_tokens=m, seed=i).id)
                i += 1
            if kill_t is None and now >= kill_after_s:
                router.replicas[n_replicas - 1].transport._proc.kill()
                kill_t = now
            for r in router.tick():
                records.append((0, r.status, len(r.tokens),
                                time.monotonic() - t0))
            time.sleep(0.005)
        deadline = time.monotonic() + 120.0
        while not router.idle:
            for r in router.tick():
                records.append((0, r.status, len(r.tokens),
                                time.monotonic() - t0))
            time.sleep(0.005)
            assert time.monotonic() < deadline, "drain never finished"
        elapsed = time.monotonic() - t0
        states = router.counts()
        missing = [x for x in submitted if router.response(x) is None]
        assert not missing, f"requests with no terminal: {missing}"
    finally:
        router.close()
    obs = obs_report(FleetObserver(router,
                                   parent_events=trace_buf.drain()),
                     submitted)
    assert kill_t is not None, "run too short to reach the kill point"
    w = min(1.0, kill_t, (elapsed - kill_t) / 2)
    before = tokens_per_sec(records, kill_t - w, kill_t)
    during = tokens_per_sec(records, kill_t, kill_t + w)
    after = tokens_per_sec(records, kill_t + w, elapsed)
    by_status = {}
    for _, s, _, _ in records:
        by_status[s] = by_status.get(s, 0) + 1
    return {
        "replicas": n_replicas,
        "transport": "proc",
        "killed_replica": n_replicas - 1,
        "kill_mode": "sigkill_process",
        "kill_at": round(kill_t, 3),
        "window": round(w, 3),
        "rate_unit": "tokens/s",
        "requests": len(submitted),
        "ticks": 0,
        "elapsed_s": round(elapsed, 3),
        "rate_before": round(before, 2),
        "rate_failover": round(during, 2),
        "rate_after": round(after, 2),
        "drop_frac": round(1.0 - during / max(before, 1e-9), 3),
        "recovered_frac": round(after / max(before, 1e-9), 3),
        "survived_failover": during > 0.0 or after > 0.0,
        "responses_by_status": by_status,
        "exactly_once": len(records) == len(submitted),
        "replica_states": states,
        "obs": obs,
    }


def straggler_trial(model, params, n_requests, seed, sleep_s=0.05,
                    duration_s=4.0):
    """N=3, replica 2 a straggler (decode sleeps ``sleep_s``): serial
    router ticks pay the sleep inline on EVERY fleet tick — nothing
    else decodes while the straggler naps; per-replica tick threads
    confine it to its own replica. Measured as steady-state goodput
    over a fixed wall-clock window with the front queue kept fed (a
    fixed-size workload would let the straggler's own tail dominate
    both arms and hide the siblings' win). Asserts threaded goodput
    >= serial goodput — the claim ``async_tick`` exists to make."""
    out = {}
    for mode in ("serial", "thread"):
        rng = np.random.RandomState(seed)
        router = make_fleet(model, params, 3,
                            fleet="thread" if mode == "thread"
                            else "inproc")
        try:
            warm(router, 3)
            backend = router.replicas[2].engine.backend
            orig = backend.decode

            def slow_decode(live, _orig=orig, **kw):
                time.sleep(sleep_s)
                return _orig(live, **kw)

            backend.decode = slow_decode
            pace = 0.01 if mode == "thread" else 0.0
            feed = iter(range(10_000))
            t0 = time.monotonic()
            deadline = t0 + duration_s
            tokens = finished = 0
            while time.monotonic() < deadline:
                while self_depth(router) < 6:     # keep the fleet fed
                    i = next(feed)
                    p, m = make_workload(1, rng)[0]
                    router.submit(p, max_new_tokens=m, seed=i)
                for r in router.tick():
                    if r.status == "ok":
                        tokens += len(r.tokens)
                        finished += 1
                if pace:
                    time.sleep(pace)
            elapsed = time.monotonic() - t0
            run_to_idle(router)                   # flush the remainder
        finally:
            router.close()
        out[mode] = {
            "window_s": round(elapsed, 3),
            "ok": finished,
            "ok_tokens": tokens,
            "goodput_tokens_s": round(tokens / max(elapsed, 1e-9), 1),
        }
    serial = out["serial"]["goodput_tokens_s"]
    threaded = out["thread"]["goodput_tokens_s"]
    out["straggler_sleep_s"] = sleep_s
    out["speedup"] = round(threaded / max(serial, 1e-9), 2)
    out["async_beats_serial"] = bool(threaded >= serial)
    assert threaded >= serial, (
        f"async ticks lost to serial under a straggler: "
        f"{threaded} < {serial} tokens/s")
    return out


def self_depth(router):
    """Outstanding work visible to the feeder: front depth plus every
    replica's queued+live share."""
    return router.queue.depth + sum(
        rep.transport.queue_depth + rep.transport.live_slots
        for rep in router.replicas if rep.state != "retired")


def handoff_trial(repeats=3):
    """Session remap TTFT, handoff vs re-prefill. Two paged replicas;
    a session decodes on its home (caching its prefix blocks), the
    home is marked suspect, and the next session request remaps. With
    KV handoff the destination imports the cached blocks and prefill
    skips them; with export disabled it re-prefills the whole prompt.

    Uses its own model config (wider + longer context than the fleet
    CFG): the win IS the prefill work saved, so the prompt has to be
    long enough that prefill costs more than shipping its blocks —
    48 tokens of a 16-wide model re-prefill in ~7ms, which any
    handoff overhead eats. Repeats each arm with a fresh fleet and
    takes the min TTFT (min is robust against scheduler noise on a
    shared host)."""
    hcfg = LMConfig(vocab=67, d_model=32, nhead=2, d_ff=64,
                    n_layers=4, seq_len=160, dropout=0.0)
    model = PipelinedLM(hcfg, 1)
    params = model.init(jax.random.key(5))
    gen_cfg = GenerationConfig(max_new_tokens=4, temperature=0.0)
    rng = np.random.RandomState(3)
    prompt = list(rng.randint(1, hcfg.vocab, size=144))  # 18 blocks

    def fleet():
        def engine():
            be = SingleDeviceSlotBackend(
                model, params, num_slots=SLOTS, max_len=160,
                gen=gen_cfg, kv_block_size=8, kv_pool_blocks=60,
                prefill_chunk=8)
            eng = ServeEngine(be, RequestQueue())
            # compile EVERY prefill path this replica will run before
            # anything is timed — including the resume-from-cached-
            # prefix program (a different trace than full prefill: the
            # remapped request must measure prefill work saved, not a
            # cold jit cache on the destination). A throwaway prompt,
            # served twice: full prefill, then the cached-prefix resume.
            warm_p = list(rng.randint(1, hcfg.vocab, size=144))
            for _ in range(2):
                eng.submit(warm_p, max_new_tokens=4, seed=9)
                eng.run_until_idle()
            return eng
        return Router([engine(), engine()], RequestQueue(),
                      policy=RouterPolicy(placement="session"))

    def serve_one(router):
        rid = router.submit(prompt, max_new_tokens=4, seed=0,
                            session="alice").id
        for _ in range(10000):
            router.tick()
            resp = router.response(rid)
            if resp is not None:
                assert resp.status == "ok", resp
                return resp
        raise AssertionError("request never finished")

    reg = get_registry()
    ttfts = {"handoff": [], "reprefill": []}
    shipped0 = reg.counter("serve.fleet.kv_handoff_shipped").value
    bytes0 = reg.counter("serve.fleet.kv_handoff_bytes").value
    for arm in ("handoff", "reprefill"):
        for _ in range(repeats):
            router = fleet()
            serve_one(router)                      # warm the home + jit
            serve_one(router)                      # steady-state TTFT
            if arm == "reprefill":
                for rep in router.replicas:        # sever the handoff
                    rep.transport.export_prefix = lambda prompt: None
            home = router._session_map["alice"]
            router.replicas[home].state = "suspect"
            resp = serve_one(router)               # remapped request
            ttfts[arm].append(resp.ttft)
            router.close()
    shipped = reg.counter("serve.fleet.kv_handoff_shipped").value \
        - shipped0
    nbytes = reg.counter("serve.fleet.kv_handoff_bytes").value - bytes0
    t_hand = min(ttfts["handoff"])
    t_cold = min(ttfts["reprefill"])
    return {
        "prompt_len": len(prompt),
        "kv_block_size": 8,
        "repeats": repeats,
        "blocks_shipped": int(shipped),
        "handoff_bytes": int(nbytes),
        "ttft_handoff_s": round(t_hand, 4),
        "ttft_reprefill_s": round(t_cold, 4),
        "ttft_win_s": round(t_cold - t_hand, 4),
        "ttft_all_handoff_s": [round(t, 4) for t in ttfts["handoff"]],
        "ttft_all_reprefill_s": [round(t, 4)
                                 for t in ttfts["reprefill"]],
        "handoff_moved_blocks": bool(shipped > 0),
    }


def prefix_placement_trial(repeats=3):
    """Gen-2 KV-aware placement: one replica holds a session's prefix
    blocks; ``placement="prefix"`` scores candidates by matched depth x
    occupancy headroom and lands the request there, vs least-loaded
    which (ties by index) sends it to the COLD replica. The TTFT gap is
    the prefill work the directory lookup saved. Plus the proactive
    arm: two concurrent sessions push a chain's refcount to the
    ``kv_hot_refs`` threshold and the controller replicates it to the
    idle sibling ahead of any remap."""
    hcfg = LMConfig(vocab=67, d_model=32, nhead=2, d_ff=64,
                    n_layers=4, seq_len=160, dropout=0.0)
    model = PipelinedLM(hcfg, 1)
    params = model.init(jax.random.key(6))
    gen_cfg = GenerationConfig(max_new_tokens=4, temperature=0.0)
    rng = np.random.RandomState(7)
    shared = list(rng.randint(1, hcfg.vocab, size=136))  # 17 blocks

    def engine():
        be = SingleDeviceSlotBackend(
            model, params, num_slots=SLOTS, max_len=160,
            gen=gen_cfg, kv_block_size=8, kv_pool_blocks=60,
            prefill_chunk=8)
        eng = ServeEngine(be, RequestQueue())
        warm_p = list(rng.randint(1, hcfg.vocab, size=144))
        for _ in range(2):                  # jit full + resume prefill
            eng.submit(warm_p, max_new_tokens=4, seed=9)
            eng.run_until_idle()
        return eng

    def fleet(policy):
        engines = [engine(), engine()]
        # replica 1 is the warm home: its pool already holds the
        # shared chain (least-loaded ties break toward replica 0)
        engines[1].submit(shared + [7], max_new_tokens=4, seed=0)
        engines[1].run_until_idle()
        return Router(engines, RequestQueue(), policy=policy)

    def serve_one(router, prompt):
        rid = router.submit(prompt, max_new_tokens=4, seed=0).id
        for _ in range(10000):
            router.tick()
            resp = router.response(rid)
            if resp is not None:
                assert resp.status == "ok", resp
                return resp
        raise AssertionError("request never finished")

    reg = get_registry()
    p0 = reg.counter("serve.fleet.prefix_placements").value
    ttfts = {"prefix": [], "least_loaded": []}
    for arm in ttfts:
        for i in range(repeats):
            router = fleet(RouterPolicy(placement=arm))
            resp = serve_one(router, shared + [11, 13 + i])
            ttfts[arm].append(resp.ttft)
            router.close()
    placements = reg.counter("serve.fleet.prefix_placements").value - p0

    # proactive replication: both sessions live on replica 0 push the
    # shared chain to refs=2; the controller ships it to replica 1
    rep0 = reg.counter("serve.fleet.kv_replicated").value
    router = Router(
        [engine(), engine()], RequestQueue(),
        policy=RouterPolicy(placement="prefix", kv_hot_refs=2))
    hot = list(rng.randint(1, hcfg.vocab, size=64))      # 8 blocks
    ra = router.submit(hot + [3], max_new_tokens=4, seed=0).id
    router.tick()
    rb = router.submit(hot + [5], max_new_tokens=4, seed=0).id
    for _ in range(10000):
        router.tick()
        if all(router.response(r) is not None for r in (ra, rb)):
            break
    replicated = reg.counter("serve.fleet.kv_replicated").value - rep0
    sibling_warm = router.replicas[1].transport.engine.backend.pool \
        .cached_prefix_blocks(hot)
    router.close()

    t_pre = min(ttfts["prefix"])
    t_ll = min(ttfts["least_loaded"])
    return {
        "prompt_len": len(shared) + 2,
        "kv_block_size": 8,
        "repeats": repeats,
        "prefix_placements": int(placements),
        "ttft_prefix_s": round(t_pre, 4),
        "ttft_least_loaded_s": round(t_ll, 4),
        "ttft_win_s": round(t_ll - t_pre, 4),
        "replicated_blocks": int(replicated),
        "sibling_warm_blocks": int(sibling_warm),
        "placement_found_prefix": bool(placements == repeats),
        "hot_chain_replicated": bool(replicated > 0
                                     and sibling_warm > 0),
    }


def _steady_state(router, make_req, duration_s, max_outstanding,
                  pace_s=0.005, on_tick=None):
    """Feed → measure → drain, with per-request wall latency. Keeps
    ``max_outstanding`` requests in flight for ``duration_s``, then
    drains to idle. ``make_req(i) -> (prompt, submit_kwargs)`` so a
    workload can vary max_new/priority/timeout_s per class;
    ``on_tick(now_s, router)`` is the chaos hook. Returns (records,
    submitted, elapsed_s) where records are (request_id, status,
    n_tokens, latency_s, t_deliver_s) — latency is wall
    submit→delivery as the CLIENT sees it, which for a disagg fleet
    spans prefill + KV handoff + decode."""
    sub_t = {}
    submitted, records = [], []  # (rid, status, ntok, wall_latency,
    t0 = time.monotonic()        #  t_deliver, ttft, engine_latency)

    def pump():
        for r in router.tick():
            now = time.monotonic() - t0
            records.append((r.request_id, r.status, len(r.tokens),
                            now - sub_t[r.request_id], now, r.ttft,
                            r.latency))

    i = 0
    while time.monotonic() - t0 < duration_s:
        while len(submitted) - len(records) < max_outstanding:
            p, kw = make_req(i)
            req = router.submit(p, seed=i, **kw)
            sub_t[req.id] = time.monotonic() - t0
            submitted.append(req.id)
            i += 1
        if on_tick is not None:
            on_tick(time.monotonic() - t0, router)
        pump()
        time.sleep(pace_s)
    deadline = time.monotonic() + 120.0
    while not router.idle:
        pump()
        time.sleep(pace_s)
        assert time.monotonic() < deadline, "drain never finished"
    elapsed = time.monotonic() - t0
    missing = [x for x in submitted if router.response(x) is None]
    assert not missing, f"requests with no terminal response: {missing}"
    return records, submitted, elapsed


DOC_LEN, DOC_NEW = 128, 4        # prefill load: 16 chunks in, 4 tokens out
CHAT_LEN, CHAT_NEW = 16, 32      # decode load: 2 chunks in, 32 tokens out
DISAGG_SLOTS = 3                 # per-replica slots in the disagg trial


def disagg_trial(seed=11, duration_s=4.0, deadline_s=0.28,
                 max_outstanding=8):
    """Disagg vs mixed at equal chips, scored as SLO goodput.

    Both arms: 2 replicas x DISAGG_SLOTS slots, per-replica tick
    threads, paged KV, a priority front queue with tiny engine queues
    (waiting happens where priority exists), and the same
    prefill-heavy two-class workload — "doc" requests (128-token
    prompt, 4 new tokens: pure chunked-prefill load, no SLO)
    interleaved 2:1 with "chat" requests (16-token prompt, 32 new
    tokens: decode load, priority, a decode-phase SLO). A chat scores
    its tokens only if its decode duration — ``Response.latency -
    Response.ttft``, the time its 16 decode chunks actually took —
    lands inside ``deadline_s``; chats carry 8x a doc's tokens, so
    the arm that protects decode cadence wins goodput. This is the
    DistServe framing: disaggregation trades first-token latency
    (the handoff hop; docs/fleet.md says so openly) for
    decode-latency SLO attainment, and the SLO is what this trial
    scores.

    The structural difference under measurement: a mixed engine's
    tick is run-to-completion — admissions first, each doc's full
    16-chunk prefill host-blocking, then ONE decode chunk for the
    live set — so every one of a chat's decode chunks queues behind
    whatever doc prefill bursts land that tick, and the chat's decode
    duration inflates at the MEDIAN, not just the tail. The disagg
    arm pins doc prefills to the prefill-only replica; the
    decode-only replica's tick thread issues the chat's chunks
    (resuming from the shipped prefix blocks) with nothing heavier
    than another chat in front. Same chips, same work — the decode
    interference is what the split removes."""
    hcfg = LMConfig(vocab=67, d_model=64, nhead=2, d_ff=128,
                    n_layers=4, seq_len=160, dropout=0.0)
    model = PipelinedLM(hcfg, 1)
    params = model.init(jax.random.key(8))
    gen_cfg = GenerationConfig(max_new_tokens=CHAT_NEW, temperature=0.0)

    def engine(phase):
        be = SingleDeviceSlotBackend(
            model, params, num_slots=DISAGG_SLOTS, max_len=160,
            gen=gen_cfg, kv_block_size=8, kv_pool_blocks=256,
            prefill_chunk=8, decode_chunk=2)
        # tiny engine queue: waiting happens at the PRIORITY front
        # queue (chats jump docs) instead of fifo behind a replica —
        # placement backpressure is what makes priority mean anything
        return ServeEngine(be, RequestQueue(capacity=2), phase=phase)

    def fleet(roles):
        trs = [InProcessTransport(engine(r), async_tick=True)
               for r in roles]
        cls = DisaggController if set(roles) != {"mixed"} \
            else FleetController
        return cls(trs, RequestQueue(capacity=256, policy="priority"),
                   policy=RouterPolicy(backoff_base_s=0.0))

    out = {}
    for arm, roles in (("mixed", ("mixed", "mixed")),
                       ("disagg", ("prefill", "decode"))):
        rng = np.random.RandomState(seed)
        docs = [rng.randint(1, hcfg.vocab, size=DOC_LEN).tolist()
                for _ in range(64)]
        chats = [rng.randint(1, hcfg.vocab, size=CHAT_LEN).tolist()
                 for _ in range(64)]
        kind_of = {}

        def make_req(i, _k=kind_of, _d=docs, _c=chats):
            # 2 docs : 1 chat — the prefill-heavy skew
            if i % 3 == 2:
                _k[i] = "chat"
                return _c[i // 3 % len(_c)], dict(
                    max_new_tokens=CHAT_NEW, priority=1)
            _k[i] = "doc"
            return _d[i % len(_d)], dict(max_new_tokens=DOC_NEW)

        ctl = fleet(roles)
        try:
            # warm through the CONTROLLER so each arm compiles exactly
            # the programs it will run: the mixed engines both classes'
            # full prefills + decode chunks, the disagg pair the
            # clamped prefill AND the destination's cached-prefix
            # resume. Each class served twice per round so the
            # resume-from-cache trace compiles too.
            for wp, mn in ((docs[0], DOC_NEW), (chats[0], CHAT_NEW)):
                for _ in range(2):
                    for _ in range(2):
                        ctl.submit(wp, max_new_tokens=mn, seed=7)
                    run_to_idle(ctl, pace_s=0.005)
            records, submitted, elapsed = _steady_state(
                ctl, make_req, duration_s, max_outstanding)
        finally:
            ctl.close()
        idx_of = {rid: i for i, rid in enumerate(submitted)}

        def decode_s(r):
            return None if r[5] is None else max(r[6] - r[5], 0.0)

        view = {}
        ok_toks = 0
        for kind in ("doc", "chat"):
            recs = [r for r in records
                    if kind_of[idx_of[r[0]]] == kind]
            ok = [r for r in recs if r[1] == "ok"]
            if kind == "chat":      # SLO-scored: decode cadence held
                good = [r for r in ok if decode_s(r) is not None
                        and decode_s(r) <= deadline_s]
            else:                   # docs carry no SLO
                good = ok
            ok_toks += sum(r[2] for r in good)
            e2e = sorted(r[3] for r in ok)
            dec = sorted(d for d in (decode_s(r) for r in ok)
                         if d is not None)
            view[kind] = {
                "requests": len(recs),
                "ok": len(ok),
                "slo_ok": len(good),
                "slo_ok_frac": round(len(good) / max(len(recs), 1),
                                     4),
                "e2e_p50_s": round(e2e[len(e2e) // 2], 4)
                if e2e else None,
                "decode_p50_s": round(dec[len(dec) // 2], 4)
                if dec else None,
                "decode_max_s": round(dec[-1], 4) if dec else None,
            }
        by_status = {}
        for r in records:
            by_status[r[1]] = by_status.get(r[1], 0) + 1
        out[arm] = {
            "replicas": len(roles),
            "roles": list(roles),
            "slots_total": len(roles) * DISAGG_SLOTS,
            "requests": len(submitted),
            "responses_by_status": by_status,
            "elapsed_s": round(elapsed, 3),
            "goodput_tokens_s": round(ok_toks / max(elapsed, 1e-9),
                                      1),
            "doc": view["doc"],
            "chat": view["chat"],
        }
    out["workload"] = {
        "doc": {"prompt_len": DOC_LEN, "max_new": DOC_NEW},
        "chat": {"prompt_len": CHAT_LEN, "max_new": CHAT_NEW,
                 "decode_slo_s": deadline_s, "priority": 1},
        "mix": "2 docs : 1 chat", "max_outstanding": max_outstanding,
        "duration_s": duration_s}
    out["disagg_beats_mixed"] = bool(
        out["disagg"]["goodput_tokens_s"]
        >= out["mixed"]["goodput_tokens_s"])
    return out


def disagg_kill_trial_proc(kill_role, seed, kill_after_s=2.0,
                           duration_s=6.0, max_outstanding=8):
    """SIGKILL one phase-specialized child mid-stream. 4 real
    processes — 2 prefill + 2 decode — under a DisaggController; every
    request crosses the prefill→handoff→decode boundary, and the kill
    lands while some are mid-crossing (shadow delivered but decode not
    yet placed, or decode in flight). The surviving role sibling must
    absorb the stream through the reclaim gate: all ids delivered
    exactly once, goodput recovers after the kill, and the
    shadow-aware token reconciliation still balances."""
    roles = ("prefill", "prefill", "decode", "decode")
    kill_idx = 1 if kill_role == "prefill" else 3
    trace_buf = TraceBuffer(maxlen=200_000)
    ctl = DisaggController(
        [ProcessReplicaTransport(dataclasses.replace(proc_spec(),
                                                     role=r))
         for r in roles],
        RequestQueue(capacity=256),
        policy=RouterPolicy(backoff_base_s=0.0,
                            heartbeat_timeout_s=5.0),
        event_log=trace_buf)
    rng = np.random.RandomState(seed)
    work = make_workload(4096, rng)
    kill_t = [None]

    def on_tick(now, router):
        if kill_t[0] is None and now >= kill_after_s:
            router.replicas[kill_idx].transport._proc.kill()
            kill_t[0] = now

    try:
        warm(ctl, len(roles))
        records, submitted, elapsed = _steady_state(
            ctl, lambda i: (work[i % len(work)][0],
                            {"max_new_tokens": work[i % len(work)][1]}),
            duration_s, max_outstanding, on_tick=on_tick)
        states = ctl.counts()
    finally:
        ctl.close()
    obs = obs_report(FleetObserver(ctl, parent_events=trace_buf.drain()),
                     submitted)
    assert kill_t[0] is not None, "run too short to reach the kill"
    kt = kill_t[0]

    def rate(lo, hi):
        return sum(r[2] for r in records
                   if r[1] == "ok" and lo <= r[4] < hi) \
            / max(hi - lo, 1e-9)

    w = min(1.0, kt, (elapsed - kt) / 2)
    before, during, after = (rate(kt - w, kt), rate(kt, kt + w),
                             rate(kt + w, elapsed))
    by_status = {}
    for r in records:
        by_status[r[1]] = by_status.get(r[1], 0) + 1
    return {
        "roles": list(roles),
        "killed_replica": kill_idx,
        "killed_role": kill_role,
        "kill_mode": "sigkill_process",
        "kill_at": round(kt, 3),
        "window": round(w, 3),
        "rate_unit": "tokens/s",
        "requests": len(submitted),
        "elapsed_s": round(elapsed, 3),
        "rate_before": round(before, 2),
        "rate_failover": round(during, 2),
        "rate_after": round(after, 2),
        "recovered_frac": round(after / max(before, 1e-9), 3),
        "survived_failover": during > 0.0 or after > 0.0,
        "responses_by_status": by_status,
        "exactly_once": len(records) == len(submitted),
        "replica_states": states,
        "obs": obs,
    }


def wire_chaos_trial(kind, seed, step=4, count=1, magnitude=2.0,
                     n_requests=16):
    """Adversarial faults on ONE replica's proc wire, full workload
    through the exactly-once ledger. ``wire_partition``: the covered
    outgoing frame is dropped and the wire goes dark for ``magnitude``
    seconds — the heal must lose nothing (retained-frame replay,
    sequence dedup) and duplicate nothing (a dup would trip the
    ledger's exactly-once raise and fail the drill loudly).
    ``wire_corrupt``: ``count`` consecutive frames are bit-flipped
    post-checksum — every one must be rejected WHOLE (CRC mismatch ->
    drop connection -> re-dial -> replay), never half-parsed into the
    dispatcher."""
    plan = ChaosPlan([Fault(kind, step=step, count=count, stage=1,
                            magnitude=magnitude)])
    transports = []
    for i in range(2):
        kw = dict(reconnect_timeout_s=15.0)
        if i == 1:
            kw.update(chaos=plan, chaos_replica=1)
        transports.append(ProcessReplicaTransport(proc_spec(), **kw))
    # heartbeat timeout ABOVE the partition hold: the drill is about
    # the wire healing under the health machine's nose, not failover
    ctl = FleetController(transports, RequestQueue(capacity=256),
                          policy=RouterPolicy(backoff_base_s=0.0,
                                              heartbeat_timeout_s=10.0))
    rng = np.random.RandomState(seed)
    work = make_workload(n_requests, rng)
    responses = {}
    try:
        warm(ctl, 2)
        t0 = time.monotonic()
        ids = [ctl.submit(p, max_new_tokens=m, seed=i).id
               for i, (p, m) in enumerate(work)]
        deadline = time.monotonic() + 120.0
        while not ctl.idle:
            for r in ctl.tick():
                assert r.request_id not in responses, \
                    f"duplicate terminal for {r.request_id}"
                responses[r.request_id] = r
            time.sleep(0.005)
            assert time.monotonic() < deadline, \
                f"{kind} drill never drained"
        elapsed = time.monotonic() - t0
        # one more heartbeat interval so the child's final counter
        # ship (crc rejects ride the hb frame) lands before we read it
        time.sleep(0.2)
        tr = transports[1]
        wire = {
            "resends": tr.wire_resends,
            "dup_suppressed": tr.wire_dup_suppressed,
            "crc_rejects_total": tr.crc_rejects_total,
        }
        fired = (tr._partition_until > 0.0 if kind == "wire_partition"
                 else wire["crc_rejects_total"] > 0)
        missing = [x for x in ids if x not in responses]
    finally:
        ctl.close()
    assert not missing, f"{kind}: requests with no terminal: {missing}"
    return {
        "kind": kind,
        "fault": {"step": step, "count": count, "magnitude": magnitude,
                  "replica": 1},
        "requests": len(ids),
        "elapsed_s": round(elapsed, 3),
        "fired": bool(fired),
        "wire": wire,
        "exactly_once": len(responses) == len(ids),
    }


def _ctl_worker_main(journal_dir, mode, seed, n_requests=40):
    """The controller half of the SIGKILL-restart drill, run as a
    child process of the bench. Builds a proc fleet journaling every
    lifecycle transition to ``journal_dir``, submits a workload,
    prints the submitted ids and a mid-flight marker on stdout, then
    ticks forever — the bench SIGKILLs this process and recovers from
    nothing but the journal plus the orphaned children."""
    journal = RequestJournal(journal_dir)
    policy = RouterPolicy(backoff_base_s=0.0, heartbeat_timeout_s=10.0)
    if mode == "disagg":
        roles = ("prefill", "prefill", "decode", "decode")
        ctl = DisaggController(
            [ProcessReplicaTransport(dataclasses.replace(proc_spec(),
                                                         role=r))
             for r in roles],
            RequestQueue(capacity=256), policy=policy, journal=journal)
    else:
        ctl = FleetController(
            [ProcessReplicaTransport(proc_spec()) for _ in range(3)],
            RequestQueue(capacity=256), policy=policy, journal=journal)
    for rep in ctl.replicas:
        journal.record_replica(rep.index, **rep.transport.rejoin_info())
    warm(ctl, len(ctl.replicas))
    rng = np.random.RandomState(seed)
    work = make_workload(n_requests, rng)
    ids = [ctl.submit(p, max_new_tokens=m, seed=i).id
           for i, (p, m) in enumerate(work)]
    print(json.dumps({"event": "submitted", "ids": ids}), flush=True)
    delivered = 0
    announced = False
    while True:
        delivered += len(ctl.tick())
        if not announced and delivered >= 2:
            # some terminals journaled, plenty still in flight: tell
            # the bench this is the adversarial moment to pull the plug
            print(json.dumps({"event": "midflight",
                              "delivered": delivered}), flush=True)
            announced = True
        time.sleep(0.002)


def ctl_restart_trial(mode, seed):
    """SIGKILL the CONTROLLER mid-stream, rebuild it from the journal.
    The controller (plus its journal WAL) lives in a separate process;
    its replica children survive the kill as orphans re-dialing the
    dead listener. The bench replays the WAL, re-binds the recorded
    ports in rejoin mode (re-registering the RUNNING children instead
    of spawning), reconciles placements against what each child still
    holds, and drains. Exactly-once across the two controller lives:
    pre-crash terminals (journaled) and post-recovery deliveries must
    partition the submitted id set — no id lost, none answered
    twice."""
    tmpdir = tempfile.mkdtemp(prefix="fleet-ctl-journal-")
    worker = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__),
         "--_ctl-worker", tmpdir, "--_ctl-mode", mode,
         "--seed", str(seed)],
        stdout=subprocess.PIPE, text=True)
    lines: "queue_mod.Queue[str]" = queue_mod.Queue()
    threading.Thread(target=lambda: [lines.put(ln) for ln in worker.stdout],
                     daemon=True).start()

    def next_event(timeout_s):
        line = lines.get(timeout=timeout_s)
        return json.loads(line)

    state = None
    ctl2 = None
    recovered = []
    try:
        sub = next_event(300.0)
        assert sub["event"] == "submitted", sub
        mid = next_event(120.0)
        assert mid["event"] == "midflight", mid
        os.kill(worker.pid, signal.SIGKILL)      # no goodbye
        worker.wait(timeout=30)
        t0 = time.monotonic()
        state = RequestJournal.recover(tmpdir)
        assert not state.clean, "a SIGKILL cannot leave a clean log"
        assert state.orphans, \
            "kill landed after the drain — nothing was in flight"
        assert sorted(state.replicas) == list(range(len(state.replicas)))
        transports = [
            ProcessReplicaTransport(
                ReplicaSpec(**state.replicas[i]["spec"]),
                rejoin=state.replicas[i])
            for i in sorted(state.replicas)]
        journal2 = RequestJournal(tmpdir)        # the WAL keeps growing
        cls = DisaggController if mode == "disagg" else FleetController
        ctl2 = cls.from_journal(
            state, transports, RequestQueue(capacity=256),
            journal=journal2,
            policy=RouterPolicy(backoff_base_s=0.0,
                                heartbeat_timeout_s=10.0))
        deadline = time.monotonic() + 180.0
        while not ctl2.idle:
            recovered.extend(ctl2.tick())
            time.sleep(0.005)
            assert time.monotonic() < deadline, \
                "recovered fleet never drained"
        elapsed = time.monotonic() - t0
        ctl2.close()
        ctl2 = None                              # closed cleanly
        journal2.close(clean=True)
    finally:
        if worker.poll() is None:
            worker.kill()
        if ctl2 is not None:
            try:
                ctl2.close()
            except Exception:
                pass
        # belt and braces: no orphaned replica child outlives the drill
        if state is not None:
            for rec in state.replicas.values():
                pid = rec.get("pid")
                if pid:
                    try:
                        os.kill(int(pid), signal.SIGKILL)
                    except (OSError, ProcessLookupError):
                        pass
        shutil.rmtree(tmpdir, ignore_errors=True)
    all_ids = sorted(state.requests)
    pre = set(state.terminal)
    post = [r.request_id for r in recovered]
    exactly_once = (sorted(pre | set(post)) == all_ids
                    and len(post) == len(set(post))
                    and not (pre & set(post)))
    return {
        "mode": mode,
        "kill_mode": "sigkill_controller",
        "requests": len(all_ids),
        "pre_crash_terminal": len(pre),
        "orphans_at_crash": len(state.orphans),
        "recovered_delivered": len(post),
        "journal_records": state.records,
        "recover_s": round(elapsed, 3),
        "exactly_once": bool(exactly_once),
    }


def saturation_trial(model, params, fleet, counts, seed,
                     duration_s=3.0, max_outstanding=12):
    """Steady-state goodput at N = counts[0]..counts[-1] replicas over
    the chosen transport, all replicas fed from the ONE front queue.
    Reports the front-queue bottleneck N: the smallest fleet within
    10% of the sweep's best goodput — past it, added replicas buy
    nothing (on this shared-core host the engines contend for the
    same processor, so the knee lands early; on a pod each replica
    owns its chips and the knee is where the front queue's
    single-threaded placement loop saturates)."""
    rng = np.random.RandomState(seed)
    work = make_workload(4096, rng)
    sweep = []
    for n in counts:
        router = make_fleet(model, params, n, fleet=fleet)
        try:
            warm(router, n)
            records, submitted, elapsed = _steady_state(
                router,
                lambda i: (work[i % len(work)][0],
                           {"max_new_tokens": work[i % len(work)][1]}),
                duration_s, max_outstanding)
        finally:
            router.close()
        ok = [r for r in records if r[1] == "ok"]
        sweep.append({
            "replicas": n,
            "slots_total": n * SLOTS,
            "requests": len(submitted),
            "ok": len(ok),
            "elapsed_s": round(elapsed, 3),
            "goodput_tokens_s": round(
                sum(r[2] for r in ok) / max(elapsed, 1e-9), 1),
        })
    best = max(s["goodput_tokens_s"] for s in sweep)
    sat = next(s["replicas"] for s in sweep
               if s["goodput_tokens_s"] >= 0.9 * best)
    return {"transport": fleet, "rate_unit": "tokens/s",
            "duration_s_per_point": duration_s,
            "max_outstanding": max_outstanding, "sweep": sweep,
            "best_goodput_tokens_s": best, "saturation_n": sat}


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="small run; single-line JSON summary")
    ap.add_argument("--fleet", choices=["inproc", "thread", "proc"],
                    default="inproc",
                    help="replica transport for the scaling + kill "
                         "trials (straggler/handoff trials are always "
                         "in-process)")
    ap.add_argument("--out", default=None,
                    help="also write the summary JSON here")
    ap.add_argument("--seed", type=int, default=0)
    # hidden: the controller half of the SIGKILL-restart drill
    ap.add_argument("--_ctl-worker", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--_ctl-mode", default="mixed", help=argparse.SUPPRESS)
    args = ap.parse_args()

    if args._ctl_worker:
        return _ctl_worker_main(args._ctl_worker, args._ctl_mode,
                                args.seed)

    t0 = time.perf_counter()
    model = PipelinedLM(CFG, 1)
    params = model.init(jax.random.key(0))

    n_requests = 24 if args.quick else 48
    replica_counts = (1, 3) if args.quick else (1, 2, 3)

    scaling = []
    for n in replica_counts:
        log(f"== scaling[{args.fleet}]: {n} replica(s), "
            f"{n_requests} requests")
        r = scaling_trial(model, params, n, n_requests, args.seed,
                          args.fleet)
        scaling.append(r)
        log(f"   {r}")

    log(f"== kill one of 3 mid-stream [{args.fleet}]")
    kill = kill_trial(model, params, 3, n_requests * 2, args.seed + 1,
                      kill_tick=6, window=4, fleet=args.fleet)
    log(f"   {kill}")

    log("== straggler: async ticks vs serial (N=3, in-process)")
    straggler = straggler_trial(model, params, n_requests, args.seed + 2)
    log(f"   {straggler}")

    log("== session-remap KV handoff TTFT (2 paged replicas)")
    handoff = handoff_trial(repeats=2 if args.quick else 3)
    log(f"   {handoff}")

    log("== prefix-aware placement + hot replication (2 paged replicas)")
    placement = prefix_placement_trial(repeats=2 if args.quick else 3)
    log(f"   {placement}")

    log("== disagg vs mixed at equal chips (prefill-heavy, deadlined)")
    disagg = disagg_trial(seed=args.seed + 3,
                          duration_s=3.0 if args.quick else 6.0)
    log(f"   {disagg}")

    log("== disagg SIGKILL drills: one prefill, then one decode (proc)")
    disagg_kills = {}
    for role in ("prefill", "decode"):
        disagg_kills[role] = disagg_kill_trial_proc(role, args.seed + 4)
        log(f"   kill {role}: {disagg_kills[role]}")

    log("== wire chaos drills: 2s partition, corruption storm (proc)")
    partition = wire_chaos_trial("wire_partition", args.seed + 6,
                                 magnitude=2.0)
    log(f"   partition: {partition}")
    corrupt = wire_chaos_trial("wire_corrupt", args.seed + 7, step=3,
                               count=15)
    log(f"   corrupt storm: {corrupt}")

    log("== controller SIGKILL + journal restart drills (proc)")
    ctl_restart = {}
    for mode in ("mixed", "disagg"):
        ctl_restart[mode] = ctl_restart_trial(mode, args.seed + 8)
        log(f"   {mode}: {ctl_restart[mode]}")

    log(f"== saturation sweep [{args.fleet}]: front-queue bottleneck")
    saturation = saturation_trial(
        model, params, args.fleet, (1, 2, 3) if args.quick
        else (1, 2, 3, 4), args.seed + 5,
        duration_s=2.5 if args.quick else 4.0)
    log(f"   {saturation}")

    stitch = kill["obs"]["trace_stitch"]
    handoff_beats_reprefill = bool(
        handoff["ttft_handoff_s"] < handoff["ttft_reprefill_s"])
    disagg_kills_ok = all(
        k["exactly_once"] and k["survived_failover"]
        and k["obs"]["reconcile"]["reconciled"]
        for k in disagg_kills.values())
    wire_ok = bool(partition["exactly_once"] and partition["fired"]
                   and corrupt["exactly_once"] and corrupt["fired"]
                   and corrupt["wire"]["crc_rejects_total"] > 0)
    restart_ok = all(r["exactly_once"] for r in ctl_restart.values())
    ok = bool(kill["exactly_once"] and kill["survived_failover"]
              and kill["recovered_frac"] > 0.3
              and straggler["async_beats_serial"]
              and handoff["handoff_moved_blocks"]
              and handoff_beats_reprefill
              and placement["placement_found_prefix"]
              and placement["hot_chain_replicated"]
              and disagg["disagg_beats_mixed"]
              and disagg_kills_ok
              and wire_ok and restart_ok
              and kill["obs"]["reconcile"]["reconciled"]
              and stitch["frac"] == 1.0
              and stitch["exactly_once"])
    summary = {
        "bench": "fleet", "rev": "r20",
        "quick": bool(args.quick),
        "fleet": args.fleet,
        "platform": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
        "slots_per_replica": SLOTS,
        "decode_chunk": CHUNK,
        "max_new_tokens": MAX_NEW,
        "contention": host_contention(),
        "scaling": scaling,
        "kill_one_of_n": kill,
        "async_vs_serial": straggler,
        "kv_handoff": handoff,
        "kv_prefix_placement": placement,
        "disagg_vs_mixed": disagg,
        "disagg_kill_drills": disagg_kills,
        "wire_chaos": {"partition": partition, "corrupt_storm": corrupt},
        "ctl_restart": ctl_restart,
        "saturation": saturation,
        "fleet_ok": ok,
        "wall_s": round(time.perf_counter() - t0, 1),
    }
    if args.out:
        with open(args.out, "w") as f:
            json.dump(summary, f, indent=2)
        log(f"wrote {args.out}")
    if args.quick:
        print(json.dumps({
            "transport": args.fleet,
            "goodput_1_replica_tokens_s":
                scaling[0]["goodput_tokens_s"],
            "goodput_3_replicas_tokens_s":
                scaling[-1]["goodput_tokens_s"],
            "kill_drop_frac": kill["drop_frac"],
            "kill_recovered_frac": kill["recovered_frac"],
            "exactly_once": kill["exactly_once"],
            "async_speedup": straggler["speedup"],
            "async_beats_serial": straggler["async_beats_serial"],
            "ttft_win_s": handoff["ttft_win_s"],
            "handoff_moved_blocks": handoff["handoff_moved_blocks"],
            "handoff_beats_reprefill": handoff_beats_reprefill,
            "disagg_goodput_tokens_s":
                disagg["disagg"]["goodput_tokens_s"],
            "mixed_goodput_tokens_s":
                disagg["mixed"]["goodput_tokens_s"],
            "disagg_beats_mixed": disagg["disagg_beats_mixed"],
            "disagg_kill_prefill_exactly_once":
                disagg_kills["prefill"]["exactly_once"],
            "disagg_kill_decode_exactly_once":
                disagg_kills["decode"]["exactly_once"],
            "partition_heals_exactly_once":
                partition["exactly_once"] and partition["fired"],
            "partition_dup_suppressed":
                partition["wire"]["dup_suppressed"],
            "corrupt_storm_ok":
                corrupt["exactly_once"] and corrupt["fired"],
            "wire_crc_rejects": corrupt["wire"]["crc_rejects_total"],
            "ctl_restart_exactly_once":
                ctl_restart["mixed"]["exactly_once"],
            "ctl_restart_disagg_exactly_once":
                ctl_restart["disagg"]["exactly_once"],
            "saturation_n": saturation["saturation_n"],
            "placement_ttft_win_s": placement["ttft_win_s"],
            "placement_found_prefix":
                placement["placement_found_prefix"],
            "hot_chain_replicated": placement["hot_chain_replicated"],
            "contended": summary["contention"]["contended"],
            "tokens_reconciled": kill["obs"]["reconcile"]["reconciled"],
            "trace_stitch_frac": stitch["frac"],
            "trace_stitch_exactly_once": stitch["exactly_once"],
            "slo_ok": kill["obs"]["slo"]["ok"],
            "fleet_ok": ok,
        }))
    else:
        print(json.dumps(summary, indent=2))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
