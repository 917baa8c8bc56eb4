"""Traffic kind ``serve_closed``: a closed loop of clients on one
``ServeEngine``, each sending its next request the moment its last returns.

Set-up builds the engine as ``apps/serve.py`` builds it, over weights the
benchmark makes from the seed; serves one request of every prefill bucket
and one full resident horizon; then runs the closed loop for a lead-in, so
that the window opens on live slots out of step with each other. The window
drives ``ServeEngine.submit`` and ``ServeEngine.tick`` and nothing else, and
closes with the first tick that ends at or after ``--seconds``.

The rate is all output tokens of the requests that finished ``ok`` inside
the window over the window's wall time. The requests in flight at either
end are cut: what they had produced before the opening is counted, what
they have produced at the close is not. That cut, of as many requests as
there are clients at each end, is most of the rate's spread from seed to
seed. The sum of each client's rate from its first reply to its last
inside the window cuts none, and stands beside the rate as the per-layer
``engine.client_tokens_per_s``.

With ``--trace 1`` the same window runs first, untraced, and gives the
numbers that need its length (the rate of the share of peak, the TTFT tail,
the clients' rates); the loop then goes on under the profiler for the
traffic file's ``trace_seconds``, which gives the device's times.

After the window the engine is freed and the plain reference runs once over
a sample, drawn from the seed, of the requests the window finished."""

from __future__ import annotations

import gc
import json
import math
import time

import jax.numpy as jnp
import numpy as np

import pb_core
import pb_trace
import pb_traffic

SPANS = ("engine.tick", "submit", "backend.prefill", "backend.decode")
TRACE_COUNTERS = ("decode_traces", "resident_traces", "prefill_traces")


def build_engine(cell, seed):
    from pipe_tpu.inference import GenerationConfig
    from pipe_tpu.serve import (BucketSpec, RequestQueue, ServeEngine,
                                SingleDeviceSlotBackend)
    cfg, fam = cell.cfg, cell.family
    e = cell.traffic["engine"]
    if e["kv"] != "slab":
        raise ValueError(f"engine.kv {e['kv']!r}: this driver builds slab")
    model = fam.build_model(cfg, 1)
    params = fam.serve_params(fam.reference.make_weights(cfg, seed))
    gen_cfg = GenerationConfig(max_new_tokens=e["max_new_tokens"],
                               temperature=e["temperature"])
    buckets = BucketSpec.pow2(min_len=e["bucket_min"],
                              max_len=e["bucket_max"])
    backend = SingleDeviceSlotBackend(
        model, params, num_slots=e["slots"],
        max_len=buckets.max_len + e["max_new_tokens"], gen=gen_cfg,
        buckets=buckets, decode_chunk=e["decode_chunk"],
        resident=e["resident"], resident_chunks=e["resident_chunks"])
    del params
    return ServeEngine(backend, RequestQueue(capacity=e["queue_capacity"],
                                             policy="fifo")), buckets


class Loop:
    """The closed loop: ``clients`` callers, one request each in flight."""

    def __init__(self, eng, source, clients):
        self.eng, self.source, self.clients = eng, source, clients
        self.spans = False         # host spans: on for the traced stretch
        self.owner = {}            # request id -> client
        self.sent = {}             # request id -> (prompt, max_new)
        self.done = []             # (time, Response)
        self.replies = [[] for _ in range(clients)]   # (time, Response)
        self.n_submitted = 0

    def submit(self, client):
        prompt, max_new = next(self.source)
        with pb_trace.span("submit", on=self.spans):
            req = self.eng.submit(prompt, max_new_tokens=max_new,
                                  seed=self.n_submitted)
        self.n_submitted += 1
        self.owner[req.id] = client
        self.sent[req.id] = (prompt, max_new)

    def start(self):
        for c in range(self.clients):
            self.submit(c)

    def turn(self):
        with pb_trace.span("engine.tick", on=self.spans):
            finished = self.eng.tick()
        now = time.perf_counter()
        for resp in finished:
            client = self.owner.pop(resp.request_id)
            self.done.append((now, resp))
            self.replies[client].append((now, resp))
            self.submit(client)

    def run_for(self, seconds):
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            self.turn()
        return t0, time.perf_counter()


class Probe:
    """Spans and counts around the calls into the backend, for the traced
    run only: real prompt tokens and padded bucket per prefill; steps, live
    slots and live cache rows per decode launch."""

    def __init__(self, eng, cfg, fam):
        self.eng, self.backend = eng, eng.backend
        self.prompt_tokens = self.padded_tokens = self.prefills = 0
        self.decode_steps = self.decode_launches = 0
        self.decode_bytes = 0.0
        self.w_bytes = fam.decode_weight_bytes(cfg)
        self.row_bytes = fam.kv_row_bytes(cfg)
        self._prefill, self._decode = self.backend.prefill, self.backend.decode
        self.backend.prefill = self.prefill
        self.backend.decode = self.decode

    def prefill(self, slot, prompt, seed, **kw):
        with pb_trace.span("backend.prefill"):
            out = self._prefill(slot, prompt, seed, **kw)
        self.prefills += 1
        self.prompt_tokens += len(prompt)
        self.padded_tokens += self.backend.buckets.bucket_for(len(prompt))
        return out

    def decode(self, live, **kw):
        rows0 = sum(len(s.req.prompt) + len(s.tokens)
                    for s in self.eng._slots if s is not None)
        with pb_trace.span("backend.decode"):
            toks, valid = self._decode(live, **kw)
        steps = int(toks.shape[1])
        n_live = int(np.asarray(live).sum())
        self.decode_launches += 1
        self.decode_steps += steps
        # each step reads the weights once and every live slot's rows so
        # far; rows grow by one a step
        self.decode_bytes += steps * self.w_bytes + self.row_bytes * (
            steps * rows0 + n_live * steps * (steps - 1) / 2)
        return toks, valid

    def remove(self):
        self.backend.prefill, self.backend.decode = self._prefill, self._decode


def warm_up(eng, buckets, cell, seed):
    """One request of every bucket, then a full resident horizon with every
    slot live; nothing of it is measured."""
    e = cell.traffic["engine"]
    rng = np.random.default_rng(seed)
    vocab = cell.cfg["vocab"]
    horizon = e["decode_chunk"] * e["resident_chunks"]
    sizes = list(buckets.lengths)
    while len(sizes) < e["slots"]:
        sizes.append(buckets.lengths[0])
    for n in sizes:
        eng.submit(rng.integers(1, vocab, size=int(n)).tolist(),
                   max_new_tokens=min(horizon + e["decode_chunk"] + 1,
                                      e["max_new_tokens"]))
    for resp in eng.run_until_idle():
        if resp.status != "ok":
            raise RuntimeError(f"warm-up request {resp.request_id} ended "
                               f"{resp.status}/{resp.finish_reason}")


def client_rates(replies, t0, t1):
    """For each client its output tokens per second from its first reply
    inside ``[t0, t1]`` to its last: the ``ok`` tokens of the replies after
    the first, over the time between the two. No request is cut. A client
    with fewer than two replies inside has no rate: None."""
    out = []
    for mine in replies:
        inside = [(t, r) for t, r in mine if t0 <= t <= t1]
        if len(inside) < 2:
            out.append(None)
            continue
        tokens = sum(len(r.tokens) for _, r in inside[1:]
                     if r.status == "ok")
        out.append(tokens / (inside[-1][0] - inside[0][0]))
    return out


def percentile(values, q):
    """Nearest-rank percentile of all ``values``."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


def sample_finished(done, sent, seed, n):
    """``n`` of the window's finished-ok requests drawn from the seed, the
    longest among them."""
    ok = [r for _, r in done if r.status == "ok" and r.tokens]
    if not ok:
        return []
    longest = max(ok, key=lambda r: r.prompt_len + len(r.tokens))
    rest = [r for r in ok if r is not longest]
    rng = np.random.default_rng(seed)
    pick = rng.permutation(len(rest))[:max(n - 1, 0)]
    return [longest] + [rest[i] for i in pick]


def reference_gaps(cell, seed, sample, sent, precision="f32"):
    """The widest gap by which a served token's logit lies below the
    reference's best, over the sample; with ``precision`` below float32
    also the widest gap of the token that precision puts first. One
    reference forward over each prompt with its served tokens, ``rows`` at a
    time, padded to the engine's longest sequence."""
    cfg, tr = cell.cfg, cell.traffic
    ref = cell.family.reference
    e = tr["engine"]
    width = e["bucket_max"] + e["max_new_tokens"]
    rows = tr["check_rows"]
    weights = ref.make_weights(cfg, seed)
    worst = worst_ctl = 0.0
    served_tokens = 0
    for i in range(0, len(sample), rows):
        block = sample[i:i + rows]
        tokens = np.zeros((rows, width), np.int32)
        chosen = np.zeros((rows, width), np.int32)
        mask = np.zeros((rows, width), bool)
        for r, resp in enumerate(block):
            prompt = sent[resp.request_id][0]
            seq = list(prompt) + list(resp.tokens)
            tokens[r, :len(seq)] = seq
            # position p predicts token p + 1
            lo, hi = len(prompt) - 1, len(seq) - 1
            chosen[r, lo:hi] = resp.tokens
            mask[r, lo:hi] = True
            served_tokens += len(resp.tokens)
        logits = ref.forward(weights, jnp.asarray(tokens), cfg)
        gaps = np.asarray(ref.gaps_below_best(logits, jnp.asarray(chosen)))
        worst = max(worst, float(gaps[mask].max()))
        if precision != "f32":
            low = ref.forward(weights, jnp.asarray(tokens), cfg,
                              precision=precision)
            first = jnp.argmax(low, axis=-1).astype(jnp.int32)
            del low
            g2 = np.asarray(ref.gaps_below_best(logits, first))
            worst_ctl = max(worst_ctl, float(g2[mask].max()))
        del logits
    del weights
    return {"served_logit_gap": worst, "control_logit_gap": worst_ctl,
            "served_tokens": served_tokens, "requests": len(sample)}


def compare(checks, done, sent, gap, limits):
    """Every ``ok`` request has the length it asked for, and ``gap`` (the
    widest by which a token put first lies below the reference's best
    logit; None where nothing was compared) is within its limit."""
    wrong = sum(1 for _, r in done if r.status == "ok"
                and len(r.tokens) != sent[r.request_id][1])
    checks.add("ok_requests_of_wrong_length", wrong,
               limits["ok_requests_of_wrong_length"])
    checks.add("served_logit_gap", gap, limits["served_logit_gap"])


def serve_window(cell, seed, seconds, trace_seconds=0.0, trace_dir=None,
                 setup_done=None, mark=lambda phase: None):
    """Build, warm, lead in, measure; then, for ``trace_seconds``, go on
    under the profiler. Returns the loop, the window's bounds and finished
    requests, the counters' growth over the window, and the traced
    stretch's probe."""
    from pipe_tpu.obs.telemetry import get_registry
    tr = cell.traffic
    reg = get_registry()
    mark("imports")
    eng, buckets = build_engine(cell, seed)
    mark("weights_and_engine")
    warm_up(eng, buckets, cell, seed)
    mark("warm_up")
    loop = Loop(eng, pb_traffic.requests(tr, seed, cell.cfg["vocab"]),
                tr["clients"])
    loop.start()
    loop.run_for(tr["lead_in_s"])
    n_lead = len(loop.done)
    gc.collect()
    gc.freeze()

    def counters():
        out = {k: reg.counter(f"serve.engine.{k}").value
               for k in TRACE_COUNTERS}
        out["host_sec"] = reg.timer("serve.engine.host_sec").total
        out["host_syncs"] = reg.counter("serve.engine.host_syncs").value
        return out

    c0 = counters()
    if setup_done is not None:
        setup_done()
    t0, t1 = loop.run_for(seconds)
    c1 = counters()
    n_window = len(loop.done)
    probe = None
    if trace_seconds:
        probe = Probe(eng, cell.cfg, cell.family)
        loop.spans = True
        with pb_trace.capture(trace_dir):
            loop.run_for(trace_seconds)
        probe.remove()
    c2 = counters()
    gc.unfreeze()
    return {"eng": eng, "loop": loop, "t0": t0, "t1": t1,
            "t_end": time.perf_counter(), "done": loop.done[n_lead:n_window],
            "probe": probe, "growth": {k: c1[k] - c0[k] for k in c0},
            "traces_after": {k: c2[k] - c1[k] for k in TRACE_COUNTERS}}


def run(ctx) -> dict:
    cell, seed = ctx.cell, ctx.seed
    tr = cell.traffic
    devices = ctx.devices[:cell.chips]
    w = serve_window(cell, seed, ctx.seconds,
                     trace_seconds=tr["trace_seconds"] if ctx.trace else 0.0,
                     trace_dir=ctx.trace_dir, setup_done=ctx.setup_done,
                     mark=ctx.mark)
    loop, done, t0, t1 = w["loop"], w["done"], w["t0"], w["t1"]
    window_s = t1 - t0
    ok = [r for _, r in done if r.status == "ok"]
    out_tokens = sum(len(r.tokens) for r in ok)
    prompt_tokens = sum(r.prompt_len for r in ok)
    rate = out_tokens / window_s
    per_client = client_rates(loop.replies, t0, t1)
    client_rate = (None if any(c is None for c in per_client)
                   else sum(per_client))
    ttfts = [r.ttft if r.status == "ok" and r.ttft is not None else window_s
             for _, r in done]
    peak = pb_core.memory_peak_bytes(devices)
    ctx.side_file({
        "window": [t0, t1], "finished": len(done),
        "ok": len(ok), "out_tokens": out_tokens, "growth": w["growth"],
        "serve_tokens_per_s": rate, "client_tokens_per_s": client_rate,
        "per_client_tokens_per_s": per_client,
        "finished_at": [round(t - t0, 4) for t, _ in done],
        "ttft": [r.ttft for _, r in done],
        "latency": [r.latency for _, r in done],
        "tokens": [len(r.tokens) for _, r in done],
        "compiles": [[round(ts - t0, 4), secs]
                     for ts, secs in ctx.clock.compiles]})
    if not done:
        raise RuntimeError(
            f"no request finished in {window_s:.1f} s: nothing to report")

    sent = loop.sent
    probe, growth, t_end = w["probe"], w["growth"], w["t_end"]
    traces = {k: growth[k] + w["traces_after"][k] for k in TRACE_COUNTERS}
    del w, loop
    gc.collect()
    sample = sample_finished(done, sent, seed, tr["check_requests"])
    gaps = reference_gaps(cell, seed, sample, sent)
    compare(ctx.checks, done, sent,
            gaps["served_logit_gap"] if gaps["requests"] else None,
            cell.limits)

    facts = {
        "kind": "serve", "window_s": window_s, "t_end": t_end,
        "out_tokens": out_tokens, "prompt_tokens": prompt_tokens,
        "requests": len(done), "host_sec": growth["host_sec"],
        "host_syncs": growth["host_syncs"], "window_traces": traces,
        "ttft_p95_ms": 1e3 * percentile(ttfts, 0.95),
        "client_tokens_per_s": client_rate,
        "spans": SPANS, "checked": gaps,
    }
    if probe is not None:
        facts["probe"] = {
            k: getattr(probe, k) for k in (
                "prompt_tokens", "padded_tokens", "prefills", "decode_steps",
                "decode_launches", "decode_bytes")}
    return {
        "attempted": len(done), "failed": len(done) - len(ok),
        "end_to_end": {"serve_tokens_per_s": rate,
                       "serve_ttft_p95_ms": facts["ttft_p95_ms"]},
        "memory_peak_bytes": peak, "facts": facts,
    }


def readings(cell, seeds, devices, control=True, faults=True, seconds=8.0):
    """For ``tools/readings.py``: for each seed a short window at the cell's
    own load, then the served tokens' widest gap and the float8 control's,
    both against the float32 reference and each under the cell's committed
    limits: ``correct`` has to read true for the program and false for the
    control in its place."""
    del devices, faults
    out = []
    for seed in seeds:
        w = serve_window(cell, seed, seconds)
        done, sent = w["done"], w["loop"].sent
        del w
        gc.collect()
        sample = sample_finished(done, sent, seed,
                                 cell.traffic["check_requests"])
        row = dict(reference_gaps(cell, seed, sample, sent,
                                  precision="fp8" if control else "f32"),
                   seed=seed, finished=len(done))
        sides = {"program": "served_logit_gap"}
        if control:
            sides["control_fp8"] = "control_logit_gap"
        for side, key in sides.items():
            checks = pb_core.Checks()
            compare(checks, done, sent,
                    row[key] if row["requests"] else None, cell.limits)
            row[side + "_correct"] = checks.correct
        out.append(row)
        print(json.dumps(row), flush=True)
    return out
