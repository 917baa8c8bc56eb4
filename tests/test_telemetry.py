"""Unified runtime telemetry (docs/observability.md): registry semantics,
structured JSONL events, StepReport math, xplane round-trip + per-stage
timeline attribution, and the executors' dispatch instrumentation.

The no-op contract matters as much as the happy path: a disabled registry
must hand back shared null instruments (no allocation, no clock reads) and
``NULL_EVENT_LOG`` must swallow spans without touching the filesystem —
the Trainer leaves its telemetry call sites unconditional on that basis.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipe_tpu.core import microbatch as mb
from pipe_tpu.core.schedule import bubble_fraction
from pipe_tpu.obs import events as ev
from pipe_tpu.obs.meters import stage_timeline_from_trace
from pipe_tpu.obs.telemetry import (MetricsRegistry, NULL_INSTRUMENT,
                                    StepReport, get_registry, null_registry,
                                    set_registry, train_flops_per_token)
from pipe_tpu.obs.xplane import (TraceEvent, TraceLine, TracePlane,
                                 encode_xspace, parse_xspace)

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))

import timeline_report  # noqa: E402

WIDTH = 8


@pytest.fixture
def registry():
    """Fresh registry installed as the process default; restored after."""
    prev = get_registry()
    reg = MetricsRegistry()
    set_registry(reg)
    yield reg
    set_registry(prev)


# ---------- registry semantics ----------

def test_counter_gauge_timer_histogram(registry):
    registry.counter("c").inc()
    registry.counter("c").inc(4)
    assert registry.counter("c").value == 5
    registry.gauge("g").set(2.5)
    assert registry.gauge("g").value == 2.5
    t = registry.timer("t")
    t.observe(1.0)
    t.observe(2.0)
    assert t.count == 2 and t.total == 3.0 and t.last == 2.0
    # EWMA after [1.0, 2.0] at alpha=0.1: 1.0 then 0.9*1.0 + 0.1*2.0
    assert t.ewma == pytest.approx(1.1)
    h = registry.histogram("h")
    for v in [0.001, 0.002, 0.004, 1.0]:
        h.observe(v)
    s = h.summary()
    assert s["count"] == 4 and s["min"] == 0.001 and s["max"] == 1.0
    assert s["sum"] == pytest.approx(1.007)
    # percentiles report the bucket's upper edge: monotone, >= the value
    assert h.percentile(0.5) >= 0.002
    assert h.percentile(0.99) >= 1.0


def test_instruments_are_interned_per_name(registry):
    assert registry.counter("x") is registry.counter("x")
    assert registry.timer("y") is registry.timer("y")


def test_timer_context_manager(registry):
    with registry.timer("ctx").time():
        pass
    assert registry.timer("ctx").count == 1
    with registry.histogram("hctx").time():
        pass
    assert registry.histogram("hctx").summary()["count"] == 1


def test_snapshot_and_scalars(registry):
    registry.counter("a.b").inc(3)
    registry.gauge("a.g").set(7.0)
    registry.timer("a.t").observe(0.5)
    registry.histogram("a.h").observe(0.25)
    snap = registry.snapshot()
    assert snap["a.b"] == 3
    assert snap["a.g"] == 7.0
    assert snap["a.t"]["count"] == 1
    assert snap["a.h"]["count"] == 1
    flat = registry.scalars()
    assert flat["a.b"] == 3.0 and flat["a.g"] == 7.0
    assert "a.t.ewma" in flat and "a.h.p50" in flat
    registry.reset()
    assert registry.snapshot() == {}


# ---------- no-op contract when disabled ----------

def test_disabled_registry_hands_back_shared_null_instrument():
    reg = null_registry()
    assert reg.counter("anything") is NULL_INSTRUMENT
    assert reg.histogram("other") is NULL_INSTRUMENT
    # nothing is allocated or recorded
    reg.counter("anything").inc(10)
    reg.gauge("g").set(1.0)
    with reg.timer("t").time():
        pass
    assert reg.snapshot() == {}


def test_disabled_registry_no_observe_calls(monkeypatch):
    """Call-count check: the null time() context must not route through
    observe (zero per-use overhead beyond a dict-free attribute hop)."""
    calls = []
    monkeypatch.setattr(type(NULL_INSTRUMENT), "observe",
                        lambda self, s: calls.append(s))
    reg = MetricsRegistry(enabled=False)
    for _ in range(100):
        with reg.timer("t").time():
            pass
        reg.counter("c").inc()
    assert calls == []
    assert reg._instruments == {}


def test_null_event_log_writes_nothing(tmp_path):
    log = ev.NULL_EVENT_LOG
    with log.span(ev.STEP, step=0):
        log.event("anything", x=1)
    log.flush()
    log.close()
    assert os.listdir(tmp_path) == []


# ---------- structured event log ----------

def test_event_log_jsonl_roundtrip_nested_spans(tmp_path):
    path = str(tmp_path / "events.jsonl")
    with ev.EventLog(path) as log:
        with log.span(ev.STEP, step=0) as step_id:
            with log.span(ev.STAGE, stage=1) as stage_id:
                with log.span(ev.MICROBATCH, microbatch=2):
                    pass
            log.event("profile_trace", path="/tmp/x")
        assert stage_id != step_id
    records = ev.EventLog.read(path)
    assert records[0]["kind"] == "log_open"
    by_kind = {}
    for r in records:
        by_kind.setdefault(r["kind"], []).append(r)
    # spans close inside-out: each child links its parent's id
    mbr, = by_kind[ev.MICROBATCH]
    st, = by_kind[ev.STAGE]
    sp, = by_kind[ev.STEP]
    assert mbr["parent"] == st["id"] and st["parent"] == sp["id"]
    assert sp["parent"] is None and sp["step"] == 0
    assert all(r["dur"] >= 0 for r in (mbr, st, sp))
    assert by_kind["profile_trace"][0]["parent"] == sp["id"]
    # every line is independently json-parseable (the JSONL contract)
    with open(path) as f:
        for line in f:
            json.loads(line)


def test_event_log_metrics_snapshot(tmp_path, registry):
    registry.counter("k").inc(2)
    path = str(tmp_path / "events.jsonl")
    with ev.EventLog(path) as log:
        log.metrics_snapshot(registry)
    records = ev.EventLog.read(path)
    snap = [r for r in records if r["kind"] == "metrics"][0]
    assert snap["metrics"]["k"] == 2


# ---------- StepReport math ----------

def test_step_report_synthetic_timings():
    r = StepReport.compute(step=3, wall_sec=0.5, tokens=4096, n_stages=4,
                           chunks=8, checkpoint="except_last",
                           schedule="1f1b",
                           analytic_bubble=bubble_fraction(8, 4))
    assert r.tokens_per_sec == pytest.approx(8192.0)
    assert r.tokens_per_sec_per_chip == pytest.approx(2048.0)
    assert r.analytic_bubble == pytest.approx((4 - 1) / (8 + 4 - 1))
    assert r.mfu is None  # no model_cfg: throughput-only report
    j = r.to_json()
    assert j["metric"] == "train_tokens_per_sec_per_chip"
    assert j["value"] == pytest.approx(2048.0)
    assert j["unit"] == "tokens/s/chip"
    assert j["analytic_bubble"] == pytest.approx(
        round(bubble_fraction(8, 4), 4))
    for k in ("n_stages", "chunks", "checkpoint", "schedule", "mfu", "hfu",
              "measured_bubble", "measured_bubble_method", "final_loss"):
        assert k in j


def test_step_report_mfu_math():
    from pipe_tpu.models.transformer_lm import LMConfig
    cfg = LMConfig().tiny()
    req_tok, hw_tok = train_flops_per_token(cfg, "never", 4)
    # peak chosen so per-chip flops run at half of it => mfu = 0.5 exactly
    tokens, wall, n = 1000, 2.0, 2
    per_chip = tokens / wall / n
    r = StepReport.compute(step=0, wall_sec=wall, tokens=tokens, n_stages=n,
                           chunks=4, checkpoint="never", model_cfg=cfg,
                           peak_flops=req_tok * per_chip * 2)
    assert r.mfu == pytest.approx(0.5)
    assert r.hfu == pytest.approx(0.5 * hw_tok / req_tok)
    assert r.hfu >= r.mfu  # hardware flops include recompute


def test_step_report_scalar_items():
    r = StepReport.compute(step=0, wall_sec=1.0, tokens=100, loss=2.0,
                           analytic_bubble=0.3,
                           memory={"cpu:0": {"peak_bytes_in_use": 2 ** 30}})
    items = dict(r.scalar_items())
    assert items["telemetry/tokens_per_sec"] == pytest.approx(100.0)
    assert items["telemetry/loss"] == 2.0
    assert items["telemetry/analytic_bubble"] == pytest.approx(0.3)
    assert items["telemetry/peak_gib/cpu:0"] == pytest.approx(1.0)


# ---------- xplane round-trip + timeline attribution ----------

def _synthetic_planes(ms=1_000_000):
    """Two device planes running an m=4, n=2 forward wave: stage j busy
    1ms per chunk, chunk i at cycle i + j."""
    planes = []
    for j in range(2):
        evs = [TraceEvent(name=f"jit_step/chunk{i}-stage{j}/fusion",
                          start_ns=(i + j) * ms, duration_ns=ms)
               for i in range(4)]
        planes.append(TracePlane(
            name=f"/device:TPU:{j}",
            lines=[TraceLine(name="XLA Ops", timestamp_ns=0, events=evs)]))
    return planes


def test_xplane_encode_parse_roundtrip():
    planes = _synthetic_planes()
    parsed = parse_xspace(encode_xspace(planes))
    assert [p.name for p in parsed] == [p.name for p in planes]
    for orig, back in zip(planes, parsed):
        assert [l.name for l in back.lines] == [l.name for l in orig.lines]
        for lo, lb in zip(orig.lines, back.lines):
            assert [(e.name, e.start_ns, e.duration_ns) for e in lb.events] \
                == [(e.name, e.start_ns, e.duration_ns) for e in lo.events]


def test_stage_timeline_from_synthetic_device_trace(tmp_path):
    with open(tmp_path / "host.xplane.pb", "wb") as f:
        f.write(encode_xspace(_synthetic_planes()))
    tl = stage_timeline_from_trace(str(tmp_path))
    assert tl["source"] == "device"
    assert sorted(tl["stages"]) == [0, 1]
    for j in (0, 1):
        st = tl["stages"][j]
        assert st["busy_sec"] == pytest.approx(4e-3)
        assert sorted(st["chunks"]) == [0, 1, 2, 3]
    lo, hi = tl["span"]
    assert (hi - lo) / 1e9 == pytest.approx(5e-3)  # cycles 0..4 inclusive


def test_stage_timeline_graceful_without_tagged_events(tmp_path):
    tl = stage_timeline_from_trace(str(tmp_path))  # empty dir
    assert tl == {"source": None, "span": (0.0, 0.0), "stages": {}}


def test_timeline_report_summary_and_render(tmp_path):
    with open(tmp_path / "host.xplane.pb", "wb") as f:
        f.write(encode_xspace(_synthetic_planes()))
    tl = stage_timeline_from_trace(str(tmp_path))
    summary = timeline_report.summarize(tl, "1f1b", 4, 2)
    assert summary["source"] == "device"
    assert summary["analytic_bubble"] == pytest.approx(bubble_fraction(4, 2))
    # 2 stages x 4ms busy over a 5ms span => 1 - 8/10
    assert summary["measured_bubble"] == pytest.approx(0.2)
    text = timeline_report.render(tl, summary, width=40)
    assert "stage 0|" in text and "stage 1|" in text

    empty = stage_timeline_from_trace(str(tmp_path / "nope"))
    fallback = timeline_report.render(
        empty, timeline_report.summarize(empty, "1f1b", 4, 2), width=40)
    assert "no chunk{i}-stage{j}" in fallback


# ---------- executor dispatch instrumentation ----------

def _uniform_pipe(n_stages=2):
    from pipe_tpu import Linear, Pipe, Sequential
    from pipe_tpu.parallel.mesh import make_mesh
    seq = Sequential([Linear(WIDTH) for _ in range(4)])
    params = seq.init(jax.random.key(0), jnp.zeros((2, WIDTH)))
    mesh = make_mesh(n_stages, 1, devices=jax.devices()[:n_stages])
    pipe = Pipe(seq, chunks=4, checkpoint="never", mesh=mesh,
                schedule="1f1b")
    grouped, off = [], 0
    for wdt in pipe.balance:
        grouped.append(params[off:off + wdt])
        off += wdt
    packed = pipe.shard_params(grouped)
    return pipe, packed


def _mse(out, tgt):
    return jnp.mean((out - tgt[:, None]) ** 2, axis=-1)


def test_uniform_fastpath_taken_and_gauged(registry):
    pipe, packed = _uniform_pipe()
    x = jax.random.normal(jax.random.key(1), (8, WIDTH))
    y = jnp.sum(jnp.sin(x), axis=-1)
    loss, grads = pipe.loss_and_grad(packed, x, targets=y, loss_fn=_mse)
    assert pipe._train_executor.uniform_fastpath is True
    assert registry.gauge("pipe.uniform_fastpath").value == 1
    assert registry.counter("pipe.lowerings.fastpath").value >= 1

    # pin the fast path against the general switch lowering
    from pipe_tpu.parallel.hetero_scheduled import HeteroScheduledPipeline
    orig = HeteroScheduledPipeline._branches_uniform
    HeteroScheduledPipeline._branches_uniform = \
        lambda self, low, *, train: False
    try:
        pipe_sw, packed_sw = _uniform_pipe()
        loss_sw, grads_sw = pipe_sw.loss_and_grad(packed_sw, x, targets=y,
                                                  loss_fn=_mse)
    finally:
        HeteroScheduledPipeline._branches_uniform = orig
    assert pipe_sw._train_executor.uniform_fastpath is False
    assert registry.gauge("pipe.uniform_fastpath").value == 0
    assert registry.counter("pipe.lowerings.switch").value >= 1
    np.testing.assert_allclose(float(loss), float(loss_sw), rtol=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(grads_sw)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)


def test_uniform_probe_verdict_cached(registry):
    """A re-lowering with identical (treedefs, boundary shapes, train)
    must reuse the cached verdict — counted as a hit, not re-traced."""
    from pipe_tpu.parallel.hetero_scheduled import HeteroScheduledPipeline
    pipe, packed = _uniform_pipe()
    x = jax.random.normal(jax.random.key(1), (8, WIDTH))
    y = jnp.sum(jnp.sin(x), axis=-1)

    def run():
        # a FRESH jit wrapper always retraces, re-running the executor's
        # Python lowering (where the probe lives)
        return jax.jit(lambda p, xx, yy: pipe.loss_and_grad(
            p, xx, targets=yy, loss_fn=_mse))(packed, x, y)

    run()
    misses0 = registry.counter("pipe.uniform_probe.cache_misses").value
    assert misses0 >= 1
    probes = []
    orig = HeteroScheduledPipeline._probe_branches_uniform
    HeteroScheduledPipeline._probe_branches_uniform = \
        lambda self, low, *, train: probes.append(1) or orig(
            self, low, train=train)
    try:
        run()
    finally:
        HeteroScheduledPipeline._probe_branches_uniform = orig
    assert probes == []
    assert registry.counter("pipe.uniform_probe.cache_hits").value >= 1
    assert registry.counter(
        "pipe.uniform_probe.cache_misses").value == misses0


def test_scheduled_lowering_counters(registry):
    """The raw table executor counts LOWERINGS (trace-time events — the
    compile/retrace signal): a cached jit call adds none, a fresh jit
    wrapper adds one."""
    from pipe_tpu.ops.layers import Linear
    from pipe_tpu.parallel.mesh import make_mesh
    from pipe_tpu.parallel.scheduled import ScheduledPipeline
    from pipe_tpu.parallel.spmd import stack_stage_params

    layer = Linear(WIDTH)
    params = [layer.init(jax.random.fold_in(jax.random.key(0), j),
                         jnp.zeros((1, WIDTH))) for j in range(2)]

    def stage_fn(p, h, ctx):
        return jnp.tanh(layer.apply(p, h))

    def pre_fn(p, x, ctx):
        return x

    def post_fn(p, h, x_mb, ctx):
        return jnp.sum((h - 1.0) ** 2, axis=-1)

    mesh = make_mesh(2, 1, devices=jax.devices()[:2])
    sched = ScheduledPipeline(mesh, stage_fn, pre_fn=pre_fn, post_fn=post_fn,
                              checkpoint="never", schedule="1f1b")
    x = jax.random.normal(jax.random.key(1), (8, WIDTH))
    xs, _ = mb.stack_scatter(x, 4)
    w = jnp.ones(xs.shape[:2], jnp.float32)
    stacked = stack_stage_params(params)

    ctr = registry.counter("scheduled.loss_and_grad.lowerings")
    before = ctr.value
    f = jax.jit(sched.loss_and_grad)
    f(stacked, {}, {}, xs, w)
    assert ctr.value == before + 1
    f(stacked, {}, {}, xs, w)          # compile-cache hit: no retrace
    assert ctr.value == before + 1
    # a distinct function object forces a retrace => one more lowering
    jax.jit(lambda *a: sched.loss_and_grad(*a))(stacked, {}, {}, xs, w)
    assert ctr.value == before + 2
    assert registry.gauge("scheduled.cycles").value > 0


# ---------- train-loop smoke: JSONL + StepReport on CPU ----------

def test_trainer_emits_events_and_step_reports(tmp_path, registry):
    import dataclasses as dc
    from pipe_tpu.data import lm_text
    from pipe_tpu.models.transformer_lm import LMConfig
    from pipe_tpu.train.loop import Trainer, TrainerConfig

    model_cfg = dc.replace(LMConfig().tiny(), n_layers=2)
    cfg = TrainerConfig(batch_size=8, eval_batch_size=8, bptt=16, chunks=4,
                        checkpoint="never", n_stages=2, schedule="gpipe",
                        telemetry_dir=str(tmp_path))
    rng = np.random.RandomState(0)
    source = lm_text.batchify(
        rng.randint(0, model_cfg.vocab, size=4096).astype(np.int32), 8)
    trainer = Trainer(model_cfg, cfg, devices=jax.devices()[:2])
    state, metrics = trainer.train_epoch(source, max_steps=3, log_every=2)
    trainer.events.close()

    path = tmp_path / "events.jsonl"
    assert path.exists()
    records = ev.EventLog.read(str(path))
    kinds = [r["kind"] for r in records]
    assert kinds[0] == "log_open"
    assert kinds.count(ev.STEP) == 3
    reports = [r for r in records if r["kind"] == "step_report"]
    assert len(reports) == 3
    for r in reports:
        assert r["analytic_bubble"] == pytest.approx(
            round(bubble_fraction(cfg.chunks, cfg.n_stages), 4))
        assert r["tokens"] == cfg.batch_size * cfg.bptt
        assert r["unit"] == "tokens/s/chip"
        # the CPU has no published peak: no utilization is reported
        assert r["mfu"] is None and r["hfu"] is None
    assert reports[0]["compile_inclusive"] is True
    assert reports[-1]["compile_inclusive"] is False
    # the same run feeds the process registry + a final snapshot record
    assert registry.counter("train.steps").value == 3
    snaps = [r for r in records if r["kind"] == "metrics"]
    assert snaps and snaps[-1]["metrics"]["train.steps"] == 3


def test_peak_flops_known_cpu_and_unknown_accelerator(monkeypatch):
    """A CPU has no peak (None); a known chip has its published one; an
    accelerator that is not in the table raises — never a v5e by default."""
    import types

    from pipe_tpu.obs.telemetry import peak_flops_per_chip

    assert peak_flops_per_chip() is None          # the suite runs on CPU

    def fake(platform, kind):
        return lambda: [types.SimpleNamespace(platform=platform,
                                              device_kind=kind)]

    monkeypatch.setattr(jax, "devices", fake("tpu", "TPU v5 lite"))
    assert peak_flops_per_chip() == 197e12
    monkeypatch.setattr(jax, "devices", fake("tpu", "TPU v9 hypothetical"))
    with pytest.raises(ValueError, match="no published bf16 peak"):
        peak_flops_per_chip()
