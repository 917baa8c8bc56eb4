"""The program's own tracing (``pipe_tpu/obs/events.py``), under a real
``jax.profiler.trace`` on the CPU: the serve tick's and the train loop's host
spans reach the capture under their names, nesting and stats, and reach an
``EventLog`` the same; the counters beside them count the same work; every
device scope the step and the resident program use is in their lowered
text; and the scopes are metadata only (the compiled HLO, metadata stripped,
is the same with ``jax.named_scope`` patched out).
"""

import contextlib
import glob
import logging
import os
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipe_tpu.inference import GenerationConfig
from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
from pipe_tpu.obs import events as ev
from pipe_tpu.obs.telemetry import (MetricsRegistry, get_registry, labelled,
                                    set_registry)
from pipe_tpu.serve import (BucketSpec, RequestQueue, ServeEngine,
                            SingleDeviceSlotBackend)
from pipe_tpu.train.loop import Trainer, TrainerConfig

CFG = LMConfig().tiny()
PROMPTS = ([1, 2, 3], [4, 5, 6, 7, 8])


def _capture(logdir):
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    return jax.profiler.trace(str(logdir), profiler_options=options)


def _program_spans(logdir):
    """``[(name, start, end, stats)]`` of the capture's ``serve.*``,
    ``train.*`` and ``step`` host spans, in time order."""
    from jax.profiler import ProfileData
    out = []
    for path in glob.glob(os.path.join(str(logdir), "plugins", "profile",
                                       "*", "*.xplane.pb")):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                for e in line.events:
                    if e.name in ev.SPAN_KINDS:
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns,
                                    dict(e.stats)))
    return sorted(out, key=lambda r: (r[1], -r[2]))


def _parent(spans, i):
    """The name of the innermost span that holds ``spans[i]``."""
    _, start, end, _ = spans[i]
    holds = [(e - s, n) for j, (n, s, e, _) in enumerate(spans)
             if j != i and s <= start and end <= e]
    return min(holds)[1] if holds else None


def _engine(event_log=None, **kw):
    model = PipelinedLM(CFG, n_stages=2)
    backend = SingleDeviceSlotBackend(
        model, model.init(jax.random.key(0)), num_slots=2, max_len=16,
        gen=GenerationConfig(max_new_tokens=8, temperature=0.0),
        buckets=BucketSpec.pow2(min_len=4, max_len=8), decode_chunk=2, **kw)
    return ServeEngine(backend, RequestQueue(capacity=8, policy="fifo"),
                       event_log=event_log)


def _serve(eng):
    eng.submit(PROMPTS[0], max_new_tokens=5)
    eng.submit(PROMPTS[1], max_new_tokens=6)
    return eng.run_until_idle()


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """A resident engine, warmed, then served once under the profiler with
    the backend's decode calls recorded from outside."""
    logdir = tmp_path_factory.mktemp("serve_capture")
    eng = _engine(resident=True, resident_chunks=2)
    _serve(eng)
    shapes, decode = [], eng.backend.decode

    def recording(live, **kw):
        toks, valid = decode(live, **kw)
        shapes.append(toks.shape)
        return toks, valid

    eng.backend.decode = recording
    reg = get_registry()
    names = ("decode_steps", "decode_launches", "prompt_tokens",
             "padded_prompt_tokens", "tokens", "admitted")
    before = {n: reg.counter(f"serve.engine.{n}").value for n in names}
    with _capture(logdir):
        responses = _serve(eng)
    growth = {n: reg.counter(f"serve.engine.{n}").value - before[n]
              for n in names}
    return {"spans": _program_spans(logdir), "shapes": shapes,
            "growth": growth, "responses": responses}


def test_serve_spans_nest_as_the_tick_runs(served):
    spans = served["spans"]
    parents = {}
    for i, (name, *_) in enumerate(spans):
        parents.setdefault(name, set()).add(_parent(spans, i))
    assert parents[ev.SERVE_TICK] == {None}
    assert parents[ev.SERVE_REAP] == {ev.SERVE_TICK}
    assert parents[ev.SERVE_ADMIT] == {ev.SERVE_TICK}
    assert parents[ev.SERVE_PREFILL] == {ev.SERVE_ADMIT}
    # the first token is waited for once the launch is in the queue
    assert parents[ev.SERVE_PREFILL_SYNC] == {ev.SERVE_DECODE}
    assert parents[ev.SERVE_DECODE] == {ev.SERVE_TICK}
    assert parents[ev.SERVE_DECODE_LAUNCH] == {ev.SERVE_DECODE}
    assert parents[ev.SERVE_DECODE_SYNC] == {ev.SERVE_DECODE}
    # the sync's two halves: the wait for the round count, then the reads
    assert parents[ev.SERVE_DECODE_WAIT] == {ev.SERVE_DECODE_SYNC}
    assert parents[ev.SERVE_DECODE_FETCH] == {ev.SERVE_DECODE_SYNC}
    # a first token lands once its launch is queued, behind its own wait
    assert parents[ev.SERVE_FIRST_TOKEN] == {ev.SERVE_DECODE}
    assert parents[ev.SERVE_RETIRE] == {ev.SERVE_TICK}
    assert parents[ev.SERVE_DECODE_DONE] == {ev.SERVE_TICK}


def test_serve_spans_carry_their_stats(served):
    by = {}
    for name, _, _, stats in served["spans"]:
        by.setdefault(name, []).append(stats)
    assert all(set(t) == {"tick", "live", "queued", "away_ms"}
               for t in by[ev.SERVE_TICK])
    assert by[ev.SERVE_TICK][0]["queued"] == 2
    # ``run_until_idle`` comes straight back: the caller is away for
    # microseconds, and before the first tick for no time at all
    assert all(0 <= t["away_ms"] < 50 for t in by[ev.SERVE_TICK])
    syncs = len(by[ev.SERVE_DECODE_SYNC])
    assert [set(w) for w in by[ev.SERVE_DECODE_WAIT]] == [{"rounds"}] * syncs
    assert [w["rounds"] for w in by[ev.SERVE_DECODE_WAIT]] == [
        d["chunks"] for d in by[ev.SERVE_DECODE_DONE]]
    # the token buffer and the per-round counts: two reads a launch here
    assert all(f["reads"] == 2 and f["bytes"] > 0
               for f in by[ev.SERVE_DECODE_FETCH])
    firsts = by[ev.SERVE_FIRST_TOKEN]
    assert [set(f) for f in firsts] == [{
        "request", "slot", "queued_ms", "admit_ms", "launch_ms",
        "ttft_ms"}] * 2
    for f, resp in zip(sorted(firsts, key=lambda f: f["request"]),
                       sorted(served["responses"],
                              key=lambda r: r.request_id)):
        assert f["request"] == resp.request_id
        assert f["ttft_ms"] == pytest.approx(1e3 * resp.ttft)
        assert f["queued_ms"] + f["admit_ms"] + f["launch_ms"] == \
            pytest.approx(f["ttft_ms"])
        assert min(f["queued_ms"], f["admit_ms"], f["launch_ms"]) >= 0
    admits = by[ev.SERVE_ADMIT]
    assert [a["prompt_len"] for a in admits] == [3, 5]
    assert all(set(a) == {"request", "trace", "slot", "prompt_len",
                          "queued_ms"} and a["queued_ms"] >= 0
               for a in admits)
    # spans of one request share ``request`` (admit) and ``slot`` (prefill)
    assert [(p["slot"], p["prompt_len"], p["bucket"])
            for p in by[ev.SERVE_PREFILL]] == [
        (a["slot"], a["prompt_len"], b) for a, b in zip(admits, (4, 8))]
    assert len({a["request"] for a in admits}) == 2
    assert all(set(d) == {"steps", "chunks", "live", "rows", "emitted",
                          "early_exit"} for d in by[ev.SERVE_DECODE_DONE])
    assert sum(r["finished"] for r in by[ev.SERVE_RETIRE]) == 2
    # rows the launch's first step attends over: each live slot's prompt
    # and the one token sampled so far
    assert by[ev.SERVE_DECODE_DONE][0]["rows"] == (3 + 1) + (5 + 1)
    assert by[ev.SERVE_DECODE_DONE][0]["live"] == 2


def test_decode_done_counts_what_the_backend_returned(served):
    done = [s for n, _, _, s in served["spans"] if n == ev.SERVE_DECODE_DONE]
    assert [d["steps"] for d in done] == [s[1] for s in served["shapes"]]
    assert all(d["steps"] == d["chunks"] * 2 for d in done)   # chunk of 2
    g = served["growth"]
    assert g["decode_launches"] == len(done)
    assert g["decode_steps"] == sum(d["steps"] for d in done)
    assert g["tokens"] == sum(d["emitted"] for d in done)
    # tokens produced = serve.engine.tokens + serve.engine.admitted: the
    # first token of a request is counted by the second
    produced = sum(len(r.tokens) for r in served["responses"])
    assert produced == g["tokens"] + g["admitted"] == 5 + 6


def test_prompt_counters_say_what_the_prefill_spans_say(served):
    prefills = [s for n, _, _, s in served["spans"] if n == ev.SERVE_PREFILL]
    g = served["growth"]
    assert g["prompt_tokens"] == sum(p["prompt_len"] for p in prefills) == 8
    assert g["padded_prompt_tokens"] == sum(p["bucket"] for p in prefills)
    assert g["padded_prompt_tokens"] == 4 + 8


def _trainer(**kw):
    tcfg = TrainerConfig(n_stages=1, n_data=1, schedule="1f1b",
                         checkpoint="except_last", batch_size=8, bptt=16,
                         chunks=4, lr=1e-3, **kw)
    return Trainer(CFG, tcfg, devices=jax.devices()[:1])


def _corpus(steps=6):
    rng = np.random.default_rng(0)
    return rng.integers(1, CFG.vocab, size=(16 * steps + 1, 8)).astype(
        np.int32)


def test_train_spans_and_the_trace_counter(tmp_path):
    trainer = _trainer()
    traces = get_registry().counter("train.step_traces")
    before = traces.value
    with _capture(tmp_path):
        _, info = trainer.train_epoch(_corpus(), max_steps=3, log_every=0)
    assert info["steps"] == 3
    spans = _program_spans(tmp_path)
    for kind in (ev.STEP, ev.TRAIN_BATCH, ev.TRAIN_DISPATCH):
        assert [s["step"] for n, _, _, s in spans if n == kind] == [0, 1, 2]
    for i, (name, *_) in enumerate(spans):
        if name in (ev.TRAIN_BATCH, ev.TRAIN_DISPATCH):
            assert _parent(spans, i) == ev.STEP
    # the compile's sync after step 0, and the last loss
    assert [s["step"] for n, _, _, s in spans if n == ev.TRAIN_SYNC] == [0, 2]
    # the counter reads what tracing made it: the step's body ran in
    # Python at least once and at most once an entry of the jit's cache
    # (an entry made for the same avals under another placement reuses the
    # trace), and a further epoch of the same shapes adds none
    traced = traces.value - before
    assert 1 <= traced <= trainer._step_fn._cache_size()
    trainer.train_epoch(_corpus(), max_steps=2, log_every=0)
    assert traces.value - before == traced


def test_event_log_spans_carry_the_same_names_and_parents(tmp_path):
    log = ev.EventLog(str(tmp_path / "events.jsonl"))
    eng = _engine(event_log=log)
    with _capture(tmp_path / "capture"):
        _serve(eng)
    log.close()
    records = [r for r in ev.EventLog.read(log.path) if "dur" in r]
    kinds = {r["id"]: r["kind"] for r in records}
    logged = sorted((r["kind"], kinds.get(r["parent"])) for r in records)
    spans = [s for s in _program_spans(tmp_path / "capture")
             # the backend holds no log: its spans reach the profiler alone
             if s[0] not in (ev.SERVE_PREFILL, ev.SERVE_DECODE_LAUNCH,
                             ev.SERVE_DECODE_SYNC, ev.SERVE_DECODE_WAIT,
                             ev.SERVE_DECODE_FETCH)]
    captured = sorted((n, _parent(spans, i))
                      for i, (n, *_) in enumerate(spans))
    assert logged == captured and len(logged) >= 8
    # the wait for a first token is the engine's span, under the launch,
    # and so is the record of its landing
    assert (ev.SERVE_PREFILL_SYNC, ev.SERVE_DECODE) in logged
    assert (ev.SERVE_FIRST_TOKEN, ev.SERVE_DECODE) in logged
    done = [r for r in records if r["kind"] == ev.SERVE_DECODE_DONE]
    assert all(r["steps"] == 2 for r in done)       # not resident: a chunk
    # what is known only at a span's end reaches the log's record too
    assert sum(r["finished"] for r in records
               if r["kind"] == ev.SERVE_RETIRE) == 2


def _op_names(lowered) -> set:
    return set(re.findall(r'loc\("([^"]+)"', lowered.as_text(
        debug_info=True)))


def _scopes_in(names) -> set:
    found = set()
    for scope in ev.DEVICE_SCOPES + (ev.REMAT_SCOPE,):
        rx = re.compile(r"(^|[/(])" + scope + r"([/)]|$)")
        if any(rx.search(n) for n in names):
            found.add(scope)
    return found


def _lower_step(trainer):
    state = trainer.init_state()
    src = _corpus(2)
    x, w = trainer._make_x(src[:16].T.copy(), src[1:17].T.copy())
    return trainer._step_fn.lower(state, x, w, jax.random.key(0),
                                  jnp.float32(1e-3))


def _lower_resident(eng):
    fn, args = eng.backend.decode_program()
    return fn.lower(*args)


def test_the_train_step_names_every_scope_it_uses():
    assert _scopes_in(_op_names(_lower_step(_trainer()))) == {
        ev.EMBED, ev.ATTENTION, ev.FFN, ev.HEAD, ev.LOSS, ev.OPTIMIZER,
        ev.REMAT_SCOPE}


def test_the_resident_program_names_every_scope_it_uses():
    eng = _engine(resident=True, resident_chunks=2)
    assert _scopes_in(_op_names(_lower_resident(eng))) == {
        ev.EMBED, ev.ATTENTION, ev.FFN, ev.HEAD, ev.KV_CACHE}


def _hlo_without_metadata(lowered) -> str:
    text = lowered.compile().as_text()
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    # the module's tables of files, functions, lines and stack frames
    return re.sub(r"(?ms)^(FileNames|FunctionNames|FileLocations|"
                  r"StackFrames)\n.*?\n\n", "", text)


@pytest.mark.parametrize("program", ["train_step", "resident"])
def test_scopes_are_metadata_only(program, monkeypatch):
    """The compiled program, metadata stripped, is the same with the
    scopes as with ``jax.named_scope`` patched to a null context."""
    def build():
        if program == "train_step":
            return _lower_step(_trainer())
        return _lower_resident(_engine(resident=True, resident_chunks=2))

    with_scopes = build()
    assert _scopes_in(_op_names(with_scopes))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = build()
    assert not _scopes_in(_op_names(without))
    assert _hlo_without_metadata(with_scopes) == _hlo_without_metadata(
        without)


def _tick_span():
    with ev.span(ev.SERVE_TICK, tick=1, live=2, queued=0, away_ms=0.5):
        pass


def _sync_spans():
    with ev.span(ev.SERVE_DECODE_SYNC):
        with ev.span(ev.SERVE_DECODE_WAIT) as wait:
            wait.set_metadata(rounds=3)
        with ev.span(ev.SERVE_DECODE_FETCH) as fetch:
            fetch.set_metadata(reads=4, bytes=4096)


def _first_token_span():
    with ev.NULL_EVENT_LOG.span(ev.SERVE_FIRST_TOKEN, request=7, slot=1,
                                queued_ms=0.1, admit_ms=2.0, launch_ms=30.0,
                                ttft_ms=32.1):
        pass


@pytest.mark.parametrize("site", [_tick_span, _sync_spans,
                                  _first_token_span])
def test_a_span_costs_next_to_nothing_with_no_session_open(site):
    import timeit
    per_call = min(timeit.repeat(site, number=2000, repeat=5)) / 2000
    assert per_call < 50e-6       # measured 0.9 us; the bound is a guard


# ---------------------------------------------------------------------------
# the launch cycle on an injected clock


class _Clock:
    """A clock that stands still but for what a test adds: ``jump`` seconds
    of wall time and ``cpu`` of the process's on entering the ``nth`` span
    of a ``kind`` (``events.span`` is patched to tell it)."""

    def __init__(self, monkeypatch):
        self.now = self.cpu = 0.0
        self.jumps = {}            # kind -> [[entries to go, wall, cpu]]
        real = ev.span

        def span(kind, **attrs):
            for jump in self.jumps.get(kind, ()):
                jump[0] -= 1
                if jump[0] == 0:
                    self.now += jump[1]
                    self.cpu += jump[2]
            return real(kind, **attrs)

        monkeypatch.setattr(ev, "span", span)
        monkeypatch.setattr(time, "process_time", lambda: self.cpu)

    def __call__(self):
        return self.now

    def jump_in(self, kind, nth, wall, cpu=0.0):
        self.jumps.setdefault(kind, []).append([nth, wall, cpu])


@pytest.fixture
def fresh_registry():
    old = set_registry(MetricsRegistry())
    try:
        yield get_registry()
    finally:
        set_registry(old)


def _clocked_engine(clock, event_log=None, model=None, **kw):
    model = model or PipelinedLM(CFG, n_stages=2)
    backend = SingleDeviceSlotBackend(
        model, model.init(jax.random.key(0)), num_slots=2, max_len=32,
        gen=GenerationConfig(max_new_tokens=24, temperature=0.0),
        buckets=BucketSpec.pow2(min_len=4, max_len=8), decode_chunk=1,
        resident=True, **kw)
    eng = ServeEngine(backend, RequestQueue(capacity=8, clock=clock),
                      event_log=event_log)
    dispatched, decode = [], backend.decode

    def recording(live, **kwargs):
        out = decode(live, **kwargs)
        dispatched.append(backend.launch_phases.dispatched)
        return out

    backend.decode = recording
    return eng, dispatched


def _cycle_timers(reg):
    return {p: reg.timer(f"serve.engine.cycle.{p}_sec")
            for p in ev.CYCLE_PHASES}


def test_the_four_timers_add_up_to_the_time_between_dispatches(
        monkeypatch, fresh_registry):
    clock = _Clock(monkeypatch)
    eng, dispatched = _clocked_engine(clock, resident_chunks=2)
    # every launch's wait 30 ms, fetch 2 ms, retirement 1 ms, its
    # dispatch 0.5 ms, and the caller away 4 ms between ticks
    for n in range(1, 40):
        clock.jump_in(ev.SERVE_DECODE_WAIT, n, 0.030)
        clock.jump_in(ev.SERVE_DECODE_FETCH, n, 0.002)
        clock.jump_in(ev.SERVE_RETIRE, n, 0.001)
        clock.jump_in(ev.SERVE_DECODE_LAUNCH, n, 0.0005)
    eng.submit(PROMPTS[0], max_new_tokens=9)
    eng.submit(PROMPTS[1], max_new_tokens=12)
    while not eng.idle:
        eng.tick()
        clock.now += 0.004
    timers = _cycle_timers(fresh_registry)
    n = len(dispatched)
    assert n >= 5 and {t.count for t in timers.values()} == {n - 1}
    assert sum(t.total for t in timers.values()) == pytest.approx(
        dispatched[-1] - dispatched[0])
    assert timers["wait"].total == pytest.approx(0.030 * (n - 1))
    assert timers["fetch"].total == pytest.approx(0.002 * (n - 1))
    assert timers["caller"].total == pytest.approx(0.004 * (n - 1))
    # what is left: retirement, and the next launch's dispatch
    assert timers["turn"].total == pytest.approx(0.0015 * (n - 1))
    assert not eng.slow_cycles
    assert fresh_registry.counter("serve.engine.stalls").value == 0


def _sdar_model():
    from pipe_tpu.models.sdar import PipelinedSdar, SdarConfig
    return PipelinedSdar(SdarConfig().tiny(), 1)


@pytest.mark.parametrize("round_", ["plain", "block"])
def test_the_three_stages_add_up_to_ttft(round_, monkeypatch, tmp_path,
                                         fresh_registry):
    clock = _Clock(monkeypatch)
    log = ev.EventLog(str(tmp_path / "events.jsonl"))
    eng, _ = _clocked_engine(
        clock, event_log=log, resident_chunks=2,
        model=_sdar_model() if round_ == "block" else None)
    for n in range(1, 40):
        clock.jump_in(ev.SERVE_ADMIT, n, 0.003)       # an admission's work
        clock.jump_in(ev.SERVE_PREFILL_SYNC, n, 0.010)   # its program
        clock.jump_in(ev.SERVE_DECODE_WAIT, n, 0.050)    # a launch
    ids = [eng.submit(PROMPTS[0], max_new_tokens=5).id]
    clock.now += 0.007                  # the second is sent 7 ms later ...
    ids.append(eng.submit(PROMPTS[1], max_new_tokens=6).id)
    clock.now += 0.002                  # ... and both wait 2 ms more
    responses = {r.request_id: r for r in eng.run_until_idle()}
    log.close()
    records = ev.EventLog.read(log.path)
    spans = {r["request"]: r for r in records
             if r["kind"] == ev.SERVE_FIRST_TOKEN}
    prefill = {r["request"]: r for r in records
               if r["kind"] == ev.REQUEST and r.get("stage") == "prefill"}
    # the second request's admission starts when the first's ends
    want_queued = {ids[0]: 9.0, ids[1]: 2.0 + 3.0}
    want_admit = {ids[0]: 3.0 + 3.0, ids[1]: 3.0}
    # a plain round's first token is its prefill's, read once the launch
    # is queued (the second behind the first); a block round's first block
    # comes with the launch
    want_launch = ({ids[0]: 10.0, ids[1]: 20.0} if round_ == "plain"
                   else {ids[0]: 50.0, ids[1]: 50.0})
    for rid in ids:
        sp, resp = spans[rid], responses[rid]
        assert sp["queued_ms"] == pytest.approx(want_queued[rid])
        assert sp["admit_ms"] == pytest.approx(want_admit[rid])
        assert sp["launch_ms"] == pytest.approx(want_launch[rid])
        assert sp["queued_ms"] + sp["admit_ms"] + sp["launch_ms"] == \
            pytest.approx(1e3 * resp.ttft) == pytest.approx(sp["ttft_ms"])
        assert {k: prefill[rid][k] for k in (
            "queued_ms", "admit_ms", "launch_ms")} == {
            k: sp[k] for k in ("queued_ms", "admit_ms", "launch_ms")}
        assert prefill[rid]["ttft"] == resp.ttft


def _stalls(reg, phase):
    return (reg.counter("serve.engine.stalls").value,
            reg.timer(labelled("serve.engine.stall_sec",
                               phase=phase)).total)


@pytest.mark.parametrize("phase, kind", [
    ("wait", ev.SERVE_DECODE_WAIT), ("fetch", ev.SERVE_DECODE_FETCH),
    ("turn", ev.SERVE_RETIRE), ("caller", None)])
def test_a_stalled_phase_is_named_once(phase, kind, monkeypatch, caplog,
                                       fresh_registry):
    clock = _Clock(monkeypatch)
    eng, _ = _clocked_engine(clock, resident_chunks=2)
    for n in range(1, 40):
        clock.jump_in(ev.SERVE_DECODE_WAIT, n, 0.020)
    if kind is not None:
        clock.jump_in(kind, 3, 1.0, cpu=0.02)      # in the third launch
    eng.submit(PROMPTS[0], max_new_tokens=12)
    eng.submit(PROMPTS[1], max_new_tokens=12)
    with caplog.at_level(logging.WARNING):
        for tick in range(6):
            eng.tick()
            if kind is None and tick == 2:
                clock.now += 1.0
                clock.cpu += 0.02
    wall = 1.02 if phase == "wait" else 1.0
    (slow,) = eng.slow_cycles
    # a turn is known when the next launch is dispatched, a caller's
    # absence when it comes back: one tick on; a wait and a fetch at once
    assert slow.tick == (2 if phase in ("wait", "fetch") else 3)
    assert (slow.phase, slow.wall_s, slow.cpu_s) == (
        phase, pytest.approx(wall), pytest.approx(0.02))
    assert _stalls(fresh_registry, phase) == (1, pytest.approx(wall))
    (record,) = [r for r in caplog.records if r.levelno == logging.WARNING]
    assert record.getMessage() == (
        f"serve engine: tick {slow.tick} stood {wall:.2f} s in {phase}, "
        "cpu 0.02 s")


def test_a_long_launch_of_many_rounds_is_no_stall(monkeypatch, caplog,
                                                  fresh_registry):
    clock = _Clock(monkeypatch)
    eng, _ = _clocked_engine(clock, resident_chunks=8)
    for n in range(1, 10):              # 0.1 s a round, 8 rounds a launch
        clock.jump_in(ev.SERVE_DECODE_WAIT, n, 0.8)
    eng.submit(PROMPTS[0], max_new_tokens=24)
    eng.submit(PROMPTS[1], max_new_tokens=24)
    with caplog.at_level(logging.WARNING):
        responses = eng.run_until_idle()
    assert [len(r.tokens) for r in responses] == [24, 24]
    assert fresh_registry.counter("serve.engine.decode_launches").value == 3
    assert not eng.slow_cycles and not caplog.records
    assert fresh_registry.counter("serve.engine.stalls").value == 0
    # the same rounds three times over their mean and a quarter second
    # more: that is one
    eng.submit(PROMPTS[0], max_new_tokens=24)
    clock.jump_in(ev.SERVE_DECODE_WAIT, 1, 0.25 + 3 * 0.8)
    eng.run_until_idle()
    assert [s.phase for s in eng.slow_cycles] == ["wait"]


def test_an_idle_engine_charges_its_caller_nothing(monkeypatch, caplog,
                                                   fresh_registry):
    """Between two requests there is no launch to hold up: the open cycle
    is dropped when a tick ends idle, and the caller's absence is neither
    a phase's time nor a stall."""
    clock = _Clock(monkeypatch)
    eng, _ = _clocked_engine(clock, resident_chunks=2)
    for _ in range(2):
        eng.submit(PROMPTS[0], max_new_tokens=6)
        with caplog.at_level(logging.WARNING):
            eng.run_until_idle()
        clock.now += 30.0
    assert _cycle_timers(fresh_registry)["caller"].total == 0.0
    assert not eng.slow_cycles and not caplog.records


def test_a_stalled_train_step_is_named_once(caplog, fresh_registry):
    trainer = _trainer()
    trainer.registry = fresh_registry
    offset, step_fn, calls = [0.0], trainer._step_fn, [0]
    trainer._clock = lambda: time.perf_counter() + offset[0]

    def stepping(*args):
        calls[0] += 1
        if calls[0] == 4:              # the fourth step's call stands 30 s
            offset[0] += 30.0
        return step_fn(*args)

    trainer._step_fn = stepping
    with caplog.at_level(logging.WARNING):
        _, info = trainer.train_epoch(_corpus(), max_steps=6, log_every=0)
    assert info["steps"] == 6
    assert fresh_registry.counter("train.stalls").value == 1
    stood = fresh_registry.timer(labelled("train.stall_sec",
                                          phase=ev.TRAIN_DISPATCH))
    assert stood.count == 1 and 30.0 <= stood.total < 40.0
    (record,) = [r for r in caplog.records if r.levelno == logging.WARNING
                 and "trainer" in r.getMessage()]
    assert re.fullmatch(r"trainer: step 3 stood 3\d\.\d\d s in "
                        r"train\.dispatch, cpu \d+\.\d\d s",
                        record.getMessage())


def test_device_scope_takes_only_the_declared_names():
    with ev.device_scope(ev.ATTENTION), ev.device_scope(ev.REMAT_SCOPE):
        pass
    with pytest.raises(ValueError, match="not one of"):
        ev.device_scope("atention")
