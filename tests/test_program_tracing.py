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
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipe_tpu.inference import GenerationConfig
from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
from pipe_tpu.obs import events as ev
from pipe_tpu.obs.telemetry import get_registry
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
    assert parents[ev.SERVE_RETIRE] == {ev.SERVE_TICK}
    assert parents[ev.SERVE_DECODE_DONE] == {ev.SERVE_TICK}


def test_serve_spans_carry_their_stats(served):
    by = {}
    for name, _, _, stats in served["spans"]:
        by.setdefault(name, []).append(stats)
    assert set(by[ev.SERVE_TICK][0]) == {"tick", "live", "queued"}
    assert by[ev.SERVE_TICK][0]["queued"] == 2
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
                             ev.SERVE_DECODE_SYNC)]
    captured = sorted((n, _parent(spans, i))
                      for i, (n, *_) in enumerate(spans))
    assert logged == captured and len(logged) >= 8
    # the wait for a first token is the engine's span, under the launch
    assert (ev.SERVE_PREFILL_SYNC, ev.SERVE_DECODE) in logged
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


def test_a_span_costs_next_to_nothing_with_no_session_open():
    import timeit

    def one():
        with ev.span(ev.SERVE_TICK, tick=1, live=2, queued=0):
            pass

    per_call = min(timeit.repeat(one, number=2000, repeat=5)) / 2000
    assert per_call < 50e-6       # measured 0.9 us; the bound is a guard


def test_device_scope_takes_only_the_declared_names():
    with ev.device_scope(ev.ATTENTION), ev.device_scope(ev.REMAT_SCOPE):
        pass
    with pytest.raises(ValueError, match="not one of"):
        ev.device_scope("atention")
