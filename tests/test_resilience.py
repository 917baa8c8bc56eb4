"""pipe_tpu.resilience: fault injection, detection, recovery (train + serve).

The two pins that frame everything here:

* **Bitwise opt-out** — with no ResilienceConfig and no ChaosPlan, the
  train step and the serve decode program lower to byte-identical HLO
  before and after the resilience machinery is constructed/used
  (``test_*_hlo_unchanged*``), and a guarded-but-fault-free run produces
  bitwise the params of the unguarded trainer.
* **Loud, contained failure** — every injected fault class recovers
  (skip-step, rewind, data retry, slot-error containment) or aborts
  explicitly (TrainingAborted), never silently corrupts.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipe_tpu.data import lm_text
from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
from pipe_tpu.obs.events import NULL_EVENT_LOG
from pipe_tpu.obs.telemetry import MetricsRegistry, get_registry, set_registry
from pipe_tpu.resilience import (ChaosError, ChaosPlan, DataIteratorFailed,
                                 Fault, ResilienceConfig,
                                 ResilienceController, RetryingIterator,
                                 TickWatchdog, TrainingAborted, step_guard)
from pipe_tpu.train.loop import Trainer, TrainerConfig

pytestmark = pytest.mark.chaos

CFG = LMConfig(vocab=67, d_model=16, nhead=2, d_ff=32, n_layers=4,
               seq_len=32, dropout=0.0)
RC = ResilienceConfig(warmup_steps=100, rewind_after=2, snapshot_every=2,
                      data_backoff_s=0.0, rewind_backoff_s=0.0)


def _tc(**kw):
    base = dict(batch_size=8, bptt=16, chunks=2, n_stages=2,
                checkpoint="never", lr=0.01)
    base.update(kw)
    return TrainerConfig(**base)


@pytest.fixture(scope="module")
def source():
    ids = np.random.RandomState(0).randint(0, CFG.vocab, size=20000)
    return lm_text.batchify(ids, 8)


@pytest.fixture(scope="module")
def chaos_trainer():
    """One compiled chaos-armed trainer shared by the fault tests: the
    inject code is a *traced* argument, so swapping ``tr.chaos`` between
    tests exercises different fault classes with zero recompiles."""
    return Trainer(CFG, _tc(resilience=RC), chaos=ChaosPlan([]))


def _params_equal(a, b):
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _params_finite(state):
    return all(bool(jnp.isfinite(l).all())
               for l in jax.tree_util.tree_leaves(state.params)
               if jnp.issubdtype(l.dtype, jnp.inexact))


# ---------------------------------------------------------------------------
# detection unit tests


def test_step_guard_verdicts():
    grads = {"w": jnp.ones((3,), jnp.float32)}
    kw = dict(spike_factor=4.0, warmup_steps=2, ewma_alpha=0.5)

    ok, ewma = step_guard(jnp.float32(2.0), grads, jnp.float32(0.0),
                          jnp.int32(0), **kw)
    assert bool(ok) and float(ewma) == 2.0          # seeds on first accept

    ok, _ = step_guard(jnp.float32(jnp.nan), grads, jnp.float32(2.0),
                       jnp.int32(5), **kw)
    assert not bool(ok)                              # non-finite loss

    bad = {"w": jnp.array([1.0, jnp.inf, 0.0], jnp.float32)}
    ok, ewma = step_guard(jnp.float32(2.0), bad, jnp.float32(2.0),
                          jnp.int32(5), **kw)
    assert not bool(ok) and float(ewma) == 2.0       # EWMA holds on reject

    ok, _ = step_guard(jnp.float32(100.0), grads, jnp.float32(2.0),
                       jnp.int32(5), **kw)
    assert not bool(ok)                              # spike past warmup

    ok, _ = step_guard(jnp.float32(100.0), grads, jnp.float32(2.0),
                       jnp.int32(1), **kw)
    assert bool(ok)                                  # warmup disarms spike


def test_tick_watchdog_validation_and_stuck_budget():
    wd = TickWatchdog(stuck_slack_ticks=3)
    assert wd.stuck_after(max_new_tokens=8, decode_chunk=4) == 2 + 3
    assert TickWatchdog(stuck_slack_ticks=None).stuck_after(8, 1) is None
    with pytest.raises(ValueError):
        TickWatchdog(tick_budget_s=0.0)
    with pytest.raises(ValueError):
        TickWatchdog(shed_ewma_threshold=1.5)


# ---------------------------------------------------------------------------
# recovery unit tests (controller + iterator; no jit)


def _aux(consec, total, ewma=1.0):
    return (jnp.float32(ewma), jnp.int32(consec), jnp.int32(total))


def test_controller_rewinds_then_aborts():
    cfg = ResilienceConfig(rewind_after=1, max_rewinds=1, snapshot_every=1,
                           warmup_steps=100)
    slept = []
    ctl = ResilienceController(cfg, get_registry(), NULL_EVENT_LOG,
                               log_fn=lambda s: None, sleep=slept.append)
    good = {"w": jnp.arange(3.0)}
    state, aux = ctl.after_step(0, good, _aux(0, 0))     # snapshots
    assert ctl.anomalies == 0
    state, aux = ctl.after_step(1, {"w": jnp.full((3,), jnp.nan)},
                                _aux(1, 1))
    assert ctl.rewinds == 1 and ctl.anomalies == 1
    assert np.array_equal(np.asarray(state["w"]), np.arange(3.0))
    assert int(aux[1]) == 0                              # consec reset
    with pytest.raises(TrainingAborted):
        ctl.after_step(2, {"w": jnp.full((3,), jnp.nan)}, _aux(1, 2))


def test_controller_aborts_without_snapshot():
    cfg = ResilienceConfig(rewind_after=1, warmup_steps=100)
    ctl = ResilienceController(cfg, get_registry(), NULL_EVENT_LOG,
                               log_fn=lambda s: None)
    with pytest.raises(TrainingAborted, match="no known-good snapshot"):
        ctl.after_step(0, {"w": jnp.zeros(2)}, _aux(1, 1))


def test_retrying_iterator_resumes_at_position():
    fails = {2: 1}      # item 2 fails once

    def factory(pos):
        def gen():
            for i in range(pos, 5):
                if fails.get(i, 0) > 0:
                    fails[i] -= 1
                    raise ChaosError(f"boom at {i}")
                yield i
        return gen()

    it = RetryingIterator(factory, retries=2, backoff_s=0.0, sleep=lambda s: None)
    assert list(it) == [0, 1, 2, 3, 4]


def test_retrying_iterator_exhausts_budget():
    def factory(pos):
        def gen():
            raise ChaosError("always")
            yield  # pragma: no cover
        return gen()

    it = RetryingIterator(factory, retries=2, backoff_s=0.0,
                          sleep=lambda s: None)
    with pytest.raises(DataIteratorFailed, match="failed 3 times"):
        next(it)


def _flaky_factory(fails):
    """Factory whose source raises ``fails['left']`` times, then yields
    0..2 from the requested position."""
    def factory(pos):
        def gen():
            if fails["left"] > 0:
                fails["left"] -= 1
                raise ChaosError("flaky source")
            for i in range(pos, 3):
                yield i
        return gen()
    return factory


def test_retrying_iterator_delivers_at_exact_retry_cap():
    # the source fails exactly `retries` times: the last permitted
    # rebuild must deliver, not abort one attempt early
    fails = {"left": 2}
    it = RetryingIterator(_flaky_factory(fails), retries=2, backoff_s=0.0,
                          sleep=lambda s: None)
    assert list(it) == [0, 1, 2]
    assert fails["left"] == 0


def test_retrying_iterator_one_past_cap_aborts():
    # one more failure than the budget allows — even though the next
    # rebuild would have succeeded, the cap is the cap
    fails = {"left": 3}
    it = RetryingIterator(_flaky_factory(fails), retries=2, backoff_s=0.0,
                          sleep=lambda s: None)
    with pytest.raises(DataIteratorFailed, match="failed 3 times"):
        next(it)


def test_retrying_iterator_backoffs_double_under_fake_clock():
    def factory(pos):
        def gen():
            raise ChaosError("always")
            yield  # pragma: no cover
        return gen()

    slept = []
    it = RetryingIterator(factory, retries=3, backoff_s=0.25,
                          sleep=slept.append)
    with pytest.raises(DataIteratorFailed):
        next(it)
    # one sleep per burnt retry (none after the final failure), each
    # exactly double the last — strictly monotone, no wall clock read
    assert slept == [0.25 * 2 ** k for k in range(3)]
    assert all(b > a for a, b in zip(slept, slept[1:]))


def test_controller_rewind_backoffs_double_and_pin_oldest_snapshot():
    cfg = ResilienceConfig(rewind_after=1, max_rewinds=3,
                           snapshot_every=100, warmup_steps=100,
                           rewind_backoff_s=0.5)
    slept, lines = [], []
    ctl = ResilienceController(cfg, get_registry(), NULL_EVENT_LOG,
                               log_fn=lines.append, sleep=slept.append)
    good = {"w": jnp.arange(3.0)}
    ctl.after_step(0, good, _aux(0, 0))          # the ONLY snapshot: step 0
    bad = {"w": jnp.full((3,), jnp.nan)}
    for k in range(3):
        state, aux = ctl.after_step(k + 1, bad, _aux(1, k + 1))
        assert np.array_equal(np.asarray(state["w"]), np.arange(3.0))
    # every rewind targeted the oldest (and only) in-memory snapshot —
    # there is nothing older to reach — and each backoff doubled
    assert ctl.rewinds == 3
    assert all("snapshot of step 0" in l for l in lines)
    assert slept == [0.5 * 2 ** k for k in range(3)]
    assert all(b > a for a, b in zip(slept, slept[1:]))
    with pytest.raises(TrainingAborted, match="after 3 rewinds"):
        ctl.after_step(4, bad, _aux(1, 4))


# ---------------------------------------------------------------------------
# guarded trainer: parity, skip-step, data retry


def test_guarded_no_fault_matches_unguarded_bitwise(source):
    """The headline parity claim: resilience ON but fault-free produces
    bitwise the params of the unguarded trainer."""
    tr_g = Trainer(CFG, _tc(resilience=RC))
    tr_d = Trainer(CFG, _tc())
    sg, ig = tr_g.train_epoch(source, 0, tr_g.init_state(), max_steps=3,
                              log_every=0)
    sd, _ = tr_d.train_epoch(source, 0, tr_d.init_state(), max_steps=3,
                             log_every=0)
    assert ig["anomalies"] == 0 and ig["rewinds"] == 0
    assert _params_equal(sg.params, sd.params)
    assert int(sg.step) == int(sd.step) == 3


def test_skip_step_on_injected_nan(chaos_trainer, source):
    tr = chaos_trainer
    tr.chaos = ChaosPlan([Fault("nan_grads", step=2)])
    state, info = tr.train_epoch(source, 0, tr.init_state(), max_steps=5,
                                 log_every=0)
    assert info["anomalies"] == 1 and info["rewinds"] == 0
    assert _params_finite(state)
    assert np.isfinite(info["loss_ewma"])
    assert int(state.step) == 5          # skipped step still advances step


def test_nan_activations_caught_by_guard(chaos_trainer, source):
    tr = chaos_trainer
    tr.chaos = ChaosPlan([Fault("nan_activations", step=1)])
    state, info = tr.train_epoch(source, 0, tr.init_state(), max_steps=3,
                                 log_every=0)
    assert info["anomalies"] == 1
    assert _params_finite(state)


def test_persistent_faults_rewind(chaos_trainer, source):
    tr = chaos_trainer
    tr.chaos = ChaosPlan([Fault("nan_grads", step=2, count=2)])
    lines = []
    state, info = tr.train_epoch(source, 0, tr.init_state(), max_steps=6,
                                 log_every=0, log_fn=lines.append)
    assert info["rewinds"] >= 1
    assert _params_finite(state)
    assert any("rewind" in l for l in lines)


def test_data_fault_retried_no_steps_lost(chaos_trainer, source):
    tr = chaos_trainer
    tr.chaos = ChaosPlan([Fault("data_raise", step=1)])
    # the trainer binds its registry at construction — count the delta
    before = tr.registry.scalars().get("resilience.data_retries", 0)
    state, info = tr.train_epoch(source, 0, tr.init_state(),
                                 max_steps=4, log_every=0)
    after = tr.registry.scalars().get("resilience.data_retries", 0)
    assert info["steps"] == 4 and info["anomalies"] == 0
    assert after - before == 1


# ---------------------------------------------------------------------------
# the HLO byte-equality pins (acceptance criterion)


def test_train_step_hlo_unchanged_by_resilience(source):
    """The default train step's lowered HLO is byte-identical before and
    after resilience machinery exists in the process — opt-in means
    *absent from the program*, not merely disabled."""
    tr = Trainer(CFG, _tc())
    state = tr.init_state()
    data, target = next(tr._batches(source, 1))
    x, w = tr._make_x(data, target)
    args = (state, x, w, jax.random.key(0), jnp.float32(0.01))
    base = tr._step_fn.lower(*args).as_text()

    chaos_tr = Trainer(CFG, _tc(resilience=RC),
                       chaos=ChaosPlan([Fault("nan_grads", step=0)]))
    aux = (jnp.float32(0.0), jnp.int32(0), jnp.int32(0))
    cs = chaos_tr.init_state()
    chaos_tr._step_fn.lower(cs, aux, x, w, jax.random.key(0),
                            jnp.float32(0.01), jnp.int32(1),
                            jnp.float32(1e3)).as_text()

    assert tr._step_fn.lower(*args).as_text() == base


def test_decode_hlo_unchanged_by_watchdog_and_chaos():
    from pipe_tpu.serve import ServeEngine, SingleDeviceSlotBackend
    from pipe_tpu.inference.generate import GenerationConfig

    model = PipelinedLM(CFG, 2)
    params = model.init(jax.random.key(0))

    def lowered():
        be = SingleDeviceSlotBackend(
            model, params, num_slots=2, max_len=16,
            gen=GenerationConfig(max_new_tokens=4, temperature=1.0))
        fn, args = be.decode_program()
        return fn.lower(*args).as_text(), be

    base, _ = lowered()
    text, be = lowered()
    ServeEngine(be, watchdog=TickWatchdog(tick_budget_s=0.1,
                                          shed_ewma_threshold=0.5),
                chaos=ChaosPlan([Fault("stall_tick", step=0)]))
    text2, _ = lowered()
    assert base == text == text2


# ---------------------------------------------------------------------------
# emulator transport faults


def test_emulator_transport_fault_targets_one_hop():
    from pipe_tpu.core import microbatch as mb
    from pipe_tpu.parallel import emulator

    def stage(p, x, ctx):
        return jnp.tanh(x @ p)

    key = jax.random.key(7)
    params = [jax.random.normal(jax.random.fold_in(key, s), (8, 8))
              for s in range(2)]
    xs = [mb.Batch(jax.random.normal(jax.random.fold_in(key, 10 + i),
                                     (4, 8)), atomic=True)
          for i in range(2)]

    def run(chaos):
        out = emulator.run([stage, stage], params, list(xs), chaos=chaos)
        return [np.asarray(b.values[0]) for b in out]

    clean = run(None)
    drop = run(ChaosPlan([Fault("transport_drop", step=0, stage=0,
                                microbatch=1)]))
    assert np.array_equal(drop[0], clean[0])       # other microbatch spared
    assert not np.array_equal(drop[1], clean[1])
    corrupt = run(ChaosPlan([Fault("transport_corrupt", step=0, stage=0,
                                   microbatch=0)]))
    assert np.isnan(corrupt[0]).all()              # NaN-poisoned hop
    assert np.array_equal(corrupt[1], clean[1])
    # a retry without the plan reproduces the clean run bitwise
    assert all(np.array_equal(a, b) for a, b in zip(run(None), clean))


# ---------------------------------------------------------------------------
# serve engine: containment, watchdog, shedding, drain


@pytest.fixture(scope="module")
def serve_backend():
    from pipe_tpu.inference.generate import GenerationConfig
    from pipe_tpu.serve import SingleDeviceSlotBackend

    model = PipelinedLM(CFG, 2)
    params = model.init(jax.random.key(0))
    return SingleDeviceSlotBackend(
        model, params, num_slots=2, max_len=32,
        gen=GenerationConfig(max_new_tokens=8, temperature=1.0))


def test_prefill_error_contained_to_one_request(serve_backend):
    from pipe_tpu.serve import ServeEngine

    be = serve_backend
    eng = ServeEngine(be)
    orig, calls = be.prefill, {"n": 0}

    def bad_prefill(slot, prompt, seed):
        calls["n"] += 1
        if calls["n"] == 1:
            raise RuntimeError("boom")
        return orig(slot, prompt, seed)

    reg = set_registry(MetricsRegistry())
    be.prefill = bad_prefill
    try:
        r1 = eng.submit([1, 2, 3], max_new_tokens=4)
        r2 = eng.submit([4, 5, 6], max_new_tokens=4)
        eng.run_until_idle()
        errs = get_registry().scalars().get("resilience.slot_errors", 0)
    finally:
        be.prefill = orig
        set_registry(reg)
    assert eng.response(r1.id).status == "error"
    assert eng.response(r1.id).finish_reason == "backend_error"
    assert eng.response(r2.id).status == "ok"      # others keep serving
    assert errs == 1
    assert eng.live_slots == 0 and len(eng._free) == be.num_slots


def test_decode_errors_tolerated_then_retire_all(serve_backend):
    from pipe_tpu.serve import ServeEngine

    be = serve_backend
    orig = be.decode
    # below the limit: tick skipped, slot state intact, request finishes
    flaky = {"n": 0}

    def flaky_decode(live, **kw):
        flaky["n"] += 1
        if flaky["n"] <= 2:
            raise RuntimeError("transient")
        return orig(live, **kw)

    eng = ServeEngine(be, decode_error_limit=3)
    be.decode = flaky_decode
    try:
        r = eng.submit([1, 2, 3], max_new_tokens=4)
        eng.run_until_idle()
    finally:
        be.decode = orig
    assert eng.response(r.id).status == "ok"

    # at the limit: live slots retired as errors, engine stays usable
    def dead_decode(live, **kw):
        raise RuntimeError("dead backend")

    eng2 = ServeEngine(be, decode_error_limit=2)
    be.decode = dead_decode
    try:
        r = eng2.submit([1, 2, 3], max_new_tokens=4)
        eng2.tick()
        assert eng2.response(r.id) is None         # first error tolerated
        eng2.tick()
        resp = eng2.response(r.id)
    finally:
        be.decode = orig
    assert resp.status == "error" and resp.finish_reason == "backend_error"
    r2 = eng2.submit([4, 5], max_new_tokens=4)     # engine still serves
    eng2.run_until_idle()
    assert eng2.response(r2.id).status == "ok"


def test_stuck_slot_retired_as_error(serve_backend):
    from pipe_tpu.serve import ServeEngine

    be = serve_backend
    orig = be.decode

    def no_progress(live, **kw):
        toks, valid = orig(live, **kw)
        return toks, np.zeros_like(valid)          # tokens never valid

    eng = ServeEngine(be, watchdog=TickWatchdog(stuck_slack_ticks=2))
    be.decode = no_progress
    try:
        r = eng.submit([1, 2, 3], max_new_tokens=4)
        for _ in range(12):
            eng.tick()
            if eng.response(r.id) is not None:
                break
    finally:
        be.decode = orig
    resp = eng.response(r.id)
    assert resp is not None and resp.status == "error"
    assert resp.finish_reason == "stuck"


def test_degraded_mode_sheds_lowest_priority(serve_backend):
    from pipe_tpu.serve import RequestQueue, ServeEngine

    t = {"now": 0.0}
    q = RequestQueue(capacity=16, policy="priority",
                     clock=lambda: t["now"])
    eng = ServeEngine(serve_backend, q, watchdog=TickWatchdog(
        shed_ewma_threshold=0.5, shed_ewma_alpha=1.0,
        stuck_slack_ticks=None))
    # a queued request missing its deadline drives the miss EWMA to 1.0
    eng.submit([1, 2], max_new_tokens=2, timeout_s=0.1)
    t["now"] = 1.0
    eng.tick()
    assert eng._miss_ewma == 1.0
    lo = eng.submit([3, 4], max_new_tokens=2, priority=-5)
    hi = eng.submit([5, 6], max_new_tokens=2, priority=5)
    eng.tick()
    assert eng.response(lo.id) is not None
    assert eng.response(lo.id).status == "shed"
    assert eng.response(lo.id).finish_reason == "shed"
    resp_hi = eng.response(hi.id)
    assert resp_hi is None or resp_hi.status != "shed"
    eng.run_until_idle()


def test_drain_finishes_live_sheds_queued(serve_backend):
    from pipe_tpu.serve import EngineDraining, ServeEngine

    eng = ServeEngine(serve_backend)
    ra = eng.submit([1, 2, 3], max_new_tokens=4)
    rb = eng.submit([4, 5], max_new_tokens=4)
    rc = eng.submit([6, 7], max_new_tokens=4)      # queued (2 slots)
    eng.tick()
    eng.drain()
    with pytest.raises(EngineDraining):
        eng.submit([8], max_new_tokens=2)
    ticks = 0
    while not eng.drained:
        eng.tick()
        ticks += 1
        assert ticks < 50
    assert eng.response(ra.id).status == "ok"
    assert eng.response(rb.id).status == "ok"
    assert eng.response(rc.id).status == "shed"
    assert eng.response(rc.id).finish_reason == "drain"


def test_queue_full_reports_depth_capacity_age():
    from pipe_tpu.serve import QueueFull, RequestQueue

    t = {"now": 100.0}
    q = RequestQueue(capacity=2, clock=lambda: t["now"])
    q.submit([1], max_new_tokens=1)
    t["now"] = 103.0
    q.submit([2], max_new_tokens=1)
    with pytest.raises(QueueFull) as ei:
        q.submit([3], max_new_tokens=1)
    e = ei.value
    assert e.depth == 2 and e.capacity == 2
    assert e.oldest_age_s == pytest.approx(3.0)
    assert "depth 2/2" in str(e) and "3.000s" in str(e)


def test_shed_lowest_orders_by_priority_then_youngest():
    from pipe_tpu.serve import RequestQueue

    q = RequestQueue(capacity=8, policy="priority")
    a = q.submit([1], max_new_tokens=1, priority=0)   # oldest at prio 0
    b = q.submit([2], max_new_tokens=1, priority=5)
    c = q.submit([3], max_new_tokens=1, priority=0)   # youngest at prio 0
    shed = q.shed_lowest(2)
    assert [r.id for r in shed] == [a.id, c.id]       # prio 5 survives...
    assert q.depth == 1 and q.pop().id == b.id
    # ...and within a priority level the youngest sheds first
    q2 = RequestQueue(capacity=8)
    x = q2.submit([1], max_new_tokens=1)
    y = q2.submit([2], max_new_tokens=1)
    assert [r.id for r in q2.shed_lowest(1)] == [y.id]
    assert q2.pop().id == x.id


# ---------------------------------------------------------------------------
# checkpoint manifest (atomic + verifiable save)


def test_checkpoint_manifest_verifies_and_names_corrupt_leaf(tmp_path,
                                                             source):
    from pipe_tpu.train.state import (CheckpointCorrupt, restore_checkpoint,
                                      save_checkpoint)

    tr = Trainer(CFG, _tc())
    state = tr.init_state()
    ckpt = str(tmp_path / "ck")
    save_checkpoint(ckpt, state, 0)
    manifest = tmp_path / "ck" / "manifest_step0.json"
    assert manifest.is_file()

    restored = restore_checkpoint(ckpt, tr.init_state())   # verify=True
    assert _params_equal(restored.params, state.params)

    # tamper one leaf's recorded hash: restore must refuse, naming it
    doc = json.loads(manifest.read_text())
    leaf = sorted(doc["leaves"])[0]
    doc["leaves"][leaf] = "0" * 64
    manifest.write_text(json.dumps(doc))
    with pytest.raises(CheckpointCorrupt) as ei:
        restore_checkpoint(ckpt, tr.init_state())
    assert leaf in str(ei.value)

    restore_checkpoint(ckpt, tr.init_state(), verify=False)  # opt-out


def test_torn_manifest_tmp_files_ignored_on_restore(tmp_path):
    """A crash between tmp-write and rename leaves ``.*.tmp`` droppings;
    only a completed rename may ever be read back."""
    from pipe_tpu.train.state import (read_buddy_manifest,
                                      restore_checkpoint, save_checkpoint,
                                      write_buddy_manifest)

    shards = {"stage0": "a" * 64, "stage1": "b" * 64}
    write_buddy_manifest(str(tmp_path), 5, shards, 2)
    # torn writes: a truncated tmp NEXT TO the good step-5 record, and
    # a step-7 write that died before its rename
    (tmp_path / ".buddy_step5.json.tmp").write_text('{"step": 5, "n_st')
    (tmp_path / ".buddy_step7.json.tmp").write_text('{"step": 7')
    doc = read_buddy_manifest(str(tmp_path), 5)
    assert doc == {"step": 5, "n_stages": 2, "stage_shards": shards}
    assert read_buddy_manifest(str(tmp_path), 7) is None

    # checkpoint side: a leftover torn manifest tmp must neither block
    # nor pollute verification of the completed manifest
    tr = Trainer(CFG, _tc())
    state = tr.init_state()
    ckpt = tmp_path / "ck"
    save_checkpoint(str(ckpt), state, 0)
    (ckpt / ".manifest_step0.json.tmp").write_text('{"step": 0, "leav')
    restored = restore_checkpoint(str(ckpt), tr.init_state())
    assert _params_equal(restored.params, state.params)


# ---------------------------------------------------------------------------
# SIGTERM autosave: signal mid-epoch -> checkpoint -> bitwise resume


def test_sigterm_autosave_resumes_next_step_bitwise(tmp_path, source):
    """The preemption flow end to end on the REAL signal: SIGTERM lands
    mid-epoch, the in-flight step finishes, the checkpoint is written,
    the epoch loop exits cleanly — and re-running the next step from the
    restored state reproduces the uninterrupted run bitwise."""
    import os
    import signal

    from pipe_tpu.train.state import latest_step, restore_checkpoint

    # uninterrupted reference: two steps
    tr_ref = Trainer(CFG, _tc())
    ref, _ = tr_ref.train_epoch(source, 0, tr_ref.init_state(),
                                max_steps=2, log_every=0)

    tr = Trainer(CFG, _tc())
    ckpt = str(tmp_path / "auto")
    prev_handler = signal.getsignal(signal.SIGTERM)
    try:
        tr.install_autosave(ckpt)                  # default: SIGTERM
        fired = {"done": False}
        orig_step = tr._step_fn

        def step_and_signal(*a, **kw):
            out = orig_step(*a, **kw)
            if not fired["done"]:
                fired["done"] = True
                os.kill(os.getpid(), signal.SIGTERM)
            return out

        tr._step_fn = step_and_signal
        lines = []
        _, stats = tr.train_epoch(source, state=tr.init_state(),
                                  max_steps=4, log_every=0,
                                  log_fn=lines.append)
        tr._step_fn = orig_step
    finally:
        signal.signal(signal.SIGTERM, prev_handler)
    assert stats["steps"] == 1                     # clean early exit
    assert any("autosave" in l for l in lines)
    assert latest_step(ckpt) == 1

    restored = restore_checkpoint(ckpt, tr.init_state())
    assert int(restored.step) == 1
    # replay step b=1 exactly as train_epoch would have (epoch-0 key
    # chain, epoch-0 StepLR)
    from pipe_tpu.utils.rng import make_key

    data, target = list(tr._batches(source, 2, start=1))[0]
    x, w = tr._make_x(data, target)
    key = jax.random.fold_in(make_key(tr.cfg.seed), 0)
    state2, _ = tr._step_fn(restored, x, w, jax.random.fold_in(key, 1),
                            jnp.float32(tr.cfg.lr))
    assert int(state2.step) == 2
    assert _params_equal(state2.params, ref.params)
