"""The fleet router (pipe_tpu/serve/router.py): health-gated failover.

The contract under test, in order of importance:

* **Exactly-once delivery.** Every id submitted at the fleet front door
  yields exactly one terminal Response through the router — including
  under ``kill_replica`` chaos, where requests bounce through eviction,
  retry parking and re-placement (the PR's acceptance pin).
* **Health gating.** SUSPECT stops placement only; WEDGED evicts the
  backlog intact, re-places it under the retry budget, and walks the
  replica through DRAINING to RETIRED. A fleet with no recoverable
  replica and no spawn hook fails stranded work loudly (``no_replicas``)
  instead of spinning.
* **Request identity survives failover.** ``submitted_at``/``deadline``
  ride the same Request object through every re-queue — no deadline
  credit — and cancellation is one flag flip wherever the request sits.
* **Zero overhead when absent.** ``chaos=None`` leaves the replica
  backends untouched (no wrappers); the single-engine path never
  constructs a Router at all.

Fast tests drive a stub slot backend on a fake clock — deterministic,
no jax in the loop. The one real-model test (slow tier) pins bitwise
token parity through a mid-stream replica kill: seeds and prompts ride
the re-placement, so failed-over greedy output matches the one-shot
Generator exactly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipe_tpu.inference import GenerationConfig, Generator
from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
from pipe_tpu.obs.telemetry import get_registry, labelled
from pipe_tpu.resilience import ChaosPlan, Fault, TickWatchdog
from pipe_tpu.serve import (DRAINING, HEALTHY, RETIRED, SUSPECT, BucketSpec,
                            EngineDraining, QueueFull, RequestQueue, Router,
                            RouterPolicy, ServeEngine,
                            SingleDeviceSlotBackend)

# ---------------------------------------------------------------------------
# stub backend: the slot-backend contract without jax


class _FakeGen:
    eos_token_id = None
    max_new_tokens = 32
    pad_token_id = 0


class FakeBackend:
    """S slots, one deterministic token per decode step, no device in
    sight — what the router sees of a backend, nothing more."""

    def __init__(self, num_slots=2, poison=None):
        self.num_slots = num_slots
        self.gen = _FakeGen()
        self.buckets = None
        self.decode_chunk = 1
        self.poison = poison          # prompts starting with this fail

    def validate(self, prompt_len, max_new_tokens):
        if max_new_tokens > self.gen.max_new_tokens:
            raise ValueError("max_new_tokens above engine cap")

    def prefill(self, slot, prompt, seed):
        if self.poison is not None and prompt[0] == self.poison:
            raise RuntimeError("poisoned prompt")
        return 1

    def decode(self, live, budgets=None, r_max=None):
        toks = np.ones((self.num_slots, 1), np.int32)
        valid = np.broadcast_to(np.asarray(live, bool)[:, None],
                                toks.shape)
        return toks, valid


def make_fleet(n_replicas, *, slots=2, replica_capacity=32,
               front_capacity=32, chaos=None, poison=None, **policy_kw):
    """N stub replicas + front queue, all on one fake clock. Returns
    (router, t) where t is the mutable clock cell."""
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    policy_kw.setdefault("backoff_base_s", 0.0)
    engines = [
        ServeEngine(FakeBackend(slots, poison=poison),
                    RequestQueue(capacity=replica_capacity, clock=clock),
                    watchdog=TickWatchdog(stuck_slack_ticks=None))
        for _ in range(n_replicas)]
    router = Router(engines,
                    RequestQueue(capacity=front_capacity, clock=clock),
                    policy=RouterPolicy(**policy_kw), chaos=chaos)
    return router, t


def run(router, t, max_ticks=300):
    out = []
    for _ in range(max_ticks):
        if router.idle:
            return out
        t[0] += 0.01
        out.extend(router.tick())
    raise AssertionError(
        f"fleet not idle after {max_ticks} ticks: {router.counts()}")


# ---------------------------------------------------------------------------
# placement


def test_least_loaded_placement_spreads_work():
    router, t = make_fleet(3, slots=2)
    ids = [router.submit([1, 2, 3], max_new_tokens=4).id
           for _ in range(6)]
    t[0] += 0.01
    router.tick()
    loads = [rep.load for rep in router.replicas]
    assert loads == [2, 2, 2], loads
    run(router, t)
    for rid in ids:
        resp = router.response(rid)
        assert resp.status == "ok" and len(resp.tokens) == 4


def test_stub_first_tokens_have_arrived_with_prefills_return():
    """The stub's ``prefill`` returns an int: the engines serve it as a
    first token that has already arrived (ttft stamped at admission,
    nothing counted as read behind a launch), and pass its ``decode``
    the two keywords it takes and no other."""
    from pipe_tpu.obs.telemetry import get_registry
    overlapped = get_registry().counter(
        "serve.engine.first_tokens_overlapped")
    before = overlapped.value
    router, t = make_fleet(2, slots=2)
    ids = [router.submit([1, 2, 3], max_new_tokens=n).id
           for n in (4, 1, 3, 2)]
    run(router, t)
    for rid, n in zip(ids, (4, 1, 3, 2)):
        resp = router.response(rid)
        assert resp.status == "ok" and resp.tokens == [1] * n
        assert resp.ttft is not None and resp.ttft <= resp.latency
    assert overlapped.value == before


def test_session_affinity_pins_then_remaps_off_unhealthy_home():
    router, t = make_fleet(3, placement="session")
    r1 = router.submit([1, 2], max_new_tokens=6, session="a")
    t[0] += 0.01
    router.tick()
    home = router._placed_on[r1.id]
    r2 = router.submit([1, 2], max_new_tokens=6, session="a")
    t[0] += 0.01
    router.tick()
    # pinned: same replica although it is now the MOST loaded
    assert router._placed_on[r2.id] == home
    # home goes unhealthy -> session falls back and REMAPS
    router.replicas[home].state = SUSPECT
    r3 = router.submit([1, 2], max_new_tokens=6, session="a")
    t[0] += 0.01
    router.tick()
    new_home = router._placed_on[r3.id]
    assert new_home != home
    assert router._session_map["a"] == new_home
    run(router, t)


# ---------------------------------------------------------------------------
# the acceptance pin: kill one of N, every id exactly once


@pytest.mark.chaos
def test_kill_replica_all_ids_resolve_exactly_once():
    """N=3, ``kill_replica`` fires mid-stream on replica 2: its queued
    backlog is evicted and re-placed, its live slots fail over, and
    every submitted id ends with exactly one terminal response — all
    ``ok``, because the kill is retryable and two replicas survive."""
    reg = get_registry()
    wedged0 = reg.counter("serve.fleet.wedged").value
    chaos = ChaosPlan([Fault("kill_replica", step=3, stage=2)])
    router, t = make_fleet(3, slots=2, chaos=chaos)
    ids = [router.submit([1, 2, 3], max_new_tokens=8, seed=i).id
           for i in range(12)]
    delivered = run(router, t)

    assert len(delivered) == len(ids)          # exactly once, in total
    assert sorted(r.request_id for r in delivered) == sorted(ids)
    for rid in ids:
        resp = router.response(rid)
        assert resp is not None and resp.status == "ok"
        assert len(resp.tokens) == 8
    # the killed replica walked WEDGED -> DRAINING -> RETIRED
    assert router.replicas[2].state == RETIRED
    assert router.counts()[HEALTHY] == 2
    assert reg.counter("serve.fleet.wedged").value == wedged0 + 1
    # work actually failed over (attempts > 1 somewhere)
    assert reg.counter("serve.fleet.failed_over").value > 0
    # per-replica labelled gauges reflect the terminal states
    assert reg.gauge(labelled("serve.fleet.replica.state",
                              replica=2)).value == 4.0  # RETIRED code


@pytest.mark.chaos
def test_wedged_backlog_is_evicted_intact_and_reserved():
    """Queued (never-admitted) requests on the killed replica come back
    INTACT and finish ok elsewhere with attempts == 2."""
    chaos = ChaosPlan([Fault("kill_replica", step=2, stage=1)])
    # slots=1 + deep replica queues so replica 1 holds a real backlog
    router, t = make_fleet(2, slots=1, chaos=chaos)
    reqs = [router.submit([1, 2], max_new_tokens=4, seed=i)
            for i in range(6)]
    run(router, t)
    assert all(router.response(r.id).status == "ok" for r in reqs)
    bounced = [r for r in reqs if r.attempts > 1]
    assert bounced, "no request ever touched the killed replica"
    assert all(r.attempts == 2 for r in bounced)


# ---------------------------------------------------------------------------
# retry budget / backoff


def test_retry_budget_exhausts_to_single_error_response():
    """A poison request that fails prefill on every replica burns its
    placements and ends as ONE ``retries_exhausted`` error, while
    healthy traffic keeps flowing."""
    router, t = make_fleet(2, poison=666, retry_budget=2,
                           wedge_error_ticks=100, wedge_decode_errors=100,
                           recover_healthy_ticks=1)
    bad = router.submit([666, 1], max_new_tokens=4)
    good = router.submit([1, 2], max_new_tokens=4)
    run(router, t)
    resp = router.response(bad.id)
    assert resp.status == "error"
    assert resp.finish_reason == "retries_exhausted"
    assert bad.attempts == 2
    assert router.response(good.id).status == "ok"


def test_backoff_parks_until_eligible():
    """With a real backoff base the bounced request sits parked until
    the clock passes ``base * 2^(attempts-1)``."""
    router, t = make_fleet(2, poison=666, retry_budget=3,
                           backoff_base_s=1.0, backoff_max_s=8.0,
                           wedge_error_ticks=100, wedge_decode_errors=100,
                           recover_healthy_ticks=1)
    bad = router.submit([666, 1], max_new_tokens=4)
    t[0] += 0.01
    router.tick()                  # placed (attempts=1), fails, parks
    assert bad.attempts == 1 and len(router._parked) == 1
    for _ in range(5):             # 0.05s << 1.0s backoff: stays parked
        t[0] += 0.01
        router.tick()
    assert bad.attempts == 1 and len(router._parked) == 1
    t[0] += 1.0                    # eligible now
    router.tick()
    assert bad.attempts == 2


def test_reclaim_park_or_finish_decisions_and_exactly_once():
    """``Router.reclaim`` is the ONE shared park-or-finish gate for
    requests knocked off a replica (wedge eviction and live-tick
    failures both route through it): cancelled/expired → parked for the
    terminal sweep, budget remaining → parked with backoff, budget
    spent → exactly one terminal error."""
    from pipe_tpu.serve.queue import Request

    router, t = make_fleet(1, retry_budget=2, backoff_base_s=1.0,
                           backoff_max_s=8.0)
    now = 5.0
    cancelled = Request(id=101, prompt=[1], max_new_tokens=4,
                        cancelled=True, attempts=1)
    expired = Request(id=102, prompt=[1], max_new_tokens=4,
                      deadline=4.0, attempts=1)
    retryable = Request(id=103, prompt=[1], max_new_tokens=4, attempts=1)
    spent = Request(id=104, prompt=[1], max_new_tokens=4, attempts=2,
                    submitted_at=1.0)

    finished = router.reclaim([cancelled, expired, retryable, spent], now)

    # only the spent request is terminal, and it is already ledgered
    assert [r.request_id for r in finished] == [104]
    assert finished[0].status == "error"
    assert finished[0].finish_reason == "retries_exhausted"
    assert router.response(104) is finished[0]
    # cancelled/expired park at `now` (no backoff credit); the
    # retryable one parks at now + base * 2^(attempts-1)
    parked = {req.id: at for at, req in router._parked}
    assert parked == {101: now, 102: now, 103: now + 1.0}
    # re-reclaiming the spent request would double-deliver: the ledger
    # refuses loudly instead of silently overwriting
    with pytest.raises(RuntimeError, match="exactly-once"):
        router.reclaim([spent], now)


# ---------------------------------------------------------------------------
# satellites: cancellation after failover, all-SUSPECT backpressure


def test_cancel_while_parked_after_failover():
    """Cancel a request sitting in the retry park (bounced off a failing
    replica, waiting out its backoff): one terminal ``cancelled``
    response, nothing delivered twice."""
    router, t = make_fleet(2, poison=666, backoff_base_s=100.0,
                           wedge_error_ticks=100, wedge_decode_errors=100)
    bad = router.submit([666, 1], max_new_tokens=4)
    t[0] += 0.01
    router.tick()                  # bounce -> parked for 100s
    assert len(router._parked) == 1
    assert router.cancel(bad.id)
    t[0] += 0.01
    delivered = router.tick()      # parked sweep emits the terminal
    assert [r.request_id for r in delivered] == [bad.id]
    resp = router.response(bad.id)
    assert resp.status == "cancelled" and resp.finish_reason == "cancelled"
    assert router.idle
    assert not router.cancel(bad.id)    # terminal ids are gone


def test_all_suspect_stops_placement_and_backpressures():
    """Every replica SUSPECT: placement halts (hysteresis — SUSPECT work
    just waits), the front queue fills, and the next submit feels
    QueueFull instead of silent loss."""
    reg = get_registry()
    rejected0 = reg.counter("serve.fleet.rejected").value
    router, t = make_fleet(2, front_capacity=4,
                           recover_healthy_ticks=1000)
    for rep in router.replicas:
        rep.state = SUSPECT
    for _ in range(4):
        router.submit([1, 2], max_new_tokens=4)
    for _ in range(3):
        t[0] += 0.01
        router.tick()
    assert router.queue.depth == 4          # nothing placed
    assert all(rep.load == 0 for rep in router.replicas)
    with pytest.raises(QueueFull):
        router.submit([1, 2], max_new_tokens=4)
    assert reg.counter("serve.fleet.rejected").value == rejected0 + 1


# ---------------------------------------------------------------------------
# deadlines survive failover


def test_no_deadline_credit_after_failover():
    """A request bounced by a failing replica keeps its ORIGINAL
    deadline through the retry park: once the clock passes it, the
    terminal record is ``timeout``/``deadline`` — not a fresh retry."""
    router, t = make_fleet(2, poison=666, backoff_base_s=0.0,
                           wedge_error_ticks=100, wedge_decode_errors=100,
                           retry_budget=100, recover_healthy_ticks=1)
    bad = router.submit([666, 1], max_new_tokens=4, timeout_s=0.5)
    deadline = bad.deadline
    t[0] += 0.01
    router.tick()                  # bounce #1
    assert bad.deadline == deadline        # identity preserved
    t[0] += 1.0                    # past the original deadline
    run(router, t)
    resp = router.response(bad.id)
    assert resp.status == "timeout" and resp.finish_reason == "deadline"
    assert resp.latency >= 0.5


# ---------------------------------------------------------------------------
# fleet drain, lifecycle, dead fleet


def test_fleet_drain_sheds_and_finishes_live():
    router, t = make_fleet(2, slots=1, replica_capacity=1,
                           front_capacity=16)
    reqs = [router.submit([1, 2], max_new_tokens=3) for _ in range(6)]
    t[0] += 0.01
    router.tick()                  # 2 live, 2 replica-queued, 2 at front
    router.drain()
    with pytest.raises(EngineDraining):
        router.submit([1, 2], max_new_tokens=3)
    run(router, t)
    assert router.drained
    statuses = {router.response(r.id).status for r in reqs}
    assert statuses <= {"ok", "shed"}
    shed = [r for r in reqs
            if router.response(r.id).finish_reason == "drain"]
    live_done = [r for r in reqs if router.response(r.id).status == "ok"]
    assert shed and live_done      # both paths exercised


def test_spawn_on_sustained_depth_and_retire_idle():
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731

    def spawn():
        return ServeEngine(FakeBackend(1),
                           RequestQueue(capacity=1, clock=clock),
                           watchdog=TickWatchdog(stuck_slack_ticks=None))

    engines = [spawn()]
    router = Router(engines, RequestQueue(capacity=32, clock=clock),
                    policy=RouterPolicy(backoff_base_s=0.0, spawn_depth=2,
                                        spawn_sustain_ticks=2,
                                        retire_idle_ticks=2,
                                        min_replicas=1),
                    spawn_fn=spawn)
    reqs = [router.submit([1, 2], max_new_tokens=4) for _ in range(6)]
    spawned0 = get_registry().counter("serve.fleet.spawned").value
    run(router, t)
    assert len(router.replicas) > 1        # depth sustained -> spawned
    assert get_registry().counter("serve.fleet.spawned").value > spawned0
    assert all(router.response(r.id).status == "ok" for r in reqs)
    for _ in range(8):                     # idle ticks -> retire back down
        t[0] += 0.01
        router.tick()
    counts = router.counts()
    assert counts[HEALTHY] == 1            # never below min_replicas
    assert counts[RETIRED] == len(router.replicas) - 1


@pytest.mark.chaos
def test_dead_fleet_fails_stranded_work_loudly():
    """Last replica wedges with work still parked/front-queued and no
    spawn hook: the stranded requests end ``no_replicas`` instead of
    parking forever — run_until_idle terminates."""
    chaos = ChaosPlan([Fault("kill_replica", step=0, stage=0)])
    router, t = make_fleet(1, chaos=chaos, wedge_error_ticks=1,
                           retry_budget=5)
    reqs = [router.submit([1, 2], max_new_tokens=4) for _ in range(3)]
    run(router, t, max_ticks=20)           # must terminate FAST
    for r in reqs:
        resp = router.response(r.id)
        assert resp.status == "error"
        assert resp.finish_reason == "no_replicas"
    for _ in range(2):                     # DRAINING -> RETIRED settles
        t[0] += 0.01
        router.tick()
    assert router.counts()[RETIRED] == 1


# ---------------------------------------------------------------------------
# zero overhead when absent


def test_chaos_none_leaves_backends_untouched():
    """No ChaosPlan -> the router installs NO wrappers: the replica
    backends' prefill/decode stay the class methods, never shadowed by
    instance attributes (the fleet layer adds zero overhead to the hot
    path)."""
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    engines = [ServeEngine(FakeBackend(2),
                           RequestQueue(capacity=8, clock=clock))
               for _ in range(2)]
    Router(engines, RequestQueue(capacity=8, clock=clock))
    for eng in engines:
        assert "decode" not in vars(eng.backend)
        assert "prefill" not in vars(eng.backend)
    # and WITH a plan, the wrappers are installed
    engines2 = [ServeEngine(FakeBackend(2),
                            RequestQueue(capacity=8, clock=clock))]
    Router(engines2, RequestQueue(capacity=8, clock=clock),
           chaos=ChaosPlan([Fault("kill_replica", step=0, stage=0)]))
    assert "decode" in vars(engines2[0].backend)
    assert "prefill" in vars(engines2[0].backend)


def test_router_rejects_shared_or_foreign_queues():
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731
    front = RequestQueue(capacity=8, clock=clock)
    shared = RequestQueue(capacity=8, clock=clock)
    with pytest.raises(ValueError):       # engine on the front queue
        Router([ServeEngine(FakeBackend(), front)], front)
    with pytest.raises(ValueError):       # two engines, one queue
        Router([ServeEngine(FakeBackend(), shared),
                ServeEngine(FakeBackend(), shared)],
               RequestQueue(capacity=8, clock=clock))
    with pytest.raises(ValueError):       # wrong clock domain
        Router([ServeEngine(FakeBackend(),
                            RequestQueue(capacity=8))], front)


# ---------------------------------------------------------------------------
# satellite units: queue re-queue identity, shed tie-break, watchdog surface


def test_requeue_preserves_identity_and_backpressures():
    t = [0.0]
    q = RequestQueue(capacity=2, clock=lambda: t[0])
    req = q.submit([1, 2], max_new_tokens=4, timeout_s=1.0)
    rid, sub, dl = req.id, req.submitted_at, req.deadline
    assert q.pop() is req
    t[0] = 5.0                     # clock moves; identity must not
    q.requeue(req)
    assert (req.id, req.submitted_at, req.deadline) == (rid, sub, dl)
    assert req.attempts == 0       # requeue never counts placements
    q.submit([3], max_new_tokens=1)
    with pytest.raises(QueueFull):
        q.requeue(req)


def test_shed_lowest_tiebreak_is_pure_request_identity():
    """Key is (priority, arrival, id): lowest priority first, youngest
    arrival within a level, highest id on exact-arrival ties — stable
    under the list reordering router re-queues cause."""
    t = [0.0]
    q = RequestQueue(capacity=8, clock=lambda: t[0])
    old = q.submit([1], max_new_tokens=1)            # t=0
    t[0] = 1.0
    y1 = q.submit([1], max_new_tokens=1)             # t=1
    y2 = q.submit([1], max_new_tokens=1)             # t=1, higher id
    hi = q.submit([1], max_new_tokens=1, priority=5)
    # reorder the backing list the way failover re-queues would
    q._waiting.reverse()
    assert [r.id for r in q.shed_lowest(2)] == [y2.id, y1.id]
    assert {r.id for r in q._waiting} == {old.id, hi.id}


def test_watchdog_read_only_health_surface():
    wd = TickWatchdog(tick_budget_s=0.1, stuck_slack_ticks=None)
    assert wd.record_tick(0.05) is False
    assert wd.slow_streak == 0 and wd.last_tick_s == 0.05
    assert wd.record_tick(0.2) is True
    assert wd.record_tick(0.3) is True
    assert (wd.slow_streak, wd.slow_ticks) == (2, 2)
    wd.record_tick(0.01)
    assert (wd.slow_streak, wd.slow_ticks) == (0, 2)
    assert wd.miss_ewma == 0.0
    assert wd.record_outcome(True) == pytest.approx(wd.shed_ewma_alpha)
    wd.record_stuck()
    assert wd.stuck_slots == 1


# ---------------------------------------------------------------------------
# real model, slow tier: bitwise parity through a mid-stream kill


CFG = LMConfig(vocab=89, d_model=32, nhead=4, d_ff=64, n_layers=4,
               seq_len=32, dropout=0.0)


@pytest.fixture(scope="module")
def model_and_params():
    model = PipelinedLM(CFG, n_stages=2)
    return model, model.init(jax.random.key(0))


@pytest.mark.chaos
def test_kill_failover_token_parity_real_model(model_and_params):
    """The gold contract survives failover: kill one of three real
    replicas mid-decode; every response is still bitwise the one-shot
    batch-1 Generator output, because the failed-over request re-enters
    a fresh slot with its original prompt AND seed."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(1, CFG.vocab, size=n))
               for n in (3, 5, 4, 7, 5, 6)]
    g = Generator(model, gen_cfg)
    refs = [np.asarray(g.generate(params, jnp.asarray(p, jnp.int32)[None],
                                  jax.random.key(7)))[0]
            for p in prompts]

    chaos = ChaosPlan([Fault("kill_replica", step=2, stage=2)])
    engines = [
        ServeEngine(SingleDeviceSlotBackend(
            model, params, num_slots=2, max_len=16, gen=gen_cfg,
            buckets=BucketSpec.of(4, 8)),
            RequestQueue(capacity=16),
            watchdog=TickWatchdog(stuck_slack_ticks=None))
        for _ in range(3)]
    router = Router(engines, RequestQueue(capacity=16),
                    policy=RouterPolicy(backoff_base_s=0.0), chaos=chaos)
    ids = [router.submit(p, max_new_tokens=6, seed=7).id for p in prompts]
    router.run_until_idle(max_ticks=200)

    assert router.replicas[2].state == RETIRED
    for i, rid in enumerate(ids):
        resp = router.response(rid)
        assert resp.status == "ok" and resp.finish_reason == "length"
        np.testing.assert_array_equal(np.asarray(resp.tokens), refs[i])


# ---------------------------------------------------------------------------
# appended with the fleet split (pipe_tpu/fleet): the exactly-once
# ledger across a TRANSPORT drop — the wire dies mid-flight while the
# replica behind it may be perfectly healthy


class _CutWire:
    """Wrap a replica's transport so the wire can be cut mid-flight:
    once ``severed``, every remote call raises TransportError while
    local state reads (queue depth, counters) stay ungated — exactly
    the failure surface of a dead socket under a live child process.
    Plain class on purpose: inheriting ReplicaTransport's default
    methods would shadow the ``__getattr__`` delegation."""

    _LOCAL = frozenset(["queue_depth", "queue_capacity", "live_slots",
                        "default_max_new_tokens", "rpc_inflight",
                        "rpc_retries", "close", "idle", "drained",
                        "engine"])

    def __init__(self, inner):
        self.inner = inner
        self.severed = False

    def __getattr__(self, name):
        from pipe_tpu.fleet import TransportError
        attr = getattr(object.__getattribute__(self, "inner"), name)
        if name in _CutWire._LOCAL:
            return attr
        if self.severed:
            raise TransportError("wire cut (test)")
        if callable(attr):
            def call(*a, **k):
                if self.severed:
                    raise TransportError("wire cut (test)")
                return attr(*a, **k)
            return call
        return attr


def test_transport_drop_mid_flight_delivers_every_id_exactly_once():
    """Cut one replica's wire (NOT the replica) with work in flight:
    the drop path reclaims the stranded in-flight set exactly once —
    every id resolves to one terminal through a sibling, the dropped
    replica walks to RETIRED, and the ledger still refuses a forged
    duplicate afterwards."""
    router, t = make_fleet(3, slots=2)
    ids = [router.submit([1, 2], max_new_tokens=8).id for _ in range(9)]
    t[0] += 0.01
    router.tick()                     # work in flight on every replica
    rep = router.replicas[0]
    wire = _CutWire(rep.transport)
    rep.transport = wire
    wire.severed = True
    out = run(router, t)
    assert sorted(r.request_id for r in out) == sorted(ids)
    assert all(r.status == "ok" for r in out)
    assert rep.state == RETIRED
    assert [r.state for r in router.replicas[1:]] == [HEALTHY, HEALTHY]
    with pytest.raises(RuntimeError, match="exactly-once"):
        router._deliver(out[0])


def test_transport_drop_of_whole_fleet_fails_each_id_once():
    """Every wire cut at once, nothing recoverable: stranded and queued
    work fails loudly (``no_replicas``) — but still exactly once per
    id, never silently dropped and never doubled."""
    router, t = make_fleet(2, slots=2)
    ids = [router.submit([3, 4], max_new_tokens=8).id for _ in range(6)]
    t[0] += 0.01
    router.tick()
    for rep in router.replicas:
        wire = _CutWire(rep.transport)
        rep.transport = wire
        wire.severed = True
    out = run(router, t)
    assert sorted(r.request_id for r in out) == sorted(ids)
    assert all(r.status == "error" for r in out)
    assert all(r.finish_reason == "no_replicas" for r in out)
    assert all(rep.state == RETIRED for rep in router.replicas)
