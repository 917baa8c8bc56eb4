"""Fleet observability plane (docs/observability.md, "Fleet
observability"): mergeable registry snapshots, label escaping, event-log
rotation, the TraceBuffer, cross-mode FleetObserver semantics, trace
stitching across failover, the SLO monitor, and the zero-overhead
pledge.

Tier-1 runs the unit pieces plus the shared observer matrix over the
in-process transports (``inproc``/``thread``) and the decode-HLO pin.
The ``slow`` tier runs the same matrix over REAL child processes plus
the acceptance drill: N=3 proc replicas, SIGKILL one mid-flight — the
per-replica delivery-synchronized token counters must sum to the
parent-observed delivered total, every delivered id must reconstruct
into exactly one stitched timeline, and a failed-over id must show BOTH
placements in one trace. Telemetry off must mean ZERO ``obs`` frames on
the wire (frame census) and byte-identical decode HLO.
"""

import json
import os
import time

import pytest

from pipe_tpu.fleet import (FleetController, ProcessReplicaTransport,
                            ReplicaSpec, RouterPolicy)
from pipe_tpu.obs.events import EventLog
from pipe_tpu.obs.fleet_obs import (STAGE_RANK, FleetObserver, SloMonitor,
                                    SloTargets, TraceBuffer,
                                    prometheus_text)
from pipe_tpu.obs.telemetry import (MetricsRegistry, get_registry, labelled,
                                    null_registry, set_registry)
from pipe_tpu.resilience import TickWatchdog
from pipe_tpu.serve import RequestQueue, Router, ServeEngine
from test_router import FakeBackend

CFG_KW = dict(vocab=61, d_model=16, nhead=2, d_ff=32, n_layers=2,
              seq_len=64, dropout=0.0)


@pytest.fixture
def registry():
    """Fresh registry installed as the process default; restored after."""
    prev = get_registry()
    reg = MetricsRegistry()
    set_registry(reg)
    yield reg
    set_registry(prev)


# ---------------------------------------------------------------------------
# labelled(): collision-safe escaping


def test_labelled_escapes_label_separators():
    # a replica id carrying the separator characters must not be able
    # to forge another series' name
    assert labelled("m", replica="a.b") == "m{replica=a\\.b}"
    forged = labelled("m", a="1,b=2")
    honest = labelled("m", a="1", b="2")
    assert forged != honest
    assert labelled("m", r="x{y}") == "m{r=x\\{y\\}}"


def test_labelled_plain_int_labels_unchanged():
    # every existing call site labels with int replica indices — their
    # series names must not churn
    assert labelled("serve.fleet.heartbeat_age_s", replica=0) == \
        "serve.fleet.heartbeat_age_s{replica=0}"


# ---------------------------------------------------------------------------
# mergeable snapshots


def test_mergeable_snapshot_roundtrips_all_instruments(registry):
    registry.counter("c").inc(7)
    registry.gauge("g").set(2.5)
    t = registry.timer("t")
    t.observe(1.0)
    t.observe(2.0)
    h = registry.histogram("h")
    for v in (0.001, 0.5, 4.0):
        h.observe(v)
    snap = registry.snapshot(mergeable=True, base={})
    out = MetricsRegistry()
    out.merge_snapshot(snap)
    assert out.counter("c").value == 7
    assert out.gauge("g").value == 2.5
    assert out.timer("t").count == 2 and out.timer("t").total == 3.0
    oh = out.histogram("h")
    assert oh.count == 3 and oh.sum == pytest.approx(4.501)
    assert oh.min == 0.001 and oh.max == 4.0


def test_mergeable_snapshot_is_delta_against_base(registry):
    base = {}
    registry.counter("c").inc(5)
    registry.histogram("h").observe(1.0)
    first = registry.snapshot(mergeable=True, base=base)
    assert first["c"]["d"] == 5
    # no movement -> zero-delta instruments are omitted entirely
    assert registry.snapshot(mergeable=True, base=base) == {}
    registry.counter("c").inc(2)
    second = registry.snapshot(mergeable=True, base=base)
    assert second["c"]["d"] == 2 and "h" not in second
    # a receiver that merges every delta reconstructs the totals
    out = MetricsRegistry()
    out.merge_snapshot(first)
    out.merge_snapshot(second)
    assert out.counter("c").value == 7
    assert out.histogram("h").count == 1


def test_merge_accumulates_histogram_buckets_across_sources(registry):
    a, b = MetricsRegistry(), MetricsRegistry()
    a.histogram("h").observe(0.01)
    a.histogram("h").observe(0.02)
    b.histogram("h").observe(8.0)
    merged = MetricsRegistry()
    merged.merge_snapshot(a.snapshot(mergeable=True, base={}))
    merged.merge_snapshot(b.snapshot(mergeable=True, base={}))
    h = merged.histogram("h")
    assert h.count == 3
    assert h.percentile(0.5) >= 0.02       # fleet median, not one source
    assert h.percentile(0.99) >= 8.0
    assert h.min == 0.01 and h.max == 8.0


def test_merge_into_disabled_registry_is_noop():
    reg = MetricsRegistry()
    reg.counter("c").inc(3)
    null_registry().merge_snapshot(reg.snapshot(mergeable=True, base={}))
    assert null_registry().counter("c").value == 0


# ---------------------------------------------------------------------------
# EventLog: size-bounded rotation + torn-final-line tolerance


def test_event_log_rotates_at_max_bytes(tmp_path):
    path = str(tmp_path / "ev.jsonl")
    with EventLog(path, max_bytes=2048) as log:
        for i in range(200):
            log.event("request", request=i, stage="queued",
                      pad="x" * 64)
    assert os.path.exists(path + ".1"), "rollover file missing"
    assert os.path.getsize(path) <= 2048 + 4096  # one record of slack
    recs = EventLog.read(path)
    assert recs, "post-rotation log must be readable"
    header = recs[0]
    assert header["kind"] == "log_open" and header.get("rotated") is True
    # the rollover file holds the OLDER records
    old = EventLog.read(path + ".1")
    assert old[-1]["request"] < recs[-1]["request"]


def test_event_log_read_tolerates_torn_final_line(tmp_path):
    path = str(tmp_path / "torn.jsonl")
    with EventLog(path) as log:
        log.event("request", request=0, stage="queued")
        log.event("request", request=1, stage="queued")
    with open(path, "a") as f:
        f.write('{"kind": "request", "request": 2, "sta')   # crash here
    recs = EventLog.read(path)
    assert [r.get("request") for r in recs if r["kind"] == "request"] \
        == [0, 1]


def test_event_log_read_raises_on_torn_middle_line(tmp_path):
    # only a TRAILING torn line is a crash artifact; garbage in the
    # middle is corruption and must stay loud
    path = str(tmp_path / "bad.jsonl")
    with open(path, "w") as f:
        f.write('{"kind": "request", "request": 0}\n')
        f.write('{"kind": "requ\n')
        f.write('{"kind": "request", "request": 1}\n')
    with pytest.raises(json.JSONDecodeError):
        EventLog.read(path)


def test_event_log_rejects_tiny_max_bytes(tmp_path):
    with pytest.raises(ValueError):
        EventLog(str(tmp_path / "x.jsonl"), max_bytes=10)


# ---------------------------------------------------------------------------
# TraceBuffer


def test_trace_buffer_bounded_drops_oldest_and_counts():
    buf = TraceBuffer(maxlen=4)
    for i in range(7):
        buf.event("request", request=i)
    assert buf.dropped == 3
    got = [r["request"] for r in buf.drain()]
    assert got == [3, 4, 5, 6]
    assert buf.drain() == []                  # drain clears


def test_trace_buffer_spans_nest_like_event_log():
    buf = TraceBuffer()
    with buf.span("request", request=1) as outer:
        with buf.span("request", request=1) as inner:
            pass
    recs = buf.drain()
    assert recs[0]["id"] == inner and recs[0]["parent"] == outer
    assert recs[1]["id"] == outer and recs[1]["parent"] is None
    assert recs[0]["dur"] >= 0.0


# ---------------------------------------------------------------------------
# stitch ordering (synthetic streams: the SIGKILL failover shape)


class _StubTransport:
    def __init__(self, events=None):
        self._events = events
        self.obs_tokens_out = 0
        self.obs_responses_out = 0
        self.queue_depth = 0
        self.live_slots = 0

    def obs_view(self):
        if self._events is None:
            return None
        return (MetricsRegistry(), 0.1, 3, list(self._events))


class _StubReplica:
    def __init__(self, index, transport):
        self.index = index
        self.state = "healthy"
        self.transport = transport


class _StubController:
    def __init__(self, replicas, parent_records):
        self.replicas = replicas
        self._responses = {}
        self.events = type("E", (), {"path": None})()
        self.parent_records = parent_records


def test_stitch_orders_failover_as_one_trace_two_placements():
    # parent skeleton: queued -> placed(1) -> retry_parked(1) ->
    # placed(2) -> delivered; child streams contribute prefill/terminal
    # from two UNRELATED clocks (replica1's t is tiny — wall-clock
    # alone would sort it before replica0's records)
    tid = "abc123"
    parent = [
        {"kind": "request", "request": 7, "trace": tid, "stage": "queued",
         "t": 1.0},
        {"kind": "request", "request": 7, "trace": tid, "stage": "placed",
         "replica": 0, "attempts": 1, "t": 1.1},
        {"kind": "resilience", "request": 7, "trace": tid,
         "stage": "retry_parked", "attempts": 1, "t": 5.0},
        {"kind": "request", "request": 7, "trace": tid, "stage": "placed",
         "replica": 1, "attempts": 2, "t": 5.1},
        {"kind": "request", "request": 7, "trace": tid,
         "stage": "delivered", "attempts": 2, "t": 9.0},
    ]
    rep0 = [{"kind": "request", "request": 7, "trace": tid,
             "stage": "prefill", "attempts": 1, "t": 900.5}]
    rep1 = [{"kind": "request", "request": 7, "trace": tid,
             "stage": "prefill", "attempts": 2, "t": 0.002},
            {"kind": "request", "request": 7, "trace": tid,
             "stage": "terminal", "attempts": 2, "t": 0.9}]
    ctl = _StubController(
        [_StubReplica(0, _StubTransport(rep0)),
         _StubReplica(1, _StubTransport(rep1))], parent)
    obs = FleetObserver(ctl, parent_events=parent)
    traces = obs.stitch()
    assert list(traces) == [tid], "one trace across the failover"
    stages = [(r["stage"], r.get("attempts", 0), r["src"])
              for r in traces[tid]]
    assert stages == [
        ("queued", 0, "parent"),
        ("placed", 1, "parent"),
        ("prefill", 1, "replica0"),
        ("retry_parked", 1, "parent"),
        ("placed", 2, "parent"),
        ("prefill", 2, "replica1"),
        ("terminal", 2, "replica1"),
        ("delivered", 2, "parent"),
    ]
    by_req = obs.stitch_by_request()
    assert list(by_req) == [7] and len(by_req[7]) == 8


def test_stitch_groups_traceless_request_records_by_request_id():
    parent = [{"kind": "request", "request": 3, "stage": "queued",
               "t": 0.0},
              {"kind": "other", "t": 0.0}]           # no trace, no request
    ctl = _StubController([], parent)
    traces = FleetObserver(ctl, parent_events=parent).stitch()
    assert list(traces) == ["req:3"]
    assert STAGE_RANK["queued"] == 0                  # pinned vocabulary


def test_observer_peeks_live_trace_buffer_without_draining():
    """A live TraceBuffer passed as ``parent_events`` (the serve
    driver's --trace-out wiring) is read non-mutatingly: stitch twice,
    buffer still full."""
    buf = TraceBuffer()
    buf.event("request", request=1, trace="t1", stage="queued")
    obs = FleetObserver(_StubController([], []), parent_events=buf)
    assert list(obs.stitch()) == ["t1"]
    assert list(obs.stitch()) == ["t1"], "peek must not drain"
    assert buf.peek() and buf.drain(), "records still buffered"


# ---------------------------------------------------------------------------
# salvage: accepted-but-unpolled responses survive a transport drop


class _FrameAcceptTransport:
    """The surface a SIGKILL leaves behind on the process transport:
    terminal responses buffered AND counted at frame-accept time
    (``obs_tokens_out``), every remote call raising TransportError once
    the wire is severed, and ``salvage()`` still readable (the buffer
    is parent-side state — no socket needed)."""

    queue_capacity = 32
    default_max_new_tokens = 32
    rpc_inflight = 0
    rpc_retries = 0

    def __init__(self):
        self.obs_tokens_out = 0
        self.obs_responses_out = 0
        self._placed = {}
        self._buffer = []
        self.severed = False

    def _gate(self):
        if self.severed:
            from pipe_tpu.fleet import TransportError
            raise TransportError("wire cut (test)")

    def validate(self, prompt_len, max_new_tokens):
        pass

    def place(self, req):
        self._gate()
        req.attempts += 1
        self._placed[req.id] = req

    def poll(self):
        self._gate()
        out, self._buffer = self._buffer, []
        return out

    def evict_queued(self):
        self._gate()
        return []

    def cancel(self, request_id):
        self._gate()
        return False

    def drain(self):
        self._gate()

    def health(self):
        self._gate()
        from pipe_tpu.fleet import ReplicaHealth
        return ReplicaHealth()

    @property
    def drained(self):
        return not self._placed

    @property
    def idle(self):
        return not self._placed and not self._buffer

    @property
    def queue_depth(self):
        return len(self._placed)

    live_slots = 0

    def close(self):
        pass

    def obs_view(self):
        return None

    def accept_response(self, resp):
        """What the pump thread does on a ``response`` frame."""
        self._placed.pop(resp.request_id, None)
        self._buffer.append(resp)
        self.obs_tokens_out += len(resp.tokens)
        self.obs_responses_out += 1

    def salvage(self):
        out, self._buffer = self._buffer, []
        return out


def test_transport_drop_salvages_accepted_responses(registry):
    """A terminal response accepted off the wire (tokens already
    counted into ``obs_tokens_out``) but never polled must be DELIVERED
    by the drop path, not re-run: the request keeps attempts=1, the
    observer's delivered-token reconciliation holds, and the rescue is
    visible in ``serve.fleet.salvaged``."""
    from pipe_tpu.fleet import InProcessTransport
    from pipe_tpu.serve.queue import Response

    clock = [0.0]
    dying = _FrameAcceptTransport()
    healthy = InProcessTransport(
        ServeEngine(FakeBackend(2),
                    RequestQueue(capacity=32, clock=lambda: clock[0]),
                    watchdog=TickWatchdog(stuck_slack_ticks=None)))
    ctl = FleetController(
        [dying, healthy],
        RequestQueue(capacity=32, clock=lambda: clock[0]),
        policy=RouterPolicy(backoff_base_s=0.0))
    try:
        req = ctl.submit([1, 2, 3], max_new_tokens=8)
        clock[0] += 0.01
        ctl.tick()
        assert req.id in dying._placed, "placed on the dying transport"
        # the child finishes; the response frame crosses into the
        # parent (counted) — and THEN the wire dies, un-polled
        dying.accept_response(Response(
            request_id=req.id, tokens=[5] * 8, status="ok",
            finish_reason="length", prompt_len=3, ttft=0.01,
            latency=0.02))
        dying.severed = True
        clock[0] += 0.01
        out = []
        for _ in range(50):
            out.extend(ctl.tick())
            clock[0] += 0.01
            if out:
                break
        assert [r.request_id for r in out] == [req.id]
        assert out[0].status == "ok" and len(out[0].tokens) == 8
        assert req.attempts == 1, "salvaged, not retried"
        rec = FleetObserver(ctl).reconcile()
        assert rec["reconciled"], rec
        assert rec["delivered_tokens"] == 8
        assert rec["per_replica_tokens_out"][0] == 8
        assert registry.counter("serve.fleet.salvaged").value == 1
    finally:
        ctl.close()


# ---------------------------------------------------------------------------
# SLO monitor + Prometheus exposition


def _slo_registry(ttfts=(0.01, 0.02), e2es=(0.1,), delivered=4, ok=4,
                  timed_out=0, shed=0):
    reg = MetricsRegistry()
    for v in ttfts:
        reg.histogram("serve.engine.ttft_sec").observe(v)
    for v in e2es:
        reg.histogram("serve.engine.e2e_sec").observe(v)
    reg.counter("serve.fleet.delivered").inc(delivered)
    reg.counter("serve.fleet.ok").inc(ok)
    reg.counter("serve.engine.timed_out").inc(timed_out)
    reg.counter("serve.engine.shed").inc(shed)
    return reg


def test_slo_verdict_ok_and_observed_fields():
    mon = SloMonitor(SloTargets(ttft_p99_s=1.0, goodput_min=0.9))
    v = mon.verdict(_slo_registry())
    assert v["ok"] and v["violations"] == []
    assert v["observed"]["goodput"] == 1.0
    assert v["observed"]["delivered"] == 4
    assert v["targets"] == {"ttft_p99_s": 1.0, "goodput_min": 0.9}


def test_slo_verdict_flags_max_and_min_sense_violations():
    mon = SloMonitor(SloTargets(ttft_p99_s=0.001, goodput_min=0.95))
    v = mon.verdict(_slo_registry(ttfts=(0.5,), delivered=10, ok=5))
    bad = {x["slo"] for x in v["violations"]}
    assert not v["ok"] and bad == {"ttft_p99_s", "goodput_min"}
    miss = SloMonitor(SloTargets(deadline_miss_max=0.1)).verdict(
        _slo_registry(delivered=10, ok=8, timed_out=2))
    assert not miss["ok"]
    assert miss["observed"]["deadline_miss_rate"] == pytest.approx(0.2)


def test_prometheus_text_renders_all_instrument_kinds():
    reg = _slo_registry()
    reg.gauge(labelled("serve.fleet.replica.state", replica=0)).set(0)
    reg.timer("serve.engine.host_sec").observe(0.5)
    text = prometheus_text(reg)
    assert "# TYPE serve_fleet_delivered counter" in text
    assert "serve_fleet_delivered 4" in text
    assert 'serve_fleet_replica_state{replica="0"} 0' in text
    assert "serve_engine_host_sec_count 1" in text
    assert 'serve_engine_ttft_sec_bucket{le="+Inf"} 2' in text
    assert "serve_engine_ttft_sec_count 2" in text


# ---------------------------------------------------------------------------
# the shared observer matrix: one contract over all three fleet modes


def _proc_spec(**kw):
    base = dict(
        lm_cfg=dict(CFG_KW),
        num_slots=2, max_len=48, init_seed=0,
        gen=dict(max_new_tokens=8, temperature=0.0),
        decode_chunk=1, heartbeat_interval_s=0.05,
    )
    base.update(kw)
    return ReplicaSpec(**base)


def _make_fleet(mode, n=2, capacity=64):
    trace_buf = TraceBuffer(maxlen=100_000)
    if mode == "proc":
        transports = [ProcessReplicaTransport(_proc_spec())
                      for _ in range(n)]
        ctl = FleetController(
            transports, RequestQueue(capacity=capacity),
            policy=RouterPolicy(backoff_base_s=0.0,
                                heartbeat_timeout_s=5.0),
            event_log=trace_buf)
        return ctl, trace_buf
    engines = [ServeEngine(FakeBackend(2),
                           RequestQueue(capacity=capacity),
                           watchdog=TickWatchdog(stuck_slack_ticks=None))
               for _ in range(n)]
    ctl = Router(engines, RequestQueue(capacity=capacity),
                 policy=RouterPolicy(backoff_base_s=0.0),
                 event_log=trace_buf,
                 async_tick=(mode == "thread"))
    return ctl, trace_buf


def _run_to_idle(ctl, timeout_s=120.0):
    deadline = time.monotonic() + timeout_s
    while not ctl.idle:
        ctl.tick()
        time.sleep(0.005)
        assert time.monotonic() < deadline, "fleet never went idle"


MODES = ["inproc", "thread",
         pytest.param("proc", marks=pytest.mark.slow)]


@pytest.mark.parametrize("mode", MODES)
def test_observer_contract_across_fleet_modes(mode, registry):
    ctl, trace_buf = _make_fleet(mode)
    try:
        ids = [ctl.submit([1 + i, 2, 3], max_new_tokens=4, seed=i).id
               for i in range(6)]
        _run_to_idle(ctl)
    finally:
        ctl.close()
    obs = FleetObserver(ctl, parent_events=trace_buf.drain())

    # reconciliation: delivery-synchronized per-replica token counters
    # sum to the parent ledger's delivered total, in every mode
    rec = obs.reconcile()
    assert rec["reconciled"], rec
    assert rec["delivered_tokens"] == sum(
        len(ctl.response(i).tokens) for i in ids)

    per = obs.per_replica()
    assert set(per) == {0, 1}
    for view in per.values():
        assert view["state"] == "healthy"
        if mode == "proc":
            assert view["shipped"] and view["staleness_s"] is not None
            assert view["obs_seq"] >= 0
        else:
            assert not view["shipped"] and view["staleness_s"] == 0.0
    assert sum(v["responses_out"] for v in per.values()) == len(ids)

    # the merged rollup carries fleet counters AND engine histograms
    # (shipped over the wire in proc mode, shared registry otherwise)
    roll = obs.rollup()
    assert roll.counter("serve.fleet.delivered").value == len(ids)
    assert roll.histogram("serve.engine.ttft_sec").count >= len(ids)

    # every submitted id reconstructs into exactly one stitched trace
    # with the full lifecycle — including engine-side stages (inherited
    # event log in-process; shipped child events over the wire)
    by_req = obs.stitch_by_request()
    owners = {}
    for key, recs in obs.stitch().items():
        for r in recs:
            if r.get("request") is not None:
                owners.setdefault(int(r["request"]), set()).add(key)
    for i in ids:
        assert i in by_req, f"request {i} lost from the stitched traces"
        assert len(owners[i]) == 1, f"request {i} split across traces"
        stages = {r.get("stage") for r in by_req[i]}
        assert {"queued", "placed", "prefill", "terminal",
                "delivered"} <= stages, (i, stages)

    if mode == "proc":
        assert registry.counter("serve.fleet.obs_frames").value > 0
        for rep in ctl.replicas:
            census = rep.transport._frame_census
            assert census.get("obs", 0) > 0, census


# ---------------------------------------------------------------------------
# acceptance drill: N=3 proc fleet, SIGKILL one child mid-flight


@pytest.mark.slow
def test_proc_drill_sigkill_reconciles_and_stitches(registry):
    trace_buf = TraceBuffer(maxlen=100_000)
    transports = [ProcessReplicaTransport(_proc_spec())
                  for _ in range(3)]
    ctl = FleetController(transports, RequestQueue(capacity=512),
                          policy=RouterPolicy(backoff_base_s=0.0,
                                              heartbeat_timeout_s=5.0),
                          event_log=trace_buf)
    ids = []
    try:
        def submit_one(i):
            ids.append(ctl.submit([i % 40 + 1, 2, 3],
                                  max_new_tokens=4, seed=i).id)

        for i in range(12):
            submit_one(i)
        # kill only once the victim HOLDS work, so at least one request
        # demonstrably fails over (same idiom as test_fleet.py)
        deadline = time.monotonic() + 60.0
        while True:
            ctl.tick()
            if transports[2]._inflight:
                break
            time.sleep(0.01)
            if ctl.idle and len(ids) < 256:
                for _ in range(12):
                    submit_one(len(ids))
            assert time.monotonic() < deadline, "victim never got work"
        victim_inflight = list(transports[2]._inflight)
        transports[2]._proc.kill()
        _run_to_idle(ctl)
    finally:
        ctl.close()

    obs = FleetObserver(ctl, parent_events=trace_buf.drain())

    # 1) merged rollups reconcile: per-replica delivery-synchronized
    #    token counters sum to the parent-observed delivered total —
    #    ACROSS the SIGKILL (tokens ride the same frame as the
    #    response, so a lost child can't desynchronize the ledger)
    rec = obs.reconcile()
    assert rec["reconciled"], rec
    assert rec["tokens_out_sum"] == sum(
        len(ctl.response(i).tokens) for i in ids)

    # 2) a stitched timeline for EVERY delivered id, each in exactly
    #    one trace
    by_req = obs.stitch_by_request()
    owners = {}
    for key, recs in obs.stitch().items():
        for r in recs:
            if r.get("request") is not None:
                owners.setdefault(int(r["request"]), set()).add(key)
    for i in ids:
        assert ctl.response(i) is not None, "id vanished across SIGKILL"
        assert i in by_req, f"request {i} lost from the stitched traces"
        assert len(owners[i]) == 1, f"request {i} split across traces"

    # 3) a failed-over id shows BOTH placements in ONE trace, ordered
    #    by attempt
    failed_over = [i for i in ids
                   if len([r for r in by_req[i]
                           if r.get("stage") == "placed"]) >= 2]
    assert failed_over, f"no failover observed (victim held "\
        f"{victim_inflight})"
    for i in failed_over:
        placed = [r for r in by_req[i] if r.get("stage") == "placed"]
        attempts = [r["attempts"] for r in placed]
        assert len(set(attempts)) == len(attempts) >= 2
        assert attempts == sorted(attempts), "placements out of order"

    # 4) the obs plane itself showed up on the wire and in metrics
    assert registry.counter("serve.fleet.obs_frames").value > 0
    per = obs.per_replica()
    assert any(v["staleness_s"] is not None for v in per.values())


# ---------------------------------------------------------------------------
# zero-overhead pledge


@pytest.mark.slow
def test_telemetry_disabled_ships_zero_obs_frames():
    tr = ProcessReplicaTransport(_proc_spec(telemetry=False))
    try:
        q = RequestQueue()
        req = q.submit([5, 6, 7], max_new_tokens=4, seed=0)
        tr.place(req)
        got = []
        deadline = time.monotonic() + 120.0
        while not got:
            got.extend(tr.poll())
            time.sleep(0.02)
            assert time.monotonic() < deadline
        # several heartbeat periods: any obs shipping would have fired
        time.sleep(0.5)
        census = dict(tr._frame_census)
    finally:
        tr.close()
    assert census.get("hb", 0) > 0, census          # wire was alive
    assert census.get("obs", 0) == 0, census        # and carried no obs
    reg, age, seq, events = tr.obs_view()
    assert age is None and events == []


def test_decode_hlo_byte_identical_under_obs_plane(registry):
    import jax

    from pipe_tpu.inference import GenerationConfig
    from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
    from pipe_tpu.serve import SingleDeviceSlotBackend

    model = PipelinedLM(LMConfig(**CFG_KW), 1)
    params = model.init(jax.random.key(0))

    def lowered():
        be = SingleDeviceSlotBackend(
            model, params, num_slots=2, max_len=24,
            gen=GenerationConfig(max_new_tokens=4, temperature=0.0))
        fn, args = be.decode_program()
        return fn.lower(*args).as_text(), be

    base, _ = lowered()

    # telemetry OFF (the child worker's spec.telemetry=False path)
    prev = get_registry()
    set_registry(null_registry())
    try:
        off, _ = lowered()
    finally:
        set_registry(prev)
    assert off == base

    # full obs plane ON: live registry, TraceBuffer event log, traced
    # requests actually served through the engine
    text, be = lowered()
    eng = ServeEngine(be, RequestQueue(), event_log=TraceBuffer())
    eng.submit([1, 2, 3], max_new_tokens=4, seed=0)
    out = eng.run_until_idle()
    assert out and out[0].status == "ok"
    after, _ = lowered()
    assert base == text == after
