"""The launch cycle's readers (``benchmark/pb_cycle.py`` and the seven
``engine.idle_*``, ``engine.ttft_*`` and ``engine.traced_gap_ratio`` readers
of ``benchmark/layers/``) against hand counts, on a capture built here with
``pipe_tpu/obs/xplane.py``'s encoder, as ``test_program_spans.py`` builds
its own. Times below in microseconds; the stretch runs from 1,000 to 21,000.

The host (tick 2 runs past the stretch's end)::

    tick 0  1,100-9,000   launch -1,500 | first token's read -2,000 |
                          wait 2,015-8,000 | fetch 8,010-8,450 | retire, done
    tick 1  9,400-17,000  launch -9,800 | first token's read -9,880 |
                          wait 9,895-16,000 | fetch 16,010-16,400 | retire, done
    tick 2  17,300-       launch -17,700 | wait 17,715-

The device is busy 1,400-7,800, 9,750-15,900 and from 17,750 on, so two of
its three idle gaps straddle a wait's end, a fetch, the tick's end, the
caller's turn and the next tick's admission::

    1,000-1,400    caller 100 | turn 300
    7,800-9,750    wait 200 | turn 10 | fetch 440 | turn 550 | caller 400 |
                   turn 350
    15,900-17,750  wait 100 | turn 10 | fetch 390 | turn 600 | caller 300 |
                   turn 415 | wait 35 (tick 2's: the launch queued, the
                   device not yet started)
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import pb_core  # noqa: E402
import pb_cycle  # noqa: E402
import pb_spans  # noqa: E402
import pb_trace  # noqa: E402
from pipe_tpu.obs import events as ev  # noqa: E402
from pipe_tpu.obs.telemetry import (MetricsRegistry, get_registry,  # noqa: E402
                                    labelled, set_registry)
from pipe_tpu.obs.xplane import (TraceEvent, TraceLine, TracePlane,  # noqa: E402
                                 encode_xspace)

K = 1000
CELLS = ["laguna-serve-closed16", "sdar-serve-closed32"]
SEVEN = ["engine.idle_wait_ms", "engine.idle_fetch_ms",
         "engine.idle_turn_ms", "engine.idle_caller_ms",
         "engine.ttft_queued_ms", "engine.ttft_launch_ms",
         "engine.traced_gap_ratio"]
PHASE_SPANS = (ev.SERVE_DECODE_WAIT, ev.SERVE_DECODE_FETCH,
               ev.SERVE_FIRST_TOKEN)


def _span(name, start, end, **stats):
    return TraceEvent(name, start * K, (end - start) * K, stats=stats)


def _op(name, start, end, program):
    hlo = f"%{name} = bf16[8,128]{{1,0}} fusion(bf16[8,128]{{1,0}} %p.0)"
    return TraceEvent(hlo, start * K, (end - start) * K,
                      meta={"program_id": program,
                            "tf_op": "jit(_resident_fn)/ffn/dot_general:"})


def _tick(n, start, end, launched, first, wait, fetch, emitted):
    """A tick's spans: the launch's dispatch ends at ``launched``; with
    ``first = (read's end, stages)`` an admission whose first token is
    read behind the dispatch; ``wait`` and ``fetch`` as ``(start, end)``."""
    out = [_span(ev.SERVE_TICK, start, end, tick=n, live=2, queued=1,
                 away_ms=0.4),
           _span(ev.SERVE_REAP, start, start + 10)]
    if first:
        out += [_span(ev.SERVE_ADMIT, start + 20, start + 300, request=n,
                      trace="", slot=0, prompt_len=9, queued_ms=0.5),
                _span(ev.SERVE_PREFILL, start + 30, start + 290, slot=0,
                      prompt_len=9, bucket=16)]
    decode_end = fetch[1] + 150 if fetch else end
    out += [_span(ev.SERVE_DECODE, launched - 80, decode_end, live=2),
            _span(ev.SERVE_DECODE_LAUNCH, launched - 70, launched, chunks=8)]
    if first:
        read_end, stages = first
        out += [_span(ev.SERVE_PREFILL_SYNC, launched + 5, read_end, slot=0),
                _span(ev.SERVE_FIRST_TOKEN, read_end + 5, read_end + 6,
                      request=n, slot=0, **stages)]
    out += [_span(ev.SERVE_DECODE_SYNC, wait[0] - 5,
                  fetch[1] + 50 if fetch else end),
            _span(ev.SERVE_DECODE_WAIT, *wait, rounds=3)]
    if fetch:
        out += [_span(ev.SERVE_DECODE_FETCH, *fetch, reads=2, bytes=4096),
                _span(ev.SERVE_RETIRE, fetch[1] + 170, fetch[1] + 250,
                      finished=1),
                _span(ev.SERVE_DECODE_DONE, fetch[1] + 260, fetch[1] + 261,
                      steps=12, chunks=3, live=2, rows=40, emitted=emitted,
                      early_exit=1)]
    return out


def build_planes(phases=True):
    ops = [_op("fusion.1", 1400, 7800, 22), _op("fusion.1", 9750, 15900, 22),
           _op("fusion.1", 17750, 21200, 22)]
    modules = [TraceEvent("jit__resident_fn(22)", s * K, (e - s) * K)
               for s, e in ((1400, 7800), (9750, 15900), (17750, 21200))]
    host = [_span(pb_trace.WINDOW_SPAN, 1000, 21000)]
    host += _tick(0, 1100, 9000, 1500,
                  (2000, dict(queued_ms=0.5, admit_ms=0.38, launch_ms=0.505,
                              ttft_ms=1.385)),
                  (2015, 8000), (8010, 8450), emitted=70)
    host += _tick(1, 9400, 17000, 9800,
                  (9880, dict(queued_ms=0.3, admit_ms=0.38, launch_ms=0.085,
                              ttft_ms=0.765)),
                  (9895, 16000), (16010, 16400), emitted=32)
    host += _tick(2, 17300, 21500, 17700, None, (17715, 21300), None,
                  emitted=0)
    if not phases:                      # a parent commit's program
        host = [e for e in host if e.name not in PHASE_SPANS]
    return [
        TracePlane("/device:TPU:0", [TraceLine(pb_trace.OPS_LINE, 0, ops),
                                     TraceLine(pb_trace.MODULES_LINE, 0,
                                               modules)]),
        TracePlane("/host:CPU", [TraceLine("main", 0, host)]),
    ]


def _facts(tmp_path, planes):
    where = tmp_path / "trace" / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(encode_xspace(planes))
    return {"trace_dir": str(tmp_path / "trace")}


@pytest.fixture
def facts(tmp_path):
    return _facts(tmp_path, build_planes())


@pytest.fixture
def registry():
    """A process whose engine closed 101 launch cycles, one of which stood
    12 s in its turn (a set-up's compile)."""
    old = set_registry(MetricsRegistry())
    reg = get_registry()
    for phase, total in (("wait", 6.0), ("fetch", 0.040), ("turn", 12.100),
                         ("caller", 0.035)):
        timer = reg.timer(f"serve.engine.cycle.{phase}_sec")
        timer.count, timer.total = 101, total
    reg.timer(labelled("serve.engine.stall_sec", phase="turn")).observe(12.0)
    try:
        yield reg
    finally:
        set_registry(old)


def _read(metric, facts):
    return pb_core.load_by_path(f"layers/{metric}.py").read(facts)


# idle by phase, in microseconds, over the stretch's two finished launches
IDLE = {"wait": 200 + 100 + 35, "fetch": 440 + 390,
        "turn": 300 + (10 + 550 + 350) + (10 + 600 + 415),
        "caller": 100 + 400 + 300}
WANT = {
    "engine.idle_wait_ms": IDLE["wait"] / 1e3 / 2,
    "engine.idle_fetch_ms": IDLE["fetch"] / 1e3 / 2,
    "engine.idle_turn_ms": IDLE["turn"] / 1e3 / 2,
    "engine.idle_caller_ms": IDLE["caller"] / 1e3 / 2,
    "engine.ttft_queued_ms": (0.5 + 0.3) / 2,
    "engine.ttft_launch_ms": (0.505 + 0.085) / 2,
    # two whole cycles, dispatch to dispatch 8,300 and 7,900, of which the
    # wait (to the round count's arrival) 6,500 and 6,200: the host's part
    # 1,800 and 1,700; the process's, the stalled turn and its cycle left
    # out: (0.040 + 0.100 + 0.035) s over 100 cycles
    "engine.traced_gap_ratio": ((1800 + 1700) / 2) / 1750,
}


@pytest.mark.parametrize("metric", SEVEN)
def test_each_reader_against_the_hand_count(metric, facts, registry):
    assert _read(metric, facts) == pytest.approx(WANT[metric])


def test_the_four_idle_metrics_add_up_to_the_stretchs_idle(facts):
    cap = pb_spans.read(facts)
    idle = (21000 - 1000) * K - sum(e - s for s, e in cap.busy)
    assert idle == 4200 * K == sum(IDLE.values()) * K
    split, launches = pb_cycle.idle_by_phase(facts)
    assert launches == 2
    assert split == pytest.approx({p: v * K for p, v in IDLE.items()})
    # the rule of the gap's middle books the two straddling gaps whole, to
    # whatever is open at 8,775 and 16,825: the ticks themselves
    assert cap.gaps_by_span()[ev.SERVE_TICK] == (1950 + 1850) * K


def test_the_whole_cycles_phase_by_phase(facts):
    assert pb_cycle.cycles(facts) == pytest.approx([
        {"wait": 6500 * K, "fetch": 440 * K, "caller": 400 * K,
         "turn": (8300 - 6500 - 440 - 400) * K},
        {"wait": 6200 * K, "fetch": 390 * K, "caller": 300 * K,
         "turn": (7900 - 6200 - 390 - 300) * K}])


def test_the_ratio_follows_the_timers(facts, registry):
    registry.timer("serve.engine.cycle.turn_sec").total -= 0.035
    assert _read("engine.traced_gap_ratio", facts) == pytest.approx(1.25)
    # a stall in the wait is no part of the host's gap: nothing changes
    registry.timer(labelled("serve.engine.stall_sec",
                            phase="wait")).observe(3.0)
    assert _read("engine.traced_gap_ratio", facts) == pytest.approx(1.25)


def test_the_report_holds_the_table(facts, registry):
    rep = pb_cycle.report(facts)
    assert rep["launches"] == 2 and rep["whole_cycles"] == 2
    assert rep["stretch_s"] == pytest.approx(0.020)
    assert rep["idle_share"] == pytest.approx(4200 / 20000)
    # what the launches emitted and the two first tokens read beside them
    assert rep["stretch_tokens_per_s"] == pytest.approx((70 + 32 + 2) / 0.02)
    assert rep["idle_ms_per_launch"] == pytest.approx(
        {p: v / 1e3 / 2 for p, v in IDLE.items()})
    # the device done and the host not yet told: 200 and 100; the other 35
    # under a wait lie at its start
    assert rep["idle_wait_tail_ms_per_launch"] == pytest.approx(0.3 / 2)
    assert rep["phase_ms_per_launch"]["wait"] == pytest.approx(6.35)
    assert rep["first_tokens"] == 2
    assert rep["first_token_ms"]["ttft_ms"] == pytest.approx(1.075)
    assert rep["process_stalls"] == {"turn": [1, 12.0]}
    assert rep["process_gap_ms"] == pytest.approx(1.75)
    assert rep["traced_gap_ratio"] == pytest.approx(1.0)


@pytest.mark.parametrize("metric", SEVEN)
def test_a_program_without_the_spans_reads_none(metric, tmp_path, registry):
    """A parent commit under this PR's benchmark files: the old spans are
    all there, the three new ones are not; nothing raises. And the timers
    alone, with no span to hold them against, give no ratio."""
    facts = _facts(tmp_path, build_planes(phases=False))
    assert pb_spans.decode_done(facts)          # the launches are there
    assert _read(metric, facts) is None
    assert _read(metric, {"trace_dir": str(tmp_path / "nothing")}) is None


def test_the_spans_without_the_timers_give_no_ratio(facts):
    old = set_registry(MetricsRegistry())
    try:
        assert _read("engine.traced_gap_ratio", facts) is None
        assert _read("engine.idle_fetch_ms", facts) is not None
    finally:
        set_registry(old)


def test_the_seven_entries_list_the_two_cells_and_no_proposed_name():
    spec = pb_core.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    proposed = {m["name"] for m in pb_core.read_json(
        os.path.join(BENCH, "layers", "proposed_per_layer.json"))}
    assert [m["name"] for m in spec["per_layer"][-7:]] == SEVEN
    for m in spec["per_layer"][-7:]:
        assert m["workloads"] == CELLS and m["name"] not in proposed
        assert m["layer"] == "serve engine"
        assert m["moves"] == "serve_tokens_per_s" and m["better"] == "lower"
        assert os.path.exists(
            os.path.join(BENCH, "layers", m["name"] + ".py"))
    assert {m["source"] for m in spec["per_layer"][-7:]} == {
        "device_trace", "program_span", "program_counter"}
    # the vocabulary the readers match on is the program's
    assert pb_cycle.PHASES == ev.CYCLE_PHASES
    assert (pb_cycle.WAIT, pb_cycle.FETCH, pb_cycle.FIRST, pb_cycle.TICK,
            pb_cycle.LAUNCH, pb_cycle.DONE) == (
        ev.SERVE_DECODE_WAIT, ev.SERVE_DECODE_FETCH, ev.SERVE_FIRST_TOKEN,
        ev.SERVE_TICK, ev.SERVE_DECODE_LAUNCH, ev.SERVE_DECODE_DONE)
