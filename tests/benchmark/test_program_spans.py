"""The readers of the program's own spans and scopes (``benchmark/
pb_spans.py`` and the fourteen ``benchmark/layers/`` readers of PR 25) against
hand counts, on a capture written with ``pipe_tpu/obs/xplane.py``'s encoder:
``benchmark/testdata/program_spans.xplane.pb``. ``build_planes`` below IS
the fixture (a test holds the committed file to it), so every number in the
hand counts can be read off this file.

The window runs from 1 ms to 11 ms. On the device: two runs of the train
step (program 11), one prefill (33) and one resident launch (22) whose
``while`` holds a ``copy``; in nanoseconds::

    train step, run 1 at 1,100,000 (run 2: the same, 2,100,000 later)
      embed 100k | attention 500k | ffn 300k | attention, remat 200k |
      head 200k | loss 100k | optimizer 400k | unscoped 100k | idle 100k
    prefill at 5,400,000: kv_cache 200k | attention 300k
    resident at 6,000,000, 4,000k long:
      copy.1 400k | while.1 3,200k { copy.2 200k | kv_cache 1,000k |
      attention 1,200k | ffn 600k | 200k of its own } | head 300k | idle
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
import pb_spans  # noqa: E402
import pb_trace  # noqa: E402
from pipe_tpu.obs import events as ev  # noqa: E402
from pipe_tpu.obs.xplane import (TraceEvent, TraceLine, TracePlane,  # noqa: E402
                                 encode_xspace)

FIXTURE = os.path.join(BENCH, "testdata", "program_spans.xplane.pb")
K = 1000                       # the fixture's times are in thousands of ns
TRAIN_OPS = (                  # (instruction, length, op_name)
    ("fusion.1", 100, "jit(_train_step)/jvp(embed)/gather:"),
    ("fusion.2", 500, "jit(_train_step)/jvp(attention)/dot_general:"),
    ("fusion.3", 300, "jit(_train_step)/jvp(ffn)/dot_general:"),
    ("fusion.4", 200, "jit(_train_step)/rematted_computation/"
                      "jvp(attention)/dot_general:"),
    ("fusion.5", 200, "jit(_train_step)/transpose(jvp(head))/dot_general:"),
    ("fusion.6", 100, "jit(_train_step)/jvp(loss)/reduce_sum:"),
    ("fusion.7", 400, "jit(_train_step)/optimizer/mul:"),
    ("copy.9", 100, None),
)
RESIDENT = "jit(_resident_fn)/while/body/while/body/"


def _op(name, start, length, op_name, program, opcode="fusion"):
    meta = {"program_id": program}
    if op_name is not None:
        meta["tf_op"] = op_name
    hlo = f"%{name} = bf16[8,128]{{1,0}} {opcode}(bf16[8,128]{{1,0}} %p.{program})"
    return TraceEvent(hlo, start * K, length * K, meta=meta)


def build_planes():
    ops, t = [], 1100
    for run in range(2):
        t = 1100 + 2100 * run
        for name, length, op_name in TRAIN_OPS:
            ops.append(_op(name, t, length, op_name, 11))
            t += length
    ops += [
        _op("fusion.20", 5400, 200,
            "jit(_prefill_fn)/kv_cache/dynamic_update_slice:", 33),
        _op("fusion.21", 5600, 300,
            "jit(_prefill_fn)/while/body/attention/dot_general:", 33),
        _op("copy.1", 6000, 400, None, 22, "copy"),
        _op("while.1", 6400, 3200, None, 22, "while"),
        _op("copy.2", 6400, 200, None, 22, "copy"),
        _op("fusion.30", 6600, 1000,
            RESIDENT + "vmap(attention)/kv_cache/dynamic_update_slice:", 22),
        _op("fusion.31", 7600, 1200,
            RESIDENT + "vmap(attention)/dot_general:", 22),
        _op("fusion.32", 8800, 600, RESIDENT + "vmap(ffn)/dot_general:", 22),
        _op("fusion.33", 9600, 300, "jit(_resident_fn)/head/dot_general:",
            22),
    ]
    modules = [
        TraceEvent("jit__train_step(11)", 1100 * K, 2000 * K),
        TraceEvent("jit__train_step(11)", 3200 * K, 2000 * K),
        TraceEvent("jit__prefill_fn(33)", 5400 * K, 500 * K),
        TraceEvent("jit__resident_fn(22)", 6000 * K, 4000 * K),
    ]

    def span(name, start, end, **stats):
        return TraceEvent(name, start * K, (end - start) * K, stats=stats)

    host = [
        span(pb_trace.WINDOW_SPAN, 1000, 11000),
        span(ev.STEP, 1005, 1052, step=0, epoch=0),
        span(ev.TRAIN_BATCH, 1006, 1009, step=0),
        span(ev.TRAIN_DISPATCH, 1010, 1050, step=0),
        span(ev.STEP, 1055, 1092, step=1, epoch=0),
        span(ev.TRAIN_BATCH, 1056, 1059, step=1),
        span(ev.TRAIN_DISPATCH, 1060, 1090, step=1),
        span(ev.TRAIN_SYNC, 1095, 5200, step=1),
        span(ev.SERVE_TICK, 5300, 10100, tick=0, live=5, queued=1),
        span(ev.SERVE_REAP, 5300, 5310),
        span(ev.SERVE_ADMIT, 5320, 5920, request=7, trace="ab", slot=1,
             prompt_len=100, queued_ms=0.5),
        span(ev.SERVE_PREFILL, 5330, 5910, slot=1, prompt_len=100,
             bucket=128),
        span(ev.SERVE_PREFILL_SYNC, 5500, 5905, slot=1),
        span(ev.SERVE_DECODE, 5925, 10020, live=6),
        span(ev.SERVE_DECODE_LAUNCH, 5930, 5990, chunks=8),
        span(ev.SERVE_DECODE_SYNC, 5995, 10010),
        span(ev.SERVE_RETIRE, 10030, 10060, finished=1),
        span(ev.SERVE_DECODE_DONE, 10070, 10071, steps=12, chunks=3,
             live=6, rows=900, emitted=70, early_exit=1),
        span(ev.SERVE_TICK, 10200, 10900, tick=1, live=7, queued=1),
        span(ev.SERVE_REAP, 10200, 10210),
        span(ev.SERVE_ADMIT, 10220, 10390, request=8, trace="cd", slot=0,
             prompt_len=20, queued_ms=0.25),
        span(ev.SERVE_PREFILL, 10230, 10380, slot=0, prompt_len=20,
             bucket=32),
        span(ev.SERVE_DECODE, 10400, 10800, live=8),
        span(ev.SERVE_DECODE_LAUNCH, 10405, 10415, chunks=8),
        span(ev.SERVE_DECODE_SYNC, 10420, 10790),
        span(ev.SERVE_RETIRE, 10810, 10820, finished=0),
        span(ev.SERVE_DECODE_DONE, 10830, 10831, steps=4, chunks=1,
             live=8, rows=1000, emitted=32, early_exit=1),
        # not the program's: the reader passes it over
        span("PjitFunction(_train_step)", 1011, 1049),
    ]
    return [
        TracePlane("/device:TPU:0", [TraceLine(pb_trace.OPS_LINE, 0, ops),
                                     TraceLine(pb_trace.MODULES_LINE, 0,
                                               modules)]),
        TracePlane("/host:CPU", [TraceLine("main", 0, host)]),
    ]


@pytest.fixture
def facts(tmp_path):
    """Facts that point at the committed fixture, laid out as a capture."""
    where = tmp_path / "trace" / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    with open(FIXTURE, "rb") as f:
        (where / "host.xplane.pb").write_bytes(f.read())
    cell = pb_core.Cell("gpt2xl-serve-closed8")
    return {"trace_dir": str(tmp_path / "trace"), "traffic": cell.traffic}


def _read(metric, facts):
    return pb_core.load_by_path(f"layers/{metric}.py").read(facts)


def test_the_committed_fixture_is_what_this_file_builds():
    with open(FIXTURE, "rb") as f:
        assert f.read() == encode_xspace(build_planes())


def test_the_readers_vocabulary_is_the_programs():
    assert pb_spans.DEVICE_SCOPES == ev.DEVICE_SCOPES
    assert pb_spans.REMAT_MARKER == ev.REMAT_SCOPE
    for kind in ev.SPAN_KINDS:
        if kind.startswith(("serve.", "train.")) or kind == ev.STEP:
            assert pb_spans.PROGRAM_SPAN.match(kind), kind


@pytest.mark.parametrize("op_name, scope, remat", [
    ("jit(s)/jvp(attention)/dot_general:", "attention", False),
    ("jit(s)/transpose(jvp(head))/dot_general:", "head", False),
    ("jit(r)/while/body/vmap(attention)/kv_cache/add:", "kv_cache", False),
    ("jit(s)/rematted_computation/jvp(ffn)/add:", "ffn", True),
    ("jit(s)/transpose(rematted_computation)/jvp(ffn)/select_n:", "ffn",
     False),
    ("jit(s)/transpose(jvp())/while/body/checkpoint/rematted_computation/"
     "attention/sub:", "attention", True),
    ("jit(loss)/jit(attention_mask)/add:", None, False),
    ("", None, False),
])
def test_scope_and_remat_from_an_op_name(op_name, scope, remat):
    assert pb_spans.scope_of(op_name) == scope
    assert pb_spans.is_remat(op_name) is remat


def test_capture_spans_ops_and_modules(facts):
    cap = pb_spans.read(facts)
    assert cap is pb_spans.read(facts)            # parsed once
    assert cap.window == (1000 * K, 11000 * K) and cap.chips == 1
    assert "PjitFunction(_train_step)" not in cap.spans
    assert [sp.stats["step"] for sp in cap.spans[ev.TRAIN_DISPATCH]] == [0, 1]
    admit = cap.spans[ev.SERVE_ADMIT][0]
    assert admit.stats == {"request": 7, "trace": "ab", "slot": 1,
                           "prompt_len": 100, "queued_ms": 0.5}
    assert [(name, pid) for name, pid, _, _ in cap.modules] == [
        ("jit__train_step(11)", 11), ("jit__train_step(11)", 11),
        ("jit__prefill_fn(33)", 33), ("jit__resident_fn(22)", 22)]
    ops = {op.name: op for op in cap.ops}
    assert ops["while.1"].is_while and not ops["while.1"].in_while
    assert ops["while.1"].self_ns == 200 * K      # 3,200k less its body
    assert ops["copy.2"].in_while and not ops["copy.1"].in_while
    assert ops["fusion.4"].remat and ops["fusion.4"].scope == "attention"
    assert not ops["fusion.2"].remat
    assert ops["fusion.30"].scope == "kv_cache"
    assert ops["fusion.30"].program == 22
    # every operation's own time adds up to the device's busy time
    busy = sum(e - s for s, e in cap.busy)
    assert sum(op.self_ns for op in cap.ops) == busy == 8200 * K


# two step programs ran; per step, in ms: the train runs' own plus what the
# serve programs put under the same scope
TRAIN_MS = {
    "blocks.attention_ms_per_step": (2 * (500 + 200) + 300 + 1200) / 2e3,
    "blocks.ffn_ms_per_step": (2 * 300 + 600) / 2e3,
    "blocks.head_loss_ms_per_step": (2 * (200 + 100) + 300) / 2e3,
    "trainer.optimizer_ms_per_step": 2 * 400 / 2e3,
    # copy.9 twice, copy.1, copy.2, and the while's own 200k
    "blocks.unscoped_ms_per_step": (2 * 100 + 400 + 200 + 200) / 2e3,
    "blocks.remat_ms_per_step": 2 * 200 / 2e3,
}


@pytest.mark.parametrize("metric", sorted(TRAIN_MS))
def test_train_scope_readers(metric, facts):
    assert _read(metric, facts) == pytest.approx(TRAIN_MS[metric])


def test_the_scopes_add_up_to_the_busy_time_per_step(facts):
    cap = pb_spans.read(facts)
    summed = sum(TRAIN_MS[m] for m in TRAIN_MS
                 if m != "blocks.remat_ms_per_step")       # it cuts across
    summed += cap.scope_ns("embed", "kv_cache") / 1e6 / 2  # no metric each
    assert summed == pytest.approx(8200 * K / 1e6 / 2)


def test_steps_in_flight(facts):
    # at run 1's start both dispatches have ended and no run has: 2; at
    # run 2's start one run has ended: 1
    assert _read("trainer.steps_in_flight", facts) == pytest.approx(1.5)


def test_step_traces_reads_the_registry(facts):
    from pipe_tpu.obs.telemetry import MetricsRegistry, set_registry
    old = set_registry(MetricsRegistry())
    try:
        assert _read("entry.step_traces", facts) is None   # never traced
        from pipe_tpu.obs.telemetry import get_registry
        get_registry().counter("train.step_traces").inc(2)
        assert _read("entry.step_traces", facts) == 2
    finally:
        set_registry(old)


SERVE = {
    "decode.steps_per_launch": (12 + 4) / 2,
    "engine.batch_occupancy": 100 * (6 * 12 + 8 * 4) / (8 * 16),
    "prefill.padding_share": 100 * (1 - (100 + 20) / (128 + 32)),
    # of the resident program's 4,000k: kv_cache 1,000k; copy.1 + copy.2
    "decode.cache_share": 25.0,
    "decode.copy_share": 100 * (400 + 200) / 4000,
    # idle inside a tick: 5,900k-6,000k (under serve.decode.launch) and
    # 9,900k to the window's end (its middle under tick 1's
    # serve.decode.sync); two launches
    "engine.relaunch_idle_ms": (100 + 1100) / 1e3 / 2,
}


@pytest.mark.parametrize("metric", sorted(SERVE))
def test_serve_readers(metric, facts):
    assert _read(metric, facts) == pytest.approx(SERVE[metric])


def test_idle_gaps_by_the_innermost_program_span(facts):
    gaps = pb_spans.read(facts).gaps_by_span()
    assert gaps == pytest.approx({
        ev.STEP: 100 * K,          # 1,000k-1,100k: at 1,050k step 0 is
        #                            open and its dispatch has just ended
        ev.TRAIN_SYNC: 200 * K,    # between the two runs
        None: 300 * K,             # 5,100k-5,400k: no program span open
        ev.SERVE_DECODE_LAUNCH: 100 * K,
        ev.SERVE_DECODE_SYNC: 1100 * K})


NEW = sorted(TRAIN_MS) + sorted(SERVE) + ["trainer.steps_in_flight",
                                          "entry.step_traces"]


def test_the_proposed_entries_are_ready_for_benchmark_json():
    """``BENCHMARK.json`` does not list the new metrics (the accepted
    ``test_layer_readers_read_hand_made_facts`` compares the dict of all of
    a cell's readers with a closed one, so an entry more fails it, and that
    file is a ``benchmark`` PR's to edit). ``layers/proposed_per_layer.json``
    holds the entries as they are to be appended: each has a reader, a
    layer and an end-to-end metric the benchmark knows, one accepted cell,
    and no name the benchmark has."""
    spec = pb_core.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    proposed = pb_core.read_json(
        os.path.join(BENCH, "layers", "proposed_per_layer.json"))
    assert sorted(m["name"] for m in proposed) == sorted(NEW)
    assert len(NEW) == 14
    have = {m["name"] for m in spec["per_layer"]}
    layers = {m["layer"] for m in spec["per_layer"]}
    cells = {w["name"] for w in spec["workloads"]}
    keys = set(spec["per_layer"][-1])              # one that lists its cells
    for m in proposed:
        assert set(m) == keys and m["name"] not in have, m["name"]
        assert m["layer"] in layers and len(m["workloads"]) == 1
        assert m["workloads"][0] in cells
        reports = {e["name"] for e in pb_core.Cell(
            m["workloads"][0]).metrics("end_to_end")}
        assert m["moves"] in reports, m["name"]
        assert os.path.exists(
            os.path.join(BENCH, "layers", m["name"] + ".py"))


@pytest.mark.parametrize("cell, want", [
    ("lm520m-train-1chip", {**TRAIN_MS, "trainer.steps_in_flight": 1.5}),
    ("gpt2xl-serve-closed8", SERVE)])
def test_span_report_prints_a_cells_proposed_metrics(cell, want, facts):
    """Until the entries are in ``BENCHMARK.json`` the readings come from
    ``tools/span_report.py --workload`` on a traced run's capture
    (``entry.step_traces`` reads the run's own registry: left out)."""
    report = pb_core.load_by_path("tools/span_report.py").report(
        facts["trace_dir"], top=3, workload=cell)
    assert report["metrics"] == pytest.approx(want)
    assert report["by_scope"]["attention"]["ms"] == pytest.approx(2.9)


@pytest.mark.parametrize("metric", [m for m in NEW
                                    if m != "entry.step_traces"])
def test_no_capture_reads_none(metric, tmp_path):
    cell = pb_core.Cell("gpt2xl-serve-closed8")
    facts = {"trace_dir": str(tmp_path / "nothing"),
             "traffic": cell.traffic}
    assert _read(metric, facts) is None


def test_a_capture_without_scopes_or_spans_reads_none(tmp_path):
    """A parent commit's program: operations with no scope, no program
    span. Nothing is called unscoped; nothing raises."""
    planes = build_planes()
    for line in planes[0].lines:
        for e in line.events:
            e.meta.pop("tf_op", None)
    planes[1].lines[0].events = planes[1].lines[0].events[:1]
    where = tmp_path / "trace" / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(encode_xspace(planes))
    cell = pb_core.Cell("gpt2xl-serve-closed8")
    facts = {"trace_dir": str(tmp_path / "trace"), "traffic": cell.traffic}
    for metric in NEW:
        if metric != "entry.step_traces":
            assert _read(metric, facts) is None, metric


def test_the_fixture_also_reads_as_a_capture_of_the_harness():
    """``pb_trace`` (the harness's reduction) and this one see the same
    busy time."""
    from jax.profiler import ProfileData
    summary = pb_trace.TraceSummary(
        list(ProfileData.from_file(FIXTURE).planes), set())
    assert summary.busy_s == pytest.approx(8200 * K / 1e9)
    assert summary.window_s == pytest.approx(0.010)
