"""The cell ``laguna-serve-closed16`` (CPU; a tiny size; no TPU is described
or touched at import): it runs end to end through the test-only entry
``run.run_cell`` and reads correct; the float8 control and each fault planted
in the reference read not correct under the same limits
(``tools/fault_readings.py``'s path); the family's arithmetic and the four
readers this cell brings give hand-counted numbers on hand-made facts."""

import copy
import json
import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import pb_core  # noqa: E402
import pb_trace  # noqa: E402
import run as bench_run  # noqa: E402
from pipe_tpu.obs import events as ev  # noqa: E402
from pipe_tpu.obs.xplane import (TraceEvent, TraceLine, TracePlane,  # noqa: E402
                                 encode_xspace)

CELL = "laguna-serve-closed16"
SPEC = pb_core.read_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW = ("moe.decode_share", "moe.experts_roofline",
       "moe.rows_per_expert_read", "decode.routed_step_roofline")


def tiny_cell():
    cell = pb_core.Cell(CELL)
    cfg, tr = copy.deepcopy(cell.cfg), copy.deepcopy(cell.traffic)
    cfg.update(vocab=96, hidden_size=64, head_dim=16, num_key_value_heads=2,
               sliding_window=8, intermediate_size=128,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               num_experts=4, num_experts_per_tok=3, experts_held=[0, 4],
               compute_dtype="float32")
    cfg["published"] = dict(cfg["published"], num_experts=8)
    cfg["num_attention_heads_per_layer"] = [
        12 if t == "full_attention" else 18 for t in cfg["layer_types"]]
    tr["prompt"].update(median=10, min=4, max=32)
    tr["output"].update(median=8, min=3, max=16)
    tr["engine"].update(slots=4, bucket_min=8, bucket_max=32,
                        max_new_tokens=16)
    tr.update(clients=4, lead_in_s=0.3, check_requests=6, check_rows=2)
    cell.limits = {"ok_requests_of_wrong_length": 0,
                   "served_logit_gap": 1e-4}
    cell.cfg, cell.traffic = cfg, tr
    return cell


# ---------------------------------------------------------------------------
# the entries and the configuration file


def test_the_cell_and_its_configuration_are_entered_as_issue_32_names_them():
    cell = pb_core.Cell(CELL)
    assert cell.chips == 1 and cell.entry["config"] == "laguna-s-2.1"
    assert cell.entry["traffic"] == "closed16-p64-2048-o32-384"
    entry = cell.config_entry
    assert entry["reduced"] == ["n_layers", "num_experts", "vocab"]
    assert entry["source"].startswith("https://huggingface.co/poolside/")
    got = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW) <= got and "decode.step_roofline" not in got
    assert {"engine.mfu", "engine.prefill_mfu", "engine.ttft_p95_ms",
            "device.peak_hbm_gib.serve"} <= got
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "serve_tokens_per_s", "setup_s"}
    for name in NEW:
        m = next(m for m in SPEC["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "serve_tokens_per_s"
    e = cell.traffic["engine"]
    assert (e["slots"], e["bucket_min"], e["bucket_max"],
            e["max_new_tokens"]) == (16, 64, 2048, 384)


def test_the_configuration_file_holds_the_published_widths_and_the_cut():
    cfg = pb_core.Cell(CELL).cfg
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Laguna-S-2.1")
        differs = [k for k, v in row["config"].items() if cfg.get(k) != v]
        assert differs == ["num_experts"]        # listed in `reduced`
    assert (cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["sliding_window"]) == (
                3072, 128, 8, 12288, 1024, 10, 512)
    assert (cfg["n_layers"], cfg["num_experts"], cfg["vocab"]) == (
        5, 128, 50176)
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 256,
                                "vocab_size": 100352}
    assert cfg["experts_held"] == [0, 128]
    assert cfg["deployment"]["chips_per_layer"] == 2
    assert cfg["deployment"]["pipeline_stages"] == 12
    assert len(cfg["assumed"]) >= 6 and all(
        isinstance(v, str) and len(v) > 15 for v in cfg["assumed"].values())
    assert cfg["held_params"] == pb_core.Cell(CELL).family.num_params(cfg)


def test_flops_and_bytes_arithmetic_against_hand_counts():
    cell = pb_core.Cell(CELL)
    fam, cfg = cell.family, cell.cfg
    d, hd = 3072, 128
    full = 2 * d * 48 * hd + 2 * d * 8 * hd + d * 48          # 44.2 M
    sliding = 2 * d * 72 * hd + 2 * d * 8 * hd + d * 72       # 63.1 M
    expert = 3 * d * 1024                                     # 9.44 M
    moe_rest = d * 256 + 3 * d * 1024                 # router, shared expert
    non_expert = (2 * full + 3 * sliding + 3 * d * 12288 + 4 * moe_rest)
    assert fam.expert_params(cfg) == expert == 9437184
    assert fam.non_expert_params(cfg) == non_expert
    assert fam.num_params(cfg) == (
        non_expert + 4 * 128 * expert + 2 * 50176 * d + 5 * 2 * d + d)
    assert fam.num_params(cfg) == 5572076544
    assert fam.expected_held_picks(cfg) == 5.0
    assert fam.forward_flops_per_token(cfg) == 2.0 * (
        non_expert + 4 * 5 * expert)
    assert fam.decode_weight_bytes(cfg) == 2.0 * (non_expert + d * 50176)
    assert fam.kv_row_bytes(cfg) == 2 * 4096      # the two full layers
    assert fam.cache_row_bytes(cfg) == 4096
    assert fam.expert_bytes(cfg) == 2 * expert
    assert fam.expert_flops_per_row(cfg) == 2 * expert
    # what the issue's sizing argument rests on: the share of the chip
    assert 0.65 < 2 * fam.num_params(cfg) / (15.75 * 2 ** 30) < 0.67


# ---------------------------------------------------------------------------
# end to end at a tiny size


def test_the_cell_runs_end_to_end_tiny_and_reads_correct(tmp_path):
    import jax
    cell = tiny_cell()
    res = bench_run.run_cell(cell, seed=3_000_000_019, seconds=1.0, trace=0,
                             devices=jax.devices(), out_dir=str(tmp_path))
    line = json.loads(json.dumps(res))
    assert list(line)[-1] == "checks" and line["correct"] is True, line
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert line["checks"]["served_logit_gap"]["value"] <= 1e-4
    assert [f for f in os.listdir(tmp_path) if f.endswith(".json")]


@pytest.fixture(scope="module")
def fault_rows():
    tool = pb_core.load_by_path("tools/fault_readings.py")
    return tool.readings(tiny_cell(), [11], seconds=0.6,
                         log=lambda line: None)


@pytest.mark.parametrize("side", [
    "control_fp8", "fault_no_window", "fault_renorm_held", "fault_no_gate"])
def test_the_control_and_each_planted_fault_read_not_correct(fault_rows,
                                                             side):
    row = fault_rows[0]
    assert row["program_correct"] is True and row["requests"] == 6
    assert row[side + "_correct"] is False
    gap = (row["control_logit_gap"] if side == "control_fp8"
           else row[side + "_gap"])
    assert gap > 100 * row["served_logit_gap"] and gap > 0.01


def test_fault_readings_verdicts_are_readings_own(fault_rows, capsys):
    verdicts = pb_core.load_by_path("tools/readings.py").verdicts
    assert verdicts(fault_rows) is True
    assert "fault_no_window: correct [False]" in capsys.readouterr().out
    passed = [dict(fault_rows[0], fault_no_gate_correct=True)]
    assert verdicts(passed) is False


# ---------------------------------------------------------------------------
# the four readers on a hand-made capture
#
# window 1 ms .. 11 ms. One prefill program (33) at 2,000k: ragged-dot 300k,
# an op under moe_experts 100k, attention 200k. One resident launch (22) at
# 4,000k, 5,000k long: router 200k | ragged-dot 1,500k | its metadata 100k |
# combine under moe_experts 400k | shared expert 300k | attention 1,500k |
# head 500k | 500k idle inside. Two `serve.decode.done` spans.

K = 1000
RES = "jit(_resident_fn)/while/body/closed_call/"


def _op(name, start, length, op_name, program, opcode="fusion"):
    meta = {"program_id": program}
    if op_name is not None:
        meta["tf_op"] = op_name
    hlo = (f"%{name} = bf16[8,128]{{1,0}} {opcode}(bf16[8,128]{{1,0}} "
           f"%p.{program})")
    return TraceEvent(hlo, start * K, length * K, meta=meta)


def build_planes(with_counts=True):
    ops = [
        _op("ragged-dot-none.9", 2000, 300, "ragged-dot-none", 33,
            "custom-call"),
        _op("fusion.1", 2300, 100,
            "jit(_prefill_fn)/while/body/ffn/moe_experts/take:", 33),
        _op("fusion.2", 2400, 200,
            "jit(_prefill_fn)/while/body/attention/attn_full/dot_general:",
            33),
        _op("fusion.10", 4000, 200, RES + "ffn/moe_router/top_k:", 22),
        _op("ragged-dot-none.1", 4200, 1500, "ragged-dot-none", 22,
            "custom-call"),
        _op("ragged-dot-metadata", 5700, 100, "ragged-dot-metadata", 22,
            "custom-call"),
        _op("fusion.11", 5800, 400, RES + "ffn/moe_experts/reduce_sum:", 22),
        _op("fusion.12", 6200, 300,
            RES + "ffn/moe_shared/dot_general:", 22),
        _op("fusion.13", 6500, 1500,
            RES + "attention/attn_window/kv_cache/dot_general:", 22),
        _op("fusion.14", 8000, 500, "jit(_resident_fn)/head/dot_general:",
            22),
    ]
    modules = [TraceEvent("jit__prefill_fn(33)", 2000 * K, 600 * K),
               TraceEvent("jit__resident_fn(22)", 4000 * K, 5000 * K)]

    def span(name, start, end, **stats):
        return TraceEvent(name, start * K, (end - start) * K, stats=stats)

    counts = [dict(expert_rows=600, absent_rows=680, experts_touched=400,
                   layer_steps=32, full_rows_read=20000,
                   window_rows_read=12000, prefill_expert_rows=5000,
                   prefill_absent_rows=5240, prefill_experts_touched=512,
                   prefill_layer_steps=4),
              dict(expert_rows=200, absent_rows=120, experts_touched=100,
                   layer_steps=8, full_rows_read=5000, window_rows_read=3000,
                   prefill_expert_rows=0, prefill_absent_rows=0,
                   prefill_experts_touched=0, prefill_layer_steps=0)]
    host = [
        span(pb_trace.WINDOW_SPAN, 1000, 11000),
        span(ev.SERVE_DECODE_DONE, 9100, 9101, steps=8, chunks=2, live=16,
             rows=900, emitted=128, early_exit=1,
             **(counts[0] if with_counts else {})),
        span(ev.SERVE_DECODE_DONE, 10100, 10101, steps=2, chunks=1, live=16,
             rows=1000, emitted=32, early_exit=1,
             **(counts[1] if with_counts else {})),
    ]
    return [TracePlane("/device:TPU:0", [
                TraceLine(pb_trace.OPS_LINE, 0, ops),
                TraceLine(pb_trace.MODULES_LINE, 0, modules)]),
            TracePlane("/host:CPU", [TraceLine("main", 0, host)])]


def make_facts(tmp_path, **kw):
    where = tmp_path / "trace" / "plugins" / "profile" / "run"
    where.mkdir(parents=True)
    (where / "host.xplane.pb").write_bytes(encode_xspace(build_planes(**kw)))
    cell = pb_core.Cell(CELL)
    return {
        "trace_dir": str(tmp_path / "trace"), "cell": cell, "cfg": cell.cfg,
        "traffic": cell.traffic,
        "peaks": {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12},
        # the resident program's device time, as pb_trace sums it
        "trace": types.SimpleNamespace(
            module_time=lambda pattern: (1.0, 5e-3))}


def _read(metric, facts):
    return pb_core.load_by_path(f"layers/{metric}.py").read(facts)


def test_the_four_readers_against_hand_counts(tmp_path):
    facts = make_facts(tmp_path)
    fam, cfg = facts["cell"].family, facts["cfg"]
    # router 200 + kernel 1500 + metadata 100 + combine 400 + shared 300
    # of the launch's 5,000k
    assert _read("moe.decode_share", facts) == pytest.approx(
        100 * 2500 / 5000)
    assert _read("moe.rows_per_expert_read", facts) == pytest.approx(
        800 / 500)
    # decode: 500 experts' bytes bound it (18.9 ms against 0.15 ms of
    # FLOPs); prefill: 512 experts' bytes (19.3 ms against 0.94 ms); over
    # the 2,400k under moe_experts (kernel, metadata and combine, both
    # programs)
    expert = 2 * 9437184
    least = (500 * expert / 1e12) + (512 * expert / 1e12)
    assert max(800, 5000) * expert / 1e14 < 512 * expert / 1e12
    assert _read("moe.experts_roofline", facts) == pytest.approx(
        100 * least / 2.4e-3)
    must = (10 * fam.decode_weight_bytes(cfg) + 500 * expert
            + (25000 + 15000) * 4096)
    assert _read("decode.routed_step_roofline", facts) == pytest.approx(
        100 * must / 1e12 / 5e-3)


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_scopes_or_counts_reads_none(metric, tmp_path):
    """A parent commit: no capture at all; and a capture whose decode spans
    carry no expert counts."""
    cell = pb_core.Cell(CELL)
    nothing = {"trace_dir": str(tmp_path / "none"), "cell": cell,
               "cfg": cell.cfg, "peaks": {}, "trace": None}
    assert _read(metric, nothing) is None
    facts = make_facts(tmp_path, with_counts=False)
    if metric != "moe.decode_share":      # the scopes are there, no counts
        assert _read(metric, facts) is None
