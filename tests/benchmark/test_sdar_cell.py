"""The cell ``sdar-serve-closed32`` (CPU; a tiny size; no TPU is described or
touched at import): its entries and configuration file as ISSUE 34 names
them, the family's arithmetic against hand counts, the traffic pool, the
cell end to end through the test-only entry ``run.run_cell`` (it reads
correct; the float8 control and each fault planted in the reference read not
correct under the same limits, by the driver's ``readings``), and the readers
this cell brings on hand-made facts."""

import copy
import json
import os
import sys
import types

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import pb_core  # noqa: E402
import pb_trace  # noqa: E402
import pb_traffic  # noqa: E402
import run as bench_run  # noqa: E402
from pipe_tpu.obs import events as ev  # noqa: E402
from pipe_tpu.obs.xplane import (TraceEvent, TraceLine, TracePlane,  # noqa: E402
                                 encode_xspace)

CELL = "sdar-serve-closed32"
SPEC = pb_core.read_json(os.path.join(ROOT, "BENCHMARK.json"))
NEW = ("diffusion.passes_per_token", "diffusion.pass_roofline",
       "diffusion.select_share")
JOINED = ("engine.mfu", "engine.host_us_per_token",
          "engine.client_tokens_per_s", "engine.ttft_p95_ms",
          "engine.prefill_mfu", "device.peak_hbm_gib.serve")
# the expert layer's three accepted readers under names of this cell's own:
# tests/benchmark/test_laguna_cell.py holds their lists to [its cell]
# (PERF.md, Open questions)
EXPERTS = ("moe.decode_share.block_round", "moe.experts_roofline.block_round",
           "moe.rows_per_expert_read.block_round")
ASSUMED = ("qk_norm", "rotary_pairing", "block_length", "denoise_steps",
           "remasking", "shift", "mask_token_id", "weights")


def tiny_cell():
    cell = pb_core.Cell(CELL)
    cfg, tr = copy.deepcopy(cell.cfg), copy.deepcopy(cell.traffic)
    cfg.update(vocab=96, vocab_size=96, hidden_size=64, head_dim=16,
               num_attention_heads=4, num_key_value_heads=2,
               moe_intermediate_size=32, num_experts=8,
               num_experts_per_tok=2, n_layers=2, compute_dtype="float32")
    cfg["published"] = dict(cfg["published"], num_experts=8)
    cfg["generation"] = dict(cfg["generation"], mask_token_id=95)
    tr["prompt"].update(median=10, min=3, max=32)
    tr["output"].update(median=9, min=3, max=16)
    tr["engine"].update(slots=4, bucket_min=8, bucket_max=32,
                        max_new_tokens=16)
    tr.update(clients=4, lead_in_s=0.3, check_requests=6, check_rows=16,
              check_pad_to=32, pool_size=12)
    cell.limits = {"ok_requests_of_wrong_length": 0,
                   "served_logit_gap": 1e-4, "reveal_confidence_gap": 1e-4,
                   "served_logit_gap_mean": 1e-5,
                   "reveal_confidence_gap_mean": 1e-5}
    cell.cfg, cell.traffic = cfg, tr
    return cell


# ---------------------------------------------------------------------------
# the entries, the configuration file, the arithmetic, the pool


def test_the_cell_and_its_configuration_are_entered_as_issue_34_names_them():
    cell = pb_core.Cell(CELL)
    assert cell.chips == 1 and cell.entry["config"] == "sdar-30b-a3b-chat"
    assert cell.entry["traffic"] == "closed32-p32-1024-o64-1024"
    entry = cell.config_entry
    assert entry["reduced"] == ["n_layers"]
    assert entry["source"] == ("https://huggingface.co/JetLM/"
                               "SDAR-30B-A3B-Chat/blob/main/config.json")
    got = {m["name"] for m in cell.metrics("per_layer")}
    assert set(NEW) | set(JOINED) | set(EXPERTS) <= got
    assert not {"decode.step_roofline", "decode.routed_step_roofline"} & got
    assert {m["name"] for m in cell.metrics("end_to_end")} == {
        "serve_tokens_per_s", "setup_s"}
    for name in NEW + EXPERTS:
        m = next(m for m in SPEC["per_layer"] if m["name"] == name)
        assert CELL in m["workloads"] and m["moves"] == "serve_tokens_per_s"
        assert m["layer"] == ("decode program" if name in NEW
                              else "expert layer")
    tr = cell.traffic
    e = tr["engine"]
    assert tr["kind"] == "serve_closed_blocks" and tr["clients"] == 32
    assert (e["slots"], e["bucket_min"], e["bucket_max"], e["max_new_tokens"],
            e["decode_chunk"], e["resident_chunks"], e["temperature"]) == (
                32, 32, 1024, 1024, 1, 8, 0.0)
    assert (tr["pool_seed"], tr["pool_size"], tr["lead_in_s"],
            tr["trace_seconds"], tr["check_requests"]) == (34, 48, 4, 4, 12)
    # ISSUE 34's first form: outputs clipped to 1,024, a pool of 48
    assert (tr["output"]["min"], tr["output"]["max"]) == (64, 1024)


def test_the_configuration_file_holds_the_published_widths_and_the_cut():
    cell = pb_core.Cell(CELL)
    cfg = cell.cfg
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.isfile(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "SDAR-30B-A3B-Chat")
        assert [k for k, v in row["config"].items() if cfg.get(k) != v] == []
        assert cfg["source"] == row["source_url"]
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"], cfg["num_experts"],
            cfg["moe_intermediate_size"], cfg["num_experts_per_tok"],
            cfg["vocab_size"], cfg["vocab"]) == (
                2048, 32, 4, 128, 128, 768, 8, 151936, 151936)
    assert cfg["n_layers"] == 6 and cfg["reduced"] == ["n_layers"]
    assert cfg["published"]["num_hidden_layers"] == 48
    dep = cfg["deployment"]
    assert (dep["pipeline_stages"], dep["layers_per_stage"],
            dep["chips_per_layer"]) == (8, 6, 1)
    g = cfg["generation"]
    assert (g["kind"], g["block_length"], g["denoise_steps"], g["remasking"],
            g["shift"], g["mask_token_id"]) == (
                "block_diffusion", 4, 4, "low_confidence_static", False,
                151669)
    assert set(ASSUMED) <= set(cfg["assumed"]) and all(
        isinstance(v, str) and len(v) > 15 for v in cfg["assumed"].values())
    assert cfg["router_init_logit_std"] == 4.0
    assert cfg["held_params"] == cell.family.num_params(cfg)
    mc = cell.family.model_config(cfg)
    assert mc.generation == ("block_diffusion", 4, 4, 151669)
    assert mc.experts_held == (0, 128)           # the whole range


def test_flops_and_bytes_arithmetic_against_hand_counts():
    cell = pb_core.Cell(CELL)
    fam, cfg = cell.family, cell.cfg
    d = 2048
    outside = (2 * d * 4096 + 2 * d * 512 + d * 128 + 2 * d + 2 * 128)
    expert = 3 * d * 768
    assert fam.layer_non_expert_params(cfg) == outside == 19_140_864
    assert fam.expert_params(cfg) == expert == 4_718_592
    assert fam.non_expert_params(cfg) == 6 * outside
    assert fam.num_params(cfg) == (
        6 * (outside + 128 * expert) + 2 * 151936 * d + d) == 4_361_055_744
    assert fam.forward_flops_per_token(cfg) == 2.0 * 6 * (
        outside + 8 * expert)
    assert fam.decode_weight_bytes(cfg) == 2.0 * 6 * outside
    assert fam.head_bytes(cfg) == 2.0 * d * 151936
    assert fam.cache_row_bytes(cfg) == 2048 and fam.kv_row_bytes(cfg) == 12288
    assert fam.expert_bytes(cfg) == 2 * expert == 9_437_184
    assert fam.expert_flops_per_row(cfg) == 2 * expert
    # what the issue's sizing argument rests on: the share of the chip
    assert 0.51 < 2 * fam.num_params(cfg) / (15.75 * 2 ** 30) < 0.52


def test_the_traffic_pool_is_the_issues():
    tr = pb_core.Cell(CELL).traffic
    prompts, outputs = pb_traffic.request_pool(tr)
    assert len(prompts) == len(outputs) == 48
    assert prompts.min() >= 32 and prompts.max() == 1024
    assert outputs.min() == 64 and outputs.max() == 1024
    assert 240 <= np.median(prompts) <= 270 and 240 <= np.median(
        outputs) <= 270
    # not rounded to the block: about three quarters end inside one
    assert 0.6 < np.mean((prompts + outputs) % 4 != 0) < 0.9
    # a slot's slab holds the longest prompt with the longest answer
    e = tr["engine"]
    assert (prompts + outputs).max() <= e["bucket_max"] + e[
        "max_new_tokens"] == 2048


# ---------------------------------------------------------------------------
# end to end at a tiny size


def test_the_cell_runs_end_to_end_tiny_and_reads_correct(tmp_path):
    import jax
    cell = tiny_cell()
    res = bench_run.run_cell(cell, seed=3_000_000_034, seconds=1.0, trace=0,
                             devices=jax.devices(), out_dir=str(tmp_path))
    line = json.loads(json.dumps(res))
    assert list(line)[-1] == "checks" and line["correct"] is True, line
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["checks"]) == {
        "ok_requests_of_wrong_length", "served_logit_gap",
        "reveal_confidence_gap", "served_logit_gap_mean",
        "reveal_confidence_gap_mean"}
    # the committed limits name numbers the driver gives
    blocks = pb_core.load_by_path("drivers/serve_closed_blocks.py")
    assert set(pb_core.Cell(CELL).limits) - {
        "ok_requests_of_wrong_length"} <= set(blocks.NUMBERS)
    assert line["checks"]["served_logit_gap"]["value"] <= 1e-4
    assert [f for f in os.listdir(tmp_path) if f.endswith(".json")]


@pytest.fixture(scope="module")
def reading_rows():
    cell = tiny_cell()
    return cell.driver.readings(cell, [11], None, seconds=0.6)


@pytest.mark.parametrize("side", [
    "control_fp8", "fault_causal_in_block", "fault_no_qk_norm",
    "fault_no_commit", "fault_no_renorm"])
def test_the_control_and_each_planted_fault_read_not_correct(reading_rows,
                                                             side):
    row = reading_rows[0]
    assert row["program_correct"] is True and row["requests"] >= 4
    assert row["served_logit_gap"] <= 1e-4
    assert row["reveal_confidence_gap"] <= 1e-4
    assert row[side + "_correct"] is False
    # by one of the cell's limits and not by each (a vocabulary of 96
    # leaves float8 the same tokens, and top-2 of 8 nearly sums to 1)
    gaps = ((row["control_logit_gap"], row["control_confidence_gap"])
            if side == "control_fp8" else
            [row[side + "_gaps"][k] for k in ("served_logit_gap",
                                              "reveal_confidence_gap")])
    assert max(gaps) > 10 * 1e-4


def test_the_readings_verdicts_are_readings_own(reading_rows, capsys):
    verdicts = pb_core.load_by_path("tools/readings.py").verdicts
    assert verdicts(reading_rows) is True
    assert "fault_no_commit: correct [False]" in capsys.readouterr().out
    passed = [dict(reading_rows[0], fault_no_commit_correct=True)]
    assert verdicts(passed) is False


def test_the_accepted_cells_keep_their_own_driver_module():
    """The blocks driver gives ITS copy of ``serve_closed.py`` another
    comparison; the module the accepted cells load is not touched."""
    blocks = pb_core.load_by_path("drivers/serve_closed_blocks.py")
    shared = pb_core.load_by_path("drivers/serve_closed.py")
    assert blocks.base is not shared
    assert shared.compare.__module__ == shared.__name__
    assert shared.reference_gaps.__module__ == shared.__name__
    assert blocks.base.compare is blocks.compare
    assert blocks.base.serve_window.__code__.co_code == \
        shared.serve_window.__code__.co_code


# ---------------------------------------------------------------------------
# the readers on a hand-made capture
#
# window 1 ms .. 11 ms. One resident launch (22) at 4,000k, 5,000k long:
# experts 2,000k | attention 1,500k | the head's projection 600k | the
# reveal under head/diffusion_select 400k | 500k idle inside. Two
# `serve.decode.done` spans.

K = 1000
RES = "jit(_resident_fn)/while/body/closed_call/"


def _op(name, start, length, op_name, program):
    hlo = (f"%{name} = bf16[8,128]{{1,0}} fusion(bf16[8,128]{{1,0}} "
           f"%p.{program})")
    return TraceEvent(hlo, start * K, length * K,
                      meta={"program_id": program, "tf_op": op_name})


def build_planes(with_counts=True, with_select=True):
    select = "head/diffusion_select/" if with_select else "head/"
    ops = [
        _op("fusion.1", 4000, 2000,
            RES + "diffusion_denoise/ffn/moe_experts/dot_general:", 22),
        _op("fusion.2", 6000, 1500,
            RES + "diffusion_commit/attention/attn_full/dot_general:", 22),
        _op("fusion.3", 7500, 600, RES + "head/dot_general:", 22),
        _op("fusion.4", 8100, 400, RES + select + "sort:", 22),
    ]
    modules = [TraceEvent("jit__resident_fn(22)", 4000 * K, 5000 * K)]

    def span(name, start, end, **stats):
        return TraceEvent(name, start * K, (end - start) * K, stats=stats)

    counts = [dict(blocks=60, denoise_passes=230, commit_passes=60,
                   tokens=230, cut_tokens=6, experts_touched=7000,
                   expert_rows=56000, full_rows_read=400000,
                   prefill_experts_touched=0, prefill_expert_rows=0),
              dict(blocks=20, denoise_passes=80, commit_passes=20,
                   tokens=80, cut_tokens=4, experts_touched=2000,
                   expert_rows=16000, full_rows_read=100000,
                   prefill_experts_touched=0, prefill_expert_rows=0)]
    host = [
        span(pb_trace.WINDOW_SPAN, 1000, 11000),
        span(ev.SERVE_DECODE_DONE, 9100, 9101, steps=15, chunks=3, live=20,
             rows=900, emitted=224, early_exit=1,
             **(counts[0] if with_counts else {})),
        span(ev.SERVE_DECODE_DONE, 10100, 10101, steps=5, chunks=1, live=20,
             rows=1000, emitted=76, early_exit=1,
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
        "trace": types.SimpleNamespace(
            module_time=lambda pattern: (1.0, 5e-3))}


def _read(metric, facts):
    return pb_core.load_by_path(f"layers/{metric}.py").read(facts)


def test_the_readers_against_hand_counts(tmp_path):
    facts = make_facts(tmp_path)
    fam, cfg = facts["cell"].family, facts["cfg"]
    # 310 denoise + 80 commit slot-passes for 310 - 10 tokens kept
    assert _read("diffusion.passes_per_token", facts) == pytest.approx(
        390 / 300)
    # 20 passes of the batch, 16 of them denoise passes
    must = (20 * fam.decode_weight_bytes(cfg) + 16 * fam.head_bytes(cfg)
            + 9000 * 9_437_184 + 500000 * 2048)
    assert _read("diffusion.pass_roofline", facts) == pytest.approx(
        100 * must / 1e12 / 5e-3)
    # the projection and the reveal of the launch's 5,000k
    assert _read("diffusion.select_share", facts) == pytest.approx(
        100 * 1000 / 5000)
    # the expert layer's readers go on adding up under this cell's names:
    # the ops under the new scopes count under the device scope around them
    assert _read(EXPERTS[0], facts) == pytest.approx(100 * 2 / 5)
    # 9,000 touched experts' bytes at 1e12 B/s over `moe_experts`' 2 ms
    assert _read(EXPERTS[1], facts) == pytest.approx(
        100 * 9000 * 9_437_184 / 1e12 / 2e-3)
    assert _read(EXPERTS[2], facts) == pytest.approx(8.0)


@pytest.mark.parametrize("metric", NEW + EXPERTS)
def test_a_program_without_the_scopes_or_counts_reads_none(metric, tmp_path):
    """A parent commit: no capture at all; and a capture whose decode spans
    carry no diffusion counts and whose head holds no reveal."""
    cell = pb_core.Cell(CELL)
    nothing = {"trace_dir": str(tmp_path / "none"), "cell": cell,
               "cfg": cell.cfg, "peaks": {}, "trace": None}
    assert _read(metric, nothing) is None
    if metric in NEW:
        facts = make_facts(tmp_path, with_counts=False, with_select=False)
        assert _read(metric, facts) is None
