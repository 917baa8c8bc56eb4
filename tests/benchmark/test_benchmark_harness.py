"""The benchmark's own tests (CPU; tiny sizes; no TPU is described or
touched at import).

What they hold: ``BENCHMARK.json`` names only what exists and only in the
allowed characters; both cells run end to end at a tiny size through the
test-only entry ``run.run_cell`` (the real command refuses to start without
a TPU); the ``train`` driver takes four stages on four devices as data; the
plain references agree with the program; the control (the reference one
precision lower in the program's place) and each planted fault come out as
not correct; the trace reduction and the FLOPs and bytes arithmetic give the
hand-counted numbers.
"""

import copy
import json
import math
import os
import re
import subprocess
import sys

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

SPEC = pb_core.read_json(os.path.join(ROOT, "BENCHMARK.json"))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TRAIN, SERVE = "lm520m-train-1chip", "gpt2xl-serve-closed8"


# ---------------------------------------------------------------------------
# BENCHMARK.json


def test_benchmark_json_names_and_units():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in SPEC[group]:
            assert NAME.match(entry["name"]), entry["name"]
            names.append((group in ("end_to_end", "per_layer"),
                          entry["name"]))
    assert len(names) == len(set(names))
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in SPEC["workloads"]}
    for m in SPEC["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        # each listed cell reports the end-to-end metric it moves
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved, m["name"]
    for w in SPEC["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200


@pytest.mark.parametrize("cell_name",
                         [w["name"] for w in SPEC["workloads"]])
def test_every_named_file_exists(cell_name):
    cell = pb_core.Cell(cell_name)
    assert os.path.isfile(os.path.join(ROOT, cell.config_entry["file"]))
    assert callable(cell.driver.run)
    assert callable(cell.family.model_config)
    for m in cell.metrics("per_layer"):
        assert callable(pb_core.load_by_path(
            f"layers/{m['name']}.py").read), m["name"]
    assert cell.limits and "limits" not in cell.traffic
    assert all(isinstance(v, (int, float)) for v in cell.limits.values())
    for entry in SPEC["configs"]:
        assert entry["file"].startswith("benchmark/configs/")


# ---------------------------------------------------------------------------
# tiny cells


def tiny_cell(name, **traffic_over):
    cell = pb_core.Cell(name)
    cfg, tr = dict(cell.cfg), copy.deepcopy(cell.traffic)
    if tr["kind"] == "train":
        cfg.update(vocab=67, d_model=16, nhead=2, d_ff=32, n_layers=2,
                   seq_len=8, compute_dtype="float32")
        tr.update(batch=4, seq=8)
        tr["corpus"]["types"] = 61
        tr["trainer"].update(chunks=2, warmup_steps=2)
        cell.limits = {"loss_rel_step2": 1e-5, "loss_rel_step3": 1e-5,
                       "grad_norm_gap": 1e-4, "grad_angle_median": 1e-4,
                       "change_norm_gap": 1e-3}
    else:
        cfg.update(vocab=67, d_model=16, nhead=2, d_ff=64, n_layers=2,
                   seq_len=64, compute_dtype="float32")
        tr["prompt"].update(median=8, min=4, max=32)
        tr["output"].update(median=6, min=3, max=16)
        tr["engine"].update(slots=4, bucket_min=8, bucket_max=32,
                            max_new_tokens=16)
        tr.update(clients=4, lead_in_s=0.3, check_requests=8, check_rows=4)
        cell.limits = {"ok_requests_of_wrong_length": 0,
                       "served_logit_gap": 1e-4}
    for k, v in traffic_over.items():
        if isinstance(v, dict):
            tr[k].update(v)
        else:
            tr[k] = v
    cell.cfg, cell.traffic = cfg, tr
    return cell


def run_tiny(cell, tmp_path, seconds=1.0, seed=3_000_000_019):
    import jax
    return bench_run.run_cell(cell, seed=seed, seconds=seconds, trace=0,
                              devices=jax.devices(), out_dir=str(tmp_path))


def check_result_line(res, cell):
    line = json.loads(json.dumps(res))
    assert list(line)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(
        line)
    assert set(line["metrics"]) == {m["name"]
                                    for m in cell.metrics("end_to_end")}
    for m in line["metrics"].values():
        assert m["value"] > 0 and isinstance(m["unit"], str)
    assert line["device"]["count"] == cell.chips
    assert line["attempted"] > 0 and line["failed"] == 0
    for c in line["checks"].values():
        assert set(c) == {"value", "limit"}


@pytest.mark.parametrize("name", [TRAIN, SERVE])
def test_cell_runs_end_to_end_tiny(name, tmp_path):
    cell = tiny_cell(name)
    res = run_tiny(cell, tmp_path)
    check_result_line(res, cell)
    assert res["correct"] is True, res["checks"]
    side = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
    assert side, "every run writes its side file"


def test_real_command_refuses_to_run_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", TRAIN,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode not in (0, None)
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_train_driver_takes_four_stages_as_data(tmp_path):
    """``lm520m-train-4chip`` is data alone: n_stages and chips come from
    the traffic file and the cell."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    cell = tiny_cell(TRAIN, trainer={"n_stages": 4, "chunks": 4})
    cell.cfg.update(n_layers=4)
    cell.traffic.update(batch=8)
    cell.chips = 4
    res = run_tiny(cell, tmp_path)
    assert res["device"]["count"] == 4
    assert res["correct"] is True, res["checks"]


# ---------------------------------------------------------------------------
# the timed path broken underneath: correct has to come out false


def _break_state_unchanged(monkeypatch):
    from pipe_tpu.train.loop import Trainer
    real = Trainer._compute_update

    def frozen(self, state, *a, **kw):
        _, _, loss, grads = real(self, state, *a, **kw)
        return state.params, state.opt_state, loss, grads
    monkeypatch.setattr(Trainer, "_compute_update", frozen)


def _break_half_batch(monkeypatch):
    from pipe_tpu.train.loop import Trainer
    real = Trainer._make_x

    def half(self, data, target):
        x, w = real(self, data, target)
        keep = np.arange(w.shape[0])[:, None] < w.shape[0] // 2
        return x, w * keep
    monkeypatch.setattr(Trainer, "_make_x", half)


def _break_token(monkeypatch):
    from pipe_tpu.serve import SingleDeviceSlotBackend
    real = SingleDeviceSlotBackend.prefill

    def altered(self, slot, prompt, seed, **kw):
        return (real(self, slot, prompt, seed, **kw) + 1) % 67
    monkeypatch.setattr(SingleDeviceSlotBackend, "prefill", altered)


@pytest.mark.parametrize("name,fault", [
    (TRAIN, _break_state_unchanged), (TRAIN, _break_half_batch),
    (SERVE, _break_token)],
    ids=["state-unchanged", "half-the-batch", "token-altered"])
def test_planted_fault_reads_not_correct(name, fault, tmp_path, monkeypatch):
    fault(monkeypatch)
    res = run_tiny(tiny_cell(name), tmp_path)
    assert res["correct"] is False, res["checks"]
    bad = [k for k, c in res["checks"].items() if c["value"] is None
           or not c["value"] <= c["limit"]]
    assert bad


# ---------------------------------------------------------------------------
# the control: the reference one precision lower, in the program's place


@pytest.mark.parametrize("name", [TRAIN, SERVE])
def test_readings_judge_control_and_faults_by_the_cells_limits(name,
                                                               capsys):
    """``tools/readings.py``'s path: the program reads correct under the
    cell's limits, the float8 control and each planted fault do not."""
    import jax
    tool = pb_core.load_by_path("tools/readings.py")
    cell = tiny_cell(name)
    rows = cell.driver.readings(cell, [11], jax.devices(), seconds=0.6)
    sides = {k: v["correct"] for k, v in rows[0].items()
             if isinstance(v, dict) and "correct" in v}
    sides.update({k[:-8]: v for k, v in rows[0].items()
                  if k.endswith("_correct")})
    want = ({"program", "control_fp8", "fault_half_batch", "fault_frozen"}
            if name == TRAIN else {"program", "control_fp8"})
    assert set(sides) == want
    assert sides.pop("program") is True
    assert not any(sides.values()), sides
    assert tool.verdicts(rows) is True
    assert "control_fp8: correct [False]" in capsys.readouterr().out
    # a control that passes, or a program that fails, is a bad verdict
    flipped = json.loads(json.dumps(rows))
    for row in flipped:
        for k, v in row.items():
            if k == "control_fp8":
                v["correct"] = True
            elif k == "control_fp8_correct":
                row[k] = True
    assert tool.verdicts(flipped) is False
    assert pb_core.judge({"a": 1.0, "b": 0.5}, {"a": 2.0}).correct is True
    assert pb_core.judge({"b": 0.5}, {"a": 2.0}).correct is False


def test_serve_traced_stretch_follows_the_window(tmp_path, monkeypatch):
    """With a trace the whole window runs first, untraced; the profiler's
    stretch comes after it and adds nothing to the window's requests."""
    import contextlib
    monkeypatch.setattr(pb_trace, "capture",
                        lambda logdir, on=True: contextlib.nullcontext())
    monkeypatch.setattr(pb_trace, "span",
                        lambda name, on=True: contextlib.nullcontext())
    cell = tiny_cell(SERVE)
    w = cell.driver.serve_window(cell, 7, 0.6, trace_seconds=0.4,
                                 trace_dir=str(tmp_path))
    assert w["done"] and all(w["t0"] <= t <= w["t1"] for t, _ in w["done"])
    assert any(t > w["t1"] for t, _ in w["loop"].done)
    assert w["probe"].decode_steps > 0 and w["probe"].prefills > 0
    assert w["t_end"] - w["t1"] >= 0.4
    assert w["eng"].backend.prefill.__self__ is w["eng"].backend


def test_serve_control_fp8_reads_a_gap():
    """At each position of the same prompts and tokens the float8 reference
    puts first a token whose float32 logit lies below the best; the float32
    reference's own first tokens read 0."""
    import jax.numpy as jnp
    cell = tiny_cell(SERVE)
    ref = cell.family.reference
    weights = ref.make_weights(cell.cfg, 5)
    tokens = jnp.asarray(np.random.default_rng(5).integers(
        1, cell.cfg["vocab"], size=(4, 48)), jnp.int32)
    logits = ref.forward(weights, tokens, cell.cfg)
    own = ref.gaps_below_best(logits, jnp.argmax(logits, -1))
    low = ref.forward(weights, tokens, cell.cfg, precision="fp8")
    ctl = ref.gaps_below_best(logits, jnp.argmax(low, -1))
    assert float(own.max()) == 0.0
    assert float(ctl.max()) > 1e-3


# ---------------------------------------------------------------------------
# the plain references against the program


def test_gpt2_reference_logits_agree_with_the_program():
    import jax
    import jax.numpy as jnp
    from pipe_tpu.core.partition import StageCtx
    cell = tiny_cell(SERVE)
    fam, cfg = cell.family, cell.cfg
    weights = fam.reference.make_weights(cfg, 7)
    model = fam.build_model(cfg, 1)
    sp, pre, post = fam.serve_params(weights)
    tokens = jnp.asarray(np.random.default_rng(7).integers(
        1, cfg["vocab"], size=(2, 24)), jnp.int32)
    ctx = StageCtx(train=False)
    h = model.pre_fn(pre, tokens, ctx)
    for blocks in sp:
        h = model.stage_fn(blocks, h, ctx)
    got = model.head.apply(post["head"], h, ctx=ctx)
    want = fam.reference.forward(weights, tokens, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-5)


def test_tutorial_lm_reference_loss_agrees_with_the_program():
    """Eval-mode loss of one batch: the program's training-path forward
    against the reference, same weights (the training step itself, dropout
    masks included, is compared in the end-to-end cell test)."""
    import jax
    import jax.numpy as jnp
    from pipe_tpu.core.partition import StageCtx
    cell = tiny_cell(TRAIN)
    fam, cfg = cell.family, cell.cfg
    ref = fam.reference
    weights = ref.make_weights(cfg, 9)
    model = fam.build_model(cfg, 1)
    # the same weights, made straight into the trainer's layout
    blocks, pre, post = fam.make_train_params(cfg, 9, 1)
    for l, bp in enumerate(blocks):
        np.testing.assert_array_equal(np.asarray(bp["ff1"]["w"][0]),
                                      np.asarray(weights["layers"]["ff1_w"][l]))
    rng = np.random.default_rng(9)
    tokens = jnp.asarray(rng.integers(0, cfg["vocab"], size=(4, 8)))
    targets = jnp.asarray(rng.integers(0, cfg["vocab"], size=(4, 8)))
    ctx = StageCtx(train=False)
    h = model.pre_fn(pre, tokens, ctx)
    h = model.stage_fn([jax.tree_util.tree_map(lambda a: a[0], b)
                        for b in blocks], h, ctx)
    got = jnp.sum(model.loss_post_fn(post, h, {"targets": targets}, ctx))
    want = ref.microbatch_loss(weights, tokens, targets, None, 0, cfg,
                               train=False)
    assert abs(float(got) - float(want)) <= 1e-5 * abs(float(want))


# ---------------------------------------------------------------------------
# arithmetic, traffic, trace reduction


def test_flops_and_bytes_arithmetic_against_hand_counts():
    from pipe_tpu.models.transformer_lm import LMConfig
    from pipe_tpu.obs.telemetry import train_flops_per_token
    lm = pb_core.Cell(TRAIN)
    cfg = lm.cfg
    required, _ = train_flops_per_token(LMConfig(), "except_last", 4)
    assert lm.family.train_flops_per_token(cfg) == required
    # by hand: 16 x (4 x 2048^2 + 2 x 2048 x 2048 + 2 x 64 x 2048)
    #          + 2048 x 28782 multiply-adds, x 2 FLOPs x 3 passes
    macs = 16 * (4 * 2048 ** 2 + 2 * 2048 * 2048 + 2 * 64 * 2048) \
        + 2048 * 28782
    assert required == 6 * macs
    assert lm.family.num_params(cfg) == cfg["parameters"] == 520_900_718
    assert lm.family.train_bytes_per_step(cfg, 32) == \
        28 * 520_900_718 + 32 * 128 * 2048 * 4 * 16

    xl = pb_core.Cell(SERVE)
    c = xl.cfg
    mm = 48 * (4 * 1600 ** 2 + 2 * 1600 * 6400) + 1600 * 50257
    assert xl.family.matmul_params(c) == mm
    assert xl.family.forward_flops_per_token(c) == 2 * mm
    per_layer = 4 * 1600 ** 2 + 4 * 1600 + 2 * 1600 * 6400 + 6400 + 1600 \
        + 4 * 1600
    assert xl.family.decode_weight_bytes(c) == \
        48 * per_layer * 2 + (1600 * 50257 + 3200) * 4
    assert xl.family.kv_row_bytes(c) == 48 * 2 * 1600 * 2
    assert xl.family.num_params(c) == 50257 * 1600 + 1024 * 1600 \
        + 48 * per_layer + 3200 + 1600 * 50257


def test_traffic_is_the_same_work_in_another_order():
    tr = pb_core.Cell(SERVE).traffic
    p, o = pb_traffic.request_pool(tr)
    assert p.min() >= 16 and p.max() <= 512 and len(set(p.tolist())) > 20
    assert o.min() >= 16 and o.max() <= 128
    # the pool holds the log-normal's shape: its median and its tails
    assert abs(float(np.median(p)) - 96) <= 8 and p.max() >= 400

    def first(seed):
        src = pb_traffic.requests(tr, seed, 50257)
        return [next(src) for _ in range(len(p))]
    a, b, a2 = first(1), first(2 ** 31 + 7), first(1)
    assert a == a2 and a != b
    assert sorted((len(x), y) for x, y in a) == \
        sorted((len(x), y) for x, y in b) == sorted(zip(p.tolist(),
                                                        o.tolist()))
    c = pb_traffic.corpus(pb_core.Cell(TRAIN).traffic, 2 ** 31 + 7, 3)
    assert c.shape == (3 * 128 + 1, 32) and c.max() < 1001
    assert len({row.tobytes() for row in c.T}) == 32


def test_a_client_rate_runs_from_its_first_reply_inside_to_its_last():
    """No request is cut: a client's rate is the ok tokens of its replies
    after the first inside the window, over the time to the last inside."""
    import types
    drv = pb_core.load_by_path("drivers/serve_closed.py")

    def reply(t, n, status="ok"):
        return (t, types.SimpleNamespace(tokens=[0] * n, status=status))
    replies = [
        # lead-in reply, then 10.5 (first), 12, 14 (failed), 19.5 (last)
        [reply(9.0, 7), reply(10.5, 5), reply(12.0, 30), reply(14.0, 9, "x"),
         reply(19.5, 50), reply(23.0, 11)],
        # first at the opening itself, last at the close
        [reply(10.0, 3), reply(16.0, 60), reply(20.0, 60)],
        # one reply inside: no rate
        [reply(9.0, 1), reply(11.0, 5), reply(21.0, 5)],
    ]
    assert drv.client_rates(replies, 10.0, 20.0) == [
        pytest.approx(80 / 9.0), pytest.approx(120 / 10.0), None]


def test_trace_reduction_on_the_recorded_fixture():
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(os.path.join(
        BENCH, "testdata", "two_chip.xplane.pb")).planes)
    s = pb_trace.TraceSummary(planes, {"train_epoch", "step_dispatch"})
    assert s.window_s == pytest.approx(1e-3)
    assert s.device_busy == pytest.approx({0: 6e-4, 1: 6e-4})
    assert s.busy_s == pytest.approx(6e-4)
    assert s.op_seconds == pytest.approx(
        {"fusion.1": 3e-4, "fusion.2": 2.5e-4, "copy.3": 5e-5,
         "fusion.9": 2.5e-5})
    assert s.module_time("train_step") == pytest.approx((1.5, 6.5e-4))
    assert s.gap_seconds == pytest.approx(
        {"train_epoch": 2.5e-4, "traced_window": 1.5e-4})
    b = s.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["idle_gaps"][0] == ["train_epoch", pytest.approx(2.5e-4)]
    idle = pb_core.load_by_path("layers/pipeline.stage_idle_share.py").read(
        {"trace": s})
    assert idle == pytest.approx(40.0)
    with pytest.raises(ValueError):
        pb_trace.TraceSummary(
            [p for p in planes if not p.name.startswith("/device")], set())


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": np.array([1.0, 2.0, 4.0]), "tiny": np.array([1e-9])}
    prog = {"a": np.array([1.0, 2.2, 4.0]), "tiny": np.array([3e-9])}
    gap, where = pb_core.worst_leaf_gap(prog, ref)
    assert where == "a[1]" and gap == pytest.approx(0.1)   # 0.2 / 2.0
    gap, _ = pb_core.worst_leaf_gap(
        {"a": ref["a"], "tiny": np.array([0.5])}, ref)
    assert gap == pytest.approx(0.5 / 1.5)                 # median 1.5
    skip = pb_core.near_zero_leaves(ref)
    assert skip["tiny"].all() and not skip["a"].any()
    assert math.isinf(pb_core.worst_leaf_gap(
        {"a": np.array([1.0, np.nan, 4.0]), "tiny": ref["tiny"]}, ref)[0])


@pytest.mark.parametrize("cell_name", [TRAIN, SERVE])
def test_layer_readers_read_hand_made_facts(cell_name):
    """Every per-layer reader of a cell on facts whose numbers are counted by
    hand: the fixture's trace (busy 0.6 ms of a 1 ms window, programs
    ``train_step``) and a chip with round peaks."""
    from jax.profiler import ProfileData
    planes = list(ProfileData.from_file(os.path.join(
        BENCH, "testdata", "two_chip.xplane.pb")).planes)
    cell = pb_core.Cell(cell_name)
    fam, cfg = cell.family, cell.cfg
    facts = {
        "cell": cell, "cfg": cfg, "chips": 1, "window_s": 2.0,
        "peaks": {"bf16_flops_per_s": 1e14, "hbm_bytes_per_s": 1e12},
        "trace": pb_trace.TraceSummary(planes, set()),
        "memory_peak_bytes": 3 * 2 ** 30, "setup_compile_s": 7.0,
        "setup_trace_s": 5.0, "window_compiles": 0,
    }
    if cell.traffic["kind"] == "train":
        facts.update(tokens=8192, steps=2, rows=32)
        want = {
            "trainer.mfu": 100 * fam.train_flops_per_token(cfg) * 4096
            / 1e14,
            # a step's FLOPs / peak over 0.3 ms busy a step
            "blocks.step_roofline": 100 * (
                fam.train_flops_per_token(cfg) * 4096 / 1e14) / 3e-4,
            "device.peak_hbm_gib.train": 3.0,
        }
    else:
        # the fixture's programs stand for prefill and decode alike
        facts["trace"].module_seconds = {
            "jit__prefill_fn(1)": (2.0, 0.5), "jit__resident_fn(2)": (4, 1.0)}
        facts.update(
            out_tokens=1000, prompt_tokens=3000, host_sec=0.25,
            window_traces={"decode_traces": 0, "resident_traces": 0,
                           "prefill_traces": 1},
            ttft_p95_ms=123.0, client_tokens_per_s=77.0,
            probe={"prefills": 2, "prompt_tokens": 300, "padded_tokens": 512,
                   "decode_steps": 40, "decode_launches": 4,
                   "decode_bytes": 40 * fam.decode_weight_bytes(cfg)})
        flops = fam.forward_flops_per_token(cfg)
        want = {
            "engine.mfu": 100 * flops * 4000 / 2.0 / 1e14,
            "engine.host_us_per_token": 250.0,
            "engine.ttft_p95_ms": 123.0,
            "engine.client_tokens_per_s": 77.0,
            "engine.prefill_mfu": 100 * flops * 300 / 0.5 / 1e14,
            "decode.step_roofline": 100 * (
                40 * fam.decode_weight_bytes(cfg) / 1e12) / 1.0,
            "device.peak_hbm_gib.serve": 3.0,
        }
    want.update({"entry.compile_s": 7.0, "entry.trace_s": 5.0,
                 "entry.window_compiles":
                 sum(facts.get("window_traces", {}).values())})
    got = {m["name"]: pb_core.load_by_path(
        f"layers/{m['name']}.py").read(facts)
        for m in cell.metrics("per_layer")}
    assert got == pytest.approx(want)
    if cell.traffic["kind"] != "train":
        facts["probe"]["prefills"] = 0       # nothing to read: left out
        assert pb_core.load_by_path(
            "layers/engine.prefill_mfu.py").read(facts) is None
