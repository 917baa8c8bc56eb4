"""Laguna-S-2.1 on the serve path, at a small size on the CPU (hidden 64,
12 | 18 query heads over 2 KV heads of 16 (the published groups of 6 and 9),
window 8, 8 experts top-3 with 4 held, the 5 leading layers, a vocabulary
slice of 96).

What they hold: the program's whole-sequence forward and the serve engine's
prefill and decode through both kinds of cache agree with the plain float32
reference (``benchmark/reference/laguna.py``, which imports nothing of the
program); the shares of an expert layer add up to the uncut layer; the window
layers' cache is a ring written at ``pos % window`` that never reads a row
the sequence has not reached; the paths that decode one stacked block refuse
the model in one sentence.
"""

import copy
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import pb_core  # noqa: E402
from pipe_tpu.inference import GenerationConfig  # noqa: E402
from pipe_tpu.models.laguna import (LAYER_COUNTS, LagunaBlock,  # noqa: E402
                                    LagunaConfig, PipelinedLaguna)
from pipe_tpu.obs.telemetry import get_registry  # noqa: E402
from pipe_tpu.ops.layers import (MultiHeadAttention, fold_heads,  # noqa: E402
                                 rope_frequencies)
from pipe_tpu.ops.moe import dropless_moe  # noqa: E402
from pipe_tpu.serve import (BucketSpec, RequestQueue, ServeEngine,  # noqa: E402
                            SingleDeviceSlotBackend)

FAMILY = pb_core.load_by_path("families/laguna.py")
REF = FAMILY.reference
TOL = 2e-4          # float32 against float32 `highest`, five layers deep


def tiny_cfg(**over):
    """The cell's configuration file at the small size."""
    cfg = copy.deepcopy(pb_core.read_json(os.path.join(
        BENCH, "configs", "laguna-s-2.1.json")))
    cfg.update(vocab=96, hidden_size=64, head_dim=16, num_key_value_heads=2,
               sliding_window=8, intermediate_size=128,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               num_experts=4, num_experts_per_tok=3, experts_held=[0, 4],
               compute_dtype="float32")
    cfg["published"] = dict(cfg["published"], num_experts=8)
    cfg["num_attention_heads_per_layer"] = [
        12 if t == "full_attention" else 18 for t in cfg["layer_types"]]
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_cfg()
    weights = REF.make_weights(cfg, 5)
    return cfg, weights, FAMILY.build_model(cfg, 1)


def test_the_family_builds_the_published_pattern(tiny):
    cfg, weights, model = tiny
    mc = model.cfg
    assert mc == dataclass_replace(LagunaConfig().tiny(), mc)
    assert mc.layer_kinds() == [
        ("full", "dense"), ("sliding", "moe"), ("sliding", "moe"),
        ("sliding", "moe"), ("full", "moe")]
    groups = model.layer_groups()
    assert [(g.n, g.cache, g.first) for g in groups] == [
        (1, "full", 0), (3, "window", 0), (1, "full", 1)]
    # the reference groups its weights the same way, from the file alone
    assert [n for _, n in REF.layer_groups(cfg)] == [1, 3, 1]
    params = FAMILY.serve_params(weights)
    assert params[0][0][1]["moe"]["w_gate"] is weights["groups"][1]["e_gate"]
    assert model.num_params(params) == REF.num_params(cfg)


def dataclass_replace(want, got):
    """``want`` with the fields the configuration file sets otherwise (the
    rotary tables' dicts and the position limit) taken from ``got``."""
    import dataclasses
    return dataclasses.replace(
        want, rope_full=got.rope_full, rope_sliding=got.rope_sliding,
        max_positions=got.max_positions)


def test_whole_sequence_forward_agrees_with_the_reference(tiny):
    cfg, weights, model = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, cfg["vocab"], size=(2, 40)), jnp.int32)
    want = REF.forward(weights, tokens, cfg)
    got = jax.jit(model.forward)(FAMILY.serve_params(weights), tokens)
    assert got.shape == (2, 40, cfg["vocab"]) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)


@pytest.mark.parametrize("fault", REF.FAULTS + ("fp8",))
def test_a_planted_fault_or_a_lower_precision_moves_the_logits(tiny, fault):
    """Each fault the limits' readings plant, and the float8 control, is
    far outside the tolerance the program is held to."""
    cfg, weights, _ = tiny
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, cfg["vocab"], size=(2, 40)), jnp.int32)
    want = REF.forward(weights, tokens, cfg)
    if fault == "fp8":
        got = REF.forward(weights, tokens, cfg, precision="fp8")
    else:
        got = REF.forward(weights, tokens, dict(cfg, fault=fault))
    assert float(jnp.abs(got - want).max()) > 100 * TOL
    with pytest.raises(ValueError):
        REF.forward(weights, tokens, dict(cfg, fault="no_such_fault"))


def test_yarn_frequencies_are_hugging_faces():
    """The published full-attention table: 32 frequencies over half a head
    of 128; fast dimensions keep theta's, slow ones are divided by the
    factor, and cos/sin carry 0.1 ln(128) + 1."""
    inv, scale = rope_frequencies(
        128, theta=500000.0, fraction=0.5,
        yarn={"factor": 128.0, "original": 8192, "beta_fast": 32.0,
              "beta_slow": 1.0})
    plain, one = rope_frequencies(128, theta=500000.0, fraction=0.5)
    assert inv.shape == (32,) and one == 1.0
    assert scale == pytest.approx(1.4852030263919618)
    np.testing.assert_allclose(inv[:8], plain[:8], rtol=1e-6)
    np.testing.assert_allclose(inv[-4:], plain[-4:] / 128.0, rtol=1e-6)
    assert np.all(np.diff(inv) < 0)
    cfg = tiny_cfg(head_dim=128)
    cos, sin, rot = REF.rope_tables(cfg, "full_attention", 3)
    assert rot == 64
    np.testing.assert_allclose(np.asarray(cos[2]), np.cos(2 * inv) * scale,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# the engine through both caches


def serve(tiny, prompts, new, slots=3, **backend):
    cfg, weights, model = tiny
    gen = GenerationConfig(max_new_tokens=24, temperature=0.0)
    be = SingleDeviceSlotBackend(
        model, FAMILY.serve_params(weights), num_slots=slots, max_len=32 + 24,
        gen=gen, buckets=BucketSpec.pow2(min_len=4, max_len=32),
        decode_chunk=2, resident=True, resident_chunks=3, **backend)
    eng = ServeEngine(be, RequestQueue(capacity=16, policy="fifo"))
    reqs = [eng.submit(p, max_new_tokens=n, seed=i)
            for i, (p, n) in enumerate(zip(prompts, new))]
    done = {r.request_id: r for r in eng.run_until_idle()}
    return be, [done[r.id] for r in reqs]


def test_prefill_and_decode_through_both_caches_agree_with_the_reference(
        tiny):
    """Seven requests over three slots: prompts of 3 to 31 tokens, up to 24
    new ones, so contexts run to 55 rows where the window is 8 and a slot is
    taken again by a shorter request than its last. Every served token is the
    reference's own best (its logit within the float32 tolerance of the best
    logit of the full forward over the prompt and the tokens served before
    it)."""
    cfg, weights, _ = tiny
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg["vocab"], size=n).tolist()
               for n in (5, 20, 31, 9, 3, 17, 12)]
    new = [24, 20, 11, 24, 7, 16, 24]
    reg = get_registry()
    names = [f"serve.moe.{n}" for n in LAYER_COUNTS] + [
        "serve.cache.full_rows_read", "serve.cache.window_rows_read"]
    before = {n: reg.counter(n).value for n in names}
    be, resps = serve(tiny, prompts, new)
    assert {k: v["k"].shape for k, v in be._caches.items()
            if k != "counts"} == {"full": (2, 3, 56, 128),
                                  "window": (3, 3, 8, 128)}
    for prompt, n, resp in zip(prompts, new, resps):
        assert resp.status == "ok" and len(resp.tokens) == n
        seq = np.asarray([prompt + resp.tokens], np.int32)
        logits = REF.forward(weights, jnp.asarray(seq), cfg)
        gaps = np.asarray(REF.gaps_below_best(
            logits, jnp.asarray(np.roll(seq, -1, axis=1))))[0]
        assert gaps[len(prompt) - 1:seq.shape[1] - 1].max() <= TOL
    grew = {n: reg.counter(n).value - before[n] for n in names}
    # every pair of a live row is computed here or left to an absent expert
    pairs = grew["serve.moe.expert_rows"] + grew["serve.moe.absent_rows"]
    assert pairs % (4 * cfg["num_experts_per_tok"]) == 0 and pairs > 0
    assert 0 < grew["serve.moe.experts_touched"] <= grew[
        "serve.moe.expert_rows"]
    assert grew["serve.moe.layer_steps"] * 4 >= grew[
        "serve.moe.experts_touched"]
    # two full layers read every row, three window layers at most eight
    assert grew["serve.cache.full_rows_read"] > grew[
        "serve.cache.window_rows_read"] > 0
    assert set(be.launch_counts) >= {
        "expert_rows", "absent_rows", "experts_touched", "layer_steps",
        "prefill_expert_rows", "full_rows_read", "window_rows_read"}


def test_the_decode_program_traces_once_and_syncs_once_a_launch(tiny):
    cfg = tiny[0]
    reg = get_registry()
    t0 = reg.counter("serve.engine.resident_traces").value
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg["vocab"], size=n).tolist()
               for n in (6, 14, 25, 9)]
    be, resps = serve(tiny, prompts, [9, 5, 12, 7], slots=2)
    assert all(r.status == "ok" for r in resps)
    assert reg.counter("serve.engine.resident_traces").value - t0 == 1
    # weights enter as the model's groups hold them: nothing stacked again
    assert be._block_stack[1]["moe"]["w_gate"] is tiny[1]["groups"][1][
        "e_gate"]


# ---------------------------------------------------------------------------
# a chip's share of the experts


@pytest.mark.parametrize("rows", [1, 5, 16])
def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(rows):
    """Guide, section 4: the routed parts that shares 0-3 and 4-7 compute,
    plus what every chip computes alike (the shared expert) counted once,
    equal the uncut reference layer."""
    cfg = tiny_cfg(num_experts=8, experts_held=[0, 8])      # the uncut layer
    group = REF.make_weights(cfg, 9)["groups"][1]           # three moe layers
    m = jax.random.normal(jax.random.key(rows), (rows, cfg["hidden_size"]))
    mm = REF._mm("f32")
    want = (REF._experts(m, dict(group, router=group["router"][1]), 1, cfg,
                         mm)
            + REF._gated_mlp(m, group["s_gate"][1], group["s_up"][1],
                             group["s_down"][1], mm))
    parts, counted = [], []
    for first in (0, 4):
        share = {"router": group["router"][1],
                 **{n: group["e" + n[1:]][1, first:first + 4]
                    for n in ("w_gate", "w_up", "w_down")}}
        y, counts = dropless_moe(share, m, top_k=3, first=first, scale=2.5)
        parts.append(y)
        counted.append(np.asarray(counts))
    shared = (jax.nn.silu(m @ group["s_gate"][1]) * (m @ group["s_up"][1])
              ) @ group["s_down"][1]
    np.testing.assert_allclose(np.asarray(parts[0] + parts[1] + shared),
                               np.asarray(want), atol=2e-5)
    # what one share leaves to absent experts is what the other computes
    assert counted[0][0] == counted[1][1] and counted[0][1] == counted[1][0]
    assert counted[0][0] + counted[0][1] == rows * 3


def test_a_layers_experts_are_taken_where_they_lie_in_the_groups_stack():
    """``layer=``: the stacked ``[layers, held, ...]`` tensors give what the
    layer's own slice gives, and dead rows take no part."""
    cfg = tiny_cfg()
    group = REF.make_weights(cfg, 3)["groups"][1]
    stacked = {"router": group["router"][2], "w_gate": group["e_gate"],
               "w_up": group["e_up"], "w_down": group["e_down"]}
    sliced = {"router": group["router"][2], "w_gate": group["e_gate"][2],
              "w_up": group["e_up"][2], "w_down": group["e_down"][2]}
    m = jax.random.normal(jax.random.key(0), (6, cfg["hidden_size"]))
    live = jnp.asarray([True, True, False, True, False, True])
    a, ca = jax.jit(lambda p, x: dropless_moe(
        p, x, top_k=3, scale=2.5, live=live, layer=2))(stacked, m)
    b, cb = dropless_moe(sliced, m, top_k=3, scale=2.5, live=live)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    assert np.array_equal(np.asarray(ca), np.asarray(cb))
    assert not np.asarray(a)[[2, 4]].any() and np.asarray(a)[0].any()
    assert int(ca[0] + ca[1]) == 4 * 3


# ---------------------------------------------------------------------------
# the ring


def window_attention(window=8):
    return MultiHeadAttention(
        32, 6, causal=True, kv_heads=2, head_dim=8, bias=False,
        rope={"theta": 10000.0, "fraction": 1.0}, window=window, gate=True)


def test_the_ring_is_written_at_pos_mod_window_and_reads_no_unreached_row():
    """Twenty positions one at a time through the slab form of a window
    attention (a ring of 8 rows a slot) against the batch form over a cache
    of every row: the same outputs; position p's key lies in row p % 8; and
    rows that a slot's sequence has not reached (here full of 1e4, what an
    earlier occupant left) are never read."""
    attn = window_attention()
    params = attn.init(jax.random.key(0), jnp.zeros((1, 1, 32)))
    xs = jax.random.normal(jax.random.key(1), (2, 20, 32))
    slab = jax.tree_util.tree_map(lambda a: a + 1e4,
                                  attn.make_slab(1, 2, 999))
    assert slab["k"].shape == (1, 2, 8, 128)
    cache = attn.make_cache(2, 20)
    step = jax.jit(lambda x, slab, pos: attn.decode(params, x, slab, pos,
                                                    layer=0))
    for p in range(20):
        want, cache = attn.decode(params, xs[:, p:p + 1], cache, p)
        # the two slots stand at different positions, as slots do
        got, slab = step(xs[:, p:p + 1], slab, jnp.asarray([p, p]))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(slab["k"][0, :, p % 8]),
            np.asarray(fold_heads(cache["k"][:, p])), atol=1e-6)
    # the batch form's window mask is the whole-sequence forward's
    full = attn.apply(params, xs)
    np.testing.assert_allclose(np.asarray(full[:, -1:]), np.asarray(want),
                               atol=1e-5)


@pytest.mark.parametrize("true_len,bucket", [(3, 4), (8, 8), (13, 16),
                                             (30, 32)])
def test_a_prompts_rows_are_seated_as_the_ring_holds_them(true_len, bucket):
    attn = window_attention()
    rows = jax.random.normal(jax.random.key(2), (1, bucket, 2, 8))
    ring = np.asarray(attn.seat(rows, jnp.int32(true_len)))
    assert ring.shape == (1, 8, 128)
    for p in range(max(0, true_len - 8), true_len):
        np.testing.assert_array_equal(ring[0, p % 8],
                                      np.asarray(fold_heads(rows[0, p])))
    plain = MultiHeadAttention(32, 6, kv_heads=2, head_dim=8)
    assert plain.seat(rows, jnp.int32(true_len)).shape == (1, bucket, 128)
    assert plain.slab_rows(640) == 640 and attn.slab_rows(640) == 8


def test_a_ring_takes_one_row_a_step():
    attn = window_attention()
    params = attn.init(jax.random.key(0), jnp.zeros((1, 1, 32)))
    with pytest.raises(ValueError, match="one new row a step"):
        attn.decode(params, jnp.zeros((2, 3, 32)), attn.make_slab(1, 2, 64),
                    jnp.asarray([0, 0]), layer=0)


# ---------------------------------------------------------------------------
# who refuses the model, each in one sentence


def _laguna():
    model = PipelinedLaguna(LagunaConfig().tiny(), 1)
    return model, model.init(jax.random.key(0))


def _refused_by_the_pool():
    model, params = _laguna()
    SingleDeviceSlotBackend(model, params, num_slots=2, max_len=32,
                            kv_block_size=8)


def _refused_by_spec_rounds():
    model, params = _laguna()
    SingleDeviceSlotBackend(model, params, num_slots=2, max_len=32,
                            spec_tokens=3)


def _refused_by_the_ring():
    from pipe_tpu.parallel.mesh import make_mesh
    from pipe_tpu.serve import RingSlotBackend
    model, (stages, pre, post) = _laguna()
    RingSlotBackend(make_mesh(1, 1, devices=jax.devices()[:1]), model,
                    stages, pre, post, max_len=32)


def _refused_by_the_pipelined_generator():
    from pipe_tpu.inference.pipelined import PipelinedGenerator
    from pipe_tpu.parallel.mesh import make_mesh
    PipelinedGenerator(make_mesh(1, 1, devices=jax.devices()[:1]),
                       _laguna()[0])


@pytest.mark.parametrize("build,who", [
    (_refused_by_the_pool, "_PoolStore"),
    (_refused_by_spec_rounds, "_spec_round"),
    (_refused_by_the_ring, "serve/ring.py"),
    (_refused_by_the_pipelined_generator, "inference/pipelined.py")])
def test_a_path_that_decodes_one_stacked_block_refuses_the_model(build, who):
    with pytest.raises(NotImplementedError) as err:
        build()
    text = str(err.value)
    assert "PipelinedLaguna" in text and who in text
    assert text.count(". ") == 0 and text.count(":") == 1   # one sentence


def test_one_stage_only_and_no_training_path():
    with pytest.raises(ValueError, match="one stage"):
        PipelinedLaguna(LagunaConfig().tiny(), 2)
    model, _ = _laguna()
    with pytest.raises(NotImplementedError, match="served, not trained"):
        model.stage_fn([], None, None)
    block = LagunaBlock(LagunaConfig().tiny(), "sliding", "moe")
    assert block.attn.window == 8 and block.attn.group == 9


def test_the_serve_app_builds_the_model_as_it_builds_gpt2(capsys):
    from pipe_tpu.apps import serve as app
    rc = app.main(["--family", "laguna", "--tiny", "--requests", "5",
                   "--rate", "0", "--slots", "2", "--max-new", "6"])
    assert rc == 0, capsys.readouterr()


def test_the_prefill_programs_trace_the_tiled_product_and_decode_does_not(
        tiny):
    """The grouped product's implementation follows the static pair count
    (``ops.moe.grouped_impl``; here top-3 over 4 held experts: tiled from 16
    pairs): the one decode program (2 slots: 6 pairs) traces the compiler's
    kernel in each of its expert-layer groups and no Pallas call; every
    prefill bucket of 8 rows or more traces the tiled kernel alone."""
    cfg, weights, model = tiny
    be = SingleDeviceSlotBackend(
        model, FAMILY.serve_params(weights), num_slots=2, max_len=32 + 24,
        gen=GenerationConfig(max_new_tokens=24, temperature=0.0),
        buckets=BucketSpec.pow2(min_len=8, max_len=32), decode_chunk=2,
        resident=True, resident_chunks=3)
    reg = get_registry()
    names = ("ops.moe.grouped.compiler", "ops.moe.grouped.tiled",
             "ops.grouped_product.interpreted")

    def traced(program):
        fn, args = program
        before = [reg.counter(n).value for n in names]
        fn.lower(*args)
        return [reg.counter(n).value - b for n, b in zip(names, before)]

    compiler, tiled, kernels = traced(be.decode_program())
    assert compiler >= 2 and tiled == 0 and kernels == 0
    for bucket in (8, 16, 32):
        compiler, tiled, kernels = traced(be.prefill_program(bucket))
        assert compiler == 0 and tiled >= 2 and kernels == tiled
