"""SDAR-30B-A3B-Chat on the serve path, at a small size on the CPU (hidden
64, 4 query heads over 2 KV heads of 16, 8 experts top-2, 3 layers, a
vocabulary of 96, blocks of 4 through 4 denoise passes, the first of which
commits the block before).

What they hold: (a) the program's whole-sequence forward agrees with the
plain float32 reference (``benchmark/reference/sdar.py``, which imports
nothing of the program); (b) ``ServeEngine`` generates, for every ``n % 4``
and ``m % 4``, several slots out of step and admissions in mid-launch, the
reference's tokens in the reference's reveal order, and the reference's
logits at the served states (``denoise_logits``) put the served tokens first;
(c) the cache holds the reference's keys and values of the clean tokens of
every block but a reply's last, and not a denoise pass's: with the first half
of the fused pass dropped the test fails; a slot's first round writes nothing
below its position; however the rounds are chopped into launches, the tokens
and their reveal passes are the same; (d) QK-norm, the block-causal prefill and the ``q = L`` decode each
against a direct ``jax.numpy`` expression; (e) the plain and speculative
rounds' own tests are elsewhere and untouched; (f) the paths that decode one
stacked block a token a step refuse the model by name.
"""

import copy
import math
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
from pipe_tpu.inference.generate import (most_confident,  # noqa: E402
                                         sample_with_confidence)
from pipe_tpu.models.sdar import PipelinedSdar, SdarConfig  # noqa: E402
from pipe_tpu.obs.telemetry import get_registry  # noqa: E402
from pipe_tpu.ops.layers import (MultiHeadAttention, RMSNorm,  # noqa: E402
                                 apply_rope, blocked_causal_attention,
                                 rope_frequencies, unfold_heads)
from pipe_tpu.serve import (BucketSpec, RequestQueue, ServeEngine,  # noqa: E402
                            SingleDeviceSlotBackend)
from pipe_tpu.serve import engine as engine_mod  # noqa: E402

FAMILY = pb_core.load_by_path("families/sdar.py")
REF = FAMILY.reference
TOL = 2e-4          # float32 against float32 `highest`, three layers deep
L = T = 4
COUNTS = ("blocks", "denoise_passes", "commit_passes", "tokens",
          "cut_tokens", "fused_commits")


def tiny_cfg(**over):
    """The cell's configuration file at the small size."""
    cfg = copy.deepcopy(pb_core.read_json(os.path.join(
        BENCH, "configs", "sdar-30b-a3b-chat.json")))
    cfg.update(vocab=96, vocab_size=96, hidden_size=64, head_dim=16,
               num_attention_heads=4, num_key_value_heads=2,
               moe_intermediate_size=32, num_experts=8,
               num_experts_per_tok=2, n_layers=3, compute_dtype="float32")
    cfg["published"] = dict(cfg["published"], num_experts=8)
    cfg["generation"] = dict(cfg["generation"], mask_token_id=95)
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_cfg()
    weights = REF.make_weights(cfg, 5)
    return cfg, weights, FAMILY.build_model(cfg, 1)


def backend(tiny, slots=3, resident_chunks=3, **kw):
    cfg, weights, model = tiny
    return SingleDeviceSlotBackend(
        model, FAMILY.serve_params(weights), num_slots=slots,
        max_len=32 + 24,
        gen=GenerationConfig(max_new_tokens=24, temperature=0.0),
        buckets=BucketSpec.pow2(min_len=8, max_len=32), decode_chunk=1,
        resident=True, resident_chunks=resident_chunks, **kw)


def test_the_family_builds_the_model_whole(tiny):
    cfg, weights, model = tiny
    import dataclasses
    assert model.cfg == dataclasses.replace(SdarConfig().tiny(),
                                            max_positions=32768)
    assert model.generation == ("block_diffusion", 4, 4, 95)
    (group,) = model.layer_groups()
    assert (group.n, group.cache, group.first) == (3, "full", 0)
    attn = group.block.attn
    assert (attn.nhead, attn.kv_heads, attn.group, attn.block,
            attn.qk_norm) == (4, 2, 2, 4, 1e-6)
    params = FAMILY.serve_params(weights)
    assert params[0][0][0]["moe"]["w_gate"] is weights["layers"]["e_gate"]
    assert model.num_params(params) == REF.num_params(cfg)
    own = model.init(jax.random.key(0))
    assert jax.tree_util.tree_structure(own) == \
        jax.tree_util.tree_structure(params)
    # every expert of a layer is held: nothing is absent
    assert model.cfg.experts_held == (0, cfg["num_experts"])


# (a) -----------------------------------------------------------------------


def test_whole_sequence_forward_agrees_with_the_reference(tiny):
    cfg, weights, model = tiny
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        1, 95, size=(2, 40)), jnp.int32)
    want = REF.forward(weights, tokens, cfg)
    got = jax.jit(model.forward)(FAMILY.serve_params(weights), tokens)
    assert got.shape == (2, 40, 96) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)
    # block-causal, not causal: a block's first position sees its last
    other = tokens.at[:, 7].set((tokens[:, 7] + 1) % 95)
    moved = np.abs(np.asarray(REF.forward(weights, other, cfg) - want))
    assert moved[:, :4].max() == 0 and moved[:, 4].max() > 1e-3


@pytest.mark.parametrize("fault", REF.FAULTS[:2] + ("no_renorm", "fp8"))
def test_a_planted_fault_or_a_lower_precision_moves_the_logits(tiny, fault):
    cfg, weights, _ = tiny
    tokens = jnp.asarray(np.random.default_rng(1).integers(
        1, 95, size=(2, 40)), jnp.int32)
    want = REF.forward(weights, tokens, cfg)
    if fault == "fp8":
        got = REF.forward(weights, tokens, cfg, precision="fp8")
    else:
        got = REF.forward(weights, tokens, dict(cfg, fault=fault))
    assert float(jnp.abs(got - want).max()) > 20 * TOL
    with pytest.raises(ValueError):
        REF.forward(weights, tokens, dict(cfg, fault="no_such_fault"))


# (b) -----------------------------------------------------------------------

# every n % 4 and m % 4; more requests than slots, so that slots run out of
# step and admissions land between launches
SHAPES = [(9, 7), (12, 8), (6, 5), (17, 10), (8, 4), (11, 13), (3, 6),
          (14, 3), (20, 9), (5, 12), (16, 1), (7, 2)]


@pytest.fixture(scope="module")
def served(tiny):
    cfg, weights, _ = tiny
    reg = get_registry()
    before = {k: reg.counter("serve.diffusion." + k).value for k in COUNTS}
    before["engine"] = reg.counter("serve.engine.tokens").value
    before["admitted"] = reg.counter("serve.engine.admitted").value
    be = backend(tiny)
    eng = ServeEngine(be, RequestQueue(capacity=16))
    rng = np.random.default_rng(1)
    sent = {}
    slot_passes = 0
    for n, m in SHAPES:
        prompt = rng.integers(1, 95, size=n).tolist()
        sent[eng.submit(prompt, max_new_tokens=m).id] = (prompt, m)
    out = {}
    while not eng.idle:
        steps0 = reg.counter("serve.engine.decode_steps").value
        live = sum(1 for s in eng._slots if s is not None) + min(
            len(eng._free), eng.queue.depth)
        for r in eng.tick():
            out[r.request_id] = r
        slot_passes += live * (
            reg.counter("serve.engine.decode_steps").value - steps0)
    grew = {k: reg.counter("serve.diffusion." + k).value - before[k]
            for k in COUNTS}
    grew["engine"] = reg.counter("serve.engine.tokens").value \
        - before["engine"]
    grew["admitted"] = reg.counter("serve.engine.admitted").value \
        - before["admitted"]
    grew["slot_passes"] = slot_passes
    return sent, out, grew, be


def test_the_engine_generates_the_references_tokens_in_its_order(tiny,
                                                                 served):
    cfg, weights, _ = tiny
    sent, out, _, _ = served
    assert {len(p) % 4 for p, _ in sent.values()} == {0, 1, 2, 3}
    assert {m % 4 for _, m in sent.values()} == {0, 1, 2, 3}
    for rid, (prompt, m) in sent.items():
        r = out[rid]
        tokens, order, _ = REF.generate(weights, prompt, m, cfg)
        assert r.status == "ok" and r.finish_reason == "length"
        assert r.tokens == tokens, (len(prompt), m)
        assert r.reveal_pass == order, (len(prompt), m)
        assert r.ttft is not None and 0 < r.ttft <= r.latency


def test_the_references_logits_at_the_served_states_put_the_served_first(
        tiny, served):
    """Every pass's logits, replayed: at the state before the pass that
    revealed a token, the reference's logits of that position put the
    served token first (within the tolerance), and the position it revealed
    is the one the reference is surest of."""
    cfg, weights, _ = tiny
    sent, out, _, _ = served
    seen = 0
    for rid, (prompt, m) in sent.items():
        r, n = out[rid], len(prompt)
        whole = (n + m) // L * L - n
        if whole <= 0:
            continue
        seq, rev = prompt + r.tokens[:whole], r.reveal_pass[:whole]
        first = n // L * L
        logits = np.asarray(REF.denoise_logits(
            weights, REF.kept_tokens(seq, n, rev, cfg),
            REF.noisy_states(seq, n, rev, first, cfg), first, cfg))
        for j, (tok, t) in enumerate(zip(seq[n:], rev)):
            row = logits[t, n - first + j]
            assert row.max() - row[tok] <= TOL
            seen += 1
    assert seen > 40


def test_the_counts_add_up(served):
    sent, out, grew, be = served
    tails = sum(len(p) % L for p, _ in sent.values())
    kept = sum(len(r.tokens) for r in out.values())
    assert kept == sum(m for _, m in sent.values())
    assert grew["tokens"] == L * grew["blocks"] - tails
    assert grew["tokens"] - grew["cut_tokens"] == kept == grew["engine"]
    # no pass runs for a commit alone: a block's commit rides the first
    # denoise pass of the round after it, whichever launch that round falls
    # in, and a reply's last block has none
    assert grew["commit_passes"] == 0
    assert grew["fused_commits"] == grew["blocks"] - len(sent)
    # a first block whose prompt ends r positions inside it takes r denoise
    # passes fewer (at L = T)
    assert grew["denoise_passes"] == T * grew["blocks"] - tails
    assert grew["denoise_passes"] == grew["slot_passes"] - tails
    # no first token comes from a prefill: every token is a launch's
    assert grew["admitted"] == len(sent)
    assert set(be.launch_counts) >= set(COUNTS) | {
        "expert_rows", "experts_touched", "full_rows_read", "absent_rows"}
    assert be.launch_counts["absent_rows"] == 0
    assert get_registry().counter("serve.moe.absent_rows").value == 0


def test_one_decode_program_and_no_retrace(tiny, served):
    be = served[3]
    assert list(be._resident_jits) == [L]
    assert be.round_passes == T and be.decode_width == L
    fn, args = be.decode_program()
    assert fn._cache_size() == 1
    text = open(engine_mod.__file__).read()
    assert text.count("def _decode_program(") == 1
    assert text.count("lax.while_loop(") == 1
    assert text.count("_Round(") == 4          # the class and three rounds


def test_sampled_generation_is_the_seeds_and_counts_alike(tiny):
    cfg, weights, model = tiny

    def serve(seed):
        be = SingleDeviceSlotBackend(
            model, FAMILY.serve_params(weights), num_slots=2, max_len=32,
            gen=GenerationConfig(max_new_tokens=12, temperature=0.8,
                                 top_k=20),
            decode_chunk=1, resident=True, resident_chunks=2)
        eng = ServeEngine(be, RequestQueue(capacity=4))
        eng.submit([3, 9, 27, 81, 50], max_new_tokens=10, seed=seed)
        (r,) = eng.run_until_idle()
        return r

    a, b, c = serve(7), serve(7), serve(8)
    assert a.tokens == b.tokens and a.reveal_pass == b.reveal_pass
    assert a.tokens != c.tokens and len(c.tokens) == 10
    assert sorted(a.reveal_pass[:3]) == [0, 1, 2]     # the first block


# (c) -----------------------------------------------------------------------


def _cache_rows(be, slot, n):
    """Rows ``0 .. n - 1`` of every layer of ``slot``: ``{"k", "v"} [layers,
    n, Hkv, D]``."""
    return {name: np.asarray(unfold_heads(a[:, slot, :n], 2, 16))
            for name, a in be._caches["full"].items()}


def _reference_rows(weights, tokens, cfg):
    """The reference's keys (after QK-norm and the rotary) and values of
    ``tokens`` in every layer, by its own pieces."""
    mm = REF._mm("f32")
    eps = cfg["rms_norm_eps"]
    s = len(tokens)
    where = (jnp.arange(s), jnp.zeros(s, jnp.int32))
    x = jnp.take(weights["embed"], jnp.asarray(tokens)[None], axis=0)
    ks, vs = [], []
    for i in range(cfg["n_layers"]):
        p = {n: a[i] for n, a in weights["layers"].items()
             if not n.startswith("e_")}
        a = REF._rms_norm(x, p["ln1_g"], eps)
        k = mm("bsd,de->bse", a, p["wk"]).reshape(1, s, 2, 16)
        ks.append(REF._rope(REF._rms_norm(k, p["gk"], eps), where[0],
                            float(cfg["rope_theta"]))[0])
        vs.append(mm("bsd,de->bse", a, p["wv"]).reshape(1, s, 2, 16)[0])
        x = REF._one_layer(x, weights["layers"], i, *where,
                           cfg_key=REF._cfg_key(cfg), precision="f32")
    return {"k": np.stack(ks), "v": np.stack(vs)}


def _serve_one(tiny, prompt, m, resident_chunks=3):
    be = backend(tiny, slots=1, resident_chunks=resident_chunks)
    eng = ServeEngine(be, RequestQueue(capacity=4))
    eng.submit(prompt, max_new_tokens=m)
    (r,) = eng.run_until_idle()
    return be, r


@pytest.mark.parametrize("resident_chunks", [1, 3])
def test_the_cache_keeps_the_commit_passs_rows(tiny, monkeypatch,
                                               resident_chunks):
    """Every block but a reply's last has the clean tokens' keys and values
    in the cache once the next round's first pass has run; the last block,
    which no round follows, keeps its last denoise pass's."""
    cfg, weights, _ = tiny
    # 14 rows asked: the reply ends inside the fourth block, which the
    # engine finishes all the same, and never commits
    prompt, m = [5, 17, 44, 80, 2, 61, 33], 7
    be, r = _serve_one(tiny, prompt, m, resident_chunks)
    tokens, order, _ = REF.generate(weights, prompt, m + 2, cfg)
    assert r.tokens == tokens[:m] and len(prompt) + len(tokens) == 16
    seq = prompt + tokens                      # the last block whole
    want = _reference_rows(weights, seq, cfg)
    got = _cache_rows(be, 0, len(seq))
    for name in ("k", "v"):
        np.testing.assert_allclose(got[name][:, :12], want[name][:, :12],
                                   atol=TOL)
    # a denoise pass's rows are another thing: the state before the last
    # pass holds the mask token where that pass revealed
    noisy = REF.kept_tokens(seq, len(prompt), order,
                            dict(cfg, fault="no_commit"))
    assert (noisy != np.asarray(seq)).sum() == 2    # blocks 2 and 3
    stale = _reference_rows(weights, noisy, cfg)
    assert np.abs(stale["k"][:, 8:12] - want["k"][:, 8:12]).max() > 100 * TOL
    # the last block's are what the cache is left with: over the clean
    # blocks before it
    last = _reference_rows(weights, list(seq[:12]) + list(noisy[12:]), cfg)
    for name in ("k", "v"):
        np.testing.assert_allclose(got[name][:, 12:], last[name][:, 12:],
                                   atol=TOL)
    assert np.abs(got["k"][:, 12:] - want["k"][:, 12:]).max() > 100 * TOL
    # and stale rows are what every block would keep if the first half of
    # the fused pass were dropped: the cache as the denoise passes left it,
    # and other tokens from the third block on
    layers = SingleDeviceSlotBackend._run_layers

    def no_commit(self, *a, lead=None, **kw):
        if lead is not None:
            lead = (lead[0], jnp.zeros_like(lead[1]))
        return layers(self, *a, lead=lead, **kw)

    monkeypatch.setattr(SingleDeviceSlotBackend, "_run_layers", no_commit)
    be2, r2 = _serve_one(tiny, prompt, m, resident_chunks)
    got2 = _cache_rows(be2, 0, len(seq))
    assert np.abs(got2["k"][:, 8:12] - want["k"][:, 8:12]).max() > 100 * TOL
    assert r2.tokens != r.tokens and r2.tokens[:1] == r.tokens[:1]


@pytest.mark.parametrize("n", [2, 4, 7, 12])
def test_a_first_round_writes_nothing_below_its_position(tiny, n):
    """Nothing awaits a commit in a slot's first round after its admission:
    the prompt's whole blocks are the prefill's, bit for bit, after it (a
    prompt shorter than a block has none, and a write at ``pos - L`` would
    have been clamped over row 0)."""
    cfg, weights, _ = tiny
    be = backend(tiny, slots=2, resident_chunks=1)
    prompt = np.random.default_rng(n).integers(1, 95, size=n).tolist()
    be.prefill(1, prompt, seed=0)
    whole = n // L * L
    masked, awaits = be._hist
    assert int(be._pos[1]) == whole and not bool(awaits[1])
    assert np.asarray(masked[1]).tolist() == [j >= n - whole
                                              for j in range(L)]
    before = {k: np.asarray(a[:, 1]) for k, a in be._caches["full"].items()}
    toks, valid = be.decode(np.asarray([False, True]),
                            budgets=np.asarray([0, 8], np.int32))
    tokens, order, _ = REF.generate(weights, prompt, L - n % L, cfg)
    assert toks[1][valid[1]].tolist() == tokens
    assert be.launch_notes[1][valid[1]].tolist() == order
    after = {k: np.asarray(a[:, 1]) for k, a in be._caches["full"].items()}
    for k in before:
        np.testing.assert_array_equal(after[k][:, :whole],
                                      before[k][:, :whole])
        assert np.abs(after[k][:, whole:whole + L]).max() > 0
        # and nothing behind the block
        np.testing.assert_array_equal(after[k][:, whole + L:],
                                      before[k][:, whole + L:])
    # the block now awaits; the slot that took no part has nothing
    assert np.asarray(be._hist[1]).tolist() == [False, True]
    assert be.launch_counts["fused_commits"] == 0
    assert be.launch_counts["commit_passes"] == 0
    assert be.launch_counts["denoise_passes"] == L - n % L
    # the round after commits it in its first pass
    be.decode(np.asarray([False, True]), budgets=np.asarray([0, 4], np.int32))
    assert be.launch_counts["fused_commits"] == 1
    seq = prompt + tokens
    want = _reference_rows(weights, seq, cfg)
    got = _cache_rows(be, 1, len(seq))
    for name in ("k", "v"):
        np.testing.assert_allclose(got[name], want[name], atol=TOL)


# a prompt shorter than a block (pos 0), every n % L, a reply that ends
# inside its last block or on its edge, a reply of one block: more requests
# than slots, so that an admission lands in one slot between two launches
# while the other's block awaits its commit
CHOPPED = [(3, 9), (2, 3), (8, 19), (9, 7), (10, 6), (11, 5), (1, 11),
           (16, 22), (13, 4)]


@pytest.fixture(scope="module")
def chopped(tiny):
    """The same requests through launches of one round and of up to eight:
    ``{resident_chunks: (replies in the order sent, admissions that landed
    while another slot's block awaited its commit)}``."""
    cfg, weights, _ = tiny
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 95, size=n).tolist() for n, _ in CHOPPED]
    got = {}
    for chunks in (1, 8):
        be = backend(tiny, slots=2, resident_chunks=chunks)
        eng = ServeEngine(be, RequestQueue(capacity=16))
        ids = [eng.submit(p, max_new_tokens=m).id
               for p, (_, m) in zip(prompts, CHOPPED)]
        out, beside = {}, 0
        while not eng.idle:
            awaits = np.asarray(be._hist[1])
            held = [None if s is None else s.req.id for s in eng._slots]
            for r in eng.tick():
                out[r.request_id] = r
            now = [None if s is None else s.req.id for s in eng._slots]
            for i in range(2):       # admitted beside a reply under way
                if now[i] is not None and now[i] != held[i] \
                        and awaits[1 - i] and held[1 - i] is not None:
                    beside += 1
        got[chunks] = ([out[i] for i in ids], beside)
    return prompts, got


@pytest.mark.parametrize("resident_chunks", [1, 8])
@pytest.mark.parametrize("case", range(len(CHOPPED)),
                         ids=["n%d-m%d" % c for c in CHOPPED])
def test_the_fused_round_gives_the_references_tokens_in_its_order(
        tiny, chopped, case, resident_chunks):
    cfg, weights, _ = tiny
    prompts, got = chopped
    (n, m), prompt = CHOPPED[case], prompts[case]
    r = got[resident_chunks][0][case]
    tokens, order, _ = REF.generate(weights, prompt, m, cfg)
    assert r.status == "ok" and r.finish_reason == "length"
    assert r.tokens == tokens
    assert r.reveal_pass == order


def test_an_awaiting_block_survives_a_launchs_end_and_an_admission(chopped):
    """However the rounds are chopped into launches: the same tokens and
    reveal passes, through admissions into the other slot between two
    launches of a slot whose block awaits its commit."""
    _, got = chopped
    one, eight = got[1], got[8]
    assert [r.tokens for r in one[0]] == [r.tokens for r in eight[0]]
    assert [r.reveal_pass for r in one[0]] == \
        [r.reveal_pass for r in eight[0]]
    assert one[1] >= 3 and eight[1] >= 1


# (d) -----------------------------------------------------------------------


def _attention():
    return MultiHeadAttention(32, 4, causal=True, kv_heads=2, head_dim=8,
                              bias=False, rope={"theta": 1e6},
                              qk_norm=1e-6, block=4)


def _direct(attn, params, x, block):
    """The attention over a whole sequence, written out."""
    b, s, _ = x.shape
    q = (x @ params["wq"]).reshape(b, s, 4, 8)
    k = (x @ params["wk"]).reshape(b, s, 2, 8)
    v = (x @ params["wv"]).reshape(b, s, 2, 8)
    q = q * jax.lax.rsqrt(jnp.mean(q * q, -1, keepdims=True) + 1e-6) \
        * params["gq"]
    k = k * jax.lax.rsqrt(jnp.mean(k * k, -1, keepdims=True) + 1e-6) \
        * params["gk"]
    inv, _ = rope_frequencies(8, theta=1e6)
    pos = jnp.arange(s)[None]
    q, k = apply_rope(q, pos, inv), apply_rope(k, pos, inv)
    k, v = jnp.repeat(k, 2, axis=2), jnp.repeat(v, 2, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(8)
    i, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    w = jax.nn.softmax(jnp.where(j // block <= i // block, scores, -1e30),
                       axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, s, 32) \
        @ params["wo"]


def test_qk_norm_block_causal_prefill_and_block_decode_against_jnp():
    attn = _attention()
    key = jax.random.key(3)
    params = attn.init(key, jnp.zeros((1, 1, 32)))
    assert params["gq"].shape == params["gk"].shape == (8,)
    params = dict(params, gq=jnp.linspace(0.5, 1.5, 8),
                  gk=jnp.linspace(1.5, 0.5, 8))
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 32))
    want = _direct(attn, params, x, 4)
    # QK-norm and the block-causal mask in the whole-sequence forward
    out, rows = attn.prefill(params, x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(attn.apply(params, x)),
                               np.asarray(want), atol=1e-5)
    plain = MultiHeadAttention(32, 4, causal=True, kv_heads=2, head_dim=8,
                               bias=False, rope={"theta": 1e6})
    assert float(jnp.abs(plain.apply(params, x) - want).max()) > 1e-2
    # the rows as a cache keeps them: normed and turned
    k = (x @ params["wk"]).reshape(2, 16, 2, 8)
    k = RMSNorm(1e-6).apply({"g": params["gk"]}, k)
    np.testing.assert_allclose(
        np.asarray(rows["k"]),
        np.asarray(apply_rope(k, jnp.arange(16)[None],
                              rope_frequencies(8, theta=1e6)[0])), atol=1e-6)
    # the q = L decode: block 3's rows over the cache of blocks 0-2, in the
    # batch form and in the slab form, under the all-ones mask
    ones = np.ones((4, 4), bool)
    cache = {n: jnp.zeros((2, 24, 2, 8)).at[:, :12].set(rows[n][:, :12])
             for n in rows}
    got, cache = attn.decode(params, x[:, 12:], cache, 12, tree=ones)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 12:]),
                               atol=1e-5)
    slab = attn.make_slab(1, 2, 24)
    slab = {n: slab[n].at[0, :, :12].set(attn.seat(rows[n][:, :12], 12))
            for n in rows}
    got, slab = attn.decode(params, x[:, 12:], slab, jnp.asarray([12, 12]),
                            tree=ones, layer=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want[:, 12:]),
                               atol=1e-5)
    with pytest.raises(ValueError, match="under tree="):
        attn.decode(params, x[:, 12:], cache, 12)
    with pytest.raises(ValueError, match="takes no window"):
        blocked_causal_attention(rows["k"], rows["k"], rows["v"], block=4,
                                 window=8)


def test_two_blocks_decode_in_one_pass_and_the_first_is_written_on_leave():
    """The fused pass's attention: ``2L`` rows at ``pos - L`` under the block
    lower-triangular mask are each block's own pass; ``lead`` says whose
    first block the cache takes, and a first block before row 0 is no
    write."""
    attn = _attention()
    key = jax.random.key(4)
    params = attn.init(key, jnp.zeros((1, 1, 32)))
    x = jax.random.normal(jax.random.fold_in(key, 1), (2, 16, 32))
    want = _direct(attn, params, x, 4)
    _, rows = attn.prefill(params, x)
    both = np.kron(np.tril(np.ones((2, 2), bool)), np.ones((4, 4), bool))
    # slot 0: blocks 0-1 cached, block 2 rides; slot 1: blocks 0-2 cached,
    # nothing rides, and what it is handed for block 2 is junk
    slab = attn.make_slab(1, 2, 24)
    slab = {n: slab[n].at[0, 0, :8].set(attn.seat(rows[n][:1, :8], 8)[0])
            .at[0, 1, :12].set(attn.seat(rows[n][1:, :12], 12)[0])
            for n in rows}
    xin = x[:, 8:].at[1, :4].set(7.0)
    got, after = attn.decode(params, xin, slab, jnp.asarray([8, 8]),
                             tree=both, layer=0,
                             lead=(4, jnp.asarray([True, False])))
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0, 8:]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[1, 4:]),
                               np.asarray(want[1, 12:]), atol=1e-5)
    for n in rows:
        seated = np.asarray(attn.seat(rows[n], 16))        # [2, 16, C]
        np.testing.assert_allclose(np.asarray(after[n][0, :, :16]), seated,
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(after[n][0, 1, :12]),
                                      np.asarray(slab[n][0, 1, :12]))
        assert not np.asarray(after[n][0, :, 16:]).any()
    # a first block at pos 0: its junk half lies before row 0 and no start
    # is clamped over the block's own rows
    empty = attn.make_slab(1, 2, 24)
    got, after = attn.decode(
        params, jnp.concatenate([xin[:, :4], x[:, :4]], axis=1), empty,
        jnp.asarray([-4, -4]), tree=both, layer=0,
        lead=(4, jnp.asarray([False, False])))
    np.testing.assert_allclose(np.asarray(got[:, 4:]),
                               np.asarray(want[:, :4]), atol=1e-5)
    for n in rows:
        np.testing.assert_allclose(
            np.asarray(after[n][0, :, :4]),
            np.asarray(attn.seat(rows[n][:, :4], 4)), atol=1e-6)
        assert not np.asarray(after[n][0, :, 4:]).any()
    with pytest.raises(ValueError, match="lead="):
        attn.decode(params, xin, {n: jnp.zeros((2, 24, 2, 8)) for n in rows},
                    8, tree=both, lead=(4, jnp.asarray([True, False])))
    with pytest.raises(ValueError, match="lead="):
        attn.decode(params, xin, slab, jnp.asarray([8, 8]), tree=both,
                    layer=0, lead=(5, jnp.asarray([True, False])))


def test_the_reveal_takes_the_surest_masked_positions():
    conf = jnp.asarray([[0.1, 0.9, 0.5, 0.7], [0.3, 0.3, 0.2, 0.9]])
    masked = jnp.asarray([[True, True, True, False],
                          [True, True, True, False]])
    got = most_confident(conf, masked, jnp.asarray([2, 1]))
    assert got.tolist() == [[False, True, True, False],
                            [True, False, False, False]]   # a tie: earlier
    assert REF.reveal([0.3, 0.3, 0.2, 0.9], [1, 1, 1, 0], 3).tolist() == \
        [True, False, False, False]
    ids, c = sample_with_confidence(
        jnp.log(jnp.asarray([[0.7, 0.2, 0.1]])), None,
        GenerationConfig(temperature=0.0))
    assert ids.tolist() == [0] and float(c[0]) == pytest.approx(0.7)


# (f) -----------------------------------------------------------------------


def _sdar():
    model = PipelinedSdar(SdarConfig().tiny(), 1)
    return model, model.init(jax.random.key(0))


def _refused_by_the_pool():
    model, params = _sdar()
    SingleDeviceSlotBackend(model, params, num_slots=2, max_len=32,
                            kv_block_size=8)


def _refused_by_spec_rounds():
    model, params = _sdar()
    SingleDeviceSlotBackend(model, params, num_slots=2, max_len=32,
                            spec_tokens=3)


def _refused_by_the_ring():
    from pipe_tpu.parallel.mesh import make_mesh
    from pipe_tpu.serve import RingSlotBackend
    model, (stages, pre, post) = _sdar()
    RingSlotBackend(make_mesh(1, 1, devices=jax.devices()[:1]), model,
                    stages, pre, post, max_len=32)


def _refused_by_the_pipelined_generator():
    from pipe_tpu.inference.pipelined import PipelinedGenerator
    from pipe_tpu.parallel.mesh import make_mesh
    PipelinedGenerator(make_mesh(1, 1, devices=jax.devices()[:1]),
                       _sdar()[0])


@pytest.mark.parametrize("build,who", [
    (_refused_by_the_pool, "_PoolStore"),
    (_refused_by_spec_rounds, "_spec_round"),
    (_refused_by_the_ring, "serve/ring.py"),
    (_refused_by_the_pipelined_generator, "inference/pipelined.py")])
def test_a_path_that_decodes_a_token_a_step_refuses_the_model(build, who):
    with pytest.raises(NotImplementedError) as err:
        build()
    text = str(err.value)
    assert "PipelinedSdar" in text and who in text
    assert "diffusion over blocks" in text
    assert text.count(". ") == 0 and text.count(":") == 1   # one sentence


def test_one_stage_one_block_a_round_and_whole_blocks_in_the_cache(tiny):
    with pytest.raises(ValueError, match="one stage"):
        PipelinedSdar(SdarConfig().tiny(), 2)
    model, params = _sdar()
    with pytest.raises(NotImplementedError, match="served, not trained"):
        model.stage_fn([], None, None)
    with pytest.raises(ValueError, match="decode_chunk must be 1"):
        SingleDeviceSlotBackend(model, params, num_slots=2, max_len=32,
                                decode_chunk=4)
    be = SingleDeviceSlotBackend(model, params, num_slots=2, max_len=32,
                                 gen=GenerationConfig(max_new_tokens=32))
    be.validate(20, 12)                      # 32 rows: the cache, whole
    with pytest.raises(ValueError, match="whole blocks of 4"):
        be.validate(20, 13)
    # a slot's rows are whole blocks: 30 asked are 32 held
    be30 = SingleDeviceSlotBackend(model, params, num_slots=2, max_len=30,
                                   gen=GenerationConfig(max_new_tokens=30))
    assert be30.max_len == 32 and be30._caches["full"]["k"].shape[2] == 32
    be30.validate(20, 12)
    with pytest.raises(ValueError, match="whole blocks of 4"):
        be30.validate(20, 13)


def test_the_serve_app_builds_the_model_by_name(capsys):
    from pipe_tpu.apps import serve as app
    rc = app.main(["--family", "sdar", "--tiny", "--requests", "5",
                   "--rate", "0", "--slots", "2", "--max-new", "6"])
    assert rc == 0, capsys.readouterr()
