"""The resident serve loop (PR 11): fused multi-chunk decode + spec lane.

Gold contract, layered on the serve suite's pins:

* **Parity.** R chunks in one launch equal R launches of one chunk:
  a resident backend — the decode program's `lax.while_loop` allowed up
  to ``resident_chunks`` chunks back-to-back on device — emits bitwise
  the tokens of ``resident=False``, the same program at a horizon of
  one chunk (the ring: its single-launch program), on both backends,
  slab and paged, greedy and sampled. The one-chunk horizon is pinned
  to the one-shot ``Generator`` by tests/test_serve.py, so the longer
  one inherits the gold contract transitively (and we re-assert it
  directly for greedy).
* **Zero steady-state recompiles.** The resident program traces exactly
  once across staggered arrivals and mixed prompt lengths
  (``serve.engine.resident_traces`` / ``serve.ring.resident_traces``).
* **The regather decision lives on device.** A steady-state tick (no
  prefill) makes ZERO host-driven gather decisions
  (``serve.kv.regather_host_decisions``), at every horizon.
* **``resident`` is a horizon and nothing else.** A backend used with
  and without ``budgets=`` traces its decode program once, and
  ``resident=False`` is bitwise ``resident=True, resident_chunks=1``.
* **Early exit.** The device loop exits before ``r_max`` when any live
  slot finishes (``serve.engine.device_exits``), so a freed slot waits
  at most one chunk, not a full horizon.
* **Speculative decode.** Every draft source — prompt-history n-gram,
  truncated-pipeline (first stage(s) + tied embedding head), and the
  multi-branch tree — emits bitwise the per-prompt ``Generator``
  tokens (draft rejection rolls back to exact greedy/sampled
  behaviour) while emitting MORE than one token per verify round on
  draftable text (``serve.engine.spec_emitted`` >
  ``serve.engine.spec_rounds``). The ring backend speaks the same
  contract: the Generator split key chain threads through the
  revolutions, so ring spec output is Generator-bitwise too, greedy
  AND sampled. Adaptive-K rung switches, one-token prompts (no draft
  history) and EOS landing mid-accepted-run all preserve the pin.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipe_tpu.inference import GenerationConfig, Generator
from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
from pipe_tpu.obs.telemetry import get_registry
from pipe_tpu.parallel.mesh import make_mesh
from pipe_tpu.parallel.spmd import stack_stage_params
from pipe_tpu.serve import (BucketSpec, RingSlotBackend, ServeEngine,
                            SingleDeviceSlotBackend)

CFG = LMConfig(vocab=89, d_model=32, nhead=4, d_ff=64, n_layers=4,
               seq_len=32, dropout=0.0)


@pytest.fixture(scope="module")
def model_and_params():
    model = PipelinedLM(CFG, n_stages=2)
    return model, model.init(jax.random.key(0))


def _one_shot_refs(model, params, prompts, gen_cfg, seed):
    g = Generator(model, gen_cfg)
    return [np.asarray(g.generate(params,
                                  jnp.asarray(p, jnp.int32)[None],
                                  jax.random.key(seed)))[0]
            for p in prompts]


def _mixed_prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, CFG.vocab, size=n)) for n in lengths]


def _make_backend(kind, model, params, gen_cfg, layout="slab",
                  max_len=16, **kw):
    """A backend with the resident knobs threaded per kind: the ring
    speaks ``resident_revolutions``, the single device
    ``resident_chunks``."""
    if layout == "paged":
        kw.setdefault("kv_block_size", 4)
        kw.setdefault("prefill_chunk", 4)
    else:
        kw.setdefault("buckets", BucketSpec.of(4, 8))
    if kind == "single":
        kw.setdefault("num_slots", 2)
        return SingleDeviceSlotBackend(model, params, max_len=max_len,
                                       gen=gen_cfg, **kw)
    if "resident_chunks" in kw:
        kw["resident_revolutions"] = kw.pop("resident_chunks")
    kw.pop("num_slots", None)
    sp, pre, post = params
    mesh = make_mesh(2, 1)
    return RingSlotBackend(mesh, model, stack_stage_params(sp), pre, post,
                           max_len=max_len, gen=gen_cfg, **kw)


def _drive_staggered(backend, prompts, seed):
    """Mid-flight arrivals: slot churn exercises relaunches with mixed
    budgets, not one clean batch."""
    eng = ServeEngine(backend)
    ids = [eng.submit(prompts[0], seed=seed).id]
    eng.tick()
    ids += [eng.submit(p, seed=seed).id for p in prompts[1:]]
    eng.run_until_idle()
    return [list(eng.response(r).tokens) for r in ids]


# ---------------------------------------------------------------------------
# parity: resident loop vs the single-chunk tick path


PARITY_CASES = [
    ("single", "slab", 0.0), ("single", "slab", 0.8),
    ("single", "paged", 0.0), ("single", "paged", 0.8),
    ("ring", "slab", 0.0), ("ring", "slab", 0.8),
    ("ring", "paged", 0.0), ("ring", "paged", 0.8),
]
PARITY_IDS = [f"{k}-{l}-{'greedy' if t == 0.0 else 'sampled'}"
              for k, l, t in PARITY_CASES]


@pytest.mark.parametrize("kind,layout,temp", PARITY_CASES, ids=PARITY_IDS)
def test_resident_matches_single_chunk_tick(kind, layout, temp,
                                            model_and_params):
    """R chunks in one launch equal R launches of one chunk, both of
    the same program: resident=True with a small horizon (forcing
    several launches) emits bitwise what resident=False does; greedy
    additionally re-pins the one-shot Generator directly."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=temp,
                               top_k=12 if temp else None)
    prompts = _mixed_prompts((3, 5, 4))

    base = _make_backend(kind, model, params, gen_cfg, layout,
                         resident=False)
    ref = _drive_staggered(base, prompts, seed=7)
    res = _make_backend(kind, model, params, gen_cfg, layout,
                        resident=True, resident_chunks=3)
    got = _drive_staggered(res, prompts, seed=7)
    assert got == ref
    if temp == 0.0:
        refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=7)
        for g, r in zip(got, refs):
            np.testing.assert_array_equal(np.asarray(g), r)


def test_resident_eos_retires_early(model_and_params):
    """Device-side eos done-masking: the resident loop retires at the
    EOS token with the same truncated output as the tick path."""
    model, params = model_and_params
    probe = GenerationConfig(max_new_tokens=8, temperature=0.0)
    prompts = _mixed_prompts((4, 6))
    free = _one_shot_refs(model, params, prompts, probe, seed=7)
    eos = int(free[0][2])   # a token greedy decoding actually emits

    gen_cfg = GenerationConfig(max_new_tokens=8, temperature=0.0,
                               eos_token_id=eos)
    base = _make_backend("single", model, params, gen_cfg,
                         resident=False)
    ref = _drive_staggered(base, prompts, seed=7)
    res = _make_backend("single", model, params, gen_cfg,
                        resident=True, resident_chunks=8)
    got = _drive_staggered(res, prompts, seed=7)
    assert got == ref
    assert any(t and t[-1] == eos for t in got)


# ---------------------------------------------------------------------------
# the trace pin + host-sync accounting


@pytest.mark.parametrize("kind", ["single", "ring"])
def test_resident_traces_once_and_counts_host_syncs(kind,
                                                    model_and_params):
    """The resident whole-program traces exactly once across staggered
    traffic and mixed prompt lengths, and every launch is one counted
    host sync feeding the host-overhead-per-token gauge."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompts = _mixed_prompts((3, 5, 4, 7, 5))
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=7)

    backend = _make_backend(kind, model, params, gen_cfg,
                            resident=True, resident_chunks=4)
    reg = get_registry()
    counter = ("serve.engine.resident_traces" if kind == "single"
               else "serve.ring.resident_traces")
    traces0 = reg.counter(counter).value
    syncs0 = reg.counter("serve.engine.host_syncs").value

    got = _drive_staggered(backend, prompts, seed=7)
    for g, r in zip(got, refs):
        np.testing.assert_array_equal(np.asarray(g), r)
    assert reg.counter(counter).value - traces0 == 1
    assert reg.counter("serve.engine.host_syncs").value - syncs0 >= 1
    assert reg.gauge("serve.engine.host_overhead_per_token").value >= 0.0


@pytest.mark.parametrize("resident", [True, False])
def test_regather_decision_stays_on_device(resident, model_and_params):
    """Paged: prefill arms the device regather flag (one host decision
    per admission); steady-state ticks make ZERO, whatever the horizon
    — the flag rides the one decode program's carry (``resident=False``
    used to decide once per tick on the host)."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    reg = get_registry()

    backend = _make_backend("single", model, params, gen_cfg, "paged",
                            resident=resident, resident_chunks=1)
    eng = ServeEngine(backend)
    d0 = reg.counter("serve.kv.regather_host_decisions").value
    eng.submit(_mixed_prompts((4,))[0], seed=7)
    eng.submit(_mixed_prompts((5,))[0], seed=7)
    eng.tick()          # prefills (arm the flag) + first launch
    assert reg.counter("serve.kv.regather_host_decisions").value - d0 == 2
    eng.tick()
    eng.tick()          # two steady-state ticks: no prefill
    assert reg.counter("serve.kv.regather_host_decisions").value - d0 == 2
    eng.run_until_idle()


def test_resident_early_exit_on_slot_free(model_and_params):
    """Backend unit: with budgets [2, many] and an 8-chunk horizon the
    device exits after chunk 2 (slot 0 done) — the readout is 2 chunks
    wide and the early-exit counter ticks."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    backend = _make_backend("single", model, params, gen_cfg,
                            resident=True, resident_chunks=8)
    backend.prefill(0, _mixed_prompts((4,))[0], seed=7)
    backend.prefill(1, _mixed_prompts((5,))[0], seed=7)
    reg = get_registry()
    exits0 = reg.counter("serve.engine.device_exits").value
    toks, valid = backend.decode(np.array([True, True]),
                                 budgets=np.array([2, 100], np.int32))
    assert toks.shape == (2, 2)
    assert valid.all()
    assert reg.counter("serve.engine.device_exits").value - exits0 == 1


# ---------------------------------------------------------------------------
# one decode program: ``resident`` is a horizon and nothing else


@pytest.mark.parametrize("layout", ["slab", "paged"])
def test_decode_traces_one_program_with_and_without_budgets(
        layout, model_and_params):
    """A backend used both ways — ``decode(live)``, then ``decode(live,
    budgets=...)`` — traces its decode program ONCE: the call without
    budgets is a launch of one chunk with no budget limit, not a second
    program. Both continue the Generator's chain."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompts = _mixed_prompts((4, 5))
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=7)
    backend = _make_backend("single", model, params, gen_cfg, layout,
                            resident=True, resident_chunks=3)
    first = [backend.prefill(s, p, seed=7) for s, p in enumerate(prompts)]
    reg = get_registry()
    names = ("serve.engine.decode_traces", "serve.engine.resident_traces")
    traces0 = sum(reg.counter(n).value for n in names)
    live = np.array([True, True])

    one, valid = backend.decode(live)
    assert one.shape == (2, 1) and valid.all()
    more, valid = backend.decode(live, budgets=np.array([4, 4], np.int32))
    assert more.shape == (2, 3) and valid.all()

    assert sum(reg.counter(n).value for n in names) - traces0 == 1
    for s, ref in enumerate(refs):
        np.testing.assert_array_equal(
            np.concatenate([[first[s]], one[s], more[s]]), ref[:5])


def test_resident_off_is_a_horizon_of_one_chunk(model_and_params):
    """``resident=False`` and ``resident=True, resident_chunks=1`` are
    the same backend: bitwise the same tokens, positions and key data
    over a staggered sampled run."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.8, top_k=12)
    prompts = _mixed_prompts((3, 5, 4))
    runs = []
    for kw in (dict(resident=False),
               dict(resident=True, resident_chunks=1)):
        backend = _make_backend("single", model, params, gen_cfg,
                                decode_chunk=2, **kw)
        assert backend.resident_chunks == 1
        tokens = _drive_staggered(backend, prompts, seed=7)
        runs.append((tokens, np.asarray(backend._tok),
                     np.asarray(backend._pos),
                     np.asarray(backend._key_data)))
    for a, b in zip(*runs):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# speculative decode: acceptance parity + rollback


SPEC_CASES = [("slab", 0.0), ("slab", 0.8), ("paged", 0.0),
              ("paged", 0.8)]
SPEC_IDS = [f"{l}-{'greedy' if t == 0.0 else 'sampled'}"
            for l, t in SPEC_CASES]


@pytest.mark.parametrize("layout,temp", SPEC_CASES, ids=SPEC_IDS)
def test_speculative_decode_matches_generator(layout, temp,
                                              model_and_params):
    """K-token draft/verify: responses are bitwise the per-prompt
    Generator output (rejections roll back exactly), and on draftable
    (repetitive) text the lane emits more than one token per verify
    round — the speedup the lane exists for."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=8, temperature=temp,
                               top_k=12 if temp else None)
    prompts = [[5, 6, 5, 6, 5, 6], [3, 3, 3, 3]]
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=11)

    backend = _make_backend("single", model, params, gen_cfg, layout,
                            max_len=24, resident=True,
                            resident_chunks=4, spec_tokens=3)
    reg = get_registry()
    rounds0 = reg.counter("serve.engine.spec_rounds").value
    emitted0 = reg.counter("serve.engine.spec_emitted").value

    got = _drive_staggered(backend, prompts, seed=11)
    for g, r in zip(got, refs):
        np.testing.assert_array_equal(np.asarray(g), r)
    rounds = reg.counter("serve.engine.spec_rounds").value - rounds0
    emitted = reg.counter("serve.engine.spec_emitted").value - emitted0
    # each response's first token comes from prefill, not the spec lane
    assert emitted >= sum(len(g) for g in got) - len(prompts)
    assert rounds > 0 and emitted > rounds   # acceptance rate > 0


# The last three since PR 29: every drafter on both carried caches (the
# slab and the paged views, rows last alike), so the views' gather and
# scatter, the tree's branch relocation and the truncated drafter's run
# through the engine's layer loop are each held to the Generator's tokens.
DRAFT_CASES = [
    ("truncated", None, "slab", 0.0), ("truncated", None, "paged", 0.8),
    ("tree", 2, "slab", 0.8), ("tree", 3, "paged", 0.0),
    ("truncated", None, "paged", 0.0), ("tree", 2, "paged", 0.8),
    ("tree", 3, "slab", 0.0),
]
DRAFT_IDS = [f"{d}{b or ''}-{l}-{'greedy' if t == 0.0 else 'sampled'}"
             for d, b, l, t in DRAFT_CASES]


@pytest.mark.parametrize("draft,branches,layout,temp", DRAFT_CASES,
                         ids=DRAFT_IDS)
def test_draft_sources_match_generator(draft, branches, layout, temp,
                                       model_and_params):
    """Model-based drafts: the truncated pipeline (stage 0 + tied
    embedding head) and the B-branch tree verified in ONE fixed-shape
    chunk under the causal tree mask both stay bitwise the Generator —
    acceptance changes throughput, never tokens."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=8, temperature=temp,
                               top_k=12 if temp else None)
    prompts = [[5, 6, 5, 6, 5, 6], [3, 3, 3, 3]]
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=11)

    backend = _make_backend("single", model, params, gen_cfg, layout,
                            max_len=24, resident=True,
                            resident_chunks=4, spec_tokens=3,
                            draft=draft, spec_branches=branches)
    reg = get_registry()
    rounds0 = reg.counter("serve.engine.spec_rounds").value
    emitted0 = reg.counter("serve.engine.spec_emitted").value
    got = _drive_staggered(backend, prompts, seed=11)
    for g, r in zip(got, refs):
        np.testing.assert_array_equal(np.asarray(g), r)
    rounds = reg.counter("serve.engine.spec_rounds").value - rounds0
    emitted = reg.counter("serve.engine.spec_emitted").value - emitted0
    assert rounds > 0 and emitted >= rounds
    if temp == 0.0:
        # greedy verify matches greedy draft often enough to accept; a
        # sampled verify on random weights legitimately accepts ~nothing
        assert emitted > rounds
    assert reg.gauge("serve.spec.draft_cost_frac").value > 0.0


RING_SPEC_CASES = [
    ("ngram", "slab", 0.0), ("ngram", "paged", 0.8),
    ("truncated", "slab", 0.8), ("truncated", "paged", 0.0),
]
RING_SPEC_IDS = [f"{d}-{l}-{'greedy' if t == 0.0 else 'sampled'}"
                 for d, l, t in RING_SPEC_CASES]


@pytest.mark.parametrize("draft,layout,temp", RING_SPEC_CASES,
                         ids=RING_SPEC_IDS)
def test_ring_speculative_matches_generator(draft, layout, temp,
                                            model_and_params):
    """Ring spec: the K-row wavefront chunk rides the ppermute message
    ring while stage n-1 verifies against the Generator split key chain
    — staggered arrivals (stale in-flight rounds discarded by the
    admission inequalities) still emit bitwise Generator tokens, greedy
    AND sampled."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=8, temperature=temp,
                               top_k=12 if temp else None)
    prompts = [[5, 6, 5, 6, 5, 6], [3, 3, 3, 3], [7, 8, 7, 8, 7]]
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=11)

    backend = _make_backend("ring", model, params, gen_cfg, layout,
                            max_len=24, resident=True,
                            resident_chunks=4, spec_tokens=3,
                            draft=draft)
    reg = get_registry()
    rounds0 = reg.counter("serve.engine.spec_rounds").value
    emitted0 = reg.counter("serve.engine.spec_emitted").value
    got = _drive_staggered(backend, prompts, seed=11)
    for g, r in zip(got, refs):
        np.testing.assert_array_equal(np.asarray(g), r)
    rounds = reg.counter("serve.engine.spec_rounds").value - rounds0
    emitted = reg.counter("serve.engine.spec_emitted").value - emitted0
    assert rounds > 0 and emitted >= rounds
    if temp == 0.0:
        assert emitted > rounds   # acceptance rate > 0 under greedy


def test_adaptive_k_shrink_grow_parity(model_and_params):
    """Per-slot acceptance-EWMA adaptive K: a draftable slot next to an
    adversarial one forces rung switches mid-stream; the rollback
    overwrite under a shrunk-then-regrown K stays bitwise the
    Generator, every rung comes from the pre-traced ladder (traces <=
    ladder rungs), and a second identical drive retraces NOTHING."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=10, temperature=0.8,
                               top_k=12)
    prompts = [[5, 6, 5, 6, 5, 6, 5, 6],        # draftable
               _mixed_prompts((7,), seed=3)[0]]  # adversarial
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=13)

    backend = _make_backend("single", model, params, gen_cfg,
                            max_len=32, resident=True,
                            resident_chunks=4, spec_tokens=4,
                            spec_adaptive=True)
    reg = get_registry()
    traces0 = reg.counter("serve.engine.resident_traces").value
    got = _drive_staggered(backend, prompts, seed=13)
    for g, r in zip(got, refs):
        np.testing.assert_array_equal(np.asarray(g), r)
    traced = reg.counter("serve.engine.resident_traces").value - traces0
    assert 1 <= traced <= len(backend._spec_ladder)
    # misses on the adversarial slot shrank its EWMA below the optimism
    # every request starts at
    assert backend._spec_ewma.min() < float(backend.spec_tokens)
    # warm steady state: the same traffic again traces zero new programs
    got2 = _drive_staggered(backend, prompts, seed=13)
    assert got2 == got
    assert reg.counter("serve.engine.resident_traces").value \
        - traces0 == traced


@pytest.mark.parametrize("kind", ["single", "ring"])
def test_spec_empty_history_slots(kind, model_and_params):
    """One-token prompts: the n-gram drafter has NO history to match
    and the truncated drafter extends a length-1 prefix — junk drafts
    must be rejected back to exact Generator output, never crash or
    corrupt the rollback."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompts = [[7], [3]]
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=5)
    for draft in ("ngram", "truncated"):
        backend = _make_backend(kind, model, params, gen_cfg,
                                max_len=24, resident=True,
                                resident_chunks=4, spec_tokens=3,
                                draft=draft)
        got = _drive_staggered(backend, prompts, seed=5)
        for g, r in zip(got, refs):
            np.testing.assert_array_equal(np.asarray(g), r)


@pytest.mark.parametrize("kind", ["single", "ring"])
def test_spec_eos_mid_accepted_run(kind, model_and_params):
    """EOS emitted in the MIDDLE of an accepted draft run: the response
    truncates exactly at EOS (tokens past it in the same round are
    dropped) and retires early, matching the Generator's own EOS
    masking."""
    model, params = model_and_params
    probe = GenerationConfig(max_new_tokens=8, temperature=0.0)
    prompts = [[5, 6, 5, 6, 5, 6], [3, 3, 3, 3]]
    free = _one_shot_refs(model, params, prompts, probe, seed=11)
    eos = int(free[0][3])   # a token greedy decoding actually emits

    gen_cfg = GenerationConfig(max_new_tokens=8, temperature=0.0,
                               eos_token_id=eos)
    refs = [r.tolist() for r in
            _one_shot_refs(model, params, prompts, gen_cfg, seed=11)]
    # the Generator pads past EOS; responses stop AT it
    refs = [r[:r.index(eos) + 1] if eos in r else r for r in refs]
    backend = _make_backend(kind, model, params, gen_cfg,
                            max_len=24, resident=True,
                            resident_chunks=4, spec_tokens=3)
    got = _drive_staggered(backend, prompts, seed=11)
    assert got == refs
    assert any(t and t[-1] == eos and len(t) < 8 for t in got)


# ---------------------------------------------------------------------------
# knob validation: loud rejections, not silent fallbacks


def test_resident_knob_validation(model_and_params):
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=4, temperature=0.0)
    with pytest.raises(ValueError, match="resident"):
        _make_backend("single", model, params, gen_cfg,
                      resident="yes")
    with pytest.raises(ValueError, match="resident_chunks"):
        _make_backend("single", model, params, gen_cfg,
                      resident=True, resident_chunks=0)
    with pytest.raises(ValueError, match="spec_tokens"):
        _make_backend("single", model, params, gen_cfg,
                      resident=True, spec_tokens=1)
    # the speculative round is a round of the one loop: any horizon
    # takes it (only the ring's wavefront is resident-only)
    assert _make_backend("single", model, params, gen_cfg, resident=False,
                         spec_tokens=3).resident_chunks == 1
    with pytest.raises(ValueError, match="resident"):
        _make_backend("ring", model, params, gen_cfg,
                      resident=False, spec_tokens=3)
    # draft knobs configure the spec lane — meaningless without it
    with pytest.raises(ValueError, match="speculative lane"):
        _make_backend("single", model, params, gen_cfg,
                      resident=True, draft="truncated")
    # the tree draft needs branches to fan out
    with pytest.raises(ValueError, match="spec_branches"):
        _make_backend("single", model, params, gen_cfg,
                      resident=True, spec_tokens=3, draft="tree")
    # the ring wavefront carries ONE linear K-row chunk per slot, so
    # the tree's branch fan-out and the adaptive ladder's shape switch
    # stay single-device; a ring draft deeper than stage 0 would need
    # layers that are not resident where the draft runs
    with pytest.raises(ValueError, match="single-device"):
        _make_backend("ring", model, params, gen_cfg, resident=True,
                      spec_tokens=3, draft="tree", spec_branches=2)
    with pytest.raises(ValueError, match="single-device"):
        _make_backend("ring", model, params, gen_cfg, resident=True,
                      spec_tokens=3, spec_adaptive=True)
    with pytest.raises(ValueError, match="STRICT prefix"):
        _make_backend("ring", model, params, gen_cfg, resident=True,
                      spec_tokens=3, draft="truncated", draft_stages=2)
    # ring spec decode is resident-only: budgets must ride the launch
    spec_ring = _make_backend("ring", model, params, gen_cfg,
                              resident=True, spec_tokens=3)
    with pytest.raises(ValueError, match="resident-only"):
        spec_ring.decode(np.array([True, False]))


def test_spec_headroom_tightens_validate(model_and_params):
    """spec_tokens=K writes K rows per verify round — K-1 rows of slack
    must stay below max_len or the fixed-shape write would clamp.
    validate() rejects at submit with the headroom named."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=8, temperature=0.0)
    backend = _make_backend("single", model, params, gen_cfg,
                            max_len=24, resident=True, spec_tokens=3,
                            buckets=BucketSpec.of(16))
    eng = ServeEngine(backend)
    with pytest.raises(ValueError, match="speculative headroom"):
        eng.submit(list(range(1, 16)), max_new_tokens=8)
    # the same request without the spec lane is servable
    plain = _make_backend("single", model, params, gen_cfg,
                          max_len=24, resident=True,
                          buckets=BucketSpec.of(16))
    ServeEngine(plain).submit(list(range(1, 16)), max_new_tokens=8)


# ---------------------------------------------------------------------------
# the slab in the carry: the slab form of block.decode, and where the
# decode programs keep the cache


def _tree_mask(K, B):
    from pipe_tpu.inference.draft import tree_layout
    return jnp.asarray(tree_layout(K, B)[1])


SLAB_FORM_CASES = [
    ("post_ln", 1, None), ("post_ln", 3, None), ("post_ln", 5, (3, 2)),
    ("pre_ln", 1, None), ("pre_ln", 3, None), ("pre_ln", 5, (3, 2)),
]


@pytest.mark.parametrize(
    "family,q,tree", SLAB_FORM_CASES,
    ids=[f"{f}-q{q}-{'tree' if t else 'linear'}"
         for f, q, t in SLAB_FORM_CASES])
def test_slab_form_matches_batch_form_over_slots(family, q, tree):
    """``block.decode(..., layer=l)`` on the stacked heads-folded
    ``[L, S, T, C]`` cache is the batch-1 form vmapped over the slots
    of layer ``l`` through a fold of the slab (the batch form keeps
    ``[b, T, H, D]``): the rows written bitwise, every other layer and
    the row's padding untouched, and the output to a float32 ulp or two
    (the slab form's sums run over the folded axis and gain exact
    zeros, which a backend may add up in another order) — per-slot
    positions all different, ``q`` 1 and ``q`` > 1, linear and tree."""
    from pipe_tpu.ops.layers import (PreLNBlock, TransformerEncoderLayer,
                                     fold_heads, slab_width, unfold_heads)
    L, S, T, d, nh = 3, 4, 16, 32, 4
    cls = TransformerEncoderLayer if family == "post_ln" else PreLNBlock
    block = cls(d, nh, 64, 0.0)
    ks = jax.random.split(jax.random.key(11), 4)
    x = jax.random.normal(ks[0], (S, q, d))
    bp = block.init(ks[1], x)
    assert slab_width(nh, d // nh) == 128 and slab_width(25, 64) == 1664
    slab = {n: fold_heads(jax.random.normal(k, (L, S, T, nh, d // nh)))
            for n, k in (("k", ks[2]), ("v", ks[3]))}
    assert slab["k"].shape == block.attn.make_slab(L, S, T)["k"].shape
    pos = jnp.asarray([0, 3, 7, 11], jnp.int32)     # 11 + 5 == T
    anc = _tree_mask(*tree) if tree else None

    def batch_form(x, slab, pos, l):
        def one(hh, cc, pp):
            out, cc2 = block.decode(
                bp, hh[None],
                jax.tree_util.tree_map(lambda a: a[None], cc), pp,
                tree=anc)
            return out[0], jax.tree_util.tree_map(lambda a: a[0], cc2)

        layer_l = jax.tree_util.tree_map(          # [S, T, H, D]
            lambda a: unfold_heads(a[l], nh, d // nh), slab)
        out, new_l = jax.vmap(one)(x, layer_l, pos)
        return out, jax.tree_util.tree_map(
            lambda a, n: a.at[l].set(fold_heads(n)), slab, new_l)

    def slab_form(x, slab, pos, l):
        return block.decode(bp, x, slab, pos, tree=anc, layer=l)

    for l in (0, L - 1):
        want = jax.jit(batch_form)(x, slab, pos, jnp.int32(l))
        got = jax.jit(slab_form)(x, slab, pos, jnp.int32(l))
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                                   rtol=0, atol=2e-6)
        for name in ("k", "v"):
            np.testing.assert_array_equal(np.asarray(got[1][name]),
                                          np.asarray(want[1][name]))
            assert not np.array_equal(np.asarray(got[1][name][l]),
                                      np.asarray(slab[name][l]))
            assert not np.asarray(got[1][name][..., d:]).any()


def _eqns(jaxpr, in_loop=False):
    """Every equation of a jaxpr, nested ones included, each with
    whether a ``scan`` or ``while`` body holds it (a ``cond`` or ``pjit``
    inside a loop body is inside the loop)."""
    for eqn in jaxpr.eqns:
        yield eqn, in_loop
        inside = in_loop or eqn.primitive.name in ("scan", "while")
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner, inside)


def _slab_program(name, backend):
    """(traced function, its arguments) of the slab backend's decode
    program. ``decode`` and ``resident`` are the one program (they were
    two); ``spec`` is the speculative round's rung."""
    return backend.decode_program()


SLAB_PROGRAMS = {
    "decode": {}, "resident": {}, "spec": {"spec_tokens": 3},
    "spec-truncated": {"spec_tokens": 3, "draft": "truncated"},
    "spec-tree": {"spec_tokens": 3, "draft": "tree", "spec_branches": 2},
}


@pytest.mark.parametrize("program", list(SLAB_PROGRAMS))
def test_slab_rides_the_layer_loops_carry(program, model_and_params):
    """The structural pin of the in-place cache: in every slab decode
    program the heads-folded ``[L, S, T, C]`` slab is a CARRY of the
    scan over layers, no scan anywhere takes it as a scanned input or
    gives it back as a stacked output — the round trip (slice a layer
    out, stack it back, copy the whole slab once a step) cannot return
    unseen — and no loop body transposes the slab or a layer of it: the
    carried layout is the one the two reads of a layer use. The
    truncated and tree drafters run the same loop over their first
    layers on the same carry; they neither slice the slab nor join it
    back, and the tree's branch relocation moves rows inside it."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    backend = _make_backend(
        "single", model, params, gen_cfg, resident=True,
        resident_chunks=2, **SLAB_PROGRAMS[program])
    slab_shape = backend._caches["k"].shape
    assert slab_shape == backend._caches["v"].shape == (
        CFG.n_layers, backend.num_slots, backend.max_len,
        128)                       # [L, S, T, C]: 4 heads of 8, one lane tile
    fn, args = _slab_program(program.split("-")[0], backend)
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    whole = (slab_shape, slab_shape[1:])
    eqns = list(_eqns(jaxpr))
    moved = [eqn for eqn, in_loop in eqns if in_loop and (
        (eqn.primitive.name == "transpose"
         and eqn.invars[0].aval.shape in whole)
        or (eqn.primitive.name == "concatenate"
            and eqn.outvars[0].aval.shape in whole))]
    assert not moved, f"a loop body transposes or rejoins the slab: {moved}"
    scans = [eqn for eqn, _ in eqns if eqn.primitive.name == "scan"]
    carried = 0
    for eqn in scans:
        nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
        xs = [v.aval.shape for v in eqn.invars[nc + nk:]]
        ys = [v.aval.shape for v in eqn.outvars[nk:]]
        assert slab_shape not in xs, "the slab is a scanned input"
        assert slab_shape not in ys, "the slab is a stacked output"
        carry = [v.aval.shape for v in eqn.invars[nc:nc + nk]]
        if eqn.params["length"] == CFG.n_layers and slab_shape in carry:
            assert carry.count(slab_shape) == 2          # k and v
            carried += 1
    assert carried >= 1, "no scan over layers carries the slab"


# ---------------------------------------------------------------------------
# tools/hlo_audit.py --slab: where a compiled program moves the slab about


def _hlo_audit():
    import importlib
    import os
    import sys
    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    if tools not in sys.path:
        sys.path.insert(0, tools)
    return importlib.import_module("hlo_audit")


@pytest.mark.parametrize("shape,want", [
    ("bf16[48,8,640,25,64]{4,3,2,1,0:T(8,128)(2,1)}", 48 * 8 * 640 * 32 * 128 * 2),
    ("bf16[48,8,640,25,64]{2,4,3,1,0:T(8,128)(2,1)}", 48 * 8 * 640 * 25 * 64 * 2),
    ("bf16[48,8,1600,640]{3,2,1,0:T(8,128)(2,1)}", 48 * 8 * 1600 * 640 * 2),
    ("bf16[48,8,1600,640]{2,3,1,0:T(8,128)(2,1)S(1)}", 48 * 8 * 1664 * 640 * 2),
    ("f32[8,25]{1,0:T(8,128)}", 8 * 128 * 4),
    ("s32[7,3]", 84),
], ids=["rows-first-padded", "rows-minor", "folded", "folded-heads-minor",
        "f32-tile", "no-layout"])
def test_hlo_audit_counts_a_tiled_layouts_bytes(shape, want):
    """The padding arithmetic PERF.md's layout findings rest on: PR 26's
    2.0 GB against 0.79 GB of one slab, read off a shape's layout."""
    assert _hlo_audit()._tiled_bytes(shape) == want


def test_hlo_audit_finds_slab_moves_in_and_around_loops():
    """``_slab_census`` on a hand-made module: a relayout of the whole
    slab before the ``while`` (PR 26's ``copy.33``), a layer-sized
    transpose inside a fusion its body calls, and nothing for arrays of
    another size or type."""
    hlo = """HloModule m

%fused.1 (p0: bf16[2,3,8,16]) -> bf16[3,16,8] {
  %p0 = bf16[2,3,8,16]{3,2,1,0} parameter(0)
  %sl = bf16[1,3,8,16]{3,2,1,0} slice(%p0), slice={[0:1], [0:3], [0:8], [0:16]}
  %bc = bf16[3,8,16]{2,1,0} bitcast(%sl)
  ROOT %tr = bf16[3,16,8]{2,1,0} transpose(%bc), dimensions={0,2,1}
}

%body (st: (bf16[2,3,8,16], s32[])) -> (bf16[2,3,8,16], s32[]) {
  %st = (bf16[2,3,8,16]{3,2,1,0}, s32[]) parameter(0)
  %slab = bf16[2,3,8,16]{3,2,1,0} get-tuple-element(%st), index=0
  %f = bf16[3,16,8]{2,1,0} fusion(%slab), kind=kLoop, calls=%fused.1
  %small = bf16[3,8]{1,0} copy(%other)
  ROOT %t = (bf16[2,3,8,16]{3,2,1,0}, s32[]) tuple(%slab, %i)
}

%cond (st: (bf16[2,3,8,16], s32[])) -> pred[] {
  %st = (bf16[2,3,8,16]{3,2,1,0}, s32[]) parameter(0)
  ROOT %lt = pred[] compare(%a, %b), direction=LT
}

ENTRY %main (a: bf16[2,3,8,16]) -> bf16[2,3,8,16] {
  %a = bf16[2,3,8,16]{1,3,2,0} parameter(0)
  %copy.33 = bf16[2,3,8,16]{3,2,1,0} copy(%a)
  %f32s = f32[2,3,8,16]{3,2,1,0} copy(%z)
  %w = (bf16[2,3,8,16]{3,2,1,0}, s32[]) while(%init), condition=%cond, body=%body
  ROOT %out = bf16[2,3,8,16]{3,2,1,0} get-tuple-element(%w), index=0
}
"""
    moves, forms = _hlo_audit()._slab_census(hlo, 2 * 3 * 8 * 16, 2, "bf16")
    assert [(m["name"], m["what"]) for m in moves["outside_loops"]] == [
        ("copy.33", "slab")]
    assert [(m["name"], m["op"], m["what"], m["computation"])
            for m in moves["in_loops"]] == [
                ("tr", "transpose", "layer", "fused.1")]
    assert set(forms) == {"bf16[2,3,8,16]{3,2,1,0}",
                          "bf16[2,3,8,16]{1,3,2,0}"}


@pytest.fixture(scope="module")
def described_v5e():
    """Skips where this installation cannot describe a v5e (the TPU's
    compiler missing). Called from a test only, never at import: one
    process at a time may load the TPU's library."""
    from jax.experimental import topologies
    try:
        topologies.get_topology_desc(platform="tpu",
                                     topology_name="v5e:2x2")
    except Exception as e:                                # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return "v5e:2x2"


def test_the_cells_resident_program_moves_no_slab_on_a_described_v5e(
        described_v5e):
    """The compile-only check of PR 29, kept: the REAL ``_resident_fn``
    at the ``gpt2xl-serve-closed8`` cell's sizes, compiled for a
    described v5e (no device, no weights; about ten seconds), carries
    the slab at its own bytes (0.82 GB a tensor: 25 x 64 folded into
    1664, 4% of padding, and none the compiler adds), holds no slab- or
    layer-sized ``copy``/``transpose`` in any loop body or around the
    ``while``, and needs under 2 GB of temporaries (the rows-first
    carry of PR 26 needed 5.81 GB: two slabs padded 2.6x). A cache
    layout the TPU's compiler pads or relays again fails here, before
    any chip time is spent."""
    out = _hlo_audit().slab(topology=described_v5e)
    assert out["slab_shape"] == [48, 8, 640, 1664]
    res = out["programs"]["resident"]
    assert res["slab_moves"] == {"in_loops": [], "outside_loops": []}
    assert set(res["slab_forms_bytes"].values()) == {out["slab_data_bytes"]}
    assert res["memory"]["temp_size_in_bytes"] < 2 * 2**30
    # the prefill program arms its slot itself (PR 31): the slots' tok,
    # pos and key_data go in and out in place beside the slab, and no
    # slab-sized copy stands in for the donation
    pre = out["programs"]["prefill512"]
    assert [a["shape"] for a in pre["io"]["aliases"]] == [
        "bf16[48,8,640,1664]", "bf16[48,8,640,1664]", "s32[8]", "s32[8]",
        "u32[8,2]"]
    assert pre["io"]["outputs"] == [
        "bf16[48,8,640,1664]", "bf16[48,8,640,1664]", "s32[8]", "s32[8]",
        "u32[8,2]", "s32[]"]
    assert not [m for m in pre["slab_moves"]["outside_loops"]
                + pre["slab_moves"]["in_loops"] if m["what"] == "slab"]
    assert out["ok"], out["violations"]


@pytest.mark.parametrize("pairs", [5120, 20480])
def test_the_tiled_grouped_product_compiles_for_a_described_v5e(
        described_v5e, pairs):
    """The expert layer's Pallas kernel at ``laguna-serve-closed16``'s
    widths (a 512 and a 2048 bucket's pairs), compiled for a described v5e
    (a few seconds each): two buffers of an expert's three whole matrices
    fit the vector memory the call asks for; the stacked ``[3, 128, ...]``
    weights reach the kernel as a bitcast (no ``copy`` of them: 2.4 GB a
    layer each); and the custom call's ``op_name`` keeps the
    ``moe_experts`` path the benchmark's readers look for."""
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from pipe_tpu.obs.events import FFN, MOE_EXPERTS, device_scope
    from pipe_tpu.ops.grouped_product import grouped_gated_mlp, tile_groups

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name=described_v5e).devices[0])
    d, f = 3072, 1024

    def experts(x, w_gate, w_up, w_down, sizes, layer):
        with device_scope(FFN), device_scope(MOE_EXPERTS):
            return grouped_gated_mlp(
                x, *(w.reshape((3 * 128,) + w.shape[2:])
                     for w in (w_gate, w_up, w_down)),
                tile_groups(sizes, pairs), first_group=layer * 128,
                interpret=False)

    hlo = jax.jit(experts).lower(*(
        jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        for shape, dtype in (((pairs, d), jnp.bfloat16),
                             ((3, 128, d, f), jnp.bfloat16),
                             ((3, 128, d, f), jnp.bfloat16),
                             ((3, 128, f, d), jnp.bfloat16),
                             ((128,), jnp.int32), ((), jnp.int32)))
    ).compile().as_text()
    calls = [ln for ln in hlo.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 1 and f"f32[{pairs},{d}]" in calls[0]
    assert "/ffn/moe_experts/grouped_product/" in calls[0]
    assert not re.search(r"bf16\[(384|3,128),\d+,\d+\][^ ]* copy\(", hlo)
