"""An admission costs the device no wait on the host (PR 31).

The contract, over the serve suite's parity pins:

* **Nothing is read back between an admission's prefill dispatch and the
  dispatch of the tick's decode launch.** The prefill program arms its own
  slot (token, position, key, draft history), ``backend.prefill`` returns
  the first token as the device gave it, and the engine reads it in the
  moment ``backend.decode`` offers once the launch is queued
  (``launched``). ``serve.engine.first_tokens_overlapped`` counts the
  first tokens read there; beside ``serve.engine.admitted`` it is the
  share of admissions the mechanism engaged on.
* **Tokens are the Generator's**, slab, paged and speculative, greedy and
  sampled, and a first token's ``ttft`` is no later than the reply.
* **A first token that ends its request** (eos, or a budget of one token)
  retires it in the tick of its admission, beside slots that go on.
* **A backend whose prefill returns an int** is served by the same engine
  code: its token has already arrived.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipe_tpu.inference import GenerationConfig, Generator
from pipe_tpu.inference.generate import sequence_lengths
from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
from pipe_tpu.obs.telemetry import get_registry
from pipe_tpu.serve import BucketSpec, ServeEngine, SingleDeviceSlotBackend

CFG = LMConfig(vocab=89, d_model=32, nhead=4, d_ff=64, n_layers=4,
               seq_len=32, dropout=0.0)

MODES = {
    "slab": dict(buckets=BucketSpec.of(4, 8)),
    "paged": dict(kv_block_size=4, prefill_chunk=4),
    "slab-spec": dict(buckets=BucketSpec.of(4, 8), spec_tokens=3),
    "paged-spec": dict(kv_block_size=4, prefill_chunk=4, spec_tokens=3),
}


@pytest.fixture(scope="module")
def model_and_params():
    model = PipelinedLM(CFG, n_stages=2)
    return model, model.init(jax.random.key(0))


def _refs(model, params, prompts, gen_cfg, seeds):
    g = Generator(model, gen_cfg)
    return [np.asarray(g.generate(params, jnp.asarray(p, jnp.int32)[None],
                                  jax.random.key(s)))[0]
            for p, s in zip(prompts, seeds)]


def _prompts(lengths, seed=0):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(1, CFG.vocab, size=n)) for n in lengths]


def _backend(model, params, gen_cfg, mode="slab", num_slots=2, max_len=24,
             **kw):
    return SingleDeviceSlotBackend(
        model, params, num_slots=num_slots, max_len=max_len, gen=gen_cfg,
        resident=True, resident_chunks=4, **MODES[mode], **kw)


def _counters(*names):
    reg = get_registry()
    return {n: reg.counter(f"serve.engine.{n}").value for n in names}


def _growth(before):
    now = _counters(*before)
    return {n: now[n] - before[n] for n in before}


class _FirstTokenSpy:
    """Stands where a prefill's first token stands: every way to its
    value on the host is written into the test's ``events``."""

    def __init__(self, tok, events, fail=False):
        self.tok, self.events, self.fail = tok, events, fail

    def _read(self):
        self.events.append("read")
        if self.fail:
            raise RuntimeError("the first token's read failed")
        return self.tok

    def __int__(self):
        return int(self._read())

    __index__ = __int__

    def __array__(self, *a, **kw):
        return np.asarray(self._read())

    def __bool__(self):
        return bool(self._read())

    def __eq__(self, other):
        return int(self) == other


def _spy_on_programs(backend, events, fail_slots=()):
    """The hook on the backend: every admission program's dispatch and
    every decode launch's goes into ``events``, and the first token an
    admission's program returns is handed on as a spy."""
    def admission(run):
        def dispatch(*args):
            events.append("prefill")
            *rest, tok0 = run(*args)
            slot = int(args[-3])             # (true_len, slot, seed, row)
            return (*rest, _FirstTokenSpy(tok0, events,
                                          fail=slot in fail_slots))
        return dispatch

    def launch(run):
        def dispatch(*args):
            events.append("launch")
            return run(*args)
        return dispatch

    if backend.paged:
        backend._sample_jit = admission(backend._sample_jit)
    else:
        backend._prefill_jit = (
            lambda make=backend._prefill_jit: admission(make()))
        backend._prefill_programs = {
            b: admission(run) for b, run in backend._prefill_programs.items()}
    backend._resident_jits = {
        k: launch(run) for k, run in backend._resident_jits.items()}


# ---------------------------------------------------------------------------
# (a) no read between a prefill's dispatch and the launch's


@pytest.mark.parametrize("mode", list(MODES))
def test_tick_reads_no_first_token_before_the_launch_is_queued(
        mode, model_and_params):
    """A tick with two admissions dispatches prefill, prefill, launch,
    and only then reads the two first tokens; both count as overlapped,
    and the tokens are the Generator's."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompts = _prompts((3, 5))
    refs = _refs(model, params, prompts, gen_cfg, (7, 7))
    backend = _backend(model, params, gen_cfg, mode)
    events = []
    _spy_on_programs(backend, events)
    eng = ServeEngine(backend)
    ids = [eng.submit(p, seed=7).id for p in prompts]
    before = _counters("admitted", "first_tokens_overlapped")

    eng.tick()

    assert events[:3] == ["prefill", "prefill", "launch"]
    assert events.count("read") == 2
    assert _growth(before) == {"admitted": 2, "first_tokens_overlapped": 2}
    # both slots hold their first token as an int, stamped
    assert all(isinstance(s.tokens[0], int) and s.ttft is not None
               for s in eng._slots)
    eng.run_until_idle()
    for rid, ref in zip(ids, refs):
        np.testing.assert_array_equal(np.asarray(eng.response(rid).tokens),
                                      ref)


def test_prefill_returns_the_device_value_and_arms_the_slot(
        model_and_params):
    """``backend.prefill`` hands back a 0-d device array and has written
    the slot's token, position and key on the device already: a decode
    may follow at once, and continues the Generator's chain."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.8, top_k=12)
    prompts = _prompts((3, 5))
    refs = _refs(model, params, prompts, gen_cfg, (5, 2**31 + 9))
    backend = _backend(model, params, gen_cfg)
    first = [backend.prefill(s, p, seed=seed)
             for s, (p, seed) in enumerate(zip(prompts, (5, 2**31 + 9)))]
    assert all(isinstance(t, jax.Array) and t.shape == () for t in first)
    np.testing.assert_array_equal(np.asarray(backend._tok),
                                  [int(t) for t in first])
    np.testing.assert_array_equal(np.asarray(backend._pos), [3, 5])
    toks, valid = backend.decode(np.array([True, True]),
                                 budgets=np.array([5, 5], np.int32))
    assert valid.all()
    for s, ref in enumerate(refs):
        np.testing.assert_array_equal(
            np.concatenate([[int(first[s])], toks[s]]),
            ref[:1 + toks.shape[1]])


def test_wrappers_that_pass_keywords_through_keep_the_overlap(
        model_and_params):
    """What replaces ``backend.prefill`` and ``backend.decode`` as
    instance attributes and passes ``**kw`` on (the benchmark's probe,
    the router's chaos wrapper) sees every call and leaves the moment
    between dispatch and sync to the engine."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    backend = _backend(model, params, gen_cfg)
    seen = {"prefill": 0, "decode": 0, "steps": 0}
    prefill, decode = backend.prefill, backend.decode

    def probing_prefill(slot, prompt, seed, **kw):
        seen["prefill"] += 1
        return prefill(slot, prompt, seed, **kw)

    def probing_decode(live, **kw):
        toks, valid = decode(live, **kw)
        seen["decode"] += 1
        seen["steps"] += toks.shape[1]
        return toks, valid

    backend.prefill, backend.decode = probing_prefill, probing_decode
    before = _counters("admitted", "first_tokens_overlapped",
                       "decode_launches", "decode_steps")
    resps = ServeEngine(backend).serve(_prompts((3, 5, 4)),
                                       seeds=[7, 7, 7])
    g = _growth(before)
    assert [r.status for r in resps] == ["ok"] * 3
    assert g["admitted"] == g["first_tokens_overlapped"] == 3
    assert seen == {"prefill": 3, "decode": g["decode_launches"],
                    "steps": g["decode_steps"]}


# ---------------------------------------------------------------------------
# (b) the Generator's tokens on the new path, ttft within the reply


@pytest.mark.parametrize("temp", [0.0, 0.8], ids=["greedy", "sampled"])
@pytest.mark.parametrize("mode", list(MODES))
def test_served_tokens_are_the_generators(mode, temp, model_and_params):
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=8, temperature=temp,
                               top_k=12 if temp else None)
    prompts = [[5, 6, 5, 6, 5, 6], [3, 3, 3, 3]] + _prompts((3, 7, 5))
    seeds = [11, 12, 13, 14, 15]
    refs = _refs(model, params, prompts, gen_cfg, seeds)
    backend = _backend(model, params, gen_cfg, mode)
    before = _counters("admitted", "first_tokens_overlapped")
    eng = ServeEngine(backend)
    ids = [eng.submit(prompts[0], seed=seeds[0]).id]
    eng.tick()
    ids += [eng.submit(p, seed=s).id
            for p, s in zip(prompts[1:], seeds[1:])]
    eng.run_until_idle()
    for rid, ref in zip(ids, refs):
        resp = eng.response(rid)
        assert resp.status == "ok"
        np.testing.assert_array_equal(np.asarray(resp.tokens), ref)
        assert resp.ttft is not None and 0 <= resp.ttft <= resp.latency
    assert _growth(before) == {"admitted": 5, "first_tokens_overlapped": 5}


# ---------------------------------------------------------------------------
# (c) a first token that ends its request


def test_a_budget_of_one_token_retires_in_its_tick_beside_seven(
        model_and_params):
    """``max_new_tokens == 1``: the request is done with its first token,
    takes no part in the launch, and retires in the tick of its
    admission with exactly that token; the launch of that tick runs its
    rounds for the seven others."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompts = _prompts((3, 5, 4, 7, 5, 3, 6, 4))
    refs = _refs(model, params, prompts, gen_cfg, [7] * 8)
    backend = _backend(model, params, gen_cfg, num_slots=8)
    eng = ServeEngine(backend)
    one = eng.submit(prompts[0], max_new_tokens=1, seed=7).id
    rest = [eng.submit(p, seed=7).id for p in prompts[1:]]
    before = _counters("admitted", "first_tokens_overlapped",
                       "decode_steps")

    finished = eng.tick()

    assert [r.request_id for r in finished] == [one]
    resp = eng.response(one)
    assert (resp.status, resp.finish_reason) == ("ok", "length")
    assert resp.tokens == [int(refs[0][0])]
    assert resp.ttft is not None and resp.ttft <= resp.latency
    g = _growth(before)
    assert g["admitted"] == g["first_tokens_overlapped"] == 8
    assert g["decode_steps"] > 0
    assert eng.live_slots == 7
    assert all(len(s.tokens) == 1 + g["decode_steps"]
               for s in eng._slots if s is not None)
    eng.run_until_idle()
    for rid, ref in zip(rest, refs[1:]):
        np.testing.assert_array_equal(np.asarray(eng.response(rid).tokens),
                                      ref)


@pytest.mark.parametrize("mode", ["slab", "paged-spec"])
def test_a_first_token_that_is_eos_retires_in_its_tick_beside_seven(
        mode, model_and_params):
    """The host learns that a first token is eos only after the launch
    is queued: the program started that slot done, so the launch ran no
    round, read back as zero steps and no error; the request retires in
    the tick of its admission with exactly one token, and the seven
    others go on to the Generator's tokens."""
    model, params = model_and_params
    free = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompts = _prompts((3, 5, 4, 7, 5, 3, 6, 4))
    eos = int(_refs(model, params, prompts[:1], free, [7])[0][0])
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0,
                               eos_token_id=eos)
    refs = _refs(model, params, prompts, gen_cfg, [7] * 8)
    lens = [int(sequence_lengths(jnp.asarray(r)[None], eos)[0])
            for r in refs]
    assert lens[0] == 1
    backend = _backend(model, params, gen_cfg, mode, num_slots=8)
    eng = ServeEngine(backend)
    ids = [eng.submit(p, seed=7).id for p in prompts]
    reg = get_registry()
    errors0 = reg.counter("resilience.decode_errors").value
    before = _counters("admitted", "decode_launches", "decode_steps")

    finished = eng.tick()

    # every request whose first token is eos is done, with that token
    firsts = [rid for rid, n, ref in zip(ids, lens, refs)
              if int(ref[0]) == eos]
    assert ids[0] in firsts
    assert sorted(r.request_id for r in finished) == sorted(firsts)
    for r in finished:
        assert (r.status, r.finish_reason, r.tokens) == ("ok", "eos", [eos])
    assert _growth(before) == {"admitted": 8, "decode_launches": 1,
                               "decode_steps": 0}
    assert reg.counter("resilience.decode_errors").value == errors0
    assert eng.live_slots == 8 - len(firsts)
    eng.run_until_idle()
    for rid, ref, n in zip(ids, refs, lens):
        np.testing.assert_array_equal(np.asarray(eng.response(rid).tokens),
                                      ref[:n])


def test_a_failed_read_fails_its_one_request(model_and_params):
    """An exception out of the deferred read is put down to that one
    request, as a raising prefill is: status ``error``, the slot free
    again, the other admission of the tick served."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompts = _prompts((3, 5))
    refs = _refs(model, params, prompts, gen_cfg, (7, 7))
    backend = _backend(model, params, gen_cfg)
    events = []
    eng = ServeEngine(backend)
    # slots are handed out from 0: the first admission's read fails
    _spy_on_programs(backend, events, fail_slots=(0,))
    ids = [eng.submit(p, seed=7).id for p in prompts]
    reg = get_registry()
    errors0 = reg.counter("resilience.slot_errors").value
    before = _counters("admitted", "first_tokens_overlapped")

    finished = eng.tick()

    assert [(r.request_id, r.status, r.finish_reason, r.tokens, r.ttft)
            for r in finished] == [(ids[0], "error", "backend_error", [],
                                    None)]
    assert isinstance(eng.last_error, RuntimeError)
    assert reg.counter("resilience.slot_errors").value - errors0 == 1
    assert _growth(before) == {"admitted": 1, "first_tokens_overlapped": 1}
    assert eng.live_slots == 1 and 0 in eng._free
    eng.run_until_idle()
    np.testing.assert_array_equal(np.asarray(eng.response(ids[1]).tokens),
                                  refs[1])


# ---------------------------------------------------------------------------
# (d) a backend whose prefill returns an int


class _IntBackend:
    """The slot-backend contract without jax, as ``tests/test_router.py``
    stubs it: ``prefill`` returns an int, ``decode`` takes the two
    keywords the engine always passes and no other."""

    class gen:
        eos_token_id = None
        max_new_tokens = 32
        pad_token_id = 0

    buckets = None
    decode_chunk = 1

    def __init__(self, num_slots=2):
        self.num_slots = num_slots
        self.calls = []

    def validate(self, prompt_len, max_new_tokens):
        pass

    def prefill(self, slot, prompt, seed):
        self.calls.append("prefill")
        return 40 + slot

    def decode(self, live, budgets=None, r_max=None):
        self.calls.append("decode")
        toks = np.ones((self.num_slots, 1), np.int32)
        return toks, np.broadcast_to(np.asarray(live, bool)[:, None],
                                     toks.shape)


def test_an_int_from_prefill_is_a_token_that_has_arrived():
    """Served unchanged: the first token is stamped at its admission,
    before any decode; nothing is overlapped, nothing is passed to a
    ``decode`` that takes no further keyword."""
    t = [0.0]

    def clock():
        t[0] += 1.0
        return t[0]

    from pipe_tpu.serve import RequestQueue
    backend = _IntBackend()
    eng = ServeEngine(backend, RequestQueue(capacity=8, clock=clock))
    ids = [eng.submit([1, 2, 3], max_new_tokens=n).id for n in (3, 1, 3)]
    before = _counters("admitted", "first_tokens_overlapped")
    finished = eng.tick()
    # the one-token request retired at its admission and freed its slot
    # for the third request within the tick, as it always did
    assert [r.request_id for r in finished] == [ids[1]]
    assert finished[0].tokens == [41] and finished[0].finish_reason == "length"
    assert backend.calls == ["prefill", "prefill", "prefill", "decode"]
    resps = {r.request_id: r for r in finished + eng.run_until_idle()}
    assert [resps[i].tokens for i in ids] == [[40, 1, 1], [41], [41, 1, 1]]
    assert all(r.status == "ok" and r.ttft is not None
               and r.ttft <= r.latency for r in resps.values())
    assert _growth(before) == {"admitted": 3, "first_tokens_overlapped": 0}
