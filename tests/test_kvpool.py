"""Paged KV pool (pipe_tpu/serve/kvpool.py): blocks, sharing, parity.

The contract under test, in order of importance:

* **Bitwise parity.** Paged decode — gather the slot's block view, run
  the UNCHANGED layer decode, scatter the new rows — matches the slab
  backends and the one-shot batch-1 Generator token-for-token, greedy
  AND sampled, on both backends, including through copy-on-write
  prefix forks (the tentpole acceptance pin).
* **One program, any shape.** Paged mode compiles ONE chunked prefill
  program and ONE decode program regardless of prompt-length mix —
  trace counters pin zero steady-state recompiles where the slab path
  keys a prefill program per bucket.
* **Allocator honesty.** Every admit/release/evict keeps
  ``free + in_use + evictable == total``; a released slot's table row
  is zeroed (sacrificial) before its blocks can be reallocated; failed
  prefills unpublish their half-written cache entries.
* **Admission control.** Block availability gates admission: requests
  park at the head of the queue (FIFO preserved) until blocks free,
  counted by ``serve.kv.admission_blocked``.
* **Opt-out is absent.** ``prefix_cache=False`` changes host policy
  only — the compiled decode HLO is byte-identical.

Pool-only tests are pure host allocator checks (no device programs);
the parity tests reuse the tiny-model fixture discipline of
``tests/test_serve.py``.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipe_tpu.inference import GenerationConfig, Generator
from pipe_tpu.models.transformer_lm import LMConfig, PipelinedLM
from pipe_tpu.obs.telemetry import get_registry
from pipe_tpu.parallel.mesh import make_mesh
from pipe_tpu.parallel.spmd import stack_stage_params
from pipe_tpu.serve import (KvPool, PoolExhausted, RequestQueue, Router,
                            RouterPolicy, ServeEngine,
                            SingleDeviceSlotBackend, block_demand)
from pipe_tpu.serve.ring import RingSlotBackend

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


def _paged_backend(kind, model, params, gen_cfg, **kw):
    if kind == "single":
        kw.setdefault("num_slots", 2)
        return SingleDeviceSlotBackend(model, params, max_len=16,
                                       gen=gen_cfg, kv_block_size=4,
                                       prefill_chunk=4, **kw)
    sp, pre, post = params
    mesh = make_mesh(2, 1)
    return RingSlotBackend(mesh, model, stack_stage_params(sp), pre, post,
                           max_len=16, gen=gen_cfg, kv_block_size=4,
                           prefill_chunk=4, **kw)


def _pool(**kw):
    kw.setdefault("num_blocks", 9)
    kw.setdefault("block_size", 4)
    kw.setdefault("num_slots", 2)
    kw.setdefault("max_len", 16)
    return KvPool(**kw)


def _conserved(pool):
    s = pool.stats()
    return (s["blocks_free"] + s["blocks_in_use"] + s["blocks_evictable"]
            == s["blocks_total"])


# ---------------------------------------------------------------------------
# host allocator (no device programs)


def test_block_demand_and_validation():
    # last sampled token's row is never written, hence the -1
    assert block_demand(5, 6, 4) == 3     # 10 rows
    assert block_demand(4, 1, 4) == 1     # 4 rows
    assert block_demand(1, 16, 4) == 4    # 16 rows
    with pytest.raises(ValueError, match="power of two"):
        _pool(block_size=3)
    with pytest.raises(ValueError, match="sacrificial"):
        _pool(num_blocks=1)


def test_admit_release_accounting_and_sacrificial_row():
    pool = _pool(prefix_cache=False)
    prompt = list(range(1, 6))
    adm = pool.admit(0, prompt, 6)        # 10 rows -> 3 blocks
    assert len(adm.blocks) == 3
    assert 0 not in adm.blocks            # block 0 never allocated
    assert pool.free_blocks == 5 and _conserved(pool)
    # reserved entries head the row; the unreserved tail is sacrificial
    assert list(pool.table[0][:3]) == adm.blocks
    assert not pool.table[0][3:].any()
    with pytest.raises(RuntimeError, match="admitted twice"):
        pool.admit(0, prompt, 6)
    pool.release(0)
    assert not pool.table[0].any()        # dead slot -> block 0 forever
    assert pool.free_blocks == 8 and _conserved(pool)
    pool.release(0)                       # idempotent


def test_prefix_reuse_and_cow_fork_plan():
    pool = _pool(num_blocks=17)
    shared = list(range(1, 9))            # exactly 2 full blocks
    a = pool.admit(0, shared + [20, 21], 4, chunk=4)
    assert a.prefix_hits == 0 and not a.cow_forks
    # same 8-token prefix, different tail: both full blocks reused
    # read-only, prefill resumes at the chunk boundary past them
    b = pool.admit(1, shared + [30], 4, chunk=4)
    assert b.prefix_hits == 2 and not b.cow_forks
    assert b.resume_from == 8
    assert b.blocks[:2] == a.blocks[:2]   # physically shared
    assert pool.stats()["shared_blocks"] == 2
    pool.release(0)
    pool.release(1)
    # identical FULL-hit prompt: the recompute tail (position plen-1)
    # falls inside the last shared block -> that block forks, the rest
    # stay read-only shares
    c = pool.admit(0, shared, 4, chunk=4)
    assert c.prefix_hits == 2 and len(c.cow_forks) == 1
    assert c.resume_from == 4
    assert c.blocks[0] == a.blocks[0]     # block 1 still shared
    assert c.blocks[1] != a.blocks[1]     # block 2 forked private
    assert _conserved(pool)


def test_release_failed_unpublishes_registered_entries():
    pool = _pool()
    prompt = list(range(1, 9))
    pool.admit(0, prompt, 4, chunk=4)
    assert pool.cached_prefix_blocks(prompt) == 2
    pool.release(0, failed=True)          # prefill died mid-write
    assert pool.cached_prefix_blocks(prompt) == 0
    assert pool.free_blocks == 8 and _conserved(pool)


def test_lru_eviction_and_invalidate():
    reg = get_registry()
    pool = _pool(num_blocks=7, num_slots=3, max_len=32)  # 6 allocatable
    p1, p2 = list(range(1, 9)), list(range(40, 48))
    pool.admit(0, p1, 1, chunk=4)             # 2 blocks, both cached
    pool.release(0)                           # refs 0 -> LRU, not free
    assert pool.free_blocks == 4 and pool.evictable_blocks == 2
    pool.admit(1, p2, 1, chunk=4)
    pool.release(1)
    assert pool.free_blocks == 2 and pool.evictable_blocks == 4
    # demand 6 > free 2: eviction reclaims the OLDEST entries (p1's)
    ev0 = reg.counter("serve.kv.evictions").value
    pool.admit(2, list(range(60, 82)), 2, chunk=4)   # 23 rows -> 6 blocks
    assert reg.counter("serve.kv.evictions").value - ev0 == 4
    assert pool.cached_prefix_blocks(p1) == 0
    pool.release(2)
    # invalidate: refcount-0 cached blocks go straight to the free list
    pool2 = _pool()
    pool2.admit(0, p1, 1, chunk=4)
    pool2.release(0)
    assert pool2.invalidate(pool2.prefix_hashes(p1)) == 2
    assert pool2.evictable_blocks == 0 and pool2.free_blocks == 8
    assert _conserved(pool2)


def test_pool_exhausted_detail_and_can_admit():
    pool = _pool(num_blocks=4)            # 3 allocatable
    assert pool.can_admit(5, 6) is True   # 10 rows -> 3 blocks, exact fit
    assert pool.can_admit(9, 8) is False  # 16 rows -> 4 blocks: never
    pool.admit(0, [1, 2, 3, 4, 5], 6)     # 3 blocks: pool now empty
    assert pool.can_admit(2, 2) is False
    with pytest.raises(PoolExhausted) as ei:
        pool.admit(1, [1, 2], 2)
    assert ei.value.free == 0 and ei.value.total == 3
    assert ei.value.demand == 1
    assert _conserved(pool)


def test_fragmentation_counts_unwritable_tail_rows():
    pool = _pool(prefix_cache=False)
    pool.admit(0, [1, 2, 3], 3)           # 5 rows over 2 blocks (8 rows)
    assert pool.stats()["fragmentation"] == pytest.approx(3 / 8)
    pool.release(0)
    assert pool.stats()["fragmentation"] == 0.0


def test_generation_config_kv_knobs():
    assert GenerationConfig().kv_block_size is None
    assert GenerationConfig().prefix_cache is True
    assert GenerationConfig(kv_block_size=8).kv_block_size == 8
    for bad in (0, 3, 6, -4):
        with pytest.raises(ValueError, match="power of two"):
            GenerationConfig(kv_block_size=bad)


# ---------------------------------------------------------------------------
# parity pins (the tentpole acceptance)


@pytest.mark.parametrize("kind", ["single", "ring"])
def test_paged_staggered_parity_and_one_program(kind, model_and_params):
    """Mixed prompt lengths arriving mid-flight through the PAGED
    backend: bitwise the one-shot Generator, with exactly ONE decode
    trace and ONE chunked-prefill trace across all five lengths (the
    slab path would have compiled one prefill per bucket)."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompts = _mixed_prompts((3, 5, 4, 7, 5))
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=7)

    backend = _paged_backend(kind, model, params, gen_cfg)
    pre = "serve.engine" if kind == "single" else "serve.ring"
    reg = get_registry()
    d0 = reg.counter(f"{pre}.decode_traces").value
    c0 = reg.counter(f"{pre}.prefill_chunk_traces").value

    eng = ServeEngine(backend)
    ids = [eng.submit(prompts[0], seed=7).id]
    eng.tick()
    ids += [eng.submit(p, seed=7).id for p in prompts[1:3]]
    eng.tick()
    ids += [eng.submit(p, seed=7).id for p in prompts[3:]]
    eng.run_until_idle()

    for i, rid in enumerate(ids):
        resp = eng.response(rid)
        assert resp.status == "ok" and resp.finish_reason == "length"
        np.testing.assert_array_equal(np.asarray(resp.tokens), refs[i])
    assert reg.counter(f"{pre}.decode_traces").value - d0 == 1
    assert reg.counter(f"{pre}.prefill_chunk_traces").value - c0 == 1
    assert backend.program_stats() == {
        "prefill_programs": 1, "decode_chunk": 1, "kv": "paged"}
    # every slot released -> the pool drained back to empty
    assert backend.pool.stats()["blocks_in_use"] == 0


def test_paged_sampled_parity_single(model_and_params):
    """temperature>0 through the paged single-device backend: the chunk
    prefill + sample epilogue replicate the batch-1 Generator key chain,
    so sampled tokens stay bitwise equal."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.8,
                               top_k=12)
    prompts = _mixed_prompts((3, 5, 4))
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=5)
    backend = _paged_backend("single", model, params, gen_cfg)
    resps = ServeEngine(backend).serve(prompts, seeds=[5] * len(prompts))
    for resp, ref in zip(resps, refs):
        np.testing.assert_array_equal(np.asarray(resp.tokens), ref)


def test_paged_sampled_parity_ring_matches_slab_ring(model_and_params):
    """The ring threads the Generator's split key chain through the
    revolutions (it used to speak its own fold_in chain), so the pin is
    three-way: paged-ring == slab-ring == the one-shot Generator,
    token-for-token."""
    model, params = model_and_params
    sp, pre, post = params
    gen_cfg = GenerationConfig(max_new_tokens=5, temperature=1.0,
                               top_k=8)
    prompts = _mixed_prompts((3, 6, 4), seed=3)
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=3)
    mesh = make_mesh(2, 1)
    slab = RingSlotBackend(mesh, model, stack_stage_params(sp), pre,
                           post, max_len=16, gen=gen_cfg)
    want = ServeEngine(slab).serve(prompts, seeds=[3] * len(prompts))
    paged = _paged_backend("ring", model, params, gen_cfg)
    got = ServeEngine(paged).serve(prompts, seeds=[3] * len(prompts))
    for a, b, ref in zip(got, want, refs):
        assert a.status == "ok"
        np.testing.assert_array_equal(np.asarray(a.tokens),
                                      np.asarray(b.tokens))
        np.testing.assert_array_equal(np.asarray(a.tokens), ref)


@pytest.mark.parametrize("kind", ["single", "ring"])
def test_shared_prefix_cow_parity(kind, model_and_params):
    """Requests sharing a system prompt reuse its cached blocks
    (prefix_hits > 0); a repeat of the IDENTICAL prompt forks the block
    its recompute tail rewrites (cow_forks > 0). Both stay bitwise equal
    to cold one-shot references — sharing is invisible to tokens."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=5, temperature=0.0)
    sys_prompt = _mixed_prompts((8,), seed=11)[0]   # exactly 2 blocks
    prompts = [sys_prompt + [3], sys_prompt + [5, 6], sys_prompt,
               sys_prompt]                          # last: full-hit fork
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=2)
    backend = _paged_backend(kind, model, params, gen_cfg,
                             kv_pool_blocks=17)
    reg = get_registry()
    h0 = reg.counter("serve.kv.prefix_hits").value
    f0 = reg.counter("serve.kv.cow_forks").value
    resps = ServeEngine(backend).serve(prompts,
                                       seeds=[2] * len(prompts))
    for resp, ref in zip(resps, refs):
        np.testing.assert_array_equal(np.asarray(resp.tokens), ref)
    assert reg.counter("serve.kv.prefix_hits").value - h0 > 0
    assert reg.counter("serve.kv.cow_forks").value - f0 > 0


def test_int8_kv_blocks_top1_agreement(model_and_params):
    """int8 KV blocks (quantize on scatter, dequantize in the gathered
    attention read): tolerance contract, not the bitwise pin — greedy
    tokens should overwhelmingly agree with the fp backend's."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompts = _mixed_prompts((5, 7), seed=4)
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=0)
    backend = _paged_backend("single", model, params, gen_cfg,
                             kv_dtype="int8")
    resps = ServeEngine(backend).serve(prompts,
                                       seeds=[0] * len(prompts))
    agree = total = 0
    for resp, ref in zip(resps, refs):
        got = np.asarray(resp.tokens)
        agree += int((got == ref[:len(got)]).sum())
        total += len(got)
    assert agree / total >= 0.8, f"int8 agreement {agree}/{total}"


def test_int8_kv_requires_paged_and_single_device(model_and_params):
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=4)
    with pytest.raises(ValueError, match="paged"):
        SingleDeviceSlotBackend(model, params, num_slots=2, max_len=16,
                                gen=gen_cfg, kv_dtype="int8")
    sp, pre, post = params
    with pytest.raises(NotImplementedError, match="single-device"):
        RingSlotBackend(make_mesh(2, 1), model, stack_stage_params(sp),
                        pre, post, max_len=16, gen=gen_cfg,
                        kv_block_size=4, kv_dtype="int8")


def test_prefix_cache_off_decode_hlo_identical(model_and_params):
    """prefix_cache=False is host allocator policy ONLY: the compiled
    paged decode program lowers to byte-identical HLO either way."""
    model, params = model_and_params

    def lowered(prefix_cache):
        gen_cfg = GenerationConfig(max_new_tokens=4, temperature=0.0,
                                   prefix_cache=prefix_cache)
        be = _paged_backend("single", model, params, gen_cfg)
        fn, args = be.decode_program()
        return fn.lower(*args).as_text()

    assert lowered(True) == lowered(False)


# ---------------------------------------------------------------------------
# admission by block availability


def test_admission_parks_at_head_until_blocks_free(model_and_params):
    """A pool too small for two concurrent requests parks the second at
    the queue head (no slot is burned, FIFO order holds) and admits it
    when the first retires — counted by serve.kv.admission_blocked."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=6, temperature=0.0)
    prompts = _mixed_prompts((5, 4, 6), seed=9)
    refs = _one_shot_refs(model, params, prompts, gen_cfg, seed=1)
    # 5 allocatable blocks; each request needs 3 -> one at a time
    backend = _paged_backend("single", model, params, gen_cfg,
                             kv_pool_blocks=6)
    reg = get_registry()
    b0 = reg.counter("serve.kv.admission_blocked").value
    eng = ServeEngine(backend)
    ids = [eng.submit(p, seed=1).id for p in prompts]
    eng.run_until_idle()
    assert reg.counter("serve.kv.admission_blocked").value - b0 > 0
    for rid, ref in zip(ids, refs):
        resp = eng.response(rid)
        assert resp.status == "ok"
        np.testing.assert_array_equal(np.asarray(resp.tokens), ref)


# ---------------------------------------------------------------------------
# router KV handoff


def test_router_session_remap_invalidates_and_counts(model_and_params):
    """A session remapped off its home replica invalidates the prefix
    blocks it cached there (no stale reuse if it ever maps back) and
    the probe of the new home classifies the handoff warm/cold."""
    model, params = model_and_params
    gen_cfg = GenerationConfig(max_new_tokens=4, temperature=0.0)
    t = [0.0]
    clock = lambda: t[0]  # noqa: E731

    def engine():
        be = _paged_backend("single", model, params, gen_cfg)
        return ServeEngine(be, RequestQueue(clock=clock))

    engines = [engine(), engine()]
    router = Router(engines, RequestQueue(clock=clock),
                    policy=RouterPolicy(placement="session"))
    prompt = _mixed_prompts((8,), seed=13)[0]       # 2 cacheable blocks

    def serve_one():
        rid = router.submit(prompt, max_new_tokens=4, seed=0,
                            session="alice").id
        for _ in range(100):
            t[0] += 0.01
            router.tick()
            if router.response(rid) is not None:
                return router.response(rid)
        raise AssertionError("request never finished")

    reg = get_registry()
    k0 = {k: reg.counter(f"serve.fleet.kv_handoff_{k}").value
          for k in ("total", "cold", "invalidated")}
    assert serve_one().status == "ok"
    home = router._session_map["alice"]
    home_pool = router.replicas[home].engine.backend.pool
    assert home_pool.cached_prefix_blocks(prompt) == 2
    assert reg.counter("serve.fleet.kv_handoff_total").value == k0["total"]

    router.replicas[home].state = "suspect"         # stop placement home
    assert serve_one().status == "ok"
    assert router._session_map["alice"] != home     # remapped
    assert home_pool.cached_prefix_blocks(prompt) == 0   # invalidated
    assert reg.counter(
        "serve.fleet.kv_handoff_total").value - k0["total"] == 1
    assert reg.counter(
        "serve.fleet.kv_handoff_cold").value - k0["cold"] == 1
    assert reg.counter(
        "serve.fleet.kv_handoff_invalidated").value \
        - k0["invalidated"] == 2
