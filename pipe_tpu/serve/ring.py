"""Continuous batching over the stage ring: slots = ring groups.

:class:`~..inference.pipelined.PipelinedGenerator` keeps every stage
busy by chasing ``n_stages`` request groups around the ring — but it
decodes one fixed batch to completion: the ring drains as groups finish
and refills only on the next ``generate`` call. This backend makes the
ring **continuously** full: each of the ``n_stages`` slots is a ring
group that can be retired and re-admitted independently, mid-flight,
without touching the other groups' in-flight state.

The trick is that the decode program carries the ring across host
ticks. One tick = ``revolutions * n_stages`` cycles of the same
wavefront recurrence as ``PipelinedGenerator`` (stage ``s``, cycle
``c`` works group ``(c - s) mod n``), but the carry — per-stage
activation ``h``, the wrap-edge token, per-stage per-group write
positions — is device-resident state returned to the host and fed back
next tick, with a monotonically increasing global cycle counter ``c0``.
Admission is a host table write: prefill walks the new prompt through
the stages (one serial ring pass, writing cache rows ``[0, p)``),
samples the first token, and the host arms ``admit_cycle[g] = c0 + g``
— the exact cycle stage 0 next meets group ``g``. Stage ``s`` treats
group ``g`` as valid from ``admit_cycle[g] + s`` on, so the new
request's wavefront threads between the live groups' wavefronts without
any of them noticing; invalid (stage, cycle, group) combinations write
to the sacrificial cache region past ``max_len``, the same masked-slot
discipline as the generators.

Like the single-device backend, the decode program is traced once
(``serve.ring.decode_traces`` pins it) and prefill compiles per prompt
bucket. Parity: requests through this backend — greedy AND sampled —
match the one-shot single-device ``Generator`` token-for-token. The
sampler threads the Generator split chain through the revolutions:
each stage carries its own device-resident per-group key table
(``key_local``, the ``pos_local`` discipline applied to PRNG state),
advancing its row by one split per valid cycle, so the key stage
``n-1`` samples with at cycle ``t`` is bitwise the ``t``-th split of
the request's seed key. That shared chain is what lets the
speculative lane extend here: a spec revolution injects a K-token
draft/verify wavefront per group (stage 0 drafts and owns
tok/pos/history, stage ``n-1`` verifies, advances the key chain by
the accepted count in-program, and rides its verdict back to stage 0
on the ring's wrap edge), emitting 1..K Generator-exact tokens per
group per revolution.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..inference.draft import DraftSource, resolve_draft
from ..inference.generate import (GenerationConfig, head_logits,
                                  sample_logits)
from ..inference.quant import QuantLeaf, dequant_tree
from ..models.common import refuse_grouped
from ..obs.telemetry import get_registry
from ..parallel.mesh import STAGE_AXIS
from .buckets import BucketSpec
from .kvpool import (KvPool, copy_block, flat_row_index,
                     gather_block_cache, scatter_block_rows)

__all__ = ["RingSlotBackend"]

_REBASE = 1 << 20   # keep the int32 cycle counter far from overflow


class RingSlotBackend:
    """``n_stages`` decode slots riding the pipeline ring, one request
    per group (rpg=1). Params are the ``PipelinedGenerator`` layout:
    ``stage_params`` stacked ``[n_stages, ...]`` and sharded over the
    ``stage`` mesh axis."""

    def __init__(self, mesh: Mesh, model, stage_params, pre_params,
                 post_params, *, max_len: int,
                 gen: GenerationConfig = GenerationConfig(),
                 buckets: Optional[BucketSpec] = None,
                 revolutions: int = 1, shape_cache_warn: int = 8,
                 kv_block_size: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 prefill_chunk: int = 16,
                 kv_dtype: Optional[str] = None,
                 kv_offload: bool = False,
                 kv_offload_blocks: Optional[int] = None,
                 resident="auto", resident_revolutions: int = 8,
                 spec_tokens: Optional[int] = None,
                 draft="ngram", draft_stages: int = 1,
                 spec_branches: Optional[int] = None,
                 spec_adaptive: bool = False):
        if STAGE_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh must have a {STAGE_AXIS!r} axis")
        if not hasattr(model, "embed_at"):
            raise TypeError(
                f"{type(model).__name__} has no embed_at; KV-cache "
                "generation needs position-offset embedding")
        if gen.num_beams != 1:
            raise ValueError(
                "the serve engine decodes greedy/sampled slots; beam "
                "search has no incremental slot form (num_beams must be 1)")
        if revolutions < 1:
            raise ValueError(
                f"revolutions must be >= 1, got {revolutions}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        refuse_grouped(model, "RingSlotBackend (serve/ring.py)")
        self.mesh = mesh
        self.model = model
        self.gen = gen
        self.buckets = buckets
        self.max_len = max_len
        self.n = mesh.shape[STAGE_AXIS]
        self.num_slots = self.n
        self.decode_chunk = revolutions   # tokens per slot per tick
        self.shape_cache_warn = shape_cache_warn
        # resident tri-state, exactly the single-device semantics:
        # "auto" keeps the cpu default on the byte-for-byte
        # single-launch path
        if resident not in ("auto", True, False):
            raise ValueError(
                f"resident must be 'auto', True or False, got {resident!r}")
        if resident == "auto":
            resident = jax.devices()[0].platform != "cpu"
        self.resident = bool(resident)
        if resident_revolutions < 1:
            raise ValueError(
                f"resident_revolutions must be >= 1, got "
                f"{resident_revolutions}")
        self.resident_revolutions = resident_revolutions
        # the engine's deadline horizon speaks in "resident chunks";
        # for the ring one chunk is one revolution (one where the
        # single-launch program runs)
        self.resident_chunks = resident_revolutions if self.resident else 1
        spec = spec_tokens if spec_tokens is not None \
            else gen.spec_tokens
        if spec is not None and spec < 2:
            raise ValueError(f"spec_tokens must be >= 2, got {spec}")
        if spec is not None and not self.resident:
            raise ValueError(
                "spec_tokens needs the resident loop (the draft/verify "
                "wavefront IS the resident revolution); pass "
                "resident=True")
        self.spec_tokens = spec
        # resident readout stride: 1 token per revolution, or a K-token
        # row per spec round
        self.decode_width = spec if spec is not None else 1
        if spec is not None:
            self._drafter = draft if isinstance(draft, DraftSource) \
                else resolve_draft(
                    draft, n_stages=mesh.shape[STAGE_AXIS],
                    layers_per_stage=len(stage_params),
                    draft_stages=draft_stages,
                    spec_branches=spec_branches)
            if self._drafter.branches > 1:
                raise ValueError(
                    "tree draft is single-device only: the ring verify "
                    "chunk is the linear K-row wavefront message (pick "
                    "draft='ngram' or 'truncated')")
            if self._drafter.name == "truncated" and draft_stages != 1:
                raise ValueError(
                    f"ring truncated draft needs draft_stages=1 (only "
                    f"stage 0's layers are resident where the draft "
                    f"runs), got {draft_stages}")
            if spec_adaptive:
                raise ValueError(
                    "spec_adaptive is single-device only: the ring's "
                    "in-flight wavefront carry is K-shaped, so a rung "
                    "switch would orphan every in-flight round")
            self._spec_overshoot = spec - 1
            self._spec_acc_total = 0
            self._spec_draft_total = 0
        else:
            if not (draft == "ngram" and draft_stages == 1
                    and spec_branches is None and not spec_adaptive):
                raise ValueError(
                    "draft/draft_stages/spec_branches/spec_adaptive "
                    "configure the speculative lane; set "
                    "gen.spec_tokens")
            self._drafter = None
            self._spec_overshoot = 0
        self._stage_params = stage_params
        self._pre = pre_params
        self._post = post_params
        self._lps = len(stage_params)

        n = self.n
        cd = model.cfg.compute_dtype
        nh, hd = model.block.attn.nhead, model.block.attn.head_dim
        stage_sh = NamedSharding(mesh, P(STAGE_AXIS))
        self._stage_sh = stage_sh

        kbs = kv_block_size if kv_block_size is not None \
            else gen.kv_block_size
        self.paged = kbs is not None
        if self.paged:
            # paged KV over the ring: every stage holds the pool rows for
            # ITS layers ([lps, num_blocks, bs, ...] per shard). The block
            # table is layer- and stage-agnostic — one table entry
            # addresses the same physical block id in each shard — so the
            # host-side KvPool needs no ring awareness at all.
            if kv_dtype is not None:
                raise NotImplementedError(
                    "int8 KV blocks are single-device only for now; the "
                    "ring pool stores the compute dtype")
            if kv_offload:
                raise NotImplementedError(
                    "kv_offload is single-device only for now: spilling "
                    "a block means a host read of every stage's shard "
                    "of it, which the ring's sharded pool layout does "
                    "not expose yet")
            if buckets is not None:
                gen.check_kv_headroom(buckets.max_len, kbs,
                                      self._spec_overshoot)
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
            self.prefill_chunk = prefill_chunk
            mb = -(-max_len // kbs)
            nb = kv_pool_blocks if kv_pool_blocks is not None \
                else n * mb + 1
            self.pool = KvPool(
                num_blocks=nb, block_size=kbs, num_slots=n,
                max_len=max_len, prefix_cache=gen.prefix_cache,
                gather_slack_rows=prefill_chunk)
            self._caches = {
                name: jax.device_put(jnp.zeros(
                    (n * self._lps, nb, kbs, nh, hd), cd), stage_sh)
                for name in ("k", "v")}
            # positions >= the reserved region clamp into table entry 0 —
            # the paged replacement for the slab's sacrificial region
            self._sacpos = (self.pool.table_width - 1) * kbs
            self._fork_jit = jax.jit(self._fork_fn, donate_argnums=(0,))
        else:
            if kv_dtype is not None:
                raise ValueError(
                    "kv_dtype needs the paged pool (set kv_block_size); "
                    "the slab path stores KV in the compute dtype")
            if kv_offload:
                raise ValueError(
                    "kv_offload needs the paged pool (set kv_block_size); "
                    "the slab path has no block-level eviction to spill")
            self.pool = None
            # sacrificial region: big enough to absorb a q=max_bucket
            # prefill write from an inactive stage, any post-retirement
            # decode overshoot within a tick, AND a q=K spec verify
            # chunk from an invalid (stage, cycle, group) combination
            max_bucket = buckets.max_len if buckets is not None \
                else max_len
            self._cache_len = max_len + max(
                max_bucket, spec if spec is not None else 1)
            self._sac = max_len
            self._caches = {
                "k": jax.device_put(jnp.zeros(
                    (n * self._lps, n, 1, self._cache_len, nh, hd), cd),
                    stage_sh),
                "v": jax.device_put(jnp.zeros(
                    (n * self._lps, n, 1, self._cache_len, nh, hd), cd),
                    stage_sh)}
        self._h = jax.device_put(
            jnp.zeros((n, 1, model.cfg.d_model), cd), stage_sh)
        self._tok_ring = jax.device_put(jnp.zeros((n,), jnp.int32),
                                        stage_sh)
        self._pos_local = jax.device_put(jnp.zeros((n, n), jnp.int32),
                                         stage_sh)
        # per-stage per-group PRNG state: stage s's row of group g's key
        # table, advanced by one split per valid cycle — every stage
        # replays the same Generator chain so stage n-1's sample at
        # generation step t uses bitwise the t-th split of the seed key
        kd0 = np.asarray(jax.random.key_data(jax.random.key(0)))
        self._kd_shape = kd0.shape
        self._key_local = jax.device_put(
            jnp.asarray(np.broadcast_to(
                kd0, (n, n) + kd0.shape).copy()), stage_sh)
        if spec is not None:
            # stage-0-authoritative spec state (other stages' rows are
            # shape-consistent garbage, never read across the psum):
            # current token, draft history, and the in-flight wavefront
            # message ring (h chunk, chunk tokens, base position,
            # validity, and the completion fields riding the wrap edge)
            self._tok_local = jax.device_put(
                jnp.zeros((n, n), jnp.int32), stage_sh)
            self._hist_local = jax.device_put(
                jnp.full((n, n, max_len + spec), gen.pad_token_id,
                         jnp.int32), stage_sh)
            self._spec_msg = {
                "h": jax.device_put(
                    jnp.zeros((n, spec, model.cfg.d_model), cd),
                    stage_sh),
                "x": jax.device_put(
                    jnp.zeros((n, spec), jnp.int32), stage_sh),
                "pos0": jax.device_put(
                    jnp.zeros((n,), jnp.int32), stage_sh),
                "vmsg": jax.device_put(
                    jnp.zeros((n,), jnp.int32), stage_sh),
                "t_seq": jax.device_put(
                    jnp.zeros((n, spec), jnp.int32), stage_sh),
                "n_emit": jax.device_put(
                    jnp.zeros((n,), jnp.int32), stage_sh),
                "cvalid": jax.device_put(
                    jnp.zeros((n,), jnp.int32), stage_sh),
            }

        # host tables (replicated program inputs)
        self._c0 = 0
        self._admit = np.zeros(n, np.int32)
        self._live_default = np.zeros(n, np.int32)
        self._tok_inject = np.zeros(n, np.int32)
        self._programs = {}

    # -- validation --------------------------------------------------------

    def validate(self, prompt_len: int, max_new_tokens: int) -> None:
        bucket = (self.buckets.bucket_for(prompt_len)
                  if self.buckets is not None and not self.paged
                  else prompt_len)
        if self.paged and self.pool.demand_for(
                prompt_len, max_new_tokens) > self.pool.allocatable:
            raise ValueError(
                f"request needs "
                f"{self.pool.demand_for(prompt_len, max_new_tokens)} KV "
                f"blocks but the whole pool holds "
                f"{self.pool.allocatable}; raise kv_pool_blocks or "
                f"shorten the request")
        if prompt_len + max_new_tokens + self._spec_overshoot \
                > self.max_len:
            extra = (f" + speculative headroom {self._spec_overshoot}"
                     if self._spec_overshoot else "")
            raise ValueError(
                f"prompt_len {prompt_len} + max_new_tokens "
                f"{max_new_tokens}{extra} exceeds the slot cache "
                f"({self.max_len} rows); raise max_len or shorten the "
                f"request")
        if max_new_tokens > self.gen.max_new_tokens:
            raise ValueError(
                f"max_new_tokens {max_new_tokens} exceeds the engine cap "
                f"({self.gen.max_new_tokens})")
        mp = getattr(self.model, "max_position", None)
        limit = mp() if callable(mp) else None
        need = max(bucket, prompt_len + max_new_tokens
                   + max(self.decode_chunk - 1, self._spec_overshoot))
        if limit is not None and need > limit:
            raise ValueError(
                f"request needs position {need} but the positional "
                f"table has {limit}")

    # -- shared device pieces ---------------------------------------------

    def _ring(self, x):
        n = self.n
        return jax.lax.ppermute(x, STAGE_AXIS,
                                [(i, (i + 1) % n) for i in range(n)])

    def _local_blocks(self, stage_params):
        cd = self.model.cfg.compute_dtype

        def local_slice(a):
            if isinstance(a, QuantLeaf):
                return QuantLeaf(q=a.q[0], scale=a.scale[0])
            return a[0].astype(cd)

        blocks = [jax.tree_util.tree_map(
                      local_slice, bp,
                      is_leaf=lambda x: isinstance(x, QuantLeaf))
                  for bp in stage_params]
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *blocks)

    def _run_blocks(self, block_stack, h, caches, grp, pos):
        """This stage's layers on ``h`` against group ``grp``'s slab —
        the ``PipelinedGenerator._run_blocks`` recurrence."""
        m = self.model
        cd = m.cfg.compute_dtype
        lps = self._lps

        def slab_slice(a):
            s = jax.lax.dynamic_slice(
                a, (0, grp) + (0,) * (a.ndim - 2),
                (lps, 1) + a.shape[2:])
            return jnp.squeeze(s, axis=1)

        def slab_write(a, new):
            return jax.lax.dynamic_update_slice(
                a, new[:, None], (0, grp) + (0,) * (a.ndim - 2))

        slab = jax.tree_util.tree_map(slab_slice, caches)

        def layer_step(h_c, inp):
            bp, cache = inp
            h_new, cache = m.block.decode(dequant_tree(bp, cd), h_c,
                                          cache, pos)
            return h_new, cache

        h, new_slab = jax.lax.scan(layer_step, h, (block_stack, slab))
        caches = jax.tree_util.tree_map(slab_write, caches, new_slab)
        return h, caches

    def _run_blocks_paged(self, block_stack, h, caches, trow, pos):
        """The paged analog of :meth:`_run_blocks`: this stage's layers
        on ``h`` against the gathered block view of the slot whose table
        row is ``trow``. The ``q = h.shape[1]`` new rows at ``pos`` are
        scattered back through the table; positions past the reserved
        region (inactive stages, dead groups) clamp into the sacrificial
        block. The layer decode itself is unchanged — the slab/paged
        bitwise-parity argument from ``serve/kvpool.py`` applies per
        stage."""
        m = self.model
        cd = m.cfg.compute_dtype
        bs = self.pool.block_size
        q = h.shape[1]
        ridx = flat_row_index(
            trow, pos + jnp.arange(q, dtype=jnp.int32), bs)

        def layer_step(h_c, inp):
            bp, pool_l = inp
            cache = gather_block_cache(pool_l, trow, block_size=bs,
                                       compute_dtype=cd)
            h_new, c2 = m.block.decode(dequant_tree(bp, cd), h_c, cache,
                                       pos)
            rows = {name: jax.lax.dynamic_slice(
                        c2[name], (0, pos) + (0,) * (c2[name].ndim - 2),
                        (1, q) + c2[name].shape[2:])[0]
                    for name in ("k", "v")}
            return h_new, scatter_block_rows(pool_l, ridx, rows)

        h, new_caches = jax.lax.scan(layer_step, h, (block_stack, caches))
        return h, new_caches

    # -- device programs ---------------------------------------------------

    def _prefill_fn(self, stage_params, pre, post, caches, pos_local,
                    prompt, true_len, slot, key):
        """One serial ring pass of the padded prompt: cycle ``i`` stage
        ``i`` runs its layers (q = bucket len) on the h arriving from
        stage ``i-1``, writing cache rows [0, B) of group ``slot``'s
        slab; stage n-1 samples the first token on the last cycle. The
        in-flight decode carry (h ring, wrap token) is untouched — live
        groups never notice an admission."""
        m, gen, n = self.model, self.gen, self.n
        cd = m.cfg.compute_dtype
        s = jax.lax.axis_index(STAGE_AXIS)
        get_registry().counter("serve.ring.prefill_traces").inc()
        block_stack = self._local_blocks(stage_params)
        pos_row = pos_local[0]                          # [n_groups]

        def cycle(carry, i):
            h_carry, caches, tok0 = carry
            active = (s == i)
            pos_w = jnp.where(active, 0, self._sac)
            h_embed = m.embed_at(pre, prompt, 0)        # [1, B, d]
            h_in = jnp.where(s == 0, h_embed, h_carry)
            h_out, caches = self._run_blocks(block_stack, h_in, caches,
                                             slot, pos_w)
            h_last = jax.lax.dynamic_slice(
                h_out, (0, true_len - 1, 0), (1, 1, h_out.shape[-1]))
            logits = head_logits(m, post, h_last)[:, 0, :]
            # `key` arrives pre-split: the host consumed k0 = key(seed)
            # as k1, sub = split(k0), passes sub here and arms the
            # stage key tables with k1 — the exact Generator chain
            tok = sample_logits(logits, key, gen)[0]
            emit = active & (s == n - 1)
            tok0 = jnp.where(emit, tok, tok0)
            return (self._ring(h_out), caches, tok0), None

        h0 = jnp.zeros((1, prompt.shape[1], m.cfg.d_model), cd)
        (_, caches, tok0), _ = jax.lax.scan(
            cycle, (h0, caches, jnp.int32(0)), jnp.arange(n))
        tok0 = jax.lax.psum(jnp.where(s == n - 1, tok0, 0), STAGE_AXIS)
        pos_row = jax.lax.dynamic_update_slice(
            pos_row, true_len[None], (slot,))
        return caches, pos_row[None], tok0

    def _step_key(self, key_row, grp, valid):
        """One Generator split on this stage's key row for ``grp``
        (frozen when the cycle is invalid): returns the sample key and
        the advanced table."""
        kd_g = jax.lax.dynamic_index_in_dim(key_row, grp, 0,
                                            keepdims=False)
        k2, sub = jax.random.split(jax.random.wrap_key_data(kd_g))
        new_kd = jnp.where(valid, jax.random.key_data(k2), kd_g)
        key_row = jax.lax.dynamic_update_slice(
            key_row, new_kd[None], (grp,) + (0,) * (key_row.ndim - 1))
        return sub, key_row

    def _decode_fn(self, stage_params, pre, post, caches, h_carry,
                   tok_ring, pos_local, key_local, c0, admit, live,
                   tok_inject):
        """``revolutions`` ring revolutions with a persistent carry. Per
        cycle ``c = c0 + i``: stage ``s`` works group ``grp = (c - s)
        mod n``; the group is valid here iff it is live and its
        admission wavefront has reached this stage (``c >= admit[grp] +
        s``); stage 0 swaps in the prefill-sampled token exactly at
        ``c == admit[grp]``. Invalid work lands in the sacrificial cache
        region. Sampling advances each stage's local key table by one
        split per valid cycle — the Generator chain. Traced once — the
        counter pins it."""
        m, gen, n = self.model, self.gen, self.n
        cd = m.cfg.compute_dtype
        R = self.decode_chunk
        s = jax.lax.axis_index(STAGE_AXIS)
        get_registry().counter("serve.ring.decode_traces").inc()
        block_stack = self._local_blocks(stage_params)
        eos = gen.eos_token_id

        def cycle(carry, i):
            h_carry, tok_ring, caches, pos_row, key_row, emitted = carry
            c = c0 + i
            grp = jnp.mod(c - s, n)
            adm = jnp.take(admit, grp)
            valid = (jnp.take(live, grp) != 0) & (c >= adm + s)
            pos = jnp.take(pos_row, grp)
            pos_use = jnp.where(valid, pos, self._sac)
            inject = c == adm
            tok_use = jnp.where(inject, jnp.take(tok_inject, grp),
                                tok_ring[0])
            h_embed = m.embed_at(pre, tok_use[None, None], pos_use)
            h_in = jnp.where(s == 0, h_embed, h_carry)
            h_out, caches = self._run_blocks(block_stack, h_in, caches,
                                             grp, pos_use)
            logits = head_logits(m, post, h_out)[:, 0, :]   # [1, V]
            sub, key_row = self._step_key(key_row, grp, valid)
            tok_out = sample_logits(logits, sub, gen)
            emit = (s == n - 1) & valid
            r = i // n
            old = jax.lax.dynamic_slice(emitted, (grp, r), (1, 1))[0, 0]
            emitted = jax.lax.dynamic_update_slice(
                emitted, jnp.where(emit, tok_out[0], old)[None, None],
                (grp, r))
            pos_row = jax.lax.dynamic_update_slice(
                pos_row, jnp.where(valid, pos + 1, pos)[None], (grp,))
            return (self._ring(h_out), self._ring(tok_out), caches,
                    pos_row, key_row, emitted), None

        emitted0 = jnp.zeros((n, R), jnp.int32)
        (h_carry, tok_ring, caches, pos_row, key_row, emitted), _ = \
            jax.lax.scan(
                cycle, (h_carry, tok_ring, caches, pos_local[0],
                        key_local[0], emitted0),
                jnp.arange(n * R))
        emitted = jax.lax.psum(
            jnp.where(s == n - 1, emitted, 0), STAGE_AXIS)
        return (caches, h_carry, tok_ring, pos_row[None],
                key_row[None], emitted)

    # -- paged device programs ---------------------------------------------

    def _prefill_chunk_fn(self, stage_params, pre, post, caches, tokens,
                          t0, true_len, trow, key):
        """THE ring prefill program: one fixed-shape ``[1, C]`` chunk at
        a traced offset, walked around the ring once (cycle ``i`` stage
        ``i`` active, exactly :meth:`_prefill_fn`'s serial pass), looped
        on the host until the prompt is covered — ANY prompt length, one
        compile, where the slab path keys a program per bucket. Inactive
        stages write their C rows at the sacrificial position; stage
        ``n - 1`` samples the chunk's candidate first token (the host
        keeps the last chunk's — only there does ``true_len - 1`` fall
        inside the chunk). The in-flight decode carry is untouched."""
        m, gen, n = self.model, self.gen, self.n
        cd = m.cfg.compute_dtype
        s = jax.lax.axis_index(STAGE_AXIS)
        get_registry().counter("serve.ring.prefill_chunk_traces").inc()
        block_stack = self._local_blocks(stage_params)

        def cycle(carry, i):
            h_carry, caches, tok0 = carry
            active = (s == i)
            pos_w = jnp.where(active, t0, self._sacpos)
            h_embed = m.embed_at(pre, tokens, t0)        # [1, C, d]
            h_in = jnp.where(s == 0, h_embed, h_carry)
            h_out, caches = self._run_blocks_paged(
                block_stack, h_in, caches, trow, pos_w)
            idx = jnp.clip(true_len - 1 - t0, 0, tokens.shape[1] - 1)
            h_last = jax.lax.dynamic_slice(
                h_out, (0, idx, 0), (1, 1, h_out.shape[-1]))
            logits = head_logits(m, post, h_last)[:, 0, :]
            tok = sample_logits(logits, key, gen)[0]   # key = pre-split sub
            emit = active & (s == n - 1)
            tok0 = jnp.where(emit, tok, tok0)
            return (self._ring(h_out), caches, tok0), None

        h0 = jnp.zeros((1, tokens.shape[1], m.cfg.d_model), cd)
        (_, caches, tok0), _ = jax.lax.scan(
            cycle, (h0, caches, jnp.int32(0)), jnp.arange(n))
        tok0 = jax.lax.psum(jnp.where(s == n - 1, tok0, 0), STAGE_AXIS)
        return caches, tok0

    def _fork_fn(self, caches, src, dst):
        """Copy-on-write block copy across every stage's layer shard
        (src/dst traced — one program for every fork; the copy is
        block-axis local, so it never crosses the stage sharding)."""
        get_registry().counter("serve.kv.fork_traces").inc()
        return copy_block(caches, src, dst, block_axis=1)

    def _decode_paged_fn(self, stage_params, pre, post, caches, h_carry,
                         tok_ring, pos_local, key_local, c0, admit,
                         live, tok_inject, tables):
        """:meth:`_decode_fn` with the slab slice/write swapped for the
        pool gather/scatter: stage ``s`` looks up group ``grp``'s table
        row and runs the SAME wavefront recurrence. Invalid (stage,
        cycle, group) work decodes at the sacrificial position, and
        released groups additionally carry all-zero table rows — a dead
        group can never touch a reallocated block. Traced once (the
        counter pins it)."""
        m, gen, n = self.model, self.gen, self.n
        R = self.decode_chunk
        s = jax.lax.axis_index(STAGE_AXIS)
        get_registry().counter("serve.ring.decode_traces").inc()
        block_stack = self._local_blocks(stage_params)

        def cycle(carry, i):
            h_carry, tok_ring, caches, pos_row, key_row, emitted = carry
            c = c0 + i
            grp = jnp.mod(c - s, n)
            adm = jnp.take(admit, grp)
            valid = (jnp.take(live, grp) != 0) & (c >= adm + s)
            pos = jnp.take(pos_row, grp)
            pos_use = jnp.where(valid, pos, self._sacpos)
            inject = c == adm
            tok_use = jnp.where(inject, jnp.take(tok_inject, grp),
                                tok_ring[0])
            h_embed = m.embed_at(pre, tok_use[None, None], pos_use)
            h_in = jnp.where(s == 0, h_embed, h_carry)
            trow = jax.lax.dynamic_index_in_dim(tables, grp, 0,
                                                keepdims=False)
            h_out, caches = self._run_blocks_paged(
                block_stack, h_in, caches, trow, pos_use)
            logits = head_logits(m, post, h_out)[:, 0, :]   # [1, V]
            sub, key_row = self._step_key(key_row, grp, valid)
            tok_out = sample_logits(logits, sub, gen)
            emit = (s == n - 1) & valid
            r = i // n
            old = jax.lax.dynamic_slice(emitted, (grp, r), (1, 1))[0, 0]
            emitted = jax.lax.dynamic_update_slice(
                emitted, jnp.where(emit, tok_out[0], old)[None, None],
                (grp, r))
            pos_row = jax.lax.dynamic_update_slice(
                pos_row, jnp.where(valid, pos + 1, pos)[None], (grp,))
            return (self._ring(h_out), self._ring(tok_out), caches,
                    pos_row, key_row, emitted), None

        emitted0 = jnp.zeros((n, R), jnp.int32)
        (h_carry, tok_ring, caches, pos_row, key_row, emitted), _ = \
            jax.lax.scan(
                cycle, (h_carry, tok_ring, caches, pos_local[0],
                        key_local[0], emitted0),
                jnp.arange(n * R))
        emitted = jax.lax.psum(
            jnp.where(s == n - 1, emitted, 0), STAGE_AXIS)
        return (caches, h_carry, tok_ring, pos_row[None],
                key_row[None], emitted)

    # -- resident device program -------------------------------------------

    def _resident_impl(self, paged, stage_params, pre, post, caches,
                       h_carry, tok_ring, pos_local, key_local, c0,
                       admit, live, tok_inject, budget, r_max,
                       tables=None):
        """The resident ring loop: a ``lax.while_loop`` whose body is
        ONE revolution of the exact wavefront recurrence above — the
        body stays switch-free (masked arithmetic + ppermute/psum, the
        ``compile_phases`` discipline; the 0-dispatch pin is
        ``tools/hlo_audit.py --resident``). Each revolution's emissions
        are psum'd so every stage can advance the replicated per-group
        ``done``/``budget`` carry; ``done`` joins the validity mask, so
        finished groups freeze (their writes route to the sacrificial
        region) instead of overshooting. Exits early when any live
        group goes done — a slot freed, host admission can matter — or
        after ``r_max`` revolutions (the deadline horizon). One host
        sync per launch: the revolution count."""
        m, gen, n = self.model, self.gen, self.n
        R = self.resident_revolutions
        s = jax.lax.axis_index(STAGE_AXIS)
        get_registry().counter("serve.ring.resident_traces").inc()
        block_stack = self._local_blocks(stage_params)
        eos = gen.eos_token_id
        sac = self._sacpos if paged else self._sac

        def body(state):
            h_carry, tok_ring, caches, pos_row, key_row, emitted, \
                done, budget, r = state

            def cycle(carry, j):
                h_carry, tok_ring, caches, pos_row, key_row, rev_tok, \
                    rev_emit = carry
                c = c0 + r * n + j
                grp = jnp.mod(c - s, n)
                adm = jnp.take(admit, grp)
                valid = (jnp.take(live, grp) != 0) \
                    & ~jnp.take(done, grp) & (c >= adm + s)
                pos = jnp.take(pos_row, grp)
                pos_use = jnp.where(valid, pos, sac)
                inject = c == adm
                tok_use = jnp.where(inject, jnp.take(tok_inject, grp),
                                    tok_ring[0])
                h_embed = m.embed_at(pre, tok_use[None, None], pos_use)
                h_in = jnp.where(s == 0, h_embed, h_carry)
                if paged:
                    trow = jax.lax.dynamic_index_in_dim(
                        tables, grp, 0, keepdims=False)
                    h_out, caches = self._run_blocks_paged(
                        block_stack, h_in, caches, trow, pos_use)
                else:
                    h_out, caches = self._run_blocks(
                        block_stack, h_in, caches, grp, pos_use)
                logits = head_logits(m, post, h_out)[:, 0, :]
                sub, key_row = self._step_key(key_row, grp, valid)
                tok_out = sample_logits(logits, sub, gen)
                emit = (s == n - 1) & valid
                old_t = jax.lax.dynamic_slice(rev_tok, (grp,), (1,))[0]
                rev_tok = jax.lax.dynamic_update_slice(
                    rev_tok, jnp.where(emit, tok_out[0], old_t)[None],
                    (grp,))
                old_e = jax.lax.dynamic_slice(rev_emit, (grp,), (1,))[0]
                rev_emit = jax.lax.dynamic_update_slice(
                    rev_emit, jnp.where(emit, jnp.int32(1), old_e)[None],
                    (grp,))
                pos_row = jax.lax.dynamic_update_slice(
                    pos_row, jnp.where(valid, pos + 1, pos)[None], (grp,))
                return (self._ring(h_out), self._ring(tok_out), caches,
                        pos_row, key_row, rev_tok, rev_emit), None

            z = jnp.zeros((n,), jnp.int32)
            (h_carry, tok_ring, caches, pos_row, key_row, rev_tok,
             rev_emit), _ = jax.lax.scan(
                cycle, (h_carry, tok_ring, caches, pos_row, key_row,
                        z, z),
                jnp.arange(n))
            rev_tok = jax.lax.psum(
                jnp.where(s == n - 1, rev_tok, 0), STAGE_AXIS)
            rev_emit = jax.lax.psum(
                jnp.where(s == n - 1, rev_emit, 0), STAGE_AXIS)
            emitted = jax.lax.dynamic_update_slice(
                emitted, rev_tok[:, None], (0, r))
            budget = budget - rev_emit
            done = done | (budget <= 0)
            if eos is not None:
                done = done | ((rev_tok == jnp.int32(eos))
                               & (rev_emit > 0))
            return (h_carry, tok_ring, caches, pos_row, key_row,
                    emitted, done, budget, r + 1)

        def cond(state):
            return (state[8] < r_max) & \
                ~jnp.any((live != 0) & state[6])

        emitted0 = jnp.zeros((n, R), jnp.int32)
        done0 = (live == 0) | (budget <= 0)
        state = (h_carry, tok_ring, caches, pos_local[0], key_local[0],
                 emitted0, done0, budget, jnp.int32(0))
        (h_carry, tok_ring, caches, pos_row, key_row, emitted, done,
         budget, r) = jax.lax.while_loop(cond, body, state)
        return (caches, h_carry, tok_ring, pos_row[None],
                key_row[None], emitted, r)

    def _resident_decode_fn(self, stage_params, pre, post, caches,
                            h_carry, tok_ring, pos_local, key_local,
                            c0, admit, live, tok_inject, budget,
                            r_max):
        return self._resident_impl(
            False, stage_params, pre, post, caches, h_carry, tok_ring,
            pos_local, key_local, c0, admit, live, tok_inject,
            budget, r_max)

    def _resident_decode_paged_fn(self, stage_params, pre, post, caches,
                                  h_carry, tok_ring, pos_local,
                                  key_local, c0, admit, live,
                                  tok_inject, tables, budget, r_max):
        return self._resident_impl(
            True, stage_params, pre, post, caches, h_carry, tok_ring,
            pos_local, key_local, c0, admit, live, tok_inject,
            budget, r_max, tables=tables)

    # -- speculative resident device program -------------------------------
    #
    # A spec revolution pipelines one draft/verify ROUND per group as a
    # K-row wavefront. Stage 0 owns the authoritative per-group state
    # (token, position, draft history): each cycle it applies the
    # completion the ring's wrap edge just delivered (stage n-1's
    # verdict for the round it injected n cycles earlier — the wrap
    # edge is group-aligned, so the verdict lands exactly one cycle
    # before the next injection), drafts K-1 continuations, and
    # launches the next chunk. Stages 1..n-2 run their layers on the
    # arriving K-row chunk. Stage n-1 owns the key table: it samples
    # the K-deep Generator split chain over the chunk logits, accepts
    # the matching draft prefix plus one correction token, advances
    # the group's key by the accepted count in-program, and rides the
    # verdict back to stage 0. Rejected rows sit at positions >= the
    # advanced pos, causally masked and re-written by the next round's
    # K-row chunk before any unmasked read — the same
    # rollback-overwrite law as the single-device lane, so accepted
    # tokens are bitwise the sequential Generator chain.

    def _spec_draft(self, paged, block_stack, caches, pre, hist_row,
                    tok_g, pos_g, pos_d, grp, trow):
        """Stage-local draft proposal for one group: K-1 candidate
        continuations of ``tok_g``. The n-gram drafter reads the
        stage-0 history table; the truncated drafter rolls this
        stage's own layers (draft_stages=1: stage 0's layers ARE the
        model's strict prefix) greedily with a tied-embedding head,
        writing draft KV rows at ``pos_d..pos_d+K-2`` — sacrificial
        everywhere but a validly-injecting stage 0, and re-written by
        the verify chunk there (the rollback-overwrite law)."""
        m, K = self.model, self.spec_tokens
        if self._drafter.name == "ngram":
            hrow = jax.lax.dynamic_index_in_dim(hist_row, grp, 0,
                                                keepdims=False)
            idx = jnp.arange(hrow.shape[0], dtype=jnp.int32)
            mask = (hrow == tok_g) & (idx < pos_g)
            j = jnp.max(jnp.where(mask, idx, jnp.int32(-1)))
            start = jnp.maximum(j + 1, 0)
            drafts = jax.lax.dynamic_slice(hrow, (start,), (K - 1,))
            return drafts, caches
        table = pre["embed"]["table"].astype(jnp.float32)
        cur = tok_g
        outs = []
        for i in range(K - 1):
            h = m.embed_at(pre, cur[None, None], pos_d + i)
            if paged:
                h, caches = self._run_blocks_paged(
                    block_stack, h, caches, trow, pos_d + i)
            else:
                h, caches = self._run_blocks(
                    block_stack, h, caches, grp, pos_d + i)
            logits = h[0, 0].astype(jnp.float32) @ table.T
            cur = jnp.argmax(logits).astype(jnp.int32)
            outs.append(cur)
        return jnp.stack(outs), caches

    def _resident_spec_impl(self, paged, stage_params, pre, post,
                            caches, msg, tok_local, pos_local,
                            key_local, hist_local, c0, admit, live,
                            budget, r_max, tables=None):
        """The resident spec ring loop: one revolution = one
        draft/verify round per group, pipelined as the K-row wavefront
        described above. Completions are recorded at stage 0 and
        psum'd at each revolution end so every stage advances the
        replicated done/budget identically; the one-revolution lag of
        that replicated view never causes an overshoot round — stage 0
        applies each completion BEFORE the same-cycle injection
        decision, through the revolution-local ``done_now`` mask."""
        m, gen, n = self.model, self.gen, self.n
        K = self.spec_tokens
        R = self.resident_revolutions
        s = jax.lax.axis_index(STAGE_AXIS)
        get_registry().counter("serve.ring.resident_traces").inc()
        block_stack = self._local_blocks(stage_params)
        eos = gen.eos_token_id
        sac = self._sacpos if paged else self._sac
        ar = jnp.arange(K, dtype=jnp.int32)

        def body(state):
            (msg, caches, tok_row, pos_row, key_row, hist_row,
             emitted, counts, done, budget, r) = state

            def cycle(carry, j):
                (msg, caches, tok_row, pos_row, key_row, hist_row,
                 done_now, rev_tok, rev_emit) = carry
                c = c0 + r * n + j
                grp = jnp.mod(c - s, n)
                adm = jnp.take(admit, grp)
                lv = jnp.take(live, grp) != 0
                x_arr = msg["x"][0]
                p0_arr = msg["pos0"][0]
                vm_arr = msg["vmsg"][0] != 0
                tseq = msg["t_seq"][0]
                ne_arr = msg["n_emit"][0]
                cv_arr = msg["cvalid"][0] != 0

                # -- completion application: gate out stale verdicts
                # (a retired-and-readmitted group re-arms ``admit``
                # past every in-flight injection cycle)
                app = cv_arr & lv & (c - n >= adm) \
                    & ~jnp.take(done, grp)
                napp = jnp.where(app, ne_arr, jnp.int32(0))
                pg = jnp.take(pos_row, grp)
                last = tseq[jnp.maximum(napp - 1, 0)]
                tok_row = jax.lax.dynamic_update_slice(
                    tok_row,
                    jnp.where(app, last, jnp.take(tok_row, grp))[None],
                    (grp,))
                hrow_g = jax.lax.dynamic_index_in_dim(
                    hist_row, grp, 0, keepdims=False)
                cur_h = jax.lax.dynamic_slice(hrow_g, (pg + 1,), (K,))
                hrow_g = jax.lax.dynamic_update_slice(
                    hrow_g, jnp.where(ar < napp, tseq, cur_h),
                    (pg + 1,))
                hist_row = jax.lax.dynamic_update_slice(
                    hist_row, hrow_g[None], (grp, 0))
                pos_row = jax.lax.dynamic_update_slice(
                    pos_row, (pg + napp)[None], (grp,))
                old_t = jax.lax.dynamic_slice(
                    rev_tok, (grp, 0), (1, K))[0]
                rev_tok = jax.lax.dynamic_update_slice(
                    rev_tok, jnp.where(app, tseq, old_t)[None],
                    (grp, 0))
                old_e = jax.lax.dynamic_slice(rev_emit, (grp,), (1,))[0]
                rev_emit = jax.lax.dynamic_update_slice(
                    rev_emit, jnp.where(app, napp, old_e)[None], (grp,))
                g_done = jnp.take(budget, grp) - napp <= 0
                if eos is not None:
                    g_done = g_done | jnp.any(
                        (tseq == jnp.int32(eos)) & (ar < napp))
                done_now = jax.lax.dynamic_update_slice(
                    done_now,
                    (jnp.take(done_now, grp) | (app & g_done))[None],
                    (grp,))

                # -- injection (stage 0): draft against the
                # just-advanced group state, launch the next chunk
                inj = lv & ~jnp.take(done_now, grp) & (c >= adm)
                use_inj = s == 0
                tok_g = jnp.take(tok_row, grp)
                pos_g = jnp.take(pos_row, grp)
                trow = (jax.lax.dynamic_index_in_dim(
                            tables, grp, 0, keepdims=False)
                        if paged else None)
                pos_d = jnp.where(use_inj & inj, pos_g, sac)
                drafts, caches = self._spec_draft(
                    paged, block_stack, caches, pre, hist_row, tok_g,
                    pos_g, pos_d, grp, trow)
                x_new = jnp.concatenate([tok_g[None], drafts])

                v_here = jnp.where(use_inj, inj,
                                   vm_arr & lv & (c >= adm + s))
                pos_chunk = jnp.where(
                    v_here, jnp.where(use_inj, pos_g, p0_arr), sac)
                x_here = jnp.where(use_inj, x_new, x_arr)
                h_embed = m.embed_at(pre, x_new[None, :], pos_chunk)
                h_in = jnp.where(use_inj, h_embed, msg["h"])
                if paged:
                    h_out, caches = self._run_blocks_paged(
                        block_stack, h_in, caches, trow, pos_chunk)
                else:
                    h_out, caches = self._run_blocks(
                        block_stack, h_in, caches, grp, pos_chunk)

                # -- verification (stage n-1): K-deep Generator split
                # chain, accept matching prefix + 1 correction, key
                # advanced by the accepted count
                logits = head_logits(m, post, h_out)[0]    # [K, V]
                kd_g = jax.lax.dynamic_index_in_dim(
                    key_row, grp, 0, keepdims=False)

                def sp(cdat, _):
                    k2, sub = jax.random.split(
                        jax.random.wrap_key_data(cdat))
                    c2 = jax.random.key_data(k2)
                    return c2, (c2, jax.random.key_data(sub))

                _, (carries, subs) = jax.lax.scan(
                    sp, kd_g, None, length=K)
                t = jax.vmap(lambda lg, sd: sample_logits(
                    lg[None], jax.random.wrap_key_data(sd), gen)[0])(
                        logits, subs)                      # [K]
                lead = jnp.cumprod(
                    (x_here[1:] == t[:K - 1]).astype(jnp.int32))
                ne_new = jnp.where(
                    v_here & (s == n - 1),
                    jnp.int32(1) + jnp.sum(lead), jnp.int32(0))
                sel = jnp.concatenate(
                    [kd_g[None], carries], axis=0)[ne_new]
                key_row = jax.lax.dynamic_update_slice(
                    key_row, sel[None],
                    (grp,) + (0,) * (key_row.ndim - 1))

                msg_out = {
                    "h": h_out,
                    "x": x_here[None],
                    "pos0": jnp.where(use_inj, pos_g, p0_arr)[None],
                    "vmsg": jnp.where(
                        use_inj, inj, vm_arr).astype(jnp.int32)[None],
                    "t_seq": jnp.where(s == n - 1, t, tseq)[None],
                    "n_emit": jnp.where(
                        s == n - 1, ne_new, ne_arr)[None],
                    "cvalid": jnp.where(
                        s == n - 1, v_here,
                        jnp.where(use_inj, False, cv_arr))
                        .astype(jnp.int32)[None],
                }
                msg = jax.tree_util.tree_map(self._ring, msg_out)
                return (msg, caches, tok_row, pos_row, key_row,
                        hist_row, done_now, rev_tok, rev_emit), None

            rt0 = jnp.zeros((n, K), jnp.int32)
            re0 = jnp.zeros((n,), jnp.int32)
            (msg, caches, tok_row, pos_row, key_row, hist_row,
             done_now, rev_tok, rev_emit), _ = jax.lax.scan(
                cycle, (msg, caches, tok_row, pos_row, key_row,
                        hist_row, done, rt0, re0),
                jnp.arange(n))
            rev_tok = jax.lax.psum(
                jnp.where(s == 0, rev_tok, 0), STAGE_AXIS)
            rev_emit = jax.lax.psum(
                jnp.where(s == 0, rev_emit, 0), STAGE_AXIS)
            emitted = jax.lax.dynamic_update_slice(
                emitted, rev_tok, (0, r * K))
            counts = jax.lax.dynamic_update_slice(
                counts, rev_emit[:, None], (0, r))
            budget = budget - rev_emit
            done = done | (budget <= 0)
            if eos is not None:
                done = done | jnp.any(
                    (rev_tok == jnp.int32(eos))
                    & (ar[None, :] < rev_emit[:, None]), axis=1)
            return (msg, caches, tok_row, pos_row, key_row, hist_row,
                    emitted, counts, done, budget, r + 1)

        def cond(state):
            return (state[10] < r_max) & \
                ~jnp.any((live != 0) & state[8])

        emitted0 = jnp.full((n, R * K), jnp.int32(gen.pad_token_id),
                            jnp.int32)
        counts0 = jnp.zeros((n, R), jnp.int32)
        done0 = (live == 0) | (budget <= 0)
        if eos is not None:
            e0 = jax.lax.psum(
                jnp.where((s == 0) & (tok_local[0] == jnp.int32(eos)),
                          1, 0), STAGE_AXIS)
            done0 = done0 | (e0 > 0)
        state = (msg, caches, tok_local[0], pos_local[0], key_local[0],
                 hist_local[0], emitted0, counts0, done0, budget,
                 jnp.int32(0))
        (msg, caches, tok_row, pos_row, key_row, hist_row, emitted,
         counts, done, budget, r) = jax.lax.while_loop(
            cond, body, state)
        return (caches, msg, tok_row[None], pos_row[None],
                key_row[None], hist_row[None], emitted, counts, r)

    def _resident_spec_fn(self, stage_params, pre, post, caches, msg,
                          tok_local, pos_local, key_local, hist_local,
                          c0, admit, live, budget, r_max):
        return self._resident_spec_impl(
            False, stage_params, pre, post, caches, msg, tok_local,
            pos_local, key_local, hist_local, c0, admit, live,
            budget, r_max)

    def _resident_spec_paged_fn(self, stage_params, pre, post, caches,
                                msg, tok_local, pos_local, key_local,
                                hist_local, c0, admit, live, tables,
                                budget, r_max):
        return self._resident_spec_impl(
            True, stage_params, pre, post, caches, msg, tok_local,
            pos_local, key_local, hist_local, c0, admit, live,
            budget, r_max, tables=tables)

    # -- backend API -------------------------------------------------------

    def _build(self, kind, B=None):
        pspec = jax.tree_util.tree_map(lambda _: P(STAGE_AXIS),
                                       self._stage_params)
        pre_spec = jax.tree_util.tree_map(lambda _: P(), self._pre)
        post_spec = jax.tree_util.tree_map(lambda _: P(), self._post)
        cache_spec = jax.tree_util.tree_map(lambda _: P(STAGE_AXIS),
                                            self._caches)
        S = P(STAGE_AXIS)
        if kind == "prefill":
            in_specs = (pspec, pre_spec, post_spec, cache_spec,
                        S, P(), P(), P(), P())
            out_specs = (cache_spec, S, P())
            fn = self._prefill_fn
        elif kind == "chunk":
            in_specs = (pspec, pre_spec, post_spec, cache_spec,
                        P(), P(), P(), P(), P())
            out_specs = (cache_spec, P())
            fn = self._prefill_chunk_fn
        elif kind == "decode_paged":
            in_specs = (pspec, pre_spec, post_spec, cache_spec,
                        S, S, S, S, P(), P(), P(), P(), P())
            out_specs = (cache_spec, S, S, S, S, P())
            fn = self._decode_paged_fn
        elif kind == "resident":
            in_specs = (pspec, pre_spec, post_spec, cache_spec,
                        S, S, S, S, P(), P(), P(), P(), P(), P())
            out_specs = (cache_spec, S, S, S, S, P(), P())
            fn = self._resident_decode_fn
        elif kind == "resident_paged":
            in_specs = (pspec, pre_spec, post_spec, cache_spec,
                        S, S, S, S, P(), P(), P(), P(), P(), P(), P())
            out_specs = (cache_spec, S, S, S, S, P(), P())
            fn = self._resident_decode_paged_fn
        elif kind == "resident_spec":
            msg_spec = jax.tree_util.tree_map(lambda _: S,
                                              self._spec_msg)
            in_specs = (pspec, pre_spec, post_spec, cache_spec,
                        msg_spec, S, S, S, S, P(), P(), P(), P(), P())
            out_specs = (cache_spec, msg_spec, S, S, S, S,
                         P(), P(), P())
            fn = self._resident_spec_fn
        elif kind == "resident_spec_paged":
            msg_spec = jax.tree_util.tree_map(lambda _: S,
                                              self._spec_msg)
            in_specs = (pspec, pre_spec, post_spec, cache_spec,
                        msg_spec, S, S, S, S, P(), P(), P(), P(), P(),
                        P())
            out_specs = (cache_spec, msg_spec, S, S, S, S,
                         P(), P(), P())
            fn = self._resident_spec_paged_fn
        else:
            in_specs = (pspec, pre_spec, post_spec, cache_spec,
                        S, S, S, S, P(), P(), P(), P())
            out_specs = (cache_spec, S, S, S, S, P())
            fn = self._decode_fn
        return jax.jit(jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False))

    def prefill(self, slot: int, prompt: Sequence[int], seed: int,
                max_new_tokens: Optional[int] = None) -> int:
        reg = get_registry()
        if self.paged:
            return self._prefill_paged(
                slot, prompt, seed,
                max_new_tokens if max_new_tokens is not None
                else self.gen.max_new_tokens)
        if self.buckets is not None:
            padded, p = self.buckets.pad(prompt, self.gen.pad_token_id)
        else:
            padded, p = list(prompt), len(prompt)
        B = len(padded)
        run = self._programs.get(("prefill", B))
        if run is None:
            reg.counter("serve.engine.prefill_program_misses").inc()
            run = self._build("prefill", B)
            self._programs[("prefill", B)] = run
            n_pre = sum(1 for k in self._programs if k[0] == "prefill")
            reg.gauge("serve.engine.prefill_programs").set(n_pre)
            if self.buckets is None and n_pre == self.shape_cache_warn + 1:
                import warnings
                warnings.warn(
                    f"ring serve backend compiled {n_pre} distinct "
                    f"prefill programs with bucketing DISABLED — every "
                    f"new prompt length recompiles. Pass a BucketSpec "
                    f"to cap the program cache.",
                    RuntimeWarning, stacklevel=3)
        else:
            reg.counter("serve.engine.prefill_program_hits").inc()
        arr = jnp.asarray(padded, jnp.int32)[None, :]
        k1, sub = jax.random.split(jax.random.key(seed))
        caches, pos_local, tok0 = run(
            self._stage_params, self._pre, self._post, self._caches,
            self._pos_local, arr, jnp.int32(p), jnp.int32(slot), sub)
        self._caches = caches
        self._pos_local = pos_local
        tok0 = int(tok0)
        self._arm_slot(slot, len(prompt), tok0, k1, prompt)
        return tok0

    def _arm_slot(self, slot, plen, tok0, k_next, prompt):
        """Host admission-table writes shared by both prefill paths:
        the admit cycle, the inject token, every stage's key-table row
        (the chain tail after the prefill's split — an np round-trip,
        the ``pos_local`` arming discipline), and in spec mode the
        stage-0-authoritative token/history rows."""
        self._admit[slot] = self._c0 + slot
        self._tok_inject[slot] = tok0
        kl = np.array(self._key_local)
        kl[:, slot] = np.asarray(jax.random.key_data(k_next))
        self._key_local = jax.device_put(jnp.asarray(kl),
                                         self._stage_sh)
        if self.spec_tokens is not None:
            tl = np.array(self._tok_local)
            tl[:, slot] = tok0
            self._tok_local = jax.device_put(jnp.asarray(tl),
                                             self._stage_sh)
            row = np.full(self._hist_local.shape[-1],
                          self.gen.pad_token_id, np.int32)
            row[:plen] = np.asarray(prompt, np.int32)
            row[plen] = tok0
            hl = np.array(self._hist_local)
            hl[:, slot, :] = row
            self._hist_local = jax.device_put(jnp.asarray(hl),
                                              self._stage_sh)

    def _prefill_paged(self, slot: int, prompt: Sequence[int], seed: int,
                       max_new_tokens: int) -> int:
        """Admit into the pool (reserving full demand), run the COW
        forks, stream the prompt's recompute tail through the one chunk
        program (one serial ring pass per chunk), then arm the host
        admission tables exactly as the slab prefill does. A failure
        mid-stream releases the reservation and unpublishes half-written
        cache entries."""
        plen = len(prompt)
        adm = self.pool.admit(slot, prompt, max_new_tokens,
                              chunk=self.prefill_chunk)
        try:
            for src, dst in adm.cow_forks:
                self._caches = self._fork_jit(
                    self._caches, jnp.int32(src), jnp.int32(dst))
            run = self._programs.get("chunk")
            if run is None:
                run = self._build("chunk")
                self._programs["chunk"] = run
            trow = jnp.asarray(adm.table)
            C = self.prefill_chunk
            pad = self.gen.pad_token_id
            k1, sub = jax.random.split(jax.random.key(seed))
            t = adm.resume_from
            tok0 = 0
            while t < plen:
                toks = list(prompt[t:t + C])
                toks += [pad] * (C - len(toks))
                arr = jnp.asarray(toks, jnp.int32)[None, :]
                self._caches, tok0 = run(
                    self._stage_params, self._pre, self._post,
                    self._caches, arr, jnp.int32(t), jnp.int32(plen),
                    trow, sub)
                t += C
            tok0 = int(tok0)
        except Exception:
            self.pool.release(slot, failed=True)
            raise
        self._arm_slot(slot, plen, tok0, k1, prompt)
        pl = np.array(self._pos_local)
        pl[:, slot] = plen
        self._pos_local = jax.device_put(jnp.asarray(pl), self._stage_sh)
        return tok0

    def decode(self, live: np.ndarray,
               budgets: Optional[np.ndarray] = None,
               r_max: Optional[int] = None):
        """One tick = ``revolutions`` tokens per live slot. Returns
        ``(tokens [S, R], valid [S, R])``; validity accounts for
        admission wavefronts still filling the ring.

        With ``budgets`` on a resident backend the call runs the
        RESIDENT loop: up to ``r_max`` revolutions in one device
        program with on-device done-masking and early exit. Without
        ``budgets`` the single-launch path runs even when
        ``resident=True`` — the parity reference. Speculative slots
        are resident-only (the wavefront needs the on-device done
        mask), so spec mode requires ``budgets``."""
        if self.spec_tokens is not None and budgets is None:
            raise ValueError(
                "ring speculative decode is resident-only: pass "
                "budgets so the K-token wavefront can done-mask on "
                "device")
        if self.resident and budgets is not None:
            return self._decode_resident(live, budgets, r_max)
        n, R = self.n, self.decode_chunk
        live = np.asarray(live).astype(np.int32)
        kind = "decode_paged" if self.paged else "decode"
        run = self._programs.get(kind)
        if run is None:
            run = self._build(kind)
            self._programs[kind] = run
        args = (
            self._stage_params, self._pre, self._post, self._caches,
            self._h, self._tok_ring, self._pos_local, self._key_local,
            jnp.int32(self._c0), jnp.asarray(self._admit),
            jnp.asarray(live), jnp.asarray(self._tok_inject))
        if self.paged:
            args = args + (jnp.asarray(self.pool.table),)
        caches, h, tok_ring, pos_local, key_local, emitted = run(*args)
        self._caches, self._h = caches, h
        self._tok_ring, self._pos_local = tok_ring, pos_local
        self._key_local = key_local
        toks = np.asarray(emitted)                       # [n, R]
        g = np.arange(n)[:, None]
        r = np.arange(R)[None, :]
        emit_cycle = self._c0 + r * n + (g + n - 1) % n
        valid = (live[:, None] != 0) & \
            (emit_cycle >= self._admit[:, None] + n - 1)
        self._c0 += n * R
        if self._c0 > _REBASE:
            shift = self._c0
            self._c0 = 0
            self._admit = np.maximum(
                self._admit - shift, -np.int32(_REBASE)).astype(np.int32)
        return toks, valid

    def _decode_resident(self, live: np.ndarray, budgets: np.ndarray,
                         r_max: Optional[int]):
        """One resident launch: up to ``r_max`` revolutions on device,
        ONE host sync (the revolution count) to size the readout."""
        if self.spec_tokens is not None:
            return self._decode_resident_spec(live, budgets, r_max)
        reg = get_registry()
        n, R = self.n, self.resident_revolutions
        rm = R if r_max is None else max(1, min(int(r_max), R))
        live = np.asarray(live).astype(np.int32)
        kind = "resident_paged" if self.paged else "resident"
        run = self._programs.get(kind)
        if run is None:
            run = self._build(kind)
            self._programs[kind] = run
        args = (
            self._stage_params, self._pre, self._post, self._caches,
            self._h, self._tok_ring, self._pos_local, self._key_local,
            jnp.int32(self._c0), jnp.asarray(self._admit),
            jnp.asarray(live), jnp.asarray(self._tok_inject))
        if self.paged:
            args = args + (jnp.asarray(self.pool.table),)
        args = args + (jnp.asarray(np.asarray(budgets, np.int32)),
                       jnp.int32(rm))
        (caches, h, tok_ring, pos_local, key_local, emitted,
         r_ran) = run(*args)
        self._caches, self._h = caches, h
        self._tok_ring, self._pos_local = tok_ring, pos_local
        self._key_local = key_local
        r_ran = int(r_ran)                   # THE host sync
        if r_ran < rm:
            reg.counter("serve.engine.device_exits").inc()
        toks = np.asarray(emitted)[:, :r_ran]
        g = np.arange(n)[:, None]
        r = np.arange(r_ran)[None, :]
        emit_cycle = self._c0 + r * n + (g + n - 1) % n
        valid = (live[:, None] != 0) & \
            (emit_cycle >= self._admit[:, None] + n - 1)
        self._c0 += n * r_ran
        if self._c0 > _REBASE:
            shift = self._c0
            self._c0 = 0
            self._admit = np.maximum(
                self._admit - shift, -np.int32(_REBASE)).astype(np.int32)
        return toks, valid

    def _decode_resident_spec(self, live: np.ndarray,
                              budgets: np.ndarray,
                              r_max: Optional[int]):
        """Spec resident launch: the readout is a ``[S, r*K]`` token
        grid with per-round accepted counts. Validity comes from the
        counts alone — stage 0 only records completions for admitted
        groups, so there is no admission arithmetic to redo here."""
        reg = get_registry()
        n, R, K = self.n, self.resident_revolutions, self.spec_tokens
        rm = R if r_max is None else max(1, min(int(r_max), R))
        live = np.asarray(live).astype(np.int32)
        kind = "resident_spec_paged" if self.paged else "resident_spec"
        run = self._programs.get(kind)
        if run is None:
            run = self._build(kind)
            self._programs[kind] = run
        args = (
            self._stage_params, self._pre, self._post, self._caches,
            self._spec_msg, self._tok_local, self._pos_local,
            self._key_local, self._hist_local,
            jnp.int32(self._c0), jnp.asarray(self._admit),
            jnp.asarray(live))
        if self.paged:
            args = args + (jnp.asarray(self.pool.table),)
        args = args + (jnp.asarray(np.asarray(budgets, np.int32)),
                       jnp.int32(rm))
        (caches, msg, tok_local, pos_local, key_local, hist_local,
         emitted, counts, r_ran) = run(*args)
        self._caches, self._spec_msg = caches, msg
        self._tok_local, self._pos_local = tok_local, pos_local
        self._key_local, self._hist_local = key_local, hist_local
        r_ran = int(r_ran)                   # THE host sync
        if r_ran < rm:
            reg.counter("serve.engine.device_exits").inc()
        counts = np.asarray(counts)[:, :r_ran]           # [n, r]
        toks = np.asarray(emitted)[:, :r_ran * K]        # [n, r*K]
        valid = (np.arange(K)[None, None, :]
                 < counts[:, :, None]).reshape(n, r_ran * K)
        valid &= live[:, None] != 0
        self._c0 += n * r_ran
        if self._c0 > _REBASE:
            shift = self._c0
            self._c0 = 0
            self._admit = np.maximum(
                self._admit - shift, -np.int32(_REBASE)).astype(np.int32)
        # spec telemetry, the single-device lane's exact surface (no
        # EWMA row — the ring has no adaptive ladder)
        lmask = live != 0
        lc = counts[lmask]
        rounds = int((lc > 0).sum())
        emitted_n = int(lc.sum())
        reg.counter("serve.engine.spec_rounds").inc(rounds)
        reg.counter("serve.engine.spec_emitted").inc(emitted_n)
        self._spec_acc_total += max(emitted_n - rounds, 0)
        self._spec_draft_total += rounds * (K - 1)
        if self._spec_draft_total:
            reg.gauge("serve.spec.acceptance_rate").set(
                self._spec_acc_total / self._spec_draft_total)
        reg.gauge("serve.spec.draft_cost_frac").set(
            self._drafter.draft_cost_frac(K, self.n * self._lps))
        hist_m = reg.histogram("serve.spec.accept_len")
        for v in lc[lc > 0]:
            hist_m.observe(float(v))
        return toks, valid

    def can_admit(self, prompt_len: int, max_new_tokens: int,
                  prompt: Optional[Sequence[int]] = None) -> bool:
        """Block-availability admission gate (always True for the slab —
        its reservation is the slot itself)."""
        if not self.paged:
            return True
        return self.pool.can_admit(prompt_len, max_new_tokens, prompt,
                                   chunk=self.prefill_chunk)

    def release(self, slot: int) -> None:
        """Engine retirement hook: return the group's blocks to the pool
        (no-op for the slab — the next prefill rewrites the rows)."""
        if self.paged:
            self.pool.release(slot)

    def program_stats(self) -> dict:
        if self.paged:
            return {"prefill_programs": 1,
                    "decode_chunk": self.decode_chunk, "kv": "paged"}
        return {"prefill_programs": sum(
                    1 for k in self._programs
                    if isinstance(k, tuple) and k[0] == "prefill"),
                "decode_chunk": self.decode_chunk, "kv": "slab"}
