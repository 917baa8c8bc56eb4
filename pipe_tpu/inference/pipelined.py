"""Pipelined autoregressive decoding over stage-sharded parameters.

Serving a model whose weights are pipeline-sharded (each device holds ONLY
its stages' blocks — the whole point of `Pipe.shard_params`) cannot use the
single-device :class:`~.generate.Generator`: every token must traverse all
stages. Naively that serializes — one token in flight, n-1 stages idle.
This module pipelines the *requests* instead: the batch is split into
``n_stages`` groups that chase each other around the stage ring, one
ppermute per cycle (the same ICI transport as the training executors), so
in steady state every stage decodes a different group's token each cycle —
aggregate throughput of one token-group per cycle, the inference analogue
of GPipe's fill-drain (which never needs a backward, so the schedule is
just the ring).

Structure per cycle (device = stage ``s``, cycle ``c``, group
``(c - s) mod n``): stage 0 embeds the group's current token (first
revolution: the prefill's sampled token, afterwards the token arriving on
the wrap edge), every stage runs its blocks through the KV caches it owns
for that group, stage n-1 samples and sends the token around the wrap to
stage 0 — which needs it exactly at cycle ``c+1``, when that group's next
revolution begins. A prefill phase first walks each group's prompt through
the ring once (q=prompt_len), filling cache rows ``[0, p)``.

Static-shape discipline: invalid fill/drain cycles write their garbage
K/V rows into a sacrificial cache region past ``p + max_new`` and their
garbage tokens into a sentinel output column (the executors' masked-slot
trick, ``parallel/buffers.py``) — no per-cycle ``lax.cond``, no dynamic
shapes. Known cost: the active group's cache slab is sliced out and
written back each cycle (same order of HBM traffic as the attention read
itself); acceptable at decode arithmetic intensity.

``tests/test_pipelined_gen.py`` pins greedy pipelined output against the
single-device Generator token-for-token.
"""

from __future__ import annotations

import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.common import refuse_grouped
from ..obs.telemetry import get_registry
from ..parallel.mesh import STAGE_AXIS
from .generate import (GenerationConfig, check_positions, head_logits,
                       sample_logits, sequence_lengths)
from .quant import QuantLeaf, dequant_tree

__all__ = ["PipelinedGenerator"]


class PipelinedGenerator:
    """Ring-pipelined KV-cache sampling over a ``stage`` mesh axis.

    ``model`` is a ``PipelinedTransformer`` LM with ``embed_at`` (see
    :class:`~.generate.Generator`); params are the training layout with
    ``stage_params`` stacked ``[n_stages, ...]`` (``stack_stage_params``)
    and sharded over ``stage`` — serve the weights exactly as trained.
    The batch must divide into ``n_stages`` groups.
    """

    def __init__(self, mesh: Mesh, model,
                 gen_cfg: GenerationConfig = GenerationConfig()):
        if STAGE_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh must have a {STAGE_AXIS!r} axis")
        if not hasattr(model, "embed_at"):
            raise TypeError(
                f"{type(model).__name__} has no embed_at; KV-cache "
                "generation needs position-offset embedding")
        refuse_grouped(model, "PipelinedGenerator (inference/pipelined.py)")
        self.mesh = mesh
        self.model = model
        self.gen_cfg = gen_cfg
        self.n_stages = mesh.shape[STAGE_AXIS]
        # jitted device programs keyed by (prompt_len, rows_per_group,
        # param treedef): jit caches by callable identity, and shard_map +
        # partial build fresh callables — without this cache every
        # generate() call would retrace AND recompile
        self._programs = {}

    # --- internals ---

    def _ring(self, x):
        n = self.n_stages
        return jax.lax.ppermute(x, STAGE_AXIS,
                                [(i, (i + 1) % n) for i in range(n)])

    def _head(self, post_params, h):
        return head_logits(self.model, post_params, h)

    def _run_blocks(self, block_stack, h, caches, grp, pos):
        """Run this stage's blocks on ``h`` against group ``grp``'s cache
        slab; returns (h, updated caches). ``caches``: pytree of
        ``[lps, n_groups, rpg, cache_len, nh, hd]``."""
        m = self.model
        cd = m.cfg.compute_dtype
        lps = jax.tree_util.tree_leaves(caches)[0].shape[0]

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

    def _device_program(self, stage_params, pre_params, post_params,
                        prompt_g, key, *, p, rpg):
        m, gen, n = self.model, self.gen_cfg, self.n_stages
        max_new = gen.max_new_tokens
        s = jax.lax.axis_index(STAGE_AXIS)
        cd = m.cfg.compute_dtype
        nh, hd = m.block.attn.nhead, m.block.attn.head_dim
        # sacrificial region: p rows past the live prefix absorbs garbage
        # writes from fill/drain cycles (prefill writes q=p rows at once)
        cache_len = p + max_new + p
        sac = p + max_new

        def local_slice(a):
            # this device's stage slice (leading dim n/n_devices == 1);
            # QuantLeaf nodes slice through their children, stay quantized
            if isinstance(a, QuantLeaf):
                return QuantLeaf(q=a.q[0], scale=a.scale[0])
            return a[0].astype(cd)

        blocks = [jax.tree_util.tree_map(
                      local_slice, bp,
                      is_leaf=lambda x: isinstance(x, QuantLeaf))
                  for bp in stage_params]
        block_stack = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *blocks)
        lps = len(blocks)
        caches = {"k": jnp.zeros((lps, n, rpg, cache_len, nh, hd), cd),
                  "v": jnp.zeros((lps, n, rpg, cache_len, nh, hd), cd)}

        def pre_key(grp):
            return jax.random.fold_in(jax.random.fold_in(key, grp), 0)

        def dec_key(grp, t):
            return jax.random.fold_in(jax.random.fold_in(key, grp), t + 1)

        # ---- prefill: each group's prompt rides the ring once (q = p)
        def pre_cycle(carry, c):
            h_carry, caches, init_toks = carry
            raw = c - s
            valid = (raw >= 0) & (raw < n)
            grp = jnp.clip(raw, 0, n - 1)
            pos = jnp.where(valid, 0, sac)
            h_embed = m.embed_at(pre_params,
                                 jnp.take(prompt_g, grp, axis=0), 0)
            h_in = jnp.where(s == 0, h_embed, h_carry)
            h_out, caches = self._run_blocks(block_stack, h_in, caches,
                                             grp, pos)
            logits = self._head(post_params, h_out[:, -1:, :])[:, 0, :]
            tok = sample_logits(logits, pre_key(grp), gen)
            emit = (s == n - 1) & valid
            old = jnp.take(init_toks, grp, axis=0)
            init_toks = jax.lax.dynamic_update_slice(
                init_toks, jnp.where(emit, tok, old)[None], (grp, 0))
            return (self._ring(h_out), caches, init_toks), None

        h0 = jnp.zeros((rpg, p, m.cfg.d_model), cd)
        init_toks = jnp.zeros((n, rpg), jnp.int32)
        (_, caches, init_toks), _ = jax.lax.scan(
            pre_cycle, (h0, caches, init_toks), jnp.arange(2 * n - 1))
        # only stage n-1 sampled real tokens; replicate its table
        init_toks = jax.lax.psum(
            jnp.where(s == n - 1, init_toks, 0), STAGE_AXIS)

        # EOS: Python-level gate so eos_token_id=None traces the exact
        # pre-EOS program. Every stage carries its own done table, but
        # only stage n-1's chain is consulted (its tokens ride the wrap
        # edge and fill `out`); the other stages' updates track garbage
        # samples harmlessly.
        eos = gen.eos_token_id

        # ---- decode: one token-group per cycle in steady state (q = 1)
        def dec_cycle(carry, c):
            if eos is None:
                h_carry, tok_ring, caches, out = carry
            else:
                h_carry, tok_ring, caches, out, done = carry
            raw = c - s
            valid = (raw >= 0) & (raw < n * max_new)
            grp = jnp.mod(raw, n)
            t = jnp.where(valid, raw // n, 0)
            pos = jnp.where(valid, p + t, sac)
            tok_use = jnp.where(c < n, jnp.take(init_toks, grp, axis=0),
                                tok_ring)
            h_embed = m.embed_at(pre_params, tok_use[:, None], pos)
            h_in = jnp.where(s == 0, h_embed, h_carry)
            h_out, caches = self._run_blocks(block_stack, h_in, caches,
                                             grp, pos)
            logits = self._head(post_params, h_out)[:, 0, :]
            tok_out = sample_logits(logits, dec_key(grp, t), gen)
            if eos is not None:
                done_g = jnp.take(done, grp, axis=0)
                tok_out = jnp.where(done_g, jnp.int32(gen.pad_token_id),
                                    tok_out)
                done = jax.lax.dynamic_update_slice(
                    done, (done_g | (tok_out == jnp.int32(eos)))[None],
                    (grp, 0))
            emit = (s == n - 1) & valid
            # slot t holds the token SAMPLED while processing decode index
            # t — i.e. generated token t+1 (the assembly below prepends
            # init_toks as generated token 0 and drops the last sample,
            # which is never re-embedded, mirroring Generator's scan)
            t_write = jnp.where(emit, t, max_new)
            out = jax.lax.dynamic_update_slice(
                out, tok_out[None, :, None], (grp, 0, t_write))
            ring_out = (self._ring(h_out), self._ring(tok_out), caches,
                        out)
            if eos is not None:
                ring_out = ring_out + (done,)
            return ring_out, None

        h0 = jnp.zeros((rpg, 1, m.cfg.d_model), cd)
        out = jnp.zeros((n, rpg, max_new + 1), jnp.int32)
        cycles = n * max_new + n - 1
        carry0 = (h0, jnp.zeros((rpg,), jnp.int32), caches, out)
        if eos is not None:
            carry0 = carry0 + (init_toks == jnp.int32(eos),)
        carry_out, _ = jax.lax.scan(dec_cycle, carry0, jnp.arange(cycles))
        out = carry_out[3]
        # tokens ENTERING each step are init_toks (t=0 slot) shifted by the
        # sampled stream: out[g, :, t] holds the token sampled AT decode
        # index t, i.e. generated token t+1; generated token 0 is
        # init_toks[g]. Assemble [n_groups, rpg, max_new].
        gen_toks = jnp.concatenate(
            [init_toks[:, :, None], out[:, :, :max_new - 1]], axis=2)
        return jax.lax.psum(jnp.where(s == n - 1, gen_toks, 0), STAGE_AXIS)

    # --- beam search over the ring -----------------------------------

    def _device_program_beam(self, stage_params, pre_params, post_params,
                             prompt_g, *, p, rpg):
        """Ring-pipelined beam search (deterministic, sum-of-log-probs —
        the single-device ``Generator._generate_beam`` contract over
        stage-sharded weights).

        The pipelined twist is the cache reorder: after stage ``n-1``'s
        top-k for group ``g`` at decode index ``t``, the surviving-beam
        parent indices must reach EVERY stage's cache slab before that
        group's step ``t+1`` — so the parent vector rides the ring with
        the activation carrier (one extra [rpg*k] int32 per hop), and
        each stage gathers its own slab rows by the arriving parents
        right before decoding. The wrap edge carries (token, parent)
        from stage n-1 to stage 0, which needs them exactly one cycle
        later — the same timing argument as the greedy path's token.

        Beams flatten row-major (``flat = row*k + beam``, matching the
        single-device cache tiling); prefill runs untiled (rpg rows) and
        the slabs tile ``rpg -> rpg*k`` once, after the prefill scan.
        """
        m, gen, n = self.model, self.gen_cfg, self.n_stages
        k = gen.num_beams
        max_new = gen.max_new_tokens
        s = jax.lax.axis_index(STAGE_AXIS)
        cd = m.cfg.compute_dtype
        nh, hd = m.block.attn.nhead, m.block.attn.head_dim
        cache_len = p + max_new + p
        sac = p + max_new

        def local_slice(a):
            if isinstance(a, QuantLeaf):
                return QuantLeaf(q=a.q[0], scale=a.scale[0])
            return a[0].astype(cd)

        blocks = [jax.tree_util.tree_map(
                      local_slice, bp,
                      is_leaf=lambda x: isinstance(x, QuantLeaf))
                  for bp in stage_params]
        block_stack = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *blocks)
        lps = len(blocks)
        caches = {"k": jnp.zeros((lps, n, rpg, cache_len, nh, hd), cd),
                  "v": jnp.zeros((lps, n, rpg, cache_len, nh, hd), cd)}

        # ---- prefill: untiled (rpg rows), identical to the greedy path
        # except stage n-1 seeds the beam state instead of sampling
        def pre_cycle(carry, c):
            h_carry, caches, tok0, sc0 = carry
            raw = c - s
            valid = (raw >= 0) & (raw < n)
            grp = jnp.clip(raw, 0, n - 1)
            pos = jnp.where(valid, 0, sac)
            h_embed = m.embed_at(pre_params,
                                 jnp.take(prompt_g, grp, axis=0), 0)
            h_in = jnp.where(s == 0, h_embed, h_carry)
            h_out, caches = self._run_blocks(block_stack, h_in, caches,
                                             grp, pos)
            logits = self._head(post_params, h_out[:, -1:, :])[:, 0, :]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            sc_g, tok_g = jax.lax.top_k(logp, k)          # [rpg, k]
            emit = (s == n - 1) & valid
            tok0 = jax.lax.dynamic_update_slice(
                tok0, jnp.where(emit, tok_g.astype(jnp.int32),
                                jnp.take(tok0, grp, axis=0))[None],
                (grp, 0, 0))
            sc0 = jax.lax.dynamic_update_slice(
                sc0, jnp.where(emit, sc_g,
                               jnp.take(sc0, grp, axis=0))[None],
                (grp, 0, 0))
            return (self._ring(h_out), caches, tok0, sc0), None

        h0 = jnp.zeros((rpg, p, m.cfg.d_model), cd)
        tok0 = jnp.zeros((n, rpg, k), jnp.int32)
        sc0 = jnp.zeros((n, rpg, k), jnp.float32)
        (_, caches, tok0, sc0), _ = jax.lax.scan(
            pre_cycle, (h0, caches, tok0, sc0), jnp.arange(2 * n - 1))
        tok0 = jax.lax.psum(jnp.where(s == n - 1, tok0, 0), STAGE_AXIS)
        sc0 = jax.lax.psum(jnp.where(s == n - 1, sc0, 0.0), STAGE_AXIS)

        # tile slabs rpg -> rpg*k (flat = row*k + beam)
        tile = jnp.arange(rpg * k) // k
        caches = jax.tree_util.tree_map(
            lambda a: jnp.take(a, tile, axis=2), caches)

        # ---- decode: beams ride the rows; parents ride the ring
        ident = jnp.arange(rpg * k, dtype=jnp.int32) % k   # [rpg*k] beams
        out0 = jnp.zeros((n, rpg, k, max_new), jnp.int32)
        out0 = out0.at[:, :, :, 0].set(tok0)
        scores0 = sc0                                       # [n, rpg, k]

        def dec_cycle(carry, c):
            (h_carry, par_h, tok_ring, par_ring, caches, scores,
             out) = carry
            raw = c - s
            valid = (raw >= 0) & (raw < n * (max_new - 1))
            grp = jnp.mod(raw, n)
            t = jnp.where(valid, raw // n, 0)
            pos = jnp.where(valid, p + t, sac)
            first = (c < n)      # step 0: beams seeded from the prefill
            tok_use = jnp.where(
                first, jnp.take(tok0, grp, axis=0).reshape(rpg * k),
                tok_ring)
            # parent of the beams being decoded this step (identity at
            # step 0 and on invalid cycles — never shuffle a slab whose
            # turn it is not)
            par_in = jnp.where(s == 0, par_ring, par_h)
            parent = jnp.where(first | ~valid, ident, par_in)
            flat_parent = (jnp.arange(rpg * k, dtype=jnp.int32) // k) * k \
                + parent
            # persistent beam reorder of this group's slab
            def slab_gather(a):
                grp_slab = jax.lax.dynamic_slice(
                    a, (0, grp) + (0,) * (a.ndim - 2),
                    (lps, 1) + a.shape[2:])
                reordered = jnp.take(grp_slab, flat_parent, axis=2)
                return jax.lax.dynamic_update_slice(
                    a, reordered, (0, grp) + (0,) * (a.ndim - 2))
            caches = jax.tree_util.tree_map(slab_gather, caches)

            h_embed = m.embed_at(pre_params, tok_use[:, None], pos)
            h_in = jnp.where(s == 0, h_embed, h_carry)
            h_out, caches = self._run_blocks(block_stack, h_in, caches,
                                             grp, pos)
            logits = self._head(post_params, h_out)[:, 0, :]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            V = logp.shape[-1]
            sc_g = jax.lax.dynamic_slice(scores, (grp, 0, 0),
                                         (1, rpg, k))[0]
            total = sc_g[:, :, None] + logp.reshape(rpg, k, V)
            sc_new, idx = jax.lax.top_k(total.reshape(rpg, k * V), k)
            par_new = (idx // V).astype(jnp.int32)          # [rpg, k]
            tok_new = (idx % V).astype(jnp.int32)
            emit = (s == n - 1) & valid
            scores = jax.lax.dynamic_update_slice(
                scores, jnp.where(emit, sc_new, sc_g)[None], (grp, 0, 0))
            out_g = jax.lax.dynamic_slice(
                out, (grp, 0, 0, 0), (1, rpg, k, max_new))[0]
            out_re = jnp.take_along_axis(out_g, par_new[:, :, None],
                                         axis=1)
            t_write = jnp.where(emit, t + 1, max_new)
            # out-of-range start clamps, so route the garbage write to a
            # full-copy no-op instead: keep out_g when not emitting
            out_wr = jax.lax.dynamic_update_slice(
                out_re, tok_new[:, :, None], (0, 0, t_write))
            out = jax.lax.dynamic_update_slice(
                out, jnp.where(emit, out_wr, out_g)[None], (grp, 0, 0, 0))
            return (self._ring(h_out), self._ring(parent),
                    self._ring(tok_new.reshape(rpg * k)),
                    self._ring(par_new.reshape(rpg * k)),
                    caches, scores, out), None

        h0 = jnp.zeros((rpg * k, 1, m.cfg.d_model), cd)
        cycles = n * (max_new - 1) + n - 1
        carry0 = (h0, ident, jnp.zeros((rpg * k,), jnp.int32), ident,
                  caches, scores0, out0)
        if max_new > 1:
            (_, _, _, _, _, scores, out), _ = jax.lax.scan(
                dec_cycle, carry0, jnp.arange(cycles))
        else:
            scores, out = scores0, out0
        best = jnp.argmax(scores, axis=2)                   # [n, rpg]
        toks = jnp.take_along_axis(
            out, best[:, :, None, None], axis=2)[:, :, 0, :]
        best_sc = jnp.take_along_axis(scores, best[:, :, None],
                                      axis=2)[:, :, 0]
        toks = jax.lax.psum(jnp.where(s == n - 1, toks, 0), STAGE_AXIS)
        best_sc = jax.lax.psum(jnp.where(s == n - 1, best_sc, 0.0),
                               STAGE_AXIS)
        return toks, best_sc

    # --- public ---

    def generate(self, stage_params, pre_params, post_params,
                 prompt: jax.Array,
                 key: Optional[jax.Array] = None) -> jax.Array:
        """Sample ``[b, max_new_tokens]`` continuations of ``prompt
        [b, prompt_len]``; rows ``[g*rpg:(g+1)*rpg]`` form ring group
        ``g``. ``num_beams > 1`` runs ring-pipelined beam search
        (deterministic; ``key`` unused)."""
        if self.gen_cfg.num_beams > 1:
            return self.generate_with_scores(stage_params, pre_params,
                                             post_params, prompt)[0]
        b, p = prompt.shape
        n = self.n_stages
        if b % n:
            raise ValueError(f"batch {b} must divide into {n} ring groups")
        check_positions(self.model, p, self.gen_cfg.max_new_tokens)
        rpg = b // n
        prompt_g = jnp.asarray(prompt, jnp.int32).reshape(n, rpg, p)
        if key is None:
            key = jax.random.key(0)

        cache_key = (p, rpg,
                     jax.tree_util.tree_structure((stage_params, pre_params,
                                                   post_params)))
        reg = get_registry()
        run = self._programs.get(cache_key)
        if run is not None:
            reg.counter("serve.pipelined.program_cache_hits").inc()
        else:
            reg.counter("serve.pipelined.program_cache_misses").inc()
            in_specs = (
                jax.tree_util.tree_map(lambda _: P(STAGE_AXIS),
                                       stage_params),
                jax.tree_util.tree_map(lambda _: P(), pre_params),
                jax.tree_util.tree_map(lambda _: P(), post_params),
                P(), P(),
            )
            run = jax.jit(jax.shard_map(
                functools.partial(self._device_program, p=p, rpg=rpg),
                mesh=self.mesh, in_specs=in_specs, out_specs=P(),
                check_vma=False))
            self._programs[cache_key] = run
        t0 = time.perf_counter()
        out = run(stage_params, pre_params, post_params, prompt_g, key)
        if reg.enabled:
            # Block for an honest wall-clock number; serving callers read
            # the tokens to host right after anyway.
            out = jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            reg.histogram("serve.pipelined.generate_sec").observe(dt)
            tokens = b * self.gen_cfg.max_new_tokens
            reg.counter("serve.pipelined.tokens").inc(tokens)
            if dt > 0:
                reg.gauge("serve.pipelined.tokens_per_sec").set(tokens / dt)
        return out.reshape(b, self.gen_cfg.max_new_tokens)

    def generate_with_lengths(self, stage_params, pre_params, post_params,
                              prompt: jax.Array,
                              key: Optional[jax.Array] = None):
        """``(tokens [b, max_new], lengths [b])`` — the pipelined analogue
        of ``Generator.generate_with_lengths``: lengths run up to and
        including the first EOS (or ``max_new_tokens`` without one)."""
        out = self.generate(stage_params, pre_params, post_params,
                            prompt, key)
        return out, sequence_lengths(out, self.gen_cfg.eos_token_id)

    def generate_with_scores(self, stage_params, pre_params, post_params,
                             prompt: jax.Array):
        """Ring-pipelined beam search returning ``(tokens [b, max_new],
        scores [b])`` — the best beam per row, matching the single-device
        ``Generator.generate_with_scores`` contract."""
        if self.gen_cfg.num_beams < 2:
            raise ValueError("generate_with_scores requires num_beams >= 2")
        b, p = prompt.shape
        n = self.n_stages
        if b % n:
            raise ValueError(f"batch {b} must divide into {n} ring groups")
        check_positions(self.model, p, self.gen_cfg.max_new_tokens)
        rpg = b // n
        prompt_g = jnp.asarray(prompt, jnp.int32).reshape(n, rpg, p)

        cache_key = ("beam", p, rpg,
                     jax.tree_util.tree_structure((stage_params, pre_params,
                                                   post_params)))
        reg = get_registry()
        run = self._programs.get(cache_key)
        if run is not None:
            reg.counter("serve.pipelined.program_cache_hits").inc()
        else:
            reg.counter("serve.pipelined.program_cache_misses").inc()
            in_specs = (
                jax.tree_util.tree_map(lambda _: P(STAGE_AXIS),
                                       stage_params),
                jax.tree_util.tree_map(lambda _: P(), pre_params),
                jax.tree_util.tree_map(lambda _: P(), post_params),
                P(),
            )
            run = jax.jit(jax.shard_map(
                functools.partial(self._device_program_beam, p=p, rpg=rpg),
                mesh=self.mesh, in_specs=in_specs, out_specs=(P(), P()),
                check_vma=False))
            self._programs[cache_key] = run
        t0 = time.perf_counter()
        toks, scores = run(stage_params, pre_params, post_params, prompt_g)
        if reg.enabled:
            toks, scores = jax.block_until_ready((toks, scores))
            dt = time.perf_counter() - t0
            reg.histogram("serve.pipelined.beam_sec").observe(dt)
            tokens = b * self.gen_cfg.max_new_tokens
            reg.counter("serve.pipelined.tokens").inc(tokens)
            if dt > 0:
                reg.gauge("serve.pipelined.tokens_per_sec").set(tokens / dt)
        return (toks.reshape(b, self.gen_cfg.max_new_tokens),
                scores.reshape(b))
