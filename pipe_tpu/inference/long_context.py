"""Long-context decoding: the prompt's KV cache stays context-sharded.

The long-context training story (ring attention over a ``context`` mesh
axis, ``ops/ring_attention.py``) has an inference counterpart: a prompt
too long for one chip's HBM must be PREFILLED sharded — and then its KV
cache IS the sharded object, so decode must attend across shards. This
module implements exactly that:

* **prefill**: each context device embeds its sequence shard (global
  position offsets), runs the blocks with ``ring_attention`` for the
  attention output (exact, block-sized peak memory), and keeps its LOCAL
  K/V rows as the prompt cache — no gather, each device permanently owns
  ``1/n_context`` of the prompt cache;
* **decode**: the new token's query is tiny, so it replicates; every
  device computes a streaming-softmax PARTIAL (numerator, normalizer,
  running max) over its prompt-cache shard, device 0 adds the partial
  over the (short, replicated) decode-time cache, and one
  ``pmax``/``psum`` pair merges the partials — the distributed
  flash-attention combine. Everything else (FFN, LN, head, sampling) is
  replicated compute on a [b, 1, d] activation: negligible next to the
  sharded cache read, and it keeps the program free of host round-trips.

Memory: per device, prompt cache = ``prompt/n_context`` rows + decode
cache = ``max_new`` rows. The decode-time traffic is one tiny
collective per layer per token over ICI.

``tests/test_long_context_gen.py`` pins greedy output token-for-token
against the single-device :class:`~.generate.Generator` on the SAME
weights (the two programs share parameter trees via ``PipelinedLM.init``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..models.long_context_lm import ContextParallelLM
from ..parallel.mesh import CONTEXT_AXIS
from .generate import GenerationConfig, check_positions, sample_logits
from .quant import dequant_tree

__all__ = ["ContextShardedGenerator"]


def _partial_attend(q, k, v, mask, scale):
    """Streaming-softmax partial of ``q`` over masked keys.

    q: [b, 1, h, hd]; k/v: [b, S, h, hd]; mask: [S] bool (which rows are
    live). Returns (o [b,1,h,hd] f32, m [b,h,1] f32, l [b,h,1] f32) — an
    UNnormalized numerator with its own max and normalizer, mergeable with
    other partials by the usual flash combine.
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    logits = jnp.where(mask[None, None, None, :], logits,
                       jnp.asarray(-jnp.inf, logits.dtype))
    m = jnp.max(logits, axis=-1)                     # [b, h, 1]
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.exp(logits - safe_m[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    l = jnp.sum(p, axis=-1)                          # [b, h, 1]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(
        jnp.float32)
    return o, m, l


def _merge_partials(parts):
    """Merge [(o, m, l), ...] partials locally (flash combine)."""
    o, m, l = parts[0]
    for o2, m2, l2 in parts[1:]:
        new_m = jnp.maximum(m, m2)
        safe = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        a1 = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
        a2 = jnp.where(jnp.isfinite(m2), jnp.exp(m2 - safe), 0.0)
        o = o * a1.transpose(0, 2, 1)[..., None] \
            + o2 * a2.transpose(0, 2, 1)[..., None]
        l = l * a1 + l2 * a2
        m = new_m
    return o, m, l


def _global_combine(o, m, l, axis):
    """psum/pmax the partials over the context axis and normalize."""
    M = jax.lax.pmax(m, axis)
    safe = jnp.where(jnp.isfinite(M), M, 0.0)
    alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - safe), 0.0)
    num = jax.lax.psum(o * alpha.transpose(0, 2, 1)[..., None], axis)
    den = jax.lax.psum(l * alpha, axis)
    return num / jnp.maximum(den, 1e-30).transpose(0, 2, 1)[..., None]


class ContextShardedGenerator:
    """KV-cached decoding with the prompt cache sharded over ``context``.

    ``model`` is a :class:`ContextParallelLM`; params come from
    ``model.init`` (identical trees to the single-device LM — serve what
    you trained). The prompt length must divide by the context-axis size.
    """

    def __init__(self, mesh: Mesh, model: ContextParallelLM,
                 gen_cfg: GenerationConfig = GenerationConfig()):
        if CONTEXT_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh must have a {CONTEXT_AXIS!r} axis")
        self.mesh = mesh
        self.model = model
        self.gen_cfg = gen_cfg
        self.n_ctx = mesh.shape[CONTEXT_AXIS]
        self._programs = {}

    # --- per-layer math (mirrors ContextParallelLM._block exactly) ---

    def _proj(self, bp, h):
        cfg = self.model.cfg
        rows, s, d = h.shape
        hd = d // cfg.nhead

        def one(w, b):
            return (jnp.einsum("bsd,de->bse", h, w) + b).reshape(
                rows, s, cfg.nhead, hd)

        a = bp["attn"]
        return (one(a["wq"], a["bq"]), one(a["wk"], a["bk"]),
                one(a["wv"], a["bv"]))

    def _post_attn(self, bp, h, a):
        L = self.model._layers
        rows, s, d = h.shape
        a = a.reshape(rows, s, d)
        a = jnp.einsum("bsd,de->bse", a, bp["attn"]["wo"]) + bp["attn"]["bo"]
        x = L["ln"].apply(bp["ln1"], h + a)
        f = jax.nn.relu(L["ff1"].apply(bp["ff1"], x))
        f = L["ff2"].apply(bp["ff2"], f)
        return L["ln"].apply(bp["ln2"], x + f)

    # --- device program ---

    def _device_program(self, stage_params, pre_params, post_params,
                        prompt, key, *, s_local):
        m, gen = self.model, self.gen_cfg
        cfg = m.cfg
        n = self.n_ctx
        cd = cfg.compute_dtype
        max_new = gen.max_new_tokens
        idx = jax.lax.axis_index(CONTEXT_AXIS)
        nh, hd = cfg.nhead, cfg.d_model // cfg.nhead
        scale = 1.0 / math.sqrt(hd)
        b = prompt.shape[0]
        s_global = s_local * n

        from .quant import QuantLeaf
        blocks = [jax.tree_util.tree_map(
                      lambda p: p if isinstance(p, QuantLeaf)
                      else p.astype(cd),
                      bp, is_leaf=lambda x: isinstance(x, QuantLeaf))
                  for stage in stage_params for bp in stage]
        L = len(blocks)

        # ---- prefill: ring attention for outputs, local K/V kept as the
        # permanently-sharded prompt cache
        from ..ops.ring_attention import ring_attention
        h = m.pre_fn(pre_params, prompt, None)
        pk = jnp.zeros((L, b, s_local, nh, hd), cd)
        pv = jnp.zeros((L, b, s_local, nh, hd), cd)
        for l, bp in enumerate(blocks):
            bp = dequant_tree(bp, cd)
            q, k, v = self._proj(bp, h)
            a = ring_attention(q, k, v, CONTEXT_AXIS, causal=cfg.causal)
            pk = pk.at[l].set(k.astype(cd))
            pv = pv.at[l].set(v.astype(cd))
            h = self._post_attn(bp, h, a)
        # first token: logits of the LAST global position (device n-1)
        logits = self._head(post_params, h[:, -1:, :])[:, 0, :]
        key, sub = jax.random.split(key)
        tok = sample_logits(logits, sub, gen)
        tok = jax.lax.psum(jnp.where(idx == n - 1, tok, 0), CONTEXT_AXIS)

        # ---- decode: replicated q, sharded prompt cache, replicated
        # decode cache (device 0 owns its attention contribution)
        dk0 = jnp.zeros((L, b, max_new, nh, hd), cd)
        dv0 = jnp.zeros((L, b, max_new, nh, hd), cd)
        block_stack = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *blocks)
        prompt_mask = jnp.ones((s_local,), bool)

        def step(carry, t):
            dk, dv, tok, key = carry
            pos = s_global + t
            h = m._posenc(
                m._layers["embed"].apply(pre_params["embed"], tok[:, None]),
                pos).astype(cd)

            def layer(h_c, inp):
                bp, pkl, pvl, dkl, dvl = inp
                bp = dequant_tree(bp, cd)
                q, k, v = self._proj(bp, h_c)
                dkl = jax.lax.dynamic_update_slice(
                    dkl, k.astype(cd), (0, t, 0, 0))
                dvl = jax.lax.dynamic_update_slice(
                    dvl, v.astype(cd), (0, t, 0, 0))
                p_prompt = _partial_attend(q, pkl, pvl, prompt_mask, scale)
                dec_mask = (jnp.arange(max_new) <= t) & (idx == 0)
                p_dec = _partial_attend(q, dkl, dvl, dec_mask, scale)
                o, mm, ll = _merge_partials([p_prompt, p_dec])
                a = _global_combine(o, mm, ll, CONTEXT_AXIS).astype(cd)
                return self._post_attn(bp, h_c, a), (dkl, dvl)

            h, (dk, dv) = jax.lax.scan(layer, h,
                                       (block_stack, pk, pv, dk, dv))
            logits = self._head(post_params, h)[:, 0, :]
            key, sub = jax.random.split(key)
            nxt = sample_logits(logits, sub, gen)
            return (dk, dv, nxt, key), tok

        (_, _, last, _), toks = jax.lax.scan(
            step, (dk0, dv0, tok, key), jnp.arange(max_new - 1))
        out = jnp.moveaxis(toks, 0, 1)
        return jnp.concatenate([out, last[:, None]], axis=1)

    def _head(self, post_params, h):
        w = post_params["decoder"]["w"]
        bb = post_params["decoder"]["b"]
        return (jnp.einsum("bsd,dv->bsv", h.astype(jnp.float32),
                           w.astype(jnp.float32)) + bb)

    # --- beam search over the sharded prompt cache -------------------

    def _device_program_beam(self, stage_params, pre_params, post_params,
                             prompt, *, s_local):
        """Context-sharded beam search (deterministic, sum-of-log-probs
        — the single-device ``Generator._generate_beam`` contract).

        The TPU-native trick: beams of one row share the prompt, so the
        (large, context-sharded) prompt cache needs NO per-beam tiling
        and NO per-step reorder — the ``k`` beam queries ride
        ``_partial_attend``'s query axis against the SAME shard (each
        query attends all masked keys independently; there is no
        intra-query coupling to break). Only the (short, replicated)
        decode-time cache tiles to ``b*k`` rows and gathers by parent
        each step, exactly like the single-device beam. Beams flatten
        row-major (``flat = row*k + beam``).
        """
        m, gen = self.model, self.gen_cfg
        cfg = m.cfg
        k = gen.num_beams
        n = self.n_ctx
        cd = cfg.compute_dtype
        max_new = gen.max_new_tokens
        idx = jax.lax.axis_index(CONTEXT_AXIS)
        nh, hd = cfg.nhead, cfg.d_model // cfg.nhead
        scale = 1.0 / math.sqrt(hd)
        b = prompt.shape[0]
        s_global = s_local * n

        from .quant import QuantLeaf
        blocks = [jax.tree_util.tree_map(
                      lambda p: p if isinstance(p, QuantLeaf)
                      else p.astype(cd),
                      bp, is_leaf=lambda x: isinstance(x, QuantLeaf))
                  for stage in stage_params for bp in stage]
        L = len(blocks)

        # ---- prefill: identical to the greedy path (untiled rows)
        from ..ops.ring_attention import ring_attention
        h = m.pre_fn(pre_params, prompt, None)
        pk = jnp.zeros((L, b, s_local, nh, hd), cd)
        pv = jnp.zeros((L, b, s_local, nh, hd), cd)
        for l, bp in enumerate(blocks):
            bp = dequant_tree(bp, cd)
            q, kk, vv = self._proj(bp, h)
            a = ring_attention(q, kk, vv, CONTEXT_AXIS, causal=cfg.causal)
            pk = pk.at[l].set(kk.astype(cd))
            pv = pv.at[l].set(vv.astype(cd))
            h = self._post_attn(bp, h, a)
        # beam seed: logits of the LAST global position (device n-1)
        logits = self._head(post_params, h[:, -1:, :])[:, 0, :]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        sc0, tok0 = jax.lax.top_k(logp, k)                # [b, k]
        tok0 = tok0.astype(jnp.int32)
        sc0 = jax.lax.psum(jnp.where(idx == n - 1, sc0, 0.0), CONTEXT_AXIS)
        tok0 = jax.lax.psum(jnp.where(idx == n - 1, tok0, 0), CONTEXT_AXIS)

        # ---- decode: beams on the rows; prompt cache untiled
        dk0 = jnp.zeros((L, b * k, max_new, nh, hd), cd)
        dv0 = jnp.zeros((L, b * k, max_new, nh, hd), cd)
        block_stack = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *blocks)
        prompt_mask = jnp.ones((s_local,), bool)
        out0 = jnp.zeros((b, k, max_new), jnp.int32)
        out0 = out0.at[:, :, 0].set(tok0)

        def step(carry, t):
            dk, dv, scores, tok, out = carry
            pos = s_global + t
            h = m._posenc(
                m._layers["embed"].apply(pre_params["embed"],
                                         tok.reshape(b * k)[:, None]),
                pos).astype(cd)

            def layer(h_c, inp):
                bp, pkl, pvl, dkl, dvl = inp
                bp = dequant_tree(bp, cd)
                q, kk, vv = self._proj(bp, h_c)       # q: [b*k, 1, nh, hd]
                dkl = jax.lax.dynamic_update_slice(
                    dkl, kk.astype(cd), (0, t, 0, 0))
                dvl = jax.lax.dynamic_update_slice(
                    dvl, vv.astype(cd), (0, t, 0, 0))
                # prompt partial: beams ride the query axis of the shared
                # (untiled) shard — o [b, k, nh, hd], m/l [b, nh, k]
                qp = q.reshape(b, k, nh, hd)
                o_p, m_p, l_p = _partial_attend(qp, pkl, pvl, prompt_mask,
                                                scale)
                p_prompt = (o_p.reshape(b * k, 1, nh, hd),
                            m_p.transpose(0, 2, 1).reshape(b * k, nh, 1),
                            l_p.transpose(0, 2, 1).reshape(b * k, nh, 1))
                dec_mask = (jnp.arange(max_new) <= t) & (idx == 0)
                p_dec = _partial_attend(q, dkl, dvl, dec_mask, scale)
                o, mm, ll = _merge_partials([p_prompt, p_dec])
                a = _global_combine(o, mm, ll, CONTEXT_AXIS).astype(cd)
                return self._post_attn(bp, h_c, a), (dkl, dvl)

            h, (dk, dv) = jax.lax.scan(layer, h,
                                       (block_stack, pk, pv, dk, dv))
            logits = self._head(post_params, h)[:, 0, :]   # [b*k, V]
            logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
            V = logp.shape[-1]
            total = scores[:, :, None] + logp.reshape(b, k, V)
            scores, top = jax.lax.top_k(total.reshape(b, k * V), k)
            parent = (top // V).astype(jnp.int32)          # [b, k]
            tok = (top % V).astype(jnp.int32)
            flat_parent = (jnp.arange(b)[:, None] * k + parent).reshape(-1)
            dk = jnp.take(dk, flat_parent, axis=1)
            dv = jnp.take(dv, flat_parent, axis=1)
            out = jnp.take_along_axis(out, parent[:, :, None], axis=1)
            out = jax.lax.dynamic_update_slice(
                out, tok[:, :, None], (0, 0, t + 1))
            return (dk, dv, scores, tok, out), None

        if max_new > 1:
            (_, _, scores, _, out), _ = jax.lax.scan(
                step, (dk0, dv0, sc0, tok0, out0),
                jnp.arange(max_new - 1))
        else:
            scores, out = sc0, out0
        best = jnp.argmax(scores, axis=1)
        toks = jnp.take_along_axis(
            out, best[:, None, None], axis=1)[:, 0, :]
        best_sc = jnp.take_along_axis(scores, best[:, None], axis=1)[:, 0]
        return toks, best_sc

    # --- public ---

    def generate(self, params, prompt: jax.Array,
                 key: Optional[jax.Array] = None) -> jax.Array:
        """Sample ``[b, max_new_tokens]`` continuations; ``prompt
        [b, s_global]`` is context-sharded on entry (s_global divisible by
        the context-axis size). ``num_beams > 1`` runs context-sharded
        beam search (deterministic; ``key`` unused)."""
        if self.gen_cfg.num_beams > 1:
            return self.generate_with_scores(params, prompt)[0]
        stage_params, pre_params, post_params = params
        b, s_global = prompt.shape
        n = self.n_ctx
        if s_global % n:
            raise ValueError(
                f"prompt length {s_global} must divide over {n} context "
                f"shards")
        check_positions(self.model, s_global, self.gen_cfg.max_new_tokens)
        if key is None:
            key = jax.random.key(0)
        s_local = s_global // n

        cache_key = (b, s_local,
                     jax.tree_util.tree_structure(params))
        run = self._programs.get(cache_key)
        if run is None:
            in_specs = (
                jax.tree_util.tree_map(lambda _: P(), stage_params),
                jax.tree_util.tree_map(lambda _: P(), pre_params),
                jax.tree_util.tree_map(lambda _: P(), post_params),
                P(None, CONTEXT_AXIS),   # prompt: sequence-sharded
                P(),
            )
            run = jax.jit(jax.shard_map(
                functools.partial(self._device_program, s_local=s_local),
                mesh=self.mesh, in_specs=in_specs, out_specs=P(),
                check_vma=False))
            self._programs[cache_key] = run
        out = run(stage_params, pre_params, post_params,
                  jnp.asarray(prompt, jnp.int32), key)
        return out

    def generate_with_scores(self, params, prompt: jax.Array):
        """Context-sharded beam search returning ``(tokens [b, max_new],
        scores [b])`` — the best beam per row, matching the single-device
        ``Generator.generate_with_scores`` contract."""
        if self.gen_cfg.num_beams < 2:
            raise ValueError("generate_with_scores requires num_beams >= 2")
        stage_params, pre_params, post_params = params
        b, s_global = prompt.shape
        n = self.n_ctx
        if s_global % n:
            raise ValueError(
                f"prompt length {s_global} must divide over {n} context "
                f"shards")
        check_positions(self.model, s_global, self.gen_cfg.max_new_tokens)
        s_local = s_global // n

        cache_key = ("beam", b, s_local,
                     jax.tree_util.tree_structure(params))
        run = self._programs.get(cache_key)
        if run is None:
            in_specs = (
                jax.tree_util.tree_map(lambda _: P(), stage_params),
                jax.tree_util.tree_map(lambda _: P(), pre_params),
                jax.tree_util.tree_map(lambda _: P(), post_params),
                P(None, CONTEXT_AXIS),   # prompt: sequence-sharded
            )
            run = jax.jit(jax.shard_map(
                functools.partial(self._device_program_beam,
                                  s_local=s_local),
                mesh=self.mesh, in_specs=in_specs, out_specs=(P(), P()),
                check_vma=False))
            self._programs[cache_key] = run
        return run(stage_params, pre_params, post_params,
                   jnp.asarray(prompt, jnp.int32))
