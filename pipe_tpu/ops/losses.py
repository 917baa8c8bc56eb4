"""Streaming (vocab-blocked) softmax cross-entropy — the fused head+loss.

The tutorial loss path materializes ``[tokens, vocab]`` f32 logits per
micro-batch (472 MB at the 520M bench shape) just to reduce them to one
scalar per row. This module computes the SAME cross-entropy without ever
holding more than one ``[tokens, block]`` logit tile: a ``lax.scan`` over
vocab blocks carries the online logsumexp (running max + rescaled sumexp —
the flash-attention recurrence applied to the vocab axis) and picks up the
target logit when its block streams past. Peak memory for the head drops
from O(tokens x vocab) to O(tokens x block), which is what makes large
vocabularies and long sequences trainable without shrinking micro-batches.

The backward recomputes each tile (softmax(tile) - onehot) from the saved
final logsumexp — one extra pass of head FLOPs, the standard remat trade —
so the residuals are O(tokens) scalars, not logits. ``custom_vjp`` keeps
the recurrence out of JAX AD (differentiating the scan would save every
tile, defeating the point).

Numerics: block-padded columns contribute exp(-inf) = 0 to the sumexp and
zero gradient; accumulation is f32 throughout; equality with the dense
``per_row_ce``(decoder(h)) path is pinned to ~1e-5 in ``tests/test_losses
.py`` for values AND all three gradients (h, W, b).

Reference baseline: the tutorial computes CrossEntropyLoss on full logits
on the last GPU (``main.py:214-216``); this is the TPU-idiomatic fusion of
that decode+loss pair.

Measured (v5e, 520M bench config, same session): streaming is ~9% SLOWER
than the dense path (140 vs 128 ms/step at block 4096/8192) — the
backward's recompute pass costs real FLOPs and at s=128 x V=28.8k the
dense logits fit comfortably, so there is nothing to win. It is a
CAPACITY knob, not a throughput knob: reach for ``LMConfig(loss_block=)``
when ``tokens x vocab`` logits do not fit (long sequences, 100k+
vocabularies), not to speed up the tutorial config. Numerics note: tiles
multiply bf16 x bf16 with f32 accumulation when ``h`` is bf16 (the dense
path upcasts to an f32 x f32 matmul), and block size changes the f32
summation order — one-step losses agree to ~1e-5, trajectories drift at
the usual float rate.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from ..obs.events import LOSS, scoped

__all__ = ["streaming_xent"]


def _pad_blocks(w, b, block):
    d, V = w.shape
    nb = -(-V // block)
    pad = nb * block - V
    if pad:
        w = jnp.pad(w, ((0, 0), (0, pad)))
        b = jnp.pad(b, (0, pad), constant_values=-jnp.inf)
    # [nb, d, block] / [nb, block]
    return (jnp.moveaxis(w.reshape(d, nb, block), 1, 0),
            b.reshape(nb, block), nb, pad)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def streaming_xent(h, w, b, targets, block: int = 8192):
    """Per-token cross-entropy ``[*, s]`` of ``softmax(h @ w + b)`` vs
    ``targets``, streamed over vocab blocks (never materializing the full
    logits). ``h``: ``[*, s, d]`` (any float dtype; matmul accumulates
    f32); ``w``: ``[d, V]``; ``b``: ``[V]``; ``targets``: int ``[*, s]``.
    """
    ce, _ = _forward(h, w, b, targets, block)
    return ce


@scoped(LOSS)                        # head and loss fused: all of it
def _forward(h, w, b, targets, block):
    wb, bb, nb, _ = _pad_blocks(w, b, block)
    # the bf16-vs-f32 tile matmul choice falls out of h's dtype: the weight
    # tile is cast TO it below and f32 accumulation is forced either way
    hf = h
    tgt = targets.astype(jnp.int32)

    def tile_logits(k, w_blk, b_blk):
        # f32-accumulated tile: [*, s, block]
        return (jnp.einsum("...sd,db->...sb", hf, w_blk.astype(hf.dtype),
                           preferred_element_type=jnp.float32)
                + b_blk.astype(jnp.float32))

    def body(carry, xs):
        m, s, gold, k = carry
        w_blk, b_blk = xs
        z = tile_logits(k, w_blk, b_blk)
        m2 = jnp.maximum(m, z.max(axis=-1))
        s = s * jnp.exp(m - m2) + jnp.exp(z - m2[..., None]).sum(axis=-1)
        # target logit, if it lives in this block
        local = tgt - k * block
        in_blk = (local >= 0) & (local < block)
        picked = jnp.take_along_axis(
            z, jnp.clip(local, 0, block - 1)[..., None], axis=-1)[..., 0]
        gold = jnp.where(in_blk, picked, gold)
        return (m2, s, gold, k + 1), None

    m0 = jnp.full(tgt.shape, -jnp.inf, jnp.float32)
    s0 = jnp.zeros(tgt.shape, jnp.float32)
    g0 = jnp.zeros(tgt.shape, jnp.float32)
    (m, s, gold, _), _ = jax.lax.scan(body, (m0, s0, g0, 0), (wb, bb))
    lse = m + jnp.log(s)
    return lse - gold, (lse,)


def _fwd(h, w, b, targets, block):
    ce, (lse,) = _forward(h, w, b, targets, block)
    return ce, (h, w, b, targets.astype(jnp.int32), lse)


@scoped(LOSS)
def _bwd(block, res, g):
    h, w, b, tgt, lse = res
    wb, bb, nb, pad = _pad_blocks(w, b, block)
    hf = h                       # see _forward: tile dtype follows h
    d, V = w.shape

    def body(carry, xs):
        dh, k = carry
        w_blk, b_blk = xs
        z = (jnp.einsum("...sd,db->...sb", hf, w_blk.astype(hf.dtype),
                        preferred_element_type=jnp.float32)
             + b_blk.astype(jnp.float32))
        p = jnp.exp(z - lse[..., None])          # softmax tile (padded
        #                                          cols: exp(-inf)=0)
        local = tgt - k * block
        in_blk = (local >= 0) & (local < block)
        onehot = (jax.nn.one_hot(jnp.clip(local, 0, block - 1), block,
                                 dtype=jnp.float32)
                  * in_blk[..., None].astype(jnp.float32))
        dz = (p - onehot) * g[..., None]         # [*, s, block]
        dh = dh + jnp.einsum("...sb,db->...sd", dz,
                             w_blk.astype(jnp.float32))
        dw_blk = jnp.einsum("...sd,...sb->db", h.astype(jnp.float32), dz)
        db_blk = dz.reshape(-1, dz.shape[-1]).sum(axis=0)
        return (dh, k + 1), (dw_blk, db_blk)

    dh0 = jnp.zeros(h.shape[:-1] + (d,), jnp.float32)
    (dh, _), (dw_t, db_t) = jax.lax.scan(body, (dh0, 0), (wb, bb))
    # [nb, d, block] -> [d, V] (drop padding)
    dw = jnp.moveaxis(dw_t, 0, 1).reshape(d, nb * block)[:, :V]
    db = db_t.reshape(nb * block)[:V]
    return dh.astype(h.dtype), dw.astype(w.dtype), db.astype(b.dtype), None


streaming_xent.defvjp(_fwd, _bwd)
