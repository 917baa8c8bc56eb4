"""Ring attention: exact attention over a sequence sharded across devices.

Long-context capability (SURVEY §5 "Long-context / sequence parallelism"):
absent from the reference (seq len is a plain dim, ``main.py:107``), but
first-class here. The TPU-idiomatic construction reuses the pipeline's own
transport primitive — ``jax.lax.ppermute`` over ICI — as a K/V ring:

* the sequence axis is sharded over a ``context`` mesh axis (each device
  holds ``seq/n`` query rows and one K/V block);
* ``n`` ring steps rotate the K/V block one hop per step while each device
  accumulates its queries' attention over the visiting block with the
  numerically-stable streaming-softmax (flash-attention) recurrence;
* XLA overlaps the collective-permute with the block einsums — the same
  latency hiding the pipeline relies on (SURVEY §2 native table);
* causal masking compares *global* positions derived from the block's origin
  device, so semantics match single-device causal attention exactly.

Communication: each step moves one K/V block (2·b·s_local·h·d elements) over
ICI; total traffic equals one all-gather of K/V but peak memory stays at one
block — that is the whole point vs. gathering the full sequence.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["ring_attention", "blockwise_attention_reference"]


def _block_attend(q, k, v, o, m, l, q_start, k_start, causal, scale):
    """One streaming-softmax accumulation step over a visiting K/V block.

    q: [b, sq, h, d]; k/v: [b, sk, h, d]; o: [b, sq, h, d] f32;
    m, l: [b, h, sq] f32 running max / normalizer.
    """
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        qpos = q_start + jnp.arange(sq)[:, None]
        kpos = k_start + jnp.arange(sk)[None, :]
        logits = jnp.where(qpos >= kpos, logits,
                           jnp.asarray(-jnp.inf, logits.dtype))

    block_max = jnp.max(logits, axis=-1)                      # [b,h,q]
    new_m = jnp.maximum(m, block_max)
    # fully-masked blocks: new_m can be -inf; make the shift a no-op then
    safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
    p = jnp.exp(logits - safe_m[..., None])
    p = jnp.where(jnp.isfinite(logits), p, 0.0)
    correction = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)

    l = l * correction + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v).astype(jnp.float32)
    o = o * correction.transpose(0, 2, 1)[..., None] + pv
    return o, new_m, l


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   axis_name: str, *, causal: bool = True,
                   scale: Optional[float] = None) -> jax.Array:
    """Exact multi-head attention with sequence sharded over ``axis_name``.

    Call inside ``shard_map``; ``q``/``k``/``v`` are the local shards
    ``[batch, seq_local, heads, head_dim]``. Returns the local output shard
    in ``q``'s dtype. Differentiable (AD reverses the ring automatically —
    the same property the pipeline's backward relies on, SURVEY §7).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, sq, h, d = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    o0 = jnp.zeros(q.shape, jnp.float32)
    m0 = jnp.full((b, h, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    q_start = idx * sq

    if n == 1:
        o, m, l = _block_attend(q, k, v, o0, m0, l0, q_start, 0, causal,
                                scale)
        return (o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
                ).astype(q.dtype)

    shift = [(i, (i + 1) % n) for i in range(n)]

    def step(carry, r):
        o, m, l, kb, vb = carry
        # after r hops along +1 ring, we hold the block born on device idx-r
        src = (idx - r) % n
        o, m, l = _block_attend(q, kb, vb, o, m, l, q_start,
                                src * kb.shape[1], causal, scale)
        kb = jax.lax.ppermute(kb, axis_name, shift)
        vb = jax.lax.ppermute(vb, axis_name, shift)
        return (o, m, l, kb, vb), None

    (o, m, l, _, _), _ = jax.lax.scan(step, (o0, m0, l0, k, v),
                                      jnp.arange(n))
    return (o / jnp.maximum(l, 1e-30).transpose(0, 2, 1)[..., None]
            ).astype(q.dtype)


def blockwise_attention_reference(q, k, v, *, causal=True, scale=None):
    """Single-device oracle with identical semantics (tests compare to this)."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), sk - sq)
        logits = jnp.where(mask, logits, -jnp.inf)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(v.dtype), v)
