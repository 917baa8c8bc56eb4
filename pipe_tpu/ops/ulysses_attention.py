"""Ulysses-style sequence parallelism: all-to-all head<->sequence resharding.

The second long-context strategy next to :mod:`.ring_attention` (SURVEY §5
names both; the reference has neither — seq len is a plain dim,
``main.py:107``). Where the ring rotates K/V blocks and keeps queries
sequence-sharded throughout, Ulysses (DeepSpeed-Ulysses lineage, Jacobs et
al. 2023) RESHARDS around the attention itself:

* inputs arrive ``[rows, seq/c, heads, d]`` (sequence sharded over the
  ``context`` axis, like every other tensor in the stage body);
* one ``jax.lax.all_to_all`` per operand flips the sharding to
  ``[rows, seq, heads/c, d]`` — each device now holds the FULL sequence for
  ``heads/c`` heads;
* attention runs UNSHARDED per device — which means the Pallas flash kernel
  (``ops.pallas_attention``) applies as-is, something the ring's streaming
  accumulation cannot use;
* one reverse all-to-all restores sequence sharding for the rest of the
  block (FFN/LN are per-token and never notice).

Trade-offs vs the ring: communication is 4 all-to-alls of activation-sized
tensors per attention (vs n ppermute hops moving K/V twice each), requires
``heads % context == 0``, and peak memory holds one full-sequence attention
for heads/c heads; the ring keeps strictly block-sized tensors. Both are
exact. AD is free: ``all_to_all``'s transpose is the reverse all-to-all, so
``jax.grad`` through this function yields the mirrored communication
pattern.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

__all__ = ["ulysses_attention"]


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      axis_name: str, *, causal: bool = True,
                      attn_fn: Optional[Callable] = None) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis_name``.

    Args:
      q, k, v: local shards ``[rows, seq_local, heads, head_dim]`` (the
        global sequence is ``seq_local * axis_size``). ``heads`` must be
        divisible by the axis size.
      axis_name: bound mesh axis to reshard over (run under ``shard_map``).
      causal: standard causal masking over GLOBAL positions (positions are
        global after the reshard, so no offset bookkeeping is needed —
        contrast ``ring_attention``'s block-origin arithmetic).
      attn_fn: ``(q, k, v, causal) -> o`` over full-sequence inputs;
        defaults to the library's auto-selected attention (Pallas flash on
        TPU at supported lengths, XLA otherwise).

    Returns the attention output with the INPUT sharding
    (``[rows, seq_local, heads, head_dim]``).
    """
    c = jax.lax.axis_size(axis_name)
    heads = q.shape[2]
    if heads % c:
        raise ValueError(
            f"ulysses_attention needs heads % axis_size == 0, got "
            f"heads={heads}, axis_size={c}")

    def reshard(x):
        # [rows, s/c, h, d] -> [rows, s, h/c, d]: split heads, gather seq
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def unshard(x):
        # [rows, s, h/c, d] -> [rows, s/c, h, d]
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    qf, kf, vf = reshard(q), reshard(k), reshard(v)
    if attn_fn is None:
        o = _default_attention(qf, kf, vf, causal)
    else:
        o = attn_fn(qf, kf, vf, causal)
    return unshard(o.astype(q.dtype))


def _default_attention(q, k, v, causal):
    """Full-sequence attention: the SHARED auto heuristic
    (``layers.flash_auto_ok``) picks the Pallas flash kernel or the XLA
    softmax path — one crossover policy for every attention call site."""
    from .layers import dot_product_attention, flash_auto_ok

    if flash_auto_ok(q.shape[1]):
        from .pallas_attention import flash_attention
        return flash_attention(q, k, v, causal=causal)
    return dot_product_attention(q, k, v, causal=causal)
