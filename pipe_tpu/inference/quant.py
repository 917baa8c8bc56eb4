"""Weight-only int8 quantization for KV-cached decoding.

Single-sequence decode re-reads every parameter once per generated token,
so by bytes it is weight-bandwidth-bound (the 520M tutorial model streams
~1 GB of bf16 weights per step; its decode time is not measured on the
current installation). Halving the bytes halves that floor: block
weights quantize to int8 with one float32 scale per output channel
(absmax symmetric), and the dequantize (`q * scale`) happens INSIDE the
compiled decode step, where XLA fuses it into the matmul's operand read —
HBM traffic is int8-sized, the MXU still sees bf16/f32 operands.

Scope and honesty: weight-only (activations and KV caches stay in the
compute dtype), inference-only, symmetric per-channel — the standard
first rung of the quantization ladder. Per-channel absmax keeps the
worst-case relative weight error ~0.4%; the accuracy contract (trained
tiny model: teacher-forced logits within tolerance, top-1 next-token
agreement) is pinned in ``tests/test_quant.py``; the throughput effect is
what ``tools/gen_bench.py --int8`` times on the chip (not measured on the
current installation).

Mechanics: :func:`quantize_params` maps every quantizable 2-D weight leaf
to a :class:`QuantLeaf` pytree node (int8 codes + f32 scales) in the SAME
tree structure; the generators call :func:`dequant_tree` on each block's
params at use time (identity on unquantized leaves), so the layer code
never knows quantization exists.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

__all__ = ["QuantLeaf", "quantize_params", "dequant_tree",
           "quantize_kv_rows"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class QuantLeaf:
    """int8 codes + grouped float32 scales for one weight (see
    :func:`_quantize_leaf` for the exact grouping per rank)."""

    q: jax.Array        # int8, original shape
    scale: jax.Array    # f32, shape [..., 1] broadcastable over axis -2

    def dequant(self, dtype=jnp.bfloat16):
        return (self.q.astype(jnp.float32) * self.scale).astype(dtype)


def _quantize_leaf(w: jax.Array) -> QuantLeaf:
    """Symmetric absmax int8, one scale per axis(-2) group.

    For the 2-D ``[d_in, d_out]`` weights of the standard model families
    axis -2 IS the contraction axis, so this is exact per-output-channel
    absmax and the ~0.4% relative-error argument in the module docstring
    applies. For higher-rank leaves (e.g. TP's ``wqkv [d, 3, heads, hd]``,
    where axis -2 is the *head* axis) the grouping is whatever axis -2
    happens to be — dequantization is exact regardless (the scale is
    stored and multiplied back), but the per-channel accuracy bound does
    NOT transfer to those layouts; measure before serving a quantized
    >2-D-weight model."""
    w32 = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
    return QuantLeaf(q=q, scale=scale)


def quantize_params(stage_params) -> Any:
    """Quantize every >=2-D weight leaf of the (per-stage) block trees.

    Input is the ``stage_params`` list from ``model.init`` (or any block
    pytree); 1-D leaves (biases, LayerNorm params) stay float — and
    embeddings sit in pre/post params, untouched, since they are gathered
    rather than matmul'd. The returned tree has the same
    structure with weights replaced by :class:`QuantLeaf` nodes — feed it
    to the generators in place of the original stage params.
    """
    def one(leaf):
        if isinstance(leaf, (jax.Array, jnp.ndarray)) and leaf.ndim >= 2:
            return _quantize_leaf(leaf)
        return leaf

    return jax.tree_util.tree_map(one, stage_params)


def quantize_kv_rows(rows: jax.Array):
    """Symmetric absmax int8 over the last axis — one f32 scale per
    ``[..., head_dim]`` vector. The KV-block analog of
    :func:`_quantize_leaf`, used by the paged pool (``serve/kvpool.py``)
    to quantize rows on scatter; the matching dequant happens inside the
    gathered attention read. Per-row per-head scales keep the relative
    error bound of the weight path; the accuracy contract is tolerance
    (``tests/test_kvpool.py``), NOT the engine's bitwise pin."""
    r32 = rows.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(r32), axis=-1, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(r32 / scale), -127, 127).astype(jnp.int8)
    return q, scale


def dequant_tree(params, dtype=jnp.bfloat16):
    """Materialize bf16 weights from QuantLeaf nodes (identity on plain
    arrays). Called inside the compiled step so XLA fuses the dequant
    into the consuming matmul's operand read."""
    return jax.tree_util.tree_map(
        lambda x: x.dequant(dtype) if isinstance(x, QuantLeaf) else x,
        params, is_leaf=lambda x: isinstance(x, QuantLeaf))
