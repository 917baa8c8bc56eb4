"""Shared scaffolding for the pipelined model families.

Every zoo model factors the same way for the compiled executors — an embed
module on stage 0, a homogeneous ring-invariant block repeated
``layers_per_stage`` times per stage, a head on the last stage — and shares
one parameter-init key schedule (``fold_in(key, 0)`` = embed, ``1`` = head,
``2 + s*lps + l`` = block ``l`` of stage ``s``). :class:`PipelinedTransformer`
holds that scaffolding once; subclasses supply the modules, the input spec,
and the loss.
"""

from __future__ import annotations

from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from ..core.partition import StageCtx
from ..obs.events import LOSS, scoped

__all__ = ["per_row_ce", "PipelinedTransformer", "refuse_grouped"]


@scoped(LOSS)
def per_row_ce(logits, targets, weights=None):
    """Per-row cross-entropy from logits (f32 accumulation).

    ``logits``: ``[rows, ..., vocab]``; ``targets``: integer ``[rows, ...]``.
    Without ``weights`` returns the mean CE over every non-row axis (or the
    bare CE when targets are scalar per row); with ``weights`` (same shape
    as targets) returns the weighted mean ``sum(w*ce)/max(sum(w), 1)`` —
    BERT's masked-LM form. Always ``[rows]`` float32.
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    ce = logz - gold
    reduce_axes = tuple(range(1, ce.ndim))
    if weights is not None:
        w = weights.astype(jnp.float32)
        return jnp.sum(ce * w, axis=reduce_axes) / jnp.maximum(
            jnp.sum(w, axis=reduce_axes), 1.0)
    if reduce_axes:
        return jnp.mean(ce, axis=reduce_axes)
    return ce


def refuse_grouped(model, who: str) -> None:
    """One sentence from a path that decodes ONE stacked block over one
    kind of cache, a token a step: a model that hands its layers over in
    groups (it has ``layer_groups``) does not run there. The model says
    why it is grouped (``grouped_because``)."""
    if getattr(model, "layer_groups", None) is not None:
        why = getattr(model, "grouped_because",
                      "has layers of unlike kinds over two kinds of cache")
        raise NotImplementedError(
            f"{type(model).__name__} {why}, and {who} decodes one stacked "
            f"block over one cache, a token a step: serve it with "
            f"SingleDeviceSlotBackend's slab cache")


class PipelinedTransformer:
    """Base factorization: embed | k blocks per stage | head.

    Subclass contract: set ``cfg`` (with ``n_layers`` and
    ``compute_dtype``), ``embed``, ``block``, ``head`` modules and
    ``input_key`` (the x_mb dict key feeding the embed) before calling
    ``super().__init__(cfg, n_stages)``; override :meth:`x_spec` /
    :meth:`h_spec` when the input is not ``[1, seq_len]`` int tokens; define
    ``loss_post_fn``. ``init`` returns
    ``(stage_params, pre_params, post_params)`` ready for
    ``stack_stage_params`` (or ``stack_interleaved_params``).
    """

    input_key = "tokens"
    post_key = "head"

    def __init__(self, cfg, n_stages: int):
        if cfg.n_layers % n_stages:
            raise ValueError(
                f"n_layers={cfg.n_layers} must divide into "
                f"n_stages={n_stages} (use Pipe for uneven splits)")
        self.cfg = cfg
        self.n_stages = n_stages
        self.layers_per_stage = cfg.n_layers // n_stages

    # --- specs (override for non-token inputs) ---

    def x_spec(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((1, self.cfg.seq_len), jnp.int32)

    def h_spec(self) -> jax.ShapeDtypeStruct:
        cfg = self.cfg
        return jax.ShapeDtypeStruct((1, cfg.seq_len, cfg.d_model),
                                    jnp.float32)

    # --- params ---

    def init(self, key: jax.Array):
        h = self.h_spec()
        pre_params = {"embed": self.embed.init(jax.random.fold_in(key, 0),
                                               self.x_spec())}
        post_params = {self.post_key: self.head.init(
            jax.random.fold_in(key, 1), h)}
        stage_params: List[Any] = []
        for s in range(self.n_stages):
            blocks = []
            for l in range(self.layers_per_stage):
                lkey = jax.random.fold_in(
                    key, 2 + s * self.layers_per_stage + l)
                blocks.append(self.block.init(lkey, h))
            stage_params.append(blocks)
        return stage_params, pre_params, post_params

    # --- SPMD stage functions ---

    def pre_fn(self, pre_params, x_mb, ctx: StageCtx):
        leaf = x_mb[self.input_key] if isinstance(x_mb, dict) else x_mb
        return self.embed.apply(pre_params["embed"], leaf, ctx=ctx)

    def stage_fn(self, blocks, h, ctx: StageCtx):
        cd = self.cfg.compute_dtype
        for l, bp in enumerate(blocks):
            bp = jax.tree_util.tree_map(lambda p: p.astype(cd), bp)
            h = self.block.apply(bp, h, ctx=ctx.fold(l))
        return h

    def num_params(self, params_tuple) -> int:
        return sum(int(p.size)
                   for p in jax.tree_util.tree_leaves(params_tuple))
