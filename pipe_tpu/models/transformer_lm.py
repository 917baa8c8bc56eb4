"""Tutorial-parity Transformer language model, pipelined both ways.

Workload parity with the reference driver (``main.py:101-120,139-171``):
WikiText-2 LM with Encoder (embedding + positional encoding), N ×
``TransformerEncoderLayer``, Decoder (projection to vocab); defaults emsize
2048, nhid 2048, nlayers 16, nhead 32, dropout 0.2, batch-first inputs
(``main.py:108-113``).

Two execution paths:

* :func:`build_sequential` — a heterogeneous ``Sequential`` for the ``Pipe``
  API / serial emulator (any stage split, like the reference's
  Encoder+blocks+Decoder partitions);
* :class:`PipelinedLM` — the SPMD path: homogeneous stacked transformer-block
  stages over the ``stage`` mesh axis, embed as ``pre_fn`` on stage 0 and
  decode (or per-token loss) as ``post_fn`` on stage n-1.

Mixed precision is TPU-idiomatic: params live in float32, stage compute can
run in bfloat16 (MXU native), logits/loss in float32.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.partition import StageCtx
from ..obs.events import EMBED, LOSS, scoped
from ..ops.layers import (Decoder, Embedding, PositionalEncoding, Sequential,
                          TransformerEncoderLayer)
from .common import PipelinedTransformer, per_row_ce

__all__ = ["LMConfig", "build_sequential", "PipelinedLM", "cross_entropy"]


@dataclasses.dataclass(frozen=True)
class LMConfig:
    """Tutorial hyperparameters (reference ``main.py:101-120``)."""

    vocab: int = 28782          # WikiText-2 vocab size ballpark
    d_model: int = 2048         # emsize
    nhead: int = 32
    d_ff: int = 2048            # nhid
    n_layers: int = 16
    dropout: float = 0.2
    seq_len: int = 128          # bptt
    causal: bool = True
    compute_dtype: Any = jnp.float32   # set jnp.bfloat16 on TPU
    attn_impl: str = "auto"            # auto | xla | flash (ops.layers.MHA)
    # Vocab block size for the streaming (fused head+loss) cross-entropy
    # (``ops/losses.streaming_xent``): the [tokens, vocab] logits never
    # materialize — peak head memory drops to O(tokens x block) at the
    # cost of one recompute pass of head FLOPs in the backward. None =
    # the dense decoder + per_row_ce path (parity default).
    loss_block: Any = None

    def tiny(self) -> "LMConfig":
        return dataclasses.replace(
            self, vocab=101, d_model=16, nhead=2, d_ff=32, n_layers=4,
            seq_len=16, dropout=0.0)


@scoped(LOSS)
def cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean token cross-entropy, float32 accumulation.

    The reference computes ``CrossEntropyLoss(output.view(-1, V), targets)``
    on the last stage's device (``main.py:216``).
    """
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(logz - gold)


# ---------------------------------------------------------------------------
# Heterogeneous Sequential path (Pipe / emulator)
# ---------------------------------------------------------------------------

def build_sequential(cfg: LMConfig) -> Sequential:
    """Encoder + N blocks + Decoder as one Sequential (reference
    ``main.py:139-157`` builds exactly this module list for ``Pipe``)."""
    layers = [
        Embedding(cfg.vocab, cfg.d_model, scale=True),
        PositionalEncoding(cfg.d_model, cfg.dropout, max_len=max(5000, cfg.seq_len)),
    ]
    for _ in range(cfg.n_layers):
        layers.append(TransformerEncoderLayer(
            cfg.d_model, cfg.nhead, cfg.d_ff, cfg.dropout, causal=cfg.causal,
            attn_impl=cfg.attn_impl))
    layers.append(Decoder(cfg.vocab))
    return Sequential(layers, name="transformer_lm")


# ---------------------------------------------------------------------------
# SPMD path: homogeneous stacked stages
# ---------------------------------------------------------------------------

class PipelinedLM(PipelinedTransformer):
    """The SPMD-ready factorization: embed | k blocks per stage | decode.

    ``init`` returns ``(stage_params, pre_params, post_params)`` where
    ``stage_params`` is a list (length n_stages) of identically-structured
    pytrees — feed through ``stack_stage_params`` and ``SpmdPipeline``.
    """

    post_key = "decoder"

    def __init__(self, cfg: LMConfig, n_stages: int):
        self.embed = Embedding(cfg.vocab, cfg.d_model, scale=True)
        self.posenc = PositionalEncoding(
            cfg.d_model, cfg.dropout, max_len=max(5000, cfg.seq_len))
        self.block = TransformerEncoderLayer(
            cfg.d_model, cfg.nhead, cfg.d_ff, cfg.dropout, causal=cfg.causal,
            attn_impl=cfg.attn_impl)
        self.decoder = Decoder(cfg.vocab)
        self.head = self.decoder  # base-class alias (init/post param slot)
        super().__init__(cfg, n_stages)

    # --- SPMD stage functions (pre adds the tutorial's posenc) ---

    def pre_fn(self, pre_params, x_mb, ctx: StageCtx):
        tokens = x_mb["tokens"] if isinstance(x_mb, dict) else x_mb
        h = self.embed.apply(pre_params["embed"], tokens, ctx=ctx)
        h = self.posenc.apply({}, h, ctx=ctx.fold(1))
        return h.astype(self.cfg.compute_dtype)

    @scoped(EMBED)
    def embed_at(self, pre_params, tokens, pos):
        """Embed tokens occupying positions ``[pos, pos+q)`` — pre_fn with
        a position offset, for incremental decoding (inference: no
        dropout)."""
        h = self.embed.apply(pre_params["embed"], tokens)
        pe = jax.lax.dynamic_slice_in_dim(
            self.posenc.pe, pos, tokens.shape[-1], axis=0)
        return (h + pe).astype(self.cfg.compute_dtype)

    @scoped(EMBED)
    def embed_tree(self, pre_params, tokens, pos, depths):
        """Embed draft-TREE chunk rows: row r of ``tokens [b, Q]`` is a
        tree node at logical position ``pos + depths[r]`` (the root sits
        at ``pos``; same-depth nodes on different branches share a
        position). :meth:`embed_at` with a per-row position gather
        instead of a contiguous slice."""
        h = self.embed.apply(pre_params["embed"], tokens)
        pe = jnp.take(self.posenc.pe, pos + depths, axis=0)
        return (h + pe).astype(self.cfg.compute_dtype)

    def max_position(self) -> int:
        """Positional capacity (sinusoid table rows) — inference guard."""
        return int(self.posenc.pe.shape[0])

    def post_fn(self, post_params, h, ctx: StageCtx):
        return self.decoder.apply(post_params["decoder"],
                                  h.astype(jnp.float32), ctx=ctx)

    def loss_post_fn(self, post_params, h, x_mb, ctx: StageCtx):
        """In-pipeline loss: per-row mean token cross-entropy [mb_rows].

        Use with ``SpmdPipeline(post_with_batch=True)`` and
        ``x = {"tokens": [m,mb,seq], "targets": [m,mb,seq]}`` — the loss is
        computed on the last stage against the matching micro-batch, so the
        [m, mb, seq, vocab] logits never materialize in HBM (the reference
        moves targets to the last GPU for the same reason, ``main.py:216``).

        With ``cfg.loss_block`` set, even the per-micro-batch
        ``[mb, seq, vocab]`` logits never materialize: the head+loss fuse
        into the vocab-streamed cross-entropy (``ops/losses``)."""
        if self.cfg.loss_block:
            from ..ops.losses import streaming_xent
            p = post_params["decoder"]
            ce = streaming_xent(h, p["w"], p["b"], x_mb["targets"],
                                int(self.cfg.loss_block))   # [mb, seq]
            return jnp.mean(ce, axis=-1)                    # [mb_rows]
        logits = self.decoder.apply(post_params["decoder"],
                                    h.astype(jnp.float32), ctx=ctx)
        return per_row_ce(logits, x_mb["targets"])  # [mb_rows]
