"""GPT-2 model family, pipelined (BASELINE.json config #3: 4-stage
GPT-2-small 124M, chunks=16, skip-connection via ``@skippable``).

Architecture: learned token + position embeddings, pre-LN blocks with gelu_new (tanh-approximate GELU)
(:class:`~pipe_tpu.ops.layers.PreLNBlock`), final LayerNorm, vocab head.
The head is untied from the embedding table: tied weights would be one
parameter owned by two pipeline stages, which the reference rejects outright
(``_verify_splitting``, reference ``pipe.py:70-87``) and which an SPMD
stage-sharded layout cannot express without replication; documented
divergence from the original GPT-2.

Two factorizations, mirroring :mod:`.transformer_lm`:

* :func:`build_sequential` — layer list for ``Pipe`` (any balance, emulator
  or ``mesh=`` executor). With ``embed_skip=True`` the embedding output is
  ``@skippable``-stashed at stage 0 and popped into the final pre-head
  LayerNorm input — a cross-stage residual demonstrating the skip subsystem
  on a real model (the BASELINE config names exactly this composition).
* :class:`PipelinedGPT2` — homogeneous stage stack for the compiled
  training executors (SpmdPipeline / ScheduledPipeline / interleaved).
"""

from __future__ import annotations

import dataclasses
from typing import Any, List

import jax
import jax.numpy as jnp

from ..core.partition import StageCtx
from ..extras.skip import pop, skippable, stash
from ..obs.events import EMBED, HEAD, scoped
from ..ops.layers import (Dropout, Linear, LayerNorm, Module, PreLNBlock,
                          Sequential, spec)
from .common import PipelinedTransformer, per_row_ce

__all__ = ["GPT2Config", "build_sequential", "PipelinedGPT2"]


@dataclasses.dataclass(frozen=True)
class GPT2Config:
    """GPT-2 small by default (124M: 12 layers, d=768, 12 heads)."""

    vocab: int = 50257
    d_model: int = 768
    nhead: int = 12
    d_ff: int = 3072               # 4 * d_model
    n_layers: int = 12
    dropout: float = 0.1
    seq_len: int = 1024
    compute_dtype: Any = jnp.float32

    def tiny(self) -> "GPT2Config":
        return dataclasses.replace(
            self, vocab=101, d_model=16, nhead=2, d_ff=64, n_layers=4,
            seq_len=16, dropout=0.0)


class GPT2Embed(Module):
    """Learned token + position embeddings with embedding dropout."""

    def __init__(self, cfg: GPT2Config):
        self.cfg = cfg
        self.drop = Dropout(cfg.dropout)
        self.name = "gpt2_embed"

    def init(self, key, tokens):
        cfg = self.cfg
        kw, kp = jax.random.split(key)
        return {
            "wte": 0.02 * jax.random.normal(
                kw, (cfg.vocab, cfg.d_model), jnp.float32),
            "wpe": 0.01 * jax.random.normal(
                kp, (cfg.seq_len, cfg.d_model), jnp.float32),
        }

    @scoped(EMBED)
    def apply(self, params, tokens, ctx: StageCtx = StageCtx()):
        s = tokens.shape[-1]
        h = jnp.take(params["wte"], tokens, axis=0) + params["wpe"][:s]
        return self.drop.apply({}, h, ctx=ctx).astype(self.cfg.compute_dtype)


class GPT2Head(Module):
    """Final LayerNorm + (untied) vocab projection."""

    def __init__(self, cfg: GPT2Config):
        self.cfg = cfg
        self.ln = LayerNorm()
        self.proj = Linear(cfg.vocab, use_bias=False)
        self.name = "gpt2_head"

    def init(self, key, h):
        kl, kp = jax.random.split(key)
        h = spec(h)
        return {"ln_f": self.ln.init(kl, h), "proj": self.proj.init(kp, h)}

    @scoped(HEAD)
    def apply(self, params, h, ctx: StageCtx = StageCtx()):
        h = self.ln.apply(params["ln_f"], h.astype(jnp.float32), ctx=ctx)
        return self.proj.apply(params["proj"], h, ctx=ctx)


@skippable(stash=["gpt2_embed"])
class _StashEmbed(Module):
    def init(self, key, h):
        return {}

    def apply(self, params, h, ctx: StageCtx = StageCtx()):
        stash("gpt2_embed", h)
        return h


@skippable(pop=["gpt2_embed"])
class _JoinEmbed(Module):
    """Embedding shortcut: re-inject the stage-0 embedding right before the
    head (a cross-stage residual riding the skip subsystem's ring lanes on
    the compiled path)."""

    def init(self, key, h):
        return {}

    def apply(self, params, h, ctx: StageCtx = StageCtx()):
        return h + pop("gpt2_embed").astype(h.dtype)


def build_sequential(cfg: GPT2Config, embed_skip: bool = False) -> Sequential:
    layers: List[Module] = [GPT2Embed(cfg)]
    if embed_skip:
        layers.append(_StashEmbed())
    for _ in range(cfg.n_layers):
        layers.append(PreLNBlock(cfg.d_model, cfg.nhead, cfg.d_ff,
                                 cfg.dropout, causal=True,
                                 activation="gelu_tanh"))
    if embed_skip:
        layers.append(_JoinEmbed())
    layers.append(GPT2Head(cfg))
    return Sequential(layers, name="gpt2")


class PipelinedGPT2(PipelinedTransformer):
    """Homogeneous factorization: embed | k pre-LN blocks per stage | head."""

    def __init__(self, cfg: GPT2Config, n_stages: int):
        self.embed = GPT2Embed(cfg)
        self.block = PreLNBlock(cfg.d_model, cfg.nhead, cfg.d_ff,
                                cfg.dropout, causal=True,
                                activation="gelu_tanh")
        self.head = GPT2Head(cfg)
        super().__init__(cfg, n_stages)

    def loss_post_fn(self, post_params, h, x_mb, ctx: StageCtx):
        """Per-row mean token CE [mb_rows] — in-pipeline loss contract."""
        logits = self.head.apply(post_params["head"], h, ctx=ctx)
        return per_row_ce(logits, x_mb["targets"])

    @scoped(EMBED)
    def embed_at(self, pre_params, tokens, pos):
        """Embed tokens occupying positions ``[pos, pos+q)`` — for
        incremental decoding (inference: no dropout)."""
        p = pre_params["embed"]
        h = jnp.take(p["wte"], tokens, axis=0)
        pe = jax.lax.dynamic_slice_in_dim(p["wpe"], pos,
                                          tokens.shape[-1], axis=0)
        return (h + pe).astype(self.cfg.compute_dtype)

    def max_position(self) -> int:
        """Positional capacity (wpe rows) — inference guard contract."""
        return self.cfg.seq_len
