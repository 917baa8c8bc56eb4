"""SDAR-30B-A3B-Chat (JetLM; ``model_type`` ``sdar_moe``), as the first
stage of a pipeline serves it: generation by diffusion over blocks, over
layers of 128 routed experts.

The published model: 48 like layers. Pre-norm RMSNorm; 32 query heads over
4 KV heads of 128, no biases, an RMSNorm with a gain of 128 on each head's
query and key (QK-norm) before rotary positions on the whole head (theta
1e6); every layer routes each token to 8 of 128 experts of width 768
(softmax over all 128 in float32, the eight picks renormalised), with no
shared expert; a final RMSNorm and an untied head over 151936 words.
Visibility is BLOCK-causal everywhere: position ``j`` is visible from ``i``
iff ``j // L <= i // L``, ``L`` the block length. The equations, and how a
reply is generated block by block (``T`` denoise passes that each reveal
the masked positions the model is surest of, then the commit: the finished
block's pass, whose keys and values the cache keeps), are written out in
``benchmark/reference/sdar.py``. The engine runs the commit as the first
half of the next block's first denoise pass (:meth:`SdarBlock.decode` over
two blocks' rows), the reference as a forward of its own.

What is held here is a stage: the ``n_layers`` leading layers, each whole
(all 128 experts: ``experts_held`` is the whole range, nothing is absent),
and the whole vocabulary.

The model declares how it generates, ``generation = ("block_diffusion", L,
T, mask_token_id)``, and the serve backend picks its round from that
(``SingleDeviceSlotBackend._block_round``); nothing else chooses it. The
layers are one group of like layers (:meth:`PipelinedSdar.layer_groups`):
the engine scans the group with its stacked parameters standing outside
the scan, so a layer's 1.2 GB of experts are never sliced out or copied.

Serving only, on one stage: ``SingleDeviceSlotBackend`` with the slab
cache. The paged pool, speculative rounds, the ring backend and the
pipelined generators refuse the model by name.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.partition import StageCtx
from ..obs.events import ATTENTION, ATTN_FULL, FFN, device_scope
from ..ops.layers import Module, MultiHeadAttention, RMSNorm
from ..ops.moe import dropless_moe, dropless_moe_init
from .common import PipelinedTransformer
from .laguna import (LAYER_COUNTS, LagunaEmbed, LagunaHead, LayerGroup,
                     stacked_layer)

__all__ = ["SdarConfig", "SdarBlock", "PipelinedSdar"]


@dataclasses.dataclass(frozen=True)
class SdarConfig:
    """The published widths, the depth held here (``n_layers``), and how
    the model generates (``block_length``, ``denoise_steps``,
    ``mask_token_id``: the config publishes none of the three)."""

    vocab: int = 151936
    d_model: int = 2048
    n_layers: int = 6                      # held of 48
    heads: int = 32
    kv_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    experts_per_tok: int = 8
    moe_d_ff: int = 768
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    max_positions: int = 32768
    block_length: int = 4
    denoise_steps: int = 4
    mask_token_id: int = 151669
    compute_dtype: Any = jnp.bfloat16

    @property
    def generation(self) -> Tuple[str, int, int, int]:
        return ("block_diffusion", self.block_length, self.denoise_steps,
                self.mask_token_id)

    @property
    def experts_held(self) -> Tuple[int, int]:
        """``(first, count)``: every expert of a layer lives here."""
        return (0, self.num_experts)

    def tiny(self) -> "SdarConfig":
        return dataclasses.replace(
            self, vocab=96, d_model=64, n_layers=3, heads=4, kv_heads=2,
            head_dim=16, num_experts=8, experts_per_tok=2, moe_d_ff=32,
            max_positions=4096, mask_token_id=95,
            compute_dtype=jnp.float32)


class SdarBlock(Module):
    """One layer: RMSNorm, grouped-query attention with QK-norm and rotary
    positions under the block-causal mask, residual; RMSNorm, the routed
    experts, residual. ``apply`` is the whole-sequence forward, ``prefill``
    the same with the cache's rows given back, ``decode`` a block's rows
    over the cache; the three share :meth:`_layer`."""

    def __init__(self, cfg: SdarConfig):
        self.cfg = cfg
        self.attn = MultiHeadAttention(
            cfg.d_model, cfg.heads, causal=True, dtype=cfg.compute_dtype,
            kv_heads=cfg.kv_heads, head_dim=cfg.head_dim, bias=False,
            rope={"theta": cfg.rope_theta}, qk_norm=cfg.rms_eps,
            block=cfg.block_length)
        self.ln1 = RMSNorm(cfg.rms_eps)
        self.ln2 = RMSNorm(cfg.rms_eps)
        self.name = "sdar_block"

    def init(self, key, x):
        cfg = self.cfg
        ka, km = jax.random.split(key)
        return {"attn": self.attn.init(ka, x),
                "ln1": self.ln1.init(None, x), "ln2": self.ln2.init(None, x),
                "moe": dropless_moe_init(
                    km, cfg.d_model, cfg.moe_d_ff, cfg.num_experts,
                    cfg.num_experts, dtype=cfg.compute_dtype)}

    def _layer(self, params, x, attend, live=None, at=None):
        """As ``LagunaBlock._layer``: ``attend(attention's parameters,
        normed x) -> (its output, what it gives back)``; ``live [b | 1, q
        | 1]``: the rows whose experts' part is wanted; ``at``: ``params``
        is the group's stacked tree and this the layer's index in it.
        Returns ``(x, what attend gave back, counts)``, the counts in
        :data:`~.laguna.LAYER_COUNTS`' order."""
        cfg = self.cfg
        params = stacked_layer(params, at)
        with device_scope(ATTENTION), device_scope(ATTN_FULL):
            a, back = attend(params["attn"],
                             self.ln1.apply(params["ln1"], x))
            x = x + a
        with device_scope(FFN):
            m = self.ln2.apply(params["ln2"], x)
            b, q, d = m.shape
            if live is not None:
                live = jnp.broadcast_to(live, (b, q)).reshape(-1)
            y, counts = dropless_moe(
                params["moe"], m.reshape(b * q, d),
                top_k=cfg.experts_per_tok, first=0, scale=1.0, live=live,
                layer=at)
            return (x + y.reshape(b, q, d), back,
                    jnp.concatenate([counts, jnp.ones((1,), jnp.int32)]))

    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        return self._layer(
            params, x, lambda p, y: (self.attn.apply(p, y, ctx=ctx), None))[0]

    def prefill(self, params, x, live=None, at=None):
        """A whole prompt ``x [b, s, d]`` from position 0 under the
        block-causal mask: ``(x, {"k", "v"} [b, s, Hkv, D], counts)``."""
        return self._layer(params, x, self.attn.prefill, live, at)

    def decode(self, params, x, cache, pos, tree=None, layer=None,
               live=None, at=None, lead=None):
        """A block's rows ``x [S, L, d]`` at block-aligned ``pos`` over
        the cache of the earlier blocks and over each other (``tree``: the
        all-ones within-chunk mask), or two blocks' ``[S, 2L, d]`` under
        the block lower-triangular one, the first of them written where
        ``lead`` says (``MultiHeadAttention.decode``): ``(x, cache,
        counts)``."""
        return self._layer(
            params, x,
            lambda p, y: self.attn.decode(p, y, cache, pos, tree=tree,
                                          layer=layer, lead=lead), live, at)


class PipelinedSdar(PipelinedTransformer):
    """embed | the held layers, one group of like layers | head, on one
    stage. ``init`` returns ``([[the group's stack]], pre, post)``: the
    layers' parameters are one tree whose leaves lead with the layer
    count, the layout they are served in."""

    # what a layer counts (``SdarBlock.decode``'s third result), as the
    # serve engine names its counters ``serve.<name>``
    layer_counts = tuple("moe." + n for n in LAYER_COUNTS)
    # why a path that decodes one stacked block a token a step refuses it
    grouped_because = ("keeps each layer's experts whole in one stacked "
                       "group and generates by diffusion over blocks")

    def __init__(self, cfg: SdarConfig, n_stages: int = 1):
        if n_stages != 1:
            raise ValueError(
                "PipelinedSdar holds one stage of a deployment (the "
                f"leading layers, each whole); got n_stages={n_stages}")
        self.embed = LagunaEmbed(cfg)
        self.head = LagunaHead(cfg)
        self.block = SdarBlock(cfg)
        super().__init__(cfg, 1)

    @property
    def generation(self) -> Tuple[str, int, int, int]:
        """How a reply is generated: the serve backend picks its round
        from this."""
        return self.cfg.generation

    def layer_groups(self) -> List[LayerGroup]:
        return [LayerGroup(self.block, self.cfg.n_layers, "full", 0)]

    def init(self, key: jax.Array):
        h = self.h_spec()
        pre = {"embed": self.embed.init(jax.random.fold_in(key, 0),
                                        self.x_spec())}
        post = {self.post_key: self.head.init(jax.random.fold_in(key, 1), h)}
        layers = [self.block.init(jax.random.fold_in(key, 2 + i), h)
                  for i in range(self.cfg.n_layers)]
        return ([[jax.tree_util.tree_map(lambda *xs: jnp.stack(xs),
                                         *layers)]], pre, post)

    def x_spec(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((1, 8), jnp.int32)

    def h_spec(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((1, 8, self.cfg.d_model),
                                    self.cfg.compute_dtype)

    def forward(self, params, tokens):
        """Logits ``[b, s, vocab]`` of the whole-sequence forward under
        the block-causal mask, no cache (tests; the serve engine goes
        through the group itself)."""
        ((stack,),), pre, post = params
        h = self.embed.apply(pre["embed"], tokens)
        for i in range(self.cfg.n_layers):
            h = self.block.apply(
                jax.tree_util.tree_map(lambda a: a[i], stack), h)
        return self.head.apply(post[self.post_key], h)

    def embed_at(self, pre_params, tokens, pos):
        """Positions are rotary and live in the attention: ``pos`` is not
        used here."""
        del pos
        return self.embed.apply(pre_params["embed"], tokens)

    def max_position(self) -> Optional[int]:
        return self.cfg.max_positions

    def stage_fn(self, blocks, h, ctx: StageCtx):
        raise NotImplementedError(
            "PipelinedSdar is served, not trained: the pipeline executors "
            "know no block-diffusion objective")
