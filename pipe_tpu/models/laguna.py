"""Laguna-S-2.1 (poolside; ``model_type`` ``laguna``), as one chip of an
expert-parallel pipeline stage serves it.

The published model: 48 layers in periods of ``[full, sliding, sliding,
sliding]`` attention. Every layer has 8 KV heads of 128 and a per-head
output gate; a full layer has 48 query heads and YaRN rotary positions on
half of each head, a sliding layer 72 query heads, a window of 512 and
plain rotary positions on the whole head. Layer 0's feed-forward is a
dense gated SiLU MLP of width 12288; every other layer routes each token
to 10 of 256 experts of width 1024 (softmax over all 256, the ten picks
renormalised and scaled by 2.5) beside one shared expert of width 1024.
RMSNorm before each branch, untied vocabulary of 100352. The equations
are written out in ``benchmark/reference/laguna.py``.

What is held here is a share, told to the model by its configuration
(``model-configs`` guide, section 4): ``n_layers`` leading layers,
experts ``experts_held = (first, count)`` of every expert layer, and the
first ``vocab`` rows of the embedding and of the head. The router keeps its
published width; what the absent experts would add is left out and the
partial result goes on (:func:`~pipe_tpu.ops.moe.dropless_moe`).

The layers are unlike (dense | expert feed-forward, 48 | 72 heads, window |
full), so they cannot be one stacked block. They come in GROUPS of like
consecutive layers (:meth:`PipelinedLaguna.layer_groups`): a group's
parameters are one stacked tree, which the serve engine scans, and the
groups run in order. Two kinds of cache stand behind them: full layers
keep every row of a sequence, sliding layers a ring of ``sliding_window``
rows.

Serving only, on one stage: ``SingleDeviceSlotBackend`` with the slab
cache. The paged pool, speculative rounds, the ring backend and the
pipelined generators refuse the model by name.
"""

from __future__ import annotations

import dataclasses
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..core.partition import StageCtx
from ..obs.events import (ATTENTION, ATTN_FULL, ATTN_WINDOW, EMBED, FFN,
                          HEAD, MOE_SHARED, device_scope, scoped)
from ..ops.layers import GatedMLP, Module, MultiHeadAttention, RMSNorm
from ..ops.moe import DROPLESS_COUNTS, dropless_moe, dropless_moe_init
from .common import PipelinedTransformer

__all__ = ["LagunaConfig", "LagunaBlock", "LayerGroup", "PipelinedLaguna",
           "LAYER_COUNTS", "stacked_layer"]

# what a layer's decode or prefill counts, in the order of its third
# result: the expert layer's three and whether the layer has experts
LAYER_COUNTS = DROPLESS_COUNTS + ("layer_steps",)

YARN_FULL = {"factor": 128.0, "original": 8192, "beta_fast": 32.0,
             "beta_slow": 1.0, "attention_factor": 1.4852030263919618}


@dataclasses.dataclass(frozen=True)
class LagunaConfig:
    """The published widths, and the share held here (``n_layers``,
    ``experts_held``, ``vocab``)."""

    vocab: int = 50176                     # rows held of 100352
    d_model: int = 3072
    n_layers: int = 5                      # held of 48
    period: Tuple[str, ...] = ("full", "sliding", "sliding", "sliding")
    heads_full: int = 48
    heads_sliding: int = 72
    kv_heads: int = 8
    head_dim: int = 128
    sliding_window: int = 512
    d_ff: int = 12288                      # the dense layers' width
    mlp_only_layers: Tuple[int, ...] = (0,)
    num_experts: int = 256                 # the router's width
    experts_per_tok: int = 10
    experts_held: Tuple[int, int] = (0, 128)     # (first, count)
    moe_d_ff: int = 1024
    shared_d_ff: int = 1024
    routed_scale: float = 2.5
    rope_full: Any = dataclasses.field(default_factory=lambda: {
        "theta": 500000.0, "fraction": 0.5, "yarn": dict(YARN_FULL)})
    rope_sliding: Any = dataclasses.field(default_factory=lambda: {
        "theta": 10000.0, "fraction": 1.0})
    rms_eps: float = 1e-6
    max_positions: int = 1048576
    compute_dtype: Any = jnp.bfloat16

    def layer_kinds(self) -> List[Tuple[str, str]]:
        """``(attention, feed-forward)`` of each held layer."""
        return [(self.period[l % len(self.period)],
                 "dense" if l in self.mlp_only_layers else "moe")
                for l in range(self.n_layers)]

    def tiny(self) -> "LagunaConfig":
        return dataclasses.replace(
            self, vocab=96, d_model=64, heads_full=12, heads_sliding=18,
            kv_heads=2, head_dim=16, sliding_window=8, d_ff=128,
            num_experts=8, experts_per_tok=3, experts_held=(0, 4),
            moe_d_ff=32, shared_d_ff=32, max_positions=4096,
            compute_dtype=jnp.float32)


class LayerGroup(NamedTuple):
    """A run of like consecutive layers: the ``block`` they share, how
    many (``n``), which slab their cache rows live in (``cache``) and the
    index of the group's first layer in that slab (``first``)."""
    block: Any
    n: int
    cache: str
    first: int


def stacked_layer(params, at):
    """Layer ``at`` of a group's stacked parameters: every leaf sliced,
    but the routed experts' tensors. Those stay as they lie, ``[layers,
    held, ...]``, for the grouped product to index: a slice of one (0.8
    GB) would be copied out for the kernel at every step. ``at`` None:
    ``params`` is one layer's already."""
    if at is None:
        return params

    def pick(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.lax.dynamic_index_in_dim(a, at, 0,
                                                   keepdims=False), tree)

    out = {k: pick(v) for k, v in params.items() if k != "moe"}
    if "moe" in params:
        out["moe"] = dict(params["moe"],
                          router=pick(params["moe"]["router"]))
    return out


class LagunaBlock(Module):
    """One layer: RMSNorm, grouped-query attention with rotary positions
    and a per-head gate (full, or a window), residual; RMSNorm,
    feed-forward (dense gated MLP, or routed experts beside a shared
    one), residual. ``apply`` is the whole-sequence forward, ``prefill``
    the same with the cache's rows given back, ``decode`` the incremental
    step; the three share :meth:`_layer`."""

    def __init__(self, cfg: LagunaConfig, attention: str, ffn: str):
        full = attention == "full"
        dt = cfg.compute_dtype
        self.cfg, self.attention, self.ffn = cfg, attention, ffn
        self.attn = MultiHeadAttention(
            cfg.d_model, cfg.heads_full if full else cfg.heads_sliding,
            causal=True, dtype=dt, kv_heads=cfg.kv_heads,
            head_dim=cfg.head_dim, bias=False,
            rope=cfg.rope_full if full else cfg.rope_sliding,
            window=None if full else cfg.sliding_window, gate=True)
        self.ln1 = RMSNorm(cfg.rms_eps)
        self.ln2 = RMSNorm(cfg.rms_eps)
        self.mlp = GatedMLP(cfg.d_ff if ffn == "dense" else cfg.shared_d_ff,
                            dtype=dt)
        self.name = f"laguna_{attention}_{ffn}"

    def init(self, key, x):
        cfg = self.cfg
        ks = jax.random.split(key, 3)
        params = {"attn": self.attn.init(ks[0], x),
                  "ln1": self.ln1.init(None, x),
                  "ln2": self.ln2.init(None, x)}
        if self.ffn == "dense":
            params["mlp"] = self.mlp.init(ks[1], x)
        else:
            params["shared"] = self.mlp.init(ks[1], x)
            params["moe"] = dropless_moe_init(
                ks[2], cfg.d_model, cfg.moe_d_ff, cfg.num_experts,
                cfg.experts_held[1], dtype=cfg.compute_dtype)
        return params

    def _layer(self, params, x, attend, live=None, at=None):
        """``attend(attention's parameters, normed x) -> (its output, what
        it gives back)``; ``live [b | 1, q | 1]``: the rows whose experts'
        part is wanted; ``at``: ``params`` is a group's stacked tree and
        this the layer's index in it. Returns ``(x, what attend gave back,
        counts)``, the counts in :data:`LAYER_COUNTS`' order."""
        cfg = self.cfg
        params = stacked_layer(params, at)
        with device_scope(ATTENTION), device_scope(
                ATTN_FULL if self.attention == "full" else ATTN_WINDOW):
            a, back = attend(params["attn"],
                             self.ln1.apply(params["ln1"], x))
            x = x + a
        with device_scope(FFN):
            m = self.ln2.apply(params["ln2"], x)
            if self.ffn == "dense":
                return (x + self.mlp.apply(params["mlp"], m), back,
                        jnp.zeros((len(LAYER_COUNTS),), jnp.int32))
            b, q, d = m.shape
            if live is not None:
                live = jnp.broadcast_to(live, (b, q)).reshape(-1)
            y, counts = dropless_moe(
                params["moe"], m.reshape(b * q, d),
                top_k=cfg.experts_per_tok, first=cfg.experts_held[0],
                scale=cfg.routed_scale, live=live, layer=at)
            with device_scope(MOE_SHARED):
                y = y.reshape(b, q, d) + self.mlp.apply(params["shared"], m)
            return (x + y, back,
                    jnp.concatenate([counts, jnp.ones((1,), jnp.int32)]))

    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        return self._layer(
            params, x, lambda p, y: (self.attn.apply(p, y, ctx=ctx), None))[0]

    def prefill(self, params, x, live=None, at=None):
        """A whole prompt ``x [b, s, d]`` from position 0: ``(x, {"k",
        "v"} [b, s, Hkv, D], counts)``."""
        return self._layer(params, x, self.attn.prefill, live, at)

    def decode(self, params, x, cache, pos, tree=None, layer=None,
               live=None, at=None):
        """Incremental step over a KV cache (the batch form, or with
        ``layer`` the slab form, a sliding layer's slab a ring):
        ``(x, cache, counts)``."""
        return self._layer(
            params, x,
            lambda p, y: self.attn.decode(p, y, cache, pos, tree=tree,
                                          layer=layer), live, at)


class LagunaEmbed(Module):
    def __init__(self, cfg: LagunaConfig):
        self.cfg = cfg
        self.name = "laguna_embed"

    def init(self, key, tokens):
        cfg = self.cfg
        return {"table": (0.02 * jax.random.normal(
            key, (cfg.vocab, cfg.d_model), jnp.float32)).astype(
                cfg.compute_dtype)}

    @scoped(EMBED)
    def apply(self, params, tokens, ctx: StageCtx = StageCtx()):
        return jnp.take(params["table"], tokens, axis=0).astype(
            self.cfg.compute_dtype)


class LagunaHead(Module):
    """Final RMSNorm and the untied head over the vocabulary rows held:
    logits in float32."""

    def __init__(self, cfg: LagunaConfig):
        self.cfg = cfg
        self.ln = RMSNorm(cfg.rms_eps)
        self.name = "laguna_head"

    def init(self, key, h):
        cfg = self.cfg
        bound = 1.0 / cfg.d_model ** 0.5
        return {"ln_f": self.ln.init(None, h),
                "proj": {"w": jax.random.uniform(
                    key, (cfg.d_model, cfg.vocab), jnp.float32, -bound,
                    bound).astype(cfg.compute_dtype)}}

    @scoped(HEAD)
    def apply(self, params, h, ctx: StageCtx = StageCtx()):
        w = params["proj"]["w"]
        h = self.ln.apply(params["ln_f"], h.astype(jnp.float32))
        return jnp.einsum("...d,dv->...v", h.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)


class PipelinedLaguna(PipelinedTransformer):
    """embed | the held layers, in groups of like layers | head, on one
    stage. ``init`` returns ``([[group stack, ...]], pre, post)``: a
    group's parameters are one tree whose leaves lead with the group's
    layer count, the layout they are served in."""

    def __init__(self, cfg: LagunaConfig, n_stages: int = 1):
        if n_stages != 1:
            raise ValueError(
                "PipelinedLaguna holds one stage of a deployment (the "
                f"leading layers, a share of the experts); got "
                f"n_stages={n_stages}")
        self.embed = LagunaEmbed(cfg)
        self.head = LagunaHead(cfg)
        blocks, self._groups, rows = {}, [], {"full": 0, "window": 0}
        for kind in cfg.layer_kinds():
            cache = "full" if kind[0] == "full" else "window"
            if kind not in blocks:
                blocks[kind] = LagunaBlock(cfg, *kind)
            last = self._groups[-1] if self._groups else None
            if last is not None and last.block is blocks[kind]:
                self._groups[-1] = last._replace(n=last.n + 1)
            else:
                self._groups.append(
                    LayerGroup(blocks[kind], 1, cache, rows[cache]))
            rows[cache] += 1
        super().__init__(cfg, 1)

    # what a layer counts (``LagunaBlock.decode``'s third result), as the
    # serve engine names its counters ``serve.<name>``
    layer_counts = tuple("moe." + n for n in LAYER_COUNTS)

    def layer_groups(self) -> List[LayerGroup]:
        return list(self._groups)

    def init(self, key: jax.Array):
        h = self.h_spec()
        pre = {"embed": self.embed.init(jax.random.fold_in(key, 0),
                                        self.x_spec())}
        post = {self.post_key: self.head.init(jax.random.fold_in(key, 1), h)}
        stacks, l = [], 0
        for g in self._groups:
            layers = [g.block.init(jax.random.fold_in(key, 2 + l + i), h)
                      for i in range(g.n)]
            stacks.append(jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *layers))
            l += g.n
        return [stacks], pre, post

    def x_spec(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((1, 8), jnp.int32)

    def h_spec(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct((1, 8, self.cfg.d_model),
                                    self.cfg.compute_dtype)

    def forward(self, params, tokens):
        """Logits ``[b, s, vocab]`` of the whole-sequence forward, no
        cache (tests; the serve engine goes through the groups itself)."""
        (stacks,), pre, post = params
        h = self.embed.apply(pre["embed"], tokens)
        for g, stack in zip(self._groups, stacks):
            for i in range(g.n):
                h = g.block.apply(
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
            "PipelinedLaguna is served, not trained: the pipeline "
            "executors run one homogeneous block a stage")
