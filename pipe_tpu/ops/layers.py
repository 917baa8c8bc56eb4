"""Minimal functional module system + transformer building blocks.

The reference stages are ``nn.Sequential`` children whose math bottoms out in
cuDNN/cuBLAS (``main.py:148``; SURVEY §2 native table). Here layers are pure
``(params, x) -> y`` functions grouped in lightweight Module objects — the
TPU-native equivalent is XLA:TPU codegen onto the MXU, so the "kernel library"
is jnp/einsum with bfloat16-friendly shapes; attention can later swap in a
Pallas flash kernel without changing this interface.

Init is shape-driven: ``module.init(key, x_spec)`` consumes only
``shape``/``dtype`` (arrays or ``jax.ShapeDtypeStruct`` both work), so whole
models initialize without running data through them.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.partition import StageCtx
from ..obs.events import (ATTENTION, EMBED, FFN, HEAD, KV_CACHE,
                          device_scope, scoped)

__all__ = [
    "Module", "Sequential", "Lambda", "Linear", "Embedding", "LayerNorm",
    "Dropout", "MultiHeadAttention", "TransformerEncoderLayer",
    "PreLNBlock", "PositionalEncoding", "Decoder", "spec",
    "slab_width", "fold_heads", "unfold_heads",
]


def spec(x) -> jax.ShapeDtypeStruct:
    """Abstract ``ShapeDtypeStruct`` of an array or spec (public helper)."""
    return jax.ShapeDtypeStruct(jnp.shape(x), jnp.result_type(x))


_spec = spec  # internal alias


class Module:
    """A pure-function layer: ``init`` makes params, ``apply`` runs the math."""

    name: str = "module"

    def init(self, key: jax.Array, *example_inputs) -> Any:
        raise NotImplementedError

    def apply(self, params, *inputs, ctx: StageCtx = StageCtx()):
        raise NotImplementedError

    def __call__(self, params, *inputs, ctx: StageCtx = StageCtx()):
        return self.apply(params, *inputs, ctx=ctx)

    def out_spec(self, params, *input_specs):
        """Abstract output spec, used to chain shape-driven inits.

        ``params`` goes through ``eval_shape`` as an argument (not a
        closure), so abstract param trees — ``ShapeDtypeStruct`` leaves, as
        produced by ``StageParamPack.abstract_tree`` for stage-sharded
        params — chain shapes without any concrete weights existing."""
        def f(p, *xs):
            return self.apply(p, *xs, ctx=StageCtx())
        return jax.eval_shape(f, params, *[_spec(x) for x in input_specs])


class Lambda(Module):
    """Wrap a parameterless function as a Module."""

    def __init__(self, fn: Callable, name: str = "lambda"):
        self.fn = fn
        self.name = name

    def init(self, key, *example_inputs):
        return {}

    def apply(self, params, *inputs, ctx: StageCtx = StageCtx()):
        return self.fn(*inputs)


class Sequential(Module):
    """Ordered composition — the analogue of the ``nn.Sequential`` the reference
    requires as Pipe input (``pipe.py:332`` via ``_verify_module``)."""

    def __init__(self, layers: Sequence[Module], name: str = "sequential"):
        self.layers = list(layers)
        self.name = name

    def __len__(self):
        return len(self.layers)

    def __iter__(self):
        return iter(self.layers)

    def __getitem__(self, idx):
        if isinstance(idx, slice):
            return Sequential(self.layers[idx])
        return self.layers[idx]

    def init(self, key, *example_inputs):
        params = []
        specs = [_spec(x) for x in example_inputs]
        for i, layer in enumerate(self.layers):
            lkey = jax.random.fold_in(key, i)
            p = layer.init(lkey, *specs)
            params.append(p)
            out = layer.out_spec(p, *specs)
            specs = list(out) if isinstance(out, (tuple, list)) else [out]
        return params

    def apply(self, params, *inputs, ctx: StageCtx = StageCtx()):
        if len(params) != len(self.layers):
            raise ValueError(
                f"Sequential got {len(params)} param entries for "
                f"{len(self.layers)} layers")
        out = inputs
        for i, (layer, p) in enumerate(zip(self.layers, params)):
            r = layer.apply(p, *out, ctx=ctx.fold(i))
            out = r if isinstance(r, tuple) else (r,)
        return out if len(out) > 1 else out[0]


class Linear(Module):
    def __init__(self, features: int, use_bias: bool = True,
                 dtype=jnp.float32, name: str = "linear"):
        self.features = features
        self.use_bias = use_bias
        self.dtype = dtype
        self.name = name

    def init(self, key, x):
        in_features = jnp.shape(x)[-1]
        bound = 1.0 / math.sqrt(in_features)
        wkey, bkey = jax.random.split(key)
        params = {
            "w": jax.random.uniform(wkey, (in_features, self.features),
                                    self.dtype, -bound, bound),
        }
        if self.use_bias:
            params["b"] = jax.random.uniform(bkey, (self.features,),
                                             self.dtype, -bound, bound)
        return params

    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        y = jnp.einsum("...i,io->...o", x, params["w"])
        if self.use_bias:
            y = y + params["b"]
        return y


class Embedding(Module):
    """Token embedding with the tutorial's sqrt(d_model) scaling
    (reference ``Encoder``, ``main.py:139-157`` vicinity)."""

    def __init__(self, vocab: int, features: int, scale: bool = True,
                 dtype=jnp.float32, name: str = "embedding"):
        self.vocab = vocab
        self.features = features
        self.scale = scale
        self.dtype = dtype
        self.name = name

    def init(self, key, x):
        table = jax.random.normal(key, (self.vocab, self.features), self.dtype)
        return {"table": table}

    @scoped(EMBED)
    def apply(self, params, tokens, ctx: StageCtx = StageCtx()):
        y = jnp.take(params["table"], tokens, axis=0)
        if self.scale:
            y = y * jnp.asarray(math.sqrt(self.features), y.dtype)
        return y


class LayerNorm(Module):
    def __init__(self, eps: float = 1e-5, dtype=jnp.float32, name: str = "ln"):
        self.eps = eps
        self.dtype = dtype
        self.name = name

    def init(self, key, x):
        d = jnp.shape(x)[-1]
        return {"g": jnp.ones((d,), self.dtype), "b": jnp.zeros((d,), self.dtype)}

    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.var(x, axis=-1, keepdims=True)
        y = (x - mu) * jax.lax.rsqrt(var + self.eps)
        return y * params["g"] + params["b"]


class Dropout(Module):
    """Inverted dropout driven by the explicit ctx key.

    Under remat the identical key replays, so the recomputed forward is
    bit-identical to the stored one — the property the reference bought with
    CUDA RNG state capture (``README.md:528-537``).
    """

    def __init__(self, rate: float, name: str = "dropout"):
        self.rate = rate
        self.name = name

    def init(self, key, x):
        return {}

    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        if not ctx.train or self.rate <= 0.0 or ctx.key is None:
            return x
        keep = 1.0 - self.rate
        mask = jax.random.bernoulli(ctx.key, keep, jnp.shape(x))
        return jnp.where(mask, x / keep, jnp.zeros_like(x))


def dot_product_attention(q, k, v, *, causal: bool = False,
                          dropout_rate: float = 0.0,
                          dropout_key: Optional[jax.Array] = None,
                          train: bool = False):
    """Softmax attention with float32 logits (MXU-friendly einsum form)."""
    d = q.shape[-1]
    logits = jnp.einsum("...qhd,...khd->...hqk", q, k).astype(jnp.float32)
    logits = logits / math.sqrt(d)
    if causal:
        qlen, klen = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((qlen, klen), bool))
        logits = jnp.where(mask, logits, jnp.asarray(-1e30, logits.dtype))
    weights = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    if train and dropout_rate > 0.0 and dropout_key is not None:
        keep = 1.0 - dropout_rate
        m = jax.random.bernoulli(dropout_key, keep, weights.shape)
        weights = jnp.where(m, weights / keep, jnp.zeros_like(weights))
    return jnp.einsum("...hqk,...khd->...qhd", weights, v)


class MultiHeadAttention(Module):
    """Self-attention block (the math inside ``nn.TransformerEncoderLayer``,
    reference ``main.py:148``), batch-first: x is [batch, seq, d_model]."""

    def __init__(self, d_model: int, nhead: int, dropout: float = 0.0,
                 causal: bool = True, dtype=jnp.float32, name: str = "mha",
                 impl: str = "auto"):
        if d_model % nhead:
            raise ValueError("nhead must divide d_model")
        if impl not in ("auto", "xla", "flash"):
            raise ValueError(f"impl must be auto|xla|flash, got {impl!r}")
        self.d_model = d_model
        self.nhead = nhead
        self.head_dim = d_model // nhead
        self.dropout = dropout
        self.causal = causal
        self.dtype = dtype
        self.name = name
        self.impl = impl

    def init(self, key, x):
        keys = jax.random.split(key, 4)
        bound = 1.0 / math.sqrt(self.d_model)

        def mat(k):
            return jax.random.uniform(k, (self.d_model, self.d_model),
                                      self.dtype, -bound, bound)

        return {
            "wq": mat(keys[0]), "wk": mat(keys[1]), "wv": mat(keys[2]),
            "wo": mat(keys[3]),
            "bq": jnp.zeros((self.d_model,), self.dtype),
            "bk": jnp.zeros((self.d_model,), self.dtype),
            "bv": jnp.zeros((self.d_model,), self.dtype),
            "bo": jnp.zeros((self.d_model,), self.dtype),
        }

    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        b, s, _ = x.shape
        h, hd = self.nhead, self.head_dim

        def proj(w, bias):
            return (jnp.einsum("bsd,de->bse", x, w) + bias).reshape(b, s, h, hd)

        q = proj(params["wq"], params["bq"])
        k = proj(params["wk"], params["bk"])
        v = proj(params["wv"], params["bv"])
        dk = ctx.fold(1).key if ctx.key is not None else None
        # The choice is static at trace time. Flash handles attention-weight
        # dropout only when compiled on TPU (the kernel's hardware PRNG
        # regenerates masks in backward; interpret mode has no PRNG).
        on_tpu = jax.default_backend() == "tpu"
        dropout_active = self.dropout > 0.0 and ctx.train and dk is not None
        if self.impl == "flash":
            # asked for by name: the kernel runs or the call fails —
            # only "auto" may choose the XLA path
            from .pallas_attention import supports
            if not supports(s):
                raise ValueError(
                    f"impl='flash' cannot tile seq_len {s} (needs a "
                    f"multiple of 8 that one 128-row block covers or "
                    f"divides); use impl='auto' or 'xla'")
            if dropout_active and not on_tpu:
                raise ValueError(
                    "impl='flash' with attention dropout needs the TPU "
                    f"PRNG, but the backend is {jax.default_backend()!r}; "
                    "use impl='auto' or 'xla'")
            use_flash = True
        elif self.impl == "auto":
            # the measured-crossover heuristic lives in flash_auto_ok
            use_flash = ((not dropout_active or on_tpu)
                         and flash_auto_ok(s))
        else:
            use_flash = False
        if use_flash:
            from .pallas_attention import flash_attention
            o = flash_attention(
                q, k, v, causal=self.causal,
                dropout_rate=self.dropout if dropout_active else 0.0,
                dropout_key=dk if dropout_active else None)
        else:
            o = dot_product_attention(q, k, v, causal=self.causal,
                                      dropout_rate=self.dropout,
                                      dropout_key=dk, train=ctx.train)
        o = o.reshape(b, s, self.d_model)
        return jnp.einsum("bsd,de->bse", o, params["wo"]) + params["bo"]

    def make_cache(self, batch: int, max_len: int, dtype=None):
        """Zeroed KV cache for incremental decoding: ``{"k","v"}`` of
        ``[batch, max_len, nhead, head_dim]``."""
        shape = (batch, max_len, self.nhead, self.head_dim)
        dt = dtype if dtype is not None else self.dtype
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def make_slab(self, layers: int, slots: int, max_len: int, dtype=None):
        """Zeroed stacked KV cache for the SLAB form of :meth:`decode`
        (``layer=``): ``{"k","v"}`` of ``[layers, slots, max_len, C]``,
        a cache row the ``nhead * head_dim`` values of :func:`fold_heads`."""
        shape = (layers, slots, max_len,
                 slab_width(self.nhead, self.head_dim))
        dt = dtype if dtype is not None else self.dtype
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def decode(self, params, x, cache, pos, tree=None, layer=None):
        """Incremental self-attention with a KV cache (inference only).

        ``x``: the new tokens' hidden states ``[b, q, d]`` occupying
        positions ``[pos, pos+q)`` (``q=1`` per decode step; ``q=prompt``
        at prefill with ``pos=0``); ``cache``: :meth:`make_cache` pytree.
        Writes the new K/V rows at ``pos`` and attends each query over
        cache positions ``<= its own`` — exactly :meth:`apply`'s causal
        mask restricted to the live prefix, so teacher-forced cached logits
        match the full forward. Returns ``(out [b, q, d], new_cache)``.

        ``tree`` (optional ``[q, q]`` bool, static): speculative tree
        verification. The q chunk rows are draft-TREE nodes, not a
        contiguous run — K/V still land at cache rows ``[pos, pos+q)``,
        but query row j attends cache rows strictly before ``pos`` plus
        the within-chunk rows where ``tree[j, r]`` (its ancestors-or-
        self). ``tree=None`` keeps the linear causal mask unchanged.

        ``layer`` (optional traced index): the SLAB form, for a loop
        over layers that carries every layer's cache. ``cache`` is then
        :meth:`make_slab`'s stacked ``{"k","v"}`` of ``[L, S, T, C]``:
        a cache row is its heads folded into one axis and zero-padded
        to whole lane tiles (:func:`fold_heads`; ``C`` 1664 for
        gpt2-xl's 25 x 64), the rows second to last. ``x`` is
        ``[S, q, d]`` (a row per slot) and ``pos`` a per-slot vector
        ``[S]``. Only the ``S x q`` new rows are written, at ``(layer,
        s, pos[s])``, and only ``cache[layer]`` is read, so the slab
        stays one buffer updated in place.

        Why this shape (PERF.md, PR 29). A TPU tiles an array's two
        minor dimensions, 16 x 128 for bf16. Rows of ``[H, D]`` (the
        batch form's, and the slab's until PR 29) end in 25 x 64, which
        pads to 32 x 128: 2.6x the bytes on every read of the cache.
        ``T x C`` is whole tiles (4% of padding), one cache row is 13
        tiles side by side, so the row write touches 13 and not the 100
        it touches when the rows are the lanes, and the slab as the
        program's argument, the loops' carry and the operand of the two
        reads have one layout, with no relayout around a launch. The
        reads are matrix products over the folded axis: the scores
        ``[q*H, C] x [T, C]^T`` with each head's query in its own block
        of ``C`` and zeros elsewhere, the mix ``[q*H, T] x [T, C]`` of
        which each head keeps its own block. The zeros cost the matrix
        unit ``H`` times the products and the memory nothing, and a
        decode step is bound by the memory. Same math as the batch form
        vmapped over slots (a sum gains exact zeros), whose cache stays
        ``[b, T, H, D]``. Returns ``(out [S, q, d], slab)``.
        """
        if not self.causal:
            raise ValueError("KV-cache decode requires causal attention")
        b, q, _ = x.shape
        h, hd = self.nhead, self.head_dim

        def proj(w, bias):
            return (jnp.einsum("bsd,de->bse", x, w) + bias).reshape(
                b, q, h, hd)

        qh = proj(params["wq"], params["bq"])
        kh = proj(params["wk"], params["bk"])
        vh = proj(params["wv"], params["bv"])
        # the cache's update, and below its two reads (every cached row
        # of k for the scores, of v for the mix): what a decode step pays
        # for the cache, apart from the projections and the softmax
        with device_scope(KV_CACHE):
            rows = {"k": kh.astype(cache["k"].dtype),
                    "v": vh.astype(cache["v"].dtype)}
            if layer is None:
                cache = {n: jax.lax.dynamic_update_slice(
                    cache[n], rows[n], (0, pos, 0, 0)) for n in rows}
                ck, cv = cache["k"], cache["v"]
            else:
                cache = {n: _write_slab_rows(cache[n], rows[n], layer, pos)
                         for n in rows}
                ck, cv = (jax.lax.dynamic_index_in_dim(          # [S, T, C]
                    cache[n], layer, 0, keepdims=False) for n in ("k", "v"))
            if layer is None:
                logits = jnp.einsum("bqhd,bkhd->bhqk", qh, ck)
            else:
                logits = jnp.einsum("bqhc,bkc->bhqk",
                                    _own_blocks(qh, ck.shape[-1]), ck)
            logits = logits.astype(jnp.float32)
        logits = logits / math.sqrt(hd)
        # a scalar pos is every row's; the slab form's is one per row
        rel = (jnp.arange(logits.shape[-1])[None, :]
               - jnp.reshape(pos, (-1, 1)))                # [1|b, K_cache]
        if tree is None:
            allowed = (rel[:, None, :]
                       <= jnp.arange(q)[None, :, None])    # [1|b, q, K]
        else:
            in_chunk = (rel >= 0) & (rel < q)
            within = jnp.moveaxis(jnp.asarray(tree)[
                :, jnp.clip(rel, 0, q - 1)], 0, 1)         # [1|b, q, K]
            allowed = (rel < 0)[:, None, :] | (in_chunk[:, None, :]
                                               & within)
        logits = jnp.where(allowed[:, None], logits,
                           jnp.asarray(-1e30, logits.dtype))
        weights = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        with device_scope(KV_CACHE):
            if layer is None:
                o = jnp.einsum("bhqk,bkhd->bqhd", weights, cv)
            else:
                o = _own_blocks_of(
                    jnp.einsum("bhqk,bkc->bqhc", weights, cv), hd)
        o = o.reshape(b, q, self.d_model)
        out = jnp.einsum("bsd,de->bse", o, params["wo"]) + params["bo"]
        return out, cache


def slab_width(nhead: int, head_dim: int) -> int:
    """Width ``C`` of a cache row in the slab form: ``nhead * head_dim``
    rounded up to whole lane tiles of 128. The rounding is what makes
    the folded axis the TPU's minor-most: of an array whose last two
    extents are both whole tiles the compiler keeps the order written,
    and otherwise it turns the array so that the padding is least
    (``[.., 640, 1600]`` it lays out rows minor-most; PERF.md, PR 29)."""
    return -(-nhead * head_dim // 128) * 128


def fold_heads(rows):
    """``[..., H, D]`` -> ``[..., C]``: a cache row as the slab form
    keeps it, the heads folded into one axis and zero-padded to
    :func:`slab_width`. The padding takes part in nothing: the scores
    multiply it by the zeros of :func:`_own_blocks`, and the mix's is
    cut off (:func:`unfold_heads`, :func:`_own_blocks_of`)."""
    h, hd = rows.shape[-2:]
    flat = rows.reshape(rows.shape[:-2] + (h * hd,))
    pad = slab_width(h, hd) - h * hd
    return jnp.pad(flat, ((0, 0),) * (flat.ndim - 1) + ((0, pad),))


def unfold_heads(rows, nhead: int, head_dim: int):
    """``[..., C]`` -> ``[..., H, D]``: :func:`fold_heads` undone."""
    return rows[..., :nhead * head_dim].reshape(
        rows.shape[:-1] + (nhead, head_dim))


def _own_blocks(qh, width: int):
    """``qh [b, q, H, D]`` -> ``[b, q, H, C]``: head ``h``'s query in
    block ``h`` of the folded axis and zeros in every other, so that one
    product with folded cache rows ``[T, C]`` gives each head the scores
    of its own keys."""
    b, q, h, hd = qh.shape
    eye = jnp.eye(h, dtype=qh.dtype)
    blocks = qh[:, :, :, None, :] * eye[None, None, :, :, None]
    return jnp.pad(blocks.reshape(b, q, h, h * hd),
                   ((0, 0),) * 3 + ((0, width - h * hd),))


def _own_blocks_of(o, head_dim: int):
    """``o [b, q, H, C]`` (each head's weights mixed over every head's
    folded values) -> ``[b, q, H, D]``: head ``h`` keeps block ``h``."""
    b, q, h, _ = o.shape
    blocks = o[..., :h * head_dim].reshape(b, q, h, h, head_dim)
    return jnp.einsum("bqhgd,hg->bqhd", blocks, jnp.eye(h, dtype=o.dtype))


def _write_slab_rows(slab, rows, layer, pos):
    """``rows [S, q, H, D]`` into ``slab [L, S, T, C]`` at ``(layer, s,
    pos[s])``, folded (:func:`fold_heads`): one ``dynamic_update_slice``
    a slot (its clamping is the batch form's), each on the buffer the
    last one left. Unrolled over the slots on purpose: on the v5e one
    scatter of ``S`` windows runs as a loop and cost four times as much
    (PERF.md, PR 26)."""
    rows = fold_heads(rows)                                # [S, q, C]
    for s in range(rows.shape[0]):
        slab = jax.lax.dynamic_update_slice(
            slab, rows[s][None, None], (layer, s, pos[s], 0))
    return slab


# "gelu" is the EXACT erf form (torch.nn.TransformerEncoderLayer's
# activation='gelu', BERT, ViT); "gelu_tanh" is the tanh approximation
# (GPT-2's gelu_new — and jax.nn.gelu's default). Models must pick the
# variant their reference implementation uses; the HF parity tests pin
# both choices.
_ACTIVATIONS = {
    "relu": jax.nn.relu,
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
}

# Minimum sequence length at which impl="auto" selects the Pallas flash
# kernel on TPU (measured crossover; see MultiHeadAttention.apply).
FLASH_AUTO_MIN_SEQ = 256


def flash_auto_ok(s: int) -> bool:
    """The auto-selection heuristic, in ONE place (MultiHeadAttention and
    ulysses_attention both consult it): flash on TPU from the measured
    crossover length up, when the kernel tiling covers ``s``. Measured on
    v5e-lite (520M LM, bf16): a single 128-token block can't amortize the
    kernel (XLA +3.7% at s=128); flash wins from s=256 (+1.9%) and grows
    with s."""
    if jax.default_backend() != "tpu" or s < FLASH_AUTO_MIN_SEQ:
        return False
    from .pallas_attention import supports
    return supports(s)


class _TransformerBlockBase(Module):
    """Shared structure of the two block families (attn + FFN + 2 LN +
    dropout, one param pytree); subclasses supply ``apply`` (LN placement)."""

    def __init__(self, d_model: int, nhead: int, dim_feedforward: int,
                 dropout: float = 0.0, causal: bool = True,
                 dtype=jnp.float32, name: str = "block",
                 attn_impl: str = "auto", activation: str = "relu"):
        if activation not in _ACTIVATIONS:
            raise ValueError(
                f"activation must be one of {sorted(_ACTIVATIONS)}, "
                f"got {activation!r}")
        self.attn = MultiHeadAttention(d_model, nhead, dropout, causal, dtype,
                                       impl=attn_impl)
        self.ff1 = Linear(dim_feedforward, dtype=dtype)
        self.ff2 = Linear(d_model, dtype=dtype)
        self.ln1 = LayerNorm(dtype=dtype)
        self.ln2 = LayerNorm(dtype=dtype)
        self.drop = Dropout(dropout)
        self.act = _ACTIVATIONS[activation]
        self.name = name

    def init(self, key, x):
        ks = jax.random.split(key, 5)
        d_model_spec = _spec(x)
        hidden = jax.ShapeDtypeStruct(
            jnp.shape(x)[:-1] + (self.ff1.features,), jnp.result_type(x))
        return {
            "attn": self.attn.init(ks[0], x),
            "ff1": self.ff1.init(ks[1], x),
            "ff2": self.ff2.init(ks[2], hidden),
            "ln1": self.ln1.init(ks[3], d_model_spec),
            "ln2": self.ln2.init(ks[4], d_model_spec),
        }


class TransformerEncoderLayer(_TransformerBlockBase):
    """Post-LN transformer block — semantics of torch's default
    ``nn.TransformerEncoderLayer`` (reference ``main.py:148``): self-attn →
    add&norm → FFN(ReLU/GELU) → add&norm, dropout on each residual branch."""

    def __init__(self, *args, name: str = "encoder_layer", **kwargs):
        super().__init__(*args, name=name, **kwargs)

    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        # each scope holds its branch whole: the sublayer, its dropout,
        # the residual add and the norm that closes it
        with device_scope(ATTENTION):
            a = self.attn.apply(params["attn"], x, ctx=ctx.fold(0))
            a = self.drop.apply({}, a, ctx=ctx.fold(1))
            x = self.ln1.apply(params["ln1"], x + a, ctx=ctx)
        with device_scope(FFN):
            h = self.act(self.ff1.apply(params["ff1"], x, ctx=ctx))
            h = self.drop.apply({}, h, ctx=ctx.fold(2))
            h = self.ff2.apply(params["ff2"], h, ctx=ctx)
            h = self.drop.apply({}, h, ctx=ctx.fold(3))
            return self.ln2.apply(params["ln2"], x + h, ctx=ctx)

    def decode(self, params, x, cache, pos, tree=None, layer=None):
        """Incremental :meth:`apply` (inference: no dropout) — same math on
        the new positions with attention served from the KV cache
        (``layer``: the slab form of :meth:`MultiHeadAttention.decode`)."""
        with device_scope(ATTENTION):
            a, cache = self.attn.decode(params["attn"], x, cache, pos,
                                        tree=tree, layer=layer)
            x = self.ln1.apply(params["ln1"], x + a)
        with device_scope(FFN):
            h = self.act(self.ff1.apply(params["ff1"], x))
            h = self.ff2.apply(params["ff2"], h)
            return self.ln2.apply(params["ln2"], x + h), cache


class PreLNBlock(_TransformerBlockBase):
    """Pre-LN transformer block (GPT-2 / ViT lineage): x + attn(ln1(x)),
    then x + ffn(ln2(x)) with GELU — the ring-invariant stage body for the
    model zoo's pipelined GPT-2/ViT factorizations. Same param pytree as
    :class:`TransformerEncoderLayer` (shared base); only LN placement
    differs."""

    def __init__(self, *args, name: str = "preln_block",
                 activation: str = "gelu", **kwargs):
        super().__init__(*args, name=name, activation=activation, **kwargs)

    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        with device_scope(ATTENTION):
            a = self.attn.apply(params["attn"],
                                self.ln1.apply(params["ln1"], x, ctx=ctx),
                                ctx=ctx.fold(0))
            x = x + self.drop.apply({}, a, ctx=ctx.fold(1))
        with device_scope(FFN):
            h = self.act(self.ff1.apply(
                params["ff1"], self.ln2.apply(params["ln2"], x, ctx=ctx),
                ctx=ctx))
            h = self.ff2.apply(params["ff2"], h, ctx=ctx)
            return x + self.drop.apply({}, h, ctx=ctx.fold(2))

    def decode(self, params, x, cache, pos, tree=None, layer=None):
        """Incremental :meth:`apply` (inference: no dropout) — same math on
        the new positions with attention served from the KV cache
        (``layer``: the slab form of :meth:`MultiHeadAttention.decode`)."""
        with device_scope(ATTENTION):
            a, cache = self.attn.decode(params["attn"],
                                        self.ln1.apply(params["ln1"], x),
                                        cache, pos, tree=tree, layer=layer)
            x = x + a
        with device_scope(FFN):
            h = self.act(self.ff1.apply(params["ff1"],
                                        self.ln2.apply(params["ln2"], x)))
            return x + self.ff2.apply(params["ff2"], h), cache


class PositionalEncoding(Module):
    """Sinusoidal positions + dropout (tutorial ``PositionalEncoding``,
    reference ``main.py`` model section). Batch-first: [batch, seq, d]."""

    def __init__(self, d_model: int, dropout: float = 0.0,
                 max_len: int = 5000, dtype=jnp.float32, name: str = "posenc"):
        self.d_model = d_model
        self.drop = Dropout(dropout)
        position = np.arange(max_len)[:, None]
        div = np.exp(np.arange(0, d_model, 2) * (-math.log(10000.0) / d_model))
        pe = np.zeros((max_len, d_model), np.float32)
        pe[:, 0::2] = np.sin(position * div)
        pe[:, 1::2] = np.cos(position * div)
        self.pe = jnp.asarray(pe, dtype)
        self.name = name

    def init(self, key, x):
        return {}

    @scoped(EMBED)
    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        s = x.shape[-2]
        x = x + self.pe[:s]
        return self.drop.apply({}, x, ctx=ctx)


class Decoder(Module):
    """Final projection to vocab logits (tutorial ``Decoder``, reference
    ``main.py`` model section)."""

    def __init__(self, vocab: int, dtype=jnp.float32, name: str = "decoder"):
        self.proj = Linear(vocab, dtype=dtype)
        self.name = name

    def init(self, key, x):
        return self.proj.init(key, x)

    @scoped(HEAD)
    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        return self.proj.apply(params, x, ctx=ctx)
