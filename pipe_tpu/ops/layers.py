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
    "RMSNorm", "GatedMLP", "Dropout", "MultiHeadAttention",
    "TransformerEncoderLayer", "PreLNBlock", "PositionalEncoding",
    "Decoder", "spec", "slab_width", "fold_heads", "unfold_heads",
    "rope_frequencies", "apply_rope", "blocked_causal_attention",
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


class RMSNorm(Module):
    """``x / rms(x) * g``, computed in float32 whatever ``x``'s type and
    given back in it (no mean, no bias)."""

    def __init__(self, eps: float = 1e-6, dtype=jnp.float32,
                 name: str = "rmsnorm"):
        self.eps = eps
        self.dtype = dtype
        self.name = name

    def init(self, key, x):
        return {"g": jnp.ones((jnp.shape(x)[-1],), self.dtype)}

    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(
            jnp.mean(jnp.square(xf), axis=-1, keepdims=True) + self.eps)
        return (y * params["g"].astype(jnp.float32)).astype(x.dtype)


class GatedMLP(Module):
    """Gated SiLU feed-forward without biases: ``(silu(x W_gate) * (x
    W_up)) W_down``. The two first products come out in float32 and the
    gate is applied there; the hidden row goes on in ``x``'s type."""

    def __init__(self, d_ff: int, dtype=jnp.float32, name: str = "gated_mlp"):
        self.d_ff = d_ff
        self.dtype = dtype
        self.name = name

    def init(self, key, x):
        d = jnp.shape(x)[-1]
        ks = jax.random.split(key, 3)

        def mat(k, shape):
            bound = 1.0 / math.sqrt(shape[0])
            return jax.random.uniform(k, shape, self.dtype, -bound, bound)

        return {"w_gate": mat(ks[0], (d, self.d_ff)),
                "w_up": mat(ks[1], (d, self.d_ff)),
                "w_down": mat(ks[2], (self.d_ff, d))}

    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        f32 = jnp.float32
        a = jnp.einsum("...d,df->...f", x, params["w_gate"],
                       preferred_element_type=f32)
        b = jnp.einsum("...d,df->...f", x, params["w_up"],
                       preferred_element_type=f32)
        h = (jax.nn.silu(a) * b).astype(x.dtype)
        return jnp.einsum("...f,fd->...d", h, params["w_down"])


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


def rope_frequencies(head_dim: int, *, theta: float, fraction: float = 1.0,
                     yarn: Optional[dict] = None):
    """``(inv_freq [rot / 2], scale)`` of rotary positions over the first
    ``rot = head_dim * fraction`` dims of a head. ``yarn`` (``factor``,
    ``original``, ``beta_fast``, ``beta_slow``, optional
    ``attention_factor``) blends interpolated and extrapolated frequencies
    as Hugging Face's ``_compute_yarn_parameters`` does, and ``scale``
    (its ``attention_factor``, ``0.1 ln(factor) + 1`` unless given)
    multiplies cos and sin."""
    rot = int(head_dim * fraction)
    pos_freqs = theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if yarn is None:
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    factor, orig = yarn["factor"], yarn["original"]

    def correction_dim(rotations):
        return (rot * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(yarn["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(yarn["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    extra = 1.0 - ramp
    inv = (1.0 / (factor * pos_freqs)) * (1.0 - extra) + extra / pos_freqs
    scale = yarn.get("attention_factor")
    if scale is None:
        scale = 0.1 * math.log(factor) + 1.0
    return inv.astype(np.float32), float(scale)


def apply_rope(x, positions, inv_freq, scale: float = 1.0):
    """Rotary positions on ``x [..., q, H, D]`` at ``positions [..., q]``
    (leading axes broadcast), over the first ``2 * len(inv_freq)`` dims of
    each head in rotate-half pairing (dim ``i`` with ``i + rot / 2``); the
    rest of the head passes. Angles, cos and sin in float32."""
    half = inv_freq.shape[0]
    ang = (positions[..., None].astype(jnp.float32)
           * jnp.asarray(inv_freq))[..., None, :]          # [..., q, 1, half]
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    xf = x.astype(jnp.float32)
    x1, x2, rest = xf[..., :half], xf[..., half:2 * half], xf[..., 2 * half:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
        axis=-1).astype(x.dtype)


def blocked_causal_attention(q, k, v, *, window: Optional[int] = None,
                             q_block: int = 256,
                             block: Optional[int] = None):
    """Causal softmax attention over a whole sequence from position 0, a
    block of ``q_block`` queries at a time, so that no score matrix of the
    whole sequence exists: ``q [b, s, H, D]``, ``k``/``v`` ``[b, s, Hkv,
    D]`` (``H`` a multiple of ``Hkv``: query head ``h`` reads KV head ``h
    // (H / Hkv)``) -> ``[b, s, H, D]``. Position ``j`` is visible from
    ``i`` iff ``j <= i`` and, with ``window``, ``j > i - window``; a
    windowed block reads its own rows and the ``window`` before them, a
    full one every row. With ``block`` (and no window) visibility is
    BLOCK-causal: ``j // block <= i // block``, every
    position of a block of ``block`` sees the whole of it. Scores and
    softmax in float32. Plain
    ``jax.numpy``: the flash kernel has neither groups nor a window."""
    b, s, h, d = q.shape
    hkv = k.shape[2]
    qb = min(q_block, s)
    if s % qb or h % hkv:
        raise ValueError(f"{s} rows in blocks of {qb}, {h} heads over "
                         f"{hkv}: neither may leave a remainder")
    if block is not None and window is not None:
        raise ValueError(f"block-causal attention in blocks of {block} "
                         f"takes no window")
    qg = q.reshape(b, s // qb, qb, hkv, h // hkv, d)
    if window is None or window >= s:
        span, front = s, 0
    else:
        span, front = window + qb, window
        pad = ((0, 0), (front, 0), (0, 0), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)

    def one(i):
        start = i * qb if front else 0       # row of k/v the span starts at
        ks = jax.lax.dynamic_slice_in_dim(k, start, span, axis=1)
        vs = jax.lax.dynamic_slice_in_dim(v, start, span, axis=1)
        kpos = start - front + jnp.arange(span)
        qpos = i * qb + jnp.arange(qb)
        logits = jnp.einsum("bqkgd,btkd->bkgqt", qg[:, i], ks).astype(
            jnp.float32) / math.sqrt(d)
        if block is None:
            seen = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
        else:
            seen = kpos[None, :] // block <= qpos[:, None] // block
        if window is not None:
            seen = seen & (kpos[None, :] > qpos[:, None] - window)
        w = jax.nn.softmax(jnp.where(seen, logits, jnp.float32(-1e30)),
                           axis=-1).astype(q.dtype)
        return jnp.einsum("bkgqt,btkd->bqkgd", w, vs)

    o = jax.lax.map(one, jnp.arange(s // qb))     # [blocks, b, qb, ...]
    return jnp.moveaxis(o, 0, 1).reshape(b, s, h, d)


class MultiHeadAttention(Module):
    """Self-attention block (the math inside ``nn.TransformerEncoderLayer``,
    reference ``main.py:148``), batch-first: x is [batch, seq, d_model].

    The defaults are that block. The keyword arguments after ``impl`` are
    what later architectures vary, each off by default: ``kv_heads``
    (grouped queries: ``nhead / kv_heads`` query heads read one cached
    head, and a cache row holds the ``kv_heads`` only), ``head_dim`` (a
    head size other than ``d_model / nhead``), ``bias``, ``rope``
    (:func:`rope_frequencies`' keywords: rotary positions on q and k,
    applied at each row's own position, so ``decode`` needs no position
    table), ``window`` (position ``j`` visible from ``i`` only if ``j > i
    - window``; the slab form's cache is then a ring of ``window`` rows),
    ``gate`` (a sigmoid of a bias-free linear map of the layer's input,
    one scalar a query head, times that head's output before ``wo``),
    ``qk_norm`` (its eps: an RMSNorm with a gain of ``head_dim``, ``gq``
    and ``gk``, on each head's query and key before the rotary positions),
    ``block`` (BLOCK-causal visibility in the whole-sequence forward:
    ``j`` visible from ``i`` iff ``j // block <= i // block``; ``decode``
    takes a block's rows at a block-aligned ``pos`` with ``tree=`` all
    ones, which is that mask over a cache of whole earlier blocks)."""

    def __init__(self, d_model: int, nhead: int, dropout: float = 0.0,
                 causal: bool = True, dtype=jnp.float32, name: str = "mha",
                 impl: str = "auto", *, kv_heads: Optional[int] = None,
                 head_dim: Optional[int] = None, bias: bool = True,
                 rope: Optional[dict] = None, window: Optional[int] = None,
                 gate: bool = False, qk_norm: Optional[float] = None,
                 block: Optional[int] = None):
        if head_dim is None and d_model % nhead:
            raise ValueError("nhead must divide d_model")
        if impl not in ("auto", "xla", "flash"):
            raise ValueError(f"impl must be auto|xla|flash, got {impl!r}")
        self.d_model = d_model
        self.nhead = nhead
        self.head_dim = head_dim if head_dim is not None else d_model // nhead
        self.kv_heads = kv_heads if kv_heads is not None else nhead
        if nhead % self.kv_heads:
            raise ValueError(f"kv_heads {self.kv_heads} must divide nhead "
                             f"{nhead}")
        self.group = nhead // self.kv_heads
        self.bias = bias
        self.rope = (None if rope is None
                     else rope_frequencies(self.head_dim, **rope))
        self.window = window
        self.gate = gate
        self.qk_norm = qk_norm
        self.block = block
        # what the flash kernel and the dropout path do not know
        self.plain = (self.group == 1 and window is None and not gate
                      and rope is None and qk_norm is None
                      and block is None)
        self.dropout = dropout
        self.causal = causal
        self.dtype = dtype
        self.name = name
        self.impl = impl

    def init(self, key, x):
        keys = jax.random.split(key, 5)
        d, hd = self.d_model, self.head_dim
        wide, narrow = self.nhead * hd, self.kv_heads * hd

        def mat(k, shape):
            bound = 1.0 / math.sqrt(shape[0])
            return jax.random.uniform(k, shape, self.dtype, -bound, bound)

        params = {
            "wq": mat(keys[0], (d, wide)), "wk": mat(keys[1], (d, narrow)),
            "wv": mat(keys[2], (d, narrow)), "wo": mat(keys[3], (wide, d)),
        }
        if self.bias:
            params.update(
                bq=jnp.zeros((wide,), self.dtype),
                bk=jnp.zeros((narrow,), self.dtype),
                bv=jnp.zeros((narrow,), self.dtype),
                bo=jnp.zeros((d,), self.dtype))
        if self.gate:
            params["wg"] = mat(keys[4], (d, self.nhead))
        if self.qk_norm is not None:
            params.update(gq=jnp.ones((hd,), jnp.float32),
                          gk=jnp.ones((hd,), jnp.float32))
        return params

    def _qkv(self, params, x, positions):
        """The three projections of ``x [b, q, d]`` as heads ``[b, q, H |
        Hkv, D]``, q and k turned to ``positions [1 | b, q]`` where the
        attention has rotary positions."""
        b, q, _ = x.shape

        def proj(w, bias, heads):
            y = jnp.einsum("bsd,de->bse", x, params[w])
            if self.bias:
                y = y + params[bias]
            return y.reshape(b, q, heads, self.head_dim)

        qh = proj("wq", "bq", self.nhead)
        kh = proj("wk", "bk", self.kv_heads)
        vh = proj("wv", "bv", self.kv_heads)
        if self.qk_norm is not None:
            norm = RMSNorm(self.qk_norm)
            qh = norm.apply({"g": params["gq"]}, qh)
            kh = norm.apply({"g": params["gk"]}, kh)
        if self.rope is not None:
            qh = apply_rope(qh, positions, *self.rope)
            kh = apply_rope(kh, positions, *self.rope)
        return qh, kh, vh

    def _out(self, params, x, o):
        """The heads' outputs ``o [b, q, H, D]``, gated by the layer's
        input ``x`` where the attention has a gate, through ``wo``."""
        b, q = o.shape[:2]
        if self.gate:
            g = jax.nn.sigmoid(jnp.einsum(
                "bsd,dh->bsh", x, params["wg"],
                preferred_element_type=jnp.float32))
            o = (o * g[..., None]).astype(o.dtype)
        out = jnp.einsum("bsd,de->bse",
                         o.reshape(b, q, self.nhead * self.head_dim),
                         params["wo"])
        return out + params["bo"] if self.bias else out

    def prefill(self, params, x):
        """:meth:`apply` over a whole prompt from position 0 without a
        cache, its attention a block of queries at a time
        (:func:`blocked_causal_attention`): ``(out [b, s, d], {"k", "v"} [b,
        s, Hkv, D])``, the rows as a cache keeps them."""
        qh, kh, vh = self._qkv(params, x, jnp.arange(x.shape[1])[None])
        o = blocked_causal_attention(qh, kh, vh, window=self.window,
                                     block=self.block)
        return self._out(params, x, o), {"k": kh, "v": vh}

    def apply(self, params, x, ctx: StageCtx = StageCtx()):
        b, s, _ = x.shape
        if not self.plain:
            if not self.causal or (self.dropout > 0.0 and ctx.train):
                raise ValueError(
                    "grouped, windowed, gated, normed, block-causal or "
                    "rotary attention runs causal and without dropout")
            return self.prefill(params, x)[0]
        q, k, v = self._qkv(params, x, None)
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
        return self._out(params, x, o)

    def make_cache(self, batch: int, max_len: int, dtype=None):
        """Zeroed KV cache for incremental decoding: ``{"k","v"}`` of
        ``[batch, max_len, kv_heads, head_dim]``."""
        shape = (batch, max_len, self.kv_heads, self.head_dim)
        dt = dtype if dtype is not None else self.dtype
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def slab_rows(self, max_len: int) -> int:
        """Rows a slot holds in the slab form: ``max_len``, or a ring of
        ``window`` where the attention sees no further back."""
        return max_len if self.window is None else self.window

    def make_slab(self, layers: int, slots: int, max_len: int, dtype=None):
        """Zeroed stacked KV cache for the SLAB form of :meth:`decode`
        (``layer=``): ``{"k","v"}`` of ``[layers, slots, rows, C]``,
        ``rows`` :meth:`slab_rows`, a cache row the ``kv_heads * head_dim``
        values of :func:`fold_heads`."""
        shape = (layers, slots, self.slab_rows(max_len),
                 slab_width(self.kv_heads, self.head_dim))
        dt = dtype if dtype is not None else self.dtype
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def seat(self, rows, true_len):
        """A prompt's cache rows ``[..., B, Hkv, D]`` (positions 0 .. B -
        1, the first ``true_len`` real) as a slot of the slab form holds
        them: folded (:func:`fold_heads`), ``[..., B, C]``; for a window
        the ring ``[..., window, C]``, row ``r`` the newest position
        before ``true_len`` that is ``r`` mod ``window`` (a row no
        position has reached holds what :meth:`decode` masks)."""
        rows = fold_heads(rows)
        if self.window is None:
            return rows
        last = true_len - 1
        src = last - (last - jnp.arange(self.window)) % self.window
        return jnp.take(rows, jnp.clip(src, 0, rows.shape[-2] - 1),
                        axis=-2)

    def decode(self, params, x, cache, pos, tree=None, layer=None,
               lead=None):
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

        ``lead`` (optional ``(n, write [S])``, slab form): the chunk's
        ``n`` leading rows are a slot's to write only where ``write``;
        elsewhere the cache keeps the rows it has at ``[pos, pos + n)``,
        which may then lie before row 0 (``pos`` negative: no start is
        clamped), and the queries read those. The other ``q - n`` rows
        are written as ever.

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

        With the constructor's later arguments (class docstring): a cache
        row holds the ``kv_heads`` only (``[b, T, Hkv, D]``; folded, ``C =
        Hkv * D``) and a group's query heads read one cached head's block;
        q and k are turned to their own positions ``pos + j`` before the
        row is written; a ``window`` masks rows ``window`` or more behind
        a query, and in the slab form the cache is then a RING of
        ``window`` rows (:meth:`make_slab`): the new row goes to ``pos %
        window``, row ``r`` holds the newest position at or before ``pos``
        that is ``r`` mod ``window``, rows no position has reached yet
        are masked, and only ``q = 1`` is taken (:meth:`seat` puts a
        prompt's rows there).
        """
        if not self.causal:
            raise ValueError("KV-cache decode requires causal attention")
        if self.block is not None and tree is None:
            raise ValueError("block-causal attention decodes a block's "
                             "rows under tree= (all ones), not a causal run")
        b, q, _ = x.shape
        hd, grp, win = self.head_dim, self.group, self.window
        # a scalar pos is every row's; the slab form's is one per row
        at = jnp.reshape(pos, (-1, 1))                     # [1|b, 1]
        qh, kh, vh = self._qkv(params, x, at + jnp.arange(q)[None, :])
        # the slab form of a windowed attention is a ring: position p
        # lives in row p % window, and row r holds the newest position at
        # or before pos that is r mod window
        ring = layer is not None and win is not None
        if ring and (q != 1 or tree is not None):
            raise ValueError("a ring of window rows takes one new row a "
                             "step (no speculative or chunked rows)")
        if lead is not None and (layer is None or ring
                                 or 2 * lead[0] > q):
            raise ValueError("lead= gates the leading rows of a slab "
                             "write, at most half the chunk's")
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
                cache = {n: _write_slab_rows(
                    cache[n], rows[n], layer, pos % win if ring else pos,
                    lead) for n in rows}
                ck, cv = (jax.lax.dynamic_index_in_dim(          # [S, T, C]
                    cache[n], layer, 0, keepdims=False) for n in ("k", "v"))
            if layer is not None:
                logits = jnp.einsum("bqhc,bkc->bhqk",
                                    _own_blocks(qh, ck.shape[-1], grp), ck)
            elif grp == 1:
                logits = jnp.einsum("bqhd,bkhd->bhqk", qh, ck)
            else:
                logits = jnp.einsum(
                    "bqkgd,btkd->bkgqt",
                    qh.reshape(b, q, self.kv_heads, grp, hd), ck).reshape(
                        b, self.nhead, q, ck.shape[1])
            logits = logits.astype(jnp.float32)
        logits = logits / math.sqrt(hd)
        # rel: a cached row's position less the first new row's
        if ring:
            rel = -((at - jnp.arange(logits.shape[-1])[None, :]) % win)
        else:
            rel = jnp.arange(logits.shape[-1])[None, :] - at   # [1|b, K]
        if tree is None:
            allowed = (rel[:, None, :]
                       <= jnp.arange(q)[None, :, None])    # [1|b, q, K]
        else:
            in_chunk = (rel >= 0) & (rel < q)
            within = jnp.moveaxis(jnp.asarray(tree)[
                :, jnp.clip(rel, 0, q - 1)], 0, 1)         # [1|b, q, K]
            allowed = (rel < 0)[:, None, :] | (in_chunk[:, None, :]
                                               & within)
        if ring:        # a row no position of this sequence has reached
            allowed = allowed & (at + rel >= 0)[:, None, :]
        elif win is not None:
            allowed = allowed & (rel[:, None, :]
                                 > jnp.arange(q)[None, :, None] - win)
        logits = jnp.where(allowed[:, None], logits,
                           jnp.asarray(-1e30, logits.dtype))
        weights = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        with device_scope(KV_CACHE):
            if layer is not None:
                o = _own_blocks_of(
                    jnp.einsum("bhqk,bkc->bqhc", weights, cv), hd, grp)
            elif grp == 1:
                o = jnp.einsum("bhqk,bkhd->bqhd", weights, cv)
            else:
                o = jnp.einsum(
                    "bkgqt,btkd->bqkgd",
                    weights.reshape(b, self.kv_heads, grp, q, cv.shape[1]),
                    cv).reshape(b, q, self.nhead, hd)
        return self._out(params, x, o), cache


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


def _block_of_head(nhead: int, group: int, dtype):
    """``[H, H / group]``: one where cached head ``g`` is the one query
    head ``h`` reads (``g == h // group``); the identity without groups."""
    eye = jnp.eye(nhead // group, dtype=dtype)
    return eye if group == 1 else jnp.repeat(eye, group, axis=0)


def _own_blocks(qh, width: int, group: int = 1):
    """``qh [b, q, H, D]`` -> ``[b, q, H, C]``: head ``h``'s query in
    block ``h // group`` of the folded axis (its cached head's) and zeros
    in every other, so that one product with folded cache rows ``[T, C]``
    gives each head the scores of its own keys."""
    b, q, h, hd = qh.shape
    own = _block_of_head(h, group, qh.dtype)
    blocks = qh[:, :, :, None, :] * own[None, None, :, :, None]
    folded = (h // group) * hd
    return jnp.pad(blocks.reshape(b, q, h, folded),
                   ((0, 0),) * 3 + ((0, width - folded),))


def _own_blocks_of(o, head_dim: int, group: int = 1):
    """``o [b, q, H, C]`` (each head's weights mixed over every cached
    head's folded values) -> ``[b, q, H, D]``: head ``h`` keeps block ``h
    // group``."""
    b, q, h, _ = o.shape
    hkv = h // group
    blocks = o[..., :hkv * head_dim].reshape(b, q, h, hkv, head_dim)
    return jnp.einsum("bqhgd,hg->bqhd", blocks,
                      _block_of_head(h, group, o.dtype))


def _write_slab_rows(slab, rows, layer, pos, lead=None):
    """``rows [S, q, H, D]`` into ``slab [L, S, T, C]`` at ``(layer, s,
    pos[s])``, folded (:func:`fold_heads`): one ``dynamic_update_slice``
    a slot (its clamping is the batch form's), each on the buffer the
    last one left. Unrolled over the slots on purpose: on the v5e one
    scatter of ``S`` windows runs as a loop and cost four times as much
    (PERF.md, PR 26). ``lead = (n, write [S])``: two a slot, the ``n``
    leading rows first. Where they are not the slot's to write they go
    where the rest goes, ``pos[s] + n``, and the rest then covers them:
    nothing is read back, and no start lies before row 0."""
    rows = fold_heads(rows)                                # [S, q, C]
    if lead is None:
        for s in range(rows.shape[0]):
            slab = jax.lax.dynamic_update_slice(
                slab, rows[s][None, None], (layer, s, pos[s], 0))
        return slab
    n, write = lead
    for s in range(rows.shape[0]):
        rest = pos[s] + n
        slab = jax.lax.dynamic_update_slice(
            slab, rows[s, :n][None, None],
            (layer, s, jnp.where(write[s], pos[s], rest), 0))
        slab = jax.lax.dynamic_update_slice(
            slab, rows[s, n:][None, None], (layer, s, rest, 0))
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
