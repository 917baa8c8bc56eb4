"""Plain reference of GPT-2's forward pass.

Learned token and position embeddings, pre-LN blocks (LayerNorm, causal
self-attention, residual; LayerNorm, feed-forward with the tanh GELU
``gelu_new``, residual), a final LayerNorm and a linear head to the
vocabulary. Departure from the published model, as the configuration file
states: the head is not tied to the token embedding.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
cache, no buckets, no slots, no kernels. It imports nothing of the program
under test and makes its own weights from the seed; the harness hands the
same arrays to the program. The block weights are made in the type they are
served in (bfloat16, one array per layer and kind); the reference reads the
same values in float32.

``precision="fp8"`` is the control: every matrix product's operands are
rounded to float8 (e4m3, per-tensor absmax scaling) before a float32
product, the nearest precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


@functools.partial(jax.jit, static_argnames=(
    "vocab", "d_model", "d_ff", "n_layers", "seq_len", "block_dtype"))
def _make_weights(key, *, vocab, d_model, d_ff, n_layers, seq_len,
                  block_dtype):
    d, ff = d_model, d_ff
    k_embed, k_pos, k_head, k_layers = jax.random.split(key, 4)

    def uni(k, shape, fan_in):
        b = 1.0 / math.sqrt(fan_in)
        return jax.random.uniform(k, shape, jnp.float32, -b, b).astype(
            block_dtype)

    def layer(k):
        ks = jax.random.split(k, 8)
        zeros = jnp.zeros((d,), block_dtype)
        return {
            "wq": uni(ks[0], (d, d), d), "wk": uni(ks[1], (d, d), d),
            "wv": uni(ks[2], (d, d), d), "wo": uni(ks[3], (d, d), d),
            "bq": zeros, "bk": zeros, "bv": zeros, "bo": zeros,
            "ff1_w": uni(ks[4], (d, ff), d), "ff1_b": uni(ks[5], (ff,), d),
            "ff2_w": uni(ks[6], (ff, d), ff), "ff2_b": uni(ks[7], (d,), ff),
            "ln1_g": jnp.ones((d,), block_dtype), "ln1_b": zeros,
            "ln2_g": jnp.ones((d,), block_dtype), "ln2_b": zeros,
        }

    return {
        "wte": 0.02 * jax.random.normal(k_embed, (vocab, d), jnp.float32),
        "wpe": 0.01 * jax.random.normal(k_pos, (seq_len, d), jnp.float32),
        "layers": [layer(k) for k in jax.random.split(k_layers, n_layers)],
        "lnf_g": jnp.ones((d,), jnp.float32),
        "lnf_b": jnp.zeros((d,), jnp.float32),
        "head_w": jax.random.uniform(
            k_head, (d, vocab), jnp.float32,
            -1.0 / math.sqrt(d), 1.0 / math.sqrt(d)),
    }


def make_weights(cfg: dict, seed: int):
    """The weights as served, on the device, one jitted call from the seed:
    block leaves in the configuration's compute type, embeddings, final norm
    and head in float32."""
    return _make_weights(
        seed_key(seed), vocab=cfg["vocab"], d_model=cfg["d_model"],
        d_ff=cfg["d_ff"], n_layers=cfg["n_layers"], seq_len=cfg["seq_len"],
        block_dtype=jnp.dtype(cfg["compute_dtype"]))


def num_params(cfg: dict) -> int:
    d, ff, L, V = cfg["d_model"], cfg["d_ff"], cfg["n_layers"], cfg["vocab"]
    per_layer = 4 * d * d + 4 * d + d * ff + ff + ff * d + d + 4 * d
    return V * d + cfg["seq_len"] * d + L * per_layer + 2 * d + d * V


def _qdq_fp8(x):
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(precision):
    def mm(spec, a, b):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if precision == "fp8":
            a, b = _qdq_fp8(a), _qdq_fp8(b)
        elif precision != "f32":
            raise ValueError(f"precision {precision!r}")
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return mm


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return ((x - mu) * jax.lax.rsqrt(var + LN_EPS) * g.astype(jnp.float32)
            + b.astype(jnp.float32))


def _block(x, p, *, nhead, mm):
    b, s, d = x.shape
    hd = d // nhead
    f32 = jnp.float32
    y = _layer_norm(x, p["ln1_g"], p["ln1_b"])

    def heads(w, bias):
        return (mm("bsd,de->bse", y, w) + bias.astype(f32)).reshape(
            b, s, nhead, hd)

    q, k, v = (heads(p["wq"], p["bq"]), heads(p["wk"], p["bk"]),
               heads(p["wv"], p["bv"]))
    logits = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    w = jax.nn.softmax(jnp.where(causal, logits, -1e30), axis=-1)
    a = mm("bhqk,bkhd->bqhd", w, v).reshape(b, s, d)
    x = x + mm("bsd,de->bse", a, p["wo"]) + p["bo"].astype(f32)
    y = _layer_norm(x, p["ln2_g"], p["ln2_b"])
    h = jax.nn.gelu(mm("bsd,df->bsf", y, p["ff1_w"])
                    + p["ff1_b"].astype(f32), approximate=True)
    return x + mm("bsf,fd->bsd", h, p["ff2_w"]) + p["ff2_b"].astype(f32)


@jax.jit
def _embed(wte, wpe, tokens):
    return jnp.take(wte, tokens, axis=0) + wpe[:tokens.shape[-1]]


@functools.partial(jax.jit, static_argnames=("nhead", "precision"))
def _one_block(x, p, *, nhead, precision):
    return _block(x, p, nhead=nhead, mm=_mm(precision))


@functools.partial(jax.jit, static_argnames=("precision",))
def _head(x, g, b, w, *, precision):
    return _mm(precision)("bsd,dv->bsv", _layer_norm(x, g, b), w)


def forward(weights, tokens, cfg: dict, precision: str = "f32"):
    """Logits ``[rows, positions, vocab]`` in float32 of the full causal
    forward pass over ``tokens [rows, positions]``, layer by layer (one
    small program run once per layer, so it compiles in seconds)."""
    x = _embed(weights["wte"], weights["wpe"], tokens)
    for p in weights["layers"]:
        x = _one_block(x, p, nhead=cfg["nhead"], precision=precision)
    return _head(x, weights["lnf_g"], weights["lnf_b"], weights["head_w"],
                 precision=precision)


@jax.jit
def gaps_below_best(logits, chosen):
    """For each position: how far the logit of ``chosen`` lies below the
    row's best logit (0 where it is the best)."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return best - got
