"""Plain reference of the tutorial language model and of its training step.

The PyTorch "Training Transformer models using Pipeline Parallelism"
tutorial's model, as the configuration file states it: token embedding
scaled by sqrt(d_model), sinusoidal positions, post-LN encoder layers
(self-attention, add and norm, ReLU feed-forward, add and norm) under a
causal mask, a linear decoder to the vocabulary, mean token cross-entropy;
trained with gradient clipping by global norm and Adam.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``:
no kernels, no pipeline, no rematerialisation, no mixed precision. It
imports nothing of the program under test and makes its own weights from the
seed (``make_weights``); the harness hands the same arrays to the program.

Dropout is part of the configuration (0.2), so the reference has to draw the
masks the run under test draws. A mask is ``bernoulli(key, keep, shape)``
with the key folded from the step key over (micro-batch, stage, site), the
schedule ``dropout_keys`` documents; the step key and the key's
implementation (``rbg`` on the TPU) are arguments, stated by the driver. The
reference therefore walks a batch micro-batch by micro-batch, which also
keeps the float32 activations of a 32 x 128 batch inside the chip's memory.

``precision="fp8"`` is the control: every matrix product's operands are
rounded to float8 (e4m3, per-tensor absmax scaling) before a float32
product, the nearest precision below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8

# name -> (shape builder, initialiser) of every per-layer parameter, stacked
# on a leading layer axis in ``weights["layers"]``
_LAYER_KINDS = ("wq", "wk", "wv", "wo", "bq", "bk", "bv", "bo",
                "ff1_w", "ff1_b", "ff2_w", "ff2_b",
                "ln1_g", "ln1_b", "ln2_g", "ln2_b")


def seed_key(seed: int, impl=None):
    """A key from any non-negative whole seed (the driver's exceed int32)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl=impl)
    return jax.random.fold_in(key, seed >> 31)


def layer_leaf(key, kind: str, layer: int, d: int, ff: int):
    """One layer's leaf of one kind, from the weights' key: uniform in
    +-1/sqrt(fan_in) for matrices and feed-forward biases (torch's
    ``Linear``), zeros for attention biases, ones and zeros for the norms.
    Every leaf has a key of its own, so a caller may build any layout
    (stacked by layer here, by stage and block for the trainer) and get the
    same values."""
    shapes = {"wq": ((d, d), d), "wk": ((d, d), d), "wv": ((d, d), d),
              "wo": ((d, d), d), "ff1_w": ((d, ff), d), "ff1_b": ((ff,), d),
              "ff2_w": ((ff, d), ff), "ff2_b": ((d,), ff)}
    if kind in shapes:
        shape, fan_in = shapes[kind]
        k = jax.random.fold_in(
            jax.random.fold_in(key, 2 + _LAYER_KINDS.index(kind)), layer)
        b = 1.0 / math.sqrt(fan_in)
        return jax.random.uniform(k, shape, jnp.float32, -b, b)
    if kind in ("ln1_g", "ln2_g"):
        return jnp.ones((d,), jnp.float32)
    return jnp.zeros((d,), jnp.float32)


def outer_leaves(key, vocab: int, d: int):
    """The embedding (normal) and the decoder (uniform, fan-in d)."""
    b = 1.0 / math.sqrt(d)
    ke, kw, kb = jax.random.split(jax.random.fold_in(key, 0), 3)
    return (jax.random.normal(ke, (vocab, d), jnp.float32),
            jax.random.uniform(kw, (d, vocab), jnp.float32, -b, b),
            jax.random.uniform(kb, (vocab,), jnp.float32, -b, b))


@functools.partial(jax.jit, static_argnames=("vocab", "d_model", "d_ff",
                                             "n_layers"))
def _make_weights(key, *, vocab, d_model, d_ff, n_layers):
    embed, dec_w, dec_b = outer_leaves(key, vocab, d_model)
    layers = {kind: jnp.stack([layer_leaf(key, kind, l, d_model, d_ff)
                               for l in range(n_layers)])
              for kind in _LAYER_KINDS}
    return {"embed": embed, "layers": layers, "dec_w": dec_w,
            "dec_b": dec_b}


def make_weights(cfg: dict, seed: int):
    """Float32 weights on the device, one jitted call from the seed."""
    return _make_weights(seed_key(seed), vocab=cfg["vocab"],
                         d_model=cfg["d_model"], d_ff=cfg["d_ff"],
                         n_layers=cfg["n_layers"])


def num_params(cfg: dict) -> int:
    d, ff, L, V = cfg["d_model"], cfg["d_ff"], cfg["n_layers"], cfg["vocab"]
    per_layer = 4 * d * d + 4 * d + d * ff + ff + ff * d + d + 4 * d
    return V * d + L * per_layer + d * V + V


# ---------------------------------------------------------------------------
# forward


def _qdq_fp8(x):
    """``x`` rounded to float8 e4m3 under a per-tensor absmax scale, as a
    float8 matrix product would see it; the gradient passes straight
    through, as it does in float8 training."""
    s = jnp.max(jnp.abs(x)) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    q = (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


@jax.custom_vjp
def _fp8_cotangent(y):
    """Identity whose cotangent is rounded to float8 e5m2 under a per-tensor
    absmax scale: the gradient operand of a float8 backward product."""
    return y


def _fp8_cotangent_fwd(y):
    return y, None


def _fp8_cotangent_bwd(_, g):
    s = jnp.max(jnp.abs(g)) / 57344.0
    s = jnp.where(s > 0, s, 1.0)
    return ((g / s).astype(jnp.float8_e5m2).astype(jnp.float32) * s,)


_fp8_cotangent.defvjp(_fp8_cotangent_fwd, _fp8_cotangent_bwd)


def _mm(precision):
    """``einsum`` at the stated precision: float32 ``highest``, or the
    control's float8 (e4m3 operands forward, e5m2 cotangents backward,
    float32 accumulation)."""
    def mm(spec, a, b):
        if precision == "fp8":
            return _fp8_cotangent(jnp.einsum(
                spec, _qdq_fp8(a), _qdq_fp8(b), precision=HIGHEST))
        if precision != "f32":
            raise ValueError(f"precision {precision!r}")
        return jnp.einsum(spec, a, b, precision=HIGHEST)
    return mm


def _layer_norm(x, g, b):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + LN_EPS) * g + b


def positions(seq: int, d_model: int):
    pos = np.arange(seq)[:, None]
    div = np.exp(np.arange(0, d_model, 2) * (-math.log(10000.0) / d_model))
    pe = np.zeros((seq, d_model), np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div)
    return jnp.asarray(pe)


def _drop(x, key, rate):
    if key is None or rate <= 0.0:
        return x
    keep = 1.0 - rate
    mask = jax.random.bernoulli(key, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


def dropout_keys(step_key, microbatch, stage):
    """The dropout key schedule of a run, as the configuration states it:
    ``k = fold(fold(step_key, microbatch), stage)``; the positional-encoding
    mask draws from ``fold(fold(k, 0), 1)``; layer ``l`` of the stage from
    ``kl = fold(fold(k, 1), l)``: attention weights ``fold(fold(kl, 0), 1)``,
    attention output ``fold(kl, 1)``, feed-forward hidden ``fold(kl, 2)``,
    feed-forward output ``fold(kl, 3)``."""
    f = jax.random.fold_in
    k = f(f(step_key, microbatch), stage)
    return f(f(k, 0), 1), f(k, 1)


def _encoder_layer(x, p, kl, *, nhead, rate, mm):
    f = jax.random.fold_in
    b, s, d = x.shape
    hd = d // nhead

    def heads(w, bias):
        return (mm("bsd,de->bse", x, w) + bias).reshape(b, s, nhead, hd)

    q, k, v = (heads(p["wq"], p["bq"]), heads(p["wk"], p["bk"]),
               heads(p["wv"], p["bv"]))
    logits = mm("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(causal, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    w = _drop(w, None if kl is None else f(f(kl, 0), 1), rate)
    a = mm("bhqk,bkhd->bqhd", w, v).reshape(b, s, d)
    a = mm("bsd,de->bse", a, p["wo"]) + p["bo"]
    a = _drop(a, None if kl is None else f(kl, 1), rate)
    x = _layer_norm(x + a, p["ln1_g"], p["ln1_b"])
    h = jax.nn.relu(mm("bsd,df->bsf", x, p["ff1_w"]) + p["ff1_b"])
    h = _drop(h, None if kl is None else f(kl, 2), rate)
    h = mm("bsf,fd->bsd", h, p["ff2_w"]) + p["ff2_b"]
    h = _drop(h, None if kl is None else f(kl, 3), rate)
    return _layer_norm(x + h, p["ln2_g"], p["ln2_b"])


def microbatch_loss(weights, tokens, targets, step_key, microbatch, cfg,
                    *, n_stages=1, precision="f32", train=True):
    """Sum over the micro-batch's rows of each row's mean token
    cross-entropy (the batch loss is that sum over all rows / rows)."""
    mm = _mm(precision)
    d, L = cfg["d_model"], cfg["n_layers"]
    rate = cfg["dropout"] if train else 0.0
    lps = L // n_stages
    x = jnp.take(weights["embed"], tokens, axis=0) * math.sqrt(d)
    x = x + positions(tokens.shape[-1], d)
    if rate > 0.0:
        k_pos, _ = dropout_keys(step_key, microbatch, 0)
        x = _drop(x, k_pos, rate)

    def body(x, inp):
        p, l = inp
        kl = None
        if rate > 0.0:
            _, k_stage = dropout_keys(step_key, microbatch, l // lps)
            kl = jax.random.fold_in(k_stage, l % lps)
        return _encoder_layer(x, p, kl, nhead=cfg["nhead"], rate=rate,
                              mm=mm), None

    x, _ = jax.lax.scan(body, x, (weights["layers"], jnp.arange(L)))
    logits = mm("bsd,dv->bsv", x, weights["dec_w"]) + weights["dec_b"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.mean(logz - gold, axis=-1))


# ---------------------------------------------------------------------------
# the training step


def init_opt(weights):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, weights)
    return {"mu": zeros,
            "nu": jax.tree_util.tree_map(jnp.zeros_like, weights),
            "count": jnp.zeros((), jnp.int32)}


@functools.partial(jax.jit, static_argnames=("cfg_items", "chunks",
                                             "n_stages", "precision",
                                             "fault"),
                   donate_argnums=(0, 1))
def _train_step(weights, opt, tokens, targets, step_key, lr, clip, *,
                cfg_items, chunks, n_stages, precision, fault):
    cfg = dict(cfg_items)
    rows = tokens.shape[0]
    mb = rows // chunks
    tok = tokens.reshape(chunks, mb, -1)
    tgt = targets.reshape(chunks, mb, -1)
    if fault == "half_batch":
        # the planted fault: the later half of the micro-batches left out,
        # the mean taken over the rest
        chunks = max(chunks // 2, 1)
        tok, tgt, rows = tok[:chunks], tgt[:chunks], chunks * mb
    elif fault == "frozen":
        # the planted fault: a step that returns its state unchanged
        lr = jnp.zeros_like(lr)
    elif fault is not None:
        raise ValueError(f"fault {fault!r}")

    def one(carry, inp):
        loss, grads = carry
        t, g, i = inp
        l, gr = jax.value_and_grad(microbatch_loss)(
            weights, t, g, step_key, i, cfg, n_stages=n_stages,
            precision=precision)
        return (loss + l, jax.tree_util.tree_map(jnp.add, grads, gr)), None

    zero = jax.tree_util.tree_map(jnp.zeros_like, weights)
    (loss, grads), _ = jax.lax.scan(
        one, (jnp.zeros((), jnp.float32), zero),
        (tok, tgt, jnp.arange(chunks)))
    loss = loss / rows
    grads = jax.tree_util.tree_map(lambda g: g / rows, grads)

    leaves = jax.tree_util.tree_leaves(grads)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in leaves))
    scale = jnp.where(gnorm < clip, 1.0, clip / gnorm)
    clipped = jax.tree_util.tree_map(lambda g: g * scale, grads)

    count = opt["count"] + 1
    t = count.astype(jnp.float32)
    mu = jax.tree_util.tree_map(
        lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g, opt["mu"], clipped)
    nu = jax.tree_util.tree_map(
        lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * jnp.square(g),
        opt["nu"], clipped)
    c1, c2 = 1 - ADAM_B1 ** t, 1 - ADAM_B2 ** t
    new = jax.tree_util.tree_map(
        lambda w, m, v: w - lr * (m / c1) / (jnp.sqrt(v / c2) + ADAM_EPS),
        weights, mu, nu)
    return new, {"mu": mu, "nu": nu, "count": count}, loss, clipped


def train_step(weights, opt, tokens, targets, step_key, cfg, *, lr, clip,
               chunks, n_stages=1, precision="f32", fault=None):
    """One step: ``(weights, opt, loss, gradient as Adam gets it)``. The
    batch loss is the mean over rows of each row's mean token cross-entropy;
    the gradient is clipped to global norm ``clip`` and fed to Adam
    (0.9, 0.999, 1e-8, bias-corrected); ``weights -= lr * update``."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str, bool))))
    return _train_step(weights, opt, jnp.asarray(tokens),
                       jnp.asarray(targets), step_key, jnp.float32(lr),
                       jnp.float32(clip), cfg_items=items, chunks=chunks,
                       n_stages=n_stages, precision=precision, fault=fault)


def leaf_norms(tree) -> dict:
    """``{name: float64 array}`` of the L2 norm of every leaf: one number
    for the embedding and decoder leaves, one per layer for the stacked."""
    out = {}
    for name in ("embed", "dec_w", "dec_b"):
        out[name] = np.asarray(jnp.sqrt(jnp.sum(jnp.square(
            tree[name].astype(jnp.float32)))), np.float64).reshape(1)
    for name in _LAYER_KINDS:
        a = tree["layers"][name].astype(jnp.float32)
        out[name] = np.asarray(jnp.sqrt(jnp.sum(
            jnp.square(a), axis=tuple(range(1, a.ndim)))), np.float64)
    return out


def sample_layers(n_layers: int):
    """The layers whose gradient is compared element by element: the
    first, the middle and the last."""
    return sorted({0, n_layers // 2, n_layers - 1})


def grad_sample(tree) -> dict:
    """``{name: array}`` of the leaves compared element by element: every
    kind of ``sample_layers`` and the embedding and decoder leaves."""
    out = {name: tree[name] for name in ("embed", "dec_w", "dec_b")}
    n_layers = tree["layers"]["wq"].shape[0]
    for l in sample_layers(n_layers):
        for kind in _LAYER_KINDS:
            out[f"{kind}[{l}]"] = tree["layers"][kind][l]
    return out
