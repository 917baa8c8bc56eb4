"""Plain reference of SDAR-30B-A3B-Chat's forward pass and of its generation
by diffusion over blocks, over the layers the configuration holds.

The equations (the program and this file both follow them). Every layer has
32 query heads over 4 KV heads of 128 (query head ``h`` reads KV head ``h //
8``), and ``L`` is the block length::

    a = RMSNorm(x, g1, eps 1e-6)
    q = a Wq  [32, 128];  k = a Wk,  v = a Wv  [4, 128]          (no biases)
    q_h <- g_q * q_h / rms(q_h),  k_h <- g_k * k_h / rms(k_h)    (QK-norm:
        gains of 128, eps 1e-6, on each head, before the rotary positions)
    rotary on q, k over the whole head, theta 1e6, dim i paired with i + 64
        (rotate-half)
    scores = q k^T / sqrt(128) in float32; position j is visible from i iff
        j // L <= i // L  (BLOCK-causal: a block sees itself whole)
    x <- x + concat(softmax(scores_h) v_{h // 8}) Wo
    m = RMSNorm(x, g2)
    r = softmax(m Wr) over all 128 outputs, in float32
    I = the eight largest;  w_i = r_i / sum_{j in I} r_j
    x <- x + sum_{i in I} w_i (silu(m Wgate_i) * (m Wup_i)) Wdown_i   width 768

then a final RMSNorm and the untied head. No shared expert, no bias.

Generation (``generate``). For a prompt of ``n`` tokens and ``m`` asked:
block by block from ``b = n // L``; the block's state is ``L`` tokens and
which are masked: the prompt's tail tokens (block ``b`` only) are revealed,
the rest are the mask token. ``T`` times while a position is masked: one
forward over everything up to the block's end; at each masked position
``c_p = max softmax(logits_p)`` and ``x_p = argmax`` (the position's own
logits, no shift); the ``ceil(masked / passes left)`` masked positions of
largest ``c_p`` take their ``x_p`` (a tie goes to the earlier position).
The finished block is then clean context for the next (the program's commit
pass: the keys and values its cache keeps are the clean tokens'). The reply
is the first ``m`` generated tokens. Greedy.

``denoise_hidden`` gives what a served request is checked against: the final
hidden states of every generated block in given noisy states, over the
clean earlier blocks, in ONE forward of ``[clean ; noisy copies]`` under the
mask that lets a noisy block see the clean blocks before it and itself
(rotary positions of a noisy copy are its twin's); ``head`` turns rows of
them into logits, a chunk at a time, so that ``[rows, 151936]`` float32 never
exists for a whole sequence.

Straightforward ``jax.numpy`` in float32 at matmul precision ``highest``: no
cache, no slots, no kernels, no grouped product. It imports nothing of the
program under test. ``make_weights`` makes the weights from the seed in the
types and the layout they are served in (bfloat16 matrices, float32 norm
gains, every leaf of the layers stacked on a leading layers axis), and the
harness hands the same arrays to the program; this file reads the same
values in float32. It computes attention a block of queries at a time and
the experts one at a time, so that it fits beside 8.1 GiB of weights.

``precision="fp8"`` is the control: every matrix product's operands are
rounded to float8 (e4m3, per-tensor absmax scaling) before a float32
product, the nearest precision below the configuration's bfloat16.
``cfg["fault"]`` plants one of ``FAULTS`` in the mathematics, for the
readings that set the cell's limits.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
EXPERT_CHUNK = 16
# planted faults: causal instead of all-to-all inside a block; QK-norm left
# out; the commit pass left out (the cache keeps the last denoise pass's
# keys and values: `kept_tokens`); the eight picks' weights not renormalised
FAULTS = ("causal_in_block", "no_qk_norm", "no_commit", "no_renorm")


def seed_key(seed: int):
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF), seed >> 31)


def generation(cfg: dict):
    """``(L, T, mask_token_id)``."""
    g = cfg["generation"]
    return g["block_length"], g["denoise_steps"], g["mask_token_id"]


# ---------------------------------------------------------------------------
# shapes and weights


def layer_shapes(cfg: dict) -> dict:
    """One layer's matrices ``{name: (shape, fan-in)}``."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    return {
        "wq": ((d, h * hd), d), "wk": ((d, hkv * hd), d),
        "wv": ((d, hkv * hd), d), "wo": ((h * hd, d), h * hd),
        # the router's "fan-in" gives its logits (of a row of unit RMS) the
        # deviation the configuration assumes for a trained router:
        # uniform +-b has variance b^2 / 3
        "router": ((d, e), d / (3.0 * cfg["router_init_logit_std"] ** 2)),
        "e_gate": ((e, d, f), d), "e_up": ((e, d, f), d),
        "e_down": ((e, f, d), f)}


def num_params(cfg: dict) -> int:
    """Parameters held here: the layers' matrices and norm gains (two of
    the hidden size, two of a head), the embedding, the head, the final
    norm."""
    d = cfg["hidden_size"]
    per = sum(int(np.prod(s)) for s, _ in layer_shapes(cfg).values())
    return (cfg["n_layers"] * (per + 2 * d + 2 * cfg["head_dim"])
            + 2 * cfg["vocab"] * d + d)


@functools.partial(jax.jit, static_argnames=("shape", "fan_in", "dtype"))
def _uniform(key, *, shape, fan_in, dtype):
    b = 1.0 / math.sqrt(fan_in)
    return jax.random.uniform(key, shape, jnp.float32, -b, b).astype(dtype)


@functools.partial(jax.jit, static_argnames=("fan_in",), donate_argnums=(0,))
def _fill(buf, key, layer, chunk, *, fan_in):
    """``EXPERT_CHUNK`` experts of layer ``layer`` of a stacked expert
    tensor, made in float32 and written in place: the whole tensor never
    exists in float32."""
    shape = (1, min(EXPERT_CHUNK, buf.shape[1])) + buf.shape[2:]
    b = 1.0 / math.sqrt(fan_in)
    part = jax.random.uniform(
        jax.random.fold_in(jax.random.fold_in(key, layer), chunk), shape,
        jnp.float32, -b, b).astype(buf.dtype)
    return jax.lax.dynamic_update_slice(
        buf, part, (layer, chunk * shape[1], 0, 0))


# the weights made last, and what they were made from
_LAST = {}
# a configuration is a dict and a jitted function's static arguments are
# hashed: the layers' programs are keyed by the configuration's text
_CFGS = {}


def _cfg_key(cfg: dict) -> str:
    key = json.dumps(cfg, sort_keys=True, default=str)
    _CFGS.setdefault(key, cfg)
    return key


def make_weights(cfg: dict, seed: int):
    """The weights as served, on the device: ``{"embed" [V, d], "head_w"
    [d, V], "lnf_g" [d], "layers": {name: [n_layers, ...]}}``, matrices in
    the configuration's compute type, norm gains in float32.

    The set made last is kept, and asked for again (the same configuration
    but for a planted fault, the same seed) it is given again: the arrays
    are a function of the two, they are 8.7 GB at the cell's size, and the
    harness asks twice in a run, for the program and later for the
    comparison, while its traced run still holds the engine and so the first
    set. A new request drops the old set before it makes its own."""
    key = (_cfg_key({k: v for k, v in cfg.items() if k != "fault"}),
           int(seed))
    if _LAST.get("key") != key:
        _LAST.clear()
        _LAST.update(key=key, weights=_make_weights(cfg, seed))
    return _LAST["weights"]


def _make_weights(cfg: dict, seed: int):
    dt = jnp.dtype(cfg["compute_dtype"])
    d, V, n, hd = (cfg["hidden_size"], cfg["vocab"], cfg["n_layers"],
                   cfg["head_dim"])
    k_embed, k_head, k_layers = jax.random.split(seed_key(seed), 3)
    layers = {"ln1_g": jnp.ones((n, d), jnp.float32),
              "ln2_g": jnp.ones((n, d), jnp.float32),
              "gq": jnp.ones((n, hd), jnp.float32),
              "gk": jnp.ones((n, hd), jnp.float32)}
    for i, (name, (shape, fan_in)) in enumerate(
            sorted(layer_shapes(cfg).items())):
        k = jax.random.fold_in(k_layers, i)
        if name.startswith("e_"):
            buf = jnp.zeros((n,) + shape, dt)
            for layer in range(n):
                for chunk in range(-(-shape[0] // EXPERT_CHUNK)):
                    buf = _fill(buf, k, layer, chunk, fan_in=fan_in)
            layers[name] = buf
        else:
            layers[name] = _uniform(k, shape=(n,) + shape, fan_in=fan_in,
                                    dtype=dt)
    return {
        "embed": (0.02 * jax.random.normal(k_embed, (V, d), jnp.float32)
                  ).astype(dt),
        "head_w": _uniform(k_head, shape=(d, V), fan_in=d, dtype=dt),
        "lnf_g": jnp.ones((d,), jnp.float32),
        "layers": layers,
    }


# ---------------------------------------------------------------------------
# the mathematics


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


def _rms_norm(x, g, eps):
    return (x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                              + eps) * g.astype(jnp.float32))


def _rope(x, positions, theta):
    """``x [b, s, H, D]`` at ``positions [s]``: rotate-half over the whole
    head."""
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (np.arange(0, 2 * half, 2, dtype=np.float64)
                          / (2 * half))
    ang = (positions.astype(jnp.float32)[:, None]
           * jnp.asarray(inv, jnp.float32)[None, :])
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _attention(a, p, where, cfg, mm):
    """``where = (positions [s], copy [s])``: row ``j`` is visible from
    ``i`` iff it is a clean row (copy 0) of an earlier block, or a row of
    ``i``'s own copy and block. All rows clean: the block-causal mask."""
    positions, copy = where
    b, s, _ = a.shape
    hd, hkv = cfg["head_dim"], cfg["num_key_value_heads"]
    heads = cfg["num_attention_heads"]
    eps, fault = cfg["rms_norm_eps"], cfg.get("fault")
    L = cfg["generation"]["block_length"]
    q = mm("bsd,de->bse", a, p["wq"]).reshape(b, s, heads, hd)
    k = mm("bsd,de->bse", a, p["wk"]).reshape(b, s, hkv, hd)
    v = mm("bsd,de->bse", a, p["wv"]).reshape(b, s, hkv, hd)
    if fault != "no_qk_norm":
        q, k = _rms_norm(q, p["gq"], eps), _rms_norm(k, p["gk"], eps)
    theta = float(cfg["rope_theta"])
    q, k = _rope(q, positions, theta), _rope(k, positions, theta)
    k = jnp.repeat(k, heads // hkv, axis=2)
    v = jnp.repeat(v, heads // hkv, axis=2)
    blk = positions // L
    outs = []
    for lo in range(0, s, Q_BLOCK):          # a block of queries at a time
        hi = min(lo + Q_BLOCK, s)
        bi, ci, pi = blk[lo:hi, None], copy[lo:hi, None], \
            positions[lo:hi, None]
        own = (copy[None, :] == ci) & (blk[None, :] == bi)
        if fault == "causal_in_block":
            own = own & (positions[None, :] <= pi)
        seen = ((copy[None, :] == 0) & (blk[None, :] < bi)) | own
        scores = mm("bqhd,bkhd->bhqk", q[:, lo:hi], k) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        outs.append(mm("bhqk,bkhd->bqhd", w, v))
    o = jnp.concatenate(outs, axis=1)                      # [b, s, H, D]
    return mm("bsd,de->bse", o.reshape(b, s, heads * hd), p["wo"])


def _gated_mlp(m, w_gate, w_up, w_down, mm):
    return mm("...f,fd->...d",
              jax.nn.silu(mm("...d,df->...f", m, w_gate))
              * mm("...d,df->...f", m, w_up), w_down)


def _experts(m, p, layer, cfg, mm):
    """``sum_{i in I} w_i E_i(m)``, the experts one at a time over every
    row (a row's weight for an expert it did not pick is 0). ``p["e_*"]``
    are the stacked tensors ``[layers, experts, ...]``."""
    r = jax.nn.softmax(mm("td,de->te", m, p["router"]), axis=-1)
    top_r, top_e = jax.lax.top_k(r, cfg["num_experts_per_tok"])
    w = top_r if cfg.get("fault") == "no_renorm" \
        else top_r / jnp.sum(top_r, -1, keepdims=True)
    weight = jnp.einsum(
        "tk,tke->te", w,
        jax.nn.one_hot(top_e, r.shape[-1], dtype=jnp.float32))   # [t, E]

    def matrix(name, e):
        a = p[name]
        return jax.lax.dynamic_slice(
            a, (layer, e, 0, 0), (1, 1) + a.shape[2:])[0, 0]

    def one(e, acc):
        w_e = jax.lax.dynamic_index_in_dim(weight, e, 1)           # [t, 1]
        return acc + w_e * _gated_mlp(m, matrix("e_gate", e),
                                      matrix("e_up", e),
                                      matrix("e_down", e), mm)

    return jax.lax.fori_loop(0, r.shape[-1], one, jnp.zeros_like(m))


@functools.partial(jax.jit, static_argnames=("cfg_key", "precision"))
def _one_layer(x, layers, layer, positions, copy, *, cfg_key, precision):
    cfg = _CFGS[cfg_key]
    mm = _mm(precision)
    p = {n: (a if n.startswith("e_") else
             jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False))
         for n, a in layers.items()}
    eps = cfg["rms_norm_eps"]
    x = x + _attention(_rms_norm(x, p["ln1_g"], eps), p, (positions, copy),
                       cfg, mm)
    m = _rms_norm(x, p["ln2_g"], eps)
    b, s, d = m.shape
    return x + _experts(m.reshape(b * s, d), p, layer, cfg, mm).reshape(
        b, s, d)


@functools.partial(jax.jit, static_argnames=("eps", "precision"))
def _head(x, g, w, *, eps, precision):
    return _mm(precision)("...d,dv->...v", _rms_norm(x, g, eps), w)


def _check(cfg):
    if cfg.get("fault") not in (None,) + FAULTS:
        raise ValueError(f"fault {cfg['fault']!r}: one of {FAULTS}")


def hidden(weights, tokens, positions, copy, cfg: dict,
           precision: str = "f32"):
    """The last layer's output ``[rows, s, d]`` over ``tokens [rows, s]``,
    every row of ``s`` at ``positions [s]`` and in copy ``copy [s]`` (0:
    clean), layer by layer (one program)."""
    _check(cfg)
    key = _cfg_key(cfg)
    positions = jnp.asarray(positions, jnp.int32)
    copy = jnp.asarray(copy, jnp.int32)
    x = jnp.take(weights["embed"], tokens, axis=0).astype(jnp.float32)
    for i in range(cfg["n_layers"]):
        x = _one_layer(x, weights["layers"], i, positions, copy,
                       cfg_key=key, precision=precision)
    return x


def head(weights, x, cfg: dict, precision: str = "f32"):
    """Float32 logits ``[..., vocab]`` of hidden rows ``x [..., d]``."""
    return _head(x, weights["lnf_g"], weights["head_w"],
                 eps=cfg["rms_norm_eps"], precision=precision)


def forward(weights, tokens, cfg: dict, precision: str = "f32"):
    """Logits ``[rows, positions, vocab]`` in float32 of the full forward
    pass over ``tokens [rows, positions]`` under the block-causal mask."""
    s = tokens.shape[1]
    return head(weights, hidden(weights, tokens, np.arange(s),
                                np.zeros(s, np.int32), cfg, precision),
                cfg, precision)


def reveal(conf, masked, passes_left):
    """Which of a block's ``masked`` positions a pass reveals: the ``ceil(
    masked / passes_left)`` of largest ``conf``, a tie to the earlier."""
    conf, masked = np.asarray(conf, np.float64), np.asarray(masked, bool)
    n = -(-int(masked.sum()) // passes_left)
    order = np.argsort(np.where(masked, -conf, 1.0), kind="stable")
    out = np.zeros(masked.shape, bool)
    out[order[:n]] = True
    return out & masked


def generate(weights, prompt, m: int, cfg: dict, precision: str = "f32"):
    """``(tokens [m], reveal_pass [m], passes)``: the reply to ``prompt``,
    the denoise pass of its block that revealed each token, and every pass
    as ``(first position of the block, pass, logits [L, vocab], masked
    before [L], revealed [L])``. A full forward a pass, no cache."""
    L, T, mask_id = generation(cfg)
    n = len(prompt)
    seq, rev, passes = list(prompt), [], []
    while len(seq) - n < m:
        start = len(seq) - len(seq) % L
        block = np.asarray(seq[start:] + [mask_id] * (start + L - len(seq)),
                           np.int32)
        masked = np.arange(L) >= len(seq) - start
        own = masked.copy()
        at = np.zeros(L, np.int64)
        for t in range(T):
            if not masked.any():
                break
            tokens = jnp.asarray(np.concatenate(
                [np.asarray(seq[:start], np.int32), block])[None], jnp.int32)
            logits = np.asarray(
                forward(weights, tokens, cfg, precision)[0, start:])
            # max softmax: 1 / sum exp(logits - their best)
            conf = 1.0 / np.exp(
                logits - logits.max(-1, keepdims=True)).sum(-1)
            now = reveal(conf, masked, T - t)
            passes.append((start, t, logits, masked.copy(), now))
            block = np.where(now, logits.argmax(-1), block).astype(np.int32)
            at[now] = t
            masked = masked & ~now
        seq = seq[:start] + block.tolist()
        rev += at[own].tolist()
    return seq[n:n + m], rev[:m], passes


def kept_tokens(seq, n_prompt: int, reveal_pass, cfg: dict):
    """The tokens whose keys and values the cache keeps for ``seq`` (a
    prompt of ``n_prompt`` and its reply): ``seq`` itself, the commit
    pass's. With the fault ``no_commit`` the last denoise pass's: a
    generated position that its block's last pass revealed is still the
    mask token there."""
    _, T, mask_id = generation(cfg)
    out = np.asarray(seq, np.int32).copy()
    if cfg.get("fault") == "no_commit":
        late = np.asarray(reveal_pass) == T - 1
        out[n_prompt:][late[:len(out) - n_prompt]] = mask_id
    return out


def noisy_states(seq, n_prompt: int, reveal_pass, first: int, cfg: dict):
    """``[T, len(seq) - first]``: positions ``first ..`` of ``seq`` as they
    stood before each denoise pass ``t`` of their block: a generated
    position revealed at pass ``t`` or later is the mask token."""
    _, T, mask_id = generation(cfg)
    at = np.full(len(seq), -1)
    at[n_prompt:] = np.asarray(reveal_pass)[:len(seq) - n_prompt]
    seq = np.asarray(seq, np.int32)
    return np.stack([np.where(at[first:] >= t, mask_id, seq[first:])
                     for t in range(T)]).astype(np.int32)


def denoise_hidden(weights, clean, noisy, first: int, cfg: dict,
                   precision: str = "f32", pad_to: int = 1):
    """The final hidden states ``[copies, n, d]`` of ``noisy [copies, n]``
    (copies of positions ``first .. first + n - 1``, whole blocks, each in
    a noisy state) over ``clean`` (the tokens of positions ``0 ..``): ONE
    forward of ``[clean ; noisy copies]`` in which a noisy block sees the
    clean blocks before it and itself. The sequence is padded to a
    multiple of ``pad_to`` with rows that see themselves alone."""
    L = cfg["generation"]["block_length"]
    clean, noisy = np.asarray(clean, np.int32), np.asarray(noisy, np.int32)
    copies, n = noisy.shape
    if first % L or n % L:
        raise ValueError(f"noisy copies of {n} positions from {first}: "
                         f"whole blocks of {L}")
    tokens = np.concatenate([clean, noisy.reshape(-1)])
    positions = np.concatenate(
        [np.arange(len(clean))] + [first + np.arange(n)] * copies)
    copy = np.concatenate(
        [np.zeros(len(clean), np.int32),
         np.repeat(np.arange(1, copies + 1, dtype=np.int32), n)])
    pad = -len(tokens) % pad_to
    # a padding row: a copy of its own, a block of its own
    tokens = np.concatenate([tokens, np.zeros(pad, np.int32)])
    positions = np.concatenate(
        [positions, L * (positions.max() // L + 1 + np.arange(pad))])
    copy = np.concatenate([copy, np.full(pad, copies + 1, np.int32)])
    x = hidden(weights, jnp.asarray(tokens[None]), positions, copy, cfg,
               precision)
    return x[0, len(clean):len(clean) + copies * n].reshape(copies, n, -1)


def denoise_logits(weights, clean, noisy, first: int, cfg: dict,
                   precision: str = "f32"):
    """``head(denoise_hidden(...))`` whole, ``[copies, n, vocab]``: for
    small sizes."""
    return head(weights, denoise_hidden(weights, clean, noisy, first, cfg,
                                        precision), cfg, precision)


@jax.jit
def best_and_chosen(logits, chosen):
    """For each row of ``logits [rows, vocab]``: its best logit, the logit
    of ``chosen [rows]``, the token put first, and the log of the best's
    probability (the log-confidence)."""
    best = jnp.max(logits, axis=-1)
    got = jnp.take_along_axis(logits, chosen[..., None], axis=-1)[..., 0]
    return (best, got, jnp.argmax(logits, axis=-1).astype(jnp.int32),
            best - jax.nn.logsumexp(logits, axis=-1))
