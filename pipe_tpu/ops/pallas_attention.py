"""Flash attention as a Pallas TPU kernel, with a full custom-VJP backward.

The reference's attention math lives in cuDNN via
``nn.TransformerEncoderLayer`` (``main.py:148``; SURVEY §2 native table —
"attention via ... a Pallas flash-attention kernel" is the designated
TPU-native replacement). This kernel keeps the O(s²) score matrix out of HBM:

* forward: grid over (batch·head, q-block); K/V stream through VMEM while a
  streaming-softmax (running max ``m``, normalizer ``l``) accumulates the
  output block on-chip; returns O and the per-row logsumexp ``L``;
* backward: the standard flash decomposition — ``D = rowsum(dO·O)``, then a
  dQ kernel (grid over q-blocks, loop over k-blocks) and a dK/dV kernel
  (grid over k-blocks, loop over q-blocks), each rebuilding ``p = exp(s−L)``
  from the saved ``L`` instead of storing attention weights;
* causal masking compares absolute positions, so any (block_q, block_k)
  tiling gives identical numbers;
* layouts follow the Mosaic block rule (last two block dims sublane/lane
  aligned): compute runs on ``[batch·head, seq, head_dim]`` views and the
  row statistics on ``[batch·head, 1, seq]``;
* off-TPU the same kernels run in interpreter mode (tests stay hermetic).

Attention-weight dropout runs *inside* the kernel on TPU (hardware PRNG
seeded per (batch·head, q-block, k-block), so forward and backward
regenerate identical masks without storing them; the normalizer ``l`` is
computed pre-dropout, matching ``dropout(softmax(s)) @ v`` semantics).
Interpret mode has no PRNG, so dropout-bearing steps off-TPU use the XLA
path (``ops.layers``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.telemetry import get_registry

__all__ = ["flash_attention", "supports"]

NEG_INF = float("-inf")


def supports(seq_len: int, *, block: int = 128, min_tile: int = 8) -> bool:
    """Whether the kernel handles this shape (else callers use the XLA path).

    Needs sublane-aligned rows (f32 tile: 8) and a block tiling that covers
    the sequence exactly (a block >= seq collapses to one full-seq block).
    """
    if seq_len < min_tile or seq_len % min_tile:
        return False
    return block >= seq_len or seq_len % block == 0


def _drop_mask(seed, bh, iq, ik, shape, rate):
    """Regenerable per-(batch*head, q-block, k-block) keep mask, scaled.

    Returns keep/rate scaling factors (0 where dropped). Seeding is a pure
    function of (seed, bh, iq, ik), so the backward kernels rebuild the
    identical mask without storing it.
    """
    # One mixed scalar (multi-operand seeding miscompiles inside fori_loop
    # on some Mosaic versions); constants are odd primes for bit dispersion.
    mixed = (seed
             + bh * jnp.int32(-1640531535)   # 2654435761 as int32
             + iq * jnp.int32(40503)
             + ik * jnp.int32(961748941))
    pltpu.prng_seed(mixed)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    threshold = jnp.uint32(min(int(rate * (1 << 32)), (1 << 32) - 1))
    keep = bits >= threshold
    return jnp.where(keep, 1.0 / (1.0 - rate), 0.0).astype(jnp.float32)


def _causal_mask(s, q_start, k_start, bq, bk):
    qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(qpos >= kpos, s, NEG_INF)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *, block_k,
                seq_len, causal, scale, dropout_rate):
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    q = q_ref[0, :, :] * scale                           # [bq, d]
    q_start = iq * bq

    o = jnp.zeros((bq, d), jnp.float32)
    m = jnp.full((bq,), NEG_INF, jnp.float32)
    l = jnp.zeros((bq,), jnp.float32)

    nk = seq_len // block_k
    nk_needed = nk if not causal else (q_start + bq - 1) // block_k + 1

    def body(ik, carry):
        o, m, l = carry
        k = k_ref[0, pl.ds(ik * block_k, block_k), :]    # [bk, d]
        v = v_ref[0, pl.ds(ik * block_k, block_k), :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [bq, bk]
        if causal:
            s = _causal_mask(s, q_start, ik * block_k, bq, block_k)
        block_max = jnp.max(s, axis=-1)
        new_m = jnp.maximum(m, block_max)
        safe_m = jnp.where(jnp.isfinite(new_m), new_m, 0.0)
        p = jnp.exp(s - safe_m[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        corr = jnp.where(jnp.isfinite(m), jnp.exp(m - safe_m), 0.0)
        l = l * corr + jnp.sum(p, axis=-1)   # normalizer: pre-dropout
        if dropout_rate > 0.0:
            p = p * _drop_mask(seed_ref[0], bh, iq, ik, p.shape,
                               dropout_rate)
        o = o * corr[:, None] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return o, new_m, l

    o, m, l = jax.lax.fori_loop(0, nk_needed, body, (o, m, l))
    o = o / jnp.maximum(l, 1e-30)[:, None]
    o_ref[0, :, :] = o.astype(o_ref.dtype)
    lse_ref[0, 0, :] = (jnp.where(jnp.isfinite(m), m, 0.0) +
                        jnp.log(jnp.maximum(l, 1e-30)))


def _smem_scalar_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _fwd(q3, k3, v3, seed, causal, scale, bq, bk, interpret, dropout_rate):
    bh, s, d = q3.shape
    grid = (bh, s // bq)
    qspec = pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0))
    kvspec = pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, block_k=bk, seq_len=s, causal=causal,
                          scale=scale, dropout_rate=dropout_rate),
        grid=grid,
        in_specs=[_smem_scalar_spec(), qspec, kvspec, kvspec],
        out_specs=[qspec,
                   pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j))],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
            jax.ShapeDtypeStruct((bh, 1, s), jnp.float32),
        ],
        interpret=interpret,
    )(seed, q3, k3, v3)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, *, block_k, seq_len, causal, scale,
                   dropout_rate):
    bq, d = q_ref.shape[1], q_ref.shape[2]
    bh = pl.program_id(0)
    iq = pl.program_id(1)
    q_start = iq * bq
    q = q_ref[0, :, :] * scale
    do = do_ref[0, :, :].astype(jnp.float32)
    lse = lse_ref[0, 0, :]
    delta = delta_ref[0, 0, :]

    nk = seq_len // block_k
    nk_needed = nk if not causal else (q_start + bq - 1) // block_k + 1

    def body(ik, dq):
        k = k_ref[0, pl.ds(ik * block_k, block_k), :]
        v = v_ref[0, pl.ds(ik * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, q_start, ik * block_k, bq, block_k)
        p = jnp.exp(s - lse[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            dp = dp * _drop_mask(seed_ref[0], bh, iq, ik, dp.shape,
                                 dropout_rate)
        ds = p * (dp - delta[:, None])
        return dq + jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, nk_needed, body, jnp.zeros((bq, d), jnp.float32))
    dq_ref[0, :, :] = (dq * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, *, block_q, seq_len, causal,
                    scale, dropout_rate):
    bk, d = k_ref.shape[1], k_ref.shape[2]
    bh = pl.program_id(0)
    ik = pl.program_id(1)
    k_start = ik * bk
    k = k_ref[0, :, :]
    v = v_ref[0, :, :]

    nq = seq_len // block_q
    iq0 = 0 if not causal else k_start // block_q

    def body(iq, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(iq * block_q, block_q), :] * scale
        do = do_ref[0, pl.ds(iq * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, 0, pl.ds(iq * block_q, block_q)]
        delta = delta_ref[0, 0, pl.ds(iq * block_q, block_q)]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal:
            s = _causal_mask(s, iq * block_q, k_start, block_q, bk)
        p = jnp.exp(s - lse[:, None])
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        if dropout_rate > 0.0:
            mask = _drop_mask(seed_ref[0], bh, iq, ik, p.shape, dropout_rate)
            p_v = p * mask
        else:
            mask = None
            p_v = p
        dv = dv + jax.lax.dot_general(
            p_v, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if mask is not None:
            dp = dp * mask
        ds = p * (dp - delta[:, None])
        dk = dk + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    dk, dv = jax.lax.fori_loop(
        iq0, nq, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32)))
    dk_ref[0, :, :] = dk.astype(dk_ref.dtype)
    dv_ref[0, :, :] = dv.astype(dv_ref.dtype)


def _bwd(causal, scale, bq, bk, interpret, dropout_rate, residuals, g):
    q3, k3, v3, seed, o3, lse = residuals
    do3 = g
    bh, s, d = q3.shape
    delta = jnp.einsum("bsd,bsd->bs", do3.astype(jnp.float32),
                       o3.astype(jnp.float32))[:, None, :]   # [bh, 1, s]

    qspec = pl.BlockSpec((1, bq, d), lambda i, j: (i, j, 0))
    full = pl.BlockSpec((1, s, d), lambda i, j: (i, 0, 0))
    row_q = pl.BlockSpec((1, 1, bq), lambda i, j: (i, 0, j))
    row_full = pl.BlockSpec((1, 1, s), lambda i, j: (i, 0, 0))

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_k=bk, seq_len=s,
                          causal=causal, scale=scale,
                          dropout_rate=dropout_rate),
        grid=(bh, s // bq),
        in_specs=[_smem_scalar_spec(), qspec, full, full, qspec, row_q,
                  row_q],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q3.dtype),
        interpret=interpret,
    )(seed, q3, k3, v3, do3, lse, delta)

    kspec = pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, seq_len=s,
                          causal=causal, scale=scale,
                          dropout_rate=dropout_rate),
        grid=(bh, s // bk),
        in_specs=[_smem_scalar_spec(), full, kspec, kspec, full, row_full,
                  row_full],
        out_specs=[kspec, kspec],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), k3.dtype),
                   jax.ShapeDtypeStruct((bh, s, d), v3.dtype)],
        interpret=interpret,
    )(seed, q3, k3, v3, do3, lse, delta)
    return dq, dk, dv, None


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _make(causal: bool, scale: float, bq: int, bk: int, interpret: bool,
          dropout_rate: float):
    @jax.custom_vjp
    def attend(q3, k3, v3, seed):
        o, _ = _fwd(q3, k3, v3, seed, causal, scale, bq, bk, interpret,
                    dropout_rate)
        return o

    def fwd(q3, k3, v3, seed):
        o, lse = _fwd(q3, k3, v3, seed, causal, scale, bq, bk, interpret,
                      dropout_rate)
        return o, (q3, k3, v3, seed, o, lse)

    attend.defvjp(fwd, functools.partial(_bwd, causal, scale, bq, bk,
                                         interpret, dropout_rate))
    return attend


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    dropout_rate: float = 0.0,
                    dropout_key: Optional[jax.Array] = None,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Flash attention over ``[batch, seq, heads, head_dim]`` inputs.

    ``interpret`` defaults to True off-TPU (tests/dev boxes) and False on
    TPU. Raises for shapes the tiling cannot cover — gate with
    :func:`supports` and fall back to the XLA path.

    ``dropout_rate`` > 0 applies attention-weight dropout *inside* the
    kernel (TPU hardware PRNG; masks are a pure function of
    ``dropout_key`` and block indices, so the backward kernels regenerate
    them bit-identically). Only available compiled on TPU — interpret mode
    has no PRNG — so callers must keep dropout off the interpret path.
    """
    b, s, h, d = q.shape
    if not supports(s, block=min(block_q, block_k)):
        raise ValueError(
            f"flash_attention: seq_len {s} not divisible into blocks; "
            f"use ops.layers.dot_product_attention")
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # trace-time counters: a run that must not interpret (chip_smoke.py)
    # asserts the first stays 0
    get_registry().counter("ops.flash_attention.interpreted" if interpret
                           else "ops.flash_attention.compiled").inc()
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1), got {dropout_rate}")
    if dropout_rate > 0.0:
        if interpret:
            raise NotImplementedError(
                "flash_attention dropout needs the TPU PRNG; interpret "
                "mode must use ops.layers.dot_product_attention")
        if dropout_key is None:
            raise ValueError("dropout_rate > 0 requires dropout_key")
        kd = jax.random.key_data(dropout_key).astype(jnp.uint32).ravel()
        seed = (kd[0] ^ kd[-1]).astype(jnp.int32).reshape((1,))
    else:
        seed = jnp.zeros((1,), jnp.int32)
    scale = float(scale if scale is not None else 1.0 / math.sqrt(d))
    bq = min(block_q, s)
    bk = min(block_k, s)
    # The kernels iterate s // bq and s // bk grids; a non-dividing block
    # (possible with mismatched non-default block_q/block_k) would silently
    # skip trailing positions instead of erroring (ADVICE r1).
    if s % bq or s % bk:
        raise ValueError(
            f"flash_attention: seq_len {s} must be divisible by block_q={bq} "
            f"and block_k={bk}; use ops.layers.dot_product_attention")

    def to3(x):  # [b, s, h, d] -> [b*h, s, d]
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    o3 = _make(causal, scale, bq, bk, bool(interpret),
               float(dropout_rate))(to3(q), to3(k), to3(v), seed)
    return o3.reshape(b, h, s, d).transpose(0, 2, 1, 3)
