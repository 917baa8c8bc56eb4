"""On-chip validation of the Pallas flash-attention kernel.

``python tools/tpu_validate.py [out.json]`` (on the TPU) runs the checks CI
cannot — the CPU tests interpret the kernel, and interpret mode has no PRNG,
so in-kernel dropout is TPU-only (see ``ops/pallas_attention.py``). It prints
the results and writes them to ``out.json`` only when a path is given.
``chip_smoke.py`` calls :func:`validate_flash` in-process. Per shape and
dtype:

1. forward parity vs the XLA reference attention (causal x non-causal);
2. gradient parity vs the XLA reference (no dropout) — the dQ and the dK/dV
   backward kernels;
3. in-kernel dropout determinism: same key -> bit-identical output and
   grads; different key -> different output;
4. in-kernel dropout unbiasedness: the mean over many keys of the dropped
   output approaches the undropped output (inverted-dropout scaling);
5. dropout backward self-consistency: the VJP regenerates the forward's
   masks bit-identically (grad of sum through same-key forwards agrees).
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from pipe_tpu.ops.pallas_attention import flash_attention

# The shapes chip_smoke.py and the CLI validate: head_dim 64 (the tutorial
# LM's), sequence lengths from the first one `auto` sends to the kernel
# (ops.layers.FLASH_AUTO_MIN_SEQ) up, in the compute and the master dtype.
SHAPES = tuple((2, s, 4, 64) for s in (256, 512, 1024))
DTYPES = (jnp.bfloat16, jnp.float32)


def xla_attention(q, k, v, causal, precision=jax.lax.Precision.HIGHEST):
    """Reference attention. On TPU a default-precision f32 einsum already
    runs as ONE bf16-input MXU pass (f32 accumulate), so the true-f32
    reference must force ``Precision.HIGHEST`` (bf16x3 passes); calling with
    ``Precision.DEFAULT`` instead yields exactly the single-pass hardware
    semantics — that is the accuracy yardstick the kernel is held to."""
    s = q.shape[1]
    scale = q.shape[-1] ** -0.5
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=precision,
                        preferred_element_type=jnp.float32) * scale
    if causal:
        mask = np.tril(np.ones((s, s), bool))
        logits = jnp.where(mask[None, None], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w.astype(q.dtype), v,
                      precision=precision,
                      preferred_element_type=jnp.float32)


def max_err(a, b):
    return float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                 - b.astype(jnp.float32))))


def validate_flash(shape, dtype, *, dropout: bool = True) -> dict:
    """Run the checks above at one ``(b, s, h, d)`` shape and dtype.

    Returns ``{"pass": bool, "checks": {name: {..., "pass": bool}}}``. The
    kernels run as the backend compiles them (``interpret`` is left to
    ``flash_attention``), so off the TPU pass ``dropout=False``.
    """
    checks = {}
    kq, kk, kv = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(kq, shape, dtype)
    k = jax.random.normal(kk, shape, dtype)
    v = jax.random.normal(kv, shape, dtype)
    # the reference sees the same (rounded) inputs, in f32
    qf, kf, vf = (a.astype(jnp.float32) for a in (q, k, v))

    # 1) forward parity. Yardstick: the error the MXU's own single-pass
    # bf16-input semantics (Precision.DEFAULT) makes against the forced-f32
    # reference (Precision.HIGHEST, bf16x3). The kernel's matmuls use the
    # same single-pass hardware mode, so it must land within 1.5x of that —
    # plus, for a bf16 output, half an ulp of the largest output value.
    for causal in (True, False):
        ref = xla_attention(qf, kf, vf, causal)
        err = max_err(jax.jit(lambda q, k, v: flash_attention(
            q, k, v, causal=causal))(q, k, v), ref)
        hw_err = max_err(
            xla_attention(qf, kf, vf, causal, jax.lax.Precision.DEFAULT),
            ref)
        out_round = (float(jnp.max(jnp.abs(ref))) * 2.0 ** -8
                     if dtype == jnp.bfloat16 else 0.0)
        tol = max(2e-3, 1.5 * hw_err) + out_round
        checks[f"fwd_parity_causal={causal}"] = {
            "max_abs_err": err, "hardware_mode_err": hw_err,
            "tol": tol, "pass": err < tol}

    # 2) gradient parity: dq from the dQ kernel, dk/dv from the dK/dV kernel
    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True)
                       .astype(jnp.float32) ** 2)

    def loss_xla(q, k, v):
        return jnp.sum(xla_attention(q, k, v, True) ** 2)

    gf = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    gx = jax.jit(jax.grad(loss_xla, argnums=(0, 1, 2)))(qf, kf, vf)
    # per-tensor relative error (dq/dk/dv scales differ; normalizing the
    # joint max by one tensor's scale would give spurious verdicts). bf16
    # adds the rounding of the forward output the loss squares and of each
    # returned gradient: 2^-8 relative each, with headroom.
    rels = [max_err(a, b) / max(float(jnp.max(jnp.abs(b))), 1e-9)
            for a, b in zip(gf, gx)]
    rel_tol = 4e-2 if dtype == jnp.bfloat16 else 2e-2
    checks["grad_parity"] = {
        "max_abs_err": max(max_err(a, b) for a, b in zip(gf, gx)),
        "rel_per_tensor": [round(r, 6) for r in rels],
        "rel": max(rels), "tol": rel_tol, "pass": max(rels) < rel_tol}

    if dropout:
        checks.update(_dropout_checks(q, k, v))
    return {"shape": list(shape), "dtype": jnp.dtype(dtype).name,
            "pass": all(c["pass"] for c in checks.values()),
            "checks": checks}


def _dropout_checks(q, k, v, n: int = 64) -> dict:
    """Checks 3-5; ``n`` keys feed the unbiasedness mean."""
    checks = {}
    rate = 0.3
    key = jax.random.key(7)
    f = jax.jit(lambda q, k, v, key: flash_attention(
        q, k, v, causal=True, dropout_rate=rate, dropout_key=key))

    # 3) dropout determinism
    o1, o2 = f(q, k, v, key), f(q, k, v, key)
    o3 = f(q, k, v, jax.random.key(8))
    checks["dropout_deterministic_same_key"] = {
        "pass": bool(jnp.array_equal(o1, o2))}
    checks["dropout_differs_across_keys"] = {
        "pass": not bool(jnp.array_equal(o1, o3))}

    # 4) dropout unbiasedness: E_key[dropped] ~ undropped. Per element, the
    # mean over K keys minus the undropped output, in units of its own
    # standard error (sample variance over the same K keys), is a t-score:
    # a wrong 1/(1-rate) scaling or a mask that ignores the key shifts
    # every element by many standard errors, while the first causal rows
    # (one or two keys to average over) are judged by their own noise.
    s1 = jnp.zeros(o1.shape, jnp.float32)
    s2 = jnp.zeros(o1.shape, jnp.float32)
    for i in range(n):
        o = f(q, k, v, jax.random.key(100 + i)).astype(jnp.float32)
        s1, s2 = s1 + o, s2 + o * o
    base = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v).astype(jnp.float32)
    mean = s1 / n
    var = (s2 / n - mean * mean) * (n / (n - 1.0))
    moved = var > 0.0               # dropout changed this element at all
    z = jnp.where(moved, (mean - base) / jnp.sqrt(
        jnp.where(moved, var, 1.0) / n), 0.0)
    moved_frac = float(jnp.mean(moved))
    mean_z2 = float(jnp.sum(z * z) / jnp.maximum(jnp.sum(moved), 1))
    max_z = float(jnp.max(jnp.abs(z)))
    # t(K-1): E[z^2] = (K-1)/(K-3) ~ 1.03 at K=64; P(|z| > 8) ~ 1e-10 per
    # element against ~5e5 elements
    checks["dropout_unbiased"] = {
        "keys": n, "moved_frac": moved_frac, "mean_z2": mean_z2,
        "max_abs_z": max_z,
        "rel_bias": max_err(mean, base) / max(
            float(jnp.max(jnp.abs(base))), 1e-9),
        "pass": moved_frac > 0.99 and 0.7 < mean_z2 < 1.5 and max_z < 8.0}

    # 5) dropout backward determinism (mask regeneration in bwd kernels)
    gdrop = jax.jit(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, dropout_rate=rate,
            dropout_key=key).astype(jnp.float32) ** 2), argnums=(0, 1, 2)))
    g1 = gdrop(q, k, v)
    g2 = gdrop(q, k, v)
    checks["dropout_grad_deterministic_and_finite"] = {
        "pass": (all(bool(jnp.array_equal(a, b)) for a, b in zip(g1, g2))
                 and all(bool(jnp.isfinite(a).all()) for a in g1))}
    return checks


def main(argv) -> int:
    on_tpu = jax.default_backend() == "tpu"
    results = {"platform": jax.default_backend(),
               "device_kind": jax.devices()[0].device_kind,
               "jax": jax.__version__,
               "runs": [validate_flash(shape, dtype, dropout=on_tpu)
                        for shape in SHAPES for dtype in DTYPES]}
    if not on_tpu:
        results["note"] = "dropout checks not run: in-kernel dropout is TPU-only"
    results["pass"] = all(r["pass"] for r in results["runs"])
    print(json.dumps(results, indent=2))
    if argv:
        with open(argv[0], "w") as f:
            json.dump(results, f, indent=2)
            f.write("\n")
    return 0 if results["pass"] else 1


if __name__ == "__main__":
    from pipe_tpu.utils.platform import configure_compile_cache
    configure_compile_cache()
    sys.exit(main(sys.argv[1:]))
