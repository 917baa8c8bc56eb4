"""Mixture-of-Experts FFN with expert parallelism (GShard/Switch lineage).

Beyond the reference (no MoE/EP there — SURVEY §2 strategy table). The
TPU-shaped design:

* **Dense dispatch, static shapes**: routing is expressed as one-hot
  dispatch/combine einsums over a fixed per-expert ``capacity`` (GShard's
  formulation) — no dynamic shapes, no sorting; XLA tiles the whole layer
  onto the MXU. Tokens over capacity fall through on the residual stream
  (standard switch behavior).
* **Expert sharding over the ``model`` mesh axis**: expert-indexed leaves
  (``w1/b1/w2/b2`` ``[E, ...]`` and the router's expert columns) shard on
  their expert dim, so each device holds ``E/ep`` experts and computes
  only their capacity slots — compute and memory scale ``1/ep``.
* **Same grad contract as tensor parallelism** (:mod:`.tp_layers`): the
  region is bracketed by the *f*/*g* custom-vjp operators (``tp_enter`` /
  ``tp_allreduce``), every sharded leaf's gradient is local by
  construction (the router weight is sharded BY EXPERT COLUMN for exactly
  this reason — its full-logit row assembles through one ``tp_allreduce``
  of zero-padded local logits), replicated leaves' gradients are
  model-identical, and executors never reduce gradients over the axis.
  Communication: two psums per MoE layer (logits assembly + output
  combine), riding the innermost (fastest-ICI) axis.

``ep_axis=None`` runs the identical math unsharded — the transparency
yardstick (``tests/test_moe.py``).

Beside it, for serving a chip's share of a large expert layer:
:func:`dropless_moe` (no capacity, no dropped token: the rows are sorted
by expert and the held experts' rows go through one grouped product).
Two implementations of that product, picked from the call's static shapes
(:func:`grouped_impl`): the compiler's own kernel (``jax.lax.ragged_dot``)
where the pairs are few (a decode step), and
:func:`~pipe_tpu.ops.grouped_product.grouped_gated_mlp`, one Pallas kernel
for the three products and the gate, in row tiles the size of an expert's
share, where they are many (a prefill).
The capacity layer above stays the training path's.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..core.partition import StageCtx
from ..obs.events import MOE_EXPERTS, MOE_ROUTER, device_scope
from ..obs.telemetry import get_registry
from ..parallel.mesh import MODEL_AXIS
from .grouped_product import grouped_gated_mlp, tile_groups
from .tp_layers import (tp_allreduce, tp_attention_init,
                        tp_attention_sublayer, tp_enter, _dropout,
                        _layernorm)

__all__ = ["moe_ffn_init", "moe_ffn_apply", "moe_ffn_specs", "moe_capacity",
           "moe_block_init", "moe_block_apply", "moe_block_decode",
           "moe_block_specs", "dropless_moe_init", "dropless_moe",
           "DROPLESS_COUNTS", "grouped_impl"]


def moe_ffn_init(key: jax.Array, d_model: int, d_ff: int, n_experts: int,
                 dtype=jnp.float32) -> Dict[str, Any]:
    ks = jax.random.split(key, 3)
    s_in = 1.0 / jnp.sqrt(d_model)
    s_out = 1.0 / jnp.sqrt(d_ff)
    return {
        "wr": jax.random.normal(ks[0], (d_model, n_experts), dtype) * s_in,
        "br": jnp.zeros((n_experts,), dtype),
        "w1": jax.random.normal(ks[1], (n_experts, d_model, d_ff),
                                dtype) * s_in,
        "b1": jnp.zeros((n_experts, d_ff), dtype),
        "w2": jax.random.normal(ks[2], (n_experts, d_ff, d_model),
                                dtype) * s_out,
        "b2": jnp.zeros((n_experts, d_model), dtype),
    }


def moe_ffn_specs() -> Dict[str, Any]:
    """Per-leaf PartitionSpecs: every expert-indexed dim shards over the
    model axis (incl. the router's expert columns)."""
    m = MODEL_AXIS
    return {
        "wr": P(None, m), "br": P(m),
        "w1": P(m, None, None), "b1": P(m, None),
        "w2": P(m, None, None), "b2": P(m, None),
    }


def moe_capacity(n_tokens: int, n_experts: int, k: int,
                 capacity_factor: float) -> int:
    """GShard capacity: per-expert slot count for a ``[n_tokens]`` batch."""
    return max(1, int(capacity_factor * n_tokens * k / n_experts))


def moe_ffn_apply(p: Dict[str, Any], h: jax.Array, ctx: StageCtx, *,
                  n_experts: int, k: int = 2,
                  capacity_factor: float = 1.25,
                  ep_axis: Optional[str] = MODEL_AXIS):
    """Top-k token-choice MoE FFN on LOCAL expert shards.

    ``h``: ``[rows, seq, d]`` replicated over the expert axis. Returns
    ``(out, aux_loss)`` where ``aux_loss`` is the standard load-balancing
    auxiliary (mean over experts of fraction-routed x mean-gate, scaled by
    E — Switch's formulation), identical on every shard.
    """
    if ep_axis is not None:
        psum = lambda v: tp_allreduce(v, ep_axis)
        h = tp_enter(h, ep_axis)
        ep = jax.lax.psum(1, ep_axis)
        shard = jax.lax.axis_index(ep_axis)
        ep_static = jax.core.concrete_or_error(
            int, ep, "expert-axis size must be static")
        if n_experts % ep_static:
            raise ValueError(
                f"n_experts={n_experts} not divisible by the expert-axis "
                f"size {ep_static}: orphaned experts would receive router "
                f"mass but produce zero output")
    else:
        psum = lambda v: v
        ep = 1
        shard = 0
    rows, seq, d = h.shape
    T = rows * seq
    E = n_experts
    e_local = E // ep
    x = h.reshape(T, d)

    # --- router: local expert columns -> full logits via one psum ------
    local_logits = x @ p["wr"] + p["br"]            # [T, E/ep]
    if ep_axis is not None:
        full = jnp.zeros((T, E), local_logits.dtype)
        full = jax.lax.dynamic_update_slice(
            full, local_logits, (0, shard * e_local))
        logits_raw = psum(full)
        # The GATING path's cotangents are shard-partial (each shard's
        # combine touches only its local experts' terms) and softmax
        # couples every column, so the full-logit cotangent must psum
        # before the router weight's column slice: a second f operator.
        # (softmax's vjp is linear in the cotangent, so psum-below ==
        # psum-above.) The AUX path's cotangents are shard-identical
        # (replicated aux value), so it branches off BEFORE tp_enter —
        # through the f operator it would be overcounted ep times.
        logits = tp_enter(logits_raw, ep_axis)
    else:
        logits_raw = local_logits
        logits = local_logits
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gates_aux = jax.nn.softmax(logits_raw.astype(jnp.float32), axis=-1)

    top_g, top_e = jax.lax.top_k(gates, k)          # [T, k]
    top_g = top_g / jnp.sum(top_g, axis=-1, keepdims=True)  # renormalize

    # --- capacity positions (computed identically on every shard) -----
    C = moe_capacity(T, E, k, capacity_factor)
    # flatten the k slots in priority order (slot 0 of every token first)
    flat_e = top_e.T.reshape(-1)                    # [k*T]
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)      # [kT, E]
    pos = jnp.cumsum(onehot, axis=0) - 1            # position within expert
    flat_pos = jnp.sum(pos * onehot, axis=-1)       # [kT]
    keep = flat_pos < C
    flat_g = top_g.T.reshape(-1).astype(h.dtype) * keep

    # --- dispatch/combine one-hots over LOCAL experts ------------------
    le = flat_e - shard * e_local                   # local expert index
    local = (flat_e >= shard * e_local) & (flat_e < (shard + 1) * e_local)
    sel = local & keep
    # [kT, E/ep, C] one-hot (0 rows where not selected)
    disp = (jax.nn.one_hot(le, e_local, dtype=h.dtype)[:, :, None]
            * jax.nn.one_hot(flat_pos, C, dtype=h.dtype)[:, None, :]
            * sel[:, None, None].astype(h.dtype))
    tok = jnp.tile(jnp.arange(T), k)                # [kT] token of each slot
    xk = x[tok]                                     # [kT, d]
    x_e = jnp.einsum("tec,td->ecd", disp, xk)       # [E/ep, C, d]

    inner = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", x_e, p["w1"])
                        + p["b1"][:, None])
    y_e = jnp.einsum("ecf,efd->ecd", inner, p["w2"]) + p["b2"][:, None]

    comb = disp * flat_g[:, None, None]             # gate-weighted combine
    y_flat = jnp.einsum("tec,ecd->td", comb, y_e)   # [kT, d] partial
    y_tok = jnp.sum(y_flat.reshape(k, T, d), axis=0)
    out = psum(y_tok).reshape(rows, seq, d)

    # --- load-balance aux (Switch): E * sum_e f_e * m_e ----------------
    # computed from the pre-tp_enter softmax (see router note above)
    assign1 = jax.nn.one_hot(top_e[:, 0], E, dtype=jnp.float32)
    frac = jnp.mean(assign1, axis=0)                # fraction routed (top-1)
    mean_gate = jnp.mean(gates_aux, axis=0)
    aux = E * jnp.sum(frac * mean_gate)
    return out, aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# MoE transformer block: TP attention + MoE FFN (the standard hybrid —
# attention heads AND experts shard over the same innermost mesh axis)
# ---------------------------------------------------------------------------

def moe_block_init(key: jax.Array, d_model: int, nhead: int, d_ff: int,
                   n_experts: int, dtype=jnp.float32) -> Dict[str, Any]:
    ka, km = jax.random.split(key)
    p = tp_attention_init(ka, d_model, nhead, dtype)   # attention + both LNs
    p["moe"] = moe_ffn_init(km, d_model, d_ff, n_experts, dtype)
    return p


def moe_block_specs() -> Dict[str, Any]:
    from .tp_layers import tp_block_specs
    t = tp_block_specs()
    return {
        "ln1": t["ln1"], "wqkv": t["wqkv"], "bqkv": t["bqkv"],
        "wo": t["wo"], "bo": t["bo"], "ln2": t["ln2"],
        "moe": moe_ffn_specs(),
    }


def moe_block_decode(p: Dict[str, Any], h: jax.Array, cache, pos, *,
                     n_experts: int, k: int = 2,
                     capacity_factor: float = 1.25,
                     ep_axis: Optional[str] = MODEL_AXIS):
    """Incremental :func:`moe_block_apply` with a KV cache (inference):
    cached TP attention (heads sharded over the same axis as the
    experts), then the MoE FFN on the new positions — routing is
    per-token, so the dense dispatch works unchanged at q=1; the aux loss
    is discarded (inference). NOTE: GShard capacity is computed from the
    CURRENT call's token count, so at tiny decode batches use a generous
    ``capacity_factor`` if parity with a full-sequence forward matters
    (over-capacity tokens fall through on the residual, in both paths).
    Returns ``(h, new_cache)``."""
    from .tp_layers import tp_attention_decode

    h, cache = tp_attention_decode(p, h, cache, pos, tp_axis=ep_axis)
    hn = _layernorm(h, p["ln2"])
    ff, _aux = moe_ffn_apply(p["moe"], hn, StageCtx(), k=k,
                             n_experts=n_experts,
                             capacity_factor=capacity_factor,
                             ep_axis=ep_axis)
    return h + ff, cache


def moe_block_apply(p: Dict[str, Any], h: jax.Array, ctx: StageCtx, *,
                    n_experts: int, k: int = 2,
                    capacity_factor: float = 1.25, dropout: float = 0.0,
                    causal: bool = True,
                    ep_axis: Optional[str] = MODEL_AXIS):
    """Pre-LN block: TP attention sublayer, then the MoE FFN on the
    LayerNorm'd stream with a residual add (dropped tokens pass through on
    the residual). Returns ``(h, aux)``."""
    key1 = key2 = None
    if ctx.key is not None:
        key1, key2 = jax.random.split(ctx.key)
    h = tp_attention_sublayer(p, h, causal=causal, dropout=dropout,
                              key=key1, tp_axis=ep_axis)
    hn = _layernorm(h, p["ln2"])
    # moe_ffn_apply is deterministic (no ctx.key use); key2 is reserved
    # for the residual dropout below
    ff, aux = moe_ffn_apply(p["moe"], hn, StageCtx(), k=k,
                            n_experts=n_experts,
                            capacity_factor=capacity_factor,
                            ep_axis=ep_axis)
    return h + _dropout(ff, dropout, key2), aux


# ---------------------------------------------------------------------------
# Dropless top-k routing over a chip's share of the experts (serving)
# ---------------------------------------------------------------------------

# what :func:`dropless_moe` counts, in the order of its second result
DROPLESS_COUNTS = ("expert_rows", "absent_rows", "experts_touched")


def dropless_moe_init(key: jax.Array, d_model: int, d_ff: int,
                      n_experts: int, held: int,
                      dtype=jnp.float32) -> Dict[str, Any]:
    """A router over all ``n_experts`` and the weights of the ``held``
    experts that live here, each a gated SiLU MLP of width ``d_ff``."""
    ks = jax.random.split(key, 4)

    def mat(k, shape, fan_in):
        b = 1.0 / float(fan_in) ** 0.5
        return jax.random.uniform(k, shape, dtype, -b, b)

    return {"router": mat(ks[0], (d_model, n_experts), d_model),
            "w_gate": mat(ks[1], (held, d_model, d_ff), d_model),
            "w_up": mat(ks[2], (held, d_model, d_ff), d_model),
            "w_down": mat(ks[3], (held, d_ff, d_model), d_ff)}


# Pairs a held expert (the mean the static shapes give) from which the tiled
# kernel takes the grouped products. One layer's three products on a v5e at
# hidden 3072, width 1024, 128 of 256 experts held, top-10
# (``tools/grouped_product_bench.py``, PR 33), compiler's | tiled, in ms:
# 160 pairs (a decode step of 16 slots, 1.25 an expert) 1.415 | 1.418, the
# whole layer 1.449 | 1.463; 640 pairs (5 an expert) 3.66 | 2.98;
# 1,280 4.85 | 3.23; 2,560 7.74 | 3.31; 5,120 8.00 | 3.48; 10,240 8.41 |
# 3.80; 20,480 9.55 | 4.44. The two cross between 1.25 and 5. At hidden
# 2048, width 768, all 128 experts held, top-8 (PR 34: SDAR's block round
# takes the tiled product AT DECODE, 8 real rows in a 128-row tile): 256
# pairs (2 an expert) 2.634 | 1.448; 512 4.447 | 1.686; 1,024 (a pass of 32
# slots, 8 an expert) 4.471 | 1.719; 2,048 4.645 | 1.844, the two bit for
# bit alike. There the tiled one wins from 2 an expert down; nothing runs
# between 1.25 and 4, so the rule stands and both models stay on their sides.
_TILED_FROM = 4


def grouped_impl(pairs: int, held: int) -> str:
    """Which implementation :func:`dropless_moe` gives the grouped product
    of ``pairs`` (rows x picks) over ``held`` experts: ``"compiler"``
    (``jax.lax.ragged_dot``) or ``"tiled"``
    (:func:`~pipe_tpu.ops.grouped_product.grouped_gated_mlp`). A rule over
    the call's static shapes and nothing else."""
    return "tiled" if pairs >= _TILED_FROM * held else "compiler"


def dropless_moe(p: Dict[str, Any], x: jax.Array, *, top_k: int,
                 first: int = 0, scale: float = 1.0, live=None, layer=None):
    """The routed part of an expert layer as ONE member of an
    expert-parallel group computes it: ``sum_{i in I, i held} w_i E_i(x)``.

    ``x [rows, d]``. ``p["router"] [d, E]`` has the PUBLISHED width: every
    row is scored against all ``E`` experts (logits, softmax and top-k in
    float32), its ``top_k`` picks ``I`` renormalised over all of them and
    scaled (``w_i = scale r_i / sum_{j in I} r_j``), whether or not pick
    ``i`` lives here. ``p["w_gate"]``, ``p["w_up"] [held, d, f]`` and
    ``p["w_down"] [held, f, d]`` are experts ``first .. first + held - 1``.
    Pairs whose expert is absent are dropped from the product, not stood
    in for: what those experts would add is another chip's part of the
    sum. ``live [rows]`` (optional) drops a row's pairs likewise (a dead
    slot, a bucket's padding). With ``layer`` (a traced index) the three
    expert tensors lead with a layers axis, ``[layers, held, ...]``, and
    the product takes layer ``layer`` of them where they lie, as groups
    ``layer * held ..`` of ``layers * held``: a scan over like layers
    never slices a layer's experts out (a copy of all of them a step).
    No capacity and no overflow: the pairs are
    sorted by expert and the held ones go through one grouped product,
    which reads the weights of the experts that have rows and of no other,
    bfloat16 operands accumulated and returned in float32. One function
    for a prefill's rows and a decode step's, and two implementations of
    the product, by :func:`grouped_impl` over the static shapes: few pairs
    (a decode step) stream through the compiler's kernel
    (``jax.lax.ragged_dot``), many (a prefill) go in row tiles the size of
    an expert's share (:mod:`~pipe_tpu.ops.grouped_product`). Trace-time
    counters ``ops.moe.grouped.compiler`` and ``ops.moe.grouped.tiled``
    say which a program took, once a call.

    Returns ``(y [rows, d] in x's type, counts int32[3])``, the counts in
    :data:`DROPLESS_COUNTS`' order: pairs computed here, pairs of live
    rows left to absent experts, held experts with at least one row."""
    held = p["w_gate"].shape[0 if layer is None else 1]
    return _dropless_moe(p, x, top_k=top_k, first=first, scale=scale,
                         live=live, layer=layer,
                         impl=grouped_impl(x.shape[0] * top_k, held))


def _dropless_moe(p, x, *, top_k, first, scale, live, layer, impl):
    """:func:`dropless_moe` with the grouped product's implementation
    named (``"compiler"`` | ``"tiled"``)."""
    rows, d = x.shape
    held = p["w_gate"].shape[0 if layer is None else 1]
    get_registry().counter(f"ops.moe.grouped.{impl}").inc()
    f32 = jnp.float32
    with device_scope(MOE_ROUTER):
        logits = jnp.einsum("td,de->te", x.astype(f32),
                            p["router"].astype(f32),
                            precision=jax.lax.Precision.HIGHEST)
        top_r, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        w = scale * top_r / jnp.sum(top_r, axis=-1, keepdims=True)
        local = top_e - first
        here = (local >= 0) & (local < held)
        if live is not None:
            here = here & live[:, None]
        # an absent pair sorts behind every held expert's
        flat_e = jnp.where(here, local, held).reshape(-1)   # [rows * k]
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        bounds = jnp.searchsorted(
            sorted_e, jnp.arange(held + 1, dtype=sorted_e.dtype))
        sizes = (bounds[1:] - bounds[:-1]).astype(jnp.int32)   # [held]
        computed = bounds[-1].astype(jnp.int32)
    with device_scope(MOE_EXPERTS):
        experts = {n: p[n] for n in ("w_gate", "w_up", "w_down")}
        first_group = 0
        if layer is not None:
            # the layer's experts where they lie: groups ``layer * held ..``
            layers = p["w_gate"].shape[0]
            experts = {n: a.reshape((layers * held,) + a.shape[2:])
                       for n, a in experts.items()}
            first_group = layer * held
        xs = jnp.take(x, order // top_k, axis=0)            # [rows * k, d]
        if impl == "tiled":
            ys = grouped_gated_mlp(
                xs, experts["w_gate"], experts["w_up"], experts["w_down"],
                tile_groups(sizes, rows * top_k), first_group=first_group)
        else:
            groups = sizes
            if layer is not None:
                groups = jax.lax.dynamic_update_slice(
                    jnp.zeros((layers * held,), jnp.int32), sizes,
                    (first_group,))
            a = jax.lax.ragged_dot(xs, experts["w_gate"], groups,
                                   preferred_element_type=f32)
            b = jax.lax.ragged_dot(xs, experts["w_up"], groups,
                                   preferred_element_type=f32)
            h = (jax.nn.silu(a) * b).astype(x.dtype)
            ys = jax.lax.ragged_dot(h, experts["w_down"], groups,
                                    preferred_element_type=f32)
        # rows behind the held pairs belong to no group: the compiler's
        # kernel leaves zeros there and the tiled one whatever the memory
        # held (NaN, interpreted); neither is read
        ys = jnp.where((jnp.arange(rows * top_k) < computed)[:, None],
                       ys * w.reshape(-1)[order][:, None], 0.0)
        # back to the pairs' own order, then each row's picks summed
        y = jnp.sum(jnp.take(ys, jnp.argsort(order), axis=0).reshape(
            rows, top_k, d), axis=1).astype(x.dtype)
    n_live = (jnp.int32(rows) if live is None
              else jnp.sum(live.astype(jnp.int32)))
    counts = jnp.stack([computed, n_live * top_k - computed,
                        jnp.sum((sizes > 0).astype(jnp.int32))])
    return y, counts
