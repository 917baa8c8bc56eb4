"""An expert layer's three grouped products as ONE Pallas TPU kernel, in row
tiles the size of an expert's share.

``out[r] = (silu(x[r] @ w_gate[e]) * (x[r] @ w_up[e])) @ w_down[e]`` for
every row ``r`` of group ``g`` and ``e = first_group + g``, the groups lying
one behind the other in ``x``: what three ``jax.lax.ragged_dot`` calls and
the gate between them compute. The compiler's own kernel for such a product
tiles the rows by 512 once they are many; where each group then holds a few
dozen rows (a prefill's pairs over a chip's held experts) nearly all of
every tile is padding. This one walks (row tile, group) *visits* (the
megablox scheme, ``jax.experimental.pallas.ops.tpu.megablox``):

* the rows are cut into tiles of ``ROW_TILE``; a visit is a tile and a group
  with rows in it, in the groups' order, so a tile's visits are consecutive
  and each writes its group's rows of the output tile and leaves the others;
* no empty group and no tile behind the last group's rows is visited: the
  visits are computed from the sizes (:func:`tile_groups`) and handed to the
  kernel as prefetched scalars; the grid has the most visits there can be,
  and the steps behind the last real one repeat its blocks (nothing is
  fetched) and compute nothing;
* a group's three matrices are taken whole, so consecutive visits of one
  group (its rows straddle a tile's edge) keep the blocks that are there: a
  group's matrices are read once, and the gated product of a tile's rows
  never leaves the chip's vector memory;
* ``first_group`` (a traced scalar) is added in the index map: a layer's
  groups are addressed where they lie in ``[layers * groups, ...]`` stacks,
  which are never sliced;
* one call a layer and not three: a Pallas call costs the host a tenth of
  a second to trace and lower, in every program that holds it.

Rows that belong to no group (behind the last group's end) are **not
written**: what the result holds there is whatever the memory held (NaN in
interpret mode), and the caller must not read it.

Off the TPU the same kernel runs in interpret mode (tests stay hermetic).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..obs.telemetry import get_registry

__all__ = ["ROW_TILE", "GroupTiles", "tile_groups", "grouped_gated_mlp"]

# Rows a visit computes. A v5e matrix unit holds a 128 x 128 block of a
# group's matrix while the rows stream through, so fewer rows a visit cost
# the same and more only pad more. The kernel on a v5e, bf16 [pairs, 3072]
# over 128 groups of 384 of width 1024, half the pairs in groups
# (``tools/grouped_product_bench.py``, PR 33), in ms, row tile 128 | 256 |
# 64: 5,120 pairs 3.505 | 3.486 | 3.555 (reading the 128 experts' 2.4 GB
# takes 2.95); 20,480 pairs 4.452 | 4.436 | 4.501. As three calls, a
# product each, the columns whole beat column tiles of 512 by 2-20%.
# Should other widths want another tile, it becomes a function of the
# widths here.
ROW_TILE = 128
# What a call may take of the chip's 128 MiB of vector memory: two buffers
# of an expert's three bf16 [3072, 1024] matrices (37.7 MB) beside the row
# and output tiles and the gate's float32 operands pass the 16 MiB a kernel
# gets unasked.
_VMEM_LIMIT = 64 * 2 ** 20


class GroupTiles(NamedTuple):
    """The visits of a grouped product over ``rows`` rows in tiles of
    ``tm``: ``offsets [groups + 1]`` (a group's first row, and the last
    one's end), ``group_ids`` and ``tile_ids [max visits]`` (entries behind
    the ``visits``-th repeat it), ``visits []``; all int32. ``tm`` is a
    Python number: the tuple lives inside one trace."""
    offsets: jax.Array
    group_ids: jax.Array
    tile_ids: jax.Array
    visits: jax.Array
    tm: int


def tile_groups(sizes: jax.Array, rows: int, *,
                tm: int = ROW_TILE) -> GroupTiles:
    """``sizes [groups]`` int32, their sum at most ``rows``."""
    groups = sizes.shape[0]
    tm = min(tm, rows)
    sizes = sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    # nothing here is negative, so lax.div (one operation to trace where
    # ``//`` is eight) rounds as ``//`` does
    first_tile = jax.lax.div(ends - sizes, tm)
    tiles = jnp.where(sizes > 0, jax.lax.div(ends - 1, tm) - first_tile + 1,
                      0)
    visit_ends = jnp.cumsum(tiles)
    visits = visit_ends[-1]
    # a tile is visited once by the group its first row is in and once more
    # by every other group that starts inside it
    most = pl.cdiv(rows, tm) + groups - 1
    v = jnp.minimum(jnp.arange(most, dtype=jnp.int32),
                    jnp.maximum(visits - 1, 0))
    # the group whose visits hold the v-th: a plain count, cheaper to trace
    # than a search
    group_ids = jnp.minimum(jnp.sum(
        (v[:, None] >= visit_ends[None, :]).astype(jnp.int32), axis=1),
        groups - 1)
    tile_ids = jnp.clip(
        (first_tile - (visit_ends - tiles))[group_ids] + v,
        0, pl.cdiv(rows, tm) - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return GroupTiles(offsets, group_ids, tile_ids, visits, tm)


def _kernel(offsets, group_ids, tile_ids, where, x, w_gate, w_up, w_down,
            out, *, tm):
    v = pl.program_id(0)

    @pl.when(v < where[0])
    def _():
        g = group_ids[v]
        row = tile_ids[v] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (row >= offsets[g]) & (row < offsets[g + 1])
        rows = x[...]
        a = jnp.dot(rows, w_gate[...], preferred_element_type=jnp.float32)
        b = jnp.dot(rows, w_up[...], preferred_element_type=jnp.float32)
        h = (jax.nn.silu(a) * b).astype(rows.dtype)
        y = jnp.dot(h, w_down[...], preferred_element_type=jnp.float32)
        out[...] = jnp.where(mine, y, out[...])


def grouped_gated_mlp(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
                      w_down: jax.Array, tiles: GroupTiles, *,
                      first_group=0,
                      interpret: Optional[bool] = None) -> jax.Array:
    """``x [m, d]``, ``w_gate``, ``w_up [all groups, d, f]``, ``w_down
    [all groups, f, d]``, ``tiles`` from :func:`tile_groups` over ``m``
    rows; group ``g``'s matrices are those at ``first_group + g``. float32
    ``[m, d]``: the three products accumulated in float32, the gated product
    rounded to ``x``'s type between them (as the three ``ragged_dot`` calls
    round it). ``interpret`` defaults to True off the TPU."""
    m, d = x.shape
    f = w_gate.shape[2]
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    # trace-time counters, as ops.flash_attention's: a run that must not
    # interpret asserts the first stays 0
    get_registry().counter("ops.grouped_product.interpreted" if interpret
                           else "ops.grouped_product.compiled").inc()
    tm = tiles.tm
    where = jnp.stack([tiles.visits, jnp.asarray(first_group, jnp.int32)])

    def rows_of(v, off, gid, tid, wh):
        return tid[v], 0

    def matrix_of(v, off, gid, tid, wh):
        return wh[1] + gid[v], 0, 0

    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles.group_ids.shape[0],),
            in_specs=[pl.BlockSpec((tm, d), rows_of),
                      pl.BlockSpec((None, d, f), matrix_of),
                      pl.BlockSpec((None, d, f), matrix_of),
                      pl.BlockSpec((None, f, d), matrix_of)],
            out_specs=pl.BlockSpec((tm, d), rows_of),
        ),
        out_shape=jax.ShapeDtypeStruct((m, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="grouped_product",
    )(tiles.offsets, tiles.group_ids, tiles.tile_ids, where, x, w_gate,
      w_up, w_down)
