"""The expert layer's grouped products in row tiles
(``ops/grouped_product.py``, one Pallas kernel, interpreted here) against
``jax.lax.ragged_dot``, and the rule by which ``dropless_moe`` picks between
the two from its static shapes.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipe_tpu.obs.telemetry import get_registry
from pipe_tpu.ops import moe
from pipe_tpu.ops.grouped_product import (ROW_TILE, grouped_gated_mlp,
                                          tile_groups)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAFFIC = os.path.join(ROOT, "benchmark", "traffic",
                       "closed16-p64-2048-o32-384.json")

# name -> (rows, d, f, sizes, layers, layer, tm)
CASES = {
    "empty_groups": (96, 64, 32, [20, 0, 0, 7, 40, 0, 3, 26], 1, 0, 16),
    "a_group_straddles_three_tiles": (96, 64, 32, [5, 50, 41], 1, 0, 16),
    "stacked_layers_traced_layer": (96, 64, 32, [30, 0, 13, 9], 3, 2, 16),
    "rows_behind_the_held_pairs": (96, 64, 32, [11, 0, 22, 6], 1, 0, 16),
    "no_held_pair_at_all": (64, 64, 32, [0, 0, 0, 0], 2, 1, 16),
    "every_row_in_one_group": (64, 64, 32, [0, 64, 0], 1, 0, 16),
    "last_tile_ragged": (75, 64, 32, [10, 33, 0, 25], 1, 0, 16),
    "fewer_rows_than_a_tile": (24, 64, 32, [5, 0, 11], 1, 0, ROW_TILE),
    "wider_than_deep": (96, 32, 256, [20, 0, 0, 7, 40, 0, 3, 26], 2, 1, 16),
    "bfloat16_at_the_row_tile": (384, 128, 128, [100, 0, 37, 150, 0, 3], 1,
                                 0, ROW_TILE),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_tiled_kernel_is_three_ragged_dots_on_the_held_rows(case):
    rows, d, f, sizes, layers, layer, tm = CASES[case]
    dtype = jnp.bfloat16 if case.startswith("bfloat16") else jnp.float32
    groups = len(sizes)
    ks = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(ks[0], (rows, d), dtype)
    w_gate, w_up = (jax.random.normal(k, (layers * groups, d, f), dtype)
                    / d ** 0.5 for k in ks[1:3])
    w_down = jax.random.normal(ks[3], (layers * groups, f, d),
                               dtype) / f ** 0.5
    sizes = jnp.asarray(sizes, jnp.int32)

    @jax.jit
    def both(x, sizes, layer):                      # ``layer`` traced
        padded = jax.lax.dynamic_update_slice(
            jnp.zeros((layers * groups,), jnp.int32), sizes,
            (layer * groups,))

        def dot(lhs, rhs):
            return jax.lax.ragged_dot(lhs, rhs, padded,
                                      preferred_element_type=jnp.float32)

        want = dot((jax.nn.silu(dot(x, w_gate)) * dot(x, w_up)).astype(dtype),
                   w_down)
        got = grouped_gated_mlp(x, w_gate, w_up, w_down,
                                tile_groups(sizes, rows, tm=tm),
                                first_group=layer * groups)
        return want, got

    want, got = both(x, sizes, layer)
    held = int(sizes.sum())
    assert got.shape == want.shape and got.dtype == jnp.float32
    # bfloat16: the gated product rounds the other way where a float32 sum
    # was added in another order
    np.testing.assert_allclose(
        np.asarray(got[:held]), np.asarray(want[:held]), rtol=0,
        atol=2e-2 if dtype == jnp.bfloat16 else 2e-5)
    assert np.isfinite(np.asarray(got[:held])).all()


def test_the_visits_skip_empty_groups_and_tiles_behind_the_last_group():
    """Sizes 5, 0, 50, 0, 9 over 96 rows in tiles of 16: group 0 in tile 0;
    group 2 (rows 5-54) in tiles 0-3; group 4 (rows 55-63) in tile 3: six
    visits, none of groups 1 and 3 nor of tiles 4 and 5; the entries behind
    repeat the last visit, so their steps fetch nothing."""
    t = tile_groups(jnp.asarray([5, 0, 50, 0, 9], jnp.int32), 96, tm=16)
    assert int(t.visits) == 6 and t.tm == 16
    assert t.group_ids.shape == t.tile_ids.shape == (96 // 16 + 5 - 1,)
    assert np.asarray(t.group_ids).tolist() == [0, 2, 2, 2, 2, 4, 4, 4, 4, 4]
    assert np.asarray(t.tile_ids).tolist() == [0, 0, 1, 2, 3, 3, 3, 3, 3, 3]
    assert np.asarray(t.offsets).tolist() == [0, 5, 5, 55, 55, 64]


def expert_layer(key, d, f, experts, held, layers=None, dtype=jnp.bfloat16):
    ks = jax.random.split(key, 4)
    lead = () if layers is None else (layers,)

    def mat(k, shape, fan_in):
        return (jax.random.normal(k, lead + shape, jnp.float32)
                / fan_in ** 0.5).astype(dtype)

    # a peaked router, as the cell's seeded weights have
    return {"router": jax.random.normal(ks[0], (d, experts)) * 4 / d ** 0.5,
            "w_gate": mat(ks[1], (held, d, f), d),
            "w_up": mat(ks[2], (held, d, f), d),
            "w_down": mat(ks[3], (held, f, d), f)}


@pytest.mark.parametrize("stacked", [False, True],
                         ids=["one_layer", "layer_of_a_stack_and_dead_rows"])
def test_a_prefills_expert_layer_is_the_same_through_either_product(stacked):
    """128 rows x top-4 over 8 of 16 experts (512 pairs, about half held,
    four row tiles): the tiled product's layer has no NaN (the rows behind
    the held pairs come back uninitialised from the kernel and are masked)
    and equals the compiler's to bfloat16's rounding; the counts are the
    same numbers."""
    rows, d, f = 128, 64, 128
    p = expert_layer(jax.random.key(3), d, f, 16, 8,
                     layers=3 if stacked else None)
    x = jax.random.normal(jax.random.key(4), (rows, d)).astype(jnp.bfloat16)
    live = (jnp.arange(rows) % 7 != 0) if stacked else None

    def layer(impl):
        return jax.jit(lambda p, x, at: moe._dropless_moe(
            p, x, top_k=4, first=4, scale=2.5, live=live,
            layer=at if stacked else None, impl=impl))(p, x, jnp.int32(1))

    (want, counts_c), (got, counts_t) = layer("compiler"), layer("tiled")
    assert got.dtype == want.dtype == jnp.bfloat16
    got, want = (np.asarray(a.astype(jnp.float32)) for a in (got, want))
    assert np.isfinite(got).all()
    # one bfloat16 step at the outputs' size, where a product's float32
    # sum rounds the other way
    np.testing.assert_allclose(got, want, atol=2 ** -7 * np.abs(want).max())
    assert np.array_equal(np.asarray(counts_t), np.asarray(counts_c))
    assert 0 < int(counts_t[0]) < rows * 4 and int(counts_t[2]) == 8
    if stacked:
        assert not got[::7].any() and got[1].any()


def cell_buckets():
    with open(TRAFFIC) as f:
        engine = json.load(f)["engine"]
    out, b = [], engine["bucket_min"]
    while b <= engine["bucket_max"]:
        out.append(b)
        b *= 2
    return engine["slots"], out


@pytest.mark.parametrize("rows,impl", [(cell_buckets()[0], "compiler")] + [
    (b, "tiled") for b in cell_buckets()[1]])
def test_the_shape_rule_at_the_cells_sizes(rows, impl):
    """``laguna-serve-closed16``: a decode step's 16 rows x 10 picks over 128
    held experts stay with the compiler's kernel; every prefill bucket its
    traffic file names goes in tiles."""
    assert moe.grouped_impl(rows * 10, 128) == impl


def test_dropless_moe_takes_the_rules_pick_and_counts_it():
    reg = get_registry()
    names = ("ops.moe.grouped.compiler", "ops.moe.grouped.tiled",
             "ops.grouped_product.interpreted")
    p = expert_layer(jax.random.key(5), 32, 32, 8, 4, dtype=jnp.float32)
    for rows, grew in ((2, (1, 0, 0)), (16, (0, 1, 1))):
        assert moe.grouped_impl(rows * 2, 4) == ("tiled" if grew[1]
                                                 else "compiler")
        before = [reg.counter(n).value for n in names]
        y, _ = moe.dropless_moe(p, jnp.ones((rows, 32)), top_k=2)
        assert np.isfinite(np.asarray(y)).all()
        assert tuple(reg.counter(n).value - b
                     for n, b in zip(names, before)) == grew
