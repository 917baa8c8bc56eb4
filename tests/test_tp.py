"""Tensor parallelism (ops/tp_layers.py + ScheduledPipeline
stage_param_specs): sharding over the model axis is a layout choice, never
a math choice.

The yardstick is always the SAME parameters through the tp_axis=None
(unsharded) computation; tp=2 forward, loss, and every gradient leaf must
match to fp-reduction tolerance (VERDICT's transparency discipline applied
to the new strategy)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from pipe_tpu.core import microbatch as mb
from pipe_tpu.core.partition import StageCtx
from pipe_tpu.models.tp_lm import TPPipelinedLM
from pipe_tpu.models.transformer_lm import LMConfig
from pipe_tpu.ops.tp_layers import (tp_block_apply, tp_block_init,
                                    tp_block_specs)
from pipe_tpu.parallel.mesh import MODEL_AXIS, make_mesh
from pipe_tpu.parallel.scheduled import ScheduledPipeline
from pipe_tpu.parallel.spmd import stack_stage_params

D, HEADS, FF, SEQ, ROWS = 16, 4, 32, 8, 2


def _tiny_cfg(n_layers=2):
    import dataclasses
    return dataclasses.replace(
        LMConfig().tiny(), d_model=D, nhead=HEADS, d_ff=FF, seq_len=SEQ,
        n_layers=n_layers, dropout=0.0)


def test_tp_block_matches_unsharded():
    """One block, tp=2, differentiated IN-PROGRAM (the executor contract:
    jax.vjp inside the shard_map body, grads never reduced over the model
    axis — sharded leaves local, replicated leaves model-identical via
    tp_enter) vs full params unsharded."""
    from jax.sharding import PartitionSpec as P

    params = tp_block_init(jax.random.key(0), D, HEADS, FF)
    h = jax.random.normal(jax.random.key(1), (ROWS, SEQ, D))
    mesh = make_mesh(1, 1, n_model=2, devices=jax.devices()[:2])

    def loss_unsharded(p, h):
        out = tp_block_apply(p, h, StageCtx(), tp_axis=None)
        return jnp.sum(out ** 2)

    l_ref, g_ref = jax.value_and_grad(loss_unsharded)(params, h)

    specs = tp_block_specs()
    grad_specs = jax.tree_util.tree_map(
        lambda s_: s_, specs, is_leaf=lambda v: isinstance(v, P))

    def device_program(p, h):
        def loss(p):
            out = tp_block_apply(p, h, StageCtx(), tp_axis=MODEL_AXIS)
            return jnp.sum(out ** 2)
        return jax.value_and_grad(loss)(p)

    run = jax.shard_map(device_program, mesh=mesh,
                        in_specs=(specs, P()),
                        out_specs=(P(), grad_specs), check_vma=False)
    l_tp, g_tp = jax.jit(run)(params, h)
    np.testing.assert_allclose(float(l_tp), float(l_ref), rtol=1e-5)
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_tp),
            jax.tree_util.tree_leaves_with_path(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5,
                                   err_msg=str(ka))


@pytest.mark.parametrize("n_stages,tp", [(1, 2), (2, 2)])
def test_pp_tp_loss_and_grad_transparency(n_stages, tp):
    """PP x TP through ScheduledPipeline(stage_param_specs=): loss and all
    grads match the unsharded (tp_axis=None) run of the same params."""
    cfg = _tiny_cfg(n_layers=n_stages)
    m = 4
    model_tp = TPPipelinedLM(cfg, n_stages)
    model_ref = TPPipelinedLM(cfg, n_stages, tp_axis=None)
    sp, prep, postp = model_ref.init(jax.random.key(0))
    stacked = stack_stage_params(sp)

    tokens = jax.random.randint(jax.random.key(1), (2 * m, cfg.seq_len),
                                0, cfg.vocab, jnp.int32)
    x, n_rows = mb.stack_scatter(
        {"tokens": tokens, "targets": jnp.roll(tokens, -1, -1)}, m)
    w = mb.valid_row_mask(x, n_rows)

    mesh_ref = make_mesh(n_stages, 1,
                         devices=jax.devices()[:n_stages])
    pipe_ref = ScheduledPipeline(
        mesh_ref, model_ref.stage_fn, pre_fn=model_ref.pre_fn,
        post_fn=model_ref.loss_post_fn, checkpoint="except_last",
        schedule="1f1b")
    l_ref, (g_ref, gpre_ref, gpost_ref) = jax.jit(pipe_ref.loss_and_grad)(
        stacked, prep, postp, x, w, key=jax.random.key(9))

    mesh_tp = make_mesh(n_stages, 1, n_model=tp,
                        devices=jax.devices()[:n_stages * tp])
    pipe_tp = ScheduledPipeline(
        mesh_tp, model_tp.stage_fn, pre_fn=model_tp.pre_fn,
        post_fn=model_tp.loss_post_fn, checkpoint="except_last",
        schedule="1f1b",
        stage_param_specs=model_tp.stage_param_specs())
    l_tp, (g_tp, gpre_tp, gpost_tp) = jax.jit(pipe_tp.loss_and_grad)(
        stacked, prep, postp, x, w, key=jax.random.key(9))

    np.testing.assert_allclose(float(l_tp), float(l_ref), rtol=1e-5)
    for name, got, exp in (("stage", g_tp, g_ref),
                           ("pre", gpre_tp, gpre_ref),
                           ("post", gpost_tp, gpost_ref)):
        for (ka, a), (kb, b) in zip(
                jax.tree_util.tree_leaves_with_path(got),
                jax.tree_util.tree_leaves_with_path(exp)):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=2e-4, atol=1e-5,
                err_msg=f"{name}{ka}")


def test_pp_tp_dp_composition():
    """The full PP x DP x TP product on 8 virtual devices: finite loss,
    grads match the unsharded yardstick."""
    cfg = _tiny_cfg(n_layers=2)
    m = 2
    model_tp = TPPipelinedLM(cfg, 2)
    model_ref = TPPipelinedLM(cfg, 2, tp_axis=None)
    sp, prep, postp = model_ref.init(jax.random.key(0))
    stacked = stack_stage_params(sp)
    tokens = jax.random.randint(jax.random.key(1), (4 * m, cfg.seq_len),
                                0, cfg.vocab, jnp.int32)
    x, n_rows = mb.stack_scatter(
        {"tokens": tokens, "targets": jnp.roll(tokens, -1, -1)}, m)
    w = mb.valid_row_mask(x, n_rows)

    mesh_ref = make_mesh(2, 1, devices=jax.devices()[:2])
    pipe_ref = ScheduledPipeline(
        mesh_ref, model_ref.stage_fn, pre_fn=model_ref.pre_fn,
        post_fn=model_ref.loss_post_fn, checkpoint="never",
        schedule="1f1b")
    l_ref, (g_ref, _, _) = jax.jit(pipe_ref.loss_and_grad)(
        stacked, prep, postp, x, w, key=jax.random.key(9))

    mesh = make_mesh(2, 2, n_model=2, devices=jax.devices()[:8])
    pipe = ScheduledPipeline(
        mesh, model_tp.stage_fn, pre_fn=model_tp.pre_fn,
        post_fn=model_tp.loss_post_fn, checkpoint="never",
        schedule="1f1b",
        stage_param_specs=model_tp.stage_param_specs())
    l_tp, (g_tp, _, _) = jax.jit(pipe.loss_and_grad)(
        stacked, prep, postp, x, w, key=jax.random.key(9))

    np.testing.assert_allclose(float(l_tp), float(l_ref), rtol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(g_tp),
                    jax.tree_util.tree_leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=1e-5)


def test_stage_param_specs_structure_mismatch_raises():
    cfg = _tiny_cfg(n_layers=1)
    model = TPPipelinedLM(cfg, 1)
    sp, prep, postp = model.init(jax.random.key(0))
    mesh = make_mesh(1, 1, n_model=2, devices=jax.devices()[:2])
    pipe = ScheduledPipeline(
        mesh, model.stage_fn, pre_fn=model.pre_fn,
        post_fn=model.loss_post_fn, checkpoint="never", schedule="1f1b",
        stage_param_specs={"wrong": "shape"})
    x, n_rows = mb.stack_scatter(
        {"tokens": jnp.zeros((2, cfg.seq_len), jnp.int32),
         "targets": jnp.zeros((2, cfg.seq_len), jnp.int32)}, 2)
    w = mb.valid_row_mask(x, n_rows)
    with pytest.raises((ValueError, TypeError)):
        pipe.loss_and_grad(stack_stage_params(sp), prep, postp, x, w)
