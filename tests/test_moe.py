"""MoE / expert parallelism (ops/moe.py): expert sharding over the model
axis is a layout choice, never a math choice — ep=2 forward, aux loss, and
every gradient leaf match the unsharded run under the executor contract
(in-program vjp, no model-axis grad reductions)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from pipe_tpu.core.partition import StageCtx
from pipe_tpu.ops.moe import moe_capacity, moe_ffn_apply, moe_ffn_init, \
    moe_ffn_specs
from pipe_tpu.parallel.mesh import MODEL_AXIS, make_mesh

D, FF, E, ROWS, SEQ = 8, 16, 4, 2, 8


@pytest.mark.parametrize("k", [1, 2])
def test_moe_ffn_matches_unsharded(k):
    params = moe_ffn_init(jax.random.key(0), D, FF, E)
    h = jax.random.normal(jax.random.key(1), (ROWS, SEQ, D))
    mesh = make_mesh(1, 1, n_model=2, devices=jax.devices()[:2])

    def loss_of(p, h, ep_axis):
        out, aux = moe_ffn_apply(p, h, StageCtx(), n_experts=E, k=k,
                                 ep_axis=ep_axis)
        return jnp.sum(out ** 2) + 0.01 * aux

    l_ref, g_ref = jax.value_and_grad(
        lambda p: loss_of(p, h, None))(params)

    specs = moe_ffn_specs()

    def device_program(p, h):
        return jax.value_and_grad(
            lambda p: loss_of(p, h, MODEL_AXIS))(p)

    run = jax.shard_map(device_program, mesh=mesh,
                        in_specs=(specs, P()),
                        out_specs=(P(), specs), check_vma=False)
    l_ep, g_ep = jax.jit(run)(params, h)
    np.testing.assert_allclose(float(l_ep), float(l_ref), rtol=1e-5)
    for (ka, a), (kb, b) in zip(
            jax.tree_util.tree_leaves_with_path(g_ep),
            jax.tree_util.tree_leaves_with_path(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=1e-5, err_msg=str(ka))


def test_pp_dp_ep_loss_and_grad_transparency():
    """The full PP x DP x EP product through
    ScheduledPipeline(stage_param_specs=): loss and all grads match the
    unsharded (ep_axis=None) run of the same params."""
    import dataclasses

    from pipe_tpu.core import microbatch as mb
    from pipe_tpu.models.moe_lm import MoELMConfig, MoEPipelinedLM
    from pipe_tpu.models.transformer_lm import LMConfig
    from pipe_tpu.parallel.scheduled import ScheduledPipeline
    from pipe_tpu.parallel.spmd import stack_stage_params

    tiny = LMConfig().tiny()
    cfg = MoELMConfig(
        **{**dataclasses.asdict(tiny),
           "d_model": D, "nhead": 2, "d_ff": FF, "n_layers": 2,
           "seq_len": SEQ, "dropout": 0.0},
        n_experts=E, top_k=2, capacity_factor=2.0)
    m = 2
    model_ep = MoEPipelinedLM(cfg, 2)
    model_ref = MoEPipelinedLM(cfg, 2, ep_axis=None)
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
    l_ref, (g_ref, gpre_ref, gpost_ref) = jax.jit(pipe_ref.loss_and_grad)(
        stacked, prep, postp, x, w, key=jax.random.key(9))

    mesh = make_mesh(2, 2, n_model=2, devices=jax.devices()[:8])
    pipe = ScheduledPipeline(
        mesh, model_ep.stage_fn, pre_fn=model_ep.pre_fn,
        post_fn=model_ep.loss_post_fn, checkpoint="never",
        schedule="1f1b",
        stage_param_specs=model_ep.stage_param_specs())
    l_ep, (g_ep, gpre_ep, gpost_ep) = jax.jit(pipe.loss_and_grad)(
        stacked, prep, postp, x, w, key=jax.random.key(9))

    np.testing.assert_allclose(float(l_ep), float(l_ref), rtol=1e-5)
    for got, exp in ((g_ep, g_ref), (gpre_ep, gpre_ref),
                     (gpost_ep, gpost_ref)):
        for (ka, a), (kb, b) in zip(
                jax.tree_util.tree_leaves_with_path(got),
                jax.tree_util.tree_leaves_with_path(exp)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=3e-4, atol=1e-5,
                                       err_msg=str(ka))


def test_moe_capacity_drops_overflow():
    """With capacity_factor tiny, overflowed tokens contribute zero output
    (they ride the residual stream in a block) — and the layer still
    differentiates."""
    params = moe_ffn_init(jax.random.key(0), D, FF, E)
    h = jax.random.normal(jax.random.key(1), (ROWS, SEQ, D))
    out_full, _ = moe_ffn_apply(params, h, StageCtx(), n_experts=E, k=1,
                                capacity_factor=4.0, ep_axis=None)
    out_tiny, _ = moe_ffn_apply(params, h, StageCtx(), n_experts=E, k=1,
                                capacity_factor=0.1, ep_axis=None)
    # capacity 0.1 * 16 / 4 -> 1 slot per expert: most tokens dropped
    assert moe_capacity(ROWS * SEQ, E, 1, 0.1) == 1
    n_zero_tiny = int(jnp.sum(jnp.all(out_tiny == 0, axis=-1)))
    n_zero_full = int(jnp.sum(jnp.all(out_full == 0, axis=-1)))
    assert n_zero_tiny > n_zero_full
    g = jax.grad(lambda p: jnp.sum(moe_ffn_apply(
        p, h, StageCtx(), n_experts=E, k=1, capacity_factor=0.1,
        ep_axis=None)[0] ** 2))(params)
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(g))
