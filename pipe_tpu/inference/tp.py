"""Tensor-parallel decoding: serve Megatron-sharded weights as trained.

A model whose blocks shard over the ``model`` mesh axis (heads + FFN
columns, ``ops/tp_layers.py``) decodes with the SAME split: each device
projects q/k/v for its local heads, keeps a head-sharded KV cache (cache
memory divides by tp like the weights), attends locally, and the block's
two psums (attention output projection, FFN second matmul) are the only
per-layer communication — identical structure to the training forward,
so serving needs no weight conversion and no resharding.

Implementation: :class:`TPShardedGenerator` subclasses the single-device
:class:`~.generate.Generator` — the inherited prefill/decode program runs
unchanged as the shard_map device program (``tp_block_decode`` binds the
model axis inside); only cache creation (local head count) and the jit
wrapping (per-leaf PartitionSpecs from ``tp_block_specs``) differ.

``tests/test_tp_gen.py`` pins greedy tp=2/tp=4 output token-for-token
against the unsharded (``tp_axis=None``) model on the same weights;
``tests/test_moe_gen.py`` does the same for the MoE family (experts +
heads sharded — ``moe_block_decode`` routes per-token, so the dense
dispatch works unchanged at q=1).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ..obs.telemetry import get_registry
from ..parallel.mesh import MODEL_AXIS
from .generate import GenerationConfig, Generator, check_positions

__all__ = ["TPShardedGenerator"]


class TPShardedGenerator(Generator):
    """KV-cached decoding over model-axis-sharded weights.

    Works for any LM whose block exposes ``tp_axis=MODEL_AXIS``, a
    cache-aware ``decode``, and whose model provides ``stage_param_specs``
    (per-leaf PartitionSpecs) — :class:`TPPipelinedLM` (Megatron split)
    and :class:`~..models.moe_lm.MoEPipelinedLM` (experts + heads
    sharded). Params are ``model.init``'s full trees — the per-leaf specs
    shard them on entry.

    Beam search works over the sharded weights too: the beam machinery is
    layout-agnostic — log-probs come off the (replicated) vocab head
    after each block's psum, so ``top_k``/parent selection compute
    identically on every model shard, and the per-step KV-cache reorder
    gathers on the BATCH axis, which the head-sharded caches keep whole.
    """

    def __init__(self, mesh: Mesh, model,
                 gen_cfg: GenerationConfig = GenerationConfig()):
        if MODEL_AXIS not in mesh.axis_names:
            raise ValueError(f"mesh must have a {MODEL_AXIS!r} axis")
        if getattr(model.block, "tp_axis", None) != MODEL_AXIS:
            raise ValueError(
                "TPShardedGenerator needs a model built with "
                f"tp_axis={MODEL_AXIS!r} (got "
                f"{getattr(model.block, 'tp_axis', None)!r})")
        super().__init__(model, gen_cfg)
        self.mesh = mesh
        self.tp = mesh.shape[MODEL_AXIS]
        if model.cfg.nhead % self.tp:
            raise ValueError(f"nhead={model.cfg.nhead} must divide over "
                             f"tp={self.tp}")
        self._programs = {}

    def _make_caches(self, blocks, batch, max_len):
        """Caches sized by the LOCAL head shard (blocks arrive inside
        shard_map with their model-axis slices)."""
        cd = self.model.cfg.compute_dtype
        caches = []
        for bp in blocks:
            h_local, hd = bp["wqkv"].shape[2], bp["wqkv"].shape[3]
            shape = (batch, max_len, h_local, hd)
            caches.append({"k": jnp.zeros(shape, cd),
                           "v": jnp.zeros(shape, cd)})
        return caches

    def _sharded_program(self, params, prompt, *, beam: bool):
        """Build (or fetch) the jitted shard_map program: greedy/sampling
        (``_generate``, keyed) or beam (``_generate_beam``, deterministic,
        two replicated outputs)."""
        stage_params, pre_params, post_params = params
        cache_key = (beam, prompt.shape,
                     jax.tree_util.tree_structure(params))
        run = self._programs.get(cache_key)
        if run is not None:
            get_registry().counter("serve.tp.program_cache_hits").inc()
            return run
        get_registry().counter("serve.tp.program_cache_misses").inc()
        stage_specs = [self.model.stage_param_specs()
                       for _ in stage_params]
        in_specs = (
            stage_specs,
            jax.tree_util.tree_map(lambda _: P(), pre_params),
            jax.tree_util.tree_map(lambda _: P(), post_params),
            P(),
        )
        if beam:
            run = jax.jit(jax.shard_map(
                lambda sp, pre, post, pr: self._generate_beam(
                    (sp, pre, post), pr),
                mesh=self.mesh, in_specs=in_specs, out_specs=(P(), P()),
                check_vma=False))
        else:
            run = jax.jit(jax.shard_map(
                lambda sp, pre, post, pr, k: self._generate(
                    (sp, pre, post), pr, k),
                mesh=self.mesh, in_specs=in_specs + (P(),),
                out_specs=P(), check_vma=False))
        self._programs[cache_key] = run
        return run

    def generate(self, params, prompt: jax.Array,
                 key: Optional[jax.Array] = None) -> jax.Array:
        """Sample ``[b, max_new_tokens]`` continuations with the weights
        sharded over the model axis. ``num_beams > 1`` runs beam search
        (deterministic; ``key`` unused)."""
        check_positions(self.model, prompt.shape[1],
                        self.gen_cfg.max_new_tokens)
        if self.gen_cfg.num_beams > 1:
            return self.generate_with_scores(params, prompt)[0]
        if key is None:
            key = jax.random.key(0)
        run = self._sharded_program(params, prompt, beam=False)
        return run(params[0], params[1], params[2],
                   jnp.asarray(prompt, jnp.int32), key)

    def generate_with_scores(self, params, prompt):
        """Beam search over the sharded weights: ``(tokens, scores)``,
        token-for-token equal to the single-device Generator's."""
        if self.gen_cfg.num_beams < 2:
            raise ValueError("generate_with_scores requires num_beams >= 2")
        check_positions(self.model, prompt.shape[1],
                        self.gen_cfg.max_new_tokens)
        run = self._sharded_program(params, prompt, beam=True)
        return run(params[0], params[1], params[2],
                   jnp.asarray(prompt, jnp.int32))
