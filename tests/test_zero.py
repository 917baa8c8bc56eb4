"""ZeRO-1 optimizer-state sharding (train/zero.py).

Contracts: (1) layout-only — training with zero=True matches the
replicated optimizer up to float reduction order (Adam is elementwise; the
only non-elementwise op in the chain is grad-clip's global norm, whose
partitioned reduction can differ by ~1 ulp, which Adam's rsqrt then
amplifies over steps — so losses match tightly, params to a looser tol);
(2) the memory claim is real — each device holds
~1/(n_stages*n_data) of the moment bytes instead of 1/n_stages; (3) the
layout survives the jitted step (constraints hold, no silent
re-replication after step 1).
"""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding

from pipe_tpu.data import lm_text
from pipe_tpu.models.transformer_lm import LMConfig
from pipe_tpu.train.loop import Trainer, TrainerConfig
from pipe_tpu.train import zero

MODEL = LMConfig(vocab=96, d_model=32, nhead=4, d_ff=64, n_layers=4,
                 seq_len=16, dropout=0.0)
CFG = TrainerConfig(batch_size=8, bptt=16, chunks=2, n_stages=2, n_data=2,
                    lr=0.1, schedule="1f1b", checkpoint="never")


def _source(cfg, n_tokens=4096, seed=3):
    ids = np.random.default_rng(seed).integers(
        0, MODEL.vocab, size=n_tokens).astype(np.int32)
    return lm_text.batchify(ids, cfg.batch_size)


def _run_steps(cfg, n_steps=3):
    tr = Trainer(MODEL, cfg)
    state = tr.init_state()
    state, stats = tr.train_epoch(_source(cfg), state=state,
                                  max_steps=n_steps, log_every=0)
    return tr, state, stats


def test_zero_losses_match_replicated():
    _, s_base, stats_base = _run_steps(CFG)
    _, s_zero, stats_zero = _run_steps(dataclasses.replace(CFG, zero=True))
    assert np.isfinite(stats_zero["loss"])
    np.testing.assert_allclose(stats_zero["loss"], stats_base["loss"],
                               rtol=1e-4)
    # params after 3 steps agree leafwise to the reduction-order tolerance
    # (see module docstring; lr=0.1 Adam amplifies ulp-level norm diffs)
    for a, b in zip(jax.tree_util.tree_leaves(s_base.params),
                    jax.tree_util.tree_leaves(s_zero.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-3)


def test_zero_moments_are_data_sharded():
    tr, state, _ = _run_steps(dataclasses.replace(CFG, zero=True), n_steps=2)
    n_data = tr.mesh.shape["data"]
    assert n_data > 1
    report = zero.zero_report(state.opt_state, tr._zero_shardings)
    # the bulk of the moment bytes actually shard (only biases/scalars may
    # stay replicated)
    assert report["data_sharded_bytes"] > 0.8 * report["total_bytes"]
    # per-device accounting: a data-sharded leaf's addressable shard holds
    # 1/n_data of the rows it would hold replicated — and the layout
    # survived the jitted step (state here is post-step, not post-init)
    checked = 0
    for leaf, sh in zip(
            jax.tree_util.tree_leaves(state.opt_state),
            jax.tree_util.tree_leaves(
                tr._zero_shardings,
                is_leaf=lambda x: isinstance(x, NamedSharding))):
        axes = [a for e in sh.spec
                for a in (e if isinstance(e, tuple) else (e,)) if e]
        if "data" not in axes:
            continue
        shard = leaf.addressable_shards[0]
        denom = 1
        for ax in axes:
            denom *= tr.mesh.shape[ax]
        assert (int(np.prod(shard.data.shape))
                == int(np.prod(leaf.shape)) // denom), (
            leaf.shape, shard.data.shape, sh.spec)
        checked += 1
    assert checked >= 4


def test_zero_requires_init_state():
    tr = Trainer(MODEL, dataclasses.replace(CFG, zero=True))
    # build a state without init_state's layout derivation
    other = Trainer(MODEL, CFG)
    state = other.init_state()
    with pytest.raises(Exception, match="init_state"):
        tr.train_epoch(_source(CFG, 1024, seed=0), state=state,
                       max_steps=1, log_every=0)


def test_moment_sharding_fallback_replicates_indivisible():
    tr = Trainer(MODEL, dataclasses.replace(CFG, zero=True))
    state = tr.init_state()
    # every sharding in the tree is a NamedSharding (checkpointable layout)
    for sh in jax.tree_util.tree_leaves(
            tr._zero_shardings,
            is_leaf=lambda x: isinstance(x, NamedSharding)):
        assert isinstance(sh, NamedSharding)
    # scalars (adam count) stay replicated
    report = zero.zero_report(state.opt_state, tr._zero_shardings)
    assert report["replicated_bytes"] >= 0


def test_mu_dtype_bf16_composes_with_zero():
    """TrainerConfig(mu_dtype='bfloat16'): the Adam first-moment leaves
    are actually stored bf16, the step runs, and it composes with ZeRO-1
    moment sharding."""
    import dataclasses
    import math

    from pipe_tpu.data import lm_text
    from pipe_tpu.models.transformer_lm import LMConfig
    from pipe_tpu.train.loop import Trainer, TrainerConfig

    lines = lm_text.synthetic_corpus(20_000, 99, seed=3)
    vocab = lm_text.Vocab(map(lm_text.basic_english_tokenize, lines))
    source = lm_text.batchify(lm_text.data_process(lines, vocab), 8)
    mcfg = dataclasses.replace(LMConfig().tiny(), n_layers=2)
    tr = Trainer(mcfg, TrainerConfig(
        schedule="1f1b", n_stages=2, n_data=2, chunks=2, batch_size=8,
        bptt=mcfg.seq_len, lr=1e-2, mu_dtype="bfloat16", zero=True))
    state, m = tr.train_epoch(source, max_steps=6, log_every=0)
    import jax.numpy as jnp
    assert m["loss"] < math.log(mcfg.vocab)
    bf16_leaves = [l for l in jax.tree_util.tree_leaves(state.opt_state)
                   if hasattr(l, "dtype") and l.dtype == jnp.bfloat16]
    assert bf16_leaves, "mu_dtype='bfloat16' produced no bf16 moments"
