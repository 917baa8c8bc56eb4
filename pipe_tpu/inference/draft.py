"""Draft sources for the speculative decode lane.

PR 11 built the hard half of speculation — the fixed-shape teacher-forced
verify chunk with exact rollback and the key chain advanced by accepted
count — and fed it the cheapest possible drafter (n-gram history lookup,
acceptance ~0.01 on the bench model). This module makes the draft side
real, behind one interface:

* :class:`NgramDraft` — the PR 11 lookup, kept as the zero-cost baseline.
* :class:`TruncatedDraft` — runs the FIRST ``draft_stages`` stages of the
  already-partitioned model (the same stacked block params the verify
  uses, QuantLeaf-aware) plus a tied-embedding head, greedy, K-1 steps.
  The "early layers carry most next-token signal" argument of LayerPipe /
  2BP applied to inference: the draft is a strict prefix of the model
  itself, so its KV rows land in the real cache and the verify pass
  overwrites every row the draft touched (the rollback-overwrite law
  needs no extra storage).
* :class:`TreeDraft` — ``branches`` top-B continuations from one shared
  truncated-model root step, each rolled out greedily to depth K-1 on a
  private copy of the draft-layer caches. All branches verify in the
  SAME fixed-shape chunk under a causal tree mask
  (:func:`tree_layout`); the engine accepts the longest matching
  root-to-leaf path.

Every drafter's ``propose`` is pure jax — it runs INSIDE the resident
``while_loop`` body, keeping the zero-host-sync steady state.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["DraftSource", "NgramDraft", "TruncatedDraft", "TreeDraft",
           "tree_layout", "resolve_draft"]


def tree_layout(K: int, branches: int) -> Tuple[np.ndarray, np.ndarray]:
    """Static layout of the flattened draft tree for ``branches`` chains
    of depth ``K-1`` sharing one root.

    Returns ``(depths [Q], anc [Q, Q])`` with ``Q = 1 + branches*(K-1)``:
    row 0 is the root (the slot's current token, depth 0); branch ``b``
    level ``i`` sits at row ``1 + b*(K-1) + i`` with depth ``i+1``.
    ``anc[j, r]`` is True when chunk row ``r`` is an ancestor-or-self of
    chunk row ``j`` — the within-chunk attention mask."""
    Q = 1 + branches * (K - 1)
    depths = np.zeros((Q,), np.int32)
    anc = np.zeros((Q, Q), bool)
    anc[0, 0] = True
    for b in range(branches):
        base = 1 + b * (K - 1)
        for i in range(K - 1):
            r = base + i
            depths[r] = i + 1
            anc[r, 0] = True
            anc[r, base:base + i + 1] = True
    return depths, anc


class DraftSource:
    """One speculative draft proposal per resident round.

    ``propose`` returns ``(drafts [S, branches, K-1] int32, caches)``:
    for each slot, ``branches`` candidate continuations of the current
    token. The caches come back because prefix drafters write real KV
    rows at positions >= ``pos`` — all of them re-written by the verify
    chunk before any unmasked read (the rollback-overwrite law).
    ``run_layers(block_stack, h, caches, pos)`` is the engine's layer
    loop over the carried cache (``SingleDeviceSlotBackend._run_layers``:
    ``[L, S, T, C]``, heads folded, slab and paged views alike); a
    drafter that runs layers runs them through it."""

    name = "?"
    branches = 1

    def propose(self, run_layers, m, pre, block_stack, caches, tok, pos,
                hist, K: int):
        raise NotImplementedError

    def draft_cost_frac(self, K: int, n_layers: int) -> float:
        """Predicted draft device-time over total round device-time,
        counting (rows x layers) work units — the breakeven input the
        planner and the bench gate consume."""
        raise NotImplementedError


# ---------------------------------------------------------------------------
# n-gram lookup (the PR 11 drafter, zero draft cost)
# ---------------------------------------------------------------------------

class NgramDraft(DraftSource):
    """Tokens following the most recent earlier occurrence of the
    current token in the slot's device-side history buffer."""

    name = "ngram"

    def propose(self, run_layers, m, pre, block_stack, caches, tok, pos,
                hist, K):
        H = hist.shape[1]
        idx = jnp.arange(H, dtype=jnp.int32)

        def draft_one(hrow, t, p):
            mask = (hrow == t) & (idx < p)
            j = jnp.max(jnp.where(mask, idx, jnp.int32(-1)))
            start = jnp.maximum(j + 1, 0)
            return jax.lax.dynamic_slice(hrow, (start,), (K - 1,))

        drafts = jax.vmap(draft_one)(hist, tok, pos)       # [S, K-1]
        return drafts[:, None, :], caches

    def draft_cost_frac(self, K, n_layers):
        return 0.0


# ---------------------------------------------------------------------------
# truncated-pipeline rollout (shared machinery for linear and tree)
# ---------------------------------------------------------------------------

def _tied_logits(m, pre, h):
    """Tied-embedding head: score hidden states against the embedding
    table. The ``sqrt(d)`` embed scaling is uniform over vocab, so the
    argmax the greedy rollout takes is scale-invariant."""
    table = pre["embed"]["table"].astype(jnp.float32)
    return h.astype(jnp.float32) @ table.T


def _draft_step(run_layers, m, dstack, caches, pre, tok, pos):
    """One q=1 greedy step through the draft-layer prefix: embeds
    ``tok`` at ``pos``, writes KV row ``pos`` in every draft layer,
    returns the tied-head hidden state ``[S, d]`` and updated caches.
    ``run_layers`` is the engine's own layer loop over the carried
    cache ``[L, S, T, C]`` (the slab form of ``block.decode``) run
    on the ``Ld`` layers of ``dstack``: it writes the first ``Ld``
    layers of ``caches`` in place and leaves the rest alone, so draft
    rows are bitwise what the verify would write for the same (token,
    position), and nothing is sliced out of the cache or joined back."""
    h = jax.vmap(
        lambda t, p: m.embed_at(pre, t[None, None], p)[0])(tok, pos)
    h, caches = run_layers(dstack, h, caches, pos)
    return h[:, 0], caches


def _slice_draft(tree, Ld):
    return jax.tree_util.tree_map(lambda a: a[:Ld], tree)


class TruncatedDraft(DraftSource):
    """Greedy K-1 step rollout through the first ``draft_layers``
    layers of the model plus a tied-embedding head."""

    name = "truncated"

    def __init__(self, draft_layers: int):
        if draft_layers < 1:
            raise ValueError(
                f"truncated draft needs >= 1 draft layer, got "
                f"{draft_layers}")
        self.draft_layers = draft_layers

    def propose(self, run_layers, m, pre, block_stack, caches, tok, pos,
                hist, K):
        dstack = _slice_draft(block_stack, self.draft_layers)
        cur, p = tok, pos
        outs = []
        for _ in range(K - 1):
            h, caches = _draft_step(run_layers, m, dstack, caches, pre,
                                    cur, p)
            cur = jnp.argmax(_tied_logits(m, pre, h),
                             axis=-1).astype(jnp.int32)
            outs.append(cur)
            p = p + 1
        drafts = jnp.stack(outs, axis=1)                   # [S, K-1]
        return drafts[:, None, :], caches

    def draft_cost_frac(self, K, n_layers):
        d = (K - 1) * self.draft_layers
        return d / (d + K * n_layers)


class TreeDraft(DraftSource):
    """Top-``branches`` first tokens from one shared truncated root
    step, each continued greedily on a private draft-cache copy. The
    branch copies are discarded — only the shared root row (re-written
    by the verify chunk) persists in the real caches."""

    name = "tree"

    def __init__(self, branches: int, draft_layers: int):
        if branches < 2:
            raise ValueError(
                f"tree draft needs >= 2 branches (1 branch IS the "
                f"truncated drafter), got {branches}")
        if draft_layers < 1:
            raise ValueError(
                f"tree draft needs >= 1 draft layer, got {draft_layers}")
        self.branches = branches
        self.draft_layers = draft_layers

    def propose(self, run_layers, m, pre, block_stack, caches, tok, pos,
                hist, K):
        Ld, B = self.draft_layers, self.branches
        S = tok.shape[0]
        dstack = _slice_draft(block_stack, Ld)
        # shared root step: writes row `pos` in the real draft caches
        h, caches = _draft_step(run_layers, m, dstack, caches, pre, tok,
                                pos)
        first = jax.lax.top_k(_tied_logits(m, pre, h), B)[1] \
            .astype(jnp.int32)                              # [S, B]
        if K > 2:
            # per-branch private rollouts: tile the draft layers' caches
            # along the slot axis (S*B pseudo-slots), reuse the same step
            bcaches = jax.tree_util.tree_map(
                lambda a: jnp.repeat(a, B, axis=1),
                _slice_draft(caches, Ld))
            cur = first.reshape(-1)
            p = jnp.repeat(pos + 1, B)
            outs = [cur]
            for _ in range(K - 2):
                h, bcaches = _draft_step(run_layers, m, dstack, bcaches,
                                         pre, cur, p)
                cur = jnp.argmax(_tied_logits(m, pre, h),
                                 axis=-1).astype(jnp.int32)
                outs.append(cur)
                p = p + 1
            drafts = jnp.stack(outs, axis=1).reshape(S, B, K - 1)
        else:
            drafts = first[:, :, None]                      # [S, B, 1]
        return drafts, caches

    def draft_cost_frac(self, K, n_layers):
        steps = 1 + self.branches * max(K - 2, 0)
        d = steps * self.draft_layers
        Q = 1 + self.branches * (K - 1)
        return d / (d + Q * n_layers)


def resolve_draft(name: str, *, n_stages: int, layers_per_stage: int,
                  draft_stages: int = 1,
                  spec_branches: Optional[int] = None) -> DraftSource:
    """Build a drafter from flag-level options, rejecting impossible
    combinations loudly (never a silent fallback)."""
    if name == "ngram":
        return NgramDraft()
    if draft_stages < 1 or draft_stages >= n_stages:
        raise ValueError(
            f"draft_stages={draft_stages} must be in [1, "
            f"{n_stages - 1}] — the draft is a STRICT prefix of the "
            f"{n_stages}-stage model (a full-depth draft is just the "
            f"model)")
    Ld = draft_stages * layers_per_stage
    if name == "truncated":
        return TruncatedDraft(Ld)
    if name == "tree":
        if spec_branches is None or spec_branches < 2:
            raise ValueError(
                f"tree draft needs spec_branches >= 2, got "
                f"{spec_branches}")
        return TreeDraft(spec_branches, Ld)
    raise ValueError(
        f"unknown draft source {name!r}: pick ngram | truncated | tree")
