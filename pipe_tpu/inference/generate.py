"""Autoregressive generation with KV caches over the pipelined LM families.

The reference package is training-only — its tutorial never samples from
the model it trains (``/root/reference/main.py`` has no generate loop). A
complete framework needs the inference surface too, so this module supplies
it the TPU way: one jitted program per (prompt_len, max_new_tokens) shape —
prefill fills every layer's KV cache in a single batched pass (MXU-sized
matmuls), then a ``lax.scan`` emits one token per step with O(1) work per
layer (the cache turns attention from O(t^2) re-forward into O(t) reads).
Static shapes throughout: the cache is allocated at ``prompt + max_new``
up front, masking handles the live prefix — no dynamic shapes, so XLA
compiles one fast program instead of recompiling per step.

Sampling: greedy (``temperature=0``), temperature softmax, optional top-k
truncation — all inside the scan, driven by an explicit PRNG key chain
(same key => same sample, the package-wide reproducibility contract).

Layer math lives with the layers (``MultiHeadAttention.decode``,
``TransformerEncoderLayer.decode``, ``PreLNBlock.decode`` in
``ops/layers.py``) so cached decode and training forward can never drift
apart; ``tests/test_generate.py`` pins teacher-forced cached logits against
the full training forward and greedy cached generation against a naive
re-forward loop.
"""

from __future__ import annotations

import dataclasses
import time
import warnings
from typing import Optional

import jax
import jax.numpy as jnp

from ..obs.telemetry import get_registry
from .quant import QuantLeaf, dequant_tree

__all__ = ["GenerationConfig", "Generator", "check_positions",
           "head_logits", "most_confident", "sample_logits",
           "sample_with_confidence", "sequence_lengths"]


@dataclasses.dataclass(frozen=True)
class GenerationConfig:
    max_new_tokens: int = 32
    temperature: float = 1.0   # 0 = greedy (argmax)
    top_k: Optional[int] = None  # None = full distribution
    # >1: beam search (deterministic, sum-of-log-probs scoring; the
    # temperature/top_k sampling knobs are ignored). KV caches are
    # physically reordered by parent beam each step.
    num_beams: int = 1
    # Stop token: once a sequence samples it, every later step emits
    # pad_token_id instead (static shapes — the scan still runs the full
    # max_new_tokens; finished rows just decode pad). None = no early
    # stop, every sequence runs to max_new_tokens, the pre-EOS behavior.
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    # Serve-side KV memory knobs (ignored by the one-shot generators,
    # which size a private cache per call). kv_block_size=None keeps the
    # monolithic per-slot slab; a power-of-two value switches the slot
    # backends to the paged pool (serve/kvpool.py). prefix_cache gates
    # shared-prefix block reuse inside the pool — pure host-side
    # allocator policy, so disabling it lowers to byte-identical device
    # programs (the absence-is-zero-cost pin, tests/test_kvpool.py).
    kv_block_size: Optional[int] = None
    prefix_cache: bool = True
    # Serve-side speculative decode lane (resident loop only): propose
    # spec_tokens - 1 draft tokens per round from an n-gram match over
    # the slot's own emitted history and verify the whole proposal in
    # ONE fixed-shape width-K pass — accepted tokens are bitwise the
    # sequential chain's (teacher-forced verify + the same split-sample
    # key walk), rejected tails cost nothing (their KV rows sit past the
    # slot position and are overwritten before any unmasked read). None
    # disables the lane; the one-shot generators ignore it.
    spec_tokens: Optional[int] = None

    def __post_init__(self):
        if self.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {self.max_new_tokens}")
        if self.temperature < 0.0:
            raise ValueError(
                f"temperature must be >= 0, got {self.temperature}")
        if self.top_k is not None and self.top_k < 1:
            raise ValueError(f"top_k must be >= 1, got {self.top_k}")
        if self.num_beams < 1:
            raise ValueError(f"num_beams must be >= 1, got {self.num_beams}")
        if self.eos_token_id is not None and self.eos_token_id < 0:
            raise ValueError(
                f"eos_token_id must be >= 0, got {self.eos_token_id}")
        if self.pad_token_id < 0:
            raise ValueError(
                f"pad_token_id must be >= 0, got {self.pad_token_id}")
        if self.kv_block_size is not None and (
                self.kv_block_size < 1
                or (self.kv_block_size & (self.kv_block_size - 1)) != 0):
            raise ValueError(
                f"kv_block_size must be a positive power of two (block "
                f"indexing is a shift+mask in the decode step), got "
                f"{self.kv_block_size}")
        if self.num_beams > 1 and self.eos_token_id is not None:
            raise ValueError(
                "eos_token_id with beam search is not implemented — "
                "EOS-aware beam pruning needs per-hypothesis length "
                "normalization; use num_beams=1 for early stopping")
        if self.spec_tokens is not None and self.spec_tokens < 2:
            raise ValueError(
                f"spec_tokens must be >= 2 (one draft token plus its "
                f"correction), got {self.spec_tokens}")
        if self.spec_tokens is not None and self.num_beams > 1:
            raise ValueError(
                "spec_tokens is a slot-decode lane; beam search has no "
                "speculative form (num_beams must be 1)")

    def check_kv_headroom(self, bucket_max_len: int,
                          block_size: Optional[int] = None,
                          spec_overshoot: int = 0) -> None:
        """Paged serving with length buckets: reject a block size that
        does not divide the per-slot KV span ``bucket_max_len +
        max_new_tokens (+ speculative headroom)`` cleanly — the last
        block would round up and silently waste its tail rows on EVERY
        slot. With ``spec_tokens=K`` the verify chunk writes past the
        last emitted row, so the slot really holds ``max_new_tokens +
        spec_overshoot`` generated rows (the same headroom
        ``validate()`` charges) — the stranded-row check must use the
        spec-padded span, not the nominal one. Called by the slot
        backends at construction (the span is only known once buckets
        are chosen, so the check cannot live in ``__post_init__``)."""
        bs = block_size if block_size is not None else self.kv_block_size
        if bs is None:
            return
        span = int(bucket_max_len) + self.max_new_tokens + spec_overshoot
        waste = -span % bs
        if waste:
            spec = (f" + speculative headroom {spec_overshoot}"
                    if spec_overshoot else "")
            raise ValueError(
                f"kv_block_size={bs} does not divide the KV headroom "
                f"bucket_max_len + max_new_tokens{spec} = "
                f"{bucket_max_len} + {self.max_new_tokens}"
                f"{' + ' + str(spec_overshoot) if spec_overshoot else ''}"
                f" = {span}: every slot's last "
                f"block would waste {waste} of {bs} rows "
                f"({waste / bs:.0%} of a block) as unwritable padding; "
                f"pick a block size dividing {span} or adjust "
                f"max_new_tokens by {waste}")

    def check_decode_headroom(self, prefix_len: int, max_new_tokens: int,
                              bucket_max_len: int,
                              spec_overshoot: int = 0) -> None:
        """Decode-only serving (fleet/disagg.py): a decode replica
        never prefills from scratch — its slot span was sized for
        ``bucket_max_len + max_new_tokens (+ speculative headroom)``
        at construction, so an imported prefix longer than the bucket
        cap plus the request's ``max_new_tokens`` would run decode past
        the last KV row. Reject it HERE, naming the overflow, instead
        of letting the decode step silently clamp (the same
        named-headroom discipline as :meth:`check_kv_headroom`)."""
        span = int(bucket_max_len) + self.max_new_tokens + spec_overshoot
        need = int(prefix_len) + int(max_new_tokens) + spec_overshoot
        if need > span:
            spec = (f" + speculative headroom {spec_overshoot}"
                    if spec_overshoot else "")
            raise ValueError(
                f"decode-only: imported prefix {prefix_len} + "
                f"max_new_tokens {max_new_tokens}{spec} = {need} rows "
                f"exceeds the decode slot span bucket_max_len + "
                f"max_new_tokens{spec} = {bucket_max_len} + "
                f"{self.max_new_tokens}"
                f"{' + ' + str(spec_overshoot) if spec_overshoot else ''}"
                f" = {span} by {need - span} rows; shorten the prefix, "
                f"lower the request's max_new_tokens, or size the "
                f"decode replica's buckets for the prefill fleet's "
                f"output lengths")


def check_positions(model, prompt_len: int, max_new_tokens: int) -> None:
    """Fail loudly when decode would run past the positional table —
    ``embed_at``'s dynamic slice clamps at the edge, which would silently
    reuse the last rows instead of erroring like the training path.
    Models advertise their capacity via ``max_position()``."""
    mp = getattr(model, "max_position", None)
    limit = mp() if callable(mp) else None
    if limit is not None and prompt_len + max_new_tokens > limit:
        raise ValueError(
            f"prompt_len {prompt_len} + max_new_tokens {max_new_tokens} "
            f"exceeds the positional table ({limit} positions)")


def head_logits(model, post_params, h: jax.Array) -> jax.Array:
    """The model head on hidden states (float32 logits) — ONE definition
    shared by the single-device and ring-pipelined generators. Quantized
    head weights (inference/quant.py) dequantize here, in-step."""
    from .quant import dequant_tree
    return model.head.apply(dequant_tree(post_params[model.post_key],
                                         jnp.float32),
                            h.astype(jnp.float32))


def _shaped_logits(logits: jax.Array, cfg: GenerationConfig) -> jax.Array:
    """The logits a token is drawn from: over the temperature, the top-k
    kept."""
    logits = logits / cfg.temperature
    if cfg.top_k is not None:
        kth = jax.lax.top_k(logits, cfg.top_k)[0][..., -1:]
        logits = jnp.where(logits >= kth, logits,
                           jnp.asarray(-1e30, logits.dtype))
    return logits


def sample_logits(logits: jax.Array, key: jax.Array,
                  cfg: GenerationConfig) -> jax.Array:
    """Next-token ids ``[b]`` from ``logits [b, vocab]`` (float32 math)."""
    logits = logits.astype(jnp.float32)
    if cfg.temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(key, _shaped_logits(logits, cfg),
                                  axis=-1).astype(jnp.int32)


def sample_with_confidence(logits: jax.Array, keys: Optional[jax.Array],
                           cfg: GenerationConfig):
    """``(ids [...], confidence [...])`` from ``logits [..., vocab]``, for
    a generation that picks positions by how sure the model is: greedy,
    the token put first and its probability (``max softmax(logits)``);
    with a temperature, a token drawn with ``keys [...]`` (one a row) as
    :func:`sample_logits` draws it, and its probability under the
    distribution it was drawn from. Float32."""
    logits = logits.astype(jnp.float32)
    if cfg.temperature == 0.0:
        ids = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    else:
        logits = _shaped_logits(logits, cfg)
        flat = logits.reshape((-1, logits.shape[-1]))
        ids = jax.vmap(lambda k, row: jax.random.categorical(k, row))(
            keys.reshape((-1,)), flat).reshape(logits.shape[:-1]).astype(
                jnp.int32)
    got = jnp.take_along_axis(logits, ids[..., None], axis=-1)[..., 0]
    return ids, jnp.exp(got - jax.nn.logsumexp(logits, axis=-1))


def most_confident(confidence: jax.Array, candidates: jax.Array,
                   n: jax.Array) -> jax.Array:
    """Of each row's ``candidates [rows, L]`` (bool) the ``n [rows]`` of
    largest ``confidence [rows, L]``, as a mask; a tie goes to the earlier
    position. Fewer candidates than ``n``: all of them."""
    score = jnp.where(candidates, confidence, -1.0)
    order = jnp.argsort(-score, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return candidates & (rank < n[:, None])


def sequence_lengths(tokens: jax.Array,
                     eos_token_id: Optional[int]) -> jax.Array:
    """Per-sequence generated length from ``tokens [..., max_new]``: the
    index of the first EOS plus one (the EOS itself counts as emitted),
    or the full width for rows that never stopped. ``None`` => every row
    ran to ``max_new_tokens``."""
    toks = jnp.asarray(tokens)
    width = toks.shape[-1]
    if eos_token_id is None:
        return jnp.full(toks.shape[:-1], width, jnp.int32)
    hit = toks == jnp.int32(eos_token_id)
    first = jnp.argmax(hit, axis=-1)
    return jnp.where(hit.any(axis=-1), first + 1, width).astype(jnp.int32)


class Generator:
    """KV-cached sampling over a :class:`~.models.common.PipelinedTransformer`
    LM factorization (``PipelinedLM`` and friends: ``embed_at`` + causal
    ``block.decode`` + ``post_fn`` head).

    ``generate`` is jitted per (batch, prompt_len) shape; params are the
    ``(stage_params, pre_params, post_params)`` triple from ``model.init``
    (the training layout — no weight conversion between train and serve).

    ``layer_scan=False`` unrolls the per-layer loop inside the decode
    step and carries the KV caches as two stacked arrays in the OUTER
    scan — no inner ``lax.scan`` with the full cache as scanned input
    and stacked output every token. It still takes each layer out of
    the stack and writes the whole layer back (``a.at[l].set``); the
    path that writes only the new rows into a carried slab is the serve
    engine's (``SingleDeviceSlotBackend._run_layers``, the slab form of
    ``block.decode``). Its effect on decode time: not measured on the
    current installation. Same math; float reduction order differs, so
    greedy ties can resolve differently on near-flat (e.g. untrained)
    logits.
    """

    def __init__(self, model, gen_cfg: GenerationConfig = GenerationConfig(),
                 *, layer_scan: bool = True, phase_timing: bool = False,
                 shape_cache_warn: int = 16):
        if not hasattr(model, "embed_at"):
            raise TypeError(
                f"{type(model).__name__} has no embed_at; KV-cache "
                "generation needs position-offset embedding")
        if not layer_scan and gen_cfg.num_beams > 1:
            raise ValueError(
                "layer_scan=False is not implemented for beam search "
                "(the beam path's cache-gather dominates its traffic; "
                "use the default scan path)")
        self.model = model
        self.gen_cfg = gen_cfg
        self.layer_scan = layer_scan
        # phase_timing=True additionally times a prefill-only program per
        # generate() call so the registry sees separate prefill/decode
        # histograms (decode = e2e - prefill). It re-runs prefill, so it
        # costs one extra prompt pass per call — opt-in, for profiling.
        self.phase_timing = phase_timing
        self._jitted = jax.jit(self._generate)
        self._jitted_beam = None  # built on first beam-search call
        self._jitted_prefill = None  # built on first phase_timing call
        # Per-shape jit cache bookkeeping: `generate` compiles one program
        # per (batch, prompt_len). That's invisible from the outside —
        # count it, and warn loudly once the cache grows past the
        # threshold (a serving workload feeding raw prompt lengths here
        # should bucket them: pipe_tpu.serve.BucketSpec).
        self.shape_cache_warn = shape_cache_warn
        self._shapes_seen: set = set()

    # --- internals ---

    def _blocks(self, stage_params):
        """Flatten the per-stage block lists into one [block0..blockL-1]
        list, cast to compute dtype (stage_fn's contract). QuantLeaf
        nodes (int8 weight-only quantization, inference/quant.py) pass
        through untouched — they dequantize at use time via _dq."""
        from .quant import QuantLeaf
        cd = self.model.cfg.compute_dtype
        flat = [bp for stage in stage_params for bp in stage]
        return [jax.tree_util.tree_map(
                    lambda p: p if isinstance(p, QuantLeaf)
                    else p.astype(cd),
                    bp, is_leaf=lambda x: isinstance(x, QuantLeaf))
                for bp in flat]

    def _dq(self, bp):
        """Materialize block weights at use time (int8 -> compute dtype
        inside the compiled step; identity when unquantized)."""
        return dequant_tree(bp, self.model.cfg.compute_dtype)

    def _head(self, post_params, h):
        return head_logits(self.model, post_params, h)

    def _make_caches(self, blocks, batch, max_len):
        """One KV cache per layer (hook: the TP generator overrides this
        to size caches by the LOCAL head shard)."""
        m = self.model
        return [m.block.attn.make_cache(batch, max_len,
                                        dtype=m.cfg.compute_dtype)
                for _ in blocks]

    def _prefill(self, blocks, pre_params, prompt, max_len):
        """One batched causal pass: embeds the prompt, writes rows
        [0, prompt_len) of every layer's cache. Returns (h, caches)."""
        m = self.model
        caches = self._make_caches(blocks, prompt.shape[0], max_len)
        h = m.embed_at(pre_params, prompt, 0)
        for l, bp in enumerate(blocks):
            h, caches[l] = m.block.decode(self._dq(bp), h, caches[l], 0)
        return h, caches

    def _layer_step(self, h_carry, inp):
        """Scan body over the stacked layers: one cached decode step."""
        bp, cache = inp
        h_new, cache = self.model.block.decode(self._dq(bp), h_carry[0],
                                               cache, h_carry[1])
        return (h_new, h_carry[1]), cache

    def _generate(self, params, prompt, key):
        m, gen = self.model, self.gen_cfg
        stage_params, pre_params, post_params = params
        blocks = self._blocks(stage_params)
        b, p = prompt.shape
        h, caches = self._prefill(blocks, pre_params, prompt,
                                  p + gen.max_new_tokens)
        key, sub = jax.random.split(key)
        tok = sample_logits(self._head(post_params, h[:, -1:, :])[:, 0, :],
                            sub, gen)

        # decode: one token per scan step, O(1) new work per layer
        cache_stack = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *caches)
        if self.layer_scan:
            block_stack = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *blocks)

            def run_layers(h, pos, caches):
                (h, _), caches = jax.lax.scan(
                    self._layer_step, (h, pos), (block_stack, caches))
                return h, caches
        else:
            # unrolled: per-layer in-place row writes on the OUTER carry —
            # no inner-scan xs->ys round-trip of the full cache per token
            def run_layers(h, pos, caches):
                for l, bp in enumerate(blocks):
                    c_l = jax.tree_util.tree_map(lambda a: a[l], caches)
                    h, c_l = m.block.decode(self._dq(bp), h, c_l, pos)
                    caches = jax.tree_util.tree_map(
                        lambda a, n: a.at[l].set(n), caches, c_l)
                return h, caches

        # EOS handling is a Python-level gate so eos_token_id=None traces
        # the exact pre-EOS program (no dead done-mask ops in the scan).
        eos = gen.eos_token_id

        def step(carry, _):
            if eos is None:
                caches, tok, pos, key = carry
            else:
                caches, tok, pos, key, done = carry
            h = m.embed_at(pre_params, tok[:, None], pos)
            h, caches = run_layers(h, pos, caches)
            key, sub = jax.random.split(key)
            nxt = sample_logits(self._head(post_params, h)[:, 0, :],
                                sub, gen)
            if eos is None:
                return (caches, nxt, pos + 1, key), tok
            # finished rows emit pad from the step AFTER their EOS; the
            # EOS token itself is emitted (it counts toward the length)
            nxt = jnp.where(done, jnp.int32(gen.pad_token_id), nxt)
            done = done | (nxt == jnp.int32(eos))
            return (caches, nxt, pos + 1, key, done), tok

        init = (cache_stack, tok, jnp.int32(p), key)
        if eos is not None:
            init = init + (tok == jnp.int32(eos),)
        carry_out, toks = jax.lax.scan(
            step, init, None, length=gen.max_new_tokens - 1)
        last = carry_out[1]
        # toks holds the tokens *entering* each step; append the final one
        out = jnp.moveaxis(toks, 0, 1)  # [b, max_new-1]
        return jnp.concatenate([out, last[:, None]], axis=1)

    def _prefill_only(self, params, prompt):
        """Prefill pass alone (same math as the head of ``_generate``),
        jitted separately so ``phase_timing`` can attribute wall time to
        prefill vs decode without instrumenting inside the scan."""
        stage_params, pre_params, post_params = params
        blocks = self._blocks(stage_params)
        h, _ = self._prefill(blocks, pre_params, prompt,
                             prompt.shape[1] + self.gen_cfg.max_new_tokens)
        return self._head(post_params, h[:, -1:, :])

    def _observe_phases(self, reg, params, prompt, e2e_sec: float) -> None:
        """Time the prefill-only program and fold the split into the
        registry. First call includes its compile (as the e2e number's
        first call does); decode is the e2e remainder."""
        if self._jitted_prefill is None:
            self._jitted_prefill = jax.jit(self._prefill_only)
        t0 = time.perf_counter()
        jax.block_until_ready(self._jitted_prefill(params, prompt))
        pf = time.perf_counter() - t0
        reg.histogram("serve.prefill_sec").observe(pf)
        reg.histogram("serve.decode_sec").observe(max(e2e_sec - pf, 0.0))

    def _generate_beam(self, params, prompt):
        """Beam search: deterministic, sum-of-log-probs scoring.

        Caches are tiled to ``b*k`` rows after prefill and physically
        re-gathered by parent beam each step (the standard KV-cache beam
        reorder — one cache-sized gather per step). Returns
        ``(tokens [b, max_new], scores [b])`` for the best beam.
        """
        m, gen = self.model, self.gen_cfg
        k = gen.num_beams
        stage_params, pre_params, post_params = params
        blocks = self._blocks(stage_params)
        b, p = prompt.shape
        # prefill on the UNtiled batch, then branch into k beams
        h, caches = self._prefill(blocks, pre_params, prompt,
                                  p + gen.max_new_tokens)
        logp = jax.nn.log_softmax(
            self._head(post_params, h[:, -1:, :])[:, 0, :], axis=-1)
        scores, tok = jax.lax.top_k(logp, k)          # [b, k] each
        tok = tok.astype(jnp.int32)

        cache_stack = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *caches)
        cache_stack = jax.tree_util.tree_map(
            lambda c: jnp.repeat(c, k, axis=1), cache_stack)  # [L, b*k, ...]
        block_stack = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs), *blocks)

        out0 = jnp.zeros((b, k, gen.max_new_tokens), jnp.int32)
        out0 = out0.at[:, :, 0].set(tok)

        def step(carry, t):
            caches, scores, tok, out = carry
            pos = p + t
            h = m.embed_at(pre_params, tok.reshape(b * k, 1), pos)
            (h, _), caches = jax.lax.scan(
                self._layer_step, (h, pos), (block_stack, caches))
            logp = jax.nn.log_softmax(
                self._head(post_params, h)[:, 0, :], axis=-1)  # [b*k, V]
            V = logp.shape[-1]
            total = scores[:, :, None] + logp.reshape(b, k, V)
            scores, idx = jax.lax.top_k(total.reshape(b, k * V), k)
            parent = idx // V                              # [b, k]
            tok = (idx % V).astype(jnp.int32)
            flat_parent = (jnp.arange(b)[:, None] * k + parent).reshape(-1)
            caches = jax.tree_util.tree_map(
                lambda c: jnp.take(c, flat_parent, axis=1), caches)
            out = jnp.take_along_axis(out, parent[:, :, None], axis=1)
            out = jax.lax.dynamic_update_slice(
                out, tok[:, :, None], (0, 0, t + 1))
            return (caches, scores, tok, out), None

        (_, scores, _, out), _ = jax.lax.scan(
            step, (cache_stack, scores, tok, out0),
            jnp.arange(gen.max_new_tokens - 1))
        best = jnp.argmax(scores, axis=1)
        toks = jnp.take_along_axis(
            out, best[:, None, None], axis=1)[:, 0, :]
        return toks, jnp.take_along_axis(scores, best[:, None], axis=1)[:, 0]

    def _note_shape(self, shape_key) -> None:
        """Track the per-shape jit cache: one program per (batch,
        prompt_len) [plus a tag for the beam variant]. Counters make the
        cache visible to serving telemetry; the warning fires when an
        unbucketed workload is compiling per raw prompt length."""
        reg = get_registry()
        if shape_key in self._shapes_seen:
            reg.counter("serve.program_cache_hits").inc()
            return
        self._shapes_seen.add(shape_key)
        reg.counter("serve.program_cache_misses").inc()
        reg.gauge("serve.program_cache_entries").set(len(self._shapes_seen))
        if len(self._shapes_seen) == self.shape_cache_warn + 1:
            warnings.warn(
                f"Generator has compiled {len(self._shapes_seen)} distinct "
                f"(batch, prompt_len) programs — every new prompt shape "
                f"recompiles the full prefill+decode step. Bucket prompt "
                f"lengths (pipe_tpu.serve.BucketSpec / ServeEngine) or pad "
                f"to a fixed shape to cap the cache.",
                RuntimeWarning, stacklevel=3)

    # --- public ---

    def generate(self, params, prompt: jax.Array,
                 key: Optional[jax.Array] = None) -> jax.Array:
        """Sample ``[b, max_new_tokens]`` continuations of ``prompt
        [b, prompt_len]`` int32 ids. ``num_beams > 1`` runs beam search
        (deterministic; ``key`` unused)."""
        check_positions(self.model, prompt.shape[1],
                        self.gen_cfg.max_new_tokens)
        if self.gen_cfg.num_beams > 1:
            return self.generate_with_scores(params, prompt)[0]
        if key is None:
            key = jax.random.key(0)
        prompt = jnp.asarray(prompt, jnp.int32)
        self._note_shape(prompt.shape)
        reg = get_registry()
        t0 = time.perf_counter()
        out = self._jitted(params, prompt, key)
        if reg.enabled:
            # Block for an honest latency number; callers read the tokens
            # to host right after anyway.
            out = jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            reg.histogram("serve.generate_sec").observe(dt)
            tokens = prompt.shape[0] * self.gen_cfg.max_new_tokens
            reg.counter("serve.tokens").inc(tokens)
            if dt > 0:
                reg.gauge("serve.tokens_per_sec").set(tokens / dt)
            if self.phase_timing:
                self._observe_phases(reg, params, prompt, dt)
        return out

    def generate_with_scores(self, params, prompt: jax.Array):
        """Beam search returning ``(tokens [b, max_new], scores [b])`` —
        the best beam's tokens and its total log-probability."""
        if self.gen_cfg.num_beams < 2:
            raise ValueError("generate_with_scores requires num_beams >= 2")
        check_positions(self.model, prompt.shape[1],
                        self.gen_cfg.max_new_tokens)
        if self._jitted_beam is None:
            self._jitted_beam = jax.jit(self._generate_beam)
        prompt = jnp.asarray(prompt, jnp.int32)
        self._note_shape(("beam",) + prompt.shape)
        reg = get_registry()
        t0 = time.perf_counter()
        out = self._jitted_beam(params, prompt)
        if reg.enabled:
            out = jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            reg.histogram("serve.beam_sec").observe(dt)
            tokens = prompt.shape[0] * self.gen_cfg.max_new_tokens
            reg.counter("serve.tokens").inc(tokens)
            if dt > 0:
                reg.gauge("serve.tokens_per_sec").set(tokens / dt)
        return out

    def generate_with_lengths(self, params, prompt: jax.Array,
                              key: Optional[jax.Array] = None):
        """``(tokens [b, max_new], lengths [b])`` — per-sequence generated
        length: up to and including the first EOS, or ``max_new_tokens``
        when the row never stopped (always ``max_new_tokens`` with
        ``eos_token_id=None``). Rows past their length hold pad."""
        out = self.generate(params, prompt, key)
        return out, sequence_lengths(out, self.gen_cfg.eos_token_id)
