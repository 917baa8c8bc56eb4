"""Slot-based continuous batching: a dynamic request stream through ONE
compiled decode step.

The paper's schedule-as-static-table discipline, applied to serving: the
device program is fixed-shape and compiled once; everything dynamic —
arrivals, retirements, deadlines — is host-side table maintenance, like
the executors' masked-slot op tables. The engine owns ``S`` decode
slots, each a row block of every layer's KV cache plus a (token,
position, PRNG key) triple. A host **tick** is:

1. reap requests that died waiting (deadline/cancel) and retire running
   slots whose deadline passed or that were cancelled;
2. admit waiting requests into free slots — one bucketed prefill program
   per prompt-length bucket (:class:`~.buckets.BucketSpec`) writes the
   slot's cache rows, samples the first token and arms the slot with
   it on the device; the host dispatches and reads nothing back;
3. launch the **one** decode program for all S slots — finished/empty
   slots write rows the next prefill overwrites, the same
   sacrificial-write trick as the pipelined generators — then, with
   the launch in the device's queue, read the admissions' first tokens
   (TTFT is measured here: the read waits for the prefill program, the
   device works on), wait for the launch, and retire slots on EOS /
   per-request ``max_new_tokens``.

The decode program of :class:`SingleDeviceSlotBackend` is one
``lax.while_loop`` with two seams: the cache store (slab or paged pool)
and the round (``decode_chunk`` plain steps, or one speculative
draft/verify round). ``resident`` sets only its horizon: how many
rounds one launch may run before the host looks again.

A model of one block is scanned as one stack of layers. A model whose
layers differ hands them over in groups of like layers
(``model.layer_groups()``; ``models/laguna.py``): a group is scanned, the
groups run in order, each kind of cache has a slab of its own in the
loop's carry (a window layer's a ring), and a prefill attends a block of
queries at a time and seats its rows in both. Only the slab store and
the plain round take such a model.

Zero steady-state recompiles is a pinned invariant, not an aspiration:
the decode program body increments ``serve.engine.decode_traces`` (a
one-chunk horizon) or ``serve.engine.resident_traces`` (a longer one)
at trace time (traces happen once per compile), and
``tests/test_serve.py`` asserts the counter stays at 1 across staggered
mixed-length traffic.

Token parity is the other pin: because each slot carries the exact
(prefill -> split -> sample -> split -> sample...) key chain of a
batch-1 :class:`~..inference.generate.Generator` call, and right-padded
bucket rows are causally masked until decode overwrites them, a request
served through the engine produces bitwise the tokens of a one-shot
``Generator.generate`` on its prompt — regardless of what the other
slots are doing.

``decode_chunk > 1`` runs K decode steps per round inside a ``lax.scan``
(one host round-trip per K tokens — the host-sync amortization knob);
the carry chain is identical however it is chopped, so parity holds.
The cost is retirement lag: a slot finishing mid-chunk wastes at most
K-1 slot-steps before the host sees it.
"""

from __future__ import annotations

import collections
import functools
import time
from typing import (Callable, List, NamedTuple, Optional, Sequence)

import jax
import jax.numpy as jnp
import numpy as np

from ..inference.draft import DraftSource, resolve_draft, tree_layout
from ..inference.generate import (GenerationConfig, head_logits,
                                  most_confident, sample_logits,
                                  sample_with_confidence)
from ..inference.quant import QuantLeaf, dequant_tree
from ..models.common import refuse_grouped
from ..obs import events as ev
from ..obs.events import NULL_EVENT_LOG, REQUEST
from ..obs.telemetry import (get_registry, host_overhead_per_token,
                             record_stall)
from ..ops.layers import fold_heads, unfold_heads
from .buckets import BucketSpec
from .kvpool import (HostKvStore, KvPool, PoolExhausted, block_demand,
                     copy_block, flat_row_index, gather_block_cache,
                     scatter_block_rows, storage_for)
from .queue import QueueFull, Request, RequestQueue, Response

__all__ = ["SingleDeviceSlotBackend", "ServeEngine", "EngineDraining",
           "LaunchPhases", "SlowCycle"]


# rows of a grouped model's carried ``counts``: who counted
COUNT_PREFILL, COUNT_DECODE = 0, 1
# what the block round counts, behind the layers' and the caches' counts:
# blocks and passes a LIVE slot went through (a pass counts once a slot;
# a commit pass is one that ran for the commit alone: the block round has
# none, so it stands at 0), positions revealed (the prompt's tail not
# counted), those of them behind the reply's asked length, which the host
# drops, and blocks whose commit rode the next block's first denoise pass
BLOCK_COUNTS = ("diffusion.blocks", "diffusion.denoise_passes",
                "diffusion.commit_passes", "diffusion.tokens",
                "diffusion.cut_tokens", "diffusion.fused_commits")


class EngineDraining(RuntimeError):
    """Raised by ``submit`` after :meth:`ServeEngine.drain`: the engine
    is finishing its live slots and admits nothing new (the graceful-
    shutdown signal — see ``apps/serve.py``'s SIGTERM handler)."""


class LaunchPhases(NamedTuple):
    """A decode launch's stamps on the backend's clock: the launch in the
    device's queue; the host's wait for it begun (the caller's ``launched``
    done); the round count back; the last blocking read back; and the
    rounds it ran. ``wait`` is ``counted - dispatched``, ``fetch`` is
    ``fetched - counted`` (``events.CYCLE_PHASES``)."""
    dispatched: float
    sync: float
    counted: float
    fetched: float
    rounds: int


class SlowCycle(NamedTuple):
    """A phase of the launch cycle that stood over its threshold: the tick
    that named it, the phase, its wall seconds, and the process's CPU
    seconds over the cycle up to then (near none: the process was not
    running, the machine's doing; near the wall seconds: it was, the
    interpreter's or a compile's)."""
    tick: int
    phase: str
    wall_s: float
    cpu_s: float


class _Cycle:
    """The open launch cycle: its dispatch, what is known of its phases,
    and the process's CPU seconds at its start."""

    __slots__ = ("dispatched", "wait", "fetch", "caller", "cpu0")

    def __init__(self, dispatched: float, wait: float, fetch: float,
                 cpu0: float):
        self.dispatched, self.wait, self.fetch = dispatched, wait, fetch
        self.caller, self.cpu0 = 0.0, cpu0


class _Slot:
    """Host-side state of one running request."""

    __slots__ = ("req", "tokens", "notes", "ttft", "admitted_tick",
                 "admit_at")

    def __init__(self, req: Request, first_token, admitted_tick: int = 0,
                 admit_at: float = 0.0):
        self.req = req
        # the first token holds its place from admission on, as the
        # backend's prefill returned it (an int, or what int() waits on
        # the device for); ServeEngine._land_first_tokens puts the int
        # there and stamps ttft, within the tick of the admission. None:
        # the prefill yields no token (a block round); the first tokens
        # come with the first launch, and ttft is stamped there
        self.tokens: List[int] = [] if first_token is None else [first_token]
        # what a noted round says of each token (the block round: the
        # denoise pass that revealed it)
        self.notes: List[int] = []
        self.ttft: Optional[float] = None
        self.admitted_tick = admitted_tick
        # when its admission began: where TTFT's ``queued`` stage ends
        self.admit_at = admit_at


class _Round(NamedTuple):
    """Seam B of the decode program: what one loop iteration emits.
    ``run(block_stack, pre, post, slots) -> slots, toks [S, width],
    n_emit [S], notes [S, width]`` over ``slots = (rows, tok, pos,
    key_data, hist, done, budget)``, writing ``rows`` cache rows a slot.
    ``tok`` and ``hist`` are the round's own: one token a slot and the
    draft history (None where nothing drafts), or, for the block round,
    two blocks of tokens ``[S, 2L]`` (the finished one that awaits its
    commit, the one under denoising) and ``(masked [S, L], awaits [S])``:
    which of the second are still masked, and whether the first is there.
    ``counted``: ``n_emit`` varies (the accepted length; a reply's last
    block cut) and the loop records it; otherwise it is None, and every
    live slot emits ``width``. ``noted``: the round says one number of
    each token it emits (the denoise pass that revealed it) and the loop
    keeps them beside the tokens; otherwise ``notes`` is None.
    ``opens_on_token``: a slot's ``tok`` at a launch's start is the last
    token of its reply, so an eos there starts the slot done; False where
    ``tok`` is the round's own state (a block under denoising)."""
    run: Callable
    width: int
    rows: int
    counted: bool
    noted: bool = False
    opens_on_token: bool = True


class _SlabStore:
    """Seam A of the decode program, the slab: the program's KV
    argument is the carried ``[L, S, T, C]`` rows themselves, and
    nothing stands behind them. For a model whose layers come in groups
    it is one slab a kind of cache (``{"full": [2, S, max_len, C],
    "window": [3, S, window, C]}``, the second a ring) and the counts
    the layers keep (``"counts"``), all of it carried alike."""

    def __init__(self, backend):
        self.b = backend

    # host side: the program's donated KV and kept arguments; its KV back
    def take(self):
        return self.b._caches, None

    def put(self, kv):
        self.b._caches = kv

    # device side: ``enter`` gives the loop its (rows, what is behind
    # them), ``commit`` takes the n rows each slot wrote from pos0 on
    # behind, ``leave`` gives back the program's KV
    def enter(self, kv, aux):
        return kv, None

    def commit(self, back, rows, aux, pos0, n):
        return back

    def leave(self, rows, back):
        return rows


class _PoolStore:
    """Seam A of the decode program, the paged pool. The carried rows
    are each slot's block view — its first ``max_blocks`` table entries,
    covering every row it can read or write (``rows_needed <=
    max_len``), exactly the slab's attention footprint — and the pool
    stands behind them. The views are gathered ONLY when the device-side
    ``regather`` flag says a prefill moved a table since the last
    launch; otherwise the ones carried from that launch are the same
    rows bitwise, because every round's rows are committed to the pool.
    The program returns the flag CLEARED, so a no-prefill tick makes
    zero host-driven gather decisions."""

    def __init__(self, backend):
        self.b = backend

    def take(self):
        b = self.b
        return ((b._pool_kv, b._views),
                (jnp.asarray(b.pool.table), b._regather))

    def put(self, kv):
        self.b._pool_kv, self.b._views, self.b._regather = kv

    def enter(self, kv, aux):
        """The views ``[L, S, R, C]`` (the carried layout of
        ``_run_layers``, heads folded: the gather is a whole copy
        anyway, so the fold rides it). The 2-branch cond is a role
        conditional (both branches give the same shape), not a dispatch."""
        pool_kv, views = kv
        tables, regather = aux
        pool = self.b.pool
        cd = self.b.model.cfg.compute_dtype
        view_t = tables[:, :pool.max_blocks + 1]

        def gather_layer(pool_l):
            out = jax.vmap(lambda tr: gather_block_cache(
                pool_l, tr, block_size=pool.block_size,
                compute_dtype=cd))(view_t)
            return {name: fold_heads(a[:, 0])
                    for name, a in out.items()}            # [S, R, C]

        return jax.lax.cond(
            regather, lambda v: jax.vmap(gather_layer)(pool_kv),
            lambda v: v, views), pool_kv

    def commit(self, pool_kv, views, aux, pos0, n):
        """The ``n`` rows every slot wrote into its view from ``pos0``
        on, unfolded, back into the pool through the FULL-width tables,
        whose sacrificial clamp routes overshoot/dead-slot writes into
        block 0 — a dead slot can never corrupt a reallocated block."""
        tables = aux[0]
        bs = self.b.pool.block_size
        attn = self.b.model.block.attn
        ridx = jax.vmap(lambda tr, p0: flat_row_index(
            tr, p0 + jnp.arange(n, dtype=jnp.int32), bs))(tables, pos0)

        def scat_layer(_, inp):
            pool_l, view_l = inp
            rows = {}
            for name in ("k", "v"):
                new = jax.vmap(
                    lambda v, p0: jax.lax.dynamic_slice_in_dim(v, p0, n))(
                        view_l[name], pos0)                # [S, n, C]
                rows[name] = unfold_heads(                 # [S*n, H, D]
                    new.reshape(-1, new.shape[-1]), attn.nhead,
                    attn.head_dim)
            return 0, scatter_block_rows(pool_l, ridx.reshape(-1), rows)

        return jax.lax.scan(scat_layer, 0, (pool_kv, views))[1]

    def leave(self, views, pool_kv):
        return pool_kv, views, jnp.zeros((), jnp.bool_)


class SingleDeviceSlotBackend:
    """S decode slots over one device's worth of (replicated) params.

    ``params`` is the training-layout ``(stage_params, pre_params,
    post_params)`` triple (``model.init``); blocks are flattened/stacked
    once at construction, quantized leaves (``inference/quant.py``) pass
    through and dequantize in-step — same weight handling as
    :class:`~..inference.generate.Generator`.
    """

    def __init__(self, model, params, *, num_slots: int, max_len: int,
                 gen: GenerationConfig = GenerationConfig(),
                 buckets: Optional[BucketSpec] = None,
                 decode_chunk: int = 1, shape_cache_warn: int = 8,
                 kv_block_size: Optional[int] = None,
                 kv_pool_blocks: Optional[int] = None,
                 prefill_chunk: int = 16,
                 kv_dtype: Optional[str] = None,
                 kv_offload: bool = False,
                 kv_offload_blocks: Optional[int] = None,
                 resident="auto", resident_chunks: int = 8,
                 spec_tokens: Optional[int] = None,
                 draft="ngram", draft_stages: int = 1,
                 spec_branches: Optional[int] = None,
                 spec_adaptive: bool = False):
        if not hasattr(model, "embed_at"):
            raise TypeError(
                f"{type(model).__name__} has no embed_at; KV-cache "
                "generation needs position-offset embedding")
        if gen.num_beams != 1:
            raise ValueError(
                "the serve engine decodes greedy/sampled slots; beam "
                "search has no incremental slot form (num_beams must be 1)")
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        if decode_chunk < 1:
            raise ValueError(
                f"decode_chunk must be >= 1, got {decode_chunk}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2, got {max_len}")
        self.model = model
        self.gen = gen
        self.num_slots = num_slots
        self.max_len = max_len
        self.buckets = buckets
        self.decode_chunk = decode_chunk
        self.shape_cache_warn = shape_cache_warn
        # resident is a horizon and nothing else: off is one chunk a
        # launch. "auto" is on where a launch and its sync cost what a
        # chunk costs (accelerators), off on the cpu.
        if resident not in ("auto", True, False):
            raise ValueError(
                f"resident must be 'auto', True or False, got {resident!r}")
        if resident == "auto":
            resident = jax.devices()[0].platform != "cpu"
        self.resident = bool(resident)
        if resident_chunks < 1:
            raise ValueError(
                f"resident_chunks must be >= 1, got {resident_chunks}")
        self.resident_chunks = resident_chunks if self.resident else 1
        spec = spec_tokens if spec_tokens is not None else gen.spec_tokens
        if spec is not None and spec < 2:
            raise ValueError(
                f"spec_tokens must be >= 2, got {spec}")
        self.spec_tokens = spec
        # how the model generates: a token a step unless it declares
        # otherwise. ``("block_diffusion", L, T, mask)``: a block of L
        # positions a step, through T denoise passes, the first of which
        # also commits the block before (:meth:`_block_round`); the
        # slots' ``tok`` is then ``[S, 2L]``, the block that awaits its
        # commit and the block under denoising, and their ``hist`` seat
        # holds ``(masked [S, L], awaits [S])``
        how = getattr(model, "generation", None)
        if how is not None and how[0] != "block_diffusion":
            raise ValueError(f"{type(model).__name__} generates by "
                             f"{how[0]!r}: no round of the decode program")
        self._block = None if how is None else tuple(how[1:])
        # tokens per round: the readout stride of the token buffer the
        # decode program returns. Spec mode re-sets this per launch to
        # the adaptive ladder rung that ran.
        self.decode_width = spec if spec is not None else decode_chunk
        # forward passes a round, where that is not its width (a block
        # round's T: the commit is no pass of its own)
        self.round_passes = None
        if self._block is not None:
            if decode_chunk != 1:
                raise ValueError(
                    "a block round is one block of the model's own length "
                    f"(decode_chunk must be 1, got {decode_chunk})")
            # the last block of a reply is written whole
            self.max_len = max_len = -(-max_len // self._block[0]) \
                * self._block[0]
            self.decode_width = self._block[0]
            self.round_passes = self._block[1]

        stage_params, pre_params, post_params = params
        cd = model.cfg.compute_dtype
        flat = [bp for stage in stage_params for bp in stage]
        blocks = [jax.tree_util.tree_map(
                      lambda p: p if isinstance(p, QuantLeaf)
                      else p.astype(cd),
                      bp, is_leaf=lambda x: isinstance(x, QuantLeaf))
                  for bp in flat]
        self._n_stages = len(stage_params)
        self._layers_per_stage = len(stage_params[0])
        # The layers come in GROUPS of like layers: a group is scanned,
        # the groups run in order. A model of one block is one group,
        # its blocks stacked here once. A model that has
        # ``layer_groups`` hands each group's parameters in already
        # stacked, the layout they are served in: nothing is stacked or
        # copied a second time (its weights may not fit twice).
        grouped = getattr(model, "layer_groups", None)
        self._groups = None if grouped is None else grouped()
        if self._groups is None:
            self._n_layers = len(blocks)
            self._block_stack = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs), *blocks)
        else:
            self._n_layers = sum(g.n for g in self._groups)
            self._block_stack = tuple(blocks)
            if kv_block_size is not None or gen.kv_block_size is not None:
                refuse_grouped(model, "_PoolStore (the paged KV pool)")
            if spec is not None:
                refuse_grouped(model, "_spec_round (speculative decoding)")
        if self._block is not None and self._groups is None:
            raise TypeError(
                f"{type(model).__name__} generates by diffusion over "
                "blocks: the block round keeps its counts with a grouped "
                "model's (layer_groups)")
        self._pre = pre_params
        self._post = post_params

        if spec is not None:
            self._drafter = draft if isinstance(draft, DraftSource) \
                else resolve_draft(
                    draft, n_stages=self._n_stages,
                    layers_per_stage=self._layers_per_stage,
                    draft_stages=draft_stages,
                    spec_branches=spec_branches)
            if self._drafter.branches > 1 and \
                    not hasattr(model, "embed_tree"):
                raise TypeError(
                    f"{type(model).__name__} has no embed_tree; tree "
                    "verification needs per-node position embedding")
            # spec verify writes Q = 1 + branches*(K-1) rows per round
            # starting at most at pos = plen + max_new - 2; headroom
            # keeps the Q-row dynamic_update_slice inside the slab/view
            # so its start is never clamped (a clamped start misaligns
            # EVERY row written)
            self._spec_overshoot = self._drafter.branches * (spec - 1)
            # adaptive-K: a small pre-traced ladder of round depths; the
            # host picks a rung per launch from the per-slot accepted-
            # length EWMA. Non-adaptive = one rung = PR 11 behavior.
            self._spec_ladder = (
                sorted({2, (spec + 2) // 2, spec}) if spec_adaptive
                else [spec])
            self._spec_ewma = np.full((num_slots,), float(spec))
            self._spec_acc_total = 0
            self._spec_draft_total = 0
        else:
            if not (draft == "ngram" and draft_stages == 1
                    and spec_branches is None and not spec_adaptive):
                raise ValueError(
                    "draft/draft_stages/spec_branches/spec_adaptive "
                    "configure the speculative lane; set spec_tokens")
            self._drafter = None
            self._spec_overshoot = 0
            self._spec_ladder = []

        kbs = kv_block_size if kv_block_size is not None \
            else gen.kv_block_size
        self.paged = kbs is not None
        self.kv_dtype = kv_dtype
        if self.paged:
            proto = model.block.attn.make_cache(1, max_len, dtype=cd)
            # paged KV: a block pool + per-slot tables replace the slab.
            # Default pool = the slab's row budget (same memory, ~2x the
            # servable live slots on mixed-length traffic) + block 0.
            if prefill_chunk < 1:
                raise ValueError(
                    f"prefill_chunk must be >= 1, got {prefill_chunk}")
            self.prefill_chunk = prefill_chunk
            mb = -(-max_len // kbs)
            nb = kv_pool_blocks if kv_pool_blocks is not None \
                else num_slots * mb + 1
            if buckets is not None:
                gen.check_kv_headroom(buckets.max_len, kbs,
                                      self._spec_overshoot)
            self.pool = KvPool(
                num_blocks=nb, block_size=kbs, num_slots=num_slots,
                max_len=max_len, prefix_cache=gen.prefix_cache,
                gather_slack_rows=prefill_chunk)
            self._pool_kv = storage_for(
                proto, self._n_layers, nb, kbs, kv_dtype=kv_dtype)
            self.kv_offload = bool(kv_offload)
            if self.kv_offload:
                # host spill target for cold refcount-0 blocks: payloads
                # are raw device bytes (int8 codes + scales for int8
                # pools), so offload -> restore is a bitwise round trip
                self._kv_store = HostKvStore(
                    max_blocks=(kv_offload_blocks
                                if kv_offload_blocks is not None
                                else nb))
                self.pool.attach_offload(self._kv_store,
                                         self._offload_read_block)
                self._restore_jit = jax.jit(self._restore_fn,
                                            donate_argnums=(0,))
            else:
                self._kv_store = None
            self._chunk_jit = jax.jit(self._chunk_fn, donate_argnums=(2,))
            self._sample_jit = jax.jit(self._sample_fn,
                                       donate_argnums=(2,))
            self._fork_jit = jax.jit(self._fork_fn, donate_argnums=(0,))
            # per-slot gathered views carried across launches, and the
            # device-side flag that has the decode program gather them
            # again (:class:`_PoolStore`): prefill arms it, the one host
            # decision, counted. Compute dtype even for int8 pools: the
            # view is the dequantized working set.
            self._views = model.block.attn.make_slab(
                self._n_layers, num_slots, self.pool.max_blocks * kbs,
                dtype=cd)
            self._regather = jnp.asarray(True)
        else:
            if kv_dtype is not None:
                raise ValueError(
                    "kv_dtype needs the paged pool (set kv_block_size); "
                    "the slab path stores KV in the compute dtype")
            if kv_offload:
                raise ValueError(
                    "kv_offload needs the paged pool (set kv_block_size); "
                    "the slab path has no block-level eviction to spill")
            self.kv_offload = False
            self._kv_store = None
            self.pool = None
            if self._groups is None:
                self._caches = model.block.attn.make_slab(
                    self._n_layers, num_slots, max_len, dtype=cd)
            else:
                self._caches = self._make_group_slabs(cd)
        self._tok = jnp.zeros((num_slots,), jnp.int32)
        if self._block is not None:
            self._tok = jnp.full((num_slots, 2 * self._block[0]),
                                 self._block[2], jnp.int32)
        self._pos = jnp.zeros((num_slots,), jnp.int32)
        kd0 = jax.random.key_data(jax.random.key(0))
        self._key_data = jnp.broadcast_to(kd0, (num_slots,) + kd0.shape)

        # device-side token history, the n-gram draft source
        # (:meth:`_arm` seeds a row; accepted tokens land in-program).
        # spec_tokens rows of slack absorb the masked write past the
        # last position. None where no round drafts.
        self._hist = None if spec is None else jnp.full(
            (num_slots, max_len + spec), gen.pad_token_id, jnp.int32)
        if self._block is not None:     # the block round's seat: masked,
            self._hist = (                # and no block awaits its commit
                jnp.ones((num_slots, self._block[0]), jnp.bool_),
                jnp.zeros((num_slots,), jnp.bool_))
        self.launch_notes = None
        # the newest launch's stamps for the engine's account of the launch
        # cycle, on ``clock``: the engine that drives this backend puts its
        # own clock there, so that both stamp on one
        self.launch_phases: Optional[LaunchPhases] = None
        self.clock: Callable[[], float] = time.monotonic

        # THE decode program: one jit per round width — one in all
        # without speculation (the plain round, or the block round of a
        # model that generates so), one per ladder rung with it (every rung
        # traces once, then the steady state is rung selection over
        # compiled programs). Donated: the store's KV and the history.
        self._store = _PoolStore(self) if self.paged else _SlabStore(self)
        if self._block is not None:
            rounds = [_Round(self._block_round, self.decode_width,
                             self.decode_width, counted=True, noted=True,
                             opens_on_token=False)]
        elif spec is None:
            rounds = [_Round(self._plain_round, decode_chunk, decode_chunk,
                             False)]
        else:
            B = self._drafter.branches
            rounds = [_Round(functools.partial(self._spec_round, k), k,
                             1 + B * (k - 1), True)
                      for k in self._spec_ladder]
        self._resident_jits = {
            rnd.width: jax.jit(self._decode_program(self._store, rnd),
                               donate_argnums=(3, 8))
            for rnd in rounds}

        self._prefill_programs = {}

    # -- validation --------------------------------------------------------

    def validate(self, prompt_len: int, max_new_tokens: int) -> None:
        """Admission-control shape checks — reject at submit, not at
        prefill, so a bad request never costs a slot. Paged mode adds
        the can-it-EVER-fit check: demand beyond the whole pool is
        unservable, not merely parked."""
        bucket = (self.buckets.bucket_for(prompt_len)
                  if self.buckets is not None and not self.paged
                  else prompt_len)
        if self.paged and self.pool.demand_for(
                prompt_len, max_new_tokens) > self.pool.allocatable:
            raise ValueError(
                f"request needs "
                f"{self.pool.demand_for(prompt_len, max_new_tokens)} KV "
                f"blocks but the whole pool holds "
                f"{self.pool.allocatable}; raise kv_pool_blocks or "
                f"shorten the request")
        rows = prompt_len + max_new_tokens + self._spec_overshoot
        if self._block is not None:      # the last block is written whole
            rows = -(-rows // self._block[0]) * self._block[0]
        if rows > self.max_len:
            extra = (f" + speculative headroom {self._spec_overshoot}"
                     if self._spec_overshoot else "")
            if self._block is not None:
                extra = f", in whole blocks of {self._block[0]},"
            raise ValueError(
                f"prompt_len {prompt_len} + max_new_tokens "
                f"{max_new_tokens}{extra} exceeds the slot cache "
                f"({self.max_len} rows); raise max_len or shorten the "
                f"request")
        if max_new_tokens > self.gen.max_new_tokens:
            raise ValueError(
                f"max_new_tokens {max_new_tokens} exceeds the engine cap "
                f"({self.gen.max_new_tokens})")
        mp = getattr(self.model, "max_position", None)
        limit = mp() if callable(mp) else None
        if limit is not None and max(bucket,
                                     prompt_len + max_new_tokens) > limit:
            raise ValueError(
                f"request needs position {max(bucket, prompt_len + max_new_tokens)} "
                f"but the positional table has {limit}")

    # -- device programs ---------------------------------------------------

    def _run_layers(self, block_stack, h, caches, pos, tree=None,
                    live=None, lead=None):
        """THE layer loop of the decode program (slab and paged views
        alike, the plain step, the speculative verify and the truncated
        drafters): ``h [S, q, d]`` through all layers
        at per-slot positions ``pos [S]``. The stacked cache
        ``[L, S, T, C]`` (``attn.make_slab``: a cache row is its heads
        folded into one axis of whole lane tiles) is the loop's CARRY,
        never a scanned input or a stacked output — each layer writes
        its ``S x q`` new rows into it and reads its own layer of it
        (the slab form of ``block.decode``), so the compiler keeps one
        buffer through the layer loop, the chunk scan and the launch's
        ``while`` instead of slicing a layer out, stacking it back and
        copying the whole slab every step. Everything carried here has
        this one shape (slab, paged views, the tree drafter's copies:
        one layout, chosen by nothing): ``T x C`` is whole tiles on the
        TPU, so the slab as the program's argument, the carry and the
        two reads of a layer share one layout with 4% of padding where
        rows of ``[H, D]`` had 2.6x, no launch relays it, and a row
        write touches 13 tiles (``MultiHeadAttention.decode``; PERF.md,
        PR 29). ``block_stack`` may hold fewer layers than the cache:
        the loop then runs the first ones (a truncated drafter).

        A model whose layers come in groups (:meth:`_run_groups`) has
        one such carried slab a kind of cache, in ``caches`` by name,
        and ``live [S]`` (or, a row at a time, ``[S, q]``) tells its
        expert layers which slots' rows to compute; ``lead``: whose
        leading rows the cache takes (``MultiHeadAttention.decode``), for
        a block that says so."""
        m = self.model
        cd = m.cfg.compute_dtype
        if self._groups is not None:
            more = {} if lead is None else {"lead": lead}

            def step(g, bp, i, h, slab):
                h, slab, counts = g.block.decode(
                    bp, h, slab, pos, tree=tree, layer=g.first + i,
                    live=live if live is None or live.ndim == 2
                    else live[:, None], at=i, **more)
                return h, slab, None, counts

            return self._run_groups(block_stack, h, caches, COUNT_DECODE,
                                    step)[:2]

        def layer(carry, inp):
            h, caches = carry
            bp, l = inp
            return m.block.decode(dequant_tree(bp, cd), h, caches, pos,
                                  tree=tree, layer=l), None

        n = jax.tree_util.tree_leaves(block_stack)[0].shape[0]
        (h, caches), _ = jax.lax.scan(
            layer, (h, caches),
            (block_stack, jnp.arange(n, dtype=jnp.int32)))
        return h, caches

    # -- layers in groups ----------------------------------------------------

    def _make_group_slabs(self, cd):
        """The carried state of a model whose layers come in groups:
        one slab a kind of cache, each as its own attention lays it out
        (``make_slab``: ``max_len`` rows a slot, or a ring of the
        window's), and ``counts [2, n]``: what the layers and the decode
        step count (``self._count_names``), row :data:`COUNT_PREFILL`
        by the prefill programs and row :data:`COUNT_DECODE` by the
        decode program (with a block round, :data:`BLOCK_COUNTS` too), summed on the device for as long as the backend
        lives (int32, wrapping: the host takes differences)."""
        kinds = {}
        for g in self._groups:
            attn, n = kinds.get(g.cache, (g.block.attn, 0))
            kinds[g.cache] = (attn, max(n, g.first + g.n))
        slabs = {kind: attn.make_slab(n, self.num_slots, self.max_len,
                                      dtype=cd)
                 for kind, (attn, n) in kinds.items()}
        self._layer_counts = tuple(self.model.layer_counts)
        self._cache_kinds = tuple(slabs)
        self._count_names = self._layer_counts + tuple(
            f"cache.{kind}_rows_read" for kind in slabs)
        if self._block is not None:
            self._count_names += BLOCK_COUNTS
        self._counts_seen = np.zeros((2, len(self._count_names)), np.uint32)
        self.launch_counts = {}
        return dict(slabs, counts=jnp.zeros(self._counts_seen.shape,
                                            jnp.int32))

    def _run_groups(self, stacks, h, caches, row, step):
        """``h`` through every group in order, a group a ``lax.scan``
        over its layers' indices with its stacked parameters standing
        outside the scan (a layer picks its own; the routed experts are
        never sliced out). ``step(group, stack, i, h, slab) -> (h, slab,
        rows, counts)``: the slab as the layer leaves it, the layer's new
        cache rows where the caller seats them itself (else None), and
        the layer's counts, which are summed into row ``row`` of the
        carried ``counts``. Returns ``(h, caches, [a group's stacked
        rows])``."""
        caches = dict(caches)
        n_counts = len(self._layer_counts)
        total = jnp.zeros((n_counts,), jnp.int32)
        rows = []
        for g, bp in zip(self._groups, stacks):
            def layer(carry, i, g=g, bp=bp):
                h, slab, acc = carry
                h, slab, new, c = step(g, bp, i, h, slab)
                return (h, slab, acc + c), new

            (h, caches[g.cache], total), new = jax.lax.scan(
                layer, (h, caches[g.cache], total),
                jnp.arange(g.n, dtype=jnp.int32))
            rows.append(new)
        caches["counts"] = caches["counts"].at[row, :n_counts].add(total)
        return h, caches, rows

    def _count_rows_read(self, caches, pos, live, q: int = 1):
        """The cache rows a decode step's live slots read, by kind of
        cache (a ring's stop growing at its length), into the carried
        counts; ``q``: the rows a slot's step writes and then reads (a
        block's)."""
        counts = caches["counts"]
        for j, kind in enumerate(self._cache_kinds,
                                 start=len(self._layer_counts)):
            layers, _, length, _ = caches[kind]["k"].shape
            rows = jnp.sum(jnp.where(live, jnp.minimum(pos + q, length), 0))
            counts = counts.at[COUNT_DECODE, j].add(rows * layers)
        return dict(caches, counts=counts)

    def _prefill_groups(self, block_stack, pre, caches, prompt, true_len,
                        slot):
        """A padded prompt through the groups without a temporary cache:
        each layer's attention runs over the prompt itself a block of
        queries at a time (``block.prefill``), and its rows are seated in
        the slot of its kind of slab as that attention keeps them
        (``attn.seat``: the bucket's rows, or the ring of the last
        ``window``). The bucket's padding takes no part in the experts'
        product. Returns ``(h [1, B, d], caches)``."""
        live = (jnp.arange(prompt.shape[1]) < true_len)[None, :]

        def step(g, bp, i, h, slab):
            h, new, counts = g.block.prefill(bp, h, live=live, at=i)
            return h, slab, new, counts

        h, caches, rows = self._run_groups(
            block_stack, self.model.embed_at(pre, prompt, 0), caches,
            COUNT_PREFILL, step)
        with ev.device_scope(ev.KV_CACHE):       # the slot's rows, seated
            for g, new in zip(self._groups, rows):
                caches[g.cache] = {
                    n: jax.lax.dynamic_update_slice(
                        slab, g.block.attn.seat(new[n], true_len).astype(
                            slab.dtype), (g.first, slot, 0, 0))
                    for n, slab in caches[g.cache].items()}
        return h, caches

    def _arm(self, state, slot, true_len, tok0, key, row):
        """Traced, the last lines of every admission's program (the
        slab's prefill, the pool's first-token epilogue): the slot's
        entries of the backend's ``(tok, pos, key_data, hist)`` written
        from the program's own first token, the prompt's length and the
        next key of the chain — what the decode program starts the slot
        from, with nothing read back to the host in between. ``row`` is
        the slot's draft history as the host knows it (the prompt, pad
        beyond); the first token goes in at ``true_len``."""
        tok, pos, key_data, hist = state
        put = jax.lax.dynamic_update_index_in_dim
        if hist is not None:
            hist = put(hist, put(row, tok0, true_len, 0), slot, 0)
        return (put(tok, tok0, slot, 0),
                put(pos, jnp.asarray(true_len, pos.dtype), slot, 0),
                put(key_data, jax.random.key_data(key), slot, 0), hist)

    def _arm_block(self, state, prompt, true_len, slot, seed):
        """Traced, the last lines of a block-round admission's program:
        the slot's block is the prompt's tail (its ``true_len % L``
        tokens behind the last whole block, revealed) and the mask token
        behind it, its position the first row no whole block filled, its
        key the request's. No block awaits its commit: the prompt's whole
        blocks are in the cache already, and the block the slot's last
        reply ended in was never owed one (the whole slab is written anew),
        so the flag is put down."""
        tok, pos, key_data, (masked, awaits) = state
        L, _, mask_id = self._block
        put = jax.lax.dynamic_update_index_in_dim
        whole = true_len - true_len % L
        # padded, so that the slice is never clamped back into the prompt
        tail = jax.lax.dynamic_slice(
            jnp.pad(prompt[0], (0, L)), (whole,), (L,))
        hidden = jnp.arange(L, dtype=jnp.int32) >= true_len - whole
        block = jnp.where(hidden, jnp.int32(mask_id), tail)
        return (put(tok, jnp.concatenate(
                    [jnp.full((L,), jnp.int32(mask_id)), block]), slot, 0),
                put(pos, jnp.asarray(whole, pos.dtype), slot, 0),
                put(key_data, jax.random.key_data(jax.random.key(seed)),
                    slot, 0),
                (put(masked, hidden, slot, 0),
                 put(awaits, jnp.zeros((), jnp.bool_), slot, 0)))

    def _prefill_fn(self, block_stack, pre, post, caches, state, prompt,
                    true_len, slot, seed, row):
        """One bucket-length-B prefill: runs the padded prompt through
        every layer against a fresh full-length temp cache (the batch
        form's ``[L, 1, T, H, D]``: a prompt's rows are a matrix, and
        the batch form reads and writes them as one), then writes the
        ENTIRE slot slab (previous occupant's rows are gone, not merely
        masked), folding its heads once on the way into the carried
        layout ``[L, S, T, C]``, samples the first token with the
        exact batch-1 Generator key chain from ``jax.random.key(seed)``,
        made here, and arms the slot with it (:meth:`_arm`)."""
        m, gen = self.model, self.gen
        cd = m.cfg.compute_dtype
        get_registry().counter("serve.engine.prefill_traces").inc()
        if self._block is not None:
            # a prompt's whole blocks are cached; its tail is the revealed
            # part of the first block, which the block round runs. No
            # head: nothing is sampled here
            L = self._block[0]
            _, caches = self._prefill_groups(
                block_stack, pre, caches, prompt, true_len - true_len % L,
                slot)
            return caches, self._arm_block(state, prompt, true_len, slot,
                                           seed), None
        if self._groups is not None:
            h, caches = self._prefill_groups(block_stack, pre, caches,
                                             prompt, true_len, slot)
            return self._prefill_end(post, caches, state, h, true_len,
                                     slot, seed, row)
        with ev.device_scope(ev.KV_CACHE):       # the temporary cache
            proto = m.block.attn.make_cache(1, self.max_len, dtype=cd)
            temp0 = jax.tree_util.tree_map(
                lambda a: jnp.zeros((self._n_layers,) + a.shape, a.dtype),
                proto)
        h = m.embed_at(pre, prompt, 0)                    # [1, B, d]

        def layer(h, inp):
            bp, cache = inp
            h, cache = m.block.decode(dequant_tree(bp, cd), h, cache, 0)
            return h, cache

        h, temp = jax.lax.scan(layer, h, (block_stack, temp0))
        with ev.device_scope(ev.KV_CACHE):       # the slot's slab, whole
            caches = jax.tree_util.tree_map(
                lambda big, rows: jax.lax.dynamic_update_slice(
                    big, fold_heads(rows), (0, slot, 0, 0)),
                caches, temp)
        return self._prefill_end(post, caches, state, h, true_len, slot,
                                 seed, row)

    def _prefill_end(self, post, caches, state, h, true_len, slot, seed,
                     row):
        """Traced, what every slab prefill ends in: the first token from
        the prompt's last real row, and the slot armed with it."""
        h_last = jax.lax.dynamic_slice(
            h, (0, true_len - 1, 0), (1, 1, h.shape[-1]))
        tok0, key = self._first_token(post, h_last, seed)
        return caches, self._arm(state, slot, true_len, tok0, key,
                                 row), tok0

    def _first_token(self, post, h_last, seed):
        """Traced: the exact batch-1 Generator key chain (key, split,
        sample) from the request's seed, a host integer until here."""
        key, sub = jax.random.split(jax.random.key(seed))
        tok0 = sample_logits(
            head_logits(self.model, post, h_last)[:, 0, :], sub,
            self.gen)[0]
        return tok0, key

    # -- paged device programs ---------------------------------------------

    def _chunk_fn(self, block_stack, pre, pool_kv, table_row, tokens,
                  t0, true_len):
        """THE prefill program: one fixed-shape ``[1, C]`` chunk at a
        traced offset, looped on the host until the prompt is covered —
        ANY prompt length, one compile (the per-bucket programs the slab
        path keys on prompt shape are gone). Each layer attends against
        the slot's gathered block view (earlier chunks' rows included)
        and scatters its C new rows back through the table; pad
        positions past ``true_len`` land in the slot's own future decode
        blocks or the sacrificial block, both rewritten/ignored before
        any unmasked read. Returns ``h`` at ``true_len - 1`` clamped
        into this chunk — the host keeps the last chunk's."""
        m = self.model
        cd = m.cfg.compute_dtype
        get_registry().counter("serve.engine.prefill_chunk_traces").inc()
        bs = self.pool.block_size
        C = tokens.shape[1]
        h = m.embed_at(pre, tokens, t0)                  # [1, C, d]
        positions = t0 + jnp.arange(C, dtype=jnp.int32)
        ridx = flat_row_index(table_row, positions, bs)

        def layer(h, inp):
            bp, pool_l = inp
            cache = gather_block_cache(pool_l, table_row, block_size=bs,
                                       compute_dtype=cd)
            h, c2 = m.block.decode(dequant_tree(bp, cd), h, cache, t0)
            rows = {name: jax.lax.dynamic_slice(
                        c2[name], (0, t0) + (0,) * (c2[name].ndim - 2),
                        (1, C) + c2[name].shape[2:])[0]
                    for name in ("k", "v")}
            return h, scatter_block_rows(pool_l, ridx, rows)

        h, pool_kv = jax.lax.scan(layer, h, (block_stack, pool_kv))
        idx = jnp.clip(true_len - 1 - t0, 0, C - 1)
        h_last = jax.lax.dynamic_slice(h, (0, idx, 0), (1, 1, h.shape[-1]))
        return pool_kv, h_last

    def _sample_fn(self, post, h_last, state, true_len, slot, seed, row):
        """First-token epilogue of a paged admission, what the slab
        prefill ends in (:meth:`_first_token`, :meth:`_arm`) — kept as
        its own fixed-shape program so the chunk loop stays
        length-agnostic."""
        tok0, key = self._first_token(post, h_last, seed)
        return self._arm(state, slot, true_len, tok0, key, row), tok0

    def _fork_fn(self, pool_kv, src, dst):
        """Copy-on-write block copy (src/dst traced — one program for
        every fork)."""
        get_registry().counter("serve.kv.fork_traces").inc()
        return copy_block(pool_kv, src, dst, block_axis=1)

    def _offload_read_block(self, bid: int) -> dict:
        """Host copy of one physical block across every pool array —
        the payload :class:`~.kvpool.HostKvStore` holds while the block
        is offloaded. Raw storage bytes (int8 codes + scales for int8
        pools), so the later restore is bitwise."""
        return {name: np.asarray(a[:, bid])
                for name, a in self._pool_kv.items()}

    def _restore_fn(self, pool_kv, dst, payload):
        """Write an offloaded block's host payload back at physical
        block ``dst`` (traced — ONE program for every restore, the
        mirror of :meth:`_fork_fn`; the view refresh rides the regather
        flag the admitting prefill arms anyway)."""
        get_registry().counter("serve.kv.restore_traces").inc()
        out = dict(pool_kv)
        with ev.device_scope(ev.KV_CACHE):
            for name, rows in payload.items():
                out[name] = jax.lax.dynamic_update_slice_in_dim(
                    pool_kv[name], rows[:, None], dst, axis=1)
        return out

    # -- THE decode program ------------------------------------------------

    def _resident_step(self, block_stack, pre, post, carry):
        """THE decode step: one token for all S slots at per-slot
        positions (the slab form of the layer decode,
        :meth:`_run_layers`), each slot on its own batch-1 Generator
        key chain, the done mask extended by eos and the token budget."""
        m, gen = self.model, self.gen
        eos = gen.eos_token_id
        caches, tok, pos, key_data, hist, done, budget = carry

        def embed_one(t, p):
            return m.embed_at(pre, t[None, None], p)[0]

        h = jax.vmap(embed_one)(tok, pos)                  # [S, 1, d]

        if self._groups is None:
            h, caches = self._run_layers(block_stack, h, caches, pos)
        else:
            h, caches = self._run_layers(block_stack, h, caches, pos,
                                         live=~done)
            caches = self._count_rows_read(caches, pos, ~done)
        logits = head_logits(m, post, h)[:, 0, :]          # [S, V]
        keys = jax.random.wrap_key_data(key_data)
        ks = jax.vmap(jax.random.split)(keys)              # [S, 2] keys
        key_data = jax.random.key_data(ks[:, 0])
        nxt = jax.vmap(
            lambda lg, k: sample_logits(lg[None], k, gen)[0])(
                logits, ks[:, 1])
        nxt = jnp.where(done, jnp.int32(gen.pad_token_id), nxt)
        budget = budget - jnp.where(done, 0, 1)
        done = done | (budget <= 0)
        if eos is not None:
            done = done | (nxt == jnp.int32(eos))
        return (caches, nxt, pos + 1, key_data, hist, done, budget), nxt

    def _plain_round(self, block_stack, pre, post, carry):
        """Seam B, the plain round: ``decode_chunk`` steps. Every live
        slot emits the whole chunk (``n_emit`` None), pad past its
        eos/budget."""
        carry, toks = jax.lax.scan(
            lambda c, _: self._resident_step(block_stack, pre, post, c),
            carry, None, length=self.decode_chunk)
        return carry, jnp.moveaxis(toks, 0, 1), None, None

    def _block_round(self, block_stack, pre, post, carry):
        """Seam B, the block round: one block of ``L`` positions for all S
        slots. Counted (a reply ends inside its last block: the tokens
        behind its budget are not valid) and noted (the pass that revealed
        each token).
        A slot's state is two blocks of tokens ``tok [S, 2L]``, the
        finished block at ``pos - L`` that awaits its commit (where
        ``awaits [S]``) and the block under denoising at the block-aligned
        ``pos``, and which positions of the second are still ``masked [S,
        L]`` (never inferred from a token id). ``T`` DENOISE passes: the
        block's rows at their positions through every layer, over the
        cache of all earlier blocks and over each other (the ``q = L`` form
        of :meth:`_run_layers` under an all-ones within-chunk mask), the
        head, and at each masked position the token ``x_p`` put first (or
        sampled on the slot's key chain) with its probability ``c_p``;
        the ``ceil(masked / passes left)`` masked positions of largest
        ``c_p`` are revealed. A pass of a slot that has nothing masked
        left changes nothing and is not counted.

        The COMMIT of a block, the pass of its finished tokens whose keys
        and values are what the cache keeps, is no pass of its own: it is
        the first half of the NEXT round's first denoise pass. That pass
        takes ``2L`` rows a slot at ``pos - L``, the awaiting block and
        then the block under denoising, under a block lower-triangular
        mask (the first sees the cache and itself; the second the cache,
        the first and itself): every row goes through what a pass of its
        own would put it through, and the weights are read once. The head
        and the reveal take the second half only. Every pass writes the
        rows it computes, the last writer wins, and no read sees an
        earlier pass's (the speculative round's "rollback is free"); the
        first half is written where a block awaits and nowhere else: a
        slot's first round after its admission has the prompt's rows
        below ``pos``, or none (``pos`` 0), and keeps them. So the round
        ends on its last denoise pass and leaves its block awaiting: across
        rounds, launches and other slots' admissions (the state is what a
        launch returns and the next takes). A reply's LAST block is never
        committed: nobody reads rows behind a reply's end, the slot
        retires, and the next admission writes the whole slab and puts the
        flag down (:meth:`_arm_block`). A slot that is done takes part in
        neither half.

        The positions that were masked at the block's start are its
        tokens (all ``L`` but for a prompt's tail in a request's first
        block), emitted left-aligned with the pass that revealed each;
        those behind the slot's budget are the host's to drop."""
        m, gen = self.model, self.gen
        L, T, mask_id = self._block
        eos = gen.eos_token_id
        caches, tok, pos, key_data, (masked, awaits), done, budget = carry
        held, tok = tok[:, :L], tok[:, L:]
        ones = np.ones((L, L), bool)
        ar = jnp.arange(L, dtype=jnp.int32)
        n_gen = jnp.sum(masked.astype(jnp.int32), axis=1)      # [S]
        base = len(self._count_names) - len(BLOCK_COUNTS)
        rides = awaits & ~done          # commits in this round's first pass

        def count(caches, *, blocks=0, denoise=0, tokens=0, cut=0, fused=0):
            """Into the carried counts, in :data:`BLOCK_COUNTS`' order."""
            add = jnp.stack([jnp.asarray(v, jnp.int32) for v in
                             (blocks, denoise, 0, tokens, cut, fused)])
            return dict(caches, counts=caches["counts"].at[
                COUNT_DECODE, base:].add(add))

        def embed(rows, at):
            return jax.vmap(
                lambda xs, p: m.embed_at(pre, xs[None], p)[0])(rows, at)

        def layers(tok, caches, work, fused):
            with ev.device_scope(ev.DIFFUSION_DENOISE):
                if fused:
                    both = np.kron(np.tril(np.ones((2, 2), bool)), ones)
                    live = jnp.repeat(jnp.stack([rides, work], axis=1), L,
                                      axis=1)                  # [S, 2L]
                    h, caches = self._run_layers(
                        block_stack,
                        embed(jnp.concatenate([held, tok], axis=1), pos - L),
                        caches, pos - L, tree=both, live=live,
                        lead=(L, rides))
                    h = h[:, L:]
                else:
                    h, caches = self._run_layers(
                        block_stack, embed(tok, pos), caches, pos,
                        tree=ones, live=work)
            # the two halves read the same rows, once
            return h, self._count_rows_read(caches, pos, work, q=L)

        def denoise(c, t, fused=False):
            caches, tok, masked, key_data, note = c
            left = jnp.sum(masked.astype(jnp.int32), axis=1)
            work = ~done & (left > 0)
            h, caches = layers(tok, caches, work, fused)
            logits = head_logits(m, post, h)                   # [S, L, V]
            with ev.device_scope(ev.HEAD), \
                    ev.device_scope(ev.DIFFUSION_SELECT):
                if gen.temperature == 0.0:
                    subs = None
                else:
                    ks = jax.vmap(jax.random.split)(
                        jax.random.wrap_key_data(key_data))
                    key_data = jax.random.key_data(ks[:, 0])
                    subs = jax.vmap(lambda k: jax.random.split(k, L))(
                        ks[:, 1])
                x, conf = sample_with_confidence(logits, subs, gen)
                reveal = most_confident(conf, masked & work[:, None],
                                        -(-left // (T - t)))
                tok = jnp.where(reveal, x, tok)
                masked = masked & ~reveal
                note = jnp.where(reveal, t, note)
            caches = count(caches, denoise=jnp.sum(work),
                           tokens=jnp.sum(reveal))
            return caches, tok, masked, key_data, note

        c = denoise((caches, tok, masked, key_data,
                     jnp.zeros(tok.shape, jnp.int32)), 0, fused=True)
        (caches, tok, masked, key_data, note), _ = jax.lax.scan(
            lambda c, t: (denoise(c, t), None), c,
            jnp.arange(1, T, dtype=jnp.int32))
        n_emit = jnp.where(done, 0, jnp.minimum(n_gen, budget))
        caches = count(caches, blocks=jnp.sum(~done), fused=jnp.sum(rides),
                       cut=jnp.sum(jnp.where(done, 0, n_gen) - n_emit))
        # the block's own tokens, left-aligned (a first block's lie
        # behind the prompt's tail), with the pass that revealed each
        src = (ar[None, :] + (L - n_gen)[:, None]) % L
        emit = ar[None, :] < n_emit[:, None]
        toks = jnp.where(emit, jnp.take_along_axis(tok, src, axis=1),
                         jnp.int32(gen.pad_token_id))
        note = jnp.where(emit, jnp.take_along_axis(note, src, axis=1), 0)
        pos = jnp.where(done, pos, pos + L)
        budget = budget - n_emit
        ran, done = ~done, done | (budget <= 0)
        if eos is not None:
            done = done | jnp.any((toks == jnp.int32(eos)) & emit, axis=1)
        # the finished block awaits its commit where the reply goes on (a
        # slot that took no part keeps what it had); the next block: all
        # of it masked
        return ((caches,
                 jnp.concatenate(
                     [jnp.where(ran[:, None], tok, held),
                      jnp.full(tok.shape, jnp.int32(mask_id))], axis=1),
                 pos, key_data,
                 (jnp.ones(masked.shape, jnp.bool_),
                  jnp.where(ran, ~done, awaits)), done, budget),
                toks, n_emit, note)

    def _decode_program(self, store, rnd):
        """Build THE decode program: one ``lax.while_loop`` of rounds
        over the carry ``(rows, tok, pos, key_data, hist, done, budget,
        back, buf, counts, notes, k)``, calling at one site each the two
        things that differ between its uses. Seam A, the cache ``store``
        (:class:`_SlabStore` | :class:`_PoolStore`): what stands behind
        the carried ``[L, S, T, C]`` rows. Seam B, the round ``rnd``
        (:meth:`_plain_round` | :meth:`_spec_round` |
        :meth:`_block_round`): what one iteration emits. ``done`` is the per-slot eos/length mask and ``budget``
        the per-slot remaining max_new_tokens; the loop exits early when
        any LIVE slot goes done (a slot freed: host admission can change
        the slot set) or after ``r_max`` rounds (traced, <= the static
        ``resident_chunks``: the deadline horizon, 1 where ``resident``
        is off). Per-step token/key/pos evolution is bitwise the batch-1
        Generator chain however the rounds are chopped into launches;
        tokens past a slot's eos/budget are pad and the host's readout
        break reaches them never.

        The program returns the store's KV, the slots' state, the token
        buffer ``[S, R*W]``, per-round valid counts ``[S, R]``, the
        round count run (the launch's ONE host sync, which sizes the
        readout) and a noted round's notes ``[S, R*W]`` (else None). Traced exactly once per round width — the counter
        below increments at trace time only, pinning the zero-recompile
        claim (``decode_traces`` for a one-chunk horizon,
        ``resident_traces`` for a longer one)."""
        W, R, S = rnd.width, self.resident_chunks, self.num_slots
        pad, eos = self.gen.pad_token_id, self.gen.eos_token_id
        traces = ("serve.engine.resident_traces" if R > 1
                  else "serve.engine.decode_traces")

        def _resident_fn(block_stack, pre, post, kv, aux, tok, pos,
                         key_data, hist, live, budget, r_max):
            get_registry().counter(traces).inc()

            # slots: what a round advances, (rows, tok, pos, key_data,
            # hist, done, budget)
            def body(state):
                slots, back, buf, counts, notes, k = state
                pos0 = slots[2]
                slots, toks, n_emit, said = rnd.run(block_stack, pre, post,
                                                    slots)
                back = store.commit(back, slots[0], aux, pos0, rnd.rows)
                buf = jax.lax.dynamic_update_slice(buf, toks, (0, k * W))
                if rnd.counted:
                    counts = jax.lax.dynamic_update_slice(
                        counts, n_emit[:, None], (0, k))
                if rnd.noted:
                    notes = jax.lax.dynamic_update_slice(notes, said,
                                                         (0, k * W))
                return slots, back, buf, counts, notes, k + 1

            def cond(state):
                slots, *_, k = state
                return (k < r_max) & ~jnp.any(live & slots[5])

            # dead slots, spent budgets, and slots whose first token is
            # eos (known only here: the host reads an admission's first
            # token after this launch is in the queue) start done
            done = ~live | (budget <= 0)
            if eos is not None and rnd.opens_on_token:
                done = done | (tok == jnp.int32(eos))
            rows, back = store.enter(kv, aux)
            slots, back, buf, counts, notes, k = jax.lax.while_loop(
                cond, body, (
                    (rows, tok, pos, key_data, hist, done, budget), back,
                    jnp.full((S, R * W), jnp.int32(pad), jnp.int32),
                    jnp.zeros((S, R), jnp.int32) if rnd.counted else None,
                    jnp.zeros((S, R * W), jnp.int32) if rnd.noted else None,
                    jnp.int32(0)))
            rows, tok, pos, key_data, hist, _, _ = slots
            if not rnd.counted:     # every live slot emitted every round
                counts = jnp.where(
                    (jnp.arange(R, dtype=jnp.int32)[None, :] < k)
                    & live[:, None], jnp.int32(W), jnp.int32(0))
            return (store.leave(rows, back), tok, pos, key_data, hist, buf,
                    counts, k, notes)

        return _resident_fn

    # -- the speculative round ---------------------------------------------
    #
    # One loop iteration becomes a draft/verify ROUND: propose
    # K-1 tokens by prompt-lookup (the most recent earlier occurrence
    # of the current token in the slot's device-side history buffer),
    # verify [tok, drafts] teacher-forced in ONE fixed-shape q=K decode
    # at the slot's offset (the chunked-prefill mechanism, whose
    # width-invariance the prefill parity pins already establish), and
    # accept the leading prefix that matches plus the one correction
    # token. Rollback is free: rejected rows sit at positions >= the
    # advanced pos, causally masked, and the next round's q=K write
    # covers them before any unmasked read. The per-slot key chain
    # consumes exactly n_emit splits, so accepted tokens are bitwise
    # the sequential Generator chain.

    def _spec_round(self, K, block_stack, pre, post, carry):
        """Seam B, the speculative round: one draft/verify round at
        ladder depth ``K``. Carry: (caches-or-views, tok, pos,
        key_data, hist, done, budget); returns the updated carry, the
        round's ``[S, K]`` token row and ``[S]`` accepted counts. In
        the paged store the ``Q`` verify rows scatter back through the
        full-width tables (rejected/dead rows route to the sacrificial
        block exactly like dead-slot decode).

        With a multi-branch drafter the verify chunk is the flattened
        draft tree — ``Q = 1 + B*(K-1)`` rows under the causal tree
        mask (:func:`~..inference.draft.tree_layout`), same-depth nodes
        sharing one sample key so whichever branch lies on the true
        sequential path replays the exact Generator chain. The longest
        matching root-to-leaf path wins; its KV rows are relocated to
        the canonical positions before the round returns, so the next
        round's chunk reads them like any linear prefix."""
        m, gen = self.model, self.gen
        eos = gen.eos_token_id
        caches, tok, pos, key_data, hist, done, budget = carry
        S = tok.shape[0]
        B = self._drafter.branches
        ar = jnp.arange(K, dtype=jnp.int32)

        # 1) draft: [S, B, K-1] candidate continuations of tok
        drafts, caches = self._drafter.propose(
            self._run_layers, m, pre, block_stack, caches, tok, pos, hist,
            K)

        # 2) verify: ONE fixed-shape q=Q teacher-forced decode. Linear
        # (B=1) keeps the PR 11 chunk byte-for-byte; tree embeds each
        # node at pos+depth and masks to ancestors-or-self.
        x = jnp.concatenate(
            [tok[:, None], drafts.reshape(S, B * (K - 1))], axis=1)
        if B == 1:
            anc = None
            h = jax.vmap(
                lambda xs, p: m.embed_at(pre, xs[None], p)[0])(x, pos)
        else:
            depths_np, anc_np = tree_layout(K, B)
            depths = jnp.asarray(depths_np)
            anc = jnp.asarray(anc_np)
            h = jax.vmap(
                lambda xs, p: m.embed_tree(pre, xs[None], p, depths)[0])(
                    x, pos)

        h, caches = self._run_layers(block_stack, h, caches, pos, tree=anc)
        logits = head_logits(m, post, h)                   # [S, Q, V]

        # 3) the sequential key chain, unrolled K deep: carries[i] is
        # the slot key AFTER i+1 splits, subs[i] the i-th sample key.
        # Tree nodes index subs by DEPTH: the sample at depth d is the
        # d-th sequential draw whichever branch it sits on.
        def chain(kd0):
            def sp(c, _):
                k2, sub = jax.random.split(jax.random.wrap_key_data(c))
                c2 = jax.random.key_data(k2)
                return c2, (c2, jax.random.key_data(sub))
            _, (carries, subs) = jax.lax.scan(sp, kd0, None, length=K)
            return carries, subs

        carries, subs = jax.vmap(chain)(key_data)
        node_subs = subs if B == 1 else subs[:, depths_np]
        t = jax.vmap(jax.vmap(
            lambda lg, sd: sample_logits(
                lg[None], jax.random.wrap_key_data(sd), gen)[0]))(
                    logits, node_subs)                     # [S, Q]

        # 4) accept the longest matching root-to-leaf path + 1
        # correction token. Any branch whose first L levels match
        # carries exactly the sequential chain's tokens, so ties agree
        # on every emitted token and argmax's first-max pick is safe.
        if B == 1:
            t_lin = t
            match = (drafts[:, 0, :] == t[:, :K - 1])
            lead = jnp.cumprod(match.astype(jnp.int32), axis=1)
            n_emit = jnp.int32(1) + jnp.sum(lead, axis=1)
        else:
            tb = t[:, 1:].reshape(S, B, K - 1)
            prev = jnp.concatenate(
                [jnp.broadcast_to(t[:, :1, None], (S, B, 1)),
                 tb[:, :, :-1]], axis=2)
            lead_b = jnp.cumprod(
                (drafts == prev).astype(jnp.int32), axis=2)
            len_b = jnp.sum(lead_b, axis=2)                # [S, B]
            bsel = jnp.argmax(len_b, axis=1).astype(jnp.int32)
            n_emit = jnp.int32(1) + jnp.take_along_axis(
                len_b, bsel[:, None], axis=1)[:, 0]
            t_lin = jnp.concatenate(
                [t[:, :1],
                 jnp.take_along_axis(
                     tb, bsel[:, None, None], axis=1)[:, 0]], axis=1)
        n_emit = jnp.where(done, jnp.int32(0), n_emit)
        emit_mask = ar[None, :] < n_emit[:, None]
        toks_out = jnp.where(emit_mask, t_lin,
                             jnp.int32(gen.pad_token_id))

        if B > 1:
            # relocate the winning branch's K-1 chunk rows to the
            # canonical rows [pos+1, pos+K): rows at or beyond the
            # advanced pos' are junk-allowed (causally masked, and the
            # next round's Q-row write covers them), so the whole
            # branch copies unconditionally.
            # In place, a slot at a time, as a layer writes its rows
            # (slab rows and paged view rows alike): no copy of the
            # carried cache is made or transposed.
            def rl(a):                              # [L, S, rows, C]
                for s in range(S):
                    rows = jax.lax.dynamic_slice(
                        a, (0, s, pos[s] + 1 + bsel[s] * (K - 1), 0),
                        (a.shape[0], 1, K - 1, a.shape[3]))
                    a = jax.lax.dynamic_update_slice(
                        a, rows, (0, s, pos[s] + 1, 0))
                return a

            caches = jax.tree_util.tree_map(rl, caches)

        # 5) advance — done slots frozen (pos/key/hist/budget untouched)
        last = jnp.take_along_axis(
            t_lin, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
        tok = jnp.where(done, tok, last)

        def hupd(hrow, p, trow, n):
            cur = jax.lax.dynamic_slice(hrow, (p + 1,), (K,))
            upd = jnp.where(ar < n, trow, cur)
            return jax.lax.dynamic_update_slice(hrow, upd, (p + 1,))

        hist = jax.vmap(hupd)(hist, pos, t_lin, n_emit)
        sel = jnp.concatenate([key_data[:, None], carries], axis=1)
        key_data = jax.vmap(lambda s, n: s[n])(sel, n_emit)
        pos = pos + n_emit
        budget = budget - n_emit
        done = done | (budget <= 0)
        if eos is not None:
            done = done | jnp.any(
                (t_lin == jnp.int32(eos)) & emit_mask, axis=1)
        return ((caches, tok, pos, key_data, hist, done, budget),
                toks_out, n_emit, None)

    # -- backend API -------------------------------------------------------

    def prefill(self, slot: int, prompt: Sequence[int], seed: int,
                max_new_tokens: Optional[int] = None):
        """Fill slot ``slot``'s cache rows from ``prompt`` and arm the
        slot, all on the device: the programs are dispatched and nothing
        is read back. Returns the first sampled token as the 0-d device
        array the program gave; ``int()`` of it waits for the program,
        and that moment is the request's first token on the host (the
        engine reads it once the tick's decode launch is in the queue).
        The slot's (token, position, key) and draft history are written
        by the program itself (:meth:`_arm`), so a ``decode`` may follow
        at once. A model that generates by blocks has no first token
        here: its prefill caches the prompt's whole blocks, arms the slot
        with the tail (:meth:`_arm_block`) and returns None. Slab mode: one program per prompt-length bucket. Paged
        mode: ONE chunked program regardless of length;
        ``max_new_tokens`` sizes the block reservation (defaults to the
        engine cap — full-demand reservation means no mid-decode OOM)."""
        reg = get_registry()
        if self.spec_tokens is not None:
            # adaptive-K starts each request optimistic: full draft depth
            # until its own acceptance says otherwise
            self._spec_ewma[slot] = float(self.spec_tokens)
        if self.paged:
            return self._prefill_paged(
                slot, prompt, seed,
                max_new_tokens if max_new_tokens is not None
                else self.gen.max_new_tokens)
        if self.buckets is not None:
            padded, p = self.buckets.pad(prompt, self.gen.pad_token_id)
        else:
            padded, p = list(prompt), len(prompt)
        B = len(padded)
        with ev.span(ev.SERVE_PREFILL, slot=slot, prompt_len=p, bucket=B):
            tok0 = self._prefill_slab(reg, slot, padded, prompt, seed)
        self._count_prompt(reg, p, B)
        return tok0

    @staticmethod
    def _count_prompt(reg, prompt_len: int, padded_len: int) -> None:
        """What the ``serve.prefill`` span carries, for operators without
        a profiler: real prompt tokens and the rows the programs ran."""
        reg.counter("serve.engine.prompt_tokens").inc(prompt_len)
        reg.counter("serve.engine.padded_prompt_tokens").inc(padded_len)

    def _prefill_slab(self, reg, slot: int, padded, prompt, seed: int):
        """Dispatch the bucket's program on the padded prompt."""
        B = len(padded)
        run = self._prefill_programs.get(B)
        if run is None:
            reg.counter("serve.engine.prefill_program_misses").inc()
            run = self._prefill_programs[B] = self._prefill_jit()
            reg.gauge("serve.engine.prefill_programs").set(
                len(self._prefill_programs))
            if self.buckets is None and \
                    len(self._prefill_programs) == self.shape_cache_warn + 1:
                import warnings
                warnings.warn(
                    f"serve engine compiled "
                    f"{len(self._prefill_programs)} distinct prefill "
                    f"programs with bucketing DISABLED — every new "
                    f"prompt length recompiles. Pass a BucketSpec to cap "
                    f"the program cache.", RuntimeWarning, stacklevel=4)
        else:
            reg.counter("serve.engine.prefill_program_hits").inc()
        self._caches, state, tok0 = run(
            *self._prefill_args(slot, padded, prompt, seed))
        self._put_slot_state(state)
        return tok0

    def _prefill_jit(self):
        """A slab prefill program. Donated: the slab and the slots'
        state, both written in place."""
        return jax.jit(self._prefill_fn, donate_argnums=(3, 4))

    def _prefill_args(self, slot: int, padded, prompt, seed: int):
        """The prefill program's arguments at this backend's state.
        Prompt, length, slot and seed go in as host values, inside the
        one call: no device program runs for any of them on its own."""
        return (self._block_stack, self._pre, self._post, self._caches,
                self._slot_state(), np.asarray(padded, np.int32)[None, :],
                *self._arm_args(slot, prompt, seed))

    def prefill_program(self, bucket: int):
        """For tests and audits: a slab prefill program as ``(jitted
        function, its arguments at this backend's sizes)`` for a prompt
        that fills ``bucket``, to ``.lower(*args)``."""
        prompt = [self.gen.pad_token_id] * bucket
        return self._prefill_jit(), self._prefill_args(0, prompt, prompt, 0)

    def _slot_state(self):
        """What :meth:`_arm` writes a slot's entries of, donated to the
        admission's program and taken back from it."""
        return self._tok, self._pos, self._key_data, self._hist

    def _put_slot_state(self, state) -> None:
        self._tok, self._pos, self._key_data, self._hist = state

    def _arm_args(self, slot: int, prompt: Sequence[int], seed: int):
        """The host's part of arming ``slot``, as the trailing arguments
        of the admission's program: ``(true_len, slot, seed, row)``,
        plain host values. The seed is the scalar ``jax.random.key``
        itself makes of a Python int (an int64, which wraps to 32 bits
        without x64), so the key made in the program is its key bit for
        bit. ``row`` is the slot's draft history before its first token
        (hist[s, p] = the token embedded at position p: the prompt, pad
        beyond), None where nothing drafts."""
        row = None
        if self.spec_tokens is not None:
            row = np.full((self._hist.shape[1],), self.gen.pad_token_id,
                          np.int32)
            row[:len(prompt)] = prompt
        return (np.int32(len(prompt)), np.int32(slot),
                np.int64(seed).astype(jax.dtypes.canonicalize_dtype(
                    np.int64)), row)

    def _prefill_paged(self, slot: int, prompt: Sequence[int], seed: int,
                       max_new_tokens: int):
        """Admit into the pool (reserving full demand), run the COW
        forks, stream the prompt's recompute tail through the one chunk
        program, sample the first token with the Generator key chain and
        arm the slot. A failure mid-stream releases the reservation and
        unpublishes any half-written cache entries."""
        plen = len(prompt)
        with ev.span(ev.SERVE_PREFILL, slot=slot, prompt_len=plen) as sp:
            adm = self.pool.admit(slot, prompt, max_new_tokens,
                                  chunk=self.prefill_chunk)
            try:
                for dst, payload in adm.restores:
                    # offloaded prefix blocks this admission reuses come
                    # back from the host store BEFORE any fork/chunk writes;
                    # the regather armed below refreshes the decode views —
                    # no extra host decision per tick
                    self._pool_kv = self._restore_jit(
                        self._pool_kv, np.int32(dst), payload)
                for src, dst in adm.cow_forks:
                    self._pool_kv = self._fork_jit(
                        self._pool_kv, np.int32(src), np.int32(dst))
                trow = np.asarray(adm.table)
                C = self.prefill_chunk
                pad = self.gen.pad_token_id
                t = adm.resume_from
                # the rows the chunk program runs for this prompt
                rows = -(-(plen - t) // C) * C
                sp.set_metadata(bucket=rows)
                h_last = None
                while t < plen:
                    toks = list(prompt[t:t + C])
                    toks += [pad] * (C - len(toks))
                    self._pool_kv, h_last = self._chunk_jit(
                        self._block_stack, self._pre, self._pool_kv, trow,
                        np.asarray(toks, np.int32)[None, :], np.int32(t),
                        np.int32(plen))
                    t += C
                state, tok0 = self._sample_jit(
                    self._post, h_last, self._slot_state(),
                    *self._arm_args(slot, prompt, seed))
            except Exception:
                self.pool.release(slot, failed=True)
                raise
            self._put_slot_state(state)
            # this slot's table moved: arm the device-side regather flag
            # — the ONE host gather decision per admission (counted here;
            # steady-state ticks make zero)
            self._regather = jnp.asarray(True)
            get_registry().counter("serve.kv.regather_host_decisions").inc()
        self._count_prompt(get_registry(), plen, rows)
        return tok0

    def _decode_args(self, live, budget, r_max):
        """The decode program's arguments at this backend's state."""
        kv, aux = self._store.take()
        return (self._block_stack, self._pre, self._post, kv, aux,
                self._tok, self._pos, self._key_data, self._hist, live,
                budget, r_max)

    def decode_program(self, k: Optional[int] = None):
        """For tests and audits: THE decode program (round width ``k``,
        a ladder rung; default the current one) as ``(jitted function,
        its arguments at this backend's sizes)``, to ``.lower(*args)``
        or ``jax.make_jaxpr``."""
        live = jnp.ones((self.num_slots,), bool)
        args = self._decode_args(live, live.astype(jnp.int32),
                                 jnp.int32(self.resident_chunks))
        return self._resident_jits[k or self.decode_width], args

    def decode(self, live: np.ndarray,
               budgets: Optional[np.ndarray] = None,
               r_max: Optional[int] = None,
               launched: Optional[Callable[[], None]] = None):
        """One launch of the decode program for all slots: up to
        ``r_max`` rounds (default and at most ``resident_chunks``) on
        device under the per-slot ``budgets`` (remaining
        max_new_tokens), ONE host sync (the round count) to size the
        readout. Returns ``(tokens [S, k*width], valid [S, k*width])``
        — dead slots are masked out by ``valid``; what they write is
        rewritten at the next prefill — or, paged, lands in the
        sacrificial block. A launch in which a live slot starts done
        (its first token eos, its budget spent) runs no round and
        returns zero columns. A noted round's notes of those tokens
        stand in ``launch_notes`` ``[S, k*width]`` afterwards. Without
        ``budgets`` the same program runs one round with no budget limit. ``launched`` is called once the
        launch is in the device's queue and before the host waits for
        it: the caller's moment for what may wait on earlier programs
        (the engine reads its admissions' first tokens there)."""
        reg = get_registry()
        R = self.resident_chunks
        if budgets is None:
            budgets = np.full((self.num_slots,), np.iinfo(np.int32).max)
            r_max = 1
        rm = R if r_max is None else max(1, min(int(r_max), R))
        live_d = np.asarray(live, bool)
        budget = np.asarray(budgets, np.int32)
        if self.spec_tokens is not None:
            self.decode_width = self._pick_spec_k(live)
        with ev.span(ev.SERVE_DECODE_LAUNCH, chunks=rm):
            kv, tok, pos, kd, hist, buf, counts, k, notes = \
                self._resident_jits[self.decode_width](
                    *self._decode_args(live_d, budget, np.int32(rm)))
            self._store.put(kv)
        t_dispatched = self.clock()
        self._put_slot_state((tok, pos, kd, hist))
        if launched is not None:
            launched()
        W = self.decode_width
        t_sync = self.clock()
        with ev.span(ev.SERVE_DECODE_SYNC):
            with ev.span(ev.SERVE_DECODE_WAIT) as wait:
                k = int(k)                     # THE host sync
                wait.set_metadata(rounds=k)
            t_counted = self.clock()
            with ev.span(ev.SERVE_DECODE_FETCH) as fetch:
                fetched = [np.asarray(buf), np.asarray(counts)]
                if notes is not None:          # a noted round's
                    fetched.append(np.asarray(notes))
                if self._groups is not None:   # and the layers' counts
                    fetched.append(np.asarray(self._caches["counts"]))
                fetch.set_metadata(reads=len(fetched),
                                   bytes=sum(a.nbytes for a in fetched))
            self.launch_phases = LaunchPhases(
                t_dispatched, t_sync, t_counted, self.clock(), k)
            buf, counts = fetched[:2]
            if notes is not None:
                self.launch_notes = fetched[2][:, :k * W]
            if self._groups is not None:
                self._land_counts(reg, fetched[-1])
        if k < rm:
            reg.counter("serve.engine.device_exits").inc()
        toks = buf[:, :k * W]
        counts = counts[:, :k]
        valid = (np.arange(W)[None, None, :]
                 < counts[:, :, None]).reshape(self.num_slots, k * W)
        if self.spec_tokens is not None:
            lmask = np.asarray(live, bool)
            lc = counts[lmask]
            rounds = int((lc > 0).sum())
            emitted = int(lc.sum())
            reg.counter("serve.engine.spec_rounds").inc(rounds)
            reg.counter("serve.engine.spec_emitted").inc(emitted)
            # spec telemetry: acceptance = accepted draft tokens over
            # drafted positions (K-1 per round), cumulative; per-round
            # accepted-length histogram (log2 buckets downstream);
            # draft cost as the drafter's work-unit prediction at the
            # rung that ran
            self._spec_acc_total += max(emitted - rounds, 0)
            self._spec_draft_total += rounds * (W - 1)
            if self._spec_draft_total:
                reg.gauge("serve.spec.acceptance_rate").set(
                    self._spec_acc_total / self._spec_draft_total)
            reg.gauge("serve.spec.draft_cost_frac").set(
                self._drafter.draft_cost_frac(W, self._n_layers))
            hist_m = reg.histogram("serve.spec.accept_len")
            for v in lc[lc > 0]:
                hist_m.observe(float(v))
            # adaptive-K: per-slot EWMA of accepted length feeds the
            # next launch's rung pick (shrink when drafts miss, grow
            # back when they land)
            if len(self._spec_ladder) > 1:
                rc = np.maximum((counts > 0).sum(axis=1), 1)
                mean_acc = counts.sum(axis=1) / rc
                upd = lmask & (counts.sum(axis=1) > 0)
                self._spec_ewma[upd] = (0.7 * self._spec_ewma[upd]
                                        + 0.3 * mean_acc[upd])
        return toks, valid

    def _land_counts(self, reg, counts: np.ndarray) -> None:
        """The device's running counts as this launch left them: what
        grew since the last launch goes to the ``serve.<name>`` counters
        (prefill programs and decode steps together) and, split, to
        ``launch_counts``, which the engine writes into the launch's
        ``serve.decode.done`` span: the decode program's under each
        count's own name, the prefill programs' since the last launch
        under ``prefill_<name>``."""
        now = counts.astype(np.uint32)
        grew = (now - self._counts_seen).astype(np.int64)   # wraps right
        self._counts_seen = now
        self.launch_counts = {}
        for j, name in enumerate(self._count_names):
            short = name.split(".", 1)[1]
            self.launch_counts[short] = int(grew[COUNT_DECODE, j])
            if name in self._layer_counts:
                self.launch_counts["prefill_" + short] = int(
                    grew[COUNT_PREFILL, j])
            if grew[:, j].any():
                reg.counter(f"serve.{name}").inc(int(grew[:, j].sum()))

    def _pick_spec_k(self, live: np.ndarray) -> int:
        """Smallest pre-traced ladder rung covering the live slots'
        accepted-length EWMA (plus one probe token so acceptance can
        grow back). Single-rung ladders — the non-adaptive default —
        short-circuit to ``spec_tokens``."""
        ladder = self._spec_ladder
        if len(ladder) == 1:
            return ladder[0]
        lmask = np.asarray(live, bool)
        if not lmask.any():
            return ladder[0]
        need = int(np.ceil(self._spec_ewma[lmask].max())) + 1
        need = max(2, min(need, self.spec_tokens))
        for k in ladder:
            if k >= need:
                return k
        return ladder[-1]

    def can_admit(self, prompt_len: int, max_new_tokens: int,
                  prompt: Optional[Sequence[int]] = None) -> bool:
        """Block-availability admission gate (always True for the slab —
        its reservation is the slot itself)."""
        if not self.paged:
            return True
        return self.pool.can_admit(prompt_len, max_new_tokens, prompt,
                                   chunk=self.prefill_chunk)

    def release(self, slot: int) -> None:
        """Engine retirement hook: return the slot's blocks to the pool
        (no-op for the slab — the next prefill rewrites the rows)."""
        if self.paged:
            self.pool.release(slot)

    def program_stats(self) -> dict:
        if self.paged:
            return {"prefill_programs": 1, "decode_chunk": self.decode_chunk,
                    "kv": "paged"}
        return {"prefill_programs": len(self._prefill_programs),
                "decode_chunk": self.decode_chunk, "kv": "slab"}

    # -- KV handoff (fleet session remap) ----------------------------------

    def export_prefix_payload(self, prompt: Sequence[int],
                              codec: str = "int8") -> Optional[dict]:
        """Serialize this backend's cached shared-prefix blocks covering
        ``prompt`` for a fleet KV handoff. ``codec="raw"`` ships the
        pool's stored bytes exactly (in-process handoff — bitwise, so
        prefix hits on the destination preserve token parity);
        ``codec="int8"`` quantizes float rows through
        :func:`~..inference.quant.quantize_kv_rows` for the wire (int8
        pools are already their own int8 path and ship raw either way).
        Returns None when there is no pool or no cached prefix."""
        if not self.paged:
            return None
        if codec not in ("raw", "int8"):
            raise ValueError(f"codec must be raw|int8, got {codec!r}")
        entries = self.pool.cached_prefix_entries(prompt)
        if not entries:
            return None
        bids = jnp.asarray([b for _, b in entries], jnp.int32)
        int8_storage = "k_scale" in self._pool_kv
        arrays = {}
        if int8_storage or codec == "raw":
            names = (("k", "v", "k_scale", "v_scale") if int8_storage
                     else ("k", "v"))
            for name in names:
                arrays[name] = np.asarray(
                    jnp.take(self._pool_kv[name], bids, axis=1))
            wire_codec = "raw"
        else:
            from ..inference.quant import quantize_kv_rows
            for name in ("k", "v"):
                q, s = quantize_kv_rows(
                    jnp.take(self._pool_kv[name], bids, axis=1))
                arrays[name] = np.asarray(q)
                arrays[name + "_scale"] = np.asarray(s)
            wire_codec = "int8"
        nbytes = sum(a.nbytes for a in arrays.values())
        get_registry().counter("serve.kv.prefix_exported").inc(len(entries))
        return {"hashes": [h for h, _ in entries],
                "block_size": self.pool.block_size,
                "n_layers": self._n_layers,
                "codec": wire_codec,
                "int8_storage": int8_storage,
                "arrays": arrays,
                "nbytes": nbytes}

    def import_prefix_payload(self, payload: dict) -> int:
        """Seat an exported prefix payload into this backend's pool:
        allocate destination blocks, write the rows onto the device
        arrays, and register the hashes as refs-0 cached entries (the
        next admission takes the refs). Hashes already cached locally
        are skipped; returns the number of blocks actually seated (0
        for slab backends or a geometry mismatch — a handoff between
        heterogeneous pools is a silent no-op, not an error: the
        destination simply re-prefills cold)."""
        if not self.paged:
            return 0
        if (payload.get("block_size") != self.pool.block_size
                or payload.get("n_layers") != self._n_layers):
            return 0
        int8_storage = "k_scale" in self._pool_kv
        fresh = [(i, h) for i, h in enumerate(payload["hashes"])
                 if h not in self.pool._cached]
        if not fresh:
            return 0
        dst = self.pool.take_blocks(len(fresh))
        fresh = fresh[:len(dst)]
        if not fresh:
            return 0
        src_idx = jnp.asarray([i for i, _ in fresh], jnp.int32)
        dst_idx = jnp.asarray(dst, jnp.int32)
        arrays = payload["arrays"]
        codec = payload.get("codec", "raw")
        if codec == "raw" and payload.get("int8_storage") == int8_storage:
            names = (("k", "v", "k_scale", "v_scale") if int8_storage
                     else ("k", "v"))
            for name in names:
                rows = jnp.take(jnp.asarray(arrays[name]), src_idx, axis=1)
                self._pool_kv[name] = self._pool_kv[name].at[
                    :, dst_idx].set(rows.astype(self._pool_kv[name].dtype))
        else:
            # cross-codec: materialize float rows, then store in this
            # pool's own layout (re-quantizing for int8 storage)
            from ..inference.quant import quantize_kv_rows
            for name in ("k", "v"):
                rows = jnp.take(jnp.asarray(arrays[name]), src_idx, axis=1)
                if codec == "int8" or payload.get("int8_storage"):
                    scale = jnp.take(
                        jnp.asarray(arrays[name + "_scale"]), src_idx,
                        axis=1)
                    rows = rows.astype(jnp.float32) * scale
                if int8_storage:
                    q, s = quantize_kv_rows(rows)
                    self._pool_kv[name] = \
                        self._pool_kv[name].at[:, dst_idx].set(q)
                    sa = self._pool_kv[name + "_scale"]
                    self._pool_kv[name + "_scale"] = \
                        sa.at[:, dst_idx].set(s)
                else:
                    self._pool_kv[name] = self._pool_kv[name].at[
                        :, dst_idx].set(
                            rows.astype(self._pool_kv[name].dtype))
        seated = self.pool.seat_prefix(
            [(h, int(b)) for (_, h), b in zip(fresh, dst)],
            chain=payload["hashes"])
        get_registry().counter("serve.kv.prefix_imported").inc(seated)
        return seated


class ServeEngine:
    """The continuous-batching scheduler over a slot backend.

    ``backend`` is a :class:`SingleDeviceSlotBackend` or
    :class:`~.ring.RingSlotBackend`; the engine itself is pure host-side
    bookkeeping (single-threaded tick loop — call ``tick`` from one
    thread). ``queue`` defaults to a fresh bounded
    :class:`~.queue.RequestQueue`; pass your own to share a front door
    or to inject a test clock.

    ``watchdog`` (a :class:`~..resilience.TickWatchdog`) arms the
    host-side health policies — slow-tick accounting, stuck-slot
    retirement, degraded-mode shedding; None (default) changes nothing.
    ``chaos`` (a :class:`~..resilience.ChaosPlan`) injects serve-side
    faults by tick index for the chaos bench/tests. A backend exception
    is contained, never fatal: a failed prefill retires only the
    offending request (``status="error"``, the slot goes back to the
    free list, ``resilience.slot_errors`` counts it); a failed decode
    skips the tick with all slot state intact, and only after
    ``decode_error_limit`` consecutive failures are the live slots
    retired as errors (batched decode cannot attribute the fault to one
    slot).
    """

    def __init__(self, backend, queue: Optional[RequestQueue] = None,
                 *, event_log=None,
                 clock: Optional[Callable[[], float]] = None,
                 watchdog=None, chaos=None, decode_error_limit: int = 3,
                 phase: str = "mixed"):
        if phase not in ("mixed", "prefill", "decode"):
            raise ValueError(
                f"phase must be 'mixed', 'prefill' or 'decode', got "
                f"{phase!r}")
        self.phase = phase
        self.backend = backend
        if queue is None:
            queue = RequestQueue(clock=clock or time.monotonic)
        elif clock is not None and clock is not queue.clock:
            raise ValueError(
                "pass the clock on the queue (engine adopts queue.clock)")
        if decode_error_limit < 1:
            raise ValueError(
                f"decode_error_limit must be >= 1, got {decode_error_limit}")
        self.queue = queue
        self.clock = queue.clock
        self.events = event_log if event_log is not None else NULL_EVENT_LOG
        self.watchdog = watchdog
        self.chaos = chaos
        self.decode_error_limit = decode_error_limit
        self._slots: List[Optional[_Slot]] = [None] * backend.num_slots
        self._free = list(range(backend.num_slots - 1, -1, -1))
        self._responses = {}
        self._tick_index = 0
        self._decode_errors = 0
        # the newest contained backend exception (prefill or decode):
        # containment keeps serving, callers that must fail read this
        self.last_error: Optional[Exception] = None
        self._miss_ewma = 0.0
        self._draining = False
        # observed per-chunk decode latency (EWMA) — sizes the resident
        # deadline horizon in chunks; None until the first decode
        self._chunk_ewma: Optional[float] = None
        # the launch cycle (``events.CYCLE_PHASES``): the open cycle, when
        # ``tick`` last returned, and the last 32 phases that stalled
        if hasattr(backend, "launch_phases"):
            backend.clock = self.clock
        self._cycle: Optional[_Cycle] = None
        self._t_left: Optional[float] = None
        self.slow_cycles: collections.deque = collections.deque(maxlen=32)

    # -- front door --------------------------------------------------------

    def submit(self, prompt: Sequence[int], *,
               max_new_tokens: Optional[int] = None, seed: int = 0,
               priority: int = 0,
               timeout_s: Optional[float] = None) -> Request:
        """Validate + enqueue. Raises ``ValueError`` on an unservable
        request (too long for the buckets/cache/positions) and
        :class:`~.queue.QueueFull` under backpressure."""
        reg = get_registry()
        if self._draining:
            raise EngineDraining(
                "engine is draining: live requests are finishing and no "
                "new work is admitted")
        if max_new_tokens is None:
            max_new_tokens = self.backend.gen.max_new_tokens
        self._check_phase(prompt, max_new_tokens)
        self.backend.validate(len(prompt), max_new_tokens)
        try:
            req = self.queue.submit(prompt, max_new_tokens=max_new_tokens,
                                    seed=seed, priority=priority,
                                    timeout_s=timeout_s)
        except QueueFull:
            reg.counter("serve.engine.rejected").inc()
            raise
        reg.counter("serve.engine.submitted").inc()
        reg.gauge("serve.engine.queue_depth").set(self.queue.depth)
        return req

    def place(self, req: Request) -> Request:
        """Router placement: admit an EXISTING :class:`~.queue.Request`
        into this engine's queue, preserving its id, arrival and
        deadline (no new deadline credit) and counting the placement in
        ``req.attempts`` — the router's retry-budget ledger. Raises
        like ``submit`` (:class:`EngineDraining`, ``ValueError``,
        :class:`~.queue.QueueFull`)."""
        reg = get_registry()
        if self._draining:
            raise EngineDraining(
                "engine is draining: live requests are finishing and no "
                "new work is admitted")
        self._check_phase(req.prompt, req.max_new_tokens)
        self.backend.validate(len(req.prompt), req.max_new_tokens)
        self.queue.requeue(req)
        req.attempts += 1
        reg.gauge("serve.engine.queue_depth").set(self.queue.depth)
        return req

    def _check_phase(self, prompt: Sequence[int],
                     max_new_tokens: int) -> None:
        """Disaggregated operating modes (fleet/disagg.py). A prefill
        replica serves ONLY the chunked-prefill program: requests must
        arrive clamped to ``max_new_tokens=1`` (the first token retires
        the slot straight off the prefill, leaving the prompt's prefix
        blocks cached for export). A decode replica never prefills from
        scratch: a prompt spanning at least one full KV block must have
        its prefix already seated (``import_prefix_payload``) so the
        admission prefill merely resumes from the cached frontier, and
        the imported-prefix length must fit the decode slot span
        (:meth:`~...inference.generate.GenerationConfig.check_decode_headroom`).
        Mixed mode (default) changes nothing."""
        if self.phase == "prefill" and max_new_tokens != 1:
            raise ValueError(
                f"prefill-only replica: requests must arrive clamped to "
                f"max_new_tokens=1, got {max_new_tokens} — route the "
                f"decode phase to a decode or mixed replica "
                f"(fleet/disagg.py owns the split)")
        if self.phase == "decode":
            pool = getattr(self.backend, "pool", None)
            if pool is not None:
                buckets = getattr(self.backend, "buckets", None)
                if buckets is not None:
                    self.backend.gen.check_decode_headroom(
                        len(prompt), max_new_tokens, buckets.max_len,
                        getattr(self.backend, "_spec_overshoot", 0))
                if (len(prompt) >= pool.block_size
                        and pool.cached_prefix_blocks(prompt) == 0):
                    raise ValueError(
                        f"decode-only replica: no cached KV prefix for "
                        f"this {len(prompt)}-token prompt — import the "
                        f"prefill replica's blocks first "
                        f"(import_prefix_payload) or route to a mixed "
                        f"replica; decode replicas never re-prefill")

    def cancel(self, request_id: int) -> bool:
        return self.queue.cancel(request_id)

    def response(self, request_id: int) -> Optional[Response]:
        return self._responses.get(request_id)

    @property
    def live_slots(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    @property
    def idle(self) -> bool:
        return self.live_slots == 0 and self.queue.depth == 0

    @property
    def consecutive_decode_errors(self) -> int:
        """Consecutive failed decode ticks (reset by any success) — a
        fleet-health signal the router reads alongside the watchdog
        properties; ``decode_error_limit`` of these retires the live
        set."""
        return self._decode_errors

    # -- graceful drain ------------------------------------------------------

    def drain(self) -> None:
        """Enter graceful shutdown: ``submit`` starts raising
        :class:`EngineDraining`, the next tick sheds everything still
        queued (``status="shed"``, ``finish_reason="drain"``), and live
        slots run to completion. Idempotent."""
        if not self._draining:
            self._draining = True
            self.events.event("resilience", action="drain",
                              live=self.live_slots, queued=self.queue.depth)

    def evict_queued(self) -> List[Request]:
        """Remove and return this engine's queued requests INTACT — no
        terminal record, no status change — so a router can re-place
        them on a healthy replica. Live slots are untouched. Contrast
        :meth:`drain`, which sheds queued work terminally
        (``finish_reason="drain"``)."""
        evicted = self.queue.evict_all()
        if evicted:
            reg = get_registry()
            reg.counter("serve.engine.evicted").inc(len(evicted))
            reg.gauge("serve.engine.queue_depth").set(self.queue.depth)
            self.events.event("resilience", action="evict_queued",
                              count=len(evicted))
        return evicted

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def drained(self) -> bool:
        """True once a drain finished: nothing queued, nothing live."""
        return self._draining and self.idle

    # -- retirement --------------------------------------------------------

    def _record(self, resp: Response, bucket: Optional[int],
                req: Optional[Request] = None) -> None:
        self._responses[resp.request_id] = resp
        self.queue.forget(resp.request_id)
        reg = get_registry()
        reg.counter("serve.engine.retired").inc()
        reg.histogram("serve.engine.e2e_sec").observe(resp.latency)
        if resp.status == "timeout":
            reg.counter("serve.engine.timed_out").inc()
        elif resp.status == "cancelled":
            reg.counter("serve.engine.cancelled").inc()
        elif resp.status == "error":
            reg.counter("serve.engine.errors").inc()
        elif resp.status == "shed":
            reg.counter("serve.engine.shed").inc()
        wd = self.watchdog
        if wd is not None and resp.status in ("ok", "timeout"):
            # only served outcomes move the deadline-miss EWMA: shedding
            # is the *response* to misses and must not latch degraded mode
            self._miss_ewma = wd.record_outcome(resp.status == "timeout")
            reg.gauge("resilience.deadline_miss_ewma").set(self._miss_ewma)
        self.events.event(
            REQUEST, request=resp.request_id, status=resp.status,
            finish_reason=resp.finish_reason, prompt_len=resp.prompt_len,
            bucket=bucket, tokens=len(resp.tokens), ttft=resp.ttft,
            latency=resp.latency, stage="terminal",
            trace=getattr(req, "trace_id", None),
            attempts=getattr(req, "attempts", 0))

    def _finish_queued(self, req: Request, reason: str,
                       now: float) -> Response:
        status = "cancelled" if reason == "cancelled" else "timeout"
        resp = Response(request_id=req.id, tokens=[], status=status,
                        finish_reason=reason, prompt_len=len(req.prompt),
                        ttft=None, latency=now - req.submitted_at)
        self._record(resp, None, req)
        return resp

    def _shed_queued(self, req: Request, reason: str,
                     now: float) -> Response:
        """Queued request pushed back out unserved (degraded-mode
        shedding or drain): ``status="shed"``."""
        resp = Response(request_id=req.id, tokens=[], status="shed",
                        finish_reason=reason, prompt_len=len(req.prompt),
                        ttft=None, latency=now - req.submitted_at)
        self._record(resp, None, req)
        return resp

    def _fail_queued(self, req: Request, exc: Exception,
                     now: float) -> Response:
        """Admission failed in the backend (prefill raised): the request
        dies ``status="error"`` — the slot was returned to the free list
        and every other request keeps serving."""
        self.last_error = exc
        get_registry().counter("resilience.slot_errors").inc()
        self.events.event("resilience", action="slot_error",
                          request=req.id, where="prefill",
                          error=type(exc).__name__)
        resp = Response(request_id=req.id, tokens=[], status="error",
                        finish_reason="backend_error",
                        prompt_len=len(req.prompt),
                        ttft=None, latency=now - req.submitted_at)
        self._record(resp, None, req)
        return resp

    def _retire(self, slot: int, status: str, reason: str,
                now: float) -> Response:
        st = self._slots[slot]
        self._slots[slot] = None
        self._free.append(slot)
        rel = getattr(self.backend, "release", None)
        if rel is not None:
            rel(slot)
        req = st.req
        bucket = (self.backend.buckets.bucket_for(len(req.prompt))
                  if self.backend.buckets is not None else len(req.prompt))
        resp = Response(request_id=req.id, tokens=list(st.tokens),
                        status=status, finish_reason=reason,
                        prompt_len=len(req.prompt), ttft=st.ttft,
                        latency=now - req.submitted_at,
                        reveal_pass=list(st.notes) if st.notes else None)
        self._record(resp, bucket, req)
        return resp

    # -- the tick ----------------------------------------------------------

    def tick(self) -> List[Response]:
        """One scheduler step: sweep deadlines/cancellations, apply the
        watchdog policies, admit into free slots, launch the decode,
        retire. Returns the requests that reached a terminal state
        during this tick."""
        t_in = self.clock()
        away = 0.0 if self._t_left is None else t_in - self._t_left
        with self.events.span(ev.SERVE_TICK, tick=self._tick_index,
                              live=self.live_slots,
                              queued=self.queue.depth, away_ms=1e3 * away):
            finished = self._tick(away)
        self._t_left = self.clock()
        return finished

    def _stalled(self, tick_idx: int, phase: str, wall: float,
                 cpu0: float) -> None:
        """A phase of the launch cycle stood over its threshold: keep it
        in ``slow_cycles`` and have it counted and named once
        (``telemetry.record_stall``), with the process's CPU seconds since
        ``cpu0``."""
        slow = SlowCycle(tick_idx, phase, wall, time.process_time() - cpu0)
        self.slow_cycles.append(slow)
        record_stall(get_registry(), "serve.engine",
                     f"serve engine: tick {tick_idx}", phase, wall,
                     slow.cpu_s)

    def _launch_dispatched(self, tick_idx: int, t0: float, t1: float,
                           cpu0: float, chunks: int) -> float:
        """A launch came back: close the cycle its dispatch ends (the four
        timers, which so add up to the time between dispatches; ``turn`` is
        what the other three leave) and open its own, whose ``wait`` and
        ``fetch`` are known. A backend that stamps nothing gives the whole
        call as ``wait``. Returns the dispatch's time."""
        reg = get_registry()
        ph = getattr(self.backend, "launch_phases", None) \
            or LaunchPhases(t0, t0, t1, t1, chunks)
        old = self._cycle
        if old is not None:
            turn = (ph.dispatched - old.dispatched - old.wait - old.fetch
                    - old.caller)
            for phase, sec in zip(ev.CYCLE_PHASES, (old.wait, old.fetch,
                                                    turn, old.caller)):
                reg.timer(f"serve.engine.cycle.{phase}_sec").observe(sec)
            if turn > ev.STALL_SEC:
                self._stalled(tick_idx, "turn", turn, old.cpu0)
        new = self._cycle = _Cycle(
            ph.dispatched, ph.counted - ph.dispatched,
            ph.fetched - ph.counted, cpu0)
        # the wait proper, behind the first-token reads, against what the
        # rounds it ran take by the running mean (none before the first
        # launch, which compiles)
        ew = self._chunk_ewma
        if ew is not None and (ph.counted - ph.sync
                               > ev.STALL_SEC + 3 * ph.rounds * ew):
            self._stalled(tick_idx, "wait", ph.counted - ph.sync, cpu0)
        if new.fetch > ev.STALL_SEC:
            self._stalled(tick_idx, "fetch", new.fetch, cpu0)
        return ph.dispatched

    def _tick(self, away: float) -> List[Response]:
        reg = get_registry()
        tick_idx = self._tick_index
        self._tick_index += 1
        if self._cycle is not None:
            self._cycle.caller += away
            if away > ev.STALL_SEC:
                self._stalled(tick_idx, "caller", away, self._cycle.cpu0)
        if self.chaos is not None:
            self._apply_chaos(reg, tick_idx)
        t_start = self.clock()
        now = t_start
        finished: List[Response] = []
        eos = self.backend.gen.eos_token_id
        wd = self.watchdog

        with self.events.span(ev.SERVE_REAP):
            # 0) drain — everything still queued goes back to its caller
            if self._draining and self.queue.depth:
                for req in self.queue.shed_lowest(self.queue.depth):
                    finished.append(self._shed_queued(req, "drain", now))

            # 1) deaths — queued first (never cost a slot), then running
            for req, reason in self.queue.reap(now):
                finished.append(self._finish_queued(req, reason, now))
            for slot in range(self.backend.num_slots):
                st = self._slots[slot]
                if st is None:
                    continue
                if st.req.cancelled:
                    finished.append(
                        self._retire(slot, "cancelled", "cancelled", now))
                elif st.req.deadline is not None and now >= st.req.deadline:
                    finished.append(
                        self._retire(slot, "timeout", "deadline", now))

            # 1b) stuck slots — alive far past the ticks their token budget
            # can possibly need; retire as errors instead of squatting
            if wd is not None and wd.stuck_slack_ticks is not None:
                chunk = getattr(self.backend, "decode_chunk", 1)
                for slot in range(self.backend.num_slots):
                    st = self._slots[slot]
                    if st is None:
                        continue
                    limit = wd.stuck_after(st.req.max_new_tokens, chunk)
                    if tick_idx - st.admitted_tick >= limit:
                        reg.counter("resilience.stuck_slots").inc()
                        wd.record_stuck()
                        self.events.event(
                            "resilience", action="stuck_slot",
                            request=st.req.id, slot=slot,
                            age_ticks=tick_idx - st.admitted_tick)
                        finished.append(
                            self._retire(slot, "error", "stuck", now))

            # 1c) degraded mode — shed lowest-priority queued work while the
            # deadline-miss EWMA sits above the threshold
            if wd is not None and wd.shed_ewma_threshold is not None \
                    and not self._draining \
                    and self._miss_ewma > wd.shed_ewma_threshold \
                    and self.queue.depth:
                n = max(1, self.queue.depth // 2)
                reg.counter("resilience.shed").inc(n)
                self.events.event("resilience", action="shed", count=n,
                                  miss_ewma=self._miss_ewma,
                                  queued=self.queue.depth)
                for req in self.queue.shed_lowest(n):
                    finished.append(self._shed_queued(req, "shed", now))

        # 2) admissions — prefill straight into the freed slots; a
        # backend failure here is attributable to ONE request: fail it,
        # free the slot, keep admitting. Paged backends gate on BLOCK
        # availability too: when the pool can't cover the head request's
        # demand, the head PARKS (it keeps its place; FIFO/priority
        # order is never rotated) but the scan tries the next request in
        # pop order — a small request behind a parked giant no longer
        # starves (serve.engine.admission_skipped counts the bypasses).
        device_sec = 0.0                    # prefill + decode launches
        pending: List[int] = []             # slots whose first token is due
        head_blocked_counted = False
        while self._free and not self._draining:
            can = getattr(self.backend, "can_admit", None)
            candidates = self.queue.admission_order()
            if not candidates:
                break
            req = None
            for cand in candidates:
                if can is None or can(len(cand.prompt),
                                      cand.max_new_tokens, cand.prompt):
                    req = cand
                    break
                if cand is candidates[0] and not head_blocked_counted:
                    head_blocked_counted = True
                    pool = getattr(self.backend, "pool", None)
                    detail = ({"blocks_free": pool.free_blocks,
                               "blocks_evictable": pool.evictable_blocks}
                              if pool is not None else {})
                    reg.counter("serve.kv.admission_blocked").inc()
                    self.events.event("serve", action="admission_blocked",
                                      request=cand.id,
                                      depth=self.queue.depth, **detail)
            if req is None:
                break                       # nothing admissible: park all
            if req is not candidates[0]:
                reg.counter("serve.engine.admission_skipped").inc()
                self.events.event("serve", action="admission_skipped",
                                  request=req.id,
                                  parked=candidates[0].id,
                                  depth=self.queue.depth)
            self.queue.take(req.id)
            slot = self._free.pop()
            t_pre = self.clock()
            with self.events.span(
                    ev.SERVE_ADMIT, request=req.id,
                    trace=req.trace_id or "", slot=slot,
                    prompt_len=len(req.prompt),
                    queued_ms=1e3 * (t_pre - req.submitted_at)):
                try:
                    if self.chaos is not None and self.chaos.serve_fault(
                            "backend_raise", tick_idx) is not None:
                        from ..resilience.chaos import ChaosError
                        raise ChaosError(
                            f"injected backend fault at tick {tick_idx}")
                    tok0 = self.backend.prefill(
                        slot, req.prompt, req.seed,
                        **self._prefill_kwargs(req))
                except Exception as e:       # noqa: BLE001 — containment
                    self._free.append(slot)
                    finished.append(
                        self._fail_queued(req, e, self.clock()))
                    continue
                device_sec += self.clock() - t_pre
                self._slots[slot] = _Slot(req, tok0, admitted_tick=tick_idx,
                                          admit_at=t_pre)
                if isinstance(tok0, (int, np.integer)):
                    # a token that has already arrived
                    self._land_first_tokens([slot], finished)
                elif tok0 is not None:
                    pending.append(slot)
                # None, a block round: the first tokens, and TTFT, come
                # with the slot's first launch

        # 3) decode — one launch for every slot, under the slots' token
        # budgets and the deadline horizon. A failure is
        # NOT attributable (all slots share the program): skip the tick
        # with slot state intact, and only a run of consecutive failures
        # retires the live set.
        budgets = np.array(
            [0 if s is None else
             max(s.req.max_new_tokens - len(s.tokens), 0)
             for s in self._slots], np.int32)
        # a slot admitted for one token has it already, on its way
        live = budgets > 0
        decode_sec = 0.0
        if live.any():
            n_live = int(live.sum())
            # rows the launch's first step attends over: each live slot's
            # prompt and the tokens sampled so far
            rows = sum(len(s.req.prompt) + len(s.tokens)
                       for s, on in zip(self._slots, live) if on)
            # the admissions' first tokens are read once the launch is in
            # the device's queue: they wait for the prefill programs, which
            # end before it does, with the device busy meanwhile
            kw = {"launched": lambda: self._land_first_tokens(
                pending, finished, dispatched=self.clock())} \
                if pending else {}
            cpu0 = time.process_time()
            t0 = self.clock()
            try:
                reg.counter("serve.engine.host_syncs").inc()
                with self.events.span(ev.SERVE_DECODE, live=n_live):
                    r_max = self._resident_horizon(now)
                    try:
                        toks, valid = self.backend.decode(
                            live, budgets=budgets, r_max=r_max, **kw)
                    finally:
                        # a backend that told nobody, or raised before it
                        self._land_first_tokens(pending, finished)
            except Exception as e:           # noqa: BLE001 — containment
                self._on_decode_error(reg, e, tick_idx, finished)
            else:
                self._decode_errors = 0
                t1 = self.clock()
                decode_sec = t1 - t0
                device_sec += decode_sec
                width = getattr(
                    self.backend, "decode_width",
                    getattr(self.backend, "decode_chunk", 1))
                chunks = max(1, toks.shape[1] // max(1, width))
                dispatched = self._launch_dispatched(tick_idx, t0, t1, cpu0,
                                                     chunks)
                per = decode_sec / chunks
                self._chunk_ewma = per if self._chunk_ewma is None \
                    else 0.8 * self._chunk_ewma + 0.2 * per
                emitted = 0
                n_before = len(finished)
                notes = getattr(self.backend, "launch_notes", None)
                with self.events.span(ev.SERVE_RETIRE) as retire:
                    for slot in range(self.backend.num_slots):
                        st = self._slots[slot]
                        if st is None:
                            continue
                        for k in range(toks.shape[1]):
                            if not valid[slot, k]:
                                continue
                            if not st.tokens:    # a block round's first
                                self._first_token_landed(slot, st, t1,
                                                         dispatched)
                            t = int(toks[slot, k])
                            st.tokens.append(t)
                            if notes is not None:
                                st.notes.append(int(notes[slot, k]))
                            emitted += 1
                            if eos is not None and t == eos:
                                finished.append(
                                    self._retire(slot, "ok", "eos", t1))
                                break
                            if len(st.tokens) >= st.req.max_new_tokens:
                                finished.append(
                                    self._retire(slot, "ok", "length", t1))
                                break
                    retire.set_metadata(finished=len(finished) - n_before)
                # the launch's counts, known only now; the counters say
                # the same to an operator without a profiler. A step is a
                # forward pass of every slot: a token column, or a round's
                # passes where the backend says a round is not its width
                steps = int(toks.shape[1])
                passes = getattr(self.backend, "round_passes", None)
                if passes is not None:
                    steps = steps // max(1, width) * passes
                with self.events.span(
                        ev.SERVE_DECODE_DONE, steps=steps, chunks=chunks,
                        live=n_live, rows=rows, emitted=emitted,
                        early_exit=int(chunks < r_max),
                        **getattr(self.backend, "launch_counts", {})):
                    pass
                reg.counter("serve.engine.decode_launches").inc()
                reg.counter("serve.engine.decode_steps").inc(steps)
                if emitted:
                    reg.counter("serve.engine.tokens").inc(emitted)
                    reg.histogram("serve.engine.token_sec").observe(
                        (t1 - t0) / emitted)
        else:
            self._land_first_tokens(pending, finished)   # no launch

        reg.gauge("serve.engine.queue_depth").set(self.queue.depth)
        reg.gauge("serve.engine.slot_occupancy").set(
            self.live_slots / self.backend.num_slots)
        pool = getattr(self.backend, "pool", None)
        if pool is not None:
            pool.observe()
        if self.idle:
            # no work to hold the device back from: what the caller does
            # until the next request is no launch's cost
            self._cycle = None
        dur = self.clock() - t_start
        # everything in the tick that was NOT a device launch (prefill
        # or decode) is host overhead the resident loop amortizes away;
        # the cumulative ratio is the SERVE_r14 before/after headline
        reg.timer("serve.engine.host_sec").observe(
            max(dur - device_sec, 0.0))
        reg.gauge("serve.engine.host_overhead_per_token").set(
            host_overhead_per_token(reg))
        if wd is not None and wd.record_tick(dur):
            reg.counter("resilience.watchdog_slow_ticks").inc()
            self.events.event("resilience", action="slow_tick",
                              tick=tick_idx, duration_s=dur,
                              budget_s=wd.tick_budget_s)
        return finished

    def _land_first_tokens(self, pending: List[int],
                           finished: List[Response],
                           dispatched: Optional[float] = None) -> None:
        """The first token of every slot in ``pending`` (emptied) arrives
        on the host: ``int()`` of what the backend's prefill returned,
        which for a device value waits until that admission's program is
        done. This is the TTFT moment: ``ttft`` is stamped, the request's
        prefill record written, and a slot whose first token is eos or
        its whole budget retires. A read that raises fails that one
        request, as a raising prefill does. ``dispatched``: when the
        tick's decode launch went into the device's queue (the backend's
        ``launched`` call), so the device works through the wait; None
        where there is no launch to ride."""
        reg = get_registry()
        eos = self.backend.gen.eos_token_id
        while pending:
            slot = pending.pop(0)
            st = self._slots[slot]
            req = st.req
            try:
                with self.events.span(ev.SERVE_PREFILL_SYNC, slot=slot):
                    tok0 = st.tokens[0] = int(st.tokens[0])
            except Exception as e:           # noqa: BLE001 — containment
                self._slots[slot] = None
                self._free.append(slot)
                rel = getattr(self.backend, "release", None)
                if rel is not None:
                    rel(slot)
                finished.append(self._fail_queued(req, e, self.clock()))
                continue
            t_first = self.clock()
            self._first_token_landed(slot, st, t_first, dispatched)
            if dispatched is not None:
                reg.counter("serve.engine.first_tokens_overlapped").inc()
            if eos is not None and tok0 == eos:
                finished.append(self._retire(slot, "ok", "eos", t_first))
            elif req.max_new_tokens == 1:
                finished.append(self._retire(slot, "ok", "length", t_first))

    def _first_token_landed(self, slot: int, st: _Slot, t_first: float,
                            dispatched: Optional[float] = None) -> None:
        """The TTFT moment of the request in ``slot``: its first token is
        on the host (a prefill's, or with the first block of a block
        round the whole block's). ``ttft`` is stamped and the request's
        prefill record written, with TTFT's three stages, which add up to
        it: in the queue, to the dispatch of the launch it rides (its own
        admission and those after it; ``dispatched``, None where it rode
        none), and from there to the token."""
        reg = get_registry()
        req = st.req
        st.ttft = t_first - req.submitted_at
        reg.counter("serve.engine.admitted").inc()
        reg.histogram("serve.engine.ttft_sec").observe(st.ttft)
        rode = t_first if dispatched is None else dispatched
        stages = {"queued_ms": 1e3 * (st.admit_at - req.submitted_at),
                  "admit_ms": 1e3 * (rode - st.admit_at),
                  "launch_ms": 1e3 * (t_first - rode)}
        with self.events.span(ev.SERVE_FIRST_TOKEN, request=req.id,
                              slot=slot, **stages, ttft_ms=1e3 * st.ttft):
            pass
        self.events.event(REQUEST, request=req.id,
                          stage="prefill", trace=req.trace_id,
                          slot=slot, ttft=st.ttft,
                          attempts=req.attempts,
                          prompt_len=len(req.prompt), **stages)

    def _resident_horizon(self, now: float) -> int:
        """How many chunks the device may run before host attention
        could matter: the soonest deadline — live slots or queued
        requests — divided by the observed per-chunk latency, clamped
        to [1, resident_chunks]. No deadlines in sight: the full
        resident depth (slot-free early exit still fires on device)."""
        R = getattr(self.backend, "resident_chunks", 1)
        dls = [s.req.deadline for s in self._slots
               if s is not None and s.req.deadline is not None]
        qd = self.queue.earliest_deadline()
        if qd is not None:
            dls.append(qd)
        if not dls:
            return R
        ew = self._chunk_ewma
        left = min(dls) - now
        if ew is None or ew <= 0.0 or left <= 0.0:
            return 1
        return int(max(1, min(R, left / ew)))

    def _prefill_kwargs(self, req: Request) -> dict:
        """Pass the request's token budget to backends whose prefill
        reserves by demand (paged pools). Legacy/stub/wrapped backends
        with a 3-arg prefill get the legacy call."""
        import inspect
        try:
            params = inspect.signature(self.backend.prefill).parameters
        except (TypeError, ValueError):
            return {}
        if "max_new_tokens" in params or any(
                p.kind is inspect.Parameter.VAR_KEYWORD
                for p in params.values()):
            return {"max_new_tokens": req.max_new_tokens}
        return {}

    def _apply_chaos(self, reg, tick_idx: int) -> None:
        """Serve-side fault injection (chaos plan only; no-op in real
        deployments). ``backend_raise`` is handled at the prefill site."""
        f = self.chaos.serve_fault("stall_tick", tick_idx)
        if f is not None:
            reg.counter("resilience.chaos_stalls").inc()
            time.sleep(f.magnitude)
        if self.chaos.serve_fault("queue_flood", tick_idx) is not None:
            i = 0
            while self.queue.depth < self.queue.capacity:
                self.queue.submit(self.chaos.flood_prompt(i),
                                  max_new_tokens=1, priority=-(10 ** 6))
                i += 1
            reg.counter("resilience.chaos_floods").inc()

    def _on_decode_error(self, reg, exc: Exception, tick_idx: int,
                         finished: List[Response]) -> None:
        self._decode_errors += 1
        self.last_error = exc
        reg.counter("resilience.decode_errors").inc()
        self.events.event("resilience", action="decode_error",
                          tick=tick_idx, consecutive=self._decode_errors,
                          error=type(exc).__name__)
        if self._decode_errors < self.decode_error_limit:
            return                           # skip the tick; state intact
        now = self.clock()
        for slot in range(self.backend.num_slots):
            if self._slots[slot] is not None:
                reg.counter("resilience.slot_errors").inc()
                finished.append(
                    self._retire(slot, "error", "backend_error", now))
        self._decode_errors = 0

    # -- convenience loops -------------------------------------------------

    def run_until_idle(self, max_ticks: int = 1_000_000) -> List[Response]:
        """Tick until every queued/running request retired."""
        finished: List[Response] = []
        for _ in range(max_ticks):
            if self.idle:
                return finished
            finished.extend(self.tick())
        raise RuntimeError(
            f"engine not idle after {max_ticks} ticks "
            f"(live={self.live_slots}, queued={self.queue.depth})")

    def serve(self, prompts: Sequence[Sequence[int]], *,
              max_new_tokens: Optional[int] = None,
              seeds: Optional[Sequence[int]] = None) -> List[Response]:
        """Batch convenience: submit all, drain, return responses in
        submit order. Oversubscription beyond queue capacity is drained
        incrementally (submit blocks on ticks, not on QueueFull)."""
        ids = {}
        i = 0
        while i < len(prompts) or not self.idle:
            while i < len(prompts):
                try:
                    req = self.submit(
                        prompts[i], max_new_tokens=max_new_tokens,
                        seed=seeds[i] if seeds is not None else 0)
                except QueueFull:
                    break
                ids[i] = req.id
                i += 1
            self.tick()
        return [self._responses[ids[j]] for j in range(len(prompts))]
