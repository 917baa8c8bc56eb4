"""The one vocabulary of spans and scopes, and the JSONL event log.

Everything the program says about where its time goes is declared here
and opened where the work happens:

* **host spans** (:data:`SPAN_KINDS`): :meth:`EventLog.span` /
  :meth:`NullEventLog.span` (code that holds an event log) and the module
  function :func:`span` (code that holds none, such as the serve slot
  backends) each enter a ``jax.profiler.TraceAnnotation`` of the span's
  kind, with the attributes as the event's stats. A call site makes one
  call; the span lands in the JSONL log when there is one and in the
  profiler's capture whenever a session is open, under the same name and
  nesting, on the device trace's clock. With no session open the
  annotation is a no-op of well under a microsecond: tracing is "on"
  exactly while somebody profiles (``jax.profiler.start_trace``,
  ``TrainerConfig.profile_every``, the benchmark's ``--trace 1``). There
  is no switch.
* **device scopes** (:data:`DEVICE_SCOPES`): :func:`device_scope` is
  ``jax.named_scope`` over a fixed set of names, so every device operation
  carries in its ``op_name`` metadata what it is for. Scopes are metadata
  only: the compiled program is the same with them as without.
  :func:`stage_scope` (``chunk{i}-stage{j}``) is the pipeline
  executors' scope of the same kind.

:class:`EventLog` records *host* structure — steps, compiles, evaluation,
serving calls, and (on the emulator, which runs tasks in Python)
per-stage/per-micro-batch task spans — as one JSON object per line, cheap
enough to leave on in production loops.

Record schema (one dict per line)::

    {"kind": <str>, "id": <int>, "parent": <int|null>,
     "t": <sec since log open>, "dur": <sec, spans only>, ...attrs}

plus a ``log_open`` header carrying the wall-clock epoch so host events
can be correlated with profiler traces. Span kinds used by the built-in
wiring: ``step``, ``stage``, ``microbatch``, ``comm``,
``checkpoint-recompute``, ``request`` (:data:`SPAN_KINDS`);
``step_report`` records carry a full :class:`~.telemetry.StepReport`
(``to_json`` payload).

Spans nest through a per-thread stack: ``parent`` is the id of the
innermost open span on the same thread. Records are written at span
*exit*, so children precede parents in the file; :meth:`EventLog.read`
returns them in file order and tests reconstruct the tree from
``id``/``parent``.

``NULL_EVENT_LOG`` is the disabled sink — same API, no file, no clock
reads beyond the context-manager protocol — so call sites never branch.
Its spans still reach the profiler, as :class:`EventLog`'s do.
"""

from __future__ import annotations

import contextlib
import functools
import json
import os
import threading
import time
from typing import Any, Dict, IO, List, Optional

import jax

__all__ = ["EventLog", "NullEventLog", "NULL_EVENT_LOG", "SPAN_KINDS",
           "STEP", "STAGE", "MICROBATCH", "COMM", "RECOMPUTE", "REQUEST",
           "RECOVERY", "CYCLE_PHASES", "STALL_SEC", "DEVICE_SCOPES",
           "MODEL_SCOPES", "REMAT_SCOPE", "device_scope",
           "scoped", "stage_scope", "span", "SpanHandle"]

STEP = "step"
STAGE = "stage"
MICROBATCH = "microbatch"
COMM = "comm"
RECOMPUTE = "checkpoint-recompute"
# serving: one record per retired request, written by the serve engine at
# retirement (see docs/observability.md "Request spans" for the schema)
REQUEST = "request"
# resilience: instantaneous records (not spans) written at every rung of
# the recovery ladder — skip/rewind (action=...) and the elastic path
# (stage_lost, replan, buddy_restore) — so a post-mortem can replay the
# escalation from the event log alone
RECOVERY = "recovery"
# train loop (train/loop.py train_epoch), inside STEP: what the host does
# for a step besides the call; the call of the step program (enqueue, not
# step time); each blocking read of a loss
TRAIN_BATCH = "train.batch"
TRAIN_DISPATCH = "train.dispatch"
TRAIN_SYNC = "train.sync"
# serve engine (serve/engine.py): ServeEngine.tick and its phases ...
SERVE_TICK = "serve.tick"
SERVE_REAP = "serve.reap"
SERVE_ADMIT = "serve.admit"
SERVE_DECODE = "serve.decode"
SERVE_RETIRE = "serve.retire"
# ... and the slot backend's calls under them: a prefill's dispatch; the
# engine's wait for its first token, once the tick's decode launch is
# queued; the dispatch of a decode or resident program, the host's wait
# for it (``serve.decode.sync``: inside it the wait for the round count,
# ``rounds`` known at its end, then the blocking reads of what the launch
# left, ``reads`` of ``bytes`` in all), and a zero-length record of the
# counts known only afterwards
SERVE_PREFILL = "serve.prefill"
SERVE_PREFILL_SYNC = "serve.prefill.sync"
SERVE_DECODE_LAUNCH = "serve.decode.launch"
SERVE_DECODE_SYNC = "serve.decode.sync"
SERVE_DECODE_WAIT = "serve.decode.wait"
SERVE_DECODE_FETCH = "serve.decode.fetch"
SERVE_DECODE_DONE = "serve.decode.done"
# ... and a zero-length record of a request's first token (under a block
# round its first block) reaching the host, with the stages of its TTFT:
# ``queued_ms`` (submit to its admission's start), ``admit_ms`` (to the
# dispatch of the launch it rides), ``launch_ms`` (to the token on the
# host), ``ttft_ms`` (their sum)
SERVE_FIRST_TOKEN = "serve.first_token"
# The launch cycle: what the host does from one decode launch's dispatch to
# the next one's, in four phases that add up to it. Each is a registry timer
# ``serve.engine.cycle.<phase>_sec`` in every run (host clock, one
# observation a closed cycle) and, while a profiler session is open, the
# spans named here on the device trace's clock:
#   wait    dispatch to the round count back on the host: the first-token
#           reads the engine makes meanwhile (``serve.prefill.sync``), then
#           ``serve.decode.wait``
#   fetch   to the last blocking read back: ``serve.decode.fetch``
#   turn    to the next dispatch, less ``caller``: every other span inside
#           the ``serve.tick``s (retirement, ``serve.decode.done``, the next
#           tick's reaping, admissions and ``serve.decode.launch``)
#   caller  ``tick`` returned to ``tick`` entered again: no span can hold
#           it, ``serve.tick`` says it as ``away_ms``
CYCLE_PHASES = ("wait", "fetch", "turn", "caller")
# A phase of the host's (a launch cycle's ``fetch``, ``turn`` or ``caller``;
# a train step's ``train.batch``) that stands longer than this is a stall:
# the device runs dry behind a process that pauses so long (PERF.md, section
# 5). A phase that waits for the device (``wait``; ``train.dispatch``,
# ``train.sync``) has three times what the device is expected to take on
# top. ``telemetry.record_stall`` counts and names one.
STALL_SEC = 0.25
SPAN_KINDS = (STEP, STAGE, MICROBATCH, COMM, RECOMPUTE, REQUEST,
              TRAIN_BATCH, TRAIN_DISPATCH, TRAIN_SYNC,
              SERVE_TICK, SERVE_REAP, SERVE_ADMIT, SERVE_DECODE,
              SERVE_RETIRE, SERVE_PREFILL, SERVE_PREFILL_SYNC,
              SERVE_DECODE_LAUNCH, SERVE_DECODE_SYNC, SERVE_DECODE_WAIT,
              SERVE_DECODE_FETCH, SERVE_DECODE_DONE, SERVE_FIRST_TOKEN)

# Device scopes: what a device operation is for, readable from a capture
# alone (the ``tf_op`` stat of a TPU op event is its op_name). A reader
# takes the innermost of these names on an op's path.
EMBED = "embed"
ATTENTION = "attention"
FFN = "ffn"
HEAD = "head"
LOSS = "loss"
OPTIMIZER = "optimizer"
KV_CACHE = "kv_cache"
DEVICE_SCOPES = (EMBED, ATTENTION, FFN, HEAD, LOSS, OPTIMIZER, KV_CACHE)
# Scopes a model opens INSIDE one of the above, for a mechanism only it has
# (an expert layer's router, grouped product and shared expert inside
# ``ffn``; the kind of attention layer inside ``attention``). A reader of
# :data:`DEVICE_SCOPES` skips them, so ``ffn``, ``attention`` and
# ``kv_cache`` go on adding up; a reader of these names looks for them
# itself. The compiler's own grouped-product kernel keeps no path at all
# (its ``op_name`` is ``ragged-dot-*``): a reader of ``moe_experts``
# counts it in. The tiled one (``ops/grouped_product.py``) keeps its path:
# ``.../ffn/moe_experts/grouped_product/pallas_call``.
MOE_ROUTER = "moe_router"
MOE_EXPERTS = "moe_experts"
MOE_SHARED = "moe_shared"
ATTN_WINDOW = "attn_window"
ATTN_FULL = "attn_full"
# Generation by diffusion over blocks (the serve engine's block round):
# around the layer loop of a denoise pass, outside every layer's own
# scopes (a round's first one also carries the commit of the block before,
# which is no pass of its own and has no scope of its own), and inside
# ``head`` around what picks the positions a pass reveals (softmax,
# confidence, the reveal). What the round counts, carried on the device and
# put on ``serve.decode.done`` beside the layers' and the caches' counts:
# ``serve.diffusion.{blocks, denoise_passes, commit_passes, tokens,
# cut_tokens, fused_commits}`` (``serve/engine.py`` ``BLOCK_COUNTS``).
DIFFUSION_DENOISE = "diffusion_denoise"
DIFFUSION_SELECT = "diffusion_select"
MODEL_SCOPES = (MOE_ROUTER, MOE_EXPERTS, MOE_SHARED, ATTN_WINDOW, ATTN_FULL,
                DIFFUSION_DENOISE, DIFFUSION_SELECT)
# Not a layer but a mark that cuts across them: a forward that runs again
# for its backward. ``jax.checkpoint`` writes this name itself; the
# scheduled executor's manual re-forward opens a scope of the same name.
REMAT_SCOPE = "rematted_computation"


def device_scope(name: str):
    """``jax.named_scope`` for a name of :data:`DEVICE_SCOPES` or
    :data:`MODEL_SCOPES` (or :data:`REMAT_SCOPE`): metadata on the ops
    traced inside, nothing at run time."""
    if name not in DEVICE_SCOPES + MODEL_SCOPES and name != REMAT_SCOPE:
        raise ValueError(
            f"{name!r} is not one of {DEVICE_SCOPES + MODEL_SCOPES}")
    return jax.named_scope(name)


def scoped(name: str):
    """Decorator: the function's whole body under :func:`device_scope`
    ``(name)``, entered at each call (so at each trace)."""
    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with device_scope(name):
                return fn(*args, **kwargs)
        return inner
    return wrap


def stage_scope(microbatch: Optional[int], stage: int):
    """The pipeline executors' device scope: ``chunk{i}-stage{j}`` (the
    reference's ``record_function("chunk%d-part%d")``), which
    ``meters.stage_timeline_from_trace`` buckets by; ``stage{j}`` where the
    micro-batch index is a traced value (``microbatch=None``)."""
    if microbatch is None:
        return jax.named_scope(f"stage{stage}")
    return jax.named_scope(f"chunk{microbatch}-stage{stage}")


def span(kind: str, **attrs: Any):
    """A host span for code that holds no event log: a profiler
    annotation named ``kind`` whose stats are ``attrs``. Nothing is
    recorded unless a profiler session is open. Entered, it gives the
    annotation, whose ``set_metadata(**attrs)`` adds what is known only
    at the span's end."""
    return jax.profiler.TraceAnnotation(kind, **attrs)


class SpanHandle(int):
    """What an event log's span gives when entered: the span's id (an
    ``int``, as before) that also takes attributes known only at the
    span's end, as the profiler's annotation does. They reach both the
    log's record and the capture."""

    def __new__(cls, span_id: int, attrs: Dict[str, Any], annotation):
        self = super().__new__(cls, span_id)
        self._attrs, self._annotation = attrs, annotation
        return self

    def set_metadata(self, **attrs: Any) -> None:
        self._attrs.update(attrs)
        self._annotation.set_metadata(**attrs)


class EventLog:
    """Append-only JSONL event sink with nested span support.

    ``max_bytes`` arms size-bounded rotation: once the live file would
    exceed it, the file is renamed to ``<path>.1`` (replacing any
    previous rollover — at most two files ever exist) and a fresh file
    opens with a ``log_open`` header carrying ``rotated=True``. Long
    fleet drills keep at most ``2 * max_bytes`` on disk. A reader that
    races a writer (or a crash mid-line) can leave a torn final line;
    :meth:`read` tolerates exactly that — a final line that does not
    parse is dropped, a torn line anywhere else still raises."""

    def __init__(self, path: str, *, autoflush: bool = True,
                 max_bytes: Optional[int] = None):
        if max_bytes is not None and max_bytes < 1024:
            raise ValueError(f"max_bytes must be >= 1024, got {max_bytes}")
        self.path = path
        self._autoflush = autoflush
        self._max_bytes = max_bytes
        self._file: Optional[IO[str]] = open(path, "a")
        self._written = self._file.tell()
        self._lock = threading.Lock()
        self._local = threading.local()
        self._next_id = 0
        self._t0 = time.perf_counter()
        self._write({"kind": "log_open", "wall_time": time.time(),
                     "id": self._alloc_id(), "parent": None, "t": 0.0})

    # -- plumbing ----------------------------------------------------------

    def _alloc_id(self) -> int:
        self._next_id += 1
        return self._next_id

    def _stack(self) -> List[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _write(self, record: Dict[str, Any]) -> None:
        line = json.dumps(record)
        with self._lock:
            if self._file is None:
                return
            if self._max_bytes is not None \
                    and self._written + len(line) + 1 > self._max_bytes \
                    and self._written > 0:
                self._rotate_locked()
            self._file.write(line + "\n")
            self._written += len(line) + 1
            if self._autoflush:
                self._file.flush()

    def _rotate_locked(self) -> None:
        """Roll the live file to ``<path>.1`` (caller holds the lock)."""
        self._file.close()
        os.replace(self.path, self.path + ".1")
        self._file = open(self.path, "a")
        self._written = 0
        header = json.dumps({"kind": "log_open", "wall_time": time.time(),
                             "id": self._alloc_id(), "parent": None,
                             "t": time.perf_counter() - self._t0,
                             "rotated": True})
        self._file.write(header + "\n")
        self._written += len(header) + 1

    # -- recording ---------------------------------------------------------

    def event(self, kind: str, **attrs: Any) -> None:
        """Instantaneous event under the current span (if any)."""
        stack = self._stack()
        rec = {"kind": kind, "id": self._alloc_id(),
               "parent": stack[-1] if stack else None,
               "t": time.perf_counter() - self._t0}
        rec.update(attrs)
        self._write(rec)

    @contextlib.contextmanager
    def span(self, kind: str, **attrs: Any):
        """Timed span; nests under the innermost open span on this thread."""
        stack = self._stack()
        span_id = self._alloc_id()
        parent = stack[-1] if stack else None
        stack.append(span_id)
        t0 = time.perf_counter()
        try:
            with span(kind, **attrs) as annotation:
                yield SpanHandle(span_id, attrs, annotation)
        finally:
            dur = time.perf_counter() - t0
            stack.pop()
            rec = {"kind": kind, "id": span_id, "parent": parent,
                   "t": t0 - self._t0, "dur": dur}
            rec.update(attrs)
            self._write(rec)

    def step_report(self, report) -> None:
        """Record a :class:`~.telemetry.StepReport` (or a plain dict)."""
        payload = report.to_json() if hasattr(report, "to_json") else report
        self.event("step_report", **payload)

    def metrics_snapshot(self, registry) -> None:
        """Record a registry snapshot (counters/gauges/timers/histograms)."""
        self.event("metrics", metrics=registry.snapshot())

    # -- lifecycle ---------------------------------------------------------

    def flush(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.flush()

    def close(self) -> None:
        with self._lock:
            if self._file is not None:
                self._file.close()
                self._file = None

    def __enter__(self) -> "EventLog":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- readback ----------------------------------------------------------

    @staticmethod
    def read(path: str) -> List[Dict[str, Any]]:
        """All records in file order (children precede their parent span).

        A torn FINAL line — the one artifact a crash or a reader racing
        the writer can legitimately produce on an append-only file — is
        dropped silently; corruption anywhere else still raises."""
        with open(path) as f:
            lines = [ln.strip() for ln in f]
        while lines and not lines[-1]:
            lines.pop()
        out: List[Dict[str, Any]] = []
        for i, line in enumerate(lines):
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except json.JSONDecodeError:
                if i == len(lines) - 1:
                    break
                raise
        return out


class NullEventLog:
    """Disabled sink: same surface as :class:`EventLog`, writes nothing."""

    path = None

    def event(self, kind: str, **attrs: Any) -> None:
        pass

    def span(self, kind: str, **attrs: Any):
        return span(kind, **attrs)

    def step_report(self, report) -> None:
        pass

    def metrics_snapshot(self, registry) -> None:
        pass

    def flush(self) -> None:
        pass

    def close(self) -> None:
        pass

    def __enter__(self) -> "NullEventLog":
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL_EVENT_LOG = NullEventLog()
