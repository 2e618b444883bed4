"""Request tracing: monotonic-clock spans with no dependencies.

The serve tier answers "why was THIS request slow" with a per-request
span breakdown instead of an aggregate histogram: every ``/v1/embed``
request carries a :class:`RequestTrace` through handler -> batcher ->
engine, collecting queue-wait / coalesce / pad / device-compute /
serialize spans stamped from ``time.perf_counter()``.  Completed traces
land in a :class:`TraceRecorder`, which keeps a bounded ring of the
slowest requests (served at ``GET /debug/slow``) and optionally samples
a deterministic fraction into a ``requests.jsonl`` sidecar.

The trainer uses the same span primitive through one process-wide
:class:`SpanRecorder` (``recorder()``): always on, in memory only, written
to ``<save_dir>/spans.jsonl`` at flush points. The names of its spans and of
the step program's ``jax.named_scope`` scopes live here, in two tuples,
because the readers (``obs/timeline.py``, ``benchmarks/spans.py``,
``benchmarks/scopes.py``) find them by name.

Everything here is host-clock arithmetic on numbers the serve path and the
host loop already have — tracing never touches a device value, so the
zero-sync discipline of ``docs/OBSERVABILITY.md`` holds with spans on.
"""

from __future__ import annotations

import collections
import heapq
import itertools
import json
import os
import threading
import time
import uuid

from simclr_tpu.utils.ioutil import atomic_append

# default depth of the slowest-requests ring at GET /debug/slow
SLOW_RING_CAPACITY = 32

_MAX_REQUEST_ID_LEN = 128


def new_request_id() -> str:
    return uuid.uuid4().hex[:16]


def clean_request_id(raw) -> str:
    """A usable request id: the client-supplied header value sanitized
    (printable, no whitespace, bounded length), or a fresh one."""
    if raw is not None:
        rid = "".join(
            c for c in str(raw).strip() if c.isprintable() and not c.isspace()
        )
        rid = rid[:_MAX_REQUEST_ID_LEN]
        if rid:
            return rid
    return new_request_id()


class RequestTrace:
    """Span collection for one request.

    Spans are ``(name, start, end)`` tuples in ``time.perf_counter()``
    seconds.  A trace crosses threads exactly once (handler -> batcher
    worker and back through the Future, which gives happens-before), but
    a lock keeps ``add`` safe regardless.
    """

    __slots__ = ("request_id", "t0", "_spans", "_lock")

    clock = staticmethod(time.perf_counter)

    def __init__(self, request_id: str | None = None):
        self.request_id = request_id or new_request_id()
        self.t0 = time.perf_counter()
        self._spans: list[tuple[str, float, float]] = []
        self._lock = threading.Lock()

    def add(self, name: str, start: float, end: float) -> None:
        with self._lock:
            self._spans.append((str(name), float(start), float(end)))

    def span(self, name: str) -> "_SpanContext":
        """``with trace.span("serialize"): ...`` stamps one span."""
        return _SpanContext(self, name)

    def spans(self) -> list[tuple[str, float, float]]:
        with self._lock:
            return list(self._spans)

    def total_s(self) -> float:
        """Request start to the last span end (0 if no spans yet)."""
        end = max((e for _, _, e in self.spans()), default=self.t0)
        return end - self.t0

    def to_dict(self) -> dict:
        spans = self.spans()
        end = max((e for _, _, e in spans), default=self.t0)
        return {
            "request_id": self.request_id,
            "total_ms": round((end - self.t0) * 1000.0, 3),
            "spans": [
                {
                    "name": name,
                    "start_ms": round((start - self.t0) * 1000.0, 3),
                    "dur_ms": round((span_end - start) * 1000.0, 3),
                }
                for name, start, span_end in spans
            ],
        }


class _SpanContext:
    """``with sink.span(name):`` — one span on the sink's ``clock``."""

    __slots__ = ("_trace", "_name", "_start")

    def __init__(self, trace, name: str):
        self._trace = trace
        self._name = name

    def __enter__(self) -> "_SpanContext":
        self._start = self._trace.clock()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self._trace.add(self._name, self._start, self._trace.clock())


class TraceRecorder:
    """Terminal sink for completed traces.

    Keeps the ``capacity`` slowest traces in a min-heap (evict the
    fastest when full) for ``GET /debug/slow``, and — when ``path`` and
    ``sample_rate`` are set — appends every Nth completed trace as one
    JSON line.  Sampling uses a deterministic accumulator rather than a
    PRNG so a rate of 0.25 means exactly every 4th request, which keeps
    the sidecar's growth rate predictable.
    """

    def __init__(
        self,
        *,
        sample_rate: float = 0.0,
        path: str | None = None,
        capacity: int = SLOW_RING_CAPACITY,
    ):
        if not 0.0 <= sample_rate <= 1.0:
            raise ValueError(f"sample_rate must be in [0, 1], got {sample_rate}")
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.sample_rate = float(sample_rate)
        self.path = path
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        # (total_ms, seq, record): seq breaks ties so dicts never compare
        self._heap: list[tuple[float, int, dict]] = []
        self._seq = 0
        self._accum = 0.0

    def record(self, trace: RequestTrace) -> dict:
        rec = {"time": round(time.time(), 6), **trace.to_dict()}
        total_ms = rec["total_ms"]
        with self._lock:
            self._seq += 1
            heapq.heappush(self._heap, (total_ms, self._seq, rec))
            if len(self._heap) > self.capacity:
                heapq.heappop(self._heap)
            sampled = False
            if self.path and self.sample_rate > 0.0:
                self._accum += self.sample_rate
                if self._accum >= 1.0:
                    self._accum -= 1.0
                    sampled = True
        if sampled:
            atomic_append(self.path, json.dumps(rec) + "\n")
        return rec

    def slowest(self) -> list[dict]:
        """Retained traces, slowest first (most recent wins ties)."""
        with self._lock:
            items = sorted(self._heap, key=lambda t: (-t[0], -t[1]))
        return [rec for _, _, rec in items]


# -- the trainer's spans ------------------------------------------------------

# Host spans of a pretraining run, by where the work happens. The names are
# the contract with the readers; docs/OBSERVABILITY.md says what each covers.
TRAIN_SPANS = (
    "boot/runtime", "boot/data", "boot/state", "boot/state/init",
    "boot/build_step", "boot/compile",
    "epoch", "input_wait", "gather", "device_put", "dispatch", "beat",
    "boundary", "boundary/loss_fetch", "boundary/telemetry", "boundary/log",
    "boundary/event", "boundary/results", "boundary/probe", "boundary/shuffle",
    "boundary/first_batch",
    "checkpoint", "checkpoint/drain", "checkpoint/save", "checkpoint/digest",
    "checkpoint/sidecar",
    "preempt", "preempt/notice", "preempt/event", "preempt/beat", "preempt/flush",
)
# the ones a step records (about five a step): kept in the ring; every other
# span is rare and kept apart, so that a long run cannot evict its boot
STEP_SPANS = frozenset(("input_wait", "gather", "device_put", "dispatch", "beat"))

# ``jax.named_scope`` names inside the step program (parallel/steps.py,
# parallel/tp.py, models/contrastive.py). Backward ops carry the same name
# inside ``transpose(jvp(...))``.
DEVICE_SCOPES = ("augment", "encoder", "head", "ntxent", "grad_reduce", "lars")
(
    SCOPE_AUGMENT, SCOPE_ENCODER, SCOPE_HEAD, SCOPE_NTXENT, SCOPE_GRAD_REDUCE,
    SCOPE_LARS,
) = DEVICE_SCOPES
# inside ``encoder``, a sequence encoder's layers (models/sequence.py):
# ``attention`` (norm, projections, rotary embedding, the attention itself)
# and ``moe``, which holds ``router`` (scores, softmax, top-k; it runs before
# the attention, on the layer's input) and ``experts`` (norm, sort, the
# grouped products, the weighted sum)
SEQUENCE_SCOPES = ("attention", "moe", "router", "experts")
SCOPE_ATTENTION, SCOPE_MOE, SCOPE_ROUTER, SCOPE_EXPERTS = SEQUENCE_SCOPES

SPANS_NAME = "spans.jsonl"

# two minutes of the fastest cell (30 ms steps, five spans a step) is 20,000
RING_CAPACITY = 32768
# a 1000-epoch run that never flushes holds ~12,000 rare spans
RARE_CAPACITY = 65536


def spans_path(save_dir: str, process_index: int = 0) -> str:
    """``spans.jsonl`` for process 0, ``spans.p<i>.jsonl`` beyond (the
    heartbeat's naming)."""
    if process_index:
        return os.path.join(save_dir, f"spans.p{int(process_index)}.jsonl")
    return os.path.join(save_dir, SPANS_NAME)


class _RecordedSpan(_SpanContext):
    """One span of the :class:`SpanRecorder`: besides its times it knows the
    span that was open on its thread when it began, and the step and epoch it
    serves. ``step``, ``epoch`` and ``n`` may be set while it is open; spans
    begun under it inherit ``step`` and ``epoch``."""

    __slots__ = ("id", "parent", "thread", "step", "epoch", "n")

    def __init__(self, trace, name, step, epoch, n):
        super().__init__(trace, name)
        self.step, self.epoch, self.n = step, epoch, n

    @property
    def name(self) -> str:
        return self._name

    def __enter__(self) -> "_RecordedSpan":
        self._trace._begin(self)
        return super().__enter__()

    def __exit__(self, exc_type, exc, tb) -> None:
        self._trace._end(self, self._trace.clock())


class SpanRecorder:
    """Host spans of one process, in memory until :meth:`flush`.

    A span is ``name, t0, t1`` in ``time.monotonic_ns()``, its ``parent`` (the
    span open on the same thread when it began; 0 for none), the ``thread``,
    and what ties the spans of one unit of work together: ``step`` (the
    optimizer step a span serves, the same number on the prefetch thread and
    on the main thread), ``epoch``, and an optional count ``n``. Recording is
    two clock reads and one ``deque.append``; nothing is written, and no
    device value is touched, until a flush.
    """

    clock = staticmethod(time.monotonic_ns)

    def __init__(self, ring: int = RING_CAPACITY, rare: int = RARE_CAPACITY):
        self._ring: collections.deque = collections.deque(maxlen=ring)
        self._rare: collections.deque = collections.deque(maxlen=rare)
        self._ids = itertools.count(1)
        # open spans by thread ident, innermost last; an entry lives only
        # while its thread has a span open
        self._open: dict[int, list[_RecordedSpan]] = {}

    # -- recording ------------------------------------------------------------
    def span(self, name: str, *, step=None, epoch=None, n=None) -> _RecordedSpan:
        """``with recorder().span("dispatch", step=k):`` records one span."""
        return _RecordedSpan(self, name, step, epoch, n)

    def begin(self, name: str, **ids) -> _RecordedSpan:
        """Open a span that another block will :meth:`end` (a boundary runs
        from the tail of one loop iteration into the head of the next)."""
        return self.span(name, **ids).__enter__()

    def end(self, span: _RecordedSpan | None) -> None:
        if span is not None:
            span.__exit__(None, None, None)

    def add(self, name, t0, t1, *, parent=0, step=None, epoch=None, n=None) -> None:
        """A span whose times were taken elsewhere (a signal handler's stamp)."""
        self._keep((
            next(self._ids), name, int(t0), int(t1), parent,
            threading.current_thread().name, step, epoch, n,
        ))

    def current(self, thread_ident: int | None = None) -> _RecordedSpan | None:
        """The innermost span open on a thread (the caller's by default)."""
        stack = self._open.get(thread_ident or threading.get_ident())
        try:
            return stack[-1] if stack else None
        except IndexError:  # popped by its own thread meanwhile
            return None

    def _begin(self, span: _RecordedSpan) -> None:
        stack = self._open.setdefault(threading.get_ident(), [])
        span.id = next(self._ids)
        span.thread = threading.current_thread().name
        if stack:
            above = stack[-1]
            span.parent = above.id
            if span.step is None:
                span.step = above.step
            if span.epoch is None:
                span.epoch = above.epoch
        else:
            span.parent = 0
        stack.append(span)

    def _end(self, span: _RecordedSpan, t1: int) -> None:
        ident = threading.get_ident()
        stack = self._open.get(ident)
        if stack and span in stack:
            # spans end innermost first; one left open by an exception that
            # passed through it goes with the span that encloses it
            del stack[stack.index(span):]
            if not stack:
                del self._open[ident]
        self._keep(self._row(span, t1))

    @staticmethod
    def _row(span: _RecordedSpan, t1: int) -> tuple:
        return (
            span.id, span._name, span._start, t1, span.parent, span.thread,
            span.step, span.epoch, span.n,
        )

    def _keep(self, row: tuple) -> None:
        (self._ring if row[1] in STEP_SPANS else self._rare).append(row)

    # -- reading and writing ----------------------------------------------------
    def __len__(self) -> int:
        return len(self._ring) + len(self._rare)

    def drain(self) -> list[dict]:
        """Every finished span since the last drain, oldest first, then the
        spans still open (``"open": true``, ended now): what a crash or a
        stall was sitting in."""
        rows = []
        for store in (self._rare, self._ring):
            while True:
                try:
                    rows.append(store.popleft())
                except IndexError:
                    break
        rows.sort(key=lambda r: r[2])
        now = self.clock()
        still_open = [
            self._row(span, now)
            for stack in list(self._open.values()) for span in list(stack)
        ]
        keys = ("id", "name", "t0", "t1", "parent", "thread", "step", "epoch", "n")

        def as_dict(row: tuple, **more) -> dict:
            return {**{k: v for k, v in zip(keys, row) if v is not None}, **more}

        return [as_dict(r) for r in rows] + [as_dict(r, open=True) for r in still_open]

    def flush(self, path: str, *, process_index: int = 0) -> int:
        """Append a header and every span since the last flush to ``path``,
        as one write; returns the number of spans written. The header is the
        clock anchor: ``wall_ns`` and ``mono_ns`` read back to back, so a
        span's Unix time is ``t + wall_ns - mono_ns``."""
        spans = self.drain()
        header = {
            "header": SPANS_NAME, "wall_ns": time.time_ns(),
            "mono_ns": time.monotonic_ns(), "pid": os.getpid(),
            "process_index": int(process_index), "spans": len(spans),
        }
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        atomic_append(
            path, "".join(json.dumps(row) + "\n" for row in (header, *spans))
        )
        return len(spans)

    def reset(self) -> None:
        """Forget everything, open spans included: an entry point starts its
        run with an empty recorder."""
        self._ring.clear()
        self._rare.clear()
        self._open.clear()


_RECORDER = SpanRecorder()


def recorder() -> SpanRecorder:
    """The process-wide recorder: ``utils/checkpoint.py`` and the prefetch
    thread record into the same one as the host loop, with no argument
    threaded through them."""
    return _RECORDER


def read_spans(path: str) -> list[dict]:
    """The spans of a spans file, oldest first; ``[]`` when it is absent. A
    span that was open at one flush and closed by a later one is in the file
    twice under one ``id``: the later line wins. Each span carries the
    ``pid`` and the clock anchor of its flush's header as ``wall_offset_ns``,
    so its Unix time is ``t0 + wall_offset_ns``."""
    spans: dict[tuple, dict] = {}
    pid, offset = None, 0
    try:
        with open(path) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except ValueError:
                    continue  # a line torn by a kill
                if not isinstance(row, dict):
                    continue
                if "header" in row:
                    pid = row.get("pid")
                    offset = int(row.get("wall_ns", 0)) - int(row.get("mono_ns", 0))
                elif "id" in row:
                    spans[(pid, row["id"])] = {
                        **row, "pid": pid, "wall_offset_ns": offset,
                    }
    except OSError:
        pass
    return sorted(spans.values(), key=lambda s: s["t0"] + s["wall_offset_ns"])
