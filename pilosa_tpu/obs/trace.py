"""Query-path distributed tracing — Span/Tracer with cross-node links.

Dapper-model tracing (low-overhead, always-on, sampled retention): every
query gets a trace; the last ``capacity`` finished traces are retained in
a ring buffer served as JSON by ``GET /debug/traces``.

A trace is a tree of :class:`Span` objects sharing one ``trace_id``.
Spans time with ``time.monotonic()`` and say where their wall time went
on the opening thread: ``cpu_ms`` on the processor
(``time.thread_time()``), ``blocked_ms`` blocked on purpose (a wait the
code chose, by kind: :class:`blocked`), and what is left
is a thread that wanted to run and did not — the GIL, the OS run queue,
a blocking call nobody wrapped.  Their wall-clock ``start`` is the
monotonic stamp moved by one process-wide offset (:func:`wall`).  They
link parent→child two ways:

* in-process via a ``contextvars.ContextVar`` holding the active span —
  crossing threads works because the executor's pool captures the
  submitting context (``contextvars.copy_context``);
* across nodes via W3C-style headers: the coordinator's rpc span id
  travels as ``X-Trace-Id``/``X-Span-Id`` on the fan-out request, the
  remote handler continues the trace under that parent, and the remote's
  finished spans return in an ``X-Trace-Spans`` response header that the
  client absorbs back into the coordinator's open trace — so ONE trace
  on the coordinator covers parse, plan, local slice execution, and
  every remote node's leg.

Work done once for several traces on a thread that has no current span
(a coalesced launch on the dispatcher) times as a :class:`SharedSpan`
and lands in each waiter's trace through :meth:`Tracer.add_span`.

While a ``/debug/profile`` session is live (:func:`set_profiling`) every
span entered also stands in the profiler's host plane as a
``jax.profiler.TraceAnnotation`` of its name, on the profiler's own
clock beside the device ops.

``NOP_TRACER`` is the disabled implementation: components constructed
without a tracer (unit tests, embedders) pay one no-op method call per
span site.
"""

from __future__ import annotations

import contextvars
import json
import random
import threading
import time
import uuid
from collections import deque

# Propagation headers (W3C trace-context shaped: 16-byte trace id,
# 8-byte span id, hex).
TRACE_HEADER = "X-Trace-Id"
SPAN_HEADER = "X-Span-Id"
SPANS_HEADER = "X-Trace-Spans"

# Bounds: spans retained per trace and spans exported in the response
# header — a pathological query cannot balloon memory or the header.
MAX_SPANS_PER_TRACE = 512
MAX_EXPORT_SPANS = 128

_current_span: "contextvars.ContextVar[Span | None]" = contextvars.ContextVar(
    "pilosa_current_span", default=None
)

# ``jax.profiler.TraceAnnotation`` while a /debug/profile session is
# live, else None: outside a session a span pays one global read.
_annotation = None

_randbits = random.getrandbits

# The one clock every interval here is read from (tests patch it).
_now = time.monotonic

# Wall clock less monotonic clock: the one place a monotonic stamp
# becomes the wall-clock ``start`` the spans and the device trace share.
# Taken at import and again as a /debug/profile session opens, so the
# spans of a profile stand on its clock however long the process has
# lived (the wall clock is disciplined, the monotonic one is not).
_wall_offset = time.time() - _now()


def wall(t_mono: float) -> float:
    """The wall-clock time of the monotonic stamp ``t_mono``."""
    return t_mono + _wall_offset


def set_profiling(annotation) -> None:
    """The /debug/profile handler brackets its session with this, under
    its single-flight lock: the profiler's ``TraceAnnotation`` class at
    the start, None at the stop."""
    global _annotation, _wall_offset
    if annotation is not None:
        _wall_offset = time.time() - _now()
    _annotation = annotation


# ---------------------------------------------------------------------------
# time blocked on purpose
# ---------------------------------------------------------------------------

# Why a thread stood still because the code told it to: ``queue`` (a
# coalescer future, a single-flight leader), ``map`` (the request
# thread's wait for its own mappers), ``device`` (a device_get a request
# thread makes itself), ``lock`` (a contended process- or view-wide lock).
BLOCKED_KINDS = ("queue", "map", "device", "lock")
_SLOT = {k: i for i, k in enumerate(BLOCKED_KINDS, 1)}
_ZERO = (0.0,) * (1 + len(BLOCKED_KINDS))


class _Blocked(threading.local):
    # This thread's running total of ms blocked, ``(sum, *by kind)``:
    # a tuple replaced at every addition, so a span notes it at open and
    # at close by reference — no copy, and ``is`` says nothing was added.
    total = _ZERO


_blocked = _Blocked()


def _add_blocked(kind: str, ms: float) -> None:
    b = list(_blocked.total)
    b[0] += ms
    b[_SLOT[kind]] += ms
    _blocked.total = tuple(b)


class blocked:
    """``with trace.blocked("queue"): fut.result()`` — the block is a
    wait the code chose, and its wall time joins the thread's total of
    that kind, which every span open on the thread reads at its close
    (``blocked_ms``).  ``t1`` is the monotonic time of the exit and
    ``ms`` the wait.  Outside any span (a process without a tracer)
    nothing is timed, unless the caller counts the wait itself
    (``untraced``).

    A lock is wrapped only where it is process- or view-wide, and only
    the acquire that has to wait; and what the wait costs is paid
    OUTSIDE the lock — ``begin`` before the blocking acquire, ``stop``
    (one clock read) once it is held, ``settle`` after the release::

        wait = None
        if not mu.acquire(blocking=False):
            wait = trace.blocked("lock").begin()
            mu.acquire()
            wait.stop()
        try: ...
        finally:
            mu.release()
            if wait is not None:
                wait.settle()

    Every microsecond added under a lock that eight threads meet at
    makes the next acquire likelier to wait too (PERF.md, PR 38)."""

    __slots__ = ("kind", "untraced", "t0", "t1", "ms")

    def __init__(self, kind: str, untraced: bool = False):
        self.kind = kind
        self.untraced = untraced
        self.t0 = self.t1 = None
        self.ms = 0.0

    def begin(self) -> "blocked":
        if self.untraced or _current_span.get() is not None:
            self.t0 = _now()
        return self

    def stop(self) -> None:
        if self.t0 is not None and self.t1 is None:
            self.t1 = _now()
            self.ms = (self.t1 - self.t0) * 1000.0

    def settle(self) -> None:
        if self.t1 is not None:
            _add_blocked(self.kind, self.ms)

    __enter__ = begin

    def __exit__(self, exc_type, exc, tb) -> None:
        self.stop()
        self.settle()


def current_span() -> "Span | None":
    """The context-current span (None outside any trace) — lets layers
    without a Tracer handle (retry policy, clients) annotate the span
    they run under."""
    return _current_span.get()


def new_trace_id() -> str:
    return uuid.uuid4().hex  # 16 bytes hex


def new_span_id() -> str:
    # 8 bytes hex.  ``uuid4`` is a third of a span's cost (a
    # ``getrandom`` system call, which RELEASES THE GIL), and is kept on
    # purpose: drawn from the Mersenne Twister instead, the cells of
    # cached and light requests gained 8-11 % and the two cells whose
    # requests sweep 1,908 fragment locks under eight threads lost 60 %
    # (PERF.md, PR 38).  A span's open is where this server's request
    # threads hand the GIL over while they hold no lock; without it
    # every switch is a forced one, a third of them inside a lock.
    return uuid.uuid4().hex[:16]


def _leaf_span_id() -> str:
    # For a recorded interval that is nobody's parent (a hand-over):
    # 8 bytes hex with no system call, so that the two spans PR 38
    # added add no GIL hand-over of their own — on the dispatcher
    # between a launch's end and its ``set_result``, least of all.
    return "%016x" % _randbits(64)


class Span:
    """One timed operation within a trace.

    Usable as a context manager (activates itself as the current span
    for the dynamic extent, finishes on exit, and records the exception
    type on error paths).
    """

    __slots__ = (
        "tracer",
        "name",
        "trace_id",
        "span_id",
        "parent_id",
        "start",
        "opened",
        "_cpu0",
        "_blocked0",
        "_thread",
        "duration_ms",
        "cpu_ms",
        "blocked_ms",
        "tags",
        "_token",
        "_anno",
    )

    def __init__(self, tracer, name: str, trace_id: str, parent_id: str | None,
                 tags: dict | None = None):
        self.tracer = tracer
        self.name = name
        self.trace_id = trace_id
        self.span_id = new_span_id()
        self.parent_id = parent_id
        # monotonic; ``start`` is the same moment on the wall clock
        self.opened = t0 = _now()
        self.start = t0 + _wall_offset
        self._cpu0 = time.thread_time()
        self._blocked0 = _blocked.total
        self._thread = threading.get_ident()
        self.duration_ms: float | None = None
        # CPU time of the opening thread over the span, and the time it
        # stood blocked on purpose (a parent's includes its children's);
        # None when the span finished on another thread.
        self.cpu_ms: float | None = None
        self.blocked_ms: float | None = None
        self.tags = dict(tags) if tags else {}
        self._token = None
        self._anno = None

    def annotate(self, **tags) -> "Span":
        self.tags.update(tags)
        return self

    def activate(self):
        """Make this the current span; returns a token for deactivate()."""
        return _current_span.set(self)

    def deactivate(self, token) -> None:
        _current_span.reset(token)

    def _stop_clocks(self) -> None:
        self.duration_ms = (_now() - self.opened) * 1000.0
        if threading.get_ident() == self._thread:
            self.cpu_ms = (time.thread_time() - self._cpu0) * 1000.0
            b0, b1 = self._blocked0, _blocked.total
            if b1 is b0:
                self.blocked_ms = 0.0
            else:
                self.blocked_ms = b1[0] - b0[0]
                self.tags["blocked"] = {
                    k: round(b1[i] - b0[i], 3)
                    for k, i in _SLOT.items()
                    if b1[i] != b0[i]
                }

    def finish(self) -> None:
        if self.duration_ms is None:
            self._stop_clocks()
            self.tracer._record(self)

    def add_child(self, name: str, start: float, duration_ms: float, **tags):
        """Record an already finished child (see Tracer.add_span)."""
        return self.tracer.add_span(self, name, start, duration_ms, **tags)

    def __enter__(self) -> "Span":
        self._token = self.activate()
        anno = _annotation
        if anno is not None:
            self._anno = anno(self.name, trace_id=self.trace_id)
            self._anno.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if self._anno is not None:
            self._anno.__exit__(exc_type, exc, tb)
            self._anno = None
        if self._token is not None:
            self.deactivate(self._token)
            self._token = None
        if exc_type is not None:
            self.tags.setdefault("error", exc_type.__name__)
        self.finish()

    def to_dict(self) -> dict:
        return {
            "name": self.name,
            "span_id": self.span_id,
            "parent_id": self.parent_id,
            "start": self.start,
            "duration_ms": round(self.duration_ms, 3)
            if self.duration_ms is not None
            else None,
            "cpu_ms": round(self.cpu_ms, 3)
            if self.cpu_ms is not None
            else None,
            "blocked_ms": round(self.blocked_ms, 3)
            if self.blocked_ms is not None
            else None,
            "tags": self.tags,
        }


class SharedSpan(Span):
    """Work done once for several traces, on a thread that has no
    current span: a coalesced launch on the dispatcher.  While open it
    is that thread's current span and belongs to no trace; children
    recorded under it (a ``compile``) are kept, and :meth:`publish`
    records it and them under each waiter's span, so every waiter's
    trace shows the one launch at the same wall-clock ``start``, with
    the ``cpu_ms`` and ``blocked_ms`` of the thread that ran it."""

    __slots__ = ("children",)

    def __init__(self, name: str, trace_id: str = "", **tags):
        # ``trace_id`` (the first waiter's) only names the profile's
        # annotation; no tracer has that trace open for this span.
        super().__init__(NOP_TRACER, name, trace_id, None, tags)
        self.children: list[tuple] = []

    def finish(self) -> None:
        if self.duration_ms is None:
            self._stop_clocks()

    def add_child(self, name: str, start: float, duration_ms: float, **tags):
        self.children.append((name, start, duration_ms, tags))

    def publish(self, parents) -> list:
        """The span recorded under each of ``parents``, in their order
        (None for a parent that is None): a caller hangs what belongs
        to one waiter alone under that waiter's copy."""
        out = []
        for parent in parents:
            if parent is None:
                out.append(None)
                continue
            sp = parent.add_child(
                self.name, self.start, self.duration_ms,
                cpu_ms=self.cpu_ms, blocked_ms=self.blocked_ms, **self.tags
            )
            for name, start, ms, tags in self.children:
                sp.add_child(name, start, ms, **tags)
            out.append(sp)
        return out


class Tracer:
    """Collects spans into traces; retains finished traces in a ring."""

    def __init__(self, capacity: int = 64):
        self.capacity = max(1, int(capacity))
        self._mu = threading.Lock()
        # trace_id -> {"root": Span, "spans": [span dicts], "started": t}
        self._open: dict[str, dict] = {}
        self._ring: "deque[dict]" = deque(maxlen=self.capacity)

    # -- span creation --------------------------------------------------

    def current(self) -> Span | None:
        return _current_span.get()

    def start_trace(
        self,
        name: str,
        trace_id: str | None = None,
        parent_span_id: str | None = None,
        **tags,
    ) -> Span:
        """Open a trace root.  ``trace_id``/``parent_span_id`` continue a
        propagated trace (the remote leg of a fan-out); both None starts
        a fresh trace."""
        span = Span(self, name, trace_id or new_trace_id(), parent_span_id, tags)
        with self._mu:
            self._open[span.trace_id] = {"root": span, "spans": []}
        return span

    def span(self, name: str, parent: Span | None = None, **tags) -> Span:
        """A child of ``parent`` (default: the context-current span).
        Without any active trace the span still times and works as a
        context manager, but is never retained."""
        parent = parent or _current_span.get()
        if parent is None:
            return Span(self, name, new_trace_id(), None, tags)
        return Span(self, name, parent.trace_id, parent.span_id, tags)

    def add_span(
        self, parent: Span, name: str, start: float, duration_ms: float,
        cpu_ms: float | None = None, blocked_ms: float | None = None,
        leaf: bool = False, **tags,
    ) -> Span:
        """Record a FINISHED child of ``parent`` with an explicit
        wall-clock ``start`` and duration — work another thread did for
        this trace (the coalescer's dispatcher has no current span), or
        an interval between two threads (a hand-over).  ``cpu_ms`` and
        ``blocked_ms`` are that thread's where one thread did all of it,
        else None; ``leaf`` says nothing will be recorded under it (its
        id is then drawn without a system call: ``_leaf_span_id``); once
        the trace is final it is dropped like any late span."""
        # No clock of this thread belongs to the interval: the fields a
        # record and a parent need, and no read of any.
        span = Span.__new__(Span)
        span.tracer = self
        span.name = name
        span.trace_id = parent.trace_id
        span.span_id = _leaf_span_id() if leaf else new_span_id()
        span.parent_id = parent.span_id
        span.start = start
        span.duration_ms = duration_ms
        span.cpu_ms = cpu_ms
        span.blocked_ms = blocked_ms
        span.tags = tags
        self._record(span)
        return span

    # -- recording ------------------------------------------------------

    def _record(self, span: Span) -> None:
        with self._mu:
            ent = self._open.get(span.trace_id)
            if ent is None:
                return  # the trace is already final
            if ent["root"] is span:
                return  # the root records at finish_root
            if len(ent["spans"]) < MAX_SPANS_PER_TRACE:
                ent["spans"].append(span.to_dict())

    def absorb(self, payload: "str | dict") -> None:
        """Merge a remote node's exported spans (the ``X-Trace-Spans``
        response header) into the matching open trace."""
        try:
            if isinstance(payload, str):
                payload = json.loads(payload)
            trace_id = payload["trace_id"]
            spans = payload["spans"]
        except (ValueError, KeyError, TypeError):
            return
        with self._mu:
            ent = self._open.get(trace_id)
            if ent is None:
                return
            room = MAX_SPANS_PER_TRACE - len(ent["spans"])
            ent["spans"].extend(
                s for s in spans[:room] if isinstance(s, dict)
            )

    def finish_root(self, root: Span) -> dict | None:
        """Finish the trace root, finalize the trace, retain it in the
        ring, and return the trace record."""
        if root.duration_ms is None:
            root._stop_clocks()
        with self._mu:
            ent = self._open.pop(root.trace_id, None)
            if ent is None:
                return None
            record = {
                "trace_id": root.trace_id,
                "name": root.name,
                "start": root.start,
                "duration_ms": round(root.duration_ms, 3),
                "spans": [root.to_dict()] + ent["spans"],
            }
            self._ring.append(record)
            return record

    # -- consumption ----------------------------------------------------

    def traces(self, min_ms: float = 0.0) -> list[dict]:
        """Retained traces, most recent last; ``min_ms`` filters on the
        root duration."""
        with self._mu:
            out = list(self._ring)
        if min_ms > 0:
            out = [t for t in out if t["duration_ms"] >= min_ms]
        return out

    def remote_headers(self, span: Span) -> dict[str, str]:
        """Headers that continue ``span``'s trace on a remote node."""
        return {TRACE_HEADER: span.trace_id, SPAN_HEADER: span.span_id}

    @staticmethod
    def export_payload(record: dict) -> str:
        """Compact JSON for the ``X-Trace-Spans`` response header."""
        return json.dumps(
            {
                "trace_id": record["trace_id"],
                "spans": record["spans"][:MAX_EXPORT_SPANS],
            },
            separators=(",", ":"),
        )


def stage_breakdown(record: dict) -> dict[str, float]:
    """Total milliseconds per span name — the slow-query log's per-stage
    breakdown.  The root span is excluded (it IS the total)."""
    root_id = record["spans"][0]["span_id"] if record["spans"] else None
    out: dict[str, float] = {}
    for s in record["spans"]:
        if s["span_id"] == root_id:
            continue
        if s["duration_ms"] is not None:
            out[s["name"]] = round(out.get(s["name"], 0.0) + s["duration_ms"], 3)
    return out


class _NopSpan(Span):
    """Inert span: annotate/finish/context-manager are no-ops beyond
    context activation (children of a nop span are nop spans)."""

    def __init__(self):  # noqa: D107 — singleton, no tracer
        pass

    def annotate(self, **tags):
        return self

    def activate(self):
        return None

    def deactivate(self, token):
        pass

    def finish(self):
        pass

    def add_child(self, name, start, duration_ms, **tags):
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        pass


NOP_SPAN = _NopSpan()


class NopTracer(Tracer):
    """Tracing disabled: every span site costs one method call."""

    def __init__(self):
        super().__init__(capacity=1)

    def start_trace(self, name, trace_id=None, parent_span_id=None, **tags):
        return NOP_SPAN

    def span(self, name, parent=None, **tags):
        return NOP_SPAN

    def add_span(self, parent, name, start, duration_ms, **tags):
        return NOP_SPAN

    def absorb(self, payload):
        pass

    def finish_root(self, root):
        return None

    def traces(self, min_ms: float = 0.0):
        return []

    def remote_headers(self, span):
        return {}


NOP_TRACER = NopTracer()
