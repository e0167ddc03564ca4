"""Cross-query coalescing: one device launch for many concurrent queries.

Without it every query dispatches its OWN fused-XLA launch, so
per-launch dispatch overhead, GIL contention, and host assembly
dominate, not compute.  The idiom that closes this gap in production
inference stacks is continuous
micro-batching, and the compile model here is already shaped for it:
``plan.compiled_batched`` keys programs by (tree shape, reduce kind) and
vmaps over a leading batch axis, so concurrent queries that share that
compile key can ride ONE launch by concatenating along the axis the
program already batches over.

Scheduling is CONTINUOUS, not windowed: a lone query on an idle device
dispatches immediately (``max_wait_us`` is only an optional accumulation
backstop, default 0); while a launch is in flight on the dispatcher
thread, new arrivals accumulate in per-compile-key queues and the next
drain takes them all.  Under serial load every query gets its own launch
at native latency; under concurrent load occupancy rises automatically
to whatever the arrival rate sustains.

Batch construction, per drained key:

* **Identity dedup.**  Waiters whose leaf batches are the SAME assembled
  array (the batch-cache hot path: a query storm over one cached entry)
  share one segment — and when the drain is one segment, the launch runs
  directly on that array with zero extra device work.  N queries, one
  launch, no copies.
* **Concatenation.**  Distinct single-device batches with the same
  compile key (expr shape, reduce kind, leaf count, words, device)
  concatenate along the leading axis, padded with cached all-zero rows
  to a power-of-two bucket so the jit cache stays bounded (one program
  per (tree shape, reduce, bucket)).  Pad rows are never scattered back
  to any waiter, so they need no masking out of per-slice reduces; the
  coalescer always launches the per-slice ``compiled_batched`` program
  (its "count" partials are int32-exact — one slice-row is <= 2^20
  bits — and each waiter host-sums only its own positions in unbounded
  Python ints, byte-identical to the limb total-count path).
* **Sharded batches dedup only.**  Mesh-sharded entries (multi-device
  hosts) still amortize duplicate waiters over one launch, but distinct
  sharded arrays are never concatenated — cross-sharding concatenation
  would move shards between devices mid-query.
* **Program-key fusion.**  Concatenation only merges queries sharing a
  compile key, so a realistic mix of DISTINCT Count/Range/Bitmap trees
  never batched and each re-streamed its planes.  With ``fuse`` on, a
  drain additionally pulls every other queue whose entries share the
  PROGRAM key (reduce kind, word geometry, device), lowers the distinct
  trees into one opcode/operand table (plan.lower_expr — expressions
  travel as DATA, like BSI predicates), and evaluates all of them in
  ONE interpreter pass (plan.interp_exec) over the union leaf set:
  K distinct queries, one launch, one pass over the resident planes.
  Identical queries share a lowered program, the emitter's value
  numbering dedups shared subtrees, and a tree that cannot lower (BSI
  aggregates, op-budget overflow) falls back to its own concat launch.
  Fused "count" results are the same per-slice int32 partials as the
  concat path — byte-identical totals.
* **Shared fetches.**  ``submit_fetch`` batches concurrent blocking
  device->host fetches (the folded TopN scorer's dominant residual)
  into one ``jax.device_get`` per drain, so DISTINCT concurrent TopN
  queries share a round trip the way PR-10's single-flight shared it
  for identical ones.

Every fragment-plane-bearing pool key in a drained batch is pinned via
the PR-3 residency pool for the launch's dispatch+fetch, so LRU eviction
can never drop a mirror out from under a coalesced program.

Observability: ``exec.coalesce.launches`` / ``coalescedQueries`` /
``padWaste`` counters and an ``exec.coalesce.batchOccupancy`` histogram;
the executor's per-query ``coalesce`` trace span carries the launch's
occupancy and row stats (and through it the slow-query log's batch
stats).  The dispatcher times each launch once as a ``launch`` span
(trace.SharedSpan) and records it under every waiter's ``coalesce``
span, so that span's self time is the queue wait alone.  The two
hand-overs that flank the launch are spans too, children of each
waiter's ``launch``: ``handoff.queue`` (from ``submit`` to the start of
the launch that serves the item; ``dispatcher`` says whether it stood
idle at the submit) and ``handoff.wake`` (from just before
``set_result`` to the return of the waiter's ``result()``:
:func:`await_result`).  The dispatcher's own life is counted as
``idle`` / ``launch`` / ``host`` seconds and ``cycles``
(``exec.dispatcher.*`` at every scrape, :meth:`CoalesceScheduler.gauges`).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from dataclasses import dataclass

import numpy as np

from pilosa_tpu import device as device_mod
from pilosa_tpu.obs import perf as perf_mod
from pilosa_tpu.obs import trace
from pilosa_tpu.obs.stats import NopStatsClient

DEFAULT_MAX_BATCH = 64
DEFAULT_MAX_WAIT_US = 0
# Most DISTINCT expression programs one fused interpreter launch may
# carry ([exec] fuse-max-programs); < 2 disables fusion entirely.
DEFAULT_FUSE_MAX_PROGRAMS = 16
# Leaf-row budget for one fused launch's combined array: segment sets
# past it split into further launches (the leaf-axis analogue of
# MAX_CONCAT_ROWS — the concat materializes a transient copy, so this
# bounds device memory, not correctness).  64 leaves x 128 KiB = 8 MiB
# per batch row.
MAX_FUSE_LEAVES = 64
# Scratch budget for one fused launch, per device.  The interpreter
# holds a register file of (leaf bucket + op bucket) slice-rows for
# EVERY batch row at once, beside the combined leaf array, so its
# footprint grows with slices x programs where MAX_FUSE_LEAVES only
# counts leaves.  What XLA allots it is FUSE_SCRATCH_FACTOR register
# files, not one: compiled for a v5e at 1024 batch rows the program's
# temp is 5.0 GiB at 4 leaves + 8 ops (a register file of 1.5 GiB),
# 5.4 at 8 + 8, 7.8 at 8 + 16 and at 16 + 8, 10.7 at 16 + 16
# (``memory_analysis`` of the chip's own compiler; tests/
# test_topn_deployment.py holds the factor).  Counted as one register
# file, launches of 5-8 GiB passed the budget on a chip whose planes
# take 8 of its 16 GB, and every batch assembled meanwhile failed for
# memory (PERF.md, PR 31).  Program sets past the budget split into
# further launches, and a pair that still does not fit launches each
# tree's own program; like the leaf budget this bounds device memory,
# not correctness.
MAX_FUSE_BYTES = 2 << 30
FUSE_SCRATCH_FACTOR = 3.4
# Reduce kinds the interpreter can evaluate; "agg" trees reduce inside
# the expression (BSI aggregates) and stay on the per-compile-key path.
# "total" is the ICI-reduced count: per-register limb pairs summed
# across the slice axis ON DEVICE (psum over the mesh for sharded
# batches), so a fused launch of K distinct Count queries returns 8·K
# bytes instead of K per-slice partial vectors.
_FUSABLE_REDUCES = frozenset({"count", "row", "total"})
# Sentinel queue key for shared device->host fetches (submit_fetch):
# concurrent TopN score fetches drain in ONE jax.device_get round trip.
_FETCH_KEY = ("__fetch__",)
# Row budget for one concatenated launch: segments beyond it split into
# further launches.  Entry batches are already pow2-padded per query, so
# this bounds transient device memory (concatenation materializes a
# copy), not correctness.
MAX_CONCAT_ROWS = 4096
# Largest all-zero LEAF pad block the scheduler keeps between launches
# (_leaf_pad_zeros); a larger one is a device-side fill of the launch
# that needs it.
ZERO_CACHE_MAX_BYTES = 32 << 20
# Backstop bound on a waiter's Future wait: a wedged device call must
# surface as a failed query, not a hung request thread.  Waiters with a
# query deadline clamp this to their REMAINING budget and detach on
# expiry without cancelling the shared launch (executor._coalesce_eval)
# — an expired waiter never poisons the batch for the others.
RESULT_TIMEOUT_S = 600.0


class CoalesceClosed(RuntimeError):
    """Raised by submit() after close(); callers fall back to a direct
    (uncoalesced) launch."""


def consume_abandoned(stats):
    """Done-callback for a coalesce future whose waiter detached on
    deadline expiry: retrieves the eventual batch-level launch error so
    it is COUNTED (``exec.coalesce.abandonedErrors``) instead of
    surfacing as per-future "exception was never retrieved" GC log spam
    — with every waiter detached, nothing else would ever observe it."""

    def _cb(fut):
        try:
            exc = fut.exception()
        except Exception:  # noqa: BLE001 — cancelled futures
            return
        if exc is not None and stats is not None:
            stats.count("exec.coalesce.abandonedErrors")

    return _cb


def await_result(fut: Future, timeout: float | None):
    """``fut.result(timeout)`` for a future of :meth:`submit` /
    :meth:`submit_fetch`, as the waiter's trace should see it: the wait
    is time blocked on purpose (kind ``queue``), and the interval from
    the dispatcher's ``set_result`` to this thread running again is the
    ``handoff.wake`` child of the waiter's ``launch`` — with the result
    ready, how long the waiter needed to get the GIL back."""
    with trace.blocked("queue") as wait:
        out = fut.result(timeout=timeout)
    handoff = getattr(fut, "handoff", None)
    if handoff is not None and wait.t1 is not None:
        launch_span, resolved, waiters = handoff
        launch_span.add_child(
            "handoff.wake", trace.wall(resolved),
            (wait.t1 - resolved) * 1e3, leaf=True, waiters=waiters,
        )
    return out


@dataclass
class _Item:
    batch: object
    future: Future
    pin_keys: tuple
    # Leaf identity keys (executor._cached_batch leaf_keys): one per
    # batch column, equal keys <=> byte-identical columns.  The fused
    # launch collapses shared columns into ONE union register, so the
    # pass streams each distinct plane row once however many queries
    # reference it.  None = no identities known (columns stay unique).
    leaf_keys: "tuple | None" = None
    # Submitting query's trace id and current span (its ``coalesce``),
    # captured at submit time: the dispatcher thread has no trace
    # contextvar, so the launch telemetry's slowest-launch attribution
    # and the ``launch`` span's parent ride the item.
    trace_id: str = ""
    span: "trace.Span | None" = None
    # The hand-over's stamps: when the item was queued (monotonic),
    # whether the dispatcher stood in ``_cv.wait()`` at that moment, and
    # this waiter's copy of the ``launch`` span that served it.
    submitted: float = 0.0
    dispatcher_idle: bool = False
    launch_span: "trace.Span | None" = None

    def resolve(self, value, waiters: int) -> None:
        """``set_result``, stamped just before for ``handoff.wake``."""
        if self.launch_span is not None:
            self.future.handoff = (
                self.launch_span, time.monotonic(), waiters
            )
        self.future.set_result(value)


def _placement(batch) -> tuple:
    """Hashable placement token for the compile key: single-device
    batches group (and concatenate) per device; sharded batches group by
    their full sharding and are marked concat-ineligible."""
    try:
        devs = list(batch.devices())
    except Exception:  # noqa: BLE001 — non-jax stand-ins in unit tests
        devs = []
    if len(devs) == 1:
        return (str(devs[0]), False)
    try:
        return (repr(batch.sharding), True)
    except Exception:  # noqa: BLE001
        return (tuple(sorted(str(d) for d in devs)), True)


class CoalesceScheduler:
    """Per-compile-key batch queues + one dispatcher thread.

    ``submit(expr, reduce, batch, pin_keys)`` returns a Future resolving
    to ``(results, info)`` where ``results`` is the host ndarray of this
    entry's rows of the launch output (``[n_rows, words]`` for "row",
    ``[n_rows]`` int32 partials for "count") and ``info`` the launch's
    batch stats for trace annotation.
    """

    def __init__(
        self,
        max_batch: int = DEFAULT_MAX_BATCH,
        max_wait_us: int = DEFAULT_MAX_WAIT_US,
        stats=None,
        fuse: bool = True,
        fuse_max_programs: int = DEFAULT_FUSE_MAX_PROGRAMS,
        health=None,
    ):
        self.max_batch = max(1, int(max_batch))
        self.max_wait_us = max(0, int(max_wait_us))
        # Device-health manager (device/health.py), wired by the Server
        # alongside the executor's: collective-bearing launches run
        # under its hung-collective watchdog and the collective path's
        # quarantine breaker; errors cross the waiter futures, where
        # each waiter's guard fails over to the host evaluator
        # independently.  None = plain serialized collectives.
        self.health = health
        # Multi-query fusion ([exec] fuse): a drain additionally pulls
        # every other queue whose entries share this key's PROGRAM key
        # (reduce kind, word geometry, device), lowers the distinct
        # trees to one opcode table, and evaluates them all in ONE
        # interpreter pass over the union leaf set (plan.interp_exec).
        self.fuse = bool(fuse) and int(fuse_max_programs) >= 2
        self.fuse_max_programs = max(1, int(fuse_max_programs))
        self.stats = stats or NopStatsClient()
        self._mu = threading.Lock()
        self._cv = threading.Condition(self._mu)
        # key -> deque[_Item]; OrderedDict gives FIFO across keys (the
        # key whose first item arrived earliest drains first).
        self._queues: "OrderedDict[tuple, deque]" = OrderedDict()
        self._closed = False
        # device -> {(pad, tail...): cached all-zero pad rows}
        self._zeros: dict = {}
        # counters (mirrored to self.stats; kept here for snapshot()/bench)
        self._launches = 0
        self._queries = 0
        self._pad_rows = 0
        self._launched_rows = 0
        self._max_occupancy = 0
        # fusion counters (exec.interp.*)
        self._fused_launches = 0
        self._fused_queries = 0
        self._fused_programs = 0
        self._fused_ops = 0
        self._fuse_dedup_hits = 0
        self._fuse_shared_leaves = 0
        self._fuse_fallbacks = 0
        self._fetch_launches = 0
        self._fetch_arrays = 0
        # The dispatcher's life, in seconds by what it was doing (plain
        # attributes the loop alone adds to): idle in ``_cv.wait()``,
        # inside a ``launch`` span, and host — all the rest of a cycle.
        self._idle_s = 0.0
        self._launch_s = 0.0
        self._host_s = 0.0
        self._cycles = 0
        # monotonic time the dispatcher went idle, None while it works;
        # when its loop began and ended
        self._idle_since: float | None = None
        self._life: tuple = (None, None)
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name="exec-coalesce"
        )
        self._thread.start()

    # ------------------------------------------------------------------
    # producer side
    # ------------------------------------------------------------------

    def submit(
        self, expr: tuple, reduce: str, batch, pin_keys=(), leaf_keys=None
    ) -> Future:
        """Enqueue one assembled leaf batch (``uint32[n, n_leaves,
        words]``) for a coalesced ``compiled_batched(expr, reduce)``
        launch.  ``leaf_keys`` (optional) are per-column identity
        tokens enabling union-leaf sharing in fused launches."""
        key = (expr, reduce, tuple(batch.shape[1:]), _placement(batch))
        fut: Future = Future()
        if leaf_keys is not None and len(leaf_keys) != int(batch.shape[1]):
            leaf_keys = None
        item = _Item(
            batch=batch,
            future=fut,
            pin_keys=tuple(k for k in pin_keys if k is not None),
            leaf_keys=leaf_keys,
            trace_id=perf_mod.current_trace_id(),
            span=trace.current_span(),
        )
        self._enqueue(key, item)
        return fut

    def submit_fetch(self, arrays) -> Future:
        """Enqueue a device->host fetch of ``arrays`` (a list of device
        arrays); resolves to ``(host_arrays, info)``.  All fetch items
        pending at a drain share ONE ``jax.device_get`` round trip —
        the TopN(src) fetch residual folds across DISTINCT concurrent
        queries this way (PR-10's single-flight only covered identical
        ones)."""
        fut: Future = Future()
        item = _Item(
            batch=list(arrays),
            future=fut,
            pin_keys=(),
            trace_id=perf_mod.current_trace_id(),
            span=trace.current_span(),
        )
        self._enqueue(_FETCH_KEY, item)
        return fut

    def _enqueue(self, key, item: _Item) -> None:
        item.submitted = time.monotonic()
        with self._cv:
            if self._closed:
                raise CoalesceClosed("coalescer closed")
            item.dispatcher_idle = self._idle_since is not None
            q = self._queues.get(key)
            if q is None:
                q = self._queues[key] = deque()
            q.append(item)
            self._cv.notify()

    def close(self) -> None:
        with self._cv:
            if self._closed:
                return
            self._closed = True
            pending = [it for q in self._queues.values() for it in q]
            self._queues.clear()
            self._cv.notify_all()
        for it in pending:
            if not it.future.done():
                it.future.set_exception(CoalesceClosed("coalescer closed"))
        self._thread.join(timeout=10)

    def snapshot(self) -> dict:
        """Counters for bench artifacts and tests."""
        with self._mu:
            launches = self._launches
            queries = self._queries
            fused_launches = self._fused_launches
            return {
                "launches": launches,
                "queries": queries,
                "pad_rows": self._pad_rows,
                "launched_rows": self._launched_rows,
                "max_occupancy": self._max_occupancy,
                "mean_occupancy": (
                    round(queries / launches, 3) if launches else None
                ),
                "fused_launches": fused_launches,
                "fused_queries": self._fused_queries,
                "fused_programs": self._fused_programs,
                "fused_ops": self._fused_ops,
                "fuse_dedup_hits": self._fuse_dedup_hits,
                "fuse_shared_leaves": self._fuse_shared_leaves,
                "fuse_fallbacks": self._fuse_fallbacks,
                "mean_fused_per_launch": (
                    round(self._fused_queries / fused_launches, 3)
                    if fused_launches
                    else None
                ),
                "fetch_launches": self._fetch_launches,
                "fetch_arrays": self._fetch_arrays,
                "dispatcher": self._dispatcher_locked(),
            }

    def _dispatcher_locked(self) -> dict:
        """The dispatcher's life so far in ms (callers hold ``_mu``).
        A wait in progress counts up to now; the cycle in progress
        lands when it ends."""
        idle = self._idle_s
        if self._idle_since is not None:
            idle += time.monotonic() - self._idle_since
        born, died = self._life
        life = 0.0 if born is None else (died or time.monotonic()) - born
        return {
            "idle_ms": round(idle * 1e3, 3),
            "launch_ms": round(self._launch_s * 1e3, 3),
            "host_ms": round(self._host_s * 1e3, 3),
            "cycles": self._cycles,
            "life_ms": round(life * 1e3, 3),
        }

    def gauges(self) -> dict:
        """``exec.dispatcher.*`` for a ``/metrics`` scrape: a dispatcher
        that is never idle with a low launch share is host-bound."""
        with self._mu:
            d = self._dispatcher_locked()
        return {
            "exec.dispatcher.idleMs": d["idle_ms"],
            "exec.dispatcher.launchMs": d["launch_ms"],
            "exec.dispatcher.hostMs": d["host_ms"],
            "exec.dispatcher.cycles": d["cycles"],
        }

    # ------------------------------------------------------------------
    # dispatcher
    # ------------------------------------------------------------------

    def _drain_locked(self, key, items: list) -> None:
        q = self._queues.get(key)
        while q and len(items) < self.max_batch:
            items.append(q.popleft())
        if q is None:
            return
        if not q:
            del self._queues[key]
        else:
            # max_batch left items behind: rotate the key behind the
            # others so one hot query shape cannot starve the rest.
            self._queues.move_to_end(key)

    def _loop(self) -> None:
        # ``mark``: up to where the thread's life is accounted for.
        mark = time.monotonic()
        self._life = (mark, None)
        while True:
            with self._cv:
                if not self._closed and not self._queues:
                    now = time.monotonic()
                    self._host_s += now - mark
                    self._idle_since = now
                    while not self._closed and not self._queues:
                        self._cv.wait()
                    mark = time.monotonic()
                    self._idle_s += mark - now
                    self._idle_since = None
                if self._closed:
                    now = time.monotonic()
                    self._host_s += now - mark
                    self._life = (self._life[0], now)
                    return
                key = next(iter(self._queues))
                items: list = []
                self._drain_locked(key, items)
            if self.max_wait_us and len(items) < self.max_batch:
                # Optional accumulation backstop: linger at most
                # max_wait_us for same-key company before launching.
                # 0 (the default) launches immediately — the in-flight
                # launch below is the only accumulation window.
                deadline = time.monotonic() + self.max_wait_us / 1e6
                with self._cv:
                    while len(items) < self.max_batch and not self._closed:
                        if key in self._queues:
                            self._drain_locked(key, items)
                            continue
                        remaining = deadline - time.monotonic()
                        if remaining <= 0:
                            break
                        self._cv.wait(timeout=remaining)
            # Program-key tier: a fusable drain additionally pulls every
            # OTHER queue whose entries share this key's program key
            # (reduce, word geometry, device) — the mixed batch of
            # distinct trees the interpreter evaluates in one pass.
            extra: list = []
            fk = self._fuse_key(key)
            if fk is not None:
                with self._cv:
                    for k2 in list(self._queues):
                        if 1 + len(extra) >= self.fuse_max_programs:
                            break
                        if len(items) + sum(
                            len(its) for _, its in extra
                        ) >= self.max_batch:
                            break
                        if k2 == key or self._fuse_key(k2) != fk:
                            continue
                        its: list = []
                        self._drain_locked(k2, its)
                        if its:
                            extra.append((k2, its))
            launched = self._launch_s
            try:
                # The launch (dispatch + fetch) runs HERE, on the
                # dispatcher thread — while it is in flight, new
                # arrivals queue up and the next iteration drains them
                # in one batch.  That in-flight window IS the
                # continuous-batching accumulation.
                self._launch(key, items, extra)
            except BaseException as e:  # noqa: BLE001 — crosses futures
                exc = e if isinstance(e, Exception) else RuntimeError(repr(e))
                for it in items + [it for _, its in extra for it in its]:
                    if not it.future.done():
                        it.future.set_exception(exc)
            # The cycle's launch spans have added themselves to
            # ``_launch_s`` (_publish_launch); the rest of it is host.
            now = time.monotonic()
            self._host_s += (now - mark) - (self._launch_s - launched)
            self._cycles += 1
            mark = now

    def _run_collective(self, fn):
        """One collective-bearing dispatch+fetch: watchdogged through
        the health manager when wired (errors and trips cross the
        waiter futures), plain serialized otherwise.  The chaos
        checkpoint (``device.launch`` path=``collective``) sits inside
        the watched body so an injected kind=hang wedges exactly where
        a real all-reduce rendezvous would."""
        from pilosa_tpu.testing import faults

        def body():
            faults.check("device.launch", path="collective")
            return fn()

        if self.health is not None:
            return self.health.run_collective(body)
        from pilosa_tpu.exec import plan

        with plan.collective_launch():
            return body()

    def _fuse_key(self, key) -> tuple | None:
        """The program-key tier's grouping token: queues whose entries
        share it may lower into ONE interpreter launch.  None = not
        fusable (fusion off, fetch items, "agg" reduce).  Sharded
        batches ARE fusable with each other when their sharding token
        matches: the fused concat runs along the LEAF axis, which
        leaves the slice-axis sharding untouched — unlike the concat
        path's slice-axis merge, no shard ever moves devices."""
        if not self.fuse or key == _FETCH_KEY:
            return None
        _expr, reduce, tail, placement = key
        if reduce not in _FUSABLE_REDUCES:
            return None
        # words + full placement token (device, or the sharding repr):
        # the geometry every fused segment must share (the leading
        # slice axis groups later, per launch).
        return (reduce, tail[-1], placement)

    @staticmethod
    def _launch_span(site: str, items: list, rows: int) -> trace.SharedSpan:
        """The ``launch`` span of one dispatch+fetch, open from just
        before the compiled call to the end of ``device_get``."""
        return trace.SharedSpan(
            "launch", items[0].trace_id,
            site=site, queries=len(items), rows=rows,
        )

    def _publish_launch(self, ls, items: list, dispatch_ms: float) -> None:
        """Record the finished launch under every waiter's span —
        BEFORE the futures resolve: a waiter that has its result may
        finish its trace, and a span for a final trace is dropped —
        and under each waiter's copy its ``handoff.queue``: from its
        ``submit`` to the start of this launch."""
        self._launch_s += ls.duration_ms / 1e3
        ls.annotate(
            dispatch_ms=round(dispatch_ms, 3), first_call=bool(ls.children)
        )
        started = ls.opened
        for it, sp in zip(items, ls.publish([it.span for it in items])):
            if sp is None:
                continue
            it.launch_span = sp
            sp.add_child(
                "handoff.queue", trace.wall(it.submitted),
                (started - it.submitted) * 1e3, leaf=True,
                dispatcher="idle" if it.dispatcher_idle else "busy",
            )

    def _launch(self, key, items: list, extra=()) -> None:
        if key == _FETCH_KEY:
            self._launch_fetch(items)
            return
        expr, reduce, _tail, placement = key
        if extra:
            self._launch_fused(reduce, [(key, items)] + list(extra))
            return
        if (
            reduce == "total"
            and self.fuse
            and len({id(it.batch) for it in items}) > 1
        ):
            # Same-compile-key Count entries over DISTINCT batches
            # cannot concatenate under "total" (each launch reduces to
            # one scalar limb pair) — the interpreter evaluates them as
            # distinct programs in ONE pass instead, preserving the
            # concat path's one-launch sharing.
            self._launch_fused(reduce, [(key, items)])
            return
        self._fallback_launch(key, items)

    def _fallback_launch(self, key, items: list) -> None:
        """The per-compile-key launch semantics fusion falls back to:
        concat for single-device batches, identity-dedup-only for
        sharded ones (cross-array slice-axis concatenation would move
        shards between devices mid-query).  "total" reduces to one
        scalar limb pair per batch, so it can never concatenate —
        identity dedup only, through the limb total-count program."""
        expr, reduce, _tail, placement = key
        if reduce == "total":
            groups: "OrderedDict[int, list]" = OrderedDict()
            for it in items:
                groups.setdefault(id(it.batch), []).append(it)
            for grp in groups.values():
                self._launch_total(expr, grp)
            return
        if not placement[1]:
            self._launch_concat(expr, reduce, items)
            return
        groups = OrderedDict()
        for it in items:
            groups.setdefault(id(it.batch), []).append(it)
        for grp in groups.values():
            self._launch_concat(expr, reduce, grp)

    def _launch_total(self, expr, items: list) -> None:
        """One identity-deduped batch through the limb total-count
        program (plan.compiled_total_count): the cross-slice reduce
        runs on device — as an all-reduce over ICI when the batch is
        mesh-sharded — and every waiter receives the SAME int32[2]
        (hi, lo) limb pair, recombined executor-side."""
        import jax

        from pilosa_tpu.exec import plan

        batch = items[0].batch
        mesh = None
        try:
            from jax.sharding import NamedSharding

            sh = batch.sharding
            if isinstance(sh, NamedSharding) and len(batch.devices()) > 1:
                mesh = sh.mesh
        except Exception:  # noqa: BLE001 — non-jax stand-ins, old arrays
            mesh = None
        pins = {k for it in items for k in it.pin_keys}
        site = "collective" if mesh is not None else "total"
        t_disp = [0.0]  # set when the async dispatch returns (pre-fetch)
        with self._launch_span(
            site, items, int(batch.shape[0])
        ) as ls, device_mod.pool().pinned(*pins):
            if mesh is not None:
                # The program psums over the mesh: serialize with every
                # other collective launch in the process (see
                # plan.collective_launch — racing dispatches can
                # deadlock the all-reduce rendezvous).  With a health
                # manager wired, the serialized dispatch+fetch also
                # rides the launch watchdog: a hung rendezvous trips,
                # fails the waiters (who fall over to the host path
                # per-waiter), and quarantines the collective path.
                def _body():
                    out = plan.compiled_total_count(expr, mesh)(batch)
                    t_disp[0] = time.monotonic()
                    return np.asarray(jax.device_get(out))

                res = self._run_collective(_body)
            else:
                out = plan.compiled_total_count(expr, mesh)(batch)
                t_disp[0] = time.monotonic()
                res = np.asarray(jax.device_get(out))
        dispatch_ms = (t_disp[0] - ls.opened) * 1e3
        self._publish_launch(ls, items, dispatch_ms)
        if perf_mod.enabled():
            perf_mod.record_launch(
                site,
                reduce="total",
                queries=len(items),
                rows=int(batch.shape[0]),
                n_bytes=perf_mod.plane_bytes(
                    int(batch.shape[0]), int(np.prod(batch.shape[1:]))
                ),
                dispatch_ms=dispatch_ms,
                total_ms=ls.duration_ms,
                trace_id=items[0].trace_id,
            )
        with self._mu:
            self._launches += 1
            self._queries += len(items)
            self._launched_rows += int(batch.shape[0])
            if len(items) > self._max_occupancy:
                self._max_occupancy = len(items)
            launch_n = self._launches
        self.stats.count("exec.coalesce.launches")
        self.stats.count("exec.coalesce.coalescedQueries", len(items))
        self.stats.histogram("exec.coalesce.batchOccupancy", float(len(items)))
        info = {
            "launch": launch_n,
            "total": True,
            "batch_queries": len(items),
            "batch_segments": 1,
            "batch_rows": int(batch.shape[0]),
            "pad_rows": 0,
        }
        for it in items:
            it.resolve((res, info), len(items))

    def _launch_concat(self, expr, reduce, items: list) -> None:
        # Identity dedup: one segment per DISTINCT batch array.
        segs: list = []
        seg_of: dict[int, int] = {}
        seg_items: list[list] = []
        for it in items:
            i = seg_of.get(id(it.batch))
            if i is None:
                i = len(segs)
                seg_of[id(it.batch)] = i
                segs.append(it.batch)
                seg_items.append([])
            seg_items[i].append(it)
        # Greedy row-budget chunks over the distinct segments.
        lo = 0
        while lo < len(segs):
            hi = lo + 1
            rows = int(segs[lo].shape[0])
            while (
                hi < len(segs)
                and rows + int(segs[hi].shape[0]) <= MAX_CONCAT_ROWS
            ):
                rows += int(segs[hi].shape[0])
                hi += 1
            self._launch_one(
                expr,
                reduce,
                segs[lo:hi],
                [it for sub in seg_items[lo:hi] for it in sub],
                seg_items[lo:hi],
            )
            lo = hi

    def _launch_one(self, expr, reduce, segs, items, seg_items) -> None:
        import jax
        import jax.numpy as jnp

        from pilosa_tpu.exec import plan

        n_rows = [int(b.shape[0]) for b in segs]
        total = sum(n_rows)
        pad = 0
        if len(segs) == 1:
            dev_in = segs[0]
        else:
            # The canonical slice-axis bucket (plan.slice_bucket): the
            # concatenated launch lands on the same compiled program a
            # direct query over that bucket would.
            bucket = plan.slice_bucket(total)
            pad = bucket - total
            parts = list(segs)
            if pad:
                parts.append(self._pad_zeros(pad, segs[0]))
            dev_in = jnp.concatenate(parts, axis=0)
        pins = {k for it in items for k in it.pin_keys}
        with self._launch_span(
            "coalesce", items, total
        ) as ls, device_mod.pool().pinned(*pins):
            out = plan.compiled_batched(expr, reduce)(dev_in)
            t_disp = time.monotonic()
            res = np.asarray(jax.device_get(out))
        dispatch_ms = (t_disp - ls.opened) * 1e3
        self._publish_launch(ls, items, dispatch_ms)
        # Logical bytes are the PRE-pad rows: pad rows are bucketing
        # overhead, not useful plane traffic.
        if perf_mod.enabled():
            perf_mod.record_launch(
                "coalesce",
                reduce=reduce,
                queries=len(items),
                rows=total,
                n_bytes=perf_mod.plane_bytes(
                    total, int(np.prod(segs[0].shape[1:]))
                ),
                dispatch_ms=dispatch_ms,
                total_ms=ls.duration_ms,
                trace_id=items[0].trace_id,
            )
        with self._mu:
            self._launches += 1
            self._queries += len(items)
            self._pad_rows += pad
            self._launched_rows += total + pad
            if len(items) > self._max_occupancy:
                self._max_occupancy = len(items)
            launch_n = self._launches
        self.stats.count("exec.coalesce.launches")
        self.stats.count("exec.coalesce.coalescedQueries", len(items))
        if pad:
            self.stats.count("exec.coalesce.padWaste", pad)
        self.stats.histogram("exec.coalesce.batchOccupancy", float(len(items)))
        info = {
            "launch": launch_n,
            "batch_queries": len(items),
            "batch_segments": len(segs),
            "batch_rows": total,
            "pad_rows": pad,
        }
        start = 0
        for rows, sub in zip(n_rows, seg_items):
            seg_res = res[start : start + rows]
            start += rows
            for it in sub:
                it.resolve((seg_res, info), len(items))

    # ------------------------------------------------------------------
    # multi-query fusion (plane-major interpreter launches)
    # ------------------------------------------------------------------

    def _launch_fused(self, reduce, buckets: list) -> None:
        """Launch a mixed drain of per-compile-key buckets
        (``[(key, items), ...]``, all sharing one program key) as
        interpreter passes.  Queries fused into one pass must share the
        leading slice-axis length (their result rows scatter back
        row-for-row), so items group by it; groups that end up with
        fewer than two distinct (tree, segment) programs — or whose
        trees refuse to lower — fall back to the ordinary
        per-compile-key concat launch, never fail."""
        by_n: "OrderedDict[int, list]" = OrderedDict()
        for key, its in buckets:
            for it in its:
                by_n.setdefault(int(it.batch.shape[0]), []).append((key, it))
        for n_rows, pairs in by_n.items():
            self._launch_interp(reduce, n_rows, pairs)

    def _fallback_by_key(self, reduce, fallback: "OrderedDict") -> None:
        for key, its in fallback.items():
            with self._mu:
                self._fuse_fallbacks += len(its)
            self.stats.count("exec.interp.fallbacks", len(its))
            self._fallback_launch(key, its)

    def _launch_interp(self, reduce, n_rows: int, pairs: list) -> None:
        import jax
        import jax.numpy as jnp

        from pilosa_tpu.exec import plan
        from pilosa_tpu.ops import bitplane as bp

        # Segments: the distinct entry batches (identity dedup — a
        # query storm repeating K distinct queries contributes K
        # segments however many waiters ride them).
        segs: list = []
        seg_keys: list = []
        seg_of: dict[int, int] = {}
        for _key, it in pairs:
            if id(it.batch) not in seg_of:
                seg_of[id(it.batch)] = len(segs)
                lk = it.leaf_keys
                if lk is None:
                    # No identities: every column is unique to this
                    # segment (no cross-segment sharing possible).
                    lk = tuple(
                        ("anon", id(it.batch), j)
                        for j in range(int(it.batch.shape[1]))
                    )
                seg_keys.append(lk)
                segs.append(it.batch)
        l_tot = sum(int(b.shape[1]) for b in segs)
        if l_tot > MAX_FUSE_LEAVES and len(segs) > 1:
            # Leaf budget exceeded: greedy segment chunks, each its own
            # fused launch (a lone oversized segment proceeds whole —
            # it would be just as big on the unfused path).
            chunk_of: dict[int, int] = {}
            chunk = rows = 0
            for si, b in enumerate(segs):
                ln = int(b.shape[1])
                if rows and rows + ln > MAX_FUSE_LEAVES:
                    chunk += 1
                    rows = 0
                chunk_of[si] = chunk
                rows += ln
            parts: dict[int, list] = {}
            for key, it in pairs:
                parts.setdefault(chunk_of[seg_of[id(it.batch)]], []).append(
                    (key, it)
                )
            for sub in parts.values():
                self._launch_interp(reduce, n_rows, sub)
            return

        # Union leaf layout: first occurrence of each identity key
        # claims a register; later references — within one query, or
        # across DISTINCT queries — collapse onto it, so the fused pass
        # streams each distinct plane row ONCE per dispatch (the
        # plane-major amortization this tier exists for).
        union: "OrderedDict[tuple, int]" = OrderedDict()
        src_of: list[tuple[int, int]] = []  # union register -> (seg, col)
        for si, lk in enumerate(seg_keys):
            for j, k in enumerate(lk):
                if k not in union:
                    union[k] = len(src_of)
                    src_of.append((si, j))
        l_union = len(src_of)
        l_bucket = bp.pow2_bucket(l_union, 1)
        leaf_maps = [[union[k] for k in lk] for lk in seg_keys]

        # Lower each DISTINCT (tree, leaf layout) once; identical
        # queries share the lowered program (the "identical leaf sets
        # evaluated once" dedup), and — with shared leaf columns
        # collapsed — the emitter's value numbering dedups shared
        # subtrees ACROSS queries too.  A tree that cannot lower (BSI
        # aggregate node, op budget) rolls the table back and routes
        # its items to the concat fallback by ORIGINAL compile key.
        em = plan.FuseEmitter(l_bucket, plan.FUSE_MAX_OPS)
        out_of: dict[tuple, int] = {}
        failed: set = set()
        fused: list = []  # (item, out_reg)
        fallback: "OrderedDict[tuple, list]" = OrderedDict()
        pks: list = []  # per pair: its (tree, leaf layout) program key
        for key, it in pairs:
            expr = key[0]
            lmap = leaf_maps[seg_of[id(it.batch)]]
            pk = (expr, tuple(lmap))
            pks.append(pk)
            reg = out_of.get(pk)
            if reg is None and pk not in failed:
                cp = em.checkpoint()
                try:
                    reg = out_of[pk] = plan.lower_expr(expr, lmap, em)
                except plan.FuseUnsupported:
                    em.rollback(cp)
                    failed.add(pk)
            if reg is None:
                fallback.setdefault(key, []).append(it)
            else:
                fused.append((it, reg))

        # Scratch budget (MAX_FUSE_BYTES): a program set whose register
        # file would not fit splits in two by program and each half
        # launches on its own; a pair that still does not fit takes the
        # concat path like any unfusable tree.
        p_bucket = bp.pow2_bucket(max(len(em.rows), 1), plan.FUSE_OPS_FLOOR)
        # Per device: a mesh-sharded batch splits its rows evenly.
        rows_per_device = n_rows // len(segs[0].devices())
        row_bytes = int(segs[0].shape[-1]) * segs[0].dtype.itemsize
        over = (
            FUSE_SCRATCH_FACTOR
            * rows_per_device * (l_bucket + p_bucket) * row_bytes
            > MAX_FUSE_BYTES
        )
        if over and len(out_of) > 2:
            progs = list(out_of)
            first = set(progs[: len(progs) // 2])
            halves: tuple[list, list] = ([], [])
            for pair, pk in zip(pairs, pks):
                halves[0 if pk in first else 1].append(pair)
            for sub in halves:
                self._launch_interp(reduce, n_rows, sub)
            return

        # Fewer than two distinct programs fused = nothing to fuse;
        # the concat path handles identity dedup with zero copies.
        if fused and (len(out_of) < 2 or over):
            for it, _reg in fused:
                fallback.setdefault(
                    next(k for k, i2 in pairs if i2 is it), []
                ).append(it)
            fused = []

        if fused:
            # Combined leaf array: each segment contributes only the
            # union columns it FIRST provided (duplicates — within a
            # query or across queries — never re-copy, never
            # re-stream).  A single full-contribution pow2 segment is
            # used as-is: zero copies, the hot repeated-mix case.
            parts = []
            for si, seg in enumerate(segs):
                cols = [j for s2, j in src_of if s2 == si]
                if not cols:
                    continue
                if cols == list(range(int(seg.shape[1]))):
                    parts.append(seg)
                else:
                    parts.append(seg[:, jnp.asarray(cols, dtype=jnp.int32)])
            if l_bucket > l_union:
                parts.append(
                    self._leaf_pad_zeros(n_rows, l_bucket - l_union, segs[0])
                )
            # Leaf-axis concat: slice-axis sharding (if any) is
            # untouched — each shard concatenates locally.
            combined = (
                parts[0]
                if len(parts) == 1
                else jnp.concatenate(parts, axis=1)
            )
            n_ops = len(em.rows)
            prog = np.zeros((p_bucket, 4), dtype=np.int32)
            if n_ops:
                prog[:n_ops] = np.asarray(em.rows, dtype=np.int32)
            out_regs = list(dict.fromkeys(reg for _it, reg in fused))
            pos_of_reg = {r: i for i, r in enumerate(out_regs)}
            k_bucket = bp.pow2_bucket(len(out_regs), 1)
            out_idx = np.asarray(
                out_regs + [out_regs[-1]] * (k_bucket - len(out_regs)),
                dtype=np.int32,
            )
            pins = {k for it, _ in fused for k in it.pin_keys}
            try:
                sharded = len(combined.devices()) > 1
            except Exception:  # noqa: BLE001 — unit-test stand-ins
                sharded = False
            site = "collective" if (reduce == "total" and sharded) else "interp"
            fused_items = [it for it, _reg in fused]
            t_disp = [0.0]
            with self._launch_span(
                site, fused_items, n_rows * l_union
            ) as ls, device_mod.pool().pinned(*pins):
                if reduce == "total" and sharded:
                    # The slice-axis limb sums psum over the mesh —
                    # serialize with other collective launches (and,
                    # with a health manager, run under the launch
                    # watchdog; see _launch_total).
                    def _body():
                        out = plan.interp_exec(
                            reduce, combined, prog, out_idx
                        )
                        t_disp[0] = time.monotonic()
                        return np.asarray(jax.device_get(out))

                    res = self._run_collective(_body)
                else:
                    out = plan.interp_exec(reduce, combined, prog, out_idx)
                    t_disp[0] = time.monotonic()
                    res = np.asarray(jax.device_get(out))
            dispatch_ms = (t_disp[0] - ls.opened) * 1e3
            self._publish_launch(ls, fused_items, dispatch_ms)
            # Logical bytes: the deduped union leaf set (streamed once
            # per pass), pad leaves excluded.
            if perf_mod.enabled():
                perf_mod.record_launch(
                    site,
                    reduce=reduce,
                    queries=len(fused),
                    rows=n_rows * l_union,
                    n_bytes=perf_mod.plane_bytes(
                        n_rows * l_union, int(combined.shape[-1])
                    ),
                    dispatch_ms=dispatch_ms,
                    total_ms=ls.duration_ms,
                    trace_id=fused[0][0].trace_id,
                )
            with self._mu:
                self._launches += 1
                self._queries += len(fused)
                self._launched_rows += n_rows
                if len(fused) > self._max_occupancy:
                    self._max_occupancy = len(fused)
                self._fused_launches += 1
                self._fused_queries += len(fused)
                self._fused_programs += len(out_of)
                self._fused_ops += n_ops
                self._fuse_dedup_hits += em.dedup_hits
                self._fuse_shared_leaves += l_tot - l_union
                launch_n = self._launches
            self.stats.count("exec.coalesce.launches")
            self.stats.count("exec.coalesce.coalescedQueries", len(fused))
            self.stats.histogram(
                "exec.coalesce.batchOccupancy", float(len(fused))
            )
            self.stats.count("exec.interp.launches")
            self.stats.count("exec.interp.fusedQueries", len(fused))
            self.stats.histogram("exec.interp.opsPerLaunch", float(n_ops))
            if l_tot > l_union:
                self.stats.count(
                    "exec.interp.sharedLeaves", l_tot - l_union
                )
            info = {
                "launch": launch_n,
                "fused": True,
                "batch_queries": len(fused),
                "programs": len(out_of),
                "ops": n_ops,
                "dedup_hits": em.dedup_hits,
                "batch_rows": n_rows,
                "leaf_rows": l_union,
                "shared_leaves": l_tot - l_union,
                "pad_leaves": l_bucket - l_union,
                }
            for it, reg in fused:
                it.resolve((res[:, pos_of_reg[reg]], info), len(fused))

        self._fallback_by_key(reduce, fallback)

    def _leaf_pad_zeros(self, n_rows: int, pad: int, like):
        """All-zero LEAF-axis pad block matching ``like``'s placement
        (single device, or the identical sharding for mesh batches) —
        bucketing the combined leaf axis of a fused launch."""
        import jax.numpy as jnp

        words = int(like.shape[-1])
        devs = list(like.devices())
        if len(devs) == 1:
            target = devs[0]
            token = str(target)
        else:
            target = like.sharding
            token = repr(target)
        # A pad past ZERO_CACHE_MAX_BYTES is made on the device for this
        # launch and dropped with it: at 1,024 batch rows a leaf pad is
        # 128 MiB a leaf, and the seven classes of a 16-leaf bucket,
        # kept, were 3.5 GB of HBM that held nothing (PERF.md, PR 31).
        shape = (n_rows, pad, words)
        if int(np.prod(shape)) * 4 > ZERO_CACHE_MAX_BYTES:
            return jnp.zeros(shape, dtype=jnp.uint32, device=target)
        zkey = ("leafpad", n_rows, pad, words, token)
        z = self._zeros.get(zkey)
        if z is None:
            z = self._zeros[zkey] = jnp.zeros(
                shape, dtype=jnp.uint32, device=target
            )
        return z

    def _launch_fetch(self, items: list) -> None:
        """Drain pending fetch items with ONE blocking device->host
        round trip: dispatches stay with their submitters (they are
        already async); only the value fetch — the dominant TopN(src)
        residual — batches here."""
        import jax

        arrays: list = []
        spans: list[tuple[int, int]] = []
        for it in items:
            arrs = it.batch
            spans.append((len(arrays), len(arrs)))
            arrays.extend(arrs)
        with self._launch_span("fetch", items, 0) as ls:
            fetched = jax.device_get(arrays)
        self._publish_launch(ls, items, 0.0)
        if perf_mod.enabled():
            perf_mod.record_launch(
                "fetch",
                reduce="fetch",
                queries=len(items),
                n_bytes=sum(int(getattr(a, "nbytes", 0) or 0) for a in arrays),
                total_ms=ls.duration_ms,
                trace_id=items[0].trace_id,
            )
        with self._mu:
            self._fetch_launches += 1
            self._fetch_arrays += len(arrays)
            n = self._fetch_launches
        self.stats.count("exec.interp.fetchLaunches")
        self.stats.count("exec.interp.fetchedArrays", len(arrays))
        info = {"fetch_launch": n}
        for it, (lo, cnt) in zip(items, spans):
            it.resolve((fetched[lo : lo + cnt], info), len(items))

    def _pad_zeros(self, pad: int, like):
        """Cached all-zero pad rows on ``like``'s device — the pad set
        is small (pow2 gaps under MAX_CONCAT_ROWS), so the cache stays
        bounded in practice."""
        import jax

        dev = list(like.devices())[0]
        zkey = (pad,) + tuple(int(d) for d in like.shape[1:]) + (str(dev),)
        z = self._zeros.get(zkey)
        if z is None:
            z = jax.device_put(
                np.zeros((pad,) + tuple(like.shape[1:]), dtype=np.uint32), dev
            )
            self._zeros[zkey] = z
        return z
