"""Executor — the distributed PQL control plane.

Behavior parity with the reference executor (reference: executor.go):
per-call dispatch, slice-list construction from the index's max slice,
map/reduce over cluster nodes with replica failover, write fan-out to all
replicas, two-phase TopN, bulk-SetRowAttrs fast path, attr broadcast.

TPU-native execution differs in structure, not results:

* A bitmap call tree is compiled to **one fused XLA program per tree
  shape** (exec/plan.py); per slice the leaves are device rows gathered
  from fragment HBM planes, so ``Count(Intersect(a, b))`` runs as a
  single fused bitwise+popcount kernel with no intermediate rows —
  replacing the reference's per-container roaring merges
  (reference: executor.go:438-505 + roaring kernels).
* The local "mapper" batches all local slices' leaves into one stacked
  device array and evaluates the tree **vmapped over slices** in a
  single device program, instead of a goroutine per slice
  (reference: executor.go:1246-1282 mapperLocal).
* Cross-node fan-out keeps the reference's HTTP+protobuf shape via an
  injectable client; intra-host multi-device reduces ride ICI
  collectives (parallel/mesh.py).
"""

from __future__ import annotations

import contextvars
import functools
import threading
import time
import queue
import weakref
from collections import OrderedDict
from concurrent.futures import FIRST_COMPLETED, Future, wait
from concurrent.futures import TimeoutError as FuturesTimeoutError
from dataclasses import dataclass, field, replace
from datetime import datetime

import jax
import jax.numpy as jnp
import numpy as np

from pilosa_tpu import bsi
from pilosa_tpu import device as device_mod
from pilosa_tpu.bsi import ripple
from pilosa_tpu.device import health as health_mod
from pilosa_tpu.cluster import topology as topo
from pilosa_tpu.cluster.topology import Cluster, Node
from pilosa_tpu.parallel import mesh as pmesh
from pilosa_tpu.core import cache as cache_mod
from pilosa_tpu.core import timequantum as tq
from pilosa_tpu.core.bitmap import RowBitmap
from pilosa_tpu.core.cache import Pair
from pilosa_tpu.core import fragment as fragment_mod
from pilosa_tpu.core.fragment import TopOptions
from pilosa_tpu.core.view import VIEW_INVERSE, VIEW_STANDARD
from pilosa_tpu.exec import coalesce as coalesce_mod
from pilosa_tpu.exec import hosteval as hosteval_mod
from pilosa_tpu.exec import plan
from pilosa_tpu.exec import topn_stack
from pilosa_tpu.exec import warmup
from pilosa_tpu.net import resilience
from pilosa_tpu.obs import perf as perf_mod
from pilosa_tpu.obs import trace
from pilosa_tpu.testing import faults
from pilosa_tpu.ops import bitplane as bp
from pilosa_tpu.pql.parser import Call, Query


def _device_get(x):
    """``jax.device_get`` on a request's own thread (a launch that does
    not ride the coalescer): time blocked on purpose, kind ``device``."""
    with trace.blocked("device"):
        return jax.device_get(x)


# Absent-row stand-in for anchored count leaf batches: an all-sentinel
# sparse payload at the bucket floor (membership False on every real
# position).  Read-only module constant.
_EMPTY_SPARSE_PAYLOAD = np.full(
    bp.PAYLOAD_BUCKET_FLOOR, bp.FMT_SENTINEL, dtype=np.uint32
)
# Fragment.row_meta's answer for a leaf whose slice has no fragment.
_ABSENT_ROW_META = (0, None)
# The row tier of a slice with no fragment, in a kept gather layout.
# Read-only module constant.
_NO_ROWS = np.zeros(0, dtype=np.int64)

# reference: executor.go:33-40
DEFAULT_FRAME = "general"
MIN_THRESHOLD = 1
# reference: pilosa.go:107-108
TIME_FORMAT = "%Y-%m-%dT%H:%M"
# reference: config.go (max-writes-per-request default)
DEFAULT_MAX_WRITES_PER_REQUEST = 5000

WRITE_CALLS = frozenset({"SetBit", "ClearBit", "SetRowAttrs", "SetColumnAttrs"})



def _fitting_runs(lo: int, hi: int, rows: int):
    """Split members ``[lo, hi)`` of a block of ``rows`` rows so that
    every split's launches, padded to their members bucket
    (``bp.gather_planes``), still end inside the block: an in-place
    write that does not fit would be shifted.  One run wherever the
    planes of a block share a shape (64 divides every larger bucket);
    a run that ends a block of mixed shapes splits at powers of two,
    which pad nothing."""
    while lo < hi:
        n = hi - lo
        b = bp.score_group_bucket(n)
        if lo + -(-n // b) * b <= rows:
            yield lo, hi
            return
        m = 1 << (n.bit_length() - 1)
        yield lo, lo + m
        lo += m

class ExecutorError(RuntimeError):
    pass


class IndexNotFoundError(ExecutorError):
    def __init__(self):
        super().__init__("index not found")


class FrameNotFoundError(ExecutorError):
    def __init__(self):
        super().__init__("frame not found")


class TooManyWritesError(ExecutorError):
    def __init__(self):
        super().__init__("too many write commands")


class SliceUnavailableError(ExecutorError):
    def __init__(self):
        super().__init__("slice unavailable")


class SlicesUnavailableError(ExecutorError):
    """Every replica for ``slices`` is down or circuit-broken and the
    query did not opt into partial results — fail fast WITH the slice
    list, so the caller knows exactly what it would have lost."""

    def __init__(self, slices, cause: Exception | None = None):
        self.slices = sorted({int(s) for s in slices})
        msg = f"slices unavailable: {self.slices}"
        if cause is not None:
            msg += f" (last error: {cause})"
        super().__init__(msg)


@dataclass
class ExecOptions:
    """reference: executor.go:1302-1304 (+ resilience extensions)"""

    remote: bool = False
    # Per-request consistency overrides (pilosa_tpu/replicate): "" means
    # the server-configured [cluster] write-consistency /
    # read-consistency default; one|quorum|all otherwise.  Ignored when
    # no Replication is wired (bare library executors).
    write_consistency: str = ""
    read_consistency: str = ""
    # Graceful degradation: when every replica for a slice is down or
    # circuit-broken, reduce over the surviving slices and record the
    # lost ones in ``missing_slices`` instead of failing the query.
    allow_partial: bool = False
    # OUT parameter — filled by _map_reduce when allow_partial dropped
    # slices; the handler surfaces it as the partial/missing_slices
    # response marker.  Sorted, deduplicated.
    missing_slices: list[int] = field(default_factory=list)
    # Originating tenant (net/admission.py TenantRegistry): set by the
    # handler after API-key resolution and forwarded as X-Tenant on
    # every remote map leg, so a coordinator's fan-out is charged to
    # the tenant that sent the query on every node it touches.  A
    # field rather than a contextvar: map legs run on pool threads
    # that don't inherit the handler's context.
    tenant: str = ""


@dataclass
class _MapResponse:
    node: Node | None = None
    slices: list[int] = field(default_factory=list)
    result: object = None
    error: Exception | None = None


def needs_slices(calls: list[Call]) -> bool:
    """reference: executor.go:1326-1343"""
    if not calls:
        return False
    return any(c.name not in WRITE_CALLS for c in calls)


def merge_counts_by_id(parts):
    """Sum (ids, counts) array pairs by id — Pairs.Add semantics
    (reference: cache.go:312-334), the ONE array implementation of the
    TopN cross-slice reduce.  Returns (uids_sorted_asc, sums) or None
    when empty."""
    parts = [p for p in parts if len(p[0])]
    if not parts:
        return None
    cat_ids = np.concatenate([i for i, _ in parts])
    cat_cnts = np.concatenate([c for _, c in parts])
    uids, inv = np.unique(cat_ids, return_inverse=True)
    sums = np.zeros(len(uids), np.int64)
    np.add.at(sums, inv, cat_cnts)
    return uids, sums


class _DaemonPool:
    """Minimal thread pool with DAEMON workers.

    Stock ThreadPoolExecutor workers are non-daemon and joined at
    interpreter exit, so one mapper wedged inside a device call (an XLA
    runtime fault) turns into a process that never exits.  Query
    fan-out must degrade to a failed query, not a hung shutdown —
    daemon workers die with the process.  Futures are the ordinary
    concurrent.futures kind, so wait()/as_completed compose."""

    def __init__(self, max_workers: int, stats=None):
        from pilosa_tpu.obs.stats import NopStatsClient

        self._max_workers = max_workers
        self._work: "queue.SimpleQueue" = queue.SimpleQueue()
        self._threads: list[threading.Thread] = []
        self._idle = 0
        self._mu = threading.Lock()
        self._shutdown = False
        self._cancel_pending = False
        # Pool visibility (/metrics): queued-but-unclaimed items, items
        # being run right now, and total worker threads ever spawned —
        # without these the pool's contribution to query latency is
        # unattributable (and coalescing wins invisible).
        self.stats = stats or NopStatsClient()
        self._depth = 0
        self._active = 0
        # Zero-publish up front: an idle pool is visible in /metrics
        # from boot, not only after its first fan-out.
        self._publish()

    def _publish(self) -> None:
        # Advisory reads outside _mu: gauges are monotonic snapshots,
        # and a stats backend must never extend the pool's critical
        # section.
        self.stats.gauge("exec.pool.queueDepth", float(self._depth))
        self.stats.gauge("exec.pool.activeWorkers", float(self._active))

    def submit(self, fn, *args, **kwargs) -> Future:
        fut: Future = Future()
        # Carry the submitter's contextvars into the worker so trace
        # spans started in a mapper attach to the submitting request's
        # trace (obs/trace.py keeps the current span in a ContextVar).
        ctx = contextvars.copy_context()
        spawned = False
        with self._mu:
            if self._shutdown:
                raise RuntimeError("cannot submit after shutdown")
            self._work.put((fut, ctx, fn, args, kwargs))
            self._depth += 1
            # Spawn only when no idle worker can take the item (the
            # counter is advisory; a race costs one extra thread, never
            # a lost task).
            if self._idle == 0 and len(self._threads) < self._max_workers:
                t = threading.Thread(
                    target=self._worker, daemon=True, name="exec-pool"
                )
                self._threads.append(t)
                t.start()
                spawned = True
        if spawned:
            self.stats.count("exec.pool.spawned")
        self._publish()
        return fut

    def _worker(self) -> None:
        while True:
            with self._mu:
                self._idle += 1
            item = self._work.get()
            with self._mu:
                self._idle -= 1
            if item is None:  # retire (shutdown)
                return
            fut, ctx, fn, args, kwargs = item
            with self._mu:
                self._depth -= 1
                self._active += 1
            self._publish()
            try:
                if self._cancel_pending:
                    fut.cancel()
                    continue
                if not fut.set_running_or_notify_cancel():
                    continue
                try:
                    fut.set_result(ctx.run(fn, *args, **kwargs))
                except BaseException as e:  # noqa: BLE001 — crosses the future
                    fut.set_exception(e)
            finally:
                with self._mu:
                    self._active -= 1
                self._publish()

    def shutdown(self, wait: bool = True, cancel_futures: bool = False) -> None:
        with self._mu:
            if self._shutdown:
                return
            self._shutdown = True
            self._cancel_pending = cancel_futures
            threads = list(self._threads)
        for _ in threads:
            self._work.put(None)
        if wait:
            for t in threads:
                t.join()


class Executor:
    """Executes PQL queries against a holder, fanning out across a cluster.

    ``client_factory(node) -> client`` supplies the inter-node data plane;
    the client must expose ``execute_query(index, query, slices, remote)
    -> list`` (see net/client.py).  Single-node setups never invoke it.
    """

    def __init__(
        self,
        holder,
        host: str = "",
        cluster: Cluster | None = None,
        client_factory=None,
        max_writes_per_request: int = DEFAULT_MAX_WRITES_PER_REQUEST,
        tracer=None,
        prefetcher=None,
        coalescer=None,
        replication=None,
        device_health=None,
    ):
        self.holder = holder
        self.host = host
        self.cluster = cluster or Cluster(nodes=[Node(host=host)])
        self.client_factory = client_factory
        self.max_writes_per_request = max_writes_per_request
        self.tracer = tracer or trace.NOP_TRACER
        # Quorum replication (pilosa_tpu/replicate): when wired (Server
        # does), write fan-out becomes W-of-N with hinted handoff and
        # reads at quorum/all consistency version-check their replicas
        # (read-repair on divergence).  None = the legacy best-effort
        # fan-out (bare library use, remote legs).
        self.replication = replication
        # Async HBM mirror prefetcher (device/prefetch.py): when wired
        # (Server does, gated on [device] prefetch), a query's cold leaf
        # mirrors re-materialize concurrently while planning proceeds.
        # None = disabled (bare library use stays fully deterministic).
        self.prefetcher = prefetcher
        # Durable-ingest manager (pilosa_tpu/ingest): when wired (Server
        # does, gated on [ingest] wal), point-write acks block on the
        # WAL group commit — the write returns only after its op record
        # is fsynced (or captured by a completed snapshot).  None =
        # the historical op-buf durability (bare library use).
        self.ingest = None
        # Cross-query coalescing scheduler (exec/coalesce.py): when
        # wired (Server does, gated on [exec] coalesce), concurrent
        # queries sharing a compile key ride ONE fused launch.  The
        # scheduler is OWNED by whoever wired it (Server.close /
        # bench), not by this executor — several executors may share
        # one.  None = every query dispatches its own launch.
        self.coalescer = coalescer
        # Device-health subsystem (device/health.py): classifies launch
        # failures, drives the per-device/collective quarantine state
        # machine, and owns the hung-collective watchdog.  The Server
        # wires a configured instance (shared with its coalescer and
        # gossiped to peers); bare library executors build a default so
        # device-fault tolerance is never off.
        self._owns_health = device_health is None
        self.device_health = device_health or health_mod.DeviceHealth(
            stats=getattr(holder, "stats", None)
        )
        # Host (numpy) evaluator over the authoritative host planes —
        # the degraded-mode data plane a quarantined device falls back
        # to, byte-identical by construction (exec/hosteval.py).
        self.hosteval = hosteval_mod.HostEvaluator(self)
        # Candidate rows a TopN scored on the host (the sparse tier's
        # probes, the host fallback) and bytes of TopN src words made on
        # the host (``_topn_src_host``): published at 0, so that a
        # reader tells "none" from "not counted".
        if getattr(holder, "stats", None) is not None:
            holder.stats.count("topn.host_scored_rows", 0)
            holder.stats.count("topn.srcHostBytes", 0)
        self._pool = _DaemonPool(
            max_workers=16, stats=getattr(holder, "stats", None)
        )
        # Shapes whose gather programs a mostly-cold miss has warmed,
        # and the last thread that did (see _warm_gather_beside).
        self._gather_warmed: set = set()
        self._gather_warming: threading.Thread | None = None
        # Assembled leaf-batch LRU (see _cached_batch); executors serve
        # concurrent HTTP request threads, so access is lock-guarded.
        self._batch_cache: "OrderedDict[tuple, dict]" = OrderedDict()
        self._batch_mu = threading.Lock()
        # Folded-TopN prep LRU (see _topn_folded_entry) — candidate
        # walks, union assembly, and gather prep cached per (query,
        # slice set), validated like _batch_cache entries.
        self._topn_cache: "OrderedDict[tuple, dict]" = OrderedDict()
        # What a direct folded-TopN build makes that no text changes,
        # kept per view, slice set and options (see _topn_kept_text).
        self._topn_kept: "OrderedDict[tuple, dict]" = OrderedDict()
        # What a Count's miss reads of a view's fragments, kept per view
        # and slice set (see _gather_layout): the pool's own mirrors and
        # host integers, no device bytes of its own.
        self._gather_layouts: "OrderedDict[tuple, dict]" = OrderedDict()
        # Slot layouts of the in-place BSI aggregate (see
        # _agg_view_layout): host integers, no device bytes.
        self._agg_layouts: "OrderedDict[tuple, dict]" = OrderedDict()
        # slice->node grouping LRU (see _slices_by_node) — host-only
        # dicts, no device bytes, so unlike the two caches above it is
        # NOT a residency-pool tenant; the count cap bounds it.
        self._slice_group_cache: "OrderedDict[tuple, dict]" = OrderedDict()
        # A fragment leaving service (delete/teardown) must release the
        # TopN prep entries pinning its HBM plane snapshots now, not at
        # LRU displacement (held weakly — see fragment._close_listeners).
        fragment_mod.register_close_listener(self._drop_closed_fragment)

    def close(self) -> None:
        fragment_mod.unregister_close_listener(self._drop_closed_fragment)
        self._pool.shutdown(wait=False, cancel_futures=True)
        if self._owns_health:
            self.device_health.close()
        # Deregister every cache entry from the residency pool so a
        # closed executor's device arrays stop counting as resident.
        pool = device_mod.pool()
        with self._batch_mu:
            batch_keys = list(self._batch_cache)
            topn_keys = list(self._topn_cache)
            kept_keys = list(self._topn_kept)
            self._batch_cache.clear()
            self._topn_cache.clear()
            self._topn_kept.clear()
            self._gather_layouts.clear()
        for k in batch_keys:
            pool.remove(self._batch_pool_key(k))
        for k in topn_keys:
            pool.remove(self._topn_pool_key(k))
        for k in kept_keys:
            pool.remove(self._topn_kept_pool_key(k))

    def _drop_closed_fragment(self, frag) -> None:
        with self._batch_mu:
            stale = [
                k
                for k, e in self._topn_cache.items()
                if any(p[0] is frag for p in e.get("parts", ()))
            ]
            for k in stale:
                del self._topn_cache[k]
            dead = [
                k
                for k, e in self._topn_kept.items()
                if any(p[0] is frag for p in e["parts"])
            ]
            for k in dead:
                del self._topn_kept[k]
            for k in [
                k
                for k, lay in self._gather_layouts.items()
                if frag._serial in lay["serials"]
            ]:
                del self._gather_layouts[k]
        for k in stale:
            device_mod.pool().remove(self._topn_pool_key(k))
        for k in dead:
            device_mod.pool().remove(self._topn_kept_pool_key(k))

    # ------------------------------------------------------------------
    # HBM residency-pool tenancy (device/pool.py): both device-holding
    # caches are byte-accounted pool tenants — the pool's LRU eviction
    # (not just the entry-count caps) bounds their device footprint.
    # ------------------------------------------------------------------

    def _batch_pool_key(self, key: tuple) -> tuple:
        return ("exec", id(self), "batch", key)

    def _topn_pool_key(self, key: tuple) -> tuple:
        return ("exec", id(self), "topn", key)

    def _topn_kept_pool_key(self, key: tuple) -> tuple:
        return ("exec", id(self), "topn_kept", key)

    def _register_cache_entry(self, pool_key, arrays, info, evict):
        """Admit a cache entry's device arrays to the residency pool;
        returns the pool key, or None when nothing lives on device."""
        bbd: dict = {}
        for arr in arrays:
            for d, n in device_mod.bytes_by_device(arr).items():
                bbd[d] = bbd.get(d, 0) + n
        if not bbd:
            return None
        device_mod.pool().admit(
            pool_key, bbd, evict, category="cache", info=info
        )
        return pool_key

    def _evict_cache_key(self, cache, key: tuple) -> bool:
        """Pool eviction hook for an entry of ``cache``: the batch
        cache, the TopN prep LRU or the kept stacks.  Non-blocking:
        the pool invokes this under ITS lock while request threads
        hold ``_batch_mu`` around cache reads/inserts (pool tenancy
        itself is registered outside ``_batch_mu`` — see
        _cached_batch_build), so a blocking acquire here could still
        deadlock through that interleaving — skipping a busy cache is
        always safe.  The lock-order analyzer (pilosa_tpu/analyze)
        tracks this as a non-blocking edge."""
        if not self._batch_mu.acquire(blocking=False):
            return False
        try:
            cache.pop(key, None)
            return True
        finally:
            self._batch_mu.release()

    def _topn_admit(self, cache, cap: int, key, ent, pool_key_of, replaced, info):
        """Insert ``ent`` as the newest entry of ``cache`` (the TopN prep
        LRU or the kept stacks, capped at ``cap``), drop the pool
        accounts of the entries it displaces, and account in the pool for
        ``replaced``: the plane snapshots that the entry alone keeps
        alive (``_replaced_planes``).  An entry with none clears any
        account left under its key."""
        displaced = []
        with self._batch_mu:
            cache[key] = ent
            cache.move_to_end(key)
            while len(cache) > cap:
                displaced.append(cache.popitem(last=False)[0])
        pool = device_mod.pool()
        for k in displaced:
            pool.remove(pool_key_of(k))
        pool_key = pool_key_of(key)
        evict = functools.partial(self._evict_cache_key, cache, key)
        if self._register_cache_entry(pool_key, replaced, info, evict) is None:
            pool.remove(pool_key)

    # ------------------------------------------------------------------
    # entry point (reference: executor.go:65-151)
    # ------------------------------------------------------------------

    def execute(
        self,
        index: str,
        q: Query,
        slices: list[int] | None = None,
        opt: ExecOptions | None = None,
    ) -> list:
        if not index:
            raise ExecutorError("index required")
        if (
            self.max_writes_per_request > 0
            and q.write_call_n() > self.max_writes_per_request
        ):
            raise TooManyWritesError()
        opt = opt or ExecOptions()

        slices = list(slices) if slices else []
        inverse_slices: list[int] = []
        column_label = "columnID"
        want_slices = needs_slices(q.calls)
        # Inverse orientation only swaps in the inverse slice list when this
        # node computed the lists itself; a coordinator-provided list (remote
        # leg) already has the right orientation and must be used as-is.
        computed_lists = False
        if not slices and want_slices:
            idx = self.holder.index(index)
            if idx is None:
                raise IndexNotFoundError()
            slices = list(range(idx.max_slice() + 1))
            inverse_slices = list(range(idx.max_inverse_slice() + 1))
            column_label = idx.column_label
            computed_lists = True

        # Cost-class accounting (exec/plan.py cost_class): the same
        # classification the admission layer gates on, counted here so
        # the executor-side mix is visible even for direct library use
        # (no HTTP front) — dashboards correlate exec.class.* against
        # net.admission.* to see what the gates actually passed.
        class_tags = [f"class:{plan.cost_class(q.calls)}"]
        if opt.tenant:
            # Tenant-tagged only when QoS resolved one: untagged
            # (library / single-tenant) deployments keep the exact
            # class-only series their dashboards already chart.
            class_tags.append(f"tenant:{opt.tenant}")
        self.holder.stats.count_with_custom_tags("exec.class", 1, class_tags)

        # Bulk attribute-insert fast path (reference: executor.go:119-122).
        if q.calls and all(c.name == "SetRowAttrs" for c in q.calls):
            return self._execute_bulk_set_row_attrs(index, q.calls, opt)

        # Version-checked replica reads (pilosa_tpu/replicate): at
        # read consistency quorum/all the touched slices' replica
        # versions must agree before execution — divergence triggers a
        # synchronous read-repair (newest -> stale, checksum-verified),
        # which is what makes read-your-writes hold at W+R > N.  The
        # default level "one" costs nothing here.
        if (
            self.replication is not None
            and not opt.remote
            and slices
            and any(c.name not in WRITE_CALLS for c in q.calls)
        ):
            level = self.replication.read_consistency_for(opt)
            if level != "one":
                with self.tracer.span(
                    "replicate.read", consistency=level
                ) as sp:
                    repaired = self.replication.ensure_read_consistency(
                        index, slices, level
                    )
                    sp.annotate(repaired=repaired)

        # Async HBM prefetch: kick cold leaf-mirror uploads for the whole
        # query now, so host->device staging overlaps the per-call
        # planning below (per-fragment locks synchronize the rendezvous).
        if self.prefetcher is not None and slices:
            self._prefetch_query(index, q.calls, slices)

        results = []
        for call in q.calls:
            # Per-call deadline gate: a multi-call query whose budget
            # ran out mid-way fails with 504 rather than starting the
            # next call's fan-out.
            resilience.check_deadline(f"before call {call.name}")
            call_slices = slices
            if call.supports_inverse() and want_slices and computed_lists:
                frame = call.args.get("frame") or DEFAULT_FRAME
                f = self.holder.frame(index, frame)
                if f is None:
                    raise FrameNotFoundError()
                if call.is_inverse(f.row_label, column_label):
                    call_slices = inverse_slices
            with self.tracer.span(f"call.{call.name}", index=index):
                results.append(
                    self._execute_call(index, call, call_slices, opt)
                )
        return results

    def _prefetch_query(self, index: str, calls, slices: list[int]) -> None:
        """Walk the query's leaf fragments (exec/plan tree + TopN frame)
        and schedule cold-mirror uploads on the prefetcher.  Strictly
        best-effort: any resolution error here is swallowed — the call's
        own execution raises the authoritative error.  Frame/view
        resolution is hoisted out of the per-slice loop and only COLD
        fragments collect, so the all-warm steady state costs one dict
        lookup + two attribute compares per existing fragment."""
        frags: list = []
        walked: set = set()

        def add_view(frame_name: str, view_name: str) -> None:
            if (frame_name, view_name) in walked:
                return  # the leaves of one query mostly share a view
            walked.add((frame_name, view_name))
            v = self.holder.view(index, frame_name, view_name)
            if v is None:
                return
            for frag in v.fragments_at(slices):
                # Advisory cold check (no lock): a racing writer only
                # flips a mirror cold; the worker re-checks under the
                # fragment lock.
                if frag is not None and (
                    frag._device is None
                    or frag._device_version != frag._version
                ):
                    frags.append(frag)

        try:
            idx = self.holder.index(index)
            if idx is None:
                return
            for call in calls:
                if call.name in WRITE_CALLS:
                    continue
                for leaf in plan.collect_leaf_calls(call):
                    if leaf.name == "Range" and leaf.conditions():
                        # BSI Range: warm the field view's plane mirrors.
                        frame = leaf.args.get("frame") or DEFAULT_FRAME
                        for field_name in leaf.conditions():
                            add_view(frame, bsi.field_view_name(field_name))
                        continue
                    if leaf.name != "Bitmap":
                        continue
                    frame = leaf.args.get("frame") or DEFAULT_FRAME
                    _, col_ok = _uint_arg(leaf, idx.column_label)
                    add_view(
                        frame, VIEW_INVERSE if col_ok else VIEW_STANDARD
                    )
                if call.name == "TopN":
                    add_view(*self._topn_frame_view(call))
                if call.name in ("Sum", "Min", "Max") and isinstance(
                    call.args.get("field"), str
                ):
                    add_view(
                        call.args.get("frame") or DEFAULT_FRAME,
                        bsi.field_view_name(call.args["field"]),
                    )
        except Exception:  # noqa: BLE001 — prefetch must never fail a query
            return
        if frags:
            with self.tracer.span("prefetch", fragments=len(frags)):
                self.prefetcher.prefetch(frags)

    # ------------------------------------------------------------------
    # dispatch (reference: executor.go:156-182)
    # ------------------------------------------------------------------

    def _execute_call(self, index: str, c: Call, slices: list[int], opt: ExecOptions):
        name = c.name
        if name == "ClearBit":
            return self._execute_clear_bit(index, c, opt)
        if name == "SetBit":
            return self._execute_set_bit(index, c, opt)
        if name == "SetRowAttrs":
            self._execute_set_row_attrs(index, c, opt)
            return None
        if name == "SetColumnAttrs":
            self._execute_set_column_attrs(index, c, opt)
            return None
        # Read calls count per call name with the index tag (reference:
        # executor.go:163-181) — the per-query stats surface dashboards
        # key on.
        self.holder.stats.count_with_custom_tags(name, 1, [f"index:{index}"])
        if name == "Count":
            return self._execute_count(index, c, slices, opt)
        if name == "TopN":
            return self._execute_topn(index, c, slices, opt)
        if name in ("Sum", "Min", "Max"):
            return self._execute_bsi_agg(index, c, slices, opt)
        return self._execute_bitmap_call(index, c, slices, opt)

    # ------------------------------------------------------------------
    # bitmap call trees — fused device programs
    # ------------------------------------------------------------------

    def _bsi_plane_fragment(self, index: str, c: Call, slice_i: int):
        return self.holder.fragment(
            index, c.args["frame"], bsi.field_view_name(c.args["field"]), slice_i
        )

    def _resolve_bitmap_leaf(self, index: str, c: Call, slice_i: int):
        """Frame/row/orientation resolution for a Bitmap() leaf
        (reference: executor.go:438-484 executeBitmapSlice)."""
        view, id_ = self._resolve_bitmap_view(index, c)
        return (view.fragment(slice_i) if view is not None else None), id_

    def _resolve_bitmap_view(self, index: str, c: Call):
        """The slice-independent half of a Bitmap() leaf: ``(view or
        None, row/column id)``.  A walk over many slices resolves this
        once and asks the view for each slice's fragment."""
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError()
        column_label = idx.column_label
        frame = c.args.get("frame") or DEFAULT_FRAME
        f = self.holder.frame(index, frame)
        if f is None:
            raise FrameNotFoundError()
        row_label = f.row_label

        row_id, row_ok = _uint_arg(c, row_label)
        col_id, col_ok = _uint_arg(c, column_label)
        if row_ok and col_ok:
            raise ExecutorError(
                f"Bitmap() cannot specify both {row_label} and {column_label} values"
            )
        if not row_ok and not col_ok:
            raise ExecutorError(
                f"Bitmap() must specify either {row_label} or {column_label} values"
            )
        view, id_ = VIEW_STANDARD, row_id
        if col_ok:
            view, id_ = VIEW_INVERSE, col_id
            if not f.inverse_enabled:
                raise ExecutorError(
                    "Bitmap() cannot retrieve columns unless inverse storage enabled"
                )
        return f.view(view), id_

    def _resolve_range(self, idx, f, c: Call):
        """Shared Range() argument resolution for the device and host
        row paths: (view_name, id, start, end, quantum)."""
        column_label = idx.column_label
        row_label = f.row_label
        col_id, col_ok = _uint_arg(c, column_label)
        row_id, row_ok = _uint_arg(c, row_label)
        if col_ok and row_ok:
            raise ExecutorError(
                f'Range() cannot contain both "{column_label}" and "{row_label}"'
            )
        if not col_ok and not row_ok:
            raise ExecutorError(
                f'Range() must specify either "{column_label}" or "{row_label}"'
            )
        view_name, id_ = (VIEW_INVERSE, col_id) if col_ok else (VIEW_STANDARD, row_id)
        return view_name, id_, _time_arg(c, "start"), _time_arg(c, "end"), f.time_quantum

    # ------------------------------------------------------------------
    # BSI rewrite — Range(field > x) / Sum / Min / Max expansion
    # ------------------------------------------------------------------

    def _bsi_resolve_field(self, index: str, c: Call):
        """(frame name, BSIField) for a BSI call — schema errors surface
        here, before any leaf machinery runs."""
        frame = c.args.get("frame") or DEFAULT_FRAME
        f = self.holder.frame(index, frame)
        if f is None:
            raise FrameNotFoundError()
        if not f.range_enabled:
            raise ExecutorError(
                f"frame {frame!r} does not support range queries"
            )
        return frame, f

    @staticmethod
    def _bsi_field_leaves(frame: str, fld) -> tuple[list[Call], int]:
        """The plane leaves of one field, padded to its depth bucket:
        exists, sign, ``depth`` magnitude planes, then all-zero pads —
        so every field in a bucket shares one compile shape (and one
        coalescer compile key) per op kind."""
        depth = fld.bit_depth
        bucket = bsi.pad_depth(depth)
        leaves = [
            Call("BsiPlane", {"frame": frame, "field": fld.name, "row": r})
            for r in (bsi.ROW_EXISTS, bsi.ROW_SIGN)
        ]
        leaves += [
            Call(
                "BsiPlane",
                {"frame": frame, "field": fld.name, "row": bsi.ROW_BIT_BASE + k},
            )
            for k in range(depth)
        ]
        leaves += [Call("BsiZero") for _ in range(bucket - depth)]
        return leaves, bucket

    def _rewrite_bsi(self, index: str, c: Call) -> Call:
        """Expand BSI Range calls (a comparison arg present) anywhere in
        a call tree into synthetic ``BsiCmp`` nodes over plane/predicate
        leaves; returns the ORIGINAL object when nothing changed, so
        non-BSI queries keep their cache keys byte-identical.  Runs on
        the node that executes the slices (map_fn side) — remote
        forwarding ships the un-rewritten PQL text, and each node
        re-expands against its own schema."""
        if c.name == "Range" and c.conditions():
            return self._rewrite_bsi_range(index, c)
        new_children = [self._rewrite_bsi(index, ch) for ch in c.children]
        if all(nc is oc for nc, oc in zip(new_children, c.children)):
            return c
        return Call(name=c.name, args=dict(c.args), children=new_children)

    def _rewrite_bsi_range(self, index: str, c: Call) -> Call:
        conds = c.conditions()
        if len(conds) != 1:
            raise ExecutorError(
                "Range() supports exactly one field comparison"
                " (use >< for between)"
            )
        (field_name, cond), = conds.items()
        frame, f = self._bsi_resolve_field(index, c)
        fld = f.bsi_field(field_name)
        if fld is None:
            raise ExecutorError(f"unknown field: {field_name!r}")
        op = bsi.OPS.get(cond.op)
        if op is None:
            raise ExecutorError(f"unknown comparison: {cond.op!r}")
        depth = fld.bit_depth
        leaves, bucket = self._bsi_field_leaves(frame, fld)
        if op == "between":
            v = cond.value
            if (
                not isinstance(v, list)
                or len(v) != 2
                or any(isinstance(x, bool) or not isinstance(x, int) for x in v)
            ):
                raise ExecutorError("between (><) requires a two-int list")
            lo, hi = bsi.clamp_between(v[0], v[1], depth)
            leaves.append(Call("BsiPred", {"v": lo, "d": bucket}))
            leaves.append(Call("BsiPred", {"v": hi, "d": bucket}))
        else:
            v = cond.value
            if isinstance(v, bool) or not isinstance(v, int):
                raise ExecutorError(
                    f"Range() comparison value must be an integer, got {v!r}"
                )
            op, v = bsi.clamp_predicate(op, v, depth)
            leaves.append(Call("BsiPred", {"v": v, "d": bucket}))
        return Call("BsiCmp", {"op": op}, children=leaves)

    def _rewrite_bsi_agg(self, index: str, c: Call) -> Call:
        """Expand Sum/Min/Max(frame=, field=, [filter child]) into the
        synthetic aggregate node the plan layer compiles (one fused
        program per (kind, depth bucket, filter-present))."""
        if len(c.children) > 1:
            raise ExecutorError(f"{c.name}() can only have one input bitmap")
        field_name = c.args.get("field")
        if not isinstance(field_name, str):
            raise ExecutorError(f"{c.name}() field required")
        frame, f = self._bsi_resolve_field(index, c)
        fld = f.bsi_field(field_name)
        if fld is None:
            raise ExecutorError(f"unknown field: {field_name!r}")
        return self.bsi_agg_call(
            c.name,
            frame,
            fld,
            self._rewrite_bsi(index, c.children[0]) if c.children else None,
        )

    @staticmethod
    def bsi_agg_call(name: str, frame: str, fld, filter_call: Call | None = None) -> Call:
        """The synthetic aggregate node of ``name`` (Sum / Min / Max)
        over one field's plane leaves, with an already-rewritten filter
        tree or none."""
        leaves, bucket = Executor._bsi_field_leaves(frame, fld)
        if filter_call is not None:
            leaves.append(filter_call)
        return Call(
            "Bsi" + name,
            {"filter": filter_call is not None, "nplanes": bucket},
            children=leaves,
        )

    def _leaf_row_host(self, index: str, c: Call, slice_i: int):
        """One leaf row's words on the host (numpy), or None when the
        row has no bits."""
        if c.name == "Bitmap":
            frag, row_id = self._resolve_bitmap_leaf(index, c, slice_i)
            if frag is None:
                return None
            return frag._row_words_host(row_id)
        if c.name == "Range":
            return self._range_row_host(index, c, slice_i)
        if c.name == "BsiPlane":
            frag = self._bsi_plane_fragment(index, c, slice_i)
            if frag is None:
                return None
            return frag._row_words_host(c.args["row"])
        if c.name == "BsiPred":
            return bsi.pred_row(c.args["v"], c.args["d"])
        if c.name == "BsiZero":
            return None
        raise plan.PlanError(f"unknown call: {c.name}")

    def _range_row_host(self, index: str, c: Call, slice_i: int):
        frame = c.args.get("frame") or DEFAULT_FRAME
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError()
        f = idx.frame(frame)
        if f is None:
            raise FrameNotFoundError()
        view_name, id_, start, end, quantum = self._resolve_range(idx, f, c)
        if not quantum:
            return None
        acc = None
        for view in tq.views_by_time_range(view_name, start, end, quantum):
            frag = self.holder.fragment(index, frame, view, slice_i)
            if frag is None:
                continue
            row = frag._row_words_host(id_)
            if row is None:
                continue
            acc = row if acc is None else (acc | row)
        return acc

    def _assemble_host_batch(self, index: str, leaves, slices: list[int]):
        """Assemble the single-device batch HOST-SIDE: one numpy fill
        plus ONE device transfer, instead of ~2 device dispatches per
        (slice, leaf) — at 954 slices the dispatch-per-leaf cold path
        is thousands of device dispatches.  The host plane is
        authoritative, so this is always coherent.  Returns (batch,
        kept, empties)."""
        n_leaves = len(leaves)
        kept: list[int] = []
        empties: list[int] = []
        with self.tracer.span("plan.leaves", path="host_fill") as sp:
            rows_buf = np.zeros(
                (len(slices), n_leaves, bp.WORDS_PER_SLICE), dtype=np.uint32
            )
            i = n_rows = 0
            for s in slices:
                any_set = False
                for j, leaf in enumerate(leaves):
                    w = self._leaf_row_host(index, leaf, s)
                    if w is not None:
                        rows_buf[i, j] = w
                        n_rows += 1
                        if leaf.name not in plan.NEUTRAL_LEAVES:
                            any_set = True
                if not leaves or not any_set:
                    # an empty slice writes nothing, so position i stays
                    # zero-initialized for the next kept slice
                    empties.append(s)
                else:
                    kept.append(s)
                    i += 1
            sp.annotate(rows=n_rows, device_copies=0)
        if not kept:
            return None, kept, empties
        with self.tracer.span("plan.transfer", devices=1) as sp:
            bucket = plan.slice_bucket(len(kept))
            if bucket <= rows_buf.shape[0]:
                # positions past the last kept slice were never written
                batch_np = rows_buf[:bucket]
            else:
                batch_np = np.zeros(
                    (bucket, n_leaves, bp.WORDS_PER_SLICE), dtype=np.uint32
                )
                batch_np[: len(kept)] = rows_buf[: len(kept)]
            sp.annotate(bytes=int(batch_np.nbytes))
            return jnp.asarray(batch_np), kept, empties

    # Assembled leaf batches kept per (index, canonical call, slice set):
    # the working set of a hot query is one entry.  Each entry holds
    # device memory comparable to the queried planes, so entries are
    # byte-accounted residency-pool tenants (device/pool.py) — under an
    # HBM budget the pool's LRU eviction, not this count cap, is the
    # operative bound; the cap remains as the unbounded-budget backstop.
    _BATCH_CACHE_CAP = 4

    def _cached_batch(self, index: str, c: Call, slices: list[int]):
        """Traced wrapper over :meth:`_cached_batch_build` — the "plan"
        stage of a query trace (tree decomposition + leaf batch
        assembly), annotated with whether the batch cache served it."""
        with self.tracer.span("plan", slices=len(slices)) as sp:
            return self._cached_batch_build(index, c, slices, sp)

    def _cached_batch_build(self, index: str, c: Call, slices: list[int], sp):
        """The assembled device batch for a bitmap call tree over
        ``slices``, CACHED across queries.

        Assembly costs a sweep of every (slice, leaf) fragment and a
        few launches a device (or, from cold planes, a host fill and a
        transfer), where the reference's goroutine-per-slice
        mapperLocal amortizes to ~zero (reference:
        executor.go:1246-1282).  Repeated query shapes skip it
        entirely: entries validate in O(1) against the global
        fragment write epoch, then (only when some fragment changed
        anywhere) against the per-fragment version vector.  Range
        leaves' validity entries additionally carry the frame's time
        quantum and every time-view fragment's version (the view set
        depends on the quantum; set_time_quantum bumps the write epoch
        so the O(1) fast path stays sound)."""
        with self.tracer.span("plan.resolve") as rs:
            c = self._rewrite_bsi(index, c)
            expr, leaves = plan.decompose(c)
            cacheable = all(leaf.name in plan.LEAF_CALLS for leaf in leaves)
            key = (index, str(c), tuple(slices))
            if cacheable:
                with self._batch_mu:
                    ent = self._batch_cache.get(key)
                if ent is not None:
                    epoch = fragment_mod.write_epoch()
                    if ent["epoch"] == epoch or ent[
                        "versions"
                    ] == self._leaf_versions(index, leaves, slices):
                        ent["epoch"] = epoch
                        with self._batch_mu:
                            if key in self._batch_cache:
                                self._batch_cache.move_to_end(key)
                        device_mod.pool().touch(self._batch_pool_key(key))
                        sp.annotate(batch_cache="hit")
                        return ent

            sp.annotate(batch_cache="miss")
            # Capture validity BEFORE building: a concurrent write during
            # assembly leaves the entry conservatively stale.  The same
            # sweep counts mirror-less fragments for the cold-path choice;
            # a view with a kept gather layout that stands is swept by it.
            epoch = fragment_mod.write_epoch()
            versions = sweep = None
            n_frag = n_cold = 0
            if cacheable:
                sweep = self._leaf_sweep(index, leaves, slices, kept=True)
                versions, n_frag, n_cold = self._sweep_versions(sweep)
            rs.annotate(fragments=n_frag, cold=n_cold)
        mesh = pmesh.default_slices_mesh()
        ent = {
            "batch": None,
            "pos_of": {},
            "mesh": None,
            "epoch": epoch,
            "versions": versions,
            "expr": expr,
        }
        # Mostly-resident dense rows gather ON the device from the plane
        # mirrors, on one chip and on a mesh alike (a fragment or two
        # invalidated by a write re-upload on the way).  MOSTLY-cold
        # fragments, a sparse-tier row or a time-quantum Range fill on
        # the host from the authoritative planes: one transfer a device,
        # and no full-plane uploads just to read two rows.
        built = None
        if sweep is not None and n_frag:
            if n_cold * 2 <= n_frag:
                built = self._assemble_gather_batch(
                    leaves, slices, sweep, mesh, keep=(epoch, versions)
                )
            else:
                self._warm_gather_beside(leaves, sweep)
        if built is not None:
            batch, pos_of, kept_slices, empties = built
        elif mesh is None:
            # one numpy fill + one transfer; the slice axis pads to a
            # power of two — one compiled program per (tree shape,
            # bucket), SURVEY.md §7 shape bucketing
            batch, kept_slices, empties = self._assemble_host_batch(
                index, leaves, slices
            )
            pos_of = {s: i for i, s in enumerate(kept_slices)}
        else:
            batch, pos_of, kept_slices, empties = self._assemble_mesh_batch_host(
                index, leaves, slices, mesh
            )
        ent.update(empties=empties, kept=kept_slices)
        if batch is not None:
            ent.update(
                batch=batch,
                pos_of=pos_of,
                mesh=mesh if len(kept_slices) > 1 else None,
            )
        # Per-column leaf identity keys for union-leaf fusion
        # (coalesce._launch_interp): equal keys guarantee byte-identical
        # columns — same leaf call, same kept-slice geometry, and the
        # same validation epoch (entries sharing an epoch were built
        # from the same plane state; a refresh never rewrites content).
        # Predicate/zero columns are slice-invariant and share globally.
        kept_sig = tuple(ent.get("kept") or ())
        ent["leaf_keys"] = tuple(
            ("zero",)
            if leaf.name == "BsiZero"
            else ("pred", leaf.args["v"], leaf.args["d"])
            if leaf.name == "BsiPred"
            else (index, str(leaf), kept_sig, epoch)
            for leaf in leaves
        )
        if cacheable:
            displaced = []
            with self.tracer.span("plan.register") as gs:
                with self._batch_mu:
                    self._batch_cache[key] = ent
                    while len(self._batch_cache) > self._BATCH_CACHE_CAP:
                        displaced.append(
                            self._batch_cache.popitem(last=False)[0]
                        )
                # Pool tenancy OUTSIDE _batch_mu: admission may evict
                # other tenants, whose callbacks take _batch_mu
                # non-blocking.
                pool = device_mod.pool()
                for k in displaced:
                    pool.remove(self._batch_pool_key(k))
                ent["pool_key"] = self._register_cache_entry(
                    self._batch_pool_key(key),
                    [ent["batch"]],
                    {"cache": "batch", "index": index, "query": str(c)},
                    functools.partial(self._evict_cache_key, self._batch_cache, key),
                )
                gs.annotate(displaced=len(displaced))
        return ent

    @staticmethod
    def _gather_columns(leaves, sweep):
        """``(cols, consts)`` of a gatherable tree: ``cols`` the columns
        that read one view, in runs — a member's rows of a run come from
        ONE plane — as ``(first column, row ids, fragments, view, kept
        gather layout or None)``; ``consts`` the slice-invariant
        predicate rows, ``(column, host row)``.  The
        BsiZero pads that follow a field's planes ride in its run as row
        id None (held nowhere: zeros), so the fields of one depth bucket
        gather by one program, as they count by one.  None for a tree
        the gather does not take: a time-quantum Range is a union over
        views."""
        if any(ent[0] == "range" for ent in sweep):
            return None
        cols: list[tuple] = []
        consts: list[tuple[int, np.ndarray]] = []
        for j, (leaf, ent) in enumerate(zip(leaves, sweep)):
            if ent[0] == "rows":
                _, view, row_id, frags, layout = ent
                if view is None:
                    continue
                if cols and cols[-1][2] is frags and cols[-1][0] + len(cols[-1][1]) == j:
                    cols[-1][1].append(row_id)
                else:
                    cols.append((j, [row_id], frags, view, layout))
            elif leaf.name == "BsiPred":
                consts.append((j, bsi.pred_row(leaf.args["v"], leaf.args["d"])))
            elif cols and cols[-1][0] + len(cols[-1][1]) == j:
                cols[-1][1].append(None)
        return cols, consts

    def _warm_gather_beside(self, leaves, sweep) -> None:
        """A miss over MOSTLY-cold fragments fills on the host, and the
        prefetcher is already uploading their mirrors: the next miss
        will gather.  Its programs, at this batch's own shapes, are
        compiled now on a thread beside the fill (a fresh node's first
        texts, a benchmark's warm-up) and nobody waits for them who does
        not need them: a gather that comes before they are ready waits
        out the rest of the compile (``bp._first_call``).  Once a
        shape."""
        gatherable = self._gather_columns(leaves, sweep)
        if gatherable is None:
            return
        devices = bp.participating_devices()
        shapes = set()
        for _, row_ids, frags, _view, _layout in gatherable[0]:
            live = [f for f in frags if f is not None]
            if live:
                n = -(-len(live) // len(devices))
                shapes.add(
                    (
                        bp.score_group_bucket(n),
                        live[0].plane_rows(),
                        plan.slice_bucket(n),
                        len(row_ids),
                        len(leaves),
                        live[0].plane_words(),
                    )
                )
        shapes -= self._gather_warmed
        if not shapes:
            return
        self._gather_warmed |= shapes

        def run():
            try:
                warmup.prewarm_gather(shapes, devices)
            except Exception:  # noqa: BLE001 — a warm-up: the gather's own
                pass  # first call compiles, and reports, what this could not

        self._gather_warming = threading.Thread(
            target=run, daemon=True, name="gather-warm"
        )
        self._gather_warming.start()

    def _assemble_gather_batch(self, leaves, slices: list[int], sweep, mesh, keep=None):
        """Assemble the leaf batch ON the device from the fragments'
        resident plane mirrors: per device, ceil(members / 64) launches
        of one gather program (``bp.gather_planes``, the TopN scorer's
        convention: the mirrors are operands, rows are picked by slot
        inside the program) written in place into one zeroed block of
        the slice bucket the consumers use.  No row is copied on the
        host and nothing crosses host<->device but the slot indices.
        One chip: the block is the batch.  A mesh: a block a home
        device over ``_mesh_placement``'s groups (a spilled slice is
        gathered where its plane lives and moved), glued by
        ``pmesh.assemble_sharded_batch``.

        Returns ``(batch, pos_of, kept, empties)`` exactly as the host
        fills give them (``kept`` is decided from the fragments' slot
        maps, no plane is read) — the three producers feed one batch
        cache — or None where the gather does not apply and the caller
        fills on the host: a time-quantum Range (a union over views), a
        sparse-tier row (no plane holds it), or a launch that failed
        for a device reason.

        A column whose view has a kept gather layout that stands
        (:meth:`_gather_layout`) takes its slots and mirrors from it, and
        no fragment is asked anything; the others ask each fragment
        (``Fragment.gather_slots``), and where ``keep`` — ``(epoch,
        validity vector)`` as they stood before the sweep — is given,
        their views' layouts are kept on the way.  The span's ``layout``
        tag says ``kept``, ``made`` or ``none``."""
        gatherable = self._gather_columns(leaves, sweep)
        if gatherable is None:
            return None
        cols, consts = gatherable
        n = len(slices)
        with self.tracer.span("plan.leaves") as sp:
            planes = [[None] * n for _ in cols]
            slots = [np.full((n, len(c[1])), -1, dtype=np.int32) for c in cols]
            held = np.zeros(n, dtype=bool)
            # the pool keys of the layouts that served, and the views
            # that were swept, a view once
            served: dict = {}
            swept: dict = {}
            for g, (j, row_ids, frags, view, view_layout) in enumerate(cols):
                got = (
                    None
                    if view_layout is None
                    else self._layout_slots(view_layout, row_ids)
                )
                if got is not None:
                    use = (got >= 0).any(axis=1)
                    planes[g] = [
                        p if u else None
                        for p, u in zip(view_layout["planes"], use.tolist())
                    ]
                    slots[g] = got
                    held |= use
                    served[id(view)] = view_layout["pins"]
                    continue
                for i, frag in enumerate(frags):
                    if frag is None:
                        continue
                    ref = frag.gather_slots(row_ids)
                    if ref is None:
                        sp.annotate(path="gather_declined", reason="sparse_tier")
                        return None
                    if ref[0] is not None:
                        planes[g][i], slots[g][i] = ref
                        held[i] = True
                if view_layout is None:
                    swept[id(view)] = (view, frags, j)
            if served:
                # one hold of the pool's lock keeps every mirror recent
                device_mod.pool().touch_many(
                    [k for pins in served.values() for k in pins]
                )
            made = 0
            if keep is not None:
                epoch, versions = keep
                for view, frags, j in swept.values():
                    made += (
                        self._keep_gather_layout(
                            view, slices, frags, epoch, versions[j]
                        )
                        is not None
                    )
            layout_tag = "made" if made else "none" if swept else "kept"
            kept = [s for i, s in enumerate(slices) if held[i]]
            empties = [s for i, s in enumerate(slices) if not held[i]]
            n_rows = int(sum((sl >= 0).sum() for sl in slots))
            if not kept:
                sp.annotate(
                    path="plane_gather",
                    rows=0,
                    launches=0,
                    device_copies=0,
                    layout=layout_tag,
                )
                return None, {}, kept, empties
            at = {s: i for i, s in enumerate(slices)}
            if mesh is None or len(kept) == 1:
                # (device, block rows, members); one kept slice of a mesh
                # is a plain one-device batch, as the host fill gives it
                layout = [
                    (bp.home_device(kept[0]), plan.slice_bucket(len(kept)), kept)
                ]
                pos_of = {s: i for i, s in enumerate(kept)}
            else:
                n_dev = int(mesh.devices.size)
                groups, chunk = self._mesh_placement(kept, n_dev)
                layout = [
                    (mesh.devices.flat[d], chunk, groups[d]) for d in range(n_dev)
                ]
                pos_of = {
                    s: d * chunk + i
                    for d in range(n_dev)
                    for i, s in enumerate(groups[d])
                }

            def block_of(dev, rows, members):
                return self._gather_block(
                    dev,
                    (rows, len(leaves), bp.WORDS_PER_SLICE),
                    cols,
                    planes,
                    slots,
                    [at[s] for s in members],
                )

            try:
                self._fault_check_launch("gather")
                # a device's own slices lead its group; what spilled in
                # from a fuller device follows
                homes = [
                    [s for s in members if bp.home_device(s) == dev]
                    for dev, _, members in layout
                ]
                blocks, launches = [], 0
                for home, (dev, rows, _) in zip(homes, layout):
                    block, n_launched = block_of(dev, rows, home)
                    blocks.append(block)
                    launches += n_launched
                sp.annotate(
                    path="plane_gather",
                    rows=n_rows,
                    launches=launches,
                    device_copies=launches,
                    layout=layout_tag,
                )
            except Exception as e:
                if health_mod.classify(e) is None:
                    raise
                sp.annotate(path="gather_declined", reason=type(e).__name__)
                return None
        with self.tracer.span("plan.transfer", devices=len(layout)) as sp:
            try:
                spilled = 0
                for d, ((dev, _, members), home) in enumerate(zip(layout, homes)):
                    block = blocks[d]
                    for i in range(len(home), len(members)):
                        s = members[i]
                        one, _ = block_of(bp.home_device(s), 1, [s])
                        block = bp.place_rows(
                            block,
                            jax.device_put(one, dev),
                            i,
                            first_call=plan.note_gather_first_call,
                        )
                        spilled += 1
                    for col, row in consts:
                        block = bp.place_const(
                            block,
                            row,
                            len(members),
                            col,
                            first_call=plan.note_gather_first_call,
                        )
                    blocks[d] = block
                batch = (
                    blocks[0]
                    if len(blocks) == 1
                    else pmesh.assemble_sharded_batch(blocks, mesh)
                )
            except Exception as e:
                if health_mod.classify(e) is None:
                    raise
                sp.annotate(declined=type(e).__name__)
                return None
            sp.annotate(bytes=int(batch.nbytes), spilled=spilled)
        return batch, pos_of, kept, empties

    @staticmethod
    def _gather_block(dev, shape: tuple, cols, planes, slots, members: list[int]):
        """The block of ``shape`` on ``dev`` whose leading rows are
        ``members`` (positions in the swept slice list, all homed on
        ``dev``), in order: every launch of the gather is dispatched
        without waiting and its output written into the zeroed block in
        place before the next is dispatched, so one launch's output (16
        MiB at 64 members x 2 rows) is all that lives beside the block.
        A padded launch's surplus rows are zeros and the next launch,
        in row order, writes over them.  A single launch that fills the
        block IS the block.  Members launch together while their planes
        share a shape (the jit key holds it); one that holds none of a
        run's rows rides with its neighbours and gathers zeros.
        Returns ``(block, launches)``."""
        block, launches = None, 0
        for g, (col0, *_) in enumerate(cols):
            pl = [planes[g][i] for i in members]
            sl = slots[g][members]
            lo, plane_shape = 0, None
            runs = []
            for m, p in enumerate(pl):
                if p is None:
                    continue
                if plane_shape is not None and p.shape != plane_shape:
                    runs.append((lo, m))
                    lo = m
                plane_shape = p.shape
            if plane_shape is not None:
                runs.append((lo, len(pl)))
            for lo, hi in runs:
                stand_in = next(p for p in pl[lo:hi] if p is not None)
                for a, b in _fitting_runs(lo, hi, shape[0]):
                    if not (sl[a:b] >= 0).any():
                        continue
                    outs = bp.gather_planes(
                        [p if p is not None else stand_in for p in pl[a:b]],
                        sl[a:b],
                        first_call=plan.note_gather_first_call,
                    )
                    for t, out in enumerate(outs):
                        launches += 1
                        if tuple(out.shape) == shape:
                            block = out
                            continue
                        if block is None:
                            block = jnp.zeros(shape, dtype=jnp.uint32, device=dev)
                        block = bp.place_rows(
                            block,
                            out,
                            a + t * int(out.shape[0]),
                            col0,
                            first_call=plan.note_gather_first_call,
                        )
        if block is None:
            block = jnp.zeros(shape, dtype=jnp.uint32, device=dev)
        return block, launches

    def _assemble_mesh_batch_host(self, index: str, leaves, slices, mesh):
        """Host-side mesh batch assembly for COLD fragments: read leaf
        rows from the authoritative numpy planes, group by home device
        (slice mod n_devices, same placement as _assemble_gather_batch,
        including balanced-chunk spill), and ship ONE block per device.
        Returns (batch, pos_of, kept, empties); batch is None when
        nothing is set, and a plain single-device array when only one
        slice survives (callers then run the non-collective path)."""
        n_leaves = len(leaves)
        rows_of: dict[int, np.ndarray] = {}
        kept: list[int] = []
        empties: list[int] = []
        with self.tracer.span("plan.leaves", path="mesh_host_fill") as sp:
            n_rows = 0
            for s in slices:
                buf = None
                any_set = False
                for j, leaf in enumerate(leaves):
                    w = self._leaf_row_host(index, leaf, s)
                    if w is not None:
                        if buf is None:
                            buf = np.zeros(
                                (n_leaves, bp.WORDS_PER_SLICE), dtype=np.uint32
                            )
                        buf[j] = w
                        n_rows += 1
                        if leaf.name not in plan.NEUTRAL_LEAVES:
                            any_set = True
                if not any_set:
                    empties.append(s)
                else:
                    kept.append(s)
                    rows_of[s] = buf
            sp.annotate(rows=n_rows, device_copies=0)
        if not kept:
            return None, {}, kept, empties
        if len(kept) == 1:
            with self.tracer.span("plan.transfer", devices=1) as sp:
                one = rows_of[kept[0]][None]
                sp.annotate(bytes=int(one.nbytes))
                return jnp.asarray(one), {kept[0]: 0}, kept, empties

        n_dev = int(mesh.devices.size)
        with self.tracer.span("plan.transfer", devices=n_dev) as sp:
            groups, chunk = self._mesh_placement(kept, n_dev)
            blocks = []
            pos_of: dict[int, int] = {}
            n_bytes = 0
            for d in range(n_dev):
                block = np.zeros(
                    (chunk, n_leaves, bp.WORDS_PER_SLICE), dtype=np.uint32
                )
                for i, s in enumerate(groups[d]):
                    block[i] = rows_of[s]
                    pos_of[s] = d * chunk + i
                n_bytes += block.nbytes
                blocks.append(jax.device_put(block, mesh.devices.flat[d]))
            sp.annotate(bytes=int(n_bytes))
            batch = pmesh.assemble_sharded_batch(blocks, mesh)
        return batch, pos_of, kept, empties

    @staticmethod
    def _mesh_placement(kept: list[int], n_dev: int):
        """Slice -> device placement shared by BOTH batch assemblers
        (plane gather and cold host blocks): home device = slice mod
        n_devices (matching fragment plane placement), chunk = balanced
        power-of-two (pow2 >= ceil(n/n_devices)), clustered overflow
        spilled to devices with free rows.  Returns ({device: [slices]},
        chunk); the two assemblers MUST produce identical pos_of layouts
        for the same kept set, since their outputs share the batch
        cache."""
        groups: dict[int, list[int]] = {d: [] for d in range(n_dev)}
        for s in kept:
            groups[s % n_dev].append(s)
        chunk = plan.slice_bucket((len(kept) + n_dev - 1) // n_dev)
        spill: list[int] = []
        for d in range(n_dev):
            while len(groups[d]) > chunk:
                spill.append(groups[d].pop())
        for d in range(n_dev):
            while spill and len(groups[d]) < chunk:
                groups[d].append(spill.pop())
        return groups, chunk

    def _leaf_sweep(
        self, index: str, leaves, slices: list[int], kept: bool = False
    ) -> list:
        """What every leaf reads over ``slices``, resolved once a leaf
        (one hold of a view's lock, ``View.fragments_at``) and not once
        a (slice, leaf): a list, an entry a leaf, of

        * ``("rows", view, row id, fragments, layout)`` — a Bitmap or
          BsiPlane leaf: one row of each slice's fragment (None where the
          view has none; the view itself None where the frame lacks it);
          ``layout`` the view's kept gather layout where ``kept`` asks
          for it and one stands (:meth:`_gather_layout`: its fragments,
          and no hold of the view's lock), else None;
        * ``("range", quantum, [fragments a time view])`` — a
          time-quantum Range leaf, ``("range", None, [])`` when it
          cannot resolve (no quantum);
        * ``("const",)`` — a slice-invariant row (BsiPred, BsiZero):
          its identity is in the canonical call string of the key.

        Pure dict lookups, no device work.  The cache's validity vector
        and the device gather are both made from it, so a miss never
        resolves the pairs twice."""
        out: list = []
        none = [None] * len(slices)
        for leaf in leaves:
            if leaf.name in plan.NEUTRAL_LEAVES:
                out.append(("const",))
            elif leaf.name == "Range":
                # frame lookup, timestamp parsing and time-view
                # enumeration are slice-invariant
                ctx = self._range_leaf_context(index, leaf)
                if ctx is None:
                    out.append(("range", None, []))
                    continue
                frame, quantum, views = ctx
                per_view = []
                for name in views:
                    v = self.holder.view(index, frame, name)
                    per_view.append(none if v is None else v.fragments_at(slices))
                out.append(("range", quantum, per_view))
            else:
                if leaf.name == "BsiPlane":
                    view = self.holder.view(
                        index,
                        leaf.args["frame"],
                        bsi.field_view_name(leaf.args["field"]),
                    )
                    row_id = leaf.args["row"]
                else:
                    view, row_id = self._resolve_bitmap_view(index, leaf)
                # leaves of one view share the list (and the lock hold)
                shared = next(
                    (e for e in out if e[0] == "rows" and e[1] is view), None
                )
                if shared is not None:
                    frags, layout = shared[3], shared[4]
                elif view is None:
                    frags, layout = none, None
                else:
                    layout = self._gather_layout(view, slices) if kept else None
                    frags = (
                        view.fragments_at(slices)
                        if layout is None
                        else layout["frags"]
                    )
                out.append(("rows", view, row_id, frags, layout))
        return out

    @staticmethod
    def _sweep_versions(sweep: list):
        """``(validity vector, n_fragments, n_without_device_mirror)``
        of a :meth:`_leaf_sweep`: (fragment identity, version) per
        (leaf, slice)."""
        out = []
        n_frag = n_cold = 0
        for ent in sweep:
            if ent[0] == "const":
                out.append(ent)
                continue
            if ent[0] == "rows" and ent[4] is not None:
                layout = ent[4]
                out.append(layout["versions"])
                n_frag += layout["n_frag"]
                n_cold += layout["n_cold"]
                continue
            lists = [ent[3]] if ent[0] == "rows" else ent[2]
            vers = []
            for frags in lists:
                vers.append(
                    tuple(
                        None if f is None else (f._serial, f._version)
                        for f in frags
                    )
                )
                live = [f for f in frags if f is not None]
                n_frag += len(live)
                n_cold += sum(1 for f in live if f._device is None)
            out.append(
                vers[0] if ent[0] == "rows" else ("range", ent[1], tuple(vers))
            )
        return tuple(out), n_frag, n_cold

    def _leaf_versions(self, index: str, leaves, slices: list[int]):
        """The cache validity vector alone (see :meth:`_leaf_sweep`)."""
        return self._sweep_versions(self._leaf_sweep(index, leaves, slices))[0]

    # Kept gather layouts, one a (view, slice set): the handful of views
    # a deployment counts over.
    _GATHER_LAYOUT_CAP = 8

    def _gather_layout(self, view, slices: list[int]) -> dict | None:
        """The kept gather layout of ``view`` over ``slices`` where it
        stands, with ``planes`` the mirror a slice (None: no dense row),
        else None (one that fails is dropped at once).  A layout
        (:meth:`_keep_gather_layout`) is what a Count's miss would ask
        every fragment of the view for, and what no text changes: the
        fragments, their validity vector, each one's mirror and where
        every dense row lies in it.  It stands while nothing was written
        since it was made (the write epoch in O(1), then the fragments'
        versions, which every write moves: a point write's queued
        scatter is applied by the sweep that then makes a fresh one)
        and every mirror it names is still its fragment's
        (``Fragment.mirror_is``, no lock: the pool's eviction or a
        re-upload moves it).  It names them weakly, so a mirror that
        the pool drops is freed with the fragment's hold on it.
        Whether an asked row is in a sparse tier is
        :meth:`_layout_slots`'s to say."""
        key = (view.index, view.frame, view.name, tuple(slices))
        with self._batch_mu:
            layout = self._gather_layouts.get(key)
            if layout is not None:
                self._gather_layouts.move_to_end(key)
        if layout is None:
            return None
        epoch = fragment_mod.write_epoch()
        planes = [None if r is None else r() for r in layout["refs"]]
        if (
            layout["epoch"] == epoch
            or layout["versions"]
            == tuple(self._frag_versions(view.fragments_at(slices)))
        ) and all(
            planes[i] is not None and f.mirror_is(planes[i])
            for i, f in layout["rowful"]
        ):
            layout["epoch"] = epoch
            return dict(layout, planes=planes)
        with self._batch_mu:
            if self._gather_layouts.get(key) is layout:
                del self._gather_layouts[key]
        return None

    def _keep_gather_layout(
        self, view, slices: list[int], frags, epoch: int, versions: tuple
    ) -> dict | None:
        """Keep what a sweep of ``view``'s ``frags`` over ``slices`` read
        as the view's gather layout (see :meth:`_gather_layout`):
        ``epoch`` and ``versions`` as they stood before the sweep read
        anything, so a write since leaves it stale and never wrong.
        Each fragment is asked once (``Fragment.gather_tiers``); the
        layout names the pool's own mirrors, weakly, and holds host
        integers: nothing is admitted to the pool.  None, and nothing
        kept, where a fragment that holds a dense row has no current
        mirror."""
        tiers, refs, rowful, sparse = [], [], [], [_NO_ROWS]
        n_frag = n_cold = 0
        for i, f in enumerate(frags):
            if f is None:
                tiers.append((_NO_ROWS, _NO_ROWS))
                refs.append(None)
                continue
            ids, at, sparse_ids, mirror = f.gather_tiers()
            n_frag += 1
            n_cold += mirror is None
            tiers.append((ids, at))
            sparse.append(sparse_ids)
            if not len(ids):
                refs.append(None)
            elif mirror is None:
                return None
            else:
                refs.append(weakref.ref(mirror))
                rowful.append((i, f))
        layout = {
            "epoch": epoch,
            "versions": versions,
            "frags": frags,
            "serials": frozenset(f._serial for f in frags if f is not None),
            "refs": refs,
            "rowful": tuple(rowful),
            "pins": tuple(f._pool_key for _, f in rowful),
            "table": topn_stack.src_table(tiers),
            "sparse": np.unique(np.concatenate(sparse)),
            "n_frag": n_frag,
            "n_cold": n_cold,
        }
        key = (view.index, view.frame, view.name, tuple(slices))
        with self._batch_mu:
            self._gather_layouts[key] = layout
            self._gather_layouts.move_to_end(key)
            while len(self._gather_layouts) > self._GATHER_LAYOUT_CAP:
                self._gather_layouts.popitem(last=False)
        return layout

    @staticmethod
    def _layout_slots(layout: dict, row_ids) -> np.ndarray | None:
        """``int32[slices, rows]``: where each of ``row_ids`` lies in each
        fragment's plane of a kept gather layout, -1 where it is not held
        (as ``Fragment.gather_slots`` gives it; a BsiZero pad, row id
        None, is held nowhere), in a fixed number of numpy calls a row.
        None where a row lives in some fragment's sparse tier: no plane
        holds it, and the sweep declines."""
        asked = [r for r in row_ids if r is not None]
        if len(layout["sparse"]) and np.isin(asked, layout["sparse"]).any():
            return None
        table = layout["table"]
        out = np.full((table.parts, len(row_ids)), -1, dtype=np.int32)
        for k, row in enumerate(row_ids):
            if row is not None:
                out[:, k] = topn_stack.row_slots(table, row)
        return out

    def _range_leaf_context(self, index: str, c: Call):
        """Slice-invariant validity context for one Range leaf:
        ``(frame, quantum, views)`` — the frame's time quantum (the view
        set depends on it) and the resolved time-view names — or None
        when the leaf cannot resolve (no frame / no quantum)."""
        frame = c.args.get("frame") or DEFAULT_FRAME
        idx = self.holder.index(index)
        f = idx.frame(frame) if idx is not None else None
        if f is None or not f.time_quantum:
            return None
        view_name, _, start, end, quantum = self._resolve_range(idx, f, c)
        views = list(tq.views_by_time_range(view_name, start, end, quantum))
        return frame, str(quantum), views

    def _fault_check_launch(self, site: str) -> None:
        """Chaos hook at a device-launch site (testing/faults.py),
        fired once per participating device so a rule can target ONE
        flaky device of the mesh.  The exception is annotated with the
        matched device ordinal, letting the health layer narrow the
        blame to that device's path."""
        host = self.host or None
        for i in range(len(self.device_health.device_paths())):
            try:
                faults.check(
                    "device.launch", host=host, path=site, device=i
                )
            except Exception as e:
                if getattr(e, "fault_device", None) is None:
                    try:
                        e.fault_device = i
                    except Exception:  # noqa: BLE001 — slots-only excs
                        pass
                raise

    def _launch_guarded(self, paths, mode, device_fn, retry_fn, host_fn):
        """Run one device launch under the health gate: classify a
        failure (device/health.classify — non-device exceptions
        re-raise unchanged), retry ONCE via ``retry_fn`` for transient
        runtime errors, drive the quarantine state machine, and fall
        back to ``host_fn`` (the byte-identical host evaluator) when
        the launch finally fails.  ``mode`` is the pre-acquired
        admission mode (possibly a half-open probe)."""
        health = self.device_health
        probe = mode == health_mod.MODE_PROBE
        try:
            res = device_fn()
        except Exception as e:
            kind = health_mod.classify(e)
            if kind is None:
                raise
            dev = getattr(e, "fault_device", None)
            if (
                kind == health_mod.KIND_ERROR
                and not probe
                and retry_fn is not None
            ):
                # Transient runtime errors get ONE immediate retry
                # before counting against the breaker — a single
                # glitch must not start the quarantine clock.
                self.holder.stats.count("device.launch.retries")
                try:
                    res = retry_fn()
                except Exception as e2:
                    kind2 = health_mod.classify(e2)
                    if kind2 is None:
                        raise
                    health.failure(
                        paths,
                        kind2,
                        probe=probe,
                        device=getattr(e2, "fault_device", dev),
                    )
                    return host_fn()
                health.success(paths, probe=probe)
                return res
            health.failure(paths, kind, probe=probe, device=dev)
            return host_fn()
        health.success(paths, probe=probe)
        return res

    def _record_direct_launch(
        self, ent: dict, reduce: str, t0, t_disp, t1, site: str = "direct"
    ) -> None:
        """Launch telemetry for an uncoalesced executor launch
        (obs/perf.py): logical plane bytes from the KEPT slice rows
        (pad slices are bucketing overhead, not plane traffic) times
        the batch's leaf x word geometry."""
        if not perf_mod.enabled():
            return
        geom = ent.get("perf_geom")
        if geom is None:
            # Computed once per (cached) batch entry: poking a sharded
            # device array's shape metadata costs tens of microseconds,
            # which would land on every query of a hot cached batch.
            # Benign race — the value is idempotent.
            batch = ent["batch"]
            rows = len(ent.get("pos_of") or ()) or int(batch.shape[0])
            words = int(np.prod(batch.shape[1:]))
            ent["perf_geom"] = geom = (rows, words)
        rows, words = geom
        perf_mod.record_launch(
            site,
            reduce=reduce,
            rows=rows,
            n_bytes=perf_mod.plane_bytes(rows, words),
            dispatch_ms=(t_disp - t0) * 1e3,
            total_ms=(t1 - t0) * 1e3,
            trace_id=perf_mod.current_trace_id(),
        )

    def _coalesce_eval(self, ent: dict, reduce: str):
        """Route one assembled batch through the coalescing scheduler;
        returns the host result rows for THIS entry (``[n, words]`` for
        "row", int32 ``[n]`` partials for "count"), or None when the
        scheduler is closed (callers fall back to a direct launch).

        The per-query ``coalesce`` span covers queue wait + the shared
        launch and carries the launch's batch stats (occupancy, rows,
        padding) — the trace-level evidence that N queries rode one
        dispatch.  The dispatcher records the launch itself as a
        ``launch`` child (with a ``compile`` under it on a first call
        per shape), so this span's self time is the queue wait."""
        # Chaos hook: an injected fault here surfaces exactly like a
        # coalesced launch error — the waiter's health guard classifies
        # it and fails over PER WAITER, never poisoning the shared
        # batch.
        self._fault_check_launch("coalesce")
        with self.tracer.span("coalesce", reduce=reduce) as sp:
            try:
                fut = self.coalescer.submit(
                    ent["expr"],
                    reduce,
                    ent["batch"],
                    pin_keys=(ent.get("pool_key"),),
                    leaf_keys=ent.get("leaf_keys"),
                )
            except coalesce_mod.CoalesceClosed:
                sp.annotate(fallback="closed")
                return None
            # The wait honors the query deadline: a flat RESULT_TIMEOUT_S
            # here once made every waiter ride out 600 s regardless of
            # its budget.  On expiry the waiter DETACHES — the shared
            # launch is never cancelled, so the batch keeps serving its
            # other waiters and the scheduler stays healthy.
            timeout = coalesce_mod.RESULT_TIMEOUT_S
            dl = resilience.current_deadline()
            if dl is not None:
                timeout = dl.clamp(timeout)
            try:
                res, info = coalesce_mod.await_result(fut, timeout)
            except FuturesTimeoutError:
                sp.annotate(deadline="expired")
                # The detached waiter will never call result() again,
                # so a batch-level launch error landing later would sit
                # unobserved (GC logs "exception was never retrieved"
                # per abandoned waiter).  Hand the future a consumer
                # that retrieves and COUNTS it instead.
                fut.add_done_callback(
                    coalesce_mod.consume_abandoned(self.holder.stats)
                )
                if dl is not None and dl.expired:
                    raise resilience.DeadlineExceeded(
                        "deadline expired waiting for coalesced launch"
                    ) from None
                raise
            sp.annotate(**info)
            if info.get("fused"):
                # The `fuse` span: this query rode a multi-query
                # interpreter launch — its batch composition (trees,
                # ops, subtree-dedup hits) lands in the trace and the
                # slow-query log's `fuse` block.
                with self.tracer.span(
                    "fuse",
                    **{
                        k: info[k]
                        for k in (
                            "batch_queries",
                            "programs",
                            "ops",
                            "dedup_hits",
                            "batch_rows",
                            "pad_leaves",
                        )
                        if k in info
                    },
                ):
                    pass
        return res

    def _eval_tree_slices(
        self, index: str, c: Call, slices: list[int], reduce: str
    ) -> dict[int, object]:
        """Evaluate a bitmap call tree over local slices as one batched
        device program: leaves for all slices stack into a
        uint32[n_slices, n_leaves, 32768] array and the jitted tree fn is
        vmapped over the slice axis — the TPU-shaped replacement for the
        reference's goroutine-per-slice mapperLocal.

        The launch rides the device-health gate: a quarantined device
        answers from the authoritative host planes (byte-identical, no
        device batch assembled at all), and a launch failure classifies,
        retries once for transient errors, then quarantine-drives the
        state machine and falls over to the host evaluator."""
        out: dict[int, object] = {}
        if not slices:
            return out
        paths = self.device_health.device_paths()
        mode = self.device_health.acquire(paths)
        if mode == health_mod.MODE_DENY:
            if reduce == "count":
                return self.hosteval.counts(index, c, slices)
            return self.hosteval.rows(index, c, slices)
        ent = self._cached_batch(index, c, slices)

        for s in ent["empties"]:
            out[s] = 0 if reduce == "count" else None
        if ent["batch"] is None:
            if mode == health_mod.MODE_PROBE:
                self.device_health.cancel_probe(paths)
            return out

        def direct():
            # Pin lease for the duration of the fused program: the pool
            # may not evict the batch out from under the dispatch+fetch.
            with device_mod.pool().pinned(
                ent.get("pool_key")
            ), self.tracer.span("exec.device", reduce=reduce):
                self._fault_check_launch("direct")
                t0 = time.monotonic()
                if ent["mesh"] is not None:
                    # plain-XLA formulation: partitions cleanly under SPMD
                    out_dev = plan.compiled_batched(ent["expr"], reduce)(
                        ent["batch"]
                    )
                    t_disp = time.monotonic()
                    res = _device_get(out_dev)
                else:
                    res = plan.compiled_batched(ent["expr"], reduce)(
                        ent["batch"]
                    )
                    t_disp = time.monotonic()
                    if reduce == "row":
                        # Every consumer of row results materializes them
                        # on the host (client responses, merges), so fetch
                        # the WHOLE batch in ONE transfer — per-slice lazy
                        # slices would each pay a device round trip when
                        # coerced.
                        res = np.asarray(_device_get(res))
                t1 = time.monotonic()
                self._record_direct_launch(ent, reduce, t0, t_disp, t1)
                return res

        def device_fn():
            # Coalesced path: concurrent queries sharing this compile key
            # ride one launch; the scheduler pins every batch in the
            # launch and scatters this entry's rows back.
            if self.coalescer is not None:
                res = self._coalesce_eval(ent, reduce)
                if res is not None:
                    return res
            return direct()

        kept = list(ent["pos_of"])
        res = self._launch_guarded(
            paths,
            mode,
            device_fn,
            retry_fn=direct,
            host_fn=lambda: (
                self.hosteval.counts(index, c, kept)
                if reduce == "count"
                else self.hosteval.rows(index, c, kept)
            ),
        )
        if isinstance(res, dict):
            out.update(res)
        else:
            out.update({s: res[p] for s, p in ent["pos_of"].items()})
        return out

    def _eval_tree_slices_host(
        self, index: str, c: Call, slices: list[int]
    ) -> dict[int, object]:
        """HOST (numpy) evaluation of a bitmap tree per slice — for
        consumers that need host words (TopN src).  Authoritative planes
        are host-resident, so this touches no device state."""
        c = self._rewrite_bsi(index, c)
        expr, leaves = plan.decompose(c)
        out: dict[int, object] = {}
        for s in slices:
            rows = [self._leaf_row_host(index, leaf, s) for leaf in leaves]
            out[s] = plan.eval_expr_np(expr, rows, bp.WORDS_PER_SLICE)
        return out

    # ------------------------------------------------------------------
    # anchored position-domain count (compressed-plane fast path)
    # ------------------------------------------------------------------

    # Anchor-cardinality routing ceiling, in positions.  Past one dense
    # row's worth of words (32768 positions = 3.1% of a slice) the
    # position-domain gathers cost more than streaming the dense words,
    # so denser anchors keep the batched word-domain path.
    ANCHORED_MAX_POSITIONS = bp.WORDS_PER_SLICE

    @staticmethod
    def _expr_fold_only(expr: tuple) -> bool:
        """True when the decomposed tree is pure set algebra (leaves +
        Intersect/Union/Difference/Xor) — membership masks compose
        pointwise only for those, never for the BSI interiors."""
        if expr[0] == "leaf":
            return True
        if expr[0] not in plan.FOLD_CALLS:
            return False
        return all(Executor._expr_fold_only(ch) for ch in expr[1:])

    @staticmethod
    def _anchor_candidates(expr: tuple) -> set:
        """Leaf indices guaranteed to be SUPERSETS of the expression
        result: the result of an Intersect is a subset of every child's
        result, a Difference of its FIRST child's — so any leaf
        reachable from the root through only those edges bounds the
        result, and counting inside its position set is exact."""
        if expr[0] == "leaf":
            return {expr[1]}
        if expr[0] == "Intersect":
            out: set = set()
            for ch in expr[1:]:
                out |= Executor._anchor_candidates(ch)
            return out
        if expr[0] == "Difference" and len(expr) > 1:
            return Executor._anchor_candidates(expr[1])
        return set()

    def _try_anchored_count(
        self, index: str, c: Call, slices: list[int], sp
    ):
        """Compressed-plane Count: when the tree is fold-only over
        Bitmap leaves and some AND-dominating leaf is sparse, evaluate
        the expression POINTWISE over that anchor leaf's positions
        against each leaf's container payload (plan.anchored_count_exec)
        — device bytes proportional to cardinality, not to leaves x
        128 KiB.  Returns the exact total, or None to decline (the
        caller falls through to the batched word-domain path; any
        failure here also declines, so the guarded path retains its
        retry/host-fallback semantics).

        Two passes.  The METADATA pass reads only what the views and
        fragments already hold and settles the route.  Where no leaf's
        view holds a sparse-tier row (View.dense_tier_only: one compare
        on a read-mostly load) it declines at once.  Otherwise it walks
        the slices over each leaf's cached cardinality and container
        format (Fragment.row_meta) for the per-slice anchor, and stops
        at the first anchor too dense or finds no compressed leaf.  It
        touches no plane, so a decline scans, expands and copies
        nothing.  Only when the route answers does the second pass
        read the anchors' positions and the leaves' payloads and
        launch.

        ``sp`` is the caller's ``anchored.prepass`` span: it leaves with
        an ``outcome`` (``not_eligible`` before any view is looked at,
        else ``declined_too_dense`` / ``declined_dense`` / ``answered``
        / ``error``), ``slices_walked`` (how far the metadata pass's
        walk got: 0 where the views alone decided) and
        ``anchors_scanned`` (Fragment.row_positions calls: 0 on every
        decline)."""
        sp.annotate(outcome="not_eligible", slices_walked=0, anchors_scanned=0)
        if bp.PLANE_FORMAT == "dense":
            return None
        try:
            expr, leaves = plan.decompose(self._rewrite_bsi(index, c))
        except Exception:  # noqa: BLE001 — let the main path raise it
            return None
        if not leaves or any(leaf.name != "Bitmap" for leaf in leaves):
            return None
        if not self._expr_fold_only(expr):
            return None
        cands = sorted(self._anchor_candidates(expr))
        if not cands:
            return None
        outcome = "error"
        walked = scanned = 0
        try:
            # Metadata pass.  Frame / view / row id resolve once per
            # leaf; a view shared by several leaves is looked at once.
            views: list = []
            leaf_keys: list[tuple[int, int]] = []  # (index into views, row id)
            for leaf in leaves:
                view, rid = self._resolve_bitmap_view(index, leaf)
                if view not in views:
                    views.append(view)
                leaf_keys.append((views.index(view), rid))
            if all(v is None or v.dense_tier_only() for v in views):
                # No fragment of any leaf's view holds a sparse-tier
                # row, so every leaf of every slice is a full dense
                # plane: the position-domain gathers save no bytes, and
                # the batched word-domain path keeps its cache/coalesce
                # behavior.  Dense-tier corpora (the default budget)
                # always leave here, having walked no slice.
                outcome = "declined_dense"
                return None
            # Each view's fragments in one lookup, then per slice one
            # row_meta per leaf.
            frags_of = [
                v.fragments_at(slices) if v is not None else [None] * len(slices)
                for v in views
            ]
            picked: list[tuple[int, int]] = []  # (position in slices, anchor)
            any_compressed = False
            for at in range(len(slices)):
                walked = at + 1
                metas = [
                    frags_of[vi][at].row_meta(rid)
                    if frags_of[vi][at] is not None
                    else _ABSENT_ROW_META
                    for vi, rid in leaf_keys
                ]
                # smallest candidate, the first of equals
                card, ai = min((metas[i][0], i) for i in cands)
                if card == 0:
                    continue  # empty anchor bounds the slice count at 0
                if card > self.ANCHORED_MAX_POSITIONS:
                    # too dense: whole query keeps one path
                    outcome = "declined_too_dense"
                    return None
                picked.append((at, ai))
                any_compressed = any_compressed or any(
                    fmt not in (None, bp.FMT_DENSE) for _, fmt in metas
                )
            if not any_compressed:
                # Sparse-tier rows exist, but none of them compressed
                # under a non-empty anchor: dense planes all the same.
                outcome = "declined_dense"
                return None
            # The route answers: anchor positions + leaf payloads,
            # grouped by the per-leaf container-format signature
            # (formats may differ per slice; each signature is its own
            # compiled wrapper).
            groups: dict[tuple, list] = {}
            for at, ai in picked:
                scanned += 1
                vi, rid = leaf_keys[ai]
                anchor = frags_of[vi][at].row_positions(rid)
                if anchor is None or len(anchor) == 0:
                    continue
                fmts: list[int] = []
                payloads: list = []
                eff = 4 * len(anchor)
                for vi, rid in leaf_keys:
                    frag = frags_of[vi][at]
                    hp = (
                        frag.host_payload(rid) if frag is not None else None
                    )
                    if hp is None:
                        # Absent row: all-sentinel sparse payload, so
                        # membership answers False on every real lane.
                        fmts.append(bp.FMT_SPARSE)
                        payloads.append(_EMPTY_SPARSE_PAYLOAD)
                        eff += _EMPTY_SPARSE_PAYLOAD.nbytes
                    else:
                        fmt, payload, nbytes, _ = hp
                        fmts.append(fmt)
                        payloads.append(payload)
                        eff += nbytes
                groups.setdefault(tuple(fmts), []).append(
                    (anchor, payloads, eff)
                )
            total = 0
            for fmts, items in groups.items():
                total += self._anchored_launch(expr, fmts, items)
            outcome = "answered"
            return int(total)
        except Exception:  # noqa: BLE001 — decline, main path decides
            return None
        finally:
            sp.annotate(
                outcome=outcome, slices_walked=walked, anchors_scanned=scanned
            )

    def _anchored_launch(
        self, expr: tuple, fmts: tuple, items: list
    ) -> int:
        """One vmapped anchored launch for a group of slices sharing a
        container-format signature.  Every axis is pow2-bucketed (slice
        axis to plan.slice_bucket, anchor/payload axes to
        bp.payload_bucket) with sentinel padding, so the jit key stays
        pure geometry."""
        n = len(items)
        n_leaves = len(fmts)
        sb = plan.slice_bucket(n)
        pb = max(bp.payload_bucket(len(a)) for a, _, _ in items)
        anchor_np = np.full((sb, pb), bp.FMT_SENTINEL, dtype=np.uint32)
        for si, (anchor, _, _) in enumerate(items):
            anchor_np[si, : len(anchor)] = anchor
        payload_np = []
        for li in range(n_leaves):
            cols = [it[1][li] for it in items]
            if fmts[li] == bp.FMT_DENSE:
                arr = np.zeros((sb, bp.WORDS_PER_SLICE), dtype=np.uint32)
            elif fmts[li] == bp.FMT_SPARSE:
                lb = max(p.shape[0] for p in cols)
                arr = np.full((sb, lb), bp.FMT_SENTINEL, dtype=np.uint32)
            else:
                lb = max(p.shape[0] for p in cols)
                arr = np.full(
                    (sb, lb, 2), bp.FMT_SENTINEL, dtype=np.uint32
                )
            for si, p in enumerate(cols):
                arr[si, : p.shape[0]] = p
            payload_np.append(arr)
        logical = n * n_leaves * bp.WORDS_PER_SLICE * 4
        eff = sum(it[2] for it in items)
        t0 = time.monotonic()
        out = plan.anchored_count_exec(
            expr, fmts, jnp.asarray(anchor_np),
            [jnp.asarray(a) for a in payload_np],
        )
        t_disp = time.monotonic()
        res = _device_get(out)
        t1 = time.monotonic()
        if perf_mod.enabled():
            perf_mod.record_launch(
                "anchored",
                reduce="count",
                rows=n * n_leaves,
                n_bytes=logical,
                eff_bytes=eff,
                dispatch_ms=(t_disp - t0) * 1e3,
                total_ms=(t1 - t0) * 1e3,
                trace_id=perf_mod.current_trace_id(),
            )
        return int(sum(int(x) for x in res[:n]))

    def _count_slices_total(self, index: str, c: Call, slices: list[int]) -> int:
        """Count(tree) over local slices with the cross-slice reduce ON
        DEVICE.

        On a multi-device mesh the per-slice popcount partials sum
        across the sharded slice axis inside the jitted program — XLA
        inserts the all-reduce (psum over ICI) and only the limb pair
        comes back to the host, the collective replacement for the
        reference's HTTP fan-in reduce (reference: executor.go:1176-
        1207).  Falls back to the per-slice host sum (int64) beyond the
        limb partial budget or on single-device hosts."""
        if not slices:
            return 0
        paths = self.device_health.device_paths()
        mode = self.device_health.acquire(paths)
        if mode == health_mod.MODE_DENY:
            # Quarantined accelerator: host popcount over the
            # authoritative planes, no device batch assembled.
            return self.hosteval.count_total(index, c, slices)
        if mode == health_mod.MODE_OK:
            # Compressed-plane fast path: a fold-only tree with a
            # sparse AND-dominating anchor counts in the position
            # domain, reading bytes proportional to cardinality.
            # Declines (None) fall through to the batched word-domain
            # path unchanged.  Healthy devices only: a granted probe
            # must resolve through the guarded launch below.
            with self.tracer.span("anchored.prepass") as sp:
                anchored = self._try_anchored_count(index, c, slices, sp)
            if anchored is not None:
                return anchored
        ent = self._cached_batch(index, c, slices)
        if ent["batch"] is None:
            if mode == health_mod.MODE_PROBE:
                self.device_health.cancel_probe(paths)
            return 0
        kept_slices = ent["kept"]
        health = self.device_health
        fits_limbs = len(kept_slices) <= plan.MAX_ONDEVICE_COUNT_PARTIALS

        # Coalesced path.  A MESH-SHARDED entry within the limb budget
        # rides the "total" reduce: the cross-slice sum happens ON
        # DEVICE inside the (possibly fused multi-query) launch as an
        # all-reduce over ICI, and only an int32[2] (hi, lo) limb pair
        # returns to the host per query.  Zero pad slices contribute
        # nothing to either limb, and entries fused into one
        # interpreter pass read only their own leaf registers, so the
        # on-device total equals the per-position host sum
        # byte-for-byte.  Unsharded entries keep the per-slice "count"
        # partials (int32-exact, <= 2^20 bits per slice-row; host sums
        # in unbounded Python ints — identical totals): the on-device
        # reduce buys them only a smaller fetch, while their batches'
        # committed-ness varies between the cold (host-assembled,
        # uncommitted) and warm (device-gathered, committed) builders —
        # distinct jit cache entries for one geometry, which would
        # break the totalCount family's hard cardinality bound.
        # A quarantined or watchdog-tripped COLLECTIVE path falls back
        # to the per-slice partials launch — single-device semantics on
        # the same sharded batch, no psum rendezvous to hang on.
        def coalesced():
            if (
                ent["mesh"] is not None
                and fits_limbs
                and health.collective_allowed()
            ):
                try:
                    res = self._coalesce_eval(ent, "total")
                except (
                    health_mod.LaunchWatchdogTimeout,
                    health_mod.CollectiveUnavailable,
                ):
                    res = None  # collective quarantined: partials below
                else:
                    if res is not None:
                        return plan.recombine_count_limbs(res)
            res = self._coalesce_eval(ent, "count")
            if res is not None:
                return sum(int(res[p]) for p in ent["pos_of"].values())
            return None

        def direct():
            with device_mod.pool().pinned(
                ent.get("pool_key")
            ), self.tracer.span("exec.device", reduce="count"):
                self._fault_check_launch("direct")
                if ent["mesh"] is not None:
                    # Zero pad slices contribute nothing, so the budget
                    # is on the real slice count, not the padded batch
                    # size.
                    if fits_limbs and health.collective_allowed():
                        # The program psums over the mesh: one
                        # collective launch in flight per process,
                        # serialized AND watchdogged
                        # (health.run_collective wraps
                        # plan.collective_launch) — a hung all-reduce
                        # trips instead of wedging the process.  The
                        # chaos checkpoint sits INSIDE the watched body
                        # so an injected kind=hang wedges where a real
                        # rendezvous would.
                        def _collective_body():
                            self._fault_check_launch("collective")
                            t0 = time.monotonic()
                            out = plan.compiled_total_count(
                                ent["expr"], ent["mesh"]
                            )(ent["batch"])
                            t_disp = time.monotonic()
                            res = jax.device_get(out)
                            self._record_direct_launch(
                                ent, "total", t0, t_disp,
                                time.monotonic(), site="collective",
                            )
                            return res

                        try:
                            # the body may run on the watchdog's
                            # thread: this one waits for it
                            with trace.blocked("device"):
                                limbs = health.run_collective(
                                    _collective_body
                                )
                            return plan.recombine_count_limbs(limbs)
                        except (
                            health_mod.LaunchWatchdogTimeout,
                            health_mod.CollectiveUnavailable,
                        ):
                            pass  # mesh path quarantined: partials
                    t0 = time.monotonic()
                    out = plan.compiled_batched(ent["expr"], "count")(
                        ent["batch"]
                    )
                    t_disp = time.monotonic()
                    res = _device_get(out)
                    self._record_direct_launch(
                        ent, "count", t0, t_disp, time.monotonic()
                    )
                    return int(
                        sum(int(res[p]) for p in ent["pos_of"].values())
                    )

                # Single device: same limb total-count program, no
                # collective — 8 bytes home instead of a per-slice
                # partial vector (zero pad slices contribute nothing).
                if fits_limbs:
                    t0 = time.monotonic()
                    limbs = plan.compiled_total_count(ent["expr"])(
                        ent["batch"]
                    )
                    t_disp = time.monotonic()
                    limbs = _device_get(limbs)
                    self._record_direct_launch(
                        ent, "total", t0, t_disp,
                        time.monotonic(), site="total",
                    )
                    return plan.recombine_count_limbs(limbs)
                t0 = time.monotonic()
                res = plan.compiled_batched(ent["expr"], "count")(ent["batch"])
                t_disp = time.monotonic()
                res = _device_get(res)
                self._record_direct_launch(
                    ent, "count", t0, t_disp, time.monotonic()
                )
                return sum(int(res[p]) for p in ent["pos_of"].values())

        def device_fn():
            if self.coalescer is not None:
                total = coalesced()
                if total is not None:
                    return total
            return direct()

        kept = list(ent["pos_of"])
        return self._launch_guarded(
            paths,
            mode,
            device_fn,
            retry_fn=direct,
            host_fn=lambda: self.hosteval.count_total(index, c, kept),
        )

    def _execute_bitmap_call(
        self, index: str, c: Call, slices: list[int], opt: ExecOptions
    ) -> RowBitmap:
        """reference: executor.go:203-261"""

        def map_fn(local_slices: list[int]):
            rows = self._eval_tree_slices(index, c, local_slices, "row")
            bm = RowBitmap()
            for s, row in rows.items():
                if row is not None:
                    bm.set_segment(s, row)
            return bm

        def reduce_fn(prev, v):
            if prev is None:
                prev = RowBitmap()
            prev.merge(v)
            return prev

        bm = self._map_reduce(index, slices, c, opt, map_fn, reduce_fn)
        if bm is None:
            bm = RowBitmap()

        # Attach attributes for Bitmap() calls (reference: executor.go:226-258).
        if c.name == "Bitmap":
            idx = self.holder.index(index)
            if idx is not None:
                column_label = idx.column_label
                col_id, col_ok = _uint_arg(c, column_label)
                if col_ok:
                    bm.attrs = idx.column_attr_store.attrs(col_id)
                else:
                    # Raw frame arg, NOT defaulted: with frame omitted the
                    # reference attaches no row attrs (executor.go:244-258).
                    frame = c.args.get("frame") or ""
                    f = idx.frame(frame) if frame else None
                    if f is not None:
                        row_id, row_ok = _uint_arg(c, f.row_label)
                        if row_ok and f.row_attr_store is not None:
                            bm.attrs = f.row_attr_store.attrs(row_id)
        return bm

    def _execute_count(
        self, index: str, c: Call, slices: list[int], opt: ExecOptions
    ) -> int:
        """reference: executor.go:611-639"""
        if len(c.children) == 0:
            raise ExecutorError("Count() requires an input bitmap")
        if len(c.children) > 1:
            raise ExecutorError("Count() only accepts a single bitmap input")
        child = c.children[0]

        def map_fn(local_slices: list[int]):
            return self._count_slices_total(index, child, local_slices)

        def reduce_fn(prev, v):
            return (prev or 0) + v

        n = self._map_reduce(index, slices, c, opt, map_fn, reduce_fn)
        return int(n or 0)

    # ------------------------------------------------------------------
    # BSI aggregates — Sum / Min / Max over integer fields
    # ------------------------------------------------------------------

    @staticmethod
    def _normalize_valcount(v):
        """Map a local (ValCount | None) or remote-decoded ([Pair] | 0)
        partial to ValCount | None.  A remote node with no valued
        columns answers an empty result that decodes to 0 — legitimate
        partials are ALWAYS Pair lists (even all-zero ones survive the
        protobuf round trip), so bare ints mean "no data"."""
        if isinstance(v, bsi.ValCount):
            return v
        if isinstance(v, list) and v:
            p = v[0]
            val = int(p.id) & 0xFFFFFFFFFFFFFFFF
            if val >= 1 << 63:  # sign-extend the u64 wire wrap
                val -= 1 << 64
            return bsi.ValCount(value=val, count=int(p.count))
        return None

    def _execute_bsi_agg(self, index: str, c: Call, slices: list[int], opt):
        """Sum/Min/Max(…, frame=f, field=q): per-slice int32 partial
        vectors from ONE fused program over the field's planes (plus an
        optional filter bitmap tree), weighted/combined in Python ints,
        reduced across nodes through the ordinary map/reduce — exactly
        like Count.  Cross-node partials ride the Pairs wire shape
        (value u64-wrapped, count), so negatives survive protobuf."""
        name = c.name

        def map_fn(local_slices: list[int]):
            return self._bsi_agg_slices(index, c, local_slices)

        def reduce_fn(prev, v):
            v = self._normalize_valcount(v)
            if v is None:
                return prev
            prev = self._normalize_valcount(prev)
            if prev is None:
                return v
            if name == "Sum":
                return bsi.ValCount(prev.value + v.value, prev.count + v.count)
            if v.value == prev.value:
                return bsi.ValCount(prev.value, prev.count + v.count)
            if name == "Min":
                return v if v.value < prev.value else prev
            return v if v.value > prev.value else prev

        res = self._map_reduce(index, slices, c, opt, map_fn, reduce_fn)
        res = self._normalize_valcount(res)
        if res is None and name == "Sum":
            res = bsi.ValCount(0, 0)
        return res

    def _bsi_agg_slices(self, index: str, c: Call, slices: list[int]):
        """One node's aggregate partial over its local slices:
        ValCount, or None when no slice holds a valued column.  Rides
        the device-health gate like the Count path: a quarantined (or
        finally-failed) launch decodes host-computed partial vectors —
        the same ripple arithmetic through the numpy backend.

        The per-slice partial vectors come one of two ways, chosen from
        what the fragments show (:meth:`_agg_in_place_prep`): IN PLACE,
        one program over the resident plane mirrors, when every leaf is
        a slot of a dense plane, a predicate or a pad; else through the
        leaf batch (:meth:`_bsi_agg_batch`).  Both end in the same
        vectors and the same decode."""
        if not slices:
            return None
        rc = self._rewrite_bsi_agg(index, c)
        bucket = int(rc.args["nplanes"])
        paths = self.device_health.device_paths()
        mode = self.device_health.acquire(paths)
        if mode == health_mod.MODE_DENY:
            parts = self.hosteval.agg_partials(index, rc, slices)
            return self._decode_agg_parts(c, bucket, parts.values())
        with self.tracer.span("bsi.agg", slices=len(slices)) as sp:
            with self.tracer.span("bsi.prep") as ps:
                prep = self._agg_in_place_prep(index, rc, slices, ps)
            if isinstance(prep, str):
                sp.annotate(way="batch", reason=prep)
                self.holder.stats.count("exec.bsi.batch")
                vecs = self._bsi_agg_batch(index, rc, slices, paths, mode)
            else:
                sp.annotate(
                    way="in_place", planes=prep["planes"], bytes=prep["bytes"]
                )
                self.holder.stats.count("exec.bsi.inPlace")
                vecs = self._bsi_agg_in_place(index, rc, prep, paths, mode, sp)
            if vecs is None:
                return None
            with self.tracer.span("bsi.decode", vectors=len(vecs)):
                return self._decode_agg_parts(c, bucket, vecs)

    def _bsi_agg_batch(self, index: str, rc: Call, slices, paths, mode):
        """The partial vectors through the assembled leaf batch (the
        batch cache, the coalescer's "agg" reduce): the way of a tree
        the in-place program does not take."""
        ent = self._cached_batch(index, rc, slices)
        if ent["batch"] is None:
            if mode == health_mod.MODE_PROBE:
                self.device_health.cancel_probe(paths)
            return None

        def direct():
            with device_mod.pool().pinned(
                ent.get("pool_key")
            ), self.tracer.span("exec.device", reduce="agg"):
                self._fault_check_launch("direct")
                return np.asarray(
                    _device_get(
                        plan.compiled_batched(ent["expr"], "agg")(ent["batch"])
                    )
                )

        def device_fn():
            if self.coalescer is not None:
                res = self._coalesce_eval(ent, "agg")
                if res is not None:
                    return np.asarray(res)
            return direct()

        kept = list(ent["pos_of"])
        res = self._launch_guarded(
            paths,
            mode,
            device_fn,
            retry_fn=direct,
            host_fn=lambda: self.hosteval.agg_partials(index, rc, kept),
        )
        if isinstance(res, dict):
            return list(res.values())
        res = np.asarray(res)
        return [res[p] for p in ent["pos_of"].values()]

    # Slot layouts kept for the in-place aggregate, one a (view, row
    # ids, slice set): host integers alone — a mirror is the residency
    # pool's to hold, and is asked of its fragment at every answer — so
    # the count cap is the whole bound.  A field's rows are the same for
    # every text; a date frame adds an entry a row that is asked for.
    _AGG_LAYOUT_CAP = 256

    def _agg_view_layout(self, view, row_ids: tuple, slices_key: tuple, frags):
        """Where ``row_ids`` lie in the planes of ``view``'s fragments
        over a slice set: ``{"slots": int32[n, k] (-1: not held),
        "rows": each fragment's plane rows (0: none), "words": the words
        of a row of that plane (0: none), "versions": the
        fragment versions the slots hold for, "sparse": a row lives in
        the sparse tier}``.  Kept across answers and validated as the
        batch cache validates (the write epoch, then the version
        vector): an answer over unchanged fragments does no per-row
        lookup at all."""
        n = len(frags)
        if view is None:  # the frame has no such view yet: no row is held
            return {
                "versions": [0] * n,
                "slots": np.full((n, len(row_ids)), -1, dtype=np.int32),
                "rows": np.zeros(n, dtype=np.int32),
                "words": np.zeros(n, dtype=np.int32),
                "held": np.zeros(n, dtype=bool),
                "sparse": False,
            }
        key = (view.index, view.frame, view.name, row_ids, slices_key)
        epoch = fragment_mod.write_epoch()
        with self._batch_mu:
            ent = self._agg_layouts.get(key)
            if ent is not None:
                self._agg_layouts.move_to_end(key)
        if ent is not None:
            if ent["epoch"] == epoch:
                return ent
            if ent["serials"] == tuple(
                None if f is None else (f._serial, f._version) for f in frags
            ):
                ent["epoch"] = epoch
                return ent
        slots = np.full((n, len(row_ids)), -1, dtype=np.int32)
        rows = np.zeros(n, dtype=np.int32)
        words = np.zeros(n, dtype=np.int32)
        versions = [0] * n
        serials: list = [None] * n
        sparse = False
        for i, f in enumerate(frags):
            if f is None:
                continue
            got = f.slots_of(row_ids)
            if got is None:
                sparse = True
                break
            slots[i], versions[i] = got
            serials[i] = (f._serial, versions[i])
            rows[i], words[i] = f.plane_rows(), f.plane_words()
        ent = {
            "epoch": epoch,
            "serials": tuple(serials),
            "versions": versions,
            "slots": slots,
            "rows": rows,
            "words": words,
            "held": slots.max(axis=1, initial=-1) >= 0,
            "sparse": sparse,
        }
        with self._batch_mu:
            self._agg_layouts[key] = ent
            while len(self._agg_layouts) > self._AGG_LAYOUT_CAP:
                self._agg_layouts.popitem(last=False)
        return ent

    @staticmethod
    def _agg_columns(leaves):
        """What a call's text alone decides of the in-place program:
        ``(cols, units)`` as ``bp.aggregate_planes`` takes them, or None
        for a tree it does not take (a time-quantum Range is a union
        over views).  A field's planes are one ``"whole"`` unit — the
        aggregate and the comparisons read most of their rows — and a
        Bitmap's row a ``"tile"`` unit of its own; a predicate is an
        operand and a depth-bucket pad a zero.  Which rows a fragment
        keeps, and where, is the slot table's to say: data."""
        cols: list[tuple] = []
        units: list[str] = []
        n_rows: list[int] = []  # row leaves a unit
        field_unit: dict[tuple, int] = {}
        n_pred = 0
        for leaf in leaves:
            if leaf.name == "BsiPred":
                cols.append(("pred", n_pred))
                n_pred += 1
            elif leaf.name == "BsiZero":
                cols.append(("zero",))
            elif leaf.name in ("BsiPlane", "Bitmap"):
                u = len(units)
                if leaf.name == "BsiPlane":
                    u = field_unit.setdefault((leaf.args["frame"], leaf.args["field"]), u)
                if u == len(units):
                    units.append("whole" if leaf.name == "BsiPlane" else "tile")
                    n_rows.append(0)
                cols.append(("row", u, n_rows[u]))
                n_rows[u] += 1
            else:
                return None
        # the slot table holds a unit's columns side by side
        first = [sum(n_rows[:u]) for u in range(len(units))]
        return (
            tuple(
                ("row", c[1], first[c[1]] + c[2]) if c[0] == "row" else c for c in cols
            ),
            tuple(units),
        )

    def _agg_batch_fits(self, batch_rows: int, plane_rows: int) -> bool:
        """Whether the leaf batches of such a text that the batch cache
        may keep (``_BATCH_CACHE_CAP``) fit a device's budget beside the
        planes they are copied from (the prefetcher uploads those
        whichever way the answer goes)."""
        budget = device_mod.pool().budget_bytes()
        rows = self._BATCH_CACHE_CAP * batch_rows + plane_rows
        return not budget or (
            perf_mod.plane_bytes(rows, bp.WORDS_PER_SLICE)
            <= budget * bp.mesh_device_count()
        )

    def _agg_in_place_prep(self, index: str, rc: Call, slices: list[int], sp):
        """What the in-place aggregate launches, from one sweep of the
        fragments (no plane is read): ``{"expr", "cols", "units",
        "preds", "groups", "kept", ...}`` — or, as a string, the reason
        the tree goes through the leaf batch: ``time_range``
        (:meth:`_agg_columns`), ``sparse_tier`` (a row no plane holds),
        ``cold_mirrors`` (most mirrors are not on the device and the
        leaf batch fits it, :meth:`_agg_batch_fits`: the host fills the
        rows it reads, as for a Count, and the prefetcher brings the
        planes for the next answer; where it does not fit — a fact
        table's first answers — the planes upload on the way).

        A ``group`` is the kept members of one device whose planes share
        a shape a unit: ``(members, planes, slots)`` as
        ``bp.aggregate_planes`` takes them."""
        expr, leaves = plan.decompose(rc)
        layout = self._agg_columns(leaves)
        if layout is None:
            return "time_range"
        cols, units = layout
        sweep = self._leaf_sweep(index, leaves, slices)
        n = len(slices)
        # what each unit reads: [view, fragments, row ids]
        reads: list[list] = [[None, None, []] for _ in units]
        for col, ent in zip(cols, sweep):
            if col[0] == "row":
                reads[col[1]][:2] = ent[1], ent[3]
                reads[col[1]][2].append(ent[2])
        slices_key = tuple(slices)
        layouts = [
            self._agg_view_layout(view, tuple(row_ids), slices_key, frags)
            for view, frags, row_ids in reads
        ]
        if any(lay["sparse"] for lay in layouts):
            return "sparse_tier"
        # a slice takes part where it holds the field's not-null row
        # (leaf 0: without it no column has a value there)
        at = np.flatnonzero(layouts[0]["slots"][:, 0] >= 0)
        kept = [slices[i] for i in at]
        # the mirrors, where they are resident and current; which are cold
        planes: list[list] = [[None] * n for _ in units]
        stale: list[tuple[int, int]] = []
        n_frag = n_cold = plane_rows = 0
        counted: set = set()
        for u, ((view, frags, _), lay) in enumerate(zip(reads, layouts)):
            held, versions, mine = lay["held"], lay["versions"], planes[u]
            first = id(view) not in counted  # two Bitmaps of one frame: one plane
            counted.add(id(view))
            for i in at:
                if not held[i]:
                    continue
                f = frags[i]
                mine[i] = f.fresh_mirror(versions[i])
                if mine[i] is None:
                    stale.append((u, i))
                if first:
                    n_frag += 1
                    n_cold += f._device is None
                    plane_rows += int(lay["rows"][i])
        sp.annotate(slices=len(kept), cold=n_cold)
        if n_cold * 2 > n_frag and self._agg_batch_fits(
            len(leaves) * plan.slice_bucket(n), plane_rows
        ):
            return "cold_mirrors"
        if not kept:
            return {"kept": kept, "groups": [], "planes": 0, "bytes": 0}
        # a mirror that is cold, or behind a write, is asked of its
        # fragment (an upload or a scatter on the way) with its slots
        tables = [lay["slots"] for lay in layouts]
        rows = [lay["rows"] for lay in layouts]
        words = [lay["words"] for lay in layouts]
        for u, i in stale:
            ref = reads[u][1][i].gather_slots(reads[u][2])
            if ref is None:
                return "sparse_tier"
            if tables[u] is layouts[u]["slots"]:
                tables[u], rows[u] = tables[u].copy(), rows[u].copy()
                words[u] = words[u].copy()
            planes[u][i], tables[u][i] = ref
            rows[u][i], words[u][i] = (0, 0) if ref[0] is None else ref[0].shape
        device_mod.pool().touch_many(
            [
                frags[i]._pool_key
                for u, (_, frags, _) in enumerate(reads)
                for i in at
                if planes[u][i] is not None
            ]
        )
        table = np.concatenate([t[at] for t in tables], axis=1)
        n_held = int((table >= 0).sum())
        preds = [
            bsi.pred_row(leaf.args["v"], leaf.args["d"])[: bp.PRED_WORDS]
            for leaf in leaves
            if leaf.name == "BsiPred"
        ]
        return {
            "expr": expr,
            "cols": cols,
            "units": units,
            "preds": (
                np.stack(preds)
                if preds
                else np.zeros((0, bp.PRED_WORDS), dtype=np.uint32)
            ),
            "groups": self._agg_member_groups(
                kept,
                [[mine[i] for i in at] for mine in planes],
                # a plane's shape as one number: rows and the words of a row
                [
                    r[at].astype(np.int64) << 16 | w[at]
                    for r, w in zip(rows, words)
                ],
                table,
            ),
            "kept": kept,
            "planes": n_held,
            "bytes": perf_mod.plane_bytes(n_held, bp.WORDS_PER_SLICE),
        }

    @staticmethod
    def _agg_member_groups(kept: list[int], planes, rows, table):
        """The launch groups of an in-place aggregate: the kept slices
        by home device and by the shape of their planes, a view at a
        time (the jit key holds both), each ``(slices, planes, slots)``
        as ``bp.aggregate_planes`` takes them — ``planes`` member-major,
        a view each.  ``planes[v][m]`` / ``rows[v][m]``: member ``m``'s
        mirror of view ``v`` and its rows (None / 0: it holds none of
        the view's rows); such a member rides with a neighbour's plane
        and reads no row of it."""
        shape_of = np.stack(
            [np.asarray(kept, dtype=np.int64) % bp.mesh_device_count(), *rows], axis=1
        )
        for v in range(1, shape_of.shape[1]):
            none = shape_of[:, v] == 0
            for d in np.unique(shape_of[none, 0]):
                on_d = shape_of[:, 0] == d
                some = shape_of[on_d & ~none, v]
                shape_of[on_d & none, v] = some[0] if some.size else -1
        _, which = np.unique(shape_of, axis=0, return_inverse=True)
        which = which.reshape(-1)
        groups = []
        for u in range(int(which.max()) + 1):
            members = np.flatnonzero(which == u)
            stand_in = [
                next((p[m] for m in members if p[m] is not None), None)
                for p in planes
            ]
            any_plane = next(p for p in stand_in if p is not None)
            stand_in = [any_plane if p is None else p for p in stand_in]
            groups.append(
                (
                    [kept[m] for m in members],
                    [
                        p[m] if p[m] is not None else stand_in[v]
                        for m in members
                        for v, p in enumerate(planes)
                    ],
                    table[members],
                )
            )
        return groups

    def _bsi_agg_in_place(self, index: str, rc: Call, prep: dict, paths, mode, sp):
        """The partial vectors from the resident plane mirrors in
        place: per group ``ceil(members / bp.agg_members(...))`` launches of
        one program (``bp.aggregate_planes``), all dispatched without
        waiting (``bsi.dispatch``; a shape's first call leaves a
        ``compile`` span under it), then ONE fetch of every launch's
        vectors through the dispatcher's lane (``bsi.fetch`` ›
        ``launch``).  Under the same health gate as the leaf batch:
        a launch that finally fails decodes ``hosteval``'s vectors."""
        kept = prep["kept"]
        if not kept:
            if mode == health_mod.MODE_PROBE:
                self.device_health.cancel_probe(paths)
            return None

        def device_fn():
            t0 = time.monotonic()
            with self.tracer.span("bsi.dispatch", groups=len(prep["groups"])) as ds:
                self._fault_check_launch("agg")
                outs = [
                    bp.aggregate_planes(
                        plan._eval_expr,
                        prep["expr"],
                        prep["cols"],
                        prep["units"],
                        planes,
                        slots,
                        prep["preds"],
                        first_call=plan.note_agg_first_call,
                    )
                    for _, planes, slots in prep["groups"]
                ]
                launches = sum(len(o) for o in outs)
                ds.annotate(launches=launches)
            sp.annotate(launches=launches)
            t_disp = time.monotonic()
            flat = [o for group in outs for o in group]
            with self.tracer.span("bsi.fetch", arrays=len(flat)) as fs:
                fetched = iter(self._shared_fetch(flat, fs))
            vecs = []
            for group, (members, _, _) in zip(outs, prep["groups"]):
                arr = np.concatenate([np.asarray(next(fetched)) for _ in group])
                vecs.extend(arr[: len(members)])
            if perf_mod.enabled():
                perf_mod.record_launch(
                    "agg",
                    reduce="agg",
                    rows=prep["planes"],
                    n_bytes=prep["bytes"],
                    dispatch_ms=(t_disp - t0) * 1e3,
                    total_ms=(time.monotonic() - t0) * 1e3,
                    trace_id=perf_mod.current_trace_id(),
                )
            return vecs

        res = self._launch_guarded(
            paths,
            mode,
            device_fn,
            retry_fn=device_fn,
            host_fn=lambda: self.hosteval.agg_partials(index, rc, kept),
        )
        return list(res.values()) if isinstance(res, dict) else res

    @staticmethod
    def _decode_agg_parts(c: Call, bucket: int, vecs):
        """Reduce per-slice aggregate partial vectors (device OR host
        produced — identical layout) into one ValCount."""
        if c.name == "Sum":
            # The decode is linear in the vector: the slices' popcounts
            # add up first (int64 holds 2^43 slices of them), and the
            # weights meet Python ints once.
            vecs = list(vecs)
            if not vecs:
                return None
            total, count = ripple.decode_sum(
                np.asarray(vecs, dtype=np.int64).sum(axis=0), bucket
            )
            return bsi.ValCount(total, count) if count else None
        best = None
        for vec in vecs:
            decoded = ripple.decode_minmax(vec, bucket)
            if decoded is None:
                continue
            val, n = decoded
            if best is None:
                best = (val, n)
            elif val == best[0]:
                best = (val, best[1] + n)
            elif (c.name == "Min") == (val < best[0]):
                best = (val, n)
        return bsi.ValCount(*best) if best is not None else None

    # ------------------------------------------------------------------
    # TopN (reference: executor.go:281-415) — two-phase
    # ------------------------------------------------------------------

    def _execute_topn(
        self, index: str, c: Call, slices: list[int], opt: ExecOptions
    ) -> list[Pair]:
        ids_arg = _uint_slice_arg(c, "ids")
        n = _uint_arg(c, "n")[0]

        # Folded single-round-trip path: when every slice is owned
        # locally (single node — the common and benchmarked shape), both
        # phases compute from ONE union scoring pass with ONE device
        # fetch; results are identical to the two-phase protocol below.
        # One slice too: an index whose rows are many and whose columns
        # are one slice's (a molecule a row) is prepared, cached and
        # scored as the many-slice ones are.
        if not ids_arg and not opt.remote and slices:
            if self._all_slices_local(index, slices):
                return self._execute_topn_folded(index, c, slices, opt)

        pairs = self._execute_topn_slices(index, c, slices, opt)
        # Phase 2 refetch only on the originating node (reference:
        # executor.go:301-321).
        if not pairs or ids_arg or opt.remote:
            return pairs
        # Phase 2 exists to get EXACT counts for winners that missed
        # some slice's local candidate list; with a single slice the
        # phase-1 scores are already exact and complete, so the refetch
        # would recompute identical counts at double the latency.
        if len(slices) <= 1:
            return pairs[:n] if n and n < len(pairs) else pairs
        return self._topn_refetch(index, c, slices, opt, n, pairs)

    def _execute_topn_two_phase(
        self, index: str, c: Call, slices: list[int], opt: ExecOptions, n: int
    ) -> list[Pair]:
        """The reference's two-round protocol, used when the folded
        path's union guard trips."""
        pairs = self._execute_topn_slices(index, c, slices, opt)
        if not pairs:
            return pairs
        return self._topn_refetch(index, c, slices, opt, n, pairs)

    def _topn_refetch(
        self,
        index: str,
        c: Call,
        slices: list[int],
        opt: ExecOptions,
        n: int,
        pairs: list[Pair],
    ) -> list[Pair]:
        """Phase 2: exact counts for the phase-1 winner union."""
        other = c.clone()
        other.args["ids"] = sorted({p.id for p in pairs})
        trimmed = self._execute_topn_slices(index, other, slices, opt)
        if n and n < len(trimmed):
            trimmed = trimmed[:n]
        return trimmed

    def _score_topn_parts(self, stack: topn_stack.ScoreStack) -> np.ndarray:
        """Score many fragments' TopN parts with as FEW device
        operations and host<->device transfers as possible; returns the
        flat ``int32`` score vector that ``stack.base`` indexes.

        ``stack``: the ``topn_stack.score_stack`` of the score entries
        (TopState, SubRef, src_words, src_slot, fragment) — the first
        three from the ``*_parts`` fragment APIs, ``src_slot`` from
        ``_attach_dev_src`` (None when the src is not a row of the
        member's own plane; only then must ``src_words`` be there),
        ``fragment`` for the host scoring fallback.  Entries with a
        SubRef are grouped there by program shape (sub shape, plane
        rows, home device) and by where their src is read from, each
        group's operands stacked once; each group is scored by
        ONE compiled program (bp.score_planes) that reads candidate AND
        src rows straight from the fragments' resident HBM mirrors — no
        stacked copy, no src upload — launched once per bp.SCORE_GROUP
        members without waiting, and every launch of every group is
        fetched in ONE round trip, where a per-fragment path would pay a
        dispatch + a 128 KiB src upload + a fetch PER SLICE.  The
        program's operands are bounded whatever the slice count, and
        nothing here walks the members in Python.

        Rides the device-health gate: a quarantined device (or a
        finally-failed scorer launch) fills the score vector from the
        fragments' authoritative host rows instead
        (hosteval.score_topn_parts) — identical arithmetic, identical
        vectors.

        The ``topn.dispatch`` / ``topn.fetch`` spans split the device
        cost: dispatch covers the async program launches (a program
        shape's first call leaves a ``compile`` span under it), fetch
        the blocking device->host transfer — with ``topn.select`` in
        the folded caller, the per-stage TopN(src) breakdown."""
        if not stack.groups:
            return topn_stack.NO_SCORES

        def host_fn():
            # The stack's states are shared by every query of a prep
            # entry: the host scorer fills clones, told the text's src
            # row where the states were made for another text.
            row = stack.src_row
            live = [
                (replace(e[0]) if row is None else replace(e[0], src_row=row), *e[1:])
                for e in stack.live
            ]
            self.hosteval.score_topn_parts(live)
            return topn_stack.host_scores(stack, [e[0].counts for e in live])

        paths = self.device_health.device_paths()
        mode = self.device_health.acquire(paths)
        if mode == health_mod.MODE_DENY:
            return host_fn()

        def device_fn():
            dev_outs = []  # a group's launches, fetched in one pass
            t0 = time.monotonic()
            with self.tracer.span(
                "topn.dispatch",
                groups=len(stack.groups),
                rows=stack.rows,
                bytes=stack.n_bytes,
            ) as sp:
                self._fault_check_launch("topn")
                for group in stack.groups:
                    dev_outs.append(
                        bp.score_planes(
                            group.planes,
                            group.slots,
                            src_slots=group.src_slots,
                            srcs=(
                                None
                                if group.srcs is None
                                else np.stack(group.srcs)
                            ),
                            first_call=plan.note_scorer_first_call,
                        )
                    )
                sp.annotate(launches=sum(len(o) for o in dev_outs))
            t_disp = time.monotonic()
            flat = [o for outs in dev_outs for o in outs]
            with self.tracer.span("topn.fetch", arrays=len(flat)) as sp:
                fetched = iter(self._shared_fetch(flat, sp))
            scores = topn_stack.flatten_scores(
                stack,
                [[np.asarray(next(fetched)) for _ in outs] for outs in dev_outs],
            )
            if perf_mod.enabled():
                perf_mod.record_launch(
                    "topn",
                    reduce="topn",
                    rows=stack.rows,
                    n_bytes=stack.n_bytes,
                    dispatch_ms=(t_disp - t0) * 1e3,
                    total_ms=(time.monotonic() - t0) * 1e3,
                    trace_id=perf_mod.current_trace_id(),
                )
            return scores

        return self._launch_guarded(
            paths, mode, device_fn, retry_fn=device_fn, host_fn=host_fn
        )

    def _count_host_scored(self, states) -> None:
        """Count the candidate rows a build scored on the host: the
        sparse tier's, probed a row at a time under the fragment's lock
        (``Fragment._top_score_parts``)."""
        n = sum(
            len(st.sparse_pos) for st in states if st.sparse_pos is not None
        )
        if n:
            self.holder.stats.count("topn.host_scored_rows", n)

    def _shared_fetch(self, arrays, sp):
        """Fetch device arrays to the host, batching the BLOCKING
        device->host round trip with other queries' concurrent fetches
        through the coalescer's fetch lane (submit_fetch) — the TopN
        fetch residual is one round trip per drain instead of one per
        query.  Dispatches already happened (async); only the wait
        folds.  Falls back to a direct ``jax.device_get`` without a
        coalescer or when it is closed."""
        co = self.coalescer
        if co is not None and hasattr(co, "submit_fetch"):
            try:
                fut = co.submit_fetch(arrays)
            except coalesce_mod.CoalesceClosed:
                fut = None
            if fut is not None:
                timeout = coalesce_mod.RESULT_TIMEOUT_S
                dl = resilience.current_deadline()
                if dl is not None:
                    timeout = dl.clamp(timeout)
                try:
                    res, info = coalesce_mod.await_result(fut, timeout)
                except FuturesTimeoutError:
                    sp.annotate(deadline="expired")
                    # Same abandoned-waiter contract as _coalesce_eval:
                    # the eventual fetch error must be consumed, not
                    # left for GC log spam.
                    fut.add_done_callback(
                        coalesce_mod.consume_abandoned(self.holder.stats)
                    )
                    if dl is not None and dl.expired:
                        raise resilience.DeadlineExceeded(
                            "deadline expired waiting for shared fetch"
                        ) from None
                    raise
                sp.annotate(**info)
                return res
        return _device_get(arrays)

    def _topn_src_leaf(self, index: str, c: Call):
        """``(frame, view, row id)`` of a TopN src that is ONE plain
        Bitmap leaf, None for any other src tree or none at all: the
        slice-independent half of finding the src row in a member's own
        plane, resolved once a call."""
        if len(c.children) != 1:
            return None
        leaf = c.children[0]
        if leaf.name != "Bitmap" or leaf.children:
            return None
        view, row_id = self._resolve_bitmap_view(index, leaf)
        if view is None:
            return None
        return view.frame, view.name, row_id

    def _attach_dev_src(self, index: str, c: Call, frag, part, leaf=None):
        """Extend a fragment's (st, SubRef, src_words) TopN part with
        the src row's SLOT in the member's own plane snapshot when the
        TopN src is a plain Bitmap leaf on the SAME fragment (the
        common ``TopN(Bitmap(frame=f), frame=f)`` shape): the fused
        scorer then reads the src row from the already-grouped plane —
        zero src bytes host->device and no extra leaf shapes in the jit
        key.  Anything else (different src frame, sparse-tier src row,
        a mirror refresh since the prepare snapshot, non-Bitmap tree)
        returns None, falling the group back to the one host-snapshot
        src transfer — always consistent, just not transfer-free.
        ``leaf``: the call's ``_topn_src_leaf``, from a caller that
        walks many fragments and resolved it once."""
        st, sub_ref, srcw = part
        slot = None
        if sub_ref is not None:
            leaf = leaf or self._topn_src_leaf(index, c)
            if leaf is not None and leaf[:2] == (frag.frame, frag.view):
                with frag._mu:
                    s = frag._slot_of.get(leaf[2])
                    # The slot is only valid against the snapshot the
                    # prepare captured; a refresh since then (writes)
                    # may have reordered the slot layout.
                    if s is not None and frag._mirror_locked() is sub_ref.plane:
                        slot = int(s)
        return st, sub_ref, srcw, slot

    def _existing_topn_slices(
        self, index: str, c: Call, slices: list[int]
    ) -> list[int]:
        """Subset of ``slices`` whose fragment of the TopN frame/view
        actually exists.  A missing fragment contributes nothing
        (``_topn_options_for_slice`` returns None for it), so skipping
        turns the per-slice host walk from O(max_slice) into
        O(existing fragments) — at bench scale (954 index slices, one
        frame fragment) that walk dominated warm TopN host time."""
        v = self._topn_view(index, c)
        if v is None:
            return []
        have = v.fragment_slices()
        return [s for s in slices if s in have]

    def _topn_view(self, index: str, c: Call):
        """The view a TopN call ranks, or None: resolved once a call,
        never once a slice."""
        frame, view = self._topn_frame_view(c)
        idx = self.holder.index(index)
        f = idx.frame(frame) if idx is not None else None
        return f.view(view) if f is not None else None

    def _all_slices_local(self, index: str, slices: list[int]) -> bool:
        rn = getattr(self.cluster, "route_nodes", None)
        nodes = rn() if rn is not None else list(self.cluster.nodes)
        try:
            m = self._slices_by_node(nodes, index, slices)
        except SliceUnavailableError:
            return False
        return set(m.keys()) == {self.host}

    # Folded-TopN prep entries kept per (index, query, slice set): the
    # working set of a hot dashboard is a handful of repeated queries.
    _TOPN_CACHE_CAP = 8

    @staticmethod
    def _frag_versions(frags) -> list:
        return [
            None if frag is None else (frag._serial, frag._version)
            for frag in frags
        ]

    def _topn_versions(self, index: str, c: Call, slices: list[int]):
        """Validity vector for a folded-TopN prep entry: the TopN
        frame's fragment versions over the ORIGINAL slice list (a
        fragment springing into existence must invalidate) plus, when a
        src tree exists, the versions of every fragment its leaves
        resolve to (the src rows were host-evaluated at prep time)."""
        view = self._topn_view(index, c)
        frags = view.fragments_at(slices) if view is not None else [None] * len(slices)
        out = self._frag_versions(frags)
        if len(c.children) == 1:
            try:
                _, leaves = plan.decompose(
                    self._rewrite_bsi(index, c.children[0])
                )
            except (plan.PlanError, ExecutorError):
                leaves = []
            out.append(tuple(self._leaf_versions(index, leaves, slices)))
        return tuple(out)

    def _topn_folded_entry(
        self, index: str, c: Call, slices: list[int]
    ) -> tuple[dict, str]:
        """The folded path's prep — candidate walks, union assembly,
        foreign-count resolution, src evaluation, and gather prep —
        CACHED per (index, query, slice set) and validated exactly like
        _cached_batch entries (O(1) against the global write epoch, then
        against the version vector).  At 64 slices the prep is ~50 ms of
        host-side numpy per query; repeated queries skip all of it and
        pay only dispatch + fetch + winner selection.  Returns the
        entry and how it was come by, ``"hit"`` or ``"built"``.

        Attr-filtered queries (filterField) are NOT cached: the attr
        store has no version vector, so a SetRowAttrs would serve stale
        candidates.

        A miss whose call may take the direct way is served from its
        view's kept stack where one stands (``_topn_kept_text``), and an
        entry that holds a kept stack (``"kept_stack"``: served from it,
        or built beside it) is validated through it and lives no longer
        than it does."""
        key = (index, str(c), tuple(slices))
        cacheable = not self._topn_parsed_args(c)[3]  # "" = no filterField
        kept_key = self._topn_kept_key(index, c, slices)
        cur_versions = None
        if cacheable:
            now = time.monotonic()
            with self._batch_mu:
                # Purge entries past their lifetime: they can never be
                # served again (the expiry below), and each pins an HBM
                # plane snapshot via its SubRefs — dead entries must not
                # hold device memory until LRU displacement.
                expired = [
                    k
                    for k, e in self._topn_cache.items()
                    if now - e["built_at"] >= cache_mod.RECALCULATE_INTERVAL_S
                ]
                for k in expired:
                    del self._topn_cache[k]
                ent = self._topn_cache.get(key)
            for k in expired:
                device_mod.pool().remove(self._topn_pool_key(k))
            # Entries also EXPIRE on the rank caches' re-sort throttle:
            # candidate counts come from the ranked caches, whose
            # throttled re-sort (RECALCULATE_INTERVAL_S) happens inside
            # the candidate walk this cache skips — without the expiry a
            # hot read-only query would freeze its candidate counts
            # forever instead of the old path's <= 10 s of staleness.
            if ent is not None and (
                time.monotonic() - ent["built_at"]
                < cache_mod.RECALCULATE_INTERVAL_S
            ):
                kept = ent.get("kept_stack")
                if kept is not None:
                    # its src is a row of the very fragments the kept
                    # stack stands for
                    stands = self._topn_kept_stands(
                        kept_key, kept, index, c, slices
                    )
                else:
                    epoch = fragment_mod.write_epoch()
                    if ent["epoch"] != epoch:
                        cur_versions = self._topn_versions(index, c, slices)
                    stands = (
                        ent["epoch"] == epoch or ent["versions"] == cur_versions
                    )
                    if stands:
                        ent["epoch"] = epoch
                if stands:
                    with self._batch_mu:
                        if key in self._topn_cache:
                            self._topn_cache.move_to_end(key)
                    device_mod.pool().touch(self._topn_pool_key(key))
                    return ent, "hit"
                # Version validation failed: the entry can never serve
                # again (a deleted or rewritten fragment), yet its
                # SubRefs pin HBM plane snapshots — drop it NOW, before
                # the rebuild, so a failing build can't resurrect it.
                with self._batch_mu:
                    if self._topn_cache.get(key) is ent:
                        del self._topn_cache[key]
                device_mod.pool().remove(self._topn_pool_key(key))
        # Capture validity BEFORE building: a concurrent write during
        # the build leaves the entry conservatively stale.  The vector
        # computed for the failed validation (if any) is reused — it
        # predates the build, which is exactly the conservative bar.
        epoch = fragment_mod.write_epoch()
        versions = None
        ent = (
            self._topn_kept_text(kept_key, index, c, slices)
            if kept_key is not None
            else None
        )
        if ent is None:
            if cacheable:
                versions = (
                    cur_versions
                    if cur_versions is not None
                    else self._topn_versions(index, c, slices)
                )
            ent = self._topn_folded_build(index, c, slices)
        ent["epoch"] = epoch
        ent["versions"] = versions
        kept = ent.get("kept_stack")
        # An entry of a kept stack is as old as the layouts it reads.
        ent["built_at"] = time.monotonic() if kept is None else kept["built_at"]
        if cacheable:
            # Byte-account the HBM plane snapshots that this entry ALONE
            # keeps alive.  A SubRef's plane is the fragment's mirror as
            # it stood at prepare time; while it still IS the mirror the
            # pool already holds it under the fragment's own key, and
            # charging it again would make 8.0 GB of resident planes
            # read as 16 and evict the very mirrors the scorer reads.
            # Only a snapshot that a later write has replaced is this
            # entry's to account for (it dies with the entry, within
            # RECALCULATE_INTERVAL_S).  An entry of a kept stack holds
            # the kept stack's snapshots, which it accounts for.
            self._topn_admit(
                self._topn_cache, self._TOPN_CACHE_CAP, key, ent,
                self._topn_pool_key,
                self._replaced_planes(ent.get("parts", ())) if kept is None else [],
                {"cache": "topn", "index": index, "query": str(c)},
            )
        return ent, "built"

    @staticmethod
    def _replaced_planes(parts) -> list:
        """The plane snapshots of ``parts`` that are no longer their
        fragment's mirror: what an entry alone keeps alive."""
        return [
            p[4].plane
            for p in parts
            if p[4] is not None and not p[0].mirror_is(p[4].plane)
        ]

    # Kept stacks, one a (view, slice set, options): the handful of
    # ranked views a deployment serves TopN over.
    _TOPN_KEPT_CAP = 8

    def _topn_kept_key(self, index: str, c: Call, slices: list[int]):
        """The key of the kept stack a call's build may take the direct
        way with (``_topn_kept_text``): ``(index, frame, view, slices,
        min threshold, has src)`` where the filters keep every counted
        row and the src, if any, is one plain Bitmap leaf; None for any
        other call (``ids=``, a count window, an attr filter, another
        src tree), which builds the general way."""
        frame, view, _n, _fld, row_ids, _min, _filters, tanimoto = (
            self._topn_parsed_args(c)
        )
        if tanimoto or row_ids:
            return None
        topt = self._topn_options(c)
        if not topt.keeps_every_counted:
            return None
        has_src = len(c.children) == 1
        if has_src and (c.children[0].name != "Bitmap" or c.children[0].children):
            return None
        return (index, frame, view, tuple(slices), topt.min_threshold, has_src)

    def _topn_kept_stands(self, kept_key, kept: dict, index, c, slices) -> bool:
        """Whether ``kept`` is still the kept stack of ``kept_key`` and
        may serve: younger than the rank caches' re-sort throttle
        (RECALCULATE_INTERVAL_S, whose re-sort happens in the layout
        calls a kept stack skips), and no write since it was made —
        the write epoch in O(1), then the fragments' version vector.
        One that fails is dropped at once."""
        if time.monotonic() - kept["built_at"] < cache_mod.RECALCULATE_INTERVAL_S:
            with self._batch_mu:
                current = self._topn_kept.get(kept_key) is kept
                if current:
                    self._topn_kept.move_to_end(kept_key)
            if current:
                epoch = fragment_mod.write_epoch()
                if kept["epoch"] == epoch:
                    return True
                view = self._topn_view(index, c)
                if view is not None and kept["versions"] == self._frag_versions(
                    view.fragments_at(slices)
                ):
                    kept["epoch"] = epoch
                    return True
        with self._batch_mu:
            dropped = self._topn_kept.get(kept_key) is kept
            if dropped:
                del self._topn_kept[kept_key]
        if dropped:
            device_mod.pool().remove(self._topn_kept_pool_key(kept_key))
        return False

    def _topn_kept_text(self, kept_key, index: str, c: Call, slices: list[int]):
        """A direct build served from the kept stack of ``kept_key``
        where one stands: of all a build makes only what depends on the
        text, the src row's slot in every part's plane, looked up in the
        stack's ``SrcTable``; the layouts, the union, the parts, the
        scorer's planes and slots and the ``TopStack`` are the kept
        stack's.  No fragment is asked anything and no fragment lock
        taken; one hold of the pool's lock keeps the mirrors recent.
        None where no kept stack stands or the src row is not in every
        part's dense tier: the caller builds in full."""
        with self._batch_mu:
            kept = self._topn_kept.get(kept_key)
        if kept is None or not self._topn_kept_stands(
            kept_key, kept, index, c, slices
        ):
            return None
        score = kept["score"]
        if kept["src"] is not None:
            leaf = self._topn_src_leaf(index, c)
            if leaf is None or leaf[:2] != kept_key[1:3]:
                return None
            slots = topn_stack.src_slots(kept["src"], leaf[2])
            if slots is None:
                return None
            score = topn_stack.for_src_row(score, slots, leaf[2])
        device_mod.pool().touch_many(kept["pins"])
        ent = {
            "parts": kept["parts"],
            "union": len(kept["union"]),
            "build": "direct",
            "stack_way": "kept",
            "score": score,
            "stack": kept["stack"],
            "pins": kept["pins"],
            "kept_stack": kept,
        }
        if kept["src"] is not None:
            ent["src"] = "plane"
        return ent

    def _topn_keep(self, kept_key, index, epoch, versions, per, parts, union,
                   score, stack, pins) -> dict:
        """Keep what a direct build made that no text changes (see
        ``_topn_kept_text``), as the kept stack of ``kept_key``:
        ``epoch`` and ``versions`` as they stood before the build read
        any layout.  Its snapshots that a write has replaced already are
        its own to account for, as a prep entry's are."""
        kept = {
            "layouts": tuple(p[1] for p in per),
            "parts": parts,
            "union": union,
            "score": score,
            "stack": stack,
            "pins": pins,
            "src": (
                topn_stack.src_table([p[0].dense_rows() for p in parts])
                if kept_key[5]
                else None
            ),
            "epoch": epoch,
            "versions": versions,
            "built_at": time.monotonic(),
        }
        self._topn_admit(
            self._topn_kept, self._TOPN_KEPT_CAP, kept_key, kept,
            self._topn_kept_pool_key, self._replaced_planes(parts),
            {"cache": "topn_kept", "index": index, "frame": kept_key[1]},
        )
        return kept

    def _topn_folded_build(self, index: str, c: Call, slices: list[int]) -> dict:
        """Build a folded-TopN prep entry (see _topn_folded_entry for
        the caching contract).  Entry shapes: ``{"empty": True}``,
        ``{"two_phase": True}``, or ``{"parts": [(frag, cand_ids,
        own_mask, st, sub_ref, src_words, src_slot), ...], "union": n,
        "build": how, "score": ..., "stack": ..., "pins": ...}`` where
        ``st`` is the part's UNSCORED TopState and ``own_mask`` says
        which of the ids it lists are the fragment's own candidates
        (phase 1 ranks those only; None: all of them).  ``score`` and
        ``stack`` hold the same parts as arrays (``topn_stack``): what
        runs per answer reads those and walks no parts, and nothing of
        an entry is written after its build but the score memo.
        ``pins``: the pool keys of every part's mirror.

        Per text the build does only what depends on the text's src.
        What a fragment's candidates are, which tier holds each and
        where its rows sit in the plane is the fragment's to know
        (``Fragment.top_layout``, kept until a write or a re-sort), and
        where the call's filters keep every counted row and the union
        is a fragment's own candidate list, its part comes from that
        layout (``top_prepare_own_parts``): no set algebra, and no host
        copy of a src row that the scorer reads from the plane.  Any
        other fragment is walked (``top_prepare_union_parts``, with the
        src's host words).  ``build`` says which: ``"direct"`` when no
        fragment was walked.

        A direct build keeps all of that for the next text of its view
        and options (``_topn_keep``; the entry says ``"stack_way":
        "made"`` and holds it as ``"kept_stack"``): what a build does
        for a text served from it is ``_topn_kept_text``'s."""
        has_src = len(c.children) == 1

        # Only slices whose fragment exists can contribute; one sweep of
        # the view finds them, and the call's arguments are parsed once.
        # A call that may go the direct way keeps what it makes for the
        # next text (_topn_keep), valid for the write epoch and versions
        # that stand before any layout is read.
        kept_key = self._topn_kept_key(index, c, slices)
        epoch = fragment_mod.write_epoch()
        view = self._topn_view(index, c)
        at = view.fragments_at(slices) if view is not None else []
        frags = [f for f in at if f is not None]
        if not frags:
            return {"empty": True}
        versions = self._frag_versions(at) if kept_key is not None else None
        topt = self._topn_options(c)
        plain = topt.keeps_every_counted
        rows = self._topn_rows_entry(index, c, view, frags, topt)
        if rows is not None:
            return rows

        # Pass 1 (host-only): per-fragment candidate (ids, cached counts)
        # arrays, WITHOUT evaluating the src tree yet — the union guard
        # below must be able to fall back before any src work is spent.
        # A src only shrinks candidate lists (tanimoto count-window), so
        # the src-free walk is a conservative union estimate.
        per: list[tuple] = []
        for frag in frags:
            if plain:
                lay = frag.top_layout()
                per.append((frag, lay, topt, lay.ids, lay.cnts))
            else:
                per.append((frag, None, topt) + frag.top_candidates_arrays(topt))
        # Guard against disjoint caches: every slice scores the WHOLE
        # union, so when the union dwarfs the largest per-slice candidate
        # list the folded pass does more device gather+score work than
        # the two saved round trips are worth — use the two-phase
        # protocol instead.  Overlapping hot rows (the common shape)
        # keep union ~= per-slice candidates and stay folded.
        union = np.unique(np.concatenate([p[3] for p in per]))
        if not len(union):
            return {"empty": True}
        max_cand = max(len(p[3]) for p in per)
        if len(union) > max(2 * max_cand, 512):
            return {"two_phase": True}

        def with_src(frag, src_rows) -> TopOptions:
            src = RowBitmap()
            row = src_rows.get(frag.slice)
            if row is not None:
                src.set_segment(frag.slice, row)
            return replace(topt, src=src)

        src_rows = None
        if has_src and topt.tanimoto_threshold > 0:
            # Tanimoto count-windows depend on the src count, so
            # re-derive candidates (and the union) with the real src.
            src_rows = self._topn_src_host(
                index, c.children[0], [f.slice for f in frags]
            )
            per = []
            for frag in frags:
                opt = with_src(frag, src_rows)
                per.append((frag, None, opt) + frag.top_candidates_arrays(opt))
            union = np.unique(np.concatenate([p[3] for p in per]))
            if not len(union):
                return {"empty": True}

        # Gather prep: the union scoring pass per fragment, WITHOUT the
        # kernel dispatch (all fragments score the same union, so the
        # gathered submatrices share a shape).  The short way first: own
        # ⊆ union by construction, so a fragment whose candidates are as
        # many as the union ranks exactly the union and nothing is
        # foreign.  With a src it also needs the src to be a row of its
        # own plane, which is where the scorer then reads it.
        leaf = self._topn_src_leaf(index, c) if has_src else None
        own_src = (
            leaf[2]
            if leaf is not None and leaf[:2] == (view.frame, view.name)
            else None
        )
        short_way = not has_src or own_src is not None
        parts: list = [None] * len(per)
        walk: list[int] = []
        for i, (frag, lay, _opt, cand_ids, _cnts) in enumerate(per):
            part = None
            if short_way and lay is not None and len(cand_ids) == len(union):
                part = frag.top_prepare_own_parts(
                    lay, topt.min_threshold, own_src
                )
            if part is not None:
                st, sub_ref, _, src_slot = self._attach_dev_src(
                    index, c, frag, part, leaf
                )
                if sub_ref is None or src_slot is not None:
                    parts[i] = (frag, cand_ids, None, st, sub_ref, None, src_slot)
                    continue
            walk.append(i)

        # The general way for the rest: the src's host words (read for
        # these fragments only, when the others are already settled),
        # counts for the foreign winners, the tier split by set algebra.
        if walk and has_src and src_rows is None:
            # Without tanimoto, candidate filtering never reads the
            # src — only the scorer does.  Attach it to the pass-1
            # options instead of re-walking every candidate list.
            src_rows = self._topn_src_host(
                index, c.children[0], [per[i][0].slice for i in walk]
            )
        for i in walk:
            frag, _lay, opt, cand_ids, cand_cnts = per[i]
            if has_src and opt.src is None:
                opt = with_src(frag, src_rows)
            st, sub_ref, srcw, src_slot = self._attach_dev_src(
                index,
                c,
                frag,
                frag.top_prepare_union_parts(union, cand_ids, cand_cnts, opt),
                leaf,
            )
            # cand_ids is a subset of what the state lists (the union's
            # foreign ids came on top), so equal lengths are equal sets.
            listed = st.done_ids if st.cand_ids is None else st.cand_ids
            own_mask = (
                None
                if len(listed) == len(cand_ids)
                else np.isin(listed, cand_ids, assume_unique=True)
            )
            parts[i] = (frag, cand_ids, own_mask, st, sub_ref, srcw, src_slot)
        score = topn_stack.score_stack(
            [(st, ref, srcw, slot, frag) for frag, _, _, st, ref, srcw, slot in parts]
        )
        self._count_host_scored(p[3] for p in parts)
        pins = tuple(p[0]._pool_key for p in parts)
        # The mirrors the parts read stay recent in the residency pool:
        # one hold of its lock a build, not one a fragment.
        device_mod.pool().touch_many(pins)
        stack = topn_stack.stack_parts(parts, union, score)
        kept = None
        if kept_key is not None and not walk:
            kept = self._topn_keep(
                kept_key, index, epoch, versions, per, parts, union, score,
                stack, pins,
            )
        # "scores" memoizes the fetched score vector for as long as
        # the ENTRY validates (fragments unchanged since build =>
        # scores unchanged); "score_event" single-flights the fused
        # scorer across concurrent queries of this entry (leader
        # scores, everyone else waits on the event — never on a lock),
        # so a 32-query storm of one TopN shape pays ONE
        # dispatch+fetch, not 32 — the topn.fetch residual ROADMAP 5
        # names.
        ent = {
            "parts": parts,
            "union": len(union),
            "build": "walked" if walk else "direct",
            "score": score,
            "stack": stack,
            "pins": pins,
        }
        if has_src:
            # where the scorer reads the src: a row of the ranked plane,
            # or host words copied for a part that has no slot for it
            host = any(p[4] is not None and p[6] is None for p in parts)
            ent["src"] = "host" if host else "plane"
        if kept is not None:
            ent.update(stack_way="made", kept_stack=kept)
        return ent

    def _topn_src_host(self, index: str, src: Call, slices: list[int]):
        """The src tree's host words over ``slices``
        (``_eval_tree_slices_host``), under a ``topn.src`` span: the
        leaves, the slices evaluated, those the src reaches (not empty)
        and the bytes of words made, which ``topn.srcHostBytes`` counts.
        A slice where the tree is empty may come back as None."""
        with self.tracer.span("topn.src") as sp:
            rows = self._eval_tree_slices_host(index, src, slices)
            words = [w for w in rows.values() if w is not None]
            n_bytes = sum(int(w.nbytes) for w in words)
            sp.annotate(
                leaves=len(plan.decompose(self._rewrite_bsi(index, src))[1]),
                slices=len(rows),
                reached=sum(1 for w in words if w.any()),
                bytes=n_bytes,
            )
        self.holder.stats.count("topn.srcHostBytes", n_bytes)
        return rows

    def _topn_rows_entry(self, index: str, c: Call, view, frags, topt) -> dict | None:
        """The prep entry of a TopN(src) over a view that is ONE
        fragment here, whose src is a dense-tier row of that very plane:
        ``{"rows": (fragment, RowsLayout, mirror, src slot, src count,
        tanimoto, min threshold), ...}``.  Every ranked row is a row of
        the one plane, so the scorer walks the plane (``bp.score_rows``)
        and applies the count window and the similarity rule where the
        counts are; with one fragment both protocol phases read the same
        scores, and nothing here depends on how many rows there are: no
        candidate list is copied, no union is sorted, no row is gathered
        by slot.  None where this does not apply and the general build
        does: more fragments, no src or another tree than a Bitmap of
        the frame itself, ``ids=``, an attr filter, a ranked row in the
        sparse tier (it is scored on the host)."""
        if len(frags) != 1 or topt.row_ids or topt.filter_field:
            return None
        leaf = self._topn_src_leaf(index, c) if len(c.children) == 1 else None
        if leaf is None or leaf[:2] != (view.frame, view.name):
            return None
        frag = frags[0]
        lay = frag.rows_layout()
        got = frag.rows_mirror(lay, leaf[2]) if lay is not None else None
        if got is None:
            return None
        mirror, src_slot = got
        # |src| is the row's own cardinality: what the general build's
        # host copy of the row would count
        s, t = frag.row_meta(leaf[2])[0], topt.tanimoto_threshold
        if t > 0:
            # cnt > s*t/100 and cnt < s*100/t, as whole numbers
            lo, hi = s * t // 100 + 1, -(-100 * s // t)
        else:
            lo, hi = max(topt.min_threshold, 1), np.iinfo(np.int64).max
        kept = max(
            int(
                np.searchsorted(lay.window, hi, "left")
                - np.searchsorted(lay.window, lo, "left")
            ),
            0,
        )
        return {
            "rows": (frag, lay, mirror, src_slot, s, t, topt.min_threshold),
            "parts": (),
            "union": kept,
            "build": "rows",
            "src": "plane",
            "pins": (frag._pool_key, frag._rows_pool_key),
        }

    def _score_topn_rows(self, ent: dict):
        """Score a rows entry (``_topn_rows_entry``): ONE launch of the
        walked scorer over the fragment's mirror, dispatched without
        waiting, and one fetch through the coalescer's lane of what it
        kept: ``(slots, shared bits)`` of the kept rows.  The
        ``topn.dispatch`` / ``topn.fetch`` spans and the device-health
        gate as ``_score_topn_parts``; the host fallback walks the
        authoritative plane by the same rules."""
        frag, lay, mirror, src_slot, s, t, m = ent["rows"]
        n_rows, words = (int(d) for d in mirror.shape)
        n_bytes = perf_mod.plane_bytes(n_rows, words)

        def host_fn():
            self.holder.stats.count("topn.host_scored_rows", n_rows)
            return self.hosteval.score_topn_rows(frag, lay, src_slot, s, t, m)

        paths = self.device_health.device_paths()
        mode = self.device_health.acquire(paths)
        if mode == health_mod.MODE_DENY:
            return host_fn()

        def device_fn():
            t0 = time.monotonic()
            with self.tracer.span(
                "topn.dispatch", groups=1, rows=n_rows, bytes=n_bytes
            ) as sp:
                self._fault_check_launch("topn")
                hits, at, shared, every = bp.score_rows(
                    mirror, lay.cnts, src_slot, s, t, m,
                    first_call=plan.note_scorer_first_call,
                )
                sp.annotate(launches=1)
            t_disp = time.monotonic()
            with self.tracer.span("topn.fetch", arrays=3) as sp:
                hits, at, shared = self._shared_fetch([hits, at, shared], sp)
                hits = int(hits)
                if hits > len(at):
                    # more rows kept than a launch hands back compacted
                    handback = "vector"
                    every = np.asarray(self._shared_fetch([every], sp)[0])
                    at = np.flatnonzero(every)
                    shared = every[at]
                else:
                    # the steps the device took to locate them
                    handback = "one" if hits <= bp.row_step(len(at)) else "more"
                    at, shared = np.asarray(at)[:hits], np.asarray(shared)[:hits]
                sp.annotate(hits=hits, handback=handback)
            if perf_mod.enabled():
                perf_mod.record_launch(
                    "topn",
                    reduce="topn",
                    rows=n_rows,
                    n_bytes=n_bytes,
                    dispatch_ms=(t_disp - t0) * 1e3,
                    total_ms=(time.monotonic() - t0) * 1e3,
                    trace_id=perf_mod.current_trace_id(),
                )
            return at, shared

        return self._launch_guarded(
            paths, mode, device_fn, retry_fn=device_fn, host_fn=host_fn
        )

    def _execute_topn_folded(
        self, index: str, c: Call, slices: list[int], opt: ExecOptions
    ) -> list[Pair]:
        """Both TopN phases from one scoring pass (reference protocol:
        executor.go:281-321 — two map/reduce rounds; here the cross-slice
        candidate union is known after a host-only cache walk, so every
        slice scores the WHOLE union once and the phase-1 winner
        selection plus the phase-2 exact counts both read those scores.
        One device round trip instead of two.)  The prep (candidates,
        union, gather layout) comes from the validated per-query cache
        (_topn_folded_entry); per query only the dispatch, the ONE
        fetch, and the winner selection run."""
        n = _uint_arg(c, "n")[0]
        if len(c.children) > 1:
            raise ExecutorError("TopN() can only have one input bitmap")
        # Canonicalize through the parsed tree BEFORE keying the prep
        # cache: the single-flighted score sharing keyed on the exact
        # query string, so semantically identical TopN(src) queries
        # whose src trees merely commute (Intersect(A,B) vs
        # Intersect(B,A)) each paid their own dispatch+fetch.  AND/OR/
        # XOR commute bit for bit, so results stay byte-identical.
        c = plan.canonicalize_call(c)
        with self.tracer.span("topn.prep", slices=len(slices)) as sp:
            ent, how = self._topn_folded_entry(index, c, slices)
            # ``union``: rows scored in every slice; ``build``: whether
            # a build walked any fragment the general way (the entry's
            # "build"; a hit built nothing)
            sp.annotate(
                prep_cache="two_phase" if ent.get("two_phase") else how,
                union=ent.get("union", 0),
            )
            if how == "built" and "build" in ent:
                sp.annotate(build=ent["build"])
                if "src" in ent:
                    # ``src``: the scorer reads the src as a row of the
                    # ranked plane (``plane``) or as host words copied
                    # for the parts (``host``)
                    sp.annotate(src=ent["src"])
                if "stack_way" in ent:
                    # ``stack``: a direct build served from its view's
                    # kept stack (``kept``), or one that made it
                    sp.annotate(stack=ent["stack_way"])
            if "rows" in ent:
                # ``candidates``: the rows the text's count window keeps
                # (upstream's filter on cached counts: a number of the
                # semantics, whatever scores them); ``rows``: the rows
                # of the plane the scorer walks
                sp.annotate(
                    candidates=ent["union"], rows=int(ent["rows"][2].shape[0])
                )
        if ent.get("empty"):
            return []
        if ent.get("two_phase"):
            return self._execute_topn_two_phase(index, c, slices, opt, n)

        # Score ONCE per validated entry: concurrent queries of the
        # same TopN shape single-flight (one leader dispatches +
        # fetches; everyone else waits on an Event — never on a lock —
        # and reuses the fetched score vector).  Scores stay valid
        # exactly as long as the entry does: entry validation already
        # proved the scored fragments unchanged since build.  The
        # vector is the only state an answer adds to the entry's arrays.
        with self.tracer.span(
            "topn.score", parts=1 if "rows" in ent else len(ent["parts"])
        ) as sp:
            if "rows" in ent:
                # the layout the scorer reads in place: the plane at the
                # width the fragment keeps
                words = int(ent["rows"][2].shape[1])
                sp.annotate(
                    layout="narrow" if words < bp.WORDS_PER_SLICE else "wide",
                    stride_words=words,
                )
            scores = None
            leader = False
            ev = None
            with self._batch_mu:
                scores = ent.get("scores")
                if scores is None:
                    ev = ent.get("score_event")
                    if ev is None:
                        ev = ent["score_event"] = threading.Event()
                        leader = True
            if scores is None and not leader:
                # A leader is scoring right now; its fetched vector
                # arrives with the event.  A failed leader leaves
                # scores unset — fall through and score directly.
                with trace.blocked("queue"):
                    ev.wait(timeout=coalesce_mod.RESULT_TIMEOUT_S)
                with self._batch_mu:
                    scores = ent.get("scores")
            if scores is None:
                try:
                    # Pin the prep entry and every scored fragment's
                    # mirror for the fused scorer's dispatch+fetch: the
                    # pool may evict none of the planes this program
                    # reads mid-query.
                    with device_mod.pool().pinned(
                        self._topn_pool_key((index, str(c), tuple(slices))),
                        *ent["pins"],
                    ):
                        scores = (
                            self._score_topn_rows(ent)
                            if "rows" in ent
                            else self._score_topn_parts(ent["score"])
                        )
                    with self._batch_mu:
                        ent["scores"] = scores
                    sp.annotate(score_cache="computed")
                finally:
                    if leader:
                        ev.set()
            else:
                sp.annotate(score_cache="shared")
                self.holder.stats.count("exec.topn.scoreShared")

        # Both protocol phases from the one score vector, over the
        # entry's stacked arrays (topn_stack.select): each slice's
        # phase-1 winners among its own candidates, as the two-phase
        # protocol's first round would have chosen them, then exact
        # sums for the winner union, which are already in hand.  No
        # call a part: ``way`` says so.  The ``topn.select`` span is
        # the host-winner-selection leg of the per-stage TopN(src)
        # breakdown (with topn.dispatch/topn.fetch).
        if "rows" in ent:
            # one fragment: its winners are the answer, and their
            # counts are exact as scored
            with self.tracer.span("topn.select", parts=1, way="rows"):
                at, sums = scores
                ids = ent["rows"][1].ids[at]
                order = np.lexsort((ids, -sums.astype(np.int64)))
                if n:
                    order = order[:n]
                return [
                    Pair(i, cnt)
                    for i, cnt in zip(ids[order].tolist(), sums[order].tolist())
                ]
        with self.tracer.span(
            "topn.select", parts=len(ent["parts"]), way="stacked"
        ):
            ids, sums = topn_stack.select(ent["stack"], scores, n)
            return [Pair(i, cnt) for i, cnt in zip(ids.tolist(), sums.tolist())]

    def _execute_topn_slices(
        self, index: str, c: Call, slices: list[int], opt: ExecOptions
    ) -> list[Pair]:
        def map_fn(local_slices: list[int]):
            # Missing fragments contribute nothing — walk only slices
            # that materialized one (O(fragments), not O(max_slice)).
            local_slices = self._existing_topn_slices(index, c, local_slices)
            # The src bitmap (if any) evaluates HOST-side per slice: the
            # scorer needs host words anyway (sparse probing + transfer
            # to the gather kernel), so a device program here would add
            # a sync round trip per query for no compute win.
            src_rows = None
            if len(c.children) == 1:
                src_rows = self._eval_tree_slices_host(
                    index, c.children[0], local_slices
                )
            elif len(c.children) > 1:
                raise ExecutorError("TopN() can only have one input bitmap")
            # Two passes: prepare every slice (candidates + gathered
            # scorer inputs), then score all slices in as few batched
            # programs as their shapes allow, fetched in one transfer —
            # one round trip per node per phase however many slices it
            # owns, the TPU shape of the reference's goroutine-per-slice
            # mapperLocal fan-in (reference: executor.go:1246-1282).
            prepped = [
                self._prepare_topn_slice(index, c, s, src_rows=src_rows)
                for s in local_slices
            ]
            states = [p for p in prepped if p is not None]
            self._count_host_scored(part[0] for _, part in states)
            entries = [
                (*self._attach_dev_src(index, c, frag, part), frag)
                for frag, part in states
            ]
            stack = topn_stack.score_stack(entries)
            pool, pins = device_mod.pool(), [frag._pool_key for frag, _ in states]
            pool.touch_many(pins)
            with pool.pinned(*pins):
                stack.hand_out(entries, self._score_topn_parts(stack))
            states = [(frag, part[0]) for frag, part in states]
            # Merge all slices' results in one numpy pass (counts sum
            # by id — Pairs.Add semantics, reference: cache.go:312-334);
            # Pairs materialize once at the protocol boundary.
            parts = []
            for frag, st in states:
                ids, cnts, keep, short = frag.top_score_arrays(st)
                if short:
                    parts.append((ids, cnts))
                else:
                    sel = keep
                    ids, cnts = ids[sel], cnts[sel]
                    if st.n and st.n < len(ids):
                        order = np.lexsort((ids, -cnts))[: st.n]
                        ids, cnts = ids[order], cnts[order]
                    parts.append((ids, cnts))
            merged = merge_counts_by_id(parts)
            if merged is None:
                return []
            uids, sums = merged
            return [Pair(int(i), int(cnt)) for i, cnt in zip(uids, sums)]

        def reduce_fn(prev, v):
            return cache_mod.add_pairs(prev or [], v)

        pairs = self._map_reduce(index, slices, c, opt, map_fn, reduce_fn) or []
        return cache_mod.sort_pairs(pairs)

    @staticmethod
    def _topn_frame_view(c: Call) -> tuple[str, str]:
        """The (frame, view) a TopN call targets — the single resolution
        point shared by option building and the existing-slice filter."""
        frame = c.args.get("frame") or DEFAULT_FRAME
        view = VIEW_INVERSE if bool(c.args.get("inverse", False)) else VIEW_STANDARD
        return frame, view

    @staticmethod
    def _topn_parsed_args(c: Call):
        """Slice-invariant TopN argument parsing (reference:
        executor.go:346-415), hoisted out of the per-slice loop — at
        hundreds of slices the repeated arg walks dominated option
        building.  Memoized ON the Call instance (clone() builds fresh
        objects, so a mutated clone — e.g. the phase-2 refetch's ids=
        — never sees a stale parse)."""
        cached = getattr(c, "_topn_parsed", None)
        if cached is not None:
            return cached
        frame, view = Executor._topn_frame_view(c)
        n = _uint_arg(c, "n")[0]
        fld = c.args.get("field", "") or ""
        row_ids = _uint_slice_arg(c, "ids")
        min_threshold = _uint_arg(c, "threshold")[0]
        if min_threshold <= 0:
            min_threshold = MIN_THRESHOLD
        filters = c.args.get("filters")
        tanimoto = _uint_arg(c, "tanimotoThreshold")[0]
        cached = (
            frame,
            view,
            n,
            fld,
            tuple(row_ids) if row_ids else None,
            min_threshold,
            tuple(filters) if filters else None,
            tanimoto,
        )
        c._topn_parsed = cached
        return cached

    def _topn_options(self, c: Call, src=None) -> TopOptions:
        """The call's arguments as a fragment's TopOptions (reference:
        executor.go:346-415); a walk over many fragments makes them
        once.  Callers validate here only once a fragment exists,
        matching the reference's ordering: a bad tanimoto over absent
        fragments yields empty results, not an error."""
        (
            _frame,
            _view,
            n,
            fld,
            row_ids,
            min_threshold,
            filters,
            tanimoto,
        ) = self._topn_parsed_args(c)
        if tanimoto > 100:
            raise ExecutorError("Tanimoto Threshold is from 1 to 100 only")
        return TopOptions(
            n=n,
            src=src,
            row_ids=list(row_ids) if row_ids else None,
            filter_field=fld,
            filter_values=list(filters) if filters else None,
            min_threshold=min_threshold,
            tanimoto_threshold=tanimoto,
        )

    def _topn_options_for_slice(self, index: str, c: Call, slice_i: int, src_rows=None):
        """reference: executor.go:346-415.  ``src_rows`` carries the
        host-evaluated src rows from _execute_topn_slices.  Returns
        ``(fragment, TopOptions)``, or None when the fragment does not
        exist."""
        frame, view = self._topn_parsed_args(c)[:2]
        src = None
        if src_rows is not None:
            src = RowBitmap()
            row = src_rows.get(slice_i)
            if row is not None:
                src.set_segment(slice_i, row)

        f = self.holder.fragment(index, frame, view, slice_i)
        if f is None:
            return None
        return f, self._topn_options(c, src)

    def _prepare_topn_slice(
        self, index: str, c: Call, slice_i: int, src_rows=None
    ):
        """``(fragment, (TopState, sub, src_words))`` with the score
        kernel NOT yet dispatched (see _score_topn_parts), or None when
        the fragment does not exist."""
        prep = self._topn_options_for_slice(index, c, slice_i, src_rows)
        if prep is None:
            return None
        f, topt = prep
        return f, f.top_prepare_parts(topt)

    # ------------------------------------------------------------------
    # writes (reference: executor.go:642-840)
    # ------------------------------------------------------------------

    def _resolve_write(self, index: str, c: Call, verb: str):
        frame_name = c.args.get("frame")
        if not isinstance(frame_name, str):
            raise ExecutorError(f"{verb}() field required: frame")
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError()
        f = idx.frame(frame_name)
        if f is None:
            raise FrameNotFoundError()
        row_label = f.row_label
        column_label = idx.column_label
        row_id, ok = _uint_arg(c, row_label)
        if not ok:
            raise ExecutorError(f"{verb}() row field '{row_label}' required")
        col_id, ok = _uint_arg(c, column_label)
        if not ok:
            raise ExecutorError(f"{verb}() column field '{column_label}' required")
        return f, row_id, col_id

    def _execute_set_bit(self, index: str, c: Call, opt: ExecOptions) -> bool:
        view = c.args.get("view", "") or ""
        f, row_id, col_id = self._resolve_write(index, c, "SetBit")

        timestamp = None
        ts = c.args.get("timestamp")
        if isinstance(ts, str):
            try:
                timestamp = datetime.strptime(ts, TIME_FORMAT)
            except ValueError:
                raise ExecutorError(f"invalid date: {ts}") from None

        ret = self._write_views(
            index, c, opt, view, f,
            lambda vw, r, cl: f.set_bit(vw, r, cl, timestamp),
            row_id, col_id,
        )
        self._wait_durable(index)
        return ret

    def _execute_clear_bit(self, index: str, c: Call, opt: ExecOptions) -> bool:
        view = c.args.get("view", "") or ""
        f, row_id, col_id = self._resolve_write(index, c, "ClearBit")
        ret = self._write_views(
            index, c, opt, view, f,
            lambda vw, r, cl: f.clear_bit(vw, r, cl),
            row_id, col_id,
        )
        self._wait_durable(index)
        return ret

    def _wait_durable(self, index: str) -> None:
        """Log-before-ack: park until every WAL append THIS thread made
        while applying the write is group-commit fsynced.  Runs OUTSIDE
        every fragment lock — a slow fsync stalls only this writer's
        ack, never a concurrent reader — and covers both the
        coordinator-local leg and remote legs (each remote node's own
        executor waits before responding)."""
        if self.ingest is None:
            return
        with self.tracer.span("ingest", index=index):
            self.ingest.wait_durable()

    def _write_views(
        self, index, c, opt, view, frame, write_fn, row_id, col_id
    ) -> bool:
        """Write to standard and/or inverse views with replica fan-out
        (reference: executor.go:679-734,783-840).  For the inverse view
        the row/column roles transpose: the slice is derived from the
        rowID and the stored (row, col) swap."""
        if view == VIEW_STANDARD:
            return self._write_one_view(index, c, opt, VIEW_STANDARD, write_fn, row_id, col_id)
        if view == VIEW_INVERSE:
            return self._write_one_view(index, c, opt, VIEW_INVERSE, write_fn, col_id, row_id)
        if view == "":
            ret = self._write_one_view(index, c, opt, VIEW_STANDARD, write_fn, row_id, col_id)
            if frame.inverse_enabled:
                if self._write_one_view(index, c, opt, VIEW_INVERSE, write_fn, col_id, row_id):
                    ret = True
            return ret
        raise ExecutorError(f"invalid view: {view}")

    def _write_one_view(
        self, index, c, opt, view, write_fn, row_id, col_id
    ) -> bool:
        # write_nodes: the read owners plus, during a rebalance
        # transition, the slice's NEW-ring owners — every write is
        # applied on both rings so no write is lost whichever ring
        # ultimately serves it (the delta log covers the copy race).
        slice_i = col_id // bp.SLICE_WIDTH
        ret = False
        wn = getattr(self.cluster, "write_nodes", None)
        targets = (
            wn(index, slice_i)
            if wn is not None
            else self.cluster.fragment_nodes(index, slice_i)
        )
        if self.replication is not None and not opt.remote:
            # Quorum path (pilosa_tpu/replicate): W-of-N acknowledgement
            # at the request's consistency, hints queued for unreachable
            # replicas, sub-W failing LOUDLY — never "success because
            # someone acked".
            return self.replication.coordinate_write(
                self, index, c, opt, view, write_fn, row_id, col_id,
                slice_i, targets,
            )
        for node in targets:
            if node.host == self.host:
                if write_fn(view, row_id, col_id):
                    ret = True
                continue
            if opt.remote:
                continue
            res = self._exec_remote(node, index, Query(calls=[c]), None, opt)
            if res and res[0]:
                ret = True
        return ret

    # ------------------------------------------------------------------
    # attribute writes (reference: executor.go:843-1040)
    # ------------------------------------------------------------------

    def _execute_set_row_attrs(self, index: str, c: Call, opt: ExecOptions) -> None:
        frame_name = c.args.get("frame")
        if not isinstance(frame_name, str):
            raise ExecutorError("SetRowAttrs() frame required")
        frame = self.holder.frame(index, frame_name)
        if frame is None:
            raise FrameNotFoundError()
        row_label = frame.row_label
        row_id, ok = _uint_arg(c, row_label)
        if not ok:
            raise ExecutorError(f"SetRowAttrs() row field '{row_label}' required")
        attrs = dict(c.args)
        attrs.pop("frame", None)
        attrs.pop(row_label, None)
        frame.row_attr_store.set_attrs(row_id, attrs)
        if opt.remote:
            return
        self._broadcast_query(index, Query(calls=[c]), opt)

    def _execute_bulk_set_row_attrs(
        self, index: str, calls: list[Call], opt: ExecOptions
    ) -> list:
        """reference: executor.go:905-985"""
        by_frame: dict[str, dict[int, dict]] = {}
        for c in calls:
            frame_name = c.args.get("frame")
            if not isinstance(frame_name, str):
                raise ExecutorError("SetRowAttrs() frame required")
            f = self.holder.frame(index, frame_name)
            if f is None:
                raise FrameNotFoundError()
            row_label = f.row_label
            row_id, ok = _uint_arg(c, row_label)
            if not ok:
                raise ExecutorError(f"SetRowAttrs row field '{row_label}' required")
            attrs = dict(c.args)
            attrs.pop("frame", None)
            attrs.pop(row_label, None)
            by_frame.setdefault(frame_name, {}).setdefault(row_id, {}).update(attrs)
        for frame_name, attr_sets in by_frame.items():
            f = self.holder.frame(index, frame_name)
            f.row_attr_store.set_bulk_attrs(attr_sets)
        if not opt.remote:
            self._broadcast_query(index, Query(calls=calls), opt)
        return [None] * len(calls)

    def _execute_set_column_attrs(self, index: str, c: Call, opt: ExecOptions) -> None:
        idx = self.holder.index(index)
        if idx is None:
            raise IndexNotFoundError()
        id_, ok = _uint_arg(c, "id")
        col_name = "id"
        if not ok:
            id_, ok = _uint_arg(c, idx.column_label)
            if not ok:
                raise ExecutorError("SetColumnAttrs() id required")
            col_name = idx.column_label
        attrs = dict(c.args)
        attrs.pop(col_name, None)
        idx.column_attr_store.set_attrs(id_, attrs)
        if opt.remote:
            return
        self._broadcast_query(index, Query(calls=[c]), opt)

    def _broadcast_query(self, index: str, q: Query, opt: ExecOptions) -> None:
        """Forward a query to every other node in parallel; first error
        wins (reference: executor.go:966-985).  During a rebalance
        transition the new ring's joining nodes receive the broadcast
        too (attribute state must be complete there at cutover)."""
        rn = getattr(self.cluster, "route_nodes", None)
        all_nodes = rn() if rn is not None else self.cluster.nodes
        others = [n for n in all_nodes if n.host != self.host]
        if not others:
            return
        futures = [
            self._pool.submit(self._exec_remote, n, index, q, None, opt)
            for n in others
        ]
        with trace.blocked("map"):
            for fut in futures:
                fut.result()

    # ------------------------------------------------------------------
    # map/reduce over the cluster (reference: executor.go:1131-1283)
    # ------------------------------------------------------------------

    def _slices_by_node(
        self,
        nodes: list[Node],
        index: str,
        slices: list[int],
        epoch: int | None = None,
    ) -> dict[str, tuple[Node, list[int]]]:
        """Group slices by owning node, CACHED per (routing version,
        node set, index, slice list): placement is pure in those inputs
        (fnv + jump hash, reference: cluster.go:202-244), and at bench
        scale re-hashing ~1000 slices per query costs more host time
        than the compiled query program.  Callers treat the result as
        read-only.

        The cluster's ``routing_version`` keys the cache (per-slice
        cutover flips during a rebalance change placement without an
        epoch bump) together with its ``health_version`` (a replica
        whose DEVICE is quarantined — learned via the gossip
        device-health piggyback — is deprioritized: the first
        non-degraded owner serves, falling back to the primary when
        every replica is degraded), and ``epoch`` — when the caller
        captured one at query start — is verified here: a ring mutation
        mid-query raises
        :class:`~pilosa_tpu.cluster.topology.MixedEpochError`
        loudly instead of reducing over a half-old, half-new route."""
        rv = getattr(self.cluster, "routing_version", 0)
        hv = getattr(self.cluster, "health_version", 0)
        if epoch is not None:
            cur = getattr(self.cluster, "epoch", 0)
            if cur != epoch:
                raise topo.MixedEpochError(epoch, cur)
        key = (rv, hv, tuple(n.host for n in nodes), index, tuple(slices))
        with self._batch_mu:
            hit = self._slice_group_cache.get(key)
            if hit is not None:
                self._slice_group_cache.move_to_end(key)
                return hit
        m: dict[str, tuple[Node, list[int]]] = {}
        node_hosts = {n.host for n in nodes}
        for s in slices:
            owners = [
                o
                for o in self.cluster.fragment_nodes(index, s)
                if o.host in node_hosts
            ]
            if not owners:
                raise SliceUnavailableError()
            owner = next(
                (o for o in owners if not getattr(o, "degraded", False)),
                owners[0],
            )
            m.setdefault(owner.host, (owner, []))[1].append(s)
        with self._batch_mu:
            self._slice_group_cache[key] = m
            while len(self._slice_group_cache) > 8:
                self._slice_group_cache.popitem(last=False)
        return m

    def _map_reduce(self, index, slices, c, opt, map_fn, reduce_fn):
        """Map slices over owning nodes, reduce INCREMENTALLY as each
        response lands, and fail a dead node's slices over to replicas
        the moment its error arrives (reference: executor.go:1149-1243
        reduces off a channel the same way).

        A slow or dead node therefore never delays reducing the fast
        nodes' results: completion order drives the reduce loop
        (FIRST_COMPLETED waits), and failover work is resubmitted while
        the healthy nodes' mappers are still in flight.

        Routing is EPOCH-GUARDED: the topology epoch is captured once
        here, and every (re)grouping — including failover re-placement
        — verifies it, so a ring mutation mid-query fails loudly
        instead of mixing epochs."""
        epoch0 = getattr(self.cluster, "epoch", None)
        if not opt.remote:
            # route_nodes = the read ring plus, during a rebalance
            # transition, the new ring's joining nodes (flipped slices
            # already route to them).
            rn = getattr(self.cluster, "route_nodes", None)
            nodes = rn() if rn is not None else list(self.cluster.nodes)
        else:
            me = self.cluster.node_by_host(self.host)
            nodes = [me] if me is not None else [Node(host=self.host)]
        if not nodes:
            nodes = [Node(host=self.host)]

        if not slices:
            # Sliceless execution still runs locally once.
            resp = self._map_node(Node(host=self.host), [], index, c, opt, map_fn)
            if resp.error:
                raise resp.error
            return reduce_fn(None, resp.result)

        result = None
        # future -> node list the future's slices may still fail over to
        inflight: dict = {}
        # Slices dropped under allow_partial (every replica down/open).
        missing: list[int] = []

        def _submit(avail_nodes, want) -> None:
            m = self._slices_by_node(avail_nodes, index, want, epoch=epoch0)
            for _, (node, node_slices) in m.items():
                fut = self._pool.submit(
                    self._map_node, node, node_slices, index, c, opt, map_fn
                )
                inflight[fut] = avail_nodes

        def _failover(resp, avail_nodes) -> None:
            """Re-place a failed mapper's slices on the remaining nodes.
            An exhausted DEADLINE is never a node failure — it fails the
            query (504), not the node.  Slices with no surviving replica
            either fail fast with the slice list or, under
            ``allow_partial``, drop into ``missing``.  A semantic error
            (bad frame, parse-adjacent failures) re-raises rather than
            masquerading as a dead node."""
            if isinstance(resp.error, resilience.DeadlineExceeded):
                raise resp.error
            if not resilience.is_node_failure(resp.error):
                raise resp.error
            remaining = [n for n in avail_nodes if n.host != resp.node.host]
            placeable, lost = self.cluster.split_by_owner(
                index, resp.slices, {n.host for n in remaining}
            )
            if lost:
                if not opt.allow_partial:
                    raise SlicesUnavailableError(lost, cause=resp.error)
                missing.extend(lost)
                self.holder.stats.count(
                    "exec.partial.slicesDropped", len(lost)
                )
            if placeable:
                _submit(remaining, placeable)

        m = self._slices_by_node(nodes, index, slices, epoch=epoch0)
        if len(m) == 1:
            # Single target (the whole single-node case): run the
            # mapper inline.  A pool hop would add a context switch
            # per query and cap request concurrency at the pool
            # size — the caller's own thread is the parallelism.
            ((node, node_slices),) = m.values()
            resp = self._map_node(node, node_slices, index, c, opt, map_fn)
            if resp.error is None:
                return reduce_fn(None, resp.result)
            _failover(resp, nodes)
        else:
            _submit(nodes, slices)

        while inflight:
            # Reduce-loop waits derive from the remaining deadline
            # budget, not a flat constant: when it runs out, abandon the
            # in-flight mappers (daemon pool) and 504.
            dl = resilience.current_deadline()
            timeout = None
            if dl is not None:
                timeout = dl.remaining()
                if timeout <= 0:
                    raise resilience.DeadlineExceeded(
                        "deadline exceeded awaiting map responses"
                    )
            # the request thread stands still for its own mappers, on
            # this node's pool or remote: blocked on purpose, not the GIL
            with trace.blocked("map"):
                done, _ = wait(
                    list(inflight), timeout=timeout,
                    return_when=FIRST_COMPLETED,
                )
            if not done:
                raise resilience.DeadlineExceeded(
                    "deadline exceeded awaiting map responses"
                )
            for fut in done:
                avail_nodes = inflight.pop(fut)
                resp = fut.result()
                if resp.error is not None:
                    _failover(resp, avail_nodes)
                    continue
                result = reduce_fn(result, resp.result)
        if missing:
            # Merge (a query may map/reduce more than once — TopN's two
            # phases), keep sorted + deduplicated for the wire marker.
            opt.missing_slices[:] = sorted(
                set(opt.missing_slices) | set(missing)
            )
        return result

    def _map_node(self, node, node_slices, index, c, opt, map_fn) -> _MapResponse:
        resp = _MapResponse(node=node, slices=node_slices)
        try:
            # The deadline contextvar crossed into this worker with the
            # submitter's context; an exhausted budget fails the QUERY
            # (504 at the handler), never the node.
            resilience.check_deadline("before map")
            if node.host == self.host:
                with self.tracer.span(
                    "map.local", node=node.host, slices=len(node_slices)
                ):
                    resp.result = map_fn(node_slices)
            else:
                results = self._exec_remote(
                    node, index, Query(calls=[c]), node_slices, opt,
                    idempotent=True,
                )
                resp.result = results[0] if results else None
        except resilience.DeadlineExceeded:
            raise
        except Exception as e:  # noqa: BLE001 — failover boundary
            resp.error = e
        return resp

    def _exec_remote(
        self, node, index, q, slices, opt, idempotent=False,
        extra_headers=None,
    ) -> list:
        """Forward a query to a peer (reference: executor.go:1045-1129).

        The rpc span's ids travel as X-Trace-Id/X-Span-Id headers; the
        remote handler continues the trace under them and ships its
        spans back, which the client absorbs into this node's trace.

        ``idempotent`` marks the call safe to retry (read-only map
        legs); write fan-out stays single-shot, matching the client's
        retry contract.  ``extra_headers`` ride the same header channel
        (the quorum coordinator's X-Write-Version stamp)."""
        if self.client_factory is None:
            raise ExecutorError(f"no client for remote node {node.host}")
        client = self.client_factory(node)
        with self.tracer.span(
            "rpc.execute", node=node.host, slices=len(slices) if slices else 0
        ) as sp:
            headers = self.tracer.remote_headers(sp)
            if extra_headers:
                headers = {**(headers or {}), **extra_headers}
            if opt.tenant:
                headers = {**(headers or {}), "X-Tenant": opt.tenant}
            kwargs = {}
            if getattr(client, "supports_resilience", False):
                kwargs["idempotent"] = idempotent
            if headers and getattr(client, "supports_trace", False):
                return client.execute_query(
                    index,
                    str(q),
                    slices,
                    remote=True,
                    trace_headers=headers,
                    tracer=self.tracer,
                    **kwargs,
                )
            return client.execute_query(
                index, str(q), slices, remote=True, **kwargs
            )


# ---------------------------------------------------------------------------


def _uint_arg(c: Call, key: str) -> tuple[int, bool]:
    """(value, present) via Call.uint_arg (negative int64s wrap to
    uint64, so e.g. rowID=-1 reads an empty astronomically-high row
    instead of erroring), with type errors normalized to ExecutorError
    at the API boundary."""
    try:
        v = c.uint_arg(key)
    except TypeError as e:
        raise ExecutorError(str(e)) from e
    return (0, False) if v is None else (v, True)


def _uint_slice_arg(c: Call, key: str) -> list[int] | None:
    try:
        return c.uint_slice_arg(key)
    except TypeError as e:
        raise ExecutorError(str(e)) from e


def _time_arg(c: Call, key: str) -> datetime:
    v = c.args.get(key)
    if not isinstance(v, str):
        raise ExecutorError(f"Range() {key} time required")
    try:
        return datetime.strptime(v, TIME_FORMAT)
    except ValueError:
        raise ExecutorError(f"cannot parse Range() {key} time") from None
