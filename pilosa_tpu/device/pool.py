"""PlanePool — the HBM residency manager.

Every device allocation the system keeps alive across queries registers
here: fragment plane mirrors (core/fragment.py `device_plane`), paged
sparse rows, and the executor's batch / TopN-prep cache entries
(exec/executor.py).  The pool keeps per-device byte accounting against a
budget (`[device] hbm-budget-bytes`) and reclaims by LRU eviction of
unpinned entries whenever an admission would exceed it — correctness is
free because the host numpy plane is always authoritative: an evicted
mirror simply rebuilds on the next read.

Design points:

* **Admission-before-upload.**  Owners call :meth:`admit` BEFORE the
  ``device_put``, so accounted residency never exceeds budget (modulo
  pinned saturation, which is counted, not hidden).
* **Pin leases.**  The executor pins the entries a fused program reads
  for the duration of dispatch+fetch; pinned entries are never victims,
  so eviction can never drop a plane mid-query.
* **Non-blocking evict callbacks.**  An evict callback must clear the
  owner's device reference under the OWNER's lock — but owners call
  into the pool while holding that lock (e.g. ``device_plane`` admits
  under the fragment lock).  To stay deadlock-free, callbacks acquire
  the owner lock with ``blocking=False`` and return False when they
  lose the race; the pool skips that victim (it is being actively used)
  and moves to the next.  The pool's own lock is reentrant, so a
  callback that calls back into :meth:`remove` is also safe.
* **LRU order** is the entry insertion/touch order; :meth:`touch` on a
  cache hit moves an entry to the MRU end.

Budget resolution (per device): an explicit positive ``configure``
value wins, then the ``PILOSA_DEVICE_HBM_BUDGET_BYTES`` env override,
then a safe fraction of the detected device memory
(``memory_stats()['bytes_limit']``).  The CPU backend reports none and
is unbounded, so tests and laptops never evict unless asked to; an
accelerator that reports none is an error, never "unbounded".
"""

from __future__ import annotations

import os
import threading
from collections import OrderedDict
from dataclasses import dataclass, field
from collections.abc import Callable

from pilosa_tpu.obs import trace
from pilosa_tpu.obs.stats import NopStatsClient

# Auto-detected budget = this fraction of the device's reported
# bytes_limit: headroom for XLA scratch, collectives, and transient
# program outputs that never register with the pool.
DEFAULT_BUDGET_FRACTION = 0.8

ENV_BUDGET = "PILOSA_DEVICE_HBM_BUDGET_BYTES"


def _device_label(dev) -> str:
    """Stable printable identity for a device key (jax Device or any
    hashable stand-in the unit tests use)."""
    i = getattr(dev, "id", None)
    if i is not None:
        return f"{getattr(dev, 'platform', 'dev')}:{i}"
    return str(dev)


@dataclass
class _Entry:
    key: tuple
    bytes_by_device: dict
    evict: Callable[[], bool]
    category: str  # "mirror" | "sparse" | "cache"
    info: dict = field(default_factory=dict)
    pins: int = 0

    @property
    def nbytes(self) -> int:
        return sum(self.bytes_by_device.values())


class PlanePool:
    """Per-device byte accounting + LRU eviction for long-lived device
    arrays.  Thread-safe; one instance serves the whole process (see
    ``pilosa_tpu.device.pool()``)."""

    def __init__(self, budget_bytes: int = 0, stats=None, tracer=None):
        # Reentrant: evict callbacks may legally call remove()/resize()
        # back into the pool from under _mu.
        self._mu = threading.RLock()
        self._entries: "OrderedDict[tuple, _Entry]" = OrderedDict()
        self._resident: dict = {}  # device -> bytes
        self._pinned: dict = {}  # device -> bytes held by pinned entries
        self._max_resident: dict = {}  # device -> high-water bytes
        self._cat_bytes: dict[str, int] = {}  # category -> bytes
        self._evictions = 0
        self._evict_skipped = 0
        self._over_budget = 0
        self._prefetch_hits = 0
        self._prefetch_misses = 0
        # Cold-staging progress (core/holder.stage_device_mirrors +
        # device/prefetch.py): scheduled/done/error counts, total bytes
        # staged, and the LAST staging error — warm_device_mirrors once
        # swallowed failures with only a log line; now every failure
        # counts and the latest surfaces in /debug/hbm.
        self._stage_scheduled = 0
        self._stage_done = 0
        self._stage_errors = 0
        self._stage_bytes = 0
        self._stage_last_error: str | None = None
        # Full mirror (re)uploads through Fragment.device_plane — the
        # cost the ingest delta-scatter exists to avoid.  Bytes, not
        # counts: a write storm that invalidates per-bit shows up as
        # plane_nbytes x writes here, vs one upload with scatter on.
        self._restage_uploads = 0
        self._restage_bytes = 0
        # Contended acquires of ``_mu`` by a request's touches and pin
        # leases (``_wait_for_mu``), and the ms they waited: the process-wide
        # lock eight request threads and the dispatcher meet at.
        self._lock_waits = 0
        self._lock_wait_ms = 0.0
        # 0 = auto (env -> detect); > 0 = explicit bytes.
        self._budget = int(budget_bytes or 0)
        self._detected: int | None = None
        self.stats = stats or NopStatsClient()
        self.tracer = tracer or trace.NOP_TRACER
        self._dev_stats: dict = {}  # device -> tagged stats child

    # ------------------------------------------------------------------
    # configuration / budget
    # ------------------------------------------------------------------

    def configure(self, budget_bytes: int | None = None, stats=None, tracer=None) -> None:
        """Server wiring: budget from config (0 = auto), stats/tracer
        for gauges and evict/prefetch spans."""
        with self._mu:
            if budget_bytes is not None:
                self._budget = int(budget_bytes)
            if stats is not None:
                self.stats = stats
                self._dev_stats.clear()
            if tracer is not None:
                self.tracer = tracer

    def budget_bytes(self) -> int:
        """The effective PER-DEVICE budget; 0 means unbounded."""
        if self._budget > 0:
            return self._budget
        raw = os.environ.get(ENV_BUDGET, "")
        if raw:
            try:
                v = int(raw)
                if v > 0:
                    return v
            except ValueError:
                pass
        return self._detect_budget()

    def _detect_budget(self) -> int:
        """``DEFAULT_BUDGET_FRACTION`` of the first local device's
        ``bytes_limit``.  The CPU backend reports no memory stats and is
        unbounded (0); an accelerator that reports no limit is an
        error — an unbounded pool there ends in a device OOM."""
        detected = self._detected
        if detected is None:
            import jax

            dev = jax.local_devices()[0]
            mem = dev.memory_stats()
            if mem and mem.get("bytes_limit"):
                detected = int(mem["bytes_limit"] * DEFAULT_BUDGET_FRACTION)
            elif dev.platform == "cpu":
                detected = 0
            else:
                raise RuntimeError(
                    f"device {_device_label(dev)} reports no bytes_limit "
                    "(memory_stats() = "
                    f"{mem!r}); set [device] hbm-budget-bytes explicitly"
                )
            self._detected = detected
        return detected

    # ------------------------------------------------------------------
    # tenant lifecycle
    # ------------------------------------------------------------------

    def admit(
        self,
        key: tuple,
        bytes_by_device: dict,
        evict: Callable[[], bool],
        category: str = "cache",
        info: dict | None = None,
    ) -> None:
        """Register (or re-register with new bytes) an entry, evicting
        LRU unpinned entries first so every touched device stays within
        budget.  Call BEFORE the actual device allocation; on upload
        failure call :meth:`remove`.  Re-admission preserves pins."""
        budget = self.budget_bytes()
        need = {d: int(n) for d, n in bytes_by_device.items() if n}
        # Stats emission happens AFTER the critical section: a stats
        # backend (UDP sendto, tag formatting) must never extend the
        # pool lock's hold time — this is the hottest query-path lock.
        n_ev = n_skip = 0
        over_budget = False
        with self._mu:
            old = self._entries.pop(key, None)
            pins = 0
            if old is not None:
                pins = old.pins
                self._debit(old)
            if budget and need and any(
                self._resident.get(d, 0) + n > budget for d, n in need.items()
            ):
                with self.tracer.span("evict", trigger=category) as sp:
                    n_ev, n_skip = self._evict_for_locked(need, budget, key)
                    sp.annotate(evicted=n_ev)
                if n_ev:
                    self._evictions += n_ev
            ent = _Entry(
                key=key,
                bytes_by_device=need,
                evict=evict,
                category=category,
                info=dict(info or {}),
                pins=pins,
            )
            self._entries[key] = ent
            self._credit(ent)
            if budget and any(
                self._resident.get(d, 0) > budget for d in need
            ):
                # All remaining tenants on the device were pinned (or
                # their owners were busy): correctness beats the budget,
                # but the breach is counted, never silent.
                self._over_budget += 1
                over_budget = True
            gauges = self._gauges_locked(need)
        if n_ev:
            self.stats.count("device.evictions", n_ev)
        if n_skip:
            self.stats.count("device.evictSkipped", n_skip)
        if over_budget:
            self.stats.count("device.overBudget")
        self._publish(gauges)

    def _wait_for_mu(self) -> "trace.blocked":
        """The contended half of a request's ``touch_many`` or pin
        lease (``if not self._mu.acquire(blocking=False)``): block for
        ``_mu`` and time the wait, which is time the thread is blocked
        on purpose (kind ``lock`` in its spans) and is counted here.
        Under the lock it costs one clock read and two additions; the
        caller settles the rest after its release (``trace.blocked``).
        A free lock never comes here and reads no clock."""
        wait = trace.blocked("lock", untraced=True).begin()
        self._mu.acquire()
        wait.stop()
        self._lock_waits += 1
        self._lock_wait_ms += wait.ms
        return wait

    def gauges(self) -> dict:
        """``pool.lockWaits`` / ``pool.lockWaitMs`` for a ``/metrics``
        scrape."""
        with self._mu:
            return {
                "pool.lockWaits": self._lock_waits,
                "pool.lockWaitMs": round(self._lock_wait_ms, 3),
            }

    def touch(self, key: tuple) -> None:
        # Not timed: a distinct Count's miss path comes here once a
        # fragment (``Fragment.gather_slots``, 1,908 times a miss), and
        # a lock taken that often by eight threads stands at the edge of
        # a convoy (PERF.md, PR 38): nothing is added around it.  What
        # waits for those touches shows at the O(1) sites below.
        with self._mu:
            if key in self._entries:
                self._entries.move_to_end(key)

    def touch_many(self, keys) -> None:
        """``touch`` each of ``keys`` under one hold of the lock: a
        query that reads hundreds of resident mirrors keeps them recent
        without taking the pool's lock once a mirror."""
        wait = None
        if not self._mu.acquire(blocking=False):
            wait = self._wait_for_mu()
        try:
            entries = self._entries
            for key in keys:
                if key in entries:
                    entries.move_to_end(key)
        finally:
            self._mu.release()
            if wait is not None:
                wait.settle()

    def resize(
        self, key: tuple, bytes_by_device: dict, info: dict | None = None
    ) -> None:
        """Update an entry's bytes in place (e.g. the sparse-row cache
        shrinking) without changing its LRU position or running
        admission eviction.  ``info`` (when given) replaces the entry's
        snapshot annotations — the compressed-container cache keeps its
        logical-bytes/format-mix surface current this way."""
        gauges = []
        with self._mu:
            ent = self._entries.get(key)
            if ent is None:
                return
            self._debit(ent)
            ent.bytes_by_device = {
                d: int(n) for d, n in bytes_by_device.items() if n
            }
            if info is not None:
                ent.info = dict(info)
            self._credit(ent)
            gauges = self._gauges_locked(ent.bytes_by_device)
        self._publish(gauges)

    def remove(self, key: tuple) -> None:
        gauges = []
        with self._mu:
            ent = self._entries.pop(key, None)
            if ent is not None:
                self._debit(ent)
                gauges = self._gauges_locked(ent.bytes_by_device)
        self._publish(gauges)

    def contains(self, key: tuple) -> bool:
        with self._mu:
            return key in self._entries

    # ------------------------------------------------------------------
    # pin leases
    # ------------------------------------------------------------------

    def pin(self, key: tuple) -> bool:
        """Take a pin lease on an entry; False when it is not resident
        (the caller's snapshot reference still keeps its array alive —
        the lease only guards the POOL's eviction choices)."""
        with self._mu:
            ent = self._entries.get(key)
            if ent is None:
                return False
            ent.pins += 1
            if ent.pins == 1:
                for d, n in ent.bytes_by_device.items():
                    self._pinned[d] = self._pinned.get(d, 0) + n
            return True

    def unpin(self, key: tuple) -> None:
        with self._mu:
            ent = self._entries.get(key)
            if ent is None or ent.pins == 0:
                return
            ent.pins -= 1
            if ent.pins == 0:
                for d, n in ent.bytes_by_device.items():
                    self._pinned[d] = max(0, self._pinned.get(d, 0) - n)

    def pin_many(self, keys) -> list:
        """Pin every present key under ONE lock acquisition; returns
        the keys actually pinned (for the matching :meth:`unpin_many`).
        A fused multi-query launch pins the UNION plane set of its
        whole drained batch — per-key lock round trips would scale the
        pool's hottest lock with batch occupancy."""
        held = []
        wait = None
        if not self._mu.acquire(blocking=False):
            wait = self._wait_for_mu()
        try:
            for k in keys:
                if k is None:
                    continue
                ent = self._entries.get(k)
                if ent is None:
                    continue
                ent.pins += 1
                if ent.pins == 1:
                    for d, n in ent.bytes_by_device.items():
                        self._pinned[d] = self._pinned.get(d, 0) + n
                held.append(k)
        finally:
            self._mu.release()
            if wait is not None:
                wait.settle()
        return held

    def unpin_many(self, keys) -> None:
        wait = None
        if not self._mu.acquire(blocking=False):
            wait = self._wait_for_mu()
        try:
            for k in keys:
                ent = self._entries.get(k)
                if ent is None or ent.pins == 0:
                    continue
                ent.pins -= 1
                if ent.pins == 0:
                    for d, n in ent.bytes_by_device.items():
                        self._pinned[d] = max(
                            0, self._pinned.get(d, 0) - n
                        )
        finally:
            self._mu.release()
            if wait is not None:
                wait.settle()

    class _PinLease:
        def __init__(self, pool: "PlanePool", keys):
            self._pool = pool
            self._keys = keys
            self._held: list = []

        def __enter__(self):
            # One lock acquisition however many keys the launch pins.
            self._held = self._pool.pin_many(self._keys)
            return self

        def __exit__(self, *exc):
            self._pool.unpin_many(self._held)

    def pinned(self, *keys) -> "PlanePool._PinLease":
        """Context manager pinning every present key for the block —
        the executor's per-program lease.  None keys are skipped."""
        return PlanePool._PinLease(self, keys)

    # ------------------------------------------------------------------
    # eviction (callers hold _mu)
    # ------------------------------------------------------------------

    def _evict_for_locked(self, need: dict, budget: int, exclude_key) -> tuple:
        """Returns ``(evicted, skipped)`` counts; the caller emits the
        stats for both outside the lock."""
        evicted = 0
        skipped = 0
        for k in list(self._entries.keys()):
            if all(
                self._resident.get(d, 0) + n <= budget
                for d, n in need.items()
            ):
                break
            if k == exclude_key:
                continue
            ent = self._entries.get(k)
            if ent is None or ent.pins > 0:
                continue
            # Only evicting entries that share a device with the need
            # can make room.
            if not any(d in need for d in ent.bytes_by_device):
                continue
            try:
                ok = bool(ent.evict())
            except Exception:  # noqa: BLE001 — a broken owner must not
                ok = True  # wedge the pool; drop the accounting.
            if ok:
                # The callback may have re-entered remove() itself.
                ent2 = self._entries.pop(k, None)
                if ent2 is not None:
                    self._debit(ent2)
                evicted += 1
            else:
                self._evict_skipped += 1
                skipped += 1
        return evicted, skipped

    # ------------------------------------------------------------------
    # accounting (callers hold _mu)
    # ------------------------------------------------------------------

    def _credit(self, ent: _Entry) -> None:
        for d, n in ent.bytes_by_device.items():
            r = self._resident.get(d, 0) + n
            self._resident[d] = r
            if r > self._max_resident.get(d, 0):
                self._max_resident[d] = r
            if ent.pins > 0:
                self._pinned[d] = self._pinned.get(d, 0) + n
        self._cat_bytes[ent.category] = (
            self._cat_bytes.get(ent.category, 0) + ent.nbytes
        )

    def _debit(self, ent: _Entry) -> None:
        for d, n in ent.bytes_by_device.items():
            self._resident[d] = max(0, self._resident.get(d, 0) - n)
            if ent.pins > 0:
                self._pinned[d] = max(0, self._pinned.get(d, 0) - n)
        self._cat_bytes[ent.category] = max(
            0, self._cat_bytes.get(ent.category, 0) - ent.nbytes
        )

    def _dev_stat(self, dev):
        # Called outside _mu (stats must not extend the critical
        # section); a racing create stores two equivalent children and
        # the last write wins — benign.
        c = self._dev_stats.get(dev)
        if c is None:
            c = self.stats.with_tags(f"device:{_device_label(dev)}")
            self._dev_stats[dev] = c
        return c

    def _gauges_locked(self, devices) -> list:
        """Snapshot the gauge values for ``devices`` under ``_mu``; the
        caller publishes via :meth:`_publish` AFTER releasing it (a
        stats backend must never extend the pool's critical section)."""
        out = [
            (d, "device.residentBytes", float(self._resident.get(d, 0)))
            for d in devices
        ]
        out.append(
            (None, "device.cacheBytes", float(self._cat_bytes.get("cache", 0)))
        )
        return out

    def _publish(self, gauges) -> None:
        for dev, name, value in gauges:
            client = self.stats if dev is None else self._dev_stat(dev)
            client.gauge(name, value)

    # ------------------------------------------------------------------
    # prefetch bookkeeping (incremented by device/prefetch.py)
    # ------------------------------------------------------------------

    def count_prefetch(self, hit: int = 0, miss: int = 0) -> None:
        with self._mu:
            self._prefetch_hits += hit
            self._prefetch_misses += miss
        if hit:
            self.stats.count("device.prefetch.hit", hit)
        if miss:
            self.stats.count("device.prefetch.miss", miss)

    def count_stage(
        self,
        scheduled: int = 0,
        done: int = 0,
        errors: int = 0,
        nbytes: int = 0,
        last_error: str | None = None,
    ) -> None:
        """Cold-staging bookkeeping (``device.stage.*`` counters) — fed
        by the holder's background stager and warm_device_mirrors."""
        with self._mu:
            self._stage_scheduled += scheduled
            self._stage_done += done
            self._stage_errors += errors
            self._stage_bytes += nbytes
            if last_error is not None:
                self._stage_last_error = str(last_error)
        if scheduled:
            self.stats.count("device.stage.scheduled", scheduled)
        if done:
            self.stats.count("device.stage.done", done)
        if errors:
            self.stats.count("device.stage.errors", errors)
        if nbytes:
            self.stats.count("device.stage.bytes", nbytes)

    def count_restage(self, nbytes: int) -> None:
        """One full plane upload through ``Fragment.device_plane`` (the
        ``device.pool.restageBytes`` counter the ingest bench contrasts
        against scatter launches)."""
        with self._mu:
            self._restage_uploads += 1
            self._restage_bytes += int(nbytes)
        if nbytes:
            self.stats.count("device.pool.restageBytes", int(nbytes))

    def restage_bytes(self) -> int:
        with self._mu:
            return self._restage_bytes

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    @property
    def evictions(self) -> int:
        return self._evictions

    def resident_bytes(self, dev=None) -> int:
        with self._mu:
            if dev is not None:
                return self._resident.get(dev, 0)
            return sum(self._resident.values())

    def max_resident_bytes(self, dev=None) -> int:
        with self._mu:
            if dev is not None:
                return self._max_resident.get(dev, 0)
            return max(self._max_resident.values(), default=0)

    def snapshot(self) -> dict:
        """JSON-ready state for ``GET /debug/hbm``: per-device budget /
        resident / pinned / high-water bytes with each device's entries
        (LRU -> MRU), a flat per-fragment residency table, and the
        eviction/prefetch counters."""
        budget = self.budget_bytes()
        with self._mu:
            per_dev: dict = {}
            fragments: list[dict] = []
            resident_total = 0
            logical_total = 0
            for ent in self._entries.values():  # LRU -> MRU order
                # Compressed-container entries annotate the dense bytes
                # they REPLACE (info["logical_bytes"]); everything else
                # is stored at its logical geometry.
                logical = int(ent.info.get("logical_bytes", ent.nbytes))
                resident_total += ent.nbytes
                logical_total += logical
                row = {
                    "kind": ent.category,
                    "bytes": ent.nbytes,
                    "logical_bytes": logical,
                    "pinned": ent.pins > 0,
                }
                if len(ent.bytes_by_device) > 1:
                    # Mesh-sharded entry: each device's row below shows
                    # only ITS shard's bytes; `bytes` is the global size.
                    row["sharded"] = True
                    row["shards"] = len(ent.bytes_by_device)
                row.update(ent.info)
                for d, n in ent.bytes_by_device.items():
                    dd = per_dev.setdefault(
                        d,
                        {
                            "device": _device_label(d),
                            "budget_bytes": budget,
                            "resident_bytes": self._resident.get(d, 0),
                            "pinned_bytes": self._pinned.get(d, 0),
                            "max_resident_bytes": self._max_resident.get(d, 0),
                            "entries": [],
                        },
                    )
                    dd["entries"].append(dict(row, bytes=n))
                if "fragment" in ent.info:
                    fragments.append(
                        dict(
                            row,
                            devices=[
                                _device_label(d) for d in ent.bytes_by_device
                            ],
                        )
                    )
            return {
                "budget_bytes": budget,
                "cache_bytes": self._cat_bytes.get("cache", 0),
                # Compressed-plane headline: resident HBM vs what the
                # same entries would cost at dense geometry.
                "resident_bytes": resident_total,
                "logical_bytes": logical_total,
                "compression_ratio": round(
                    logical_total / resident_total, 3
                )
                if resident_total
                else 1.0,
                "devices": sorted(
                    per_dev.values(), key=lambda d: d["device"]
                ),
                "fragments": fragments,
                "counters": {
                    "evictions": self._evictions,
                    "evictSkipped": self._evict_skipped,
                    "overBudget": self._over_budget,
                    "prefetchHit": self._prefetch_hits,
                    "prefetchMiss": self._prefetch_misses,
                    "restageUploads": self._restage_uploads,
                    "restageBytes": self._restage_bytes,
                },
                # Cold-staging progress for rolling restarts: a
                # restarted node serves while this drains toward
                # scheduled == done + errors.
                "staging": {
                    "scheduled": self._stage_scheduled,
                    "done": self._stage_done,
                    "errors": self._stage_errors,
                    "pending": max(
                        0,
                        self._stage_scheduled
                        - self._stage_done
                        - self._stage_errors,
                    ),
                    "bytes": self._stage_bytes,
                    "last_error": self._stage_last_error,
                },
            }
