"""Node runtime — wires Holder + Executor + Handler + Cluster + loops.

The counterpart of the reference's root Server (reference:
server.go:44-172): open the holder, start the broadcast receiver and
node set, build the executor, serve HTTP, and run three background
loops — anti-entropy, max-slice polling, and runtime metrics (here the
cache flusher keeps the reference's holder flush loop as well,
reference: holder.go:318-352).
"""

from __future__ import annotations

import threading
import time

import jax

from pilosa_tpu import __version__
from pilosa_tpu.cluster import broadcast as bc
from pilosa_tpu.cluster.topology import Cluster, Node
from pilosa_tpu.core.holder import Holder
from pilosa_tpu.exec.executor import Executor
from pilosa_tpu.exec import warmup
from pilosa_tpu.net import resilience as rz
from pilosa_tpu.net import wire_pb2 as wire
from pilosa_tpu.net.client import InternalClient
from pilosa_tpu.net.handler import Handler, make_http_server
from pilosa_tpu.obs.trace import Tracer
from pilosa_tpu.testing import faults

# reference: server.go:38-40
DEFAULT_ANTI_ENTROPY_INTERVAL = 600.0
DEFAULT_POLLING_INTERVAL = 60.0
# reference: holder.go:30-31
DEFAULT_CACHE_FLUSH_INTERVAL = 60.0


class Server:
    """One node of the cluster."""

    def __init__(
        self,
        data_dir: str,
        host: str = "127.0.0.1:0",
        cluster: Cluster | None = None,
        broadcaster=None,
        broadcast_receiver=None,
        anti_entropy_interval: float = DEFAULT_ANTI_ENTROPY_INTERVAL,
        polling_interval: float = DEFAULT_POLLING_INTERVAL,
        cache_flush_interval: float = DEFAULT_CACHE_FLUSH_INTERVAL,
        max_writes_per_request: int | None = None,
        logger=None,
        stats=None,
        compilation_cache_dir: str | None = None,
        prewarm: bool = False,
        stream_chunk_bytes: int = 0,
        slow_query_ms: float = 0.0,
        trace_ring: int = 64,
        mesh_devices: int = 0,
        hbm_budget_bytes: int = 0,
        device_prefetch: bool = True,
        device_stage: bool = True,
        stage_throttle_ms: float = 0.0,
        launch_watchdog_ms: float = 60_000.0,
        quarantine_threshold: int = 3,
        quarantine_open_ms: float = 10_000.0,
        quarantine_probe_successes: int = 1,
        plane_format: str = "auto",
        plane_sparse_max_bytes: int = 65536,
        plane_rle_max_bytes: int = 65536,
        coalesce: bool = True,
        coalesce_max_batch: int = 64,
        coalesce_max_wait_us: int = 0,
        fuse: bool = True,
        fuse_max_programs: int = 16,
        query_timeout_ms: float = 60_000.0,
        broadcast_timeout_ms: float = 5_000.0,
        retry_attempts: int = 3,
        retry_backoff_ms: float = 100.0,
        breaker_failure_threshold: int = 5,
        breaker_open_ms: float = 10_000.0,
        admission: bool = True,
        admission_point_concurrency: int = 32,
        admission_heavy_concurrency: int = 8,
        admission_write_concurrency: int = 16,
        admission_internal_concurrency: int = 128,
        admission_queue_depth: int = 64,
        rebalance_throttle_mbps: float = 0.0,
        rebalance_verify_rounds: int = 3,
        rebalance_delta_cap: int = 50_000,
        rebalance_release_delay_ms: float = 200.0,
        rebalance_on_join: bool = False,
        write_consistency: str = "quorum",
        read_consistency: str = "one",
        hint_cap: int = 10_000,
        hint_replay_throttle_mbps: float = 0.0,
        tier_store: str = "",
        tier_hydrate_throttle_mbps: float = 0.0,
        tier_disk_budget_bytes: int = 0,
        tier_retention_age_s: float = 0.0,
        tier_retention_delete_s: float = 0.0,
        tier_sweep_interval_s: float = 60.0,
        subscribe_enabled: bool = True,
        subscribe_max_subscriptions: int = 10_000,
        subscribe_queue_cap: int = 256,
        subscribe_delta_cap: int = 50_000,
        subscribe_coalesce_ms: float = 5.0,
        subscribe_refresh_ms: float = 500.0,
        ingest_wal: bool = True,
        ingest_group_commit_ms: float = 2.0,
        ingest_group_commit_max: int = 128,
        ingest_scatter: bool = True,
        ingest_wal_segment_bytes: int = 4 << 20,
        admission_subscribe_concurrency: int = 4,
        tenants=None,
        tenant_keys=None,
        tenant_default: str = "default",
        tenant_internal_token: str = "",
        latency_buckets_ms=None,
        slo_ms: float = 0.0,
        slo_objective: float = 0.999,
    ):
        self.data_dir = data_dir
        self.host = host
        self.cluster = cluster or Cluster()
        self.broadcaster = broadcaster or bc.NopBroadcaster()
        self.broadcast_receiver = broadcast_receiver or bc.NopBroadcastReceiver()
        self.anti_entropy_interval = anti_entropy_interval
        self.polling_interval = polling_interval
        self.cache_flush_interval = cache_flush_interval
        self.max_writes_per_request = max_writes_per_request
        self.logger = logger or (lambda m: None)
        self.stats = stats
        self.compilation_cache_dir = compilation_cache_dir
        self.prewarm = prewarm
        # Chunk size for streamed HTTP bodies (export/backup data
        # plane); 0 = stream.DEFAULT_CHUNK_BYTES.
        self.stream_chunk_bytes = stream_chunk_bytes
        # Always-on query tracing (Dapper model): every query gets a
        # trace; the last trace_ring traces are retained and served at
        # GET /debug/traces.  slow_query_ms > 0 additionally emits one
        # structured slow-query log line per over-threshold query.
        self.tracer = Tracer(capacity=trace_ring)
        self.slow_query_ms = slow_query_ms
        # Mesh data plane ([device] mesh-devices): devices participating
        # in slice placement and the sharded data plane.  0 = all
        # visible (sharded execution engages by default with >1 device),
        # 1 = force single-device, N = cap.  Placement is process-global
        # (ops/bitplane), so this is applied at open().
        self.mesh_devices = mesh_devices
        # HBM residency manager ([device] config): per-device budget for
        # pool-registered device memory (0 = auto), plus the async
        # cold-mirror prefetcher toggle.
        self.hbm_budget_bytes = hbm_budget_bytes
        self.device_prefetch = device_prefetch
        # Lazy overlapped cold staging ([device] stage): a restarted
        # node starts serving immediately while its fragment mirrors
        # stream into HBM in the background — gossip-hot slices first,
        # then the pre-restart residency order.  stage_throttle_ms
        # rate-limits the background lane (0 = full speed).
        self.device_stage = device_stage
        self.stage_throttle_ms = stage_throttle_ms
        self.staging_job = None
        # Compressed device planes ([device] plane-format / plane-*-max-
        # bytes, ops/bitplane.encode_row): per-row container format
        # selection on the device.  Process-global, applied at open().
        self.plane_format = plane_format
        self.plane_sparse_max_bytes = plane_sparse_max_bytes
        self.plane_rle_max_bytes = plane_rle_max_bytes
        # Device-fault tolerance ([device] launch-watchdog-ms /
        # quarantine-*, device/health.py): per-device + collective-path
        # quarantine state machine with half-open probes, and the
        # hung-collective launch watchdog.  Shared by the executor and
        # the coalescer; state changes flip the local node's degraded
        # flag (and, with gossip, every peer's view), and a HEAL kicks
        # the staging lane to re-materialize HBM mirrors.
        from pilosa_tpu.device.health import DeviceHealth

        self.device_health = DeviceHealth(
            quarantine_threshold=quarantine_threshold,
            open_ms=quarantine_open_ms,
            probe_successes=quarantine_probe_successes,
            watchdog_ms=launch_watchdog_ms,
            stats=stats,
            logger=self.logger,
            on_state_change=self._on_device_health_change,
        )
        # Cross-query coalescing ([exec] config): concurrent queries
        # sharing a compile key ride one fused launch (exec/coalesce.py).
        self.coalesce = coalesce
        self.coalesce_max_batch = coalesce_max_batch
        self.coalesce_max_wait_us = coalesce_max_wait_us
        # Plane-major multi-query fusion ([exec] fuse): distinct trees
        # sharing a program key evaluate in one interpreter pass.
        self.fuse = fuse
        self.fuse_max_programs = fuse_max_programs
        self.coalescer = None
        # Cluster resilience ([net] config, net/resilience.py): the
        # retry policy and per-host circuit breakers every client this
        # server hands out shares, plus the default query deadline.
        # Deadlines flow per request (X-Deadline-Ms); breakers make a
        # down host fail in microseconds instead of a socket timeout.
        self.broadcast_timeout_ms = broadcast_timeout_ms
        self.resilience = rz.Resilience(
            retry=rz.RetryPolicy(
                attempts=retry_attempts,
                backoff=retry_backoff_ms / 1000.0,
                stats=stats,
            ),
            breakers=rz.BreakerRegistry(
                failure_threshold=breaker_failure_threshold,
                open_s=breaker_open_ms / 1000.0,
                stats=stats,
            ),
            query_timeout_ms=query_timeout_ms,
        )
        # Admission control ([net] admission-*, net/admission.py):
        # per-cost-class concurrency gates + bounded queues in front of
        # the executor, shedding 429 + Retry-After when predicted queue
        # wait exceeds the request's remaining deadline.  Remote map
        # legs ride a separate internal priority lane so a saturated
        # cluster cannot distributed-livelock.
        # Tenant QoS ([net] tenants/tenant-keys, net/admission.py
        # TenantRegistry): API-key -> tenant resolution, WFQ weights,
        # and quota buckets.  Built even when admission is off so the
        # internal-lane token check and /debug/tenants still work.
        from pilosa_tpu.net.admission import TenantRegistry

        self.tenants = TenantRegistry(
            tenants=tenants,
            keys=tenant_keys,
            default_tenant=tenant_default,
            internal_token=tenant_internal_token,
            stats=stats,
        )
        self.admission = None
        if admission:
            from pilosa_tpu.net.admission import AdmissionController

            self.admission = AdmissionController(
                point_concurrency=admission_point_concurrency,
                heavy_concurrency=admission_heavy_concurrency,
                write_concurrency=admission_write_concurrency,
                internal_concurrency=admission_internal_concurrency,
                subscribe_concurrency=admission_subscribe_concurrency,
                queue_depth=admission_queue_depth,
                stats=stats,
                tenants=self.tenants,
            )

        self.holder = Holder(data_dir)
        # Elastic-cluster rebalancer ([cluster] rebalance-*,
        # pilosa_tpu/rebalance): applies fanned-out topology events on
        # every node and coordinates background slice migration on the
        # node that receives POST /cluster/resize.  The bandwidth
        # throttle keeps bulk copies from starving client traffic; the
        # release delay lets in-flight old-ring reads drain before a
        # migrated-away slice's data goes.
        self.rebalance_throttle_mbps = rebalance_throttle_mbps
        self.rebalance_verify_rounds = rebalance_verify_rounds
        self.rebalance_delta_cap = rebalance_delta_cap
        self.rebalance_release_delay_ms = rebalance_release_delay_ms
        self.rebalance_on_join = rebalance_on_join
        from pilosa_tpu.rebalance import Rebalancer

        self.rebalance = Rebalancer(self)
        # Quorum replication ([cluster] write-consistency /
        # read-consistency, pilosa_tpu/replicate): per-slice monotonic
        # write versions, W-of-N write acknowledgement with hinted
        # handoff for unreachable replicas, version-checked reads with
        # read-repair.  The hint replayer triggers off the shared
        # per-host breakers (open -> half-open = the recovery signal)
        # and its repair pushes ride the rebalancer's delta machinery.
        from pilosa_tpu.replicate import Replication

        self.replication = Replication(
            host=self.host,
            cluster=self.cluster,
            holder=self.holder,
            client_factory=self._client_factory,
            breakers=self.resilience.breakers,
            rebalancer=self.rebalance,
            tracer=self.tracer,
            stats=self.holder.stats,
            logger=self.logger,
            data_dir=data_dir,
            write_consistency=write_consistency,
            read_consistency=read_consistency,
            hint_cap=hint_cap,
            hint_replay_throttle_mbps=hint_replay_throttle_mbps,
        )
        # Tiered storage ([tier] config, pilosa_tpu/tier): the shared
        # object-store cold tier.  Built at open() (the store client
        # shares the server's retry/breaker wiring); None when no
        # store is configured.
        self.tier_store = tier_store
        self.tier_hydrate_throttle_mbps = tier_hydrate_throttle_mbps
        self.tier_disk_budget_bytes = tier_disk_budget_bytes
        self.tier_retention_age_s = tier_retention_age_s
        self.tier_retention_delete_s = tier_retention_delete_s
        self.tier_sweep_interval_s = tier_sweep_interval_s
        self.tier = None
        # Standing queries ([subscribe] config, pilosa_tpu/subscribe):
        # built at open() AFTER the executor exists (the delta engine
        # pulls through it on overflow/TopN/topology change); None when
        # disabled.
        self.subscribe_enabled = subscribe_enabled
        self.subscribe_max_subscriptions = subscribe_max_subscriptions
        self.subscribe_queue_cap = subscribe_queue_cap
        self.subscribe_delta_cap = subscribe_delta_cap
        self.subscribe_coalesce_ms = subscribe_coalesce_ms
        self.subscribe_refresh_ms = subscribe_refresh_ms
        self.subscribe = None
        # Durable ingest ([ingest] config, pilosa_tpu/ingest): the WAL
        # manager is built at open() BEFORE holder.open() — fragments
        # replay their WAL tails as they open and attach writers via
        # the module registry.  None when the WAL is disabled.
        self.ingest_wal = ingest_wal
        self.ingest_group_commit_ms = ingest_group_commit_ms
        self.ingest_group_commit_max = ingest_group_commit_max
        self.ingest_scatter = ingest_scatter
        self.ingest_wal_segment_bytes = ingest_wal_segment_bytes
        self.ingest = None
        # Performance observability ([obs] latency-buckets-ms / slo-*,
        # obs/perf.py): native fixed-bucket latency histograms + SLO
        # burn gauges live on the Handler.
        self.latency_buckets_ms = latency_buckets_ms
        self.slo_ms = slo_ms
        self.slo_objective = slo_objective
        self.executor: Executor | None = None
        self.handler: Handler | None = None
        self._http = None
        self._http_thread = None
        self._closing = threading.Event()
        self._loops: list[threading.Thread] = []
        self._ae_ticks = 0

    def _client_factory(self, node) -> InternalClient:
        """Inter-node clients carrying this server's resilience wiring:
        shared retry policy, shared per-host breakers, and (via the
        deadline contextvar) the active query's remaining budget."""
        host = node if isinstance(node, str) else node.host
        return InternalClient(
            host,
            retry=self.resilience.retry,
            breakers=self.resilience.breakers,
            internal_token=self.tenants.internal_token,
        )

    # ------------------------------------------------------------------
    # lifecycle (reference: server.go:99-198)
    # ------------------------------------------------------------------

    def open(self) -> None:
        bind_host, _, bind_port = self.host.partition(":")
        port = int(bind_port or 0)
        # Chaos layer (testing/faults.py): announce an active
        # PILOSA_FAULTS plan loudly — a soak run must be unmistakable.
        plan = faults.active()
        if plan is not None and plan.rules:
            self.logger(
                f"FAULT INJECTION ACTIVE: {len(plan.rules)} rule(s): "
                + "; ".join(
                    f"{r.stage}/{r.mode}" for r in plan.rules
                )
            )

        # Max-slice growth must reach peers before queries route there
        # (reference: view.go:236-241 broadcasts CreateSliceMessage).
        self.holder.on_create_slice = self._on_create_slice
        if self.stats is not None:
            # Root of the tag chain: indexes opened from disk (and all
            # their frames/views/fragments) pick up tagged children
            # (reference: server.go wiring of holder.Stats).
            self.holder.stats = self.stats
        # Route storage-layer notices (e.g. op-log tail repairs on
        # fragment open) through the server's configured logger.
        self.holder.logger = self.logger
        # Configure the process-global HBM residency pool before any
        # fragment opens (device mirrors register on first upload): the
        # budget bounds mirrors, paged sparse rows, and executor caches;
        # gauges/counters flow through the server's stats client and
        # evict/prefetch spans into its tracer.
        from pilosa_tpu import device as device_mod
        from pilosa_tpu.ops import bitplane as bp

        # Mesh-devices cap BEFORE any fragment opens: slice placement
        # (home_device) and the slices mesh both derive from it.  Only
        # an explicit cap is applied — the process-global default (all
        # visible devices) must survive in-process multi-server setups.
        if self.mesh_devices > 0:
            bp.configure_mesh_devices(self.mesh_devices)
        devs = jax.devices()
        self.logger(
            f"devices: platform={devs[0].platform} "
            f"kind={devs[0].device_kind!r} count={len(devs)}"
        )
        n_mesh = bp.mesh_device_count()
        if n_mesh > 1:
            self.logger(
                f"data plane: mesh-sharded over {n_mesh} devices "
                "(slice planes placed per shard, counts reduce over ICI); "
                "set [device] mesh-devices = 1 to force single-device"
            )
        device_mod.pool().configure(
            budget_bytes=self.hbm_budget_bytes,
            stats=self.stats,
            tracer=self.tracer,
        )
        # Resolve the budget NOW: an accelerator that reports no
        # bytes_limit must fail open(), not the first admission.
        budget = device_mod.pool().budget_bytes()
        self.logger(
            "hbm budget: "
            + (f"{budget} bytes per device" if budget else "unbounded")
        )
        # Cold-start elimination (see exec/warmup.py): persistent XLA
        # compile cache so restarts deserialize programs from disk, and
        # a background pre-warm of the standard query shapes so even a
        # first boot doesn't pay compiles at query time.
        if self.compilation_cache_dir is not None:
            # The ACTIVE dir is logged (JAX_COMPILATION_CACHE_DIR, the
            # configured one, or the fixed default; first caller in the
            # process wins) so operators never chase an empty dir.
            active = warmup.enable_compile_cache(self.compilation_cache_dir)
            if active is not None:
                self.logger(f"compilation cache: {active}")
            elif self.compilation_cache_dir != "off":
                # An unwritable cache dir must be VISIBLE: every restart
                # silently pays full recompiles otherwise.
                wanted = warmup.resolve_cache_dir(self.compilation_cache_dir)
                self.logger(
                    f"compilation cache DISABLED: could not create {wanted!r}"
                    "; queries recompile from scratch on every process "
                    "start"
                )
        # Durable ingest: flip the module-level scatter switch and
        # register the WAL manager BEFORE holder.open() — fragments
        # replay their WAL tails as they open and attach writers
        # through the module registry (path-prefix ownership keeps
        # multiple in-process servers isolated).
        from pilosa_tpu.ingest import scatter as scatter_mod
        from pilosa_tpu.ingest import wal as wal_mod

        scatter_mod.ENABLED = bool(self.ingest_scatter)
        # Compressed device planes: flip the module-level format policy
        # before any fragment encodes a payload.
        from pilosa_tpu.ops import bitplane as bp_mod

        bp_mod.configure_plane_format(
            mode=self.plane_format,
            sparse_max_bytes=self.plane_sparse_max_bytes,
            rle_max_bytes=self.plane_rle_max_bytes,
        )
        if self.ingest_wal:
            self.ingest = wal_mod.IngestManager(
                self.data_dir,
                wal=True,
                group_commit_ms=self.ingest_group_commit_ms,
                group_commit_max=self.ingest_group_commit_max,
                wal_segment_bytes=self.ingest_wal_segment_bytes,
                stats=self.holder.stats if self.stats is not None else None,
                logger=self.logger,
                versions=self.replication.versions,
            )
            wal_mod.register_manager(self.ingest)
        self.holder.open()

        # Tiered storage: open the cold-store client (sharing the
        # server's retry policy + per-host breakers), then BOOTSTRAP —
        # restore the schema and register store-held fragments as cold
        # BEFORE the first query routes, so a node with an empty data
        # dir and only [tier] store configured serves the whole index,
        # hydrating on demand.
        if self.tier_store:
            from pilosa_tpu.tier import TierManager, open_store

            store = open_store(
                self.tier_store,
                stats=self.stats,
                retry=self.resilience.retry,
                breakers=self.resilience.breakers,
            )
            self.tier = TierManager(
                holder=self.holder,
                store=store,
                prefetcher=device_mod.prefetcher(),
                stats=self.stats,
                tracer=self.tracer,
                logger=self.logger,
                hydrate_throttle_mbps=self.tier_hydrate_throttle_mbps,
                disk_budget_bytes=self.tier_disk_budget_bytes,
                retention_age_s=self.tier_retention_age_s,
                retention_delete_s=self.tier_retention_delete_s,
            )
            boot = self.tier.bootstrap()
            self.logger(
                f"tier: cold store {store.url} attached "
                f"({boot['cold']} cold fragment(s) registered, "
                f"{boot['frames']} frame(s) restored from schema)"
            )

        if self.coalesce:
            from pilosa_tpu.exec.coalesce import CoalesceScheduler

            self.coalescer = CoalesceScheduler(
                max_batch=self.coalesce_max_batch,
                max_wait_us=self.coalesce_max_wait_us,
                stats=self.stats,
                fuse=self.fuse,
                fuse_max_programs=self.fuse_max_programs,
                health=self.device_health,
            )
        prewarm_thread = None
        if self.prewarm:
            # With coalescing on, also compile the coalescer's
            # power-of-two bucket shapes for the common Count trees so
            # the first coalesced batch doesn't eat a cold compile.
            # The TopN scorer's, the leaf-batch gather's and the in-place
            # BSI aggregate's shapes are read off the holder HERE, as it
            # was opened: read on the prewarm thread they would be those
            # of whatever an import had loaded by then.
            prewarm_thread = warmup.prewarm_async(
                logger=self.logger,
                coalesce=self.coalesce,
                topn=warmup.topn_shapes(self.holder),
                gather=warmup.gather_shapes(self.holder),
                agg=warmup.agg_shapes(self.holder),
                rows=warmup.rows_shapes(self.holder),
            )

        # Start HTTP listener first so ":0" resolves to the real port
        # before the node self-registers (reference: server.go:109-125).
        self.handler = Handler(
            holder=self.holder,
            cluster=self.cluster,
            broadcaster=self.broadcaster,
            client_factory=self._client_factory,
            version=__version__,
            logger=self.logger,
            stats=self.stats,
            stream_chunk_bytes=self.stream_chunk_bytes,
            tracer=self.tracer,
            slow_query_ms=self.slow_query_ms,
            resilience=self.resilience,
            admission=self.admission,
            tenants=self.tenants,
            rebalance=self.rebalance,
            tier=self.tier,
            replication=self.replication,
            latency_buckets_ms=self.latency_buckets_ms,
            slo_ms=self.slo_ms,
            slo_objective=self.slo_objective,
        )
        # Profiler captures (GET /debug/profile) tar under the data dir
        # so the artifact survives the request and ships with backups.
        self.handler.profile_dir = self.data_dir
        # The prewarm's outcome (programs compiled, or the error that
        # stopped it) shows at GET /debug/health.
        self.handler.prewarm = prewarm_thread
        # Migration arrivals (?stage=true restores) register their HBM
        # mirrors through the background staging lane.
        self.handler.prefetcher = device_mod.prefetcher()
        # The rebalance delta log captures the write stream of every
        # actively-migrating slice from the fragment write hook; the
        # replication listener bumps per-slice write versions and feeds
        # the quorum coordinator's hint-capture scope on the same hook.
        from pilosa_tpu.core import fragment as fragment_mod

        fragment_mod.register_write_listener(self.rebalance.delta_log.record)
        if self.stats is not None:
            self.replication.stats = self.holder.stats
            self.replication.versions.stats = self.holder.stats
            self.replication.hints.stats = self.holder.stats
        fragment_mod.register_write_listener(self.replication.on_local_write)
        # ONE provider feeds both /state (the stream fallback's pull
        # endpoint, any cluster type) and gossip's piggybacked state —
        # the digest gossip advertises must be of the exact blob /state
        # serves.
        state_provider = lambda: self.local_status().SerializeToString()  # noqa: E731
        self.handler.state_provider = state_provider
        self._http = make_http_server(self.handler, bind_host or "127.0.0.1", port)
        addr = self._http.server_address
        # Keep the *configured* host string as the node identity — it must
        # string-match the cluster.hosts entries or placement forks per
        # node; only a ":0" port is replaced with the bound one.
        if port == 0:
            self.host = f"{bind_host or addr[0]}:{addr[1]}"

        # Self-register in the cluster (reference: server.go:117-125) —
        # UNLESS a ring is already configured that this host is not
        # part of: that is a JOINING node (it would fork placement if
        # it inserted itself), which receives ownership only through a
        # rebalance transition (POST /cluster/resize).
        if self.cluster.node_by_host(self.host) is None:
            if self.cluster.nodes:
                self.logger(
                    f"host {self.host} is not in the configured ring "
                    f"({len(self.cluster.nodes)} nodes); joining — slice "
                    "ownership arrives via /cluster/resize"
                )
            else:
                self.cluster.add_node(self.host)

        # Crash recovery: a persisted in-flight topology transition
        # (both rings + flipped slices) restores BEFORE the first query
        # routes; migration resumes when the operator re-issues the
        # resize.
        self.rebalance.resume_from_disk()

        # Replication opens AFTER the node identity is final (a ":0"
        # port just resolved): persisted write versions restore and the
        # hint replayer starts watching the shared breakers.
        self.replication.host = self.host
        self.replication.open()

        self.broadcast_receiver.start(self)
        ns = getattr(self.cluster, "node_set", None)
        if ns is not None:
            # Gossip backends piggyback node state on probes and surface
            # membership changes (reference: gossip.go:191-222 LocalState/
            # MergeRemoteState, cluster.go:161-173 node states).
            if hasattr(ns, "state_provider") and ns.state_provider is None:
                ns.state_provider = state_provider
            if hasattr(ns, "state_merger") and ns.state_merger is None:

                def _merge(blob: bytes) -> None:
                    st = wire.NodeStatus()
                    st.ParseFromString(blob)
                    self.handle_remote_status(st)

                ns.state_merger = _merge
            if hasattr(ns, "hot_provider") and ns.hot_provider is None:
                # Announce this node's hottest resident slices on every
                # ping/ack, so restarting peers stage what the cluster
                # is being asked about FIRST.
                ns.hot_provider = self.holder.hot_slices
            if hasattr(ns, "health_provider") and ns.health_provider is None:
                # Device-health piggyback: the degraded flag rides
                # every ping/ack; receivers deprioritize this node as a
                # replica while its accelerator is quarantined.
                ns.health_provider = self.device_health.degraded
                ns.on_peer_health = self.cluster.note_degraded
            if hasattr(ns, "on_membership_change"):
                ns.on_membership_change = self._on_membership_change
            ns.open()

        kwargs = {}
        if self.max_writes_per_request is not None:
            kwargs["max_writes_per_request"] = self.max_writes_per_request
        self.executor = Executor(
            holder=self.holder,
            host=self.host,
            cluster=self.cluster,
            client_factory=self._client_factory,
            tracer=self.tracer,
            prefetcher=(
                device_mod.prefetcher() if self.device_prefetch else None
            ),
            coalescer=self.coalescer,
            replication=self.replication,
            device_health=self.device_health,
            **kwargs,
        )
        # Log-before-ack: point-write acks through this executor wait
        # on the WAL group commit (no-op when the WAL is disabled).
        self.executor.ingest = self.ingest
        self.handler.executor = self.executor
        self.handler.ingest = self.ingest

        # Standing queries ([subscribe], pilosa_tpu/subscribe): the
        # manager registers its own fragment write/close listeners and
        # runs the notifier thread; built after the executor because
        # overflow/TopN/topology-change evaluation pulls through it.
        if self.subscribe_enabled:
            from pilosa_tpu.subscribe import SubscriptionManager

            self.subscribe = SubscriptionManager(
                executor=self.executor,
                cluster=self.cluster,
                stats=self.stats,
                tracer=self.tracer,
                admission=self.admission,
                data_dir=self.data_dir,
                logger=self.logger,
                max_subscriptions=self.subscribe_max_subscriptions,
                queue_cap=self.subscribe_queue_cap,
                delta_cap=self.subscribe_delta_cap,
                coalesce_ms=self.subscribe_coalesce_ms,
                refresh_interval_ms=self.subscribe_refresh_ms,
            )
            self.subscribe.open()
            self.handler.subscribe = self.subscribe

        # Lazy overlapped cold staging: serving starts NOW; fragment
        # mirrors stream into HBM behind it — gossip-announced hot
        # slices first, then the pre-restart residency table (MRU
        # first), then everything else.  A query landing on a still-
        # cold slice stages exactly its own planes through the query
        # path/prefetcher and jumps this backlog.  The eager
        # warm_device_mirrors loop this replaces serialized the whole
        # mirror set (~254 MB, cold e2e 4.79 s) before the first
        # answer.
        if self.device_stage:
            self.staging_job = self.holder.stage_device_mirrors(
                device_mod.prefetcher(),
                hot_slices=self._gossip_hot_slices(),
                throttle_s=self.stage_throttle_ms / 1000.0,
                tracer=self.tracer,
            )
            if self.staging_job.total:
                self.logger(
                    f"staging {self.staging_job.total} fragment mirrors "
                    "in the background (device.stage.* / /debug/hbm)"
                )

        self._http_thread = threading.Thread(
            target=self._http.serve_forever, daemon=True, name=f"http:{self.host}"
        )
        self._http_thread.start()

        # Background loops (reference: server.go:166-169).
        loops = [
            ("anti-entropy", self._tick_anti_entropy, self.anti_entropy_interval),
            ("max-slices", self._tick_max_slices, self.polling_interval),
            ("cache-flush", self._tick_cache_flush, self.cache_flush_interval),
            ("runtime", self._tick_runtime, self.polling_interval),
        ]
        if self.tier is not None:
            # Retention aging/deletion + disk-budget LRU demotion.
            loops.append(
                ("tier-sweep", self.tier.sweep, self.tier_sweep_interval_s)
            )
        for name, fn, interval in loops:
            t = threading.Thread(
                target=self._loop,
                args=(fn, interval),
                daemon=True,
                name=f"{name}:{self.host}",
            )
            t.start()
            self._loops.append(t)

    def close(self) -> None:
        self._closing.set()
        # Stop push delivery first: the notifier must not evaluate
        # against a holder/executor that is mid-teardown.
        if self.subscribe is not None:
            self.subscribe.close()
        self.rebalance.close()
        # Stops the hint replayer and persists the per-slice write
        # versions (.replication.json) so a clean restart compares.
        self.replication.close()
        from pilosa_tpu.core import fragment as fragment_mod

        fragment_mod.unregister_write_listener(
            self.rebalance.delta_log.record
        )
        fragment_mod.unregister_write_listener(self.replication.on_local_write)
        if self._http is not None:
            self._http.shutdown()
            self._http.server_close()
        if hasattr(self.broadcast_receiver, "close"):
            self.broadcast_receiver.close()
        if self.executor is not None:
            self.executor.close()
        if self.coalescer is not None:
            # After the executor: in-flight queries fall back to the
            # direct launch path when submit() raises CoalesceClosed.
            self.coalescer.close()
        self.device_health.close()
        self.holder.close()
        # After holder.close(): fragments detached their WAL writers
        # (final commit each) during close; now stop the committer and
        # drop the registry entry so a later in-process server on the
        # same data dir attaches fresh.
        if self.ingest is not None:
            from pilosa_tpu.ingest import wal as wal_mod

            wal_mod.unregister_manager(self.ingest)
            self.ingest.close()
            self.ingest = None
        # Release stats transports (the StatsD UDP socket) last: the
        # close path above may still observe.
        if self.stats is not None:
            close = getattr(self.stats, "close", None)
            if close is not None:
                close()

    def __enter__(self):
        self.open()
        return self

    def __exit__(self, *exc):
        self.close()

    # ------------------------------------------------------------------
    # background loops (reference: server.go:200-274, holder.go:318-352)
    # ------------------------------------------------------------------

    def _loop(self, fn, interval: float) -> None:
        while not self._closing.wait(interval):
            try:
                fn()
            except Exception as e:  # noqa: BLE001 — loops must survive
                self.logger(f"background loop error: {e}")

    # Every Nth anti-entropy tick ignores the version-agreement fast
    # path and walks full block checksums — the backstop for the
    # (crash-reset, equal-but-wrong) version edge cases.
    FULL_SYNC_EVERY = 4

    def _tick_anti_entropy(self) -> None:
        from pilosa_tpu.sync.syncer import HolderSyncer

        self._ae_ticks += 1
        HolderSyncer(
            holder=self.holder,
            host=self.host,
            cluster=self.cluster,
            closing=self._closing,
            replication=self.replication,
            full=(self._ae_ticks % self.FULL_SYNC_EVERY == 0),
        ).sync_holder()

    def _tick_max_slices(self) -> None:
        """Poll peers' max slices so remote-only slices are queryable
        (reference: server.go:238-274).  The timeout is the configured
        ``[net] broadcast-timeout-ms`` (once hardcoded 5.0 here), and
        the GETs ride the shared retry policy + breakers."""
        for node in self.cluster.nodes:
            if node.host == self.host:
                continue
            try:
                client = InternalClient(
                    node.host,
                    timeout=self.broadcast_timeout_ms / 1000.0,
                    retry=self.resilience.retry,
                    breakers=self.resilience.breakers,
                    internal_token=self.tenants.internal_token,
                )
                for index_name, max_slice in client.max_slice_by_index().items():
                    idx = self.holder.index(index_name)
                    if idx is not None:
                        idx.set_remote_max_slice(max_slice)
                for index_name, max_slice in client.max_slice_by_index(
                    inverse=True
                ).items():
                    idx = self.holder.index(index_name)
                    if idx is not None:
                        idx.set_remote_max_inverse_slice(max_slice)
            except Exception:  # noqa: BLE001 — peer may be down
                continue

    def _tick_cache_flush(self) -> None:
        self.holder.flush_caches()

    def _tick_runtime(self) -> None:
        """Runtime gauges — the analog of the reference's goroutine gauge
        + GC notifications (reference: server.go:459-488)."""
        if self.stats is None:
            return
        import gc

        self.stats.gauge("threads", threading.active_count())
        counts = gc.get_count()
        self.stats.gauge("gc.gen0_pending", counts[0])
        try:
            from pilosa_tpu.ingest import scatter as scatter_mod

            scatter_mod.publish_stats(self.stats)
        except Exception:  # noqa: BLE001 — stats are best-effort
            pass

    def _on_device_health_change(self, path: str, state: str) -> None:
        """Device-health transitions (quarantine/heal) from the health
        manager: mirror the node's degraded flag into the local routing
        table (gossip carries it to peers), and on a DEVICE-path heal
        re-materialize HBM mirrors through the staging lane — the mesh
        re-resolves to the healthy device set on the next launch
        (parallel/mesh.default_slices_mesh is derived per call), and
        staging restores the plane mirrors host-fallback service never
        touched."""
        try:
            self.cluster.note_degraded(self.host, self.device_health.degraded())
        except Exception as e:  # noqa: BLE001 — advisory path
            self.logger(f"degraded-flag routing update error: {e}")
        from pilosa_tpu.device.health import STATE_HEALTHY

        if (
            state == STATE_HEALTHY
            and path.startswith("device:")
            and self.device_stage
            and self.holder is not None
        ):
            from pilosa_tpu import device as device_mod

            try:
                job = self.holder.stage_device_mirrors(
                    device_mod.prefetcher(),
                    throttle_s=self.stage_throttle_ms / 1000.0,
                    tracer=self.tracer,
                )
                if job.total:
                    self.logger(
                        f"device health: {path} healed — re-materializing "
                        f"{job.total} fragment mirrors via the staging lane"
                    )
            except Exception as e:  # noqa: BLE001 — staging is best-effort
                self.logger(f"post-heal staging error: {e}")

    def _gossip_hot_slices(self) -> dict[str, list[int]]:
        """Peers' fresh hot-slice announcements (union), when the
        cluster runs a gossip node set; {} otherwise."""
        ns = getattr(self.cluster, "node_set", None)
        fn = getattr(ns, "remote_hot_slices", None)
        if fn is None:
            return {}
        try:
            return fn()
        except Exception:  # noqa: BLE001 — staging order is best-effort
            return {}

    def _on_membership_change(self, items) -> None:
        """Merge NodeSet membership into cluster node *states*.  The
        node list itself never reshards on liveness flaps (reference:
        cluster.go:161-173) — placement changes ONLY through the
        versioned rebalance transition.  A gossip-announced host that
        is not in the ring is surfaced as a JOIN CANDIDATE (and, with
        [cluster] rebalance-on-join, auto-admitted via resize)."""
        for host, state in items:
            node = self.cluster.node_by_host(host)
            if node is not None:
                node.set_state(state)
            else:
                try:
                    self.rebalance.note_membership(host, state)
                except Exception as e:  # noqa: BLE001 — advisory path
                    self.logger(f"join-candidate tracking error: {e}")

    def _on_create_slice(self, index: str, view_name: str, slice_i: int) -> None:
        from pilosa_tpu.core.view import is_inverse_view

        try:
            self.broadcaster.send_async(
                wire.CreateSliceMessage(
                    Index=index, Slice=slice_i, IsInverse=is_inverse_view(view_name)
                )
            )
        except Exception as e:  # noqa: BLE001 — broadcast is best-effort
            self.logger(f"create-slice broadcast error: {e}")

    # ------------------------------------------------------------------
    # BroadcastHandler (reference: server.go:277-325)
    # ------------------------------------------------------------------

    def receive_message(self, msg) -> None:
        if isinstance(msg, wire.CreateSliceMessage):
            idx = self.holder.index(msg.Index)
            if idx is None:
                raise RuntimeError("index not found")
            if msg.IsInverse:
                idx.set_remote_max_inverse_slice(msg.Slice)
            else:
                idx.set_remote_max_slice(msg.Slice)
        elif isinstance(msg, wire.CreateIndexMessage):
            opts = {}
            if msg.Meta.ColumnLabel:
                opts["column_label"] = msg.Meta.ColumnLabel
            if msg.Meta.TimeQuantum:
                opts["time_quantum"] = msg.Meta.TimeQuantum
            self.holder.create_index_if_not_exists(msg.Index, **opts)
        elif isinstance(msg, wire.DeleteIndexMessage):
            self.holder.delete_index(msg.Index)
        elif isinstance(msg, wire.CreateFrameMessage):
            idx = self.holder.index(msg.Index)
            if idx is None:
                raise RuntimeError("index not found")
            opts = {}
            if msg.Meta.RowLabel:
                opts["row_label"] = msg.Meta.RowLabel
            if msg.Meta.InverseEnabled:
                opts["inverse_enabled"] = True
            if msg.Meta.CacheType:
                opts["cache_type"] = msg.Meta.CacheType
            if msg.Meta.CacheSize:
                opts["cache_size"] = msg.Meta.CacheSize
            if msg.Meta.TimeQuantum:
                opts["time_quantum"] = msg.Meta.TimeQuantum
            idx.create_frame_if_not_exists(msg.Frame, **opts)
        elif isinstance(msg, wire.DeleteFrameMessage):
            idx = self.holder.index(msg.Index)
            if idx is not None:
                idx.delete_frame(msg.Frame)
        else:
            raise ValueError(f"unknown message type: {type(msg).__name__}")

    # ------------------------------------------------------------------
    # status (reference: server.go:331-412)
    # ------------------------------------------------------------------

    def local_status(self) -> wire.NodeStatus:
        pb = wire.NodeStatus(Host=self.host, State="UP")
        for idx in self.holder.indexes().values():
            pb_idx = wire.Index(
                Name=idx.name,
                Meta=wire.IndexMeta(
                    ColumnLabel=idx.column_label, TimeQuantum=idx.time_quantum
                ),
                MaxSlice=idx.max_slice(),
            )
            for f in idx.frames().values():
                pb_idx.Frames.append(
                    wire.Frame(
                        Name=f.name,
                        Meta=wire.FrameMeta(
                            RowLabel=f.row_label,
                            InverseEnabled=f.inverse_enabled,
                            CacheType=f.cache_type,
                            CacheSize=f.cache_size,
                            TimeQuantum=f.time_quantum,
                        ),
                    )
                )
            pb.Indexes.append(pb_idx)
        return pb

    def handle_remote_status(self, status: wire.NodeStatus) -> None:
        """Merge a peer's schema into ours (reference:
        server.go:382-412) — creates missing indexes/frames and adopts
        remote max slices."""
        for pb_idx in status.Indexes:
            opts = {}
            if pb_idx.Meta.ColumnLabel:
                opts["column_label"] = pb_idx.Meta.ColumnLabel
            if pb_idx.Meta.TimeQuantum:
                opts["time_quantum"] = pb_idx.Meta.TimeQuantum
            idx = self.holder.create_index_if_not_exists(pb_idx.Name, **opts)
            idx.set_remote_max_slice(pb_idx.MaxSlice)
            for pb_f in pb_idx.Frames:
                fopts = {}
                if pb_f.Meta.RowLabel:
                    fopts["row_label"] = pb_f.Meta.RowLabel
                if pb_f.Meta.InverseEnabled:
                    fopts["inverse_enabled"] = True
                if pb_f.Meta.CacheType:
                    fopts["cache_type"] = pb_f.Meta.CacheType
                if pb_f.Meta.CacheSize:
                    fopts["cache_size"] = pb_f.Meta.CacheSize
                if pb_f.Meta.TimeQuantum:
                    fopts["time_quantum"] = pb_f.Meta.TimeQuantum
                idx.create_frame_if_not_exists(pb_f.Name, **fopts)
