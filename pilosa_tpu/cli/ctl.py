"""Command logic for the CLI (reference: ctl/*.go, server/server.go).

Each ``run_*`` takes the parsed argparse namespace.  Separated from the
flag definitions the way the reference splits ``ctl/`` from ``cmd/``.
"""

from __future__ import annotations

import csv
import io
import os
import sys
import time
from datetime import datetime, timezone

from pilosa_tpu import config as config_mod
from pilosa_tpu.ops import roaring
from pilosa_tpu.ops.bitplane import SLICE_WIDTH

# reference: pilosa.go:107-108
TIME_FORMAT = "%Y-%m-%dT%H:%M"


class CommandError(RuntimeError):
    pass


def _client(host: str):
    from pilosa_tpu.net.client import InternalClient

    return InternalClient(host, timeout=60.0)


def _out(args, attr="output_file"):
    path = getattr(args, attr, "") or ""
    if path:
        return open(path, "wb")
    return sys.stdout.buffer


# ---------------------------------------------------------------------------
# server (reference: server/server.go:49-203)
# ---------------------------------------------------------------------------


def build_server(cfg: config_mod.Config):
    """Config -> wired Server (the reference's SetupServer)."""
    from pilosa_tpu.cluster import broadcast as bc
    from pilosa_tpu.cluster.topology import Cluster
    from pilosa_tpu.net.server import Server
    from pilosa_tpu.obs.stats import new_stats_client

    if cfg.tpu.mesh_shape:
        os.environ["PILOSA_TPU_MESH_SHAPE"] = cfg.tpu.mesh_shape


    # Logging: log-path file or stderr (reference: server/server.go:125-133).
    if cfg.log_path:
        log_file = open(os.path.expanduser(cfg.log_path), "a", buffering=1)

        def logger(msg: str) -> None:
            log_file.write(msg.rstrip() + "\n")
    else:

        def logger(msg: str) -> None:
            print(msg, file=sys.stderr)

    cluster = Cluster(
        replica_n=cfg.cluster.replicas,
        long_query_time=cfg.cluster.long_query_time,
    )
    for host in cfg.cluster.hosts:
        cluster.add_node(host)

    stats = new_stats_client(cfg.metrics.service, cfg.metrics.host)
    broadcaster = bc.NopBroadcaster()
    receiver = bc.NopBroadcastReceiver()
    if cfg.cluster.type == "http":
        peers = [h for h in cfg.cluster.internal_hosts]
        broadcaster = bc.HTTPBroadcaster(peers)
        bind = cfg.host.split(":")[0] or "0.0.0.0"
        receiver = bc.HTTPBroadcastReceiver(bind, cfg.cluster.internal_port)
    elif cfg.cluster.type == "gossip":
        from pilosa_tpu.cluster.gossip import GossipNodeSet

        nodeset = GossipNodeSet(
            host=cfg.host,
            seed=cfg.cluster.gossip_seed,
            logger=logger,
            stats=stats,
            ack_timeout=cfg.gossip.ack_timeout_ms / 1000.0,
            stream_timeout=cfg.gossip.stream_timeout_ms / 1000.0,
        )
        broadcaster = nodeset
        receiver = nodeset
        cluster.node_set = nodeset

    return Server(
        data_dir=os.path.expanduser(cfg.data_dir),
        host=cfg.host,
        cluster=cluster,
        broadcaster=broadcaster,
        broadcast_receiver=receiver,
        anti_entropy_interval=cfg.anti_entropy_interval,
        polling_interval=cfg.cluster.polling_interval,
        max_writes_per_request=cfg.max_writes_per_request,
        logger=logger,
        stats=stats,
        compilation_cache_dir=cfg.tpu.compilation_cache_dir,
        prewarm=cfg.tpu.prewarm,
        stream_chunk_bytes=cfg.net.stream_chunk_bytes,
        slow_query_ms=cfg.obs.slow_query_ms,
        trace_ring=cfg.obs.trace_ring,
        latency_buckets_ms=(cfg.obs.latency_buckets_ms or None),
        slo_ms=cfg.obs.slo_ms,
        slo_objective=cfg.obs.slo_objective,
        mesh_devices=cfg.device.mesh_devices,
        hbm_budget_bytes=cfg.device.hbm_budget_bytes,
        device_prefetch=cfg.device.prefetch,
        device_stage=cfg.device.stage,
        stage_throttle_ms=cfg.device.stage_throttle_ms,
        launch_watchdog_ms=cfg.device.launch_watchdog_ms,
        quarantine_threshold=cfg.device.quarantine_threshold,
        quarantine_open_ms=cfg.device.quarantine_open_ms,
        quarantine_probe_successes=cfg.device.quarantine_probe_successes,
        plane_format=cfg.device.plane_format,
        plane_sparse_max_bytes=cfg.device.plane_sparse_max_bytes,
        plane_rle_max_bytes=cfg.device.plane_rle_max_bytes,
        coalesce=cfg.exec.coalesce,
        coalesce_max_batch=cfg.exec.coalesce_max_batch,
        coalesce_max_wait_us=cfg.exec.coalesce_max_wait_us,
        fuse=cfg.exec.fuse,
        fuse_max_programs=cfg.exec.fuse_max_programs,
        query_timeout_ms=cfg.net.query_timeout_ms,
        broadcast_timeout_ms=cfg.net.broadcast_timeout_ms,
        retry_attempts=cfg.net.retry_attempts,
        retry_backoff_ms=cfg.net.retry_backoff_ms,
        breaker_failure_threshold=cfg.net.breaker_failure_threshold,
        breaker_open_ms=cfg.net.breaker_open_ms,
        admission=cfg.net.admission,
        admission_point_concurrency=cfg.net.admission_point_concurrency,
        admission_heavy_concurrency=cfg.net.admission_heavy_concurrency,
        admission_write_concurrency=cfg.net.admission_write_concurrency,
        admission_internal_concurrency=cfg.net.admission_internal_concurrency,
        admission_queue_depth=cfg.net.admission_queue_depth,
        admission_subscribe_concurrency=cfg.net.admission_subscribe_concurrency,
        tenants=cfg.net.tenants,
        tenant_keys=cfg.net.tenant_keys,
        tenant_default=cfg.net.tenant_default,
        tenant_internal_token=cfg.net.tenant_internal_token,
        rebalance_throttle_mbps=cfg.cluster.rebalance_throttle_mbps,
        rebalance_verify_rounds=cfg.cluster.rebalance_verify_rounds,
        rebalance_delta_cap=cfg.cluster.rebalance_delta_cap,
        rebalance_release_delay_ms=cfg.cluster.rebalance_release_delay_ms,
        rebalance_on_join=cfg.cluster.rebalance_on_join,
        write_consistency=cfg.cluster.write_consistency,
        read_consistency=cfg.cluster.read_consistency,
        hint_cap=cfg.cluster.hint_cap,
        hint_replay_throttle_mbps=cfg.cluster.hint_replay_throttle_mbps,
        tier_store=cfg.tier.store,
        tier_hydrate_throttle_mbps=cfg.tier.hydrate_throttle_mbps,
        tier_disk_budget_bytes=cfg.tier.disk_budget_bytes,
        tier_retention_age_s=cfg.tier.retention_age_s,
        tier_retention_delete_s=cfg.tier.retention_delete_s,
        tier_sweep_interval_s=cfg.tier.sweep_interval_s,
        subscribe_enabled=cfg.subscribe.enabled,
        subscribe_max_subscriptions=cfg.subscribe.max_subscriptions,
        subscribe_queue_cap=cfg.subscribe.queue_cap,
        subscribe_delta_cap=cfg.subscribe.delta_cap,
        subscribe_coalesce_ms=cfg.subscribe.coalesce_ms,
        subscribe_refresh_ms=cfg.subscribe.refresh_interval_ms,
        ingest_wal=cfg.ingest.wal,
        ingest_group_commit_ms=cfg.ingest.group_commit_ms,
        ingest_group_commit_max=cfg.ingest.group_commit_max,
        ingest_scatter=cfg.ingest.scatter,
        ingest_wal_segment_bytes=cfg.ingest.wal_segment_bytes,
    )


def run_server(args) -> int:
    overrides = {}
    if args.data_dir:
        overrides["data_dir"] = args.data_dir
    if args.bind:
        overrides["host"] = args.bind
    cfg = config_mod.load(args.config or None, overrides=overrides)
    server = build_server(cfg)
    if args.dry_run:
        print("dry-run: config ok", file=sys.stderr)
        return 0
    # Join a multi-host JAX process group when the launcher configured
    # one (JAX_COORDINATOR_ADDRESS etc.); after the dry-run exit — the
    # coordinator barrier blocks until all peers connect.
    from pilosa_tpu.parallel import multihost

    multihost.initialize()
    server.open()
    print(f"listening on http://{server.host}", file=sys.stderr)
    stop_profile = _start_cpu_profile(
        getattr(args, "cpuprofile", ""), getattr(args, "cputime", 30)
    )
    # SIGTERM must run the shutdown path (close listeners, flush caches,
    # finalize --cpuprofile), not hard-kill the process.
    import signal

    def _on_term(_sig, _frame):
        raise SystemExit(0)

    signal.signal(signal.SIGTERM, _on_term)
    try:
        while True:
            time.sleep(3600)
    except (KeyboardInterrupt, SystemExit):
        pass
    finally:
        # A second TERM during cleanup must not abort server.close();
        # restore the default disposition so it hard-kills instead of
        # raising mid-finally.
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        stop_profile()
        server.close()
    return 0


def _start_cpu_profile(path: str, seconds: int):
    """Server-side CPU profiling flags (reference: server/server.go:56-57
    cpuprofile/cputime): run the same folded-stack sampler the
    /debug/pprof/profile endpoint uses, in a daemon thread, writing to
    ``path`` when sampling ends (the --cputime deadline, or shutdown for
    ``seconds == 0``).  Returns a callable that finalizes the file (a
    no-op when profiling is off)."""
    if not path:
        return lambda: None
    import threading

    from pilosa_tpu.net import handler as _handler

    stop = threading.Event()
    # Shared with the sampler thread, which accumulates in place — the
    # stop path can write a snapshot even if the thread is wedged.
    counts: dict[str, int] = {}

    def _write() -> None:
        # dict(counts) is a single C-level copy under the GIL, safe even
        # if the sampler thread is still inserting keys.
        with open(path, "w") as f:
            f.write(_handler._fold_counts(dict(counts)))
        print(f"cpu profile written to {path}", file=sys.stderr)

    def _run() -> None:
        if seconds > 0:
            _handler._sample_cpu_counts(seconds, stop=stop, counts=counts)
        else:
            # "until shutdown", literally: re-arm in bounded legs.
            while not stop.is_set():
                _handler._sample_cpu_counts(3600, stop=stop, counts=counts)
        _write()

    t = threading.Thread(target=_run, daemon=True, name="cpuprofile")
    t.start()

    def _stop() -> None:
        stop.set()
        t.join(timeout=30)
        if t.is_alive():
            print(
                "warning: cpu profiler did not stop; writing snapshot",
                file=sys.stderr,
            )
            try:
                _write()
            except OSError as e:  # never abort the shutdown path
                print(f"warning: cpu profile write failed: {e}", file=sys.stderr)

    return _stop


def run_warm(args) -> int:
    """Offline compile warm-up: populate the persistent XLA compile
    cache with the standard query-shape programs AND the coalescer's
    power-of-two bucket shapes, so a subsequently started server (or
    the next process on this machine) answers its first queries — and
    its first coalesced batches — without a multi-second cold compile.
    Honors the config's `[tpu] compilation-cache-dir` resolution; the
    warm is wasted (in-process only) when the cache is disabled, which
    is reported."""
    from pilosa_tpu.exec import warmup

    cfg = config_mod.load(args.config or None)
    cache_dir = warmup.enable_compile_cache(cfg.tpu.compilation_cache_dir)
    if cache_dir is not None:
        print(f"compilation cache: {cache_dir}", file=sys.stderr)
    else:
        print(
            "warning: persistent compile cache disabled; warming only "
            "this process's in-memory jit cache",
            file=sys.stderr,
        )
    t0 = time.monotonic()
    n = warmup.prewarm(coalesce=cfg.exec.coalesce)
    if not cfg.exec.coalesce:
        print(
            "note: [exec] coalesce is off; coalescer buckets not warmed",
            file=sys.stderr,
        )
    print(
        f"warmed {n} query programs in {time.monotonic() - t0:.1f}s",
        file=sys.stderr,
    )
    return 0


# ---------------------------------------------------------------------------
# import (reference: ctl/import.go:30-195)
# ---------------------------------------------------------------------------


def run_import(args) -> int:
    client = _client(args.host)
    for path in args.paths:
        if getattr(args, "value", ""):
            _import_value_path(client, args, path)
        else:
            _import_path(client, args, path)
    return 0


def _import_value_path(client, args, path: str) -> None:
    """``--value FIELD``: CSV records are ``column,value`` (signed
    integers), imported columnar into a BSI field via /import-value."""
    if path == "-":
        _import_value_reader(client, args, sys.stdin)
        return
    with open(path, newline="") as f:
        _import_value_reader(client, args, f)


def _import_value_reader(client, args, f) -> None:
    buf: list[tuple[int, int]] = []
    for rnum, record in enumerate(csv.reader(f), start=1):
        if not record or record[0] == "":
            continue
        if len(record) < 2:
            raise CommandError(f"bad column count on row {rnum}")
        try:
            col_id = int(record[0])
        except ValueError:
            raise CommandError(f"invalid column id on row {rnum}: {record[0]!r}") from None
        try:
            value = int(record[1])
        except ValueError:
            raise CommandError(f"invalid value on row {rnum}: {record[1]!r}") from None
        buf.append((col_id, value))
        if len(buf) >= args.buffer_size:
            _flush_values(client, args, buf)
            buf.clear()
    _flush_values(client, args, buf)


def _flush_values(client, args, pairs: list[tuple[int, int]]) -> None:
    if not pairs:
        return
    by_slice: dict[int, list] = {}
    for col, val in pairs:
        by_slice.setdefault(col // SLICE_WIDTH, []).append((col, val))
    for slice_i in sorted(by_slice):
        group = by_slice[slice_i]
        print(
            f"importing values: slice={slice_i}, n={len(group)}",
            file=sys.stderr,
        )
        client.import_value(
            args.index,
            args.frame,
            args.value,
            slice_i,
            [c for c, _ in group],
            [v for _, v in group],
            consistency=getattr(args, "consistency", "quorum"),
        )


# Native CSV fast path reads the file in blocks of this many bytes, so
# memory stays bounded regardless of file size.
_CSV_BLOCK = 64 << 20


def _import_path(client, args, path: str) -> None:
    if path == "-":
        _import_reader(client, args, sys.stdin)
        return
    # Fast path: the native CSV parser handles plain "row,col" files,
    # streamed block-by-block (split at the last newline); anything it
    # can't parse (timestamps, quoting) falls back to Python csv.  A
    # fallback after a partially imported file is safe: imports are
    # idempotent bit-sets, so re-importing earlier records is a no-op.
    if _import_native(client, args, path):
        return
    with open(path, newline="") as f:
        _import_reader(client, args, f)


def _import_native(client, args, path: str) -> bool:
    from pilosa_tpu import native

    if not native.available():
        return False
    with open(path, "rb") as fb:
        carry = b""
        while True:
            block = fb.read(_CSV_BLOCK)
            if not block:
                break
            block = carry + block
            cut = block.rfind(b"\n") + 1
            if cut == 0:
                carry, block = b"", block  # no newline: final partial line
            else:
                carry, block = block[cut:], block[:cut]
            if not _import_parsed_block(client, args, block):
                return False
        if carry and not _import_parsed_block(client, args, carry):
            return False
    return True


def _import_parsed_block(client, args, block: bytes) -> bool:
    from pilosa_tpu import native

    if not block:
        return True
    parsed = native.parse_csv(block)
    if parsed is None:
        return False
    rows, cols = parsed
    import numpy as np

    from pilosa_tpu.ops.bitplane import np_group_by

    # Fully vectorized: one stable sort groups by slice (no per-bit
    # Python objects, no per-slice full-array rescans), shipped to the
    # client in buffer_size chunks so request payloads stay bounded.
    slices = cols // np.uint64(SLICE_WIDTH)
    for s, (r_s, c_s) in np_group_by(slices, rows, cols):
        print(f"importing slice: {s}, n={len(r_s)}", file=sys.stderr)
        for lo in range(0, len(r_s), args.buffer_size):
            client.import_bits(
                args.index,
                args.frame,
                s,
                (r_s[lo : lo + args.buffer_size], c_s[lo : lo + args.buffer_size]),
                consistency=getattr(args, "consistency", "quorum"),
            )
    return True


def _import_reader(client, args, f) -> None:
    buf: list[tuple[int, int, int]] = []
    for rnum, record in enumerate(csv.reader(f), start=1):
        if not record or record[0] == "":
            continue
        if len(record) < 2:
            raise CommandError(f"bad column count on row {rnum}")
        try:
            row_id = int(record[0])
        except ValueError:
            raise CommandError(f"invalid row id on row {rnum}: {record[0]!r}") from None
        try:
            col_id = int(record[1])
        except ValueError:
            raise CommandError(f"invalid column id on row {rnum}: {record[1]!r}") from None
        ts = 0
        if len(record) > 2 and record[2]:
            try:
                dt = datetime.strptime(record[2], TIME_FORMAT)
            except ValueError:
                raise CommandError(
                    f"invalid timestamp on row {rnum}: {record[2]!r}"
                ) from None
            # wire carries unix nanoseconds (reference: ctl/import.go:157)
            ts = int(dt.replace(tzinfo=timezone.utc).timestamp() * 1e9)
        buf.append((row_id, col_id, ts))
        if len(buf) >= args.buffer_size:
            _flush_bits(client, args, buf)
            buf.clear()
    _flush_bits(client, args, buf)


def _flush_bits(client, args, bits: list[tuple[int, int, int]]) -> None:
    if not bits:
        return
    by_slice: dict[int, list] = {}
    for b in bits:
        by_slice.setdefault(b[1] // SLICE_WIDTH, []).append(b)
    for slice_i in sorted(by_slice):
        print(
            f"importing slice: {slice_i}, n={len(by_slice[slice_i])}",
            file=sys.stderr,
        )
        client.import_bits(
            args.index,
            args.frame,
            slice_i,
            by_slice[slice_i],
            consistency=getattr(args, "consistency", "quorum"),
        )


# ---------------------------------------------------------------------------
# export / backup / restore (reference: ctl/export.go, backup.go, restore.go)
# ---------------------------------------------------------------------------


def run_export(args) -> int:
    client = _client(args.host)
    w = _out(args)
    try:
        max_slices = client.max_slice_by_index()
        for slice_i in range(max_slices.get(args.index, 0) + 1):
            # Chunked end to end: the server streams csv_chunks and
            # export_to copies constant-size chunks straight into the
            # output file — no slice is ever held whole.
            client.export_to(w, args.index, args.frame, args.view, slice_i)
    finally:
        if w is not sys.stdout.buffer:
            w.close()
    return 0


def run_backup(args) -> int:
    client = _client(args.host)
    if getattr(args, "store", ""):
        return _backup_to_store(client, args)
    if not args.frame:
        raise CommandError("--frame required (unless backing up --store)")
    w = _out(args)
    try:
        client.backup_to(w, args.index, args.frame, args.view)
    finally:
        if w is not sys.stdout.buffer:
            w.close()
    return 0


def _backup_to_store(client, args) -> int:
    """``backup --store URL``: archive the server's schema plus every
    fragment tar of the view into the object store (the tier layout —
    ``schema.json`` + ``fragments/<index>/<frame>/<view>/<slice>.tar``)
    so a node with only ``[tier] store`` configured cold-boots the
    index from the store alone."""
    import json as _json

    from pilosa_tpu.tier import fragment_store_key, open_store
    from pilosa_tpu.tier.manager import SCHEMA_KEY

    store = open_store(args.store)
    if store is None:
        raise CommandError("--store must name a store location")
    schema = client.schema()
    store.put(SCHEMA_KEY, _json.dumps({"indexes": schema}).encode())
    frames = (
        [args.frame]
        if args.frame
        else [
            f["name"]
            for idx in schema
            if idx["name"] == args.index
            for f in idx.get("frames", [])
        ]
    )
    n = 0
    for frame in frames:
        views = (
            [args.view] if args.view else client.frame_views(args.index, frame)
        )
        for view in views:
            max_slices = client.max_slice_by_index(
                inverse=view.startswith("inverse")
            )
            for slice_i in range(max_slices.get(args.index, 0) + 1):
                payload = client.backup_slice(args.index, frame, view, slice_i)
                if payload is None:
                    continue
                store.put(
                    fragment_store_key(args.index, frame, view, slice_i),
                    payload,
                )
                n += 1
    print(f"backed up {n} fragment(s) to {store.url}", file=sys.stderr)
    return 0


def run_restore(args) -> int:
    client = _client(args.host)
    if getattr(args, "store", ""):
        return _restore_from_store(client, args)
    if not args.input_file:
        raise CommandError("--input-file (or --store) required")
    if not args.frame:
        raise CommandError("--frame required (unless restoring --store)")
    with open(args.input_file, "rb") as r:
        client.restore_from(r, args.index, args.frame, args.view)
    return 0


def _restore_from_store(client, args) -> int:
    """``restore --store URL``: push every matching fragment tar from
    the object store into the server (its restore endpoint verifies
    the tar's embedded checksums before installing)."""
    import io as _io

    from pilosa_tpu.tier import open_store, parse_fragment_store_key
    from pilosa_tpu.tier.manager import FRAGMENT_PREFIX

    store = open_store(args.store)
    if store is None:
        raise CommandError("--store must name a store location")
    prefix = f"{FRAGMENT_PREFIX}{args.index}/"
    if args.frame:
        prefix += f"{args.frame}/"
        if args.view:
            prefix += f"{args.view}/"
    n = 0
    for meta in store.list(prefix):
        parsed = parse_fragment_store_key(meta.key)
        if parsed is None:
            continue
        index, frame, view, slice_i = parsed
        client.restore_slice_from(
            index, frame, view, slice_i, _io.BytesIO(store.get(meta.key))
        )
        n += 1
    if n == 0:
        raise CommandError(f"store holds no fragments under {prefix!r}")
    print(f"restored {n} fragment(s) from {store.url}", file=sys.stderr)
    return 0


# ---------------------------------------------------------------------------
# check / inspect (reference: ctl/check.go:46-125, ctl/inspect.go)
# ---------------------------------------------------------------------------


def _map_or_read(f):
    """mmap a data file for O(file) checks without heap-copying it
    (reference: ctl/check.go mmaps before roaring.Check); empty files
    (not mmap-able) read as bytes."""
    import mmap as _mmap

    try:
        return _mmap.mmap(f.fileno(), 0, access=_mmap.ACCESS_READ)
    except (ValueError, OSError):
        return f.read()


def run_check(args) -> int:
    """Offline consistency check of roaring data files; skips .cache and
    .snapshotting files like the reference."""
    ok = True
    for path in args.paths:
        if path.endswith(".cache") or path.endswith(".snapshotting"):
            print(f"skipping: {path}", file=sys.stderr)
            continue
        with open(path, "rb") as f:
            data = _map_or_read(f)
        try:
            problems = roaring.check(data)
        except roaring.CorruptError as e:
            problems = [str(e)]
        if problems:
            ok = False
            for p in problems:
                print(f"{path}: {p}")
        else:
            print(f"{path}: ok", file=sys.stderr)
    return 0 if ok else 1


def run_inspect(args) -> int:
    for path in args.paths:
        with open(path, "rb") as f:
            data = _map_or_read(f)
        bi = roaring.info(data)
        print(f"{path}:")
        print(f"  containers: {len(bi.containers)}")
        print(f"  bits: {sum(c.n for c in bi.containers)}")
        print(f"  ops: {bi.ops}")
        for c in bi.containers:
            print(f"  container key={c.key} type={c.type} n={c.n}")
    return 0


# ---------------------------------------------------------------------------
# bench (reference: ctl/bench.go:52-102)
# ---------------------------------------------------------------------------


def run_bench(args) -> int:
    import random

    client = _client(args.host)
    if args.operation == "set-bit":
        n = args.num
        if n <= 0:
            raise CommandError("--num must be > 0")
        # Mirror of the reference's random set-bit workload
        # (reference: ctl/bench.go:70-102): rowID in [0,1000), columnID in
        # [0,100000).
        t0 = time.monotonic()
        batch = []
        for _ in range(n):
            row = random.randrange(1000)
            col = random.randrange(100000)
            batch.append(f'SetBit(frame="{args.frame}", rowID={row}, columnID={col})')
            if len(batch) == 1000:
                client.execute_query(args.index, "\n".join(batch))
                batch.clear()
        if batch:
            client.execute_query(args.index, "\n".join(batch))
        elapsed = time.monotonic() - t0
        print(f"executed {n} operations in {elapsed:.3f}s ({n / elapsed:.0f} op/sec)")
        return 0

    # Read-query benches over EXISTING data (BASELINE.json configs[1-2]):
    # p50/p95 over --num iterations (default 20) of one PQL query.
    if args.operation == "intersect-count":
        pql = (
            f'Count(Intersect(Bitmap(frame="{args.frame}", rowID={args.row1}),'
            f' Bitmap(frame="{args.frame}", rowID={args.row2})))'
        )
    else:  # topn
        pql = f'TopN(frame="{args.frame}", n={args.topn_n})'
    iters = args.num if args.num > 0 else 20
    result = client.execute_pql(args.index, pql)  # warm (compile/caches)
    lat = []
    for _ in range(iters):
        t0 = time.monotonic()
        result = client.execute_pql(args.index, pql)
        lat.append(time.monotonic() - t0)
    lat.sort()
    p50 = lat[len(lat) // 2]
    p95 = lat[min(len(lat) - 1, int(len(lat) * 0.95))]
    shown = result if isinstance(result, int) else f"{len(result)} pairs"
    print(
        f"{args.operation}: {iters} queries, p50 {p50*1e3:.2f} ms,"
        f" p95 {p95*1e3:.2f} ms (result: {shown})"
    )
    return 0


# ---------------------------------------------------------------------------
# resize — live cluster grow/drain (pilosa_tpu/rebalance)
# ---------------------------------------------------------------------------


def run_resize(args) -> int:
    """Drive a live topology change: POST /cluster/resize with the
    complete target host list (grow = current + joiners, drain =
    current - leavers), then optionally poll /debug/rebalance until the
    background migration completes."""
    import json as _json

    client = _client(args.host)

    def status() -> dict:
        st, data = client._request("GET", "/debug/rebalance")
        return _json.loads(client._check(st, data))

    if args.status:
        print(_json.dumps(status(), indent=2, sort_keys=True))
        return 0
    if args.abort:
        st, data = client._request("POST", "/cluster/resize/abort")
        client._check(st, data)
        print("resize aborted", file=sys.stderr)
        return 0
    hosts = [h.strip() for h in (args.hosts or "").split(",") if h.strip()]
    if not hosts:
        raise CommandError("--hosts required (the complete target host list)")
    st, data = client._request(
        "POST", "/cluster/resize", body=_json.dumps({"hosts": hosts}).encode()
    )
    client._check(st, data)
    print(f"resize to {hosts} started", file=sys.stderr)
    if not args.wait:
        print("poll with: pilosa-tpu resize --status", file=sys.stderr)
        return 0
    while True:
        snap = status()
        if not snap.get("running"):
            coord = snap.get("coordinator") or {}
            if coord.get("error") or snap.get("lastError"):
                raise CommandError(
                    f"migration stopped: {coord.get('error') or snap['lastError']}"
                )
            if snap.get("transition") is None:
                print("resize complete", file=sys.stderr)
                return 0
        states = (snap.get("coordinator") or {}).get("sliceStates", {})
        print(f"migrating: {states}", file=sys.stderr)
        time.sleep(1.0)


# ---------------------------------------------------------------------------
# sort (reference: ctl/sort.go)
# ---------------------------------------------------------------------------


def run_sort(args) -> int:
    if args.path == "-":
        rows = list(csv.reader(sys.stdin))
    else:
        with open(args.path, newline="") as f:
            rows = list(csv.reader(f))
    rows = [r for r in rows if r and r[0] != ""]
    try:
        rows.sort(key=lambda r: (int(r[1]) // SLICE_WIDTH, int(r[0]), int(r[1])))
    except (ValueError, IndexError) as e:
        raise CommandError(f"bad csv row: {e}") from e
    w = csv.writer(sys.stdout)
    w.writerows(rows)
    return 0


# ---------------------------------------------------------------------------
# config / generate-config (reference: ctl/config.go, generate_config.go)
# ---------------------------------------------------------------------------


def run_config(args) -> int:
    cfg = config_mod.load(args.config or None)
    sys.stdout.write(cfg.to_toml())
    return 0


def run_generate_config(args) -> int:
    sys.stdout.write(config_mod.Config().to_toml())
    return 0
