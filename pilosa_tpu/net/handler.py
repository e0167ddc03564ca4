"""HTTP API — the full REST surface of the framework.

Route table and response shapes reproduce the reference's handler
(reference: handler.go:93-133 router, :1380-1470 codecs) so external
clients of the reference server work unchanged:

  GET    /                                  web console
  GET    /assets/{file}                     console assets
  GET    /schema | /index                   schema listing
  GET    /status /hosts /version            introspection
  GET    /slices/max                        per-index max slice (json|proto)
  GET/POST/DELETE /index/{i}                index CRUD
  POST   /index/{i}/query                   PQL execution (body = raw PQL
                                            or protobuf QueryRequest)
  PATCH  /index/{i}/time-quantum
  POST   /index/{i}/attr/diff               column-attr anti-entropy
  POST/DELETE /index/{i}/frame/{f}          frame CRUD
  PATCH  /index/{i}/frame/{f}/time-quantum
  GET    /index/{i}/frame/{f}/views
  POST   /index/{i}/frame/{f}/attr/diff     row-attr anti-entropy
  POST   /index/{i}/frame/{f}/restore       pull frame from another cluster
  POST   /import                            protobuf bulk import
  GET    /export                            CSV fragment export
  GET    /fragment/nodes                    owners of a slice
  GET/POST /fragment/data                   fragment tar backup/restore
  GET    /fragment/blocks /fragment/block/data   sync checksums / block dump
  GET    /debug/vars /debug/pprof/          expvar metrics / profiling info
  GET    /debug/hbm                         HBM residency (budget/resident/pinned)

The handler itself is transport-independent: ``Handler.dispatch`` maps a
parsed request to a ``Response``; ``serve`` mounts it on a stdlib
ThreadingHTTPServer (the reference rides net/http + gorilla/mux).
"""

from __future__ import annotations

import base64
import io
import json
import os
import re
import shutil
import sys
import tarfile
import tempfile
import threading
import time
import traceback
import urllib.parse
from dataclasses import dataclass, field
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from collections.abc import Callable, Iterable
from typing import Any

import numpy as np

from pilosa_tpu import __version__
from pilosa_tpu import stream as stream_mod
from pilosa_tpu.core import attr as attr_mod
from pilosa_tpu.core import timequantum as tq
from pilosa_tpu.core.bitmap import RowBitmap
from pilosa_tpu.exec import plan as plan_mod
from pilosa_tpu.exec.executor import (
    ExecOptions,
    ExecutorError,
    TooManyWritesError,
)
from pilosa_tpu.net import admission as adm
from pilosa_tpu.net import codec
from pilosa_tpu.net import resilience as rz
from pilosa_tpu.net import wire_pb2 as wire
from pilosa_tpu.obs import perf as perf_mod
from pilosa_tpu.obs import prom, trace
from pilosa_tpu.pql.parser import ParseError, parse_string
from pilosa_tpu.replicate import quorum as replicate_mod
from pilosa_tpu.subscribe import registry as subscribe_reg
from pilosa_tpu.subscribe import sse as sse_mod
from pilosa_tpu.testing import faults

PROTOBUF = "application/x-protobuf"
JSON = "application/json"


@dataclass
class Request:
    method: str
    path: str
    query: dict[str, str] = field(default_factory=dict)
    headers: dict[str, str] = field(default_factory=dict)
    body: bytes = b""
    # Incremental body source (file-like with read(n)); set by the HTTP
    # adapter instead of materializing the payload.  Routes marked
    # @stream_body consume it directly; everyone else gets ``body``
    # materialized by dispatch.
    stream: Any = None

    def header(self, key: str) -> str:
        return self.headers.get(key.lower(), "")

    def body_reader(self):
        """The body as a file object — the pending stream when one
        exists, else the materialized bytes."""
        return self.stream if self.stream is not None else io.BytesIO(self.body)

    def read_body(self) -> bytes:
        """Materialize (and cache) the body."""
        if self.stream is not None:
            self.body = self.stream.read()
            self.stream = None
        return self.body


def stream_body(fn):
    """Mark a route handler as consuming ``Request.stream`` itself —
    dispatch will not materialize the body first."""
    fn.streams_body = True
    return fn


def _route_template(pattern: str) -> str:
    """Route regex -> bounded metric label: named groups become
    ``{name}`` placeholders (``/index/(?P<index>[^/]+)/query`` ->
    ``/index/{index}/query``), so the HTTP latency histogram's ``path``
    label set is the route table, never raw request paths."""
    tmpl = re.sub(r"\(\?P<(\w+)>[^)]*\)", r"{\1}", pattern)
    return tmpl.replace("?", "") or "/"


@dataclass
class Response:
    status: int = 200
    body: bytes = b""
    content_type: str = JSON
    # Iterator body: when set, the HTTP adapter streams it with chunked
    # transfer encoding and constant-size writes instead of sending
    # ``body`` with a Content-Length.
    body_iter: Iterable[bytes] | None = None
    # Extra response headers (trace span export, etc.).
    headers: dict[str, str] = field(default_factory=dict)

    @classmethod
    def stream(
        cls, chunks: Iterable[bytes], content_type: str, chunk_bytes: int = 0
    ) -> "Response":
        return cls(
            body_iter=stream_mod.IterBody(chunks, chunk_bytes=chunk_bytes),
            content_type=content_type,
        )

    @classmethod
    def json(cls, obj: Any, status: int = 200) -> "Response":
        return cls(status=status, body=(json.dumps(obj) + "\n").encode())

    @classmethod
    def proto(cls, msg, status: int = 200) -> "Response":
        return cls(status=status, body=msg.SerializeToString(), content_type=PROTOBUF)

    @classmethod
    def error(cls, message: str, status: int) -> "Response":
        # reference uses http.Error (text/plain); we keep a JSON body and
        # the same status codes.
        return cls.json({"error": message}, status=status)


class Handler:
    """Routes requests to the holder/executor/cluster underneath."""

    def __init__(
        self,
        holder=None,
        executor=None,
        cluster=None,
        broadcaster=None,
        client_factory=None,
        version: str = __version__,
        logger=None,
        stats=None,
        stream_chunk_bytes: int = 0,
        tracer=None,
        slow_query_ms: float = 0.0,
        resilience=None,
        admission=None,
        tenants=None,
        rebalance=None,
        tier=None,
        replication=None,
        latency_buckets_ms=None,
        slo_ms: float = 0.0,
        slo_objective: float = 0.999,
    ):
        self.holder = holder
        self.executor = executor
        self.cluster = cluster
        self.broadcaster = broadcaster
        self.client_factory = client_factory
        self.version = version
        self.logger = logger or (lambda msg: print(msg, file=sys.stderr))
        self.stats = stats
        # Query-path tracing (obs/trace.py): always-on when a Tracer is
        # wired (Server does); NOP otherwise.
        self.tracer = tracer or trace.NOP_TRACER
        # Structured slow-query log threshold in ms ([obs] slow-query-ms);
        # 0 disables.  Distinct from cluster.long-query-time (the
        # reference-parity plain-text log below).
        self.slow_query_ms = slow_query_ms
        # Resilience bundle (net/resilience.py): supplies the default
        # query deadline and the breaker registry behind
        # GET /debug/health.  None = no deadlines, no health detail.
        self.resilience = resilience
        # Admission control (net/admission.py): per-cost-class
        # concurrency gates + bounded queues in front of the executor.
        # A request the node cannot serve within its deadline answers
        # 429 + Retry-After BEFORE any coalescer/device work.  None =
        # admit everything (bare handler / tests).
        self.admission = admission
        # Tenant QoS (net/admission.py TenantRegistry): API-key ->
        # tenant resolution, internal-lane token verification, and the
        # per-tenant table behind GET /debug/tenants.  None = every
        # request rides the default tenant and the internal lane is
        # open (bare handler / tests).
        self.tenants = tenants
        # Elastic-cluster rebalancer (pilosa_tpu/rebalance): topology
        # events, resize coordination, delta-log/copy/release
        # endpoints, /debug/rebalance.  None = static cluster surface
        # (the endpoints answer 501).
        self.rebalance = rebalance
        # Tiered storage (pilosa_tpu/tier): the TierManager behind
        # GET /debug/tier and the store-riding rebalance restore
        # endpoint POST /tier/restore.  None = no cold tier (the
        # endpoints answer 501 / a stub document).
        self.tier = tier
        # Quorum replication (pilosa_tpu/replicate): version/hint
        # endpoints, /debug/replication, per-request consistency
        # overrides, and the X-Write-Version stamp on remote write
        # legs.  None = static single-copy surface (endpoints 501).
        self.replication = replication
        # Standing queries (pilosa_tpu/subscribe): POST /subscribe
        # registration, SSE / long-poll delivery, /debug/subscriptions.
        # Wired by the Server after the executor exists (like
        # ``executor`` itself); None = endpoints answer 501.
        self.subscribe = None
        # Staging-lane prefetcher (device/prefetch.py), wired by the
        # Server: fragments restored with ?stage=true (migration
        # arrivals) register their HBM mirrors through it.
        self.prefetcher = None
        # Durable ingest (pilosa_tpu/ingest): WAL group-commit manager,
        # wired by the Server when [ingest] wal is on.  Serves
        # GET /debug/ingest; None = WAL disabled (stub JSON).
        self.ingest = None
        # Native fixed-bucket latency histograms + SLO burn rate
        # (obs/perf.py): query latency per admission class, HTTP
        # latency per route template — rendered as Prometheus
        # histogram families on /metrics alongside the Expvar
        # summaries.
        self.latency = perf_mod.LatencyHistograms(
            buckets_ms=latency_buckets_ms,
            slo_ms=slo_ms,
            slo_objective=slo_objective,
        )
        # Base dir for /debug/profile trace tarballs, wired by the
        # Server (data dir); bare handlers fall back to a tempdir.
        self.profile_dir = None
        # The server's background prewarm thread (exec/warmup.py
        # prewarm_async), wired by the Server; GET /debug/health shows
        # its outcome.  None = prewarm off.
        self.prewarm = None
        # Single-flight guard for /debug/profile: one device trace at a
        # time, concurrent requests answer 409.
        self._profile_mu = threading.Lock()
        # Chunk size for streamed (chunked transfer encoding) bodies:
        # CSV export and fragment archives move in writes of this size.
        self.stream_chunk_bytes = stream_chunk_bytes or stream_mod.DEFAULT_CHUNK_BYTES
        # Serialized NodeStatus provider (wired by Server): serves the
        # gossip stream fallback's GET /state (the TCP push/pull analog,
        # reference: gossip/gossip.go:191-222).
        self.state_provider = None
        # (method, compiled-regex, fn) — order matters, first match wins
        # (reference: handler.go:93-133).
        self._routes: list[tuple[str, re.Pattern, Callable]] = [
            ("GET", r"/", self.handle_webui),
            ("GET", r"/assets/(?P<file>[^/]+)", self.handle_webui_asset),
            ("GET", r"/schema", self.handle_get_schema),
            ("GET", r"/status", self.handle_get_status),
            ("GET", r"/state", self.handle_get_state),
            ("GET", r"/hosts", self.handle_get_hosts),
            ("GET", r"/version", self.handle_get_version),
            ("GET", r"/slices/max", self.handle_get_slice_max),
            ("GET", r"/index", self.handle_get_indexes),
            ("GET", r"/index/(?P<index>[^/]+)", self.handle_get_index),
            ("POST", r"/index/(?P<index>[^/]+)", self.handle_post_index),
            ("DELETE", r"/index/(?P<index>[^/]+)", self.handle_delete_index),
            ("POST", r"/index/(?P<index>[^/]+)/query", self.handle_post_query),
            ("PATCH", r"/index/(?P<index>[^/]+)/time-quantum", self.handle_patch_index_time_quantum),
            ("POST", r"/index/(?P<index>[^/]+)/attr/diff", self.handle_post_index_attr_diff),
            ("POST", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)", self.handle_post_frame),
            ("DELETE", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)", self.handle_delete_frame),
            ("PATCH", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/time-quantum", self.handle_patch_frame_time_quantum),
            ("GET", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/views", self.handle_get_frame_views),
            ("POST", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/attr/diff", self.handle_post_frame_attr_diff),
            ("POST", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/restore", self.handle_post_frame_restore),
            ("GET", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/fields", self.handle_get_frame_fields),
            ("POST", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/field/(?P<fld>[^/]+)", self.handle_post_frame_field),
            ("DELETE", r"/index/(?P<index>[^/]+)/frame/(?P<frame>[^/]+)/field/(?P<fld>[^/]+)", self.handle_delete_frame_field),
            ("POST", r"/import", self.handle_post_import),
            ("POST", r"/import-value", self.handle_post_import_value),
            ("GET", r"/export", self.handle_get_export),
            ("GET", r"/fragment/nodes", self.handle_get_fragment_nodes),
            ("GET", r"/fragment/data", self.handle_get_fragment_data),
            ("POST", r"/fragment/data", self.handle_post_fragment_data),
            ("GET", r"/fragment/blocks", self.handle_get_fragment_blocks),
            ("POST", r"/fragment/import-view", self.handle_post_import_view),
            ("GET", r"/fragment/block/data", self.handle_get_fragment_block_data),
            ("POST", r"/cluster/resize", self.handle_post_resize),
            ("POST", r"/cluster/resize/abort", self.handle_post_resize_abort),
            ("POST", r"/cluster/topology", self.handle_post_topology),
            ("POST", r"/rebalance/delta", self.handle_post_rebalance_delta),
            ("POST", r"/rebalance/release", self.handle_post_rebalance_release),
            ("POST", r"/tier/restore", self.handle_post_tier_restore),
            ("POST", r"/replicate/versions", self.handle_post_replicate_versions),
            ("POST", r"/replicate/hint", self.handle_post_replicate_hint),
            ("POST", r"/replicate/replay", self.handle_post_replicate_replay),
            ("POST", r"/subscribe", self.handle_post_subscribe),
            ("GET", r"/subscribe/(?P<sid>[^/]+)/stream", self.handle_get_subscribe_stream),
            ("GET", r"/subscribe/(?P<sid>[^/]+)/poll", self.handle_get_subscribe_poll),
            ("DELETE", r"/subscribe/(?P<sid>[^/]+)", self.handle_delete_subscribe),
            ("GET", r"/debug/subscriptions", self.handle_get_subscriptions),
            ("GET", r"/debug/replication", self.handle_get_replication),
            ("GET", r"/debug/tier", self.handle_get_tier),
            ("GET", r"/debug/ingest", self.handle_get_ingest),
            ("GET", r"/debug/rebalance", self.handle_get_rebalance),
            ("GET", r"/debug/vars", self.handle_get_vars),
            ("GET", r"/debug/tenants", self.handle_get_tenants),
            ("GET", r"/debug/health", self.handle_get_health),
            ("GET", r"/debug/hbm", self.handle_get_hbm),
            ("GET", r"/debug/perf", self.handle_get_perf),
            ("GET", r"/debug/profile", self.handle_get_profile),
            ("GET", r"/debug/stacks", self.handle_get_stacks),
            ("GET", r"/debug/traces", self.handle_get_traces),
            ("GET", r"/metrics", self.handle_get_metrics),
            ("GET", r"/debug/pprof(?P<rest>/.*)?", self.handle_get_pprof),
        ]
        self._compiled = [
            (m, re.compile("^" + p + "$"), fn, _route_template(p))
            for m, p, fn in self._routes
        ]
        self._start_time = time.time()

    # ------------------------------------------------------------------
    # dispatch
    # ------------------------------------------------------------------

    def dispatch(self, req: Request) -> Response:
        t0 = time.monotonic()
        route = None  # matched route TEMPLATE (bounded label cardinality)
        try:
            # Chaos hook: the RPC-receive boundary (testing/faults.py).
            # An injected error here answers 500 — the shape of a node
            # that accepted the connection but is failing inside.
            faults.check(
                "rpc.recv",
                host=getattr(self.executor, "host", "") or None,
                path=req.path,
            )
            for method, pattern, fn, tmpl in self._compiled:
                m = pattern.match(req.path.rstrip("/") or "/")
                if m and method == req.method:
                    route = tmpl
                    if req.stream is not None and not getattr(
                        fn, "streams_body", False
                    ):
                        req.read_body()
                    resp = fn(req, **m.groupdict())
                    break
            else:
                resp = Response.error("not found", 404)
        except Exception as e:  # noqa: BLE001 — API boundary
            self.logger(f"handler error {req.method} {req.path}: {e}\n"
                        + traceback.format_exc())
            resp = Response.error(str(e), 500)
        elapsed = time.monotonic() - t0
        # Metrics and logging never drop a response, and a failing stats
        # backend must not silence the slow-query log: each observes
        # independently.
        try:
            self._observe_stats(req, elapsed, route)
        except Exception:  # noqa: BLE001
            pass
        try:
            self._observe_slow_query(req, elapsed)
        except Exception:  # noqa: BLE001
            pass
        return resp

    def _observe_stats(
        self, req: Request, elapsed: float, route: str | None = None
    ) -> None:
        if self.stats is not None:
            # per-endpoint latency histogram (reference: handler.go:140-167)
            self.stats.histogram(
                f"http.{req.method}.{req.path.split('?')[0]}", elapsed * 1000.0
            )
        if route is not None:
            # Native bucketed HTTP histogram keyed by route TEMPLATE
            # ("/index/{index}/query"), not the raw path — per-index
            # paths would be an unbounded label cardinality.
            self.latency.observe_http(req.method, route, elapsed * 1000.0)

    def _observe_slow_query(self, req: Request, elapsed: float) -> None:
        # slow-query log gated by cluster.long-query-time
        # (reference: handler.go:158-163); exact route match so frames
        # legally named "query" don't trigger it
        lqt = getattr(self.cluster, "long_query_time", 0.0) if self.cluster else 0.0
        is_query_route = req.method == "POST" and bool(
            re.match(r"^/index/[^/]+/query$", req.path)
        )
        if float(lqt) > 0 and elapsed > float(lqt) and is_query_route:
            if req.header("Content-Type") == PROTOBUF:
                try:
                    pb = wire.QueryRequest()
                    pb.ParseFromString(req.body)
                    query_text = pb.Query
                except Exception:  # noqa: BLE001 — logging only
                    query_text = "<unparseable protobuf>"
            else:
                query_text = req.body[:512].decode(errors="replace")
            self.logger(f"slow query {elapsed:.3f}s: {query_text[:512]}")

    # ------------------------------------------------------------------
    # introspection
    # ------------------------------------------------------------------

    def handle_webui(self, req: Request) -> Response:
        from pilosa_tpu.net import webui

        return Response(body=webui.INDEX_HTML.encode(), content_type="text/html")

    def handle_webui_asset(self, req: Request, file: str) -> Response:
        from pilosa_tpu.net import webui

        asset = webui.ASSETS.get(file)
        if asset is None:
            return Response.error("not found", 404)
        body, ctype = asset
        return Response(body=body.encode(), content_type=ctype)

    def handle_get_schema(self, req: Request) -> Response:
        return Response.json({"indexes": self.holder.schema()})

    def handle_get_indexes(self, req: Request) -> Response:
        return self.handle_get_schema(req)

    def handle_get_status(self, req: Request) -> Response:
        if self.cluster is not None:
            # Refresh Node.state from the membership backend (or the
            # static all-UP default) before reporting.
            self.cluster.node_states()
        status = {
            "Nodes": [
                {
                    "Host": n.host,
                    "State": n.state,
                    "Indexes": self.holder.schema() if n.host == getattr(self.executor, "host", None) else [],
                }
                for n in (self.cluster.nodes if self.cluster else [])
            ]
        }
        return Response.json({"status": status})

    def handle_get_state(self, req: Request) -> Response:
        """The node's serialized state blob (NodeStatus protobuf) — the
        gossip stream fallback pulls it here when UDP chunking stalls
        or the blob is large."""
        if self.state_provider is None:
            return Response.error("state provider not configured", 404)
        body = self.state_provider()
        return Response(body=body, content_type=PROTOBUF)

    def handle_get_hosts(self, req: Request) -> Response:
        return Response.json([n.to_dict() for n in self.cluster.nodes])

    def handle_get_version(self, req: Request) -> Response:
        return Response.json({"version": self.version})

    def handle_get_slice_max(self, req: Request) -> Response:
        inverse = req.query.get("inverse") == "true"
        ms = (
            self.holder.max_inverse_slices()
            if inverse
            else self.holder.max_slices()
        )
        if PROTOBUF in req.header("Accept"):
            pb = wire.MaxSlicesResponse()
            for k, v in ms.items():
                pb.MaxSlices[k] = v
            return Response.proto(pb)
        return Response.json({"maxSlices": ms})

    # ------------------------------------------------------------------
    # index CRUD
    # ------------------------------------------------------------------

    def handle_get_index(self, req: Request, index: str) -> Response:
        idx = self.holder.index(index)
        if idx is None:
            return Response.error("index not found", 404)
        return Response.json({"index": {"name": idx.name}})

    def handle_post_index(self, req: Request, index: str) -> Response:
        options = {}
        if req.body:
            try:
                payload = json.loads(req.body)
            except json.JSONDecodeError as e:
                return Response.error(str(e), 400)
            options = payload.get("options", {}) or {}
        kwargs = {}
        if "columnLabel" in options:
            kwargs["column_label"] = options["columnLabel"]
        if "timeQuantum" in options:
            kwargs["time_quantum"] = options["timeQuantum"]
        if self.holder.index(index) is not None:
            return Response.error("index already exists", 409)
        try:
            idx = self.holder.create_index(index, **kwargs)
        except ValueError as e:
            return Response.error(str(e), 400)
        self._broadcast(
            wire.CreateIndexMessage(
                Index=index,
                Meta=wire.IndexMeta(
                    ColumnLabel=idx.column_label, TimeQuantum=idx.time_quantum
                ),
            )
        )
        return Response.json({})

    def handle_delete_index(self, req: Request, index: str) -> Response:
        self.holder.delete_index(index)
        self._broadcast(wire.DeleteIndexMessage(Index=index))
        return Response.json({})

    def handle_patch_index_time_quantum(self, req: Request, index: str) -> Response:
        try:
            payload = json.loads(req.body)
        except json.JSONDecodeError as e:
            return Response.error(str(e), 400)
        try:
            q = tq.parse_time_quantum(payload.get("timeQuantum", ""))
        except ValueError:
            return Response.error("invalid time quantum", 400)
        idx = self.holder.index(index)
        if idx is None:
            return Response.error("index not found", 404)
        idx.set_time_quantum(q)
        return Response.json({})

    def handle_post_index_attr_diff(self, req: Request, index: str) -> Response:
        idx = self.holder.index(index)
        if idx is None:
            return Response.error("index not found", 404)
        return self._attr_diff(req, idx.column_attr_store)

    # ------------------------------------------------------------------
    # frame CRUD
    # ------------------------------------------------------------------

    def handle_post_frame(self, req: Request, index: str, frame: str) -> Response:
        idx = self.holder.index(index)
        if idx is None:
            return Response.error("index not found", 404)
        options = {}
        if req.body:
            try:
                payload = json.loads(req.body)
            except json.JSONDecodeError as e:
                return Response.error(str(e), 400)
            options = payload.get("options", {}) or {}
        kwargs = {}
        for json_key, py_key in (
            ("rowLabel", "row_label"),
            ("inverseEnabled", "inverse_enabled"),
            ("cacheType", "cache_type"),
            ("cacheSize", "cache_size"),
            ("timeQuantum", "time_quantum"),
            ("rangeEnabled", "range_enabled"),
            ("retentionAgeS", "retention_age_s"),
            ("retentionDeleteS", "retention_delete_s"),
        ):
            if json_key in options:
                kwargs[py_key] = options[json_key]
        if idx.frame(frame) is not None:
            return Response.error("frame already exists", 409)
        try:
            f = idx.create_frame(frame, **kwargs)
        except (ValueError, RuntimeError) as e:
            return Response.error(str(e), 400)
        self._broadcast(
            wire.CreateFrameMessage(
                Index=index, Frame=frame, Meta=_frame_meta_proto(f)
            )
        )
        return Response.json({})

    def handle_delete_frame(self, req: Request, index: str, frame: str) -> Response:
        idx = self.holder.index(index)
        if idx is None:
            return Response.error("index not found", 404)
        idx.delete_frame(frame)
        self._broadcast(wire.DeleteFrameMessage(Index=index, Frame=frame))
        return Response.json({})

    def handle_patch_frame_time_quantum(
        self, req: Request, index: str, frame: str
    ) -> Response:
        try:
            payload = json.loads(req.body)
        except json.JSONDecodeError as e:
            return Response.error(str(e), 400)
        try:
            q = tq.parse_time_quantum(payload.get("timeQuantum", ""))
        except ValueError:
            return Response.error("invalid time quantum", 400)
        f = self.holder.frame(index, frame)
        if f is None:
            return Response.error("frame not found", 404)
        f.set_time_quantum(q)
        return Response.json({})

    def handle_get_frame_views(self, req: Request, index: str, frame: str) -> Response:
        f = self.holder.frame(index, frame)
        if f is None:
            return Response.error("frame not found", 404)
        return Response.json({"views": sorted(f.views().keys())})

    def handle_post_frame_attr_diff(
        self, req: Request, index: str, frame: str
    ) -> Response:
        f = self.holder.frame(index, frame)
        if f is None:
            return Response.error("frame not found", 404)
        return self._attr_diff(req, f.row_attr_store)

    def handle_post_frame_restore(
        self, req: Request, index: str, frame: str
    ) -> Response:
        """Pull every slice of a frame from a remote cluster
        (reference: handler.go:1253-1341)."""
        host = req.query.get("host")
        if not host:
            return Response.error("host required", 400)
        f = self.holder.frame(index, frame)
        if f is None:
            return Response.error("frame not found", 404)
        if self.client_factory is None:
            return Response.error("no client", 500)
        client = self.client_factory(host)
        max_slices = client.max_slice_by_index()
        max_inverse = client.max_slice_by_index(inverse=True)
        for view_name in client.frame_views(index, frame):
            from pilosa_tpu.core.view import is_inverse_view

            ms = (
                max_inverse.get(index, 0)
                if is_inverse_view(view_name)
                else max_slices.get(index, 0)
            )
            for slice_i in range(ms + 1):
                view = f.create_view_if_not_exists(view_name)
                frag = view.create_fragment_if_not_exists(slice_i)
                # Stream the remote archive straight into the fragment
                # instead of materializing it first.
                src = client.stream_backup_slice(index, frame, view_name, slice_i)
                if src is None:
                    continue
                with src:
                    frag.read_from(src)
        return Response.json({})

    # ------------------------------------------------------------------
    # BSI integer fields (pilosa_tpu/bsi)
    # ------------------------------------------------------------------
    #
    # Field schema rides JSON endpoints (a pilosa_tpu extension): the
    # protobuf FrameMeta broadcast reproduces the reference wire
    # contract exactly, which predates BSI — so field create/delete fan
    # out as plain HTTP to every peer instead (``?remote=true`` marks
    # the relayed leg).  Field metadata persists in each node's frame
    # .meta and is served by /schema, so restarts recover it locally.

    def handle_get_frame_fields(self, req: Request, index: str, frame: str) -> Response:
        f = self.holder.frame(index, frame)
        if f is None:
            return Response.error("frame not found", 404)
        return Response.json(
            {"fields": [fld.to_dict() for fld in f.bsi_fields()]}
        )

    def handle_post_frame_field(
        self, req: Request, index: str, frame: str, fld: str
    ) -> Response:
        from pilosa_tpu import bsi

        f = self.holder.frame(index, frame)
        if f is None:
            return Response.error("frame not found", 404)
        try:
            payload = json.loads(req.body) if req.body else {}
        except json.JSONDecodeError as e:
            return Response.error(str(e), 400)
        try:
            lo = int(payload.get("min", 0))
            hi = int(payload.get("max", 0))
        except (TypeError, ValueError):
            return Response.error("min/max must be integers", 400)
        remote = req.query.get("remote") == "true"
        if remote and not f.range_enabled:
            # The relayed leg implies range support: the coordinator
            # validated the operator-facing schema rules.
            f.set_options(range_enabled=True)
        try:
            f.create_field(fld, lo, hi)
        except bsi.BSIError as e:
            return Response.error(str(e), 400)
        except Exception as e:  # noqa: BLE001 — duplicate / not range-enabled
            return Response.error(str(e), 409)
        if not remote:
            self._fanout_field(
                "POST",
                f"/index/{index}/frame/{frame}/field/{fld}",
                json.dumps({"min": lo, "max": hi}).encode(),
            )
        return Response.json({})

    def handle_delete_frame_field(
        self, req: Request, index: str, frame: str, fld: str
    ) -> Response:
        f = self.holder.frame(index, frame)
        if f is None:
            return Response.error("frame not found", 404)
        try:
            f.delete_field(fld)
        except Exception as e:  # noqa: BLE001 — unknown field
            return Response.error(str(e), 404)
        if req.query.get("remote") != "true":
            self._fanout_field(
                "DELETE", f"/index/{index}/frame/{frame}/field/{fld}", b""
            )
        return Response.json({})

    def _fanout_field(self, method: str, path: str, body: bytes) -> None:
        """Relay a field schema change to every other node.  Collected
        errors surface as one exception AFTER every reachable peer got
        the change — a dead peer re-converges via its own retry, not by
        aborting the survivors."""
        if self.cluster is None or self.client_factory is None:
            return
        me = getattr(self.executor, "host", None)
        errs = []
        for node in self.cluster.nodes:
            if node.host == me:
                continue
            try:
                client = self.client_factory(node.host)
                status, data = client._request(
                    method, path, query={"remote": "true"}, body=body
                )
                client._check(status, data)
            except Exception as e:  # noqa: BLE001 — collect per-host
                errs.append(f"{node.host}: {e}")
        if errs:
            raise RuntimeError("field fanout: " + "; ".join(errs))

    def handle_post_import_value(self, req: Request) -> Response:
        """Columnar integer import (JSON, a pilosa_tpu extension):
        ``{"index","frame","field","slice","columnIDs":[],"values":[]}``
        — one value per column, written as vectorized plane set+clear
        passes through Frame.import_value.  Ownership-guarded like
        /import; the client fans a slice's payload to every replica."""
        ticket, shed = self._admit(adm.CLASS_WRITE, req)
        if shed is not None:
            return shed
        try:
            return self._handle_post_import_value(req)
        finally:
            if ticket is not None:
                ticket.release()

    def _handle_post_import_value(self, req: Request) -> Response:
        try:
            payload = json.loads(req.body)
        except json.JSONDecodeError as e:
            return Response.error(str(e), 400)
        index = payload.get("index", "")
        frame = payload.get("frame", "")
        field_name = payload.get("field", "")
        slice_i = payload.get("slice", 0)
        cols = payload.get("columnIDs", [])
        vals = payload.get("values", [])
        if not isinstance(cols, list) or not isinstance(vals, list) or len(
            cols
        ) != len(vals):
            return Response.error("columnIDs/values must be equal-length lists", 400)
        if self.cluster is not None and self.executor is not None:
            # Write-ownership guard: during a rebalance transition the
            # new ring's owners accept imports too (dual-write cutover).
            if not self.cluster.is_write_owner(
                self.executor.host, index, slice_i
            ):
                return Response.error(
                    f"host does not own slice {self.executor.host}"
                    f" slice={slice_i}",
                    412,
                )
        f = self.holder.frame(index, frame)
        if f is None:
            return Response.error("frame not found", 404)
        try:
            f.import_value(field_name, cols, vals)
        except Exception as e:  # noqa: BLE001 — unknown field / out of range
            return Response.error(str(e), 400)
        return Response.json({})

    # ------------------------------------------------------------------
    # query
    # ------------------------------------------------------------------

    def handle_post_query(self, req: Request, index: str) -> Response:
        """Traced query entry: the root span opens here (continuing a
        propagated trace on the remote leg of a fan-out), the body runs
        under it, and the finalized trace feeds the ring buffer, the
        remote span export header, and the structured slow-query log."""
        in_trace = req.header(trace.TRACE_HEADER)
        root = self.tracer.start_trace(
            "query",
            trace_id=in_trace or None,
            parent_span_id=req.header(trace.SPAN_HEADER) or None,
            index=index,
            node=getattr(self.executor, "host", ""),
        )
        # Deadline: the request's X-Deadline-Ms (the remote leg of a
        # fan-out, or an external per-request override) wins over the
        # configured [net] query-timeout-ms default.  The scope rides
        # a contextvar, so every remote leg, retry sleep, and coalesce
        # wait under execute() derives its timeout from what's left.
        dl = None
        if self.resilience is not None:
            dl = self.resilience.query_deadline(req.header(rz.DEADLINE_HEADER))
        else:
            dl = rz.Deadline.from_header(req.header(rz.DEADLINE_HEADER))
        token = root.activate()
        t0 = time.monotonic()
        try:
            with rz.deadline_scope(dl):
                resp = self._handle_post_query(req, index, root)
        finally:
            root.deactivate(token)
            record = self.tracer.finish_root(root)
            # Native per-class latency histogram + SLO accounting —
            # measured here (not from the trace record, which a full
            # ring may drop) so every query observes exactly once.
            try:
                self.latency.observe_query(
                    str(root.tags.get("cost_class") or "unclassified"),
                    (time.monotonic() - t0) * 1e3,
                    tenant=str(root.tags.get("tenant") or ""),
                )
            except Exception:  # noqa: BLE001 — metrics never drop a response
                pass
        if record is not None:
            if in_trace:
                # Remote leg: ship this node's spans back to the
                # coordinator, which absorbs them into the one trace.
                resp.headers[trace.SPANS_HEADER] = self.tracer.export_payload(
                    record
                )
            elif (
                self.slow_query_ms > 0
                and record["duration_ms"] >= self.slow_query_ms
            ):
                try:
                    self._log_slow_query(index, root, record)
                except Exception:  # noqa: BLE001 — logging never drops a response
                    pass
        return resp

    def _log_slow_query(self, index: str, root, record: dict) -> None:
        """Exactly one structured line per slow coordinator query."""
        line = {
            "ms": record["duration_ms"],
            "index": index,
            "query": root.tags.get("query", ""),
            "slices": root.tags.get("slices", "all"),
            "trace_id": record["trace_id"],
            "stages": trace.stage_breakdown(record),
        }
        co = _coalesce_batch_stats(record)
        if co is not None:
            line["coalesce"] = co
        fu = _fuse_batch_stats(record)
        if fu is not None:
            line["fuse"] = fu
        self.logger("slow query " + json.dumps(line, sort_keys=True))

    def _handle_post_query(self, req: Request, index: str, root) -> Response:
        try:
            qreq = self._read_query_request(req)
        except ValueError as e:
            return self._query_error(req, str(e), 400)
        root.annotate(
            query=qreq["query"][:512],
            slices=qreq["slices"] if qreq["slices"] is not None else "all",
            remote=qreq["remote"],
        )
        try:
            with self.tracer.span("parse"):
                q = parse_string(qreq["query"])
        except Exception as e:  # parser error
            return self._query_error(req, str(e), 400)
        # Per-request consistency overrides (pilosa_tpu/replicate):
        # header wins over query param; junk is a 400, not a silent
        # default.
        try:
            write_consistency = _consistency_arg(
                req, "X-Write-Consistency", "writeConsistency"
            )
            read_consistency = _consistency_arg(
                req, "X-Read-Consistency", "readConsistency"
            )
        except ValueError as e:
            return self._query_error(req, str(e), 400)
        # Internal-lane verification (net/admission.py TenantRegistry):
        # the Remote flag earns the internal priority lane only with
        # the cluster's token (when one is configured) — a client
        # spoofing Remote is classified and charged like any other
        # client request.  The coordinator forwards the ORIGIN tenant
        # as X-Tenant on its map legs, so the fan-out is charged to
        # whoever sent the query, on every node it touches.
        internal = qreq["remote"] and self._internal_ok(req)
        tenant = self._resolve_tenant(req, internal)
        opt = ExecOptions(
            remote=qreq["remote"],
            allow_partial=(
                req.query.get("allowPartial") == "true"
                or req.header("X-Allow-Partial") in ("1", "true")
            ),
            write_consistency=write_consistency,
            read_consistency=read_consistency,
            tenant=tenant,
        )
        # Remote write legs carry the quorum coordinator's per-slice
        # version stamp (taken at the PRIMARY after its local apply).
        # Versions are pure local write counts — comparable across
        # replicas because every replica applies the same stream — so
        # the stamp is NOT merged into the clock (that would double-
        # count this very write); it is the replica's self-staleness
        # probe: applying this write should land the local counter AT
        # the stamp, and landing short means earlier writes were missed
        # (surfaced as cluster.replication.staleSelf before read-repair
        # or hint replay ever looks).
        stale_probe = None
        if qreq["remote"] and self.replication is not None:
            stamp = req.header(replicate_mod.WRITE_VERSION_HEADER)
            if stamp:
                try:
                    slice_s, _, ver_s = stamp.partition(":")
                    stale_probe = (int(slice_s), int(ver_s))
                except (TypeError, ValueError):
                    pass  # malformed stamp must not fail the write
        # Admission gate: classify from the parsed plan (remote map
        # legs ride the internal priority lane — a saturated node must
        # never starve another coordinator's fan-out behind its own
        # client queue), then admit or shed 429 BEFORE the executor,
        # coalescer, or device see the query.
        # Classified unconditionally (not only under admission): the
        # class keys the native query-latency histogram and the SLO
        # burn rate, which exist with or without admission gates.
        cls = (
            adm.CLASS_INTERNAL
            if internal
            else plan_mod.cost_class(q.calls)
        )
        root.annotate(cost_class=cls)
        if tenant:
            root.annotate(tenant=tenant)
        ticket = None
        if self.admission is not None:
            try:
                with self.tracer.span("admission", cost_class=cls) as sp:
                    ticket = self.admission.acquire(
                        cls,
                        tenant=tenant,
                        nbytes=len(req.body or b""),
                    )
                    sp.annotate(wait_ms=round(ticket.wait_ms, 3))
            except rz.ShedError as e:
                root.annotate(shed=True)
                return self._shed_response(req, e)
        try:
            rz.check_deadline("before execute")
            with self.tracer.span("execute"):
                results = self.executor.execute(index, q, qreq["slices"], opt)
        except TooManyWritesError as e:
            return self._query_error(req, str(e), 413)
        except rz.DeadlineExceeded as e:
            # 504 carries the trace id: the retained trace shows where
            # the budget went.
            root.annotate(error="DeadlineExceeded")
            trace_id = getattr(root, "trace_id", "") or "none"
            return self._query_error(req, f"{e} [trace {trace_id}]", 504)
        except Exception as e:  # noqa: BLE001 — executor boundary
            return self._query_error(req, str(e), 500)
        finally:
            if ticket is not None:
                ticket.release()

        if stale_probe is not None:
            probe_slice, probe_ver = stale_probe
            if self.replication.versions.get(index, probe_slice) < probe_ver:
                self.replication.stats.count(
                    "cluster.replication.staleSelf"
                )
                root.annotate(stale_self=True)

        column_attr_sets = None
        if qreq["column_attrs"]:
            idx = self.holder.index(index)
            column_ids: list[int] = []
            for r in results:
                if isinstance(r, RowBitmap):
                    bits = codec.bitmap_to_json(r)["bits"]
                    column_ids = sorted(set(column_ids) | set(bits))
            column_attr_sets = []
            if idx is not None:
                for cid in column_ids:
                    attrs = idx.column_attr_store.attrs(cid)
                    if attrs:
                        column_attr_sets.append((cid, attrs))

        if PROTOBUF in req.header("Accept"):
            resp = Response.proto(
                codec.response_to_proto(results, column_attr_sets)
            )
            if opt.missing_slices:
                # The wire protobuf has no partial field (reference
                # parity); internal callers read the marker off this
                # header instead.
                resp.headers["X-Missing-Slices"] = ",".join(
                    str(s) for s in opt.missing_slices
                )
            return resp
        payload = codec.response_to_json(results, column_attr_sets)
        if opt.missing_slices:
            payload["partial"] = True
            payload["missingSlices"] = opt.missing_slices
        return Response.json(payload)

    def _read_query_request(self, req: Request) -> dict:
        """reference: handler.go:863-944.

        ``time_granularity`` / ``QueryRequest.Quantum`` is VALIDATED
        (invalid values are a 400) and carried on the wire, but — by
        exact reference parity — never consumed by execution: the
        reference parses it (handler.go:913-926), decodes it from
        protobuf (handler.go:1396-1408), and then no code path reads
        ``QueryRequest.Quantum`` again; remote exec re-marshals without
        it (executor.go:1048-1052) and Range() always uses the frame's
        own quantum (executor.go:572-573).  We reproduce that contract
        verbatim rather than invent semantics the reference lacks."""
        if req.header("Content-Type") == PROTOBUF:
            pb = wire.QueryRequest()
            pb.ParseFromString(req.body)
            return {
                "query": pb.Query,
                "slices": list(pb.Slices) or None,
                "column_attrs": pb.ColumnAttrs,
                "quantum": pb.Quantum or "YMDH",
                "remote": pb.Remote,
            }
        valid = {
            "slices",
            "columnAttrs",
            "time_granularity",
            "allowPartial",
            "writeConsistency",
            "readConsistency",
        }
        for key in req.query:
            if key not in valid:
                raise ValueError("invalid query params")
        slices = None
        if req.query.get("slices"):
            try:
                slices = [int(s) for s in req.query["slices"].split(",")]
            except ValueError:
                raise ValueError("invalid slice argument") from None
        quantum = "YMDH"
        if req.query.get("time_granularity"):
            try:
                quantum = tq.parse_time_quantum(req.query["time_granularity"])
            except ValueError:
                raise ValueError("invalid time granularity") from None
        return {
            "query": req.body.decode(),
            "slices": slices,
            "column_attrs": req.query.get("columnAttrs") == "true",
            "quantum": quantum,
            "remote": False,
        }

    def _query_error(self, req: Request, message: str, status: int) -> Response:
        if PROTOBUF in req.header("Accept"):
            return Response.proto(wire.QueryResponse(Err=message), status=status)
        return Response.json({"error": message}, status=status)

    def _internal_ok(self, req: Request) -> bool:
        """May this request claim the internal lane?  Open when no
        registry / no token is configured (trusted network, every
        pre-tenant deployment); token-gated otherwise, so tenants
        cannot spoof X-Internal-Lane or the Remote flag past QoS."""
        if self.tenants is None:
            return True
        return self.tenants.internal_ok(req.header("X-Internal-Token"))

    def _resolve_tenant(self, req: Request, internal: bool = False) -> str:
        """The tenant this request is charged to.  Client traffic:
        X-Api-Key via the registry (a bare X-Tenant only for configured
        tenants).  Verified internal traffic: the coordinator's
        forwarded X-Tenant verbatim — the origin already paid admission
        at its front door and map legs must charge the same account."""
        if self.tenants is None:
            return ""
        if internal:
            return req.header("X-Tenant") or self.tenants.default_tenant
        return self.tenants.resolve(
            req.header("X-Api-Key"), req.header("X-Tenant")
        )

    def _shed_response(self, req: Request, e: rz.ShedError) -> Response:
        """429 + Retry-After: the node is healthy but at capacity, and
        the request was answered before any executor/device work.  The
        header carries whole seconds (HTTP contract, floored at 1);
        the JSON body carries the precise millisecond hint.  Quota
        sheds additionally carry X-Quota-Limit / X-Quota-Remaining so
        a well-behaved client can pace itself instead of retrying into
        the same empty bucket."""
        import math

        if PROTOBUF in req.header("Accept"):
            resp = Response.proto(wire.QueryResponse(Err=str(e)), status=429)
        else:
            body = {
                "error": str(e),
                "retryAfterMs": round(e.retry_after_s * 1000.0, 1),
            }
            if isinstance(e, adm.QuotaError):
                body["quota"] = {
                    "tenant": e.tenant,
                    "kind": e.quota_kind,
                    "limit": e.quota_limit,
                    "remaining": round(e.quota_remaining, 3),
                }
            resp = Response.json(body, status=429)
        resp.headers["Retry-After"] = str(max(1, math.ceil(e.retry_after_s)))
        if isinstance(e, adm.QuotaError):
            resp.headers["X-Quota-Limit"] = f"{e.quota_limit:g}"
            resp.headers["X-Quota-Remaining"] = f"{max(0.0, e.quota_remaining):g}"
        return resp

    def _admit(self, cls: str, req: Request):
        """Admission for non-query routes (imports, repair pushes):
        returns ``(ticket, None)`` or ``(None, 429 response)``.  The
        deadline comes straight off the request header — these routes
        run outside the query path's deadline scope.

        ``X-Internal-Lane`` reclasses the request onto the internal
        priority lane: hint replays push queued /import payloads
        through the client write route, and cluster-internal traffic
        must never starve behind (or be shed as) a client storm.  The
        reclass is token-gated like the query path's Remote flag."""
        if self.admission is None:
            return None, None
        internal = False
        if req.header("X-Internal-Lane") in ("1", "true") and (
            self._internal_ok(req)
        ):
            cls = adm.CLASS_INTERNAL
            internal = True
        tenant = self._resolve_tenant(req, internal)
        dl = rz.Deadline.from_header(req.header(rz.DEADLINE_HEADER))
        try:
            return (
                self.admission.acquire(
                    cls,
                    deadline=dl,
                    tenant=tenant,
                    nbytes=len(req.body or b""),
                ),
                None,
            )
        except rz.ShedError as e:
            return None, self._shed_response(req, e)

    # ------------------------------------------------------------------
    # import / export
    # ------------------------------------------------------------------

    def handle_post_import(self, req: Request) -> Response:
        """reference: handler.go:969-1046"""
        ticket, shed = self._admit(adm.CLASS_WRITE, req)
        if shed is not None:
            return shed
        try:
            return self._handle_post_import(req)
        finally:
            if ticket is not None:
                ticket.release()

    def _handle_post_import(self, req: Request) -> Response:
        pb = wire.ImportRequest()
        try:
            pb.ParseFromString(req.body)
        except Exception as e:  # noqa: BLE001
            return Response.error(str(e), 400)
        # Ownership guard (reference: handler.go:1004) — write-ring
        # aware: a migration target accepts imports before its cutover.
        if self.cluster is not None and self.executor is not None:
            if not self.cluster.is_write_owner(
                self.executor.host, pb.Index, pb.Slice
            ):
                return Response.error(
                    f"host does not own slice {self.executor.host}"
                    f" slice={pb.Slice}",
                    412,
                )
        f = self.holder.frame(pb.Index, pb.Frame)
        if f is None:
            return Response.error("frame not found", 404)
        timestamps = [
            None if ts == 0 else _dt_from_unix(ts) for ts in pb.Timestamps
        ] if pb.Timestamps else None
        try:
            # (fromiter: a third of asarray's time over a repeated field,
            # and a unit of a tall frame is tens of millions of bits)
            f.import_bulk(
                np.fromiter(pb.RowIDs, np.int64, len(pb.RowIDs)),
                np.fromiter(pb.ColumnIDs, np.int64, len(pb.ColumnIDs)),
                timestamps,
            )
        except Exception as e:  # noqa: BLE001
            return Response.proto(wire.ImportResponse(Err=str(e)), status=500)
        return Response.proto(wire.ImportResponse())

    def handle_post_import_view(self, req: Request) -> Response:
        """View-scoped raw sets/clears — the anti-entropy repair path
        for derived (inverse/time) views, which the PQL write fan-out
        cannot target individually (pilosa_tpu extension; the reference
        only repairs the standard view, fragment.go:1443).  Rides the
        internal admission lane: anti-entropy repair is cluster-internal
        traffic and must not starve behind a client-write storm."""
        ticket, shed = self._admit(adm.CLASS_INTERNAL, req)
        if shed is not None:
            return shed
        try:
            return self._handle_post_import_view(req)
        finally:
            if ticket is not None:
                ticket.release()

    def _handle_post_import_view(self, req: Request) -> Response:
        pb = wire.ImportViewRequest()
        try:
            pb.ParseFromString(req.body)
        except Exception as e:  # noqa: BLE001
            return Response.error(str(e), 400)
        if self.cluster is not None and self.executor is not None:
            # Write-ring aware: delta-log replay pushes land on the
            # migration target before (and after) its cutover.
            if not self.cluster.is_write_owner(
                self.executor.host, pb.Index, pb.Slice
            ):
                return Response.error(
                    f"host does not own slice {self.executor.host}"
                    f" slice={pb.Slice}",
                    412,
                )
        f = self.holder.frame(pb.Index, pb.Frame)
        if f is None:
            return Response.error("frame not found", 404)
        if len(pb.RowIDs) != len(pb.ColumnIDs) or len(pb.ClearRowIDs) != len(
            pb.ClearColumnIDs
        ):
            # zip would silently truncate a malformed pair list — reject
            # like Fragment.merge_block does on the read side.
            return Response.error("row/column id length mismatch", 400)
        try:
            view = f.create_view_if_not_exists(pb.View)
            frag = view.create_fragment_if_not_exists(pb.Slice)
            for r, c in zip(pb.RowIDs, pb.ColumnIDs):
                frag.set_bit(int(r), int(c))
            for r, c in zip(pb.ClearRowIDs, pb.ClearColumnIDs):
                frag.clear_bit(int(r), int(c))
        except Exception as e:  # noqa: BLE001
            return Response.proto(wire.ImportResponse(Err=str(e)), status=500)
        return Response.proto(wire.ImportResponse())

    def handle_get_export(self, req: Request) -> Response:
        """CSV export of one fragment (reference: handler.go:1049-1098)."""
        if "text/csv" not in req.header("Accept"):
            return Response.error("not acceptable", 406)
        index = req.query.get("index", "")
        frame = req.query.get("frame", "")
        view = req.query.get("view", "")
        try:
            slice_i = int(req.query.get("slice", ""))
        except ValueError:
            return Response.error("invalid slice", 400)
        if self.cluster is not None and self.executor is not None:
            owners = {n.host for n in self.cluster.fragment_nodes(index, slice_i)}
            if self.executor.host not in owners:
                return Response.error("host does not own slice", 412)
        frag = self.holder.fragment(index, frame, view, slice_i)
        if frag is None:
            return Response.error("fragment not found", 404)
        # Stream the CSV: csv_chunks is a row-block generator and the
        # adapter moves constant-size chunks, so the response never
        # materializes (reference: handler.go:1049-1098 writes rows
        # straight to the ResponseWriter).
        return Response.stream(
            frag.csv_chunks(), "text/csv", chunk_bytes=self.stream_chunk_bytes
        )

    # ------------------------------------------------------------------
    # fragment internals (sync/backup data plane)
    # ------------------------------------------------------------------

    def handle_get_fragment_nodes(self, req: Request) -> Response:
        """Owners of a slice.  ``?write=true`` answers the WRITE target
        set instead — during a rebalance transition that is both rings'
        owners, so import fan-outs dual-write migrating slices."""
        index = req.query.get("index", "")
        try:
            slice_i = int(req.query.get("slice", ""))
        except ValueError:
            return Response.error("invalid slice", 400)
        if req.query.get("write") == "true":
            nodes = self.cluster.write_nodes(index, slice_i)
        else:
            nodes = self.cluster.fragment_nodes(index, slice_i)
        return Response.json([n.to_dict() for n in nodes])

    def _fragment_from_query(self, req: Request):
        index = req.query.get("index", "")
        frame = req.query.get("frame", "")
        view = req.query.get("view", "")
        slice_s = req.query.get("slice", "")
        if not slice_s.isdigit():
            return None, Response.error("slice required", 400)
        frag = self.holder.fragment(index, frame, view, int(slice_s))
        if frag is None:
            return None, Response.error("fragment not found", 404)
        return frag, None

    def handle_get_fragment_data(self, req: Request) -> Response:
        frag, err = self._fragment_from_query(req)
        if err:
            return err
        # Chunked tar stream (reference: handler.go:1102-1123 hands the
        # ResponseWriter to Fragment.WriteTo).
        return Response.stream(
            frag.tar_chunks(chunk_bytes=self.stream_chunk_bytes),
            "application/octet-stream",
            chunk_bytes=self.stream_chunk_bytes,
        )

    @stream_body
    def handle_post_fragment_data(self, req: Request) -> Response:
        """Fragment restore — operator backup/restore AND the rebalance
        bulk-copy arrival path.  Rides the internal admission lane
        (cluster data-plane traffic must not starve behind a client
        write storm); ``?stage=true`` (migration arrivals) hands the
        restored fragment to the HBM staging lane so its mirror
        registers with the PlanePool in the background."""
        ticket, shed = self._admit(adm.CLASS_INTERNAL, req)
        if shed is not None:
            return shed
        try:
            index = req.query.get("index", "")
            frame = req.query.get("frame", "")
            view = req.query.get("view", "")
            slice_s = req.query.get("slice", "")
            if not slice_s.isdigit():
                return Response.error("slice required", 400)
            f = self.holder.frame(index, frame)
            if f is None:
                return Response.error("frame not found", 404)
            from pilosa_tpu.core.fragment import ArchiveChecksumError

            vw = f.create_view_if_not_exists(view)
            frag = vw.create_fragment_if_not_exists(int(slice_s))
            # The tar reader pulls straight off the request body stream;
            # payloads verify against the archive's embedded checksums
            # before anything installs (core/fragment.read_from).
            try:
                frag.read_from(req.body_reader())
            except ArchiveChecksumError as e:
                # Torn bytes rejected with a NAMED failure — the sender
                # must not believe a corrupt restore succeeded.
                return Response.error(str(e), 422)
            if req.query.get("stage") == "true" and self.prefetcher is not None:
                self.prefetcher.stage([frag])
            return Response.json({})
        finally:
            if ticket is not None:
                ticket.release()

    def handle_get_fragment_blocks(self, req: Request) -> Response:
        frag, err = self._fragment_from_query(req)
        if err:
            return err
        blocks = [
            {"id": bid, "checksum": base64.b64encode(chk).decode()}
            for bid, chk in frag.blocks()
        ]
        return Response.json({"blocks": blocks})

    def handle_get_fragment_block_data(self, req: Request) -> Response:
        """protobuf in/out (reference: handler.go:1213-1246)."""
        pb = wire.BlockDataRequest()
        try:
            pb.ParseFromString(req.body)
        except Exception as e:  # noqa: BLE001
            return Response.error(str(e), 400)
        frag = self.holder.fragment(pb.Index, pb.Frame, pb.View, pb.Slice)
        if frag is None:
            return Response.error("fragment not found", 404)
        ps = frag.block_data(pb.Block)
        out = wire.BlockDataResponse()
        out.RowIDs.extend(int(r) for r in ps.row_ids)
        out.ColumnIDs.extend(int(c) for c in ps.column_ids)
        return Response.proto(out)

    # ------------------------------------------------------------------
    # elastic cluster: resize / topology events / migration data plane
    # ------------------------------------------------------------------

    def handle_post_resize(self, req: Request) -> Response:
        """Operator entry: start (or resume) a live resize.  Body:
        ``{"hosts": ["h1:p", "h2:p", ...]}`` — the COMPLETE target host
        list (grow = current + new, drain = current - leaving).  The
        receiving node becomes the migration coordinator; progress at
        GET /debug/rebalance."""
        if self.rebalance is None:
            return Response.error("rebalance not configured", 501)
        try:
            payload = json.loads(req.body or b"{}")
        except json.JSONDecodeError as e:
            return Response.error(str(e), 400)
        hosts = payload.get("hosts")
        if not isinstance(hosts, list) or not all(
            isinstance(h, str) and h for h in hosts
        ):
            return Response.error("hosts must be a non-empty string list", 400)
        try:
            return Response.json(self.rebalance.start_resize(hosts))
        except Exception as e:  # noqa: BLE001 — operator boundary
            return Response.error(str(e), 409)

    def handle_post_resize_abort(self, req: Request) -> Response:
        if self.rebalance is None:
            return Response.error("rebalance not configured", 501)
        try:
            return Response.json(self.rebalance.abort())
        except Exception as e:  # noqa: BLE001 — operator boundary
            return Response.error(str(e), 409)

    def handle_post_topology(self, req: Request) -> Response:
        """Internal fan-out target for topology events (begin / flip /
        unflip / commit / abort) — rides the internal admission lane so
        cutover control can never starve behind client traffic."""
        if self.rebalance is None:
            return Response.error("rebalance not configured", 501)
        ticket, shed = self._admit(adm.CLASS_INTERNAL, req)
        if shed is not None:
            return shed
        try:
            payload = json.loads(req.body or b"{}")
            return Response.json(self.rebalance.apply_event(payload))
        except Exception as e:  # noqa: BLE001 — peer boundary
            return Response.error(str(e), 400)
        finally:
            if ticket is not None:
                ticket.release()

    def handle_post_rebalance_delta(self, req: Request) -> Response:
        """Internal migration control on a SOURCE (or checksum on any
        node): start/stop the slice's delta log, bulk-copy the slice's
        fragments to a target, replay the drained log, or report
        per-view checksums.  Internal admission lane."""
        if self.rebalance is None:
            return Response.error("rebalance not configured", 501)
        ticket, shed = self._admit(adm.CLASS_INTERNAL, req)
        if shed is not None:
            return shed
        try:
            payload = json.loads(req.body or b"{}")
            return Response.json(self.rebalance.delta_action(payload))
        except Exception as e:  # noqa: BLE001 — peer boundary
            return Response.error(str(e), 400)
        finally:
            if ticket is not None:
                ticket.release()

    def handle_post_rebalance_release(self, req: Request) -> Response:
        """Internal: drop a migrated-away slice's fragments (HBM + disk
        returned).  Refused while this node still owns the slice."""
        if self.rebalance is None:
            return Response.error("rebalance not configured", 501)
        ticket, shed = self._admit(adm.CLASS_INTERNAL, req)
        if shed is not None:
            return shed
        try:
            payload = json.loads(req.body or b"{}")
            return Response.json(
                self.rebalance.release_slice(
                    str(payload.get("index", "")), int(payload.get("slice", 0))
                )
            )
        except Exception as e:  # noqa: BLE001 — peer boundary
            return Response.error(str(e), 409)
        finally:
            if ticket is not None:
                ticket.release()

    def handle_post_tier_restore(self, req: Request) -> Response:
        """Store-riding rebalance bulk copy, target side: restore one
        fragment from THIS node's configured object store instead of a
        peer stream (the source verified the store copy's checksum is
        fresh first).  Internal admission lane; 501 without a
        configured tier so the source falls back to streaming."""
        if self.tier is None:
            return Response.error("tier not configured", 501)
        ticket, shed = self._admit(adm.CLASS_INTERNAL, req)
        if shed is not None:
            return shed
        try:
            payload = json.loads(req.body or b"{}")
            nbytes = self.tier.restore_from_store(
                str(payload.get("index", "")),
                str(payload.get("frame", "")),
                str(payload.get("view", "")),
                int(payload.get("slice", 0)),
            )
            if self.prefetcher is not None:
                frag = self.holder.fragment(
                    str(payload.get("index", "")),
                    str(payload.get("frame", "")),
                    str(payload.get("view", "")),
                    int(payload.get("slice", 0)),
                )
                if frag is not None:
                    self.prefetcher.stage([frag])
            return Response.json({"bytes": nbytes})
        except Exception as e:  # noqa: BLE001 — peer boundary
            return Response.error(str(e), 409)
        finally:
            if ticket is not None:
                ticket.release()

    def handle_get_tier(self, req: Request) -> Response:
        """Tiered-storage observability: per-fragment state (+ the
        cold→hydrating→hot transition history), counts by state, disk
        usage vs budget, retention config, and the store client's
        health."""
        if self.tier is None:
            return Response.json(
                {"fragments": {}, "note": "tier not configured"}
            )
        return Response.json(self.tier.snapshot())

    def handle_get_ingest(self, req: Request) -> Response:
        """Durable-ingest observability: WAL group-commit state (per-
        fragment segment sizes, buffered ops, last fsync latency and
        batch size), replay history, and the device delta-scatter
        counters (launches / updates applied / fallback invalidations)."""
        from pilosa_tpu.ingest import scatter as ingest_scatter

        doc = {
            "scatter": dict(ingest_scatter.counters()),
            "scatterEnabled": bool(ingest_scatter.ENABLED),
        }
        if self.ingest is None:
            doc["wal"] = {"walEnabled": False, "note": "ingest WAL not configured"}
        else:
            doc["wal"] = self.ingest.snapshot()
        return Response.json(doc)

    # ------------------------------------------------------------------
    # quorum replication: versions / hints / replay
    # ------------------------------------------------------------------

    def handle_post_replicate_versions(self, req: Request) -> Response:
        """Per-slice write versions — the read path's staleness probe.
        Body ``{"index", "slices": [...]}`` answers the versions map;
        ``{"action": "observe", "index", "slice", "version"}`` stamps
        the slice's version forward (max-merge, post-repair marker).
        Internal admission lane (replication control traffic)."""
        if self.replication is None:
            return Response.error("replication not configured", 501)
        ticket, shed = self._admit(adm.CLASS_INTERNAL, req)
        if shed is not None:
            return shed
        try:
            payload = json.loads(req.body or b"{}")
            index = str(payload.get("index", ""))
            if payload.get("action") == "observe":
                v = self.replication.versions.observe(
                    index,
                    int(payload.get("slice", 0)),
                    int(payload.get("version", 0)),
                )
                return Response.json({"ok": True, "version": v})
            slices = payload.get("slices") or []
            return Response.json(
                {
                    "versions": {
                        str(s): v
                        for s, v in self.replication.versions.get_many(
                            index, slices
                        ).items()
                    }
                }
            )
        except (TypeError, ValueError, json.JSONDecodeError) as e:
            return Response.error(str(e), 400)
        finally:
            if ticket is not None:
                ticket.release()

    def handle_post_replicate_hint(self, req: Request) -> Response:
        """Queue a write payload on THIS node as a hint destined for
        an unreachable replica (hinted handoff; the client-side import
        fan-out posts here when a replica is down).  Body ``{"target",
        "index", "slice", "kind": import|import-value|pql,
        "payload"(b64)|"query", "rows"}``.  Internal lane."""
        if self.replication is None:
            return Response.error("replication not configured", 501)
        ticket, shed = self._admit(adm.CLASS_INTERNAL, req)
        if shed is not None:
            return shed
        try:
            payload = json.loads(req.body or b"{}")
            target = str(payload.get("target", ""))
            index = str(payload.get("index", ""))
            slice_i = int(payload.get("slice", 0))
            kind = str(payload.get("kind", ""))
            if not target or not index:
                return Response.error("target and index required", 400)
            if kind == "pql":
                queued = self.replication.hints.queue_pql(
                    target, index, slice_i, str(payload.get("query", ""))
                )
            else:
                queued = self.replication.hints.queue_payload(
                    target,
                    index,
                    slice_i,
                    kind,
                    base64.b64decode(payload.get("payload", "")),
                    int(payload.get("rows", 1)),
                )
            if queued:
                self.replication.stats.count(
                    "cluster.replication.hintsQueued"
                )
            return Response.json({"queued": bool(queued)})
        except (TypeError, ValueError, json.JSONDecodeError) as e:
            return Response.error(str(e), 400)
        finally:
            if ticket is not None:
                ticket.release()

    def handle_post_replicate_replay(self, req: Request) -> Response:
        """Force a synchronous hint replay (ops/test convenience —
        the background replayer normally triggers off the target's
        breaker transition).  Body ``{"target"?: host}``; answers the
        per-target replayed-entry counts.  Internal lane."""
        if self.replication is None:
            return Response.error("replication not configured", 501)
        ticket, shed = self._admit(adm.CLASS_INTERNAL, req)
        if shed is not None:
            return shed
        try:
            payload = json.loads(req.body or b"{}")
            return Response.json(
                {
                    "replayed": self.replication.replay_now(
                        str(payload.get("target", "")) or None
                    )
                }
            )
        except (TypeError, ValueError, json.JSONDecodeError) as e:
            return Response.error(str(e), 400)
        finally:
            if ticket is not None:
                ticket.release()

    # ------------------------------------------------------------------
    # standing queries (pilosa_tpu/subscribe)
    # ------------------------------------------------------------------

    def handle_post_subscribe(self, req: Request) -> Response:
        """Register a standing query.  Body: JSON ``{"index": ...,
        "query": "Subscribe(Count(...))"}``.  Returns the subscription
        id plus the registration snapshot (version 1) — clients then
        stream or long-poll from that version.  The registration
        evaluation rides the dedicated subscribe admission lane."""
        if self.subscribe is None:
            return Response.error("subscribe not configured", 501)
        ticket, shed = self._admit(adm.CLASS_SUBSCRIBE, req)
        if shed is not None:
            return shed
        try:
            try:
                payload = json.loads(req.body or b"{}")
            except ValueError as e:
                return Response.error(f"bad request body: {e}", 400)
            if not isinstance(payload, dict):
                return Response.error("bad request body: expected object", 400)
            index = payload.get("index") or req.query.get("index", "")
            query = payload.get("query", "")
            if not index or not query:
                return Response.error("index and query required", 400)
            try:
                sub = self.subscribe.register(index, query)
            except (
                subscribe_reg.SubscribeError,
                ParseError,
                plan_mod.PlanError,
                ExecutorError,
            ) as e:
                # Registration compiles AND snapshot-evaluates the
                # expression, so executor-level rejections (unknown
                # field, bad Range bounds) are client errors here.
                return Response.error(str(e), 400)
            return Response.json(
                {
                    "id": sub.id,
                    "index": sub.index,
                    "kind": sub.kind,
                    "version": sub.version,
                    "epoch": sub.epoch,
                    "value": sub.value_json,
                },
                status=201,
            )
        finally:
            if ticket is not None:
                ticket.release()

    def _subscription_for(self, sid: str):
        if self.subscribe is None:
            return None, Response.error("subscribe not configured", 501)
        sub = self.subscribe.get(sid)
        if sub is None:
            return None, Response.error(f"no such subscription: {sid}", 404)
        return sub, None

    def handle_get_subscribe_stream(self, req: Request, sid: str) -> Response:
        """SSE delivery: every retained update newer than ``?after=``
        (version-monotonic, at-least-once), then live updates as
        notification batches publish them; keepalive comments while
        idle.  The wait itself holds no admission slot — evaluation
        already paid on the notifier's lane."""
        sub, err = self._subscription_for(sid)
        if err is not None:
            return err
        try:
            after = int(req.query.get("after", "0"))
        except ValueError:
            return Response.error("invalid after", 400)
        gen = sse_mod.event_stream(self.subscribe, sub, after)
        return Response(
            body_iter=sse_mod.EventBody(gen),
            content_type=sse_mod.CONTENT_TYPE,
        )

    def handle_get_subscribe_poll(self, req: Request, sid: str) -> Response:
        """Long-poll delivery: block until the subscription moves past
        ``?after=`` or ``?timeout_ms=`` elapses (bounded).  A timeout
        answers 200 with ``"timeout": true`` so clients distinguish
        quiet from gone (410 = unsubscribed mid-wait)."""
        sub, err = self._subscription_for(sid)
        if err is not None:
            return err
        try:
            after = int(req.query.get("after", "0"))
            timeout_ms = float(req.query.get("timeout_ms", "30000"))
        except ValueError:
            return Response.error("invalid after/timeout_ms", 400)
        timeout_ms = max(0.0, min(timeout_ms, 120_000.0))
        upd = self.subscribe.wait_update(sub, after, timeout=timeout_ms / 1000.0)
        if upd is None:
            if sub.closed:
                return Response.error("subscription closed", 410)
            return Response.json(
                {"id": sub.id, "version": after, "timeout": True}
            )
        return Response.json(upd)

    def handle_delete_subscribe(self, req: Request, sid: str) -> Response:
        if self.subscribe is None:
            return Response.error("subscribe not configured", 501)
        if not self.subscribe.unregister(sid):
            return Response.error(f"no such subscription: {sid}", 404)
        return Response.json({"unsubscribed": sid})

    def handle_get_subscriptions(self, req: Request) -> Response:
        """Standing-query observability: registry size, pending delta
        backlog, notification lag percentiles, lifetime counters, and
        the first page of subscriptions."""
        if self.subscribe is None:
            return Response.json(
                {"count": 0, "note": "subscribe not configured"}
            )
        return Response.json(self.subscribe.snapshot())

    def handle_get_replication(self, req: Request) -> Response:
        """Replication observability: consistency defaults, per-replica
        hint backlog (entries/bits/slices, last replay outcome), local
        per-slice write versions, and the replayer's state."""
        if self.replication is None:
            return Response.json(
                {"hints": {}, "note": "replication not configured"}
            )
        return Response.json(self.replication.snapshot())

    def handle_get_rebalance(self, req: Request) -> Response:
        """Migration observability: topology epoch + transition, the
        coordinator's per-slice state machine, delta-log occupancy, and
        gossip join candidates."""
        if self.rebalance is None:
            return Response.json(
                {
                    "transition": None,
                    "running": False,
                    "note": "rebalance not configured",
                }
            )
        return Response.json(self.rebalance.snapshot())

    # ------------------------------------------------------------------
    # debug
    # ------------------------------------------------------------------

    def handle_get_vars(self, req: Request) -> Response:
        """expvar equivalent (reference: handler.go:1360-1374)."""
        payload: dict[str, Any] = {
            "uptime_seconds": time.time() - self._start_time,
            "version": self.version,
            "threads": threading.active_count(),
        }
        if self.stats is not None and hasattr(self.stats, "snapshot"):
            payload["stats"] = self.stats.snapshot()
        return Response.json(payload)

    def handle_get_health(self, req: Request) -> Response:
        """Cluster-resilience view of this node: per-host circuit
        breaker states (closed/open/half-open, consecutive failures,
        opens), the retry policy, the default query deadline, and the
        membership-level node states."""
        out: dict[str, Any] = {"node": getattr(self.executor, "host", "")}
        if self.cluster is not None:
            out["nodes"] = [
                {"host": h, "state": s}
                for h, s in sorted(self.cluster.node_states().items())
            ]
        if self.resilience is not None:
            out.update(self.resilience.snapshot())
        if self.admission is not None:
            # Per-class gate state: concurrency/queue bounds, live
            # occupancy, EWMA service time, admitted/shed totals.
            out["admission"] = self.admission.snapshot()
        dh = getattr(self.executor, "device_health", None)
        if dh is not None:
            # Device-health state machine (device/health.py): per-path
            # healthy/suspect/quarantined, watchdog trips, and the
            # node-level degraded flag peers see via gossip.
            out["device"] = dh.snapshot()
        if self.prewarm is not None:
            out["prewarm"] = {
                "done": not self.prewarm.is_alive(),
                "programs": self.prewarm.programs,
                "error": (
                    None
                    if self.prewarm.error is None
                    else repr(self.prewarm.error)
                ),
            }
        return Response.json(out)

    def handle_get_tenants(self, req: Request) -> Response:
        """The per-tenant QoS table (net/admission.py TenantRegistry):
        weight, admitted/shed/quota-shed counters, queue-wait EWMA, and
        live quota headroom per tenant, plus the per-class queue split
        when any gate has tenants backlogged.  The operator's first
        stop during a noisy-neighbor incident."""
        if self.tenants is None:
            return Response.json({"tenants": {}})
        out: dict = {
            "defaultTenant": self.tenants.default_tenant,
            "tenants": self.tenants.snapshot(),
        }
        if self.admission is not None:
            queued = {}
            for cls, snap in self.admission.snapshot().items():
                by = snap.get("queuedByTenant")
                if by:
                    queued[cls] = by
            if queued:
                out["queuedByClass"] = queued
        return Response.json(out)

    def handle_get_hbm(self, req: Request) -> Response:
        """HBM residency (device/pool.py): per-device budget / resident
        / pinned / high-water bytes with each device's LRU-ordered
        entries, a per-fragment residency table, and the eviction /
        prefetch counters."""
        from pilosa_tpu import device as device_mod

        return Response.json(device_mod.pool().snapshot())

    def handle_get_traces(self, req: Request) -> Response:
        """The tracer's retained query traces as JSON; ``?min_ms=``
        filters on trace (root span) duration."""
        try:
            min_ms = float(req.query.get("min_ms", "0"))
        except ValueError:
            return Response.error("invalid min_ms", 400)
        return Response.json({"traces": self.tracer.traces(min_ms=min_ms)})

    def handle_get_metrics(self, req: Request) -> Response:
        """Prometheus text exposition of the Expvar store plus process
        gauges (obs/prom.py)."""
        snap: dict = {}
        if self.stats is not None and hasattr(self.stats, "snapshot"):
            try:
                snap = self.stats.snapshot()
            except Exception:  # noqa: BLE001 — stats must not fail the scrape
                snap = {}
        self._inject_program_cache_gauges(snap)
        self._inject_device_memory_gauges(snap)
        self._inject_host_wait_gauges(snap)
        if self.admission is not None:
            # Scrape-time admission gauges (active/queued/concurrency/
            # EWMA per class) — like the program-cache gauges, they
            # must render even without a stats backend.
            try:
                snap.setdefault("gauges", {}).update(self.admission.gauges())
            except Exception:  # noqa: BLE001 — stats must not fail the scrape
                pass
        dh = getattr(self.executor, "device_health", None)
        if dh is not None:
            # Scrape-time device-health gauges (device.health.state per
            # path, device.health.degraded, device.watchdogTrips).
            try:
                snap.setdefault("gauges", {}).update(dh.gauges())
            except Exception:  # noqa: BLE001 — stats must not fail the scrape
                pass
        if self.subscribe is not None:
            # Scrape-time standing-query gauges (active subscriptions,
            # pending delta bits).
            try:
                snap.setdefault("gauges", {}).update(self.subscribe.gauges())
            except Exception:  # noqa: BLE001 — stats must not fail the scrape
                pass
        # Scrape-time launch-telemetry gauges (per-site launches, bytes,
        # GB/s) — injected like the program-cache ones.
        try:
            snap.setdefault("gauges", {}).update(perf_mod.registry().gauges())
        except Exception:  # noqa: BLE001 — stats must not fail the scrape
            pass
        body = prom.render(
            snap,
            extra_gauges={
                "uptime_seconds": time.time() - self._start_time,
                "threads": threading.active_count(),
            },
        )
        # Native histogram families (query latency per class, HTTP
        # latency per route) + SLO gauges render their own exposition
        # block — bucketed cumulative counters, not summaries.
        try:
            body += self.latency.render()
        except Exception:  # noqa: BLE001 — stats must not fail the scrape
            pass
        return Response(body=body.encode(), content_type=prom.CONTENT_TYPE)

    @staticmethod
    def _inject_program_cache_gauges(snap: dict) -> None:
        """Scrape-time ``exec.programCache.entries`` gauge — total plus
        one ``cache:<family>`` label per jit wrapper family (exec/plan.py
        program_cache_stats): the observability prerequisite for capping
        compiled-program cardinality (ROADMAP 2a).  Injected into the
        snapshot (not the stats store), so it renders on every scrape
        even when the node runs without a stats backend.  Same-depth-
        bucket BSI queries sharing one program per op kind is asserted
        against exactly this gauge."""
        try:
            from pilosa_tpu.exec import plan as plan_mod

            stats = plan_mod.program_cache_stats()
            gauges = snap.setdefault("gauges", {})
            gauges["exec.programCache.entries"] = stats.pop("total")
            for family, n in stats.items():
                gauges[f"exec.programCache.entries[cache:{family}]"] = n
            # Hard per-family cardinality bounds implied by the pow2
            # bucket grids (entries <= bound is an invariant; a breach
            # means a caller stopped canonicalizing its compile key).
            bounds = plan_mod.program_cache_bounds()
            gauges["exec.programCache.bound"] = sum(bounds.values())
            for family, n in bounds.items():
                gauges[f"exec.programCache.bound[cache:{family}]"] = n
            # Cumulative compile-bearing first-call wall ms per family:
            # how much of this process's life went to XLA compilation.
            for family, ms in plan_mod.program_cache_compile_ms().items():
                gauges[f"exec.programCache.compileMs[cache:{family}]"] = ms
        except Exception:  # noqa: BLE001 — stats must not fail the scrape
            pass

    @staticmethod
    def _inject_device_memory_gauges(snap: dict) -> None:
        """Scrape-time allocator gauges per local device, from
        ``memory_stats()``: ``device.<i>.hbm_bytes_in_use`` and
        ``device.<i>.hbm_peak_bytes_in_use``, the allocator's high-water
        mark since the process started.  Read at the scrape, so a peak
        between two scrapes is not lost; a backend that reports no
        memory statistics (CPU) renders neither."""
        try:
            import jax

            gauges = snap.setdefault("gauges", {})
            for i, dev in enumerate(jax.local_devices()):
                mem = dev.memory_stats() or {}
                for key in ("bytes_in_use", "peak_bytes_in_use"):
                    if key in mem:
                        gauges[f"device.{i}.hbm_{key}"] = mem[key]
        except Exception:  # noqa: BLE001 — stats must not fail the scrape
            pass

    def _inject_host_wait_gauges(self, snap: dict) -> None:
        """Scrape-time account of where the host waits: the coalescer's
        dispatcher by what it was doing (``exec.dispatcher.idleMs`` /
        ``launchMs`` / ``hostMs`` / ``cycles``, which add up to its
        life) and the residency pool's contended lock
        (``pool.lockWaits`` / ``pool.lockWaitMs``).  Plain attributes
        the hot path adds to, read here."""
        try:
            gauges = snap.setdefault("gauges", {})
            co = getattr(self.executor, "coalescer", None)
            if co is not None and hasattr(co, "gauges"):
                gauges.update(co.gauges())
            from pilosa_tpu import device as device_mod

            gauges.update(device_mod.pool().gauges())
        except Exception:  # noqa: BLE001 — stats must not fail the scrape
            pass

    def handle_get_perf(self, req: Request) -> Response:
        """The launch-telemetry table (obs/perf.py): per-site
        launches, logical bytes streamed, host-clock GB/s, p50/p99
        launch ms, batch occupancy — plus
        the slowest recent launches with their trace ids (feed one to
        ``/debug/traces`` for the full span breakdown) and cumulative
        per-family compile ms."""
        snap = perf_mod.registry().snapshot()
        try:
            snap["compile_ms"] = plan_mod.program_cache_compile_ms()
        except Exception:  # noqa: BLE001 — introspection must not fail
            snap["compile_ms"] = {}
        return Response.json(snap)

    def handle_get_stacks(self, req: Request) -> Response:
        """All thread stacks via ``sys._current_frames`` — the
        wedge-diagnosis companion to the PR-15 launch watchdog: when a
        device call hangs, this shows WHERE every thread is stuck
        without attaching a debugger.  (Alias of the pprof "goroutine"
        dump under a first-class route.)  Each thread's line carries
        its CPU seconds so far, read only here: a thread that opens no
        span and holds the GIL (a rank-cache re-sort, the WAL, a
        compile) shows between two calls."""
        frames = sys._current_frames()
        out = io.StringIO()
        out.write(f"{len(frames)} threads\n\n")
        for t in threading.enumerate():
            out.write(
                f"thread {t.name} id={t.ident} (daemon={t.daemon})"
                f"{_thread_cpu(t.ident)}\n"
            )
            fr = frames.get(t.ident)
            if fr is not None:
                out.write("".join(traceback.format_stack(fr)))
            out.write("\n")
        return Response(body=out.getvalue().encode(), content_type="text/plain")

    def handle_get_profile(self, req: Request) -> Response:
        """On-demand device profile: wraps ``jax.profiler.trace`` for
        ``?seconds=N`` (clamped to 60), tars the trace directory under
        the data dir, and returns its path.  Single-flight — a second
        concurrent request answers 409; a runtime without the profiler
        answers 501 (the capture is optional, the endpoint is not).

        The Python tracer is OFF unless ``?python=1`` asks for it: it
        hooks every call of a server whose bottleneck is Python and
        stalls it for seconds at the stop, so a profile taken with it
        measures the profiler.  The host tracer stays on, and while the
        session is live every program span also enters a
        ``TraceAnnotation`` (obs/trace.py), so the spans stand in the
        profile's host plane beside the device ops."""
        try:
            seconds = max(0.05, min(float(req.query.get("seconds", "3")), 60.0))
        except ValueError:
            return Response.error("invalid seconds", 400)
        profiler = _jax_profiler()
        if profiler is None:
            return Response.error("jax profiler unavailable", 501)
        if not self._profile_mu.acquire(blocking=False):
            return Response.error("profile already in flight", 409)
        try:
            base = self.profile_dir or tempfile.mkdtemp(
                prefix="pilosa-profile-"
            )
            trace_dir = os.path.join(
                base, "profiles",
                time.strftime("trace-%Y%m%d-%H%M%S"),
            )
            os.makedirs(trace_dir, exist_ok=True)
            try:
                opts = profiler.ProfileOptions()
                opts.python_tracer_level = int(req.query.get("python") == "1")
                with profiler.trace(trace_dir, profiler_options=opts):
                    trace.set_profiling(profiler.TraceAnnotation)
                    try:
                        time.sleep(seconds)
                    finally:
                        trace.set_profiling(None)
            except Exception as e:  # noqa: BLE001 — backend without xprof
                shutil.rmtree(trace_dir, ignore_errors=True)
                return Response.error(f"jax profiler unavailable: {e}", 501)
            tar_path = trace_dir + ".tar.gz"
            with tarfile.open(tar_path, "w:gz") as tf:
                tf.add(trace_dir, arcname=os.path.basename(trace_dir))
            shutil.rmtree(trace_dir, ignore_errors=True)
            return Response.json(
                {
                    "seconds": seconds,
                    "trace": tar_path,
                    "bytes": os.path.getsize(tar_path),
                }
            )
        finally:
            self._profile_mu.release()

    def handle_get_pprof(self, req: Request, rest: str | None = None) -> Response:
        """Profiling endpoints — the Python analog of the reference's
        net/http/pprof mount (reference: handler.go:111-112):

        * ``/debug/pprof`` or ``/goroutine`` — live thread-stack dump;
        * ``/debug/pprof/profile?seconds=N`` — statistical CPU profile:
          samples every thread's stack at ~100 Hz for N seconds (default
          5, max 60) and returns folded stacks ("f1;f2;f3 count"), the
          flamegraph-ready equivalent of the pprof CPU profile;
        * ``/debug/pprof/heap`` — tracemalloc top allocations
          (``?start=1`` begins tracing, ``?stop=1`` ends it).
        """
        kind = (rest or "/").strip("/") or "goroutine"
        if kind == "goroutine":
            frames = sys._current_frames()
            out = io.StringIO()
            for t in threading.enumerate():
                out.write(f"thread {t.name} (daemon={t.daemon})\n")
                fr = frames.get(t.ident)
                if fr is not None:
                    out.write("".join(traceback.format_stack(fr)))
                out.write("\n")
            return Response(body=out.getvalue().encode(), content_type="text/plain")
        if kind == "profile":
            try:
                seconds = min(float(req.query.get("seconds", "5")), 60.0)
            except ValueError:
                return Response.error("invalid seconds", 400)
            folded = _sample_cpu_profile(seconds)
            return Response(body=folded.encode(), content_type="text/plain")
        if kind == "heap":
            import tracemalloc

            if req.query.get("start"):
                tracemalloc.start(16)
                return Response(body=b"tracemalloc started\n",
                                content_type="text/plain")
            if req.query.get("stop"):
                tracemalloc.stop()
                return Response(body=b"tracemalloc stopped\n",
                                content_type="text/plain")
            if not tracemalloc.is_tracing():
                return Response(
                    body=b"tracemalloc not tracing; GET ?start=1 first\n",
                    content_type="text/plain",
                )
            snap = tracemalloc.take_snapshot()
            out = io.StringIO()
            for stat in snap.statistics("lineno")[:50]:
                out.write(f"{stat}\n")
            return Response(body=out.getvalue().encode(), content_type="text/plain")
        return Response.error(f"unknown profile: {kind}", 404)

    # ------------------------------------------------------------------
    # helpers
    # ------------------------------------------------------------------

    def _attr_diff(self, req: Request, store) -> Response:
        """Shared column/row attr-diff logic (reference:
        handler.go:514-570, 782-838)."""
        try:
            payload = json.loads(req.body)
        except json.JSONDecodeError as e:
            return Response.error(str(e), 400)
        remote_blocks = [
            (b["id"], base64.b64decode(b["checksum"]))
            for b in payload.get("blocks", [])
        ]
        local_blocks = store.blocks()
        diff_ids = attr_mod.diff_blocks(local_blocks, remote_blocks)
        attrs: dict[str, dict] = {}
        for bid in diff_ids:
            for id_, a in store.block_data(bid).items():
                attrs[str(id_)] = a
        return Response.json({"attrs": attrs})

    def _broadcast(self, msg) -> None:
        if self.broadcaster is not None:
            try:
                self.broadcaster.send_sync(msg)
            except Exception as e:  # noqa: BLE001 — broadcast is best-effort
                self.logger(f"broadcast error: {e}")


def _thread_cpu(ident) -> str:
    """`` cpu=<seconds>s`` of the thread ``ident`` so far, empty where
    the platform has no per-thread CPU clock or the thread has gone."""
    try:
        clock = time.pthread_getcpuclockid(ident)
        return f" cpu={time.clock_gettime(clock):.3f}s"
    except (AttributeError, OSError, TypeError):
        return ""


def _jax_profiler():
    """Resolve ``jax.profiler`` (None when absent or without ``trace``)
    — separated out so the /debug/profile 501 path is testable by
    monkeypatching."""
    try:
        from jax import profiler
    except Exception:  # noqa: BLE001 — stub/absent jax
        return None
    return profiler if hasattr(profiler, "trace") else None


def _consistency_arg(req: Request, header: str, param: str) -> str:
    """A per-request consistency override: the header wins over the
    query param; "" means the server default; anything else must be a
    valid level (raises ValueError -> 400)."""
    raw = req.header(header) or req.query.get(param, "")
    if not raw:
        return ""
    return replicate_mod.validate_level(raw, param)


def _coalesce_batch_stats(record: dict) -> dict | None:
    """Aggregate the coalescer's batch stats from a trace's ``coalesce``
    spans (exec/coalesce.py annotates each with its launch's occupancy)
    — the slow-query line's evidence of whether a slow query rode a
    shared launch and how full it was.  None when the query never hit
    the coalescer."""
    spans = [s for s in record.get("spans", ()) if s.get("name") == "coalesce"]
    occ = [
        s["tags"]["batch_queries"]
        for s in spans
        if isinstance(s.get("tags", {}).get("batch_queries"), (int, float))
    ]
    if not spans:
        return None
    out: dict = {"launches": len(spans)}
    if occ:
        out["mean_occupancy"] = round(sum(occ) / len(occ), 2)
        out["max_occupancy"] = max(occ)
    return out


def _fuse_batch_stats(record: dict) -> dict | None:
    """Aggregate multi-query-fusion composition from a trace's ``fuse``
    spans (executor._coalesce_eval emits one per fused launch the query
    rode, tagged with tree count / op count / subtree-dedup hits) —
    the slow-query line's evidence that a slow query shared an
    interpreter pass, and with how many distinct trees.  None when the
    query never fused."""
    spans = [s for s in record.get("spans", ()) if s.get("name") == "fuse"]
    if not spans:
        return None
    out: dict = {"launches": len(spans)}
    for tag, label in (
        ("batch_queries", "mean_fused_queries"),
        ("programs", "mean_programs"),
        ("ops", "mean_ops"),
        ("dedup_hits", "mean_dedup_hits"),
    ):
        vals = [
            s["tags"][tag]
            for s in spans
            if isinstance(s.get("tags", {}).get(tag), (int, float))
        ]
        if vals:
            out[label] = round(sum(vals) / len(vals), 2)
    return out


def _sample_cpu_counts(
    seconds: float,
    hz: float = 100.0,
    stop: "threading.Event | None" = None,
    counts: "dict[str, int] | None" = None,
) -> dict[str, int]:
    """Sample every thread's stack at ``hz`` for up to ``seconds``
    (``stop`` cuts the run short), accumulating folded-stack sample
    counts into ``counts`` in place so a caller on another thread can
    snapshot mid-run."""
    if counts is None:
        counts = {}
    me = threading.get_ident()
    deadline = time.monotonic() + seconds
    interval = 1.0 / hz
    while time.monotonic() < deadline and not (stop is not None and stop.is_set()):
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue  # don't profile the profiler
            parts = []
            f = frame
            while f is not None:
                code = f.f_code
                parts.append(f"{code.co_name} ({code.co_filename}:{f.f_lineno})")
                f = f.f_back
            stack = ";".join(reversed(parts)) or "<idle>"
            counts[stack] = counts.get(stack, 0) + 1
        time.sleep(interval)
    return counts


def _fold_counts(counts: dict[str, int]) -> str:
    lines = [
        f"{stack} {n}"
        for stack, n in sorted(counts.items(), key=lambda kv: -kv[1])
    ]
    return "\n".join(lines) + ("\n" if lines else "")


def _sample_cpu_profile(seconds: float, hz: float = 100.0) -> str:
    """Statistical whole-process CPU profile: sample for ``seconds`` and
    fold identical stacks into "frame1;frame2;... count" lines
    (most-sampled first) — the flamegraph-collapsed equivalent of the
    reference's pprof CPU profile endpoint."""
    return _fold_counts(_sample_cpu_counts(seconds, hz))


def _frame_meta_proto(f) -> wire.FrameMeta:
    return wire.FrameMeta(
        RowLabel=f.row_label,
        InverseEnabled=f.inverse_enabled,
        CacheType=f.cache_type,
        CacheSize=f.cache_size,
        TimeQuantum=f.time_quantum,
    )


def _dt_from_unix(ts: int):
    """ImportRequest timestamps are Unix *nanoseconds* (reference:
    ctl/import.go:157 stores t.UnixNano())."""
    from datetime import datetime, timezone

    return datetime.fromtimestamp(ts / 1e9, tz=timezone.utc).replace(tzinfo=None)


# ---------------------------------------------------------------------------
# stdlib HTTP adapter
# ---------------------------------------------------------------------------


def make_http_server(handler: Handler, host: str = "127.0.0.1", port: int = 0):
    """Mount a Handler on a ThreadingHTTPServer; returns the server
    (call .serve_forever() in a thread; .server_address has the bound
    port when port=0).

    Bodies stream in both directions: chunked (or Content-Length)
    request bodies reach streaming routes as an incremental reader, and
    a Response.body_iter goes out with chunked transfer encoding in
    constant-size writes — no large body is ever held whole.
    """

    class _Adapter(BaseHTTPRequestHandler):
        protocol_version = "HTTP/1.1"

        def _run(self):
            parsed = urllib.parse.urlsplit(self.path)
            query = dict(urllib.parse.parse_qsl(parsed.query))
            te = (self.headers.get("Transfer-Encoding") or "").lower()
            if "chunked" in te:
                body_stream = stream_mod.ChunkedBodyReader(self.rfile)
            else:
                length = int(self.headers.get("Content-Length") or 0)
                body_stream = stream_mod.LengthBodyReader(self.rfile, length)
            req = Request(
                method=self.command,
                path=parsed.path,
                query=query,
                headers={k.lower(): v for k, v in self.headers.items()},
                stream=body_stream,
            )
            resp = handler.dispatch(req)
            # Unread request bytes must leave the socket before the
            # response for keep-alive framing to survive; a huge
            # abandoned body drops the connection instead.
            try:
                if not body_stream.drain():
                    self.close_connection = True
            except (OSError, ValueError):
                self.close_connection = True
            # Streamed request bodies count toward the bytes-moved
            # surface (reads already happened inside the route).
            received = getattr(body_stream, "bytes_read", 0)
            if received:
                self._count_stream_bytes("stream.bytesReceived", received)
            if resp.body_iter is not None:
                self._send_stream(resp)
            else:
                self.send_response(resp.status)
                self.send_header("Content-Type", resp.content_type)
                for k, v in resp.headers.items():
                    self.send_header(k, v)
                self.send_header("Content-Length", str(len(resp.body)))
                self.end_headers()
                self.wfile.write(resp.body)

        def _count_stream_bytes(self, name: str, n: int) -> None:
            if handler.stats is None or n <= 0:
                return
            try:
                handler.stats.count(name, n)
            except Exception:  # noqa: BLE001 — stats never break transport
                pass

        def _send_stream(self, resp: Response) -> None:
            self.send_response(resp.status)
            self.send_header("Content-Type", resp.content_type)
            for k, v in resp.headers.items():
                self.send_header(k, v)
            self.send_header("Transfer-Encoding", "chunked")
            self.end_headers()
            sent = 0
            try:
                for chunk in resp.body_iter:
                    if chunk:
                        self.wfile.write(stream_mod.encode_chunk(chunk))
                        sent += len(chunk)
                self.wfile.write(stream_mod.CHUNK_TERMINATOR)
            except (BrokenPipeError, ConnectionResetError):
                self.close_connection = True
            except Exception as e:  # noqa: BLE001 — mid-stream producer error
                # Headers are gone; all we can do is truncate the
                # chunked body (no terminator => client sees an error)
                # and log.
                handler.logger(f"stream error {self.path}: {e}")
                self.close_connection = True
            finally:
                self._count_stream_bytes("stream.bytesSent", sent)
                close = getattr(resp.body_iter, "close", None)
                if close is not None:
                    close()

        do_GET = do_POST = do_DELETE = do_PATCH = _run

        def log_message(self, fmt, *args):  # quiet
            pass

    class _Server(ThreadingHTTPServer):
        # socketserver's default listen backlog is 5: a burst of a few
        # dozen clients connecting at once was reset at the socket,
        # before admission (32 point slots + a queue of 64) ever saw it.
        request_queue_size = 128

    return _Server((host, port), _Adapter)
