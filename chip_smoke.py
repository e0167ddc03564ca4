#!/usr/bin/env python3
"""chip_smoke.py — the served path, once, on the chip.

The quickest proof that the system still starts on a TPU and that the
TPU, not the host fallback, does the work:

* This process (the parent) never initialises a JAX backend: it speaks
  HTTP, keeps a numpy oracle, and asserts at exit that it created no
  backend.  One child owns the chip:
  ``python -m pilosa_tpu.cli server --data-dir <tmp> --bind 127.0.0.1:<port>``
  on the default configuration (WAL + group commit, scatter, coalesce +
  fuse, admission and prewarm all on).  The only settings
  passed are deployment ones: the bind address, the data dir and
  ``[metrics] service = expvar`` so ``/metrics`` carries the counters
  the checks read.
* Loads one index of ``--slices`` x 2^20 columns x ``--rows`` rows
  (default 954 x 32: 1B columns, 4.0 GB of resident planes) through
  ``POST /import``, plus a small BSI field through ``/import-value``.
  Data comes from ``--seed``.  Row densities are skewed — row 0 at 2 %,
  row 1 at 1 %, the rest falling from 2e-4 by 0.9 per row — about 34M
  bits at the default size.
* Asks Count / Intersect / Union / Difference / Bitmap / TopN / Sum /
  Range / SetBit / ClearBit and compares every answer bit-exact with
  the oracle; restarts the server on the same data dir and compile
  cache and asks again, the written bit included.
* Then reads the server's own surfaces (``/debug/health``,
  ``/debug/perf``, ``/debug/hbm``, ``/debug/ingest``, ``/metrics``, its
  log) and fails unless the devices are TPUs, no launch was retried or
  answered by ``hosteval``, every program family the queries reach
  launched, the planes are resident, the budget was detected and the
  compile cache filled once and hit on the second boot.

Exit code 0 and a last stdout line ``{"ok": true, "device": {"platform":
..., "kind": ..., "count": ...}}`` — those keys and no others — only
when every check held; the line before it is the full report (the same
object as ``result.json``: failures, launches per program family,
resident bytes, health, compile cache, timings).  With no TPU it exits 2
at once and prints no result; ``--cpu-rehearsal`` (for a sandbox without a chip, at a tiny
``--slices``) accepts the CPU backend and says ``"platform": "cpu"``.
At that size a launch takes microseconds and concurrent queries never
meet in the coalescer, so the rehearsal alone also sets ``[exec]
coalesce-max-wait-us``; on the chip nothing is set.
Timings it prints are information for the next issue, never a claim.
"""

from __future__ import annotations

import argparse
import http.client
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

SLICE_WIDTH = 1 << 20
WORDS64 = SLICE_WIDTH // 64
ROW_BYTES = SLICE_WIDTH // 8  # one dense (slice, row): 128 KiB
ROW_BLOCK = 8  # planes pad rows to a power of two, at least this

INDEX = "smoke"
FRAME = "f"
BSI_FRAME = "v"
BSI_FIELD = "q"
BSI_SLICES = 4
BSI_COLUMNS_PER_SLICE = 2000
BSI_MIN, BSI_MAX = -1000, 1000
# A time Range is a union over a frame's time views, the tree the
# in-place aggregate does not take: a Sum filtered by one goes through
# the leaf batch, so that way's "agg" reduce still meets the chip.  Frame
# f has no time quantum, so the Range selects nothing and its Union with
# a comparison is the comparison.
TIME_RANGE = (
    f'Range(frame={FRAME}, rowID=0, start="2017-01-01T00:00", end="2018-01-01T00:00")'
)

# BASELINE.json's own n ("TopN(n=100)"): at least the rows, so every row
# is a candidate and the scorer runs the program a restart prewarms.
TOPN = 100
LOAD_THREADS = 4
STORM_WAVES = 4
# The contract gives 1200 s, compilation included.
TIME_LIMIT_S = 1170
# Every query carries its own deadline: a cold first compile at this
# size may outlast the server's 60 s default, and how long it took is
# what the smoke reports.
DEADLINE_MS = 600_000

# Launch sites of obs/perf.py, every one of them, so that a family that
# did not launch is named in the result and not skipped.
SITES = (
    "direct", "coalesce", "interp", "total", "collective",
    "topn", "fetch", "agg", "anchored", "hosteval",
)

LOG_MUST_NOT_HAVE = (
    "prewarm failed",
    "compilation cache DISABLED",
    "QUARANTINED",
    "watchdog TRIPPED",
    "Traceback (most recent call last)",
)


def log(msg: str) -> None:
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", flush=True)


class SmokeError(RuntimeError):
    """A phase could not run to its end (as opposed to a check that ran
    and did not hold, which is recorded and reported at the end)."""


# ---------------------------------------------------------------------------
# data and oracle: plain numpy on packed bitsets, nothing of the package
# ---------------------------------------------------------------------------


def density(row: int) -> float:
    if row == 0:
        return 0.02
    if row == 1:
        return 0.01
    return 2e-4 * 0.9 ** (row - 2)


def pack(offsets: np.ndarray) -> np.ndarray:
    """Sorted unique bit offsets in [0, 2^20) -> uint64[WORDS64]."""
    out = np.zeros(WORDS64, dtype=np.uint64)
    if offsets.size:
        w = offsets >> 6
        m = np.uint64(1) << (offsets & 63).astype(np.uint64)
        start = np.flatnonzero(np.r_[True, w[1:] != w[:-1]])
        out[w[start]] = np.bitwise_or.reduceat(m, start)
    return out


def popcount(words: np.ndarray, axis=None):
    return np.bitwise_count(words).sum(axis=axis, dtype=np.int64)


class Oracle:
    """The reference: every row of frame ``f`` as a packed bitset
    ``uint64[n_slices, WORDS64]``, the BSI field as a dict."""

    def __init__(self, seed: int, n_slices: int, n_rows: int):
        self.seed = seed
        self.n_slices = n_slices
        self.n_rows = n_rows
        self.bits = np.zeros((n_rows, n_slices, WORDS64), dtype=np.uint64)
        self.values: dict[int, int] = {}

    def make_slice(self, s: int) -> tuple[np.ndarray, np.ndarray]:
        """Generate slice ``s`` from the seed, record it, and return the
        ``(rowIDs, columnIDs)`` to import."""
        rng = np.random.default_rng([self.seed, s])
        rows, cols = [], []
        for r in range(self.n_rows):
            k = rng.binomial(SLICE_WIDTH, density(r))
            offs = np.unique(rng.integers(0, SLICE_WIDTH, size=k))
            self.bits[r, s] = pack(offs)
            rows.append(np.full(offs.size, r, dtype=np.uint64))
            cols.append(offs.astype(np.uint64) + np.uint64(s * SLICE_WIDTH))
        return np.concatenate(rows), np.concatenate(cols)

    def make_values(self, s: int) -> tuple[np.ndarray, np.ndarray]:
        rng = np.random.default_rng([self.seed, 1_000_003, s])
        cols = np.unique(
            rng.integers(0, SLICE_WIDTH, size=BSI_COLUMNS_PER_SLICE)
        ) + s * SLICE_WIDTH
        vals = rng.integers(BSI_MIN, BSI_MAX + 1, size=cols.size)
        self.values.update(zip(cols.tolist(), vals.tolist()))
        return cols, vals

    def vals(self) -> np.ndarray:
        return np.array(list(self.values.values()), dtype=np.int64)

    # -- answers -----------------------------------------------------------

    def count(self, op: str, a: int, b: int | None = None) -> int:
        x = self.bits[a]
        if op == "Bitmap":
            return int(popcount(x))
        y = self.bits[b]
        if op == "Intersect":
            return int(popcount(x & y))
        if op == "Union":
            return int(popcount(x | y))
        if op == "Difference":
            return int(popcount(x & ~y))
        if op == "Xor":
            return int(popcount(x ^ y))
        raise ValueError(op)

    def columns(self, r: int) -> np.ndarray:
        flat = self.bits[r].ravel()
        w = np.flatnonzero(flat)
        b = np.unpackbits(
            flat[w].view(np.uint8).reshape(-1, 8), axis=1, bitorder="little"
        )
        wi, bi = np.nonzero(b)
        return w[wi] * 64 + bi

    def topn(self, n: int, src: int | None = None) -> list[tuple[int, int]]:
        """Pilosa's TopN as documented: every slice nominates its own
        top ``n`` rows (by cached count, or by |row AND src| when a src
        is given; count desc, id asc), and the union of the nominees is
        ranked by exact counts summed over all slices."""
        scores = np.empty((self.n_slices, self.n_rows), dtype=np.int64)
        for r in range(self.n_rows):
            x = self.bits[r] if src is None else self.bits[r] & self.bits[src]
            scores[:, r] = popcount(x, axis=1)
        ids = np.arange(self.n_rows)
        nominees: set[int] = set()
        for s in range(self.n_slices):
            live = ids[scores[s] > 0]
            order = np.lexsort((live, -scores[s, live]))[:n]
            nominees.update(live[order].tolist())
        cand = np.array(sorted(nominees), dtype=np.int64)
        totals = scores[:, cand].sum(axis=0)
        order = np.lexsort((cand, -totals))[:n]
        return [(int(cand[i]), int(totals[i])) for i in order if totals[i] > 0]

    def set_bit(self, r: int, col: int, on: bool) -> None:
        s, off = divmod(col, SLICE_WIDTH)
        mask = np.uint64(1) << np.uint64(off & 63)
        if on:
            self.bits[r, s, off >> 6] |= mask
        else:
            self.bits[r, s, off >> 6] &= ~mask

    def has_bit(self, r: int, col: int) -> bool:
        s, off = divmod(col, SLICE_WIDTH)
        return bool((int(self.bits[r, s, off >> 6]) >> (off & 63)) & 1)


# ---------------------------------------------------------------------------
# the server child
# ---------------------------------------------------------------------------


class Server:
    def __init__(self, data_dir: str, log_path: str, extra_env: dict):
        self.data_dir = data_dir
        self.log_path = log_path
        self.extra_env = extra_env
        self.proc: subprocess.Popen | None = None
        self.port = 0
        self.log_offset = 0  # where this boot's lines start

    def start(self) -> None:
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            self.port = s.getsockname()[1]
        env = dict(os.environ)
        env["PILOSA_METRICS_SERVICE"] = "expvar"
        env["PYTHONUNBUFFERED"] = "1"
        env.update(self.extra_env)
        self.log_offset = (
            os.path.getsize(self.log_path) if os.path.exists(self.log_path) else 0
        )
        with open(self.log_path, "ab") as logf:
            self.proc = subprocess.Popen(
                [
                    sys.executable, "-m", "pilosa_tpu.cli", "server",
                    "--data-dir", self.data_dir,
                    "--bind", f"127.0.0.1:{self.port}",
                ],
                cwd=HERE,
                env=env,
                stdin=subprocess.DEVNULL,
                stdout=logf,
                stderr=subprocess.STDOUT,
                start_new_session=True,
            )

    def boot_log(self) -> str:
        with open(self.log_path, "rb") as f:
            f.seek(self.log_offset)
            return f.read().decode("utf-8", "replace")

    def wait_listening(self, timeout: float) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self.proc.poll() is not None:
                raise SmokeError(
                    f"server exited with {self.proc.returncode} during boot:\n"
                    + self.boot_log()[-4000:]
                )
            if "listening on http://" in self.boot_log():
                return
            time.sleep(0.2)
        raise SmokeError(f"server not listening after {timeout:.0f} s")

    def stop(self, timeout: float = 180.0) -> int:
        """SIGTERM (the shutdown path: close listeners, flush, release
        the chip) and wait for the exit."""
        self.proc.send_signal(signal.SIGTERM)
        try:
            return self.proc.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            self.kill()
            raise SmokeError(f"server did not exit {timeout:.0f} s after SIGTERM")

    def kill(self) -> None:
        if self.proc is not None and self.proc.poll() is None:
            try:
                os.killpg(self.proc.pid, signal.SIGKILL)
            except ProcessLookupError:
                pass
            self.proc.wait()

    # -- HTTP ---------------------------------------------------------------

    def request(self, method: str, path: str, body: bytes | None = None,
                timeout: float = DEADLINE_MS / 1000 + 30) -> tuple[int, bytes]:
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=timeout)
        try:
            conn.request(
                method, path, body=body,
                headers={"X-Deadline-Ms": str(DEADLINE_MS)},
            )
            resp = conn.getresponse()
            return resp.status, resp.read()
        finally:
            conn.close()

    def get_json(self, path: str):
        status, data = self.request("GET", path)
        if status != 200:
            raise SmokeError(f"GET {path} -> {status}: {data[:300]!r}")
        return json.loads(data)

    def query(self, pql: str):
        """One PQL call over ``POST /index/<i>/query`` -> its result."""
        status, data = self.request(
            "POST", f"/index/{INDEX}/query", pql.encode()
        )
        if status != 200:
            raise SmokeError(f"query {pql!r} -> HTTP {status}: {data[:500]!r}")
        doc = json.loads(data)
        if doc.get("error"):
            raise SmokeError(f"query {pql!r} -> {doc['error']}")
        return doc["results"][0]

    def metrics(self) -> dict[str, float]:
        """``/metrics`` as {series: value}; a series is the metric name
        with its label block, as the server printed it."""
        status, data = self.request("GET", "/metrics")
        if status != 200:
            raise SmokeError(f"GET /metrics -> {status}")
        out = {}
        for line in data.decode().splitlines():
            if line and not line.startswith("#"):
                series, _, value = line.rpartition(" ")
                try:
                    out[series] = float(value)
                except ValueError:
                    pass
        return out


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------


def bitmap(row: int) -> str:
    return f"Bitmap(frame={FRAME}, rowID={row})"


def pair_query(op: str, a: int, b: int) -> str:
    return f"Count({op}({bitmap(a)}, {bitmap(b)}))"


def pairs_of(result) -> list[tuple[int, int]]:
    return [(int(p["id"]), int(p["count"])) for p in result]


class Run:
    def __init__(self, args):
        self.args = args
        self.failures: list[str] = []
        self.timings: dict[str, float] = {}
        self.report: dict = {}
        self.oracle = Oracle(args.seed, args.slices, args.rows)
        self.out_dir = args.out
        os.makedirs(self.out_dir, exist_ok=True)
        self.data_dir = tempfile.mkdtemp(prefix="chip-smoke-data-")
        log_path = os.path.join(self.out_dir, "server.log")
        if os.path.exists(log_path):
            os.unlink(log_path)
        self.server = Server(
            self.data_dir, log_path,
            {"PILOSA_EXEC_COALESCE_MAX_WAIT_US": "50000"}
            if args.cpu_rehearsal else {},
        )
        self.device: dict = {}
        self.cache_dir = ""
        # The TopN src is also the row the write phase flips bits in, so
        # one TopN text reads the writes back through the mirrors (its
        # prep-cache entry points at them and is charged for none since
        # PR 29).  The row fetched whole is a sparse one.
        self.src_row = 1
        self.fetch_row = min(20, args.rows - 1)

    # -- bookkeeping ---------------------------------------------------------

    def check(self, name: str, ok: bool, detail: str = "") -> bool:
        log(f"{'ok  ' if ok else 'FAIL'} {name}{' — ' + detail if detail else ''}")
        if not ok:
            self.failures.append(f"{name}: {detail}" if detail else name)
        return ok

    def expect(self, name: str, got, want) -> None:
        same = got == want
        self.check(name, same, "" if same else f"got {got!r}, oracle says {want!r}")

    def timed(self, key: str, fn):
        t0 = time.monotonic()
        out = fn()
        self.timings[key] = round(time.monotonic() - t0, 3)
        return out

    # -- phases --------------------------------------------------------------

    def boot(self, label: str) -> None:
        t0 = time.monotonic()
        self.server.start()
        self.server.wait_listening(timeout=300)
        self.timings[f"{label}_boot_s"] = round(time.monotonic() - t0, 3)
        boot_log = self.server.boot_log()
        m = re.search(
            r"devices: platform=(\S+) kind='([^']*)' count=(\d+)", boot_log
        )
        if m is None:
            raise SmokeError("server logged no 'devices:' line:\n" + boot_log[-2000:])
        device = {
            "platform": m.group(1), "kind": m.group(2), "count": int(m.group(3)),
        }
        if self.device and device != self.device:
            raise SmokeError(f"devices changed across the restart: {device}")
        self.device = device
        log(f"{label}: server up in {self.timings[f'{label}_boot_s']} s on {device}")
        want = "cpu" if self.args.cpu_rehearsal else "tpu"
        if device["platform"] != want:
            # Nothing else is worth running; no result is printed.
            self.server.kill()
            print(
                f"chip_smoke: the server runs on {device['platform']!r}, "
                f"not {want!r}", file=sys.stderr,
            )
            raise SystemExit(2)
        m = re.search(r"compilation cache: (\S+)", boot_log)
        self.check(f"{label}: compile cache enabled", m is not None)
        if m is not None:
            self.cache_dir = m.group(1)
        if device["count"] > 1:
            self.check(
                f"{label}: mesh-sharded data plane engaged",
                f"mesh-sharded over {device['count']} devices" in boot_log,
            )

    def load(self) -> None:
        from pilosa_tpu.net.client import InternalClient

        host = f"127.0.0.1:{self.server.port}"
        client = InternalClient(host, timeout=120.0)
        client.create_index(INDEX)
        client.create_frame(INDEX, FRAME)
        client.create_frame(INDEX, BSI_FRAME, {"rangeEnabled": True})
        client.create_field(INDEX, BSI_FRAME, BSI_FIELD, BSI_MIN, BSI_MAX)

        n_bits = 0
        mu = threading.Lock()

        def one(s: int) -> None:
            nonlocal n_bits
            rows, cols = self.oracle.make_slice(s)
            InternalClient(host, timeout=120.0).import_bits(
                INDEX, FRAME, s, (rows, cols)
            )
            with mu:
                n_bits += rows.size

        t0 = time.monotonic()
        with ThreadPoolExecutor(LOAD_THREADS) as pool:
            # list() reads every future: an import that failed raises here.
            list(pool.map(one, range(self.args.slices)))
        dt = time.monotonic() - t0
        for s in range(min(BSI_SLICES, self.args.slices)):
            cols, vals = self.oracle.make_values(s)
            client.import_value(INDEX, BSI_FRAME, BSI_FIELD, s, cols, vals)
        self.timings["load_s"] = round(dt, 3)
        self.report["load"] = {
            "columns": self.args.slices * SLICE_WIDTH,
            "rows": self.args.rows,
            "bits": n_bits,
            "bits_per_s": round(n_bits / dt),
            "bsi_values": len(self.oracle.values),
            "densities": "row0 2%, row1 1%, then 2e-4 * 0.9^(row-2)",
        }
        log(f"loaded {n_bits} bits over {self.args.slices} slices x "
            f"{self.args.rows} rows in {dt:.1f} s ({n_bits / dt:,.0f} bits/s)")

    def bsi_ways(self) -> tuple[int, int]:
        """How many BSI aggregates went in place and how many through
        the leaf batch, by the program's own counters."""
        m = self.server.metrics()
        return (int(m.get("pilosa_exec_bsi_inPlace_total", 0)),
                int(m.get("pilosa_exec_bsi_batch_total", 0)))

    def row_pairs(self) -> list[tuple[int, int]]:
        """Eight distinct row pairs over the two dense rows and a few
        sparse ones — more than the 4-entry batch cache holds, so the
        miss path and its host->device copy run."""
        return [(0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3), (0, 4), (1, 4)]

    def topn_src_query(self) -> str:
        return f"TopN({bitmap(self.src_row)}, frame={FRAME}, n={TOPN})"

    def storm(self, key: str, name: str, queries: list) -> None:
        """Fire ``queries`` at once, one client each; check every answer."""
        gate = threading.Barrier(len(queries))

        def fire(q):
            gate.wait(timeout=60)
            return self.server.query(pair_query(*q))

        t0 = time.monotonic()
        with ThreadPoolExecutor(len(queries)) as pool:
            answers = list(pool.map(fire, queries))
        self.timings[key] = round(time.monotonic() - t0, 3)
        bad = [
            f"{q}: got {g}, oracle says {self.oracle.count(*q)}"
            for q, g in zip(queries, answers) if g != self.oracle.count(*q)
        ]
        self.check(name, not bad, "; ".join(bad))

    def queries(self) -> None:
        srv, orc = self.server, self.oracle
        got = self.timed("first_answer_cold_s", lambda: srv.query(f"Count({bitmap(0)})"))
        self.expect("Count(Bitmap(0)), the first answer", got, orc.count("Bitmap", 0))
        log(f"first answer (cold) took {self.timings['first_answer_cold_s']} s")

        pairs = self.row_pairs()
        t0 = time.monotonic()
        for i, (a, b) in enumerate(pairs):
            ops = ("Intersect", "Union", "Difference") if i < 2 else ("Intersect",)
            for op in ops:
                self.expect(
                    f"Count({op}({a},{b}))", srv.query(pair_query(op, a, b)),
                    orc.count(op, a, b),
                )
        self.timings["distinct_pairs_s"] = round(time.monotonic() - t0, 3)

        # Sixteen at once over the eight distinct pairs: every one
        # misses the batch cache, so sixteen host assemblies and
        # host->device copies run side by side.
        self.storm(
            "concurrent_distinct_s", "16 concurrent Counts over 8 distinct pairs",
            [(op, a, b) for (a, b) in pairs for op in ("Intersect", "Union")],
        )
        # Queries meet in the coalescer only while a launch is in flight,
        # and at this size a launch takes a millisecond where a query's
        # host work takes from 0.2 s (an Intersect whose batch is cached:
        # the anchored-count route walks every slice before it declines)
        # to seconds (a missed batch): those never meet.  Cached Union
        # and Xor trees reach the coalescer in a millisecond or two, so
        # four such texts (two tree shapes: the interpreter needs
        # distinct programs to fuse) with eight clients each do meet.
        # Still chance, so a few waves at most.  Where no fused launch
        # fits the scratch budget the scheduler launches each tree's
        # own program and counts a fallback: that is then the sign that
        # they met, here and in chip_checks.
        texts = [(op, a, b) for (a, b) in pairs[:2] for op in ("Union", "Xor")]
        for q in texts:
            self.expect(f"Count({q[0]}({q[1]},{q[2]}))",
                        srv.query(pair_query(*q)), orc.count(*q))
        met = (
            "pilosa_exec_interp_launches_total" if self.fusion_fits()
            else "pilosa_exec_interp_fallbacks_total"
        )
        for wave in range(1, STORM_WAVES + 1):
            self.storm(
                f"concurrent_repeated_wave{wave}_s",
                f"32 concurrent Counts over 4 cached texts, wave {wave}", texts * 8,
            )
            if srv.metrics().get(met, 0) > 0:
                break
        self.report["repeated_storm_waves"] = wave

        got = self.timed("bitmap_fetch_s", lambda: srv.query(bitmap(self.fetch_row)))
        want = orc.columns(self.fetch_row)
        self.check(
            f"Bitmap(row {self.fetch_row}): {want.size} columns",
            np.array_equal(np.asarray(got["bits"], dtype=np.int64), want),
        )

        got = self.timed("topn_s", lambda: srv.query(f"TopN(frame={FRAME}, n={TOPN})"))
        self.expect(f"TopN(frame=f, n={TOPN})", pairs_of(got), orc.topn(TOPN))
        got = self.timed(
            "topn_src_cold_s", lambda: srv.query(self.topn_src_query())
        )
        self.expect(
            f"TopN(Bitmap({self.src_row}), frame=f, n={TOPN})",
            pairs_of(got), orc.topn(TOPN, src=self.src_row),
        )
        log(f"TopN(src) over the whole frame (cold) took "
            f"{self.timings['topn_src_cold_s']} s")

        vals = orc.vals()
        before = self.bsi_ways()
        got = srv.query(f"Sum(frame={BSI_FRAME}, field={BSI_FIELD})")
        self.expect(
            "Sum(frame=v, field=q)", (got["value"], got["count"]),
            (int(vals.sum()), int(vals.size)),
        )
        # Cold mirrors take the leaf batch where it fits the chip, as
        # here; the prefetcher, kicked as the query came in, may have
        # brought them by the time the aggregate looks, and then it goes
        # in place.  Either way it is one of the two, and the next check
        # sends a tree that takes the batch whatever is resident.
        first = self.bsi_ways()
        self.expect("Sum(frame=v, field=q), the field's planes cold, went one way",
                    sum(first) - sum(before), 1)
        got = srv.query(
            f"Sum(Union({TIME_RANGE}, Range(frame={BSI_FRAME}, {BSI_FIELD} > 100)), "
            f"frame={BSI_FRAME}, field={BSI_FIELD})"
        )
        self.expect("Sum(Union(Range(f, start, end), Range(q > 100)), frame=v, field=q)",
                    (got["value"], got["count"]),
                    (int(vals[vals > 100].sum()), int((vals > 100).sum())))
        after = self.bsi_ways()
        self.expect("a Sum under a time Range went through the leaf batch",
                    (after[0] - first[0], after[1] - first[1]), (0, 1))
        got = srv.query(f"Count(Range(frame={BSI_FRAME}, {BSI_FIELD} > 100))")
        self.expect("Count(Range(q > 100))", got, int((vals > 100).sum()))

    def fusion_fits(self) -> bool:
        """Whether the scheduler's scratch budget admits the smallest
        fused launch the storm can make (two programs over one pair of
        rows), by ``CoalesceScheduler._launch_interp``'s own arithmetic.
        At the default size it does on four chips (256 batch rows a
        device) and not on one (1,024)."""
        from pilosa_tpu.exec import coalesce, plan

        rows = plan.slice_bucket(self.args.slices) // self.device["count"]
        registers = 2 + plan.FUSE_OPS_FLOOR
        return (
            coalesce.FUSE_SCRATCH_FACTOR * rows * registers * (SLICE_WIDTH // 8)
            <= coalesce.MAX_FUSE_BYTES
        )

    def writes(self) -> None:
        """SetBit then ClearBit on a resident row, each read back twice:
        by a Count, and by the TopN whose scorer reads that row through
        the HBM mirror the delta-scatter maintains.  A second bit stays
        set for the restart to find."""
        srv, orc, r = self.server, self.oracle, self.src_row
        before = srv.get_json("/debug/ingest")["scatter"]

        def free_column(s: int) -> int:
            rng = np.random.default_rng([self.args.seed, 2_000_003, s])
            while True:
                col = s * SLICE_WIDTH + int(rng.integers(0, SLICE_WIDTH))
                if not orc.has_bit(r, col):
                    return col

        c1 = free_column(self.args.slices // 2)
        c2 = free_column(self.args.slices - 1)
        for what, verb, col, on in (
            ("SetBit", "SetBit", c1, True),
            ("ClearBit", "ClearBit", c1, False),
            ("the bit left set", "SetBit", c2, True),
        ):
            changed = srv.query(f"{verb}(frame={FRAME}, rowID={r}, columnID={col})")
            self.expect(f"{verb}(row {r}, column {col}) changed a bit", changed, True)
            orc.set_bit(r, col, on)
            self.expect(f"Count(Bitmap({r})) after {what}",
                        srv.query(f"Count({bitmap(r)})"), orc.count("Bitmap", r))
            self.expect(
                f"TopN(Bitmap({r}), frame=f, n={TOPN}) after {what}",
                pairs_of(srv.query(self.topn_src_query())), orc.topn(TOPN, src=r),
            )
        after = srv.get_json("/debug/ingest")["scatter"]
        self.report["scatter"] = after
        self.check(
            "delta-scatter launched for the point writes",
            after["launches"] > before["launches"],
            f"launches {before['launches']} -> {after['launches']}",
        )
        self.check(
            "no point write fell back to invalidating a mirror",
            after["fallbackInvalidations"] == before["fallbackInvalidations"],
            f"{before['fallbackInvalidations']} -> {after['fallbackInvalidations']}",
        )

    def repeated(self, label: str) -> None:
        """The queries both boots ask once every mirror is resident —
        at the end of the first, after the staging of the second — so
        that both take the same route to the device and the second
        finds every program in the compile cache.  Each text is new to
        the first boot's batch cache (the writes invalidated frame f's
        entries), so both boots assemble and launch, neither answers
        from a cached batch."""
        srv, orc, r = self.server, self.oracle, self.src_row
        self.expect(
            f"{label}: Count(Bitmap({r})) holds the acked write",
            srv.query(f"Count({bitmap(r)})"), orc.count("Bitmap", r),
        )
        for op, a, b in (("Intersect", 0, 1), ("Union", 0, 2), ("Difference", 0, 1)):
            self.expect(f"{label}: Count({op}({a},{b}))",
                        srv.query(pair_query(op, a, b)), orc.count(op, a, b))
        got = self.timed(
            f"{label}_topn_src_s", lambda: srv.query(self.topn_src_query())
        )
        self.expect(f"{label}: TopN(Bitmap({r}), frame=f, n={TOPN})",
                    pairs_of(got), orc.topn(TOPN, src=r))
        vals = orc.vals()
        before = self.bsi_ways()
        got = srv.query(
            f"Sum(Range(frame={BSI_FRAME}, {BSI_FIELD} > 0), "
            f"frame={BSI_FRAME}, field={BSI_FIELD})"
        )
        self.expect(f"{label}: Sum(Range(q > 0), frame=v, field=q)",
                    (got["value"], got["count"]),
                    (int(vals[vals > 0].sum()), int((vals > 0).sum())))
        # and the plain Sum, whose program a restart prewarms from the
        # holder's own fields: the first boot has to have compiled it
        got = srv.query(f"Sum(frame={BSI_FRAME}, field={BSI_FIELD})")
        self.expect(f"{label}: Sum(frame=v, field=q)", (got["value"], got["count"]),
                    (int(vals.sum()), int(vals.size)))
        after = self.bsi_ways()
        self.expect(
            f"{label}: the multi-slice Sums over resident planes went in place",
            (after[0] - before[0], after[1] - before[1]), (2, 0))

    def after_restart(self) -> None:
        srv, orc = self.server, self.oracle
        got = self.timed(
            "first_answer_after_restart_s", lambda: srv.query(f"Count({bitmap(0)})")
        )
        self.expect("boot2: Count(Bitmap(0)), the first answer", got,
                    orc.count("Bitmap", 0))
        log(f"first answer after the restart took "
            f"{self.timings['first_answer_after_restart_s']} s")
        # Cold staging streams every mirror back into HBM behind the
        # first answers; the repeated queries wait for it.
        t0 = time.monotonic()
        while True:
            staging = srv.get_json("/debug/hbm")["staging"]
            if (staging["scheduled"] and not staging["pending"]) or (
                time.monotonic() - t0 > 300
            ):
                break
            time.sleep(0.5)
        self.timings["staging_s"] = round(time.monotonic() - t0, 3)
        self.report["staging"] = staging
        self.check(
            "boot2: cold staging brought every mirror back",
            staging["scheduled"] > 0 and not staging["pending"]
            and not staging["errors"],
            json.dumps(staging),
        )
        self.repeated("boot2")

    # -- what makes it a check of the chip -----------------------------------

    def plane_bytes_by_device(self) -> dict[int, int]:
        """The dense planes the load made, by home device (slice mod n)."""
        n = self.device["count"]
        padded = max(ROW_BLOCK, 1 << (self.args.rows - 1).bit_length())
        out = {d: 0 for d in range(n)}
        for s in range(self.args.slices):
            out[s % n] += padded * ROW_BYTES
        return out

    def wait_prewarm(self, label: str) -> None:
        deadline = time.monotonic() + 600
        while True:
            pw = self.server.get_json("/debug/health").get("prewarm")
            if pw is None or pw["done"] or time.monotonic() > deadline:
                break
            time.sleep(1.0)
        self.report.setdefault("prewarm", {})[label] = pw
        self.check(
            f"{label}: prewarm compiled every standard program",
            bool(pw) and pw["done"] and pw["error"] is None and pw["programs"] > 0,
            json.dumps(pw),
        )

    def chip_checks(self, label: str, full: bool) -> None:
        """``full`` after the first boot's whole query set; after the
        restart only what the repeated subset reaches is required."""
        srv, n_dev = self.server, self.device["count"]
        on_chip = self.device["platform"] != "cpu"
        self.wait_prewarm(label)

        health = srv.get_json("/debug/health")["device"]
        metrics = srv.metrics()
        retries = metrics.get("pilosa_device_launch_retries_total", 0.0)
        snap = {
            "degraded": health["degraded"],
            "paths": {p: st["state"] for p, st in health["paths"].items()},
            "failures": {
                p: st["failures"] for p, st in health["paths"].items()
                if st.get("failures")
            },
            "watchdog_trips": health["watchdogTrips"],
            "launch_retries": int(retries),
        }
        self.report.setdefault("health", {})[label] = snap
        self.check(
            f"{label}: device healthy — no failure, retry, trip or quarantine",
            not snap["degraded"] and not snap["failures"]
            and snap["watchdog_trips"] == 0 and snap["launch_retries"] == 0
            and all(s == "healthy" for s in snap["paths"].values()),
            json.dumps(snap),
        )

        perf = srv.get_json("/debug/perf")["sites"]
        sites = {s: int(perf.get(s, {}).get("launches", 0)) for s in SITES}
        reduces = {s: perf[s]["reduces"] for s in perf}
        interp = int(metrics.get("pilosa_exec_interp_launches_total", 0))
        declined = int(metrics.get("pilosa_exec_interp_fallbacks_total", 0))
        self.report.setdefault("launches", {})[label] = dict(
            sites, fused_interpreter=interp, fuse_fallbacks=declined,
            fusion_fits=self.fusion_fits(), reduces=reduces,
        )
        self.check(
            f"{label}: no launch was answered by hosteval",
            sites["hosteval"] == 0, f"{sites['hosteval']} host evaluations",
        )
        need = {"topn": sites["topn"], "fetch": sites["fetch"],
                "agg (the in-place aggregate)": sites["agg"]}
        if n_dev > 1:
            # The ICI-reduced limb count (plan.compiled_total_count and
            # the interpreter's "total") records as site "collective".
            need["collective(total)"] = reduces.get("collective", {}).get("total", 0)
        else:
            need["coalesce(count)"] = reduces.get("coalesce", {}).get("count", 0)
        if full:
            need["coalesce(row)"] = reduces.get("coalesce", {}).get("row", 0)
            need["coalesce(agg)"] = reduces.get("coalesce", {}).get("agg", 0)
            if self.fusion_fits():
                need["fused interpreter"] = interp
            else:
                # Distinct programs met in the dispatcher and were
                # launched apart (the coalesce counts above are theirs).
                need["the interpreter's fallback past its budget"] = declined
        missing = [k for k, v in need.items() if not v]
        self.check(
            f"{label}: every program family the queries reach launched",
            not missing, "never launched: " + ", ".join(missing) if missing else "",
        )

        hbm = srv.get_json("/debug/hbm")
        want = self.plane_bytes_by_device()
        # /debug/hbm labels a device "<platform>:<id>".
        labelled = {d["device"]: d for d in hbm["devices"]}
        by_dev = {
            d: labelled.get(f"{self.device['platform']}:{d}", {})
            for d in range(n_dev)
        }
        mirror = {
            d: sum(e["bytes"] for e in by_dev[d].get("entries", [])
                   if e["kind"] == "mirror"
                   and e.get("fragment", "").startswith(f"{INDEX}/{FRAME}/"))
            for d in range(n_dev)
        }
        resident = {
            "budget_bytes": hbm["budget_bytes"],
            "resident_bytes": hbm["resident_bytes"],
            "per_device": {
                str(d): {
                    "plane_bytes_loaded": want[d],
                    "mirror_bytes_resident": mirror[d],
                    "resident_bytes": by_dev[d].get("resident_bytes", 0),
                } for d in range(n_dev)
            },
        }
        self.report.setdefault("hbm", {})[label] = resident
        if full:
            # The scorer read every fragment's mirror, so all of the
            # loaded planes are resident, each on its home device.
            self.check(
                f"{label}: the loaded planes are resident on "
                f"{'all ' + str(n_dev) + ' devices' if n_dev > 1 else 'the device'}",
                all(mirror[d] == want[d] for d in range(n_dev)),
                json.dumps(resident["per_device"]),
            )
        if on_chip:
            self.check(
                f"{label}: HBM budget detected from bytes_limit, not unbounded",
                hbm["budget_bytes"] > 0, f"budget_bytes={hbm['budget_bytes']}",
            )
            if full:
                self.check_bytes_in_use(label, mirror)
        else:
            log("skip the HBM budget and bytes_in_use checks: the cpu backend "
                "reports no memory_stats()")

        boot_log = srv.boot_log()
        found = [s for s in LOG_MUST_NOT_HAVE if s in boot_log]
        self.check(f"{label}: the server log carries no swallowed failure",
                   not found, ", ".join(found))

    def check_bytes_in_use(self, label: str, mirror: dict[int, int]) -> None:
        """The allocator's own figure (the runtime loop publishes it once
        a polling interval, 60 s) must cover what the pool says is
        resident."""
        deadline = time.monotonic() + 75
        in_use = {}
        while time.monotonic() < deadline:
            m = self.server.metrics()
            in_use = {
                d: int(m.get(f"pilosa_device_{d}_hbm_bytes_in_use", 0))
                for d in mirror
            }
            if all(in_use[d] >= mirror[d] > 0 for d in mirror):
                break
            time.sleep(3.0)
        for d, n in in_use.items():
            self.report["hbm"][label]["per_device"][str(d)]["hbm_bytes_in_use"] = n
        self.check(
            f"{label}: device.<i>.hbm_bytes_in_use covers the resident planes",
            all(in_use[d] >= mirror[d] > 0 for d in mirror),
            json.dumps({"in_use": in_use, "mirrors": mirror}),
        )

    def cache_entries(self) -> set[str]:
        if not self.cache_dir or not os.path.isdir(self.cache_dir):
            return set()
        # JAX keeps a "-atime" stamp beside each entry and rewrites it on
        # every hit; only the executables themselves count.
        return {n for n in os.listdir(self.cache_dir) if not n.endswith("-atime")}

    # -- main ----------------------------------------------------------------

    def run(self) -> None:
        from pilosa_tpu import native

        self.report["native_codec"] = native.available()
        log(f"native codec available: {self.report['native_codec']}")
        self.check("native codec built from roaring_native.cpp",
                   self.report["native_codec"])

        self.boot("boot1")
        cache_before = self.cache_entries()
        self.load()
        self.queries()
        self.writes()
        self.repeated("boot1")
        self.chip_checks("boot1", full=True)
        if self.failures:
            # Nothing a restart could add; do not spend the chip on it.
            self.server.stop()
            return

        t0 = time.monotonic()
        rc = self.server.stop()
        self.timings["shutdown_s"] = round(time.monotonic() - t0, 3)
        self.check("the server exits cleanly on SIGTERM", rc == 0, f"exit code {rc}")
        after_boot1 = self.cache_entries()
        self.check(
            "the first boot filled the compile cache",
            len(after_boot1) > 0,
            f"{len(after_boot1)} entries in {self.cache_dir}"
            f" ({len(cache_before)} before this run)",
        )

        self.boot("boot2")
        self.after_restart()
        self.chip_checks("boot2", full=False)
        self.server.stop()
        new = sorted(self.cache_entries() - after_boot1)
        self.report["compile_cache"] = {
            "dir": self.cache_dir,
            "entries_before_run": len(cache_before),
            "entries_after_boot1": len(after_boot1),
            "new_entries_in_boot2": new,
        }
        self.check(
            "the second boot compiled nothing the first had not cached",
            not new, f"{len(new)} new entries: {new[:6]}",
        )

    def close(self) -> None:
        self.server.kill()
        shutil.rmtree(self.data_dir, ignore_errors=True)


def jax_backend_in_this_process() -> bool:
    """Whether this (parent) process created a JAX backend — importing
    the client pulls ``jax`` in, which is harmless; initialising a
    backend would take the chip from the server."""
    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--slices", type=int, default=954,
                    help="2^20 columns each; 954 = 1B columns")
    ap.add_argument("--rows", type=int, default=32)
    ap.add_argument("--out", default=os.path.join(HERE, "chiprun_out", "chip_smoke"),
                    help="where the server log and result.json go")
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="accept the CPU backend (a sandbox without a chip; "
                    "use a tiny --slices) and report platform cpu")
    args = ap.parse_args()
    if args.rows < 8 or args.slices < 2:
        ap.error("need --rows >= 8 and --slices >= 2")

    platforms = os.environ.get("JAX_PLATFORMS", "")
    if not args.cpu_rehearsal and platforms and "tpu" not in platforms.split(","):
        print(f"chip_smoke: JAX_PLATFORMS={platforms!r} hides the TPU; "
              "nothing to check here", file=sys.stderr)
        return 2

    # Imported here, not at the top: beside chip_smoke.py alone, with no
    # package, this is where the script stops.
    import pilosa_tpu  # noqa: F401

    def on_alarm(_sig, _frame):
        raise SmokeError(f"not done after {TIME_LIMIT_S} s")

    signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(TIME_LIMIT_S)

    t0 = time.monotonic()
    run = Run(args)
    try:
        run.run()
    except SmokeError as e:
        run.failures.append(f"stopped: {e}")
        log(f"FAIL stopped: {e}")
    finally:
        signal.alarm(0)
        run.close()
    if jax_backend_in_this_process():
        run.failures.append("the parent process initialised a JAX backend")
    run.timings["total_s"] = round(time.monotonic() - t0, 3)

    report = {
        "ok": not run.failures,
        "device": run.device,
        "failures": run.failures,
        **run.report,
        "timings_s": run.timings,
        "claim": None,
    }
    with open(os.path.join(run.out_dir, "result.json"), "w") as f:
        json.dump(report, f, indent=1)
    # The report is the line before last; the last line is the verdict
    # alone, to the driver's contract: "ok" and "device", nothing else.
    print(json.dumps(report), flush=True)
    if not run.device:
        # The server never said what it runs on: there is no verdict to
        # give about a device, only the failures above.
        return 1
    print(json.dumps({"ok": report["ok"], "device": run.device}), flush=True)
    return 0 if report["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
