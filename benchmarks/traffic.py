"""The one general traffic generator.

A mix is a data file, ``traffic/<mix>.json``; this module reads what is
the same for every deployment (the loop, the clients, the warm-up's
rounds, the open loop's schedule) and drives ``POST /index/<i>/query``.
What a request says is the deployment kind's (``deployments/<kind>.py``):
its ``Traffic`` is a :class:`Mix` that fills in the reads, the warm-up's
texts and, where the mix writes, :meth:`Mix.write_request`.  Everything a
run sends is drawn from ``--seed`` before the window opens, and a seed
changes the order of the work and not the work.

Keys of a mix that every kind shares (a kind's docstring lists its own,
under ``read`` and ``write``):

``loop``         ``closed`` (``clients`` connections, each waits for its
                 reply) or ``open`` (``rate_per_s`` requests a second on
                 a fixed schedule, at most ``clients`` in flight).
``read.texts``   a list (fixed texts, named in the file and cycled) or
                 ``"distinct"`` (no text is sent twice in a run).
``write_share``  share of requests that are writes (open loop only).
``warmup``       ``rounds`` of ``clients`` concurrent requests before the
                 window.  A fixed mix cycles its texts.  A distinct mix
                 has ``fresh_texts`` texts of its own for them (none is
                 sent in the window) and then ``repeat_rounds`` /
                 ``repeat_last``: see :meth:`Mix.warmup_rounds`.
"""

from __future__ import annotations

import itertools
import json
import threading
import time
import uuid

import numpy as np

from server import DEADLINE_MS


class Request:
    __slots__ = ("kind", "text", "key", "due")

    def __init__(self, kind: str, text: str, key: tuple, due: float | None = None):
        self.kind = kind  # "read" | "write"
        self.text = text
        self.key = key  # what the kind's reference answers or applies
        self.due = due  # open loop: seconds after the window opens


class Record:
    """One request as the client saw it.  ``sent`` and ``done`` are wall
    clock (the server's spans carry wall-clock starts); ``latency_s`` is
    from a monotonic clock, and in an open loop runs from ``due``."""

    __slots__ = ("client", "req", "sent", "done", "latency_s", "late_s",
                 "status", "answer", "trace_id")


def deck(cards: dict[str, int], n: int, rng) -> list[str]:
    """``n`` draws, every whole deck holding each card its stated number
    of times."""
    pile = [name for name, k in cards.items() for _ in range(int(k))]
    out: list[str] = []
    while len(out) < n:
        out.extend(rng.permutation(pile).tolist())
    return out[:n]


def zipf_rank(u: float, n: int, theta: float) -> int:
    """The rank in ``[0, n)`` that a uniform draw ``u`` falls on under a
    Zipf law of skew ``theta`` (0: uniform)."""
    if theta <= 0.0:
        rank = int(u * n)
    elif abs(theta - 1.0) < 1e-9:
        rank = int(n ** u) - 1
    else:
        rank = int(((n ** (1 - theta) - 1) * u + 1) ** (1 / (1 - theta))) - 1
    return min(max(rank, 0), n - 1)


class Mix:
    """What :class:`Load` drives, and what every kind's requests share.
    A kind's ``Traffic(mix, config, seed)`` calls this initialiser, then
    fills ``_reads`` (the window's reads in order) and ``_warm`` (the
    warm-up's texts) with draws from ``rng``, and overrides
    :meth:`write_request` where its mixes write."""

    def __init__(self, mix: dict, config: dict, seed: int):
        self.mix = mix
        self.index = config["index"]
        self.clients = int(mix["clients"])
        self.loop = mix["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop must be closed or open, not {self.loop!r}")
        self.rng = np.random.default_rng([int(seed), 7_000_003])
        self.write_share = float(mix.get("write_share", 0.0))
        self.fixed = mix["read"]["texts"] != "distinct"
        self.warm_rounds = int(mix.get("warmup", {}).get("rounds", 1))
        self._reads: list[Request] = []
        self._warm: list[Request] = []

    # -- what is sent -------------------------------------------------------

    def warmup_rounds(self) -> list[list[Request]]:
        """Rounds of ``clients`` concurrent requests.  A fixed mix cycles
        its texts.  A distinct mix sends its fresh texts once (the miss
        path and each operator's program), then ``repeat_rounds`` rounds
        over the last ``repeat_last`` of them: those sit in the batch
        cache and reach the coalescer close together, which compiles
        some of the programs that queries meeting in the window need
        (not all: ``PERF.md``, Open questions)."""
        warm = self.mix.get("warmup", {})
        if self.fixed:
            it = itertools.cycle(self._warm)
            return [list(itertools.islice(it, self.clients))
                    for _ in range(self.warm_rounds)]
        it = iter(self._warm)
        rounds = [list(itertools.islice(it, self.clients))
                  for _ in range(self.warm_rounds)]
        last = self._warm[len(self._warm) - int(warm.get("repeat_last", 4)):]
        for _ in range(int(warm.get("repeat_rounds", 0))):
            rounds.append([last[i % len(last)] for i in range(self.clients)])
        return [r for r in rounds if r]

    def read(self, i: int, client: int = 0) -> Request:
        if self.fixed:
            return self._reads[(i + client) % len(self._reads)]
        if i >= len(self._reads):
            raise IndexError(
                f"the mix ran out of distinct texts after {len(self._reads)}"
            )
        return self._reads[i]

    def write_request(self) -> Request:
        raise ValueError(f"this kind sends no writes: write_share = {self.write_share}")

    def schedule(self, seconds: float) -> list[Request]:
        """Open loop: every request of the window with its due time."""
        rate = float(self.mix["rate_per_s"])
        n = int(rate * seconds)
        n_writes = int(round(n * self.write_share))
        kinds = ["write"] * n_writes + ["read"] * (n - n_writes)
        kinds = [kinds[i] for i in self.rng.permutation(n)]
        out, reads = [], 0
        for i, kind in enumerate(kinds):
            if kind == "write":
                req = self.write_request()
            else:
                base = self.read(reads)
                req = Request("read", base.text, base.key)
                reads += 1
            req.due = i / rate
            out.append(req)
        return out


# ---------------------------------------------------------------------------
# the load
# ---------------------------------------------------------------------------


class Load:
    """Sends requests over keep-alive connections, one per client thread,
    and keeps a :class:`Record` of each."""

    def __init__(self, server, index: str, traced: bool):
        self.server = server
        self.path = f"/index/{index}/query"
        self.traced = traced
        self.records: list[Record] = []
        self._mu = threading.Lock()
        self._errors: list[BaseException] = []

    def _guard(self, fn):
        """A client thread's exception is the run's: kept, raised after
        the join."""
        def run(*args):
            try:
                fn(*args)
            except Exception as e:  # noqa: BLE001 — re-raised by _join
                self._errors.append(e)
        return run

    def _join(self, threads) -> None:
        for t in threads:
            t.join()
        if self._errors:
            raise self._errors[0]

    def _send(self, conn, client: int, req: Request, due_mono: float | None) -> Record:
        rec = Record()
        rec.client, rec.req = client, req
        headers = {"X-Deadline-Ms": str(DEADLINE_MS)}
        rec.trace_id = ""
        if self.traced:
            # The server continues a trace whose id it is given, so the
            # client can find its own request at /debug/traces.
            rec.trace_id = uuid.uuid4().hex
            headers["X-Trace-Id"] = rec.trace_id
        t0 = time.monotonic()
        rec.sent = time.time()
        rec.late_s = 0.0 if due_mono is None else max(0.0, t0 - due_mono)
        try:
            conn.request("POST", self.path, body=req.text.encode(), headers=headers)
            resp = conn.getresponse()
            data = resp.read()
            rec.status = resp.status
            doc = json.loads(data) if resp.status == 200 else {}
            rec.answer = None if doc.get("error") else doc.get("results", [None])[0]
        except (OSError, ValueError) as e:
            rec.status, rec.answer = -1, repr(e)
            conn.close()
        t1 = time.monotonic()
        rec.done = rec.sent + (t1 - t0)
        rec.latency_s = t1 - (t0 if due_mono is None else due_mono)
        return rec

    def round(self, reqs: list[Request]) -> list[Record]:
        """Fire ``reqs`` at once, one connection each (the warm-up)."""
        out: list[Record | None] = [None] * len(reqs)

        def one(i: int) -> None:
            conn = self.server.connect()
            try:
                out[i] = self._send(conn, i, reqs[i], None)
            finally:
                conn.close()

        threads = [threading.Thread(target=one, args=(i,)) for i in range(len(reqs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return out

    def _clients(self, n: int, body, lead_s: float = 0.0) -> tuple[float, float]:
        """``n`` client threads, each with a keep-alive connection, run
        ``body(c, conn, t0, keep)`` from a common start ``t0`` (monotonic).
        Returns the wall-clock window: the start, and the last reply."""
        start = threading.Barrier(n + 1)
        t0 = [0.0]

        def client(c: int) -> None:
            conn = self.server.connect()
            mine: list[Record] = []
            try:
                start.wait()
                body(c, conn, t0[0], mine.append)
            finally:
                conn.close()
                with self._mu:
                    self.records.extend(mine)

        threads = [threading.Thread(target=self._guard(client), args=(c,))
                   for c in range(n)]
        for t in threads:
            t.start()
        t0[0] = time.monotonic() + lead_s
        w0 = time.time() + lead_s
        start.wait()
        self._join(threads)
        return w0, max([r.done for r in self.records], default=w0)

    def closed(self, traffic: Mix, seconds: float) -> tuple[float, float]:
        """``clients`` closed loops for ``seconds``; a request in flight
        at the close is waited for."""
        if traffic.write_share > 0:
            raise ValueError("a closed loop sends no writes: say loop = open")
        counter = itertools.count()

        def body(c, conn, t0, keep) -> None:
            for k in itertools.count():
                if time.monotonic() >= t0 + seconds:
                    return
                req = traffic.read(k if traffic.fixed else next(counter), c)
                keep(self._send(conn, c, req, None))

        return self._clients(traffic.clients, body)

    def open(self, traffic: Mix, seconds: float) -> tuple[float, float]:
        """Requests on a fixed schedule whatever the replies do; latency
        runs from the time a request was due."""
        plan = traffic.schedule(seconds)
        nxt = itertools.count()

        def body(c, conn, t0, keep) -> None:
            while (i := next(nxt)) < len(plan):
                due = t0 + plan[i].due
                wait = due - time.monotonic()
                if wait > 0:
                    time.sleep(wait)
                keep(self._send(conn, c, plan[i], due))

        return self._clients(traffic.clients, body, lead_s=0.05)

    def run(self, traffic: Mix, seconds: float) -> tuple[float, float]:
        return (self.open if traffic.loop == "open" else self.closed)(traffic, seconds)
