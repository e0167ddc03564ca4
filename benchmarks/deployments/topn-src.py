"""The deployment kind ``topn-src``: one index, one frame with a ranked
cache, rows drawn from one density rule and loaded through ``/import``,
asked ``TopN(Bitmap(frame, rowID=src), frame, n)`` and ``TopN(frame, n)``,
answered with a ranked list of ``{"id", "count"}`` pairs.

**The plain reference.**  Numpy and the standard library only; nothing
of ``pilosa_tpu`` is imported and nothing the server produced is read.
A row is kept as the sorted array of its set column ids.  The answer to
``("TopN", src, n)`` is, for every row, the size of its intersection
with row ``src`` over all slices (``src`` ``None``: the row's own
cardinality), ranked by count falling and equal counts by id rising,
pairs with count 0 left out, the first ``n``.  That is the exact ranking:
the server's ranked caches and its two-phase protocol have to equal it
where, as the configuration states, ``n`` and ``cacheSize`` are at least
the number of rows.  Each key is computed once.

``broken`` turns the reference into the control: the same arithmetic
with one stated guarantee given up, which the comparison has to refuse.

**The data** is ``two-row-count``'s own rule, copied (a kind stands
alone): the same seed gives the same bits as ``segment-1b``.

**The requests.**  Keys of a mix beside those every kind shares
(``traffic.py``): ``read.template`` (PQL with ``{frame} {src} {n}``),
``read.template_plain`` (``{frame} {n}``) and ``read.texts``, a list of
``[src, n]`` with ``src`` ``null`` for the plain text.  The list is dealt
to the clients in turn (request ``j`` of the window is text ``j`` mod the
list's length: :meth:`Traffic.read`), so a text returns after as many
requests as the list is long.  ``traffic.Mix`` would have every client
walk the whole list one step behind the next, and each text would be
asked ``clients`` times within as many request times: fine for three
cached Counts, but a TopN's prep and scores are memoised for 10 s, and
the cell is there to measure the scorer, not the memo.
"""

from __future__ import annotations

import os

import numpy as np

from server import HarnessError
from traffic import Mix, Request

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Launch site of obs/perf.py that the fused TopN scorer rides.
SITES = ("topn",)

# The ways the control gives up "every count is over every column of
# every slice".  ``drop_last_slice``: the last slice not counted.
# ``stale_cache``: counts from before the last tenth of the slices was
# loaded, what a ranked cache that was never recalculated would say.
CONTROLS = ("drop_last_slice", "stale_cache")


def density(rule: dict, row: int) -> float:
    """Share of columns set in ``row`` under a configuration's rule:
    ``head`` lists the first rows, the rest fall from ``base`` by
    ``decay`` a row and never under ``floor``."""
    head = rule["head"]
    if row < len(head):
        return float(head[row])
    return max(rule["base"] * rule["decay"] ** (row - len(head)), rule["floor"])


def schema(config: dict) -> list[dict]:
    options = {"cacheType": "ranked", "cacheSize": int(config["cache_size"])}
    return [{"name": config["index"],
             "frames": [{"name": config["frame"], "options": options}]}]


def normalise(result):
    """The ``(id, count)`` pairs in the order the server ranks them."""
    return [(p["id"], p["count"]) for p in result]


def program_can_serve(config: dict, root: str = ROOT) -> None:
    """Refuse at once a program that cannot serve the configuration.
    Its ``needs`` names a file of the program and a text that file has
    to hold, read as text (nothing of the program is imported): for
    ``segment-1b-topn`` the scorer whose program is bounded in operands.
    The program before it compiles one operand per slice for over a
    minute under the cell's warm-up and answers 500 two minutes into
    the run (``PERF.md``, PR 29): a run that can only fail says so
    before it boots a server."""
    for need in config.get("needs", ()):
        path = os.path.join(root, need["file"])
        try:
            with open(path) as f:
                held = need["text"] in f.read()
        except OSError:
            held = False
        if not held:
            raise HarnessError(
                f"this program cannot serve {config.get('name')!r}: {need['file']} "
                f"lacks {need['text']!r} ({need['why']})")


class Reference:
    def __init__(self, config: dict, seed: int):
        program_can_serve(config)
        self.seed = int(seed)
        self.index, self.frame = config["index"], config["frame"]
        self.n_slices = int(config["slices"])
        self.n_rows = int(config["rows"])
        self.slice_width = int(config["slice_width"])
        self.densities = [density(config["density"], r) for r in range(self.n_rows)]
        self._parts: list[dict[int, np.ndarray]] = [{} for _ in range(self.n_rows)]
        self._rows: list[np.ndarray] = []
        self._answers: dict[tuple, list] = {}
        self.n_loaded = 0

    # -- data and load ------------------------------------------------------

    def units(self) -> range:
        """The units of load in order: one slice each."""
        return range(self.n_slices)

    def make(self, s: int) -> dict:
        """Generate slice ``s`` from the seed, keep it, and return it as
        the unit to import."""
        rng = np.random.default_rng([self.seed, s])
        rows, cols = [], []
        base = np.uint64(s * self.slice_width)
        for r, p in enumerate(self.densities):
            k = rng.binomial(self.slice_width, p)
            offs = np.unique(rng.integers(0, self.slice_width, size=k)).astype(np.uint64)
            offs += base
            self._parts[r][s] = offs
            rows.append(np.full(offs.size, r, dtype=np.uint64))
            cols.append(offs)
        return {"route": "import", "index": self.index, "frame": self.frame,
                "slice": s, "rows": np.concatenate(rows), "cols": np.concatenate(cols)}

    def seal(self) -> None:
        """After every slice is made: one sorted array per row."""
        self._rows = [
            np.concatenate([parts[s] for s in sorted(parts)])
            if parts else np.zeros(0, dtype=np.uint64)
            for parts in self._parts
        ]
        self._parts = []
        self.n_loaded = int(sum(r.size for r in self._rows))

    # -- answers -----------------------------------------------------------

    def _counted_slices(self, broken: str | None) -> int:
        """How many of the slices, from the first, a count is over."""
        if broken is None:
            return self.n_slices
        if broken == "drop_last_slice":
            return self.n_slices - 1
        if broken == "stale_cache":
            return self.n_slices - max(1, self.n_slices // 10)
        raise ValueError(f"unknown control {broken!r}")

    def answer(self, key: tuple, broken: str | None = None) -> list:
        """A read's key is ``("TopN", src, n)``; ``src`` is a row id or
        ``None``."""
        if (key, broken) not in self._answers:
            call, src, n = key
            if call != "TopN":
                raise ValueError(call)
            end = np.uint64(self._counted_slices(broken) * self.slice_width)
            rows = [x[: int(np.searchsorted(x, end))] for x in self._rows]
            if src is None:
                counts = [int(x.size) for x in rows]
            else:
                # a row the index does not hold is an empty bitmap
                y = rows[src] if src < len(rows) else rows[0][:0]
                counts = [_both(x, y) for x in rows]
            ranked = sorted(((r, c) for r, c in enumerate(counts) if c > 0),
                            key=lambda p: (-p[1], p[0]))
            self._answers[(key, broken)] = ranked[:n]
        return self._answers[(key, broken)]

    def apply(self, key: tuple) -> None:
        raise ValueError("this kind sends no writes")

    def readback(self) -> list[Request]:
        return []


def _both(x: np.ndarray, y: np.ndarray) -> int:
    """Size of the intersection of two sorted arrays of distinct ids."""
    small, big = (x, y) if x.size <= y.size else (y, x)
    if not small.size:
        return 0
    at = np.minimum(np.searchsorted(big, small), big.size - 1)
    return int(np.count_nonzero(big[at] == small))


class Traffic(Mix):
    def __init__(self, mix: dict, config: dict, seed: int):
        super().__init__(mix, config, seed)
        if not self.fixed:
            raise ValueError("this kind's mixes name their texts")
        read = mix["read"]
        frame = config["frame"]
        self._reads = []
        for src, n in read["texts"]:
            if src is None:
                text = read["template_plain"].format(frame=frame, n=n)
            else:
                text = read["template"].format(frame=frame, src=src, n=n)
            self._reads.append(Request("read", text, ("TopN", src, n)))
        self._warm = self._reads

    def read(self, i: int, client: int = 0) -> Request:
        """Client ``client``'s ``i``-th request: the list dealt in turn."""
        return self._reads[(i * self.clients + client) % len(self._reads)]
