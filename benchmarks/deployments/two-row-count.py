"""The deployment kind ``two-row-count``: one index, one frame without
options, rows drawn from one density rule and loaded through ``/import``,
asked ``Count(<op>(Bitmap, Bitmap))``, answered with one integer.  A
configuration whose file names no ``kind`` is of this one.

**The plain reference.**  Numpy only; nothing of ``pilosa_tpu`` is
imported here and nothing the server produced is read.  A row is kept as
the sorted array of its set column ids (the data is sparse: tens of
millions of bits in a billion columns), and a Count over two rows is set
algebra on those arrays.  The server keeps dense bit planes, so the two
share no representation.

``broken`` turns the reference into the control of ``PERF.md`` §2: the
same arithmetic with one stated guarantee (bit-exact answers over every
slice) given up, which the comparison has to refuse.

**The requests.**  Keys of a mix beside those every kind shares
(``traffic.py``): ``read.template`` (PQL with ``{op} {frame} {a} {b}``)
and ``read.texts``: a list of ``[op, a, b]`` (``sources`` beside them
says where each comes from), or ``"distinct"`` with an ``op_deck``
(operator -> cards in the deck): the row pairs drawn without replacement
over every row that takes no write, every seed the same number of each
operator (a shuffled deck, not a coin).  ``write`` gives the writes'
``template`` (``{frame} {row} {col}``), ``rows`` (how many rows take
writes: the last rows of the configuration, which no read text names)
and ``column_zipf`` (the skew of the written column).
"""

from __future__ import annotations

import itertools

import numpy as np

from metrics import COUNT_SITES
from traffic import Mix, Request, deck, zipf_rank

OPS = ("Intersect", "Union", "Difference", "Xor")

# Launch sites of obs/perf.py on which a Count can ride: a run needs one
# launch there (``device_launches``).
SITES = COUNT_SITES

# The ways the control gives up the guarantee.  ``drop_last_slice``: a
# stale or short answer (the ragged last slice not counted).
# ``sample_slices``: an estimate, the even slices counted twice.
CONTROLS = ("drop_last_slice", "sample_slices")


def density(rule: dict, row: int) -> float:
    """Share of columns set in ``row`` under a configuration's rule:
    ``head`` lists the first rows, the rest fall from ``base`` by
    ``decay`` a row and never under ``floor``."""
    head = rule["head"]
    if row < len(head):
        return float(head[row])
    return max(rule["base"] * rule["decay"] ** (row - len(head)), rule["floor"])


def schema(config: dict) -> list[dict]:
    return [{"name": config["index"], "frames": [{"name": config["frame"]}]}]


def normalise(result):
    """A Count's answer is the integer the server sends."""
    return result


class Reference:
    def __init__(self, config: dict, seed: int):
        self.seed = int(seed)
        self.index, self.frame = config["index"], config["frame"]
        self.n_slices = int(config["slices"])
        self.n_rows = int(config["rows"])
        self.slice_width = int(config["slice_width"])
        self.densities = [density(config["density"], r) for r in range(self.n_rows)]
        self._parts: list[dict[int, np.ndarray]] = [{} for _ in range(self.n_rows)]
        self._rows: list[np.ndarray] | None = None
        self._written: set[int] = set()
        self.n_loaded = 0

    # -- data and load ------------------------------------------------------

    def units(self) -> range:
        """The units of load in order: one slice each."""
        return range(self.n_slices)

    def make(self, s: int) -> dict:
        """Generate slice ``s`` from the seed, keep it, and return it as
        the unit to import: the route and the ``rows`` / ``cols`` arrays."""
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

    def answer(self, key: tuple, broken: str | None = None) -> int:
        """A read's key is ``(op, a, b)``, or ``("Bitmap", a)``."""
        return self.count(*key, broken=broken)

    def apply(self, key: tuple) -> None:
        """An acknowledged write's key is ``(row, col)``."""
        self.set_bit(*key)
        self._written.add(key[0])

    def readback(self) -> list[Request]:
        """The reads that show every applied write: a Count of each row
        written to."""
        return [Request("read", f"Count(Bitmap(frame={self.frame}, rowID={row}))",
                        ("Bitmap", row)) for row in sorted(self._written)]

    def set_bit(self, row: int, col: int) -> bool:
        """Apply an acknowledged ``SetBit``; True if it changed a bit."""
        x = self._rows[row]
        i = int(np.searchsorted(x, np.uint64(col)))
        if i < x.size and int(x[i]) == col:
            return False
        self._rows[row] = np.insert(x, i, np.uint64(col))
        return True

    def _row(self, r: int, broken: str | None) -> np.ndarray:
        x = self._rows[r]
        if broken is None:
            return x
        s = x // np.uint64(self.slice_width)
        if broken == "drop_last_slice":
            return x[s != np.uint64(self.n_slices - 1)]
        if broken == "sample_slices":
            return x[s % np.uint64(2) == 0]
        raise ValueError(f"unknown control {broken!r}")

    def count(self, op: str, a: int, b: int | None = None,
              broken: str | None = None) -> int:
        x = self._row(a, broken)
        if op == "Bitmap":
            n = int(x.size)
        else:
            y = self._row(b, broken)
            small, big = (x, y) if x.size <= y.size else (y, x)
            if big.size:
                at = np.minimum(np.searchsorted(big, small), big.size - 1)
                both = int(np.count_nonzero(big[at] == small))
            else:
                both = 0
            if op == "Intersect":
                n = both
            elif op == "Union":
                n = int(x.size + y.size - both)
            elif op == "Difference":
                n = int(x.size - both)
            elif op == "Xor":
                n = int(x.size + y.size - 2 * both)
            else:
                raise ValueError(op)
        return 2 * n if broken == "sample_slices" else n


class Traffic(Mix):
    def __init__(self, mix: dict, config: dict, seed: int):
        super().__init__(mix, config, seed)
        self.frame = config["frame"]
        rng = self.rng
        write = mix.get("write") or {}
        n_rows = int(config["rows"])
        self.write_rows = list(range(n_rows - int(write.get("rows", 0)), n_rows))
        read_rows = n_rows - len(self.write_rows)
        read = mix["read"]
        warm = mix.get("warmup", {})

        request = self._request
        if self.fixed:
            self._reads = [request(*t) for t in read["texts"]]
            self._warm = self._reads
        else:
            pairs = [(a, b) for a in range(read_rows) for b in range(read_rows)
                     if a != b]
            pairs = [pairs[i] for i in rng.permutation(len(pairs))]
            ops = deck(read["op_deck"], len(pairs), rng)
            # The warm-up's own texts come off the far end: the window
            # starts at the near one and never gets there (it raises).
            # Their operators go round the deck's kinds, so that every
            # operator's program is compiled before the window.
            n = int(warm.get("fresh_texts", self.clients))
            kinds = itertools.cycle(read["op_deck"])
            self._warm = [request(op, a, b)
                          for op, (a, b) in zip(kinds, pairs[len(pairs) - n:])]
            self._reads = [request(op, a, b)
                           for op, (a, b) in zip(ops, pairs[: len(pairs) - n])]
        self._write = write
        self._n_columns = int(config["slices"]) * int(config["slice_width"])

    def _request(self, op: str, a: int, b: int) -> Request:
        text = self.mix["read"]["template"].format(op=op, frame=self.frame, a=a, b=b)
        return Request("read", text, (op, a, b))

    def write_request(self) -> Request:
        n = self._n_columns
        rank = zipf_rank(float(self.rng.random()), n,
                         float(self._write.get("column_zipf", 0.0)))
        # Spread the hot ranks over the column space.
        col = (rank * 2_654_435_761) % n
        row = self.write_rows[int(self.rng.integers(len(self.write_rows)))]
        text = self._write["template"].format(frame=self.frame, row=row, col=col)
        return Request("write", text, (row, col))
