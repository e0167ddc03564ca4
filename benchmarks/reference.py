"""The plain reference: data from the seed and the answers to it.

Numpy only; nothing of ``pilosa_tpu`` is imported here and nothing the
server produced is read.  A row is kept as the sorted array of its set
column ids (the data is sparse: tens of millions of bits in a billion
columns), and a Count over two rows is set algebra on those arrays.
The server keeps dense bit planes, so the two share no representation.

``broken`` turns the reference into the control of ``PERF.md`` §2: the
same arithmetic with one stated guarantee (bit-exact answers over every
slice) given up, which the comparison has to refuse.
"""

from __future__ import annotations

import numpy as np

OPS = ("Intersect", "Union", "Difference", "Xor")

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


class Reference:
    def __init__(self, seed: int, n_slices: int, n_rows: int, slice_width: int,
                 rule: dict):
        self.seed = int(seed)
        self.n_slices = n_slices
        self.n_rows = n_rows
        self.slice_width = slice_width
        self.densities = [density(rule, r) for r in range(n_rows)]
        self._parts: list[dict[int, np.ndarray]] = [{} for _ in range(n_rows)]
        self._rows: list[np.ndarray] | None = None
        self.n_bits = 0

    def make_slice(self, s: int) -> tuple[np.ndarray, np.ndarray]:
        """Generate slice ``s`` from the seed, keep it, and return the
        ``(rowIDs, columnIDs)`` to import."""
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
        return np.concatenate(rows), np.concatenate(cols)

    def seal(self) -> None:
        """After every slice is made: one sorted array per row."""
        self._rows = [
            np.concatenate([parts[s] for s in sorted(parts)])
            if parts else np.zeros(0, dtype=np.uint64)
            for parts in self._parts
        ]
        self._parts = []
        self.n_bits = int(sum(r.size for r in self._rows))

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
