"""A second deployment kind, for the tests alone: it shows that the
harness takes a kind it has never seen with no edit outside this
directory.  One index with a ranked-cache frame loaded through
``/import`` and a ``rangeEnabled`` frame with one BSI field loaded
through ``/import-value``; the reads are ``TopN(Bitmap(...), frame=...,
n=...)`` (a list-valued answer) and ``Sum(Range(...), ...)`` (a
dict-valued one).  Numpy only; nothing of ``pilosa_tpu`` is imported.

Keys of a mix's ``read.texts``: ``["TopN", row, n]`` and
``["Sum", op, value]`` (``op`` one of ``>``, ``<``, ``>=``, ``<=``).
"""

from __future__ import annotations

import numpy as np

from traffic import Mix, Request

# Launch sites of obs/perf.py that a TopN scorer and a BSI aggregate ride.
SITES = ("topn", "coalesce", "direct")

# The control gives up "every answer is over every slice": the last
# slice's bits and values are not counted.
CONTROLS = ("drop_last_slice",)

COMPARE = {">": np.greater, "<": np.less, ">=": np.greater_equal, "<=": np.less_equal}


def schema(config: dict) -> list[dict]:
    t, v = config["tags"], config["values"]
    return [{
        "name": config["index"],
        "frames": [
            {"name": t["frame"],
             "options": {"cacheType": "ranked", "cacheSize": t["cache_size"]}},
            {"name": v["frame"], "options": {"rangeEnabled": True},
             "fields": [{"name": v["field"], "min": v["min"], "max": v["max"]}]},
        ],
    }]


def normalise(result):
    """TopN: the ``(id, count)`` pairs in the order the server ranks
    them (count falling; equal counts by id).  Sum: ``(value, count)``."""
    if isinstance(result, list):
        return [(p["id"], p["count"]) for p in result]
    if isinstance(result, dict):
        return (result["value"], result["count"])
    return result


class Reference:
    def __init__(self, config: dict, seed: int):
        self.config, self.seed = config, int(seed)
        self.n_slices = int(config["slices"])
        self.width = int(config["slice_width"])
        self._bits: dict[int, dict] = {}
        self._values: dict[int, dict] = {}
        self.n_loaded = 0

    # -- data and load ------------------------------------------------------

    def units(self) -> list[tuple[str, int]]:
        return [(what, s) for s in range(self.n_slices) for what in ("tags", "values")]

    def make(self, unit: tuple[str, int]) -> dict:
        what, s = unit
        spec = self.config[what]
        rng = np.random.default_rng([self.seed, s, what == "values"])
        base = {"index": self.config["index"], "frame": spec["frame"], "slice": s}
        first = s * self.width
        if what == "tags":
            rows, cols = [], []
            for r, k in enumerate(spec["bits_per_row"]):
                offs = np.unique(rng.integers(0, spec["columns_used"], size=k))
                rows.append(np.full(offs.size, r, dtype=np.uint64))
                cols.append(offs.astype(np.uint64) + np.uint64(first))
            u = {**base, "route": "import",
                 "rows": np.concatenate(rows), "cols": np.concatenate(cols)}
            self._bits[s] = u
            return u
        columns = first + np.unique(rng.integers(0, spec["columns_used"],
                                                 size=spec["valued"])).astype(np.int64)
        values = rng.integers(spec["min"], spec["max"] + 1, size=columns.size)
        u = {**base, "route": "import-value", "field": spec["field"],
             "columns": columns, "values": values}
        self._values[s] = u
        return u

    def seal(self) -> None:
        self.n_loaded = (sum(u["rows"].size for u in self._bits.values())
                         + sum(u["values"].size for u in self._values.values()))

    # -- answers -----------------------------------------------------------

    def _slices(self, broken: str | None) -> range:
        if broken is None:
            return range(self.n_slices)
        if broken == "drop_last_slice":
            return range(self.n_slices - 1)
        raise ValueError(f"unknown control {broken!r}")

    def answer(self, key: tuple, broken: str | None = None):
        call, a, b = key
        slices = self._slices(broken)
        if call == "TopN":
            counts: dict[int, int] = {}
            for s in slices:
                rows, cols = self._bits[s]["rows"], self._bits[s]["cols"]
                src = cols[rows == a]
                for r in np.unique(rows).tolist():
                    both = np.intersect1d(cols[rows == r], src, assume_unique=True).size
                    counts[r] = counts.get(r, 0) + int(both)
            ranked = sorted(((r, c) for r, c in counts.items() if c > 0),
                            key=lambda p: (-p[1], p[0]))
            return ranked[:b]
        if call == "Sum":
            total = n = 0
            for s in slices:
                values = self._values[s]["values"]
                hit = COMPARE[a](values, b)
                total += int(values[hit].sum())
                n += int(np.count_nonzero(hit))
            return (total, n)
        raise ValueError(call)

    def apply(self, key: tuple) -> None:
        raise ValueError("this kind sends no writes")

    def readback(self) -> list[Request]:
        return []


class Traffic(Mix):
    def __init__(self, mix: dict, config: dict, seed: int):
        super().__init__(mix, config, seed)
        if not self.fixed:
            raise ValueError("this kind's mixes name their texts")
        t, v = config["tags"], config["values"]
        self._reads = []
        for call, a, b in mix["read"]["texts"]:
            if call == "TopN":
                text = (f"TopN(Bitmap(frame={t['frame']}, rowID={a}), "
                        f"frame={t['frame']}, n={b})")
            else:
                text = (f"Sum(Range(frame={v['frame']}, {v['field']} {a} {b}), "
                        f"frame={v['frame']}, field={v['field']})")
            self._reads.append(Request("read", text, (call, a, b)))
        self._warm = self._reads
