"""The deployment kind ``ssb-q1``: the Star Schema Benchmark's query
flight 1 (O'Neil, O'Neil, Chen, *Star Schema Benchmark*, rev. 3, 2009)
on the flat ``lineorder``: one index whose columns are ``lineorder``
rows, the measures as BSI fields of one ``rangeEnabled`` frame loaded
through ``/import-value``, the ``date`` dimension's attributes as plain
frames (a row a year, a month of the year, a week of the year) loaded
through ``/import``, asked

    select sum(lo_extendedprice * lo_discount) from lineorder, date
    where lo_orderdate = d_datekey and <date restriction>
      and lo_discount between a and b and lo_quantity <range>

as ``Sum(Intersect(Bitmap(...), Range(...), Range(...)), frame, field)``
and answered with ``{"value", "count"}``.

**The plain reference.**  Numpy and the standard library only; nothing
of ``pilosa_tpu`` is imported and nothing the server produced is read.
The loaded ``lineorder`` rows are kept as plain columns (an array a
measure, an array a date attribute); an answer is a boolean mask over
them and an ``int64`` sum.  The server keeps bit-sliced planes, so the
two share no representation.  ``broken`` turns the reference into the
control: the same arithmetic with one stated guarantee given up.

**The data**, per slice from ``[seed, slice]``, by dbgen's rules as far
as they are known here (the configuration's ``assumed`` lists each rule
set from memory): ``lo_quantity`` uniform on [1, 50], ``lo_discount``
uniform on [0, 10], ``lo_extendedprice`` = quantity x the part's retail
price, the order date uniform over the ``date`` table's days.  The
product ``lo_extendedprice * lo_discount`` is stored as a field of its
own (``Sum`` takes one field).  ``rows_loaded_per_slice`` rows a slice
are loaded, on distinct columns drawn over the slice's share of the
600,037,902 columns (the last slice is ragged).

**The requests.**  Keys of a mix beside those every kind shares
(``traffic.py``): ``read.templates``, the PQL of each of the flight's
three queries with its constants as ``{...}``; ``read.paper``, the
constants the paper prints, which lead the warm-up; ``read.texts`` is
``"distinct"``: every combination of a template's constants is one
text, each template's combinations are shuffled from the seed and the
three piles dealt in turn, 1 : 1 : 1, until the smallest runs out.
``warmup.fresh_texts`` texts (the paper's three first) are the
warm-up's own and are not sent in the window.
"""

from __future__ import annotations

import itertools
import os
import threading

import numpy as np

from server import HarnessError
from traffic import Mix, Request

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Launch sites of obs/perf.py that a BSI aggregate rides: the in-place
# aggregate over the resident planes, and the leaf batch's programs.
SITES = ("agg", "coalesce", "direct", "interp")

# The ways the control gives up a stated guarantee.  ``drop_last_slice``:
# "over every valued column of every slice" (the ragged last slice not
# summed).  ``between_exclusive``: "``><`` is inclusive at both ends".
CONTROLS = ("drop_last_slice", "between_exclusive")

DATE_ATTRS = ("d_year", "d_monthnuminyear", "d_weeknuminyear")
MEASURES = ("lo_quantity", "lo_discount", "lo_discounted")


def schema(config: dict) -> list[dict]:
    m = config["measures"]
    frames = [{"name": name} for name in DATE_ATTRS]
    frames.append({
        "name": m["frame"], "options": {"rangeEnabled": True},
        "fields": [{"name": f, "min": m["fields"][f][0], "max": m["fields"][f][1]}
                   for f in MEASURES],
    })
    return [{"name": config["index"], "frames": frames}]


def normalise(result):
    """A ``Sum``'s answer: the total and the number of columns summed."""
    return (result["value"], result["count"])


def program_can_serve(config: dict, root: str = ROOT) -> None:
    """Refuse at once a program that cannot serve the configuration.
    Its ``needs`` names a file of the program and a text that file has
    to hold, read as text (nothing of the program is imported): for
    ``ssb-sf100-q1`` the aggregate that reads a field's planes where
    they live.  The program before it copies every plane of every slice
    into a block of 7.8 GB a text beside the 10.2 GB that are resident
    (``PERF.md``, PR 34): a run that can only fail says so before it
    boots a server."""
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


def retail_price_cents(partkey: np.ndarray) -> np.ndarray:
    """dbgen's ``p_retailprice`` in cents (TPC-H 4.2.3, which SSB's
    ``part`` keeps): 90000 + ((partkey / 10) mod 20001) + 100 x (partkey
    mod 1000)."""
    return 90000 + (partkey // 10) % 20001 + 100 * (partkey % 1000)


class Reference:
    def __init__(self, config: dict, seed: int):
        program_can_serve(config)
        self.config, self.seed = config, int(seed)
        self.index = config["index"]
        self.frame = config["measures"]["frame"]
        self.n_slices = int(config["slices"])
        self.width = int(config["slice_width"])
        self.n_columns = int(config["columns"])
        self.per_slice = int(config["rows_loaded_per_slice"])
        self.parts = int(config["data"]["parts"])
        self.first_day = np.datetime64(config["data"]["first_order_date"])
        self.n_days = int(config["data"]["order_days"])
        self._made: dict[int, dict[str, np.ndarray]] = {}
        self._mu = threading.Lock()
        self._cols: dict[str, np.ndarray] = {}
        self.n_loaded = 0

    # -- data and load ------------------------------------------------------

    def units(self) -> list[tuple[str, int]]:
        """The units of load in order: for every slice one ``/import``
        a date attribute's frame, then one ``/import-value`` a measure."""
        return [(what, s) for s in range(self.n_slices)
                for what in DATE_ATTRS + MEASURES]

    def rows_of(self, s: int) -> dict[str, np.ndarray]:
        """Slice ``s``'s loaded ``lineorder`` rows as plain columns,
        made once from ``[seed, s]``."""
        with self._mu:
            got = self._made.get(s)
        if got is not None:
            return got
        rng = np.random.default_rng([self.seed, s])
        first = s * self.width
        held = min(self.width, self.n_columns - first)
        n = min(self.per_slice, held)
        column = first + np.sort(rng.choice(held, size=n, replace=False)).astype(np.int64)
        quantity = rng.integers(1, 51, size=n)
        discount = rng.integers(0, 11, size=n)
        partkey = rng.integers(1, self.parts + 1, size=n)
        date = self.first_day + rng.integers(0, self.n_days, size=n)
        year = date.astype("datetime64[Y]")
        got = {
            "column": column,
            "lo_quantity": quantity,
            "lo_discount": discount,
            "lo_discounted": quantity * retail_price_cents(partkey) * discount,
            "d_year": year.astype(np.int64) + 1970,
            "d_monthnuminyear": date.astype("datetime64[M]").astype(np.int64) % 12 + 1,
            "d_weeknuminyear": (date - year).astype(np.int64) // 7 + 1,
        }
        with self._mu:
            return self._made.setdefault(s, got)

    def make(self, unit: tuple[str, int]) -> dict:
        what, s = unit
        rows = self.rows_of(s)
        base = {"index": self.index, "slice": s}
        if what in DATE_ATTRS:
            return {**base, "route": "import", "frame": what,
                    "rows": rows[what].astype(np.uint64),
                    "cols": rows["column"].astype(np.uint64)}
        return {**base, "route": "import-value", "frame": self.frame, "field": what,
                "columns": rows["column"], "values": rows[what]}

    def seal(self) -> None:
        """After every unit is made: one array a column of the table."""
        made = [self.rows_of(s) for s in range(self.n_slices)]
        self._cols = {k: np.concatenate([m[k] for m in made]) for k in made[0]}
        self._made = {}
        self.n_loaded = int(self._cols["column"].size) * len(DATE_ATTRS + MEASURES)

    # -- answers -----------------------------------------------------------

    def _between(self, name: str, lo: int, hi: int, broken: str | None) -> np.ndarray:
        x = self._cols[name]
        if broken == "between_exclusive":
            return (x > lo) & (x < hi)
        return (x >= lo) & (x <= hi)

    def answer(self, key: tuple, broken: str | None = None) -> tuple[int, int]:
        """A read's key is ``(template, constants...)``: ``("q1.1",
        year, d, k)``, ``("q1.2", year, month, d, q)``, ``("q1.3", week,
        year, d, q)``; the discount is ``[d, d + 2]``, the quantity
        ``< k`` or ``[q, q + 9]``."""
        if broken not in (None, *CONTROLS):
            raise ValueError(f"unknown control {broken!r}")
        c = self._cols
        which, *a = key
        if which == "q1.1":
            year, d, k = a
            hit = (c["d_year"] == year) & (c["lo_quantity"] < k)
        elif which == "q1.2":
            year, month, d, q = a
            hit = ((c["d_year"] == year) & (c["d_monthnuminyear"] == month)
                   & self._between("lo_quantity", q, q + 9, broken))
        elif which == "q1.3":
            week, year, d, q = a
            hit = ((c["d_weeknuminyear"] == week) & (c["d_year"] == year)
                   & self._between("lo_quantity", q, q + 9, broken))
        else:
            raise ValueError(which)
        hit &= self._between("lo_discount", d, d + 2, broken)
        if broken == "drop_last_slice":
            hit &= c["column"] < (self.n_slices - 1) * self.width
        return (int(c["lo_discounted"][hit].sum(dtype=np.int64)),
                int(np.count_nonzero(hit)))

    def apply(self, key: tuple) -> None:
        raise ValueError("this kind sends no writes")

    def readback(self) -> list[Request]:
        return []


class Traffic(Mix):
    def __init__(self, mix: dict, config: dict, seed: int):
        super().__init__(mix, config, seed)
        if self.fixed:
            raise ValueError("this kind's mixes draw their texts: read.texts = distinct")
        read = mix["read"]
        self._templates = read["templates"]
        years = range(*config["dimensions"]["d_year"])
        months = range(*config["dimensions"]["d_monthnuminyear"])
        weeks = range(*config["dimensions"]["d_weeknuminyear"])
        d = range(*read["discount_from"])
        k = range(*read["quantity_below"])
        q = range(*read["quantity_from"])
        combos = {
            "q1.1": itertools.product(years, d, k),
            "q1.2": itertools.product(years, months, d, q),
            "q1.3": itertools.product(weeks, years, d, q),
        }
        paper = {name: tuple(args) for name, args in read["paper"].items()}
        piles = {}
        for name in sorted(combos):
            pile = [c for c in combos[name] if c != paper[name]]
            piles[name] = [pile[i] for i in self.rng.permutation(len(pile))]
        # The warm-up's own texts: the paper's three, then fresh ones
        # off the far end of each pile, which the window never reaches.
        n_warm = int(mix.get("warmup", {}).get("fresh_texts", self.clients))
        self._warm = [self._request(name, paper[name]) for name in sorted(paper)]
        for name in itertools.cycle(sorted(piles)):
            if len(self._warm) >= n_warm:
                break
            self._warm.append(self._request(name, piles[name].pop()))
        self._reads = [self._request(name, args)
                       for row in zip(*(piles[n] for n in sorted(piles)))
                       for name, args in zip(sorted(piles), row)]

    def _request(self, name: str, args: tuple) -> Request:
        if name == "q1.1":
            year, d, k = args
            text = self._templates[name].format(year=year, dlo=d, dhi=d + 2, k=k)
        elif name == "q1.2":
            year, month, d, q = args
            text = self._templates[name].format(
                year=year, month=month, dlo=d, dhi=d + 2, qlo=q, qhi=q + 9)
        else:
            week, year, d, q = args
            text = self._templates[name].format(
                week=week, year=year, dlo=d, dhi=d + 2, qlo=q, qhi=q + 9)
        return Request("read", text, (name, *args))
