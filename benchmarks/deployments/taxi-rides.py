"""The deployment kind ``taxi-rides``: upstream Pilosa's transportation
example, NYC taxi trips as one index whose columns are rides, numbered
in pickup-time order, and a frame a dimension, every ride setting one
row in each: ``pickup_year``, ``passenger_count`` and ``dist_miles``
(``round(trip_distance)``, 63 and over in row 63).  Asked the "1.1
Billion Taxi Rides" queries that count by dimensions (Litwintschik's Q3
and Q4) as ``TopN`` over a src taken from other frames:

    Q3  TopN(Bitmap(frame=pickup_year, rowID=y), frame=passenger_count, n=10)
    Q4  TopN(Intersect(Bitmap(frame=pickup_year, rowID=y),
                       Bitmap(frame=passenger_count, rowID=p)),
             frame=dist_miles, n=64)

and answered with a ranked list of ``{"id", "count"}`` pairs.

**The plain reference.**  Numpy and the standard library only; nothing
of ``pilosa_tpu`` is imported and nothing the server produced is read.
The loaded rides are kept as three plain columns in column order (a
year, a passenger count and a distance row a ride); an answer is a mask
over the rides its src keeps, a ``bincount`` of the ranked frame's
values under it, ranked by count falling and equal counts by id rising,
pairs with count 0 left out, the first ``n``.  That is the exact
ranking: the server's ranked caches and its two-phase protocol have to
equal it where, as the configuration states, ``n`` and ``cacheSize``
are at least the ranked frame's rows.

``broken`` turns the reference into the control: ``drop_last_slice``
(the last slice not counted) and ``src_ignored`` (the ranked frame's
plain ranking, what an answer that lost its src would say).

**The data**, per slice from ``[seed, slice]``.  Slice ``s`` covers
columns ``[s * W, (s + 1) * W)`` of the full index (``W`` =
``slice_width``; the last slice is ragged), and year ``y`` the columns
that its rides take in pickup-time order (``year.rides``, in order).
Of the columns where slice ``s`` and year ``y`` meet (``o`` of them),
``max(P, round(rides_loaded_per_slice * o / W))`` distinct ones are
loaded, drawn uniformly; ``P`` is the passenger count's rows.  Each
such group holds every passenger count once and the rest drawn from
``passengers.share``; each slice holds every distance row once and the
rest drawn as ``round`` of a log-normal of median ``median_miles`` and
shape ``sigma``, clipped to the last row.  At full size a slice holds
about 10^6 rides and every value of every frame, however rare; the
floors keep that at the loaded size, so the planes are of the
deployment's row classes and a text reaches the slices it reaches at
full size (``reducers/taxi_roofline.py`` counts them from the
configuration alone).

**The requests.**  Keys of a mix beside those every kind shares
(``traffic.py``): ``read.templates`` (``q3``: PQL with ``{year}`` and
``{n}``; ``q4``: with ``{year}``, ``{passengers}`` and ``{n}``),
``read.n`` (each template's ``n``) and ``read.texts``, a list of
``["q3", year]`` and ``["q4", year, passengers]``.  Every round of
requests deals each text once, in an order shuffled from the seed
(:meth:`Traffic.read`); ``warmup.texts``, some of them, are the
warm-up's, and the window's first round deals them last.
"""

from __future__ import annotations

import threading

import numpy as np

from traffic import Mix, Request

# Launch site of obs/perf.py that the fused TopN scorer rides.
SITES = ("topn",)

# The ways the control gives up a stated guarantee.  ``drop_last_slice``:
# "every count is over every column of every slice".  ``src_ignored``:
# "a count is over the rides the src keeps".
CONTROLS = ("drop_last_slice", "src_ignored")


def schema(config: dict) -> list[dict]:
    options = {"cacheType": config["cache_type"], "cacheSize": int(config["cache_size"])}
    return [{"name": config["index"],
             "frames": [{"name": config[dim]["frame"], "options": options}
                        for dim in ("year", "passengers", "distance")]}]


def normalise(result):
    """The ``(id, count)`` pairs in the order the server ranks them."""
    return [(p["id"], p["count"]) for p in result]


class Reference:
    def __init__(self, config: dict, seed: int):
        self.seed = int(seed)
        self.index = config["index"]
        self.n_slices = int(config["slices"])
        self.width = int(config["slice_width"])
        self.per_slice = int(config["rides_loaded_per_slice"])
        self.first_year = int(config["year"]["first"])
        # the first column of each year's rides, and the end of the last
        self.starts = np.concatenate(
            [[0], np.cumsum(config["year"]["rides"])]).astype(np.int64)
        if int(self.starts[-1]) != int(config["rides"]):
            raise ValueError("year.rides does not add up to rides")
        self.frames = {dim: config[dim]["frame"] for dim in ("year", "passengers", "distance")}
        self.n_pass = int(config["passengers"]["rows"])
        share = np.asarray(config["passengers"]["share"], dtype=np.float64)
        if share.size != self.n_pass:
            raise ValueError("passengers.share needs a share a row")
        self.pass_share = share / share.sum()
        dist = config["distance"]
        self.n_dist = int(dist["rows"])
        self.mu, self.sigma = float(np.log(dist["median_miles"])), float(dist["sigma"])
        self._made: dict[int, dict[str, np.ndarray]] = {}
        self._mu = threading.Lock()
        self._cols: dict[str, np.ndarray] = {}
        self._last = 0
        self._answers: dict[tuple, list] = {}
        self.n_loaded = 0

    # -- data and load ------------------------------------------------------

    def units(self) -> list[tuple[str, int]]:
        """The units of load in order: for every slice one ``/import`` a
        frame."""
        return [(dim, s) for s in range(self.n_slices) for dim in self.frames]

    def rides_of(self, s: int) -> dict[str, np.ndarray]:
        """Slice ``s``'s loaded rides as plain columns, made once from
        ``[seed, s]``: ``column``, and ``year`` (an index from the first
        year), ``passengers`` and ``distance`` (rows)."""
        with self._mu:
            got = self._made.get(s)
        if got is not None:
            return got
        rng = np.random.default_rng([self.seed, s])
        lo, hi = s * self.width, min((s + 1) * self.width, int(self.starts[-1]))
        cols, years, pcs = [], [], []
        for y in range(len(self.starts) - 1):
            a, b = max(lo, int(self.starts[y])), min(hi, int(self.starts[y + 1]))
            if a >= b:
                continue
            n = max(self.n_pass, round(self.per_slice * (b - a) / self.width))
            if n > b - a:
                raise ValueError(f"slice {s} holds {b - a} rides of year "
                                 f"{self.first_year + y}: fewer than the "
                                 f"{self.n_pass} passenger counts")
            cols.append(a + np.sort(rng.choice(b - a, size=n, replace=False)))
            years.append(np.full(n, y, dtype=np.int8))
            pc = rng.choice(self.n_pass, size=n, p=self.pass_share).astype(np.int8)
            pc[rng.permutation(n)[: self.n_pass]] = np.arange(self.n_pass)
            pcs.append(pc)
        m = sum(c.size for c in cols)
        if m < self.n_dist:
            raise ValueError(f"slice {s} loads {m} rides: fewer than the "
                             f"{self.n_dist} distance rows")
        miles = rng.lognormal(self.mu, self.sigma, size=m)
        dist = np.minimum(np.floor(miles + 0.5), self.n_dist - 1).astype(np.int8)
        dist[rng.permutation(m)[: self.n_dist]] = np.arange(self.n_dist)
        got = {"column": np.concatenate(cols).astype(np.uint64),
               "year": np.concatenate(years), "passengers": np.concatenate(pcs),
               "distance": dist}
        with self._mu:
            return self._made.setdefault(s, got)

    def make(self, unit: tuple[str, int]) -> dict:
        dim, s = unit
        rides = self.rides_of(s)
        rows = rides[dim].astype(np.uint64)
        if dim == "year":
            rows += np.uint64(self.first_year)
        return {"route": "import", "index": self.index, "frame": self.frames[dim],
                "slice": s, "rows": rows, "cols": rides["column"]}

    def seal(self) -> None:
        """After every unit is made: one array a dimension, rides in
        column order."""
        made = [self.rides_of(s) for s in range(self.n_slices)]
        self._cols = {k: np.concatenate([m[k] for m in made])
                      for k in ("year", "passengers", "distance")}
        self._last = int(made[-1]["column"].size)
        self._made = {}
        self.n_loaded = int(self._cols["year"].size) * len(self.frames)

    # -- answers -----------------------------------------------------------

    def answer(self, key: tuple, broken: str | None = None) -> list:
        """A read's key is ``("q3", year, n)`` (the passenger counts of
        a year's rides) or ``("q4", year, passengers, n)`` (the distance
        rows of a year's rides with that many passengers)."""
        if broken not in (None, *CONTROLS):
            raise ValueError(f"unknown control {broken!r}")
        if (key, broken) not in self._answers:
            c = self._cols
            if broken == "drop_last_slice":
                c = {k: v[: v.size - self._last] for k, v in c.items()}
            keep = c["year"] == key[1] - self.first_year
            if key[0] == "q3":
                ranked, rows, n = c["passengers"], self.n_pass, key[2]
            elif key[0] == "q4":
                keep &= c["passengers"] == key[2]
                ranked, rows, n = c["distance"], self.n_dist, key[3]
            else:
                raise ValueError(key[0])
            if broken == "src_ignored":
                keep = slice(None)
            counts = np.bincount(ranked[keep], minlength=rows)
            order = np.lexsort((np.arange(rows), -counts))
            self._answers[(key, broken)] = [
                (int(r), int(counts[r])) for r in order[:n] if counts[r] > 0]
        return self._answers[(key, broken)]

    def apply(self, key: tuple) -> None:
        raise ValueError("this kind sends no writes")

    def readback(self) -> list[Request]:
        return []


class Traffic(Mix):
    def __init__(self, mix: dict, config: dict, seed: int):
        super().__init__(mix, config, seed)
        if not self.fixed:
            raise ValueError("this kind's mixes name their texts")
        self.seed = int(seed)
        read = mix["read"]
        self._reads = []
        for which, year, *passengers in read["texts"]:
            n = int(read["n"][which])
            args = {"year": year, "n": n}
            if passengers:
                args["passengers"] = passengers[0]
            text = read["templates"][which].format(**args)
            self._reads.append(Request("read", text, (which, year, *passengers, n)))
        listed = [tuple(t) for t in read["texts"]]
        self._warm_at = [listed.index(tuple(t)) for t in mix["warmup"]["texts"]]
        self._warm = [self._reads[i] for i in self._warm_at]
        self._rounds: dict[int, list[int]] = {}

    def _round(self, r: int) -> list[int]:
        """Round ``r``'s order of the texts: shuffled from the seed, the
        warm-up's texts last in the first round."""
        order = self._rounds.get(r)
        if order is None:
            rng = np.random.default_rng([self.seed, 7_000_019, r])
            order = rng.permutation(len(self._reads)).tolist()
            if r == 0:
                warm = set(self._warm_at)
                order = [i for i in order if i not in warm] + self._warm_at
            order = self._rounds.setdefault(r, order)
        return order

    def read(self, i: int, client: int = 0) -> Request:
        """Client ``client``'s ``i``-th request: the rounds dealt in turn."""
        r, at = divmod(i * self.clients + client, len(self._reads))
        return self._reads[self._round(r)[at]]
