"""The deployment kind ``tanimoto-topn``: upstream's chemical-similarity
index.  One index, one frame with a ranked cache, **a row a molecule**
and **a column a fingerprint position**, loaded through ``/import``,
asked ``TopN(Bitmap(frame, rowID=q), frame, n, tanimotoThreshold=t)``:
every molecule whose Tanimoto similarity with molecule ``q`` is above
``t`` %, ranked by the bits they share.

**The plain reference.**  Numpy and the standard library only; nothing
of ``pilosa_tpu`` is imported and nothing the server produced is read.
The fingerprints are one ``uint64[molecules, bits / 64]`` matrix.  The
answer to ``("TopN", q, n, t)`` follows upstream's rule as its docs and
``fragment.go`` state it, not the program:

* the candidates are the rows of the ranked cache (every molecule: the
  configuration's ``cache_size`` is at least ``molecules``);
* the count window on cardinalities: a row is scored when
  ``|row| > |q| * t / 100`` and ``|row| < |q| * 100 / t``;
* a scored row is kept when it shares ``c > 0`` bits with ``q`` and
  ``ceil(100 * c / (|row| + |q| - c)) > t``;
* pairs ``(id, c)`` by ``c`` falling, equal ``c`` by id rising, the
  first ``n``.

``c`` is the popcount of ``fp[row] & fp[q]``.  ``answer_plain`` is the
rule as it stands: that popcount for every row, the window, the ceil
rule, the ranking.  It reads every fingerprint (889 MB at the cell's
size: most of a second an answer), and a run compares some ten thousand
answers one after another once the server has stopped, inside the
driver's limit on a run (360 s with the set-up and the window; PR 36's
first check was cut there).  So ``answer`` works the same rule out for
the rows that can pass it alone.  A kept row has
``100 c / (|row| + |q| - c) > t`` and ``|row| >= c``, hence
``c > |q| t / 100``: it lacks at most ``miss = |q| - (|q| t // 100 + 1)``
of the query's positions, so of any ``L`` of them it holds at least
``L - miss``.  The reference keeps, a position, the sorted ids of the
molecules that set it (``holders``: the loaded pairs sorted by
position, nothing derived from them), counts over the query's ``L``
rarest positions how many of them each molecule holds (one
``bincount``), and computes ``c`` with the popcount above for the
molecules that hold enough: a few thousand rows and not 1.7M.  The
tier-1 tests hold ``answer`` to ``answer_plain`` on every key they
ask.  ``window_rows(key)`` is how many
rows the window keeps: what ``device.tanimoto_roofline`` counts bytes
for, and what the server's ``topn.prep`` span has to say in its
``candidates`` tag.

``broken`` turns the reference into the control (``CONTROLS``): the same
arithmetic with one stated guarantee given up, which the comparison has
to refuse.

**The data rule** (all of it assumed, as the configuration's file says:
no public source at hand gives ChEMBL's fingerprint statistics).
Uniform random bits will not do: two random 48-bit fingerprints share
0.6 bits and every text would answer with the query alone.  Molecules
come in *series*, as a medicinal-chemistry collection does: a series has
a parent fingerprint of ``bits_mean`` draws (a normal spread cut to
``bits_range``; a position drawn twice is set once) from a skewed
popularity over the positions (``1 / (rank + popularity_offset) **
popularity_skew`` over a seeded shuffle: a few substructure bits are
set in most molecules, most in few); series sizes are heavy-tailed (a
Pareto law of shape ``series_shape`` and mean ``series_mean``, cut at
``series_max``); a member replaces a geometric number ``g`` (mean
``edits_mean``) of its parent's bits: it drops ``g`` of the parent's
draws and sets ``g`` drawn from the same popularity.
A load unit is a run of whole series, generated from ``[seed, unit]``.

**The requests.**  Keys of a mix beside those every kind shares
(``traffic.py``): ``read.template`` (PQL with ``{frame} {q} {n} {t}``),
``read.n``, ``read.thresholds`` (a deck: threshold -> cards).  Every
text asks about another molecule, drawn from ``--seed`` without
replacement from the loaded rows, its threshold dealt from the shuffled
deck; the warm-up's molecules are not used again.  Requests are made as
they are asked for, so a window can deal as many texts as there are
molecules.
"""

from __future__ import annotations

import os

import numpy as np

from server import HarnessError
from traffic import Mix, Request, deck

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Launch site of obs/perf.py that the TopN scorers ride.
SITES = ("topn",)

# The ways the control gives up a guarantee the configuration states.
# ``floor_not_ceil``: the similarity rounded down where upstream rounds
# up.  ``cache_default``: only the 50,000 rows of highest cardinality are
# candidates, what upstream's default cacheSize would answer.
# ``drop_last_rows``: the last tenth of the molecules not scored.
CONTROLS = ("floor_not_ceil", "cache_default", "drop_last_rows")

DEFAULT_CACHE = 50_000

# Of the query's rarest positions an answer counts, how many a row has to
# hold to be scored: the positions counted are those a kept row may lack
# and HELD more.
HELD = 4


def schema(config: dict) -> list[dict]:
    options = {"cacheType": "ranked", "cacheSize": int(config["cache_size"])}
    return [{"name": config["index"],
             "frames": [{"name": config["frame"], "options": options}]}]


def normalise(result):
    """The ``(id, count)`` pairs in the order the server ranks them."""
    return [(p["id"], p["count"]) for p in result]


def program_can_serve(config: dict, root: str = ROOT) -> None:
    """Refuse at once a program that cannot serve the configuration: its
    ``needs`` names files of the program and a text each has to hold,
    read as text.  The program before the narrow plane layout gives a
    molecule a 128 KiB row: 65,536 of them are 8.6 GB of host memory and
    as much of the device's, and the other 1.67M are scored on the host a
    row at a time; a run that can only fail says so before it boots."""
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


def popularity(config: dict, seed: int) -> np.ndarray:
    """Each position's share of the draws: a power law over a seeded
    shuffle of the positions."""
    rule = config["data"]
    bits = int(config["fingerprint_bits"])
    ranks = np.random.default_rng([int(seed), 11]).permutation(bits)
    w = 1.0 / (ranks + float(rule["popularity_offset"])) ** float(rule["popularity_skew"])
    return w / w.sum()


def make_molecules(config: dict, seed: int, unit: int, count: int) -> tuple:
    """``count`` molecules of load unit ``unit`` by the data rule: the
    set ``(molecule, position)`` pairs, molecules numbered from 0 within
    the unit, a pair at most once."""
    rule = config["data"]
    bits = int(config["fingerprint_bits"])
    rng = np.random.default_rng([int(seed), 13, int(unit)])
    weights = popularity(config, seed)
    # series sizes: Pareto with the stated shape and mean, cut and summed
    # up to the unit's count
    shape = float(rule["series_shape"])
    scale = float(rule["series_mean"]) * (shape - 1.0) / shape
    sizes = np.zeros(0, dtype=np.int64)
    while sizes.sum() < count:
        more = np.floor(scale * (1.0 + rng.pareto(shape, size=max(64, count // 8))))
        sizes = np.concatenate(
            [sizes, np.clip(more, 1, int(rule["series_max"])).astype(np.int64)])
    ends = np.cumsum(sizes)
    n_series = int(np.searchsorted(ends, count)) + 1
    sizes = sizes[:n_series].copy()
    sizes[-1] -= int(ends[n_series - 1]) - count
    lo, hi = rule["bits_range"]
    k = np.clip(np.rint(rng.normal(rule["bits_mean"], rule["bits_spread"], n_series)),
                lo, hi).astype(np.int64)
    parents = rng.choice(bits, size=int(k.sum()), p=weights)
    # every member starts from its series' parent: a pair (member, position)
    series_of = np.repeat(np.arange(n_series), sizes)
    first = np.cumsum(k) - k  # where a parent's positions start
    reps = k[series_of]
    member = np.repeat(np.arange(count), reps)
    starts = np.cumsum(reps) - reps
    within = np.arange(int(reps.sum())) - np.repeat(starts, reps)
    pos = parents[np.repeat(first[series_of], reps) + within]
    edits = np.minimum(rng.geometric(1.0 / (1.0 + float(rule["edits_mean"])), count) - 1,
                       reps - 1)
    edits[np.cumsum(sizes) - sizes] = 0  # a series' first member is its parent
    # the parent's draws stand in random order, so a run of them from a
    # random place is a random choice of them
    turn = rng.integers(0, reps)
    keep = (within - turn[member]) % reps[member] >= edits[member]
    new_member = np.repeat(np.arange(count), edits)
    new_pos = rng.choice(bits, size=len(new_member), p=weights)
    flat = np.unique(np.concatenate([member[keep], new_member]) * bits
                     + np.concatenate([pos[keep], new_pos]))
    return flat // bits, flat % bits


class Reference:
    def __init__(self, config: dict, seed: int):
        program_can_serve(config)
        self.config, self.seed = config, int(seed)
        self.index, self.frame = config["index"], config["frame"]
        self.n = int(config["molecules"])
        self.bits = int(config["fingerprint_bits"])
        self.n_units = int(config["load_units"])
        self.fp = np.zeros((self.n, self.bits // 64), dtype=np.uint64)
        # a unit's pairs sorted by position: (molecule ids, where each
        # position's run of them starts)
        self._holders: list = [None] * self.n_units
        self._answers: dict[tuple, list] = {}
        self._default_cache: np.ndarray | None = None
        self.n_loaded = 0

    # -- data and load ------------------------------------------------------

    def units(self) -> range:
        """The units of load in order: a run of molecules each (all of
        one slice: the columns are the fingerprint's positions)."""
        return range(self.n_units)

    def _bounds(self, unit: int) -> tuple[int, int]:
        return unit * self.n // self.n_units, (unit + 1) * self.n // self.n_units

    def make(self, unit: int) -> dict:
        """Generate the unit's molecules from the seed, keep them (a
        fingerprint a molecule, and the pairs sorted by position), and
        return the unit to import."""
        lo, hi = self._bounds(unit)
        mol, pos = make_molecules(self.config, self.seed, unit, hi - lo)
        np.bitwise_or.at(
            self.fp[lo:hi], (mol, pos >> 6), np.uint64(1) << (pos & 63).astype(np.uint64))
        # the pairs come sorted by molecule: a stable sort by position
        # leaves every position's molecules in rising order
        order = np.argsort(pos.astype(np.uint16), kind="stable")
        starts = np.zeros(self.bits + 1, dtype=np.int64)
        np.cumsum(np.bincount(pos, minlength=self.bits), out=starts[1:])
        self._holders[unit] = ((mol[order] + lo).astype(np.uint32), starts)
        return {"route": "import", "index": self.index, "frame": self.frame, "slice": 0,
                "rows": (mol + lo).astype(np.uint64), "cols": pos.astype(np.uint64)}

    def seal(self) -> None:
        """After every unit is made: the cardinalities, in order too, and
        how many molecules set each position."""
        self.card = np.bitwise_count(self.fp).sum(axis=1, dtype=np.int64)
        self.card_sorted = np.sort(self.card)
        self.n_holders = sum(np.diff(starts) for _, starts in self._holders)
        self.n_loaded = int(self.card.sum())

    # -- answers -----------------------------------------------------------

    def _window(self, s: int, t: int) -> tuple[float, float]:
        """The count window of a query of ``s`` bits at threshold ``t``:
        a row is a candidate when its cardinality lies strictly inside."""
        return s * t / 100, s * 100 / t

    def window_rows(self, key: tuple) -> int:
        """Rows whose cardinality lies in the key's count window."""
        _call, q, _n, t = key
        above, below = self._window(int(self.card[q]), t)
        lo = int(np.searchsorted(self.card_sorted, above, side="right"))
        hi = int(np.searchsorted(self.card_sorted, below, side="left"))
        return max(hi - lo, 0)

    def _can_pass(self, q: int, t: int) -> np.ndarray:
        """The molecules that hold enough of query ``q``'s rarest
        positions to share more than ``t`` % of its bits (the module's
        docstring has the argument), ids rising.  A superset of the kept
        rows; every other row fails the rule whatever else it holds."""
        s = int(self.card[q])
        miss = s - (s * t // 100 + 1)
        if miss < 0:
            return np.zeros(0, dtype=np.int64)  # no row shares more than all
        positions = np.flatnonzero(
            np.unpackbits(self.fp[q].view(np.uint8), bitorder="little"))
        # its rarest positions: one more than a kept row may lack would
        # do (the union of their holders: a tenth of the collection);
        # HELD of them beyond that leave a few thousand rows at most
        positions = positions[np.argsort(self.n_holders[positions], kind="stable")]
        positions = positions[:min(s, miss + HELD)]
        pairs = np.sort(np.concatenate(
            [ids[starts[p]:starts[p + 1]]
             for p in positions.tolist() for ids, starts in self._holders]))
        # an id that stands k times in the sorted pairs stands k - 1 places
        # after itself
        last = len(pairs) - (len(positions) - miss) + 1
        return np.unique(pairs[:last][pairs[:last] == pairs[len(pairs) - last:]]
                         ).astype(np.int64)

    def _ranked(self, rows: np.ndarray, q: int, n: int, t: int,
                broken: str | None) -> list:
        """The rule over ``rows`` (ids rising): the window, the shared
        bits, the ceil rule, the ranking."""
        s = int(self.card[q])
        above, below = self._window(s, t)
        card = self.card[rows]
        inside = (card > above) & (card < below)
        rows, card = rows[inside], card[inside]
        c = np.bitwise_count(self.fp[rows] & self.fp[q]).sum(axis=1, dtype=np.int64)
        similarity = 100.0 * c / np.maximum(card + s - c, 1)
        rounded = np.floor(similarity) if broken == "floor_not_ceil" \
            else np.ceil(similarity)
        keep = (c > 0) & (rounded > t)
        if broken == "cache_default":
            if self._default_cache is None:
                # the ranked cache's own order: cardinality falling, ids rising
                self._default_cache = np.sort(
                    np.lexsort((np.arange(self.n), -self.card))[:DEFAULT_CACHE])
            keep &= np.isin(rows, self._default_cache, assume_unique=True)
        elif broken == "drop_last_rows":
            keep &= rows < self.n - self.n // 10
        ids, shared = rows[keep], c[keep]
        order = np.lexsort((ids, -shared))[:n]
        return list(zip(ids[order].tolist(), shared[order].tolist()))

    def answer(self, key: tuple, broken: str | None = None) -> list:
        """A read's key is ``("TopN", q, n, t)``."""
        if broken not in (None, *CONTROLS):
            raise ValueError(f"unknown control {broken!r}")
        if (key, broken) not in self._answers:
            call, q, n, t = key
            if call != "TopN":
                raise ValueError(call)
            self._answers[(key, broken)] = self._ranked(
                self._can_pass(q, t), q, n, t, broken)
        return self._answers[(key, broken)]

    def answer_plain(self, key: tuple) -> list:
        """The same answer with every row put through the rule: what
        ``answer`` is held to by the tests."""
        _call, q, n, t = key
        return self._ranked(np.arange(self.n), q, n, t, None)

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
        self._template = read["template"]
        self._frame, self._n = config["frame"], int(read["n"])
        molecules = int(config["molecules"])
        self._molecule = self.rng.permutation(molecules)
        self._threshold = np.asarray(
            deck(read["thresholds"], molecules, self.rng), dtype=np.int64)
        n_warm = int(mix.get("warmup", {}).get("fresh_texts", self.clients))
        # the warm-up's molecules come off the far end, which the window
        # never reaches
        self._texts = molecules - n_warm
        self._warm = [self._request(molecules - 1 - i) for i in range(n_warm)]

    def _request(self, i: int) -> Request:
        q, t = int(self._molecule[i]), int(self._threshold[i])
        text = self._template.format(frame=self._frame, q=q, n=self._n, t=t)
        return Request("read", text, ("TopN", q, self._n, t))

    def read(self, i: int, client: int = 0) -> Request:
        if i >= self._texts:
            raise IndexError(f"the mix ran out of distinct texts after {self._texts}")
        return self._request(i)
