"""The deployment ``nyc-taxi-rides`` at a size a test holds: the served
``TopN`` over a src from other frames (the billion-rides Q3 and Q4)
against the deployment kind's own plain reference, the reference against
a count of the seeded rides one at a time, the controls, the deal of the
texts, the reach the roofline reducer counts, and a rehearsal of the
cell through the benchmark's ``run_cell``.  Everything here runs on the
CPU; what the cell does on the chip only a chip run can say
(``PERF.md``)."""

import collections
import copy
import json
import os
import sys

import pytest

from pilosa_tpu.net.client import InternalClient
from pilosa_tpu.net.server import Server
from pilosa_tpu.obs import stats as stats_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import control  # noqa: E402 — benchmarks/control.py
import run  # noqa: E402 — benchmarks/run.py
from reducers import taxi_roofline  # noqa: E402

CELL = "nyc-taxi-rides.q3q4"
SLICE = 1 << 20
# 3.5 slices of rides, years of unequal length that meet 1 to 2 slices
YEAR_RIDES = [700_000, 500_000, 600_000, 400_000, 300_000, 800_000, 370_016]


def tiny_config(per_slice=400) -> dict:
    """The shipped configuration cut to a test's size: its kind, frames,
    shares and rules as they are, 4 slices of rides."""
    cfg = run.read_json(os.path.join(BENCH, "configs", "nyc-taxi-rides.json"))
    rides = sum(YEAR_RIDES)
    assert rides == 7 * SLICE // 2
    cfg.update(rides=rides, columns=rides, slices=4, rides_loaded_per_slice=per_slice)
    cfg["year"] = dict(cfg["year"], rides=YEAR_RIDES)
    return cfg


@pytest.fixture(scope="module")
def kind():
    return run.load_kind("taxi-rides")


@pytest.fixture(scope="module")
def mix():
    return run.read_json(os.path.join(BENCH, "traffic", "rides-q3q4.json"))


def _made(kind, cfg, seed):
    """The reference with the seed's data made, and the import units."""
    ref = kind.Reference(cfg, seed)
    units = [ref.make(u) for u in ref.units()]
    ref.seal()
    return ref, units


# ---------------------------------------------------------------------------
# (a) the reference and the data rule
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", [5, 2_900_000_077])
def test_the_reference_is_a_count_of_the_seeded_rides_one_at_a_time(kind, mix, seed):
    cfg = tiny_config()
    ref, units = _made(kind, cfg, seed)
    frames = {cfg[d]["frame"]: d for d in ("year", "passengers", "distance")}
    ride = collections.defaultdict(dict)
    for u in units:
        for r, col in zip(u["rows"].tolist(), u["cols"].tolist()):
            ride[col][frames[u["frame"]]] = r
    # every loaded ride set one row of each frame: the frames partition them
    assert all(len(v) == 3 for v in ride.values())
    assert ref.n_loaded == 3 * len(ride)
    traffic = kind.Traffic(mix, cfg, seed)
    for req in traffic._reads:
        which, year, *rest = req.key
        counts = collections.Counter(
            v["passengers"] if which == "q3" else v["distance"]
            for v in ride.values()
            if v["year"] == year and (which == "q3" or v["passengers"] == rest[0]))
        want = sorted(((r, c) for r, c in counts.items()), key=lambda p: (-p[1], p[0]))
        assert ref.answer(req.key) == want[: req.key[-1]], req.text
    # a Q3 answer is every passenger count; rows ranked as the guarantee says
    q3 = ref.answer(("q3", 2011, 10))
    assert sorted(r for r, _ in q3) == list(range(10))


def test_every_slice_of_a_year_holds_every_passenger_count_and_distance(kind):
    """The floors of the data rule: the reach the roofline reducer counts
    from the configuration alone is the data's."""
    cfg = tiny_config()
    ref, _units = _made(kind, cfg, 7)
    for s in range(cfg["slices"]):
        rides = ref.rides_of(s)
        assert sorted(set(rides["distance"].tolist())) == list(range(64))
    for y in range(7):
        slices = {s for s in range(cfg["slices"])
                  if (ref.rides_of(s)["year"] == y).any()}
        for p in range(10):
            held = {s for s in slices if ((ref.rides_of(s)["year"] == y)
                                          & (ref.rides_of(s)["passengers"] == p)).any()}
            assert held == slices
        for text in (f"TopN(Bitmap(frame=pickup_year, rowID={2009 + y}), "
                     "frame=passenger_count, n=10)",
                     f"TopN(Intersect(Bitmap(frame=pickup_year, rowID={2009 + y}), "
                     "Bitmap(frame=passenger_count, rowID=9)), frame=dist_miles, n=64)"):
            assert taxi_roofline.reached_slices(cfg, text) == len(slices)


def test_the_shipped_configuration_reaches_its_years_slices():
    cfg = run.read_json(os.path.join(BENCH, "configs", "nyc-taxi-rides.json"))
    assert sum(cfg["year"]["rides"]) == cfg["rides"]
    assert -(-cfg["rides"] // cfg["slice_width"]) == cfg["slices"] == 1050
    reach = [taxi_roofline.reached_slices(
        cfg, f"TopN(Bitmap(frame=pickup_year, rowID={y}), frame=passenger_count, n=10)")
        for y in range(2009, 2016)]
    assert reach == [158, 157, 164, 166, 162, 167, 82]
    # six boundaries inside a slice: each such slice is reached by two years
    assert sum(reach) == cfg["slices"] + 6


# ---------------------------------------------------------------------------
# (b) the served path against the kind's reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module", params=[13, 2_900_000_083])
def served(request, kind, tmp_path_factory):
    """A server with the kind's schema and the seed's data, loaded as
    the harness loads it, and the reference that made the data."""
    cfg = tiny_config()
    s = Server(
        data_dir=str(tmp_path_factory.mktemp("taxi") / "data"),
        stats=stats_mod.ExpvarStatsClient(),
        anti_entropy_interval=3600, polling_interval=3600,
        cache_flush_interval=3600,
    )
    s.open()
    try:
        c = InternalClient(s.host, timeout=120.0)
        for index in kind.schema(cfg):
            c.create_index(index["name"], index.get("options"))
            for frame in index["frames"]:
                c.create_frame(index["name"], frame["name"], frame.get("options"))
        ref = kind.Reference(cfg, request.param)
        for unit in ref.units():
            u = ref.make(unit)
            c.import_bits(u["index"], u["frame"], u["slice"], (u["rows"], u["cols"]))
        ref.seal()
        yield c, ref, cfg, s
    finally:
        s.close()


# Q3 of a year in one slice and of one over two; Q4 of a common and of the
# rarest passenger count
@pytest.mark.parametrize("key", [("q3", 2009, 10), ("q3", 2010, 10),
                                 ("q4", 2012, 1, 64), ("q4", 2015, 9, 64)])
def test_the_served_topn_is_the_references_exact_ranking(served, kind, mix, key):
    c, ref, cfg, s = served
    args = {"year": key[1], "n": key[-1]}
    if key[0] == "q4":
        args["passengers"] = key[2]
    text = mix["read"]["templates"][key[0]].format(**args)
    # as the benchmark's client asks: JSON over POST /index/<i>/query
    status, data = c._request("POST", f"/index/{cfg['index']}/query", body=text.encode())
    assert status == 200
    got = kind.normalise(json.loads(data)["results"][0])
    assert got == ref.answer(key) and got
    counts = [cnt for _, cnt in got]
    assert counts == sorted(counts, reverse=True) and counts[-1] > 0
    # the walked build, its src evaluated on the host
    t = json.loads(c._request("GET", "/debug/traces")[1])["traces"][-1]
    spans = {sp["name"]: sp["tags"] for sp in t["spans"]}
    assert spans["topn.prep"]["build"] == "walked" and spans["topn.prep"]["src"] == "host"
    assert spans["topn.src"]["leaves"] == (1 if key[0] == "q3" else 2)
    assert spans["topn.src"]["reached"] == taxi_roofline.reached_slices(cfg, text)
    # the controls differ from it here too
    for broken in kind.CONTROLS:
        if broken == "drop_last_slice" and key[1] != 2015:
            continue  # the last slice holds 2015 alone
        assert ref.answer(key, broken=broken) != got


# ---------------------------------------------------------------------------
# (c) the requests and the control
# ---------------------------------------------------------------------------


def test_the_texts_are_dealt_once_a_round_in_a_new_order(kind, mix):
    cfg = tiny_config()
    traffic = kind.Traffic(mix, cfg, 2_900_000_089)
    n = len(traffic._reads)
    assert n == 77 == len({r.text for r in traffic._reads})
    dealt = [traffic.read(i // 8, i % 8).key for i in range(3 * n)]
    rounds = [dealt[k * n:(k + 1) * n] for k in range(3)]
    assert all(sorted(r) == sorted(x.key for x in traffic._reads) for r in rounds)
    assert rounds[0] != rounds[1] != rounds[2]
    # the warm-up: the mix's 8 texts in one round, both src shapes over
    # both ranked frames, the last year's among them; dealt last in round 0
    (warm,) = traffic.warmup_rounds()
    assert [r.key[:-1] for r in warm] == [tuple(t) for t in mix["warmup"]["texts"]]
    assert {r.key[0] for r in warm} == {"q3", "q4"}
    assert {("q3", 2015), ("q4", 2015)} <= {r.key[:2] for r in warm}
    assert set(rounds[0][-8:]) == {r.key for r in warm}
    assert traffic.read(0, 0).text.startswith("TopN(")
    other = kind.Traffic(mix, cfg, 2_900_000_091)
    assert [other.read(0, c).key for c in range(8)] != rounds[0][:8]


@pytest.mark.parametrize("seed", [3, 2_900_000_093])
def test_both_controls_are_refused_and_the_sound_reference_is_not(kind, mix, seed):
    cfg = tiny_config()
    ref, _units = _made(kind, cfg, seed)
    traffic = kind.Traffic(mix, cfg, seed)
    assert control.judge(ref, traffic, 100, None)["correct"] is True
    for broken in kind.CONTROLS:
        verdict = control.judge(ref, traffic, 100, broken)
        assert verdict["correct"] is False
        assert verdict["compared"]["wrong_answers"]["value"] >= 11


# ---------------------------------------------------------------------------
# (d) the cell through the benchmark's own run_cell
# ---------------------------------------------------------------------------

# Per-layer metrics listed for the cell that only a chip run can read.
DEVICE_ONLY = {"device.idle_share", "device.taxi_roofline",
               "device.hbm_in_use_bytes", "device.hbm_peak_bytes"}


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """``BENCHMARK.json`` with the cell's configuration cut to 4 slices;
    the kind, the mix and the metrics' files are the shipped ones."""
    bench = copy.deepcopy(run.read_json(os.path.join(REPO, "BENCHMARK.json")))
    path = tmp_path_factory.mktemp("cfg") / "taxi-tiny.json"
    path.write_text(json.dumps(tiny_config()))
    next(c for c in bench["configs"] if c["name"] == "nyc-taxi-rides")["file"] = str(path)
    return bench


def _rig():
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "PILOSA_TPU_COMPILATION_CACHE_DIR": "off"}
    return run.Rig(platform="cpu", extra_env=env)


def test_a_traced_rehearsal_is_correct_and_reads_every_listed_metric(
    monkeypatch, tiny_bench
):
    # run_cell refuses a caller that has initialised a JAX backend (on the
    # chip's machine it would hold the chip); the server child here is
    # held to the CPU by its environment
    monkeypatch.setattr(run, "jax_backend_in_this_process", lambda: False)
    rc, line = run.run_cell(tiny_bench, CELL, 2_900_000_097, 0.5, True, _rig())
    assert rc == 0
    line = json.loads(json.dumps(line))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8
    c = line["compared"]
    assert c["wrong_answers"] == {"value": 0, "limit": 0}
    assert c["hosteval_launches"] == {"value": 0, "limit": 0}
    assert c["device_launches"]["value"] >= 1
    cell = run.Cell(tiny_bench, CELL, _rig())
    listed = {m["name"] for m in cell.per_layer}
    assert {m for m in listed if "taxi" in m} == {
        "exec.taxi_prep_ms", "exec.taxi_src_ms", "exec.taxi_src_reach_share",
        "exec.taxi_src_host_bytes", "exec.taxi_select_ms", "device.taxi_dispatch_ms",
        "device.taxi_fetch_ms", "exec.taxi_scored_share",
        "exec.taxi_prep_gil_wait_share", "device.taxi_roofline"}
    assert not {"exec.topn_prep_ms", "device.topn_roofline"} & listed
    assert set(line["metrics"]) == listed - DEVICE_ONLY
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # every text a round reaches the slices of its year: 1 or 2 of 4
    assert 25.0 <= m["exec.taxi_src_reach_share"] <= 50.0
    # a 128 KiB row of words a slice reached, per built answer at most
    assert 0 < m["exec.taxi_src_host_bytes"] <= 2 * 131072
    assert m["exec.taxi_scored_share"] > 50.0
    assert m["exec.taxi_prep_ms"] > 0 and m["exec.taxi_src_ms"] > 0
    # the warm-up compiled every program the window runs
    assert m["device.window_new_programs"] == 0 and m["device.window_compile_ms"] == 0
