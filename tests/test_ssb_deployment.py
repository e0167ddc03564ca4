"""The deployment ``ssb-sf100-q1`` at a size a test holds: the served
flight-1 ``Sum`` against the deployment kind's own plain reference, the
control, and a rehearsal of the cell through the benchmark's
``run_cell``.  Everything here runs on the CPU; what the cell does on
the chip only a chip run can say (``PERF.md``).  The in-place aggregate
itself is held to the leaf batch and to ``hosteval`` in
``tests/test_bsi_in_place.py``."""

import copy
import json
import os
import sys

import numpy as np
import pytest

from pilosa_tpu.net.client import InternalClient
from pilosa_tpu.net.server import Server
from pilosa_tpu.obs import stats as stats_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import control  # noqa: E402 — benchmarks/control.py
import metrics  # noqa: E402 — benchmarks/metrics.py
import run  # noqa: E402 — benchmarks/run.py

CELL = "ssb-sf100-q1.sum-drill"
LAST_SLICE_COLUMNS = 252_430  # SF100's 600,037,902 rows leave the last slice ragged


def tiny_config(slices=3, per_slice=600) -> dict:
    """The shipped configuration cut to a test's size: its kind, schema,
    field ranges and keys as they are, fewer slices and rows."""
    cfg = run.read_json(os.path.join(BENCH, "configs", "ssb-sf100-q1.json"))
    cfg.update(slices=slices, rows_loaded_per_slice=per_slice,
               columns=(slices - 1) * cfg["slice_width"] + LAST_SLICE_COLUMNS)
    return cfg


@pytest.fixture(scope="module")
def kind():
    return run.load_kind("ssb-q1")


@pytest.fixture(scope="module")
def mix():
    return run.read_json(os.path.join(BENCH, "traffic", "sum-drill.json"))


# ---------------------------------------------------------------------------
# (a) the served path against the kind's reference
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served(kind, mix, tmp_path_factory):
    """A server with the kind's schema and the seed's data, loaded as
    the harness loads it, the reference that made the data, and the
    seed's requests."""
    cfg, seed = tiny_config(), 3_400_000_021
    ref = kind.Reference(cfg, seed)
    s = Server(
        data_dir=str(tmp_path_factory.mktemp("ssb") / "data"),
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
                for fld in frame.get("fields", ()):
                    c.create_field(index["name"], frame["name"], fld["name"],
                                   fld["min"], fld["max"])
        for unit in ref.units():
            u = ref.make(unit)
            if u["route"] == "import":
                c.import_bits(u["index"], u["frame"], u["slice"], (u["rows"], u["cols"]))
            else:
                c.import_value(u["index"], u["frame"], u["field"], u["slice"],
                               u["columns"], u["values"])
        ref.seal()
        # the planes resident, as the cell's are once its first answers
        # (whose leaf batches would not fit the chip) have uploaded them
        s.holder.warm_device_mirrors()
        yield c, ref, cfg, kind.Traffic(mix, cfg, seed), s
    finally:
        s.close()


# the paper's three texts, then one more of each template
@pytest.mark.parametrize("which", range(6))
def test_the_served_sum_is_the_references_exact_sum(served, kind, which):
    c, ref, cfg, traffic, _ = served
    req = traffic._warm[which]
    status, data = c._request("POST", f"/index/{cfg['index']}/query",
                              body=req.text.encode())
    assert status == 200
    got = kind.normalise(json.loads(data)["results"][0])
    want = ref.answer(req.key)
    assert got == want
    if which == 0:
        # Q1.1 as the paper prints it selects rows of the tiny table too
        assert want[1] > 0 and want[0] > 0
        assert ref.answer(req.key, broken="between_exclusive") != want


def test_the_served_sums_took_the_in_place_way(served):
    """From the program's own counters, as ``chip_smoke.py`` reads
    them: every aggregate over the loaded (dense, resident) planes."""
    *_, s = served
    counts = s.stats.snapshot()["counts"]
    assert counts.get("exec.bsi.inPlace", 0) >= 1
    assert "exec.bsi.batch" not in counts


def test_the_reference_keeps_ssbs_shapes(kind):
    cfg = tiny_config(slices=2, per_slice=4000)
    ref = kind.Reference(cfg, 7)
    for unit in ref.units():
        ref.make(unit)
    ref.seal()
    c = ref._cols
    assert c["column"].size == 8000 and len(set(c["column"].tolist())) == 8000
    assert c["column"].max() < cfg["columns"]
    assert (c["lo_quantity"].min(), c["lo_quantity"].max()) == (1, 50)
    assert (c["lo_discount"].min(), c["lo_discount"].max()) == (0, 10)
    assert 0 <= c["lo_discounted"].min() and c["lo_discounted"].max() <= 104_950_000
    assert (c["d_year"].min(), c["d_year"].max()) == (1992, 1998)
    assert (c["d_monthnuminyear"].min(), c["d_monthnuminyear"].max()) == (1, 12)
    assert (c["d_weeknuminyear"].min(), c["d_weeknuminyear"].max()) == (1, 53)
    # the last order date is 1998-08-02: no order in the autumn of 1998
    assert c["d_monthnuminyear"][c["d_year"] == 1998].max() == 8
    fields = cfg["measures"]["fields"]
    assert {f: tuple(b) for f, b in fields.items()} == {
        "lo_quantity": (1, 50), "lo_discount": (0, 10), "lo_discounted": (0, 104_950_000)}
    prices = kind.retail_price_cents(np.arange(1, cfg["data"]["parts"] + 1))
    assert 90_000 <= prices.min() and prices.max() <= 209_900


# ---------------------------------------------------------------------------
# (b) the cell's files, the control, the bytes function
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    """``BENCHMARK.json`` with the cell's configuration cut to 3 slices;
    the kind, the mix and the metrics' files are the shipped ones."""
    bench = copy.deepcopy(run.read_json(os.path.join(REPO, "BENCHMARK.json")))
    path = tmp_path_factory.mktemp("cfg") / "ssb-tiny.json"
    path.write_text(json.dumps(tiny_config()))
    next(c for c in bench["configs"] if c["name"] == "ssb-sf100-q1")["file"] = str(path)
    return bench


def _rig(server_argv=None):
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "PILOSA_TPU_COMPILATION_CACHE_DIR": "off"}
    return run.Rig(platform="cpu", server_argv=server_argv, extra_env=env)


def test_the_cells_files_are_found_by_name(tiny_bench, kind):
    cell = run.Cell(tiny_bench, CELL, _rig())
    assert cell.kind.__file__ == kind.__file__ and cell.chips == 1
    assert "agg" in cell.kind.SITES
    (index,) = cell.kind.schema(cell.config)
    assert [f["name"] for f in index["frames"]] == [
        "d_year", "d_monthnuminyear", "d_weeknuminyear", "lo"]
    assert index["frames"][3]["options"] == {"rangeEnabled": True}
    traffic = cell.kind.Traffic(cell.mix, cell.config, 1)
    assert not traffic.fixed
    # dealt 1 : 1 : 1 until Q1.1's 3,087 combinations (less the warm-up's) run out
    names = [r.key[0] for r in traffic._reads]
    assert names[:6] == ["q1.1", "q1.2", "q1.3"] * 2
    assert len(names) == 3 * (7 * 9 * 49 - 6) and len({r.text for r in traffic._reads}) == len(names)
    warm = traffic.warmup_rounds()
    assert [len(r) for r in warm] == [8, 8]
    assert [r.key for r in warm[0][:3]] == [
        ("q1.1", 1993, 1, 25), ("q1.2", 1994, 1, 4, 26), ("q1.3", 6, 1994, 5, 26)]
    assert warm[0][0].text == (
        "Sum(Intersect(Bitmap(frame=d_year, rowID=1993), Range(frame=lo, lo_discount >< [1, 3]), "
        "Range(frame=lo, lo_quantity < 25)), frame=lo, field=lo_discounted)")
    sent = {r.text for r in traffic._reads}
    assert not any(r.text in sent for rnd in warm for r in rnd)
    # another seed deals other texts first
    assert cell.kind.Traffic(cell.mix, cell.config, 2)._reads[0].key != traffic._reads[0].key
    listed = {m["name"] for m in cell.per_layer}
    assert {"exec.bsi_prep_ms", "device.bsi_dispatch_ms", "device.bsi_fetch_ms",
            "exec.bsi_decode_ms", "exec.bsi_inplace_share", "device.bsi_roofline",
            "device.launch_ms", "device.window_new_programs"} <= listed
    assert not {"exec.plan_ms", "device.count_roofline", "exec.topn_prep_ms"} & listed


def test_the_roofline_counts_the_planes_an_answer_has_to_read(mix):
    """The reducer's bytes function, from the request's text and the
    configuration's field ranges: the not-null row and the magnitude
    rows of each field named, a row a Bitmap; no sign row where no value
    is negative, no pad."""
    read = metrics.load_reducer("bsi_roofline")
    planes_of, field_planes = (read.__globals__[n] for n in ("planes_of", "field_planes"))
    cfg = run.read_json(os.path.join(BENCH, "configs", "ssb-sf100-q1.json"))
    assert [field_planes(cfg["measures"]["fields"][f])
            for f in ("lo_discounted", "lo_discount", "lo_quantity")] == [28, 5, 7]
    assert field_planes([-3, 2]) == 1 + 2 + 1
    fill = dict(year=1, month=1, week=1, dlo=1, dhi=3, k=9, qlo=1, qhi=10)
    for name, text in mix["read"]["templates"].items():
        assert planes_of(text.format(**fill), cfg) == cfg["planes_read"][name]
    assert cfg["planes_read"]["q1.1"] == 41 and cfg["planes_read"]["q1.3"] == 42
    assert read({"profile": None}) is None  # no profile: nothing to read, not 0


def test_a_program_without_the_in_place_aggregate_is_refused_before_a_server_boots(
    kind, tmp_path
):
    """The configuration names what it needs of the program, and the kind
    reads it as text: the parent commit, which copies a 7.8 GB leaf batch
    a text beside 10.2 GB of planes, fails at once and boots no server."""
    cfg = run.read_json(os.path.join(BENCH, "configs", "ssb-sf100-q1.json"))
    assert [n["text"] for n in cfg["needs"]] == ["def aggregate_planes"]
    kind.program_can_serve(cfg)  # this tree
    old = tmp_path / "pilosa_tpu" / "ops"
    old.mkdir(parents=True)
    (old / "bitplane.py").write_text("def gather_planes(planes, slots): ...\n")
    with pytest.raises(run.HarnessError, match="cannot serve 'ssb-sf100-q1'"):
        kind.program_can_serve(cfg, root=str(tmp_path))
    with pytest.raises(run.HarnessError, match="cannot serve 'ssb-sf100-q1'"):
        kind.Reference(dict(cfg, needs=[dict(cfg["needs"][0], file="nowhere.py")]), 1)


@pytest.mark.parametrize("seed", [3, 2_147_483_659, 3_400_000_041])
def test_both_controls_are_refused_and_the_sound_reference_is_not(tiny_bench, seed):
    cell = run.Cell(tiny_bench, CELL, _rig())
    ref = cell.kind.Reference(cell.config, seed)
    for unit in ref.units():
        ref.make(unit)
    ref.seal()
    traffic = cell.kind.Traffic(cell.mix, cell.config, seed)
    assert control.judge(ref, traffic, 60, None)["correct"] is True
    for broken in cell.kind.CONTROLS:
        verdict = control.judge(ref, traffic, 60, broken)
        assert verdict["correct"] is False
        assert verdict["compared"]["wrong_answers"]["value"] >= 5
    with pytest.raises(ValueError, match="unknown control"):
        ref.answer(traffic._reads[0].key, broken="nothing")


# ---------------------------------------------------------------------------
# (c) the cell through the benchmark's own run_cell
# ---------------------------------------------------------------------------

# Per-layer metrics listed for the cell that only a chip run can read.
DEVICE_ONLY = {"device.idle_share", "device.bsi_roofline",
               "device.hbm_in_use_bytes", "device.hbm_peak_bytes"}


@pytest.fixture
def in_a_test_process(monkeypatch):
    """``run_cell`` refuses a caller that has initialised a JAX backend,
    because on the chip's machine that caller would hold the chip.  This
    process has, and the server child is held to the CPU by its
    environment."""
    monkeypatch.setattr(run, "jax_backend_in_this_process", lambda: False)


def test_a_traced_rehearsal_is_correct_and_reads_every_listed_metric(
    in_a_test_process, tiny_bench
):
    rc, line = run.run_cell(tiny_bench, CELL, 3_400_000_033, 1.5, True, _rig())
    assert rc == 0
    line = json.loads(json.dumps(line))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8
    c = line["compared"]
    assert c["wrong_answers"] == {"value": 0, "limit": 0}
    assert c["hosteval_launches"] == {"value": 0, "limit": 0}
    assert c["device_launches"]["value"] >= 1
    cell = run.Cell(tiny_bench, CELL, _rig())
    listed = {m["name"] for m in cell.per_layer}
    assert set(line["metrics"]) == listed - DEVICE_ONLY
    m = {k: v["value"] for k, v in line["metrics"].items()}
    assert m["exec.bsi_inplace_share"] == 100.0
    # the warm-up compiled every template's program
    assert m["device.window_new_programs"] == 0 and m["device.window_compile_ms"] == 0
    assert m["exec.bsi_prep_ms"] > 0 and m["device.bsi_dispatch_ms"] > 0
    assert m["device.bsi_fetch_ms"] > 0 and m["exec.bsi_decode_ms"] > 0
    assert m["device.launch_ms"] > 0
