"""The deployment ``chembl-tanimoto`` at a size a test holds: the served
``TopN(Bitmap(q), n, tanimotoThreshold=t)`` against the deployment
kind's own plain reference through HTTP, on a narrow plane and on one
of full width; the control's broken variants; the data rule's three
properties; and a traced rehearsal of the cell through the benchmark's
``run_cell`` that reads every per-layer metric the cell lists.
Everything here runs on the CPU; what the cell does on the chip only a
chip run can say (``PERF.md``)."""

import copy
import json
import os
import sys

import numpy as np
import pytest

from pilosa_tpu.net.client import InternalClient
from pilosa_tpu.net.server import Server
from pilosa_tpu.obs import stats as stats_mod
from pilosa_tpu.ops import bitplane as bp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import control  # noqa: E402 — benchmarks/control.py
import run  # noqa: E402 — benchmarks/run.py

CELL = "chembl-tanimoto.screen"
# what only a chip's profile gives
DEVICE_ONLY = {"device.tanimoto_roofline", "device.idle_share",
               "device.hbm_in_use_bytes", "device.hbm_peak_bytes"}


def tiny_config(molecules=3000) -> dict:
    """The shipped configuration cut to a test's size: its kind, schema,
    frame options, data rule and keys as they are."""
    cfg = run.read_json(os.path.join(BENCH, "configs", "chembl-tanimoto.json"))
    cfg.update(molecules=molecules, load_units=2)
    return cfg


@pytest.fixture(scope="module")
def kind():
    return run.load_kind("tanimoto-topn")


def loaded(kind, cfg, seed):
    ref = kind.Reference(cfg, seed)
    units = [ref.make(u) for u in ref.units()]
    ref.seal()
    return ref, units


@pytest.fixture(scope="module", params=["narrow", "wide"])
def served(request, kind, tmp_path_factory):
    """A server with the kind's schema and the seed's molecules, loaded
    as the harness loads them.  ``wide``: one more row holds the slice's
    last column, so the plane is of full width."""
    cfg = tiny_config()
    ref, units = loaded(kind, cfg, 36_000_000_011)
    s = Server(
        data_dir=str(tmp_path_factory.mktemp(request.param) / "data"),
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
        for u in units:
            c.import_bits(u["index"], u["frame"], u["slice"], (u["rows"], u["cols"]))
        if request.param == "wide":
            c.execute_pql(cfg["index"], f"SetBit(frame={cfg['frame']}, "
                          f"rowID={10 * cfg['molecules']}, columnID={bp.SLICE_WIDTH - 1})")
        frag = s.holder.fragment(cfg["index"], cfg["frame"], "standard", 0)
        assert frag.plane_words() == (128 if request.param == "narrow" else 32768)
        assert not frag.holds_sparse_tier_rows()
        yield c, ref, cfg, s
    finally:
        s.close()


def asked(c, cfg, q, t, n=2_000_000):
    text = (f"TopN(Bitmap(frame={cfg['frame']}, rowID={q}), frame={cfg['frame']}, "
            f"n={n}, tanimotoThreshold={t})")
    status, data = c._request("POST", f"/index/{cfg['index']}/query", body=text.encode())
    assert status == 200, data
    return json.loads(data)["results"][0]


@pytest.mark.parametrize("t", [1, 70, 80, 90, 100])
def test_the_served_answers_are_the_references_exact_pairs(served, kind, t):
    c, ref, cfg, _ = served
    rng = np.random.default_rng(t)
    sizes = []
    for q in rng.choice(ref.n, 12, replace=False).tolist():
        want = ref.answer(("TopN", q, 2_000_000, t))
        # the reference scores the rows that can pass; every row put
        # through the rule says the same
        assert want == ref.answer_plain(("TopN", q, 2_000_000, t))
        assert kind.normalise(asked(c, cfg, q, t)) == want
        sizes.append(len(want))
        if t < 100:
            # itself (and any copy of it) first: it shares all its bits
            assert (q, int(ref.card[q])) in want and want[0][1] == ref.card[q]
    assert (max(sizes) == 0) == (t == 100)
    # n trims the ranking
    q = int(np.argmax(ref.card))
    full = ref.answer(("TopN", q, 2_000_000, 1))
    assert len(full) > 3
    assert kind.normalise(asked(c, cfg, q, 1, n=3)) == full[:3] == ref.answer(("TopN", q, 3, 1))


def test_a_query_alone_in_its_answer_and_one_with_an_empty_window(served, kind):
    c, ref, cfg, s = served
    # a molecule whose only hit at t = 90 is itself
    alone = next(q for q in range(ref.n)
                 if ref.answer(("TopN", q, 2_000_000, 90)) == [(q, int(ref.card[q]))])
    assert kind.normalise(asked(c, cfg, alone, 90)) == [(alone, int(ref.card[alone]))]
    # a row the index does not hold: nothing is its window; and t = 100
    # keeps no cardinality (cnt > s and cnt < s)
    assert asked(c, cfg, 5 * ref.n, 70) == []
    assert ref.window_rows(("TopN", alone, 2_000_000, 100)) == 0
    assert asked(c, cfg, alone, 100) == []
    # nothing was scored on the host, by either tier's way
    assert s.holder.stats.snapshot()["counts"].get("topn.host_scored_rows") == 0


def test_the_answers_took_the_walked_scorer_over_the_plane_in_place(served, kind):
    c, ref, cfg, s = served
    tracer = s.executor.tracer
    q, t = 17, 70
    asked(c, cfg, q, t)
    spans = {sp["name"]: sp for sp in json.loads(
        c._request("GET", "/debug/traces")[1])["traces"][-1]["spans"]}
    frag = s.holder.fragment(cfg["index"], cfg["frame"], "standard", 0)
    prep, score = spans["topn.prep"]["tags"], spans["topn.score"]["tags"]
    assert prep["build"] == "rows" and prep["rows"] == frag.plane_rows()
    # the window's row count is the reference's: what the roofline divides by
    assert prep["candidates"] == ref.window_rows(("TopN", q, 2_000_000, t))
    narrow = frag.plane_words() < bp.WORDS_PER_SLICE
    assert score["layout"] == ("narrow" if narrow else "wide")
    assert score["stride_words"] == frag.plane_words()
    assert spans["topn.dispatch"]["tags"]["launches"] == 1
    assert spans["topn.dispatch"]["tags"]["bytes"] == frag.plane_nbytes
    fetch = spans["topn.fetch"]["tags"]
    assert fetch["handback"] == "one"
    assert fetch["hits"] == len(ref.answer(("TopN", q, 2_000_000, t)))
    assert spans["topn.select"]["tags"] == {"parts": 1, "way": "rows"}
    assert "hosteval" not in spans and tracer is not None


@pytest.fixture
def tiny_handback(monkeypatch):
    """A step of 4 rows and a launch of 16, so that a test's answers
    reach past each; the walked scorer's programs are traced anew under
    them and again after."""
    monkeypatch.setattr(bp, "ROW_STEP", 4)
    monkeypatch.setattr(bp, "ROW_HITS", 16)
    bp._score_rows_xla.clear_cache()
    bp._SCORE_SEEN.clear()
    yield
    bp._score_rows_xla.clear_cache()
    bp._SCORE_SEEN.clear()


@pytest.mark.parametrize("handback, pairs", [
    ("one", range(1, 5)), ("more", range(5, 17)), ("vector", range(17, 3000)),
])
def test_the_fetch_says_how_the_kept_rows_were_handed_back(
    served, kind, tiny_handback, handback, pairs
):
    """``topn.fetch`` tags what the device's hand-back took: ``one``
    step, ``more`` steps, or the whole ``vector`` for a text that keeps
    more rows than a launch compacts; ``hits`` is the reference's pair
    count (``n`` never trims)."""
    c, ref, cfg, _ = served
    # molecules no other test here asks about: a score is memoized 10 s
    q, t, want = next(
        (q, t, want) for t in (90, 70, 1) for q in range(1000, 1200)
        if len(want := ref.answer(("TopN", q, 2_000_000, t))) in pairs
    )
    assert kind.normalise(asked(c, cfg, q, t)) == want
    fetch = next(sp for sp in json.loads(
        c._request("GET", "/debug/traces")[1])["traces"][-1]["spans"]
        if sp["name"] == "topn.fetch")["tags"]
    assert fetch["handback"] == handback and fetch["hits"] == len(want)
    assert fetch["arrays"] == 3


def test_more_hits_than_a_launch_compacts_are_all_returned(served, kind, monkeypatch):
    c, ref, cfg, _ = served
    q = int(np.argmax(ref.card))
    want = ref.answer(("TopN", q, 2_000_000, 1))
    monkeypatch.setattr(bp, "ROW_HITS", 8)
    bp._score_rows_xla.clear_cache()
    bp._SCORE_SEEN.clear()
    try:
        assert len(want) > 8 and kind.normalise(asked(c, cfg, q, 1)) == want
    finally:
        bp._score_rows_xla.clear_cache()
        bp._SCORE_SEEN.clear()


# ---------------------------------------------------------------------------
# the data rule, the control, the files
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def rule(kind):
    cfg = tiny_config(molecules=20000)
    ref, _ = loaded(kind, cfg, 36_000_000_029)
    traffic = kind.Traffic(
        run.read_json(os.path.join(BENCH, "traffic", "tanimoto-screen.json")), cfg, 5)
    return ref, [traffic.read(i).key for i in range(120)]


def test_the_data_rule_gives_what_a_screen_sees(rule):
    ref, keys = rule
    lo, hi = ref.config["data"]["bits_range"]
    assert lo - 15 <= ref.card.min() and ref.card.max() <= hi
    assert 40 <= ref.card.mean() <= 56
    by_t = {t: [len(ref.answer(k)) for k in keys if k[3] == t] for t in (70, 80, 90)}
    assert all(len(v) == 40 for v in by_t.values())  # dealt 1 : 1 : 1
    assert 5 <= np.median(by_t[70]) <= 500
    assert min(by_t[90]) >= 1 and np.median(by_t[90]) >= 2
    share = np.mean([ref.window_rows(k) for k in keys]) / ref.n
    assert 0.2 <= share <= 0.9
    # every text asks about another molecule, none the warm-up's
    assert len({k[1] for k in keys}) == len(keys)


@pytest.mark.parametrize("t", [1, 34, 70, 80, 90, 99, 100])
def test_the_reference_scores_the_rows_that_can_pass_and_loses_none(rule, t):
    """``answer`` counts over the query's rarest positions and scores who
    holds enough of them; ``answer_plain`` puts every row through the
    rule.  The same pairs in the same order, whatever the threshold
    leaves a row free to lack, and under every ``n``."""
    ref, keys = rule
    kept = 0
    for _call, q, _n, _t in keys[:40]:
        for n in (2_000_000, 2):
            key = ("TopN", q, n, t)
            assert ref.answer(key) == ref.answer_plain(key), key
        kept += len(ref.answer(("TopN", q, 2_000_000, t)))
    assert (kept == 0) == (t == 100)


@pytest.mark.parametrize("seed", [3, 36_000_000_041])
def test_every_control_is_refused_and_the_sound_reference_is_not(kind, seed):
    cfg = tiny_config(molecules=60000)  # above the default cache's 50,000
    ref, _ = loaded(kind, cfg, seed)
    mix = run.read_json(os.path.join(BENCH, "traffic", "tanimoto-screen.json"))
    traffic = kind.Traffic(mix, cfg, seed)
    assert control.judge(ref, traffic, 60, None)["correct"] is True
    for broken in kind.CONTROLS:
        verdict = control.judge(ref, traffic, 60, broken)
        assert verdict["correct"] is False, broken
        assert verdict["compared"]["wrong_answers"]["value"] >= 1


def test_a_program_without_the_narrow_layout_is_refused_before_a_server_boots(
    kind, tmp_path
):
    cfg = run.read_json(os.path.join(BENCH, "configs", "chembl-tanimoto.json"))
    assert [n["text"] for n in cfg["needs"]] == ["def score_rows", "def _relayout_locked"]
    kind.program_can_serve(cfg)  # this tree
    old = tmp_path / "pilosa_tpu" / "ops"
    old.mkdir(parents=True)
    (old / "bitplane.py").write_text("def score_planes(planes, slots): ...\n")
    with pytest.raises(run.HarnessError, match="cannot serve 'chembl-tanimoto'"):
        kind.program_can_serve(cfg, root=str(tmp_path))
    assert cfg["molecules"] == 1_735_442 and cfg["fingerprint_bits"] == 4096
    assert cfg["cache_size"] >= cfg["molecules"] and cfg["n"] >= cfg["molecules"]
    assert set(cfg["reduced"]) == {"inverse_view"}


# ---------------------------------------------------------------------------
# a rehearsal of the cell through the benchmark's run_cell
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    bench = copy.deepcopy(run.read_json(os.path.join(REPO, "BENCHMARK.json")))
    path = tmp_path_factory.mktemp("cfg") / "chembl-tiny.json"
    path.write_text(json.dumps(tiny_config(molecules=4000)))
    next(c for c in bench["configs"] if c["name"] == "chembl-tanimoto")["file"] = str(path)
    return bench


def _rig():
    env = {"JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
           "PILOSA_TPU_COMPILATION_CACHE_DIR": "off"}
    return run.Rig(platform="cpu", extra_env=env)


def test_a_traced_rehearsal_is_correct_and_reads_every_listed_metric(
    monkeypatch, tiny_bench
):
    monkeypatch.setattr(run, "jax_backend_in_this_process", lambda: False)
    rc, line = run.run_cell(tiny_bench, CELL, 3_600_000_033, 1.0, True, _rig())
    assert rc == 0
    line = json.loads(json.dumps(line))
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 8
    c = line["compared"]
    assert c["wrong_answers"] == {"value": 0, "limit": 0}
    assert c["hosteval_launches"] == {"value": 0, "limit": 0}
    assert c["device_launches"]["value"] >= 1
    cell = run.Cell(tiny_bench, CELL, _rig())
    listed = {m["name"] for m in cell.per_layer}
    assert {"exec.tanimoto_prep_ms", "exec.tanimoto_select_ms",
            "device.tanimoto_dispatch_ms", "device.tanimoto_fetch_ms",
            "exec.tanimoto_narrow_share", "exec.tanimoto_host_scored_rows",
            "device.tanimoto_roofline",
            "device.tanimoto_handback_one_share"} <= listed
    assert not any("topn_" in name or "bsi_" in name for name in listed)
    assert set(line["metrics"]) == listed - DEVICE_ONLY
    m = {k: v["value"] for k, v in line["metrics"].items()}
    # every text is new and every answer walked the narrow rows in place
    assert m["exec.tanimoto_narrow_share"] == 100.0
    assert m["exec.tanimoto_host_scored_rows"] == 0
    # a test's 4,000 molecules keep far fewer rows than a step locates
    assert m["device.tanimoto_handback_one_share"] == 100.0
    assert m["device.window_new_programs"] == 0 and m["device.window_compile_ms"] == 0
    assert m["exec.tanimoto_prep_ms"] > 0 and m["device.tanimoto_dispatch_ms"] > 0
