"""Deployment kinds: found by name, held to the seven parts, and judged
by the one comparison whatever their answers look like.  No server runs
here; ``test_rehearsal.py`` puts both kinds through a whole run."""

import json
import os

import pytest

import control
import metrics
import run
from conftest import BENCH, FIXTURES, ROOT
from server import HarnessError
from traffic import Mix, Record, Request

FIXTURE_RIG = run.Rig(root=ROOT, mix_dir=os.path.join(FIXTURES, "traffic"),
                      kind_dir=os.path.join(FIXTURES, "deployments"))


def fixture_bench(config, mix):
    return {
        "configs": [{"name": config, "file": f"benchmarks/tests/fixtures/{config}.json"}],
        "workloads": [{"name": "cell", "config": config, "traffic": mix, "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }


def fixture_cell(config="ranked-bsi-tiny", mix="topn-sum-tiny", rig=FIXTURE_RIG):
    return run.Cell(fixture_bench(config, mix), "cell", rig)


def loaded(cell, seed):
    ref = cell.kind.Reference(cell.config, seed)
    for unit in ref.units():
        ref.make(unit)
    ref.seal()
    return ref


def test_a_configuration_without_a_kind_is_two_row_count():
    bench = run.read_json(os.path.join(ROOT, "BENCHMARK.json"))
    for w in bench["workloads"]:
        cell = run.Cell(bench, w["name"])
        assert "kind" not in cell.config
        assert cell.kind.__file__ == os.path.join(BENCH, "deployments", "two-row-count.py")
        assert cell.kind.SITES == metrics.COUNT_SITES
        assert cell.kind.schema(cell.config) == [
            {"name": "segment", "frames": [{"name": "f"}]}]


def test_a_configuration_names_its_kind_and_the_rig_says_where_kinds_live():
    cell = fixture_cell()
    assert cell.config["kind"] == "ranked-bsi"
    assert cell.kind.__file__ == os.path.join(FIXTURES, "deployments", "ranked-bsi.py")
    # the shipped directory has no such kind: the same cell there is refused
    with pytest.raises(HarnessError, match="no deployment kind 'ranked-bsi'"):
        run.Cell(fixture_bench("ranked-bsi-tiny", "topn-sum-tiny"), "cell",
                 run.Rig(mix_dir=FIXTURE_RIG.mix_dir))


@pytest.mark.parametrize("name", ["no-such-kind", "../run", "", "two-row-count.py"])
def test_a_kind_that_is_no_file_of_the_directory_is_refused(name):
    with pytest.raises(HarnessError, match="no deployment kind"):
        run.load_kind(name)


@pytest.mark.parametrize("part", run.KIND_PARTS)
def test_a_kind_that_lacks_a_part_is_refused(tmp_path, part):
    lines = [f"{p} = None" for p in run.KIND_PARTS if p != part]
    (tmp_path / "short.py").write_text("\n".join(lines) + "\n")
    with pytest.raises(HarnessError, match=f"lacks {part}"):
        run.load_kind("short", str(tmp_path))


@pytest.mark.parametrize("seed", [1, 2_147_483_700, 3_000_000_019])
def test_the_fixture_kinds_control_is_not_correct(seed):
    """List- and dict-valued answers through the comparison a run makes."""
    cell = fixture_cell()
    ref = loaded(cell, seed)
    traffic = cell.kind.Traffic(cell.mix, cell.config, seed)
    sound = control.judge(ref, traffic, 16, None)
    assert sound["correct"] is True
    assert sound["compared"]["answers_compared"]["value"] == 16
    (broken,) = cell.kind.CONTROLS
    out = control.judge(ref, traffic, 16, broken)
    assert out["correct"] is False
    # every TopN and every Sum reads over the last slice too
    assert out["compared"]["wrong_answers"]["value"] == 16


def test_the_fixture_kinds_answers_in_the_servers_form_are_normalised():
    cell = fixture_cell()
    ref = loaded(cell, 11)
    traffic = cell.kind.Traffic(cell.mix, cell.config, 11)
    records = control.answers(ref, traffic, 8, None)
    for rec in records:  # as the server sends them: pairs as objects, a Sum as one
        if rec.req.key[0] == "TopN":
            assert len(rec.answer) == min(rec.req.key[2], 12)
            counts = [c for _id, c in rec.answer]
            assert counts == sorted(counts, reverse=True)
            rec.answer = [{"id": i, "count": c} for i, c in rec.answer]
        else:
            rec.answer = {"value": rec.answer[0], "count": rec.answer[1]}
    _records, compared = run.compare_answers(records, ref, cell.kind.normalise)
    assert compared["wrong_answers"] == {"value": 0, "limit": 0}
    records[0].answer[0]["count"] += 1
    records[1].answer["value"] += 1
    _records, compared = run.compare_answers(records, ref, cell.kind.normalise)
    assert compared["wrong_answers"] == {"value": 2, "limit": 0}
    assert run.is_correct(compared) is False


def test_the_fixture_kinds_reference_agrees_with_python_sets():
    cell = fixture_cell()
    ref = loaded(cell, 5)
    rows: dict[int, set] = {}
    for u in ref._bits.values():
        for r, c in zip(u["rows"].tolist(), u["cols"].tolist()):
            rows.setdefault(r, set()).add(c)
    want = sorted(((r, len(cols & rows[7])) for r, cols in rows.items()),
                  key=lambda p: (-p[1], p[0]))
    assert ref.answer(("TopN", 7, 12)) == [p for p in want if p[1]][:12]
    values = [v for u in ref._values.values() for v in u["values"].tolist()]
    assert ref.answer(("Sum", ">", 100)) == (
        sum(v for v in values if v > 100), sum(1 for v in values if v > 100))
    routes = {ref.make(u)["route"] for u in ref.units()}
    assert routes == {"import", "import-value"}


class AnsweringServer:
    """In the server's place for ``Run.readback``: answers a Count of one
    row from a reference of its own, or what it is told to."""

    def __init__(self, ref, lie=0):
        self.ref, self.lie, self.asked = ref, lie, []

    def request(self, method, path, body):
        self.asked.append((method, path, body.decode()))
        row = int(body.decode().split("rowID=")[1].rstrip("))"))
        result = self.ref.answer(("Bitmap", row)) + self.lie
        return 200, json.dumps({"results": [result]}).encode()

    def kill(self):
        pass


@pytest.mark.parametrize("lie,wrong", [(0, 0), (1, 2)])
def test_acknowledged_writes_are_applied_and_read_back_through_the_kind(lie, wrong):
    rig = run.Rig(mix_dir=FIXTURE_RIG.mix_dir)  # the shipped kinds, the tests' mix
    cell = fixture_cell("tiny", "rw-mix-tiny", rig)
    r = run.Run(cell, 9, 1.0, False, rig)
    try:
        for unit in r.ref.units():
            r.ref.make(unit)
        r.ref.seal()
        theirs = loaded(cell, 9)
        plan = r.traffic.schedule(5.0)
        raw = []
        for i, req in enumerate(plan):
            rec = Record()
            rec.req = req
            # every fourth write was refused: it must not be applied
            rec.status = 503 if req.kind == "write" and i % 4 == 0 else 200
            raw.append(rec)
            if req.kind == "write" and rec.status == 200:
                theirs.apply(req.key)
        acked = {q.req.key[0] for q in raw if q.req.kind == "write" and q.status == 200}
        assert acked == {22, 23}  # the configuration's last two rows
        r.server = AnsweringServer(theirs, lie)
        compared = {}
        r.readback({"raw_records": raw}, compared)
        assert compared == {"writes_not_read_back": {"value": wrong, "limit": 0}}
        assert [a[2] for a in r.server.asked] == [
            "Count(Bitmap(frame=f, rowID=22))", "Count(Bitmap(frame=f, rowID=23))"]
        assert all(a[:2] == ("POST", "/index/segment/query") for a in r.server.asked)
    finally:
        r.close()


def test_a_mix_of_a_kind_that_sends_no_writes_cannot_schedule_one():
    cell = fixture_cell()
    mix = dict(cell.mix, loop="open", rate_per_s=10, write_share=0.5)
    traffic = cell.kind.Traffic(mix, cell.config, 1)
    assert isinstance(traffic, Mix) and isinstance(traffic.read(0), Request)
    with pytest.raises(ValueError, match="sends no writes"):
        traffic.schedule(1.0)
