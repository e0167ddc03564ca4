#!/usr/bin/env python3
"""The control of a cell's ``correct``: the reference put in the
server's place with one stated guarantee given up.

    python3 benchmarks/control.py --workload <cell> --seed <n> [--answers 100]

No server runs.  The cell's data is made from the seed at the cell's own
size, the mix's texts are drawn as a window draws them, and each is
answered by the reference of the configuration's kind with one stated
guarantee broken in one of the ways the kind names in ``CONTROLS`` (for
``two-row-count``, "bit-exact over every column of every slice": the
last slice not counted; the even slices counted twice as an estimate).
Those answers go through the comparison a run makes
(``run.compare_answers`` and ``run.is_correct``) as a window's answers
do, and have to come out not correct.  One line of JSON per control.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

from traffic import Mix, Record  # noqa: E402


def answers(ref, traffic: Mix, n: int, broken: str | None) -> list[Record]:
    """The window's first ``n`` requests, answered by the reference with
    ``broken`` given up, as the records a window keeps."""
    out = []
    for i in range(n):
        rec = Record()
        rec.client = i % traffic.clients
        rec.req = traffic.read(i // traffic.clients if traffic.fixed else i, rec.client)
        rec.status, rec.answer = 200, ref.answer(rec.req.key, broken=broken)
        rec.sent = rec.done = rec.latency_s = rec.late_s = 0.0
        rec.trace_id = ""
        out.append(rec)
    return out


def judge(ref, traffic: Mix, n: int, broken: str | None) -> dict:
    """The run's own comparison of those answers: the numbers beside
    their limits, and ``correct``.  The answers come from a reference, in
    the form it answers in, so nothing is left to normalise."""
    from run import compare_answers, is_correct

    _records, compared = compare_answers(answers(ref, traffic, n, broken), ref,
                                         lambda answer: answer)
    return {"correct": is_correct(compared), "compared": compared}


def main(argv: list[str] | None = None) -> int:
    from run import ROOT, Cell, read_json

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--answers", type=int, default=100)
    args = ap.parse_args(argv)
    cell = Cell(read_json(os.path.join(ROOT, "BENCHMARK.json")), args.workload)
    ref = cell.kind.Reference(cell.config, args.seed)
    for unit in ref.units():
        ref.make(unit)
    ref.seal()
    traffic = cell.kind.Traffic(cell.mix, cell.config, args.seed)
    for broken in (None, *cell.kind.CONTROLS):
        print(json.dumps({
            "workload": args.workload, "seed": args.seed, "control": broken,
            **judge(ref, traffic, args.answers, broken),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
