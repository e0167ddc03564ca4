#!/usr/bin/env python3
"""A planted fault under a real run at a cell's own size, on the chip:

    python3 benchmarks/tests/fault_at_size.py --workload <cell> --seed <n> \
        --seconds <s> --fault answer_altered

``run.run_cell`` as the command line drives it, with the server started
through ``broken_server.py``.  Prints the result line; exits 0 when the
run came out not correct, which is what has to happen, and 1 when the
comparison let the fault through.
"""

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fault", required=True)
    args = ap.parse_args()
    rig = run.Rig(server_argv=[sys.executable, os.path.join(HERE, "broken_server.py"),
                               args.fault, "server"])
    bench = run.read_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    rc, line = run.run_cell(bench, args.workload, args.seed, args.seconds, False, rig)
    print(json.dumps(line), flush=True)
    return 0 if rc == 0 and line is not None and line["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main())
