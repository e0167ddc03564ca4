"""The server with its timed path broken underneath, for the tests that
must see ``correct`` come out false.

    python benchmarks/tests/broken_server.py <fault> server --data-dir ... --bind ...

``answer_altered``     every third answer the executor produces to a Count,
                       a TopN or a Sum is one too high (a TopN's first pair)
``half_left_out``      a Count over all slices is answered from every second slice
``exchange_left_out``  on a mesh, the total is one chip's partial: the
                       all-reduce between the chips is skipped
"""

import dataclasses
import os
import sys

fault = sys.argv.pop(1)
# The child's cwd is the checkout's root; a script's own directory is
# all that python puts on the path.
sys.path.insert(0, os.getcwd())

from pilosa_tpu.exec import executor as executor_mod  # noqa: E402
from pilosa_tpu.exec import plan  # noqa: E402

if fault == "answer_altered":
    orig_execute = executor_mod.Executor.execute
    calls = [0]

    def one_too_high(answer):
        if type(answer) is int:
            return answer + 1
        if isinstance(answer, list) and answer:  # TopN's pairs
            first = answer[0]
            return [dataclasses.replace(first, count=first.count + 1)] + answer[1:]
        if hasattr(answer, "value"):  # a Sum's ValCount
            return dataclasses.replace(answer, value=answer.value + 1)
        return answer

    def execute(self, index, q, slices=None, opt=None):
        out = orig_execute(self, index, q, slices, opt)
        if out and any(call in str(q) for call in ("Count", "TopN", "Sum")):
            calls[0] += 1
            if calls[0] % 3 == 0:
                out = [one_too_high(out[0])] + list(out[1:])
        return out

    executor_mod.Executor.execute = execute
elif fault == "half_left_out":
    orig_execute = executor_mod.Executor.execute

    def execute(self, index, q, slices=None, opt=None):
        if slices is None and "Count" in str(q):
            n = self.holder.index(index).max_slice() + 1
            slices = list(range(0, n, 2))
        return orig_execute(self, index, q, slices, opt)

    executor_mod.Executor.execute = execute
elif fault == "exchange_left_out":
    orig_total = plan.compiled_total_count

    def compiled_total_count(expr, mesh=None):
        if mesh is None:
            return orig_total(expr, None)
        alone = orig_total(expr, None)

        def one_chip(batch):
            return alone(batch.addressable_shards[0].data)

        return one_chip

    plan.compiled_total_count = compiled_total_count
else:
    raise SystemExit(f"unknown fault {fault!r}")

from pilosa_tpu.cli.main import main  # noqa: E402

sys.exit(main())
