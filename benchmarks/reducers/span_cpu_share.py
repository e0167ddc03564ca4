"""Percent of the wall time of the spans named ``span`` that their
opening thread spent on the processor: the sum of ``cpu_ms`` over the
sum of ``duration_ms``, over the spans that have both.  What is left is
time off the processor: the GIL, a lock, a blocking fetch."""

from metrics import spans_named


def read(ev, span):
    both = [s for s in spans_named(ev, span)
            if s.get("cpu_ms") is not None and s["duration_ms"]]
    if not both:
        return None
    return 100.0 * sum(s["cpu_ms"] for s in both) / sum(s["duration_ms"] for s in both)
