"""Summed duration of the spans named ``span`` over the window's
traces: 0 where there are traces and no such span, nothing to read only
with no trace at all."""

from metrics import spans_named


def read(ev, span):
    if not ev["traces"]:
        return None
    return sum(s["duration_ms"] or 0.0 for s in spans_named(ev, span))
