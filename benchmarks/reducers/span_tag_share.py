"""Percent of the spans named ``span`` whose tag ``tag`` is ``value``."""

from metrics import spans_named


def read(ev, span, tag, value):
    spans = spans_named(ev, span)
    if not spans:
        return None
    return 100.0 * sum(1 for s in spans if s["tags"].get(tag) == value) / len(spans)
