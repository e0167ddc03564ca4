"""Percent that the sum of the tag ``num`` is of the sum of the tag
``den`` over the spans named ``span`` that carry both: for instance the
slices a TopN src reaches over the slices it was evaluated on."""

from metrics import spans_named


def read(ev, span, num, den):
    spans = [s for s in spans_named(ev, span)
             if num in s["tags"] and den in s["tags"]]
    total = sum(s["tags"][den] for s in spans)
    if not total:
        return None
    return 100.0 * sum(s["tags"][num] for s in spans) / total
