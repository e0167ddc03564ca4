"""Growth over the window of the ``/metrics`` series ``series``, per
answer the window received."""


def read(ev, series):
    before, after = ev["metrics"]["before"], ev["metrics"]["after"]
    answered = sum(1 for r in ev["records"] if r["ok"])
    if series not in after or not answered:
        return None
    return (after[series] - before.get(series, 0.0)) / answered
