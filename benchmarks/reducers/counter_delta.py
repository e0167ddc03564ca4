"""Growth over the window of the ``/metrics`` series ``series``."""


def read(ev, series):
    before, after = ev["metrics"]["before"], ev["metrics"]["after"]
    if series not in after:
        return None
    return after[series] - before.get(series, 0.0)
