"""Highest value after the window among the ``/metrics`` series that
start with ``prefix`` and end with ``suffix``."""


def read(ev, prefix, suffix):
    xs = [v for k, v in ev["metrics"]["after"].items()
          if k.startswith(prefix) and k.endswith(suffix)]
    return max(xs) if xs else None
