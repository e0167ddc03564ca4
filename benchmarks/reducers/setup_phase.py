"""One phase of the set-up, on the harness's clock."""


def read(ev, phase):
    return ev["setup"].get(phase)
