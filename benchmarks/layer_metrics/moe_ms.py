"""Device milliseconds per step in the scope ``moe`` (inside ``encoder``: a
sequence encoder's ``router`` and ``experts``), both directions, on device 0."""

from benchmarks import scopes


def read(run: dict) -> float | None:
    return scopes.ms_in(run, ["moe"])
