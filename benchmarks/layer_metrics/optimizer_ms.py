"""Device milliseconds per step in the scope ``lars`` (the update and its
application), on device 0."""

from benchmarks import scopes


def read(run: dict) -> float | None:
    return scopes.read(run, "optimizer")
