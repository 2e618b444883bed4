"""Device milliseconds per step in the scope ``augment`` (sample keys and the
two views), on device 0."""

from benchmarks import scopes


def read(run: dict) -> float | None:
    return scopes.read(run, "augment")
