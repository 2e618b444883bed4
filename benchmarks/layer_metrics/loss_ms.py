"""Device milliseconds per step in the scope ``ntxent``, both directions, its
``all_gather`` included, on device 0."""

from benchmarks import scopes


def read(run: dict) -> float | None:
    return scopes.read(run, "loss")
