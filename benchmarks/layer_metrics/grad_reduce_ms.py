"""Device milliseconds per step in the scope ``grad_reduce`` (the gradient sum
across chips and what prepares it), on device 0. Four-chip cell only."""

from benchmarks import scopes


def read(run: dict) -> float | None:
    return scopes.read(run, "grad_reduce")
