"""Device milliseconds per step in the scopes ``encoder`` and ``head``, ops with
``transpose(`` in their ``op_name``: the backward pass, on device 0."""

from benchmarks import scopes


def read(run: dict) -> float | None:
    return scopes.read(run, "bwd")
