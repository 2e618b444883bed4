"""Device milliseconds per step in the scopes ``encoder`` and ``head``, ops
without ``transpose(`` in their ``op_name``: the forward passes, on device 0."""

from benchmarks import scopes


def read(run: dict) -> float | None:
    return scopes.read(run, "fwd")
