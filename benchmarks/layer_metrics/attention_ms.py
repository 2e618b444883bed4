"""Device milliseconds per step in the scope ``attention`` (inside
``encoder``: a sequence encoder's norm, q, k, v and output projections,
rotary embedding and the attention itself), both directions, on device 0."""

from benchmarks import scopes


def read(run: dict) -> float | None:
    return scopes.ms_in(run, ["attention"])
