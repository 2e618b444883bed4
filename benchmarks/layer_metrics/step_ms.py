"""Milliseconds per step over the measured epochs: the window's seconds over
its steps, boundaries included."""

from benchmarks import reduce


def read(run: dict) -> float | None:
    m = reduce.measured(run)
    if m is None:
        return None
    return 1e3 * m["seconds"] / m["steps"]
