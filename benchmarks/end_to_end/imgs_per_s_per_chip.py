"""Images trained per second and chip, over all the work and all the time
between the first and the last ``epoch`` event of the window."""

from benchmarks import reduce


def read(run: dict) -> float | None:
    m = reduce.measured(run)
    if m is None:
        return None
    return m["images"] / m["seconds"] / run["device"]["count"]
