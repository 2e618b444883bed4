"""The fullest held expert's (token, expert) pairs over the mean held
expert's, in the layer where that is largest: the ``epoch`` events' counter
of that name, mean over the window's events."""

from benchmarks import sequence_work


def read(run: dict) -> float | None:
    return sequence_work.counter(run, "expert_load_max_over_mean")
