"""Seconds from process start to the trainer's ``run_start`` event: imports,
TPU runtime start, the synthetic images, the initial state. No step program
has been lowered yet."""

from benchmarks import reduce


def read(run: dict) -> float | None:
    starts = reduce.events_of(run, "run_start")
    if not starts:
        return None
    return starts[0]["seen"] - run["t_start"]
