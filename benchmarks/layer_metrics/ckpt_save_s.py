"""Seconds of the ``checkpoint/save`` span of the preemption's checkpoint: the
orbax save to its commit."""

from benchmarks import spans


def read(run: dict) -> float | None:
    return spans.duration(run, "checkpoint/save", under="preempt")
