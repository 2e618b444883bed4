"""Seconds from SIGTERM to the end of the preemption's ``checkpoint/drain``
span: the signal noticed by the host loop, and the steps in flight drained."""

from benchmarks import spans


def read(run: dict) -> float | None:
    drain = spans.last(run, "checkpoint/drain", under="preempt")
    if drain is None or run.get("t_sigterm") is None:
        return None
    return drain["t1"] - run["t_sigterm"]
