"""Seconds of ``checkpoint/digest`` + ``checkpoint/sidecar`` of the
preemption's checkpoint: the sha256 walk over its files and the sidecar's
atomic write."""

from benchmarks import spans


def read(run: dict) -> float | None:
    digest = spans.duration(run, "checkpoint/digest", under="preempt")
    sidecar = spans.duration(run, "checkpoint/sidecar", under="preempt")
    return None if digest is None else digest + (sidecar or 0.0)
