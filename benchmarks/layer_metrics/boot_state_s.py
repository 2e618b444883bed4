"""Seconds of the trainer's ``boot/state`` span: optimizer, model, initial
state and its placement on the mesh, up to the ``run_start`` event."""

from benchmarks import spans


def read(run: dict) -> float | None:
    return spans.duration(run, "boot/state")
