"""Seconds from process start to the end of the trainer's ``boot/runtime``
span: imports, compile-cache placement, configuration and the TPU runtime's
start (``main`` to ``mesh_from_config`` returned)."""

from benchmarks import spans


def read(run: dict) -> float | None:
    span = spans.last(run, "boot/runtime")
    return None if span is None else span["t1"] - run["t_start"]
