"""Seconds of the trainer's ``boot/data`` span: ``load_dataset`` (50,000
synthetic images in the benchmark's cells)."""

from benchmarks import spans


def read(run: dict) -> float | None:
    return spans.duration(run, "boot/data")
