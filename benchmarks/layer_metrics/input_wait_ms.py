"""Milliseconds per step the main thread waited for its next batch
(``input_wait`` spans of the measured epochs, boundaries' first batches left
out)."""

from benchmarks import spans


def read(run: dict) -> float | None:
    return spans.input_wait_ms(run)
