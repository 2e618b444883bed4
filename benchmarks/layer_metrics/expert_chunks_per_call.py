"""The chunks of sorted (token, expert) pairs that a step's expert layers
ran, over the step's layer-calls (``simclr_tpu/models/experts.py``): the
``epoch`` events' counter of that name, mean over the window's events. 1.0
where every call's held pairs fit one chunk; the static number of chunks
where every chunk ran."""

from benchmarks import sequence_work


def read(run: dict) -> float | None:
    return sequence_work.counter(run, "expert_chunks_per_call")
