"""The time the chip's peaks need for the ``experts`` scope's work
(``sequence_work.experts_work``: the three products of every pair the held
experts computed, by the program's counter ``held_pairs``, forward and
backward once) over the scope's measured device time."""

from benchmarks import scopes, sequence_work


def read(run: dict) -> float | None:
    measured, peak = scopes.ms_in(run, ["experts"]), sequence_work.peak_of(run)
    pairs = sequence_work.counter(run, "held_pairs")
    if not measured or peak is None or pairs is None:
        return None
    work = sequence_work.experts_work(run["cell"]["config"], pairs)
    return 100.0 * sequence_work.roofline_ms(work, peak) / measured
