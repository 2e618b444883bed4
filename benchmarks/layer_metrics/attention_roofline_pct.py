"""The time the chip's peaks need for the ``attention`` scope's work
(``sequence_work.attention_work``: projections and the allowed score pairs,
forward and backward once) over the scope's measured device time."""

from benchmarks import scopes, sequence_work


def read(run: dict) -> float | None:
    measured, peak = scopes.ms_in(run, ["attention"]), sequence_work.peak_of(run)
    mix = run["cell"]["traffic"]
    if not measured or peak is None or "crop_lengths" not in mix:
        return None
    work = sequence_work.attention_work(
        run["cell"]["config"], mix["crop_lengths"], mix["rows_per_step"]
    )
    return 100.0 * sequence_work.roofline_ms(work, peak) / measured
