"""Share of the reduced trace window in which device 0 ran no op and the
main thread was in no ``boundary``, ``checkpoint`` or ``preempt`` span: idle
that is the step loop's own doing. ``device_idle_pct`` less this is the
boundaries' share."""

from benchmarks import spans


def read(run: dict) -> float | None:
    return spans.idle_in_steps_pct(run)
