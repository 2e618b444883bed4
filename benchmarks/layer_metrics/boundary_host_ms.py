"""Mean milliseconds of host work per epoch boundary of the measured epochs
during which the device has nothing to run: from the end of
``boundary/loss_fetch`` to the end of ``boundary``."""

from benchmarks import spans


def read(run: dict) -> float | None:
    return spans.boundary_host_ms(run)
