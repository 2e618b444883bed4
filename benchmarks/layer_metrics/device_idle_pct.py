"""Share of the traced window in which no op ran on the device, mean over
the cell's devices."""

from benchmarks import xplane


def read(run: dict) -> float | None:
    trace = run.get("trace")
    if not trace:
        return None
    both = xplane.busy_and_window_s(trace["devices"], trace.get("window_ns"))
    if both is None:
        return None
    busy_s, window_s = both
    return 100.0 * (1.0 - busy_s / window_s)
