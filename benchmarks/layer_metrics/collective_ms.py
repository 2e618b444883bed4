"""Milliseconds per step in which a collective was in progress on device 0
(total, not only the part compute did not hide)."""

from benchmarks import xplane


def read(run: dict) -> float | None:
    trace = run.get("trace")
    if not trace or not trace["devices"]:
        return None
    return xplane.collective_ms_per_step(trace["devices"][0])
