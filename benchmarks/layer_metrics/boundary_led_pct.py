"""Share of the measured epochs' boundaries at which the next epoch's first
step was already dispatched when the loss fetch began: ``boundary`` spans
with ``n >= 1``. A boundary belongs to the measured epochs if its
``boundary/loss_fetch`` ends inside them, as for ``boundary_host_ms``. A
trainer that records no ``n`` on its boundaries reads ``None``."""

from benchmarks import spans


def read(run: dict) -> float | None:
    data = spans.of_run(run)
    if data is None:
        return None
    intervals = spans.measured_intervals(run)
    led = [
        boundary["n"] >= 1
        for boundary in spans.named(data["spans"], "boundary")
        if "n" in boundary and any(
            child["name"] == "boundary/loss_fetch" and spans.inside(child["t1"], intervals)
            for child in spans.children(data["spans"], boundary)
        )
    ]
    return 100.0 * sum(led) / len(led) if led else None
