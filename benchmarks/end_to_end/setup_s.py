"""Seconds from process start to the first heartbeat with ``step >= 1``: the
first step is dispatched, so imports, runtime start, data, state and the
compilation (or its load from the cache) are behind it."""


def read(run: dict) -> float | None:
    if run.get("t_first_beat") is None:
        return None
    return run["t_first_beat"] - run["t_start"]
