"""Seconds the trainer spent lowering and compiling ``pretrain_step``, or
lowering it and loading it from the persistent cache (the ``compile``
event's own ``seconds``; its ``cache_hit`` is printed before the result)."""

from benchmarks import reduce


def read(run: dict) -> float | None:
    compiles = [
        e for e in reduce.events_of(run, "compile")
        if e.get("name") == "pretrain_step"
    ]
    if not compiles:
        return None
    return float(compiles[0]["seconds"])
