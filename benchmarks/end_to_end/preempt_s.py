"""Seconds from SIGTERM to the trainer's own exit, a resumable checkpoint on
disk: the in-flight steps drained, the orbax save, the sha256. What the
interpreter and the TPU runtime take to shut down afterwards is left out."""


def read(run: dict) -> float | None:
    if run.get("t_sigterm") is None or run.get("t_exit") is None:
        return None
    return run["t_exit"] - run["t_sigterm"]
