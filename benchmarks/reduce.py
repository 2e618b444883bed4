"""From a run record to the window's arithmetic and to ``correct``.

A run record is what a runner returns (``runners/train.py``): the trainer's
events, each stamped by the benchmark's clock when it was seen, the times of
the first heartbeat, of SIGTERM and of the exit, the exit code and the
devices. Everything here is plain arithmetic on that record, so the tests
drive it with hand-written records.
"""

from __future__ import annotations

import hashlib
import math
import os

EXIT_PREEMPTED = 75  # the trainer's "preempted, resumable" (sysexits EX_TEMPFAIL)

# NT-Xent over 2N views scores each anchor against 2N-1 candidates, so an
# untrained encoder (every similarity alike) reads ln(2N-1). Rounding in
# bf16 and the first steps of warm-up move that by under 0.3% (the chip read
# 8.317-8.325 against ln 4095 = 8.3175, 6.928-6.939 against ln 1023 = 6.930,
# 9.724 against ln 16383 = 9.704), so 2% above it is already a loss that
# climbs: diverging. And a first epoch more than 5% below it has
# collapsed inside one warm-up epoch, which an objective that works does
# not do (no first epoch on the chip read under it at all).
LOSS_CEILING = 1.02
FIRST_LOSS_FLOOR = 0.95


def events_of(run: dict, kind: str) -> list[dict]:
    return [e for e in run["events"] if e.get("event") == kind]


def window_epochs(run: dict) -> list[dict]:
    """The ``epoch`` events seen from the first heartbeat to SIGTERM."""
    t0, t1 = run.get("t_first_beat"), run.get("t_sigterm")
    if t0 is None or t1 is None:
        return []
    return [e for e in events_of(run, "epoch") if t0 <= e["seen"] <= t1]


def measured(run: dict) -> dict | None:
    """Steps, images and seconds between the first and the last ``epoch``
    event of the window. Both ends follow a loss fetch, so the device had
    finished; the epoch before the first event (compile, warm-up) is not
    counted, the work at each boundary between them is. In a traced run the
    epochs that overlap the profiler's start, capture and write-out are left
    out: they stalled ResNet-50's host for 6.7 s of a 12 s epoch, which is
    the profiler's time and not the program's (chip run, PR 24). ``None``
    unless two events a quarter of a second apart are left: a host clock is
    not trusted over less."""
    epochs = window_epochs(run)
    starts = events_of(run, "run_start")
    c0, c1 = run.get("capture") or (math.inf, -math.inf)
    pairs = [
        (a, b) for a, b in zip(epochs, epochs[1:])
        if b["seen"] <= c0 or a["seen"] >= c1
    ]
    if not pairs or not starts:
        return None
    seconds = sum(b["seen"] - a["seen"] for a, b in pairs)
    steps = sum(int(b["step"]) - int(a["step"]) for a, b in pairs)
    if seconds < 0.25 or steps <= 0:
        return None
    return {
        "steps": steps,
        "images": steps * int(starts[-1]["global_batch"]),
        "seconds": seconds,
        "epochs": len(pairs),
    }


def checkpoint_digest(path: str) -> str:
    """sha256 over a checkpoint directory: every file in sorted order, framed
    by its relative path and size. The trainer's rule
    (``simclr_tpu/utils/checkpoint.py``), kept here so that the program
    cannot pass its own check by changing it."""
    h = hashlib.sha256()
    files = []
    for root, _dirs, names in os.walk(path):
        for name in names:
            full = os.path.join(root, name)
            files.append((os.path.relpath(full, path), full))
    for rel, full in sorted(files):
        h.update(f"{rel}\x00{os.path.getsize(full)}\x00".encode())
        with open(full, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
    return h.hexdigest()


def checkpoint_verifies(run: dict) -> bool:
    """The ``preempt`` event names a checkpoint whose bytes match its
    ``.sha256`` sidecar. (The event, not a name pattern: a SIGTERM that lands
    on an epoch boundary leaves a plain boundary checkpoint.)"""
    preempts = events_of(run, "preempt")
    if not preempts:
        return False
    path = str(preempts[-1].get("checkpoint", "")).rstrip("/")
    try:
        with open(path + ".sha256") as f:
            recorded = f.read().split()
        return bool(recorded) and recorded[0] == checkpoint_digest(path)
    except OSError:
        return False


def attempted_and_failed(run: dict) -> tuple[int, int]:
    """Steps the trainer ran, and how many of them were lost: all of them if
    it did not leave as a preempted run does, the rolled-back ones if a
    non-finite loss sent it back to a checkpoint."""
    steps = [int(e["step"]) for e in run["events"] if "step" in e]
    attempted = max(steps, default=0)
    if run.get("exit_code") != EXIT_PREEMPTED or not events_of(run, "preempt"):
        return attempted, attempted
    if events_of(run, "nan_rollback"):
        kept = int(events_of(run, "preempt")[-1]["step"])
        return attempted, max(attempted - kept, 1)
    return attempted, 0


def verdict(run: dict) -> list[str]:
    """Why the run is not correct; empty if it is."""
    reasons = []
    if run["device"]["platform"] != "tpu":
        reasons.append(f"platform is {run['device']['platform']!r}, not 'tpu'")
    if run.get("exit_code") != EXIT_PREEMPTED:
        reasons.append(f"exit code {run.get('exit_code')}, not {EXIT_PREEMPTED}")
    if not run.get("checkpoint_verifies"):
        reasons.append("no preemption checkpoint whose sha256 verifies")
    if events_of(run, "nan_rollback"):
        reasons.append("a non-finite loss rolled the run back")
    starts, epochs = events_of(run, "run_start"), events_of(run, "epoch")
    if not starts or not epochs:
        reasons.append("no run_start or no epoch event")
    else:
        untrained = math.log(2 * int(starts[-1]["global_batch"]) - 1)
        losses = [float(e["loss"]) for e in epochs]
        bad = [l for l in losses if not math.isfinite(l) or l > LOSS_CEILING * untrained]
        if bad:
            reasons.append(
                f"epoch loss {bad[0]} is not finite or is above "
                f"{LOSS_CEILING} x ln(2N-1) = {LOSS_CEILING * untrained:.4f}"
            )
        if math.isfinite(losses[0]) and losses[0] < FIRST_LOSS_FLOOR * untrained:
            reasons.append(
                f"first epoch loss {losses[0]} is more than 5% under "
                f"ln(2N-1) = {untrained:.4f}"
            )
    t0 = run.get("t_first_beat")
    late = [
        e for e in run["events"]
        if e.get("event") in ("compile", "recompile_alarm")
        and t0 is not None and e["seen"] > t0
    ]
    if late:
        reasons.append(f"{len(late)} compilation(s) inside the window")
    if len(window_epochs(run)) < 2:
        reasons.append("the window held fewer than two epoch events")
    return reasons

