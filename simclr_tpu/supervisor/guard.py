"""In-process fault-tolerance guard for the training entry points.

Wired into ``main.py`` and ``supervised.py``, this gives every run three
reflexes the reference never had (save-only checkpoints, SURVEY §5.3-4):

  * **preemption**: SIGTERM/SIGINT set a flag; the host loop checks it at
    each step boundary, lands a checkpoint, and the entry point exits with
    the reserved "preempted, resumable" code 75 (EX_TEMPFAIL) — the contract
    the supervisor runner (and any outer orchestrator) keys restart-vs-crash off;
  * **heartbeat**: every process atomically rewrites its own
    ``<save_dir>/heartbeat[.p<i>].json`` each step so a supervisor can tell
    a slow step from a wedged one — and a per-host supervisor can tell
    WHICH host wedged;
  * **non-finite loss**: a NaN/Inf epoch loss rolls the run back to the
    newest sha256-verified checkpoint, with a bounded retry budget before
    the run is declared poisoned (exit 76).

The guard is constructed unconditionally — with no supervisor attached the
heartbeat is just a cheap status file and the signal handlers upgrade bare
``kill``/Ctrl-C into a clean resumable exit.
"""

from __future__ import annotations

import math
import signal
import threading
import time

from simclr_tpu.supervisor.faults import FaultPlan
from simclr_tpu.supervisor.heartbeat import (
    STATUS_PREEMPTED,
    heartbeat_path,
    write_heartbeat,
)
from simclr_tpu.utils.logging import get_logger, is_logging_host

logger = get_logger()

# Exit-code contract (docs/FAULT_TOLERANCE.md). 75 is sysexits.h EX_TEMPFAIL
# ("temporary failure, user is invited to retry"); 76 (EX_PROTOCOL) is
# repurposed as "poisoned: retrying cannot help, do NOT auto-restart".
EXIT_PREEMPTED = 75
EXIT_POISONED = 76


class PreemptedRun(Exception):
    """Raised at a step boundary after the preemption checkpoint landed;
    entry points catch it in ``main()`` and exit :data:`EXIT_PREEMPTED`."""

    def __init__(self, checkpoint: str):
        super().__init__(f"preempted; resumable checkpoint at {checkpoint}")
        self.checkpoint = checkpoint


class PoisonedRun(Exception):
    """Raised when the NaN-rollback budget is exhausted (or no verified
    checkpoint exists to roll back to); entry points exit
    :data:`EXIT_POISONED` and the supervisor will NOT restart."""


def resume_point(step: int, steps_per_epoch: int) -> tuple[int, int]:
    """Map a restored step counter to ``(start_epoch, skip_steps)``.

    A boundary checkpoint resumes at the next epoch with nothing to skip; a
    mid-epoch (preemption) checkpoint replays its epoch's deterministic
    batch order, skipping the ``skip_steps`` batches already consumed — the
    per-step RNG folds on the absolute step index, so the continuation is
    exactly the run that would have happened without the preemption.
    """
    steps_per_epoch = max(steps_per_epoch, 1)
    return step // steps_per_epoch + 1, step % steps_per_epoch


class RunGuard:
    """One per run; see module docstring. Usage::

        guard = RunGuard(save_dir, nan_retry_budget=2)
        guard.install_signals()
        try:
            ... guard.beat(step, epoch, loss) each step ...
            ... if guard.preempt_requested: save + raise PreemptedRun ...
            ... loss = guard.checked_loss(step, loss); rollback on non-finite ...
        finally:
            guard.restore_signals()
    """

    def __init__(
        self,
        save_dir: str,
        *,
        nan_retry_budget: int = 2,
        telemetry=None,
        events=None,
        process_index: int = 0,
    ):
        self.save_dir = save_dir
        self.process_index = int(process_index)
        # every process beats into its OWN file (heartbeat.json for process
        # 0, heartbeat.p<i>.json beyond) so a per-host supervisor can
        # attribute a wedge to the host that stopped beating first
        self.heartbeat_file = heartbeat_path(save_dir, self.process_index)
        self.faults = FaultPlan(save_dir, process_index=self.process_index)
        self.nan_retry_budget = int(nan_retry_budget)
        self.nan_rollbacks = 0
        # optional observability attachments (simclr_tpu/obs/): a Telemetry
        # registry whose snapshot rides on every beat, and an EventLog for
        # the structured run timeline — duck-typed, no import needed
        self.telemetry = telemetry
        self.events = events
        self._preempt = threading.Event()
        # time.monotonic_ns() at the first SIGTERM/SIGINT: where the host
        # loop's `preempt/notice` span begins (obs/trace.py)
        self.preempt_ns: int | None = None
        self._previous_handlers: dict[int, object] = {}
        self._beats = True

    def _telemetry_snapshot(self) -> dict | None:
        return self.telemetry.snapshot() if self.telemetry is not None else None

    # -- signals ------------------------------------------------------------
    @property
    def preempt_requested(self) -> bool:
        return self._preempt.is_set()

    def _on_signal(self, signum, frame) -> None:
        # handler does the minimum: the host loop owns the checkpoint save
        # (a save from inside a handler could re-enter orbax mid-write)
        if not self._preempt.is_set():
            self.preempt_ns = time.monotonic_ns()
            self._preempt.set()
            logger.info(
                "signal %d: checkpoint at the next step boundary, then exit %d",
                signum, EXIT_PREEMPTED,
            )

    def install_signals(self) -> None:
        """Claim SIGTERM/SIGINT; no-op off the main thread (in-process test
        drivers and notebook callers keep their own handlers)."""
        if threading.current_thread() is not threading.main_thread():
            return
        for sig in (signal.SIGTERM, signal.SIGINT):
            self._previous_handlers[sig] = signal.signal(sig, self._on_signal)

    def restore_signals(self) -> None:
        for sig, handler in self._previous_handlers.items():
            signal.signal(sig, handler)
        self._previous_handlers.clear()

    # -- heartbeat + fault hooks --------------------------------------------
    def beat(
        self,
        step: int,
        epoch: int,
        loss: float | None = None,
        status: str = "running",
    ) -> None:
        """Once per host-loop step, and once more at each epoch boundary
        with the epoch's loss. Fires the die/wedge faults first: they must
        be able to kill the beat itself."""
        self.faults.maybe_die(step)
        self.faults.maybe_wedge(step)
        if self._beats:
            write_heartbeat(
                self.heartbeat_file, step=step, epoch=epoch, loss=loss,
                status=status, telemetry=self._telemetry_snapshot(),
            )

    def beat_preempted(self, step: int, epoch: int) -> None:
        """Final beat after the preemption checkpoint landed (forensics: the
        supervisor and operators see WHY the file stopped changing)."""
        if self._beats:
            write_heartbeat(
                self.heartbeat_file, step=step, epoch=epoch,
                status=STATUS_PREEMPTED, telemetry=self._telemetry_snapshot(),
            )

    def after_save(self, epoch: int, checkpoint_path: str) -> None:
        """Post-save hook: the corrupt-latest fault lives here (process 0
        only — it mutates the shared checkpoint files)."""
        if is_logging_host():
            self.faults.maybe_corrupt(epoch, checkpoint_path)

    # -- non-finite-loss guard ---------------------------------------------
    def checked_loss(self, step: int, loss: float) -> float:
        """The epoch-boundary loss, through the NaN fault hook."""
        return self.faults.maybe_nan(step, loss)

    def record_rollback(self, loss: float, restored: str | None) -> None:
        """Book one non-finite-loss rollback against the budget; raises
        :class:`PoisonedRun` when the budget is exhausted or there was no
        verified checkpoint to roll back to (``restored=None``)."""
        self.nan_rollbacks += 1
        if self.telemetry is not None:
            self.telemetry.record_nan_rollback()
        if self.events is not None:
            self.events.emit(
                "nan_rollback", loss=loss, checkpoint=restored,
                retry=self.nan_rollbacks, budget=self.nan_retry_budget,
            )
        if restored is None:
            raise PoisonedRun(
                f"loss {loss!r} is non-finite and no verified checkpoint "
                f"exists to roll back to: the run is poisoned"
            )
        if self.nan_rollbacks > self.nan_retry_budget:
            raise PoisonedRun(
                f"loss {loss!r} is non-finite and the rollback budget "
                f"(supervisor.nan_retry_budget={self.nan_retry_budget}) is "
                f"exhausted: the run is poisoned"
            )
        logger.warning(
            "non-finite loss %r: rolled back to %s (retry %d/%d)",
            loss, restored, self.nan_rollbacks, self.nan_retry_budget,
        )


def nonfinite(value: float) -> bool:
    return not math.isfinite(value)


def preempt_checkpoint_name(step: int, steps_per_epoch: int, stem: str) -> str:
    """Checkpoint directory name for a preemption save at ``step``.

    At an exact epoch boundary this IS the regular boundary checkpoint name
    (idempotent with a scheduled save of the same state). Mid-epoch it
    carries epoch = completed-epochs plus a ``-preempt`` tag;
    ``list_checkpoints`` orders the tagged variant after the plain boundary
    checkpoint of the same epoch — it holds strictly more steps.
    """
    from simclr_tpu.utils.checkpoint import checkpoint_name

    epochs_done, into_epoch = step // max(steps_per_epoch, 1), step % max(
        steps_per_epoch, 1
    )
    name = checkpoint_name(epochs_done, stem)
    if into_epoch:
        name += "-preempt"
    return name
