"""Elastic multi-host supervisor: per-host children, remesh-on-loss, grow-back.

``python -m simclr_tpu.supervisor.elastic --nprocs N --devices-per-proc D --
<entrypoint> <overrides…>`` runs one supervised training child PER HOST and
keeps the RUN alive across single-host failures, where the plain runner
(``runner.py``) wraps one process group and a single lost host kills the
world. The shape borrowed from MPMD worker-group recovery (PAPERS.md): lose
a host, keep the run.

A live ``jax.distributed`` process group cannot be resized, so elasticity is
group *generations*:

  1. launch one child per active host under a fresh rendezvous env
     (``parallel.multihost.group_env`` — new coordinator port, rewritten
     ``JAX_NUM_PROCESSES``, ranks reassigned over the active hosts);
  2. watch every child's exit code AND its per-host heartbeat
     (``heartbeat.json`` / ``heartbeat.p<i>.json``);
  3. on a single-host crash/wedge/preemption: emit ``host_lost``, tear the
     whole group down (the survivors are blocked in collectives — nothing
     gentler than SIGKILL reaches them), put the lost host on a cooldown,
     and relaunch on the survivors' smaller mesh — the child resumes from
     the latest sha256-verified checkpoint via the existing cross-topology
     restore, with ``experiment.batches`` rescaled so the GLOBAL batch (and
     with it steps/epoch and the per-step RNG schedule) is preserved;
  4. when the lost host's cooldown expires, drain the running group with
     SIGTERM (every guard checkpoints at the next epoch boundary and exits
     75) and relaunch at full topology — the grow-back.

Coordinator-aware backoff: each host carries its own consecutive-failure
counter, and its re-admission cooldown doubles from
``supervisor.grow_back_cooldown_s`` up to ``supervisor.backoff_max_s`` — a
flapping host burns its own cooldown, not the group's restart budget.

Every generation transition lands in the shared ``events.jsonl``
(``host_lost`` / ``remesh`` / ``grow_back``), and the summary written to
``supervisor_summary.json`` carries ``remesh_count``, ``grow_back_count``,
the ``hosts_timeline`` (e.g. ``[2, 1, 2]``) and a per-host table — the
post-mortem names which host died and when (``obs/report.py`` renders the
"hosts: 2→1→2" line from the remesh events).

Exit-code contract: same as the runner (0 clean / 75 preempted / 76
poisoned / last child code when the budget runs out).
"""

from __future__ import annotations

import json
import math
import os
import signal
import socket
import subprocess
import sys
import threading
import time

from simclr_tpu.obs.events import EventLog
from simclr_tpu.parallel.multihost import group_env
from simclr_tpu.supervisor.guard import EXIT_POISONED, EXIT_PREEMPTED
from simclr_tpu.supervisor.heartbeat import heartbeat_path, read_heartbeat
from simclr_tpu.supervisor.runner import (
    ENTRYPOINTS,
    ENV_ATTEMPT,
    OUTCOME_CLEAN,
    OUTCOME_CRASHED,
    OUTCOME_POISONED,
    OUTCOME_PREEMPTED,
    SupervisorKnobs,
    _BeatTracker,
    _write_summary,
    backoff_delay,
)

# the host's slot index within the FULL topology, exported to each child for
# log forensics (distinct from JAX_PROCESS_ID, which is the rank within the
# current — possibly shrunken — generation)
ENV_HOST_SLOT = "SIMCLR_ELASTIC_HOST_SLOT"


def free_port() -> int:
    """A fresh coordinator port per generation: the old group's coordinator
    socket may linger in TIME_WAIT, and a rebind race would hang the new
    rendezvous until the fail-fast timeout."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def rescaled_per_device_batch(
    global_batch: int, devices_per_host: int, n_hosts: int
) -> int:
    """Per-device batch that preserves ``global_batch`` on ``n_hosts`` hosts.

    The invariant elasticity must not break: global batch fixed means
    steps/epoch is fixed, means the per-step RNG schedule (which folds on
    the absolute step index) is the same trajectory the full mesh was
    walking. A topology whose device count does not divide the global batch
    is rejected loudly — silently rounding would fork the schedule.
    """
    n_devices = devices_per_host * n_hosts
    if n_devices <= 0 or global_batch % n_devices:
        raise ValueError(
            f"global batch {global_batch} is not divisible by "
            f"{n_devices} devices ({n_hosts} hosts x {devices_per_host}); "
            "this topology cannot preserve the global batch — pick a global "
            "batch divisible by every surviving-device count you expect"
        )
    return global_batch // n_devices


class _Host:
    """One host slot of the full topology: availability + its own
    consecutive-failure ledger (the coordinator-aware backoff)."""

    def __init__(self, slot: int):
        self.slot = slot
        self.lost = False
        self.reallocated = False
        self.failures = 0
        self.cooldown_until = 0.0
        self.loss_reasons: list[str] = []

    def mark_lost(self, reason: str, knobs: SupervisorKnobs, now: float) -> None:
        self.lost = True
        self.failures += 1
        self.loss_reasons.append(reason)
        cooldown = max(
            getattr(knobs, "grow_back_cooldown_s", 60.0),
            backoff_delay(knobs, self.failures - 1),
        )
        self.cooldown_until = now + min(cooldown, knobs.backoff_max_s)

    def mark_reallocated(self) -> None:
        """Lend this host to the serve tier: lost as far as the training
        mesh is concerned, but on an INFINITE cooldown — not a failure (no
        backoff ledger entry), and never readmittable until ``release``."""
        self.lost = True
        self.reallocated = True
        self.cooldown_until = math.inf

    def release(self, now: float) -> None:
        """Hand the host back to training: immediately readmittable, so the
        existing grow-back trigger fires on the next supervisor poll."""
        if self.reallocated:
            self.reallocated = False
            self.cooldown_until = now

    def readmittable(self, now: float) -> bool:
        return self.lost and now >= self.cooldown_until


class ElasticSupervisor:
    """Coordinator-side group supervisor; see module docstring.

    ``cmd_prefix`` is the child command WITHOUT the per-generation overrides
    (``[sys.executable, "-m", module, *overrides]``); each generation appends
    ``experiment.batches=<rescaled>`` plus ``resume_args`` after the first.
    """

    def __init__(
        self,
        cmd_prefix: list[str],
        save_dir: str,
        knobs: SupervisorKnobs,
        *,
        nprocs: int,
        devices_per_proc: int,
        global_batch: int,
        grow_back_cooldown_s: float = 60.0,
        resume_args: tuple[str, ...] = ("experiment.resume=true",),
        force_cpu: bool = False,
        coord_timeout_s: float | None = None,
        env: dict | None = None,
        events: EventLog | None = None,
        fleet=None,
    ):
        if nprocs < 1:
            raise ValueError(f"nprocs must be >= 1, got {nprocs}")
        self.cmd_prefix = list(cmd_prefix)
        self.save_dir = save_dir
        self.knobs = knobs
        # stash the elastic-only knob on the shared knobs object so
        # _Host.mark_lost sees one policy source
        self.knobs.grow_back_cooldown_s = float(grow_back_cooldown_s)
        self.nprocs = int(nprocs)
        self.devices_per_proc = int(devices_per_proc)
        self.global_batch = int(global_batch)
        self.resume_args = tuple(resume_args)
        self.force_cpu = bool(force_cpu)
        self.coord_timeout_s = coord_timeout_s
        self.base_env = dict(os.environ if env is None else env)
        self.events = events if events is not None else EventLog(
            save_dir, enabled=False
        )
        # optional obs.fleet.FleetCollector: scrapes every generation's
        # per-host exporters for the run's lifetime; its final snapshot is
        # embedded into the summary. The caller owns its lifecycle.
        self.fleet = fleet
        self.hosts = [_Host(i) for i in range(self.nprocs)]
        self.remesh_count = 0
        self.grow_back_count = 0
        self.reallocate_count = 0
        self.hosts_timeline: list[int] = []
        self._stop: dict[str, int | None] = {"sig": None}
        self._realloc: dict[str, bool] = {"shrink": False}
        self._children: list[subprocess.Popen] = []
        # validate the FULL topology up front: a bad global batch must fail
        # before any child is spawned, not at the first remesh
        rescaled_per_device_batch(
            self.global_batch, self.devices_per_proc, self.nprocs
        )

    # -- group lifecycle ----------------------------------------------------
    def _spawn_group(
        self, active: list[_Host], generation: int, resume: bool
    ) -> list[subprocess.Popen]:
        per_device = rescaled_per_device_batch(
            self.global_batch, self.devices_per_proc, len(active)
        )
        coordinator = f"127.0.0.1:{free_port()}"
        cmd = list(self.cmd_prefix) + [f"experiment.batches={per_device}"]
        if resume:
            cmd += list(self.resume_args)
        children = []
        for rank, host in enumerate(active):
            child_env = group_env(
                self.base_env,
                coordinator=coordinator,
                num_processes=len(active),
                process_id=rank,
                devices_per_proc=(
                    self.devices_per_proc if self.force_cpu else None
                ),
                coord_timeout_s=self.coord_timeout_s,
            )
            child_env[ENV_ATTEMPT] = str(generation)
            child_env[ENV_HOST_SLOT] = str(host.slot)
            if len(active) > 1 and "OMP_NUM_THREADS" not in child_env:
                child_env["OMP_NUM_THREADS"] = "1"
            children.append(subprocess.Popen(cmd, env=child_env))
        return children

    def _kill_group(self, sig: int = signal.SIGKILL) -> None:
        for proc in self._children:
            if proc.poll() is None:
                try:
                    proc.send_signal(sig)
                except OSError:
                    pass
        for proc in self._children:
            if proc.poll() is None:
                proc.wait()

    def _signal_group(self, sig: int) -> None:
        for proc in self._children:
            if proc.poll() is None:
                try:
                    proc.send_signal(sig)
                except OSError:
                    pass

    # -- elastic reallocation (coscheduler) ---------------------------------
    @property
    def active_host_count(self) -> int:
        return sum(1 for h in self.hosts if not h.lost)

    @property
    def reallocated_hosts(self) -> list[int]:
        return [h.slot for h in self.hosts if h.reallocated]

    def request_shrink(self) -> bool:
        """Ask the main loop to drain ONE host out of the training mesh and
        lend its devices to the serve tier. Serviced at the next supervisor
        poll: the group drains via SIGTERM (guards checkpoint at the epoch
        boundary and exit 75) and relaunches on the survivors through the
        ordinary remesh path. Returns False — request dropped — when the
        mesh is already at one host (a run always trains). Thread-safe:
        called from the coscheduler's pressure-policy thread.
        """
        if self.active_host_count <= 1:
            return False
        self._realloc["shrink"] = True
        return True

    def release_reallocation(self) -> int:
        """Hand every lent host back to training. The hosts become
        readmittable immediately, so the existing grow-back trigger drains
        the running group and remeshes back up at its next poll. Returns
        the number of hosts released."""
        now = time.monotonic()
        released = [h for h in self.hosts if h.reallocated]
        for h in released:
            h.release(now)
        if released:
            self.events.emit(
                "reallocate", direction="release",
                hosts=[h.slot for h in released],
            )
        return len(released)

    def _on_stop(self, signum, frame) -> None:
        escalate = self._stop["sig"] is not None
        self._stop["sig"] = signum
        # first request drains the group (guards checkpoint and exit 75);
        # repeats escalate to SIGKILL, same as the plain runner
        self._signal_group(signal.SIGKILL if escalate else signum)

    # -- wedge attribution --------------------------------------------------
    @staticmethod
    def _stalest_rank(trackers: dict[int, _BeatTracker]) -> int:
        """The rank whose beat went stale FIRST — the wedged host. The wedge
        fault fires before the beat write, so the culprit's last beat is one
        step older than its peers' (they beat once more, then block in the
        next collective). A rank with no beat at all is stalest of all."""
        def key(rank: int):
            tracker = trackers[rank]
            return (
                tracker.last_change is None,
                -(tracker.last_change or 0.0),
            )
        return max(trackers, key=key)

    # -- main loop ----------------------------------------------------------
    def run(self) -> dict:
        os.makedirs(self.save_dir, exist_ok=True)
        t0 = time.monotonic()
        poll_s = min(0.5, max(0.05, self.knobs.heartbeat_min_timeout_s / 4.0))
        generation = 0
        restarts = {"host_lost": 0, "grow_back": 0}
        last_rc: int | None = None

        previous_handlers = {}
        if threading.current_thread() is threading.main_thread():
            for sig in (signal.SIGTERM, signal.SIGINT):
                previous_handlers[sig] = signal.signal(sig, self._on_stop)

        def summary(outcome: str, exit_code: int, error: str | None = None):
            result = {
                "outcome": outcome,
                "exit": exit_code,
                "attempts": generation,
                "resumed": max(generation - 1, 0),
                "remesh_count": self.remesh_count,
                "grow_back_count": self.grow_back_count,
                "reallocate_count": self.reallocate_count,
                "hosts_timeline": list(self.hosts_timeline),
                "hosts": "→".join(str(n) for n in self.hosts_timeline),
                "host_table": {
                    str(h.slot): {
                        "losses": h.failures,
                        "reasons": list(h.loss_reasons),
                        "lost": h.lost,
                        "reallocated": h.reallocated,
                    }
                    for h in self.hosts
                },
                "restarts": dict(restarts),
                "final_child_exit": last_rc,
                "global_batch": self.global_batch,
                "save_dir": self.save_dir,
                "wall_time_s": round(time.monotonic() - t0, 3),
            }
            if error:
                result["error"] = error
            if self.fleet is not None:
                result["fleet"] = self.fleet.snapshot()
            self.events.emit(
                "outcome", outcome=outcome, exit=exit_code,
                attempt=generation, remesh_count=self.remesh_count,
                grow_back_count=self.grow_back_count,
            )
            _write_summary(self.save_dir, result)
            return result

        try:
            while True:
                now = time.monotonic()
                active = [h for h in self.hosts if not h.lost]
                if not active:
                    # every host is cooling down: wait for the earliest
                    # re-admission (interruptible by a stop request)
                    wake = min(h.cooldown_until for h in self.hosts)
                    while time.monotonic() < wake:
                        if self._stop["sig"] is not None:
                            return summary(OUTCOME_PREEMPTED, EXIT_PREEMPTED)
                        time.sleep(poll_s)
                    now = time.monotonic()
                for host in self.hosts:
                    if host.readmittable(now):
                        host.lost = False
                active = [h for h in self.hosts if not h.lost]

                generation += 1
                try:
                    self._children = self._spawn_group(
                        active, generation, resume=generation > 1
                    )
                except ValueError as exc:
                    # an indivisible surviving topology: reject loudly
                    return summary(OUTCOME_CRASHED, 1, error=str(exc))
                self.hosts_timeline.append(len(active))
                if generation > 1:
                    self.remesh_count += 1
                    self.events.emit(
                        "remesh",
                        attempt=generation,
                        hosts_before=self.hosts_timeline[-2],
                        hosts_after=len(active),
                        per_device_batch=rescaled_per_device_batch(
                            self.global_batch, self.devices_per_proc,
                            len(active),
                        ),
                        global_batch=self.global_batch,
                    )

                trackers = {
                    rank: _BeatTracker(
                        self.knobs,
                        read_heartbeat(heartbeat_path(self.save_dir, rank)),
                        time.monotonic(),
                    )
                    for rank in range(len(active))
                }
                drain_for_grow_back = False
                drain_for_realloc = False
                drain_deadline = None
                lost: tuple[_Host, str, int | None] | None = None

                while True:
                    exits = {
                        rank: proc.poll()
                        for rank, proc in enumerate(self._children)
                    }
                    if all(rc is not None for rc in exits.values()):
                        break
                    now = time.monotonic()
                    for rank, tracker in trackers.items():
                        tracker.observe(
                            read_heartbeat(
                                heartbeat_path(self.save_dir, rank)
                            ),
                            now,
                        )
                    if self._stop["sig"] is not None:
                        self._signal_group(signal.SIGTERM)
                        for proc in self._children:
                            proc.wait()
                        return summary(OUTCOME_PREEMPTED, EXIT_PREEMPTED)

                    finished = {
                        r: rc for r, rc in exits.items() if rc is not None
                    }
                    if finished and not (drain_for_grow_back or drain_for_realloc):
                        rank, rc = next(iter(finished.items()))
                        if len(finished) > 1:
                            # the faulted host's peers crash moments later
                            # (their collectives error out against the dead
                            # peer); the culprit is the one whose heartbeat
                            # went stale FIRST, same rule as the wedge path
                            rank = self._stalest_rank(
                                {r: trackers[r] for r in finished}
                            )
                            rc = finished[rank]
                        for r, code in finished.items():
                            if code == EXIT_POISONED:
                                rank, rc = r, code
                                break
                        last_rc = rc
                        if rc == EXIT_POISONED:
                            self._kill_group()
                            self.events.emit(
                                "child_exit", attempt=generation, exit=rc,
                                rank=rank, host=active[rank].slot,
                            )
                            return summary(OUTCOME_POISONED, EXIT_POISONED)
                        # a single child stopped while peers run: host loss
                        # (crash, injected die, or an externally preempted
                        # host exiting 75 on its own). The peers are blocked
                        # in a collective that will never complete.
                        reason = (
                            "preempted" if rc == EXIT_PREEMPTED else "crashed"
                        )
                        lost = (active[rank], reason, rc)
                        break
                    if (drain_for_grow_back or drain_for_realloc) and now > (
                        drain_deadline or 0
                    ):
                        # drain overran the deadline (a child stuck before
                        # its next boundary): force it — the relaunch resumes
                        # from the previous checkpoint either way
                        self._kill_group()
                        break
                    if not (drain_for_grow_back or drain_for_realloc):
                        hung = [
                            rank
                            for rank, tracker in trackers.items()
                            if tracker.timed_out(now)
                        ]
                        if hung:
                            culprit = self._stalest_rank(trackers)
                            lost = (active[culprit], "wedged", None)
                            break
                    if (
                        not (drain_for_grow_back or drain_for_realloc)
                        and self._realloc["shrink"]
                        and any(
                            t.last_change is not None
                            for t in trackers.values()
                        )
                    ):
                        # coscheduler asked for a host: drain the group at
                        # the next epoch boundary and relaunch one smaller.
                        # Deliberately the same remesh-on-loss machinery a
                        # real host loss takes — except the victim is parked
                        # (infinite cooldown), not penalized (no failure
                        # ledger entry, no restart-budget burn).
                        self._realloc["shrink"] = False
                        if len(active) > 1:
                            victim = active[-1]
                            victim.mark_reallocated()
                            drain_for_realloc = True
                            drain_deadline = now + self.knobs.startup_grace_s
                            self.reallocate_count += 1
                            self.events.emit(
                                "reallocate", direction="shrink",
                                attempt=generation, host=victim.slot,
                                hosts_before=len(active),
                                hosts_after=len(active) - 1,
                            )
                            self._signal_group(signal.SIGTERM)
                    if (
                        not (drain_for_grow_back or drain_for_realloc)
                        and len(active) < self.nprocs
                        and any(
                            h.readmittable(now) for h in self.hosts if h.lost
                        )
                        and any(
                            t.last_change is not None
                            for t in trackers.values()
                        )
                    ):
                        # a lost host is back and this generation has made
                        # progress: drain at the next epoch boundary and
                        # remesh back up
                        drain_for_grow_back = True
                        drain_deadline = now + self.knobs.startup_grace_s
                        self.grow_back_count += 1
                        restarts["grow_back"] += 1
                        returning = [
                            h.slot for h in self.hosts if h.readmittable(now)
                        ]
                        self.events.emit(
                            "grow_back", attempt=generation,
                            hosts=returning,
                            hosts_before=len(active),
                            hosts_after=len(active) + len(returning),
                        )
                        self._signal_group(signal.SIGTERM)
                    time.sleep(poll_s)

                if lost is not None:
                    host, reason, rc = lost
                    # the rest of the group is unrecoverable (blocked in
                    # collectives / half a mesh): tear it all down
                    self._kill_group()
                    now = time.monotonic()
                    host.mark_lost(reason, self.knobs, now)
                    self.events.emit(
                        "host_lost", attempt=generation, host=host.slot,
                        reason=reason, exit=rc,
                        cooldown_s=round(host.cooldown_until - now, 3),
                        failures=host.failures,
                    )
                    restarts["host_lost"] += 1
                    total_losses = sum(restarts.values()) - restarts["grow_back"]
                    if total_losses > self.knobs.max_restarts:
                        return summary(
                            OUTCOME_CRASHED,
                            rc if rc and 0 < rc < 256 else 1,
                            error=(
                                f"host-loss budget exhausted "
                                f"({self.knobs.max_restarts} restarts)"
                            ),
                        )
                    # brief group backoff before relaunching the survivors;
                    # the per-host cooldown (not this) is what throttles a
                    # flapping host
                    deadline = time.monotonic() + backoff_delay(
                        self.knobs, total_losses - 1
                    )
                    while time.monotonic() < deadline:
                        if self._stop["sig"] is not None:
                            return summary(OUTCOME_PREEMPTED, EXIT_PREEMPTED)
                        time.sleep(poll_s)
                    continue

                exits = [proc.returncode for proc in self._children]
                last_rc = exits[0] if exits else None
                if all(rc == 0 for rc in exits):
                    return summary(OUTCOME_CLEAN, 0)
                if drain_for_grow_back or drain_for_realloc:
                    # drained (75s, or forced): relaunch at the grown (or
                    # reallocation-shrunken) topology next iteration
                    continue
                if all(rc == EXIT_PREEMPTED for rc in exits):
                    # the whole group drained without a stop from us or a
                    # grow-back: an external whole-slice preemption
                    return summary(OUTCOME_PREEMPTED, EXIT_PREEMPTED)
                if any(rc == EXIT_POISONED for rc in exits):
                    return summary(OUTCOME_POISONED, EXIT_POISONED)
                # simultaneous multi-child crash: burn one restart and rerun
                # the same topology (no single host to blame)
                restarts["host_lost"] += 1
                total_losses = sum(restarts.values()) - restarts["grow_back"]
                bad = next(rc for rc in exits if rc != 0)
                last_rc = bad
                self.events.emit(
                    "child_exit", attempt=generation, exit=bad, group=True,
                )
                if total_losses > self.knobs.max_restarts:
                    return summary(
                        OUTCOME_CRASHED, bad if 0 < bad < 256 else 1
                    )
                deadline = time.monotonic() + backoff_delay(
                    self.knobs, total_losses - 1
                )
                while time.monotonic() < deadline:
                    if self._stop["sig"] is not None:
                        return summary(OUTCOME_PREEMPTED, EXIT_PREEMPTED)
                    time.sleep(poll_s)
        finally:
            self._kill_group()
            for sig, handler in previous_handlers.items():
                signal.signal(sig, handler)


def main(argv: list[str] | None = None) -> int:
    """CLI: ``python -m simclr_tpu.supervisor.elastic --nprocs N
    --devices-per-proc D [--force-cpu] -- <entrypoint> <overrides…>``."""
    import argparse

    from simclr_tpu.config import (
        ConfigError,
        check_supervisor_conf,
        check_telemetry_conf,
        load_config,
        resolve_save_dir,
    )

    parser = argparse.ArgumentParser(
        prog="python -m simclr_tpu.supervisor.elastic",
        description="Per-host elastic supervisor: remesh-on-loss + grow-back.",
        epilog="On hardware a chip belongs to one process: run ONE process per host. All of this command's children start on this machine and nothing partitions its chips between them, so without --force-cpu they would each claim every chip; it has only ever run as a CPU dry-run.",
    )
    parser.add_argument(
        "--nprocs", type=int, required=True,
        help="hosts (JAX processes) in the full topology",
    )
    parser.add_argument(
        "--devices-per-proc", type=int, required=True,
        help="accelerator devices per host (batch-rescale math)",
    )
    parser.add_argument(
        "--force-cpu", action="store_true",
        help="force that many VIRTUAL CPU devices per child (dryrun harness)",
    )
    parser.add_argument(
        "--coord-timeout-s", type=float, default=None,
        help="rendezvous fail-fast deadline exported to every child",
    )
    parser.add_argument("rest", nargs=argparse.REMAINDER)
    args = parser.parse_args(sys.argv[1:] if argv is None else argv)

    rest = list(args.rest)
    if rest and rest[0] == "--":
        rest = rest[1:]
    if not rest or rest[0] not in ENTRYPOINTS:
        known = ", ".join(sorted(set(ENTRYPOINTS)))
        print(
            "usage: python -m simclr_tpu.supervisor.elastic --nprocs N "
            "--devices-per-proc D -- <entrypoint> [overrides...]\n"
            f"  entrypoint: one of {known}",
            file=sys.stderr,
        )
        return 2
    module, config_name = ENTRYPOINTS[rest[0]]
    overrides = rest[1:]

    try:
        cfg = load_config(config_name, overrides=overrides)
        check_supervisor_conf(cfg)
        check_telemetry_conf(cfg)
        knobs = SupervisorKnobs.from_config(cfg)
        grow_back_cooldown_s = float(
            cfg.select("supervisor.grow_back_cooldown_s", 60.0)
        )
        save_dir = resolve_save_dir(cfg)
        per_device = int(cfg.select("experiment.batches", 0) or 0)
        if per_device <= 0:
            raise ConfigError(
                f"experiment.batches must be a positive per-device batch, "
                f"got {per_device!r}"
            )
    except ConfigError as e:
        print(f"elastic supervisor: {e}", file=sys.stderr)
        return 2
    if not cfg.select("experiment.save_dir"):
        overrides = overrides + [f"experiment.save_dir={save_dir}"]

    # experiment.batches carries PER-DEVICE semantics; the configured value
    # defines the run's invariant GLOBAL batch at full topology, and each
    # generation gets a rescaled per-device override appended (trailing
    # overrides win)
    global_batch = per_device * args.devices_per_proc * args.nprocs
    # fleet plane (telemetry.fleet=true): scrape every generation's per-host
    # exporters and serve the merged simclr_fleet_* endpoint for the run
    from simclr_tpu.obs.fleet import maybe_start_fleet

    fleet = maybe_start_fleet(cfg, save_dir, nprocs=args.nprocs)
    supervisor = ElasticSupervisor(
        [sys.executable, "-m", module, *overrides],
        save_dir,
        knobs,
        nprocs=args.nprocs,
        devices_per_proc=args.devices_per_proc,
        global_batch=global_batch,
        grow_back_cooldown_s=grow_back_cooldown_s,
        force_cpu=args.force_cpu,
        coord_timeout_s=args.coord_timeout_s,
        events=EventLog(
            save_dir, enabled=bool(cfg.select("telemetry.events", True))
        ),
        fleet=fleet,
    )
    try:
        result = supervisor.run()
    finally:
        if fleet is not None:
            fleet.close()
    print(json.dumps(result), flush=True)
    return int(result["exit"])


if __name__ == "__main__":
    sys.exit(main())
