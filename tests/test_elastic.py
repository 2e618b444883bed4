"""Elastic multi-host suite (simclr_tpu/supervisor/elastic.py + topology.py,
docs/FAULT_TOLERANCE.md §"Elastic remeshing").

Two tiers, both under the ``supervisor`` marker:

* fast policy tests — process-scoped fault plumbing, per-host heartbeat
  paths, capped backoff, batch-rescale math, the topology sidecar's
  accept/reject rules, wedge attribution, and the ElasticSupervisor itself
  driven by stdlib-only fake host children through the full lifecycle
  (host loss -> remesh down -> grow back -> clean). Part of the not-slow
  core set.
* slow e2e proofs (also marked ``slow``) — real training subprocesses:
  a checkpoint written on the 8-device mesh resumes onto a 4-device mesh
  with the per-device batch rescaled and the loss trajectory matching an
  uninterrupted same-seed run; a global-batch fork and a mid-epoch
  cross-topology resume are rejected loudly; replicated AND sharded arrays
  land with the CURRENT mesh's residency after a cross-topology restore.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time
import types

import pytest

import simclr_tpu

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(simclr_tpu.__file__)))

from simclr_tpu.obs.events import EventLog
from simclr_tpu.supervisor.elastic import (
    ENV_HOST_SLOT,
    ElasticSupervisor,
    _Host,
    free_port,
    rescaled_per_device_batch,
)
from simclr_tpu.supervisor.faults import (
    ENV_DIE,
    ENV_DIE_PROCESS,
    ENV_WEDGE,
    ENV_WEDGE_PROCESS,
    FAULT_CRASH_CODE,
    FaultPlan,
    _env_process_step,
)
from simclr_tpu.supervisor.guard import EXIT_POISONED, EXIT_PREEMPTED
from simclr_tpu.supervisor.heartbeat import (
    heartbeat_path,
    read_heartbeat,
    write_heartbeat,
)
from simclr_tpu.supervisor.runner import (
    ENV_ATTEMPT,
    SUMMARY_NAME,
    SupervisorKnobs,
    backoff_delay,
)
from simclr_tpu.supervisor.topology import (
    check_resume_topology,
    read_topology,
    write_topology,
)

pytestmark = pytest.mark.supervisor

# fast-failing policy for fake-host tests: near-zero backoff, sub-second
# re-admission, generous wedge floor so a 0.05s beat cadence never trips it
EFAST = dict(
    max_restarts=5,
    backoff_base_s=0.05,
    backoff_max_s=2.0,
    heartbeat_timeout_factor=10.0,
    heartbeat_min_timeout_s=2.0,
    startup_grace_s=30.0,
)


# ---------------------------------------------------------------------------
# process-scoped fault injection (SIMCLR_FAULT_{DIE,WEDGE}_PROCESS=P:K)
# ---------------------------------------------------------------------------


class TestProcessScopedFaults:
    def test_spec_parses_and_malformed_raises(self, monkeypatch):
        monkeypatch.setenv(ENV_DIE_PROCESS, "1:4")
        assert _env_process_step(ENV_DIE_PROCESS) == (1, 4)
        monkeypatch.delenv(ENV_DIE_PROCESS)
        assert _env_process_step(ENV_DIE_PROCESS) is None
        # a typo'd fault that silently never fires would green-light the
        # e2e it was meant to drive — malformed must raise, not no-op
        monkeypatch.setenv(ENV_DIE_PROCESS, "4")
        with pytest.raises(ValueError, match="PROCESS:STEP"):
            _env_process_step(ENV_DIE_PROCESS)

    def test_fault_arms_only_on_the_named_process(self, tmp_path, monkeypatch):
        monkeypatch.setenv(ENV_DIE_PROCESS, "1:4")
        monkeypatch.setenv(ENV_WEDGE_PROCESS, "0:9")
        culprit = FaultPlan(str(tmp_path), process_index=1)
        assert culprit.die_at_step == 4 and culprit.wedge_at_step is None
        peer = FaultPlan(str(tmp_path), process_index=0)
        assert peer.die_at_step is None and peer.wedge_at_step == 9

    def test_scoped_fault_folds_into_global_trigger(self, tmp_path, monkeypatch):
        # earliest wins: the scoped fault shares the global fault's trigger,
        # markers, and FAULT_CRASH_CODE contract
        monkeypatch.setenv(ENV_DIE, "10")
        monkeypatch.setenv(ENV_DIE_PROCESS, "0:4")
        assert FaultPlan(str(tmp_path), process_index=0).die_at_step == 4
        assert FaultPlan(str(tmp_path), process_index=2).die_at_step == 10

    def test_scoped_die_fires_once_per_run_dir(self, tmp_path, monkeypatch):
        """The marker lives in the SHARED save_dir: a host that returns
        after a remesh re-executes the same env but must not re-fire."""
        monkeypatch.setenv(ENV_DIE_PROCESS, "1:2")
        script = tmp_path / "child.py"
        script.write_text(textwrap.dedent(
            """
            import sys
            from simclr_tpu.supervisor.faults import FaultPlan
            plan = FaultPlan(sys.argv[1], process_index=int(sys.argv[2]))
            for step in range(1, 5):
                plan.maybe_die(step)
            sys.exit(0)
            """
        ))

        def run(process_index):
            return subprocess.run(
                [sys.executable, str(script), str(tmp_path), str(process_index)],
                env=dict(os.environ, PYTHONPATH=REPO_ROOT), cwd=REPO_ROOT,
                timeout=120,
            ).returncode

        assert run(0) == 0  # wrong process: never arms
        assert run(1) == FAULT_CRASH_CODE
        assert os.path.exists(tmp_path / ".fault_fired.die")
        assert run(1) == 0  # the returned host does not die again


# ---------------------------------------------------------------------------
# per-host heartbeats
# ---------------------------------------------------------------------------


class TestPerHostHeartbeat:
    def test_process_zero_keeps_the_historical_name(self, tmp_path):
        d = str(tmp_path)
        assert heartbeat_path(d) == os.path.join(d, "heartbeat.json")
        assert heartbeat_path(d, 0) == os.path.join(d, "heartbeat.json")
        assert heartbeat_path(d, 2) == os.path.join(d, "heartbeat.p2.json")

    def test_per_host_files_do_not_collide(self, tmp_path):
        for rank in range(3):
            write_heartbeat(heartbeat_path(str(tmp_path), rank),
                            step=10 + rank, epoch=1)
        for rank in range(3):
            beat = read_heartbeat(heartbeat_path(str(tmp_path), rank))
            assert beat["step"] == 10 + rank


# ---------------------------------------------------------------------------
# capped backoff + config validation (supervisor.backoff_max_s knob)
# ---------------------------------------------------------------------------


class TestBackoffCap:
    def test_delay_doubles_then_caps(self):
        knobs = SupervisorKnobs(backoff_base_s=1.0, backoff_max_s=5.0)
        assert [backoff_delay(knobs, n) for n in range(5)] == [
            1.0, 2.0, 4.0, 5.0, 5.0]

    def test_cap_defaults_from_yaml(self):
        from simclr_tpu.config import load_config

        for name in ("config", "supervised_config"):
            cfg = load_config(name)
            assert float(cfg.select("supervisor.backoff_max_s")) == 300.0
            assert float(cfg.select("supervisor.grow_back_cooldown_s")) == 60.0

    @pytest.mark.parametrize("override, match", [
        ("supervisor.backoff_max_s=-1", "backoff_max_s"),
        ("supervisor.backoff_max_s=90000", "backoff_max_s"),
        ("supervisor.backoff_max_s=2", "backoff_base_s"),  # cap < base (5.0)
        ("supervisor.grow_back_cooldown_s=-3", "grow_back_cooldown_s"),
        ("supervisor.grow_back_cooldown_s=90000", "grow_back_cooldown_s"),
    ])
    def test_bad_knobs_rejected_at_load(self, override, match):
        from simclr_tpu.config import (
            ConfigError,
            check_supervisor_conf,
            load_config,
        )

        with pytest.raises(ConfigError, match=match):
            check_supervisor_conf(load_config("config", overrides=[override]))


# ---------------------------------------------------------------------------
# batch-rescale math + the topology sidecar
# ---------------------------------------------------------------------------


class TestRescaleMath:
    def test_global_batch_preserved_across_topologies(self):
        assert rescaled_per_device_batch(64, 4, 2) == 8
        assert rescaled_per_device_batch(64, 4, 1) == 16
        assert rescaled_per_device_batch(64, 8, 1) == 8

    def test_indivisible_topology_rejected_loudly(self):
        with pytest.raises(ValueError, match="not divisible"):
            rescaled_per_device_batch(12, 4, 2)  # 8 devices, global 12


class TestTopologySidecar:
    def test_roundtrip_and_missing_reads_none(self, tmp_path):
        d = str(tmp_path)
        assert read_topology(d) is None
        write_topology(d, n_devices=8, n_processes=2, global_batch=32)
        assert read_topology(d) == {
            "n_devices": 8, "n_processes": 2, "global_batch": 32}

    def test_garbage_sidecar_reads_none(self, tmp_path):
        (tmp_path / "topology.json").write_text('{"n_devices": ')
        assert read_topology(str(tmp_path)) is None
        (tmp_path / "topology.json").write_text("[1, 2]")
        assert read_topology(str(tmp_path)) is None

    def test_unchanged_topology_and_no_prior_accept_silently(self):
        prior = {"n_devices": 8, "n_processes": 2, "global_batch": 32}
        assert check_resume_topology(
            prior, n_devices=8, n_processes=2, global_batch=32, skip_steps=3,
        ) is None
        assert check_resume_topology(
            None, n_devices=4, n_processes=1, global_batch=32, skip_steps=0,
        ) is None

    def test_boundary_cross_topology_accepted_with_change_record(self):
        prior = {"n_devices": 8, "n_processes": 2, "global_batch": 32}
        change = check_resume_topology(
            prior, n_devices=4, n_processes=1, global_batch=32, skip_steps=0,
        )
        assert change == {
            "devices_before": 8, "devices_after": 4,
            "hosts_before": 2, "hosts_after": 1,
            "per_device_batch": 8,
        }

    def test_global_batch_fork_rejected(self):
        prior = {"n_devices": 8, "n_processes": 2, "global_batch": 32}
        with pytest.raises(ValueError, match="GLOBAL batch"):
            check_resume_topology(
                prior, n_devices=4, n_processes=1, global_batch=16,
                skip_steps=0,
            )

    def test_mid_epoch_cross_topology_rejected(self):
        prior = {"n_devices": 8, "n_processes": 2, "global_batch": 32}
        with pytest.raises(ValueError, match="epoch boundaries"):
            check_resume_topology(
                prior, n_devices=4, n_processes=1, global_batch=32,
                skip_steps=1,
            )


# ---------------------------------------------------------------------------
# elastic supervisor policy (fake stdlib-only host children)
# ---------------------------------------------------------------------------


def _tracker(last_change):
    return types.SimpleNamespace(last_change=last_change)


class TestWedgeAttribution:
    def test_stalest_beat_names_the_culprit(self):
        # the wedge fires BEFORE the beat write: the culprit's last beat is
        # older than its peers', which beat once more then block
        trackers = {0: _tracker(10.0), 1: _tracker(7.0), 2: _tracker(10.5)}
        assert ElasticSupervisor._stalest_rank(trackers) == 1

    def test_never_beaten_rank_is_stalest_of_all(self):
        trackers = {0: _tracker(3.0), 1: _tracker(None)}
        assert ElasticSupervisor._stalest_rank(trackers) == 1


class TestHostLedger:
    def test_cooldown_doubles_per_consecutive_failure_and_caps(self):
        knobs = SupervisorKnobs(**{
            **EFAST, "backoff_base_s": 1.0, "backoff_max_s": 3.0})
        knobs.grow_back_cooldown_s = 0.5
        host = _Host(1)
        # failure 1: max(grow_back_cooldown, base * 2^0) = 1.0
        host.mark_lost("crashed", knobs, now=100.0)
        assert host.cooldown_until == pytest.approx(101.0)
        # failure 2 doubles, failure 3 hits the backoff_max_s ceiling
        host.mark_lost("crashed", knobs, now=100.0)
        assert host.cooldown_until == pytest.approx(102.0)
        host.mark_lost("wedged", knobs, now=100.0)
        assert host.cooldown_until == pytest.approx(103.0)
        assert host.failures == 3
        assert host.loss_reasons == ["crashed", "crashed", "wedged"]
        assert not host.readmittable(102.9)
        assert host.readmittable(103.0)


# one fake child per host: beats into its OWN per-rank heartbeat file and
# logs its argv + rendezvous env per (generation, rank) for assertions
ELASTIC_CHILD_HEADER = textwrap.dedent(
    f"""
    import json, os, signal, sys, time

    d = sys.argv[1]
    rank = int(os.environ.get("JAX_PROCESS_ID", "0"))
    nprocs = int(os.environ.get("JAX_NUM_PROCESSES", "1"))
    attempt = int(os.environ.get({ENV_ATTEMPT!r}, "0"))
    slot = os.environ.get({ENV_HOST_SLOT!r}, "")
    name = "heartbeat.json" if rank == 0 else "heartbeat.p%d.json" % rank
    hb = os.path.join(d, name)

    def beat(step):
        tmp = hb + ".tmp"
        with open(tmp, "w") as f:
            json.dump({{"step": step, "epoch": 1, "time": time.time(),
                       "loss": None, "pid": os.getpid(),
                       "status": "running"}}, f)
        os.replace(tmp, hb)

    with open(os.path.join(d, "argv.g%d.r%d" % (attempt, rank)), "w") as f:
        json.dump({{"argv": sys.argv[2:], "nprocs": nprocs, "slot": slot,
                   "coord": os.environ.get("JAX_COORDINATOR_ADDRESS")}}, f)
    """
)


def _elastic_child(tmp_path, body: str) -> list[str]:
    script = tmp_path / "host_child.py"
    script.write_text(ELASTIC_CHILD_HEADER + textwrap.dedent(body))
    run_dir = tmp_path / "run"
    run_dir.mkdir(exist_ok=True)
    return [sys.executable, str(script), str(run_dir)], str(run_dir)


def _events(run_dir, kind=None):
    path = os.path.join(run_dir, "events.jsonl")
    if not os.path.exists(path):
        return []
    rows = [json.loads(l) for l in open(path) if l.strip()]
    return [r for r in rows if kind is None or r["event"] == kind]


def _gen_argv(run_dir, generation, rank):
    with open(os.path.join(run_dir, f"argv.g{generation}.r{rank}")) as f:
        return json.load(f)


class TestElasticSupervisor:
    def _supervisor(self, cmd, run_dir, knobs=None, **kwargs):
        knobs = knobs or SupervisorKnobs(**EFAST)
        kwargs.setdefault("nprocs", 2)
        kwargs.setdefault("devices_per_proc", 4)
        kwargs.setdefault("global_batch", 64)
        kwargs.setdefault("grow_back_cooldown_s", 1.0)
        kwargs.setdefault("events", EventLog(run_dir, enabled=True, attempt=0))
        return ElasticSupervisor(cmd, run_dir, knobs, **kwargs)

    def test_full_lifecycle_loss_remesh_grow_back_clean(self, tmp_path):
        """The tentpole's policy proof on fake hosts: rank 1 dies in
        generation 1 -> remesh to ONE host with the per-device batch doubled
        (global preserved) -> when the lost host's cooldown expires the
        running group is drained -> generation 3 runs the full topology
        again -> clean, with the whole story in events + summary."""
        cmd, run_dir = _elastic_child(tmp_path, f"""
            if attempt == 1:
                if rank == 1:
                    beat(1); beat(2)
                    time.sleep(0.2)
                    os._exit({FAULT_CRASH_CODE})
                beat(1)
                for i in range(2, 600):
                    beat(i); time.sleep(0.05)
                os._exit(1)  # gen-1 survivor must be torn down, not finish
            elif attempt == 2:
                signal.signal(
                    signal.SIGTERM, lambda s, f: os._exit({EXIT_PREEMPTED}))
                for i in range(1, 600):
                    beat(i); time.sleep(0.05)
                os._exit(1)  # must be drained by the grow-back, not finish
            else:
                beat(1); beat(2)
                sys.exit(0)
            """)
        summary = self._supervisor(cmd, run_dir).run()

        assert summary["outcome"] == "clean" and summary["exit"] == 0
        assert summary["remesh_count"] == 2
        assert summary["grow_back_count"] == 1
        assert summary["hosts_timeline"] == [2, 1, 2]
        assert summary["hosts"] == "2→1→2"
        assert summary["host_table"]["1"] == {
            "losses": 1, "reasons": ["crashed"], "lost": False,
            "reallocated": False}
        assert summary["host_table"]["0"]["losses"] == 0
        # grow-backs do not burn the restart budget
        assert summary["restarts"] == {"host_lost": 1, "grow_back": 1}
        on_disk = json.load(open(os.path.join(run_dir, SUMMARY_NAME)))
        assert on_disk == summary

        # the events timeline tells the whole story
        (loss,) = _events(run_dir, "host_lost")
        assert loss["host"] == 1 and loss["reason"] == "crashed"
        assert loss["exit"] == FAULT_CRASH_CODE
        remeshes = _events(run_dir, "remesh")
        assert [(r["hosts_before"], r["hosts_after"]) for r in remeshes] == [
            (2, 1), (1, 2)]
        assert remeshes[0]["per_device_batch"] == 16
        assert remeshes[1]["per_device_batch"] == 8
        assert remeshes[0]["global_batch"] == 64
        (grow,) = _events(run_dir, "grow_back")
        assert grow["hosts"] == [1]
        assert (grow["hosts_before"], grow["hosts_after"]) == (1, 2)

        # per-generation children: rescaled batch override + resume args
        g1 = _gen_argv(run_dir, 1, 0)
        assert "experiment.batches=8" in g1["argv"]
        assert "experiment.resume=true" not in g1["argv"]
        g2 = _gen_argv(run_dir, 2, 0)
        assert "experiment.batches=16" in g2["argv"]
        assert "experiment.resume=true" in g2["argv"]
        assert g2["nprocs"] == 1 and g2["slot"] == "0"
        g3r1 = _gen_argv(run_dir, 3, 1)
        assert "experiment.batches=8" in g3r1["argv"]
        assert g3r1["nprocs"] == 2 and g3r1["slot"] == "1"
        # a fresh rendezvous per generation: no stale-coordinator rebind race
        coords = {g1["coord"], g2["coord"], g3r1["coord"]}
        assert len(coords) == 3 and None not in coords

    def test_wedged_host_is_attributed_by_stalest_beat(self, tmp_path):
        """Rank 1 stops beating (wedge fires before the beat write); rank 0
        beats on. The supervisor must blame rank 1, not the live peer, then
        remesh down and finish on the survivor."""
        cmd, run_dir = _elastic_child(tmp_path, """
            if attempt == 1 and rank == 1:
                beat(1)
                time.sleep(600)  # wedged: holds its slot, never beats again
            elif attempt == 1:
                for i in range(1, 600):
                    beat(i); time.sleep(0.05)
                os._exit(1)
            else:
                beat(1)
                sys.exit(0)
            """)
        knobs = SupervisorKnobs(**{
            **EFAST, "heartbeat_min_timeout_s": 0.4,
            "heartbeat_timeout_factor": 4.0})
        summary = self._supervisor(
            cmd, run_dir, knobs=knobs, grow_back_cooldown_s=30.0,
        ).run()
        assert summary["outcome"] == "clean"
        assert summary["hosts_timeline"] == [2, 1]
        (loss,) = _events(run_dir, "host_lost")
        assert loss["host"] == 1 and loss["reason"] == "wedged"
        assert summary["host_table"]["1"]["reasons"] == ["wedged"]

    def test_lone_preempted_host_remeshes_instead_of_killing_the_run(
        self, tmp_path
    ):
        """A single host exiting 75 on its own (externally preempted) is a
        host LOSS — the run continues on the survivors."""
        cmd, run_dir = _elastic_child(tmp_path, f"""
            if attempt == 1 and rank == 1:
                beat(1)
                os._exit({EXIT_PREEMPTED})
            elif attempt == 1:
                for i in range(1, 600):
                    beat(i); time.sleep(0.05)
                os._exit(1)
            else:
                beat(1)
                sys.exit(0)
            """)
        summary = self._supervisor(
            cmd, run_dir, grow_back_cooldown_s=30.0,
        ).run()
        assert summary["outcome"] == "clean"
        assert summary["hosts_timeline"] == [2, 1]
        (loss,) = _events(run_dir, "host_lost")
        assert loss["reason"] == "preempted" and loss["exit"] == EXIT_PREEMPTED

    def test_poisoned_child_is_terminal_without_remesh(self, tmp_path):
        cmd, run_dir = _elastic_child(tmp_path, f"""
            if rank == 1:
                beat(1)
                os._exit({EXIT_POISONED})
            beat(1)
            for i in range(2, 600):
                beat(i); time.sleep(0.05)
            """)
        summary = self._supervisor(cmd, run_dir).run()
        assert summary["outcome"] == "poisoned"
        assert summary["exit"] == EXIT_POISONED
        assert summary["remesh_count"] == 0
        assert not _events(run_dir, "host_lost")

    def test_host_loss_budget_exhaustion_reports_crash(self, tmp_path):
        cmd, run_dir = _elastic_child(tmp_path, """
            beat(1)
            if rank == 1:
                os._exit(7)
            for i in range(2, 600):
                beat(i); time.sleep(0.05)
            """)
        knobs = SupervisorKnobs(**{**EFAST, "max_restarts": 1})
        summary = self._supervisor(
            cmd, run_dir, knobs=knobs, grow_back_cooldown_s=0.0,
        ).run()
        assert summary["outcome"] == "crashed"
        assert "budget" in summary["error"]
        assert summary["exit"] == 7

    def test_indivisible_surviving_topology_is_rejected_loudly(self, tmp_path):
        """3 hosts x 4 devices with global batch 12: losing one host leaves
        8 devices, which cannot preserve the global batch — the remesh must
        fail loudly, not silently round the schedule."""
        cmd, run_dir = _elastic_child(tmp_path, """
            beat(1)
            if attempt == 1 and rank == 2:
                os._exit(3)
            for i in range(2, 600):
                beat(i); time.sleep(0.05)
            """)
        summary = self._supervisor(
            cmd, run_dir, nprocs=3, global_batch=12, grow_back_cooldown_s=30.0,
        ).run()
        assert summary["outcome"] == "crashed"
        assert "not divisible" in summary["error"]

    def test_invalid_full_topology_rejected_before_any_spawn(self, tmp_path):
        with pytest.raises(ValueError, match="not divisible"):
            self._supervisor(
                ["true"], str(tmp_path), nprocs=2, devices_per_proc=4,
                global_batch=12,
            )

    def test_all_hosts_clean_is_clean_without_remesh(self, tmp_path):
        cmd, run_dir = _elastic_child(tmp_path, """
            beat(1)
            sys.exit(0)
            """)
        summary = self._supervisor(cmd, run_dir).run()
        assert summary["outcome"] == "clean" and summary["exit"] == 0
        assert summary["remesh_count"] == 0
        assert summary["hosts_timeline"] == [2]

    def test_whole_group_preempted_is_preempted_not_host_loss(self, tmp_path):
        cmd, run_dir = _elastic_child(tmp_path, f"""
            beat(1)
            os._exit({EXIT_PREEMPTED})
            """)
        summary = self._supervisor(cmd, run_dir).run()
        assert summary["outcome"] == "preempted"
        assert summary["exit"] == EXIT_PREEMPTED
        assert summary["remesh_count"] == 0


class TestElasticCli:
    def test_unknown_entrypoint_usage(self):
        proc = subprocess.run(
            [sys.executable, "-m", "simclr_tpu.supervisor.elastic",
             "--nprocs", "2", "--devices-per-proc", "4", "--", "nonsense"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode == 2
        assert "entrypoint" in proc.stderr

    def test_bad_knob_rejected_before_spawn(self):
        proc = subprocess.run(
            [sys.executable, "-m", "simclr_tpu.supervisor.elastic",
             "--nprocs", "2", "--devices-per-proc", "4", "--", "pretrain",
             "supervisor.backoff_max_s=-5"],
            capture_output=True, text=True, cwd=REPO_ROOT, timeout=120,
            env=dict(os.environ, JAX_PLATFORMS="cpu"),
        )
        assert proc.returncode == 2
        assert "backoff_max_s" in proc.stderr


# ---------------------------------------------------------------------------
# slow e2e: real cross-topology resumes (8-device mesh -> 4-device mesh)
# ---------------------------------------------------------------------------

SYNTH = [
    "experiment.synthetic_data=true",
    "experiment.synthetic_size=64",
]
RECIPE = [
    "parameter.epochs=4",
    "parameter.warmup_epochs=1",
    "experiment.save_model_epoch=1",
]


def _device_env(n_devices):
    """A training-subprocess env pinned to ``n_devices`` virtual CPU devices
    (the conftest pins this process to 8; cross-topology needs another
    count), with any ambient rendezvous vars scrubbed."""
    from simclr_tpu.parallel.multihost import GROUP_ENV_VARS

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    flags = [
        f for f in env.get("XLA_FLAGS", "").split()
        if "--xla_force_host_platform_device_count" not in f
    ]
    flags.append(f"--xla_force_host_platform_device_count={n_devices}")
    env["XLA_FLAGS"] = " ".join(flags)
    for var in GROUP_ENV_VARS:
        env.pop(var, None)
    return env


def _run_pretrain(args, n_devices, timeout=900):
    return subprocess.run(
        [sys.executable, "-m", "simclr_tpu.main", *SYNTH, *args],
        env=_device_env(n_devices), capture_output=True, text=True,
        cwd=REPO_ROOT, timeout=timeout,
    )


@pytest.mark.slow
class TestCrossTopologyResumeE2E:
    def test_8dev_checkpoint_resumes_on_4dev_mesh_matching_trajectory(
        self, tmp_path
    ):
        """The remesh-down resume the elastic supervisor relies on: epochs
        1-2 train on 8 devices (per-device batch 4, global 32), epochs 3-4
        resume the SAME run on 4 devices with the per-device batch rescaled
        to 8 — and the full loss history matches an uninterrupted same-seed
        8-device run within 5e-2 (reduction order differs across meshes, so
        bitwise equality is not the bar)."""
        elastic_dir = str(tmp_path / "elastic")
        proc = _run_pretrain(
            RECIPE + ["experiment.batches=4", "parameter.epochs=2",
                      f"experiment.save_dir={elastic_dir}"],
            n_devices=8,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert read_topology(elastic_dir)["n_devices"] == 8

        proc = _run_pretrain(
            RECIPE + ["experiment.batches=8", "experiment.resume=true",
                      f"experiment.save_dir={elastic_dir}"],
            n_devices=4,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        # the sidecar now records the shrunken topology for the NEXT resume
        assert read_topology(elastic_dir) == {
            "n_devices": 4, "n_processes": 1, "global_batch": 32}
        with open(os.path.join(elastic_dir, "pretrain_results.json")) as f:
            remeshed = json.load(f)
        assert remeshed["complete"] is True
        assert [e for e, _ in remeshed["loss_history"]] == [1, 2, 3, 4]
        # the topology_change event landed in the merged timeline
        changes = _events(elastic_dir, "topology_change")
        assert changes and changes[-1]["devices_before"] == 8
        assert changes[-1]["devices_after"] == 4
        assert changes[-1]["per_device_batch"] == 8

        clean_dir = str(tmp_path / "clean")
        proc = _run_pretrain(
            RECIPE + ["experiment.batches=4",
                      f"experiment.save_dir={clean_dir}"],
            n_devices=8,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        with open(os.path.join(clean_dir, "pretrain_results.json")) as f:
            clean = json.load(f)
        deltas = [
            abs(a - b)
            for (_, a), (_, b) in zip(
                remeshed["loss_history"], clean["loss_history"])
        ]
        assert max(deltas) <= 5e-2, deltas

    def test_global_batch_fork_is_rejected_on_resume(self, tmp_path):
        save_dir = str(tmp_path / "fork")
        proc = _run_pretrain(
            RECIPE + ["experiment.batches=4", "parameter.epochs=1",
                      f"experiment.save_dir={save_dir}"],
            n_devices=8,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        # 4 devices x 4 = global 16, was 32: forks the RNG schedule
        proc = _run_pretrain(
            RECIPE + ["experiment.batches=4", "experiment.resume=true",
                      f"experiment.save_dir={save_dir}"],
            n_devices=4,
        )
        assert proc.returncode != 0
        assert "GLOBAL batch" in proc.stderr

    def test_mid_epoch_cross_topology_resume_is_rejected(self, tmp_path):
        """A SIGTERM lands a MID-epoch preempt checkpoint (4 steps/epoch);
        resuming it onto a different device count must be refused — the
        partial-epoch replay is defined in the old per-device layout."""
        save_dir = str(tmp_path / "mid")
        proc = subprocess.Popen(
            [sys.executable, "-m", "simclr_tpu.main", *SYNTH,
             "experiment.synthetic_size=128",  # 4 steps/epoch on 8 devices
             "experiment.batches=4", "parameter.epochs=2",
             "parameter.warmup_epochs=1", "experiment.save_model_epoch=2",
             f"experiment.save_dir={save_dir}"],
            env=_device_env(8),
        )
        hb = heartbeat_path(save_dir)
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline:
            beat = read_heartbeat(hb)
            if beat and beat["step"] >= 1:
                break
            assert proc.poll() is None, "training died before first beat"
            time.sleep(0.2)
        else:
            pytest.fail("no heartbeat within 600s")
        proc.send_signal(signal.SIGTERM)
        assert proc.wait(timeout=300) == EXIT_PREEMPTED

        resumed = _run_pretrain(
            ["experiment.synthetic_size=128", "experiment.batches=8",
             "parameter.epochs=2", "parameter.warmup_epochs=1",
             "experiment.save_model_epoch=2", "experiment.resume=true",
             f"experiment.save_dir={save_dir}"],
            n_devices=4,
        )
        assert resumed.returncode != 0
        assert "epoch boundaries" in resumed.stderr


@pytest.mark.slow
class TestCrossTopologyResidency:
    def test_restore_applies_current_mesh_shardings(self, tmp_path):
        """A checkpoint saved with one REPLICATED and one row-SHARDED array
        on the 8-device mesh must restore onto a 4-device mesh with the
        CURRENT mesh's residency: the sharded array spread over all 4
        devices, the replicated one resident on every device."""
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import Mesh, NamedSharding, PartitionSpec

        from simclr_tpu.utils.checkpoint import save_checkpoint

        mesh = Mesh(np.asarray(jax.devices()), ("data",))
        tree = {
            "sharded": jax.device_put(
                jnp.arange(32, dtype=jnp.float32).reshape(8, 4),
                NamedSharding(mesh, PartitionSpec("data", None)),
            ),
            "replicated": jax.device_put(
                jnp.arange(4, dtype=jnp.float32),
                NamedSharding(mesh, PartitionSpec()),
            ),
        }
        path = str(tmp_path / "epoch=1-m")
        save_checkpoint(path, tree)

        code = textwrap.dedent(
            f"""
            import jax, numpy as np
            import jax.numpy as jnp
            from jax.sharding import Mesh, NamedSharding, PartitionSpec
            from simclr_tpu.utils.checkpoint import restore_checkpoint
            assert jax.device_count() == 4, jax.device_count()
            mesh = Mesh(np.asarray(jax.devices()), ("data",))
            target = {{
                "sharded": jax.ShapeDtypeStruct(
                    (8, 4), jnp.float32,
                    sharding=NamedSharding(mesh, PartitionSpec("data", None))),
                "replicated": jax.ShapeDtypeStruct(
                    (4,), jnp.float32,
                    sharding=NamedSharding(mesh, PartitionSpec())),
            }}
            out = restore_checkpoint({path!r}, target)
            assert len(out["sharded"].sharding.device_set) == 4
            assert not out["sharded"].sharding.is_fully_replicated
            assert out["replicated"].sharding.is_fully_replicated
            assert len(out["replicated"].sharding.device_set) == 4
            np.testing.assert_array_equal(
                np.asarray(out["sharded"]),
                np.arange(32, dtype=np.float32).reshape(8, 4))
            np.testing.assert_array_equal(
                np.asarray(out["replicated"]),
                np.arange(4, dtype=np.float32))
            print("RESIDENCY_OK")
            """
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            env=_device_env(4), capture_output=True, text=True,
            cwd=REPO_ROOT, timeout=300,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        assert "RESIDENCY_OK" in proc.stdout


# ---------------------------------------------------------------------------
# report rendering: elastic events surface in the run report (satellite 6)
# ---------------------------------------------------------------------------


class TestElasticReport:
    """build_report/render_report surface the hosts timeline and per-attempt
    elastic counters from host_lost/remesh/grow_back events."""

    def _run_dir(self, tmp_path):
        run = tmp_path / "run"
        run.mkdir()
        log = EventLog(str(run))
        log.emit("run_start", attempt=1, epochs=4)
        log.emit("epoch", epoch=1, attempt=1)
        log.emit("host_lost", attempt=1, host=1, reason="crashed", exit=13)
        log.emit(
            "remesh", attempt=1, hosts_before=2, hosts_after=1,
            per_device_batch=16, global_batch=64,
        )
        log.emit("run_start", attempt=2, epochs=4)
        log.emit("epoch", epoch=2, attempt=2)
        log.emit("grow_back", attempt=2, hosts=[1])
        log.emit(
            "remesh", attempt=2, hosts_before=1, hosts_after=2,
            per_device_batch=8, global_batch=64,
        )
        log.emit("run_start", attempt=3, epochs=4)
        log.emit("epoch", epoch=3, attempt=3)
        log.emit("epoch", epoch=4, attempt=3)
        with open(run / "supervisor_summary.json", "w") as f:
            json.dump(
                {"outcome": "clean", "exit": 0,
                 "remesh_count": 2, "grow_back_count": 1,
                 "hosts_timeline": [2, 1, 2]},
                f,
            )
        return str(run)

    def test_report_stitches_run_level_hosts_timeline(self, tmp_path):
        from simclr_tpu.obs.report import build_report

        report = build_report(self._run_dir(tmp_path))
        assert report["hosts_timeline"] == [2, 1, 2]
        assert report["outcome"] == "clean"
        a1 = report["attempts"]["1"]
        assert a1["hosts_lost"] == 1
        assert a1["remeshes"] == 1
        assert a1["host_transitions"] == [2, 1]
        a2 = report["attempts"]["2"]
        assert a2["grow_backs"] == 1
        assert a2["remeshes"] == 1
        assert a2["host_transitions"] == [1, 2]
        a3 = report["attempts"]["3"]
        assert a3["hosts_lost"] == 0 and a3["grow_backs"] == 0

    def test_render_shows_hosts_line_and_per_attempt_elastic(self, tmp_path):
        from simclr_tpu.obs.report import build_report, render_report

        text = render_report(build_report(self._run_dir(tmp_path)))
        assert "hosts: 2→1→2" in text
        assert "elastic: hosts_lost=1 remeshes=1 grow_backs=0 hosts: 2→1" in text
        assert "elastic: hosts_lost=0 remeshes=1 grow_backs=1 hosts: 1→2" in text

    def test_non_elastic_report_has_no_hosts_line(self, tmp_path):
        from simclr_tpu.obs.report import build_report, render_report

        run = tmp_path / "plain"
        run.mkdir()
        log = EventLog(str(run))
        log.emit("run_start", attempt=1, epochs=1)
        log.emit("epoch", epoch=1, attempt=1)
        report = build_report(str(run))
        assert report["hosts_timeline"] == []
        text = render_report(report)
        assert "hosts:" not in text
        assert "elastic:" not in text


# ---------------------------------------------------------------------------
# layout-invariant augmentation keys: the RNG half of the remesh contract
# ---------------------------------------------------------------------------


class TestLayoutInvariantAugmentKeys:
    """``steps._global_sample_keys`` derives per-sample augmentation keys
    from GLOBAL batch position, so a remesh that rescales the per-device
    batch (same global batch) draws bit-identical parameters — the property
    the elastic dryrun's loss-trajectory parity stands on."""

    def _global_keys(self, n_shards, n_local, views=2):
        import jax
        import numpy as np
        from jax.sharding import Mesh, PartitionSpec as P

        from simclr_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
        from simclr_tpu.parallel.steps import _global_sample_keys

        devices = np.array(jax.devices()[:n_shards]).reshape(n_shards, 1)
        mesh = Mesh(devices, (DATA_AXIS, MODEL_AXIS))
        fn = jax.shard_map(
            lambda rng: jax.random.key_data(
                _global_sample_keys(rng, n_local, views=views)
            ).reshape(views, n_local, -1),
            mesh=mesh,
            in_specs=P(),
            out_specs=P(None, DATA_AXIS),
            check_vma=False,
        )
        with mesh:
            return np.asarray(jax.jit(fn)(jax.random.key(42)))

    def test_same_global_keys_on_8_and_4_and_2_shard_meshes(self):
        import numpy as np

        want = self._global_keys(8, 4)  # global batch 32, 4/device
        assert want.shape[:2] == (2, 32)
        np.testing.assert_array_equal(self._global_keys(4, 8), want)
        np.testing.assert_array_equal(self._global_keys(2, 16), want)

    def test_single_view_schedule_matches_across_layouts(self):
        import numpy as np

        want = self._global_keys(8, 2, views=1)  # supervised: one view
        np.testing.assert_array_equal(self._global_keys(2, 8, views=1), want)

    def test_views_draw_distinct_streams(self):
        import numpy as np

        keys = self._global_keys(4, 4)
        assert not np.array_equal(keys[0], keys[1])
