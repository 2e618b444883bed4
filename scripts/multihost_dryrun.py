"""Multi-host dryrun: 2-process CPU rendezvous + chunked-ring parity check.

Proves the multi-host path end to end WITHOUT a pod: spawns a real
2-process jobs via ``simclr_tpu.launch`` (coordinator rendezvous over
``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID``,
4 forced-CPU devices per process), runs the ``simclr_tpu.multihost_dryrun``
worker on the resulting 8-device global mesh, then runs the SAME worker
single-process on 8 devices and compares checksums. The worker exercises
rendezvous and ``grad_allreduce(..., overlap="chunked")``
(int8 ring, non-divisible chunk count) — so bitwise parity here means the
multi-host code path computes exactly what the single-process path does.

ONE JSON payload line:

    {"metric": "multihost_dryrun_parity", "value": 1.0, "unit": "bool",
     "process_count": 2, "parity": true,
     "multi": {...worker line...}, "single": {...worker line...}}

A passing run has ``"process_count": 2`` and ``"parity": true``.
Robustness contract (same as allreduce_bench.py):
never exits nonzero, never ends on a traceback, emits EXACTLY ONE payload
line; failures land in an ``"error"`` field.

``--fleet`` runs the fleet-observability smoke: a short fault-free
2-process elastic run with
``telemetry.fleet=true``, whose supervisor-side FleetCollector must expose
a merged scrape carrying gauges labeled for BOTH hosts plus the
straggler-skew gauge, and embed the fleet snapshot into
``supervisor_summary.json``. The evidence lines from the merged scrape are
printed verbatim, then ONE payload::

    {"metric": "fleet_smoke", "value": 1.0, "unit": "bool",
     "hosts_seen": ["0", "1"], "skew_ratio": 1.08,
     "summary_embeds_fleet": true, ...}

``--elastic`` runs the OTHER multi-host proof instead — the elastic
supervisor's full kill/remesh/grow-back cycle: a 2-process CPU pretrain
whose process 1 is hard-killed
mid-run via ``SIMCLR_FAULT_DIE_PROCESS``, which must remesh down to 1
process, resume from the last verified checkpoint with the global batch
preserved, grow back to 2 processes, and finish clean — then an
uninterrupted same-seed single-process run on the same 8-device global
mesh, with per-epoch loss-trajectory parity within 5e-2 (reduction order
differs across topologies, so bitwise is not expected). Its payload::

    {"metric": "elastic_dryrun", "value": 1.0, "unit": "bool",
     "outcome": "clean", "remesh_count": 2, "grow_back_count": 1,
     "hosts": [2, 1, 2], "parity": true,
     "fleet": {"hosts_seen": ["0", "1"], "skew_gauge_seen": true, ...}, ...}

The elastic run also runs the fleet plane (``telemetry.fleet=true``): its
merged scrape must label both hosts and expose the skew gauge, and the
summary must embed the fleet snapshot — all part of the elastic payload's
ok gate.

Env knobs: ``MULTIHOST_DRYRUN_TIMEOUT_S`` (per-phase subprocess timeout,
default 300), ``MULTIHOST_DRYRUN_COORD_TIMEOUT_S`` (rendezvous fail-fast
deadline exported as ``JAX_COORDINATOR_TIMEOUT_S``, default 60),
``ELASTIC_DRYRUN_TIMEOUT_S`` (the elastic phase's own timeout, default 1200
— it spans three compile-from-scratch generations).
"""

from __future__ import annotations

import json
import os
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

WORKER_MODULE = "simclr_tpu.multihost_dryrun"
NPROCS = 2
DEVICES_PER_PROC = 4

# which payload the error backstops stamp; flipped by --elastic
_METRIC = "multihost_dryrun_parity"

_PAYLOAD_EMITTED = False


def _emit_payload(payload: dict) -> None:
    """Print the run's single payload line, exactly once."""
    global _PAYLOAD_EMITTED
    if _PAYLOAD_EMITTED:
        return
    _PAYLOAD_EMITTED = True
    print(json.dumps(payload), flush=True)


def last_ditch_payload(exc: BaseException) -> dict:
    return {
        "metric": _METRIC,
        "value": 0.0,
        "unit": "bool",
        "parity": False,
        "error": repr(exc),
    }


def _sigterm_backstop(signum, frame) -> None:
    if not _PAYLOAD_EMITTED:
        _emit_payload(
            last_ditch_payload(
                RuntimeError(f"terminated by signal {signum} before finishing")
            )
        )
    os._exit(0)


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parse_worker_line(stdout: str, label: str) -> dict:
    """The worker prints one JSON line from process 0; find it."""
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                obj = json.loads(line)
            except json.JSONDecodeError:
                continue
            if obj.get("worker") == "multihost_dryrun":
                return obj
    raise RuntimeError(f"{label}: no worker payload line in output")


def _run(cmd: list[str], env: dict, timeout_s: float, label: str) -> dict:
    proc = subprocess.run(
        cmd, env=env, capture_output=True, text=True, timeout=timeout_s,
        cwd=REPO_ROOT,
    )
    # surface worker stderr for the log, prefixed as commentary
    for line in proc.stderr.splitlines()[-20:]:
        print(f"# [{label}] {line}", file=sys.stderr)
    if proc.returncode != 0:
        raise RuntimeError(
            f"{label} exited {proc.returncode}; last stderr: "
            f"{proc.stderr.strip().splitlines()[-1] if proc.stderr.strip() else '<empty>'!r}"
        )
    return _parse_worker_line(proc.stdout, label)


def _scrubbed_env() -> dict:
    """os.environ minus any ambient rendezvous/backend config, so each phase
    fully controls its own; plus the fail-fast coordinator deadline."""
    env = {
        k: v
        for k, v in os.environ.items()
        if k
        not in (
            "JAX_COORDINATOR_ADDRESS",
            "COORDINATOR_ADDRESS",
            "JAX_NUM_PROCESSES",
            "JAX_PROCESS_ID",
            "JAX_PLATFORMS",
            "XLA_FLAGS",
        )
    }
    env["JAX_COORDINATOR_TIMEOUT_S"] = os.environ.get(
        "MULTIHOST_DRYRUN_COORD_TIMEOUT_S", "60"
    )
    return env


# Elastic recipe: 4 global devices (2 processes x 2 — fewer virtual CPU
# devices than the parity dryrun because XLA device threads oversubscribe
# a CI core), global batch 16 (4 per device x 4), synthetic 16 samples ->
# ONE step/epoch (the lightest epoch that still walks the whole
# restore/remesh machinery); one checkpoint per epoch so every epoch
# boundary is a restore point. The survivor topology (1 process x 2
# devices) divides the global batch (-> 8 per device), so the remesh
# preserves it. Three epochs is the
# minimum lifecycle: epoch 1 (checkpoint) -> die at the epoch-2 beat ->
# shrunken epoch 2 -> grow-back drain -> full-size epoch 3.
ELASTIC_DEVICES_PER_PROC = 2
ELASTIC_RECIPE = [
    "experiment.synthetic_data=true",
    "experiment.synthetic_size=16",
    "experiment.batches=4",
    "parameter.epochs=3",
    "parameter.warmup_epochs=1",
    "experiment.save_model_epoch=1",
    # policy tuned for a CI-speed cycle: near-instant group relaunch, 1 s
    # lost-host cooldown so grow-back triggers right after the shrunken
    # generation's first completed epoch
    "supervisor.backoff_base_s=0.1",
    "supervisor.backoff_max_s=2.0",
    "supervisor.grow_back_cooldown_s=1.0",
    "supervisor.startup_grace_s=600.0",
    # a contended CI machine can sit minutes between two beats (a
    # generation's first step compiles) — the default 30 s floor would
    # declare a live host wedged, so park hang detection out of the way
    # (this e2e injects a hard DIE, not a wedge)
    "supervisor.heartbeat_min_timeout_s=900.0",
]

# steps/epoch = 1, so the guard's step beats fall at steps 1, 2, 3...:
# 1:2 hard-kills process 1 at its epoch-2 beat — BEFORE that epoch's
# checkpoint lands, so the remeshed generation must resume from epoch 1
ELASTIC_DIE_FAULT = "1:2"


def _fleet_overrides(run_dir: str) -> list[str]:
    """Fleet-plane knobs for an elastic run: every process publishes its
    per-host exporter ready file and the supervisor's FleetCollector
    scrapes them into the merged ``simclr_fleet_*`` endpoint (discovered
    through ``<run_dir>/fleet.ready``)."""
    return [
        f"telemetry.ready_file={os.path.join(run_dir, 'telemetry.ready')}",
        "telemetry.fleet=true",
        # scrape fast enough that even the shrunken generation's short
        # epochs land on the fleet page
        "telemetry.fleet_poll_s=0.5",
    ]


SKEW_GAUGE = "simclr_fleet_step_time_skew_ratio"


class _FleetWatch:
    """Polls the supervisor's merged fleet endpoint while the run lives.

    Collects the acceptance evidence: at least one gauge labeled for EACH
    host, the straggler-skew gauge (and its last positive value), and a
    few verbatim sample lines for the log.
    """

    def __init__(self, run_dir: str):
        self.ready_path = os.path.join(run_dir, "fleet.ready")
        self.hosts_seen: set[str] = set()
        self.skew_gauge_seen = False
        self.skew_ratio = 0.0
        self.sample_lines: dict[str, str] = {}
        self.scrapes = 0

    def poll(self) -> None:
        try:
            with open(self.ready_path) as f:
                info = json.load(f)
            url = (
                f"http://{info.get('host', '127.0.0.1')}:{info['port']}/metrics"
            )
            with urllib.request.urlopen(url, timeout=5) as resp:
                text = resp.read().decode()
        except Exception:  # noqa: BLE001 - collector not up yet / mid-restart
            return
        self.scrapes += 1
        for line in text.splitlines():
            for rank in ("0", "1"):
                if f'host="{rank}"' in line:
                    self.hosts_seen.add(rank)
                    self.sample_lines.setdefault(f"host{rank}", line)
            if line.startswith(SKEW_GAUGE + " "):
                self.skew_gauge_seen = True
                self.sample_lines["skew"] = line
                try:
                    value = float(line.split()[1])
                except (IndexError, ValueError):
                    value = 0.0
                if value > 0:
                    self.skew_ratio = value

    @property
    def both_hosts_labeled(self) -> bool:
        return {"0", "1"} <= self.hosts_seen

    def evidence(self) -> dict:
        return {
            "hosts_seen": sorted(self.hosts_seen),
            "skew_gauge_seen": self.skew_gauge_seen,
            "skew_ratio": self.skew_ratio,
            "fleet_scrapes": self.scrapes,
        }

    def print_samples(self) -> None:
        # the evidence lines verbatim: the host="1" label and the skew gauge
        for key in ("host0", "host1", "skew"):
            if key in self.sample_lines:
                print(self.sample_lines[key], flush=True)


def _run_elastic_supervisor(
    cmd: list[str], env: dict, timeout_s: float, run_dir: str, label: str
) -> tuple[dict, _FleetWatch]:
    """Spawn the elastic supervisor, scraping the fleet endpoint while it
    runs; returns (summary line, fleet evidence). Output goes to files,
    not pipes — the poll loop below never drains, and a chatty supervisor
    would deadlock a full pipe buffer."""
    with tempfile.TemporaryFile(mode="w+") as out_f, \
            tempfile.TemporaryFile(mode="w+") as err_f:
        proc = subprocess.Popen(
            cmd, env=env, stdout=out_f, stderr=err_f, text=True,
            cwd=REPO_ROOT,
        )
        watch = _FleetWatch(run_dir)
        deadline = time.monotonic() + timeout_s
        while proc.poll() is None and time.monotonic() < deadline:
            watch.poll()
            time.sleep(0.5)
        if proc.poll() is None:
            proc.kill()
            proc.wait()
            raise RuntimeError(f"{label} timed out after {timeout_s:.0f}s")
        out_f.seek(0)
        err_f.seek(0)
        stdout, stderr = out_f.read(), err_f.read()
    for line in stderr.splitlines()[-20:]:
        print(f"# [{label}] {line}", file=sys.stderr)
    summary = None
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                summary = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    if summary is None:
        raise RuntimeError(
            f"{label} exited {proc.returncode} with no summary line"
        )
    summary["_returncode"] = proc.returncode
    return summary, watch


def _summary_embeds_fleet(run_dir: str) -> bool:
    try:
        with open(os.path.join(run_dir, "supervisor_summary.json")) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return False
    return isinstance(payload, dict) and isinstance(payload.get("fleet"), dict)


def _load_results(save_dir: str, label: str) -> dict:
    path = os.path.join(save_dir, "pretrain_results.json")
    try:
        with open(path, encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError) as exc:
        raise RuntimeError(f"{label}: unreadable {path}: {exc!r}") from exc


def _event_counts(save_dir: str) -> dict:
    counts: dict[str, int] = {}
    try:
        with open(os.path.join(save_dir, "events.jsonl"), encoding="utf-8") as f:
            for line in f:
                try:
                    event = json.loads(line)
                except ValueError:
                    continue
                kind = event.get("event")
                if isinstance(kind, str):
                    counts[kind] = counts.get(kind, 0) + 1
    except OSError:
        pass
    return counts


def elastic_main() -> None:
    try:
        signal.signal(signal.SIGTERM, _sigterm_backstop)
    except ValueError:
        pass
    timeout_s = float(os.environ.get("ELASTIC_DRYRUN_TIMEOUT_S", 1200))
    base_env = _scrubbed_env()
    workdir = tempfile.mkdtemp(prefix="elastic_dryrun_")
    elastic_dir = os.path.join(workdir, "elastic")
    ref_dir = os.path.join(workdir, "reference")

    # phase 1: elastic run — process 1 hard-killed at its epoch-2 beat;
    # fleet plane on, its merged endpoint scraped live from this process
    elastic_env = dict(base_env)
    elastic_env["SIMCLR_FAULT_DIE_PROCESS"] = ELASTIC_DIE_FAULT
    summary, watch = _run_elastic_supervisor(
        [
            sys.executable, "-m", "simclr_tpu.supervisor.elastic",
            "--nprocs", str(NPROCS),
            "--devices-per-proc", str(ELASTIC_DEVICES_PER_PROC),
            "--force-cpu",
            "--coord-timeout-s", base_env["JAX_COORDINATOR_TIMEOUT_S"],
            "--", "pretrain", *ELASTIC_RECIPE,
            *_fleet_overrides(elastic_dir),
            f"experiment.save_dir={elastic_dir}",
        ],
        elastic_env, timeout_s, elastic_dir, "elastic",
    )
    returncode = summary.pop("_returncode")

    # phase 2: uninterrupted same-seed reference on the same 4-device
    # global mesh, single process
    ref_env = dict(base_env)
    ref_env["JAX_PLATFORMS"] = "cpu"
    ref_env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        f"{NPROCS * ELASTIC_DEVICES_PER_PROC}"
    )
    ref = subprocess.run(
        [
            sys.executable, "-m", "simclr_tpu.main", *ELASTIC_RECIPE,
            f"experiment.save_dir={ref_dir}",
        ],
        env=ref_env, capture_output=True, text=True, timeout=timeout_s,
        cwd=REPO_ROOT,
    )
    for line in ref.stderr.splitlines()[-10:]:
        print(f"# [reference] {line}", file=sys.stderr)
    if ref.returncode != 0:
        raise RuntimeError(f"reference run exited {ref.returncode}")

    # loss-trajectory parity: same epochs, every per-epoch loss within 5e-2
    # (cross-topology reduction order shifts floats; the trajectory must not
    # fork beyond that)
    elastic_hist = _load_results(elastic_dir, "elastic").get("loss_history", [])
    ref_hist = _load_results(ref_dir, "reference").get("loss_history", [])
    elastic_losses = {int(e): float(v) for e, v in elastic_hist}
    ref_losses = {int(e): float(v) for e, v in ref_hist}
    epochs_match = sorted(elastic_losses) == sorted(ref_losses) and elastic_losses
    max_delta = (
        max(abs(elastic_losses[e] - ref_losses[e]) for e in elastic_losses)
        if epochs_match else None
    )
    parity = bool(epochs_match) and max_delta is not None and max_delta <= 5e-2

    events = _event_counts(elastic_dir)
    events_ok = all(
        events.get(kind, 0) >= 1
        for kind in ("host_lost", "remesh", "grow_back")
    )
    outcome = summary.get("outcome")
    remesh_count = int(summary.get("remesh_count", 0) or 0)
    grow_back_count = int(summary.get("grow_back_count", 0) or 0)
    # fleet acceptance: merged scrape carried gauges for BOTH hosts plus
    # the skew gauge, and the run-end summary embeds the fleet snapshot.
    # The embedded snapshot itself is kept OUT of the payload (its per-host
    # "error" keys would read as a failure of this run).
    embeds_fleet = (
        isinstance(summary.pop("fleet", None), dict)
        and _summary_embeds_fleet(elastic_dir)
    )
    fleet_ok = (
        watch.both_hosts_labeled and watch.skew_gauge_seen and embeds_fleet
    )
    watch.print_samples()
    ok = (
        outcome == "clean"
        and returncode == 0
        and remesh_count >= 1
        and grow_back_count >= 1
        and parity
        and events_ok
        and fleet_ok
    )
    payload = {
        "metric": "elastic_dryrun",
        "value": 1.0 if ok else 0.0,
        "unit": "bool",
        "outcome": outcome,
        "remesh_count": remesh_count,
        "grow_back_count": grow_back_count,
        "hosts": summary.get("hosts_timeline"),
        "parity": parity,
        "max_loss_delta": max_delta,
        "events": {
            k: events.get(k, 0) for k in ("host_lost", "remesh", "grow_back")
        },
        "fleet": {**watch.evidence(), "summary_embeds_fleet": embeds_fleet},
        "supervisor": summary,
    }
    if not ok:
        failures = []
        if outcome != "clean":
            failures.append(f"outcome={outcome}")
        if remesh_count < 1:
            failures.append("no remesh")
        if grow_back_count < 1:
            failures.append("no grow-back")
        if not parity:
            failures.append(f"loss trajectory diverged (max delta {max_delta})")
        if not events_ok:
            failures.append(f"missing elastic events ({events})")
        if not fleet_ok:
            failures.append(f"fleet evidence incomplete ({watch.evidence()})")
        payload["error"] = "; ".join(failures) or "unknown failure"
    _emit_payload(payload)


def fleet_main() -> None:
    """Fleet-observability smoke: a fault-free 2-process elastic run whose
    merged fleet scrape must label BOTH hosts and carry the straggler-skew
    gauge, with the snapshot embedded in the run-end summary."""
    try:
        signal.signal(signal.SIGTERM, _sigterm_backstop)
    except ValueError:
        pass
    timeout_s = float(os.environ.get("FLEET_SMOKE_TIMEOUT_S", 900))
    base_env = _scrubbed_env()
    run_dir = os.path.join(tempfile.mkdtemp(prefix="fleet_smoke_"), "run")

    summary, watch = _run_elastic_supervisor(
        [
            sys.executable, "-m", "simclr_tpu.supervisor.elastic",
            "--nprocs", str(NPROCS),
            "--devices-per-proc", str(ELASTIC_DEVICES_PER_PROC),
            "--force-cpu",
            "--coord-timeout-s", base_env["JAX_COORDINATOR_TIMEOUT_S"],
            "--", "pretrain", *ELASTIC_RECIPE,
            *_fleet_overrides(run_dir),
            f"experiment.save_dir={run_dir}",
        ],
        base_env, timeout_s, run_dir, "fleet_smoke",
    )
    returncode = summary.pop("_returncode")
    embeds_fleet = (
        isinstance(summary.pop("fleet", None), dict)
        and _summary_embeds_fleet(run_dir)
    )
    watch.print_samples()
    outcome = summary.get("outcome")
    ok = (
        outcome == "clean"
        and returncode == 0
        and watch.both_hosts_labeled
        and watch.skew_gauge_seen
        and embeds_fleet
    )
    payload = {
        "metric": "fleet_smoke",
        "value": 1.0 if ok else 0.0,
        "unit": "bool",
        "outcome": outcome,
        **watch.evidence(),
        "summary_embeds_fleet": embeds_fleet,
    }
    if not ok:
        failures = []
        if outcome != "clean":
            failures.append(f"outcome={outcome}")
        if not watch.both_hosts_labeled:
            failures.append(f"hosts seen {sorted(watch.hosts_seen)} != [0, 1]")
        if not watch.skew_gauge_seen:
            failures.append("no skew gauge on the merged scrape")
        if not embeds_fleet:
            failures.append("summary does not embed the fleet snapshot")
        payload["error"] = "; ".join(failures) or "unknown failure"
    _emit_payload(payload)


def main() -> None:
    try:
        signal.signal(signal.SIGTERM, _sigterm_backstop)
    except ValueError:  # non-main thread (embedded runs)
        pass
    timeout_s = float(os.environ.get("MULTIHOST_DRYRUN_TIMEOUT_S", 300))
    # a wedged coordinator fails in ~1 min, not jax's 5-minute default
    base_env = _scrubbed_env()

    # phase 1: real 2-process rendezvous, 4 CPU devices each => 8 global
    multi_cmd = [
        sys.executable, "-m", "simclr_tpu.launch",
        "--nprocs", str(NPROCS),
        "--coordinator", f"127.0.0.1:{_free_port()}",
        "--devices-per-proc", str(DEVICES_PER_PROC),
        "-m", WORKER_MODULE,
    ]
    multi = _run(multi_cmd, base_env, timeout_s, "multi")

    # phase 2: single-process reference on the same 8-device global mesh
    single_env = dict(base_env)
    single_env["JAX_PLATFORMS"] = "cpu"
    single_env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={NPROCS * DEVICES_PER_PROC}"
    )
    single = _run(
        [sys.executable, "-m", WORKER_MODULE], single_env, timeout_s, "single"
    )

    parity = (
        multi["process_count"] == NPROCS
        and multi["n_devices"] == single["n_devices"]
        and multi["checksum"] == single["checksum"]  # bitwise, no tolerance
    )
    payload = {
        "metric": "multihost_dryrun_parity",
        "value": 1.0 if parity else 0.0,
        "unit": "bool",
        "process_count": multi["process_count"],
        "parity": parity,
        "multi": multi,
        "single": single,
    }
    if not parity:
        payload["error"] = "multi-process run diverged from single-process run"
    _emit_payload(payload)


if __name__ == "__main__":
    elastic_mode = "--elastic" in sys.argv[1:]
    fleet_mode = "--fleet" in sys.argv[1:]
    if elastic_mode:
        _METRIC = "elastic_dryrun"
    elif fleet_mode:
        _METRIC = "fleet_smoke"
    try:
        if elastic_mode:
            elastic_main()
        elif fleet_mode:
            fleet_main()
        else:
            main()
    except Exception as exc:  # last-ditch contract keeper: one line, rc 0
        print(f"# unexpected error: {exc!r}", file=sys.stderr)
        _emit_payload(last_ditch_payload(exc))
    sys.exit(0)
