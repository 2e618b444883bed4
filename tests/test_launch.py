"""Multi-process launcher integration (simclr_tpu/launch.py).

True multi-PROCESS semantics — separate address spaces, per-process input
pipelines feeding ``make_array_from_process_local_data``, collectives over the
jax distributed runtime — cannot be covered by the in-process 8-device mesh
the rest of the suite uses, so this spawns real subprocesses. The reference's
launcher contract being checked: child env wiring, pass-through of dotted
overrides, fail-fast on child failure (``/root/reference/launch.py:255-259``).
"""

import os
import socket
import subprocess
import sys

import pytest

from simclr_tpu.eval import SWEEP_CONFIG_KEY

pytestmark = pytest.mark.slow  # multi-minute on a 1-core host

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _launcher_env():
    # children must pick their own platform/device env, not inherit the
    # conftest's in-process pins
    return {
        k: v
        for k, v in os.environ.items()
        if k not in ("XLA_FLAGS", "JAX_PLATFORMS")
    }


def _coordinator() -> str:
    """OS-assigned ephemeral coordinator port: a fixed port collides with
    stale coordinators from killed runs or concurrent pytest invocations,
    presenting as flaky rendezvous timeouts (ADVICE r2)."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return f"127.0.0.1:{s.getsockname()[1]}"


def _run_launcher(args, timeout=420):
    env = _launcher_env()
    return subprocess.run(
        [sys.executable, "-m", "simclr_tpu.launch",
         "--coordinator", _coordinator(), *args],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_two_process_pretrain_end_to_end(tmp_path):
    save_dir = tmp_path / "ckpts"
    result = _run_launcher(
        [
            "--nprocs", "2",
            "--devices-per-proc", "2",
            "-m", "simclr_tpu.main",
            "parameter.epochs=1",
            "experiment.batches=8",
            "parameter.warmup_epochs=0",
            "experiment.save_model_epoch=1",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            f"experiment.save_dir={save_dir}",
        ]
    )
    assert result.returncode == 0, result.stderr[-2000:]
    assert (save_dir / "epoch=1-cifar10").exists(), result.stderr[-2000:]
    # exactly one process logs (the reference's rank-0-only logging)
    assert result.stderr.count("Epoch:1/1") == 1, result.stderr[-2000:]


def test_two_process_eval_end_to_end(tmp_path):
    """Multi-host feature extraction (VERDICT r1 #5): eval's input side must
    assemble globally-sharded batches from per-process row blocks
    (``put_global_batch``), not ``device_put`` arrays it can't fully address.
    Covers extract_features + centroid probe + results JSON under 2 real
    processes."""
    save_dir = tmp_path / "ckpts"
    result = _run_launcher(
        [
            "--nprocs", "2",
            "--devices-per-proc", "2",
            "-m", "simclr_tpu.main",
            "parameter.epochs=1",
            "experiment.batches=8",
            "parameter.warmup_epochs=0",
            "experiment.save_model_epoch=1",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            f"experiment.save_dir={save_dir}",
        ]
    )
    assert result.returncode == 0, result.stderr[-2000:]
    eval_dir = tmp_path / "eval"
    result = _run_launcher(
        [
            "--nprocs", "2",
            "--devices-per-proc", "2",
            "-m", "simclr_tpu.eval",
            "parameter.classifier=centroid",
            "experiment.batches=8",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            f"experiment.target_dir={save_dir}",
            f"experiment.save_dir={eval_dir}",
        ]
    )
    assert result.returncode == 0, result.stderr[-2000:]
    results_files = list(eval_dir.rglob("results.json"))
    assert len(results_files) == 1, result.stderr[-2000:]
    import json

    results = json.load(open(results_files[0]))
    (ckpt_results,) = (
        v for k, v in results.items() if k != SWEEP_CONFIG_KEY
    )
    assert 0.0 <= ckpt_results["val_acc"] <= 1.0


def test_two_process_linear_probe_and_save_features(tmp_path):
    """The two entry surfaces round 2 left untested under real processes
    (VERDICT r2 item 4): `eval` with classifier=linear — learnable_probe
    trains on the full replicated feature matrix per process, so the
    host-local `jnp.asarray` upload feeding an unsharded jit must behave
    identically on both — and `save_features`, whose augmented-features
    input side reuses put_global_batch with per-process row blocks. One
    shared pretrain keeps the wall-clock down."""
    save_dir = tmp_path / "ckpts"
    result = _run_launcher(
        [
            "--nprocs", "2",
            "--devices-per-proc", "2",
            "-m", "simclr_tpu.main",
            "parameter.epochs=1",
            "experiment.batches=8",
            "parameter.warmup_epochs=0",
            "experiment.save_model_epoch=1",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            f"experiment.save_dir={save_dir}",
        ]
    )
    assert result.returncode == 0, result.stderr[-2000:]

    eval_dir = tmp_path / "eval"
    result = _run_launcher(
        [
            "--nprocs", "2",
            "--devices-per-proc", "2",
            "-m", "simclr_tpu.eval",
            "parameter.classifier=linear",
            "parameter.epochs=2",
            "experiment.batches=8",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            f"experiment.target_dir={save_dir}",
            f"experiment.save_dir={eval_dir}",
        ],
        timeout=600,
    )
    assert result.returncode == 0, result.stderr[-2000:]
    import json

    (results_file,) = list(eval_dir.rglob("results.json"))
    (ckpt_results,) = (
        v for k, v in json.load(open(results_file)).items() if k != SWEEP_CONFIG_KEY
    )
    assert len(ckpt_results["val_accuracies"]) == 2
    assert all(0.0 <= a <= 1.0 for a in ckpt_results["val_accuracies"])

    feat_dir = tmp_path / "features"
    result = _run_launcher(
        [
            "--nprocs", "2",
            "--devices-per-proc", "2",
            "-m", "simclr_tpu.save_features",
            "experiment.batches=8",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            f"experiment.target_dir={save_dir}",
            f"experiment.save_dir={feat_dir}",
        ],
        timeout=600,
    )
    assert result.returncode == 0, result.stderr[-2000:]
    names = {p.name for p in feat_dir.rglob("*.npy")}
    key = "epoch=1-cifar10"
    for expected in (
        f"{key}.train.features.npy",
        f"{key}.train.labels.npy",
        f"{key}.val.features.npy",
        f"{key}.val.labels.npy",
        f"{key}.train.aug-1.features.npy",
        f"{key}.train.aug-5.features.npy",
        f"{key}.train.aug-20.features.npy",
    ):
        assert expected in names, (expected, names)


def test_two_process_pretrain_with_monitor(tmp_path):
    """experiment.eval_every under 2 real processes: the monitor's
    replicated gather (jitted identity over non-addressable shards) and the
    multi-host feature extraction must both work mid-training."""
    save_dir = tmp_path / "ckpts"
    result = _run_launcher(
        [
            "--nprocs", "2",
            "--devices-per-proc", "2",
            "-m", "simclr_tpu.main",
            "parameter.epochs=1",
            "experiment.batches=8",
            "parameter.warmup_epochs=0",
            "experiment.save_model_epoch=1",
            "experiment.eval_every=1",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            f"experiment.save_dir={save_dir}",
        ],
        timeout=600,
    )
    assert result.returncode == 0, result.stderr[-2000:]
    # one process logs: the random-init anchor and the epoch's probe
    for epoch in (0, 1):
        assert result.stderr.count(f"Epoch:{epoch} centroid probe") == 1, (
            result.stderr[-2000:]
        )


def test_two_process_tp_pretrain(tmp_path):
    """mesh.model=2 under 2 real processes (mesh (data=2, model=2) over 2x2
    devices): TP state layout spans processes, batches upload per-process
    row blocks, and the jit-level optimizer reduces LARS norms across
    shards it cannot address locally."""
    save_dir = tmp_path / "ckpts"
    result = _run_launcher(
        [
            "--nprocs", "2",
            "--devices-per-proc", "2",
            "-m", "simclr_tpu.main",
            "mesh.model=2",
            "parameter.epochs=1",
            "experiment.batches=8",
            "parameter.warmup_epochs=0",
            "experiment.save_model_epoch=1",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            f"experiment.save_dir={save_dir}",
        ]
    )
    assert result.returncode == 0, result.stderr[-2000:]
    assert (save_dir / "epoch=1-cifar10").exists(), result.stderr[-2000:]
    assert result.stderr.count("Epoch:1/1") == 1, result.stderr[-2000:]


def test_two_process_supervised(tmp_path):
    """The supervised loop under 2 real processes: per-process row blocks of
    images AND labels, and the masked distributed validation sweep."""
    save_dir = tmp_path / "ckpts"
    result = _run_launcher(
        [
            "--nprocs", "2",
            "--devices-per-proc", "2",
            "-m", "simclr_tpu.supervised",
            "parameter.epochs=1",
            "experiment.batches=8",
            "parameter.warmup_epochs=0",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            f"experiment.save_dir={save_dir}",
        ],
        timeout=900,
    )
    assert result.returncode == 0, result.stderr[-2000:]
    kept = [
        p for p in save_dir.iterdir() if p.name.startswith("epoch=") and p.is_dir()
    ]
    assert len(kept) == 1, result.stderr[-2000:]


def test_fail_fast_on_child_killed_mid_run(tmp_path):
    """SIGKILL one child mid-training: the launcher must notice the dead
    peer (even though the survivor blocks in a collective waiting for it)
    and terminate the job, not hang — SURVEY §5.3's fail-fast contract."""
    import signal
    import time

    log_path = tmp_path / "launcher.log"
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "simclr_tpu.launch",
                "--coordinator", _coordinator(),
                "--nprocs", "2",
                "--devices-per-proc", "1",
                "-m", "simclr_tpu.main",
                "parameter.epochs=500",  # long enough to still be running
                "experiment.batches=8",
                "parameter.warmup_epochs=0",
                "experiment.save_model_epoch=500",
                "experiment.synthetic_data=true",
                "experiment.synthetic_size=64",
                f"experiment.save_dir={tmp_path / 'ckpts'}",
            ],
            cwd=REPO,
            env=_launcher_env(),
            stdout=log,
            stderr=log,
            start_new_session=True,  # its own process group, so we can find children
        )
    try:
        # wait until training has genuinely started (an epoch line logged) so
        # the survivor is killed MID-TRAINING, inside/around a collective —
        # not during import or rendezvous, which the config-failure test
        # already covers
        deadline = time.time() + 300
        while time.time() < deadline:
            assert proc.poll() is None, (
                f"launcher exited rc={proc.returncode} before training "
                f"started:\n{log_path.read_text()[-2000:]}"
            )
            if b"Epoch:" in log_path.read_bytes():
                break
            time.sleep(0.5)
        else:
            raise AssertionError(
                f"training never started:\n{log_path.read_text()[-2000:]}"
            )
        pgid_procs = subprocess.run(
            ["pgrep", "-g", str(proc.pid)], capture_output=True, text=True
        ).stdout.split()
        kids = [int(p) for p in pgid_procs if int(p) != proc.pid]
        assert len(kids) >= 2, f"expected 2 children, found {kids}"
        os.kill(kids[-1], signal.SIGKILL)
        rc = proc.wait(timeout=120)
        assert rc != 0
    finally:
        if proc.poll() is None:
            os.killpg(proc.pid, signal.SIGKILL)
            proc.wait()


def test_supervised_sigkill_then_resume(tmp_path):
    """VERDICT r3 item 6's done-criterion: SIGKILL a supervised run mid-way,
    relaunch with experiment.resume=true, and the job continues from the
    persisted best checkpoint instead of restarting the 200-epoch recipe
    from scratch (the reference cannot do this, SURVEY §5.3)."""
    import signal
    import time

    save_dir = tmp_path / "sup-ckpts"
    env = _launcher_env()
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    args = [
        "experiment.batches=4",  # x8 devices: global 32 -> 2 steps/epoch
        "parameter.warmup_epochs=0",
        "parameter.metric=acc",
        "experiment.synthetic_data=true",
        "experiment.synthetic_size=64",
        f"experiment.save_dir={save_dir}",
    ]
    log_path = tmp_path / "killed-run.log"
    with open(log_path, "wb") as log:
        proc = subprocess.Popen(
            [sys.executable, "-m", "simclr_tpu.supervised",
             "parameter.epochs=500", *args],
            cwd=REPO, env=env, stdout=log, stderr=log,
        )
    try:
        # kill as soon as the first best checkpoint is finalized on disk
        # (orbax renames atomically; list_checkpoints skips its tmp dirs)
        from simclr_tpu.utils.checkpoint import latest_checkpoint

        deadline = time.time() + 300
        while time.time() < deadline:
            assert proc.poll() is None, (
                f"run exited rc={proc.returncode} before a checkpoint "
                f"landed:\n{log_path.read_text()[-2000:]}"
            )
            if latest_checkpoint(str(save_dir)) is not None:
                break
            time.sleep(0.2)
        else:
            raise AssertionError(
                f"no checkpoint appeared:\n{log_path.read_text()[-2000:]}"
            )
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()

    # relaunch with resume: continues from the surviving best checkpoint.
    # Wherever the kill landed, the resumed run must (a) start past epoch 1,
    # (b) finish the recipe: final step count == epochs * steps_per_epoch.
    from simclr_tpu.supervised import main as supervised_main

    resumed = supervised_main(
        ["parameter.epochs=6", "experiment.resume=true", *args]
    )
    assert resumed["history"], "resumed run trained no epochs"
    assert resumed["history"][0]["epoch"] >= 2, "resume restarted from scratch"
    assert resumed["steps"] == 12
    ckpts = [
        d for d in os.listdir(save_dir)
        if d.startswith("epoch=") and os.path.isdir(os.path.join(save_dir, d))
    ]
    assert len(ckpts) == 1  # best-only policy intact across the crash


def test_two_process_chunked_overlap_pretrain(tmp_path):
    """parallel.comm_overlap=chunked under 2 real processes: every ppermute
    hop of the chunked int8 ring crosses the process boundary (4+4 devices),
    so a rank bookkeeping bug in the ring schedule cannot hide behind
    single-process device shuffling. The run must train to a checkpoint,
    not just rendezvous. (The state is born at its shardings by one compiled
    program: a plain device_put of the state pytree onto a non-addressable
    sharding runs per-leaf equality-check broadcasts that crash gloo's TCP
    pairs at this device count — pair.cc enforce op.preamble.length <=
    op.nbytes.)"""
    save_dir = tmp_path / "ckpts"
    result = _run_launcher(
        [
            "--nprocs", "2",
            "--devices-per-proc", "4",
            "-m", "simclr_tpu.main",
            "parallel.grad_allreduce=int8",
            "parallel.comm_overlap=chunked",
            "parallel.comm_chunks=3",
            "parameter.epochs=1",
            "experiment.batches=8",
            "parameter.warmup_epochs=0",
            "experiment.save_model_epoch=1",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            f"experiment.save_dir={save_dir}",
        ],
        timeout=600,
    )
    assert result.returncode == 0, result.stderr[-2000:]
    assert (save_dir / "epoch=1-cifar10").exists(), result.stderr[-2000:]
    assert result.stderr.count("Epoch:1/1") == 1, result.stderr[-2000:]


def test_multihost_dryrun_script_two_process_parity(tmp_path):
    """scripts/multihost_dryrun.py end to end: one payload line claiming a
    REAL 2-process rendezvous whose chunked-ring checksum bitwise-matches
    the single-process reference."""
    import json

    env = _launcher_env()
    result = subprocess.run(
        [sys.executable, "scripts/multihost_dryrun.py"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=420,
    )
    assert result.returncode == 0, result.stderr[-2000:]
    payload_lines = [
        l for l in result.stdout.splitlines() if l.startswith("{")
    ]
    assert len(payload_lines) == 1, result.stdout
    payload = json.loads(payload_lines[0])
    assert payload.get("process_count") == 2, payload
    assert payload.get("parity") is True, payload
    assert "error" not in payload, payload


def test_coordinator_timeout_env_fails_fast():
    """JAX_COORDINATOR_TIMEOUT_S caps the rendezvous wait: a half-configured
    pod (coordinator never comes up) must fail in seconds, not hang out
    jax's 5-minute default."""
    import time

    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS",)}
    env["JAX_PLATFORMS"] = "cpu"
    # a bound-but-never-accepting coordinator port: connection is refused or
    # times out, never completes rendezvous
    env["JAX_COORDINATOR_ADDRESS"] = _coordinator()
    env["JAX_NUM_PROCESSES"] = "2"
    env["JAX_PROCESS_ID"] = "0"
    env["JAX_COORDINATOR_TIMEOUT_S"] = "5"
    t0 = time.monotonic()
    result = subprocess.run(
        [
            sys.executable,
            "-c",
            "from simclr_tpu.parallel.multihost import maybe_initialize_multihost;"
            "maybe_initialize_multihost()",
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=240,
    )
    elapsed = time.monotonic() - t0
    assert result.returncode != 0
    # two observed failure shapes, both diagnosable: jax raises and our
    # wrapper names the fix ("rendezvous" in the message), or XLA's
    # distributed client LOG(FATAL)s on the RegisterTask deadline
    # (DEADLINE_EXCEEDED) before the Python exception path is reached.
    assert (
        "rendezvous" in result.stderr or "DEADLINE_EXCEEDED" in result.stderr
    ), result.stderr[-2000:]
    assert elapsed < 120, f"timeout env ignored: took {elapsed:.0f}s"

    # a malformed value must be rejected loudly, not silently ignored
    env["JAX_COORDINATOR_TIMEOUT_S"] = "soon"
    result = subprocess.run(
        [
            sys.executable,
            "-c",
            "from simclr_tpu.parallel.multihost import maybe_initialize_multihost;"
            "maybe_initialize_multihost()",
        ],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert result.returncode != 0
    assert "JAX_COORDINATOR_TIMEOUT_S" in result.stderr


def test_fail_fast_on_child_failure():
    result = _run_launcher(
        [
            "--nprocs", "2",
            "--devices-per-proc", "1",
            "-m", "simclr_tpu.main",
            "parameter.epochs=not_an_int",  # config validation fails in children
        ],
        timeout=180,
    )
    assert result.returncode != 0


def test_partial_multihost_env_fails_loudly():
    # JAX_NUM_PROCESSES without a coordinator address must raise, not
    # silently degrade into an uncoordinated single-process run
    env = {k: v for k, v in os.environ.items() if k not in ("XLA_FLAGS",)}
    env["JAX_NUM_PROCESSES"] = "2"
    env.pop("JAX_COORDINATOR_ADDRESS", None)
    env["JAX_PLATFORMS"] = "cpu"
    result = subprocess.run(
        [
            sys.executable,
            "-c",
            "from simclr_tpu.parallel.multihost import maybe_initialize_multihost;"
            "maybe_initialize_multihost()",
        ],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert result.returncode != 0
    assert "rendezvous" in result.stderr


def test_proc_id_mode_runs_module_in_process(tmp_path):
    # single-process "multi-host" invocation: --proc-id 0 of 1 execs the module
    save_dir = tmp_path / "ckpts"
    result = _run_launcher(
        [
            "--nprocs", "1",
            "--proc-id", "0",
            "--devices-per-proc", "2",
            "-m", "simclr_tpu.main",
            "parameter.epochs=1",
            "experiment.batches=8",
            "parameter.warmup_epochs=0",
            "experiment.save_model_epoch=1",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=32",
            f"experiment.save_dir={save_dir}",
        ]
    )
    assert result.returncode == 0, result.stderr[-2000:]
    assert (save_dir / "epoch=1-cifar10").exists()


def test_four_process_pretrain_and_resumed_eval(tmp_path):
    """VERDICT r4 item 7 — the closest attainable rehearsal of the v4-32
    multi-host contract: 4 real processes x 2 devices each.

    Covers, at a process count where rank bookkeeping bugs can't hide as
    binary symmetry: per-process row blocks of every batch, per-epoch
    checkpointing, then an eval sweep on the shared
    filesystem interrupted and RESUMED — the skipped checkpoint carried
    verbatim from the results blob, the fingerprint surviving, only the
    missing checkpoint recomputed by all four processes in lockstep."""
    import json

    save_dir = tmp_path / "ckpts"
    result = _run_launcher(
        [
            "--nprocs", "4",
            "--devices-per-proc", "2",
            "-m", "simclr_tpu.main",
            "parameter.epochs=2",
            "experiment.batches=4",
            "parameter.warmup_epochs=0",
            "experiment.save_model_epoch=1",
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            f"experiment.save_dir={save_dir}",
        ],
        timeout=1800,  # four step compiles share the single host core
    )
    assert result.returncode == 0, result.stderr[-2000:]
    for epoch in (1, 2):
        assert (save_dir / f"epoch={epoch}-cifar10").exists(), (
            result.stderr[-2000:]
        )
    assert result.stderr.count("Epoch:2/2") == 1, result.stderr[-2000:]

    eval_dir = tmp_path / "eval"
    eval_args = [
        "--nprocs", "4",
        "--devices-per-proc", "2",
        "-m", "simclr_tpu.eval",
        "parameter.classifier=centroid",
        "experiment.batches=4",
        "experiment.synthetic_data=true",
        "experiment.synthetic_size=64",
        f"experiment.target_dir={save_dir}",
        f"experiment.save_dir={eval_dir}",
    ]
    result = _run_launcher(eval_args, timeout=1800)
    assert result.returncode == 0, result.stderr[-2000:]
    results_path = eval_dir / "results.json"
    blob = json.loads(results_path.read_text())
    assert set(blob) == {SWEEP_CONFIG_KEY, "epoch=1-cifar10", "epoch=2-cifar10"}

    # simulate a crash after checkpoint 1 on the shared FS, then resume
    del blob["epoch=2-cifar10"]
    blob["epoch=1-cifar10"] = {"sentinel": 4.0}
    results_path.write_text(json.dumps(blob))
    result = _run_launcher(eval_args + ["experiment.resume=true"], timeout=1800)
    assert result.returncode == 0, result.stderr[-2000:]
    resumed = json.loads(results_path.read_text())
    assert resumed["epoch=1-cifar10"] == {"sentinel": 4.0}  # carried, not redone
    assert 0.0 <= resumed["epoch=2-cifar10"]["val_acc"] <= 1.0  # recomputed
    assert resumed[SWEEP_CONFIG_KEY]["classifier"] == "centroid"
