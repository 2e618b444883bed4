"""The traced rehearsal once more, with the per-layer metrics that read what
the trainer records inside itself: ``spans.jsonl`` and ``step_scopes.json``
are found in the run directory, the span readers read them, and the readers
that need a device plane report nothing on the CPU. A file and a manifest of
its own, so that it runs beside the other two rehearsals."""

import json
import os
import subprocess
import sys

from bm_rehearsal import HERE, ROOT, last_line

sys.path.insert(0, ROOT)

from benchmarks import spans  # noqa: E402

MANIFEST = os.path.join(HERE, "rehearsal_spans_manifest.json")
RUN_DIR = os.path.join(ROOT, "benchmarks", ".work", "rehearsal-spans", "run")


def test_traced_rehearsal_reads_the_trainers_spans():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=1 --xla_backend_optimization_level=0"
    )
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "rehearsal-spans", "--seed", str(2**31 + 29),
         "--seconds", "16.0", "--trace", "1", "--rehearse", MANIFEST],
        cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result, notes = last_line(lines), json.loads(lines[-2])
    assert notes["conductor_error"] is None
    metrics = result["metrics"]
    # what the spans give on any backend: boot, and the preemption's parts
    always = {"boot_s", "compile_s", "boot_runtime_s", "boot_data_s",
              "boot_state_s", "preempt_drain_s", "ckpt_save_s", "ckpt_digest_s"}
    # and over measured epochs, if the shared CPU left whole ones
    measured = {"step_ms", "boundary_host_ms", "input_wait_ms"} if notes["measured"] else set()
    assert always <= set(metrics) <= always | measured | {"step_ms", "boundary_host_ms", "input_wait_ms"}
    assert measured <= set(metrics)
    # nothing from the CPU under a device metric's name: no scope's
    # milliseconds, no idle share
    assert not {"augment_ms", "fwd_ms", "bwd_ms", "loss_ms", "optimizer_ms",
                "grad_reduce_ms", "idle_in_steps_pct"} & set(metrics)
    boot = sum(metrics[m]["value"] for m in ("boot_runtime_s", "boot_data_s", "boot_state_s"))
    assert boot <= metrics["boot_s"]["value"] + 0.5

    # the run directory holds the two files, and the spans the readers name
    headers, recorded = spans.read_file(os.path.join(RUN_DIR, "spans.jsonl"))
    names = {s["name"] for s in recorded}
    assert {"boot/runtime", "boot/data", "boot/state", "boot/build_step",
            "boot/compile", "epoch", "dispatch", "beat", "input_wait", "gather",
            "device_put", "boundary", "boundary/loss_fetch", "boundary/telemetry",
            "boundary/log", "boundary/event", "boundary/results",
            "boundary/shuffle", "boundary/first_batch", "preempt",
            "preempt/notice", "checkpoint", "checkpoint/drain",
            "checkpoint/save", "checkpoint/digest", "checkpoint/sidecar",
            "preempt/event", "preempt/beat", "preempt/flush"} <= names
    assert headers and all(h["pid"] == headers[0]["pid"] for h in headers)
    assert not any(s.get("open") for s in recorded)
    with open(os.path.join(RUN_DIR, "step_scopes.json")) as f:
        scopes = json.load(f)
    parts = {p for op_name in scopes.values() for p in op_name.replace("(", "/").replace(")", "/").split("/")}
    assert {"augment", "encoder", "head", "ntxent", "lars"} <= parts
    # a healthy run: the detector called no step slow
    with open(os.path.join(RUN_DIR, "events.jsonl")) as f:
        assert "slow_step" not in {json.loads(line)["event"] for line in f}
