"""Shared by the two rehearsal tests: runs benchmarks/run.py as the driver
would, but on the CPU and on the toy manifest."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(workload: str, trace: int, rehearse: bool = True, seconds: float = 8.0):
    """``(return code, stdout lines)``. One CPU device, whatever the suite's
    conftest asked for, and BENCH_RUN set as the driver sets it."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=1 --xla_backend_optimization_level=0"
    )
    env["BENCH_RUN"] = "ignored"
    cmd = [
        sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
        "--workload", workload, "--seed", str(2**31 + 17),
        "--seconds", str(seconds), "--trace", str(trace),
    ]
    if rehearse:
        cmd += ["--rehearse", os.path.join(HERE, "rehearsal_manifest.json")]
    proc = subprocess.run(
        cmd, cwd=ROOT, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=240,
    )
    return proc.returncode, proc.stdout.strip().splitlines(), proc.stderr


def last_line(lines: list[str]) -> dict:
    result = json.loads(lines[-1])
    assert set(result) == KEYS, result.keys()  # breakdown only with device ops
    assert set(result["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    return result
