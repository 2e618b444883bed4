"""Shared by the sequence encoder's rehearsal tests: ``benchmarks/run.py``
(or ``bm_fault_run.py`` in front of it) on the toy cell ``rehearsal-st`` of
``rehearsal_st_manifest.json``, on the CPU, and ``smallthinker_calibrate.py``
on the same cell."""

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
MANIFEST = os.path.join(HERE, "rehearsal_st_manifest.json")
CELL = "rehearsal-st"
LONG_S = 40.0


def _env() -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count=1 --xla_backend_optimization_level=0"
    )
    env["BENCH_RUN"] = "ignored"
    return env


def run_cell(trace: int, seconds: float = 10.0, fault: str | None = None):
    """``(return code, stdout lines, stderr)`` of one run of the toy cell."""
    command = [os.path.join(ROOT, "benchmarks", "run.py")]
    if fault:
        command = [os.path.join(HERE, "bm_fault_run.py"), fault]
    proc = subprocess.run(
        [sys.executable, *command, "--workload", CELL, "--seed", str(2**31 + 23),
         "--seconds", str(seconds), "--trace", str(trace), "--rehearse", MANIFEST],
        cwd=ROOT, env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=300,
    )
    lines = proc.stdout.strip().splitlines()
    # beside five other test workers the window can close before the three
    # steps the reference follows: no reading, so once more with a longer one
    if seconds < LONG_S and len(lines) > 1 and "steps to compare" in lines[-2]:
        return run_cell(trace, LONG_S, fault)
    return proc.returncode, lines, proc.stderr


def calibrate(*extra: str) -> dict:
    """``{what: errors}`` of one seed through ``smallthinker_calibrate.py``."""
    proc = subprocess.run(
        [sys.executable,
         os.path.join(ROOT, "benchmarks", "references", "smallthinker_calibrate.py"),
         "--workload", CELL, "--seeds", str(2**31 + 5), "--manifest", MANIFEST,
         "--out", os.path.join(ROOT, "benchmarks", ".work", "calibrate"), *extra],
        cwd=ROOT, env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = [json.loads(line) for line in proc.stdout.splitlines() if line.startswith("{")]
    return {row["what"]: row["errors"] for row in rows if "what" in row}
