"""``chip_smoke.py`` on a machine without a chip: it must refuse, by name.

The smoke itself runs through the chip tool (README, "Tests"). What the CPU
suite can pin is its contract at the edges: no accelerator means a non-zero
exit and no result line, the parent process stays off JAX, and the phases
themselves hold together at toy sizes behind ``--cpu-dry-run``.
"""

import ast
import json
import os
import shutil
import subprocess
import sys


REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO_ROOT, "chip_smoke.py")


def _run(args, cwd, env_extra=None, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env, text=True,
        capture_output=True, timeout=timeout,
    )


def _result_lines(stdout: str) -> list[dict]:
    out = []
    for line in stdout.splitlines():
        try:
            parsed = json.loads(line)
        except ValueError:
            continue
        if isinstance(parsed, dict) and "ok" in parsed:
            out.append(parsed)
    return out


def test_parent_process_imports_no_jax():
    """A parent that touched JAX would hold the chip its children need: at
    module level the smoke may import neither jax nor the package."""
    with open(SMOKE) as f:
        tree = ast.parse(f.read())
    top_level = set()
    for node in tree.body:
        if isinstance(node, ast.Import):
            top_level.update(alias.name.split(".")[0] for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            top_level.add((node.module or "").split(".")[0])
    assert not top_level & {"jax", "jaxlib", "flax", "simclr_tpu"}


def test_no_accelerator_is_a_failure_that_names_the_platform(tmp_path):
    checkout = tmp_path / "checkout"
    shutil.copytree(
        REPO_ROOT, checkout,
        ignore=shutil.ignore_patterns(
            ".git", "results", "chiprun_out", ".jax_cache", "__pycache__",
            ".chip_smoke_work", "tests", "docs",
        ),
    )
    proc = _run(["chip_smoke.py"], cwd=checkout)
    assert proc.returncode != 0
    assert _result_lines(proc.stdout) == []
    assert "platform='cpu'" in proc.stderr
    # it stopped at the first child: no trainer was started, nothing is left
    assert not (checkout / ".chip_smoke_work").exists()
    assert sorted(os.listdir(checkout / "chiprun_out" / "chip_smoke")) == [
        "checks.log", "summary.json"
    ]


def test_alone_in_a_directory_is_a_failure(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run(["chip_smoke.py"], cwd=tmp_path)
    assert proc.returncode != 0
    assert _result_lines(proc.stdout) == []


def test_cpu_dry_run_passes_every_phase(tmp_path):
    """The smoke's phases at toy sizes on two virtual CPU devices: train,
    resume from the compile cache, fused kernels (interpreted), serve with
    two replicas and a two-shard corpus. Prints no ``ok`` line."""
    checkout = tmp_path / "checkout"
    shutil.copytree(
        REPO_ROOT, checkout,
        ignore=shutil.ignore_patterns(
            ".git", "results", "chiprun_out", ".jax_cache", "__pycache__",
            ".chip_smoke_work", "tests", "docs",
        ),
    )
    proc = _run(
        ["chip_smoke.py", "--cpu-dry-run"], cwd=checkout,
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=2 "
                   "--xla_backend_optimization_level=0"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert _result_lines(proc.stdout) == []
    last = json.loads(proc.stdout.splitlines()[-1])
    assert last["dry_run"] is True and last["device"]["count"] == 2
    assert last["phases"] == [
        "checks", "train", "resume", "fused", "serve", "serve_restart"
    ]
    summary = json.loads(
        (checkout / "chiprun_out" / "chip_smoke" / "summary.json").read_text()
    )
    assert summary["claim"] is None
    assert summary["phases"]["resume"]["compile_cache"]["hits"] >= 1
    assert summary["compile_cache_dir"] == str(checkout / ".jax_cache")
    assert not (checkout / ".chip_smoke_work").exists()
