#!/usr/bin/env python
"""Observability end-to-end smoke (docs/OBSERVABILITY.md).

Spawns a REAL training run (`python -m simclr_tpu.main`) with the telemetry
exporter enabled on an ephemeral port, then — from the outside, pure stdlib,
no jax in this process — waits for the ready file, scrapes ``GET /metrics``
until the ``simclr_train_imgs_per_sec`` gauge goes positive (proof the
exporter is publishing LIVE epoch telemetry, not a dead registry), reads
``GET /healthz``, exercises one on-demand profiler capture
(``POST /debug/trace``), and finally SIGTERMs the run — which must land a
preempt checkpoint and exit through the 0/75 contract.

The full /metrics payload is printed so the log keeps the metric catalog.

    python scripts/obs_smoke.py [--timeout 600] [-- override ...]
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

GAUGE = "simclr_train_imgs_per_sec"
# live HBM accounting (obs/device.py): at least one gauge with this prefix
# must appear in the final payload on EVERY backend (the high-watermark
# gauge renders even when the allocator reports no stats)
HBM_PREFIX = "simclr_train_hbm_"
# SIGTERM lands the preempt path: EXIT_PREEMPTED (75) or 0 if the run had
# already finished — both are clean shutdowns (docs/FAULT_TOLERANCE.md)
OK_EXITS = (0, 75)


def _get(url: str, timeout: float = 10.0) -> str:
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.read().decode()


def _gauge_value(metrics_text: str, name: str) -> float | None:
    for line in metrics_text.splitlines():
        if line.startswith(name + " "):
            return float(line.split()[1])
    return None


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument(
        "--timeout", type=float, default=600.0,
        help="overall budget in seconds (covers the first compile)")
    parser.add_argument(
        "--save-dir", default=None,
        help="run directory (default: a fresh tempdir)")
    parser.add_argument(
        "overrides", nargs="*",
        help="extra config overrides appended to the child command")
    args = parser.parse_args(argv)

    save_dir = args.save_dir or tempfile.mkdtemp(prefix="obs_smoke_")
    ready = os.path.join(save_dir, "telemetry_ready.json")
    cmd = [
        sys.executable, "-m", "simclr_tpu.main",
        # small but long enough that the run is still alive while we scrape
        "parameter.epochs=50", "parameter.warmup_epochs=0",
        "parameter.num_workers=2",
        # batches such that 1024 synthetic rows still give whole epochs on
        # any device count up to 8 (cf. the supervisor_smoke stage)
        "experiment.batches=128",
        "experiment.synthetic_data=true", "experiment.synthetic_size=1024",
        "experiment.save_model_epoch=1000",
        f"experiment.save_dir={save_dir}",
        f"telemetry.ready_file={ready}",
        *args.overrides,
    ]
    print("obs_smoke: spawning", " ".join(cmd), flush=True)
    child = subprocess.Popen(cmd)
    deadline = time.time() + args.timeout
    base = None
    metrics_text = ""
    ok = False
    try:
        # 1. ready file → exporter address
        while time.time() < deadline and base is None:
            if child.poll() is not None:
                print(f"obs_smoke: child died early rc={child.returncode}")
                return 1
            try:
                with open(ready) as f:
                    info = json.load(f)
                base = f"http://{info['host']}:{info['port']}"
            except (OSError, ValueError, KeyError):
                time.sleep(0.5)
        if base is None:
            print("obs_smoke: exporter never published its ready file")
            return 1
        print(f"obs_smoke: exporter up at {base}", flush=True)

        # 2. scrape until the throughput gauge proves live epoch telemetry
        while time.time() < deadline:
            if child.poll() is not None:
                print(f"obs_smoke: child died early rc={child.returncode}")
                return 1
            try:
                metrics_text = _get(base + "/metrics")
            except (urllib.error.URLError, OSError):
                time.sleep(1.0)
                continue
            value = _gauge_value(metrics_text, GAUGE)
            if value is not None and value > 0:
                ok = True
                print(f"obs_smoke: {GAUGE} {value:.1f}", flush=True)
                break
            time.sleep(1.0)
        if not ok:
            print(f"obs_smoke: {GAUGE} never went positive within budget")
            return 1

        # 2b. live HBM accounting must be present on every backend: the
        # high-watermark gauge renders unconditionally (obs/device.py), so
        # a payload with no simclr_train_hbm_ line means the DeviceMonitor
        # never attached
        if not any(
            line.startswith(HBM_PREFIX) for line in metrics_text.splitlines()
        ):
            print(f"obs_smoke: no {HBM_PREFIX}* gauge in /metrics")
            return 1

        # 3. healthz carries the same snapshot that rides heartbeat.json
        print("obs_smoke: /healthz", _get(base + "/healthz"), flush=True)

        # 3b. fleet merge: a FleetCollector pointed at the same ready file
        # (exactly what the supervisor runs with telemetry.fleet=true) must
        # re-serve the live exporter's samples under the fleet namespace
        # with a host label. fleet.py is stdlib-only, so this process still
        # never imports jax.
        from simclr_tpu.obs.fleet import FleetCollector

        collector = FleetCollector(
            save_dir, nprocs=1, train_ready_file=ready, poll_s=60.0,
        )
        try:
            collector.scrape_once()
            fleet_text = _get(
                f"http://127.0.0.1:{collector.port}/metrics"
            )
            fleet_line = next(
                (
                    line
                    for line in fleet_text.splitlines()
                    if line.startswith("simclr_fleet_imgs_per_sec{")
                ),
                None,
            )
            if fleet_line is None:
                print("obs_smoke: fleet merge missing the throughput gauge")
                return 1
            print(f"obs_smoke: fleet {fleet_line}", flush=True)
        finally:
            collector.close()

        # 4. one on-demand profiler capture (best-effort: trace support
        # varies by backend, so a failure here warns instead of failing).
        # stop_trace waits out the in-flight step, so the HTTP timeout must
        # cover a whole step time, not just the requested capture window.
        try:
            req = urllib.request.Request(
                base + "/debug/trace?ms=300", method="POST")
            with urllib.request.urlopen(req, timeout=120) as r:
                resp = json.loads(r.read().decode())
            trace_dir = resp.get("trace_dir", "")
            entries = os.listdir(trace_dir) if os.path.isdir(trace_dir) else []
            print(f"obs_smoke: trace -> {trace_dir} ({len(entries)} entries)")
        except Exception as e:  # noqa: BLE001 - diagnostic path only
            print(f"obs_smoke: WARNING trace capture failed: {e}")
    finally:
        if child.poll() is None:
            child.send_signal(signal.SIGTERM)
            try:
                child.wait(timeout=120)
            except subprocess.TimeoutExpired:
                child.kill()
                child.wait()
    print(f"obs_smoke: child exit rc={child.returncode}")
    if child.returncode not in OK_EXITS:
        print(f"obs_smoke: unclean shutdown (expected rc in {OK_EXITS})")
        return 1
    # the catalog, for the log and the done-marker grep
    print("--- /metrics ---")
    print(metrics_text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
