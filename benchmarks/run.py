"""The benchmark's command: runs one cell of ``BENCHMARK.json`` once.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

prints as its last line one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace 0``,
its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``. This file knows no cell, model or metric by name: a cell is an
entry of the manifest, its configuration, traffic mix and cell data are the
JSON files of those names, the mix names its runner, and each metric is read
by the file of its name (README.md).
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # before the heavy imports: set-up counts them

import argparse
import dataclasses
import importlib.util
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, ROOT)  # the program and this package, from the checkout

from benchmarks import reduce, xplane  # noqa: E402


def load_json(*parts: str) -> dict:
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def load_module(*parts: str):
    """A Python file under ``benchmarks/``, by path: a metric's name may hold
    ``.`` and ``-``, which a module name may not."""
    path = os.path.join(BENCH_DIR, *parts)
    spec = importlib.util.spec_from_file_location(
        "bench_" + "_".join(parts).replace(".", "_").replace("-", "_"), path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def find_cell(manifest: dict, name: str) -> dict:
    """The workload's manifest entry with its three data files read in."""
    for entry in manifest["workloads"]:
        if entry["name"] == name:
            return {
                **entry,
                "config": load_json("configs", entry["config"] + ".json"),
                "traffic": load_json("traffic", entry["traffic"] + ".json"),
                "data": load_json("cells", name + ".json"),
            }
    raise SystemExit(f"no workload {name!r} in the manifest")


def read_metrics(manifest: dict, group: str, directory: str, run: dict) -> dict:
    """``{name: {"value", "unit"}}`` for the group's metrics that this cell
    reports; a reader that finds nothing to read leaves its metric out."""
    out = {}
    for metric in manifest[group]:
        if run["cell"]["name"] not in metric.get("workloads", [run["cell"]["name"]]):
            continue
        value = load_module(directory, metric["name"] + ".py").read(run)
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out


def read_trace(run: dict) -> dict | None:
    """The traced run's device planes, cut to the capture's last ``trace_s``
    seconds, and the wall clock at the trace's time 0; ``None`` where no
    trace was written."""
    path = xplane.find_xplane(run["trace_dir"]) if run.get("trace_dir") else None
    if path is None:
        return None
    devices, start_wall_ns = xplane.read(path)
    devices, window = xplane.last_seconds(devices, run["trace_s"])
    return {
        "path": path,
        "devices": devices,
        "window_ns": window,
        "start_wall_ns": start_wall_ns,
    }


def breakdown(run: dict) -> dict | None:
    """The ten kinds of device op that took most time, and device 0's five
    longest idle gaps, each named by the trainer's event nearest its start
    (within a quarter second), else ``none``."""
    trace = run.get("trace")
    if not trace or not trace["devices"]:
        return None
    device = trace["devices"][0]
    gaps = []
    for start_ns, duration_ns in xplane.idle_gaps(device):
        what = "unplaced"
        if trace["start_wall_ns"] is not None:
            wall = (start_ns + trace["start_wall_ns"]) / 1e9
            near = min(
                run["events"], key=lambda e: abs(e["time"] - wall), default=None
            )
            what = "none"
            if near is not None and abs(near["time"] - wall) < 0.25:
                what = str(near["event"])
        gaps.append([what, duration_ns / 1e9])
    return {
        "device_ops": [[name, s] for name, s in xplane.op_table(device)],
        "idle_gaps": gaps,
    }


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    parser.add_argument(
        "--rehearse", metavar="MANIFEST", default=None,
        help="take the cell from this manifest instead of BENCHMARK.json and "
        "accept the CPU: for rehearsals and tests, never correct",
    )
    args = parser.parse_args(argv)
    with open(args.rehearse or os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = find_cell(manifest, args.workload)
    runner = load_module("runners", cell["traffic"]["runner"] + ".py")
    run = runner.run(cell, {
        "seed": args.seed,
        "seconds": args.seconds,
        "trace": bool(args.trace),
        "rehearse": args.rehearse is not None,
        "t_start": T_START,
        # a fixed place inside the checkout, emptied by the next run
        "work_dir": os.path.join(BENCH_DIR, ".work", cell["name"]),
    })
    reasons = reduce.verdict(run)
    if args.rehearse:
        reasons.append("a rehearsal")
    attempted, failed = reduce.attempted_and_failed(run)
    device = {**run["device"], "memory_peak_bytes": run["memory_peak_bytes"]}
    result = {"attempted": attempted, "failed": failed}
    # for the reader of a log; the driver reads only the last line
    notes = {
        "workload": cell["name"], "seed": args.seed, "why_not_correct": reasons,
        "measured": reduce.measured(run),
        # seconds after the first heartbeat: each epoch event seen, SIGTERM
        "epochs_seen_s": [
            round(e["seen"] - run["t_first_beat"], 3)
            for e in reduce.events_of(run, "epoch") if run.get("t_first_beat")
        ],
        "sigterm_s": (
            round(run["t_sigterm"] - run["t_first_beat"], 3)
            if run.get("t_sigterm") and run.get("t_first_beat") else None
        ),
        "compile": [
            {k: e.get(k) for k in ("name", "seconds", "cache_hit")}
            for e in reduce.events_of(run, "compile")
        ],
        "epoch_losses": [e["loss"] for e in reduce.events_of(run, "epoch")],
        "conductor_error": run.get("conductor_error"),
    }
    if args.trace:
        run["trace"] = read_trace(run)
        both = None
        if run["trace"]:
            both = xplane.busy_and_window_s(
                run["trace"]["devices"], run["trace"]["window_ns"]
            )
        if both is None:
            reasons.append("the trace shows no op on any device")
        else:
            device["busy_s"], device["window_s"] = both
        result["metrics"] = read_metrics(manifest, "per_layer", "layer_metrics", run)
        parts = breakdown(run)
        if parts:
            result["breakdown"] = parts
            # for the log: the collectives device 0's trace holds, same table
            first = run["trace"]["devices"][0]
            notes["collective_ops"] = xplane.op_table(
                dataclasses.replace(first, ops=xplane.collective_events(first)), top=8
            )
    else:
        result["metrics"] = read_metrics(manifest, "end_to_end", "end_to_end", run)
    result = {"correct": not reasons, **result, "device": device}
    print(json.dumps(notes))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
