"""Runs one cell several times and prints each metric's spread: the tool for
setting a bound (two sets of six runs, the same seeds in both) and for a
look at a traced run. Each run is a process of its own, one after another,
and this parent never imports JAX, so it never holds the chip.

    python3 benchmarks/sets.py --workload <cell> --seeds 101,102,103 --sets 2 \
        [--seconds S] [--trace 0|1] [--out chiprun_out/bench]

A spread is the distance between the first and the third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values: list[float]) -> float | None:
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--sets", type=int, default=1)
    parser.add_argument("--seconds", type=float, default=manifest["run_seconds"])
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "bench"))
    args = parser.parse_args()
    os.makedirs(args.out, exist_ok=True)
    log_path = os.path.join(args.out, f"{args.workload}.trace{args.trace}.jsonl")
    seeds = [int(s) for s in args.seeds.split(",")]
    by_set: list[dict[str, list[float]]] = []
    for set_index in range(args.sets):
        values: dict[str, list[float]] = {}
        for seed in seeds:
            t0 = time.monotonic()
            proc = subprocess.run(
                [*manifest["command"], "--workload", args.workload, "--seed", str(seed),
                 "--seconds", str(args.seconds), "--trace", str(args.trace)],
                cwd=ROOT, stdout=subprocess.PIPE, text=True,
            )
            wall = time.monotonic() - t0
            lines = proc.stdout.strip().splitlines()
            row = {"set": set_index, "seed": seed, "rc": proc.returncode, "wall_s": wall}
            if proc.returncode == 0 and lines:
                row["result"] = json.loads(lines[-1])
                row["notes"] = lines[-2] if len(lines) > 1 else None
                for name, metric in row["result"]["metrics"].items():
                    values.setdefault(name, []).append(metric["value"])
            with open(log_path, "a") as f:
                f.write(json.dumps(row) + "\n")
            print(json.dumps(row), flush=True)
            if "result" not in row:  # a fault: the next run would only repeat it
                return 1
        by_set.append(values)
    for set_index, values in enumerate(by_set):
        for name, vals in values.items():
            print(f"set {set_index} {name}: n={len(vals)} median={statistics.median(vals)!r} "
                  f"spread={spread(vals)!r} values={vals!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
