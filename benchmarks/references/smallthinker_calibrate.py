"""Reads, for a cell of ``smallthinker-21ba3b-enc`` and many seeds in one
process, what a limit is set between: the program's distance from the
float32 reference (the lower reading), and the control's and each planted
fault's (the upper readings). ``calibrate.py`` for the reference
``smallthinker_simclr``:

    python3 benchmarks/references/smallthinker_calibrate.py --workload <cell> \
        --seeds 1,2,3 [--control-seeds 1] [--precisions float8] [--rows 0.5] \
        [--faults no_renorm,rope_on_global,no_window,router_normed,frozen] \
        [--override k=v ...] [--manifest <file>] [--out chiprun_out/calibrate]

Each seed goes through the trainer's own entry point with the cell's
overrides and leaves it after the third step
(``first_steps.StopAfterCapture``). For the seeds of ``--control-seeds``
(default: the first) it then runs the reference in the program's place with
every product's inputs rounded to each of ``--precisions``, over the first
share of each view's rows for each of ``--rows`` (0.5: half of a step's
documents left out), and with each of ``--faults`` planted, and reads each
against the float32 reference as ``compare`` reads the program.
``--override`` adds trainer overrides after the cell's. One JSON
line per reading on standard output and in ``<out>/<cell>.jsonl`` (there
with every leaf's numbers); needs the cell's chips unless ``--manifest``
names a rehearsal manifest.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main() -> int:
    from benchmarks import run as bench
    from benchmarks.references import smallthinker_simclr as ref
    from benchmarks.references.first_steps import StopAfterCapture

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True, help="comma-separated")
    parser.add_argument("--control-seeds", default=None)
    parser.add_argument("--precisions", default="float8")
    parser.add_argument("--rows", default="0.5")
    parser.add_argument("--faults", default=",".join((*ref.FAULTS, "frozen")))
    parser.add_argument("--override", action="append", default=[])
    parser.add_argument("--manifest", default=os.path.join(ROOT, "BENCHMARK.json"))
    parser.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "calibrate"))
    args = parser.parse_args()
    with open(args.manifest) as f:
        cell = bench.find_cell(json.load(f), args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = (
        seeds[:1] if args.control_seeds is None
        else [int(s) for s in args.control_seeds.split(",") if s]
    )
    stand_ins = [(p, {"precision": p}) for p in args.precisions.split(",") if p]
    stand_ins += [(f"rows {r}", {"rows": float(r)}) for r in args.rows.split(",") if r]
    stand_ins += [(f, {"fault": f}) for f in args.faults.split(",") if f]
    rehearse = os.path.abspath(args.manifest) != os.path.join(ROOT, "BENCHMARK.json")
    train = bench.load_module("runners", cell["traffic"]["runner"] + ".py")
    device = train.check_devices(int(cell["chips"]), rehearse)
    entry = importlib.import_module(cell["traffic"]["entry"])
    os.makedirs(args.out, exist_ok=True)
    log = os.path.join(args.out, cell["name"] + ".jsonl")

    def emit(row: dict) -> None:
        row = {"cell": cell["name"], **row}
        with open(log, "a") as f:
            f.write(json.dumps(row) + "\n")
        print(json.dumps({k: v for k, v in row.items() if k != "leaves"}), flush=True)

    emit({"device": device})
    for seed in seeds:
        t0 = time.monotonic()
        seen = ref.HostFirstSteps(entry, keep=ref.STEPS, stop=True).install()
        try:
            entry.main([
                *cell["config"].get("overrides", []),
                *cell["traffic"].get("overrides", []),
                *args.override,
                f"parameter.seed={seed % (2**31 - 1)}",
                "experiment.save_dir="
                + os.path.join(ROOT, "benchmarks", ".work", "calibrate", cell["name"]),
            ])
        except StopAfterCapture:
            pass
        finally:
            seen.remove()
        gc.collect()
        t1 = time.monotonic()
        peak = train.memory_peak_bytes()
        theirs = ref.reference_trajectory(cell, seen)
        leaves: dict = {}
        found = ref.distances(cell, seen, ref.program_trajectory(seen), theirs, leaves)
        emit({
            "seed": seed, "what": "program", "errors": found, "leaves": leaves,
            "losses": theirs["loss"], "program_losses": ref.program_trajectory(seen)["loss"],
            "trainer_s": t1 - t0, "reference_s": time.monotonic() - t1,
            "memory_peak_bytes": peak,
        })
        if seed in controls:
            for what, how in stand_ins:
                t2 = time.monotonic()
                if how.get("fault") == "frozen":
                    other = ref.frozen(theirs, seen)
                else:
                    other = ref.reference_trajectory(cell, seen, **how)
                emit({"seed": seed, "what": what,
                      "errors": ref.distances(
                          cell, seen, other, theirs, fault=how.get("fault")),
                      "losses": other["loss"], "seconds": time.monotonic() - t2})
                del other
                gc.collect()
        del seen, theirs
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
