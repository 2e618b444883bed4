"""The counter the chunked pair buffer brought (``expert_chunks_per_call``,
``simclr_tpu/models/experts.py``) through the harness: the toy cell of
``rehearsal_st_manifest.json`` under a name and a work directory of its own
(``rehearsal-st-chunks``, so that it shares nothing with the tests that run
``rehearsal-st`` beside it), with the metric's entry as ``BENCHMARK.json``
has it; and the reader alone on records with and without the counter. About
a minute."""

import json
import os
import subprocess
import sys

from bm_rehearsal import last_line
from bm_st_rehearsal import LONG_S, MANIFEST, ROOT, _env

sys.path.insert(0, ROOT)

from benchmarks.run import load_module  # noqa: E402

METRIC = "expert_chunks_per_call"
CELL = "rehearsal-st-chunks"


def manifest_with_the_counter(path) -> str:
    with open(MANIFEST) as f:
        manifest = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(m for m in json.load(f)["per_layer"] if m["name"] == METRIC)
    manifest["workloads"][0]["name"] = CELL
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [CELL]
    manifest["per_layer"].append({**entry, "workloads": [CELL]})
    with open(path, "w") as f:
        json.dump(manifest, f)
    return str(path)


def test_a_traced_rehearsal_reads_the_chunks_a_call_ran(tmp_path):
    manifest = manifest_with_the_counter(tmp_path / "manifest.json")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 29), "--seconds", str(LONG_S), "--trace", "1",
         "--rehearse", manifest],
        cwd=ROOT, env=_env(), stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True, timeout=400,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    metrics = last_line(proc.stdout.strip().splitlines())["metrics"]
    # 256 pairs a view at the toy sizes: one chunk of all rows, which every
    # call of every layer ran; and the counters beside it are still read
    assert metrics[METRIC] == {"value": 1.0, "unit": "ratio"}
    assert metrics["expert_load_max_over_mean"]["value"] >= 1.0


def record(**counters) -> dict:
    return {
        "cell": {"name": "st21b-docs-n128-r8k-1chip"},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "events": [
            {"event": "epoch", "step": 6 * n, "loss": 5.5, "seen": 3.0 + 7.0 * n,
             **{k: v[n - 1] for k, v in counters.items()}}
            for n in (1, 2, 3)
        ],
        "t_first_beat": 1.0, "t_sigterm": 40.0, "run_dir": None, "trace": None,
    }


def test_the_reader_takes_the_mean_of_the_windows_events_or_nothing():
    read = load_module("layer_metrics", METRIC + ".py").read
    assert read(record(expert_chunks_per_call=[1.0, 1.25, 1.0])) == (1.0 + 1.25 + 1.0) / 3
    # the parent's program writes no such counter: nothing, and no error
    assert read(record(held_pairs=[196608, 196000, 197000])) is None
