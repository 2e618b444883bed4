"""BENCHMARK.json against the contract's limits, and every name in it
against the files the harness finds by that name."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmarks")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


MANIFEST = load(os.path.join(ROOT, "BENCHMARK.json"))
REHEARSAL = load(os.path.join(os.path.dirname(os.path.abspath(__file__)), "rehearsal_manifest.json"))
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def test_top_level_keys_and_limits():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer",
    }
    assert MANIFEST["paths"] == ["benchmarks", "tests/benchmarks"]
    assert MANIFEST["command"] == ["python3", "benchmarks/run.py"]
    assert isinstance(MANIFEST["run_seconds"], int) and 1 <= MANIFEST["run_seconds"] <= 51
    # 2 + 14 x 24 runs of run_seconds + 60 s, 180 s a cell to compile, 1200 spare
    assert (2 + 14 * 24) * (MANIFEST["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(MANIFEST["workloads"]) <= 24 and 1 <= len(MANIFEST["configs"]) <= 24
    assert 1 <= len(MANIFEST["end_to_end"]) <= 16 and 1 <= len(MANIFEST["per_layer"]) <= 128


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    end_to_end = metric in MANIFEST["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if end_to_end else {"layer", "moves"}
    )
    assert set(metric) - {"workloads"} == keys
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    if end_to_end:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
    else:
        assert metric["moves"] in {m["name"] for m in MANIFEST["end_to_end"]}
        assert 1 <= len(metric["layer"]) <= 200 and "\n" not in metric["layer"]
    for cell in metric.get("workloads", []):
        assert cell in {w["name"] for w in MANIFEST["workloads"]}


def test_names_are_unique_and_setup_s_is_there():
    for group in (METRICS, MANIFEST["workloads"], MANIFEST["configs"]):
        names = [x["name"] for x in group]
        assert len(names) == len(set(names))
    assert "setup_s" in {m["name"] for m in MANIFEST["end_to_end"]}
    pairs = [(w["config"], w["traffic"]) for w in MANIFEST["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("config", MANIFEST["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    for key in ("source", "why"):
        assert 1 <= len(config[key]) <= 200 and "\n" not in config[key] and "\t" not in config[key]
    assert config["file"] == f"benchmarks/configs/{config['name']}.json"
    body = load(os.path.join(ROOT, config["file"]))
    assert body["name"] == config["name"]
    assert body["reduced"] == config["reduced"] == []
    assert isinstance(body["overrides"], list) and "assumed" in body
    assert config["name"] in {w["config"] for w in MANIFEST["workloads"]}
    # a configuration pins the job, never a path switch of the program
    for override in body["overrides"]:
        assert not override.startswith(("runtime.", "loss.", "model.", "parallel."))


@pytest.mark.parametrize(
    "cell", MANIFEST["workloads"] + REHEARSAL["workloads"], ids=lambda w: w["name"]
)
def test_every_file_a_cell_names_is_found(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key])
    assert cell["chips"] in (1, 4)
    assert 1 <= len(cell["why"]) <= 200 and "\n" not in cell["why"]
    assert os.path.isfile(os.path.join(BENCH, "configs", cell["config"] + ".json"))
    mix = load(os.path.join(BENCH, "traffic", cell["traffic"] + ".json"))
    assert mix["name"] == cell["traffic"]
    assert os.path.isfile(os.path.join(BENCH, "runners", mix["runner"] + ".py"))
    assert isinstance(mix["overrides"], list) and mix["settle_s"] > 0 and mix["entry"]
    for override in mix["overrides"]:
        assert not override.startswith(("runtime.", "loss.", "model.", "parallel."))
    data = load(os.path.join(BENCH, "cells", cell["name"] + ".json"))
    assert data["flops_per_image"] > 0 and data["flops_per_image_from"]


def test_the_toy_mix_is_in_no_cell_of_the_benchmark():
    assert "rehearsal-toy" not in {w["traffic"] for w in MANIFEST["workloads"]}


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = [w for w in MANIFEST["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(MANIFEST["workloads"]) // 4)


@pytest.mark.parametrize("group,directory", [("end_to_end", "end_to_end"), ("per_layer", "layer_metrics")])
def test_every_metric_has_a_reader_of_its_name(group, directory):
    for manifest in (MANIFEST, REHEARSAL):
        for metric in manifest[group]:
            path = os.path.join(BENCH, directory, metric["name"] + ".py")
            assert os.path.isfile(path), path
            with open(path) as f:
                assert "def read(run" in f.read()


def test_every_cell_reports_setup_another_end_to_end_metric_and_a_layer_metric():
    for cell in MANIFEST["workloads"]:
        def reported(group):
            return [m["name"] for m in MANIFEST[group]
                    if cell["name"] in m.get("workloads", [cell["name"]])]
        assert "setup_s" in reported("end_to_end") and len(reported("end_to_end")) >= 2
        assert reported("per_layer")
        for metric in MANIFEST["per_layer"]:
            if metric["name"] in reported("per_layer"):
                assert metric["moves"] in reported("end_to_end")


def test_files_under_paths_are_named_from_the_allowed_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in MANIFEST["paths"]:
        for root, dirs, files in os.walk(os.path.join(ROOT, base)):
            dirs[:] = [d for d in dirs if d not in (".work", "__pycache__")]
            for name in files:
                rel = os.path.relpath(os.path.join(root, name), ROOT)
                assert allowed.match(rel), rel


def test_peaks_table_names_its_source():
    peaks = load(os.path.join(BENCH, "peaks.json"))["chips"]
    from simclr_tpu.obs.peaks import CHIP_PEAKS

    for kind, row in peaks.items():
        assert row["source"]
        assert row["bf16_flops_per_s"] == CHIP_PEAKS[kind].bf16_flops_per_s
