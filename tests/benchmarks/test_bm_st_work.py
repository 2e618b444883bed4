"""``benchmarks/sequence_work.py`` against hand-worked cases, the new cell's
data against the program's experiment group, and the five readers on a run
record that holds none of what they read (a parent without the scopes and
counters: nothing, and no error)."""

import json
import os
import sys

import pytest
import yaml

from bm_rehearsal import ROOT

sys.path.insert(0, ROOT)

from benchmarks import sequence_work as work  # noqa: E402
from benchmarks.run import load_json, load_module  # noqa: E402

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# one layer of each kind at sizes a hand can follow
TOY = {
    "hidden_size": 8, "head_dim": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
    "num_hidden_layers": 2, "rope_layout": [0, 1, 1], "sliding_window_size": 3,
    "moe_ffn_hidden_size": 5, "moe_num_primary_experts": 2,
}


@pytest.mark.parametrize("length,window,pairs", [
    (1, None, 1), (4, None, 10), (4, 3, 9), (3, 3, 6), (5, 2, 9), (4608, 4096, 8390656 + 512 * 4096),
])
def test_allowed_pairs(length, window, pairs):
    assert work.allowed_pairs(length, window) == pairs
    brute = sum(1 for q in range(length) for k in range(q + 1) if window is None or q - k < window)
    assert length > 64 or brute == pairs


def test_attention_work_by_hand():
    # crops (4, 1), 2 rows: 10 tokens; per token q, k, v, o: 2 * 8 * 4 * (2 + 1 + 1 + 2) = 384
    # layer 0 (global): pairs 2 * (10 + 1) = 22; layer 1 (window 3): 2 * (9 + 1) = 20
    # per pair: heads 2 * 4 * d 4 = 32
    flops = 3 * ((10 * 384 + 22 * 32) + (10 * 384 + 20 * 32))
    parameters = 8 * 4 * 6 + 8  # q, k, v, o and the norm's scale
    activations = 10 * (2 * 4 * 8 + 2 * 2 * 4 * 6)
    got = work.attention_work(TOY, [4, 1], 2)
    assert got == {"flops": float(flops), "bytes": float(2 * (12 * parameters + 3 * activations))}


def test_experts_work_by_hand():
    got = work.experts_work(TOY, 7)
    assert got["flops"] == 3 * 7 * 3 * 2 * 8 * 5
    assert got["bytes"] == 12 * 2 * (2 * 3 * 8 * 5 + 8) + 3 * 7 * 2 * (2 * 8 + 4 * 5)


def test_roofline_takes_the_larger_bound():
    assert work.roofline_ms({"flops": 197e12, "bytes": 1.0}, PEAK) == pytest.approx(1e3)
    assert work.roofline_ms({"flops": 1.0, "bytes": 819e9}, PEAK) == pytest.approx(1e3)


def test_the_cells_work_is_what_perf_md_says():
    config = load_json("configs", "smallthinker-21ba3b-enc.json")
    mix = load_json("traffic", "pretrain-docs-n128-r8k.json")
    assert sum(mix["crop_lengths"]) == 8192 and len(mix["crop_lengths"]) == 64
    assert mix["rows_per_step"] * 64 == 2 * mix["batch_per_chip"]  # two views
    attention = work.attention_work(config, mix["crop_lengths"], mix["rows_per_step"])
    experts = work.experts_work(config, 32768 * 6 * 4 / 4)  # an even router's pairs
    assert attention["flops"] == pytest.approx(24.31e12, rel=1e-3)
    assert experts["flops"] == pytest.approx(6.958e12, rel=1e-3)
    # both compute-bound on the v5e
    for w in (attention, experts):
        assert w["flops"] / PEAK["bf16_flops_per_s"] > w["bytes"] / PEAK["hbm_bytes_per_s"]


def test_the_mix_and_the_configuration_say_what_the_program_runs():
    with open(os.path.join(ROOT, "simclr_tpu", "conf", "experiment", "smallthinker-docs.yaml")) as f:
        group = yaml.safe_load(f)["experiment"]
    mix = load_json("traffic", "pretrain-docs-n128-r8k.json")
    config = load_json("configs", "smallthinker-21ba3b-enc.json")
    assert sorted(group["crop_lengths"]) == sorted(mix["crop_lengths"])
    assert group["documents"] == config["recipe"]["rows"] and group["batches"] == mix["batch_per_chip"]
    from simclr_tpu.models.arch import SEQUENCE_ENCODERS

    spec = SEQUENCE_ENCODERS[group["base_cnn"]]
    assert (spec.hidden, spec.heads, spec.kv_heads, spec.head_dim) == (
        config["hidden_size"], config["num_attention_heads"],
        config["num_key_value_heads"], config["head_dim"])
    assert (spec.experts, spec.experts_held, spec.experts_per_token, spec.expert_width) == (
        config["as_run"]["router_outputs"], config["moe_num_primary_experts"],
        config["moe_num_active_primary_experts"], config["moe_ffn_hidden_size"])
    assert (spec.window, spec.rope_theta, spec.rms_eps, spec.vocab_held) == (
        config["sliding_window_size"], config["rope_theta"], config["rms_norm_eps"],
        config["vocab_size"])
    assert list(spec.layout) == config["rope_layout"][: config["num_hidden_layers"]]


def test_the_configurations_file_holds_the_catalog_rows_numbers():
    """Every key of the catalog row's ``config`` under the same name, equal
    unless ``reduced`` lists it (the catalog is beside the model-configs
    guide; skipped where that is not installed)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "SmallThinker-21BA3B-Instruct")
    config = load_json("configs", "smallthinker-21ba3b-enc.json")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in config["reduced"]:
            assert config[key] != value and config["published"][key] == value
        else:
            assert config[key] == value, key


@pytest.mark.parametrize("metric", [
    "attention_ms", "moe_ms", "expert_load_max_over_mean",
    "attention_roofline_pct", "experts_roofline_pct",
])
def test_a_reader_finds_nothing_in_a_run_without_its_scope_or_counter(metric):
    """The parent's program has neither the scopes nor the counters: on its
    record each reader returns None and does not raise."""
    run = {
        "cell": {"name": "st21b-docs-n128-r8k-1chip",
                 "config": load_json("configs", "smallthinker-21ba3b-enc.json"),
                 "traffic": load_json("traffic", "pretrain-docs-n128-r8k.json")},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
        "events": [{"event": "epoch", "step": 6, "loss": 5.5, "seen": 10.0},
                   {"event": "epoch", "step": 12, "loss": 5.5, "seen": 17.0}],
        "t_first_beat": 1.0, "t_sigterm": 40.0, "run_dir": None, "trace": None,
    }
    assert load_module("layer_metrics", metric + ".py").read(run) is None
