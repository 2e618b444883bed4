"""Config system tests: composition, overrides, validation contracts."""

import pytest

from simclr_tpu.config import (
    Config,
    ConfigError,
    check_eval_conf,
    check_pretrain_conf,
    check_serve_conf,
    load_config,
    resolve_save_dir,
)


def test_pretrain_defaults_match_reference_tree():
    cfg = load_config("config")
    # /root/reference/conf/config.yaml:8-17
    assert cfg.parameter.seed == 7
    assert cfg.parameter.d == 128
    assert cfg.parameter.temperature == 0.5
    assert cfg.parameter.epochs == 1000
    assert cfg.parameter.momentum == 0.9
    assert cfg.parameter.warmup_epochs == 10
    assert cfg.parameter.linear_schedule is True
    # /root/reference/conf/experiment/cifar10.yaml:2-10
    assert cfg.experiment.decay == 1.0e-4
    assert cfg.experiment.lr == 1.0
    assert cfg.experiment.strength == 0.5
    assert cfg.experiment.base_cnn == "resnet18"
    assert cfg.experiment.batches == 512
    assert cfg.experiment.name == "cifar10"
    assert cfg.mesh.data == -1


def test_dotted_overrides_are_yaml_typed():
    cfg = load_config(
        "config",
        ["parameter.epochs=200", "experiment.lr=0.5", "parameter.linear_schedule=false"],
    )
    assert cfg.parameter.epochs == 200
    assert isinstance(cfg.parameter.epochs, int)
    assert cfg.experiment.lr == 0.5
    assert cfg.parameter.linear_schedule is False


def test_group_choice_override_selects_cifar100():
    cfg = load_config("config", ["experiment=cifar100"])
    assert cfg.experiment.name == "cifar100"
    assert cfg.experiment.output_model_name == "cifar100.pt"


def test_eval_config_defaults():
    cfg = load_config("eval")
    # /root/reference/conf/eval.yaml:2-17
    assert cfg.parameter.epochs == 100
    assert cfg.parameter.warmup_epochs == 0
    assert cfg.parameter.top_k == 5
    assert cfg.parameter.use_full_encoder is False
    assert cfg.parameter.classifier == "centroid"
    assert cfg.experiment.decay == 0.0
    assert cfg.experiment.lr == 0.1
    assert cfg.experiment.target_dir == "DUMMY-PATH"


def test_validation_rejects_bad_values():
    cfg = load_config("config")
    check_pretrain_conf(cfg)  # defaults pass
    cfg.parameter.epochs = 0
    with pytest.raises(ConfigError):
        check_pretrain_conf(cfg)

    ev = load_config("eval")
    with pytest.raises(ConfigError):  # DUMMY-PATH target_dir must be rejected
        check_eval_conf(ev)
    ev.experiment.target_dir = "/tmp/ckpts"
    check_eval_conf(ev)
    ev.parameter.classifier = "svm"
    with pytest.raises(ConfigError):
        check_eval_conf(ev)


def test_validation_rejects_unknown_grad_allreduce():
    """Both entry points' check_*_conf reject an unknown wire format, and
    the message names the valid set (the operator's fix is in the error)."""
    from simclr_tpu.config import check_supervised_conf

    cfg = load_config("config")
    assert cfg.parallel.grad_allreduce == "exact"
    cfg.parallel.grad_allreduce = "int8"
    check_pretrain_conf(cfg)  # every shipped mode passes
    cfg.parallel.grad_allreduce = "fp4"
    with pytest.raises(ConfigError, match="exact.*bf16.*int8"):
        check_pretrain_conf(cfg)

    sup = load_config("supervised_config")
    sup.parallel.grad_allreduce = "fp4"
    with pytest.raises(ConfigError, match="exact.*bf16.*int8"):
        check_supervised_conf(sup)


def test_validation_accepts_async_overlap_and_rejects_bad_chunks():
    """parallel.comm_overlap=async is a shipped mode in both entry points;
    the eager-ring path reuses comm_chunks, so a chunk count outside
    [1, 64] (or a non-int) must be rejected up front — an invalid bucket
    split would otherwise surface as a shape error mid-compile."""
    from simclr_tpu.config import check_supervised_conf

    cfg = load_config("config")
    cfg.parallel.comm_overlap = "async"
    check_pretrain_conf(cfg)  # async with the default comm_chunks passes
    cfg.parallel.comm_chunks = 64
    check_pretrain_conf(cfg)
    for bad in (0, -1, 65, True):
        cfg.parallel.comm_chunks = bad
        with pytest.raises(ConfigError, match=r"comm_chunks.*\[1, 64\]"):
            check_pretrain_conf(cfg)
    cfg.parallel.comm_chunks = 4
    cfg.parallel.comm_overlap = "eager"
    with pytest.raises(ConfigError, match="off.*chunked.*async"):
        check_pretrain_conf(cfg)

    sup = load_config("supervised_config")
    sup.parallel.comm_overlap = "async"
    sup.parallel.comm_chunks = 8
    check_supervised_conf(sup)
    sup.parallel.comm_chunks = 0
    with pytest.raises(ConfigError, match="comm_chunks"):
        check_supervised_conf(sup)


def test_serve_config_defaults_and_validation():
    cfg = load_config("serve")
    assert cfg.serve.max_batch == 256
    assert cfg.serve.max_delay_ms == 5.0
    assert cfg.serve.queue_depth == 64
    assert cfg.serve.checkpoint is None
    with pytest.raises(ConfigError):  # no checkpoint AND DUMMY-PATH target
        check_serve_conf(cfg)
    cfg.experiment.target_dir = "/tmp/ckpts"
    check_serve_conf(cfg)
    cfg.serve.max_batch = 0
    with pytest.raises(ConfigError):
        check_serve_conf(cfg)
    cfg.serve.max_batch = 256
    cfg.serve.port = 70000
    with pytest.raises(ConfigError):
        check_serve_conf(cfg)
    cfg.serve.port = 0
    cfg.experiment.target_dir = "DUMMY-PATH"
    cfg.serve.checkpoint = "/tmp/ckpts/epoch=1-m"  # explicit checkpoint suffices
    check_serve_conf(cfg)


def test_serve_retrieval_knob_validation():
    cfg = load_config("serve")
    cfg.serve.checkpoint = "/tmp/ckpts/epoch=1-m"
    # defaults: exact fp32 scan
    assert cfg.serve.corpus_dtype == "fp32"
    assert cfg.serve.ann_cells == 0
    assert cfg.serve.ann_probe == 1
    check_serve_conf(cfg)

    cfg.serve.corpus_dtype = "int8"
    check_serve_conf(cfg)
    cfg.serve.corpus_dtype = "fp16"
    with pytest.raises(ConfigError, match="corpus_dtype must be fp32|int8"):
        check_serve_conf(cfg)
    cfg.serve.corpus_dtype = "fp32"

    for bad_cells in (-1, 65537, 4.0, True):
        cfg.serve.ann_cells = bad_cells
        with pytest.raises(ConfigError, match="ann_cells"):
            check_serve_conf(cfg)
    cfg.serve.ann_cells = 65536
    cfg.serve.ann_probe = 65536
    check_serve_conf(cfg)

    for bad_probe in (0, -3, 2.0, False):
        cfg.serve.ann_probe = bad_probe
        with pytest.raises(ConfigError, match="ann_probe"):
            check_serve_conf(cfg)

    # probe may not exceed the cell count when the IVF scan is on...
    cfg.serve.ann_cells = 8
    cfg.serve.ann_probe = 9
    with pytest.raises(ConfigError, match="ann_probe must be <= serve.ann_cells"):
        check_serve_conf(cfg)
    # ...but any probe is fine on the exact path (cells == 0)
    cfg.serve.ann_cells = 0
    check_serve_conf(cfg)


def test_cosched_serve_retrieval_knob_defaults():
    cfg = load_config("cosched")
    assert cfg.serve.corpus_dtype == "fp32"
    assert cfg.serve.ann_cells == 0
    assert cfg.serve.ann_probe == 1


def test_bad_override_syntax_raises():
    with pytest.raises(ConfigError):
        load_config("config", ["parameter.epochs"])


def test_strict_overrides_reject_typos_but_allow_plus_prefix():
    with pytest.raises(ConfigError):
        load_config("config", ["parameter.eopchs=5"])  # typo'd key
    cfg = load_config("config", ["+parameter.extra=5"])
    assert cfg.parameter.extra == 5


@pytest.mark.parametrize("name", ["config", "supervised_config"])
@pytest.mark.parametrize(
    "override",
    [
        "runtime.epoch_compile=true",
        "runtime.epochs_per_compile=2",
        "runtime.dataset_residency=sharded",
    ],
)
def test_removed_epoch_scan_keys_are_refused_by_name(name, override):
    """The epoch-scan family went in PR 31 with no alias: an old command line
    fails at load, naming the key, before any device work (docs/MIGRATION.md)."""
    key = override.split("=")[0]
    with pytest.raises(ConfigError, match=f"override key '{key}' not in config"):
        load_config(name, overrides=[override])
    assert key.split(".")[1] not in load_config(name).runtime


def test_scientific_notation_override_is_float():
    cfg = load_config("config", ["experiment.decay=1e-4"])
    assert cfg.experiment.decay == pytest.approx(1e-4)
    assert isinstance(cfg.experiment.decay, float)


def test_override_cannot_clobber_scalar_with_section():
    with pytest.raises(ConfigError):
        load_config("config", ["+parameter.epochs.typo=5"])


def test_save_dir_resolution():
    import datetime

    cfg = load_config("config")
    now = datetime.datetime(2026, 7, 29, 12, 34, 56)
    assert resolve_save_dir(cfg, now) == "results/cifar10/seed-7/2026-07-29/12-34-56"
    cfg.experiment.save_dir = "/tmp/run1"
    assert resolve_save_dir(cfg) == "/tmp/run1"


def test_config_node_behaves_like_mapping():
    cfg = Config({"a": {"b": 1}})
    assert cfg.a.b == 1
    assert cfg.select("a.b") == 1
    assert cfg.select("a.missing", 42) == 42
    cfg.update_dotted("a.c.d", "x")
    assert cfg.a.c.d == "x"
    assert "a" in cfg and dict(cfg.a.items())["b"] == 1


class TestOverrideMarker:
    def test_override_group_beats_root_defaults(self):
        from simclr_tpu.config import load_config

        cfg = load_config("config", ["experiment=cifar10-large-batch"])
        assert cfg.parameter.lr_scale_batch == "global"
        assert cfg.parameter.linear_schedule is False
        # non-override groups still lose to root (reference semantics)
        assert cfg.parameter.seed == 7

    def test_cli_still_beats_override_group(self):
        from simclr_tpu.config import load_config

        cfg = load_config(
            "config",
            ["experiment=cifar10-large-batch", "parameter.linear_schedule=true"],
        )
        assert cfg.parameter.linear_schedule is True


def test_expand_sweep_cartesian_product_in_argv_order():
    from simclr_tpu.config import expand_sweep

    combos = expand_sweep(["a.b=1,2", "c.d=x", "e.f=3,4"])
    assert combos == [
        ["a.b=1", "c.d=x", "e.f=3"],
        ["a.b=1", "c.d=x", "e.f=4"],
        ["a.b=2", "c.d=x", "e.f=3"],
        ["a.b=2", "c.d=x", "e.f=4"],
    ]


def test_expand_sweep_bracketed_list_is_one_value():
    from simclr_tpu.config import expand_sweep

    # a YAML list value is NOT a sweep axis (Hydra semantics)
    assert expand_sweep(["a.b=[1,2]"]) == [["a.b=[1,2]"]]
    assert expand_sweep(["a.b=7"]) == [["a.b=7"]]


def test_expand_sweep_rejects_empty_values():
    from simclr_tpu.config import expand_sweep

    with pytest.raises(ConfigError, match="empty value"):
        expand_sweep(["a.b=1,,2"])
    with pytest.raises(ConfigError, match="key=value"):
        expand_sweep(["no-equals-sign"])


def test_split_multirun_flag():
    from simclr_tpu.config import split_multirun_flag

    assert split_multirun_flag(["a=1"]) == (False, ["a=1"])
    assert split_multirun_flag(["--multirun", "a=1"]) == (True, ["a=1"])
    assert split_multirun_flag(["a=1", "-m"]) == (True, ["a=1"])


def test_run_multirun_layout_and_order(tmp_path):
    """Jobs share one sweep root with <job_idx> subdirs — the analogue of
    Hydra's hydra.sweep.dir/subdir layout
    (/root/reference/conf/hydra/output/custom.yaml:6-8)."""
    from simclr_tpu.config import run_multirun

    seen = []

    def record(cfg):
        seen.append((cfg.parameter.seed, cfg.experiment.save_dir))
        return cfg.parameter.seed

    results = run_multirun(
        record, "config",
        [f"experiment.save_dir={tmp_path}/sweep", "parameter.seed=3,5"],
    )
    assert results == [3, 5]
    assert seen == [
        (3, f"{tmp_path}/sweep/0"),
        (5, f"{tmp_path}/sweep/1"),
    ]
