"""The sequence encoder through the trainer's own entry point on the CPU
mesh (``python -m simclr_tpu.main experiment=smallthinker-docs`` at toy
widths): the one step builder, the one host loop, LARS, checkpoints and
resume, the expert layers' counters in the ``epoch`` events; and the
surfaces that take images refusing it by name."""

import json
import os

import numpy as np
import pytest

from simclr_tpu.config import ConfigError, check_pretrain_conf, load_config
from simclr_tpu.main import main as pretrain_main

TOY = [
    "experiment=smallthinker-docs",
    "experiment.base_cnn=smallthinker-tiny",
    "experiment.documents=128",
    "experiment.document_tokens=128",
    "experiment.crop_lengths=[24,16,8,8,4,2,1,1]",
    "experiment.batches=8",  # a row a view and device; x8 devices = 64 documents a step
]


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    save_dir = str(tmp_path_factory.mktemp("docs"))
    return pretrain_main(TOY + [
        "parameter.epochs=2", "parameter.warmup_epochs=1",
        "experiment.save_model_epoch=1", f"experiment.save_dir={save_dir}",
    ])


def events_of(run, kind):
    with open(os.path.join(run["save_dir"], "events.jsonl")) as f:
        return [e for e in map(json.loads, f) if e["event"] == kind]


class TestPretrainOnDocuments:
    def test_summary_and_checkpoints(self, run):
        assert run["steps"] == 4 and run["global_batch"] == 64 and run["n_data_shards"] == 8
        assert np.isfinite(run["final_loss"])
        # an untrained encoder reads NT-Xent's ln(2N-1) = ln 127, give or take
        # what 64 rows of noise give
        assert abs(run["loss_history"][0][1] - np.log(127)) < 0.4
        assert {"epoch=1-smallthinker-docs", "epoch=2-smallthinker-docs"} <= set(
            os.listdir(run["save_dir"]))

    def test_epoch_events_carry_the_expert_layers_counters(self, run):
        epochs = events_of(run, "epoch")
        assert len(epochs) == 2
        for event in epochs:
            # 16 rows of 64 tokens a step over the mesh, 2 of 8 experts a token,
            # 4 held, 4 layers: 4,096 pairs if the router were even
            assert 2500 < event["held_pairs"] < 6000
            assert event["expert_load_max_over_mean"] >= 1.0
            # 256 pairs a call at these sizes: one chunk of all rows
            assert event["expert_chunks_per_call"] == 1.0
        assert events_of(run, "run_start")[0]["global_batch"] == 64

    def test_resume_continues_from_checkpoint(self, run, tmp_path):
        import shutil

        save_dir = str(tmp_path / "resume-copy")
        shutil.copytree(run["save_dir"], save_dir)
        summary = pretrain_main(TOY + [
            "parameter.epochs=3", "parameter.warmup_epochs=1",
            "experiment.save_model_epoch=3", "experiment.resume=true",
            f"experiment.save_dir={save_dir}",
        ])
        assert summary["steps"] == 6  # resumed at step 4: one more epoch

    def test_the_image_surfaces_refuse_the_checkpoint_by_name(self, run):
        from simclr_tpu.eval import build_eval_model, load_model_variables
        from simclr_tpu.models.contrastive import ContrastiveModel
        from simclr_tpu.serve.engine import EmbedEngine

        with pytest.raises(ValueError, match="sequence encoder"):
            load_model_variables(os.path.join(run["save_dir"], "epoch=2-smallthinker-docs"))
        cfg = load_config("eval", overrides=["experiment.base_cnn=smallthinker-tiny"])
        with pytest.raises(ValueError, match="takes images"):
            build_eval_model(cfg)
        with pytest.raises(ValueError, match="sequence encoder"):
            EmbedEngine(ContrastiveModel(base_cnn="smallthinker-tiny"), {"params": {}}, warmup=False)


@pytest.mark.parametrize("overrides,message", [
    (["experiment.base_cnn=smallthinker-tiny"], "synthetic-docs"),  # images for a token encoder
    (["experiment=smallthinker-docs", "experiment.base_cnn=resnet18"], "cifar10"),
    (["experiment=smallthinker-docs", "experiment.batches=100"], "fill rows of 64"),
    (["experiment=smallthinker-docs", "experiment.eval_every=1"], "eval_every"),
    (["experiment=smallthinker-docs", "experiment.crop_lengths=[9000]"], "longest crop"),
    (["experiment.base_cnn=vit"], "experiment.base_cnn must be"),
])
def test_configurations_that_mix_the_two_kinds_are_refused(overrides, message):
    with pytest.raises(ConfigError, match=message):
        check_pretrain_conf(load_config("config", overrides=overrides))


def test_the_published_group_passes_validation_and_names_the_encoder():
    cfg = load_config("config", overrides=["experiment=smallthinker-docs"])
    check_pretrain_conf(cfg)
    assert cfg.experiment.base_cnn == "smallthinker-21ba3b-enc"
    assert len(cfg.experiment.crop_lengths) == 64 and sum(cfg.experiment.crop_lengths) == 8192
    assert cfg.experiment.batches == 128 and cfg.experiment.documents == 768


def test_a_model_axis_is_refused_with_a_message(tmp_path):
    with pytest.raises(ValueError, match="no exchange over a model axis"):
        pretrain_main(TOY + [
            "mesh.data=4", "mesh.model=2", f"experiment.save_dir={tmp_path}"])
