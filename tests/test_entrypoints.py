"""End-to-end entry-point tests on the 8-device CPU mesh (SURVEY §4):
tiny-epoch pretrain → eval → save_features round trip on synthetic data,
plus the supervised baseline. These are the integration gate: every layer
(config, data, model, loss, optimizer, SPMD steps, checkpointing, probes,
JSON/npy outputs) runs in one pipe.
"""

import json
import os

import numpy as np
import pytest

from simclr_tpu.eval import SWEEP_CONFIG_KEY
from simclr_tpu.eval import main as eval_main
from simclr_tpu.main import main as pretrain_main
from simclr_tpu.save_features import main as save_features_main
from simclr_tpu.supervised import main as supervised_main

# tier-1 runs the trainers' loops end to end (TestPretrain, TestMonitor,
# TestSupervised: summary, checkpoints, resume, the host probe); the
# downstream tools' sweeps stay slow (multi-minute on a 1-core host)

SYNTH = [
    "experiment.synthetic_data=true",
    "experiment.synthetic_size=64",
    "experiment.batches=4",  # x8 devices = global batch 32 -> 2 steps/epoch
]


@pytest.fixture(scope="module")
def pretrain_run(tmp_path_factory):
    """One tiny pretrain run shared by the downstream entry-point tests."""
    save_dir = str(tmp_path_factory.mktemp("pretrain"))
    summary = pretrain_main(
        SYNTH
        + [
            "parameter.epochs=2",
            "parameter.warmup_epochs=1",
            "experiment.save_model_epoch=1",
            f"experiment.save_dir={save_dir}",
        ]
    )
    return summary


class TestPretrain:
    def test_summary(self, pretrain_run):
        assert pretrain_run["steps"] == 4  # 2 epochs x (64 // 32) steps
        assert np.isfinite(pretrain_run["final_loss"])
        assert pretrain_run["global_batch"] == 32
        assert pretrain_run["n_data_shards"] == 8

    def test_checkpoints_on_disk(self, pretrain_run):
        entries = sorted(os.listdir(pretrain_run["save_dir"]))
        assert "epoch=1-cifar10" in entries
        assert "epoch=2-cifar10" in entries

    def test_resume_continues_from_checkpoint(self, pretrain_run, tmp_path):
        """Re-running with resume=true and more epochs continues, not restarts."""
        # copy the run dir: the module fixture must stay immutable for the
        # eval tests that enumerate its checkpoints
        import shutil

        save_dir = str(tmp_path / "resume-copy")
        shutil.copytree(pretrain_run["save_dir"], save_dir)
        summary = pretrain_main(
            SYNTH
            + [
                "parameter.epochs=3",
                "parameter.warmup_epochs=1",
                "experiment.save_model_epoch=3",
                "experiment.resume=true",
                f"experiment.save_dir={save_dir}",
            ]
        )
        # resumed at step 4 (epoch 3 only): 2 more steps
        assert summary["steps"] == 6


class TestMonitor:
    def test_eval_every_runs_centroid_probe(self, tmp_path):
        """experiment.eval_every=1: the in-training centroid monitor (a real
        implementation of the reference's stubbed validation(), SURVEY
        §2.5.6) probes the test split each epoch and surfaces the last val
        accuracy in the summary."""
        summary = pretrain_main(
            SYNTH
            + [
                "parameter.epochs=2",
                "parameter.warmup_epochs=0",
                "experiment.save_model_epoch=2",
                "experiment.eval_every=1",
                f"experiment.save_dir={tmp_path / 'mon'}",
            ]
        )
        assert 0.0 <= summary["monitor_val_acc"] <= 1.0

    def test_eval_every_off_by_default(self, pretrain_run):
        assert "monitor_val_acc" not in pretrain_run

    def test_eval_every_under_tensor_parallelism(self, tmp_path):
        """The monitor's replicated gather must handle model-sharded head
        leaves (jitted identity with replicated out_shardings)."""
        summary = pretrain_main(
            SYNTH
            + [
                "mesh.model=2",
                "parameter.epochs=1",
                "parameter.warmup_epochs=0",
                "experiment.save_model_epoch=1",
                "experiment.eval_every=1",
                f"experiment.save_dir={tmp_path / 'mon-tp'}",
            ]
        )
        assert 0.0 <= summary["monitor_val_acc"] <= 1.0


@pytest.mark.slow
class TestEval:
    def test_centroid(self, pretrain_run, tmp_path):
        out = str(tmp_path / "eval-centroid")
        results = eval_main(
            SYNTH
            + [
                "parameter.classifier=centroid",
                f"experiment.target_dir={pretrain_run['save_dir']}",
                f"experiment.save_dir={out}",
            ]
        )
        assert set(results.keys()) == {
            SWEEP_CONFIG_KEY, "epoch=1-cifar10", "epoch=2-cifar10"
        }
        assert results[SWEEP_CONFIG_KEY]["classifier"] == "centroid"
        for key, metrics in results.items():
            if key == SWEEP_CONFIG_KEY:
                continue
            assert 0.0 <= metrics["val_acc"] <= 1.0
            assert metrics["val_acc"] <= metrics["val_top_5_acc"] <= 1.0
        with open(os.path.join(out, "results.json")) as f:
            assert json.load(f).keys() == results.keys()

    def test_resume_skips_evaluated_checkpoints(self, pretrain_run, tmp_path):
        """experiment.resume=true on an eval sweep: checkpoints already in
        the results file are carried verbatim (not recomputed), only the
        missing ones run, and the incremental per-checkpoint persistence
        makes a crashed sweep resumable at checkpoint granularity."""
        out = str(tmp_path / "eval-resume")
        args = SYNTH + [
            "parameter.classifier=centroid",
            f"experiment.target_dir={pretrain_run['save_dir']}",
            f"experiment.save_dir={out}",
        ]
        eval_main(args)
        path = os.path.join(out, "results.json")
        with open(path) as f:
            blob = json.load(f)
        # simulate a crash after checkpoint 1: drop epoch=2, poison epoch=1
        # with a sentinel so recomputation would be visible
        del blob["epoch=2-cifar10"]
        blob["epoch=1-cifar10"] = {"sentinel": 123}
        with open(path, "w") as f:
            json.dump(blob, f)

        resumed = eval_main(args + ["experiment.resume=true"])
        assert set(resumed.keys()) == {
            SWEEP_CONFIG_KEY, "epoch=1-cifar10", "epoch=2-cifar10"
        }
        assert resumed["epoch=1-cifar10"] == {"sentinel": 123}  # skipped
        assert 0.0 <= resumed["epoch=2-cifar10"]["val_acc"] <= 1.0  # recomputed
        with open(path) as f:
            assert json.load(f).keys() == resumed.keys()

    def test_resume_refuses_config_mismatch(self, pretrain_run, tmp_path):
        """VERDICT r4 weak-item 5: resuming a sweep with settings that change
        what the stored numbers MEAN (a different probe classifier) must
        hard-fail instead of silently mixing result semantics in one file."""
        out = str(tmp_path / "eval-fpr")
        args = SYNTH + [
            "parameter.classifier=centroid",
            f"experiment.target_dir={pretrain_run['save_dir']}",
            f"experiment.save_dir={out}",
        ]
        eval_main(args)
        with pytest.raises(ValueError, match="fingerprint"):
            eval_main(
                SYNTH
                + [
                    "parameter.classifier=linear",
                    f"experiment.target_dir={pretrain_run['save_dir']}",
                    f"experiment.save_dir={out}",
                    "experiment.resume=true",
                ]
            )
        # the stored blob is untouched by the refused resume
        with open(os.path.join(out, "results.json")) as f:
            blob = json.load(f)
        assert blob[SWEEP_CONFIG_KEY]["classifier"] == "centroid"
        assert set(blob.keys()) == {
            SWEEP_CONFIG_KEY, "epoch=1-cifar10", "epoch=2-cifar10"
        }

    def test_multirun_sweeps_three_probes(self, pretrain_run, tmp_path):
        """VERDICT r4 item 6: ONE command sweeps the three probe classifiers
        over a checkpoint dir — `--multirun` expands the comma list into
        sequential jobs, each in its own <sweep_root>/<job_idx> subdir with
        its own fingerprinted results.json (the reference's Hydra sweep
        surface, conf/hydra/output/custom.yaml:6-8)."""
        out = str(tmp_path / "sweep")
        results = eval_main(
            SYNTH
            + [
                "--multirun",
                "parameter.classifier=centroid,linear,nonlinear",
                "parameter.epochs=1",
                f"experiment.target_dir={pretrain_run['save_dir']}",
                f"experiment.save_dir={out}",
            ]
        )
        assert [r[SWEEP_CONFIG_KEY]["classifier"] for r in results] == [
            "centroid", "linear", "nonlinear"
        ]
        for i, kind in enumerate(("centroid", "linear", "nonlinear")):
            with open(os.path.join(out, str(i), "results.json")) as f:
                blob = json.load(f)
            assert blob[SWEEP_CONFIG_KEY]["classifier"] == kind
            assert set(blob.keys()) == {
                SWEEP_CONFIG_KEY, "epoch=1-cifar10", "epoch=2-cifar10"
            }

    @pytest.mark.parametrize("content", ["null", '{"trunca'])
    def test_resume_recovers_from_corrupt_results_file(self, pretrain_run,
                                                       tmp_path, content):
        """A results file that parses but is not a dict (null) or does not
        parse at all (truncated JSON) must not crash resume or be silently
        overwritten: it is set aside as .corrupt and the sweep restarts."""
        out = str(tmp_path / "eval-corrupt")
        args = SYNTH + [
            "parameter.classifier=centroid",
            f"experiment.target_dir={pretrain_run['save_dir']}",
            f"experiment.save_dir={out}",
        ]
        os.makedirs(out, exist_ok=True)
        path = os.path.join(out, "results.json")
        with open(path, "w") as f:
            f.write(content)

        resumed = eval_main(args + ["experiment.resume=true"])
        assert set(resumed.keys()) == {
            SWEEP_CONFIG_KEY, "epoch=1-cifar10", "epoch=2-cifar10"
        }
        with open(path + ".corrupt") as f:
            assert f.read() == content  # evidence preserved

    @pytest.mark.parametrize("kind", ["linear", "nonlinear"])
    def test_learnable(self, pretrain_run, tmp_path, kind):
        out = str(tmp_path / f"eval-{kind}")
        results = eval_main(
            SYNTH
            + [
                f"parameter.classifier={kind}",
                "parameter.epochs=2",
                f"experiment.target_dir={pretrain_run['save_dir']}",
                f"experiment.save_dir={out}",
            ]
        )
        for key, metrics in results.items():
            if key == SWEEP_CONFIG_KEY:
                continue
            assert len(metrics["val_accuracies"]) == 2
            assert metrics["highest_val_acc"] == max(metrics["val_accuracies"])
            assert all(np.isfinite(v) for v in metrics["val_losses"])

    def test_full_encoder_features(self, pretrain_run, tmp_path):
        out = str(tmp_path / "eval-full")
        results = eval_main(
            SYNTH
            + [
                "parameter.classifier=centroid",
                "parameter.use_full_encoder=true",
                f"experiment.target_dir={pretrain_run['save_dir']}",
                f"experiment.save_dir={out}",
            ]
        )
        assert results


@pytest.mark.slow
class TestSaveFeatures:
    def test_npy_exports(self, pretrain_run, tmp_path, monkeypatch):
        import simclr_tpu.save_features as sf

        monkeypatch.setattr(sf, "NUM_AUGMENTATIONS", 2)
        monkeypatch.setattr(sf, "SNAPSHOT_PASSES", (1, 2))
        out = str(tmp_path / "features")
        written = save_features_main(
            SYNTH
            + [
                f"experiment.target_dir={pretrain_run['save_dir']}",
                f"experiment.save_dir={out}",
            ]
        )
        assert written
        train_feats = [p for p in written if p.endswith(".train.features.npy")]
        X = np.load(train_feats[0])
        assert X.shape == (64, 512)  # resnet18 feature dim
        aug1 = [p for p in written if ".train.aug-1." in p][0]
        aug2 = [p for p in written if ".train.aug-2." in p][0]
        a1, a2 = np.load(aug1), np.load(aug2)
        assert a1.shape == X.shape
        # averaging over different augmentations must change the features
        assert np.abs(a1 - a2).max() > 0

    def test_resume_skips_complete_exports(self, pretrain_run, tmp_path,
                                           monkeypatch):
        """experiment.resume=true: a checkpoint with its full export set on
        disk is skipped; one with a missing file is re-exported."""
        import simclr_tpu.save_features as sf

        monkeypatch.setattr(sf, "NUM_AUGMENTATIONS", 1)
        monkeypatch.setattr(sf, "SNAPSHOT_PASSES", (1,))
        out = str(tmp_path / "features-resume")
        args = SYNTH + [
            f"experiment.target_dir={pretrain_run['save_dir']}",
            f"experiment.save_dir={out}",
        ]
        save_features_main(args)
        # simulate a crash mid-export of epoch=2: drop one of its files and
        # poison an epoch=1 file so recomputation would be visible
        victim = os.path.join(out, "epoch=2-cifar10.val.features.npy")
        os.remove(victim)
        sentinel_path = os.path.join(out, "epoch=1-cifar10.train.features.npy")
        sentinel = np.full((2, 2), 7.0, np.float32)
        np.save(sentinel_path, sentinel)

        written = save_features_main(args + ["experiment.resume=true"])
        assert os.path.exists(victim)  # epoch=2 re-exported
        np.testing.assert_array_equal(np.load(sentinel_path), sentinel)  # skipped
        # the returned manifest still lists every expected file
        assert len([p for p in written if "epoch=1-" in os.path.basename(p)]) == 5
        assert len([p for p in written if "epoch=2-" in os.path.basename(p)]) == 5


class TestSupervised:
    def test_one_epoch(self, tmp_path):
        save_dir = str(tmp_path / "supervised")
        summary = supervised_main(
            SYNTH
            + [
                # 48 is NOT divisible by the global batch of 32: the val
                # tail (16 rows) must ride the masked jitted eval path
                "experiment.synthetic_size=48",
                "parameter.epochs=1",
                "parameter.warmup_epochs=0",
                f"experiment.save_dir={save_dir}",
            ]
        )
        assert summary["steps"] == 1  # train drop_last: 48 // 32
        assert summary["best_epoch"] == 1
        assert os.path.isdir(summary["best_path"])
        assert 0.0 <= summary["history"][0]["val_acc"] <= 1.0

    @pytest.mark.slow  # two runs, 41 s alone on the CPU mesh
    def test_resume_continues_from_best(self, tmp_path):
        """experiment.resume=true (VERDICT r3 item 6): restore the persisted
        best checkpoint, re-validate it to re-establish best_value, and
        continue from the best epoch — under the best-only deletion policy
        the on-disk best is the only resume point that exists."""
        save_dir = str(tmp_path / "supervised-resume")
        first = supervised_main(
            SYNTH
            + [
                "parameter.epochs=2",
                "parameter.warmup_epochs=0",
                "parameter.metric=acc",
                f"experiment.save_dir={save_dir}",
            ]
        )
        assert first["steps"] == 4  # 2 epochs x 2 steps
        resumed = supervised_main(
            SYNTH
            + [
                "parameter.epochs=4",
                "parameter.warmup_epochs=0",
                "parameter.metric=acc",
                "experiment.resume=true",
                f"experiment.save_dir={save_dir}",
            ]
        )
        # resumed from the best epoch's checkpoint, not from scratch: the
        # first post-resume epoch is best_epoch+1, and the epoch count ends
        # at 4 regardless of which epoch had been best
        assert resumed["history"][0]["epoch"] == first["best_epoch"] + 1
        assert resumed["steps"] == 8
        # the re-validation seeded best_value: epoch best_epoch+1 could only
        # become the new best by actually beating the restored accuracy
        assert resumed["best_value"] is not None
        ckpts = [  # directories: each has a .sha256 sidecar beside it
            d for d in os.listdir(save_dir)
            if d.startswith("epoch=") and os.path.isdir(os.path.join(save_dir, d))
        ]
        assert len(ckpts) == 1  # best-only policy survives resume

    def test_resume_of_completed_run_is_clean_noop(self, tmp_path):
        """Resuming a run that already reached its epoch target must exit
        cleanly (no training, summary intact) — the epoch loop never runs,
        so nothing loop-local may be relied on afterwards."""
        save_dir = str(tmp_path / "supervised-done")
        args = SYNTH + [
            "parameter.epochs=1",
            "parameter.warmup_epochs=0",
            f"experiment.save_dir={save_dir}",
        ]
        supervised_main(args)
        resumed = supervised_main(args + ["experiment.resume=true"])
        assert resumed["steps"] == 2  # restored step count, no new epochs
        assert resumed["history"] == []
        # the restored checkpoint itself is the re-validated best
        assert resumed["best_epoch"] == 1
        assert resumed["best_value"] is not None

    def test_best_only_policy(self, tmp_path):
        save_dir = str(tmp_path / "supervised-best")
        summary = supervised_main(
            SYNTH
            + [
                "parameter.epochs=2",
                "parameter.warmup_epochs=0",
                "parameter.metric=loss",
                f"experiment.save_dir={save_dir}",
            ]
        )
        # only ONE checkpoint dir remains (previous best deleted)
        ckpts = [  # directories: each has a .sha256 sidecar beside it
            d for d in os.listdir(save_dir)
            if d.startswith("epoch=") and os.path.isdir(os.path.join(save_dir, d))
        ]
        assert len(ckpts) == 1
        assert summary["metric"] == "loss"


@pytest.mark.slow
class TestProfileTrace:
    def test_trace_written_and_closed(self, tmp_path):
        """profile_dir captures a steady-state trace; short runs still close it."""
        save_dir = str(tmp_path / "prof-run")
        trace_dir = str(tmp_path / "trace")
        pretrain_main(
            SYNTH
            + [
                "parameter.epochs=2",
                "parameter.warmup_epochs=0",
                "experiment.save_model_epoch=2",
                f"experiment.profile_dir={trace_dir}",
                "experiment.profile_steps=100",  # window outlives the run
                f"experiment.save_dir={save_dir}",
            ]
        )
        import glob

        assert glob.glob(os.path.join(trace_dir, "**", "*.pb"), recursive=True) or \
            glob.glob(os.path.join(trace_dir, "**", "*.trace.json.gz"), recursive=True)


@pytest.mark.slow
class TestCifar100:
    def test_pretrain_and_centroid_eval(self, tmp_path):
        """The cifar100 branch: 100-class synthetic data through pretrain ->
        centroid probe (NUM_CLASSES plumbing in both entry points)."""
        save_dir = str(tmp_path / "c100")
        pretrain_main(
            [
                "experiment=cifar100",
                "experiment.synthetic_data=true",
                "experiment.synthetic_size=200",
                "experiment.batches=4",
                "parameter.epochs=1",
                "parameter.warmup_epochs=0",
                "experiment.save_model_epoch=1",
                f"experiment.save_dir={save_dir}",
            ]
        )
        results = eval_main(
            [
                "experiment.name=cifar100",
                "experiment.synthetic_data=true",
                "experiment.synthetic_size=200",
                "experiment.batches=4",
                "parameter.classifier=centroid",
                f"experiment.target_dir={save_dir}",
                f"experiment.save_dir={tmp_path / 'c100-eval'}",
            ]
        )
        (metrics,) = (v for k, v in results.items() if k != SWEEP_CONFIG_KEY)
        # 100-class synthetic: top-5 >= top-1, both valid probabilities
        assert 0.0 <= metrics["val_acc"] <= metrics["val_top_5_acc"] <= 1.0
