"""The host loop's one-step lead over an epoch boundary (``main.py``):
the next epoch's first step is dispatched before the finished epoch's loss is
fetched. The trajectory, the ``epoch`` events and the checkpoints are those of
the loop that drains the device at every boundary; a NaN rollback and a
preemption that meet a led boundary leave what they left before."""

import hashlib
import json
import os
import signal

import jax
import numpy as np
import pytest

from simclr_tpu import main as pretrain
from simclr_tpu.config import load_config
from simclr_tpu.obs.trace import read_spans, recorder, spans_path
from simclr_tpu.supervisor.guard import PreemptedRun, RunGuard
from simclr_tpu.utils.checkpoint import (
    checkpoint_name,
    latest_checkpoint,
    restore_checkpoint,
)
from tests.helpers import TinyContrastive

STEPS = 3  # 96 rows over a global batch of 4 x 8 devices
TOY = [
    "experiment.synthetic_data=true",
    "experiment.synthetic_size=96",
    "experiment.batches=4",
    "parameter.warmup_epochs=1",
]


@pytest.mark.parametrize(
    "epoch, save_every, eval_every, leads",
    [
        (1, 100, 0, True),
        (99, 100, 0, True),
        (100, 100, 0, False),  # a checkpoint is due: it saves the epoch's state
        (200, 100, 0, False),
        (3, 100, 3, False),  # a probe is due: it reads the epoch's state
        (4, 100, 3, True),
        (1000, 7, 0, False),  # the last epoch: no step to lead with
        (1001, 7, 0, False),
    ],
)
def test_where_a_boundary_is_led(epoch, save_every, eval_every, leads):
    assert pretrain.leads_over_boundary(
        epoch, epochs=1000, save_model_epoch=save_every, eval_every=eval_every
    ) is leads


def _tiny(monkeypatch):
    import simclr_tpu.eval as evaluate

    monkeypatch.setattr(pretrain, "build_model", lambda cfg: TinyContrastive())
    monkeypatch.setattr(evaluate, "build_eval_model", lambda cfg: TinyContrastive())


def _never_led(monkeypatch):
    """Every boundary through the synchronous path: the loop's own predicate
    says no."""
    monkeypatch.setattr(pretrain, "leads_over_boundary", lambda epoch, **kw: False)


def _run(save_dir, *overrides):
    cfg = load_config(
        "config", overrides=[*TOY, *overrides, f"experiment.save_dir={save_dir}"]
    )
    return pretrain.run_pretrain(cfg)


def _events(save_dir, *kinds):
    with open(os.path.join(save_dir, "events.jsonl")) as f:
        events = [json.loads(line) for line in f]
    return [e for e in events if e["event"] in kinds]


def _spans(save_dir):
    return read_spans(spans_path(str(save_dir)))


def _state_digest(path):
    """sha256 over the restored leaves: orbax's own bytes carry the time and
    random file names, so two saves of one state differ on disk."""
    h = hashlib.sha256()
    tree = restore_checkpoint(path, target=None)
    for key, leaf in jax.tree_util.tree_leaves_with_path(tree):
        leaf = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(key)} {leaf.dtype} {leaf.shape}".encode())
        h.update(leaf.tobytes())
    return h.hexdigest()


def _signal_once(guard):
    if not guard.preempt_requested:
        guard._on_signal(signal.SIGTERM, None)


def _signal_in_beat(monkeypatch, at_step):
    """SIGTERM during the beat of step ``at_step`` (a step's beat carries no
    loss; the boundary's does)."""
    real_beat = RunGuard.beat

    def beat(self, step, epoch, **kw):
        real_beat(self, step, epoch, **kw)
        if step == at_step and "loss" not in kw:
            _signal_once(self)

    monkeypatch.setattr(RunGuard, "beat", beat)


def _signal_in_fetch(monkeypatch, at_step):
    """SIGTERM while the host sits in the fetch of the boundary at ``at_step``."""
    real_checked = RunGuard.checked_loss

    def checked_loss(self, step, loss):
        if step == at_step:
            _signal_once(self)
        return real_checked(self, step, loss)

    monkeypatch.setattr(RunGuard, "checked_loss", checked_loss)


# -- the step the loop dispatches ----------------------------------------------

@pytest.mark.parametrize(
    "model_axis, module, name",
    [
        # the dp builder is looked up on ``simclr_tpu.main`` when the step is
        # built: benchmarks/references/first_steps.py replaces that attribute
        (1, "simclr_tpu.main", "make_pretrain_step"),
        (2, "simclr_tpu.parallel.tp", "make_pretrain_step_tp"),
    ],
)
def test_the_step_is_built_in_one_place_from_one_set_of_keywords(
    tmp_path, monkeypatch, model_axis, module, name
):
    import importlib
    import inspect

    from simclr_tpu.parallel.steps import make_pretrain_step
    from simclr_tpu.parallel.tp import make_pretrain_step_tp

    class Built(Exception):
        pass

    asked = {}

    def builder(*args, **kwargs):
        asked.update(args=args, kwargs=kwargs)
        raise Built

    _tiny(monkeypatch)
    monkeypatch.setattr(importlib.import_module(module), name, builder)
    with pytest.raises(Built):
        _run(tmp_path, f"mesh.model={model_axis}", "parameter.epochs=1")
    model, tx, mesh = asked["args"]
    assert isinstance(model, TinyContrastive) and mesh.shape["model"] == model_axis
    assert set(asked["kwargs"]) == {
        "temperature", "strength", "negatives", "fused", "forward_mode", "remat",
        "grad_allreduce", "comm_overlap", "comm_chunks", "augment_impl", "sentry",
    }
    assert asked["kwargs"]["sentry"] is not None
    for real in (make_pretrain_step, make_pretrain_step_tp):
        keywords = inspect.signature(real).parameters
        assert set(asked["kwargs"]) <= set(keywords), real.__name__
    assert list(inspect.signature(make_pretrain_step).parameters) == list(
        inspect.signature(make_pretrain_step_tp).parameters
    )


# -- the same run, led and not -------------------------------------------------

CASES = {
    # epochs, overrides, the boundaries that must not be led
    # one image a device (24 rows over a global batch of 8): the real encoder
    # at the least the CPU can be asked for
    "resnet18": (
        4,
        ["experiment.save_model_epoch=3", "experiment.batches=1",
         "experiment.synthetic_size=24"],
        {3: "save", 4: "last"},
    ),
    "tiny-probed": (
        6, ["experiment.save_model_epoch=3", "experiment.eval_every=4"],
        {3: "save", 4: "probe", 6: "last"},
    ),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_lead_changes_nothing_but_the_order_of_host_work(tmp_path, monkeypatch, case):
    epochs, overrides, synchronous = CASES[case]
    if case != "resnet18":
        _tiny(monkeypatch)
    overrides = [f"parameter.epochs={epochs}", *overrides]
    led_dir, sync_dir = str(tmp_path / "led"), str(tmp_path / "sync")
    led = _run(led_dir, *overrides)
    with monkeypatch.context() as m:
        _never_led(m)
        sync = _run(sync_dir, *overrides)

    assert led["loss_history"] == sync["loss_history"]
    assert [row[0] for row in led["loss_history"]] == list(range(1, epochs + 1))
    assert led["steps"] == sync["steps"] == epochs * STEPS
    assert led.get("monitor_history") == sync.get("monitor_history")

    def told(save_dir):
        return [
            (e["event"], e["epoch"], e.get("step"), e.get("loss"))
            for e in _events(save_dir, "epoch", "checkpoint")
        ]

    assert told(led_dir) == told(sync_dir)
    assert [e["step"] for e in _events(led_dir, "epoch")] == [
        e * STEPS for e in range(1, epochs + 1)
    ]
    final = checkpoint_name(epochs, "cifar10")
    assert _state_digest(os.path.join(led_dir, final)) == _state_digest(
        os.path.join(sync_dir, final)
    )

    spans = _spans(led_dir)
    assert not any(s.get("open") for s in spans)
    boundaries = {s["epoch"]: s for s in spans if s["name"] == "boundary"}
    assert {e: b["n"] for e, b in boundaries.items()} == {
        e: 0 if e in synchronous else 1 for e in range(1, epochs + 1)
    }
    assert all(b["step"] == e * STEPS for e, b in boundaries.items())
    assert {s["n"] for s in _spans(sync_dir) if s["name"] == "boundary"} == {0}
    # an epoch's event is written after its loss was fetched, and before
    # anything of the next epoch's boundary
    fetched = {
        s["epoch"]: (s["t1"] + s["wall_offset_ns"]) / 1e9
        for s in spans if s["name"] == "boundary/loss_fetch"
    }
    events = _events(led_dir, "epoch")
    for event, following in zip(events, events[1:] + [None]):
        assert event["time"] >= fetched[event["epoch"]] - 1e-3
        if following is not None:
            assert event["time"] <= fetched[following["epoch"]]
    # where the boundary is led, the next epoch's first batch and first step
    # went out before it opened; elsewhere the batch is the boundary's child
    first_batch = {s["epoch"]: s for s in spans if s["name"] == "boundary/first_batch"}
    dispatches = [s for s in spans if s["name"] == "dispatch"]
    for e, boundary in boundaries.items():
        if e == epochs:
            continue
        step_1 = next(d for d in dispatches if d["step"] == e * STEPS + 1)
        if boundary["n"]:
            assert first_batch[e + 1]["parent"] == 0
            assert first_batch[e + 1]["t1"] <= step_1["t0"] <= step_1["t1"] <= boundary["t0"]
        else:
            assert first_batch[e + 1]["parent"] == boundary["id"]
            assert boundary["t1"] <= step_1["t0"]
    by_id = {s["id"]: s for s in spans}
    for child in ("loss_fetch", "telemetry", "log", "event", "results"):
        found = [s for s in spans if s["name"] == f"boundary/{child}"]
        assert len(found) == epochs
        assert all(by_id[s["parent"]]["name"] == "boundary" for s in found)


# -- the three exits through a led boundary ---------------------------------------

def test_nan_at_a_led_boundary_discards_the_led_step(tmp_path, monkeypatch):
    _tiny(monkeypatch)
    # saves after epochs 2 and 4: the boundaries of 1 and 3 are led
    monkeypatch.setenv("SIMCLR_FAULT_NAN_AT_STEP", str(3 * STEPS))
    save_dir = str(tmp_path / "run")
    summary = _run(save_dir, "parameter.epochs=5", "experiment.save_model_epoch=2")

    (rollback,) = _events(save_dir, "nan_rollback")
    assert rollback["checkpoint"].endswith(checkpoint_name(2, "cifar10"))
    # the timeline holds each epoch once, and the run ends where it should
    assert [(e["epoch"], e["step"]) for e in _events(save_dir, "epoch")] == [
        (e, e * STEPS) for e in range(1, 6)
    ]
    assert [row[0] for row in summary["loss_history"]] == [1, 2, 3, 4, 5]
    assert all(np.isfinite(row[1]) for row in summary["loss_history"])
    assert summary["steps"] == 5 * STEPS
    spans = _spans(save_dir)
    assert not any(s.get("open") for s in spans)
    # epoch 3 ran twice: first behind a led boundary (the step it led with,
    # 10, went with the state it produced), then again from step 7 on
    third = [s for s in spans if s["name"] == "boundary" and s["epoch"] == 3]
    assert [b["n"] for b in third] == [1, 1] and [b["step"] for b in third] == [9, 9]
    steps = [s["step"] for s in spans if s["name"] == "dispatch"]
    assert steps == [1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 7, 8, 9, 10, 11, 12, 13, 14, 15]


def _results(save_dir):
    with open(os.path.join(save_dir, "pretrain_results.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seen", ["in_the_led_steps_beat", "in_the_led_boundarys_fetch"])
def test_preemption_over_a_led_boundary_resumes_exactly(tmp_path, monkeypatch, seen):
    _tiny(monkeypatch)
    overrides = ["parameter.epochs=4", "experiment.save_model_epoch=3"]
    whole = _run(str(tmp_path / "whole"), *overrides)

    save_dir = str(tmp_path / "cut")
    with monkeypatch.context() as m:
        # epoch 2's boundary is led by step 7
        if seen == "in_the_led_steps_beat":
            _signal_in_beat(m, at_step=2 * STEPS + 1)
        else:
            _signal_in_fetch(m, at_step=2 * STEPS)
        with pytest.raises(PreemptedRun):
            _run(save_dir, *overrides)
    # epoch 2's row, event and results first, then the checkpoint one step on
    told = [(e["event"], e["epoch"], e["step"]) for e in _events(save_dir, "epoch", "preempt")]
    assert told == [("epoch", 1, 3), ("epoch", 2, 6), ("preempt", 3, 7)]
    assert _results(save_dir)["loss_history"] == whole["loss_history"][:2]
    (preempt,) = _events(save_dir, "preempt")
    assert os.path.basename(preempt["checkpoint"]) == checkpoint_name(2, "cifar10") + "-preempt"
    assert latest_checkpoint(save_dir) == preempt["checkpoint"]
    assert int(restore_checkpoint(preempt["checkpoint"], target=None)["step"]) == 7
    spans = _spans(save_dir)
    assert not any(s.get("open") for s in spans)
    assert [s["step"] for s in spans if s["name"] == "dispatch"] == [1, 2, 3, 4, 5, 6, 7]
    assert [(s["epoch"], s["n"]) for s in spans if s["name"] == "boundary"] == [(1, 1), (2, 1)]

    resumed = _run(save_dir, *overrides, "experiment.resume=true")
    assert resumed["loss_history"] == whole["loss_history"]
    assert resumed["steps"] == whole["steps"] == 4 * STEPS
    final = checkpoint_name(4, "cifar10")
    assert _state_digest(os.path.join(save_dir, final)) == _state_digest(
        str(tmp_path / "whole" / final)
    )


def test_flag_set_at_the_loops_exit_takes_the_exact_boundary_checkpoint(
    tmp_path, monkeypatch
):
    """The elastic grow-back case: SIGTERM drained at an epoch's last step.
    No lead; the boundary checkpoint under its plain name, the epoch's row."""
    _tiny(monkeypatch)
    save_dir = str(tmp_path / "run")
    _signal_in_beat(monkeypatch, at_step=2 * STEPS)
    with pytest.raises(PreemptedRun):
        _run(save_dir, "parameter.epochs=4", "experiment.save_model_epoch=3")
    told = [(e["event"], e["epoch"], e["step"]) for e in _events(save_dir, "epoch", "preempt")]
    assert told == [("epoch", 1, 3), ("epoch", 2, 6), ("preempt", 2, 6)]
    (preempt,) = _events(save_dir, "preempt")
    assert os.path.basename(preempt["checkpoint"]) == checkpoint_name(2, "cifar10")
    assert [row[0] for row in _results(save_dir)["loss_history"]] == [1, 2]
    spans = _spans(save_dir)
    assert [s["step"] for s in spans if s["name"] == "dispatch"] == [1, 2, 3, 4, 5, 6]
    # epoch 1's boundary was led; epoch 2 never opened one
    assert [(s["epoch"], s["n"]) for s in spans if s["name"] == "boundary"] == [(1, 1)]
    assert len(recorder()) == 0 and recorder().current() is None
