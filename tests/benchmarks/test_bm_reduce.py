"""The window's arithmetic and ``correct`` (benchmarks/reduce.py and the
metric readers) against hand-written run records."""

import copy
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import reduce  # noqa: E402
from benchmarks.run import load_module  # noqa: E402

UNTRAINED = math.log(2 * 2048 - 1)  # 8.3175


def reader(group: str, name: str):
    return load_module(group, name + ".py").read


def good_run() -> dict:
    """Process start at 100.0, run_start seen at 150.0, first beat at 167.0,
    epoch events of 24 steps of 2048 images seen every 3 s from 170.0,
    SIGTERM at 179.25, exit at 181.75."""
    events = [
        {"event": "run_start", "seen": 150.0, "time": 1150.0, "global_batch": 2048,
         "steps_per_epoch": 24},
        {"event": "compile", "seen": 166.9, "time": 1166.9, "name": "pretrain_step",
         "seconds": 16.5, "cache_hit": True, "recompile": False},
    ]
    for k, loss in enumerate([8.317, 8.21, 8.15, 8.0]):
        events.append({"event": "epoch", "seen": 170.0 + 3 * k, "time": 1170.0 + 3 * k,
                       "epoch": k + 1, "step": 24 * (k + 1), "loss": loss})
    events.append({"event": "preempt", "seen": 181.7, "time": 1181.7, "step": 100,
                   "epoch": 5, "checkpoint": "/nowhere/epoch=4-cifar10-preempt"})
    return {
        "cell": {"name": "r18-b2048-1chip", "chips": 1, "data": {"flops_per_image": 5.86e9}},
        "t_start": 100.0, "t_first_beat": 167.0, "t_sigterm": 179.25, "t_exit": 181.75,
        "exit_code": 75, "events": events, "checkpoint_verifies": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def test_measured_epochs_leave_out_the_first_and_count_the_rest():
    m = reduce.measured(good_run())
    assert m == {"steps": 72, "images": 72 * 2048, "seconds": 9.0, "epochs": 3}


def test_epochs_that_overlap_the_capture_are_left_out_of_a_traced_run():
    run = good_run()  # epoch events seen at 170, 173, 176, 179
    run["capture"] = (174.0, 175.5)  # inside the epoch 173-176
    assert reduce.measured(run) == {
        "steps": 48, "images": 48 * 2048, "seconds": 6.0, "epochs": 2}
    run["capture"] = (172.0, 176.5)  # touches all three
    assert reduce.measured(run) is None
    assert reduce.verdict(run) == []  # nothing to read, but nothing wrong
    run["capture"] = None
    assert reduce.measured(run)["epochs"] == 3


def test_events_after_sigterm_are_outside_the_window():
    run = good_run()
    run["events"].append({"event": "epoch", "seen": 180.5, "time": 1180.5, "epoch": 5,
                          "step": 120, "loss": 7.9})
    assert reduce.measured(run)["steps"] == 72


@pytest.mark.parametrize("group,name,expected", [
    ("end_to_end", "imgs_per_s_per_chip", 72 * 2048 / 9.0),
    ("end_to_end", "preempt_s", 2.5),
    ("end_to_end", "setup_s", 67.0),
    ("layer_metrics", "step_ms", 125.0),
    ("layer_metrics", "boot_s", 50.0),
    ("layer_metrics", "compile_s", 16.5),
    ("layer_metrics", "mfu_pct", 100 * (72 * 2048 / 9.0) * 5.86e9 / 197e12),
])
def test_metric_readers(group, name, expected):
    assert reader(group, name)(good_run()) == pytest.approx(expected)


def test_rate_is_per_chip():
    run = good_run()
    run["device"]["count"] = 4
    assert reader("end_to_end", "imgs_per_s_per_chip")(run) == pytest.approx(72 * 2048 / 9.0 / 4)


@pytest.mark.parametrize("group,name", [
    ("end_to_end", "imgs_per_s_per_chip"), ("layer_metrics", "step_ms"),
    ("layer_metrics", "mfu_pct"),
])
def test_a_window_with_one_epoch_event_reads_nothing(group, name):
    run = good_run()
    run["t_sigterm"] = 171.0
    assert reduce.measured(run) is None
    assert reader(group, name)(run) is None


def test_trace_readers_read_nothing_without_a_trace():
    assert reader("layer_metrics", "device_idle_pct")(good_run()) is None
    assert reader("layer_metrics", "collective_ms")(good_run()) is None


def test_trace_readers_read_the_reduction():
    from test_bm_xplane import device

    run = good_run()
    run["trace"] = {"devices": [device()], "window_ns": (0, 2500), "start_wall_ns": None}
    # busy 1,250 of the 2,500 ns given as the window
    assert reader("layer_metrics", "device_idle_pct")(run) == pytest.approx(50.0)
    assert reader("layer_metrics", "collective_ms")(run) == pytest.approx(350e-6)


def test_mfu_has_no_default_peak():
    run = good_run()
    run["device"]["kind"] = "TPU v9 imaginary"
    with pytest.raises(KeyError):
        reader("layer_metrics", "mfu_pct")(run)
    run["device"] = {"platform": "cpu", "kind": "cpu", "count": 1}
    assert reader("layer_metrics", "mfu_pct")(run) is None


def test_a_good_run_is_correct():
    assert reduce.verdict(good_run()) == []
    assert reduce.attempted_and_failed(good_run()) == (100, 0)


def _set(run, **kw):
    run.update(kw)


def _loss(run, index, value):
    [e for e in run["events"] if e["event"] == "epoch"][index]["loss"] = value


def _event(run, **event):
    run["events"].append(event)


@pytest.mark.parametrize("case,change,reason", [
    ("platform cpu", lambda r: r["device"].update(platform="cpu"), "platform"),
    ("exit code 0", lambda r: _set(r, exit_code=0), "exit code 0"),
    ("exit code 76", lambda r: _set(r, exit_code=76), "exit code 76"),
    ("no verified checkpoint", lambda r: _set(r, checkpoint_verifies=False), "sha256"),
    ("nan loss", lambda r: _loss(r, 2, float("nan")), "not finite"),
    ("inf loss", lambda r: _loss(r, 1, float("inf")), "not finite"),
    ("diverging loss", lambda r: _loss(r, 3, 1.03 * UNTRAINED), "above"),
    ("first loss collapsed", lambda r: _loss(r, 0, 0.94 * UNTRAINED), "under"),
    ("compile in the window", lambda r: _event(
        r, event="compile", seen=172.0, time=1172.0, name="pretrain_step",
        seconds=60.0, recompile=True), "compilation"),
    ("recompile alarm", lambda r: _event(
        r, event="recompile_alarm", seen=175.0, time=1175.0, name="pretrain_step"),
     "compilation"),
    ("rolled back", lambda r: _event(
        r, event="nan_rollback", seen=176.0, time=1176.0, loss=float("nan")), "rolled"),
    ("window too short", lambda r: _set(r, t_sigterm=171.0), "fewer than two"),
    ("no epoch at all", lambda r: _set(
        r, events=[e for e in r["events"] if e["event"] != "epoch"]), "no epoch"),
])
def test_verdict_turns_false(case, change, reason):
    run = good_run()
    change(run)
    reasons = reduce.verdict(run)
    assert reasons, case
    assert any(reason in r for r in reasons), (case, reasons)


@pytest.mark.parametrize("loss_ok", [UNTRAINED, 1.019 * UNTRAINED, 0.951 * UNTRAINED])
def test_first_loss_inside_the_band_is_correct(loss_ok):
    run = good_run()
    _loss(run, 0, loss_ok)
    assert reduce.verdict(run) == []


def test_the_compile_before_the_first_beat_is_set_up():
    run = good_run()
    assert [e for e in run["events"] if e["event"] == "compile"][0]["seen"] < run["t_first_beat"]
    assert reduce.verdict(run) == []


@pytest.mark.parametrize("change,expected", [
    (lambda r: _set(r, exit_code=1), (100, 100)),  # died: every step is lost
    (lambda r: _set(r, events=[e for e in r["events"] if e["event"] != "preempt"]), (96, 96)),
    (lambda r: _event(r, event="nan_rollback", seen=176.0, time=1176.0, step=120), (120, 20)),
])
def test_failed_steps(change, expected):
    run = good_run()
    change(run)
    assert reduce.attempted_and_failed(run) == expected


def test_checkpoint_digest_verifies_and_catches_a_changed_byte(tmp_path):
    ckpt = tmp_path / "epoch=3-cifar10-preempt"
    (ckpt / "d").mkdir(parents=True)
    (ckpt / "a.bin").write_bytes(b"abc")
    (ckpt / "d" / "b.bin").write_bytes(b"\x00" * 10)
    run = {"events": [{"event": "preempt", "checkpoint": str(ckpt)}]}
    assert not reduce.checkpoint_verifies(run)  # no sidecar yet
    digest = reduce.checkpoint_digest(str(ckpt))
    (tmp_path / "epoch=3-cifar10-preempt.sha256").write_text(f"{digest}  {ckpt.name}\n")
    assert reduce.checkpoint_verifies(run)
    (ckpt / "a.bin").write_bytes(b"abd")
    assert not reduce.checkpoint_verifies(run)
    assert not reduce.checkpoint_verifies({"events": []})


def test_the_digest_is_the_trainers():
    """The copy under benchmarks/ must agree with the program's rule, or every
    run would fail its checkpoint check."""
    from simclr_tpu.utils.checkpoint import checkpoint_digest

    here = os.path.dirname(os.path.abspath(__file__))
    assert reduce.checkpoint_digest(here) == checkpoint_digest(here)


def test_good_run_is_not_mutated_by_the_readers():
    run = good_run()
    before = copy.deepcopy(run)
    reduce.verdict(run), reduce.measured(run), reduce.attempted_and_failed(run)
    assert run == before
