"""``boundary_led_pct`` against a hand-written ``spans.jsonl``: the share of
the measured epochs' boundaries that had the next epoch's first step queued
before their loss fetch, and the ``None`` cases."""

import os

import pytest
from test_bm_spans import SpanFile, reader


def led_boundary(f: SpanFile, at: float, epoch: int, n: int | None):
    """A boundary whose loss fetch ends at ``at``. Led (``n`` 1), the next
    epoch's first batch and first step come before it opens and it ends with
    its results file; not led, it stays open until that batch is in hand."""
    ids = {} if n is None else {"n": n}
    if n:
        first = f.add("boundary/first_batch", at - 1.22, at - 1.21, epoch=epoch + 1)
        f.add("input_wait", at - 1.22, at - 1.21, parent=first)
        lead = f.add("epoch", at - 1.21, at - 1.2, epoch=epoch + 1, step=2 * epoch + 1)
        f.add("dispatch", at - 1.21, at - 1.205, parent=lead, step=2 * epoch + 1)
    b = f.add("boundary", at - 1.2, at + 0.026, epoch=epoch, step=2 * epoch, **ids)
    f.add("boundary/loss_fetch", at - 1.2, at, parent=b)
    f.add("boundary/telemetry", at, at + 0.008, parent=b)
    f.add("boundary/results", at + 0.019, at + 0.020, parent=b)
    if not n:
        f.add("boundary/first_batch", at + 0.020, at + 0.026, parent=b, epoch=epoch + 1)


def run_record(tmp_path, led=(1, 1, 0, 1, 1), with_spans=True) -> dict:
    """First beat 167.0; epoch events seen at 170, 173, 176, 179; SIGTERM
    179.25. The boundary of epoch 1 ends the unmeasured first epoch, those of
    epochs 2-4 end the three measured ones, epoch 5's comes after SIGTERM."""
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir, exist_ok=True)
    f = SpanFile()
    events = [{"event": "run_start", "seen": 134.5, "global_batch": 2048}]
    for k, n in enumerate(led):
        at = 170.0 + 3 * k
        led_boundary(f, at, k + 1, n)
        events.append({"event": "epoch", "seen": at + 0.002, "epoch": k + 1,
                       "step": 2 * (k + 1), "loss": 8.3})
    if with_spans:
        f.write(run_dir, flush_at=184.0)
    return {
        "cell": {"name": "r18-b2048-1chip"}, "run_dir": run_dir, "events": events,
        "t_start": 100.0, "t_first_beat": 167.0, "t_sigterm": 179.25,
        "t_exit": 183.0, "capture": None, "trace": None,
    }


@pytest.mark.parametrize(
    "led, share",
    [
        ((1, 1, 0, 1, 1), 200.0 / 3),  # one of the three measured is not led
        ((0, 1, 1, 1, 0), 100.0),  # the ones outside the measured epochs: ignored
        ((1, 0, 0, 0, 1), 0.0),
    ],
)
def test_share_of_the_measured_boundaries_that_were_led(tmp_path, led, share):
    assert reader("boundary_led_pct")(run_record(tmp_path, led)) == pytest.approx(share)


def test_epochs_the_profiler_touched_are_left_out(tmp_path):
    run = run_record(tmp_path, (1, 0, 1, 1, 1))
    run["capture"] = (171.0, 172.0)  # inside the epoch that boundary 2 ends
    assert reader("boundary_led_pct")(run) == pytest.approx(100.0)


@pytest.mark.parametrize("case", ["no_spans_file", "no_n_on_the_boundaries", "nothing_measured"])
def test_none_where_there_is_nothing_to_read(tmp_path, case):
    if case == "no_spans_file":  # a program from before the recorder
        run = run_record(tmp_path, with_spans=False)
    elif case == "no_n_on_the_boundaries":  # a program from before the lead
        run = run_record(tmp_path, (None,) * 5)
    else:
        run = run_record(tmp_path)
        run["t_sigterm"] = None
    assert reader("boundary_led_pct")(run) is None
