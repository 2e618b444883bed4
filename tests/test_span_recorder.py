"""The trainer's span recorder (``simclr_tpu/obs/trace.py``): the process-wide
``SpanRecorder``, its flush file and clock anchor, and where ``run_pretrain``
flushes it.

Everything but the last two tests is plain Python on the recorder; those two
run a toy pretraining in this process (one compile each) and end it as a
preemption and as an exception, which is what a flush in ``finally`` is for.
"""

import json
import os
import signal
import threading
import time

import pytest

from simclr_tpu.obs.trace import (
    DEVICE_SCOPES,
    RING_CAPACITY,
    STEP_SPANS,
    TRAIN_SPANS,
    SpanRecorder,
    read_spans,
    recorder,
    spans_path,
)

pytestmark = pytest.mark.obs


def _by_name(spans):
    out = {}
    for s in spans:
        out.setdefault(s["name"], []).append(s)
    return out


class TestRecording:
    def test_parent_is_the_span_open_on_the_same_thread(self):
        rec = SpanRecorder()
        with rec.span("epoch", epoch=3, step=7) as epoch:
            with rec.span("dispatch") as dispatch:
                pass
            with rec.span("beat"):
                assert rec.current().name == "beat"
        spans = _by_name(rec.drain())
        assert spans["epoch"][0]["parent"] == 0
        assert spans["dispatch"][0]["parent"] == epoch.id
        assert spans["beat"][0]["parent"] == epoch.id
        assert dispatch.id != epoch.id
        # a child serves its parent's step and epoch unless told otherwise
        assert spans["dispatch"][0]["step"] == 7 and spans["dispatch"][0]["epoch"] == 3
        for s in spans["epoch"] + spans["dispatch"]:
            assert s["t1"] >= s["t0"] > 0 and s["thread"] == "MainThread"
            assert "open" not in s

    def test_each_thread_has_a_stack_of_its_own_and_step_ties_them(self):
        """The prefetch thread gathers and copies batch k while the main
        thread dispatches batch k-1: neither is the other's parent, and the
        ``step`` both carry is what joins them."""
        rec = SpanRecorder()
        gathered = threading.Event()

        def prefetch():
            with rec.span("gather", step=5, epoch=1, n=2048):
                pass
            with rec.span("device_put", step=5, epoch=1, n=6299648):
                gathered.set()
                time.sleep(0.02)

        worker = threading.Thread(target=prefetch, name="prefetch")
        with rec.span("epoch", epoch=1, step=4) as epoch:
            worker.start()
            assert gathered.wait(10)
            with rec.span("dispatch"):
                # the other thread's open span is not this thread's
                assert rec.current().name == "dispatch"
                assert rec.current(worker.ident).name == "device_put"
            epoch.step = 5
            with rec.span("dispatch"):
                pass
        worker.join(10)
        assert not worker.is_alive()
        spans = _by_name(rec.drain())
        assert [s["parent"] for s in spans["gather"] + spans["device_put"]] == [0, 0]
        assert {s["thread"] for s in spans["gather"] + spans["device_put"]} == {"prefetch"}
        assert [s["step"] for s in spans["dispatch"]] == [4, 5]
        of_step_5 = {s["name"] for ss in spans.values() for s in ss if s.get("step") == 5}
        assert {"gather", "device_put", "dispatch"} <= of_step_5
        assert spans["device_put"][0]["n"] == 6299648

    def test_a_span_left_open_by_an_exception_goes_with_its_parent(self):
        rec = SpanRecorder()
        with pytest.raises(RuntimeError):
            with rec.span("boundary"):
                rec.begin("boundary/results")  # never ended: the call raised
                raise RuntimeError("disk full")
        assert rec.current() is None
        with rec.span("epoch"):
            pass
        assert _by_name(rec.drain())["epoch"][0]["parent"] == 0

    def test_add_takes_times_from_elsewhere(self):
        rec = SpanRecorder()
        with rec.span("preempt") as pre:
            rec.add("preempt/notice", 1_000, 5_000, parent=pre.id, step=9)
        notice = _by_name(rec.drain())["preempt/notice"][0]
        assert (notice["t0"], notice["t1"], notice["parent"]) == (1_000, 5_000, pre.id)

    def test_the_ring_is_bounded_and_boot_spans_survive_it(self):
        """10^5 step spans pass through a ring of RING_CAPACITY; the rare
        spans are kept apart, so a long run's boot is still there."""
        rec = SpanRecorder()
        for name in ("boot/runtime", "boot/data", "boot/state"):
            with rec.span(name):
                pass
        for step in range(100_000):
            with rec.span("dispatch", step=step):
                pass
        assert RING_CAPACITY >= 16_384
        assert len(rec) == RING_CAPACITY + 3
        spans = _by_name(rec.drain())
        assert set(spans) == {"boot/runtime", "boot/data", "boot/state", "dispatch"}
        assert [s["step"] for s in spans["dispatch"]][-1] == 99_999
        assert len(spans["dispatch"]) == RING_CAPACITY
        assert len(rec) == 0  # a drain empties it

    def test_the_names_are_one_vocabulary(self):
        assert STEP_SPANS <= set(TRAIN_SPANS)
        assert len(set(TRAIN_SPANS)) == len(TRAIN_SPANS)
        assert DEVICE_SCOPES == (
            "augment", "encoder", "head", "ntxent", "grad_reduce", "lars"
        )
        assert recorder() is recorder()  # one per process


class TestFlush:
    def test_nothing_is_written_before_a_flush(self, tmp_path):
        rec = SpanRecorder()
        path = spans_path(str(tmp_path / "run"))
        for step in range(100):
            with rec.span("dispatch", step=step):
                pass
        assert not os.path.exists(os.path.dirname(path))
        assert rec.flush(path) == 100
        assert os.path.exists(path)
        # and nothing twice: the next flush holds only what came after
        with rec.span("beat"):
            pass
        assert rec.flush(path) == 1
        with open(path) as f:
            rows = [json.loads(line) for line in f]
        headers = [r for r in rows if "header" in r]
        assert len(headers) == 2 and len(rows) == 2 + 101
        assert [h["spans"] for h in headers] == [100, 1]
        assert headers[0]["pid"] == os.getpid() and headers[0]["process_index"] == 0

    def test_the_anchor_maps_monotonic_to_wall_within_a_millisecond(self, tmp_path):
        rec = SpanRecorder()
        path = spans_path(str(tmp_path))
        wall_before = time.time_ns()
        with rec.span("boot/data"):
            time.sleep(0.01)
        wall_after = time.time_ns()
        rec.flush(path)
        (span,) = read_spans(path)
        start_wall = span["t0"] + span["wall_offset_ns"]
        end_wall = span["t1"] + span["wall_offset_ns"]
        assert wall_before - 1_000_000 <= start_wall <= end_wall <= wall_after + 1_000_000
        # and the benchmark's conductor stamps with time.monotonic(): the
        # same clock as a span's t0, with no conversion at all
        assert abs(time.monotonic() - time.monotonic_ns() / 1e9) < 1e-3

    def test_an_open_span_is_written_as_open_and_once_more_when_it_ends(self, tmp_path):
        """What a crash or a stall was sitting in is in the file; a reader
        sees the span once, under its last line."""
        rec = SpanRecorder()
        path = spans_path(str(tmp_path))
        preempt = rec.begin("preempt", step=13)
        with rec.span("preempt/flush"):
            rec.flush(path)
        rec.end(preempt)
        rec.flush(path)
        with open(path) as f:
            rows = [json.loads(line) for line in f if "header" not in line]
        assert [(r["name"], r.get("open", False)) for r in rows] == [
            ("preempt", True), ("preempt/flush", True),
            ("preempt", False), ("preempt/flush", False),  # oldest first
        ]
        spans = read_spans(path)
        assert [s["name"] for s in spans] == ["preempt", "preempt/flush"]
        assert not any(s.get("open") for s in spans)

    def test_other_processes_write_beside_process_zero(self, tmp_path):
        assert spans_path(str(tmp_path)).endswith("spans.jsonl")
        assert spans_path(str(tmp_path), 3).endswith("spans.p3.jsonl")
        assert read_spans(str(tmp_path / "absent.jsonl")) == []

    def test_recording_and_flushing_touch_no_device(self, tmp_path, monkeypatch):
        """No span may add a device sync: neither recording nor writing goes
        near the fences the trainer counts."""
        import jax

        from simclr_tpu.utils import profiling

        def fence(*_a, **_k):
            raise AssertionError("the recorder waited for the device")

        monkeypatch.setattr(profiling, "synchronize", fence)
        monkeypatch.setattr(jax, "block_until_ready", fence)
        monkeypatch.setattr(jax, "device_get", fence)
        rec = SpanRecorder()
        with rec.span("epoch", epoch=1):
            for step in range(10):
                with rec.span("dispatch", step=step):
                    pass
        assert rec.flush(spans_path(str(tmp_path))) == 11


def test_the_timeline_puts_each_threads_spans_on_a_row_of_the_hosts_track(tmp_path):
    """``python -m simclr_tpu.obs.timeline <run_dir>``: the recorder's reader
    outside the benchmark, beside the events of the same run."""
    from simclr_tpu.obs.events import EventLog
    from simclr_tpu.obs.timeline import PID_HOST_BASE, TID_SPAN_BASE, build_timeline

    run_dir = str(tmp_path)
    EventLog(run_dir).emit("run_start", entry="pretrain")
    rec = SpanRecorder()
    with rec.span("epoch", epoch=1, step=1):
        with rec.span("dispatch"):
            time.sleep(0.002)
    worker = threading.Thread(
        target=lambda: rec.span("gather", step=1, n=8).__enter__().__exit__(None, None, None),
        name="prefetch",
    )
    worker.start()
    worker.join(10)
    rec.flush(spans_path(run_dir))
    other = SpanRecorder()  # a second host's file
    with other.span("epoch", epoch=1):
        pass
    other.flush(spans_path(run_dir, 1), process_index=1)
    open(os.path.join(run_dir, "heartbeat.p1.json"), "w").write("{}")

    events = build_timeline(run_dir)["traceEvents"]
    rows = {
        (e["pid"], e["args"]["name"]): e["tid"] for e in events
        if e["ph"] == "M" and e["name"] == "thread_name"
    }
    assert set(rows) == {
        (PID_HOST_BASE, "spans: MainThread"), (PID_HOST_BASE, "spans: prefetch"),
        (PID_HOST_BASE + 1, "spans: MainThread"),
    }
    assert all(tid >= TID_SPAN_BASE for tid in rows.values())
    drawn = {(e["pid"], e["tid"], e["name"]): e for e in events if e["ph"] == "X"}
    dispatch = drawn[(PID_HOST_BASE, rows[(PID_HOST_BASE, "spans: MainThread")], "dispatch")]
    assert dispatch["dur"] >= 2000 and dispatch["args"] == {"step": 1, "epoch": 1}
    gather = drawn[(PID_HOST_BASE, rows[(PID_HOST_BASE, "spans: prefetch")], "gather")]
    assert gather["args"] == {"step": 1, "n": 8}
    # on the wall clock of the events: the run_start instant comes first
    start = next(e for e in events if e["name"] == "run_start")
    assert start["ts"] <= dispatch["ts"] < start["ts"] + 5_000_000


# -- where run_pretrain flushes -------------------------------------------------

TOY = [
    "experiment.synthetic_data=true",
    "experiment.synthetic_size=64",
    "experiment.batches=4",  # x8 devices = global batch 32 -> 2 steps/epoch
    "parameter.warmup_epochs=1",
    "parameter.epochs=3",
    "experiment.save_model_epoch=1",
]


def _signal_at_step(monkeypatch, at_step: int):
    """SIGTERM as the supervisor sends it, once the loop has beaten at_step."""
    from simclr_tpu.supervisor.guard import RunGuard

    real_beat = RunGuard.beat

    def beat(self, step, epoch, **kw):
        real_beat(self, step, epoch, **kw)
        if step == at_step:
            self._on_signal(signal.SIGTERM, None)

    monkeypatch.setattr(RunGuard, "beat", beat)


def _fail_the_save(monkeypatch):
    from simclr_tpu import main

    def save_checkpoint(path, state):
        raise OSError("no space left on device")

    monkeypatch.setattr(main, "save_checkpoint", save_checkpoint)


@pytest.mark.parametrize("ending", ["preempted", "preempted_led", "exception"])
def test_run_pretrain_flushes_however_it_ends(tmp_path, monkeypatch, ending):
    from simclr_tpu.config import load_config
    from simclr_tpu.main import run_pretrain
    from simclr_tpu.supervisor.guard import PreemptedRun

    save_dir = str(tmp_path / ending)
    overrides = TOY + [f"experiment.save_dir={save_dir}"]
    if ending == "preempted_led":
        # no save due after epoch 1: its boundary is led by step 3, which
        # goes out with epoch 2's first batch before the boundary opens
        overrides.append("experiment.save_model_epoch=3")
    cfg = load_config("config", overrides=overrides)
    if ending.startswith("preempted"):
        _signal_at_step(monkeypatch, at_step=3)  # mid-epoch 2
        with pytest.raises(PreemptedRun):
            run_pretrain(cfg)
    else:
        _fail_the_save(monkeypatch)
        with pytest.raises(OSError, match="no space left"):
            run_pretrain(cfg)
    assert len(recorder()) == 0 and recorder().current() is None  # left clean
    spans = read_spans(spans_path(save_dir))
    names = _by_name(spans)
    assert set(names) <= set(TRAIN_SPANS)
    assert {"boot/runtime", "boot/data", "boot/state", "boot/state/init",
            "boot/build_step", "boot/compile", "epoch", "dispatch", "beat",
            "input_wait", "gather", "device_put",
            "boundary/first_batch"} <= set(names)
    # the state is one compiled program
    (state,), (init,) = names["boot/state"], names["boot/state/init"]
    assert init["parent"] == state["id"] and 0 <= init["n"] <= 2
    # the first dispatch is a dispatch: the compilation sat in boot/build_step
    build, compiled = names["boot/build_step"][0], names["boot/compile"][0]
    assert compiled["parent"] == build["id"]
    assert names["dispatch"][0]["t0"] >= build["t1"] >= compiled["t1"]
    # one step's spans on both threads carry one number
    for step in (1, 2):
        assert {"gather", "device_put", "dispatch", "beat"} <= {
            s["name"] for s in spans if s.get("step") == step
        }
    if ending.startswith("preempted"):
        (preempt,) = names["preempt"]
        under = {s["name"] for s in spans if s["parent"] == preempt["id"]}
        assert under == {"preempt/notice", "checkpoint", "preempt/event",
                         "preempt/beat", "preempt/flush"}
        (checkpoint,) = [s for s in names["checkpoint"] if s["parent"] == preempt["id"]]
        assert [s["name"] for s in spans if s["parent"] == checkpoint["id"]] == [
            "checkpoint/drain", "checkpoint/save", "checkpoint/digest",
            "checkpoint/sidecar",
        ]
        assert not any(s.get("open") for s in spans)
        (boundary,) = names["boundary"]
        second = names["boundary/first_batch"][1]
        with open(spans_path(save_dir)) as f:
            flushes = sum("header" in line for line in f)
        if ending == "preempted":
            # epoch 1's boundary saved a checkpoint and flushed after it,
            # and stayed open until epoch 2's first batch was in hand
            assert flushes >= 3
            assert boundary["n"] == 0 and second["parent"] == boundary["id"]
        else:
            # the batch and step 3 before the boundary, which ends with its
            # results file; the signal, seen in step 3's beat, is acted on
            # after it
            assert flushes == 2 and boundary["n"] == 1 and second["parent"] == 0
            led = next(s for s in names["dispatch"] if s["step"] == 3)
            assert second["t1"] <= led["t0"] <= led["t1"] <= boundary["t0"]
            assert boundary["t1"] <= preempt["t0"]
            assert [s["name"] for s in spans if s["parent"] == boundary["id"]][-1] == "boundary/results"
    else:
        # the save that raised: its checkpoint span ended with the drain in
        # it and no save, and the boundary it sat in is written as open
        assert "checkpoint/drain" in names and "checkpoint/save" not in names
        assert {s["name"] for s in spans if s.get("open")} == {"boundary"}
