"""The span readers (benchmarks/spans.py and the metric files that call it)
against a hand-written ``spans.jsonl`` and run record whose answers are
known, a hand-built device trace beside them, and the ``None`` cases."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import spans, xplane  # noqa: E402
from benchmarks.run import load_module  # noqa: E402

# Unix ns at monotonic 0, and at the trace's time 0: the trace began when
# the monotonic clock read 175 s
WALL_AT_MONO_0 = 1_790_000_000 * 10**9
TRACE_START_MONO_S = 175.0
OP = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p.0)"


def reader(name: str):
    return load_module("layer_metrics", name + ".py").read


class SpanFile:
    """Writes spans as the trainer's recorder does: ids count up, times in
    monotonic ns, a header before each flush."""

    def __init__(self):
        self.rows, self.next_id = [], 1

    def add(self, name, t0, t1, parent=0, thread="MainThread", **ids) -> int:
        row = {"id": self.next_id, "name": name, "t0": int(t0 * 1e9),
               "t1": int(t1 * 1e9), "parent": parent, "thread": thread, **ids}
        self.rows.append(row)
        self.next_id += 1
        return row["id"]

    def header(self, mono_s: float) -> dict:
        return {"header": "spans.jsonl", "mono_ns": int(mono_s * 1e9),
                "wall_ns": WALL_AT_MONO_0 + int(mono_s * 1e9), "pid": 4242,
                "process_index": 0}

    def write(self, run_dir, flush_at=182.0) -> None:
        with open(os.path.join(run_dir, "spans.jsonl"), "w") as f:
            for row in (self.header(flush_at), *self.rows):
                f.write(json.dumps(row) + "\n")


def boundary(f: SpanFile, at: float, epoch: int, host_ms: float, checkpoint=False):
    """A boundary whose loss fetch ends at ``at`` (the ``epoch`` event is
    seen just after) and whose host work lasts ``host_ms`` after it."""
    end = at + host_ms / 1e3
    b = f.add("boundary", at - 1.2, end, epoch=epoch, step=2 * epoch)
    f.add("boundary/loss_fetch", at - 1.2, at, parent=b)
    f.add("boundary/telemetry", at, at + 0.008, parent=b)
    f.add("beat", at + 0.008, at + 0.009, parent=b)
    f.add("boundary/log", at + 0.009, at + 0.016, parent=b)
    f.add("boundary/event", at + 0.016, at + 0.017, parent=b)
    if checkpoint:
        c = f.add("checkpoint", at + 0.017, at + 0.019, parent=b)
        f.add("checkpoint/drain", at + 0.017, at + 0.0171, parent=c)
        f.add("checkpoint/save", at + 0.0171, at + 0.018, parent=c)
        f.add("checkpoint/digest", at + 0.018, at + 0.0189, parent=c)
        f.add("checkpoint/sidecar", at + 0.0189, at + 0.019, parent=c)
    f.add("boundary/results", at + 0.019, at + 0.020, parent=b)
    first = f.add("boundary/first_batch", end - 0.006, end, parent=b, epoch=epoch + 1)
    f.add("input_wait", end - 0.006, end - 0.0001, parent=first)  # not a step's wait
    f.add("boundary/shuffle", at + 0.020, at + 0.021, thread="prefetch", epoch=epoch + 1)
    return end


def epoch(f: SpanFile, start: float, number: int, waits_ms=(0.05, 100.0)):
    """Two steps; the second ``next()`` is the one that finds the iterator
    spent (100 ms in the trainer: the prefetcher's poll)."""
    e = f.add("epoch", start, start + 1.7, epoch=number, step=2 * number - 1)
    t = start
    for k, wait in enumerate(waits_ms):
        step = 2 * number - 1 + k
        f.add("gather", t, t + 0.003, thread="prefetch", step=step, n=2048)
        f.add("device_put", t + 0.003, t + 0.004, thread="prefetch", step=step, n=6299648)
        f.add("dispatch", t, t + 0.003, parent=e, step=step)
        f.add("beat", t + 0.003, t + 0.004, parent=e, step=step)
        f.add("input_wait", t + 0.004, t + 0.004 + wait / 1e3, parent=e, step=step + 1)
        t += 0.2


def run_record(tmp_path, host_ms=(30.0, 26.0, 34.0, 28.0), with_spans=True) -> dict:
    """Process start at 100.0; first beat 167.0; epoch events seen at 170,
    173, 176, 179 (two steps each); SIGTERM 179.25; exit 181.75."""
    run_dir = str(tmp_path / "run")
    os.makedirs(run_dir, exist_ok=True)
    f = SpanFile()
    f.add("boot/runtime", 108.0, 110.0)
    f.add("boot/data", 110.0, 113.0)
    f.add("boot/state", 113.0, 134.0)
    build = f.add("boot/build_step", 134.5, 166.9)
    f.add("boot/compile", 135.0, 166.8, parent=build)
    events = [{"event": "run_start", "seen": 134.5, "global_batch": 2048}]
    end = 166.9
    for k, ms in enumerate(host_ms):
        at = 170.0 + 3 * k
        epoch(f, end, k + 1)
        end = boundary(f, at, k + 1, ms, checkpoint=(k == 1))
        events.append({"event": "epoch", "seen": at + 0.002, "epoch": k + 1,
                       "step": 2 * (k + 1), "loss": 8.3})
    epoch(f, end, 5, waits_ms=(0.05,))
    pre = f.add("preempt", 179.40, 181.72, epoch=5, step=9)
    f.add("preempt/notice", 179.30, 179.40, parent=pre)
    c = f.add("checkpoint", 179.40, 181.60, parent=pre)
    f.add("checkpoint/drain", 179.40, 180.00, parent=c)
    f.add("checkpoint/save", 180.00, 181.20, parent=c)
    f.add("checkpoint/digest", 181.20, 181.50, parent=c)
    f.add("checkpoint/sidecar", 181.50, 181.51, parent=c)
    f.add("preempt/event", 181.60, 181.61, parent=pre)
    f.add("preempt/beat", 181.61, 181.62, parent=pre)
    f.add("preempt/flush", 181.62, 181.64, parent=pre)
    if with_spans:
        f.write(run_dir)
    return {
        "cell": {"name": "r18-b2048-1chip"}, "run_dir": run_dir, "events": events,
        "t_start": 100.0, "t_first_beat": 167.0, "t_sigterm": 179.25,
        "t_exit": 181.75, "capture": None, "trace": None,
    }


def test_boot_spans_split_boot_s(tmp_path):
    run = run_record(tmp_path)
    assert reader("boot_runtime_s")(run) == pytest.approx(10.0)  # from process start
    assert reader("boot_data_s")(run) == pytest.approx(3.0)
    assert reader("boot_state_s")(run) == pytest.approx(21.0)


def test_the_preemption_has_three_parts_and_they_are_the_preempts_own(tmp_path):
    """The boundary checkpoint of epoch 2 took 2 ms; the parts read are those
    under ``preempt``, and they add up to SIGTERM-to-exit less the tail."""
    run = run_record(tmp_path)
    drain = reader("preempt_drain_s")(run)
    save, digest = reader("ckpt_save_s")(run), reader("ckpt_digest_s")(run)
    assert drain == pytest.approx(0.75)  # 179.25 -> 180.00: notice + drain
    assert save == pytest.approx(1.20)
    assert digest == pytest.approx(0.31)  # digest + sidecar
    assert (run["t_exit"] - run["t_sigterm"]) - (drain + save + digest) == pytest.approx(0.24)


def test_boundary_host_ms_is_the_mean_over_the_measured_boundaries(tmp_path):
    """Boundaries 2-4 begin their host work inside the measured epochs
    (170.002 .. 179.002); the first, whose ``epoch`` event opens the window,
    belongs to the warm-up epoch and is not counted."""
    run = run_record(tmp_path, host_ms=(550.0, 26.0, 34.0, 28.0))
    assert reader("boundary_host_ms")(run) == pytest.approx((26.0 + 34.0 + 28.0) / 3)
    # the profiler at work from 173.5 to 177.5: the pairs it touches go, as in
    # reduce.measured, and one boundary is left
    run = {**run_record(tmp_path), "capture": (173.5, 177.5)}
    assert spans.measured_intervals(run) == [(170.002, 173.002)]
    assert reader("boundary_host_ms")(run) == pytest.approx(26.0)


def test_input_wait_leaves_out_the_boundarys_first_batch(tmp_path):
    """Three measured epochs of two steps: each waited 0.05 + 100 ms inside
    its steps; the 5.9 ms of each first batch belong to the boundary."""
    run = run_record(tmp_path)
    assert reader("input_wait_ms")(run) == pytest.approx(3 * 100.05 / 6)


def test_self_time_is_a_span_less_its_children(tmp_path):
    run = run_record(tmp_path)
    all_spans = spans.of_run(run)["spans"]
    first = spans.named(all_spans, "boundary")[0]
    kids = {c["name"] for c in spans.children(all_spans, first)}
    assert {"boundary/loss_fetch", "boundary/telemetry", "beat", "boundary/log",
            "boundary/event", "boundary/results", "boundary/first_batch"} == kids
    # 1.23 s less fetch 1.2, telemetry 8, beat 1, log 7, event 1, results 1, first batch 6 ms
    assert spans.self_time(all_spans, first) == pytest.approx(0.006, abs=1e-6)
    report = spans.report(run["run_dir"])
    assert report["boot_s"]["boot/state"] == pytest.approx(21.0)
    assert report["preempt_s"]["checkpoint/save"] == pytest.approx(1.2)
    assert list(report["boundary_children_self_ms"])[0] == "boundary/loss_fetch"


def test_a_span_flushed_open_and_again_closed_counts_once(tmp_path):
    run = run_record(tmp_path)
    path = os.path.join(run["run_dir"], "spans.jsonl")
    with open(path) as f:
        lines = f.readlines()
    preempt = next(json.loads(l) for l in lines if '"preempt"' in l)
    opened = {**preempt, "t1": preempt["t1"] - 10**8, "open": True}
    with open(path, "w") as f:  # the open copy came with the earlier flush
        f.writelines([lines[0], json.dumps(opened) + "\n", *lines])
    headers, read = spans.read_file(path)
    assert len(headers) == 2
    assert len(spans.named(read, "preempt")) == 1
    assert reader("ckpt_save_s")(run) == pytest.approx(1.2)


# -- beside a device trace -------------------------------------------------------

def traced(run: dict, ops, window) -> dict:
    device = xplane.DeviceTrace(index=0, ops=ops, async_ops=[], modules=[])
    run["trace"] = {
        "devices": [device], "window_ns": window,
        "start_wall_ns": WALL_AT_MONO_0 + int(TRACE_START_MONO_S * 1e9),
    }
    return run


def ns(mono_s: float) -> float:
    return (mono_s - TRACE_START_MONO_S) * 1e9


def test_idle_is_named_by_the_span_the_host_was_in_and_split_at_its_edges(tmp_path):
    """The device finishes epoch 3's last step 4 ms before the loss fetch
    returns at 176.0, stays idle through the boundary's 34 ms of host work
    and 3 ms into the next epoch's first dispatch; and idles 2 ms inside a
    step at 177.0, which no span excuses."""
    run = traced(
        run_record(tmp_path),
        ops=[
            (ns(175.5), ns(175.996) - ns(175.5), OP),
            (ns(176.037), ns(177.0) - ns(176.037), OP),
            (ns(177.002), ns(178.5) - ns(177.002), OP),
        ],
        window=(ns(175.5), ns(178.5)),
    )
    by = spans.idle_by_span(run)
    assert by["boundary/loss_fetch"] == pytest.approx(0.004, abs=1e-6)
    assert by["boundary/telemetry"] == pytest.approx(0.008, abs=1e-6)
    assert by["boundary/log"] == pytest.approx(0.007, abs=1e-6)
    assert by["input_wait"] == pytest.approx(0.0059, abs=1e-6)  # the first batch's
    assert by["dispatch"] == pytest.approx(0.003, abs=1e-6)
    assert sum(by.values()) == pytest.approx(0.043, abs=1e-6)
    # of 43 ms idle in 3 s, the 41 ms stretch touches the boundary's host
    # work and is the boundary's, fetch latency and first dispatch included;
    # the 2 ms inside a step are the loop's own
    assert reader("idle_in_steps_pct")(run) == pytest.approx(100 * 0.002 / 3.0, rel=1e-3)


def test_idle_while_the_host_only_waits_in_the_fetch_is_the_loops_own(tmp_path):
    """From 177.8 the host sits in boundary 4's loss fetch with steps still
    queued: a device that idles a millisecond then is not waiting for the
    boundary's host work."""
    run = traced(
        run_record(tmp_path),
        ops=[(ns(177.0), 1e9, OP), (ns(178.001), 0.4e9, OP)],
        window=(ns(177.0), ns(178.401)),
    )
    assert reader("idle_in_steps_pct")(run) == pytest.approx(100 * 0.001 / 1.401, rel=1e-3)
    assert reader("boundary_host_ms")(run) is not None  # from the measured epochs, not the trace


# -- nothing to read --------------------------------------------------------------

SPAN_METRICS = (
    "boot_runtime_s", "boot_data_s", "boot_state_s", "input_wait_ms",
    "boundary_host_ms", "idle_in_steps_pct", "preempt_drain_s", "ckpt_save_s",
    "ckpt_digest_s",
)


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_a_program_without_the_recorder_reads_as_none(tmp_path, name):
    """The parent commit writes no spans.jsonl: every reader leaves its
    metric out and none raises, traced or not."""
    run = run_record(tmp_path, with_spans=False)
    assert reader(name)(run) is None
    traced(run, ops=[(0.0, 1e9, OP)], window=(0.0, 1e9))
    assert reader(name)(run) is None


def test_none_where_the_window_or_the_trace_holds_nothing(tmp_path):
    run = run_record(tmp_path)
    assert reader("idle_in_steps_pct")(run) is None  # untraced
    assert spans.idle_by_span(run) is None
    # a window with one epoch event measures nothing
    short = {**run_record(tmp_path), "t_sigterm": 171.0}
    assert reader("boundary_host_ms")(short) is None
    assert reader("input_wait_ms")(short) is None
    # a trace file without its start time cannot be set beside the spans
    no_start = traced(run_record(tmp_path), ops=[(0.0, 1e9, OP)], window=(0.0, 1e9))
    no_start["trace"]["start_wall_ns"] = None
    assert reader("idle_in_steps_pct")(no_start) is None
    # a CPU trace: a file with its start time and no device plane
    no_device = traced(run_record(tmp_path), ops=[], window=None)
    assert reader("idle_in_steps_pct")(no_device) is None
    no_device["trace"]["devices"] = []
    assert reader("idle_in_steps_pct")(no_device) is None
    assert spans.idle_by_span(no_device) is None
    # a run that never reached its preemption
    killed = run_record(tmp_path)
    killed["t_sigterm"] = None
    assert reader("preempt_drain_s")(killed) is None


def test_a_torn_line_is_skipped(tmp_path):
    run = run_record(tmp_path)
    with open(os.path.join(run["run_dir"], "spans.jsonl"), "a") as f:
        f.write('{"id": 9999, "name": "dispa')
    assert reader("boot_data_s")(run) == pytest.approx(3.0)
