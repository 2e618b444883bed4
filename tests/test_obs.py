"""Observability suite (simclr_tpu/obs/, docs/OBSERVABILITY.md).

Covers the four tentpole layers plus their contracts:

* metric primitives — the new fixed-bucket :class:`Histogram` and the
  serve-tier back-compat shim: ``serve/metrics.py`` must re-export the SAME
  primitive classes and render ``/metrics`` byte-identically to the
  pre-refactor implementation (golden generated from that implementation);
* the :class:`Telemetry` registry — throughput/MFU/wire-bytes math against
  XLA's step cost and the compress model it reuses, snapshot shape;
* the ``events.jsonl`` timeline — atomic appends, attempt tagging, torn-line
  tolerance, and the resume re-seat discipline;
* the HTTP exporter — scrape/healthz/trace endpoints, port semantics;
* config validation ranges for the ``telemetry.*`` knobs;
* slow e2e proofs — a mid-run scrape adds ZERO ``synchronize`` calls to the
  training loop, and an injected hard crash under the supervisor yields ONE
  merged two-attempt timeline with no duplicated epoch events.
"""

import http.client
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

import simclr_tpu

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(simclr_tpu.__file__)))

from simclr_tpu.obs import metrics as obs_metrics
from simclr_tpu.obs.anomaly import StepAnomalyDetector, maybe_detector
from simclr_tpu.obs.events import (
    ENV_ATTEMPT,
    EventLog,
    events_path,
    read_events,
)
from simclr_tpu.obs.exporter import maybe_start_exporter, start_exporter
from simclr_tpu.obs.metrics import Histogram
from simclr_tpu.obs.report import build_report, load_baseline, render_report
from simclr_tpu.obs.trace import RequestTrace, TraceRecorder, clean_request_id
from simclr_tpu.utils.ioutil import atomic_append

pytestmark = pytest.mark.obs


# ---------------------------------------------------------------------------
# serve-tier back-compat shim
# ---------------------------------------------------------------------------

# Golden /metrics render generated from the PRE-refactor serve/metrics.py
# (primitives still private to the serve tier) with the exact feed sequence
# of _feed_serve_metrics below, extended in place when the serve tier grows
# a metric (client_disconnects_total rode in with request tracing). The
# shim must reproduce it byte for byte.
SERVE_GOLDEN = """\
# HELP simclr_serve_requests_total Embed requests accepted into the queue
# TYPE simclr_serve_requests_total counter
simclr_serve_requests_total 7
# HELP simclr_serve_rows_total Image rows accepted into the queue
# TYPE simclr_serve_rows_total counter
simclr_serve_rows_total 200
# HELP simclr_serve_rejected_total Embed requests rejected with backpressure (queue full)
# TYPE simclr_serve_rejected_total counter
simclr_serve_rejected_total 1
# HELP simclr_serve_failed_total Embed requests that failed in the engine
# TYPE simclr_serve_failed_total counter
simclr_serve_failed_total 0
# HELP simclr_serve_batches_total Engine batches dispatched
# TYPE simclr_serve_batches_total counter
simclr_serve_batches_total 4
# HELP simclr_serve_batch_requests_total Requests coalesced into dispatched batches
# TYPE simclr_serve_batch_requests_total counter
simclr_serve_batch_requests_total 10
# HELP simclr_serve_batch_rows_total Rows across dispatched batches
# TYPE simclr_serve_batch_rows_total counter
simclr_serve_batch_rows_total 180
# HELP simclr_serve_batch_capacity_total Padded bucket capacity across dispatched batches (rows)
# TYPE simclr_serve_batch_capacity_total counter
simclr_serve_batch_capacity_total 256
# HELP simclr_serve_compile_cache_hits_total Engine batches whose bucket was already warm (no compile)
# TYPE simclr_serve_compile_cache_hits_total counter
simclr_serve_compile_cache_hits_total 3
# HELP simclr_serve_compile_cache_misses_total Engine batches that compiled a cold bucket
# TYPE simclr_serve_compile_cache_misses_total counter
simclr_serve_compile_cache_misses_total 1
# HELP simclr_serve_recompile_alarms_total Buckets compiled after warmup completed — live traffic paid a compile
# TYPE simclr_serve_recompile_alarms_total counter
simclr_serve_recompile_alarms_total 0
# HELP simclr_serve_queue_depth Requests waiting in the batcher queue
# TYPE simclr_serve_queue_depth gauge
simclr_serve_queue_depth 2
# HELP simclr_serve_request_latency_ms Submit-to-result latency per request (milliseconds)
# TYPE simclr_serve_request_latency_ms summary
simclr_serve_request_latency_ms{quantile="0.5"} 2.5
simclr_serve_request_latency_ms{quantile="0.95"} 9.25
simclr_serve_request_latency_ms{quantile="0.99"} 9.85
simclr_serve_request_latency_ms_sum 14
simclr_serve_request_latency_ms_count 3
# HELP simclr_serve_batch_latency_ms Engine forward latency per dispatched batch (milliseconds)
# TYPE simclr_serve_batch_latency_ms summary
simclr_serve_batch_latency_ms{quantile="0.5"} 4.25
simclr_serve_batch_latency_ms{quantile="0.95"} 4.25
simclr_serve_batch_latency_ms{quantile="0.99"} 4.25
simclr_serve_batch_latency_ms_sum 4.25
simclr_serve_batch_latency_ms_count 1
# HELP simclr_serve_client_disconnects_total Responses dropped mid-write by a disconnecting client
# TYPE simclr_serve_client_disconnects_total counter
simclr_serve_client_disconnects_total 0
# HELP simclr_serve_neighbors_requests_total Neighbor-search requests answered
# TYPE simclr_serve_neighbors_requests_total counter
simclr_serve_neighbors_requests_total 2
# HELP simclr_serve_neighbors_queries_total Query rows across neighbor-search requests
# TYPE simclr_serve_neighbors_queries_total counter
simclr_serve_neighbors_queries_total 5
# HELP simclr_serve_neighbors_latency_ms On-device top-k latency per neighbors request (milliseconds)
# TYPE simclr_serve_neighbors_latency_ms summary
simclr_serve_neighbors_latency_ms{quantile="0.5"} 3.5
simclr_serve_neighbors_latency_ms{quantile="0.95"} 3.5
simclr_serve_neighbors_latency_ms{quantile="0.99"} 3.5
simclr_serve_neighbors_latency_ms_sum 3.5
simclr_serve_neighbors_latency_ms_count 1
# HELP simclr_serve_corpus_hbm_bytes Row-sharded retrieval corpus bytes resident in device HBM
# TYPE simclr_serve_corpus_hbm_bytes gauge
simclr_serve_corpus_hbm_bytes 0
# HELP simclr_serve_corpus_rows Embedding rows in the resident retrieval corpus
# TYPE simclr_serve_corpus_rows gauge
simclr_serve_corpus_rows 0
# HELP simclr_serve_ann_cells_probed IVF cells scored per query per shard (0 = exact scan)
# TYPE simclr_serve_ann_cells_probed gauge
simclr_serve_ann_cells_probed 0
# HELP simclr_serve_weights_generation Checkpoint generation the replica pool is serving (0 = startup weights)
# TYPE simclr_serve_weights_generation gauge
simclr_serve_weights_generation 0
# HELP simclr_serve_corpus_generation Encoder generation that embedded the resident retrieval corpus
# TYPE simclr_serve_corpus_generation gauge
simclr_serve_corpus_generation 0
# HELP simclr_serve_checkpoint_staleness_seconds Seconds since the serving generation's checkpoint was written
# TYPE simclr_serve_checkpoint_staleness_seconds gauge
simclr_serve_checkpoint_staleness_seconds 0
# HELP simclr_serve_weight_swaps_total Zero-downtime weight generation swaps committed to every replica
# TYPE simclr_serve_weight_swaps_total counter
simclr_serve_weight_swaps_total 0
# HELP simclr_serve_swap_rejected_total Checkpoint swaps refused (corrupt/unverified/incompatible); prior generation kept
# TYPE simclr_serve_swap_rejected_total counter
simclr_serve_swap_rejected_total 0
# HELP simclr_serve_avg_batch_fill Mean requests per dispatched batch
# TYPE simclr_serve_avg_batch_fill gauge
simclr_serve_avg_batch_fill 2.5
# HELP simclr_serve_batch_fill_ratio Mean rows over padded bucket capacity
# TYPE simclr_serve_batch_fill_ratio gauge
simclr_serve_batch_fill_ratio 0.703125
"""


def _feed_serve_metrics(m):
    m.requests_total.inc(7)
    m.rows_total.inc(200)
    m.rejected_total.inc()
    m.batches_total.inc(4)
    m.batch_requests_total.inc(10)
    m.batch_rows_total.inc(180)
    m.batch_capacity_total.inc(256)
    m.compile_cache_hits_total.inc(3)
    m.compile_cache_misses_total.inc(1)
    m.queue_depth.set(2)
    for v in (1.5, 2.5, 10.0):
        m.request_latency_ms.observe(v)
    m.batch_latency_ms.observe(4.25)
    m.neighbors_requests_total.inc(2)
    m.neighbors_queries_total.inc(5)
    m.neighbors_latency_ms.observe(3.5)


class TestServeShim:
    def test_primitives_are_the_same_classes(self):
        from simclr_tpu.serve import metrics as serve_metrics

        assert serve_metrics.Counter is obs_metrics.Counter
        assert serve_metrics.Gauge is obs_metrics.Gauge
        assert serve_metrics.Summary is obs_metrics.Summary
        assert serve_metrics.Histogram is obs_metrics.Histogram

    def test_serve_render_is_byte_identical_to_pre_refactor(self):
        from simclr_tpu.serve.metrics import ServeMetrics

        m = ServeMetrics()
        _feed_serve_metrics(m)
        assert m.render() == SERVE_GOLDEN


# ---------------------------------------------------------------------------
# Histogram primitive
# ---------------------------------------------------------------------------


class TestHistogram:
    def test_cumulative_buckets_and_inf(self):
        h = Histogram("t_seconds", "help", buckets=(0.1, 1.0, 10.0))
        for v in (0.05, 0.5, 50.0):
            h.observe(v)
        text = h.render()
        assert 't_seconds_bucket{le="0.1"} 1' in text
        assert 't_seconds_bucket{le="1"} 2' in text
        assert 't_seconds_bucket{le="10"} 2' in text
        assert 't_seconds_bucket{le="+Inf"} 3' in text
        assert "t_seconds_sum 50.55" in text
        assert "t_seconds_count 3" in text
        assert h.count == 3 and h.sum == pytest.approx(50.55)

    def test_le_is_inclusive(self):
        # Prometheus le semantics: a value equal to a bound counts in it
        h = Histogram("t", "help", buckets=(1.0, 2.0))
        h.observe(1.0)
        assert 't_bucket{le="1"} 1' in h.render()

    def test_empty_histogram_renders_zeros(self):
        h = Histogram("t", "help", buckets=(1.0,))
        text = h.render()
        assert 't_bucket{le="1"} 0' in text
        assert 't_bucket{le="+Inf"} 0' in text
        assert "t_count 0" in text

    def test_unsorted_bounds_are_sorted(self):
        h = Histogram("t", "help", buckets=(5.0, 1.0))
        assert h.buckets == (1.0, 5.0)

    def test_no_buckets_rejected(self):
        with pytest.raises(ValueError, match="bucket"):
            Histogram("t", "help", buckets=())


# ---------------------------------------------------------------------------
# Chip peak table
# ---------------------------------------------------------------------------


class TestChipPeaks:
    def test_v5e_row_and_source(self):
        from simclr_tpu.obs.peaks import chip_peak

        peak = chip_peak("tpu", "TPU v5 lite")
        assert peak.bf16_flops_per_s == 197e12
        assert peak.hbm_bytes_per_s == 819e9
        assert "TPU v5e" in peak.source

    def test_cpu_has_no_peak(self):
        from simclr_tpu.obs.peaks import chip_peak

        assert chip_peak("cpu", "cpu") is None

    def test_unknown_accelerator_is_an_error(self):
        from simclr_tpu.obs.peaks import chip_peak

        with pytest.raises(KeyError, match="TPU v9"):
            chip_peak("tpu", "TPU v9")

    def test_scripts_read_the_same_table(self):
        """roofline_model.py models the v5e from the table's row, not from a
        constant of its own."""
        import importlib.util

        from simclr_tpu.obs.peaks import CHIP_PEAKS, V5E

        spec = importlib.util.spec_from_file_location(
            "roofline", os.path.join(REPO_ROOT, "scripts", "roofline_model.py")
        )
        roofline = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(roofline)
        assert roofline.PEAK is CHIP_PEAKS[V5E]


# ---------------------------------------------------------------------------
# Telemetry registry
# ---------------------------------------------------------------------------


class TestTelemetry:
    def _make(self, **kw):
        from simclr_tpu.obs.telemetry import Telemetry

        from simclr_tpu.obs.peaks import CHIP_PEAKS, V5E

        base = dict(
            global_batch=64, n_devices=8,
            peak_flops=CHIP_PEAKS[V5E].bf16_flops_per_s,
        )
        base.update(kw)
        flops = base.pop("step_flops", 5.86e9 * 8)
        t = Telemetry(**base)
        if flops:
            # what the compile sentry reports for the step program
            t.observe_xla_cost("pretrain_step", flops_per_step=flops)
        return t

    def test_flops_are_xlas_count_of_the_newest_step_program(self):
        """The MFU gauge divides the compiler's own FLOPs of the newest
        watched program (every one runs one step a call), never a model's."""
        from simclr_tpu.obs.compile import CompileSentry

        t = self._make(step_flops=None)
        assert t.flops_per_step is None
        sentry = CompileSentry(telemetry=t)
        sentry.record_compile("supervised_step", seconds=1.0, flops=1e9)
        assert t.flops_per_step == pytest.approx(1e9)
        sentry.record_compile("pretrain_step", seconds=1.0, flops=3e9)
        assert t.flops_per_step == pytest.approx(3e9)
        assert "mfu_roofline_xla_drift" not in t.render()

    def test_observe_epoch_sets_rates_and_mfu(self):
        t = self._make()
        t.observe_epoch(
            3, epochs=10, step=6, steps=2, seconds=4.0, loss=1.5, lr=0.1
        )
        assert t.epoch.value == 3 and t.step.value == 6
        assert t.loss.value == 1.5 and t.lr.value == pytest.approx(0.1)
        assert t.imgs_per_sec.value == pytest.approx(2 * 64 / 4.0)
        assert t.imgs_per_sec_per_chip.value == pytest.approx(2 * 64 / 4.0 / 8)
        # step_time = 2.0s; MFU = flops / (step_time * peak)
        assert t.mfu.value == pytest.approx(
            t.flops_per_step / (2.0 * t.peak_flops)
        )
        assert t.step_time.count == 1

    def test_no_step_program_means_honest_zero_mfu(self):
        t = self._make(step_flops=None)
        assert t.flops_per_step is None
        t.observe_epoch(1, epochs=2, step=2, steps=2, seconds=1.0, loss=1.0, lr=0.1)
        assert t.mfu.value == 0.0
        assert t.imgs_per_sec.value > 0  # throughput still reported

    def test_no_peak_means_no_mfu(self):
        """The CPU platform has no row in the peak table (chip_peak -> None):
        throughput is reported, utilization is not."""
        t = self._make(peak_flops=None)
        assert t.flops_per_step
        t.observe_epoch(1, epochs=2, step=2, steps=2, seconds=1.0, loss=1.0, lr=0.1)
        assert t.mfu.value == 0.0
        assert "exposed_comm" not in t.render()
        assert t.imgs_per_sec.value > 0

    def test_a_costless_executable_leaves_the_flops_alone(self):
        """A backend whose cost analysis reports no flops (0.0) must not
        zero the gauge's numerator."""
        t = self._make(step_flops=2e9)
        t.observe_xla_cost("pretrain_step", flops_per_step=0.0)
        assert t.flops_per_step == pytest.approx(2e9)

    def test_wire_bytes_match_compress_model(self):
        from simclr_tpu.parallel.compress import allreduce_wire_bytes

        t = self._make(
            grad_allreduce="int8", grad_elements=11_000_000, allreduce_devices=4
        )
        assert t.allreduce_wire_bytes.value == pytest.approx(
            allreduce_wire_bytes(11_000_000, 4, "int8")
        )
        assert (
            'simclr_train_grad_allreduce_mode{mode="int8"} 1' in t.render()
        )

    def test_snapshot_shape(self):
        t = self._make()
        t.observe_epoch(1, epochs=2, step=2, steps=2, seconds=1.0, loss=2.5, lr=0.3)
        snap = t.snapshot()
        assert set(snap) == {
            "epoch", "step", "loss", "lr", "step_time_s", "imgs_per_sec",
            "imgs_per_sec_per_chip", "mfu", "slow_steps",
            "stalls", "auto_traces", "compiles", "recompile_alarms",
            "uptime_s", "mesh_hosts",
        }
        assert snap["mesh_hosts"] == 1.0
        assert snap["loss"] == 2.5
        # the fleet straggler ratio divides these across hosts
        assert snap["step_time_s"] == pytest.approx(0.5)
        assert json.loads(json.dumps(snap)) == snap  # heartbeat-serializable

    def test_checkpoint_and_rollback_counters(self):
        t = self._make()
        t.observe_save(1.25)
        t.observe_restore(0.5)
        t.record_nan_rollback()
        assert t.checkpoint_saves.value == 1
        assert t.checkpoint_save_seconds.count == 1
        assert t.checkpoint_restore_seconds.sum == pytest.approx(0.5)
        assert t.nan_rollbacks.value == 1

    def test_anomaly_counters(self):
        t = self._make()
        t.record_slow_step()
        t.record_slow_step()
        t.record_stall()
        t.record_auto_trace()
        t.record_scrape_disconnect()
        assert t.anomaly_slow_steps.value == 2
        assert t.anomaly_stalls.value == 1
        assert t.auto_traces.value == 1
        assert t.scrape_disconnects.value == 1
        text = t.render()
        assert "simclr_train_anomaly_slow_steps_total 2" in text
        assert "simclr_train_anomaly_stalls_total 1" in text
        assert "simclr_train_auto_traces_total 1" in text
        assert "simclr_train_scrape_disconnects_total 1" in text
        snap = t.snapshot()
        assert snap["slow_steps"] == 2.0 and snap["stalls"] == 1.0
        assert snap["auto_traces"] == 1.0


# ---------------------------------------------------------------------------
# events.jsonl timeline
# ---------------------------------------------------------------------------


class TestEventLog:
    def test_emit_read_roundtrip(self, tmp_path):
        log = EventLog(str(tmp_path))
        log.emit("run_start", epochs=3)
        log.emit("epoch", epoch=1, loss=2.5)
        events = read_events(events_path(str(tmp_path)))
        assert [e["event"] for e in events] == ["run_start", "epoch"]
        assert events[1]["epoch"] == 1 and events[1]["loss"] == 2.5
        for e in events:
            assert "time" in e and "monotonic" in e and e["attempt"] == 1

    def test_attempt_from_supervisor_env(self, tmp_path, monkeypatch):
        monkeypatch.setenv(ENV_ATTEMPT, "3")
        log = EventLog(str(tmp_path))
        log.emit("resume", epoch=2)
        assert read_events(log.path)[0]["attempt"] == 3

    def test_explicit_fields_override_defaults(self, tmp_path):
        # the supervisor runner stamps the attempt that just exited, not its
        # own (always-1) environment
        log = EventLog(str(tmp_path))
        log.emit("child_exit", attempt=4, exit=77)
        assert read_events(log.path)[0]["attempt"] == 4

    def test_disabled_log_is_a_noop(self, tmp_path):
        log = EventLog(str(tmp_path), enabled=False)
        log.emit("run_start")
        log.reseat(1)
        assert not os.path.exists(log.path)

    def test_reseat_drops_only_rerunnable_events(self, tmp_path):
        log = EventLog(str(tmp_path))
        log.emit("run_start", epochs=3)
        log.emit("epoch", epoch=1)
        log.emit("checkpoint", epoch=1)
        log.emit("epoch", epoch=2)
        log.emit("checkpoint", epoch=2)
        log.emit("nan_rollback", epoch=2)  # forensic: must survive
        log.emit("preempt", epoch=2, step=3)  # forensic: must survive
        log.reseat(2)
        kinds = [(e["event"], e.get("epoch")) for e in read_events(log.path)]
        assert kinds == [
            ("run_start", None), ("epoch", 1), ("checkpoint", 1),
            ("nan_rollback", 2), ("preempt", 2),
        ]

    def test_torn_final_line_is_skipped_and_dropped_by_reseat(self, tmp_path):
        log = EventLog(str(tmp_path))
        log.emit("epoch", epoch=1)
        with open(log.path, "a") as f:
            f.write('{"event": "epoch", "epo')  # SIGKILL mid-write
        assert [e["epoch"] for e in read_events(log.path)] == [1]
        log.reseat(5)  # keeps epoch 1, rewrites without the torn tail
        lines = open(log.path).read().splitlines()
        assert len(lines) == 1 and json.loads(lines[0])["epoch"] == 1

    def test_read_missing_file_is_empty(self, tmp_path):
        assert read_events(str(tmp_path / "nope.jsonl")) == []

    def test_atomic_append_creates_and_appends(self, tmp_path):
        path = str(tmp_path / "x.log")
        atomic_append(path, "a\n")
        atomic_append(path, "b\n")
        assert open(path).read() == "a\nb\n"


# ---------------------------------------------------------------------------
# request tracing (obs/trace.py)
# ---------------------------------------------------------------------------


class TestRequestTrace:
    def test_clean_request_id(self):
        assert clean_request_id("req-42") == "req-42"
        # whitespace and unprintables stripped, never passed through
        assert clean_request_id("  a b\tc\n ") == "abc"
        assert len(clean_request_id("x" * 500)) == 128
        # absent or unusable header -> a fresh generated id
        assert len(clean_request_id(None)) == 16
        assert len(clean_request_id("\x00\x01 ")) == 16
        assert clean_request_id(None) != clean_request_id(None)

    def test_span_math(self):
        trace = RequestTrace("rid")
        t0 = trace.t0
        trace.add("a", t0, t0 + 0.010)
        trace.add("b", t0 + 0.010, t0 + 0.025)
        assert trace.total_s() == pytest.approx(0.025)
        d = trace.to_dict()
        assert d["request_id"] == "rid"
        assert d["total_ms"] == pytest.approx(25.0)
        assert [s["name"] for s in d["spans"]] == ["a", "b"]
        assert d["spans"][1]["start_ms"] == pytest.approx(10.0)
        assert d["spans"][1]["dur_ms"] == pytest.approx(15.0)

    def test_span_context_manager(self):
        trace = RequestTrace()
        with trace.span("serialize"):
            pass
        ((name, start, end),) = trace.spans()
        assert name == "serialize" and end >= start


class TestTraceRecorder:
    def _trace(self, total_ms, rid=None):
        trace = RequestTrace(rid)
        trace.add("work", trace.t0, trace.t0 + total_ms / 1000.0)
        return trace

    def test_keeps_only_the_slowest_ordered(self):
        rec = TraceRecorder(capacity=3)
        for ms in (1, 5, 3, 2, 4):
            rec.record(self._trace(ms))
        assert [r["total_ms"] for r in rec.slowest()] == [5.0, 4.0, 3.0]

    def test_deterministic_sampling_into_sidecar(self, tmp_path):
        path = tmp_path / "requests.jsonl"
        rec = TraceRecorder(sample_rate=0.5, path=str(path))
        for i in range(4):
            rec.record(self._trace(1, rid=f"r{i}"))
        lines = [json.loads(line) for line in open(path)]
        # accumulator sampling: rate 0.5 means exactly every 2nd request
        assert [l["request_id"] for l in lines] == ["r1", "r3"]
        assert all("time" in l and l["spans"] for l in lines)

    def test_rate_zero_writes_nothing(self, tmp_path):
        path = tmp_path / "requests.jsonl"
        TraceRecorder(sample_rate=0.0, path=str(path)).record(self._trace(1))
        assert not path.exists()

    def test_bad_params_rejected(self):
        with pytest.raises(ValueError, match="sample_rate"):
            TraceRecorder(sample_rate=1.5)
        with pytest.raises(ValueError, match="capacity"):
            TraceRecorder(capacity=0)


# ---------------------------------------------------------------------------
# step anomaly detection (obs/anomaly.py)
# ---------------------------------------------------------------------------


class _AnomalyCounters:
    """Telemetry duck type recording the anomaly hook calls."""

    def __init__(self):
        self.slow = self.stall = self.trace = 0

    def record_slow_step(self):
        self.slow += 1

    def record_stall(self):
        self.stall += 1

    def record_auto_trace(self):
        self.trace += 1


class _StepTime(_AnomalyCounters):
    """A telemetry that knows the last epoch's mean step time."""

    def __init__(self, step_time_s):
        super().__init__()
        self.step_time_s = step_time_s


def _fake_clock(start=100.0):
    state = {"t": start}
    return state, (lambda: state["t"])


class TestAnomalyDetector:
    def test_steady_stream_never_flags(self, tmp_path):
        # sub-percent jitter around a constant step time (MAD ~ 0) must not
        # flag: the MAD floor absorbs it
        state, clock = _fake_clock()
        det = StepAnomalyDetector(str(tmp_path), warmup=4, clock=clock)
        try:
            for i in range(50):
                state["t"] += 0.1 if i % 2 else 0.101
                assert det.tick(i) is None
            assert det.slow_steps == 0
        finally:
            det.close()

    def test_slow_step_classifies_and_records(self, tmp_path):
        state, clock = _fake_clock()
        events = EventLog(str(tmp_path))
        telem = _AnomalyCounters()
        # a window the steady ticks fill: a tick that stands out before the
        # window has filled once is the end of the run-ahead burst (below)
        det = StepAnomalyDetector(
            str(tmp_path), warmup=4, window=8, events=events, telemetry=telem,
            clock=clock,
        )
        try:
            for i in range(10):
                state["t"] += 0.1
                det.tick(i, epoch=1)
            assert det.slow_steps == 0
            state["t"] += 1.0  # 10x the median step time
            assert det.tick(10, epoch=2) == "slow_step"
            assert det.slow_steps == 1 and telem.slow == 1
        finally:
            det.close()
        (slow,) = [
            e for e in read_events(events.path) if e["event"] == "slow_step"
        ]
        assert slow["step"] == 10 and slow["epoch"] == 2
        assert slow["seconds"] == pytest.approx(1.0)
        assert slow["median_s"] == pytest.approx(0.1)
        assert slow["threshold_s"] < 1.0

    def test_run_ahead_burst_does_not_seed_the_window(self, tmp_path):
        """On the chip the host dispatches 11 steps ahead in ~5 ms each, then
        every dispatch blocks for a device step (~126 ms). The median of the
        burst called every steady step slow: ~20 events in a healthy run."""
        state, clock = _fake_clock()
        events = EventLog(str(tmp_path))
        det = StepAnomalyDetector(str(tmp_path), events=events, clock=clock)
        try:
            for i in range(11):
                state["t"] += 0.005
                assert det.tick(i, epoch=1) is None
            for i in range(11, 61):
                state["t"] += 0.126
                assert det.tick(i, epoch=1) is None
            assert det.slow_steps == 0
            assert min(det._samples) > 0.1  # seeded from blocked dispatches only
            # an epoch boundary's sync, then the next burst: the epoch's mean
            # step time is known now, and ticks far under it are dispatches
            det.pause()
            det.telemetry = _StepTime(0.127)
            for i in range(61, 72):
                state["t"] += 0.005
                assert det.tick(i, epoch=2) is None
            assert min(det._samples) > 0.1  # dispatches are not steps
            for i in range(72, 85):
                state["t"] += 0.126
                assert det.tick(i, epoch=2) is None
            # and a step that really is slow still alarms
            state["t"] += 1.0
            assert det.tick(85, epoch=2) == "slow_step"
        finally:
            det.close()
        assert [
            e["step"] for e in read_events(events.path)
            if e["event"] == "slow_step"
        ] == [85]

    def test_an_epoch_shorter_than_the_burst_never_alarms(self, tmp_path):
        """Four chips, 6-step epochs: the host dispatches a whole epoch in
        ~50 ms and waits 0.8 s in the boundary's loss fetch. Every tick is a
        dispatch of 8-15 ms; their jitter read as 14 slow steps in one run on
        the chip. The epoch's mean step time (140 ms) says none is a step."""
        state, clock = _fake_clock()
        events = EventLog(str(tmp_path))
        det = StepAnomalyDetector(
            str(tmp_path), events=events, clock=clock, stall_min_s=1e6
        )
        jitter = (0.0085, 0.0083, 0.0117, 0.0086, 0.0155, 0.0469)
        try:
            for epoch in range(1, 40):
                for k in range(6):
                    state["t"] += jitter[(epoch + k) % 6]
                    assert det.tick(6 * (epoch - 1) + k + 1, epoch) is None
                det.pause()
                state["t"] += 0.8  # the loss fetch: the device's six steps
                det.telemetry = _StepTime(0.140)
            assert det.slow_steps == 0
            # dispatches are not samples: the first epoch's five, taken
            # before any epoch's mean was known, and the one of 47 ms an epoch
            assert all(dt > 0.035 for dt in list(det._samples)[5:])
            # a dispatch that hangs for seconds is still a step's worth
            det.tick(998, 40)  # the first tick after a pause only anchors
            state["t"] += 3.0
            det.tick(999, 40)
            assert det._samples[-1] == pytest.approx(3.0)
        finally:
            det.close()
        assert not [
            e for e in read_events(events.path) if e["event"] == "slow_step"
        ]

    def test_warmup_grace_swallows_early_outliers(self, tmp_path):
        # fewer than `warmup` samples (e.g. right after a compile) must never
        # classify, however extreme the duration
        state, clock = _fake_clock()
        det = StepAnomalyDetector(str(tmp_path), warmup=8, clock=clock)
        try:
            for i in range(4):
                state["t"] += 0.1
                det.tick(i)
            state["t"] += 50.0
            assert det.tick(4) is None and det.slow_steps == 0
        finally:
            det.close()

    def test_stall_watchdog_fires_while_loop_is_stuck(self, tmp_path):
        events = EventLog(str(tmp_path))
        telem = _AnomalyCounters()
        captured = []
        det = StepAnomalyDetector(
            str(tmp_path), warmup=2, stall_min_s=0.1, stall_factor=2.0,
            auto_trace=True, auto_trace_ms=10.0, auto_trace_cooldown_s=0.0,
            events=events, telemetry=telem,
            capture_fn=lambda d, s: captured.append((d, s)),
        )
        try:
            for i in range(4):
                det.tick(i, epoch=1)
                time.sleep(0.02)
            # go silent: the watchdog thread must report the stall itself
            deadline = time.monotonic() + 10
            while det.stalls == 0 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert det.stalls == 1 and telem.stall == 1
            deadline = time.monotonic() + 10
            while det.auto_traces == 0 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert det.auto_traces == 1 and telem.trace == 1
            # fire-once-per-arm: continued silence adds no second stall
            time.sleep(0.3)
            assert det.stalls == 1
        finally:
            det.close()
        stall_events = [
            e for e in read_events(events.path) if e["event"] == "stall"
        ]
        assert stall_events and stall_events[0]["silence_s"] > 0
        (trace_dir, seconds) = captured[0]
        assert seconds == pytest.approx(0.01)
        assert os.path.isdir(trace_dir)
        assert os.sep + "trace_auto" + os.sep in trace_dir
        (auto,) = [
            e for e in read_events(events.path) if e["event"] == "auto_trace"
        ]
        assert auto["reason"] == "stall" and auto["trace_dir"] == trace_dir

    def test_auto_trace_budget_and_failure_are_contained(self, tmp_path):
        state, clock = _fake_clock()

        def failing_capture(d, s):
            raise RuntimeError("profiler busy")

        det = StepAnomalyDetector(
            str(tmp_path), warmup=2, window=4, auto_trace=True,
            auto_trace_max=1, auto_trace_cooldown_s=0.0,
            capture_fn=failing_capture, clock=clock,
        )
        try:
            for i in range(6):
                state["t"] += 0.1
                det.tick(i)
            state["t"] += 5.0
            assert det.tick(6) == "slow_step"  # starts the capture thread
            deadline = time.monotonic() + 10
            while det._traces_started == 0 and time.monotonic() < deadline:
                time.sleep(0.02)
            # a failed capture spends the budget but counts nothing
            assert det.auto_traces == 0 and det._traces_started == 1
            state["t"] += 5.0
            det.tick(7)  # second anomaly: budget of 1 already spent
            assert det._traces_started == 1
        finally:
            det.close()

    def test_pause_prevents_stall_and_gap_sampling(self, tmp_path):
        det = StepAnomalyDetector(
            str(tmp_path), warmup=2, stall_min_s=0.1, stall_factor=2.0
        )
        try:
            for i in range(4):
                det.tick(i)
                time.sleep(0.02)
            det.pause()  # epoch-boundary work: probe / checkpoint I/O
            time.sleep(0.4)
            assert det.stalls == 0
            n = len(det._samples)
            det.tick(5)  # re-anchors without sampling the paused gap
            assert len(det._samples) == n
        finally:
            det.close()

    def test_maybe_detector_config_gate(self, tmp_path):
        from simclr_tpu.config import load_config

        cfg = load_config("config", overrides=["telemetry.anomaly=false"])
        assert maybe_detector(cfg, str(tmp_path)) is None
        cfg = load_config(
            "config",
            overrides=[
                "telemetry.anomaly_warmup=3", "telemetry.stall_min_s=7.5"
            ],
        )
        det = maybe_detector(cfg, str(tmp_path))
        try:
            assert det is not None
            assert det.warmup == 3 and det.stall_min_s == 7.5
        finally:
            det.close()


# ---------------------------------------------------------------------------
# run reports (obs/report.py)
# ---------------------------------------------------------------------------


class TestRunReport:
    def _run_dir(self, tmp_path):
        """Synthetic two-attempt run: attempt 1 stalls and is killed hung,
        attempt 2 finishes clean; final heartbeat carries telemetry."""
        from simclr_tpu.supervisor.heartbeat import (
            heartbeat_path,
            write_heartbeat,
        )

        run = tmp_path / "run"
        run.mkdir(exist_ok=True)
        log = EventLog(str(run))
        log.emit("run_start", epochs=3)
        log.emit("epoch", epoch=1)
        log.emit("checkpoint", epoch=1)
        log.emit("slow_step", step=3, epoch=2, seconds=1.0)
        log.emit("stall", step=4, epoch=2, silence_s=3.0)
        log.emit("auto_trace", reason="stall", trace_dir="t")
        log.emit("child_exit", attempt=1, exit=-9, hung=True)
        log.emit("restart", attempt=2)
        log.emit("run_start", attempt=2, epochs=3)
        log.emit("epoch", epoch=2, attempt=2)
        log.emit("epoch", epoch=3, attempt=2)
        write_heartbeat(
            heartbeat_path(str(run)), step=6, epoch=3,
            telemetry={"imgs_per_sec_per_chip": 80.0},
        )
        with open(run / "supervisor_summary.json", "w") as f:
            json.dump({"outcome": "clean", "exit": 0, "resumed": 1}, f)
        return str(run)

    def _baseline(self, tmp_path, value=100.0, shape="payload"):
        path = tmp_path / f"BENCH_{shape}.json"
        if shape == "payload":
            payload = {
                "captured_at": "2026-01-01",
                "payload": {
                    "metric": "pretrain_imgs_per_sec_per_chip",
                    "value": value,
                },
            }
        else:
            payload = {
                "n": 1,
                "parsed": {
                    "metric": "pretrain_imgs_per_sec_per_chip",
                    "value": value,
                },
            }
        path.write_text(json.dumps(payload))
        return str(path)

    def test_per_attempt_counts_and_stalled_attempts(self, tmp_path):
        report = build_report(self._run_dir(tmp_path))
        a1 = report["attempts"]["1"]
        assert a1["epochs"] == 1 and a1["checkpoints"] == 1
        assert a1["slow_steps"] == 1 and a1["stalls"] == 1
        assert a1["auto_traces"] == 1
        assert a1["exit"] == -9 and a1["hung"] is True
        assert report["attempts"]["2"]["epochs"] == 2
        assert report["stalled_attempts"] == [1]
        assert report["outcome"] == "clean"
        assert report["verdict"] == "NO_BASELINE"  # no --baseline given

    def test_verdict_ok_and_regression(self, tmp_path):
        run = self._run_dir(tmp_path)
        base = self._baseline(tmp_path, value=100.0)
        ok = build_report(run, baseline_path=base, threshold=0.8)
        assert ok["verdict"] == "OK"
        assert ok["throughput_ratio"] == pytest.approx(0.8)
        bad = build_report(run, baseline_path=base, threshold=0.9)
        assert bad["verdict"] == "REGRESSION"

    def test_baseline_shapes_and_failures(self, tmp_path):
        assert load_baseline(self._baseline(tmp_path)) == 100.0
        assert load_baseline(self._baseline(tmp_path, shape="parsed")) == 100.0
        assert load_baseline(str(tmp_path / "nope.json")) is None
        dead = tmp_path / "dead_probe.json"
        dead.write_text(json.dumps({"n": 3, "parsed": None}))
        assert load_baseline(str(dead)) is None

    def test_empty_run_dir_is_no_data(self, tmp_path):
        empty = tmp_path / "empty"
        empty.mkdir()
        report = build_report(
            str(empty), baseline_path=self._baseline(tmp_path)
        )
        assert report["verdict"] == "NO_DATA"

    def test_cli_prints_greppable_verdict_line(self, tmp_path, capsys):
        from simclr_tpu.obs import report as report_mod

        run = self._run_dir(tmp_path)
        out_json = tmp_path / "report.json"
        rc = report_mod.main(
            [run, "--baseline", self._baseline(tmp_path),
             "--json", str(out_json)]
        )
        assert rc == 0
        out = capsys.readouterr().out
        assert "stalled attempts: 1" in out
        # the verdict is the LAST line and greppable
        assert out.strip().splitlines()[-1].startswith("run_report verdict: OK")
        assert json.load(open(out_json))["verdict"] == "OK"


# ---------------------------------------------------------------------------
# HTTP exporter
# ---------------------------------------------------------------------------


class _StubTelemetry:
    """render()/snapshot() duck type — exporter tests need no jax."""

    def render(self):
        return "# HELP x y\n# TYPE x gauge\nx 1\n"

    def snapshot(self):
        return {"epoch": 7.0, "imgs_per_sec": 123.0}


class _DisconnectingScrapeTelemetry(_StubTelemetry):
    """render() far larger than the socket buffer, so a client that hangs
    up unread forces the server's write to fail mid-stream."""

    def __init__(self):
        self.disconnects = 0

    def render(self):
        return "# HELP x y\n# TYPE x gauge\nx 1\n" + "#" * (4 << 20) + "\n"

    def record_scrape_disconnect(self):
        self.disconnects += 1


def _get(url, timeout=10):
    with urllib.request.urlopen(url, timeout=timeout) as resp:
        return resp.status, resp.headers.get("Content-Type"), resp.read().decode()


def _post(url, timeout=60):
    req = urllib.request.Request(url, method="POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


@pytest.fixture
def exporter(tmp_path):
    exp = start_exporter(
        _StubTelemetry(), str(tmp_path), trace_max_ms=5000,
        ready_file=str(tmp_path / "ready.json"),
    )
    yield exp
    exp.close()


class TestExporter:
    def test_ready_file_publishes_ephemeral_port(self, exporter, tmp_path):
        info = json.load(open(tmp_path / "ready.json"))
        assert info == {
            "host": "127.0.0.1", "port": exporter.port, "pid": os.getpid()
        }
        assert exporter.port > 0

    def test_metrics_scrape(self, exporter):
        status, ctype, body = _get(
            f"http://127.0.0.1:{exporter.port}/metrics"
        )
        assert status == 200
        assert ctype == "text/plain; version=0.0.4"
        assert body == _StubTelemetry().render()

    def test_healthz_carries_snapshot(self, exporter):
        status, _, body = _get(f"http://127.0.0.1:{exporter.port}/healthz")
        assert status == 200
        assert json.loads(body) == {
            "status": "ok", "epoch": 7.0, "imgs_per_sec": 123.0
        }

    def test_unknown_paths_404(self, exporter):
        for method in (_get, _post):
            with pytest.raises(urllib.error.HTTPError) as err:
                method(f"http://127.0.0.1:{exporter.port}/nope")
            assert err.value.code == 404

    def test_trace_ms_validation(self, exporter):
        base = f"http://127.0.0.1:{exporter.port}/debug/trace"
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(base + "?ms=banana")
        assert err.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as err:
            _post(base + "?ms=999999")  # over trace_max_ms=5000
        assert err.value.code == 400
        assert "trace_max_ms" in err.value.read().decode()

    def test_trace_capture_writes_nonempty_dir(self, exporter, tmp_path):
        import jax.numpy as jnp

        jnp.ones(8).sum().block_until_ready()  # device warm before tracing
        status, payload = _post(
            f"http://127.0.0.1:{exporter.port}/debug/trace?ms=50"
        )
        assert status == 200 and payload["ms"] == 50
        trace_dir = payload["trace_dir"]
        assert trace_dir.startswith(str(tmp_path))
        assert os.listdir(trace_dir), "trace capture left an empty directory"

    def test_close_removes_ready_file(self, tmp_path):
        # a stale ready file after close would point monitors at a dead
        # (or recycled) port
        ready = tmp_path / "gone.json"
        exp = start_exporter(
            _StubTelemetry(), str(tmp_path), trace_max_ms=5000,
            ready_file=str(ready),
        )
        assert ready.exists()
        exp.close()
        assert not ready.exists()

    def test_scrape_disconnect_is_counted_not_fatal(self, tmp_path):
        import socket
        import struct

        telem = _DisconnectingScrapeTelemetry()
        exp = start_exporter(telem, str(tmp_path), trace_max_ms=5000,
                             ready_file=str(tmp_path / "r.json"))
        try:
            s = socket.create_connection(("127.0.0.1", exp.port), timeout=10)
            s.sendall(b"GET /metrics HTTP/1.1\r\nHost: t\r\n\r\n")
            # RST immediately with megabytes still unread: the server's
            # write must fail mid-body
            s.setsockopt(
                socket.SOL_SOCKET, socket.SO_LINGER, struct.pack("ii", 1, 0)
            )
            s.close()
            deadline = time.monotonic() + 10
            while telem.disconnects == 0 and time.monotonic() < deadline:
                time.sleep(0.05)
            assert telem.disconnects >= 1
            # the exporter survived and still answers
            status, _, _ = _get(f"http://127.0.0.1:{exp.port}/healthz")
            assert status == 200
        finally:
            exp.close()

    def test_maybe_start_exporter_port_semantics(self, tmp_path):
        from simclr_tpu.config import load_config

        # default: port 0, no ready file -> disabled, no socket
        cfg = load_config("config")
        assert maybe_start_exporter(cfg, _StubTelemetry(), str(tmp_path)) is None
        # port 0 + ready_file -> ephemeral port, published
        ready = tmp_path / "r.json"
        cfg = load_config(
            "config", overrides=[f"telemetry.ready_file={ready}"]
        )
        exp = maybe_start_exporter(cfg, _StubTelemetry(), str(tmp_path))
        try:
            assert exp is not None
            assert json.load(open(ready))["port"] == exp.port
        finally:
            exp.close()


class TestConfigValidation:
    def test_defaults_validate(self):
        from simclr_tpu.config import check_telemetry_conf, load_config

        check_telemetry_conf(load_config("config"))
        check_telemetry_conf(load_config("supervised_config"))

    @pytest.mark.parametrize(
        "override, expected_range",
        [
            ("telemetry.port=-1", "[0, 65535]"),
            ("telemetry.port=65536", "[0, 65535]"),
            ("telemetry.trace_max_ms=0", "(0, 600000]"),
            ("telemetry.trace_max_ms=900000", "(0, 600000]"),
            ("telemetry.events=maybe", "(true|false)"),
            ("telemetry.anomaly=maybe", "(true|false)"),
            ("telemetry.anomaly_warmup=1", "[2, 10000]"),
            ("telemetry.anomaly_warmup=2.5", "[2, 10000]"),
            ("telemetry.slow_step_factor=0", "[1, 1000]"),
            ("telemetry.stall_factor=0", "[1, 1000]"),
            ("telemetry.stall_min_s=0", "(0, 3600]"),
            ("telemetry.auto_trace=maybe", "(true|false)"),
            ("telemetry.auto_trace_ms=100000", "(0, 60000]"),
            ("telemetry.auto_trace_cooldown_s=-1", "[0, 86400]"),
            ("telemetry.auto_trace_max=0", "[1, 100]"),
            ("telemetry.auto_trace_max=101", "[1, 100]"),
            ("telemetry.fleet=maybe", "(true|false)"),
            ("telemetry.fleet_port=65536", "[0, 65535]"),
            ("telemetry.fleet_poll_s=0", "(0, 3600]"),
            ("telemetry.fleet_stale_after_s=0", "(0, 86400]"),
        ],
    )
    def test_bad_knobs_name_the_valid_range(self, override, expected_range):
        from simclr_tpu.config import ConfigError, check_telemetry_conf, load_config

        cfg = load_config("config", overrides=[override])
        with pytest.raises(ConfigError, match="telemetry\\.") as err:
            check_telemetry_conf(cfg)
        assert expected_range in str(err.value)

    @pytest.mark.parametrize(
        "override, expected",
        [
            ("serve.trace_sample_rate=1.5", "[0.0, 1.0]"),
            ("serve.trace_sample_rate=-0.25", "[0.0, 1.0]"),
            ("serve.requests_log=7", "path string or null"),
        ],
    )
    def test_serve_trace_knobs_name_the_valid_range(self, override, expected):
        from simclr_tpu.config import ConfigError, check_serve_conf, load_config

        cfg = load_config("serve", overrides=[override])
        with pytest.raises(ConfigError, match="serve\\.") as err:
            check_serve_conf(cfg)
        assert expected in str(err.value)

    def test_both_entry_point_checks_cover_telemetry(self):
        from simclr_tpu.config import (
            ConfigError,
            check_pretrain_conf,
            check_supervised_conf,
            load_config,
        )

        bad = ["telemetry.port=-1"]
        with pytest.raises(ConfigError, match="telemetry.port"):
            check_pretrain_conf(load_config("config", overrides=bad))
        with pytest.raises(ConfigError, match="telemetry.port"):
            check_supervised_conf(
                load_config("supervised_config", overrides=bad)
            )


# ---------------------------------------------------------------------------
# e2e proofs (slow: real training runs on the 8-device CPU mesh)
# ---------------------------------------------------------------------------

SYNTH = [
    "experiment.synthetic_data=true",
    "experiment.synthetic_size=64",
    "experiment.batches=4",  # x8 devices = global batch 32 -> 2 steps/epoch
    "parameter.warmup_epochs=1",
    "experiment.save_model_epoch=1",
]


class _NoSpans:
    """The span recorder's surface with nothing behind it: the baseline of
    the zero-sync proof runs the same host loop and records no span."""

    class _Span:
        id, name, step, epoch, n = 0, None, None, None, None

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return None

    def span(self, name, **ids):
        return self._Span()

    begin = span

    def end(self, span):
        pass

    def add(self, *args, **ids):
        pass

    def current(self, thread_ident=None):
        return None

    def flush(self, path, **kw):
        return 0

    def reset(self):
        pass


def _run_pretrain_counting_syncs(overrides, monkeypatch, scrape=None):
    """Run a tiny in-process pretrain with ``utils.profiling.synchronize``
    wrapped by a counter; optionally run ``scrape(ready_path)`` concurrently
    from this thread while training runs in a worker thread. Returns
    (summary, sync_count)."""
    from simclr_tpu.config import load_config
    from simclr_tpu.main import run_pretrain
    from simclr_tpu.utils import profiling

    counts = [0]
    real_sync = profiling.synchronize

    def counting_sync(tree):
        counts[0] += 1
        return real_sync(tree)

    monkeypatch.setattr(profiling, "synchronize", counting_sync)
    cfg = load_config("config", overrides=overrides)
    result = {}
    if scrape is None:
        result["summary"] = run_pretrain(cfg)
    else:
        worker = threading.Thread(
            target=lambda: result.update(summary=run_pretrain(cfg))
        )
        worker.start()
        scrape(worker)
        worker.join(timeout=900)
        assert not worker.is_alive(), "training thread did not finish"
    monkeypatch.setattr(profiling, "synchronize", real_sync)
    return result["summary"], counts[0]


@pytest.mark.slow
class TestEndToEnd:
    def test_scrape_adds_zero_syncs_and_writes_timeline(
        self, tmp_path, monkeypatch
    ):
        """Acceptance proof for the zero-sync contract: the same 2-epoch run
        with the exporter enabled and /metrics scraped continuously performs
        EXACTLY as many ``synchronize`` device fences as the run with no
        exporter at all. (Sync points are fixed loop landmarks, so the count
        is deterministic per config.)"""
        # anomaly detection is ON by default; warmup=2 makes sure the
        # median/MAD classification path actually runs inside this short
        # run, so the zero-sync proof covers the detector too
        base = SYNTH + ["parameter.epochs=2", "telemetry.anomaly_warmup=2"]
        # ... and the span recorder, which is always on: the baseline runs
        # with no recorder behind the spans, the observed run with the real
        # one, so the equal counts below cover every span around a fence
        # (boundary/loss_fetch, checkpoint/drain) as well
        from simclr_tpu.obs import trace as obs_trace

        with monkeypatch.context() as m:
            m.setattr(obs_trace, "_RECORDER", _NoSpans())
            _, baseline_syncs = _run_pretrain_counting_syncs(
                base + [f"experiment.save_dir={tmp_path / 'plain'}"], m
            )
        assert not os.path.exists(obs_trace.spans_path(str(tmp_path / "plain")))

        obs_dir = tmp_path / "observed"
        ready = obs_dir / "ready.json"
        scrapes = [0]

        def scrape(worker):
            deadline = time.monotonic() + 600
            port = None
            while time.monotonic() < deadline and worker.is_alive():
                if port is None:
                    try:
                        port = json.load(open(ready))["port"]
                    except (OSError, ValueError, KeyError):
                        time.sleep(0.2)
                        continue
                try:
                    _, _, body = _get(f"http://127.0.0.1:{port}/metrics")
                    _get(f"http://127.0.0.1:{port}/healthz")
                    assert "simclr_train_imgs_per_sec" in body
                    # the DeviceMonitor samples on this scrape path; its
                    # fallback gauge must be present on every backend and
                    # (per the sync-count assertion below) add zero fences
                    assert "simclr_train_hbm_high_watermark_bytes" in body
                    scrapes[0] += 1
                except (urllib.error.URLError, OSError):
                    pass  # exporter already closed at run end
                time.sleep(0.1)

        summary, observed_syncs = _run_pretrain_counting_syncs(
            base + [
                f"experiment.save_dir={obs_dir}",
                f"telemetry.ready_file={ready}",
            ],
            monkeypatch,
            scrape=scrape,
        )
        assert scrapes[0] > 0, "no scrape actually landed mid-run"
        assert observed_syncs == baseline_syncs
        assert summary["complete"] is True
        recorded = {s["name"] for s in obs_trace.read_spans(
            obs_trace.spans_path(str(obs_dir))
        )}
        assert {"dispatch", "boundary/loss_fetch", "checkpoint/drain",
                "checkpoint/save", "input_wait", "device_put"} <= recorded

        # the same run also wrote a coherent single-attempt timeline
        events = read_events(events_path(str(obs_dir)))
        kinds = [e["event"] for e in events]
        assert kinds[0] == "run_start" and kinds[-1] == "run_end"
        assert [e["epoch"] for e in events if e["event"] == "epoch"] == [1, 2]
        assert "checkpoint" in kinds
        assert {e["attempt"] for e in events} == {1}

    def test_nonzero_process_scrape_adds_zero_syncs(self, tmp_path, monkeypatch):
        """The fleet plane runs an exporter on EVERY host, so the zero-sync
        contract must hold for a non-logging process too: a run seen as
        process 1 (exporter publishing ``telemetry.p1.ready``, no event log,
        no detector) scraped continuously performs EXACTLY the fences of the
        same non-logging run with no exporter. ``jax.process_index`` itself
        stays 0 (patching it would corrupt mesh/data sharding in this
        single-process harness); only the observability call sites see the
        non-zero identity."""
        from simclr_tpu import main as main_mod
        from simclr_tpu.obs import exporter as exporter_mod
        from simclr_tpu.obs.fleet import telemetry_ready_path

        real_maybe = exporter_mod.maybe_start_exporter

        def as_process_1(cfg, telemetry, save_dir, *, process_index=0):
            return real_maybe(cfg, telemetry, save_dir, process_index=1)

        monkeypatch.setattr(main_mod, "maybe_start_exporter", as_process_1)
        monkeypatch.setattr(main_mod, "is_logging_host", lambda: False)
        base = SYNTH + ["parameter.epochs=2", "telemetry.anomaly_warmup=2"]

        plain_dir = tmp_path / "plain"
        plain_dir.mkdir()  # non-logging hosts never makedirs the run dir
        _, baseline_syncs = _run_pretrain_counting_syncs(
            base + [f"experiment.save_dir={plain_dir}"], monkeypatch
        )

        obs_dir = tmp_path / "observed"
        obs_dir.mkdir()
        ready = obs_dir / "telemetry.ready"
        p1_ready = telemetry_ready_path(str(ready), 1)
        assert p1_ready.endswith("telemetry.p1.ready")
        scrapes = [0]

        def scrape(worker):
            deadline = time.monotonic() + 600
            port = None
            while time.monotonic() < deadline and worker.is_alive():
                if port is None:
                    try:
                        port = json.load(open(p1_ready))["port"]
                    except (OSError, ValueError, KeyError):
                        time.sleep(0.2)
                        continue
                try:
                    _, _, body = _get(f"http://127.0.0.1:{port}/metrics")
                    assert "simclr_train_imgs_per_sec" in body
                    scrapes[0] += 1
                except (urllib.error.URLError, OSError):
                    pass
                time.sleep(0.1)

        summary, observed_syncs = _run_pretrain_counting_syncs(
            base + [
                f"experiment.save_dir={obs_dir}",
                f"telemetry.ready_file={ready}",
            ],
            monkeypatch,
            scrape=scrape,
        )
        assert scrapes[0] > 0, "no scrape landed on the process-1 exporter"
        assert observed_syncs == baseline_syncs
        assert summary["complete"] is True
        # process 0's configured path was never claimed by this process,
        # and the per-process file was removed on clean exit
        assert not ready.exists()
        assert not os.path.exists(p1_ready)

    def test_injected_crash_yields_merged_two_attempt_timeline(self, tmp_path):
        """Acceptance proof: hard-kill + auto-resume under the supervisor
        leaves ONE events.jsonl telling the whole story — both attempts, in
        order, each epoch exactly once, the supervisor's own child_exit /
        restart / outcome events interleaved, and the final telemetry
        snapshot surfaced in supervisor_summary.json."""
        from simclr_tpu.supervisor.faults import ENV_DIE

        save_dir = str(tmp_path / "killed")
        env = dict(os.environ, JAX_PLATFORMS="cpu", **{ENV_DIE: "3"})
        proc = subprocess.run(
            [sys.executable, "-m", "simclr_tpu.supervisor", "--", "pretrain",
             *SYNTH, "parameter.epochs=3", "supervisor.backoff_base_s=0.05",
             f"experiment.save_dir={save_dir}"],
            env=env, capture_output=True, text=True, timeout=900,
        )
        assert proc.returncode == 0, proc.stderr[-2000:]
        summary = json.loads(
            [l for l in proc.stdout.splitlines() if l.startswith("{")][-1]
        )
        assert summary["outcome"] == "clean" and summary["resumed"] >= 1
        # the child's last heartbeat telemetry rides into the summary
        assert summary["telemetry"]["epoch"] == 3.0

        events = read_events(events_path(save_dir))
        # every epoch exactly once and in order, attempts merged
        assert [e["epoch"] for e in events if e["event"] == "epoch"] == [1, 2, 3]
        attempts = {e["attempt"] for e in events}
        assert {1, 2} <= attempts
        # both attempts announced themselves; the resume re-seated cleanly
        assert sum(e["event"] == "run_start" for e in events) >= 2
        assert any(e["event"] == "resume" and e["attempt"] >= 2 for e in events)
        # supervisor forensics interleaved in the same file
        assert any(
            e["event"] == "child_exit" and e["exit"] != 0 for e in events
        )
        assert any(e["event"] == "restart" for e in events)
        outcome = [e for e in events if e["event"] == "outcome"]
        assert outcome and outcome[-1]["outcome"] == "clean"
        # wall-clock ordering holds across the attempt boundary
        times = [e["time"] for e in events]
        assert times == sorted(times)

    def test_wedged_run_yields_stall_autotrace_and_report(self, tmp_path):
        """Flight-recorder acceptance: a host loop that silently wedges
        (fault injection at beat 6, the last step) must — with no operator
        anywhere — produce a ``stall`` event from the watchdog thread, show
        the incremented counter on a live ``/metrics`` scrape while still
        wedged, capture an automatic profiler trace, surface the anomaly
        counts in ``supervisor_summary.json`` after the supervisor kills and
        resumes it, and have the post-mortem report name the stalled
        attempt."""
        from simclr_tpu.supervisor.faults import ENV_WEDGE

        save_dir = str(tmp_path / "wedged")
        ready = tmp_path / "ready.json"
        env = dict(os.environ, JAX_PLATFORMS="cpu", **{ENV_WEDGE: "6"})
        proc = subprocess.Popen(
            [sys.executable, "-m", "simclr_tpu.supervisor", "--", "pretrain",
             *SYNTH, "parameter.epochs=3",
             "supervisor.backoff_base_s=0.05",
             # the stall watchdog (deadline ~2x the ~6s CPU step median)
             # must beat the supervisor's hang kill by a wide margin, and
             # the floor must leave room for a resumed attempt's first
             # post-compile step gap (~13s on CPU: the step-5 beat lands
             # right after compile, before step 5 even executes)
             "supervisor.heartbeat_min_timeout_s=30",
             "supervisor.heartbeat_timeout_factor=10",
             "telemetry.anomaly_warmup=2",
             "telemetry.stall_min_s=1.0",
             "telemetry.stall_factor=2.0",
             "telemetry.auto_trace=true",
             "telemetry.auto_trace_ms=200",
             "telemetry.auto_trace_cooldown_s=0",
             f"telemetry.ready_file={ready}",
             f"experiment.save_dir={save_dir}"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True,
        )
        # live scrape: the stall counter must go positive while the host
        # loop is still stuck (the exporter thread keeps serving)
        stall_scraped = False
        deadline = time.monotonic() + 600
        while time.monotonic() < deadline and proc.poll() is None:
            try:
                port = json.load(open(ready))["port"]
                _, _, body = _get(
                    f"http://127.0.0.1:{port}/metrics", timeout=5
                )
                if re.search(r"simclr_train_anomaly_stalls_total [1-9]", body):
                    stall_scraped = True
                    break
            except (OSError, ValueError, KeyError, urllib.error.URLError,
                    http.client.HTTPException):
                # the exporter can vanish mid-response when the supervisor
                # SIGKILLs the wedged attempt — keep polling
                pass
            time.sleep(0.2)
        try:
            stdout, stderr = proc.communicate(timeout=900)
        except subprocess.TimeoutExpired:
            proc.kill()
            raise
        assert proc.returncode == 0, stderr[-2000:]
        assert stall_scraped, "stall counter never appeared on a live scrape"

        summary = json.loads(
            [l for l in stdout.splitlines() if l.startswith("{")][-1]
        )
        assert summary["outcome"] == "clean"
        assert summary["anomalies"]["stalls"] >= 1
        assert summary["anomalies"]["auto_traces"] >= 1

        events = read_events(events_path(save_dir))
        stalls = [e for e in events if e["event"] == "stall"]
        assert stalls and stalls[0]["attempt"] == 1
        traces = [e for e in events if e["event"] == "auto_trace"]
        assert traces, "no automatic capture was recorded"
        trace_dir = traces[0]["trace_dir"]
        assert os.sep + "trace_auto" + os.sep in trace_dir
        assert os.path.isdir(trace_dir) and os.listdir(trace_dir), (
            "auto-trace directory is missing or empty"
        )

        # the post-mortem names the stalled attempt and judges throughput
        baseline = tmp_path / "BENCH_FAKE.json"
        baseline.write_text(json.dumps({
            "payload": {
                "metric": "pretrain_imgs_per_sec_per_chip", "value": 1e-9
            }
        }))
        report = build_report(
            save_dir, baseline_path=str(baseline), threshold=0.05
        )
        assert 1 in report["stalled_attempts"]
        text = render_report(report)
        assert text.splitlines()[-1].startswith("run_report verdict: OK")
