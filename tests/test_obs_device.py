"""Device-side observability (simclr_tpu/obs/device.py, obs/compile.py).

Covers the PR's three tentpole layers on a CPU backend, where every
hardening path is live:

* **HBM accounting** — ``sample_memory_stats`` degradation (a backend
  without stats yields absent gauges, never a KeyError), DeviceMonitor
  peak/watermark tracking with synthetic devices, rate-limited ``hbm``
  events, and the zero-added-syncs contract of
  continuous sampling;
* **Compile sentry** — fingerprint stability across lowerings, the
  signature discipline (a changing python-int step counter is NOT a new
  program; a changed shape IS), the recompile alarm on a post-warmup shape
  change (counter + event + auto-trace hook), and cost extraction from a
  real compiled executable;
* **OOM forensics** — ``maybe_dump_oom_profile`` writes the profile and
  the ``oom`` event for RESOURCE_EXHAUSTED only, and never raises even
  when the profiler itself is broken;

plus the acceptance flow: a watched function that alarms, a monitor that
peaks, and a monkeypatched OOM leave an ``events.jsonl`` whose compile /
recompile_alarm / oom entries the run report renders (verdict line still
last), with the live ``/metrics`` scrape carrying the HBM gauges and the
alarm counter.
"""

import json
import subprocess
import sys
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_tpu.obs.compile import (
    CompileSentry,
    args_signature,
    executable_cost,
    lowered_fingerprint,
    maybe_sentry,
)
from simclr_tpu.obs.device import (
    DeviceMonitor,
    is_oom_error,
    maybe_dump_oom_profile,
    maybe_monitor,
    sample_memory_stats,
)
from simclr_tpu.obs.events import EventLog, events_path, read_events
from simclr_tpu.obs.exporter import start_exporter
from simclr_tpu.obs.telemetry import Telemetry

pytestmark = pytest.mark.obs


class _FakeDevice:
    """A device whose ``memory_stats`` payload the test scripts."""

    def __init__(self, device_id, stats):
        self.id = device_id
        self._stats = stats

    def memory_stats(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def _make_telemetry():
    return Telemetry(global_batch=4, n_devices=1)


# ---------------------------------------------------------------------------
# sample_memory_stats hardening
# ---------------------------------------------------------------------------


class TestSampleMemoryStats:
    def test_raising_backend_degrades_to_none(self):
        assert sample_memory_stats(_FakeDevice(0, RuntimeError("no stats"))) is None

    def test_empty_and_none_payloads_degrade_to_none(self):
        assert sample_memory_stats(_FakeDevice(0, {})) is None
        assert sample_memory_stats(_FakeDevice(0, None)) is None

    def test_non_numeric_values_are_filtered(self):
        stats = sample_memory_stats(
            _FakeDevice(
                0,
                {
                    "bytes_in_use": 123,
                    "largest_alloc": 7.0,
                    "backend": "tpu",  # str: dropped
                    "pinned": True,  # bool: dropped (isinstance int!)
                },
            )
        )
        assert stats == {"bytes_in_use": 123, "largest_alloc": 7}


# ---------------------------------------------------------------------------
# DeviceMonitor
# ---------------------------------------------------------------------------


class TestDeviceMonitor:
    def test_cpu_like_backend_renders_only_watermark(self):
        """Satellite contract: a backend with no memory stats serves the
        unconditional high-watermark gauge (0) and nothing else — no
        KeyError, no per-device series."""
        monitor = DeviceMonitor(devices=[_FakeDevice(0, RuntimeError("cpu"))])
        text = monitor.render()
        assert "simclr_train_hbm_high_watermark_bytes 0" in text
        assert "device=" not in text

    def test_per_device_gauges_and_watermark(self):
        """The peak is arrays plus the runtime's reservation for program
        temporaries (on the v5e the second is 20x the first): what
        ``benchmarks/runners/train.py::memory_peak_bytes`` reports."""
        monitor = DeviceMonitor(
            devices=[
                _FakeDevice(0, {"bytes_in_use": 100, "peak_bytes_in_use": 150,
                                "bytes_reserved": 3000,
                                "peak_bytes_reserved": 4000,
                                "bytes_limit": 10000}),
                _FakeDevice(1, {"bytes_in_use": 200, "peak_bytes_in_use": 250,
                                "bytes_reserved": 3000,
                                "peak_bytes_reserved": 5000,
                                "bytes_limit": 10000}),
                # a backend without the reserved keys: the allocator's peak
                _FakeDevice(2, {"bytes_in_use": 10, "peak_bytes_in_use": 20}),
            ]
        )
        text = monitor.render()
        assert 'simclr_train_hbm_bytes_in_use{device="0"} 100' in text
        assert 'simclr_train_hbm_bytes_in_use{device="1"} 200' in text
        assert 'simclr_train_hbm_peak_bytes{device="0"} 4150' in text
        assert 'simclr_train_hbm_peak_bytes{device="1"} 5250' in text
        assert 'simclr_train_hbm_peak_bytes{device="2"} 20' in text
        assert 'simclr_train_hbm_bytes_limit{device="0"} 10000' in text
        assert monitor.high_watermark_bytes == 5250
        assert "simclr_train_hbm_high_watermark_bytes 5250" in text

    def test_partial_stats_render_partial_gauges(self):
        """A backend reporting only bytes_in_use must yield only that gauge
        — absent keys are absent series, not KeyErrors."""
        monitor = DeviceMonitor(devices=[_FakeDevice(3, {"bytes_in_use": 42})])
        text = monitor.render()
        assert 'simclr_train_hbm_bytes_in_use{device="3"} 42' in text
        assert "simclr_train_hbm_bytes_limit" not in text

    def test_no_preflight_drift_series(self):
        """Nothing in the program estimates a resident footprint, so the
        monitor takes none and a scrape carries no drift series."""
        with pytest.raises(TypeError):
            DeviceMonitor(expected_resident_bytes=80)
        monitor = DeviceMonitor(devices=[_FakeDevice(0, {"bytes_in_use": 100})])
        assert "preflight_drift" not in monitor.render()
        assert not hasattr(monitor, "drift_bytes")

    def test_hbm_events_are_growth_rate_limited(self, tmp_path):
        device = _FakeDevice(0, {"bytes_in_use": 100})
        events = EventLog(str(tmp_path))
        monitor = DeviceMonitor(events=events, devices=[device])
        for in_use in (100, 101, 102, 500, 501, 502):
            device._stats = {"bytes_in_use": in_use}
            monitor.sample()
        emitted = [e for e in read_events(events_path(str(tmp_path)))
                   if e["event"] == "hbm"]
        # 100 (first growth over 0) and 500 (>1.1x) emit; the +1 creeps don't
        assert [e["high_watermark"] for e in emitted] == [100, 500]
        assert emitted[0]["per_device"] == {"0": 100}

    def test_continuous_sampling_adds_zero_syncs(self, monkeypatch):
        """The telemetry stack's zero-added-syncs contract extends to the
        monitor: sampling is a host-side allocator query, never a device
        fence. (The slow e2e in test_obs.py proves the same for the full
        scrape path by exact sync-count equality.)"""
        from simclr_tpu.utils import profiling

        def fence_means_failure(tree):
            raise AssertionError("DeviceMonitor sampled through a device fence")

        monkeypatch.setattr(profiling, "synchronize", fence_means_failure)
        monitor = DeviceMonitor(
            devices=[_FakeDevice(0, {"bytes_in_use": 1})] + list(jax.local_devices())
        )
        for _ in range(50):
            monitor.render()
        assert monitor.high_watermark_bytes >= 1

    def test_maybe_monitor_respects_config_gate(self):
        class _Cfg:
            def __init__(self, value):
                self._value = value

            def select(self, key, default=None):
                return self._value if key == "telemetry.hbm" else default

        assert maybe_monitor(_Cfg(False)) is None
        assert isinstance(maybe_monitor(_Cfg(True)), DeviceMonitor)


# ---------------------------------------------------------------------------
# compile sentry
# ---------------------------------------------------------------------------


def _double(x):
    return x * 2.0


class TestCompileSentry:
    def test_fingerprint_stable_across_lowerings(self):
        fn = jax.jit(_double)
        x = jnp.ones((4, 3))
        fp1 = lowered_fingerprint(fn.lower(x))
        fp2 = lowered_fingerprint(fn.lower(jnp.zeros((4, 3))))
        assert fp1 and fp1 == fp2
        fp_other = lowered_fingerprint(fn.lower(jnp.ones((8, 3))))
        assert fp_other and fp_other != fp1

    def test_signature_ignores_python_scalar_values(self):
        x = jnp.ones((4,))
        assert args_signature((x, 3)) == args_signature((x, 4))
        assert args_signature((x, 3)) != args_signature((jnp.ones((5,)), 3))
        assert args_signature((x, 3)) != args_signature((x, 3.0))

    def test_executable_cost_reads_one_dict(self):
        compiled = jax.jit(_double).lower(jnp.ones((16, 16))).compile()
        flops, bytes_accessed = executable_cost(compiled)
        assert flops >= 0.0 and bytes_accessed > 0.0

        class _PartialCost:
            def cost_analysis(self):
                return {"flops": 7.0}

        assert executable_cost(_PartialCost()) == (7.0, 0.0)

    def test_watch_counts_compiles_and_caches(self, tmp_path):
        telemetry = _make_telemetry()
        events = EventLog(str(tmp_path))
        sentry = CompileSentry(telemetry=telemetry, events=events)
        step = sentry.watch(jax.jit(_double), "step")
        out = step(jnp.ones((4,)))
        np.testing.assert_allclose(np.asarray(out), 2.0)
        step(jnp.ones((4,)))  # cache hit: no new compile
        assert sentry.compiles == 1
        assert sentry.recompile_alarms == 0
        assert telemetry.compiles.value == 1
        compile_events = [e for e in read_events(events_path(str(tmp_path)))
                          if e["event"] == "compile"]
        assert len(compile_events) == 1
        assert compile_events[0]["name"] == "step"
        assert compile_events[0]["recompile"] is False
        assert compile_events[0]["fingerprint"]
        assert compile_events[0]["seconds"] > 0

    def test_compile_event_says_when_the_persistent_cache_answered(
        self, tmp_path, monkeypatch
    ):
        """``seconds`` of a cached step is lowering plus a load: the event
        must say which kind it is (``chip_smoke.py`` reads this on resume)."""
        from simclr_tpu.utils import platform

        hits = iter([0, 0, 4, 5])  # before/after: a miss, then a hit
        monkeypatch.setattr(
            platform, "compilation_cache_stats",
            lambda: {"dir": None, "hits": next(hits), "misses": 0},
        )
        events = EventLog(str(tmp_path))
        sentry = CompileSentry(events=events)
        step = sentry.watch(jax.jit(_double), "step")
        step(jnp.ones((4,)))
        step(jnp.ones((8,)))
        compile_events = [e for e in read_events(events_path(str(tmp_path)))
                          if e["event"] == "compile"]
        assert [e["cache_hit"] for e in compile_events] == [False, True]
        # a caller that cannot tell (serve bucket warm-up) says nothing
        assert "cache_hit" not in sentry.record_compile("bucket", seconds=0.1)

    def test_recompile_alarm_on_shape_change(self, tmp_path):
        """The tentpole scenario: a step function recompiling after warmup
        must raise the alarm — counter, event, and auto-trace hook."""
        traced = []
        telemetry = _make_telemetry()
        events = EventLog(str(tmp_path))
        sentry = CompileSentry(
            telemetry=telemetry, events=events,
            auto_trace=lambda reason, seconds: traced.append(reason),
        )
        step = sentry.watch(jax.jit(_double), "step")
        step(jnp.ones((4,)))          # warmup compile
        step(jnp.ones((8,)))          # shape drift: post-warmup recompile
        assert sentry.compiles == 2
        assert sentry.recompile_alarms == 1
        assert telemetry.recompile_alarms.value == 1
        assert traced == ["recompile_alarm"]
        kinds = [e["event"] for e in read_events(events_path(str(tmp_path)))]
        assert kinds.count("compile") == 2
        assert kinds.count("recompile_alarm") == 1
        text = telemetry.render()
        assert "simclr_train_compiles_total 2" in text
        assert "simclr_train_recompile_alarms_total 1" in text

    def test_python_step_counter_never_alarms(self):
        """jit weak types: a python-int argument changing value every call
        (the host-side step counter) must not read as a new program."""
        sentry = CompileSentry()
        step = sentry.watch(jax.jit(lambda x, i: x + i), "step")
        for i in range(5):
            step(jnp.ones((4,)), i)
        assert sentry.compiles == 1
        assert sentry.recompile_alarms == 0

    @pytest.mark.parametrize("stage", ["lower", "compile"])
    def test_watch_reraises_lowering_and_compile_errors(self, stage):
        """A kernel the backend refuses must stop the run, not be retried
        through plain dispatch with the failure swallowed."""

        class _Lowered:
            def as_text(self):
                return "module"

            def compile(self):
                raise RuntimeError("Mosaic refused the kernel")

        class _Jitted:
            calls = 0

            def lower(self, *args):
                if stage == "lower":
                    raise NotImplementedError("Unimplemented primitive: rev")
                return _Lowered()

            def __call__(self, *args):
                self.calls += 1

        fn = _Jitted()
        sentry = CompileSentry()
        step = sentry.watch(fn, "step")
        with pytest.raises((NotImplementedError, RuntimeError)):
            step(jnp.ones((4,)))
        assert fn.calls == 0
        assert sentry.compiles == 0

    def test_watch_degrades_without_aot(self):
        """A callable with no ``lower`` at all (a plain Python wrapper)
        still dispatches and still books its compiles."""
        sentry = CompileSentry()
        step = sentry.watch(lambda x: x * 2.0, "plain")
        assert step(2.0) == 4.0
        assert step(3.0) == 6.0
        assert sentry.compiles == 1
        assert sentry.records[0]["fingerprint"] == ""

    def test_maybe_sentry_respects_config_gate(self):
        class _Cfg:
            def __init__(self, value):
                self._value = value

            def select(self, key, default=None):
                return self._value if key == "telemetry.compile_sentry" else default

        assert maybe_sentry(_Cfg(False)) is None
        assert isinstance(maybe_sentry(_Cfg(True)), CompileSentry)


# ---------------------------------------------------------------------------
# OOM forensics
# ---------------------------------------------------------------------------


class TestOOMForensics:
    def test_non_oom_error_is_a_no_op(self, tmp_path):
        events = EventLog(str(tmp_path))
        path = maybe_dump_oom_profile(
            str(tmp_path), ValueError("shape mismatch"), events=events,
            profile_fn=lambda: b"x",
        )
        assert path is None
        assert not (tmp_path / "oom_device_memory.prof").exists()
        assert read_events(events_path(str(tmp_path))) == []

    def test_oom_writes_profile_and_event(self, tmp_path):
        events = EventLog(str(tmp_path))
        exc = RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating 2.1G")
        assert is_oom_error(exc)
        path = maybe_dump_oom_profile(
            str(tmp_path), exc, events=events,
            profile_fn=lambda: b"pprof-payload",
        )
        assert path == str(tmp_path / "oom_device_memory.prof")
        assert open(path, "rb").read() == b"pprof-payload"
        (oom,) = read_events(events_path(str(tmp_path)))
        assert oom["event"] == "oom"
        assert "RESOURCE_EXHAUSTED" in oom["error"]
        assert oom["profile"] == path

    def test_broken_profiler_still_emits_event_and_never_raises(self, tmp_path):
        events = EventLog(str(tmp_path))
        exc = RuntimeError("RESOURCE_EXHAUSTED: oom")

        def broken():
            raise RuntimeError("profiler unavailable")

        path = maybe_dump_oom_profile(
            str(tmp_path), exc, events=events, profile_fn=broken,
        )
        assert path is None
        (oom,) = read_events(events_path(str(tmp_path)))
        assert oom["event"] == "oom" and oom["profile"] is None


# ---------------------------------------------------------------------------
# acceptance: alarm + HBM + OOM land in the scrape and the run report
# ---------------------------------------------------------------------------


class TestAcceptanceFlow:
    def test_scrape_and_report_carry_device_observability(self, tmp_path):
        """The issue's e2e: a shape-drifting watched step, a sampling
        monitor, and a (monkeypatched) OOM leave (a) a live /metrics scrape
        with HBM gauges and the recompile-alarm counter, and (b) an
        events.jsonl whose compile/recompile_alarm/oom entries the report
        CLI renders — verdict line still last."""
        telemetry = _make_telemetry()
        events = EventLog(str(tmp_path))
        sentry = CompileSentry(telemetry=telemetry, events=events)
        monitor = DeviceMonitor(
            events=events,
            devices=[_FakeDevice(0, {"bytes_in_use": 100,
                                     "peak_bytes_in_use": 120,
                                     "bytes_limit": 1000})],
        )
        telemetry.attach_device_monitor(monitor)

        step = sentry.watch(jax.jit(_double), "pretrain_step")
        step(jnp.ones((4,)))
        step(jnp.ones((6,)))  # fault-injected shape change -> alarm
        maybe_dump_oom_profile(
            str(tmp_path),
            RuntimeError("RESOURCE_EXHAUSTED: out of memory"),
            events=events, profile_fn=lambda: b"pprof",
        )

        exporter = start_exporter(telemetry, str(tmp_path))
        try:
            with urllib.request.urlopen(
                f"http://{exporter.host}:{exporter.port}/metrics", timeout=10
            ) as resp:
                body = resp.read().decode()
        finally:
            exporter.close()
        assert 'simclr_train_hbm_bytes_in_use{device="0"} 100' in body
        assert "simclr_train_hbm_high_watermark_bytes 120" in body
        assert "preflight_drift" not in body
        assert "simclr_train_compiles_total 2" in body
        assert "simclr_train_recompile_alarms_total 1" in body
        assert 'simclr_train_xla_cost_flops{executable="pretrain_step"}' in body

        kinds = [e["event"] for e in read_events(events_path(str(tmp_path)))]
        assert kinds.count("compile") == 2
        assert "recompile_alarm" in kinds and "oom" in kinds and "hbm" in kinds

        report = subprocess.run(
            [sys.executable, "-m", "simclr_tpu.obs.report", str(tmp_path)],
            capture_output=True, text=True, timeout=120,
        )
        assert report.returncode == 0, report.stderr
        out = report.stdout
        assert "compiles: 2" in out
        assert "RECOMPILE_ALARMS=1" in out
        assert "OOMS=1" in out
        assert "hbm peak: dev0=" in out
        assert out.strip().splitlines()[-1].startswith("run_report verdict: ")
