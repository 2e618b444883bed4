"""Step-time anomaly detection: slow-step classifier, stall watchdog,
rate-limited automatic profiler capture.

The step-time histogram (PR 6) proves a latency tail existed; this
module answers *when* and records *why*.  The trainers tick a
:class:`StepAnomalyDetector` once per host step with nothing but
``time.monotonic()`` — zero extra device syncs:

* **slow_step** — a rolling median/MAD window over step durations
  classifies a step as slow when it exceeds
  ``median + slow_factor * MAD`` (MAD floored at 5% of the median so a
  perfectly steady stream, whose MAD is ~0, never flags ordinary
  jitter).  Emitted into ``events.jsonl`` and counted in ``/metrics``.
  On an accelerator the host runs ahead of the device: a run begins with
  a burst of dispatches (11 of ~5 ms on the v5e) before the first one
  blocks on the device and ticks become step times (~126 ms), the burst
  comes again after every boundary's sync, and where an epoch is shorter
  than the burst (6 steps on four chips) no dispatch ever blocks inside
  the loop. A dispatch is not a step. So, first, until the window has
  filled once, the first tick that stands out is read as the first
  dispatch that blocked, and the window is seeded anew from it with no
  alarm; second, a tick far under the last epoch's mean step time (the
  telemetry's, which divides the epoch's wall time, sync included, by its
  steps) is a dispatch: neither sampled nor classified.
* **stall** — a wedged host loop cannot report its own absence, so a
  daemon watchdog thread is armed at every tick with a deadline of
  ``max(stall_min_s, stall_factor * median)``; if no tick (or
  :meth:`StepAnomalyDetector.pause`) lands in time, the watchdog emits a
  ``stall`` event from its own thread while the loop is still stuck —
  before the supervisor's heartbeat timeout SIGKILLs the process.  The
  event names the host span open on the main thread (``obs/trace.py``),
  and the span recorder is flushed, so the file shows the call it sat in.
* **auto_trace** — both anomaly kinds can fire the existing
  ``capture_trace`` machinery (``utils/profiling.py``) so the chip's
  state at the moment of the anomaly is recorded with no operator
  present.  Captures run on a daemon thread (``capture_trace`` sleeps
  for the capture window), are rate-limited by a cooldown and a
  per-attempt budget, and land under ``<save_dir>/trace_auto/``.

Stdlib-only at import time; ``jax`` is imported lazily inside the
capture thread so the module stays usable from jax-free paths.
"""

from __future__ import annotations

import collections
import logging
import os
import statistics
import threading
import time

from simclr_tpu.obs.events import EventLog
from simclr_tpu.obs.trace import recorder, spans_path

logger = logging.getLogger("simclr_tpu")

SLOW_STEP_EVENT = "slow_step"
STALL_EVENT = "stall"
AUTO_TRACE_EVENT = "auto_trace"

# auto captures land in <save_dir>/trace_auto/trace-<unix>-<seq>/
AUTO_TRACE_DIR = "trace_auto"

# MAD floor: max(MAD, _MAD_FLOOR_FRAC * median, _MAD_FLOOR_ABS) keeps a
# constant-rate stream (MAD ~ 0) from flagging sub-percent jitter
_MAD_FLOOR_FRAC = 0.05
_MAD_FLOOR_ABS = 1e-4


class StepAnomalyDetector:
    """Rolling median/MAD slow-step classifier plus stall watchdog.

    ``tick()`` is called once per completed host step; ``pause()``
    before epoch-boundary work (probe, checkpoint I/O) so that gap is
    neither sampled as a step nor misread as a stall; ``close()`` once
    in the trainer's ``finally``.
    """

    def __init__(
        self,
        save_dir: str,
        *,
        telemetry=None,
        events: EventLog | None = None,
        window: int = 64,
        warmup: int = 8,
        slow_factor: float = 4.0,
        stall_factor: float = 10.0,
        stall_min_s: float = 2.0,
        auto_trace: bool = False,
        auto_trace_ms: float = 500.0,
        auto_trace_cooldown_s: float = 300.0,
        auto_trace_max: int = 3,
        capture_fn=None,
        clock=time.monotonic,
    ):
        self.save_dir = str(save_dir)
        self.telemetry = telemetry
        self.events = events
        self.warmup = max(2, int(warmup))
        self.slow_factor = float(slow_factor)
        self.stall_factor = float(stall_factor)
        self.stall_min_s = float(stall_min_s)
        self.auto_trace_enabled = bool(auto_trace)
        self.auto_trace_ms = float(auto_trace_ms)
        self.auto_trace_cooldown_s = float(auto_trace_cooldown_s)
        self.auto_trace_max = int(auto_trace_max)
        self._capture_fn = capture_fn
        self._clock = clock
        # the window must hold at least `warmup` samples or the detector
        # could never leave its grace period
        self._samples = collections.deque(maxlen=max(int(window), self.warmup))
        # False until the first dispatch that blocked has been seen, or the
        # window has filled with ticks among which none stood out
        self._seeded = False
        self._last_tick: float | None = None
        self._step = 0
        self._epoch = 0
        self.slow_steps = 0
        self.stalls = 0
        self.auto_traces = 0
        self._trace_lock = threading.Lock()
        self._traces_started = 0
        self._last_trace_at: float | None = None
        self._watchdog = _Watchdog(self._on_stall, clock=clock)

    # -- classification ------------------------------------------------

    def _stats(self):
        if len(self._samples) < self.warmup:
            return None, None
        med = statistics.median(self._samples)
        mad = statistics.median(abs(x - med) for x in self._samples)
        return med, mad

    def _step_time(self) -> float:
        """The last epoch's mean step time as the telemetry has it (the
        epoch's wall time, its sync included, over its steps); 0.0 before
        the first epoch ends or without a telemetry that knows."""
        return float(getattr(self.telemetry, "step_time_s", 0.0) or 0.0)

    def tick(self, step: int = 0, epoch: int = 0) -> str | None:
        """Record one completed step; returns ``"slow_step"`` when the
        step classified as anomalous, else None."""
        now = self._clock()
        self._step, self._epoch = int(step), int(epoch)
        verdict = None
        dt = None if self._last_tick is None else now - self._last_tick
        # a tick far under the epoch's mean step time is a run-ahead
        # dispatch, not a step
        if dt is not None and dt * self.slow_factor >= self._step_time():
            med, mad = self._stats()
            if med is not None:
                threshold = med + self.slow_factor * max(
                    mad, _MAD_FLOOR_FRAC * med, _MAD_FLOOR_ABS
                )
                if dt > threshold and self._seeded:
                    verdict = self._slow_step(dt, med, threshold)
                elif dt > threshold:
                    # the run-ahead burst is over: this dispatch blocked on
                    # the device, and step times start with it
                    self._samples.clear()
                    self._seeded = True
            self._samples.append(dt)
            if len(self._samples) == self._samples.maxlen:
                self._seeded = True  # a full window, and no tick stood out
        self._last_tick = now
        pace = self._stats()[0] or self._step_time()
        if pace:
            self._watchdog.arm(
                now + max(self.stall_min_s, self.stall_factor * pace)
            )
        return verdict

    def _slow_step(self, dt: float, med: float, threshold: float) -> str:
        self.slow_steps += 1
        if self.telemetry is not None:
            self.telemetry.record_slow_step()
        if self.events is not None:
            self.events.emit(
                SLOW_STEP_EVENT,
                step=self._step,
                epoch=self._epoch,
                seconds=round(dt, 6),
                median_s=round(med, 6),
                threshold_s=round(threshold, 6),
            )
        logger.warning(
            "slow step %d: %.3fs vs median %.3fs (threshold %.3fs)",
            self._step, dt, med, threshold,
        )
        self._maybe_auto_trace(SLOW_STEP_EVENT, dt)
        return SLOW_STEP_EVENT

    def pause(self) -> None:
        """Disarm across non-step work (probe, checkpoint, validation):
        the next tick re-anchors the clock without sampling the gap."""
        self._watchdog.disarm()
        self._last_tick = None

    def close(self) -> None:
        self._watchdog.close()

    # -- stall path (watchdog thread) ----------------------------------

    def _on_stall(self, armed_at: float) -> None:
        silence = self._clock() - armed_at
        self.stalls += 1
        # the call the host loop sits in: the innermost span open on the main
        # thread, written out with everything recorded so far
        spans = recorder()
        stuck = spans.current(threading.main_thread().ident)
        in_span = stuck.name if stuck is not None else None
        if self.telemetry is not None:
            self.telemetry.record_stall()
        if self.events is not None:
            self.events.emit(
                STALL_EVENT,
                step=self._step,
                epoch=self._epoch,
                silence_s=round(silence, 3),
                span=in_span,
            )
        try:
            spans.flush(spans_path(self.save_dir))
        except OSError as exc:
            logger.warning("spans not written on a stall: %s", exc)
        logger.warning(
            "stall: no step completed for %.1fs after step %d (epoch %d), in "
            "span %s",
            silence,
            self._step,
            self._epoch,
            in_span,
        )
        self._maybe_auto_trace(STALL_EVENT, silence)

    # -- automatic capture ---------------------------------------------

    def _maybe_auto_trace(self, reason: str, seconds: float) -> None:
        if not self.auto_trace_enabled:
            return
        now = self._clock()
        with self._trace_lock:
            if self._traces_started >= self.auto_trace_max:
                return
            if (
                self._last_trace_at is not None
                and now - self._last_trace_at < self.auto_trace_cooldown_s
            ):
                return
            self._traces_started += 1
            self._last_trace_at = now
            seq = self._traces_started
        trace_dir = os.path.join(
            self.save_dir, AUTO_TRACE_DIR, f"trace-{int(time.time())}-{seq:03d}"
        )
        # capture_trace sleeps for the whole window; never block the
        # caller (the train loop, or the watchdog that must stay alive)
        threading.Thread(
            target=self._capture,
            args=(trace_dir, reason, round(seconds, 3)),
            name="anomaly-auto-trace",
            daemon=True,
        ).start()

    def _capture(self, trace_dir: str, reason: str, seconds: float) -> None:
        capture = self._capture_fn
        if capture is None:
            from simclr_tpu.utils.profiling import capture_trace as capture
        try:
            os.makedirs(trace_dir, exist_ok=True)
            capture(trace_dir, self.auto_trace_ms / 1000.0)
        except Exception as exc:  # TraceInProgressError, profiler failures
            logger.warning("auto-trace (%s) failed: %s", reason, exc)
            return
        self.auto_traces += 1
        if self.telemetry is not None:
            self.telemetry.record_auto_trace()
        if self.events is not None:
            self.events.emit(
                AUTO_TRACE_EVENT,
                reason=reason,
                trigger_s=seconds,
                trace_dir=trace_dir,
                ms=self.auto_trace_ms,
                step=self._step,
                epoch=self._epoch,
            )
        logger.warning("auto-trace (%s) captured into %s", reason, trace_dir)


class _Watchdog:
    """Daemon thread that fires ``on_stall(armed_at)`` once per arming
    when the deadline passes without a re-arm or disarm."""

    def __init__(self, on_stall, clock=time.monotonic):
        self._on_stall = on_stall
        self._clock = clock
        self._cv = threading.Condition()
        self._deadline: float | None = None
        self._armed_at: float | None = None
        self._closed = False
        self._thread = threading.Thread(
            target=self._run, name="step-watchdog", daemon=True
        )
        self._thread.start()

    def arm(self, deadline: float) -> None:
        with self._cv:
            self._deadline = deadline
            self._armed_at = self._clock()
            self._cv.notify()

    def disarm(self) -> None:
        with self._cv:
            self._deadline = None
            self._cv.notify()

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify()
        self._thread.join(timeout=5.0)

    def _run(self) -> None:
        while True:
            with self._cv:
                if self._closed:
                    return
                if self._deadline is None:
                    self._cv.wait()
                    continue
                remaining = self._deadline - self._clock()
                if remaining > 0:
                    # a fake clock in tests never advances; the timed
                    # wait keeps this loop from spinning in that case
                    self._cv.wait(remaining)
                    continue
                armed_at = self._armed_at
                self._deadline = None  # fire once per arm
            self._on_stall(armed_at)


def maybe_detector(
    cfg, save_dir: str, *, telemetry=None, events=None
) -> StepAnomalyDetector | None:
    """Config-gated constructor used by the trainers (process 0 only)."""
    if not bool(cfg.select("telemetry.anomaly", True)):
        return None
    return StepAnomalyDetector(
        save_dir,
        telemetry=telemetry,
        events=events,
        warmup=int(cfg.select("telemetry.anomaly_warmup", 8)),
        slow_factor=float(cfg.select("telemetry.slow_step_factor", 4.0)),
        stall_factor=float(cfg.select("telemetry.stall_factor", 10.0)),
        stall_min_s=float(cfg.select("telemetry.stall_min_s", 2.0)),
        auto_trace=bool(cfg.select("telemetry.auto_trace", False)),
        auto_trace_ms=float(cfg.select("telemetry.auto_trace_ms", 500)),
        auto_trace_cooldown_s=float(
            cfg.select("telemetry.auto_trace_cooldown_s", 300.0)
        ),
        auto_trace_max=int(cfg.select("telemetry.auto_trace_max", 3)),
    )
