"""Profiling & step-timing — a subsystem the reference lacks entirely.

SURVEY §5.1: the reference has no profiler hooks or timers anywhere. TPU
builds live or die by the profile, so this module provides:

  * :func:`start_server` — ``jax.profiler`` trace server for live capture
    (connect with TensorBoard / xprof);
  * :func:`trace` — context manager writing a trace for a code region;
  * :class:`StepTimer` — fenced step timing with imgs/sec and
    imgs/sec/chip (the BASELINE.json north-star metric).
"""

from __future__ import annotations

import contextlib
import threading
import time

import jax


def synchronize(tree) -> None:
    """Wait until every array in ``tree`` has been computed on device."""
    jax.block_until_ready(tree)


def start_server(port: int = 9999):
    """Start the profiler server; returns the server object (keep it alive)."""
    return jax.profiler.start_server(port)


def device_trace_options():
    """Profiler options for a capture of the device planes only, as the
    benchmark's capture takes it. With JAX's defaults (Python and host
    tracers on) a 3 s capture of the training loop was 70-140 MB and blocked
    its caller for one to two minutes while it was written out (one host
    event per image row in ``device_put``); with both off, 13 MB and a few
    seconds (chip runs, PR 24, root PERF.md). What the host did meanwhile is
    in ``spans.jsonl`` (``obs/trace.py``), on a clock the trace's
    ``profile_start_time`` ties to."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    return options


@contextlib.contextmanager
def trace(log_dir: str):
    """Capture a profiler trace of the enclosed region into ``log_dir``."""
    jax.profiler.start_trace(log_dir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


class TraceInProgressError(RuntimeError):
    """A profiler capture is already running (jax allows one at a time)."""


# serializes on-demand captures (obs/exporter.py handler threads); a capture
# racing a StepTraceWindow still fails inside jax, reported as this error
_capture_lock = threading.Lock()


def capture_trace(log_dir: str, seconds: float) -> None:
    """Blocking on-demand profiler capture of the NEXT ``seconds`` of device
    activity into ``log_dir`` (the ``POST /debug/trace?ms=N`` backend).

    The capture rides alongside the training loop without touching it: the
    profiler observes whatever the devices are doing, so this adds no sync
    to the loop — only the exporter's handler thread sleeps.
    """
    if not _capture_lock.acquire(blocking=False):
        raise TraceInProgressError("a profiler capture is already in progress")
    try:
        try:
            jax.profiler.start_trace(
                str(log_dir), profiler_options=device_trace_options()
            )
        except Exception as e:  # e.g. a StepTraceWindow already tracing
            raise TraceInProgressError(str(e)) from e
        try:
            time.sleep(max(float(seconds), 0.0))
        finally:
            jax.profiler.stop_trace()
    finally:
        _capture_lock.release()


class StepTraceWindow:
    """Capture a profiler trace of steps [start, start+length) of a loop.

    Call :meth:`tick` once per step with the host step index (before
    running the step); call :meth:`close` after the loop — the trace is
    stopped there too if the window ran past the end of training.
    """

    def __init__(self, log_dir: str | None, start: int, length: int, enabled: bool = True):
        self.log_dir = log_dir
        self.start = start
        self.stop_at = start + length
        self.enabled = bool(log_dir) and enabled
        self._active = False

    def tick(self, step: int, pending=None) -> None:
        if not self.enabled:
            return
        if not self._active and step == self.start:
            jax.profiler.start_trace(
                str(self.log_dir), profiler_options=device_trace_options()
            )
            self._active = True
        elif self._active and step >= self.stop_at:
            synchronize(pending)
            jax.profiler.stop_trace()
            self._active = False
            self.enabled = False

    def close(self, pending=None) -> None:
        if self._active:
            synchronize(pending)
            jax.profiler.stop_trace()
            self._active = False
            self.enabled = False


class StepTimer:
    """Steady-state throughput measurement for a compiled step.

    The first ``WARMUP`` ticks (the compiling step and the queue filling
    behind it) are outside the window. Usage::

        timer = StepTimer(global_batch)
        for i in range(n):
            out = step(...)
            timer.tick(out)        # blocks on the WARMUP-th tick only
        print(timer.summary())
    """

    WARMUP = 3  # timing starts at this tick

    def __init__(self, global_batch: int):
        self.global_batch = global_batch
        self._count = 0
        self._t0: float | None = None
        self._timed_steps = 0
        self._last = None
        self._paused_at: float | None = None
        self._excluded = 0.0

    def tick(self, device_output=None) -> None:
        self._count += 1
        self._last = device_output
        if self._count == self.WARMUP:
            synchronize(device_output)
            self._t0 = time.perf_counter()
        elif self._count > self.WARMUP:
            self._timed_steps += 1

    def pause(self, device_output=None) -> None:
        """Exclude a non-step interval (checkpoint save, eval sweep) from the
        timed window. Fences outstanding step work first, so the excluded
        span contains only the paused activity."""
        if self._t0 is not None and self._paused_at is None:
            synchronize(device_output if device_output is not None else self._last)
            self._paused_at = time.perf_counter()

    def resume(self) -> None:
        if self._paused_at is not None:
            self._excluded += time.perf_counter() - self._paused_at
            self._paused_at = None

    def summary(self) -> dict:
        if self._t0 is None or self._timed_steps == 0:
            return {"imgs_per_sec": 0.0, "imgs_per_sec_per_chip": 0.0, "steps": 0}
        self.resume()
        synchronize(self._last)
        dt = time.perf_counter() - self._t0 - self._excluded
        imgs_per_sec = self._timed_steps * self.global_batch / dt
        return {
            "imgs_per_sec": imgs_per_sec,
            "imgs_per_sec_per_chip": imgs_per_sec / jax.device_count(),
            "steps": self._timed_steps,
            "seconds": dt,
        }
