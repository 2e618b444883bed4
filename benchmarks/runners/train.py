"""Runner ``train``: one pretraining run through the trainer's own entry point.

One process, because one process may hold the chip: the entry point named by
the mix (``python -m <entry>``'s ``main``) runs on the main thread, where its
preemption guard takes its signals, and a conductor thread watches the run
directory with the benchmark's own clock:

    process start .. first heartbeat with step >= 1     set-up
    first heartbeat .. + ``--seconds``                  the window
    epoch events seen inside the window                 the measured epochs
    last such event + ``settle_s``                      SIGTERM
    SIGTERM .. the trainer's ``SystemExit`` in hand     preemption

The trainer writes an ``epoch`` event after it has fetched the epoch's loss,
so the device has finished the epoch when the line appears. The conductor
stamps each line when it sees it (every 5 ms), so the times are the
benchmark's and only the signal is the program's. The last event of the
window is the one after which another epoch and ``settle_s`` would not fit.
SIGTERM goes out ``settle_s`` after it, a fixed point of the epoch's cycle:
the host then has its full queue of steps in flight, as for most of a real
run, and what has to drain is the same in every run. A SIGTERM at a random
point would drain anything between nothing and the whole queue (0 to 1.5 s
of a 2.5 s figure at 2048 images a step), and the metric would read noise.

If the trainer stalls and no event comes, SIGTERM goes out at the window's
end (seen once in 38 runs on the chip: a loss fetch that hung for 8 s).

With ``--trace 1`` a third thread captures the device's trace from 0.7 of
the window on, for ``TRACE_LEAD_S`` + ``trace_s`` seconds, of which the last
``trace_s`` are reduced. SIGTERM waits until the trace is written out (a few
seconds on one chip, about 50 s on four), so a traced run can outlast its
window; the epochs the profiler touched are left out of what it measures.
"""

from __future__ import annotations

import importlib
import json
import os
import shutil
import signal
import sys
import threading
import time

from benchmarks import reduce

POLL_S = 0.005
FIRST_BEAT_LIMIT_S = 1150.0  # a first run compiles; the driver allows 1200
EXIT_LIMIT_S = 240.0  # SIGTERM to exit, before the run is given up
# Starting the profiler holds the host for one to two seconds. The device
# works that off its queue of steps and then starves: ResNet-50 at 512 idled
# 0.56 s some 1.5 s into a capture (chip run, PR 24). So a capture lasts
# this much longer than the seconds that are reduced, which are its last.
TRACE_LEAD_S = 4.0
# The capture begins at this share of the window. Writing the trace out
# takes a few seconds for ResNet-18 on one chip, ~35 s for ResNet-50 and
# ~50 s on four chips, and the run goes on meanwhile with a slowed host; the
# epochs before the capture are the traced run's clean ones, so it comes late.
TRACE_AT = 0.7


def _fail(message: str) -> None:
    """No result line, another exit code than 0."""
    print(f"benchmarks/runners/train.py: {message}", file=sys.stderr, flush=True)
    sys.exit(2)


def check_devices(chips: int, rehearse: bool) -> dict:
    """The devices as JAX reports them. Measuring needs exactly the cell's
    chips; the CPU is good enough only for a rehearsal."""
    import jax

    devices = jax.devices()
    found = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if found["platform"] == "cpu" and not rehearse:
        _fail(f"JAX found no accelerator: {found}")
    if found["count"] != chips:
        _fail(f"the cell asks for {chips} chip(s), JAX holds {found['count']}")
    return found


def memory_peak_bytes() -> int:
    """Peak bytes on the fullest chip. On the TPU the allocator counts the
    arrays (``peak_bytes_in_use``) and the runtime reserves each program's
    temporaries apart (``peak_bytes_reserved``: 7.2 GB for the ResNet-18 step
    at 2048 images, equal to the compiler's own figure, while the arrays peak
    at 0.3 GB). The chip holds both at once while a step runs. Backends that
    report nothing give 0."""
    import jax

    peaks = [0]
    for device in jax.local_devices():
        stats = device.memory_stats() or {}
        peaks.append(
            int(stats.get("peak_bytes_in_use", 0))
            + int(stats.get("peak_bytes_reserved", 0))
        )
    return max(peaks)


class Conductor(threading.Thread):
    """Watches the run directory, ends the window, sends SIGTERM."""

    def __init__(self, run_dir: str, seconds: float, settle_s: float,
                 trace: dict | None):
        super().__init__(daemon=True, name="bench-conductor")
        self.run_dir = run_dir
        self.seconds = seconds
        self.settle_s = settle_s
        self.trace = trace  # {"dir", "seconds"} or None
        self.trainer_done = threading.Event()
        self.t_first_beat: float | None = None
        self.t_sigterm: float | None = None
        self.events: list[dict] = []  # each with "seen", the conductor's clock
        self.capture: tuple[float, float] | None = None  # the profiler at work
        self.error: str | None = None
        self._offset = 0
        self._partial = b""

    # -- reading the run directory ------------------------------------------
    def _first_beat(self) -> bool:
        path = os.path.join(self.run_dir, "heartbeat.json")
        limit = time.monotonic() + FIRST_BEAT_LIMIT_S
        while not self.trainer_done.is_set():
            # events first: what the trainer wrote before its first beat
            # (run_start, compile) is then stamped before it
            self.poll_events()
            try:
                with open(path) as f:
                    if int(json.load(f).get("step", 0)) >= 1:
                        self.t_first_beat = time.monotonic()
                        return True
            except (OSError, ValueError):
                pass
            if time.monotonic() > limit:
                self.error = f"no first step within {FIRST_BEAT_LIMIT_S:.0f} s"
                return False
            time.sleep(POLL_S)
        return False

    def poll_events(self) -> None:
        """Append the lines ``events.jsonl`` has gained, stamped now."""
        path = os.path.join(self.run_dir, "events.jsonl")
        try:
            size = os.path.getsize(path)
        except OSError:
            return
        if size < self._offset:  # rewritten by a rollback's re-seat
            self._offset, self._partial = size, b""
        if size == self._offset:
            return
        seen = time.monotonic()
        with open(path, "rb") as f:
            f.seek(self._offset)
            data = self._partial + f.read(size - self._offset)
        self._offset = size
        *lines, self._partial = data.split(b"\n")
        for line in lines:
            try:
                event = json.loads(line)
            except ValueError:
                continue
            if isinstance(event, dict):
                self.events.append({**event, "seen": seen})

    # -- the trace ------------------------------------------------------------
    def _capture(self) -> None:
        import jax

        options = jax.profiler.ProfileOptions()
        # Only the device planes are reduced. With the host and Python
        # tracers on, a 3 s capture was 70-140 MB (one host event per image
        # row transposed) and took one to two minutes to write out (chip
        # runs, PR 24).
        options.python_tracer_level = 0
        options.host_tracer_level = 0
        t0 = time.monotonic()
        try:
            jax.profiler.start_trace(self.trace["dir"], profiler_options=options)
            try:
                time.sleep(TRACE_LEAD_S + self.trace["seconds"])
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # the run goes on; the metrics report nothing
            self.error = f"trace capture failed: {e!r}"
        self.capture = (t0, time.monotonic())

    # -- the window -----------------------------------------------------------
    def _last_epoch_event(self, deadline: float) -> float | None:
        """Polls until the window's last ``epoch`` event is in; returns when
        it was seen, or ``None`` if the window held none."""
        while not self.trainer_done.is_set():
            self.poll_events()
            seen = [e["seen"] for e in self.events if e.get("event") == "epoch"]
            now = time.monotonic()
            if seen:
                period = seen[-1] - (seen[-2] if len(seen) > 1 else self.t_first_beat)
                if seen[-1] + period + self.settle_s > deadline:
                    return seen[-1]
            if now > deadline:  # no event, or the trainer has stalled
                return seen[-1] if seen else None
            time.sleep(POLL_S)
        return None

    def run(self) -> None:
        if not self._first_beat():
            if self.error:
                print(f"bench-conductor: {self.error}", file=sys.stderr, flush=True)
                os._exit(3)
            return
        deadline = self.t_first_beat + self.seconds
        tracer = None
        if self.trace is not None:
            tracer = threading.Timer(TRACE_AT * self.seconds, self._capture)
            tracer.daemon = True
            tracer.start()
        last = self._last_epoch_event(deadline)
        # the trace is written out before SIGTERM; events go on being stamped
        # as they come, or the epochs after it would all read this moment
        while tracer is not None and tracer.is_alive():
            self.poll_events()
            time.sleep(POLL_S)
        if last is not None:
            time.sleep(max(0.0, last + self.settle_s - time.monotonic()))
        if self.trainer_done.is_set():
            return
        self.poll_events()
        self.t_sigterm = time.monotonic()
        os.kill(os.getpid(), signal.SIGTERM)
        if not self.trainer_done.wait(EXIT_LIMIT_S):
            print(
                f"bench-conductor: the trainer did not exit within "
                f"{EXIT_LIMIT_S:.0f} s of SIGTERM", file=sys.stderr, flush=True,
            )
            os._exit(3)


def run(cell: dict, opts: dict) -> dict:
    """Run the cell once; returns the run record the metric readers take."""
    mix, config = cell["traffic"], cell["config"]
    devices = check_devices(int(cell["chips"]), bool(opts["rehearse"]))
    run_dir = os.path.join(opts["work_dir"], "run")
    shutil.rmtree(opts["work_dir"], ignore_errors=True)
    os.makedirs(run_dir)
    trace = None
    if opts["trace"]:
        trace = {
            "dir": os.path.join(opts["work_dir"], "trace"),
            "seconds": float(mix["trace_s"]),
        }
    overrides = [
        *config.get("overrides", []),
        *mix.get("overrides", []),
        # the trainer draws weights, shuffle order and augmentation keys from
        # this one number; kept within 31 bits, which every PRNG in it takes
        f"parameter.seed={int(opts['seed']) % (2**31 - 1)}",
        f"experiment.save_dir={run_dir}",
    ]
    entry = importlib.import_module(mix["entry"])
    conductor = Conductor(run_dir, float(opts["seconds"]), float(mix["settle_s"]), trace)
    conductor.start()
    exit_code = 0
    try:
        entry.main(overrides)
    except SystemExit as e:
        exit_code = e.code if isinstance(e.code, int) else 1
    finally:
        t_exit = time.monotonic()
        conductor.trainer_done.set()
    conductor.join()
    conductor.poll_events()
    record = {
        "cell": cell,
        "seed": int(opts["seed"]),
        "seconds": float(opts["seconds"]),
        "run_dir": run_dir,
        "t_start": opts["t_start"],
        "t_first_beat": conductor.t_first_beat,
        "t_sigterm": conductor.t_sigterm,
        "t_exit": t_exit,
        "exit_code": exit_code,
        "events": conductor.events,
        "device": devices,
        "memory_peak_bytes": memory_peak_bytes(),
        "trace_dir": trace["dir"] if trace else None,
        "trace_s": trace["seconds"] if trace else None,
        "capture": conductor.capture,
        "conductor_error": conductor.error,
    }
    record["checkpoint_verifies"] = reduce.checkpoint_verifies(record)
    # verified, and hundreds of megabytes: the run directory keeps its small
    # files only
    for item in os.scandir(run_dir):
        if item.is_dir() and item.name.startswith("epoch="):
            shutil.rmtree(item.path, ignore_errors=True)
    return record
