"""Training-side telemetry registry (the ``simclr_train_*`` metric set).

One :class:`Telemetry` per run, wired into ``main.py``/``supervised.py`` and
scraped by ``obs/exporter.py``. The cardinal design rule (Podracer,
PAPERS.md: monitoring must cost zero host syncs): every update takes only
host-side floats the training loop ALREADY fetched through its
``utils/profiling.synchronize`` value fences — the epoch loss, the schedule
lr, wall-clock epoch durations. Rendering ``/metrics`` reads those floats
back; no method here ever touches a ``jax.Array``, so a scrape can never
add a device round-trip to the hot loop.

MFU divides XLA's own count of the step program's FLOPs per device-step
(the compile sentry's cost analysis, ``observe_xla_cost``) by the measured
step time over the chip's bf16 peak (``obs/peaks.py``, keyed by
``device_kind``; no peak or no recorded step program, no MFU).
Grad-allreduce wire bytes come from
:func:`simclr_tpu.parallel.compress.allreduce_wire_bytes` — analytic, per
device, per step.
"""

from __future__ import annotations

import threading
import time

from simclr_tpu.obs.metrics import Counter, Gauge, Histogram, Summary

# step-time bucket bounds (seconds): 1 ms (CIFAR-small steps on chip) up
# through minutes (a large model's step on a CPU test mesh)
STEP_TIME_BUCKETS = (
    0.001, 0.002, 0.005, 0.01, 0.02, 0.05, 0.1, 0.25, 0.5,
    1.0, 2.5, 5.0, 10.0, 30.0, 60.0, 120.0, 300.0,
)


class Telemetry:
    """The run's metric registry; see module docstring. Usage::

        telemetry = Telemetry(global_batch=512, n_devices=1, ...)
        telemetry.observe_epoch(epoch, loss=..., lr=..., steps=..., seconds=...)
        text = telemetry.render()        # the /metrics payload
        beat = telemetry.snapshot()      # the heartbeat.json enrichment

    ``flops_per_step`` is XLA's FLOP count of the newest watched step
    program, per device-step (``observe_xla_cost``); until one is recorded
    the MFU gauge reads 0. ``peak_flops`` is the chip's bf16 peak from
    ``obs/peaks.py``; ``None`` (the CPU platform) leaves the MFU gauge unset.
    """

    def __init__(
        self,
        *,
        global_batch: int,
        n_devices: int,
        mesh_hosts: int = 1,
        grad_allreduce: str = "exact",
        grad_elements: int | None = None,
        allreduce_devices: int | None = None,
        comm_overlap: str = "off",
        comm_chunks: int = 1,
        peak_flops: float | None = None,
    ):
        self.global_batch = int(global_batch)
        self.n_devices = max(int(n_devices), 1)
        self.peak_flops = float(peak_flops) if peak_flops else None
        self.flops_per_step: float | None = None
        self._lock = threading.Lock()

        self.step_time = Histogram(
            "simclr_train_step_time_seconds",
            "Mean step wall time, observed once per epoch from the host loop",
            STEP_TIME_BUCKETS,
        )
        self.imgs_per_sec = Gauge(
            "simclr_train_imgs_per_sec",
            "Training throughput over the last epoch (dataset images/s)")
        self.imgs_per_sec_per_chip = Gauge(
            "simclr_train_imgs_per_sec_per_chip",
            "Per-device training throughput over the last epoch")
        self.mfu = Gauge(
            "simclr_train_mfu",
            "Model FLOPs utilization vs the chip's bf16 peak, from XLA's FLOP "
            "count of the compiled step program (0 until one is recorded, or "
            "when no peak applies)")
        self.loss = Gauge(
            "simclr_train_loss", "Epoch-mean training loss (last epoch)")
        self.lr = Gauge(
            "simclr_train_lr", "Learning rate at the last completed step")
        self.epoch = Gauge(
            "simclr_train_epoch", "Last completed epoch")
        self.epochs_total = Gauge(
            "simclr_train_epochs_total", "Configured total epochs for the run")
        self.step = Gauge(
            "simclr_train_step", "Last completed optimizer step")
        self.val_acc = Gauge(
            "simclr_train_val_acc",
            "Latest validation/monitor-probe accuracy (0 until first probe)")
        self.allreduce_wire_bytes = Gauge(
            "simclr_train_grad_allreduce_wire_bytes",
            "Analytic per-device wire bytes of one gradient all-reduce "
            "(parallel/compress.py)")
        self.checkpoint_save_seconds = Summary(
            "simclr_train_checkpoint_save_seconds",
            "Checkpoint save duration (excluded from throughput windows)")
        self.checkpoint_restore_seconds = Summary(
            "simclr_train_checkpoint_restore_seconds",
            "Checkpoint restore duration (resume and NaN rollback)")
        self.checkpoint_saves = Counter(
            "simclr_train_checkpoint_saves_total", "Checkpoints saved")
        self.nan_rollbacks = Counter(
            "simclr_train_nan_rollbacks_total",
            "Non-finite-loss rollbacks booked against the retry budget")
        self.anomaly_slow_steps = Counter(
            "simclr_train_anomaly_slow_steps_total",
            "Steps classified slow by the rolling median/MAD detector "
            "(obs/anomaly.py)")
        self.anomaly_stalls = Counter(
            "simclr_train_anomaly_stalls_total",
            "Stall-watchdog firings: no step completed within the armed "
            "deadline")
        self.auto_traces = Counter(
            "simclr_train_auto_traces_total",
            "Automatic profiler captures fired by the anomaly detector")
        self.scrape_disconnects = Counter(
            "simclr_train_scrape_disconnects_total",
            "Scrape responses dropped mid-write by a disconnecting peer")
        self.compiles = Counter(
            "simclr_train_compiles_total",
            "XLA compilations recorded by the compile sentry (obs/compile.py)")
        self.compile_seconds = Summary(
            "simclr_train_compile_seconds",
            "Wall time of each recorded XLA lower+compile")
        self.recompile_alarms = Counter(
            "simclr_train_recompile_alarms_total",
            "Post-warmup recompilations of a watched step function — the "
            "silent TPU perf killer")
        self.mesh_hosts = Gauge(
            "simclr_train_mesh_hosts",
            "Host processes backing the current mesh — drops on an elastic "
            "remesh-down, recovers on grow-back (parallel/mesh.py "
            "mesh_host_count)")
        self.mesh_hosts.set(float(max(int(mesh_hosts), 1)))
        self.grad_allreduce_mode = str(grad_allreduce)
        self.comm_overlap = str(comm_overlap)
        self.comm_chunks = int(comm_chunks)
        # name -> (flops/step, bytes/step) from the compile sentry, rendered
        # as labeled per-executable cost gauges
        self._xla_costs: dict[str, tuple[float, float]] = {}
        self._device_monitor = None
        if grad_elements:
            from simclr_tpu.parallel.compress import allreduce_wire_bytes

            # the gradient all-reduce spans the DATA axis, not the full mesh
            self.allreduce_wire_bytes.set(
                allreduce_wire_bytes(
                    int(grad_elements),
                    allreduce_devices or self.n_devices,
                    self.grad_allreduce_mode,
                    overlap=self.comm_overlap,
                    chunks=self.comm_chunks,
                )
            )
        self._metrics = (
            self.step_time, self.imgs_per_sec, self.imgs_per_sec_per_chip,
            self.mfu, self.loss, self.lr, self.epoch, self.epochs_total,
            self.step, self.val_acc, self.allreduce_wire_bytes,
            self.checkpoint_save_seconds, self.checkpoint_restore_seconds,
            self.checkpoint_saves, self.nan_rollbacks,
            self.anomaly_slow_steps, self.anomaly_stalls, self.auto_traces,
            self.scrape_disconnects, self.compiles, self.compile_seconds,
            self.recompile_alarms, self.mesh_hosts,
        )
        self._started = time.time()
        # mean step time over the last completed epoch: its wall time, the
        # boundary's sync included, over its steps (the anomaly detector reads it)
        self.step_time_s = 0.0

    def attach_device_monitor(self, monitor) -> None:
        """Render the DeviceMonitor's HBM gauges with every scrape.

        Sampling happens inside :meth:`render`, i.e. on the exporter's
        handler thread — host-side ``memory_stats`` queries, zero device
        syncs (the monitor's contract, see obs/device.py).
        """
        self._device_monitor = monitor

    # -- update hooks (host floats only; no device values) -----------------
    def observe_epoch(
        self,
        epoch: int,
        *,
        epochs: int,
        step: int,
        steps: int,
        seconds: float,
        loss: float,
        lr: float,
    ) -> None:
        """Once per completed epoch: ``steps`` host-loop steps took
        ``seconds`` of wall clock (non-step work like eval/saves excluded by
        the caller's timer pauses where it matters). The loop knows the
        epoch's step count and duration without extra syncs."""
        self.epoch.set(float(epoch))
        self.epochs_total.set(float(epochs))
        self.step.set(float(step))
        self.loss.set(float(loss))
        self.lr.set(float(lr))
        steps = max(int(steps), 1)
        seconds = max(float(seconds), 1e-9)
        step_time = seconds / steps
        self.step_time.observe(step_time)
        self.step_time_s = step_time
        rate = steps * self.global_batch / seconds
        self.imgs_per_sec.set(rate)
        self.imgs_per_sec_per_chip.set(rate / self.n_devices)
        if self.flops_per_step and self.peak_flops:
            self.mfu.set(self.flops_per_step / (step_time * self.peak_flops))

    def observe_save(self, seconds: float) -> None:
        self.checkpoint_save_seconds.observe(float(seconds))
        self.checkpoint_saves.inc()

    def observe_restore(self, seconds: float) -> None:
        self.checkpoint_restore_seconds.observe(float(seconds))

    def observe_val_acc(self, acc: float) -> None:
        self.val_acc.set(float(acc))

    def record_nan_rollback(self) -> None:
        self.nan_rollbacks.inc()

    def record_slow_step(self) -> None:
        self.anomaly_slow_steps.inc()

    def record_stall(self) -> None:
        self.anomaly_stalls.inc()

    def record_auto_trace(self) -> None:
        self.auto_traces.inc()

    def record_scrape_disconnect(self) -> None:
        self.scrape_disconnects.inc()

    def record_compile(self, seconds: float) -> None:
        self.compiles.inc()
        self.compile_seconds.observe(float(seconds))

    def record_recompile_alarm(self) -> None:
        self.recompile_alarms.inc()

    def observe_xla_cost(
        self, name: str, *, flops_per_step: float = 0.0,
        bytes_per_step: float = 0.0,
    ) -> None:
        """Per-executable analytic cost from the compile sentry. Every
        executable the trainers watch is a step program, so the newest one's
        FLOPs per device-step are what the MFU gauge divides."""
        with self._lock:
            self._xla_costs[str(name)] = (
                float(flops_per_step), float(bytes_per_step)
            )
        if flops_per_step > 0:
            self.flops_per_step = float(flops_per_step)

    # -- read side ----------------------------------------------------------
    def snapshot(self) -> dict:
        """The compact latest-values dict riding on ``heartbeat.json`` (and
        surfaced by ``supervisor_summary.json``)."""
        return {
            "epoch": self.epoch.value,
            "step": self.step.value,
            "loss": self.loss.value,
            "lr": self.lr.value,
            # the FleetCollector's skew ratio divides these across hosts,
            # so the snapshot carries the scalar, not just the histogram
            "step_time_s": self.step_time_s,
            "imgs_per_sec": self.imgs_per_sec.value,
            "imgs_per_sec_per_chip": self.imgs_per_sec_per_chip.value,
            "mfu": self.mfu.value,
            "slow_steps": self.anomaly_slow_steps.value,
            "stalls": self.anomaly_stalls.value,
            "auto_traces": self.auto_traces.value,
            "compiles": self.compiles.value,
            "recompile_alarms": self.recompile_alarms.value,
            "mesh_hosts": self.mesh_hosts.value,
            "uptime_s": round(time.time() - self._started, 3),
        }

    def render(self) -> str:
        parts = [m.render() for m in self._metrics]
        # mode as a labeled constant gauge — the Prometheus idiom for
        # categorical facts (like build_info)
        parts.append(
            "# HELP simclr_train_grad_allreduce_mode Wire format of the "
            "data-axis gradient all-reduce\n"
            "# TYPE simclr_train_grad_allreduce_mode gauge\n"
            f'simclr_train_grad_allreduce_mode{{mode="{self.grad_allreduce_mode}"}} 1\n'
        )
        with self._lock:
            costs = dict(self._xla_costs)
        if costs:
            flop_lines = "".join(
                f'simclr_train_xla_cost_flops{{executable="{name}"}} '
                f"{flops:g}\n"
                for name, (flops, _) in sorted(costs.items())
            )
            byte_lines = "".join(
                f'simclr_train_xla_cost_bytes_accessed{{executable="{name}"}} '
                f"{nbytes:g}\n"
                for name, (_, nbytes) in sorted(costs.items())
            )
            parts.append(
                "# HELP simclr_train_xla_cost_flops XLA analytic flops per "
                "step of each compiled executable (obs/compile.py)\n"
                "# TYPE simclr_train_xla_cost_flops gauge\n" + flop_lines
            )
            parts.append(
                "# HELP simclr_train_xla_cost_bytes_accessed XLA analytic "
                "bytes accessed per step of each compiled executable\n"
                "# TYPE simclr_train_xla_cost_bytes_accessed gauge\n"
                + byte_lines
            )
        if self._device_monitor is not None:
            # live HBM sampling happens here, on the scraping thread; a
            # backend hiccup must never break the whole /metrics payload
            try:
                parts.append(self._device_monitor.render())
            except Exception:
                pass
        return "".join(parts)
