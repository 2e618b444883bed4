"""Live HBM accounting: per-device allocator gauges and OOM forensics.

A :class:`DeviceMonitor` attached to the telemetry registry samples
``device.memory_stats()`` for every local device **at render time** — i.e.
on the exporter's handler thread, per scrape. ``memory_stats`` is a
host-side allocator query (no device sync, no dispatch), so continuous
scraping keeps the zero-added-syncs contract of the whole telemetry stack
(counting-tested in tests/test_obs_device.py).

``memory_stats`` is backend-dependent: TPU/GPU report ``bytes_in_use`` /
``peak_bytes_in_use`` / ``bytes_limit``, and the TPU runtime reserves each
loaded program's temporaries apart (``bytes_reserved`` /
``peak_bytes_reserved``: 7.2 GB beside 0.3 GB of arrays for ResNet-18 at
2048 images a step, root PERF.md), so a peak is the sum of the two; CPU
test meshes report nothing (or raise). Every access is hardened — a backend without stats degrades to
absent per-device gauges, never a KeyError. The host-side high-watermark
gauge renders unconditionally (0 until a backend reports), so every
backend serves at least one ``simclr_train_hbm_*`` line (the
``scripts/obs_smoke.py`` contract).

On RESOURCE_EXHAUSTED the trainers call :func:`maybe_dump_oom_profile`:
a ``jax.profiler.device_memory_profile()`` forensic lands in the run dir
and an ``oom`` event in ``events.jsonl`` before the error re-raises — the
allocator's final state survives the crash.
"""

from __future__ import annotations

import os
import threading

OOM_EVENT = "oom"
HBM_EVENT = "hbm"

# the forensic pprof dump written next to events.jsonl on an allocator OOM
OOM_PROFILE_NAME = "oom_device_memory.prof"

# substrings identifying an allocator out-of-memory failure; XLA raises
# RESOURCE_EXHAUSTED, some backends phrase it as plain "out of memory"
OOM_MARKERS = ("RESOURCE_EXHAUSTED", "Out of memory", "out of memory")

# the derived stat sample_memory_stats adds: peak_bytes_in_use +
# peak_bytes_reserved, what the chip held at once (the benchmark's
# memory_peak_bytes is the same sum)
PEAK_BYTES = "peak_bytes"

# (memory_stats key, metric name, help) for the per-device gauges
HBM_GAUGES = (
    (
        "bytes_in_use",
        "simclr_train_hbm_bytes_in_use",
        "Live allocator bytes in use per local device",
    ),
    (
        PEAK_BYTES,
        "simclr_train_hbm_peak_bytes",
        "Peak bytes per local device: the allocator's arrays plus the "
        "runtime's reservation for program temporaries",
    ),
    (
        "bytes_limit",
        "simclr_train_hbm_bytes_limit",
        "Allocator capacity per local device",
    ),
)

# an hbm event is emitted when the high-watermark grows by this factor
# over the last emitted value (bounds the event count to O(log growth))
_EMIT_GROWTH_FACTOR = 1.1


def local_devices() -> list:
    """``jax.local_devices()``, or ``[]`` when jax/the backend is absent.

    Module-level so tests can monkeypatch in fake devices with synthetic
    ``memory_stats`` (CPU reports none).
    """
    try:
        import jax

        return list(jax.local_devices())
    except Exception:
        return []


def sample_memory_stats(device) -> dict | None:
    """Backend-hardened ``device.memory_stats()``: numeric keys or None.

    Filters to int/float values so a backend returning partial or exotic
    payloads can never leak a non-numeric value into a gauge; a backend
    without the API (or returning nothing) yields None. Where the backend
    reports an allocator peak, ``peak_bytes`` is added: that peak plus the
    runtime's ``peak_bytes_reserved``.
    """
    try:
        stats = device.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    out = {}
    try:
        items = stats.items()
    except AttributeError:
        return None
    for key, value in items:
        if isinstance(value, (int, float)) and not isinstance(value, bool):
            out[str(key)] = int(value)
    if "peak_bytes_in_use" in out:
        out[PEAK_BYTES] = out["peak_bytes_in_use"] + out.get("peak_bytes_reserved", 0)
    return out or None


class DeviceMonitor:
    """Per-device HBM sampler rendered into the ``/metrics`` payload.
    Thread-safe: scrapes arrive on exporter handler threads.
    """

    def __init__(self, *, events=None, devices=None):
        self.events = events
        self._devices = devices
        self._lock = threading.Lock()
        self._peaks: dict[str, int] = {}
        self._high_watermark = 0
        self._last_emitted = 0

    # -- sampling (host-side allocator queries; zero device syncs) ---------
    def sample(self) -> dict[str, dict]:
        """One ``memory_stats`` pass over the local devices.

        Returns ``{device_label: {stat: bytes}}``; devices whose backend
        reports nothing are simply absent. Updates the per-device peaks
        and the run-wide high-watermark, and emits a rate-limited ``hbm``
        event when the watermark grows.
        """
        if self._devices is None:
            self._devices = local_devices()
        samples: dict[str, dict] = {}
        for i, device in enumerate(self._devices):
            stats = sample_memory_stats(device)
            if stats is None:
                continue
            label = str(getattr(device, "id", i))
            samples[label] = stats
            peak = max(
                stats.get(PEAK_BYTES, 0),
                stats.get("bytes_in_use", 0) + stats.get("bytes_reserved", 0),
            )
            with self._lock:
                if peak > self._peaks.get(label, 0):
                    self._peaks[label] = peak
                if peak > self._high_watermark:
                    self._high_watermark = peak
        self._maybe_emit(samples)
        return samples

    @property
    def high_watermark_bytes(self) -> int:
        with self._lock:
            return self._high_watermark

    def _maybe_emit(self, samples: dict[str, dict]) -> None:
        if self.events is None or not samples:
            return
        with self._lock:
            watermark = self._high_watermark
            if watermark <= self._last_emitted * _EMIT_GROWTH_FACTOR:
                return
            self._last_emitted = watermark
            peaks = dict(self._peaks)
        try:
            self.events.emit(
                HBM_EVENT,
                per_device=peaks,
                high_watermark=watermark,
            )
        except Exception:
            pass

    # -- rendering (called from Telemetry.render on the exporter thread) ---
    def render(self) -> str:
        samples = self.sample()
        parts = []
        for key, name, help_text in HBM_GAUGES:
            lines = [
                f'{name}{{device="{label}"}} {stats[key]:g}'
                for label, stats in samples.items()
                if key in stats
            ]
            if lines:
                parts.append(
                    f"# HELP {name} {help_text}\n"
                    f"# TYPE {name} gauge\n" + "\n".join(lines) + "\n"
                )
        # unconditional: every backend serves at least one HBM gauge
        parts.append(
            "# HELP simclr_train_hbm_high_watermark_bytes Highest per-device "
            "peak (arrays + reserved) observed this run (0 until the backend "
            "reports)\n"
            "# TYPE simclr_train_hbm_high_watermark_bytes gauge\n"
            f"simclr_train_hbm_high_watermark_bytes {self.high_watermark_bytes:g}\n"
        )
        return "".join(parts)


# -- OOM forensics ----------------------------------------------------------
def is_oom_error(exc: BaseException) -> bool:
    """Does this exception look like an allocator RESOURCE_EXHAUSTED?"""
    text = f"{type(exc).__name__}: {exc}"
    return any(marker in text for marker in OOM_MARKERS)


def maybe_dump_oom_profile(
    save_dir, exc: BaseException, *, events=None, profile_fn=None
) -> str | None:
    """On an allocator OOM: dump the device memory profile + ``oom`` event.

    Called from the trainers' crash path with the in-flight exception; a
    non-OOM error is a no-op. The ``jax.profiler.device_memory_profile()``
    pprof payload lands at ``<save_dir>/oom_device_memory.prof`` (what each
    live buffer is and who allocated it — the question a post-mortem asks
    first). Never raises: forensics must not mask the original error,
    which the caller re-raises.
    """
    if not is_oom_error(exc):
        return None
    path = os.path.join(str(save_dir), OOM_PROFILE_NAME)
    try:
        if profile_fn is None:
            import jax

            profile_fn = jax.profiler.device_memory_profile
        payload = profile_fn()
        os.makedirs(str(save_dir), exist_ok=True)
        with open(path, "wb") as f:
            f.write(payload)
    except Exception:
        path = None
    try:
        if events is not None:
            events.emit(OOM_EVENT, error=str(exc)[:500], profile=path)
    except Exception:
        pass
    return path


def maybe_monitor(cfg, *, events=None) -> DeviceMonitor | None:
    """Config-gated constructor used by the trainers (process 0 only)."""
    if not bool(cfg.select("telemetry.hbm", True)):
        return None
    return DeviceMonitor(events=events)
