"""Compile-side observability: cost extraction, compile records, recompile
sentry.

The host-side telemetry (PR 6/7) sees the run through step wall times; this
module watches the *compiler* boundary — the other place a TPU run silently
loses its performance:

* **Cost attribution** — :func:`executable_cost` extracts XLA's analytic
  flops / bytes-accessed from an AOT-compiled executable (promoted from
  ``scripts/perf_attrib.py`` so the one-off attribution script and the live
  telemetry share one extraction), and the per-executable numbers are
  exported as labeled gauges plus a roofline-vs-XLA MFU drift signal.
* **Compile records** — every lower+compile is timed, fingerprinted
  (sha256 of the lowered StableHLO/jaxpr text), and emitted as a
  ``compile`` event into ``events.jsonl`` — wall time, fingerprint,
  analytic cost in one line.
* **Recompile sentry** — a step function that recompiles after warmup is
  the classic silent TPU perf killer (a shape or dtype drifted and every
  step now pays a multi-second compile). :meth:`CompileSentry.watch` wraps
  a jitted step in an explicit AOT lower/compile cache keyed by the
  abstract argument signature, so any post-warmup compilation is observed
  the moment it happens: alarm counter + ``recompile_alarm`` event +
  the PR 7 rate-limited auto-trace hook.

``watch`` takes the AOT path (``fn.lower(*args).compile()`` — the only
way to both time a compile precisely and keep the executable for
fingerprint/cost analysis); a lowering or compile error propagates. Only a
wrapped callable that has no ``lower`` at all is dispatched plainly with
call-duration timing. Stdlib-only at import time; jax is imported lazily
inside the signature helper.
"""

from __future__ import annotations

import hashlib
import threading
import time

COMPILE_EVENT = "compile"
RECOMPILE_ALARM_EVENT = "recompile_alarm"


def executable_cost(compiled) -> tuple[float, float]:
    """(flops, bytes_accessed) from an AOT executable's XLA cost analysis.

    The extraction ``scripts/perf_attrib.py`` used privately, promoted so
    live telemetry and the attribution script agree by construction. A
    backend whose analysis omits a key reports 0.0 for it.
    """
    ca = compiled.cost_analysis() or {}
    return float(ca.get("flops", 0.0)), float(ca.get("bytes accessed", 0.0))


def lowered_fingerprint(lowered) -> str:
    """Stable hex fingerprint of a lowered program's StableHLO/jaxpr text.

    Two lowerings of the same function at the same abstract signature hash
    identically, so a changed fingerprint in a ``compile`` event names a
    genuinely different program, not a re-run.
    """
    return hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]


def args_signature(args: tuple) -> tuple:
    """Hashable abstract signature of a call's arguments.

    Array leaves contribute ``(shape, dtype)``; non-array leaves (python
    scalars — jit's weak types) contribute their type only, so a step
    counter changing value does not look like a new program. This is the
    compile-cache key the sentry's AOT cache shares with jit's own
    dispatch logic for our purposes: same signature, same executable.
    """
    import jax

    def leaf_sig(x):
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return (type(x).__name__,)
        return (tuple(shape), str(dtype))

    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (tuple(leaf_sig(leaf) for leaf in leaves), str(treedef))


class CompileSentry:
    """Registry of observed XLA compilations for one run.

    ``record_compile`` is the single funnel: it counts, emits the
    ``compile`` event, pushes cost numbers into telemetry, and — when the
    compile happened after warmup (``warm=True``) — raises the recompile
    alarm (counter + ``recompile_alarm`` event + auto-trace hook).
    ``auto_trace`` is the detector's ``_maybe_auto_trace(reason, seconds)``
    bound method (PR 7), so alarms share its cooldown and budget.
    """

    def __init__(
        self,
        *,
        telemetry=None,
        events=None,
        auto_trace=None,
        clock=time.perf_counter,
    ):
        self.telemetry = telemetry
        self.events = events
        self._auto_trace = auto_trace
        self._clock = clock
        self._lock = threading.Lock()
        self.records: list[dict] = []
        self.compiles = 0
        self.recompile_alarms = 0

    def record_compile(
        self,
        name: str,
        *,
        seconds: float,
        fingerprint: str = "",
        flops: float = 0.0,
        bytes_accessed: float = 0.0,
        steps_per_call: int = 1,
        warm: bool = False,
        cache_hit: bool | None = None,
    ) -> dict:
        """Book one observed compilation of executable ``name``.

        ``steps_per_call`` normalizes cost for epoch-compiled programs (one
        executable runs a whole epoch's scan) so per-step cost gauges and
        the MFU drift compare like with like. ``warm=True`` marks a
        post-warmup compilation and fires the alarm path. ``cache_hit``
        says whether JAX's persistent compilation cache supplied the
        executable (``seconds`` is then lowering plus a load, not a
        compile); ``None`` when the caller cannot tell.
        """
        steps = max(int(steps_per_call), 1)
        record = {
            "name": str(name),
            "seconds": float(seconds),
            "fingerprint": fingerprint,
            "flops": float(flops),
            "bytes_accessed": float(bytes_accessed),
            "steps_per_call": steps,
            "recompile": bool(warm),
        }
        known = {} if cache_hit is None else {"cache_hit": bool(cache_hit)}
        record.update(known)
        with self._lock:
            self.records.append(record)
            self.compiles += 1
            if warm:
                self.recompile_alarms += 1
        if self.telemetry is not None:
            self.telemetry.record_compile(seconds)
            self.telemetry.observe_xla_cost(
                name,
                flops_per_step=record["flops"] / steps,
                bytes_per_step=record["bytes_accessed"] / steps,
            )
        if self.events is not None:
            self.events.emit(
                COMPILE_EVENT,
                name=record["name"],
                seconds=round(record["seconds"], 6),
                fingerprint=fingerprint,
                flops=record["flops"],
                bytes_accessed=record["bytes_accessed"],
                recompile=bool(warm),
                **known,
            )
        if warm:
            if self.telemetry is not None:
                self.telemetry.record_recompile_alarm()
            if self.events is not None:
                self.events.emit(
                    RECOMPILE_ALARM_EVENT,
                    name=record["name"],
                    seconds=round(record["seconds"], 6),
                    fingerprint=fingerprint,
                )
            if self._auto_trace is not None:
                try:
                    self._auto_trace(RECOMPILE_ALARM_EVENT, record["seconds"])
                except Exception:
                    pass
        return record

    def watch(self, fn, name: str, *, steps_from_args=None):
        """Wrap a jitted callable so its every compilation is observed."""
        return WatchedFunction(fn, name, self, steps_from_args=steps_from_args)


class WatchedFunction:
    """AOT lower/compile wrapper around one jitted step function.

    Keeps its own signature-keyed executable cache — each new abstract
    signature triggers an explicit ``lower`` + timed ``compile`` whose
    executable is fingerprinted and cost-analyzed, then cached; repeat
    signatures dispatch straight to the cached executable. Donation and
    sharding are captured at lowering, so the compiled program behaves
    exactly like the jit dispatch it replaces. A signature seen after the
    first completed call means the step function recompiled after warmup —
    the sentry's alarm condition.

    Called from the single training-loop thread (matching how the step
    functions it wraps are used); the sentry's own bookkeeping is locked.
    """

    def __init__(self, fn, name: str, sentry: CompileSentry, *, steps_from_args=None):
        self._fn = fn
        self.name = str(name)
        self._sentry = sentry
        self._steps_from_args = steps_from_args
        self._cache: dict = {}
        self._calls = 0

    def _steps_per_call(self, args) -> int:
        if self._steps_from_args is None:
            return 1
        try:
            return max(int(self._steps_from_args(args)), 1)
        except Exception:
            return 1

    def __call__(self, *args):
        sig = args_signature(args)
        entry = self._cache.get(sig)
        if entry is not None:
            self._calls += 1
            return entry(*args)
        warm = self._calls > 0
        clock = self._sentry._clock
        t0 = clock()
        if not hasattr(self._fn, "lower"):
            # not a jitted function: dispatch plainly — the first call at a
            # new signature still IS the compiling call, so its duration is
            # the (upper-bound) compile time
            out = self._fn(*args)
            self._sentry.record_compile(
                self.name,
                seconds=clock() - t0,
                warm=warm,
                steps_per_call=self._steps_per_call(args),
            )
            self._cache[sig] = self._fn
            self._calls += 1
            return out
        from simclr_tpu.utils.platform import compilation_cache_stats

        cache_hits = compilation_cache_stats()["hits"]
        lowered = self._fn.lower(*args)
        compiled = lowered.compile()
        cache_hit = compilation_cache_stats()["hits"] > cache_hits
        flops, bytes_accessed = executable_cost(compiled)
        self._sentry.record_compile(
            self.name,
            seconds=clock() - t0,
            fingerprint=lowered_fingerprint(lowered),
            flops=flops,
            bytes_accessed=bytes_accessed,
            warm=warm,
            steps_per_call=self._steps_per_call(args),
            cache_hit=cache_hit,
        )
        self._cache[sig] = compiled
        self._calls += 1
        return compiled(*args)


def maybe_sentry(cfg, *, telemetry=None, events=None, detector=None):
    """Config-gated constructor used by the trainers (process 0 only).

    Reuses the anomaly detector's rate-limited auto-trace (cooldown +
    per-attempt budget) as the alarm's capture hook when one is running.
    """
    if not bool(cfg.select("telemetry.compile_sentry", True)):
        return None
    auto_trace = detector._maybe_auto_trace if detector is not None else None
    return CompileSentry(
        telemetry=telemetry, events=events, auto_trace=auto_trace
    )
