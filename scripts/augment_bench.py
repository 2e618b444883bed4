"""Two-view augmentation microbenchmark: xla chain vs fused Pallas kernel.

Times both implementations of the SimCLR two-view augmentation — the
vmapped XLA chain (``data/augment.simclr_two_views``) and the one-VMEM-pass
Pallas kernel (``ops/augment_pallas.fused_two_views``) — on resident uint8
batches at the flagship sizes, and reports, per (batch, impl), measured
ms/batch next to the analytic HBM bytes from
``roofline_model.augment_bytes``. ONE JSON payload line:

    {"metric": "augment_hbm_reduction_fused_vs_xla", "value": 2.9,
     "unit": "x", "headline_batch": 256, "backend": ..., "iters": ...,
     "recompile_alarms": 0,
     "batches": {"256": {"impls": {"xla":   {"ms_per_batch": ...,
                                             "hbm_mb": ...},
                                   "fused": {"ms_per_batch": ...,
                                             "hbm_mb": ...}}}, ...}}

The headline is the acceptance number: analytic HBM-traffic reduction of
fused vs xla at the FIRST batch size. It is analytic — a property of the
memory-access pattern, not the host — so the payload is meaningful even
from a CPU run (where the Pallas kernel executes in interpret mode);
ms/batch carries the measured side and names its backend.

``recompile_alarms`` counts post-warmup recompilations of either timed
callable (jit cache growth after the warmup iterations) — the same silent
perf killer CompileSentry watches in training; 0 expected.

Robustness contract (same as allreduce_bench.py): never exits
nonzero, never ends on a traceback, emits EXACTLY ONE payload line; a
wall-clock budget drops unfinished (batch, impl) pairs LOUDLY under
``"skipped"``, and SIGTERM emits best-so-far.

Env knobs: ``AUGMENT_BENCH_BATCHES`` (default ``256,512,1024,2048``),
``AUGMENT_BENCH_IMPLS`` (default ``xla,fused``), ``AUGMENT_BENCH_ITERS``
(default 10), ``AUGMENT_BENCH_BUDGET_S`` (default 600).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# scripts/ is not a package; augment_bytes lives next door
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

DEFAULT_BATCHES = "256,512,1024,2048"
DEFAULT_IMPLS = "xla,fused"
DEFAULT_ITERS = 10
WARMUP_ITERS = 2
DEFAULT_BUDGET_S = 600.0
EMIT_RESERVE_S = 5.0

_PAYLOAD_EMITTED = False
_BEST_SO_FAR: dict | None = None


def _emit_payload(payload: dict) -> None:
    """Print the run's single payload line, exactly once (bench.py contract)."""
    global _PAYLOAD_EMITTED
    if _PAYLOAD_EMITTED:
        return
    _PAYLOAD_EMITTED = True
    print(json.dumps(payload), flush=True)


def last_ditch_payload(exc: BaseException) -> dict:
    return {
        "metric": "augment_hbm_reduction_fused_vs_xla",
        "value": 0.0,
        "unit": "x",
        "error": repr(exc),
    }


def _sigterm_backstop(signum, frame) -> None:
    if not _PAYLOAD_EMITTED:
        _emit_payload(
            _BEST_SO_FAR
            if _BEST_SO_FAR is not None
            else last_ditch_payload(
                RuntimeError(f"terminated by signal {signum} before finishing")
            )
        )
    os._exit(0)


def bench_impl(batch: int, impl: str, iters: int) -> tuple[float, int]:
    """(median ms per two-view batch, post-warmup recompiles) for one impl.

    The rng is folded per iteration from a traced step counter, so every
    timed call sees fresh randomness at a single compiled signature — cache
    growth after warmup is a genuine recompile, counted and reported.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from simclr_tpu.data.augment import simclr_two_views
    from simclr_tpu.ops.augment_pallas import fused_two_views, validate_impl

    validate_impl(impl)
    two_views = fused_two_views if impl == "fused" else simclr_two_views

    @jax.jit
    def fn(step, images):
        rng = jax.random.fold_in(jax.random.key(0), step)
        return two_views(rng, images, 0.5, 32)

    images = jnp.asarray(
        np.random.default_rng(0).integers(
            0, 256, size=(batch, 32, 32, 3), dtype=np.uint8
        )
    )
    for step in range(WARMUP_ITERS):
        jax.block_until_ready(fn(jnp.int32(step), images))
    baseline = fn._cache_size()
    times = []
    for step in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(jnp.int32(WARMUP_ITERS + step), images))
        times.append((time.perf_counter() - t0) * 1000.0)
    times.sort()
    return times[len(times) // 2], max(fn._cache_size() - baseline, 0)


def assemble_payload(batches: dict, extra: dict) -> dict:
    """Headline: analytic HBM reduction fused vs xla at the first batch."""
    from roofline_model import augment_bytes

    headline_batch = next(iter(batches), None)
    value = 0.0
    if headline_batch is not None:
        b = int(headline_batch)
        value = augment_bytes(b, "xla") / augment_bytes(b, "fused")
    payload = {
        "metric": "augment_hbm_reduction_fused_vs_xla",
        "value": round(value, 3),
        "unit": "x",
        "headline_batch": headline_batch,
        "batches": batches,
    }
    payload.update(extra)
    return payload


def main() -> None:
    global _BEST_SO_FAR
    deadline = time.monotonic() + float(
        os.environ.get("AUGMENT_BENCH_BUDGET_S", DEFAULT_BUDGET_S)
    )
    try:
        signal.signal(signal.SIGTERM, _sigterm_backstop)
    except ValueError:  # non-main thread (embedded runs)
        pass

    import jax

    from roofline_model import augment_bytes
    from simclr_tpu.ops.augment_pallas import validate_impl

    impls = [
        validate_impl(i.strip())
        for i in os.environ.get("AUGMENT_BENCH_IMPLS", DEFAULT_IMPLS).split(",")
        if i.strip()
    ]
    batch_sizes = [
        int(b)
        for b in os.environ.get("AUGMENT_BENCH_BATCHES", DEFAULT_BATCHES).split(",")
        if b.strip()
    ]
    iters = int(os.environ.get("AUGMENT_BENCH_ITERS", DEFAULT_ITERS))
    extra = {
        "backend": jax.default_backend(),
        "iters": iters,
        "recompile_alarms": 0,
    }

    batches: dict[str, dict] = {}
    skipped: list[str] = []
    alarms = 0
    for batch in batch_sizes:
        entry = {"impls": {}}
        for impl in impls:
            # budget discipline: drop unfinished pairs loudly, not silently
            if time.monotonic() > deadline - EMIT_RESERVE_S:
                skipped.append(f"{batch}/{impl}")
                continue
            ms, recompiles = bench_impl(batch, impl, iters)
            alarms += recompiles
            entry["impls"][impl] = {
                "ms_per_batch": round(ms, 3),
                "hbm_mb": round(augment_bytes(batch, impl) / 2**20, 3),
            }
            print(f"# batch {batch}/{impl}: {ms:.3f} ms/batch", file=sys.stderr)
        if entry["impls"]:
            batches[str(batch)] = entry
        else:
            skipped.append(str(batch))
        extra["recompile_alarms"] = alarms
        _BEST_SO_FAR = assemble_payload(batches, extra)

    payload = assemble_payload(batches, extra)
    if skipped:
        payload["skipped"] = skipped
        print(f"# budget exhausted; skipped {skipped}", file=sys.stderr)
    _emit_payload(payload)


if __name__ == "__main__":
    try:
        main()
    except Exception as exc:  # last-ditch contract keeper: one line, rc 0
        print(f"# unexpected error: {exc!r}", file=sys.stderr)
        _emit_payload(last_ditch_payload(exc))
    sys.exit(0)
