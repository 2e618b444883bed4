"""Gradient all-reduce microbenchmark: exact vs bf16 vs int8 wire formats.

Times ``compress.grad_allreduce`` under ``shard_map`` over the data axis at
the flagship gradient sizes (ResNet-18 and ResNet-50 contrastive pytrees,
counted via ``jax.eval_shape`` — no weights materialized) and reports, per
(model, mode), measured ms/step next to the analytic bytes-on-wire from
``compress.allreduce_wire_bytes``. ONE JSON payload line:

    {"metric": "allreduce_wire_reduction_int8_vs_exact", "value": 3.98,
     "unit": "x", "headline_model": "resnet18", "n_devices": ...,
     "models": {"resnet18": {"n_elements": ...,
                             "modes": {"exact": {"ms_per_step": ...,
                                                 "wire_mb_per_device": ...},
                                       ...}}}, ...}

The headline is the acceptance number: bytes-on-wire reduction of int8 vs
fp32 at the FIRST model's gradient size (>= 3x required). It is analytic —
a property of the wire format, not the host — so the payload is meaningful
even from a CPU run; ms/step carries the measured side and names its
backend.

With ``--overlap`` (or ``ALLREDUCE_BENCH_OVERLAP=1``) every mode entry also
carries an ``"overlap"`` table — ms/step and analytic ring wire bytes per
chunk count (``parallel.comm_overlap=chunked``), the on/off columns the
ROADMAP's pod-scaling item asks for:

    "modes": {"int8": {"ms_per_step": ..., "wire_mb_per_device": ...,
                       "overlap": {"4": {"ms_per_step": ...,
                                         "wire_mb_per_device": ...}, ...}}}

With ``--overlap-async`` (also spelled ``--overlap async``, or
``ALLREDUCE_BENCH_ASYNC=1``) every mode entry
additionally carries ``comm_overlap=async`` rows in a SEPARATE
``"overlap_async"`` table (so the chunked table's shape stays pinned),
each with a MEASURED exposed-comm column: median ms of a dummy-compute +
allreduce program minus the same compute alone — the wire time the
scheduler did NOT hide. The mode entry also gets the single-shot baseline
(``"exposed_comm_ms"``) next to it, plus ``"async_matches_off"`` (gradient
parity of async vs the single-shot path on the same inputs/key) and the
payload a ``"recompile_alarms"``
count (post-warmup jit cache growth across the async benches; 0 expected):

    "modes": {"int8": {..., "exposed_comm_ms": ...,
                       "async_matches_off": true,
                       "overlap_async": {"4": {"ms_per_step": ...,
                                               "wire_mb_per_device": ...,
                                               "exposed_comm_ms": ...}}}}

Robustness contract (same as bench.py / serve_bench.py): never exits
nonzero, never ends on a traceback, emits EXACTLY ONE payload line; a
wall-clock budget drops unfinished (model, mode) pairs LOUDLY under
``"skipped"``, and SIGTERM emits best-so-far.

Env knobs: ``ALLREDUCE_BENCH_SIZES`` (``name=n_elements,...`` — bypasses
model tracing; the fast tests use a tiny size), ``ALLREDUCE_BENCH_MODES``
(default ``exact,bf16,int8``), ``ALLREDUCE_BENCH_ITERS`` (default 10),
``ALLREDUCE_BENCH_BUDGET_S`` (default 600), ``ALLREDUCE_BENCH_OVERLAP``
(truthy = same as ``--overlap``), ``ALLREDUCE_BENCH_CHUNKS`` (chunk counts
for the overlap tables, default ``2,4,8``), ``ALLREDUCE_BENCH_ASYNC``
(truthy = same as ``--overlap-async``).
"""

from __future__ import annotations

import json
import os
import signal
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

DEFAULT_MODES = "exact,bf16,int8"
DEFAULT_OVERLAP_CHUNKS = "2,4,8"
DEFAULT_ITERS = 10
WARMUP_ITERS = 2
DEFAULT_BUDGET_S = 600.0
EMIT_RESERVE_S = 5.0

# dummy-compute stand-in for the backward the async schedule hides under:
# COMPUTE_MATMULS chained (COMPUTE_DIM, COMPUTE_DIM) matmuls — enough MXU
# time to overlap wire hops with, small enough to compile fast on CPU
COMPUTE_DIM = 256
COMPUTE_MATMULS = 8

# grad-parity tolerance of async vs the single-shot path, per wire format
# (matches tests/test_compress.py CHUNK_TOL: the schedules draw different
# rounding noise, so parity is statistical, not bitwise, vs "off")
PARITY_TOL = {"exact": 1e-4, "bf16": 2e-2, "int8": 5e-2}

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
        "metric": "allreduce_wire_reduction_int8_vs_exact",
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


def gradient_sizes() -> dict[str, int]:
    """{model: flat gradient element count}, traced — no params materialized.

    The gradient pytree the train step all-reduces is exactly the params
    pytree, so the element count is the param count of the contrastive
    model (encoder + projection head).
    """
    sizes_env = os.environ.get("ALLREDUCE_BENCH_SIZES")
    if sizes_env:
        out = {}
        for item in sizes_env.split(","):
            name, _, n = item.partition("=")
            out[name.strip()] = int(n)
        return out

    import jax
    import jax.numpy as jnp

    from simclr_tpu.models.contrastive import ContrastiveModel

    out = {}
    for base_cnn in ("resnet18", "resnet50"):
        model = ContrastiveModel(base_cnn=base_cnn, d=128)
        shapes = jax.eval_shape(
            lambda k, m=model: m.init(
                k, jnp.zeros((2, 32, 32, 3), jnp.float32), train=False
            ),
            jax.random.key(0),
        )
        out[base_cnn] = sum(
            int(l.size) for l in jax.tree.leaves(shapes["params"])
        )
    return out


def bench_mode(
    mesh, n_elements: int, mode: str, iters: int,
    overlap: str = "off", chunks: int = 1,
) -> float:
    """Median ms per grad_allreduce step on a flat vector of ``n_elements``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from simclr_tpu.parallel import compress
    from simclr_tpu.parallel.mesh import DATA_AXIS

    def body(x, step):
        i = jax.lax.axis_index(DATA_AXIS)
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(0), step), i)
        return compress.grad_allreduce(
            {"g": x}, DATA_AXIS, mode, key=key, overlap=overlap, chunks=chunks
        )["g"]

    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)
    )
    x = jnp.linspace(-1.0, 1.0, n_elements, dtype=jnp.float32)
    for step in range(WARMUP_ITERS):
        fn(x, jnp.int32(step)).block_until_ready()
    times = []
    for step in range(iters):
        t0 = time.perf_counter()
        fn(x, jnp.int32(WARMUP_ITERS + step)).block_until_ready()
        times.append((time.perf_counter() - t0) * 1000.0)
    times.sort()
    return times[len(times) // 2]


def _median_ms(fn, args_for_step, iters: int) -> float:
    import jax

    for step in range(WARMUP_ITERS):
        jax.block_until_ready(fn(*args_for_step(step)))
    times = []
    for step in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args_for_step(WARMUP_ITERS + step)))
        times.append((time.perf_counter() - t0) * 1000.0)
    times.sort()
    return times[len(times) // 2]


def bench_exposed(
    mesh, n_elements: int, mode: str, iters: int,
    overlap: str = "off", chunks: int = 1,
) -> tuple[float, int]:
    """Measured exposed-comm ms for one schedule, plus post-warmup recompiles.

    exposed = median ms of (dummy compute + allreduce, one program) minus
    median ms of the same compute alone, clamped at 0 — the wire time XLA's
    scheduler failed to hide under the compute. Recompiles are jit cache
    growth after warmup (the CompileSentry stand-in for a bare script).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from simclr_tpu.parallel import compress
    from simclr_tpu.parallel.mesh import DATA_AXIS

    def compute(w, h):
        for _ in range(COMPUTE_MATMULS):
            h = jnp.tanh(h @ w)
        return h

    def body_both(w, h, g, step):
        i = jax.lax.axis_index(DATA_AXIS)
        key = jax.random.fold_in(jax.random.fold_in(jax.random.key(1), step), i)
        out = compress.grad_allreduce(
            {"g": g}, DATA_AXIS, mode, key=key, overlap=overlap, chunks=chunks
        )["g"]
        return compute(w, h).sum() + out.sum()

    def body_compute(w, h, step):
        return compute(w, h).sum()

    fn_both = jax.jit(jax.shard_map(
        body_both, mesh=mesh, in_specs=(P(), P(), P(), P()), out_specs=P(),
        check_vma=False,
    ))
    fn_compute = jax.jit(jax.shard_map(
        body_compute, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
        check_vma=False,
    ))
    w = jnp.eye(COMPUTE_DIM, dtype=jnp.float32) * 0.5
    h = jnp.ones((COMPUTE_DIM, COMPUTE_DIM), jnp.float32)
    g = jnp.linspace(-1.0, 1.0, n_elements, dtype=jnp.float32)
    ms_both = _median_ms(fn_both, lambda s: (w, h, g, jnp.int32(s)), iters)
    cache_after_warmup = fn_both._cache_size()
    ms_compute = _median_ms(fn_compute, lambda s: (w, h, jnp.int32(s)), iters)
    recompiles = max(0, fn_both._cache_size() - cache_after_warmup)
    return max(0.0, ms_both - ms_compute), recompiles


def async_parity(mesh, n_elements: int, mode: str, chunks: int) -> float:
    """Max relative |async - off| on the same inputs/key."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from simclr_tpu.parallel import compress
    from simclr_tpu.parallel.mesh import DATA_AXIS

    def body(x):
        i = jax.lax.axis_index(DATA_AXIS)
        key = jax.random.fold_in(jax.random.key(7), i)
        off = compress.grad_allreduce({"g": x}, DATA_AXIS, mode, key=key)["g"]
        asy = compress.grad_allreduce(
            {"g": x}, DATA_AXIS, mode, key=key, overlap="async", chunks=chunks
        )["g"]
        return jnp.max(jnp.abs(asy - off)), jnp.max(jnp.abs(off))

    fn = jax.jit(
        jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=(P(), P()), check_vma=False)
    )
    x = jnp.linspace(-1.0, 1.0, n_elements, dtype=jnp.float32)
    diff, ref = fn(x)
    return float(diff) / max(float(ref), 1e-12)


def assemble_payload(models: dict, extra: dict) -> dict:
    """Headline: analytic wire reduction int8 vs exact at the first model."""
    from simclr_tpu.parallel.compress import allreduce_wire_bytes

    headline_model = next(iter(models), None)
    value = 0.0
    if headline_model is not None:
        n = models[headline_model]["n_elements"]
        n_dev = extra["n_devices"]
        value = allreduce_wire_bytes(n, n_dev, "exact") / allreduce_wire_bytes(
            n, n_dev, "int8"
        )
    payload = {
        "metric": "allreduce_wire_reduction_int8_vs_exact",
        "value": round(value, 3),
        "unit": "x",
        "headline_model": headline_model,
        "models": models,
    }
    payload.update(extra)
    return payload


def main() -> None:
    global _BEST_SO_FAR
    deadline = time.monotonic() + float(
        os.environ.get("ALLREDUCE_BENCH_BUDGET_S", DEFAULT_BUDGET_S)
    )
    try:
        signal.signal(signal.SIGTERM, _sigterm_backstop)
    except ValueError:  # non-main thread (embedded runs)
        pass

    import jax

    from simclr_tpu.parallel.compress import (
        DEFAULT_BUCKET_SIZE,
        allreduce_wire_bytes,
        validate_mode,
    )
    from simclr_tpu.parallel.mesh import MeshSpec, create_mesh

    modes = [
        validate_mode(m.strip())
        for m in os.environ.get("ALLREDUCE_BENCH_MODES", DEFAULT_MODES).split(",")
        if m.strip()
    ]
    iters = int(os.environ.get("ALLREDUCE_BENCH_ITERS", DEFAULT_ITERS))
    overlap_on = "--overlap" in sys.argv[1:] or bool(
        os.environ.get("ALLREDUCE_BENCH_OVERLAP")
    )
    # both spellings reach the async rows: the dedicated --overlap-async
    # flag, and `--overlap async` (value form) next to the bare chunked
    # `--overlap`
    async_on = (
        "--overlap-async" in sys.argv[1:]
        or "async" in sys.argv[1:]
        or bool(os.environ.get("ALLREDUCE_BENCH_ASYNC"))
    )
    chunk_counts = [
        int(c)
        for c in os.environ.get(
            "ALLREDUCE_BENCH_CHUNKS", DEFAULT_OVERLAP_CHUNKS
        ).split(",")
        if c.strip()
    ] if (overlap_on or async_on) else []
    mesh = create_mesh(MeshSpec(data=-1, model=1))
    n_dev = len(jax.devices())
    extra = {
        "backend": jax.default_backend(),
        "n_devices": n_dev,
        "bucket_size": DEFAULT_BUCKET_SIZE,
        "iters": iters,
    }
    if overlap_on or async_on:
        extra["overlap_chunks"] = chunk_counts
    if async_on:
        extra["recompile_alarms"] = 0

    sizes = gradient_sizes()
    models: dict[str, dict] = {}
    skipped: list[str] = []
    for name, n_elements in sizes.items():
        entry = {"n_elements": n_elements, "modes": {}}
        for mode in modes:
            # budget discipline: drop unfinished pairs loudly, not silently
            if time.monotonic() > deadline - EMIT_RESERVE_S:
                skipped.append(f"{name}/{mode}")
                continue
            ms = bench_mode(mesh, n_elements, mode, iters)
            entry["modes"][mode] = {
                "ms_per_step": round(ms, 3),
                "wire_mb_per_device": round(
                    allreduce_wire_bytes(n_elements, n_dev, mode) / 2**20, 3
                ),
            }
            print(f"# {name}/{mode}: {ms:.3f} ms/step", file=sys.stderr)
            # overlap on/off columns: the chunked ring at each chunk count,
            # next to the single-shot number above (off). Same budget
            # discipline per (model, mode, chunks) triple.
            if overlap_on:
                for c in chunk_counts:
                    if time.monotonic() > deadline - EMIT_RESERVE_S:
                        skipped.append(f"{name}/{mode}/chunks={c}")
                        continue
                    ms_c = bench_mode(
                        mesh, n_elements, mode, iters, overlap="chunked", chunks=c
                    )
                    entry["modes"][mode].setdefault("overlap", {})[str(c)] = {
                        "ms_per_step": round(ms_c, 3),
                        "wire_mb_per_device": round(
                            allreduce_wire_bytes(
                                n_elements, n_dev, mode,
                                overlap="chunked", chunks=c,
                            ) / 2**20, 3
                        ),
                    }
                    print(
                        f"# {name}/{mode}/chunks={c}: {ms_c:.3f} ms/step",
                        file=sys.stderr,
                    )
            # async rows (separate table so the chunked one's shape stays
            # pinned): ms/step + the ring's analytic wire MB + the MEASURED
            # exposed-comm column, next to the single-shot baseline
            if async_on:
                for c in chunk_counts:
                    if time.monotonic() > deadline - EMIT_RESERVE_S:
                        skipped.append(f"{name}/{mode}/async={c}")
                        continue
                    if "exposed_comm_ms" not in entry["modes"][mode]:
                        exp_off, rc = bench_exposed(
                            mesh, n_elements, mode, iters
                        )
                        entry["modes"][mode]["exposed_comm_ms"] = round(exp_off, 3)
                        extra["recompile_alarms"] += rc
                    ms_a = bench_mode(
                        mesh, n_elements, mode, iters, overlap="async", chunks=c
                    )
                    exp_a, rc = bench_exposed(
                        mesh, n_elements, mode, iters, overlap="async", chunks=c
                    )
                    extra["recompile_alarms"] += rc
                    entry["modes"][mode].setdefault("overlap_async", {})[str(c)] = {
                        "ms_per_step": round(ms_a, 3),
                        "wire_mb_per_device": round(
                            allreduce_wire_bytes(
                                n_elements, n_dev, mode,
                                overlap="async", chunks=c,
                            ) / 2**20, 3
                        ),
                        "exposed_comm_ms": round(exp_a, 3),
                    }
                    print(
                        f"# {name}/{mode}/async={c}: {ms_a:.3f} ms/step, "
                        f"{exp_a:.3f} ms exposed",
                        file=sys.stderr,
                    )
                if "overlap_async" in entry["modes"][mode]:
                    rel = async_parity(mesh, n_elements, mode, chunk_counts[0])
                    entry["modes"][mode]["async_vs_off_max_rel_diff"] = round(
                        rel, 6
                    )
                    entry["modes"][mode]["async_matches_off"] = bool(
                        rel <= PARITY_TOL[mode]
                    )
        if entry["modes"]:
            models[name] = entry
        else:
            skipped.append(name)
        _BEST_SO_FAR = assemble_payload(models, extra)

    payload = assemble_payload(models, extra)
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
