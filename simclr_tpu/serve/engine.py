"""Embedding engine: a trained checkpoint behind a bucketed jitted forward.

The serving counterpart of ``eval.extract_features``: restore the encoder
from an orbax checkpoint (integrity-verified, ``utils/checkpoint.py``),
build the no-augmentation frozen forward out of
``models/contrastive.ContrastiveModel`` (float32, ``to_float`` uint8
normalization — numerically the same forward eval and save_features use),
and serve arbitrary-size request batches through a small set of static
shapes:

  * request batches are padded up to the nearest **power-of-two bucket**
    (1, 2, 4, … ``max_batch``) and sliced back after the forward, so XLA
    compiles one program per bucket instead of one per request size;
  * every bucket is **warmup-compiled at startup** (fenced with
    ``utils.profiling.synchronize``), so no live request ever pays a
    compile;
  * each engine is single-device BY PLACEMENT: request batches are
    latency-bound and small, so sharding one forward buys nothing — the
    ``device`` argument pins the committed weight copy (and therefore every
    bucket program) to one chip, and scale-out is one engine per local
    device behind the shared front-end queue (``serve/replica.py``'s
    :class:`ReplicaPool`; capacity math in ``docs/SERVING.md``);
  * ``weights`` selects the resident storage format
    (:data:`~simclr_tpu.parallel.compress.WEIGHT_QUANT_MODES`): ``exact``
    keeps fp32; ``bf16`` halves it; ``int8`` stores the bucketed
    deterministic quantization from ``parallel/compress.py`` and
    dequantizes INSIDE the jitted forward, so per-replica HBM holds int8
    buckets + one fp32 scale per 1024 weights (~3.98x under fp32).

Thread model: ``embed`` is called only from one batcher worker thread (its
replica's worker under a pool); construction and warmup happen before the
worker starts.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from simclr_tpu.data.augment import to_float
from simclr_tpu.models.arch import refuse_sequence_encoder
from simclr_tpu.obs.compile import CompileSentry
from simclr_tpu.parallel.compress import (
    dequantize_weight_buckets,
    quantize_weight_buckets,
    validate_weight_mode,
    weight_storage_bytes,
)
from simclr_tpu.utils.fetch import fetch
from simclr_tpu.utils.profiling import synchronize


class RequestTooLargeError(ValueError):
    """A single request carries more rows than the largest bucket."""


class WeightsIncompatibleError(ValueError):
    """Staged weights do not match the resident storage layout.

    Raised by :meth:`EmbedEngine.stage_weights` when the new checkpoint's
    packed param tree differs from the committed one in structure, shape,
    or dtype — swapping it in would force a fresh XLA compile per bucket
    (or worse, run a wrong program), so the swap is refused instead.
    """


class StagedWeights:
    """A packed-and-device-resident weight set awaiting :meth:`commit`.

    Produced by :meth:`EmbedEngine.stage_weights`; carries the same pytree
    structure/shapes/dtypes as the committed storage, so the engine's
    existing bucket programs run on it without recompiling. Holding one of
    these costs a second resident weight copy on the device until it is
    committed (then the old copy is dropped) or discarded.
    """

    __slots__ = ("params", "batch_stats", "checkpoint_path")

    def __init__(self, params, batch_stats, checkpoint_path=None):
        self.params = params
        self.batch_stats = batch_stats
        self.checkpoint_path = checkpoint_path


def make_buckets(max_batch: int) -> tuple[int, ...]:
    """Power-of-two batch buckets up to ``max_batch`` (inclusive).

    A non-power-of-two ``max_batch`` contributes itself as the final bucket
    (``max_batch=24`` -> ``(1, 2, 4, 8, 16, 24)``), so the configured
    ceiling is always exactly servable.
    """
    if max_batch < 1:
        raise ValueError(f"max_batch must be >= 1, got {max_batch}")
    buckets = []
    b = 1
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch)
    return tuple(buckets)


class EmbedEngine:
    """Checkpoint -> warm compiled forwards -> ``embed(images)``.

    ``model`` is any flax module with the :class:`ContrastiveModel` API
    (``encode``/``__call__``, params + batch_stats); ``variables`` a host
    pytree with ``params`` and ``batch_stats``. ``use_full_encoder=False``
    serves encoder features h (the representation probes consume); True
    serves projection-head output z.
    """

    def __init__(
        self,
        model,
        variables: dict,
        *,
        max_batch: int = 256,
        use_full_encoder: bool = False,
        input_shape: tuple[int, ...] = (32, 32, 3),
        metrics=None,
        warmup: bool = True,
        sentry=None,
        device=None,
        replica_id: int | None = None,
        weights: str = "exact",
    ):
        # requests are pixel arrays of `input_shape`
        refuse_sequence_encoder(str(getattr(model, "base_cnn", "")), "the embed engine")
        refuse_sequence_encoder(variables.get("params", {}), "the embed engine")
        self.model = model
        self.max_batch = int(max_batch)
        self.use_full_encoder = bool(use_full_encoder)
        self.input_shape = tuple(input_shape)
        self.buckets = make_buckets(self.max_batch)
        self.metrics = metrics
        self.device = device
        self.replica_id = replica_id
        self.weights_mode = validate_weight_mode(weights)
        # compile sentry (obs/compile.py): every bucket compilation is
        # recorded; a bucket compiled after warmup completes is the serve
        # tier's recompile alarm. A bare sentry (records only) is kept when
        # the caller has no events/telemetry to wire in. Warmup gating is
        # PER ENGINE (_warmup_done below), so under a ReplicaPool each
        # replica's own warmup never alarms even when the pool shares one
        # sentry — only a post-warmup cold bucket on that replica does.
        self.sentry = sentry if sentry is not None else CompileSentry()
        self._warmup_done = False
        self._warm: set[int] = set()
        # (name, start, end) perf_counter spans of the LAST embed() call
        # (pad + device_compute), read by the batcher's span_source. embed()
        # runs only on this engine's one batcher worker thread (see
        # embed()), so a plain attribute swap is safe.
        self.last_spans: tuple = ()
        # one committed device copy of the variables, shared by every bucket
        # program — per-request device_put of the params would dominate the
        # forward at small batches. Committing to an explicit `device` pins
        # every bucket program there (jit follows committed arguments), so
        # N engines over N devices run concurrently. The (params,
        # batch_stats) pair lives in ONE tuple attribute so hot-reload can
        # swap both atomically under concurrent embeds — a reader never
        # sees generation N params with generation N-1 batch stats.
        packed, dequant, self._n_weight_elements = self._pack_params(
            variables["params"]
        )
        self._resident = (packed, self._put(variables.get("batch_stats", {})))
        # weight-generation bookkeeping for zero-downtime hot-reload
        # (coscheduler/reload.py): 0 = construction-time variables, each
        # commit() increments. checkpoint_path names the committed source.
        self.generation = 0
        self.checkpoint_path = None

        def forward(params, batch_stats, images):
            x = to_float(images)
            vs = {"params": dequant(params), "batch_stats": batch_stats}
            if self.use_full_encoder:
                return model.apply(vs, x, train=False).astype(jnp.float32)
            return model.apply(
                vs, x, train=False, method=model.encode
            ).astype(jnp.float32)

        # jit's shape-keyed executable cache IS the bucket compile cache:
        # padding constrains every call to one of `buckets` shapes, warmup
        # populates each entry, and self._warm tracks which buckets have a
        # compiled program (the hit/miss metric).
        self._fwd = jax.jit(forward)
        if warmup:
            self.warmup()

    # -- weight storage ----------------------------------------------------
    @property
    def _params(self):
        return self._resident[0]

    @property
    def _batch_stats(self):
        return self._resident[1]

    def _put(self, tree):
        if self.device is None:
            return jax.device_put(tree)
        return jax.device_put(tree, self.device)

    def _pack_params(self, host_params):
        """Device-resident param storage per ``weights`` mode.

        Returns ``(packed, dequant, n_float_elements)`` where ``dequant``
        maps the packed storage back to the forward's fp-typed param tree
        inside the jitted program. ``int8`` quantizes the FLOAT leaves as
        one flat vector (deterministic, ``parallel/compress.py`` bucket
        format — same input, same bytes, every load and every replica) and
        carries any non-float leaf exact.
        """
        leaves, treedef = jax.tree.flatten(host_params)
        host = [np.asarray(l) for l in leaves]
        is_float = [np.issubdtype(h.dtype, np.floating) for h in host]
        n_float = int(sum(h.size for h, f in zip(host, is_float) if f))
        # exact-carried bytes (non-float param leaves) for the analytic gauge
        self._nonfloat_param_bytes = int(
            sum(h.nbytes for h, f in zip(host, is_float) if not f)
        )
        if self.weights_mode == "exact":
            return self._put(host_params), (lambda p: p), n_float
        if self.weights_mode == "bf16":
            packed = self._put(
                jax.tree.unflatten(
                    treedef,
                    [
                        h.astype(jnp.bfloat16) if f else h
                        for h, f in zip(host, is_float)
                    ],
                )
            )

            def dequant_bf16(p):
                return jax.tree.map(
                    lambda x: x.astype(jnp.float32)
                    if jnp.issubdtype(x.dtype, jnp.floating)
                    else x,
                    p,
                )

            return packed, dequant_bf16, n_float
        flat = (
            np.concatenate(
                [h.reshape(-1).astype(np.float32) for h, f in zip(host, is_float) if f]
            )
            if n_float
            else np.zeros((0,), np.float32)
        )
        q, scales = quantize_weight_buckets(flat)
        packed = self._put(
            {
                "q": q,
                "scales": scales,
                "exact": [h for h, f in zip(host, is_float) if not f],
            }
        )
        meta = [(h.shape, h.size, h.dtype) for h in host]

        def dequant_int8(p):
            vec = dequantize_weight_buckets(p["q"], p["scales"], n_float)
            out, off, exact = [], 0, iter(p["exact"])
            for (shape, size, dtype), f in zip(meta, is_float):
                if f:
                    out.append(vec[off : off + size].reshape(shape).astype(dtype))
                    off += size
                else:
                    out.append(next(exact))
            return jax.tree.unflatten(treedef, out)

        return packed, dequant_int8, n_float

    def weight_hbm_bytes(self) -> int:
        """Measured resident weight bytes on this replica's device (params
        storage + batch stats), summed from the committed arrays."""
        return int(
            sum(
                l.nbytes
                for l in jax.tree.leaves((self._params, self._batch_stats))
            )
        )

    def weight_hbm_analytic_bytes(self) -> int:
        """Analytic resident weight bytes under the storage mode:
        :func:`~simclr_tpu.parallel.compress.weight_storage_bytes` over the
        float param elements, plus the exact-carried non-float leaves and
        batch stats. Rendered next to the measured gauge so preflight and
        reality can be reconciled per replica."""
        stats_bytes = int(
            sum(l.nbytes for l in jax.tree.leaves(self._batch_stats))
        )
        return (
            weight_storage_bytes(self._n_weight_elements, self.weights_mode)
            + self._nonfloat_param_bytes
            + stats_bytes
        )

    # -- hot-reload (zero-downtime generation swap) ------------------------
    @staticmethod
    def _storage_signature(tree):
        """(treedef, [(shape, dtype)...]) of a packed tree — the identity a
        staged weight set must share with the committed one for jit's
        shape-keyed executable cache to serve it without recompiling."""
        leaves, treedef = jax.tree.flatten(tree)
        return treedef, [(tuple(l.shape), str(l.dtype)) for l in leaves]

    def stage_weights(self, variables: dict, checkpoint_path=None) -> StagedWeights:
        """Pack new checkpoint variables into a device-resident staged copy.

        Runs the SAME packing path the constructor used (so int8 staging
        yields the identical ``{"q","scales","exact"}`` layout the compiled
        forward's dequant closure expects) and verifies the packed tree is
        structure/shape/dtype-identical to the committed storage — the
        precondition for every existing bucket program to run on it with
        zero recompiles. A mismatched checkpoint (different architecture,
        head dim, weights mode artifacts) raises
        :class:`WeightsIncompatibleError` and leaves the engine untouched.

        Thread-safe against concurrent ``embed`` calls: nothing the request
        path reads is mutated until :meth:`commit`.
        """
        packed, _dequant, _n = self._pack_params(variables["params"])
        batch_stats = self._put(variables.get("batch_stats", {}))
        cur_params, cur_stats = self._resident
        if self._storage_signature(packed) != self._storage_signature(cur_params):
            raise WeightsIncompatibleError(
                "staged params storage differs from the committed layout "
                "(architecture/d/weights-mode mismatch); refusing a swap "
                "that would recompile every bucket"
            )
        if self._storage_signature(batch_stats) != self._storage_signature(
            cur_stats
        ):
            raise WeightsIncompatibleError(
                "staged batch_stats differ from the committed layout; "
                "refusing the swap"
            )
        return StagedWeights(packed, batch_stats, checkpoint_path)

    def embed_with(self, staged: StagedWeights, images: np.ndarray) -> np.ndarray:
        """Forward ``images`` through STAGED (uncommitted) weights.

        Used by the co-scheduler to re-embed the retrieval corpus with the
        incoming generation BEFORE it starts serving — the corpus swap and
        the weight swap then land back-to-back, so ``/v1/neighbors`` never
        mixes generations with ``/v1/embed``. Runs the same compiled bucket
        programs (staged storage is shape-identical by construction), and
        deliberately touches no serving metrics or spans: traffic
        accounting belongs to the committed generation.
        """
        images = np.asarray(images)
        n = images.shape[0]
        bucket = self.bucket_for(n)
        if n < bucket:
            images = np.concatenate(
                [images, np.zeros((bucket - n, *self.input_shape), np.uint8)]
            )
        out = fetch(self._fwd(staged.params, staged.batch_stats, images))
        return out[:n]

    def commit(self, staged: StagedWeights, *, generation: int | None = None):
        """Atomically swap the staged weights in as the serving generation.

        One tuple-attribute assignment: every in-flight ``embed`` finishes
        on the copy it already read, every subsequent one reads the new
        pair — zero downtime, no torn (params, batch_stats) mix. The old
        copy's device memory is released once its last reader returns.
        """
        self._resident = (staged.params, staged.batch_stats)
        self.generation = (
            self.generation + 1 if generation is None else int(generation)
        )
        if staged.checkpoint_path is not None:
            self.checkpoint_path = staged.checkpoint_path
        return self.generation

    # -- lifecycle ---------------------------------------------------------
    def warmup(self) -> dict[int, float]:
        """Compile every bucket before traffic; returns per-bucket seconds.

        Fenced with :func:`utils.profiling.synchronize` so the timing (and
        the readiness it implies) reflects finished device work, not queued
        dispatches.
        """
        times: dict[int, float] = {}
        for b in self.buckets:
            if b in self._warm:
                continue
            t0 = time.perf_counter()
            out = self._fwd(
                self._params,
                self._batch_stats,
                np.zeros((b, *self.input_shape), np.uint8),
            )
            synchronize(out)
            times[b] = time.perf_counter() - t0
            self._warm.add(b)
            self.sentry.record_compile(
                self._compile_name(b), seconds=times[b], warm=self._warmup_done
            )
        self._warmup_done = True
        return times

    def _compile_name(self, bucket: int) -> str:
        """Sentry name for a bucket compile; replica-tagged under a pool so
        fan-out keeps per-replica compile attribution distinct."""
        if self.replica_id is None:
            return f"serve_bucket_{bucket}"
        return f"serve_r{self.replica_id}_bucket_{bucket}"

    def warm_state(self) -> list[int]:
        """Buckets with a compiled program (sorted) — /healthz evidence."""
        return sorted(self._warm)

    # -- request path ------------------------------------------------------
    def bucket_for(self, n_rows: int) -> int:
        """Smallest bucket holding ``n_rows``; raises past ``max_batch``."""
        if n_rows < 1:
            raise ValueError(f"need at least one row, got {n_rows}")
        if n_rows > self.max_batch:
            raise RequestTooLargeError(
                f"{n_rows} rows exceeds serve.max_batch={self.max_batch}; "
                f"split the request"
            )
        for b in self.buckets:
            if b >= n_rows:
                return b
        raise AssertionError("unreachable: buckets end at max_batch")

    def embed(self, images: np.ndarray) -> np.ndarray:
        """Embed ``(n, *input_shape)`` uint8 rows; returns ``(n, d)`` float32.

        Pads up to the bucket, runs the warm program, slices the padding
        back off. Zero-padding is sound because the frozen forward is
        row-independent (eval-mode BN uses running statistics), so the
        padded rows cannot perturb the real ones.
        """
        images = np.asarray(images)
        if images.dtype != np.uint8:
            raise ValueError(f"images must be uint8 pixels, got {images.dtype}")
        if images.shape[1:] != self.input_shape:
            raise ValueError(
                f"images must be (n, {', '.join(map(str, self.input_shape))}), "
                f"got {images.shape}"
            )
        n = images.shape[0]
        bucket = self.bucket_for(n)
        cold = bucket not in self._warm
        if self.metrics is not None:
            if cold:
                self.metrics.compile_cache_misses_total.inc()
                if self._warmup_done:
                    self.metrics.recompile_alarms_total.inc()
            else:
                self.metrics.compile_cache_hits_total.inc()
        if cold:
            self._warm.add(bucket)
        t_pad = time.perf_counter()
        if n < bucket:
            images = np.concatenate(
                [images, np.zeros((bucket - n, *self.input_shape), np.uint8)]
            )
        t0 = time.perf_counter()
        # ONE read of the resident tuple: params and batch_stats are always
        # the same generation even if commit() swaps mid-call
        params, batch_stats = self._resident
        out = fetch(self._fwd(params, batch_stats, images))
        done = time.perf_counter()
        if cold:
            # the compiling dispatch: its duration upper-bounds the compile.
            # warm=True (post-warmup cold bucket) raises the recompile alarm.
            self.sentry.record_compile(
                self._compile_name(bucket),
                seconds=done - t0,
                warm=self._warmup_done,
            )
        # kept even for exact-bucket batches (a ~0 pad span) so every
        # request trace carries the same span shape
        self.last_spans = (("pad", t_pad, t0), ("device_compute", t0, done))
        if self.metrics is not None:
            self.metrics.batches_total.inc()
            self.metrics.batch_rows_total.inc(n)
            self.metrics.batch_capacity_total.inc(bucket)
            self.metrics.batch_latency_ms.observe((done - t0) * 1000.0)
        return out[:n]

    @property
    def feature_dim(self) -> int:
        """Output feature dimension (probed with a one-row forward)."""
        return int(
            jax.eval_shape(
                self._fwd,
                self._params,
                self._batch_stats,
                jax.ShapeDtypeStruct((1, *self.input_shape), jnp.uint8),
            ).shape[-1]
        )

    # -- construction from a run directory ---------------------------------
    @classmethod
    def from_checkpoint(
        cls,
        cfg,
        *,
        metrics=None,
        warmup: bool = True,
        sentry=None,
        device=None,
        replica_id: int | None = None,
    ):
        """Restore the newest (or explicitly chosen) checkpoint of a run.

        Uses eval's blessed constructor/loader so served embeddings are the
        same features eval and save_features compute for that checkpoint.
        Restore goes through the sha256-verified path: a truncated
        checkpoint raises before the server ever binds its port.
        """
        from simclr_tpu.eval import build_eval_model, load_model_variables
        from simclr_tpu.utils.checkpoint import latest_checkpoint

        ckpt = cfg.select("serve.checkpoint")
        if not ckpt:
            target_dir = str(cfg.experiment.target_dir)
            ckpt = latest_checkpoint(target_dir)
            if ckpt is None:
                raise FileNotFoundError(
                    f"no checkpoints found under {target_dir!r}; set "
                    f"experiment.target_dir or serve.checkpoint"
                )
        model = build_eval_model(cfg)
        variables = load_model_variables(str(ckpt))
        engine = cls(
            model,
            variables,
            max_batch=int(cfg.serve.max_batch),
            use_full_encoder=bool(cfg.parameter.use_full_encoder),
            metrics=metrics,
            warmup=warmup,
            sentry=sentry,
            device=device,
            replica_id=replica_id,
            weights=str(cfg.select("serve.weights", "exact")),
        )
        engine.checkpoint_path = str(ckpt)
        return engine
