"""On-device top-k retrieval over a row-sharded embedding corpus.

The similarity-search half of the serve tier (``POST /v1/neighbors``). The
corpus — an ``(n, d)`` float32 embedding matrix, typically produced by
``eval.save_features`` — is sharded ONCE onto a data-axis-only mesh over
every local device, so per-chip HBM holds ``~n/S`` rows and the corpus can
grow with the slice. Queries are answered entirely on device. The DEFAULT
path is exact brute-force and unchanged:

  * each shard computes its local score block ``q @ shard.T`` (B x R) and
    keeps only its local ``top_k`` — the full B x n similarity matrix is
    never materialized anywhere, host or device;
  * the ``min(k, R)`` local winners per shard (scores + GLOBAL row ids,
    padding rows masked to -inf) are ``all_gather``ed and merged with one
    final ``top_k`` over the ``S * min(k, R)`` candidates. ``min(k, R)``
    per shard is sufficient for exactness: no shard can place more than
    ``R`` rows in the global top-k.
  * the merge is **oracle-exact including ties**: XLA's TopK is stable
    (equal scores -> lowest index first), and candidates are laid out
    shard-major, so the global tie-break is lowest global row id — exactly
    ``np.argsort(-scores, kind="stable")`` (pinned by test).

Two orthogonal scaling knobs change what each shard SCORES, not how the
winners merge (all four mode combinations share the gather/merge tail):

  * ``serve.corpus_dtype=int8`` stores each shard's ``(R*d,)`` row block in
    ``compress.py``'s deterministic bucketed int8 format (one fp32 scale
    per 1024 elements, round-to-nearest) and dequantizes INSIDE the jitted
    kernel — ~3.98x more rows per device at the same HBM, still scoring
    every row (only the stored corpus is quantized; scores are fp32).
  * ``serve.ann_cells > 0`` turns on a two-stage IVF scan: at load each
    shard k-means-clusters its own row block (``eval.kmeans`` — the
    centroid-probe machinery reused as a coarse quantizer) into ``C`` cells
    stored as padded ``(C, L, d)`` tiles; at query time each query routes to
    its ``ann_probe`` nearest cells (``argmax(q·c - ||c||²/2)``) and scores
    only those tiles — ``probe/cells`` of the exact FLOPs and bytes. Because
    every row lives in exactly one cell, ``ann_probe == ann_cells`` scores
    the full shard and the candidate set equals the exact path's (recall
    1.0, pinned by test); recall is monotone in ``ann_probe`` since the
    candidate sets nest.

Query batches are padded to the same power-of-two buckets the embed path
uses (one compiled program per (k, bucket), warmed lazily); compiles are
recorded to the CompileSentry with ``warm=False`` so a novel ``k`` never
trips the serve recompile alarm, which guards the *embed* warmup contract.

:class:`MutableCorpus` makes the corpus a live, writable store: upserts and
deletes (``POST /v1/corpus/{upsert,delete}``) rebuild a fresh generation-
tagged :class:`NeighborIndex` off to the side and commit it with one atomic
reference swap (the same stage-then-commit discipline as the coscheduler's
``ReloadManager``) — in-flight queries keep the index they started with, so
a mutation can never serve a torn shard.
"""

from __future__ import annotations

import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from simclr_tpu.parallel.compress import (
    DEFAULT_BUCKET_SIZE,
    dequantize_weight_buckets,
    quantize_weight_buckets,
    validate_corpus_dtype,
)
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    batch_sharding,
    retrieval_mesh,
)
from simclr_tpu.serve.engine import make_buckets
from simclr_tpu.utils.fetch import fetch

METRICS = ("dot", "cosine")

# routing score for a padding centroid (shards with fewer real rows than
# cells): the -||c||²/2 term makes a huge-norm centroid unroutable without
# ever producing a non-finite value inside the kernel
_PAD_CENTROID = 1.0e4


def _normalize_rows(x: np.ndarray) -> np.ndarray:
    norms = np.linalg.norm(x, axis=1, keepdims=True)
    return x / np.where(norms > 0.0, norms, 1.0)


def _balanced_assign(block: np.ndarray, cent: np.ndarray, cap: int) -> np.ndarray:
    """Capacity-capped nearest-centroid assignment for IVF tile packing.

    Rows claim their best cell (k-means rule: argmax of ``x·c - ||c||²/2``)
    in confidence order; a full cell spills the row to its next-best cell
    with space. Total capacity ``cells * cap >= len(block)`` is guaranteed
    by the caller's tile sizing, so every row lands somewhere and the
    probe == cells candidate set still covers the whole shard.
    """
    logits = block @ cent.T - 0.5 * np.sum(cent * cent, axis=1)[None, :]
    ranked = np.argsort(-logits, axis=1)
    order = np.argsort(-np.max(logits, axis=1))
    counts = np.zeros(cent.shape[0], np.int64)
    assign = np.empty(block.shape[0], np.int32)
    for i in order:
        for c in ranked[i]:
            if counts[c] < cap:
                assign[i] = c
                counts[c] += 1
                break
    return assign


def _load_corpus(path: str):
    """Host array from ``.npy``/``.npz`` (``eval.save_features`` layout).

    ``.npy`` opens as ``mmap_mode="r"`` — :class:`NeighborIndex` slices one
    shard's row block at a time off the map, so a multi-GiB corpus is never
    duplicated in host RAM on the way to HBM. ``.npz`` is zip-compressed
    (not mappable): the named array decompresses fully, as before.
    """
    path = str(path)
    if path.endswith(".npz"):
        with np.load(path) as z:
            key = "features" if "features" in z.files else z.files[0]
            return z[key]
    return np.load(path, mmap_mode="r")


def _merge_local_topk(q, vals, gidx, k: int):
    """Shared merge tail: per-shard (B, kk) winners -> global (B, k).

    (S, B, kk) -> shard-major (B, S*kk) candidate lists: stable TopK over
    this layout tie-breaks to the lowest global row id.
    """
    vals_all = jax.lax.all_gather(vals, DATA_AXIS)
    gidx_all = jax.lax.all_gather(gidx, DATA_AXIS)
    cand_vals = jnp.moveaxis(vals_all, 0, 1).reshape(q.shape[0], -1)
    cand_idx = jnp.moveaxis(gidx_all, 0, 1).reshape(q.shape[0], -1)
    top_vals, pos = jax.lax.top_k(cand_vals, k)
    top_idx = jnp.take_along_axis(cand_idx, pos, axis=1)
    return top_vals, top_idx


class NeighborIndex:
    """Row-sharded corpus + per-(k, bucket) compiled top-k programs.

    ``metric="cosine"`` L2-normalizes corpus rows at upload and queries at
    request time, reducing cosine similarity to the same dot-product
    kernel. ``corpus_dtype`` picks the resident storage format and
    ``ann_cells``/``ann_probe`` the scan strategy (module docstring); the
    defaults — fp32, exact — are byte-identical to the original index.
    Thread model: ``query`` may be called from any handler thread; a lock
    serializes program build + compile bookkeeping (the matmul itself is
    serialized by jax's dispatch anyway).
    """

    def __init__(
        self,
        corpus,
        *,
        metric: str = "dot",
        max_queries: int = 256,
        mesh=None,
        sentry=None,
        metrics=None,
        generation: int = 0,
        corpus_dtype: str = "fp32",
        ann_cells: int = 0,
        ann_probe: int = 1,
        row_ids=None,
    ):
        if metric not in METRICS:
            raise ValueError(f"neighbors metric must be one of {METRICS}, got {metric!r}")
        validate_corpus_dtype(corpus_dtype)
        if int(ann_cells) < 0:
            raise ValueError(f"ann_cells must be >= 0 (0 = exact scan), got {ann_cells}")
        if int(ann_probe) < 1:
            raise ValueError(f"ann_probe must be >= 1, got {ann_probe}")
        # keep ndarrays (incl. np.memmap) by reference: shard blocks are
        # sliced off lazily so a memmapped corpus never fully materializes
        host = corpus if isinstance(corpus, np.ndarray) else np.asarray(corpus, np.float32)
        if host.ndim != 2 or host.shape[0] < 1:
            raise ValueError(f"corpus must be (n >= 1, d), got {host.shape}")
        self.metric = metric
        self.dtype = corpus_dtype
        # which encoder generation embedded this corpus (coscheduler swap /
        # corpus-mutation tag): a fresh index is built per swap and the
        # server's index reference swapped atomically, so /v1/neighbors
        # always answers from one coherent (weights, corpus) generation
        self.generation = int(generation)
        self.n, self.d = host.shape
        if row_ids is not None:
            row_ids = np.asarray(row_ids, np.int64).reshape(-1)
            if row_ids.shape[0] != self.n:
                raise ValueError(
                    f"row_ids must have one id per corpus row ({self.n}), "
                    f"got {row_ids.shape[0]}"
                )
        # external ids for the rows (MutableCorpus); None = positions are ids
        self.row_ids = row_ids
        self.mesh = mesh if mesh is not None else retrieval_mesh()
        self.n_shards = self.mesh.shape[DATA_AXIS]
        self.rows_per_shard = -(-self.n // self.n_shards)
        self.max_queries = int(max_queries)
        self.buckets = make_buckets(self.max_queries)
        self.sentry = sentry
        self.metrics = metrics
        self._lock = threading.Lock()
        self._fns: dict[int, object] = {}
        self._compiled: set[tuple[int, int]] = set()
        self._build_device_state(host, int(ann_cells), int(ann_probe))
        if metrics is not None:
            hbm = sum(int(a.nbytes) for a in self._device_arrays)
            if hasattr(metrics, "corpus_hbm_bytes"):
                metrics.corpus_hbm_bytes.set(hbm)
            if hasattr(metrics, "corpus_rows"):
                metrics.corpus_rows.set(self.n)
            if hasattr(metrics, "ann_cells_probed"):
                metrics.ann_cells_probed.set(self.ann_probe if self.ann_cells else 0)

    # -- corpus residency ---------------------------------------------------
    def _shard_block(self, host, s: int) -> np.ndarray:
        """Shard ``s``'s padded (R, d) fp32 row block, sliced from ``host``.

        Materializes ONE shard's rows (fp32-converts + normalizes just that
        slice) — with a memmapped ``host`` this is the only host copy that
        ever exists, which is the point of ``from_file``'s ``mmap_mode``.
        """
        r = self.rows_per_shard
        start, stop = s * r, min((s + 1) * r, self.n)
        x = np.asarray(host[start:stop], np.float32)
        if self.metric == "cosine":
            x = _normalize_rows(x)
        if stop - start < r:
            pad = np.zeros((r - max(stop - start, 0), self.d), np.float32)
            x = np.concatenate([x, pad]) if x.size else pad
        return x

    def _build_device_state(self, host, ann_cells: int, ann_probe: int) -> None:
        """Build the mode's device-resident arrays, one shard at a time."""
        s_count, r, d = self.n_shards, self.rows_per_shard, self.d
        shard0 = NamedSharding(self.mesh, P(DATA_AXIS))
        self.ann_cells = 0
        self.ann_probe = 0
        self.cell_rows = 0
        self.corpus = None

        if not ann_cells:
            if self.dtype == "fp32":
                # device-resident, row-sharded over the data axis; the padded
                # tail is masked to -inf in the kernel so it can never win
                self.corpus = jax.make_array_from_callback(
                    (s_count * r, d),
                    batch_sharding(self.mesh),
                    lambda idx: self._shard_block(host, (idx[0].start or 0) // r),
                )
                self._device_arrays = (self.corpus,)
                self._operands = (self.corpus,)
            else:
                nb = -(-(r * d) // DEFAULT_BUCKET_SIZE) if r * d else 1
                cache: dict[int, tuple[np.ndarray, np.ndarray]] = {}

                def qblock(s):
                    if s not in cache:
                        cache[s] = quantize_weight_buckets(
                            self._shard_block(host, s).reshape(-1)
                        )
                    return cache[s]

                q8 = jax.make_array_from_callback(
                    (s_count, nb, DEFAULT_BUCKET_SIZE),
                    shard0,
                    lambda idx: qblock(idx[0].start or 0)[0][None],
                )
                sc = jax.make_array_from_callback(
                    (s_count, nb),
                    shard0,
                    lambda idx: qblock(idx[0].start or 0)[1][None],
                )
                self._device_arrays = (q8, sc)
                self._operands = (q8, sc)
            return

        # IVF: per-shard k-means — each shard clusters its own row block, so
        # the FLOP savings stay local and the exact path's gather/merge tail
        # is reused unchanged (probe == cells scores exactly the exact
        # path's candidate set)
        from simclr_tpu.eval import kmeans  # lazy: pulls in the eval stack

        cells = max(1, min(ann_cells, r))
        # Balanced tiles: every cell is capped at ``tile`` rows (mean
        # occupancy + 25% slack, rounded to a multiple of 8), and rows that
        # overflow their nearest cell spill to the next-nearest with space.
        # Without the cap one skewed k-means cell sets the shared tile
        # length for ALL cells, ballooning both the padded HBM footprint
        # and the per-query candidate set (probe * tile) by the skew factor.
        cap = -(-r // cells)
        tile = max(1, min(r, ((cap + (cap + 3) // 4) + 7) // 8 * 8))
        cents, assigns = [], []
        for s in range(s_count):
            real = max(0, min(self.n - s * r, r))
            block = self._shard_block(host, s)[:real]
            if real:
                c_s, _ = kmeans(block, cells, seed=0)
                a_s = _balanced_assign(block, c_s, tile)
            else:
                c_s, a_s = np.zeros((0, d), np.float32), np.zeros((0,), np.int32)
            if c_s.shape[0] < cells:
                # pad with unroutable centroids (huge norm loses the
                # -||c||²/2 routing race); their cells hold only padding ids
                pad = np.full((cells - c_s.shape[0], d), _PAD_CENTROID, np.float32)
                c_s = np.concatenate([c_s, pad]) if c_s.size else pad
            cents.append(c_s)
            assigns.append(a_s)
        self.ann_cells = cells
        self.ann_probe = min(ann_probe, cells)
        self.cell_rows = tile

        tile_cache: dict[int, tuple[np.ndarray, np.ndarray]] = {}

        def tiles_for(s):
            if s not in tile_cache:
                block = self._shard_block(host, s)
                a_s = assigns[s]
                ids = np.full((cells, tile), -1, np.int32)
                rows = np.zeros((cells, tile, d), np.float32)
                for c in range(cells):
                    pos = np.nonzero(a_s == c)[0]
                    ids[c, : len(pos)] = s * r + pos
                    rows[c, : len(pos)] = block[pos]
                tile_cache[s] = (ids, rows)
            return tile_cache[s]

        cent = jax.make_array_from_callback(
            (s_count, cells, d), shard0, lambda idx: cents[idx[0].start or 0][None]
        )
        cell_ids = jax.make_array_from_callback(
            (s_count, cells, tile),
            shard0,
            lambda idx: tiles_for(idx[0].start or 0)[0][None],
        )
        if self.dtype == "fp32":
            tiles = jax.make_array_from_callback(
                (s_count, cells, tile, d),
                shard0,
                lambda idx: tiles_for(idx[0].start or 0)[1][None],
            )
            self._device_arrays = (cent, cell_ids, tiles)
            self._operands = (cent, cell_ids, tiles)
        else:
            nbc = -(-(tile * d) // DEFAULT_BUCKET_SIZE)
            quant_cache: dict[int, tuple[np.ndarray, np.ndarray]] = {}

            def qtiles_for(s):
                if s not in quant_cache:
                    rows = tiles_for(s)[1]
                    qs = [quantize_weight_buckets(rows[c].reshape(-1)) for c in range(cells)]
                    quant_cache[s] = (
                        np.stack([q for q, _ in qs]),
                        np.stack([sc for _, sc in qs]),
                    )
                return quant_cache[s]

            tiles_q = jax.make_array_from_callback(
                (s_count, cells, nbc, DEFAULT_BUCKET_SIZE),
                shard0,
                lambda idx: qtiles_for(idx[0].start or 0)[0][None],
            )
            tiles_s = jax.make_array_from_callback(
                (s_count, cells, nbc),
                shard0,
                lambda idx: qtiles_for(idx[0].start or 0)[1][None],
            )
            self._device_arrays = (cent, cell_ids, tiles_q, tiles_s)
            self._operands = (cent, cell_ids, tiles_q, tiles_s)

    @classmethod
    def from_file(cls, path: str, **kwargs):
        """Load an ``(n, d)`` corpus from ``.npy`` (memmapped — never doubles
        host RAM) or ``.npz`` (first array, or the ``features`` key)."""
        return cls(_load_corpus(path), **kwargs)

    # -- program construction ----------------------------------------------
    def _fn_for(self, k: int):
        """The jitted shard_map top-k program for one ``k`` (shape-keyed jit
        cache handles the query buckets)."""
        fn = self._fns.get(k)
        if fn is not None:
            return fn
        n, r, d = self.n, self.rows_per_shard, self.d

        if not self.ann_cells:
            kk = min(k, r)
            if self.dtype == "fp32":

                def local_merge(q, shard):
                    # q: (B, d) replicated; shard: (R, d) this shard's rows
                    scores = q @ shard.T  # the only similarity block ever built
                    sidx = jax.lax.axis_index(DATA_AXIS)
                    global_idx = sidx * r + jnp.arange(r, dtype=jnp.int32)
                    scores = jnp.where(global_idx[None, :] < n, scores, -jnp.inf)
                    vals, idx = jax.lax.top_k(scores, kk)
                    gidx = jnp.take(global_idx, idx)
                    return _merge_local_topk(q, vals, gidx, k)

                n_operands = 1
            else:

                def local_merge(q, q8, sc):
                    # HBM holds int8 buckets + scales; the fp32 shard exists
                    # transiently inside this program only
                    shard = dequantize_weight_buckets(q8[0], sc[0], r * d).reshape(r, d)
                    scores = q @ shard.T
                    sidx = jax.lax.axis_index(DATA_AXIS)
                    global_idx = sidx * r + jnp.arange(r, dtype=jnp.int32)
                    scores = jnp.where(global_idx[None, :] < n, scores, -jnp.inf)
                    vals, idx = jax.lax.top_k(scores, kk)
                    gidx = jnp.take(global_idx, idx)
                    return _merge_local_topk(q, vals, gidx, k)

                n_operands = 2
        else:
            p, tile = self.ann_probe, self.cell_rows
            m = p * tile
            kk = min(k, m)

            def route(q, cent):
                # nearest-centroid routing: argmax(q·c - ||c||²/2) — the
                # k-means assignment rule, so queries land in the cells
                # their neighbors were binned into
                cs = q @ cent.T - 0.5 * jnp.sum(cent * cent, axis=1)[None, :]
                _, cell_idx = jax.lax.top_k(cs, p)
                return cell_idx  # (B, p)

            if self.dtype == "fp32":

                def local_merge(q, cent, ids, tiles):
                    b = q.shape[0]
                    cell_idx = route(q, cent[0])
                    # (B, m, d) candidate block scored as a batched matvec —
                    # the 4-d einsum form lowers to scalar loops on CPU
                    t = tiles[0][cell_idx].reshape(b, m, d)
                    gid = ids[0][cell_idx].reshape(b, m)
                    scores = jax.lax.dot_general(
                        t, q, (((2,), (1,)), ((0,), (0,)))
                    )
                    scores = jnp.where(gid >= 0, scores, -jnp.inf)
                    vals, idx = jax.lax.top_k(scores, kk)
                    gidx = jnp.take_along_axis(gid, idx, axis=1)
                    return _merge_local_topk(q, vals, gidx, k)

                n_operands = 3
            else:

                def local_merge(q, cent, ids, tq, ts):
                    b = q.shape[0]
                    cell_idx = route(q, cent[0])
                    # gather stays int8 — only the probed tiles dequantize
                    x = tq[0][cell_idx].astype(jnp.float32) * ts[0][cell_idx][..., None]
                    t = x.reshape(b, p, -1)[:, :, : tile * d].reshape(b, m, d)
                    gid = ids[0][cell_idx].reshape(b, m)
                    scores = jax.lax.dot_general(
                        t, q, (((2,), (1,)), ((0,), (0,)))
                    )
                    scores = jnp.where(gid >= 0, scores, -jnp.inf)
                    vals, idx = jax.lax.top_k(scores, kk)
                    gidx = jnp.take_along_axis(gid, idx, axis=1)
                    return _merge_local_topk(q, vals, gidx, k)

                n_operands = 4

        fn = jax.jit(
            jax.shard_map(
                local_merge,
                mesh=self.mesh,
                in_specs=(P(),) + (P(DATA_AXIS),) * n_operands,
                out_specs=(P(), P()),
                check_vma=False,
            )
        )
        self._fns[k] = fn
        return fn

    def bucket_for(self, n_queries: int) -> int:
        if n_queries < 1:
            raise ValueError(f"need at least one query, got {n_queries}")
        if n_queries > self.max_queries:
            raise ValueError(
                f"{n_queries} queries exceeds the {self.max_queries}-query "
                f"ceiling; split the request"
            )
        for b in self.buckets:
            if b >= n_queries:
                return b
        raise AssertionError("unreachable: buckets end at max_queries")

    def warmup(self, k: int) -> None:
        """Pre-compile every query bucket for one ``k`` (served cold
        otherwise — neighbors compiles never alarm)."""
        for b in self.buckets:
            self._query_padded(np.zeros((b, self.d), np.float32), k, b)

    # -- request path ------------------------------------------------------
    def query(self, queries, k: int) -> tuple[np.ndarray, np.ndarray]:
        """Top-``k`` over the corpus; ``(B, k)`` scores + row indices.

        ``queries``: ``(B, d)`` float rows. ``k`` must fit the corpus
        (``1 <= k <= n``); under ANN it must also fit the probed candidate
        set. Exact modes fill every slot with a real row; ANN slots beyond
        the probed cells' real rows come back as index -1 / score -inf.
        """
        q = np.asarray(queries, np.float32)
        if q.ndim != 2 or q.shape[1] != self.d:
            raise ValueError(f"queries must be (B, {self.d}), got {q.shape}")
        if not 1 <= int(k) <= self.n:
            raise ValueError(f"k must be in [1, {self.n}] for a {self.n}-row corpus, got {k}")
        k = int(k)
        if self.ann_cells:
            cand = self.n_shards * self.ann_probe * self.cell_rows
            if k > cand:
                raise ValueError(
                    f"k={k} exceeds the {cand} candidates reachable at "
                    f"ann_probe={self.ann_probe} (raise serve.ann_probe)"
                )
        b = q.shape[0]
        bucket = self.bucket_for(b)
        if self.metric == "cosine":
            q = _normalize_rows(q)
        if b < bucket:
            q = np.concatenate([q, np.zeros((bucket - b, self.d), np.float32)])
        t0 = time.perf_counter()
        vals, idx = self._query_padded(q, k, bucket)
        if self.metrics is not None:
            self.metrics.neighbors_requests_total.inc()
            self.metrics.neighbors_queries_total.inc(b)
            self.metrics.neighbors_latency_ms.observe(
                (time.perf_counter() - t0) * 1000.0
            )
        return np.asarray(vals[:b]), np.asarray(idx[:b], np.int64)

    def _query_padded(self, q: np.ndarray, k: int, bucket: int):
        with self._lock:
            fn = self._fn_for(k)
            cold = (k, bucket) not in self._compiled
            if cold:
                self._compiled.add((k, bucket))
        t0 = time.perf_counter()
        out_vals, out_idx = fn(q, *self._operands)
        vals, idx = fetch(out_vals), fetch(out_idx)
        if cold and self.sentry is not None:
            # warm=False by design: novel (k, bucket) programs are an
            # expected lazy compile, not a broken embed warmup
            self.sentry.record_compile(
                f"neighbors_k{k}_q{bucket}",
                seconds=time.perf_counter() - t0,
                warm=False,
            )
        return vals, idx

    # -- observability ------------------------------------------------------
    def hbm_state(self) -> dict:
        """The /healthz ``neighbors`` entry: corpus residency + programs."""
        return {
            "rows": self.n,
            "dim": self.d,
            "metric": self.metric,
            "corpus_dtype": self.dtype,
            "ann_cells": self.ann_cells,
            "ann_probe": self.ann_probe,
            "cell_rows": self.cell_rows,
            "generation": self.generation,
            "shards": self.n_shards,
            "rows_per_shard": self.rows_per_shard,
            "corpus_hbm_bytes": sum(int(a.nbytes) for a in self._device_arrays),
            "compiled_programs": sorted(self._compiled),
        }


class MutableCorpus:
    """Generation-tagged mutable corpus: the store behind ``/v1/corpus/*``.

    Holds the authoritative host rows + external int64 ids and rebuilds a
    fresh :class:`NeighborIndex` per mutation, committing it to the server
    with one atomic reference swap INSIDE the mutation lock — concurrent
    mutations therefore commit in generation order, and a reader either
    sees the old complete index or the new complete index, never a torn
    mix (handlers read ``server.index`` once per request). ``index_kwargs``
    (metric, dtype, ANN knobs, mesh, sentry, max_queries) are captured at
    construction and reused for every rebuild.

    A memmapped ``embeddings`` (the ``from_file`` path) stays on the map
    until the first mutation, which materializes a private fp32 copy.
    """

    def __init__(
        self,
        embeddings,
        *,
        ids=None,
        server=None,
        metrics=None,
        generation: int = 0,
        **index_kwargs,
    ):
        rows = embeddings if isinstance(embeddings, np.ndarray) else np.asarray(
            embeddings, np.float32
        )
        if rows.ndim != 2 or rows.shape[0] < 1:
            raise ValueError(f"corpus must be (n >= 1, d), got {rows.shape}")
        n = rows.shape[0]
        if ids is None:
            id_arr = np.arange(n, dtype=np.int64)
        else:
            id_arr = np.asarray(ids, np.int64).reshape(-1)
            if id_arr.shape[0] != n:
                raise ValueError(f"need one id per row ({n}), got {id_arr.shape[0]}")
            if np.unique(id_arr).shape[0] != n:
                raise ValueError("corpus ids must be unique")
        self._rows = rows
        self._ids = id_arr
        self.server = server
        self.metrics = metrics
        self._kwargs = dict(index_kwargs)
        self.lock = threading.Lock()
        self.generation = int(generation)
        self.index = None
        with self.lock:
            self._commit(self.generation)

    @classmethod
    def from_file(cls, path: str, **kwargs):
        """Memmap-backed store from ``.npy``/``.npz`` (same loader as
        :meth:`NeighborIndex.from_file`)."""
        return cls(_load_corpus(path), **kwargs)

    @property
    def rows(self) -> int:
        return self._rows.shape[0]

    def _commit(self, generation: int) -> None:
        """Build + publish one generation. Caller holds ``self.lock``: the
        swap happens inside the mutation critical section so generations
        can only ever become visible in the order they were built."""
        index = NeighborIndex(
            self._rows,
            metrics=self.metrics,
            generation=int(generation),
            row_ids=self._ids.copy(),
            **self._kwargs,
        )
        self.index = index
        self.generation = int(generation)
        if self.server is not None:
            self.server.swap_index(index)
        if self.metrics is not None and hasattr(self.metrics, "corpus_generation"):
            self.metrics.corpus_generation.set(self.generation)

    def _materialized(self) -> np.ndarray:
        """A private writable fp32 copy of the rows (mutations never write
        through to a caller's array or a read-only memmap)."""
        return np.array(self._rows, np.float32)

    def upsert(self, ids, embeddings) -> dict:
        """Insert-or-update rows by external id; returns the new state."""
        id_arr = np.asarray(ids, np.int64).reshape(-1)
        emb = np.asarray(embeddings, np.float32)
        if emb.ndim != 2 or emb.shape[0] != id_arr.shape[0]:
            raise ValueError(
                f"embeddings must be ({id_arr.shape[0]}, d) — one row per id — "
                f"got {emb.shape}"
            )
        if np.unique(id_arr).shape[0] != id_arr.shape[0]:
            raise ValueError("upsert ids must be unique within one request")
        with self.lock:
            if emb.shape[1] != self._rows.shape[1]:
                raise ValueError(
                    f"embedding dim {emb.shape[1]} != corpus dim {self._rows.shape[1]}"
                )
            pos = {int(v): i for i, v in enumerate(self._ids)}
            rows = self._materialized()
            fresh = [i for i, v in enumerate(id_arr) if int(v) not in pos]
            for i, v in enumerate(id_arr):
                p = pos.get(int(v))
                if p is not None:
                    rows[p] = emb[i]
            if fresh:
                rows = np.concatenate([rows, emb[fresh]])
                self._ids = np.concatenate([self._ids, id_arr[fresh]])
            self._rows = rows
            self._commit(self.generation + 1)
            return {"generation": self.generation, "rows": self.rows}

    def delete(self, ids) -> dict:
        """Remove rows by external id; unknown ids are an error (a delete
        that silently no-ops would mask producer/consumer id drift)."""
        id_arr = np.asarray(ids, np.int64).reshape(-1)
        if id_arr.shape[0] < 1:
            raise ValueError("delete needs at least one id")
        with self.lock:
            known = set(int(v) for v in self._ids)
            missing = sorted(int(v) for v in id_arr if int(v) not in known)
            if missing:
                raise ValueError(f"unknown corpus ids: {missing[:8]}")
            drop = set(int(v) for v in id_arr)
            keep = np.array([int(v) not in drop for v in self._ids], bool)
            if not keep.any():
                raise ValueError(
                    "cannot delete every corpus row (the index needs n >= 1)"
                )
            self._rows = self._materialized()[keep]
            self._ids = self._ids[keep]
            self._commit(self.generation + 1)
            return {"generation": self.generation, "rows": self.rows}

    def replace(self, embeddings, generation: int) -> dict:
        """Wholesale corpus swap — the coscheduler's per-weight-swap re-embed
        path. Ids reset to row positions; the committed generation is the
        caller's tag unless interleaved mutations already advanced past it
        (``max`` keeps the sequence monotone either way)."""
        rows = embeddings if isinstance(embeddings, np.ndarray) else np.asarray(
            embeddings, np.float32
        )
        if rows.ndim != 2 or rows.shape[0] < 1:
            raise ValueError(f"corpus must be (n >= 1, d), got {rows.shape}")
        with self.lock:
            self._rows = rows
            self._ids = np.arange(rows.shape[0], dtype=np.int64)
            self._commit(max(int(generation), self.generation + 1))
            return {"generation": self.generation, "rows": self.rows}
