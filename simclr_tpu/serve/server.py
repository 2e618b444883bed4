"""HTTP front end: stdlib ThreadingHTTPServer JSON API over the batcher.

Endpoints:

  * ``POST /v1/embed`` — body ``{"instances": [image, ...]}`` where each
    image is a nested list of uint8 pixels shaped like the engine's input
    (CIFAR: 32x32x3). Response ``{"embeddings": [[...], ...], "model": ...}``
    row-aligned with the instances, with an ``X-Served-By: <replica>``
    header naming the replica that computed it. Errors: 400 malformed
    body/shape/range, 413 more rows than ``serve.max_batch``, 429 queue
    full (backpressure — retry with backoff), 500 engine failure, 503
    draining.
  * ``POST /v1/neighbors`` — body ``{"queries": [[d floats], ...],
    "k": int}`` (``k`` optional, default ``serve.neighbors_k``). Exact
    top-k over the row-sharded in-HBM corpus (``serve.corpus``,
    ``serve/retrieval.py``); response ``{"indices": [[...]], "scores":
    [[...]]}`` row-aligned with the queries. 404 when no corpus is
    configured, 400 malformed queries/k, 503 draining.
  * ``POST /v1/corpus/upsert`` — body ``{"ids": [int, ...], "embeddings":
    [[d floats], ...]}``: insert-or-update corpus rows by external id.
    ``POST /v1/corpus/delete`` — body ``{"ids": [int, ...]}``. Both commit
    a fresh generation-tagged index with one atomic swap (zero downtime —
    in-flight queries finish on the generation they started with) and
    answer ``{"generation": g, "rows": n}`` + ``X-Corpus-Generation``.
    404 when the corpus is not mutable (no store), 400 bad ids/shapes,
    503 draining.
  * ``GET /healthz`` — 200 once warm and accepting (with per-replica
    state under ``"replicas"``, corpus residency under ``"neighbors"``, and
    the platform, device kind and device count under ``"device"``), 503
    while draining.
  * ``GET /metrics`` — Prometheus text format (``serve/metrics.py``).
  * ``GET /debug/slow`` — the slowest recent requests with their span
    breakdowns (``obs/trace.py`` ring buffer).

Request tracing: every ``/v1/embed`` request gets an ``X-Request-Id``
(client-supplied, sanitized, or generated), echoed on the response and
used in log lines, and records queue_wait / coalesce / pad /
device_compute / serialize spans into the server's
:class:`~simclr_tpu.obs.trace.TraceRecorder` — which also samples
completed traces into ``serve.requests_log`` at
``serve.trace_sample_rate``.

Shutdown contract (tested): SIGTERM (or SIGINT) flips the server into
draining — new embeds get 503, ``/healthz`` reports draining — then the
batcher drains (every accepted request is answered), the accept loop
stops, in-flight handler threads are joined, and the process exits 0.

JSON float fidelity: embeddings are float32; Python serializes each via
the shortest repr of its exact double value, so a client reading the JSON
back into float32 recovers the embedding **bitwise** — the e2e test
asserts equality through the full HTTP round trip.
"""

from __future__ import annotations

import json
import signal
import threading
from concurrent.futures import TimeoutError as _FutureTimeout
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np

from simclr_tpu.obs.trace import RequestTrace, TraceRecorder, clean_request_id
from simclr_tpu.serve.batcher import BackpressureError, BatcherClosedError
from simclr_tpu.utils.logging import get_logger
from simclr_tpu.utils.platform import compilation_cache_stats, device_identity

logger = get_logger()


class EmbedServer(ThreadingHTTPServer):
    """ThreadingHTTPServer carrying the serving stack for its handlers.

    ``daemon_threads=True`` with the default ``block_on_close=True``:
    handler threads never outlive a crash, but a clean ``server_close()``
    still joins them — required for the drain guarantee.
    """

    daemon_threads = True
    allow_reuse_address = True

    def __init__(
        self,
        address,
        engine,
        batcher,
        metrics,
        request_timeout_s=30.0,
        recorder: TraceRecorder | None = None,
        pool=None,
        index=None,
        neighbors_k_default=10,
        corpus_store=None,
    ):
        super().__init__(address, EmbedHandler)
        self.engine = engine
        self.batcher = batcher
        self.metrics = metrics
        self.pool = pool          # serve/replica.py ReplicaPool (healthz fan-out)
        self.index = index        # serve/retrieval.py NeighborIndex, or None
        # serve/retrieval.py MutableCorpus: enables /v1/corpus/* mutations
        self.corpus_store = corpus_store
        self.neighbors_k_default = int(neighbors_k_default)
        self.request_timeout_s = float(request_timeout_s)
        self.recorder = recorder if recorder is not None else TraceRecorder()
        self.draining = threading.Event()

    def swap_index(self, index) -> None:
        """Atomically replace the retrieval index (generation-tagged corpus
        swap). Handlers read ``self.server.index`` exactly once per request,
        so an in-flight ``/v1/neighbors`` finishes on the index it started
        with and the next request sees the new generation — the corpus
        counterpart of ``EmbedEngine.commit``."""
        self.index = index


class EmbedHandler(BaseHTTPRequestHandler):
    server: EmbedServer
    server_version = "simclr-serve/1.0"
    protocol_version = "HTTP/1.1"
    # TCP_NODELAY: without it, Nagle + delayed ACK stalls small
    # response-then-request exchanges on keep-alive connections by ~40ms —
    # an order of magnitude over the coalescing window itself
    disable_nagle_algorithm = True

    # quiet per-request lines; keep them reachable at debug level
    def log_message(self, fmt, *args):  # noqa: D102
        logger.debug("http %s", fmt % args)

    def _send(self, code: int, body: bytes, content_type: str, headers=()) -> None:
        try:
            self.send_response(code)
            self.send_header("Content-Type", content_type)
            self.send_header("Content-Length", str(len(body)))
            rid = getattr(self, "_request_id", None)
            if rid:
                self.send_header("X-Request-Id", rid)
            for k, v in headers:
                self.send_header(k, v)
            self.end_headers()
            self.wfile.write(body)
        except (BrokenPipeError, ConnectionResetError):
            # client hung up mid-response: routine (impatient callers,
            # load-balancer health probes) — count it, don't traceback
            if self.server.metrics is not None:
                self.server.metrics.client_disconnects_total.inc()
            self.close_connection = True

    def _send_json(self, code: int, payload: dict, headers=()) -> None:
        self._send(
            code, json.dumps(payload).encode(), "application/json", headers
        )

    # -- GET ---------------------------------------------------------------
    def do_GET(self):  # noqa: N802 - BaseHTTPRequestHandler API
        # one handler instance serves a whole keep-alive connection; clear
        # any id left by a previous POST on the same socket
        self._request_id = None
        if self.path == "/healthz":
            if self.server.draining.is_set():
                self._send_json(503, {"status": "draining"})
            else:
                payload = {
                    "status": "ok",
                    "buckets": list(self.server.engine.buckets),
                    "max_batch": self.server.engine.max_batch,
                    "feature_dim": self.server.engine.feature_dim,
                    "checkpoint": getattr(
                        self.server.engine, "checkpoint_path", None
                    ),
                    "device": device_identity(),
                    "compile_cache": compilation_cache_stats(),
                }
                if self.server.pool is not None:
                    payload["replicas"] = self.server.pool.state()
                    # serving generation = min across replicas: advances
                    # only once EVERY replica committed the new weights
                    payload["weights_generation"] = (
                        self.server.pool.weights_generation
                    )
                index = self.server.index
                if index is not None:
                    payload["neighbors"] = index.hbm_state()
                    payload["corpus_generation"] = int(
                        getattr(index, "generation", 0)
                    )
                self._send_json(200, payload)
        elif self.path == "/metrics":
            self._send(
                200,
                self.server.metrics.render().encode(),
                "text/plain; version=0.0.4",
            )
        elif self.path == "/debug/slow":
            self._send_json(200, {"slowest": self.server.recorder.slowest()})
        else:
            self._send_json(404, {"error": f"no such path {self.path!r}"})

    # -- POST --------------------------------------------------------------
    def do_POST(self):  # noqa: N802 - BaseHTTPRequestHandler API
        # resolved first so EVERY response (including errors) echoes the id
        rid = clean_request_id(self.headers.get("X-Request-Id"))
        self._request_id = rid
        if self.path == "/v1/neighbors":
            self._post_neighbors(rid)
            return
        if self.path in ("/v1/corpus/upsert", "/v1/corpus/delete"):
            self._post_corpus(rid, self.path.rsplit("/", 1)[1])
            return
        if self.path != "/v1/embed":
            self._send_json(404, {"error": f"no such path {self.path!r}"})
            return
        if self.server.draining.is_set():
            self._send_json(
                503, {"error": "server is draining"}, [("Retry-After", "1")]
            )
            return
        try:
            images = self._parse_instances()
        except _BadRequest as e:
            logger.debug("embed %s rejected (%d): %s", rid, e.code, e)
            self._send_json(e.code, {"error": str(e)})
            return
        trace = RequestTrace(rid)
        try:
            future = self.server.batcher.submit(images, trace=trace)
        except BackpressureError as e:
            self._send_json(429, {"error": str(e)}, [("Retry-After", "1")])
            return
        except BatcherClosedError as e:
            self._send_json(503, {"error": str(e)}, [("Retry-After", "1")])
            return
        try:
            embeddings = future.result(timeout=self.server.request_timeout_s)
        except (TimeoutError, _FutureTimeout):
            logger.warning(
                "embed %s timed out after %.1fs",
                rid,
                self.server.request_timeout_s,
            )
            self._send_json(
                504,
                {"error": f"embed timed out after {self.server.request_timeout_s}s"},
            )
            return
        except BatcherClosedError as e:
            self._send_json(503, {"error": str(e)})
            return
        except Exception as e:  # engine failure — already counted by batcher
            logger.warning("embed %s failed in engine: %r", rid, e)
            self._send_json(500, {"error": repr(e)})
            return
        # ndarray.tolist() converts float32 -> exact Python double in C
        # (same shortest-repr doubles as the old per-element loop, so the
        # JSON round trip stays bitwise exact — tested), without an O(n*d)
        # Python-level loop
        with trace.span("serialize"):
            body = json.dumps(
                {"embeddings": np.asarray(embeddings).tolist()}
            ).encode()
        rec = self.server.recorder.record(trace)
        logger.debug(
            "embed %s: %d rows in %.1f ms", rid, len(embeddings), rec["total_ms"]
        )
        # stamped by the dispatching replica's worker before the future
        # resolved (pool mode); absent on the legacy single-engine path
        served_by = getattr(future, "replica_id", None)
        headers = (
            [("X-Served-By", str(served_by))] if served_by is not None else []
        )
        # the weight generation the dispatching replica served this request
        # with — what the co-scheduler smoke compares against the corpus
        # generation for embed/neighbors consistency
        generation = getattr(future, "generation", None)
        if generation is not None:
            headers.append(("X-Weights-Generation", str(generation)))
        self._send(200, body, "application/json", headers)

    def _post_neighbors(self, rid) -> None:
        index = self.server.index
        if index is None:
            self._send_json(
                404,
                {"error": "no retrieval corpus configured (set serve.corpus)"},
            )
            return
        if self.server.draining.is_set():
            self._send_json(
                503, {"error": "server is draining"}, [("Retry-After", "1")]
            )
            return
        try:
            queries, k = self._parse_neighbors(index)
        except _BadRequest as e:
            logger.debug("neighbors %s rejected (%d): %s", rid, e.code, e)
            self._send_json(e.code, {"error": str(e)})
            return
        try:
            scores, indices = index.query(queries, k)
        except ValueError as e:
            self._send_json(400, {"error": str(e)})
            return
        except Exception as e:  # device failure
            logger.warning("neighbors %s failed: %r", rid, e)
            self._send_json(500, {"error": repr(e)})
            return
        payload = {
            "indices": indices.tolist(),
            "scores": scores.tolist(),
            "k": k,
            "metric": index.metric,
        }
        row_ids = getattr(index, "row_ids", None)
        if row_ids is not None:
            # external ids for a mutable corpus; ANN padding slots (idx -1)
            # stay -1
            payload["ids"] = np.where(
                indices >= 0,
                row_ids[np.clip(indices, 0, len(row_ids) - 1)],
                -1,
            ).tolist()
        self._send_json(
            200,
            payload,
            [("X-Corpus-Generation", str(getattr(index, "generation", 0)))],
        )

    def _post_corpus(self, rid, action: str) -> None:
        store = self.server.corpus_store
        if store is None:
            self._send_json(
                404,
                {"error": "corpus is not mutable "
                          "(serve without a corpus store; set serve.corpus)"},
            )
            return
        if self.server.draining.is_set():
            self._send_json(
                503, {"error": "server is draining"}, [("Retry-After", "1")]
            )
            return
        length = int(self.headers.get("Content-Length") or 0)
        if length <= 0:
            self._send_json(400, {"error": "missing request body"})
            return
        try:
            payload = json.loads(self.rfile.read(length))
        except (ValueError, UnicodeDecodeError) as e:
            self._send_json(400, {"error": f"body is not valid JSON: {e}"})
            return
        needed = ("ids", "embeddings") if action == "upsert" else ("ids",)
        if not isinstance(payload, dict) or any(k not in payload for k in needed):
            self._send_json(
                400,
                {"error": f'body must be a JSON object with {" and ".join(needed)}'},
            )
            return
        try:
            if action == "upsert":
                out = store.upsert(payload["ids"], payload["embeddings"])
            else:
                out = store.delete(payload["ids"])
        except (ValueError, TypeError) as e:
            logger.debug("corpus %s %s rejected: %s", action, rid, e)
            self._send_json(400, {"error": str(e)})
            return
        except Exception as e:  # device failure mid-rebuild
            logger.warning("corpus %s %s failed: %r", action, rid, e)
            self._send_json(500, {"error": repr(e)})
            return
        out = dict(out)
        out["status"] = "committed"
        self._send_json(
            200, out, [("X-Corpus-Generation", str(out["generation"]))]
        )

    def _parse_neighbors(self, index) -> tuple:
        length = int(self.headers.get("Content-Length") or 0)
        if length <= 0:
            raise _BadRequest("missing request body")
        try:
            payload = json.loads(self.rfile.read(length))
        except (ValueError, UnicodeDecodeError) as e:
            raise _BadRequest(f"body is not valid JSON: {e}") from None
        if not isinstance(payload, dict) or "queries" not in payload:
            raise _BadRequest('body must be a JSON object with "queries"')
        try:
            queries = np.asarray(payload["queries"], np.float32)
        except (ValueError, TypeError) as e:
            raise _BadRequest(f"queries are not a rectangular float array: {e}") from None
        if queries.ndim != 2 or queries.shape[1] != index.d:
            raise _BadRequest(
                f"queries must be shaped (n, {index.d}), got {queries.shape}"
            )
        if not 1 <= queries.shape[0] <= index.max_queries:
            raise _BadRequest(
                f"queries must carry 1..{index.max_queries} rows, "
                f"got {queries.shape[0]}"
            )
        if not np.isfinite(queries).all():
            raise _BadRequest("queries must be finite floats")
        k = payload.get("k", self.server.neighbors_k_default)
        if not isinstance(k, int) or isinstance(k, bool):
            raise _BadRequest(f"k must be an integer, got {k!r}")
        if not 1 <= k <= index.n:
            raise _BadRequest(
                f"k must be in [1, {index.n}] for a {index.n}-row corpus, got {k}"
            )
        return queries, k

    def _parse_instances(self) -> np.ndarray:
        length = int(self.headers.get("Content-Length") or 0)
        if length <= 0:
            raise _BadRequest("missing request body")
        try:
            payload = json.loads(self.rfile.read(length))
        except (ValueError, UnicodeDecodeError) as e:
            raise _BadRequest(f"body is not valid JSON: {e}") from None
        if isinstance(payload, dict) and ("input_ids" in payload or "tokens" in payload):
            raise _BadRequest(
                "this server embeds images (\"instances\" of pixels); token "
                "inputs for a sequence encoder are not built (ROADMAP.md, Reach)"
            )
        if not isinstance(payload, dict) or "instances" not in payload:
            raise _BadRequest('body must be a JSON object with "instances"')
        engine = self.server.engine
        try:
            images = np.asarray(payload["instances"])
        except (ValueError, TypeError) as e:
            raise _BadRequest(f"instances are not a rectangular array: {e}") from None
        if images.ndim != 1 + len(engine.input_shape) or (
            images.shape[1:] != engine.input_shape
        ):
            raise _BadRequest(
                f"instances must be shaped (n, "
                f"{', '.join(map(str, engine.input_shape))}), got {images.shape}"
            )
        if images.shape[0] < 1:
            raise _BadRequest("instances must carry at least one image")
        if images.shape[0] > engine.max_batch:
            raise _BadRequest(
                f"{images.shape[0]} instances exceeds max_batch="
                f"{engine.max_batch}; split the request",
                code=413,
            )
        if not np.issubdtype(images.dtype, np.integer):
            raise _BadRequest(f"pixels must be integers 0..255, got {images.dtype}")
        if images.min() < 0 or images.max() > 255:
            raise _BadRequest("pixel values must be uint8 (0..255)")
        return images.astype(np.uint8)


class _BadRequest(ValueError):
    def __init__(self, message: str, code: int = 400):
        super().__init__(message)
        self.code = code


def run_server(cfg) -> int:
    """Build the stack from ``cfg``, serve until SIGTERM/SIGINT, drain, 0.

    The ``python -m simclr_tpu.serve`` body, also callable in-process (the
    e2e tests drive it via :func:`start_server` below instead, which skips
    the signal wiring the test process cannot own).
    """
    from simclr_tpu.config import check_serve_conf
    from simclr_tpu.serve.metrics import ServeMetrics
    from simclr_tpu.serve.replica import ReplicaPool

    check_serve_conf(cfg)
    metrics = ServeMetrics()
    logger.info("restoring checkpoint and building replicas...")
    pool = ReplicaPool.from_checkpoint(cfg, metrics=metrics, warmup=False)
    warm_times = pool.warmup()
    for rid, times in sorted(warm_times.items()):
        logger.info(
            "replica %d: warmed %d bucket programs (max_batch=%d): %s",
            rid, len(times), pool.primary.max_batch,
            " ".join(f"b{b}={t:.2f}s" for b, t in sorted(times.items())),
        )
    server, _batcher = start_server(cfg, pool=pool, metrics=metrics)

    def _terminate(signum, frame):
        # shutdown() must not run on the serve_forever thread (it blocks on
        # the loop stopping); hand the drain to a helper thread and return
        # from the handler immediately
        logger.info("signal %d: draining...", signum)
        threading.Thread(
            target=shutdown_gracefully, args=(server,), daemon=True
        ).start()

    previous = {
        sig: signal.signal(sig, _terminate)
        for sig in (signal.SIGTERM, signal.SIGINT)
    }
    try:
        logger.info(
            "serving embeddings on http://%s:%d (POST /v1/embed)",
            *server.server_address[:2],
        )
        _write_ready_file(cfg, server)
        server.serve_forever(poll_interval=0.1)
    finally:
        for sig, handler in previous.items():
            signal.signal(sig, handler)
        server.server_close()  # joins in-flight handler threads
    logger.info("drained; exiting 0")
    return 0


def start_server(
    cfg, *, engine=None, metrics=None, pool=None, index=None, corpus_store=None
) -> tuple:
    """Construct (EmbedServer, DynamicBatcher) bound to ``serve.host:port``
    without entering the accept loop — the embeddable/testable core of
    :func:`run_server`. Caller runs ``serve_forever`` and later
    :func:`shutdown_gracefully`.

    ``pool`` (a :class:`~simclr_tpu.serve.replica.ReplicaPool`) is the
    replicated path; a bare ``engine`` is wrapped into a pool of one, so
    every server runs the same per-replica worker machinery. ``index``
    (a :class:`~simclr_tpu.serve.retrieval.NeighborIndex`) enables
    ``/v1/neighbors``; when None it is built from ``serve.corpus`` if set —
    through a :class:`~simclr_tpu.serve.retrieval.MutableCorpus`, so a
    file-configured corpus accepts ``/v1/corpus/*`` mutations out of the
    box. An explicit ``corpus_store`` supplies both the index and the
    mutation path.
    """
    from simclr_tpu.serve.batcher import DynamicBatcher
    from simclr_tpu.serve.metrics import ServeMetrics
    from simclr_tpu.serve.replica import ReplicaPool

    metrics = metrics if metrics is not None else ServeMetrics()
    if pool is None:
        if engine is not None:
            pool = ReplicaPool([engine])
        else:
            pool = ReplicaPool.from_checkpoint(cfg, metrics=metrics)
    metrics.attach_pool(pool)
    primary = pool.primary
    batcher = DynamicBatcher(
        pool=pool,
        max_batch=primary.max_batch,
        max_delay_ms=float(cfg.serve.max_delay_ms),
        queue_depth=int(cfg.serve.queue_depth),
        metrics=metrics,
    )
    if index is None and corpus_store is not None:
        index = corpus_store.index
    if index is None:
        corpus = cfg.select("serve.corpus")
        if corpus:
            from simclr_tpu.serve.retrieval import MutableCorpus

            corpus_store = MutableCorpus.from_file(
                str(corpus),
                metrics=metrics,
                metric=str(cfg.select("serve.neighbors_metric", "dot")),
                max_queries=primary.max_batch,
                sentry=primary.sentry,
                corpus_dtype=str(cfg.select("serve.corpus_dtype", "fp32")),
                ann_cells=int(cfg.select("serve.ann_cells", 0) or 0),
                ann_probe=int(cfg.select("serve.ann_probe", 1) or 1),
            )
            index = corpus_store.index
            scan = (
                f"ivf {index.ann_cells}x{index.cell_rows} probe {index.ann_probe}"
                if index.ann_cells else "exact"
            )
            logger.info(
                "retrieval corpus resident: %d rows x %d dims over %d shards "
                "(%s, %s, %.1f MiB HBM)",
                index.n, index.d, index.n_shards, index.dtype, scan,
                index.hbm_state()["corpus_hbm_bytes"] / 2**20,
            )
    requests_log = cfg.select("serve.requests_log")
    recorder = TraceRecorder(
        sample_rate=float(cfg.select("serve.trace_sample_rate", 0.0) or 0.0),
        path=str(requests_log) if requests_log else None,
    )
    server = EmbedServer(
        (str(cfg.serve.host), int(cfg.serve.port)),
        primary,
        batcher,
        metrics,
        request_timeout_s=float(cfg.serve.request_timeout_s),
        recorder=recorder,
        pool=pool,
        index=index,
        neighbors_k_default=int(cfg.select("serve.neighbors_k", 10)),
        corpus_store=corpus_store,
    )
    if corpus_store is not None:
        # mutations committed from here on swap this server's index
        corpus_store.server = server
    return server, batcher


def shutdown_gracefully(server: EmbedServer, drain_timeout_s: float = 30.0) -> None:
    """Drain-then-stop, idempotent: 503 new work, answer everything
    accepted, stop the accept loop."""
    if server.draining.is_set():
        return
    server.draining.set()
    server.batcher.close(drain=True, timeout=drain_timeout_s)
    server.shutdown()


def _write_ready_file(cfg, server: EmbedServer) -> None:
    """Publish the bound address (``serve.ready_file``) — how orchestration
    and the SIGTERM e2e test learn an ephemeral port (``serve.port=0``)."""
    import os

    path = cfg.select("serve.ready_file")
    if not path:
        return
    from simclr_tpu.utils.ioutil import atomic_write

    host, port = server.server_address[:2]
    atomic_write(
        str(path),
        lambda f: json.dump(
            {"host": host, "port": port, "pid": os.getpid()}, f
        ),
    )
