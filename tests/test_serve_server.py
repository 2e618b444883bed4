"""HTTP server e2e: live in-process server + real ``python -m simclr_tpu.serve``.

In-process tests bind an :class:`EmbedServer` on an ephemeral port around a
TinyContrastive engine and drive it with real HTTP clients — JSON parsing,
dynamic batching, metrics, and the drain contract all under test. The
subprocess test is the full acceptance path: synthetic resnet18 checkpoint
-> ``python -m simclr_tpu.serve`` -> concurrent clients -> SIGTERM -> every
in-flight request answered -> exit 0.

Bitwise contract through HTTP: embeddings are float32 serialized as JSON
floats (exact shortest-repr doubles), so a client reading them back into
float32 must recover the engine's output bit-for-bit. Because coalescing
decides which bucket shape a request runs at, the reference is computed at
every candidate bucket and the served rows must match one of them (row
values are position- and content-independent in the frozen forward; only
the program's batch shape matters).
"""

import http.client
import json
import os
import signal
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_tpu.config import load_config
from simclr_tpu.serve.engine import EmbedEngine
from simclr_tpu.serve.metrics import ServeMetrics
from simclr_tpu.serve.server import shutdown_gracefully, start_server
from tests.helpers import TinyContrastive, random_images

pytestmark = pytest.mark.serve

MAX_BATCH = 8


def serve_cfg(**serve_overrides):
    base = {
        "serve.port": 0,
        "serve.max_batch": MAX_BATCH,
        "serve.max_delay_ms": 60,
        "serve.queue_depth": 32,
        "experiment.target_dir": "/nonexistent-unused",
    }
    base.update(serve_overrides)
    return load_config("serve", overrides=[f"{k}={v}" for k, v in base.items()])


class LiveServer:
    def __init__(self, server, batcher, engine, metrics):
        self.server = server
        self.batcher = batcher
        self.engine = engine
        self.metrics = metrics
        self.port = server.server_address[1]
        self.thread = threading.Thread(
            target=server.serve_forever, kwargs={"poll_interval": 0.05}, daemon=True
        )
        self.thread.start()

    def request(self, method, path, body=None, timeout=30, headers=None):
        conn = http.client.HTTPConnection("127.0.0.1", self.port, timeout=timeout)
        try:
            payload = None if body is None else json.dumps(body).encode()
            hdrs = {"Content-Type": "application/json"}
            if headers:
                hdrs.update(headers)
            conn.request(method, path, payload, hdrs)
            r = conn.getresponse()
            return r.status, r.read(), dict(r.getheaders())
        finally:
            conn.close()

    def embed(self, images: np.ndarray, timeout=30):
        status, body, _ = self.request(
            "POST", "/v1/embed", {"instances": np.asarray(images).tolist()},
            timeout=timeout,
        )
        payload = json.loads(body)
        if status == 200:
            return status, np.asarray(payload["embeddings"], np.float32)
        return status, payload


@pytest.fixture
def live():
    model = TinyContrastive(bn_cross_replica_axis=None)
    variables = jax.tree.map(
        np.asarray, model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)))
    )
    metrics = ServeMetrics()
    engine = EmbedEngine(model, variables, max_batch=MAX_BATCH, metrics=metrics)
    server, batcher = start_server(serve_cfg(), engine=engine, metrics=metrics)
    ls = LiveServer(server, batcher, engine, metrics)
    yield ls
    shutdown_gracefully(server, drain_timeout_s=10)
    ls.thread.join(timeout=10)
    server.server_close()


def bucket_references(engine, images: np.ndarray) -> list[np.ndarray]:
    """The engine's forward of ``images`` at every candidate bucket shape —
    whichever bucket coalescing picked, the served rows equal one of these."""
    n = images.shape[0]
    refs = []
    for b in engine.buckets:
        if b < n:
            continue
        padded = np.concatenate(
            [images, np.zeros((b - n, *engine.input_shape), np.uint8)]
        )
        refs.append(
            np.asarray(engine._fwd(engine._params, engine._batch_stats, padded))[:n]
        )
    return refs


def metric_value(text: str, name: str) -> float:
    for line in text.splitlines():
        if line.startswith(f"{name} "):
            return float(line.split()[1])
    raise AssertionError(f"metric {name} not found in exposition:\n{text}")


class TestEndpoints:
    def test_healthz_reports_serving_surface(self, live):
        status, body, _ = live.request("GET", "/healthz")
        assert status == 200
        payload = json.loads(body)
        assert payload["status"] == "ok"
        assert payload["buckets"] == [1, 2, 4, 8]
        assert payload["max_batch"] == MAX_BATCH
        assert payload["feature_dim"] == 16

    def test_healthz_names_the_device(self, live):
        """Whoever reads a number off this server can read where it ran."""
        import jax

        payload = json.loads(live.request("GET", "/healthz")[1])
        assert payload["device"] == {
            "platform": "cpu",
            "kind": jax.devices()[0].device_kind,
            "count": jax.device_count(),
        }
        assert set(payload["compile_cache"]) == {"dir", "hits", "misses"}

    def test_metrics_exposition_parses(self, live):
        live.embed(random_images(2))
        status, body, headers = live.request("GET", "/metrics")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        text = body.decode()
        assert metric_value(text, "simclr_serve_requests_total") == 1
        assert metric_value(text, "simclr_serve_rows_total") == 2
        assert metric_value(text, "simclr_serve_batches_total") == 1
        assert metric_value(text, "simclr_serve_queue_depth") == 0

    def test_unknown_path_404(self, live):
        assert live.request("GET", "/nope")[0] == 404
        assert live.request("POST", "/nope")[0] == 404


class TestEmbed:
    def test_roundtrip_is_bitwise_exact(self, live):
        images = random_images(3, seed=1)
        status, got = live.embed(images)
        assert status == 200
        assert got.shape == (3, 16)
        # a lone request runs at bucket_for(3) == 4 — the first candidate
        # bucket >= 3; JSON must not have perturbed a single bit
        np.testing.assert_array_equal(got, bucket_references(live.engine, images)[0])

    def test_concurrent_requests_coalesce_and_stay_exact(self, live):
        n_clients, rows_each = 6, 2
        images = random_images(n_clients * rows_each, seed=2)
        deadline = time.monotonic() + 30
        while True:
            barrier = threading.Barrier(n_clients)
            results: dict[int, tuple] = {}

            def client(i):
                chunk = images[i * rows_each : (i + 1) * rows_each]
                barrier.wait()
                results[i] = live.embed(chunk)

            threads = [
                threading.Thread(target=client, args=(i,)) for i in range(n_clients)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            for i in range(n_clients):
                status, got = results[i]
                assert status == 200, got
                chunk = images[i * rows_each : (i + 1) * rows_each]
                refs = bucket_references(live.engine, chunk)
                assert any(np.array_equal(got, r) for r in refs), (
                    f"client {i}: served rows match no candidate bucket program"
                )
            # the acceptance number: concurrent load must actually coalesce
            if live.metrics.avg_batch_fill() > 1.0:
                break
            assert time.monotonic() < deadline, (
                "avg_batch_fill never exceeded 1.0 under concurrent load"
            )
        text = live.request("GET", "/metrics")[1].decode()
        assert metric_value(text, "simclr_serve_avg_batch_fill") > 1.0


class TestErrorStatuses:
    def test_malformed_bodies_400(self, live):
        status, body, _ = live.request("POST", "/v1/embed")
        assert status == 400  # no body
        conn = http.client.HTTPConnection("127.0.0.1", live.port, timeout=10)
        conn.request("POST", "/v1/embed", b"{not json", {"Content-Length": "9"})
        assert conn.getresponse().status == 400
        conn.close()
        assert live.request("POST", "/v1/embed", {"wrong": []})[0] == 400
        ragged = {"instances": [[1, 2], [3]]}
        assert live.request("POST", "/v1/embed", ragged)[0] == 400

    def test_wrong_shape_and_range_400(self, live):
        bad_shape = {"instances": np.zeros((1, 16, 16, 3), int).tolist()}
        assert live.request("POST", "/v1/embed", bad_shape)[0] == 400
        floats = {"instances": (np.zeros((1, 32, 32, 3)) + 0.5).tolist()}
        assert live.request("POST", "/v1/embed", floats)[0] == 400
        out_of_range = {"instances": (np.zeros((1, 32, 32, 3), int) + 300).tolist()}
        assert live.request("POST", "/v1/embed", out_of_range)[0] == 400
        empty = {"instances": np.zeros((0, 32, 32, 3), int).tolist()}
        assert live.request("POST", "/v1/embed", empty)[0] == 400

    def test_oversize_request_413(self, live):
        status, payload = live.embed(random_images(MAX_BATCH + 1))
        assert status == 413
        assert "max_batch" in payload["error"]

    def test_queue_full_429_with_retry_after(self, live):
        from simclr_tpu.serve.batcher import BackpressureError

        class FullQueue:
            def submit(self, images, trace=None):
                raise BackpressureError("request queue full (test)")

        real = live.server.batcher
        live.server.batcher = FullQueue()
        try:
            status, body, headers = live.request(
                "POST", "/v1/embed",
                {"instances": random_images(1).tolist()},
            )
            assert status == 429
            assert headers["Retry-After"] == "1"
        finally:
            live.server.batcher = real

    def test_draining_503(self, live):
        live.server.draining.set()
        try:
            assert live.request("GET", "/healthz")[0] == 503
            status, payload = live.embed(random_images(1))
            assert status == 503
        finally:
            live.server.draining.clear()


class TestRequestTracing:
    def test_client_request_id_echoed(self, live):
        status, _, headers = live.request(
            "POST", "/v1/embed", {"instances": random_images(1).tolist()},
            headers={"X-Request-Id": "my-req-1"},
        )
        assert status == 200
        assert headers["X-Request-Id"] == "my-req-1"

    def test_generated_request_id_when_absent(self, live):
        ids = set()
        for _ in range(2):
            status, _, headers = live.request(
                "POST", "/v1/embed", {"instances": random_images(1).tolist()}
            )
            assert status == 200
            rid = headers["X-Request-Id"]
            assert len(rid) >= 8
            ids.add(rid)
        assert len(ids) == 2, "generated ids must differ across requests"

    def test_request_id_echoed_on_errors(self, live):
        # a failed request is exactly the one the client wants to report by
        # id — error responses must carry the header too
        status, _, headers = live.request(
            "POST", "/v1/embed", {"wrong": []},
            headers={"X-Request-Id": "err-1"},
        )
        assert status == 400
        assert headers["X-Request-Id"] == "err-1"

    def test_debug_slow_serves_span_breakdown(self, live):
        for i in range(3):
            status, _ = live.embed(random_images(2, seed=i))
            assert status == 200
        status, body, _ = live.request("GET", "/debug/slow")
        assert status == 200
        slowest = json.loads(body)["slowest"]
        assert len(slowest) == 3
        totals = [r["total_ms"] for r in slowest]
        assert totals == sorted(totals, reverse=True)
        # every stage of the request's life is accounted for
        names = {s["name"] for s in slowest[0]["spans"]}
        assert {
            "queue_wait", "coalesce", "pad", "device_compute", "serialize"
        } <= names
        assert all(r["request_id"] for r in slowest)

    def test_requests_log_sidecar_sampling(self, tmp_path):
        sidecar = tmp_path / "requests.jsonl"
        model = TinyContrastive(bn_cross_replica_axis=None)
        variables = jax.tree.map(
            np.asarray, model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)))
        )
        metrics = ServeMetrics()
        engine = EmbedEngine(
            model, variables, max_batch=MAX_BATCH, metrics=metrics
        )
        server, batcher = start_server(
            serve_cfg(**{
                "serve.trace_sample_rate": 1.0,
                "serve.requests_log": str(sidecar),
            }),
            engine=engine, metrics=metrics,
        )
        ls = LiveServer(server, batcher, engine, metrics)
        try:
            for i in range(2):
                status, _ = ls.embed(random_images(1, seed=i))
                assert status == 200
            lines = [json.loads(line) for line in open(sidecar)]
            assert len(lines) == 2
            assert all(l["total_ms"] > 0 and l["spans"] for l in lines)
        finally:
            shutdown_gracefully(server, drain_timeout_s=10)
            ls.thread.join(timeout=10)
            server.server_close()


class TestGracefulShutdown:
    def test_inflight_requests_answered_before_stop(self):
        model = TinyContrastive(bn_cross_replica_axis=None)
        variables = jax.tree.map(
            np.asarray, model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)))
        )
        metrics = ServeMetrics()
        engine = EmbedEngine(model, variables, max_batch=MAX_BATCH, metrics=metrics)
        real_embed = engine.embed
        engine.embed = lambda images: (time.sleep(0.5), real_embed(images))[1]
        server, _ = start_server(serve_cfg(), engine=engine, metrics=metrics)
        ls = LiveServer(server, None, engine, metrics)
        try:
            images = random_images(2, seed=5)
            result = {}

            def client():
                result["r"] = ls.embed(images, timeout=30)

            t = threading.Thread(target=client)
            t.start()
            time.sleep(0.15)  # request now accepted / in the slow forward
            shutdown_gracefully(server, drain_timeout_s=10)
            t.join(timeout=30)
            status, got = result["r"]
            assert status == 200  # drained, not dropped
            assert any(np.array_equal(got, r) for r in bucket_references(engine, images))
            ls.thread.join(timeout=10)
            assert not ls.thread.is_alive()  # accept loop exited
        finally:
            server.server_close()


class TestSubprocessSigterm:
    """The full acceptance path through ``python -m simclr_tpu.serve``."""

    def test_serve_main_drains_on_sigterm_and_exits_zero(self, tmp_path):
        from simclr_tpu.eval import build_eval_model
        from simclr_tpu.utils.checkpoint import save_checkpoint

        ckpt = str(tmp_path / "epoch=1-m")
        ready = str(tmp_path / "ready.json")
        cfg = load_config(
            "serve", overrides=[f"serve.checkpoint={ckpt}", "serve.max_batch=4"]
        )
        model = build_eval_model(cfg)
        variables = jax.tree.map(
            np.asarray,
            model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3), jnp.float32)),
        )
        save_checkpoint(ckpt, variables)

        env = dict(os.environ, JAX_PLATFORMS="cpu")
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "simclr_tpu.serve",
                f"serve.checkpoint={ckpt}", "serve.port=0",
                f"serve.ready_file={ready}", "serve.max_batch=4",
                "serve.max_delay_ms=300", "serve.queue_depth=16",
                # single replica: this test is the drain contract; the
                # replicated drain is TestMultiReplicaSigterm (the default
                # replicas=-1 would warm one engine per virtual device here)
                "serve.replicas=1",
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        try:
            deadline = time.monotonic() + 180
            while not os.path.exists(ready):
                assert proc.poll() is None, (
                    f"server died before ready:\n"
                    f"{proc.stdout.read().decode(errors='replace')}"
                )
                assert time.monotonic() < deadline, "server never became ready"
                time.sleep(0.2)
            with open(ready) as f:
                addr = json.load(f)
            port = addr["port"]
            assert addr["pid"] == proc.pid

            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("GET", "/healthz")
            health = json.loads(conn.getresponse().read())
            conn.close()
            assert health["status"] == "ok"
            assert health["checkpoint"] == ckpt
            assert health["buckets"] == [1, 2, 4]

            # in-flight work: with a 300ms coalescing window these requests
            # are still unanswered when SIGTERM lands — the drain contract
            # says they complete with 200, never dropped
            images = random_images(4, seed=11)
            results = {}

            def client(i):
                c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
                body = json.dumps(
                    {"instances": images[i * 2 : (i + 1) * 2].tolist()}
                )
                c.request(
                    "POST", "/v1/embed", body, {"Content-Type": "application/json"}
                )
                r = c.getresponse()
                results[i] = (r.status, json.loads(r.read()))
                c.close()

            threads = [threading.Thread(target=client, args=(i,)) for i in (0, 1)]
            for t in threads:
                t.start()
            time.sleep(0.1)
            proc.send_signal(signal.SIGTERM)
            for t in threads:
                t.join(timeout=60)

            for i in (0, 1):
                status, payload = results[i]
                assert status == 200, payload
                got = np.asarray(payload["embeddings"], np.float32)
                assert got.shape == (2, 512)  # resnet18 encoder width
                assert np.isfinite(got).all()

            assert proc.wait(timeout=60) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
