"""Replica fan-out (serve/replica.py + batcher pool mode + HTTP surface).

The scale-out contracts: N engines over N distinct devices each running
the identical single-device program (exact weights => responses bitwise
identical to the single-replica path); work-stealing off the one shared
queue spreads load across replicas and stamps every answered future with
its replica id (X-Served-By); per-engine warmup gating means replica
warmups NEVER fire the serve recompile alarm while a post-warmup cold
bucket on ANY replica still does.

Heavy end-to-end claims — >= 2x aggregate throughput in serve_bench
output and the multi-replica SIGTERM drain through ``python -m
simclr_tpu.serve`` — run as subprocesses and are marked slow.
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

from simclr_tpu.obs.compile import CompileSentry
from simclr_tpu.serve.batcher import DynamicBatcher
from simclr_tpu.serve.engine import EmbedEngine
from simclr_tpu.serve.metrics import ServeMetrics
from simclr_tpu.serve.replica import ReplicaPool, ReplicaState
from tests.helpers import TinyContrastive, random_images

pytestmark = pytest.mark.serve

MAX_BATCH = 8
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_model_and_variables():
    model = TinyContrastive(bn_cross_replica_axis=None)
    variables = jax.tree.map(
        np.asarray, model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)))
    )
    return model, variables


@pytest.fixture(scope="module")
def pool2():
    """One shared 2-replica exact-weights pool (warmup is the slow part)."""
    model, variables = tiny_model_and_variables()
    pool = ReplicaPool.from_model(model, variables, replicas=2, max_batch=MAX_BATCH)
    pool._test_variables = variables
    pool._test_model = model
    return pool


class TestPoolConstruction:
    def test_one_engine_per_distinct_device(self, pool2):
        assert pool2.size == 2
        devices = [rep.engine.device for rep in pool2.replicas]
        assert None not in devices
        assert len(set(devices)) == 2
        assert pool2.primary is pool2.replicas[0].engine
        # every replica warmed every bucket with its own jit cache
        for rep in pool2.replicas:
            assert rep.engine.warm_state() == [1, 2, 4, 8]
        # weights actually live on the pinned device per replica
        for rep in pool2.replicas:
            leaf = jax.tree.leaves(rep.engine._params)[0]
            assert leaf.sharding.device_set == {rep.engine.device}

    def test_replicas_must_fit_local_devices(self):
        from simclr_tpu.parallel.mesh import serve_replica_devices

        assert len(serve_replica_devices(-1)) == len(jax.local_devices())
        assert len(serve_replica_devices(2)) == 2
        with pytest.raises(ValueError, match="replicas"):
            serve_replica_devices(len(jax.local_devices()) + 1)
        with pytest.raises(ValueError):
            ReplicaPool([])

    def test_state_snapshot_shape(self, pool2):
        states = pool2.state()
        assert [s["replica"] for s in states] == [0, 1]
        for s in states:
            assert s["weights"] == "exact"
            assert s["warmed_buckets"] == [1, 2, 4, 8]
            assert s["in_flight"] == 0


class TestBitwiseParity:
    def test_pool_replicas_match_single_engine_bitwise(self, pool2):
        """The acceptance bit: on exact weights every replica's forward is
        byte-for-byte the single-engine (single-replica path) forward."""
        single = EmbedEngine(
            pool2._test_model, pool2._test_variables, max_batch=MAX_BATCH
        )
        for n in (1, 3, MAX_BATCH):  # exact bucket and padded shapes
            images = random_images(n, seed=n)
            ref = single.embed(images)
            for rep in pool2.replicas:
                np.testing.assert_array_equal(rep.engine.embed(images), ref)


class _GatedEngine:
    """A fake engine whose embed blocks until released — makes the shared
    queue's work-stealing deterministic: while one worker is held inside
    embed, the next request MUST land on the other replica."""

    max_batch = MAX_BATCH

    def __init__(self, dim=4):
        self.started = threading.Event()
        self.release = threading.Event()
        self.calls = 0
        self.last_spans = ()

    def embed(self, images):
        self.calls += 1
        self.started.set()
        assert self.release.wait(timeout=30)
        t = time.perf_counter()
        self.last_spans = (("device_compute", t, t + 0.001),)
        return np.zeros((images.shape[0], 4), np.float32)


class TestPoolDispatch:
    def test_work_steals_across_replicas_and_stamps_replica_id(self):
        engines = [_GatedEngine(), _GatedEngine()]
        pool = ReplicaPool(engines)
        batcher = DynamicBatcher(
            pool=pool, max_batch=MAX_BATCH, max_delay_ms=0, queue_depth=16
        )
        try:
            f1 = batcher.submit(random_images(1, seed=0))
            # some worker is now held inside embed; the other must steal
            assert any(e.started.wait(timeout=30) for e in engines)
            f2 = batcher.submit(random_images(1, seed=1))
            deadline = time.monotonic() + 60
            while not all(e.started.is_set() for e in engines):
                assert time.monotonic() < deadline, (
                    "second request never reached the idle replica: "
                    f"calls={[e.calls for e in engines]}"
                )
                time.sleep(0.01)
            for e in engines:
                e.release.set()
            out1, out2 = f1.result(timeout=10), f2.result(timeout=10)
            assert out1.shape == out2.shape == (1, 4)
            # both dispatches stamped their replica — and they differ
            assert {f1.replica_id, f2.replica_id} == {0, 1}
            assert [rep.batches for rep in pool.replicas] == [1, 1]
            assert all(rep.in_flight == 0 for rep in pool.replicas)
        finally:
            for e in engines:
                e.release.set()
            batcher.close(timeout=10)

    def test_engine_failure_clears_in_flight_and_relays(self):
        class Boom:
            max_batch = MAX_BATCH
            last_spans = ()

            def embed(self, images):
                raise RuntimeError("chip fell over")

        pool = ReplicaPool([Boom()])
        batcher = DynamicBatcher(pool=pool, max_batch=MAX_BATCH, max_delay_ms=0)
        try:
            f = batcher.submit(random_images(1))
            with pytest.raises(RuntimeError, match="chip fell over"):
                f.result(timeout=10)
            assert pool.replicas[0].in_flight == 0
        finally:
            batcher.close(timeout=10)


class TestSentryFanOut:
    def test_replica_warmups_never_alarm_but_cold_bucket_on_any_replica_does(self):
        """The serve gating contract under fan-out: N warmups against one
        shared sentry/metrics are all warm=False (no alarm), while a
        post-warmup cold bucket on ANY replica — here replica 1, with
        replica 0 fully warm — still raises the recompile alarm."""
        model, variables = tiny_model_and_variables()
        metrics = ServeMetrics()
        sentry = CompileSentry()
        pool = ReplicaPool.from_model(
            model, variables, replicas=2, max_batch=4,
            metrics=metrics, sentry=sentry,
        )
        # 2 replicas x 3 buckets compiled, every one during ITS replica's
        # warmup: zero alarms, and per-replica sentry attribution kept
        assert sentry.compiles == 6
        assert sentry.recompile_alarms == 0
        assert metrics.recompile_alarms_total.value == 0
        names = {r["name"] for r in sentry.records}
        assert names == {
            f"serve_r{rid}_bucket_{b}" for rid in (0, 1) for b in (1, 2, 4)
        }
        # replica 0 serving warm stays quiet
        pool.replicas[0].engine.embed(random_images(3, seed=0))
        assert metrics.recompile_alarms_total.value == 0
        # simulate a post-warmup cold bucket on replica 1 only
        pool.replicas[1].engine._warm.discard(4)
        pool.replicas[1].engine.embed(random_images(3, seed=1))
        assert metrics.recompile_alarms_total.value == 1
        assert sentry.recompile_alarms == 1


class TestObservability:
    def test_metrics_render_labels_every_replica(self, pool2):
        metrics = ServeMetrics()
        metrics.attach_pool(pool2)
        text = metrics.render()
        for rid in (0, 1):
            for gauge in (
                "simclr_serve_replica_batch_fill",
                "simclr_serve_replica_in_flight",
                "simclr_serve_replica_compute_ms",
                "simclr_serve_replica_weight_hbm_bytes",
                "simclr_serve_replica_weight_hbm_analytic_bytes",
            ):
                assert f'{gauge}{{replica="{rid}"}}' in text
        # exact weights: measured resident bytes match the analytic model
        for rep in pool2.replicas:
            assert (
                rep.engine.weight_hbm_bytes()
                == rep.engine.weight_hbm_analytic_bytes()
                > 0
            )

    def test_live_server_healthz_and_served_by_header(self, pool2):
        from simclr_tpu.serve.server import shutdown_gracefully, start_server
        from tests.test_serve_server import LiveServer, serve_cfg

        metrics = ServeMetrics()
        server, batcher = start_server(serve_cfg(), pool=pool2, metrics=metrics)
        ls = LiveServer(server, batcher, pool2.primary, metrics)
        try:
            status, body, _ = ls.request("GET", "/healthz")
            assert status == 200
            replicas = json.loads(body)["replicas"]
            assert [r["replica"] for r in replicas] == [0, 1]
            assert all(r["warmed_buckets"] == [1, 2, 4, 8] for r in replicas)
            status, _, headers = ls.request(
                "POST", "/v1/embed",
                {"instances": random_images(2, seed=3).tolist()},
            )
            assert status == 200
            assert headers["X-Served-By"] in ("0", "1")
        finally:
            shutdown_gracefully(server, drain_timeout_s=10)
            ls.thread.join(timeout=10)
            server.server_close()


@pytest.mark.slow
class TestAggregateScaling:
    """The acceptance number, measured by scripts/serve_bench.py: N
    synthetic replicas behind the REAL pool +
    batcher + HTTP stack must at least double single-replica throughput."""

    def test_serve_bench_reports_2x_speedup_at_4_replicas(self):
        env = dict(
            os.environ,
            SERVE_BENCH_SYNTH_MS="4",
            SERVE_BENCH_REPLICAS="1,4",
            SERVE_BENCH_CONCURRENCY="16",
            SERVE_BENCH_DURATION_S="3",
            SERVE_BENCH_BUDGET_S="120",
        )
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts", "serve_bench.py")],
            capture_output=True, text=True, timeout=300, env=env, cwd=REPO,
        )
        assert r.returncode == 0, r.stderr
        payload = json.loads(r.stdout.strip().splitlines()[-1])
        assert "error" not in payload
        assert payload["recompile_alarms"] == 0
        scaling = payload["scaling"]
        assert scaling["replicas"] == 4
        assert scaling["speedup"] >= 2.0, payload
        assert payload["p99_ms"] > 0


@pytest.mark.slow
class TestMultiReplicaSigterm:
    """Full acceptance path with fan-out: ``python -m simclr_tpu.serve``
    on 2 fake devices / 2 replicas, both replicas proven serving, then
    SIGTERM with requests in flight -> every request answered 200 across
    both replicas -> exit 0."""

    def test_drains_in_flight_across_two_replicas_and_exits_zero(self, tmp_path):
        from simclr_tpu.config import load_config
        from simclr_tpu.eval import build_eval_model
        from simclr_tpu.utils.checkpoint import save_checkpoint

        ckpt = str(tmp_path / "epoch=1-m")
        ready = str(tmp_path / "ready.json")
        cfg = load_config(
            "serve", overrides=[f"serve.checkpoint={ckpt}", "serve.max_batch=2"]
        )
        model = build_eval_model(cfg)
        variables = jax.tree.map(
            np.asarray,
            model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3), jnp.float32)),
        )
        save_checkpoint(ckpt, variables)

        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS="--xla_force_host_platform_device_count=2",
        )
        proc = subprocess.Popen(
            [
                sys.executable, "-m", "simclr_tpu.serve",
                f"serve.checkpoint={ckpt}", "serve.port=0",
                f"serve.ready_file={ready}", "serve.max_batch=2",
                "serve.replicas=2", "serve.max_delay_ms=0",
                "serve.queue_depth=16",
            ],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
        )
        try:
            deadline = time.monotonic() + 240
            while not os.path.exists(ready):
                assert proc.poll() is None, (
                    f"server died before ready:\n"
                    f"{proc.stdout.read().decode(errors='replace')}"
                )
                assert time.monotonic() < deadline, "server never became ready"
                time.sleep(0.2)
            with open(ready) as f:
                port = json.load(f)["port"]

            def get_json(path):
                c = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
                c.request("GET", path)
                out = json.loads(c.getresponse().read())
                c.close()
                return out

            health = get_json("/healthz")
            assert [r["replica"] for r in health["replicas"]] == [0, 1]

            served_by = set()
            results = {}

            def client(i, images):
                c = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
                c.request(
                    "POST", "/v1/embed",
                    json.dumps({"instances": images.tolist()}),
                    {"Content-Type": "application/json"},
                )
                r = c.getresponse()
                results[i] = (r.status, json.loads(r.read()),
                              r.getheader("X-Served-By"))
                c.close()

            # full-bucket concurrent rounds: with max_batch=2 no worker can
            # coalesce two of these, so concurrent requests must spread —
            # loop until BOTH replicas have provably served
            images = random_images(2, seed=7)
            round_no = 0
            while served_by != {"0", "1"}:
                assert time.monotonic() < deadline, (
                    f"both replicas never served; saw {served_by}"
                )
                ids = [f"warm-{round_no}-{j}" for j in range(4)]
                threads = [
                    threading.Thread(target=client, args=(i, images))
                    for i in ids
                ]
                for t in threads:
                    t.start()
                for t in threads:
                    t.join(timeout=60)
                for i in ids:
                    status, payload, rep = results[i]
                    assert status == 200, payload
                    served_by.add(rep)
                round_no += 1

            # the drain contract under fan-out: in-flight on both workers
            final = [f"final-{j}" for j in range(4)]
            threads = [
                threading.Thread(target=client, args=(i, images)) for i in final
            ]
            for t in threads:
                t.start()
            time.sleep(0.1)
            proc.send_signal(signal.SIGTERM)
            for t in threads:
                t.join(timeout=60)
            for i in final:
                status, payload, rep = results[i]
                assert status == 200, payload
                got = np.asarray(payload["embeddings"], np.float32)
                assert got.shape == (2, 512)
                assert np.isfinite(got).all()
                assert rep in ("0", "1")
            assert proc.wait(timeout=60) == 0
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
