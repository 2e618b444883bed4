"""Co-scheduler contracts (simclr_tpu/coscheduler/): hot-reload + policy.

The unit/chaos half of the continuous train+serve subsystem:

  * **zero-recompile swap pin** — a verified checkpoint hot-swaps into a
    warmed replica pool with ``simclr_serve_recompile_alarms_total`` still
    0, and the pool then serves bitwise what a fresh engine built from the
    new weights serves;
  * **chaos corruption** — a checkpoint corrupted mid-swap (the
    ``supervisor/faults.py`` injector) is rejected exactly once, the prior
    generation keeps serving bitwise-unchanged on EVERY replica, and a
    later good checkpoint still swaps;
  * **generation-consistent corpus** — each committed generation republishes
    a /v1/neighbors index tagged with the same generation number;
  * **reallocation policy** — pure hysteresis state machine: sustain,
    band-reset, cooldown, cancel;
  * plus the cosched config surface, the run-report serve section, the
    fleet auto-discovery of co-scheduled serve replicas, and the CLI's
    config-error exit code.

The full-lifecycle e2e (2-process CPU dryrun with one shrink/grow-back
cycle) lives in scripts/cosched_smoke.py.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from simclr_tpu.config import (
    ConfigError,
    check_cosched_conf,
    check_serve_conf,
    load_config,
)
from simclr_tpu.coscheduler.policy import (
    RELEASE,
    SHRINK,
    ReallocationPolicy,
    pressure_of,
)
from simclr_tpu.coscheduler.reload import ReloadManager
from simclr_tpu.obs.compile import CompileSentry
from simclr_tpu.obs.events import EventLog, events_path, read_events
from simclr_tpu.serve.engine import EmbedEngine
from simclr_tpu.serve.metrics import ServeMetrics
from simclr_tpu.serve.replica import ReplicaPool
from simclr_tpu.serve.retrieval import NeighborIndex
from simclr_tpu.utils.checkpoint import (
    checkpoint_digest,
    digest_path,
    restore_checkpoint,
    save_checkpoint,
)
from tests.helpers import TinyContrastive, random_images

pytestmark = pytest.mark.serve

MAX_BATCH = 4


@pytest.fixture(scope="module")
def tiny():
    """One model with two distinct weight generations (host numpy)."""
    model = TinyContrastive(bn_cross_replica_axis=None)
    zeros = jnp.zeros((2, 32, 32, 3))
    v0 = jax.tree.map(np.asarray, model.init(jax.random.key(0), zeros))
    v1 = jax.tree.map(np.asarray, model.init(jax.random.key(1), zeros))
    return model, v0, v1


def _pool(model, variables, *, replicas=1, metrics=None, sentry=None):
    return ReplicaPool.from_model(
        model, variables, replicas=replicas, max_batch=MAX_BATCH,
        metrics=metrics, sentry=sentry,
    )


def _save_ckpt(tmp_path, epoch, variables):
    path = str(tmp_path / f"epoch={epoch}-model")
    save_checkpoint(path, variables)
    return path


def _restore(path):
    return restore_checkpoint(path)


# ---------------------------------------------------------------------------
# hot-reload protocol (coscheduler/reload.py)
# ---------------------------------------------------------------------------


class TestHotReload:
    def test_swap_is_zero_recompile_and_bitwise_exact(self, tmp_path, tiny):
        model, v0, v1 = tiny
        metrics, sentry = ServeMetrics(), CompileSentry()
        pool = _pool(model, v0, replicas=2, metrics=metrics, sentry=sentry)
        mgr = ReloadManager(
            pool, save_dir=str(tmp_path), metrics=metrics,
            events=EventLog(str(tmp_path)), load_fn=_restore,
        )
        assert mgr.generation == 0 and mgr._staleness() == 0.0

        ckpt = _save_ckpt(tmp_path, 1, v1)
        assert mgr.poll_once() is True
        assert pool.weights_generation == 1
        assert mgr.swapped_epoch == 1 and mgr.swap_count == 1

        # post-swap traffic across every warm bucket: zero recompile alarms
        for n in (1, 2, 3, 4):
            pool.primary.embed(random_images(n, seed=n))
        assert sentry.recompile_alarms == 0
        assert metrics.recompile_alarms_total.value == 0
        rendered = metrics.render()
        assert "simclr_serve_recompile_alarms_total 0" in rendered
        assert "simclr_serve_weights_generation 1" in rendered
        assert "simclr_serve_weight_swaps_total 1" in rendered
        assert "simclr_serve_checkpoint_staleness_seconds" in rendered
        assert mgr._staleness() >= 0.0

        # every replica now serves exactly what a fresh engine built from
        # the new checkpoint's weights serves
        fresh = EmbedEngine(model, v1, max_batch=MAX_BATCH, warmup=False)
        images = random_images(3, seed=7)
        want = fresh.embed(images)
        for rep in pool.replicas:
            assert np.array_equal(rep.engine.embed(images), want)

        (swap,) = [
            e for e in read_events(events_path(str(tmp_path)))
            if e["event"] == "swap"
        ]
        assert swap["epoch"] == 1 and swap["generation"] == 1
        assert swap["replicas"] == 2 and swap["path"] == ckpt

    def test_corrupted_checkpoint_rejected_prior_generation_bitwise(
        self, tmp_path, tiny
    ):
        from simclr_tpu.supervisor.faults import corrupt_checkpoint_bytes

        model, v0, v1 = tiny
        metrics = ServeMetrics()
        pool = _pool(model, v0, replicas=2, metrics=metrics)
        mgr = ReloadManager(
            pool, save_dir=str(tmp_path), metrics=metrics,
            events=EventLog(str(tmp_path)), load_fn=_restore,
        )
        _save_ckpt(tmp_path, 1, v1)
        assert mgr.poll_once() is True and pool.weights_generation == 1

        images = random_images(4, seed=11)
        before = [rep.engine.embed(images) for rep in pool.replicas]

        # chaos: epoch-2 checkpoint lands corrupted (bit flip after the
        # sha256 sidecar committed — exactly what the fault injector does)
        bad = _save_ckpt(tmp_path, 2, v0)
        corrupt_checkpoint_bytes(bad)
        assert mgr.poll_once() is False

        # prior generation keeps serving, bitwise, on every replica
        assert pool.weights_generation == 1
        for rep, want in zip(pool.replicas, before):
            assert np.array_equal(rep.engine.embed(images), want)
        assert metrics.swap_rejected_total.value == 1
        assert "simclr_serve_swap_rejected_total 1" in metrics.render()
        rejects = [
            e for e in read_events(events_path(str(tmp_path)))
            if e["event"] == "swap_rejected"
        ]
        assert len(rejects) == 1
        assert rejects[0]["epoch"] == 2
        assert rejects[0]["serving_generation"] == 1
        assert rejects[0]["reason"].startswith("digest mismatch")

        # a rejected path is never retried (one event, one counter bump)...
        assert mgr.poll_once() is False
        assert mgr.rejected_count == 1
        assert metrics.swap_rejected_total.value == 1

        # ...and a later good checkpoint still swaps
        _save_ckpt(tmp_path, 3, v1)
        assert mgr.poll_once() is True
        assert pool.weights_generation == 2 and mgr.swapped_epoch == 3

    def test_missing_sidecar_waits_instead_of_rejecting(self, tmp_path, tiny):
        model, v0, v1 = tiny
        pool = _pool(model, v0)
        mgr = ReloadManager(pool, save_dir=str(tmp_path), load_fn=_restore)
        ckpt = _save_ckpt(tmp_path, 1, v1)
        os.unlink(digest_path(ckpt))

        # no sidecar = save not committed yet: wait, don't reject
        assert mgr.poll_once() is False
        assert mgr.rejected_count == 0 and mgr.swap_count == 0
        assert pool.weights_generation == 0

        digest = checkpoint_digest(ckpt)
        with open(digest_path(ckpt), "w") as f:
            f.write(f"{digest}  {os.path.basename(ckpt)}\n")
        assert mgr.poll_once() is True
        assert pool.weights_generation == 1

    def test_newest_verified_checkpoint_wins(self, tmp_path, tiny):
        model, v0, v1 = tiny
        pool = _pool(model, v0)
        loads = []

        def load(path):
            loads.append(path)
            return _restore(path)

        mgr = ReloadManager(pool, save_dir=str(tmp_path), load_fn=load)
        _save_ckpt(tmp_path, 1, v1)
        _save_ckpt(tmp_path, 2, v1)
        assert mgr.poll_once() is True
        # the stale epoch-1 checkpoint was never even loaded
        assert mgr.swapped_epoch == 2 and mgr.swap_count == 1
        assert len(loads) == 1 and "epoch=2" in loads[0]

    def test_incompatible_weights_rejected_before_any_commit(
        self, tmp_path, tiny
    ):
        model, v0, _v1 = tiny
        pool = _pool(model, v0, replicas=2)
        mgr = ReloadManager(
            pool, save_dir=str(tmp_path),
            events=EventLog(str(tmp_path)),
            load_fn=lambda p: {"params": {}, "batch_stats": {}},
        )
        images = random_images(2, seed=5)
        before = [rep.engine.embed(images) for rep in pool.replicas]
        _save_ckpt(tmp_path, 1, v0)

        assert mgr.poll_once() is False
        assert mgr.rejected_count == 1
        assert pool.weights_generation == 0
        for rep, want in zip(pool.replicas, before):
            assert np.array_equal(rep.engine.embed(images), want)
        (reject,) = [
            e for e in read_events(events_path(str(tmp_path)))
            if e["event"] == "swap_rejected"
        ]
        assert reject["serving_generation"] == 0

    def test_resync_engine_joins_grown_replica_at_serving_generation(
        self, tmp_path, tiny
    ):
        model, v0, v1 = tiny
        pool = _pool(model, v0)
        mgr = ReloadManager(pool, save_dir=str(tmp_path), load_fn=_restore)
        mgr.current_variables = v0  # the core seeds generation 0
        _save_ckpt(tmp_path, 1, v1)
        assert mgr.poll_once() is True and pool.weights_generation == 1

        # an elastically grown replica boots from the SERVING generation,
        # so the pool-min generation never regresses when the tier grows
        grown = EmbedEngine(model, v0, max_batch=MAX_BATCH, warmup=False)
        mgr.resync_engine(grown)
        assert grown.generation == 1
        images = random_images(3, seed=9)
        assert np.array_equal(grown.embed(images), pool.primary.embed(images))
        pool.add_replica(grown)
        assert pool.weights_generation == 1

    def test_corpus_republished_per_generation(self, tmp_path, tiny):
        model, v0, v1 = tiny

        class _FakeServer:
            def __init__(self):
                self.indexes = []

            def swap_index(self, index):
                self.indexes.append(index)

        metrics = ServeMetrics()
        pool = _pool(model, v0, metrics=metrics)
        server = _FakeServer()
        corpus = random_images(6, seed=3)
        mgr = ReloadManager(
            pool, save_dir=str(tmp_path), server=server, metrics=metrics,
            corpus_images=corpus, reembed_batch=4, load_fn=_restore,
        )
        mgr.current_variables = v0
        mgr.bootstrap_corpus()
        assert isinstance(server.indexes[-1], NeighborIndex)
        assert server.indexes[-1].generation == 0
        assert server.indexes[-1].n == 6
        assert metrics.corpus_generation.value == 0

        _save_ckpt(tmp_path, 1, v1)
        assert mgr.poll_once() is True
        # /v1/neighbors answers from the same generation /v1/embed computes
        # with: the fresh index carries the committed generation tag
        assert server.indexes[-1].generation == 1
        assert server.indexes[-1].generation == pool.weights_generation
        assert metrics.corpus_generation.value == 1
        assert "simclr_serve_corpus_generation 1" in metrics.render()


# ---------------------------------------------------------------------------
# reallocation policy (coscheduler/policy.py) — pure, clock-injected
# ---------------------------------------------------------------------------


class TestPressure:
    def test_pressure_normalization(self):
        assert pressure_of(0, 0) == 0.0
        assert pressure_of(5, 0) == 0.0
        assert pressure_of(2, 4) == 0.5
        assert pressure_of(9, 4) == 1.0
        assert pressure_of(-3, 4) == 0.0

    def test_any_rejection_saturates(self):
        # a 429 between samples means the ceiling was hit even if the
        # queue looks empty now
        assert pressure_of(0, 64, rejected_delta=1) == 1.0


class TestReallocationPolicy:
    def test_shrink_requires_sustained_pressure(self):
        p = ReallocationPolicy(high=0.75, low=0.1, sustain_s=10, cooldown_s=0)
        assert p.observe(1.0, 0.0) is None
        assert p.observe(1.0, 5.0) is None
        assert p.observe(0.5, 6.0) is None     # band sample resets the timer
        assert p.observe(1.0, 7.0) is None
        assert p.observe(1.0, 16.0) is None    # only 9s since re-entry
        assert p.observe(1.0, 17.5) == SHRINK
        assert p.state == "lent"
        assert p.observe(1.0, 30.0) is None    # SHRINK fires exactly once

    def test_release_needs_ebb_and_cooldown(self):
        p = ReallocationPolicy(high=0.75, low=0.1, sustain_s=1, cooldown_s=100)
        p.observe(1.0, 0.0)
        assert p.observe(1.0, 1.5) == SHRINK
        assert p.observe(0.0, 2.0) is None
        assert p.observe(0.0, 50.0) is None    # sustained ebb, not cooled
        assert p.observe(0.0, 102.0) == RELEASE
        assert p.state == "idle"

    def test_cancel_reverts_refused_move(self):
        p = ReallocationPolicy(sustain_s=0, cooldown_s=0)
        assert p.observe(1.0, 0.0) == SHRINK
        p.cancel(0.0)  # training mesh already at one host: undo
        assert p.state == "idle"
        assert p.observe(1.0, 1.0) == SHRINK

    def test_disabled_policy_never_moves(self):
        p = ReallocationPolicy(sustain_s=0, cooldown_s=0, enabled=False)
        assert p.observe(1.0, 0.0) is None
        assert p.state == "idle"

    @pytest.mark.parametrize(
        "low,high", [(0.5, 0.5), (0.8, 0.2), (-0.1, 0.5), (0.1, 1.5)]
    )
    def test_empty_or_invalid_band_rejected(self, low, high):
        with pytest.raises(ValueError):
            ReallocationPolicy(high=high, low=low)


# ---------------------------------------------------------------------------
# config surface (conf/cosched.yaml + check_cosched_conf)
# ---------------------------------------------------------------------------


class TestCoschedConfig:
    def test_cosched_composes_pretrain_root_without_checkpoint(self):
        cfg = load_config("cosched")
        check_cosched_conf(cfg)  # no checkpoint source required
        assert cfg.cosched.serve_devices == 1
        assert cfg.cosched.max_serve_devices >= cfg.cosched.serve_devices
        assert cfg.serve.checkpoint is None
        # full training root composed underneath: training overrides work
        assert cfg.parameter.epochs > 0
        assert load_config(
            "cosched", overrides=["parameter.epochs=6"]
        ).parameter.epochs == 6

    @pytest.mark.parametrize(
        "override",
        [
            "cosched.serve_devices=0",
            "cosched.max_serve_devices=0",
            "cosched.reload_poll_s=0.0",
            "cosched.pressure_high=1.5",
            "cosched.pressure_low=0.9",   # >= pressure_high: empty band
            "cosched.pressure_sustain_s=-1",
            "cosched.realloc_cooldown_s=-1",
            "cosched.corpus_images=-1",
            "cosched.reembed_batch=0",
        ],
    )
    def test_bad_cosched_knobs_raise(self, override):
        with pytest.raises(ConfigError):
            check_cosched_conf(load_config("cosched", overrides=[override]))

    def test_standalone_serve_still_requires_checkpoint_source(self):
        cfg = load_config("serve")
        with pytest.raises(ConfigError):
            check_serve_conf(cfg)
        check_serve_conf(cfg, require_checkpoint_source=False)

    def test_cli_rejects_bad_config_with_exit_2(self, capsys):
        from simclr_tpu.coscheduler.__main__ import main

        rc = main(
            ["--nprocs", "2", "--devices-per-proc", "2", "--",
             "cosched.pressure_high=1.5"]
        )
        assert rc == 2
        assert "cosched.pressure_high" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# combined train+serve post-mortem (obs/report.py)
# ---------------------------------------------------------------------------


class TestReportServeSection:
    def _run_dir(self, tmp_path):
        from simclr_tpu.obs.report import COSCHED_SUMMARY_NAME

        run = tmp_path / "run"
        run.mkdir()
        events = [
            {"event": "run_start", "attempt": 1},
            {"event": "swap", "epoch": 1, "generation": 1, "replicas": 1},
            {"event": "swap", "epoch": 2, "generation": 2, "replicas": 2},
            {"event": "swap_rejected", "epoch": 3, "serving_generation": 2,
             "reason": "digest mismatch"},
            {"event": "reallocate", "direction": "shrink", "host": 1},
            {"event": "reallocate", "direction": "release", "host": 1},
        ]
        with open(run / "events.jsonl", "w") as f:
            f.writelines(json.dumps(e) + "\n" for e in events)
        (run / COSCHED_SUMMARY_NAME).write_text(
            json.dumps({
                "outcome": "clean", "serve_replicas": 2,
                "serving_generation": 2, "swaps": 2,
            })
        )
        return str(run)

    def test_serve_section_counts_and_render(self, tmp_path):
        from simclr_tpu.obs.report import build_report, render_report

        report = build_report(self._run_dir(tmp_path))
        serve = report["serve"]
        assert serve["swaps"] == 2 and serve["swap_rejections"] == 1
        assert serve["reallocations"] == 1 and serve["releases"] == 1
        assert serve["serving_generation"] == 2
        assert serve["last_swap_epoch"] == 2
        assert serve["serve_replicas"] == 2
        text = render_report(report)
        assert (
            "serve: swaps=2 REJECTED=1 generation=2 reallocations=1 "
            "(released 1) replicas=2"
        ) in text
        assert "last swap: epoch 2" in text
        assert text.splitlines()[-1].startswith("run_report verdict:")

    def test_summary_only_run_still_reports_serve(self, tmp_path):
        from simclr_tpu.obs.report import COSCHED_SUMMARY_NAME, build_report

        run = tmp_path / "bare"
        run.mkdir()
        (run / COSCHED_SUMMARY_NAME).write_text(
            json.dumps({"serving_generation": 3, "serve_replicas": 1})
        )
        serve = build_report(str(run))["serve"]
        assert serve["swaps"] == 0 and serve["serving_generation"] == 3
        assert serve["last_swap_epoch"] is None

    def test_no_serve_activity_no_section(self, tmp_path):
        from simclr_tpu.obs.report import build_report

        empty = tmp_path / "empty"
        empty.mkdir()
        assert build_report(str(empty))["serve"] is None


# ---------------------------------------------------------------------------
# fleet auto-discovery of co-scheduled serve replicas (obs/fleet.py)
# ---------------------------------------------------------------------------


class _ReplicaTelemetry:
    def render(self):
        return "simclr_serve_requests_total 7\n"

    def snapshot(self):
        return {"status": "ok"}


class TestFleetServeDiscovery:
    def test_collector_adopts_serve_ready_files_from_run_dir(self, tmp_path):
        from simclr_tpu.obs.exporter import start_exporter
        from simclr_tpu.obs.fleet import FleetCollector

        exporter = start_exporter(
            _ReplicaTelemetry(), str(tmp_path), trace_max_ms=5000,
            ready_file=str(tmp_path / "serve.ready"),
        )
        # no serve_ready_files listing: the collector must find the
        # co-scheduled replica's ready file in the run dir on its own
        collector = FleetCollector(str(tmp_path), nprocs=0, poll_s=60.0)
        try:
            collector.scrape_once()
            assert collector.snapshot()["replicas_up"] == 1
            assert (
                'simclr_fleet_serve_requests_total{replica="0"} 7'
                in collector.render()
            )
            # idempotent: a second pass does not duplicate the endpoint
            collector.scrape_once()
            assert len(collector.serve_ready_files) == 1
        finally:
            collector.close()
            exporter.close()
