"""Co-scheduler core: pretraining + the serve tier as one supervised system.

``python -m simclr_tpu.coscheduler`` runs three cooperating planes in the
coordinator process:

  * **train** — an :class:`~simclr_tpu.supervisor.elastic.ElasticSupervisor`
    on a background thread, launching the usual per-host training children
    (``simclr_tpu.main``) with every serve/cosched override filtered out;
  * **serve** — the full HTTP stack (ReplicaPool over
    ``cosched.serve_devices`` local devices, DynamicBatcher, EmbedServer)
    in-process, starting on random generation-0 weights and hot-reloading
    each sha256-verified checkpoint the run writes
    (:class:`~simclr_tpu.coscheduler.reload.ReloadManager`);
  * **policy** — a pressure sampler feeding
    :class:`~simclr_tpu.coscheduler.policy.ReallocationPolicy`: sustained
    queue pressure lends a training host to the serve tier (a deliberate
    remesh-on-loss shrink + a new serve replica), ebbing traffic retires
    the extra replica and grows training back.

The training run dir is the single rendezvous surface: checkpoints flow
train->serve through it, events.jsonl interleaves supervisor lifecycle
with swap/reallocation events, and ``serve.ready`` publishes the bound
endpoint next to the telemetry ready files (auto-discovered by the fleet
collector).
"""

from __future__ import annotations

import json
import logging
import os
import signal
import sys
import threading
import time

import numpy as np

from simclr_tpu.config import ConfigError, resolve_save_dir
from simclr_tpu.coscheduler.policy import (
    RELEASE,
    SHRINK,
    ReallocationPolicy,
    pressure_of,
)
from simclr_tpu.coscheduler.reload import ReloadManager
from simclr_tpu.obs.events import EventLog
from simclr_tpu.utils.ioutil import atomic_write

logger = logging.getLogger("simclr_tpu.coscheduler")

_POLICY_POLL_S = 0.25


class CoScheduler:
    """Wire the three planes together over one run dir; see module docs.

    ``train_overrides`` is the already-filtered override list for the
    training children (no ``serve.*``/``cosched.*`` keys — those configure
    this process, and ``simclr_tpu.main``'s strict config would reject
    them).
    """

    def __init__(
        self,
        cfg,
        *,
        nprocs: int,
        devices_per_proc: int,
        force_cpu: bool = False,
        coord_timeout_s: float | None = None,
        train_overrides: list[str] | None = None,
    ):
        self.cfg = cfg
        self.nprocs = int(nprocs)
        self.devices_per_proc = int(devices_per_proc)
        self.force_cpu = bool(force_cpu)
        self.coord_timeout_s = coord_timeout_s
        self.train_overrides = list(train_overrides or [])
        self.serve_devices = int(cfg.select("cosched.serve_devices", 1))
        self.max_serve_devices = int(
            cfg.select("cosched.max_serve_devices", self.serve_devices)
        )
        per_device = int(cfg.select("experiment.batches", 0) or 0)
        if per_device <= 0:
            raise ConfigError(
                f"experiment.batches must be a positive per-device batch, "
                f"got {per_device!r}"
            )
        self.global_batch = per_device * self.devices_per_proc * self.nprocs
        # populated by run(); held as attributes so the policy handlers and
        # tests can reach the live stack
        self.pool = None
        self.batcher = None
        self.server = None
        self.metrics = None
        self.reload = None
        self.supervisor = None
        self.events = None
        self._model = None

    # -- serve plane ---------------------------------------------------------
    def _build_serve_stack(self, save_dir: str):
        import jax
        import jax.numpy as jnp

        from simclr_tpu.eval import build_eval_model
        from simclr_tpu.parallel.train_state import init_variables
        from simclr_tpu.serve.metrics import ServeMetrics
        from simclr_tpu.serve.replica import ReplicaPool
        from simclr_tpu.serve.server import _write_ready_file, start_server

        cfg = self.cfg
        seed = int(cfg.parameter.seed)
        self._model = model = build_eval_model(cfg)
        # generation 0: random-init weights with the checkpoint's exact
        # variable structure (same model builder eval uses), so the first
        # real checkpoint stages shape-identically — zero recompiles
        variables = jax.tree.map(
            np.asarray,
            init_variables(
                model, jax.random.key(seed),
                jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32),
            ),
        )
        self.metrics = metrics = ServeMetrics()
        logger.info(
            "building %d serve replica(s) on generation-0 weights...",
            self.serve_devices,
        )
        self.pool = pool = ReplicaPool.from_model(
            model,
            variables,
            replicas=self.serve_devices,
            max_batch=int(cfg.serve.max_batch),
            use_full_encoder=bool(cfg.parameter.use_full_encoder),
            metrics=metrics,
            warmup=True,
            weights=str(cfg.select("serve.weights", "exact")),
        )
        metrics.weights_generation.set(0)
        self.server, self.batcher = start_server(cfg, pool=pool, metrics=metrics)

        n_corpus = int(cfg.select("cosched.corpus_images", 0) or 0)
        corpus_images = None
        if n_corpus > 0:
            # deterministic synthetic corpus: what matters is that every
            # generation re-embeds the SAME rows, so /v1/neighbors answers
            # track the encoder, not the data
            rng = np.random.default_rng(seed)
            corpus_images = rng.integers(
                0, 256, size=(n_corpus, 32, 32, 3), dtype=np.uint8
            )
        self.reload = ReloadManager(
            pool,
            save_dir=save_dir,
            server=self.server,
            events=self.events,
            metrics=metrics,
            corpus_images=corpus_images,
            reembed_batch=int(cfg.select("cosched.reembed_batch", 256)),
            neighbors_metric=str(cfg.select("serve.neighbors_metric", "dot")),
            corpus_dtype=str(cfg.select("serve.corpus_dtype", "fp32")),
            ann_cells=int(cfg.select("serve.ann_cells", 0) or 0),
            ann_probe=int(cfg.select("serve.ann_probe", 1) or 1),
            poll_s=float(cfg.select("cosched.reload_poll_s", 2.0)),
        )
        self.reload.current_variables = variables
        self.reload.bootstrap_corpus()

        server_thread = threading.Thread(
            target=self.server.serve_forever,
            kwargs={"poll_interval": 0.1},
            name="cosched-serve",
            daemon=True,
        )
        server_thread.start()
        _write_ready_file(cfg, self.server)
        host, port = self.server.server_address[:2]
        logger.info("serve tier up on http://%s:%d", host, port)
        return server_thread

    # -- elastic reallocation handlers ---------------------------------------
    def _grow_serve(self, now: float, policy: ReallocationPolicy) -> None:
        """SHRINK: lend one training host, add one serve replica."""
        import jax

        from simclr_tpu.serve.engine import EmbedEngine

        if self.pool.size >= self.max_serve_devices:
            policy.cancel(now)
            return
        if not self.supervisor.request_shrink():
            policy.cancel(now)  # training mesh already at one host
            return
        devices = jax.local_devices()
        device = devices[min(self.pool.size, len(devices) - 1)]
        cfg = self.cfg
        engine = EmbedEngine(
            self._model,
            self.reload.current_variables,
            max_batch=int(cfg.serve.max_batch),
            use_full_encoder=bool(cfg.parameter.use_full_encoder),
            metrics=self.metrics,
            warmup=True,
            device=device,
            replica_id=self.pool.size,
            weights=str(cfg.select("serve.weights", "exact")),
        )
        # bring it onto the serving generation under the swap lock (a swap
        # may have landed while the engine warmed)
        self.reload.resync_engine(engine)
        rep = self.pool.add_replica(engine)
        self.batcher.add_worker(rep)
        self.events.emit(
            "serve_scale", direction="grow", replicas=self.pool.size,
            replica=rep.rid,
        )
        logger.info(
            "queue pressure sustained: serve tier grown to %d replica(s); "
            "training mesh shrinking one host", self.pool.size,
        )

    def _shrink_serve(self, now: float, policy: ReallocationPolicy) -> None:
        """RELEASE: retire the lent replica, give the host back to training."""
        timeline = self.supervisor.hosts_timeline
        if not timeline or timeline[-1] >= self.nprocs:
            # The lent host is still draining out of the mesh: a generation
            # smaller than nprocs has not spawned yet. Releasing now would
            # make the host readmittable before the relaunch, so the remesh
            # would re-adopt it and training would never actually run on
            # the smaller mesh (and the grow-back path would never fire).
            # Stay lent; the policy retries after its cooldown.
            policy.cancel(now)
            return
        if self.pool.size > self.serve_devices:
            rid = max(r.rid for r in self.pool.replicas)
            self.batcher.retire_worker(rid)
            self.pool.remove_replica(rid)
            self.events.emit(
                "serve_scale", direction="shrink", replicas=self.pool.size,
                replica=rid,
            )
        released = self.supervisor.release_reallocation()
        logger.info(
            "pressure ebbed: serve tier back to %d replica(s); %d host(s) "
            "released to training", self.pool.size, released,
        )

    # -- run -----------------------------------------------------------------
    def run(self) -> dict:
        from simclr_tpu.obs.fleet import maybe_start_fleet
        from simclr_tpu.serve.server import shutdown_gracefully
        from simclr_tpu.supervisor.elastic import ElasticSupervisor
        from simclr_tpu.supervisor.runner import SupervisorKnobs

        cfg = self.cfg
        save_dir = resolve_save_dir(cfg)
        os.makedirs(save_dir, exist_ok=True)
        if not cfg.select("experiment.save_dir"):
            cfg.update_dotted("experiment.save_dir", save_dir, allow_new=True)
        if not cfg.select("serve.ready_file"):
            cfg.update_dotted(
                "serve.ready_file", os.path.join(save_dir, "serve.ready")
            )
        events_on = bool(cfg.select("telemetry.events", True))
        self.events = EventLog(save_dir, enabled=events_on)

        server_thread = self._build_serve_stack(save_dir)

        train_overrides = list(self.train_overrides)
        if not any(
            o.split("=", 1)[0].lstrip("+") == "experiment.save_dir"
            for o in train_overrides
        ):
            train_overrides.append(f"experiment.save_dir={save_dir}")
        fleet = maybe_start_fleet(cfg, save_dir, nprocs=self.nprocs)
        self.supervisor = ElasticSupervisor(
            [sys.executable, "-m", "simclr_tpu.main", *train_overrides],
            save_dir,
            SupervisorKnobs.from_config(cfg),
            nprocs=self.nprocs,
            devices_per_proc=self.devices_per_proc,
            global_batch=self.global_batch,
            grow_back_cooldown_s=float(
                cfg.select("supervisor.grow_back_cooldown_s", 60.0)
            ),
            force_cpu=self.force_cpu,
            coord_timeout_s=self.coord_timeout_s,
            events=EventLog(save_dir, enabled=events_on),
            fleet=fleet,
        )

        result_box: dict = {}

        def _train():
            try:
                result_box["result"] = self.supervisor.run()
            except BaseException as e:  # noqa: BLE001 - recorded in summary
                logger.exception("training supervisor died")
                result_box["error"] = f"{type(e).__name__}: {e}"

        train_thread = threading.Thread(
            target=_train, name="cosched-train", daemon=True
        )
        stop_reload = threading.Event()
        reload_thread = threading.Thread(
            target=self.reload.run,
            args=(stop_reload,),
            name="cosched-reload",
            daemon=True,
        )

        previous_handlers = {}
        if threading.current_thread() is threading.main_thread():
            # first signal drains training (guards checkpoint + exit 75 ->
            # clean supervisor exit); the serve tier then drains in the
            # ordinary teardown below
            def _on_stop(signum, frame):
                self.supervisor._on_stop(signum, frame)

            for sig in (signal.SIGTERM, signal.SIGINT):
                previous_handlers[sig] = signal.signal(sig, _on_stop)

        policy = ReallocationPolicy(
            high=float(cfg.select("cosched.pressure_high", 0.75)),
            low=float(cfg.select("cosched.pressure_low", 0.1)),
            sustain_s=float(cfg.select("cosched.pressure_sustain_s", 10.0)),
            cooldown_s=float(cfg.select("cosched.realloc_cooldown_s", 30.0)),
            enabled=bool(cfg.select("cosched.reallocation", True))
            and self.nprocs > 1,
        )
        queue_capacity = int(cfg.serve.queue_depth)
        train_thread.start()
        reload_thread.start()
        last_rejected = self.metrics.rejected_total.value
        try:
            while train_thread.is_alive():
                time.sleep(_POLICY_POLL_S)
                now = time.monotonic()
                rejected = self.metrics.rejected_total.value
                pressure = pressure_of(
                    int(self.metrics.queue_depth.value),
                    queue_capacity,
                    rejected - last_rejected,
                )
                last_rejected = rejected
                action = policy.observe(pressure, now)
                try:
                    if action == SHRINK:
                        self._grow_serve(now, policy)
                    elif action == RELEASE:
                        self._shrink_serve(now, policy)
                except Exception:  # pragma: no cover - policy must not
                    # take down a healthy train+serve system
                    logger.exception("reallocation move failed")
            train_thread.join()
        finally:
            for sig, handler in previous_handlers.items():
                signal.signal(sig, handler)
            stop_reload.set()
            reload_thread.join(timeout=60.0)
            shutdown_gracefully(self.server)
            self.server.server_close()
            server_thread.join(timeout=10.0)
            if fleet is not None:
                fleet.close()

        train_result = result_box.get("result") or {
            "outcome": "error",
            "exit": 1,
            "error": result_box.get("error", "supervisor thread died"),
        }
        summary = {
            "outcome": train_result.get("outcome"),
            "exit": int(train_result.get("exit", 1)),
            "swaps": self.reload.swap_count,
            "swap_rejected": self.reload.rejected_count,
            "reallocations": self.supervisor.reallocate_count,
            "serving_generation": self.pool.weights_generation,
            "serve_replicas": self.pool.size,
            "corpus_generation": getattr(
                getattr(self.server, "corpus_store", None), "generation", None
            ),
            "corpus_rows": getattr(
                getattr(self.server, "corpus_store", None), "rows", None
            ),
            "train": train_result,
        }
        atomic_write(
            os.path.join(save_dir, "cosched_summary.json"),
            lambda f: json.dump(summary, f, indent=2),
        )
        return summary
