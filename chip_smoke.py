#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the main path once, through the entry points a user calls, at the
full width of the default model (``conf/config.yaml``: ResNet-18, 512 per
device, bf16, global negatives) with random weights from the config's seed:

  checks   one JAX process: device identity, fused NT-Xent (Pallas, compiled
           by Mosaic) against ``ops/ntxent.py`` at the train step's shapes,
           fused augmentation against ``data/augment.py`` at batch 512, and
           on several chips batch placement and the sharded loss over ICI
  train    ``python -m simclr_tpu.main``: 3 epochs of 8 global batches of
           synthetic data, a checkpoint each
  resume   the last checkpoint is taken away and the same command run with
           ``experiment.resume=true`` in a new process: it redoes the last
           epoch, and its train step must come from the compile cache
  fused    one more epoch with ``loss.fused=true`` and
           ``runtime.augment_impl=fused`` (both Pallas kernels in the step)
  serve    ``python -m simclr_tpu.serve`` on the last checkpoint with a
           seeded 65,536 x 512 corpus: /healthz, /v1/embed across buckets,
           /v1/neighbors against an exact NumPy top-k, /metrics, SIGTERM;
           then started again, with every bucket from the compile cache

This process never imports JAX: each phase is a child that is the only
process holding the chips while it runs. Any phase that fails, or that ran
on a platform other than ``tpu``, fails the smoke with a non-zero exit and
no result line. On success the last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

Logs and ``summary.json`` land in ``chiprun_out/chip_smoke/``; checkpoints
and the corpus live in ``.chip_smoke_work/`` and are removed afterwards.
The smoke records no rate: what it times is start-up (compile, warm-up).

``--cpu-dry-run`` is for the CPU test suite only: the same phases at toy
sizes on whatever platform JAX has, printing a line without an ``ok`` key.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import http.client
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
WORK_DIR = os.path.join(ROOT, ".chip_smoke_work")
RUN_DIR = os.path.join(WORK_DIR, "run")
CKPT_STEM = "cifar10"  # conf/experiment/cifar10.yaml output_model_name

TIME_LIMIT_S = 1150  # the contract allows 1200 s, compilation included

# conf/config.yaml and conf/serve.yaml defaults, and the toy sizes the CPU
# test suite runs the same phases at
FULL = dict(per_device_batch=512, batches_per_epoch=8, max_batch=256,
            corpus_rows=65536, embed_sizes=(1, 3, 17, 100, 256),
            augment_batch=512)
TOY = dict(per_device_batch=2, batches_per_epoch=2, max_batch=4,
           corpus_rows=2048, embed_sizes=(1, 3, 4), augment_batch=8)
TRAIN_EPOCHS = 3
FEATURE_DIM = 512  # ResNet-18 encoder output, what /v1/embed serves
NEIGHBORS_K = 10
SEED = 7


class SmokeFailure(Exception):
    """A phase failed; the message names the phase and what it found."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# the JAX child: everything that needs jax in-process
# ---------------------------------------------------------------------------

def jax_checks(out_path: str, sizes: dict, dry_run: bool) -> int:
    from simclr_tpu.utils.platform import (
        configure_compilation_cache,
        device_identity,
    )

    cache_dir = configure_compilation_cache()
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    device = device_identity()
    if device["platform"] != "tpu" and not dry_run:
        print(
            f"chip_smoke: JAX found no accelerator: platform="
            f"{device['platform']!r} device_kind={device['kind']!r} "
            f"count={device['count']}",
            file=sys.stderr,
        )
        return 3

    from simclr_tpu.data.augment import simclr_two_views
    from simclr_tpu.native.lib import native_available
    from simclr_tpu.obs.peaks import chip_peak
    from simclr_tpu.ops import augment_pallas, ntxent_pallas
    from simclr_tpu.ops.ntxent import ntxent_loss, ntxent_loss_sharded_rows
    from simclr_tpu.parallel.mesh import (
        DATA_AXIS,
        batch_sharding,
        create_mesh,
        put_global_batch,
    )

    n = device["count"]
    report: dict = {
        "device": device,
        "compile_cache_dir": cache_dir,
        "jax": jax.__version__,
        "native_available": native_available(),
        "memory_stats_keys": sorted(jax.devices()[0].memory_stats() or {}),
        "env": {
            k: v for k, v in os.environ.items()
            if k.startswith(("TPU_", "JAX_", "XLA_", "MEGASCALE_", "LIBTPU"))
        },
    }
    try:
        import libtpu

        report["libtpu"] = libtpu.__version__
    except ImportError:
        report["libtpu"] = None
    chip_peak(device["platform"], device["kind"])  # unknown TPU kind: error

    on_cpu = device["platform"] == "cpu"
    check(ntxent_pallas._interpret() == on_cpu and
          augment_pallas._interpret() == on_cpu,
          "Pallas interpret mode must be on exactly when the platform is cpu")

    mesh = create_mesh()
    b_local = sizes["per_device_batch"]
    rng = np.random.default_rng(SEED)
    z0 = rng.normal(size=(b_local * n, 128)).astype(np.float32)
    z1 = (z0 + 0.5 * rng.normal(size=z0.shape)).astype(np.float32)

    # -- fused NT-Xent vs ops/ntxent.py at the train step's shapes:
    #    2*b_local local anchors x 2*b_local*n gathered candidates, d=128
    def sharded(loss_fn):
        fn = jax.shard_map(
            lambda a, b: loss_fn(a, b, DATA_AXIS, 0.5),
            mesh=mesh, in_specs=(P(DATA_AXIS), P(DATA_AXIS)), out_specs=P(),
            check_vma=False,
        )
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))

    # the references multiply in float32 ("highest"): XLA's default matmul
    # precision on TPU is coarser than the kernels, which keep float32
    sharding = batch_sharding(mesh)
    a, b = put_global_batch(z0, sharding), put_global_batch(z1, sharding)
    with jax.default_matmul_precision("highest"):
        loss_d, grads_d = sharded(ntxent_loss_sharded_rows)(a, b)
    loss_f, grads_f = sharded(ntxent_pallas.ntxent_loss_fused_sharded)(a, b)
    loss_d, loss_f = float(loss_d), float(loss_f)
    g_d = np.concatenate([np.asarray(g) for g in grads_d])
    g_f = np.concatenate([np.asarray(g) for g in grads_f])
    grad_rel = float(np.linalg.norm(g_f - g_d) / np.linalg.norm(g_d))
    report["fused_ntxent"] = {
        "anchors": 2 * b_local, "candidates": 2 * b_local * n,
        "loss_dense": loss_d, "loss_fused": loss_f, "grad_rel_err": grad_rel,
    }
    check(math.isfinite(loss_f) and abs(loss_f - loss_d) <= 1e-5 * abs(loss_d),
          f"fused NT-Xent loss {loss_f} != dense {loss_d}")
    check(np.isfinite(g_f).all() and grad_rel <= 2e-3,
          f"fused NT-Xent gradient off by {grad_rel:.3g} (relative norm)")

    # -- fused augmentation vs data/augment.py, same keys, uint8 batch
    images = rng.integers(
        0, 256, size=(sizes["augment_batch"], 32, 32, 3), dtype=np.uint8
    )
    key = jax.random.key(SEED)
    with jax.default_matmul_precision("highest"):
        ref = simclr_two_views(key, jnp.asarray(images))
    got = jax.jit(augment_pallas.fused_two_views)(key, jnp.asarray(images))
    max_diff = float(max(
        np.abs(np.asarray(g) - np.asarray(r)).max() for g, r in zip(got, ref)
    ))
    report["fused_augment"] = {
        "batch": int(images.shape[0]), "max_abs_pixel_diff": max_diff,
    }
    check(all(np.isfinite(np.asarray(g)).all() for g in got) and max_diff <= 1e-4,
          f"fused augmentation differs from data/augment.py: "
          f"{report['fused_augment']}")

    # -- several chips: placement, and the sharded loss against one device
    if n > 1:
        shard_devices = {s.device for s in a.addressable_shards}
        check(shard_devices == set(jax.devices()),
              f"put_global_batch left devices without a shard: "
              f"{sorted(map(str, set(jax.devices()) - shard_devices))}")
        one = jax.devices()[0]
        with jax.default_matmul_precision("highest"):
            loss_one = float(jax.jit(ntxent_loss)(
                jax.device_put(z0, one), jax.device_put(z1, one)
            ))
        report["sharded_loss"] = {"n_chip_mesh": loss_d, "one_device": loss_one}
        check(abs(loss_d - loss_one) <= 1e-5 * abs(loss_one),
              f"sharded global-negatives loss {loss_d} over {n} devices != "
              f"one-device ntxent_loss {loss_one}")

    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)
    return 0


# ---------------------------------------------------------------------------
# the parent: runs the children one after another
# ---------------------------------------------------------------------------

class Smoke:
    def __init__(self, dry_run: bool):
        self.dry_run = dry_run
        self.sizes = TOY if dry_run else FULL
        self.t0 = time.monotonic()
        self.children: list[subprocess.Popen] = []
        self.summary: dict = {"phases": {}}
        self.device: dict | None = None

    # -- process plumbing ---------------------------------------------------
    def remaining(self) -> float:
        return TIME_LIMIT_S - (time.monotonic() - self.t0)

    def spawn(self, phase: str, cmd: list[str]) -> subprocess.Popen:
        log = open(os.path.join(OUT_DIR, f"{phase}.log"), "wb")
        proc = subprocess.Popen(
            cmd, cwd=ROOT, stdout=log, stderr=subprocess.STDOUT,
            start_new_session=True,
        )
        log.close()
        self.children.append(proc)
        return proc

    def wait(self, phase: str, proc: subprocess.Popen) -> int:
        try:
            return proc.wait(timeout=max(self.remaining(), 1))
        except subprocess.TimeoutExpired:
            raise SmokeFailure(
                f"{phase}: still running at the {TIME_LIMIT_S} s limit"
            ) from None

    def run(self, phase: str, cmd: list[str]) -> float:
        t0 = time.monotonic()
        rc = self.wait(phase, self.spawn(phase, cmd))
        check(rc == 0, f"{phase}: exit code {rc}\n{self.log_tail(phase)}")
        return time.monotonic() - t0

    def log_tail(self, phase: str, n: int = 30) -> str:
        with open(os.path.join(OUT_DIR, f"{phase}.log"), errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def stop_children(self) -> None:
        for proc in self.children:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()

    def note(self, phase: str, **facts) -> None:
        self.summary["phases"][phase] = facts
        print(f"[{time.monotonic() - self.t0:6.1f}s] {phase}: "
              f"{json.dumps(facts)}", flush=True)

    def same_device(self, phase: str, device: dict) -> None:
        """Every phase must have run on the devices the checks child saw
        (which has already refused any platform but ``tpu``)."""
        check(device == self.device,
              f"{phase}: ran on {device}, the checks child saw {self.device}")

    # -- phases --------------------------------------------------------------
    def checks(self) -> None:
        out = os.path.join(OUT_DIR, "checks.json")
        cmd = [sys.executable, os.path.abspath(__file__), "--jax-child", out]
        if self.dry_run:
            cmd.append("--cpu-dry-run")
        seconds = self.run("checks", cmd)
        with open(out) as f:
            report = json.load(f)
        self.device = report["device"]
        self.summary.update(
            device=self.device, jax=report["jax"], libtpu=report["libtpu"],
            native_available=report["native_available"],
            compile_cache_dir=report["compile_cache_dir"],
        )
        self.note(
            "checks", seconds=round(seconds, 1), device=self.device,
            native_available=report["native_available"],
            fused_ntxent=report["fused_ntxent"],
            fused_augment=report["fused_augment"],
            sharded_loss=report.get("sharded_loss"),
        )

    def train_cmd(self, epochs: int, *extra: str) -> list[str]:
        n = self.device["count"]
        per_device = self.sizes["per_device_batch"]
        return [
            sys.executable, "-m", "simclr_tpu.main",
            "experiment.synthetic_data=true",
            f"experiment.synthetic_size="
            f"{self.sizes['batches_per_epoch'] * per_device * n}",
            f"experiment.batches={per_device}",
            "experiment.save_model_epoch=1",
            f"experiment.save_dir={RUN_DIR}",
            "mesh.data=-1",
            f"parameter.epochs={epochs}",
            "parameter.warmup_epochs=1",
            *extra,
        ]

    def train_phase(self, phase: str, epochs: int, *extra: str) -> dict:
        """Run the trainer to ``epochs`` and check what it left behind."""
        n = self.device["count"]
        seconds = self.run(phase, self.train_cmd(epochs, *extra))
        with open(os.path.join(RUN_DIR, "pretrain_results.json")) as f:
            results = json.load(f)
        with open(os.path.join(RUN_DIR, "events.jsonl")) as f:
            events = [json.loads(line) for line in f]
        # this process's events: a resumed run re-seats the file, so count
        # from its own run_start, not from a line number
        starts = [i for i, e in enumerate(events) if e["event"] == "run_start"]
        events = events[starts[-1]:]
        check(results["complete"] is True, f"{phase}: run not complete")
        self.same_device(phase, results["device"])
        check(f"platform={self.device['platform']} " in self.log_tail(phase, 10**6),
              f"{phase}: the trainer's log does not name the platform")
        losses = dict(map(tuple, results["loss_history"]))
        check(sorted(losses) == list(range(1, epochs + 1))
              and all(math.isfinite(v) for v in losses.values()),
              f"{phase}: loss history {results['loss_history']}")
        check(results["n_data_shards"] == n and results["global_batch"]
              == n * self.sizes["per_device_batch"],
              f"{phase}: {results['n_data_shards']} data shards, global batch "
              f"{results['global_batch']} on {n} devices")
        compiles = [e for e in events if e["event"] == "compile"]
        check([e["name"] for e in compiles] == ["pretrain_step"]
              and not any(e["event"] == "recompile_alarm" for e in events),
              f"{phase}: expected one pretrain_step compile and no recompile "
              f"alarm, got {[e['event'] + ':' + e.get('name', '') for e in events]}")
        ckpt = os.path.join(RUN_DIR, f"epoch={epochs}-{CKPT_STEM}")
        check(os.path.isdir(ckpt) and os.path.isfile(ckpt + ".sha256"),
              f"{phase}: no checkpoint and sha256 sidecar at {ckpt}")
        facts = dict(
            seconds=round(seconds, 1), device=results["device"],
            loss=[round(losses[e], 4) for e in sorted(losses)],
            step_compile_seconds=round(compiles[0]["seconds"], 2),
            step_from_cache=compiles[0]["cache_hit"],
            step_fingerprint=compiles[0]["fingerprint"],
            compile_cache=results["compile_cache"],
        )
        self.note(phase, **facts)
        return {"events": events, "checkpoint": ckpt, **facts}

    def train(self) -> None:
        self.trained = self.train_phase("train", TRAIN_EPOCHS)

    def resume(self) -> None:
        # the lr schedule is compiled into the step as constants of
        # parameter.epochs, so only the same command lowers the same
        # program: lose the newest checkpoint and run it again
        ckpt = self.trained["checkpoint"]
        shutil.rmtree(ckpt)
        os.unlink(ckpt + ".sha256")
        got = self.train_phase("resume", TRAIN_EPOCHS, "experiment.resume=true")
        resumed = [e for e in got["events"] if e["event"] == "resume"]
        check(len(resumed) == 1 and resumed[0]["epoch"] == TRAIN_EPOCHS,
              f"resume: expected to resume at epoch {TRAIN_EPOCHS}, got {resumed}")
        # the same program was lowered, and the cache answered for it
        check(got["step_fingerprint"] == self.trained["step_fingerprint"]
              and got["step_from_cache"] is True,
              f"resume: the train step was not served from the compile cache: "
              f"{got['compile_cache']}, fingerprint {got['step_fingerprint']} "
              f"vs {self.trained['step_fingerprint']}")

    def fused(self) -> None:
        got = self.train_phase(
            "fused", TRAIN_EPOCHS + 1, "experiment.resume=true",
            "loss.fused=true", "runtime.augment_impl=fused",
        )
        self.checkpoint = got["checkpoint"]

    def start_server(self, phase: str, corpus_path: str):
        """Start ``python -m simclr_tpu.serve`` on the last checkpoint and
        wait for its ready file; returns (process, call, start-up seconds),
        ``call(method, path, body)`` being one checked HTTP exchange."""
        ready = os.path.join(WORK_DIR, f"{phase}.ready")
        t0 = time.monotonic()
        proc = self.spawn(phase, [
            sys.executable, "-m", "simclr_tpu.serve",
            f"serve.checkpoint={self.checkpoint}",
            f"serve.corpus={corpus_path}",
            f"serve.max_batch={self.sizes['max_batch']}",
            "serve.replicas=-1", "serve.port=0", f"serve.ready_file={ready}",
        ])
        while not os.path.exists(ready):
            check(proc.poll() is None,
                  f"{phase}: exited {proc.returncode} before ready\n"
                  f"{self.log_tail(phase)}")
            check(self.remaining() > 0, f"{phase}: not ready at the time limit")
            time.sleep(0.2)
        startup = time.monotonic() - t0
        with open(ready) as f:
            port = json.load(f)["port"]

        def call(method: str, path: str, body=None):
            conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
            try:
                conn.request(method, path, json.dumps(body) if body else None)
                resp = conn.getresponse()
                data = resp.read()
            finally:
                conn.close()
            check(resp.status == 200, f"{phase}: {method} {path} -> "
                  f"{resp.status} {data[:300]!r}")
            return data

        return proc, call, startup

    def stop_server(self, phase: str, proc: subprocess.Popen) -> None:
        os.kill(proc.pid, signal.SIGTERM)
        rc = self.wait(phase, proc)
        check(rc == 0, f"{phase}: exit code {rc} after SIGTERM\n"
              f"{self.log_tail(phase)}")

    def serve(self) -> None:
        n = self.device["count"]
        rng = np.random.default_rng(SEED)
        corpus = rng.standard_normal(
            (self.sizes["corpus_rows"], FEATURE_DIM), dtype=np.float32
        )
        corpus_path = os.path.join(WORK_DIR, "corpus.npy")
        np.save(corpus_path, corpus)
        proc, call, startup = self.start_server("serve", corpus_path)

        health = json.loads(call("GET", "/healthz"))
        self.same_device("serve", health["device"])
        buckets = health["buckets"]
        # programs the warm-up compiled and wrote to the compile cache (JAX
        # leaves out those that compile in under a second)
        warmup_wrote = health["compile_cache"]["misses"]
        check(health["status"] == "ok" and len(health["replicas"]) == n and all(
            r["warmed_buckets"] == buckets for r in health["replicas"]),
            f"serve: /healthz {health}")
        check(len({r["device"] for r in health["replicas"]}) == n,
              f"serve: replicas share devices: {health['replicas']}")
        check(health["neighbors"]["shards"] == n
              and health["neighbors"]["rows"] == len(corpus),
              f"serve: corpus residency {health['neighbors']}")

        def embed(call, images) -> np.ndarray:
            reply = call("POST", "/v1/embed", {"instances": images.tolist()})
            return np.asarray(json.loads(reply)["embeddings"], dtype=np.float32)

        # embed: sizes that land in different buckets, each sent twice
        for size in self.sizes["embed_sizes"]:
            images = rng.integers(0, 256, size=(size, 32, 32, 3), dtype=np.uint8)
            first, again = embed(call, images), embed(call, images)
            check(first.shape == (size, FEATURE_DIM) and np.isfinite(first).all(),
                  f"serve: embed of {size} rows -> shape {first.shape}, "
                  f"finite={np.isfinite(first).all()}")
            check(np.array_equal(first, again),
                  f"serve: embed of {size} rows differs between repeats")
        # concurrent singles until every replica has answered something
        one = {"instances": rng.integers(
            0, 256, size=(1, 32, 32, 3), dtype=np.uint8).tolist()}
        for _ in range(20):
            with concurrent.futures.ThreadPoolExecutor(4 * n) as pool:
                # list(): a failed exchange raises here, not in its thread
                list(pool.map(lambda _: call("POST", "/v1/embed", one),
                              range(4 * n)))
            replicas = json.loads(call("GET", "/healthz"))["replicas"]
            if all(r["batches"] > 0 for r in replicas):
                break
        check(all(r["batches"] > 0 for r in replicas),
              f"serve: replicas that served nothing: {replicas}")

        # neighbors: noisy copies of corpus rows, against exact NumPy top-k
        n_queries = min(5, self.sizes["max_batch"])
        planted = rng.choice(len(corpus), size=n_queries, replace=False)
        queries = corpus[planted] + 0.1 * rng.standard_normal(
            (n_queries, FEATURE_DIM), dtype=np.float32)
        reply = json.loads(call(
            "POST", "/v1/neighbors",
            {"queries": queries.tolist(), "k": NEIGHBORS_K},
        ))
        indices = np.asarray(reply["indices"])
        scores = np.asarray(reply["scores"], dtype=np.float64)
        exact = queries.astype(np.float64) @ corpus.astype(np.float64).T
        kth_best = -np.sort(-exact, axis=1)[:, NEIGHBORS_K - 1]
        returned = np.take_along_axis(exact, indices, axis=1)
        # the index scores in the backend's default matmul precision; a rank
        # may swap inside that error, a wrong row may not get in
        tol = 1e-2 * np.abs(exact).max()
        check(indices.shape == (n_queries, NEIGHBORS_K)
              and (indices[:, 0] == planted).all()
              and np.abs(scores - returned).max() <= tol
              and (returned.min(axis=1) >= kth_best - tol).all(),
              f"serve: neighbors disagree with exact top-{NEIGHBORS_K}: "
              f"first={indices[:, 0].tolist()} planted={planted.tolist()} "
              f"score err {np.abs(scores - returned).max():.4g} tol {tol:.4g}")

        metrics = call("GET", "/metrics").decode()
        fills = {
            line.split("}")[0].split('"')[1]: float(line.rsplit(" ", 1)[1])
            for line in metrics.splitlines()
            if line.startswith("simclr_serve_replica_batch_fill{")
        }
        check(len(fills) == n and all(v > 0 for v in fills.values()),
              f"serve: per-replica batch fill in /metrics: {fills}")
        check("simclr_serve_recompile_alarms_total 0" in metrics,
              "serve: a live request paid a compile (recompile alarm)")
        health = json.loads(call("GET", "/healthz"))
        self.stop_server("serve", proc)
        self.note(
            "serve", startup_seconds=round(startup, 1),
            device=health["device"], replicas=n, buckets=buckets,
            compile_cache=health["compile_cache"],
            corpus_shards=health["neighbors"]["shards"],
            batches_per_replica=[r["batches"] for r in health["replicas"]],
            embed_sizes=list(self.sizes["embed_sizes"]),
        )

        # a restarted server: what the first one's warm-up wrote to the
        # compile cache is found again, and it answers as the first one did
        proc, call, startup = self.start_server("serve_restart", corpus_path)
        health = json.loads(call("GET", "/healthz"))
        self.same_device("serve_restart", health["device"])
        check(health["compile_cache"]["hits"] >= warmup_wrote,
              f"serve_restart: the first warm-up wrote {warmup_wrote} programs "
              f"to the compile cache, the restart found "
              f"{health['compile_cache']}")
        # (images, first): the last and largest request of the embed loop
        check(np.array_equal(embed(call, images), first),
              "serve_restart: embeddings differ from the first server's")
        self.stop_server("serve_restart", proc)
        self.note("serve_restart", startup_seconds=round(startup, 1),
                  compile_cache=health["compile_cache"])

    # -- driver ---------------------------------------------------------------
    def main(self) -> int:
        for path in (OUT_DIR, WORK_DIR):
            shutil.rmtree(path, ignore_errors=True)
            os.makedirs(path)
        try:
            for phase in (self.checks, self.train, self.resume, self.fused,
                          self.serve):
                phase()
        except SmokeFailure as e:
            print(f"chip_smoke: FAILED after "
                  f"{time.monotonic() - self.t0:.0f} s: {e}", file=sys.stderr)
            return 1
        finally:
            self.stop_children()
            shutil.rmtree(WORK_DIR, ignore_errors=True)
            self.summary["seconds"] = round(time.monotonic() - self.t0, 1)
            # this smoke measures no rate, utilization or latency
            self.summary["claim"] = None
            with open(os.path.join(OUT_DIR, "summary.json"), "w") as f:
                json.dump(self.summary, f, indent=1)
        if self.dry_run:
            print(json.dumps({"dry_run": True, "device": self.device,
                              "phases": list(self.summary["phases"])}))
        else:
            print(json.dumps({"ok": True, "device": self.device}))
        return 0


if __name__ == "__main__":
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--cpu-dry-run", action="store_true",
                        help="test suite only: toy sizes, any platform")
    parser.add_argument("--jax-child", metavar="OUT", help=argparse.SUPPRESS)
    args = parser.parse_args()
    if args.jax_child:
        try:
            sys.exit(jax_checks(
                args.jax_child, TOY if args.cpu_dry_run else FULL,
                args.cpu_dry_run,
            ))
        except SmokeFailure as e:
            print(f"chip_smoke checks: {e}", file=sys.stderr)
            sys.exit(1)
    sys.exit(Smoke(args.cpu_dry_run).main())
