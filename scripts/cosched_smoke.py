"""Train+serve co-scheduler e2e smoke.

Drives ``python -m simclr_tpu.coscheduler`` through its FULL lifecycle on
CPU — 2 training processes x 2 virtual devices plus the in-process serve
tier — and judges the whole co-scheduling claim:

  1. **hot reload**: the run's sha256-verified epoch checkpoints must land
     in the serve tier as at least TWO zero-downtime generation swaps
     (``swap`` events; the first checkpoint and at least one successor);
  2. **elastic reallocation**: once serving is live, a synthetic load
     burst (more concurrent embed clients than ``serve.queue_depth``)
     must push sustained queue pressure past ``cosched.pressure_high`` so
     the policy lends a training host to serving (``reallocate``
     direction=shrink + a second serve replica); the burst then stops and
     the ebb must release the host (direction=release) and grow training
     back (``grow_back_count >= 1``);
  3. **generation consistency**: after swaps, a live probe pairs one
     ``POST /v1/embed`` (``X-Weights-Generation``) with one
     ``POST /v1/neighbors`` over the returned embedding
     (``X-Corpus-Generation``) — the retrieval corpus must be re-embedded
     by the SAME encoder generation that answers embeds;
  4. **trajectory parity**: the shrink/grow-back cycle preserves the
     global batch, so the run's per-epoch losses must match an
     uninterrupted same-seed single-process reference within 5e-2.

Contract: exits 0 ALWAYS and prints exactly one JSON payload line — its
fields (swaps, reallocations, generation consistency, no error field) are
the judge, not the exit code.
Reuses the scrubbed-env/backstop plumbing from ``multihost_dryrun.py``
(same directory, so it imports directly). ``COSCHED_SMOKE_TIMEOUT_S``
overrides the co-scheduler phase's own deadline (default 1500 s — it
spans three compile-from-scratch training generations plus the serve
tier's bucket warmup).
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

import multihost_dryrun as mhd

REPO_ROOT = mhd.REPO_ROOT

# training recipe: the elastic dryrun's 1-step epochs, stretched to 4
# epochs so the burst->shrink->ebb->grow-back cycle has room to complete
# while checkpoints are still landing (one per epoch => up to 4 swaps;
# the whole cycle finishes by epoch 2, and a 1-core CI host pays ~2 min
# per contended epoch, so more epochs only risk the stage timeout)
EPOCHS = 4
TRAIN_RECIPE = [
    o for o in mhd.ELASTIC_RECIPE if not o.startswith("parameter.epochs=")
] + [f"parameter.epochs={EPOCHS}"]

# serve/cosched knobs, CI-speed: a 4-deep queue that 6 concurrent clients
# overwhelm instantly (rejects pin pressure at 1.0), sub-second
# sustain/cooldown so one short burst crosses the policy thresholds, a
# tiny 8-row corpus so each swap's re-embed is one batch, max_batch 8 so
# the warmup compiles 4 bucket programs, not 6
COSCHED_OVERRIDES = [
    "serve.queue_depth=4",
    "serve.max_batch=8",
    "serve.max_delay_ms=20.0",
    "cosched.serve_devices=1",
    "cosched.max_serve_devices=2",
    "cosched.reload_poll_s=0.25",
    "cosched.corpus_images=8",
    "cosched.reembed_batch=8",
    "cosched.pressure_high=0.5",
    "cosched.pressure_low=0.05",
    "cosched.pressure_sustain_s=0.5",
    "cosched.realloc_cooldown_s=0.5",
]

BURST_THREADS = 6
BURST_MAX_S = 300.0  # give up on the shrink after this; payload shows why

_EMBED_BODY = json.dumps(
    {"instances": [[[[128, 128, 128]] * 32] * 32]}
).encode()
_JSON_HEADERS = {"Content-Type": "application/json"}


def _last_ditch(exc: BaseException) -> dict:
    return {
        "metric": "cosched_smoke",
        "value": 0.0,
        "unit": "bool",
        "parity": False,
        "error": repr(exc),
    }


def _sigterm_backstop(signum, frame) -> None:
    if not mhd._PAYLOAD_EMITTED:
        mhd._emit_payload(
            _last_ditch(
                RuntimeError(f"terminated by signal {signum} before finishing")
            )
        )
    os._exit(0)


def _read_events(run_dir: str) -> list[dict]:
    events: list[dict] = []
    try:
        with open(os.path.join(run_dir, "events.jsonl"), encoding="utf-8") as f:
            for line in f:
                try:
                    event = json.loads(line)
                except ValueError:  # torn tail line mid-write
                    continue
                if isinstance(event, dict):
                    events.append(event)
    except OSError:
        pass
    return events


def _count(events: list[dict], kind: str, **fields) -> int:
    return sum(
        1
        for e in events
        if e.get("event") == kind
        and all(e.get(k) == v for k, v in fields.items())
    )


def _serve_url(run_dir: str) -> str | None:
    try:
        with open(os.path.join(run_dir, "serve.ready"), encoding="utf-8") as f:
            info = json.load(f)
        return f"http://{info.get('host', '127.0.0.1')}:{info['port']}"
    except (OSError, ValueError, KeyError):
        return None


class _LoadBurst:
    """Concurrent embed clients hammering the serve endpoint; 429s are the
    point (rejects pin the co-scheduler's pressure signal at 1.0)."""

    def __init__(self, url: str, threads: int = BURST_THREADS):
        self.url = url
        self.stop = threading.Event()
        self.sent = 0
        self.rejected = 0
        self.failed = 0
        self._threads = [
            threading.Thread(target=self._loop, daemon=True)
            for _ in range(threads)
        ]

    def start(self) -> None:
        for t in self._threads:
            t.start()

    def _loop(self) -> None:
        while not self.stop.is_set():
            req = urllib.request.Request(
                self.url + "/v1/embed",
                data=_EMBED_BODY,
                headers=_JSON_HEADERS,
                method="POST",
            )
            try:
                with urllib.request.urlopen(req, timeout=30) as resp:
                    resp.read()
                self.sent += 1
            except urllib.error.HTTPError as e:
                e.close()
                if e.code == 429:
                    self.rejected += 1
                else:
                    self.failed += 1
            except Exception:  # noqa: BLE001 - server mid-swap/teardown
                self.failed += 1
                time.sleep(0.05)

    def finish(self) -> dict:
        self.stop.set()
        for t in self._threads:
            t.join(timeout=35.0)
        return {
            "sent": self.sent,
            "rejected": self.rejected,
            "failed": self.failed,
        }


def _generation_probe(url: str) -> tuple[int | None, int | None]:
    """One embed + one neighbors query over the returned embedding; the
    pair's generation headers are the consistency evidence."""
    req = urllib.request.Request(
        url + "/v1/embed",
        data=_EMBED_BODY,
        headers=_JSON_HEADERS,
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        wgen = resp.headers.get("X-Weights-Generation")
        embeddings = json.loads(resp.read())["embeddings"]
    req = urllib.request.Request(
        url + "/v1/neighbors",
        data=json.dumps({"queries": embeddings, "k": 3}).encode(),
        headers=_JSON_HEADERS,
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        cgen = resp.headers.get("X-Corpus-Generation")
        json.loads(resp.read())
    return (
        int(wgen) if wgen is not None else None,
        int(cgen) if cgen is not None else None,
    )


def _drive_coscheduler(
    cmd: list[str], env: dict, timeout_s: float, run_dir: str
) -> tuple[dict, int, dict]:
    """Run the co-scheduler while driving its lifecycle from outside:
    wait for the first swap, burst load until the shrink lands, ebb, and
    probe embed/neighbors generation consistency. Returns (summary line,
    returncode, drive evidence). Output goes to files, not pipes — the
    poll loop never drains, and a chatty run would deadlock a full pipe
    buffer."""
    burst = None
    load: dict = {}
    phase = "wait_swap"
    burst_deadline = 0.0
    last_probe_t = 0.0
    probe = (None, None)
    probes = 0
    with tempfile.TemporaryFile(mode="w+") as out_f, \
            tempfile.TemporaryFile(mode="w+") as err_f:
        proc = subprocess.Popen(
            cmd, env=env, stdout=out_f, stderr=err_f, text=True,
            cwd=REPO_ROOT,
        )
        deadline = time.monotonic() + timeout_s
        try:
            while proc.poll() is None and time.monotonic() < deadline:
                time.sleep(0.5)
                events = _read_events(run_dir)
                now = time.monotonic()
                if phase == "wait_swap":
                    url = _serve_url(run_dir)
                    if url is not None and _count(events, "swap") >= 1:
                        burst = _LoadBurst(url)
                        burst.start()
                        phase = "burst"
                        burst_deadline = now + BURST_MAX_S
                elif phase == "burst":
                    if (
                        _count(events, "reallocate", direction="shrink") >= 1
                        or now >= burst_deadline
                    ):
                        load = burst.finish()
                        phase = "ebb"
                elif phase == "ebb" and now - last_probe_t >= 2.0:
                    # opportunistic consistency probe; the LAST successful
                    # pair is the evidence (a draining server near the end
                    # simply stops updating it)
                    last_probe_t = now
                    url = _serve_url(run_dir)
                    try:
                        result = _generation_probe(url)
                    except Exception:  # noqa: BLE001 - mid-swap/draining
                        continue
                    if result[0] is not None and result[1] is not None:
                        probe = result
                        probes += 1
        finally:
            if burst is not None and not load:
                load = burst.finish()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
            raise RuntimeError(
                f"co-scheduler timed out after {timeout_s:.0f}s "
                f"(phase {phase})"
            )
        out_f.seek(0)
        err_f.seek(0)
        stdout, stderr = out_f.read(), err_f.read()
    for line in stderr.splitlines()[-20:]:
        print(f"# [cosched] {line}", file=sys.stderr)
    summary = None
    for line in reversed(stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                summary = json.loads(line)
                break
            except json.JSONDecodeError:
                continue
    if summary is None:
        raise RuntimeError(
            f"co-scheduler exited {proc.returncode} with no summary line"
        )
    drive = {
        "phase": phase,
        "load": load,
        "probe": {
            "weights_generation": probe[0],
            "corpus_generation": probe[1],
            "successes": probes,
        },
    }
    return summary, proc.returncode, drive


def main() -> None:
    try:
        signal.signal(signal.SIGTERM, _sigterm_backstop)
    except ValueError:  # non-main thread (embedded runs)
        pass
    timeout_s = float(os.environ.get("COSCHED_SMOKE_TIMEOUT_S", 1500))
    base_env = mhd._scrubbed_env()
    workdir = tempfile.mkdtemp(prefix="cosched_smoke_")
    run_dir = os.path.join(workdir, "cosched")
    ref_dir = os.path.join(workdir, "reference")

    summary, returncode, drive = _drive_coscheduler(
        [
            sys.executable, "-m", "simclr_tpu.coscheduler",
            "--nprocs", str(mhd.NPROCS),
            "--devices-per-proc", str(mhd.ELASTIC_DEVICES_PER_PROC),
            "--force-cpu",
            "--coord-timeout-s", base_env["JAX_COORDINATOR_TIMEOUT_S"],
            "--", *TRAIN_RECIPE, *COSCHED_OVERRIDES,
            f"experiment.save_dir={run_dir}",
        ],
        base_env, timeout_s, run_dir,
    )

    # no-reallocation reference: uninterrupted same-seed run on the same
    # 4-device global mesh, single process — the trajectory the elastic
    # shrink/grow-back cycle must preserve
    ref_env = dict(base_env)
    ref_env["JAX_PLATFORMS"] = "cpu"
    ref_env["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        f"{mhd.NPROCS * mhd.ELASTIC_DEVICES_PER_PROC}"
    )
    ref = subprocess.run(
        [
            sys.executable, "-m", "simclr_tpu.main", *TRAIN_RECIPE,
            f"experiment.save_dir={ref_dir}",
        ],
        env=ref_env, capture_output=True, text=True, timeout=timeout_s,
        cwd=REPO_ROOT,
    )
    for line in ref.stderr.splitlines()[-10:]:
        print(f"# [reference] {line}", file=sys.stderr)
    if ref.returncode != 0:
        raise RuntimeError(f"reference run exited {ref.returncode}")

    co_hist = mhd._load_results(run_dir, "cosched").get("loss_history", [])
    ref_hist = mhd._load_results(ref_dir, "reference").get("loss_history", [])
    co_losses = {int(e): float(v) for e, v in co_hist}
    ref_losses = {int(e): float(v) for e, v in ref_hist}
    epochs_match = sorted(co_losses) == sorted(ref_losses) and co_losses
    max_delta = (
        max(abs(co_losses[e] - ref_losses[e]) for e in co_losses)
        if epochs_match else None
    )
    parity = bool(epochs_match) and max_delta is not None and max_delta <= 5e-2

    events = _read_events(run_dir)
    train = summary.get("train") or {}
    swaps = int(summary.get("swaps", 0) or 0)
    swap_rejected = int(summary.get("swap_rejected", 0) or 0)
    reallocations = int(summary.get("reallocations", 0) or 0)
    releases = _count(events, "reallocate", direction="release")
    grow_back = int(train.get("grow_back_count", 0) or 0)
    wgen = drive["probe"]["weights_generation"]
    cgen = drive["probe"]["corpus_generation"]
    generation_consistent = (
        wgen is not None and cgen is not None and wgen == cgen and wgen >= 1
    )
    outcome = summary.get("outcome")
    ok = (
        outcome == "clean"
        and returncode == 0
        and swaps >= 2
        and swap_rejected == 0
        and reallocations >= 1
        and releases >= 1
        and grow_back >= 1
        and generation_consistent
        and parity
    )
    payload = {
        "metric": "cosched_smoke",
        "value": 1.0 if ok else 0.0,
        "unit": "bool",
        "outcome": outcome,
        "swaps": swaps,
        "swap_rejected": swap_rejected,
        "reallocations": reallocations,
        "releases": releases,
        "grow_back_count": grow_back,
        "serving_generation": summary.get("serving_generation"),
        "generation_consistent": generation_consistent,
        "parity": parity,
        "max_loss_delta": max_delta,
        "drive": drive,
        "events": {
            k: _count(events, k)
            for k in ("swap", "swap_rejected", "reallocate", "serve_scale")
        },
    }
    if not ok:
        failures = []
        if outcome != "clean":
            failures.append(f"outcome={outcome}")
        if returncode != 0:
            failures.append(f"exit={returncode}")
        if swaps < 2:
            failures.append(f"only {swaps} swap(s)")
        if swap_rejected:
            failures.append(f"{swap_rejected} swap(s) rejected without fault")
        if reallocations < 1:
            failures.append("pressure burst never triggered a shrink")
        if releases < 1:
            failures.append("ebb never released the lent host")
        if grow_back < 1:
            failures.append("training never grew back")
        if not generation_consistent:
            failures.append(
                f"embed generation {wgen} != corpus generation {cgen}"
            )
        if not parity:
            failures.append(f"loss trajectory diverged (max delta {max_delta})")
        payload["error"] = "; ".join(failures) or "unknown failure"
    mhd._emit_payload(payload)


if __name__ == "__main__":
    try:
        main()
    except Exception as exc:  # last-ditch contract keeper: one line, rc 0
        print(f"# unexpected error: {exc!r}", file=sys.stderr)
        mhd._emit_payload(_last_ditch(exc))
    sys.exit(0)
