"""Multi-host (DCN) runtime initialization.

The reference's multi-host story is NCCL ``env://`` rendezvous driven by a
vendored launcher exporting MASTER_ADDR/PORT/RANK per process
(``/root/reference/launch.py:209-229``) — and is in fact broken multi-node
because the global rank is taken from ``local_rank`` (SURVEY §2.5.4). The
TPU-native shape: ONE process per host calls ``jax.distributed.initialize``
once; afterwards ``jax.devices()`` spans every chip in the slice and the
same SPMD program runs unchanged — collectives ride ICI within a host's
chips and DCN across hosts, laid out by XLA from the mesh.

On Cloud TPU slices ``jax.distributed.initialize()`` discovers coordinator,
process count, and process id from the TPU metadata automatically; explicit
values (or the standard ``JAX_COORDINATOR_ADDRESS`` / ``JAX_NUM_PROCESSES``
/ ``JAX_PROCESS_ID`` env vars) are only needed off-cloud. This module wraps
that in an idempotent, single-host-safe call used by every entry point.
"""

from __future__ import annotations

import os

import jax

from simclr_tpu.utils.logging import get_logger

logger = get_logger()

_initialized = False

# every env var that parameterizes one process-group generation; a remesh
# must rewrite ALL of them (a stale JAX_NUM_PROCESSES from the old topology
# would hang the new rendezvous waiting for hosts that no longer exist)
GROUP_ENV_VARS = (
    "JAX_COORDINATOR_ADDRESS",
    "COORDINATOR_ADDRESS",
    "JAX_NUM_PROCESSES",
    "JAX_PROCESS_ID",
)


def group_env(
    base: dict,
    *,
    coordinator: str,
    num_processes: int,
    process_id: int,
    devices_per_proc: int | None = None,
    coord_timeout_s: float | None = None,
) -> dict:
    """Child env for ONE generation of a process group.

    A live ``jax.distributed`` group cannot be resized: elasticity is a full
    teardown of the old group's processes plus a relaunch under a REWRITTEN
    rendezvous env — new coordinator port, new ``JAX_NUM_PROCESSES``, ranks
    reassigned 0..N-1 over the surviving hosts. This helper is the one place
    that rewrite happens (the elastic supervisor composes every child env
    through it): stale group vars are scrubbed from ``base`` first, so a
    child can never rendezvous against the previous topology.

    ``devices_per_proc`` forces the CPU backend with that many virtual
    devices (the 2-process dryrun harness); ``coord_timeout_s`` exports the
    fail-fast rendezvous deadline ``maybe_initialize_multihost`` honors.
    """
    env = {k: v for k, v in base.items() if k not in GROUP_ENV_VARS}
    env["JAX_COORDINATOR_ADDRESS"] = coordinator
    env["JAX_NUM_PROCESSES"] = str(int(num_processes))
    env["JAX_PROCESS_ID"] = str(int(process_id))
    if devices_per_proc:
        env["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={int(devices_per_proc)}"
        xla_flags = " ".join(
            part
            for part in env.get("XLA_FLAGS", "").split()
            if not part.startswith("--xla_force_host_platform_device_count=")
        )
        env["XLA_FLAGS"] = (xla_flags + " " + flag).strip()
    if coord_timeout_s is not None:
        env["JAX_COORDINATOR_TIMEOUT_S"] = str(coord_timeout_s)
    return env


def maybe_initialize_multihost() -> bool:
    """Initialize the distributed runtime when configured; returns True when
    running multi-host after the call.

    Triggers when any standard JAX cluster variable is set, or on TPU
    platforms where auto-discovery works. Safe to call repeatedly, and a
    silent no-op for plain single-host CPU/GPU development runs.
    """
    global _initialized
    if _initialized:
        return jax.process_count() > 1

    env_configured = any(
        os.environ.get(k)
        for k in (
            "JAX_COORDINATOR_ADDRESS",
            "COORDINATOR_ADDRESS",
            "JAX_NUM_PROCESSES",
        )
    )
    # TPU-slice metadata only counts when we are actually running on TPU —
    # a CPU-forced dev run on a TPU host must not try to rendezvous.
    # JAX_PLATFORMS is a priority list; its FIRST entry is the default
    # backend, so 'cpu' or 'cpu,tpu' both mean a CPU run. A slice of one
    # host (TPU_WORKER_HOSTNAMES names a single worker) has nobody to
    # rendezvous with: its process already sees every chip.
    platforms = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    cpu_forced = platforms.split(",")[0].strip() == "cpu"
    workers = [
        h for h in os.environ.get("TPU_WORKER_HOSTNAMES", "").split(",")
        if h.strip()
    ]
    on_tpu_slice = (
        len(workers) > 1 or os.environ.get("MEGASCALE_COORDINATOR_ADDRESS")
    ) and not cpu_forced
    if not env_configured and not on_tpu_slice:
        return False

    # jax's no-arg initialize() only discovers process count/id on managed
    # clusters (Cloud TPU metadata, SLURM, OpenMPI, k8s — jax/_src/clusters).
    # The generic JAX_NUM_PROCESSES / JAX_PROCESS_ID variables this module
    # documents (and simclr_tpu.launch exports) are our own convention, so
    # pass them explicitly when present.
    kwargs: dict = {}
    coordinator = os.environ.get("JAX_COORDINATOR_ADDRESS") or os.environ.get(
        "COORDINATOR_ADDRESS"
    )
    if coordinator and os.environ.get("JAX_NUM_PROCESSES"):
        num_processes = int(os.environ["JAX_NUM_PROCESSES"])
        if num_processes > 1 and "JAX_PROCESS_ID" not in os.environ:
            # defaulting every host to process 0 would hang the coordinator
            # (waiting for N distinct ids that never arrive) instead of
            # failing fast on all hosts
            raise RuntimeError(
                "JAX_NUM_PROCESSES > 1 requires JAX_PROCESS_ID to be set on "
                "every host (0..N-1)"
            )
        kwargs = {
            "coordinator_address": coordinator,
            "num_processes": num_processes,
            "process_id": int(os.environ.get("JAX_PROCESS_ID", "0")),
        }
        # JAX_COORDINATOR_TIMEOUT_S: rendezvous deadline in seconds. jax's
        # default initialization_timeout is 300 s, so a half-configured pod
        # (one host missing, a typo'd coordinator address) hangs five
        # minutes before the loud RuntimeError below; ops set this low
        # (the multihost_dryrun watcher stage uses it) to fail fast instead.
        timeout_s = os.environ.get("JAX_COORDINATOR_TIMEOUT_S")
        if timeout_s:
            try:
                kwargs["initialization_timeout"] = int(float(timeout_s))
            except ValueError:
                raise RuntimeError(
                    "JAX_COORDINATOR_TIMEOUT_S must be a number of seconds, "
                    f"got {timeout_s!r}"
                ) from None
    try:
        jax.distributed.initialize(**kwargs)
        _initialized = True
        logger.info(
            "multihost: process %d/%d, %d global devices",
            jax.process_index(), jax.process_count(), jax.device_count(),
        )
    except (RuntimeError, ValueError) as e:
        benign_double_init = (
            "only be called once" in str(e) or "already initialized" in str(e).lower()
        )
        if benign_double_init:
            # the runtime IS initialized (someone else did it) — record that
            # so later entry-point calls don't re-attempt and re-warn
            _initialized = True
            logger.warning("jax.distributed already initialized elsewhere: %s", e)
        else:
            # Multihost was explicitly requested (cluster env vars) or this
            # is a real TPU slice: silently degrading to N independent
            # single-process jobs would have every host believe it is
            # process 0 — all logging, all writing checkpoints to the same
            # save_dir. Fail loudly instead.
            raise RuntimeError(
                "multihost rendezvous failed; set BOTH "
                "JAX_COORDINATOR_ADDRESS and JAX_NUM_PROCESSES (and "
                "JAX_PROCESS_ID on every host), or unset them for a "
                "single-process run"
            ) from e
    return jax.process_count() > 1
