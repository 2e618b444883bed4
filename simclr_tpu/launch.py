"""Multi-process launcher — the TPU-native analogue of the reference's
vendored ``launch.py``.

The reference spawns one process PER GPU and wires NCCL rendezvous env vars
into each (``/root/reference/launch.py:202-259``). Under SPMD there is one
process per HOST, so this launcher exists for the two situations where
something must start those host processes:

  * **Local multi-process testing** (``--nprocs N --devices-per-proc D``):
    a CPU dry-run. Spawns N processes on this machine, each a full JAX
    distributed participant with its own block of virtual CPU devices — the
    only way to exercise true multi-PROCESS semantics
    (``jax.make_array_from_process_local_data``, per-host input sharding,
    cross-process collectives over the distributed runtime) without a real
    multi-host slice. An 8-device single-process mesh cannot cover this: it
    has one address space and one input pipeline. Without
    ``--devices-per-proc`` every child would claim all of the host's chips
    (a chip belongs to one process, and nothing here partitions them): on
    hardware use the ``--proc-id`` form, one process per host.
  * **Unmanaged multi-host launch** (``--proc-id I --nprocs N --coordinator
    HOST:PORT``): runs the training module in-process on each host of a
    cluster that lacks auto-discovery (no Cloud TPU metadata, no SLURM).

Reference behaviors kept (they are launcher API, not NCCL details):
  * fail-fast: wait on children, kill survivors and raise on the first
    nonzero exit (``launch.py:255-259``);
  * ``OMP_NUM_THREADS=1`` guard when spawning >1 process per machine
    (``launch.py:216-223``);
  * pass-through of the training module and its Hydra-style overrides:
    ``python -m simclr_tpu.launch --nprocs 2 -m simclr_tpu.main
    parameter.epochs=1 ...``.

Rendezvous uses the JAX_COORDINATOR_ADDRESS / JAX_NUM_PROCESSES /
JAX_PROCESS_ID convention consumed by
:func:`simclr_tpu.parallel.multihost.maybe_initialize_multihost`.
"""

from __future__ import annotations

import argparse
import os
import runpy
import signal
import subprocess
import sys
import time


def _parse_args(argv: list[str] | None = None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(
        prog="python -m simclr_tpu.launch",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument(
        "--nprocs", type=int, default=1,
        help="total number of JAX processes (hosts) in the job; on hardware "
        "one process per host (--proc-id), spawning all of them locally is "
        "a CPU dry-run (--devices-per-proc)",
    )
    parser.add_argument(
        "--proc-id", type=int, default=None,
        help="this host's process id (multi-host mode; omit to spawn all "
        "processes locally)",
    )
    parser.add_argument(
        "--coordinator", default="127.0.0.1:12321",
        help="coordinator HOST:PORT (process 0's address in multi-host mode)",
    )
    parser.add_argument(
        "--devices-per-proc", type=int, default=None,
        help="virtual CPU devices per process (local testing mode; forces "
        "JAX_PLATFORMS=cpu and --xla_force_host_platform_device_count)",
    )
    parser.add_argument(
        "-m", dest="module", required=True,
        help="training module to run (e.g. simclr_tpu.main)",
    )
    parser.add_argument(
        "overrides", nargs="*",
        help="dotted config overrides passed through to the module",
    )
    return parser.parse_args(argv)


def _child_env(args: argparse.Namespace, proc_id: int) -> dict[str, str]:
    env = dict(os.environ)
    env["JAX_COORDINATOR_ADDRESS"] = args.coordinator
    env["JAX_NUM_PROCESSES"] = str(args.nprocs)
    env["JAX_PROCESS_ID"] = str(proc_id)
    if args.devices_per_proc:
        env["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={args.devices_per_proc}"
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + flag).strip()
    if args.proc_id is None and args.nprocs > 1 and "OMP_NUM_THREADS" not in env:
        # reference launch.py:216-223 — avoid N processes x all cores. The
        # guard is per-MACHINE: it applies to local spawn mode only; a
        # --proc-id multi-host launch runs one process per machine
        env["OMP_NUM_THREADS"] = "1"
    return env


def main(argv: list[str] | None = None) -> None:
    args = _parse_args(argv)
    if args.nprocs < 1:
        raise SystemExit("--nprocs must be >= 1")
    if args.devices_per_proc is not None and args.devices_per_proc < 1:
        raise SystemExit("--devices-per-proc must be >= 1")

    if args.proc_id is not None:
        # multi-host mode: become the training module on this host
        os.environ.update(_child_env(args, args.proc_id))
        sys.argv = [args.module] + list(args.overrides)
        runpy.run_module(args.module, run_name="__main__", alter_sys=True)
        return

    # local mode: spawn every process here. Spawning INSIDE the try keeps a
    # mid-spawn interrupt or Popen failure from orphaning children already
    # started (they would block in rendezvous forever waiting for peers).
    cmd = [sys.executable, "-m", args.module] + list(args.overrides)
    children: list[subprocess.Popen] = []
    try:
        for i in range(args.nprocs):
            children.append(subprocess.Popen(cmd, env=_child_env(args, i)))
        # poll ALL children: an ordered wait() would miss a crash of child k
        # while child 0 blocks in a collective waiting for it, hanging the
        # job instead of failing fast
        failed_rc: int | None = None
        while failed_rc is None and any(c.poll() is None for c in children):
            for child in children:
                rc = child.poll()
                if rc is not None and rc != 0:
                    failed_rc = rc
                    break
            else:
                time.sleep(0.2)
        if failed_rc is None:
            failed_rc = next((c.returncode for c in children if c.returncode), None)
        if failed_rc is not None:
            for child in children:
                if child.poll() is None:
                    child.send_signal(signal.SIGTERM)
            for child in children:
                child.wait()
            raise subprocess.CalledProcessError(failed_rc, cmd)
    except subprocess.CalledProcessError:
        raise  # children already reaped above
    except BaseException:  # interrupt or spawn failure: no orphans
        for child in children:
            if child.poll() is None:
                child.send_signal(signal.SIGTERM)
        raise


if __name__ == "__main__":
    main()
