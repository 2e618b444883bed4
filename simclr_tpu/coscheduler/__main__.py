"""CLI: ``python -m simclr_tpu.coscheduler --nprocs N --devices-per-proc D
[--force-cpu] [--coord-timeout-s T] -- <overrides...>``.

Loads ``conf/cosched.yaml`` (which composes the full pretrain root, so
every training override works unchanged), validates the co-scheduling
surface, and runs :class:`~simclr_tpu.coscheduler.core.CoScheduler`.
Overrides in the ``serve.*``/``cosched.*`` namespaces configure this
process only; everything else is forwarded to the training children.
Prints the run summary as one JSON line (the same contract as
``python -m simclr_tpu.supervisor.elastic``).
"""

from __future__ import annotations

import argparse
import json
import logging
import os
import sys


def main(argv: list[str] | None = None) -> int:
    from simclr_tpu.config import (
        ConfigError,
        check_cosched_conf,
        load_config,
        resolve_save_dir,
    )

    parser = argparse.ArgumentParser(
        prog="python -m simclr_tpu.coscheduler",
        description="Continuous train+serve co-scheduler: supervised "
        "pretraining + checkpoint-hot-reloading serve tier on one pod.",
        epilog="On hardware a chip belongs to one process: run ONE process per host. All of this command's children start on this machine and nothing partitions its chips between them, so without --force-cpu they would each claim every chip; it has only ever run as a CPU dry-run. The serve tier "
        "also initializes JAX in this parent process before the training "
        "children start.",
    )
    parser.add_argument(
        "--nprocs", type=int, required=True,
        help="training hosts (JAX processes) in the full topology",
    )
    parser.add_argument(
        "--devices-per-proc", type=int, required=True,
        help="accelerator devices per training host (batch-rescale math)",
    )
    parser.add_argument(
        "--force-cpu", action="store_true",
        help="virtual CPU devices for children AND the serve tier (dryrun)",
    )
    parser.add_argument(
        "--coord-timeout-s", type=float, default=None,
        help="rendezvous fail-fast deadline exported to every child",
    )
    parser.add_argument("rest", nargs=argparse.REMAINDER)
    args = parser.parse_args(sys.argv[1:] if argv is None else argv)

    overrides = list(args.rest)
    if overrides and overrides[0] == "--":
        overrides = overrides[1:]

    try:
        cfg = load_config("cosched", overrides=overrides)
        check_cosched_conf(cfg)
        save_dir = resolve_save_dir(cfg)
    except ConfigError as e:
        print(f"coscheduler: {e}", file=sys.stderr)
        return 2
    if not cfg.select("experiment.save_dir"):
        cfg.update_dotted("experiment.save_dir", save_dir, allow_new=True)

    if args.force_cpu:
        # the serve tier lives in THIS process and needs its own virtual
        # device slice, sized for the fully-grown tier; must land before
        # the first jax import (children get theirs via group_env)
        max_serve = int(
            cfg.select(
                "cosched.max_serve_devices",
                cfg.select("cosched.serve_devices", 1),
            )
        )
        os.environ["JAX_PLATFORMS"] = "cpu"
        flag = f"--xla_force_host_platform_device_count={max_serve}"
        xla_flags = " ".join(
            part
            for part in os.environ.get("XLA_FLAGS", "").split()
            if not part.startswith("--xla_force_host_platform_device_count=")
        )
        os.environ["XLA_FLAGS"] = (xla_flags + " " + flag).strip()

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )

    from simclr_tpu.coscheduler.core import CoScheduler
    from simclr_tpu.utils.platform import configure_compilation_cache

    configure_compilation_cache()
    # serve./cosched. keys configure this process; the training children's
    # strict pretrain config would reject them
    train_overrides = [
        o
        for o in overrides
        if o.split("=", 1)[0].lstrip("+").split(".")[0]
        not in ("serve", "cosched")
    ]
    try:
        co = CoScheduler(
            cfg,
            nprocs=args.nprocs,
            devices_per_proc=args.devices_per_proc,
            force_cpu=args.force_cpu,
            coord_timeout_s=args.coord_timeout_s,
            train_overrides=train_overrides,
        )
    except ConfigError as e:
        print(f"coscheduler: {e}", file=sys.stderr)
        return 2
    summary = co.run()
    print(json.dumps(summary), flush=True)
    if summary.get("outcome") == "clean":
        return 0
    return int(summary.get("exit", 1) or 1)


if __name__ == "__main__":
    sys.exit(main())
