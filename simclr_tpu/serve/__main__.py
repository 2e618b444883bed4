"""``python -m simclr_tpu.serve`` — the embedding server entry point.

Same override surface as every other entry point::

    python -m simclr_tpu.serve \
        experiment.target_dir=results/cifar10/seed-7/<date>/<time> \
        serve.port=8000 serve.max_batch=256 serve.max_delay_ms=5
"""

from __future__ import annotations

import sys


def main(argv: list[str] | None = None) -> int:
    from simclr_tpu.utils.platform import configure_compilation_cache

    configure_compilation_cache()
    from simclr_tpu.config import load_config
    from simclr_tpu.serve.server import run_server

    cfg = load_config(
        "serve", overrides=list(sys.argv[1:] if argv is None else argv)
    )
    return run_server(cfg)


if __name__ == "__main__":
    sys.exit(main())
