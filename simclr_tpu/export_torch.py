"""CLI: export orbax checkpoints as reference-compatible ``.pt`` files.

Closes the migration loop from the command line (the library surface is
:mod:`simclr_tpu.utils.torch_export`): every checkpoint directory under
``--target-dir`` (the same enumeration eval/save_features use, mirroring
the reference's ``*.pt`` glob over ``experiment.target_dir``) becomes a
``<name>.pt`` state dict the reference's own ``torch.load`` +
``load_state_dict`` consume (``/root/reference/eval.py:256-263``).

    python -m simclr_tpu.export_torch \
        --target-dir results/cifar10/seed-7/... --out-dir exported/

Plain argparse rather than the Hydra-style config tree: this tool is an
auxiliary bridge with no reference counterpart, so it takes no recipe
keys — only paths and the model identity.
"""

from __future__ import annotations

import argparse
import os

from simclr_tpu.utils.checkpoint import list_checkpoints_or_raise
from simclr_tpu.utils.torch_export import save_torch_checkpoint


def main(argv: list[str] | None = None) -> list[str]:
    from simclr_tpu.utils.platform import configure_compilation_cache

    configure_compilation_cache()
    ap = argparse.ArgumentParser(
        prog="python -m simclr_tpu.export_torch", description=__doc__
    )
    ap.add_argument("--target-dir", required=True,
                    help="directory of orbax checkpoint dirs (epoch=N-...)")
    ap.add_argument("--out-dir", required=True)
    ap.add_argument("--base-cnn", default="resnet18")
    ap.add_argument("--kind", choices=["contrastive", "supervised"],
                    default="contrastive")
    ap.add_argument("--ddp-prefix", action="store_true",
                    help="prefix keys with 'module.' like the reference's "
                         "DDP-wrapped saves")
    args = ap.parse_args(argv)

    from simclr_tpu.eval import load_model_variables

    checkpoints = list_checkpoints_or_raise(args.target_dir)
    os.makedirs(args.out_dir, exist_ok=True)
    written = []
    for ckpt in checkpoints:
        variables = load_model_variables(ckpt)
        path = os.path.join(args.out_dir, os.path.basename(ckpt) + ".pt")
        save_torch_checkpoint(
            path, variables, args.base_cnn, args.kind, args.ddp_prefix
        )
        print(path)
        written.append(path)
    return written


if __name__ == "__main__":
    main()
