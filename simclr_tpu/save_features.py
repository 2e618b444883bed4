"""Feature export: clean and augmentation-averaged features as ``.npy``.

TPU-native counterpart of ``/root/reference/save_features.py``: for each
checkpoint in ``experiment.target_dir``,

  * dump clean (no-augmentation) train/val features + labels as four ``.npy``
    files (``save_features.py:152-163``);
  * dump augmentation-averaged train features: a running mean over 20 passes
    of one stochastic SimCLR view, saved at t ∈ {1, 5, 20}
    (``save_features.py:166-179``).

    python -m simclr_tpu.save_features experiment.target_dir=results/...

Uses the eval config (same as the reference, ``save_features.py:119``).
"""

from __future__ import annotations

import math
import os
import sys

import jax
import numpy as np

from simclr_tpu.config import Config, check_save_features_conf, load_config, resolve_save_dir
from simclr_tpu.data.cifar import load_dataset
from simclr_tpu.eval import (
    build_eval_model,
    extract_features,
    load_model_variables,
)
from simclr_tpu.parallel.mesh import (
    batch_sharding,
    mesh_from_config,
    process_local_rows,
    put_global_batch,
    validate_per_device_batch,
)
from simclr_tpu.parallel.steps import make_augmented_encode_step
from simclr_tpu.utils.checkpoint import list_checkpoints_or_raise
from simclr_tpu.utils.fetch import fetch
from simclr_tpu.utils.ioutil import atomic_write
from simclr_tpu.utils.logging import get_logger, is_logging_host

logger = get_logger()

# reference: 20 passes, snapshots at 1/5/20 (save_features.py:166-179)
NUM_AUGMENTATIONS = 20
SNAPSHOT_PASSES = (1, 5, 20)


def augmented_features(
    model, variables, images: np.ndarray, mesh, batch: int, strength: float,
    seed: int, num_passes: int, snapshots: tuple[int, ...],
    use_full_encoder: bool = False,
) -> dict[int, np.ndarray]:
    """Running mean of single-view augmented features, snapshotted at
    ``snapshots`` pass counts (``/root/reference/save_features.py:166-179``)."""
    encode = make_augmented_encode_step(
        model, mesh, strength=strength, use_full_encoder=use_full_encoder
    )
    sharding = batch_sharding(mesh)
    n = len(images)
    steps = math.ceil(n / batch)
    pad = steps * batch - n
    padded = (
        np.concatenate([images, np.zeros((pad, *images.shape[1:]), images.dtype)])
        if pad
        else images
    )
    local = process_local_rows(batch)  # multi-host: upload only this
    # process's row block of each chunk (see eval.extract_features)
    mean = None
    out: dict[int, np.ndarray] = {}
    for t in range(1, num_passes + 1):
        feats = []
        for i in range(steps):
            chunk = put_global_batch(padded[i * batch : (i + 1) * batch][local], sharding)
            rng = jax.random.fold_in(jax.random.key(seed), t * steps + i)
            # dispatch only; the device->host sync happens once per pass so
            # upload/compute pipeline across chunks (see eval.extract_features)
            feats.append(encode(variables["params"], variables["batch_stats"], chunk, rng))
        pass_feats = np.concatenate([fetch(f) for f in feats])[:n]
        mean = pass_feats if mean is None else mean + (pass_feats - mean) / t
        if t in snapshots:
            out[t] = mean.copy()
    return out


def run_save_features(cfg: Config) -> list[str]:
    check_save_features_conf(cfg)
    mesh = mesh_from_config(cfg)
    synthetic_ok = bool(cfg.select("experiment.synthetic_data", False))
    data_dir = cfg.select("experiment.data_dir")
    train_ds = load_dataset(
        cfg.experiment.name, "train", data_dir=data_dir, synthetic_ok=synthetic_ok,
        synthetic_size=cfg.select("experiment.synthetic_size"),
        synthetic_noise=cfg.select("experiment.synthetic_noise"),
    )
    val_ds = load_dataset(
        cfg.experiment.name, "test", data_dir=data_dir, synthetic_ok=synthetic_ok,
        synthetic_size=cfg.select("experiment.synthetic_size"),
        synthetic_noise=cfg.select("experiment.synthetic_noise"),
    )

    model = build_eval_model(cfg)
    batch = validate_per_device_batch(int(cfg.experiment.batches), mesh)
    use_full_encoder = bool(cfg.parameter.use_full_encoder)
    strength = float(cfg.select("experiment.strength", 0.5))
    seed = int(cfg.parameter.seed)
    out_dir = resolve_save_dir(cfg)
    if is_logging_host():
        os.makedirs(out_dir, exist_ok=True)

    written: list[str] = []

    def save(name: str, array: np.ndarray) -> None:
        path = os.path.join(out_dir, name)
        if is_logging_host():
            # atomic: a SIGKILL mid-write must not leave a truncated .npy
            # that the resume existence-gate would then carry forward as
            # complete. The file-object form keeps np.save from appending
            # a second .npy suffix to the tmp name.
            atomic_write(path, lambda f: np.save(f, array), mode="wb")
        written.append(path)

    checkpoints = list_checkpoints_or_raise(str(cfg.experiment.target_dir))

    # experiment.resume=true: skip checkpoints whose full export set already
    # exists — a crashed multi-checkpoint export (20 augmentation passes per
    # checkpoint are the expensive part) resumes at checkpoint granularity.
    # Improvement over the reference (redoes everything, save_features.py).
    # Multi-process: out_dir must be a shared filesystem so every process
    # makes the SAME skip decision (only process 0 writes; a per-host local
    # out_dir would desynchronize the collective extract path) — the same
    # contract as checkpoint and eval-sweep resume.
    resume = bool(cfg.select("experiment.resume", False))

    for ckpt in checkpoints:
        key = os.path.basename(ckpt)
        expected = [
            f"{key}.train.features.npy", f"{key}.train.labels.npy",
            f"{key}.val.features.npy", f"{key}.val.labels.npy",
        ] + [f"{key}.train.aug-{t}.features.npy" for t in SNAPSHOT_PASSES]
        if resume and all(
            os.path.exists(os.path.join(out_dir, p)) for p in expected
        ):
            logger.info("Skipping %s (features already exported)", key)
            written.extend(os.path.join(out_dir, p) for p in expected)
            continue
        logger.info("Extracting features with %s", key)
        variables = load_model_variables(ckpt)

        # clean features, train + val (reference save_features.py:152-163)
        train_X = extract_features(
            model, variables, train_ds.images, mesh, batch, use_full_encoder
        )
        val_X = extract_features(
            model, variables, val_ds.images, mesh, batch, use_full_encoder
        )
        save(f"{key}.train.features.npy", train_X)
        save(f"{key}.train.labels.npy", train_ds.labels)
        save(f"{key}.val.features.npy", val_X)
        save(f"{key}.val.labels.npy", val_ds.labels)

        # augmentation-averaged train features (save_features.py:166-179)
        snapshots = augmented_features(
            model, variables, train_ds.images, mesh, batch, strength, seed,
            NUM_AUGMENTATIONS, SNAPSHOT_PASSES, use_full_encoder,
        )
        for t, mean in snapshots.items():
            save(f"{key}.train.aug-{t}.features.npy", mean)

    return written


def main(argv: list[str] | None = None):
    from simclr_tpu.parallel.multihost import maybe_initialize_multihost
    from simclr_tpu.utils.platform import configure_compilation_cache

    configure_compilation_cache()
    maybe_initialize_multihost()
    from simclr_tpu.config import run_multirun, split_multirun_flag

    multirun, args = split_multirun_flag(list(sys.argv[1:] if argv is None else argv))
    if multirun:
        return run_multirun(run_save_features, "eval", args)
    cfg = load_config("eval", overrides=args)
    return run_save_features(cfg)


if __name__ == "__main__":
    main()
