"""Checkpoint save/restore (orbax) — params + optimizer + step.

The reference is save-only and params-only: rank 0 ``torch.save``s the
DDP-wrapped ``state_dict`` every ``save_model_epoch`` epochs
(``/root/reference/main.py:129-131``) and nothing can resume mid-run (SURVEY
§5.3-4). Here the whole :class:`TrainState` pytree (params, BN stats,
optimizer state, step counter) round-trips through orbax, giving exact
resume; loading just the model variables for eval/export is the restricted
case of the same mechanism.

Checkpoint directories are ``<save_dir>/epoch=<E>-<name>`` mirroring the
reference's ``epoch={E}-{output_model_name}`` filename scheme
(``main.py:129-131``) minus the ``.pt`` suffix, so downstream globbing in
eval/save_features enumerates them the same way the reference globs ``*.pt``
(``eval.py:248``).
"""

from __future__ import annotations

import hashlib
import os
import re

import jax
import numpy as np
import orbax.checkpoint as ocp

from simclr_tpu.obs.trace import recorder

_EPOCH_RE = re.compile(r"epoch=(\d+)-")

# integrity sidecar written next to each checkpoint directory: sha256 over
# every file the checkpoint contains, so a consumer (the serving engine
# above all — it must never answer traffic from a truncated restore) can
# verify the bytes on disk are the bytes that were saved
DIGEST_SUFFIX = ".sha256"


def digest_path(path: str) -> str:
    """Sidecar path for a checkpoint directory (``<path>.sha256``)."""
    return path.rstrip("/") + DIGEST_SUFFIX


def checkpoint_digest(path: str) -> str:
    """sha256 hex digest over a checkpoint directory's full contents.

    Hashes every regular file in sorted relative-path order, framing each
    with its path and size so file renames, truncations, and content swaps
    all change the digest. Deterministic across hosts: orbax writes the
    same bytes it later reads, and the walk order is sorted, not
    filesystem-dependent.
    """
    h = hashlib.sha256()
    path = os.path.abspath(path)
    files = []
    for root, _dirs, names in os.walk(path):
        for name in names:
            full = os.path.join(root, name)
            files.append((os.path.relpath(full, path), full))
    for rel, full in sorted(files):
        size = os.path.getsize(full)
        h.update(f"{rel}\x00{size}\x00".encode())
        with open(full, "rb") as f:
            for chunk in iter(lambda: f.read(1 << 20), b""):
                h.update(chunk)
    return h.hexdigest()


def checkpoint_name(epoch: int, output_model_name: str) -> str:
    """``epoch=<E>-<stem>`` (reference: ``f"epoch={epoch}-{name}.pt"``)."""
    stem = output_model_name
    if stem.endswith(".pt"):
        stem = stem[: -len(".pt")]
    return f"epoch={epoch}-{stem}"


def epoch_of(path: str) -> int:
    """Parse the epoch out of a checkpoint directory name (-1 if absent)."""
    m = _EPOCH_RE.search(os.path.basename(path.rstrip("/")))
    return int(m.group(1)) if m else -1


def list_checkpoints(target_dir: str) -> list[str]:
    """All checkpoint dirs under ``target_dir``, epoch-sorted.

    The eval/export analogue of the reference's ``Path(...).glob("*.pt")``
    (``/root/reference/eval.py:248``).
    """
    if not os.path.isdir(target_dir):
        return []
    out = []
    for entry in os.listdir(target_dir):
        full = os.path.join(target_dir, entry)
        # skip orbax's in-progress tmp dirs (name carries the final dir's
        # "epoch=" prefix): a crash mid-save must not offer a half-written
        # checkpoint to resume/eval. Integrity sidecars and atomic_write
        # temp files also carry the "epoch=" prefix and must never be
        # enumerated as checkpoints (they are files, but be explicit).
        if "orbax-checkpoint-tmp" in entry:
            continue
        if entry.endswith(DIGEST_SUFFIX) or ".tmp." in entry:
            continue
        if os.path.isdir(full) and _EPOCH_RE.search(entry):
            out.append(full)
    # Within an epoch, a "-preempt" checkpoint sorts AFTER the plain boundary
    # checkpoint: it was taken mid-way through the NEXT epoch, so it holds
    # strictly more steps. (A name tiebreak alone would get this wrong for
    # stems that sort before "preempt", e.g. "epoch=2-model" < "epoch=2-model-preempt"
    # but "epoch=2-preempt-…" < "epoch=2-supervised-…".)
    return sorted(
        out,
        key=lambda p: (
            epoch_of(p),
            1 if "-preempt" in os.path.basename(p) else 0,
            os.path.basename(p),
        ),
    )


def list_checkpoints_or_raise(target_dir: str) -> list[str]:
    """:func:`list_checkpoints`, raising ``FileNotFoundError`` when empty —
    the shared preflight of every checkpoint-consuming entry point
    (eval / save_features / export_torch)."""
    checkpoints = list_checkpoints(target_dir)
    if not checkpoints:
        raise FileNotFoundError(f"no checkpoints found under {target_dir!r}")
    return checkpoints


def save_checkpoint(path: str, state) -> None:
    """Save a pytree (TrainState or plain dict) to ``path`` atomically.

    After the orbax save commits, process 0 writes a sha256 sidecar
    (``<path>.sha256``, via ``ioutil.atomic_write`` so a crash leaves either
    no sidecar or a complete one) that :func:`restore_checkpoint` verifies
    before trusting the bytes — a truncated or bit-rotted checkpoint fails
    loudly at load instead of silently serving garbage embeddings.
    """
    path = os.path.abspath(path)
    spans = recorder()
    with spans.span("checkpoint/save"):  # to the commit: the close waits for it
        # Uncompressed: float32 weights and momentum give zstd 8% and cost it
        # 20 core-seconds of 26 at 4.5 GB, on cores the host shares; the
        # format is the standard one and restores through the same calls
        with ocp.Checkpointer(ocp.PyTreeCheckpointHandler(use_compression=False)) as ckptr:
            ckptr.save(path, args=ocp.args.PyTreeSave(state), force=True)
    if jax.process_index() == 0:
        from simclr_tpu.utils.ioutil import atomic_write

        with spans.span("checkpoint/digest"):
            digest = checkpoint_digest(path)
        with spans.span("checkpoint/sidecar"):
            atomic_write(
                digest_path(path),
                lambda f: f.write(f"{digest}  {os.path.basename(path)}\n"),
            )


class CheckpointCorruptionError(ValueError):
    """The on-disk checkpoint bytes do not match their recorded digest."""


def verify_checkpoint(path: str) -> bool:
    """Check ``path`` against its sha256 sidecar.

    Returns True when the digest matches, False when no sidecar exists (a
    legacy checkpoint saved before integrity sidecars landed — callers
    warn, not fail), and raises :class:`CheckpointCorruptionError` on a
    mismatch or an unparseable sidecar.
    """
    sidecar = digest_path(os.path.abspath(path))
    if not os.path.exists(sidecar):
        return False
    with open(sidecar) as f:
        recorded = f.read().split()
    if not recorded or len(recorded[0]) != 64:
        raise CheckpointCorruptionError(
            f"unparseable checkpoint digest sidecar {sidecar!r}"
        )
    actual = checkpoint_digest(path)
    if actual != recorded[0]:
        raise CheckpointCorruptionError(
            f"checkpoint {path!r} does not match its recorded sha256 "
            f"(recorded {recorded[0][:12]}…, actual {actual[:12]}…): the "
            f"checkpoint is truncated or corrupt — do not resume or serve "
            f"from it"
        )
    return True


def restore_checkpoint(path: str, target=None, *, verify: bool = True):
    """Restore into the structure/shardings of ``target``; with ``target=None``
    return the raw pytree (dict of numpy arrays) — the eval/export load path.

    With ``verify=True`` (default) the sha256 sidecar is checked first when
    present; legacy checkpoints without a sidecar load with a warning.
    """
    path = os.path.abspath(path)
    if verify:
        if not verify_checkpoint(path):
            from simclr_tpu.utils.logging import get_logger

            get_logger().warning(
                "checkpoint %s has no sha256 sidecar (saved before integrity "
                "sidecars landed); loading unverified", path,
            )
    if target is None:
        # Host-numpy restore, independent of the saving topology: the
        # StandardCheckpointer default re-applies the SAVED shardings, so a
        # checkpoint written on an 8-device mesh refuses to load in a
        # single-device process (train on a pod, serve/eval on one chip).
        with ocp.PyTreeCheckpointer() as ckptr:
            tree = ckptr.metadata(path).item_metadata.tree
            restore_args = jax.tree.map(
                lambda _: ocp.RestoreArgs(restore_type=np.ndarray), tree
            )
            return ckptr.restore(
                path, args=ocp.args.PyTreeRestore(restore_args=restore_args)
            )
    with ocp.StandardCheckpointer() as ckptr:
        return ckptr.restore(path, target)


def delete_checkpoint(path: str) -> None:
    """Remove a checkpoint directory and its digest sidecar (the supervised
    best-only policy, ``/root/reference/supervised.py:151-162``)."""
    import shutil

    if os.path.isdir(path):
        shutil.rmtree(path)
    sidecar = digest_path(path)
    if os.path.exists(sidecar):
        os.unlink(sidecar)


def latest_checkpoint(save_dir: str) -> str | None:
    """Newest checkpoint in a run dir, for ``--resume`` semantics."""
    ckpts = list_checkpoints(save_dir)
    return ckpts[-1] if ckpts else None


def restore_checkpoint_with_fallback(save_dir: str, target=None):
    """Restore the newest checkpoint whose sha256 sidecar verifies.

    Walks the run's checkpoints newest-first; a corrupt one (digest mismatch)
    is logged and skipped, and restore falls back to the next-older verified
    checkpoint — losing a few epochs of progress beats losing the run.
    Returns ``(restored, path)``, or ``(None, None)`` when the directory holds
    no checkpoints at all (a fresh run). Raises
    :class:`CheckpointCorruptionError` only when checkpoints exist but NONE
    verifies — there is nothing trustworthy to resume from.
    """
    from simclr_tpu.utils.logging import get_logger

    ckpts = list_checkpoints(save_dir)
    if not ckpts:
        return None, None
    skipped = []
    for path in reversed(ckpts):
        try:
            restored = restore_checkpoint(path, target)
        except CheckpointCorruptionError as e:
            skipped.append(path)
            get_logger().warning(
                "skipping corrupt checkpoint %s (%s); falling back to the "
                "previous one", path, e,
            )
            continue
        if skipped:
            get_logger().warning(
                "restored %s after skipping %d corrupt checkpoint(s): %s",
                path, len(skipped), ", ".join(os.path.basename(p) for p in skipped),
            )
        return restored, path
    raise CheckpointCorruptionError(
        f"all {len(ckpts)} checkpoint(s) under {save_dir!r} fail sha256 "
        f"verification; nothing trustworthy to resume from"
    )
