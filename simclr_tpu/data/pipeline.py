"""Host-side input pipeline: shuffle, shard, batch, device feed.

Replaces the reference's ``DistributedSampler`` + ``DataLoader(num_workers=8,
pin_memory, drop_last)`` stack (``/root/reference/main.py:169-173``) with the
SPMD-native shape: ONE process per host iterates the epoch, draws globally
shuffled batches, keeps only its own host's rows, and ``device_put``s them
with a batch-sharded ``NamedSharding`` so every chip holds exactly its shard.
Augmentation happens on device inside the jitted step (see
``data/augment.py``), so the host only moves raw uint8.

Parity points (SURVEY §2.5.11):
  * per-epoch reshuffle seeded by ``seed + epoch`` — DistributedSampler's
    ``set_epoch`` determinism (``/root/reference/main.py:101-102``);
  * ``drop_last=True`` truncation: ``steps = N // global_batch``;
  * global batch = per-device batch x number of data shards, matching the
    reference's per-GPU ``experiment.batches`` semantics.
"""

from __future__ import annotations

from collections.abc import Iterator

import jax
import numpy as np

from simclr_tpu.data.cifar import Dataset
from simclr_tpu.native.lib import DEFAULT_THREADS, gather_rows2
from simclr_tpu.obs.trace import recorder
from simclr_tpu.parallel.mesh import put_global_batch


def epoch_permutation(num_samples: int, seed: int, epoch: int) -> np.ndarray:
    """Deterministic per-epoch shuffle (DistributedSampler ``set_epoch``)."""
    return np.random.default_rng(np.uint64(seed) + np.uint64(epoch)).permutation(
        num_samples
    )


class EpochIterator:
    """Iterates one split in globally-shuffled, host-sharded batches.

    Yields dicts with uint8 ``image`` (host-local rows of the global batch)
    and int32 ``label``. With ``sharding`` set, arrays are ``device_put`` so
    downstream ``jit`` consumes already-sharded global arrays (single-host:
    the full global batch; multi-host: this host's rows assembled into a
    global array via ``make_array_from_process_local_data``).
    """

    def __init__(
        self,
        dataset: Dataset,
        global_batch: int,
        seed: int = 0,
        shuffle: bool = True,
        sharding: jax.sharding.NamedSharding | None = None,
        drop_last: bool = True,
        gather_threads: int | None = None,
    ):
        if global_batch <= 0:
            raise ValueError("global_batch must be positive")
        self.dataset = dataset
        self.global_batch = global_batch
        self.seed = seed
        self.shuffle = shuffle
        self.sharding = sharding
        self.drop_last = drop_last
        # native gather thread-pool width; the reference's parameter
        # 'num_workers' (DataLoader workers) maps here. 0 means
        # single-threaded (like num_workers=0), not "use the default".
        self.gather_threads = (
            gather_threads if gather_threads is not None else DEFAULT_THREADS
        )
        n = len(dataset)
        self.steps_per_epoch = n // global_batch if drop_last else -(-n // global_batch)
        if self.steps_per_epoch == 0:
            raise ValueError(
                f"dataset of {n} samples smaller than global batch {global_batch}"
            )
        if not drop_last and sharding is not None and n % global_batch:
            raise ValueError(
                f"drop_last=False with a device sharding requires the dataset "
                f"size ({n}) to divide the global batch ({global_batch}): a "
                f"partial final batch cannot be laid out over the mesh (pad "
                f"the tail on the host instead, as supervised.py does)"
            )
        n_proc = jax.process_count()
        if global_batch % n_proc:
            raise ValueError(
                f"global batch {global_batch} must be divisible by the "
                f"process count {n_proc}; otherwise hosts would silently "
                f"drop {global_batch % n_proc} rows per step"
            )

    def _order(self, epoch: int) -> np.ndarray:
        if self.shuffle:
            return epoch_permutation(len(self.dataset), self.seed, epoch)
        return np.arange(len(self.dataset))

    def batches(self, epoch: int) -> Iterator[dict[str, np.ndarray | jax.Array]]:
        # spans (obs/trace.py): this generator runs on the prefetch thread;
        # `step` is the optimizer step a batch feeds, the number the host
        # loop's `dispatch` span of the same batch carries
        spans = recorder()
        with spans.span("boundary/shuffle", epoch=epoch):
            order = self._order(epoch)
        n_proc = jax.process_count()
        proc = jax.process_index()
        for step in range(self.steps_per_epoch):
            ids = {"epoch": epoch, "step": (epoch - 1) * self.steps_per_epoch + step + 1}
            idx = order[step * self.global_batch : (step + 1) * self.global_batch]
            # each host materializes only its contiguous row block
            per_host = len(idx) // n_proc if n_proc > 1 else len(idx)
            local_idx = idx[proc * per_host : (proc + 1) * per_host]
            # native multithreaded row gather (numpy-take fallback inside)
            with spans.span("gather", n=len(local_idx), **ids):
                images, labels = gather_rows2(
                    self.dataset.images, self.dataset.labels, local_idx,
                    n_threads=self.gather_threads,
                )
            batch = {"image": images, "label": labels}
            if self.sharding is not None:
                with spans.span(
                    "device_put", n=images.nbytes + labels.nbytes, **ids
                ):
                    batch = {
                        k: self._to_device(v, k) for k, v in batch.items()
                    }
            yield batch

    def _to_device(self, array: np.ndarray, name: str) -> jax.Array:
        return put_global_batch(array, self.sharding)
