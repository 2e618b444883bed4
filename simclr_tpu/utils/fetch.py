"""Device array -> host numpy, multi-host safe — the one fetch helper.

Shared by eval's feature extraction, save_features' augmentation averaging,
and the serving engine (``simclr_tpu/serve/engine.py``) so every surface
that materializes device output on the host goes through the same
multi-host-aware path (previously a private ``eval._fetch`` that
save_features reached into across modules).
"""

from __future__ import annotations

import jax
import numpy as np


def fetch(x: jax.Array) -> np.ndarray:
    """Device array -> host numpy, multi-host safe.

    Under multi-host SPMD a sharded output spans chips this process cannot
    address; ``process_allgather`` assembles the full array on every host
    (the arrays fetched here are small: N x 512 floats). Single-process,
    this is a plain ``np.asarray`` value fetch, which also waits for the
    computation.
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(x, tiled=True))
    return np.asarray(x)
