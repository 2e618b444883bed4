"""Training state pytree.

The reference keeps four loose Python objects per process — DDP-wrapped
module, SGD/LARC optimizer, torch scheduler, and the int epoch/step counters
(``/root/reference/main.py:85-120``). Under SPMD-with-jit, all mutable train
state must be one pytree that the compiled step consumes and returns (donated,
so XLA updates it in place). Checkpointing this one pytree gives params +
optimizer + step resume — a capability the reference lacks (SURVEY §5.3-4:
save-only, params-only).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import struct


@struct.dataclass
class TrainState:
    """All mutable training state as a single donated pytree.

    ``step`` is the global optimizer-step counter driving the LR schedule
    (the reference's ``current_step``, ``/root/reference/main.py:104-120``).
    ``batch_stats`` are BatchNorm running stats — with the batch sharded over
    the data axis these are global-batch statistics, i.e. reference SyncBN.
    """

    step: jnp.ndarray
    params: Any
    batch_stats: Any
    opt_state: Any


def init_variables(model, rng: jax.Array, sample, **init_kwargs):
    """``model.init`` on zeros of ``sample``'s shape and dtype, as one
    compiled program (see :func:`create_train_state`)."""

    def init(key):
        return model.init(key, jnp.zeros(sample.shape, sample.dtype), **init_kwargs)

    return jax.jit(init)(rng)


def create_train_state(model, tx, rng: jax.Array, sample_batch, shardings=None) -> TrainState:
    """Initial params / stats / opt-state as the output of one compiled program.

    Run eagerly, ``model.init`` and ``tx.init`` are one small device program
    per initializer and per ``zeros_like`` (about 160 for ResNet-18 and 290
    for ResNet-50), each compiled anew by every process because none takes
    long enough for the persistent cache. Under one ``jit`` the forward pass
    flax runs to create the variables is dead code. The random bits are the
    same; on the CPU so is every value, while on a TPU the truncated-normal
    kernels differ from an eager init's in the last digits (under 2e-6 of a
    leaf's largest weight): their scalars (``sqrt(variance) / c``,
    ``erf(-2 / sqrt 2)``) are folded on the host here and were computed on
    the device there, one ulp apart. ``sample_batch`` gives a batch's shape
    and dtype (an array or a ``jax.ShapeDtypeStruct``, or a pytree of them
    where the model takes one, as a sequence encoder's packed view; the
    values are never read).

    ``shardings`` are the program's ``out_shardings``, so the state is born
    where it lives and nothing is moved afterwards: one sharding for every
    leaf, or a function from the abstract state (``jax.eval_shape``'s) to a
    ``TrainState`` of shardings, e.g. ``partial(tp_state_shardings, mesh)``.
    ``None`` leaves the placement to JAX, as an eager init did.
    """

    def init(key):
        variables = model.init(
            key, jax.tree.map(lambda x: jnp.zeros(x.shape, x.dtype), sample_batch),
            train=True,
        )
        params = variables["params"]
        return TrainState(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=variables.get("batch_stats", {}),
            opt_state=tx.init(params),
        )

    if callable(shardings):
        shardings = shardings(jax.eval_shape(init, rng))
    return jax.jit(init, out_shardings=shardings)(rng)


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))
