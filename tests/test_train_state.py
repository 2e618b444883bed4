"""The initial train state (``simclr_tpu/parallel/train_state.py``): one
compiled program, the same bits as the eager construction it replaced, every
leaf born at the sharding that was asked for.

The eager construction is written out here as the reference: ``model.init``
and ``tx.init`` outside any ``jit``, one device program per initializer.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

from simclr_tpu.models.contrastive import ContrastiveModel, SupervisedModel
from simclr_tpu.models.heads import LinearClassifier, NonLinearClassifier
from simclr_tpu.ops.lars import lars, simclr_weight_decay_mask
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    MeshSpec,
    create_mesh,
    replicated_sharding,
)
from simclr_tpu.parallel.tp import tp_state_shardings
from simclr_tpu.parallel.train_state import (
    TrainState,
    create_train_state,
    init_variables,
    param_count,
)
from simclr_tpu.utils.platform import backend_compilations
from simclr_tpu.utils.schedule import warmup_cosine_schedule
from tests.helpers import TinyContrastive

SAMPLE = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)
SEED = 7


def _model(kind: str, arch: str):
    if kind == "contrastive":
        return ContrastiveModel(
            base_cnn=arch, d=128, cifar_stem=True, dtype=jnp.bfloat16,
            bn_cross_replica_axis=DATA_AXIS,
        )
    return SupervisedModel(
        base_cnn=arch, num_classes=10, cifar_stem=True, dtype=jnp.bfloat16,
        bn_cross_replica_axis=DATA_AXIS,
    )


def _lars():
    return lars(
        warmup_cosine_schedule(1.0, 20, 2),
        weight_decay=1e-4,
        weight_decay_mask=simclr_weight_decay_mask,
    )


def _eager_state(model, tx, rng, sample) -> TrainState:
    """What ``create_train_state`` was before it became one program."""
    variables = model.init(rng, sample, train=True)
    params = variables["params"]
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=variables.get("batch_stats", {}),
        opt_state=tx.init(params),
    )


def _assert_same_bits(got, want):
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for (path, a), b in zip(
        jax.tree_util.tree_flatten_with_path(got)[0], jax.tree.leaves(want)
    ):
        where = jax.tree_util.keystr(path)
        assert a.shape == b.shape and a.dtype == b.dtype, where
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=where)


@pytest.fixture(scope="module")
def built():
    """One jitted build per model, shared by the tests below and dropped with
    the module: the state and the backend compilations JAX reported inside
    the call."""

    @functools.cache
    def build(kind: str, arch: str):
        model, tx, rng = _model(kind, arch), _lars(), jax.random.key(SEED)
        before = backend_compilations()
        state = create_train_state(model, tx, rng, SAMPLE)
        return model, tx, rng, state, backend_compilations() - before

    yield build
    build.cache_clear()


MODELS = [
    (kind, arch)
    for kind in ("contrastive", "supervised")
    for arch in ("resnet18", "resnet50")
]


@pytest.mark.parametrize("kind,arch", MODELS)
def test_one_program_draws_the_bits_of_the_eager_init(built, kind, arch):
    model, tx, rng, state, _ = built(kind, arch)
    eager = _eager_state(model, tx, rng, jnp.zeros(SAMPLE.shape, SAMPLE.dtype))
    _assert_same_bits(state, eager)


@pytest.mark.parametrize("kind,arch", MODELS)
def test_the_state_costs_at_most_two_backend_compilations(built, kind, arch):
    *_, compilations = built(kind, arch)
    assert 0 <= compilations <= 2


def test_default_call_returns_what_callers_expect(built):
    """No shardings: an uncommitted state on the default device, the layout an
    eager init gave, whatever array the caller passes as the sample."""
    model, tx, rng, state, _ = built("contrastive", "resnet18")
    assert isinstance(state, TrainState)
    assert state.step.dtype == jnp.int32 and int(state.step) == 0
    assert set(state.params) == {"f", "g"} and set(state.batch_stats) == {"f", "g"}
    assert param_count(state.params) == 11_498_048
    default = SingleDeviceSharding(jax.devices()[0])
    for leaf in jax.tree.leaves(state):
        assert isinstance(leaf, jax.Array) and leaf.sharding == default
        assert not leaf._committed  # a jitted step may still place it
    for sample in (
        np.zeros(SAMPLE.shape, np.float32), jnp.zeros(SAMPLE.shape, jnp.float32)
    ):
        _assert_same_bits(create_train_state(model, tx, rng, sample), state)


def test_every_leaf_is_born_replicated_over_the_mesh(built):
    model, tx, rng, plain, _ = built("contrastive", "resnet18")
    mesh = create_mesh(MeshSpec(data=4, model=1), devices=jax.devices()[:4])
    replicated = replicated_sharding(mesh)
    state = create_train_state(model, tx, rng, SAMPLE, shardings=replicated)
    for leaf in jax.tree.leaves(state):
        assert leaf.sharding == replicated and leaf.is_fully_replicated
        assert len(leaf.addressable_shards) == 4
    _assert_same_bits(state, plain)


def test_tensor_parallel_shardings_come_from_the_abstract_state(built):
    model, tx, rng, plain, _ = built("contrastive", "resnet18")
    mesh = create_mesh(MeshSpec(data=2, model=2), devices=jax.devices()[:4])
    asked = []

    def shardings(abstract):
        assert all(
            isinstance(leaf, jax.ShapeDtypeStruct) for leaf in jax.tree.leaves(abstract)
        )
        asked.append(tp_state_shardings(mesh, abstract))
        return asked[-1]

    state = create_train_state(model, tx, rng, SAMPLE, shardings=shardings)
    (want,) = asked
    for (path, leaf), sharding in zip(
        jax.tree_util.tree_flatten_with_path(state)[0], jax.tree.leaves(want)
    ):
        assert leaf.sharding == sharding, jax.tree_util.keystr(path)
    kernel = state.params["g"]["linear1"]["kernel"]
    assert kernel.sharding == NamedSharding(mesh, P(None, MODEL_AXIS))
    assert kernel.addressable_shards[0].data.shape == (512, 256)
    momentum = [
        leaf for path, leaf in jax.tree_util.tree_flatten_with_path(state.opt_state)[0]
        if "linear2" in jax.tree_util.keystr(path) and leaf.ndim == 2
    ]
    assert momentum and all(
        m.sharding == NamedSharding(mesh, P(MODEL_AXIS, None)) for m in momentum
    )
    assert state.params["f"]["stem_conv"]["kernel"].is_fully_replicated
    _assert_same_bits(state, plain)


@pytest.mark.parametrize("kind", ["linear", "nonlinear", "tiny"])
def test_init_variables_is_model_init_in_one_program(kind):
    if kind == "tiny":
        model, sample, kwargs = TinyContrastive(), SAMPLE, {"train": True}
    else:
        cls = LinearClassifier if kind == "linear" else NonLinearClassifier
        model, kwargs = cls(num_classes=10), {}
        sample = jax.ShapeDtypeStruct((2, 512), jnp.float32)
    rng = jax.random.key(SEED)
    before = backend_compilations()
    variables = init_variables(model, rng, sample, **kwargs)
    assert backend_compilations() - before <= 1
    _assert_same_bits(
        variables, model.init(rng, jnp.zeros(sample.shape, sample.dtype), **kwargs)
    )
