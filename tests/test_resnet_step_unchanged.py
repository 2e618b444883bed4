"""What the sequence encoder's arrival (PR 34) left alone, held by what a
leaf and a program are: the ResNets' train step traces to the same jaxpr as
before it, and the weight-decay mask by leaf kind gives every ResNet leaf
what the mask by leaf name gave it."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from simclr_tpu.models.contrastive import ContrastiveModel, SupervisedModel
from simclr_tpu.ops.lars import lars, simclr_weight_decay_mask
from simclr_tpu.parallel.steps import make_pretrain_step
from simclr_tpu.parallel.train_state import create_train_state

# sha256 of the step's jaxpr as printed (sets in sorted order: their print
# order follows the process's hash seed), traced on the commit before the
# sequence encoder came (8f25b41) by this file's `jaxpr_text`, jax 0.9.0
BEFORE = {
    ("resnet18", 8, 1): (933071, "21a372527a7896943e4d7e71f187245ee0024bcfef25cb89147692553a5cf89e"),
    ("resnet50", 4, 2): (2079943, "e3be3b2a6989d7bb70212486f41ff4e10da3e3586b992b5d666a1463336b6c12"),
}


def jaxpr_text(name: str, batch: int, devices: int) -> str:
    mesh = Mesh(np.array(jax.devices()[:devices]).reshape(devices, 1), ("data", "model"))
    model = ContrastiveModel(base_cnn=name, dtype=jnp.bfloat16, bn_cross_replica_axis="data")
    tx = lars(0.1, weight_decay=1e-4, weight_decay_mask=simclr_weight_decay_mask)
    state = jax.eval_shape(
        lambda k: create_train_state(
            model, tx, k, jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.float32)),
        jax.random.key(0),
    )
    step = make_pretrain_step(model, tx, mesh)
    rows = jax.ShapeDtypeStruct((batch * devices, 32, 32, 3), jnp.uint8)
    text = str(jax.make_jaxpr(step)(state, rows, jax.random.key(1)))
    return re.sub(
        r"frozenset\(\{([^}]*)\}\)",
        lambda m: "frozenset({" + ", ".join(sorted(m.group(1).split(", "))) + "})", text,
    )


@pytest.mark.parametrize("case", sorted(BEFORE))
def test_the_resnet_steps_jaxpr_prints_the_same_text(case):
    text = jaxpr_text(*case)
    assert (len(text), hashlib.sha256(text.encode()).hexdigest()) == BEFORE[case]


def mask_by_leaf_name(params):
    """The structural mask as it was before PR 34: no decay on a leaf named
    ``bias`` or ``scale``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef, [str(path[-1].key) not in ("bias", "scale") for path, _ in flat])


@pytest.mark.parametrize("name", ["resnet18", "resnet34", "resnet50", "resnet101"])
@pytest.mark.parametrize("kind", [ContrastiveModel, SupervisedModel])
def test_the_mask_by_leaf_kind_gives_the_resnets_the_mask_they_had(kind, name):
    model = kind(base_cnn=name)
    params = jax.eval_shape(
        lambda k: model.init(k, jnp.zeros((2, 32, 32, 3)), train=True), jax.random.key(0)
    )["params"]
    now, before = simclr_weight_decay_mask(params), mask_by_leaf_name(params)
    named = jax.tree_util.tree_flatten_with_path(now)[0]
    assert len(named) > 50
    for (path, mine), theirs in zip(named, jax.tree.leaves(before)):
        assert mine is theirs or mine == theirs, jax.tree_util.keystr(path)
    assert jax.tree.structure(now) == jax.tree.structure(before)


def test_the_mask_takes_a_sequence_encoders_norm_scales_off_decay():
    from simclr_tpu.models.arch import SEQUENCE_ENCODERS
    from simclr_tpu.models.sequence import PackedView

    row = jnp.zeros((1, 64), jnp.int32)
    model = ContrastiveModel(base_cnn="smallthinker-tiny")
    params = jax.eval_shape(
        lambda k: model.init(k, PackedView(row, row, row, jnp.zeros((1, 8), jnp.int32)), train=True),
        jax.random.key(0),
    )["params"]
    mask = simclr_weight_decay_mask(params)
    layer = mask["f"]["layer_1"]
    assert not layer["attention_norm"] and not layer["experts_norm"] and not mask["f"]["final_norm"]
    assert all(layer[k] for k in ("q", "k", "v", "o", "router", "gate", "up", "down"))
    assert mask["f"]["embedding"] and not mask["g"]["bn1"]["scale"] and not mask["g"]["linear1"]["bias"]
    assert len(SEQUENCE_ENCODERS["smallthinker-tiny"].layout) == 4
