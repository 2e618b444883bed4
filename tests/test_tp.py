"""Tensor parallelism of the projection head (parallel/tp.py).

The `model` mesh axis stops being decorative here: the head runs
Megatron-style column->row parallel inside shard_map, and these tests pin
(a) the sharded forward against the unsharded module, (b) the state layout,
and (c) full-step equivalence between a (data, model) mesh and its
(data, 1) degenerate — same data-axis size, so augmentation RNG streams are
identical and losses/params must match to float tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from simclr_tpu.eval import SWEEP_CONFIG_KEY
from simclr_tpu.models.contrastive import ContrastiveModel
from simclr_tpu.models.heads import ProjectionHead
from simclr_tpu.ops.lars import lars, simclr_weight_decay_mask
from simclr_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    MeshSpec,
    batch_sharding,
    create_mesh,
)
from simclr_tpu.parallel.tp import (
    make_pretrain_step_tp,
    state_pspecs,
    tp_state_shardings,
    tree_pspecs,
)
from simclr_tpu.parallel.train_state import create_train_state
from simclr_tpu.utils.schedule import warmup_cosine_schedule


def test_head_pspecs_layout():
    model = ContrastiveModel(base_cnn="resnet18", d=128, dtype=jnp.float32)
    init = model.init(jax.random.key(0), jnp.zeros((2, 32, 32, 3)), train=True)
    specs = tree_pspecs(init["params"])
    assert specs["g"]["linear1"]["kernel"] == P(None, MODEL_AXIS)
    assert specs["g"]["linear1"]["bias"] == P(MODEL_AXIS)
    assert specs["g"]["bn1"]["scale"] == P(MODEL_AXIS)
    assert specs["g"]["linear2"]["kernel"] == P(MODEL_AXIS, None)
    # encoder stays replicated
    assert specs["f"]["stem_conv"]["kernel"] == P()
    stats_specs = tree_pspecs(init["batch_stats"])
    assert stats_specs["g"]["bn1"]["mean"] == P(MODEL_AXIS)
    assert stats_specs["f"]["BatchNorm_0"]["mean"] == P()


def test_sharded_head_forward_matches_unsharded():
    """Column->row parallel head == unsharded head, eval mode, any tp."""
    tp = 8
    mesh = create_mesh(MeshSpec(data=1, model=tp))
    head = ProjectionHead(d=128, dtype=jnp.float32)
    h = jax.random.normal(jax.random.key(1), (16, 512), jnp.float32)
    variables = head.init(jax.random.key(2), h, train=True)
    want = head.apply(variables, h, train=False)

    local = ProjectionHead(d=128, dtype=jnp.float32, hidden=512 // tp,
                           tp_axis=MODEL_AXIS)
    # reuse the 'g'-anchored spec rule by wrapping the head tree
    p_specs = tree_pspecs({"g": variables["params"]})["g"]
    s_specs = tree_pspecs({"g": variables["batch_stats"]})["g"]

    def fwd(p, s, x):
        return local.apply({"params": p, "batch_stats": s}, x, train=False)

    sharded = jax.shard_map(
        fwd, mesh=mesh, in_specs=(p_specs, s_specs, P()), out_specs=P(),
        check_vma=False,
    )
    got = sharded(variables["params"], variables["batch_stats"], h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def _run_steps(mesh, n_steps=2, per_device_batch=4, dtype=jnp.float32,
               **step_kwargs):
    model = ContrastiveModel(
        base_cnn="resnet18", d=128, dtype=dtype,
        bn_cross_replica_axis=DATA_AXIS,
    )
    tx = lars(
        warmup_cosine_schedule(0.1, 20, 2),
        weight_decay=1e-4,
        weight_decay_mask=simclr_weight_decay_mask,
    )
    state = create_train_state(
        model, tx, jax.random.key(7), jnp.zeros((2, 32, 32, 3), jnp.float32)
    )
    state = jax.device_put(state, tp_state_shardings(mesh, state))
    step = make_pretrain_step_tp(
        model, tx, mesh, temperature=0.5, strength=0.5, **step_kwargs
    )

    n_data = mesh.shape[DATA_AXIS]
    global_batch = per_device_batch * n_data
    images = np.random.default_rng(0).integers(
        0, 256, size=(global_batch, 32, 32, 3), dtype=np.uint8
    )
    batch = jax.device_put(images, batch_sharding(mesh))
    losses = []
    for i in range(n_steps):
        state, metrics = step(state, batch, jax.random.key(100 + i))
        losses.append(float(metrics["loss"]))
    return losses, jax.device_get(state.params)


def test_tp_step_matches_degenerate_model_axis():
    """(data=2, model=4) == (data=2, model=1): same data-axis size keeps the
    augmentation key streams identical, so the ONLY difference is the head
    sharding — losses and updated params must agree."""
    devices = jax.devices()
    mesh_tp = create_mesh(MeshSpec(data=2, model=4), devices=devices)
    mesh_dp = create_mesh(MeshSpec(data=2, model=1), devices=devices[:2])

    losses_tp, params_tp = _run_steps(mesh_tp)
    losses_dp, params_dp = _run_steps(mesh_dp)

    np.testing.assert_allclose(losses_tp, losses_dp, rtol=1e-4)
    flat_tp = jax.tree_util.tree_leaves_with_path(params_tp)
    flat_dp = dict(
        (jax.tree_util.keystr(p), v)
        for p, v in jax.tree_util.tree_leaves_with_path(params_dp)
    )
    for path, leaf in flat_tp:
        key = jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(leaf), np.asarray(flat_dp[key]), atol=2e-5, err_msg=key
        )


def test_tp_entrypoint_and_eval_round_trip(tmp_path):
    """`mesh.model=2` end to end: pretrain on a (4,2) mesh, checkpoint
    (global-view arrays), then eval the checkpoint on the default (8,1)
    mesh — the cross-layout restore path."""
    from simclr_tpu.eval import main as eval_main
    from simclr_tpu.main import main as pretrain_main

    save_dir = str(tmp_path / "tp-ckpts")
    overrides = [
        "experiment.synthetic_data=true",
        "experiment.synthetic_size=64",
        "experiment.batches=4",
        "mesh.model=2",
        "parameter.epochs=1",
        "parameter.warmup_epochs=0",
        "experiment.save_model_epoch=1",
        f"experiment.save_dir={save_dir}",
    ]
    summary = pretrain_main(overrides)
    assert summary["steps"] == 64 // (4 * 4)  # data axis = 4
    assert np.isfinite(summary["final_loss"])

    out = str(tmp_path / "tp-eval")
    results = eval_main(
        [
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            "experiment.batches=4",
            "parameter.classifier=centroid",
            f"experiment.target_dir={save_dir}",
            f"experiment.save_dir={out}",
        ]
    )
    for key, metrics in results.items():
        if key == SWEEP_CONFIG_KEY:
            continue
        assert 0.0 <= metrics["val_acc"] <= 1.0


@pytest.mark.slow
def test_tp_resume(tmp_path):
    """experiment.resume=true under mesh.model=2: the restore template
    carries the TP layout (head leaves sharded over model), so resuming a
    tensor-parallel run keeps training where it left off."""
    from simclr_tpu.main import main as pretrain_main

    save_dir = str(tmp_path / "tp-resume")
    base = [
        "experiment.synthetic_data=true",
        "experiment.synthetic_size=64",
        "experiment.batches=4",
        "mesh.model=2",
        "parameter.warmup_epochs=0",
        "experiment.save_model_epoch=1",
        f"experiment.save_dir={save_dir}",
    ]
    first = pretrain_main(base + ["parameter.epochs=1"])
    assert first["steps"] == 4  # data axis 4, global batch 16, 64 samples
    resumed = pretrain_main(base + ["parameter.epochs=2", "experiment.resume=true"])
    assert resumed["steps"] == 8  # epoch 2 only: 4 more steps


@pytest.mark.slow
def test_dp_checkpoint_resumes_under_tp(tmp_path):
    """A checkpoint written by a data-parallel run restores into the
    tensor-parallel layout (orbax reshards the global-view arrays onto the
    TP template): same global batch on both sides keeps step accounting
    aligned (dp: 8x4, tp: 4 data shards x 8/device)."""
    from simclr_tpu.main import main as pretrain_main

    save_dir = str(tmp_path / "dp-to-tp")
    common = [
        "experiment.synthetic_data=true",
        "experiment.synthetic_size=64",
        "parameter.warmup_epochs=0",
        "experiment.save_model_epoch=1",
        f"experiment.save_dir={save_dir}",
    ]
    first = pretrain_main(
        common + ["experiment.batches=4", "parameter.epochs=1"]
    )
    assert first["steps"] == 2  # global batch 32 (4 x 8 devices)
    resumed = pretrain_main(
        common
        + [
            "experiment.batches=8",  # 8 x 4 data shards = same global 32
            "mesh.model=2",
            "parameter.epochs=2",
            "experiment.resume=true",
        ]
    )
    assert resumed["steps"] == 4  # epoch 2 only: 2 more steps
    assert np.isfinite(resumed["final_loss"])


@pytest.mark.slow
def test_tp_matches_degenerate_in_bf16():
    """bf16 dp-vs-tp sanity: whole-step losses track between a (2,4) and a
    (2,1) mesh with dtype=bfloat16. Coarse by nature (bf16 reorderings) —
    the f32-upcast invariant itself is pinned by the cancellation test
    below, not by this tolerance."""
    devices = jax.devices()
    mesh_tp = create_mesh(MeshSpec(data=2, model=4), devices=devices)
    mesh_dp = create_mesh(MeshSpec(data=2, model=1), devices=devices[:2])

    losses_tp, _ = _run_steps(mesh_tp, dtype=jnp.bfloat16)
    losses_dp, _ = _run_steps(mesh_dp, dtype=jnp.bfloat16)
    np.testing.assert_allclose(losses_tp, losses_dp, rtol=1e-2)


def test_tp_output_psum_operand_is_f32():
    """Trace-level pin of the f32 upcast before the row-parallel output
    psum (ADVICE r2; heads.py). A NUMERICAL cpu test cannot see the
    deviation — XLA's CPU all-reduce accumulates bf16 operands in f32
    internally (verified: bf16 psum of [1024, 1, -1024, 1] returns exactly
    2) — but on TPU ICI the all-reduce accumulation precision follows the
    operand dtype, which is exactly why the head casts up first. So pin
    the jaxpr: with a bfloat16 head, every psum the TP forward emits must
    take float32 operands."""
    tp = 4
    mesh = create_mesh(MeshSpec(data=1, model=tp), devices=jax.devices()[:tp])
    head = ProjectionHead(d=128, dtype=jnp.bfloat16)
    h = jnp.ones((2, 512), jnp.float32)
    variables = head.init(jax.random.key(0), h, train=True)

    local = ProjectionHead(d=128, dtype=jnp.bfloat16, hidden=512 // tp,
                           tp_axis=MODEL_AXIS)
    p_specs = tree_pspecs({"g": variables["params"]})["g"]
    s_specs = tree_pspecs({"g": variables["batch_stats"]})["g"]

    def fwd(p, s, x):
        return local.apply({"params": p, "batch_stats": s}, x, train=False)

    sharded = jax.shard_map(
        fwd, mesh=mesh, in_specs=(p_specs, s_specs, P()), out_specs=P(),
        check_vma=False,
    )
    jaxpr = jax.make_jaxpr(sharded)(
        variables["params"], variables["batch_stats"], h
    )

    def walk(jx):
        for eqn in jx.eqns:
            yield eqn
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else (v,)):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from walk(inner)

    psum_in_dtypes = [
        v.aval.dtype
        for eqn in walk(jaxpr.jaxpr)
        if "psum" in eqn.primitive.name
        for v in eqn.invars
        if hasattr(v.aval, "dtype")
    ]
    assert psum_in_dtypes, "no psum found in the TP head forward"
    assert all(dt == jnp.float32 for dt in psum_in_dtypes), psum_in_dtypes


def test_tp_remat_matches_non_remat():
    """model.remat under TP: jax.checkpoint recomputes the forward in the
    backward pass but must not change the math — one step, same state/batch/
    rng, losses and updated head shards agree to float tolerance."""
    mesh = create_mesh(MeshSpec(data=2, model=4))
    model = ContrastiveModel(
        base_cnn="resnet18", d=128, dtype=jnp.float32,
        bn_cross_replica_axis=DATA_AXIS,
    )
    tx = lars(warmup_cosine_schedule(0.1, 20, 2), weight_decay=1e-4,
              weight_decay_mask=simclr_weight_decay_mask)

    def fresh_state():
        s = create_train_state(
            model, tx, jax.random.key(7), jnp.zeros((2, 32, 32, 3), jnp.float32)
        )
        return jax.device_put(s, tp_state_shardings(mesh, s))

    images = np.random.default_rng(3).integers(
        0, 256, size=(8, 32, 32, 3), dtype=np.uint8
    )
    batch = jax.device_put(images, batch_sharding(mesh))
    rng = jax.random.key(9)

    outs = {}
    for remat in (False, True):
        step = make_pretrain_step_tp(model, tx, mesh, remat=remat)
        state, m = step(fresh_state(), batch, rng)
        outs[remat] = (float(m["loss"]), jax.device_get(state.params))

    assert outs[False][0] == pytest.approx(outs[True][0], rel=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
        outs[False][1], outs[True][1],
    )


def test_tp_rejects_unsupported_combinations():
    """loss.negatives / loss.fused are first-class under mesh.model>1 (they
    dispatch inside the tp step like the dp step); the one remaining gap in
    the support matrix is the concat forward, refused by the builder before
    the entry point has compiled anything."""
    from simclr_tpu.main import run_pretrain
    from simclr_tpu.config import load_config

    cfg = load_config(
        "config",
        overrides=[
            "experiment.synthetic_data=true",
            "experiment.synthetic_size=64",
            "experiment.batches=4",
            "mesh.model=2",
            "model.forward_mode=concat",
            "parameter.epochs=1",
            "parameter.warmup_epochs=0",
        ],
    )
    with pytest.raises(ValueError, match="tensor parallelism"):
        run_pretrain(cfg)


def _tiny_tp():
    mesh = create_mesh(MeshSpec(data=4, model=2))
    model = ContrastiveModel(
        base_cnn="resnet18", d=128, dtype=jnp.float32,
        bn_cross_replica_axis=DATA_AXIS,
    )
    return mesh, model, lars(0.1)


def test_tp_builder_refuses_the_concat_forward():
    """The refusal lives in the builder (it took ``main.py``'s place), with
    the dp builder's keyword, and names the mesh and the option."""
    mesh, model, tx = _tiny_tp()
    with pytest.raises(ValueError, match=r"mesh\.model=2.*forward_mode='concat'"):
        make_pretrain_step_tp(model, tx, mesh, forward_mode="concat")
    make_pretrain_step_tp(model, tx, mesh, forward_mode="two_pass")


def test_tp_builder_watches_its_own_jit_as_pretrain_step(tmp_path):
    """``sentry=`` is the dp builder's keyword: the tp step's one compilation
    lands as one ``compile`` event named ``pretrain_step``, the name the
    benchmark and ``step_scopes.json`` go by, and a second call at the same
    signature compiles nothing."""
    from simclr_tpu.obs.compile import CompileSentry
    from simclr_tpu.obs.events import EventLog, events_path, read_events

    mesh, model, tx = _tiny_tp()
    events = EventLog(str(tmp_path))
    sentry = CompileSentry(events=events)
    step = make_pretrain_step_tp(model, tx, mesh, sentry=sentry)
    state = create_train_state(
        model, tx, jax.random.key(7), jnp.zeros((2, 32, 32, 3), jnp.float32),
        shardings=lambda abstract: tp_state_shardings(mesh, abstract),
    )
    batch = jax.device_put(
        np.random.default_rng(3).integers(
            0, 256, size=(8, 32, 32, 3), dtype=np.uint8
        ),
        batch_sharding(mesh),
    )
    for i in range(2):
        state, metrics = step(state, batch, jax.random.key(i))
    assert np.isfinite(float(metrics["loss"]))
    compiles = [
        e for e in read_events(events_path(str(tmp_path))) if e["event"] == "compile"
    ]
    assert [e["name"] for e in compiles] == ["pretrain_step"]
    assert sentry.compiles == 1 and sentry.recompile_alarms == 0


def test_tp_builders_validate_loss_variants_eagerly():
    """The tp builder accepts every dp loss variant and rejects the same
    invalid combinations as parallel/steps.py, at construction time (before
    any trace) so a bad config fails fast, not mid-compile."""
    mesh, model, tx = _tiny_tp()
    for negatives, fused in [
        ("global", False), ("local", False), ("ring", False),
        ("global", True), ("local", True),
    ]:
        make_pretrain_step_tp(
            model, tx, mesh, negatives=negatives, fused=fused
        )
    with pytest.raises(ValueError, match="global|local|ring"):
        make_pretrain_step_tp(model, tx, mesh, negatives="cross")
    with pytest.raises(ValueError, match="fused"):
        make_pretrain_step_tp(model, tx, mesh, negatives="ring", fused=True)


@pytest.mark.parametrize("negatives,fused", [
    ("local", False), ("ring", False), ("global", True),
])
def test_tp_loss_variants_match_degenerate_model_axis(negatives, fused):
    """dp-vs-tp loss parity per NT-Xent variant: a (data=2, model=4) mesh
    against its (data=2, model=1) degenerate with the SAME data-axis size,
    so augmentation RNG streams are identical and the only difference is
    the head sharding. Before the variants were threaded through tp.py the
    builders silently ran negatives='global', unfused — this matrix pins
    that each variant's ring/local/fused math survives the model axis.
    (global+unfused is pinned by test_tp_step_matches_degenerate.)"""
    devices = jax.devices()
    mesh_tp = create_mesh(MeshSpec(data=2, model=4), devices=devices)
    mesh_dp = create_mesh(MeshSpec(data=2, model=1), devices=devices[:2])

    kw = dict(negatives=negatives, fused=fused)
    losses_tp, _ = _run_steps(mesh_tp, **kw)
    losses_dp, _ = _run_steps(mesh_dp, **kw)
    assert all(np.isfinite(losses_tp))
    np.testing.assert_allclose(losses_tp, losses_dp, rtol=1e-4)


def test_tp_state_sharding_shapes():
    """Global state arrays keep global shapes; device shards split the head."""
    mesh = create_mesh(MeshSpec(data=2, model=4))
    model = ContrastiveModel(base_cnn="resnet18", d=128, dtype=jnp.float32)
    tx = lars(0.1)
    state = create_train_state(
        model, tx, jax.random.key(7), jnp.zeros((2, 32, 32, 3), jnp.float32)
    )
    state = jax.device_put(state, tp_state_shardings(mesh, state))
    k = state.params["g"]["linear1"]["kernel"]
    assert k.shape == (512, 512)  # global view
    # each device holds a (512, 128) column slice
    assert k.addressable_shards[0].data.shape == (512, 512 // 4)
    k2 = state.params["g"]["linear2"]["kernel"]
    assert k2.addressable_shards[0].data.shape == (512 // 4, 128)
