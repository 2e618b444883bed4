"""The step program's ``jax.named_scope`` scopes (``obs/trace.py``
``DEVICE_SCOPES``; ``parallel/steps.py``, ``parallel/tp.py``,
``models/contrastive.py``) and the map from a compiled program's instructions
to them (``obs/compile.py::instruction_scopes``).

Scopes are HLO metadata. They must cost nothing: the lowered program with its
metadata left out is the same text with and without them. And they must be
there: every name appears in the lowered program's locations, the encoder's,
the head's and the loss's also inside ``transpose(``, which is how JAX names
the backward ops.
"""

import contextlib
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from simclr_tpu.models.contrastive import ContrastiveModel
from simclr_tpu.obs.compile import (
    STEP_SCOPES_NAME,
    CompileSentry,
    instruction_scopes,
)
from simclr_tpu.obs.events import EventLog
from simclr_tpu.obs.trace import DEVICE_SCOPES
from simclr_tpu.ops.lars import lars, simclr_weight_decay_mask
from simclr_tpu.parallel.mesh import DATA_AXIS, MeshSpec, batch_sharding, create_mesh
from simclr_tpu.parallel.steps import make_pretrain_step
from simclr_tpu.parallel.tp import make_pretrain_step_tp
from simclr_tpu.parallel.train_state import create_train_state

from tests.helpers import random_images

pytestmark = pytest.mark.obs

BATCH = 16


def _lowered(builder, mesh, **kw):
    model = ContrastiveModel(base_cnn="resnet18", d=32, bn_cross_replica_axis=DATA_AXIS)
    tx = lars(0.1, weight_decay=1e-4, weight_decay_mask=simclr_weight_decay_mask)
    sample = jnp.zeros((BATCH, 32, 32, 3), jnp.float32)
    state = create_train_state(model, tx, jax.random.key(0), sample)
    step = builder(model, tx, mesh, **kw)
    images = jax.device_put(random_images(BATCH, seed=0), batch_sharding(mesh))
    return step.lower(state, images, jax.random.key(1))


_SPLIT = re.compile(r"[/()]")  # as benchmarks/scopes.py takes a path apart


def _op_names(lowered) -> list[str]:
    """The ``op_name`` paths of a lowered program: its named locations."""
    return re.findall(r'loc\("([^"]*)"', lowered.as_text(debug_info=True))


def _without_scopes(monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())


BUILDERS = {
    "dp": (make_pretrain_step, lambda: create_mesh()),
    "tp": (make_pretrain_step_tp, lambda: create_mesh(MeshSpec(data=4, model=2))),
}


@pytest.mark.parametrize("which", ["dp", "tp"])
def test_scopes_name_every_part_and_change_nothing(monkeypatch, which):
    builder, mesh = BUILDERS[which][0], BUILDERS[which][1]()
    scoped = _lowered(builder, mesh)
    names = _op_names(scoped)
    forward = {part for n in names if "transpose(" not in n for part in _SPLIT.split(n)}
    backward = {part for n in names if "transpose(" in n for part in _SPLIT.split(n)}
    assert set(DEVICE_SCOPES) <= forward
    # the backward of what is differentiated carries its forward's scope
    assert {"encoder", "head", "ntxent"} <= backward
    assert not {"augment", "grad_reduce", "lars"} & backward
    with monkeypatch.context() as m:
        _without_scopes(m)
        plain = _lowered(builder, mesh)
        assert not any(
            part in DEVICE_SCOPES for n in _op_names(plain) for part in _SPLIT.split(n)
        )
    # the program, metadata left out, is the same to the byte
    assert scoped.as_text() == plain.as_text()


HLO = """HloModule jit_local_step, is_scheduled=true

%fused_computation.1 (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %tanh.0 = f32[8]{0} tanh(%param_0.1), metadata={op_name="jit(local_step)/jvp(encoder)/tanh" stack_frame_id=2}
  ROOT %mul.0 = f32[8]{0} multiply(%tanh.0, %tanh.0), metadata={op_name="jit(local_step)/transpose(jvp(encoder))/mul" stack_frame_id=2}
}

%fused_computation.2.clone (param_0.2: f32[8]) -> (f32[8], f32[8]) {
  %param_0.2 = f32[8]{0} parameter(0)
  %add.3 = f32[8]{0} add(%param_0.2, %param_0.2), metadata={op_name="jit(local_step)/augment/add"}
  %sub.3 = f32[8]{0} subtract(%param_0.2, %add.3), metadata={op_name="jit(local_step)/augment/sub"}
  %neg.3 = f32[8]{0} negate(%sub.3), metadata={op_name="jit(local_step)/augment/sub"}
  ROOT %tuple.3 = (f32[8]{0}, f32[8]{0}) tuple(%add.3, %neg.3)
}

%fused_computation.3 (param_0.3: f32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0} parameter(0)
  ROOT %fusion.31 = f32[8]{0} fusion(%param_0.3), kind=kLoop, calls=%fused_computation.1
}

%region_0.4 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={op_name="jit(local_step)/grad_reduce/psum"}
}

ENTRY %main.5 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0), metadata={op_name="x"}
  %constant.7 = f32[] constant(0)
  %copy-start.2 = (f32[8]{0}, f32[8]{0}, u32[]) copy-start(%x.1)
  %copy-done.2 = f32[8]{0} copy-done(%copy-start.2)
  %fusion.11 = f32[8]{0} fusion(%copy-done.2), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(local_step)/lars/mul" stack_frame_id=9}
  %fusion.12 = (f32[8]{0}, f32[8]{0}) fusion(%fusion.11), kind=kLoop, calls=%fused_computation.2.clone
  %fusion.13 = f32[8]{0} fusion(%fusion.11), kind=kCustom, calls=%fused_computation.3, backend_config={"flag_configs":[]}
  %all-reduce.4 = f32[8]{0} all-reduce(%fusion.13), replica_groups={}, to_apply=%region_0.4, metadata={op_name="jit(local_step)/grad_reduce/psum"}
  ROOT %convolution.6 = f32[8]{0} convolution(%all-reduce.4, %x.1), metadata={op_name="jit(local_step)/jvp(encoder)/f/conv_general_dilated"}
}
"""


def test_instruction_scopes_reads_a_compiled_programs_text():
    scopes = instruction_scopes(HLO)
    assert scopes == {
        "x.1": "x",
        # the compiler's own copy has no metadata: it moves what x.1 is
        "copy-start.2": "x",
        "copy-done.2": "x",
        # its own metadata wins over its root's
        "fusion.11": "jit(local_step)/lars/mul",
        # a root the compiler made: what most of the fused instructions carry
        "fusion.12": "jit(local_step)/augment/sub",
        # a fusion of a fusion: the inner root's
        "fusion.13": "jit(local_step)/transpose(jvp(encoder))/mul",
        "all-reduce.4": "jit(local_step)/grad_reduce/psum",
        "convolution.6": "jit(local_step)/jvp(encoder)/f/conv_general_dilated",
        # a called computation's instructions can show in a trace; a fusion's cannot
        "add.9": "jit(local_step)/grad_reduce/psum",
    }
    assert instruction_scopes("") == {}


# a Pallas kernel's custom call prints its kernel metadata over several lines
# that begin in the first column (libtpu 0.0.34), its ``metadata=`` on the last
KERNEL_HLO = """HloModule jit_local_step

ENTRY %main.9 (x.1: bf16[8,128]) -> bf16[8,128] {
  %x.1 = bf16[8,128]{1,0} parameter(0)
  %fusion.1 = bf16[8,128]{1,0} fusion(%x.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(local_step)/jvp(encoder)/f/layer_0/attention/mul"}
  %splash_mqa_fwd.2 = bf16[8,128]{1,0} custom-call(%fusion.1), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 512}"
}}, metadata={op_name="jit(local_step)/jvp(encoder)/f/layer_0/attention/pallas_call" stack_frame_id=7}, backend_config={"custom_call_config": {}}
  %copy.3 = bf16[8,128]{1,0} copy(%splash_mqa_fwd.2)
  ROOT %fusion.4 = bf16[8,128]{1,0} fusion(%copy.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(local_step)/jvp(encoder)/f/layer_0/moe/experts/mul"}
}

%fused_computation.1 (p: bf16[8,128]) -> bf16[8,128] {
  %p = bf16[8,128]{1,0} parameter(0)
  ROOT %mul.5 = bf16[8,128]{1,0} multiply(%p, %p), metadata={op_name="inside"}
}
"""


def test_an_instruction_printed_over_several_lines_neither_ends_its_computation_nor_loses_its_name():
    assert instruction_scopes(KERNEL_HLO) == {
        "fusion.1": "jit(local_step)/jvp(encoder)/f/layer_0/attention/mul",
        "splash_mqa_fwd.2": "jit(local_step)/jvp(encoder)/f/layer_0/attention/pallas_call",
        "copy.3": "jit(local_step)/jvp(encoder)/f/layer_0/attention/pallas_call",
        # before PR 34 everything from here on was lost with the kernel's lines
        "fusion.4": "jit(local_step)/jvp(encoder)/f/layer_0/moe/experts/mul",
    }


# a loop: the trace shows the ``while`` as one event over its body's events
LOOP_HLO = """HloModule jit_local_step

%body.1 (carry: (s32[], f32[8])) -> (s32[], f32[8]) {
  %carry = (s32[], f32[8]{0}) parameter(0)
  %i.2 = s32[] get-tuple-element(%carry), index=0
  %x.3 = f32[8]{0} get-tuple-element(%carry), index=1
  %fusion.4 = f32[8]{0} fusion(%x.3), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(local_step)/jvp(encoder)/f/layer_0/moe/experts/while/body/gather"}
  ROOT %tuple.5 = (s32[], f32[8]{0}) tuple(%i.2, %fusion.4)
}

%cond.1 (carry.1: (s32[], f32[8])) -> pred[] {
  %carry.1 = (s32[], f32[8]{0}) parameter(0)
  %i.6 = s32[] get-tuple-element(%carry.1), index=0
  %n.7 = s32[] constant(3)
  ROOT %lt.8 = pred[] compare(%i.6, %n.7), direction=LT, metadata={op_name="jit(local_step)/jvp(encoder)/f/layer_0/moe/experts/while/cond/lt"}
}

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.9 = f32[8]{0} multiply(%p, %p), metadata={op_name="inside"}
}

ENTRY %main.9 (x.1: f32[8]) -> f32[8] {
  %x.1 = f32[8]{0} parameter(0)
  %zero.2 = s32[] constant(0)
  %tuple.3 = (s32[], f32[8]{0}) tuple(%zero.2, %x.1)
  %while.4 = (s32[], f32[8]{0}) while(%tuple.3), condition=%cond.1, body=%body.1, metadata={op_name="jit(local_step)/jvp(encoder)/f/layer_0/moe/experts/while"}
  %get-tuple-element.5 = f32[8]{0} get-tuple-element(%while.4), index=1
  ROOT %copy.6 = f32[8]{0} copy(%get-tuple-element.5)
}
"""


def test_a_loop_is_left_out_and_its_bodys_instructions_are_not():
    experts = "jit(local_step)/jvp(encoder)/f/layer_0/moe/experts/while"
    assert instruction_scopes(LOOP_HLO) == {
        "fusion.4": experts + "/body/gather",
        "lt.8": experts + "/cond/lt",
        # what moves the loop's result is still the loop's
        "get-tuple-element.5": experts,
        "copy.6": experts,
    }


def test_a_watched_step_writes_its_scope_map_beside_the_compile_event(tmp_path):
    """Once per compile, inside the sentry's own seconds; nothing on a later
    call. On the CPU too, where instruction names differ and the path is the
    same."""
    events = EventLog(str(tmp_path))
    sentry = CompileSentry(events=events, scopes_dir=str(tmp_path))

    def f(x):
        with jax.named_scope("lars"):
            return jnp.tanh(x) * 2.0

    step = sentry.watch(jax.jit(f), "pretrain_step")
    step(jnp.ones((8,)))
    path = os.path.join(str(tmp_path), STEP_SCOPES_NAME)
    with open(path) as fh:
        scopes = json.load(fh)
    assert scopes and any("/lars/" in op_name for op_name in scopes.values())
    written = os.path.getmtime(path)
    os.utime(path, (0, 0))
    step(jnp.ones((8,)))  # the cached executable: no compile, no write
    assert os.path.getmtime(path) == 0 and written > 0
    # a sentry that was given no directory writes none, and says nothing
    bare = CompileSentry()
    bare.watch(jax.jit(f), "pretrain_step")(jnp.ones((8,)))
    assert sentry.compiles == 1 and bare.compiles == 1
