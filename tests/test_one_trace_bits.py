"""One trace a bundle (PR 34), second file (``tests/test_one_trace.py`` has
the counting tests): the programs a bundle makes from its kept trace are the
programs a fresh trace of ``train_step`` gives. The lowered text is the same
but for the running numbers of private functions, and on the CPU the K = 8
window program, the 1-step program and a partial window (K = 3) give the
same losses and the same state bit for bit, for a GPT block, the looped stack
and the stack of several kinds.
"""

import re

import jax
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from saturn_tpu.core.mesh import make_submesh
from tests.test_one_trace import _task, _technique


def _fresh_programs(tech, task, devices, config, bundle):
    """The 1-step program and the K-step window programs made the way the
    parent made them: ``train_step`` traced afresh inside each ``jit``."""
    axis_names, axis_sizes = tech.mesh_spec(len(devices), task, config)
    mesh = make_submesh(devices, axis_names, axis_sizes)
    spec = task.get_model(**tech._model_overrides(config))
    _, train_step = tech.make_step_fns(spec, task, config, mesh,
                                       task.get_dataset())
    scalar = NamedSharding(mesh, P())

    def window(state, stack):
        return jax.lax.scan(train_step, state, stack)

    step = jax.jit(train_step,
                   in_shardings=(bundle.state_shardings, bundle.batch_sharding),
                   out_shardings=(bundle.state_shardings, scalar),
                   donate_argnums=(0,))
    fused = jax.jit(window,
                    in_shardings=(bundle.state_shardings,
                                  bundle.stacked_sharding()),
                    out_shardings=(bundle.state_shardings, scalar),
                    donate_argnums=(0, 1))
    return step, fused


def _program_text(lowered):
    """The lowered module with the running numbers of its private functions
    (``@_where_161``) and the module's own name taken off: the rest is the
    program."""
    text = re.sub(r"@([A-Za-z_][\w.]*?)_\d+\b", r"@\1", lowered.as_text())
    return re.sub(r"module @\w+", "module", text)


def _same_bits(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


@pytest.mark.parametrize("preset", [
    "test-tiny", "ouro-test-tiny", "olmo-hybrid-test-tiny"])
def test_replayed_programs_match_fresh_traces_bitwise(preset, tmp_path,
                                                      devices8):
    task = _task(tmp_path, preset, f"bits-{preset}", batch=2)
    tech = _technique("dp")
    devices = devices8[:1]
    config = {"remat": True}
    assert config in tech.candidate_configs(task, 1)
    bundle = tech.build(task, devices, config)
    step, fused = _fresh_programs(tech, task, devices, config, bundle)
    ds = task.get_dataset()

    def stack(k):
        return jax.device_put(
            np.stack([np.asarray(ds.batch(i)) for i in range(k)]),
            bundle.stacked_sharding())

    # the same text: what the chip's compiler is handed is the parent's program
    assert _program_text(bundle.lowered) == _program_text(
        step.lower(bundle.state_shapes, bundle.batch_sds))
    window_sds = jax.ShapeDtypeStruct((8, *bundle.batch_sds.shape),
                                      bundle.batch_sds.dtype)
    assert _program_text(
        jax.jit(lambda s, w: jax.lax.scan(bundle.replay, s, w),
                in_shardings=(bundle.state_shardings,
                              bundle.stacked_sharding()),
                out_shardings=(bundle.state_shardings,
                               NamedSharding(bundle.mesh, P())),
                donate_argnums=(0, 1)).lower(bundle.state_shapes, window_sds)
    ) == _program_text(fused.lower(bundle.state_shapes, window_sds))

    for k in (8, 3):
        got_state, got_losses = bundle.fused_compiled(k)(bundle.init(), stack(k))
        want_state, want_losses = fused(bundle.init(), stack(k))
        assert np.asarray(got_losses).shape == (k,)
        _same_bits(got_losses, want_losses)
        _same_bits(got_state, want_state)

    got, want = bundle.init(), bundle.init()
    for i in range(2):
        batch = jax.device_put(ds.batch(i), bundle.batch_sharding)
        got, got_loss = bundle.compiled(got, batch)
        want, want_loss = step(want, batch)
        _same_bits(got_loss, want_loss)
    _same_bits(got, want)
    assert bundle.step_traces == 1


def test_a_trace_made_beside_the_measuring_thread_is_the_fresh_trace(
        tmp_path, devices8):
    """A grid's points after the first are built while ``search``'s measuring
    thread runs the one before on the chip (PR 37): the program a point's
    bundle keeps from there is, text for text, what a fresh trace gives."""
    task = _task(tmp_path, "test-tiny", "bits-prep", batch=2)
    tech = _technique("dp")
    devices = devices8[:1]
    grid = tech.candidate_configs(task, 1)
    assert len(grid) >= 2
    best, _ = tech.search(task, devices, 0)
    assert best in grid
    for config in grid:
        bundle = tech._cached_bundle(task, devices, config)
        assert bundle.step_traces == 1
        step, _ = _fresh_programs(tech, task, devices, config, bundle)
        assert _program_text(bundle.lowered) == _program_text(
            step.lower(bundle.state_shapes, bundle.batch_sds))
