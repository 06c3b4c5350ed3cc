"""One trace a bundle (PR 34): ``SPMDTechnique._build_uncached`` traces the
train step once to a closed jaxpr, and the 1-step program, every K-step
window program, the memlens audit (``trace_step``) and ``_step_flops`` all
replay or read that one trace.

(a) every technique family: a grid point through ``search`` (build, window
    compile, memory check with its ``memlens_calibration`` event) and then
    ``_step_flops`` calls the model's Python step function once;
(b) programs made from the kept trace are bit-identical, losses and state,
    to programs made by tracing ``train_step`` afresh (a GPT block, the looped
    stack, the stack of several kinds; K = 8, the 1-step program, K = 3):
    ``tests/test_one_trace_bits.py``, a file of its own so that
    ``--dist loadfile`` spreads the compiles;
(c) ``trace_step`` from a cached bundle equals a fresh one;
(d) ``ce_plan`` / ``gdn_plan`` arrive on the ``trial_config`` event of a
    K = 8 search in which the 1-step program was never lowered.
"""

import jax
import pytest

from saturn_tpu.analysis.memlens import liveness
from saturn_tpu.analysis.shardflow.interp import interpret
from saturn_tpu.core.mesh import make_submesh
from saturn_tpu.utils import metrics


def _technique(name):
    # by name from the package's own table, not from the library's registry:
    # other test files of the same worker deregister techniques
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    return BUILTIN_TECHNIQUES[name]()


def _task(tmp_path, preset, name, batch=8):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models import gpt2
    from saturn_tpu.models.loss import pretraining_loss

    build = {"ouro-test-tiny": gpt2.build_ouro,
             "olmo-hybrid-test-tiny": gpt2.build_olmo_hybrid}.get(
                 preset, gpt2.build_gpt2)
    return Task(
        get_model=lambda **kw: build(preset, **kw),
        get_dataloader=lambda: make_lm_dataset(
            context_length=64, batch_size=batch, vocab_size=256,
            n_tokens=64 * batch * 16),
        loss_fn=pretraining_loss,
        hparams=HParams(lr=1e-3, batch_count=16),
        save_dir=str(tmp_path / f"ckpts-{name}"),
        name=name,
    )


def _count_step_calls(tech):
    """Count every Python call of the technique's raw ``train_step`` (and of
    ``make_step_fns``), whoever makes it: independent of the bundle's own
    ``step_traces``."""
    calls = {"make_step_fns": 0, "train_step": 0}
    make = tech.make_step_fns

    def counting(*args, **kwargs):
        calls["make_step_fns"] += 1
        init_state, train_step = make(*args, **kwargs)

        def counted(state, batch):
            calls["train_step"] += 1
            return train_step(state, batch)

        return init_state, counted

    tech.make_step_fns = counting
    return calls


# ------------------------------------------- (a) one Python trace a grid point
@pytest.mark.parametrize("name, size", [
    ("dp", 2), ("fsdp", 2), ("tp", 2), ("pp", 2), ("ring", 2), ("ep", 2),
    ("offload", 1),
])
def test_grid_point_traces_its_step_once(name, size, tmp_path, devices8):
    task = _task(tmp_path, "moe-test-tiny" if name == "ep" else "test-tiny",
                 f"once-{name}")
    tech = _technique(name)
    devices = devices8[:size]
    config = tech.candidate_configs(task, size)[0]
    tech.candidate_configs = lambda task, n: [dict(config)]
    calls = _count_step_calls(tech)

    path = str(tmp_path / "events.jsonl")
    with metrics.scoped(path):
        best, t = tech.search(task, devices, 0)
        flops = tech._step_flops(task, devices, config)
    assert best == config and t > 0
    assert calls == {"make_step_fns": 1, "train_step": 1}

    (point,) = metrics.read_events(path, kind="trial_config")
    assert point["step_traces"] == 1 and point["per_batch_s"] == t
    (cal,) = metrics.read_events(path, kind="memlens_calibration")
    assert cal["predicted_bytes"] > 0
    assert flops and flops > 0

    (build,) = metrics.read_events(path, kind="trial.build")
    assert build["cache"] == "miss" and build["traces"] == 1
    for reader in ("trial.compile", "trial.memlens"):
        (sp,) = _of_the_step(metrics.read_events(path, kind=reader))
        assert sp["trace"] == "shared", (reader, sp)

    k = tech._profile_window(config)
    bundle = tech._cached_bundle(task, devices, config)
    assert bundle.step_traces == 1
    if k > 1:
        # the search profiled the window program: the 1-step program was
        # never lowered, and is there when an interval's tail asks
        assert bundle.has_fused(k) and bundle._lowered is None
        assert bundle.compiled is not None and bundle._lowered is not None
        assert calls["train_step"] == 1


def _of_the_step(found):
    """Without the init program's ``trial.compile`` (``program="init"``, PR
    39): it reads no trace of the train step."""
    return [sp for sp in found if sp.get("program") != "init"]


def test_every_point_of_a_grid_traces_once_on_the_callers_thread(
        tmp_path, devices8):
    """The whole grid (PR 37: the caller's thread walks it and prepares while
    a measuring thread behind it measures): still one Python trace a point,
    made where the point is built, under whatever context the caller set."""
    task = _task(tmp_path, "test-tiny", "once-grid")
    tech = _technique("dp")
    devices = devices8[:1]
    grid = tech.candidate_configs(task, 1)
    assert len(grid) >= 2
    calls = _count_step_calls(tech)

    path = str(tmp_path / "events.jsonl")
    with metrics.scoped(path):
        best, t = tech.search(task, devices, 0)
    assert best in grid and t > 0
    assert calls == {"make_step_fns": len(grid), "train_step": len(grid)}
    points = metrics.read_events(path, kind="trial_config")
    assert len(points) == len(grid)
    assert all(p["step_traces"] == 1 and "per_batch_s" in p for p in points)
    builds = metrics.read_events(path, kind="trial.build")
    assert [b["traces"] for b in builds] == [1] * len(grid)
    assert {b["thread"] for b in builds} == {"MainThread"}
    for reader in ("trial.compile", "trial.memlens"):
        found = _of_the_step(metrics.read_events(path, kind=reader))
        assert len(found) == len(grid)
        assert all(sp["trace"] == "shared" for sp in found), reader
    assert {e["thread"] for e in metrics.read_events(path, kind="trial.timing")
            } == {"meas-MainThread"}
    # the init program is compiled where the point is prepared (``jit`` keeps
    # what ``lower().compile()`` made): putting the state on the chip is a
    # call, on the measuring thread, and compiles nothing there
    inits = [e for e in metrics.read_events(path, kind="compile")
             if "saturn_init" in e["program"]]
    assert len(inits) == len(grid)
    assert {e["thread"] for e in inits} == {"MainThread"}
    assert {e["in_span"]["name"] for e in inits} == {"trial.compile"}
    by_id = {e["id"]: e for e in metrics.read_events(path, kind="trial.compile")}
    assert {by_id[e["in_span"]["id"]]["program"] for e in inits} == {"init"}
    for config in grid:
        assert tech._cached_bundle(task, devices, config).step_traces == 1


# --------------------------------- (c) trace_step: cached bundle = fresh trace
@pytest.mark.parametrize("name", ["dp", "fsdp", "tp"])
def test_trace_step_from_cached_bundle_equals_fresh(name, tmp_path, devices8):
    task = _task(tmp_path, "test-tiny", f"same-{name}")
    devices = devices8[:4]
    tech = _technique(name)
    config = tech.candidate_configs(task, 4)[0]

    bundle = tech.build(task, devices, config)
    cached = tech.trace_step(task, devices, config)
    assert cached["jaxpr"] is bundle.traced["jaxpr"]
    assert tech.build(task, devices, config) is bundle
    assert bundle.step_traces == 1

    # a technique instance with no bundle traces for itself ...
    fresh = _technique(name).trace_step(task, devices, config)
    assert fresh["jaxpr"] is not cached["jaxpr"]
    # ... and so does ``jax.make_jaxpr`` of the raw step, the parent's way
    axis_names, axis_sizes = tech.mesh_spec(4, task, config)
    mesh = make_submesh(devices, axis_names, axis_sizes)
    _, train_step = tech.make_step_fns(
        task.get_model(**tech._model_overrides(config)), task, config, mesh,
        task.get_dataset())
    raw = jax.make_jaxpr(train_step)(cached["state_shapes"],
                                     cached["batch_sds"])

    assert len(cached["jaxpr"].eqns) == len(fresh["jaxpr"].eqns) \
        == len(raw.eqns) > 10   # the step's equations, not one opaque call
    assert [e.primitive.name for e in cached["jaxpr"].eqns] \
        == [e.primitive.name for e in raw.eqns]
    assert set(cached) == set(fresh)
    for key in ("state_specs", "batch_spec", "mesh_axes", "technique", "size",
                "config", "param_memory_kind", "batch_sds"):
        assert cached[key] == fresh[key], key
    for window in (1, 8):
        assert liveness.analyze(cached, window=window).peak_bytes \
            == liveness.analyze(fresh, window=window).peak_bytes
    assert interpret(cached).flops == interpret(fresh).flops > 0


# ------------------- (d) the plans ride the one trace, no 1-step lowering needed
def test_plans_arrive_without_the_one_step_program(tmp_path, devices8):
    task = _task(tmp_path, "olmo-hybrid-test-tiny", "plans", batch=2)
    tech = _technique("dp")
    devices = devices8[:1]
    config = {"remat": False}
    assert config in tech.candidate_configs(task, 1)
    tech.candidate_configs = lambda task, n: [dict(config)]

    path = str(tmp_path / "events.jsonl")
    with metrics.scoped(path):
        best, _ = tech.search(task, devices, 0)
    assert best == config
    (point,) = metrics.read_events(path, kind="trial_config")
    assert "per_batch_s" in point and point["step_traces"] == 1
    # off the chip the fused head computes through plain XLA ops (plan None)
    # and the rule through its plain scan: both were seen in the one trace
    assert "ce_plan" in point
    assert point["gdn_plan"]["chunk"] > 0
    bundle = tech._cached_bundle(task, devices, config)
    assert bundle.has_fused(8)
    assert bundle._lowered is None and bundle._compiled is None


def test_flash_plan_arrives_on_the_trial_config_event(tmp_path, devices8):
    """A grid point whose attention runs the flash kernels says under which
    blocks (``ops/flash.py::flash_plan``, collected at the one trace); the
    dense point beside it says nothing."""
    from saturn_tpu.ops.flash import flash_plan

    task = _task(tmp_path, "test-tiny", "flash-plan", batch=2)
    tech = _technique("dp")
    grid = [{"remat": False, "attention": "flash"},
            {"remat": False, "attention": "dense"}]
    tech.candidate_configs = lambda task, n: [dict(c) for c in grid]

    path = str(tmp_path / "events.jsonl")
    with metrics.scoped(path):
        tech.search(task, devices8[:1], 0)
    points = {e["config"]["attention"]: e
              for e in metrics.read_events(path, kind="trial_config")}
    head_dim = 16   # test-tiny: d_model 64 over 4 heads
    assert points["flash"]["flash_plan"] == flash_plan(64, head_dim)
    assert points["flash"]["flash_plan"]["fwd"]["visited"] == 1
    assert "flash_plan" not in points["dense"]
    assert points["flash"]["step_traces"] == 1
