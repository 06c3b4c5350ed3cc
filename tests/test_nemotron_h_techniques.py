"""(Second file of two, so that ``--dist loadfile`` spreads the compiles: the
model and its ops are ``tests/test_nemotron_h.py``.) The Nemotron-H stack
(``build_nemotron_h``) at ``nemotron-test-tiny`` on the CPU, in float32,
through ``search`` -> ``orchestrate`` under dp and through every technique's
own step, against the plain reference ``perf/reference/nemotron_h.py`` from
the same seeded weights. Tolerances as ``tests/test_laguna_techniques.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import refcheck
from perf.reference import nemotron_h as nh
from saturn_tpu.core.technique import InfeasibleConfig
from saturn_tpu.models.gpt2 import build_nemotron_h
from saturn_tpu.utils import metrics
from tests.test_nemotron_h import ARCH, HELD, KINDS, SEED, SEQ

LR = 1e-3


def _weights():
    return nh.program_params(ARCH, nh.seed_key(SEED))


def _task(save_dir, name, batch=2, steps=8, **model_kw):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.loss import pretraining_loss

    def get_model(**kw):
        spec = build_nemotron_h("nemotron-test-tiny", dtype=jnp.float32,
                                **{"seq_len": SEQ, **HELD, **model_kw, **kw})
        return dataclasses.replace(spec, init_fn=lambda rng: _weights())

    return Task(
        get_model=get_model,
        get_dataloader=lambda: make_lm_dataset(
            context_length=SEQ, batch_size=batch, vocab_size=256,
            n_tokens=SEQ * batch * 8, seed=5),
        loss_fn=pretraining_loss, hparams=HParams(lr=LR, batch_count=steps),
        chip_range=[1], name=name, save_dir=str(save_dir))


@pytest.fixture()
def library_as_found():
    from saturn_tpu import library

    before = dict(library._REGISTRY)
    library.register_default_library()
    yield library
    library._REGISTRY.clear()
    library._REGISTRY.update(before)


def test_dp_through_search_and_orchestrate_reproduces_the_reference(
        tmp_path, devices8, library_as_found, monkeypatch):
    import saturn_tpu
    from saturn_tpu.core.mesh import SliceTopology
    from saturn_tpu.ops import moe
    from saturn_tpu.utils import checkpoint

    # (a buffer no step can overflow, as ``tests/test_laguna_techniques.py``:
    # at 128 tokens a step the held pairs swing by a third of their mean)
    monkeypatch.setattr(moe, "BUFFER", 100.0)
    task = _task(tmp_path / "ck", "nemotron-dp")
    topo = SliceTopology(list(devices8[:1]))
    ev = {k: str(tmp_path / f"{k}.jsonl") for k in ("search", "window")}
    with jax.default_matmul_precision("highest"):
        stats = saturn_tpu.search([task], technique_names=["dp"], topology=topo,
                                  metrics_path=ev["search"], profile_cache=False)
        assert stats["errors"] == 0 and 1 in task.feasible_strategies()
        result = saturn_tpu.orchestrate([task], interval=600.0, topology=topo,
                                        metrics_path=ev["window"], solver_time_limit=2.0)
    assert result["completed"] == ["nemotron-dp"] and not result["failed"]
    batches = [task.batch_at(i) for i in range(8)]
    ref_losses, ref_state = nh.train(ARCH, SEED, batches, LR, keep_state=True)
    (interval,) = metrics.read_events(ev["window"], kind="task_interval")
    np.testing.assert_allclose(interval["losses"], ref_losses, rtol=2e-5)
    state = refcheck.checkpoint_state(checkpoint.load_arrays(task.ckpt_path))
    errors = refcheck.state_errors(ref_state, state)
    assert errors["grad_rel_rms"] < 1e-3 and errors["update_rel_rms"] < 3e-3, errors
    # what the events say of the stack, and the routed layers' counters
    assert (interval["stack_layers"], interval["stack_kinds"]) == (11, KINDS)
    assert "stack_lead" not in interval
    assert "mfu" not in interval and "tflops" not in interval     # no wrong figure
    assert 0 < interval["moe_pairs_held"] <= 3 * 2 * SEQ and interval["moe_second_path"] == 0
    assert interval["moe_rows_max"] >= interval["moe_rows_mean"] == \
        pytest.approx(interval["moe_pairs_held"] / 4)
    configs = metrics.read_events(ev["search"], kind="trial_config")
    assert configs and all((e["stack_layers"], e["stack_kinds"]) == (11, KINDS)
                           for e in configs)
    plan = configs[0]["moe_plan"]       # off the TPU the grid holds the plain twins only
    assert plan == {"impl": "xla", "tokens": 2 * SEQ, "experts": 12, "held": 4, "top_k": 3,
                    "row_tile": 8, "rows": 384 + 32, "worst_rows": 384 + 32,
                    "act": "relu2", "latent": 32, "bias": True, "groups": 0,
                    "groups_kept": 0, "score": "sigmoid", "route_from": "ff_input",
                    "eps": 0.0, "second_path": False}
    ssd_plan = configs[0]["ssd_plan"]
    assert (ssd_plan["impl"], ssd_plan["chunk"], ssd_plan["heads"], ssd_plan["groups"],
            ssd_plan["heads_published"], ssd_plan["groups_published"]) == ("xla", 16, 4, 2, 16, 8)
    assert ssd_plan["state_bytes_kept"] == (SEQ // 16) * 2 * 4 * 8 * 16 * 4
    assert ssd_plan["vmem_bytes"] is None


def test_the_kernel_grid_point_says_its_plans(tmp_path, devices8):
    from saturn_tpu.parallel.dp import DataParallel

    tech, devices = DataParallel(), list(devices8[:1])
    task = _task(tmp_path, "nemotron-plans")
    config = {"remat": True, "attention": "flash"}
    tech.build(task, devices, config)
    fields = tech._plan_fields(task, devices, config)
    assert fields["moe_plan"]["impl"] == "kernel" and fields["step_traces"] == 1
    assert fields["ssd_plan"]["impl"] == "kernel" and fields["ssd_plan"]["vmem_bytes"] > 0
    assert "gdn_plan" not in fields and "window_plan" not in fields


# --------------------------------------------------- every technique
def _technique_names():
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    return sorted(BUILTIN_TECHNIQUES)


@pytest.fixture(scope="module")
def two_reference_steps():
    task = _task("/nonexistent", "ref", batch=4)
    batches = [task.batch_at(i) for i in range(2)]
    losses, state = nh.train(ARCH, SEED, batches, LR, keep_state=True)
    return batches, losses, state


def _picks(configs):
    """The first grid point, and the first of each kind that rebuilds the
    model from ``hints["pipeline"]`` (``overlap``: the ZeRO-3 program of fsdp
    and tp; ``stream``: offload's layer loop): their unit is the period of
    eleven mixer-alone layers."""
    out = [configs[0]]
    for key in ("overlap", "stream"):
        hit = next((c for c in configs if c.get(key)), None)
        if hit is not None and hit not in out:
            out.append(hit)
    return out


def _refused(tech, task, devices, configs, tmp_path, reason):
    """Every grid point ends as a ``trial.config`` span with the reason, and
    a hand-made strategy is refused in the same place."""
    events = str(tmp_path / "ev.jsonl")
    with metrics.scoped(events):
        assert tech.search(task, devices, 0) == (None, None)
    spans = metrics.read_events(events, kind="trial.config")
    noted = metrics.read_events(events, kind="trial_config")
    assert configs and len(spans) == len(noted) == len(configs)
    for span, event in zip(spans, noted):
        assert span["outcome"] == "infeasible" and reason in span["reason"]
        assert event["infeasible"] == span["reason"] and event["stack_kinds"] == KINDS
    with pytest.raises(InfeasibleConfig, match=reason):
        tech.build(task, devices, configs[0], use_cache=False)


@pytest.mark.parametrize("name", _technique_names())
def test_every_technique_runs_the_stack_or_refuses_with_a_reason(
        name, tmp_path, devices8, two_reference_steps, monkeypatch):
    from saturn_tpu.ops import moe
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    monkeypatch.setattr(moe, "BUFFER", 100.0)
    tech, devices = BUILTIN_TECHNIQUES[name](), list(devices8[:4])
    task = _task(tmp_path, f"nemotron-{name}", batch=4)
    batches, ref_losses, ref_state = two_reference_steps
    configs = tech.candidate_configs(task, len(devices))
    if name == "ep":    # the held share is one program's: no exchange of latent rows yet
        return _refused(tech, task, devices, configs, tmp_path, "exchange of tokens")
    if name == "pp":
        return _refused(tech, task, devices, configs, tmp_path, "several block kinds")
    if name in ("ring", "ulysses"):
        # a state-space layer's state crosses the whole sequence: the model
        # says it is not sequence-parallel, and the techniques offer no grid point
        assert not configs and task.get_model().hints["seq_parallel"] is False
        assert tech.search(task, devices, 0) == (None, None)
        return
    for config in _picks(configs):
        with jax.default_matmul_precision("highest"):
            bundle = tech.build(task, devices, config, use_cache=False)
            state, losses = bundle.init(), []
            for tokens in batches:
                state, loss = bundle.step(
                    state, jax.device_put(np.asarray(tokens), bundle.batch_sharding))
                losses.append(float(loss[0] if isinstance(loss, tuple) else loss))
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-5, err_msg=str(config))
        got = nh.flat(jax.tree_util.tree_map(np.asarray, jax.device_get(state["params"])))
        off = sum(float(np.sum(np.square(got[k] - v))) for k, v in ref_state["params"].items())
        moved = sum(v ** 2 for v in ref_state["moved"].values())
        assert (off / moved) ** 0.5 < 3e-3, (config, (off / moved) ** 0.5)


def test_step_flops_are_left_out_and_the_analyses_walk_the_chunk_scan(tmp_path, devices8):
    """Shardflow cannot see into the recurrence's kernel and ``custom_vjp``,
    so the package reports no ``tflops`` / ``mfu`` rather than a figure short
    by a mixer; shardflow and memlens still walk the step's one trace, the
    chunk scan inside the period and the routed layers' ``cond`` included."""
    from saturn_tpu.analysis.memlens import liveness
    from saturn_tpu.analysis.shardflow.interp import interpret
    from saturn_tpu.parallel.dp import DataParallel

    tech, devices = DataParallel(), list(devices8[:1])
    config = {"remat": True, "attention": "dense"}
    task = _task(tmp_path, "nemotron-flops")
    assert tech._step_flops(task, devices, config) is None
    traced = tech.trace_step(task, devices, config)
    assert liveness.analyze(traced, window=1).peak_bytes > 0
    assert liveness.analyze(traced, window=8).peak_bytes >= \
        liveness.analyze(traced, window=1).peak_bytes
    assert interpret(traced).flops > 0
