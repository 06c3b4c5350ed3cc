"""(Second file of two, so that ``--dist loadfile`` spreads the compiles: the
model and its ops are ``tests/test_smallthinker.py``.) The SmallThinker stack
(``build_smallthinker``) at ``smallthinker-test-tiny`` on the CPU, in float32,
through ``search`` -> ``orchestrate`` under dp and through every technique's
own step, against the plain reference ``perf/reference/smallthinker.py`` from
the same seeded weights. A technique that rebuilds the model from
``hints["pipeline"]`` (fsdp / tp overlap, offload's stream) walks whole
periods: the route a block makes ahead of its mixer never leaves the block.
Tolerances as ``tests/test_laguna_techniques.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import refcheck
from perf.reference import smallthinker as st
from saturn_tpu.core.technique import InfeasibleConfig
from saturn_tpu.models.gpt2 import build_smallthinker
from saturn_tpu.utils import metrics
from tests.test_smallthinker import ARCH, FULL, KINDS, SEED, SEQ, SLIDING

LR = 1e-3


def _weights():
    return st.program_params(ARCH, st.seed_key(SEED))


def _task(save_dir, name, batch=2, steps=8, **model_kw):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.loss import pretraining_loss

    def get_model(**kw):
        # (a buffer no step can overflow: at 128 tokens a step the held pairs
        # of a step swing by a third of their mean)
        spec = build_smallthinker(
            "smallthinker-test-tiny", dtype=jnp.float32,
            **{"seq_len": SEQ, "routed_buffer": 100.0, **model_kw, **kw})
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
        tmp_path, devices8, library_as_found):
    import saturn_tpu
    from saturn_tpu.core.mesh import SliceTopology
    from saturn_tpu.utils import checkpoint

    task = _task(tmp_path / "ck", "smallthinker-dp")
    topo = SliceTopology(list(devices8[:1]))
    ev = {k: str(tmp_path / f"{k}.jsonl") for k in ("search", "window")}
    with jax.default_matmul_precision("highest"):
        stats = saturn_tpu.search([task], technique_names=["dp"], topology=topo,
                                  metrics_path=ev["search"], profile_cache=False)
        assert stats["errors"] == 0 and 1 in task.feasible_strategies()
        result = saturn_tpu.orchestrate([task], interval=600.0, topology=topo,
                                        metrics_path=ev["window"], solver_time_limit=2.0)
    assert result["completed"] == ["smallthinker-dp"] and not result["failed"]
    batches = [task.batch_at(i) for i in range(8)]
    ref_losses, ref_state = st.train(ARCH, SEED, batches, LR, keep_state=True)
    (interval,) = metrics.read_events(ev["window"], kind="task_interval")
    np.testing.assert_allclose(interval["losses"], ref_losses, rtol=2e-5)
    state = refcheck.checkpoint_state(checkpoint.load_arrays(task.ckpt_path))
    leaves = {}
    errors = refcheck.state_errors(ref_state, state, leaves=leaves)
    assert errors["grad_rel_rms"] < 1e-3 and errors["update_rel_rms"] < 3e-3, errors
    assert all(leaves[f"blocks/l{i}/router"]["grad_rel_rms"] < 1e-3 for i in range(4))
    # what the events say of the stack, and the routed layers' counters
    assert (interval["stack_layers"], interval["stack_kinds"], interval.get("stack_lead")) == (
        4, KINDS, None)
    assert "mfu" not in interval and "tflops" not in interval     # no wrong figure
    assert 0 < interval["moe_pairs_held"] <= 4 * 2 * SEQ and interval["moe_second_path"] == 0
    assert interval["moe_rows_max"] >= interval["moe_rows_mean"] == \
        pytest.approx(interval["moe_pairs_held"] / 4)
    configs = metrics.read_events(ev["search"], kind="trial_config")
    assert configs and all((e["stack_layers"], e["stack_kinds"], e.get("stack_lead")) == (
        4, KINDS, None) for e in configs)
    plan = configs[0]["moe_plan"]       # off the TPU the grid holds the plain twins only
    assert plan == {"impl": "xla", "tokens": 2 * SEQ, "experts": 16, "held": 4, "top_k": 4,
                    "row_tile": 8, "rows": 512 + 32, "worst_rows": 512 + 32,
                    "act": "reglu", "latent": 0, "bias": False, "groups": 0,
                    "groups_kept": 0, "score": "softmax", "route_from": "block_input",
                    "eps": 0.0, "second_path": False}
    assert configs[0]["rotary_plan"] == {"rotated": [SLIDING], "unrotated": [FULL]}
    assert "window_plan" not in configs[0]            # the masked einsum has no blocks


def test_the_flash_grid_point_says_its_plans(tmp_path, devices8):
    from saturn_tpu.parallel.dp import DataParallel

    tech, devices = DataParallel(), list(devices8[:1])
    task = _task(tmp_path, "smallthinker-plans")
    config = {"remat": True, "attention": "flash"}
    tech.build(task, devices, config)
    fields = tech._plan_fields(task, devices, config)
    assert fields["moe_plan"]["impl"] == "kernel" and fields["step_traces"] == 1
    assert (fields["moe_plan"]["route_from"], fields["moe_plan"]["score"],
            fields["moe_plan"]["act"]) == ("block_input", "softmax", "reglu")
    assert fields["rotary_plan"] == {"rotated": [SLIDING], "unrotated": [FULL]}
    plan = fields["window_plan"]
    assert plan["window"] == 32 and "flash_plan" in fields
    # at the tiny preset the window is half of one block: the few-blocks plan,
    # one block walked by the grid (the cell's 4096 of 8192 takes the causal
    # kernels': tests/test_flash.py::test_window_plan_is_a_pure_function_of_the_shapes)
    assert all((plan[k]["block_q"], plan[k]["block_k"], plan[k]["chunk"],
                plan[k]["steps"], plan[k]["blocks_a_step"]) == (64, 64, 64, 1, 1)
               for k in ("fwd", "dq", "dkv"))


# --------------------------------------------------- every technique
def _technique_names():
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    return sorted(BUILTIN_TECHNIQUES)


@pytest.fixture(scope="module")
def two_reference_steps():
    task = _task("/nonexistent", "ref", batch=4)
    batches = [task.batch_at(i) for i in range(2)]
    losses, state = st.train(ARCH, SEED, batches, LR, keep_state=True)
    return batches, losses, state


def _picks(configs):
    """The first grid point, and the first of each kind that rebuilds the
    model from ``hints["pipeline"]`` (``overlap``: the ZeRO-3 program of fsdp
    and tp; ``stream``: offload's layer loop): their unit is the period, so
    a block's route crosses its mixer inside the unit they walk."""
    out = [configs[0]]
    for key in ("overlap", "stream"):
        hit = next((c for c in configs if c.get(key)), None)
        if hit is not None and hit not in out:
            out.append(hit)
    return out


def refused(tech, task, devices, configs, tmp_path, reason):
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


def offers_nothing(tech, task, devices, configs, tmp_path):
    """No grid point, so no span: the model says it is not sequence-parallel
    (a sliding layer's mask and a routed layer are single-program), and the
    configuration refuses a sequence axis where a model is built."""
    assert not configs and task.get_model().hints["seq_parallel"] is False
    events = str(tmp_path / "ev.jsonl")
    with metrics.scoped(events):
        assert tech.search(task, devices, 0) == (None, None)
    assert not metrics.read_events(events, kind="trial.config")
    with pytest.raises(ValueError, match="single-program"):
        build_smallthinker("smallthinker-test-tiny", seq_axis="seq", seq_axis_size=2)


@pytest.mark.parametrize("name", _technique_names())
def test_every_technique_runs_the_stack_or_refuses_with_a_reason(
        name, tmp_path, devices8, two_reference_steps):
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    tech, devices = BUILTIN_TECHNIQUES[name](), list(devices8[:4])
    task = _task(tmp_path, f"smallthinker-{name}", batch=4)
    batches, ref_losses, ref_state = two_reference_steps
    configs = tech.candidate_configs(task, len(devices))
    if name == "ep":    # the held share is one program's: no exchange of tokens yet
        return refused(tech, task, devices, configs, tmp_path, "exchange of tokens")
    if name == "pp":
        return refused(tech, task, devices, configs, tmp_path, "several block kinds")
    if name in ("ring", "ulysses"):
        return offers_nothing(tech, task, devices, configs, tmp_path)
    for config in _picks(configs):
        with jax.default_matmul_precision("highest"):
            bundle = tech.build(task, devices, config, use_cache=False)
            state, losses = bundle.init(), []
            for tokens in batches:
                state, loss = bundle.step(
                    state, jax.device_put(np.asarray(tokens), bundle.batch_sharding))
                losses.append(float(loss[0] if isinstance(loss, tuple) else loss))
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-5, err_msg=str(config))
        got = st.flat(jax.tree_util.tree_map(np.asarray, jax.device_get(state["params"])))
        off = sum(float(np.sum(np.square(got[k] - v))) for k, v in ref_state["params"].items())
        moved = sum(v ** 2 for v in ref_state["moved"].values())
        assert (off / moved) ** 0.5 < 3e-3, (config, (off / moved) ** 0.5)
