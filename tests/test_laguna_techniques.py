"""(Second file of two, so that ``--dist loadfile`` spreads the compiles: the
model and its ops are ``tests/test_laguna.py``.) The Laguna stack
(``build_laguna``) at ``laguna-test-tiny`` on the CPU, in float32, through
``search`` -> ``orchestrate`` under dp and through every technique's own
step, against the plain reference ``perf/reference/laguna.py`` from the same
seeded weights. Tolerances as ``tests/test_olmo_hybrid_techniques.py``."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import refcheck
from perf.reference import laguna as lg
from saturn_tpu.core.technique import InfeasibleConfig
from saturn_tpu.models.gpt2 import build_laguna
from saturn_tpu.utils import metrics
from tests.test_laguna import ARCH, FULL, KINDS, SEED, SEQ, SLIDING

LR = 1e-3
LEAD = {"full_attention_dense": 1}


def _weights():
    return lg.program_params(ARCH, lg.seed_key(SEED))


def _task(save_dir, name, batch=2, steps=8, **model_kw):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.loss import pretraining_loss

    def get_model(**kw):
        spec = build_laguna("laguna-test-tiny", dtype=jnp.float32,
                            **{"seq_len": SEQ, **model_kw, **kw})
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
    from saturn_tpu.utils import checkpoint

    # (a buffer no step can overflow: at 128 tokens a step the held pairs of
    # a step swing by a third of their mean, and ``moe_second_path`` would
    # count the steps past the default buffer: 4 of 8 here)
    from saturn_tpu.ops import moe

    monkeypatch.setattr(moe, "BUFFER", 100.0)
    task = _task(tmp_path / "ck", "laguna-dp")
    topo = SliceTopology(list(devices8[:1]))
    ev = {k: str(tmp_path / f"{k}.jsonl") for k in ("search", "window")}
    with jax.default_matmul_precision("highest"):
        stats = saturn_tpu.search([task], technique_names=["dp"], topology=topo,
                                  metrics_path=ev["search"], profile_cache=False)
        assert stats["errors"] == 0 and 1 in task.feasible_strategies()
        result = saturn_tpu.orchestrate([task], interval=600.0, topology=topo,
                                        metrics_path=ev["window"], solver_time_limit=2.0)
    assert result["completed"] == ["laguna-dp"] and not result["failed"]
    batches = [task.batch_at(i) for i in range(8)]
    ref_losses, ref_state = lg.train(ARCH, SEED, batches, LR, keep_state=True)
    (interval,) = metrics.read_events(ev["window"], kind="task_interval")
    np.testing.assert_allclose(interval["losses"], ref_losses, rtol=2e-5)
    state = refcheck.checkpoint_state(checkpoint.load_arrays(task.ckpt_path))
    errors = refcheck.state_errors(ref_state, state)
    assert errors["grad_rel_rms"] < 1e-3 and errors["update_rel_rms"] < 3e-3, errors
    # what the events say of the stack, and the routed layers' counters: read
    # back with the losses, one number an interval
    assert (interval["stack_layers"], interval["stack_kinds"], interval["stack_lead"]) == (
        5, KINDS, LEAD)
    assert "mfu" not in interval and "tflops" not in interval     # no wrong figure
    assert 0 < interval["moe_pairs_held"] <= 4 * 2 * SEQ and interval["moe_second_path"] == 0
    assert interval["moe_rows_max"] >= interval["moe_rows_mean"] == \
        pytest.approx(interval["moe_pairs_held"] / 4)
    configs = metrics.read_events(ev["search"], kind="trial_config")
    assert configs and all((e["stack_layers"], e["stack_kinds"], e["stack_lead"]) == (
        5, KINDS, LEAD) for e in configs)
    plan = configs[0]["moe_plan"]       # off the TPU the grid holds the plain twins only
    assert plan == {"impl": "xla", "tokens": 2 * SEQ, "experts": 16, "held": 4, "top_k": 4,
                    "row_tile": 8, "rows": 512 + 32, "worst_rows": 512 + 32,
                    "act": "swiglu", "latent": 0, "bias": False,   # (PR 42's fields)
                    "groups": 0, "groups_kept": 0,                 # (PR 45's)
                    "score": "sigmoid", "route_from": "ff_input",  # (PR 49's)
                    "eps": 0.0, "second_path": False}                 # (PR 52's)
    assert "window_plan" not in configs[0]            # the masked einsum has no blocks


def test_the_flash_grid_point_says_its_window_plan(tmp_path, devices8):
    from saturn_tpu.parallel.dp import DataParallel

    tech, devices = DataParallel(), list(devices8[:1])
    task = _task(tmp_path, "laguna-plans")
    config = {"remat": True, "attention": "flash"}
    tech.build(task, devices, config)
    fields = tech._plan_fields(task, devices, config)
    assert fields["moe_plan"]["impl"] == "kernel" and fields["step_traces"] == 1
    plan = fields["window_plan"]
    assert (plan["window"], plan["seq"], plan["head_dim"]) == (24, SEQ, 16)
    assert plan["fwd"] == plan["dq"] == plan["dkv"] == {    # one block is all of T
        "block_q": 64, "block_k": 64, "chunk": 64, "visited": 1, "masked": 1,
        "steps": 1, "blocks_a_step": 1, "computed_over_needed": 3.251}


# --------------------------------------------------- every technique
def _technique_names():
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    return sorted(BUILTIN_TECHNIQUES)


@pytest.fixture(scope="module")
def two_reference_steps():
    task = _task("/nonexistent", "ref", batch=4)
    batches = [task.batch_at(i) for i in range(2)]
    losses, state = lg.train(ARCH, SEED, batches, LR, keep_state=True)
    return batches, losses, state


def _picks(configs):
    """The first grid point, and the first of each kind that rebuilds the
    model from ``hints["pipeline"]`` (``overlap``: the ZeRO-3 program of fsdp
    and tp; ``stream``: offload's layer loop): their ``embed`` runs the
    leading layer, their unit is the period."""
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
        name, tmp_path, devices8, two_reference_steps):
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    tech, devices = BUILTIN_TECHNIQUES[name](), list(devices8[:4])
    task = _task(tmp_path, f"laguna-{name}", batch=4)
    batches, ref_losses, ref_state = two_reference_steps
    configs = tech.candidate_configs(task, len(devices))
    if name == "ep":    # the held share is one program's: no exchange of tokens yet
        return _refused(tech, task, devices, configs, tmp_path, "exchange of tokens")
    if name == "pp":
        return _refused(tech, task, devices, configs, tmp_path, "several block kinds")
    if name in ("ring", "ulysses"):
        # a sliding layer's mask and a routed layer are single-program: the
        # model says it is not sequence-parallel and no grid point is offered
        # (the configuration refuses a sequence axis where a model is built)
        assert not configs and task.get_model().hints["seq_parallel"] is False
        with pytest.raises(ValueError, match="single-program"):
            build_laguna("laguna-test-tiny", seq_axis="seq", seq_axis_size=2)
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
        got = lg.flat(jax.tree_util.tree_map(np.asarray, jax.device_get(state["params"])))
        off = sum(float(np.sum(np.square(got[k] - v))) for k, v in ref_state["params"].items())
        moved = sum(v ** 2 for v in ref_state["moved"].values())
        assert (off / moved) ** 0.5 < 3e-3, (config, (off / moved) ** 0.5)
