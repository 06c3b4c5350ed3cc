"""(Second file of two, so that ``--dist loadfile`` spreads the compiles: the\nmodel itself is ``tests/test_olmo_hybrid.py``.) The hybrid stack (``build_olmo_hybrid``: periods of three gated-delta-rule
layers and one full-attention layer, one ``nn.scan`` over a period block) at
``olmo-hybrid-test-tiny`` (two periods deep) on the CPU, in float32, against
the plain reference ``perf/reference/olmo_hybrid.py`` (the rule token by
token) from the same seeded weights.

Tolerances. Program and reference are both float32 here and differ by the
order of their roundings only (the chunked form against the token scan, a
fused qkv against three products, flax's norm against the written-out one):
logits to 2e-5 absolute of values around 0.5, gradients to 2e-4 of each leaf's
norm. Through AdamW a rounding difference in a gradient element near zero
becomes a difference of a whole step in that element, so weights after
training are held to 3e-3 of the distance training moved them and losses to
2e-5 relative (``tests/test_ouro.py``'s figures).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import refcheck
from perf.reference import olmo_hybrid as oh
from saturn_tpu.core.technique import InfeasibleConfig
from saturn_tpu.models.gpt2 import build_olmo_hybrid
from saturn_tpu.utils import metrics

PERIOD = ("linear_attention",) * 3 + ("full_attention",)
KINDS = {"linear_attention": 3, "full_attention": 1}
ARCH = oh.Arch(vocab_size=256, d_model=64, kinds=PERIOD * 2, period=4, n_heads=4,
               head_dim=16, key_dim=12, value_dim=24, conv_taps=4, neg_eigval=True,
               d_inner=176, norm_eps=1e-6)
SEQ, SEED, LR = 64, 2_147_483_659, 1e-3
VARIANTS = {"dense": {"attention": "dense"},
            "dense-remat": {"attention": "dense", "remat": True},
            "flash": {"attention": "flash"},          # both Pallas kernels, interpret mode
            "flash-remat": {"attention": "flash", "remat": True}}


def _tokens(seed, batch=2, seq=SEQ):
    return np.random.default_rng(seed).integers(0, 256, size=(batch, seq), dtype=np.int32)


def _spec(**kw):
    return build_olmo_hybrid("olmo-hybrid-test-tiny", dtype=jnp.float32, **kw)


def _weights(arch=ARCH):
    return oh.program_params(arch, oh.seed_key(SEED))


# --------------------------------------------- search -> orchestrate, dp
def _task(save_dir, name, batch=2, steps=8, seeded=True, **model_kw):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.loss import pretraining_loss

    def get_model(**kw):
        spec = _spec(**{"seq_len": SEQ, **model_kw, **kw})
        if not seeded:
            return spec
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

    task = _task(tmp_path / "ck", "hybrid-dp")
    topo = SliceTopology(list(devices8[:1]))
    ev = {k: str(tmp_path / f"{k}.jsonl") for k in ("search", "window")}
    with jax.default_matmul_precision("highest"):
        stats = saturn_tpu.search([task], technique_names=["dp"], topology=topo,
                                  metrics_path=ev["search"], profile_cache=False)
        assert stats["errors"] == 0 and 1 in task.feasible_strategies()
        result = saturn_tpu.orchestrate([task], interval=600.0, topology=topo,
                                        metrics_path=ev["window"], solver_time_limit=2.0)
    assert result["completed"] == ["hybrid-dp"] and not result["failed"]
    batches = [task.batch_at(i) for i in range(8)]
    ref_losses, ref_state = oh.train(ARCH, SEED, batches, LR, keep_state=True)
    (interval,) = metrics.read_events(ev["window"], kind="task_interval")
    np.testing.assert_allclose(interval["losses"], ref_losses, rtol=2e-5)
    state = refcheck.checkpoint_state(checkpoint.load_arrays(task.ckpt_path))
    errors = refcheck.state_errors(ref_state, state)
    # (the first moment after 8 steps sums 8 gradients taken at weights that
    # already differ by Adam's rounding: 3e-4 read, 2e-4 for one gradient)
    assert errors["grad_rel_rms"] < 1e-3 and errors["update_rel_rms"] < 3e-3, errors
    # what the events say of the stack and of the rule's implementation
    assert (interval["stack_layers"], interval["stack_kinds"]) == (8, KINDS)
    assert "mfu" not in interval and "tflops" not in interval     # no wrong figure
    configs = metrics.read_events(ev["search"], kind="trial_config")
    assert configs and all((e["stack_layers"], e["stack_kinds"]) == (8, KINDS) for e in configs)
    plan = configs[0]["gdn_plan"]       # off the TPU the grid holds the plain scan only
    assert plan == {"impl": "xla", "chunk": 16, "n": 2 * 4, "chunks": 4, "dk": 12, "dv": 24,
                    "vmem_bytes": None}


# --------------------------------------------------- every technique
def _technique_names():
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    return sorted(BUILTIN_TECHNIQUES)


@pytest.fixture(scope="module")
def two_reference_steps():
    task = _task("/nonexistent", "ref", batch=4)
    batches = [task.batch_at(i) for i in range(2)]
    losses, state = oh.train(ARCH, SEED, batches, LR, keep_state=True)
    return batches, losses, state


def _picks(configs):
    """The first grid point, and the first of each kind that rebuilds the
    model from ``hints["pipeline"]`` (``overlap``: the ZeRO-3 program of fsdp
    and tp; ``stream``: offload's layer loop): their unit is the period."""
    out = [configs[0]]
    for key in ("overlap", "stream"):
        hit = next((c for c in configs if c.get(key)), None)
        if hit is not None and hit not in out:
            out.append(hit)
    return out




@pytest.mark.parametrize("name", _technique_names())
def test_every_technique_runs_the_hybrid_or_refuses_with_a_reason(
        name, tmp_path, devices8, two_reference_steps):
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    tech, devices = BUILTIN_TECHNIQUES[name](), list(devices8[:4])
    task = _task(tmp_path, f"hybrid-{name}", batch=4)
    batches, ref_losses, ref_state = two_reference_steps
    configs = tech.candidate_configs(task, len(devices))
    if name == "ep":    # no experts to shard: refused as for every dense model
        assert not configs and task.get_model().hints["moe"] is None
        return
    if name in ("ring", "ulysses"):
        # a linear layer's state crosses the whole sequence: the model says it
        # is not sequence-parallel, and the techniques offer no grid point
        assert not configs and task.get_model().hints["seq_parallel"] is False
        assert tech.search(task, devices, 0) == (None, None)
        return
    if name == "pp":
        events = str(tmp_path / "ev.jsonl")
        with metrics.scoped(events):
            assert tech.search(task, devices, 0) == (None, None)
        spans = metrics.read_events(events, kind="trial.config")
        noted = metrics.read_events(events, kind="trial_config")
        assert configs and len(spans) == len(noted) == len(configs)
        for span, event in zip(spans, noted):
            assert span["outcome"] == "infeasible" and "several block kinds" in span["reason"]
            assert event["infeasible"] == span["reason"] and event["stack_kinds"] == KINDS
        with pytest.raises(InfeasibleConfig, match="several block kinds"):
            tech.build(task, devices, configs[0], use_cache=False)
        return
    for config in _picks(configs):
        with jax.default_matmul_precision("highest"):
            bundle = tech.build(task, devices, config, use_cache=False)
            state, losses = bundle.init(), []
            for tokens in batches:
                state, loss = bundle.step(
                    state, jax.device_put(np.asarray(tokens), bundle.batch_sharding))
                losses.append(float(loss))
        np.testing.assert_allclose(losses, ref_losses, rtol=2e-5, err_msg=str(config))
        got = oh.flat(jax.tree_util.tree_map(np.asarray, jax.device_get(state["params"])))
        off = sum(float(np.sum(np.square(got[k] - v))) for k, v in ref_state["params"].items())
        moved = sum(v ** 2 for v in ref_state["moved"].values())
        assert (off / moved) ** 0.5 < 3e-3, (config, (off / moved) ** 0.5)


def test_step_flops_are_left_out_rather_than_short_by_a_mixer(tmp_path, devices8):
    from saturn_tpu.parallel.dp import DataParallel

    tech, devices = DataParallel(), list(devices8[:1])
    config = {"remat": False, "attention": "dense"}
    assert tech._step_flops(_task(tmp_path, "hybrid-flops", seeded=False), devices, config) is None
