"""The looped LM (``build_ouro``: one scanned stack run ``n_passes`` times on
shared weights) at ``ouro-test-tiny`` on the CPU, in float32, against the
plain reference ``perf/reference/ouro.py`` from the same seeded weights.

Tolerances. Program and reference are both float32 here and differ by the
order of their roundings only (a scan against a Python loop, a fused qkv
against three products, flax's norm against the written-out one): logits to
2e-5 absolute of values around 0.5, gradients to 2e-4 of each leaf's norm.
Through AdamW a rounding difference in a gradient element near zero becomes a
difference of a whole step in that element (the first steps are
``lr * sign(g)``), so weights after training are held to 1e-3 of the
distance training moved them and losses to 2e-5 relative, the figures
``perf/tests/test_reference.py`` uses for the GPT families.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import refcheck
from perf.reference import ouro
from saturn_tpu.core.technique import InfeasibleConfig
from saturn_tpu.models.gpt2 import build_gpt2, build_ouro
from saturn_tpu.utils import metrics

ARCH = ouro.Arch(vocab_size=256, d_model=64, layers_held=2, ut_steps=4,
                 n_heads=4, d_inner=176, rope_theta=1e6, norm_eps=1e-6)
SEQ, SEED, LR = 64, 2_147_483_659, 1e-3
VARIANTS = {"dense": {"attention": "dense"},
            "dense-remat": {"attention": "dense", "remat": True},
            "flash": {"attention": "flash"},          # Pallas, interpret mode
            "flash-remat": {"attention": "flash", "remat": True}}


def _tokens(seed, batch=2, seq=SEQ):
    return np.random.default_rng(seed).integers(0, 256, size=(batch, seq), dtype=np.int32)


def _spec(**kw):
    return build_ouro("ouro-test-tiny", dtype=jnp.float32, **kw)


def _weights():
    return ouro.program_params(ARCH, ouro.seed_key(SEED))


def _rel(got, want):
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


@pytest.fixture(scope="module")
def reference_grads():
    tokens = jnp.asarray(_tokens(1))
    with jax.default_matmul_precision("highest"):
        grads = jax.grad(lambda p: ouro.loss_fn(ARCH, p, tokens))(
            ouro.seeded_params(ARCH, ouro.seed_key(SEED)))
    return ouro.program_layout(ARCH, grads)


# ------------------------------------------------------------ the model
def test_preset_is_the_published_model_and_the_tree_is_the_references():
    cfg = build_ouro("ouro-2.6b").config
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.ff_dim, cfg.vocab_size,
            cfg.n_layers, cfg.n_passes, cfg.rope_theta) == (
        2048, 16, 128, 5632, 49152, 48, 4, 1e6)
    assert (cfg.norm, cfg.mlp_act, cfg.sandwich_norm, cfg.use_bias, cfg.tie_head,
            cfg.rotary, cfg.rotary_dim) == ("rmsnorm", "swiglu", True, False, False, True, None)
    want = jax.eval_shape(_spec().init_fn, jax.random.PRNGKey(0))
    got = jax.eval_shape(_weights)
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert jax.tree_util.tree_leaves(want) == jax.tree_util.tree_leaves(got)
    assert "lm_head" in got and "wpe" not in got and "bias" not in str(got)


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_logits_agree_with_the_reference(variant):
    tokens = _tokens(1)
    with jax.default_matmul_precision("highest"):
        got = _spec(**VARIANTS[variant]).apply_fn(_weights(), jnp.asarray(tokens))
    want = ouro.logits_of(ARCH, SEED, tokens)
    assert float(jnp.max(jnp.abs(want))) > 0.3
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0, atol=2e-5)


@pytest.mark.parametrize("variant", list(VARIANTS))
def test_gradients_agree_with_the_reference(variant, reference_grads):
    """The fused head + loss path the techniques train through; each shared
    leaf's gradient is the sum over its four uses."""
    spec, tokens = _spec(**VARIANTS[variant]), jnp.asarray(_tokens(1))
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda p: spec.fused_loss_fn(p, tokens))(_weights())
    worst = jax.tree_util.tree_map(_rel, got, reference_grads)
    assert max(jax.tree_util.tree_leaves(worst)) < 2e-4, worst


def test_shared_weight_backward_is_the_sum_over_an_untied_twin(reference_grads):
    """The twin: 4 x N layers with weights of their own (copies of the shared
    ones), built from the pieces ``hints["pipeline"]`` hands a technique
    (embed, block, the between-passes function, head). Its per-copy gradients
    differ from pass to pass; summed over the four copies of a layer they are
    the looped model's."""
    from saturn_tpu.models.loss import pretraining_loss

    spec, tokens, params = _spec(attention="dense"), jnp.asarray(_tokens(1)), _weights()
    pipe = spec.hints["pipeline"]
    assert pipe["passes"] == spec.stack_passes == 4 and spec.stack_layers == 2
    n = ARCH.layers_held
    other = {k: v for k, v in params.items() if k != "blocks"}
    copies = jax.tree_util.tree_map(lambda a: jnp.tile(a, (4,) + (1,) * (a.ndim - 1)),
                                    params["blocks"])

    def twin_loss(copies, other):
        x = pipe["embed"](other, tokens)
        for t in range(4):
            if t:
                x = pipe["between"](other, x)
            for l in range(n):
                x = pipe["block"](jax.tree_util.tree_map(lambda a: a[t * n + l], copies), x)
        return pretraining_loss(pipe["head"](other, x), tokens)

    with jax.default_matmul_precision("highest"):
        g_copies, g_other = jax.grad(twin_loss, argnums=(0, 1))(copies, other)
    per_pass = jax.tree_util.tree_map(lambda g: g.reshape((4, n) + g.shape[1:]), g_copies)
    leaf = per_pass["mlp_out"]["kernel"]
    assert _rel(leaf[0], leaf[3]) > 0.1          # the passes do differ
    summed = jax.tree_util.tree_map(lambda g: g.sum(axis=0), per_pass)
    worst = jax.tree_util.tree_map(_rel, dict(g_other, blocks=summed), reference_grads)
    assert max(jax.tree_util.tree_leaves(worst)) < 2e-4, worst


def test_one_pass_is_the_plain_stack():
    """``n_passes=1`` takes the path every other preset takes (no outer
    scan) and equals the reference with one pass; four passes do not."""
    tokens = _tokens(2)
    spec = _spec(n_passes=1, attention="dense")
    assert spec.stack_passes == 1 and spec.hints["pipeline"]["between"] is None
    with jax.default_matmul_precision("highest"):
        got = spec.apply_fn(_weights(), jnp.asarray(tokens))
    one = ouro.Arch(**{**ARCH.__dict__, "ut_steps": 1})
    np.testing.assert_allclose(np.asarray(got), np.asarray(ouro.logits_of(one, SEED, tokens)),
                               rtol=0, atol=2e-5)
    assert float(jnp.max(jnp.abs(got - ouro.logits_of(ARCH, SEED, tokens)))) > 1e-2
    # and a model that says nothing has one pass
    assert build_gpt2("gptj-test-tiny").stack_passes == 1


def test_new_options_are_validated():
    with pytest.raises(ValueError, match="sandwich_norm"):
        build_gpt2("gptj-test-tiny", sandwich_norm=True)
    with pytest.raises(ValueError, match="n_passes"):
        build_ouro("ouro-test-tiny", n_passes=0)
    with pytest.raises(ValueError, match="looped"):
        build_gpt2("moe-test-tiny", n_passes=2)


# ------------------------------------------- search -> orchestrate, dp
def _task(save_dir, name, batch=2, steps=8, seeded=True, **model_kw):
    from saturn_tpu import HParams, Task
    from saturn_tpu.data.lm_dataset import make_lm_dataset
    from saturn_tpu.models.loss import pretraining_loss
    import dataclasses

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

    task = _task(tmp_path / "ck", "ouro-dp")
    topo = SliceTopology(list(devices8[:1]))
    ev = {k: str(tmp_path / f"{k}.jsonl") for k in ("search", "window")}
    with jax.default_matmul_precision("highest"):
        stats = saturn_tpu.search([task], technique_names=["dp"], topology=topo,
                                  metrics_path=ev["search"], profile_cache=False)
        assert stats["errors"] == 0 and 1 in task.feasible_strategies()
        result = saturn_tpu.orchestrate([task], interval=600.0, topology=topo,
                                        metrics_path=ev["window"], solver_time_limit=2.0)
    assert result["completed"] == ["ouro-dp"] and not result["failed"]
    batches = [task.batch_at(i) for i in range(8)]
    ref_losses, ref_state = ouro.train(ARCH, SEED, batches, LR, keep_state=True)
    (interval,) = metrics.read_events(ev["window"], kind="task_interval")
    np.testing.assert_allclose(interval["losses"], ref_losses, rtol=2e-5)
    state = refcheck.checkpoint_state(checkpoint.load_arrays(task.ckpt_path))
    errors = refcheck.state_errors(ref_state, state)
    assert errors["grad_rel_rms"] < 2e-4 and errors["update_rel_rms"] < 1e-3, errors
    # what the events say of the stack
    assert (interval["stack_layers"], interval["stack_passes"]) == (2, 4)
    configs = metrics.read_events(ev["search"], kind="trial_config")
    assert configs and all((e["stack_layers"], e["stack_passes"]) == (2, 4) for e in configs)


# --------------------------------------------------- every technique
def _technique_names():
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    return sorted(BUILTIN_TECHNIQUES)


@pytest.fixture(scope="module")
def two_reference_steps():
    task = _task("/nonexistent", "ref", batch=4)
    batches = [task.batch_at(i) for i in range(2)]
    losses, state = ouro.train(ARCH, SEED, batches, LR, keep_state=True)
    return batches, losses, state


def _picks(configs):
    """The first grid point, and the first of each kind that rebuilds the
    model from ``hints["pipeline"]`` (``overlap``: the ZeRO-3 program of fsdp
    and tp; ``stream``: offload's layer loop)."""
    out = [configs[0]]
    for key in ("overlap", "stream"):
        hit = next((c for c in configs if c.get(key)), None)
        if hit is not None and hit not in out:
            out.append(hit)
    return out


@pytest.mark.parametrize("name", _technique_names())
def test_every_technique_runs_the_loop_or_refuses_with_a_reason(
        name, tmp_path, devices8, two_reference_steps):
    from saturn_tpu.parallel import BUILTIN_TECHNIQUES

    tech, devices = BUILTIN_TECHNIQUES[name](), list(devices8[:4])
    task = _task(tmp_path, f"ouro-{name}", batch=4)
    batches, ref_losses, ref_state = two_reference_steps
    configs = tech.candidate_configs(task, len(devices))
    if name in ("pp", "ep"):
        events = str(tmp_path / "ev.jsonl")
        with metrics.scoped(events):
            assert tech.search(task, devices, 0) == (None, None)
        if name == "ep":   # no experts to shard: refused as for every dense model
            assert not configs and task.get_model().hints["moe"] is None
            return
        # every grid point is refused where the step would be built
        # (``make_step_fns``), so ``execute`` on a hand-made strategy is too
        spans = metrics.read_events(events, kind="trial.config")
        noted = metrics.read_events(events, kind="trial_config")
        assert configs and len(spans) == len(noted) == len(configs)
        for span, event in zip(spans, noted):
            assert span["outcome"] == "infeasible"
            assert "4 times" in span["reason"] and name in span["reason"]
            assert event["infeasible"] == span["reason"] and event["stack_passes"] == 4
        with pytest.raises(InfeasibleConfig, match="4 times"):
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
        got = ouro.flat(jax.tree_util.tree_map(np.asarray, jax.device_get(state["params"])))
        off = sum(float(np.sum(np.square(got[k] - v))) for k, v in ref_state["params"].items())
        moved = sum(v ** 2 for v in ref_state["moved"].values())
        # 3e-3, not the dense path's 1e-3: the sequence-parallel techniques
        # sum the loss shard by shard (another rounding order), and two Adam
        # steps turn a gradient element's last bit near zero into 2 x lr
        # (ring and ulysses read 1.5e-3; a dropped pass reads above 0.5)
        assert (off / moved) ** 0.5 < 3e-3, (config, (off / moved) ** 0.5)


# ----------------------------------------------------- static analyses
def _nested(passes, layers, width=32):
    """A scan over layers inside a scan over passes, each layer one
    (width x width) product whose input is kept (a stash, as under remat)."""
    def model(w, x):
        def one_pass(h, _):
            def layer(h, wl):
                return jnp.tanh(h @ wl), h
            return jax.lax.scan(layer, h, w)
        return jax.lax.scan(one_pass, x, None, length=passes)

    w = jax.ShapeDtypeStruct((layers, width, width), jnp.float32)
    x = jax.ShapeDtypeStruct((8, width), jnp.float32)
    return jax.make_jaxpr(model)(w, x), [((), (), ()), ((), ())]


@pytest.mark.parametrize("passes,layers", [(1, 2), (4, 2), (1, 6), (4, 6)])
def test_shardflow_counts_a_scan_in_a_scan_by_both_trip_counts(passes, layers):
    from saturn_tpu.analysis.shardflow.interp import Interpreter

    closed, specs = _nested(passes, layers)
    interp = Interpreter({"data": 1})
    interp.run(closed, specs)
    assert interp.ledger.flops == 2.0 * 8 * 32 * 32 * passes * layers


def test_memlens_keeps_a_stash_of_both_trip_counts():
    from saturn_tpu.analysis.memlens.liveness import analyze_closed

    def peak(passes, layers):
        closed, specs = _nested(passes, layers)
        return analyze_closed(closed, specs, {"data": 1}).peak_bytes

    one = 8 * 32 * 4                       # one kept layer input
    base = peak(1, 2)
    assert peak(4, 2) - base == 3 * 2 * one
    assert peak(1, 6) - base == 4 * (one + 32 * 32 * 4)   # and four more layers' weights
    assert peak(4, 6) - peak(1, 6) == 3 * 6 * one


def test_step_flops_of_the_looped_model_follow_applications(tmp_path, devices8):
    """The package's own count (``analysis/shardflow`` over the traced dp
    step): dense FLOPs = head + passes x layers x one layer's."""
    from saturn_tpu.analysis.shardflow.interp import interpret
    from saturn_tpu.parallel.dp import DataParallel

    def flops(passes, layers):
        task = _task(tmp_path, f"f{passes}{layers}", seeded=False,
                     n_passes=passes, n_layers=layers)
        return interpret(DataParallel().trace_step(
            task, list(devices8[:1]), {"remat": False, "attention": "dense"})).flops

    base, per_layer = flops(1, 2), (flops(1, 4) - flops(1, 2)) / 2
    assert per_layer > 0
    assert flops(4, 2) - base == pytest.approx(3 * 2 * per_layer, rel=1e-6)
