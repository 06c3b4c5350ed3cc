"""The plain reference against the package's model at tiny widths, on the CPU:
forward logits, and the losses of a few AdamW steps through the package's own
step scaffold. The package is built in float32 here, so the two differ by
rounding order only and the tolerance is float32's; the bf16 comparison at
published widths is the benchmark's reference check, on the chip."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from perf.lib import refcheck
from perf.reference import gpt
from saturn_tpu.models.gpt2 import build_gpt2
from saturn_tpu.models.loss import pretraining_loss

ARCHS = {
    "test-tiny": gpt.Arch("gpt2", vocab_size=256, d_model=64, n_layers=2,
                          n_heads=4, d_ff=256, n_positions=64),
    "gptj-test-tiny": gpt.Arch("gptj", vocab_size=256, d_model=64, n_layers=2,
                               n_heads=4, d_ff=256, n_positions=64, rotary_dim=8),
}


def _tokens(seed, batch=2, seq=64):
    return np.random.default_rng(seed).integers(0, 256, size=(batch, seq), dtype=np.int32)


@pytest.mark.parametrize("preset", list(ARCHS))
def test_program_params_have_the_packages_tree(preset):
    spec = build_gpt2(preset)
    want = jax.eval_shape(spec.init_fn, jax.random.PRNGKey(0))
    got = jax.eval_shape(lambda: gpt.program_params(ARCHS[preset], gpt.seed_key(3)))
    assert jax.tree_util.tree_structure(want) == jax.tree_util.tree_structure(got)
    assert jax.tree_util.tree_leaves(want) == jax.tree_util.tree_leaves(got)


@pytest.mark.parametrize("preset", list(ARCHS))
def test_forward_agrees_with_the_package(preset):
    arch, tokens = ARCHS[preset], _tokens(1)
    spec = build_gpt2(preset, dtype=jnp.float32, attention="dense")
    with jax.default_matmul_precision("highest"):
        sys_logits = spec.apply_fn(gpt.program_params(arch, gpt.seed_key(11)), jnp.asarray(tokens))
    ref_logits = gpt.logits_of(arch, 11, tokens)
    np.testing.assert_allclose(np.asarray(sys_logits), np.asarray(ref_logits),
                               rtol=0, atol=2e-5)


def test_interleaved_rotary_needs_the_lane_permutation():
    """The reference's published (interleaved) rotary and the package's split
    halves agree only through ``program_params``' permutation: handing the
    package the reference's own layout must not agree."""
    arch, tokens = ARCHS["gptj-test-tiny"], _tokens(2)
    spec = build_gpt2("gptj-test-tiny", dtype=jnp.float32, attention="dense")
    with jax.default_matmul_precision("highest"):
        wrong = spec.apply_fn(gpt.seeded_params(arch, gpt.seed_key(11)), jnp.asarray(tokens))
    ref = gpt.logits_of(arch, 11, tokens)
    assert float(jnp.max(jnp.abs(wrong - ref))) > 1e-3


@pytest.mark.parametrize("preset", list(ARCHS))
def test_adamw_steps_agree_with_optax_through_the_package(preset):
    arch, lr, steps = ARCHS[preset], 1e-3, 4
    batches = [_tokens(10 + i) for i in range(steps)]
    spec = build_gpt2(preset, dtype=jnp.float32, attention="dense")
    tx = optax.adamw(lr)
    params = gpt.program_params(arch, gpt.seed_key(5))
    opt = tx.init(params)
    sys_losses = []
    with jax.default_matmul_precision("highest"):
        for b in batches:
            loss, grads = jax.value_and_grad(
                lambda p: pretraining_loss(spec.apply_fn(p, jnp.asarray(b)), jnp.asarray(b)))(params)
            updates, opt = tx.update(grads, opt, params)
            params = optax.apply_updates(params, updates)
            sys_losses.append(float(loss))
    ref_losses, ref_state = gpt.train(arch, 5, batches, lr, keep_state=True)
    np.testing.assert_allclose(sys_losses, ref_losses, rtol=2e-5)
    # the final state, leaf by leaf in the program's layout (for GPT-J: the
    # lane permutation on host arrays), as the reference check compares it
    sys_state = {"m": gpt.flat(jax.tree_util.tree_map(np.asarray, opt[0].mu)),
                 "params": gpt.flat(jax.tree_util.tree_map(np.asarray, params))}
    errors = refcheck.state_errors(ref_state, sys_state)
    assert errors["grad_rel_rms"] < 1e-4 and errors["update_rel_rms"] < 1e-3, errors


def test_seed_past_31_bits_is_a_seed():
    a = ARCHS["test-tiny"]
    make = jax.jit(lambda k: gpt.seeded_params(a, k)["wte"])
    big, small = make(gpt.seed_key(2**31 + 12345)), make(gpt.seed_key(12345))
    assert not np.allclose(np.asarray(big), np.asarray(small))
