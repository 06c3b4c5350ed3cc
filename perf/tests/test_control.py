"""The control of the reference check, at a size a test run can hold: the
plain reference with its matmuls, forward and backward, in fp8 (the precision
below the configurations' bf16) has to fall outside the limits of
``perf/reference/limits.json`` -- by its logits (forward), by its gradients
(backward) and by its weights (optimizer), each alone -- and the same reference in bf16, what the
program computes in, has to stand inside them. The readings at the cells' own
sizes were taken on the chip by ``control_on_chip.py`` (PERF.md)."""

import numpy as np
import pytest

from perf.lib import refcheck
from perf.reference import gpt

ARCH = gpt.Arch("gptj", vocab_size=512, d_model=128, n_layers=2, n_heads=4,
                d_ff=512, n_positions=128, rotary_dim=16)
SEEDS = (2_147_483_659, 7, 99)


@pytest.fixture(scope="module")
def readings():
    out = {}
    for seed in SEEDS:
        _, batches = refcheck.sample_batches(512, 128, 2, 4, seed)
        ref_losses, ref_logits, ref_state = refcheck.reference_side(
            gpt, ARCH, seed, batches, 1e-3)
        for kind in ("bf16", "fp8"):
            losses, logits, state = refcheck.reference_side(
                gpt, ARCH, seed, batches, 1e-3, refcheck.lowp_mm(kind))
            out[(seed, kind)] = {
                "logits_rel_rms": refcheck.logits_error(ref_logits, logits),
                **refcheck.loss_errors(ref_losses, losses),
                **refcheck.state_errors(ref_state, state)}
    return out


def test_fp8_control_is_not_correct_and_bf16_is(readings):
    limits = refcheck.load_limits()
    said = []
    for (seed, kind), numbers in readings.items():
        ok = refcheck.verdict(numbers, limits, said.append, f"{kind}@{seed}")
        assert ok == (kind == "bf16"), (seed, kind, numbers, limits)


NUMBERS = ["logits_rel_rms", "grad_rel_rms", "update_rel_rms"]


@pytest.mark.parametrize("number", NUMBERS)
def test_control_fails_by_forward_and_by_backward_alone(readings, number):
    limit = refcheck.load_limits()[number]
    for (seed, kind), numbers in readings.items():
        assert (numbers[number] <= limit) == (kind == "bf16"), (seed, kind, numbers)


@pytest.mark.parametrize("number", NUMBERS)
def test_control_stands_three_times_off(readings, number):
    sound = max(v[number] for (s, k), v in readings.items() if k == "bf16")
    control = min(v[number] for (s, k), v in readings.items() if k == "fp8")
    assert control > 3 * sound, (sound, control)


def test_fp8_backward_alone_moves_the_gradients():
    """A forward in float32 with the backward products in fp8 -- what a later
    PR's low-precision dx / dw kernel would be -- leaves the logits exact and
    is caught by ``grad_rel_rms``."""
    import jax

    fp8 = refcheck.lowp_mm("fp8")

    @jax.custom_vjp
    def mm(x, w):
        return x @ w

    mm.defvjp(lambda x, w: (x @ w, (x, w)),
              lambda kept, g: jax.vjp(fp8, *kept)[1](g))
    seed = SEEDS[0]
    _, batches = refcheck.sample_batches(512, 128, 2, 4, seed)
    ref_losses, ref_logits, ref_state = refcheck.reference_side(
        gpt, ARCH, seed, batches, 1e-3)
    losses, logits, state = refcheck.reference_side(gpt, ARCH, seed, batches, 1e-3, mm)
    limits = refcheck.load_limits()
    assert refcheck.logits_error(ref_logits, logits) < 1e-6
    assert refcheck.state_errors(ref_state, state)["grad_rel_rms"] > limits["grad_rel_rms"]


def test_a_wrong_length_trajectory_is_not_correct():
    assert not np.isfinite(refcheck.loss_errors([1.0, 0.9], [1.0])["loss_drop_rel"])


def test_a_state_with_a_leaf_missing_is_not_correct():
    state = {"m": {"a": np.ones(3, np.float32)}, "params": {"a": np.ones(3, np.float32)},
             "moved": {"a": 1.0}}
    other = {"m": {}, "params": {}}
    assert not np.isfinite(refcheck.state_errors(state, other)["grad_rel_rms"])
