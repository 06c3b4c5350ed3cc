"""``ops/kda.py``: the chunked delta rule with a decay a key channel,
forward and gradient, against the rule run token by token;
with the gates at their bound over whole chunks (-4.99 a token: ``exp(-G_j)``
alone would overflow at the twentieth token of a chunk) and at 0.

Tolerances as ``tests/test_gdn.py``: float32 inputs, so the two forms differ
by the order of their roundings only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from saturn_tpu.ops import kda
from saturn_tpu.ops import plans as op_plans

GATES = {"spread": None, "at-the-bound": -4.99, "at-zero": 0.0}


def _inputs(seed, t, b=2, h=3, dk=24, dv=40, dtype=jnp.float32, gate=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, h, t, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, h, t, dk)))
    v = jax.random.normal(ks[2], (b, h, t, dv))
    if gate is None:     # over (-5, 0), most near 0, a channel in ten under -2
        g = -5.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[3], (b, h, t, dk)) - 3.0)
    else:
        g = jnp.full((b, h, t, dk), gate, jnp.float32)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, t)) + 1)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.fixture(scope="module")
def token_by_token():
    """(gate, seq length) -> (inputs, a cotangent, the rule's output, its
    gradients)."""
    out = {}
    for name, gate in GATES.items():
        for t in (128, 50):      # two whole chunks of 64, and no multiple
            x = _inputs(t, t, gate=gate)
            w = jax.random.normal(jax.random.PRNGKey(99), x[2].shape)
            want = kda.recurrent_kda(*x)
            grads = jax.grad(lambda *a: jnp.sum(kda.recurrent_kda(*a) * w),
                             argnums=(0, 1, 2, 3, 4))(*x)
            out[name, t] = (x, w, want, grads)
    return out


@pytest.mark.parametrize("t", [128, 50])
@pytest.mark.parametrize("gate", list(GATES))
def test_forward_is_the_rule_token_by_token(token_by_token, gate, t):
    x, _, want, _ = token_by_token[gate, t]
    got = kda.kda(*x)                        # chunks of 64, sub-blocks of 16
    assert got.shape == want.shape and got.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-6)
    assert float(jnp.max(jnp.abs(want))) > 0.2


@pytest.mark.parametrize("t", [128, 50])
@pytest.mark.parametrize("gate", list(GATES))
def test_gradient_is_the_rules_token_by_token(token_by_token, gate, t):
    x, w, _, want = token_by_token[gate, t]
    got = jax.grad(lambda *a: jnp.sum(kda.kda(*a) * w),
                   argnums=(0, 1, 2, 3, 4))(*x)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert bool(jnp.all(jnp.isfinite(a))), name
        # at the bound a state lives a token or two, and g's gradient is what
        # is left of it: 1e-3 of the other inputs' and as much noisier
        tol = 2e-3 if (name, gate) == ("g", "at-the-bound") else 1e-5
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < tol, name


def test_a_state_carried_over_many_chunks():
    x = _inputs(3, 512, b=1, h=2, dk=16, dv=32)
    np.testing.assert_allclose(kda.kda(*x), kda.recurrent_kda(*x), rtol=0, atol=5e-6)


def test_keys_that_are_alike_under_a_strong_beta_and_a_weak_decay():
    """Neighbouring tokens' keys half shared, ``beta`` 0.9, a decay of 0.02 a
    token: the inverse by one doubling product over the chunk holds powers of
    ``A`` whose entries pass 1e12 and cancel (it reads 1.6e3 off the rule
    here); by blocks of 16 merged pair by pair it is the rule."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    shape = (1, 2, 128, 16)
    k = unit(0.5 * jax.random.normal(ks[0], (1, 2, 1, 16)) + 0.5 * jax.random.normal(ks[1], shape))
    x = (unit(jax.random.normal(ks[2], shape)) / 4, k, jax.random.normal(ks[3], shape),
         jnp.full(shape, -0.02), jnp.full(shape[:3], 0.9))
    want = kda.recurrent_kda(*x)
    got = kda.kda(*x)
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 1e-5
    grads = [jax.grad(lambda *a: jnp.sum(f(*a) ** 2), argnums=(0, 1, 2, 3, 4))(*x)
             for f in (kda.kda, kda.recurrent_kda)]
    for a, b in zip(*grads):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-4
    # the one doubling product, on the same chunk's matrix, for comparison
    a = jnp.tril(0.9 * jnp.einsum("id,jd->ij", k[0, 0, :64], k[0, 0, :64]), -1)[None]
    exact = np.linalg.inv(np.eye(64) + np.asarray(a[0], np.float64))
    off = lambda t: float(np.linalg.norm(np.asarray(t[0]) - exact) / np.linalg.norm(exact))
    assert off(kda._unit_lower_inverse_by_blocks(a)) < 1e-5 < 1.0 < off(kda._unit_lower_inverse(a))


def test_a_vector_gate_is_not_its_channels_mean():
    """The decay sits inside the contraction over channels: the same call
    with every channel at the head's mean decay is another function."""
    x = _inputs(4, 64)
    mean = jnp.broadcast_to(jnp.mean(x[3], axis=-1, keepdims=True), x[3].shape)
    a, b = kda.kda(*x), kda.kda(x[0], x[1], x[2], mean, x[4])
    assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(a)) > 0.05
    # and with equal channels it is the gated delta rule of ``ops/gdn.py``
    from saturn_tpu.ops import gdn

    np.testing.assert_allclose(
        b, gdn.gated_delta_rule(x[0], x[1], x[2], mean[..., 0], x[4]), rtol=0, atol=5e-6)


def test_the_decay_ratios_exponent_alone_would_overflow_and_the_sub_blocks_do_not():
    g = jnp.full((1, 64, 8), -4.99)
    big = jnp.cumsum(g, axis=1)
    assert not bool(jnp.all(jnp.isfinite(jnp.exp(-big))))         # the form gdn.py has
    k = jnp.ones((1, 64, 8), jnp.float32)
    scores = kda._decayed_scores(k, k, big, jnp.float32)
    assert bool(jnp.all(jnp.isfinite(scores)))
    i, j = np.tril_indices(64)
    want = 8 * np.exp(-4.99 * (i - j))
    np.testing.assert_allclose(np.asarray(scores)[0][i, j], want, rtol=1e-4, atol=1e-30)


def test_bf16_operands_and_a_float32_state():
    x = _inputs(5, 128, dtype=jnp.bfloat16)
    want = kda.recurrent_kda(*x)       # float32 throughout
    got = kda.kda(*x)
    assert got.dtype == jnp.float32             # o is handed on unrounded
    assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-2
    # the state each chunk starts from is kept, and carried, in float32
    _, starts = kda._fwd_scan(*(t.reshape(6, *t.shape[2:]) for t in x))
    assert starts.dtype == jnp.float32 and starts.shape == (2, 6, 24, 40)


def test_the_plan_of_a_call():
    x = _inputs(7, 100)
    with op_plans.traced() as got:
        jax.eval_shape(kda.kda, *x)
    assert got["kda"] == [kda.KDAPlan("xla", 64, 16, 6, 2, 24, 40, 2 * 6 * 24 * 40 * 4)]
