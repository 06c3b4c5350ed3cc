"""``ops/gdn.py``: the chunked gated delta rule, both implementations (the
plain ``lax.scan`` twin and the Pallas kernel in interpret mode), forward and
gradient, against the rule run token by token.

Tolerances. Inputs are float32 here, so every product of the chunked form is
a float32 product at precision ``highest`` and the two forms differ by the
order of their roundings only: outputs to 5e-6 absolute of values up to 0.7,
gradients to 1e-5 of each input's gradient norm. With bf16 inputs the kernel
and the twin round the same operands and agree to bf16's last bit or two.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from saturn_tpu.ops import gdn
from saturn_tpu.ops import plans as op_plans

IMPLS = ("xla", "kernel")


def _inputs(seed, t, b=2, h=3, dk=24, dv=40, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (b, h, t, dk))) / np.sqrt(dk)
    k = unit(jax.random.normal(ks[1], (b, h, t, dk)))
    v = jax.random.normal(ks[2], (b, h, t, dv))
    g = -jnp.exp(jax.random.normal(ks[3], (b, h, t)) - 1.0)          # log decay
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (b, h, t)) + 1)   # most above 1
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.fixture(scope="module")
def token_by_token():
    """seq length -> (inputs, the rule's output, its gradients under a fixed
    random cotangent)."""
    out = {}
    for t in (64, 50):      # a multiple of the chunk, and not
        x = _inputs(t, t)
        w = jax.random.normal(jax.random.PRNGKey(99), x[2].shape)
        want = gdn.recurrent_gated_delta_rule(*x)
        grads = jax.grad(lambda *a: jnp.sum(gdn.recurrent_gated_delta_rule(*a) * w),
                         argnums=(0, 1, 2, 3, 4))(*x)
        out[t] = (x, w, want, grads)
    return out


@pytest.mark.parametrize("t", [64, 50])
@pytest.mark.parametrize("impl", IMPLS)
def test_forward_is_the_rule_token_by_token(token_by_token, impl, t):
    x, _, want, _ = token_by_token[t]
    assert float(jnp.mean(x[4] > 1)) > 0.5          # negative eigenvalues exercised
    got = gdn.gated_delta_rule(*x, impl=impl, chunk=16)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=5e-6)
    assert float(jnp.max(jnp.abs(want))) > 0.3


@pytest.mark.parametrize("t", [64, 50])
@pytest.mark.parametrize("impl", IMPLS)
def test_gradient_is_the_rules_token_by_token(token_by_token, impl, t):
    x, w, _, want = token_by_token[t]
    got = jax.grad(lambda *a: jnp.sum(gdn.gated_delta_rule(*a, impl=impl, chunk=16) * w),
                   argnums=(0, 1, 2, 3, 4))(*x)
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got, want):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5, name


def test_the_default_chunk_of_64_and_a_state_carried_over_many_chunks():
    x = _inputs(3, 256, b=1, h=2, dk=16, dv=32)
    want = gdn.recurrent_gated_delta_rule(*x)
    for impl in IMPLS:
        np.testing.assert_allclose(gdn.gated_delta_rule(*x, impl=impl), want,
                                   rtol=0, atol=5e-6)


def test_triangular_inverse_by_products():
    a = np.tril(np.random.default_rng(0).normal(size=(3, 16, 16)), -1).astype(np.float32)
    got = gdn._unit_lower_inverse(jnp.asarray(a))
    np.testing.assert_allclose(got, np.linalg.inv(np.eye(16) + a), rtol=0, atol=2e-4)


def test_bf16_operands_float32_state_kernel_and_twin_alike():
    x = _inputs(5, 64, dtype=jnp.bfloat16)
    want = gdn.recurrent_gated_delta_rule(*x)       # float32 throughout
    outs = {impl: gdn.gated_delta_rule(*x, impl=impl, chunk=16) for impl in IMPLS}
    for impl, got in outs.items():
        assert got.dtype == jnp.float32             # o is handed on unrounded
        # bf16 products against float32 ones: 3 decimal digits
        assert float(jnp.linalg.norm(got.astype(jnp.float32) - want)
                     / jnp.linalg.norm(want)) < 2e-2, impl
    np.testing.assert_allclose(outs["kernel"].astype(jnp.float32),
                               outs["xla"].astype(jnp.float32), rtol=0, atol=2e-2)


def test_which_kernel_runs_where_and_the_plan_of_a_call():
    x = _inputs(7, 64)
    fn = lambda *a: jnp.sum(gdn.gated_delta_rule(*a, impl="kernel", chunk=16))
    alone = str(jax.make_jaxpr(fn)(*x))
    assert "saturn_gdn_fwd_only" in alone            # outside a gradient: no states kept
    under_grad = str(jax.make_jaxpr(jax.grad(fn))(*x)).replace("saturn_gdn_fwd_only", "")
    assert "saturn_gdn_fwd" in under_grad            # the differentiated forward keeps them
    with op_plans.traced() as got:
        jax.eval_shape(lambda *a: gdn.gated_delta_rule(*a, impl="kernel", chunk=16), *x)
        jax.eval_shape(lambda *a: gdn.gated_delta_rule(*a, impl="xla"), *x)
    plans = got["gdn"]
    assert plans[0] == gdn.GDNPlan("kernel", 16, 6, 4, 24, 40,
                                   gdn.fwd_vmem_bytes(16, 24, 40, 4))
    assert plans[1] == gdn.GDNPlan("xla", 64, 6, 1, 24, 40, None)
    # at the published head widths the kernel's blocks are far inside VMEM
    assert gdn.fwd_vmem_bytes(64, 96, 192, 2) < 2 * 2**20
    with pytest.raises(ValueError, match="impl"):
        gdn.gated_delta_rule(*x, impl="flash")
