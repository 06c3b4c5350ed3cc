"""``ops/ssd.py``: the chunked Mamba-2 recurrence, both implementations (the
plain ``lax.scan`` twin and the Pallas kernel in interpret mode), forward and
gradient, against the recurrence run token by token.

Tolerances as ``tests/test_gdn.py``: inputs are float32 here, so every
product of the chunked form is a float32 product at precision ``highest`` and
the two forms differ by the order of their roundings only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from saturn_tpu.ops import plans as op_plans
from saturn_tpu.ops import ssd

IMPLS = ("xla", "kernel")
NAMES = ("x", "dt", "a", "b", "c", "d")


def _inputs(seed, t, bsz=2, h=4, p=8, g=2, n=16, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (bsz, t, h, p))
    dt = 0.3 * jax.nn.softplus(jax.random.normal(ks[1], (bsz, t, h)))
    a = -jnp.exp(jax.random.uniform(ks[2], (h,), minval=0.0, maxval=2.0))
    b = jax.random.normal(ks[3], (bsz, t, g, n))
    c = jax.random.normal(ks[4], (bsz, t, g, n))
    d = 1.0 + 0.1 * jax.random.normal(ks[5], (h,))
    return x.astype(dtype), dt, a, b.astype(dtype), c.astype(dtype), d


@pytest.fixture(scope="module")
def token_by_token():
    """seq length -> (inputs, the recurrence's output, its gradients under a
    fixed random cotangent)."""
    out = {}
    for t in (64, 50):      # a multiple of the chunk, and not
        x = _inputs(t, t)
        w = jax.random.normal(jax.random.PRNGKey(99), x[0].shape)
        want = ssd.recurrent_ssd(*x)
        grads = jax.grad(lambda *a: jnp.sum(ssd.recurrent_ssd(*a) * w),
                         argnums=tuple(range(6)))(*x)
        out[t] = (x, w, want, grads)
    return out


@pytest.mark.parametrize("t", [64, 50])
@pytest.mark.parametrize("impl", IMPLS)
def test_forward_is_the_recurrence_token_by_token(token_by_token, impl, t):
    x, _, want, _ = token_by_token[t]
    with jax.default_matmul_precision("highest"):
        got = ssd.ssd(*x, impl=impl, chunk=16)
    assert got.shape == want.shape and got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=0, atol=3e-5)
    assert float(jnp.max(jnp.abs(want))) > 3.0


@pytest.mark.parametrize("t", [64, 50])
@pytest.mark.parametrize("impl", IMPLS)
def test_gradient_is_the_recurrences_token_by_token(token_by_token, impl, t):
    x, w, _, want = token_by_token[t]
    with jax.default_matmul_precision("highest"):
        got = jax.grad(lambda *a: jnp.sum(ssd.ssd(*a, impl=impl, chunk=16) * w),
                       argnums=tuple(range(6)))(*x)
    for name, a, b in zip(NAMES, got, want):
        assert float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b)) < 1e-5, name


def test_the_default_chunk_of_128_and_a_state_carried_over_many_chunks():
    x = _inputs(3, 512, bsz=1, h=2, p=8, g=1, n=16)
    want = ssd.recurrent_ssd(*x)
    for impl in IMPLS:
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(ssd.ssd(*x, impl=impl), want, rtol=0, atol=5e-5)


def test_a_group_of_heads_shares_b_and_c_and_groups_are_independent():
    """Head h reads group h // (H / G): the two groups' halves of a call are
    two calls of one group each."""
    x, dt, a, b, c, d = _inputs(11, 32)
    whole = ssd.ssd(x, dt, a, b, c, d, chunk=16)
    for grp in range(2):
        hs = slice(2 * grp, 2 * grp + 2)
        part = ssd.ssd(x[:, :, hs], dt[:, :, hs], a[hs], b[:, :, grp:grp + 1],
                       c[:, :, grp:grp + 1], d[hs], chunk=16)
        np.testing.assert_allclose(part, whole[:, :, hs], rtol=0, atol=1e-6)
    with pytest.raises(ValueError, match="groups"):
        ssd.ssd(x[:, :, :3], dt[:, :, :3], a[:3], b, c, d[:3])


def test_bf16_operands_float32_state_kernel_and_twin_alike():
    x = _inputs(5, 64, dtype=jnp.bfloat16)
    want = ssd.recurrent_ssd(*x)                    # float32 throughout
    outs = {impl: ssd.ssd(*x, impl=impl, chunk=16) for impl in IMPLS}
    for impl, got in outs.items():
        assert got.dtype == jnp.float32             # o is handed on unrounded
        assert float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want)) < 2e-2, impl
    np.testing.assert_allclose(outs["kernel"], outs["xla"], rtol=0, atol=5e-2)


def test_which_kernel_runs_where_and_the_plan_of_a_call():
    x = _inputs(7, 64)
    fn = lambda *a: jnp.sum(ssd.ssd(*a, impl="kernel", chunk=16))
    alone = str(jax.make_jaxpr(fn)(*x))
    assert "saturn_ssd_fwd_only" in alone            # outside a gradient: no states kept
    under_grad = str(jax.make_jaxpr(jax.grad(fn))(*x)).replace("saturn_ssd_fwd_only", "")
    assert "saturn_ssd_fwd" in under_grad            # the differentiated forward keeps them
    with op_plans.traced() as got:
        jax.eval_shape(lambda *a: ssd.ssd(*a, impl="kernel", chunk=16,
                                          published=(16, 8)), *x)
        jax.eval_shape(lambda *a: ssd.ssd(*a, impl="xla"), *x)
    plans = got["ssd"]
    kept = 4 * 2 * 4 * 8 * 16 * 4          # chunks x batch x heads x P x N x 4 B
    assert plans[0] == ssd.SSDPlan("kernel", 16, 4, 4, 4, 2, 16, 8, 8, 16, kept,
                                   ssd.fwd_vmem_bytes(16, 2, 8, 16, 4))
    assert plans[1] == ssd.SSDPlan("xla", 128, 4, 1, 4, 2, 4, 2, 8, 16, kept // 4, None)
    # at the published widths (a group's 16 heads of 64 x 128) the kernel's
    # blocks are inside the 16 MiB a v5e core gives a kernel by default
    assert ssd.fwd_vmem_bytes(128, 16, 64, 128, 2) < 12 * 2**20
    with pytest.raises(ValueError, match="impl"):
        ssd.ssd(*x, impl="flash")
