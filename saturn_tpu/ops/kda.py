"""Kimi delta attention: the delta rule with a decay a key channel.

Per head, with a float32 state ``S`` of (d_k, d_v), ``S_0 = 0``:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``alpha_t = exp(g_t)`` in (0, 1]^d_k is the decay, one number a key channel
(``ops/gdn.py``'s rule has one a head), ``beta_t`` the writing strength. The
caller normalises ``q`` and ``k`` and bounds the gate (``g`` in (-5, 0) in the
one model that calls this: ``kda_lower_bound``); nothing here knows a model.

**The chunked form** (chunks of ``C`` = 64 tokens). With ``G_i`` in R^d_k the
sum of ``g`` over the chunk's tokens up to ``i``, ``Gamma = exp(G)`` and ``S``
the state the chunk starts from, writing the rule as
``S_t = Diag(alpha_t) S_{t-1} + k_t u_t^T`` gives

    (I + A) U = diag(beta) (V - (Gamma * K) S),
    A[i, j] = beta_i sum_c k_ic exp(G_ic - G_jc) k_jc   for j < i, else 0
    O      = (Gamma * Q) S + (P * [j <= i]) U,
    P[i, j] = sum_c q_ic exp(G_ic - G_jc) k_jc
    S_next = Diag(Gamma_C) S + (exp(G_C - G) * K)^T U.

With a decay a head, ``exp(G_i - G_j)`` multiplies ``Q K^T`` after the
product. Here it sits inside the contraction over channels, so ``A`` and ``P``
are products of *decayed* operands, and ``exp(-G_j)`` alone overflows float32
over a 64-token chunk at the gate's bound (64 x 5 = 320 against 88). So the
rows go by **sub-blocks of** ``SUB`` = 16 tokens with a reference point: for
the rows ``I`` of one sub-block and ``r`` its first token,

    P[I, :] = (Q_I * exp(G_I - G_r)) (K * exp(G_r - G))^T

where the left exponent is <= 0 and the right one is <= 0 for the keys before
``r`` and at most 15 x 5 = 75 < 88 for the keys of the sub-block itself (what
the gate's bound is for); the keys after it are under the mask, and their
exponent is cut at ``_CUT`` so that nothing under the mask is infinite.
``T = (I + A)^-1`` **by blocks**: each diagonal block of ``SUB`` tokens by
``ops/gdn.py``'s doubling product, then neighbouring blocks merged pair by
pair, ``[[T1, 0], [-T2 A21 T1, T2]]``, up to the chunk. (The doubling product
over a whole chunk of 64 holds powers of ``A`` up to the 32nd on its way, and
where neighbouring tokens' keys are alike and ``beta`` is near 1 their entries
pass 1e12 and cancel: at keys half shared, ``beta`` 0.9 and a decay of 0.02 a
token it reads 1.6e3 off the rule in float32, by blocks 8e-7:
``tests/test_kda.py``.) The state, the gates' sums and
``T`` are float32; the other products take their operands in the inputs' dtype
(bf16 in a training step) and accumulate in float32.

One implementation of that form: batched products and a ``lax.scan`` over
the chunks' states, in plain XLA ops. (A Pallas forward, ``saturn_kda_fwd``
at several heads a grid step, was written with it and read 10.6 ms a layer
alone where this scan reads 9.07: each head-chunk's own work, the inverse's
twelve float32 products at ``highest`` first, bounds it, not the grid. It
comes back with the change that makes it win: ROADMAP.md, M5.)

**The backward** is a ``custom_vjp``, as ``ops/gdn.py``'s: the forward keeps
the inputs and the state each chunk started from ((T / C) x d_k x d_v float32
a head: 268 MB a layer at 32 heads of 128 x 128 and 8192 tokens), and a
reverse ``lax.scan`` carries ``dS`` from chunk to chunk, taking each chunk's
gradients as the vjp of the chunk's own forward, recomputed.

``tests/test_kda.py`` holds the form, forward and gradient, to the rule run
token by token, with the gates at their bound and at 0.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from saturn_tpu.ops import plans
from saturn_tpu.ops.gdn import (_HIGHEST, _by_chunks, _from_chunks, _mm, _mm32,
                                _unit_lower_inverse)

CHUNK = 64
SUB = 16
#: the largest exponent taken of a decay ratio: what lies beyond it is under
#: the mask (a key after the row's sub-block), and must be finite there
_CUT = 80.0


def _decayed_scores(rows, keys, big, dtype):
    """``[sum_c rows_ic exp(G_ic - G_jc) keys_jc]`` (N, C, C) float32, right
    for j <= i within i's sub-block and for every j before it (the caller
    masks the rest): sub-block by sub-block against the reference point of
    the module docstring. ``rows`` / ``keys`` (N, C, d), ``big`` (N, C, d)."""
    n, c, d = rows.shape
    blocks = lambda x: x.reshape(n, c // SUB, SUB, d)
    ref = blocks(big)[:, :, :1]                                       # G_r: (N, nb, 1, d)
    left = blocks(rows.astype(jnp.float32)) * jnp.exp(blocks(big) - ref)
    right = keys.astype(jnp.float32)[:, None] * jnp.exp(
        jnp.minimum(ref - big[:, None], _CUT))                        # (N, nb, C, d)
    return _mm(left, right, "nbid,nbjd->nbij", dtype).reshape(n, c, c)


@jax.custom_vjp
def _unit_lower_inverse_by_blocks(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` (N, C, C), C a power
    of two times ``SUB``: by blocks (module docstring). Its gradient is the
    inverse's own, ``dA = -T^T dT T^T``: two products, where differentiating
    through the blocks' products takes some forty (1.3 MiB more of compiled
    step a layer, compiled for a described v5e)."""
    c, size = a.shape[-1], SUB
    spec = "nbij,nbjk->nbik"

    def blocks(side, down, step):
        """The (side x side) blocks ``down`` rows under the diagonal, every
        ``step``: (N, C / step, side, side)."""
        return jnp.stack([a[:, r + down:r + down + side, r:r + side]
                          for r in range(0, c, step)], axis=1)

    t = _unit_lower_inverse(blocks(size, 0, size))
    while size < c:
        t1, t2 = t[:, 0::2], t[:, 1::2]
        low = -_mm32(t2, _mm32(blocks(size, size, 2 * size), t1, spec), spec)
        t = jnp.concatenate([jnp.concatenate([t1, jnp.zeros_like(t1)], axis=-1),
                             jnp.concatenate([low, t2], axis=-1)], axis=-2)
        size *= 2
    return t[:, 0]


def _inverse_fwd(a):
    t = _unit_lower_inverse_by_blocks(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm32(tt, _mm32(dt, tt, "nij,njk->nik"), "nij,njk->nik"),)


_unit_lower_inverse_by_blocks.defvjp(_inverse_fwd, _inverse_bwd)


def _chunk(s, q, k, v, g, beta):
    """One chunk of every (batch x head): ``s`` (N, dk, dv) float32, ``q`` /
    ``k`` (N, C, dk), ``v`` (N, C, dv), ``g`` (N, C, dk) / ``beta`` (N, C)
    float32 -> (``o`` (N, C, dv) float32, the next state)."""
    dt, c = q.dtype, q.shape[1]
    big = jnp.cumsum(g, axis=1)                                       # G
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    a = jnp.where(i > j, beta[:, :, None] * _decayed_scores(k, k, big, dt), 0.0)
    t = _unit_lower_inverse_by_blocks(a)
    gamma = jnp.exp(big)
    kf = k.astype(jnp.float32)
    w = _mm32(t, beta[..., None] * gamma * kf, "nij,njd->nid")
    u = _mm32(t, beta[..., None] * v.astype(jnp.float32), "nij,njd->nid")
    u = u - _mm(w, s, "nid,nde->nie", dt)
    attn = jnp.where(i >= j, _decayed_scores(q, k, big, dt), 0.0)
    o = _mm(gamma * q.astype(jnp.float32), s, "nid,nde->nie", dt) \
        + _mm(attn, u, "nij,nje->nie", dt)
    last = big[:, -1:]                                                # (N, 1, dk)
    s_next = jnp.exp(last[:, 0])[..., None] * s \
        + _mm(jnp.exp(last - big) * kf, u, "nid,nie->nde", dt)
    return o, s_next


# The scans sit behind ``jit``'s tracing cache (as ``ops/flash.py``'s launchers
# do): the same layer again (five of a period's six, each again under remat
# and in the ``custom_vjp``'s rules) binds what was traced the first time. A
# chunk's vjp is among the step's longest traces.
@plans._traced_once()
def _fwd_scan(q, k, v, g, beta):
    """-> (o (N, T, dv) float32, the state each chunk started from
    (T / C, N, dk, dv) float32)."""
    def body(s, xs):
        o, s_next = _chunk(s, *xs)
        return s_next, (o, s)

    s0 = jnp.zeros((q.shape[0], q.shape[-1], v.shape[-1]), jnp.float32)
    _, (o, starts) = jax.lax.scan(
        body, s0, tuple(_by_chunks(x, CHUNK) for x in (q, k, v, g, beta)))
    return _from_chunks(o), starts


@plans._traced_once()
def _bwd_scan(q, k, v, g, beta, starts, do):
    def body(ds, xs):
        s, do_c, *inputs = xs
        _, vjp = jax.vjp(_chunk, s, *inputs)
        ds_prev, *grads = vjp((do_c, ds))
        return ds_prev, tuple(grads)

    xs = (starts,) + tuple(_by_chunks(x, CHUNK) for x in (do, q, k, v, g, beta))
    _, grads = jax.lax.scan(body, jnp.zeros_like(starts[0]), xs, reverse=True)
    return tuple(_from_chunks(x) for x in grads)


@jax.custom_vjp
def _kda(q, k, v, g, beta):
    return _fwd_scan(q, k, v, g, beta)[0]


def _kda_fwd(q, k, v, g, beta):
    o, starts = _fwd_scan(q, k, v, g, beta)
    return o, (q, k, v, g, beta, starts)


_kda.defvjp(_kda_fwd, lambda res, do: _bwd_scan(*res, do))


# ------------------------------------------------------------------- plan
class KDAPlan(NamedTuple):
    """What one call of :func:`kda` was traced as (``kda_plan`` on the
    ``trial_config`` event)."""
    impl: str            # "xla": the plain chunked scan (no kernel yet)
    chunk: int
    sub: int             # tokens of a sub-block: one reference point each
    n: int               # batch x heads
    chunks: int
    dk: int
    dv: int
    state_bytes_kept: int       # the chunks' starting states kept for the backward


def kda(q, k, v, g, beta):
    """``q`` / ``k`` (B, H, T, dk), ``v`` (B, H, T, dv), ``g`` (B, H, T, dk)
    (log decay a channel, <= 0, and no lower than -88 / ``SUB`` a token) /
    ``beta`` (B, H, T) float32 -> ``o`` (B, H, T, dv) **float32** (unrounded,
    as ``ops/gdn.py``'s: a norm follows); differentiable in all five. A
    sequence that is no multiple of ``CHUNK`` is padded at its end with
    tokens that write nothing (``beta`` 0, ``g`` 0) and the padding cut off
    again."""
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    pad = -t % CHUNK
    flat = lambda x: jnp.pad(x.reshape(b * h, *x.shape[2:]),
                             ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
    chunks = (t + pad) // CHUNK
    plans.record("kda", KDAPlan("xla", CHUNK, SUB, b * h, chunks, dk, dv,
                                chunks * b * h * dk * dv * 4))
    o = _kda(flat(q), flat(k), flat(v), flat(g.astype(jnp.float32)),
             flat(beta.astype(jnp.float32)))
    return o[:, :t].reshape(b, h, t, dv)


def recurrent_kda(q, k, v, g, beta):
    """The rule token by token, float32 at precision ``highest``: what the
    tests hold the chunked form to. Same shapes as :func:`kda`."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None] * s
        erased = jnp.einsum("bhd,bhde->bhe", k_t, s, precision=_HIGHEST)
        s = s + jnp.einsum("bhd,bhe->bhde", k_t, b_t[..., None] * (v_t - erased),
                           precision=_HIGHEST)
        return s, jnp.einsum("bhd,bhde->bhe", q_t, s, precision=_HIGHEST)

    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 2, 0)
    s0 = jnp.zeros(q.shape[:2] + (q.shape[-1], v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(f32(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 2)
