"""The gated delta rule (linear attention with a decayed, key-erasing state).

Per head, with a float32 state ``S`` of (d_k, d_v), ``S_0 = 0``:

    S_t = alpha_t (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

(the row convention; ``S^T`` is the (d_v, d_k) state of the usual statement
``S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``,
``o_t = S_t q_t``). ``alpha_t = exp(g_t)`` in (0, 1] is the decay and
``beta_t`` the writing strength; ``beta_t > 1`` (allowed: the caller's
``2 sigmoid``) gives ``I - beta k k^T`` a negative eigenvalue along ``k``.
The caller normalises ``q`` and ``k``; nothing here knows a model.

**The chunked form** (chunks of ``C`` = 64 tokens; what both implementations
compute). With ``G_i`` the sum of ``g`` over the chunk's tokens up to ``i``
and ``S`` the state the chunk starts from, writing the rule as
``S_t = alpha_t S_{t-1} + k_t u_t^T`` gives the pseudo-values

    (I + A) U = diag(beta) (V - diag(exp G) K S),
    A[i, j] = beta_i exp(G_i - G_j) (k_i . k_j) for j < i, else 0

so ``U = T (beta V) - (T (beta exp(G) K)) S`` with ``T = (I + A)^-1`` (the
WY / UT transform: unit lower triangular), and

    O      = (exp(G) Q) S + ((Q K^T) * exp(G_i - G_j) * [j <= i]) U
    S_next = exp(G_C) S + (exp(G_C - G) K)^T U.

``A`` is strictly lower triangular, so ``A^C = 0`` and
``T = (I - A)(I + A^2)(I + A^4) ... (I + A^(C/2))``: log2(C) small products
in float32, no substitution loop. The state, the decay and ``T`` are float32;
the other products take their operands in the inputs' dtype (bf16 in a
training step) and accumulate in float32.

Two implementations of that form, chosen by the caller as flash and dense
attention are (``GPT2Config.attention``):

- ``impl="xla"``: a ``lax.scan`` over the chunks in plain ``jax.numpy``;
- ``impl="kernel"``: the Pallas kernel ``saturn_gdn_fwd``, one grid step a
  (batch x head, chunk), the chunk axis sequential with the state in VMEM
  scratch. A differentiated step calls it once a layer, twice under remat
  (it keeps the chunks' starting states for the backward; a rematerialised
  layer's first forward is the vjp's forward rule too); outside a gradient
  the same kernel without that output runs as ``saturn_gdn_fwd_only``. Off
  the TPU both run in interpret mode (the numerics tests).

**The backward** is one for both (a ``custom_vjp``): the forward keeps the
inputs and the state each chunk started from ((T / C) x d_k x d_v float32 a
head: 141 MB a layer at 15 heads of 96 x 192 and 8192 tokens), and a reverse
``lax.scan`` carries ``dS`` from chunk to chunk, taking each chunk's
gradients as the vjp of the chunk's own forward, recomputed. No backward
kernel yet (``saturn_gdn_bwd``: ROADMAP.md).

``tests/test_gdn.py`` holds both implementations, forward and gradient, to
the rule run token by token.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from saturn_tpu.ops import plans

CHUNK = 64
_HIGHEST = jax.lax.Precision.HIGHEST


def _use_interpret() -> bool:
    """Pallas TPU lowering needs a real TPU; interpret everywhere else."""
    return jax.default_backend() != "tpu"


# ------------------------------------------------------------ one chunk, XLA
def _mm(a, b, spec, dtype):
    """A product of the chunk with operands in ``dtype`` (the inputs'),
    accumulated in float32."""
    return jnp.einsum(spec, a.astype(dtype), b.astype(dtype),
                      preferred_element_type=jnp.float32,
                      precision=_HIGHEST if dtype == jnp.float32 else None)


def _mm32(a, b, spec):
    return _mm(a, b, spec, jnp.float32)


def _unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a`` (..., C, C) by the
    product of the module docstring."""
    c = a.shape[-1]
    eye = jnp.eye(c, dtype=jnp.float32)
    p = -a
    t = eye + p
    for _ in range(max(c - 1, 1).bit_length() - 1):
        p = _mm32(p, p, "...ij,...jk->...ik")
        t = t + _mm32(t, p, "...ij,...jk->...ik")
    return t


def _chunk(s, q, k, v, g, beta):
    """One chunk of every (batch x head): ``s`` (N, dk, dv) float32, ``q`` /
    ``k`` (N, C, dk), ``v`` (N, C, dv), ``g`` / ``beta`` (N, C) float32 ->
    (``o`` (N, C, dv) float32, the next state)."""
    dt, c = q.dtype, q.shape[1]
    big = jnp.cumsum(g, axis=-1)                                   # G
    i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(i >= j, big[:, :, None] - big[:, None, :], 0.0))
    a = jnp.where(i > j, beta[:, :, None] * _mm(k, k, "nid,njd->nij", dt) * decay, 0.0)
    t = _unit_lower_inverse(a)
    eg = jnp.exp(big)[..., None]
    w = _mm32(t, (beta[..., None] * eg) * k.astype(jnp.float32), "nij,njd->nid")
    u = _mm32(t, beta[..., None] * v.astype(jnp.float32), "nij,njd->nid")
    u = u - _mm(w, s, "nid,nde->nie", dt)
    attn = jnp.where(i >= j, _mm(q, k, "nid,njd->nij", dt) * decay, 0.0)
    o = _mm(eg * q.astype(jnp.float32), s, "nid,nde->nie", dt) \
        + _mm(attn, u, "nij,nje->nie", dt)
    last = big[:, -1:]
    k_dec = jnp.exp(last - big)[..., None] * k.astype(jnp.float32)
    s_next = jnp.exp(last)[..., None] * s + _mm(k_dec, u, "nid,nie->nde", dt)
    return o, s_next


def _by_chunks(x, c):
    """(N, T, ...) -> (T / C, N, C, ...)."""
    n, t = x.shape[:2]
    return jnp.moveaxis(x.reshape(n, t // c, c, *x.shape[2:]), 1, 0)


def _from_chunks(x):
    """(T / C, N, C, ...) -> (N, T, ...)."""
    x = jnp.moveaxis(x, 0, 1)
    return x.reshape(x.shape[0], x.shape[1] * x.shape[2], *x.shape[3:])


def _fwd_xla(q, k, v, g, beta, c):
    """-> (o (N, T, dv) float32, the state each chunk started from
    (T / C, N, dk, dv) float32)."""
    def body(s, xs):
        o, s_next = _chunk(s, *xs)
        return s_next, (o, s)

    s0 = jnp.zeros((q.shape[0], q.shape[-1], v.shape[-1]), jnp.float32)
    _, (o, starts) = jax.lax.scan(body, s0, tuple(_by_chunks(x, c) for x in (q, k, v, g, beta)))
    return _from_chunks(o), starts


# ------------------------------------------------------- one chunk, Pallas
def _dot(a, b, dims):
    """A product inside the kernel, accumulated in float32. Float32 operands
    (the triangular transform, its application) are multiplied at full
    precision: the compiler's default for them is one bf16 pass, which read
    1.2 % off the token-by-token rule on the chip where the plain twin read
    0.3 % (my chip run, PR 33)."""
    return jax.lax.dot_general(
        a, b, (dims, ((), ())), preferred_element_type=jnp.float32,
        precision=_HIGHEST if a.dtype == jnp.float32 else None)


def _fwd_kernel(q_ref, k_ref, v_ref, gcol_ref, grow_ref, beta_ref,
                o_ref, *rest, c):
    """One chunk of one (batch x head); see the module docstring. ``gcol`` /
    ``grow`` are ``G`` as a column (C, 1) and as a row (1, C) (both are handed
    in: a kernel does not transpose a vector), ``beta`` a column. ``rest`` is
    the state scratch, after the output for the chunks' starting states
    where the call keeps them."""
    start_ref, s_scr = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(1) == 0)
    def _start():
        s_scr[:] = jnp.zeros_like(s_scr)

    q, k, v = q_ref[0, 0], k_ref[0, 0], v_ref[0, 0]
    dt = q.dtype
    gc, gr, beta = gcol_ref[0, 0], grow_ref[0, 0], beta_ref[0, 0]
    s = s_scr[:]
    if start_ref is not None:
        start_ref[0, 0] = s
    i = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    decay = jnp.exp(jnp.where(i >= j, gc - gr, 0.0))
    a = jnp.where(i > j, beta * _dot(k, k, ((1,), (1,))) * decay, 0.0)
    p = -a
    t = jnp.where(i == j, 1.0, 0.0) + p
    for _ in range(max(c - 1, 1).bit_length() - 1):
        p = _dot(p, p, ((1,), (0,)))
        t = t + _dot(t, p, ((1,), (0,)))
    eg = jnp.exp(gc)
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    w = _dot(t, (beta * eg) * kf, ((1,), (0,)))
    u = _dot(t, beta * vf, ((1,), (0,)))
    s_dt = s.astype(dt)
    u = u - _dot(w.astype(dt), s_dt, ((1,), (0,)))
    attn = jnp.where(i >= j, _dot(q, k, ((1,), (1,))) * decay, 0.0)
    o = _dot((eg * q.astype(jnp.float32)).astype(dt), s_dt, ((1,), (0,))) \
        + _dot(attn.astype(dt), u.astype(dt), ((1,), (0,)))
    o_ref[0, 0] = o.astype(o_ref.dtype)
    last = gr[:, c - 1:c]                                        # (1, 1)
    k_dec = (jnp.exp(last - gc) * kf).astype(dt)
    s_scr[:] = jnp.exp(last) * s + _dot(k_dec, u.astype(dt), ((0,), (0,)))


def fwd_vmem_bytes(c: int, dk: int, dv: int, itemsize: int) -> int:
    """What one grid step of ``saturn_gdn_fwd`` holds in VMEM: the pipelined
    blocks twice (q, k, v in the inputs' dtype; o, the three gate vectors and
    the state written out in float32, a (C, 1) column padded to 128 lanes),
    the state scratch, and the float32 (C, C) / (C, d) temporaries."""
    lane = lambda n: -(-n // 128) * 128
    blocks = (2 * c * lane(dk) + c * lane(dv)) * itemsize \
        + (c * lane(dv) + 2 * c * 128 + 8 * lane(c)) * 4 + dk * lane(dv) * 4
    temps = (6 * c * lane(c) + 3 * c * lane(dk) + 4 * c * lane(dv)) * 4
    return 2 * blocks + dk * lane(dv) * 4 + temps


def _fwd_kernel_call(q, k, v, g, beta, c, keep_starts=True):
    """Same contract as :func:`_fwd_xla`, by ``saturn_gdn_fwd``: the call a
    differentiated step makes of a layer, which keeps the chunks' starting
    states for the backward. ``keep_starts=False`` is the call outside any
    gradient (a forward alone), ``saturn_gdn_fwd_only``, which writes ``o``
    and no state."""
    n, t, dk = q.shape
    dv, nc = v.shape[-1], t // c
    big = jnp.cumsum(g.reshape(n, nc, c), axis=-1)
    chunked = lambda x: x.reshape(n, nc, c, x.shape[-1])
    block = lambda *tail: pl.BlockSpec((1, 1) + tail, lambda b, i: (b, i, 0, 0))
    o, *starts = pl.pallas_call(
        functools.partial(_fwd_kernel, c=c),
        grid=(n, nc),
        in_specs=[block(c, dk), block(c, dk), block(c, dv),
                  block(c, 1), block(1, c), block(c, 1)],
        out_specs=[block(c, dv)] + [block(dk, dv)] * keep_starts,
        out_shape=[jax.ShapeDtypeStruct((n, nc, c, dv), jnp.float32)]
        + [jax.ShapeDtypeStruct((n, nc, dk, dv), jnp.float32)] * keep_starts,
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="saturn_gdn_fwd" if keep_starts else "saturn_gdn_fwd_only",
        interpret=_use_interpret(),
    )(chunked(q), chunked(k), chunked(v), big[..., None], big[:, :, None, :],
      beta.reshape(n, nc, c, 1))
    return o.reshape(n, t, dv), (jnp.moveaxis(starts[0], 1, 0) if starts else None)


# ------------------------------------------------------------- custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _gdn(q, k, v, g, beta, c, kernel):
    if kernel:
        return _fwd_kernel_call(q, k, v, g, beta, c, keep_starts=False)[0]
    return _fwd_xla(q, k, v, g, beta, c)[0]


def _gdn_fwd(q, k, v, g, beta, c, kernel):
    o, starts = (_fwd_kernel_call if kernel else _fwd_xla)(q, k, v, g, beta, c)
    return o, (q, k, v, g, beta, starts)


def _gdn_bwd(c, kernel, res, do):
    del kernel  # one backward for both (module docstring)
    q, k, v, g, beta, starts = res

    def body(ds, xs):
        s, do_c, *inputs = xs
        _, vjp = jax.vjp(_chunk, s, *inputs)
        ds_prev, *grads = vjp((do_c, ds))
        return ds_prev, tuple(grads)

    xs = (starts, _by_chunks(do, c)) + tuple(_by_chunks(x, c) for x in (q, k, v, g, beta))
    _, grads = jax.lax.scan(body, jnp.zeros_like(starts[0]), xs, reverse=True)
    return tuple(_from_chunks(x) for x in grads)


_gdn.defvjp(_gdn_fwd, _gdn_bwd)


# ------------------------------------------------------------------- plan
class GDNPlan(NamedTuple):
    """What one call of :func:`gated_delta_rule` was traced as."""
    impl: str            # "kernel" | "xla"
    chunk: int
    n: int               # batch x heads: the kernel's parallel grid axis
    chunks: int          # its sequential one
    dk: int
    dv: int
    vmem_bytes: Optional[int]   # the kernel's VMEM sum; None for "xla"


def gated_delta_rule(q, k, v, g, beta, *, impl: str = "xla", chunk: int = CHUNK):
    """``q`` / ``k`` (B, H, T, dk), ``v`` (B, H, T, dv), ``g`` (log decay,
    <= 0) / ``beta`` (B, H, T) float32 -> ``o`` (B, H, T, dv) **float32**;
    differentiable in all five. (``o`` is handed on unrounded: what follows
    it in a layer is a norm, whose backward hands back a ``do`` at right
    angles to the ``o`` it saw; a decay gate's gradient is what is left of
    ``<do, o>``-sized terms after that cancels, and an ``o`` rounded to bf16
    in between read 12 % off in ``lin_a``'s gradient on the chip, PR 33.) A sequence that is no multiple of the
    chunk is padded at its end with tokens that write nothing (``beta`` 0,
    ``g`` 0) and the padding cut off again."""
    if impl not in ("xla", "kernel"):
        raise ValueError(f"impl must be 'xla' or 'kernel', got {impl!r}")
    b, h, t, dk = q.shape
    dv = v.shape[-1]
    pad = -t % chunk
    flat = lambda x: jnp.pad(x.reshape(b * h, *x.shape[2:]),
                             ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 3))
    plans.record("gdn", GDNPlan(
        impl, chunk, b * h, (t + pad) // chunk, dk, dv,
        fwd_vmem_bytes(chunk, dk, dv, q.dtype.itemsize) if impl == "kernel" else None))
    o = _gdn(flat(q), flat(k), flat(v), flat(g.astype(jnp.float32)),
             flat(beta.astype(jnp.float32)), chunk, impl == "kernel")
    return o[:, :t].reshape(b, h, t, dv)


def recurrent_gated_delta_rule(q, k, v, g, beta):
    """The rule token by token, float32 at precision ``highest``: what the
    tests hold the chunked form to. Same shapes as
    :func:`gated_delta_rule`."""
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None, None] * s
        erased = jnp.einsum("bhd,bhde->bhe", k_t, s, precision=_HIGHEST)
        s = s + jnp.einsum("bhd,bhe->bhde", k_t, b_t[..., None] * (v_t - erased),
                           precision=_HIGHEST)
        return s, jnp.einsum("bhd,bhde->bhe", q_t, s, precision=_HIGHEST)

    f32 = lambda x: jnp.moveaxis(x.astype(jnp.float32), 2, 0)
    s0 = jnp.zeros(q.shape[:2] + (q.shape[-1], v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(step, s0, tuple(f32(x) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 2)
