"""The Mamba-2 recurrence (a state-space layer's scan) in its chunked form.

Per head, with a float32 state ``S`` of (P, N), ``S_0 = 0``, a scalar log
decay ``g_t = dt_t A <= 0`` and a step ``dt_t > 0``:

    S_t = exp(g_t) S_{t-1} + dt_t x_t B_t^T
    o_t = S_t C_t + D x_t

``x_t`` is the head's (P,) input row, ``B_t`` / ``C_t`` the (N,) input and
output projections of the head's *group* (``H / G`` heads share them), ``D`` a
scalar a head. The caller makes ``dt`` (its softplus), the convolution and
the gated norm; nothing here knows a model.

**The chunked form** (state-space duality; chunks of ``C`` = 128 tokens; what
both implementations compute). With ``G_i`` the sum of ``g`` over the chunk's
tokens up to ``i``, ``S`` the state the chunk starts from and ``X~ = dt * X``:

    O      = ((C B^T) * exp(G_i - G_j) * [j <= i]) X~ + exp(G) * (C S^T)
    S_next = exp(G_C) S + (exp(G_C - G) * X~)^T B

``C B^T`` is one (C, C) product a *group*; a head adds its own decay mask and
two products 128 rows deep. The state, the decay and its cumulative sums are
float32; the products take their operands in the inputs' dtype (bf16 in a
training step) and accumulate in float32.

Two implementations of that form, chosen by the caller as flash and dense
attention are (``GPT2Config.attention``):

- ``impl="xla"``: batched products and a ``lax.scan`` over the chunks;
- ``impl="kernel"``: the Pallas kernel ``saturn_ssd_fwd``, one grid step a
  (batch x group, chunk) with the group's heads together, the chunk axis
  sequential with the heads' states in VMEM scratch. A differentiated step
  calls it once a layer, twice under remat (it keeps the chunks' starting
  states for the backward); outside a gradient the same kernel without that
  output runs as ``saturn_ssd_fwd_only``. Off the TPU both run in interpret
  mode (the numerics tests).

**The backward** is one for both (a ``custom_vjp``, as ``ops/gdn.py``'s): the
forward keeps the inputs and the state each chunk started from
((T / C) x P x N float32 a head: 64 MiB a layer at 32 heads of 64 x 128 and
8192 tokens), and a reverse ``lax.scan`` carries ``dS`` from chunk to chunk,
taking each chunk's gradients as the vjp of the chunk's own forward,
recomputed. No backward kernel yet (ROADMAP.md).

``tests/test_ssd.py`` holds both implementations, forward and gradient, to
the recurrence run token by token.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from saturn_tpu.ops import plans
from saturn_tpu.ops.gdn import _by_chunks, _dot, _from_chunks, _mm, _use_interpret

CHUNK = 128
_HIGHEST = jax.lax.Precision.HIGHEST


# ------------------------------------------------------------ one chunk, XLA
def _chunk(s, xdt, b, c, g):
    """One chunk of every (batch x group): ``s`` (n, h, P, N) float32, ``xdt``
    (n, h, C, P), ``b`` / ``c`` (n, C, N), ``g`` (n, h, C) float32 ->
    (``o`` (n, h, C, P) float32, the next state)."""
    dt, size = xdt.dtype, xdt.shape[2]
    big = jnp.cumsum(g, axis=-1)                                   # G
    i, j = jnp.arange(size)[:, None], jnp.arange(size)[None, :]
    decay = jnp.exp(jnp.where(i >= j, big[..., :, None] - big[..., None, :], 0.0))
    cb = _mm(c, b, "nid,njd->nij", dt)                             # once a group
    m = jnp.where(i >= j, cb[:, None] * decay, 0.0)
    o = _mm(m, xdt, "nhij,nhjp->nhip", dt) \
        + jnp.exp(big)[..., None] * _mm(c, s, "nid,nhpd->nhip", dt)
    last = big[..., -1:]
    x_dec = jnp.exp(last - big)[..., None] * xdt.astype(jnp.float32)
    s_next = jnp.exp(last)[..., None] * s + _mm(x_dec, b, "nhip,nid->nhpd", dt)
    return o, s_next


def _chunked(x, c, axis):
    """``_by_chunks`` of an array whose sequence axis is ``axis``."""
    return _by_chunks(jnp.moveaxis(x, axis, 1), c)                 # (T / C, n, C, ...)


def _xs(xdt, b, c, g, size):
    """The scan's operands, chunk by chunk, in ``_chunk``'s layouts."""
    return (jnp.moveaxis(_chunked(xdt, size, 2), 2, 3),            # (nc, n, h, C, P)
            _by_chunks(b, size), _by_chunks(c, size),
            jnp.moveaxis(_chunked(g, size, 2), 2, 3))              # (nc, n, h, C)


def _o_from_chunks(o):
    """(T / C, n, h, C, P) -> (n, h, T, P)."""
    return jnp.moveaxis(_from_chunks(jnp.moveaxis(o, 3, 2)), 1, 2)


def _fwd_xla(xdt, b, c, g, size):
    """-> (o (n, h, T, P) float32, the state each chunk started from
    (T / C, n, h, P, N) float32)."""
    def body(s, xs):
        o, s_next = _chunk(s, *xs)
        return s_next, (o, s)

    n, h, _, p = xdt.shape
    s0 = jnp.zeros((n, h, p, b.shape[-1]), jnp.float32)
    _, (o, starts) = jax.lax.scan(body, s0, _xs(xdt, b, c, g, size))
    return _o_from_chunks(o), starts


# ------------------------------------------------------- one chunk, Pallas
def _fwd_kernel(x_ref, b_ref, c_ref, gcol_ref, grow_ref, o_ref, *rest, size, heads):
    """One chunk of one (batch x group): ``C B^T`` once, then head by head the
    decay mask, the two products of the output and the state's update.
    ``gcol`` / ``grow`` are a head's ``G`` as a column (C, 1) and as a row
    (1, C). ``rest`` is the state scratch, after the output for the chunks'
    starting states where the call keeps them."""
    start_ref, s_scr = rest if len(rest) == 2 else (None, rest[0])

    @pl.when(pl.program_id(1) == 0)
    def _start():
        s_scr[...] = jnp.zeros_like(s_scr)

    bm, cm = b_ref[0, 0], c_ref[0, 0]
    dt = bm.dtype
    i = jax.lax.broadcasted_iota(jnp.int32, (size, size), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (size, size), 1)
    cb = _dot(cm, bm, ((1,), (1,)))                                # (C, C), the group's

    def one_head(h, _):
        x = x_ref[0, h, 0]
        gc, gr = gcol_ref[0, h, 0], grow_ref[0, h, 0]
        s = s_scr[h]
        if start_ref is not None:
            start_ref[0, 0, h] = s
        m = jnp.where(i >= j, cb * jnp.exp(jnp.where(i >= j, gc - gr, 0.0)), 0.0)
        o = _dot(m.astype(dt), x, ((1,), (0,))) \
            + jnp.exp(gc) * _dot(cm, s.astype(dt), ((1,), (1,)))
        o_ref[0, h, 0] = o.astype(o_ref.dtype)
        last = gr[:, size - 1:size]                                # (1, 1)
        x_dec = (jnp.exp(last - gc) * x.astype(jnp.float32)).astype(dt)
        s_scr[h] = jnp.exp(last) * s + _dot(x_dec, bm, ((0,), (0,)))
        return _

    jax.lax.fori_loop(0, heads, one_head, None)


def fwd_vmem_bytes(size: int, heads: int, p: int, n: int, itemsize: int) -> int:
    """What one grid step of ``saturn_ssd_fwd`` holds in VMEM: the pipelined
    blocks twice (x, B, C in the inputs' dtype; o, the heads' two decay
    vectors and the states written out in float32; a block's last dimension
    padded to 128 lanes), the state scratch, and the float32 (C, C) / (C, P)
    temporaries of one head."""
    lane = lambda k: -(-k // 128) * 128
    blocks = (heads * size * lane(p) + 2 * size * lane(n)) * itemsize \
        + heads * (size * lane(p) + size * 128 + 8 * lane(size) + p * lane(n)) * 4
    temps = (4 * size * lane(size) + 3 * size * lane(p) + 2 * p * lane(n)) * 4
    return 2 * blocks + heads * p * lane(n) * 4 + temps


def _fwd_kernel_call(xdt, b, c, g, size, keep_starts=True):
    """Same contract as :func:`_fwd_xla`, by ``saturn_ssd_fwd``: the call a
    differentiated step makes of a layer, which keeps the chunks' starting
    states for the backward. ``keep_starts=False`` is the call outside any
    gradient, ``saturn_ssd_fwd_only``, which writes ``o`` and no state."""
    n, h, t, p = xdt.shape
    d, nc = b.shape[-1], t // size
    big = jnp.cumsum(g.reshape(n, h, nc, size), axis=-1)
    per_head = lambda *tail: pl.BlockSpec((1, h, 1) + tail, lambda a, i: (a, 0, i, 0, 0))
    per_group = pl.BlockSpec((1, 1, size, d), lambda a, i: (a, i, 0, 0))
    o, *starts = pl.pallas_call(
        functools.partial(_fwd_kernel, size=size, heads=h),
        grid=(n, nc),
        in_specs=[per_head(size, p), per_group, per_group,
                  per_head(size, 1), per_head(1, size)],
        out_specs=[per_head(size, p)]
        + [pl.BlockSpec((1, 1, h, p, d), lambda a, i: (a, i, 0, 0, 0))] * keep_starts,
        out_shape=[jax.ShapeDtypeStruct((n, h, nc, size, p), jnp.float32)]
        + [jax.ShapeDtypeStruct((n, nc, h, p, d), jnp.float32)] * keep_starts,
        scratch_shapes=[pltpu.VMEM((h, p, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="saturn_ssd_fwd" if keep_starts else "saturn_ssd_fwd_only",
        interpret=_use_interpret(),
    )(xdt.reshape(n, h, nc, size, p), b.reshape(n, nc, size, d),
      c.reshape(n, nc, size, d), big[..., None], big[..., None, :])
    return o.reshape(n, h, t, p), (jnp.moveaxis(starts[0], 1, 0) if starts else None)


# ------------------------------------------------------------- custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ssd(xdt, b, c, g, size, kernel):
    if kernel:
        return _fwd_kernel_call(xdt, b, c, g, size, keep_starts=False)[0]
    return _fwd_xla(xdt, b, c, g, size)[0]


def _ssd_fwd(xdt, b, c, g, size, kernel):
    o, starts = (_fwd_kernel_call if kernel else _fwd_xla)(xdt, b, c, g, size)
    return o, (xdt, b, c, g, starts)


def _ssd_bwd(size, kernel, res, do):
    del kernel  # one backward for both (module docstring)
    xdt, b, c, g, starts = res

    def body(ds, xs):
        s, do_c, *inputs = xs
        _, vjp = jax.vjp(_chunk, s, *inputs)
        ds_prev, *grads = vjp((do_c, ds))
        return ds_prev, tuple(grads)

    do_c = jnp.moveaxis(_chunked(do, size, 2), 2, 3)
    _, (dx, db, dc, dg) = jax.lax.scan(
        body, jnp.zeros_like(starts[0]), (starts, do_c) + _xs(xdt, b, c, g, size),
        reverse=True)
    return (_o_from_chunks(dx), _from_chunks(db), _from_chunks(dc),
            jnp.moveaxis(_from_chunks(jnp.moveaxis(dg, 3, 2)), 1, 2))


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


# ------------------------------------------------------------------- plan
class SSDPlan(NamedTuple):
    """What one call of :func:`ssd` was traced as (``ssd_plan`` on the
    ``trial_config`` event)."""
    impl: str            # "kernel" | "xla"
    chunk: int
    n: int               # batch x groups: the kernel's parallel grid axis
    chunks: int          # its sequential one
    heads: int           # heads held
    groups: int          # groups held
    heads_published: int
    groups_published: int
    head_dim: int
    state: int
    state_bytes_kept: int       # the chunks' starting states, kept for the backward
    vmem_bytes: Optional[int]   # the kernel's VMEM sum; None for "xla"


def ssd(x, dt, a, b, c, d, *, impl: str = "xla", chunk: int = CHUNK,
        published: Optional[tuple] = None):
    """``x`` (B, T, H, P); ``dt`` (B, T, H) float32, > 0; ``a`` (H,) float32,
    < 0; ``b`` / ``c`` (B, T, G, N), head ``h`` reading group ``h // (H / G)``;
    ``d`` (H,) float32 -> ``o`` (B, T, H, P) **float32**; differentiable in
    all six. (``o`` is handed on unrounded, as ``ops/gdn.py``'s: a norm
    follows it.) A sequence that is no multiple of the chunk is padded at its
    end with tokens that write nothing and decay nothing, and the padding cut
    off again. ``published``: (heads, groups) of the uncut layer, for the
    plan."""
    if impl not in ("xla", "kernel"):
        raise ValueError(f"impl must be 'xla' or 'kernel', got {impl!r}")
    bsz, t, h, p = x.shape
    groups, n = b.shape[2], b.shape[3]
    if h % groups:
        raise ValueError(f"{h} heads do not divide over {groups} groups")
    per = h // groups
    pad = -t % chunk
    f32 = jnp.float32
    dt = dt.astype(f32)
    g = dt * a.astype(f32)                                         # (B, T, H)
    xdt = (x.astype(f32) * dt[..., None]).astype(x.dtype)

    def heads_first(y):     # (B, T, H, ...) -> (B x G, H / G, T + pad, ...)
        y = jnp.moveaxis(y, 1, 2).reshape(bsz * groups, per, t, *y.shape[3:])
        return jnp.pad(y, ((0, 0), (0, 0), (0, pad)) + ((0, 0),) * (y.ndim - 3))

    def groups_first(y):    # (B, T, G, N) -> (B x G, T + pad, N)
        y = jnp.moveaxis(y, 1, 2).reshape(bsz * groups, t, n)
        return jnp.pad(y, ((0, 0), (0, pad), (0, 0)))

    chunks = (t + pad) // chunk
    heads_all, groups_all = published or (h, groups)
    plans.record("ssd", SSDPlan(
        impl, chunk, bsz * groups, chunks, h, groups, heads_all, groups_all, p, n,
        chunks * bsz * h * p * n * 4,
        fwd_vmem_bytes(chunk, per, p, n, x.dtype.itemsize) if impl == "kernel" else None))
    o = _ssd(heads_first(xdt), groups_first(b), groups_first(c), heads_first(g),
             chunk, impl == "kernel")
    o = jnp.moveaxis(o[:, :, :t].reshape(bsz, h, t, p), 1, 2)
    return o + d.astype(f32)[:, None] * x.astype(f32)


def recurrent_ssd(x, dt, a, b, c, d):
    """The recurrence token by token, float32 at precision ``highest``: what
    the tests hold the chunked form to. Same shapes as :func:`ssd`."""
    bsz, t, h, p = x.shape
    per = h // b.shape[2]
    f32 = jnp.float32

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs                       # (B, H, P), (B, H), (B, H, N) x 2
        s = jnp.exp(dt_t * a)[..., None, None] * s + jnp.einsum(
            "bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t, precision=_HIGHEST)
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t, precision=_HIGHEST)

    seq = lambda y: jnp.moveaxis(y.astype(f32), 1, 0)
    wide = lambda y: jnp.repeat(y, per, axis=2)
    s0 = jnp.zeros((bsz, h, p, b.shape[-1]), f32)
    _, o = jax.lax.scan(step, s0, (seq(x), seq(dt), seq(wide(b)), seq(wide(c))))
    return jnp.moveaxis(o, 0, 1) + d.astype(f32)[:, None] * x.astype(f32)
