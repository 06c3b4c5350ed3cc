"""Pallas flash attention for TPU: fused causal attention, fwd + bwd.

The hot op of every model in the zoo. XLA already fuses the dense attention
einsums well, but it materializes the (T, T) score matrix in HBM between the
two matmuls; this kernel keeps score blocks in VMEM with the online-softmax
recurrence (Flash-Attention-2 style), so HBM traffic drops from O(T²) to
O(T·D) and both matmuls feed the MXU back-to-back.

VMEM footprint is O(block · D) per program, independent of T: the key/value
walk is a **grid dimension** (innermost, sequential on TPU), with k/v tiles
pipelined HBM→VMEM by Pallas block specs and the softmax state (m, l, acc)
carried in VMEM scratch across the kv steps — so long-context sequences
never stage a full (T, D) operand on chip.

Shapes: (B, H, T, D) with T % block == 0. The backward pass is the standard
two-kernel split — a dQ kernel gridded over (query block × kv step) and a
dK/dV kernel gridded over (kv block × query step) — recomputing
P = exp(S - lse) from the forward's saved logsumexp.

Used by the model zoo when ``GPT2Config.attention`` resolves to "flash" —
which is the DEFAULT on TPU: on one v5e chip at GPT-J widths (head 256,
seq 2048 x batch 4) the search timed the flash point at 309.5 ms a batch
against 343.8 ms for dense (PERF.md section 5, the GPT-J cell's search), and
dense is the side the memory check refuses first as the sequence grows.
Numerics are validated against the dense reference in interpret mode on CPU
(``tests/test_flash.py``).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _use_interpret() -> bool:
    """Pallas TPU lowering needs a real TPU; interpret everywhere else."""
    return jax.default_backend() != "tpu"


def _block_mask(iq, jk, block_q, block_k):
    """(BQ, BK) causal mask for query block iq vs key block jk."""
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return q_pos >= k_pos


def _window_mask(iq, jk, block_q, block_k, window):
    """(BQ, BK) mask of a sliding layer: key j is read by the queries
    j .. j + window - 1 (the window counts the token itself)."""
    q_pos = iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = jk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return jnp.logical_and(q_pos >= k_pos, q_pos - k_pos < window)


def _window_blocks(window: int, block: int) -> int:
    """Key blocks a query block reaches back over (itself included), and
    query blocks a key block is read by, at equal blocks: the kv (q) axis of
    a window kernel's grid. The other T / block - this many are never
    visited: not fetched, not masked, not multiplied."""
    return -(-(window - 1) // block) + 1


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


# --------------------------------------------------------------------- fwd
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, block_q, block_k, scale, causal, window=None):
    iq, jk = pl.program_id(1), pl.program_id(2)
    n_kv = pl.num_programs(2)
    step = jk

    @pl.when(step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # Blocks fully above the causal diagonal contribute nothing: skip the
    # matmuls (the k/v fetch is pipelined by the grid either way).
    needed = True
    if window is not None:
        # the grid's kv axis walks the blocks iq - n_kv + 1 .. iq only; one
        # before the sequence's start is skipped (its fetch is clamped)
        jk = iq - (n_kv - 1) + step
        needed = jk >= 0
    elif causal:
        needed = jk * block_k <= iq * block_q + block_q - 1

    @pl.when(needed)
    def _accumulate():
        # Matmul inputs stay in the storage dtype (bf16): the MXU computes
        # bf16×bf16→f32 natively via preferred_element_type, while f32×f32
        # needs multiple passes — upcasting before the dot costs ~2x. Scale
        # is applied to the f32 scores, softmax state stays f32.
        q = q_ref[0]                                      # (BQ, D)
        kb = k_ref[0]                                     # (BK, D)
        vb = v_ref[0]
        s = _dot(q, kb, ((1,), (1,))) * scale             # (BQ, BK) f32
        if window is not None:
            s = jnp.where(_window_mask(iq, jk, block_q, block_k, window), s,
                          NEG_INF)
        elif causal:
            s = jnp.where(_block_mask(iq, jk, block_q, block_k), s, NEG_INF)
        m_prev, l_prev = m_scr[:, 0], l_scr[:, 0]
        m_new = jnp.maximum(m_prev, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        m_scr[:, 0] = m_new
        l_scr[:, 0] = corr * l_prev + p.sum(axis=-1)
        acc_scr[:] = corr[:, None] * acc_scr[:] + _dot(
            p.astype(vb.dtype), vb, ((1,), (0,))
        )

    @pl.when(step == n_kv - 1)
    def _finalize():
        l = l_scr[:, 0]
        o_ref[0] = (acc_scr[:] / l[:, None]).astype(o_ref.dtype)
        # lse rides a trailing singleton dim: Mosaic requires the last two
        # block dims be (mult-of-8, mult-of-128) or equal to the array dims,
        # so a 2-D (1, block_q) lse block cannot lower; (1, block_q, 1) can.
        lse_ref[0] = (m_scr[:, 0] + jnp.log(l))[:, None]


def _kv_of(h: int, kv: int):
    """Flat (B*H) q-head program index -> flat (B*KV) k/v row.

    Grouped-query attention: ``rep = h // kv`` consecutive q heads share
    one k/v head, so the k/v BlockSpec index maps a q-head grid step to its
    group's row — the kernels never see repeated k/v and the (B, H, T, D)
    activation expansion never materializes. rep == 1 is the identity."""
    rep = h // kv

    def to_kv(bh):
        return (bh // h) * kv + (bh % h) // rep

    return to_kv


def _kv_walk(window, block_q, block_k, n_kv):
    """(kv steps of the grid, the key block a (query block, step) reads)."""
    if window is None:
        return n_kv, lambda i, j: j
    if block_q != block_k:
        raise ValueError("a window kernel takes equal blocks")
    n_w = min(_window_blocks(window, block_k), n_kv)
    return n_w, lambda i, j: jnp.maximum(i - (n_w - 1) + j, 0)


def _fwd(q, k, v, *, block_q, block_k, scale, causal, h, kv, window=None):
    BH, T, D = q.shape
    kv_of = _kv_of(h, kv)
    n_steps, key_block = _kv_walk(window, block_q, block_k, T // block_k)
    grid = (BH, T // block_q, n_steps)
    kernel_kw = {} if window is None else {"window": window}
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_q=block_q, block_k=block_k, scale=scale,
            causal=causal, **kernel_kw,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, i, j: (kv_of(bh), key_block(i, j), 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, i, j: (kv_of(bh), key_block(i, j), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, T, D), q.dtype),
            jax.ShapeDtypeStruct((BH, T, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max
            pltpu.VMEM((block_q, 1), jnp.float32),   # running denom
            pltpu.VMEM((block_q, D), jnp.float32),   # output accumulator
        ],
        name="saturn_flash_fwd" if window is None else "saturn_swa_fwd",
        interpret=_use_interpret(),
    )(q, k, v)
    return o, lse


# --------------------------------------------------------------------- bwd
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, block_q, block_k, scale, causal, window=None):
    iq, jk = pl.program_id(1), pl.program_id(2)
    n_kv = pl.num_programs(2)
    step = jk

    @pl.when(step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    needed = True
    if window is not None:
        jk = iq - (n_kv - 1) + step     # see the fwd kernel
        needed = jk >= 0
    elif causal:
        needed = jk * block_k <= iq * block_q + block_q - 1

    @pl.when(needed)
    def _accumulate():
        # bf16 matmul inputs, f32 accumulation — see the fwd kernel note.
        q = q_ref[0]
        kb = k_ref[0]
        vb = v_ref[0]
        do = do_ref[0]
        s = _dot(q, kb, ((1,), (1,))) * scale
        if window is not None:
            s = jnp.where(_window_mask(iq, jk, block_q, block_k, window), s,
                          NEG_INF)
        elif causal:
            s = jnp.where(_block_mask(iq, jk, block_q, block_k), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])          # lse block is (block_q, 1)
        dp = _dot(do, vb, ((1,), (1,)))
        ds = p * (dp - delta_ref[0])
        dq_scr[:] = dq_scr[:] + _dot(ds.astype(kb.dtype), kb, ((1,), (0,)))

    @pl.when(step == n_kv - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, block_q, block_k, scale,
                causal, window=None, n_q_blocks=None):
    # Grid (bkv, jk, g, iq): g walks the q heads sharing this k/v head
    # (size 1 without GQA); the (bkv, jk) output block stays resident across
    # the whole inner (g, iq) sweep, so dk/dv accumulate the group sum the
    # transpose of the activation-side repeat would otherwise need.
    jk, g, iq = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    n_g, n_q = pl.num_programs(2), pl.num_programs(3)
    step = iq

    @pl.when(jnp.logical_and(g == 0, step == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    needed = True
    if window is not None:
        # the grid's q axis walks the blocks jk .. jk + n_q - 1 only; one
        # past the sequence's end is skipped (its fetch is clamped)
        iq = jk + step
        needed = iq < n_q_blocks
    elif causal:
        needed = iq * block_q + block_q - 1 >= jk * block_k

    @pl.when(needed)
    def _accumulate():
        # bf16 matmul inputs, f32 accumulation — see the fwd kernel note.
        kb = k_ref[0]
        vb = v_ref[0]
        qb = q_ref[0]
        dob = do_ref[0]
        s = _dot(qb, kb, ((1,), (1,))) * scale
        if window is not None:
            s = jnp.where(_window_mask(iq, jk, block_q, block_k, window), s,
                          NEG_INF)
        elif causal:
            s = jnp.where(_block_mask(iq, jk, block_q, block_k), s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])                         # (BQ, BK)
        dv_scr[:] = dv_scr[:] + _dot(p.astype(dob.dtype), dob, ((0,), (0,)))
        dp = _dot(dob, vb, ((1,), (1,)))
        ds = (p * (dp - delta_ref[0])).astype(qb.dtype)
        # ds·q is unscaled; the scale factor lands in the finalize below.
        dk_scr[:] = dk_scr[:] + _dot(ds, qb, ((0,), (0,)))

    @pl.when(jnp.logical_and(g == n_g - 1, step == n_q - 1))
    def _finalize():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(block_q, block_k, scale, causal, h, kv, res, do, window=None):
    q, k, v, o, lse = res
    BH, T, D = q.shape
    BKV = k.shape[0]
    rep = h // kv
    kv_of = _kv_of(h, kv)
    n_steps, key_block = _kv_walk(window, block_q, block_k, T // block_k)
    n_q = T // block_q
    if window is None:
        kernel_kw, dkv_kw, q_block = {}, {}, lambda j, i: i
    else:
        kernel_kw = {"window": window}
        dkv_kw = {"window": window, "n_q_blocks": n_q}
        q_block = lambda j, i: jnp.minimum(j + i, n_q - 1)   # noqa: E731
    # (BH, T, 1) like lse — see the fwd finalize note on Mosaic block rules.
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, block_q=block_q, block_k=block_k, scale=scale,
            causal=causal, **kernel_kw,
        ),
        grid=(BH, T // block_q, n_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, i, j: (kv_of(bh), key_block(i, j), 0)),
            pl.BlockSpec((1, block_k, D),
                         lambda bh, i, j: (kv_of(bh), key_block(i, j), 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda bh, i, j: (bh, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        name="saturn_flash_dq" if window is None else "saturn_swa_dq",
        interpret=_use_interpret(),
    )(q, k, v, do, lse, delta)

    def qh(bkv, g):
        # flat (B*KV) k/v row + group member -> flat (B*H) q-head row
        return (bkv // kv) * h + (bkv % kv) * rep + g

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, block_q=block_q, block_k=block_k, scale=scale,
            causal=causal, **dkv_kw,
        ),
        grid=(BKV, T // block_k, rep, n_q if window is None else n_steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D),
                         lambda bkv, j, g, i: (qh(bkv, g), q_block(j, i), 0)),
            pl.BlockSpec((1, block_k, D), lambda bkv, j, g, i: (bkv, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda bkv, j, g, i: (bkv, j, 0)),
            pl.BlockSpec((1, block_q, D),
                         lambda bkv, j, g, i: (qh(bkv, g), q_block(j, i), 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda bkv, j, g, i: (qh(bkv, g), q_block(j, i), 0)),
            pl.BlockSpec((1, block_q, 1),
                         lambda bkv, j, g, i: (qh(bkv, g), q_block(j, i), 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bkv, j, g, i: (bkv, j, 0)),
            pl.BlockSpec((1, block_k, D), lambda bkv, j, g, i: (bkv, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, T, D), k.dtype),
            jax.ShapeDtypeStruct((BKV, T, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        name="saturn_flash_dkv" if window is None else "saturn_swa_dkv",
        interpret=_use_interpret(),
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------- public
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bh(q, k, v, block_q, block_k, causal, h, kv):
    scale = 1.0 / math.sqrt(q.shape[-1])
    o, _ = _fwd(q, k, v, block_q=block_q, block_k=block_k, scale=scale,
                causal=causal, h=h, kv=kv)
    return o


def _flash_bh_fwd(q, k, v, block_q, block_k, causal, h, kv):
    scale = 1.0 / math.sqrt(q.shape[-1])
    o, lse = _fwd(q, k, v, block_q=block_q, block_k=block_k, scale=scale,
                  causal=causal, h=h, kv=kv)
    return o, (q, k, v, o, lse)


def _flash_bh_bwd(block_q, block_k, causal, h, kv, res, do):
    scale = 1.0 / math.sqrt(res[0].shape[-1])
    return _bwd(block_q, block_k, scale, causal, h, kv, res, do)


_flash_bh.defvjp(_flash_bh_fwd, _flash_bh_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _swa_bh(q, k, v, block, window, h, kv):
    """Sliding-window attention on flat heads: ``_flash_bh``'s kernels with
    the window's mask, their grids walking only the blocks the window
    reaches, under names of their own (``saturn_swa_*``: a reader that counts
    a ``saturn_flash_*`` call as full causal attention must not meet one)."""
    return _swa_bh_fwd(q, k, v, block, window, h, kv)[0]


def _swa_bh_fwd(q, k, v, block, window, h, kv):
    scale = 1.0 / math.sqrt(q.shape[-1])
    o, lse = _fwd(q, k, v, block_q=block, block_k=block, scale=scale,
                  causal=True, h=h, kv=kv, window=window)
    return o, (q, k, v, o, lse)


def _swa_bh_bwd(block, window, h, kv, res, do):
    scale = 1.0 / math.sqrt(res[0].shape[-1])
    return _bwd(block, block, scale, True, h, kv, res, do, window=window)


_swa_bh.defvjp(_swa_bh_fwd, _swa_bh_bwd)


_WINDOW_PLANS: list = []


@contextlib.contextmanager
def traced_window_plans():
    """Collects ``window_plan`` of every window call traced inside (as
    ``ops/ce.py``'s ``traced_plans``)."""
    global _WINDOW_PLANS
    before, _WINDOW_PLANS = _WINDOW_PLANS, []
    try:
        yield _WINDOW_PLANS
    finally:
        _WINDOW_PLANS = before


def window_plan(T: int, window: int, block: Optional[int] = None) -> dict:
    """The window kernels' grid at sequence ``T``: the (equal) block, the
    key blocks a query block visits and how many of a causal walk's it
    skips a call (mean over query blocks)."""
    b = block or _window_block(T)
    n = T // b
    n_w = min(_window_blocks(window, b), n)
    return {"window": window, "block": b, "blocks_visited": n_w,
            "blocks_skipped_per_call": n * (n + 1) // 2 - sum(
                min(i + 1, n_w) for i in range(n))}


def _window_block(T: int) -> int:
    """256 where it divides T: at a window of 512 a query block then visits
    3 key blocks (768 keys for the 512 it needs); 512 would visit 2 (1024),
    128 five (640) in products a quarter the size."""
    for b in (256, 128):
        if T % b == 0:
            return b
    return min(128, T)


def _default_block(T: int) -> int:
    """Largest power-of-two block ≤ 512 dividing T: bigger blocks mean fewer
    grid programs and larger MXU matmuls; VMEM stays comfortable (the f32
    score block at 512² is 1 MiB)."""
    for b in (512, 256, 128):
        if T % b == 0:
            return b
    return min(128, T)


def flash_supported(cfg=None) -> bool:
    """Can the Pallas kernel lower (not interpret) for this model config?

    Real lowering needs the TPU backend; interpret mode exists only for
    numerics tests. With a config, also checks the kernel's shape contract
    (seq divisible by the default block) and that attention is single-program
    (sequence-parallel configs have their own kernels). Used by the executors'
    autotune grids so the trial runner profiles flash-vs-dense per task and
    the solver selects from measurements (VERDICT r1 items 2-3).
    """
    import jax

    if jax.default_backend() != "tpu":
        return False
    if cfg is not None:
        T = getattr(cfg, "seq_len", None)
        if T is not None and T % min(128, T) != 0:
            return False
        if getattr(cfg, "seq_axis", None) is not None:
            return False
    return True


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused causal attention over (B, H, T, D); differentiable.

    ``window`` (causal only): query i reads keys i - window + 1 .. i, through
    the ``saturn_swa_*`` kernels, whose grids visit the blocks the window
    reaches and no other (``window_plan``); one block size, ``block_q``.

    Grouped-query attention is native: ``k``/``v`` may carry fewer heads
    (B, KV, T, D) with KV dividing H — the kernels index each q head's
    group row directly, so the (B, H, T, D) k/v expansion (and its HBM at
    long context) never exists, and dk/dv come back at (B, KV, T, D) with
    the group sum done in-kernel.

    T must divide by the block sizes (default: the largest of 512/256/128
    dividing T, else min(128, T) — see ``_default_block``) or this raises —
    the model config validates the constraint up front
    (``GPT2Config.__post_init__``); this op stays strict.
    """
    B, H, T, D = q.shape
    KV = k.shape[1]
    if v.shape[1] != KV or KV < 1 or H % KV != 0:
        raise ValueError(
            f"k/v heads ({k.shape[1]}, {v.shape[1]}) must match and divide "
            f"q heads ({H})"
        )
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * KV, T, D)
    vf = v.reshape(B * KV, T, D)
    if window is not None:
        if not causal or window < 1 or (block_k or block_q) != block_q:
            raise ValueError("a window is causal, >= 1, with one block size")
        b = block_q or _window_block(T)
        if T % b:
            raise ValueError(f"seq len {T} not divisible by the block ({b})")
        _WINDOW_PLANS.append(window_plan(T, int(window), b))
        return _swa_bh(qf, kf, vf, b, int(window), H, KV).reshape(B, H, T, D)
    bq = block_q or _default_block(T)
    bk = block_k or _default_block(T)
    if T % bq or T % bk:
        raise ValueError(f"seq len {T} not divisible by blocks ({bq}, {bk})")
    o = _flash_bh(qf, kf, vf, bq, bk, causal, H, KV)
    return o.reshape(B, H, T, D)
