"""Pallas flash attention for TPU: fused causal attention, fwd + bwd.

The hot op of every model in the zoo. XLA already fuses the dense attention
einsums well, but it materializes the (T, T) score matrix in HBM between the
two matmuls; this kernel keeps score blocks in VMEM with the online-softmax
recurrence (Flash-Attention-2 style), so HBM traffic drops from O(T²) to
O(T·D) and both matmuls feed the MXU back-to-back.

Shapes: (B, H, T, D) with T % block == 0. Three ``pallas_call``s an
attention call: the forward, and the standard two-kernel backward — a dQ
kernel gridded over query blocks and a dK/dV kernel gridded over key blocks —
recomputing P = exp(S - lse) from the forward's saved logsumexp. Their names
are ``saturn_flash_fwd`` / ``_dq`` / ``_dkv`` (``saturn_swa_*`` under a
window): the benchmark's roofline reader credits every call of the first
three names with a whole causal attention.

What a kernel does follows from what the call can see: T, the head dim, the
window (PR 41; ``flash_plan`` and ``window_plan`` have the blocks and why,
PERF.md section 6 the chip's readings):

- **The walk over the other sequence is a loop in the kernel.** A grid step
  holds one block of the side that stays (queries for fwd and dq, keys for
  dkv) and a *chunk* of the walked side (``_chunk``: all of T at every
  cell's shapes, a fraction of it in whole blocks beyond 8 MiB of VMEM),
  fetched once a row block and not once a score block; one
  ``lax.fori_loop`` walks the chunk's blocks (``_for_blocks``). The state
  (m, l, acc; dq; dk, dv) lives in VMEM scratch across the loop and the
  chunks. Under a window (``window_plan``, PR 50) fwd and dq run this same
  walk over the span of the chunk the window reaches; dkv, whose walked
  operands are another head's at every grid step, does so from a window of
  four walked blocks on (SmallThinker's 4096 keys) and under that (Laguna's
  512) keeps a chunk of one block, the reached blocks a grid axis: what a
  step fetches is then what the window reaches.
- **Which blocks** (``_reach``): a block wholly above the diagonal (or
  outside the window) is in no loop; every other runs the one loop body,
  mask and all. The mask is one iota pair that does not depend on the block
  and one scalar that does (``_masked``); a second body without it for the
  blocks wholly under the diagonal read no faster on the chip and cost the
  host its trace and lowering (PERF.md section 6, PR 41).
- **The row statistics are vectors along lanes.** m and l (and lse, delta in
  the dq kernel) live replicated over a 128-lane row, so updating and
  applying them are plain vector operations; lse and delta cross HBM as
  lane-dense (BH, 1, T) rows. The dkv kernel computes its scores transposed
  (keys down the rows): there those rows broadcast over sublanes, and
  dV = P^T dO and dK = dS^T Q need no transposed operand. Under a head dim
  of 128 the forward does the same (``_keys_down``): its statistics are
  then a few vregs and its accumulator (D, block_q), where a (block_q, 64)
  float32 tile half-fills every vreg it touches.
- **The softmax scale** multiplies a (block, D) operand where that is exact
  (a power of two: head dims 64 and 256) and the scores where it is not
  (128).

The launchers sit behind ``jit``'s tracing cache (``plans._traced_once``): the
layer traced again under remat, or the next grid point of a search, binds
the ``pallas_call`` traced the first time and traces no kernel body again.

Used by the model zoo when ``GPT2Config.attention`` resolves to "flash" —
which is the DEFAULT on TPU: on one v5e chip at GPT-J widths (head 256,
seq 2048 x batch 4) the search timed the flash point at 309.5 ms a batch
against 343.8 ms for dense (PERF.md section 5, the GPT-J cell's search), and
dense is the side the memory check refuses first as the sequence grows.
Numerics are validated against the dense reference in interpret mode on CPU
(``tests/test_flash.py``).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from saturn_tpu.ops import plans

NEG_INF = -1e30
_LANES = 128


def _use_interpret() -> bool:
    """Pallas TPU lowering needs a real TPU; interpret everywhere else."""
    return jax.default_backend() != "tpu"


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32)


def _lanes(x, n: int):
    """A row statistic kept replicated over a 128-lane row, at ``n`` lanes
    (a score block's or the accumulator's width): whole vregs side by side,
    no broadcast out of lane 0."""
    if n <= _LANES:
        return x[:, :n]
    if n % _LANES == 0:
        return jnp.tile(x, (1, n // _LANES))
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _column(row):
    """A (1, n) row of per-query numbers as a lane-replicated (n, 128)
    column: one transpose a query block, not a relayout a step."""
    return jnp.broadcast_to(row, (_LANES, row.shape[1])).T


def _folds(scale: float) -> bool:
    """A power of two multiplies a bf16 operand exactly (1/sqrt(64),
    1/sqrt(256)); any other scale (1/sqrt(128)) stays on the f32 scores."""
    return math.frexp(scale)[0] == 0.5


def _scores(a, b, scale):
    """``a . b^T * scale`` in float32: the scale on ``a`` (block x D) where
    that is exact, on the (block x block) product where it is not."""
    if _folds(scale):
        return _dot(a * scale, b, ((1,), (1,)))
    return _dot(a, b, ((1,), (1,))) * scale


def _masked(s, q_lo, k_lo, q_axis: int, window):
    """``s`` with the causal (and the window's) mask of the block whose
    first query is ``q_lo`` and first key ``k_lo``; queries lie along
    ``q_axis`` of ``s``."""
    # q_pos >= k_pos  <=>  (row - column) >= k_lo - q_lo: one iota pair
    # that does not depend on the block, one scalar that does
    ahead = (jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
             - jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis))
    keep = ahead >= k_lo - q_lo
    if window is not None:
        keep = jnp.logical_and(keep, ahead < window + k_lo - q_lo)
    return jnp.where(keep, s, NEG_INF)


def _div(a, b: int):
    """``a // b`` of a count that is not negative: one equation in a kernel
    (``//`` on a traced integer is floor division's ten)."""
    return a // b if isinstance(a, int) else jax.lax.div(a, jnp.int32(b))


def _most(a, b):
    return max(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.maximum(a, b)


def _least(a, b):
    return min(a, b) if isinstance(a, int) and isinstance(b, int) \
        else jnp.minimum(a, b)


def _reach(r, b_row, b_col, n_col, causal, window, rows_are_queries):
    """``(n_lo, n_hi)``: of the ``n_col`` column blocks beside row block ``r``
    of the score matrix, the mask leaves something of [n_lo, n_hi) and
    nothing of the rest. Rows are query blocks and columns key blocks (fwd,
    dq), or the other way round (dkv). A bound the arguments fix is a Python
    int, and both are where ``r`` is one."""
    if not causal:
        return 0, n_col
    lo = r * b_row
    hi = lo + b_row - 1
    if rows_are_queries:
        n_lo, n_hi = 0, _div(hi, b_col) + 1
        if window is not None:      # key > query - window
            n_lo = _div(_most(lo - window + 1, 0), b_col)
    else:
        n_lo, n_hi = _div(lo, b_col), n_col
        if window is not None:      # query < key + window
            n_hi = _least(_div(hi + window - 1, b_col) + 1, n_col)
    return n_lo, n_hi


def _when(*conds):
    """``pl.when`` of the conditions together; a plain call where each is
    known to hold as the kernel is traced (``True``: an axis of one step), so
    that no ``cond`` is traced or lowered for it."""
    live = [c for c in conds if c is not True]
    if not live:
        return lambda f: f()
    return pl.when(functools.reduce(jnp.logical_and, live))


def _for_blocks(body, reach, first, per_chunk):
    """``body(c)`` for each column block ``c`` that ``reach`` (``_reach``)
    needs of the chunk that starts at block ``first``: one loop; a chunk the
    mask leaves nothing of runs it no time. ``first`` None: the one chunk
    holds every block."""
    lo, hi = reach
    if first is not None:
        lo, hi = jnp.maximum(lo, first), jnp.minimum(hi, first + per_chunk)

    def step(c, carry):
        body(c)
        return carry

    jax.lax.fori_loop(lo, hi, step, 0)


def _chunk_walk(b_row, b_col, chunk, T, causal, window, rows_are_queries):
    """The grid's innermost axis: (its steps, (row block, step) -> the chunk
    (of ``chunk // b_col`` column blocks) fetched). A row block's steps start
    at the first chunk it needs (``_first``); a step past its last names
    that last chunk again, so nothing is fetched for it, and the kernel's
    loops find nothing to do there. The steps are the most chunks any row
    block needs: all of them under the diagonal alone, the few a window
    reaches under a window."""
    per_chunk, n_col = chunk // b_col, T // b_col

    def chunks(r):
        n_lo, n_hi = _reach(r, b_row, b_col, n_col, causal, window,
                            rows_are_queries)
        return _div(n_lo, per_chunk), _div(n_hi - 1, per_chunk)

    steps = T // chunk
    if window is not None:
        steps = max(last - first + 1
                    for first, last in map(chunks, range(T // b_row)))
    if T == chunk:      # every cell's case: an index map with nothing in it
        return 1, lambda r, step: 0

    def fetched(r, step):
        first, last = chunks(r)
        return jnp.minimum(first + step, last)

    return steps, fetched


def _first(reach, step, per_chunk, n_col):
    """The first column block of the chunk a kernel works on at ``step``;
    None where the one chunk holds every block."""
    if per_chunk == n_col:
        return None
    return (_div(reach[0], per_chunk) + step) * per_chunk


def _name(part: str, window, D: int, Dv: int) -> str:
    """A kernel's name in the device trace: ``saturn_flash_<part>``, under a
    window ``saturn_swa_<part>``, and where the values are narrower or wider
    than the scores' lanes (latent attention: 192 / 128) ``saturn_mla_<part>``:
    a reader that credits a ``saturn_flash_*`` call with one head width must
    not meet one."""
    if D != Dv:
        return f"saturn_mla_{part}"
    return f"saturn_flash_{part}" if window is None else f"saturn_swa_{part}"


#: the launchers' static arguments
_STATIC = ("block_q", "block_k", "chunk", "scale", "causal", "h", "kv",
           "window", "interpret")


# --------------------------------------------------------------------- fwd
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, block_q, block_k, seq, steps, scale, causal, window,
                keys_down):
    iq, step = pl.program_id(1), pl.program_id(2)
    per_chunk = k_ref.shape[1] // block_k

    @_when(steps == 1 or step == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    reach = _reach(iq, block_q, block_k, seq // block_k, causal, window, True)
    first = _first(reach, step, per_chunk, seq // block_k)
    base = 0 if first is None else first

    def _accumulate(jk):
        # Matmul inputs stay in the storage dtype (bf16): the MXU computes
        # bf16 x bf16 -> f32 natively via preferred_element_type, while
        # f32 x f32 needs multiple passes. Scores, softmax state and the
        # accumulator are f32.
        at = pl.multiple_of((jk - base) * block_k, block_k)
        kb = k_ref[0, pl.ds(at, block_k), :]              # (BK, D)
        if keys_down:
            # The scores transposed, keys down the rows: m and l are rows
            # over the queries (a few vregs where a column of BQ takes
            # BQ / 8), the accumulator is (D, BQ) and v comes as (D, T): at
            # a head dim under 128 nothing is a half-empty vreg.
            vt = v_ref[0, :, pl.ds(at, block_k)]          # (D, BK)
            s = _scores(kb, q_ref[0], scale)              # (BK, BQ) f32
            if causal:
                s = _masked(s, iq * block_q, jk * block_k, 1, window)
            m_prev = m_scr[:]                             # (8, BQ)
            m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
            p = jnp.exp(s - m_new[:1])
            corr = jnp.exp(m_prev - m_new)
            m_scr[:] = m_new
            l_scr[:] = corr * l_scr[:] + p.sum(axis=0, keepdims=True)
            acc_scr[:] = corr[:1] * acc_scr[:] + _dot(
                vt, p.astype(vt.dtype), ((1,), (0,)))
            return
        # Queries down the rows: m and l are replicated across a 128-lane
        # row so that their update and their use are plain vector operations.
        vb = v_ref[0, pl.ds(at, block_k), :]
        s = _scores(q_ref[0], kb, scale)                  # (BQ, BK) f32
        if causal:
            s = _masked(s, iq * block_q, jk * block_k, 0, window)
        m_prev = m_scr[:]                                 # (BQ, 128)
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, block_k))
        corr = jnp.exp(m_prev - m_new)
        m_scr[:] = m_new
        l_scr[:] = corr * l_scr[:] + p.sum(axis=-1, keepdims=True)
        acc_scr[:] = _lanes(corr, acc_scr.shape[1]) * acc_scr[:] + _dot(
            p.astype(vb.dtype), vb, ((1,), (0,))
        )

    _for_blocks(_accumulate, reach, first, per_chunk)

    @_when(steps == 1 or step == steps - 1)
    def _finalize():
        # lse leaves as a (1, block_q) row of a (BH, 1, T) array: lane-dense
        # in HBM (a (T, 1) column would be padded to 128 lanes there), read
        # as it is by the dkv kernel, whose scores are transposed
        l = l_scr[:]
        lse = m_scr[:] + jnp.log(l)
        if keys_down:
            o_ref[0] = (acc_scr[:] / l[:1]).astype(o_ref.dtype)   # (D, BQ)
            lse_ref[0] = lse[:1]
        else:
            o_ref[0] = (acc_scr[:] / _lanes(l, acc_scr.shape[1])).astype(
                o_ref.dtype)
            lse_ref[0] = lse.T[:1]


def _kv_of(h: int, kv: int):
    """Flat (B*H) q-head program index -> flat (B*KV) k/v row.

    Grouped-query attention: ``rep = h // kv`` consecutive q heads share
    one k/v head, so the k/v BlockSpec index maps a q-head grid step to its
    group's row — the kernels never see repeated k/v and the (B, H, T, D)
    activation expansion never materializes. rep == 1 is the identity."""
    rep = h // kv
    if rep == 1:
        return lambda bh: bh

    def to_kv(bh):
        return _div(bh, h) * kv + _div(jax.lax.rem(bh, jnp.int32(h)), rep)

    return to_kv


def _keys_down(D: int) -> bool:
    """The forward's orientation, from the head dim: keys down the rows of
    the score block where a (block, D) float32 tile would half-fill its
    vregs (head 64: 27 % of the forward's time on the chip, PERF.md section
    6, PR 41); queries down the rows from 128 on, where the transposed PV
    product would stream only D rows through each weight tile."""
    return D < _LANES


@plans._traced_once(*_STATIC)
def _fwd(q, k, v, *, block_q, block_k, chunk, scale, causal, h, kv,
         window=None, interpret=False):
    BH, T, D = q.shape
    Dv = v.shape[-1]        # the values' lanes (D but under latent attention)
    kv_of = _kv_of(h, kv)
    keys_down = _keys_down(D)
    steps, fetched = _chunk_walk(block_q, block_k, chunk, T, causal, window,
                                 True)
    walked = pl.BlockSpec((1, chunk, D),
                          lambda bh, i, j: (kv_of(bh), fetched(i, j), 0))
    if keys_down:   # v and o cross the kernel's edge as (D, T)
        v = jnp.swapaxes(v, 1, 2)
        v_spec = pl.BlockSpec((1, Dv, chunk),
                              lambda bh, i, j: (kv_of(bh), 0, fetched(i, j)))
        o_spec = pl.BlockSpec((1, Dv, block_q), lambda bh, i, j: (bh, 0, i))
        o_shape, stat, acc = (BH, Dv, T), (8, block_q), (Dv, block_q)
    else:
        v_spec = walked if Dv == D else pl.BlockSpec(
            (1, chunk, Dv), lambda bh, i, j: (kv_of(bh), fetched(i, j), 0))
        o_spec = pl.BlockSpec((1, block_q, Dv), lambda bh, i, j: (bh, i, 0))
        o_shape, stat, acc = (BH, T, Dv), (block_q, _LANES), (block_q, Dv)
    o, lse = pl.pallas_call(
        functools.partial(
            _fwd_kernel, block_q=block_q, block_k=block_k, seq=T, steps=steps,
            scale=scale, causal=causal, window=window, keys_down=keys_down,
        ),
        grid=(BH, T // block_q, steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            walked,
            v_spec,
        ],
        out_specs=[
            o_spec,
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(o_shape, q.dtype),
            jax.ShapeDtypeStruct((BH, 1, T), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM(stat, jnp.float32),    # running max
            pltpu.VMEM(stat, jnp.float32),    # running denom
            pltpu.VMEM(acc, jnp.float32),     # output accumulator
        ],
        name=_name("fwd", window, D, Dv),
        interpret=interpret,
    )(q, k, v)
    return (jnp.swapaxes(o, 1, 2) if keys_down else o), lse


# --------------------------------------------------------------------- bwd
def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, lse_scr, delta_scr, *, block_q, block_k, seq, steps,
               scale, causal, window):
    iq, step = pl.program_id(1), pl.program_id(2)
    per_chunk = k_ref.shape[1] // block_k

    @_when(steps == 1 or step == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        # the rows of lse and delta as lane-wide columns, once a query block
        lse_scr[:] = _column(lse_ref[0])
        delta_scr[:] = _column(delta_ref[0])

    reach = _reach(iq, block_q, block_k, seq // block_k, causal, window, True)
    first = _first(reach, step, per_chunk, seq // block_k)
    base = 0 if first is None else first

    def _accumulate(jk):
        # bf16 matmul inputs, f32 accumulation — see the fwd kernel note.
        rows = pl.ds(pl.multiple_of((jk - base) * block_k, block_k),
                     block_k)
        kb, vb = k_ref[0, rows, :], v_ref[0, rows, :]
        s = _scores(q_ref[0], kb, scale)
        if causal:
            s = _masked(s, iq * block_q, jk * block_k, 0, window)
        p = jnp.exp(s - _lanes(lse_scr[:], block_k))
        dp = _dot(do_ref[0], vb, ((1,), (1,)))
        ds = p * (dp - _lanes(delta_scr[:], block_k))
        dq_scr[:] = dq_scr[:] + _dot(ds.astype(kb.dtype), kb, ((1,), (0,)))

    _for_blocks(_accumulate, reach, first, per_chunk)

    @_when(steps == 1 or step == steps - 1)
    def _finalize():
        dq_ref[0] = (dq_scr[:] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, block_q, block_k, seq,
                steps, rep, scale, causal, window):
    # Grid (bkv, jk, g, step): g walks the q heads sharing this k/v head
    # (size 1 without GQA); the (bkv, jk) output block stays resident across
    # the whole inner (g, step) sweep, so dk/dv accumulate the group sum the
    # transpose of the activation-side repeat would otherwise need.
    jk, g, step = pl.program_id(1), pl.program_id(2), pl.program_id(3)
    per_chunk = q_ref.shape[1] // block_q

    @_when(rep == 1 or g == 0, steps == 1 or step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    reach = _reach(jk, block_k, block_q, seq // block_q, causal, window, False)
    first = _first(reach, step, per_chunk, seq // block_q)
    base = 0 if first is None else first

    def _accumulate(iq):
        # The scores transposed, keys down the rows: lse and delta are
        # (1, BQ) rows that broadcast over sublanes, and dv = P^T dO and
        # dk = dS^T Q are plain products (P and dS never transposed).
        at = pl.multiple_of((iq - base) * block_q, block_q)
        qb, dob = q_ref[0, pl.ds(at, block_q), :], do_ref[0, pl.ds(at, block_q), :]
        s = _scores(k_ref[0], qb, scale)                    # (BK, BQ)
        if causal:
            s = _masked(s, iq * block_q, jk * block_k, 1, window)
        p = jnp.exp(s - lse_ref[0, :, pl.ds(at, block_q)])
        dv_scr[:] = dv_scr[:] + _dot(p.astype(dob.dtype), dob, ((1,), (0,)))
        dp = _dot(v_ref[0], dob, ((1,), (1,)))
        ds = (p * (dp - delta_ref[0, :, pl.ds(at, block_q)])).astype(qb.dtype)
        # ds·q is unscaled; the scale factor lands in the finalize below.
        dk_scr[:] = dk_scr[:] + _dot(ds, qb, ((1,), (0,)))

    _for_blocks(_accumulate, reach, first, per_chunk)

    @_when(rep == 1 or g == rep - 1, steps == 1 or step == steps - 1)
    def _finalize():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


@plans._traced_once(*_STATIC)
def _dq(q, k, v, do, lse, delta, *, block_q, block_k, chunk, scale, causal,
        h, kv, window=None, interpret=False):
    BH, T, D = q.shape
    Dv = v.shape[-1]
    kv_of = _kv_of(h, kv)
    steps, fetched = _chunk_walk(block_q, block_k, chunk, T, causal,
                                         window, True)
    return pl.pallas_call(
        functools.partial(
            _dq_kernel, block_q=block_q, block_k=block_k, seq=T, steps=steps,
            scale=scale, causal=causal, window=window,
        ),
        grid=(BH, T // block_q, steps),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, chunk, D),
                         lambda bh, i, j: (kv_of(bh), fetched(i, j), 0)),
            pl.BlockSpec((1, chunk, Dv),
                         lambda bh, i, j: (kv_of(bh), fetched(i, j), 0)),
            pl.BlockSpec((1, block_q, Dv), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
            pl.BlockSpec((1, 1, block_q), lambda bh, i, j: (bh, 0, i)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, T, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # lse, lane-wide
            pltpu.VMEM((block_q, _LANES), jnp.float32),   # delta, lane-wide
        ],
        name=_name("dq", window, D, Dv),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


@plans._traced_once(*_STATIC)
def _dkv(q, k, v, do, lse, delta, *, block_q, block_k, chunk, scale, causal,
         h, kv, window=None, interpret=False):
    T, D = q.shape[1:]
    Dv = v.shape[-1]
    BKV = k.shape[0]
    rep = h // kv
    steps, fetched = _chunk_walk(block_k, block_q, chunk, T, causal,
                                         window, False)

    def qh(bkv, g):
        # flat (B*KV) k/v row + group member -> flat (B*H) q-head row
        if rep == 1:
            return bkv
        return _div(bkv, kv) * h + jax.lax.rem(bkv, jnp.int32(kv)) * rep + g

    return pl.pallas_call(
        functools.partial(
            _dkv_kernel, block_q=block_q, block_k=block_k, seq=T, steps=steps,
            rep=rep, scale=scale, causal=causal, window=window,
        ),
        grid=(BKV, T // block_k, rep, steps),
        in_specs=[
            pl.BlockSpec((1, chunk, D),
                         lambda bkv, j, g, i: (qh(bkv, g), fetched(j, i), 0)),
            pl.BlockSpec((1, block_k, D), lambda bkv, j, g, i: (bkv, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda bkv, j, g, i: (bkv, j, 0)),
            pl.BlockSpec((1, chunk, Dv),
                         lambda bkv, j, g, i: (qh(bkv, g), fetched(j, i), 0)),
            pl.BlockSpec((1, 1, chunk),
                         lambda bkv, j, g, i: (qh(bkv, g), 0, fetched(j, i))),
            pl.BlockSpec((1, 1, chunk),
                         lambda bkv, j, g, i: (qh(bkv, g), 0, fetched(j, i))),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bkv, j, g, i: (bkv, j, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda bkv, j, g, i: (bkv, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, T, D), k.dtype),
            jax.ShapeDtypeStruct((BKV, T, Dv), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
        ],
        name=_name("dkv", window, D, Dv),
        interpret=interpret,
    )(q, k, v, do, lse, delta)


def _bwd(blocks, scale, causal, h, kv, res, do, window=None):
    """dq, dk, dv; ``blocks`` = (block_q, block_k, chunk) of the dq kernel
    and of the dkv kernel."""
    q, k, v, o, lse = res
    # a (BH, 1, T) row like lse
    delta = jnp.sum(
        do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1
    )[:, None, :]
    kw = dict(scale=scale, causal=causal, h=h, kv=kv, window=window,
              interpret=_use_interpret())
    (dq_q, dq_k, dq_c), (dkv_q, dkv_k, dkv_c) = blocks
    dq = _dq(q, k, v, do, lse, delta, block_q=dq_q, block_k=dq_k, chunk=dq_c,
             **kw)
    dk, dv = _dkv(q, k, v, do, lse, delta, block_q=dkv_q, block_k=dkv_k,
                  chunk=dkv_c, **kw)
    return dq, dk, dv


# ---------------------------------------------------------------- public
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bh(q, k, v, blocks, causal, h, kv, window=None):
    """Attention on flat heads. ``blocks``: the (block_q, block_k, chunk) of
    the fwd, dq and dkv kernels. With a ``window`` the same kernels run under
    names of their own (``saturn_swa_*``: a reader that counts a
    ``saturn_flash_*`` call as full causal attention must not meet one)."""
    return _flash_bh_fwd(q, k, v, blocks, causal, h, kv, window)[0]


def _flash_bh_fwd(q, k, v, blocks, causal, h, kv, window):
    scale = 1.0 / math.sqrt(q.shape[-1])
    bq, bk, chunk = blocks[0]
    o, lse = _fwd(q, k, v, block_q=bq, block_k=bk, chunk=chunk, scale=scale,
                  causal=causal, h=h, kv=kv, window=window,
                  interpret=_use_interpret())
    return o, (q, k, v, o, lse)


def _flash_bh_bwd(blocks, causal, h, kv, window, res, do):
    scale = 1.0 / math.sqrt(res[0].shape[-1])
    return _bwd(blocks[1:], scale, causal, h, kv, res, do, window=window)


_flash_bh.defvjp(_flash_bh_fwd, _flash_bh_bwd)


def _largest_block(T: int, most: int) -> int:
    """The largest of ``most``, ``most / 2``, .. 128 that divides T; a T no
    such block divides is one block (or 128s where those divide it)."""
    b = most
    while b >= _LANES:
        if T % b == 0:
            return b
        b //= 2
    return min(_LANES, T)


#: the most elements of one walked operand's chunk (chunk x D): with two
#: operands, each double-buffered, 8 MiB of VMEM in bf16. A guard, not a
#: plan: every cell's walked side fits whole (the longest, 8192 x 128, just)
_CHUNK_ELEMENTS = 1024 * 1024


def _chunk(T: int, D: int, block: int) -> int:
    """Columns of the walked sequence a grid step holds in VMEM: all of T
    where that fits ``_CHUNK_ELEMENTS`` (every cell's does, and the chip has
    read no other), else the largest whole fraction of T in whole blocks
    that does (one block at the least): a longer sequence still compiles,
    at a fetch a chunk. The walk inside a chunk is a loop in the kernel, so
    a grid step's fixed cost and the operand's fetch are paid once a chunk,
    not once a block."""
    for n in range(1, T // block + 1):
        if T % n == 0 and (T // n) % block == 0 and (T // n) * D <= _CHUNK_ELEMENTS:
            return T // n
    return block


def _walk(T: int, block_q: int, block_k: int, chunk: int,
          rows_are_queries: bool, window: Optional[int] = None) -> dict:
    """Of the (T / block_q) x (T / block_k) score blocks of a causal head:
    how many the kernel visits (the rest lie above the diagonal, or beyond
    the ``window``, and are in no loop) and how many of those the diagonal
    (or the window's far edge) crosses: the blocks whose mask changes a
    score (the one loop body applies it to every visited block: a second,
    unmasked body read no faster on the chip, PERF.md section 6, PR 41)."""
    b_row, b_col = (block_q, block_k) if rows_are_queries else (block_k, block_q)
    reach = window if window is not None else T
    visited = masked = 0
    for r in range(T // b_row):
        n_lo, n_hi = _reach(r, b_row, b_col, T // b_col, True, window,
                            rows_are_queries)
        lo, hi = r * b_row, (r + 1) * b_row - 1
        visited += n_hi - n_lo
        # kept as a whole: the block's last key at or before its first
        # query, and its first key within reach of its last query
        masked += sum(
            not (c * b_col + b_col - 1 <= lo and c * b_col > hi - reach
                 if rows_are_queries
                 else c * b_col >= hi and c * b_col + b_col - 1 < lo + reach)
            for c in range(n_lo, n_hi))
    return {"block_q": block_q, "block_k": block_k, "chunk": chunk,
            "visited": visited, "masked": masked}


def flash_plan(T: int, D: int, block_q: Optional[int] = None,
               block_k: Optional[int] = None, d_v: Optional[int] = None) -> dict:
    """The causal kernels' blocks at sequence ``T`` and head dim ``D``, with
    what each kernel's walk over a head then is (``_walk``). A pure function
    of its arguments: no device, no compile, nothing tried. ``block_q`` /
    ``block_k`` put all three kernels on the caller's blocks. The q heads a
    k/v head (Laguna's 6) select nothing: the dkv kernel walks a group's
    heads one after the other on the same blocks.

    The rule, from the chip's readings at the cells' shapes (PERF.md section
    6, PR 41; ``tools/flash_blocks.py``): the walked side (keys for fwd and
    dq, queries for dkv) in blocks of 512, the side that stays (the grid's
    row block) 512, and 1024 from T 8192 on at a head dim up to 128. Smaller
    blocks lose at every shape although they compute fewer scores above the
    diagonal: a block's step in the kernel's loop is a chain of product,
    reduction, exp, product that does not overlap the next block's, and its
    fixed part weighs more the smaller the block; wider ones spill the score
    block. A row block's fixed cost (state in and out, the dq kernel's two
    transposes, the fetch of q) is paid T / block times a head, which from
    T 8192 on is worth the larger block; at head dim 256 the 1024-row
    operands crowd VMEM and the reading is worse.

    ``d_v``: the values' lanes where they are not the scores' ``D`` (latent
    attention: q and k of 192 lanes, v of 128). The blocks and the chunk go
    by ``D``, the wider operand of every product (no chip has read another
    rule at two widths), and the plan says both (``d_qk``, ``d_v``).
    """
    stays = _largest_block(T, 1024 if T >= 8192 and D <= _LANES else 512)
    walked = _largest_block(T, 512)
    blocks = {"fwd": (block_q or stays, block_k or walked),
              "dq": (block_q or stays, block_k or walked),
              "dkv": (block_q or walked, block_k or stays)}
    out = {"seq": T, "head_dim": D, "keys_down": _keys_down(D)}
    if d_v is not None and d_v != D:
        out.update(d_qk=D, d_v=d_v)
    for name, (bq, bk) in blocks.items():
        walks_keys = name != "dkv"
        out[name] = _walk(T, bq, bk, _chunk(T, D, bk if walks_keys else bq),
                          walks_keys)
    return out


#: the dkv kernel walks a window's queries by the loop inside a chunk from
#: this many walked blocks a window on; under it by the grid (``window_plan``)
_MANY_BLOCKS = 4


def _window_block(T: int, window: int) -> int:
    """The window kernels' one block: the largest of 512, 256, 128 dividing
    T that is no longer than the window (128 at the least). A block of 512
    at a window of 512 computes 1024 keys a query where 256s compute 768 and
    still reads a third faster on the chip (PERF.md section 6, PR 50): a
    block's step is a chain that does not overlap the next block's, and its
    fixed part weighs more the smaller the block (``flash_plan``)."""
    most = 512
    while most > _LANES and most > window:
        most //= 2
    return _largest_block(T, most)


def window_plan(T: int, D: int, window: int, block_q: Optional[int] = None,
                block_k: Optional[int] = None) -> dict:
    """The window kernels' blocks at sequence ``T``, head dim ``D`` and a
    window of ``window`` keys, with what each kernel's walk over a head then
    is: to the ``saturn_swa_*`` kernels what ``flash_plan`` is to the causal
    ones, and as pure. ``block_q`` / ``block_k`` (one of them: both) put all
    three kernels on the caller's blocks.

    One block on both sides (``_window_block``: 512 from a window of 512
    on), and the walk over the reached blocks by what a chunk of the walked
    side costs to fetch (the chip's readings: PERF.md section 6, PR 50;
    ``tools/flash_blocks.py``):

    - **fwd and dq walk the keys**, and k / v are shared by every row block
      and every q head of a group: a chunk of all of T (``_chunk``) is
      fetched once a k/v head, so the walk is always the loop inside the
      kernel over ``_reach``'s span of it (2 blocks of 512 at Laguna's
      window of 512, at most 9 at SmallThinker's 4096) and the grid has one
      step a row block.
    - **dkv walks the queries**, and q / dO are another head's at every
      step of the grid: a chunk is fetched anew each time. Where the window
      spans ``_MANY_BLOCKS`` walked blocks or more (SmallThinker's 8) the
      loop's work outweighs the fetch of all of T and the walk is the loop;
      under that (Laguna's 1) the chunk is one block and the reached blocks
      are a grid axis, so that a step fetches what the window reaches and no
      more.

    Each kernel's entry says its blocks and chunk, ``_walk``'s counts under
    the window (``visited`` / ``masked`` score blocks a head), ``steps`` (the
    grid's innermost axis: chunks a row block walks), ``blocks_a_step`` (the
    most blocks the loop walks in one of them) and ``computed_over_needed``:
    the scores the visited blocks hold over the scores the window needs
    (``sum_i min(i + 1, window)``).
    """
    b = _window_block(T, window)
    bq, bk = block_q or block_k or b, block_k or block_q or b
    w = min(window, T)
    needed = w * (w + 1) // 2 + (T - w) * w
    out = {"window": window, "seq": T, "head_dim": D}
    for name in ("fwd", "dq", "dkv"):
        walks_keys = name != "dkv"
        b_row, b_col = (bq, bk) if walks_keys else (bk, bq)
        loop = walks_keys or window >= _MANY_BLOCKS * b_col
        chunk = _chunk(T, D, b_col) if loop else b_col
        walk = _walk(T, bq, bk, chunk, walks_keys, window)
        walk["steps"] = _chunk_walk(b_row, b_col, chunk, T, True, window,
                                    walks_keys)[0]
        walk["blocks_a_step"] = min(chunk // b_col, max(
            hi - lo for lo, hi in (
                _reach(r, b_row, b_col, T // b_col, True, window, walks_keys)
                for r in range(T // b_row))))
        walk["computed_over_needed"] = round(
            walk["visited"] * bq * bk / needed, 3)
        out[name] = walk
    return out


def flash_supported(cfg=None) -> bool:
    """Can the Pallas kernel lower (not interpret) for this model config?

    Real lowering needs the TPU backend; interpret mode exists only for
    numerics tests. With a config, also checks the kernel's shape contract
    (seq divisible by the plan's smallest block) and that attention is
    single-program (sequence-parallel configs have their own kernels). Used by the executors'
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

    ``v`` (and the result) may have other lanes than ``q`` and ``k``
    (latent attention: (B, H, T, 192) scores over (B, H, T, 128) values, at
    scale 1 / sqrt(192)): the same three kernels under the names
    ``saturn_mla_*``, each operand's block at its own width.

    ``window`` (causal only): query i reads keys i - window + 1 .. i, through
    the ``saturn_swa_*`` kernels, which visit the blocks the window reaches
    and no other, on ``window_plan``'s blocks and chunks: the causal
    kernels' in-kernel loop over the reached span, but for dkv at a window
    of few blocks, whose reached blocks stay a grid axis.

    Grouped-query attention is native: ``k``/``v`` may carry fewer heads
    (B, KV, T, D) with KV dividing H — the kernels index each q head's
    group row directly, so the (B, H, T, D) k/v expansion (and its HBM at
    long context) never exists, and dk/dv come back at (B, KV, T, D) with
    the group sum done in-kernel.

    T must divide by the block sizes (default: ``flash_plan``'s, the largest
    of 1024 or 512 / 256 / 128 dividing T, else min(128, T)) or this raises
    — the model config validates the constraint up front
    (``GPT2Config.__post_init__``); this op stays strict. ``block_q`` /
    ``block_k`` put all three kernels on the caller's blocks (under a
    window one of them alone names both).
    """
    B, H, T, D = q.shape
    KV = k.shape[1]
    Dv = v.shape[-1]
    if v.shape[1] != KV or KV < 1 or H % KV != 0:
        raise ValueError(
            f"k/v heads ({k.shape[1]}, {v.shape[1]}) must match and divide "
            f"q heads ({H})"
        )
    if k.shape[-1] != D or (Dv != D and window is not None):
        raise ValueError(
            f"q and k share their lanes ({D}, {k.shape[-1]}); v's may differ "
            f"({Dv}) without a window")
    qf = q.reshape(B * H, T, D)
    kf = k.reshape(B * KV, T, D)
    vf = v.reshape(B * KV, T, Dv)
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is causal and >= 1")
        window = int(window)
        family, plan = "window", window_plan(T, D, window, block_q, block_k)
    else:
        family, plan = "flash", flash_plan(T, D, block_q, block_k, d_v=Dv)
    blocks = tuple((plan[n]["block_q"], plan[n]["block_k"], plan[n]["chunk"])
                   for n in ("fwd", "dq", "dkv"))
    if any(T % b for triple in blocks for b in triple):
        raise ValueError(f"seq len {T} not divisible by blocks {blocks}")
    if causal:
        plans.record(family, plan)
    o = _flash_bh(qf, kf, vf, blocks, causal, H, KV, window)
    return o.reshape(B, H, T, Dv)
